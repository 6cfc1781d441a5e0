"""Unified telemetry (paddle_tpu/obs, docs/observability.md).

Covers the four planes end-to-end on the virtual CPU mesh:

- the process-wide metrics registry (counters/gauges/histograms with
  labels, Prometheus + JSON exposition, the --metrics_port HTTP
  endpoint) and the serving/trainer views over it;
- the step timeline (phase durations sum to ~wall-clock, data-wait
  inflates under a throttled reader, a bounded number of phase records a
  batch and the same costs as an uninstrumented loop; the measured
  overhead < 3% in a ``slow`` twin) and the live MFU gauge pinned to the
  analytic-FLOPs walker ``SGDTrainer.step_flops`` reads;
- the rank-tagged event journal: crash-safe writes (a REAL SIGKILL
  mid-record via chaos.kill_mid_journal_write), torn-tail-tolerant
  reads, cross-rank causal merge, the `obs merge`/`obs dump` CLI, and
  the 2-process elastic-gang acceptance (per-rank journals interleave
  into ONE ordered timeline containing the resize);
- on-demand profiler capture windows (flag- and arm()-driven) and the
  `lint --obs` zero-added-host-transfer contract.
"""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax

import paddle_tpu.nn as nn
from paddle_tpu.obs import (EventJournal, ProfilerCapture, StepTimeline,
                            close_journal, get_journal, get_registry,
                            journal_event, journal_path, merge_journals,
                            read_journal, reset_registry,
                            start_metrics_server)
from paddle_tpu.obs.registry import MetricsRegistry
from paddle_tpu.param.optimizers import Adam, SGD
from paddle_tpu.resilience import chaos
from paddle_tpu.trainer import SGDTrainer, events as ev
from paddle_tpu.utils.flags import FLAGS

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_OBS_FLAGS = ("obs_timeline", "obs_journal", "obs_peak_flops",
              "metrics_port", "profile_dir", "profile_steps",
              "save_dir", "saving_period", "log_period", "enable_timers")


@pytest.fixture(autouse=True)
def _obs_state():
    """Process-global telemetry state is per-test: flags restored, the
    global registry cleared, and the lazy process journal closed."""
    keep = {k: getattr(FLAGS, k) for k in _OBS_FLAGS}
    FLAGS.log_period = 0
    yield
    for k, v in keep.items():
        setattr(FLAGS, k, v)
    close_journal()
    reset_registry()


def _tiny_trainer(seed=0, hidden=8, in_dim=8, lr=0.05, opt=None):
    nn.reset_naming()
    x = nn.data("x", size=in_dim)
    y = nn.data("y", size=2)
    h = nn.fc(x, hidden, act="relu", name="h")
    cost = nn.mse_cost(input=nn.fc(h, 2, name="out"), label=y)
    return SGDTrainer(cost, opt or Adam(learning_rate=lr), seed=seed)


def _feeds(n, batch=4, in_dim=8, seed=0):
    rs = np.random.RandomState(seed)
    return [{"x": rs.randn(batch, in_dim).astype(np.float32),
             "y": rs.randn(batch, 2).astype(np.float32)}
            for _ in range(n)]


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("evts_total", "events")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    # same (name, labelvalues) -> the SAME child (a view, not a copy)
    assert reg.counter("evts_total") is c

    g = reg.gauge("depth", "queue depth")
    assert g.value is None
    g.set(7)
    assert g.value == 7.0

    h = reg.histogram("lat_s", "latency", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count == 4 and h.counts == [1, 1, 1, 1]
    assert h.min == 0.005 and h.max == 5.0
    assert h.mean == pytest.approx(5.555 / 4)


def test_registry_labels_make_distinct_series():
    reg = MetricsRegistry()
    a = reg.counter("phase_total", "by phase", labels=("phase",), phase="h2d")
    b = reg.counter("phase_total", "by phase", labels=("phase",), phase="step")
    a.inc(3)
    b.inc()
    assert a is not b and a.value == 3 and b.value == 1
    series = reg.snapshot()["phase_total"]["series"]
    assert {s["labels"]["phase"]: s["value"] for s in series} == {
        "h2d": 3.0, "step": 1.0}


def test_registry_rejects_shape_changing_reregistration():
    reg = MetricsRegistry()
    reg.counter("m", "help")
    with pytest.raises(ValueError, match="re-registered"):
        reg.gauge("m", "help")
    with pytest.raises(ValueError, match="re-registered"):
        reg.counter("m", "help", labels=("x",), x="1")


def test_prometheus_text_exposition():
    reg = MetricsRegistry()
    reg.counter("reqs_total", "requests", labels=("code",), code="200").inc(4)
    h = reg.histogram("dur_s", "duration", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = reg.prometheus_text()
    assert "# TYPE reqs_total counter" in text
    assert 'reqs_total{code="200"} 4.0' in text
    # histogram buckets are CUMULATIVE and end at +Inf == count
    assert 'dur_s_bucket{le="0.1"} 1' in text
    assert 'dur_s_bucket{le="1.0"} 2' in text
    assert 'dur_s_bucket{le="+Inf"} 2' in text
    assert "dur_s_count 2" in text
    assert "dur_s_sum 0.55" in text
    # a never-set gauge is OMITTED (Prometheus convention), never 0: a
    # dark train_mfu must not scrape as "0% utilization"
    reg.gauge("dark", "never set")
    reg.gauge("lit", "set").set(0.0)
    text = reg.prometheus_text()
    assert "dark 0" not in text and "# TYPE dark gauge" in text
    assert "lit 0.0" in text


def test_snapshot_is_json_serializable():
    reg = MetricsRegistry()
    reg.gauge("g", "gauge")                    # never set -> None
    reg.histogram("h", "hist").observe(0.2)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["g"]["series"][0]["value"] is None
    assert snap["h"]["series"][0]["count"] == 1


def test_http_endpoint_serves_prometheus_and_json():
    reg = MetricsRegistry()
    reg.counter("up_total", "liveness").inc()
    srv = start_metrics_server(0, reg)         # port 0: ephemeral
    try:
        base = f"http://127.0.0.1:{srv.server_port}"
        text = urllib.request.urlopen(base + "/metrics", timeout=5).read()
        assert b"up_total 1.0" in text
        snap = json.loads(urllib.request.urlopen(
            base + "/metrics.json", timeout=5).read())
        assert snap["up_total"]["series"][0]["value"] == 1.0
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/nope", timeout=5)
    finally:
        srv.shutdown()


def test_server_metrics_is_a_registry_view():
    """serving.ServerMetrics counters ARE registry counters: healthz and
    a /metrics scrape read the same monotonic series."""
    from paddle_tpu.serving.metrics import ServerMetrics

    m = ServerMetrics()
    m.inc("accepted", 3)
    m.observe_latency(0.02)
    snap = get_registry().snapshot()
    label = m._label
    series = {tuple(sorted(s["labels"].items())): s
              for s in snap["serving_accepted"]["series"]}
    assert series[(("server", label),)]["value"] == 3.0
    assert m.snapshot()["counters"]["accepted"] == 3
    lat = {s["labels"]["server"]: s
           for s in snap["serving_latency_seconds"]["series"]}
    assert lat[label]["count"] == 1


# ---------------------------------------------------------------------------
# analytic FLOPs: ONE walker for step_flops and the live gauge
# ---------------------------------------------------------------------------


def test_flops_walker_counts_exact_matmul():
    from paddle_tpu.analysis.flops import jaxpr_flops

    a = np.zeros((4, 8), np.float32)
    b = np.zeros((8, 2), np.float32)
    assert jaxpr_flops(lambda x, y: x @ y, a, b) == 2.0 * 4 * 8 * 2


def test_step_flops_is_the_walker_on_the_trainers_step():
    """``SGDTrainer.step_flops`` (what the live ``train_mfu`` gauge
    divides) is ``analysis.flops.jaxpr_flops`` on the trainer's own step:
    the SAME analytic FLOPs for the same golden train step."""
    from paddle_tpu.analysis.flops import jaxpr_flops

    tr = _tiny_trainer()
    feed = _feeds(1)[0]
    live = tr.step_flops(feed)
    rng = jax.random.PRNGKey(0)

    def one_step(carry):
        return tr._step_fn(tr.params, tr.state, tr.opt_state, {}, rng, carry)

    offline = jaxpr_flops(one_step, feed)
    assert live is not None and offline is not None
    assert live == offline                 # identical, not merely close
    assert live > 0


def test_live_gauge_reads_the_trace_jit_made_of_the_step():
    """``step_flops`` runs in the first iteration of every ``train()``: it
    asks for the trace of the function the trainer's jit wraps, which the
    first step left in the tracing cache, and does not walk the model a
    second time (seconds of set-up at a real size)."""
    import jax.monitoring

    traces, armed = [], [False]
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: traces.append(name)
        if armed[0] and name.endswith("jaxpr_trace_duration") else None)
    tr = _tiny_trainer()
    feed = _feeds(1)[0]
    armed[0] = True
    try:
        tr.train_batch(feed)
        of_the_step, traces[:] = len(traces), []
        flops = tr.step_flops(feed)
        of_the_gauge = len(traces)
    finally:
        armed[0] = False
    assert flops and of_the_step > 10 * max(of_the_gauge, 1), (
        of_the_step, of_the_gauge)


def test_chip_peak_tables_resolve_tpu_kinds_only():
    from paddle_tpu.analysis.flops import chip_peak_bandwidth, chip_peak_flops

    assert chip_peak_flops("TPU v5e") == 197e12
    assert chip_peak_flops("TPU v4") == 275e12
    with pytest.raises(ValueError, match="unknown TPU"):
        chip_peak_flops("TPU v99")                 # never another chip's peak
    assert chip_peak_flops("cpu") is None          # off-TPU: no peak
    assert chip_peak_bandwidth("TPU v4") == 1228e9
    assert chip_peak_bandwidth("Host CPU") is None


# ---------------------------------------------------------------------------
# step timeline
# ---------------------------------------------------------------------------


def _run_one_pass(tr, feeds, **kw):
    tr.train(lambda: iter(feeds), num_passes=1, **kw)
    return tr.timeline


def test_timeline_phases_sum_to_wallclock(monkeypatch):
    monkeypatch.setattr(FLAGS, "obs_timeline", True)
    tr = _tiny_trainer()
    tables = []

    def grab(e):
        # end_pass() resets the per-pass stats: render the Stat-print
        # table while the pass is still open
        if isinstance(e, ev.EndPass):
            tables.append(tr.timeline.table())

    tl = _run_one_pass(tr, _feeds(8), event_handler=grab)
    summary = tl.last_pass_summary
    assert summary is not None and summary["pass"] == 0
    assert summary["phases"]["step"]["count"] == 8
    # the instrumented phases account for (almost) the whole pass: the
    # uncovered remainder is loop glue (float(), logging, bookkeeping)
    assert summary["covered_s"] <= summary["wall_s"] * 1.01 + 0.02
    assert summary["covered_s"] >= summary["wall_s"] * 0.7
    # the table renders every recorded phase with its share
    assert tables and "step" in tables[0] and "%" in tables[0]


def test_timeline_off_leaves_loop_uninstrumented(monkeypatch):
    monkeypatch.setattr(FLAGS, "obs_timeline", False)
    tr = _tiny_trainer()
    assert _run_one_pass(tr, _feeds(2)) is None


def test_timeline_data_wait_inflates_when_reader_throttled(monkeypatch):
    """The input-bound diagnosis: a throttled reader (chaos.slow_client
    pacing) must show up as data_wait, and ONLY as data_wait."""
    monkeypatch.setattr(FLAGS, "obs_timeline", True)
    tr = _tiny_trainer()
    feeds = _feeds(10)
    base = _run_one_pass(tr, feeds).last_pass_summary
    slow = None

    def reader():
        return chaos.slow_client(feeds, delay_s=0.02)

    tr.train(reader, num_passes=1)
    slow = tr.timeline.last_pass_summary
    base_wait = base["phases"].get("data_wait", {"total": 0.0})["total"]
    slow_wait = slow["phases"]["data_wait"]["total"]
    assert slow_wait >= 9 * 0.02 * 0.8          # ~the injected pacing
    assert slow_wait > 5 * base_wait + 0.05
    # pacing lands in data_wait, not smeared into the step phase
    assert (slow["phases"]["step"]["total"]
            < slow_wait + base["phases"]["step"]["total"] + 0.05)


def test_timeline_feeds_registry_histograms(monkeypatch):
    monkeypatch.setattr(FLAGS, "obs_timeline", True)
    tr = _tiny_trainer()
    _run_one_pass(tr, _feeds(5))
    snap = get_registry().snapshot()
    series = {s["labels"]["phase"]: s
              for s in snap["train_phase_seconds"]["series"]}
    assert series["step"]["count"] >= 5
    assert series["data_wait"]["count"] >= 5
    assert snap["train_batches_total"]["series"][0]["value"] >= 5
    assert snap["train_last_cost"]["series"][0]["value"] is not None


def test_live_mfu_gauge_with_peak_override(monkeypatch):
    """Off-TPU there is no chip peak, so --obs_peak_flops arms the gauge;
    MFU == flops / step_seconds / peak, with flops from the SHARED
    walker (== step_flops)."""
    monkeypatch.setattr(FLAGS, "obs_timeline", True)
    monkeypatch.setattr(FLAGS, "obs_peak_flops", 1e15)
    tr = _tiny_trainer()
    tl = _run_one_pass(tr, _feeds(4))
    assert tl.peak_flops == 1e15 and tl.wants_mfu
    assert tl.flops == tr.step_flops(_feeds(1)[0])
    assert tl.mfu == pytest.approx(
        tl.flops / tl.last["step"] / 1e15, rel=1e-6)
    snap = get_registry().snapshot()
    assert snap["train_mfu"]["series"][0]["value"] == pytest.approx(
        tl.mfu, abs=1e-6)
    # the FLOPs themselves are in the pass's journal record and on the
    # timeline; the registry keeps the ratio alone (PR 52)
    assert "train_step_flops" not in snap and "train_step_seconds" not in snap
    # extras surface the live numbers next to the elastic keys
    assert tr._last_extras["mfu"] == pytest.approx(tl.mfu, rel=1e-6)
    assert tr._last_extras["step_time_s"] == tl.last["step"]


def test_peak_resolution_scales_with_mesh_size(monkeypatch):
    """step_flops counts the WHOLE SPMD step's work, so the MFU
    denominator is chip peak x participating devices — a data-parallel
    mesh must not read 8x too utilized.  An explicit --obs_peak_flops is
    the TOTAL peak, taken as given."""
    import paddle_tpu.analysis.flops as flops_mod

    monkeypatch.setattr(FLAGS, "obs_peak_flops", 0.0)
    monkeypatch.setattr(flops_mod, "chip_peak_flops", lambda kind: 100e12)
    tl = StepTimeline(n_devices=4)
    assert tl.peak_flops == 400e12
    tl.set_devices(2)                        # elastic shrink rescales
    assert tl.peak_flops == 200e12

    monkeypatch.setattr(FLAGS, "obs_peak_flops", 1e15)
    tl = StepTimeline(n_devices=4)
    assert tl.peak_flops == 1e15             # override is TOTAL, as given
    tl.set_devices(8)
    assert tl.peak_flops == 1e15


def test_failed_flops_trace_is_not_retried_per_batch():
    """set_flops(None) — the side trace failed — still marks the attempt
    so the trainer never re-traces the whole step every batch; only an
    explicit invalidate (elastic resize) re-arms it."""
    tl = StepTimeline(peak_flops=1e12)
    assert not tl.flops_attempted
    tl.set_flops(None)
    assert tl.flops_attempted and tl.flops is None
    tl.invalidate_flops()
    assert not tl.flops_attempted


def test_mfu_gauge_stays_dark_without_a_peak(monkeypatch):
    """No chip peak resolvable (CPU, no override): the timeline must NOT
    pay a second trace for a gauge that can never light up."""
    monkeypatch.setattr(FLAGS, "obs_timeline", True)
    monkeypatch.setattr(FLAGS, "obs_peak_flops", 0.0)
    tr = _tiny_trainer()
    tl = _run_one_pass(tr, _feeds(2))
    assert tl.peak_flops is None and not tl.wants_mfu
    assert tl.flops is None and tl.mfu is None


def test_instrumentation_overhead_under_3_percent(monkeypatch):
    """What the instrumented loop adds, as counts: the same costs batch for
    batch as the uninstrumented one, no timeline at all when off, and when
    on a bounded number of phase records a batch (one ``prepare`` and one
    ``step``, a ``data_wait`` more for the reader's end, the callbacks).
    What that costs in wall-clock is the ``_timed`` twin's to say."""
    feeds, costs = _feeds(8), {}
    for obs_on in (False, True):
        monkeypatch.setattr(FLAGS, "obs_timeline", obs_on)
        tr, got = _tiny_trainer(), []
        tl = _run_one_pass(tr, feeds, event_handler=lambda e: got.append(
            e.cost) if isinstance(e, ev.EndIteration) else None)
        costs[obs_on] = got
        if not obs_on:
            assert tl is None
    np.testing.assert_array_equal(costs[False], costs[True])
    counts = {k: v["count"] for k, v in tl.last_pass_summary["phases"].items()}
    n = len(feeds)
    assert counts["step"] == counts["prepare"] == n
    assert counts["data_wait"] == n + 1
    assert sum(counts.values()) <= 6 * n, counts


@pytest.mark.slow
def test_instrumentation_overhead_under_3_percent_timed(monkeypatch):
    """The acceptance bound: the instrumented loop (timeline + registry
    mirrors + explicit synced h2d) must cost < 3% wall-clock vs the
    uninstrumented loop.  One trainer, alternating measured runs,
    best-of-3 per config to shed scheduler noise."""
    nn.reset_naming()
    x = nn.data("x", size=512)
    y = nn.data("y", size=2)
    h = nn.fc(x, 512, act="relu", name="h1")
    h = nn.fc(h, 512, act="relu", name="h2")
    cost = nn.mse_cost(input=nn.fc(h, 2, name="out"), label=y)
    tr = SGDTrainer(cost, SGD(learning_rate=0.01), seed=0)
    rs = np.random.RandomState(0)
    # a step big enough (~10ms) that per-batch instrumentation cost
    # (~0.1-0.2ms of phase contexts + explicit h2d) is honestly measured
    # against real work, and a run long enough (~0.3s) to rise above the
    # scheduler's noise floor — tiny 3ms steps made jitter dwarf signal
    feeds = [{"x": rs.randn(256, 512).astype(np.float32),
              "y": rs.randn(256, 2).astype(np.float32)} for _ in range(25)]

    def timed(obs_on):
        monkeypatch.setattr(FLAGS, "obs_timeline", obs_on)
        t0 = time.perf_counter()
        tr.train(lambda: iter(feeds), num_passes=1)
        return time.perf_counter() - t0

    import gc

    timed(False)                  # compile warmup
    timed(True)                   # registry-family warmup for the on path
    off_times, on_times = [], []
    gc.collect()
    gc.disable()                  # a GC pause must not masquerade as cost
    try:
        for _ in range(5):        # INTERLEAVED pairs: load drift during a
            off_times.append(timed(False))   # long suite hits both configs
            on_times.append(timed(True))
    finally:
        gc.enable()
    # MEDIANS, not mins: one outlier-fast baseline run (scheduler luck)
    # must not read as instrumentation overhead on the other side
    import statistics

    off = statistics.median(off_times)
    on = statistics.median(on_times)
    # small absolute allowance: timer granularity on a sub-second loop
    assert on <= off * 1.03 + 0.03, (
        f"instrumented loop {on:.4f}s vs uninstrumented {off:.4f}s "
        f"({(on / off - 1) * 100:.2f}% overhead; off={off_times} "
        f"on={on_times})")


# ---------------------------------------------------------------------------
# event journal
# ---------------------------------------------------------------------------


def test_journal_roundtrip_with_sticky_context(tmp_path):
    j = EventJournal(journal_path(str(tmp_path), 0), rank=0, world_size=4)
    j.set_context(pass_id=2, batch_id=7, epoch=1)
    j.record("checkpoint_commit", fsync=True, dir="pass-00002")
    j.set_context(batch_id=8)
    j.record("bad_step", streak=1)
    j.close()
    recs, torn = read_journal(journal_path(str(tmp_path), 0))
    assert torn == 0 and [r["kind"] for r in recs] == [
        "checkpoint_commit", "bad_step"]
    assert recs[0]["pass"] == 2 and recs[0]["batch"] == 7
    assert recs[1]["batch"] == 8 and recs[1]["world_size"] == 4
    assert recs[0]["seq"] == 0 and recs[1]["seq"] == 1


def test_journal_merge_orders_across_ranks_by_time_then_rank_seq(tmp_path):
    # crafted timestamps: deterministic cross-rank interleave + tie-break
    rows = {
        "events-r00000.jsonl": [
            {"t": 1.0, "rank": 0, "seq": 0, "kind": "a"},
            {"t": 3.0, "rank": 0, "seq": 1, "kind": "c"},
        ],
        "events-r00001.jsonl": [
            {"t": 2.0, "rank": 1, "seq": 0, "kind": "b"},
            {"t": 3.0, "rank": 1, "seq": 1, "kind": "d"},  # tie: rank 0 first
        ],
    }
    for name, recs in rows.items():
        with open(tmp_path / name, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in recs)
    merged, torn = merge_journals([str(tmp_path)])
    assert torn == 0
    assert [r["kind"] for r in merged] == ["a", "b", "c", "d"]


def test_journal_reader_tolerates_torn_and_corrupt_lines(tmp_path):
    p = tmp_path / "events-r00000.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps({"t": 1.0, "rank": 0, "seq": 0, "kind": "ok"})
                + "\n")
        f.write("{not json}\n")                       # corrupt middle line
        f.write('{"t": 2.0, "rank": 0, "seq": 1, "ki')  # torn final line
    recs, torn = read_journal(str(p))
    assert [r["kind"] for r in recs] == ["ok"]
    assert torn == 2


def test_chaos_sigkill_mid_write_merged_timeline_survives(tmp_path):
    """THE crash-safety proof: a REAL writer process is SIGKILLed between
    the two halves of a record write; every whole record survives, the
    torn tail is counted not fatal, and the merge with a healthy rank's
    journal still yields one ordered timeline."""
    jd = str(tmp_path)
    healthy = EventJournal(journal_path(jd, 0), rank=0, world_size=2)
    healthy.set_context(pass_id=1)
    healthy.record("begin_pass")
    whole = chaos.kill_mid_journal_write(jd, rank=1, whole_records=5)
    healthy.record("end_pass", fsync=True)
    healthy.close()

    merged, torn = merge_journals([jd])
    assert torn == 1                                  # exactly the torn tail
    victim = [r for r in merged if r["rank"] == 1]
    assert len(victim) == whole
    assert all(r["kind"] == "victim_step" for r in victim)
    assert {r["kind"] for r in merged if r["rank"] == 0} == {
        "begin_pass", "end_pass"}
    ts = [r["t"] for r in merged]
    assert ts == sorted(ts)
    # every record kept its pass/world context through the crash
    assert all(r.get("pass") == 1 for r in merged)


def test_process_journal_armed_by_flag(tmp_path, monkeypatch):
    assert get_journal() is None                      # '' = off
    journal_event("noop")                             # cheap no-op when off
    monkeypatch.setattr(FLAGS, "obs_journal", str(tmp_path))
    journal_event("armed", detail=1)
    close_journal()
    recs, _ = read_journal(journal_path(str(tmp_path), 0))
    assert [r["kind"] for r in recs] == ["armed"]


def test_trainer_journals_lifecycle_and_fsynced_checkpoint_commits(
        tmp_path, monkeypatch):
    monkeypatch.setattr(FLAGS, "obs_journal", str(tmp_path / "journal"))
    monkeypatch.setattr(FLAGS, "save_dir", str(tmp_path / "ckpts"))
    monkeypatch.setattr(FLAGS, "saving_period", 1)
    tr = _tiny_trainer()
    tr.train(lambda: iter(_feeds(3)), num_passes=2)
    close_journal()
    recs, torn = read_journal(journal_path(str(tmp_path / "journal"), 0))
    assert torn == 0
    kinds = [r["kind"] for r in recs]
    assert kinds[0] == "train_start"
    assert kinds.count("begin_pass") == 2 and kinds.count("end_pass") == 2
    assert kinds.count("checkpoint_commit") == 2     # saving_period=1
    assert kinds.count("pass_timing") == 2           # timeline journaled
    assert kinds[-1] == "train_end"
    commit = next(r for r in recs if r["kind"] == "checkpoint_commit")
    assert commit["saved_pass"] == 0 and "pass-00000" in commit["dir"]
    timing = next(r for r in recs if r["kind"] == "pass_timing")
    assert timing["phases"]["step"]["count"] == 3


# ---------------------------------------------------------------------------
# obs CLI (merge / dump)
# ---------------------------------------------------------------------------


def _write_journal(tmp_path, rank, kinds, t0=100.0):
    j = EventJournal(journal_path(str(tmp_path), rank), rank=rank,
                     world_size=2)
    j.set_context(pass_id=0)
    for k in kinds:
        j.record(k)
    j.close()


def test_obs_cli_merge_and_kind_filter(tmp_path, capsys):
    from paddle_tpu.obs.cli import run

    _write_journal(tmp_path, 0, ["begin_pass", "gang_resize"])
    _write_journal(tmp_path, 1, ["begin_pass"])
    assert run(["merge", str(tmp_path)]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 3
    assert run(["merge", str(tmp_path), "--kind", "gang_resize",
                "--format", "json"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1 and json.loads(out[0])["kind"] == "gang_resize"


def test_obs_cli_dump_counts_kinds(tmp_path, capsys):
    from paddle_tpu.obs.cli import run

    _write_journal(tmp_path, 0, ["a", "a", "b"])
    assert run(["dump", str(tmp_path)]) == 0
    captured = capsys.readouterr()
    assert "# a: 2" in captured.err and "# b: 1" in captured.err
    assert len(captured.out.strip().splitlines()) == 3


def test_obs_cli_empty_exits_2(tmp_path, capsys):
    from paddle_tpu.obs.cli import run

    assert run(["merge", str(tmp_path)]) == 2
    assert "no journal records" in capsys.readouterr().err
    # a healthy journal where --kind matches nothing is SUCCESS (exit 0
    # is "journal read fine, no such events"), with an honest message
    _write_journal(tmp_path, 0, ["begin_pass"])
    assert run(["merge", str(tmp_path), "--kind", "gang_resize"]) == 0
    assert "no 'gang_resize' records" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# profiler capture windows
# ---------------------------------------------------------------------------


class _FakeProfiler:
    def __init__(self, monkeypatch):
        self.starts, self.stops = [], 0
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda d: self.starts.append(d))
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: setattr(self, "stops", self.stops + 1))


def test_profiler_window_skips_compile_step_and_bounds_capture(
        tmp_path, monkeypatch):
    fake = _FakeProfiler(monkeypatch)
    cap = ProfilerCapture(str(tmp_path), steps=2, skip_first=1)
    cap.tick()                                   # step 0: compile, skipped
    assert fake.starts == []
    cap.tick()                                   # arms window-000
    assert fake.starts == [os.path.join(str(tmp_path), "window-000")]
    cap.tick()
    assert fake.stops == 0
    cap.tick()                                   # 2 steps captured -> stop
    assert fake.stops == 1
    cap.tick()                                   # disarmed: nothing more
    assert len(fake.starts) == 1

    cap.arm()                                    # SIGUSR2 path re-arms
    cap.tick()
    assert fake.starts[-1].endswith("window-001")
    cap.close()                                  # open window closed
    assert fake.stops == 2


def test_trainer_flag_armed_profile_window(tmp_path, monkeypatch):
    fake = _FakeProfiler(monkeypatch)
    monkeypatch.setattr(FLAGS, "profile_dir", str(tmp_path))
    monkeypatch.setattr(FLAGS, "profile_steps", 2)
    tr = _tiny_trainer()
    tr.train(lambda: iter(_feeds(5)), num_passes=1)
    # ONE bounded window under profile_dir — not the whole-run trace
    assert fake.starts == [os.path.join(str(tmp_path), "window-000")]
    assert fake.stops == 1


# ---------------------------------------------------------------------------
# the zero-added-host-transfer contract (lint --obs)
# ---------------------------------------------------------------------------


def test_audit_telemetry_step_is_clean():
    from paddle_tpu.obs.audit import audit_telemetry_step

    findings = audit_telemetry_step()
    assert findings == [], [f"{f.check}: {f.message}" for f in findings]


# ---------------------------------------------------------------------------
# acceptance: 2-process elastic gang -> one causal merged timeline
# ---------------------------------------------------------------------------

GANG_WORKER = """\
import json, os, sys, time

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("PADDLE_TPU_COMPUTE_DTYPE", "float32")

import numpy as np

import paddle_tpu.nn as nn
from paddle_tpu.param.optimizers import Adam
from paddle_tpu.resilience import chaos
from paddle_tpu.trainer import SGDTrainer, events as ev
from paddle_tpu.utils import FLAGS

save_dir, out_dir, chaos_rank = sys.argv[1:4]
rank = int(os.environ["PADDLE_TPU_PROCESS_ID"])
FLAGS.save_dir = save_dir
FLAGS.log_period = 0

x = nn.data("x", size=4)
y = nn.data("y", size=2)
cost = nn.mse_cost(input=nn.fc(x, 2, act="relu", name="h"), label=y)
tr = SGDTrainer(cost, Adam(learning_rate=0.05), seed=0)

rs = np.random.RandomState(0)
feeds = [{"x": rs.randn(4, 4).astype(np.float32),
          "y": rs.randn(4, 2).astype(np.float32)} for _ in range(6)]

def pace(e):
    if isinstance(e, ev.EndIteration):
        time.sleep(0.1)

handler = pace
if rank == int(chaos_rank):
    handler = chaos.die_at(pass_id=1, batch=2,
                           marker=os.path.join(out_dir, "fault-fired"),
                           inner=pace)

tr.train(lambda: iter(feeds), num_passes=3, event_handler=handler,
         resume="auto")
"""


def test_gang_journals_merge_into_one_timeline_with_resize(
        tmp_path, monkeypatch):
    """THE journal acceptance: rank 1 of a real 2-process elastic gang is
    SIGKILLed mid-pass.  Every rank (and the supervisor) journals into a
    shared --obs_journal dir; `obs merge` interleaves them into ONE
    causally-ordered timeline that tells the whole incident: the death,
    the shrink publish, the survivor's resize adopt + fsync'd checkpoint
    commit, the grow-back, and the joiner's join."""
    from paddle_tpu.resilience.cluster import GangSupervisor

    jdir = str(tmp_path / "journal")
    monkeypatch.setattr(FLAGS, "obs_journal", jdir)   # arms the supervisor
    script = tmp_path / "worker.py"
    script.write_text(GANG_WORKER)
    out_dir = tmp_path / "out"
    out_dir.mkdir()
    sup = GangSupervisor(
        ["localhost"] * 2, str(script),
        [str(tmp_path / "ckpts"), str(out_dir), "1"],
        gang_dir=str(tmp_path / "gang"), max_restarts=2, elastic=True,
        heartbeat_s=0.2, watchdog_s=5.0, startup_grace_s=180.0,
        backoff_s=0.05, poll_s=0.05,
        env={"PYTHONPATH": REPO_ROOT + os.pathsep
             + os.environ.get("PYTHONPATH", ""),
             "PADDLE_TPU_OBS_JOURNAL": jdir})
    result = sup.run()
    assert result.shrinks == 1 and result.grows == 1
    assert result.resize_fallbacks == 0

    # per-rank files: one per worker rank + the supervisor's
    names = sorted(os.listdir(jdir))
    assert "events-r00000.jsonl" in names
    assert "events-r00001.jsonl" in names
    assert "events-rsup.jsonl" in names

    merged, torn = merge_journals([jdir])
    # rank 1's SIGKILL may leave at most one torn tail; never unreadable
    assert torn <= 1
    ts = [r["t"] for r in merged]
    assert ts == sorted(ts)                       # ONE causal order
    kinds = [r["kind"] for r in merged]
    by_rank = {r: {x["kind"] for x in merged if x["rank"] == r}
               for r in (-1, 0, 1)}

    # the supervisor half: launch, the death, both world publishes, done
    assert "gang_launch" in by_rank[-1]
    assert "rank_failed" in by_rank[-1]
    assert "world_publish" in by_rank[-1]
    assert "gang_done" in by_rank[-1]
    # the survivor adopted the resize and committed the checkpoint
    assert "gang_resize" in by_rank[0]
    assert "checkpoint_commit" in by_rank[0]
    # the joiner's second incarnation journaled its join
    assert "gang_join" in by_rank[1]
    # causality: the death precedes the publish precedes the adopt
    assert (kinds.index("rank_failed")
            < kinds.index("world_publish")
            < kinds.index("gang_resize"))
    # every trainer record carries the world context for postmortems
    resize = next(r for r in merged if r["kind"] == "gang_resize")
    assert resize["new_world"] == 1 and resize["world_size"] == 1
    join = next(r for r in merged if r["kind"] == "gang_join")
    assert join["world_size"] == 2

    # and the CLI view of the same incident
    from paddle_tpu.obs.cli import run

    assert run(["merge", jdir, "--kind", "world_publish"]) == 0
