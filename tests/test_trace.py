"""Request-level distributed tracing (paddle_tpu/obs/trace.py;
docs/observability.md "Request tracing").

The acceptance bar:

- **span-tree invariants** — children nest inside their parents (ids AND
  times), and a serving request's root span duration matches the
  client-measured submit->reply wall-clock within tolerance;
- **tail-based sampling** — shed / deadline-exceeded / evicted /
  bad-step traces are kept at 100% even at ``--trace_sample=0``, the p99
  reservoir keeps outlier-slow traces, and head sampling drops the rest;
- **the straggler attribution scenario** — a short request co-scheduled
  with a chaos ``straggler_request`` decomposes span-by-span (queue wait
  vs. fused steps shared with the straggler at measured occupancy),
  reconstructable by ``python -m paddle_tpu obs trace`` and exportable
  as valid Perfetto/Chrome-trace JSON;
- **crash safety** — ``chaos.kill_mid_journal_write`` holds for span
  records exactly as for plain events (whole spans + one torn tail);
- **near-zero cost** — the tracing-armed training loop gives the same
  costs and writes a bounded number of span records a step (tier-1); its
  ``slow`` twin ``_timed`` holds the loop within the same <3% bound PR 9
  pinned for the timeline; ``lint --obs`` proves tracing adds ZERO
  compiled equations (tests/test_obs.py covers the audit's cleanliness).
"""

import json
import signal
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu.nn as nn
import paddle_tpu.ops as O
from paddle_tpu.__main__ import main
from paddle_tpu.obs import (EventJournal, close_journal, collect_traces,
                            format_trace_tree, journal_path, merge_journals,
                            perfetto_trace, read_journal, reset_registry,
                            reset_tracer)
from paddle_tpu.obs.trace import Tracer, get_tracer, null_tracer
from paddle_tpu.ops.decode import LogitsReadout
from paddle_tpu.param.optimizers import SGD
from paddle_tpu.resilience import chaos
from paddle_tpu.serving import InferenceServer, SlotBackend
from paddle_tpu.trainer import SGDTrainer
from paddle_tpu.utils.flags import FLAGS

HARD_TIMEOUT_S = 180


@pytest.fixture(autouse=True)
def hard_timeout_and_clean_tracer():
    def _abort(signum, frame):
        raise RuntimeError(f"trace test exceeded {HARD_TIMEOUT_S}s")

    prev = signal.signal(signal.SIGALRM, _abort)
    signal.alarm(HARD_TIMEOUT_S)
    keep = (FLAGS.obs_journal, FLAGS.trace_sample, FLAGS.trace_tail_p99)
    yield
    FLAGS.obs_journal, FLAGS.trace_sample, FLAGS.trace_tail_p99 = keep
    close_journal()
    reset_tracer()
    signal.alarm(0)
    signal.signal(signal.SIGALRM, prev)


# ---------------------------------------------------------------------------
# tracer unit: span trees, context propagation, sampling
# ---------------------------------------------------------------------------


def test_span_tree_ids_times_and_thread_context():
    tr = Tracer()   # journal=None: kept records collect in tr.records
    with tr.start_trace("request", request="req-1", mode="test") as root:
        time.sleep(0.01)
        with tr.span("child_a") as a:      # parented via the thread stack
            time.sleep(0.01)
            tr.span("grandchild").end()    # parented on child_a
        assert tr.current() is root        # stack popped back to the root
        root.child("child_b").end(status="done")
    recs = tr.records
    assert [r["name"] for r in recs] == [
        "grandchild", "child_a", "child_b", "request"]
    by_name = {r["name"]: r for r in recs}
    rootr = by_name["request"]
    assert rootr["request"] == "req-1" and "parent" not in rootr
    assert rootr["attrs"]["mode"] == "test"
    assert by_name["child_a"]["parent"] == rootr["span"]
    assert by_name["grandchild"]["parent"] == by_name["child_a"]["span"]
    # every record of the trace carries the request id for --request=
    assert all(r["request"] == "req-1" and r["trace"] == rootr["trace"]
               for r in recs)
    # times nest: children start/end inside the root's window
    for r in recs:
        assert r["t0"] >= rootr["t0"] - 1e-6
        assert r["t0"] + r["dur"] <= rootr["t0"] + rootr["dur"] + 1e-6
    assert by_name["child_a"]["dur"] >= 0.009


def test_span_outside_any_trace_is_inert_and_null_tracer_is_free():
    tr = Tracer()
    sp = tr.span("orphan")          # no parent, no thread context
    sp.set(x=1).event("e")
    sp.end()
    assert tr.records == []
    nt = null_tracer()
    assert not nt.enabled
    root = nt.start_trace("r")
    with root.child("c"):
        root.child_at("d", 0.0, 1.0)
        root.retain("x")
    assert nt.trace_at("r", 0.0, 1.0) == ""


def test_tail_sampling_keeps_retained_drops_headsampled():
    tr = Tracer(sample=0.0, tail_p99=False)
    ok = tr.start_trace("request")
    ok.end(status="completed")
    assert tr.records == [] and tr.dropped == 1
    bad = tr.start_trace("request")
    bad.retain("deadline_expired")
    bad.end(status="deadline_expired")
    assert tr.kept == 1
    assert tr.records[-1]["retained"] == "deadline_expired"
    # sample=1.0 keeps everything, stamped with the sampling reason
    tr2 = Tracer(sample=1.0, tail_p99=False)
    tr2.start_trace("request").end(status="completed")
    assert tr2.records[-1]["retained"] == "head_sample"


def test_p99_reservoir_keeps_outliers_even_at_sample_zero():
    tr = Tracer(sample=0.0, tail_p99=True, min_reservoir=32)
    for i in range(40):   # durations 1..40 ms warm the reservoir
        tr.trace_at("step", 100.0, 100.0 + 0.001 * (i + 1))
    before = len(tr.records)
    tr.trace_at("step", 200.0, 200.06)       # 60ms: far past the p99
    assert len(tr.records) == before + 1
    assert tr.records[-1]["retained"] == "p99_tail"
    tr.trace_at("step", 300.0, 300.005)      # 5ms: mid-distribution
    assert len(tr.records) == before + 1     # dropped
    # reservoirs are per root name: a different kind starts cold
    tr.trace_at("request", 400.0, 400.001)
    assert len(tr.records) == before + 1


def test_trace_buffer_bounds_spans_and_reports_drops():
    tr = Tracer()
    tr.MAX_SPANS_PER_TRACE = 4
    root = tr.start_trace("r")
    for i in range(10):
        root.child_at(f"c{i}", 0.0, 0.1)
    root.end()
    kept = [r["name"] for r in tr.records]
    assert len(kept) == 5                     # 4 children + the root
    assert tr.records[-1]["spans_dropped"] == 6   # no silent truncation


# ---------------------------------------------------------------------------
# serving: the straggler attribution scenario (THE acceptance run)
# ---------------------------------------------------------------------------

V, H, K = 12, 8, 2


class ToyLM(SlotBackend):
    """EOS-prone GRU LM behind the slot protocol (the test_serving_slots
    pattern): per-request state carries the chaos ``eos_bias`` so
    ``straggler_request`` can pin a request never-EOS."""

    beam_size, vocab_size, bos, eos = K, V, 0, 1
    length_penalty = 0.0
    use_kernel = None

    def __init__(self, rng, *, max_len=10, eos_boost=6.0):
        self.max_len = max_len
        self.p = {
            "emb": jnp.asarray(0.5 * rng.randn(V, H).astype(np.float32)),
            "wx": jnp.asarray(0.5 * rng.randn(H, 3 * H).astype(np.float32)),
            "wh": jnp.asarray(0.5 * rng.randn(H, 3 * H).astype(np.float32)),
            "out": jnp.asarray(rng.randn(H, V).astype(np.float32)),
            "outb": jnp.asarray(
                np.eye(1, V, 1)[0].astype(np.float32) * eos_boost),
        }
        self.readout = LogitsReadout()

    def prefill(self, feed):
        return {"h": jnp.asarray(feed["h"], jnp.float32),
                "bias": jnp.asarray(feed["eos_bias"], jnp.float32)}

    def step_fn(self, tokens, state):
        e = jnp.take(self.p["emb"], tokens, axis=0)
        h2 = O.gru_step(O.linear(e, self.p["wx"]), state["h"], self.p["wh"])
        logits = O.linear(h2, self.p["out"], self.p["outb"])
        logits = logits.at[:, self.eos].add(state["bias"][:, 0])
        return logits, dict(state, h=h2)

    def example_feed(self, rows=1):
        return {"h": np.zeros((rows, H), np.float32),
                "eos_bias": np.zeros((rows, 1), np.float32)}


def _feed(rng, rows=1):
    return {"h": rng.randn(rows, H).astype(np.float32),
            "eos_bias": np.zeros((rows, 1), np.float32)}


def _arm(tmp_path, sample=1.0, tail=True):
    jd = str(tmp_path / "journal")
    FLAGS.obs_journal = jd
    FLAGS.trace_sample = sample
    FLAGS.trace_tail_p99 = tail
    close_journal()
    reset_tracer()
    return jd


def _spans(jd):
    close_journal()
    reset_tracer()
    records, torn = merge_journals([jd])
    assert torn == 0
    return collect_traces(records)


def test_straggler_run_attributes_short_request_span_by_span(
        rng, tmp_path, capsys):
    """THE acceptance scenario: a chaos straggler shares the slot table
    with short requests; the merged journal yields each short request's
    latency decomposed into queue wait vs. fused steps shared with the
    straggler (slot ids + occupancy per step), the trace reconstructs
    via `obs trace`, and the Perfetto export is valid Chrome-trace
    JSON."""
    jd = _arm(tmp_path)
    be = ToyLM(rng, max_len=40, eos_boost=8.0)
    srv = InferenceServer(be, mode="generation", slots=2,
                          batch_delay_ms=0.0, max_queue=32,
                          default_deadline_ms=120000.0)
    srv.start()
    with srv:
        f_strag = srv.submit(chaos.straggler_request(_feed(rng)),
                             deadline_ms=240000.0)
        t0 = time.time()
        shorts = [srv.submit(_feed(rng), max_len=6) for _ in range(3)]
        for f in shorts:
            assert f.error(120) is None
        wall = time.time() - t0
        assert f_strag.error(240) is None
        rid_short = shorts[0].req_id
        assert rid_short.startswith("req-")

    traces = _spans(jd)
    # every request left a trace (sample=1.0): 1 straggler + 3 shorts
    roots = {tid: next(s for s in sp if not s.get("parent"))
             for tid, sp in traces.items()}
    assert len(roots) == 4
    short_tid = next(t for t, r in roots.items()
                     if r.get("request") == rid_short)
    spans = traces[short_tid]
    root = roots[short_tid]
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    assert set(by_name) >= {"request", "admission", "queue", "prefill",
                            "decode_step", "harvest"}
    # span-sum invariant: the root duration matches the client-measured
    # submit->reply wall-clock (generous tolerance: the client waited on
    # three futures, each root must be <= total and > 0)
    assert 0 < root["dur"] <= wall + 0.5
    # children nest inside the root window
    for s in spans:
        if s is root:
            continue
        assert s["t0"] >= root["t0"] - 1e-3
        assert s["t0"] + s["dur"] <= root["t0"] + root["dur"] + 1e-3
    # decode steps carry slot + occupancy attribution; the short shared
    # the 2-slot table with the straggler, so occupancy was 1.0
    steps = by_name["decode_step"]
    assert all(s["attrs"]["slots"] for s in steps)
    assert any(s["attrs"]["occupancy"] == 1.0 for s in steps)
    assert root["status"] == "completed"
    # the straggler decoded its full budget: >= 40 step spans
    strag_tid = max(roots, key=lambda t: roots[t]["dur"])
    n_steps = sum(1 for s in traces[strag_tid]
                  if s["name"] == "decode_step")
    assert n_steps >= 40

    # `obs trace DIR` (index), `--trace=ID` (tree), `--request=ID`
    assert main(["obs", "trace", jd]) == 0
    out = capsys.readouterr().out
    assert short_tid in out and strag_tid in out
    assert main(["obs", "trace", jd, "--trace", short_tid]) == 0
    tree = capsys.readouterr().out
    assert "decode_step" in tree and "queue" in tree and "harvest" in tree
    assert main(["obs", "trace", jd, "--request", rid_short]) == 0
    assert "decode_step" in capsys.readouterr().out

    # Perfetto export: loadable Chrome-trace JSON with complete events
    assert main(["obs", "trace", jd, "--format", "perfetto"]) == 0
    doc = json.loads(capsys.readouterr().out)
    evs = doc["traceEvents"]
    assert evs and {"X", "i", "M"} >= {e["ph"] for e in evs}
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) >= len(spans)
    for e in xs:
        assert isinstance(e["ts"], int) and isinstance(e["dur"], int)
        assert e["dur"] >= 1 and e["name"]


def test_tail_sampling_keeps_every_incident_drops_completed(rng, tmp_path):
    """--trace_sample=0: shed, queued-expired, and mid-generation-evicted
    requests ALL keep their traces; completed requests keep none."""
    jd = _arm(tmp_path, sample=0.0, tail=False)
    be = ToyLM(rng, max_len=5000)
    srv = InferenceServer(be, mode="generation", slots=1,
                          batch_delay_ms=0.0, max_queue=1,
                          default_deadline_ms=120000.0)
    srv.start()
    with srv:
        # resident straggler: expires mid-decode -> evicted.  Wait for it
        # to actually occupy the slot, or the next submit contends for
        # the depth-1 queue with it and sheds nondeterministically.
        f_evicted = srv.submit(chaos.straggler_request(_feed(rng)),
                               deadline_ms=500.0)
        deadline = time.monotonic() + 30
        while srv._scheduler.occupied() == 0 \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        assert srv._scheduler.occupied() == 1
        # queued behind it with a tiny deadline -> expires queued
        f_queued = srv.submit(_feed(rng), deadline_ms=30.0)
        # the bounded queue (depth 1) is full -> shed
        from paddle_tpu.serving import ShedError

        with pytest.raises(ShedError):
            srv.submit(_feed(rng))
        assert f_queued.error(60) is not None
        assert f_evicted.error(60) is not None
        # a healthy completed request afterwards: head-sampled away
        assert srv.submit(_feed(rng), max_len=3,
                          deadline_ms=120000.0).error(120) is None

    traces = _spans(jd)
    statuses = sorted(
        next(s for s in sp if not s.get("parent")).get("status")
        for sp in traces.values())
    assert statuses == ["deadline_expired", "deadline_expired", "shed"]
    # the evicted one is distinguishable from the queued-expired one
    evicted = [sp for sp in traces.values()
               if any(s.get("attrs", {}).get("evicted") for s in sp
                      if not s.get("parent"))]
    assert len(evicted) == 1
    root = next(s for s in evicted[0] if not s.get("parent"))
    assert any(ev["name"] == "evicted" for ev in root.get("events", []))
    assert all(
        next(s for s in sp if not s.get("parent")).get("retained")
        in ("shed", "deadline_expired") for sp in traces.values())


def test_latency_histogram_buckets_carry_trace_exemplars(rng, tmp_path):
    """The exemplar linkage: a completed request's latency observation
    stamps its trace id onto the histogram bucket, so a dashboard spike
    links to a concrete trace."""
    from paddle_tpu.obs import get_registry

    jd = _arm(tmp_path)
    reset_registry()
    be = ToyLM(rng, max_len=10)
    srv = InferenceServer(be, mode="generation", slots=2,
                          batch_delay_ms=0.0, default_deadline_ms=120000.0)
    srv.start()
    with srv:
        assert srv.submit(_feed(rng), max_len=3).error(120) is None
        # snapshot INSIDE the server's lifetime (close() retires the
        # series), POLLING for the observation: the future resolves
        # before the worker's observe_latency call
        series = _latency_series(get_registry())
    exemplars = [e for s in series for e in (s.get("exemplars") or {}).values()]
    assert exemplars, series
    traces = _spans(jd)
    assert any(ex["trace"] in traces for ex in exemplars)


def _latency_series(reg, timeout=10.0):
    deadline = time.monotonic() + timeout
    series = []
    while time.monotonic() < deadline:
        snap = reg.snapshot().get("serving_latency_seconds", {})
        series = snap.get("series", [])
        if any(s.get("count") for s in series):
            return series
        time.sleep(0.01)
    return series


def test_exemplar_only_links_traces_the_journal_actually_kept(
        rng, tmp_path):
    """--trace_sample=0: a completed request's trace is DROPPED, so its
    latency observation must carry no exemplar — a dashboard must never
    link to a trace `obs trace` cannot find."""
    from paddle_tpu.obs import get_registry

    _arm(tmp_path, sample=0.0, tail=False)
    reset_registry()
    be = ToyLM(rng, max_len=10)
    srv = InferenceServer(be, mode="generation", slots=2,
                          batch_delay_ms=0.0, default_deadline_ms=120000.0)
    srv.start()
    with srv:
        assert srv.submit(_feed(rng), max_len=3).error(120) is None
        series = _latency_series(get_registry())
    assert any(s.get("count") for s in series)   # the observation landed
    assert all(not s.get("exemplars") for s in series), series


def test_registry_histogram_exemplar_unit():
    from paddle_tpu.obs.registry import MetricsRegistry

    reg = MetricsRegistry()
    h = reg.histogram("lat", "test")
    h.observe(0.02)                      # no exemplar: nothing stored
    h.observe(0.3, exemplar="tid123")
    snap = reg.snapshot()["lat"]["series"][0]
    assert snap["count"] == 2
    assert list(snap["exemplars"].values()) != []
    (ex,) = [v for v in snap["exemplars"].values()]
    assert ex["trace"] == "tid123" and ex["value"] == 0.3
    # classic Prometheus text stays exemplar-free (v0.0.4 has no syntax)
    assert "tid123" not in reg.prometheus_text()


# ---------------------------------------------------------------------------
# trainer: step-span traces
# ---------------------------------------------------------------------------


def _tiny_trainer():
    nn.reset_naming()
    x = nn.data("tx", size=8)
    y = nn.data("ty", size=2)
    cost = nn.mse_cost(input=nn.fc(x, 2, name="th"), label=y)
    return SGDTrainer(cost, SGD(learning_rate=0.01), seed=0)


def _feeds(n, rng, nan_at=None):
    feeds = []
    for i in range(n):
        f = {"tx": rng.randn(4, 8).astype(np.float32),
             "ty": rng.randn(4, 2).astype(np.float32)}
        if i == nan_at:
            f = chaos.nan_feed(f)
        feeds.append(f)
    return feeds


def test_trainer_step_spans_with_phase_children(rng, tmp_path):
    jd = _arm(tmp_path)
    tr = _tiny_trainer()
    tr.train(lambda: iter(_feeds(3, rng)), num_passes=1)
    traces = _spans(jd)
    roots = [next(s for s in sp if not s.get("parent"))
             for sp in traces.values()]
    steps = [r for r in roots if r["name"] == "train_step"]
    assert len(steps) == 3
    assert sorted(r["attrs"]["batch"] for r in steps) == [0, 1, 2]
    assert all(r["status"] == "ok" and "cost" in r["attrs"]
               for r in steps)
    # phases ride as children, and the journal's sticky context stamps
    # every span record with pass/batch
    sp = traces[steps[0]["trace"]]
    names = {s["name"] for s in sp}
    assert names >= {"train_step", "data_wait", "prepare", "step",
                     "callback"}
    assert all(s.get("pass") == 0 for s in sp)
    root = steps[0]
    covered = sum(s["dur"] for s in sp if s.get("parent") == root["span"])
    assert covered <= root["dur"] + 0.01
    for s in sp:
        if not s.get("parent"):
            continue
        assert s["t0"] >= root["t0"] - 1e-3
        assert s["t0"] + s["dur"] <= root["t0"] + root["dur"] + 1e-3


def test_trainer_bad_step_trace_retained_at_sample_zero(rng, tmp_path):
    jd = _arm(tmp_path, sample=0.0, tail=False)
    tr = _tiny_trainer()
    tr.train(lambda: iter(_feeds(5, rng, nan_at=2)), num_passes=1)
    assert tr.bad_steps_total == 1
    traces = _spans(jd)
    roots = [next(s for s in sp if not s.get("parent"))
             for sp in traces.values()]
    assert len(roots) == 1                       # ONLY the incident kept
    (r,) = roots
    assert r["retained"] == "bad_step"
    assert r["attrs"]["bad_step"] is True and r["attrs"]["batch"] == 2


def test_tracing_off_leaves_no_spans_and_no_request_ids(rng, tmp_path):
    """'' journal = tracing disarmed: the loop pays one enabled check,
    requests carry no ids, and nothing is written anywhere."""
    assert not get_tracer().enabled
    be = ToyLM(rng, max_len=10)
    srv = InferenceServer(be, mode="generation", slots=2,
                          batch_delay_ms=0.0, default_deadline_ms=120000.0)
    srv.start()
    with srv:
        fut = srv.submit(_feed(rng), max_len=3)
        assert fut.error(120) is None
        assert not hasattr(fut, "req_id")


# ---------------------------------------------------------------------------
# crash safety + CLI filters
# ---------------------------------------------------------------------------


def test_kill_mid_journal_write_holds_for_span_records(tmp_path, capsys):
    """The PR 9 crash contract extended to trace persistence: a rank
    SIGKILLed mid-flush leaves whole span records plus one torn tail,
    and the merged trace still reconstructs."""
    jd = str(tmp_path)
    healthy = EventJournal(journal_path(jd, 0), rank=0, world_size=2)
    healthy.record("begin_pass")
    whole = chaos.kill_mid_journal_write(jd, rank=1, whole_records=6,
                                         record_kind="span")
    healthy.close()
    merged, torn = merge_journals([jd])
    assert torn == 1
    traces = collect_traces(merged)
    assert list(traces) == ["deadbeefdeadbeef"]
    assert len(traces["deadbeefdeadbeef"]) == whole
    tree = format_trace_tree(traces["deadbeefdeadbeef"])
    assert "victim_root" in tree and "victim_child" in tree
    doc = perfetto_trace(traces["deadbeefdeadbeef"])
    assert len([e for e in doc["traceEvents"] if e["ph"] == "X"]) == whole
    assert main(["obs", "trace", jd, "--trace", "deadbeefdeadbeef"]) == 0
    assert "victim_root" in capsys.readouterr().out


def test_obs_merge_and_dump_trace_request_filters(tmp_path, capsys):
    """The --trace/--request plumbing on merge/dump: filters select the
    span records; zero matches exits 0 with an honest message (the
    --kind contract pinned in PR 9)."""
    jd = str(tmp_path)
    j = EventJournal(journal_path(jd, 0), rank=0)
    tr = Tracer(journal=j)
    root = tr.start_trace("request", request="req-zz")
    root.child_at("queue", root.t_start, root.t_start + 0.01)
    root.end(status="completed")
    tid = root.trace_id
    j.record("begin_pass")
    j.close()

    assert main(["obs", "merge", jd, "--trace", tid]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 2 and all("span" in l for l in out)
    assert main(["obs", "merge", jd, "--request", "req-zz",
                 "--format", "json"]) == 0
    rows = [json.loads(l) for l in
            capsys.readouterr().out.strip().splitlines()]
    assert {r["request"] for r in rows} == {"req-zz"}
    # dump takes the same filters
    assert main(["obs", "dump", jd, "--trace", tid]) == 0
    assert "# span: 2" in capsys.readouterr().err
    # zero matches: honest message, exit 0 (NOT the exit-2 empty case)
    assert main(["obs", "merge", jd, "--trace", "nope"]) == 0
    assert "no records with trace" in capsys.readouterr().err
    assert main(["obs", "trace", jd, "--request", "nope"]) == 0
    assert "no trace with request" in capsys.readouterr().err
    # a journal with records but no spans: obs trace exits 0, honestly
    jd2 = str(tmp_path / "nospans")
    j2 = EventJournal(journal_path(jd2, 0), rank=0)
    j2.record("begin_pass")
    j2.close()
    assert main(["obs", "trace", jd2]) == 0
    assert "no span records" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# overhead: the PR 9 bound holds with tracing armed
# ---------------------------------------------------------------------------


def test_tracing_overhead_under_3_percent(rng, tmp_path):
    """What tracing ARMED at full sampling adds, as counts: the same costs
    batch for batch as the disarmed loop, which writes nothing; armed, one
    ``train_step`` tree a batch with a bounded number of span records in it
    (the root and its phase children).  What that costs in wall-clock is
    the ``_timed`` twin's to say."""
    from paddle_tpu.trainer import events as ev

    feeds, costs = _feeds(8, rng), {}
    for armed in (False, True):
        if armed:
            jd = _arm(tmp_path)
        tr, got = _tiny_trainer(), []
        tr.train(lambda: iter(feeds), num_passes=1,
                 event_handler=lambda e: got.append(e.cost)
                 if isinstance(e, ev.EndIteration) else None)
        costs[armed] = got
        if not armed:
            assert not get_tracer().enabled
            assert not (tmp_path / "journal").exists()
    np.testing.assert_array_equal(costs[False], costs[True])
    trees = [sp for sp in _spans(jd).values()
             if any(s["name"] == "train_step" and not s.get("parent")
                    for s in sp)]
    assert len(trees) == len(feeds)
    assert max(len(sp) for sp in trees) <= 12, [len(sp) for sp in trees]


@pytest.mark.slow
def test_tracing_overhead_under_3_percent_timed(tmp_path, monkeypatch):
    """The acceptance bound, matching PR 9's pattern: the loop with
    tracing ARMED at full sampling (journal + step spans + phase
    children) must stay within 3% of the disarmed loop."""
    nn.reset_naming()
    x = nn.data("x", size=512)
    y = nn.data("y", size=2)
    h = nn.fc(x, 512, act="relu", name="h1")
    h = nn.fc(h, 512, act="relu", name="h2")
    cost = nn.mse_cost(input=nn.fc(h, 2, name="out"), label=y)
    tr = SGDTrainer(cost, SGD(learning_rate=0.01), seed=0)
    rs = np.random.RandomState(0)
    feeds = [{"x": rs.randn(256, 512).astype(np.float32),
              "y": rs.randn(256, 2).astype(np.float32)} for _ in range(25)]
    jd = str(tmp_path / "journal")

    def timed(trace_on):
        FLAGS.obs_journal = jd if trace_on else ""
        FLAGS.trace_sample = 1.0
        close_journal()
        reset_tracer()
        t0 = time.perf_counter()
        tr.train(lambda: iter(feeds), num_passes=1)
        return time.perf_counter() - t0

    import gc
    import statistics

    timed(False)                  # compile warmup
    timed(True)                   # journal/tracer warmup for the on path
    off_times, on_times = [], []
    gc.collect()
    gc.disable()
    try:
        for _ in range(5):        # INTERLEAVED pairs, like test_obs
            off_times.append(timed(False))
            on_times.append(timed(True))
    finally:
        gc.enable()
    off = statistics.median(off_times)
    on = statistics.median(on_times)
    assert on <= off * 1.03 + 0.03, (
        f"traced loop {on:.4f}s vs untraced {off:.4f}s "
        f"({(on / off - 1) * 100:.2f}% overhead; off={off_times} "
        f"on={on_times})")


def test_supervisor_tracer_writes_incident_traces(tmp_path):
    """The gang half: a Tracer bound to the supervisor's rank -1 journal
    flushes retained incident spans immediately (trace_at), and they
    merge into the same timeline as worker spans."""
    jd = str(tmp_path)
    j = EventJournal(journal_path(jd, -1), rank=-1)
    tr = Tracer(journal=j, sample=0.0)   # incidents must not need sampling
    tid = tr.trace_at("gang_shrink", 100.0, 102.5, retain="gang_resize",
                      epoch=1, world=3)
    j.close()
    recs, torn = read_journal(journal_path(jd, -1))
    assert torn == 0 and len(recs) == 1
    (r,) = recs
    assert r["kind"] == "span" and r["trace"] == tid
    assert r["rank"] == -1 and r["retained"] == "gang_resize"
    assert r["dur"] == 2.5 and r["attrs"]["epoch"] == 1
