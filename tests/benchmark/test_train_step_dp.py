"""The four-chip cell ``seq2seq-train-dp4`` on the CPU's virtual devices: the
runner ``train_step_dp`` against the one-chip runner on the whole batch, the
number ``replica_param_gap``, the manifest, ``correct`` with its control and
with the timed path broken underneath (a state returned unchanged, half of
the batch left out, the exchange between chips left out), and the readers of
the layer ``parallel`` on events written by hand and on ``dp4.xplane.pb.gz``,
a trace of four chips recorded on a v5e host (record_dp4.py)."""

import copy
import gzip
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, trace_chips, trace_reduce  # noqa: E402

DP4, S2S, LSTM = ("seq2seq-train-dp4", "seq2seq-train-b384-s96",
                  "lstm-trainer-b256-t640")
CHIPS = 4
FAKE_HOST = {"platform": "tpu", "kind": "TPU v5 lite", "count": CHIPS}
FOUR_PLANES_GZ = os.path.join(HERE, "dp4.xplane.pb.gz")
ONE_PLANE = os.path.join(HERE, "spans.xplane.pb")
NEW = ["device_ms_per_step.collectives", "collective_exposed_ms_per_step",
       "chip_busy_skew_pct"]
SCOPES = ["device_ms_per_step.encoder", "device_ms_per_step.decoder",
          "device_ms_per_step.readout_ce", "device_ms_per_step.optimizer"]
TINY = (dict(src_vocab=211, trg_vocab=211, emb_dim=32, enc_dim=32,
             dec_dim=32, att_dim=32),
        dict(batch_per_chip=2, src_len=6, trg_len=6,
             reference_rows_per_block=4, warmup_steps=1, ring=4))
#: what the sharded step may differ by from the one-chip step on the same
#: rows in float32: another order of summing the rows' gradients.  Losses
#: relative; a leaf's parameters as the norm of the difference over the norm
#: of the leaf's change in three steps (Adam divides a gradient by its own
#: size, so an element whose gradient all but cancels moves by the learning
#: rate either way: the bound is on the leaf, not on the element).  Read at
#: this size over seeds 11-14: 1.8e-7 and 2.5e-4 (``att_dec_w``) at most
LOSS_RTOL, CHANGE_RTOL = 2e-6, 2e-3


def tiny_cell():
    """The cell at a toy size, its limits read at that size on the CPU by
    the rule the chip's were (``check_correct.suggest_limits``: 8 sound
    seeds, 4 control seeds)."""
    cell = copy.deepcopy(manifest.cell(DP4))
    cell["config"].update(TINY[0])
    cell["traffic"].update(TINY[1])
    with open(os.path.join(HERE, "tiny_limits_dp4.json")) as f:
        cell["limits"] = json.load(f)
    return cell


@pytest.fixture
def four_devices():
    import jax

    if jax.device_count() < CHIPS:
        pytest.skip(f"the process started with {jax.device_count()} devices")


def _set_policy(monkeypatch, compute_dtype):
    from paddle_tpu.utils.flags import FLAGS

    for flag, value in (("dtype", "float32"), ("amp", False),
                        ("compute_dtype", compute_dtype)):
        monkeypatch.setattr(FLAGS, flag, value)


@pytest.fixture
def policy(monkeypatch):
    """The precision policy the configuration states, as run.py sets it."""
    _set_policy(monkeypatch, "bfloat16")


@pytest.fixture
def float32_policy(monkeypatch):
    _set_policy(monkeypatch, "float32")


def _runner():
    return manifest.runner("train_step_dp")


def _measure(cell, runner, seed=3, seconds=0.3):
    from benchmark import run

    return run.measure(cell, manifest.reference(cell["config"]), runner,
                       seed, seconds, 0, FAKE_HOST)


# -- the manifest ------------------------------------------------------------


def test_manifest_finds_the_cell_its_limits_and_its_metrics():
    cell = manifest.cell(DP4)
    entry = next(w for w in manifest.benchmark_json()["workloads"]
                 if w["name"] == DP4)
    assert entry["chips"] == cell["chips"] == CHIPS
    assert len(entry["why"]) <= 200
    tr = cell["traffic"]
    assert (tr["runner"], tr["src_len"], tr["trg_len"], tr["lengths"]) == (
        "train_step_dp", 96, 32, "full")
    assert "grown" in tr and tr["batch_per_chip"] % 128 == 0
    runner = _runner()
    assert callable(runner.run) and callable(runner.correct_numbers)
    assert runner.global_traffic(cell)["batch"] == CHIPS * tr["batch_per_chip"]
    assert callable(runner.program(cell).parallel_train_step)
    ref = manifest.reference(cell["config"])
    leaves = {"grad_diff." + k for k in ref.param_shapes(cell["config"])}
    assert leaves | {"grad_diff_median"} == {
        k for k in cell["limits"] if k.startswith("grad_diff")}
    assert set(cell["limits"]) >= {"loss_gap", "delta_norm_gap"}
    assert all(cell["limits"][k] == 0 for k in (
        "replica_param_gap", "nonfinite_losses", "bad_steps",
        "compiles_in_window"))
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tokens_per_s",
                                                      "setup_s"}
    assert {m["name"] for m in cell["per_layer"]} == set(NEW + SCOPES) | {
        "compile_s", "mfu_pct", "pallas_share_pct.train",
        "device_idle_pct.train"}
    # the one-chip cells did not get the new layer's metrics
    for other in (S2S, LSTM):
        assert not set(NEW) & {m["name"] for m in
                               manifest.cell(other)["per_layer"]}
    # at most a quarter of the cells, and one always, may take four chips
    cells = manifest.benchmark_json()["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)


# -- the runner against the one-chip runner ----------------------------------


def test_dp_step_gives_the_one_chip_steps_losses_and_parameters(
        four_devices, float32_policy):
    import jax

    from benchmark import correct, traffic

    cell = tiny_cell()
    runner, one_chip = _runner(), manifest.runner("train_step")
    cfg, tr = cell["config"], runner.global_traffic(cell)
    ref = manifest.reference(cfg)
    params = correct.init_params(ref, cfg, 11)
    host_ring = traffic.batches(ref, cfg, tr, 11, correct.STEPS)
    step, opt = manifest.program(cfg).train_step(cfg)
    want, want_p, _ = one_chip.first_steps(
        step, params, opt.init_state(params),
        [jax.device_put(b) for b in host_ring], cfg["optimizer"]["beta1"])
    got, got_p, _, _, ring = runner._dp_steps(cell, cfg, params, host_ring)
    assert len(got_p["out_w"].sharding.device_set) == CHIPS
    rows = ring[0]["src_ids"].addressable_shards
    assert {s.data.shape[0] for s in rows} == {tr["batch"] // CHIPS}
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=LOSS_RTOL)
    moved = 0
    for k in params:
        change = float(np.linalg.norm(np.asarray(want_p[k])
                                      - np.asarray(params[k])))
        apart = float(np.linalg.norm(np.asarray(got_p[k])
                                     - np.asarray(want_p[k])))
        moved += change > 0
        assert apart <= CHANGE_RTOL * change, (k, apart, change)
    assert moved == len(params)
    assert runner.replica_param_gap(got_p) == 0.0


def test_replica_param_gap_sees_one_chips_copy_differ(four_devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    runner = _runner()
    mesh = Mesh(np.asarray(jax.devices()[:CHIPS]), ("data",))
    same = jax.device_put(jnp.arange(8.0), NamedSharding(mesh, P()))
    assert runner.replica_param_gap({"w": same}) == 0.0
    # "replicated" by declaration only: chip i holds i in every element
    apart = jax.jit(jax.shard_map(
        lambda x: x + jax.lax.axis_index("data"), mesh=mesh,
        in_specs=P(), out_specs=P(), check_vma=False))(same)
    assert runner.replica_param_gap({"w": same, "v": apart}) == 3.0
    with pytest.raises(ValueError):
        runner.replica_param_gap({"w": jax.device_put(
            jnp.arange(8.0), NamedSharding(mesh, P("data")))})


# -- correct -----------------------------------------------------------------


def test_sound_run_is_correct_and_reports_the_cells_metrics(
        four_devices, policy, capsys):
    line = _measure(tiny_cell(), _runner())
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["count"] == CHIPS
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    compared = [json.loads(l) for l in capsys.readouterr().out.splitlines()
                if l.startswith('{"compared"')][-1]["compared"]
    assert all(v["ok"] for v in compared.values())
    assert compared["replica_param_gap"] == {"value": 0.0, "limit": 0,
                                             "ok": True}


@pytest.mark.parametrize("control", [True, "half_batch", "no_exchange"])
def test_control_and_planted_faults_are_not_correct(four_devices, policy,
                                                    control):
    """The fp8 control, and the reference in the program's place with half
    of the rows or all but one chip's rows left out of the mean."""
    from benchmark import correct

    cell = tiny_cell()
    runner = _runner()
    ref = manifest.reference(cell["config"])
    seed = 2 ** 31 + 5
    numbers = runner.correct_numbers(cell, ref, seed, control=control)
    held = {k: v for k, v in cell["limits"].items() if k in numbers}
    assert held and not correct.judge(numbers, held)
    if control is True:
        sound = runner.correct_numbers(cell, ref, seed)
        assert correct.judge(sound, {k: v for k, v in cell["limits"].items()
                                     if k in sound})


def _broken(monkeypatch, runner, rebuild):
    """``runner`` with its program's ``parallel_train_step`` replaced by
    ``rebuild(real)(cfg, chips)``."""
    real = runner.program

    def program(cell):
        module = real(cell)
        module.parallel_train_step = rebuild(module.parallel_train_step)
        return module

    monkeypatch.setattr(runner, "program", program)
    return runner


def test_step_that_returns_its_state_unchanged_is_not_correct(
        four_devices, policy, monkeypatch):
    import jax
    import jax.numpy as jnp

    def rebuild(real):
        def build(cfg, chips):
            step, opt, mesh = real(cfg, chips)

            def lazy(params, opt_state, batch):
                loss, _, _ = step(*jax.tree_util.tree_map(
                    jnp.copy, (params, opt_state)), batch)
                return loss, params, opt_state

            return lazy, opt, mesh

        return build

    assert _measure(tiny_cell(), _broken(monkeypatch, _runner(), rebuild))[
        "correct"] is False


def test_step_that_drops_half_the_batch_is_not_correct(
        four_devices, policy, monkeypatch):
    """Every chip's second half of rows left out, the mean over the rest."""
    import jax

    def rebuild(real):
        def build(cfg, chips):
            step, opt, mesh = real(cfg, chips)

            def half(params, opt_state, batch):
                from paddle_tpu import parallel

                rows = jax.tree_util.tree_leaves(batch)[0].shape[0] // 2
                kept = parallel.shard_batch(mesh, jax.tree_util.tree_map(
                    lambda a: np.asarray(a)[:rows], batch))
                return step(params, opt_state, kept)

            return half, opt, mesh

        return build

    assert _measure(tiny_cell(), _broken(monkeypatch, _runner(), rebuild))[
        "correct"] is False


def test_step_without_the_exchange_is_not_correct(four_devices, policy,
                                                  monkeypatch, capsys):
    """Every chip applies the gradient of its own rows: no all-reduce.  The
    state is replicated by declaration only, and the copies drift apart."""
    import jax
    from jax.sharding import PartitionSpec as P

    def rebuild(real):
        def build(cfg, chips):
            from paddle_tpu.models import Seq2SeqAttention

            _, opt, mesh = real(cfg, chips)
            model = Seq2SeqAttention(
                src_vocab=cfg["src_vocab"], trg_vocab=cfg["trg_vocab"],
                emb_dim=cfg["emb_dim"], enc_dim=cfg["enc_dim"],
                dec_dim=cfg["dec_dim"], att_dim=cfg["att_dim"])

            def local(params, opt_state, batch):
                loss, grads = jax.value_and_grad(model.loss)(params, batch)
                params, opt_state = opt.update(params, grads, opt_state)
                return loss, params, opt_state

            return jax.jit(jax.shard_map(
                local, mesh=mesh, in_specs=(P(), P(), P("data")),
                out_specs=P(), check_vma=False)), opt, mesh

        return build

    line = _measure(tiny_cell(), _broken(monkeypatch, _runner(), rebuild))
    assert line["correct"] is False
    compared = [json.loads(l) for l in capsys.readouterr().out.splitlines()
                if l.startswith('{"compared"')][-1]["compared"]
    assert compared["replica_param_gap"]["value"] > 0
    assert compared["replica_param_gap"]["ok"] is False


# -- the readers of the layer ``parallel`` -----------------------------------


def _read(name, facts):
    read, args = manifest.layer_metric_reader(name)
    return read(facts, **args)


def test_collective_names():
    c = trace_chips.collective
    assert c("%all-reduce.29 = (f32[], f32[512,512]{1,0}) all-reduce(%a, %b), "
             "channel_id=3") == ("all-reduce", "")
    assert c("%all-gather-start.2 = (s32[8]) all-gather-start(%p)") == (
        "all-gather", "-start")
    assert c("%ar-done = f32[2] all-reduce-done(%all-reduce-start.1)") == (
        "all-reduce", "-done")
    assert c("%async-collective-start = (s32[12288]) fusion(%reshape.290), "
             "kind=kCustom, calls=%fused_computation.568") == (
        "async-collective", "-start")
    assert c("%async-collective-done.3") == ("async-collective", "-done")
    assert c("%reduce-scatter.1 = f32[4] reduce-scatter(%x)")[0] == \
        "reduce-scatter"
    assert c("%collective-permute.4")[0] == "collective-permute"
    # an operand called after a collective does not make a fusion one
    assert c("%fusion.3 = f32[] fusion(%all-reduce.2), kind=kLoop") is None
    assert c("%convolution_tanh_fusion") is None


def test_chip_times_on_events_written_by_hand():
    """A ``while`` holding a synchronous all-reduce and a fusion; then an
    all-gather in flight behind a fusion; the window cuts the last event."""
    events = [
        (0.0, 30.0, "%while.1 = (f32[]) while(%t)"),
        (0.0, 10.0, "%all-reduce.2 = f32[4] all-reduce(%x), channel_id=1"),
        (10.0, 28.0, "%fusion.1 = f32[4] fusion(%all-reduce.2), kind=kLoop"),
        (40.0, 41.0, "%all-gather-start.1 = (s32[8]) all-gather-start(%p)"),
        (41.0, 50.0, "%fusion.2 = f32[4] fusion(%y), kind=kLoop"),
        (50.0, 54.0, "%all-gather-done.1 = s32[8] all-gather-done(%s)"),
        (60.0, 80.0, "%async-collective-done.7"),
    ]
    got = trace_chips.chip_times(events, (0.0, 70.0))
    assert got["busy_ns"] == 30.0 + 14.0 + 10.0
    assert got["by_kind"] == {"all-reduce": 10.0, "all-gather-start": 1.0,
                              "all-gather-done": 4.0,
                              "async-collective-done": 10.0}
    assert got["collective_ns"] == 25.0
    assert got["exposed_ns"] == 24.0          # all of it but the start
    assert trace_chips.chip_times(events[2:3], (0.0, 70.0))[
        "collective_ns"] == 0.0


def test_readers_mean_over_chips_and_skew_between_them():
    def chip(busy, coll, start):
        by_kind = {"all-reduce": coll - start, "all-gather-start": start}
        return {"busy_ns": busy, "collective_ns": coll, "by_kind": by_kind,
                "exposed_ns": trace_chips.exposed_ns_of(by_kind)}

    chips = [chip(90e6, 8e6, 1e6), chip(100e6, 10e6, 1e6),
             chip(110e6, 12e6, 1e6), chip(100e6, 10e6, 1e6)]
    facts = {"_trace_chips": {"chips": chips}, "steps": 2}
    assert _read("device_ms_per_step.collectives", facts) == \
        pytest.approx(5.0)
    assert _read("collective_exposed_ms_per_step", facts) == \
        pytest.approx(4.5)
    assert _read("chip_busy_skew_pct", facts) == pytest.approx(20.0)
    # nothing to read is None, never 0: one chip; no collective; no trace
    one = {"_trace_chips": {"chips": chips[:1]}, "steps": 2}
    none = {"_trace_chips": {"chips": [chip(90e6, 0.0, 0.0)] * 4}, "steps": 2}
    for name in NEW:
        assert _read(name, one) is None
        assert _read(name, {"_trace_chips": None, "steps": 2}) is None
    assert _read("device_ms_per_step.collectives", none) is None
    assert _read("collective_exposed_ms_per_step", none) is None
    assert _read("chip_busy_skew_pct", none) == 0.0


@pytest.mark.parametrize("metric", NEW)
def test_new_reader_finds_nothing_in_a_trace_of_one_chip(metric):
    assert _read(metric, {"xplane": ONE_PLANE, "steps": 7}) is None


@pytest.mark.parametrize("metric", [m for m in SCOPES if "optimizer" not in m])
def test_seqtoseq_scope_metric_finds_nothing_in_the_lstm_trace(metric):
    """What test_trace_scopes.py asserts of the metrics that list the
    one-chip seqToseq cell alone, for the three that now list two cells."""
    assert _read(metric, {"xplane": ONE_PLANE, "steps": 7}) is None


def _recorded():
    with open(os.path.join(HERE, "dp4.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def four_planes(tmp_path_factory):
    """The recorded trace unpacked: ``ProfileData`` reads a file."""
    path = str(tmp_path_factory.mktemp("dp4") / "dp4.xplane.pb")
    with gzip.open(FOUR_PLANES_GZ, "rb") as src, open(path, "wb") as dst:
        dst.write(src.read())
    return path


@pytest.fixture(scope="module")
def dp4_facts(four_planes):
    return {"xplane": four_planes, "steps": _recorded()["steps"]}


def test_recorded_trace_reduces_chip_by_chip_and_to_the_mean(four_planes):
    parsed = trace_chips.parse(four_planes)
    chips = parsed["chips"]
    assert [c["plane"] for c in chips] == [
        f"/device:TPU:{i}" for i in range(CHIPS)]
    assert all(c["busy_ns"] > 0 and c["collective_ns"] > 0 for c in chips)
    assert all(0 < c["exposed_ns"] <= c["collective_ns"] <= c["busy_ns"]
               for c in chips)
    # the partitioner's collectives, under the names the trace gives them
    kinds = set().union(*(c["by_kind"] for c in chips))
    assert "all-reduce" in kinds
    # the summary every other metric reads is the mean over the planes,
    # not their sum or their union
    summary = trace_reduce.reduce_trace(four_planes,
                                        window_span="bench.window")
    assert summary["devices"] == CHIPS
    mean_busy = sum(c["busy_ns"] for c in chips) / CHIPS
    assert summary["busy_s"] * 1e9 == pytest.approx(mean_busy)
    assert summary["busy_s"] <= summary["window_s"]
    assert max(c["busy_ns"] for c in chips) <= summary["window_s"] * 1e9
    # every Pallas gate is closed; what is_kernel still finds is XLA's own
    # bare ``custom-call`` (a buffer allocation), nanoseconds a step
    assert summary["kernel_s"] < 1e-3 * summary["busy_s"]
    assert all(n.startswith("%custom-call") for n, _ in
               summary["kernel_ops"])


@pytest.mark.parametrize("metric", NEW + SCOPES)
def test_dp4_metric_reads_on_the_slimmed_trace_what_it_read_on_the_chip(
        metric, dp4_facts):
    value = _read(metric, dp4_facts)
    assert value is not None and value >= 0
    assert value == pytest.approx(_recorded()["metrics"][metric]["value"])
