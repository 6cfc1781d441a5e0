"""The benchmark's own harness, on the CPU: the manifest, the traffic
generator, the trace reduction on a small trace recorded on a v5e, the refusal
to run without a TPU, and the two tests ``correct`` rests on: the
lower-precision control fails it, and so does a broken timed path."""

import copy
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, trace_reduce, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
FAKE_TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

#: each cell cut to what a test can hold: the widths are toys, the code path
#: (runner, reference, comparison, result line) is the cell's own
TINY = {
    "seq2seq-train-b384-s96": (
        dict(src_vocab=211, trg_vocab=211, emb_dim=32, enc_dim=32,
             dec_dim=32, att_dim=32),
        dict(batch=8, src_len=6, trg_len=6, reference_rows_per_block=4,
             warmup_steps=1, ring=4)),
    "lstm-trainer-b256-t640": (
        dict(vocab=211, emb_dim=16, hid_dim=32),
        dict(batch=8, seq_len=10, lengths={"lo": 5, "hi": 10},
             reference_rows_per_block=4, ring=4)),
}
CELLS = sorted(TINY)


def tiny_cell(name):
    """The cell at a toy size, with limits read at that size on the CPU by
    the rule the chip's were (``check_correct.suggest_limits``: 8 sound
    seeds, 4 control seeds)."""
    cell = copy.deepcopy(manifest.cell(name))
    cell["config"].update(TINY[name][0])
    cell["traffic"].update(TINY[name][1])
    with open(os.path.join(HERE, "tiny_limits.json")) as f:
        cell["limits"] = json.load(f)[name]
    return cell


@pytest.fixture
def policy(monkeypatch):
    """The precision policy the configurations state (bf16 operands, f32
    everything else), set the way run.py sets it and put back after."""
    from paddle_tpu.utils.flags import FLAGS

    for flag in ("dtype", "compute_dtype", "amp", "prefetch_depth",
                 "guard_nonfinite", "obs_timeline", "save_dir", "log_period"):
        monkeypatch.setattr(FLAGS, flag, getattr(FLAGS, flag))
    FLAGS.dtype, FLAGS.compute_dtype, FLAGS.amp = "float32", "bfloat16", False
    return FLAGS


# -- manifest ---------------------------------------------------------------


def test_manifest_names_units_and_files():
    bj = manifest.benchmark_json()
    assert sorted(bj) == ["command", "configs", "end_to_end", "paths",
                          "per_layer", "run_seconds", "workloads"]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bj[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names), names
    for m in bj["end_to_end"] + bj["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in bj["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in bj["workloads"]}
    for m in bj["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", [])) <= cells
        read, _ = manifest.layer_metric_reader(m["name"])
        assert callable(read)
    for c in bj["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert any(c["file"].startswith(p + "/") for p in bj["paths"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_exist(name):
    cell = manifest.cell(name)
    assert callable(manifest.runner(cell["traffic"]["runner"]).run)
    ref = manifest.reference(cell["config"])
    assert set(ref.param_shapes(cell["config"]))
    assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}
    assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
    assert set(cell["limits"]) >= {"loss_gap",
                                   "delta_norm_gap", "nonfinite_losses",
                                   "bad_steps", "compiles_in_window"}
    # every leaf's first gradient is held, not a choice of them
    leaves = {"grad_diff." + k for k in ref.param_shapes(cell["config"])}
    assert leaves | {"grad_diff_median"} == {
        k for k in cell["limits"] if k.startswith("grad_diff")}
    assert callable(ref.batch) and callable(ref.real_tokens)
    assert ref.step_flops(cell["config"], cell["traffic"]) > 0
    prog = manifest.program(cell["config"])
    assert callable(getattr(prog, {"train_step": "train_step",
                                   "trainer_loop": "trainer"}[
        cell["traffic"]["runner"]]))


def test_unknown_device_kind_has_no_peaks():
    assert manifest.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        manifest.peaks("TPU v99")


# -- traffic ------------------------------------------------------------------


@pytest.mark.parametrize("name", CELLS)
def test_traffic_is_seeded_and_seed_keeps_the_work(name):
    cell = tiny_cell(name)
    cfg, tr = cell["config"], cell["traffic"]
    ref = manifest.reference(cfg)
    a = traffic.batches(ref, cfg, tr, 2 ** 31 + 11, 3)
    b = traffic.batches(ref, cfg, tr, 2 ** 31 + 11, 3)
    c = traffic.batches(ref, cfg, tr, 7, 3)
    import jax

    flat = lambda bs: [np.asarray(x) for x in jax.tree_util.tree_leaves(bs)]  # noqa: E731
    assert all(np.array_equal(x, y) for x, y in zip(flat(a), flat(b)))
    assert any(not np.array_equal(x, y) for x, y in zip(flat(a), flat(c)))
    assert not np.array_equal(flat(a[0])[0], flat(a[1])[0])   # batches differ
    # another seed, the same amount of work
    assert ([ref.real_tokens(x) for x in a]
            == [ref.real_tokens(x) for x in c])


# -- trace reduction ----------------------------------------------------------


def test_union_and_self_times():
    assert trace_reduce.union_intervals([(5, 7), (0, 2), (1, 3)]) == \
        [[0, 3], [5, 7]]
    events = [(0.0, 10.0, "while"), (1.0, 4.0, "fusion"),
              (5.0, 9.0, "fusion"), (12.0, 13.0, "copy")]
    own = trace_reduce.self_times(events)
    assert own == {"while": 3.0, "fusion": 7.0, "copy": 1.0}


def test_reduce_recorded_trace():
    """A trace of four dispatches of one small jitted program, recorded on a
    TPU v5e (PR 23): one device, busy well under the window, the
    benchmark's own spans own the idle gaps."""
    path = os.path.join(HERE, "small.xplane.pb")
    s = trace_reduce.reduce_trace(path)
    assert s["devices"] == 1
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["device_ops"] and len(s["device_ops"]) <= 10
    assert abs(sum(t for _, t in s["device_ops"]) - s["busy_s"]) \
        <= 0.05 * s["busy_s"] + 1e-9
    owners = {n for n, _ in s["idle_gaps"]}
    assert owners & {"bench.dispatch_step", "bench.loss_fetch"}
    assert 0.0 <= s["kernel_s"] <= s["busy_s"]
    w0 = s["t0_ns"] + 0.5e9 * s["window_s"]
    half = trace_reduce.reduce_trace(path, window=(w0, w0 + 1e9))
    assert 0 < half["busy_s"] < s["busy_s"]
    # a host span as the window: idle at its two ends counts, and the span
    # itself owns no gap
    spanned = trace_reduce.reduce_trace(path,
                                        window_span="bench.dispatch_step")
    assert spanned["window_s"] > 0 and spanned["busy_s"] <= s["busy_s"]
    assert "bench.dispatch_step" not in {n for n, _ in spanned["idle_gaps"]}
    with pytest.raises(ValueError):
        trace_reduce.reduce_trace(path, window_span="bench.no_such_span")


# -- the entry point ------------------------------------------------------------


def test_run_without_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert "correct" not in out.stdout and "metrics" not in out.stdout


# -- correct ----------------------------------------------------------------------


def _measure(cell, seed=3, seconds=0.3):
    from benchmark import run

    return run.measure(cell, manifest.reference(cell["config"]),
                       manifest.runner(cell["traffic"]["runner"]), seed,
                       seconds, 0, FAKE_TPU)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct_and_reports_the_cells_metrics(name, policy,
                                                            capsys):
    line = _measure(tiny_cell(name))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    assert line["device"]["kind"] == "TPU v5 lite"
    compared = [json.loads(l) for l in capsys.readouterr().out.splitlines()
                if l.startswith('{"compared"')]
    assert compared and all("limit" in v for v in
                            compared[-1]["compared"].values())


@pytest.mark.parametrize("name", CELLS)
def test_lower_precision_control_is_not_correct(name, policy):
    """The control: the plain reference in the program's place with fp8
    operands, the precision below the bf16 operands the configuration
    states.  It has to fail a limit; the program must not."""
    from benchmark import correct

    cell = tiny_cell(name)
    runner = manifest.runner(cell["traffic"]["runner"])
    ref = manifest.reference(cell["config"])
    seed = 2 ** 31 + 5
    sound = runner.correct_numbers(cell, ref, seed)
    control = runner.correct_numbers(cell, ref, seed, control=True)
    held = {k: v for k, v in cell["limits"].items() if k in control}
    assert correct.judge(sound, held)
    assert not correct.judge(control, held)


def test_limit_rule():
    from benchmark.check_correct import suggest_limits

    got = suggest_limits(
        {"a": 0.01, "b": 0.01, "c": 0.01, "loss_gap": 0.002},
        {"a": 0.09, "b": 0.02, "c": 0.036, "loss_gap": 0.003})
    # every number is held: 3 x the sound runs' largest, or the control's
    # smallest / 1.5 where the control sits closer than 4.5 x
    assert got == {"a": 0.03, "b": 0.03, "c": 0.024, "loss_gap": 0.006}


def test_broken_train_step_is_not_correct(policy, monkeypatch):
    """A step that computes its loss and returns its state unchanged."""
    cell = tiny_cell("seq2seq-train-b384-s96")
    program = manifest.program(cell["config"])
    real = program.train_step

    def broken(cfg):
        step, opt = real(cfg)

        def lazy(params, opt_state, batch):
            loss, _, _ = step(params, opt_state, batch)
            return loss, params, opt_state

        return lazy, opt

    monkeypatch.setattr(program, "train_step", broken)
    monkeypatch.setattr(manifest, "program", lambda config: program)
    assert _measure(cell)["correct"] is False


def test_trainer_that_drops_half_the_batch_is_not_correct(policy,
                                                          monkeypatch):
    """The trainer fed only the first half of every batch's rows."""
    import jax

    from paddle_tpu.trainer import SGDTrainer

    real = SGDTrainer.train_batch

    def half(self, feed):
        rows = jax.tree_util.tree_leaves(feed)[0].shape[0] // 2
        return real(self, jax.tree_util.tree_map(lambda a: a[:rows], feed))

    monkeypatch.setattr(SGDTrainer, "train_batch", half)
    assert _measure(tiny_cell("lstm-trainer-b256-t640"))["correct"] is False
