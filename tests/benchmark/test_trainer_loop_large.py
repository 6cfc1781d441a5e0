"""The runner ``trainer_loop_large`` and the cell it serves
(``lfm2moe-train-b1-t8192``) on the CPU at a toy size: the manifest finds the
cell's files, a sound program is ``correct``, the fp8 control is not, a step
that returns its state unchanged is not, a program that leaves a routed
assignment uncomputed is not; and the readers this PR adds (expert load,
roofline shares) on facts written by hand."""

import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import correct, manifest  # noqa: E402

CELL = "lfm2moe-train-b1-t8192"
FAKE_TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
#: hidden 64, 8 experts with 2 held, top 2, 5 layers, T 64: the widths are
#: toys, the code path (runner, reference, comparison, result line) the cell's
TINY_CONFIG = dict(hidden_size=64, num_attention_heads=4,
                   num_key_value_heads=2, head_dim=16, intermediate_size=96,
                   moe_intermediate_size=48, router_outputs=8, num_experts=2,
                   num_experts_per_tok=2, vocab_size=50)
TINY_TRAFFIC = dict(batch=2, seq_len=64, ring=4)


def tiny_cell():
    """The cell at a toy size, with limits read at that size on the CPU by
    the rule the chip's were (``check_correct.suggest_limits``: 8 sound
    seeds, 4 control seeds)."""
    cell = copy.deepcopy(manifest.cell(CELL))
    cell["config"].update(TINY_CONFIG)
    cell["traffic"].update(TINY_TRAFFIC)
    with open(os.path.join(HERE, "tiny_limits_lfm2.json")) as f:
        cell["limits"] = json.load(f)
    return cell


@pytest.fixture
def policy(monkeypatch):
    """The precision policy the configuration states, set the way run.py
    sets it and put back after."""
    from paddle_tpu.utils.flags import FLAGS

    for flag in ("dtype", "compute_dtype", "amp", "prefetch_depth",
                 "guard_nonfinite", "obs_timeline", "save_dir", "log_period"):
        monkeypatch.setattr(FLAGS, flag, getattr(FLAGS, flag))
    FLAGS.dtype, FLAGS.compute_dtype, FLAGS.amp = "float32", "bfloat16", False
    return FLAGS


def _measure(cell, seed=3, seconds=0.3):
    from benchmark import run

    return run.measure(cell, manifest.reference(cell["config"]),
                       manifest.runner(cell["traffic"]["runner"]), seed,
                       seconds, 0, FAKE_TPU)


def test_cell_files_exist_and_hold_the_published_widths():
    cell = manifest.cell(CELL)
    cfg = cell["config"]
    assert callable(manifest.runner(cell["traffic"]["runner"]).run)
    ref = manifest.reference(cfg)
    assert callable(manifest.program(cfg).trainer)
    # every published width, the router's outputs and experts a token
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["conv_L_cache"],
            cfg["router_outputs"], cfg["num_experts_per_tok"]) == (
        2048, 11776, 1536, 32, 8, 64, 3, 64, 4)
    entry = next(c for c in manifest.benchmark_json()["configs"]
                 if c["name"] == "lfm2-24b-a2b-ep8")
    assert sorted(entry["reduced"]) == sorted(cfg["published"])
    shapes = ref.param_shapes(cfg)
    count = sum(int(__import__("numpy").prod(s)) for s, _ in shapes.values())
    assert count == 469_285_248
    assert all(std is not None for _, std in shapes.values())
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tokens_per_s",
                                                      "setup_s"}
    assert {m["name"] for m in cell["per_layer"]} == {
        "compile_s", "mfu_pct", "pallas_share_pct.train",
        "device_idle_pct.train", "device_ms_per_step.moe",
        "device_ms_per_step.moe_dispatch", "device_ms_per_step.attention",
        "device_ms_per_step.short_conv", "moe_load_max_over_mean",
        "roofline_pct.moe_experts", "roofline_pct.attention"}
    assert set(cell["limits"]) >= {
        "loss_gap", "delta_norm_gap", "nonfinite_losses", "bad_steps",
        "compiles_in_window", "uncomputed_assignments", "grad_diff_median"}
    assert {"grad_diff." + k for k in shapes} == {
        k for k in cell["limits"] if k.startswith("grad_diff.")}
    assert all(cell["limits"][k] == 0 for k in (
        "nonfinite_losses", "bad_steps", "compiles_in_window",
        "uncomputed_assignments"))
    per_token = ref.forward_flops_per_token(cfg, 8192)
    assert 1.21e9 < 3 * sum(per_token.values()) < 1.23e9    # ISSUE 28's figure
    assert ref.step_flops(cfg, cell["traffic"]) == pytest.approx(
        3 * sum(per_token.values()) * 8192)


def test_sound_run_is_correct_and_reports_the_cells_metrics(policy, capsys):
    line = _measure(tiny_cell())
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    out = capsys.readouterr().out.splitlines()
    compared = [json.loads(l) for l in out if l.startswith('{"compared"')]
    assert compared and all(v["ok"] for v in compared[-1]["compared"].values())
    assert compared[-1]["compared"]["uncomputed_assignments"]["value"] == 0
    noted = [json.loads(l) for l in out if '"expert_load"' in l]
    assert sorted(noted[-1]["expert_load"]) == ["moe1", "moe2", "moe3", "moe4"]


def test_lower_precision_control_is_not_correct(policy):
    """The control: the plain reference in the program's place with fp8
    operands.  It has to fail a limit; the program must not."""
    cell = tiny_cell()
    runner = manifest.runner(cell["traffic"]["runner"])
    ref = manifest.reference(cell["config"])
    seed = 2 ** 31 + 5
    sound = runner.correct_numbers(cell, ref, seed)
    control = runner.correct_numbers(cell, ref, seed, control=True)
    held = {k: v for k, v in cell["limits"].items() if k in control}
    assert correct.judge(sound, {k: v for k, v in cell["limits"].items()
                                 if k in sound})
    assert not correct.judge(control, held)


def test_step_that_returns_its_state_unchanged_is_not_correct(policy,
                                                              monkeypatch):
    """A trainer whose step computes its loss and keeps its parameters."""
    from paddle_tpu.trainer import SGDTrainer

    real = SGDTrainer.train_batch

    def lazy(self, feed):
        params = self.params
        self.params = {k: v + 0 for k, v in params.items()}   # donated copy
        loss = real(self, feed)
        self.params = params
        return loss

    monkeypatch.setattr(SGDTrainer, "train_batch", lazy)
    assert _measure(tiny_cell())["correct"] is False


def test_uncomputed_assignment_is_not_correct(policy, monkeypatch):
    """One routed assignment the program owns up to not computing."""
    cell = tiny_cell()
    program = manifest.program(cell["config"])
    monkeypatch.setattr(program, "uncomputed_assignments", lambda: 1.0)
    monkeypatch.setattr(manifest, "program", lambda config: program)
    assert _measure(cell)["correct"] is False


# -- the readers ------------------------------------------------------------------


def test_expert_load_reader():
    read, _ = manifest.layer_metric_reader("moe_load_max_over_mean")
    assert read({}) is None and read({"expert_load": {}}) is None
    assert read({"expert_load": {"moe1": [10, 10], "moe2": [30, 10]}}) == 1.5


@pytest.mark.parametrize("metric,ms,lo,hi", [
    ("roofline_pct.attention", 10.0, 40.0, 60.0),
    ("roofline_pct.moe_experts", 12.0, 40.0, 70.0)])
def test_roofline_readers_count_what_the_issue_says(metric, ms, lo, hi,
                                                    monkeypatch):
    """At the cell's sizes and even routing (512 assignments an expert held a
    step), a scope that takes ``ms`` reads between ``lo`` and ``hi`` percent;
    no scope or no counter reads nothing."""
    from benchmark import trace_scopes

    read, args = manifest.layer_metric_reader(metric)
    cell = manifest.cell(CELL)
    steps = 10
    facts = {"config": cell["config"], "traffic": cell["traffic"],
             "steps": steps, "peaks": manifest.peaks("TPU v5 lite"),
             "expert_load": {f"moe{i}": [512 * steps] * 8 for i in (1, 2, 3, 4)},
             "_trace_scopes": {"devices": 1}}
    monkeypatch.setattr(trace_scopes, "scope_ns",
                        lambda parsed, scopes: ms * 1e6 * steps)
    assert lo < read(facts, **args) < hi
    monkeypatch.setattr(trace_scopes, "scope_ns", lambda parsed, scopes: None)
    assert read(facts, **args) is None
    assert read({"_trace_scopes": None}, **args) is None


def test_roofline_work_is_an_under_count():
    """Attention: the elements at or under the diagonal, fewer than the
    blocks any kernel runs; experts: the assignments routed, no padding."""
    roofline = manifest.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "readers", "roofline.py"), "rf")
    cell = manifest.cell(CELL)
    ops, _ = roofline.attention_work(cell["config"], cell["traffic"])
    blocks_1024 = 8 * 9 / 2 * 1024 * 1024           # the kernels' block pairs
    assert ops < 32 * blocks_1024 * 11 * 2 * 64     # 2 + 2 + 7 products run
    ops, nbytes = roofline.moe_experts_work(
        cell["config"], {"moe1": [512] * 8}, 1)
    assert ops == 4096 * 12 * 2 * 2048 * 1536
    assert nbytes > 4 * 3 * 8 * 2048 * 1536 * 4
