"""The cell ``kanana2moe-train-b1-t8192`` (PR 35) through the runner
``trainer_loop_large`` on the CPU at a toy size: the manifest finds the
cell's files, the configuration holds the published widths and 575.9 M
parameters, a sound program is ``correct``, the fp8 control is not; and the
reader this PR adds (``roofline_mla``) on facts written by hand."""

import copy
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import correct, manifest  # noqa: E402

CELL = "kanana2moe-train-b1-t8192"
CONFIG = "kanana-2-30b-a3b-ep8"
FAKE_TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
#: hidden 64, 4 heads of 16 + 8 rotary with values of 16 from a latent of 32,
#: 8 experts of 48 with 2 held, top 3, 2 shared, 5 layers, T 64: the widths
#: are toys, the code path (runner, reference, comparison, result line) the
#: cell's
TINY_CONFIG = dict(hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
                   qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                   intermediate_size=96, moe_intermediate_size=48,
                   router_outputs=8, n_routed_experts=2,
                   num_experts_per_tok=3, vocab_size=50)
TINY_TRAFFIC = dict(batch=2, seq_len=64, ring=4)


def tiny_cell(limits=True):
    """The cell at a toy size, with limits read at that size on the CPU by
    the rule the chip's were (``check_correct.suggest_limits``: 8 sound
    seeds, 4 control seeds)."""
    cell = copy.deepcopy(manifest.cell(CELL))
    cell["config"].update(TINY_CONFIG)
    cell["traffic"].update(TINY_TRAFFIC)
    if limits:
        with open(os.path.join(HERE, "tiny_limits_kanana2.json")) as f:
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
    assert cell["chips"] == 1
    assert callable(manifest.runner(cell["traffic"]["runner"]).run)
    ref = manifest.reference(cfg)
    prog = manifest.program(cfg)
    assert all(callable(getattr(prog, f)) for f in (
        "require", "trainer", "expert_load", "uncomputed_assignments"))
    # every published width, the router's outputs, experts a token, shared
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["router_outputs"],
            cfg["num_experts_per_tok"], cfg["n_shared_experts"],
            cfg["routed_scaling_factor"], cfg["rope_theta"],
            cfg["rms_norm_eps"], cfg["q_lora_rank"]) == (
        2048, 6144, 768, 32, 512, 128, 64, 128, 128, 6, 2, 2.448, 1000000,
        1e-6, None)
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["first_expert"],
            cfg["vocab_size"]) == (5, 1, 16, 0, 16032)
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 128,
                                "vocab_size": 128256}
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    # what the runner reads is what the source's own keys say
    assert cfg["layer_types"] == ["latent_attention"] * 5
    assert cfg["num_dense_layers"] == cfg["first_k_dense_replace"]
    assert not cfg["tie_word_embeddings"] and not cfg["amp"]
    assert cfg["optimizer"]["learning_rate"] == 1e-4
    assert all(cfg.get(k) for k in ("deployment", "assumed", "why"))
    entry = next(c for c in manifest.benchmark_json()["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"]
    assert sorted(entry["reduced"]) == sorted(cfg["published"]) == sorted(
        ["num_hidden_layers", "n_routed_experts", "vocab_size"])
    shapes = ref.param_shapes(cfg)
    count = sum(int(np.prod(s)) for s, _ in shapes.values())
    assert count == 575_955_968                  # ISSUE 35's 575.9 M
    assert all(std is not None for _, std in shapes.values())
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tokens_per_s",
                                                      "setup_s"}
    assert {m["name"] for m in cell["per_layer"]} == {
        "compile_s", "mfu_pct", "pallas_share_pct.train",
        "device_idle_pct.train", "device_ms_per_step.mla",
        "device_ms_per_step.mla_proj", "device_ms_per_step.moe_shared",
        "device_ms_per_step.moe_routed", "roofline_pct.mla_core"}
    assert {"grad_diff." + k for k in shapes} == {
        k for k in cell["limits"] if k.startswith("grad_diff.")}
    assert set(cell["limits"]) >= {"loss_gap", "delta_norm_gap",
                                   "grad_diff_median"}
    assert all(cell["limits"][k] == 0 for k in (
        "nonfinite_losses", "bad_steps", "compiles_in_window",
        "uncomputed_assignments"))
    per_token = ref.forward_flops_per_token(cfg, 8192)
    # 254 M active parameters a token x 2 and the causal half of 32 heads'
    # 192 + 128 channels over 8192 positions, in 5 layers
    assert sum(per_token.values()) - per_token["mla_core"] == pytest.approx(
        2 * 255.3e6, rel=0.01)
    assert per_token["mla_core"] == 5 * 8192 * 32 * 320
    assert ref.step_flops(cfg, cell["traffic"]) == pytest.approx(
        3 * sum(per_token.values()) * 8192)


def test_new_metrics_are_this_cells_alone():
    """LFM2's cell keeps exactly its set: each metric this PR adds lists
    this cell and no other."""
    bj = manifest.benchmark_json()
    new = [m for m in bj["per_layer"] if CELL in m.get("workloads", [])]
    assert sorted(m["name"] for m in new) == [
        "device_ms_per_step.mla", "device_ms_per_step.mla_proj",
        "device_ms_per_step.moe_routed", "device_ms_per_step.moe_shared",
        "roofline_pct.mla_core"]
    assert all(m["workloads"] == [CELL]
               and m["moves"] == "train_tokens_per_s" for m in new)
    assert bj["workloads"][-1]["name"] == CELL
    assert bj["configs"][-1]["name"] == CONFIG
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1


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
    assert "grad_diff._cost.w" in compared[-1]["compared"]
    assert "grad_diff._moe1.shared_w2" in compared[-1]["compared"]
    noted = [json.loads(l) for l in out if '"expert_load"' in l]
    assert sorted(noted[-1]["expert_load"]) == ["moe1", "moe2", "moe3", "moe4"]
    assert all(len(v) == 2 for v in noted[-1]["expert_load"].values())


def test_lower_precision_control_is_not_correct(policy):
    """The control: the plain reference in the program's place with fp8
    operands.  It has to fail a limit; the program must not."""
    cell = tiny_cell()
    runner = manifest.runner(cell["traffic"]["runner"])
    ref = manifest.reference(cell["config"])
    seed = 2 ** 31 + 7
    sound = runner.correct_numbers(cell, ref, seed)
    control = runner.correct_numbers(cell, ref, seed, control=True)
    held = {k: v for k, v in cell["limits"].items() if k in control}
    assert correct.judge(sound, {k: v for k, v in cell["limits"].items()
                                 if k in sound})
    assert not correct.judge(control, held)


def test_parent_without_the_model_exits_at_once(monkeypatch):
    """A checkout whose program has no ``kanana2_moe_net`` (this PR's
    parent): ``require()`` exits with a message, before any weight."""
    import paddle_tpu.models as models

    prog = manifest.program(manifest.cell(CELL)["config"])
    prog.require()                               # this checkout: fine
    monkeypatch.delattr(models, "kanana2_moe_net")
    with pytest.raises(SystemExit, match="cannot run kanana-2-30b-a3b-ep8"):
        prog.require()


# -- the reader ------------------------------------------------------------------


def _facts(cell, steps):
    return {"config": cell["config"], "traffic": cell["traffic"],
            "steps": steps, "peaks": manifest.peaks("TPU v5 lite"),
            "_trace_scopes": {"devices": 1}}


def test_mla_roofline_reader_counts_what_the_issue_says(monkeypatch):
    """At the cell's sizes the cores' least time is 1.24e13 operations at
    197 TFLOP/s = 62.8 ms a step: a scope that takes 125.6 ms reads 50%; no
    scope, no trace or another configuration reads nothing."""
    from benchmark import trace_scopes

    read, args = manifest.layer_metric_reader("roofline_pct.mla_core")
    assert args == {"scopes": ["attn_core"]}
    cell = manifest.cell(CELL)
    steps = 10
    facts = _facts(cell, steps)
    monkeypatch.setattr(trace_scopes, "scope_ns",
                        lambda parsed, scopes: 125.6e6 * steps)
    assert read(facts, **args) == pytest.approx(50.0, rel=0.01)
    other = _facts(manifest.cell("lfm2moe-train-b1-t8192"), steps)
    assert read(other, **args) is None
    monkeypatch.setattr(trace_scopes, "scope_ns", lambda parsed, scopes: None)
    assert read(facts, **args) is None
    assert read({"_trace_scopes": None}, **args) is None


def test_mla_roofline_work_is_an_under_count():
    """2304 operations an element at or under the diagonal, per head and
    layer: fewer elements than the 1024-blocks the kernels run, and fewer
    products than they run (the backward recomputes scores and dp twice)."""
    mod = manifest.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "readers", "roofline_mla.py"),
        "rf_mla")
    cell = manifest.cell(CELL)
    ops, nbytes = mod.mla_core_work(cell["config"], cell["traffic"])
    assert ops == 5 * 32 * (8192 * 8193 / 2) * 2304
    assert 1.23e13 < ops < 1.25e13
    blocks_1024 = 8 * 9 / 2 * 1024 * 1024
    run = 2 * ((192 + 128) + (192 + 128 + 192) + (192 + 128 + 128 + 192))
    assert ops < 5 * 32 * blocks_1024 * run
    # q, k at 192 and v, o at 128 forward; those and their gradients backward
    assert nbytes == 5 * 8192 * 32 * 2 * (640 + 1280)
    assert nbytes / 819e9 < ops / 197e12      # the cores are compute-bound
