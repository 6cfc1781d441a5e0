"""The cell ``nemotron3nano-train-b1-t4096`` (PR 43) through the runner
``trainer_loop_large`` on the CPU at a toy size: the manifest finds the
cell's files, the configuration holds the published widths and 666.96 M
parameters, a sound program is ``correct``, the fp8 control is not; and the
reader this PR adds (``roofline_nemotron``) on facts written by hand.

Where this file says where the cell's entries stand in ``BENCHMARK.json`` it
says so RELATIVE to their neighbours (after Qwen3-Next's, in their own
order), never as "the last": the next PR that adds a cell appends after them
(tests/conftest.py ``OUTDATED_PINS`` has the three pins that said "last")."""

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

CELL = "nemotron3nano-train-b1-t4096"
CONFIG = "nemotron-3-nano-30b-a3b-ep16"
QWEN3NEXT = "qwen3next-train-b1-t8192"
FAKE_TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
#: in the order BENCHMARK.json has them
NEW_METRICS = ["device_ms_per_step.mamba", "device_ms_per_step.mamba_proj",
               "device_ms_per_step.ssd_scan", "kernel_ms_per_step.ssd",
               "roofline_pct.ssd_scan", "device_ms_per_step.moe_relu2",
               "roofline_pct.moe_relu2"]
QWEN3NEXT_METRICS = ["device_ms_per_step.gdn", "device_ms_per_step.gdn_proj",
                     "device_ms_per_step.gdn_scan",
                     "device_ms_per_step.gated_attn",
                     "kernel_ms_per_step.gdn", "roofline_pct.gdn_scan"]
#: hidden 64; mixers of 4 heads of 16 in 2 groups with a state of 32;
#: attention of 4 heads of 16 over 2 key-value heads; 8 experts of 48 with 2
#: held, top 3, a shared expert of 32; the cell's nine layers, T 256 (two
#: chunks of the scan): the widths are toys, the code path (runner,
#: reference, comparison, result line) the cell's
TINY_CONFIG = dict(hidden_size=64, mamba_num_heads=4, mamba_head_dim=16,
                   n_groups=2, ssm_state_size=32, num_attention_heads=4,
                   num_key_value_heads=2, head_dim=16,
                   moe_intermediate_size=48,
                   moe_shared_expert_intermediate_size=32, router_outputs=8,
                   n_routed_experts=2, num_experts_per_tok=3, vocab_size=50)
TINY_TRAFFIC = dict(batch=2, seq_len=256, ring=4)
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072}


def tiny_cell(limits=True):
    """The cell at a toy size, with limits read at that size on the CPU by
    the rule the chip's were (``check_correct.suggest_limits``: 8 sound
    seeds, 4 control seeds)."""
    cell = copy.deepcopy(manifest.cell(CELL))
    cell["config"].update(TINY_CONFIG)
    cell["traffic"].update(TINY_TRAFFIC)
    if limits:
        with open(os.path.join(HERE, "tiny_limits_nemotron.json")) as f:
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
    cfg, tr = cell["config"], cell["traffic"]
    assert cell["chips"] == 1
    assert callable(manifest.runner(tr["runner"]).run)
    assert (tr["batch"], tr["seq_len"], tr["lengths"], tr["ring"],
            tr["prefetch_depth"]) == (1, 4096, "full", 8, 2)
    ref = manifest.reference(cfg)
    prog = manifest.program(cfg)
    assert all(callable(getattr(prog, f)) for f in (
        "require", "trainer", "expert_load", "uncomputed_assignments"))
    # every published width, the router's outputs, experts a token
    assert (cfg["hidden_size"], cfg["mamba_num_heads"], cfg["mamba_head_dim"],
            cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"],
            cfg["chunk_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["router_outputs"], cfg["num_experts_per_tok"],
            cfg["routed_scaling_factor"], cfg["layer_norm_epsilon"],
            cfg["mlp_hidden_act"]) == (
        2688, 64, 64, 8, 128, 4, 128, 32, 2, 128, 1856, 3712, 128, 6, 2.5,
        1e-5, "relu2")
    assert (cfg["num_hidden_layers"], cfg["hybrid_override_pattern"],
            cfg["n_routed_experts"], cfg["first_expert"],
            cfg["vocab_size"]) == (9, "MEMEM*EME", 8, 0, 16384)
    assert cfg["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "hybrid_override_pattern": PUBLISHED["hybrid_override_pattern"],
        "vocab_size": 131072}
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["n_routed_experts"] * 16 == cfg["published"][
        "n_routed_experts"]
    # the cut is the pattern's own first nine layers
    assert cfg["published"]["hybrid_override_pattern"].startswith(
        cfg["hybrid_override_pattern"])
    assert [cfg["published"]["hybrid_override_pattern"].count(c)
            for c in "ME*"] == [23, 23, 6]
    # what the runner reads is what the source's own keys say
    kinds = {"mamba": "mamba", "attn": "attention", "moe": "moe"}
    assert cfg["layer_types"] == [kinds[k] for k in ref.layer_kinds(cfg)] == [
        "mamba", "moe", "mamba", "moe", "mamba", "attention", "moe", "mamba",
        "moe"]
    assert cfg["num_dense_layers"] == 0
    assert not cfg["tie_word_embeddings"] and not cfg["amp"]
    assert cfg["optimizer"]["learning_rate"] == 1e-5
    assert cfg["recompute_layers"] == list(range(9))
    assert all(cfg.get(k) for k in ("deployment", "assumed", "why"))
    entry = next(c for c in manifest.benchmark_json()["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"]
    assert sorted(entry["reduced"]) == sorted(cfg["published"]) == sorted(
        ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
         "vocab_size"])
    shapes = ref.param_shapes(cfg)
    count = sum(int(np.prod(s)) for s, _ in shapes.values())
    assert count == 666_963_456                  # ISSUE 43's arithmetic
    mamba = sum(int(np.prod(s)) for k, (s, _) in shapes.items()
                if k.startswith("_mamba0."))
    attn = sum(int(np.prod(s)) for k, (s, _) in shapes.items()
               if k.startswith("_attn5."))
    moe = sum(int(np.prod(s)) for k, (s, _) in shapes.items()
              if k.startswith("_moe1."))
    assert (mamba, attn, moe) == (38_744_896 - 2688, 23_399_040 - 2688,
                                  100_125_440 - 2688)   # less the layer's norm
    assert all(std is not None for _, std in shapes.values())
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tokens_per_s",
                                                      "setup_s"}
    assert {m["name"] for m in cell["per_layer"]} == {
        "compile_s", "mfu_pct", "pallas_share_pct.train",
        "device_idle_pct.train", *NEW_METRICS}
    assert {"grad_diff." + k for k in shapes} == {
        k for k in cell["limits"] if k.startswith("grad_diff.")}
    assert set(cell["limits"]) >= {"loss_gap", "delta_norm_gap",
                                   "grad_diff_median"}
    assert all(cell["limits"][k] == 0 for k in (
        "nonfinite_losses", "bad_steps", "compiles_in_window",
        "uncomputed_assignments"))
    per_token = ref.forward_flops_per_token(cfg, 4096)
    # the four state-space layers are about 45% of a token's arithmetic
    # (ISSUE 43), nearly all of it their two projections
    share = (per_token["mamba_proj"] + per_token["ssd_scan"]) / sum(
        per_token.values())
    assert share == pytest.approx(0.47, abs=0.03)
    assert per_token["ssd_scan"] == 4 * 64 * 4 * 64 * 128
    assert per_token["attn_core"] == 4096 * 32 * 256
    # two matrices an expert: 4 x D x F a token and assignment, 6 x 8 / 128
    # assignments a token expected, four expert layers
    assert per_token["experts"] == 4 * (6 * 8 / 128) * 4 * 2688 * 1856
    assert per_token["shared_expert"] == 4 * 4 * 2688 * 3712
    assert sum(per_token.values()) == pytest.approx(679e6, rel=0.001)
    assert ref.step_flops(cfg, tr) == pytest.approx(
        3 * sum(per_token.values()) * 4096)


def test_every_number_of_the_catalog_entry_is_in_the_file():
    """The source's keys under their own names; the four reduced ones differ
    and nothing else does."""
    cfg = manifest.cell(CELL)["config"]
    differ = sorted(k for k, v in PUBLISHED.items() if cfg[k] != v)
    assert differ == ["hybrid_override_pattern", "n_routed_experts",
                      "num_hidden_layers", "vocab_size"]
    assert all(cfg["published"][k] == PUBLISHED[k] for k in differ)


def test_new_metrics_are_this_cells_alone_and_follow_qwen3nexts():
    """Each metric this PR adds lists this cell and no other; the older
    cells keep exactly their sets.  Positions are RELATIVE: this cell's
    entries follow Qwen3-Next's directly, in their own order; nothing here
    says they are the last."""
    bj = manifest.benchmark_json()
    new = [m for m in bj["per_layer"] if CELL in m.get("workloads", [])]
    assert [m["name"] for m in new] == NEW_METRICS
    assert all(m["workloads"] == [CELL]
               and m["moves"] == "train_tokens_per_s"
               and m["source"] == "device_trace" for m in new)
    assert all(m["unit"] == ("%" if m["name"].startswith("roofline_pct.")
                             else "ms") for m in new)
    names = [m["name"] for m in bj["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + 7] == NEW_METRICS
    # what PR 41's pin still holds of: Qwen3-Next's six, in their order,
    # directly before this cell's seven, and its alone
    assert names[at - 6:at] == QWEN3NEXT_METRICS
    assert all(bj["per_layer"][i]["workloads"] == [QWEN3NEXT]
               for i in range(at - 6, at))
    cells = [w["name"] for w in bj["workloads"]]
    assert cells.index(CELL) == cells.index(QWEN3NEXT) + 1
    configs = [c["name"] for c in bj["configs"]]
    assert configs.index(CONFIG) == configs.index(
        "qwen3-next-80b-a3b-ep32") + 1
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1
    for cell in cells:
        held = {m["name"] for m in manifest.cell(cell)["per_layer"]}
        assert (set(NEW_METRICS) <= held) == (cell == CELL)
        assert cell == CELL or not set(NEW_METRICS) & held
        assert (set(QWEN3NEXT_METRICS) <= held) == (cell == QWEN3NEXT)


def test_new_metrics_resolve_to_their_readers():
    want = {
        "device_ms_per_step.mamba": ("scope_ms", {"scopes": [
            "mamba0", "mamba2", "mamba4", "mamba7"]}),
        "device_ms_per_step.mamba_proj": ("scope_ms",
                                          {"scopes": ["mamba_proj"]}),
        "device_ms_per_step.ssd_scan": ("scope_ms", {"scopes": ["ssd_scan"]}),
        "kernel_ms_per_step.ssd": ("kernel_ms", {"kernels": [
            "ssd_chunk_fwd", "ssd_chunk_bwd"]}),
        "roofline_pct.ssd_scan": ("roofline_nemotron", {
            "kind": "ssd_scan", "scopes": ["ssd_scan"]}),
        "device_ms_per_step.moe_relu2": ("scope_ms",
                                         {"scopes": ["moe_experts"]}),
        "roofline_pct.moe_relu2": ("roofline_nemotron", {
            "kind": "moe_relu2", "scopes": ["moe_experts"]})}
    assert list(want) == NEW_METRICS
    for name, (reader, args) in want.items():
        read, got = manifest.layer_metric_reader(name)
        assert callable(read) and got == args
        assert read.__module__ == "bench_reader_" + reader
        # no trace (the parent's run, or an untraced one): nothing, no raise
        assert read({"_trace_scopes": None}, **args) is None


def test_sound_run_is_correct_and_reports_the_cells_metrics(policy, capsys,
                                                            own_registry):
    line = _measure(tiny_cell())
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    out = capsys.readouterr().out.splitlines()
    compared = [json.loads(l) for l in out if l.startswith('{"compared"')]
    assert compared and all(v["ok"] for v in compared[-1]["compared"].values())
    assert compared[-1]["compared"]["uncomputed_assignments"]["value"] == 0
    assert "grad_diff._mamba0.a_log" in compared[-1]["compared"]
    assert "grad_diff._moe8.shared_w2" in compared[-1]["compared"]
    noted = [json.loads(l) for l in out if '"expert_load"' in l]
    # the runner asked for moe0..moe8; the four expert layers answered
    assert sorted(noted[-1]["expert_load"]) == ["moe1", "moe3", "moe6",
                                                "moe8"]
    assert all(len(v) == 2 for v in noted[-1]["expert_load"].values())


def test_lower_precision_control_is_not_correct(policy, own_registry):
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
    """A checkout whose program has no ``nemotron_h_net`` (this PR's
    parent): ``require()`` exits with a message, before any weight."""
    import paddle_tpu.models as models

    prog = manifest.program(manifest.cell(CELL)["config"])
    prog.require()                               # this checkout: fine
    monkeypatch.delattr(models, "nemotron_h_net")
    with pytest.raises(SystemExit,
                       match="cannot run nemotron-3-nano-30b-a3b-ep16"):
        prog.require()


# -- the reader --------------------------------------------------------------


def _facts(cell, steps):
    return {"config": cell["config"], "traffic": cell["traffic"],
            "steps": steps, "peaks": manifest.peaks("TPU v5 lite"),
            "_trace_scopes": {"devices": 1}}


def _reader_module():
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "readers",
        "roofline_nemotron.py"), "rf_nemotron")


def test_ssd_roofline_count_against_a_hand_count_at_a_tiny_shape():
    """Three layers of which two are mixers, 4 heads of 8 in 2 groups, a
    state of 16, a row of 256 = 2 chunks, batch 3."""
    mod = _reader_module()
    cfg = {"mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
           "ssm_state_size": 16, "hybrid_override_pattern": "MEM"}
    ops, nbytes = mod.ssd_scan_work(cfg, {"seq_len": 256, "batch": 3})
    Q, W, N = 128, 16, 16
    chunk = 2 * (Q * Q * N          # C B^T
                 + Q * Q * W        # the in-chunk product, a group's heads
                 + Q * N * W        # C S
                 + Q * N * W)       # B^T (dec x dt)
    assert ops == 2 * 3 * 2 * 2 * 3 * chunk   # layers B groups chunks x3
    rows = 2 * 3 * 256
    assert nbytes == (rows * 2 * (32 + 32 + 32 + 32)         # x B C -> y
                      + rows * 2 * (32 + 32 + 32 + 32)       # x B C dy ->
                      + rows * 2 * (32 + 32 + 32)            # dx dB dC
                      + 2 * 3 * 2 * 2 * 16 * 16 * 4 * 2)     # states, twice


def test_roofline_readers_at_the_cells_sizes(monkeypatch):
    """At the cell's sizes the scan counts 54.5 M operations a chunk and
    group forward, x 3 x 8 groups x 32 chunks x 4 layers = 1.68e11 (0.85 ms
    at 197 TFLOP/s) and 1.41 GB moved (1.72 ms at 819 GB/s): bound by
    memory, and a scope that takes 17.2 ms reads 10%.  The experts at the
    even load of 192 assignments count 6 products an assignment and their
    weights three times over in float32: bound by memory too.  No scope, no
    trace, no counter or another configuration reads nothing."""
    from benchmark import trace_scopes

    mod = _reader_module()
    cell = manifest.cell(CELL)
    ops, nbytes = mod.ssd_scan_work(cell["config"], cell["traffic"])
    assert ops == 4 * 8 * 32 * 3 * 54_525_952
    assert ops / 197e12 < nbytes / 819e9
    assert nbytes == 4 * (4096 * 2 * (10240 + 16384)
                          + 8 * 32 * 128 * 512 * 4 * 2)
    assert nbytes / 819e9 == pytest.approx(1.72e-3, rel=0.01)
    assert mod.CHUNK == 128
    steps = 10
    load = {f"moe{i}": [192.0 * steps] * 8 for i in (1, 3, 6, 8)}
    ops_e, bytes_e = mod.moe_relu2_work(cell["config"], load, steps)
    assert ops_e == 4 * 8 * 192 * 6 * 2 * 2688 * 1856
    assert bytes_e == 4 * (3 * 2 * 8 * 2688 * 1856 * 4
                           + 8 * 192 * 2 * 6 * (2688 + 1856))
    assert ops_e / 197e12 < bytes_e / 819e9
    facts = dict(_facts(cell, steps), expert_load=load)
    scan, scan_args = manifest.layer_metric_reader("roofline_pct.ssd_scan")
    moe, moe_args = manifest.layer_metric_reader("roofline_pct.moe_relu2")
    monkeypatch.setattr(trace_scopes, "scope_ns",
                        lambda parsed, scopes: 17.2e6 * steps)
    assert scan(facts, **scan_args) == pytest.approx(10.0, rel=0.01)
    assert moe(facts, **moe_args) == pytest.approx(
        100 * (bytes_e / 819e9) / 17.2e-3, rel=1e-6)
    assert moe(_facts(cell, steps), **moe_args) is None        # no counter
    other = _facts(manifest.cell(QWEN3NEXT), steps)
    assert scan(other, **scan_args) is None
    assert moe(dict(other, expert_load=load), **moe_args) is None
    monkeypatch.setattr(trace_scopes, "scope_ns", lambda parsed, scopes: None)
    assert scan(facts, **scan_args) is None
    assert scan({"_trace_scopes": None}, **scan_args) is None


def test_reader_chunk_is_the_programs():
    from paddle_tpu.ops import ssd_scan as SS

    assert _reader_module().CHUNK == SS.CHUNK
