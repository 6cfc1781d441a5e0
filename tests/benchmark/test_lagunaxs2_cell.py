"""The cell ``lagunaxs2-train-b1-t16384`` (PR 50) through the runner
``trainer_loop_large`` on the CPU at a toy size: the manifest finds the
cell's files, the configuration holds the published widths and 389.63 M
parameters, a sound program is ``correct``, the fp8 control is not; and the
reader this PR adds (``window_attn``) on facts written by hand.

Where this file says where the cell's entries stand in ``BENCHMARK.json`` it
says so RELATIVE to their neighbours (after Keye-VL-2.0's, in their own
order), never as "the last": the next PR that adds a cell appends after
them."""

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

CELL = "lagunaxs2-train-b1-t16384"
CONFIG = "laguna-xs.2-ep32"
KEYE = "keyevl2-train-b1-t16384"
FAKE_TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
#: in the order BENCHMARK.json has them
NEW_METRICS = ["device_ms_per_step.attn_full",
               "device_ms_per_step.attn_window",
               "device_ms_per_step.attn_window_core",
               "kernel_ms_per_step.window_attn", "roofline_pct.attn_window",
               "roofline_pct.attn_full", "attn_band_share_pct"]
KEYE_LAST_METRICS = ["roofline_pct.indexer", "roofline_pct.topk_select",
                     "roofline_pct.attn_selected"]
#: hidden 64; full layers of 6 query heads and window layers of 8 over 2
#: key-value heads of 16; a window of 48; 8 experts of 48 with 2 held, top 3,
#: a shared expert of 32, a dense layer of 96; the cell's five layers and
#: both rope_parameters blocks as published, T 128 (nearly three windows'
#: worth of a row): the widths are toys, the code path (runner, reference, comparison,
#: result line) the cell's
TINY_CONFIG = dict(hidden_size=64, num_attention_heads=6,
                   num_attention_heads_per_layer=[6, 8, 8, 8, 6],
                   num_key_value_heads=2, head_dim=16, sliding_window=48,
                   intermediate_size=96, moe_intermediate_size=48,
                   shared_expert_intermediate_size=32, router_outputs=8,
                   num_experts=2, num_experts_per_tok=3, vocab_size=50)
TINY_TRAFFIC = dict(batch=2, seq_len=128, ring=4)
FIVE = ["full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
#: the catalog row's ``config`` (architectures.jsonl, Laguna-XS.2), its
#: three lists by their period
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": FIVE[:4] * 10,
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10}
REDUCED = ["layer_types", "mlp_layer_types", "num_attention_heads_per_layer",
           "num_experts", "num_hidden_layers", "vocab_size"]


def tiny_cell(limits=True):
    """The cell at a toy size, with limits read at that size on the CPU by
    the rule the chip's were (``check_correct.suggest_limits``: 8 sound
    seeds, 4 control seeds)."""
    cell = copy.deepcopy(manifest.cell(CELL))
    cell["config"].update(TINY_CONFIG)
    cell["traffic"].update(TINY_TRAFFIC)
    if limits:
        with open(os.path.join(HERE, "tiny_limits_lagunaxs2.json")) as f:
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
            tr["prefetch_depth"]) == (1, 16384, "full", 8, 2)
    ref = manifest.reference(cfg)
    prog = manifest.program(cfg)
    assert all(callable(getattr(prog, f)) for f in (
        "require", "trainer", "expert_load", "uncomputed_assignments"))
    # every published width, the router's outputs, experts a token
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["sliding_window"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"], cfg["router_outputs"],
            cfg["num_experts_per_tok"], cfg["moe_routed_scaling_factor"],
            cfg["rms_norm_eps"], cfg["gating"]) == (
        2048, 48, 8, 128, 512, 8192, 512, 512, 256, 8, 2.5, 1e-6, True)
    assert cfg["rope_parameters"] == PUBLISHED["rope_parameters"]
    assert cfg["layer_types"] == FIVE
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert cfg["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["first_expert"], cfg["vocab_size"],
            cfg["num_dense_layers"]) == (5, 8, 0, 12544, 1)
    assert {k: cfg["published"][k] for k in (
        "num_hidden_layers", "num_experts", "vocab_size")} == {
            "num_hidden_layers": 40, "num_experts": 256,
            "vocab_size": 100352}
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_experts"] * 32 == cfg["published"]["num_experts"]
    assert "32 chips share each layer" in cfg["deployment"]
    assert not cfg["tie_word_embeddings"] and not cfg["amp"]
    assert (cfg["param_dtype"], cfg["compute_dtype"]) == ("float32",
                                                         "bfloat16")
    assert cfg["recompute_layers"] == [0, 1, 2, 3, 4]
    assert all(cfg.get(k) for k in ("deployment", "assumed", "why",
                                    "optimizer_note"))
    assert {"head_norms", "sliding_window", "gating", "routing",
            "selection_bias", "rope_parameters", "stds"} <= set(
                cfg["assumed"])
    entry = next(c for c in manifest.benchmark_json()["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json")
    assert sorted(entry["reduced"]) == sorted(cfg["published"]) == REDUCED
    shapes = ref.param_shapes(cfg)
    count = sum(int(np.prod(s)) for s, _ in shapes.values())
    assert count == 389_634_048                  # ISSUE 50's arithmetic
    layer = lambda i: sum(  # noqa: E731
        int(np.prod(s)) for k, (s, _) in shapes.items()
        if k.split(".")[0] in (f"_attn{i}", f"_mlp{i}", f"_moe{i}",
                               f"_norm_op{i}", f"_norm_ffn{i}"))
    full = 2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48
    window = 2 * 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64
    experts = 2048 * 256 + 9 * 3 * 2048 * 512    # router, 8 held, 1 shared
    assert layer(0) == full + 3 * 2048 * 8192 + 2 * 2048 == 79_794_176
    assert layer(1) == layer(2) == layer(3) == window + experts + 2 * 2048 \
        == 66_719_744
    assert layer(4) == full + experts + 2 * 2048 == 58_298_368
    assert not [k for k in shapes if "q_norm" in k or "expert_bias" in k]
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
    parts = ref.forward_flops_per_row(cfg, 16384)
    # attention over the pairs each layer SEES: the band in layers 1-3
    assert parts["attn_window"] == 3 * 8_257_792 * 2 * 64 * 2 * 128
    assert parts["attn_full"] == 2 * 134_225_920 * 2 * 48 * 2 * 128
    assert parts["experts"] == 4 * 16384 * (8 * 8 / 256) * 6 * 2048 * 512
    assert parts["shared"] == 4 * 16384 * 6 * 2048 * 512
    assert parts["dense_mlp"] == 16384 * 6 * 2048 * 8192
    assert parts["attn_window"] / parts["attn_full"] == pytest.approx(
        0.123, abs=0.001)        # 3 x 64 heads x 6.15% over 2 x 48 heads
    assert ref.step_flops(cfg, tr) == pytest.approx(3 * sum(parts.values()))


def test_every_number_of_the_catalog_entry_is_in_the_file():
    """The source's keys under their own names, nested groups whole; the six
    reduced ones differ (the three lists cut to their first five entries)
    and nothing else does."""
    cfg = manifest.cell(CELL)["config"]
    differ = sorted(k for k, v in PUBLISHED.items() if cfg[k] != v)
    assert differ == REDUCED
    for k in ("layer_types", "mlp_layer_types",
              "num_attention_heads_per_layer"):
        assert cfg[k] == PUBLISHED[k][:5]
    assert all(cfg["published"][k] == PUBLISHED[k] for k in (
        "num_hidden_layers", "num_experts", "vocab_size"))


def test_new_metrics_are_this_cells_alone_and_follow_keyes():
    """Each metric this PR adds lists this cell and no other; the older
    cells keep exactly their sets.  Positions are RELATIVE: this cell's
    entries follow Keye-VL-2.0's directly, in their own order; nothing here
    says they are the last."""
    bj = manifest.benchmark_json()
    new = [m for m in bj["per_layer"] if CELL in m.get("workloads", [])]
    assert [m["name"] for m in new] == NEW_METRICS
    assert all(m["workloads"] == [CELL]
               and m["moves"] == "train_tokens_per_s" for m in new)
    assert all(m["source"] == ("program_counter" if m["name"]
                               == "attn_band_share_pct" else "device_trace")
               for m in new)
    assert all(m["unit"] == ("%" if "_pct" in m["name"] else "ms")
               for m in new)
    names = [m["name"] for m in bj["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + len(NEW_METRICS)] == NEW_METRICS
    assert names[at - 3:at] == KEYE_LAST_METRICS
    assert all(bj["per_layer"][i]["workloads"] == [KEYE]
               for i in range(at - 3, at))
    cells = [w["name"] for w in bj["workloads"]]
    assert cells.index(CELL) == cells.index(KEYE) + 1
    configs = [c["name"] for c in bj["configs"]]
    assert configs.index(CONFIG) == configs.index(
        "keye-vl-2.0-30b-a3b-ep16") + 1
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1
    for cell in cells:
        held = {m["name"] for m in manifest.cell(cell)["per_layer"]}
        assert (set(NEW_METRICS) <= held) == (cell == CELL)
        assert cell == CELL or not set(NEW_METRICS) & held


def test_new_metrics_resolve_to_their_readers():
    want = {
        "device_ms_per_step.attn_full": ("scope_ms", {"scopes": [
            "attn0", "attn4"]}),
        "device_ms_per_step.attn_window": ("scope_ms", {"scopes": [
            "attn1", "attn2", "attn3"]}),
        "device_ms_per_step.attn_window_core": ("scope_ms", {"scopes": [
            "attn_window"]}),
        "kernel_ms_per_step.window_attn": ("kernel_ms", {"kernels": [
            "flash_attn_win_fwd", "flash_attn_win_bwd"]}),
        "roofline_pct.attn_window": ("window_attn", {
            "kind": "attn_window", "scopes": ["attn_window"]}),
        "roofline_pct.attn_full": ("window_attn", {
            "kind": "attn_full", "scopes": ["attn_core"]}),
        "attn_band_share_pct": ("window_attn", {"kind": "band_share"})}
    assert list(want) == NEW_METRICS
    for name, (reader, args) in want.items():
        read, got = manifest.layer_metric_reader(name)
        assert callable(read) and got == args
        assert read.__module__ == "bench_reader_" + reader
        # no trace, no configuration (the parent's run, or an untraced
        # one): nothing, no raise
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
    for leaf in ("_attn0.wg", "_attn2.wq", "_mlp0.w2", "_moe4.shared_w1",
                 "_moe1.router"):
        assert "grad_diff." + leaf in compared[-1]["compared"], leaf
    noted = [json.loads(l) for l in out if '"expert_load"' in l]
    assert sorted(noted[-1]["expert_load"]) == ["moe1", "moe2", "moe3",
                                                "moe4"]
    assert all(len(v) == 2 for v in noted[-1]["expert_load"].values())
    # the counter the trainer fed: the share of the causal pairs the window
    # layers' masks let through is the configuration's own, exactly
    cell = tiny_cell()
    read, args = manifest.layer_metric_reader("attn_band_share_pct")
    share = read({"config": cell["config"], "traffic": cell["traffic"]},
                 **args)
    band, causal = 48 * 49 // 2 + 80 * 48, 128 * 129 // 2
    assert share == pytest.approx(100.0 * band / causal, rel=1e-9)
    from paddle_tpu.obs import get_registry

    series = get_registry().snapshot()["window_attn_pairs"]["series"]
    assert sorted(s["labels"]["layer"] for s in series) == [
        "attn1", "attn2", "attn3"]


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
    """A checkout whose program has no ``laguna_net`` (this PR's parent):
    ``require()`` exits with a message, before any weight."""
    import paddle_tpu.models as models

    prog = manifest.program(manifest.cell(CELL)["config"])
    prog.require()                               # this checkout: fine
    monkeypatch.delattr(models, "laguna_net")
    with pytest.raises(SystemExit, match="cannot run laguna-xs.2-ep32"):
        prog.require()


# -- the reader --------------------------------------------------------------


def _facts(cell, steps):
    return {"config": cell["config"], "traffic": cell["traffic"],
            "steps": steps, "peaks": manifest.peaks("TPU v5 lite"),
            "_trace_scopes": {"devices": 1}}


def _reader_module():
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "readers", "window_attn.py"),
        "rf_window_attn")


TINY_WORK = ({"layer_types": ["full_attention", "sliding_attention",
                              "sliding_attention", "full_attention"],
              "num_attention_heads_per_layer": [2, 4, 6, 2],
              "num_attention_heads": 2, "num_key_value_heads": 2,
              "head_dim": 8, "sliding_window": 3},
             {"seq_len": 5, "batch": 2})


@pytest.mark.parametrize("kind", ["attn_window", "attn_full"])
def test_work_functions_against_a_hand_count_at_a_tiny_shape(kind):
    """A row of 5 under a window of 3, batch 2: 15 causal pairs, 1 + 2 + 3 +
    3 + 3 = 12 in the band; window layers of 4 and 6 heads, two full layers
    of 2, all over 2 key-value heads of 8."""
    mod = _reader_module()
    assert (mod.causal_pairs(5), mod.band_pairs(5, 3)) == (15, 12)
    assert mod.band_pairs(2, 3) == 3 and mod.band_pairs(3, 3) == 6
    ops, nbytes = mod.WORK[kind](*TINY_WORK)
    rows = 2 * 5 * 8 * 2                          # batch x T x dh x bf16
    if kind == "attn_window":
        # (4 + 6) heads x 12 pairs x 7 products of 8 channels
        assert ops == 2 * (4 + 6) * 12 * 7 * 2 * 8
        assert nbytes == rows * ((2 * 4 + 2 * 2) + (4 * 4 + 4 * 2)) \
            + rows * ((2 * 6 + 2 * 2) + (4 * 6 + 4 * 2))
    else:
        # two layers of 2 heads x 15 pairs x 7 products of 8 channels
        assert ops == 2 * 2 * 2 * 15 * 7 * 2 * 8
        assert nbytes == 2 * rows * ((2 * 2 + 2 * 2) + (4 * 2 + 4 * 2))


def test_roofline_readers_at_the_cells_sizes(monkeypatch):
    """At the cell's sizes: the band is 2.84e12 operations a step (14.4 ms
    at 197 TFLOP/s) against 1.3 GB, the triangle of the two full layers
    2.31e13 (117 ms): both bound by compute.  No scope, no trace, another
    configuration: nothing."""
    from benchmark import trace_scopes

    mod = _reader_module()
    cell = manifest.cell(CELL)
    cfg, tr = cell["config"], cell["traffic"]
    assert (mod.causal_pairs(16384), mod.band_pairs(16384, 512)) == (
        134_225_920, 8_257_792)
    ops_w, bytes_w = mod.window_attn_work(cfg, tr)
    assert ops_w == 3 * 64 * 8_257_792 * 7 * 2 * 128
    assert ops_w / 197e12 == pytest.approx(14.4e-3, rel=0.01)
    assert ops_w / 197e12 > bytes_w / 819e9
    ops_f, bytes_f = mod.full_attn_work(cfg, tr)
    assert ops_f == 2 * 48 * 134_225_920 * 7 * 2 * 128
    assert ops_f / 197e12 == pytest.approx(117e-3, rel=0.01)
    ref = manifest.reference(cfg)
    parts = ref.forward_flops_per_row(cfg, 16384)
    assert ops_w == 3.5 * parts["attn_window"]
    assert ops_f == 3.5 * parts["attn_full"]
    steps = 3
    facts = _facts(cell, steps)
    monkeypatch.setattr(trace_scopes, "scope_ns",
                        lambda parsed, scopes: 200e6 * steps)
    for name, least in (("roofline_pct.attn_window", ops_w / 197e12),
                        ("roofline_pct.attn_full", ops_f / 197e12)):
        read, args = manifest.layer_metric_reader(name)
        assert read(facts, **args) == pytest.approx(100 * least / 0.2,
                                                    rel=1e-9)
        assert 0 < read(facts, **args) < 100
        other = _facts(manifest.cell(KEYE), steps)
        assert read(other, **args) is None
    monkeypatch.setattr(trace_scopes, "scope_ns", lambda parsed, scopes: None)
    read, args = manifest.layer_metric_reader("roofline_pct.attn_window")
    assert read(facts, **args) is None
    assert read({"_trace_scopes": None}, **args) is None


def test_band_share_reads_nothing_without_the_counter(own_registry):
    cell = manifest.cell(CELL)
    read, args = manifest.layer_metric_reader("attn_band_share_pct")
    assert read(_facts(cell, 3), **args) is None
    assert read(_facts(manifest.cell(KEYE), 3), **args) is None
