"""The cell ``keyevl2-train-b1-t16384`` (PR 47) through the runner
``trainer_loop_large`` on the CPU at a toy size: the manifest finds the
cell's files, the configuration holds the published widths and 314.40 M
parameters, a sound program is ``correct``, the fp8 control is not; and the
reader this PR adds (``sparse_attn``) on facts written by hand.

Where this file says where the cell's entries stand in ``BENCHMARK.json`` it
says so RELATIVE to their neighbours (after Nemotron-3-Nano's, in their own
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

CELL = "keyevl2-train-b1-t16384"
CONFIG = "keye-vl-2.0-30b-a3b-ep16"
NEMOTRON = "nemotron3nano-train-b1-t4096"
FAKE_TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
#: in the order BENCHMARK.json has them
NEW_METRICS = ["device_ms_per_step.sparse_attn", "device_ms_per_step.indexer",
               "device_ms_per_step.topk_select",
               "device_ms_per_step.attn_selected",
               "device_ms_per_step.indexer_loss",
               "kernel_ms_per_step.sparse_attn", "attn_kept_share_pct",
               "roofline_pct.indexer", "roofline_pct.topk_select",
               "roofline_pct.attn_selected"]
NEMOTRON_METRICS = ["device_ms_per_step.mamba",
                    "device_ms_per_step.mamba_proj",
                    "device_ms_per_step.ssd_scan", "kernel_ms_per_step.ssd",
                    "roofline_pct.ssd_scan", "device_ms_per_step.moe_relu2",
                    "roofline_pct.moe_relu2"]
#: hidden 64; 4 query heads of 16 over 2 key-value heads; an indexer of 2
#: heads of 16 over one key head that keeps 64; 8 experts of 48 with 2 held,
#: top 3; the cell's four layers, T 256 (four selections' worth of a row):
#: the widths are toys, the code path (runner, reference, comparison, result
#: line) the cell's
TINY_CONFIG = dict(hidden_size=64, num_attention_heads=4,
                   num_key_value_heads=2, head_dim=16,
                   moe_intermediate_size=48, router_outputs=8, num_experts=2,
                   num_experts_per_tok=3, vocab_size=50)
TINY_SA = dict(indexer_num_heads=2, indexer_head_dim=16, topk=64)
TINY_TRAFFIC = dict(batch=2, seq_len=256, ring=4)
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def tiny_cell(limits=True):
    """The cell at a toy size, with limits read at that size on the CPU by
    the rule the chip's were (``check_correct.suggest_limits``: 8 sound
    seeds, 4 control seeds)."""
    cell = copy.deepcopy(manifest.cell(CELL))
    cell["config"].update(TINY_CONFIG)
    cell["config"]["sa_config"].update(TINY_SA)
    cell["traffic"].update(TINY_TRAFFIC)
    if limits:
        with open(os.path.join(HERE, "tiny_limits_keyevl2.json")) as f:
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
            cfg["moe_intermediate_size"], cfg["router_outputs"],
            cfg["num_experts_per_tok"], cfg["rms_norm_eps"],
            cfg["rope_theta"], cfg["norm_topk_prob"]) == (
        2048, 32, 4, 128, 768, 128, 8, 1e-6, 10000000, True)
    assert cfg["sa_config"] == PUBLISHED["sa_config"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["first_expert"], cfg["vocab_size"]) == (4, 8, 0, 18992)
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "vocab_size": 151936}
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_experts"] * 16 == cfg["published"]["num_experts"]
    assert cfg["layer_types"] == ["attention"] * 4
    assert cfg["num_dense_layers"] == 0 and cfg["mlp_only_layers"] == []
    assert not cfg["tie_word_embeddings"] and not cfg["amp"]
    assert (cfg["param_dtype"], cfg["compute_dtype"],
            cfg["indexer_dtype"]) == ("float32", "bfloat16", "bfloat16")
    assert cfg["recompute_layers"] == [0, 1, 2, 3]
    assert all(cfg.get(k) for k in ("deployment", "assumed", "why",
                                    "optimizer_note"))
    assert {"indexer", "selection", "indexer_loss", "head_norms", "stds",
            "indexer_dtype"} <= set(cfg["assumed"])
    entry = next(c for c in manifest.benchmark_json()["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
        "config.json")
    assert sorted(entry["reduced"]) == sorted(cfg["published"]) == sorted(
        ["num_hidden_layers", "num_experts", "vocab_size"])
    shapes = ref.param_shapes(cfg)
    count = sum(int(np.prod(s)) for s, _ in shapes.values())
    assert count == 314_396_160                  # ISSUE 47's arithmetic
    attn = sum(int(np.prod(s)) for k, (s, _) in shapes.items()
               if k.startswith("_attn0."))
    moe = sum(int(np.prod(s)) for k, (s, _) in shapes.items()
              if k.startswith("_moe0."))
    assert attn == 18_874_624 + 2_261_120        # main heads + the indexer
    assert moe == 2048 * 128 + 8 * 3 * 2048 * 768
    assert attn + moe + 2 * 2048 == 59_150_720   # a layer, with its norms
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
    # the main heads over the KEPT pairs, the indexer over every causal one
    assert ref.kept_pairs(16384, 2048) == 31_458_304
    assert parts["attn_selected"] == 4 * 31_458_304 * 2 * 32 * 2 * 128
    assert parts["indexer_scores"] == 4 * 134_225_920 * 2 * 16 * 64
    assert parts["indexer_target"] == parts["attn_selected"] / 2
    assert parts["experts"] == 4 * 16384 * (8 * 8 / 128) * 6 * 2048 * 768
    quadratic = (parts["attn_selected"] + parts["indexer_scores"]
                 + parts["indexer_target"])
    # half of the mathematics; about three quarters of what the kernels run,
    # which visit every causal tile and drop what is not kept
    assert quadratic / sum(parts.values()) == pytest.approx(0.49, abs=0.02)
    assert ref.step_flops(cfg, tr) == pytest.approx(
        3 * (sum(parts.values()) - parts["indexer_target"])
        + parts["indexer_target"])


def test_every_number_of_the_catalog_entry_is_in_the_file():
    """The source's keys under their own names, nested groups whole; the
    three reduced ones differ and nothing else does."""
    cfg = manifest.cell(CELL)["config"]
    differ = sorted(k for k, v in PUBLISHED.items() if cfg[k] != v)
    assert differ == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert all(cfg["published"][k] == PUBLISHED[k] for k in differ)


def test_new_metrics_are_this_cells_alone_and_follow_nemotrons():
    """Each metric this PR adds lists this cell and no other; the older
    cells keep exactly their sets.  Positions are RELATIVE: this cell's
    entries follow Nemotron-3-Nano's directly, in their own order; nothing
    here says they are the last."""
    bj = manifest.benchmark_json()
    new = [m for m in bj["per_layer"] if CELL in m.get("workloads", [])]
    assert [m["name"] for m in new] == NEW_METRICS
    assert all(m["workloads"] == [CELL]
               and m["moves"] == "train_tokens_per_s" for m in new)
    assert all(m["source"] == ("program_counter" if m["name"]
                               == "attn_kept_share_pct" else "device_trace")
               for m in new)
    assert all(m["unit"] == ("%" if "_pct" in m["name"] else "ms")
               for m in new)
    names = [m["name"] for m in bj["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + len(NEW_METRICS)] == NEW_METRICS
    assert names[at - 7:at] == NEMOTRON_METRICS
    assert all(bj["per_layer"][i]["workloads"] == [NEMOTRON]
               for i in range(at - 7, at))
    cells = [w["name"] for w in bj["workloads"]]
    assert cells.index(CELL) == cells.index(NEMOTRON) + 1
    configs = [c["name"] for c in bj["configs"]]
    assert configs.index(CONFIG) == configs.index(
        "nemotron-3-nano-30b-a3b-ep16") + 1
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1
    for cell in cells:
        held = {m["name"] for m in manifest.cell(cell)["per_layer"]}
        assert (set(NEW_METRICS) <= held) == (cell == CELL)
        assert cell == CELL or not set(NEW_METRICS) & held
        assert (set(NEMOTRON_METRICS) <= held) == (cell == NEMOTRON)


def test_new_metrics_resolve_to_their_readers():
    want = {
        "device_ms_per_step.sparse_attn": ("scope_ms", {"scopes": [
            "attn0", "attn1", "attn2", "attn3"]}),
        "device_ms_per_step.indexer": ("scope_ms", {"scopes": ["indexer"]}),
        "device_ms_per_step.topk_select": ("scope_ms",
                                           {"scopes": ["topk_select"]}),
        "device_ms_per_step.attn_selected": ("scope_ms",
                                             {"scopes": ["attn_core"]}),
        "device_ms_per_step.indexer_loss": ("scope_ms",
                                            {"scopes": ["indexer_loss"]}),
        "kernel_ms_per_step.sparse_attn": ("kernel_ms", {"kernels": [
            "indexer_scores", "topk_select", "flash_attn_sel_fwd",
            "flash_attn_sel_bwd", "indexer_loss"]}),
        "attn_kept_share_pct": ("sparse_attn", {"kind": "kept_share"}),
        "roofline_pct.indexer": ("sparse_attn", {
            "kind": "indexer", "scopes": ["indexer"]}),
        "roofline_pct.topk_select": ("sparse_attn", {
            "kind": "topk_select", "scopes": ["topk_select"]}),
        "roofline_pct.attn_selected": ("sparse_attn", {
            "kind": "attn_selected", "scopes": ["attn_core"]})}
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
    assert "grad_diff._attn0.wiq" in compared[-1]["compared"]
    assert "grad_diff._attn3.ik_bias" in compared[-1]["compared"]
    assert "grad_diff._moe3.w2" in compared[-1]["compared"]
    noted = [json.loads(l) for l in out if '"expert_load"' in l]
    assert sorted(noted[-1]["expert_load"]) == ["moe0", "moe1", "moe2",
                                                "moe3"]
    assert all(len(v) == 2 for v in noted[-1]["expert_load"].values())
    # the counters the trainer fed: the share of the causal pairs kept is
    # the configuration's own, exactly, and every layer's term is positive
    cell = tiny_cell()
    read, args = manifest.layer_metric_reader("attn_kept_share_pct")
    share = read({"config": cell["config"], "traffic": cell["traffic"]},
                 **args)
    kept, causal = 64 * 65 // 2 + 192 * 64, 256 * 257 // 2
    assert share == pytest.approx(100.0 * kept / causal, rel=1e-9)
    from paddle_tpu.obs import get_registry

    series = get_registry().snapshot()["indexer_kl"]["series"]
    assert sorted(s["labels"]["layer"] for s in series) == [
        "attn0", "attn1", "attn2", "attn3"]
    assert all(s["value"] > 0 for s in series)


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
    """A checkout whose program has no ``keye_vl2_net`` (this PR's parent):
    ``require()`` exits with a message, before any weight."""
    import paddle_tpu.models as models

    prog = manifest.program(manifest.cell(CELL)["config"])
    prog.require()                               # this checkout: fine
    monkeypatch.delattr(models, "keye_vl2_net")
    with pytest.raises(SystemExit,
                       match="cannot run keye-vl-2.0-30b-a3b-ep16"):
        prog.require()


# -- the reader --------------------------------------------------------------


def _facts(cell, steps):
    return {"config": cell["config"], "traffic": cell["traffic"],
            "steps": steps, "peaks": manifest.peaks("TPU v5 lite"),
            "_trace_scopes": {"devices": 1}}


def _reader_module():
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "readers", "sparse_attn.py"),
        "rf_sparse_attn")


TINY_WORK = ({"num_hidden_layers": 3, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 8,
              "sa_config": {"indexer_num_heads": 2, "indexer_head_dim": 4,
                            "topk": 3}},
             {"seq_len": 5, "batch": 2})


@pytest.mark.parametrize("kind", ["indexer", "topk_select", "attn_selected"])
def test_work_functions_against_a_hand_count_at_a_tiny_shape(kind):
    """Three layers, a row of 5 with 3 kept, batch 2: 15 causal pairs, 1 +
    2 + 3 + 3 + 3 = 12 kept."""
    mod = _reader_module()
    assert (mod.causal_pairs(5), mod.kept_pairs(5, 3)) == (15, 12)
    assert mod.kept_pairs(2, 3) == 3 and mod.kept_pairs(3, 3) == 6
    ops, nbytes = mod.WORK[kind](*TINY_WORK)
    each = 3 * 2                                  # layers x batch
    if kind == "indexer":
        # 2 heads: a product of 4 channels (8 ops), ReLU, weight, add
        assert ops == each * 15 * (2 * 2 * 4 + 3 * 2)
        assert nbytes == each * (5 * 2 * 4 * 2 + 5 * 4 * 2 + 5 * 2 * 4
                                 + 15 * 4)
    elif kind == "topk_select":
        assert ops == 0
        assert nbytes == each * (15 * 4 + 5 * 4)
    else:
        # 4 heads x (q k^T + p v) of 8 channels, forward + 2 x backward
        assert ops == each * 12 * 3 * 4 * 2 * (2 * 8)
        rows = each * 5 * 8 * 2
        assert nbytes == (rows * (2 * 4 + 2 * 2)         # q o; k v
                          + rows * (4 * 4 + 4 * 2)       # q o do dq; k v dk dv
                          + each * 2 * 15 / 8)           # the selection


def test_roofline_readers_at_the_cells_sizes(monkeypatch):
    """At the cell's sizes: the indexer counts 2096 operations a causal
    pair, 1.13e12 a step (5.7 ms at 197 TFLOP/s) against 2.2 GB (2.7 ms):
    bound by compute; the selection is 2.15 GB a step (2.6 ms at 819 GB/s);
    the attention over the kept pairs 6.18e12 (31.4 ms).  No scope, no
    trace, another configuration: nothing."""
    from benchmark import trace_scopes

    mod = _reader_module()
    cell = manifest.cell(CELL)
    cfg, tr = cell["config"], cell["traffic"]
    assert (mod.causal_pairs(16384), mod.kept_pairs(16384, 2048)) == (
        134_225_920, 31_458_304)
    ops_i, bytes_i = mod.indexer_work(cfg, tr)
    assert ops_i == 4 * 134_225_920 * 2096
    assert ops_i / 197e12 > bytes_i / 819e9
    ops_s, bytes_s = mod.topk_select_work(cfg, tr)
    assert (ops_s, bytes_s) == (0, 4 * (134_225_920 * 4 + 16384 * 4))
    ops_a, bytes_a = mod.attn_selected_work(cfg, tr)
    ref = manifest.reference(cfg)
    assert ops_a == 3 * ref.forward_flops_per_row(cfg, 16384)["attn_selected"]
    assert ops_a / 197e12 == pytest.approx(31.4e-3, rel=0.01)
    assert ops_a / 197e12 > bytes_a / 819e9
    steps = 3
    facts = _facts(cell, steps)
    monkeypatch.setattr(trace_scopes, "scope_ns",
                        lambda parsed, scopes: 100e6 * steps)
    for name, least in (("roofline_pct.indexer", ops_i / 197e12),
                        ("roofline_pct.topk_select", bytes_s / 819e9),
                        ("roofline_pct.attn_selected", ops_a / 197e12)):
        read, args = manifest.layer_metric_reader(name)
        assert read(facts, **args) == pytest.approx(100 * least / 0.1,
                                                    rel=1e-9)
        assert 0 < read(facts, **args) < 100
        other = _facts(manifest.cell(NEMOTRON), steps)
        assert read(other, **args) is None
    monkeypatch.setattr(trace_scopes, "scope_ns", lambda parsed, scopes: None)
    read, args = manifest.layer_metric_reader("roofline_pct.indexer")
    assert read(facts, **args) is None
    assert read({"_trace_scopes": None}, **args) is None


def test_kept_share_reads_nothing_without_the_counter(own_registry):
    cell = manifest.cell(CELL)
    read, args = manifest.layer_metric_reader("attn_kept_share_pct")
    assert read(_facts(cell, 3), **args) is None
    assert read(_facts(manifest.cell(NEMOTRON), 3), **args) is None
