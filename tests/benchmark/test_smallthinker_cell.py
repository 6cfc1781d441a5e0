"""The cell ``smallthinker-train-b1-t16384`` (PR 57) through the runner
``trainer_loop_large`` on the CPU at a toy size: the manifest finds the
cell's files, the configuration holds the published widths and 370,547,200
parameters, a sound program is ``correct``, the fp8 control is not; the nine
metrics this PR adds, their readers on facts written by hand and on the
counters a run fed.

Where this file says where the cell's entries stand in ``BENCHMARK.json`` it
says so RELATIVE to their neighbours (after Ouro's, in their own order),
never as "the last": the next PR that adds a cell appends after them."""

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

CELL = "smallthinker-train-b1-t16384"
CONFIG = "smallthinker-21b-a3b-ep8"
OURO = "ouro-train-b1-t4096"
LAGUNA = "lagunaxs2-train-b1-t16384"
FAKE_TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
SOURCE = ("https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
          "blob/main/config.json")
#: in the order BENCHMARK.json has them: name -> (reader, args)
NEW_METRICS = {
    "device_ms_per_step.early_router": ("scope_ms", {"scopes": [
        "moe_routing"]}),
    "device_ms_per_step.moe_reglu": ("scope_ms", {"scopes": ["moe_experts"]}),
    "roofline_pct.moe_reglu": ("smallthinker", {
        "kind": "moe_reglu", "scopes": ["moe_experts"]}),
    "device_ms_per_step.attn_nope_full": ("scope_ms", {"scopes": [
        "attn_core"]}),
    "device_ms_per_step.attn_window4k": ("scope_ms", {"scopes": [
        "attn_window"]}),
    "roofline_pct.attn_window4k": ("window_attn", {
        "kind": "attn_window", "scopes": ["attn_window"]}),
    "roofline_pct.attn_nope_full": ("window_attn", {
        "kind": "attn_full", "scopes": ["attn_core"]}),
    "attn_window4k_share_pct": ("window_attn", {"kind": "band_share"}),
    "moe_gate_zero_share_pct": ("smallthinker", {"kind": "gate_zero_share"}),
}
OURO_LAST_METRICS = ["roofline_pct.loop_attn_core", "roofline_pct.exit_heads",
                     "loop_expected_exit_step"]
#: hidden 64; 7 query heads over 1 key-value head of 16 (the group of seven);
#: a window of 16; 8 router outputs, experts of 32, 4 held, 3 a token; the
#: cell's four layers by both layouts, T 64 (four windows' worth of a row):
#: the widths are toys, the code path (runner, reference, comparison, result
#: line) the cell's
TINY_CONFIG = dict(hidden_size=64, num_attention_heads=7,
                   num_attention_heads_per_layer=[7, 7, 7, 7],
                   num_key_value_heads=1, head_dim=16, sliding_window_size=16,
                   sliding_window=16, moe_ffn_hidden_size=32,
                   router_outputs=8, moe_num_primary_experts=4,
                   moe_num_active_primary_experts=3, vocab_size=50)
TINY_TRAFFIC = dict(batch=2, seq_len=64, ring=4)
#: the catalog row's ``config`` (architectures.jsonl,
#: SmallThinker-21BA3B-Instruct), its two lists by their period
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936}
REDUCED = ["moe_num_primary_experts", "num_hidden_layers", "rope_layout",
           "sliding_window_layout", "vocab_size"]


def tiny_cell(limits=True):
    """The cell at a toy size, with limits read at that size on the CPU by
    the rule the chip's were (``check_correct.suggest_limits``: 8 sound
    seeds, 4 control seeds)."""
    cell = copy.deepcopy(manifest.cell(CELL))
    cell["config"].update(TINY_CONFIG)
    cell["traffic"].update(TINY_TRAFFIC)
    if limits:
        with open(os.path.join(HERE, "tiny_limits_smallthinker.json")) as f:
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
    assert tr["seq_len"] == cfg["max_position_embeddings"]
    ref = manifest.reference(cfg)
    prog = manifest.program(cfg)
    assert all(callable(getattr(prog, f)) for f in (
        "require", "trainer", "expert_load", "uncomputed_assignments"))
    # every published width, the router's outputs, experts a token
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["sliding_window_size"], cfg["moe_ffn_hidden_size"],
            cfg["router_outputs"], cfg["moe_num_active_primary_experts"],
            cfg["rope_theta"], cfg["rms_norm_eps"]) == (
        2560, 28, 4, 128, 4096, 768, 64, 6, 1500000, 1e-6)
    assert cfg["sliding_window_layout"] == cfg["rope_layout"] == [0, 1, 1, 1]
    assert (cfg["num_hidden_layers"], cfg["moe_num_primary_experts"],
            cfg["first_expert"], cfg["vocab_size"],
            cfg["num_dense_layers"]) == (4, 8, 0, 18992, 0)
    # what the runner and the accepted readers read, derived and said so
    assert cfg["layer_types"] == ["full_attention"] + [
        "sliding_attention"] * 3
    assert cfg["num_attention_heads_per_layer"] == [28] * 4
    assert cfg["sliding_window"] == cfg["sliding_window_size"]
    assert all("derived" in cfg["assumed"][k] for k in (
        "layer_types", "num_dense_layers", "num_attention_heads_per_layer",
        "sliding_window"))
    assert {k: cfg["published"][k] for k in (
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size")} == {
            "num_hidden_layers": 52, "moe_num_primary_experts": 64,
            "vocab_size": 151936}
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["moe_num_primary_experts"] * 8 == cfg["published"][
        "moe_num_primary_experts"] == cfg["router_outputs"]
    assert "8 chips share each layer" in cfg["deployment"]
    assert not cfg["tie_word_embeddings"] and not cfg["amp"]
    assert (cfg["param_dtype"], cfg["compute_dtype"], cfg["router_dtype"]) \
        == ("float32", "bfloat16", "float32")
    assert cfg["recompute_layers"] == [0, 1, 2, 3]
    assert all(cfg.get(k) for k in ("deployment", "assumed", "why",
                                    "optimizer_note"))
    assert {"router_placement", "routing", "experts", "positions",
            "sliding_window", "head_norms", "norms", "mtp", "stds"} <= set(
                cfg["assumed"])
    entry = next(c for c in manifest.benchmark_json()["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] == SOURCE
    assert sorted(entry["reduced"]) == sorted(cfg["published"]) == REDUCED
    # 370,547,200 parameters, from the configuration file alone
    shapes = ref.param_shapes(cfg)
    count = sum(int(np.prod(s)) for s, _ in shapes.values())
    assert count == 370_547_200 and "370,547,200" in cfg["why"]
    assert len(shapes) == 43
    layer = lambda i: sum(  # noqa: E731
        int(np.prod(s)) for k, (s, _) in shapes.items()
        if k.split(".")[0] in (f"_attn{i}", f"_moe{i}", f"_norm_op{i}",
                               f"_norm_ffn{i}"))
    attention = 2 * 2560 * 3584 + 2 * 2560 * 512
    experts = 2560 * 64 + 8 * 3 * 2560 * 768
    assert attention == 20_971_520 and experts == 163_840 + 47_185_920
    assert [layer(i) for i in range(4)] == [attention + experts + 2 * 2560
                                            ] * 4 == [68_326_400] * 4
    assert int(np.prod(shapes["_emb.w0"][0])) == int(np.prod(
        shapes["_cost.w"][0])) == 18_992 * 2_560
    assert not [k for k in shapes if "q_norm" in k or "expert_bias" in k
                or "shared" in k or "_mlp" in k]
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
    assert ref.seen_pairs(16384, 4096) == 58_722_304
    assert ref.seen_pairs(16384) == 134_225_920
    assert parts["attn_window"] == 3 * 58_722_304 * 2 * 28 * 2 * 128
    assert parts["attn_full"] == 134_225_920 * 2 * 28 * 2 * 128
    assert parts["experts"] == 4 * 16384 * (6 * 8 / 64) * 6 * 2560 * 768
    assert parts["router"] == 4 * 16384 * 2 * 2560 * 64
    assert parts["head"] == 16384 * 2 * 2560 * 18992
    cores = parts["attn_window"] + parts["attn_full"]
    assert cores / sum(parts.values()) == pytest.approx(0.47, abs=0.01)
    assert ref.step_flops(cfg, tr) == pytest.approx(3 * sum(parts.values()))


def test_every_number_of_the_catalog_entry_is_in_the_file():
    """The source's keys under their own names; the five reduced ones differ
    (the two lists cut to their first period) and nothing else does."""
    cfg = manifest.cell(CELL)["config"]
    assert set(PUBLISHED) <= set(cfg)
    differ = sorted(k for k, v in PUBLISHED.items() if cfg[k] != v)
    assert differ == REDUCED
    for k in ("sliding_window_layout", "rope_layout"):
        assert cfg[k] == PUBLISHED[k][:4]
    assert all(cfg["published"][k] == PUBLISHED[k] for k in (
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size"))


def test_benchmark_json_names_the_configuration_the_cell_and_nine_metrics():
    """Each metric this PR adds lists this cell and no other, has a spec
    file and a reader file that exist; the older cells keep exactly their
    sets.  Positions are RELATIVE: this cell's entries follow Ouro's
    directly, in their own order; nothing here says they are the last."""
    bj = manifest.benchmark_json()
    new = [m for m in bj["per_layer"] if CELL in m.get("workloads", [])]
    assert [m["name"] for m in new] == list(NEW_METRICS) and len(new) == 9
    assert all(m["workloads"] == [CELL]
               and m["moves"] == "train_tokens_per_s" for m in new)
    assert all(m["source"] == ("program_counter" if m["name"].endswith(
        "share_pct") else "device_trace") for m in new)
    assert all(m["unit"] == ("%" if "_pct" in m["name"] else "ms")
               for m in new)
    assert all(m["layer"] == ("kernels" if m["name"].startswith("roofline")
                              else "model step") for m in new)
    for name, (reader, _) in NEW_METRICS.items():
        assert os.path.exists(os.path.join(
            manifest.BENCH, "layer_metrics", name + ".json"))
        assert os.path.exists(os.path.join(
            manifest.BENCH, "layer_metrics", "readers", reader + ".py"))
    names = [m["name"] for m in bj["per_layer"]]
    at = names.index(list(NEW_METRICS)[0])
    assert names[at:at + 9] == list(NEW_METRICS)
    assert names[at - 3:at] == OURO_LAST_METRICS
    cells = [w["name"] for w in bj["workloads"]]
    assert cells.index(CELL) == cells.index(OURO) + 1
    configs = [c["name"] for c in bj["configs"]]
    assert configs.index(CONFIG) == configs.index("ouro-2.6b-loop4") + 1
    entry = bj["configs"][configs.index(CONFIG)]
    assert os.path.exists(os.path.join(ROOT, entry["file"]))
    work = bj["workloads"][cells.index(CELL)]
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG, CELL, 1)
    assert os.path.exists(os.path.join(
        manifest.BENCH, "workloads", work["traffic"] + ".json"))
    assert len(work["why"]) <= 200 and len(entry["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1
    for cell in cells:
        held = {m["name"] for m in manifest.cell(cell)["per_layer"]}
        assert (set(NEW_METRICS) <= held) == (cell == CELL)
        assert cell == CELL or not set(NEW_METRICS) & held


def test_new_metrics_resolve_to_their_readers():
    for name, (reader, args) in NEW_METRICS.items():
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
    for leaf in ("_attn0.wq", "_attn2.wk", "_moe0.router", "_moe3.router",
                 "_moe1.w3", "_norm_op2.w"):
        assert "grad_diff." + leaf in compared[-1]["compared"], leaf
    noted = [json.loads(l) for l in out if '"expert_load"' in l]
    assert sorted(noted[-1]["expert_load"]) == ["moe0", "moe1", "moe2",
                                                "moe3"]
    assert all(len(v) == 4 for v in noted[-1]["expert_load"].values())
    # the counters the trainer fed: the share of the causal pairs the window
    # layers' masks let through is the configuration's own, exactly; the
    # share of hidden units the ReLU zeroed is near a half
    cell = tiny_cell()
    facts = {"config": cell["config"], "traffic": cell["traffic"]}
    read, args = manifest.layer_metric_reader("attn_window4k_share_pct")
    band, causal = 16 * 17 // 2 + 48 * 16, 64 * 65 // 2
    assert read(facts, **args) == pytest.approx(100.0 * band / causal,
                                                rel=1e-9)
    read, args = manifest.layer_metric_reader("moe_gate_zero_share_pct")
    assert 30.0 < read(facts, **args) < 70.0
    from paddle_tpu.obs import get_registry

    snap = get_registry().snapshot()
    assert sorted(s["labels"]["layer"] for s in snap[
        "window_attn_pairs"]["series"]) == ["attn1", "attn2", "attn3"]
    assert sorted(s["labels"]["layer"] for s in snap[
        "moe_gate_zero_units"]["series"]) == ["moe0", "moe1", "moe2", "moe3"]
    zeros = sum(s["value"] for s in snap["moe_gate_zero_units"]["series"])
    rows = sum(s["value"] for s in snap["moe_assignments"]["series"])
    assert read(facts, **args) == pytest.approx(100.0 * zeros / (rows * 32))


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
    """A checkout whose program has no ``smallthinker_net`` (this PR's
    parent): ``require()`` exits with a message, before any weight."""
    import paddle_tpu.models as models

    prog = manifest.program(manifest.cell(CELL)["config"])
    prog.require()                               # this checkout: fine
    monkeypatch.delattr(models, "smallthinker_net")
    with pytest.raises(SystemExit,
                       match="cannot run smallthinker-21b-a3b-ep8"):
        prog.require()


# -- the readers -------------------------------------------------------------


def _facts(cell, steps, **more):
    return {"config": cell["config"], "traffic": cell["traffic"],
            "steps": steps, "peaks": manifest.peaks("TPU v5 lite"),
            "_trace_scopes": {"devices": 1}, **more}


def _reader_module():
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "readers", "smallthinker.py"),
        "rf_smallthinker")


def test_reglu_work_against_a_hand_count_at_a_tiny_shape():
    """Two layers, hidden 4, experts of 3: 10 and 6 rows a step over 2
    steps; 9 products of 2 x 4 x 3 a row; three matrices of 4 x 3 an expert
    held read twice and written once in float32; bf16 rows of 4 and of 3 in
    and out of each product."""
    mod = _reader_module()
    ops, nbytes = mod.moe_reglu_work(
        {"hidden_size": 4, "moe_ffn_hidden_size": 3},
        {"moe0": [12, 8], "moe1": [4, 4, 4]}, 2)
    assert ops == (10 + 6) * 9 * 2 * 4 * 3
    assert nbytes == 3 * 3 * (2 + 3) * 4 * 3 * 4 + (10 + 6) * 2 * 9 * (4 + 3)


def test_roofline_readers_at_the_cells_sizes(monkeypatch):
    """At the cell's sizes: the band of three layers of 28 heads is 8.84e12
    operations a step (44.9 ms at 197 TFLOP/s), the triangle of the NoPE
    layer 6.73e12 (34.2 ms), even routing's 49,152 rows a step 1.74e12 (8.8
    ms) against 2.3 GB: all bound by compute.  No scope, no trace, another
    configuration: nothing."""
    from benchmark import trace_scopes

    window = manifest.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "readers", "window_attn.py"),
        "rf_window_attn_st")
    cell = manifest.cell(CELL)
    cfg, tr = cell["config"], cell["traffic"]
    assert (window.causal_pairs(16384), window.band_pairs(16384, 4096)) == (
        134_225_920, 58_722_304)
    assert window.band_pairs(16384, 4095) == 58_710_015
    assert window.band_pairs(16384, 4097) == 58_734_592
    ops_w, bytes_w = window.window_attn_work(cfg, tr)
    assert ops_w == 3 * 28 * 58_722_304 * 7 * 2 * 128
    assert ops_w / 197e12 == pytest.approx(44.9e-3, rel=0.01)
    assert ops_w / 197e12 > bytes_w / 819e9
    ops_f, bytes_f = window.full_attn_work(cfg, tr)
    assert ops_f == 28 * 134_225_920 * 7 * 2 * 128
    assert ops_f / 197e12 == pytest.approx(34.2e-3, rel=0.01)
    ref = manifest.reference(cfg)
    parts = ref.forward_flops_per_row(cfg, 16384)
    assert ops_w == 3.5 * parts["attn_window"]
    assert ops_f == 3.5 * parts["attn_full"]
    steps = 3
    even = {f"moe{i}": [1536 * steps] * 8 for i in range(4)}
    ops_e, bytes_e = _reader_module().moe_reglu_work(cfg, even, steps)
    assert ops_e == 4 * 12288 * 9 * 2 * 2560 * 768 == 3 * parts["experts"]
    assert ops_e / 197e12 == pytest.approx(8.83e-3, rel=0.01)
    assert ops_e / 197e12 > bytes_e / 819e9
    facts = _facts(cell, steps, expert_load=even)
    monkeypatch.setattr(trace_scopes, "scope_ns",
                        lambda parsed, scopes: 100e6 * steps)
    for name, least in (("roofline_pct.attn_window4k", ops_w / 197e12),
                        ("roofline_pct.attn_nope_full", ops_f / 197e12),
                        ("roofline_pct.moe_reglu", ops_e / 197e12)):
        read, args = manifest.layer_metric_reader(name)
        assert read(facts, **args) == pytest.approx(100 * least / 0.1,
                                                    rel=1e-9)
        assert 0 < read(facts, **args) < 100
        other = _facts(manifest.cell(OURO), steps, expert_load=even)
        assert read(other, **args) is None
    # Laguna-XS.2's cell has windows but no ReLU-gated experts
    read, args = manifest.layer_metric_reader("roofline_pct.moe_reglu")
    assert read(_facts(manifest.cell(LAGUNA), steps, expert_load=even),
                **args) is None
    assert read(_facts(cell, steps), **args) is None      # no counter
    monkeypatch.setattr(trace_scopes, "scope_ns", lambda parsed, scopes: None)
    for name in NEW_METRICS:
        read, args = manifest.layer_metric_reader(name)
        if "scopes" in args:
            assert read(facts, **args) is None
            assert read({"_trace_scopes": None}, **args) is None


def test_counter_shares_read_nothing_without_their_counters(own_registry):
    cell = manifest.cell(CELL)
    for name in ("attn_window4k_share_pct", "moe_gate_zero_share_pct"):
        read, args = manifest.layer_metric_reader(name)
        assert read(_facts(cell, 3), **args) is None
        assert read(_facts(manifest.cell(OURO), 3), **args) is None
