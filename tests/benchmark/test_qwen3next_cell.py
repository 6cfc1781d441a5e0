"""The cell ``qwen3next-train-b1-t8192`` (PR 41) through the runner
``trainer_loop_large`` on the CPU at a toy size: the manifest finds the
cell's files, the configuration holds the published widths and 424.3 M
parameters, a sound program is ``correct``, the fp8 control is not; and the
reader this PR adds (``roofline_gdn``) on facts written by hand."""

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

CELL = "qwen3next-train-b1-t8192"
CONFIG = "qwen3-next-80b-a3b-ep32"
KANANA2 = "kanana2moe-train-b1-t8192"
FAKE_TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
NEW_METRICS = ["device_ms_per_step.gated_attn", "device_ms_per_step.gdn",
               "device_ms_per_step.gdn_proj", "device_ms_per_step.gdn_scan",
               "kernel_ms_per_step.gdn", "roofline_pct.gdn_scan"]
#: hidden 64; delta nets of 2 key and 4 value heads of 16; attention of 4
#: heads of 16 over 2 key-value heads; 8 experts of 48 with 2 held, top 3, a
#: shared expert of 32; 4 layers, T 128 (two chunks of the scan): the widths
#: are toys, the code path (runner, reference, comparison, result line) the
#: cell's
TINY_CONFIG = dict(hidden_size=64, linear_num_key_heads=2,
                   linear_num_value_heads=4, linear_key_head_dim=16,
                   linear_value_head_dim=16, num_attention_heads=4,
                   num_key_value_heads=2, head_dim=16,
                   moe_intermediate_size=48,
                   shared_expert_intermediate_size=32, router_outputs=8,
                   num_experts=2, num_experts_per_tok=3, vocab_size=50)
TINY_TRAFFIC = dict(batch=2, seq_len=128, ring=4)


def tiny_cell(limits=True):
    """The cell at a toy size, with limits read at that size on the CPU by
    the rule the chip's were (``check_correct.suggest_limits``: 8 sound
    seeds, 4 control seeds)."""
    cell = copy.deepcopy(manifest.cell(CELL))
    cell["config"].update(TINY_CONFIG)
    cell["traffic"].update(TINY_TRAFFIC)
    if limits:
        with open(os.path.join(HERE, "tiny_limits_qwen3next.json")) as f:
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
    # every published width, the router's outputs, experts a token
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["partial_rotary_factor"],
            cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
            cfg["linear_conv_kernel_dim"], cfg["full_attention_interval"],
            cfg["router_outputs"], cfg["num_experts_per_tok"],
            cfg["rope_theta"], cfg["rms_norm_eps"]) == (
        2048, 5120, 512, 512, 16, 2, 256, 0.25, 16, 32, 128, 128, 4, 4, 512,
        10, 10000000, 1e-6)
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["first_expert"], cfg["vocab_size"]) == (4, 16, 0, 18992)
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                "vocab_size": 151936}
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_experts"] * 32 == cfg["published"]["num_experts"]
    # what the runner reads is what the source's own keys say
    assert cfg["layer_types"] == ["linear_attention"] * 3 + ["full_attention"]
    assert cfg["layer_types"] == [
        "full_attention" if ref.is_full_attention(cfg, i)
        else "linear_attention" for i in range(4)]
    assert cfg["num_dense_layers"] == 0 and cfg["mlp_only_layers"] == []
    assert not cfg["tie_word_embeddings"] and not cfg["amp"]
    assert cfg["optimizer"]["learning_rate"] == 1e-5
    assert all(cfg.get(k) for k in ("deployment", "assumed", "why"))
    entry = next(c for c in manifest.benchmark_json()["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"]
    assert sorted(entry["reduced"]) == sorted(cfg["published"]) == sorted(
        ["num_hidden_layers", "num_experts", "vocab_size"])
    shapes = ref.param_shapes(cfg)
    count = sum(int(np.prod(s)) for s, _ in shapes.values())
    assert count == 424_340_544                  # ISSUE 41's 424.3 M
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
    per_token = ref.forward_flops_per_token(cfg, 8192)
    # ISSUE 41: the three delta nets about 225 of 467 MFLOP a token forward,
    # the fourth layer's attention another 122
    assert per_token["gdn_proj"] + per_token["gdn_scan"] == pytest.approx(
        225e6, rel=0.1)
    assert per_token["attn_proj"] + per_token["attn_core"] == pytest.approx(
        122e6, rel=0.01)
    assert per_token["attn_core"] == 8192 * 16 * 512
    assert sum(per_token.values()) == pytest.approx(467e6, rel=0.05)
    assert ref.step_flops(cfg, cell["traffic"]) == pytest.approx(
        3 * sum(per_token.values()) * 8192)


def test_every_number_of_the_catalog_entry_is_in_the_file():
    """The source's shape-bearing keys under their own names; the three
    reduced ones differ and nothing else does."""
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    cfg = manifest.cell(CELL)["config"]
    differ = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differ == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert all(cfg["published"][k] == published[k] for k in differ)


def test_new_metrics_are_this_cells_alone():
    """The older cells keep exactly their sets: each metric this PR adds
    lists this cell and no other, and Kanana-2's five still list Kanana-2's
    alone."""
    bj = manifest.benchmark_json()
    new = [m for m in bj["per_layer"] if CELL in m.get("workloads", [])]
    assert sorted(m["name"] for m in new) == NEW_METRICS
    assert all(m["workloads"] == [CELL]
               and m["moves"] == "train_tokens_per_s" for m in new)
    assert [m["name"] for m in bj["per_layer"][-6:]] == [
        "device_ms_per_step.gdn", "device_ms_per_step.gdn_proj",
        "device_ms_per_step.gdn_scan", "device_ms_per_step.gated_attn",
        "kernel_ms_per_step.gdn", "roofline_pct.gdn_scan"]
    kanana = [m for m in bj["per_layer"] if KANANA2 in m.get("workloads", [])]
    assert len(kanana) == 5 and all(m["workloads"] == [KANANA2]
                                    for m in kanana)
    names = [w["name"] for w in bj["workloads"]]
    assert names.index(CELL) == names.index(KANANA2) + 1 == len(names) - 1
    assert [c["name"] for c in bj["configs"]][-2:] == [
        "kanana-2-30b-a3b-ep8", CONFIG]
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1
    # PR 38's eight parts of the trainer's idle stay the LSTM cell's alone,
    # in their order, just before this PR's six
    eight = [m["name"] for m in bj["per_layer"][-14:-6]]
    assert eight == ["idle_ms_per_step." + part for part in (
        "sync_guard", "sync_loss", "rng", "step_host", "bookkeeping",
        "unnamed", "sync_head", "prefetch_overlap")]
    for cell in names:
        held = {m["name"] for m in manifest.cell(cell)["per_layer"]}
        assert (set(eight) <= held) == (cell == "lstm-trainer-b256-t640")
        assert cell == "lstm-trainer-b256-t640" or not set(eight) & held


def test_new_metrics_resolve_to_their_readers():
    want = {"device_ms_per_step.gdn": ("scope_ms",
                                       {"scopes": ["gdn0", "gdn1", "gdn2"]}),
            "device_ms_per_step.gdn_proj": ("scope_ms",
                                            {"scopes": ["gdn_proj"]}),
            "device_ms_per_step.gdn_scan": ("scope_ms",
                                            {"scopes": ["gdn_scan"]}),
            "device_ms_per_step.gated_attn": ("scope_ms",
                                              {"scopes": ["attn3"]}),
            "kernel_ms_per_step.gdn": ("kernel_ms", {"kernels": [
                "gdn_chunk_fwd", "gdn_chunk_bwd"]}),
            "roofline_pct.gdn_scan": ("roofline_gdn",
                                      {"scopes": ["gdn_scan"]})}
    assert sorted(want) == NEW_METRICS
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
    assert "grad_diff._gdn0.a_log" in compared[-1]["compared"]
    assert "grad_diff._moe0.shared_gate" in compared[-1]["compared"]
    noted = [json.loads(l) for l in out if '"expert_load"' in l]
    assert sorted(noted[-1]["expert_load"]) == ["moe0", "moe1", "moe2", "moe3"]
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
    """A checkout whose program has no ``qwen3_next_net`` (this PR's
    parent): ``require()`` exits with a message, before any weight."""
    import paddle_tpu.models as models

    prog = manifest.program(manifest.cell(CELL)["config"])
    prog.require()                               # this checkout: fine
    monkeypatch.delattr(models, "qwen3_next_net")
    with pytest.raises(SystemExit,
                       match="cannot run qwen3-next-80b-a3b-ep32"):
        prog.require()


# -- the reader ------------------------------------------------------------------


def _facts(cell, steps):
    return {"config": cell["config"], "traffic": cell["traffic"],
            "steps": steps, "peaks": manifest.peaks("TPU v5 lite"),
            "_trace_scopes": {"devices": 1}}


def _reader_module():
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "readers", "roofline_gdn.py"),
        "rf_gdn")


def test_gdn_roofline_count_against_a_hand_count_at_a_tiny_shape():
    """One layer in three a delta net... here: 2 layers of which the second
    is full attention, 2 value heads, heads of 8 and 16, a row of 128 = 2
    chunks, batch 3."""
    mod = _reader_module()
    cfg = {"linear_num_value_heads": 2, "linear_key_head_dim": 8,
           "linear_value_head_dim": 16, "full_attention_interval": 2,
           "num_hidden_layers": 2}
    ops, nbytes = mod.gdn_scan_work(cfg, {"seq_len": 128, "batch": 3})
    C = 64
    chunk = 2 * (C * C * 8      # k k^T
                 + C * C * 8    # q k^T
                 + C * C * 16   # T R
                 + C * C * 16   # P Vn
                 + 3 * C * 8 * 16)      # Kb S, Qe S, Ke^T Vn
    assert ops == 1 * 3 * 2 * 2 * 3 * chunk     # layers B heads chunks x3
    rows = 1 * 3 * 2 * 128
    assert nbytes == (rows * 2 * (8 + 8 + 16 + 16)          # q k v -> o
                      + rows * 2 * (8 + 8 + 16 + 16)        # q k v do ->
                      + rows * 2 * (8 + 8 + 16)             # dq dk dv
                      + 1 * 3 * 2 * 2 * 8 * 16 * 4 * 2)     # states, twice


def test_gdn_roofline_reader_at_the_cells_sizes(monkeypatch):
    """At the cell's sizes: 10.49 M operations a chunk and head forward,
    x 3 x 32 heads x 128 chunks x 3 layers = 3.87e11 (1.96 ms at 197
    TFLOP/s); 3.8 GB moved (4.66 ms at 819 GB/s): the scan is bound by
    memory, and a scope that takes 93.2 ms reads 5%.  No scope, no trace or
    another configuration reads nothing."""
    from benchmark import trace_scopes

    mod = _reader_module()
    read, args = manifest.layer_metric_reader("roofline_pct.gdn_scan")
    cell = manifest.cell(CELL)
    ops, nbytes = mod.gdn_scan_work(cell["config"], cell["traffic"])
    assert ops == 3 * 32 * 128 * 3 * 10_485_760
    assert ops / 197e12 < nbytes / 819e9
    assert nbytes / 819e9 == pytest.approx(4.66e-3, rel=0.01)
    # an under-count: the program's kernels run the forward's products twice
    # (recomputation) and the backward's own 17 beside the forward's 7 again
    assert mod.CHUNK == 64
    steps = 10
    facts = _facts(cell, steps)
    monkeypatch.setattr(trace_scopes, "scope_ns",
                        lambda parsed, scopes: 93.2e6 * steps)
    assert read(facts, **args) == pytest.approx(5.0, rel=0.01)
    other = _facts(manifest.cell(KANANA2), steps)
    assert read(other, **args) is None
    monkeypatch.setattr(trace_scopes, "scope_ns", lambda parsed, scopes: None)
    assert read(facts, **args) is None
    assert read({"_trace_scopes": None}, **args) is None


def test_reader_chunk_is_the_programs():
    from paddle_tpu.ops import delta_rule as DR

    assert _reader_module().CHUNK == DR.CHUNK
