"""The cell ``ouro-train-b1-t4096`` (PR 54) through the runner
``trainer_loop_large`` on the CPU at a toy size: the manifest finds the
cell's files, the configuration holds the published widths and 509,661,185
parameters stored once, a sound program is ``correct``, the fp8 control is
not; and the reader this PR adds (``loop``) on facts written by hand.

Where this file says where the cell's entries stand in ``BENCHMARK.json`` it
says so RELATIVE to Laguna-XS.2's (after them, in their own order), never as
"the last": the next PR that adds a cell appends after them
(tests/conftest.py ``OUTDATED_PINS`` says what became of pins that did)."""

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

CELL = "ouro-train-b1-t4096"
CONFIG = "ouro-2.6b-loop4"
LAGUNA = "lagunaxs2-train-b1-t16384"
FAKE_TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
#: in the order BENCHMARK.json has them
NEW_METRICS = ["device_ms_per_step.loop_attn", "device_ms_per_step.loop_mlp",
               "device_ms_per_step.loop_last_pass",
               "device_ms_per_step.exits", "roofline_pct.loop_attn_core",
               "roofline_pct.exit_heads", "loop_expected_exit_step"]
LAGUNA_LAST_METRICS = ["roofline_pct.attn_window", "roofline_pct.attn_full",
                       "attn_band_share_pct"]
#: hidden 64, 4 heads of 16 over 4 key-value heads, an MLP of 96, a
#: vocabulary of 50, TWO layers run the cell's four times, T 64: the widths
#: and the depth are toys, the code path (runner, reference, comparison,
#: result line, counters) the cell's
TINY_CONFIG = dict(hidden_size=64, num_attention_heads=4,
                   num_key_value_heads=4, head_dim=16, intermediate_size=96,
                   vocab_size=50, num_hidden_layers=2,
                   layer_types=["full_attention"] * 2, num_dense_layers=2,
                   recompute_layers=[0, 1])
TINY_TRAFFIC = dict(batch=2, seq_len=64, ring=4)
#: the catalog row's ``config`` (architectures.jsonl, Ouro-2.6B)
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}
REDUCED = ["layer_types", "num_hidden_layers"]


def tiny_cell(limits=True):
    """The cell at a toy size, with limits read at that size on the CPU by
    the rule the chip's were (``check_correct.suggest_limits``: 8 sound
    seeds, 4 control seeds)."""
    cell = copy.deepcopy(manifest.cell(CELL))
    cell["config"].update(TINY_CONFIG)
    cell["traffic"].update(TINY_TRAFFIC)
    if limits:
        with open(os.path.join(HERE, "tiny_limits_ouro.json")) as f:
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
    assert (tr["runner"], tr["batch"], tr["seq_len"], tr["lengths"],
            tr["ring"], tr["prefetch_depth"]) == (
        "trainer_loop_large", 1, 4096, "full", 8, 2)
    ref = manifest.reference(cfg)
    prog = manifest.program(cfg)
    assert all(callable(getattr(prog, f)) for f in (
        "require", "net", "trainer", "expert_load",
        "uncomputed_assignments"))
    assert prog.expert_load([]) == {} and prog.uncomputed_assignments() == 0.0
    # every published width, all four passes, the whole vocabulary
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["total_ut_steps"], cfg["rope_theta"], cfg["rms_norm_eps"],
            cfg["early_exit_threshold"]) == (
        2048, 16, 16, 128, 5632, 49152, 4, 1000000, 1e-6, 1)
    assert cfg["layer_types"] == ["full_attention"] * 6
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"]) == (6, 6)
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "layer_types": "full_attention, 48 entries"}
    assert cfg["exit_beta"] == 0.1
    for said in ("nothing is sliced", "layers 0-5", "seven further stages",
                 "four times", "embedding", "final norm", "exit gate",
                 "3.4%", "22%"):
        assert said in cfg["deployment"], said
    assert not cfg["tie_word_embeddings"] and not cfg["amp"]
    assert (cfg["param_dtype"], cfg["compute_dtype"]) == ("float32",
                                                         "bfloat16")
    assert cfg["recompute_layers"] == [0, 1, 2, 3, 4, 5]
    assert cfg["optimizer"] == {"kind": "adam", "learning_rate": 1e-05,
                                "beta1": 0.9, "beta2": 0.999,
                                "epsilon": 1e-08}
    assert all(cfg.get(k) for k in ("deployment", "assumed", "why",
                                    "optimizer_note"))
    assert {"sandwich_norms", "norm_after_every_pass", "exit_gate",
            "exit_distribution", "objective", "early_exit_threshold",
            "attention", "mlp", "stds", "num_dense_layers"} == set(
                cfg["assumed"])
    # each assumption names its other reading; stage II is said not built
    for k in ("sandwich_norms", "norm_after_every_pass", "exit_gate",
              "exit_distribution", "objective"):
        assert "other reading" in cfg["assumed"][k], k
    assert "NOT built" in cfg["assumed"]["objective"]
    entry = next(c for c in manifest.benchmark_json()["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json")
    assert entry["file"] == "benchmark/configs/ouro-2.6b-loop4.json"
    assert sorted(entry["reduced"]) == sorted(cfg["published"]) == REDUCED
    shapes = ref.param_shapes(cfg)
    count = {k: int(np.prod(s)) for k, (s, _) in shapes.items()}
    assert sum(count.values()) == 509_661_185    # ISSUE 54's arithmetic
    assert count["_emb.w0"] == count["_cost.w"] == 100_663_296
    layer = lambda i: sum(v for k, v in count.items()  # noqa: E731
                          if k.split(".")[0] in (
        f"_attn{i}", f"_mlp{i}", f"_norm_op{i}", f"_post_op{i}",
        f"_norm_ffn{i}", f"_post_ffn{i}"))
    assert [layer(i) for i in range(6)] == [51_388_416] * 6
    assert 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048 == 51_388_416
    assert count["_norm_out.w"] == 2048
    assert count["_exit_gate.w"] + count["_exit_gate.b"] == 2049
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


def test_step_flops_are_the_issues_arithmetic():
    """Forward arithmetic a token and pass: 6 x (102.76 M of products +
    16.78 M of attention at 4096) + 201.3 M of head = 918.5 MFLOP; four
    passes 3.67 GFLOP; a step 3 x that x 4096 = 45.1 TFLOP."""
    cell = manifest.cell(CELL)
    cfg, tr = cell["config"], cell["traffic"]
    ref = manifest.reference(cfg)
    parts = ref.forward_flops_per_row(cfg, 4096)
    assert ref.causal_pairs(4096) == 8_390_656
    assert parts["attn_proj"] == 4 * 6 * 4096 * 2 * 4 * 2048 * 2048
    assert parts["mlp"] == 4 * 6 * 4096 * 6 * 2048 * 5632
    assert parts["attn_core"] == 4 * 6 * 8_390_656 * 2 * 16 * 2 * 128
    assert parts["head"] == 4 * 4096 * 2 * 2048 * 49152
    assert parts["gate"] == 3 * 4096 * 2 * 2048
    token_pass = lambda k: parts[k] / 4096 / 4          # noqa: E731
    assert (token_pass("attn_proj") + token_pass("mlp")) / 6 == pytest.approx(
        102.76e6, rel=1e-4)
    assert token_pass("attn_core") / 6 == pytest.approx(16.78e6, rel=1e-3)
    assert token_pass("head") == pytest.approx(201.3e6, rel=1e-3)
    assert sum(parts.values()) / 4096 / 4 == pytest.approx(918.5e6, rel=1e-4)
    assert ref.step_flops(cfg, tr) == pytest.approx(3 * sum(parts.values()))
    assert ref.step_flops(cfg, tr) == pytest.approx(45.1e12, rel=2e-3)
    # the exits' share of a pass: 22% at six layers, 3.4% at the published 48
    exits = token_pass("head")
    layer = (token_pass("attn_proj") + token_pass("mlp")
             + token_pass("attn_core")) / 6
    assert exits / (6 * layer + exits) == pytest.approx(0.22, abs=0.005)
    assert exits / (48 * layer + exits) == pytest.approx(0.034, abs=0.001)


def test_every_number_of_the_catalog_entry_is_in_the_file():
    """The source's keys under their own names; the two reduced ones differ
    (the list cut to its first six entries) and nothing else does."""
    cfg = manifest.cell(CELL)["config"]
    differ = sorted(k for k, v in PUBLISHED.items() if cfg[k] != v)
    assert differ == REDUCED
    assert cfg["layer_types"] == PUBLISHED["layer_types"][:6]
    assert cfg["published"]["num_hidden_layers"] == PUBLISHED[
        "num_hidden_layers"]


def test_new_metrics_are_this_cells_alone_and_follow_lagunas():
    """Each metric this PR adds lists this cell and no other; the older
    cells keep exactly their sets.  Positions are RELATIVE: this cell's
    entries follow Laguna-XS.2's directly, in their own order; nothing here
    says they are the last."""
    bj = manifest.benchmark_json()
    new = [m for m in bj["per_layer"] if CELL in m.get("workloads", [])]
    assert [m["name"] for m in new] == NEW_METRICS
    assert all(m["workloads"] == [CELL]
               and m["moves"] == "train_tokens_per_s" for m in new)
    assert all(m["source"] == ("program_counter" if m["name"]
                               == "loop_expected_exit_step"
                               else "device_trace") for m in new)
    assert [m["unit"] for m in new] == ["ms"] * 4 + ["%"] * 2 + ["passes"]
    assert [m["layer"] for m in new] == ["model step"] * 4 + [
        "kernels"] * 2 + ["model step"]
    names = [m["name"] for m in bj["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + len(NEW_METRICS)] == NEW_METRICS
    assert at > names.index(LAGUNA_LAST_METRICS[-1])
    assert all(LAGUNA in bj["per_layer"][names.index(n)]["workloads"]
               for n in LAGUNA_LAST_METRICS)
    cells = [w["name"] for w in bj["workloads"]]
    assert cells.index(CELL) > cells.index(LAGUNA)
    configs = [c["name"] for c in bj["configs"]]
    assert configs.index(CONFIG) > configs.index("laguna-xs.2-ep32")
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1
    for cell in cells:
        held = {m["name"] for m in manifest.cell(cell)["per_layer"]}
        assert (set(NEW_METRICS) <= held) == (cell == CELL)
        assert cell == CELL or not set(NEW_METRICS) & held


def test_new_metrics_resolve_to_their_readers():
    six = lambda *names: [f"{n}{i}" for n in names     # noqa: E731
                          for i in range(6)]
    want = {
        "device_ms_per_step.loop_attn": ("scope_ms", {"scopes": six("attn")}),
        "device_ms_per_step.loop_mlp": ("scope_ms", {"scopes": six(
            "mlp", "norm_ffn", "post_ffn", "res_ffn")}),
        "device_ms_per_step.loop_last_pass": ("scope_ms", {"scopes": [
            "loop3"]}),
        "device_ms_per_step.exits": ("scope_ms", {"scopes": ["exits"]}),
        "roofline_pct.loop_attn_core": ("loop", {
            "kind": "loop_attn_core", "scopes": ["attn_core"]}),
        "roofline_pct.exit_heads": ("loop", {
            "kind": "exit_heads", "scopes": ["exit_head"]}),
        "loop_expected_exit_step": ("loop", {"kind": "expected_exit"})}
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
    for leaf in ("_attn0.wq", "_mlp1.w2", "_post_op0.w", "_post_ffn1.w",
                 "_norm_out.w", "_cost.w", "_exit_gate.w", "_exit_gate.b"):
        assert "grad_diff." + leaf in compared[-1]["compared"], leaf
    noted = [json.loads(l) for l in out if '"expert_load"' in l]
    assert noted[-1]["expert_load"] == {}
    # the counters the trainer fed from the step's extra outputs: four exits'
    # masses that add up to the tokens of every step, four cross-entropies,
    # one entropy; the expected exit step read from them
    from paddle_tpu.obs import get_registry

    snap = get_registry().snapshot()
    steps = sum(s["value"] for s in snap["train_batches_total"]["series"])
    mass = {int(s["labels"]["step"]): s["value"]
            for s in snap["loop_exit_mass"]["series"]}
    assert sorted(mass) == [1, 2, 3, 4] and all(m > 0 for m in mass.values())
    assert sum(mass.values()) == pytest.approx(steps * 2 * 64, rel=1e-4)
    ce = {int(s["labels"]["step"]): s["value"]
          for s in snap["loop_exit_ce"]["series"]}
    assert sorted(ce) == [1, 2, 3, 4]
    assert all(v > steps * 128 for v in ce.values())     # over ln 50 a token
    (entropy,) = snap["loop_exit_entropy"]["series"]
    assert 0 < entropy["value"] <= steps * 128 * np.log(4) * (1 + 1e-6)
    cell = tiny_cell()
    read, args = manifest.layer_metric_reader("loop_expected_exit_step")
    expected = read({"config": cell["config"], "traffic": cell["traffic"]},
                    **args)
    assert expected == pytest.approx(
        sum(t * m for t, m in mass.items()) / sum(mass.values()))
    assert 1 < expected < 4


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
    """A checkout whose program has no ``ouro_net`` (this PR's parent):
    ``require()`` exits with a message, before any weight."""
    import paddle_tpu.models as models

    prog = manifest.program(manifest.cell(CELL)["config"])
    prog.require()                               # this checkout: fine
    monkeypatch.delattr(models, "ouro_net")
    with pytest.raises(SystemExit, match="cannot run ouro-2.6b-loop4"):
        prog.require()


# -- the reader --------------------------------------------------------------


def _facts(cell, steps):
    return {"config": cell["config"], "traffic": cell["traffic"],
            "steps": steps, "peaks": manifest.peaks("TPU v5 lite"),
            "_trace_scopes": {"devices": 1}}


def _reader_module():
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "readers", "loop.py"), "rf_loop")


def test_work_functions_against_a_hand_count_at_a_tiny_shape():
    """Three passes of two layers, 2 heads of 8 over 1 key-value head, a row
    of 5, batch 2: 15 causal pairs; a head of 16 x 11."""
    mod = _reader_module()
    cfg = {"total_ut_steps": 3, "num_hidden_layers": 2,
           "layer_types": ["full_attention"] * 2,
           "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 8,
           "hidden_size": 16, "vocab_size": 11}
    tr = {"seq_len": 5, "batch": 2}
    ops, nbytes = mod.loop_attention_work(cfg, tr)
    assert ops == 6 * 2 * 2 * 15 * 7 * 2 * 8
    assert nbytes == 6 * (2 * 5 * 8 * 2) * ((2 * 2 + 2 * 1) + (4 * 2 + 4 * 1))
    ops, nbytes = mod.exit_heads_work(cfg, tr)
    assert ops == 3 * 3 * 2 * 10 * 16 * 11
    assert nbytes == 3 * (16 * 11 * 8 + 10 * 11 * 4 + 10 * 16 * 8)


def test_roofline_readers_at_the_cells_sizes(monkeypatch):
    """At the cell's sizes: 24 attention cores are 5.77e12 operations a step
    (29.3 ms at 197 TFLOP/s), four exits' head 9.90e12 (50 ms), both bound
    by compute.  No scope, no trace, another configuration: nothing."""
    from benchmark import trace_scopes

    mod = _reader_module()
    cell = manifest.cell(CELL)
    cfg, tr = cell["config"], cell["traffic"]
    ops_a, bytes_a = mod.loop_attention_work(cfg, tr)
    assert ops_a == 24 * 16 * 8_390_656 * 7 * 2 * 128
    assert ops_a / 197e12 == pytest.approx(29.3e-3, rel=0.01)
    assert ops_a / 197e12 > bytes_a / 819e9
    ops_e, bytes_e = mod.exit_heads_work(cfg, tr)
    assert ops_e == 4 * 3 * 2 * 4096 * 2048 * 49152
    assert ops_e == pytest.approx(9.90e12, rel=1e-3)
    assert ops_e / 197e12 == pytest.approx(50e-3, rel=0.01)
    assert ops_e / 197e12 > bytes_e / 819e9
    parts = manifest.reference(cfg).forward_flops_per_row(cfg, 4096)
    assert ops_a == 3.5 * parts["attn_core"]
    assert ops_e == 3 * parts["head"]
    steps = 3
    facts = _facts(cell, steps)
    monkeypatch.setattr(trace_scopes, "scope_ns",
                        lambda parsed, scopes: 100e6 * steps)
    for name, least in (("roofline_pct.loop_attn_core", ops_a / 197e12),
                        ("roofline_pct.exit_heads", ops_e / 197e12)):
        read, args = manifest.layer_metric_reader(name)
        assert read(facts, **args) == pytest.approx(100 * least / 0.1,
                                                    rel=1e-9)
        assert 0 < read(facts, **args) < 100
        other = _facts(manifest.cell(LAGUNA), steps)
        assert read(other, **args) is None
    monkeypatch.setattr(trace_scopes, "scope_ns", lambda parsed, scopes: None)
    read, args = manifest.layer_metric_reader("roofline_pct.exit_heads")
    assert read(facts, **args) is None
    assert read({"_trace_scopes": None}, **args) is None


def test_expected_exit_reads_nothing_without_the_counter(own_registry):
    cell = manifest.cell(CELL)
    read, args = manifest.layer_metric_reader("loop_expected_exit_step")
    assert read(_facts(cell, 3), **args) is None
    assert read(_facts(manifest.cell(LAGUNA), 3), **args) is None
