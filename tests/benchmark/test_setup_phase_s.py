"""The per-layer metrics ``setup_phase_s.*`` (PR 52): the reader
``benchmark/layer_metrics/readers/setup_phase_s.py`` on hand-made facts, on
what a run of the LSTM cell at toy size prints in its note line, and the eight
entries of ``BENCHMARK.json``: where they stand and which cells hold them."""

import ast
import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402

from test_harness import FAKE_TPU, policy, tiny_cell  # noqa: E402,F401

LSTM = "lstm-trainer-b256-t640"
P = "/paddle_tpu/setup/"
STEP = P + "first_iteration/first_step/"
#: metric -> (layer, the events it sums)
EIGHT = {
    "setup_phase_s.import": ("entry points and compile cache",
                             [P + "import"]),
    "setup_phase_s.trainer_build": ("trainer", [P + "trainer_build"]),
    "setup_phase_s.data_start": ("trainer", [P + "first_iteration/data"]),
    "setup_phase_s.first_step_trace": ("trainer", [STEP + "trace"]),
    "setup_phase_s.first_step_lower": ("trainer", [STEP + "lower"]),
    "setup_phase_s.first_step_load": ("trainer", [STEP + "compile",
                                                  STEP + "cache_load"]),
    "setup_phase_s.first_step_other": ("trainer", [STEP + "other"]),
    "setup_phase_s.flops_trace": ("trainer",
                                  [P + "first_iteration/flops_trace"]),
}
#: the cells whose tests hold their per-layer set with ``==``
PINNED = ["lfm2moe-train-b1-t8192", "kanana2moe-train-b1-t8192",
          "qwen3next-train-b1-t8192", "nemotron3nano-train-b1-t4096",
          "keyevl2-train-b1-t16384", "lagunaxs2-train-b1-t16384",
          "seq2seq-train-dp4"]


def _read(name, facts):
    read, args = manifest.layer_metric_reader(name)
    return read(facts, **args)


# -- the reader ---------------------------------------------------------------


@pytest.mark.parametrize("seen, want", [
    ({STEP + "compile": 20.0, STEP + "cache_load": 1.5}, 21.5),
    ({STEP + "cache_load": 1.5, STEP + "trace": 7.0}, 1.5),   # a warm run
    ({STEP + "compile": 0.0}, 0.0),                           # read, and 0
    ({STEP + "trace": 7.0, "/jax/core/compile/backend_compile_duration": 9.0},
     None),
    ({}, None),
])
def test_reader_sums_the_events_it_is_given_and_none_where_none_is_there(
        seen, want):
    assert _read("setup_phase_s.first_step_load",
                 {"setup_durations": seen}) == want


@pytest.mark.parametrize("metric", EIGHT)
def test_reader_returns_none_on_a_program_that_publishes_nothing(metric):
    """The parent commit's note line: JAX's own events and no other."""
    jax_only = {"/jax/core/compile/jaxpr_trace_duration": 31.0,
                "/jax/core/compile/backend_compile_duration": 40.0}
    assert _read(metric, {"setup_durations": jax_only}) is None
    assert _read(metric, {}) is None


def test_reader_imports_nothing_of_the_program():
    path = os.path.join(ROOT, "benchmark", "layer_metrics", "readers",
                        "setup_phase_s.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    assert not [n for n in ast.walk(tree)
                if isinstance(n, (ast.Import, ast.ImportFrom))]


# -- what a run prints --------------------------------------------------------


@pytest.fixture
def note_line(policy, capsys, monkeypatch):  # noqa: F811
    """One run of the LSTM cell at toy size, as the harness drives it, in a
    process whose set-up record is open: the note line's events.  The CPU
    has no peak, so the MFU gauge (and its ``flops_trace``) is given one."""
    from paddle_tpu.obs import reset_setup, setup_record
    from benchmark import run

    monkeypatch.setattr(policy, "obs_peak_flops", 1e12)
    reset_setup().add("import", time.perf_counter() - 0.25)
    try:
        cell = tiny_cell(LSTM)
        line = run.measure(cell, manifest.reference(cell["config"]),
                           manifest.runner(cell["traffic"]["runner"]), 5,
                           0.3, 0, FAKE_TPU)
    finally:
        setup_record().closed = True
    assert line["correct"] is True
    notes = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if "setup_compile_events_s" in ln]
    return notes[-1]


def test_every_run_of_the_lstm_cell_prints_the_phases_and_all_eight_read(
        note_line):
    events = note_line["setup_compile_events_s"]
    values = {m: _read(m, {"setup_durations": events}) for m in EIGHT}
    assert all(v is not None and v >= 0 for v in values.values()), values
    # the 0.25 s handed in, and a subpackage this process had not imported
    assert 0.25 <= values["setup_phase_s.import"] < note_line["setup_s"]
    # the first call's four parts are its whole time, and the build and the
    # first iteration lie inside set-up, before the window
    step = events[STEP.rstrip("/")]
    assert sum(values["setup_phase_s.first_step_" + p] for p in (
        "trace", "lower", "load", "other")) == pytest.approx(step, abs=1e-3)
    assert (values["setup_phase_s.trainer_build"]
            + events[P + "first_iteration"]) < note_line["setup_s"]
    # the program's events do not enter the accepted compile_s
    assert _read("compile_s", {"setup_durations": events}) == sum(
        v for k, v in events.items() if k.startswith("/jax/") and k.rsplit(
            "/", 1)[1] in ("jaxpr_trace_duration",
                           "jaxpr_to_mlir_module_duration",
                           "backend_compile_duration",
                           "cache_retrieval_time_sec"))


# -- BENCHMARK.json -----------------------------------------------------------


@pytest.mark.parametrize("metric", EIGHT)
def test_new_metric_is_the_lstm_cells_alone_and_says_what_it_reads(metric):
    layer, events = EIGHT[metric]
    entry = next(m for m in manifest.benchmark_json()["per_layer"]
                 if m["name"] == metric)
    assert entry == {"name": metric, "unit": "s", "better": "lower",
                     "source": "program_span", "layer": layer,
                     "moves": "setup_s", "workloads": [LSTM]}
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "setup_phase_s"
    assert spec["args"] == {"events": events}
    assert "`" + events[0][len(P):] + "`" in spec["what"]
    for event in events:
        assert "`/" + event.rsplit("/", 1)[1] + "`" in spec["what"] or (
            "`" + event[len(P):] + "`" in spec["what"])


def test_the_eight_follow_the_accepted_metrics_in_their_order():
    """Positions relative to neighbours only: a later PR appends after."""
    names = [m["name"] for m in manifest.benchmark_json()["per_layer"]]
    at = [names.index(m) for m in EIGHT]
    assert at == list(range(at[0], at[0] + 8))
    assert names.index("attn_band_share_pct") < at[0]       # PR 50's last
    assert names.index("compile_s") < at[0]                 # and it stays
    assert names.index("idle_ms_per_step.prefetch_overlap") < at[0]


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  manifest.benchmark_json()["workloads"]])
def test_only_the_lstm_cell_holds_them(cell):
    held = {m["name"] for m in manifest.cell(cell)["per_layer"]}
    if cell == LSTM:
        assert set(EIGHT) <= held
    else:
        assert not set(EIGHT) & held
    assert cell == LSTM or cell in PINNED or cell == "seq2seq-train-b384-s96"
