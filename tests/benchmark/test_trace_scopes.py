"""The readers of the program's own names (benchmark/trace_scopes.py and
the per-layer metrics of PR 25), on two traces recorded on a TPU v5e:
``spans.xplane.pb``, the LSTM cell at toy size through ``SGDTrainer.train``
(tests/benchmark/record_spans.py), and ``small.xplane.pb`` (PR 23), a trace
without any of the names, as the parent commit's are: there every reader
returns ``None`` and raises nothing."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, trace_reduce, trace_scopes  # noqa: E402

SPANS = os.path.join(HERE, "spans.xplane.pb")
NAMELESS = os.path.join(HERE, "small.xplane.pb")
LSTM, S2S = "lstm-trainer-b256-t640", "seq2seq-train-b384-s96"
IDLE = ["idle_ms_per_step.dispatch", "idle_ms_per_step.callback",
        "idle_ms_per_step.data", "idle_ms_per_step.loop"]


def _new_metrics():
    """The per-layer metrics whose reader reads the raw trace."""
    out = []
    for m in manifest.benchmark_json()["per_layer"]:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               m["name"] + ".json")) as f:
            if json.load(f)["reader"] in ("own_ms", "idle_owner_ms",
                                          "host_syncs"):
                out.append(m)
    return out


NEW = _new_metrics()


def _recorded():
    """What the recording run printed on the chip (record_spans.py)."""
    with open(os.path.join(HERE, "spans.json")) as f:
        return json.load(f)


def _steps():
    return _recorded()["steps"]


def _read(name, facts):
    read, args = manifest.layer_metric_reader(name)
    return read(facts, **args)


@pytest.fixture(scope="module")
def lstm_facts():
    """``facts`` as run.py builds it, pointed at the recorded trace; the
    readers parse it once and share the result through it."""
    return {"xplane": SPANS, "steps": _steps()}


def test_the_pr_adds_fourteen_metrics_that_are_all_lower_is_better():
    assert len(NEW) == 14
    assert {m["better"] for m in NEW} == {"lower"}
    assert {m["moves"] for m in NEW} == {"train_tokens_per_s"}
    assert all(m["workloads"] for m in NEW)


# -- the pieces -------------------------------------------------------------


def test_scope_names_drop_the_primitive():
    path = "jit(step)/jit(main)/transpose(jvp(forward))/lstm0/mul:"
    assert trace_scopes.scope_names(path) == {
        "jit", "step", "main", "transpose", "jvp", "forward", "lstm0"}
    assert "dot_general" not in trace_scopes.scope_names(
        "jit(f)/dot_general:")


def test_innermost_span_at_every_instant():
    spans = [(0.0, 10.0, "iteration"), (1.0, 2.0, "data_wait"),
             (3.0, 9.0, "step"), (4.0, 5.0, "step.dispatch"),
             (5.0, 8.0, "step.sync"), (12.0, 13.0, "iteration")]
    assert trace_scopes.innermost(spans) == [
        (0.0, 1.0, "iteration"), (1.0, 2.0, "data_wait"),
        (2.0, 3.0, "iteration"), (3.0, 4.0, "step"),
        (4.0, 5.0, "step.dispatch"), (5.0, 8.0, "step.sync"),
        (8.0, 9.0, "step"), (9.0, 10.0, "iteration"),
        (12.0, 13.0, "iteration")]


def test_idle_gaps_go_to_the_innermost_span_and_sum_to_the_idle_time():
    parsed = {"spans": [(0.0, 10.0, "iteration"), (2.0, 6.0, "step.sync")],
              "gaps": [(1.0, 3.0), (5.0, 7.0), (9.0, 12.0)], "devices": 1}
    assert trace_scopes.idle_by_owner(parsed) == {
        "iteration": 1.0 + 1.0 + 1.0, "step.sync": 1.0 + 1.0, "none": 2.0}
    assert trace_scopes.idle_by_owner(dict(parsed, spans=[])) is None


def test_the_scope_table_is_decoded_from_the_event_metadata():
    """``tf_op`` of the operations' event metadata, which ProfileData does
    not show (wire format of xplane.proto, by hand)."""
    table = trace_scopes.op_scopes(NAMELESS)
    assert list(table) == ["/device:TPU:0"]
    by_op = {op.split(" = ")[0]: scope
             for op, scope in table["/device:TPU:0"].items()}
    assert by_op["%convolution_tanh_fusion"] == "jit(<lambda>)/dot_general:"
    assert by_op["%dynamic_slice.1"] == "jit(dynamic_slice)/dynamic_slice:"


# -- on the trace with the names ---------------------------------------------


def test_recorded_trace_holds_the_programs_names():
    parsed = trace_scopes.parse(SPANS)
    assert parsed["devices"] == 1
    kernels = {name.split(" = ")[0].lstrip("%").split(".")[0]
               for name, _, _ in parsed["ops"] if trace_reduce.is_kernel(name)}
    assert kernels == {"lstm_seq_fwd", "lstm_seq_bwd"}
    held = set().union(*(trace_scopes.scope_names(scope)
                         for _, scope, _ in parsed["ops"] if scope))
    assert {"forward", "emb", "lstm0", "lstm1", "logits",
            "optimizer_apply", "transpose", "jvp"} <= held
    spans = {name for _, _, name in parsed["spans"]}
    assert {"iteration", "data_wait", "callback", "prepare", "step",
            "step.dispatch", "step.sync"} <= spans
    assert trace_scopes.iterations(parsed) == _steps()


@pytest.mark.parametrize("metric", [m["name"] for m in NEW
                                    if LSTM in m["workloads"]])
def test_lstm_cells_metric_reads_a_value(metric, lstm_facts):
    value = _read(metric, lstm_facts)
    assert value is not None and value >= 0
    if not metric.startswith("idle_ms_per_step"):
        assert value > 0
    # the slimmed trace reads what the whole one read on the chip
    assert value == pytest.approx(_recorded()["metrics"][metric]["value"])


@pytest.mark.parametrize("metric", [m["name"] for m in NEW
                                    if m["workloads"] == [S2S]])
def test_seqtoseq_only_metric_finds_nothing_in_the_lstm_trace(metric,
                                                              lstm_facts):
    assert _read(metric, lstm_facts) is None


def test_identities_scopes_sum_to_busy_and_owners_to_idle(lstm_facts):
    parsed = trace_scopes.trace_of(lstm_facts)
    steps = lstm_facts["steps"]
    w0, w1 = parsed["window"]
    busy_ms = parsed["busy_ns"] / steps / 1e6
    idle_ms = ((w1 - w0) - parsed["busy_ns"]) / steps / 1e6
    # the same window and busy time as PR 23's reduction
    summary = trace_reduce.reduce_trace(SPANS, window_span="bench.window")
    assert summary["busy_s"] * 1e3 / steps == pytest.approx(busy_ms)
    assert summary["window_s"] * 1e3 / steps == pytest.approx(
        (w1 - w0) / steps / 1e6)
    # scoped + unscoped own time = busy time (the union of the intervals)
    report = trace_scopes.report(
        SPANS, steps, ["lstm0", "lstm1", "optimizer_apply"])
    assert set(report["scope_ms"]) == {"lstm0", "lstm1", "optimizer_apply",
                                       "unscoped"}
    assert sum(report["scope_ms"].values()) == pytest.approx(busy_ms)
    assert _read("device_ms_per_step.lstm_layers", lstm_facts) == \
        pytest.approx(report["scope_ms"]["lstm0"]
                      + report["scope_ms"]["lstm1"])
    assert _read("device_ms_per_step.optimizer", lstm_facts) == \
        pytest.approx(report["scope_ms"]["optimizer_apply"])
    # the kernels run inside their layers' scopes
    assert _read("kernel_ms_per_step.lstm", lstm_facts) < \
        _read("device_ms_per_step.lstm_layers", lstm_facts) < busy_ms
    # the four owners of the idle time sum to it
    assert sum(_read(m, lstm_facts) for m in IDLE) == pytest.approx(idle_ms)
    # guard and loss: two blocking fetches a step
    assert _read("host_syncs_per_step", lstm_facts) == 2.0


# -- on a trace without the names ---------------------------------------------


@pytest.mark.parametrize("metric", [m["name"] for m in NEW])
def test_reader_returns_none_on_a_trace_without_the_names(metric):
    assert _read(metric, {"xplane": NAMELESS, "steps": 4}) is None


def test_no_trace_at_all_is_none(tmp_path, monkeypatch):
    monkeypatch.setattr(trace_scopes, "TRACE_ROOT", str(tmp_path))
    assert trace_scopes.newest_trace(str(tmp_path)) is None
    for m in NEW:
        assert _read(m["name"], {"steps": 4}) is None
