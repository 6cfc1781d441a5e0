"""The parts of ``idle_ms_per_step.loop`` (benchmark/trace_spans.py and the
reader ``idle_parts``, PR 38): on hand-made spans and gaps, on the traces the
older readers are tested on (``spans.xplane.pb`` has ``step.sync`` with its
``reason`` and none of the new names, as the parent commit's traces;
``small.xplane.pb`` has no name at all), and on ``spans_parts.xplane.pb``,
the LSTM cell at toy size recorded on a TPU v5e by
tests/benchmark/record_spans.py from the tree that names the whole
turn-around (``spans_parts.json``: what that run printed)."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, trace_scopes, trace_spans  # noqa: E402

PARTS_TRACE = os.path.join(HERE, "spans_parts.xplane.pb")
OLD_VOCABULARY = os.path.join(HERE, "spans.xplane.pb")
NAMELESS = os.path.join(HERE, "small.xplane.pb")
LSTM = "lstm-trainer-b256-t640"
SIX = list(trace_spans.PARTS)
OVERLAYS = ["sync_head", "prefetch_overlap"]
EIGHT = ["idle_ms_per_step." + p for p in SIX + OVERLAYS]
ACCEPTED = ["idle_ms_per_step.dispatch", "idle_ms_per_step.callback",
            "idle_ms_per_step.data", "idle_ms_per_step.loop"]


def _read(name, facts):
    read, args = manifest.layer_metric_reader(name)
    return read(facts, **args)


# -- hand-made spans and gaps -------------------------------------------------

#: one iteration of the loop, ns: (start, end, name, reason)
ITERATION = [
    (0, 100, "iteration", None), (1, 5, "poll", None),
    (6, 10, "data_wait", None), (11, 12, "callback", None),
    (13, 14, "prepare", None), (15, 80, "step", None),
    (16, 24, "step.rng", None), (25, 35, "step.dispatch", None),
    (36, 40, "step.post", None), (41, 60, "step.sync", "guard"),
    (62, 70, "step.sync", "loss"), (72, 79, "step.counters", None),
    (82, 88, "extras", None), (89, 91, "callback", None),
    (92, 99, "close", None)]
#: the device is busy 30-50 and 52-55 of every 100 ns
BUSY = [(30, 50), (52, 55)]


def _synthetic(iterations=3, prefetch=(), loop=ITERATION):
    """``facts`` as the two readers leave them after their parses: the
    loop's spans by name for ``trace_scopes``, by owner for ``trace_spans``."""
    names, owners, gaps = [], [], []
    for k in range(iterations):
        t = 100.0 * k
        for s, e, name, reason in loop:
            names.append((t + s, t + e, name))
            owners.append((t + s, t + e,
                           f"{name}:{reason}" if reason else name))
        edges = [t] + [t + x for b in BUSY for x in b] + [t + 100]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)]
    parsed = {"devices": 1, "window": (0.0, 100.0 * iterations),
              "gaps": gaps, "spans": names}
    return {"_trace_scopes": parsed, "steps": iterations,
            "_trace_spans": {"loop": owners,
                             "prefetch": sorted(prefetch)}}


def _ms(ns, steps=3):
    return ns / steps / 1e6


@pytest.mark.parametrize("part, ns", [
    ("sync_guard", 2 + 5),           # 41-60: idle 50-52 and 55-60
    ("sync_loss", 8), ("rng", 8),
    ("step_host", 0 + 7),            # step.post is under the busy device
    ("bookkeeping", 4 + 6 + 7),
    ("unnamed", 10 + 7)])            # iteration's own and step's own
def test_each_part_gets_the_idle_time_of_its_spans(part, ns):
    facts = _synthetic()
    assert _read("idle_ms_per_step." + part, facts) == pytest.approx(
        _ms(3 * ns))


@pytest.mark.parametrize("devices", [1, 2])
def test_six_parts_sum_to_what_the_loop_metric_reads(devices):
    facts = _synthetic()
    parsed = facts["_trace_scopes"]
    parsed["devices"] = devices
    parsed["gaps"] = sorted(parsed["gaps"] * devices)
    loop = _read("idle_ms_per_step.loop", facts)
    assert loop > 0
    assert sum(_read("idle_ms_per_step." + p, facts)
               for p in SIX) == pytest.approx(loop, rel=1e-12)
    # and with the three accepted owners to the window's idle time
    total = sum(_read(m, facts) for m in ACCEPTED)
    assert total == pytest.approx(_ms(3 * (100 - 20 - 3)), rel=1e-12)


def test_a_span_without_a_part_is_unnamed_so_the_sum_holds():
    loop = [(s, e, "eval" if n == "close" else n, r)
            for s, e, n, r in ITERATION]
    facts = _synthetic(loop=loop)
    assert _read("idle_ms_per_step.bookkeeping", facts) == pytest.approx(
        _ms(3 * 10))
    assert sum(_read("idle_ms_per_step." + p, facts) for p in SIX) == \
        pytest.approx(_read("idle_ms_per_step.loop", facts), rel=1e-12)


@pytest.mark.parametrize("busy, sync, head", [
    # the device begins 4 ns into the fetch: the launch is what waited
    ([(45, 58)], (41, 60), 4),
    # busy when the fetch begins: what follows is the tail
    ([(30, 50)], (41, 60), 0),
    # idle all through the fetch: nothing starts in it, all tail
    ([(30, 40)], (41, 60), 0),
    # the device starts as the fetch ends: not inside it
    ([(60, 70)], (41, 60), 0),
    # two starts inside: the first one ends the head
    ([(44, 46), (50, 55)], (41, 60), 3)],
    ids=["late_launch", "busy_at_the_start", "idle_throughout",
         "starts_at_the_end", "first_start_only"])
def test_sync_head_is_the_idle_before_the_device_starts_inside_a_fetch(
        busy, sync, head):
    edges = [0.0] + [float(x) for b in busy for x in b] + [100.0]
    parsed = {"devices": 1, "window": (0.0, 100.0),
              "gaps": [(edges[i], edges[i + 1])
                       for i in range(0, len(edges), 2)
                       if edges[i + 1] > edges[i]]}
    spans = {"loop": [(0.0, 100.0, "iteration"), (16.0, 24.0, "step.rng"),
                      (float(sync[0]), float(sync[1]), "step.sync:guard")],
             "prefetch": []}
    assert trace_spans.sync_head_ns(parsed, spans) == head
    facts = {"_trace_scopes": dict(parsed, spans=[]), "steps": 2,
             "_trace_spans": spans}
    assert _read("idle_ms_per_step.sync_head", facts) == pytest.approx(
        _ms(head, 2))


def test_sync_head_on_two_devices_is_their_mean():
    parsed = {"devices": 2, "window": (0.0, 100.0),
              "gaps": sorted([(0.0, 45.0), (58.0, 100.0),      # device 0
                              (0.0, 30.0), (50.0, 100.0)])}    # device 1
    spans = {"loop": [(41.0, 60.0, "step.sync:loss")], "prefetch": []}
    assert trace_spans.sync_head_ns(parsed, spans) == 4 / 2


@pytest.mark.parametrize("prefetch, ns", [
    # prepare 0-8 meets the idle 0-30 in 8; h2d 48-53 the idle 50-52 in 2;
    # ``put`` (waiting for room) is not work
    ([(0, 8, "prepare"), (48, 53, "h2d"), (56, 99, "put")], 10),
    # overlapping spans of two threads count an instant once
    ([(0, 8, "prepare"), (4, 10, "h2d")], 10),
    ([(31, 49, "h2d")], 0)],
    ids=["prepare_and_h2d", "union", "under_the_busy_device"])
def test_prefetch_overlap_is_idle_while_the_second_thread_works(prefetch,
                                                                ns):
    facts = _synthetic(iterations=1,
                       prefetch=[(float(s), float(e), n)
                                 for s, e, n in prefetch])
    assert _read("idle_ms_per_step.prefetch_overlap", facts) == \
        pytest.approx(_ms(ns, 1))


def test_prefetch_overlap_without_a_prefetch_thread_is_none():
    facts = _synthetic()
    assert _read("idle_ms_per_step.prefetch_overlap", facts) is None
    assert _read("idle_ms_per_step.rng", facts) is not None


# -- traces without the new names ---------------------------------------------


@pytest.mark.parametrize("trace", [OLD_VOCABULARY, NAMELESS],
                         ids=["parents_vocabulary", "nameless"])
@pytest.mark.parametrize("metric", EIGHT)
def test_reader_returns_none_on_a_trace_without_the_new_spans(metric,
                                                              trace):
    """``null`` and not 0: the parent records ``step.sync`` with its
    ``reason`` but no ``step.rng``, so nothing partitions its ``.loop``."""
    assert _read(metric, {"xplane": trace, "steps": 7}) is None


def test_the_old_fixture_still_reads_its_reasons_here():
    spans = trace_spans.host_spans(OLD_VOCABULARY)
    owners = {e[2] for e in spans["loop"]}
    assert {"step.sync:guard", "step.sync:loss", "iteration"} <= owners
    assert trace_spans.MARKER not in owners and spans["prefetch"] == []


def test_no_trace_at_all_is_none(tmp_path, monkeypatch):
    monkeypatch.setattr(trace_scopes, "TRACE_ROOT", str(tmp_path))
    assert all(_read(m, {"steps": 3}) is None for m in EIGHT)


# -- the recorded trace -------------------------------------------------------


def _recorded():
    with open(os.path.join(HERE, "spans_parts.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def facts():
    return {"xplane": PARTS_TRACE, "steps": _recorded()["steps"]}


def test_recorded_trace_holds_both_threads_names(facts):
    parsed, spans = trace_spans.spans_of(facts)
    owners = {e[2] for e in spans["loop"]}
    assert {"iteration", "poll", "data_wait", "callback", "prepare", "step",
            "step.rng", "step.dispatch", "step.post", "step.sync:guard",
            "step.sync:loss", "extras", "close"} <= owners
    steps = trace_scopes.iterations(parsed)
    assert steps == facts["steps"]
    # the second thread: one ``h2d`` and one ``put`` a batch (the cell has
    # no feeder, so ``prepare`` is there and empty), and not among the
    # loop's spans, which ``trace_scopes`` still finds on one thread
    names = [e[2] for e in spans["prefetch"]]
    assert set(names) == {"prepare", "h2d", "put"}
    assert names.count("h2d") >= steps
    assert not {"put", "h2d"} & {name for _, _, name in parsed["spans"]}
    assert [e[:2] for e in parsed["spans"]] == [e[:2] for e in spans["loop"]]


@pytest.mark.parametrize("metric", EIGHT)
def test_lstm_cells_new_metric_reads_what_the_chip_run_printed(metric,
                                                               facts):
    value = _read(metric, facts)
    assert isinstance(value, float) and value >= 0
    assert value == pytest.approx(_recorded()["metrics"][metric]["value"],
                                  rel=1e-9, abs=1e-12)


def test_recorded_parts_sum_to_loop_and_owners_to_the_idle_time(facts):
    loop = _read("idle_ms_per_step.loop", facts)
    parts = {p: _read("idle_ms_per_step." + p, facts) for p in SIX}
    assert sum(parts.values()) == pytest.approx(loop, rel=1e-9)
    assert loop == pytest.approx(
        _recorded()["metrics"]["idle_ms_per_step.loop"]["value"], rel=1e-9)
    parsed = trace_scopes.trace_of(facts)
    w0, w1 = parsed["window"]
    idle_ms = ((w1 - w0) - parsed["busy_ns"]) / facts["steps"] / 1e6
    assert sum(_read(m, facts) for m in ACCEPTED) == pytest.approx(
        idle_ms, rel=1e-9)
    # the overlays lie inside what they overlap
    head = _read("idle_ms_per_step.sync_head", facts)
    assert head <= parts["sync_guard"] + parts["sync_loss"] + 1e-12
    assert _read("idle_ms_per_step.prefetch_overlap", facts) <= idle_ms
    # what the toy shows of the real cell: the fetches and the key split
    # own most of the loop's idle time, and little is left without a name
    assert parts["sync_guard"] > 0 and parts["rng"] > 0
    assert parts["unnamed"] < 0.25 * loop


def test_report_shows_that_the_toys_bookkeeping_is_one_long_span():
    """``python benchmark/trace_spans.py <trace>``, the builder's view of a
    cell whose metrics do not list the parts: a fetch under its reason, the
    second thread's own time, and each span's longest reading, which is how
    ``extras`` (the ``train_mfu`` gauge's one trace of the step a
    ``train()`` call) is told from a cost a step."""
    r = trace_spans.report(PARTS_TRACE)
    assert r["steps"] == _recorded()["steps"]
    assert r["loop_ms"] == pytest.approx(sum(r["parts_ms"].values()))
    assert {"step.sync:guard", "step.sync:loss", "step.rng",
            "extras"} <= set(r["idle_ms_by_owner"])
    assert set(r["prefetch_thread_ms"]) == {"prepare", "h2d", "put"}
    extras_total = r["idle_ms_by_owner"]["extras"] * r["steps"]
    assert r["longest_span_ms"]["extras"] > 0.9 * extras_total
    assert trace_spans.report(OLD_VOCABULARY) == {"parts_ms": None}


# -- BENCHMARK.json -----------------------------------------------------------


@pytest.mark.parametrize("metric", EIGHT)
def test_new_metric_is_the_lstm_cells_alone_and_says_what_it_reads(metric):
    entry = next(m for m in manifest.benchmark_json()["per_layer"]
                 if m["name"] == metric)
    assert entry == {"name": metric, "unit": "ms", "better": "lower",
                     "source": "program_span", "layer": "trainer",
                     "moves": "train_tokens_per_s", "workloads": [LSTM]}
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    part = metric.rpartition(".")[2]
    assert spec["reader"] == "idle_parts" and spec["args"] == {"part": part}
    for owner in trace_spans.PARTS.get(part, ()):
        span, _, reason = owner.partition(":")
        assert span in spec["what"] and reason in spec["what"]
    assert ("not part of" if part in OVERLAYS
            else "equal .loop") in spec["what"]
    assert "S3" in spec["what"] or part in ("step_host", "unnamed")


def test_the_eight_come_last_and_the_cells_pinned_sets_do_not_hold_them():
    bj = manifest.benchmark_json()
    assert [m["name"] for m in bj["per_layer"]][-8:] == EIGHT
    for cell in (w["name"] for w in bj["workloads"]):
        held = {m["name"] for m in manifest.cell(cell)["per_layer"]}
        assert (set(EIGHT) <= held) == (cell == LSTM)
        assert (cell == LSTM) or not set(EIGHT) & held
