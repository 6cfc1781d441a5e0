"""The set-up record (paddle_tpu/obs/timeline.py ``SetupRecord``;
docs/observability.md "Set-up"): phases that nest and their self time, JAX's
own trace / lower / compile events as the parts of the phase that is open,
the close at the first completed iteration and the no-op afterwards, the four
sinks, the late-compile counter, and the rule the record is built on: nothing
is run in order to be measured."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.nn as nn
from paddle_tpu.obs import (close_journal, get_registry, read_journal,
                            reset_registry, reset_setup, setup_phase,
                            setup_record, timeline)
from paddle_tpu.param.optimizers import Adam
from paddle_tpu.trainer import SGDTrainer
from paddle_tpu.utils.flags import FLAGS

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE, LOWER, COMPILE, RETRIEVAL = timeline.JAX_STAGES
_FLAGS = ("obs_timeline", "obs_journal", "obs_peak_flops", "log_period",
          "prefetch_depth", "guard_nonfinite")


@pytest.fixture(autouse=True)
def _fresh_record():
    """The record is the process's: every test starts from an open, empty
    one and leaves a closed one, as a worker that has trained holds."""
    keep = {k: getattr(FLAGS, k) for k in _FLAGS}
    FLAGS.log_period = 0
    reset_registry()
    reset_setup()
    yield
    for k, v in keep.items():
        setattr(FLAGS, k, v)
    close_journal()
    setup_record().closed = True
    reset_registry()


def _tiny_trainer(hidden=8):
    nn.reset_naming()
    x = nn.data("x", size=8)
    y = nn.data("y", size=2)
    h = nn.fc(x, hidden, act="relu", name="h")
    cost = nn.mse_cost(input=nn.fc(h, 2, name="out"), label=y)
    return SGDTrainer(cost, Adam(learning_rate=0.05), seed=0)


def _feeds(n, seed=0):
    rs = np.random.RandomState(seed)
    return [{"x": rs.randn(4, 8).astype(np.float32),
             "y": rs.randn(4, 2).astype(np.float32)} for _ in range(n)]


class _Clock:
    """``time.perf_counter`` by hand."""

    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


@pytest.fixture
def clock(monkeypatch):
    c = _Clock()
    monkeypatch.setattr(timeline.time, "perf_counter", c)
    return c


# -- the record by hand -------------------------------------------------------


def test_phases_nest_and_self_time_is_less_the_children(clock):
    with setup_phase("trainer_build"):
        clock.now += 1.0
        with setup_phase("params"):
            clock.now += 2.0
        with setup_phase("opt_state"):
            clock.now += 3.0
            with setup_phase("inner"):
                clock.now += 0.5
        clock.now += 0.25
    t = setup_record().table()
    assert list(t) == ["trainer_build", "trainer_build/params",
                       "trainer_build/opt_state",
                       "trainer_build/opt_state/inner"]
    assert t["trainer_build"]["s"] == pytest.approx(6.75)
    assert t["trainer_build"]["self_s"] == pytest.approx(1.25)
    assert t["trainer_build/opt_state"]["s"] == pytest.approx(3.5)
    assert t["trainer_build/opt_state"]["self_s"] == pytest.approx(3.0)
    assert setup_record().open is None


def test_a_phase_entered_twice_is_one_path_with_a_count(clock):
    with setup_phase("first_iteration"):
        for _ in range(3):
            with setup_phase("data"):
                clock.now += 0.5
    t = setup_record().table()
    assert t["first_iteration/data"]["count"] == 3
    assert t["first_iteration/data"]["s"] == pytest.approx(1.5)
    assert t["first_iteration"]["self_s"] == pytest.approx(0.0)


def test_stage_events_go_to_the_innermost_open_phase_with_counts(clock):
    rec = setup_record()
    with setup_phase("first_iteration"):
        with setup_phase("first_step"):
            clock.now += 2.0
            timeline._on_duration(TRACE, 2.0)
            clock.now += 1.0
            timeline._on_duration(LOWER, 1.0)
            clock.now += 4.0
            timeline._on_duration(COMPILE, 4.0)
            clock.now += 0.5
        clock.now += 0.25
        timeline._on_duration(TRACE, 0.25)
    timeline._on_duration("/jax/some/other_event", 9.0)
    t = rec.table()
    parts = t["first_iteration/first_step"]["parts"]
    assert {k: (v["s"], v["count"]) for k, v in parts.items()} == {
        "trace": (2.0, 1), "lower": (1.0, 1), "compile": (4.0, 1),
        "other": (pytest.approx(0.5), 1)}
    assert t["first_iteration"]["parts"]["trace"] == {"s": 0.25, "count": 1}
    # self time = the stages + other, by construction
    assert sum(v["s"] for v in parts.values()) == pytest.approx(
        t["first_iteration/first_step"]["self_s"])


def test_a_stage_inside_a_stage_is_not_counted_twice(clock):
    """Every jit traced inside the step's trace reports its own trace:
    the outermost event stands, in seconds and in count."""
    with setup_phase("first_step"):
        for _ in range(5):                 # five inner jits, 0.1 s each
            clock.now += 0.1
            timeline._on_duration(TRACE, 0.1)
        clock.now += 0.5
        timeline._on_duration(TRACE, 1.0)  # the step's own: began 1 s ago
        clock.now += 0.3
        timeline._on_duration(TRACE, 0.3)  # a second top-level trace
    parts = setup_record().table()["first_step"]["parts"]
    assert parts["trace"] == {"s": pytest.approx(1.3), "count": 2}
    assert parts["other"]["s"] == pytest.approx(0.0)


def test_a_compile_event_that_held_a_cache_retrieval_is_a_load(clock):
    """JAX times the retrieval inside ``backend_compile_duration``: the
    two are one stage, named by whether the cache served it."""
    with setup_phase("first_step"):
        clock.now += 2.0
        timeline._on_duration(RETRIEVAL, 1.5)
        timeline._on_duration(COMPILE, 2.0)      # a hit: key, read, load
        clock.now += 30.0
        timeline._on_duration(COMPILE, 30.0)     # a miss: XLA compiled
    parts = setup_record().table()["first_step"]["parts"]
    assert parts["cache_load"] == {"s": 2.0, "count": 1}
    assert parts["compile"] == {"s": 30.0, "count": 1}
    assert parts["other"]["s"] == pytest.approx(0.0)


def test_events_with_no_phase_open_are_outside(clock):
    clock.now += 3.0
    timeline._on_duration(COMPILE, 3.0)
    summary = setup_record().close()
    assert summary["phases"]["outside"] == {
        "parts": {"compile": {"s": 3.0, "count": 1}}}
    assert summary["seconds"] == {"outside/compile": 3.0}


def test_close_ends_open_phases_and_setup_phase_is_the_shared_noop(clock):
    rec = setup_record()
    outer = setup_phase("first_iteration")
    outer.__enter__()
    clock.now += 2.0
    rec.close()
    clock.now += 5.0
    outer.__exit__(None, None, None)      # harmless after the close
    assert rec.summary["seconds"] == {"first_iteration": 2.0}
    assert setup_phase("x") is setup_phase("y") is timeline._NO_PHASE
    with setup_phase("late"):
        pass
    assert len(rec.phases) == 1 and rec.close() is rec.summary


# -- the four sinks -----------------------------------------------------------


def _hear(into):
    def on(event, seconds, **kw):
        if event.startswith(timeline.SETUP_EVENT_PREFIX):
            into.append((event[len(timeline.SETUP_EVENT_PREFIX):], seconds,
                         kw))
    jax.monitoring.register_event_duration_secs_listener(on)
    return on


def test_close_feeds_gauge_journal_accessor_and_monitoring_once(
        clock, tmp_path):
    FLAGS.obs_journal = str(tmp_path)
    heard = []
    on = _hear(heard)
    try:
        setup_record().add("import", clock.now - 0.5)
        with setup_phase("trainer_build"):
            clock.now += 1.0
            timeline._on_duration(COMPILE, 1.0)
            timeline._on_duration(COMPILE, 0.0)
        summary = timeline.close_setup()
        timeline.close_setup()                      # published once
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    want = {"import": 0.5, "trainer_build": 1.0,
            "trainer_build/compile": 1.0, "trainer_build/other": 0.0}
    assert summary["seconds"] == want
    assert {n: s for n, s, _ in heard} == want and len(heard) == len(want)
    assert dict((n, kw) for n, _, kw in heard)["trainer_build/compile"] == {
        "count": 2}
    series = get_registry().snapshot()["setup_seconds"]["series"]
    assert {s["labels"]["phase"]: s["value"] for s in series} == want
    close_journal()
    records, torn = read_journal(os.path.join(str(tmp_path),
                                              "events-r00000.jsonl"))
    timing = [r for r in records if r["kind"] == "setup_timing"]
    assert len(timing) == 1 and timing[0]["seconds"] == want and not torn


# -- a real trainer -----------------------------------------------------------


def test_record_closes_at_the_first_completed_iteration_and_stays():
    FLAGS.obs_timeline, FLAGS.obs_peak_flops = True, 1e12
    rec = setup_record()
    tr = _tiny_trainer()
    assert not rec.closed and rec.open is None
    seen = []

    def handler(e):
        seen.append((type(e).__name__, rec.closed))

    tr.train(lambda: iter(_feeds(3)), num_passes=1, event_handler=handler)
    # open through iteration 0's EndIteration, closed before iteration 1
    assert seen[:3] == [("BeginPass", False), ("BeginIteration", False),
                        ("EndIteration", False)]
    assert all(closed for _, closed in seen[3:])
    paths = set(rec.summary["phases"])
    assert {"trainer_build", "trainer_build/topology",
            "trainer_build/params", "trainer_build/opt_state",
            "trainer_build/step_build", "first_iteration",
            "first_iteration/data", "first_iteration/first_step",
            "first_iteration/flops_trace",
            "first_iteration/callback"} <= paths
    held = len(rec.phases)
    tr.train(lambda: iter(_feeds(3)), num_passes=1)
    _tiny_trainer()
    assert len(rec.phases) == held and rec is setup_record()


def test_an_empty_pass_leaves_the_record_open_for_the_next():
    rec = setup_record()
    tr = _tiny_trainer()
    tr.train(lambda: iter([]), num_passes=1)
    assert not rec.closed and rec.open is None
    tr.train(lambda: iter(_feeds(1)), num_passes=1)
    assert rec.closed
    assert rec.summary["phases"]["first_iteration"]["count"] == 2


def test_jax_events_land_on_the_first_step_with_counts():
    rec = setup_record()
    tr = _tiny_trainer(hidden=11)      # a width no other test compiles
    tr.train(lambda: iter(_feeds(2)), num_passes=1)
    step = rec.summary["phases"]["first_iteration/first_step"]
    parts = step["parts"]
    assert parts["trace"]["count"] == 1 and parts["lower"]["count"] == 1
    # compiled or served by the persistent cache: one executable either way
    assert (parts.get("compile", {}).get("count", 0)
            + parts.get("cache_load", {}).get("count", 0)) == 1
    assert parts["other"]["s"] >= 0
    assert sum(p["s"] for p in parts.values()) == pytest.approx(
        step["self_s"])
    # the build makes its parameters and slots program by program
    build = rec.summary["phases"]
    assert build["trainer_build/params"]["parts"]["trace"]["count"] >= 1


def _stage_counts(run):
    counts = dict.fromkeys(timeline.JAX_STAGES, 0)

    def on(event, seconds, **_):
        if event in counts:
            counts[event] += 1

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        run()
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    return counts


def test_set_up_traces_lowers_and_compiles_as_often_without_the_record(
        monkeypatch):
    """PR 37's lesson: the record runs nothing to be measured.  The same
    set-up with the record open and its listener on, and with the record
    closed and the listener gone, makes JAX emit each of the four events
    equally often."""
    FLAGS.obs_timeline, FLAGS.obs_peak_flops = True, 1e12

    def set_up():
        tr = _tiny_trainer(hidden=13)
        tr.train(lambda: iter(_feeds(2)), num_passes=1)

    # every program goes to the persistent cache, however fast it compiled:
    # what the cache serves is then the same in both runs
    option = "jax_persistent_cache_min_compile_time_secs"
    keep = getattr(jax.config, option)
    jax.config.update(option, 0.0)
    try:
        set_up()                  # the process's own caches, warm for both
        reset_setup()
        with_record = _stage_counts(set_up)
        assert setup_record().closed and setup_record().summary
        reset_setup().closed = True
        jax.monitoring.unregister_event_duration_listener(
            timeline._on_duration)
        monkeypatch.setattr(timeline, "_LISTENING", True)  # and stays gone
        try:
            without = _stage_counts(set_up)
        finally:
            jax.monitoring.register_event_duration_secs_listener(
                timeline._on_duration)
    finally:
        jax.config.update(option, keep)
    assert not setup_record().phases
    assert with_record == without
    assert with_record[TRACE] > 0 and with_record[COMPILE] > 0


@pytest.mark.parametrize("steps", [2, 6])
def test_no_call_into_the_record_per_step_after_the_close(steps,
                                                          monkeypatch):
    """After the close a ``train()`` call asks for a phase a fixed number
    of times (the pass's reader and prefetcher, ``first_iteration``, the
    MFU gauge's trace), whatever the number of steps."""
    FLAGS.obs_timeline, FLAGS.obs_peak_flops = True, 1e12
    tr = _tiny_trainer()
    tr.train(lambda: iter(_feeds(1)), num_passes=1)
    assert setup_record().closed
    calls = []
    real = timeline.setup_phase

    def counting(name, **kw):
        calls.append(name)
        return real(name, **kw)

    import paddle_tpu.trainer.trainer as trainer_mod

    monkeypatch.setattr(trainer_mod, "setup_phase", counting)
    tr.train(lambda: iter(_feeds(steps)), num_passes=1)
    assert sorted(calls) == ["data", "data", "first_iteration",
                             "flops_trace"]


def test_flops_trace_keeps_its_span_after_the_close():
    tr = _tiny_trainer()
    tr.train(lambda: iter(_feeds(1)), num_passes=1)
    kept = setup_phase("flops_trace", span_after_close=True)
    assert isinstance(kept, jax.profiler.TraceAnnotation)
    assert setup_phase("flops_trace") is timeline._NO_PHASE


def test_a_compile_after_the_close_is_a_late_compile(tmp_path):
    FLAGS.obs_journal = str(tmp_path)
    tr = _tiny_trainer()
    late = []

    def handler(e):
        if type(e).__name__ == "EndIteration" and e.batch_id == 1:
            # a program first met inside training, at pass 0 batch 1
            late.append(jax.jit(lambda a: a * 3 + 1)(jnp.arange(7.0)))

    tr.train(lambda: iter(_feeds(3)), num_passes=1, event_handler=handler)
    snap = get_registry().snapshot()["train_late_compiles_total"]
    assert snap["series"][0]["value"] >= 1
    close_journal()
    records, _ = read_journal(os.path.join(str(tmp_path),
                                           "events-r00000.jsonl"))
    got = [r for r in records if r["kind"] == "late_compile"]
    assert got and got[0]["batch"] == 1 and got[0]["pass"] == 0
    assert got[0]["seconds"] > 0
    assert [r["kind"] for r in records].index("setup_timing") < [
        r["kind"] for r in records].index("late_compile")


def test_train_batch_alone_needs_an_explicit_close():
    rec = setup_record()
    tr = _tiny_trainer()
    float(tr.train_batch(_feeds(1)[0]))
    assert not rec.closed
    summary = timeline.close_setup()
    assert "trainer_build" in summary["phases"]
    # the step's first call had no phase of the loop around it
    assert "compile" in summary["phases"]["outside"]["parts"] or \
        "cache_load" in summary["phases"]["outside"]["parts"]


# -- a process of its own: import and init are heard by a late listener -------

SCRIPT = """\
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("PADDLE_TPU_COMPUTE_DTYPE", "float32")
import paddle_tpu
from paddle_tpu.utils.devices import init
init([])
import jax
heard = []
jax.monitoring.register_event_duration_secs_listener(
    lambda e, s, **kw: heard.append((e, s)) if e.startswith("/paddle_tpu/")
    else None)
import numpy as np
import paddle_tpu.nn as nn
from paddle_tpu.param.optimizers import Adam
from paddle_tpu.trainer import SGDTrainer
x = nn.data("x", size=8); y = nn.data("y", size=2)
cost = nn.mse_cost(input=nn.fc(x, 2, name="out"), label=y)
tr = SGDTrainer(cost, Adam(learning_rate=0.05), seed=0)
rs = np.random.RandomState(0)
feeds = [{"x": rs.randn(4, 8).astype(np.float32),
          "y": rs.randn(4, 2).astype(np.float32)} for _ in range(2)]
tr.train(lambda: iter(feeds), num_passes=1)
tr.train(lambda: iter(feeds), num_passes=1)
print("HEARD " + json.dumps(heard))
"""


@pytest.fixture(scope="module")
def heard_by_a_late_listener():
    env = dict(os.environ, PYTHONPATH=REPO_ROOT, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("HEARD ")]
    return json.loads(line[-1][len("HEARD "):])


@pytest.mark.parametrize("phase", ["import", "init", "trainer_build",
                                   "first_iteration",
                                   "first_iteration/first_step"])
def test_a_listener_registered_after_import_hears_the_phase_once(
        heard_by_a_late_listener, phase):
    names = [e for e, _ in heard_by_a_late_listener]
    assert names.count("/paddle_tpu/setup/" + phase) == 1


def test_every_event_is_published_once_and_the_top_level_adds_up(
        heard_by_a_late_listener):
    names = [e for e, _ in heard_by_a_late_listener]
    assert len(names) == len(set(names))
    seconds = dict(heard_by_a_late_listener)
    step = "/paddle_tpu/setup/first_iteration/first_step"
    parts = [seconds[n] for n in names
             if n.startswith(step + "/")]
    assert sum(parts) == pytest.approx(seconds[step], abs=1e-3)
    assert seconds["/paddle_tpu/setup/import"] > 0
