"""Step timeline — where does a training step's wall-clock go?

The MFU push (ROADMAP item 3) needs to know whether a model is
input-bound, launch-bound, or compute-bound *live*, not from an offline
capture.  The trainer instruments its loop into phases:

================ ===========================================================
phase            wall-clock covered
================ ===========================================================
data_wait        blocking on the reader for the next raw batch
prepare          the DataFeeder converting rows to arrays (host CPU)
h2d              host->device transfer of the prepared feed (synced)
step             the compiled train step, device-synced on its loss
callback         user event handlers (BeginIteration/EndIteration)
checkpoint       atomic checkpoint save (incl. gang barriers)
eval             test()/evaluator runs (mid-pass and end-of-pass)
================ ===========================================================

Per-phase durations aggregate into per-pass stats AND registry histograms
(``train_phase_seconds{phase=...}``), so a scrape of ``--metrics_port``
shows the live breakdown.  The ``step`` phase additionally drives the
**live MFU gauge**: analytic FLOPs of the traced step (the
``analysis.flops`` walker through ``SGDTrainer.step_flops``)
divided by measured step seconds and chip peak FLOP/s
(``train_mfu`` gauge; ``--obs_peak_flops`` overrides the chip table for
virtual-device runs).

Everything here is host-side ``perf_counter`` bookkeeping around the
existing per-batch host sync (the loop's ``float(loss)``, which ends the
``step`` phase); ``SGDTrainer._ph`` takes the boundaries once and hands
them to this timeline, to the profiler's ``paddle_tpu.trainer.<phase>``
span and to the request tracer;
the compiled program is byte-identical with telemetry on or off (gated by
``lint --obs``) and the loop overhead is bounded <3% by test.

All of that begins at the first iteration of a pass.  What a process does
before it (the package's import, ``utils.devices.init``, the trainer's
build, the step's first call, the MFU gauge's second trace) is the
**set-up record** at the end of this file: one per process, phases that
nest, JAX's own trace / lowering / compile events attributed to the phase
that is open, closed and published once when the first iteration of the
first ``SGDTrainer.train`` pass has completed (docs/observability.md
"Set-up").
"""

from __future__ import annotations

import functools
import sys
import time
from typing import Any, Dict, List, Optional

__all__ = ["StepTimeline", "PHASES", "SetupRecord", "setup_phase",
           "in_setup_phase", "setup_record", "close_setup", "reset_setup"]

PHASES = ("data_wait", "prepare", "h2d", "step", "callback", "checkpoint",
          "eval")


class _PhaseStat:
    __slots__ = ("total", "count", "max")

    def __init__(self) -> None:
        self.total = 0.0
        self.count = 0
        self.max = 0.0

    def add(self, s: float) -> None:
        self.total += s
        self.count += 1
        if s > self.max:
            self.max = s


class StepTimeline:
    """Per-pass phase aggregation + live MFU, mirrored into the metrics
    registry.  One instance per ``train()`` call; host-side only."""

    def __init__(self, *, registry=None, label: str = "train",
                 peak_flops: Optional[float] = None,
                 n_devices: int = 1) -> None:
        from paddle_tpu.obs.registry import get_registry

        reg = registry if registry is not None else get_registry()
        self._label = label
        self._hist = {
            p: reg.histogram("train_phase_seconds",
                             "wall-clock per training-loop phase",
                             labels=("phase",), phase=p)
            for p in PHASES
        }
        self._mfu_gauge = reg.gauge(
            "train_mfu", "live model FLOPs utilization of the train step")
        self._pass_stats: Dict[str, _PhaseStat] = {}
        self._pass_t0 = time.perf_counter()
        self.last: Dict[str, float] = {}      # most recent duration per phase
        self.flops: Optional[float] = None    # analytic FLOPs of one step
        self.flops_attempted = False          # one trace attempt per program
        self.mfu: Optional[float] = None      # last computed MFU
        self.steps = 0
        self.n_devices = max(1, int(n_devices))
        self._peak_override = peak_flops
        self.peak_flops = (peak_flops if peak_flops
                           else self._resolve_peak(self.n_devices))
        self.last_pass_summary: Optional[Dict[str, Any]] = None

    @staticmethod
    def _resolve_peak(n_devices: int = 1) -> Optional[float]:
        """Aggregate peak of the participating devices: ``step_flops``
        counts the WHOLE SPMD step's work (global batch), so the MFU
        denominator is chip peak x mesh size, not one chip — a
        data-parallel mesh must not read 8x too utilized.  An explicit
        ``--obs_peak_flops`` is taken as the TOTAL peak, as given."""
        from paddle_tpu.analysis.flops import chip_peak_flops
        from paddle_tpu.utils.flags import FLAGS

        override = float(getattr(FLAGS, "obs_peak_flops", 0.0) or 0.0)
        if override > 0:
            return override
        try:
            import jax

            kind = jax.devices()[0].device_kind
        except Exception:  # no backend at all: no peak, no MFU
            return None
        chip = chip_peak_flops(kind)  # an unknown TPU raises — see flops.py
        return None if chip is None else chip * max(1, int(n_devices))

    def set_devices(self, n_devices: int) -> None:
        """An elastic resize changed the mesh: rescale the table-derived
        peak (an explicit override stays authoritative as given)."""
        self.n_devices = max(1, int(n_devices))
        if not self._peak_override:
            self.peak_flops = self._resolve_peak(self.n_devices)

    # -- recording -------------------------------------------------------

    def add(self, name: str, seconds: float) -> None:
        """One phase took ``seconds``: ``SGDTrainer._ph`` is the one caller
        in the loop, with the same pair of boundaries it gives the
        profiler's ``paddle_tpu.trainer.<phase>`` span."""
        self.last[name] = seconds
        stat = self._pass_stats.get(name)
        if stat is None:
            stat = self._pass_stats[name] = _PhaseStat()
        stat.add(seconds)
        hist = self._hist.get(name)
        if hist is not None:
            hist.observe(seconds)
        if name == "step":
            self.steps += 1
            if self.flops and self.peak_flops and seconds > 0:
                self.mfu = self.flops / seconds / self.peak_flops
                self._mfu_gauge.set(round(self.mfu, 6))

    @property
    def wants_mfu(self) -> bool:
        """Whether computing analytic FLOPs would buy a live gauge: only
        with a resolved peak (real TPU or ``--obs_peak_flops``) — tracing
        the step a second time for a gauge that can never light up would
        be pure startup cost."""
        return self.peak_flops is not None

    def set_flops(self, flops: Optional[float]) -> None:
        """Record the (attempted) analytic FLOPs of one step.  A None —
        the trace failed — still counts as attempted: re-tracing the
        whole step after EVERY batch in the hope it starts working would
        sink throughput exactly where it is being measured."""
        self.flops = flops
        self.flops_attempted = True

    def invalidate_flops(self) -> None:
        """The compiled program changed shape (elastic resize): stale
        FLOPs would skew the gauge — re-trace at the next step."""
        self.flops = None
        self.flops_attempted = False

    def recompute_mfu(self) -> None:
        """Refresh the gauge from the LAST step duration — used when the
        FLOPs count arrives after the first step already ran."""
        sec = self.last.get("step")
        if sec and self.flops and self.peak_flops:
            self.mfu = self.flops / sec / self.peak_flops
            self._mfu_gauge.set(round(self.mfu, 6))

    # -- per-pass aggregation -------------------------------------------

    def pass_stats(self) -> Dict[str, Dict[str, float]]:
        """Per-phase {total, count, mean, max} for the CURRENT pass."""
        return {
            name: {"total": s.total, "count": s.count,
                   "mean": s.total / s.count if s.count else 0.0,
                   "max": s.max}
            for name, s in sorted(self._pass_stats.items())
        }

    def end_pass(self, pass_id: int, journal=None) -> Dict[str, Any]:
        """Close the pass: snapshot the per-phase table (+ phase share of
        the pass wall-clock), journal it, reset for the next pass."""
        wall = time.perf_counter() - self._pass_t0
        stats = self.pass_stats()
        covered = sum(s["total"] for s in stats.values())
        summary = {
            "pass": pass_id,
            "wall_s": round(wall, 6),
            "covered_s": round(covered, 6),
            "phases": {k: {kk: round(vv, 6) for kk, vv in v.items()}
                       for k, v in stats.items()},
            "mfu": None if self.mfu is None else round(self.mfu, 4),
            "flops_per_step": self.flops,
        }
        self.last_pass_summary = summary
        if journal is not None:
            journal.record("pass_timing", **summary)
        self._pass_stats = {}
        self._pass_t0 = time.perf_counter()
        return summary

    def table(self) -> str:
        """Human-readable per-pass table (the Stat print analog)."""
        stats = self.pass_stats()
        total = sum(s["total"] for s in stats.values()) or 1e-12
        rows = ["%-12s %8s %12s %12s %8s" % ("phase", "count", "total(s)",
                                             "mean(ms)", "share")]
        for name, s in sorted(stats.items(), key=lambda kv: -kv[1]["total"]):
            rows.append("%-12s %8d %12.3f %12.3f %7.1f%%" % (
                name, s["count"], s["total"], s["mean"] * 1e3,
                100.0 * s["total"] / total))
        if self.mfu is not None:
            rows.append(f"live MFU: {self.mfu:.4f}")
        return "\n".join(rows)


# ---------------------------------------------------------------------------
# The set-up record: what the process did before its first step had run
# ---------------------------------------------------------------------------

#: a phase on a profiler trace: NOT ``paddle_tpu.trainer.``, whose unknown
#: names ``benchmark/trace_spans.py`` gives to ``idle_ms_per_step.unnamed``
SETUP_SPAN_PREFIX = "paddle_tpu.setup."
#: a phase or a part as a ``jax.monitoring`` duration event, at the close
SETUP_EVENT_PREFIX = "/paddle_tpu/setup/"
#: JAX's own duration events, heard while they happen: the stage of a
#: program's first call each one times
JAX_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
#: the parts of a phase, in the order a first call goes through them;
#: ``other`` is the rest of the phase's self time (dispatch, the run, a wait)
PARTS = ("trace", "lower", "compile", "cache_load")
#: what JAX did while the record was open and no phase was: the caller's own
#: programs (a harness's reference, an evaluation before any trainer)
OUTSIDE = "outside"


class _NoPhase:
    """What ``setup_phase`` returns once the record has closed: one shared
    object whose ``with`` does nothing."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NO_PHASE = _NoPhase()


class _Phase:
    """One phase of the record, a context manager: name, the phase that
    was open when it began, start and end on ``time.perf_counter()``, and
    the JAX stages heard while it was the innermost one open."""

    __slots__ = ("record", "name", "parent", "path", "t0", "t1", "parts",
                 "_heard", "_span")

    def __init__(self, record: "SetupRecord", name: str) -> None:
        self.record, self.name = record, name
        self.parent: Optional[_Phase] = None
        self.path = name
        self.t0 = self.t1 = None
        # part -> [seconds, count]
        self.parts: Dict[str, List[float]] = {}
        # the stage events still standing: (start, seconds, part), a later
        # one that began before them holds them (a jit traced inside a
        # trace, a trace inside a lowering) and takes their place
        self._heard: List[tuple] = []
        self._span = None

    def __enter__(self):
        rec = self.record
        self.parent = rec.open
        if self.parent is not None:
            self.path = self.parent.path + "/" + self.name
        if "jax" in sys.modules:
            # no backend is touched: a list append the first time, and a
            # flag check while no profiler session runs
            import jax

            _listen(jax)
            self._span = jax.profiler.TraceAnnotation(
                SETUP_SPAN_PREFIX + self.name)
            self._span.__enter__()
        rec.phases.append(self)
        rec.open = self
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self.t1 is None:      # else the record closed over it
            self.t1 = time.perf_counter()
        if self.record.open is self:
            self.record.open = self.parent
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        return False

    def hear(self, part: str, seconds: float) -> None:
        """A stage of ``seconds`` ended now.  JAX times stages that lie
        inside one another (every ``jit`` traced inside the step's trace
        reports its own), so the sum of the events is more than the clock:
        an event takes the place of the ones that began after it did, and
        a part's seconds are wall-clock, its count the outermost events."""
        start = time.perf_counter() - seconds
        held = self._heard
        while held and held[-1][0] >= start - 1e-4:
            _, s, p = held.pop()
            got = self.parts[p]
            got[0] -= s
            got[1] -= 1
        held.append((start, seconds, part))
        got = self.parts.setdefault(part, [0.0, 0])
        got[0] += seconds
        got[1] += 1


class SetupRecord:
    """The process's set-up, in memory: phases in the order they began,
    each with its parent, so a phase's self time is its duration less its
    children's.  Open from the first phase (the package's import) until
    ``close``; after that ``setup_phase`` hands out the shared no-op and
    the record does not change."""

    def __init__(self) -> None:
        self.phases: List[_Phase] = []
        self.open: Optional[_Phase] = None     # the innermost open phase
        self.closed = False
        self.outside = _Phase(self, OUTSIDE)
        self._cache_hit = False
        self.summary: Optional[Dict[str, Any]] = None

    def add(self, name: str, t0: float) -> None:
        """A phase that began at ``t0`` and ends now, for the one caller
        that runs before this module can be imported (the package's
        ``__init__``)."""
        if not self.closed:
            with _Phase(self, name) as ph:
                ph.t0 = t0

    def hear(self, event: str, seconds: float) -> None:
        """One of JAX's stage events while the record is open: to the
        innermost open phase.  A backend compile that held a cache
        retrieval was a load (JAX times the retrieval inside the compile
        event), any other a compile; the retrieval's own event is only
        that mark."""
        part = JAX_STAGES[event]
        if part == "cache_load":
            self._cache_hit = True
            return
        if part == "compile" and self._cache_hit:
            part, self._cache_hit = "cache_load", False
        (self.open or self.outside).hear(part, seconds)

    def table(self) -> Dict[str, Any]:
        """``{path: {"s", "self_s", "count", "parts": {part: {"s",
        "count"}}}}``: phases of one path summed (a phase entered twice
        counts twice), ``other`` among the parts of a phase that heard any
        stage: its self time less the stages."""
        now = time.perf_counter()
        out: Dict[str, Any] = {}
        for ph in self.phases:
            row = out.setdefault(ph.path, {"s": 0.0, "self_s": 0.0,
                                           "count": 0, "parts": {}})
            dur = (ph.t1 if ph.t1 is not None else now) - ph.t0
            row["s"] += dur
            row["self_s"] += dur
            row["count"] += 1
            if ph.parent is not None:
                out[ph.parent.path]["self_s"] -= dur
            _add_parts(row["parts"], ph.parts)
        for row in out.values():
            if row["parts"]:
                row["parts"]["other"] = {
                    "s": row["self_s"] - sum(p["s"] for p in
                                             row["parts"].values()),
                    "count": row["count"]}
        if self.outside.parts:
            out[OUTSIDE] = {"parts": _add_parts({}, self.outside.parts)}
        return out

    def close(self, journal=None) -> Optional[Dict[str, Any]]:
        """End the record and hand it to its four readers, once: the
        registry gauge ``setup_seconds{phase=}``, the journal record
        ``setup_timing`` (where a journal is on), ``self.summary`` for a
        caller, and one ``jax.monitoring`` duration event
        ``/paddle_tpu/setup/<path>[/<part>]`` per phase and part (a
        part's ``count`` as the event's keyword).  Publishing here and not
        at each phase's end is what lets a listener registered after
        ``import paddle_tpu`` hear ``import`` and ``init``."""
        if self.closed:
            return self.summary
        now = time.perf_counter()
        for ph in self.phases:
            if ph.t1 is None:
                ph.t1 = now
        self.closed, self.open = True, None
        table = self.table()
        flat: Dict[str, tuple] = {}
        for path, row in table.items():
            if "s" in row:
                flat[path] = (row["s"], row["count"])
            for part, got in row["parts"].items():
                flat[path + "/" + part] = (got["s"], got["count"])
        self.summary = {"phases": table,
                        "seconds": {k: round(v[0], 6)
                                    for k, v in flat.items()}}
        from paddle_tpu.obs.registry import get_registry

        reg = get_registry()
        for name, (seconds, _) in flat.items():
            reg.gauge("setup_seconds", "a phase of the process's set-up, or "
                      "a part of one (obs/timeline.py SetupRecord)",
                      labels=("phase",), phase=name).set(round(seconds, 6))
        if journal is None:
            from paddle_tpu.obs.journal import get_journal

            journal = get_journal()
        if journal is not None:
            journal.record("setup_timing", **self.summary)
        if "jax" in sys.modules:
            import jax

            for name, (seconds, count) in flat.items():
                jax.monitoring.record_event_duration_secs(
                    SETUP_EVENT_PREFIX + name, seconds, count=count)
        return self.summary


def _add_parts(into: Dict[str, Any], parts: Dict[str, List[float]]):
    for part in PARTS:
        if part in parts and parts[part][1] > 0:
            got = into.setdefault(part, {"s": 0.0, "count": 0})
            got["s"] += parts[part][0]
            got["count"] += int(parts[part][1])
    return into


_RECORD = SetupRecord()


def setup_record() -> SetupRecord:
    """The process's record (``.table()`` while open, ``.summary`` once
    closed)."""
    return _RECORD


def setup_phase(name: str, *, span_after_close: bool = False):
    """``with setup_phase("trainer_build"):`` — the ONE way a phase of
    set-up is recorded, in the style of ``SGDTrainer._ph``: a pair of
    ``perf_counter`` reads where control passes anyway, the phase that is
    open as its parent, and a ``jax.profiler.TraceAnnotation``
    ``paddle_tpu.setup.<name>`` (a flag check without a session).  Nothing
    is run in order to be measured.  Once the record has closed this is
    one attribute check and the shared no-op; with ``span_after_close``
    (the MFU gauge's second trace, which runs once per ``train()`` call)
    the annotation alone stays, so the copy inside a later pass is on the
    profiler's trace too."""
    rec = _RECORD
    if not rec.closed:
        return _Phase(rec, name)
    if span_after_close and "jax" in sys.modules:
        import jax

        return jax.profiler.TraceAnnotation(SETUP_SPAN_PREFIX + name)
    return _NO_PHASE


def in_setup_phase(name: str):
    """``setup_phase`` around every call of a function (``SGDTrainer``'s
    constructor): the phase is asked for at the call, not at the ``def``."""

    def wrap(fn):
        @functools.wraps(fn)
        def phased(*args, **kwargs):
            with setup_phase(name):
                return fn(*args, **kwargs)

        return phased

    return wrap


def close_setup(journal=None) -> Optional[Dict[str, Any]]:
    """End the record now: ``SGDTrainer.train`` does at the end of its
    first iteration; a caller that drives ``train_batch`` itself calls
    this after its first step."""
    return _RECORD.close(journal)


def reset_setup() -> SetupRecord:
    """A new, open record (tests that build several trainers in one
    process; beside ``reset_registry`` / ``reset_tracer``).  The listener
    stays: it reads the record through this module."""
    global _RECORD
    _RECORD = SetupRecord()
    return _RECORD


def _on_duration(event: str, seconds: float, **_) -> None:
    """The program's one ``jax.monitoring`` duration listener.  While the
    record is open a stage event goes to the innermost open phase; after
    the close a backend compile (compiled or loaded from the cache) is a
    LATE compile: a program first met inside training."""
    if event not in JAX_STAGES:
        return
    rec = _RECORD
    if not rec.closed:
        rec.hear(event, seconds)
    elif JAX_STAGES[event] == "compile":
        from paddle_tpu.obs.journal import journal_event
        from paddle_tpu.obs.registry import get_registry

        get_registry().counter(
            "train_late_compiles_total", "programs compiled or loaded from "
            "the compile cache after the set-up record closed").inc()
        journal_event("late_compile", seconds=round(seconds, 6))


_LISTENING = False


def _listen(jax) -> None:
    """Register ``_on_duration``, once a process."""
    global _LISTENING
    if not _LISTENING:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _LISTENING = True
