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
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

__all__ = ["StepTimeline", "PHASES"]

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
        self._step_gauge = reg.gauge(
            "train_step_seconds", "device-synced seconds of the last step")
        self._flops_gauge = reg.gauge(
            "train_step_flops", "analytic FLOPs of one train step "
            "(analysis.flops walker, SGDTrainer.step_flops)")
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
            self._step_gauge.set(seconds)
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
        if flops:
            self._flops_gauge.set(float(flops))

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
