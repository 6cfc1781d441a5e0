"""``lint --obs`` — prove telemetry never touches the compiled step.

The whole design of ``paddle_tpu.obs`` is that instrumentation lives in
host-side Python around the already-existing per-batch sync: the jitted
train step must compile to the SAME program with telemetry on.  This
audit builds a small trainer twice — timeline/journal/MFU plumbing
enabled vs disabled — and

1. runs the jaxpr auditor's host-transfer/constant-bloat checks over the
   telemetry-enabled step (the ``audit_decode`` contract: ERROR-free
   means no host round-trip per step), and
2. asserts the two traced programs are equation-for-equation IDENTICAL —
   zero *added* anything, not merely zero transfers.

Request tracing (obs/trace.py) extends the same contract to ALL hot
lifecycles: the train step, the continuous-batching ``decode_step`` AND
the speculative wide ``spec_verify_step`` are traced with tracing armed
(``--obs_journal`` + ``--trace_sample``) vs off and must be
equation-identical — spans are host-side bookkeeping around calls the
loop already makes; tracing adds ZERO compiled equations.

The set-up record (obs/timeline.py) is held to the same: the train step
traced inside an open phase and after the record has closed is one
program.
"""

from __future__ import annotations

from typing import List

from paddle_tpu.analysis.findings import Finding

__all__ = ["audit_telemetry_step"]

#: the checks that matter here — same set a serving/decode closure gets
_CHECKS = ("host-transfer", "constant-bloat")


def _tiny_trainer():
    import numpy as np

    import paddle_tpu.nn as nn
    from paddle_tpu.param.optimizers import Adam
    from paddle_tpu.trainer import SGDTrainer

    nn.reset_naming()
    x = nn.data("obs_audit_x", size=8)
    y = nn.data("obs_audit_y", size=2)
    cost = nn.mse_cost(input=nn.fc(x, 2, act="relu", name="obs_audit_h"),
                       label=y)
    tr = SGDTrainer(cost, Adam(learning_rate=0.01), seed=0)
    rs = np.random.RandomState(0)
    feed = {"obs_audit_x": rs.randn(4, 8).astype(np.float32),
            "obs_audit_y": rs.randn(4, 2).astype(np.float32)}
    return tr, feed


def _tiny_decode_step():
    """A minimal slot-table ``decode_step`` closure + carry — enough to
    pin the compiled fused step's identity under tracing flags without
    building the full flagship backend."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.decode import (LogitsReadout, decode_step,
                                       init_slot_carry)

    w = jnp.ones((4, 8), jnp.float32) * 0.1

    def step_fn(tokens, state):
        logits = state["h"] @ w
        return logits, {"h": state["h"] * 0.9}

    tpl = {"h": jax.ShapeDtypeStruct((1, 4), jnp.float32)}
    carry = init_slot_carry(tpl, slots=2, beam_size=2, max_len=4, eos=1)

    def fn(c):
        return decode_step(step_fn, LogitsReadout(), c, vocab_size=8,
                           eos=1)

    return fn, carry


def _tiny_spec_step():
    """K=1 variant of :func:`_tiny_decode_step` exercising the fused wide
    ``spec_verify_step`` — the speculative-decoding hot program must stay
    equation-identical with tracing armed, same as ``decode_step``."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.decode import (LogitsReadout, init_slot_carry,
                                       spec_verify_step)

    w = jnp.ones((4, 8), jnp.float32) * 0.1

    def step_fn(tokens, state):
        logits = state["h"] @ w
        return logits, {"h": state["h"] * 0.9}

    tpl = {"h": jax.ShapeDtypeStruct((1, 4), jnp.float32)}
    carry = init_slot_carry(tpl, slots=2, beam_size=1, max_len=4, eos=1)
    drafts = jnp.zeros((2, 3), jnp.int32)
    cap = jnp.full((2,), 4, jnp.int32)

    def fn(c):
        return spec_verify_step(step_fn, LogitsReadout(), c, drafts, cap,
                                vocab_size=8, eos=1)[0]

    return fn, carry


def audit_telemetry_step() -> List[Finding]:
    """Trace the trainer step with telemetry ON, audit it, and diff the
    jaxpr against the telemetry-OFF trace; then diff the train step AND
    the slot-table ``decode_step`` with request tracing armed vs off.
    Returns findings (ERROR on any host transfer or any added
    equation)."""
    import tempfile

    import jax

    from paddle_tpu.utils.flags import FLAGS

    findings: List[Finding] = []
    try:
        tr, feed = _tiny_trainer()
        rng = jax.random.PRNGKey(0)
        args = (tr.params, tr.state, tr.opt_state, {}, rng, feed)

        keep = (FLAGS.obs_timeline, FLAGS.obs_peak_flops)
        try:
            FLAGS.obs_timeline = True
            FLAGS.obs_peak_flops = 1e12  # force the MFU/FLOPs plumbing live
            from paddle_tpu.analysis import audit_fn

            findings.extend(audit_fn(
                tr._step_fn, *args, label="obs:train_step", checks=_CHECKS))
            on = jax.make_jaxpr(tr._step_fn)(*args)
            FLAGS.obs_timeline = False
            FLAGS.obs_peak_flops = 0.0
            off = jax.make_jaxpr(tr._step_fn)(*args)
        finally:
            FLAGS.obs_timeline, FLAGS.obs_peak_flops = keep
        if str(on) != str(off):
            findings.append(Finding(
                check="obs-step-drift", severity="ERROR",
                where="obs:train_step",
                message="the compiled train step DIFFERS with telemetry "
                        "enabled — instrumentation must stay host-side "
                        f"({len(on.jaxpr.eqns)} vs {len(off.jaxpr.eqns)} "
                        "top-level eqns)"))

        # request tracing (obs/trace.py): arm the tracer flags and re-pin
        # BOTH hot programs — the train step and the fused decode_step —
        # equation-identical to tracing-off (spans never enter the trace)
        dec_fn, dec_carry = _tiny_decode_step()
        spec_fn, spec_carry = _tiny_spec_step()
        keep_trace = (FLAGS.obs_journal, FLAGS.trace_sample)
        with tempfile.TemporaryDirectory() as td:
            try:
                FLAGS.obs_journal = td
                FLAGS.trace_sample = 1.0
                step_on = jax.make_jaxpr(tr._step_fn)(*args)
                dec_on = jax.make_jaxpr(dec_fn)(dec_carry)
                spec_on = jax.make_jaxpr(spec_fn)(spec_carry)
                FLAGS.obs_journal = ""
                step_off = jax.make_jaxpr(tr._step_fn)(*args)
                dec_off = jax.make_jaxpr(dec_fn)(dec_carry)
                spec_off = jax.make_jaxpr(spec_fn)(spec_carry)
            finally:
                FLAGS.obs_journal, FLAGS.trace_sample = keep_trace
        for tag, a, b in (("train_step", step_on, step_off),
                          ("decode_step", dec_on, dec_off),
                          ("spec_verify_step", spec_on, spec_off)):
            if str(a) != str(b):
                findings.append(Finding(
                    check="obs-trace-drift", severity="ERROR",
                    where=f"obs:{tag}",
                    message=f"the compiled {tag} DIFFERS with request "
                            "tracing armed — spans must stay host-side "
                            f"({len(a.jaxpr.eqns)} vs "
                            f"{len(b.jaxpr.eqns)} top-level eqns)"))
        # the set-up record (obs/timeline.py): the step traced inside an
        # open phase, as its first call is, against the step traced after
        # the close, as every later program is
        from paddle_tpu.obs import timeline

        keep_record = timeline._RECORD
        try:
            timeline._RECORD = timeline.SetupRecord()
            with timeline.setup_phase("first_step"):
                rec_open = jax.make_jaxpr(tr._step_fn)(*args)
            timeline._RECORD.closed = True
            with timeline.setup_phase("first_step"):
                rec_closed = jax.make_jaxpr(tr._step_fn)(*args)
        finally:
            timeline._RECORD = keep_record
        if str(rec_open) != str(rec_closed):
            findings.append(Finding(
                check="obs-setup-drift", severity="ERROR",
                where="obs:train_step",
                message="the compiled train step DIFFERS inside a phase of "
                        "the set-up record — a phase is two clock reads "
                        f"around the call ({len(rec_open.jaxpr.eqns)} vs "
                        f"{len(rec_closed.jaxpr.eqns)} top-level eqns)"))
    except Exception as e:  # a step that fails to trace is itself a finding
        findings.append(Finding(
            check="obs-build", severity="ERROR", where="obs:train_step",
            message=f"telemetry audit failed to build/trace the step: "
                    f"{type(e).__name__}: {e}"))
    return findings
