"""``lint --hbm`` — static HBM footprint + donation audit.

An OOM or a silently-unhonored donation shows up as a pod falling over
(or a 2x HBM bill) minutes into a run; both are visible in the *closed
jaxpr* before anything compiles.  This pass runs the
``analysis.jaxpr_walk.peak_live_bytes`` liveness walk (buffers born at
their producing eqn, dead after last read, donated args credited at
their donation point) over the real compiled steps and reports:

- ``hbm-peak`` — static peak live bytes vs the chip HBM table
  (``analysis.flops.CHIP_HBM_BYTES``): INFO with the utilization when it
  fits, ERROR when the step cannot fit the chip (off-TPU there is no
  capacity and the estimate reports as INFO);
- ``hbm-donation-reuse`` (ERROR) — a donated argument still read AFTER
  the eqn producing its shape/dtype-matched output: XLA cannot honor the
  aliasing and silently materializes a copy, exactly the 2x-params bill
  donation exists to avoid;
- ``hbm-donation-unmatched`` (WARN) — a donated argument with no
  shape/dtype-matched output at all (the donation is silently dropped);
- ``hbm-f64-const`` (ERROR) — a float64 constant/literal in the trace:
  besides the 2x bytes, an x64 constant makes the jaxpr — and therefore
  the compile-cache key — differ from the f32 trace every other process
  builds;
- ``hbm-weak-arg`` (WARN) — a weak-type argument aval (a Python scalar
  passed positionally): weak/strong flips retrace and defeat the
  persistent compile cache key (docs/deploy.md).

``run_hbm()`` audits the representative trainer step (the exact
``_step_fn`` closure ``train_batch`` compiles, with its real
``donate_argnums=(0, 2, 3)``) and the flagship fused decode step;
``audit_hbm_jaxpr`` is the direct entry for any closed jaxpr.
"""

from __future__ import annotations

from typing import List, Sequence

from paddle_tpu.analysis.findings import Finding

__all__ = ["audit_hbm_jaxpr", "run_hbm"]


def _fmt_bytes(n: float) -> str:
    if n >= 1 << 30:
        return f"{n / (1 << 30):.2f} GiB"
    return f"{n / (1 << 20):.1f} MiB"


def _donation_findings(jaxpr, donate_argnums: Sequence[int],
                       label: str) -> List[Finding]:
    from paddle_tpu.analysis.jaxpr_walk import _is_var

    findings: List[Finding] = []
    producer = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.outvars:
            producer[v] = i
    claimed = set()
    for argnum in donate_argnums:
        if not 0 <= argnum < len(jaxpr.invars):
            continue
        inv = jaxpr.invars[argnum]
        sig = (tuple(getattr(inv.aval, "shape", ())),
               str(getattr(inv.aval, "dtype", "")))
        if inv in jaxpr.outvars:
            continue  # identity passthrough: trivially aliasable
        match = None
        for out in jaxpr.outvars:
            if not _is_var(out) or out in claimed or out not in producer:
                continue
            osig = (tuple(getattr(out.aval, "shape", ())),
                    str(getattr(out.aval, "dtype", "")))
            if osig == sig:
                match = out
                break
        if match is None:
            findings.append(Finding(
                check="hbm-donation-unmatched", severity="WARN",
                where=f"{label}/invar[{argnum}]",
                message=f"donated arg {argnum} {sig[0]}:{sig[1]} has no "
                        f"shape/dtype-matched output — the donation is "
                        f"silently dropped and the buffer stays live"))
            continue
        claimed.add(match)
        # the donated buffer is reused the moment the matched output is
        # produced; any read of the input AFTER that eqn needs the old
        # bytes, so XLA copies and the donation saves nothing
        last_read = max((i for i, eqn in enumerate(jaxpr.eqns)
                         if inv in eqn.invars), default=-1)
        if last_read > producer[match]:
            findings.append(Finding(
                check="hbm-donation-reuse", severity="ERROR",
                where=f"{label}/invar[{argnum}]",
                message=f"donated arg {argnum} {sig[0]}:{sig[1]} is still "
                        f"read at eqn[{last_read}] after its aliased "
                        f"output is produced at eqn[{producer[match]}] — "
                        f"donation cannot be honored (silent copy; "
                        f"use-after-donation)"))
    return findings


def _const_findings(closed, label: str) -> List[Finding]:
    import numpy as np

    from paddle_tpu.analysis.jaxpr_walk import walk_eqns

    findings: List[Finding] = []
    jaxpr = getattr(closed, "jaxpr", closed)
    for i, v in enumerate(getattr(closed, "consts", ()) or ()):
        dt = np.asarray(v).dtype if not hasattr(v, "dtype") else v.dtype
        if str(dt) in ("float64", "complex128", "int64") and \
                str(dt) == "float64":
            findings.append(Finding(
                check="hbm-f64-const", severity="ERROR",
                where=f"{label}/const[{i}]",
                message=f"float64 constant {tuple(np.shape(v))} in the "
                        f"trace: 2x HBM and a compile-cache key no f32 "
                        f"process reproduces (jnp.asarray(..., "
                        f"jnp.float32) it)"))
    for eqn, path in walk_eqns(jaxpr):
        for v in eqn.invars:
            if hasattr(v, "val"):  # Literal
                dt = getattr(getattr(v, "aval", None), "dtype", None)
                if dt is not None and str(dt) == "float64":
                    findings.append(Finding(
                        check="hbm-f64-const", severity="ERROR",
                        where=f"{label}/{path}",
                        message="float64 literal in the trace defeats "
                                "the f32 compile-cache key (and doubles "
                                "the constant's HBM)"))
    for i, v in enumerate(jaxpr.invars):
        if getattr(getattr(v, "aval", None), "weak_type", False):
            findings.append(Finding(
                check="hbm-weak-arg", severity="WARN",
                where=f"{label}/invar[{i}]",
                message=f"argument {i} traces weak-typed (a bare Python "
                        f"scalar): weak/strong flips retrace the step "
                        f"and defeat the persistent compile cache key"))
    return findings


def audit_hbm_jaxpr(closed, *, donate_argnums: Sequence[int] = (),
                    label: str = "step") -> List[Finding]:
    """Full ``--hbm`` check set over one closed jaxpr: peak-live-bytes vs
    the chip table, donation audit, f64/weak-type constants."""
    from paddle_tpu.analysis.flops import chip_hbm_bytes
    from paddle_tpu.analysis.jaxpr_walk import peak_live_bytes

    findings: List[Finding] = []
    stats = peak_live_bytes(closed, donate_argnums)
    peak = stats["peak_bytes"]
    try:
        import jax

        kind = jax.devices()[0].device_kind
    except Exception:  # no backend at all: report the estimate bare
        kind = ""
    cap = chip_hbm_bytes(kind)  # an unknown TPU raises — see flops.py
    msg = (f"static peak live {_fmt_bytes(peak)} (args "
           f"{_fmt_bytes(stats['args_bytes'])}, consts "
           f"{_fmt_bytes(stats['consts_bytes'])}, outputs "
           f"{_fmt_bytes(stats['out_bytes'])}, donated "
           f"{_fmt_bytes(stats['donated_bytes'])})")
    if cap:
        pct = 100.0 * peak / cap
        fits = peak <= cap
        findings.append(Finding(
            check="hbm-peak", severity="INFO" if fits else "ERROR",
            where=label,
            message=msg + f" = {pct:.1f}% of chip HBM "
                          f"({_fmt_bytes(cap)})"
                    + ("" if fits else " — the step cannot fit")))
    else:
        findings.append(Finding(
            check="hbm-peak", severity="INFO", where=label,
            message=msg + " (no TPU backend: chip capacity unknown)"))
    jaxpr = getattr(closed, "jaxpr", closed)
    findings.extend(_donation_findings(jaxpr, donate_argnums, label))
    findings.extend(_const_findings(closed, label))
    return findings


def _train_step_closed():
    """Trace the representative trainer's REAL ``_step_fn`` (embedding +
    stacked LSTM + BN head + CE, the amp-audit shape) and return
    ``(closed_jaxpr, donate_argnums)`` — the same (0, 2, 3) donation the
    trainer's jit applies (params, opt_state, accumulators in place)."""
    import jax

    from paddle_tpu.analysis.amp_audit import _amp_trainer

    tr, feed = _amp_trainer()
    rng = jax.random.PRNGKey(0)
    args = (tr.params, tr.state, tr.opt_state, {}, rng, feed)
    closed = jax.make_jaxpr(tr._step_fn)(*args)
    # jit's donate_argnums are PYTREE positions; the jaxpr's invars are
    # the flattened leaves — map (0, 2, 3) to flat leaf index ranges
    donate = []
    off = 0
    for argnum, a in enumerate(args):
        n = len(jax.tree_util.tree_leaves(a))
        if argnum in (0, 2, 3):
            donate.extend(range(off, off + n))
        off += n
    return closed, tuple(donate)


def _decode_step_closed():
    """Trace the flagship fused decode closure at a compact
    flagship-shaped model (the ``--decode`` audit shape)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import Seq2SeqAttention

    B, S, K, L = 8, 8, 4, 8
    m = Seq2SeqAttention(src_vocab=1024, trg_vocab=1024, emb_dim=128,
                         enc_dim=128, dec_dim=128, att_dim=128)
    params = m.init(jax.random.PRNGKey(0))
    src = jnp.zeros((B, S), jnp.int32)
    src_len = jnp.full((B,), S, jnp.int32)
    closed = jax.make_jaxpr(
        lambda p, s, l: m.beam_search(p, s, l, beam_size=K, max_len=L))(
        params, src, src_len)
    return closed, ()


def run_hbm() -> List[Finding]:
    """The ``--hbm`` pass: audit the real compiled train step and decode
    step (build failures are findings, never crashes)."""
    findings: List[Finding] = []
    for name, build in (("hbm:train_step", _train_step_closed),
                        ("hbm:decode_step", _decode_step_closed)):
        try:
            closed, donate = build()
        except Exception as e:
            findings.append(Finding(
                check="hbm-build", severity="ERROR", where=name,
                message=f"step failed to trace for the HBM audit: "
                        f"{type(e).__name__}: {e}"))
            continue
        try:
            findings.extend(audit_hbm_jaxpr(
                closed, donate_argnums=donate, label=name))
        except Exception as e:  # auditor bug: a finding, not a crash
            findings.append(Finding(
                check="hbm-build", severity="INFO", where=name,
                message=f"HBM auditor internal error: "
                        f"{type(e).__name__}: {e}"))
    return findings
