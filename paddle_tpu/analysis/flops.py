"""Analytic FLOPs walker + chip roofline tables — ONE source of truth.

MFU is computed from analytic matmul+conv FLOPs (XLA's
``cost_analysis`` undercounts ``lax.scan`` bodies and lets
rematerialization inflate an implementation's op count).  The walker has
two users, ``SGDTrainer.step_flops`` and through it the live ``train_mfu``
gauge (``paddle_tpu.obs.timeline``); neither carries a private copy.  The
peak tables below are held equal to ``benchmark/peaks.json``, what the
ledger's ``mfu_pct`` divides by (tests/test_repo_account.py).

Counting convention: 2*M*N*K per ``dot_general`` and
2*out_elems*(filter_spatial*Cin/groups) per ``conv_general_dilated``,
recursing through pjit/scan/cond/custom-vjp sub-jaxprs via the shared
``analysis.jaxpr_walk`` key table (scan bodies multiplied by trip count;
``cond`` counts its WORST branch, since exactly one executes).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

__all__ = ["count_jaxpr_flops", "jaxpr_flops", "chip_peak_flops",
           "chip_peak_bandwidth", "chip_hbm_bytes", "CHIP_PEAK_FLOPS",
           "CHIP_PEAK_BW", "CHIP_HBM_BYTES"]

#: chip peak dense FLOP/s (bf16) by device_kind substring, most specific
#: first — the denominator of every MFU number this repo publishes
CHIP_PEAK_FLOPS = (
    ("v6 lite", 918e12), ("v6e", 918e12),
    ("v5 lite", 197e12), ("v5e", 197e12), ("v5p", 459e12), ("v5", 459e12),
    ("v4", 275e12), ("v3", 123e12), ("v2", 46e12),
)

#: chip peak HBM bandwidth (bytes/s) — the other roofline axis
CHIP_PEAK_BW = (
    ("v6 lite", 1640e9), ("v6e", 1640e9),
    ("v5 lite", 819e9), ("v5e", 819e9), ("v5p", 2765e9), ("v5", 2765e9),
    ("v4", 1228e9), ("v3", 900e9), ("v2", 700e9),
)


_GiB = float(1 << 30)

#: HBM capacity per chip (bytes) — the denominator of the static
#: peak-live-bytes gate (``lint --hbm``)
CHIP_HBM_BYTES = (
    ("v6 lite", 32 * _GiB), ("v6e", 32 * _GiB),
    ("v5 lite", 16 * _GiB), ("v5e", 16 * _GiB), ("v5p", 95 * _GiB),
    ("v5", 95 * _GiB),
    ("v4", 32 * _GiB), ("v3", 32 * _GiB), ("v2", 16 * _GiB),
)


def _chip_lookup(kind: str, table) -> Optional[float]:
    """None off-TPU; a TPU generation the table does not name is an
    error — a peak borrowed from another chip makes every share computed
    from it wrong without saying so."""
    k = (kind or "").lower()
    if "tpu" not in k:
        return None
    for sub, val in table:
        if sub in k:
            return val
    raise ValueError(
        f"unknown TPU device_kind {kind!r}: add its peaks to "
        f"paddle_tpu/analysis/flops.py with their source")


def chip_peak_flops(kind: str) -> Optional[float]:
    """Peak dense FLOP/s for a ``device_kind`` string; None off-TPU."""
    return _chip_lookup(kind, CHIP_PEAK_FLOPS)


def chip_peak_bandwidth(kind: str) -> Optional[float]:
    """Peak HBM bytes/s for a ``device_kind`` string; None off-TPU."""
    return _chip_lookup(kind, CHIP_PEAK_BW)


def chip_hbm_bytes(kind: str) -> Optional[float]:
    """HBM bytes per chip for a ``device_kind`` string; None off-TPU."""
    return _chip_lookup(kind, CHIP_HBM_BYTES)


def count_jaxpr_flops(jaxpr) -> float:
    """Analytic matmul+conv FLOPs of an (open) jaxpr, recursing into
    sub-jaxprs through the shared known-key walker (the old
    recurse-into-every-param loop double-counted primitives carrying
    several sub-jaxprs — custom_vjp holds primal + fwd/bwd rules)."""
    from paddle_tpu.analysis.jaxpr_walk import eqn_subjaxprs

    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval
            k = float(np.prod([lhs.shape[d] for d in lc], dtype=np.float64))
            out = float(np.prod(eqn.outvars[0].aval.shape, dtype=np.float64))
            total += 2.0 * out * k
        elif name == "conv_general_dilated":
            dn = eqn.params["dimension_numbers"]
            rhs = eqn.invars[1].aval
            # rhs_spec[0]=out-chan dim, [1]=in-chan(per group), rest spatial
            k = float(np.prod([rhs.shape[d] for d in dn.rhs_spec[1:]],
                              dtype=np.float64))
            out = float(np.prod(eqn.outvars[0].aval.shape, dtype=np.float64))
            total += 2.0 * out * k
        elif name == "cond":
            # a cond executes ONE branch: count the worst case, not the
            # sum (the generic walker yields every branch)
            branches = eqn.params.get("branches", ())
            if branches:
                total += max(count_jaxpr_flops(b.jaxpr) for b in branches)
        else:
            for inner, mult in eqn_subjaxprs(eqn):
                total += mult * count_jaxpr_flops(inner)
    return total


def jaxpr_flops(fn, *args, **kwargs) -> Optional[float]:
    """Trace ``fn(*args, **kwargs)`` and return its analytic FLOPs, or
    None when the trace fails (a bench row degrades to ``mfu: null``
    rather than sinking the capture)."""
    import jax

    try:
        return count_jaxpr_flops(jax.make_jaxpr(fn)(*args, **kwargs).jaxpr)
    except Exception:
        return None
