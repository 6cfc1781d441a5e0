"""Shared jaxpr traversal — ONE definition of "recurse into sub-jaxprs".

Grown out of a FLOPs walker that recursed into *every*
jaxpr-valued param of every primitive: primitives carrying several
sub-jaxprs (``custom_vjp_call`` holds the primal *and* fwd/bwd rules,
``linear_solve`` holds four) were double-counted.  Here recursion is
per-primitive into the known key — ``scan``/``while``/``cond`` get their
trip-count/branch semantics, everything else takes the FIRST of
``call_jaxpr``/``jaxpr``/``fun_jaxpr`` (the primal computation the
primitive will actually execute once).
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Sequence, Set, Tuple

__all__ = ["eqn_subjaxprs", "walk_eqns", "find_primitives",
           "aval_bytes", "peak_live_bytes"]

#: primal-computation param keys, most specific first; exactly ONE is taken
_PRIMAL_KEYS = ("call_jaxpr", "jaxpr", "fun_jaxpr")


def _as_jaxpr(v):
    """Unwrap ClosedJaxpr -> Jaxpr; None for non-jaxpr values."""
    inner = getattr(v, "jaxpr", v)
    return inner if hasattr(inner, "eqns") else None


def eqn_subjaxprs(eqn) -> Iterator[Tuple[object, float]]:
    """Yield ``(jaxpr, multiplier)`` for the sub-jaxprs the primitive
    executes.  ``scan`` bodies carry their trip count as the multiplier
    (the case XLA's own FLOPs counter gets wrong); ``cond`` yields every
    branch with multiplier 1 — callers wanting max-over-branches (FLOPs)
    must special-case ``cond`` themselves."""
    name = eqn.primitive.name
    params = eqn.params
    if name == "scan":
        inner = _as_jaxpr(params.get("jaxpr"))
        if inner is not None:
            yield inner, float(params.get("length", 1))
        return
    if name == "while":
        for key in ("cond_jaxpr", "body_jaxpr"):
            inner = _as_jaxpr(params.get(key))
            if inner is not None:
                yield inner, 1.0
        return
    if name == "cond":
        for branch in params.get("branches", ()):
            inner = _as_jaxpr(branch)
            if inner is not None:
                yield inner, 1.0
        return
    for key in _PRIMAL_KEYS:
        inner = _as_jaxpr(params.get(key))
        if inner is not None:
            yield inner, 1.0
            return
    # unknown primitive without a known key: take the FIRST jaxpr-valued
    # param only — never sum over all of them (that is the double-count)
    for v in params.values():
        inner = _as_jaxpr(v)
        if inner is not None:
            yield inner, 1.0
            return


def walk_eqns(jaxpr, path: str = "", *,
              max_depth: int = 32) -> Iterator[Tuple[object, str]]:
    """Depth-first (eqn, provenance-path) pairs over ``jaxpr`` and every
    sub-jaxpr.  Paths look like ``eqn[4]:scan/eqn[1]:dot_general``."""
    jaxpr = _as_jaxpr(jaxpr) or jaxpr
    if max_depth <= 0:
        return
    for i, eqn in enumerate(getattr(jaxpr, "eqns", ())):
        here = f"{path}/eqn[{i}]:{eqn.primitive.name}" if path else \
            f"eqn[{i}]:{eqn.primitive.name}"
        yield eqn, here
        for inner, _mult in eqn_subjaxprs(eqn):
            yield from walk_eqns(inner, here, max_depth=max_depth - 1)


def find_primitives(jaxpr, names: Set[str],
                    path: str = "") -> List[Tuple[str, str]]:
    """All (primitive-name, path) occurrences of ``names`` anywhere in the
    (possibly nested) jaxpr — e.g. residual scan/while after an unrolling
    export (config/deploy._unrolled_scans verification)."""
    return [(eqn.primitive.name, p) for eqn, p in walk_eqns(jaxpr, path)
            if eqn.primitive.name in names]


def aval_bytes(aval) -> int:
    """HBM bytes of one abstract value (0 for tokens/abstract avals)."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        try:
            n *= int(d)
        except (TypeError, ValueError):  # symbolic dim: count as 1
            pass
    return n * getattr(dtype, "itemsize", 4)


def _is_var(v) -> bool:
    return hasattr(v, "aval") and not hasattr(v, "val")  # Var, not Literal


def _last_uses(jaxpr) -> Dict[object, int]:
    """var -> index of the LAST eqn reading it (len(eqns) for jaxpr
    outputs, which stay live to the end; absent = never read)."""
    last: Dict[object, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if _is_var(v):
                last[v] = i
    for v in jaxpr.outvars:
        if _is_var(v):
            last[v] = len(jaxpr.eqns)
    return last


def _open_peak(jaxpr) -> Tuple[int, int]:
    """(peak live bytes, boundary bytes) of an OPEN jaxpr, all inputs
    treated non-donated.  ``boundary`` = invars + constvars + outvars —
    the bytes that alias the enclosing scope's buffers, which a caller
    subtracts to get the sub-jaxpr's *transient* contribution."""
    stats = _liveness(jaxpr, donated=frozenset())
    boundary = (stats["args_bytes"] + stats["consts_bytes"]
                + stats["out_bytes"])
    return stats["peak_bytes"], boundary


def _inner_extra(eqn) -> int:
    """Transient HBM a primitive's sub-jaxpr needs beyond its own
    boundary buffers (worst branch for ``cond``; one iteration's
    transients for ``scan``/``while`` — buffers are reused per step)."""
    extra = 0
    for inner, _mult in eqn_subjaxprs(eqn):
        peak, boundary = _open_peak(inner)
        extra = max(extra, max(0, peak - boundary))
    return extra


def _liveness(jaxpr, donated: frozenset) -> Dict[str, int]:
    last = _last_uses(jaxpr)
    alive: Set[object] = set()
    cur = 0
    args_bytes = consts_bytes = 0
    for v in jaxpr.constvars:
        consts_bytes += aval_bytes(v.aval)
    for v in jaxpr.invars:
        args_bytes += aval_bytes(v.aval)
    for v in list(jaxpr.constvars) + list(jaxpr.invars):
        if v in alive:
            continue
        alive.add(v)
        cur += aval_bytes(v.aval)
        if v not in donated:
            # the caller owns a non-donated input: its buffer exists for
            # the whole program whether or not we still read it
            last[v] = max(last.get(v, 0), len(jaxpr.eqns))
    peak = cur
    # free never-read donated inputs/consts immediately
    for v in list(alive):
        if last.get(v, -1) < 0:
            cur -= aval_bytes(v.aval)
            alive.discard(v)
    for i, eqn in enumerate(jaxpr.eqns):
        born = sum(aval_bytes(v.aval) for v in eqn.outvars)
        peak = max(peak, cur + born + _inner_extra(eqn))
        for v in eqn.outvars:
            if v not in alive:
                alive.add(v)
                cur += aval_bytes(v.aval)
        for v in list(eqn.invars) + list(eqn.outvars):
            if _is_var(v) and v in alive and last.get(v, -1) <= i:
                cur -= aval_bytes(v.aval)
                alive.discard(v)
    out_bytes = sum(aval_bytes(v.aval) for v in jaxpr.outvars
                    if hasattr(v, "aval"))
    return {"peak_bytes": peak, "args_bytes": args_bytes,
            "consts_bytes": consts_bytes, "out_bytes": out_bytes,
            "end_bytes": cur}


def peak_live_bytes(closed, donate_argnums: Sequence[int] = ()
                    ) -> Dict[str, int]:
    """Static peak-live-bytes estimate of a (closed) jaxpr.

    A liveness walk over eqn outputs: every buffer is born at its
    producing eqn, dies after its last read, non-donated arguments and
    jaxpr outputs stay live for the whole program, and donated arguments
    are credited back at their donation point (last read — XLA reuses the
    buffer for a shape/dtype-matched output from there).  Sub-jaxprs
    (scan/while/cond bodies) contribute their transient peak on top of
    the live set at their eqn.  Returns ``{"peak_bytes", "args_bytes",
    "consts_bytes", "out_bytes", "donated_bytes"}``."""
    jaxpr = getattr(closed, "jaxpr", closed)
    donated = frozenset(jaxpr.invars[i] for i in donate_argnums
                        if 0 <= i < len(jaxpr.invars))
    stats = _liveness(jaxpr, donated)
    stats["donated_bytes"] = sum(aval_bytes(v.aval) for v in donated)
    del stats["end_bytes"]
    return stats


def hlo_control_flow(hlo_text: str) -> List[str]:
    """Control-flow op mnemonics present in an HLO/StableHLO text dump —
    the post-lowering half of the scan-unrolling verification: after
    ``export_aot_hlo(unroll_scans=True)`` the module should contain no
    ``while``/``conditional`` ops (the trace-time patch is best-effort;
    anything that bound ``lax.scan`` early, or used ``while_loop``
    directly, still lowers a loop)."""
    found = []
    for op in ("while", "conditional"):
        # HLO text: `%x = ... while(...)`; StableHLO: `"stablehlo.while"` /
        # `stablehlo.while(` — match the op mnemonic at a call position
        if f" {op}(" in hlo_text or f".{op}\"" in hlo_text or \
                f"stablehlo.{op}" in hlo_text:
            found.append(op)
    return found
