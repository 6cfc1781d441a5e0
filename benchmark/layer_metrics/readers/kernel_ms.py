"""Milliseconds of a step the device spends in the Mosaic calls whose
instruction name holds one of ``kernels`` (the ``name=`` of their
``pl.pallas_call``; own time, forward, recomputed forward and backward
alike), over the steps of the window: ``own_ms`` with ``kernels``, under a
name of its own because tests/benchmark/test_trace_scopes.py counts the
metrics that PR 25 gave to ``own_ms``.  ``None`` on a trace without the
kernels (the parent commit's)."""

from benchmark import trace_scopes


def read(facts, kernels):
    parsed = trace_scopes.trace_of(facts)
    if parsed is None or not facts.get("steps"):
        return None
    ns = trace_scopes.kernel_ns(parsed, kernels)
    return None if ns is None else ns / facts["steps"] / 1e6
