"""Milliseconds of a step the device spends in the operations whose scope
path holds one of ``scopes`` (own time, kernels included; forward,
recomputed forward under ``checkpoint`` and ``transpose(jvp(...))`` alike),
over the steps of the window: ``own_ms`` with ``scopes``, under a name of
its own because tests/benchmark/test_trace_scopes.py counts the metrics
that PR 25 gave to ``own_ms``.  ``None`` on a trace without the scopes."""

from benchmark import trace_scopes


def read(facts, scopes):
    parsed = trace_scopes.trace_of(facts)
    if parsed is None or not facts.get("steps"):
        return None
    ns = trace_scopes.scope_ns(parsed, scopes)
    return None if ns is None else ns / facts["steps"] / 1e6
