"""Milliseconds of a step the device spends in a set of operations, forward
and backward together, over the steps of the window (own time: a ``while``
does not count its body twice).  With ``kernels``: the Mosaic calls whose
instruction name holds one of them (the ``name=`` of the ``pl.pallas_call``).
With ``scopes``: every operation, kernels included, whose scope path (the
``tf_op`` stat of its event metadata, see benchmark/trace_scopes.py) holds
one of them, in forward or ``transpose(jvp(...))`` form.  ``None`` on a trace
in which nothing carries the names."""

from benchmark import trace_scopes


def read(facts, kernels=(), scopes=()):
    parsed = trace_scopes.trace_of(facts)
    if parsed is None or not facts.get("steps"):
        return None
    ns = (trace_scopes.kernel_ns(parsed, kernels) if kernels
          else trace_scopes.scope_ns(parsed, scopes))
    return None if ns is None else ns / facts["steps"] / 1e6
