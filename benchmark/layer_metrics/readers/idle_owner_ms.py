"""Milliseconds of a step in which the device was idle while the innermost
open ``paddle_tpu.trainer.*`` span on the trainer's thread was one of
``owners`` (names without the prefix); with ``others``, while it was any
other span or none, so that the metrics of one cell sum to the idle time of
the window.  ``None`` on a trace without the trainer's spans."""

from benchmark import trace_scopes


def read(facts, owners, others=False):
    parsed = trace_scopes.trace_of(facts)
    if parsed is None or not facts.get("steps"):
        return None
    idle = trace_scopes.idle_by_owner(parsed)
    if idle is None:
        return None
    ns = sum(v for k, v in idle.items() if (k in owners) != bool(others))
    return ns / facts["steps"] / 1e6
