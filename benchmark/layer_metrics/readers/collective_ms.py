"""Milliseconds of a step a chip spends in collective operations
(all-reduce, reduce-scatter, all-gather, collective-permute, all-to-all and
their ``-start`` / ``-done`` halves): own time on the ``XLA Ops`` line, mean
over the chips, over the window's steps.  With ``exposed``: of that, the
time in which the chip runs nothing else (``trace_chips.exposed_ns_of`` has
the definition).  ``None`` on a trace of one chip or without a collective:
nothing to read is not 0."""

from benchmark import trace_chips


def read(facts, exposed=False):
    chips = trace_chips.chips_of(facts)
    if not chips or len(chips) < 2 or not facts.get("steps"):
        return None
    if not any(c["collective_ns"] for c in chips):
        return None
    key = "exposed_ns" if exposed else "collective_ns"
    return sum(c[key] for c in chips) / len(chips) / facts["steps"] / 1e6
