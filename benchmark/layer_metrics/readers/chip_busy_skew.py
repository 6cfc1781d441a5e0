"""How unevenly the chips of a cell were busy in the traced window:
(busiest chip - idlest chip) over the mean busy time, in percent.  ``None``
on a trace of one chip."""

from benchmark import trace_chips


def read(facts):
    chips = trace_chips.chips_of(facts)
    if not chips or len(chips) < 2:
        return None
    busy = [c["busy_ns"] for c in chips]
    mean = sum(busy) / len(busy)
    return 100.0 * (max(busy) - min(busy)) / mean if mean else None
