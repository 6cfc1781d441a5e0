"""How unevenly the router loaded the experts held, from the program's
counter ``moe_assignments`` over the window (``facts["expert_load"]``:
layer -> assignments per expert held): the largest over the layers of
busiest / mean.  ``None`` where the run has no such counter."""


def read(facts):
    load = facts.get("expert_load") or {}
    ratios = [max(counts) * len(counts) / sum(counts)
              for counts in load.values() if counts and sum(counts) > 0]
    return max(ratios) if ratios else None
