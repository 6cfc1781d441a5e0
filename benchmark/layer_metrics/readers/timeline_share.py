"""Share of the window in the named phases of the trainer's StepTimeline."""


def read(facts, phases):
    stats = facts.get("timeline")
    if not stats or not facts.get("window_s"):
        return None
    return 100.0 * sum(stats.get(p, {}).get("total", 0.0)
                       for p in phases) / facts["window_s"]
