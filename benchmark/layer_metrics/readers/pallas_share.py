"""Share of device busy time spent in Mosaic (Pallas) kernels."""


def read(facts):
    t = facts.get("trace") or {}
    if not t.get("busy_s"):
        return None
    return 100.0 * t["kernel_s"] / t["busy_s"]
