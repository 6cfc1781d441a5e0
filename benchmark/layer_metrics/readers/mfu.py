"""Model FLOP/s utilization of the traced window, in percent."""


def read(facts):
    if not facts.get("flops_per_step") or not facts.get("steps"):
        return None
    rate = facts["flops_per_step"] * facts["steps"] / facts["window_s"]
    return 100.0 * rate / (facts["chips"] * facts["peaks"]["bf16_flops_per_s"])
