"""Share of the roofline a scope of the program reaches: the least time the
chip could take for the work the scope does, max(operations / peak FLOP/s,
bytes / peak bytes/s) from ``peaks.json``, over the scope's own device time
in the trace.  The operations and bytes are computed here, from the
configuration's shapes and the program's counters, and are UNDER-counts
(padding rows, blocks cut by the diagonal and the layout changes inside the
scope are left out): a share over 100% would mean work counted that was not
done.  ``None`` where the trace has no such scope or the run no counter."""

from benchmark import trace_scopes


def moe_experts_work(cfg: dict, expert_load: dict, steps: int):
    """(operations, bytes) a step of the experts' grouped products: per
    assignment 3 products forward, 3 when the forward is recomputed, 6
    backward (da, dW2, dW1, dW3 and the two halves of dx), each 2 x D x F;
    the weights of the experts held read once per pass (3 passes), their
    gradients written once, float32; bf16 rows in and out."""
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    passes = 3 if cfg.get("recompute_layers") else 2
    products = 3 * (passes - 1) + 6
    ops = nbytes = 0.0
    for counts in expert_load.values():
        rows = sum(counts) / steps
        ops += rows * products * 2 * D * F
        nbytes += (passes + 1) * 3 * len(counts) * D * F * 4
        nbytes += rows * 2 * passes * (2 * D + 3 * F)
    return ops, nbytes


def attention_work(cfg: dict, traffic: dict):
    """(operations, bytes) a step of the causal attention cores: 2 products
    forward (scores, values) and 5 backward, each 2 x head_dim an element
    of the T(T+1)/2 at or under the diagonal, per query head (the forward
    is not run again when its layer is recomputed: its output is kept); q,
    k, v, the output and their gradients moved once a pass in bf16."""
    T, B = traffic["seq_len"], traffic["batch"]
    H, Hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    layers = sum(k == "full_attention" for k in cfg["layer_types"])
    forward = 1   # the kernels' output is kept across a recomputation block
    products = 2 * forward + 5
    ops = layers * B * H * (T * (T + 1) / 2) * products * 2 * dh
    nbytes = layers * B * T * dh * 2 * (forward * (2 * H + 2 * Hkv)
                                        + 4 * H + 4 * Hkv)
    return ops, nbytes


def read(facts, kind, scopes):
    parsed = trace_scopes.trace_of(facts)
    cfg, steps = facts.get("config"), facts.get("steps")
    if parsed is None or not cfg or not steps:
        return None
    ns = trace_scopes.scope_ns(parsed, scopes)
    if not ns:
        return None
    if kind == "moe_experts":
        if not facts.get("expert_load"):
            return None
        ops, nbytes = moe_experts_work(cfg, facts["expert_load"], steps)
    elif kind == "attention":
        ops, nbytes = attention_work(cfg, facts["traffic"])
    else:
        raise ValueError(f"no work function for {kind!r}")
    peaks = facts["peaks"]
    least_s = max(ops / peaks["bf16_flops_per_s"],
                  nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ns / steps / 1e9)
