"""Share of the roofline that the latent-attention cores reach: the least
time the chip could take for their work, max(operations / peak FLOP/s, bytes
/ peak bytes/s) from ``peaks.json``, over the own device time of the scope
``attn_core`` in the trace.  ``roofline.py`` knows one ``head_dim``; latent
attention scores with ``qk_nope_head_dim + qk_rope_head_dim`` channels and
sums values of ``v_head_dim``, so its work is computed here.  An UNDER-count,
as ``roofline.py``'s: the elements at or under the diagonal (fewer than the
blocks the kernels run), each product once (the backward kernels recompute
the scores twice and ``dp`` twice), bf16 bytes (the gradients leave the
kernels in float32), no layout change.  ``None`` where the trace has no such
scope or the configuration no latent attention."""

from benchmark import trace_scopes


def mla_core_work(cfg: dict, traffic: dict):
    """(operations, bytes) a step of the causal latent-attention cores.
    Forward, once (its output is kept across the layer's recomputation): the
    scores over ``dqk`` channels and the values over ``dv``.  Backward: the
    scores, ``dq`` and ``dk`` over ``dqk``; ``dp`` and ``dv`` over ``dv``.
    Each 2 operations a channel and element of the ``T (T + 1) / 2`` at or
    under the diagonal, per head: 2 x ((1 + 3) x 192 + (1 + 2) x 128) = 2304
    at the published widths.  q, k, v, the output and their gradients moved
    once a pass at their own widths, in bf16."""
    T, B = traffic["seq_len"], traffic["batch"]
    H = cfg["num_attention_heads"]
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    layers = cfg["num_hidden_layers"]
    ops = layers * B * H * (T * (T + 1) / 2) * 2 * (4 * dqk + 3 * dv)
    nbytes = layers * B * T * H * 2 * ((2 * dqk + 2 * dv)      # forward
                                       + (4 * dqk + 4 * dv))   # backward
    return ops, nbytes


def read(facts, scopes):
    parsed = trace_scopes.trace_of(facts)
    cfg, steps = facts.get("config"), facts.get("steps")
    if parsed is None or not cfg or not steps or "kv_lora_rank" not in cfg:
        return None
    ns = trace_scopes.scope_ns(parsed, scopes)
    if not ns:
        return None
    ops, nbytes = mla_core_work(cfg, facts["traffic"])
    peaks = facts["peaks"]
    least_s = max(ops / peaks["bf16_flops_per_s"],
                  nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ns / steps / 1e9)
