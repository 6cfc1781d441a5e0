"""Learned sparse attention's work counts and what is read against them.

``kind="kept_share"``: the share of the causal (query, position) pairs the
selection kept, in percent, from the program's counters: the registry's
``sparse_attn_kept_pairs`` summed over its layers, over ``train_batches_total
x layers x batch x T (T + 1) / 2``; 23.44 at a row of 16384 with 2048 kept
if the selection does what the configuration says.

``kind="indexer" | "topk_select" | "attn_selected"``: the share of its
roofline that a scope of the attention layers reaches: the least time the
chip could take for the scope's work, max(operations / peak FLOP/s, bytes /
peak bytes/s) from ``peaks.json``, over the scope's own device time in the
trace.  The work functions read the configuration and the traffic, never the
implementation: a later kernel is read against the same work.  All three are
UNDER-counts of what today's kernels do: the mathematics once forward and
(where a gradient flows) twice backward, operands in bf16 and each array
once, though the attention's kernels visit every causal tile and drop what
is not kept, the selection reads its scores 47 times from VMEM, and the
indexer's scope also holds its three projections, which are not counted.

``None`` where the trace has no such scope, the registry no such counter, or
the configuration no ``sa_config`` (the parent commit's run)."""

from benchmark import trace_scopes


def causal_pairs(T: int) -> int:
    return T * (T + 1) // 2


def kept_pairs(T: int, topk: int) -> int:
    """``sum_t min(t + 1, topk)`` over a row of ``T`` queries."""
    full = min(T, topk)
    return full * (full + 1) // 2 + (T - full) * topk


def _sizes(cfg: dict, traffic: dict):
    sa = cfg["sa_config"]
    return (traffic["batch"], traffic["seq_len"], cfg["num_hidden_layers"],
            sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"])


def indexer_work(cfg: dict, traffic: dict):
    """(operations, bytes) a step of the indexer's scores: ``J`` products of
    ``d`` channels, a ReLU and a weighted sum over every causal pair, 2
    operations a multiply-add, once (no gradient flows through the scores
    the selection reads; the loss's pass over them is ``indexer_loss``'s);
    bytes: ``qI`` and ``kI`` in bf16 and ``w`` in float32 in, the scores
    float32 out over the causal pairs, once."""
    B, T, layers, J, d, _ = _sizes(cfg, traffic)
    ops = layers * B * causal_pairs(T) * (2 * J * d + 3 * J)
    nbytes = layers * B * (T * J * d * 2 + T * d * 2 + T * J * 4
                           + causal_pairs(T) * 4)
    return ops, nbytes


def topk_select_work(cfg: dict, traffic: dict):
    """(operations, bytes) a step of the selection: bandwidth alone: the
    causal scores read once in float32, a threshold a row out (no
    operations are counted: a comparison a pair is not the MXU's work)."""
    B, T, layers, _, _, _ = _sizes(cfg, traffic)
    return 0.0, layers * B * (causal_pairs(T) * 4 + T * 4)


def attn_selected_work(cfg: dict, traffic: dict):
    """(operations, bytes) a step of the attention over the kept pairs, as
    the reference's ``step_flops`` counts them: ``q k^T`` and ``p v`` over
    ``dh`` channels for every head and kept pair, once forward and twice
    backward; bytes: q, k, v in and o out forward, q, k, v, o, do in and dq,
    dk, dv out backward, in bf16, and the selection as one BIT a causal pair
    each way."""
    B, T, layers, _, _, topk = _sizes(cfg, traffic)
    H, Hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    ops = layers * B * kept_pairs(T, topk) * 3 * 2 * H * 2 * dh
    rows = layers * B * T * dh * 2
    nbytes = rows * ((2 * H + 2 * Hkv) + (4 * H + 4 * Hkv))
    nbytes += layers * B * 2 * causal_pairs(T) / 8
    return ops, nbytes


WORK = {"indexer": indexer_work, "topk_select": topk_select_work,
        "attn_selected": attn_selected_work}


def kept_share_pct(cfg: dict, traffic: dict):
    """From the registry (this process's): pairs kept over causal pairs."""
    try:
        from paddle_tpu.obs import get_registry
    except ImportError:
        return None
    snap = get_registry().snapshot()
    kept = sum(s["value"] for s in snap.get(
        "sparse_attn_kept_pairs", {}).get("series", []))
    steps = sum(s["value"] for s in snap.get(
        "train_batches_total", {}).get("series", []))
    if not kept or not steps:
        return None
    B, T, layers = (traffic["batch"], traffic["seq_len"],
                    cfg["num_hidden_layers"])
    return 100.0 * kept / (steps * layers * B * causal_pairs(T))


def read(facts, kind, scopes=()):
    cfg, traffic = facts.get("config"), facts.get("traffic")
    if not cfg or not traffic or "sa_config" not in cfg:
        return None
    if kind == "kept_share":
        return kept_share_pct(cfg, traffic)
    if kind not in WORK:
        raise ValueError(f"no work function for {kind!r}")
    parsed, steps = trace_scopes.trace_of(facts), facts.get("steps")
    if parsed is None or not steps:
        return None
    ns = trace_scopes.scope_ns(parsed, scopes)
    if not ns:
        return None
    ops, nbytes = WORK[kind](cfg, traffic)
    peaks = facts["peaks"]
    least_s = max(ops / peaks["bf16_flops_per_s"],
                  nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ns / steps / 1e9)
