"""Share of the roofline that the gated delta rule's chunked scan reaches:
the least time the chip could take for its work, max(operations / peak
FLOP/s, bytes / peak bytes/s) from ``peaks.json``, over the own device time
of the scope ``gdn_scan`` in the trace (the kernels ``gdn_chunk_fwd`` /
``gdn_chunk_bwd`` and the layout changes around them).  An UNDER-count, as
its two neighbours': each product of a chunk's WY form once forward and
twice backward (the backward kernel makes the forward's again before its
own, and a recomputed layer runs the forward kernel twice), the 64 x 64
solve not counted, operands and results in bf16, every chunk's starting
state written once and read once in float32, no layout change.  ``None``
where the trace has no such scope or the configuration no delta net."""

from benchmark import trace_scopes

#: tokens per chunk of the program's scan (ops/delta_rule.py ``CHUNK``)
CHUNK = 64


def gdn_scan_work(cfg: dict, traffic: dict):
    """(operations, bytes) a step of the delta-rule scans.  A chunk of ``C``
    tokens of one value head: ``k k^T`` and ``q k^T`` (``C x C x dk``), the
    solve's application and the output's in-chunk part (``C x C x dv``), and
    three products with the state (``C x dk x dv``: the correction read
    under the keys, the output read under the queries, the state's update);
    2 operations a multiply-add, once forward and twice backward.  Bytes: q,
    k, v in and o out forward; q, k, v, do in and dq, dk, dv out backward, in
    bf16; the chunks' starting states ``[dk, dv]`` float32 out forward and
    in backward."""
    T, B = traffic["seq_len"], traffic["batch"]
    Hv = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    every = cfg["full_attention_interval"]
    layers = sum((i + 1) % every != 0 for i in range(cfg["num_hidden_layers"]))
    chunks = -(-T // CHUNK)
    C = CHUNK
    forward = 2 * (2 * C * C * dk + 2 * C * C * dv + 3 * C * dk * dv)
    ops = layers * B * Hv * chunks * 3 * forward
    rows = layers * B * Hv * T
    nbytes = rows * 2 * ((2 * dk + 2 * dv) + (4 * dk + 3 * dv))
    nbytes += layers * B * Hv * chunks * dk * dv * 4 * 2
    return ops, nbytes


def read(facts, scopes):
    parsed = trace_scopes.trace_of(facts)
    cfg, steps = facts.get("config"), facts.get("steps")
    if (parsed is None or not cfg or not steps
            or "linear_num_value_heads" not in cfg):
        return None
    ns = trace_scopes.scope_ns(parsed, scopes)
    if not ns:
        return None
    ops, nbytes = gdn_scan_work(cfg, facts["traffic"])
    peaks = facts["peaks"]
    least_s = max(ops / peaks["bf16_flops_per_s"],
                  nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ns / steps / 1e9)
