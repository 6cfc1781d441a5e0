"""Shares of the roofline that two scopes of the Nemotron-H block reach: the
least time the chip could take for the scope's work, max(operations / peak
FLOP/s, bytes / peak bytes/s) from ``peaks.json``, over the scope's own
device time in the trace.

``kind="ssd_scan"``: the state-space recurrence's chunked scan (scope
``ssd_scan`` inside ``mamba<i>``: the kernels ``ssd_chunk_fwd`` /
``ssd_chunk_bwd`` and what surrounds them).  ``kind="moe_relu2"``: the
grouped products of experts of TWO matrices (scope ``moe_experts`` inside
``moe<i>``: ``moe_gmm`` / ``moe_tgmm`` at 2688 x 1856 and the squared ReLU
between them), from the program's counter of assignments.

Both are UNDER-counts, as their neighbours': each product once forward and
(the scan) twice or (the experts) four times backward, though a recomputed
layer runs the experts' forward products twice and the scan's reverse kernel
makes the forward's parts again; the in-chunk product at a head's own 64
channels though the kernel runs it a lane tile wide; bf16 rows, no scalars,
no layout change.  ``None`` where the trace has no such scope, the run no
counter, or the configuration no such layer."""

from benchmark import trace_scopes

#: tokens per chunk of the program's scan (ops/ssd_scan.py ``CHUNK``)
CHUNK = 128


def ssd_scan_work(cfg: dict, traffic: dict):
    """(operations, bytes) a step of the state-space scans.  A chunk of ``Q``
    tokens of one GROUP of ``W`` channels (its heads' ``P`` each): ``C B^T``
    (``Q x Q x N``), the in-chunk product (``Q x Q x W``: a head's ``L o C
    B^T`` against its own channels) and two products with the state (``Q x N
    x W`` each: the output's read, the state's update); 2 operations a
    multiply-add, once forward and twice backward.  Bytes: x, B, C in and y
    out forward; x, B, C, dy in and dx, dB, dC out backward, in bf16; the
    chunks' starting states ``[N, W]`` float32 out forward and in
    backward."""
    T, B = traffic["seq_len"], traffic["batch"]
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    layers = cfg["hybrid_override_pattern"].count("M")
    W, Q = H // G * P, CHUNK
    chunks = -(-T // Q)
    forward = 2 * (Q * Q * N + Q * Q * W + 2 * Q * N * W)
    ops = layers * B * G * chunks * 3 * forward
    rows = layers * B * T
    nbytes = rows * 2 * ((2 * H * P + 2 * G * N) + (3 * H * P + 4 * G * N))
    nbytes += layers * B * G * chunks * N * W * 4 * 2
    return ops, nbytes


def moe_relu2_work(cfg: dict, expert_load: dict, steps: int):
    """(operations, bytes) a step of the two-matrix experts' grouped
    products: per assignment computed 2 products forward and 4 backward (da,
    dW2, dW1, dx), each 2 x D x F; the two matrices of every expert held read
    once forward and once backward and their gradients written once,
    float32; bf16 rows in and out of each product."""
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    ops = nbytes = 0.0
    for counts in expert_load.values():
        rows = sum(counts) / steps
        ops += rows * 6 * 2 * D * F
        nbytes += 3 * 2 * len(counts) * D * F * 4
        nbytes += rows * 2 * 6 * (D + F)
    return ops, nbytes


def read(facts, kind, scopes):
    parsed = trace_scopes.trace_of(facts)
    cfg, steps = facts.get("config"), facts.get("steps")
    if (parsed is None or not cfg or not steps
            or "hybrid_override_pattern" not in cfg):
        return None
    ns = trace_scopes.scope_ns(parsed, scopes)
    if not ns:
        return None
    if kind == "ssd_scan":
        ops, nbytes = ssd_scan_work(cfg, facts["traffic"])
    elif kind == "moe_relu2":
        if not facts.get("expert_load"):
            return None
        ops, nbytes = moe_relu2_work(cfg, facts["expert_load"], steps)
    else:
        raise ValueError(f"no work function for {kind!r}")
    peaks = facts["peaks"]
    least_s = max(ops / peaks["bf16_flops_per_s"],
                  nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ns / steps / 1e9)
