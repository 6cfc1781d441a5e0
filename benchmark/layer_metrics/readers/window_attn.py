"""Window attention's work counts and what is read against them.

``kind="band_share"``: the share of the causal (query, position) pairs that
the window layers' masks let through, in percent, from the program's
counters: the registry's ``window_attn_pairs`` summed over its layers, over
``train_batches_total x window layers x batch x T (T + 1) / 2``; 6.152 at a
row of 16384 under a window of 512 if the mask is what the configuration
says.

``kind="attn_window" | "attn_full"``: the share of its roofline that the
attention cores of one kind of layer reach: the least time the chip could
take for the scope's work, max(operations / peak FLOP/s, bytes / peak
bytes/s) from ``peaks.json``, over the scope's own device time in the trace.
The work functions read the configuration and the traffic, never the
implementation: the window layers' count is of the BAND, whatever block
pairs the kernels visit, so a later kernel is read against the same work;
the full layers' is ``roofline.py``'s count of the triangle.  Both are
UNDER-counts of what the kernels do (whole block pairs are visited, and
masked inside).

``None`` where the trace has no such scope, the registry no such counter, or
the configuration no window layer (the parent commit's run)."""

import functools
import os

from benchmark import manifest, trace_scopes

WINDOW = "sliding_attention"


def causal_pairs(T: int) -> int:
    return T * (T + 1) // 2


def band_pairs(T: int, window: int) -> int:
    """``sum_t min(t + 1, window)`` over a row of ``T`` queries."""
    full = min(T, window)
    return full * (full + 1) // 2 + (T - full) * full


def _window_heads(cfg: dict) -> list:
    """Query heads of each window layer."""
    return [H for kind, H in zip(cfg["layer_types"],
                                 cfg["num_attention_heads_per_layer"])
            if kind == WINDOW]


def window_attn_work(cfg: dict, traffic: dict):
    """(operations, bytes) a step of the window layers' attention cores: 2
    products forward (scores, values) and 5 backward, as ``roofline.py``
    counts the triangle, each 2 x head_dim an element of the BAND, per query
    head (the forward is not run again when its layer is recomputed: its
    output is kept); q, k, v, the output and their gradients moved once a
    pass in bf16."""
    B, T = traffic["batch"], traffic["seq_len"]
    Hkv, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    pairs = band_pairs(T, cfg["sliding_window"])
    ops = nbytes = 0.0
    for H in _window_heads(cfg):
        ops += B * H * pairs * 7 * 2 * dh
        nbytes += B * T * dh * 2 * ((2 * H + 2 * Hkv) + (4 * H + 4 * Hkv))
    return ops, nbytes


@functools.lru_cache(maxsize=None)
def _roofline():
    return manifest.load_module(os.path.join(
        manifest.BENCH, "layer_metrics", "readers", "roofline.py"),
        "bench_reader_roofline")


def full_attn_work(cfg: dict, traffic: dict):
    """(operations, bytes) a step of the full layers' attention cores:
    ``roofline.py``'s count of the causal triangle, which reads the layers
    of kind ``full_attention`` and ``num_attention_heads`` (the full layers'
    count in this family)."""
    return _roofline().attention_work(cfg, traffic)


WORK = {"attn_window": window_attn_work, "attn_full": full_attn_work}


def band_share_pct(cfg: dict, traffic: dict):
    """From the registry (this process's): pairs seen over causal pairs."""
    try:
        from paddle_tpu.obs import get_registry
    except ImportError:
        return None
    snap = get_registry().snapshot()
    seen = sum(s["value"] for s in snap.get(
        "window_attn_pairs", {}).get("series", []))
    steps = sum(s["value"] for s in snap.get(
        "train_batches_total", {}).get("series", []))
    layers = len(_window_heads(cfg))
    if not seen or not steps or not layers:
        return None
    B, T = traffic["batch"], traffic["seq_len"]
    return 100.0 * seen / (steps * layers * B * causal_pairs(T))


def read(facts, kind, scopes=()):
    cfg, traffic = facts.get("config"), facts.get("traffic")
    if (not cfg or not traffic or "sliding_window" not in cfg
            or "num_attention_heads_per_layer" not in cfg):
        return None
    if kind == "band_share":
        return band_share_pct(cfg, traffic)
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
