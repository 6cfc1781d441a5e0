"""Plain reference of Laguna-XS.2 (``model_type`` ``laguna``) as one chip of a
thirty-two-chip expert-parallel deployment holds it, in float32
``jax.numpy``.  It imports nothing of the program; parameter names are the
program's so that one set of seeded weights serves both.

The equations (from the published ``config.json``; what the config does not
fix is listed under ``assumed`` in the configuration file).  No bias
anywhere.  Layer ``i``: ``h = x + Attn_i(RMSNorm(x))``, ``y = h +
FFN_i(RMSNorm(h))``; a final RMSNorm, then a head ``W_head`` ``[hidden,
vocab]`` that is NOT the embedding (untied).

- Heads: ``q = u W_q`` ``[T, H_i, dh]``, ``k = u W_k``, ``v = u W_v`` ``[T,
  Hkv, dh]``; ``H_i`` is ``num_attention_heads_per_layer[i]`` (48 in a full
  layer, 64 in a window layer), key-value head ``j`` serves query heads ``j
  G_i .. j G_i + G_i - 1``, ``G_i = H_i / Hkv``.  No head norms.
- Positions, by the layer's kind (``rope_parameters[layer_types[i]]``), in
  the half-rotation form at positions ``0..T-1``.  ``sliding_attention``:
  all ``dh`` channels at ``theta ** (-2j / dh)``, no scaling.
  ``full_attention``: the FIRST ``dh partial_rotary_factor`` channels, the
  rest untouched, at YaRN's frequencies (:func:`yarn`): pair ``j`` of
  ``width / 2`` turns at ``f_j = theta ** (-2j / width)`` up to pair
  ``low``, at ``f_j / factor`` from pair ``high`` on, a linear ramp between;
  cos and sin are multiplied by ``attention_factor``.
- Scores ``q_h[t] . k_(h // G)[s] dh ** -0.5``, softmax over the positions
  seen: a full layer ``s <= t``; a window layer ``t - sliding_window < s <=
  t`` (the query's own position counts).
- Gate: ``g = sigmoid(u W_g)`` ``[T, H_i]``; head ``h``'s result is
  multiplied by ``g[t, h]``; ``Attn = concat(g o) W_o``.
- ``FFN_i``: ``mlp_layer_types[i]`` ``dense``: ``W_2(silu(W_1 x) * W_3 x)``
  at ``intermediate_size``.  ``sparse``: router logits over
  ``router_outputs`` experts, sigmoid, the ``num_experts_per_tok`` largest
  (no selection bias), divided by their sum plus 1e-6, times
  ``moe_routed_scaling_factor``; the sum over the chosen experts of ``w_e
  E_e(x)``, ``E_e`` a gated MLP of ``moe_intermediate_size``; plus ONE
  shared expert of ``shared_expert_intermediate_size``, unweighted.

The chip's share: it holds experts ``first_expert .. first_expert +
num_experts - 1`` of ``router_outputs``, and rows ``0 .. vocab_size - 1`` of
the published vocabulary.  The router keeps all its outputs and its experts
a token; what the absent experts would have added is left out, here as in
the program, and that partial result goes on to the next layer.

How it fits at a row of 16384 beside the runner's ``p``, ``m``, ``v`` and one
gradient (6.2 GB): ``jax.checkpoint`` by layer; attention one key-value
head's group of query heads at a time FROM THEIR COLUMNS OF THE PROJECTIONS
TO THEIR ROWS OF ``W_o`` (a ``lax.scan`` that sums the groups' parts, each
recomputed: 64 heads' queries in float32 are 537 MB, and a layer's backward
pass held five such arrays) and inside it a block of ``QUERY_BLOCK`` queries
at a time (a ``lax.map``, each block recomputed): a full layer's block
against EVERY position under the causal mask (one shape for every block), a
window layer's against the ``QUERY_BLOCK + window`` positions that end with
the block (cut from keys padded in front; what lies before the row's start
is masked); the dense layer's MLP in blocks of positions; the experts as a
plain loop over the experts held with a mask (a ``lax.scan``), one expert
recomputed at a time; the head and loss in blocks of positions.

Weights: ``correct.init_params`` draws EVERY leaf zero-mean normal with the
``std`` given here, norm weights included (std 1; see the LFM2 reference for
why).  Projections have std ``fan_in ** -0.5``; the EMBEDDING has std 1, as
Keye-VL-2.0's file has and for its reason: at 0.02 every token of a row
reaches the routers as nearly the same vector and one expert takes the row.
What the experts held get at std 1 is in the cell's ``.limits.why.txt``
(the builder's reading on the chip).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: queries per block of the reference's attention
QUERY_BLOCK = 512
#: positions per block of the reference's head and loss
HEAD_BLOCK = 2048


def _dims(cfg: dict) -> dict:
    return {"D": cfg["hidden_size"], "Hkv": cfg["num_key_value_heads"],
            "dh": cfg["head_dim"], "heads":
            cfg["num_attention_heads_per_layer"],
            "F": cfg["intermediate_size"],
            "Fe": cfg["moe_intermediate_size"],
            "Fs": cfg["shared_expert_intermediate_size"],
            "E": cfg["router_outputs"], "Eh": cfg["num_experts"],
            "V": cfg["vocab_size"], "layers": cfg["num_hidden_layers"]}


def is_dense(cfg: dict, i: int) -> bool:
    return cfg["mlp_layer_types"][i] == "dense"


def is_window(cfg: dict, i: int) -> bool:
    return cfg["layer_types"][i] == "sliding_attention"


def param_shapes(cfg: dict) -> dict:
    d = _dims(cfg)
    D, Hkv, dh = d["D"], d["Hkv"], d["dh"]
    shapes = {"_emb.w0": ((d["V"], D), 1.0), "_norm_out.w": ((D,), 1.0),
              "_cost.w": ((D, d["V"]), D ** -0.5)}
    for i in range(d["layers"]):
        H = d["heads"][i]
        shapes[f"_norm_op{i}.w"] = ((D,), 1.0)
        shapes[f"_norm_ffn{i}.w"] = ((D,), 1.0)
        shapes[f"_attn{i}.wq"] = ((D, H * dh), D ** -0.5)
        shapes[f"_attn{i}.wk"] = ((D, Hkv * dh), D ** -0.5)
        shapes[f"_attn{i}.wv"] = ((D, Hkv * dh), D ** -0.5)
        shapes[f"_attn{i}.wo"] = ((H * dh, D), (H * dh) ** -0.5)
        shapes[f"_attn{i}.wg"] = ((D, H), D ** -0.5)
        if is_dense(cfg, i):
            shapes[f"_mlp{i}.w1"] = ((D, d["F"]), D ** -0.5)
            shapes[f"_mlp{i}.w3"] = ((D, d["F"]), D ** -0.5)
            shapes[f"_mlp{i}.w2"] = ((d["F"], D), d["F"] ** -0.5)
        else:
            shapes[f"_moe{i}.router"] = ((D, d["E"]), D ** -0.5)
            shapes[f"_moe{i}.w1"] = ((d["Eh"], D, d["Fe"]), D ** -0.5)
            shapes[f"_moe{i}.w3"] = ((d["Eh"], D, d["Fe"]), D ** -0.5)
            shapes[f"_moe{i}.w2"] = ((d["Eh"], d["Fe"], D), d["Fe"] ** -0.5)
            shapes[f"_moe{i}.shared_w1"] = ((D, d["Fs"]), D ** -0.5)
            shapes[f"_moe{i}.shared_w3"] = ((D, d["Fs"]), D ** -0.5)
            shapes[f"_moe{i}.shared_w2"] = ((d["Fs"], D), d["Fs"] ** -0.5)
    return shapes


def mm(a, b):
    """Every matrix multiplication of this file.  The lower-precision control
    (benchmark/correct.py) swaps it for one that rounds its operands."""
    return jnp.matmul(a, b)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


# -- positions ----------------------------------------------------------------

def yarn(width: int, rope: dict):
    """``(inv_freq [width / 2], factor on cos and sin)`` of a ``rope_type``
    ``yarn`` block over ``width`` channels, as HF's
    ``_compute_yarn_parameters`` reads it: ``c(n) = width ln(original / (2
    pi n)) / (2 ln theta)`` is the pair that completes ``n`` turns over the
    original context; ``low = floor(c(beta_fast))``, ``high =
    ceil(c(beta_slow))``, both kept inside ``0 .. width - 1``; pair ``j``'s
    share of the interpolated frequency is ``clip((j - low) / (high - low),
    0, 1)``."""
    theta, factor = rope["rope_theta"], rope["factor"]
    original = rope["original_max_position_embeddings"]
    c = lambda n: (width * np.log(original / (n * 2 * np.pi))    # noqa: E731
                   / (2 * np.log(theta)))
    low = max(np.floor(c(rope["beta_fast"])), 0)
    high = min(np.ceil(c(rope["beta_slow"])), width - 1)
    if low == high:
        high += 0.001
    j = np.arange(width // 2, dtype=np.float64)
    f = theta ** (-2.0 * j / width)
    share = np.clip((j - low) / (high - low), 0.0, 1.0)
    inv = f * (1.0 - share) + f / factor * share
    scale = rope.get("attention_factor")
    if scale is None:
        scale = 0.1 * np.log(factor) + 1.0
    return inv.astype(np.float32), float(scale)


def rotary(x, rope: dict):
    """x ``[B, T, heads, dh]`` under one ``rope_parameters`` block: the
    half-rotation form over the first ``dh partial_rotary_factor`` channels,
    positions 0..T-1."""
    T, dh = x.shape[1], x.shape[-1]
    width = int(dh * rope.get("partial_rotary_factor", 1.0))
    if rope["rope_type"] == "yarn":
        inv, scale = yarn(width, rope)
        inv = jnp.asarray(inv)
    elif rope["rope_type"] == "default":
        inv = rope["rope_theta"] ** (
            -jnp.arange(0, width, 2, dtype=jnp.float32) / width)
        scale = 1.0
    else:
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1) * scale
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1) * scale
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    turn, rest = x[..., :width], x[..., width:]
    x1, x2 = turn[..., :width // 2], turn[..., width // 2:]
    turned = turn * cos + jnp.concatenate([-x2, x1], -1) * sin
    return jnp.concatenate([turned, rest], -1)


# -- attention ----------------------------------------------------------------

def attention(cfg, p, pre, i, x):
    """``Attn_i(x)``: full or under the window, by the layer's kind.  One
    key-value head and its group of query heads at a time, from their
    columns of the projections to their rows of ``W_o``: the sum over the
    groups is the layer's result, and nothing of ``H_i`` heads' width is ever
    held."""
    d = _dims(cfg)
    B, T, D = x.shape
    H, Hkv, dh = d["heads"][i], d["Hkv"], d["dh"]
    G = H // Hkv             # key-value head j serves query heads jG..jG+G-1
    rope = cfg["rope_parameters"][cfg["layer_types"][i]]
    window = cfg["sliding_window"] if is_window(cfg, i) else None
    block = min(QUERY_BLOCK, T)
    if T % block:
        raise ValueError(f"a row of {T} is not whole blocks of {block}")
    # a window layer's block of queries reads the ``span`` positions that
    # end with it; keys and values are padded in front so that every block
    # cuts the same shape, and what lies before the row's start is masked
    span = T if window is None else min(T, block + window)
    front = 0 if window is None else span - block

    def columns(w, per_head):        # [D, Hkv G n] -> [Hkv, D, G n]
        return w.reshape(D, Hkv, -1).transpose(1, 0, 2) if per_head else w

    @jax.checkpoint
    def group(x, wq, wk, wv, wg, wo):
        """One key-value head and its query heads: ``[B, T, D]``, the
        group's part of ``concat(g o) W_o``."""
        q = rotary(mm(x, wq).reshape(B, T, G, dh), rope).transpose(0, 2, 1, 3)
        k = rotary(mm(x, wk)[:, :, None], rope).transpose(0, 2, 1, 3)
        v = mm(x, wv)[:, None]                                 # [B, 1, T, dh]
        if front:
            pad = ((0, 0), (0, 0), (front, 0), (0, 0))
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)

        @jax.checkpoint
        def rows(lo):        # one block of queries, recomputed backward
            qb = jax.lax.dynamic_slice_in_dim(q, lo, block, 2)
            if window is None:
                kb, vb, first = k, v, 0
            else:
                kb = jax.lax.dynamic_slice_in_dim(k, lo, span, 2)
                vb = jax.lax.dynamic_slice_in_dim(v, lo, span, 2)
                first = lo - front
            at = lo + jnp.arange(block)[:, None]
            pos = first + jnp.arange(span)[None, :]
            seen = (pos <= at) & (pos >= 0)
            if window is not None:
                seen = seen & (pos > at - window)
            s = mm(qb, kb.swapaxes(-1, -2)) * dh ** -0.5
            a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
            return mm(a, vb)                                   # [B,G,block,dh]

        o = jax.lax.map(rows, jnp.arange(0, T, block))   # [blocks,B,G,block,dh]
        o = o.transpose(1, 0, 3, 2, 4).reshape(B, T, G, dh)
        gate = jax.nn.sigmoid(mm(x, wg))                       # [B, T, G]
        return mm((o * gate[..., None]).reshape(B, T, G * dh), wo)

    def add(y, w):
        return y + group(x, *w), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(x), (
        columns(p[pre + ".wq"], True), columns(p[pre + ".wk"], True),
        columns(p[pre + ".wv"], True), columns(p[pre + ".wg"], True),
        p[pre + ".wo"].reshape(Hkv, G * dh, D)))
    return y


# -- the feed-forward ---------------------------------------------------------

def gated_mlp(x, w1, w3, w2):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def route(cfg, p, pre, x):
    """``(experts [.., k], weights [.., k])`` of every token."""
    s = jax.nn.sigmoid(mm(x, p[pre + ".router"]))
    chosen, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    chosen = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-6)
    return idx, chosen * cfg["moe_routed_scaling_factor"]


def routed_experts(cfg, p, pre, x, first_expert=None, held=None):
    """The part of the routed result that the experts held give; the weights
    ``p[pre + ".w1"]`` etc. are those of experts ``first_expert ..``."""
    first = cfg["first_expert"] if first_expert is None else first_expert
    held = cfg["num_experts"] if held is None else held
    idx, w = route(cfg, p, pre, x)

    @jax.checkpoint
    def one(x, idx, w, e, w1, w3, w2):
        gate = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        return gate[..., None] * gated_mlp(x, w1, w3, w2)

    y, _ = jax.lax.scan(lambda y, ex: (y + one(x, idx, w, *ex), None),
                        jnp.zeros_like(x), (
        jnp.arange(held), p[pre + ".w1"][:held], p[pre + ".w3"][:held],
        p[pre + ".w2"][:held]))
    return y


def row_blocks(f, x):
    """``f`` over blocks of ``HEAD_BLOCK`` positions of ``x`` ``[B, T, D]``,
    each block recomputed in the backward pass: a feed-forward 8192 wide
    holds a block's hidden units, not the row's."""
    B, T, D = x.shape
    block = min(HEAD_BLOCK, T)
    if T % block:
        return f(x)
    xs = x.reshape(B, T // block, block, D).swapaxes(0, 1)
    return jax.lax.map(jax.checkpoint(f), xs).swapaxes(0, 1).reshape(B, T, D)


def shared_expert(p, pre, x):
    return gated_mlp(x, p[pre + ".shared_w1"], p[pre + ".shared_w3"],
                     p[pre + ".shared_w2"])


def layer(cfg, p, i, x):
    eps = cfg["rms_norm_eps"]
    h = x + attention(cfg, p, f"_attn{i}", i,
                      rms_norm(x, p[f"_norm_op{i}.w"], eps))
    hn = rms_norm(h, p[f"_norm_ffn{i}.w"], eps)
    if is_dense(cfg, i):
        return h + row_blocks(lambda rows: gated_mlp(
            rows, p[f"_mlp{i}.w1"], p[f"_mlp{i}.w3"], p[f"_mlp{i}.w2"]), hn)
    return h + routed_experts(cfg, p, f"_moe{i}", hn) + shared_expert(
        p, f"_moe{i}", hn)


def hidden(cfg: dict, p: dict, ids):
    x = p["_emb.w0"][ids]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(lambda p, x, i=i: layer(cfg, p, i, x))(p, x)
    return rms_norm(x, p["_norm_out.w"], cfg["rms_norm_eps"])


def loss_sum(cfg: dict, p: dict, batch: dict):
    """(sum of the target tokens' cross-entropies over the vocabulary slice,
    the number of target tokens)."""
    ids, lengths = batch["tokens"]
    targets, _ = batch["next_tokens"]
    h = hidden(cfg, p, ids)
    mask = (jnp.arange(ids.shape[1])[None, :] < lengths[:, None])
    mask = mask.astype(jnp.float32)

    @jax.checkpoint
    def block(hb, w, tb, mb):        # a block of positions' logits at a time
        logp = jax.nn.log_softmax(mm(hb, w), axis=-1)
        picked = jnp.take_along_axis(logp, tb[..., None], -1)[..., 0]
        return -(picked * mb).sum()

    total = sum(block(h[:, lo:lo + HEAD_BLOCK], p["_cost.w"],
                      targets[:, lo:lo + HEAD_BLOCK],
                      mask[:, lo:lo + HEAD_BLOCK])
                for lo in range(0, ids.shape[1], HEAD_BLOCK))
    return total, mask.sum()


# -- what only this configuration knows about its traffic -------------------

def batch(cfg: dict, traffic: dict, gen) -> dict:
    """One feed: rows of ``seq_len + 1`` ids drawn uniformly over the
    vocabulary slice (one document a row, the row full); ``tokens`` is all
    but the last id, ``next_tokens`` all but the first.  ``gen`` is
    benchmark/traffic.py's generator."""
    B, T = traffic["batch"], traffic["seq_len"]
    ids = gen.rng.integers(0, cfg["vocab_size"], (B, T + 1), dtype=np.int32)
    lengths = gen.lengths(traffic["lengths"], B, T)
    return {"tokens": (ids[:, :-1], lengths),
            "next_tokens": (ids[:, 1:], lengths)}


def real_tokens(feed: dict) -> int:
    """What a step counts as its tokens: the target tokens."""
    return int(feed["next_tokens"][1].sum())


def seen_pairs(seq_len: int, window=None) -> int:
    """(query, position) pairs a row's mask lets through: ``sum_t min(t + 1,
    window)`` under a window, the causal triangle without."""
    full = seq_len if window is None else min(seq_len, window)
    return full * (full + 1) // 2 + (seq_len - full) * full


def forward_flops_per_row(cfg: dict, seq_len: int) -> dict:
    """Operations of one row's forward pass by part, from the shapes alone,
    2 a multiply-add: the mathematics, not what a kernel visits.  Attention
    over the pairs each layer SEES (the band in a window layer, the
    triangle in a full one): scores over ``dh``, values over ``dh``; the
    routed experts at the expected ``num_experts_per_tok * num_experts /
    router_outputs`` assignments a token."""
    d = _dims(cfg)
    D, Hkv, dh, T = d["D"], d["Hkv"], d["dh"], seq_len
    layers = range(d["layers"])
    sparse = sum(not is_dense(cfg, i) for i in layers)
    held = cfg["num_experts_per_tok"] * d["Eh"] / d["E"]
    pairs = [seen_pairs(T, cfg["sliding_window"] if is_window(cfg, i)
                        else None) for i in layers]
    return {
        "attn_proj": sum(T * 2 * (2 * D * H * dh + 2 * D * Hkv * dh + D * H)
                         for H in d["heads"]),
        "attn_full": sum(n * 2 * H * 2 * dh for i, (n, H) in enumerate(
            zip(pairs, d["heads"])) if not is_window(cfg, i)),
        "attn_window": sum(n * 2 * H * 2 * dh for i, (n, H) in enumerate(
            zip(pairs, d["heads"])) if is_window(cfg, i)),
        "dense_mlp": (d["layers"] - sparse) * T * 6 * D * d["F"],
        "router": sparse * T * 2 * D * d["E"],
        "experts": sparse * T * held * 6 * D * d["Fe"],
        "shared": sparse * T * 6 * D * d["Fs"],
        "head": T * 2 * D * d["V"],
    }


def step_flops(cfg: dict, traffic: dict) -> float:
    """Operations one training step needs: 3 x the forward pass (what the
    program recomputes, and the pairs a kernel visits and masks, are not
    counted)."""
    parts = forward_flops_per_row(cfg, traffic["seq_len"])
    return traffic["batch"] * 3.0 * sum(parts.values())
