"""Plain reference of SmallThinker-21BA3B-Instruct (``model_name``
``smallthinker_21b_instruct``) as one chip of an eight-chip expert-parallel
deployment holds it, in float32 ``jax.numpy``.  It imports nothing of the
program; parameter names are the program's so that one set of seeded weights
serves both.

The equations (from the published ``config.json`` and, where its keys cannot
say it, the catalog row's ``described_as``: "sparse ReGLU; router placed
before attention"; every reading is listed under ``assumed`` in the
configuration file).  No bias anywhere.  Layer ``i``, input ``x``:

- ``u = RMSNorm_op(x)``.  THE ROUTER READS ``u``, the attention's input:
  ``r = u W_r`` ``[T, router_outputs]``; ``S_t`` = the
  ``moe_num_active_primary_experts`` largest of ``r_t`` (``lax.top_k``: the
  lower index first among equals); ``w_t = softmax over S_t of r_t`` (the
  softmax is taken AFTER the choice, ``moe_primary_router_apply_softmax``).
- ``h = x + concat_h(Attn_h) W_o``; ``q = u W_q`` ``[T, H, dh]``, ``k = u
  W_k``, ``v = u W_v`` ``[T, Hkv, dh]``, key-value head ``j`` serves query
  heads ``j G .. j G + G - 1``, ``G = H / Hkv`` (seven).  No head norms.
  ``rope_layout[i] == 1``: q and k are turned by the plain rotary embedding
  over all ``dh`` channels at ``rope_theta``, half-rotation form, positions
  ``0..T-1``; ``== 0``: they are not turned at all (NoPE).  Scores ``q_h[t] .
  k_(h // G)[s] dh ** -0.5``, softmax over the positions seen:
  ``sliding_window_layout[i] == 0``: ``s <= t``; ``== 1``: ``t -
  sliding_window_size < s <= t`` (the query's own position counts).
- ``v = RMSNorm_ffn(h)``; ``y = h + sum over e in S_t of w_t[e] W2_e
  (relu(W1_e v) * W3_e v)``: the experts read ``v``, the weights and the
  choice come from ``u``; the gate's activation is ReLU.
- A final RMSNorm, then a head ``W_head`` ``[hidden, vocab]`` that is NOT the
  embedding (untied).

The chip's share: it holds experts ``first_expert .. first_expert +
moe_num_primary_experts - 1`` of ``router_outputs``, and rows ``0 ..
vocab_size - 1`` of the published vocabulary.  The router keeps all its
outputs and its experts a token; what the absent experts would have added is
left out, here as in the program, and that partial result goes on to the
next layer.

How it fits at a row of 16384 beside the runner's ``p``, ``m``, ``v`` and one
gradient (5.9 GB): ``jax.checkpoint`` by layer; attention one key-value
head's group of seven query heads at a time FROM THEIR COLUMNS OF THE
PROJECTIONS TO THEIR ROWS OF ``W_o`` (a ``lax.scan`` that sums the groups'
parts, each recomputed) and inside it a block of ``QUERY_BLOCK`` queries at
a time (a ``lax.map``, each block recomputed): a full layer's block against
EVERY position under the causal mask (one shape for every block), a window
layer's against the ``QUERY_BLOCK + window`` positions that end with the
block (cut from keys padded in front; what lies before the row's start is
masked); the experts as a plain loop over the experts held, EVERY token
through each under a mask (a ``lax.scan``, one expert recomputed at a time:
no dispatch, no grouping); the head and loss in blocks of positions.

Weights: ``correct.init_params`` draws EVERY leaf zero-mean normal with the
``std`` given here, norm weights included (std 1; see the LFM2 reference for
why).  Projections (the router among them) have std ``fan_in ** -0.5``; the
EMBEDDING has std 1, as Keye-VL-2.0's and Laguna-XS.2's files have and for
their reason: at 0.02 every token of a row reaches the routers as nearly the
same vector and one expert takes the row.  What the 8 experts held get a
layer at std 1 is in the cell's ``.limits.why.txt`` (the builder's reading on
the chip; 1,536 a step and expert if routing is even).
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
    return {"D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "dh": cfg["head_dim"],
            "F": cfg["moe_ffn_hidden_size"], "E": cfg["router_outputs"],
            "Eh": cfg["moe_num_primary_experts"],
            "k": cfg["moe_num_active_primary_experts"],
            "V": cfg["vocab_size"], "layers": cfg["num_hidden_layers"]}


def is_window(cfg: dict, i: int) -> bool:
    return bool(cfg["sliding_window_layout"][i])


def has_positions(cfg: dict, i: int) -> bool:
    return bool(cfg["rope_layout"][i])


def param_shapes(cfg: dict) -> dict:
    d = _dims(cfg)
    D, H, Hkv, dh, F = d["D"], d["H"], d["Hkv"], d["dh"], d["F"]
    shapes = {"_emb.w0": ((d["V"], D), 1.0), "_norm_out.w": ((D,), 1.0),
              "_cost.w": ((D, d["V"]), D ** -0.5)}
    for i in range(d["layers"]):
        shapes[f"_norm_op{i}.w"] = ((D,), 1.0)
        shapes[f"_norm_ffn{i}.w"] = ((D,), 1.0)
        shapes[f"_attn{i}.wq"] = ((D, H * dh), D ** -0.5)
        shapes[f"_attn{i}.wk"] = ((D, Hkv * dh), D ** -0.5)
        shapes[f"_attn{i}.wv"] = ((D, Hkv * dh), D ** -0.5)
        shapes[f"_attn{i}.wo"] = ((H * dh, D), (H * dh) ** -0.5)
        shapes[f"_moe{i}.router"] = ((D, d["E"]), D ** -0.5)
        shapes[f"_moe{i}.w1"] = ((d["Eh"], D, F), D ** -0.5)
        shapes[f"_moe{i}.w3"] = ((d["Eh"], D, F), D ** -0.5)
        shapes[f"_moe{i}.w2"] = ((d["Eh"], F, D), F ** -0.5)
    return shapes


def mm(a, b):
    """Every matrix multiplication of this file.  The lower-precision control
    (benchmark/correct.py) swaps it for one that rounds its operands."""
    return jnp.matmul(a, b)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def rotary(x, theta: float):
    """x ``[B, T, heads, dh]``: the plain rotary embedding over all ``dh``
    channels, half-rotation form, positions 0..T-1."""
    T, dh = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


# -- attention ----------------------------------------------------------------

def attention(cfg, p, pre, i, u):
    """``concat_h(Attn_h) W_o`` of layer ``i`` over its normed input ``u``:
    full or under the window, with positions or without, by the two layouts.
    One key-value head and its group of query heads at a time, from their
    columns of the projections to their rows of ``W_o``: the sum over the
    groups is the layer's result."""
    d = _dims(cfg)
    B, T, D = u.shape
    H, Hkv, dh = d["H"], d["Hkv"], d["dh"]
    G = H // Hkv             # key-value head j serves query heads jG..jG+G-1
    window = cfg["sliding_window_size"] if is_window(cfg, i) else None
    place = ((lambda x: rotary(x, cfg["rope_theta"]))
             if has_positions(cfg, i) else (lambda x: x))
    block = min(QUERY_BLOCK, T)
    if T % block:
        raise ValueError(f"a row of {T} is not whole blocks of {block}")
    # a window layer's block of queries reads the ``span`` positions that
    # end with it; keys and values are padded in front so that every block
    # cuts the same shape, and what lies before the row's start is masked
    span = T if window is None else min(T, block + window)
    front = 0 if window is None else span - block

    def columns(w):                  # [D, Hkv G n] -> [Hkv, D, G n]
        return w.reshape(D, Hkv, -1).transpose(1, 0, 2)

    @jax.checkpoint
    def group(u, wq, wk, wv, wo):
        """One key-value head and its query heads: ``[B, T, D]``, the
        group's part of ``concat(o) W_o``."""
        q = place(mm(u, wq).reshape(B, T, G, dh)).transpose(0, 2, 1, 3)
        k = place(mm(u, wk)[:, :, None]).transpose(0, 2, 1, 3)
        v = mm(u, wv)[:, None]                                 # [B, 1, T, dh]
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

        o = jax.lax.map(rows, jnp.arange(0, T, block))  # [blocks,B,G,block,dh]
        o = o.transpose(1, 0, 3, 2, 4).reshape(B, T, G * dh)
        return mm(o, wo)

    def add(y, w):
        return y + group(u, *w), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(u), (
        columns(p[pre + ".wq"]), columns(p[pre + ".wk"]),
        columns(p[pre + ".wv"]), p[pre + ".wo"].reshape(Hkv, G * dh, D)))
    return y


# -- the experts --------------------------------------------------------------

def route(cfg, p, pre, u):
    """``(experts [.., k], weights [.., k])`` of every token, from ``u``: the
    largest logits, then the softmax over those."""
    r = mm(u, p[pre + ".router"])
    chosen, idx = jax.lax.top_k(r, cfg["moe_num_active_primary_experts"])
    return idx, jax.nn.softmax(chosen, axis=-1)


def reglu(x, w1, w3, w2):
    return mm(jax.nn.relu(mm(x, w1)) * mm(x, w3), w2)


def routed_experts(cfg, p, pre, u, v, first_expert=None, held=None):
    """The part of the routed result that the experts held give: routed by
    ``u``, computed over ``v``; the weights ``p[pre + ".w1"]`` etc. are those
    of experts ``first_expert ..``.  Every token goes through every expert
    held, and the mask keeps what was routed there."""
    first = cfg["first_expert"] if first_expert is None else first_expert
    held = cfg["moe_num_primary_experts"] if held is None else held
    idx, w = route(cfg, p, pre, u)

    @jax.checkpoint
    def one(v, idx, w, e, w1, w3, w2):
        gate = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        return gate[..., None] * reglu(v, w1, w3, w2)

    y, _ = jax.lax.scan(lambda y, ex: (y + one(v, idx, w, *ex), None),
                        jnp.zeros_like(v), (
        jnp.arange(held), p[pre + ".w1"][:held], p[pre + ".w3"][:held],
        p[pre + ".w2"][:held]))
    return y


def layer(cfg, p, i, x, first_expert=None, held=None):
    eps = cfg["rms_norm_eps"]
    u = rms_norm(x, p[f"_norm_op{i}.w"], eps)
    h = x + attention(cfg, p, f"_attn{i}", i, u)
    v = rms_norm(h, p[f"_norm_ffn{i}.w"], eps)
    return h + routed_experts(cfg, p, f"_moe{i}", u, v, first_expert, held)


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
    experts at the expected ``moe_num_active_primary_experts *
    moe_num_primary_experts / router_outputs`` assignments a token (6 x 8 /
    64 of an expert)."""
    d = _dims(cfg)
    D, H, Hkv, dh, T = d["D"], d["H"], d["Hkv"], d["dh"], seq_len
    layers = range(d["layers"])
    pairs = [seen_pairs(T, cfg["sliding_window_size"] if is_window(cfg, i)
                        else None) for i in layers]
    return {
        "attn_proj": d["layers"] * T * 2 * (2 * D * H * dh
                                            + 2 * D * Hkv * dh),
        "attn_full": sum(n * 2 * H * 2 * dh for i, n in enumerate(pairs)
                         if not is_window(cfg, i)),
        "attn_window": sum(n * 2 * H * 2 * dh for i, n in enumerate(pairs)
                           if is_window(cfg, i)),
        "router": d["layers"] * T * 2 * D * d["E"],
        "experts": d["layers"] * T * (d["k"] * d["Eh"] / d["E"]) * 6 * D
        * d["F"],
        "head": T * 2 * D * d["V"],
    }


def step_flops(cfg: dict, traffic: dict) -> float:
    """Operations one training step needs: 3 x the forward pass (what the
    program recomputes, and the pairs a kernel visits and masks, are not
    counted)."""
    parts = forward_flops_per_row(cfg, traffic["seq_len"])
    return traffic["batch"] * 3.0 * sum(parts.values())
