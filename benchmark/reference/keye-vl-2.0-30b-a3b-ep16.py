"""Plain reference of the language model of Keye-VL-2.0-30B-A3B
(``model_type`` ``KeyeVL2``) as one chip of a sixteen-chip expert-parallel
deployment holds it, in float32 ``jax.numpy``.  It imports nothing of the
program; parameter names are the program's so that one set of seeded weights
serves both.

The equations (from the published ``config.json``; what the config does not
fix is listed under ``assumed`` in the configuration file).  No bias but the
indexer's key norm.  Layer ``i``: ``h = x + Attn_i(RMSNorm(x))``, ``y = h +
MoE_i(RMSNorm(h))``; a final RMSNorm, then a head ``W_head`` ``[hidden,
vocab]`` that is NOT the embedding (untied).  Text tokens only: the three
position streams of ``mrope_section`` are equal, the rotary embedding is the
plain one (half-rotation form, positions 0..T-1, no scaling).

- Main heads: ``q = HeadNorm(x W_q)`` ``[T, H, dh]``, ``k = HeadNorm(x
  W_k)`` ``[T, Hkv, dh]``, ``v = x W_v`` ``[T, Hkv, dh]``; RMSNorm over each
  head's ``dh`` channels, one weight vector for queries and one for keys;
  rotary embedding over all ``dh`` channels of q and k.
- Indexer (the lightning indexer of DeepSeek Sparse Attention, fed from the
  layer's normed input): with ``u = stop_gradient(RMSNorm(x))``: ``qI = u
  W_Iq`` ``[T, J, d]``, ``kI = LayerNorm(u W_Ik)`` ``[T, d]`` (ONE key head;
  weight and bias), rotary embedding over the ``d`` channels of both, ``w =
  u W_Iw (J d) ** -0.5`` ``[T, J]``; ``I[t, s] = sum_j w[t, j] relu(qI[t, j]
  . kI[s])`` for ``s <= t``.
- Selection: ``S_t`` = the positions of the ``min(t + 1, topk)`` largest
  ``I[t, s]``, ``s <= t``, among equal scores the lower position first
  (``lax.top_k``'s rule).
- Attention over the kept positions, the same ``S_t`` for every head:
  ``a_h[t, s] = softmax_{s in S_t}(q_h[t] . k_{h // G}[s] dh ** -0.5)``,
  ``o_h[t] = sum_{s in S_t} a_h[t, s] v_{h // G}[s]``, ``Attn = concat(o)
  W_o``.
- The indexer's loss: ``p[t, s] = stop_gradient((1 / H) sum_h a_h[t, s])``
  on ``S_t``; ``L_I = sum_t KL(p[t] || softmax_{s in S_t} I[t, s])``.  The
  step's loss is ``(sum_t CE_t + sum over the layers of L_I) / tokens``.
- ``MoE``: router logits over ``router_outputs`` experts, softmax, the
  ``num_experts_per_tok`` largest, divided by their sum (no epsilon, no
  scaling factor, no bias, no shared expert); the sum over the chosen
  experts of ``w_e E_e(x)``, ``E_e`` a gated MLP ``W_2(silu(W_1 x) * W_3
  x)`` of ``moe_intermediate_size``.

The chip's share: it holds experts ``first_expert .. first_expert +
num_experts - 1`` of ``router_outputs``, and rows ``0 .. vocab_size - 1`` of
the published vocabulary.  The router keeps all its outputs and its experts
a token; what the absent experts would have added is left out, here as in
the program, and that partial result goes on to the next layer.

How it fits at a row of 16384 beside the runner's ``p``, ``m``, ``v`` and two
gradients (6.3 GB): ``jax.checkpoint`` by layer; the attention a block of
``QUERY_BLOCK`` queries at a time against EVERY position (one shape for
every block: a ``lax.map`` over the blocks, each recomputed in the backward
pass): its scores ``[block, T]``, its selection by ``lax.top_k`` and a
scatter of the chosen indices, and the main heads one key-value head's
group at a time (a ``lax.map`` whose body is recomputed too); the experts
as a plain loop over the experts held with a mask (a ``lax.scan``), one
expert recomputed at a time; the head and loss in blocks of positions.

Weights: ``correct.init_params`` draws EVERY leaf zero-mean normal with the
``std`` given here, norm weights included (std 1; see the LFM2 reference for
why).  Projections have std ``fan_in ** -0.5``; the indexer's key norm has
weight std 1 and bias std 0.5; the EMBEDDING has std 1 where the siblings'
have 0.02: at 0.02 a token's own vector is a fiftieth of what the first
attention layer adds to it, which is nearly the same for every query (a mean
over up to 2048 values), so every token of a row reaches the routers as the
same vector and one expert takes the whole row (read on the CPU at a row of
1024: 1021 of 1024 tokens to one expert in layer 3; the experts held got 12
to 1240 assignments a layer by the seed, and the cell's rate followed them,
2.7% between seeds on the chip).  At std 1 tokens stay distinct and the
seeded routers spread them as a trained router's balance loss would (482 to
663 a layer against the even 512).  The configuration's ``assumed.stds``
says what the rest gives.
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
    sa = cfg["sa_config"]
    return {"D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "dh": cfg["head_dim"],
            "J": sa["indexer_num_heads"], "d": sa["indexer_head_dim"],
            "topk": sa["topk"], "Fe": cfg["moe_intermediate_size"],
            "E": cfg["router_outputs"], "Eh": cfg["num_experts"],
            "V": cfg["vocab_size"], "layers": cfg["num_hidden_layers"]}


def param_shapes(cfg: dict) -> dict:
    d = _dims(cfg)
    D, H, Hkv, dh, J, di = d["D"], d["H"], d["Hkv"], d["dh"], d["J"], d["d"]
    shapes = {"_emb.w0": ((d["V"], D), 1.0), "_norm_out.w": ((D,), 1.0),
              "_cost.w": ((D, d["V"]), D ** -0.5)}
    for i in range(d["layers"]):
        shapes[f"_norm_op{i}.w"] = ((D,), 1.0)
        shapes[f"_norm_ffn{i}.w"] = ((D,), 1.0)
        shapes[f"_attn{i}.wq"] = ((D, H * dh), D ** -0.5)
        shapes[f"_attn{i}.wk"] = ((D, Hkv * dh), D ** -0.5)
        shapes[f"_attn{i}.wv"] = ((D, Hkv * dh), D ** -0.5)
        shapes[f"_attn{i}.wo"] = ((H * dh, D), (H * dh) ** -0.5)
        shapes[f"_attn{i}.q_norm"] = ((dh,), 1.0)
        shapes[f"_attn{i}.k_norm"] = ((dh,), 1.0)
        shapes[f"_attn{i}.wiq"] = ((D, J * di), D ** -0.5)
        shapes[f"_attn{i}.wik"] = ((D, di), D ** -0.5)
        shapes[f"_attn{i}.wiw"] = ((D, J), D ** -0.5)
        shapes[f"_attn{i}.ik_norm"] = ((di,), 1.0)
        shapes[f"_attn{i}.ik_bias"] = ((di,), 0.5)
        shapes[f"_moe{i}.router"] = ((D, d["E"]), D ** -0.5)
        shapes[f"_moe{i}.w1"] = ((d["Eh"], D, d["Fe"]), D ** -0.5)
        shapes[f"_moe{i}.w3"] = ((d["Eh"], D, d["Fe"]), D ** -0.5)
        shapes[f"_moe{i}.w2"] = ((d["Eh"], d["Fe"], D), d["Fe"] ** -0.5)
    return shapes


def mm(a, b):
    """Every matrix multiplication of this file.  The lower-precision control
    (benchmark/correct.py) swaps it for one that rounds its operands."""
    return jnp.matmul(a, b)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w + b


def rotary(x, theta):
    """x [B, T, heads, dr]: the half-rotation form, positions 0..T-1."""
    T, dr = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


# -- attention under the indexer's selection --------------------------------

def indexer_inputs(cfg, p, pre, x):
    """``(qI [B, J, T, d], kI [B, T, d], w [B, T, J])`` from the layer's
    normed input, which the indexer reads as a constant."""
    d = _dims(cfg)
    B, T, _ = x.shape
    J, di = d["J"], d["d"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    u = jax.lax.stop_gradient(x)
    qI = rotary(mm(u, p[pre + ".wiq"]).reshape(B, T, J, di), theta)
    kI = layer_norm(mm(u, p[pre + ".wik"]), p[pre + ".ik_norm"],
                    p[pre + ".ik_bias"], eps)
    kI = rotary(kI[:, :, None], theta)[:, :, 0]
    w = mm(u, p[pre + ".wiw"]) * (J * di) ** -0.5
    return qI.transpose(0, 2, 1, 3), kI, w


def block_scores(qI, kI, w, lo):
    """The indexer's scores of a block of queries (``qI`` ``[B, J, q, d]``,
    ``w`` ``[B, q, J]``, the first at position ``lo``) against EVERY
    position: ``[B, q, T]``, ``-inf`` in the future."""
    pre = mm(qI, kI[:, None].swapaxes(-1, -2))                 # [B, J, q, T]
    scores = jnp.sum(w.swapaxes(1, 2)[..., None] * jax.nn.relu(pre), 1)
    at = lo + jnp.arange(qI.shape[2])[:, None]
    return jnp.where(jnp.arange(kI.shape[1])[None, :] <= at, scores, -jnp.inf)


def block_selection(scores, lo, topk):
    """bool like ``scores``: the ``min(t + 1, topk)`` largest of each row by
    ``lax.top_k`` (among equal scores the lower position first) and a
    scatter of the chosen positions; a row shorter than ``topk`` chooses
    futures too, which the causal mask takes away again."""
    B, rows, cols = scores.shape
    causal = jnp.arange(cols)[None, :] <= lo + jnp.arange(rows)[:, None]
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(scores), min(topk, cols))
    chosen = jnp.zeros(scores.shape, bool).at[
        jnp.arange(B)[:, None, None], jnp.arange(rows)[None, :, None],
        idx].set(True)
    return chosen & causal


def indexed_attention(cfg, p, pre, x):
    """``(Attn(x), L_I summed over the batch, pairs kept)``."""
    d = _dims(cfg)
    B, T, _ = x.shape
    H, Hkv, dh, topk = d["H"], d["Hkv"], d["dh"], d["topk"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    G = H // Hkv             # key-value head j serves query heads jG..jG+G-1
    q = mm(x, p[pre + ".wq"]).reshape(B, T, H, dh)
    k = mm(x, p[pre + ".wk"]).reshape(B, T, Hkv, dh)
    v = mm(x, p[pre + ".wv"]).reshape(B, T, Hkv, dh)
    q = rotary(rms_norm(q, p[pre + ".q_norm"], eps), theta)
    k = rotary(rms_norm(k, p[pre + ".k_norm"], eps), theta)
    qI, kI, w = indexer_inputs(cfg, p, pre, x)
    qs = q.reshape(B, T, Hkv, G, dh).transpose(2, 0, 3, 1, 4)  # [Hkv,B,G,T,dh]
    ks = k.transpose(2, 0, 1, 3)[:, :, None]                   # [Hkv,B,1,T,dh]
    vs = v.transpose(2, 0, 1, 3)[:, :, None]
    block = min(QUERY_BLOCK, T)
    if T % block:
        raise ValueError(f"a row of {T} is not whole blocks of {block}")

    @jax.checkpoint
    def rows(lo):
        """One block of queries against every position (one shape for every
        block, so the blocks are a ``lax.map``); recomputed in the backward
        pass."""
        cut = lambda a, axis: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, lo, block, axis)
        scores = block_scores(cut(qI, 2), kI, cut(w, 1), lo)   # [B, q, T]
        keep = block_selection(scores, lo, topk)

        @jax.checkpoint
        def group(qkv):          # one key-value head and its query heads
            qg, kg, vg = qkv
            s = mm(cut(qg, 2), kg.swapaxes(-1, -2)) * dh ** -0.5
            a = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf), -1)
            return mm(a, vg), jnp.sum(a, 1)

        o, a_sum = jax.lax.map(group, (qs, ks, vs))
        target = jax.lax.stop_gradient(jnp.sum(a_sum, 0) / H)
        logq = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
        live = keep & (target > 0)
        kl = jnp.sum(jnp.where(
            live, target * (jnp.log(jnp.where(live, target, 1.0))
                            - jnp.where(live, logq, 0.0)), 0.0))
        return o, kl, jnp.sum(keep)

    o, kl, kept = jax.lax.map(rows, jnp.arange(0, T, block))
    # [blocks, Hkv, B, G, block, dh] -> [B, T, H dh]
    o = o.transpose(2, 0, 4, 1, 3, 5).reshape(B, T, H * dh)
    return mm(o, p[pre + ".wo"]), jnp.sum(kl), jnp.sum(kept)


# -- the expert layer -------------------------------------------------------

def gated_mlp(x, w1, w3, w2):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def route(cfg, p, pre, x):
    """``(experts [.., k], weights [.., k])`` of every token."""
    s = jax.nn.softmax(mm(x, p[pre + ".router"]), axis=-1)
    chosen, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        chosen = chosen / jnp.sum(chosen, -1, keepdims=True)
    return idx, chosen


def routed_experts(cfg, p, pre, x, first_expert=None, held=None):
    """The part of the routed result that the experts held give; the weights
    ``p[pre + ".w1"]`` etc. are those of experts ``first_expert ..``."""
    first = cfg["first_expert"] if first_expert is None else first_expert
    held = cfg["num_experts"] if held is None else held
    idx, w = route(cfg, p, pre, x)

    @jax.checkpoint
    def one(y, expert):
        e, w1, w3, w2 = expert
        gate = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        return y + gate[..., None] * gated_mlp(x, w1, w3, w2), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.arange(held), p[pre + ".w1"][:held], p[pre + ".w3"][:held],
        p[pre + ".w2"][:held]))
    return y


def layer(cfg, p, i, x):
    """``(y, L_I, pairs kept)`` of layer ``i``."""
    eps = cfg["rms_norm_eps"]
    attn, kl, kept = indexed_attention(
        cfg, p, f"_attn{i}", rms_norm(x, p[f"_norm_op{i}.w"], eps))
    h = x + attn
    hn = rms_norm(h, p[f"_norm_ffn{i}.w"], eps)
    return h + routed_experts(cfg, p, f"_moe{i}", hn), kl, kept


def hidden(cfg: dict, p: dict, ids):
    """``(final hidden state, sum over the layers of L_I, pairs kept a
    layer)``."""
    x = p["_emb.w0"][ids]
    kl_sum, kept = 0.0, []
    for i in range(cfg["num_hidden_layers"]):
        x, kl, n = jax.checkpoint(
            lambda p, x, i=i: layer(cfg, p, i, x))(p, x)
        kl_sum = kl_sum + kl
        kept.append(n)
    return rms_norm(x, p["_norm_out.w"], cfg["rms_norm_eps"]), kl_sum, kept


def loss_sum(cfg: dict, p: dict, batch: dict):
    """(sum of the target tokens' cross-entropies over the vocabulary slice
    PLUS the sum over the layers of the indexer's loss, the number of target
    tokens)."""
    ids, lengths = batch["tokens"]
    targets, _ = batch["next_tokens"]
    h, kl_sum, _ = hidden(cfg, p, ids)
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
    return total + kl_sum, mask.sum()


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


def kept_pairs(seq_len: int, topk: int) -> int:
    """(query, position) pairs a row of ``seq_len`` keeps: ``sum_t min(t + 1,
    topk)``."""
    full = min(seq_len, topk)
    return full * (full + 1) // 2 + (seq_len - full) * topk


def forward_flops_per_row(cfg: dict, seq_len: int) -> dict:
    """Operations of one row's forward pass by part, from the shapes alone,
    2 a multiply-add: the mathematics, not what a kernel visits.  The main
    heads over the KEPT pairs (scores over ``dh``, values over ``dh``), the
    indexer over every causal pair (``J`` heads of ``d``), the target's
    pass (the heads' scores again) over the kept pairs; the routed experts
    at the expected ``num_experts_per_tok * num_experts / router_outputs``
    assignments a token."""
    d = _dims(cfg)
    D, H, Hkv, dh, J, di = d["D"], d["H"], d["Hkv"], d["dh"], d["J"], d["d"]
    n, T = d["layers"], seq_len
    kept = kept_pairs(T, d["topk"])
    causal = T * (T + 1) // 2
    held = cfg["num_experts_per_tok"] * d["Eh"] / d["E"]
    return {
        "attn_proj": n * T * 2 * (2 * D * H * dh + 2 * D * Hkv * dh),
        "indexer_proj": n * T * 2 * (D * J * di + D * di + D * J),
        "indexer_scores": n * causal * 2 * J * di,
        "attn_selected": n * kept * 2 * H * 2 * dh,
        "indexer_target": n * kept * 2 * H * dh,
        "router": n * T * 2 * D * d["E"],
        "experts": n * T * held * 6 * D * d["Fe"],
        "head": T * 2 * D * d["V"],
    }


def step_flops(cfg: dict, traffic: dict) -> float:
    """Operations one training step needs: 3 x the forward pass of what a
    gradient flows through, the target's pass once (it is a constant: no
    backward); what the program recomputes, and the pairs a kernel visits
    and drops, are not counted."""
    parts = forward_flops_per_row(cfg, traffic["seq_len"])
    target = parts.pop("indexer_target")
    return traffic["batch"] * (3.0 * sum(parts.values()) + target)
