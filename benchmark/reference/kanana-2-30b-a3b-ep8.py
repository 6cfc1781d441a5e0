"""Plain reference of Kanana-2-30B-A3B (``model_type`` ``deepseek_v3``) as
one chip of an eight-chip expert-parallel deployment holds it, in float32
``jax.numpy``.  It imports nothing of the program; parameter names are the
program's so that one set of seeded weights serves both.

The equations (from the published ``config.json``; what the config does not
fix is listed under ``assumed`` in the configuration file).  No bias
anywhere.  Layer ``i``: ``h = x + MLA_i(RMSNorm(x))``, ``y = h +
FFN_i(RMSNorm(h))``; a final RMSNorm, then a head ``W_head`` ``[hidden,
vocab]`` that is NOT the embedding (untied).

- ``MLA`` (latent attention, the expanded form that training uses; ``H``
  heads, ``r = kv_lora_rank``, ``dn = qk_nope_head_dim``, ``dr =
  qk_rope_head_dim``, ``dv = v_head_dim``): ``q = x W_q`` -> ``[T, H, dn +
  dr]``, split ``q_nope | q_rope`` (``q_lora_rank`` is null: no query
  latent).  ``ckv = x W_kv_a`` -> ``[T, r + dr]``; ``c = RMSNorm_r(ckv[:,
  :r])`` with a weight of its own; ``k_rope = ckv[:, r:]``, ONE head.  ``kv =
  c W_kv_b`` -> ``[T, H, dn + dv]``, split ``k_nope | v``.  Rotary embedding
  (half-rotation form, positions 0..T-1, no scaling) on ``q_rope`` and
  ``k_rope`` only.  ``k = [k_nope | k_rope shared by every head]``; causal
  softmax of ``q k^T (dn + dr) ** -0.5``; ``o = P v`` ``[T, H, dv]``;
  ``MLA = o W_o``.  No QK-norm.
- ``FFN`` of the first ``first_k_dense_replace`` layers: ``W_2(silu(W_1 x) *
  W_3 x)`` of ``intermediate_size``.  Of the others: ``s = sigmoid(x W_r)``
  over ``router_outputs`` experts; the ``num_experts_per_tok`` experts with
  the largest ``s + b`` (``b`` enters the selection only; ``n_group`` =
  ``topk_group`` = 1, so no group limit); weights ``s_e / (sum of the chosen
  s + 1e-6)`` times ``routed_scaling_factor``; the sum over the chosen
  experts of ``w_e E_e(x)``, ``E_e`` a gated MLP of
  ``moe_intermediate_size``; plus ``S(x)``, the shared experts: ONE gated
  MLP of ``n_shared_experts * moe_intermediate_size``, unweighted.

The chip's share: it holds experts ``first_expert .. first_expert +
n_routed_experts - 1`` of ``router_outputs``, and rows ``0 .. vocab_size -
1`` of the published vocabulary.  The router keeps all its outputs and its
experts a token; what the absent experts would have added is left out, here
as in the program, and that partial result goes on to the next layer.  The
shared experts are computed whole: every chip of the eight computes them
alike on its own tokens.

How it fits beside 9.2 GB of optimizer state (the runner's reference loop
holds ``p``, ``m``, ``v``, the last gradient and the new one: 11.5 GB before
a temporary): ``jax.checkpoint`` by layer, attention a group of heads at a
time and in blocks of queries (each recomputed in the backward pass), the
experts as a plain loop
over the experts held with a mask (a ``lax.scan``; no grouping, no kernel),
one expert recomputed at a time with its weighting inside the recomputed
part, and the head and loss in blocks of positions.

Weights: ``correct.init_params`` draws EVERY leaf zero-mean normal with the
``std`` given here, norm weights included (std 1; see the LFM2 reference for
why).  Projections have std ``fan_in ** -0.5``; the experts' bias has std
0.01, a twentieth of the spread of the router's scores.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: queries per block of the reference's attention
QUERY_BLOCK = 1024
#: groups the heads are taken in, one group at a time
HEAD_GROUPS = 4
#: positions per block of the reference's head and loss
HEAD_BLOCK = 2048


def _dims(cfg: dict) -> dict:
    return {"D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "r": cfg["kv_lora_rank"], "dn": cfg["qk_nope_head_dim"],
            "dr": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
            "F": cfg["intermediate_size"],
            "Fe": cfg["moe_intermediate_size"],
            "Fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "E": cfg["router_outputs"], "Eh": cfg["n_routed_experts"],
            "V": cfg["vocab_size"], "layers": cfg["num_hidden_layers"],
            "dense": cfg["first_k_dense_replace"]}


def param_shapes(cfg: dict) -> dict:
    d = _dims(cfg)
    D, H, r, dn, dr, dv = d["D"], d["H"], d["r"], d["dn"], d["dr"], d["dv"]
    shapes = {"_emb.w0": ((d["V"], D), 0.02), "_norm_out.w": ((D,), 1.0),
              "_cost.w": ((D, d["V"]), D ** -0.5)}
    for i in range(d["layers"]):
        shapes[f"_norm_op{i}.w"] = ((D,), 1.0)
        shapes[f"_norm_ffn{i}.w"] = ((D,), 1.0)
        shapes[f"_mla{i}.wq"] = ((D, H * (dn + dr)), D ** -0.5)
        shapes[f"_mla{i}.wkv_a"] = ((D, r + dr), D ** -0.5)
        shapes[f"_mla{i}.kv_norm"] = ((r,), 1.0)
        shapes[f"_mla{i}.wkv_b"] = ((r, H * (dn + dv)), r ** -0.5)
        shapes[f"_mla{i}.wo"] = ((H * dv, D), (H * dv) ** -0.5)
        if i < d["dense"]:
            shapes[f"_mlp{i}.w1"] = ((D, d["F"]), D ** -0.5)
            shapes[f"_mlp{i}.w3"] = ((D, d["F"]), D ** -0.5)
            shapes[f"_mlp{i}.w2"] = ((d["F"], D), d["F"] ** -0.5)
        else:
            shapes[f"_moe{i}.router"] = ((D, d["E"]), D ** -0.5)
            shapes[f"_moe{i}.expert_bias"] = ((d["E"],), 0.01)
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


def rotary(x, theta):
    """x [B, T, heads, dr]: the half-rotation form, positions 0..T-1."""
    T, dr = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def latent_attention(cfg, p, pre, x):
    d = _dims(cfg)
    B, T, _ = x.shape
    H, r, dn, dr, dv = d["H"], d["r"], d["dn"], d["dr"], d["dv"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = mm(x, p[pre + ".wq"]).reshape(B, T, H, dn + dr)
    ckv = mm(x, p[pre + ".wkv_a"])
    c = rms_norm(ckv[..., :r], p[pre + ".kv_norm"], eps)
    k_rope = rotary(ckv[..., r:].reshape(B, T, 1, dr), theta)
    kv = mm(c, p[pre + ".wkv_b"]).reshape(B, T, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], rotary(q[..., dn:], theta)], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.tile(k_rope, (1, 1, H, 1))], -1)
    v = kv[..., dn:]
    G = HEAD_GROUPS if H % HEAD_GROUPS == 0 else 1

    def split(a):            # [B, T, H, d] -> [G, B, H / G, T, d]
        return a.reshape(B, T, G, H // G, -1).transpose(2, 0, 3, 1, 4)

    @jax.checkpoint
    def heads(qkv):
        """One group of heads, its queries a block at a time."""
        q, k, v = qkv

        def block(lo):
            # the slices are taken inside the recomputed part: what the
            # backward pass holds of a block is the group's q, k and v
            @jax.checkpoint
            def run(q, k, v):
                qb, kb, vb = (q[:, :, lo:lo + QUERY_BLOCK],
                              k[:, :, :lo + QUERY_BLOCK],
                              v[:, :, :lo + QUERY_BLOCK])
                s = mm(qb, kb.swapaxes(-1, -2)) * (dn + dr) ** -0.5
                rows = lo + jnp.arange(qb.shape[2])[:, None]
                s = jnp.where(jnp.arange(kb.shape[2])[None, :] <= rows, s,
                              -jnp.inf)
                return mm(jax.nn.softmax(s, axis=-1), vb)

            return run(q, k, v)

        return jnp.concatenate([block(lo) for lo in range(0, T, QUERY_BLOCK)],
                               axis=2)

    # a map over groups of heads and not one pass over all: the scores of a
    # block and the zero-padded pieces of dk and dv are a group's, not 32
    # heads', and a group's gradients are written into its place
    o = jax.lax.map(heads, (split(q), split(k), split(v)))  # [G,B,H/G,T,dv]
    o = o.transpose(1, 3, 0, 2, 4).reshape(B, T, H * dv)
    return mm(o, p[pre + ".wo"])


def gated_mlp(x, w1, w3, w2):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def route(cfg, p, pre, x):
    """``(experts [.., k], weights [.., k])`` of every token."""
    s = jax.nn.sigmoid(mm(x, p[pre + ".router"]))
    _, idx = jax.lax.top_k(s + p[pre + ".expert_bias"],
                           cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        chosen = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-6)
    return idx, chosen * cfg["routed_scaling_factor"]


def routed_experts(cfg, p, pre, x, first_expert=None, held=None):
    """The part of the routed result that the experts held give; the weights
    ``p[pre + ".w1"]`` etc. are those of experts ``first_expert ..``."""
    first = cfg["first_expert"] if first_expert is None else first_expert
    held = cfg["n_routed_experts"] if held is None else held
    idx, w = route(cfg, p, pre, x)

    @jax.checkpoint
    def one(y, expert):      # recomputed whole, the weighting too: no
        e, w1, w3, w2 = expert         # expert's output is held
        gate = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        return y + gate[..., None] * gated_mlp(x, w1, w3, w2), None

    # a scan and not a Python loop: a weight's gradient is then written into
    # its expert's place, where a loop makes one zero-padded copy of the
    # whole stack per expert (16 x 3 x 100 MB here)
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.arange(held), p[pre + ".w1"][:held], p[pre + ".w3"][:held],
        p[pre + ".w2"][:held]))
    return y


def shared_experts(p, pre, x):
    return gated_mlp(x, p[pre + ".shared_w1"], p[pre + ".shared_w3"],
                     p[pre + ".shared_w2"])


def expert_layer(cfg, p, pre, x):
    return routed_experts(cfg, p, pre, x) + shared_experts(p, pre, x)


def layer(cfg, p, i, x):
    eps = cfg["rms_norm_eps"]
    h = x + latent_attention(cfg, p, f"_mla{i}",
                             rms_norm(x, p[f"_norm_op{i}.w"], eps))
    hn = rms_norm(h, p[f"_norm_ffn{i}.w"], eps)
    if i < cfg["first_k_dense_replace"]:
        return h + gated_mlp(hn, p[f"_mlp{i}.w1"], p[f"_mlp{i}.w3"],
                             p[f"_mlp{i}.w2"])
    return h + expert_layer(cfg, p, f"_moe{i}", hn)


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


def forward_flops_per_token(cfg: dict, seq_len: int) -> dict:
    """Operations of one token's forward pass by part, from the shapes alone:
    the routed experts at the expected ``num_experts_per_tok *
    n_routed_experts / router_outputs`` assignments a token, causal attention
    at half the square with scores over ``dn + dr`` and values over ``dv``."""
    d = _dims(cfg)
    D, H, r, dn, dr, dv = d["D"], d["H"], d["r"], d["dn"], d["dr"], d["dv"]
    n, n_dense = d["layers"], min(d["dense"], d["layers"])
    n_moe = n - n_dense
    held = cfg["num_experts_per_tok"] * d["Eh"] / d["E"]
    return {
        "mla_proj": n * 2 * (D * H * (dn + dr) + D * (r + dr)
                             + r * H * (dn + dv) + H * dv * D),
        "mla_core": n * seq_len * H * ((dn + dr) + dv),
        "dense_mlp": n_dense * 6 * D * d["F"],
        "router": n_moe * 2 * D * d["E"],
        "experts": n_moe * held * 6 * D * d["Fe"],
        "shared_experts": n_moe * 6 * D * d["Fs"],
        "head": 2 * D * d["V"],
    }


def step_flops(cfg: dict, traffic: dict) -> float:
    """Operations one training step needs: 3 x the forward pass; what the
    program recomputes is not counted."""
    per_token = sum(forward_flops_per_token(cfg, traffic["seq_len"]).values())
    return 3.0 * per_token * traffic["batch"] * traffic["seq_len"]
