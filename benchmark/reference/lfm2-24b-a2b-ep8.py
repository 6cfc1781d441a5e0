"""Plain reference of LFM2-24B-A2B (``model_type`` ``lfm2_moe``) as one chip
of an eight-chip expert-parallel deployment holds it, in float32
``jax.numpy``.  It imports nothing of the program; parameter names are the
program's so that one set of seeded weights serves both.

The equations (from the published ``config.json``; what the config does not
fix is listed under ``assumed`` in the configuration file).  No bias
anywhere.  Layer ``i``: ``h = x + Op_i(RMSNorm(x))``, ``y = h +
FFN_i(RMSNorm(h))``; a final RMSNorm, then the head, which is the embedding
matrix (tied).

- ``Op`` of a ``conv`` layer: ``[B, C, u] = split3(x W_in)``; ``z = B * u``;
  ``c_t = sum_j k_j z_{t-(L-1)+j}`` (depthwise, causal, ``L = conv_L_cache``,
  zero before the row's start); ``Op = (C * c) W_out``.
- ``Op`` of a ``full_attention`` layer: grouped-query attention; RMSNorm over
  the ``head_dim`` of every query head and every key head (one weight vector
  for the queries, one for the keys), rotary embedding in the half-rotation
  form, causal softmax at scale ``head_dim ** -0.5``, ``W_o``.
- ``FFN`` of the first ``num_dense_layers`` layers: ``W_2(silu(W_1 x) * W_3
  x)``.  Of the others: ``s = sigmoid(x W_r)`` over ``router_outputs``
  experts; the ``num_experts_per_tok`` experts with the largest ``s + b``
  (``b`` enters the selection only); weights ``s_e / (sum of the chosen s +
  1e-6)`` times ``routed_scaling_factor``; the sum over the chosen experts of
  ``w_e W_2e(silu(W_1e x) * W_3e x)``.

The chip's share: it holds experts ``first_expert .. first_expert +
num_experts - 1`` of ``router_outputs``, and rows ``0 .. vocab_size - 1`` of
the published vocabulary.  The router keeps all its outputs and its experts a
token; what the absent experts would have added is left out, here as in the
program, and that partial result goes on to the next layer.

How it fits beside 7.5 GB of optimizer state: ``jax.checkpoint`` by layer,
attention in blocks of queries (each block recomputed in the backward pass),
the experts as a plain loop over the experts held with a mask (no grouping,
no kernel), one expert recomputed at a time.

Weights: ``correct.init_params`` draws EVERY leaf zero-mean normal with the
``std`` given here, norm weights included (std 1: a random sign and size per
channel keeps activations of order one, which a constant 1 would too, but a
weight that is exactly 1 hides a gradient that ignores it).  Projections have
std ``fan_in ** -0.5``; the experts' bias has std 0.01, a twentieth of the
spread of the router's scores, as a bias that balances the load has: at 0.1 it
decided the selection and one expert held took 79 of every 113 assignments
(PERF.md, PR 28).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: queries per block of the reference's attention
QUERY_BLOCK = 1024


def _dims(cfg: dict) -> dict:
    D = cfg["hidden_size"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"D": D, "H": H, "Hkv": Hkv, "dh": cfg["head_dim"],
            "L": cfg["conv_L_cache"], "F": cfg["intermediate_size"],
            "Fe": cfg["moe_intermediate_size"], "E": cfg["router_outputs"],
            "Eh": cfg["num_experts"], "V": cfg["vocab_size"]}


def param_shapes(cfg: dict) -> dict:
    d = _dims(cfg)
    D, dh = d["D"], d["dh"]
    shapes = {"_emb.w0": ((d["V"], D), 0.02), "_norm_out.w": ((D,), 1.0)}
    for i, kind in enumerate(cfg["layer_types"]):
        shapes[f"_norm_op{i}.w"] = ((D,), 1.0)
        shapes[f"_norm_ffn{i}.w"] = ((D,), 1.0)
        if kind == "conv":
            shapes[f"_conv{i}.w_in"] = ((D, 3 * D), D ** -0.5)
            shapes[f"_conv{i}.kernel"] = ((d["L"], D), d["L"] ** -0.5)
            shapes[f"_conv{i}.w_out"] = ((D, D), D ** -0.5)
        else:
            shapes[f"_attn{i}.wq"] = ((D, d["H"] * dh), D ** -0.5)
            shapes[f"_attn{i}.wk"] = ((D, d["Hkv"] * dh), D ** -0.5)
            shapes[f"_attn{i}.wv"] = ((D, d["Hkv"] * dh), D ** -0.5)
            shapes[f"_attn{i}.wo"] = ((d["H"] * dh, D),
                                      (d["H"] * dh) ** -0.5)
            shapes[f"_attn{i}.q_norm"] = ((dh,), 1.0)
            shapes[f"_attn{i}.k_norm"] = ((dh,), 1.0)
        if i < cfg["num_dense_layers"]:
            shapes[f"_mlp{i}.w1"] = ((D, d["F"]), D ** -0.5)
            shapes[f"_mlp{i}.w3"] = ((D, d["F"]), D ** -0.5)
            shapes[f"_mlp{i}.w2"] = ((d["F"], D), d["F"] ** -0.5)
        else:
            shapes[f"_moe{i}.router"] = ((D, d["E"]), D ** -0.5)
            shapes[f"_moe{i}.expert_bias"] = ((d["E"],), 0.01)
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


def rotary(x, theta):
    """x [B, T, heads, dh]: the half-rotation form, positions 0..T-1."""
    T, dh = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def short_conv(cfg, p, pre, x):
    L = cfg["conv_L_cache"]
    b, c, u = jnp.split(mm(x, p[pre + ".w_in"]), 3, axis=-1)
    z = b * u
    zp = jnp.pad(z, ((0, 0), (L - 1, 0), (0, 0)))
    T = z.shape[1]
    conv = sum(p[pre + ".kernel"][j] * zp[:, j:j + T] for j in range(L))
    return mm(c * conv, p[pre + ".w_out"])


def attention(cfg, p, pre, x):
    d = _dims(cfg)
    B, T, _ = x.shape
    H, Hkv, dh = d["H"], d["Hkv"], d["dh"]
    eps, theta = cfg["norm_eps"], cfg["rope_parameters"]["rope_theta"]
    q = mm(x, p[pre + ".wq"]).reshape(B, T, H, dh)
    k = mm(x, p[pre + ".wk"]).reshape(B, T, Hkv, dh)
    v = mm(x, p[pre + ".wv"]).reshape(B, T, Hkv, dh)
    q = rotary(rms_norm(q, p[pre + ".q_norm"], eps), theta)
    k = rotary(rms_norm(k, p[pre + ".k_norm"], eps), theta)
    # each key-value head serves H / Hkv consecutive query heads
    k = jnp.repeat(k, H // Hkv, axis=2).transpose(0, 2, 1, 3)   # [B,H,T,dh]
    v = jnp.repeat(v, H // Hkv, axis=2).transpose(0, 2, 1, 3)
    q = q.transpose(0, 2, 1, 3)

    @jax.checkpoint
    def block(qb, kb, vb, first):
        s = mm(qb, kb.swapaxes(-1, -2)) * dh ** -0.5
        rows = first + jnp.arange(qb.shape[2])[:, None]
        s = jnp.where(jnp.arange(kb.shape[2])[None, :] <= rows, s, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), vb)

    out = [block(q[:, :, lo:lo + QUERY_BLOCK], k[:, :, :lo + QUERY_BLOCK],
                 v[:, :, :lo + QUERY_BLOCK], lo)
           for lo in range(0, T, QUERY_BLOCK)]
    o = jnp.concatenate(out, axis=2).transpose(0, 2, 1, 3).reshape(B, T, -1)
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


def experts(cfg, p, pre, x, first_expert=None, held=None):
    """The part of the expert layer's result that the experts held give."""
    first = cfg["first_expert"] if first_expert is None else first_expert
    held = cfg["num_experts"] if held is None else held
    idx, w = route(cfg, p, pre, x)
    one = jax.checkpoint(gated_mlp)
    y = jnp.zeros_like(x)
    for e in range(held):
        gate = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        y = y + gate[..., None] * one(x, p[pre + ".w1"][e],
                                      p[pre + ".w3"][e], p[pre + ".w2"][e])
    return y


def layer(cfg, p, i, x):
    eps = cfg["norm_eps"]
    xn = rms_norm(x, p[f"_norm_op{i}.w"], eps)
    if cfg["layer_types"][i] == "conv":
        h = x + short_conv(cfg, p, f"_conv{i}", xn)
    else:
        h = x + attention(cfg, p, f"_attn{i}", xn)
    hn = rms_norm(h, p[f"_norm_ffn{i}.w"], eps)
    if i < cfg["num_dense_layers"]:
        return h + gated_mlp(hn, p[f"_mlp{i}.w1"], p[f"_mlp{i}.w3"],
                             p[f"_mlp{i}.w2"])
    return h + experts(cfg, p, f"_moe{i}", hn)


def hidden(cfg: dict, p: dict, ids):
    x = p["_emb.w0"][ids]
    for i in range(len(cfg["layer_types"])):
        x = jax.checkpoint(lambda p, x, i=i: layer(cfg, p, i, x))(p, x)
    return rms_norm(x, p["_norm_out.w"], cfg["norm_eps"])


def loss_sum(cfg: dict, p: dict, batch: dict):
    """(sum of the target tokens' cross-entropies over the vocabulary slice,
    the number of target tokens)."""
    ids, lengths = batch["tokens"]
    targets, _ = batch["next_tokens"]
    z = mm(hidden(cfg, p, ids), p["_emb.w0"].T)
    logp = jax.nn.log_softmax(z, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
    mask = (jnp.arange(ids.shape[1])[None, :] < lengths[:, None])
    mask = mask.astype(jnp.float32)
    return -(picked * mask).sum(), mask.sum()


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
    the experts at the expected ``num_experts_per_tok * num_experts /
    router_outputs`` assignments a token, causal attention at half the
    square."""
    d = _dims(cfg)
    D, dh, H, Hkv = d["D"], d["dh"], d["H"], d["Hkv"]
    kinds = cfg["layer_types"]
    n_conv = sum(k == "conv" for k in kinds)
    n_attn = len(kinds) - n_conv
    n_dense = min(cfg["num_dense_layers"], len(kinds))
    n_moe = len(kinds) - n_dense
    held = cfg["num_experts_per_tok"] * d["Eh"] / d["E"]
    return {
        "short_conv": n_conv * (2 * D * 3 * D + 2 * D * D + 2 * d["L"] * D),
        "attention_proj": n_attn * (4 * D * H * dh + 4 * D * Hkv * dh),
        "attention_core": n_attn * 2 * seq_len * H * dh,
        "dense_mlp": n_dense * 6 * D * d["F"],
        "router": n_moe * 2 * D * d["E"],
        "experts": n_moe * held * 6 * D * d["Fe"],
        "head": 2 * D * d["V"],
    }


def step_flops(cfg: dict, traffic: dict) -> float:
    """Operations one training step needs: 3 x the forward pass; what the
    program recomputes is not counted."""
    per_token = sum(forward_flops_per_token(cfg, traffic["seq_len"]).values())
    return 3.0 * per_token * traffic["batch"] * traffic["seq_len"]
