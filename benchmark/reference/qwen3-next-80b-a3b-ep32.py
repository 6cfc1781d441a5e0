"""Plain reference of Qwen3-Next-80B-A3B (``model_type`` ``qwen3_next``) as
one chip of a 32-chip expert-parallel deployment holds it, in float32
``jax.numpy``.  It imports nothing of the program; parameter names are the
program's so that one set of seeded weights serves both.

The equations (from the published ``config.json``; what the config does not
fix is listed under ``assumed`` in the configuration file).  No bias
anywhere.  Every RMSNorm of the stack, and ``q_norm`` / ``k_norm``, is ``x *
rsqrt(mean x^2 + eps) * (1 + w)``.  Layer ``i``: ``h = x + Mix_i(RMSNorm(x))``,
``y = h + FFN_i(RMSNorm(h))``; ``Mix_i`` is full attention where ``(i + 1) %
full_attention_interval == 0`` and a gated delta net elsewhere; a final
RMSNorm, then a head ``W_head`` ``[hidden, vocab]`` that is NOT the embedding.

- ``GDN`` (gated delta net; ``Hk`` key heads and ``Hv`` value heads of ``dk``
  and ``dv`` channels): ``[q | k | v | z] = x W_qkvz``, ``[b | a] = x W_ba``.
  ``[q | k | v]`` goes through a depthwise causal convolution of
  ``linear_conv_kernel_dim`` taps (``out_t = sum_j kernel[j] in[t - (L-1) +
  j]``, zero before the row's start, no bias), then SiLU.  ``q`` and ``k``
  are L2-normalised over a head's channels (``x * rsqrt(sum x^2 + 1e-6)``),
  key head ``j`` serves value heads ``j G .. j G + G - 1`` (``G = Hv / Hk``)
  and ``q`` is scaled by ``dk ** -0.5``.  ``beta_t = sigmoid(b_t)``, ``g_t =
  -exp(A_log) * softplus(a_t + dt_bias)``.  A value head's state ``S`` ``[dk,
  dv]`` is zero at the row's start, and TOKEN BY TOKEN: ``S' = exp(g_t)
  S_{t-1}``; ``S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T``; ``o_t = S_t^T
  q_t``.  Then ``y = (w * o * rsqrt(mean o^2 + eps)) * silu(z)`` over each
  head's ``dv`` channels (a plain weight ``w``, not ``1 + w``), and ``y
  W_out``.
- ``Attn`` (``H`` heads of ``dh``, ``Hkv`` key-value heads): ``x W_q`` gives
  each head ``[q | gate]`` of ``dh + dh``; ``q_norm`` and ``k_norm`` over
  ``dh``; rotary embedding (half-rotation form, positions 0..T-1) on the
  first ``dh * partial_rotary_factor`` channels only; causal softmax of ``q
  k^T dh ** -0.5``; the result times ``sigmoid(gate)``; ``W_o``.
- ``FFN``, in every layer: the float32 softmax of ``x W_r`` over
  ``router_outputs`` experts; the ``num_experts_per_tok`` largest; weights
  divided by their sum (no epsilon, no bias, no scaling); the sum over the
  chosen experts of ``w_e E_e(x)``, ``E_e`` a gated MLP of
  ``moe_intermediate_size``; plus ``sigmoid(x w_g) * S(x)``, ``S`` one gated
  MLP of ``shared_expert_intermediate_size``.

The chip's share: it holds experts ``first_expert .. first_expert +
num_experts - 1`` of ``router_outputs``, and rows ``0 .. vocab_size - 1`` of
the published vocabulary.  The router keeps all its outputs and its experts a
token; what the absent experts would have added is left out, here as in the
program, and that partial result goes on to the next layer.  The mixers, the
router and the shared expert are computed whole: every chip of the 32
computes them alike on its own tokens.

How it fits beside 6.8 GB of optimizer state: ``jax.checkpoint`` by layer;
the delta rule as a scan over blocks of :data:`TOKEN_BLOCK` tokens whose
inner token-by-token scan is recomputed in the backward pass (a plain scan's
backward would keep every token's state: 17 GB a layer), attention a
key-value head at a time and in blocks of queries, the experts as a plain
loop over the experts held with a mask (a ``lax.scan``), and the head and
loss in blocks of positions.

Weights: ``correct.init_params`` draws EVERY leaf zero-mean normal with the
``std`` given here, norm weights included (std 1).  Projections have std
``fan_in ** -0.5``; ``A_log`` and ``dt_bias`` std 1.0, so a token's decay
``exp(g)`` spans about 0.01 to 0.99: memory both dies inside 64 tokens and
lives across many.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: tokens per block of the delta rule's outer scan
TOKEN_BLOCK = 64
#: queries per block of the reference's attention
QUERY_BLOCK = 1024
#: positions per block of the reference's head and loss
HEAD_BLOCK = 2048


def _dims(cfg: dict) -> dict:
    return {"D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "dh": cfg["head_dim"],
            "rd": int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
            "Hk": cfg["linear_num_key_heads"],
            "Hv": cfg["linear_num_value_heads"],
            "dk": cfg["linear_key_head_dim"],
            "dv": cfg["linear_value_head_dim"],
            "L": cfg["linear_conv_kernel_dim"],
            "Fe": cfg["moe_intermediate_size"],
            "Fs": cfg["shared_expert_intermediate_size"],
            "E": cfg["router_outputs"], "Eh": cfg["num_experts"],
            "V": cfg["vocab_size"], "layers": cfg["num_hidden_layers"]}


def is_full_attention(cfg: dict, i: int) -> bool:
    return (i + 1) % cfg["full_attention_interval"] == 0


def param_shapes(cfg: dict) -> dict:
    d = _dims(cfg)
    D, H, Hkv, dh = d["D"], d["H"], d["Hkv"], d["dh"]
    nk, nv = d["Hk"] * d["dk"], d["Hv"] * d["dv"]
    shapes = {"_emb.w0": ((d["V"], D), 0.02), "_norm_out.w": ((D,), 1.0),
              "_cost.w": ((D, d["V"]), D ** -0.5)}
    for i in range(d["layers"]):
        shapes[f"_norm_op{i}.w"] = ((D,), 1.0)
        shapes[f"_norm_ffn{i}.w"] = ((D,), 1.0)
        if is_full_attention(cfg, i):
            pre = f"_attn{i}"
            shapes[pre + ".wq"] = ((D, H * 2 * dh), D ** -0.5)
            shapes[pre + ".wk"] = ((D, Hkv * dh), D ** -0.5)
            shapes[pre + ".wv"] = ((D, Hkv * dh), D ** -0.5)
            shapes[pre + ".wo"] = ((H * dh, D), (H * dh) ** -0.5)
            shapes[pre + ".q_norm"] = ((dh,), 1.0)
            shapes[pre + ".k_norm"] = ((dh,), 1.0)
        else:
            pre = f"_gdn{i}"
            shapes[pre + ".w_qkvz"] = ((D, 2 * nk + 2 * nv), D ** -0.5)
            shapes[pre + ".w_ba"] = ((D, 2 * d["Hv"]), D ** -0.5)
            shapes[pre + ".kernel"] = ((d["L"], 2 * nk + nv), d["L"] ** -0.5)
            shapes[pre + ".a_log"] = ((d["Hv"],), 1.0)
            shapes[pre + ".dt_bias"] = ((d["Hv"],), 1.0)
            shapes[pre + ".norm"] = ((d["dv"],), 1.0)
            shapes[pre + ".w_out"] = ((nv, D), nv ** -0.5)
        pre = f"_moe{i}"
        shapes[pre + ".router"] = ((D, d["E"]), D ** -0.5)
        shapes[pre + ".w1"] = ((d["Eh"], D, d["Fe"]), D ** -0.5)
        shapes[pre + ".w3"] = ((d["Eh"], D, d["Fe"]), D ** -0.5)
        shapes[pre + ".w2"] = ((d["Eh"], d["Fe"], D), d["Fe"] ** -0.5)
        shapes[pre + ".shared_w1"] = ((D, d["Fs"]), D ** -0.5)
        shapes[pre + ".shared_w3"] = ((D, d["Fs"]), D ** -0.5)
        shapes[pre + ".shared_w2"] = ((d["Fs"], D), d["Fs"] ** -0.5)
        shapes[pre + ".shared_gate"] = ((D,), D ** -0.5)
    return shapes


def mm(a, b):
    """Every matrix multiplication of this file.  The lower-precision control
    (benchmark/correct.py) swaps it for one that rounds its operands."""
    return jnp.matmul(a, b)


def rms_norm(x, w, eps):
    """The stack's norm: the weight is applied as ``1 + w``."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * (1.0 + w)


def rotary(x, theta):
    """x [B, T, heads, dr]: the half-rotation form, positions 0..T-1."""
    T, dr = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


# -- the gated delta net ----------------------------------------------------

def delta_rule(q, k, v, g, beta):
    """q, k ``[B, T, H, dk]``, v ``[B, T, H, dv]``, g, beta ``[B, T, H]`` ->
    ``[B, T, H, dv]``: the recurrence itself, one token at a time."""
    B, T, H, dk = q.shape
    block = min(TOKEN_BLOCK, T)
    if T % block:
        raise ValueError(f"a row of {T} tokens is not whole blocks of {block}")

    def token(S, x):
        qt, kt, vt, gt, bt = x                   # [B, H, d], [B, H]
        S = jnp.exp(gt)[..., None, None] * S
        u = bt[..., None] * (vt - mm(kt[..., None, :], S)[..., 0, :])
        S = S + mm(kt[..., :, None], u[..., None, :])
        return S, mm(qt[..., None, :], S)[..., 0, :]

    @jax.checkpoint
    def tokens(S, xs):       # recomputed: the backward holds a block's
        return jax.lax.scan(token, S, xs)        # states, not the row's

    def blocks(a):           # [B, T, ...] -> [T / block, block, B, ...]
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((T // block, block) + a.shape[1:])

    S0 = jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(tokens, S0, tuple(blocks(a) for a in
                                          (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((T,) + o.shape[2:]), 0, 1)


def gated_delta_net(cfg, p, pre, x):
    d = _dims(cfg)
    B, T, _ = x.shape
    Hk, Hv, dk, dv, L = d["Hk"], d["Hv"], d["dk"], d["dv"], d["L"]
    nk, nv = Hk * dk, Hv * dv
    qkvz = mm(x, p[pre + ".w_qkvz"])
    ba = mm(x, p[pre + ".w_ba"])
    mixed = qkvz[..., :2 * nk + nv]
    padded = jnp.pad(mixed, ((0, 0), (L - 1, 0), (0, 0)))
    conv = sum(p[pre + ".kernel"][j] * padded[:, j:j + T] for j in range(L))
    conv = jax.nn.silu(conv)
    z = qkvz[..., 2 * nk + nv:].reshape(B, T, Hv, dv)

    def unit(h):
        return h * jax.lax.rsqrt(jnp.sum(jnp.square(h), -1, keepdims=True)
                                 + 1e-6)

    q = unit(conv[..., :nk].reshape(B, T, Hk, dk)) * dk ** -0.5
    k = unit(conv[..., nk:2 * nk].reshape(B, T, Hk, dk))
    q, k = (jnp.repeat(h, Hv // Hk, axis=2) for h in (q, k))
    v = conv[..., 2 * nk:].reshape(B, T, Hv, dv)
    beta = jax.nn.sigmoid(ba[..., :Hv])
    g = -jnp.exp(p[pre + ".a_log"]) * jax.nn.softplus(
        ba[..., Hv:] + p[pre + ".dt_bias"])
    o = delta_rule(q, k, v, g, beta)
    y = (p[pre + ".norm"] * o * jax.lax.rsqrt(
        jnp.mean(jnp.square(o), -1, keepdims=True) + cfg["rms_norm_eps"])
         ) * jax.nn.silu(z)
    return mm(y.reshape(B, T, nv), p[pre + ".w_out"])


# -- gated full attention ---------------------------------------------------

def gated_attention(cfg, p, pre, x):
    d = _dims(cfg)
    B, T, _ = x.shape
    H, Hkv, dh, rd = d["H"], d["Hkv"], d["dh"], d["rd"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    qg = mm(x, p[pre + ".wq"]).reshape(B, T, H, 2 * dh)
    q, gate = qg[..., :dh], qg[..., dh:]
    k = mm(x, p[pre + ".wk"]).reshape(B, T, Hkv, dh)
    v = mm(x, p[pre + ".wv"]).reshape(B, T, Hkv, dh)

    def turn(h):             # the first rd channels only
        return jnp.concatenate([rotary(h[..., :rd], theta), h[..., rd:]], -1)

    q = turn(rms_norm(q, p[pre + ".q_norm"], eps))
    k = turn(rms_norm(k, p[pre + ".k_norm"], eps))
    G = H // Hkv             # key-value head j serves query heads jG..jG+G-1
    block = min(QUERY_BLOCK, T)

    @jax.checkpoint
    def group(qkv):
        """One key-value head and its query heads, a block of queries at a
        time."""
        q, k, v = qkv        # [B, G, T, dh], [B, 1, T, dh] x 2

        def rows(lo):
            @jax.checkpoint
            def run(q, k, v):
                qb, kb, vb = (q[:, :, lo:lo + block], k[:, :, :lo + block],
                              v[:, :, :lo + block])
                s = mm(qb, kb.swapaxes(-1, -2)) * dh ** -0.5
                at = lo + jnp.arange(qb.shape[2])[:, None]
                s = jnp.where(jnp.arange(kb.shape[2])[None, :] <= at, s,
                              -jnp.inf)
                return mm(jax.nn.softmax(s, axis=-1), vb)

            return run(q, k, v)

        return jnp.concatenate([rows(lo) for lo in range(0, T, block)],
                               axis=2)

    qs = q.reshape(B, T, Hkv, G, dh).transpose(2, 0, 3, 1, 4)
    ks = k.transpose(2, 0, 1, 3)[:, :, None]
    vs = v.transpose(2, 0, 1, 3)[:, :, None]
    o = jax.lax.map(group, (qs, ks, vs))          # [Hkv, B, G, T, dh]
    o = o.transpose(1, 3, 0, 2, 4).reshape(B, T, H, dh)
    o = o * jax.nn.sigmoid(gate)
    return mm(o.reshape(B, T, H * dh), p[pre + ".wo"])


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


def shared_expert(p, pre, x):
    gate = jax.nn.sigmoid(mm(x, p[pre + ".shared_gate"][:, None]))
    return gate * gated_mlp(x, p[pre + ".shared_w1"], p[pre + ".shared_w3"],
                            p[pre + ".shared_w2"])


def expert_layer(cfg, p, pre, x):
    return routed_experts(cfg, p, pre, x) + shared_expert(p, pre, x)


def layer(cfg, p, i, x):
    eps = cfg["rms_norm_eps"]
    normed = rms_norm(x, p[f"_norm_op{i}.w"], eps)
    if is_full_attention(cfg, i):
        h = x + gated_attention(cfg, p, f"_attn{i}", normed)
    else:
        h = x + gated_delta_net(cfg, p, f"_gdn{i}", normed)
    return h + expert_layer(cfg, p, f"_moe{i}",
                            rms_norm(h, p[f"_norm_ffn{i}.w"], eps))


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
    the delta rule as the RECURRENCE needs them (three products of ``dk x
    dv`` a token and value head: reading the state under the key, the
    rank-one correction, reading it under the query; a chunked form computes
    more), causal attention at half the square, the routed experts at the
    expected ``num_experts_per_tok * num_experts / router_outputs``
    assignments a token."""
    d = _dims(cfg)
    D, H, Hkv, dh = d["D"], d["H"], d["Hkv"], d["dh"]
    nk, nv = d["Hk"] * d["dk"], d["Hv"] * d["dv"]
    n = d["layers"]
    n_full = sum(is_full_attention(cfg, i) for i in range(n))
    n_gdn = n - n_full
    held = cfg["num_experts_per_tok"] * d["Eh"] / d["E"]
    return {
        "gdn_proj": n_gdn * 2 * (D * (2 * nk + 2 * nv) + D * 2 * d["Hv"]
                                 + d["L"] * (2 * nk + nv) + nv * D),
        "gdn_scan": n_gdn * d["Hv"] * 3 * 2 * d["dk"] * d["dv"],
        "attn_proj": n_full * 2 * (D * H * 2 * dh + 2 * D * Hkv * dh
                                   + H * dh * D),
        "attn_core": n_full * seq_len * H * 2 * dh,
        "router": n * 2 * D * d["E"],
        "experts": n * held * 6 * D * d["Fe"],
        "shared_expert": n * (6 * D * d["Fs"] + 2 * D),
        "head": 2 * D * d["V"],
    }


def step_flops(cfg: dict, traffic: dict) -> float:
    """Operations one training step needs: 3 x the forward pass; what the
    program recomputes is not counted."""
    per_token = sum(forward_flops_per_token(cfg, traffic["seq_len"]).values())
    return 3.0 * per_token * traffic["batch"] * traffic["seq_len"]
