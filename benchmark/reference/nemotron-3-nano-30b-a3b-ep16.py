"""Plain reference of NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type``
``nemotron_h``) as one chip of a 16-chip expert-parallel deployment holds it,
in float32 ``jax.numpy``.  It imports nothing of the program; parameter names
are the program's so that one set of seeded weights serves both.

The equations (from the published ``config.json``; what the config does not
fix is listed under ``assumed`` in the configuration file).  Every layer is
ONE sub-block, ``x = x + f_i(RMSNorm_i(x))``, ``f_i`` chosen by letter ``i``
of ``hybrid_override_pattern``; RMSNorm is ``x * rsqrt(mean x^2 + eps) * w``;
a final RMSNorm, then a head ``W_head`` ``[hidden, vocab]`` that is NOT the
embedding.

- ``M``, Mamba-2 (SSD; ``H`` heads of ``P`` channels, ``G`` groups of ``N``
  state channels, ``d_inner = H P``): ``[z | x | B | C | dt] = u W_in``
  (``H P | H P | G N | G N | H`` columns, no bias).  ``[x | B | C]`` goes
  through a depthwise causal convolution of ``conv_kernel`` taps (``out_t =
  sum_j kernel[j] in[t - (L-1) + j] + bias``, zero before the row's start),
  then SiLU.  ``dt_t = softplus(dt_t + dt_bias)`` (one a head and token, no
  clamp), ``A = -exp(A_log)`` (one a head).  A head's state ``S`` ``[P, N]``
  is zero at the row's start, and TOKEN BY TOKEN: ``S_t = exp(dt_t A) S_{t-1}
  + (dt_t x_t) B_t^T``; ``y_t = S_t C_t + D x_t`` (head ``h`` reads group ``h
  // (H / G)``'s ``B`` and ``C``).  Then the gate FIRST, ``y = y * silu(z)``,
  the RMS norm over each group's ``H P / G`` channels times a weight of ``H
  P``, and ``y W_out``.
- ``*``, attention (``Hq`` heads of ``dh`` over ``Hkv`` key-value heads):
  causal softmax of ``q k^T dh ** -0.5``; no bias, NO QK-norm and NO rotary
  embedding.
- ``E``, experts: scores ``sigmoid(x W_r)`` over ``router_outputs`` experts;
  the ``num_experts_per_tok`` largest of ``score + expert_bias``; the chosen
  SCORES divided by their sum (+ 1e-6) times ``routed_scaling_factor``; the
  sum over the chosen experts of ``w_e E_e(x)``, ``E_e(x) = W_2 relu(W_1
  x)^2`` (two matrices, not gated); plus one shared expert of the same form,
  unweighted, on every token.

The chip's share: it holds experts ``first_expert .. first_expert +
n_routed_experts - 1`` of ``router_outputs``, and rows ``0 .. vocab_size - 1``
of the published vocabulary.  The router keeps all its outputs and its
experts a token; what the absent experts would have added is left out, here
as in the program, and that partial result goes on to the next layer.  The
mixers, the router and the shared expert are computed whole: every chip of
the 16 computes them alike on its own tokens.

How it fits beside 10.7 GB of optimizer state: ``jax.checkpoint`` by layer;
the recurrence as a scan over blocks of :data:`TOKEN_BLOCK` tokens whose
inner token-by-token scan is recomputed in the backward pass (a plain scan's
backward would keep every token's state: 8.6 GB a layer), attention a
key-value head at a time and in blocks of queries, the experts as a plain
loop over the experts held with a mask (a ``lax.scan``), and the head and
loss in blocks of positions.

Weights: ``correct.init_params`` draws EVERY leaf zero-mean normal with the
``std`` given here, norm weights included (std 1).  Projections have std
``fan_in ** -0.5``; ``A_log`` and ``dt_bias`` std 1.0, so a token's decay
``exp(dt A)`` spans about 0.01 to 0.99: memory both dies inside a chunk and
lives across many; ``D`` std 1.0 and the convolution's bias std 0.5.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: tokens per block of the recurrence's outer scan
TOKEN_BLOCK = 64
#: queries per block of the reference's attention
QUERY_BLOCK = 1024
#: positions per block of the reference's head and loss
HEAD_BLOCK = 1024

KINDS = {"M": "mamba", "*": "attn", "E": "moe"}


def _dims(cfg: dict) -> dict:
    return {"D": cfg["hidden_size"], "H": cfg["mamba_num_heads"],
            "P": cfg["mamba_head_dim"], "G": cfg["n_groups"],
            "N": cfg["ssm_state_size"], "L": cfg["conv_kernel"],
            "Hq": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "dh": cfg["head_dim"],
            "Fe": cfg["moe_intermediate_size"],
            "Fs": cfg["moe_shared_expert_intermediate_size"],
            "E": cfg["router_outputs"], "Eh": cfg["n_routed_experts"],
            "V": cfg["vocab_size"], "layers": cfg["num_hidden_layers"]}


def layer_kinds(cfg: dict) -> list:
    """``mamba`` / ``attn`` / ``moe`` of every layer, from the pattern."""
    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError(f"{len(pattern)} letters for "
                         f"{cfg['num_hidden_layers']} layers")
    return [KINDS[c] for c in pattern]


def param_shapes(cfg: dict) -> dict:
    d = _dims(cfg)
    D, H, P, G, N = d["D"], d["H"], d["P"], d["G"], d["N"]
    inner, conv = H * P, H * P + 2 * G * N
    shapes = {"_emb.w0": ((d["V"], D), 0.02), "_norm_out.w": ((D,), 1.0),
              "_cost.w": ((D, d["V"]), D ** -0.5)}
    for i, kind in enumerate(layer_kinds(cfg)):
        shapes[f"_norm{i}.w"] = ((D,), 1.0)
        pre = f"_{kind}{i}"
        if kind == "mamba":
            shapes[pre + ".w_in"] = ((D, inner + conv + H), D ** -0.5)
            shapes[pre + ".kernel"] = ((d["L"], conv), d["L"] ** -0.5)
            shapes[pre + ".conv_bias"] = ((conv,), 0.5)
            shapes[pre + ".a_log"] = ((H,), 1.0)
            shapes[pre + ".dt_bias"] = ((H,), 1.0)
            shapes[pre + ".d"] = ((H,), 1.0)
            shapes[pre + ".norm"] = ((inner,), 1.0)
            shapes[pre + ".w_out"] = ((inner, D), inner ** -0.5)
        elif kind == "attn":
            Hq, Hkv, dh = d["Hq"], d["Hkv"], d["dh"]
            shapes[pre + ".wq"] = ((D, Hq * dh), D ** -0.5)
            shapes[pre + ".wk"] = ((D, Hkv * dh), D ** -0.5)
            shapes[pre + ".wv"] = ((D, Hkv * dh), D ** -0.5)
            shapes[pre + ".wo"] = ((Hq * dh, D), (Hq * dh) ** -0.5)
        else:
            shapes[pre + ".router"] = ((D, d["E"]), D ** -0.5)
            shapes[pre + ".expert_bias"] = ((d["E"],), 0.01)
            shapes[pre + ".w1"] = ((d["Eh"], D, d["Fe"]), D ** -0.5)
            shapes[pre + ".w2"] = ((d["Eh"], d["Fe"], D), d["Fe"] ** -0.5)
            shapes[pre + ".shared_w1"] = ((D, d["Fs"]), D ** -0.5)
            shapes[pre + ".shared_w2"] = ((d["Fs"], D), d["Fs"] ** -0.5)
    return shapes


def mm(a, b):
    """Every matrix multiplication of this file.  The lower-precision control
    (benchmark/correct.py) swaps it for one that rounds its operands."""
    return jnp.matmul(a, b)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


# -- the Mamba-2 mixer ------------------------------------------------------

def ssd_recurrence(x, Bm, Cm, dt, A):
    """x ``[B, T, H, P]``, Bm, Cm ``[B, T, H, N]`` (the groups' repeated over
    their heads), dt ``[B, T, H]``, A ``[H]`` -> ``[B, T, H, P]``: the
    recurrence itself, one token at a time."""
    B, T, H, P = x.shape
    block = min(TOKEN_BLOCK, T)
    if T % block:
        raise ValueError(f"a row of {T} tokens is not whole blocks of {block}")

    def token(S, c):
        xt, bt, ct, dtt = c                      # [B, H, .], dt [B, H]
        S = jnp.exp(dtt * A)[..., None, None] * S + mm(
            (dtt[..., None] * xt)[..., :, None], bt[..., None, :])
        return S, mm(S, ct[..., :, None])[..., 0]

    @jax.checkpoint
    def tokens(S, cs):       # recomputed: the backward holds a block's
        return jax.lax.scan(token, S, cs)        # states, not the row's

    def blocks(a):           # [B, T, ...] -> [T / block, block, B, ...]
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((T // block, block) + a.shape[1:])

    S0 = jnp.zeros((B, H, P, Bm.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(tokens, S0, tuple(blocks(a) for a in
                                          (x, Bm, Cm, dt)))
    return jnp.moveaxis(y.reshape((T,) + y.shape[2:]), 0, 1)


def mamba2(cfg, p, pre, u):
    d = _dims(cfg)
    B, T, _ = u.shape
    H, P, G, N, L = d["H"], d["P"], d["G"], d["N"], d["L"]
    inner, gn = H * P, G * N
    zxbcdt = mm(u, p[pre + ".w_in"])
    z = zxbcdt[..., :inner]
    mixed = zxbcdt[..., inner:2 * inner + 2 * gn]
    dt = jax.nn.softplus(zxbcdt[..., 2 * inner + 2 * gn:]
                         + p[pre + ".dt_bias"])
    padded = jnp.pad(mixed, ((0, 0), (L - 1, 0), (0, 0)))
    conv = sum(p[pre + ".kernel"][j] * padded[:, j:j + T] for j in range(L))
    conv = jax.nn.silu(conv + p[pre + ".conv_bias"])
    x = conv[..., :inner].reshape(B, T, H, P)
    Bm = jnp.repeat(conv[..., inner:inner + gn].reshape(B, T, G, N),
                    H // G, axis=2)
    Cm = jnp.repeat(conv[..., inner + gn:].reshape(B, T, G, N), H // G,
                    axis=2)
    y = ssd_recurrence(x, Bm, Cm, dt, -jnp.exp(p[pre + ".a_log"]))
    y = y + p[pre + ".d"][:, None] * x
    y = y.reshape(B, T, inner) * jax.nn.silu(z)          # the gate first
    y = y.reshape(B, T, G, inner // G)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + cfg["layer_norm_epsilon"])
    y = y.reshape(B, T, inner) * p[pre + ".norm"]
    return mm(y, p[pre + ".w_out"])


# -- attention --------------------------------------------------------------

def attention(cfg, p, pre, x):
    d = _dims(cfg)
    B, T, _ = x.shape
    H, Hkv, dh = d["Hq"], d["Hkv"], d["dh"]
    q = mm(x, p[pre + ".wq"]).reshape(B, T, H, dh)
    k = mm(x, p[pre + ".wk"]).reshape(B, T, Hkv, dh)
    v = mm(x, p[pre + ".wv"]).reshape(B, T, Hkv, dh)
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
    o = o.transpose(1, 3, 0, 2, 4).reshape(B, T, H * dh)
    return mm(o, p[pre + ".wo"])


# -- the expert layer -------------------------------------------------------

def relu2_mlp(x, w1, w2):
    return mm(jnp.square(jax.nn.relu(mm(x, w1))), w2)


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
    def one(y, expert):
        e, w1, w2 = expert
        gate = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        return y + gate[..., None] * relu2_mlp(x, w1, w2), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.arange(held), p[pre + ".w1"][:held], p[pre + ".w2"][:held]))
    return y


def shared_expert(p, pre, x):
    return relu2_mlp(x, p[pre + ".shared_w1"], p[pre + ".shared_w2"])


def expert_layer(cfg, p, pre, x):
    return routed_experts(cfg, p, pre, x) + shared_expert(p, pre, x)


SUBLAYERS = {"mamba": mamba2, "attn": attention, "moe": expert_layer}


def layer(cfg, p, i, x):
    kind = layer_kinds(cfg)[i]
    normed = rms_norm(x, p[f"_norm{i}.w"], cfg["layer_norm_epsilon"])
    return x + SUBLAYERS[kind](cfg, p, f"_{kind}{i}", normed)


def hidden(cfg: dict, p: dict, ids):
    x = p["_emb.w0"][ids]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(lambda p, x, i=i: layer(cfg, p, i, x))(p, x)
    return rms_norm(x, p["_norm_out.w"], cfg["layer_norm_epsilon"])


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
    the state-space scan as the RECURRENCE needs them (two products of ``P x
    N`` a token and head: the state's update and its reading; a chunked form
    computes more), causal attention at half the square, the routed experts
    at the expected ``num_experts_per_tok * n_routed_experts /
    router_outputs`` assignments a token, an expert as its TWO products."""
    d = _dims(cfg)
    D, H, P, G, N = d["D"], d["H"], d["P"], d["G"], d["N"]
    inner, conv = H * P, H * P + 2 * G * N
    kinds = layer_kinds(cfg)
    n_m, n_a, n_e = (kinds.count(k) for k in ("mamba", "attn", "moe"))
    held = cfg["num_experts_per_tok"] * d["Eh"] / d["E"]
    Hq, Hkv, dh = d["Hq"], d["Hkv"], d["dh"]
    return {
        "mamba_proj": n_m * 2 * (D * (inner + conv + H) + d["L"] * conv
                                 + inner * D),
        "ssd_scan": n_m * H * 2 * 2 * P * N,
        "attn_proj": n_a * 2 * (D * Hq * dh + 2 * D * Hkv * dh
                                + Hq * dh * D),
        "attn_core": n_a * seq_len * Hq * 2 * dh,
        "router": n_e * 2 * D * d["E"],
        "experts": n_e * held * 4 * D * d["Fe"],
        "shared_expert": n_e * 4 * D * d["Fs"],
        "head": 2 * D * d["V"],
    }


def step_flops(cfg: dict, traffic: dict) -> float:
    """Operations one training step needs: 3 x the forward pass; what the
    program recomputes is not counted."""
    per_token = sum(forward_flops_per_token(cfg, traffic["seq_len"]).values())
    return 3.0 * per_token * traffic["batch"] * traffic["seq_len"]
