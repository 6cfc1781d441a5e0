"""Plain reference of Ouro-2.6B (``model_type`` ``ouro``; "Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741) in float32
``jax.numpy``: a dense decoder-only stack whose layers run ``total_ut_steps``
times over ONE set of weights, trained on the expected loss over the exits.
It imports nothing of the program; parameter names are the program's so that
one set of seeded weights serves both.

The equations (widths, the number of passes, theta, eps and the untied head
from the published ``config.json``; what the config does not fix is listed
under ``assumed`` in the configuration file).  ``RMS(x; w) = x rsqrt(mean
x^2 + eps) w``.  No bias in any projection.

- Layer ``l``, a sandwich (a norm before AND after each sub-block): ``a =
  Attn_l(RMS(x; n1_l))``, ``h = x + RMS(a; n2_l)``; ``u = RMS(h; n3_l)``,
  ``m = W2_l (silu(u W1_l) * (u W3_l))``, ``y = h + RMS(m; n4_l)``.
- ``Attn``: ``q, k, v = u Wq, u Wk, u Wv`` as ``H`` heads of ``dh`` (as many
  key-value heads as query heads at the published sizes; key-value head ``j``
  serves query heads ``j G .. j G + G - 1``); q and k turned by the rotary
  embedding over the whole head (``theta ** (-2j / dh)``, half-rotation form,
  positions 0..T-1); causal softmax at scale ``dh ** -0.5``; times ``Wo``.
  No head norm, no gate.
- The loop, the SAME layers, final norm, head and gate at every pass: ``x^0 =
  E[ids]``; for ``t = 1..R``: ``z = x^(t-1)`` through layers ``1..L``, ``x^t
  = RMS(z; n_out)``, which is an exit AND pass ``t + 1``'s input; ``logits^t
  = x^t W_head`` (untied), ``g^t = x^t w_g + b_g`` (one number a token).
- A token's exit distribution: ``lam_t = sigmoid(g^t)``, ``S_0 = 1``, ``S_t
  = S_(t-1) (1 - lam_t)``; ``p_t = lam_t S_(t-1)`` for ``t < R`` and ``p_R =
  S_(R-1)``, the mass that is left (``g^R`` moves nothing).
- ``cost = sum over real tokens of [sum_t p_t CE_t - beta H(p)] / tokens``,
  ``CE_t = logsumexp(logits^t) - logits^t[next token]``, ``H(p) = -sum_t p_t
  log p_t`` (``log p_t`` taken as 0 where ``p_t`` is 0 in float32).

Nothing is sliced: a dense model is trained data-parallel, every chip holds
every head, every hidden unit and the whole vocabulary; the cut is depth
alone (the configuration's ``deployment``).

How it fits beside the runner's ``p``, ``m``, ``v`` and one gradient (8.15
GB at the cell's sizes): a ``lax.scan`` over the passes whose body is a
Python loop over the layers with ``jax.checkpoint`` a (pass, layer), 24
inputs of ``[T, D]`` held; attention a block of ``QUERY_BLOCK`` queries at a
time against every position under the causal mask (a ``lax.map``, each block
recomputed); the head, the cross-entropy and the gate a block of
``HEAD_BLOCK`` positions of one exit at a time (a ``lax.map``, recomputed),
so that a block's logits are held and not an exit's.

Weights: ``correct.init_params`` draws EVERY leaf zero-mean normal with the
``std`` given here, norm weights included (std 1; see the LFM2 reference for
why).  Projections, the head and the gate's vector have std ``fan_in **
-0.5``, the gate's bias 0.5, the EMBEDDING 1 (Keye-VL-2.0's file says why a
token's vector must not drown in the norm's epsilon; here there is no router
to flood, and 1 keeps the sibling cells' convention).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: queries per block of the reference's attention
QUERY_BLOCK = 512
#: positions per block of the reference's head, cross-entropy and gate
HEAD_BLOCK = 1024

NORMS = ("norm_op", "post_op", "norm_ffn", "post_ffn")


def _dims(cfg: dict) -> dict:
    return {"D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "dh": cfg["head_dim"],
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "layers": cfg["num_hidden_layers"], "R": cfg["total_ut_steps"]}


def param_shapes(cfg: dict) -> dict:
    """leaf -> (shape, std): every weight ONCE, whatever the passes."""
    d = _dims(cfg)
    D, H, Hkv, dh, F = d["D"], d["H"], d["Hkv"], d["dh"], d["F"]
    shapes = {"_emb.w0": ((d["V"], D), 1.0), "_norm_out.w": ((D,), 1.0),
              "_cost.w": ((D, d["V"]), D ** -0.5),
              "_exit_gate.w": ((D,), D ** -0.5),
              "_exit_gate.b": ((1,), 0.5)}
    for i in range(d["layers"]):
        for n in NORMS:
            shapes[f"_{n}{i}.w"] = ((D,), 1.0)
        shapes[f"_attn{i}.wq"] = ((D, H * dh), D ** -0.5)
        shapes[f"_attn{i}.wk"] = ((D, Hkv * dh), D ** -0.5)
        shapes[f"_attn{i}.wv"] = ((D, Hkv * dh), D ** -0.5)
        shapes[f"_attn{i}.wo"] = ((H * dh, D), (H * dh) ** -0.5)
        shapes[f"_mlp{i}.w1"] = ((D, F), D ** -0.5)
        shapes[f"_mlp{i}.w3"] = ((D, F), D ** -0.5)
        shapes[f"_mlp{i}.w2"] = ((F, D), F ** -0.5)
    return shapes


def mm(a, b):
    """Every matrix multiplication of this file.  The lower-precision control
    (benchmark/correct.py) swaps it for one that rounds its operands."""
    return jnp.matmul(a, b)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def rotary(x, theta: float):
    """x ``[B, T, heads, dh]``: the half-rotation form over the whole head,
    positions 0..T-1."""
    T, dh = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(cfg, p, pre, x):
    """``Attn(x)``: every head's causal softmax, a block of queries at a
    time against every position."""
    d = _dims(cfg)
    B, T, _ = x.shape
    H, Hkv, dh = d["H"], d["Hkv"], d["dh"]
    q = rotary(mm(x, p[pre + ".wq"]).reshape(B, T, H, dh), cfg["rope_theta"])
    k = rotary(mm(x, p[pre + ".wk"]).reshape(B, T, Hkv, dh),
               cfg["rope_theta"])
    v = mm(x, p[pre + ".wv"]).reshape(B, T, Hkv, dh)
    q = q.transpose(0, 2, 1, 3)                               # [B, H, T, dh]
    k = jnp.repeat(k.transpose(0, 2, 1, 3), H // Hkv, axis=1)
    v = jnp.repeat(v.transpose(0, 2, 1, 3), H // Hkv, axis=1)
    block = min(QUERY_BLOCK, T)
    if T % block:
        raise ValueError(f"a row of {T} is not whole blocks of {block}")

    @jax.checkpoint
    def rows(lo):            # one block of queries, recomputed backward
        qb = jax.lax.dynamic_slice_in_dim(q, lo, block, 2)
        seen = jnp.arange(T)[None, :] <= lo + jnp.arange(block)[:, None]
        s = mm(qb, k.swapaxes(-1, -2)) * dh ** -0.5
        return mm(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)

    o = jax.lax.map(rows, jnp.arange(0, T, block))    # [blocks,B,H,block,dh]
    o = o.transpose(1, 0, 3, 2, 4).reshape(B, T, H * dh)
    return mm(o, p[pre + ".wo"])


def gated_mlp(x, w1, w3, w2):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def layer(cfg, p, i, x):
    eps = cfg["rms_norm_eps"]
    a = attention(cfg, p, f"_attn{i}", rms_norm(x, p[f"_norm_op{i}.w"], eps))
    h = x + rms_norm(a, p[f"_post_op{i}.w"], eps)
    m = gated_mlp(rms_norm(h, p[f"_norm_ffn{i}.w"], eps), p[f"_mlp{i}.w1"],
                  p[f"_mlp{i}.w3"], p[f"_mlp{i}.w2"])
    return h + rms_norm(m, p[f"_post_ffn{i}.w"], eps)


def exits(cfg: dict, p: dict, ids):
    """The ``R`` exits ``x^t``, ``[R, B, T, D]``: the same layers and final
    norm every pass, one recomputation block a (pass, layer).  The passes
    are a ``lax.scan`` whose body is ONE pass (a Python loop over its
    layers): the same weights every iteration is what the model is, and the
    compiled reference is a quarter of the unrolled one (which, at 227 MB,
    no compile cache would keep)."""
    def one_pass(x, _):
        for i in range(cfg["num_hidden_layers"]):
            x = jax.checkpoint(lambda p, x, i=i: layer(cfg, p, i, x))(p, x)
        x = rms_norm(x, p["_norm_out.w"], cfg["rms_norm_eps"])
        return x, x

    _, out = jax.lax.scan(one_pass, p["_emb.w0"][ids], None,
                          length=cfg["total_ut_steps"])
    return out


def exit_readout(p: dict, xs, targets):
    """``(CE [R, B, T], g [R, B, T])`` of the exits ``xs`` ``[R, B, T, D]``:
    the head's cross-entropy and the gate's logit of every position, a block
    of ``HEAD_BLOCK`` positions of one exit at a time (a ``lax.map``, each
    block recomputed in the backward pass)."""
    @jax.checkpoint
    def block(xb, tb):
        logits = mm(xb, p["_cost.w"])
        ce = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, tb[..., None], -1)[..., 0]
        return ce, mm(xb, p["_exit_gate.w"][:, None])[..., 0] \
            + p["_exit_gate.b"]

    R, B, T, D = xs.shape
    size = HEAD_BLOCK if T % HEAD_BLOCK == 0 else T
    n = T // size
    xb = xs.reshape(R, B, n, size, D).transpose(0, 2, 1, 3, 4)
    tb = jnp.broadcast_to(targets.reshape(B, n, size).transpose(1, 0, 2),
                          (R, n, B, size))
    ce, g = jax.lax.map(lambda a: block(*a), (
        xb.reshape(R * n, B, size, D), tb.reshape(R * n, B, size)))
    whole = lambda a: a.reshape(R, n, B, size).transpose(  # noqa: E731
        0, 2, 1, 3).reshape(R, B, T)
    return whole(ce), whole(g)


def exit_distribution(gates):
    """``[p_1 .. p_R]`` from the gate logits ``[g^1 .. g^(R-1)]``, at least
    one."""
    stay, p = 1.0, []
    for g in gates:
        lam = jax.nn.sigmoid(g)
        p.append(lam * stay)
        stay = stay * (1.0 - lam)
    return p + [stay]


def entropy(p):
    """``H(p)`` a token; an exit of no mass in float32 adds nothing."""
    return -sum(jnp.where(q > 0, q * jnp.log(jnp.where(q > 0, q, 1.0)), 0.0)
                for q in p)


def exit_terms(cfg: dict, p: dict, batch: dict):
    """``(CE_t [R, B, T], p_t [R, B, T], mask [B, T])``; the last exit's
    gate logit is made with the others and read by nothing."""
    ids, lengths = batch["tokens"]
    targets, _ = batch["next_tokens"]
    mask = (jnp.arange(ids.shape[1])[None, :] < lengths[:, None])
    ce, g = exit_readout(p, exits(cfg, p, ids), targets)
    dist = exit_distribution(list(g[:-1])) if len(g) > 1 \
        else [jnp.ones_like(ce[0])]
    return ce, jnp.stack(dist), mask.astype(jnp.float32)


def loss_sum(cfg: dict, p: dict, batch: dict):
    """(the sum over the real target tokens of ``sum_t p_t CE_t - beta
    H(p)``, the number of target tokens)."""
    ce, dist, mask = exit_terms(cfg, p, batch)
    per_tok = jnp.sum(dist * ce, 0) - cfg["exit_beta"] * entropy(list(dist))
    return jnp.sum(per_tok * mask), mask.sum()


# -- what only this configuration knows about its traffic -------------------

def batch(cfg: dict, traffic: dict, gen) -> dict:
    """One feed: rows of ``seq_len + 1`` ids drawn uniformly over the whole
    vocabulary (one document a row, the row full); ``tokens`` is all but the
    last id, ``next_tokens`` all but the first.  ``gen`` is
    benchmark/traffic.py's generator."""
    B, T = traffic["batch"], traffic["seq_len"]
    ids = gen.rng.integers(0, cfg["vocab_size"], (B, T + 1), dtype=np.int32)
    lengths = gen.lengths(traffic["lengths"], B, T)
    return {"tokens": (ids[:, :-1], lengths),
            "next_tokens": (ids[:, 1:], lengths)}


def real_tokens(feed: dict) -> int:
    """What a step counts as its tokens: the target tokens."""
    return int(feed["next_tokens"][1].sum())


def causal_pairs(seq_len: int) -> int:
    """(query, position) pairs at or under the diagonal of one row."""
    return seq_len * (seq_len + 1) // 2


def forward_flops_per_row(cfg: dict, seq_len: int) -> dict:
    """Operations of one row's forward pass by part, ALL ``R`` passes, from
    the shapes alone, 2 a multiply-add: the mathematics, not what a kernel
    visits.  A weight that is used ``R`` times is counted ``R`` times: the
    arithmetic is a pass's, whoever owns the leaf."""
    d = _dims(cfg)
    D, H, Hkv, dh, T, R = d["D"], d["H"], d["Hkv"], d["dh"], seq_len, d["R"]
    L = d["layers"]
    return {
        "attn_proj": R * L * T * 2 * (2 * D * H * dh + 2 * D * Hkv * dh),
        "attn_core": R * L * causal_pairs(T) * 2 * H * 2 * dh,
        "mlp": R * L * T * 6 * D * d["F"],
        "head": R * T * 2 * D * d["V"],
        "gate": (R - 1) * T * 2 * D,
    }


def step_flops(cfg: dict, traffic: dict) -> float:
    """Operations one training step needs: 3 x the forward pass (what the
    program recomputes, and the pairs a kernel visits and masks, are not
    counted)."""
    parts = forward_flops_per_row(cfg, traffic["seq_len"])
    return traffic["batch"] * 3.0 * sum(parts.values())
