"""Learned sparse attention: a light indexer scores every past position of
every query, the ``topk`` best are kept, causal softmax attention runs over
those alone, and the indexer learns from the attention it steers (the
lightning indexer of DeepSeek Sparse Attention).

With ``qI`` ``[B, T, J, d]``, ``kI`` ``[B, T, d]`` (ONE indexer key head)
and ``w`` ``[B, T, J]``:

- scores ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``;
- selection ``S_t``: the positions of the ``min(t + 1, topk)`` largest
  ``I[t, s]``, among equal scores the lower position first
  (``lax.top_k``'s rule); the same for every head;
- attention ``o_h[t] = sum_{s in S_t} softmax_{S_t}(q_h[t] . k[s] scale)
  v[s]``; a position not kept adds exactly nothing, forward and backward;
- the indexer's loss ``L_I = sum_t KL(p[t] || softmax_{S_t} I[t])`` with the
  target ``p[t, s] = (1 / H) sum_h a_h[t, s]`` taken as a constant.

Gradients (one ``jax.custom_vjp``): ``q``, ``k``, ``v`` get the attention's
alone (the selection is no function of them that a gradient sees, the
target is a constant), ``qI``, ``kI``, ``w`` get ``L_I``'s alone through
``dL_I / dI[t, s] = softmax_{S_t}(I)[t, s] - p[t, s]`` on ``S_t``.  ``L_I``'s
gradient does not depend on what comes after it but by a factor, so the
forward pass makes it beside the loss's value and the backward pass scales
it.

Two paths behind :func:`sparse_attention`, as ``causal_attention`` has
them.  On the TPU at tile-aligned shapes (:func:`sparse_kernel_blocks`) the
kernels of ops/pallas_kernels.py: ``indexer_scores``, ``topk_select`` (the
threshold of a row found by counting, no sort), the two flash kernels under
the selection (``keep=``) and ``indexer_loss``; of ``[T, T]`` arrays only
the scores (float32) and the selection (int8) cross HBM: the scores are
written once and read twice (by ``topk_select`` and, a tile at a time, by
``indexer_loss``, which does not make them again), and the selection is
kept across a recomputation block.  Elsewhere a loop over blocks of
``ATTN_XLA_BLOCK`` queries in XLA with the same mathematics; at ``T <=
topk`` its attention is ``causal_attention``'s bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from paddle_tpu.ops import decoder_block as DB
from paddle_tpu.ops.numerics import acc_dtype, dot_dtype, mxu_cast

__all__ = ["sparse_attention", "sparse_kernel_blocks", "indexer_scores",
           "select_topk", "block_target"]


def sparse_kernel_blocks(T: int, dh: int, H: int, Hkv: int, d_index: int):
    """The kernels' gate: the tiles' side, or ``None`` for the XLA path.
    Needs what the flash kernels need (``attention_kernel_blocks``), an
    indexer head of whole 64s and a row of whole ``topk_select`` blocks."""
    from paddle_tpu.ops.pallas_kernels import TOPK_SELECT_ROWS

    blocks = DB.attention_kernel_blocks(T, dh, H, Hkv)
    if blocks is None or d_index % 64 or T % TOPK_SELECT_ROWS:
        return None
    return blocks[0]


# ---------------------------------------------------------------------------
# the XLA path: blocks of queries
# ---------------------------------------------------------------------------

def indexer_scores(qI, kI, w, lo: int, hi: int):
    """I ``[B, hi - lo, hi]`` float32 of queries ``lo..hi-1`` against
    positions ``0..hi-1``, ``-inf`` where ``s > t``."""
    f32 = acc_dtype()
    pre = jnp.einsum("bqjd,bkd->bjqk", qI[:, lo:hi], kI[:, :hi],
                     preferred_element_type=f32)
    scores = jnp.einsum("bqj,bjqk->bqk", w[:, lo:hi].astype(f32),
                        jax.nn.relu(pre))
    rows = lo + jnp.arange(hi - lo)[:, None]
    return jnp.where(jnp.arange(hi)[None, :] <= rows, scores, -jnp.inf)


def select_topk(scores, lo: int, topk: int):
    """bool ``[.., rows, cols]``: row ``r`` (query ``lo + r``) keeps the
    ``min(lo + r + 1, topk)`` largest of its scores (``-inf`` in the
    future), among equal scores the lower position first.  By the k-th
    largest value and a count of what lies above it: no index is moved."""
    rows, cols = scores.shape[-2:]
    causal = jnp.arange(cols)[None, :] <= lo + jnp.arange(rows)[:, None]
    if cols <= topk:
        return jnp.broadcast_to(causal, scores.shape)
    tau = jax.lax.top_k(scores, topk)[0][..., -1:]
    above, equal = scores > tau, scores == tau
    need = topk - jnp.sum(above, -1, keepdims=True)
    return (above | (equal & (jnp.cumsum(equal, -1) <= need))) & causal


def block_target(q, k, lse, keep, lo: int, hi: int, scale: float):
    """The indexer's target for queries ``lo..hi-1``: the mean over the
    heads of their probabilities (by the attention's ``lse``) on the kept
    positions, 0 elsewhere; ``[B, hi - lo, hi]`` float32, a row sums to 1."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q[:, lo:hi], k[:, :hi],
                   preferred_element_type=acc_dtype()) * scale
    p = jnp.sum(jnp.exp(s - lse[..., lo:hi, None]), (1, 2))
    return jnp.where(keep, p / (q.shape[2] * q.shape[3]), 0.0)


def _xla_forward(q, k, v, qI, kI, w, real, scale, topk, block):
    """q ``[B, T, Hkv, G, dh]``, real ``[B, T]``; -> (out float32, lse,
    keep: one bool ``[B, rows, hi]`` a block, kl ``[B]``, the indexer's
    gradients)."""
    T = q.shape[1]
    f32 = acc_dtype()
    keep = []
    for lo in range(0, T, block):
        hi = min(T, lo + block)
        with jax.named_scope("indexer"):
            scores = indexer_scores(qI, kI, w, lo, hi)
        with jax.named_scope("topk_select"):
            keep.append(select_topk(scores, lo, topk))
    with jax.named_scope("attn_core"):
        out, lse = DB._xla_fwd(q, k, v, scale, block, keep=keep)
    kl = jnp.zeros((q.shape[0],), f32)
    grads = tuple(jnp.zeros(a.shape, f32) for a in (qI, kI, w))
    with jax.named_scope("indexer_loss"):
        for i, lo in enumerate(range(0, T, block)):
            hi = min(T, lo + block)
            p = block_target(q, k, lse, keep[i], lo, hi, scale)
            scores, pull = jax.vjp(
                lambda a, b, c: indexer_scores(a, b, c, lo, hi), qI, kI, w)
            kept = jnp.where(keep[i], scores, -jnp.inf)
            logq = scores - jax.nn.logsumexp(kept, -1, keepdims=True)
            rows = real[:, lo:hi, None]
            kl = kl + jnp.sum(rows * jnp.where(p > 0.0, p * (jnp.log(
                jnp.where(p > 0.0, p, 1.0)) - logq), 0.0), (1, 2))
            d_scores = rows * (jnp.where(keep[i], jnp.exp(logq), 0.0) - p)
            grads = tuple(g + d.astype(f32)
                          for g, d in zip(grads, pull(d_scores)))
    return out, lse, keep, kl, grads


# ---------------------------------------------------------------------------
# one function, two paths
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _sparse_attention(q, k, v, qI, kI, w, real, scale, topk):
    return _sparse_fwd(q, k, v, qI, kI, w, real, scale, topk)[0]


def _sparse_fwd(q, k, v, qI, kI, w, real, scale, topk):
    B, T, H, dh = q.shape
    Hkv, dv = k.shape[2], v.shape[3]
    qc, kc, vc, qIc, kIc = mxu_cast(q, k, v, qI, kI)
    wf = w.astype(acc_dtype())
    like = tuple(jnp.zeros((0,), a.dtype) for a in (q, k, v, qI, kI, w))
    block = sparse_kernel_blocks(T, dh, H, Hkv, qI.shape[3])
    if block is not None:
        from paddle_tpu.ops import pallas_kernels as PK

        qh, kh, vh, qIh = (jnp.swapaxes(a, 1, 2) for a in (qc, kc, vc, qIc))
        with jax.named_scope("indexer"):
            scores = PK.indexer_scores_pallas(qIh, kIc, wf, block=block)
        with jax.named_scope("topk_select"):
            keep, lse_i = PK.topk_select_pallas(
                scores, topk=topk, rows=PK.TOPK_SELECT_ROWS)
        with jax.named_scope("attn_core"):
            oh, lse = PK.flash_attn_fwd_pallas(
                qh, kh, vh, scale=scale, block_q=block, block_k=block,
                keep=keep)
        with jax.named_scope("indexer_loss"):
            kl_rows, dqI, dkI, dw = PK.indexer_loss_pallas(
                qh, kh, lse, qIh, kIc, wf, lse_i, real[..., None], keep,
                scores, scale=scale, block=block)
            kl = jnp.sum(kl_rows, (1, 2))
            dqI = jnp.swapaxes(dqI, 1, 2)
        kept = jnp.sum(jnp.where(real[..., None] > 0, keep, 0), (1, 2),
                       dtype=jnp.int32)
        # kept across a recomputation block: the backward's second forward
        # recomputes the projections, not the indexer, the selection, the
        # attention or the indexer's loss
        oh, lse, keep, kl, kept, dqI, dkI, dw = (
            checkpoint_name(a, "remat_keep")
            for a in (oh, lse, keep, kl, kept, dqI, dkI, dw))
        out = jnp.swapaxes(oh, 1, 2).astype(dot_dtype())
        return (out, kl, kept), (qh, kh, vh, oh, lse, keep, (dqI, dkI, dw),
                                 like, real)
    qg = qc.reshape(B, T, Hkv, H // Hkv, dh)
    out, lse, keep, kl, grads = _xla_forward(
        qg, kc, vc, qIc, kIc, wf, real, scale, topk, DB.ATTN_XLA_BLOCK)
    kept = sum(jnp.sum(jnp.where(real[:, lo:lo + m.shape[1], None] > 0, m, 0),
                       (1, 2), dtype=jnp.int32)
               for lo, m in zip(range(0, T, DB.ATTN_XLA_BLOCK), keep))
    return ((out.reshape(B, T, H, dv).astype(dot_dtype()), kl, kept),
            (qg, kc, vc, out, lse, keep, grads, like, real))


def _sparse_bwd(scale, topk, res, cts):
    q, k, v, out, lse, keep, grads, like, real = res
    d_out, d_kl = cts[0], cts[1]
    if out.ndim == 4:       # the kernels' heads-major residuals
        from paddle_tpu.ops.pallas_kernels import flash_attn_bwd_pallas

        block = sparse_kernel_blocks(q.shape[2], q.shape[3], q.shape[1],
                                     k.shape[1], grads[0].shape[3])
        do = jnp.swapaxes(d_out, 1, 2).astype(q.dtype)
        with jax.named_scope("attn_core"):
            dq, dk, dv = flash_attn_bwd_pallas(
                q, k, v, out, lse, do, scale=scale, block_q=block,
                block_k=block, keep=keep)
        dq, dk, dv = (jnp.swapaxes(a, 1, 2) for a in (dq, dk, dv))
    else:
        B, T, Hkv, G, dh = q.shape
        with jax.named_scope("attn_core"):
            dq, dk, dv = DB._xla_bwd(
                q, k, v, out, lse, d_out.reshape(B, T, Hkv, G, v.shape[3]),
                scale, DB.ATTN_XLA_BLOCK, keep=keep)
        dq = dq.reshape(B, T, Hkv * G, dh)
    with jax.named_scope("indexer_loss"):
        weight = d_kl.astype(acc_dtype())
        d_index = tuple(g * weight.reshape((-1,) + (1,) * (g.ndim - 1))
                        for g in grads)
    return (*(a.astype(z.dtype)
              for a, z in zip((dq, dk, dv, *d_index), like)),
            jnp.zeros_like(real))


_sparse_attention.defvjp(_sparse_fwd, _sparse_bwd)


def sparse_attention(q, k, v, qI, kI, w, *, scale: float, topk: int,
                     real=None):
    """Causal grouped-query attention over the positions an indexer keeps.
    q ``[B, T, H, dh]``, k ``[B, T, Hkv, dh]``, v ``[B, T, Hkv, dv]`` as
    ``causal_attention`` takes them; qI ``[B, T, J, d]``, kI ``[B, T, d]``,
    w ``[B, T, J]``: the indexer's queries, its one key head and its heads'
    weights; real ``[B, T]``: 1 at a real query, 0 at a padded one (all real
    by default), whose pairs are not counted and which adds nothing to
    ``L_I``.  -> (out ``[B, T, H, dv]``; ``L_I`` ``[B]`` float32, a row's
    sum over its queries of ``KL(mean of the heads' probabilities ||
    softmax of the kept scores)``; pairs kept ``[B]`` int32).  The
    indexer's products take their operands in the compute dtype (bf16 under
    the default policy), float32 sums; ``w``, the scores and every
    statistic are float32."""
    real = (jnp.ones(q.shape[:2], acc_dtype()) if real is None
            else real.astype(acc_dtype()))
    return _sparse_attention(q, k, v, qI, kI, w, real, float(scale),
                             int(topk))
