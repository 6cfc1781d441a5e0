"""Cost/loss ops — analog of the reference's cost layers and CE kernels.

Reference surface: hl_matrix cross-entropy kernels
(paddle/cuda/src/hl_cuda_matrix.cu: crossEntropy/crossEntropyBp) and the cost
layer family (paddle/gserver/layers/CostLayer.cpp: multi-class CE, soft CE,
huber, MSE/sum-of-squares, smooth-l1, rank cost, multi-binary-label CE;
LambdaCost.cpp).  TPU-first: all are fused log-softmax formulations — never
materialize probabilities then log() (numerically unstable, and XLA fuses the
subtraction into the softmax reduction).

Sequence-aware variants take a mask [B, T]; padded positions contribute zero
loss and the mean is taken over *real* tokens, matching the reference's
flat-sequence costs (no padding there by construction).
"""

from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "cross_entropy",
    "soft_cross_entropy",
    "binary_cross_entropy",
    "multi_binary_label_cross_entropy",
    "mse",
    "huber",
    "smooth_l1",
    "rank_cost",
    "token_count",
    "token_mean_over_shards",
    "masked_token_mean",
    "sequence_cross_entropy",
]


def _f32(x):
    """Losses and their softmax/logsumexp statistics run in f32 — the
    ``--amp`` allowlist (bf16 logsumexp loses ~3 decimal digits exactly
    where training signal lives); a no-op for f32 inputs."""
    return x.astype(jnp.float32) if jnp.issubdtype(x.dtype, jnp.floating) \
        else x


def cross_entropy(logits, labels, *, axis=-1):
    """Multi-class CE from logits and integer labels; per-example losses."""
    logp = jax.nn.log_softmax(_f32(logits), axis=axis)
    lab = jnp.expand_dims(labels.astype(jnp.int32), axis)
    nll = -jnp.take_along_axis(logp, lab, axis=axis)
    return jnp.squeeze(nll, axis)


def soft_cross_entropy(logits, target_probs, *, axis=-1):
    logp = jax.nn.log_softmax(_f32(logits), axis=axis)
    return -jnp.sum(_f32(target_probs) * logp, axis=axis)


def binary_cross_entropy(logits, labels):
    # stable BCE-with-logits
    logits, labels = _f32(logits), _f32(labels)
    z = jax.nn.log_sigmoid(logits)
    zneg = jax.nn.log_sigmoid(-logits)
    return -(labels * z + (1.0 - labels) * zneg)


def multi_binary_label_cross_entropy(logits, label_matrix):
    """Per-class independent BCE summed over classes (reference
    MultiBinaryLabelCrossEntropy)."""
    return jnp.sum(binary_cross_entropy(logits, label_matrix), axis=-1)


def mse(pred, target):
    return 0.5 * jnp.sum(jnp.square(_f32(pred) - _f32(target)), axis=-1)


def huber(pred, target, delta=1.0):
    d = _f32(pred) - _f32(target)
    a = jnp.abs(d)
    quad = 0.5 * jnp.square(d)
    lin = delta * (a - 0.5 * delta)
    return jnp.sum(jnp.where(a <= delta, quad, lin), axis=-1)


def smooth_l1(pred, target):
    return huber(pred, target, delta=1.0)


def rank_cost(score_left, score_right, label, weight=None):
    """Pairwise rank cost (reference RankingCost): -o*log(s)-(1-o)*log(1-s)
    with s = sigmoid(left-right), o = label in [0,1]."""
    d = score_left - score_right
    cost = binary_cross_entropy(d, label)
    if weight is not None:
        cost = cost * weight
    return cost


_shard_trace = threading.local()


@contextlib.contextmanager
def token_mean_over_shards(axes):
    """Entered by a data-parallel ``shard_map`` body (``parallel/api.py``
    ``data_parallel_body``) around the trace of its loss and gradient: the
    rows are split over the mesh axes ``axes``, and :func:`token_count`
    then counts over all of them.  Acts at trace time only, like
    ``ops/pallas_kernels.xla_paths_only``."""
    held = getattr(_shard_trace, "axes", None)
    _shard_trace.axes = axes
    try:
        yield
    finally:
        _shard_trace.axes = held


def token_count(mask):
    """What a mean over real tokens divides by, forward and in the
    hand-written backwards: ``max(sum(mask), 1)``.

    Inside a data-parallel body (:func:`token_mean_over_shards`) it is the
    MEAN count over the shards, ``max(psum(sum(mask)), 1) / n``: the mean
    over the ``n`` shards of ``shard_total / token_count`` is then the token
    mean of the global batch, ragged lengths and all, and not a mean of
    per-shard means.  The count depends on the mask alone, so no gradient
    passes through the collective."""
    count = jnp.sum(mask)
    axes = getattr(_shard_trace, "axes", None)
    if axes is None:
        return jnp.maximum(count, 1.0)
    return jnp.maximum(lax.psum(count, axes), 1.0) / lax.axis_size(axes)


def masked_token_mean(per_token, mask):
    """Mean over real (mask>0) positions — the sequence-cost reduction."""
    mask = mask.astype(per_token.dtype)
    total = jnp.sum(per_token * mask)
    return total / token_count(mask)


def sequence_cross_entropy(logits, labels, mask):
    """Token-level CE over a padded [B, T, V] batch, averaged over real tokens."""
    per_tok = cross_entropy(logits, labels)
    return masked_token_mean(per_tok, mask)


def _readout_logits(states, w, b):
    from jax import lax

    from paddle_tpu.ops.numerics import mxu_cast

    sc, wc = mxu_cast(states, w)
    logits = lax.dot_general(sc, wc, (((sc.ndim - 1,), (0,)), ((), ())))
    return logits + b.astype(logits.dtype)             # [B, T, V] compute dtype


# One-pass Pallas logsumexp for the readout: A/B-measured and LOST on v5e
# at the WMT14 headline shape (33.4 vs 22.5 ms/step, B384 T32 V30k,
# row_tile 64): the kernel's sequential row-tile grid serializes what
# XLA's fused two-pass reduction overlaps with the readout matmul.  The
# kernel + custom-VJP path is kept (with its interpret-mode equivalence
# test) as a recorded losing A/B — this switch stays off.
_USE_PALLAS_LSE_READOUT = False


@jax.custom_vjp
def _ce_readout_fused(states, w, b, labels, mask):
    """Pallas-lse variant: identical math, logits read once for the
    softmax statistics instead of twice (max pass + exp-sum pass)."""
    loss, _ = _ce_readout_fwd(states, w, b, labels, mask)
    return loss


def _ce_readout_fwd(states, w, b, labels, mask):
    import math

    from paddle_tpu.ops.pallas_kernels import logsumexp_rows_pallas

    B, T, _ = states.shape
    logits = _readout_logits(states, w, b)
    V = logits.shape[-1]
    # the kernel requires N % row_tile == 0; gcd keeps the recorded-A/B
    # path runnable at ANY B*T (ADVICE r4: row_tile=64 traced-failed when
    # B*T wasn't a multiple of 64)
    rt = math.gcd(B * T, 64)
    if rt < 8:
        # ADVICE r5: a row tile below the (8, 128) sublane makes the Pallas
        # grid as long as B*T with sublane-unaligned blocks — an untested
        # Mosaic corner that is at best very slow.  Use the XLA reduction
        # (identical statistics) instead of shrinking the tile.
        lf32 = logits.astype(jnp.float32)
        m = jnp.max(lf32, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(lf32 - m[..., None]), axis=-1))
    else:
        lse = logsumexp_rows_pallas(logits.reshape(B * T, V),
                                    row_tile=rt).reshape(B, T)
    lab = jnp.expand_dims(labels.astype(jnp.int32), -1)
    tok = jnp.squeeze(jnp.take_along_axis(logits, lab, axis=-1), -1)
    per_tok = lse - tok.astype(jnp.float32)
    loss = masked_token_mean(per_tok, mask)
    return loss, (states, w, logits, lse, labels, mask)


def _ce_readout_bwd(res, d):
    states, w, logits, lse, labels, mask = res
    f32 = jnp.float32
    mask_f = mask.astype(f32)
    denom = token_count(mask_f)
    scale = (d * mask_f / denom)                       # [B, T]
    # d_logits = (softmax - onehot) * scale, materialized once in the
    # compute dtype; softmax recomputed from the saved logits + lse
    p = jnp.exp(logits.astype(f32) - lse[..., None])
    d_logits = (p * scale[..., None]).astype(logits.dtype)
    lab = jnp.expand_dims(labels.astype(jnp.int32), -1)
    upd = jnp.take_along_axis(d_logits, lab, axis=-1) - \
        scale[..., None].astype(d_logits.dtype)
    d_logits = jnp.put_along_axis(d_logits, lab, upd, axis=-1,
                                  inplace=False)
    from paddle_tpu.ops.numerics import mxu_cast

    dl_c, w_c, s_c = mxu_cast(d_logits, w, states)
    d_states = jnp.einsum("btv,dv->btd", dl_c, w_c,
                          preferred_element_type=f32).astype(states.dtype)
    d_w = jnp.einsum("btd,btv->dv", s_c, dl_c,
                     preferred_element_type=f32).astype(w.dtype)
    d_b = jnp.sum(d_logits.astype(f32), axis=(0, 1))
    return d_states, d_w, d_b, None, None


_ce_readout_fused.defvjp(_ce_readout_fwd, _ce_readout_bwd)


def _tiled_ce_cfg(B, T, D, V):
    """Vocab-tiled Pallas CE gate: (row_block, v_tile) or None for the XLA
    path.  Needs a TPU backend, lane-aligned D, a sublane-aligned row block
    dividing B*T, and the backward's VMEM-resident working set (full-N
    d_states accumulator + states + double-buffered logits/d_l tiles +
    lane-padded per-row vectors) must fit the raised scoped-VMEM budget —
    larger shapes fall back to the XLA path instead of failing at compile.
    V itself only sets tile padding (handled in the wrapper)."""
    from paddle_tpu.ops.numerics import compute_dtype
    from paddle_tpu.ops.pallas_kernels import compiled_kernels

    if not compiled_kernels():
        return None
    if D % 128:
        return None
    N = B * T
    rb = next((r for r in (2048, 1024, 512, 256, 128, 64, 32, 16, 8)
               if N % r == 0), None)
    if rb is None:
        return None
    vt = 512
    cd = jnp.dtype(compute_dtype()).itemsize
    # per row: the d_states accumulator + states, the double-buffered
    # logits tile + its f32 d_l, three lane-padded per-row vectors; per
    # call: the double-buffered w tile and d_w tile and the d_w product's
    # own float32 result before it is stored, which grow with D and not
    # with N.  Held against the installed v5e compiler at thirteen shapes
    # from D=128 to D=4096 (bf16 policy, 112 MiB asked of the kernel):
    # every shape at or under this budget compiled, and the three that did
    # not (N=6144,D=2048; N=3072,D=4096; N=4096,D=2688, which asked for
    # 113.6 MiB and which the estimate without the product's result put
    # at 100.7) come out over it.
    est = (N * (D * (4 + cd) + vt * (2 * cd + 4) + 3 * 512)
           + D * vt * (2 * cd + 16))
    if est > 108 * 1024 * 1024:
        return None
    return rb, vt


@functools.lru_cache(maxsize=None)
def _tiled_ce_fn(rb, vt, V, sdt, wdt, bdt):
    """custom_vjp instance for one static (row_block, v_tile, V, dtypes)
    configuration of the vocab-tiled Pallas CE (kernels in
    ops/pallas_kernels.py: ce_readout_fwd/bwd_pallas): ``[B, T]``
    cross-entropies out, a ``[B, T]`` cotangent in (the kernels never saw
    the mask: a mean over real tokens is its caller's)."""
    from paddle_tpu.ops.pallas_kernels import (ce_readout_bwd_pallas,
                                               ce_readout_fwd_pallas)

    f32 = jnp.float32

    @jax.custom_vjp
    def tiled(states, w, b, labels):
        per_tok, _ = fwd(states, w, b, labels)
        return per_tok

    def fwd(states, w, b, labels):
        from paddle_tpu.ops.numerics import mxu_cast

        B, T, D = states.shape
        N = B * T
        sc, wc = mxu_cast(states.reshape(N, D), w)
        Vp = -(-V // vt) * vt
        w_p = jnp.pad(wc, ((0, 0), (0, Vp - V)))
        # padded vocab columns get bias -1e30: exp underflows to zero so
        # the statistics and every gradient are exact
        b_p = jnp.pad(b.astype(f32).reshape(1, V), ((0, 0), (0, Vp - V)),
                      constant_values=-1e30)
        lab = labels.astype(jnp.int32).reshape(N, 1)
        per_tok, lse, logits = ce_readout_fwd_pallas(
            sc, w_p, b_p, lab, row_block=rb, v_tile=vt)
        # residual saves the PRIMAL w (free — aliases the input); the padded
        # compute-dtype copy is re-derived in bwd rather than pinning an
        # extra [D, Vp] buffer across the fwd->bwd interval
        return per_tok.reshape(B, T), (sc, w, lab, lse, logits)

    def bwd(res, d):
        from paddle_tpu.ops.numerics import mxu_cast

        sc, w, lab, lse, logits = res
        w_p = jnp.pad(mxu_cast(w), ((0, 0), (0, logits.shape[1] - V)))
        N, D = sc.shape
        B, T = d.shape
        d_states, d_w_p, d_b_p = ce_readout_bwd_pallas(
            logits, sc, w_p, lab, lse, d.reshape(N, 1), v_tile=vt)
        return (d_states.reshape(B, T, D).astype(sdt),
                d_w_p[:, :V].astype(wdt),
                d_b_p[0, :V].astype(bdt), None)

    tiled.defvjp(fwd, bwd)
    return tiled


def softmax_ce_readout_per_token(states, w, b, labels):
    """The readout's cross-entropy of every position, ``[B, T]`` float32:
    ``logsumexp(states w + b) - (states w + b)[label]``, padded positions
    included (the caller masks).  What :func:`sequence_softmax_ce_readout`
    is the masked mean of, on the same two paths: the vocab-tiled kernel
    pair where ``_tiled_ce_cfg`` admits the shape (its backward takes the
    ``[B, T]`` cotangent as the kernel's per-row scale), else the logits
    once in the compute dtype and XLA's reductions.  A loss that weighs
    each token's cross-entropy by something that has a gradient of its own
    (``nn.loop_exit_cost``) reads this form."""
    cfg = _tiled_ce_cfg(states.shape[0], states.shape[1], states.shape[2],
                        w.shape[1])
    if cfg is not None:
        fn = _tiled_ce_fn(cfg[0], cfg[1], int(w.shape[1]),
                          str(states.dtype), str(w.dtype), str(b.dtype))
        return fn(states, w, b, labels)
    logits = _readout_logits(states, w, b)
    lf32 = lambda: logits.astype(jnp.float32)          # fused upcast per use
    m = jnp.max(lf32(), axis=-1, keepdims=True)
    lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(lf32() - m), axis=-1))
    lab = jnp.expand_dims(labels.astype(jnp.int32), -1)
    tok = jnp.squeeze(jnp.take_along_axis(logits, lab, axis=-1), -1)
    return lse - tok.astype(jnp.float32)


def sequence_softmax_ce_readout(states, w, b, labels, mask):
    """Fused vocab readout + token CE: states [B, T, D] x w [D, V] -> loss.

    The O(B*T*V) logits buffer dominates HBM traffic for big-vocab decoders
    (hl_matrix crossEntropy operates on an f32 prob matrix; on TPU a 30k-vocab
    readout at B=256,T=32 is ~1GB in f32).  On TPU the whole tier runs as
    the VOCAB-TILED Pallas kernel pair (ops/pallas_kernels.py): forward
    computes each [rows, v_tile] logits tile on the MXU and folds it into
    online softmax statistics in VMEM (streaming the tile out once, in
    bf16, as the backward residual); backward reads each tile once and
    contracts (softmax - onehot)*scale straight into d_states/d_w — the
    d_logits buffer never exists in HBM.  Off-TPU (or gated shapes), the
    logits are materialized once in the compute dtype and XLA's fused
    reductions produce the statistics — both match ``linear`` +
    ``sequence_cross_entropy`` numerics to bf16 rounding.  The masked mean
    of :func:`softmax_ce_readout_per_token`.
    """
    if _USE_PALLAS_LSE_READOUT and _tiled_ce_cfg(
            states.shape[0], states.shape[1], states.shape[2],
            w.shape[1]) is None:
        return _ce_readout_fused(states, w, b, labels, mask)
    return masked_token_mean(
        softmax_ce_readout_per_token(states, w, b, labels), mask)
