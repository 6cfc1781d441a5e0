"""The sequence mixers and norms of a decoder-only block.

Nothing of the reference (2016) has these; they are what today's decoder
stacks are made of: RMSNorm, rotary position embedding (half-rotation form),
a gated short causal convolution (depthwise, a few taps), and causal
grouped-query attention computed blockwise so that the ``[T, T]`` scores of
a long row never exist in HBM.

Attention has two paths behind one ``jax.custom_vjp``: on the TPU, at
tile-aligned shapes, the flash kernels of ops/pallas_kernels.py
(``flash_attn_fwd`` and ONE backward, ``flash_attn_bwd``, that makes a block
pair's probabilities once for ``dq``, ``dk`` and ``dv``, with ``dk`` and
``dv`` of a key-value head resident in VMEM; a row too long for that is cut
into super-blocks of keys by ``flash_bwd_key_rows``, and no row leaves the
kernels for it); elsewhere a loop over blocks of queries in XLA, each block
against the keys at or before its last row, with the same saved statistics
(the output and the rows' log-sum-exp) and a backward that recomputes each
block's scores.  With ``window=`` a query sees its last ``window`` positions
alone, and both paths walk the band's blocks and no others
(``flash_attn_win_fwd`` / ``flash_attn_win_bwd``; in XLA each block of
queries against the keys from ``window - 1`` before its first row on).

The rotary embedding is one elementwise pass over whole heads, ``x * C +
partner(x) * S``, forward and (the sine negated) backward: on the TPU the
kernel ``rotary_turn``, which finds a channel's partner by a lane roll in
registers (:func:`rotary_kernel_blocks`), elsewhere the same expression in
XLA.  No half of a head is sliced off and nothing is concatenated.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from paddle_tpu.ops.numerics import acc_dtype, dot_dtype, mxu_cast

__all__ = ["rms_norm", "layer_norm", "unit_norm", "rotary_embedding",
           "rotary_kernel_blocks", "yarn_frequencies", "causal_short_conv",
           "causal_attention", "attention_kernel_blocks", "ATTN_XLA_BLOCK"]

#: queries per block of the XLA path
ATTN_XLA_BLOCK = 512


def rms_norm(x, w, eps: float, zero_centered: bool = False):
    """``x / rms(x) * w`` over the last axis; statistics in float32.
    ``zero_centered``: the weight is stored about zero and applied as
    ``1 + w``."""
    xf = x.astype(acc_dtype())
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    w = w.astype(acc_dtype())
    return (xf * inv * (1.0 + w if zero_centered else w)).astype(x.dtype)


def layer_norm(x, w, b, eps: float):
    """``(x - mean) / sqrt(var + eps) * w + b`` over the last axis;
    statistics in float32."""
    xf = x.astype(acc_dtype())
    xf = xf - jnp.mean(xf, -1, keepdims=True)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (xf * inv * w.astype(acc_dtype())
            + b.astype(acc_dtype())).astype(x.dtype)


def unit_norm(x, eps: float = 1e-6):
    """``x * rsqrt(sum x^2 + eps)`` over the last axis (an L2 norm with no
    weight); statistics in float32."""
    xf = x.astype(acc_dtype())
    return (xf * jax.lax.rsqrt(jnp.sum(jnp.square(xf), -1, keepdims=True)
                               + eps)).astype(x.dtype)


def _partner(x, span):
    """Channel ``j``'s partner in the half-rotation at channel ``j``: ``j +
    rd/2`` on the first half of ``span`` ``(a, b)``, ``j - rd/2`` on its
    second (``rd = b - a``); outside the span whatever the rolls bring, which
    the sine's table multiplies by zero.  No slice of a head is made."""
    a, b = span
    dh, half = x.shape[-1], (b - a) // 2
    if b - a == dh:                   # a whole head: one roll by half of it
        return jnp.roll(x, half, axis=-1)
    first = jnp.arange(dh) < a + half
    return jnp.where(first, jnp.roll(x, -half, axis=-1),
                     jnp.roll(x, half, axis=-1))


def rotary_kernel_blocks(T: int, H: int, dh: int):
    """The rotary kernel's gate: ``(heads_major, L, block_rows, block_lanes)``
    or ``None`` for the XLA form.  The kernel turns slabs of ``L`` lanes that
    hold whole heads.  Heads of whole lane tiles (128, 256) are a slab each
    and go in heads-major, ``[B, H, T, dh]``, the layout the flash kernels
    read and write; any other width goes in as the token-major ``[B, T, H
    dh]`` with ``L = lcm(dh, 128)`` (two heads of 64 a slab; two of 192 on
    three tiles), which needs the heads to fill whole slabs: a lone head of
    64 keeps to XLA.  Needs the TPU backend and a row that blocks of 16
    rows or more divide (whole sublane tiles of float32 and of bfloat16); a
    block is the most slabs within 2 MB of float32 that divide the heads."""
    from paddle_tpu.ops.pallas_kernels import compiled_kernels

    if not compiled_kernels():
        return None
    heads_major = dh % 128 == 0
    L = dh if heads_major else math.lcm(dh, 128)
    slabs, part = divmod(H * dh, L)
    if part:
        return None
    for rows in (512, 256, 128, 64, 32, 16):
        per = [n for n in range(1, slabs + 1)
               if slabs % n == 0 and n * L * rows * 4 <= 2 << 20]
        if T % rows == 0 and per:
            return heads_major, L, rows, per[-1] * L
    return None


def _turned(x, cos, sin, span):
    """``x * cos + partner(x) * sin`` over the whole last axis, in float32,
    rounded to ``x``'s dtype: one elementwise pass, the kernel
    ``rotary_turn`` where :func:`rotary_kernel_blocks` opens."""
    B, T, H, dh = x.shape
    with jax.named_scope("rotary"):
        blocks = rotary_kernel_blocks(T, H, dh)
        if blocks is None:
            xf = x.astype(acc_dtype())
            return (xf * cos[None, :, None, :] + _partner(xf, span)
                    * sin[None, :, None, :]).astype(x.dtype)
        from paddle_tpu.ops.pallas_kernels import rotary_pallas

        heads_major, L, rows, lanes = blocks
        view = (jnp.swapaxes(x, 1, 2) if heads_major
                else x.reshape(B, T, H * dh))
        out = rotary_pallas(
            view, jnp.tile(cos, (1, L // dh)), jnp.tile(sin, (1, L // dh)),
            head_dim=dh, span=span, block_rows=rows, block_lanes=lanes)
        return jnp.swapaxes(out, 1, 2) if heads_major else out.reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rotate(x, cos, sin, span):
    return _turned(x, cos, sin, span)


def _rotate_fwd(x, cos, sin, span):
    return _turned(x, cos, sin, span), (cos, sin)


def _rotate_bwd(span, tables, d_out):
    # the rotation is linear and its matrix orthogonal up to ``factor``: the
    # transpose is the same pass with the sine negated, and nothing of x is
    # kept
    cos, sin = tables
    return _turned(d_out, cos, -sin, span), None, None


_rotate.defvjp(_rotate_fwd, _rotate_bwd)


def rotary_embedding(x, theta: float, rotary_dim=None, *, span=None,
                     inv_freq=None, factor: float = 1.0):
    """x ``[B, T, heads, dh]`` at positions ``0..T-1``: the half-rotation
    form (the first half of the turned channels pairs with the second).
    ``span`` ``(a, b)``: turn channels ``a..b-1`` only (paired among
    themselves) and pass the rest through; ``rotary_dim`` is the span ``(0,
    rotary_dim)``; neither: the whole head.  ``inv_freq``: the frequencies
    of the turned channels' pairs, one a pair, in ``theta ** (-2j /
    width)``'s place (a scaled embedding: :func:`yarn_frequencies`);
    ``factor`` multiplies cos and sin (YaRN's attention factor).

    The rotation is ONE elementwise pass over the whole head, ``x * C +
    partner(x) * S``, with two ``[T, dh]`` float32 tables made once a call
    from the positions: ``C`` holds cos on the span and 1 outside it, ``S``
    holds -sin on the span's first half, +sin on its second and 0 outside;
    ``partner`` brings channel ``j +- (b - a) / 2`` to channel ``j``
    (:func:`_partner`).  No slice of a head is made and nothing is
    concatenated at the activations' size, so the channels passed through
    ride in the same pass.  Float32 arithmetic whatever ``x``'s dtype, the
    result in ``x``'s dtype.  The backward (a ``jax.custom_vjp``) is the same
    pass over the cotangent with the sine negated; its residuals are the two
    tables alone."""
    T, dh = x.shape[1], x.shape[-1]
    if span is None:
        span = (0, dh if rotary_dim is None else rotary_dim)
    elif rotary_dim is not None:
        raise ValueError("rotary_dim and span are two names of one thing")
    a, b = (int(i) for i in span)
    if not 0 <= a < b <= dh or (b - a) % 2:
        raise ValueError(f"rotary span {a}:{b} of a head of {dh}")
    f32 = acc_dtype()
    if inv_freq is None:
        inv = theta ** (-jnp.arange(0, b - a, 2, dtype=f32) / (b - a))
    else:
        inv = jnp.asarray(inv_freq, f32)
        if inv.shape != ((b - a) // 2,):
            raise ValueError(f"{inv.shape[0]} frequencies for {b - a} "
                             f"channels")
    ang = jnp.arange(T, dtype=f32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    ones, zeros = jnp.ones((T, dh), f32), jnp.zeros((T, dh), f32)
    return _rotate(
        x, jnp.concatenate([ones[:, :a], cos, cos, ones[:, b:]], -1),
        jnp.concatenate([zeros[:, :a], -sin, sin, zeros[:, b:]], -1), (a, b))


def yarn_frequencies(width: int, *, rope_theta: float, factor: float,
                     original_max_position_embeddings: int,
                     beta_fast: float = 32.0, beta_slow: float = 1.0,
                     attention_factor=None):
    """``(inv_freq [width / 2] float32, attention factor)`` of a rotary
    embedding over ``width`` channels scaled by YaRN, from the numbers of a
    config's ``rope_parameters`` (``rope_type`` ``yarn``), as HF's
    ``_compute_yarn_parameters`` makes them.  Pair ``j`` turns at ``f_j =
    rope_theta ** (-2j / width)`` where it completes more than ``beta_fast``
    turns over the original context (``j <= low``), at ``f_j / factor``
    where fewer than ``beta_slow`` (``j >= high``), and on a linear ramp
    between; ``low = floor(c(beta_fast))``, ``high = ceil(c(beta_slow))``,
    ``c(n) = width ln(original / (2 pi n)) / (2 ln rope_theta)``, both kept
    inside ``0 .. width - 1``.  The attention factor multiplies cos and sin
    (``0.1 ln(factor) + 1`` where the config gives none)."""
    def pair_of(turns):
        return (width * np.log(original_max_position_embeddings
                               / (turns * 2 * np.pi))
                / (2 * np.log(rope_theta)))

    low = max(int(np.floor(pair_of(beta_fast))), 0)
    high = min(int(np.ceil(pair_of(beta_slow))), width - 1)
    plain = float(rope_theta) ** (-np.arange(0, width, 2, dtype=np.float64)
                                  / width)
    ramp = np.clip((np.arange(width // 2) - low)
                   / ((high - low) or 0.001), 0.0, 1.0)
    inv = plain * (1.0 - ramp) + plain / factor * ramp
    if attention_factor is None:
        attention_factor = 0.1 * np.log(factor) + 1.0
    return inv.astype(np.float32), float(attention_factor)


def causal_short_conv(z, kernel, bias=None):
    """Depthwise causal convolution over time: ``z`` ``[B, T, D]``, ``kernel``
    ``[L, D]``, ``bias`` ``[D]`` where the model has one; ``out_t = sum_j
    kernel[j] * z[t - (L-1) + j] (+ bias)``, zero before the row's start.
    ``L`` shifted multiply-adds: at a few taps this is bandwidth, not a
    convolution worth a kernel."""
    L, T = kernel.shape[0], z.shape[1]
    zp = jnp.pad(z, ((0, 0), (L - 1, 0), (0, 0)))
    k = kernel.astype(z.dtype)
    out = k[0] * zp[:, 0:T]
    for j in range(1, L):
        out = out + k[j] * zp[:, j:j + T]
    return out if bias is None else out + bias.astype(z.dtype)


# ---------------------------------------------------------------------------
# blockwise causal attention
# ---------------------------------------------------------------------------


def attention_kernel_blocks(T: int, dh: int, H: int, Hkv: int, dv=None,
                            window=None):
    """The flash kernels' gate: ``(block_q, block_k)`` or ``None`` for the
    XLA path.  ``dh`` is the width of a query and key head, ``dv`` that of a
    value head (``dh`` where not given).  Needs the TPU backend, a row length
    the blocks divide, both widths multiples of 64 (a block's minor axis is
    the whole head, so 192 beside 128 is as good as 64 beside 64) and whole
    groups of query heads.  The row's length closes nothing: what the
    backward keeps resident is reckoned against the kernels' VMEM budget by
    ``pallas_kernels.flash_bwd_key_rows`` (the whole row's ``dk`` and ``dv``
    up to 23k rows at 192/128 and 35k at 64/64 with blocks of 1024, beyond
    that super-blocks of keys), inside ``flash_attn_bwd_pallas``.

    The band's rule, with ``window``: the kernels visit whole block pairs, so
    blocks wider than the window would spend most of a visited pair on
    positions outside it (at blocks of 1024 a window of 512 uses a quarter
    of each visited pair, at 512 a half): the blocks are the largest that
    divide the row and are no wider than the window (but 128 at least).
    The window kernels have no walk over super-blocks of keys: a row too
    long for the backward to keep whole takes the XLA path, which walks the
    band too."""
    from paddle_tpu.ops.pallas_kernels import (compiled_kernels,
                                               flash_bwd_key_rows)

    if not compiled_kernels():
        return None
    dv = dh if dv is None else dv
    if dh % 64 or dv % 64 or H % Hkv:
        return None
    for blk in (1024, 512, 256, 128):
        if window is not None and blk > max(window, 128):
            continue
        if T % blk == 0 and T >= 2 * blk:
            if window is not None and flash_bwd_key_rows(T, dh, dv, blk,
                                                         blk) < T:
                return None
            return blk, blk
    return None


def _block_mask(keep, i, lo, hi, first=0, window=None):
    """What queries ``lo..hi-1`` see of positions ``first..hi-1``: the causal
    mask, or block ``i`` of a selection (``keep``: one bool ``[B, hi - lo,
    hi]`` a block of queries, the same for every head, all at or before the
    query), or, with ``window``, the band ``t - window < s <= t``."""
    if keep is not None:
        return keep[i][:, None, None]
    rows = lo + jnp.arange(hi - lo)[:, None]
    if window is None:
        return jnp.arange(hi)[None, :] <= rows
    cols = jnp.arange(first, hi)[None, :]
    return (cols <= rows) & (cols > rows - window)


def _band_start(lo: int, window) -> int:
    """The first position any query from ``lo`` on sees."""
    return 0 if window is None else max(0, lo - window + 1)


def _xla_fwd(q, k, v, scale, block, keep=None, window=None):
    """q ``[B, T, Hkv, G, dh]``, k ``[B, T, Hkv, dh]``, v ``[B, T, Hkv, dv]``
    in the compute dtype -> (out ``[B, T, Hkv, G, dv]`` in float32, lse
    ``[B, Hkv, G, T]``).  ``keep``: a selection in the causal mask's place
    (:func:`_block_mask`; ops/sparse_attention.py).  ``window``: the band in
    its place, each block of queries against the band's keys alone."""
    T = q.shape[1]
    f32 = acc_dtype()
    outs, lses = [], []
    for i, lo in enumerate(range(0, T, block)):
        hi = min(T, lo + block)
        k0 = _band_start(lo, window)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q[:, lo:hi], k[:, k0:hi],
                       preferred_element_type=f32) * scale
        s = jnp.where(_block_mask(keep, i, lo, hi, k0, window), s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v[:, k0:hi],
                       preferred_element_type=f32)
        outs.append(o / jnp.moveaxis(l, 3, 1))     # l [b,h,g,q,1]->[b,q,h,g,1]
        lses.append((m + jnp.log(l))[..., 0])
    return jnp.concatenate(outs, axis=1), jnp.concatenate(lses, axis=-1)


def _xla_bwd(q, k, v, out, lse, d_out, scale, block, keep=None,
             window=None):
    T = q.shape[1]
    f32 = acc_dtype()
    delta = jnp.sum(d_out.astype(f32) * out.astype(f32), -1)   # [b,q,h,g]
    dq = []
    dk = jnp.zeros(k.shape, f32)
    dv = jnp.zeros(v.shape, f32)
    do_c = d_out.astype(v.dtype)
    for i, lo in enumerate(range(0, T, block)):
        hi = min(T, lo + block)
        k0 = _band_start(lo, window)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q[:, lo:hi], k[:, k0:hi],
                       preferred_element_type=f32) * scale
        live = _block_mask(keep, i, lo, hi, k0, window)
        p = jnp.where(live, jnp.exp(s - lse[..., lo:hi, None]), 0.0)
        dp = jnp.einsum("bqhgd,bkhd->bhgqk", do_c[:, lo:hi], v[:, k0:hi],
                        preferred_element_type=f32)
        dl = jnp.moveaxis(delta[:, lo:hi], 1, 3)[..., None]    # [b,h,g,q,1]
        ds = (p * (dp - dl) * scale).astype(q.dtype)
        dq.append(jnp.einsum("bhgqk,bkhd->bqhgd", ds, k[:, k0:hi],
                             preferred_element_type=f32))
        dk = dk.at[:, k0:hi].add(jnp.einsum(
            "bhgqk,bqhgd->bkhd", ds, q[:, lo:hi], preferred_element_type=f32))
        dv = dv.at[:, k0:hi].add(jnp.einsum(
            "bhgqk,bqhgd->bkhd", p.astype(v.dtype), do_c[:, lo:hi],
            preferred_element_type=f32))
    return jnp.concatenate(dq, axis=1), dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attention(q, k, v, scale, window):
    return _attention_fwd(q, k, v, scale, window)[0]


def _attention_fwd(q, k, v, scale, window):
    B, T, H, dh = q.shape
    Hkv, dv = k.shape[2], v.shape[3]
    qc, kc, vc = mxu_cast(q, k, v)
    like = tuple(jnp.zeros((0,), a.dtype) for a in (q, k, v))
    blocks = attention_kernel_blocks(T, dh, H, Hkv, dv, window)
    if blocks is not None:
        from paddle_tpu.ops.pallas_kernels import flash_attn_fwd_pallas

        # heads-major: a (head, block of rows) is one contiguous tile
        qh, kh, vh = (jnp.swapaxes(a, 1, 2) for a in (qc, kc, vc))
        oh, lse = flash_attn_fwd_pallas(qh, kh, vh, scale=scale,
                                        block_q=blocks[0], block_k=blocks[1],
                                        window=window)
        # kept across a recomputation block: the backward's second forward
        # recomputes the projections, not the attention
        oh, lse = (checkpoint_name(a, "remat_keep") for a in (oh, lse))
        out = jnp.swapaxes(oh, 1, 2)
        return out.astype(dot_dtype()), (qh, kh, vh, oh, lse, like)
    qg = qc.reshape(B, T, Hkv, H // Hkv, dh)
    out, lse = _xla_fwd(qg, kc, vc, scale, ATTN_XLA_BLOCK, window=window)
    return (out.reshape(B, T, H, dv).astype(dot_dtype()),
            (qg, kc, vc, out, lse, like))


def _attention_bwd(scale, window, res, d_out):
    q, k, v, out, lse, like = res
    if out.ndim == 4:       # the kernels' heads-major residuals
        from paddle_tpu.ops.pallas_kernels import flash_attn_bwd_pallas

        blocks = attention_kernel_blocks(q.shape[2], q.shape[3], q.shape[1],
                                         k.shape[1], v.shape[3], window)
        do = jnp.swapaxes(d_out, 1, 2).astype(q.dtype)
        dq, dk, dv = flash_attn_bwd_pallas(
            q, k, v, out, lse, do, scale=scale, block_q=blocks[0],
            block_k=blocks[1], window=window)
        return tuple(jnp.swapaxes(a, 1, 2).astype(z.dtype)
                     for a, z in zip((dq, dk, dv), like))
    B, T, Hkv, G, dh = q.shape
    dq, dk, dv = _xla_bwd(q, k, v, out, lse,
                          d_out.reshape(B, T, Hkv, G, v.shape[3]), scale,
                          ATTN_XLA_BLOCK, window=window)
    return tuple(a.astype(z.dtype) for a, z in zip(
        (dq.reshape(B, T, Hkv * G, dh), dk, dv), like))


_attention.defvjp(_attention_fwd, _attention_bwd)


def causal_attention(q, k, v, *, scale: float, window=None):
    """Causal softmax attention with grouped key-value heads: q ``[B, T, H,
    dh]``, k ``[B, T, Hkv, dh]``, v ``[B, T, Hkv, dv]`` (key-value head ``j``
    serves query heads ``j*G .. j*G+G-1``, ``G = H / Hkv``) -> ``[B, T, H,
    dv]``.  The values' width is their own: latent attention scores with
    192-wide queries and keys and sums 128-wide values.  bf16 operands under
    the default policy, float32 scores and statistics; the scores exist one
    block at a time, forward and backward.  ``window``: query ``t`` sees
    positions ``t - window < s <= t`` alone (its own position counts); what
    lies outside contributes exactly nothing, forward and backward, and a
    window no shorter than the row is no window."""
    if window is not None:
        window = int(window)
        if window < 1:
            raise ValueError(f"a window of {window} positions")
        if window >= q.shape[1]:
            window = None
    return _attention(q, k, v, float(scale), window)
