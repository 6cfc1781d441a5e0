"""The gated delta rule, the recurrence of a gated-delta-net's linear
attention, as a chunked scan.

A head keeps a state ``S`` ``[dk, dv]`` (float32, zero at the row's start).
Token ``t`` decays it, corrects what it holds under the token's key towards
the token's value, and reads it with the token's query::

    S' = exp(g_t) S_{t-1}
    S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
    o_t = S_t^T q_t

Nothing of the reference (2016) has this.  Token by token it is ``T``
dependent rank-one updates; here a row is cut into chunks of
:data:`CHUNK` tokens and a chunk is a handful of matrix products (the WY
form).  With ``gamma_i`` the sum of ``g`` from the chunk's start to token
``i``, ``d_ij = exp(gamma_i - gamma_j)`` for ``i >= j`` (0 above the
diagonal), ``S`` the state at the chunk's start::

    A  = strictly lower part of  beta_i d_ij (k_i . k_j)
    T  = (I + A)^-1                       unit lower triangular, float32
    R  = beta v - (beta exp(gamma) k) S
    Vn = T R                              every token's corrected value
    O  = (exp(gamma) q) S + (d_ij (q_i . k_j)) Vn
    S_end = exp(gamma_C) S + (exp(gamma_C - gamma) k)^T Vn

``A`` is nilpotent, so ``T = (I - A)(I + A^2)(I + A^4)...(I + A^32)``: ten
64 x 64 products on the MXU and no substitution.  Only differences
``gamma_i - gamma_j`` with ``i >= j``, sums from the chunk's start and sums
to the chunk's end are exponentiated, all of them ``<= 0``: nothing is ever
divided by a decay, and a token with ``g = -30`` is as safe as one with
``g = 0``.  The sums of ``g``, the solve and ``S`` are float32; the
products' operands are in the compute dtype with float32 accumulation.

Two paths behind one gate, :func:`delta_rule_kernel_chunk` (the TPU backend,
head widths that are multiples of 128, a row of whole chunks): the Pallas
kernels ``gdn_chunk_fwd`` / ``gdn_chunk_bwd`` of ops/pallas_kernels.py under
a ``jax.custom_vjp`` (the chunk axis of the grid is sequential and ``S``
stays in VMEM; the forward writes every chunk's starting state and its
solve ``T`` out, and the backward walks the chunks in reverse with ``dS``
resident, reading ``T`` back and recomputing a chunk's ``R`` and ``Vn`` from
the saved state); and the same algebra in ``jax.numpy`` with a ``lax.scan``
over chunks, differentiated by JAX.
The chunk's algebra is written once, on 2-D arrays, and both paths call it
(:func:`chunk_forward`; the kernels' :func:`chunk_backward` is its
transpose by hand).

Heads: ``q`` and ``k`` come at ``Hk`` key heads, ``v``, ``g`` and ``beta`` at
``Hv`` value heads, whole groups over the key heads; value head ``h`` reads
key head ``h // (Hv // Hk)``.  The kernels read it through their index maps
and write ``dq``, ``dk`` a value head; the XLA path repeats the key heads
(float32, before the cast, as the layer did until PR 42).  Who casts to the
compute dtype: :func:`delta_rule` (its ``heads_major`` on the XLA path, the
kernels' ``custom_vjp`` on theirs, so the group's gradient is summed in
float32 and stays float32); :func:`conv_delta_rule`'s kernel.

:func:`conv_delta_rule` is the delta net's whole way from its in-projection
to the scan's output on kernels alone, behind a gate of its own,
:func:`prep_kernel_rows` (the scan's gate, whole groups, a convolution of at
most 9 taps, a row that whole blocks of rows divide and that the scan does
not pad): ``gdn_prep_fwd`` makes the convolution, SiLU, the L2 norms, ``q``'s
scale and the heads-major compute-dtype layout in ONE pass over the
projection, ``gdn_prep_bwd`` their transpose in one, and one
``jax.custom_vjp`` holds both pairs, so the scan's per-value-head ``dq``,
``dk`` go to ``gdn_prep_bwd`` as they are and are summed over a group there,
in VMEM.  Behind the closed gate the layer's ``jax.numpy`` chain and
:func:`delta_rule` run (``nn.gated_delta_net``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from paddle_tpu.ops.numerics import acc_dtype, compute_dtype, dot_dtype

__all__ = ["delta_rule", "delta_rule_kernel_chunk", "conv_delta_rule",
           "prep_kernel_rows", "group_columns", "CHUNK",
           "KERNEL_BLOCK_CHUNKS", "chunk_forward", "chunk_backward"]

#: tokens per chunk of the WY form
CHUNK = 64
#: chunks one grid step of the kernels takes (the row is padded to whole
#: blocks); a block's chunks are independent but for the state, so what does
#: not wait for the state overlaps with what does
KERNEL_BLOCK_CHUNKS = 8

_HI = lax.Precision.HIGHEST


def delta_rule_kernel_chunk(T: int, dk: int, dv: int):
    """The kernels' gate: the chunk length, or ``None`` for the XLA path.
    Needs the TPU backend, lane-aligned head widths and a row of whole
    chunks."""
    from paddle_tpu.ops.pallas_kernels import compiled_kernels

    if not compiled_kernels():
        return None
    if dk % 128 or dv % 128 or T % CHUNK:
        return None
    return CHUNK


# -- one chunk, on 2-D arrays (both paths) ----------------------------------

def _dot(a, b, ca: int, cb: int, dt=None, precision=None):
    """``a`` contracted over its axis ``ca`` with ``b`` over ``cb``, float32
    accumulation; operands cast to ``dt`` where given."""
    if dt is not None:
        a, b = a.astype(dt), b.astype(dt)
    return lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                           preferred_element_type=jnp.float32,
                           precision=precision)


def _iotas(C: int):
    return (lax.broadcasted_iota(jnp.int32, (C, C), 0),
            lax.broadcasted_iota(jnp.int32, (C, C), 1))


def col_of_row(row):
    """``[1, C]`` -> ``[C, 1]`` without a transpose (a kernel holds ``g`` and
    ``beta`` lane-dense, one chunk a row)."""
    i, j = _iotas(row.shape[1])
    return jnp.sum(jnp.where(i == j, row, 0.0), axis=1, keepdims=True)


def row_of_col(col):
    """``[C, 1]`` -> ``[1, C]``."""
    i, j = _iotas(col.shape[0])
    return jnp.sum(jnp.where(i == j, col, 0.0), axis=0, keepdims=True)


def _unit_lower_inverse(A):
    """``(I + A)^-1`` for a strictly lower triangular ``A`` ``[C, C]``,
    float32: ``A`` is nilpotent, so the inverse is the finite product
    ``(I - A)(I + A^2)(I + A^4)...`` up to ``A^(C/2)``."""
    C = A.shape[0]
    i, j = _iotas(C)
    T = jnp.where(i == j, 1.0, 0.0).astype(A.dtype) - A
    P, n = A, 1
    while 2 * n < C:
        P = _dot(P, P, 1, 0, precision=_HI)
        T = T + _dot(T, P, 1, 0, precision=_HI)
        n *= 2
    return T


def _chunk_parts(q, k, gcol, grow, bcol, dt, T=None):
    """What a chunk needs that does not wait for the state; ``T`` where the
    caller has the chunk's solve already (the reverse walk).  The forward's
    solve stays HERE, between ``A`` and ``P``: the kernel's schedule follows
    the order of the equations (12,198 bundles a block of 8 chunks for the
    described v5e; 13,326 with the solve after the exponentials)."""
    C = q.shape[0]
    i, j = _iotas(C)
    decay = jnp.exp(jnp.where(i >= j, gcol - grow, -jnp.inf))   # d_ij
    kk = _dot(k, k, 1, 1, dt)
    A = jnp.where(i > j, bcol * decay * kk, 0.0)
    # [1, 1]; a masked sum and not the slice grow[:, C-1:]: Mosaic cannot
    # broadcast a value that sits at lane 63 over sublanes and lanes at once
    glast = jnp.sum(jnp.where(j[:1] == C - 1, grow, 0.0), axis=1,
                    keepdims=True)
    return {"decay": decay, "kk": kk, "A": A, "strict": i > j,
            "T": _unit_lower_inverse(A) if T is None else T,
            "P": decay * _dot(q, k, 1, 1, dt),
            "e": jnp.exp(gcol), "ec": jnp.exp(glast - gcol),
            "a": jnp.exp(glast)}


def _chunk_state_parts(p, q, k, v, bcol, S, dt):
    f32 = jnp.float32
    qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
    Kb = (bcol * p["e"]) * kf
    R = bcol * vf - _dot(Kb, S, 1, 0, dt)
    return qf, kf, vf, Kb, R, _dot(p["T"], R, 1, 0, dt)


def chunk_forward(q, k, v, gcol, grow, bcol, S, dt):
    """One chunk of one head.  ``q``, ``k`` ``[C, dk]``, ``v`` ``[C, dv]``;
    ``gcol`` ``[C, 1]`` and ``grow`` ``[1, C]``: the sums of ``g`` from the
    chunk's start, both ways round; ``bcol`` ``[C, 1]``; ``S`` ``[dk, dv]``
    float32.  Returns ``(o [C, dv], S_end, T [C, C])``, float32: ``T`` is
    the chunk's solve ``(I + A)^-1``, which depends on ``k``, ``g`` and
    ``beta`` alone and which the forward kernel keeps for the reverse walk."""
    p = _chunk_parts(q, k, gcol, grow, bcol, dt)
    qf, kf, _, _, _, Vn = _chunk_state_parts(p, q, k, v, bcol, S, dt)
    o = _dot(p["e"] * qf, S, 1, 0, dt) + _dot(p["P"], Vn, 1, 0, dt)
    return o, p["a"] * S + _dot(p["ec"] * kf, Vn, 0, 0, dt), p["T"]


def chunk_backward(q, k, v, gcol, grow, bcol, S, T, dO, dS1, dt):
    """The transpose of :func:`chunk_forward`, by hand, for the kernel:
    ``T`` (the solve :func:`chunk_forward` returned for this chunk), ``dO``
    ``[C, dv]`` and ``dS1`` (the gradient of the chunk's END state) in,
    ``(dq, dk, dv, dgamma_col [C, 1], dgamma_row [1, C], dgamma_last [1, 1],
    dbeta_col, dS)`` out, float32.  The gradient of the chunk's sums of ``g``
    comes in three parts: ``dgamma_col + transpose(dgamma_row)``, and
    ``dgamma_last`` for the last token's alone.  ``R`` and ``Vn`` are made
    again from ``S``, and what else does not wait for the state (the decays,
    ``k k^T``, ``A``, ``P``) from the operands: one pass of the MXU or vector
    work each.  The solve, ten float32 products at ``highest``, is not."""
    p = _chunk_parts(q, k, gcol, grow, bcol, dt, T)
    qf, kf, vf, Kb, R, Vn = _chunk_state_parts(p, q, k, v, bcol, S, dt)
    e, ec, a, decay = p["e"], p["ec"], p["a"], p["decay"]
    Qe, Ke = e * qf, ec * kf

    dVn = _dot(p["P"], dO, 0, 0, dt) + _dot(Ke, dS1, 1, 0, dt)
    dP = _dot(dO, Vn, 1, 1, dt)
    dQe = _dot(dO, S, 1, 1, dt)
    dKe = _dot(Vn, dS1, 1, 1, dt)
    dR = _dot(p["T"], dVn, 0, 0, dt)
    # T = (I + A)^-1 and T R = Vn:  dA = -T^T dT T^T = -dR Vn^T
    dA = jnp.where(p["strict"], -_dot(dR, Vn, 1, 1, dt), 0.0)
    dKb = -_dot(dR, S, 1, 1, dt)
    dS = (_dot(Qe, dO, 0, 0, dt) + a * dS1 - _dot(Kb, dR, 0, 0, dt))

    dKK = dA * bcol * decay
    dQK = dP * decay
    dq = _dot(dQK, k, 1, 0, dt) + e * dQe
    dk = (_dot(dKK, k, 1, 0, dt) + _dot(dKK, k, 0, 0, dt)
          + _dot(dQK, q, 0, 0, dt) + (bcol * e) * dKb + ec * dKe)
    dv = bcol * dR
    dbeta = (jnp.sum(dA * decay * p["kk"], axis=1, keepdims=True)
             + jnp.sum(dKb * (e * kf), axis=1, keepdims=True)
             + jnp.sum(dR * vf, axis=1, keepdims=True))
    G = dA * p["A"] + dP * p["P"]           # every d_ij's part: +i, -j
    to_end = jnp.sum(dKe * Ke, axis=1, keepdims=True)
    dg_col = (jnp.sum(G, axis=1, keepdims=True)
              + jnp.sum(dQe * Qe, axis=1, keepdims=True)
              + jnp.sum(dKb * Kb, axis=1, keepdims=True) - to_end)
    dg_row = -jnp.sum(G, axis=0, keepdims=True)
    dg_last = (jnp.sum(to_end, axis=0, keepdims=True)
               + a * jnp.sum(jnp.sum(dS1 * S, axis=1, keepdims=True), axis=0,
                             keepdims=True))
    return dq, dk, dv, dg_col, dg_row, dg_last, dbeta, dS


# -- the XLA path -----------------------------------------------------------

def _scan_xla(q, k, v, gamma, beta):
    """Heads-major ``q``, ``k`` ``[B, H, N, C, dk]``, ``v`` ``[B, H, N, C,
    dv]``, ``gamma``, ``beta`` ``[B, H, N, C]`` -> ``[B, H, N, C, dv]``
    float32: a ``lax.scan`` over the chunks, every head at once."""
    dt = q.dtype
    B, H = q.shape[:2]

    def one(S, x):
        qc, kc, vc, gc, bc = x
        o, S_end, _ = chunk_forward(qc, kc, vc, gc[:, None], gc[None, :],
                                    bc[:, None], S, dt)
        return S_end, o

    chunks_first = tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v, gamma, beta))
    S0 = jnp.zeros((B, H, q.shape[-1], v.shape[-1]), jnp.float32)
    _, o = lax.scan(jax.vmap(jax.vmap(one)), S0, chunks_first)   # over B, H
    return jnp.moveaxis(o, 0, 2)


# -- the kernels' path ------------------------------------------------------

def _scan_fwd(q, k, v, gamma, beta):
    from paddle_tpu.ops.pallas_kernels import gdn_chunk_fwd_pallas

    # (o, every chunk's starting state, every chunk's solve), all three kept
    # across a recomputation block, as attention's output is: the backward's
    # second forward makes the projections again, not the scan, and the
    # reverse walk reads the solve (256 bytes a token and head beside the
    # states' 1,024): ten float32 products at ``highest`` it does not make
    return tuple(checkpoint_name(a, "remat_keep")
                 for a in gdn_chunk_fwd_pallas(q, k, v, gamma, beta))


@jax.custom_vjp
def _scan_kernels(q, k, v, gamma, beta):
    """Heads-major float32 ``q``, ``k`` ``[B, Hk, T, dk]``, ``v`` ``[B, H, T,
    dv]``; the cast to the compute dtype is inside, so the gradients come
    back float32 and a group's ``dq``, ``dk`` are summed in float32."""
    return _scan_kernels_fwd(q, k, v, gamma, beta)[0]


def _scan_kernels_fwd(q, k, v, gamma, beta):
    q, k, v = (a.astype(compute_dtype()) for a in (q, k, v))
    o, states, solves = _scan_fwd(q, k, v, gamma, beta)
    return o, (q, k, v, gamma, beta, states, solves)


def _scan_kernels_bwd(res, do):
    from paddle_tpu.ops.pallas_kernels import gdn_chunk_bwd_pallas

    q, k, v, gamma, beta, states, solves = res
    dq, dk, dv, dgamma, dbeta = gdn_chunk_bwd_pallas(
        q, k, v, gamma, beta, states, solves, do.astype(q.dtype))
    B, Hk, T, d = q.shape
    f32 = jnp.float32
    # the transpose of the heads' repeat: a group's sum
    dq, dk = (a.reshape(B, Hk, -1, T, d).astype(f32).sum(2) for a in (dq, dk))
    return dq, dk, dv.astype(f32), dgamma, dbeta


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


def _chunked_gates(g, beta, N: int):
    """``g``, ``beta`` ``[B, T, H]`` (``T = N`` chunks) -> ``g`` summed from
    each chunk's start and ``beta``, ``[B, H, N, CHUNK]`` float32."""
    B, _, H = g.shape
    gh, bh = (jnp.moveaxis(a.astype(acc_dtype()), 2, 1).reshape(
        B, H, N, CHUNK) for a in (g, beta))
    return jnp.cumsum(gh, axis=-1), bh


def delta_rule(q, k, v, g, beta):
    """The gated delta rule over a row: ``q``, ``k`` ``[B, T, Hk, dk]`` (the
    caller has normalised and scaled them), ``v`` ``[B, T, H, dv]``, ``g``
    (the log of a token's decay, ``<= 0``) and ``beta`` ``[B, T, H]`` ->
    ``o`` ``[B, T, H, dv]``; ``H`` is whole groups over ``Hk``, and value
    head ``h`` reads key head ``h // (H // Hk)``.  The state is zero at the
    row's start.  bf16 operands under the default policy; ``g``'s sums, the
    solve inside a chunk and the state in float32."""
    B, T, Hk, dk = q.shape
    H, dv = v.shape[2:]
    if H % Hk:
        raise ValueError(f"{H} value heads are not whole groups over {Hk} "
                         "key heads")
    kernels = delta_rule_kernel_chunk(T, dk, dv) is not None
    N = -(-T // CHUNK)
    if kernels and N > KERNEL_BLOCK_CHUNKS:
        N = -(-N // KERNEL_BLOCK_CHUNKS) * KERNEL_BLOCK_CHUNKS
    pad = N * CHUNK - T     # a padded token has k = 0, beta = 0, g = 0: it
    # leaves the state as it is, and its output is cut off

    def heads_major(a, dtype):
        a = jnp.moveaxis(a.astype(dtype), 2, 1)
        if pad:
            a = jnp.pad(a, ((0, 0), (0, 0), (0, pad))
                        + ((0, 0),) * (a.ndim - 3))
        return a

    if pad:
        g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (g, beta))
    gamma, bh = _chunked_gates(g, beta, N)
    if kernels:
        o = _scan_kernels(*(heads_major(a, acc_dtype()) for a in (q, k, v)),
                          gamma, bh)
    else:
        cd = compute_dtype()
        qh, kh, vh = (heads_major(a, cd) for a in (
            *(jnp.repeat(a, H // Hk, axis=2) for a in (q, k)), v))
        o = _scan_xla(*(a.reshape(B, H, N, CHUNK, a.shape[-1])
                        for a in (qh, kh, vh)), gamma, bh)
        o = o.reshape(B, H, N * CHUNK, dv)
    return jnp.moveaxis(o[:, :, :T], 1, 2).astype(dot_dtype())


# -- from the in-projection to the scan's output, on kernels alone ----------

def prep_kernel_rows(T: int, Hk: int, Hv: int, dk: int, dv: int, taps: int):
    """The gate of ``gdn_prep_fwd`` / ``gdn_prep_bwd``: the rows of a block,
    or ``None`` for the layer's ``jax.numpy`` chain.  Needs the scan's gate
    open (the TPU backend, head widths that are multiples of 128, a row of
    whole chunks), whole groups of value heads, a convolution whose history
    fits the halo, a row that the scan does not pad and that whole blocks
    divide, and a block (its float32 rows in and out, the gradients' rows
    and two scratch copies, the streamed ones twice) within the kernels'
    VMEM."""
    from paddle_tpu.ops.pallas_kernels import (GDN_PREP_HALO,
                                               GDN_PREP_VMEM_LIMIT_BYTES)

    if delta_rule_kernel_chunk(T, dk, dv) is None or Hv % Hk:
        return None
    n = T // CHUNK
    if not 1 <= taps <= GDN_PREP_HALO + 1 or (
            n > KERNEL_BLOCK_CHUNKS and n % KERNEL_BLOCK_CHUNKS):
        return None
    group = Hv // Hk
    # a row of the reverse kernel's block: six float32 copies of a group's
    # columns (x and dx, each in two buffers, and the two scratch copies)
    # and two buffers of the gradients' compute-dtype rows; as much again
    # is left for the body's own temporaries
    row_bytes = (6 * 4 * (2 * dk + group * dv)
                 + 2 * 2 * group * (2 * dk + dv))
    for rows in (512, 256, 128, 64):
        if T % rows == 0 and 2 * rows * row_bytes <= GDN_PREP_VMEM_LIMIT_BYTES:
            return rows
    return None


def group_columns(a, Hk: int, dk: int):
    """The last axis from ``[q | k | v]`` (``Hk dk + Hk dk + Hv dv``) to
    groups by key head, ``Hk`` times ``[q_j | k_j | v of the group's value
    heads]``: the column order the prep kernels read, for the projection's
    weight and the convolution's kernel alike."""
    nk, lead = Hk * dk, a.shape[:-1]
    parts = (a[..., :nk], a[..., nk:2 * nk], a[..., 2 * nk:])
    return jnp.concatenate([p.reshape(*lead, Hk, -1) for p in parts],
                           axis=-1).reshape(a.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _conv_scan_kernels(x, w, gamma, beta, dk, dv, rows):
    return _conv_scan_fwd(x, w, gamma, beta, dk, dv, rows)[0]


def _conv_scan_fwd(x, w, gamma, beta, dk, dv, rows):
    from paddle_tpu.ops.pallas_kernels import gdn_prep_fwd_pallas

    with jax.named_scope("gdn_proj"):
        q, k, v = gdn_prep_fwd_pallas(x, w, dk=dk, dv=dv, rows=rows,
                                      out_dtype=compute_dtype())
    with jax.named_scope("gdn_scan"):
        o, states, solves = _scan_fwd(q, k, v, gamma, beta)
    return o, (x, w, q, k, v, gamma, beta, states, solves)


def _conv_scan_bwd(dk, dv, rows, res, do):
    from paddle_tpu.ops.pallas_kernels import (gdn_chunk_bwd_pallas,
                                               gdn_prep_bwd_pallas)

    x, w, q, k, v, gamma, beta, states, solves = res
    with jax.named_scope("gdn_scan"):
        dq, dk_, dv_, dgamma, dbeta = gdn_chunk_bwd_pallas(
            q, k, v, gamma, beta, states, solves, do.astype(q.dtype))
    with jax.named_scope("gdn_proj"):
        dx, dw = gdn_prep_bwd_pallas(x, w, dq, dk_, dv_, rows=rows)
    return dx, dw, dgamma, dbeta


_conv_scan_kernels.defvjp(_conv_scan_fwd, _conv_scan_bwd)


def conv_delta_rule(x, kernel, g, beta, *, key_head_dim: int,
                    value_head_dim: int, rows: int):
    """A delta net from its in-projection to the scan's output, where
    :func:`prep_kernel_rows` gave ``rows``: ``x`` ``[B, T, 2 Hk dk + Hv dv]``
    float32 with its columns grouped by key head (:func:`group_columns`, on
    the projection's weight), ``kernel`` ``[L, 2 Hk dk + Hv dv]`` the
    convolution's, grouped alike, ``g`` and ``beta`` ``[B, T, Hv]`` -> ``o``
    ``[B, T, Hv, dv]``: the depthwise causal convolution, SiLU, ``q`` and
    ``k`` L2-normalised over a head (float32 statistics), ``q`` times ``dk **
    -0.5``, one cast to the compute dtype, and :func:`delta_rule` with the key
    heads read by their groups.  Scopes ``gdn_proj`` (the kernels
    ``gdn_prep_fwd`` / ``gdn_prep_bwd``) and ``gdn_scan`` (``gdn_chunk_fwd`` /
    ``gdn_chunk_bwd`` and the layout of ``g``, ``beta`` and ``o``)."""
    dk, dv, f32 = key_head_dim, value_head_dim, jnp.float32
    T, Hv = g.shape[1:]
    Hk = (x.shape[-1] - Hv * dv) // (2 * dk)
    with jax.named_scope("gdn_proj"):       # [L, Hk, W] -> a group a block
        w = jnp.moveaxis(kernel.astype(f32).reshape(-1, Hk, x.shape[-1] // Hk),
                         1, 0)
    with jax.named_scope("gdn_scan"):
        gamma, bh = _chunked_gates(g, beta, T // CHUNK)
    o = _conv_scan_kernels(x.astype(f32), w, gamma, bh, dk, dv, rows)
    with jax.named_scope("gdn_scan"):
        return jnp.moveaxis(o, 1, 2).astype(dot_dtype())
