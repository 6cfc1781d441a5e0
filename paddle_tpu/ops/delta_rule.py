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
stays in VMEM; the forward writes every chunk's starting state out, and the
backward walks the chunks in reverse with ``dS`` resident, recomputing a
chunk's ``T``, ``R`` and ``Vn`` from the saved state); and the same algebra
in ``jax.numpy`` with a ``lax.scan`` over chunks, differentiated by JAX.
The chunk's algebra is written once, on 2-D arrays, and both paths call it
(:func:`chunk_forward`; the kernels' :func:`chunk_backward` is its
transpose by hand).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from paddle_tpu.ops.numerics import acc_dtype, compute_dtype, dot_dtype

__all__ = ["delta_rule", "delta_rule_kernel_chunk", "CHUNK",
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


def _chunk_parts(q, k, gcol, grow, bcol, dt):
    """What a chunk needs that does not wait for the state."""
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
            "T": _unit_lower_inverse(A),
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
    float32.  Returns ``(o [C, dv], S_end)``, float32."""
    p = _chunk_parts(q, k, gcol, grow, bcol, dt)
    qf, kf, _, _, _, Vn = _chunk_state_parts(p, q, k, v, bcol, S, dt)
    o = _dot(p["e"] * qf, S, 1, 0, dt) + _dot(p["P"], Vn, 1, 0, dt)
    return o, p["a"] * S + _dot(p["ec"] * kf, Vn, 0, 0, dt)


def chunk_backward(q, k, v, gcol, grow, bcol, S, dO, dS1, dt):
    """The transpose of :func:`chunk_forward`, by hand, for the kernel:
    ``dO`` ``[C, dv]`` and ``dS1`` (the gradient of the chunk's END state)
    in, ``(dq, dk, dv, dgamma_col [C, 1], dgamma_row [1, C], dgamma_last
    [1, 1], dbeta_col, dS)`` out, float32.  The gradient of the chunk's sums
    of ``g`` comes in three parts: ``dgamma_col + transpose(dgamma_row)``,
    and ``dgamma_last`` for the last token's alone.  ``T``, ``R`` and ``Vn``
    are made again from ``S``."""
    p = _chunk_parts(q, k, gcol, grow, bcol, dt)
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
        return chunk_forward(qc, kc, vc, gc[:, None], gc[None, :],
                             bc[:, None], S, dt)[::-1]

    chunks_first = tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v, gamma, beta))
    S0 = jnp.zeros((B, H, q.shape[-1], v.shape[-1]), jnp.float32)
    _, o = lax.scan(jax.vmap(jax.vmap(one)), S0, chunks_first)   # over B, H
    return jnp.moveaxis(o, 0, 2)


# -- the kernels' path ------------------------------------------------------

@jax.custom_vjp
def _scan_kernels(q, k, v, gamma, beta):
    return _scan_kernels_fwd(q, k, v, gamma, beta)[0]


def _scan_kernels_fwd(q, k, v, gamma, beta):
    from paddle_tpu.ops.pallas_kernels import gdn_chunk_fwd_pallas

    o, states = gdn_chunk_fwd_pallas(q, k, v, gamma, beta)
    # kept across a recomputation block, as attention's output is: the
    # backward's second forward makes the projections again, not the scan
    o, states = (checkpoint_name(a, "remat_keep") for a in (o, states))
    return o, (q, k, v, gamma, beta, states)


def _scan_kernels_bwd(res, do):
    from paddle_tpu.ops.pallas_kernels import gdn_chunk_bwd_pallas

    q, k, v, gamma, beta, states = res
    return gdn_chunk_bwd_pallas(q, k, v, gamma, beta, states,
                                do.astype(q.dtype))


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


def delta_rule(q, k, v, g, beta):
    """The gated delta rule over a row: ``q``, ``k`` ``[B, T, H, dk]`` (the
    caller has normalised and scaled them), ``v`` ``[B, T, H, dv]``, ``g``
    (the log of a token's decay, ``<= 0``) and ``beta`` ``[B, T, H]`` ->
    ``o`` ``[B, T, H, dv]``.  The state is zero at the row's start.  bf16
    operands under the default policy; ``g``'s sums, the solve inside a
    chunk and the state in float32."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    f32 = acc_dtype()
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

    cd = compute_dtype()
    qh, kh, vh = (heads_major(a, cd) for a in (q, k, v))
    gh, bh = (heads_major(a, f32).reshape(B, H, N, CHUNK) for a in (g, beta))
    gamma = jnp.cumsum(gh, axis=-1)
    if kernels:
        o = _scan_kernels(qh, kh, vh, gamma, bh)
    else:
        o = _scan_xla(*(a.reshape(B, H, N, CHUNK, a.shape[-1])
                        for a in (qh, kh, vh)), gamma, bh)
        o = o.reshape(B, H, N * CHUNK, dv)
    return jnp.moveaxis(o[:, :, :T], 1, 2).astype(dot_dtype())
