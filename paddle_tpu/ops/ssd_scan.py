"""The state-space duality (SSD) recurrence of a Mamba-2 layer, as a chunked
scan.

A head of ``P`` channels keeps a state ``S`` ``[N, P]`` (float32, zero at the
row's start).  Token ``t`` decays it by one scalar and adds the outer product
of the token's ``B`` and its input, and reads it with the token's ``C``::

    S_t = exp(dt_t A) S_{t-1} + B_t (dt_t x_t)^T
    y_t = S_t^T C_t

``A < 0`` is one scalar a head, ``dt_t > 0`` one a head and token; ``B`` and
``C`` ``[N]`` are shared by the heads of a GROUP (head ``h`` reads group ``h //
(H / G)``).  The ``D x_t`` skip and everything else of the layer are the
layer's (``nn.mamba2_mixer``).  Nothing of the reference (2016) has this.

Next to ``ops/delta_rule.py`` this is the other chunk algebra behind the same
grid: a decay a head with NO delta correction, so a chunk needs no solve.
With ``a_i`` the sum of ``dt A`` from the chunk's start to token ``i``, ``L_ij
= exp(a_i - a_j)`` for ``i >= j`` (0 above the diagonal) and ``S`` the state
at the chunk's start, a chunk of ``Q`` tokens is::

    Y     = (L o (C B^T)) (dt x)  +  exp(a) (C S)
    S_end = exp(a_Q) S  +  B^T (exp(a_Q - a) dt x)

Only differences ``a_i - a_j`` with ``i >= j``, sums from the chunk's start
and sums to the chunk's end are exponentiated, all ``<= 0``: nothing is
divided by a decay.  The sums of ``dt A``, the decays, ``dt`` and ``S`` are
float32; the products' operands are in the compute dtype with float32
accumulation.

What is shared with the delta rule and what is not.  Shared: the shape of
the thing (a grid whose last axis walks blocks of chunks with the state
resident in VMEM, every chunk's STARTING state written out for the reverse
walk, which makes the chunk's parts again from it; the forward's outputs kept
across a recomputation block; the chunk's algebra written once on 2-D arrays
and called by both paths; ``delta_rule``'s ``_dot``, ``col_of_row``,
``row_of_col``).  Not shared: the unit of work.  A head here is 64 wide, half
a lane tile, and ``C B^T`` is one product for the heads of a group, so a grid
step takes a GROUP: its heads' channels are the lanes (``[Q, 8 x 64]``), the
state is ``[N, 512]``, the two products with the state and the state's update
are one product each for all eight heads, ``dB`` and ``dC`` come out summed
over the group, and ``x``, ``B``, ``C`` and ``y`` are read and written in the
layer's own layout (tokens by channels: no heads-major copy), as blocks of
the prep kernel's two arrays, x and ``[B | C]``, or of three.  Only the
in-chunk product is a head's own (its ``L``): it runs a lane tile at a time,
each of a tile's heads against the whole tile with the other heads' lanes
masked off the result.

The chunk is :data:`CHUNK` = 128 tokens: ``L`` is then one ``128 x 128`` tile
a head, and the chunks' scalars (``a``, ``dt``: ``[heads, 128]`` a chunk) are
whole lane rows.  The published ``chunk_size`` (128 too) is the source's
kernel choice; the result does not depend on it.  64 would halve ``L`` but
double the states written (the scan is bound by memory, and the states are
its largest array); 256 doubles the exponentials a token.

Two paths behind one gate, :func:`ssd_kernel_chunk`: the Pallas kernels
``ssd_chunk_fwd`` / ``ssd_chunk_bwd`` of ops/pallas_kernels.py under a
``jax.custom_vjp``, and the same algebra in ``jax.numpy`` with a ``lax.scan``
over chunks, differentiated by JAX.  A row the chunk does not divide is
PADDED (a padded token has ``dt = 0``: it leaves the state as it is, and its
output is cut off).

Behind a second gate, :func:`prep_kernel_block`, :func:`conv_ssd_scan` takes
a Mamba-2 mixer from its INPUT to the scan's output on kernels alone: the
in-projection's ``[x | B | C]`` columns go through ``mamba_prep_fwd``
(convolution, bias, SiLU, one cast) where the product wrote them, the scan
reads x and B and C as blocks of that kernel's arrays (x's, and one of ``[B |
C]``; the layer's skip reads x from a third, in the product's dtype as the
chain's does), and in reverse ``mamba_prep_bwd`` takes the scan's three gradients and the skip's; the
in-projection's transpose is then two products (the ``z`` columns' and the
``[x | B | C]`` columns') joined at the weight's size, and nothing is sliced,
joined, padded or cast by XLA at the activations' size in between.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from paddle_tpu.ops.delta_rule import _dot, _iotas, col_of_row, row_of_col
from paddle_tpu.ops.numerics import (acc_dtype, compute_dtype, dot_dtype,
                                     mxu_cast)

__all__ = ["ssd_scan", "ssd_kernel_chunk", "CHUNK", "KERNEL_BLOCK_CHUNKS",
           "chunk_forward", "chunk_backward", "prep_kernel_block",
           "conv_ssd_scan"]

#: tokens per chunk
CHUNK = 128
#: chunks one grid step of the kernels takes (the row is padded to whole
#: blocks)
KERNEL_BLOCK_CHUNKS = 4
#: lanes of a vector register: the in-chunk product goes a tile of heads at
#: a time
_LANES = 128

_HI = lax.Precision.HIGHEST


def ssd_kernel_chunk(T: int, head_dim: int, group_heads: int, state: int):
    """The kernels' gate: the chunk length, or ``None`` for the XLA path.
    Needs the TPU backend, heads that tile the 128 lanes (64 wide, two a
    tile, or a multiple of 128), groups whose channels are whole lane tiles,
    a lane-aligned state width and a row of whole chunks."""
    from paddle_tpu.ops.pallas_kernels import compiled_kernels

    if not compiled_kernels():
        return None
    P = head_dim
    if (_LANES % P and P % _LANES) or (group_heads * P) % _LANES:
        return None
    if state % _LANES or T % CHUNK:
        return None
    return CHUNK


# -- one chunk of one group, on 2-D arrays (both paths) ---------------------

def _head_mask(W: int, P: int, h: int):
    """``[1, W]``: the lanes of head ``h``."""
    lane = lax.broadcasted_iota(jnp.int32, (1, W), 1)
    return (lane >= h * P) & (lane < (h + 1) * P)


def _expand(cols, P: int):
    """Per-head columns ``[Q, 1]`` -> ``[Q, heads * P]``, a head's value on
    each of its lanes."""
    W = len(cols) * P
    out = jnp.broadcast_to(cols[0], (cols[0].shape[0], W))
    for h in range(1, len(cols)):
        out = jnp.where(_head_mask(W, P, h), cols[h], out)
    return out


def _head_sums(z, P: int, heads: int):
    """``[Q, heads * P]`` -> ``[heads, Q]``: the sum over each head's lanes,
    as a product with the heads' indicator (exact at ``highest``)."""
    W = heads * P
    head = lax.broadcasted_iota(jnp.int32, (heads, W), 0)
    lane = lax.broadcasted_iota(jnp.int32, (heads, W), 1)
    ind = ((lane >= head * P) & (lane < (head + 1) * P)).astype(z.dtype)
    return _dot(ind, z, 1, 1, precision=_HI)


def _stack_rows(rows):
    """``[1, Q]`` a head -> ``[heads, Q]`` without a sublane concatenation."""
    n, Q = len(rows), rows[0].shape[1]
    r = lax.broadcasted_iota(jnp.int32, (n, Q), 0)
    out = jnp.broadcast_to(rows[0], (n, Q))
    for h in range(1, n):
        out = jnp.where(r == h, rows[h], out)
    return out


def _tile_heads(P: int, heads: int) -> int:
    """Heads of ``P`` channels that share a lane tile: as many as 128 lanes
    hold (at the kernels' shapes: two of 64), whole tiles over the group."""
    return next(n for n in range(max(1, min(heads, _LANES // P)), 0, -1)
                if heads % n == 0)


def _chunk_parts(x, Bc, Cc, arows, dtrows, P, dt):
    """What a chunk needs that does not wait for the state."""
    f32 = jnp.float32
    heads, Q = arows.shape
    i, j = _iotas(Q)
    CB = _dot(Cc, Bc, 1, 1, dt)                              # [Q, Q]
    acols = [col_of_row(arows[h:h + 1]) for h in range(heads)]
    dcols = [col_of_row(dtrows[h:h + 1]) for h in range(heads)]
    L = [jnp.exp(jnp.where(i >= j, acols[h] - arows[h:h + 1], -jnp.inf))
         for h in range(heads)]
    aE, dtE = _expand(acols, P), _expand(dcols, P)
    alast = aE[Q - 1:Q, :]                                   # [1, W]
    xdt = x.astype(f32) * dtE
    return {"CB": CB, "L": L, "M": [l * CB for l in L], "dtE": dtE,
            "e": jnp.exp(aE), "dec": jnp.exp(alast - aE),
            "ea": jnp.exp(alast), "xdt": xdt}


def _by_tile(W: int, P: int, one_head):
    """``one_head(h, the lanes of its tile)`` for every head, a head's result
    (a tile wide) kept on its own lanes; the tiles side by side -> ``[Q,
    W]``."""
    hp = _tile_heads(P, W // P)
    tw = hp * P
    tiles = []
    for t in range(W // tw):
        lanes = slice(t * tw, (t + 1) * tw)
        acc = one_head(t * hp, lanes)
        for s in range(1, hp):
            acc = jnp.where(_head_mask(tw, P, s), one_head(t * hp + s, lanes),
                            acc)
        tiles.append(acc)
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)


def chunk_forward(x, Bc, Cc, arows, dtrows, S, P, dt):
    """One chunk of one group.  ``x`` ``[Q, heads * P]`` (a head's channels
    contiguous), ``Bc``, ``Cc`` ``[Q, N]``; ``arows`` ``[heads, Q]``: the sums
    of ``dt A`` from the chunk's start; ``dtrows`` ``[heads, Q]``; ``S`` ``[N,
    heads * P]`` float32.  Returns ``(y [Q, heads * P], S_end)``, float32."""
    p = _chunk_parts(x, Bc, Cc, arows, dtrows, P, dt)
    xc = p["xdt"].astype(dt)
    y = _by_tile(x.shape[1], P, lambda h, lanes: _dot(
        p["M"][h], xc[:, lanes], 1, 0, dt))
    y = y + p["e"] * _dot(Cc, S, 1, 0, dt)
    return y, p["ea"] * S + _dot(Bc, p["dec"] * p["xdt"], 0, 0, dt)


def chunk_backward(x, Bc, Cc, arows, dtrows, S, dY, dS1, P, dt):
    """The transpose of :func:`chunk_forward`, by hand, for the kernel:
    ``dY`` ``[Q, heads * P]`` and ``dS1`` (the gradient of the chunk's END
    state) in, ``(dx, dB, dC, da [heads, Q], ddt [heads, Q], dS)`` out,
    float32; ``dB`` and ``dC`` are the group's (summed over its heads).  The
    chunk's parts are made again from ``S``."""
    f32 = jnp.float32
    heads, Q = arows.shape
    W = x.shape[1]
    hp = _tile_heads(P, heads)
    tw = hp * P
    p = _chunk_parts(x, Bc, Cc, arows, dtrows, P, dt)
    e, dec, ea, xdt = p["e"], p["dec"], p["ea"], p["xdt"]
    xc = xdt.astype(dt)
    dYf = dY.astype(f32)
    eY = e * dYf
    BdS = _dot(Bc, dS1, 1, 0, dt)                            # [Q, W]
    CS = _dot(Cc, S, 1, 0, dt)

    dxdt = dec * BdS + _by_tile(W, P, lambda h, lanes: _dot(
        p["M"][h], dY[:, lanes], 0, 0, dt))
    dCB, rows = None, []
    for h in range(heads):
        t, s = divmod(h, hp)
        lanes = slice(t * tw, (t + 1) * tw)
        dYh = dYf[:, lanes] if hp == 1 else jnp.where(
            _head_mask(tw, P, s), dYf[:, lanes], 0.0)
        dM = _dot(dYh, xc[:, lanes], 1, 1, dt)               # [Q, Q]
        part = p["L"][h] * dM
        dCB = part if dCB is None else dCB + part
        G = dM * p["M"][h]            # every L_ij's part: +a_i, -a_j
        rows.append(row_of_col(jnp.sum(G, axis=1, keepdims=True))
                    - jnp.sum(G, axis=0, keepdims=True))
    dC = _dot(dCB, Bc, 1, 0, dt) + _dot(eY, S, 1, 1, dt)
    dB = _dot(dCB, Cc, 0, 0, dt) + _dot(dec * xdt, dS1, 1, 1, dt)
    dS = ea * dS1 + _dot(Cc, eY, 0, 0, dt)

    to_end = xdt * BdS * dec                       # d(dec) dec: -a_j, +a_Q
    z = eY * CS - to_end
    last = (jnp.sum(to_end, axis=0, keepdims=True)
            + ea * jnp.sum(dS1 * S, axis=0, keepdims=True))   # [1, W]
    r = lax.broadcasted_iota(jnp.int32, (Q, 1), 0)
    z = z + jnp.where(r == Q - 1, last, 0.0)
    da = _head_sums(z, P, heads) + _stack_rows(rows)
    ddt = _head_sums(dxdt * x.astype(f32), P, heads)
    return dxdt * p["dtE"], dB, dC, da, ddt, dS


# -- the XLA path -----------------------------------------------------------

def _scan_xla(x, Bm, Cm, a, dt, P):
    """``x`` ``[B, G, n, Q, W]``, ``Bm``, ``Cm`` ``[B, G, n, Q, N]`` in the
    compute dtype, ``a``, ``dt`` ``[B, G, n, heads, Q]`` float32 -> ``[B, G,
    n, Q, W]`` float32: a ``lax.scan`` over the chunks, every group at once."""
    cd = x.dtype

    def one(S, c):
        xc, bc, cc, ac, dc = c
        return chunk_forward(xc, bc, cc, ac, dc, S, P, cd)[::-1]

    chunks_first = tuple(jnp.moveaxis(z, 2, 0) for z in (x, Bm, Cm, a, dt))
    S0 = jnp.zeros(x.shape[:2] + (Bm.shape[-1], x.shape[-1]), jnp.float32)
    _, y = lax.scan(jax.vmap(jax.vmap(one)), S0, chunks_first)
    return jnp.moveaxis(y, 0, 2)


# -- the kernels' path ------------------------------------------------------

@jax.custom_vjp
def _scan_kernels(x, Bm, Cm, a, dt):
    """``x`` ``[B, T, H P]``, ``Bm``, ``Cm`` ``[B, T, G N]`` (any float
    dtype; cast to the compute dtype inside, gradients come back in theirs),
    ``a``, ``dt`` ``[B, G, n, heads, Q]`` float32 -> ``y`` ``[B, T, H P]`` in
    the compute dtype."""
    return _scan_kernels_fwd(x, Bm, Cm, a, dt)[0]


def _scan_kernels_fwd(x, Bm, Cm, a, dt):
    from paddle_tpu.ops.pallas_kernels import ssd_chunk_fwd_pallas

    like = tuple(jnp.zeros((0,), z.dtype) for z in (x, Bm, Cm))
    x, Bm, Cm = (z.astype(compute_dtype()) for z in (x, Bm, Cm))
    y, states = ssd_chunk_fwd_pallas(x, Bm, Cm, a, dt)
    # kept across a recomputation block, as attention's output is: the
    # backward's second forward makes the projections again, not the scan
    y, states = (checkpoint_name(z, "remat_keep") for z in (y, states))
    return y, (x, Bm, Cm, a, dt, states, like)


def _scan_kernels_bwd(res, dy):
    from paddle_tpu.ops.pallas_kernels import ssd_chunk_bwd_pallas

    x, Bm, Cm, a, dt, states, like = res
    dx, dB, dC, da, ddt = ssd_chunk_bwd_pallas(x, Bm, Cm, a, dt, states,
                                               dy.astype(x.dtype))
    return (*(g.astype(z.dtype) for g, z in zip((dx, dB, dC), like)),
            da, ddt)


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


def _chunked_scalars(z, G: int, n: int):
    """``[B, T, H]`` (``T = n`` chunks) -> ``[B, G, n, H / G, CHUNK]``."""
    B, _, H = z.shape
    return jnp.transpose(z.reshape(B, n, CHUNK, G, H // G), (0, 3, 1, 4, 2))


def _chunk_sums(dt, A, G: int, n: int):
    """``dt`` ``[B, n CHUNK, H]`` float32, ``A`` ``[H]`` -> the sums of ``dt
    A`` from each chunk's start and ``dt``, ``[B, G, n, H / G, CHUNK]``."""
    return (jnp.cumsum(_chunked_scalars(dt * A.astype(dt.dtype), G, n),
                       axis=-1), _chunked_scalars(dt, G, n))


def ssd_scan(x, Bm, Cm, dt, A):
    """The SSD recurrence over a row: ``x`` ``[B, T, H, P]``, ``Bm``, ``Cm``
    ``[B, T, G, N]``, ``dt`` ``[B, T, H]`` (a token's step, ``> 0``), ``A``
    ``[H]`` (``< 0``) -> ``y`` ``[B, T, H, P]``; ``H`` is whole groups over
    ``G`` and head ``h`` reads group ``h // (H // G)``.  The state is zero at
    the row's start.  bf16 operands under the default policy; ``dt``, the
    sums of ``dt A``, the decays and the state in float32."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    if H % G:
        raise ValueError(f"{H} heads are not whole groups over {G}")
    f32 = acc_dtype()
    kernels = ssd_kernel_chunk(T, P, H // G, N) is not None
    n = -(-T // CHUNK)
    if kernels and n > KERNEL_BLOCK_CHUNKS:
        n = -(-n // KERNEL_BLOCK_CHUNKS) * KERNEL_BLOCK_CHUNKS
    pad = n * CHUNK - T

    def rows(z):            # [B, T, ...] -> [B, n CHUNK, flat]
        z = z.reshape(B, T, -1)
        return jnp.pad(z, ((0, 0), (0, pad), (0, 0))) if pad else z

    a, dtc = _chunk_sums(rows(dt.astype(f32)), A, G, n)
    xf, Bf, Cf = rows(x), rows(Bm), rows(Cm)
    if kernels:
        y = _scan_kernels(xf, Bf, Cf, a, dtc)
    else:
        cd = compute_dtype()

        def grouped(z):     # [B, n Q, G w] -> [B, G, n, Q, w]
            return jnp.moveaxis(z.astype(cd).reshape(B, n, CHUNK, G, -1),
                                3, 1)

        y = _scan_xla(grouped(xf), grouped(Bf), grouped(Cf), a, dtc, P)
        y = jnp.moveaxis(y, 1, 3).reshape(B, n * CHUNK, H * P)
    return y[:, :T].reshape(B, T, H, P).astype(dot_dtype())


# -- from the mixer's input to the scan's output, on kernels alone -----------

def prep_kernel_block(T: int, heads: int, head_dim: int, groups: int,
                      state: int, taps: int, offset: int):
    """The gate of ``mamba_prep_fwd`` / ``mamba_prep_bwd``: ``(rows, columns)``
    of a block, or ``None`` for the layer's ``jax.numpy`` chain.  ``offset``
    is the in-projection's column where ``[x | B | C]`` start.  Needs the
    scan's gate open (the TPU backend, heads that tile the lanes, a
    lane-aligned state, a row of whole chunks), ``H P``, ``G N`` and the
    offset multiples of the 128 lanes, a convolution whose history fits the
    halo, a row that the scan does not pad and that whole blocks divide, and
    a block within the kernels' VMEM."""
    from paddle_tpu.ops.pallas_kernels import (GDN_PREP_HALO,
                                               GDN_PREP_SUB_ROWS,
                                               GDN_PREP_VMEM_LIMIT_BYTES)

    if heads % groups or ssd_kernel_chunk(
            T, head_dim, heads // groups, state) is None:
        return None
    HP, GN, n = heads * head_dim, groups * state, T // CHUNK
    if offset % _LANES or not 1 <= taps <= GDN_PREP_HALO + 1:
        return None
    if n > KERNEL_BLOCK_CHUNKS and n % KERNEL_BLOCK_CHUNKS:
        return None
    cols = next(c for c in (512, 256, _LANES)
                if not (offset % c or HP % c or GN % c))
    # a column of the reverse kernel's block, float32 at the widest: the
    # projection, four gradients and the result, each in two buffers, and
    # three scratch copies; as much again is left for the body's temporaries
    row_bytes = 4 * (2 * 6 + 3) * cols
    for rows in (512, 256, GDN_PREP_SUB_ROWS):
        if T % rows == 0 and 2 * rows * row_bytes <= GDN_PREP_VMEM_LIMIT_BYTES:
            return rows, cols
    return None


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _proj_conv_scan(u, w, wb, a, dt, inner, block):
    return _proj_conv_scan_fwd(u, w, wb, a, dt, inner, block)[0]


def _proj_conv_scan_fwd(u, w, wb, a, dt, inner, block):
    from paddle_tpu.ops.matmul import linear
    from paddle_tpu.ops.pallas_kernels import (mamba_prep_fwd_pallas,
                                               ssd_chunk_fwd_pallas)

    with jax.named_scope("mamba_proj"):
        zxbc = linear(u, w)
        xc, bc, x = mamba_prep_fwd_pallas(
            zxbc, wb, offset=inner, width=inner, rows=block[0],
            cols=block[1], out_dtype=compute_dtype())
        z = zxbc[..., :inner]
    with jax.named_scope("ssd_scan"):
        y, states = ssd_chunk_fwd_pallas(xc, bc, None, a, dt)
        # kept across a recomputation block, as in ``_scan_kernels_fwd``
        y, states = (checkpoint_name(v, "remat_keep") for v in (y, states))
    return (z, x, y), (u, w, wb, zxbc, xc, bc, a, dt, states)


def _proj_conv_scan_bwd(inner, block, res, cts):
    from paddle_tpu.ops.pallas_kernels import (mamba_prep_bwd_pallas,
                                               ssd_chunk_bwd_pallas)

    u, w, wb, zxbc, xc, bc, a, dt, states = res
    dz, dskip, dy = cts
    with jax.named_scope("ssd_scan"):
        dx, dB, dC, da, ddt = ssd_chunk_bwd_pallas(
            xc, bc, None, a, dt, states, dy.astype(xc.dtype))
    with jax.named_scope("mamba_proj"):
        dpre, dwb = mamba_prep_bwd_pallas(
            zxbc, wb, dx, dskip, dB, dC, offset=inner, rows=block[0],
            cols=block[1], out_dtype=xc.dtype)
        # the in-projection's transpose as ``linear``'s own (operands in the
        # compute dtype, gradients rounded to it), a product pair for z's
        # columns and one for [x | B | C]'s: what is joined has the weight's
        # size, and the halves of du are summed in float32 and rounded ONCE,
        # as the chain's one product rounds it
        uc, wc = mxu_cast(u, w)
        f32, rows = acc_dtype(), tuple(range(u.ndim - 1))
        du, dw = 0.0, []
        for g, wh in ((dz, wc[:, :inner]), (dpre, wc[:, inner:])):
            du = du + lax.dot_general(g, wh, (((u.ndim - 1,), (1,)), ((), ())),
                                      preferred_element_type=f32)
            dw.append(lax.dot_general(uc, g, ((rows, rows), ((), ())),
                                      preferred_element_type=f32))
        return (du.astype(uc.dtype).astype(u.dtype),
                jnp.concatenate(dw, axis=1).astype(wc.dtype).astype(w.dtype),
                dwb, da, ddt)


_proj_conv_scan.defvjp(_proj_conv_scan_fwd, _proj_conv_scan_bwd)


def conv_ssd_scan(u, w, kernel, bias, dt, A, *, groups: int, block):
    """A Mamba-2 mixer from its input to the scan's output, where
    :func:`prep_kernel_block` gave ``block``: ``u`` ``[B, T, D]``, ``w`` ``[D,
    H P + (H P + 2 G N)]`` the in-projection's ``[z | x | B | C]`` columns,
    ``kernel`` ``[L, H P + 2 G N]`` and ``bias`` the convolution's, ``dt``
    ``[B, T, H]`` float32 (``> 0``), ``A`` ``[H]`` (``< 0``) -> ``(z [B, T, H
    P]`` as the product leaves it, ``x`` and ``y`` ``[B, T, H, P]``: the
    convolved x in the PRODUCT's dtype for the layer's skip, as the chain
    hands it on (float32 unless ``--amp``), and the recurrence's output as
    :func:`ssd_scan` returns it).  ``u w`` is ONE product; ``[x | B | C]``
    are convolved, biased and SiLU'd in float32 and cast once to the compute
    dtype by ``mamba_prep_fwd``, the scan's kernels read them as blocks of
    its arrays (x's and ``[B | C]``'s), and the skip's x is that kernel's
    third output, so its gradient comes back in x's own dtype too.  Scopes
    ``mamba_proj`` (the product, its transpose and the kernels
    ``mamba_prep_fwd`` / ``mamba_prep_bwd``) and ``ssd_scan``
    (``ssd_chunk_fwd`` / ``ssd_chunk_bwd``, the sums of ``dt A`` and the
    scalars' chunked layout)."""
    B, T, H = dt.shape
    f32 = acc_dtype()
    with jax.named_scope("mamba_proj"):
        wb = jnp.concatenate([kernel.astype(f32), bias.astype(f32)[None]])[None]
    with jax.named_scope("ssd_scan"):
        a, dtc = _chunk_sums(dt.astype(f32), A, groups, T // CHUNK)
    z, x, y = _proj_conv_scan(u, w, wb, a, dtc, w.shape[1] - wb.shape[2],
                              block)
    with jax.named_scope("ssd_scan"):
        y = y.reshape(B, T, H, -1).astype(dot_dtype())
    return z, x.reshape(B, T, H, -1), y
