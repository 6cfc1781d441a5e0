"""Fused-backward GRU/LSTM sequence ops.

Same restructuring as ops/attention_decoder.py, applied to the plain
recurrent layers (the encoder of the seq2seq flagship, stacked LSTM/GRU text
models).  Three structural changes vs XLA's autodiff of the time scan:

1. The forward (Pallas kernel or masked lax.scan — one numerics source of
   truth either way) SAVES the per-step pre-activations ``z`` and the held
   carries ``h_prev``/``c_prev``.  The backward therefore needs NO forward
   replay scan: the time-sequential work drops from three T-length loops
   per layer (fwd + replay + reverse) to two (fwd + reverse), and the
   reverse step recomputes gates from ``z`` with pure elementwise math —
   its only matmul is the unavoidable ``d_z @ w_h^T`` carry propagation.
2. The recurrent weight gradient is NOT dragged through the scan: the
   reverse loop emits the small per-step cotangents ``d_z`` and ``d_w_h``
   is reconstructed afterwards as one batched MXU contraction
   (``einsum('tbh,tbz->hz', h_prev, d_z)``), which also serves as ``d_xp``
   directly since the input projection enters the cell additively.
3. (LSTM) The gradients that reduce ``d_z`` over (time, batch) to the size of
   a parameter, the bias's and the three peepholes', are accumulated inside
   the reverse Pallas kernel, where ``d_z``, ``c_prev`` and ``c_new`` are in
   VMEM anyway; XLA would stream ``d_z`` from HBM once more for each.  That is
   why the op takes the bias.  The lax.scan backward (CPU, boot states, shapes
   past the gate) leaves them to XLA as one batched reduction each.
4. (LSTM) The op takes the input projection's operands (``x``, ``w_x``)
   when the caller has them, and then every reader of ``d_z`` outside the
   reverse loop is a matrix product the op itself writes: ``d_w_h``, and
   ``dx`` and ``d_w_x``, the transpose of its own ``linear(x, w_x)``.  All
   three round their operands to the compute dtype, and the float32 readers
   (the bias's and the peepholes' reductions, the carry product) are inside
   the loop since point 3, so the reverse kernel then stores ``d_z`` in
   ``residual_dtype(H)`` like the streams it reads (``z``, ``h_prev``,
   ``c_prev``): bf16 under the production policy at H <= 512, half the
   stream that the loop writes once and the products read three times.
   Rounded once where it is produced, the products get the operand they
   would have rounded it to.  A caller that passes the projection itself
   (``w_x=None``: ``lstmemory(projected_input=True)``, whose ``d_xp`` goes on
   into whatever made ``xp``, a bias's reduction or an activation's backward)
   gets ``d_xp`` in float32, unrounded, as does the lax.scan backward, where
   narrowing would be one more pass and save none.  The GRU's ``d_xp`` stays
   float32: its bias gradient is still an XLA reduction over it.
5. (LSTM) Given the same operands, the forward kernel makes the input
   projection too: it takes ``x`` time-major [T,B,D] as the layer got it,
   ``w_x`` [D,4H] (resident, like ``w_h``) and ``b``, and forms
   ``z_t = (x_t W_x + b) + h_{t-1} W_h`` itself: the operands, the float32
   accumulation and the two float32 additions of ``linear()`` and the carry
   product, in their order.  The [T,B,4H] float32 projection, which XLA
   would write and the kernel read straight back, never crosses HBM, and
   nothing else wants it: the backward works from ``z``, ``h_prev``,
   ``c_prev`` and from ``x`` itself, and ``jax.vjp(linear, x, w_x)`` with
   its primal result unread is two transposes and no product.  A layer
   stacked on another reads that layer's [T,B,H] kernel output where it
   lies.  The kernel is handed the projection, as before, by a caller
   that made it (``w_x=None``: ``lstmemory(projected_input=True)``,
   ``lstm_forward_pallas``), and where ``rnn_kernel_ok(proj_dim=D)`` says
   no: below B = D/4 rows, where passing [D,4H] through the MXU on every
   time step costs more than the [B,4H] block's way through HBM (measured
   on the v5e: PERF.md section 6, PR 34), or where ``w_x`` does not fit
   VMEM beside ``w_h``.  The lax.scan path (CPU, a boot state, ``reset``, a
   shape past the gate, ``xla_paths_only()``) makes it with ``linear()``.

Semantics match ``scan_rnn`` + ``gru_step``/``lstm_step`` exactly (carry
held and outputs zeroed at masked steps); equivalence is pinned by
tests/test_rnn_fused.py.

Reference analog: the fused CUDA cells hl_cuda_lstm.cu:26-58 /
hl_gru_ops.cuh — the reference hand-writes both directions of its hot
recurrent kernels; this is the TPU rendition of the backward half.

Tradeoff: custom_vjp ops do not support forward-mode autodiff (jvp/jacfwd
through a default-cell layer raises) — reverse-mode (grad/vjp), the only
mode the trainer and checkgrad use, is unaffected.  Pass a non-default
activation to route through the plain scan if forward-mode is ever needed.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.ops.matmul import linear

__all__ = ["gru_sequence_fused", "lstm_sequence_fused", "rnn_kernel_ok"]


def residual_dtype(hidden: int):
    """Dtype of the streams the kernel pair exchanges with HBM: the
    z/h_prev/c_prev residuals and, where the LSTM op owns the input
    projection, the reverse kernel's d_z (module docstring, point 4).  bf16
    under the prod compute policy for H <= 512 (halves that HBM traffic and
    the VMEM those blocks take), f32 otherwise — bf16 streams at H > 512
    have not been compiled or measured on the installed stack."""
    from paddle_tpu.ops.numerics import compute_dtype

    cd = compute_dtype()
    return cd if (cd == jnp.bfloat16 and hidden <= 512) else jnp.float32


# amp-aware backward matmul/einsum policy — shared with the hand-written
# attention-decoder backward (ops/numerics.bwd_mm/bwd_einsum): f32
# operands by default, bf16 operands + f32 accumulation under --amp
from paddle_tpu.ops.numerics import bwd_einsum as _bwd_einsum  # noqa: E402
from paddle_tpu.ops.numerics import bwd_mm as _bwd_mm  # noqa: E402


def rnn_kernel_ok(batch: int, hidden: int, gates: int, *,
                  backward: bool = False,
                  proj_dim: int | None = None) -> bool:
    """The recurrent time-loop kernels' gate, forward and (``backward``)
    reverse: True for the Pallas kernel, False for the lax.scan path.
    ``gates``: 4 = LSTM, 3 = GRU.  ``proj_dim`` = D asks for the LSTM
    forward kernel that makes the input projection of a [B,T,D] input
    itself (module docstring, point 5): refused where the resident [D,4H]
    input matrix does not fit beside the recurrent one, and the forward then
    asks again without it.  Needs the TPU backend and tile-aligned shapes:
    the kernels slice gate blocks out of [B, gates*H], so H must fill whole
    128-lane tiles and B whole 8-sublane tiles or Mosaic rejects the
    lowering.  The callers see to the rest: the default-activation cell and
    a zero boot state (peepholes are supported in-kernel; reverse rides a
    flip upstream)."""
    from paddle_tpu.ops.pallas_kernels import (RNN_VMEM_LIMIT_BYTES,
                                               compiled_kernels,
                                               rnn_vmem_bytes)

    if not compiled_kernels():
        return False
    if hidden % 128 != 0 or batch % 8 != 0:
        return False
    # the kernel's in-register/VMEM temporaries grow with the [B, gates*H]
    # step tile and are not part of the estimate below: B*H = 384*512 (the
    # flagship's encoder) is the largest tile the kernels are compiled at
    # (tests/test_tpu_compile.py); beyond it the scan path runs
    if batch * hidden > 384 * 512:
        return False
    # the kernel's own projection streams the [D, 4H] input matrix through
    # the MXU on every time step, which costs the same for any B up to about
    # 270 rows, and spares the [B, 4H] block's way out to HBM and back,
    # which grows with B: measured on the v5e, it loses 40% at B = D/8
    # (B64 H512 D512, B16 H1024 D1024) and wins from B = D/4 on (PERF.md
    # section 6, PR 34)
    if proj_dim is not None and 4 * batch < proj_dim:
        return False
    # what the kernel keeps in VMEM (the resident [H, gates*H] weight grows
    # with H^2, the per-step blocks with B*H; the reverse kernel's z + d_z
    # blocks make its working set the larger) must fit the scoped limit the
    # kernels ask for
    need = rnn_vmem_bytes(
        batch, hidden, gates, backward=backward,
        residual_itemsize=jnp.dtype(residual_dtype(hidden)).itemsize,
        proj_dim=proj_dim)
    return need <= RNN_VMEM_LIMIT_BYTES


# ---------------------------------------------------------------------------
# GRU
# ---------------------------------------------------------------------------


def _gru_fwd_scan(xp, mask, w_h, h0):
    """Masked forward scan; xp [B,T,3H], mask [B,T] -> (h_seq [B,T,H],
    h_fin, z [T,B,3H] pre-activations, hprev [T,B,H]).
    Mirrors scan_rnn(gru_step) numerics (bf16 matmul operands in linear).
    Residuals are stored in ``residual_dtype(H)`` (bf16 under the
    production policy for H <= 512, f32 otherwise and in tests): they
    exist only to recompute gates in the backward, and halving their HBM
    stream is worth the rounding — gradients become approximate at bf16's
    0.4% ULP, standard mixed precision practice."""
    H = w_h.shape[0]
    rd = residual_dtype(H)
    xp_tb = jnp.moveaxis(xp, 1, 0)
    m_tb = jnp.moveaxis(mask, 1, 0)

    def step(h, inp):
        xp_t, m_t = inp
        zr = xp_t[..., : 2 * H] + linear(h, w_h[:, : 2 * H])
        r, u = jnp.split(jax.nn.sigmoid(zr), 2, axis=-1)
        zc = xp_t[..., 2 * H:] + linear(r * h, w_h[:, 2 * H:])
        cand = jnp.tanh(zc)
        h_new = u * h + (1.0 - u) * cand
        keep = (m_t > 0)[:, None]
        h_out = jnp.where(keep, h_new, h)
        z = jnp.concatenate([zr, zc], -1)
        return h_out, (h_out * m_t[:, None].astype(h_out.dtype),
                       z.astype(rd), h.astype(rd))

    h_fin, (outs, z_tb, hprev_tb) = lax.scan(step, h0, (xp_tb, m_tb))
    # residuals leave TIME-major [T,B,*] — one fixed layout contract with
    # the backward regardless of which path produced them (the kernels are
    # time-major too: Mosaic wants the last two block dims tile-aligned)
    return jnp.moveaxis(outs, 0, 1), h_fin, z_tb, hprev_tb


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def gru_sequence_fused(xp, mask, w_h, h0, allow_pallas=False):
    """GRU over a padded batch given the input projection ``xp`` [B,T,3H].
    ``allow_pallas`` (static) lets the forward use the Pallas time-loop
    kernel — only legal when the caller statically knows h0 is zeros (the
    kernel boots from zeros)."""
    # primal-only call (inference, no grad pending): skip the residuals —
    # the Pallas outputs would be materialized to HBM even if unused
    h_seq, h_fin = _gru_core_fwd(xp, mask, w_h, h0, allow_pallas,
                                 residuals=False)[:2]
    return h_seq, h_fin


def _gru_core_fwd(xp, mask, w_h, h0, allow_pallas, *, residuals=True):
    if allow_pallas:
        B, T, H3 = xp.shape
        H = H3 // 3
        if rnn_kernel_ok(B, H, 3):
            from paddle_tpu.ops.pallas_kernels import _gru_pallas_raw

            xp_tb = jnp.moveaxis(xp.astype(jnp.float32), 1, 0)
            m_tb = jnp.moveaxis(mask.astype(jnp.float32), 1, 0)
            outs = _gru_pallas_raw(xp_tb, m_tb, w_h.astype(jnp.float32),
                                   residuals=residuals)
            h_tb, h_fin = outs[0], outs[1]
            z_r, hprev_r = (outs[2], outs[3]) if residuals else (None, None)
            return jnp.moveaxis(h_tb, 0, 1), h_fin, z_r, hprev_r
    out = _gru_fwd_scan(xp, mask, w_h, h0)
    return out if residuals else (out[0], out[1], None, None)


def _gru_seq_fwd(xp, mask, w_h, h0, allow_pallas):
    h_seq, h_fin, z_tb, hprev_tb = _gru_core_fwd(xp, mask, w_h, h0,
                                                 allow_pallas)
    # zero-size sentinels carry the caller dtypes through the residual
    # pytree (dtype objects are not valid JAX residuals)
    meta = (jnp.zeros((0,), xp.dtype), jnp.zeros((0,), h0.dtype))
    return (h_seq, h_fin), (mask, w_h, z_tb, hprev_tb, meta)


def _gru_seq_bwd(allow_pallas, res, ct):
    mask, w_h, z_r, hprev_r, (xp_s, h0_s) = res
    xp_dtype, h0_dtype = xp_s.dtype, h0_s.dtype
    d_hseq, d_hfin = ct
    H = w_h.shape[0]
    B = mask.shape[0]
    f32 = jnp.float32
    w_f = w_h.astype(f32)

    hp_f = hprev_r.astype(f32)                   # residuals are [T,B,*]
    if allow_pallas and rnn_kernel_ok(B, H, 3, backward=True):
        from paddle_tpu.ops.pallas_kernels import _gru_bwd_pallas_raw

        # residual streams enter the kernel in their STORED dtype (bf16
        # under the prod policy) — casting happens per-block in VMEM
        d_xp_tb, d_h0 = _gru_bwd_pallas_raw(
            jnp.moveaxis(d_hseq, 1, 0).astype(f32),
            jnp.moveaxis(mask, 1, 0).astype(f32),
            z_r, hprev_r, w_f.T.copy(), d_hfin.astype(f32))
    else:
        m_tb = jnp.moveaxis(mask, 1, 0)
        d_out_tb = jnp.moveaxis(d_hseq, 1, 0).astype(f32)
        # gates recomputed from the SAVED pre-activations, vectorized over
        # all timesteps at once (pure elementwise — XLA fuses; no replay)
        z_f = z_r.astype(f32)
        ru = jax.nn.sigmoid(z_f[..., : 2 * H])
        r = ru[..., :H]
        u = ru[..., H:]
        cand = jnp.tanh(z_f[..., 2 * H:])

        def rev_step(d_c, inp):
            d_out_t, m_t, r_t, u_t, cand_t, hp_t = inp
            mcol = (m_t > 0)[:, None].astype(f32)
            d_hnew = mcol * (d_out_t + d_c)
            d_u = d_hnew * (hp_t - cand_t)
            d_cand = d_hnew * (1.0 - u_t)
            d_hp = d_hnew * u_t
            d_zc = d_cand * (1.0 - cand_t * cand_t)
            d_rh = _bwd_mm(d_zc, w_f[:, 2 * H:].T)
            d_r = d_rh * hp_t
            d_hp = d_hp + d_rh * r_t
            d_zr = jnp.concatenate(
                [d_r * r_t * (1 - r_t), d_u * u_t * (1 - u_t)], -1)
            d_hp = d_hp + _bwd_mm(d_zr, w_f[:, : 2 * H].T)
            d_xp_t = jnp.concatenate([d_zr, d_zc], -1)
            d_c_out = (1.0 - mcol) * d_c + d_hp
            return d_c_out, d_xp_t

        d_h0, d_xp_tb = lax.scan(
            rev_step, d_hfin.astype(f32),
            (d_out_tb, m_tb, r, u, cand, hp_f), reverse=True)

    # shared tail — batched weight gradient: zr part against h_prev, cand
    # part against r*h (ONE copy for both reverse-loop implementations)
    rh = jax.nn.sigmoid(z_r[..., :H].astype(f32)) * hp_f
    d_w_gates = _bwd_einsum("tbh,tbz->hz", hp_f, d_xp_tb[..., : 2 * H])
    d_w_cand = _bwd_einsum("tbh,tbz->hz", rh, d_xp_tb[..., 2 * H:])
    d_wh = jnp.concatenate([d_w_gates, d_w_cand], axis=1).astype(w_h.dtype)
    d_xp = jnp.moveaxis(d_xp_tb, 0, 1).astype(xp_dtype)
    return d_xp, None, d_wh, d_h0.astype(h0_dtype)


gru_sequence_fused.defvjp(_gru_seq_fwd, _gru_seq_bwd)


# ---------------------------------------------------------------------------
# LSTM
# ---------------------------------------------------------------------------


def _lstm_fwd_scan(xp, mask, w_h, h0, c0, pi, pf, po):
    """Masked forward scan; xp [B,T,4H] (gate order i,f,o,g as lstm_step),
    pi/pf/po [H] peephole ("check") vectors (zeros = plain cell)
    -> (h_seq, h_fin, c_fin, z [T,B,4H] PRE-peephole, hprev, cprev) —
    residuals in ``residual_dtype(H)`` (see _gru_fwd_scan)."""
    H = w_h.shape[0]
    rd = residual_dtype(H)
    xp_tb = jnp.moveaxis(xp, 1, 0)
    m_tb = jnp.moveaxis(mask, 1, 0)

    def step(carry, inp):
        h, c = carry
        xp_t, m_t = inp
        z = xp_t + linear(h, w_h)
        i = jax.nn.sigmoid(z[..., :H] + pi * c)
        f = jax.nn.sigmoid(z[..., H: 2 * H] + pf * c)
        g = jnp.tanh(z[..., 3 * H:])
        c_new = f * c + i * g
        o = jax.nn.sigmoid(z[..., 2 * H: 3 * H] + po * c_new)
        h_new = o * jnp.tanh(c_new)
        keep = (m_t > 0)[:, None]
        h_out = jnp.where(keep, h_new, h)
        c_out = jnp.where(keep, c_new, c)
        return ((h_out, c_out),
                (h_out * m_t[:, None].astype(h_out.dtype),
                 z.astype(rd), h.astype(rd), c.astype(rd)))

    (h_fin, c_fin), (outs, z_tb, hprev_tb, cprev_tb) = lax.scan(
        step, (h0, c0), (xp_tb, m_tb))
    # residuals leave TIME-major (layout contract with the backward)
    return (jnp.moveaxis(outs, 0, 1), h_fin, c_fin,
            z_tb, hprev_tb, cprev_tb)


@partial(jax.custom_vjp, nondiff_argnums=(9, 10))
def lstm_sequence_fused(x, b, mask, w_h, h0, c0, pi, pf, po,
                        allow_pallas=False, has_peepholes=True, w_x=None):
    """LSTM over a padded batch given the layer input ``x`` [B,T,D] and the
    input matrix ``w_x`` [D,4H], or, with ``w_x=None``, the input projection
    WITHOUT its bias as ``x`` [B,T,4H]; and the bias ``b`` [4H]: the op adds
    the bias itself, so that its backward can hand back ``d_b`` from the
    reverse kernel's accumulator and XLA never reads ``d_z`` for it.  Given
    ``w_x`` the op makes the projection (``linear(x, w_x)``) and its
    backward the two products that transpose it, so that ``d_z`` can cross
    HBM narrow between them (module docstring, point 4).
    pi/pf/po: [H] peephole vectors (pass zeros for the plain cell — the
    math degenerates exactly).  ``has_peepholes`` (static) lets the
    backward skip the d_peep reductions when the caller statically knows
    the peepholes are zeros."""
    # primal-only call (inference): residual-free variant — see GRU twin
    h_seq, h_fin, c_fin = _lstm_core_fwd(x, w_x, b, mask, w_h, h0, c0, pi,
                                         pf, po, allow_pallas,
                                         residuals=False)[:3]
    return h_seq, h_fin, c_fin


def _lstm_core_fwd(x, w_x, b, mask, w_h, h0, c0, pi, pf, po, allow_pallas, *,
                   residuals=True, xp=None):
    """``xp``: ``linear(x, w_x)`` where the caller has made it already (the
    VJP's forward, which keeps its transpose)."""
    from paddle_tpu.ops.numerics import dot_dtype

    def projection():
        # the same add, in the same place, as linear(x, w_x, b) makes: XLA
        # fuses it into the projection's output
        p = xp
        if p is None:
            p = x if w_x is None else linear(x, w_x)
        return p + b.astype(p.dtype)

    B, H = mask.shape[0], w_h.shape[0]
    # the forward kernel makes the projection where the op has its operands
    # and the input matrix fits beside the recurrent one (point 5)
    owned = (allow_pallas and w_x is not None
             and rnn_kernel_ok(B, H, 4, proj_dim=x.shape[-1]))
    if owned or (allow_pallas and rnn_kernel_ok(B, H, 4)):
        from paddle_tpu.ops.pallas_kernels import _lstm_pallas_raw

        f32 = jnp.float32
        if owned:
            # x as the layer got it (the kernel rounds its block in VMEM): a
            # layer stacked on another reads that one's [T,B,H] output
            # where it lies, with no pass in between
            x_in, proj = x, dict(w_x=w_x.astype(f32), b=b,
                                 xp_dtype=dot_dtype())
        else:
            x_in, proj = projection().astype(f32), {}
        outs = _lstm_pallas_raw(
            jnp.moveaxis(x_in, 1, 0),
            jnp.moveaxis(mask.astype(f32), 1, 0), w_h.astype(f32),
            pi.astype(f32), pf.astype(f32), po.astype(f32),
            residuals=residuals, **proj)
        h_tb, h_fin, c_fin = outs[0], outs[1], outs[2]
        z_r, hprev_r, cprev_r = (
            (outs[3], outs[4], outs[5]) if residuals
            else (None, None, None))
        return (jnp.moveaxis(h_tb, 0, 1), h_fin, c_fin,
                z_r, hprev_r, cprev_r)
    out = _lstm_fwd_scan(projection(), mask, w_h, h0, c0, pi, pf, po)
    return out if residuals else (out[0], out[1], out[2], None, None, None)


def _lstm_seq_fwd(x, b, mask, w_h, h0, c0, pi, pf, po, allow_pallas,
                  has_peepholes, w_x):
    # the projection's transpose travels as autodiff writes it for the
    # linear() the forward ran (one operand policy, ops/matmul.py), holding
    # the compute-dtype copies of x and w_x it multiplies with.  Where the
    # forward kernel makes the projection, nothing reads this primal result
    # and XLA drops the product
    xp, proj_vjp = (x, None) if w_x is None else jax.vjp(linear, x, w_x)
    h_seq, h_fin, c_fin, z_tb, hprev_tb, cprev_tb = _lstm_core_fwd(
        x, w_x, b, mask, w_h, h0, c0, pi, pf, po, allow_pallas, xp=xp)
    meta = (jnp.zeros((0,), xp.dtype), jnp.zeros((0,), h0.dtype),
            jnp.zeros((0,), c0.dtype),
            jnp.zeros((0,), b.dtype))  # dtype sentinels (see GRU fwd)
    return ((h_seq, h_fin, c_fin),
            (mask, w_h, pi, pf, po, z_tb, hprev_tb, cprev_tb, meta,
             proj_vjp))


def _lstm_seq_bwd(allow_pallas, has_peepholes, res, ct):
    mask, w_h, pi, pf, po, z_r, hprev_r, cprev_r, meta, proj_vjp = res
    xp_dt, h0_dt, c0_dt, b_dt = (s.dtype for s in meta)
    d_hseq, d_hfin, d_cfin = ct
    H = w_h.shape[0]
    B = mask.shape[0]
    f32 = jnp.float32
    w_f = w_h.astype(f32)
    pi_f, pf_f, po_f = (p.astype(f32) for p in (pi, pf, po))
    d_peep = None

    if allow_pallas and rnn_kernel_ok(B, H, 4, backward=True):
        from paddle_tpu.ops.pallas_kernels import _lstm_bwd_pallas_raw

        # residual streams enter in their STORED dtype (see GRU twin); the
        # bias and peephole gradients leave the kernel already reduced.
        # d_z leaves narrow where the op owns every product that reads it,
        # and float32 where it is the caller's d_xp (module docstring, 4)
        d_z_tb, d_h0, d_c0, d_b, d_peep = _lstm_bwd_pallas_raw(
            jnp.moveaxis(d_hseq, 1, 0).astype(f32),
            jnp.moveaxis(mask, 1, 0).astype(f32),
            z_r, cprev_r, w_f.T.copy(),
            pi_f[None], pf_f[None], po_f[None],
            d_hfin.astype(f32), d_cfin.astype(f32),
            has_peepholes=has_peepholes,
            dz_dtype=f32 if proj_vjp is None else residual_dtype(H))
        d_b = d_b[0]
    else:
        cp_f = cprev_r.astype(f32)               # residuals are [T,B,*]
        m_tb = jnp.moveaxis(mask, 1, 0)
        d_out_tb = jnp.moveaxis(d_hseq, 1, 0).astype(f32)
        # gate math vectorized over every timestep from the saved z/c_prev —
        # the reverse scan below is left with elementwise chain math plus
        # the single unavoidable carry matmul d_z @ w^T.  z is PRE-peephole;
        # peephole ("check") terms: i,f see c_prev, o sees c_new
        # (hl_lstm_ops.cuh), so d_c picks up pi/pf feedthrough and d_o
        # feeds c_new.
        z = z_r.astype(f32)
        i = jax.nn.sigmoid(z[..., :H] + pi_f * cp_f)
        f = jax.nn.sigmoid(z[..., H: 2 * H] + pf_f * cp_f)
        g = jnp.tanh(z[..., 3 * H:])
        cn_tb = f * cp_f + i * g
        o = jax.nn.sigmoid(z[..., 2 * H: 3 * H] + po_f * cn_tb)
        tc = jnp.tanh(cn_tb)

        def rev_step(carry, inp):
            d_h, d_c = carry
            d_out_t, m_t, i_t, f_t, o_t, g_t, tc_t, cp_t = inp
            mcol = (m_t > 0)[:, None].astype(f32)
            d_hnew = mcol * (d_out_t + d_h)
            d_zo = d_hnew * tc_t * o_t * (1 - o_t)
            d_cnew = (mcol * d_c + d_hnew * o_t * (1.0 - tc_t * tc_t)
                      + d_zo * po_f)
            d_zi = d_cnew * g_t * i_t * (1 - i_t)
            d_zf = d_cnew * cp_t * f_t * (1 - f_t)
            d_zg = d_cnew * i_t * (1 - g_t * g_t)
            d_cp = d_cnew * f_t + d_zi * pi_f + d_zf * pf_f
            d_z = jnp.concatenate([d_zi, d_zf, d_zo, d_zg], -1)
            d_hp = _bwd_mm(d_z, w_f.T)
            d_h_out = (1.0 - mcol) * d_h + d_hp
            d_c_out = (1.0 - mcol) * d_c + d_cp
            return (d_h_out, d_c_out), d_z

        (d_h0, d_c0), d_z_tb = lax.scan(
            rev_step, (d_hfin.astype(f32), d_cfin.astype(f32)),
            (d_out_tb, m_tb, i, f, o, g, tc, cp_f), reverse=True)
        # the scan leaves the parameter-sized reductions to XLA: one batched
        # pass over d_z each (the kernel path accumulates them in its loop)
        d_b = jnp.sum(d_z_tb, axis=(0, 1))
        if has_peepholes:
            d_peep = (
                _bwd_einsum("tbh,tbh->h", d_z_tb[..., :H], cp_f),
                _bwd_einsum("tbh,tbh->h", d_z_tb[..., H: 2 * H], cp_f),
                _bwd_einsum("tbh,tbh->h", d_z_tb[..., 2 * H: 3 * H], cn_tb))

    if d_peep is None:
        d_pi, d_pf, d_po = (jnp.zeros_like(p) for p in (pi, pf, po))
    else:
        d_pi, d_pf, d_po = (d.astype(p.dtype)
                            for d, p in zip(d_peep, (pi, pf, po)))
    # shared tail (ONE copy for both reverse-loop implementations): the
    # products that read d_z
    d_wh = _bwd_einsum("tbh,tbz->hz", hprev_r.astype(d_z_tb.dtype),
                       d_z_tb).astype(w_h.dtype)
    d_xp = jnp.moveaxis(d_z_tb, 0, 1).astype(xp_dt)
    # given w_x, XLA fuses the widening of a narrow d_z into the operand
    # reads of dx and d_w_x (tests/test_tpu_compile.py holds it to that)
    d_x, d_wx = (d_xp, None) if proj_vjp is None else proj_vjp(d_xp)
    return (d_x, d_b.astype(b_dt), None, d_wh, d_h0.astype(h0_dt),
            d_c0.astype(c0_dt), d_pi, d_pf, d_po, d_wx)


lstm_sequence_fused.defvjp(_lstm_seq_fwd, _lstm_seq_bwd)
