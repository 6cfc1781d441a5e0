"""Recurrent cells and full-sequence RNN ops — analog of the reference's RNN tier.

Reference surface: fused LSTM/GRU cell kernels (paddle/cuda/src/hl_cuda_lstm.cu,
hl_lstm_ops.cuh / hl_gru_ops.cuh, with peephole "check" weights) and the
batch-per-timestep scheduling that keeps one matmul per step over all active
sequences (gserver/layers/SequenceToBatch.h:23-46, LstmLayer.cpp,
GatedRecurrentLayer.cpp, --rnn_use_batch).

TPU-first design:
- The input projection for *all* timesteps is hoisted out of the recurrence as
  one [B*T, D] x [D, 4H] MXU matmul (the analog of the reference pre-computing
  ``input * W`` before the frame loop).
- The recurrence itself is a ``lax.scan`` over time with a single [B, H] x
  [H, 4H] matmul per step — XLA compiles the scan once; no Python frame loop.
- Variable length is handled by masking: past a row's length the state carries
  through unchanged, which makes ``h[:, L-1]`` == final state, matching the
  reference's flat-sequence semantics without padding-dependent results.
- Cells are exposed separately (``lstm_step``/``gru_step``) for the decoder /
  recurrent-group machinery and beam search.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.ops.matmul import linear, matmul
from paddle_tpu.ops.numerics import dot_dtype
from paddle_tpu.ops.activations import get_activation

__all__ = [
    "lstm_step",
    "gru_step",
    "lstm_layer",
    "gru_layer",
    "bigru_layer",
    "scan_rnn",
]


def lstm_step(xp, h, c, w_h, *, peep_i=None, peep_f=None, peep_o=None,
              act="tanh", gate_act="sigmoid", state_act="tanh"):
    """One LSTM step. xp: [B, 4H] precomputed input projection (+bias),
    h/c: [B, H], w_h: [H, 4H]. Gate layout: [i, f, o, g].

    Peepholes (``check`` weights in the reference's hl_lstm_ops.cuh) are
    optional per-unit vectors applied as in legacy Paddle: i,f see c_{t-1},
    o sees c_t.
    """
    ga = get_activation(gate_act)
    sa = get_activation(state_act)
    aa = get_activation(act)
    z = xp + linear(h, w_h)
    i, f, o, g = jnp.split(z, 4, axis=-1)
    if peep_i is not None:
        i = i + peep_i.astype(z.dtype) * c
    if peep_f is not None:
        f = f + peep_f.astype(z.dtype) * c
    i, f = ga(i), ga(f)
    c_new = f * c + i * aa(g)
    if peep_o is not None:
        o = o + peep_o.astype(z.dtype) * c_new
    o = ga(o)
    h_new = o * sa(c_new)
    return h_new, c_new


def gru_step(xp, h, w_h, *, act="tanh", gate_act="sigmoid"):
    """One GRU step. xp: [B, 3H] input projection (+bias), gate layout
    [r, u, c]; w_h: [H, 3H] with the candidate block applied to (r * h),
    matching the reference's GatedRecurrentLayer formulation."""
    ga = get_activation(gate_act)
    aa = get_activation(act)
    H = h.shape[-1]
    w_gates = w_h[:, : 2 * H]
    w_cand = w_h[:, 2 * H :]
    zr = xp[..., : 2 * H] + linear(h, w_gates)
    r, u = jnp.split(ga(zr), 2, axis=-1)
    cand = aa(xp[..., 2 * H :] + linear(r * h, w_cand))
    h_new = u * h + (1.0 - u) * cand
    return h_new


def scan_rnn(step_fn, carry_init, xs_btd, mask_bt, *, reverse=False,
             reset_bt=None):
    """Scan ``step_fn(carry, x_t) -> (carry, out_t)`` over time with length
    masking: where mask==0 the carry is held, out is zeroed.

    xs may be a pytree of [B, T, ...] arrays; outputs are [B, T, ...].

    ``reset_bt`` ([B,T], optional) marks SEQUENCE-PACKING boundaries
    (ops/sequence.segment_starts): where it is 1 the incoming carry is
    replaced by ``carry_init`` before the step, so recurrent state never
    flows from one packed segment into the next — each segment computes
    exactly what it would alone in its own row (docs/data.md).
    """
    T = mask_bt.shape[1]
    xs_tb = jax.tree_util.tree_map(lambda a: jnp.moveaxis(a, 1, 0), xs_btd)
    mask_tb = jnp.moveaxis(mask_bt, 1, 0)
    reset_tb = (None if reset_bt is None
                else jnp.moveaxis(reset_bt, 1, 0))

    def masked_step(carry, inp):
        if reset_tb is None:
            x_t, m_t = inp
        else:
            x_t, m_t, r_t = inp

            def re(init, c):
                r = r_t.reshape(r_t.shape + (1,) * (c.ndim - 1))
                return jnp.where(r.astype(c.dtype) > 0, init, c)

            carry = jax.tree_util.tree_map(re, carry_init, carry)
        new_carry, out = step_fn(carry, x_t)

        def bmask(a):  # [B] mask broadcast against [B, ...] of any rank
            return m_t.reshape(m_t.shape + (1,) * (a.ndim - 1)).astype(a.dtype)

        def sel(new, old):
            return jnp.where(bmask(new) > 0, new, old)

        carry_out = jax.tree_util.tree_map(sel, new_carry, carry)
        out = jax.tree_util.tree_map(lambda o: o * bmask(o), out)
        return carry_out, out

    ins = (xs_tb, mask_tb) if reset_tb is None else (xs_tb, mask_tb, reset_tb)
    final, outs_tb = lax.scan(masked_step, carry_init, ins, reverse=reverse)
    outs = jax.tree_util.tree_map(lambda a: jnp.moveaxis(a, 0, 1), outs_tb)
    return final, outs


def lstm_layer(x, mask, w_x, w_h, b, *, h0=None, c0=None, reverse=False,
               peep_i=None, peep_f=None, peep_o=None,
               act="tanh", gate_act="sigmoid", state_act="tanh",
               reset=None):
    """Full LSTM over a padded batch. x: [B,T,D] -> h_seq [B,T,H], (h,c) final.

    Equivalent capability to the reference's lstmemory layer
    (trainer_config_helpers/layers.py:1121 + LstmLayer.cpp); the input
    projection is one big MXU matmul over all timesteps.  ``w_x=None``
    means x IS the [B,T,4H] pre-projection (the reference's convention,
    where a preceding mixed layer owns the input matrix).

    ``reset`` ([B,T], sequence packing — docs/data.md) zeroes the (h,c)
    carry at segment-entry positions; it routes through the lax.scan
    path (the fused/Pallas time loop has no reset port), which is the
    documented packing trade: denser rows for the scan-path step.
    """
    B, T, _ = x.shape
    H = w_h.shape[0]
    if reset is None and \
            (act, gate_act, state_act) == ("tanh", "sigmoid", "tanh"):
        # default cell (peepholes included — zeros degenerate exactly):
        # fused-backward sequence op (hand-written VJP batches d_w_h after
        # the reverse loop; Pallas fwd+bwd kernels when the gate allows —
        # see ops/rnn_fused.py).  reverse rides a flip: identical to
        # scan_rnn(reverse=True) including mask hold/zero semantics.
        # The op makes the projection and adds the bias itself (to the
        # projection's output, where linear() would), because its backward
        # owns the bias gradient and the projection's two products.
        from paddle_tpu.ops.rnn_fused import lstm_sequence_fused

        xp_dt = x.dtype if w_x is None else dot_dtype()
        allow_pallas = h0 is None and c0 is None
        h0a = jnp.zeros((B, H), xp_dt) if h0 is None else h0
        c0a = jnp.zeros((B, H), xp_dt) if c0 is None else c0
        has_peeps = any(p is not None for p in (peep_i, peep_f, peep_o))
        zp = jnp.zeros((H,), xp_dt)
        # peepholes join the carry arithmetic: f32 check params would
        # promote the bf16 scan carry under --amp (scan requires a stable
        # carry dtype) — cast at the boundary like every other operand
        pi = zp if peep_i is None else peep_i.astype(xp_dt)
        pf = zp if peep_f is None else peep_f.astype(xp_dt)
        po = zp if peep_o is None else peep_o.astype(xp_dt)
        x_r = jnp.flip(x, 1) if reverse else x
        m_r = jnp.flip(mask, 1) if reverse else mask
        h_seq, h_fin, c_fin = lstm_sequence_fused(x_r, b, m_r, w_h, h0a,
                                                  c0a, pi, pf, po,
                                                  allow_pallas, has_peeps,
                                                  w_x)
        if reverse:
            h_seq = jnp.flip(h_seq, 1)
        return h_seq, (h_fin, c_fin)
    xp = (x + b.astype(x.dtype)) if w_x is None else linear(x, w_x, b)
    h0 = jnp.zeros((B, H), xp.dtype) if h0 is None else h0
    c0 = jnp.zeros((B, H), xp.dtype) if c0 is None else c0

    def step(carry, xp_t):
        h, c = carry
        h2, c2 = lstm_step(
            xp_t, h, c, w_h, peep_i=peep_i, peep_f=peep_f, peep_o=peep_o,
            act=act, gate_act=gate_act, state_act=state_act,
        )
        return (h2, c2), h2

    (h_fin, c_fin), h_seq = scan_rnn(step, (h0, c0), xp, mask,
                                     reverse=reverse, reset_bt=reset)
    return h_seq, (h_fin, c_fin)


def gru_layer(x, mask, w_x, w_h, b, *, h0=None, reverse=False,
              act="tanh", gate_act="sigmoid", reset=None):
    """Full GRU over a padded batch. x: [B,T,D] -> h_seq [B,T,H], h final.

    Capability analog of grumemory (trainer_config_helpers/layers.py:1228 +
    GatedRecurrentLayer.cpp).  ``w_x=None``: x is the [B,T,3H]
    pre-projection (see lstm_layer).  ``reset`` as in ``lstm_layer``
    (sequence packing: carry zeroed at segment entries, scan path).
    """
    B, T, _ = x.shape
    H = w_h.shape[0]
    xp = (x + b.astype(x.dtype)) if w_x is None else linear(x, w_x, b)
    if reset is None and (act, gate_act) == ("tanh", "sigmoid"):
        # default cell: fused-backward sequence op (see lstm_layer above)
        from paddle_tpu.ops.rnn_fused import gru_sequence_fused

        allow_pallas = h0 is None
        h0a = jnp.zeros((B, H), xp.dtype) if h0 is None else h0
        xp_r = jnp.flip(xp, 1) if reverse else xp
        m_r = jnp.flip(mask, 1) if reverse else mask
        h_seq, h_fin = gru_sequence_fused(xp_r, m_r, w_h, h0a, allow_pallas)
        if reverse:
            h_seq = jnp.flip(h_seq, 1)
        return h_seq, h_fin
    h0 = jnp.zeros((B, H), xp.dtype) if h0 is None else h0

    def step(h, xp_t):
        h2 = gru_step(xp_t, h, w_h, act=act, gate_act=gate_act)
        return h2, h2

    h_fin, h_seq = scan_rnn(step, h0, xp, mask, reverse=reverse,
                            reset_bt=reset)
    return h_seq, h_fin


def bigru_layer(x, mask, wx_fw, wh_fw, b_fw, wx_bw, wh_bw, b_bw):
    """Bidirectional GRU over a padded batch — the encoder composition of
    the seq2seq flagship (reference: seqToseq_net.py's forward + backward
    grumemory pair): a forward and a reversed ``gru_layer`` over the same
    input, each choosing its own kernel.

    Returns (h_fw [B,T,H], h_bw [B,T,H], h_bw_final [B,H]).
    """
    h_fw, _ = gru_layer(x, mask, wx_fw, wh_fw, b_fw)
    h_bw, h_bw_fin = gru_layer(x, mask, wx_bw, wh_bw, b_bw, reverse=True)
    return h_fw, h_bw, h_bw_fin
