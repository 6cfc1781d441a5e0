"""Fused-backward attention GRU decoder — the seq2seq training hot loop.

Semantically identical to scanning ``additive_attention_scores`` + ``attend``
+ concat + ``linear`` + ``gru_step`` over the target sequence (the Bahdanau
decoder of demo/seqToseq, reference: demo/seqToseq/api_train_v2.py:90-189,
gserver/gradientmachines/RecurrentGradientMachine.cpp) — but with a
hand-written VJP that restructures the backward pass for TPU HBM bandwidth.

Why: XLA's autodiff of that scan carries the cotangent accumulators
``d_enc`` [B,S,2H] and the weight grads through HBM on EVERY reverse step —
at WMT14 bench shapes that is ~45+ MB of accumulator read+write per step,
~10x the cost of the forward scan (measured 4.4 ms backward vs 0.45 ms
forward on v5e).  The custom VJP instead:

- precomputes the GRU gates and attention queries for ALL steps as batched
  MXU matmuls before the reverse scan (they depend only on saved forward
  values),
- emits the SMALL per-step cotangents (``d_xp`` [B,3D], ``sum_dpre``
  [B,A]) as stacked scan outputs,
- reconstructs every weight gradient AFTER the scan as one batched MXU
  contraction each (``d_enc``, ``d_Wx``, ``d_Wh``, ``d_attw``, ``d_b``,
  ``d_y``),
- keeps only the genuinely unavoidable accumulators (``d_enc_proj`` —
  nonlinear in t — and the tiny ``d_v``) in the reverse scan.  Scan
  accumulators are f32: summing T bfloat16 terms drifts for long targets,
  and a bf16 ``d_enc_proj`` carry A/B-measured slower anyway.

Forward saves (probs [T,B,S] f32, ctx [T,B,2H] in the compute dtype,
s_prev [T,B,D] f32 — the carry entering each step, stacked so the backward
needs no sequential carry-reconstruction scan) — O(B·T·(S+2H+D)) residual
buffers alongside the primal states output, ~100-125 MB at bench shapes vs
the ~1.3 GB/step-loop accumulator traffic the restructure removes.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.ops.matmul import linear
from paddle_tpu.ops.numerics import (acc_dtype, bwd_einsum,
                                     bwd_mm, dot_dtype, mxu_cast)

__all__ = ["attention_gru_decoder"]


def _attn_pallas_block(B, S, D, A, H2):
    """Batch-block size for the VMEM-resident Pallas decoder kernels
    (ops/pallas_kernels.py: attn_dec_fwd_pallas / attn_dec_bwd_pallas), or
    None to use the XLA scan path.  Gates: TPU backend + lane/tile
    alignment (the kernels slice [Bb, S, A]/[Bb, gates*D] blocks) + the
    resident working set (enc, enc_proj, the backward's d_enc_proj
    accumulator and its d_pre temporary, all per block) must fit the raised
    VMEM budget."""
    from paddle_tpu.ops.pallas_kernels import compiled_kernels

    if not compiled_kernels():
        return None
    if D % 128 or A % 128 or H2 % 128 or S % 8:
        return None
    for bb in (128, 96, 64, 32, 16, 8):
        # f32 worst case: enc_proj + enc resident, plus 2x [Bb,S,A] f32
        # (accumulator + d_pre temp) in the backward
        if B % bb == 0 and bb * S * (12 * A + 4 * H2) <= 48 * 1024 * 1024:
            return bb
    return None


def _fwd_step(s, xp_y_t, enc, enc_proj, src_mask, att_w, att_v, wx_c, wh):
    """One decoder step; mirrors additive_attention_scores/attend/gru_step
    numerics (bf16 matmul operands, f32 accumulation).  ``xp_y_t`` is the
    teacher-forced half of the input projection, HOISTED out of the scan as
    one [B,T,E]x[E,3D] MXU matmul (+bias) — only the context half
    (``ctx @ wx_c``) depends on the recurrent state, so only it stays in the
    loop.  Measured step-time NEUTRAL on v5e at B384 WMT14 shapes (24.5 vs
    24.6 ms — the scan is latency-bound, not FLOP-bound); kept because it
    shrinks the sequential per-step work and matches the DSL's
    separate-projection composition."""
    D = s.shape[-1]
    # --- additive_attention_scores ---
    q = linear(s, att_w)[:, None, :]
    enc_proj_c, q_c = mxu_cast(enc_proj, q)
    pre = jnp.tanh(enc_proj_c + q_c)                       # [B,S,A]
    scores = jnp.einsum("bsa,a->bs", pre, att_v.astype(pre.dtype),
                        preferred_element_type=acc_dtype())
    # --- attend ---
    neg = jnp.finfo(scores.dtype).min
    z = jnp.where(src_mask > 0, scores, neg)
    w0 = jax.nn.softmax(z, axis=-1)
    w1 = w0 * src_mask.astype(scores.dtype)
    n = jnp.maximum(jnp.sum(w1, axis=-1, keepdims=True), 1e-9)
    w = w1 / n
    wc, vc = mxu_cast(w, enc)
    # the context is an ACTIVATION (the softmax above stays f32): it
    # leaves at dot_dtype, bf16 under --amp
    ctx = jnp.einsum("bs,bsd->bd", wc, vc,
                     preferred_element_type=dot_dtype()).astype(dot_dtype())
    # --- input projection + gru_step ---
    xp = xp_y_t + linear(ctx, wx_c)
    zr = xp[..., : 2 * D] + linear(s, wh[:, : 2 * D])
    r, u = jnp.split(jax.nn.sigmoid(zr), 2, axis=-1)
    cand = jnp.tanh(xp[..., 2 * D:] + linear(r * s, wh[:, 2 * D:]))
    s_new = u * s + (1.0 - u) * cand
    return s_new, (w, ctx, pre)


@partial(jax.custom_vjp, nondiff_argnums=())
def attention_gru_decoder(y_emb, s0, enc, enc_proj, src_mask, trg_mask,
                          att_w, att_v, wx, b, wh):
    """y_emb [B,T,E], s0 [B,D], enc [B,S,2H], enc_proj [B,S,A],
    src_mask [B,S], trg_mask [B,T] -> states [B,T,D] (zeroed at padded
    target steps, carry held — scan_rnn masking semantics)."""
    states, _ = _decoder_fwd_scan(y_emb, s0, enc, enc_proj, src_mask,
                                  trg_mask, att_w, att_v, wx, b, wh)
    return states


def _decoder_fwd_scan(y_emb, s0, enc, enc_proj, src_mask, trg_mask,
                      att_w, att_v, wx, b, wh):
    E = y_emb.shape[-1]
    # hoisted teacher-forced half of the input projection (+ bias), one
    # batched MXU matmul over all steps
    xp_y = linear(y_emb, wx[:E], b)                        # [B,T,3D] f32
    xp_y_tb = jnp.moveaxis(xp_y, 1, 0)                     # [T,B,3D]
    m_tb = jnp.moveaxis(trg_mask, 1, 0)                    # [T,B]
    wx_c = wx[E:]

    from paddle_tpu.ops.numerics import compute_dtype

    rd = compute_dtype()  # residual stream dtype (bf16 under prod policy)

    B, T = trg_mask.shape
    bb = _attn_pallas_block(B, enc.shape[1], s0.shape[-1],
                            enc_proj.shape[-1], enc.shape[2])
    if bb is not None:
        from paddle_tpu.ops.pallas_kernels import attn_dec_fwd_pallas

        f32 = jnp.float32
        enc_c, encP_c, attw_c, attv_c, wxc_c, wh_c = mxu_cast(
            enc, enc_proj, att_w, att_v, wx_c, wh)
        outs, probs, ctxs, s_prev = attn_dec_fwd_pallas(
            xp_y_tb.astype(f32), m_tb.astype(f32), s0.astype(f32),
            enc_c, encP_c, src_mask.astype(f32),
            attw_c, attv_c, wxc_c, wh_c, block_b=bb)
        return jnp.moveaxis(outs, 0, 1), (probs, ctxs, s_prev)

    def step(s, inp):
        xp_y_t, m_t = inp
        s_new, (w, ctx, _pre) = _fwd_step(s, xp_y_t, enc, enc_proj, src_mask,
                                          att_w, att_v, wx_c, wh)
        keep = (m_t > 0)[:, None]
        s_out = jnp.where(keep, s_new, s)
        out = s_out * m_t[:, None].astype(s_out.dtype)
        # s (the carry ENTERING the step) is exactly the s_prev the backward
        # needs — stacking it here deletes the backward's sequential
        # carry-reconstruction scan
        return s_out, (out, w, ctx.astype(rd), s)

    _, (outs, probs, ctxs, s_prev) = lax.scan(step, s0, (xp_y_tb, m_tb))
    states = jnp.moveaxis(outs, 0, 1)                      # [B,T,D]
    return states, (probs, ctxs, s_prev)


def _agd_fwd(y_emb, s0, enc, enc_proj, src_mask, trg_mask,
             att_w, att_v, wx, b, wh):
    states, (probs, ctxs, s_prev) = _decoder_fwd_scan(
        y_emb, s0, enc, enc_proj, src_mask, trg_mask, att_w, att_v, wx, b, wh)
    res = (y_emb, s0, enc, enc_proj, src_mask, trg_mask,
           att_w, att_v, wx, b, wh, s_prev, probs, ctxs)
    return states, res


def _agd_bwd(res, d_states):
    (y_emb, s0, enc, enc_proj, src_mask, trg_mask,
     att_w, att_v, wx, b, wh, s_prev, probs, ctxs) = res
    B, T = trg_mask.shape
    D = s0.shape[-1]
    S = enc.shape[1]
    E = y_emb.shape[-1]
    f32 = jnp.float32

    y_tb = jnp.moveaxis(y_emb, 1, 0)                       # [T,B,E]
    m_tb = jnp.moveaxis(trg_mask, 1, 0)                    # [T,B]
    # recompute the hoisted y-projection (single deterministic matmul ->
    # bitwise-identical to the forward's values; cheaper than carrying a
    # [T,B,3D] f32 residual)
    xp_y_tb = jnp.moveaxis(linear(y_emb, wx[:E], b), 1, 0)
    d_out_tb = jnp.moveaxis(d_states, 1, 0).astype(f32)    # [T,B,D]
    # s_prev [T,B,D] arrives stacked straight from the forward scan (the
    # carry entering each step) — no reconstruction scan needed

    att_w_f, att_v_f = att_w.astype(f32), att_v.astype(f32)
    wx_f, wh_f = wx.astype(f32), wh.astype(f32)
    neg = jnp.finfo(f32).min
    maskb = (src_mask > 0)
    mask_f = src_mask.astype(f32)

    # ---- GRU gate recompute VECTORIZED over all steps (batched MXU
    # matmuls; was two matmuls inside every reverse step) ----
    xp_all = (xp_y_tb + linear(ctxs, wx[E:])).astype(f32)  # [T,B,3D]
    zr_all = xp_all[..., : 2 * D] + linear(s_prev, wh[:, : 2 * D]).astype(f32)
    ru_all = jax.nn.sigmoid(zr_all)
    r_all = ru_all[..., :D]
    u_all = ru_all[..., D:]
    cand_all = jnp.tanh(xp_all[..., 2 * D:]
                        + linear((r_all * s_prev.astype(f32)).astype(
                            s_prev.dtype), wh[:, 2 * D:]).astype(f32))
    # the attention query is also state-only: one batched matmul
    q_all = linear(s_prev, att_w)                          # [T,B,A]

    def rev_step(carry, inp):
        d_s, d_encP, d_v = carry
        d_out_t, m_t, w_t, sp_t, r, u, cand, q_t = inp
        mcol = (m_t > 0)[:, None].astype(f32)
        d_snew = mcol * (d_out_t + d_s)
        sp = sp_t.astype(f32)

        # ---- GRU backward (gates precomputed above) ----
        d_u = d_snew * (sp - cand)
        d_cand = d_snew * (1.0 - u)
        d_h = d_snew * u
        d_zc = d_cand * (1.0 - cand * cand)
        d_rh = bwd_mm(d_zc, wh_f[:, 2 * D:].T)
        d_r = d_rh * sp
        d_h = d_h + d_rh * r
        d_zr = jnp.concatenate([d_r * r * (1 - r), d_u * u * (1 - u)], -1)
        d_h = d_h + bwd_mm(d_zr, wh_f[:, : 2 * D].T)
        d_xp = jnp.concatenate([d_zr, d_zc], -1)           # [B,3D]
        d_ctx = bwd_mm(d_xp, wx_f[E:].T)                   # [B,2H]

        # ---- attention backward (attend) ----
        d_w = jnp.einsum("bh,bsh->bs", d_ctx.astype(enc.dtype), enc,
                         preferred_element_type=f32)
        # recompute softmax chain from the precomputed query
        enc_proj_c, q_c = mxu_cast(enc_proj, q_t[:, None, :])
        pre = jnp.tanh(enc_proj_c + q_c)                   # [B,S,A] cd
        scores = jnp.einsum("bsa,a->bs", pre, att_v.astype(pre.dtype),
                            preferred_element_type=f32)
        z = jnp.where(maskb, scores, neg)
        w0 = jax.nn.softmax(z, axis=-1)
        w1 = w0 * mask_f
        n = jnp.maximum(jnp.sum(w1, axis=-1, keepdims=True), 1e-9)
        # w = w1/n
        d_w1 = d_w / n
        d_n = -jnp.sum(d_w * w1, axis=-1, keepdims=True) / (n * n)
        d_w1 = d_w1 + d_n * (jnp.sum(w1, -1, keepdims=True) > 1e-9).astype(f32)
        d_w0 = d_w1 * mask_f
        d_z = w0 * (d_w0 - jnp.sum(w0 * d_w0, axis=-1, keepdims=True))
        d_scores = jnp.where(maskb, d_z, 0.0)
        pre_f = pre.astype(f32)
        d_pre = (1.0 - pre_f * pre_f) * (d_scores[..., None] * att_v_f)
        # accumulate in f32: summing T bf16 terms loses precision for long
        # targets, and a bf16 accumulator A/B-measured SLOWER anyway
        # (23.6 vs 22.5 ms at B384 — the per-step down-cast pass costs
        # more than the narrower carry saves)
        d_encP = d_encP + d_pre
        sum_dpre = jnp.sum(d_pre, axis=1)                  # [B,A]
        d_h = d_h + bwd_mm(sum_dpre, att_w_f.T)
        d_v = d_v + bwd_einsum("bs,bsa->a", d_scores, pre_f)

        d_s_out = (1.0 - mcol) * d_s + d_h
        return (d_s_out, d_encP, d_v), (d_xp, sum_dpre)

    A = enc_proj.shape[-1]
    bb = _attn_pallas_block(B, S, D, A, enc.shape[2])
    if bb is not None:
        from paddle_tpu.ops.pallas_kernels import attn_dec_bwd_pallas

        enc_c, encP_c, attv_c = mxu_cast(enc, enc_proj, att_v)
        d_xp_tb, sum_dpre_tb, d_encP, d_v, d_s0 = attn_dec_bwd_pallas(
            d_out_tb, m_tb.astype(f32), s_prev.astype(f32),
            r_all, u_all, cand_all, q_all,
            enc_c, encP_c, src_mask.astype(f32),
            att_w_f, attv_c, att_v_f, wh_f, wx_f[E:], block_b=bb)
    else:
        acc0 = (jnp.zeros((B, D), f32),
                jnp.zeros((B, S, A), f32),
                jnp.zeros(att_v.shape, f32))
        (d_s0, d_encP, d_v), (d_xp_tb, sum_dpre_tb) = lax.scan(
            rev_step, acc0,
            (d_out_tb, m_tb, probs, s_prev, r_all, u_all, cand_all, q_all),
            reverse=True)
    d_b = jnp.sum(d_xp_tb, axis=(0, 1))  # bias grad off the stacked output

    # ---- batched post-scan contractions (weight grads were carried
    # through the scan before — each is now ONE MXU einsum) ----
    d_ctx_tb = bwd_mm(d_xp_tb, wx_f[E:].T)                 # [T,B,2H]
    sp_f = s_prev.astype(f32)
    d_wh = jnp.concatenate(
        [bwd_einsum("tbd,tbz->dz", sp_f, d_xp_tb[..., : 2 * D]),
         bwd_einsum("tbd,tbz->dz", r_all * sp_f, d_xp_tb[..., 2 * D:])],
        axis=1)
    d_attw = bwd_einsum("tbd,tba->da", sp_f, sum_dpre_tb)
    # d_enc: the only use of enc is ctx_t = w_t @ enc
    d_enc = bwd_einsum("tbs,tbh->bsh", probs,
                       d_ctx_tb).astype(enc.dtype)
    # d_wx in two blocks (x = [y, ctx]); identical to the old einsum over
    # the concatenated x
    d_wx_y = bwd_einsum("tbi,tbo->io", y_tb.astype(f32), d_xp_tb)
    d_wx_c = bwd_einsum("tbi,tbo->io", ctxs.astype(f32), d_xp_tb)
    d_wx = jnp.concatenate([d_wx_y, d_wx_c], axis=0)
    d_y = bwd_mm(d_xp_tb, wx_f[:E].T).astype(y_emb.dtype)  # [T,B,E]
    d_y_emb = jnp.moveaxis(d_y, 0, 1)

    return (d_y_emb, d_s0.astype(s0.dtype), d_enc,
            d_encP.astype(enc_proj.dtype),
            None, None,
            d_attw.astype(att_w.dtype), d_v.astype(att_v.dtype),
            d_wx.astype(wx.dtype), d_b.astype(b.dtype),
            d_wh.astype(wh.dtype))


attention_gru_decoder.defvjp(_agd_fwd, _agd_bwd)
