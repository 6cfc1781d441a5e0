"""Pallas TPU kernels for the hot ops, and the backend half of their gates.

The reference's performance tier is hand-written CUDA: fused LSTM cell with
intra-sequence parallelism (paddle/cuda/src/hl_cuda_lstm.cu:26-58, PTX
bar.sync), fused GRU (hl_gru_ops.cuh).  The TPU analog: the *whole* LSTM/GRU
time loop runs inside ONE Pallas kernel — the grid's sequential dimension is
time, recurrent weights stay resident in VMEM across all timesteps, and the
h/c state lives in VMEM scratch, so per-step HBM traffic is just the input
block in (the LSTM forward takes the layer's input and its input matrix and
makes the projection itself; the others take the projection) and the hidden
block out.

What the file holds, family by family, with the gate that chooses each
kernel over its XLA twin.  A gate lives in the module that calls the kernel
and is a function of the backend (:func:`compiled_kernels`: the ``"tpu"``
backend, outside :func:`xla_paths_only`) and of the shape; nothing a user
sets takes part:

- LSTM / GRU time loops, forward (``lstm_seq_fwd``, ``gru_seq_fwd``; they
  stream out the residuals the backward needs) and reverse (``lstm_seq_bwd``,
  ``gru_seq_bwd``; no forward replay).  Gate: ``ops/rnn_fused.rnn_kernel_ok``
  (``backward=`` for the reverse kernels, ``proj_dim=`` for the LSTM forward
  that makes the input projection), sized by :func:`rnn_vmem_bytes`.
  ``lstm_forward_pallas`` / ``gru_forward_pallas`` are direct entries for
  tests, with an autodiff-of-reference backward.
- Attention GRU decoder, forward and reverse.  Gate:
  ``ops/attention_decoder._attn_pallas_block``.
- Vocab-tiled readout + softmax cross-entropy, forward and backward.  Gate:
  ``ops/losses._tiled_ce_cfg``.
- Vocab-tiled top-k + logsumexp readout (decode).  Gate:
  ``ops/decode.decode_kernel_config``.
- Causal flash attention, forward (``flash_attn_fwd``) and one backward
  (``flash_attn_bwd``: dq, dk and dv from one set of probabilities).  Gate:
  ``ops/decoder_block.attention_kernel_blocks``; how many keys' ``dk`` and
  ``dv`` a backward call keeps in VMEM: :func:`flash_bwd_key_rows`.  Under a
  window (a query sees its last ``window`` positions) the same two kernels
  are ``flash_attn_win_fwd`` / ``flash_attn_win_bwd``: the key axis of their
  grids is the band of a block of queries (:func:`flash_band_blocks`) and
  starts at the band's first block.
- Grouped matrix products over experts.  Gate:
  ``ops/moe.moe_kernel_row_tile``.
- The gated delta rule's chunked scan, forward (``gdn_chunk_fwd``, which
  also writes every chunk's starting state and its 64 x 64 solve) and
  reverse (``gdn_chunk_bwd``, which reads both and solves nothing); ``q``
  and ``k`` come at the key heads and a value head reads its key head
  through the index maps.  Gate:
  ``ops/delta_rule.delta_rule_kernel_chunk``.
- The state-space (SSD) recurrence's chunked scan, forward
  (``ssd_chunk_fwd``) and reverse (``ssd_chunk_bwd``); a grid step takes a
  group of heads, whose channels are the lanes, where the layer keeps them.
  Gate: ``ops/ssd_scan.ssd_kernel_chunk``.
- A delta net's way from its in-projection to that scan, one pass forward
  (``gdn_prep_fwd``: the depthwise causal convolution, SiLU, the L2 norms,
  ``q``'s scale, the cast, the heads-major layout) and one back
  (``gdn_prep_bwd``, which also sums ``dq``, ``dk`` over a key head's value
  heads and accumulates the convolution kernel's gradient).  Gate:
  ``ops/delta_rule.prep_kernel_rows``.
- A Mamba-2 mixer's way from its in-projection to that scan, one pass
  forward (``mamba_prep_fwd``: the depthwise causal convolution over ``[x |
  B | C]`` read at their column offset in the projection, the bias, SiLU, the
  cast; x and ``[B | C]``, two arrays of which the scan's kernels read a
  group's blocks, and x in the projection's dtype for the layer's skip) and
  one back
  (``mamba_prep_bwd``, which also adds the skip's part of ``dx`` and
  accumulates the convolution kernel's and the bias's gradients).  Gate:
  ``ops/ssd_scan.prep_kernel_block``.
- Learned sparse attention: the indexer's scores (``indexer_scores``), the
  selection of a row's ``topk`` best by a threshold found by counting
  (``topk_select``), the flash kernels under that selection
  (``flash_attn_sel_fwd`` / ``flash_attn_sel_bwd``: the two flash kernels
  with ``keep=``) and the indexer's loss with its gradient
  (``indexer_loss``).  Gate: ``ops/sparse_attention.sparse_kernel_blocks``.
- The rotary embedding as one elementwise pass over whole heads
  (``rotary_turn``, forward and, with the sine negated, backward): a
  channel's partner by a lane roll in registers.  Gate:
  ``ops/decoder_block.rotary_kernel_blocks``.

Where a gate is closed (the CPU, a shape past it, a step that jit partitions
over a mesh: one with sharding rules, or ``SGDTrainer(mesh=...)``) the
caller's lax.scan / XLA path runs.  Off the TPU the kernels
run in interpret mode, which is how the CPU tests compare both paths.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["pallas_available", "compiled_kernels", "xla_paths_only",
           "lstm_forward_pallas", "gru_forward_pallas",
           "attn_dec_fwd_pallas", "attn_dec_bwd_pallas",
           "topk_lse_readout_pallas", "topk_lse_logits_pallas", "TOPK_LANES",
           "flash_attn_fwd_pallas", "flash_attn_bwd_pallas",
           "flash_bwd_key_rows", "flash_band_blocks",
           "gmm_pallas", "tgmm_pallas",
           "gdn_chunk_fwd_pallas", "gdn_chunk_bwd_pallas",
           "gdn_prep_fwd_pallas", "gdn_prep_bwd_pallas", "GDN_PREP_HALO",
           "ssd_chunk_fwd_pallas", "ssd_chunk_bwd_pallas",
           "mamba_prep_fwd_pallas", "mamba_prep_bwd_pallas",
           "indexer_scores_pallas", "topk_select_pallas",
           "indexer_loss_pallas", "TOPK_SELECT_ROWS", "rotary_pallas"]


def _compiler_params(**kw):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kw)


def pallas_available() -> bool:
    """Kernels run compiled on TPU and in interpret mode on the CPU (the
    test platform); any other backend has neither."""
    return jax.default_backend() in ("tpu", "cpu")


def _interpret() -> bool:
    """Interpret mode is a function of the backend name alone: off on
    ``"tpu"``, on everywhere else (the CPU tests)."""
    return jax.default_backend() != "tpu"


_mesh_trace = threading.local()
_warned_kernels_off = False


@contextlib.contextmanager
def xla_paths_only():
    """Entered (also as a decorator) around the trace of a step that jit
    partitions over MORE THAN ONE device: the kernel gates then keep to
    their XLA paths.  A Mosaic kernel is a custom call the SPMD
    partitioner cannot split ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map") — inside a
    ``shard_map`` body the kernels are fine and this is not needed.  The
    switch acts at trace time only: a function jitted and traced before it
    was entered keeps the kernels it was traced with.

    Two steps still enter it and lose the kernels: a
    ``parallel.make_parallel_train_step`` with ``rules`` (a model axis),
    and ``SGDTrainer(mesh=...)`` (guard, pserver tiers and extras inside
    one partitioned step).  The pure data-parallel step does not: it is a
    ``shard_map`` whose body sees one chip's rows
    (``parallel/api.py`` ``data_parallel_body``).  On a TPU the loss of the
    kernels is logged, once per process."""
    global _warned_kernels_off
    if not _warned_kernels_off and jax.default_backend() == "tpu":
        _warned_kernels_off = True
        from paddle_tpu.utils.log import logger

        logger.warning(
            "this step (sharding rules over a model axis, or SGDTrainer "
            "with a mesh) is partitioned over the mesh by jit, which Mosaic "
            "kernels do not survive: every kernel gate is closed while it "
            "is traced, and it runs the XLA paths; a pure data-parallel "
            "parallel.make_parallel_train_step (no rules) keeps the kernels")
    _mesh_trace.depth = getattr(_mesh_trace, "depth", 0) + 1
    try:
        yield
    finally:
        _mesh_trace.depth -= 1


def compiled_kernels() -> bool:
    """The backend half of every kernel gate: Mosaic kernels are chosen on
    the ``"tpu"`` backend and nowhere else, and not while a step for an
    auto-partitioned mesh is being traced (:func:`xla_paths_only`)."""
    return (jax.default_backend() == "tpu"
            and not getattr(_mesh_trace, "depth", 0))


# ---------------------------------------------------------------------------
# LSTM: one kernel over the whole sequence
# ---------------------------------------------------------------------------

#: scoped VMEM the four recurrent time-loop kernels (LSTM/GRU, forward and
#: reverse) ask of Mosaic.  The default is 16 MiB, which the resident
#: recurrent weight alone exceeds at H=1280 ([H,4H] f32 = 25 MiB); the
#: gate (ops/rnn_fused.rnn_kernel_ok) admits a shape only when
#: rnn_vmem_bytes() fits it.
RNN_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def rnn_vmem_bytes(batch: int, hidden: int, gates: int, *, backward: bool,
                   residual_itemsize: int,
                   proj_dim: Optional[int] = None) -> int:
    """Scoped VMEM one recurrent time-loop kernel holds, from its
    BlockSpecs: the recurrent weight ``[H, gates*H]`` f32 stays resident
    (one buffer — its block index never changes), every per-step block is
    double-buffered, finals/seeds and the carry scratches are ``[B, H]``
    f32.  ``gates``: 4 = LSTM (two carries), 3 = GRU (one).  Counted with
    the widest variant (training residuals; the LSTM reverse kernel's
    peephole accumulators and its ``d_z`` in f32, which is half that where
    the op owns the input projection).  Given ``proj_dim`` = D, the LSTM
    forward kernel makes the input projection itself: a resident ``[D, 4H]``
    f32 weight in, and a ``[B, D]`` f32 block a time step in place of the
    ``[B, 4H]`` one.  Agrees to within 1% with what the v5e compiler of the
    installed libtpu reports when it refuses a kernel (jax 0.9.0,
    tests/test_tpu_compile.py holds the gates to it)."""
    carries = 2 if gates == 4 else 1
    rs = residual_itemsize
    weight = 4 * gates * hidden * hidden
    fixed = 0
    if backward:
        # d_out in, z + held-carry residuals in, d_z out, then per carry:
        # seed in, d_0 out, scratch
        per_unit = 8 + 2 * rs * (gates + 1) + 8 * gates + 12 * carries
        if gates == 4:
            # the LSTM's bias and peephole gradients, which do not grow with
            # B: f32 sublane-tile accumulators [8, 4H] + [3, 8, H], and the
            # double-buffered blocks they leave in, [1, 4H] and [3 -> 4, H]
            fixed = 4 * hidden * (8 * (gates + 3) + 2 * gates + 2 * 4)
    else:
        # xp (or x) in, h_seq out, z + held-carry residuals out, then per
        # carry: final out, scratch
        per_unit = 8 + 2 * rs * (gates + carries) + 8 * carries
        if proj_dim is None:
            per_unit += 8 * gates
        else:
            fixed = 4 * proj_dim * (gates * hidden + 2 * batch)
    return weight + fixed + per_unit * batch * hidden


def _lstm_kernel(x_ref, m_ref, wh_ref, *rest, hidden: int, mxu_dtype,
                 xp_dtype, project: bool):
    from jax.experimental import pallas as pl

    # rest: with ``project`` the input matrix and the bias rows first; the
    # peepholes; the outputs, of which the residuals (zseq, hprev, cprev) are
    # there in training only; the two carry scratches
    if project:
        wx_ref, b_ref, *rest = rest
    pi_ref, pf_ref, po_ref, hseq_ref, hfin_ref, cfin_ref, *rest = rest
    save_residuals = len(rest) == 5
    if save_residuals:
        zseq_ref, hprev_ref, cprev_ref, h_scr, c_scr = rest
    else:
        h_scr, c_scr = rest

    t = pl.program_id(0)
    T = pl.num_programs(0)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)
        c_scr[...] = jnp.zeros_like(c_scr)

    H = hidden
    f32 = jnp.float32
    h = h_scr[...]
    c = c_scr[...]
    if project:
        # linear(x, w_x) + b as XLA makes it: operands in the compute dtype,
        # float32 accumulation, the product and then the sum rounded to the
        # projection's dtype (float32 unless --amp)
        xp = jnp.dot(x_ref[0].astype(mxu_dtype), wx_ref[...].astype(mxu_dtype),
                     preferred_element_type=f32)
        xp = xp.astype(xp_dtype).astype(f32) + b_ref[0]
        xp = xp.astype(xp_dtype).astype(f32)
    else:
        xp = x_ref[0]                       # [B, 4H], bias added
    # matmul operands follow the framework's compute-dtype policy (bf16 by
    # default) so this kernel computes the same function as the lax.scan
    # path (linear()/mxu_cast) that the custom_vjp backward differentiates
    z = xp + jnp.dot(h.astype(mxu_dtype), wh_ref[...].astype(mxu_dtype),
                     preferred_element_type=f32)
    # peephole ("check") vectors ride resident [1,H] blocks; zeros = plain
    # cell (hl_lstm_ops.cuh: i,f see c_prev, o sees c_new)
    i = jax.nn.sigmoid(z[:, :H] + pi_ref[0] * c)
    f = jax.nn.sigmoid(z[:, H : 2 * H] + pf_ref[0] * c)
    g = jnp.tanh(z[:, 3 * H :])
    c_new = f * c + i * g
    o = jax.nn.sigmoid(z[:, 2 * H : 3 * H] + po_ref[0] * c_new)
    h_new = o * jnp.tanh(c_new)
    m = m_ref[0]                            # [B, 1]
    keep = m > 0
    if save_residuals:
        # backward residuals: pre-activations + held carries stream straight
        # out of the forward, so the backward pass needs NO replay scan
        zseq_ref[0] = z.astype(zseq_ref.dtype)
        hprev_ref[0] = h.astype(hprev_ref.dtype)
        cprev_ref[0] = c.astype(cprev_ref.dtype)
    h_new = jnp.where(keep, h_new, h)
    c_new = jnp.where(keep, c_new, c)
    h_scr[...] = h_new
    c_scr[...] = c_new
    # padded steps emit zeros (carry is held in scratch) — identical output
    # semantics to scan_rnn, so the recompute-backward differentiates the
    # same function the forward computes
    hseq_ref[0] = h_new * m

    @pl.when(t == T - 1)
    def _fin():
        hfin_ref[...] = h_new
        cfin_ref[...] = c_new


def _lstm_pallas_raw(x_tb, mask_tb, w_h, pi, pf, po, *, w_x=None, b=None,
                     xp_dtype=jnp.float32, residuals: bool = True):
    """TIME-MAJOR: mask [T,B] and either the input projection with its bias,
    ``x_tb`` [T,B,4H] float32, or (``w_x`` [D,4H] and ``b`` [4H] given) the
    layer input ``x_tb`` [T,B,D] as the layer got it, from which the kernel
    makes ``z_t = (x_t W_x + b) + h_{t-1} W_h`` itself, so that the [T,B,4H]
    float32 projection never crosses HBM; ``xp_dtype`` is the dtype
    ``linear(x, w_x)`` would have given it.  The kernel rounds the products'
    operands to the compute dtype in VMEM.  Mosaic requires the last two
    block dims tile-aligned or full, so time must lead; callers transpose
    once per layer.  ``residuals=False`` (inference / primal-only forward)
    skips the z/h_prev/c_prev outputs entirely — pallas_call is opaque to
    XLA, so unused outputs would otherwise be materialized to HBM (hundreds
    of MB at the gate ceiling), not DCE'd."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops.numerics import compute_dtype
    from paddle_tpu.ops.rnn_fused import residual_dtype

    T, B, D = x_tb.shape
    H = w_h.shape[0]
    H4 = 4 * H
    project = w_x is not None
    rd = residual_dtype(H)
    kernel = functools.partial(_lstm_kernel, hidden=H,
                               mxu_dtype=compute_dtype(),
                               xp_dtype=jnp.dtype(xp_dtype), project=project)
    step = lambda t: (t, 0, 0)
    resident = lambda t: (0, 0)
    in_specs = [
        pl.BlockSpec((1, B, D), step),
        pl.BlockSpec((1, B, 1), step),
        pl.BlockSpec((H, H4), resident),
    ]
    operands = [x_tb, mask_tb[..., None], w_h]
    if project:
        in_specs += [pl.BlockSpec((D, H4), resident),
                     pl.BlockSpec((1, H4), resident)]
        # the bias as the projection's dtype holds it, widened once here
        operands += [w_x, b.astype(xp_dtype).astype(jnp.float32)
                     .reshape(1, H4)]
    in_specs += [pl.BlockSpec((1, H), resident)] * 3
    operands += [pi.reshape(1, H), pf.reshape(1, H), po.reshape(1, H)]
    out_specs = [
        pl.BlockSpec((1, B, H), step),
        pl.BlockSpec((B, H), resident),
        pl.BlockSpec((B, H), resident),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((T, B, H), jnp.float32),
        jax.ShapeDtypeStruct((B, H), jnp.float32),
        jax.ShapeDtypeStruct((B, H), jnp.float32),
    ]
    if residuals:
        out_specs += [
            pl.BlockSpec((1, B, H4), step),
            pl.BlockSpec((1, B, H), step),
            pl.BlockSpec((1, B, H), step),
        ]
        out_shape += [
            jax.ShapeDtypeStruct((T, B, H4), rd),            # z residual
            jax.ShapeDtypeStruct((T, B, H), rd),             # h_prev
            jax.ShapeDtypeStruct((T, B, H), rd),             # c_prev
        ]
    return pl.pallas_call(
        kernel,
        name="lstm_seq_fwd",
        grid=(T,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((B, H), jnp.float32),
            pltpu.VMEM((B, H), jnp.float32),
        ],
        compiler_params=_compiler_params(
            vmem_limit_bytes=RNN_VMEM_LIMIT_BYTES),
        interpret=_interpret(),
    )(*operands)


def _lstm_reference(xp, mask, w_h):
    """Pure-JAX twin (same math, same f32 compute dtype) used for the
    custom_vjp backward; differentiating through the entry casts yields
    gradients in the caller's original dtypes."""
    from paddle_tpu.ops.rnn import lstm_step, scan_rnn

    xp = xp.astype(jnp.float32)
    w_h = w_h.astype(jnp.float32)

    def step(carry, xp_t):
        h, c = carry
        h2, c2 = lstm_step(xp_t, h, c, w_h)
        return (h2, c2), h2

    B = xp.shape[0]
    H = w_h.shape[0]
    z = jnp.zeros((B, H), jnp.float32)
    (h_f, c_f), h_seq = scan_rnn(step, (z, z), xp, mask)
    return h_seq, h_f, c_f


@jax.custom_vjp
def lstm_forward_pallas(xp, mask, w_h):
    """xp: [B,T,4H] input projection (+bias), mask [B,T], w_h [H,4H].
    Returns (h_seq [B,T,H], h_final, c_final), always float32; h_seq is zero
    at padded timesteps (same semantics as the scan path). No peepholes
    (gated upstream).

    Direct kernel entry (tests exercise it in interpret mode; backward is
    autodiff-of-reference).  The PRODUCTION path is
    ops/rnn_fused.lstm_sequence_fused, which pairs the same raw kernel with
    the hand-written fast backward."""
    H = w_h.shape[0]
    zp = jnp.zeros((H,), jnp.float32)
    h_tb, h_f, c_f = _lstm_pallas_raw(
        jnp.moveaxis(xp.astype(jnp.float32), 1, 0),
        jnp.moveaxis(mask.astype(jnp.float32), 1, 0),
        w_h.astype(jnp.float32), zp, zp, zp, residuals=False)
    return jnp.moveaxis(h_tb, 0, 1), h_f, c_f


def _lstm_fwd(xp, mask, w_h):
    out = lstm_forward_pallas(xp, mask, w_h)
    return out, (xp, mask, w_h)


def _lstm_bwd(res, ct):
    xp, mask, w_h = res
    _, vjp = jax.vjp(lambda xp, w_h: _lstm_reference(xp, mask, w_h), xp, w_h)
    d_xp, d_wh = vjp(ct)
    return d_xp, None, d_wh


lstm_forward_pallas.defvjp(_lstm_fwd, _lstm_bwd)


# ---------------------------------------------------------------------------
# GRU: same structure
# ---------------------------------------------------------------------------


def _gru_kernel(xp_ref, m_ref, wh_ref, hseq_ref, hfin_ref, *rest,
                hidden: int, mxu_dtype):
    from jax.experimental import pallas as pl

    save_residuals = len(rest) == 3  # (zseq, hprev, h_scr) vs (h_scr,)
    if save_residuals:
        zseq_ref, hprev_ref, h_scr = rest
    else:
        (h_scr,) = rest

    t = pl.program_id(0)
    T = pl.num_programs(0)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    h = h_scr[...]
    H = hidden
    xp = xp_ref[0]                                      # [B, 3H]
    w = wh_ref[...].astype(mxu_dtype)                   # [H, 3H]

    def rdot(v, lo, hi):
        return jnp.dot(v.astype(mxu_dtype), w[:, lo:hi],
                       preferred_element_type=jnp.float32)

    zr = xp[:, : 2 * H] + rdot(h, 0, 2 * H)
    r = jax.nn.sigmoid(zr[:, :H])
    u = jax.nn.sigmoid(zr[:, H:])
    zc = xp[:, 2 * H :] + rdot(r * h, 2 * H, 3 * H)
    cand = jnp.tanh(zc)
    h_new = u * h + (1.0 - u) * cand
    m = m_ref[0]
    if save_residuals:
        # backward residuals (see _lstm_kernel)
        zseq_ref[0, :, : 2 * H] = zr.astype(zseq_ref.dtype)
        zseq_ref[0, :, 2 * H:] = zc.astype(zseq_ref.dtype)
        hprev_ref[0] = h.astype(hprev_ref.dtype)
    h_new = jnp.where(m > 0, h_new, h)
    h_scr[...] = h_new
    hseq_ref[0] = h_new * m

    @pl.when(t == T - 1)
    def _fin():
        hfin_ref[...] = h_new


def _gru_pallas_raw(xp_tb, mask_tb, w_h, *, residuals: bool = True):
    """TIME-MAJOR (see _lstm_pallas_raw).  ``residuals=False``: inference
    variant without the z/h_prev outputs."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops.numerics import compute_dtype

    T, B, H3 = xp_tb.shape
    H = H3 // 3
    kernel = functools.partial(_gru_kernel, hidden=H,
                               mxu_dtype=compute_dtype())
    step = lambda t: (t, 0, 0)
    out_specs = [
        pl.BlockSpec((1, B, H), step),
        pl.BlockSpec((B, H), lambda t: (0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((T, B, H), jnp.float32),
        jax.ShapeDtypeStruct((B, H), jnp.float32),
    ]
    if residuals:
        from paddle_tpu.ops.rnn_fused import residual_dtype

        rd = residual_dtype(H)
        out_specs += [
            pl.BlockSpec((1, B, H3), step),
            pl.BlockSpec((1, B, H), step),
        ]
        out_shape += [
            jax.ShapeDtypeStruct((T, B, H3), rd),            # z residual
            jax.ShapeDtypeStruct((T, B, H), rd),             # h_prev
        ]
    return pl.pallas_call(
        kernel,
        name="gru_seq_fwd",
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, B, H3), step),
            pl.BlockSpec((1, B, 1), step),
            pl.BlockSpec((H, H3), lambda t: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((B, H), jnp.float32)],
        compiler_params=_compiler_params(
            vmem_limit_bytes=RNN_VMEM_LIMIT_BYTES),
        interpret=_interpret(),
    )(xp_tb, mask_tb[..., None], w_h)


def _gru_reference(xp, mask, w_h):
    from paddle_tpu.ops.rnn import gru_step, scan_rnn

    xp = xp.astype(jnp.float32)
    w_h = w_h.astype(jnp.float32)

    def step(h, xp_t):
        h2 = gru_step(xp_t, h, w_h)
        return h2, h2

    B = xp.shape[0]
    H = w_h.shape[0]
    h_f, h_seq = scan_rnn(step, jnp.zeros((B, H), jnp.float32), xp, mask)
    return h_seq, h_f


@jax.custom_vjp
def gru_forward_pallas(xp, mask, w_h):
    """xp: [B,T,3H], mask [B,T], w_h [H,3H] -> (h_seq [B,T,H], h_final),
    always float32; h_seq is zero at padded timesteps.

    Direct kernel entry (tests/interpret mode); production uses
    ops/rnn_fused.gru_sequence_fused — see lstm_forward_pallas."""
    h_tb, h_f = _gru_pallas_raw(
        jnp.moveaxis(xp.astype(jnp.float32), 1, 0),
        jnp.moveaxis(mask.astype(jnp.float32), 1, 0),
        w_h.astype(jnp.float32), residuals=False)
    return jnp.moveaxis(h_tb, 0, 1), h_f


def _gru_fwd(xp, mask, w_h):
    out = gru_forward_pallas(xp, mask, w_h)
    return out, (xp, mask, w_h)


def _gru_bwd(res, ct):
    xp, mask, w_h = res
    _, vjp = jax.vjp(lambda xp, w_h: _gru_reference(xp, mask, w_h), xp, w_h)
    d_xp, d_wh = vjp(ct)
    return d_xp, None, d_wh


gru_forward_pallas.defvjp(_gru_fwd, _gru_bwd)


# ---------------------------------------------------------------------------
# Backward time-loop kernels: the reverse scans of rnn_fused as single
# Pallas programs.  Residuals (z, carries) stream in per step, the d_h/d_c
# cotangent carries live in VMEM scratch, the transposed recurrent weight
# stays resident, and the per-step d_z cotangent streams out — the
# hand-written reverse half of hl_cuda_lstm.cu, TPU-style.  The batched
# d_w_h einsum and d_xp remain outside (they are one-shot MXU ops); the
# LSTM's bias and peephole gradients are accumulated inside its loop.
# ---------------------------------------------------------------------------


def _lstm_bwd_kernel(dout_ref, m_ref, z_ref, cp_ref, wt_ref, pi_ref,
                     pf_ref, po_ref, dhfin_ref, dcfin_ref,
                     dz_ref, dh0_ref, dc0_ref, db_ref, *rest, hidden: int):
    """One reverse step (grid runs t = T-1 .. 0 via the index maps).
    Mirrors rnn_fused._lstm_seq_bwd.rev_step numerics exactly (f32),
    including peephole feedthrough.  The parameter-sized reductions of
    ``d_z`` over (time, batch) are accumulated here, where every operand is
    already in VMEM: the bias gradient always, the three peephole gradients
    when peepholes are live (rest = (dpeep_ref, scratches) or scratches)."""
    from jax.experimental import pallas as pl

    if len(rest) == 5:
        dpeep_ref, dh_scr, dc_scr, db_scr, dpeep_scr = rest
    else:
        dpeep_ref = dpeep_scr = None
        dh_scr, dc_scr, db_scr = rest

    t = pl.program_id(0)
    T = pl.num_programs(0)
    H = hidden

    @pl.when(t == 0)  # first grid step == last timestep: d_hfin/d_cfin seed
    def _init():
        dh_scr[...] = dhfin_ref[...]
        dc_scr[...] = dcfin_ref[...]
        db_scr[...] = jnp.zeros_like(db_scr)
        if dpeep_scr is not None:
            dpeep_scr[...] = jnp.zeros_like(dpeep_scr)

    d_h = dh_scr[...]
    d_c = dc_scr[...]
    z = z_ref[0].astype(jnp.float32)
    cp = cp_ref[0].astype(jnp.float32)
    pi = pi_ref[0]
    pf = pf_ref[0]
    po = po_ref[0]
    i = jax.nn.sigmoid(z[:, :H] + pi * cp)
    f = jax.nn.sigmoid(z[:, H: 2 * H] + pf * cp)
    g = jnp.tanh(z[:, 3 * H:])
    cn = f * cp + i * g
    o = jax.nn.sigmoid(z[:, 2 * H: 3 * H] + po * cn)
    tc = jnp.tanh(cn)
    m = m_ref[0]
    mcol = (m > 0).astype(jnp.float32)
    d_hnew = mcol * (dout_ref[0] + d_h)
    d_zo = d_hnew * tc * o * (1 - o)
    d_cnew = mcol * d_c + d_hnew * o * (1.0 - tc * tc) + d_zo * po
    d_zi = d_cnew * g * i * (1 - i)
    d_zf = d_cnew * cp * f * (1 - f)
    d_z = jnp.concatenate([
        d_zi, d_zf, d_zo, d_cnew * i * (1 - g * g)], -1)
    d_hp = jnp.dot(d_z, wt_ref[...], preferred_element_type=jnp.float32)
    dh_scr[...] = (1.0 - mcol) * d_h + d_hp
    dc_scr[...] = ((1.0 - mcol) * d_c + d_cnew * f
                   + d_zi * pi + d_zf * pf)
    # the only rounding of d_z: the HBM copy takes the block's dtype; the
    # carry product above and the accumulators below keep the float32 value
    dz_ref[0] = d_z.astype(dz_ref.dtype)

    def sublane_partial(x):
        # [B, n] -> [8, n]: the B/8 sublane tiles added together, vreg by
        # vreg; the 8 -> 1 reduction across sublanes waits for the last step
        return x.reshape(x.shape[0] // 8, 8, x.shape[1]).sum(0)

    # masked steps add zero: every term of d_z carries mcol
    db_scr[...] += sublane_partial(d_z)
    if dpeep_scr is not None:
        dpeep_scr[0] += sublane_partial(d_zi * cp)
        dpeep_scr[1] += sublane_partial(d_zf * cp)
        dpeep_scr[2] += sublane_partial(d_zo * cn)

    @pl.when(t == T - 1)  # last grid step == timestep 0
    def _fin():
        dh0_ref[...] = dh_scr[...]
        dc0_ref[...] = dc_scr[...]
        db_ref[...] = db_scr[...].sum(0, keepdims=True)
        if dpeep_scr is not None:
            dpeep_ref[...] = dpeep_scr[...].sum(1)


def _lstm_bwd_pallas_raw(dout_tb, m_tb, z_tb, cp_tb, w_t, pi, pf, po,
                         d_hfin, d_cfin, *, has_peepholes: bool = True,
                         dz_dtype=jnp.float32):
    """TIME-MAJOR: dout/m/z/cp [T,B,*] f32; w_t: [4H,H] (w_h transposed);
    pi/pf/po: [1,H] peephole rows; d_hfin/d_cfin: [B,H] cotangent seeds
    (loaded into the carry scratch at the last timestep — they propagate
    through masked tails exactly as the scan's initial carry does).
    Returns (d_z [T,B,4H] stored as ``dz_dtype``, d_h0, d_c0, d_b [1,4H] =
    the float32 d_z summed over (t, b), d_peep [3,H] = rows d_pi, d_pf,
    d_po, or None without peepholes).
    B must be a multiple of 8 (the gate's tile constraint): the
    accumulators hold one sublane tile per feature column."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, B, H4 = z_tb.shape
    H = H4 // 4
    rev = lambda t: (T - 1 - t, 0, 0)
    resident = lambda t: (0, 0)
    kernel = functools.partial(_lstm_bwd_kernel, hidden=H)
    out_specs = [
        pl.BlockSpec((1, B, H4), rev),
        pl.BlockSpec((B, H), resident),
        pl.BlockSpec((B, H), resident),
        pl.BlockSpec((1, H4), resident),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((T, B, H4), dz_dtype),
        jax.ShapeDtypeStruct((B, H), jnp.float32),
        jax.ShapeDtypeStruct((B, H), jnp.float32),
        jax.ShapeDtypeStruct((1, H4), jnp.float32),
    ]
    scratch_shapes = [
        pltpu.VMEM((B, H), jnp.float32),
        pltpu.VMEM((B, H), jnp.float32),
        pltpu.VMEM((8, H4), jnp.float32),
    ]
    if has_peepholes:
        out_specs.append(pl.BlockSpec((3, H), resident))
        out_shape.append(jax.ShapeDtypeStruct((3, H), jnp.float32))
        scratch_shapes.append(pltpu.VMEM((3, 8, H), jnp.float32))
    outs = pl.pallas_call(
        kernel,
        name="lstm_seq_bwd",
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, B, H), rev),
            pl.BlockSpec((1, B, 1), rev),
            pl.BlockSpec((1, B, H4), rev),
            pl.BlockSpec((1, B, H), rev),
            pl.BlockSpec((H4, H), resident),
            pl.BlockSpec((1, H), resident),
            pl.BlockSpec((1, H), resident),
            pl.BlockSpec((1, H), resident),
            pl.BlockSpec((B, H), resident),
            pl.BlockSpec((B, H), resident),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        compiler_params=_compiler_params(
            vmem_limit_bytes=RNN_VMEM_LIMIT_BYTES),
        interpret=_interpret(),
    )(dout_tb, m_tb[..., None], z_tb, cp_tb, w_t, pi, pf, po,
      d_hfin, d_cfin)
    d_z, d_h0, d_c0, d_b, *d_peep = outs
    return d_z, d_h0, d_c0, d_b, (d_peep[0] if has_peepholes else None)


def _gru_bwd_kernel(dout_ref, m_ref, z_ref, hp_ref, wt_ref, dhfin_ref,
                    dz_ref, dh0_ref, dh_scr, *, hidden: int):
    """Reverse GRU step — mirrors rnn_fused._gru_seq_bwd.rev_step (f32)."""
    from jax.experimental import pallas as pl

    t = pl.program_id(0)
    T = pl.num_programs(0)
    H = hidden

    @pl.when(t == 0)  # d_hfin seeds the carry at the last timestep
    def _init():
        dh_scr[...] = dhfin_ref[...]

    d_c = dh_scr[...]
    z = z_ref[0].astype(jnp.float32)
    hp = hp_ref[0].astype(jnp.float32)
    r = jax.nn.sigmoid(z[:, :H])
    u = jax.nn.sigmoid(z[:, H: 2 * H])
    cand = jnp.tanh(z[:, 2 * H:])
    m = m_ref[0]
    mcol = (m > 0).astype(jnp.float32)
    d_hnew = mcol * (dout_ref[0] + d_c)
    d_u = d_hnew * (hp - cand)
    d_zc = d_hnew * (1.0 - u) * (1.0 - cand * cand)
    w_t = wt_ref[...]

    def rtdot(v, lo, hi):
        return jnp.dot(v, w_t[lo:hi, :], preferred_element_type=jnp.float32)

    d_rh = rtdot(d_zc, 2 * H, 3 * H)
    d_r = d_rh * hp
    d_zr = jnp.concatenate([d_r * r * (1 - r), d_u * u * (1 - u)], -1)
    d_hp = d_hnew * u + d_rh * r + rtdot(d_zr, 0, 2 * H)
    dh_scr[...] = (1.0 - mcol) * d_c + d_hp
    dz_ref[0, :, : 2 * H] = d_zr
    dz_ref[0, :, 2 * H:] = d_zc

    @pl.when(t == T - 1)
    def _fin():
        dh0_ref[...] = dh_scr[...]


def _gru_bwd_pallas_raw(dout_tb, m_tb, z_tb, hp_tb, w_t, d_hfin):
    """TIME-MAJOR twin of _lstm_bwd_pallas_raw for the GRU; w_t: [3H,H]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, B, H3 = z_tb.shape
    H = H3 // 3
    rev = lambda t: (T - 1 - t, 0, 0)
    kernel = functools.partial(_gru_bwd_kernel, hidden=H)
    return pl.pallas_call(
        kernel,
        name="gru_seq_bwd",
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, B, H), rev),
            pl.BlockSpec((1, B, 1), rev),
            pl.BlockSpec((1, B, H3), rev),
            pl.BlockSpec((1, B, H), rev),
            pl.BlockSpec((H3, H), lambda t: (0, 0)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, B, H3), rev),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, H3), jnp.float32),
            jax.ShapeDtypeStruct((B, H), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((B, H), jnp.float32)],
        compiler_params=_compiler_params(
            vmem_limit_bytes=RNN_VMEM_LIMIT_BYTES),
        interpret=_interpret(),
    )(dout_tb, m_tb[..., None], z_tb, hp_tb, w_t, d_hfin)


# ---------------------------------------------------------------------------
# Row logsumexp with ONE HBM pass: each grid step loads a full-vocab
# [row_tile, V] block into VMEM (f32 temporaries included — size the tile
# accordingly) and reduces it there, where XLA's fused max + exp-sum
# otherwise reads the [N, V] logits buffer twice (~737 MB of bf16 per pass
# at WMT14 bench shapes).  NOTE: A/B-measured SLOWER than the XLA two-pass
# on v5e (see losses._USE_PALLAS_LSE_READOUT) — kept as a recorded losing
# A/B with its interpret-mode equivalence test.  Rows must divide into the
# tile (logsumexp_rows_pallas raises otherwise) — anyone re-running the
# A/B at new shapes must re-check that gate.
# ---------------------------------------------------------------------------


def _lse_kernel(x_ref, lse_ref):
    x = x_ref[...].astype(jnp.float32)           # [TN, V] — full row in VMEM
    m = jnp.max(x, axis=-1, keepdims=True)
    lse_ref[...] = m + jnp.log(jnp.sum(jnp.exp(x - m), axis=-1,
                                       keepdims=True))


def logsumexp_rows_pallas(x, *, row_tile: int = 64):
    """x [N, V] -> lse [N] f32 with ONE HBM pass over x: each grid step
    loads a [row_tile, V] block (the full vocab row — V need not be
    lane-aligned when the block spans the whole axis) and reduces it in
    VMEM.  Caller gates: N % row_tile == 0 and row_tile*V*itemsize within
    VMEM incl. the f32 exp temporaries (~12 MB at bf16 row_tile=64, V=30k)."""
    from jax.experimental import pallas as pl

    N, V = x.shape
    row_tile = min(row_tile, N)
    if N % row_tile:
        raise ValueError(f"N={N} not divisible by row_tile={row_tile}")
    out = pl.pallas_call(
        _lse_kernel,
        name="lse_rows",
        grid=(N // row_tile,),
        in_specs=[pl.BlockSpec((row_tile, V), lambda n: (n, 0))],
        out_specs=pl.BlockSpec((row_tile, 1), lambda n: (n, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1), jnp.float32),
        interpret=_interpret(),
    )(x)
    return out[:, 0]


# ---------------------------------------------------------------------------
# Attention GRU decoder time-loop kernels — the flagship's structural
# bottleneck (VERDICT r4 item 1).  The XLA scan re-reads enc [B,S,2H] and
# enc_proj [B,S,A] from HBM on EVERY decoder step, and the backward
# additionally carries the d_enc_proj [B,S,A] f32 cotangent accumulator
# through HBM each reverse step (~88 MB/step at WMT14 bench shapes,
# ~2.8 GB per backward).  Here the grid is (batch-blocks, T) with time
# innermost: enc/enc_proj (and in the backward, the d_enc_proj accumulator
# block) stay VMEM-RESIDENT across all T steps of a batch block — per-step
# HBM traffic drops to the small [Bb,*] streams.  Mosaic's default 16 MB
# scoped-VMEM cap is raised via CompilerParams (v5e has 128 MB physical
# VMEM); block sizes are gated to fit.
#
# Numerics mirror ops/attention_decoder.py exactly: forward follows
# _fwd_step (compute-dtype MXU operands, f32 accumulation), backward
# follows _agd_bwd.rev_step (all-f32 with compute-dtype enc/enc_proj
# reads), so the interpret-mode equivalence tests compare bitwise-same
# ops on CPU (f32 policy).
# ---------------------------------------------------------------------------


def _attn_dec_fwd_kernel(xp_y_ref, m_ref, s0_ref, encP_ref, enc_ref,
                         smask_ref, attw_ref, attv_ref, wxc_ref, wh_ref,
                         out_ref, probs_ref, ctx_ref, sprev_ref,
                         s_scr, *, mxu_dtype):
    from jax.experimental import pallas as pl

    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        s_scr[...] = s0_ref[...]

    s = s_scr[...]                                   # [Bb, D] f32
    f32 = jnp.float32
    # --- additive_attention_scores (mirrors _fwd_step) ---
    q = jnp.dot(s.astype(mxu_dtype), attw_ref[...],
                preferred_element_type=f32)          # [Bb, A]
    encP = encP_ref[...]                             # [Bb, S, A] cd
    pre = jnp.tanh(encP + q[:, None, :].astype(encP.dtype))
    # score reduction on the VPU: Mosaic supports neither the
    # [Bb,S,A]->[Bb*S,A] matvec route's output fold nor batched matvecs
    scores = jnp.sum((pre * attv_ref[...][None]).astype(f32), axis=-1)
    # --- attend ---
    smask = smask_ref[...]                           # [Bb, S] f32
    neg = jnp.finfo(f32).min
    z = jnp.where(smask > 0, scores, neg)
    w0 = jax.nn.softmax(z, axis=-1)
    w1 = w0 * smask
    n = jnp.maximum(jnp.sum(w1, axis=-1, keepdims=True), 1e-9)
    w = w1 / n                                       # [Bb, S] f32
    # batched matvec ctx[b] = w[b] @ enc[b] as a VPU broadcast-multiply +
    # S-reduction: Mosaic lowers neither the [Bb,S]->[Bb,1,S] shape cast
    # nor a dot_general with no lhs non-contracting dims
    # (minor-dim insert must happen on the f32 array — Mosaic only supports
    # non-no-op minor-dim insertion for 32-bit types)
    ctx = jnp.sum((w[:, :, None].astype(mxu_dtype)
                   * enc_ref[...]).astype(f32), axis=1)     # [Bb, 2H]
    # --- input projection + gru_step ---
    D = s.shape[-1]
    xp = xp_y_ref[0] + jnp.dot(ctx.astype(mxu_dtype), wxc_ref[...],
                               preferred_element_type=f32)      # [Bb, 3D]
    zr = xp[:, : 2 * D] + jnp.dot(s.astype(mxu_dtype), wh_ref[:, : 2 * D],
                                  preferred_element_type=f32)
    r = jax.nn.sigmoid(zr[:, :D])
    u = jax.nn.sigmoid(zr[:, D:])
    cand = jnp.tanh(xp[:, 2 * D:]
                    + jnp.dot((r * s).astype(mxu_dtype), wh_ref[:, 2 * D:],
                              preferred_element_type=f32))
    s_new = u * s + (1.0 - u) * cand
    m = m_ref[0]                                     # [Bb, 1]
    s_out = jnp.where(m > 0, s_new, s)
    s_scr[...] = s_out
    out_ref[0] = s_out * m
    probs_ref[0] = w
    ctx_ref[0] = ctx.astype(ctx_ref.dtype)
    sprev_ref[0] = s


def attn_dec_fwd_pallas(xp_y_tb, m_tb, s0, enc, enc_proj, src_mask,
                        att_w, att_v, wx_c, wh, *, block_b):
    """TIME-MAJOR forward: xp_y [T,B,3D] f32 (teacher-forced half of the
    input projection, bias included), m [T,B] f32, s0 [B,D] f32; enc/
    enc_proj/att_w/att_v/wx_c/wh pre-cast to the compute dtype by the
    caller.  Returns (states [T,B,D] f32, probs [T,B,S] f32, ctx [T,B,2H]
    enc.dtype, s_prev [T,B,D] f32) — identical layout/semantics to
    attention_decoder._decoder_fwd_scan's stacked scan outputs."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops.numerics import compute_dtype

    T, B, D3 = xp_y_tb.shape
    D = D3 // 3
    S, H2 = enc.shape[1], enc.shape[2]
    A = enc_proj.shape[2]
    nB = B // block_b
    Bb = block_b
    kernel = functools.partial(_attn_dec_fwd_kernel,
                               mxu_dtype=compute_dtype())
    step = lambda b, t: (t, b, 0)
    blk = lambda b, t: (b, 0, 0)
    blk2 = lambda b, t: (b, 0)
    const = lambda b, t: (0, 0)
    return pl.pallas_call(
        kernel,
        name="attn_dec_fwd",
        grid=(nB, T),
        in_specs=[
            pl.BlockSpec((1, Bb, D3), step),         # xp_y
            pl.BlockSpec((1, Bb, 1), step),          # mask col
            pl.BlockSpec((Bb, D), blk2),             # s0
            pl.BlockSpec((Bb, S, A), blk),           # enc_proj (resident)
            pl.BlockSpec((Bb, S, H2), blk),          # enc (resident)
            pl.BlockSpec((Bb, S), blk2),             # src_mask
            pl.BlockSpec((D, A), const),             # att_w
            pl.BlockSpec((1, A), const),             # att_v row
            pl.BlockSpec((H2, D3), const),           # wx_c
            pl.BlockSpec((D, D3), const),            # wh
        ],
        out_specs=[
            pl.BlockSpec((1, Bb, D), step),
            pl.BlockSpec((1, Bb, S), step),
            pl.BlockSpec((1, Bb, H2), step),
            pl.BlockSpec((1, Bb, D), step),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, D), jnp.float32),   # states (masked)
            jax.ShapeDtypeStruct((T, B, S), jnp.float32),   # attention probs
            jax.ShapeDtypeStruct((T, B, H2), enc.dtype),    # ctx residual
            jax.ShapeDtypeStruct((T, B, D), jnp.float32),   # s_prev residual
        ],
        scratch_shapes=[pltpu.VMEM((Bb, D), jnp.float32)],
        compiler_params=_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_interpret(),
    )(xp_y_tb, m_tb[..., None], s0, enc_proj, enc, src_mask,
      att_w, att_v.reshape(1, A), wx_c, wh)


def _attn_dec_bwd_kernel(dout_ref, m_ref, sp_ref, r_ref, u_ref, cand_ref,
                         q_ref, encP_ref, enc_ref, smask_ref,
                         attwT_ref, attv_ref, attvf_ref,
                         whTzr_ref, whTc_ref, wxcT_ref,
                         dxp_ref, sumdpre_ref, dencP_ref, dv_ref, ds0_ref,
                         ds_scr, *, mxu_dtype):
    from jax.experimental import pallas as pl

    t = pl.program_id(1)
    T = pl.num_programs(1)
    f32 = jnp.float32

    @pl.when(t == 0)  # first grid step == LAST timestep: zero cotangent seed
    def _init():
        ds_scr[...] = jnp.zeros_like(ds_scr)
        dencP_ref[...] = jnp.zeros_like(dencP_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    d_s = ds_scr[...]                                # [Bb, D]
    m = m_ref[0]                                     # [Bb, 1]
    mcol = (m > 0).astype(f32)
    d_snew = mcol * (dout_ref[0] + d_s)
    sp = sp_ref[0]                                   # [Bb, D] f32
    r = r_ref[0]
    u = u_ref[0]
    cand = cand_ref[0]

    # ---- GRU backward (gates precomputed outside, streamed in) ----
    d_u = d_snew * (sp - cand)
    d_cand = d_snew * (1.0 - u)
    d_h = d_snew * u
    d_zc = d_cand * (1.0 - cand * cand)
    d_rh = jnp.dot(d_zc, whTc_ref[...], preferred_element_type=f32)
    d_r = d_rh * sp
    d_h = d_h + d_rh * r
    d_zr = jnp.concatenate([d_r * r * (1 - r), d_u * u * (1 - u)], -1)
    d_h = d_h + jnp.dot(d_zr, whTzr_ref[...], preferred_element_type=f32)
    d_xp = jnp.concatenate([d_zr, d_zc], -1)         # [Bb, 3D]
    d_ctx = jnp.dot(d_xp, wxcT_ref[...], preferred_element_type=f32)

    # ---- attention backward (mirrors _agd_bwd.rev_step) ----
    enc = enc_ref[...]                               # [Bb, S, 2H] cd
    # batched matvec d_w[b,s] = d_ctx[b] . enc[b,s] on the VPU (see the
    # forward kernel's ctx note)
    d_w = jnp.sum((d_ctx[:, None, :].astype(enc.dtype) * enc).astype(f32),
                  axis=-1)                           # [Bb, S]
    encP = encP_ref[...]
    q = q_ref[0]                                     # [Bb, A] f32
    pre = jnp.tanh(encP + q[:, None, :].astype(encP.dtype))
    scores = jnp.sum((pre * attv_ref[...][None]).astype(f32), axis=-1)
    smask = smask_ref[...]
    maskb = smask > 0
    neg = jnp.finfo(f32).min
    z = jnp.where(maskb, scores, neg)
    w0 = jax.nn.softmax(z, axis=-1)
    w1 = w0 * smask
    n = jnp.maximum(jnp.sum(w1, axis=-1, keepdims=True), 1e-9)
    d_w1 = d_w / n
    d_n = -jnp.sum(d_w * w1, axis=-1, keepdims=True) / (n * n)
    d_w1 = d_w1 + d_n * (jnp.sum(w1, -1, keepdims=True) > 1e-9).astype(f32)
    d_w0 = d_w1 * smask
    d_z = w0 * (d_w0 - jnp.sum(w0 * d_w0, axis=-1, keepdims=True))
    d_scores = jnp.where(maskb, d_z, 0.0)
    pre_f = pre.astype(f32)
    d_pre = (1.0 - pre_f * pre_f) * (d_scores[..., None] * attvf_ref[0])
    dencP_ref[...] += d_pre                          # VMEM-resident accum
    sum_dpre = jnp.sum(d_pre, axis=1)                # [Bb, A]
    d_h = d_h + jnp.dot(sum_dpre, attwT_ref[...], preferred_element_type=f32)
    # d_v block is [1, 8, A] (8 sublane rows purely for Mosaic tiling; only
    # row 0 carries data — the wrapper sums row 0 over blocks).  VPU
    # broadcast-reduce: Mosaic can't fold [Bb,S] into lanes for a matvec.
    dv_ref[0, 0:1, :] += jnp.sum(d_scores[:, :, None] * pre_f,
                                 axis=(0, 1))[None, :]

    ds_scr[...] = (1.0 - mcol) * d_s + d_h
    dxp_ref[0] = d_xp
    sumdpre_ref[0] = sum_dpre

    @pl.when(t == T - 1)  # last grid step == timestep 0
    def _fin():
        ds0_ref[...] = ds_scr[...]


def attn_dec_bwd_pallas(dout_tb, m_tb, sp_tb, r_tb, u_tb, cand_tb, q_tb,
                        enc, enc_proj, src_mask,
                        att_w_f, att_v_cd, att_v_f, wh_f, wx_c_f, *,
                        block_b):
    """TIME-MAJOR reverse pass.  dout/sp/r/u/cand [T,B,D] f32, q [T,B,A]
    f32, m [T,B] f32; enc/enc_proj compute dtype; *_f weights f32.
    Returns (d_xp [T,B,3D] f32, sum_dpre [T,B,A] f32, d_encP [B,S,A] f32,
    d_v [A] f32, d_s0 [B,D] f32) — the exact quantities _agd_bwd's reverse
    scan produces; every weight gradient is reconstructed outside from
    these (one batched MXU contraction each)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops.numerics import compute_dtype

    T, B, D = dout_tb.shape
    S, H2 = enc.shape[1], enc.shape[2]
    A = enc_proj.shape[2]
    nB = B // block_b
    Bb = block_b
    kernel = functools.partial(_attn_dec_bwd_kernel,
                               mxu_dtype=compute_dtype())
    rev = lambda b, t: (T - 1 - t, b, 0)
    blk = lambda b, t: (b, 0, 0)
    blk2 = lambda b, t: (b, 0)
    const = lambda b, t: (0, 0)
    outs = pl.pallas_call(
        kernel,
        name="attn_dec_bwd",
        grid=(nB, T),
        in_specs=[
            pl.BlockSpec((1, Bb, D), rev),           # d_out
            pl.BlockSpec((1, Bb, 1), rev),           # mask col
            pl.BlockSpec((1, Bb, D), rev),           # s_prev
            pl.BlockSpec((1, Bb, D), rev),           # r
            pl.BlockSpec((1, Bb, D), rev),           # u
            pl.BlockSpec((1, Bb, D), rev),           # cand
            pl.BlockSpec((1, Bb, A), rev),           # q
            pl.BlockSpec((Bb, S, A), blk),           # enc_proj (resident)
            pl.BlockSpec((Bb, S, H2), blk),          # enc (resident)
            pl.BlockSpec((Bb, S), blk2),             # src_mask
            pl.BlockSpec((A, D), const),             # att_w^T f32
            pl.BlockSpec((1, A), const),             # att_v cd row
            pl.BlockSpec((1, A), const),             # att_v f32 row
            pl.BlockSpec((2 * D, D), const),         # wh[:, :2D]^T f32
            pl.BlockSpec((D, D), const),             # wh[:, 2D:]^T f32
            pl.BlockSpec((3 * D, H2), const),        # wx_c^T f32
        ],
        out_specs=[
            pl.BlockSpec((1, Bb, 3 * D), rev),
            pl.BlockSpec((1, Bb, A), rev),
            pl.BlockSpec((Bb, S, A), blk),           # d_encP (resident accum)
            pl.BlockSpec((1, 8, A), blk),            # d_v per block (row 0)
            pl.BlockSpec((Bb, D), blk2),             # d_s0
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, 3 * D), jnp.float32),
            jax.ShapeDtypeStruct((T, B, A), jnp.float32),
            jax.ShapeDtypeStruct((B, S, A), jnp.float32),
            jax.ShapeDtypeStruct((nB, 8, A), jnp.float32),
            jax.ShapeDtypeStruct((B, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((Bb, D), jnp.float32)],
        compiler_params=_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_interpret(),
    )(dout_tb, m_tb[..., None], sp_tb, r_tb, u_tb, cand_tb, q_tb,
      enc_proj, enc, src_mask,
      jnp.transpose(att_w_f), att_v_cd.reshape(1, A),
      att_v_f.reshape(1, A),
      jnp.transpose(wh_f[:, : 2 * D]), jnp.transpose(wh_f[:, 2 * D:]),
      jnp.transpose(wx_c_f))
    d_xp_tb, sum_dpre_tb, d_encP, d_v_blocks, d_s0 = outs
    return (d_xp_tb, sum_dpre_tb, d_encP,
            jnp.sum(d_v_blocks[:, 0, :], axis=0), d_s0)


# ---------------------------------------------------------------------------
# Fused vocab-readout + softmax-CE kernels — the flagship's other
# bandwidth tier.  The XLA path materializes the [B*T, V] logits (bf16)
# and, in the backward, the same-shaped d_logits, then re-reads each for
# the softmax statistics / the two weight contractions: ~2.2 GB of HBM
# traffic per step at WMT14 bench shapes on top of the matmul FLOPs.
# Here the vocabulary is tiled:
#
# - forward, grid (row-blocks, vocab-tiles) with vocab innermost: each
#   [Rb, Vt] logits tile is computed on the MXU and consumed IN VMEM by an
#   online max/sum-exp update (flash-attention-style) + the label-logit
#   gather; the tile is also streamed out in bf16 as the backward residual
#   (one write instead of XLA's write + two stat reads).
# - backward, grid (vocab-tiles,) with the full row dimension resident:
#   each logits tile is read once, d_l = (softmax - onehot)*scale is formed
#   in VMEM and immediately contracted into BOTH d_states (resident f32
#   accumulator) and that tile's d_w column block — d_logits never exists
#   in HBM.
#
# The vocabulary is padded to a lane multiple by the wrapper with bias
# -1e30 (exp underflows to 0, so the statistics and gradients are exact).
# ---------------------------------------------------------------------------


def _ce_fwd_kernel(s_ref, w_ref, b_ref, lab_ref,
                   ptok_ref, lse_ref, ltile_ref,
                   m_scr, s_scr, tok_scr, *, v_tile: int):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    v = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(v == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        s_scr[...] = jnp.zeros_like(s_scr)
        tok_scr[...] = jnp.zeros_like(tok_scr)

    l = jnp.dot(s_ref[...], w_ref[...],
                preferred_element_type=f32) + b_ref[...]      # [Rb, Vt] f32
    ltile_ref[...] = l.astype(ltile_ref.dtype)
    m_old = m_scr[...]
    m_new = jnp.maximum(m_old, jnp.max(l, axis=-1, keepdims=True))
    s_scr[...] = (s_scr[...] * jnp.exp(m_old - m_new)
                  + jnp.sum(jnp.exp(l - m_new), axis=-1, keepdims=True))
    m_scr[...] = m_new
    col = jax.lax.broadcasted_iota(jnp.int32, l.shape, 1) + v * v_tile
    hit = col == lab_ref[...]
    tok_scr[...] += jnp.sum(jnp.where(hit, l, 0.0), axis=-1, keepdims=True)

    @pl.when(v == nv - 1)
    def _fin():
        lse = m_scr[...] + jnp.log(s_scr[...])
        lse_ref[...] = lse
        ptok_ref[...] = lse - tok_scr[...]


def ce_readout_fwd_pallas(states_c, w_c, b_f, labels, *,
                          row_block: int, v_tile: int):
    """states_c [N, D] compute dtype, w_c [D, V'] compute dtype, b_f [1, V']
    f32 (padded tail at -1e30), labels [N, 1] i32 -> (per_tok [N,1] f32,
    lse [N,1] f32, logits [N, V'] compute dtype — the backward residual)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, D = states_c.shape
    Vp = w_c.shape[1]
    nR, nV = N // row_block, Vp // v_tile
    Rb, Vt = row_block, v_tile
    kernel = functools.partial(_ce_fwd_kernel, v_tile=Vt)
    return pl.pallas_call(
        kernel,
        name="ce_readout_fwd",
        grid=(nR, nV),
        in_specs=[
            pl.BlockSpec((Rb, D), lambda r, v: (r, 0)),    # states (resident)
            pl.BlockSpec((D, Vt), lambda r, v: (0, v)),    # w tile
            pl.BlockSpec((1, Vt), lambda r, v: (0, v)),    # bias tile
            pl.BlockSpec((Rb, 1), lambda r, v: (r, 0)),    # labels
        ],
        out_specs=[
            pl.BlockSpec((Rb, 1), lambda r, v: (r, 0)),
            pl.BlockSpec((Rb, 1), lambda r, v: (r, 0)),
            pl.BlockSpec((Rb, Vt), lambda r, v: (r, v)),   # logits residual
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, Vp), states_c.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((Rb, 1), jnp.float32),
            pltpu.VMEM((Rb, 1), jnp.float32),
            pltpu.VMEM((Rb, 1), jnp.float32),
        ],
        compiler_params=_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_interpret(),
    )(states_c, w_c, b_f, labels)


def _ce_bwd_kernel(l_ref, s_ref, w_ref, lab_ref, lse_ref, scale_ref,
                   ds_ref, dw_ref, db_ref, *, v_tile: int, mxu_dtype):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    v = pl.program_id(0)

    @pl.when(v == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    l = l_ref[...].astype(f32)                            # [N, Vt]
    p = jnp.exp(l - lse_ref[...])
    col = jax.lax.broadcasted_iota(jnp.int32, l.shape, 1) + v * v_tile
    hit = col == lab_ref[...]
    d_l = (p - jnp.where(hit, 1.0, 0.0)) * scale_ref[...]
    db_ref[...] = jnp.sum(d_l, axis=0, keepdims=True)
    d_lc = d_l.astype(mxu_dtype)
    # d_states += d_l @ w_tile^T  (accumulates across vocab tiles in VMEM)
    ds_ref[...] += jax.lax.dot_general(
        d_lc, w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=f32)
    # d_w tile = states^T @ d_l — contract the row dim
    dw_ref[...] = jax.lax.dot_general(
        s_ref[...], d_lc, (((0,), (0,)), ((), ())),
        preferred_element_type=f32)


def ce_readout_bwd_pallas(logits_c, states_c, w_c, labels, lse, scale, *,
                          v_tile: int):
    """One pass over the saved bf16 logits: d_l is formed per [N, Vt] tile
    in VMEM and contracted immediately.  Returns (d_states [N, D] f32,
    d_w [D, V'] f32, d_b [1, V'] f32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops.numerics import compute_dtype

    N, Vp = logits_c.shape
    D = states_c.shape[1]
    nV = Vp // v_tile
    Vt = v_tile
    kernel = functools.partial(_ce_bwd_kernel, v_tile=Vt,
                               mxu_dtype=compute_dtype())
    return pl.pallas_call(
        kernel,
        name="ce_readout_bwd",
        grid=(nV,),
        in_specs=[
            pl.BlockSpec((N, Vt), lambda v: (0, v)),       # logits tile
            pl.BlockSpec((N, D), lambda v: (0, 0)),        # states (resident)
            pl.BlockSpec((D, Vt), lambda v: (0, v)),       # w tile
            pl.BlockSpec((N, 1), lambda v: (0, 0)),        # labels
            pl.BlockSpec((N, 1), lambda v: (0, 0)),        # lse
            pl.BlockSpec((N, 1), lambda v: (0, 0)),        # scale
        ],
        out_specs=[
            pl.BlockSpec((N, D), lambda v: (0, 0)),        # d_states resident
            pl.BlockSpec((D, Vt), lambda v: (0, v)),
            pl.BlockSpec((1, Vt), lambda v: (0, v)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, D), jnp.float32),
            jax.ShapeDtypeStruct((D, Vp), jnp.float32),
            jax.ShapeDtypeStruct((1, Vp), jnp.float32),
        ],
        compiler_params=_compiler_params(
            dimension_semantics=("arbitrary",),
            # the resident d_states accumulator + states + per-tile
            # temporaries: losses._tiled_ce_cfg admits a shape only while
            # its estimate stays under 108 MiB, and the installed v5e
            # compiler accepts this limit of its 128 MiB
            # (tests/test_tpu_compile.py)
            vmem_limit_bytes=112 * 1024 * 1024),
        interpret=_interpret(),
    )(logits_c, states_c, w_c, labels, lse, scale)


# ---------------------------------------------------------------------------
# Fused vocab-tiled top-k + logsumexp readout — the decode engine's kernel
# (ops/decode.py).  The unfused decode step materializes the full [B*K, V]
# logits in HBM, log-softmaxes them in f32 (a second same-shaped buffer),
# and top-k's over K*V — at the WMT14 gen shape that is ~46 MB of HBM
# round-trips per emitted token for statistics that fit in a few lanes.
# Here the vocabulary is tiled exactly like the CE readout above: each
# [Rb, Vt] logits tile is computed on the MXU (or streamed in, for the
# pre-materialized-logits variant) and consumed IN VMEM by
#
#   - an online max/sum-exp logsumexp update (flash-attention-style), and
#   - a running top-k merge: k masked-argmax passes over (tile ∪ running),
#     tie-broken toward the LOWEST vocab index so the selection is
#     bit-identical to ``lax.top_k`` over the full row (stable sort).
#
# Neither the logits nor any f32 log-softmax buffer ever exists in HBM;
# per row the kernel writes k values + k indices + one logsumexp.  The
# top-k scratch rides lane-padded [Rb, TOPK_LANES] blocks (only the first
# k lanes carry data) — Mosaic-friendly full-lane vectors instead of
# ragged k-wide tiles.  k is a static unroll; the decode gate bounds it.
# ---------------------------------------------------------------------------

#: lane padding of the top-k scratch/output blocks (first k lanes are real)
TOPK_LANES = 128

#: index sentinel for empty top-k slots (greater than any real vocab id)
_IDX_SENTINEL = 2 ** 30

#: bias/padding value for vocab columns past V: exp underflows to exactly
#: zero, so the logsumexp is exact; the top-k merge additionally masks pad
#: columns to -inf so they can never be SELECTED either (a user row may
#: carry -inf logits — constrained decoding — which would otherwise lose
#: to a -1e30 pad and leak out-of-vocab indices)
_PAD_NEG = -1e30


def _topk_lse_update(l, base_col, vocab, k, m_scr, s_scr, tv_scr, ti_scr):
    """Fold one [Rb, Vt] f32 logits tile (global column offset ``base_col``,
    real vocabulary size ``vocab``) into the running logsumexp (m/s) and
    top-k (tv/ti) scratches."""
    f32 = jnp.float32
    # --- online logsumexp ---
    # the lse path runs on FINITE-clamped values: a tile that is entirely
    # -inf for a row (ban-prefix constrained decoding) would otherwise
    # poison the running stats with exp(-inf - -inf) = nan.  Clamped
    # entries contribute exp(finfo.min - m) == 0 exactly once any finite
    # logit has been seen, so the statistics stay exact; an all--inf row
    # yields ~finfo.min instead of the reference's nan (documented edge).
    lo = jnp.finfo(f32).min
    l_lse = jnp.maximum(l, lo)
    m_old = m_scr[...]
    m_new = jnp.maximum(m_old, jnp.max(l_lse, axis=-1, keepdims=True))
    s_scr[...] = (s_scr[...] * jnp.exp(m_old - m_new)
                  + jnp.sum(jnp.exp(l_lse - m_new), axis=-1, keepdims=True))
    m_scr[...] = m_new
    # --- running top-k merge ---
    col = jax.lax.broadcasted_iota(jnp.int32, l.shape, 1) + base_col
    # pad columns drop to -inf for SELECTION (not for the lse, whose exact
    # zero contribution needs the finite -1e30): a real -inf logit then
    # still beats them on the index tie-break, so indices stay < vocab and
    # all--inf tails resolve to the lowest ids exactly like lax.top_k
    tile_v = jnp.where(col < vocab, l, -jnp.inf)
    tile_i = jnp.where(col < vocab, col, _IDX_SENTINEL)
    run_v, run_i = tv_scr[...], ti_scr[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, run_v.shape, 1)
    new_v = jnp.full_like(run_v, -jnp.inf)
    new_i = jnp.full_like(run_i, _IDX_SENTINEL)
    for j in range(k):
        # the arg-min over matching entries EXCLUDES sentinel-indexed slots
        # (removed winners, empty run slots, pad columns after masking), so
        # a legitimate -inf logit is still selectable by lowest index
        t_m = jnp.max(tile_v, axis=-1, keepdims=True)
        t_i = jnp.min(jnp.where(tile_v == t_m, tile_i, _IDX_SENTINEL),
                      axis=-1, keepdims=True)
        r_m = jnp.max(run_v, axis=-1, keepdims=True)
        r_i = jnp.min(jnp.where(run_v == r_m, run_i, _IDX_SENTINEL),
                      axis=-1, keepdims=True)
        # lax.top_k tie order: equal values resolve to the lower vocab
        # index.  Running entries come from earlier tiles (smaller ids),
        # so on a value tie the tile wins only with a smaller index.
        take_tile = (t_m > r_m) | ((t_m == r_m) & (t_i < r_i))
        c_v = jnp.where(take_tile, t_m, r_m).astype(f32)
        c_i = jnp.where(take_tile, t_i, r_i)
        new_v = jnp.where(lane == j, c_v, new_v)
        new_i = jnp.where(lane == j, c_i, new_i)
        # remove the winner from its source BY INDEX (ids are unique across
        # both): value alone is ambiguous once real -inf logits exist
        hit_t, hit_r = tile_i == c_i, run_i == c_i
        tile_v = jnp.where(hit_t, -jnp.inf, tile_v)
        tile_i = jnp.where(hit_t, _IDX_SENTINEL, tile_i)
        run_v = jnp.where(hit_r, -jnp.inf, run_v)
        run_i = jnp.where(hit_r, _IDX_SENTINEL, run_i)
    tv_scr[...] = new_v
    ti_scr[...] = new_i


def _topk_init(m_scr, s_scr, tv_scr, ti_scr):
    # m starts at the finite f32 min (not -inf): see _topk_lse_update's
    # clamp note.  The top-k value scratch keeps -inf (selection wants
    # true -inf semantics for empty slots).
    m_scr[...] = jnp.full_like(m_scr, jnp.finfo(jnp.float32).min)
    s_scr[...] = jnp.zeros_like(s_scr)
    tv_scr[...] = jnp.full_like(tv_scr, -jnp.inf)
    ti_scr[...] = jnp.full_like(ti_scr, _IDX_SENTINEL)


def _topk_emit(topv_ref, topi_ref, lse_ref, m_scr, s_scr, tv_scr, ti_scr):
    lse_ref[...] = m_scr[...] + jnp.log(s_scr[...])
    topv_ref[...] = tv_scr[...]
    topi_ref[...] = ti_scr[...]


def _topk_readout_kernel(s_ref, w_ref, b_ref, topv_ref, topi_ref, lse_ref,
                         m_scr, s_scr, tv_scr, ti_scr, *, vocab, k, v_tile):
    from jax.experimental import pallas as pl

    v = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(v == 0)
    def _init():
        _topk_init(m_scr, s_scr, tv_scr, ti_scr)

    l = jnp.dot(s_ref[...], w_ref[...],
                preferred_element_type=jnp.float32) + b_ref[...]  # [Rb, Vt]
    _topk_lse_update(l, v * v_tile, vocab, k, m_scr, s_scr, tv_scr, ti_scr)

    @pl.when(v == nv - 1)
    def _fin():
        _topk_emit(topv_ref, topi_ref, lse_ref, m_scr, s_scr, tv_scr, ti_scr)


def topk_lse_readout_pallas(states_c, w_p, b_p, *, vocab: int, k: int,
                            row_block: int, v_tile: int):
    """states_c [N, D] compute dtype, w_p [D, V'] compute dtype, b_p [1, V']
    f32 (padded tail at -1e30), ``vocab`` the REAL V (columns >= vocab are
    padding and can never be selected) -> (topv [N, TOPK_LANES] f32,
    topi [N, TOPK_LANES] i32, lse [N, 1] f32).  Only the first ``k`` lanes
    of topv/topi carry data — the caller slices ``[:, :k]``.  The [N, V']
    logits never exist outside VMEM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, D = states_c.shape
    Vp = w_p.shape[1]
    nR, nV = N // row_block, Vp // v_tile
    Rb, Vt, L = row_block, v_tile, TOPK_LANES
    kernel = functools.partial(_topk_readout_kernel, vocab=vocab, k=k,
                               v_tile=Vt)
    return pl.pallas_call(
        kernel,
        name="topk_lse_readout",
        grid=(nR, nV),
        in_specs=[
            pl.BlockSpec((Rb, D), lambda r, v: (r, 0)),    # states (resident)
            pl.BlockSpec((D, Vt), lambda r, v: (0, v)),    # w tile
            pl.BlockSpec((1, Vt), lambda r, v: (0, v)),    # bias tile
        ],
        out_specs=[
            pl.BlockSpec((Rb, L), lambda r, v: (r, 0)),
            pl.BlockSpec((Rb, L), lambda r, v: (r, 0)),
            pl.BlockSpec((Rb, 1), lambda r, v: (r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, L), jnp.float32),
            jax.ShapeDtypeStruct((N, L), jnp.int32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((Rb, 1), jnp.float32),
            pltpu.VMEM((Rb, 1), jnp.float32),
            pltpu.VMEM((Rb, L), jnp.float32),
            pltpu.VMEM((Rb, L), jnp.int32),
        ],
        compiler_params=_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=_interpret(),
    )(states_c, w_p, b_p)


def _topk_logits_kernel(l_ref, topv_ref, topi_ref, lse_ref,
                        m_scr, s_scr, tv_scr, ti_scr, *, vocab, k, v_tile):
    from jax.experimental import pallas as pl

    v = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(v == 0)
    def _init():
        _topk_init(m_scr, s_scr, tv_scr, ti_scr)

    l = l_ref[...].astype(jnp.float32)
    _topk_lse_update(l, v * v_tile, vocab, k, m_scr, s_scr, tv_scr, ti_scr)

    @pl.when(v == nv - 1)
    def _fin():
        _topk_emit(topv_ref, topi_ref, lse_ref, m_scr, s_scr, tv_scr, ti_scr)


def topk_lse_logits_pallas(logits, *, vocab: int, k: int, row_block: int,
                           v_tile: int):
    """Pre-materialized-logits variant (opaque step nets whose readout the
    engine cannot tile): logits [N, V'] (tail padded at -1e30, ``vocab``
    the real V) are read ONCE instead of XLA's three passes (max, exp-sum,
    top-k) and no f32 log-softmax buffer is ever built.  Same outputs as
    ``topk_lse_readout_pallas``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, Vp = logits.shape
    nR, nV = N // row_block, Vp // v_tile
    Rb, Vt, L = row_block, v_tile, TOPK_LANES
    kernel = functools.partial(_topk_logits_kernel, vocab=vocab, k=k,
                               v_tile=Vt)
    return pl.pallas_call(
        kernel,
        name="topk_lse_logits",
        grid=(nR, nV),
        in_specs=[pl.BlockSpec((Rb, Vt), lambda r, v: (r, v))],
        out_specs=[
            pl.BlockSpec((Rb, L), lambda r, v: (r, 0)),
            pl.BlockSpec((Rb, L), lambda r, v: (r, 0)),
            pl.BlockSpec((Rb, 1), lambda r, v: (r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, L), jnp.float32),
            jax.ShapeDtypeStruct((N, L), jnp.int32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((Rb, 1), jnp.float32),
            pltpu.VMEM((Rb, 1), jnp.float32),
            pltpu.VMEM((Rb, L), jnp.float32),
            pltpu.VMEM((Rb, L), jnp.int32),
        ],
        compiler_params=_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=_interpret(),
    )(logits)


# ---------------------------------------------------------------------------
# Causal flash attention (decoder-only blocks): forward, backward
# ---------------------------------------------------------------------------
# Heads-major operands: q [B, H, T, dh], k [B, Hkv, T, dh], v [B, Hkv, T, dv],
# o/do [B, H, T, dv] (key-value head j serves query heads j*G..j*G+G-1; dv is
# dh in grouped-query attention, 128 beside 192 in latent attention), lse
# [B, H, T, 1] float32.  A block's minor axis is the whole head.  A grid
# step is one (block of queries, block of keys); blocks above the diagonal
# are skipped, and their key/value block index is clamped to the last one
# needed so that a skipped step moves nothing.  The scores of one block pair
# live in VMEM only.  Both kernels walk the keys of a block of queries.  The
# backward is ONE kernel: a pair's probabilities and ``ds`` are made once and
# feed dq, dk and dv (two kernels, one for dq and one for dk/dv, made them
# twice: 11 MXU passes of 1024 x 1024 x 128 a pair at 192/128 where 8 are
# needed, 7 for 5 at 64/64).  Of the two accumulations that cannot both
# follow the walk, dk/dv is the one kept resident in VMEM over a whole
# key-value head (its query heads add into it in turn): [T, dh] + [T, dv]
# float32 is 8 + 4 MiB at 192/128 and 4 + 4 at 64/64 (lanes padded), where a
# resident dq is G x [T, dh], 8 and 16; the walk then fetches a key and a
# value block a step (0.64 MiB) and not a query, an output and a gradient
# block (0.9).  No partial sum crosses HBM.
# Under a window the key axis of both grids is the band of a block of queries
# and no more (a skipped grid step still costs its fixed time): step ``j`` of
# a block of queries is key block ``first block of its band + j``, clamped to
# the diagonal's and skipped past it; inside a visited pair the mask is the
# band's, ``t - window < s <= t``.

#: scoped VMEM the attention kernels ask for (score tiles of 1024 x 1024
#: float32 and their bf16 copies, beside the operand blocks and, in the
#: backward, the resident dk and dv)
FLASH_VMEM_LIMIT_BYTES = 100 * 1024 * 1024


def _causal_scores(q, k, qi, kj, *, scale, block_q, block_k):
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(cols <= rows, s, -jnp.inf)


def _selected_scores(q, k, keep, *, scale):
    """The scores of one block pair under a selection: ``keep`` (int8, 1 at
    the positions a query sees, all at or before it) takes the causal
    mask's place."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    return jnp.where(keep.astype(jnp.int32) != 0, s, -jnp.inf)


def _band_scores(q, k, qi, kj, *, scale, block_q, block_k, window):
    """The scores of one block pair under a window: query ``t`` sees the
    ``window`` positions ``t - window < s <= t`` (its own among them)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where((cols <= rows) & (cols > rows - window), s, -jnp.inf)


def _band_first_block(qi, *, block_q, block_k, window):
    """The first block of keys that a block of queries sees under a
    window (``qi`` a Python int or a traced index)."""
    return jnp.maximum(qi * block_q - (window - 1), 0) // block_k


def flash_band_blocks(T: int, block_q: int, block_k: int, window: int) -> int:
    """Blocks of keys in the band of a block of queries, the widest over the
    row: the key axis of the window kernels' grids (2 at blocks of 512 or
    1024 under a window of 512, 3 at 256)."""
    return max((qi * block_q + block_q - 1) // block_k
               - max(qi * block_q - (window - 1), 0) // block_k + 1
               for qi in range(T // block_q))


def _flash_fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, block_q, block_k,
                      selected=False, window=None):
    from jax.experimental import pallas as pl

    keep_ref, rest = (rest[0], rest[1:]) if selected else (None, rest)
    o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    qi, kj = pl.program_id(2), pl.program_id(3)
    step = kj          # the walk's step; ``kj`` is the key block of the row
    if window is not None:
        kj = step + _band_first_block(qi, block_q=block_q, block_k=block_k,
                                      window=window)

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(kj * block_k <= qi * block_q + block_q - 1)
    def _block():
        v = v_ref[0, 0]
        m_old = m_scr[...]
        if selected or window is not None:
            # a query may keep nothing in its first blocks of keys (under a
            # window: the band's first block holds nothing of the block's
            # last queries): its running maximum is then still -inf, and the
            # exponentials are taken against 0 (they are 0 either way)
            s = (_selected_scores(q_ref[0, 0], k_ref[0, 0], keep_ref[0],
                                  scale=scale) if selected
                 else _band_scores(q_ref[0, 0], k_ref[0, 0], qi, kj,
                                   scale=scale, block_q=block_q,
                                   block_k=block_k, window=window))
            m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
            m_ref = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        else:
            s = _causal_scores(q_ref[0, 0], k_ref[0, 0], qi, kj, scale=scale,
                               block_q=block_q, block_k=block_k)
            m_ref = m_new = jnp.maximum(m_old,
                                        jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_ref)
        alpha = jnp.exp(m_old - m_ref)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(step == pl.num_programs(3) - 1)
    def _fin():
        o_ref[0, 0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log(l_scr[...])


def flash_attn_fwd_pallas(q, k, v, *, scale: float, block_q: int,
                          block_k: int, keep=None, window=None):
    """-> (out [B, H, T, dv] in q's dtype, lse [B, H, T, 1] float32).
    ``keep`` ``[B, T, T]`` int8: the selection (1 where query ``t`` sees
    position ``s``, all with ``s <= t``, at least one a query, the same for
    every head); the kernel is then ``flash_attn_sel_fwd`` and reads a tile
    of it beside every block pair it visits.  ``window``: query ``t`` sees
    positions ``t - window < s <= t``; the kernel is then
    ``flash_attn_win_fwd``, its grid's key axis the band of a block of
    queries (:func:`flash_band_blocks`) from the band's first block on."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if keep is not None and window is not None:
        raise ValueError("a window under a selection has no kernel")
    B, H, T, dh = q.shape
    dv = v.shape[3]
    G = H // k.shape[1]
    nq, nk = T // block_q, T // block_k
    if window is not None:
        nk = flash_band_blocks(T, block_q, block_k, window)

    def last_block(qi):
        return (qi * block_q + block_q - 1) // block_k

    def kv_map(b, h, qi, kj):
        if window is not None:
            kj = kj + _band_first_block(qi, block_q=block_q, block_k=block_k,
                                        window=window)
        return (b, h // G, jnp.minimum(kj, last_block(qi)), 0)

    selection = [] if keep is None else [pl.BlockSpec(
        (1, block_q, block_k),
        lambda b, h, qi, kj: (b, qi, jnp.minimum(kj, last_block(qi))))]
    kernel = functools.partial(_flash_fwd_kernel, scale=scale,
                               block_q=block_q, block_k=block_k,
                               selected=keep is not None, window=window)
    spec = dict(
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh), lambda b, h, qi, kj: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, dh), kv_map),
            pl.BlockSpec((1, 1, block_k, dv), kv_map),
            *selection,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, dv), lambda b, h, qi, kj: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, qi, kj: (b, h, qi, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, H, T, dv), q.dtype),
                   jax.ShapeDtypeStruct((B, H, T, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, dv), jnp.float32)],
        compiler_params=_compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=FLASH_VMEM_LIMIT_BYTES),
        interpret=_interpret())
    if window is not None:
        return pl.pallas_call(kernel, name="flash_attn_win_fwd", **spec)(
            q, k, v)
    if keep is None:
        return pl.pallas_call(kernel, name="flash_attn_fwd", **spec)(q, k, v)
    return pl.pallas_call(kernel, name="flash_attn_sel_fwd", **spec)(
        q, k, v, keep)


def flash_bwd_key_rows(T: int, dh: int, dv: int, block_q: int,
                       block_k: int) -> int:
    """Rows of keys whose ``dk`` and ``dv`` one ``flash_attn_bwd`` call keeps
    resident in VMEM: the whole row where that fits the kernels' budget (the
    benchmark cells' rows of 4096, 8192 and 16384; up to 23k rows at 192/128
    and 35k at 64/64 or 128/128 with blocks of 1024), else the row cut into
    equal super-blocks of whole key blocks.  Counted against ``FLASH_VMEM_LIMIT_BYTES``: the resident pair in
    float32 with lanes padded to 128, twice (as if the pipeline kept two
    buffers of an output block), the block pair's four float32 score tiles
    and four bf16 ones (``p``, ``ds`` and their transposes), and the operand,
    ``lse`` and ``dq`` blocks, twice each.  The v5e compiler takes every row
    this admits, and some it does not (28k at 192/128)."""
    def lanes(d):
        return -(-d // 128) * 128

    tiles = block_q * block_k * (4 * 4 + 4 * 2)
    blocks = 2 * (2 * (block_q + block_k) * lanes(dh)
                  + 2 * (2 * block_q + block_k) * lanes(dv)
                  + 4 * block_q * (lanes(dh) + 128))
    per_row = 2 * 4 * (lanes(dh) + lanes(dv))
    fit = (FLASH_VMEM_LIMIT_BYTES - tiles - blocks) // (per_row * block_k)
    nk = T // block_k
    n_super = -(-nk // max(1, fit))
    return -(-nk // n_super) * block_k


def _flash_probs(q, k, v, o, do, lse, qi, kj, *, scale, block_q, block_k,
                 keep=None, window=None):
    """(p, ds) of one block pair from the saved statistics, float32."""
    f32 = jnp.float32
    if window is not None:
        s = _band_scores(q, k, qi, kj, scale=scale, block_q=block_q,
                         block_k=block_k, window=window)
    else:
        s = (_causal_scores(q, k, qi, kj, scale=scale, block_q=block_q,
                            block_k=block_k) if keep is None
             else _selected_scores(q, k, keep, scale=scale))
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)
    delta = jnp.sum(do.astype(f32) * o.astype(f32), axis=-1, keepdims=True)
    return p, p * (dp - delta) * scale


def _flash_bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, *rest,
                      scale, block_q, block_k, n_q, q_first, k_first,
                      selected=False, window=None):
    """One (block of queries, block of keys) of the backward: the pair's
    probabilities and ``ds`` are made once and feed all three gradients.
    ``dq`` of the block of queries accumulates in its output block over the
    walk along the keys; ``dk`` and ``dv`` of ALL the call's keys stay in
    their output blocks over both inner axes, and every query block (of
    every head of the group, in turn) adds its part at its key block's
    rows.  Under a ``window`` the walk starts at the band's first block."""
    from jax.experimental import pallas as pl

    keep_ref, rest = (rest[0], rest[1:]) if selected else (None, rest)
    dq_ref, dk_ref, dv_ref = rest
    t, step = pl.program_id(2), pl.program_id(3)
    qi, kj = q_first + t % n_q, step
    if window is not None:      # the call holds the whole row's keys
        kj = step + _band_first_block(qi, block_q=block_q, block_k=block_k,
                                      window=window)
    kg = k_first + kj                           # blocks of the whole row

    @pl.when((t == 0) & (step == 0))
    def _zero():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    @pl.when(step == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(kg * block_k <= qi * block_q + block_q - 1)
    def _block():
        q, k, do = q_ref[0, 0], k_ref[0, 0], do_ref[0, 0]
        p, ds = _flash_probs(q, k, v_ref[0, 0], o_ref[0, 0], do,
                             lse_ref[0, 0], qi, kg, scale=scale,
                             block_q=block_q, block_k=block_k,
                             keep=keep_ref[0] if selected else None,
                             window=window)
        ds = ds.astype(q.dtype)
        contract_rows = (((0,), (0,)), ((), ()))
        keys = pl.ds(pl.multiple_of(kj * block_k, block_k), block_k)
        dv_ref[0, 0, keys, :] += jax.lax.dot_general(
            p.astype(do.dtype), do, contract_rows,
            preferred_element_type=jnp.float32)
        dk_ref[0, 0, keys, :] += jax.lax.dot_general(
            ds, q, contract_rows, preferred_element_type=jnp.float32)
        dq_ref[0, 0] += jnp.dot(ds, k, preferred_element_type=jnp.float32)


def flash_attn_bwd_pallas(q, k, v, o, lse, do, *, scale: float,
                          block_q: int, block_k: int, keep=None,
                          window=None):
    """-> (dq [B, H, T, dh], dk [B, Hkv, T, dh], dv [B, Hkv, T, dv]),
    float32; ``o`` and ``do`` are [B, H, T, dv].  ONE kernel,
    ``flash_attn_bwd``, that visits each live block pair once, recomputes
    its probabilities from ``lse`` and accumulates all three gradients from
    them.  It walks the keys of a block of queries (``dq`` in its output
    block), and the ``dk`` [T, dh] and ``dv`` [T, dv] of the key-value head
    stay in VMEM over all the head's (and its group's) query blocks, so no
    partial sum crosses HBM.  Where a row is too long for that
    (:func:`flash_bwd_key_rows`), the keys are cut
    into super-blocks, one call each over the queries at or after its first
    key, and the calls' ``dq`` are summed here.  ``keep``: the selection
    the forward ran under (``flash_attn_fwd_pallas``); the kernel is then
    ``flash_attn_sel_bwd``.  ``window``: the forward's; the kernel is then
    ``flash_attn_win_bwd``, the walk along the keys of a block of queries
    is its band's blocks alone, and the row has to be one the call keeps
    whole (``decoder_block.attention_kernel_blocks`` sees to that)."""
    from jax.experimental import pallas as pl

    B, H, T, dh = q.shape
    Hkv, dv = k.shape[1], v.shape[3]
    G = H // Hkv
    key_rows = flash_bwd_key_rows(T, dh, dv, block_q, block_k)
    if window is not None and (keep is not None or key_rows < T):
        raise ValueError(f"no window kernel under a selection, or for a row "
                         f"of {T} cut into super-blocks of {key_rows} keys")

    def part(k_lo, k_hi):
        """The gradients of keys ``k_lo..k_hi`` and what they add to the
        ``dq`` of the queries from ``q_lo`` (the first that see them) on."""
        k_first, q_first = k_lo // block_k, k_lo // block_q
        q_lo = q_first * block_q
        nq, nk = (T - q_lo) // block_q, (k_hi - k_lo) // block_k
        if window is not None:
            nk = flash_band_blocks(T, block_q, block_k, window)

        def q_map(b, hk, t, kj):
            return (b, hk * G + t // nq, q_first + t % nq, 0)

        def key_block(t, kj):
            qi = q_first + t % nq
            if window is not None:
                kj = kj + _band_first_block(qi, block_q=block_q,
                                            block_k=block_k, window=window)
            last = (qi * block_q + block_q - 1) // block_k
            return jnp.minimum(k_first + kj, last)

        def kv_map(b, hk, t, kj):
            return (b, hk, key_block(t, kj), 0)

        def resident(b, hk, t, kj):
            return (b, hk, 0, 0)

        def dq_map(b, hk, t, kj):
            return (b, hk * G + t // nq, t % nq, 0)

        q_spec = pl.BlockSpec((1, 1, block_q, dh), q_map)
        o_spec = pl.BlockSpec((1, 1, block_q, dv), q_map)
        selection = [] if keep is None else [pl.BlockSpec(
            (1, block_q, block_k),
            lambda b, hk, t, kj: (b, q_first + t % nq, key_block(t, kj)))]
        kernel = functools.partial(
            _flash_bwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
            n_q=nq, q_first=q_first, k_first=k_first,
            selected=keep is not None, window=window)
        spec = dict(
            grid=(B, Hkv, G * nq, nk),
            in_specs=[q_spec, pl.BlockSpec((1, 1, block_k, dh), kv_map),
                      pl.BlockSpec((1, 1, block_k, dv), kv_map), o_spec,
                      o_spec, pl.BlockSpec((1, 1, block_q, 1), q_map),
                      *selection],
            out_specs=[pl.BlockSpec((1, 1, block_q, dh), dq_map),
                       pl.BlockSpec((1, 1, k_hi - k_lo, dh), resident),
                       pl.BlockSpec((1, 1, k_hi - k_lo, dv), resident)],
            out_shape=[
                jax.ShapeDtypeStruct((B, H, T - q_lo, dh), jnp.float32),
                jax.ShapeDtypeStruct((B, Hkv, k_hi - k_lo, dh), jnp.float32),
                jax.ShapeDtypeStruct((B, Hkv, k_hi - k_lo, dv), jnp.float32)],
            compiler_params=_compiler_params(
                dimension_semantics=("parallel", "parallel", "arbitrary",
                                     "arbitrary"),
                vmem_limit_bytes=FLASH_VMEM_LIMIT_BYTES),
            interpret=_interpret())
        if window is not None:
            return pl.pallas_call(kernel, name="flash_attn_win_bwd", **spec)(
                q, k, v, o, do, lse)
        if keep is None:
            return pl.pallas_call(kernel, name="flash_attn_bwd", **spec)(
                q, k, v, o, do, lse)
        return pl.pallas_call(kernel, name="flash_attn_sel_bwd", **spec)(
            q, k, v, o, do, lse, keep)

    parts = [part(lo, min(lo + key_rows, T)) for lo in range(0, T, key_rows)]
    if len(parts) == 1:
        return tuple(parts[0])
    dqs, d_ks, d_vs = zip(*parts)
    dq = dqs[0]
    for more in dqs[1:]:
        dq = dq.at[:, :, T - more.shape[2]:].add(more)
    return dq, jnp.concatenate(d_ks, axis=2), jnp.concatenate(d_vs, axis=2)


# ---------------------------------------------------------------------------
# Grouped matrix products over the experts held (dropless expert layer)
# ---------------------------------------------------------------------------
# Rows are grouped by expert and every group starts on a row tile (ops/moe.py
# pads each group to whole tiles), so a tile of ``tm`` rows belongs to ONE
# expert: ``tile_expert[i]``.  Only the first ``n_active[0]`` tiles hold
# rows; the buffer is sized for the worst routing, and a grid step past the
# active tiles computes nothing and moves nothing (its block indices are
# clamped to the last active tile's).  Tiles of one expert are consecutive,
# so with the tile index innermost a weight block is fetched once per run.

GMM_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _last_active(i, na_ref):
    return jnp.minimum(i, jnp.maximum(na_ref[0] - 1, 0))


def _gmm_kernel(te_ref, na_ref, lhs_ref, rhs_ref, out_ref, *, transpose_rhs):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) < na_ref[0])
    def _tile():
        x = lhs_ref[...]
        w = rhs_ref[0].astype(x.dtype)
        dims = (((1,), (1,)), ((), ())) if transpose_rhs \
            else (((1,), (0,)), ((), ()))
        out_ref[...] = jax.lax.dot_general(
            x, w, dims, preferred_element_type=jnp.float32
        ).astype(out_ref.dtype)


def gmm_pallas(lhs, rhs, tile_expert, n_active, *, tm: int, tn: int,
               transpose_rhs: bool = False, out_dtype=jnp.float32):
    """``out[rows of tile i] = lhs[rows of tile i] @ rhs[tile_expert[i]]``
    for the active tiles (other rows are left unwritten).  lhs ``[M, K]`` in
    the compute dtype, rhs ``[E, K, N]`` (``[E, N, K]`` with
    ``transpose_rhs``) in its stored dtype, cast a block at a time.  ``tn``
    need not divide ``N``: the last block of columns then hangs over the
    edge, and what it computes there is dropped."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = lhs.shape
    N = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    rhs_block = (1, tn, K) if transpose_rhs else (1, K, tn)

    def rhs_map(j, i, te, na):
        e = te[_last_active(i, na)]
        return (e, j, 0) if transpose_rhs else (e, 0, j)

    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        name="moe_gmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(pl.cdiv(N, tn), M // tm),
            in_specs=[
                pl.BlockSpec((tm, K),
                             lambda j, i, te, na: (_last_active(i, na), 0)),
                pl.BlockSpec(rhs_block, rhs_map),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, i, te, na: (_last_active(i, na), j)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=GMM_VMEM_LIMIT_BYTES),
        interpret=_interpret(),
    )(tile_expert, n_active, lhs, rhs)


def _tgmm_kernel(te_ref, na_ref, lhs_ref, rhs_ref, out_ref):
    from jax.experimental import pallas as pl

    i = pl.program_id(2)
    active = i < na_ref[0]
    first = jnp.logical_or(i == 0,
                           te_ref[i] != te_ref[jnp.maximum(i - 1, 0)])

    @pl.when(jnp.logical_and(active, first))
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(active)
    def _tile():
        out_ref[0] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def tgmm_pallas(lhs, rhs, tile_expert, n_active, *, experts: int, tm: int,
                tk: int, tn: int):
    """``out[e] = sum over the tiles of expert e of lhs_tile^T @ rhs_tile``:
    lhs ``[M, K]``, rhs ``[M, N]`` -> ``[experts, K, N]`` float32.  The
    block of an expert no tile belongs to is never written: the caller
    zeroes the experts whose count is 0.  ``tk`` and ``tn`` need not divide
    ``K`` and ``N`` (both are the result's axes; the sum is over rows)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = lhs.shape
    N = rhs.shape[1]
    return pl.pallas_call(
        _tgmm_kernel,
        name="moe_tgmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(pl.cdiv(K, tk), pl.cdiv(N, tn), M // tm),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda a, b, i, te, na:
                             (_last_active(i, na), a)),
                pl.BlockSpec((tm, tn), lambda a, b, i, te, na:
                             (_last_active(i, na), b)),
            ],
            out_specs=pl.BlockSpec(
                (1, tk, tn), lambda a, b, i, te, na:
                (te[_last_active(i, na)], a, b)),
        ),
        out_shape=jax.ShapeDtypeStruct((experts, K, N), jnp.float32),
        compiler_params=_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=GMM_VMEM_LIMIT_BYTES),
        interpret=_interpret(),
    )(tile_expert, n_active, lhs, rhs)


# ---------------------------------------------------------------------------
# The gated delta rule as a chunked scan (ops/delta_rule.py has the algebra)
# ---------------------------------------------------------------------------
# Grid (batch, head, block of chunks); the last axis is sequential and the
# head's state ``S`` [dk, dv] float32 (``dS`` in the reverse kernel) stays in
# VMEM scratch across it.  A grid step takes ``KERNEL_BLOCK_CHUNKS`` chunks,
# unrolled: a chunk's ``k k^T``, its 64 x 64 solve and ``q k^T`` do not wait
# for the state, so the scheduler has them to run beside the three products
# that do.  ``g``'s sums and ``beta`` come lane-dense, one chunk a row
# ([.., N, 64]); the column forms the algebra needs are made in VMEM.  The
# forward writes every chunk's STARTING state out ([B, H, N, dk, dv]
# float32) and its solve T ([B, H, 64, N * 64] float32, chunk ``c`` at lanes
# ``64 c`` on: two neighbouring chunks fill a lane tile, and nothing is
# padded to 128); the reverse kernel reads both back and makes the chunk's R
# and Vn again, not the solve.

def _traced_once(*static):
    """``jax.jit`` around a kernel's wrapper.  A step calls such a wrapper
    once a layer and pass with the same shapes, and a process traces its
    step several times; inside ``jit`` the kernel's body is traced, and
    lowered into a module, once for all of them.  What the trace takes from
    the backend (interpret mode) goes in as a static argument, so that it
    is part of the key."""
    def wrap(fn):
        inner = jax.jit(fn, static_argnames=(*static, "interpret"))

        @functools.wraps(fn)
        def call(*args, **kwargs):
            return inner(*args, interpret=_interpret(), **kwargs)

        return call

    return wrap


def _gdn_block(T: int, chunk: int):
    from paddle_tpu.ops.delta_rule import KERNEL_BLOCK_CHUNKS

    n = T // chunk
    per = min(n, KERNEL_BLOCK_CHUNKS)
    if n % per:
        raise ValueError(f"a row of {n} chunks is not whole blocks of {per}")
    return n, per


def _gdn_fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s_ref, t_ref,
                    S_scr, *, chunk, per):
    from jax.experimental import pallas as pl

    from paddle_tpu.ops import delta_rule as DR

    @pl.when(pl.program_id(2) == 0)
    def _init():
        S_scr[...] = jnp.zeros_like(S_scr)

    for c in range(per):
        rows = slice(c * chunk, (c + 1) * chunk)
        grow, brow = g_ref[0, 0, c:c + 1, :], b_ref[0, 0, c:c + 1, :]
        S = S_scr[...]
        s_ref[0, 0, c] = S
        q = q_ref[0, 0, rows, :]
        o, S_end, T = DR.chunk_forward(
            q, k_ref[0, 0, rows, :], v_ref[0, 0, rows, :],
            DR.col_of_row(grow), grow, DR.col_of_row(brow), S, q.dtype)
        o_ref[0, 0, rows, :] = o.astype(o_ref.dtype)
        t_ref[0, 0, :, rows] = T
        S_scr[...] = S_end


@_traced_once()
def gdn_chunk_fwd_pallas(q, k, v, gamma, beta, *, interpret):
    """q, k ``[B, Hk, T, dk]``, v ``[B, H, T, dv]`` in the compute dtype, ``H``
    whole groups over ``Hk``: value head ``h`` reads key head ``h // (H //
    Hk)`` through the index maps, and nothing is repeated in HBM; gamma
    (``g`` summed from each chunk's start) and beta ``[B, H, N, C]`` float32
    -> (o ``[B, H, T, dv]`` in q's dtype, every chunk's starting state ``[B,
    H, N, dk, dv]`` float32, every chunk's solve ``(I + A)^-1`` ``[B, H, C,
    N C]`` float32 as ``chunk_forward`` made it, chunk ``c`` in columns ``c
    C`` on)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, dv = v.shape
    dk, chunk, group = q.shape[3], gamma.shape[3], H // q.shape[1]
    n, per = _gdn_block(T, chunk)
    rows = lambda b, h, i: (b, h, i, 0)  # noqa: E731
    key_rows = lambda b, h, i: (b, h // group, i, 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_gdn_fwd_kernel, chunk=chunk, per=per),
        name="gdn_chunk_fwd",
        grid=(B, H, n // per),
        in_specs=[pl.BlockSpec((1, 1, per * chunk, dk), key_rows),
                  pl.BlockSpec((1, 1, per * chunk, dk), key_rows),
                  pl.BlockSpec((1, 1, per * chunk, dv), rows),
                  pl.BlockSpec((1, 1, per, chunk), rows),
                  pl.BlockSpec((1, 1, per, chunk), rows)],
        out_specs=[pl.BlockSpec((1, 1, per * chunk, dv), rows),
                   pl.BlockSpec((1, 1, per, dk, dv),
                                lambda b, h, i: (b, h, i, 0, 0)),
                   pl.BlockSpec((1, 1, chunk, per * chunk),
                                lambda b, h, i: (b, h, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((B, H, T, dv), q.dtype),
                   jax.ShapeDtypeStruct((B, H, n, dk, dv), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, chunk, T), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, gamma, beta)


def _gdn_bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, t_ref, do_ref,
                    dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dS_scr,
                    *, chunk, per):
    from jax.experimental import pallas as pl

    from paddle_tpu.ops import delta_rule as DR

    @pl.when(pl.program_id(2) == 0)     # the row's LAST block: the walk is
    def _init():                        # reversed by the index maps
        dS_scr[...] = jnp.zeros_like(dS_scr)

    last = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1
    for c in reversed(range(per)):
        rows = slice(c * chunk, (c + 1) * chunk)
        grow, brow = g_ref[0, 0, c:c + 1, :], b_ref[0, 0, c:c + 1, :]
        q = q_ref[0, 0, rows, :]
        dq, dk, dv, dg_col, dg_row, dg_last, db, dS = DR.chunk_backward(
            q, k_ref[0, 0, rows, :], v_ref[0, 0, rows, :],
            DR.col_of_row(grow), grow, DR.col_of_row(brow), s_ref[0, 0, c],
            t_ref[0, 0, :, rows], do_ref[0, 0, rows, :], dS_scr[...],
            q.dtype)
        dq_ref[0, 0, rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[0, 0, rows, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0, rows, :] = dv.astype(dv_ref.dtype)
        dg_ref[0, 0, c:c + 1, :] = (DR.row_of_col(dg_col) + dg_row
                                    + jnp.where(last, dg_last, 0.0))
        db_ref[0, 0, c:c + 1, :] = DR.row_of_col(db)
        dS_scr[...] = dS


@_traced_once()
def gdn_chunk_bwd_pallas(q, k, v, gamma, beta, states, solves, do, *,
                         interpret):
    """The reverse walk: what :func:`gdn_chunk_fwd_pallas` took and wrote
    (its second and third result, ``states`` and ``solves``: the kernel
    makes no chunk's solve again), and ``do`` ``[B, H, T, dv]`` -> (dq, dk
    ``[B, H, T, dk]``, a VALUE head each: the sum over a key head's group is
    the caller's; dv in q's dtype; dgamma, dbeta ``[B, H, N, C]`` float32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, dv = v.shape
    dk, chunk, group = q.shape[3], gamma.shape[3], H // q.shape[1]
    n, per = _gdn_block(T, chunk)
    nb = n // per
    rows = lambda b, h, i: (b, h, nb - 1 - i, 0)  # noqa: E731
    key_in = pl.BlockSpec((1, 1, per * chunk, dk),
                          lambda b, h, i: (b, h // group, nb - 1 - i, 0))
    wide_k = pl.BlockSpec((1, 1, per * chunk, dk), rows)
    wide_v = pl.BlockSpec((1, 1, per * chunk, dv), rows)
    scalars = pl.BlockSpec((1, 1, per, chunk), rows)
    return tuple(pl.pallas_call(
        functools.partial(_gdn_bwd_kernel, chunk=chunk, per=per),
        name="gdn_chunk_bwd",
        grid=(B, H, nb),
        in_specs=[key_in, key_in, wide_v, scalars, scalars,
                  pl.BlockSpec((1, 1, per, dk, dv),
                               lambda b, h, i: (b, h, nb - 1 - i, 0, 0)),
                  pl.BlockSpec((1, 1, chunk, per * chunk),
                               lambda b, h, i: (b, h, 0, nb - 1 - i)),
                  wide_v],
        out_specs=[wide_k, wide_k, wide_v, scalars, scalars],
        out_shape=[jax.ShapeDtypeStruct((B, H, T, dk), q.dtype),
                   jax.ShapeDtypeStruct((B, H, T, dk), k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(gamma.shape, jnp.float32),
                   jax.ShapeDtypeStruct(beta.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, gamma, beta, states, solves, do))


# ---------------------------------------------------------------------------
# The state-space (SSD) recurrence as a chunked scan (ops/ssd_scan.py has the
# algebra)
# ---------------------------------------------------------------------------
# Grid (batch, group, block of chunks); the last axis is sequential and the
# GROUP's state ``[N, heads * P]`` float32 (its gradient in the reverse
# kernel) stays in VMEM scratch across it.  ``x`` and ``y`` are read and
# written where the layer keeps them, ``[B, T, H * P]``: a group's heads are
# a block of lanes, and so are ``B`` and ``C`` of ``[B, T, G * N]``; or
# ``B`` and ``C`` are blocks of ONE array ``[B, T, 2 G * N]``, ``[B | C]`` as
# ``mamba_prep_fwd`` writes it (``Cm`` ``None``: the same blocks at column
# blocks ``g`` and ``G + g``; nothing is sliced out of it).  The reverse
# kernel writes ``dx``, ``dB``, ``dC`` as three arrays either way.  The
# chunks' scalars (``dt`` and the sums of ``dt A``) come ``[B, G, n, heads,
# Q]``, a chunk's a whole lane row a head.  A grid step takes
# ``KERNEL_BLOCK_CHUNKS`` chunks, unrolled.  The forward writes every
# chunk's STARTING state out ([B, G, n, N, heads * P] float32); the reverse
# kernel reads it back and makes the chunk's parts again.

SSD_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _ssd_block(n: int):
    from paddle_tpu.ops.ssd_scan import KERNEL_BLOCK_CHUNKS

    per = min(n, KERNEL_BLOCK_CHUNKS)
    if n % per:
        raise ValueError(f"a row of {n} chunks is not whole blocks of {per}")
    return per


def _ssd_operands(x, Bm, Cm, G: int):
    """``(operands, N, first column blocks of B and of C)`` of the scan's
    kernels: three arrays, or with ``Cm`` ``None`` two, ``Bm`` holding ``[B |
    C]`` ``[B, T, 2 G N]``, of which group ``g`` reads the column blocks ``g``
    and ``G + g``."""
    if Cm is not None:
        return (x, Bm, Cm), Bm.shape[2] // G, (0, 0)
    if Bm.shape[2] % (2 * G):
        raise ValueError(f"{Bm.shape[2]} columns are not [B | C] of {G} "
                         "groups")
    return (x, Bm, Bm), Bm.shape[2] // (2 * G), (0, G)


def _ssd_fwd_kernel(x_ref, b_ref, c_ref, a_ref, dt_ref, y_ref, s_ref, S_scr,
                    *, chunk, per, P):
    from jax.experimental import pallas as pl

    from paddle_tpu.ops import ssd_scan as SS

    @pl.when(pl.program_id(2) == 0)
    def _init():
        S_scr[...] = jnp.zeros_like(S_scr)

    for c in range(per):
        rows = slice(c * chunk, (c + 1) * chunk)
        S = S_scr[...]
        s_ref[0, 0, c] = S
        x = x_ref[0, rows, :]
        y, S_end = SS.chunk_forward(
            x, b_ref[0, rows, :], c_ref[0, rows, :], a_ref[0, 0, c],
            dt_ref[0, 0, c], S, P, x.dtype)
        y_ref[0, rows, :] = y.astype(y_ref.dtype)
        S_scr[...] = S_end


@_traced_once()
def ssd_chunk_fwd_pallas(x, Bm, Cm, a, dt, *, interpret):
    """x ``[B, T, H P]``, Bm, Cm ``[B, T, G N]`` in the compute dtype (head
    ``h`` of ``H`` reads group ``h // (H // G)``; a head's and a group's
    channels contiguous), or Bm ``[B, T, 2 G N]``, the two side by side, and
    Cm ``None``; a (``dt A`` summed from each chunk's start) and dt ``[B, G,
    n, H / G, Q]`` float32 -> (y ``[B, T, H P]`` in x's dtype, every chunk's
    starting state ``[B, G, n, N, (H / G) P]`` float32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, HP = x.shape
    _, G, n, heads, chunk = a.shape
    operands, N, first = _ssd_operands(x, Bm, Cm, G)
    W = HP // G
    per = _ssd_block(n)
    rows = lambda c0: (lambda b, g, i: (b, i, c0 + g))  # noqa: E731
    scalars = pl.BlockSpec((1, 1, per, heads, chunk),
                           lambda b, g, i: (b, g, i, 0, 0))
    return pl.pallas_call(
        functools.partial(_ssd_fwd_kernel, chunk=chunk, per=per,
                          P=W // heads),
        name="ssd_chunk_fwd",
        grid=(B, G, n // per),
        in_specs=[pl.BlockSpec((1, per * chunk, W), rows(0)),
                  pl.BlockSpec((1, per * chunk, N), rows(first[0])),
                  pl.BlockSpec((1, per * chunk, N), rows(first[1])),
                  scalars, scalars],
        out_specs=[pl.BlockSpec((1, per * chunk, W), rows(0)),
                   pl.BlockSpec((1, 1, per, N, W),
                                lambda b, g, i: (b, g, i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((B, G, n, N, W), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, W), jnp.float32)],
        compiler_params=_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=SSD_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*operands, a, dt)


def _ssd_bwd_kernel(x_ref, b_ref, c_ref, a_ref, dt_ref, s_ref, dy_ref,
                    dx_ref, db_ref, dc_ref, da_ref, ddt_ref, dS_scr,
                    *, chunk, per, P):
    from jax.experimental import pallas as pl

    from paddle_tpu.ops import ssd_scan as SS

    @pl.when(pl.program_id(2) == 0)     # the row's LAST block: the walk is
    def _init():                        # reversed by the index maps
        dS_scr[...] = jnp.zeros_like(dS_scr)

    for c in reversed(range(per)):
        rows = slice(c * chunk, (c + 1) * chunk)
        x = x_ref[0, rows, :]
        dx, dB, dC, da, ddt, dS = SS.chunk_backward(
            x, b_ref[0, rows, :], c_ref[0, rows, :], a_ref[0, 0, c],
            dt_ref[0, 0, c], s_ref[0, 0, c], dy_ref[0, rows, :],
            dS_scr[...], P, x.dtype)
        dx_ref[0, rows, :] = dx.astype(dx_ref.dtype)
        db_ref[0, rows, :] = dB.astype(db_ref.dtype)
        dc_ref[0, rows, :] = dC.astype(dc_ref.dtype)
        da_ref[0, 0, c] = da
        ddt_ref[0, 0, c] = ddt
        dS_scr[...] = dS


@_traced_once()
def ssd_chunk_bwd_pallas(x, Bm, Cm, a, dt, states, dy, *, interpret):
    """The reverse walk: what :func:`ssd_chunk_fwd_pallas` took and wrote,
    and ``dy`` ``[B, T, H P]`` -> (dx in x's dtype; dB, dC ``[B, T, G N]`` in
    theirs, a group's heads summed, two arrays also where B and C were read
    from one; da, ddt ``[B, G, n, H / G, Q]`` float32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, HP = x.shape
    _, G, n, heads, chunk = a.shape
    operands, N, first = _ssd_operands(x, Bm, Cm, G)
    W = HP // G
    per = _ssd_block(n)
    nb = n // per
    rows = lambda c0: (  # noqa: E731
        lambda b, g, i: (b, nb - 1 - i, c0 + g))
    wide = pl.BlockSpec((1, per * chunk, W), rows(0))
    narrow = pl.BlockSpec((1, per * chunk, N), rows(0))
    scalars = pl.BlockSpec((1, 1, per, heads, chunk),
                           lambda b, g, i: (b, g, nb - 1 - i, 0, 0))
    return tuple(pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, chunk=chunk, per=per,
                          P=W // heads),
        name="ssd_chunk_bwd",
        grid=(B, G, nb),
        in_specs=[wide,
                  pl.BlockSpec((1, per * chunk, N), rows(first[0])),
                  pl.BlockSpec((1, per * chunk, N), rows(first[1])),
                  scalars, scalars,
                  pl.BlockSpec((1, 1, per, N, W),
                               lambda b, g, i: (b, g, nb - 1 - i, 0, 0)),
                  wide],
        out_specs=[wide, narrow, narrow, scalars, scalars],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((B, T, G * N), operands[1].dtype),
                   jax.ShapeDtypeStruct((B, T, G * N), operands[2].dtype),
                   jax.ShapeDtypeStruct(a.shape, jnp.float32),
                   jax.ShapeDtypeStruct(dt.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, W), jnp.float32)],
        compiler_params=_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=SSD_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*operands, a, dt, states, dy))


# ---------------------------------------------------------------------------
# From a delta net's in-projection to the scan: convolution, SiLU, L2 norms,
# heads-major layout (ops/delta_rule.py ``conv_delta_rule`` calls the pair)
# ---------------------------------------------------------------------------
# ``x`` ``[B, T, Hk * W]`` is the projection with its columns GROUPED by key
# head (``delta_rule.group_columns``): group ``j`` holds ``[q_j | k_j | the
# Hv / Hk value heads that read them]``, ``W = 2 dk + (Hv / Hk) dv`` columns,
# so one block of one array is everything a key head's group needs.  Grid
# (key head, batch, block of rows).  A block brings the ``GDN_PREP_HALO`` rows
# before it as a second block of the same array (zero before the row's
# start) and is copied behind them into VMEM scratch once; every tap of the
# depthwise convolution is then a read of that scratch at a row offset.  The
# work goes one head at a time in sub-blocks of ``GDN_PREP_SUB_ROWS`` rows, a
# loop over them, so the body is traced at one sub-block's size and a
# sub-block's float32 arrays stay small.  The reverse kernel also brings the
# rows AFTER the block (its own rows' gradient reaches them through the
# taps), makes the pre-activation again, sums ``dq`` and ``dk`` over a key
# head's value heads in float32, and accumulates the convolution kernel's
# gradient in its output block across batch and rows.

#: rows before (and, in reverse, after) a block: one float32 tile; the
#: convolution may have up to ``GDN_PREP_HALO + 1`` taps
GDN_PREP_HALO = 8
#: rows of the gradients' block after a block: one bf16 tile
_GDN_PREP_D_HALO = 16
#: rows per sub-block of the kernels' bodies, one iteration of their loops:
#: an iteration is a chain the scheduler does not overlap with the next, so
#: long sub-blocks run faster (PERF.md section 6, PR 42: 128 rows against
#: 32), and the loop, not an unrolled body, keeps the kernels' jaxprs, which
#: every trace of a step walks, small
GDN_PREP_SUB_ROWS = 128
#: the L2 norms' epsilon (``decoder_block.unit_norm``'s default)
_GDN_PREP_EPS = 1e-6
GDN_PREP_VMEM_LIMIT_BYTES = 48 * 1024 * 1024


def _gdn_prep_pieces(dk: int, dv: int, group: int):
    """``(first column, width, kind, head in the group)`` of a group's
    heads."""
    return ([(0, dk, "q", 0), (dk, dk, "k", 0)]
            + [(2 * dk + m * dv, dv, "v", m) for m in range(group)])


def _gdn_prep_conv(xs_ref, w_ref, r0, n: int, cols, taps: int):
    """The taps' rows of ``xs_ref`` for output rows ``r0 .. r0 + n`` of the
    block (``r0`` a multiple of the tile's 8 rows) and their weighted sum, in
    ``causal_short_conv``'s order."""
    from jax.experimental import pallas as pl

    first = GDN_PREP_HALO - (taps - 1)
    # a read at a row that is not a multiple of 8 needs a static offset, so:
    # the tile-aligned window, then the taps' rows of it
    window = xs_ref[pl.ds(r0, GDN_PREP_HALO + n), cols]
    xt = [window[first + j:first + j + n] for j in range(taps)]
    a = w_ref[0, 0:1, cols] * xt[0]
    for j in range(1, taps):
        a = a + w_ref[0, j:j + 1, cols] * xt[j]
    return xt, a


def _gdn_prep_sub_blocks(n: int, sub: int, body, init=None):
    """``body(first row, carry)`` over the ``n`` sub-blocks of a block: a
    loop, so that the kernel is traced at one sub-block's size."""
    from jax.experimental import pallas as pl

    return lax.fori_loop(
        0, n, lambda t, c: body(pl.multiple_of(t * sub, sub), c), init)


def _gdn_prep_fwd_kernel(x_ref, h_ref, w_ref, q_ref, k_ref, v_ref, xs_ref,
                         *, taps, dk, dv, group, sub):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    R, halo = x_ref.shape[1], GDN_PREP_HALO
    xs_ref[0:halo, :] = jnp.where(pl.program_id(2) > 0,
                                  h_ref[0].astype(f32), 0.0)
    xs_ref[halo:halo + R, :] = x_ref[0].astype(f32)
    outs = {"q": q_ref, "k": k_ref, "v": v_ref}
    for c0, width, kind, m in _gdn_prep_pieces(dk, dv, group):
        cols = slice(c0, c0 + width)

        def rows(r0, _, cols=cols, kind=kind, m=m):
            _, a = _gdn_prep_conv(xs_ref, w_ref, r0, sub, cols, taps)
            s = a * jax.nn.sigmoid(a)
            if kind != "v":
                s = s * lax.rsqrt(jnp.sum(s * s, axis=-1, keepdims=True)
                                  + _GDN_PREP_EPS)
            if kind == "q":
                s = s * dk ** -0.5
            outs[kind][0, m, pl.ds(r0, sub), :] = s.astype(outs[kind].dtype)

        _gdn_prep_sub_blocks(R // sub, sub, rows)


def _gdn_prep_specs(T: int, W: int, rows: int, taps: int):
    """Block specs over the grouped projection (a block of rows, the tile of
    rows before it, the tile after it) and over the grouped convolution
    kernel; the halos' indices are clamped at the row's two ends, where the
    kernels put zeros in their place."""
    from jax.experimental import pallas as pl

    per = rows // GDN_PREP_HALO
    x = pl.BlockSpec((1, rows, W), lambda j, b, i: (b, i, j))
    before = pl.BlockSpec(
        (1, GDN_PREP_HALO, W),
        lambda j, b, i: (b, jnp.maximum(i * per - 1, 0), j))
    after = pl.BlockSpec(
        (1, GDN_PREP_HALO, W),
        lambda j, b, i: (b, jnp.minimum((i + 1) * per,
                                        T // GDN_PREP_HALO - 1), j))
    return x, before, after, pl.BlockSpec((1, taps, W),
                                          lambda j, b, i: (j, 0, 0))


def _gdn_prep_sub(rows: int, sub: int) -> int:
    sub = min(sub, rows)
    if rows % sub or sub % GDN_PREP_HALO:
        raise ValueError(f"a block of {rows} rows is not whole sub-blocks "
                         f"of {sub}")
    return sub


@_traced_once("dk", "dv", "rows", "out_dtype", "sub")
def gdn_prep_fwd_pallas(x, w, *, dk: int, dv: int, rows: int, out_dtype,
                        sub: int = GDN_PREP_SUB_ROWS, interpret):
    """``x`` ``[B, T, Hk * W]`` float32, grouped columns; ``w`` ``[Hk, L, W]``
    float32, the convolution kernel grouped alike -> ``q``, ``k`` ``[B, Hk, T,
    dk]`` (convolved, SiLU, L2-normalised over the head, ``q`` times ``dk **
    -0.5``) and ``v`` ``[B, Hv, T, dv]`` (convolved, SiLU) in ``out_dtype``:
    what ``gdn_chunk_fwd_pallas`` takes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, _ = x.shape
    Hk, taps, W = w.shape
    group = (W - 2 * dk) // dv
    xs, before, _, ws = _gdn_prep_specs(T, W, rows, taps)
    qk = pl.BlockSpec((1, 1, rows, dk), lambda j, b, i: (b, j, i, 0))
    return pl.pallas_call(
        functools.partial(_gdn_prep_fwd_kernel, taps=taps, dk=dk, dv=dv,
                          group=group, sub=_gdn_prep_sub(rows, sub)),
        name="gdn_prep_fwd",
        grid=(Hk, B, T // rows),
        in_specs=[xs, before, ws],
        out_specs=[qk, qk, pl.BlockSpec((1, group, rows, dv),
                                        lambda j, b, i: (b, j, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, Hk, T, dk), out_dtype),
                   jax.ShapeDtypeStruct((B, Hk, T, dk), out_dtype),
                   jax.ShapeDtypeStruct((B, Hk * group, T, dv), out_dtype)],
        scratch_shapes=[pltpu.VMEM((GDN_PREP_HALO + rows, W), jnp.float32)],
        compiler_params=_compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=GDN_PREP_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(x, x, w)


def _gdn_prep_bwd_kernel(x_ref, h_ref, t_ref, w_ref, dq_ref, dqt_ref, dk_ref,
                         dkt_ref, dv_ref, dvt_ref, dx_ref, dw_ref, xs_ref,
                         da_ref, *, taps, dk, dv, group, sub):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    R, halo = x_ref.shape[1], GDN_PREP_HALO
    i, last = pl.program_id(2), pl.num_programs(2) - 1
    xs_ref[0:halo, :] = jnp.where(i > 0, h_ref[0].astype(f32), 0.0)
    xs_ref[halo:halo + R, :] = x_ref[0].astype(f32)
    xs_ref[halo + R:, :] = t_ref[0].astype(f32)

    @pl.when(jnp.logical_and(pl.program_id(1) == 0, i == 0))
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def grad_rows(d_ref, r0, d0, n, cols, kind, m):
        """``d loss / d (the convolution's output)`` at rows ``r0 .. r0 + n``
        of the block (``r0 = R``: the first rows after it), whose gradients
        are rows ``d0 .. d0 + n`` of ``d_ref``; and the taps' rows of ``x``."""
        xt, a = _gdn_prep_conv(xs_ref, w_ref, r0, n, cols, taps)
        rows = pl.ds(d0, n)
        sg = jax.nn.sigmoid(a)
        if kind == "v":
            ds = d_ref[0, m, rows, :].astype(f32)
        else:       # the transpose of the repeat: the group's sum, float32
            dn = d_ref[0, 0, rows, :].astype(f32)
            for other in range(1, group):
                dn = dn + d_ref[0, other, rows, :].astype(f32)
            s = a * sg
            inv = lax.rsqrt(jnp.sum(s * s, axis=-1, keepdims=True)
                            + _GDN_PREP_EPS)
            if kind == "q":
                dn = dn * dk ** -0.5
            ds = (dn - s * (inv * inv * jnp.sum(dn * s, axis=-1,
                                                keepdims=True))) * inv
        return xt, ds * (sg * (1.0 + a * (1.0 - sg)))

    grads = {"q": (dq_ref, dqt_ref), "k": (dk_ref, dkt_ref),
             "v": (dv_ref, dvt_ref)}
    for c0, width, kind, m in _gdn_prep_pieces(dk, dv, group):
        cols = slice(c0, c0 + width)
        own, after = grads[kind]

        def rows(r0, acc, cols=cols, kind=kind, m=m, own=own):
            xt, da = grad_rows(own, r0, r0, sub, cols, kind, m)
            da_ref[pl.ds(r0, sub), cols] = da
            out = []
            for j in range(taps):     # one tile of partial sums a tap; the
                prod = da * xt[j]     # rows of a tile are summed at the end
                part = acc[j]
                for t0 in range(0, sub, halo):
                    part = part + prod[t0:t0 + halo]
                out.append(part)
            return tuple(out)

        acc = _gdn_prep_sub_blocks(
            R // sub, sub, rows,
            tuple(jnp.zeros((halo, width), f32) for _ in range(taps)))
        _, da = grad_rows(after, R, 0, halo, cols, kind, m)
        da_ref[R:R + halo, cols] = jnp.where(i < last, da, 0.0)
        for j in range(taps):
            dw_ref[0, j:j + 1, cols] += jnp.sum(acc[j], axis=0, keepdims=True)

        def back(r0, _, cols=cols):   # the convolution's transpose
            window = da_ref[pl.ds(r0, sub + halo), cols]
            dx = w_ref[0, taps - 1:taps, cols] * window[:sub]
            for j in range(taps - 1):
                dx = dx + w_ref[0, j:j + 1, cols] * window[
                    taps - 1 - j:taps - 1 - j + sub]
            dx_ref[0, pl.ds(r0, sub), cols] = dx.astype(dx_ref.dtype)

        _gdn_prep_sub_blocks(R // sub, sub, back)


@_traced_once("rows", "sub")
def gdn_prep_bwd_pallas(x, w, dq, dk_, dv_, *, rows: int,
                        sub: int = GDN_PREP_SUB_ROWS, interpret):
    """The transpose of :func:`gdn_prep_fwd_pallas`: its ``x`` and ``w``, and
    the scan's ``dq``, ``dk`` ``[B, Hv, T, dk]`` (a VALUE head each) and
    ``dv`` ``[B, Hv, T, dv]`` -> (``dx`` like ``x``, ``dw`` like ``w``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, _ = x.shape
    Hk, taps, W = w.shape
    dk, dv = dq.shape[3], dv_.shape[3]
    group = dq.shape[1] // Hk
    per = rows // _GDN_PREP_D_HALO

    def d_specs(width):
        return [pl.BlockSpec((1, group, rows, width),
                             lambda j, b, i: (b, j, i, 0)),
                pl.BlockSpec((1, group, _GDN_PREP_D_HALO, width),
                             lambda j, b, i: (b, j, jnp.minimum(
                                 (i + 1) * per,
                                 T // _GDN_PREP_D_HALO - 1), 0))]

    xs, before, after, ws = _gdn_prep_specs(T, W, rows, taps)
    dx, dw = pl.pallas_call(
        functools.partial(_gdn_prep_bwd_kernel, taps=taps, dk=dk, dv=dv,
                          group=group, sub=_gdn_prep_sub(rows, sub)),
        name="gdn_prep_bwd",
        grid=(Hk, B, T // rows),
        in_specs=[xs, before, after, ws, *d_specs(dk), *d_specs(dk),
                  *d_specs(dv)],
        out_specs=[xs, ws],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(w.shape, jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((2 * GDN_PREP_HALO + rows, W), jnp.float32),
            pltpu.VMEM((GDN_PREP_HALO + rows, W), jnp.float32)],
        compiler_params=_compiler_params(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=GDN_PREP_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(x, x, x, w, dq, dq, dk_, dk_, dv_, dv_)
    return dx, dw


# ---------------------------------------------------------------------------
# From a Mamba-2 mixer's in-projection to the scan: convolution, bias, SiLU
# (ops/ssd_scan.py ``conv_ssd_scan`` calls the pair)
# ---------------------------------------------------------------------------
# ``zxbc`` ``[B, T, ...]`` is the projection as the product wrote it; the
# convolved columns ``[x | B | C]`` start at column ``offset`` and the index
# maps read them THERE, a block of ``cols`` columns at a time (``cols``
# divides the offset and the widths of x and of B, so a block lies in one of
# the three).  Grid (block of columns, batch, block of rows).  As the delta
# nets' pair above, whose ``_gdn_prep_conv`` and ``_gdn_prep_sub_blocks`` both
# bodies call: a block brings the rows before it as a halo block of the same
# array (one tile of the projection's dtype, of which the last
# ``GDN_PREP_HALO`` are kept; zero before the row's start) and is copied
# behind them into float32 VMEM scratch once, the taps are reads of that
# scratch at a row offset, and the work goes a lane tile of columns at a time
# in sub-blocks of ``GDN_PREP_SUB_ROWS`` rows, a loop over them.  The forward
# writes x ``[B, T, H P]`` and ``[B | C]`` ``[B, T, 2 G N]`` in the compute
# dtype, of which the scan's kernels read a group's blocks (B and C as column
# blocks ``g`` and ``G + g`` of the one array: at the cell's 16 MB XLA keeps
# it in VMEM between the two kernels, as it kept the chain's sliced B and
# C), and x once more in the PROJECTION's dtype for the layer's skip ``D
# x``: the chain rounds x to the compute dtype for the scan alone, so beside
# a float32 projection the skip reads float32.  The grid walks x's blocks of
# columns first; an output's index stands still while the other part's
# blocks are worked, so every block is written back once.  The reverse
# kernel takes ``dx``, ``dB``, ``dC`` as the scan's reverse
# kernel writes them, three arrays, and the skip's part of ``dx`` in the
# dtype x left in: a block of columns reads the ONE it lies in (the others'
# index maps stand still meanwhile, so nothing of them is fetched), brings
# the rows AFTER the block too, makes the pre-activation again, and
# accumulates the convolution kernel's and the bias's gradients in its second
# output's block across batch and rows.  The convolution's kernel and bias
# travel as one ``[1, taps + 1, C]`` float32 array (the taps' rows, then the
# bias), and so do their gradients.

_MAMBA_PREP_LANES = 128


def _tile_rows(dtype) -> int:
    """Rows of a tile of ``dtype``: 8 of float32, 16 of bfloat16."""
    return 32 // jnp.dtype(dtype).itemsize


def _mamba_prep_fill(xs_ref, x_ref, h_ref, t_ref=None):
    """The block behind the ``GDN_PREP_HALO`` rows before it (and, where
    ``t_ref`` is given, before as many after it) in float32 scratch."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    R, halo = x_ref.shape[1], GDN_PREP_HALO
    xs_ref[0:halo, :] = jnp.where(pl.program_id(2) > 0,
                                  h_ref[0].astype(f32)[-halo:], 0.0)
    xs_ref[halo:halo + R, :] = x_ref[0].astype(f32)
    if t_ref is not None:
        xs_ref[halo + R:, :] = t_ref[0].astype(f32)[:halo]


def _mamba_prep_fwd_kernel(x_ref, h_ref, wb_ref, ox_ref, obc_ref, os_ref,
                           xs_ref, *, taps, sub, nx):
    from jax.experimental import pallas as pl

    R, C = x_ref.shape[1:]
    over_x = pl.program_id(0) < nx
    _mamba_prep_fill(xs_ref, x_ref, h_ref)
    for c0 in range(0, C, _MAMBA_PREP_LANES):
        cols = slice(c0, c0 + _MAMBA_PREP_LANES)

        def rows(r0, _, cols=cols):
            _, a = _gdn_prep_conv(xs_ref, wb_ref, r0, sub, cols, taps)
            a = a + wb_ref[0, taps:taps + 1, cols]
            s = a * jax.nn.sigmoid(a)

            @pl.when(over_x)
            def _x():       # for the scan, and for the layer's skip
                ox_ref[0, pl.ds(r0, sub), cols] = s.astype(ox_ref.dtype)
                os_ref[0, pl.ds(r0, sub), cols] = s.astype(os_ref.dtype)

            @pl.when(jnp.logical_not(over_x))
            def _bc():
                obc_ref[0, pl.ds(r0, sub), cols] = s.astype(obc_ref.dtype)

        _gdn_prep_sub_blocks(R // sub, sub, rows)


def _mamba_prep_specs(zxbc, T: int, C: int, offset: int, rows: int,
                      cols: int, taps: int):
    """Block specs over the projection's ``[x | B | C]`` columns (a block, the
    tile of rows before it, the tile after it) and over the convolution's
    ``[1, taps + 1, C]`` kernel and bias; the halos' indices are clamped at
    the row's two ends, where the kernels put zeros in their place."""
    from jax.experimental import pallas as pl

    if (offset % cols or C % cols or cols % _MAMBA_PREP_LANES
            or zxbc.shape[2] < offset + C):
        raise ValueError(f"blocks of {cols} columns do not tile {C} columns "
                         f"from column {offset} of {zxbc.shape[2]}")
    tile = _tile_rows(zxbc.dtype)
    if T % rows or rows % tile:
        raise ValueError(f"a row of {T} is not whole blocks of {rows} rows")
    first, per = offset // cols, rows // tile
    x = pl.BlockSpec((1, rows, cols), lambda j, b, i: (b, i, first + j))
    before = pl.BlockSpec(
        (1, tile, cols),
        lambda j, b, i: (b, jnp.maximum(i * per - 1, 0), first + j))
    after = pl.BlockSpec(
        (1, tile, cols),
        lambda j, b, i: (b, jnp.minimum((i + 1) * per, T // tile - 1),
                         first + j))
    return x, before, after, pl.BlockSpec((1, taps + 1, cols),
                                          lambda j, b, i: (0, 0, j))


@_traced_once("offset", "width", "rows", "cols", "out_dtype")
def mamba_prep_fwd_pallas(zxbc, wb, *, offset: int, width: int, rows: int,
                          cols: int, out_dtype, interpret):
    """``zxbc`` ``[B, T, >= offset + C]``, the in-projection; ``wb`` ``[1,
    taps + 1, C]`` float32, the convolution's kernel and under it its bias
    -> ``silu(conv(zxbc[..., offset:offset + C]) + bias)``, float32 inside,
    as three arrays: its first ``width`` columns (x) ``[B, T, width]`` and
    the others (``[B | C]``) ``[B, T, C - width]`` in ``out_dtype``, what
    ``ssd_chunk_fwd_pallas(x, [B | C], None, ...)`` reads, and x once more in
    ``zxbc``'s dtype, what the layer's skip reads."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, _ = zxbc.shape
    taps, C = wb.shape[1] - 1, wb.shape[2]
    xs, before, _, ws = _mamba_prep_specs(zxbc, T, C, offset, rows, cols,
                                          taps)
    if not 0 < width < C or width % cols:
        raise ValueError(f"blocks of {cols} columns do not tile x's {width} "
                         f"of {C} columns")
    nx, last = width // cols, (B - 1, T // rows - 1, width // cols - 1)

    # the grid walks x's blocks of columns first: x's outputs stand where
    # the last of them left them while [B | C]'s are worked, and [B | C]'s
    # waits at its first block until then, so a block is written back once
    def x_index(j, b, i):
        return tuple(jnp.where(j < nx, v, e) for v, e in zip((b, i, j), last))

    def bc_index(j, b, i):
        return tuple(jnp.where(j < nx, 0, v) for v in (b, i, j - nx))

    return tuple(pl.pallas_call(
        functools.partial(_mamba_prep_fwd_kernel, taps=taps, nx=nx,
                          sub=_gdn_prep_sub(rows, GDN_PREP_SUB_ROWS)),
        name="mamba_prep_fwd",
        grid=(C // cols, B, T // rows),
        in_specs=[xs, before, ws],
        out_specs=[pl.BlockSpec((1, rows, cols), x_index),
                   pl.BlockSpec((1, rows, cols), bc_index),
                   pl.BlockSpec((1, rows, cols), x_index)],
        out_shape=[jax.ShapeDtypeStruct((B, T, width), out_dtype),
                   jax.ShapeDtypeStruct((B, T, C - width), out_dtype),
                   jax.ShapeDtypeStruct((B, T, width), zxbc.dtype)],
        scratch_shapes=[pltpu.VMEM((GDN_PREP_HALO + rows, cols), jnp.float32)],
        compiler_params=_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=GDN_PREP_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(zxbc, zxbc, wb))


def _mamba_prep_bwd_kernel(x_ref, h_ref, t_ref, wb_ref, dx_ref, dxt_ref,
                           sk_ref, skt_ref, db_ref, dbt_ref, dc_ref, dct_ref,
                           o_ref, dwb_ref, xs_ref, da_ref, ds_ref,
                           *, taps, sub, nx, nb):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    R, C = x_ref.shape[1:]
    halo = GDN_PREP_HALO
    j, i, last = pl.program_id(0), pl.program_id(2), pl.num_programs(2) - 1
    _mamba_prep_fill(xs_ref, x_ref, h_ref, t_ref)

    @pl.when(jnp.logical_and(pl.program_id(1) == 0, i == 0))
    def _init():
        dwb_ref[...] = jnp.zeros_like(dwb_ref)

    # the gradient of the part this block of columns lies in, float32, the
    # first rows after the block behind it
    @pl.when(j < nx)
    def _x():
        ds_ref[0:R, :] = dx_ref[0].astype(f32) + sk_ref[0].astype(f32)
        ds_ref[R:, :] = (dxt_ref[0].astype(f32)[:halo]
                         + skt_ref[0].astype(f32)[:halo])

    for on, own, after in (
            (jnp.logical_and(j >= nx, j < nx + nb), db_ref, dbt_ref),
            (j >= nx + nb, dc_ref, dct_ref)):
        @pl.when(on)
        def _narrow(own=own, after=after):
            ds_ref[0:R, :] = own[0].astype(f32)
            ds_ref[R:, :] = after[0].astype(f32)[:halo]

    def grad_rows(r0, n, cols):
        """``d loss / d (the convolution's output)`` at rows ``r0 .. r0 + n``
        of the block (``r0 = R``: the first rows after it), and the taps'
        rows of the projection."""
        xt, a = _gdn_prep_conv(xs_ref, wb_ref, r0, n, cols, taps)
        a = a + wb_ref[0, taps:taps + 1, cols]
        sg = jax.nn.sigmoid(a)
        return xt, ds_ref[pl.ds(r0, n), cols] * (sg * (1.0 + a * (1.0 - sg)))

    for c0 in range(0, C, _MAMBA_PREP_LANES):
        cols = slice(c0, c0 + _MAMBA_PREP_LANES)

        def rows(r0, acc, cols=cols):
            xt, da = grad_rows(r0, sub, cols)
            da_ref[pl.ds(r0, sub), cols] = da
            out = []
            for prod, part in zip([da * x for x in xt] + [da], acc):
                for t0 in range(0, sub, halo):   # one tile of partial sums
                    part = part + prod[t0:t0 + halo]     # a row of ``dwb``
                out.append(part)
            return tuple(out)

        acc = _gdn_prep_sub_blocks(
            R // sub, sub, rows,
            tuple(jnp.zeros((halo, _MAMBA_PREP_LANES), f32)
                  for _ in range(taps + 1)))
        _, da = grad_rows(R, halo, cols)
        da_ref[R:R + halo, cols] = jnp.where(i < last, da, 0.0)
        for k in range(taps + 1):
            dwb_ref[0, k:k + 1, cols] += jnp.sum(acc[k], axis=0,
                                                 keepdims=True)

        def back(r0, _, cols=cols):   # the convolution's transpose
            window = da_ref[pl.ds(r0, sub + halo), cols]
            dx = wb_ref[0, taps - 1:taps, cols] * window[:sub]
            for k in range(taps - 1):
                dx = dx + wb_ref[0, k:k + 1, cols] * window[
                    taps - 1 - k:taps - 1 - k + sub]
            o_ref[0, pl.ds(r0, sub), cols] = dx.astype(o_ref.dtype)

        _gdn_prep_sub_blocks(R // sub, sub, back)


@_traced_once("offset", "rows", "cols", "out_dtype")
def mamba_prep_bwd_pallas(zxbc, wb, dx, dskip, dB, dC, *, offset: int,
                          rows: int, cols: int, out_dtype, interpret):
    """The transpose of :func:`mamba_prep_fwd_pallas`: its ``zxbc`` and
    ``wb``, the scan's ``dx`` ``[B, T, H P]``, ``dB``, ``dC`` ``[B, T, G N]``
    and ``dskip`` ``[B, T, H P]`` in a dtype of its own (what else reaches
    x: the layer's ``D x``, which read x in the projection's dtype) ->
    (the gradient of ``zxbc[..., offset:offset + C]`` ``[B, T, C]`` in
    ``out_dtype``, ``dwb`` like ``wb``: the kernel's and the bias's
    gradients summed in float32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, _ = zxbc.shape
    taps, C = wb.shape[1] - 1, wb.shape[2]
    HP, GN = dx.shape[2], dB.shape[2]
    if HP + 2 * GN != C or HP % cols or GN % cols:
        raise ValueError(f"blocks of {cols} columns do not tile [x | B | C] "
                         f"of {HP} + 2 x {GN} columns")
    xs, before, after, ws = _mamba_prep_specs(zxbc, T, C, offset, rows, cols,
                                              taps)

    def d_specs(part, lo, n):
        """A part's own rows and the tile after them, where the block of
        columns lies in the part (blocks ``lo .. lo + n``); elsewhere the
        index stands at the part's first block, so that nothing moves."""
        tile = _tile_rows(part.dtype)
        per = rows // tile

        def at(row):
            def index(j, b, i):
                on = jnp.logical_and(j >= lo, j < lo + n)
                return tuple(jnp.where(on, v, 0)
                             for v in (b, row(i), j - lo))
            return index
        return [pl.BlockSpec((1, rows, cols), at(lambda i: i)),
                pl.BlockSpec((1, tile, cols), at(lambda i: jnp.minimum(
                    (i + 1) * per, T // tile - 1)))]

    nx, nb = HP // cols, GN // cols
    out = pl.BlockSpec((1, rows, cols), lambda j, b, i: (b, i, j))
    return tuple(pl.pallas_call(
        functools.partial(_mamba_prep_bwd_kernel, taps=taps, nx=nx, nb=nb,
                          sub=_gdn_prep_sub(rows, GDN_PREP_SUB_ROWS)),
        name="mamba_prep_bwd",
        grid=(C // cols, B, T // rows),
        in_specs=[xs, before, after, ws, *d_specs(dx, 0, nx),
                  *d_specs(dskip, 0, nx), *d_specs(dB, nx, nb),
                  *d_specs(dC, nx + nb, nb)],
        out_specs=[out, ws],
        out_shape=[jax.ShapeDtypeStruct((B, T, C), out_dtype),
                   jax.ShapeDtypeStruct(wb.shape, jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((2 * GDN_PREP_HALO + rows, cols), jnp.float32),
            pltpu.VMEM((GDN_PREP_HALO + rows, cols), jnp.float32),
            pltpu.VMEM((GDN_PREP_HALO + rows, cols), jnp.float32)],
        compiler_params=_compiler_params(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=GDN_PREP_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(zxbc, zxbc, zxbc, wb, dx, dx, dskip, dskip, dB, dB, dC, dC))


# ---------------------------------------------------------------------------
# Learned sparse attention: the indexer's scores, the selection, the
# indexer's loss (ops/sparse_attention.py has the algebra and the XLA twin)
# ---------------------------------------------------------------------------
# Three kernels beside the two flash kernels' ``keep=`` form.  Square tiles
# of ``block`` queries by ``block`` keys, tiles above the diagonal skipped
# (their block indices clamped, so a skipped step moves nothing).
# ``indexer_scores``: I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]), -inf at
# s > t; the J products of a tile live in VMEM only, the result is ONE
# [T, T] float32.  ``topk_select``: a block of whole rows of I in VMEM; the
# k-th largest of a row by bisection on the scores' bits (32 counting
# passes, exact), ties at the threshold by position (15 more), the row's
# selection written as int8 with the log-sum-exp of the kept scores.
# ``indexer_loss``: per tile the 32 heads' probabilities from the forward's
# lse, summed in VMEM scratch.  The grid's innermost axis is the KEY-VALUE
# head (PR 53): a step holds a tile and a group's query heads, walks the
# tile slab by slab and adds the group's heads to a slab, in the heads'
# order, before the slab goes back, so the scratch is read and written once
# a group and the key block is fetched once a group.  The group's lse comes
# with the queries along the lanes (``[B, Hkv, G, T]``, 32 KB a block: a
# block of ``[.., 1024, 1]`` columns is fetched as 4 MB of lane padding) and
# is turned into columns once a step.  On the last group the indexer's
# scores are READ from I (the tile ``indexer_scores`` wrote), then the tile's
# part of KL(p || q) and of the gradient into qI, kI (resident over a batch
# row, as the flash backward's dk) and w.  No [T, T] array but I and the
# selection crosses HBM; I is read twice (``topk_select``, ``indexer_loss``).

SPARSE_VMEM_LIMIT_BYTES = 100 * 1024 * 1024
#: rows of scores one ``topk_select`` step holds
TOPK_SELECT_ROWS = 128
_INT_MIN = -2 ** 31


def _indexer_tile(q_ref, k, w, heads):
    """The indexer's scores of one tile, float32: ``sum_j w[:, j] relu(q_j
    k^T)``; ``q_ref`` is the block ``[1, J, rows, d]``."""
    acc = None
    for j in range(heads):
        pre = jax.lax.dot_general(q_ref[0, j], k, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        term = w[:, j:j + 1] * jnp.maximum(pre, 0.0)
        acc = term if acc is None else acc + term
    return acc


def _indexer_scores_kernel(q_ref, k_ref, w_ref, o_ref, *, heads, block):
    from jax.experimental import pallas as pl

    qi, kj = pl.program_id(1), pl.program_id(2)

    @pl.when(kj > qi)
    def _future():
        o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)

    @pl.when(kj <= qi)
    def _live():
        acc = _indexer_tile(q_ref, k_ref[0], w_ref[0], heads)
        rows = qi * block + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        cols = kj * block + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
        o_ref[0] = jnp.where(cols <= rows, acc, -jnp.inf)


@_traced_once("block")
def indexer_scores_pallas(qI, kI, w, *, block: int, interpret: bool):
    """qI ``[B, J, T, d]``, kI ``[B, T, d]`` (the compute dtype), w ``[B, T,
    J]`` float32 -> I ``[B, T, T]`` float32, ``-inf`` above the diagonal."""
    from jax.experimental import pallas as pl

    B, J, T, d = qI.shape
    n = T // block
    return pl.pallas_call(
        functools.partial(_indexer_scores_kernel, heads=J, block=block),
        name="indexer_scores",
        grid=(B, n, n),
        in_specs=[
            pl.BlockSpec((1, J, block, d), lambda b, qi, kj: (b, 0, qi, 0)),
            pl.BlockSpec((1, block, d),
                         lambda b, qi, kj: (b, jnp.minimum(kj, qi), 0)),
            pl.BlockSpec((1, block, J), lambda b, qi, kj: (b, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block, block),
                               lambda b, qi, kj: (b, qi, kj)),
        out_shape=jax.ShapeDtypeStruct((B, T, T), jnp.float32),
        compiler_params=_compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=SPARSE_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(qI, kI, w)


def _topk_select_kernel(s_ref, keep_ref, lse_ref, *, topk, rows, pos_bits):
    from jax.experimental import pallas as pl

    x = s_ref[0]                                       # [rows, T] float32
    # the scores' bits in an order that is the scores' own (-0.0 is +0.0)
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    key = jnp.where(bits == jnp.int32(_INT_MIN), 0, key)
    first = pl.program_id(1) * rows
    t = first + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    want = jnp.minimum(t + 1, topk).astype(jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    int_min = jnp.int32(_INT_MIN)

    def count(cond):
        return jnp.sum(jnp.where(cond, 1.0, 0.0), axis=1, keepdims=True)

    def value_bit(i, tau):      # the largest threshold >= ``want`` scores
        cand = tau | (jnp.int32(1) << (31 - i))        # reach, bit by bit
        return jnp.where(count(key >= (cand ^ int_min)) >= want, cand, tau)

    tau = jax.lax.fori_loop(0, 32, value_bit,
                            jnp.zeros((rows, 1), jnp.int32)) ^ int_min
    above, equal = key > tau, key == tau
    need = want - count(above)      # of the scores AT the threshold, the
                                    # ``need`` lowest positions are kept

    def position_bit(i, p):
        cand = p | (jnp.int32(1) << (pos_bits - 1 - i))
        return jnp.where(count(equal & (col < cand)) < need, cand, p)

    last = jax.lax.fori_loop(0, pos_bits, position_bit,
                             jnp.zeros((rows, 1), jnp.int32))
    keep = above | (equal & (col <= last))
    keep_ref[0] = keep.astype(jnp.int8)
    kept = jnp.where(keep, x, -jnp.inf)
    m = jnp.max(kept, axis=1, keepdims=True)
    lse_ref[0] = m + jnp.log(jnp.sum(jnp.exp(kept - m), axis=1,
                                     keepdims=True))


@_traced_once("topk", "rows")
def topk_select_pallas(scores, *, topk: int, rows: int, interpret: bool):
    """scores ``[B, T, T]`` float32 with ``-inf`` above the diagonal ->
    (keep ``[B, T, T]`` int8: 1 at the ``min(t + 1, topk)`` largest of row
    ``t``, among equal scores the lower position first; lse ``[B, T, 1]``:
    the log-sum-exp of a row's kept scores)."""
    from jax.experimental import pallas as pl

    B, T, _ = scores.shape
    return pl.pallas_call(
        functools.partial(_topk_select_kernel, topk=topk, rows=rows,
                          pos_bits=max(1, (T - 1).bit_length())),
        name="topk_select",
        grid=(B, T // rows),
        in_specs=[pl.BlockSpec((1, rows, T), lambda b, r: (b, r, 0))],
        out_specs=[pl.BlockSpec((1, rows, T), lambda b, r: (b, r, 0)),
                   pl.BlockSpec((1, rows, 1), lambda b, r: (b, r, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, T, T), jnp.int8),
                   jax.ShapeDtypeStruct((B, T, 1), jnp.float32)],
        compiler_params=_compiler_params(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=SPARSE_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(scores)


def _indexer_loss_slab(block: int):
    """(rows, columns) of the piece of a tile that ``indexer_loss`` adds a
    group's heads to at a time: a quarter of the tile's rows by up to four
    lane tiles of its columns (256 x 512 at tiles of 1024).  The wider the
    piece, the more columns share a row's lse, whose spread over the lanes
    binds a piece of one lane tile; at 512 columns the MXU binds."""
    return block // 4, min(block, 512)


def _indexer_loss_kernel(q_ref, k_ref, lse_ref, qi_ref, ki_ref, w_ref,
                         lsei_ref, rows_ref, keep_ref, scores_ref, kl_ref,
                         dqi_ref, dki_ref, dw_ref, acc_scr, lse_scr, *, scale,
                         heads, group, idx_heads, block):
    from jax.experimental import pallas as pl

    qi, kj, kv = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    live = kj <= qi

    @pl.when((qi == 0) & (kj == 0) & (kv == 0))
    def _zero_keys():
        dki_ref[...] = jnp.zeros_like(dki_ref)

    @pl.when((kj == 0) & (kv == 0))
    def _zero_queries():
        kl_ref[...] = jnp.zeros_like(kl_ref)
        dqi_ref[...] = jnp.zeros_like(dqi_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(live & (kv == 0))
    def _zero_tile():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(live)
    def _group():       # this group's probabilities, by the forward's lse
        rows, cols = _indexer_loss_slab(block)
        across = block // cols
        lse_scr[...] = lse_ref[0, 0].T      # a head's lse down a column

        def add(i, carry):
            r = pl.ds(pl.multiple_of(i // across * rows, rows), rows)
            c = pl.ds(pl.multiple_of(i % across * cols, cols), cols)
            keys = k_ref[0, 0, c, :]
            acc = acc_scr[r, c]
            for h in range(group):      # head by head: the order of the sum
                s = jax.lax.dot_general(
                    q_ref[0, h, r, :], keys, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                acc = acc + jnp.exp(s - lse_scr[r, h:h + 1])
            acc_scr[r, c] = acc
            return carry

        jax.lax.fori_loop(0, block // rows * across, add, 0)

    @pl.when(live & (kv == heads // group - 1))
    def _indexer():
        keep = keep_ref[0].astype(jnp.int32) != 0
        p = jnp.where(keep, acc_scr[...] * (1.0 / heads), 0.0)
        kI, w = ki_ref[0], w_ref[0]
        # the scores as ``indexer_scores`` wrote them: -inf above the
        # diagonal, where ``keep`` is 0 and every use below is masked
        logq = scores_ref[0] - lsei_ref[0]
        real = rows_ref[0]          # 1 at a real query, 0 at a padded one
        kl_ref[0] += real * jnp.sum(
            jnp.where(p > 0.0, p * (jnp.log(p) - logq), 0.0), axis=1,
            keepdims=True)
        d_scores = real * (jnp.where(keep, jnp.exp(logq), 0.0) - p)
        keys = pl.ds(pl.multiple_of(kj * block, block), block)
        for j in range(idx_heads):
            qj = qi_ref[0, j]
            pre = jax.lax.dot_general(qj, kI, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            dw_ref[0, :, j:j + 1] += jnp.sum(
                d_scores * jnp.maximum(pre, 0.0), axis=1, keepdims=True)
            g = jnp.where(pre > 0.0, d_scores * w[:, j:j + 1],
                          0.0).astype(qj.dtype)
            dqi_ref[0, j] += jnp.dot(g, kI,
                                     preferred_element_type=jnp.float32)
            dki_ref[0, keys, :] += jax.lax.dot_general(
                g, qj, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)


@_traced_once("scale", "block")
def indexer_loss_pallas(q, k, lse, qI, kI, w, lse_i, rows, keep, scores, *,
                        scale: float, block: int, interpret: bool):
    """The indexer's loss and its gradient in one pass over the tiles, a
    grid step a tile and key-value head.  q ``[B, H, T, dh]``, k ``[B, Hkv,
    T, dh]``, lse ``[B, H, T, 1]`` (the selected attention's; the kernel
    takes it as ``[B, Hkv, G, T]``), qI ``[B, J, T, d]``, kI ``[B, T, d]``,
    w ``[B, T, J]``, lse_i ``[B, T, 1]`` and keep ``[B, T, T]``
    (``topk_select``'s), rows ``[B, T, 1]`` float32 (1 at a real query, 0 at
    a padded one, which then adds nothing), scores ``[B, T, T]`` float32
    (``indexer_scores_pallas``'s of the same qI, kI, w: read, not made
    again) ->
    (kl ``[B, T, 1]``: a row's ``KL(p || softmax_kept(I))`` with ``p`` the
    heads' mean probability; dqI ``[B, J, T, d]``, dkI ``[B, T, d]``, dw
    ``[B, T, J]``: the gradient of the rows' sum, float32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, dh = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    J, d = qI.shape[1], qI.shape[3]
    n = T // block

    def key_block(qi, kj):
        return jnp.minimum(kj, qi)

    return pl.pallas_call(
        functools.partial(_indexer_loss_kernel, scale=scale, heads=H,
                          group=G, idx_heads=J, block=block),
        name="indexer_loss",
        grid=(B, n, n, Hkv),
        in_specs=[
            pl.BlockSpec((1, G, block, dh),
                         lambda b, qi, kj, g: (b, g, qi, 0)),
            pl.BlockSpec((1, 1, block, dh),
                         lambda b, qi, kj, g: (b, g, key_block(qi, kj), 0)),
            pl.BlockSpec((1, 1, G, block),
                         lambda b, qi, kj, g: (b, g, 0, qi)),
            pl.BlockSpec((1, J, block, d),
                         lambda b, qi, kj, g: (b, 0, qi, 0)),
            pl.BlockSpec((1, block, d),
                         lambda b, qi, kj, g: (b, key_block(qi, kj), 0)),
            pl.BlockSpec((1, block, J), lambda b, qi, kj, g: (b, qi, 0)),
            pl.BlockSpec((1, block, 1), lambda b, qi, kj, g: (b, qi, 0)),
            pl.BlockSpec((1, block, 1), lambda b, qi, kj, g: (b, qi, 0)),
            pl.BlockSpec((1, block, block),
                         lambda b, qi, kj, g: (b, qi, key_block(qi, kj))),
            pl.BlockSpec((1, block, block),
                         lambda b, qi, kj, g: (b, qi, key_block(qi, kj))),
        ],
        out_specs=[
            pl.BlockSpec((1, block, 1), lambda b, qi, kj, g: (b, qi, 0)),
            pl.BlockSpec((1, J, block, d),
                         lambda b, qi, kj, g: (b, 0, qi, 0)),
            pl.BlockSpec((1, T, d), lambda b, qi, kj, g: (b, 0, 0)),
            pl.BlockSpec((1, block, J), lambda b, qi, kj, g: (b, qi, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, T, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, J, T, d), jnp.float32),
                   jax.ShapeDtypeStruct((B, T, d), jnp.float32),
                   jax.ShapeDtypeStruct((B, T, J), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, block), jnp.float32),
                        pltpu.VMEM((block, G), jnp.float32)],
        compiler_params=_compiler_params(
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=SPARSE_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(q, k, lse.reshape(B, Hkv, G, T), qI, kI, w, lse_i, rows, keep, scores)


# ---------------------------------------------------------------------------
# rotary embedding: one elementwise pass over whole heads
# ---------------------------------------------------------------------------
#
# ``y = x * C + partner(x) * S`` (ops/decoder_block.rotary_embedding).  In
# XLA a channel's partner is a slice and a concatenation on the lane axis,
# which the TPU compiles into half-lane reads and writes of the whole array;
# here it is ``pltpu.roll`` on a slab whose lanes are whole heads, in
# registers.  A slab is ``[rows, L]``: a head of ``L = head_dim`` lanes where
# that is whole lane tiles (x heads-major, ``[B, H, T, dh]``: what the flash
# kernels read and write, so nothing is transposed on the way), else
# ``L = lcm(head_dim, 128)`` lanes of the token-major ``[B, T, H dh]`` view
# (a head of 64: two a slab; of 192: two heads on three tiles).  The tables
# come ``[T, L]`` and a block of them serves every slab of a block of rows.

#: scoped VMEM the rotary kernel asks for (blocks of 2 MB in and out, twice)
ROTARY_VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def _rotary_kernel(x_ref, cos_ref, sin_ref, o_ref, *, head_dim, span):
    from jax.experimental.pallas import tpu as pltpu

    heads_major = len(x_ref.shape) == 4
    a, b = span
    half = (b - a) // 2
    cos, sin = cos_ref[...], sin_ref[...]
    L = cos.shape[-1]
    first = None
    if b - a != L:
        # which of its two partners a lane takes: the channel within its head
        lane = lax.broadcasted_iota(jnp.int32, cos.shape, 1)
        ch = lane
        for i in range(1, L // head_dim):
            ch = jnp.where(lane >= i * head_dim, lane - i * head_dim, ch)
        first = ch < a + half
    slabs = x_ref.shape[1] if heads_major else x_ref.shape[2] // L
    for g in range(slabs):
        at = (0, g) if heads_major else (0, slice(None),
                                         slice(g * L, (g + 1) * L))
        x = x_ref[at].astype(jnp.float32)
        if first is None:
            p = pltpu.roll(x, half, 1)
        else:
            p = jnp.where(first, pltpu.roll(x, L - half, 1),
                          pltpu.roll(x, half, 1))
        o_ref[at] = (x * cos + p * sin).astype(o_ref.dtype)


@_traced_once("head_dim", "span", "block_rows", "block_lanes")
def rotary_pallas(x, cos, sin, *, head_dim, span, block_rows, block_lanes,
                  interpret):
    """x heads-major ``[B, H, T, dh]`` (``dh`` whole lane tiles) or
    token-major ``[B, T, H dh]``; cos, sin ``[T, L]`` float32 with the sine signed (-sin
    on the span's first half, +sin on its second, 0 outside it) and ``L`` the
    slab's lanes (the tables of one head, repeated over a slab's heads) ->
    ``x * cos + partner(x) * sin`` in ``x``'s shape and dtype, float32
    arithmetic.  ``span`` ``(a, b)``: the turned channels of a head.  A
    block is ``block_rows`` positions by ``block_lanes`` lanes (a whole
    number of slabs)."""
    from jax.experimental import pallas as pl

    L = cos.shape[-1]
    table = pl.BlockSpec((block_rows, L), lambda b, i, j: (i, 0))
    if x.ndim == 4:
        B, H, T, _ = x.shape
        per = block_lanes // L
        block = pl.BlockSpec((1, per, block_rows, L),
                             lambda b, i, j: (b, j, i, 0))
        grid = (B, T // block_rows, H // per)
    else:
        B, T, W = x.shape
        block = pl.BlockSpec((1, block_rows, block_lanes),
                             lambda b, i, j: (b, i, j))
        grid = (B, T // block_rows, W // block_lanes)
    return pl.pallas_call(
        functools.partial(_rotary_kernel, head_dim=head_dim, span=span),
        name="rotary_turn",
        grid=grid,
        in_specs=[block, table, table],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=ROTARY_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(x, cos, sin)
