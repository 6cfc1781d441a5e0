"""VMEM-resident Pallas attention-decoder kernels (interpret mode on CPU)
vs the XLA scan path of ops/attention_decoder.py — forward, residuals, and
every gradient.

The Pallas path is gated to the TPU backend (attention_decoder.
_attn_pallas_block), so on CPU the gate is monkeypatched to a fixed batch
block and the kernels run through the Pallas interpreter; numerics then
mirror the scan path exactly (f32 compute policy) and the comparisons pin
the whole custom-VJP pipeline — in-kernel reverse step + post-kernel
batched weight-grad contractions — to XLA autodiff of the identical math.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops import attention_decoder as ad
from paddle_tpu.ops.attention_decoder import attention_gru_decoder
from paddle_tpu.ops.pallas_kernels import pallas_available

from test_attention_decoder import ORDER, _tols, make_args, reference

def _hw() -> bool:
    from conftest import on_accelerator

    return on_accelerator()


# The force_pallas tests run the kernels through the INTERPRETER on tiny
# non-tile-aligned shapes — on real hardware those shapes cannot lower, so
# they are CPU-only; test_aligned_shapes_real_lowering below covers the
# actual Mosaic path in hardware mode.
pytestmark = [
    pytest.mark.skipif(not pallas_available(), reason="pallas unavailable"),
]

interpret_only = pytest.mark.skipif(
    _hw(), reason="interpret-mode equivalence (non-aligned shapes); the "
    "hardware path is covered by test_aligned_shapes_real_lowering")


@pytest.fixture
def force_pallas(monkeypatch):
    """Route attention_gru_decoder through the Pallas kernels regardless of
    backend (interpret mode handles the non-tile-aligned test shapes)."""
    monkeypatch.setattr(ad, "_attn_pallas_block", lambda B, S, D, A, H2: 2)


def test_aligned_shapes_real_lowering(monkeypatch):
    """Tile-aligned shapes through the kernels on whatever backend is live:
    real Mosaic lowering in hardware mode, interpreter on CPU.  Forward and
    enc/enc_proj/att_v grads vs the scan reference."""
    monkeypatch.setattr(ad, "_attn_pallas_block", lambda B, S, D, A, H2: 8)
    args = make_args(B=16, S=8, T=5, E=32, H2=256, D=128, A=128,
                     src_lens=(8, 5, 8, 3) * 4, trg_lens=(5, 4, 5, 2) * 4)
    vals = [args[k] for k in ORDER]
    # tolerances: one notch looser than _tols() — at these wider dims the
    # fused path's split in-projection (xp_y + ctx@wx_c vs the reference's
    # single concat matmul) reassociates ~300-term dot products, so f32
    # rounding alone exceeds the tiny-shape tolerance (this is a property
    # of the decoder decomposition, not of the Pallas kernels)
    tols = _tols() if _hw() else dict(rtol=3e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(reference(*vals)),
                               np.asarray(attention_gru_decoder(*vals)),
                               **tols)

    def loss(fn, *v):
        return jnp.sum(fn(*v) ** 2)

    g_ref = jax.grad(lambda *v: loss(reference, *v),
                     argnums=(0, 2, 3, 7, 10))(*vals)
    g_new = jax.grad(lambda *v: loss(attention_gru_decoder, *v),
                     argnums=(0, 2, 3, 7, 10))(*vals)
    for a, b, nm in zip(g_ref, g_new, ("y_emb", "enc", "enc_proj",
                                       "att_v", "wh")):
        scale = np.abs(np.asarray(a, np.float64)).max() + 1e-12
        # CPU atol sits just above the interpreter's observed worst case
        # (enc_proj: 4/16384 elements at 6.2e-3, max rel diff 1.2% — f32
        # reassociation in the split in-projection, same cause as the
        # forward tolerance note above), not at a round number below it
        np.testing.assert_allclose(np.asarray(a, np.float64) / scale,
                                   np.asarray(b, np.float64) / scale,
                                   atol=8e-3 if not _hw() else 2e-2,
                                   err_msg=nm)


@interpret_only
def test_forward_matches_scan(force_pallas):
    vals = [make_args()[k] for k in ORDER]
    np.testing.assert_allclose(np.asarray(reference(*vals)),
                               np.asarray(attention_gru_decoder(*vals)),
                               **_tols())


@interpret_only
def test_residuals_match_scan_path(monkeypatch):
    """probs/ctx/s_prev streamed out of the forward kernel must equal the
    scan path's stacked residuals — the backward consumes them directly."""
    vals = [make_args()[k] for k in ORDER]
    _, res_scan = ad._decoder_fwd_scan(*vals)
    monkeypatch.setattr(ad, "_attn_pallas_block", lambda *a: 2)
    _, res_pl = ad._decoder_fwd_scan(*vals)
    for a, b, nm in zip(res_scan, res_pl, ("probs", "ctx", "s_prev")):
        assert a.dtype == b.dtype, nm
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **_tols(),
                                   err_msg=nm)


@interpret_only
@pytest.mark.parametrize("seed", [0, 1])
def test_all_gradients_match_autodiff(force_pallas, seed):
    args = make_args(seed=seed)
    vals = [args[k] for k in ORDER]
    rs = np.random.RandomState(100 + seed)
    ct = jnp.asarray(rs.randn(4, 6, 8).astype(np.float32))
    diff_idx = [0, 1, 2, 3, 6, 7, 8, 9, 10]  # everything but the masks

    def wrap(fn):
        def loss(*dv):
            full = list(vals)
            for i, ix in enumerate(diff_idx):
                full[ix] = dv[i]
            return jnp.sum(fn(*full) * ct)
        return loss

    dv = [vals[i] for i in diff_idx]
    g_ref = jax.grad(wrap(reference), argnums=tuple(range(len(dv))))(*dv)
    g_new = jax.grad(wrap(attention_gru_decoder),
                     argnums=tuple(range(len(dv))))(*dv)
    for i, (a, b) in enumerate(zip(g_ref, g_new)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **_tols(),
                                   err_msg=f"grad {ORDER[diff_idx[i]]}")


@interpret_only
def test_masked_rows_and_uneven_block(force_pallas):
    """Masked source/target tails + a block size that splits the batch (B=4,
    Bb=2): the per-block d_enc_proj/d_v accumulators must concatenate/sum
    to the scan path's values."""
    args = make_args(src_lens=(5, 2, 4, 1), trg_lens=(3, 6, 1, 5))
    vals = [args[k] for k in ORDER]

    def loss(fn, *v):
        return jnp.sum(fn(*v) ** 2)

    g_ref = jax.grad(lambda *v: loss(reference, *v),
                     argnums=(2, 3, 7))(*vals)  # enc, enc_proj, att_v
    g_new = jax.grad(lambda *v: loss(attention_gru_decoder, *v),
                     argnums=(2, 3, 7))(*vals)
    for a, b, nm in zip(g_ref, g_new, ("enc", "enc_proj", "att_v")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **_tols(),
                                   err_msg=nm)


@interpret_only
def test_bf16_operand_policy(force_pallas):
    """bf16 enc/enc_proj (the production cache dtype): kernel path stays
    finite and within bf16 tolerance of the all-f32 kernel run."""
    args = make_args(T=8, trg_lens=(8, 6, 8, 4))
    vals = [args[k] for k in ORDER]
    i_enc, i_encP = ORDER.index("enc"), ORDER.index("enc_proj")

    def loss(enc, enc_proj, cast):
        full = list(vals)
        full[i_enc] = enc.astype(jnp.bfloat16) if cast else enc
        full[i_encP] = enc_proj.astype(jnp.bfloat16) if cast else enc_proj
        return jnp.sum(attention_gru_decoder(*full) ** 2)

    g32 = jax.grad(loss, argnums=(0, 1))(args["enc"], args["enc_proj"], False)
    g16 = jax.grad(loss, argnums=(0, 1))(args["enc"], args["enc_proj"], True)
    for a, b, nm in zip(g32, g16, ("enc", "enc_proj")):
        scale = np.abs(np.asarray(a, np.float64)).max() + 1e-6
        np.testing.assert_allclose(np.asarray(a, np.float64) / scale,
                                   np.asarray(b, np.float64) / scale,
                                   atol=3e-2, err_msg=nm)


def test_gate_rejects_cpu_and_misaligned_shapes(monkeypatch):
    """The production gate must route CPU backends and non-tile-aligned
    shapes to the XLA scan path (returning None), never to a kernel that
    cannot lower."""
    if jax.default_backend() != "tpu":
        assert ad._attn_pallas_block(384, 32, 512, 512, 1024) is None
    # force the backend probe open so the alignment branches execute on
    # CPU too (otherwise the backend check short-circuits them)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ad._attn_pallas_block(384, 32, 512, 512, 1024) == 128
    # misaligned dims can never tile
    assert ad._attn_pallas_block(384, 32, 500, 512, 1024) is None
    assert ad._attn_pallas_block(384, 30, 512, 512, 1024) is None
    # a batch with no sublane-aligned divisor
    assert ad._attn_pallas_block(7, 32, 512, 512, 1024) is None
