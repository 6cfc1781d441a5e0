"""Pallas fused RNN kernels (interpret mode on CPU) vs the lax.scan reference —
the device-equivalence pattern of the reference's math tests.

The scan twins are imported from pallas_kernels itself (_lstm_reference /
_gru_reference) so the cell math has exactly one source of truth.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.ops as O
from paddle_tpu.ops import (attention_decoder, decode, decoder_block, losses,
                            moe, rnn_fused)
from paddle_tpu.ops.pallas_kernels import (
    _gru_reference,
    _lstm_reference,
    gru_forward_pallas,
    lstm_forward_pallas,
    pallas_available,
)
from test_rnn_fused import _backward_kernel

pytestmark = pytest.mark.skipif(not pallas_available(), reason="pallas unavailable")


def _data(rng, B=4, T=6, H=8, gates=4, dtype=np.float32):
    xp = jnp.asarray(rng.randn(B, T, gates * H).astype(dtype) * 0.3)
    lengths = jnp.asarray(np.array([6, 3, 5, 1, 2, 6, 4, 1], np.int32)[:B])
    mask = O.mask_from_lengths(lengths, T)
    w_h = jnp.asarray(rng.randn(H, gates * H).astype(dtype) * 0.2)
    return xp, mask, w_h


def test_lstm_pallas_matches_scan(rng):
    xp, mask, w_h = _data(rng)
    h_seq_p, h_f_p, c_f_p = lstm_forward_pallas(xp, mask, w_h)
    h_seq, h_f, c_f = _lstm_reference(xp, mask, w_h)
    # identical semantics including zeros at padded timesteps
    np.testing.assert_allclose(np.asarray(h_seq_p), np.asarray(h_seq),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h_f_p), np.asarray(h_f), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(c_f_p), np.asarray(c_f), rtol=1e-5, atol=1e-6)


def test_gru_pallas_matches_scan(rng):
    xp, mask, w_h = _data(rng, gates=3)
    h_seq_p, h_f_p = gru_forward_pallas(xp, mask, w_h)
    h_seq, h_f = _gru_reference(xp, mask, w_h)
    np.testing.assert_allclose(np.asarray(h_seq_p), np.asarray(h_seq),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h_f_p), np.asarray(h_f), rtol=1e-5, atol=1e-6)


def test_lstm_pallas_grad_matches_scan(rng):
    xp, mask, w_h = _data(rng)

    def weighted(h_seq, h_f):
        w = jnp.cos(jnp.arange(h_seq.size).reshape(h_seq.shape))
        return jnp.sum(h_seq * w) + jnp.sum(h_f)

    def loss_p(xp, w_h):
        h_seq, h_f, _ = lstm_forward_pallas(xp, mask, w_h)
        return weighted(h_seq, h_f)

    def loss_s(xp, w_h):
        h_seq, h_f, _ = _lstm_reference(xp, mask, w_h)
        return weighted(h_seq, h_f)

    gp = jax.grad(loss_p, argnums=(0, 1))(xp, w_h)
    gs = jax.grad(loss_s, argnums=(0, 1))(xp, w_h)
    for a, b in zip(gp, gs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_lstm_pallas_grad_bfloat16(rng):
    """bf16 inputs must flow through forward and backward (grads in bf16)."""
    xp, mask, w_h = _data(rng, dtype=np.float32)
    xp, w_h = xp.astype(jnp.bfloat16), w_h.astype(jnp.bfloat16)

    def loss(xp, w_h):
        h_seq, h_f, _ = lstm_forward_pallas(xp, mask, w_h)
        return jnp.sum(h_seq) + jnp.sum(h_f)

    d_xp, d_wh = jax.grad(loss, argnums=(0, 1))(xp, w_h)
    assert d_xp.dtype == jnp.bfloat16 and d_wh.dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(d_xp, np.float32)).all()
    f32 = jax.grad(
        lambda a, b: loss(a.astype(jnp.float32), b.astype(jnp.float32)),
        argnums=(0, 1),
    )(xp.astype(jnp.float32), w_h.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(d_xp, np.float32), np.asarray(f32[0]),
                               rtol=0.1, atol=0.05)


def test_lstm_pallas_matches_scan_bf16_policy(rng):
    """Under the production compute_dtype=bfloat16 policy the kernel must
    compute the same function as the scan path it shares gradients with."""
    from paddle_tpu.utils.flags import FLAGS

    xp, mask, w_h = _data(rng)
    old = FLAGS.compute_dtype
    FLAGS.compute_dtype = "bfloat16"
    try:
        h_seq_p, h_f_p, c_f_p = lstm_forward_pallas(xp, mask, w_h)
        h_seq, h_f, c_f = _lstm_reference(xp, mask, w_h)
    finally:
        FLAGS.compute_dtype = old
    np.testing.assert_allclose(np.asarray(h_seq_p), np.asarray(h_seq),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h_f_p), np.asarray(h_f),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(c_f_p), np.asarray(c_f),
                               rtol=1e-5, atol=1e-6)


class TestBackwardKernels:
    """The Pallas reverse-loop kernels (interpret mode) must produce the
    exact gradients of the scan forward they pair with in rnn_fused."""

    @pytest.mark.parametrize("has_peepholes", [True, False],
                             ids=["peepholes", "plain"])
    def test_lstm_fused_grads_with_pallas_bwd(self, rng, monkeypatch,
                                              has_peepholes):
        """Both variants of the kernel: the bias gradient always leaves it
        reduced, the three peephole gradients when peepholes are live."""
        from paddle_tpu.ops.rnn_fused import lstm_sequence_fused
        # B fills a sublane tile, as the gate demands of every shape it
        # admits: the kernel's accumulators hold one tile per column
        B, T, H = 8, 6, 8
        xp, mask, w_h = _data(rng, B=B, T=T, H=H, gates=4)
        b = jnp.asarray(rng.randn(4 * H).astype(np.float32) * 0.1)
        z = jnp.zeros((B, H), jnp.float32)
        ct_seq = jnp.asarray(rng.randn(B, T, H).astype(np.float32))
        ct_h = jnp.asarray(rng.randn(B, H).astype(np.float32))
        ct_c = jnp.asarray(rng.randn(B, H).astype(np.float32))

        pi, pf, po = (
            jnp.asarray(rng.randn(H).astype(np.float32) * 0.3 * has_peepholes)
            for _ in range(3))

        def obj(fn):
            def f(xp, w_h, b, pi, pf, po):
                h_seq, h_f, c_f = fn(xp, b, mask, w_h, z, z, pi, pf, po,
                                     True, has_peepholes)
                return ((h_seq * ct_seq).sum() + (h_f * ct_h).sum()
                        + (c_f * ct_c).sum())
            return f

        # reference: identical function with the scan backward (gate off)
        args = (xp, w_h, b, pi, pf, po)
        _backward_kernel(monkeypatch, False)
        g_ref = jax.grad(obj(lstm_sequence_fused), tuple(range(6)))(*args)
        _backward_kernel(monkeypatch, True)
        g_pal = jax.grad(obj(lstm_sequence_fused), tuple(range(6)))(*args)
        for a, b in zip(g_ref, g_pal):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    def test_gru_fused_grads_with_pallas_bwd(self, rng, monkeypatch):
        from paddle_tpu.ops.rnn_fused import gru_sequence_fused
        B, T, H = 4, 6, 8
        xp, mask, w_h = _data(rng, B=B, T=T, H=H, gates=3)
        z = jnp.zeros((B, H), jnp.float32)
        ct_seq = jnp.asarray(rng.randn(B, T, H).astype(np.float32))
        ct_h = jnp.asarray(rng.randn(B, H).astype(np.float32))

        def obj():
            def f(xp, w_h):
                h_seq, h_f = gru_sequence_fused(xp, mask, w_h, z, True)
                return (h_seq * ct_seq).sum() + (h_f * ct_h).sum()
            return f

        _backward_kernel(monkeypatch, False)
        g_ref = jax.grad(obj(), (0, 1))(xp, w_h)
        _backward_kernel(monkeypatch, True)
        g_pal = jax.grad(obj(), (0, 1))(xp, w_h)
        for a, b in zip(g_ref, g_pal):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


class TestFusedCEReadout:
    """One-pass Pallas logsumexp CE readout (interpret mode) vs the plain
    jnp formulation — value and every gradient."""

    def test_values_and_grads_match_reference(self, rng):
        from paddle_tpu.ops.losses import (_ce_readout_fused,
                                           _readout_logits,
                                           masked_token_mean)
        B, T, D, V = 2, 4, 8, 64
        states = jnp.asarray(rng.randn(B, T, D).astype(np.float32))
        w = jnp.asarray(rng.randn(D, V).astype(np.float32) * 0.2)
        b = jnp.asarray(rng.randn(V).astype(np.float32) * 0.1)
        labels = jnp.asarray(rng.randint(0, V, (B, T)).astype(np.int32))
        mask = jnp.asarray((rng.rand(B, T) > 0.3).astype(np.float32))

        def ref(states, w, b):
            logits = _readout_logits(states, w, b)
            lf = logits.astype(jnp.float32)
            m = jnp.max(lf, -1, keepdims=True)
            lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(lf - m), -1))
            tok = jnp.squeeze(jnp.take_along_axis(
                logits, labels[..., None], axis=-1), -1)
            return masked_token_mean(lse - tok.astype(jnp.float32), mask)

        def fused(states, w, b):
            return _ce_readout_fused(states, w, b, labels, mask)

        from conftest import on_accelerator

        # hardware mode runs the bf16 compute policy: the two formulations
        # agree only to bf16 rounding there, exactly on the f32 CPU policy
        rtol, atol = (0.05, 1e-3) if on_accelerator() else (1e-5, 1e-6)
        val_rtol = 0.05 if on_accelerator() else 1e-6  # exact on f32 CPU
        np.testing.assert_allclose(float(ref(states, w, b)),
                                   float(fused(states, w, b)),
                                   rtol=val_rtol)
        g_ref = jax.grad(ref, (0, 1, 2))(states, w, b)
        g_new = jax.grad(fused, (0, 1, 2))(states, w, b)
        for name, a, c in zip(("states", "w", "b"), g_ref, g_new):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       rtol=rtol, atol=atol, err_msg=name)


def _rnn_gate(backward):
    # the flagship's encoder GRU and the LSTM cell's layers
    return lambda: (
        rnn_fused.rnn_kernel_ok(384, 512, 3, backward=backward)
        and rnn_fused.rnn_kernel_ok(256, 512, 4, backward=backward))


@pytest.mark.parametrize("gate,opens_with", [
    pytest.param(_rnn_gate(False), True, id="rnn_forward"),
    pytest.param(_rnn_gate(True), True, id="rnn_reverse"),
    pytest.param(lambda: attention_decoder._attn_pallas_block(
        384, 96, 512, 512, 1024), 32, id="attention_decoder"),
    pytest.param(lambda: losses._tiled_ce_cfg(384, 32, 512, 30000),
                 (2048, 512), id="tiled_ce"),             # N = 12288 rows
    # no cell generates: the flagship's beam-3 rows, 384 x 3
    pytest.param(lambda: decode.decode_kernel_config(1152, 512, 30000, 3),
                 (128, 512), id="topk_readout"),
    pytest.param(lambda: decoder_block.attention_kernel_blocks(
        8192, 64, 32, 8), (1024, 1024), id="flash_attention"),
    pytest.param(lambda: moe.moe_kernel_row_tile(2048, 1536, 8192 * 4),
                 256, id="grouped_products"),
])
def test_gate_follows_backend_and_mesh(monkeypatch, gate, opens_with):
    """Every kernel family's gate, at the shape its benchmark cell runs: the
    backend and the shape decide.  Closed on the CPU; open on the ``"tpu"``
    backend; closed again while a step that jit partitions over a mesh is
    traced (``xla_paths_only``), also as a decorator, and open after it."""
    from paddle_tpu.ops import pallas_kernels as pk
    from paddle_tpu.utils.flags import FLAGS

    # the cells run the production policy, and the VMEM estimates of the
    # RNN and CE gates count its operand widths
    monkeypatch.setattr(FLAGS, "compute_dtype", "bfloat16")
    if jax.default_backend() != "tpu":
        assert not gate()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pk, "_warned_kernels_off", True)   # keep it quiet
    assert gate() == opens_with
    with pk.xla_paths_only():
        assert not gate()
        with pk.xla_paths_only():                          # nests
            assert not gate()
        assert not gate()
    assert pk.xla_paths_only()(gate)() in (None, False)
    assert gate() == opens_with
