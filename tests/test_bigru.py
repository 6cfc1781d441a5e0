"""Fused bidirectional GRU (one time loop for both directions) vs two
``gru_layer`` calls — values, final state, and every gradient, including
ragged masks (the flip trick must be exact for right-padded batches)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu.ops as O
from paddle_tpu.ops import rnn_fused
from paddle_tpu.ops.pallas_kernels import pallas_available

pytestmark = pytest.mark.skipif(not pallas_available(),
                                reason="pallas unavailable")


def _args(rng, B=4, T=6, E=8, H=8):
    x = jnp.asarray(rng.randn(B, T, E).astype(np.float32) * 0.3)
    lens = jnp.asarray(np.array([T, 3, 5, 1], np.int32)[:B])
    mask = O.mask_from_lengths(lens, T)
    def w(shape, s=0.2):
        return jnp.asarray(rng.randn(*shape).astype(np.float32) * s)
    return (x, mask, w((E, 3 * H)), w((H, 3 * H)), jnp.zeros((3 * H,)),
            w((E, 3 * H)), w((H, 3 * H)), jnp.zeros((3 * H,)))


def _force_fused(monkeypatch):
    monkeypatch.setattr(rnn_fused, "_use_pallas_bigru", lambda B, H: True)


def test_fused_matches_two_calls(monkeypatch, rng):
    args = _args(rng)
    ref = O.bigru_layer(*args)  # gate off on CPU -> two gru_layer calls
    _force_fused(monkeypatch)
    got = O.bigru_layer(*args)  # fused core through the interpreter
    for a, b, nm in zip(ref, got, ("h_fw", "h_bw", "h_bw_fin")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6, err_msg=nm)


def test_fused_gradients_match(monkeypatch, rng):
    args = _args(rng)
    ct = jnp.asarray(rng.randn(4, 6, 8).astype(np.float32))

    def loss(x, wxf, whf, wxb, whb):
        h_fw, h_bw, h_fin = O.bigru_layer(x, args[1], wxf, whf, args[4],
                                          wxb, whb, args[7])
        return (jnp.sum(h_fw * ct) + jnp.sum(h_bw * ct * 0.5)
                + jnp.sum(h_fin ** 2))

    dv = (args[0], args[2], args[3], args[5], args[6])
    g_ref = jax.grad(loss, argnums=tuple(range(5)))(*dv)
    _force_fused(monkeypatch)
    g_new = jax.grad(loss, argnums=tuple(range(5)))(*dv)
    for a, b, nm in zip(g_ref, g_new, ("x", "wx_fw", "wh_fw", "wx_bw",
                                       "wh_bw")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6, err_msg=nm)


def test_gate_respects_backend_and_shapes():
    assert not rnn_fused._use_pallas_bigru(4, 100)  # lane-misaligned H
    import jax as _jax

    if _jax.default_backend() != "tpu":
        assert not rnn_fused._use_pallas_bigru(384, 512)
