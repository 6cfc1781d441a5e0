"""Vocab-tiled fused readout+CE Pallas kernels (interpret mode on CPU) vs
the XLA path of ops/losses.sequence_softmax_ce_readout — loss and all three
gradients, including a vocab that does NOT divide the tile (padding with
-1e30 bias must keep statistics and gradients exact) and masked rows."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops import losses as L
from paddle_tpu.ops.pallas_kernels import pallas_available

pytestmark = pytest.mark.skipif(not pallas_available(),
                                reason="pallas unavailable")


def _data(rng, B=4, T=6, D=128, V=300, lens=(6, 4, 5, 2)):
    states = jnp.asarray(rng.randn(B, T, D).astype(np.float32) * 0.3)
    w = jnp.asarray(rng.randn(D, V).astype(np.float32) * 0.1)
    b = jnp.asarray(rng.randn(V).astype(np.float32) * 0.1)
    labels = jnp.asarray(rng.randint(0, V, (B, T)).astype(np.int32))
    mask = jnp.asarray((np.arange(T)[None]
                        < np.asarray(lens)[:, None]).astype(np.float32))
    return states, w, b, labels, mask


@pytest.mark.parametrize("V", [300, 256])  # non-divisible and exact tiles
def test_tiled_ce_matches_xla_path(monkeypatch, rng, V):
    states, w, b, labels, mask = _data(rng, V=V)

    def loss(states, w, b):
        return L.sequence_softmax_ce_readout(states, w, b, labels, mask)

    l_ref, g_ref = jax.value_and_grad(loss, argnums=(0, 1, 2))(states, w, b)
    monkeypatch.setattr(L, "_tiled_ce_cfg", lambda B, T, D, V: (8, 128))
    l_t, g_t = jax.value_and_grad(loss, argnums=(0, 1, 2))(states, w, b)
    np.testing.assert_allclose(float(l_ref), float(l_t), rtol=1e-6)
    for a, c, nm in zip(g_ref, g_t, ("d_states", "d_w", "d_b")):
        a = np.asarray(a, np.float64)
        c = np.asarray(c, np.float64)
        scale = np.abs(a).max() + 1e-12
        np.testing.assert_allclose(a / scale, c / scale, atol=2e-6,
                                   err_msg=nm)


def test_tiled_ce_bf16_operands(monkeypatch, rng):
    """bf16 compute policy (the production path): tiled vs XLA stay within
    bf16 rounding of each other."""
    monkeypatch.setenv("PADDLE_TPU_COMPUTE_DTYPE", "bfloat16")
    from paddle_tpu.utils.flags import FLAGS

    monkeypatch.setattr(FLAGS, "compute_dtype", "bfloat16")
    states, w, b, labels, mask = _data(rng)

    def loss(states, w, b):
        return L.sequence_softmax_ce_readout(states, w, b, labels, mask)

    l_ref, g_ref = jax.value_and_grad(loss, argnums=(0, 1, 2))(states, w, b)
    monkeypatch.setattr(L, "_tiled_ce_cfg", lambda B, T, D, V: (8, 128))
    l_t, g_t = jax.value_and_grad(loss, argnums=(0, 1, 2))(states, w, b)
    assert abs(float(l_ref) - float(l_t)) / abs(float(l_ref)) < 2e-2
    for a, c, nm in zip(g_ref, g_t, ("d_states", "d_w", "d_b")):
        a = np.asarray(a, np.float64)
        c = np.asarray(c, np.float64)
        scale = np.abs(a).max() + 1e-12
        np.testing.assert_allclose(a / scale, c / scale, atol=3e-2,
                                   err_msg=nm)


def test_gate_rejects_cpu_and_bad_shapes():
    import jax as _jax

    if _jax.default_backend() != "tpu":
        assert L._tiled_ce_cfg(4, 8, 128, 300) is None  # CPU backend
    # lane-misaligned D can never tile
    assert L._tiled_ce_cfg(4, 8, 100, 300) is None


def test_lse_readout_falls_back_below_sublane(monkeypatch, rng):
    """ADVICE r5 / ops/losses.py:140 regression: when gcd(B*T, 64) < 8 the
    row tile would drop below the (8, 128) sublane — the recorded-A/B lse
    kernel must NOT be called (the XLA reduction takes over) and the
    numerics must match the default XLA path exactly.  B*T odd forces
    gcd == 1."""
    from paddle_tpu.ops import pallas_kernels as pk

    def boom(*a, **k):
        raise AssertionError("pallas lse called with a sub-sublane tile")

    monkeypatch.setattr(pk, "logsumexp_rows_pallas", boom)
    B, T, D, Vv = 3, 3, 16, 50  # B*T = 9 (odd): gcd(9, 64) == 1
    states = jnp.asarray(rng.randn(B, T, D).astype(np.float32) * 0.3)
    w = jnp.asarray(rng.randn(D, Vv).astype(np.float32) * 0.1)
    b = jnp.asarray(rng.randn(Vv).astype(np.float32) * 0.1)
    labels = jnp.asarray(rng.randint(0, Vv, (B, T)).astype(np.int32))
    mask = jnp.asarray((np.arange(T)[None] < np.array([3, 1, 2])[:, None])
                       .astype(np.float32))

    def fused(states, w, b):
        return L._ce_readout_fused(states, w, b, labels, mask)

    def ref(states, w, b):  # the default XLA branch
        return L.sequence_softmax_ce_readout(states, w, b, labels, mask)

    monkeypatch.setattr(L, "_tiled_ce_cfg", lambda *a: None)
    l_f, g_f = jax.value_and_grad(fused, argnums=(0, 1, 2))(states, w, b)
    l_r, g_r = jax.value_and_grad(ref, argnums=(0, 1, 2))(states, w, b)
    np.testing.assert_allclose(float(l_f), float(l_r), rtol=1e-6)
    for a, c, nm in zip(g_r, g_f, ("d_states", "d_w", "d_b")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-5, atol=1e-6, err_msg=nm)


def test_lse_readout_uses_kernel_when_sublane_aligned(monkeypatch, rng):
    from paddle_tpu.ops import pallas_kernels as pk

    calls = []
    orig = pk.logsumexp_rows_pallas

    def spy(*a, **k):
        calls.append(k.get("row_tile"))
        return orig(*a, **k)

    monkeypatch.setattr(pk, "logsumexp_rows_pallas", spy)
    B, T, D, Vv = 2, 4, 16, 50  # B*T = 8: gcd(8, 64) == 8, kernel stays
    states = jnp.asarray(rng.randn(B, T, D).astype(np.float32) * 0.3)
    w = jnp.asarray(rng.randn(D, Vv).astype(np.float32) * 0.1)
    b = jnp.zeros((Vv,), jnp.float32)
    labels = jnp.asarray(rng.randint(0, Vv, (B, T)).astype(np.int32))
    mask = jnp.ones((B, T), jnp.float32)
    loss = L._ce_readout_fused(states, w, b, labels, mask)
    assert calls == [8]
    assert np.isfinite(float(loss))
