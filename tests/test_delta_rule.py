"""The gated delta rule's chunked scan (ops/delta_rule.py, PR 41) against the
token-by-token recurrence it stands for: values and all five gradients, on
the ``jax.numpy`` path (a ``lax.scan`` over chunks, differentiated by JAX)
and through the Pallas kernels in interpret mode (``gdn_chunk_fwd`` and the
hand-written reverse walk ``gdn_chunk_bwd``), at 1, 2 and 5 chunks, over two
kernel blocks, off the chunk length, and with a decay of ``exp(-30)`` a
token, where anything that divided by a decay would overflow.

Both sides are float32 here and differ by the order of sums and by the
chunk's 64 x 64 solve: 2e-5 of the largest value, 2e-4 of a gradient's norm.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import delta_rule as DR
from paddle_tpu.ops import pallas_kernels as PK

VAL_TOL, GRAD_TOL = 2e-5, 2e-4


def recurrence(q, k, v, g, beta):
    """The definition: one rank-one correction a token."""
    B, T, H, dk = q.shape

    def step(S, x):
        qt, kt, vt, gt, bt = x            # [B, H, d], [B, H]
        S = jnp.exp(gt)[..., None, None] * S
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", S, kt))
        S = S + kt[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    S0 = jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        _, o = jax.lax.scan(step, S0, xs)
    return jnp.moveaxis(o, 0, 1)


def inputs(seed, B, T, H, dk, dv, g_scale=1.0, g_const=None):
    r = np.random.RandomState(seed)
    q = r.randn(B, T, H, dk).astype(np.float32)
    k = r.randn(B, T, H, dk).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * dk ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.randn(B, T, H, dv).astype(np.float32)
    # decays from 0.01 to 0.99 a token, as the cell's seeded weights give
    g = -np.exp(g_scale * r.randn(B, T, H)).astype(np.float32)
    if g_const is not None:
        g = np.full_like(g, g_const)
    beta = (1 / (1 + np.exp(-r.randn(B, T, H)))).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (q, k, v, g, beta))


def kernels_path(q, k, v, g, beta):
    """``delta_rule`` as the TPU runs it: the custom_vjp over the two
    kernels (interpret mode here), with the layout changes around them."""
    B, T, H, dk = q.shape
    N = T // DR.CHUNK
    hm = lambda a: jnp.moveaxis(a, 2, 1)   # noqa: E731
    gamma = jnp.cumsum(hm(g).reshape(B, H, N, DR.CHUNK), axis=-1)
    o = DR._scan_kernels(hm(q), hm(k), hm(v), gamma,
                         hm(beta).reshape(B, H, N, DR.CHUNK))
    return jnp.moveaxis(o, 1, 2)


def loss_and_grads(fn, args, seed=7):
    w = jnp.asarray(np.random.RandomState(seed).randn(
        *args[2].shape).astype(np.float32))
    return jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2, 3, 4))(*args)


def assert_same(got, want, what):
    (o_got, g_got), (o_want, g_want) = got, want
    np.testing.assert_allclose(o_got, o_want, rtol=1e-5, err_msg=what)
    for name, a, b in zip("q k v g beta".split(), g_got, g_want):
        assert np.all(np.isfinite(a)), (what, name)
        gap = np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)
        assert gap < GRAD_TOL, (what, name, gap)


@pytest.mark.parametrize("chunks", [1, 2, 5])
def test_chunked_scan_matches_the_recurrence(chunks):
    args = inputs(chunks, 2, chunks * DR.CHUNK, 3, 16, 24)
    o, want = DR.delta_rule(*args), recurrence(*args)
    assert np.max(np.abs(o - want)) < VAL_TOL * np.max(np.abs(want))
    assert_same(loss_and_grads(DR.delta_rule, args),
                loss_and_grads(recurrence, args), f"{chunks} chunks")


def test_row_off_the_chunk_length_is_padded():
    args = inputs(11, 1, 100, 2, 16, 16)
    o, want = DR.delta_rule(*args), recurrence(*args)
    assert o.shape == want.shape
    assert np.max(np.abs(o - want)) < VAL_TOL * np.max(np.abs(want))
    assert_same(loss_and_grads(DR.delta_rule, args),
                loss_and_grads(recurrence, args), "T = 100")


@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_decay_of_exp_minus_30_a_token_stays_finite(path):
    """``exp(-cumsum(g))`` would be ``exp(1920)`` at a chunk's end."""
    args = inputs(5, 1, 2 * DR.CHUNK, 2, 16, 16, g_const=-30.0)
    fn = DR.delta_rule if path == "xla" else kernels_path
    got, want = loss_and_grads(fn, args), loss_and_grads(recurrence, args)
    assert np.isfinite(got[0])
    for name, a, b in zip("q k v g beta".split(), got[1], want[1]):
        assert np.all(np.isfinite(a)), name
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-3, err_msg=name)


@pytest.mark.parametrize("chunks", [1, 2, 5, 16])
def test_kernels_in_interpret_mode_match_the_xla_path(chunks):
    """16 chunks are two grid steps of 8: the state (and, in reverse, its
    gradient) crosses from one block of chunks to the next in scratch."""
    args = inputs(20 + chunks, 1, chunks * DR.CHUNK, 2, 16, 24)
    o, want = kernels_path(*args), DR.delta_rule(*args)
    assert np.max(np.abs(o - want)) < VAL_TOL * np.max(np.abs(want))
    assert_same(loss_and_grads(kernels_path, args),
                loss_and_grads(DR.delta_rule, args), f"{chunks} chunks")


def test_kernels_match_the_recurrence():
    args = inputs(3, 2, 3 * DR.CHUNK, 2, 16, 16)
    assert_same(loss_and_grads(kernels_path, args),
                loss_and_grads(recurrence, args), "kernels")


def test_forward_kernel_writes_every_chunks_starting_state():
    q, k, v, g, beta = inputs(9, 1, 3 * DR.CHUNK, 2, 16, 16)
    hm = lambda a: jnp.moveaxis(a, 2, 1)   # noqa: E731
    gamma = jnp.cumsum(hm(g).reshape(1, 2, 3, DR.CHUNK), axis=-1)
    _, states = PK.gdn_chunk_fwd_pallas(
        hm(q), hm(k), hm(v), gamma, hm(beta).reshape(1, 2, 3, DR.CHUNK))
    assert states.shape == (1, 2, 3, 16, 16)
    assert not np.any(states[:, :, 0])

    def state_after(t):      # the recurrence's state after t tokens
        S = np.zeros((2, 16, 16), np.float64)
        for i in range(t):
            S = np.exp(g[0, i])[:, None, None] * S
            u = beta[0, i][:, None] * (v[0, i] - np.einsum(
                "hkv,hk->hv", S, k[0, i]))
            S = S + np.asarray(k[0, i])[:, :, None] * u[:, None, :]
        return S

    np.testing.assert_allclose(states[0, :, 2], state_after(2 * DR.CHUNK),
                               atol=2e-5)


def test_gate_is_a_function_of_backend_and_shape(monkeypatch):
    assert DR.delta_rule_kernel_chunk(8192, 128, 128) is None     # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert DR.delta_rule_kernel_chunk(8192, 128, 128) == DR.CHUNK
    assert DR.delta_rule_kernel_chunk(8192, 64, 128) is None
    assert DR.delta_rule_kernel_chunk(8192, 128, 192) is None
    assert DR.delta_rule_kernel_chunk(8200, 128, 128) is None
    with PK.xla_paths_only():
        assert DR.delta_rule_kernel_chunk(8192, 128, 128) is None
