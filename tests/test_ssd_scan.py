"""The state-space (SSD) recurrence's chunked scan (ops/ssd_scan.py, PR 43)
against the token-by-token recurrence it stands for: values and all five
gradients, on the ``jax.numpy`` path (a ``lax.scan`` over chunks,
differentiated by JAX) and through the Pallas kernels in interpret mode
(``ssd_chunk_fwd`` and the hand-written reverse walk ``ssd_chunk_bwd``), at
1, 2 and 5 chunks, over two kernel blocks, off the chunk length, at heads of
64 (two a lane tile, the cell's) and of 16, and with a decay of ``exp(-30)``
a token, where anything that divided by a decay would overflow.

Both sides are float32 here and differ by the order of sums only: 2e-5 of
the largest value, 2e-4 of a gradient's norm.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas_kernels as PK
from paddle_tpu.ops import ssd_scan as SS

VAL_TOL, GRAD_TOL = 2e-5, 2e-4
Q = SS.CHUNK


def recurrence(x, Bm, Cm, dt, A):
    """The definition: one decay and one outer product a token."""
    B, T, H, P = x.shape
    G = Bm.shape[2]
    Bh, Ch = (jnp.repeat(a, H // G, axis=2) for a in (Bm, Cm))

    def step(S, c):
        xt, bt, ct, dtt = c               # [B, H, .], dt [B, H]
        S = (jnp.exp(dtt * A)[..., None, None] * S
             + (dtt[..., None] * xt)[..., :, None] * bt[..., None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, ct)

    cs = tuple(jnp.moveaxis(a, 1, 0) for a in (x, Bh, Ch, dt))
    S0 = jnp.zeros((B, H, P, Bm.shape[-1]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        _, y = jax.lax.scan(step, S0, cs)
    return jnp.moveaxis(y, 0, 1)


def inputs(seed, B, T, H, P, G, N, a_const=None):
    r = np.random.RandomState(seed)
    x = r.randn(B, T, H, P).astype(np.float32)
    Bm = (0.3 * r.randn(B, T, G, N)).astype(np.float32)
    Cm = (0.3 * r.randn(B, T, G, N)).astype(np.float32)
    # steps of 0.03 to 3 and A of -0.1 to -10: decays from 0.01 to 0.99 a
    # token and beyond, as the cell's seeded weights give
    dt = np.log1p(np.exp(1.4 * r.randn(B, T, H))).astype(np.float32)
    A = -np.exp(r.randn(H)).astype(np.float32)
    if a_const is not None:
        dt, A = np.ones_like(dt), np.full_like(A, a_const)
    return tuple(jnp.asarray(a) for a in (x, Bm, Cm, dt, A))


def kernels_path(*args):
    """``ssd_scan`` as the TPU runs it: the custom_vjp over the two kernels
    (interpret mode here), with the layout changes around them."""
    gate = SS.ssd_kernel_chunk
    SS.ssd_kernel_chunk = lambda *a: SS.CHUNK
    try:
        return SS.ssd_scan(*args)
    finally:
        SS.ssd_kernel_chunk = gate


def rel(got, want):
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


def check(fn, args, seed=0):
    w = jnp.asarray(np.random.RandomState(seed + 100).randn(
        *args[0].shape).astype(np.float32))
    want = recurrence(*args)
    got = fn(*args)
    assert float(jnp.abs(got - want).max()) <= VAL_TOL * float(
        jnp.abs(want).max())
    loss = lambda f: (lambda *a: jnp.sum(f(*a) * w))  # noqa: E731
    g_want = jax.grad(loss(recurrence), argnums=(0, 1, 2, 3, 4))(*args)
    g_got = jax.grad(loss(fn), argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, gw in zip(("x", "B", "C", "dt", "A"), g_got, g_want):
        assert float(jnp.linalg.norm(gw)) > 0, name
        assert rel(g, gw) <= GRAD_TOL, name


@pytest.mark.parametrize("chunks", [1, 2, 5])
def test_chunked_scan_matches_the_recurrence(chunks):
    check(SS.ssd_scan, inputs(chunks, 2, chunks * Q, 4, 16, 2, 32), chunks)


def test_row_off_the_chunk_length_is_padded():
    """A row the chunk does not divide is PADDED, not refused: a padded token
    has ``dt = 0``, leaves the state as it is, and its output is cut off."""
    args = inputs(7, 2, Q + 37, 4, 16, 2, 32)
    check(SS.ssd_scan, args)
    assert SS.ssd_scan(*args).shape == (2, Q + 37, 4, 16)
    check(kernels_path, args)


def test_memory_is_carried_across_chunks():
    """With decays near 1, moving the first token's input moves the last
    token's output, three chunk boundaries later."""
    x, Bm, Cm, dt, A = inputs(3, 1, 4 * Q, 2, 16, 1, 32)
    A = jnp.full_like(A, -1e-3)
    moved = x.at[:, 0].add(1.0)
    for fn in (SS.ssd_scan, kernels_path):
        a, b = fn(x, Bm, Cm, dt, A), fn(moved, Bm, Cm, dt, A)
        assert rel(a[:, -1], b[:, -1]) > 1e-3
        assert rel(b, recurrence(moved, Bm, Cm, dt, A)) <= 1e-5


@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_decay_of_exp_minus_30_a_token_stays_finite(path):
    args = inputs(5, 1, 2 * Q, 2, 16, 1, 32, a_const=-30.0)
    fn = SS.ssd_scan if path == "xla" else kernels_path
    out, grads = jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a)), argnums=(0, 1, 2, 3, 4))(*args)
    assert np.isfinite(float(out))
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)
    np.testing.assert_allclose(fn(*args), recurrence(*args), atol=1e-5)


@pytest.mark.parametrize("heads,width,chunks", [
    (4, 64, 2),      # the cell's heads: two a lane tile
    (4, 16, 1), (4, 16, 5), (2, 128, 2)])
def test_kernels_in_interpret_mode_match_the_xla_path(heads, width, chunks):
    args = inputs(chunks, 1, chunks * Q, heads, width, 2, 128)
    w = jnp.asarray(np.random.RandomState(9).randn(
        *args[0].shape).astype(np.float32))
    a, ga = jax.value_and_grad(
        lambda *z: jnp.sum(SS.ssd_scan(*z) * w), argnums=(0, 1, 2, 3, 4))(
            *args)
    b, gb = jax.value_and_grad(
        lambda *z: jnp.sum(kernels_path(*z) * w), argnums=(0, 1, 2, 3, 4))(
            *args)
    assert float(a) == pytest.approx(float(b), rel=1e-5, abs=1e-4)
    for g, gw in zip(gb, ga):
        assert rel(g, gw) <= 2e-5


def test_kernels_match_the_recurrence_over_two_blocks():
    """Six chunks are padded to two kernel blocks of four: the state, and in
    reverse its gradient, crosses the grid's sequential axis."""
    assert SS.KERNEL_BLOCK_CHUNKS == 4
    check(kernels_path, inputs(2, 1, 6 * Q, 4, 64, 2, 128))


def test_forward_kernel_writes_every_chunks_starting_state():
    x, Bm, Cm, dt, A = inputs(4, 1, 3 * Q, 4, 16, 2, 32)
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    dtc = SS._chunked_scalars(dt, G, 3)
    a = jnp.cumsum(SS._chunked_scalars(dt * A, G, 3), axis=-1)
    _, states = PK.ssd_chunk_fwd_pallas(
        x.reshape(B, T, -1), Bm.reshape(B, T, -1), Cm.reshape(B, T, -1), a,
        dtc)
    assert states.shape == (1, G, 3, N, H // G * P)
    assert not np.asarray(states[:, :, 0]).any()     # zero at the row's start
    # chunk 1 starts where the recurrence stands after Q tokens
    Bh = jnp.repeat(Bm, H // G, axis=2)
    S = jnp.zeros((H, P, N))
    for t in range(Q):
        S = (jnp.exp(dt[0, t] * A)[:, None, None] * S
             + (dt[0, t, :, None] * x[0, t])[:, :, None] * Bh[0, t][:, None, :])
    want = jnp.transpose(S.reshape(G, H // G, P, N), (0, 3, 1, 2)).reshape(
        G, N, -1)
    np.testing.assert_allclose(states[0, :, 1], want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("heads,width,chunks", [(4, 64, 2), (2, 128, 5)])
def test_B_and_C_as_blocks_of_one_array_equal_three_arrays(heads, width,
                                                           chunks):
    """``Cm`` ``None``: the kernels read B and C as column blocks of ONE ``[B,
    T, 2 G N]`` array (what ``mamba_prep_fwd`` writes beside x) and give
    what they give on three arrays: the forward's output and states to the
    bit, the reverse walk's five gradients to the last digits (two programs
    of the interpreter's, whose sums XLA:CPU may order differently), ``dx``,
    ``dB``, ``dC`` as three arrays either way."""
    x, Bm, Cm, dt, A = inputs(chunks, 2, chunks * Q, heads, width, 2, 128)
    B, T = x.shape[:2]
    G = Bm.shape[2]
    n = -(-chunks // SS.KERNEL_BLOCK_CHUNKS) * SS.KERNEL_BLOCK_CHUNKS \
        if chunks > SS.KERNEL_BLOCK_CHUNKS else chunks
    pad = ((0, 0), (0, n * Q - T), (0, 0))
    three = tuple(jnp.pad(a.reshape(B, T, -1), pad) for a in (x, Bm, Cm))
    a, dtc = SS._chunk_sums(jnp.pad(dt, pad), A, G, n)
    two = three[0], jnp.concatenate(three[1:], axis=-1), None
    dy = jnp.asarray(np.random.RandomState(11).randn(
        *three[0].shape).astype(np.float32))
    want = PK.ssd_chunk_fwd_pallas(*three, a, dtc)
    got = PK.ssd_chunk_fwd_pallas(*two, a, dtc)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    want = PK.ssd_chunk_bwd_pallas(*three, a, dtc, want[1], dy)
    got = PK.ssd_chunk_bwd_pallas(*two, a, dtc, got[1], dy)
    assert [g.shape for g in got[:3]] == [t.shape for t in three]
    for g, w in zip(got, want):
        assert np.asarray(w).any()
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="are not"):
        PK.ssd_chunk_fwd_pallas(two[0], two[1][..., :-1], None, a, dtc)


def test_heads_that_are_not_whole_groups_are_refused():
    x, Bm, Cm, dt, A = inputs(1, 1, Q, 3, 16, 2, 32)
    with pytest.raises(ValueError, match="whole groups"):
        SS.ssd_scan(x, Bm, Cm, dt, A)


def test_gate_is_a_function_of_backend_and_shape(monkeypatch):
    assert SS.ssd_kernel_chunk(4096, 64, 8, 128) is None      # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert SS.ssd_kernel_chunk(4096, 64, 8, 128) == 128       # the cell's
    assert SS.ssd_kernel_chunk(4096, 128, 4, 128) == 128
    assert SS.ssd_kernel_chunk(4096 + 64, 64, 8, 128) is None  # not chunks
    assert SS.ssd_kernel_chunk(4096, 48, 8, 128) is None   # heads off a tile
    assert SS.ssd_kernel_chunk(4096, 64, 3, 128) is None   # a group of 192
    assert SS.ssd_kernel_chunk(4096, 64, 8, 64) is None    # a narrow state
    with PK.xla_paths_only():
        assert SS.ssd_kernel_chunk(4096, 64, 8, 128) is None
