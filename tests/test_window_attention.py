"""Window attention and the scaled rotary embedding (PR 50):
``ops.causal_attention(window=)`` on its XLA walk and on the two window
kernels in interpret mode against a dense masked softmax written here,
forward and backward; the band's rule of the gate and the kernels' grids;
YaRN's frequencies at Laguna-XS.2's published numbers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import decoder_block as DB
from paddle_tpu.ops import pallas_kernels as PK

B, T, H, HKV, DH = 1, 1024, 4, 2, 16


def dense(q, k, v, scale, window=None):
    """Softmax over the positions a query sees, every ``[T, T]`` score made:
    ``s <= t``, and ``s > t - window`` under a window."""
    G = q.shape[2] // k.shape[2]
    kk, vv = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, kk) * scale
    t = jnp.arange(q.shape[1])[:, None]
    c = jnp.arange(q.shape[1])[None, :]
    seen = c <= t
    if window is not None:
        seen = seen & (c > t - window)
    a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
    return jnp.einsum("bhts,bshd->bthd", a, vv)


def operands(seed=0, t=T):
    key = jax.random.PRNGKey(seed)
    shapes = [(B, t, H, DH), (B, t, HKV, DH), (B, t, HKV, DH), (B, t, H, DH)]
    return [jax.random.normal(jax.random.fold_in(key, i), s)
            for i, s in enumerate(shapes)]


def rel(got, want):
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


@pytest.fixture
def kernels(monkeypatch):
    """The gate opens with blocks of 128 (no wider than the window, as its
    band's rule has it, but 128 at least) and the kernels run in interpret
    mode."""
    monkeypatch.setattr(
        DB, "attention_kernel_blocks",
        lambda T, dh, H, Hkv, dv=None, window=None: (128, 128))


def both(window, q, k, v, w):
    scale = DH ** -0.5
    got = jax.value_and_grad(lambda q, k, v: (DB.causal_attention(
        q, k, v, scale=scale, window=window) * w).sum(), (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(lambda q, k, v: (dense(
        q, k, v, scale, window) * w).sum(), (0, 1, 2))(q, k, v)
    return got, want


# smaller than, equal to and larger than a block (512 queries on the XLA
# walk, 128 on the kernels)
@pytest.mark.parametrize("window", [100, 512, 700])
def test_xla_walk_matches_a_dense_masked_softmax(window):
    q, k, v, w = operands()
    (loss, grads), (want_loss, want_grads) = both(window, q, k, v, w)
    assert abs(float(loss - want_loss)) <= 1e-4 * abs(float(want_loss)) + 1e-3
    for g, g0 in zip(grads, want_grads):
        assert rel(g, g0) <= 1e-5


@pytest.mark.parametrize("window", [100, 128, 300])
def test_window_kernels_match_a_dense_masked_softmax(window, kernels):
    q, k, v, w = operands(1)
    (loss, grads), (want_loss, want_grads) = both(window, q, k, v, w)
    assert abs(float(loss - want_loss)) <= 1e-4 * abs(float(want_loss)) + 1e-3
    for g, g0 in zip(grads, want_grads):
        assert rel(g, g0) <= 1e-5


@pytest.mark.parametrize("window", [T, T + 1, 10 * T])
def test_a_window_no_shorter_than_the_row_is_the_unwindowed_op(window):
    """Bit for bit, forward and backward."""
    q, k, v, w = operands(2, t=640)
    run = lambda win: jax.value_and_grad(  # noqa: E731
        lambda q, k, v: (DB.causal_attention(
            q, k, v, scale=0.25, window=win) * w).sum(), (0, 1, 2))(q, k, v)
    (loss, grads), (want_loss, want_grads) = run(window), run(None)
    assert float(loss) == float(want_loss)
    for g, g0 in zip(grads, want_grads):
        assert bool(jnp.all(g == g0))


def test_a_position_outside_the_window_contributes_exactly_nothing():
    """The gradient into a key and a value that no query sees through the
    window of the LAST queries alone is exactly zero."""
    q, k, v, w = operands(3)
    w = w.at[:, :-64].set(0.0)          # only the last 64 queries count
    grads = jax.grad(lambda k, v: (DB.causal_attention(
        q, k, v, scale=0.25, window=100) * w).sum(), (0, 1))(k, v)
    first_seen = T - 64 - 99
    for g in grads:
        assert not bool(jnp.any(g[:, :first_seen]))
        assert bool(jnp.any(g[:, first_seen]))


def test_causal_attention_refuses_an_empty_window():
    q, k, v, _ = operands()
    with pytest.raises(ValueError, match="window"):
        DB.causal_attention(q, k, v, scale=1.0, window=0)


def _grids(fn, *args):
    """kernel name -> grid of every ``pallas_call`` in ``fn``'s jaxpr."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = tuple(
                    eqn.params["grid_mapping"].grid)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def test_window_kernels_grids_hold_the_bands_blocks_alone(kernels):
    """The key axis of both grids is the band of a block of queries: 2 blocks
    of 128 under a window of 100 and 4 under 300, where the unwindowed
    kernels walk all 8."""
    q, k, v, w = operands()

    def step(window):
        return lambda q, k, v: jax.grad(lambda q, k, v: (DB.causal_attention(
            q, k, v, scale=0.25, window=window) * w).sum(), (0, 1, 2))(q, k, v)

    nq, G = T // 128, H // HKV
    assert _grids(step(100), q, k, v) == {
        "flash_attn_win_fwd": (B, H, nq, 2),
        "flash_attn_win_bwd": (B, HKV, G * nq, 2)}
    assert _grids(step(300), q, k, v) == {
        "flash_attn_win_fwd": (B, H, nq, 4),
        "flash_attn_win_bwd": (B, HKV, G * nq, 4)}
    assert _grids(step(None), q, k, v) == {
        "flash_attn_fwd": (B, H, nq, nq),
        "flash_attn_bwd": (B, HKV, G * nq, nq)}


def test_band_blocks_at_the_cells_shapes():
    assert PK.flash_band_blocks(16384, 512, 512, 512) == 2
    assert PK.flash_band_blocks(16384, 1024, 1024, 512) == 2
    assert PK.flash_band_blocks(16384, 256, 256, 512) == 3
    assert PK.flash_band_blocks(16384, 128, 128, 512) == 5
    assert PK.flash_band_blocks(16384, 512, 512, 513) == 2
    assert PK.flash_band_blocks(16384, 512, 512, 514) == 3
    assert PK.flash_band_blocks(1024, 512, 512, 1) == 1


def test_gates_band_rule(monkeypatch):
    """On the TPU backend: blocks no wider than the window (128 at least);
    a windowed row too long for the backward to keep whole has no kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    gate = DB.attention_kernel_blocks
    assert gate(16384, 128, 64, 8) == (1024, 1024)
    assert gate(16384, 128, 64, 8, window=512) == (512, 512)
    assert gate(16384, 128, 64, 8, window=4096) == (1024, 1024)
    assert gate(16384, 128, 64, 8, window=300) == (256, 256)
    assert gate(16384, 128, 64, 8, window=64) == (128, 128)
    assert gate(16384 + 8, 128, 64, 8, window=512) is None
    assert PK.flash_bwd_key_rows(131072, 128, 128, 512, 512) < 131072
    assert gate(131072, 128, 64, 8) == (1024, 1024)
    assert gate(131072, 128, 64, 8, window=512) is None
    with pytest.raises(ValueError, match="super-blocks"):
        z = jnp.zeros((1, 1, 131072, 128), jnp.bfloat16)
        jax.eval_shape(lambda: PK.flash_attn_bwd_pallas(
            z, z, z, z, z[..., :1].astype(jnp.float32), z, scale=1.0,
            block_q=512, block_k=512, window=512))


# -- the rotary embedding under YaRN -----------------------------------------

#: Laguna-XS.2's ``rope_parameters.full_attention`` (its config.json)
YARN = dict(rope_theta=500000, factor=64,
            original_max_position_embeddings=4096, beta_slow=1, beta_fast=64,
            attention_factor=1.4158883083359672)


def test_yarn_frequencies_at_the_published_numbers():
    """Over 64 channels (half a head of 128): pairs 0-5 turn as the
    published theta turns them, 16-31 are slowed 64 times, a ramp of
    elevenths between; cos and sin are scaled by the config's factor, which
    is ``0.1 ln 64 + 1``."""
    inv, factor = DB.yarn_frequencies(64, **YARN)
    plain = 500000.0 ** (-np.arange(32) * 2 / 64)
    assert inv.shape == (32,) and inv.dtype == np.float32
    assert inv[0] == 1.0
    np.testing.assert_allclose(inv[:6], plain[:6], rtol=1e-6)
    np.testing.assert_allclose(inv[16:], plain[16:] / 64, rtol=1e-6)
    np.testing.assert_allclose(inv[31], 500000.0 ** (-62 / 64) / 64,
                               rtol=1e-6)
    share = (np.arange(6, 16) - 5) / 11.0        # low 5, high 16
    np.testing.assert_allclose(
        inv[6:16], plain[6:16] * (1 - share) + plain[6:16] / 64 * share,
        rtol=1e-6)
    assert factor == 1.4158883083359672
    assert DB.yarn_frequencies(64, **{**YARN, "attention_factor": None})[1] \
        == pytest.approx(0.1 * np.log(64) + 1, rel=1e-12)


def test_rotary_embedding_takes_frequencies_and_a_factor():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 2, 16))
    inv = np.asarray([1.0, 0.3, 0.05, 0.002], np.float32)
    got = DB.rotary_embedding(x, 1e4, 8, inv_freq=inv, factor=1.5)
    ang = np.arange(40)[:, None] * inv[None, :]
    cos, sin = (np.cos(ang) * 1.5)[None, :, None], \
        (np.sin(ang) * 1.5)[None, :, None]
    x1, x2 = np.asarray(x[..., :4]), np.asarray(x[..., 4:8])
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                           np.asarray(x[..., 8:])], -1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the defaults are the plain embedding, bit for bit
    plain = 1e4 ** (-np.arange(0, 8, 2, dtype=np.float32) / 8)
    assert bool(jnp.all(DB.rotary_embedding(x, 1e4, 8)
                        == DB.rotary_embedding(x, 1e4, 8, inv_freq=None,
                                               factor=1.0)))
    np.testing.assert_allclose(
        DB.rotary_embedding(x, 1e4, 8),
        DB.rotary_embedding(x, 0.0, 8, inv_freq=plain), rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="frequencies"):
        DB.rotary_embedding(x, 1e4, 8, inv_freq=inv[:3])
