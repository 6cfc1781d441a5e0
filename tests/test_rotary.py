"""The rotary embedding as one pass over whole heads (PR 51).

``ops.decoder_block.rotary_embedding`` against the form it had before,
written out here (slice the span off, negate and concatenate its halves in
float32, concatenate the rest back) so that the old function's behaviour
stays pinned after its code is gone: over the spans the five decoder models
use, for float32 and bfloat16 inputs, forward, ``jax.vjp`` and ``jax.grad``
through ``jax.checkpoint`` (the recomputed pass).  Both the XLA form (the
CPU's) and the kernel ``rotary_turn`` in interpret mode, in both of its
layouts.

Run op by op the new form gives the old one's float32 results and gradients
to the last bit.  Inside one compiled program XLA:CPU contracts a multiply
and an add into a fused multiply-add where it sees fit, differently for the
two expressions, so what is compiled (the interpreted kernel, a
``jax.checkpoint``'s body) is held to two float32 roundings of the
reference, not to its bits; on the chip, which has no fused multiply-add,
the kernel read equal to the old form bit for bit (PERF.md section 6, PR 51).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import decoder_block as DB
from paddle_tpu.ops import pallas_kernels as PK

T = 64
YARN_INV, YARN_FACTOR = DB.yarn_frequencies(
    64, rope_theta=500000.0, factor=32.0,
    original_max_position_embeddings=4096, beta_fast=64.0, beta_slow=1.0)

#: (heads, head width, span, inv_freq, factor): Laguna-XS.2's window layers
#: and Keye-VL-2.0's main heads; Laguna-XS.2's full layers under YaRN;
#: Qwen3-Next; LFM2 and the indexer's lone key head; Kanana-2's queries
SPANS = {
    "whole128": (4, 128, (0, 128), None, 1.0),
    "yarn64of128": (3, 128, (0, 64), YARN_INV, YARN_FACTOR),
    "first64of256": (2, 256, (0, 64), None, 1.0),
    "whole64_one_head": (1, 64, (0, 64), None, 1.0),
    "last64of192": (4, 192, (128, 192), None, 1.0),
}
CASES = [pytest.param(name, dtype, id=f"{name}-{dtype}")
         for name in SPANS for dtype in ("float32", "bfloat16")]
THETA = 1e6


def reference(x, span, inv_freq=None, factor=1.0):
    """The function as it was before PR 51, on the span's channels."""
    a, b = span
    part = x[..., a:b]
    rd = b - a
    f32 = jnp.float32
    inv = (THETA ** (-jnp.arange(0, rd, 2, dtype=f32) / rd)
           if inv_freq is None else jnp.asarray(inv_freq, f32))
    ang = jnp.arange(x.shape[1], dtype=f32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    xf = part.astype(f32)
    x1, x2 = xf[..., :rd // 2], xf[..., rd // 2:]
    turned = (xf * cos + jnp.concatenate([-x2, x1], -1) * sin).astype(x.dtype)
    return jnp.concatenate([x[..., :a], turned, x[..., b:]], -1)


def case(name, dtype):
    H, dh, span, inv, factor = SPANS[name]
    key = jax.random.PRNGKey(sum(map(ord, name)))
    x = jax.random.normal(key, (2, T, H, dh)).astype(dtype)
    ct = jax.random.normal(jax.random.fold_in(key, 1), x.shape).astype(dtype)
    new = lambda x: DB.rotary_embedding(                        # noqa: E731
        x, THETA, span=span, inv_freq=inv, factor=factor)
    old = lambda x: reference(x, span, inv, factor)             # noqa: E731
    return x, ct, new, old


def same_bits(got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def two_roundings(got, want):
    """Equal but for where a compiled program fused a multiply and an add:
    within two roundings of the largest term (a result may be the small
    difference of two products, and the fusion rounds a product's size)."""
    assert got.dtype == want.dtype and got.shape == want.shape
    eps = 2.0 ** -7 if got.dtype == jnp.bfloat16 else 2.0 ** -23
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.max(np.abs(got - want)) <= 2 * eps * np.abs(want).max()


@pytest.mark.parametrize("name,dtype", CASES)
def test_forward_is_the_old_forms_to_the_last_bit(name, dtype):
    x, _, new, old = case(name, dtype)
    same_bits(new(x), old(x))
    # what is passed through is passed through
    a, b = SPANS[name][2]
    same_bits(new(x)[..., :a], x[..., :a])
    same_bits(new(x)[..., b:], x[..., b:])


@pytest.mark.parametrize("name,dtype", CASES)
def test_vjp_is_the_old_forms_vjp(name, dtype):
    """The backward is the same pass with the sine negated, and keeps
    nothing of ``x``: equal to autodiff's transposes of slice, negate and
    concatenate, to the last bit."""
    x, ct, new, old = case(name, dtype)
    got_y, got_vjp = jax.vjp(new, x)
    want_y, want_vjp = jax.vjp(old, x)
    same_bits(got_y, want_y)
    same_bits(got_vjp(ct)[0], want_vjp(ct)[0])


@pytest.mark.parametrize("name,dtype", CASES)
def test_gradient_through_a_recomputation_block(name, dtype):
    """``jax.grad`` through ``jax.checkpoint``: the forward runs a second
    time inside the backward, from the block's input."""
    x, ct, new, old = case(name, dtype)

    def loss(fn):
        return lambda x: jnp.sum(
            jax.checkpoint(fn)(x).astype(jnp.float32)
            * ct.astype(jnp.float32))

    two_roundings(jax.grad(loss(new))(x), jax.grad(loss(old))(x))
    same_bits(jax.grad(loss(new))(x),
              jax.vjp(new, x)[1](ct.astype(jnp.float32).astype(dtype))[0])


def test_residuals_are_the_tables_alone():
    """Nothing of ``x``'s size is kept for the backward: the rotation is
    linear, so the residuals are the two ``[T, dh]`` tables."""
    x = jnp.ones((1, T, 8, 128))
    _, vjp = jax.vjp(lambda x: DB.rotary_embedding(x, THETA), x)
    kept = [leaf.shape for leaf in jax.tree_util.tree_leaves(vjp)
            if hasattr(leaf, "shape")]
    assert kept and all(shape == (T, 128) for shape in kept), kept


def test_rotary_dim_is_the_span_from_zero():
    x = jax.random.normal(jax.random.PRNGKey(3), (1, T, 2, 32))
    same_bits(DB.rotary_embedding(x, THETA, 8),
              DB.rotary_embedding(x, THETA, span=(0, 8)))
    same_bits(DB.rotary_embedding(x, THETA),
              DB.rotary_embedding(x, THETA, span=(0, 32)))


@pytest.mark.parametrize("kwargs,match", [
    (dict(span=(0, 7)), "span"),            # an odd span
    (dict(span=(8, 8)), "span"),            # an empty one
    (dict(span=(24, 40)), "span"),          # past the head's end
    (dict(span=(-2, 6)), "span"),           # before its start
    (dict(rotary_dim=40), "span"),          # wider than the head
    (dict(rotary_dim=6, span=(0, 6)), "one thing"),
    (dict(span=(8, 16), inv_freq=np.ones(3, np.float32)), "frequencies"),
])
def test_a_span_that_is_no_span_is_refused(kwargs, match):
    x = jnp.ones((1, 4, 2, 32))
    with pytest.raises(ValueError, match=match):
        DB.rotary_embedding(x, THETA, **kwargs)


# -- the kernel, interpreted --------------------------------------------------

@pytest.fixture
def kernels_open(monkeypatch):
    """The backend half of the kernel gates, as on the chip; the kernels
    themselves run interpreted on the CPU."""
    monkeypatch.setattr(PK, "compiled_kernels", lambda: True)


def test_gate_is_a_function_of_the_shape(kernels_open, monkeypatch):
    # a head of whole lane tiles is a slab and goes in heads-major; a block
    # is the most heads within 2 MB that divide them
    assert DB.rotary_kernel_blocks(16384, 64, 128) == (True, 128, 512, 1024)
    assert DB.rotary_kernel_blocks(16384, 48, 128) == (True, 128, 512, 1024)
    assert DB.rotary_kernel_blocks(8192, 16, 256) == (True, 256, 512, 1024)
    # any other width in the token-major view, slabs of lcm(dh, 128) lanes
    assert DB.rotary_kernel_blocks(8192, 32, 192) == (False, 384, 512, 768)
    assert DB.rotary_kernel_blocks(8192, 32, 64) == (False, 128, 512, 1024)
    # heads that fill no whole slab, a row no block divides: XLA
    assert DB.rotary_kernel_blocks(16384, 1, 64) is None
    assert DB.rotary_kernel_blocks(8192, 3, 192) is None
    assert DB.rotary_kernel_blocks(8200, 8, 128) is None
    # a row of few positions takes smaller blocks, of 16 rows at least (whole
    # sublane tiles of bfloat16 too)
    assert DB.rotary_kernel_blocks(48, 8, 128) == (True, 128, 16, 1024)
    assert DB.rotary_kernel_blocks(24, 8, 128) is None
    monkeypatch.setattr(PK, "compiled_kernels", lambda: False)
    assert DB.rotary_kernel_blocks(16384, 64, 128) is None


KERNEL_CASES = [pytest.param(name, dtype, id=f"{name}-{dtype}")
                for name in SPANS if name != "whole64_one_head"
                for dtype in ("float32", "bfloat16")]


@pytest.mark.parametrize("name,dtype", KERNEL_CASES)
def test_kernel_forward_and_vjp(name, dtype, kernels_open):
    """``rotary_turn`` in both layouts (heads-major slabs of a head of 128
    or 256; token-major slabs of 384 lanes for two heads of 192) against the
    old form, forward and backward, with the span's pass-through channels
    riding in the same pass."""
    H, dh, span, _, _ = SPANS[name]
    assert DB.rotary_kernel_blocks(T, H, dh) is not None
    x, ct, new, old = case(name, dtype)
    got_y, got_vjp = jax.vjp(new, x)
    want_y, want_vjp = jax.vjp(old, x)
    assert "rotary_turn" in str(jax.make_jaxpr(new)(x))
    two_roundings(got_y, want_y)
    two_roundings(got_vjp(ct)[0], want_vjp(ct)[0])
    a, b = span
    same_bits(got_y[..., :a], x[..., :a])
    same_bits(got_y[..., b:], x[..., b:])


def test_kernel_two_heads_of_64_a_slab(kernels_open):
    """LFM2's heads and the indexer's: two heads share a lane tile, and a
    lane's partner is 32 lanes away inside its own head."""
    x = jax.random.normal(jax.random.PRNGKey(5), (1, T, 6, 64))
    assert DB.rotary_kernel_blocks(T, 6, 64) == (False, 128, 64, 384)
    two_roundings(DB.rotary_embedding(x, THETA), reference(x, (0, 64)))
    two_roundings(DB.rotary_embedding(x, THETA, 32), reference(x, (0, 32)))


def test_kernel_blocks_walk_the_whole_array():
    """More than one block along every axis of the grid (batch, rows,
    slabs), in both layouts: the kernel called with blocks of its own."""
    key = jax.random.PRNGKey(7)
    inv = THETA ** (-jnp.arange(0, 128, 2, dtype=jnp.float32) / 128)
    ang = jnp.arange(48, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([-jnp.sin(ang), jnp.sin(ang)], -1)
    x = jax.random.normal(key, (2, 48, 6, 128))
    want = reference(x, (0, 128))
    got = PK.rotary_pallas(
        jnp.swapaxes(x, 1, 2), cos, sin, head_dim=128, span=(0, 128),
        block_rows=16, block_lanes=256)
    two_roundings(jnp.swapaxes(got, 1, 2), want)
    got = PK.rotary_pallas(
        x.reshape(2, 48, 768), cos, sin, head_dim=128, span=(0, 128),
        block_rows=16, block_lanes=256)
    two_roundings(got.reshape(x.shape), want)
