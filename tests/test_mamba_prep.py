"""What lies between a Mamba-2 mixer's in-projection and its scan, as ONE
pass each way (PR 44): the Pallas pair ``mamba_prep_fwd`` / ``mamba_prep_bwd``
in interpret mode against the ``jax.numpy`` chain that runs behind the closed
gate (the ``[x | B | C]`` columns of the projection, depthwise causal
convolution, bias, SiLU), values and every gradient; the rows where a block
needs its neighbours; the gate; and the layer behind the closed gate and the
open one.

Both sides are float32 here (conftest) and differ by the order of sums:
1e-5 of the largest value, 1e-4 of a gradient's norm.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.nn as nn
import paddle_tpu.ops as O
from paddle_tpu.ops import decoder_block as DB
from paddle_tpu.ops import pallas_kernels as PK
from paddle_tpu.ops import ssd_scan as SS
from paddle_tpu.ops.numerics import mxu_cast
from test_gdn_prep import _kernel_calls, close

VAL_TOL, GRAD_TOL = 1e-5, 1e-4
#: open gate against closed with bf16 operands: the same roundings on both
#: sides, but a float32 sum in another order can tip one
BF16_TOL = 3e-4
LANES = 128
#: the kernels' smallest shape: x of two column blocks, B and C of one each,
#: behind as many ``z`` columns as x has
HP, GN = 2 * LANES, LANES
CONV = HP + 2 * GN


def chain(zxbc, kernel, bias, offset=HP):
    """``nn.mamba2_mixer`` between the projection and the scan as PR 43 wrote
    it: ``[x | B | C]`` ``[B, T, HP + 2 GN]``."""
    f32 = jnp.float32
    return jax.nn.silu(DB.causal_short_conv(
        zxbc[..., offset:offset + kernel.shape[1]].astype(f32), kernel,
        bias)).astype(zxbc.dtype)


def wb_of(kernel, bias):
    return jnp.concatenate([kernel, bias[None]])[None]


def inputs(seed, B, T, taps, offset=HP, hp=HP, gn=GN, tail=0):
    """The projection (``offset`` columns of ``z``, the convolved columns and
    ``tail`` more), the convolution's kernel and bias, and cotangents for x
    (the scan's and the skip's), B and C."""
    r = np.random.RandomState(seed)
    C = hp + 2 * gn
    arrays = [r.randn(B, T, offset + C + tail), 0.5 * r.randn(taps, C),
              0.5 * r.randn(C), r.randn(B, T, hp), r.randn(B, T, hp),
              r.randn(B, T, gn), r.randn(B, T, gn)]
    return tuple(jnp.asarray(a.astype(np.float32)) for a in arrays)


def chain_gradients(zxbc, kernel, bias, dx, dskip, dB, dC, offset=HP):
    out, vjp = jax.vjp(lambda a, k, b: chain(a, k, b, offset), zxbc, kernel,
                       bias)
    return out, vjp(jnp.concatenate([dx + dskip, dB, dC], axis=-1))


def check_pair(seed, B, T, taps, rows, cols, offset=HP, hp=HP, gn=GN, **kw):
    """Forward values and the three gradients (the projection's, the
    convolution kernel's ``[L, C]`` and the bias's) of the pair against the
    chain's.  The projection's gradient is the chain's at the convolved
    columns and nothing elsewhere (the kernel writes those columns only)."""
    zxbc, kernel, bias, dx, dskip, dB, dC = inputs(seed, B, T, taps, offset,
                                                   hp, gn, kw.pop("tail", 0))
    C = hp + 2 * gn
    static = dict(offset=offset, rows=rows, cols=cols, out_dtype=jnp.float32,
                  **kw)
    wb = wb_of(kernel, bias)
    x, bc, skip_x = PK.mamba_prep_fwd_pallas(zxbc, wb, width=hp, **static)
    want, (dzxbc, dkernel, dbias) = chain_gradients(
        zxbc, kernel, bias, dx, dskip, dB, dC, offset)
    # every block of the three written, once: x's two are left alone while
    # B's and C's blocks of columns are worked, and [B | C]'s until then
    assert x.shape == (B, T, hp) and bc.shape == (B, T, 2 * gn)
    got = jnp.concatenate([x, bc], axis=-1)
    assert np.max(np.abs(got - want)) < VAL_TOL * np.max(np.abs(want))
    np.testing.assert_array_equal(skip_x, x)
    dpre, dwb = PK.mamba_prep_bwd_pallas(zxbc, wb, dx, dskip, dB, dC,
                                         **static)
    assert not np.asarray(dzxbc[..., :offset]).any()
    assert not np.asarray(dzxbc[..., offset + C:]).any()
    close(dpre, dzxbc[..., offset:offset + C], GRAD_TOL, "dzxbc")
    assert dwb.shape == wb.shape
    close(dwb[0, :taps], dkernel, GRAD_TOL, "dkernel")
    close(dwb[0, taps], dbias, GRAD_TOL, "dbias")


@pytest.mark.parametrize("blocks", [1, 4], ids=["one_block", "four_blocks"])
@pytest.mark.parametrize("taps", [4, 2])
def test_kernel_pair_matches_the_chain(taps, blocks):
    """Two rows of a batch, 64 tokens in one block of rows or four, x over
    two blocks of columns, B and C one each."""
    check_pair(taps + blocks, 2, 64, taps, 64 // blocks, LANES)


def test_wide_blocks_of_columns_and_two_sub_blocks_a_block():
    """Blocks of 256 columns (two lane tiles a block: x of 512 is two
    blocks, B and C of 256 one each) behind an offset of 768 and before 128
    columns more, and blocks of 256 rows, each worked in two sub-blocks of
    ``GDN_PREP_SUB_ROWS``."""
    assert PK.GDN_PREP_SUB_ROWS == 128
    check_pair(3, 1, 512, 4, 256, 2 * LANES, offset=6 * LANES, hp=4 * LANES,
               gn=2 * LANES, tail=LANES)


def test_blocks_that_do_not_tile_the_parts_are_refused():
    zxbc, kernel, bias, dx, dskip, dB, dC = inputs(1, 1, 64, 4)
    wb = wb_of(kernel, bias)
    static = dict(rows=64, out_dtype=jnp.float32)
    with pytest.raises(ValueError, match="do not tile"):    # B is 128 wide
        PK.mamba_prep_bwd_pallas(zxbc, wb, dx, dskip, dB, dC, offset=HP,
                                 cols=2 * LANES, **static)
    with pytest.raises(ValueError, match="do not tile"):    # the offset
        PK.mamba_prep_fwd_pallas(zxbc, wb, offset=HP - 64, width=HP,
                                 cols=LANES, **static)
    with pytest.raises(ValueError, match="do not tile x"):  # x is 256 wide
        PK.mamba_prep_fwd_pallas(zxbc, wb, offset=HP, width=HP + 64,
                                 cols=LANES, **static)


# -- the rows where a block needs its neighbours ----------------------------

ROWS, TAPS = 16, 4


def _two_rows_of_four_blocks(seed=5):
    return inputs(seed, 2, 4 * ROWS, TAPS)


def _forward(zxbc, kernel, bias):
    return jnp.concatenate(PK.mamba_prep_fwd_pallas(
        zxbc, wb_of(kernel, bias), offset=HP, width=HP, rows=ROWS,
        cols=LANES, out_dtype=jnp.float32)[:2], axis=-1)


def _backward(zxbc, kernel, bias, *grads):
    return PK.mamba_prep_bwd_pallas(zxbc, wb_of(kernel, bias), *grads,
                                    offset=HP, rows=ROWS, cols=LANES,
                                    out_dtype=jnp.float32)


def test_first_tokens_of_a_row_have_zero_history():
    """Tokens 0 .. L-2 of EVERY row of the batch see zeros before them, not
    the halo block's content (row 1's halo index is clamped onto its own
    first rows; row 0's tail is not row 1's history)."""
    zxbc, kernel, bias, *_ = _two_rows_of_four_blocks()
    got = _forward(zxbc, kernel, bias)
    alone = chain(zxbc[1:, :TAPS - 1], kernel, bias)    # nothing before them
    np.testing.assert_allclose(got[1, :TAPS - 1], alone[0], rtol=1e-5,
                               atol=1e-6)


def test_a_blocks_first_row_reads_the_previous_blocks_last_rows():
    zxbc, kernel, bias, *_ = _two_rows_of_four_blocks()
    moved = zxbc.at[:, ROWS - 1].add(1.0)       # the last row of block 0
    base, got = _forward(zxbc, kernel, bias), _forward(moved, kernel, bias)
    want = chain(moved, kernel, bias)
    assert np.max(np.abs(got[:, ROWS] - base[:, ROWS])) > 1e-3
    np.testing.assert_allclose(got[:, ROWS:ROWS + TAPS],
                               want[:, ROWS:ROWS + TAPS], rtol=1e-5,
                               atol=1e-6)
    # and reaches no further than the taps
    np.testing.assert_array_equal(got[:, ROWS + TAPS - 1:],
                                  base[:, ROWS + TAPS - 1:])


def test_backward_halo_a_blocks_last_rows_take_the_next_blocks_gradient():
    """A cotangent on the first row of block 1 alone reaches the projection
    at the last ``L - 1`` rows of block 0 through the taps, and the
    convolution kernel's and the bias's gradients count that row once."""
    zxbc, kernel, bias, *grads = _two_rows_of_four_blocks()
    grads = [jnp.zeros_like(g).at[:, ROWS].set(g[:, ROWS]) for g in grads]
    dpre, dwb = _backward(zxbc, kernel, bias, *grads)
    _, (dzxbc, dkernel, dbias) = chain_gradients(zxbc, kernel, bias, *grads)
    want = dzxbc[..., HP:]
    assert np.min(np.max(np.abs(want[:, ROWS - TAPS + 1:ROWS]), axis=-1)) > 0
    np.testing.assert_allclose(dpre, want, rtol=1e-4, atol=1e-6)
    assert not np.any(dpre[:, :ROWS - TAPS + 1])
    assert not np.any(dpre[:, ROWS + 1:])
    close(dwb[0, :TAPS], dkernel, GRAD_TOL, "dkernel")
    close(dwb[0, TAPS], dbias, GRAD_TOL, "dbias")


def test_the_rows_last_block_has_nothing_after_it():
    """The reverse kernel's halo index is clamped at the row's end; what it
    brings must not count: a cotangent on the last row alone."""
    zxbc, kernel, bias, *grads = _two_rows_of_four_blocks(6)
    grads = [jnp.zeros_like(g).at[:, -1].set(g[:, -1]) for g in grads]
    dpre, _ = _backward(zxbc, kernel, bias, *grads)
    _, (dzxbc, _, _) = chain_gradients(zxbc, kernel, bias, *grads)
    np.testing.assert_allclose(dpre, dzxbc[..., HP:], rtol=1e-4, atol=1e-6)


def test_a_block_of_columns_reads_the_gradient_of_its_own_part():
    """A cotangent on B alone moves the projection's gradient at B's columns
    and nowhere else; so for x (the scan's and the skip's parts add) and C."""
    zxbc, kernel, bias, dx, dskip, dB, dC = _two_rows_of_four_blocks(7)
    zero = jnp.zeros_like
    parts = {"x": (slice(0, HP), (dx, zero(dskip), zero(dB), zero(dC))),
             "skip": (slice(0, HP), (zero(dx), dskip, zero(dB), zero(dC))),
             "B": (slice(HP, HP + GN), (zero(dx), zero(dskip), dB, zero(dC))),
             "C": (slice(HP + GN, CONV), (zero(dx), zero(dskip), zero(dB),
                                          dC))}
    for name, (cols, grads) in parts.items():
        dpre, _ = _backward(zxbc, kernel, bias, *grads)
        _, (dzxbc, _, _) = chain_gradients(zxbc, kernel, bias, *grads)
        assert np.asarray(dpre[..., cols]).any(), name
        np.testing.assert_allclose(dpre, dzxbc[..., HP:], rtol=1e-4,
                                   atol=1e-6, err_msg=name)
        outside = np.ones(CONV, bool)
        outside[cols] = False
        assert not np.asarray(dpre[..., outside]).any(), name


# -- the gate ----------------------------------------------------------------

CELL = dict(T=4096, heads=64, head_dim=64, groups=8, state=128, taps=4,
            offset=4096)


def _block(**changed):
    return SS.prep_kernel_block(**{**CELL, **changed})


def test_gate_opens_at_the_cells_shape_on_the_tpu_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _block() == (512, 512)
    assert _block(T=256) == (256, 512) and _block(T=128) == (128, 512)
    assert _block(taps=2) == (512, 512)
    assert _block(offset=4096 + 256) == (512, 256)
    assert _block(head_dim=128, heads=32, offset=128) == (512, 128)
    # x need not be whole blocks of the state's width: B and C have an array
    assert _block(heads=8, groups=1, state=384) == (512, 128)


@pytest.mark.parametrize("why,changed", [
    ("heads of 48, off the lane tile", dict(head_dim=48)),
    ("a state of 64, which the scan's gate refuses", dict(state=64)),
    ("x, B and C from a column that is no multiple of 128",
     dict(offset=4096 + 64)),
    ("a row that no chunk divides", dict(T=4096 + 8)),
    ("a row of 9 chunks, which the scan pads to 12", dict(T=9 * 128)),
    ("heads that are not whole groups", dict(heads=60)),
    ("a convolution whose history is longer than the halo", dict(taps=10)),
])
def test_gate_is_closed_at(why, changed, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _block() is not None and _block(**changed) is None, why


def test_gate_is_closed_past_the_kernels_vmem(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(PK, "GDN_PREP_VMEM_LIMIT_BYTES", 8 * 1024 * 1024)
    assert _block() == (128, 512)
    monkeypatch.setattr(PK, "GDN_PREP_VMEM_LIMIT_BYTES", 1024 * 1024)
    assert _block() is None


def test_gate_is_closed_off_the_tpu_and_inside_xla_paths_only(monkeypatch):
    assert _block() is None                                 # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with PK.xla_paths_only():
        assert _block() is None
    assert _block() == (512, 512)


# -- the layer, gate closed and open -----------------------------------------

D, LAYER = 32, dict(num_heads=4, head_dim=64, n_groups=2, state_size=128,
                    conv_kernel_size=4)
T_LAYER = 256


def _layer(seed=0, T=T_LAYER, **changed):
    nn.reset_naming()
    node = nn.mamba2_mixer(nn.data("x", size=D, is_seq=True), name="mamba0",
                           **{**LAYER, **changed})
    topo = nn.Topology(node)
    params = dict(topo.init(jax.random.PRNGKey(seed))[0])
    r = np.random.RandomState(seed)
    # a bias and a skip that are not their initial 0 and 1
    params["_mamba0.conv_bias"] = jnp.asarray(
        0.5 * r.randn(*params["_mamba0.conv_bias"].shape).astype(np.float32))
    params["_mamba0.d"] = jnp.asarray(
        r.randn(*params["_mamba0.d"].shape).astype(np.float32))
    x, w = (jnp.asarray(r.randn(2, T, D).astype(np.float32)) for _ in "xw")
    lengths = jnp.full((2,), T, jnp.int32)

    def loss(p, v):
        out = topo.apply(p, {}, {"x": (v, lengths)}, train=True)[0]
        return jnp.sum(out[node.name].value * w)

    return params, x, w, loss


def _layer_of_pr_43(p, u):
    """``nn.mamba2_mixer``'s forward as PR 43 wrote it."""
    H, P, G, N = 4, 64, 2, 128
    B, T = u.shape[:2]
    f32 = jnp.float32
    inner, conv = H * P, H * P + 2 * G * N
    w = p["_mamba0.w_in"]
    zxbc = O.linear(u, w[:, :inner + conv])
    uc, wd = mxu_cast(u, w[:, inner + conv:])
    dt = jax.nn.softplus(jnp.matmul(uc, wd, preferred_element_type=f32)
                         + p["_mamba0.dt_bias"].astype(f32))
    z = zxbc[..., :inner]
    xbc = jax.nn.silu(DB.causal_short_conv(
        zxbc[..., inner:].astype(f32), p["_mamba0.kernel"],
        p["_mamba0.conv_bias"])).astype(zxbc.dtype)
    x = xbc[..., :inner].reshape(B, T, H, P)
    Bm = xbc[..., inner:inner + G * N].reshape(B, T, G, N)
    Cm = xbc[..., inner + G * N:].reshape(B, T, G, N)
    A = -jnp.exp(p["_mamba0.a_log"].astype(f32))
    y = SS.ssd_scan(x, Bm, Cm, dt, A)
    y = y.astype(f32) + p["_mamba0.d"].astype(f32)[:, None] * x.astype(f32)
    y = y.reshape(B, T, inner) * jax.nn.silu(z.astype(f32))
    y = DB.rms_norm(y.reshape(B, T, G, inner // G),
                    p["_mamba0.norm"].reshape(G, inner // G), 1e-5)
    return O.linear(y.reshape(B, T, inner).astype(zxbc.dtype),
                    p["_mamba0.w_out"])


def test_closed_gate_is_pr_43s_layer_bit_for_bit():
    """On the CPU the gate is closed and the layer runs the ``jax.numpy``
    chain: value and every gradient equal PR 43's expressions to the bit."""
    params, x, w, loss = _layer()
    got = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    want = jax.value_and_grad(
        lambda p, v: jnp.sum(_layer_of_pr_43(p, v) * w),
        argnums=(0, 1))(params, x)
    assert float(got[0]) == float(want[0])
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("block", [(256, 128), (128, 256)],
                         ids=["one_block_of_rows", "two_blocks_of_rows"])
def test_open_gate_matches_the_chain_through_to_u_and_every_leaf(block,
                                                                 monkeypatch):
    """The layer with both gates patched open (the kernels in interpret
    mode: one product, the prep pair, the scan on blocks of its arrays, the
    in-projection's transpose as two products) against the layer with them
    closed: the value and the gradient of every leaf and of the input."""
    params, x, _, loss = _layer(1)
    want = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    monkeypatch.setattr(SS, "prep_kernel_block", lambda *a: block)
    got = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for name in sorted(want[1][0]):
        assert np.asarray(want[1][0][name]).any(), name
        close(got[1][0][name], want[1][0][name], GRAD_TOL, name)
    close(got[1][1], want[1][1], GRAD_TOL, "u")


def _primitives(jaxpr, out=None):
    """``[(primitive, operand shapes)]`` through sub-jaxprs, the kernels'
    own bodies left out."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if str(eqn.params.get("name", "")).endswith("_pallas"):
            continue
        out.append((eqn.primitive.name,
                    [tuple(v.aval.shape) for v in eqn.invars
                     if hasattr(v.aval, "shape")]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, out)
    return out


def test_open_gate_names_its_kernels_under_the_layers_scopes(monkeypatch):
    """``mamba_prep_fwd`` / ``mamba_prep_bwd`` under ``mamba_proj``, the
    scan's pair under ``ssd_scan``, forward and backward; and in the
    differentiated layer nothing joins or pads an array of the convolved
    columns' size, and nothing is sliced out of the prep kernel's arrays
    (the skip's x is an output of its own)."""
    params, x, _, loss = _layer(2)
    monkeypatch.setattr(SS, "prep_kernel_block", lambda *a: (128, 128))
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params, x)
    calls = _kernel_calls(jaxpr.jaxpr)
    assert sorted(calls) == ["mamba_prep_bwd_pallas", "mamba_prep_fwd_pallas",
                             "ssd_chunk_bwd_pallas", "ssd_chunk_fwd_pallas"]
    for name, stacks in calls.items():
        scope = "mamba_proj" if "prep" in name else "ssd_scan"
        assert len(stacks) == 1 and scope in stacks[0], (name, stacks)
        assert "mamba0" in stacks[0], (name, stacks)
    conv = 4 * 64 + 2 * 2 * 128
    wide = (2, T_LAYER, conv)
    prims = _primitives(jaxpr.jaxpr)
    for prim, shapes in prims:
        if prim in ("concatenate", "pad"):
            assert all(s[:2] != wide[:2] for s in shapes), (prim, shapes)
    sliced = [shapes for prim, shapes in prims
              if prim == "slice" and wide in shapes]
    assert not sliced, sliced


def _kernel_avals(jaxpr, out=None):
    """``{kernel wrapper: (operands' dtypes, results' dtypes)}`` through
    sub-jaxprs."""
    out = {} if out is None else out
    for eqn in jaxpr.eqns:
        name = str(eqn.params.get("name", ""))
        if name.endswith("_pallas"):
            out[name] = ([str(v.aval.dtype) for v in eqn.invars],
                         [str(v.aval.dtype) for v in eqn.outvars])
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_avals(sub, out)
    return out


def test_the_skip_keeps_the_projections_float32_beside_bf16_operands(
        monkeypatch):
    """The benchmark's policy, bf16 operands and float32 results (``--amp``
    off): the chain rounds x to bf16 for the scan ALONE and its skip ``D x``
    reads the float32 x.  So behind the open gate: the prep kernel's arrays
    are bf16, its third output float32, and the skip's part of ``dx`` reaches
    the reverse kernel in float32.  Against the chain over the same scan
    kernels the value and the leaves' gradients then differ by float32 sums
    in another order, which now and then tip a rounding (6e-5 at the most
    over three seeds; with the skip through bf16 ``d``, ``kernel`` and
    ``conv_bias`` stood 1e-3 to 3e-3 off).  ``w_in`` and ``u`` are looser
    HERE: XLA:CPU multiplies the chain's float32 cotangent as it is, where
    the MXU rounds it to the bf16 that the reverse kernel writes."""
    from paddle_tpu.utils.flags import FLAGS

    monkeypatch.setattr(FLAGS, "compute_dtype", "bfloat16")
    monkeypatch.setattr(SS, "ssd_kernel_chunk", lambda *a: SS.CHUNK)
    params, x, _, loss = _layer(4)
    monkeypatch.setattr(SS, "prep_kernel_block", lambda *a: None)
    want = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    monkeypatch.setattr(SS, "prep_kernel_block", lambda *a: (128, 128))
    avals = _kernel_avals(jax.make_jaxpr(jax.grad(loss))(params, x).jaxpr)
    assert avals["mamba_prep_fwd_pallas"] == (
        ["float32", "float32"], ["bfloat16", "bfloat16", "float32"])
    # zxbc, wb, dx, dskip, dB, dC
    assert avals["mamba_prep_bwd_pallas"][0] == [
        "float32", "float32", "bfloat16", "float32", "bfloat16", "bfloat16"]
    assert avals["ssd_chunk_fwd_pallas"][0][0] == "bfloat16"
    got = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for name in sorted(want[1][0]):
        close(got[1][0][name], want[1][0][name],
              1e-2 if name.endswith("w_in") else BF16_TOL, name)
    close(got[1][1], want[1][1], 1e-2, "u")
