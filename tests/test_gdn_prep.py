"""What lies between a gated delta net's in-projection and its scan, as ONE
pass each way (PR 42): the Pallas pair ``gdn_prep_fwd`` / ``gdn_prep_bwd``
in interpret mode against the ``jax.numpy`` chain that runs behind the closed
gate (depthwise causal convolution, SiLU, L2 norms of ``q`` and ``k``, ``q``'s
scale, heads-major layout), values and every gradient; the rows where a block
needs its neighbours; the gate; and ``delta_rule`` with ``q`` and ``k`` at the
key heads against the same with them repeated.

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
from paddle_tpu.ops import delta_rule as DR
from paddle_tpu.ops import pallas_kernels as PK

VAL_TOL, GRAD_TOL = 1e-5, 1e-4
DK = DV = 16


def chain(qkv, kernel, Hk, Hv, dk=DK, dv=DV):
    """``nn.gated_delta_net`` between the projection and the scan as PR 41
    wrote it: ``q``, ``k`` ``[B, T, Hk, dk]``, ``v`` ``[B, T, Hv, dv]``."""
    B, T, _ = qkv.shape
    nk = Hk * dk
    a = jax.nn.silu(DB.causal_short_conv(qkv, kernel))
    q = DB.unit_norm(a[..., :nk].reshape(B, T, Hk, dk)) * dk ** -0.5
    k = DB.unit_norm(a[..., nk:2 * nk].reshape(B, T, Hk, dk))
    return q, k, a[..., 2 * nk:].reshape(B, T, Hv, dv)


def heads_major(a):
    return jnp.moveaxis(a, 2, 1)


def pair_forward(qkv, kernel, Hk, Hv, rows, dk=DK, dv=DV, **sub):
    """The forward kernel on the grouped columns: heads-major q, k, v."""
    x = DR.group_columns(qkv, Hk, dk)
    w = jnp.moveaxis(DR.group_columns(kernel, Hk, dk).reshape(
        kernel.shape[0], Hk, -1), 1, 0)
    return x, w, PK.gdn_prep_fwd_pallas(x, w, dk=dk, dv=dv, rows=rows,
                                        out_dtype=jnp.float32, **sub)


def inputs(seed, B, T, Hk, Hv, taps, dk=DK, dv=DV):
    r = np.random.RandomState(seed)
    C = 2 * Hk * dk + Hv * dv
    arrays = [r.randn(B, T, C), 0.5 * r.randn(taps, C),
              r.randn(B, Hv, T, dk), r.randn(B, Hv, T, dk),
              r.randn(B, Hv, T, dv)]
    return tuple(jnp.asarray(a.astype(np.float32)) for a in arrays)


def chain_gradients(qkv, kernel, dq, dk_, dv_, Hk, Hv):
    """The chain's vjp for cotangents that come a VALUE head each, as the
    scan's reverse kernel writes them: the repeat's transpose sums a key
    head's group."""
    B, _, T, d = dq.shape
    (q, k, v), vjp = jax.vjp(
        lambda a, b: chain(a, b, Hk, Hv, d, dv_.shape[-1]), qkv, kernel)
    grouped = [heads_major(a.reshape(B, Hk, Hv // Hk, T, d).sum(2))
               for a in (dq, dk_)]
    return (q, k, v), vjp((*grouped, heads_major(dv_)))


def close(got, want, tol, what):
    gap = np.linalg.norm(np.asarray(got - want)) / max(
        np.linalg.norm(np.asarray(want)), 1e-30)
    assert np.all(np.isfinite(got)) and gap < tol, (what, gap)


def check_pair(seed, B, T, Hk, Hv, taps, rows, d=DK, **sub):
    """Forward values and both gradients (the ``[q | k | v]`` columns' and
    the convolution kernel's ``[L, C]``) of the pair against the chain's."""
    qkv, kernel, dq, dk_, dv_ = inputs(seed, B, T, Hk, Hv, taps, d, d)
    x, w, got = pair_forward(qkv, kernel, Hk, Hv, rows, d, d, **sub)
    want, (dqkv, dkernel) = chain_gradients(qkv, kernel, dq, dk_, dv_, Hk, Hv)
    for a, b in zip(got, want):
        assert a.shape == heads_major(b).shape
        assert np.max(np.abs(a - heads_major(b))) < VAL_TOL * np.max(np.abs(b))
    dx, dw = PK.gdn_prep_bwd_pallas(x, w, dq, dk_, dv_, rows=rows, **sub)
    close(dx, DR.group_columns(dqkv, Hk, d), GRAD_TOL, "dx")
    close(jnp.moveaxis(dw, 0, 1).reshape(taps, -1),
          DR.group_columns(dkernel, Hk, d), GRAD_TOL, "dkernel")


@pytest.mark.parametrize("blocks", [1, 4], ids=["one_block", "four_blocks"])
@pytest.mark.parametrize("taps", [4, 2])
@pytest.mark.parametrize("Hk,Hv", [(2, 2), (2, 4)],
                         ids=["Hv_is_Hk", "Hv_is_2Hk"])
def test_kernel_pair_matches_the_chain(Hk, Hv, taps, blocks):
    """Two rows of a batch, 64 tokens in one block of rows or four."""
    check_pair(Hk + taps + blocks, 2, 64, Hk, Hv, taps, 64 // blocks)


def test_lane_wide_heads_and_two_sub_blocks_a_block():
    """Heads of 128 (a lane tile each, the width the gate admits) and blocks
    of 64 rows, each worked in two sub-blocks of 32."""
    check_pair(3, 1, 128, 1, 2, 4, 64, d=128, sub=32)


# -- the rows where a block needs its neighbours ----------------------------

ROWS, TAPS = 16, 4


def _two_rows_of_four_blocks(seed=5):
    return inputs(seed, 2, 4 * ROWS, 2, 4, TAPS)


def test_first_tokens_of_a_row_have_zero_history():
    """Tokens 0 .. L-2 of EVERY row of the batch see zeros before them, not
    the halo block's content (row 1's halo index is clamped onto its own
    first rows; row 0's tail is not row 1's history)."""
    qkv, kernel, *_ = _two_rows_of_four_blocks()
    _, _, got = pair_forward(qkv, kernel, 2, 4, ROWS)
    alone = chain(qkv[1:, :TAPS - 1], kernel, 2, 4)     # nothing before them
    for a, b in zip(got, alone):
        np.testing.assert_allclose(a[1, :, :TAPS - 1], heads_major(b)[0],
                                   rtol=1e-5, atol=1e-6)


def test_a_blocks_first_row_reads_the_previous_blocks_last_rows():
    qkv, kernel, *_ = _two_rows_of_four_blocks()
    moved = qkv.at[:, ROWS - 1].add(1.0)      # the last row of block 0
    _, _, a = pair_forward(qkv, kernel, 2, 4, ROWS)
    _, _, b = pair_forward(moved, kernel, 2, 4, ROWS)
    want = chain(moved, kernel, 2, 4)
    for got, base, ref in zip(b, a, want):
        assert np.max(np.abs(got[:, :, ROWS] - base[:, :, ROWS])) > 1e-3
        np.testing.assert_allclose(got[:, :, ROWS:ROWS + TAPS],
                                   heads_major(ref)[:, :, ROWS:ROWS + TAPS],
                                   rtol=1e-5, atol=1e-6)
        # and reaches no further than the taps
        np.testing.assert_array_equal(got[:, :, ROWS + TAPS - 1:],
                                      base[:, :, ROWS + TAPS - 1:])


def test_backward_halo_a_blocks_last_rows_take_the_next_blocks_gradient():
    """A cotangent on the first row of block 1 alone reaches ``x`` at the
    last ``L - 1`` rows of block 0 through the taps, and the convolution
    kernel's gradient counts that row once."""
    qkv, kernel, dq, dk_, dv_ = _two_rows_of_four_blocks()
    only = lambda a: jnp.zeros_like(a).at[:, :, ROWS].set(a[:, :, ROWS])  # noqa: E731,E501
    dq, dk_, dv_ = only(dq), only(dk_), only(dv_)
    x, w, _ = pair_forward(qkv, kernel, 2, 4, ROWS)
    dx, dw = PK.gdn_prep_bwd_pallas(x, w, dq, dk_, dv_, rows=ROWS)
    _, (dqkv, dkernel) = chain_gradients(qkv, kernel, dq, dk_, dv_, 2, 4)
    want = DR.group_columns(dqkv, 2, DK)
    assert np.min(np.max(np.abs(want[:, ROWS - TAPS + 1:ROWS]), axis=-1)) > 0
    np.testing.assert_allclose(dx, want, rtol=1e-4, atol=1e-6)
    assert not np.any(dx[:, :ROWS - TAPS + 1]) and not np.any(dx[:, ROWS + 1:])
    close(jnp.moveaxis(dw, 0, 1).reshape(TAPS, -1),
          DR.group_columns(dkernel, 2, DK), GRAD_TOL, "dkernel")


def test_the_rows_last_block_has_nothing_after_it():
    """The reverse kernel's halo index is clamped at the row's end; what it
    brings must not count: a cotangent on the last row alone."""
    qkv, kernel, dq, dk_, dv_ = _two_rows_of_four_blocks(6)
    only = lambda a: jnp.zeros_like(a).at[:, :, -1].set(a[:, :, -1])  # noqa: E731,E501
    dq, dk_, dv_ = only(dq), only(dk_), only(dv_)
    x, w, _ = pair_forward(qkv, kernel, 2, 4, ROWS)
    dx, _ = PK.gdn_prep_bwd_pallas(x, w, dq, dk_, dv_, rows=ROWS)
    _, (dqkv, _) = chain_gradients(qkv, kernel, dq, dk_, dv_, 2, 4)
    np.testing.assert_allclose(dx, DR.group_columns(dqkv, 2, DK),
                               rtol=1e-4, atol=1e-6)


# -- the gate ----------------------------------------------------------------

CELL = dict(T=8192, Hk=16, Hv=32, dk=128, dv=128, taps=4)


def _rows(**changed):
    return DR.prep_kernel_rows(**{**CELL, **changed})


def test_gate_opens_at_the_cells_shape_on_the_tpu_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _rows() == 512
    assert _rows(T=256) == 256 and _rows(T=64) == 64
    assert _rows(Hv=16) == 512 and _rows(taps=2) == 512


@pytest.mark.parametrize("why,changed", [
    ("key heads of 64", dict(dk=64)),
    ("value heads of 192", dict(dv=192)),
    ("a row that no block of rows divides", dict(T=8192 + 8)),
    ("a row of 9 chunks, which the scan pads to 16", dict(T=9 * 64)),
    ("value heads that are not whole groups", dict(Hv=24)),
    ("a convolution whose history is longer than the halo", dict(taps=10)),
])
def test_gate_is_closed_at(why, changed, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _rows() is not None and _rows(**changed) is None, why


def test_gate_is_closed_off_the_tpu_and_inside_xla_paths_only(monkeypatch):
    assert _rows() is None                                  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with PK.xla_paths_only():
        assert _rows() is None
    assert _rows() == 512


# -- the layer, gate closed and open -----------------------------------------

D, LAYER = 32, dict(num_key_heads=2, num_value_heads=4, key_head_dim=DK,
                    value_head_dim=DV, conv_kernel_size=4)


def _layer(seed=0, T=128, **changed):
    nn.reset_naming()
    node = nn.gated_delta_net(nn.data("x", size=D, is_seq=True), name="gdn0",
                              **{**LAYER, **changed})
    topo = nn.Topology(node)
    params = dict(topo.init(jax.random.PRNGKey(seed))[0])
    r = np.random.RandomState(seed)
    x, w = (jnp.asarray(r.randn(2, T, D).astype(np.float32)) for _ in "xw")
    lengths = jnp.full((2,), T, jnp.int32)

    def loss(p, v):
        out = topo.apply(p, {}, {"x": (v, lengths)}, train=True)[0]
        return jnp.sum(out[node.name].value * w)

    return params, x, loss


def _layer_of_pr_41(p, x):
    """``nn.gated_delta_net``'s forward as PR 41 wrote it, the key heads
    repeated before the scan."""
    Hk, Hv, dk, dv = 2, 4, DK, DV
    B, T = x.shape[:2]
    nk, nv = Hk * dk, Hv * dv
    conv = 2 * nk + nv
    f32 = jnp.float32
    qkvz = O.linear(x, p["_gdn0.w_qkvz"])
    ba = O.linear(x, p["_gdn0.w_ba"]).astype(f32)
    qkv = jax.nn.silu(DB.causal_short_conv(qkvz[..., :conv],
                                           p["_gdn0.kernel"]))
    z = qkvz[..., conv:].reshape(B, T, Hv, dv)
    q = DB.unit_norm(qkv[..., :nk].reshape(B, T, Hk, dk)) * dk ** -0.5
    k = DB.unit_norm(qkv[..., nk:2 * nk].reshape(B, T, Hk, dk))
    q, k = (jnp.repeat(h, Hv // Hk, axis=2) for h in (q, k))
    v = qkv[..., 2 * nk:].reshape(B, T, Hv, dv)
    beta = jax.nn.sigmoid(ba[..., :Hv])
    g = -jnp.exp(p["_gdn0.a_log"].astype(f32)) * jax.nn.softplus(
        ba[..., Hv:] + p["_gdn0.dt_bias"].astype(f32))
    o = DR.delta_rule(q, k, v, g, beta)
    y = DB.rms_norm(o, p["_gdn0.norm"], 1e-6) * jax.nn.silu(z)
    return O.linear(y.reshape(B, T, nv), p["_gdn0.w_out"])


def test_closed_gate_is_pr_41s_layer_bit_for_bit():
    """On the CPU the gate is closed and the layer runs the ``jax.numpy``
    chain: value and every gradient equal PR 41's expressions to the bit."""
    params, x, loss = _layer()
    w = np.random.RandomState(0)
    w.randn(2, 128, D)                              # _layer's x, then its w
    w = jnp.asarray(w.randn(2, 128, D).astype(np.float32))
    got = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    want = jax.value_and_grad(
        lambda p, v: jnp.sum(_layer_of_pr_41(p, v) * w),
        argnums=(0, 1))(params, x)
    assert float(got[0]) == float(want[0])
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("rows", [128, 64], ids=["one_block", "two_blocks"])
def test_open_gate_matches_the_chain_through_to_x_and_w_qkvz(rows,
                                                             monkeypatch):
    """The layer with the gate patched open (the kernels in interpret mode,
    the projection read as two products of grouped columns) against the
    layer with it closed: the value and the gradient of every leaf and of
    the input."""
    params, x, loss = _layer(1)
    want = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    monkeypatch.setattr(DR, "prep_kernel_rows", lambda *a: rows)
    got = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for name in sorted(want[1][0]):
        assert np.asarray(want[1][0][name]).any(), name
        close(got[1][0][name], want[1][0][name], GRAD_TOL, name)
    close(got[1][1], want[1][1], GRAD_TOL, "x")


def _kernel_calls(jaxpr, out=None):
    """``{wrapper's name: name stacks of its calls}`` through sub-jaxprs: the
    kernels' wrappers are jitted, so each call is one equation."""
    out = {} if out is None else out
    for eqn in jaxpr.eqns:
        name = eqn.params.get("name", "")
        if isinstance(name, str) and name.endswith("_pallas"):
            out.setdefault(name, []).append(str(eqn.source_info.name_stack))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(sub, out)
    return out


def test_open_gate_names_its_kernels_under_the_layers_scopes(monkeypatch):
    """``gdn_prep_fwd`` / ``gdn_prep_bwd`` under ``gdn_proj``, the scan's
    pair under ``gdn_scan``, forward and backward, and no repeat of the key
    heads anywhere in the differentiated layer (value heads of 24 here, so
    that a ``[B, T, Hv, dk]`` array can only be a repeated ``q`` or ``k``)."""
    params, x, loss = _layer(2, value_head_dim=24)
    monkeypatch.setattr(DR, "prep_kernel_rows", lambda *a: 64)
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params, x)
    calls = _kernel_calls(jaxpr.jaxpr)
    assert sorted(calls) == ["gdn_chunk_bwd_pallas", "gdn_chunk_fwd_pallas",
                             "gdn_prep_bwd_pallas", "gdn_prep_fwd_pallas"]
    for name, stacks in calls.items():
        scope = "gdn_proj" if "prep" in name else "gdn_scan"
        assert len(stacks) == 1 and scope in stacks[0], (name, stacks)
        assert "gdn0" in stacks[0], (name, stacks)
    assert "2,128,4,16" not in str(jaxpr).replace(" ", "")  # [B, T, Hv, dk]


# -- delta_rule with q and k at the key heads --------------------------------

def _scan_inputs(seed, B, T, Hk, H, dk, dv):
    r = np.random.RandomState(seed)
    q = r.randn(B, T, Hk, dk).astype(np.float32)
    k = r.randn(B, T, Hk, dk).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * dk ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.randn(B, T, H, dv).astype(np.float32)
    g = -np.exp(r.randn(B, T, H)).astype(np.float32)
    beta = (1 / (1 + np.exp(-r.randn(B, T, H)))).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (q, k, v, g, beta))


def _kernels_path(q, k, v, g, beta):
    """``delta_rule`` as the TPU runs it (interpret mode here)."""
    B, T, H, _ = v.shape
    gamma, bh = DR._chunked_gates(g, beta, T // DR.CHUNK)
    o = DR._scan_kernels(heads_major(q), heads_major(k), heads_major(v),
                         gamma, bh)
    return jnp.moveaxis(o, 1, 2)


@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_delta_rule_reads_key_heads_by_their_groups(path):
    """``q``, ``k`` at 2 key heads under 6 value heads equal the same repeated
    to 6 heads, values and all five gradients; the gradient of a key head is
    its group's sum."""
    args = _scan_inputs(4, 2, 2 * DR.CHUNK, 2, 6, 16, 24)
    fn = DR.delta_rule if path == "xla" else _kernels_path
    w = jnp.asarray(np.random.RandomState(8).randn(
        *args[2].shape).astype(np.float32))

    def grouped(q, k, v, g, beta):
        return jnp.sum(fn(q, k, v, g, beta) * w)

    def repeated(q, k, v, g, beta):
        q, k = (jnp.repeat(a, 3, axis=2) for a in (q, k))
        return jnp.sum(fn(q, k, v, g, beta) * w)

    got = jax.value_and_grad(grouped, argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.value_and_grad(repeated, argnums=(0, 1, 2, 3, 4))(*args)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-6)
    for name, a, b in zip("q k v g beta".split(), got[1], want[1]):
        assert a.shape == b.shape
        close(a, b, 1e-5, name)


def test_delta_rule_refuses_heads_that_are_not_whole_groups():
    q, k, v, g, beta = _scan_inputs(1, 1, DR.CHUNK, 2, 3, 16, 16)
    with pytest.raises(ValueError, match="whole groups"):
        DR.delta_rule(q, k, v, g, beta)
