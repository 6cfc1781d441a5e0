"""The gated delta rule's chunked scan (ops/delta_rule.py, PR 41) against the
token-by-token recurrence it stands for: values and all five gradients, on
the ``jax.numpy`` path (a ``lax.scan`` over chunks, differentiated by JAX)
and through the Pallas kernels in interpret mode (``gdn_chunk_fwd`` and the
hand-written reverse walk ``gdn_chunk_bwd``), at 1, 2 and 5 chunks, over two
kernel blocks, off the chunk length, and with a decay of ``exp(-30)`` a
token, where anything that divided by a decay would overflow.  Since PR 55
the forward kernel writes every chunk's 64 x 64 solve and the reverse walk
reads it: held, bit for bit, to the walk that solved each chunk again.

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
    _, states, _ = PK.gdn_chunk_fwd_pallas(
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


def kernel_operands(seed, chunks, Hk=1, H=2, dk=16, dv=24):
    """What the two kernels take: heads-major ``q``, ``k`` at ``Hk`` key
    heads, ``v`` at ``H`` value heads, ``gamma`` and ``beta`` a chunk a
    row."""
    T = chunks * DR.CHUNK
    q, k, _, _, _ = inputs(seed, 1, T, Hk, dk, dv)
    _, _, v, g, beta = inputs(seed + 1, 1, T, H, dk, dv)
    hm = lambda a: jnp.moveaxis(a, 2, 1)   # noqa: E731
    gamma = jnp.cumsum(hm(g).reshape(1, H, chunks, DR.CHUNK), axis=-1)
    return (hm(q), hm(k), hm(v), gamma,
            hm(beta).reshape(1, H, chunks, DR.CHUNK))


@pytest.mark.parametrize("chunks", [1, 2, 5, 16])
def test_forward_kernel_writes_every_chunks_solve(chunks):
    """The third result is ``_unit_lower_inverse(A)`` of every chunk and
    value head as ``chunk_forward`` made it, float32 and not rounded, chunk
    ``c`` in columns ``64 c`` on (two chunks a lane tile on the chip)."""
    q, k, v, gamma, beta = kernel_operands(30 + chunks, chunks)
    C = DR.CHUNK
    _, _, solves = PK.gdn_chunk_fwd_pallas(q, k, v, gamma, beta)
    assert solves.shape == (1, 2, C, chunks * C)
    assert solves.dtype == jnp.float32
    for h in range(2):
        for c in range(chunks):
            rows = slice(c * C, (c + 1) * C)
            grow, brow = gamma[0, h, c][None, :], beta[0, h, c][None, :]
            want = DR._unit_lower_inverse(DR._chunk_parts(
                q[0, 0, rows], k[0, 0, rows], DR.col_of_row(grow), grow,
                DR.col_of_row(brow), q.dtype)["A"])
            got = np.asarray(solves[0, h, :, rows])
            np.testing.assert_array_equal(got, want, err_msg=f"{h}, {c}")
            assert not np.any(np.triu(got, 1)) and np.all(np.diag(got) == 1)


def parents_chunk_backward(q, k, v, gcol, grow, bcol, S, dO, dS1, dt):
    """``chunk_backward`` as it stood until PR 55, which began with the
    chunk's own solve (ten float32 products at ``highest``): the reference
    the reverse kernel's results are held to, bit for bit."""
    _dot = DR._dot
    p = DR._chunk_parts(q, k, gcol, grow, bcol, dt)
    qf, kf, vf, Kb, R, Vn = DR._chunk_state_parts(p, q, k, v, bcol, S, dt)
    e, ec, a, decay = p["e"], p["ec"], p["a"], p["decay"]
    Qe, Ke = e * qf, ec * kf

    dVn = _dot(p["P"], dO, 0, 0, dt) + _dot(Ke, dS1, 1, 0, dt)
    dP = _dot(dO, Vn, 1, 1, dt)
    dQe = _dot(dO, S, 1, 1, dt)
    dKe = _dot(Vn, dS1, 1, 1, dt)
    dR = _dot(p["T"], dVn, 0, 0, dt)
    dA = jnp.where(p["strict"], -_dot(dR, Vn, 1, 1, dt), 0.0)
    dKb = -_dot(dR, S, 1, 1, dt)
    dS = (_dot(Qe, dO, 0, 0, dt) + a * dS1 - _dot(Kb, dR, 0, 0, dt))

    dKK = dA * bcol * decay
    dQK = dP * decay
    dq = _dot(dQK, k, 1, 0, dt) + e * dQe
    dk = (_dot(dKK, k, 1, 0, dt) + _dot(dKK, k, 0, 0, dt)
          + _dot(dQK, q, 0, 0, dt) + (bcol * e) * dKb + ec * dKe)
    dv = bcol * dR
    dbeta = (jnp.sum(dA * decay * p["kk"], axis=1, keepdims=True)
             + jnp.sum(dKb * (e * kf), axis=1, keepdims=True)
             + jnp.sum(dR * vf, axis=1, keepdims=True))
    G = dA * p["A"] + dP * p["P"]
    to_end = jnp.sum(dKe * Ke, axis=1, keepdims=True)
    dg_col = (jnp.sum(G, axis=1, keepdims=True)
              + jnp.sum(dQe * Qe, axis=1, keepdims=True)
              + jnp.sum(dKb * Kb, axis=1, keepdims=True) - to_end)
    dg_row = -jnp.sum(G, axis=0, keepdims=True)
    dg_last = (jnp.sum(to_end, axis=0, keepdims=True)
               + a * jnp.sum(jnp.sum(dS1 * S, axis=1, keepdims=True), axis=0,
                             keepdims=True))
    return dq, dk, dv, dg_col, dg_row, dg_last, dbeta, dS


RESULTS = "dq dk dv dg_col dg_row dg_last dbeta dS".split()


def test_chunk_backward_given_the_solve_is_the_parents_bit_for_bit():
    """One chunk on 2-D arrays: the eight results with the forward's ``T``
    handed in equal the eight of the walk that solved the chunk again."""
    r = np.random.RandomState(41)
    q, k, v, gamma, beta = kernel_operands(41, 1, H=1)
    grow, brow = gamma[0, 0], beta[0, 0]
    gcol, bcol = DR.col_of_row(grow), DR.col_of_row(brow)
    S, dS1 = (jnp.asarray(r.randn(16, 24).astype(np.float32))
              for _ in range(2))
    dO = jnp.asarray(r.randn(DR.CHUNK, 24).astype(np.float32))
    chunk = (q[0, 0], k[0, 0], v[0, 0], gcol, grow, bcol, S)
    _, _, T = DR.chunk_forward(*chunk, q.dtype)
    got = DR.chunk_backward(*chunk, T, dO, dS1, q.dtype)
    want = parents_chunk_backward(*chunk, dO, dS1, q.dtype)
    for name, a, b in zip(RESULTS, got, want):
        assert np.asarray(b).any(), name
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.fixture
def unfused_cpu_programs():
    """XLA:CPU compiles an interpreted kernel's body as one program and fuses
    it as it likes: with the solve gone, ``A`` has one reader fewer, the
    sums of ``dA A + dP P`` fuse another way and ``dgamma`` moves in its
    last bit (72 of 256 elements by 3e-8 at two chunks; ``dq``, ``dk``,
    ``dv`` and ``dbeta`` do not).  With the optimisation passes off every
    equation is its own loop, as on 2-D arrays in eager mode, and what is
    compared is the algebra."""
    jax.config.update("jax_disable_most_optimizations", True)
    yield
    jax.config.update("jax_disable_most_optimizations", False)


@pytest.mark.parametrize("chunks", [1, 2, 5, 16])
def test_reverse_kernel_fed_the_forwards_solve_is_the_parents_bit_for_bit(
        chunks, monkeypatch, unfused_cpu_programs):
    """``gdn_chunk_bwd`` reads every chunk's ``T`` where the parent's made
    it again from the same operands by the same code: all five results
    (``dgamma`` holds three of ``chunk_backward``'s eight, ``dS`` rides in
    scratch from chunk to chunk and block to block) are the parent's to the
    last bit, over a group of two value heads on one key head."""
    q, k, v, gamma, beta = kernel_operands(50 + chunks, chunks)
    do = jnp.asarray(np.random.RandomState(chunks).randn(
        *v.shape).astype(np.float32))
    _, states, solves = PK.gdn_chunk_fwd_pallas(q, k, v, gamma, beta)
    # what the jitted wrapper wraps, so that the body is traced anew: once
    # as it stands and once with the parent's walk, which takes no solve, in
    # ``chunk_backward``'s place
    reverse = PK.gdn_chunk_bwd_pallas.__wrapped__
    got = reverse(q, k, v, gamma, beta, states, solves, do, interpret=True)
    monkeypatch.setattr(
        DR, "chunk_backward",
        lambda q, k, v, gcol, grow, bcol, S, T, dO, dS1, dt:
        parents_chunk_backward(q, k, v, gcol, grow, bcol, S, dO, dS1, dt))
    want = reverse(q, k, v, gamma, beta, states, jnp.zeros_like(solves), do,
                   interpret=True)
    for name, a, b in zip("dq dk dv dgamma dbeta".split(), got, want):
        assert np.asarray(b).any(), name
        np.testing.assert_array_equal(a, b, err_msg=name)


def _calls(jaxpr, wrapper: str) -> int:
    """Calls of a kernel's jitted wrapper, through sub-jaxprs."""
    return sum((eqn.params.get("name") == wrapper)
               + sum(_calls(sub, wrapper)
                     for sub in jax.core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


def test_recomputation_block_runs_the_forward_kernel_once():
    """The output, the states and the solves are all named ``remat_keep``:
    under a block that saves those names alone, the backward's second
    forward has nothing left to ask ``gdn_chunk_fwd`` for."""
    args = inputs(61, 1, 2 * DR.CHUNK, 2, 16, 16)
    block = jax.checkpoint(
        lambda *a: jnp.sum(kernels_path(*a) ** 2),
        policy=jax.checkpoint_policies.save_only_these_names("remat_keep"))
    jaxpr = jax.make_jaxpr(jax.grad(block, argnums=(0, 1, 2, 3, 4)))(*args)
    assert _calls(jaxpr.jaxpr, "gdn_chunk_fwd_pallas") == 1
    assert _calls(jaxpr.jaxpr, "gdn_chunk_bwd_pallas") == 1


def test_gate_is_a_function_of_backend_and_shape(monkeypatch):
    assert DR.delta_rule_kernel_chunk(8192, 128, 128) is None     # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert DR.delta_rule_kernel_chunk(8192, 128, 128) == DR.CHUNK
    assert DR.delta_rule_kernel_chunk(8192, 64, 128) is None
    assert DR.delta_rule_kernel_chunk(8192, 128, 192) is None
    assert DR.delta_rule_kernel_chunk(8200, 128, 128) is None
    with PK.xla_paths_only():
        assert DR.delta_rule_kernel_chunk(8192, 128, 128) is None
