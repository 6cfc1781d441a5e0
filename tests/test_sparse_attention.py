"""Learned sparse attention (PR 47): ``ops.sparse_attention`` on its XLA path
and on its five kernels in interpret mode, against a dense float32 form
written here (``lax.top_k``, a scatter, full ``[T, T]`` softmaxes); the layer
``nn.indexed_self_attention`` and the whole ``keye_vl2_net`` against the
plain reference of benchmark/reference on seeded weights (loss, every leaf's
gradient); the share test of the model-configs guide, section 4.

Program and reference are both float32 here, so both sides compute the
indexer's scores in float32 and select the SAME positions: the share of
pairs selected differently is held at exactly 0, and what is left between
them is rounding and the order of sums."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.nn as nn
from paddle_tpu.ops import decoder_block as DB
from paddle_tpu.ops import pallas_kernels as PK
from paddle_tpu.ops import sparse_attention as SA

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import correct, manifest  # noqa: E402

NAME = "keye-vl-2.0-30b-a3b-ep16"
PATHS = ["xla", "kernels"]
#: hidden 64; 4 query heads of 16 over 2 key-value heads; an indexer of 2
#: heads of 16 that keeps 64; 8 experts of 48 with 2 held, top 3; T 256
CFG = dict(
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16,
    sa_config=dict(indexer_head_dim=16, indexer_num_heads=2,
                   indexer_num_kv_heads=1, kv_chunk_size=512,
                   q_chunk_size=512, topk=64),
    moe_intermediate_size=48, router_outputs=8, num_experts=2,
    first_expert=2, num_experts_per_tok=3, norm_topk_prob=True,
    rms_norm_eps=1e-6, rope_theta=1e7, vocab_size=50)
B, T = 2, 256


@pytest.fixture
def path(request, monkeypatch):
    """``kernels``: the gate opens with tiles of 128 and the five kernels
    run in interpret mode; ``xla``: the gate is as the CPU leaves it."""
    if request.param == "kernels":
        monkeypatch.setattr(SA, "sparse_kernel_blocks",
                            lambda T, *a: 128 if T % 128 == 0 else None)
    return request.param


@pytest.fixture(scope="module")
def ref():
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "reference", NAME + ".py"), "keye_ref")


@pytest.fixture(scope="module")
def program_file():
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "programs", NAME + ".py"), "keye_program")


def rel(got, want):
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


# -- the op against a dense form ---------------------------------------------


def operands(t=T, heads=4, kv_heads=2, dh=64, idx_heads=3, d=64, seed=0,
             ties=True):
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)  # noqa: E731
    q, k, v = f(B, t, heads, dh), f(B, t, kv_heads, dh), f(B, t, kv_heads, dh)
    qI, kI, w = f(B, t, idx_heads, d), f(B, t, d), f(B, t, idx_heads) / 8
    if ties:      # four positions with one key: their scores are equal
        kI = kI.at[:, 5:9].set(kI[:, 4:5])
    return q, k, v, qI, kI, w


def dense_selection(scores, topk):
    """bool ``[B, T, T]``: ``lax.top_k`` over the masked row and a scatter."""
    Bn, t, _ = scores.shape
    causal = jnp.tril(jnp.ones((t, t), bool))
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, t))
    chosen = jnp.zeros(scores.shape, bool).at[
        jnp.arange(Bn)[:, None, None], jnp.arange(t)[None, :, None],
        idx].set(True)
    return chosen & causal


def dense(q, k, v, qI, kI, w, scale, topk):
    """(out, L_I [B], keep, target p) with full ``[T, T]`` arrays."""
    G = q.shape[2] // k.shape[2]
    with jax.default_matmul_precision("highest"):
        pre = jnp.einsum("bqjd,bkd->bjqk", qI, kI)
        scores = jnp.einsum("bqj,bjqk->bqk", w, jax.nn.relu(pre))
        keep = dense_selection(jax.lax.stop_gradient(scores), topk)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, G, 2)) * scale
        a = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf), -1)
        out = jnp.einsum("bhqk,bkhd->bqhd", a, jnp.repeat(v, G, 2))
        p = jax.lax.stop_gradient(a.mean(1))
        logq = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
        kl = jnp.sum(jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0))
                                           - jnp.where(keep, logq, 0.0)),
                               0.0), (1, 2))
    return out, kl, keep, p


@pytest.mark.parametrize("path", PATHS, indirect=True)
@pytest.mark.parametrize("t,topk,heads", [(256, 64, 4), (384, 100, 4),
                                          (384, 100, 8)])
def test_op_matches_the_dense_form(path, t, topk, heads):
    """Output, ``L_I``, the pairs kept and all six gradients, with repeated
    scores in every row; a loss that weighs both the output and ``L_I``.
    With 8 heads a key-value head's group is 4 wide: ``indexer_loss`` adds a
    group in one grid step, over three tiles a side."""
    args = operands(t, heads=heads)
    scale = 64 ** -0.5
    weights = jnp.cos(jnp.arange(args[0].size, dtype=jnp.float32).reshape(
        args[0].shape) * 0.1)

    def loss(fn):
        def of(*a):
            out, kl = fn(*a)[:2]
            return jnp.sum(out * weights) + 2.0 * jnp.sum(kl)
        return of

    program = lambda *a: SA.sparse_attention(  # noqa: E731
        *a, scale=scale, topk=topk)
    plain = lambda *a: dense(*a, scale, topk)  # noqa: E731
    out, kl, kept = program(*args)
    want_out, want_kl, keep, _ = plain(*args)
    assert rel(out, want_out) <= 1e-5
    np.testing.assert_allclose(kl, want_kl, rtol=1e-5)
    np.testing.assert_array_equal(kept, keep.sum((1, 2)))
    got = jax.grad(loss(program), argnums=range(6))(*args)
    want = jax.grad(loss(plain), argnums=range(6))(*args)
    for name, g, g0 in zip(("q", "k", "v", "qI", "kI", "w"), got, want):
        assert rel(g, g0) <= 2e-5, name


def tied_scores(t, seed=1):
    """Rows of scores drawn from FIVE values (and ``-inf`` in the future):
    every threshold is a tie many positions wide."""
    rng = np.random.RandomState(seed)
    s = rng.randint(-2, 3, (B, t, t)).astype(np.float32) * 0.5
    s[:, :, 0] = 0.0                        # exact zeros too
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)


@pytest.mark.parametrize("topk", [1, 7, 100, 256, 300])
def test_selection_keeps_min_t_plus_1_k_none_in_the_future_ties_by_position(
        topk):
    """Both selection routines (the XLA path's, by the k-th largest value
    and a count; the kernel's, by 32 + 8 counting passes) against
    ``lax.top_k`` and a scatter on rows with repeated values: exactly
    ``min(t + 1, k)`` a row, none in the future, among equal scores the
    lower position first."""
    t = 256
    scores = tied_scores(t)
    want = dense_selection(scores, topk)
    np.testing.assert_array_equal(
        want.sum(-1)[0], np.minimum(np.arange(t) + 1, topk))
    assert not bool(jnp.any(want & ~jnp.tril(jnp.ones((t, t), bool))))
    np.testing.assert_array_equal(SA.select_topk(scores, 0, topk), want)
    lo = 128                                # a later block of queries
    np.testing.assert_array_equal(
        SA.select_topk(scores[:, lo:], lo, topk), want[:, lo:])
    keep, lse = PK.topk_select_pallas(scores, topk=topk, rows=128)
    np.testing.assert_array_equal(keep.astype(bool), want)
    np.testing.assert_allclose(
        lse[..., 0], jax.nn.logsumexp(jnp.where(want, scores, -jnp.inf), -1),
        rtol=1e-6)


def test_threshold_routine_orders_floats_as_floats():
    """The kernel finds a row's threshold on the scores' BITS: negative
    values, tiny and huge values are ordered as floats, and ``-0.0`` is
    ``+0.0`` (a tie, kept by position), as the XLA path's comparison of
    floats has it; ``lax.top_k`` may order the two zeros, so against it the
    rows are compared by the VALUES kept."""
    t = 128
    rng = np.random.RandomState(5)
    vals = np.concatenate([rng.randn(40) * 1e3, rng.randn(40) * 1e-3,
                           [0.0, -0.0, 1e-30, -1e-30, 3e38, -3e38],
                           rng.randn(42)]).astype(np.float32)
    rows = np.stack([rng.permutation(vals) for _ in range(t)])
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), rows[None], -jnp.inf)
    for topk in (5, 64):
        keep, _ = PK.topk_select_pallas(scores, topk=topk, rows=128)
        want = dense_selection(scores, topk)
        np.testing.assert_array_equal(keep.astype(bool),
                                      SA.select_topk(scores, 0, topk))
        same = np.asarray((keep.astype(bool) == want).all(-1))[0]
        kept_vals = np.sort(np.where(np.asarray(keep[0], bool), rows, np.inf))
        want_vals = np.sort(np.where(np.asarray(want[0]), rows, np.inf))
        np.testing.assert_array_equal(kept_vals, want_vals)
        assert same.mean() > 0.9


@pytest.mark.parametrize("path", PATHS, indirect=True)
def test_short_rows_are_causal_attention(path):
    """At ``T <= topk`` every past position is kept and the output is
    ``causal_attention``'s: bit for bit on the XLA path, to rounding between
    the selected flash kernel and the XLA loop."""
    q, k, v, qI, kI, w = operands(256)
    out, _, kept = SA.sparse_attention(q, k, v, qI, kI, w, scale=0.125,
                                       topk=256)
    want = DB.causal_attention(q, k, v, scale=0.125)
    np.testing.assert_array_equal(kept, 256 * 257 // 2)
    if path == "xla":
        np.testing.assert_array_equal(out, want)
    else:
        assert rel(out, want) <= 1e-6


@pytest.mark.parametrize("path", PATHS, indirect=True)
def test_the_two_halves_of_the_gradient_are_exactly_zero(path):
    """A loss on the output moves q, k, v and not the indexer's operands; a
    loss on ``L_I`` moves the indexer's operands and not q, k, v: both
    halves exactly 0."""
    args = operands(256)
    run = lambda *a: SA.sparse_attention(  # noqa: E731
        *a, scale=0.125, topk=64)
    on_out = jax.grad(lambda *a: jnp.sum(jnp.sin(run(*a)[0])),
                      argnums=range(6))(*args)
    on_kl = jax.grad(lambda *a: jnp.sum(run(*a)[1]),
                     argnums=range(6))(*args)
    for g in on_out[3:] + on_kl[:3]:
        assert not bool(jnp.any(g))
    for g in on_out[:3] + on_kl[3:]:
        assert float(jnp.linalg.norm(g)) > 0


def test_the_target_sums_to_one_over_the_kept_positions():
    """The heads' mean probability over ``S_t`` sums to 1 a row and is 0
    elsewhere, and agrees with the dense form's."""
    q, k, v, qI, kI, w = operands(256)
    scale, topk = 0.125, 64
    keep = SA.select_topk(SA.indexer_scores(qI, kI, w, 0, 256), 0, topk)
    qg = q.reshape(B, 256, 2, 2, 64)
    _, lse = DB._xla_fwd(qg, k, v, scale, 256, keep=[keep])
    p = SA.block_target(qg, k, lse, keep, 0, 256, scale)
    np.testing.assert_allclose(p.sum(-1), 1.0, rtol=1e-5)
    assert not bool(jnp.any(jnp.where(keep, 0.0, p)))
    np.testing.assert_allclose(p, dense(q, k, v, qI, kI, w, scale, topk)[3],
                               atol=1e-6)


@pytest.mark.parametrize("path", PATHS, indirect=True)
@pytest.mark.parametrize("t,heads,lengths", [(256, 4, (256, 200)),
                                             (384, 8, (380, 200))])
def test_padded_queries_add_nothing_to_the_indexers_loss(path, t, heads,
                                                         lengths,
                                                         monkeypatch):
    """``real`` 0 at a row's last queries: their pairs are not counted and
    ``L_I`` and its gradient are those of the real queries alone.  The
    second case has groups of 4 heads over 2 key-value heads, three tiles a
    side and both rows padded: a group's sum crosses the kernel's slabs, its
    tiles and the diagonal; there the kernels are held to the XLA path
    too."""
    args = operands(t, heads=heads)
    real = jnp.asarray(np.arange(t)[None, :] < np.array(lengths)[:, None],
                       jnp.float32)
    run = lambda *a: SA.sparse_attention(  # noqa: E731
        *a, scale=0.125, topk=64, real=real)
    _, kl, kept = run(*args)
    _, _, keep, p = dense(*args, 0.125, 64)
    np.testing.assert_array_equal(
        kept, (keep * real[..., None].astype(bool)).sum((1, 2)))

    def plain(qI, kI, w):
        pre = jnp.einsum("bqjd,bkd->bjqk", qI, kI)
        scores = jnp.einsum("bqj,bjqk->bqk", w, jax.nn.relu(pre))
        logq = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
        rows = jnp.sum(jnp.where(p > 0, p * (jnp.log(jnp.where(
            p > 0, p, 1.0)) - jnp.where(keep, logq, 0.0)), 0.0), -1)
        return jnp.sum(rows * real)

    with jax.default_matmul_precision("highest"):
        want, want_g = jax.value_and_grad(plain, argnums=(0, 1, 2))(*args[3:])
    np.testing.assert_allclose(jnp.sum(kl), want, rtol=1e-5)
    got_g = jax.grad(lambda *a: jnp.sum(run(*a)[1]),
                     argnums=(3, 4, 5))(*args)
    for g, g0 in zip(got_g, want_g):
        assert rel(g, g0) <= 2e-5
    if path == "kernels":
        monkeypatch.setattr(SA, "sparse_kernel_blocks", lambda *a: None)
        np.testing.assert_allclose(kl, run(*args)[1], rtol=1e-5)
        xla_g = jax.grad(lambda *a: jnp.sum(run(*a)[1]),
                         argnums=(3, 4, 5))(*args)
        for g, g0 in zip(got_g, xla_g):
            assert rel(g, g0) <= 2e-5


def loss_kernel_operands(t, block):
    """``indexer_loss_pallas``'s operands as the kernels' path makes them,
    heads-major, float32; 8 query heads over 2 key-value heads."""
    q, k, v, qI, kI, w = operands(t, heads=8)
    qh, kh, vh, qIh = (jnp.swapaxes(a, 1, 2) for a in (q, k, v, qI))
    scores = PK.indexer_scores_pallas(qIh, kI, w, block=block)
    keep, lse_i = PK.topk_select_pallas(scores, topk=100, rows=128)
    _, lse = PK.flash_attn_fwd_pallas(qh, kh, vh, scale=0.125, block_q=block,
                                      block_k=block, keep=keep)
    real = jnp.ones((B, t, 1), jnp.float32)
    return [qh, kh, lse, qIh, kI, w, lse_i, real, keep], scores


def test_indexer_loss_reads_minus_inf_above_the_diagonal_as_nothing():
    """The scores the kernel reads hold ``-inf`` above the diagonal where
    the tile it used to make again held finite numbers: every use is under
    the selection's mask, so no NaN reaches ``kl`` or a gradient, and the
    results are bit for bit those of scores that are finite there."""
    t, block = 256, 128
    args, scores = loss_kernel_operands(t, block)
    assert bool(jnp.all(jnp.isneginf(scores[:, 0, 1:])))
    got = PK.indexer_loss_pallas(*args, scores, scale=0.125, block=block)
    finite = jnp.where(jnp.isneginf(scores), 0.25, scores)
    want = PK.indexer_loss_pallas(*args, finite, scale=0.125, block=block)
    for name, a, b in zip(("kl", "dqI", "dkI", "dw"), got, want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        np.testing.assert_array_equal(a, b, err_msg=name)
    assert float(jnp.sum(got[0])) > 0


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


def test_indexer_loss_steps_once_a_tile_and_key_value_head():
    """At the cell's shapes (``tests/test_tpu_compile.py`` ``KEYE``: a row of
    16384 in tiles of 1024, 32 query heads over 4 key-value heads) the
    kernel's grid is a tile by a KEY-VALUE head, 1,024 steps: the query
    head is no grid axis (PR 53; it was ``(1, 16, 16, 32)``)."""
    T_, H, Hkv, dh, J, d, block = 16384, 32, 4, 128, 16, 64, 1024
    s = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt)
    f32 = jnp.float32
    grids = _grids(
        lambda *a: PK.indexer_loss_pallas(*a, scale=dh ** -0.5, block=block),
        s((1, H, T_, dh)), s((1, Hkv, T_, dh)), s((1, H, T_, 1), f32),
        s((1, J, T_, d)), s((1, T_, d)), s((1, T_, J), f32),
        s((1, T_, 1), f32), s((1, T_, 1), f32), s((1, T_, T_), jnp.int8),
        s((1, T_, T_), f32))
    assert grids == {"indexer_loss": (1, 16, 16, 4)}


# -- the layer and the model against the plain reference ---------------------


def feed(seed=0, t=T):
    ids = np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], (B, t + 1)).astype(np.int32)
    lengths = np.full((B,), t, np.int32)
    return {"tokens": (ids[:, :-1], lengths),
            "next_tokens": (ids[:, 1:], lengths)}


INDEXER = ("wiq", "wik", "wiw", "ik_norm", "ik_bias")
LEAVES = sorted(
    ["_emb.w0", "_norm_out.w", "_cost.w"]
    + [f"_norm_{s}{i}.w" for i in range(2) for s in ("op", "ffn")]
    + [f"_attn{i}.{p}" for i in range(2)
       for p in ("wq", "wk", "wv", "wo", "q_norm", "k_norm") + INDEXER]
    + [f"_moe{i}.{p}" for i in range(2) for p in ("router", "w1", "w3", "w2")])


def model_numbers(ref, program_file, path):
    cost, extras = program_file.net(dict(CFG, recompute_layers=[0, 1]))
    topo = nn.Topology([cost] + extras)
    params = correct.init_params(ref, CFG, 3)
    assert ({k: v.shape for k, v in topo.init(jax.random.PRNGKey(0))[0].items()}
            == {k: v.shape for k, v in params.items()})
    assert sorted(params) == LEAVES
    batch = feed()

    def program(p):
        outs, _ = topo.apply(p, {}, batch, train=True)
        return outs["cost"].value, {e.name: outs[e.name].value
                                    for e in extras}

    def reference(p):
        total, count = ref.loss_sum(CFG, p, batch)
        return total / count

    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.value_and_grad(reference))(params)
        got = jax.jit(jax.value_and_grad(program, has_aux=True))(params)
        _, _, kept = ref.hidden(CFG, params, batch["tokens"][0])
    return got, want, [int(n) for n in kept]


@pytest.mark.parametrize("path", PATHS, indirect=True)
def test_model_matches_the_reference(ref, program_file, path):
    """Loss (cross-entropy plus the layers' ``L_I``, over the tokens) and
    every leaf's gradient; the program's count of kept pairs is the
    reference's own and the share selected differently is exactly 0 (both
    sides compute the scores in float32 here)."""
    ((loss, extras), grads), (want_loss, want_grads), kept = model_numbers(
        ref, program_file, path)
    assert abs(float(loss) - float(want_loss)) <= 1e-5 * float(want_loss)
    for leaf in LEAVES:
        assert rel(grads[leaf], want_grads[leaf]) <= 1e-3, leaf
    pairs = B * (64 * 65 // 2 + (T - 64) * 64)
    assert kept == [pairs, pairs]
    assert [int(extras[f"attn{i}_kept"]) for i in range(2)] == kept
    assert all(float(extras[f"attn{i}_kl"]) > 0 for i in range(2))


def test_program_and_reference_select_the_same_positions(ref):
    """The layer's selection (``ops.sparse_attention``'s routines on the
    layer's own operands) against the reference's (``lax.top_k`` and a
    scatter), both in float32: 0 pairs of B x T x T differ."""
    params = correct.init_params(ref, CFG, 5)
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (B, T, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        qI, kI, w = ref.indexer_inputs(CFG, params, "_attn0", x)
        want = ref.block_selection(ref.block_scores(qI, kI, w, 0), 0, 64)
        got = SA.select_topk(SA.indexer_scores(
            qI.transpose(0, 2, 1, 3), kI, w, 0, T), 0, 64)
    assert int(jnp.sum(got != want)) == 0
    assert int(jnp.sum(want)) == B * (64 * 65 // 2 + (T - 64) * 64)


@pytest.mark.parametrize("path", PATHS, indirect=True)
def test_cross_entropy_and_indexer_loss_move_disjoint_leaves(ref,
                                                             program_file,
                                                             path):
    """Through the whole model: the cross-entropy's gradient into the
    indexer's five leaves a layer and ``L_I``'s into every other leaf are
    exactly 0 (the two ``stop_gradient``s)."""
    cost, extras = program_file.net(dict(CFG, recompute_layers=[]))
    kl_names = [e.name for e in extras if e.name.endswith("_kl")]
    topo = nn.Topology([cost] + extras)
    params = correct.init_params(ref, CFG, 4)
    batch = feed(1)

    def terms(p):
        outs, _ = topo.apply(p, {}, batch, train=True)
        kl = sum(outs[n].value for n in kl_names)
        return outs["cost"].value - kl / (B * T), kl

    g_ce = jax.grad(lambda p: terms(p)[0])(params)
    g_kl = jax.grad(lambda p: terms(p)[1])(params)
    for leaf in LEAVES:
        indexer = leaf.rsplit(".", 1)[1] in INDEXER
        zero, moved = (g_kl, g_ce) if not indexer else (g_ce, g_kl)
        if indexer:
            # the cost layer adds L_I back: the cross-entropy alone is the
            # cost minus it, and cancels to rounding, not to 0, so the
            # indexer's half is read from the op-level test above; here the
            # leaf's gradient under the cost IS L_I's over the tokens
            assert rel(g_ce[leaf] + g_kl[leaf] / (B * T),
                       g_kl[leaf] / (B * T)) <= 1e-5, leaf
        else:
            assert not bool(jnp.any(zero[leaf])), leaf
        assert float(jnp.linalg.norm(moved[leaf])) > 0 or indexer, leaf


def test_shares_add_up_to_the_uncut_layer_with_attention_counted_once(ref):
    """Section 4's share test: the routed parts that the four shares of two
    experts give (the cell's sixteen of eight, at a toy size), with the
    attention under the indexer, which every chip computes alike on its own
    tokens, counted ONCE, add up to what the uncut reference (all eight
    experts) gives for the whole layer."""
    cfg = dict(CFG, num_experts=8, first_expert=0)
    shapes = ref.param_shapes(cfg)
    names = [k for k in shapes if k.startswith(("_attn0.", "_moe0.",
                                                "_norm_op0.", "_norm_ffn0."))]
    whole = correct.init_params(
        type("R", (), {"param_shapes": staticmethod(
            lambda c: {k: shapes[k] for k in names})}), cfg, 9)
    x = np.random.default_rng(8).standard_normal((B, T, 64)).astype(np.float32)
    lengths = np.full((B,), T, np.int32)
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref.layer(cfg, whole, 0, jnp.asarray(x))
        nn.reset_naming()
        data = nn.data("x", size=64, is_seq=True)
        attn = nn.indexed_self_attention(
            nn.rms_norm(data, eps=1e-6, name="norm_op0"), num_heads=4,
            num_kv_heads=2, head_dim=16, indexer_heads=2,
            indexer_head_dim=16, topk=64, rope_theta=1e7, name="attn0")
        h = nn.addto([data, attn], name="h")
        once = nn.Topology(h).apply(whole, {}, {"x": (x, lengths)})[0][
            "h"].value
        total, load = once, []
        for first in range(0, 8, 2):
            nn.reset_naming()
            node = nn.expert_mlp(
                nn.rms_norm(nn.data("h", size=64, is_seq=True), eps=1e-6,
                            name="norm_ffn0"), 48, num_experts=8,
                experts_held=(first, 2), top_k=3, scoring="softmax",
                name="moe0")
            share = dict(whole)
            for leaf in ("w1", "w3", "w2"):
                share[f"_moe0.{leaf}"] = whole[f"_moe0.{leaf}"][first:first + 2]
            out = nn.Topology(node).apply(
                share, {}, {"h": (once, lengths)})[0][node.name]
            total = total + out.value            # this chip's routed part
            load += list(np.asarray(out.state["expert_load"]))
    assert rel(total, want) <= 1e-5
    assert sum(load) == B * T * 3       # every choice landed on one chip


def test_layer_refuses_heads_that_are_not_whole_groups():
    from paddle_tpu.utils.error import ConfigError

    with pytest.raises(ConfigError, match="whole groups"):
        nn.indexed_self_attention(nn.data("x", size=64, is_seq=True),
                                  num_heads=5, num_kv_heads=2, head_dim=16,
                                  indexer_heads=2, indexer_head_dim=16,
                                  topk=8)
