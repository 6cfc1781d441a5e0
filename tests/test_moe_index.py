"""The expert layer's index work (ops/moe.py ``route_tokens``,
``count_assignments``, ``group_assignments``) against the forms it replaced
(PR 49): the chosen scores by ``take_along_axis``, the sorted keys by
``key[order]``, the rows' assignments by a scatter of ``order``.  On the chip
each of those walks the ``N*k`` assignments an element at a time (5-10 ns an
element, PERF.md section 6); the forms in the program pick by comparison and
gather a buffer's rows.  Same integers and same floats, bit for bit, at the
five expert cells' routing shapes cut to test size and at the edges.  The
census of the compiled step that keeps the walk out is
tests/test_tpu_compile.py's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import moe as M

#: (N, E, k, held, first_expert, tm, scoring, bias, scaling): the cells'
#: routing with the tokens cut to 96 and the row tile to 8
CELLS = {
    "lfm2": (96, 64, 4, 8, 0, 8, "sigmoid", True, 1.0),
    "kanana2": (96, 128, 6, 16, 0, 8, "sigmoid", True, 2.448),
    "qwen3next": (96, 512, 10, 16, 0, 8, "softmax", False, 1.0),
    "nemotron3nano": (96, 128, 6, 8, 0, 8, "sigmoid", True, 2.5),
    "keyevl2": (96, 128, 8, 8, 0, 8, "softmax", False, 1.0),
}


def _route_by_gather(x, w_router, bias, *, top_k, norm_topk, scaling,
                     scoring):
    """``route_tokens`` as it stood: the chosen scores gathered."""
    logits = jnp.matmul(x, w_router, precision=jax.lax.Precision.HIGHEST)
    s = (jax.nn.sigmoid(logits) if scoring == "sigmoid"
         else jax.nn.softmax(logits, axis=-1))
    picked = s if bias is None else s + jax.lax.stop_gradient(bias)
    _, idx = jax.lax.top_k(picked, top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        chosen = chosen / (jnp.sum(chosen, -1, keepdims=True)
                           + (1e-6 if scoring == "sigmoid" else 0.0))
    return idx.astype(jnp.int32), chosen * scaling


ROUTINGS = [(name, c[1], c[2], c[6], c[7], True, c[8])
            for name, c in CELLS.items()] + [
    (f"{scoring}-{'bias' if bias else 'nobias'}-{'norm' if norm else 'raw'}",
     16, 3, scoring, bias, norm, 1.5)
    for scoring in ("sigmoid", "softmax") for bias in (False, True)
    for norm in (False, True)]


@pytest.mark.parametrize("name,E,k,scoring,bias,norm,scaling", ROUTINGS,
                         ids=[r[0] for r in ROUTINGS])
def test_router_weights_equal_the_gathered_ones(name, E, k, scoring, bias,
                                                norm, scaling):
    r = np.random.default_rng(E * 31 + k)
    N, D = 96, 32
    x = jnp.asarray(r.standard_normal((N, D)).astype(np.float32))
    w = jnp.asarray(r.standard_normal((D, E)).astype(np.float32) * D ** -0.5)
    b = jnp.asarray(r.standard_normal((E,)).astype(np.float32) * 0.1
                    ) if bias else None
    cot = jnp.asarray(r.standard_normal((N, k)).astype(np.float32))
    kw = dict(top_k=k, norm_topk=norm, scaling=scaling, scoring=scoring)
    idx, weights = M.route_tokens(x, w, b, **kw)
    want_idx, want = _route_by_gather(x, w, b, **kw)
    assert idx.dtype == want_idx.dtype and weights.dtype == want.dtype
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_array_equal(weights, want)

    def grads(route):
        return jax.grad(lambda x, w: jnp.sum(route(x, w, b, **kw)[1] * cot),
                        argnums=(0, 1))(x, w)

    for got, ref in zip(grads(M.route_tokens), grads(_route_by_gather)):
        assert np.abs(np.asarray(ref)).max() > 0
        np.testing.assert_array_equal(got, ref)


def _group_by_scatter(idx, *, first_expert, held, tm, rows):
    """``count_assignments`` + ``group_assignments`` as they stood: the
    sorted keys gathered, a row for every sorted assignment, ``order``
    scattered to the rows."""
    local = idx.reshape(-1) - first_expert
    key = jnp.where((local >= 0) & (local < held), local, held)
    key = key.astype(jnp.int32)
    A = key.shape[0]
    counts = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                     dtype=jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sorted_key = key[order]
    tiles = -(-counts // tm)
    group_row0 = (jnp.cumsum(tiles) - tiles) * tm
    sorted_start = jnp.cumsum(counts) - counts
    safe = jnp.minimum(sorted_key, held - 1)
    rank = jnp.arange(A, dtype=jnp.int32) - sorted_start[safe]
    row = jnp.where(sorted_key < held, group_row0[safe] + rank, rows)
    row = jnp.minimum(row, rows).astype(jnp.int32)
    row_assign = jnp.full((rows,), A, jnp.int32).at[row].set(order,
                                                             mode="drop")
    ends = jnp.cumsum(tiles)
    n_tiles = rows // tm
    tile_expert = jnp.minimum(
        jnp.sum(jnp.arange(n_tiles)[:, None] >= ends[None, :], axis=1),
        held - 1).astype(jnp.int32)
    placed = jnp.sum(row < rows, dtype=jnp.int32)
    return order, M.Grouping(
        row_assign, tile_expert,
        jnp.minimum(ends[-1:], n_tiles).astype(jnp.int32), counts,
        jnp.sum(counts) - placed)


def _choices(kind, N, E, k, held, first):
    r = np.random.default_rng(N + E * 7 + k * 131 + held)
    idx = np.stack([r.permutation(E)[:k] for _ in range(N)]).astype(np.int32)
    if kind == "empty_expert":      # an expert held that no token chose
        gone = first + held // 2
        idx[idx == gone] = (first + held) % E if held < E else gone
    elif kind == "one_expert":      # every token's first choice is one expert
        idx[:, 0] = first + held - 1
        idx[:, 1:] = (first + held + np.arange(k - 1)) % E
    elif kind == "padded":          # a padded position is no token
        idx[r.random(N) < 0.3] = -1
    elif kind == "none_held":
        idx[:] = (first + held + np.arange(k)) % E
    return jnp.asarray(idx)


GROUPINGS = [(name, "random") + c[:6] for name, c in CELLS.items()] + [
    ("lfm2", "empty_expert", 96, 64, 4, 8, 0, 8),
    ("kanana2", "empty_expert", 96, 128, 6, 16, 16, 8),
    ("keyevl2", "one_expert", 96, 128, 8, 8, 0, 8),
    ("qwen3next", "one_expert", 96, 512, 10, 16, 32, 8),
    ("nemotron3nano", "padded", 96, 128, 6, 8, 8, 8),
    ("lfm2", "padded", 40, 8, 2, 3, 2, 16),
    ("held1", "random", 64, 8, 2, 1, 3, 8),
    ("held1", "one_expert", 64, 8, 2, 1, 3, 8),
    ("held1", "padded", 64, 8, 2, 1, 0, 4),
    ("every_expert_held", "random", 64, 8, 3, 8, 0, 8),
    ("lfm2", "none_held", 64, 64, 4, 8, 8, 8),
]


@pytest.mark.parametrize("name,kind,N,E,k,held,first,tm", GROUPINGS,
                         ids=[f"{g[0]}-{g[1]}" for g in GROUPINGS])
def test_grouping_equals_the_scattered_one(name, kind, N, E, k, held, first,
                                           tm):
    idx = _choices(kind, N, E, k, held, first)
    counts, order = M.count_assignments(idx, first_expert=first, held=held)
    usual, worst = M.buffer_rows(N, k, E, held, tm)
    seen_short = False
    for rows in sorted({usual, worst, tm}):    # ``tm``: too small a buffer
        want_order, want = _group_by_scatter(idx, first_expert=first,
                                             held=held, tm=tm, rows=rows)
        got = M.group_assignments(counts, order, tm=tm, rows=rows)
        np.testing.assert_array_equal(order, want_order)
        for field, a, b in zip(M.Grouping._fields, got, want):
            assert a.dtype == b.dtype and a.shape == b.shape, field
            np.testing.assert_array_equal(a, b, err_msg=f"{field} {rows}")
        seen_short |= int(got.uncomputed) > 0
        if rows == worst:
            assert int(got.uncomputed) == 0
    if kind == "random":
        assert seen_short       # a buffer of one tile owned up to the rest
    if kind == "empty_expert":
        assert int(counts[held // 2]) == 0 < int(counts.sum())
    if kind == "none_held":
        assert int(counts.sum()) == 0 and int(got.n_active[0]) == 0
