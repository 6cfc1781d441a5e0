"""The grouped products of an expert layer's FIRST matrix ``[held, D, F]``
under both views (ops/moe.py, PR 45): where ``F`` is no multiple of 128 and
``D`` is one, the TPU keeps the leaf transposed and the three products read
``swapaxes(w, 1, 2)``; elsewhere they read the leaf as it is stored.  One
algorithm either way, so value and every gradient equal the plain loop over
the experts in float64, on the XLA path and on the kernels (interpret mode
here), with experts of two matrices and of three.  The compile for the
described chip that shows no copy of the leaf is tests/test_tpu_compile.py's.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import moe as M

VALUE_TOL, GRAD_TOL = 1e-5, 2e-4        # tests/test_nemotron_h.py's
N, K, EXPERTS, HELD, FIRST, TM = 64, 2, 5, 3, 1, 8

#: (D, F, the view engages).  704 = 5.5 x 128 is over the products' tile of
#: 512 with no divisor, 1088 = 8.5 x 128 over the weight gradient's 1024: a
#: last block hangs over the edge of ``F`` wherever ``F`` is a result's axis
SHAPES = [(256, 192, True), (256, 704, True), (256, 1088, True),
          (256, 256, False), (192, 192, False)]


def rel(got, want):
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(np.asarray(got, np.float64) - want)
                 / max(np.linalg.norm(want), 1e-30))


def _inputs(D, F, gated):
    r = np.random.default_rng(D * 10007 + F)
    draw = lambda *s: r.standard_normal(s).astype(np.float32)  # noqa: E731
    idx = r.integers(0, EXPERTS, (N, K)).astype(np.int32)
    idx[idx == FIRST + 1] = 0       # the second expert held gets no row
    args = {"x": draw(N, D), "wts": r.random((N, K)).astype(np.float32),
            "w1": draw(HELD, D, F) * D ** -0.5,
            "w2": draw(HELD, F, D) * F ** -0.5}
    if gated:
        args["w3"] = draw(HELD, D, F) * D ** -0.5
    return idx, args


def _plain_loop(idx, a):
    """``sum over the choices held of weight * W_2e act(W_1e x)``, expert by
    expert over ALL the tokens, in the dtype of the arguments."""
    y = 0.0
    for e in range(HELD):
        coef = jnp.sum(jnp.where(idx == FIRST + e, a["wts"], 0.0), axis=1)
        h = a["x"] @ a["w1"][e]
        act = (jax.nn.silu(h) * (a["x"] @ a["w3"][e]) if "w3" in a
               else jnp.square(jax.nn.relu(h)))
        y = y + coef[:, None] * (act @ a["w2"][e])
    return y


@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "gated"])
@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
@pytest.mark.parametrize("D,F,view", SHAPES,
                         ids=[f"{d}x{f}" for d, f, _ in SHAPES])
def test_first_matrix_products_match_the_plain_loop(D, F, view, kernels,
                                                    gated):
    idx, args = _inputs(D, F, gated)
    assert M._kept_transposed(jnp.zeros((HELD, D, F))) is view
    if view:    # a hanging block where the tile does not divide F
        assert F <= 512 or M._largest_tile(F, 512) == 512 and F % 512
    counts, order = M.count_assignments(
        jnp.asarray(idx), first_expert=FIRST, held=HELD)
    g = M.group_assignments(
        counts, order, tm=TM,
        rows=M.buffer_rows(N, K, EXPERTS, HELD, TM)[1])
    assert int(g.counts[1]) == 0 and int(g.uncomputed) == 0
    w = np.random.default_rng(1).standard_normal((N, D))

    def layer(a):
        return M.grouped_expert_mlp(a["x"], a["wts"], g, a["w1"],
                                    a.get("w3"), a["w2"], tm=TM,
                                    kernels=kernels, expert_act=(
                                        "gated_silu" if "w3" in a
                                        else "relu2"))

    got, got_g = jax.value_and_grad(
        lambda a: jnp.sum(layer(a) * w.astype(np.float32)))(
            {k: jnp.asarray(v) for k, v in args.items()})
    with jax.enable_x64(True):
        want, want_g = jax.value_and_grad(
            lambda a: jnp.sum(_plain_loop(idx, a) * w))(
                {k: jnp.asarray(v, jnp.float64) for k, v in args.items()})
        want, want_g = float(want), {k: np.asarray(v)
                                     for k, v in want_g.items()}
    assert float(got) == pytest.approx(want, rel=VALUE_TOL)
    assert sorted(got_g) == sorted(want_g)
    for name in want_g:
        assert got_g[name].shape == args[name].shape
        assert got_g[name].dtype == jnp.float32
        assert rel(got_g[name], want_g[name]) <= GRAD_TOL, name
    # the expert no row went to: a zero gradient, not an unwritten block
    assert not np.any(np.asarray(got_g["w1"][1]))


def test_the_view_is_counted_when_the_layer_is_traced():
    """``moe_weight_view_total{view=}``: one count a first matrix and trace
    of the forward, and the view's products carry the scope ``w_view_t``
    inside ``moe_experts``."""
    from paddle_tpu.obs import get_registry

    def count(view):
        return get_registry().counter("moe_weight_view_total",
                                      labels=("view",), view=view).value

    def layer(D, F, gated):
        idx, a = _inputs(D, F, gated)
        return a, lambda a: jnp.sum(M.expert_layer(
            a["x"], jnp.asarray(idx), a["wts"], a["w1"], a.get("w3"),
            a["w2"], num_experts=EXPERTS, first_expert=FIRST, tm=TM,
            kernels=False,
            expert_act="gated_silu" if gated else "relu2")[0])

    def trace(D, F, gated):
        a, f = layer(D, F, gated)
        jax.make_jaxpr(f)(a)

    usual, worst = M.buffer_rows(N, K, EXPERTS, HELD, TM)
    traces = 1 if usual == worst else 2     # the cond's branches
    before = count("transposed"), count("stored")
    trace(256, 192, False)
    assert (count("transposed"), count("stored")) == (before[0] + traces,
                                                      before[1])
    trace(256, 256, True)       # w1 and w3
    assert (count("transposed"), count("stored")) == (
        before[0] + traces, before[1] + 2 * traces)

    def scopes(D, F):
        a, f = layer(D, F, False)
        text = jax.jit(jax.grad(f)).lower(a).as_text(debug_info=True)
        return re.search(rf"moe_experts\)*/{M.VIEW_SCOPE}/", text) is not None

    assert scopes(256, 192) and not scopes(256, 256)
