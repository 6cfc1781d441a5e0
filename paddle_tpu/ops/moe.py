"""A dropless expert layer as ONE chip of an expert-parallel deployment runs
it: route every token over ALL the experts, keep the assignments that land
on the experts held here, group them by expert (ragged groups, no capacity,
nothing dropped), run each group's expert (a gated MLP or one of the other
forms of ``EXPERT_ACTS``) as grouped matrix
products, and combine the results back onto the tokens with the router's
weights.  What the experts held elsewhere would have added is left out: on
one chip the layer runs without its exchange, and nothing stands in for it.

Layout.  An assignment is a (token, choice) pair.  The assignments to the
experts held are sorted by expert and every group is padded to whole row
tiles of ``tm`` rows, so a tile belongs to one expert and the grouped
products are tile-by-tile dense products (``ops/pallas_kernels.py``
``moe_gmm`` / ``moe_tgmm`` on the TPU; a masked loop over the experts
elsewhere).  The row buffer has a usual size, twice the even share, and a
worst-case size (every token sends ``min(k, held)`` choices here); the layer
takes the small one whenever the step's routing fits it, so no routing drops
a token; only the tiles that hold rows are computed.

Index work.  A gather or a scatter of SCALARS runs on the TPU as a walk, 5-10
ns an element whatever the table's size, and a layer has ``A = N k``
assignments (131,072 at a row of 16384, top 8) where its buffer has a third
to a seventh as many rows.  So no step of the dispatch walks the
assignments: the router's chosen scores are picked by comparison (a one-hot
over the router's outputs summed, exact: one term is not zero), the sorted
keys are never made (the counts say them), and a row finds its assignment by
ONE gather of the buffer's rows out of ``order``; what is left over ``A``
is the stable sort itself, once a layer.  Rows of ``D`` numbers still move
by the buffer's rows (``_take_rows``, ``_add_rows``).  PERF.md section 6,
PR 49, has the chip's numbers.

Precision.  The router (``x W_r``, the sigmoid or softmax, the selection and
the weights) runs in float32 at ``highest`` precision whatever the operand
policy: a selection made on bf16 scores picks other experts than the float32
reference on near ties.  The experts' products take operands in the compute
dtype with float32 accumulation, like every other matrix product.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from paddle_tpu.ops.numerics import acc_dtype, compute_dtype, dot_dtype

__all__ = ["route_tokens", "count_assignments", "group_assignments",
           "grouped_expert_mlp", "expert_layer", "buffer_rows", "EXPERT_ACTS",
           "moe_kernel_row_tile", "Grouping"]


def _pick(table, idx):
    """``table[..., idx]`` along the last axis, by comparison: a one-hot of
    ``idx`` over the axis, the one term that is not zero summed out (exact),
    which XLA fuses into a pass over ``table``; its transpose is a select
    and a sum.  For where a gather would walk ``idx`` a scalar at a time
    (the module's "Index work")."""
    hot = idx[..., None] == jnp.arange(table.shape[-1], dtype=idx.dtype)
    return jnp.sum(jnp.where(hot, table, 0), axis=-1)


def route_tokens(x, w_router, bias, *, top_k: int, norm_topk: bool,
                 scaling: float, scoring: str = "sigmoid"):
    """x ``[N, D]`` -> (experts ``[N, k]`` int32, weights ``[N, k]``
    float32).  ``scoring="sigmoid"``: scores are ``sigmoid(x W_r)``, the
    ``top_k`` largest of ``score + bias`` are chosen (the bias enters the
    selection only, so its gradient is exactly zero) and, with
    ``norm_topk``, the chosen scores are divided by their sum plus 1e-6.
    ``scoring="softmax"``: scores are the softmax over all the router's
    outputs, the ``top_k`` largest are chosen (``bias`` is ``None``) and,
    with ``norm_topk``, divided by their sum, with no epsilon.  The chosen
    scores are picked from ``s`` by comparison with ``idx``, not gathered:
    one fused pass over ``s`` forward, a select and a sum over the choices
    backward, where ``take_along_axis`` walks ``N k`` scalars (and its
    gradient sorts as many indices for a scatter-add); the same floats."""
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"route_tokens: unknown scoring {scoring!r}")
    f32 = acc_dtype()
    logits = jnp.matmul(x.astype(f32), w_router.astype(f32),
                        precision=jax.lax.Precision.HIGHEST)
    if scoring == "sigmoid":
        s = jax.nn.sigmoid(logits)
    else:
        s = jax.nn.softmax(logits, axis=-1)
    picked = s if bias is None else s + jax.lax.stop_gradient(
        bias.astype(f32))
    _, idx = jax.lax.top_k(picked, top_k)
    idx = checkpoint_name(idx, "remat_keep")
    chosen = _pick(s[:, None, :], idx)
    if norm_topk:
        chosen = chosen / (jnp.sum(chosen, -1, keepdims=True)
                           + (1e-6 if scoring == "sigmoid" else 0.0))
    return idx.astype(jnp.int32), chosen * scaling


class Grouping(NamedTuple):
    """Where every assignment to an expert held sits in the row buffer."""
    row_assign: jax.Array    # [M]     flat assignment of the row; N*k if none
    tile_expert: jax.Array   # [M/tm]  expert (0..held-1) of the tile
    n_active: jax.Array      # [1]     tiles that hold rows
    counts: jax.Array        # [held]  assignments per expert held
    uncomputed: jax.Array    # []      assignments held that got no row


def moe_kernel_row_tile(d_model: int, d_expert: int, assignments: int):
    """The grouped-product kernels' gate: their row tile, or ``None`` for
    the XLA path.  Needs the TPU backend, a lane-aligned model width and an
    expert width of whole half tiles (a multiple of 64: an expert of 1856 =
    29 x 64 runs the kernels).  The model width is always tiled by a divisor;
    the expert width is tiled with a masked last block where it is a
    product's RESULT (``x W_1``, ``dy W_2^T``: tiles of 512; ``d_W_1``,
    ``d_W_2``: tiles of 1024) and taken whole where it is summed over
    (``a W_2``, ``dh W_1^T``), whichever view a first matrix is read through
    (:func:`grouped_expert_mlp`, :func:`_largest_tile`)."""
    from paddle_tpu.ops.pallas_kernels import compiled_kernels

    if not compiled_kernels():
        return None
    if d_model % 128 or d_expert % 64 or assignments < 2048:
        return None
    return 256


def buffer_rows(tokens: int, top_k: int, experts: int, held: int, tm: int):
    """``(usual, worst)`` sizes of the row buffer.  ``worst`` holds any
    routing: every token sends ``min(k, held)`` choices here, and every
    group wastes less than a tile.  ``usual`` holds twice the even share
    (``tokens * k * held / experts``); the layer takes it whenever the
    step's routing fits, so that its gathers, scatters and gates move a
    third of the rows."""
    worst = (-(-tokens * min(top_k, held) // tm) + held) * tm
    even = -(-tokens * top_k * held // experts)
    return min(worst, (-(-2 * even // tm) + held) * tm), worst


def count_assignments(idx, *, first_expert: int, held: int):
    """idx ``[N, k]``, the experts each token chose over ALL experts ->
    (counts ``[held]``: the assignments to each expert held; order
    ``[N*k]``: the assignments sorted, stably, by the expert held, those to
    experts held elsewhere last).  The sort is kept across a recomputation
    block (``remat_keep``): the backward's second forward does not sort
    again.  Only ``order`` leaves the sort: the keys in sorted order are what
    the counts say (``counts[0]`` zeros, then ``counts[1]`` ones, ...), and
    :func:`group_assignments` reads them there, where ``key[order]`` would
    be a walk over the assignments."""
    local = idx.reshape(-1) - first_expert
    key = jnp.where((local >= 0) & (local < held), local, held)
    key = key.astype(jnp.int32)
    counts = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                     dtype=jnp.int32)
    order = checkpoint_name(jnp.argsort(key, stable=True).astype(jnp.int32),
                            "remat_keep")
    return counts, order


def group_assignments(counts, order, *, tm: int, rows: int) -> Grouping:
    """Rows for the assignments held, sorted by expert, every group padded
    to whole tiles of ``tm``, in a buffer of ``rows`` (whole tiles).  Made
    from the ROWS' side: a tile's expert follows from the counts, a row's
    rank in its group from its tile, the row is live where the rank is
    under the group's count, and then it holds ``order[start of the group in
    the sorted assignments + rank]``: one gather of ``rows`` scalars.  (The
    assignments' side, a row computed for each of the ``N*k`` sorted
    assignments and ``order`` scattered to it, walks ``N*k`` elements to
    fill a buffer a third to a seventh of that.)  What is a table of
    ``held`` entries is picked by comparison (:func:`_pick`), a tile at a
    time."""
    A, held = order.shape[0], counts.shape[0]
    tiles = -(-counts // tm)
    ends = jnp.cumsum(tiles)                                   # [held]
    n_tiles = rows // tm
    tile = jnp.arange(n_tiles, dtype=jnp.int32)
    tile_expert = jnp.minimum(
        jnp.sum(tile[:, None] >= ends[None, :], axis=1),
        held - 1).astype(jnp.int32)
    # a tile past the groups reads the last expert's tables: its ranks are
    # past that group's whole tiles, so past its count
    rank = ((tile - _pick(ends - tiles, tile_expert)) * tm)[:, None] + (
        jnp.arange(tm, dtype=jnp.int32)[None, :])              # [n_tiles, tm]
    live = rank < _pick(counts, tile_expert)[:, None]
    sorted_at = _pick(jnp.cumsum(counts) - counts, tile_expert)[:, None] + rank
    row_assign = jnp.take(order, jnp.where(live, sorted_at, A).reshape(-1),
                          mode="fill", fill_value=A)
    return Grouping(row_assign, tile_expert,
                    jnp.minimum(ends[-1:], n_tiles).astype(jnp.int32),
                    counts, jnp.sum(counts) - jnp.sum(live, dtype=jnp.int32))


# -- the grouped products ----------------------------------------------------

def _largest_tile(n: int, cap: int) -> int:
    """The tile of a RESULT's axis of ``n`` (never of the axis a product sums
    over): ``n`` whole where it fits ``cap``, else the largest multiple of
    128 under ``cap`` that divides it, else (no such divisor: 1856) the
    largest multiple of 128 under ``cap``, the kernels' grids then rounding
    up and the last block hanging over the edge (what is read there is
    never summed into a column that is kept, what is written there is
    dropped).  The rule holds under both views of a first matrix: the view
    turns which OPERAND axis a result's axis is (``moe_gmm``'s ``tn`` runs
    over the rhs block's last axis as stored and over its middle axis
    through the view), not which axes are results."""
    if n <= cap:
        return n
    cap -= cap % 128
    return next((t for t in range(cap, 0, -128) if n % t == 0), cap)


def _row_expert(g: Grouping, tm: int):
    """[M] expert of each row, ``-1`` past the active tiles."""
    live = jnp.arange(g.tile_expert.shape[0]) < g.n_active[0]
    return jnp.repeat(jnp.where(live, g.tile_expert, -1), tm)


def _gmm(lhs, rhs, g: Grouping, tm, kernels, transpose_rhs=False,
         out_dtype=None):
    out_dtype = out_dtype or acc_dtype()
    if kernels:
        from paddle_tpu.ops.pallas_kernels import gmm_pallas

        n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
        return gmm_pallas(lhs, rhs, g.tile_expert, g.n_active, tm=tm,
                          tn=_largest_tile(n, 512),
                          transpose_rhs=transpose_rhs, out_dtype=out_dtype)
    row_e = _row_expert(g, tm)
    out = None
    for e in range(rhs.shape[0]):
        w = rhs[e].astype(lhs.dtype)
        y = jnp.matmul(lhs, w.T if transpose_rhs else w,
                       preferred_element_type=acc_dtype())
        y = jnp.where((row_e == e)[:, None], y, 0.0)
        out = y if out is None else out + y
    return out.astype(out_dtype)


def _tgmm(lhs, rhs, g: Grouping, tm, kernels):
    experts = g.counts.shape[0]
    if kernels:
        from paddle_tpu.ops.pallas_kernels import tgmm_pallas

        out = tgmm_pallas(lhs, rhs, g.tile_expert, g.n_active,
                          experts=experts, tm=tm,
                          tk=_largest_tile(lhs.shape[1], 1024),
                          tn=_largest_tile(rhs.shape[1], 1024))
        # an expert without a tile has a block the kernel never wrote
        return jnp.where((g.counts > 0)[:, None, None], out, 0.0)
    row_e = _row_expert(g, tm)
    return jnp.stack([
        jnp.matmul(jnp.where((row_e == e)[:, None], lhs, 0).T, rhs,
                   preferred_element_type=acc_dtype())
        for e in range(experts)])


# -- a first matrix, read where the chip keeps it ---------------------------
# The TPU keeps a float32 leaf ``[held, D, F]`` whose last axis is no multiple
# of 128 lanes TRANSPOSED where the axis before it is one (1856 = 14.5 x 128
# would be padded to 1920 lanes, 2688 = 21 x 128 is not): the step's entry
# layout for the leaf, its Adam slots and the new values is ``{1,2,0}``,
# physically ``[held, F, D]``.  A Mosaic call takes its operands and writes
# its results in the default layout, so the kernels handed ``w`` cost copies
# of the whole leaf, and ``d_w`` one more on its way to an update that reads
# the slots where they are kept.  ``jnp.swapaxes(w, 1, 2)`` of such a leaf is
# a bitcast into the default layout, so there the three products of a first
# matrix take the VIEW: the same algorithm and the same pair of kernels, the
# operand's orientation following the width (PERF.md section 6, PR 45).  The
# choice reads two static shapes.

#: the scope of the view's products inside ``moe_experts`` on a device trace
VIEW_SCOPE = "w_view_t"


def _kept_transposed(w) -> bool:
    """Whether the chip keeps the first matrix ``w`` ``[held, D, F]`` with
    ``D`` minor, so that its products read ``[held, F, D]``."""
    return w.shape[2] % 128 != 0 and w.shape[1] % 128 == 0


def _count_view(view: bool):
    """``moe_weight_view_total{view=}``: one count a first matrix and trace
    of the layer's forward (the choice is made when the step is traced)."""
    from paddle_tpu.obs import get_registry

    get_registry().counter(
        "moe_weight_view_total",
        "first matrices of an expert layer by the view the grouped products "
        "read them through, counted when the layer's forward is traced",
        labels=("view",), view="transposed" if view else "stored").inc()


# the three products of a first matrix; their tiles under each view are
# listed in grouped_expert_mlp's docstring

def _rows_by_first(xs, w, g: Grouping, tm, kernels, out_dtype):
    """``xs W_e`` ``[M, F]``."""
    view = _kept_transposed(w)
    _count_view(view)
    if not view:
        return _gmm(xs, w, g, tm, kernels, out_dtype=out_dtype)
    with jax.named_scope(VIEW_SCOPE):
        return _gmm(xs, jnp.swapaxes(w, 1, 2), g, tm, kernels,
                    transpose_rhs=True, out_dtype=out_dtype)


def _rows_by_first_t(dh, w, g: Grouping, tm, kernels):
    """``dh W_e^T`` ``[M, D]`` float32."""
    if not _kept_transposed(w):
        return _gmm(dh, w, g, tm, kernels, transpose_rhs=True)
    with jax.named_scope(VIEW_SCOPE):
        return _gmm(dh, jnp.swapaxes(w, 1, 2), g, tm, kernels)


def _first_grad(xs, dh, w, g: Grouping, tm, kernels):
    """``d_W_e = xs_e^T dh_e`` in ``w``'s shape, float32.  Under the view
    the kernel writes ``[held, F, D]`` and the swap back is a bitcast into
    the layout the leaf, and an update that reads its slots, are kept in."""
    if not _kept_transposed(w):
        return _tgmm(xs, dh, g, tm, kernels)
    with jax.named_scope(VIEW_SCOPE):
        return jnp.swapaxes(_tgmm(dh, xs, g, tm, kernels), 1, 2)


def _take_rows(a, idx):
    """``a[idx]`` with zeros where ``idx`` is out of range (the sentinels)."""
    return jnp.take(a, idx, axis=0, mode="fill", fill_value=0)


def _add_rows(rows, idx, n):
    """``out[idx[r]] += rows[r]`` into ``n`` zero rows; sentinels dropped."""
    return jnp.zeros((n,) + rows.shape[1:], rows.dtype).at[idx].add(
        rows, mode="drop")


class _Form(NamedTuple):
    """One form of expert: ``a = act(h1, h3)`` between the first matrices'
    products and ``W_2``, all float32.  ``grads(da, h1, h3) -> (dh1, dh3)``;
    ``h3`` and ``dh3`` are ``None`` for a form of two matrices.
    ``zero_gates``: whether the layer counts the hidden units the form's
    ReLU gate made exactly zero (the counter ``moe_gate_zero_units``)."""
    gated: bool
    act: Callable
    grads: Callable
    zero_gates: bool = False


def _gated_silu_grads(da, h1, h3):
    sig = jax.nn.sigmoid(h1)
    return da * h3 * sig * (1.0 + h1 * (1.0 - sig)), da * h1 * sig


#: ``expert_act`` -> the experts' form.  ``gated_silu``: ``W_2 (silu(W_1 x) *
#: W_3 x)``; ``relu2``: two matrices, ``W_2 relu(W_1 x)^2``; ``gated_relu``
#: (ReGLU): ``W_2 (relu(W_1 x) * W_3 x)``, a hidden unit exactly zero, forward
#: and backward, wherever ``W_1 x <= 0``.
EXPERT_ACTS = {
    "gated_silu": _Form(True, lambda h1, h3: jax.nn.silu(h1) * h3,
                        _gated_silu_grads),
    "relu2": _Form(False, lambda h1, h3: jnp.square(jax.nn.relu(h1)),
                   lambda da, h1, h3: (da * 2.0 * jax.nn.relu(h1), None)),
    "gated_relu": _Form(True, lambda h1, h3: jax.nn.relu(h1) * h3,
                        lambda da, h1, h3: (da * h3 * (h1 > 0),
                                            da * jax.nn.relu(h1)),
                        zero_gates=True),
}


def _form(expert_act: str, w3) -> _Form:
    if expert_act not in EXPERT_ACTS:
        raise ValueError(f"unknown expert_act {expert_act!r}; "
                         f"have {sorted(EXPERT_ACTS)}")
    form = EXPERT_ACTS[expert_act]
    if form.gated != (w3 is not None):
        raise ValueError(f"expert_act {expert_act!r} takes "
                         f"{'a' if form.gated else 'no'} w3")
    return form


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _expert_mlp(x, weights, w1, w3, w2, g, tm, kernels, expert_act):
    return _expert_mlp_fwd(x, weights, w1, w3, w2, g, tm, kernels,
                           expert_act)[0]


def _expert_mlp_fwd(x, weights, w1, w3, w2, g: Grouping, tm, kernels,
                    expert_act):
    """-> ``(y [N, D], gate_zero)``: ``gate_zero`` is how many of the live
    rows' ``W_1 x`` a ReLU gate made exactly zero (int32; 0 for a form
    without one)."""
    form = _form(expert_act, w3)
    N, k = weights.shape
    f32, cd = acc_dtype(), compute_dtype()
    with jax.named_scope("moe_grouping"):
        row_token = g.row_assign // k                         # N where none
        xs = _take_rows(x.astype(cd), row_token)              # [M, D]
    with jax.named_scope("moe_experts"):
        h1 = _rows_by_first(xs, w1, g, tm, kernels, cd)
        h3 = (_rows_by_first(xs, w3, g, tm, kernels, cd) if form.gated
              else None)
        a = form.act(h1.astype(f32),
                     h3.astype(f32) if form.gated else None).astype(cd)
        ys = _gmm(a, w2, g, tm, kernels, out_dtype=cd)        # [M, D]
        gate_zero = jnp.zeros((), jnp.int32)
        if form.zero_gates:
            live = (g.row_assign < N * k)[:, None]
            gate_zero = jnp.sum(live & (h1 <= 0), dtype=jnp.int32)
    with jax.named_scope("moe_combine"):
        row_w = _take_rows(weights.reshape(-1).astype(f32), g.row_assign)
        y = _add_rows(ys.astype(f32) * row_w[:, None], row_token, N)
    return (y.astype(dot_dtype()), gate_zero), (
        xs, h1, h3, a, ys, row_w, w1, w3, w2, g, jnp.zeros((0,), x.dtype),
        jnp.zeros((0, k), weights.dtype))


def _expert_mlp_bwd(tm, kernels, expert_act, res, cts):
    xs, h1, h3, a, ys, row_w, w1, w3, w2, g, x_like, w_like = res
    form, dy = EXPERT_ACTS[expert_act], cts[0]
    N, k = dy.shape[0], w_like.shape[1]
    f32, cd = acc_dtype(), compute_dtype()
    row_token = g.row_assign // k
    with jax.named_scope("moe_combine"):
        dyr = _take_rows(dy.astype(f32), row_token)               # [M, D]
        d_row_w = jnp.sum(dyr * ys.astype(f32), axis=-1)
        d_weights = _add_rows(d_row_w, g.row_assign, N * k).reshape(N, k)
        dys = (dyr * row_w[:, None]).astype(cd)
    with jax.named_scope("moe_experts"):
        da = _gmm(dys, w2, g, tm, kernels, transpose_rhs=True)   # [M, F] f32
        d_w2 = _tgmm(a, dys, g, tm, kernels)
        dh1, dh3 = form.grads(da, h1.astype(f32),
                              h3.astype(f32) if form.gated else None)
        dh1 = dh1.astype(cd)
        if form.gated:
            dh3 = dh3.astype(cd)
            d_w3 = _first_grad(xs, dh3, w3, g, tm, kernels).astype(w3.dtype)
            dxs = (_rows_by_first_t(dh1, w1, g, tm, kernels)
                   + _rows_by_first_t(dh3, w3, g, tm, kernels))
        else:
            d_w3 = None
            dxs = _rows_by_first_t(dh1, w1, g, tm, kernels)
        d_w1 = _first_grad(xs, dh1, w1, g, tm, kernels)
    with jax.named_scope("moe_grouping"):
        dx = _add_rows(dxs, row_token, N)
    return (dx.astype(x_like.dtype), d_weights.astype(w_like.dtype),
            d_w1.astype(w1.dtype), d_w3, d_w2.astype(w2.dtype), None)


_expert_mlp.defvjp(_expert_mlp_fwd, _expert_mlp_bwd)


def grouped_expert_mlp(x, weights, g: Grouping, w1, w3, w2, *, tm: int,
                       kernels: bool, expert_act: str = "gated_silu"):
    """x ``[N, D]``, weights ``[N, k]`` (the router's, of every choice), the
    experts held ``w1``/``w3`` ``[held, D, F]`` and ``w2`` ``[held, F, D]``
    -> ``[N, D]``: ``sum over the choices held of weight * E_e(x)``, ``E_e``
    of the form ``expert_act`` names (:data:`EXPERT_ACTS`; ``w3`` is ``None``
    for a form of two matrices).  Only the buffer's rows move: tokens
    are gathered into rows and rows added back onto tokens, forward and
    backward, and the backward reads the rows the forward wrote (no second
    sort).

    The grouped products on the kernels, for a first matrix ``W`` (``w1``,
    ``w3``) read as STORED or through the VIEW ``[held, F, D]`` that
    :func:`_kept_transposed` chooses (``F % 128 != 0`` and ``D % 128 == 0``).
    Rows go in tiles of ``tm``; ``t | D`` is the largest multiple of 128
    under the cap that divides ``D``; "masked": the last block hangs over
    the edge where the tile does not divide ``F``:

    - ``h = xs W`` sums over ``D`` whole; ``F`` in tiles of 512, masked.
      Stored: ``moe_gmm`` plain, rhs block ``(D, 512)``; view: transposed,
      rhs block ``(512, D)``.
    - ``dxs = dh W^T`` sums over ``F`` whole; ``D`` in tiles ``t | D`` of at
      most 512.  Stored: transposed, rhs block ``(t, F)``; view: plain,
      rhs block ``(F, t)``.
    - ``d_W = xs^T dh`` sums over an expert's rows; ``D`` in tiles ``t | D``
      of at most 1024, ``F`` in tiles of 1024, masked.  Stored: ``moe_tgmm``
      writes ``[D, F]``; view: it writes ``[F, D]`` and the swap back to the
      leaf's shape is a bitcast.
    - ``ys = a W_2`` sums over ``F`` whole: plain, ``D`` in tiles ``t | D``.
    - ``da = dy W_2^T`` sums over ``D`` whole: transposed, ``F`` in tiles of
      512, masked.
    - ``d_W_2 = a^T dy``: ``[F, D]``, ``F`` by 1024, masked, ``D`` by
      ``t | D``.

    Through the view a first matrix's three products take the shapes
    ``W_2``'s three have, so no kernel configuration is the view's alone;
    ``w2`` has ``D`` minor already and is read as stored."""
    return _expert_mlp(x, weights, w1, w3, w2, g, tm, kernels, expert_act)[0]


def expert_layer(x, idx, weights, w1, w3, w2, *, num_experts: int,
                 first_expert: int, tm: int, kernels: bool,
                 expert_act: str = "gated_silu", sorted_by_expert=None):
    """The part of a dropless expert layer's result that the experts held
    give -> (y ``[N, D]``, assignments per expert held, assignments held
    that got no row: 0, hidden units a ReLU gate made exactly zero: 0 for a
    form without one).  ``expert_act``: the experts' form
    (:func:`grouped_expert_mlp`).  ``sorted_by_expert``: ``(counts, order)``
    of :func:`count_assignments` where the router made them already (a
    router that runs ahead of its experts, beside another layer).  The row
    buffer has its usual size when the step's routing fits it and the worst
    routing's size when not (:func:`buffer_rows`): one ``lax.cond``, the
    same rows either way."""
    N, k = idx.shape
    held = w1.shape[0]
    if sorted_by_expert is None:
        with jax.named_scope("moe_grouping"):
            sorted_by_expert = count_assignments(
                idx, first_expert=first_expert, held=held)
    counts, order = sorted_by_expert
    usual, worst = buffer_rows(N, k, num_experts, held, tm)

    def run(rows):
        with jax.named_scope("moe_grouping"):
            g = group_assignments(counts, order, tm=tm, rows=rows)
        return (*_expert_mlp(x, weights, w1, w3, w2, g, tm, kernels,
                             expert_act), g.uncomputed)

    if usual == worst:
        y, gate_zero, uncomputed = run(worst)
    else:
        fits = jnp.sum(-(-counts // tm)) * tm <= usual
        y, gate_zero, uncomputed = jax.lax.cond(
            fits, lambda: run(usual), lambda: run(worst))
    return y, counts, uncomputed, gate_zero
