"""The bad-step guard's hold (paddle_tpu/resilience/guard.py; docs/resilience.md).

A step whose loss or gradient norm is not finite leaves parameters, every
optimizer slot, the step count and layer state bit for bit as they were.
The hold is a select inside each leaf's own update (``Optimizer.update``'s
``finite``), in the form that leaf's path already holds rows by; no leaf
crosses a ``lax.cond`` for it, and a row-sparse leaf is never selected over
at table size.  Tier-1 safe: CPU, tiny shapes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.nn as nn
from paddle_tpu.analysis.jaxpr_walk import walk_eqns
from paddle_tpu.param.optimizers import SGD, Adam, Momentum
from paddle_tpu.resilience import chaos
from paddle_tpu.resilience.guard import (guarded_update, init_loss_scale,
                                         scaled_guarded_update)
from paddle_tpu.trainer import SGDTrainer
from paddle_tpu.utils.flags import FLAGS

V, D, K = 16, 4, 4
ROWS = (1, 5, 7)                     # the rows a batch touches: fewer than K

OPTIMIZERS = {"sgd": lambda: SGD(learning_rate=0.1),
              "momentum": lambda: Momentum(learning_rate=0.1),
              "adam": lambda: Adam(learning_rate=0.1)}
#: what ``sparse_rows`` says of the table: the three paths of the per-leaf loop
LEAF_KINDS = {"dense": None, "masked_rows": True, "k_rows": K}


@pytest.fixture(autouse=True)
def fresh_names():
    nn.reset_naming()
    yield


def _leaves(seed=0):
    rs = np.random.RandomState(seed)
    return {"table": jnp.asarray(rs.randn(V, D).astype(np.float32)),
            "w": jnp.asarray(rs.randn(3, 5).astype(np.float32))}


def _grads(seed, kind):
    """A batch's gradients: every element of ``w``, and of the table the
    touched rows alone where its path is row-sparse."""
    rs = np.random.RandomState(100 + seed)
    table = rs.randn(V, D).astype(np.float32)
    if kind != "dense":
        mask = np.zeros((V, 1), np.float32)
        mask[list(ROWS)] = 1.0
        table = table * mask
    return {"table": jnp.asarray(table),
            "w": jnp.asarray(rs.randn(3, 5).astype(np.float32))}


def _poisoned(grads):
    bad = np.asarray(grads["table"]).copy()
    bad[ROWS[1], 2] = np.inf
    return {**grads, "table": jnp.asarray(bad)}


def _host(tree):
    return jax.tree_util.tree_map(lambda a: np.asarray(a).copy(), tree)


def _assert_bit_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _update_fn(opt, kind):
    sparse_rows = {"table": LEAF_KINDS[kind]} if LEAF_KINDS[kind] else None

    def update(p, g, o, finite):
        return opt.update(p, g, o, sparse_rows=sparse_rows, finite=finite)

    return update


@pytest.mark.parametrize("fault", ["nan_loss", "inf_gradient"])
@pytest.mark.parametrize("kind", list(LEAF_KINDS))
@pytest.mark.parametrize("opt_name", list(OPTIMIZERS))
def test_bad_step_holds_every_leaf_and_leaves_no_trace(opt_name, kind, fault):
    """Parameters, every slot and ``step`` come back bit for bit from a step
    whose loss or gradient is not finite, ``bad_step`` reads 1, and the next
    finite step ends where a run that never saw the bad batch ends."""
    opt = OPTIMIZERS[opt_name]()
    update = _update_fn(opt, kind)

    @jax.jit
    def step(params, opt_state, grads, loss):
        new_p, new_o, _, extras = guarded_update(
            update, loss=loss, grads=grads, params=params,
            opt_state=opt_state, new_state={}, old_state={})
        return new_p, new_o, extras

    good = [_grads(i, kind) for i in range(2)]
    bad = (_poisoned(_grads(9, kind)) if fault == "inf_gradient"
           else _grads(9, kind))
    bad_loss = 1.0 if fault == "inf_gradient" else np.nan

    params = _leaves()
    p, o, extras = step(params, opt.init_state(params), good[0], 1.0)
    assert int(extras["bad_step"]) == 0
    held = _host((p, o))
    p, o, extras = step(p, o, bad, bad_loss)
    assert int(extras["bad_step"]) == 1
    _assert_bit_equal((p, o), held)
    p, o, extras = step(p, o, good[1], 1.0)
    assert int(extras["bad_step"]) == 0

    rp, ro, _ = step(params, opt.init_state(params), good[0], 1.0)
    rp, ro, _ = step(rp, ro, good[1], 1.0)
    _assert_bit_equal((p, o), (rp, ro))
    assert int(o["step"]) == 2
    # the two good steps moved what they touched, so the hold held something
    assert not np.array_equal(np.asarray(p["table"]),
                              np.asarray(params["table"]))


@pytest.mark.parametrize("fault", ["overflow", "nan_loss"])
@pytest.mark.parametrize("kind", list(LEAF_KINDS))
@pytest.mark.parametrize("opt_name", list(OPTIMIZERS))
def test_overflow_under_loss_scaling_holds_state_and_halves_the_scale(
        opt_name, kind, fault):
    """``scaled_guarded_update``: an overflow of the scaled gradients holds
    parameters, slots and ``step`` bit for bit and halves the scale without
    counting as a bad step; a non-finite loss does the same and counts.  The
    next finite step, at the halved scale, ends where a run that never saw
    the overflow ends (the scale is a power of two: unscaling is exact)."""
    opt = OPTIMIZERS[opt_name]()
    update = _update_fn(opt, kind)

    @jax.jit
    def step(params, opt_state, amp_state, grads, loss):
        scaled = jax.tree_util.tree_map(lambda g: g * amp_state["scale"],
                                        grads)
        new_p, new_o, _, new_amp, extras = scaled_guarded_update(
            update, loss=loss, scaled_grads=scaled, amp_state=amp_state,
            params=params, opt_state=opt_state, new_state={}, old_state={},
            growth_interval=2000, max_scale=2.0 ** 24)
        return new_p, new_o, new_amp, extras

    good = [_grads(i, kind) for i in range(2)]
    bad = _poisoned(_grads(9, kind)) if fault == "overflow" else _grads(
        9, kind)
    bad_loss = 1.0 if fault == "overflow" else np.nan

    params = _leaves()
    amp = init_loss_scale(1024.0)
    p, o, amp, extras = step(params, opt.init_state(params), amp, good[0],
                             1.0)
    held = _host((p, o))
    p, o, amp, extras = step(p, o, amp, bad, bad_loss)
    assert int(extras["amp_overflow"]) == 1
    assert int(extras["bad_step"]) == (1 if fault == "nan_loss" else 0)
    assert float(amp["scale"]) == 512.0 and int(amp["good_steps"]) == 0
    _assert_bit_equal((p, o), held)
    p, o, amp, extras = step(p, o, amp, good[1], 1.0)
    assert int(extras["amp_overflow"]) == 0 and float(amp["scale"]) == 512.0

    ramp = init_loss_scale(1024.0)
    rp, ro, ramp, _ = step(params, opt.init_state(params), ramp, good[0], 1.0)
    rp, ro, ramp, _ = step(rp, ro, ramp, good[1], 1.0)
    _assert_bit_equal((p, o), (rp, ro))


def _table_selects(jaxpr):
    """``select_n`` equations, through sub-jaxprs, whose result is as large
    as the table."""
    return [path for eqn, path in walk_eqns(jaxpr)
            if eqn.primitive.name == "select_n"
            and eqn.outvars[0].aval.shape == (V, D)]


@pytest.mark.parametrize("opt_name", list(OPTIMIZERS))
def test_a_k_row_leaf_is_never_selected_over_at_table_size(opt_name):
    """The hold of a ``sparse_rows=K`` leaf is ``finite`` joined to the rows'
    liveness: the update under the guard's predicate traces to no select of
    the table's shape beyond those the unguarded update has (its fallback for
    a batch over K rows), ``row_apply`` and ``sparse_apply_rows`` to none at
    all, and a held step takes the K-row branch, not the fallback."""
    opt = OPTIMIZERS[opt_name]()
    params = _leaves()
    state = opt.init_state(params)
    grads = _grads(0, "k_rows")

    def update(finite):
        return jax.make_jaxpr(lambda p, g, o: opt.update(
            p, g, o, sparse_rows={"table": K}, finite=finite))(
                params, grads, state).jaxpr

    assert len(_table_selects(update(jnp.bool_(True)))) == len(
        _table_selects(update(None)))

    ids = jnp.asarray([1, 5, 5, 7, V, V], jnp.int32)
    rows = jnp.asarray(np.ones((6, D), np.float32))
    pushed = jax.make_jaxpr(lambda p, s, live: opt.sparse_apply_rows(
        p, jnp.where(live, ids, V), rows, s, lr_eff=0.1,
        step=jnp.int32(1)))(params["table"], state["slots"]["table"],
                            jnp.bool_(True)).jaxpr
    assert not _table_selects(pushed)

    # every row of the gradient non-finite: more than K rows differ from 0,
    # and the held step still runs the K-row branch with nothing live
    all_bad = {**grads, "table": jnp.full((V, D), jnp.nan)}
    conds = [e for e, _ in walk_eqns(update(jnp.bool_(False)))
             if e.primitive.name == "cond"]
    assert len(conds) == 1
    new_p, new_o = jax.jit(lambda p, g, o: opt.update(
        p, g, o, sparse_rows={"table": K}, finite=jnp.bool_(False)))(
            params, all_bad, state)
    _assert_bit_equal((new_p, new_o), (params, state))


# -- the trainer's own step --------------------------------------------------


def _bn_trainer(**kw):
    x = nn.data("x", size=4)
    y = nn.data("y", size=2)
    h = nn.batch_norm(nn.fc(x, 6, act="linear", name="h"), act="relu",
                      name="bn")
    cost = nn.mse_cost(input=nn.fc(h, 2, act="linear", name="o"), label=y)
    return SGDTrainer(cost, Adam(learning_rate=0.05), seed=0, **kw)


def _pruned_trainer(**kw):
    x = nn.data("x", size=4)
    y = nn.data("y", size=2)
    h = nn.fc(x, 6, act="relu", name="h",
              param_attr=nn.ParamAttr(pruning_ratio=0.5))
    cost = nn.mse_cost(input=nn.fc(h, 2, act="linear", name="o"), label=y)
    return SGDTrainer(cost, Adam(learning_rate=0.05), seed=0, **kw)


def _feeds(n, batch=3):
    rs = np.random.RandomState(0)
    return [{"x": rs.randn(batch, 4).astype(np.float32),
             "y": rs.randn(batch, 2).astype(np.float32)} for _ in range(n)]


@pytest.fixture(params=[False, True], ids=["guard", "amp"])
def amp(request, monkeypatch):
    monkeypatch.setattr(FLAGS, "amp", request.param)
    return request.param


@pytest.mark.parametrize("build", [_bn_trainer, _pruned_trainer],
                         ids=["batch_norm_state", "pruning_masks"])
def test_trainer_bad_step_holds_params_slots_step_and_layer_state(build, amp):
    """Through ``SGDTrainer``'s jitted, donated step, with and without
    ``--amp``: a NaN batch leaves parameters, slots, ``step`` and the layer
    state (batch norm's running statistics) bit for bit, also where pruning
    masks multiply the parameters after the update (a held leaf went in
    masked), and the next batch ends where a run without the NaN batch
    ends."""
    feeds = _feeds(2)
    tr = build()
    tr.train_batch(feeds[0])
    held = _host((tr.params, tr.state,
                  {k: v for k, v in tr.opt_state.items() if k != "amp"}))
    tr.train_batch(chaos.nan_feed(feeds[1]))
    assert tr.bad_steps_total == 1
    _assert_bit_equal((tr.params, tr.state,
                       {k: v for k, v in tr.opt_state.items() if k != "amp"}),
                      held)
    if build is _bn_trainer:
        assert jax.tree_util.tree_leaves(tr.state)        # something to hold
    else:
        assert tr.masks and float(
            np.mean(np.asarray(tr.params["_h.w0"]) == 0)) >= 0.5
    if amp:
        assert float(tr.opt_state["amp"]["scale"]) == FLAGS.loss_scale / 2
        return      # the next step runs at another scale: compared above
    tr.train_batch(feeds[1])
    nn.reset_naming()
    ref = build()
    ref.train_batch(feeds[0])
    ref.train_batch(feeds[1])
    _assert_bit_equal((tr.params, tr.state, tr.opt_state),
                      (ref.params, ref.state, ref.opt_state))


def test_guarded_trainer_step_has_no_cond_over_a_parameter(amp):
    """The jaxpr of a guarded ``SGDTrainer`` step without a pserver tier
    holds no ``cond`` with an operand of a parameter's shape: a conditional
    takes its operands in the default layout, and the chip keeps some leaves
    otherwise (seven copies a leaf and step, PERF.md PR 45 and PR 48)."""
    tr = _bn_trainer()
    assert tr.guard_nonfinite
    feed = _feeds(1)[0]
    closed = jax.make_jaxpr(tr._step_fn)(
        tr.params, tr.state, tr.opt_state, {}, jax.random.PRNGKey(0), feed)
    shapes = {tuple(p.shape) for p in tr.params.values()}
    assert (3, 6) not in shapes                # no activation's shape
    over_params = [path for eqn, path in walk_eqns(closed.jaxpr)
                   if eqn.primitive.name == "cond"
                   and any(getattr(v.aval, "shape", None) in shapes
                           for v in eqn.invars)]
    assert not over_params, over_params
    selects = [eqn for eqn, _ in walk_eqns(closed.jaxpr)
               if eqn.primitive.name == "select_n"
               and eqn.outvars[0].aval.shape in shapes]
    assert len(selects) >= 3 * len(shapes)     # p, m and v of every leaf
