"""Mixed-precision training pipeline (--amp; docs/mixed_precision.md).

Coverage map (the PR's acceptance bars):

- dtype policy: matmul/conv outputs bf16 under --amp, BN statistics and
  softmax/logsumexp reductions and the loss stay f32 (the allowlist),
  master weights stay f32;
- `lint --amp` gate: the REAL trainer step's jaxpr contains zero
  non-allowlisted all-f32 dot_generals (asserted over an lstm model AND
  via the CLI), and the check itself catches a planted f32 dot;
- loss scaling <-> bad-step guard interplay: an injected overflow halves
  the scale and skips without aborting, the growth schedule recovers,
  pure gradient overflow never advances the abort streak;
- checkpoint/resume: masters restore bit-exact, a resumed --amp run
  (scale state included) matches an uninterrupted one exactly;
- convergence parity bf16-vs-f32 on a small model within tolerance;
- the optimizer apply: ``update`` == ``update_leaf`` on each leaf alone,
  bit-identical params AND slots for every shipped optimizer (clipping,
  lr scales, decays, statics, row-sparse leaves beside dense ones), with
  no concatenate in its jaxpr and one chain per leaf;
- --remat: identical training trajectory with remat in the jaxpr.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.nn as nn
from paddle_tpu.param.optimizers import (SGD, Adam, AdaGrad, AdaMax,
                                         AdaDelta, DecayedAdaGrad, Momentum,
                                         RMSProp)
from paddle_tpu.resilience import chaos
from paddle_tpu.trainer import SGDTrainer, events as ev
from paddle_tpu.utils.flags import FLAGS


@pytest.fixture(autouse=True)
def fresh_names():
    nn.reset_naming()
    yield


@pytest.fixture
def amp_on(monkeypatch):
    monkeypatch.setattr(FLAGS, "amp", True)
    yield


def _mse_trainer(seed=0, opt=None, **kw):
    x = nn.data("x", size=4)
    y = nn.data("y", size=2)
    cost = nn.mse_cost(input=nn.fc(x, 2, act="relu", name="h"), label=y)
    return SGDTrainer(cost, opt or Adam(learning_rate=0.05), seed=seed, **kw)


def _feeds(n=6, batch=4):
    rs = np.random.RandomState(0)
    return [{"x": rs.randn(batch, 4).astype(np.float32),
             "y": rs.randn(batch, 2).astype(np.float32)} for _ in range(n)]


def _host(params):
    return {k: np.asarray(v).copy() for k, v in params.items()}


def _lstm_trainer(seed=0):
    from paddle_tpu.models import lstm_benchmark_net

    cost, _ = lstm_benchmark_net(128, emb_dim=16, hid_dim=16, num_layers=1)
    return SGDTrainer(cost, Adam(learning_rate=1e-3), seed=seed)


def _lstm_feed(B=4, T=8):
    rs = np.random.RandomState(0)
    return {"words": (rs.randint(3, 128, (B, T)).astype(np.int32),
                      np.full((B,), T, np.int32)),
            "label": rs.randint(0, 2, (B, 1)).astype(np.int32)}


# ---------------------------------------------------------------------------
# dtype policy
# ---------------------------------------------------------------------------


def test_amp_dtype_policy_bf16_activations_f32_allowlist(amp_on):
    from paddle_tpu.ops.conv import batch_norm, conv2d
    from paddle_tpu.ops.losses import cross_entropy, mse
    from paddle_tpu.ops.matmul import linear, matmul

    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(4, 8).astype(np.float32))
    w = jnp.asarray(rs.randn(8, 8).astype(np.float32))
    assert linear(x, w).dtype == jnp.bfloat16          # activation bf16
    assert matmul(x, w).dtype == jnp.bfloat16
    img = jnp.asarray(rs.randn(2, 8, 8, 3).astype(np.float32))
    k = jnp.asarray(rs.randn(3, 3, 3, 4).astype(np.float32))
    assert conv2d(img, k).dtype == jnp.bfloat16
    # BN statistics accumulate f32 even over bf16 activations
    xb = img.astype(jnp.bfloat16)
    y, nm, nv = batch_norm(xb, jnp.ones(3), jnp.zeros(3),
                           jnp.zeros(3), jnp.ones(3), train=True)
    assert y.dtype == jnp.bfloat16          # activation stream stays bf16
    assert nm.dtype == jnp.float32 and nv.dtype == jnp.float32
    # losses leave in f32 regardless of input dtype
    logits = jnp.asarray(rs.randn(4, 10).astype(np.float32)).astype(
        jnp.bfloat16)
    assert cross_entropy(logits, jnp.arange(4)).dtype == jnp.float32
    assert mse(logits, logits).dtype == jnp.float32


def test_amp_off_keeps_f32_everything():
    from paddle_tpu.ops.matmul import linear

    x = jnp.ones((2, 4), jnp.float32)
    w = jnp.ones((4, 4), jnp.float32)
    assert linear(x, w).dtype == jnp.float32


def test_softmax_statistics_run_f32_but_keep_caller_dtype():
    from paddle_tpu.ops.activations import softmax

    x = jnp.linspace(-4, 4, 16, dtype=jnp.float32).astype(jnp.bfloat16)
    out = softmax(x)
    assert out.dtype == jnp.bfloat16
    # f32 statistics: the normalizer really summed in f32 (a bf16 sum of
    # these 16 terms deviates past bf16 ULP of 1.0)
    np.testing.assert_allclose(float(out.astype(jnp.float32).sum()), 1.0,
                               atol=2e-2)


def test_amp_masters_stay_f32_and_loss_tracks_f32(amp_on, monkeypatch):
    feeds = _feeds(4)
    tr_amp = _mse_trainer()
    losses_amp = [float(tr_amp.train_batch(f)) for f in feeds]
    assert all(str(v.dtype) == "float32" for v in tr_amp.params.values())
    assert all(str(l.dtype) == "float32"
               for l in jax.tree_util.tree_leaves(
                   {k: v for k, v in tr_amp.opt_state["slots"].items()}))
    monkeypatch.setattr(FLAGS, "amp", False)
    nn.reset_naming()
    tr_f32 = _mse_trainer()
    losses_f32 = [float(tr_f32.train_batch(f)) for f in feeds]
    np.testing.assert_allclose(losses_amp, losses_f32, rtol=0.05, atol=0.05)


# ---------------------------------------------------------------------------
# lint --amp gate
# ---------------------------------------------------------------------------


def test_real_lstm_step_has_zero_f32_matmuls_under_amp(amp_on):
    """Acceptance: the compiled --amp train step (embedding + LSTM + CE +
    loss scaling + guarded optimizer apply) contains ZERO non-allowlisted f32
    dot_generals — asserted over the REAL trainer step jaxpr."""
    from paddle_tpu.analysis import audit_amp_matmuls

    tr = _lstm_trainer()
    rng = jax.random.PRNGKey(0)
    closed = jax.make_jaxpr(tr._step_fn)(
        tr.params, tr.state, tr.opt_state, {}, rng, _lstm_feed())
    findings = audit_amp_matmuls(closed, label="test:amp_step")
    assert findings == [], "\n".join(f.message for f in findings)


def test_lint_amp_cli_gate_green(capsys):
    from paddle_tpu.analysis.cli import run

    assert run(["--amp"]) == 0
    assert "0 error" in capsys.readouterr().out


def test_audit_amp_matmuls_catches_planted_f32_dot():
    from paddle_tpu.analysis import audit_amp_matmuls

    def f(a, b):
        good = jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))
        bad = jnp.matmul(a, b)  # all-f32 dot in an otherwise-bf16 net
        return good.astype(jnp.float32) + bad

    a = jnp.ones((4, 4), jnp.float32)
    closed = jax.make_jaxpr(f)(a, a)
    findings = audit_amp_matmuls(closed, label="planted")
    assert len(findings) == 1 and findings[0].severity == "ERROR"
    assert findings[0].check == "amp-f32-matmul"
    # the allowlist (path substring) releases a deliberate f32 island
    assert audit_amp_matmuls(closed, label="planted",
                             allow=("planted",)) == []


def test_audit_amp_matmuls_flags_never_engaged_policy():
    """An 'amp' trace with NO bf16 MXU op at all is itself an ERROR — the
    policy silently not engaging is the worst failure mode."""
    from paddle_tpu.analysis import audit_amp_matmuls

    a = jnp.ones((4, 4), jnp.float32)
    closed = jax.make_jaxpr(lambda x: jnp.matmul(x, x))(a)
    findings = audit_amp_matmuls(closed, label="allf32")
    assert any("never engaged" in f.message for f in findings)


# ---------------------------------------------------------------------------
# loss scaling <-> bad-step guard
# ---------------------------------------------------------------------------


def test_nan_batch_halves_scale_and_skips_without_abort(amp_on):
    """Satellite: injected overflow (chaos NaN-grad) halves the scale and
    skips — params, slots, and scale-halving all observable — and training
    continues (no TooManyBadSteps)."""
    tr = _mse_trainer()
    feeds = _feeds(4)
    tr.train_batch(feeds[0])
    p_before = _host(tr.params)
    scale0 = float(tr.opt_state["amp"]["scale"])
    tr.train_batch(chaos.nan_feed(feeds[1]))
    assert int(tr._last_extras["bad_step"]) == 1
    assert int(tr._last_extras["amp_overflow"]) == 1
    assert float(tr.opt_state["amp"]["scale"]) == scale0 / 2
    for k in p_before:  # the poisoned step held the params
        np.testing.assert_array_equal(p_before[k], np.asarray(tr.params[k]))
    assert tr.amp_overflows_total == 1
    # a good batch afterwards trains normally and resets the streak
    tr.train_batch(feeds[2])
    assert tr.bad_steps_streak == 0


def test_pure_grad_overflow_never_advances_abort_streak(amp_on, monkeypatch):
    """A too-high initial scale takes several halvings to find range; with
    max_bad_steps=2 that search must NOT abort — pure gradient overflow
    (finite loss) is a rescale event, not a bad step."""
    monkeypatch.setattr(FLAGS, "loss_scale", 3.0e38)
    monkeypatch.setattr(FLAGS, "max_bad_steps", 2)
    tr = _mse_trainer(max_bad_steps=2)
    feeds = _feeds(8)
    overflowed = 0
    for f in feeds:  # never raises TooManyBadSteps
        tr.train_batch(f)
        overflowed += int(tr._last_extras["amp_overflow"])
        assert int(tr._last_extras["bad_step"]) == 0
    assert overflowed >= 2                      # the search actually ran
    assert tr.bad_steps_streak == 0
    assert float(tr.opt_state["amp"]["scale"]) < 3.0e38  # and came down


def test_growth_schedule_doubles_and_caps(amp_on, monkeypatch):
    monkeypatch.setattr(FLAGS, "loss_scale", 1024.0)
    monkeypatch.setattr(FLAGS, "loss_scale_growth", 2)
    monkeypatch.setattr(FLAGS, "loss_scale_max", 4096.0)
    tr = _mse_trainer()
    feeds = _feeds(8)
    for f in feeds:
        tr.train_batch(f)
    # 8 good steps / growth 2 -> doubled until the 4096 cap
    assert float(tr.opt_state["amp"]["scale"]) == 4096.0


def test_scale_recovers_after_overflow(amp_on, monkeypatch):
    """Satellite: growth schedule recovers the scale after an overflow."""
    monkeypatch.setattr(FLAGS, "loss_scale", 1024.0)
    monkeypatch.setattr(FLAGS, "loss_scale_growth", 2)
    tr = _mse_trainer()
    feeds = _feeds(6)
    tr.train_batch(chaos.nan_feed(feeds[0]))
    assert float(tr.opt_state["amp"]["scale"]) == 512.0
    for f in feeds[1:5]:
        tr.train_batch(f)
    assert float(tr.opt_state["amp"]["scale"]) >= 1024.0


def test_persistent_nan_loss_still_aborts(amp_on):
    """--amp must not weaken the abort contract: persistently poisoned
    LOSS (not a scale problem) still raises after max_bad_steps."""
    from paddle_tpu.resilience import TooManyBadSteps

    tr = _mse_trainer(max_bad_steps=3)
    bad = chaos.nan_feed(_feeds(1)[0])
    with pytest.raises(TooManyBadSteps):
        for _ in range(5):
            tr.train_batch(bad)


# ---------------------------------------------------------------------------
# checkpoint / resume
# ---------------------------------------------------------------------------


def test_amp_checkpoint_restores_masters_and_scale_bitexact(
        amp_on, tmp_path, monkeypatch):
    monkeypatch.setattr(FLAGS, "loss_scale_growth", 2)
    tr = _mse_trainer()
    for f in _feeds(5):
        tr.train_batch(f)
    tr.save(str(tmp_path), 0)
    nn.reset_naming()
    tr2 = _mse_trainer(seed=123)        # different init — the load wins
    tr2.load(str(tmp_path), 0)
    for k in tr.params:
        assert str(np.asarray(tr2.params[k]).dtype) == "float32"
        np.testing.assert_array_equal(np.asarray(tr.params[k]),
                                      np.asarray(tr2.params[k]))
    assert float(tr2.opt_state["amp"]["scale"]) == \
        float(tr.opt_state["amp"]["scale"])
    assert int(tr2.opt_state["amp"]["good_steps"]) == \
        int(tr.opt_state["amp"]["good_steps"])


def test_amp_resumed_run_matches_uninterrupted(amp_on, tmp_path, monkeypatch):
    """Acceptance: a resumed --amp run (params + slots + RNG + loss-scale
    state all restored) matches an uninterrupted one bit-for-bit."""
    feeds = _feeds(6)

    def reader():
        return iter(feeds)

    monkeypatch.setattr(FLAGS, "save_dir", "")
    tr_a = _mse_trainer()
    tr_a.train(reader, num_passes=3)
    final_a = _host(tr_a.params)

    monkeypatch.setattr(FLAGS, "save_dir", str(tmp_path))
    nn.reset_naming()
    tr_b = _mse_trainer()
    tr_b.train(reader, num_passes=1)    # checkpoint after pass 0
    nn.reset_naming()
    tr_c = _mse_trainer(seed=99)
    tr_c.train(reader, num_passes=3, resume="auto")
    for k in final_a:
        np.testing.assert_array_equal(final_a[k], np.asarray(tr_c.params[k]))


# ---------------------------------------------------------------------------
# convergence parity
# ---------------------------------------------------------------------------


def test_amp_convergence_parity_small_model(monkeypatch):
    """bf16-vs-f32 training parity: the same small regression net reaches
    the same loss neighborhood after 60 steps."""
    rs = np.random.RandomState(0)
    w_true = rs.randn(4, 2).astype(np.float32)
    xs = rs.randn(64, 4).astype(np.float32)
    ys = xs @ w_true
    feeds = [{"x": xs[i:i + 8], "y": ys[i:i + 8]} for i in range(0, 64, 8)]

    def linear_trainer():
        nn.reset_naming()
        x = nn.data("x", size=4)
        y = nn.data("y", size=2)
        cost = nn.mse_cost(input=nn.fc(x, 2, act="linear", name="h"),
                           label=y)
        return SGDTrainer(cost, Adam(learning_rate=0.05), seed=0)

    final = {}
    for amp in (False, True):
        monkeypatch.setattr(FLAGS, "amp", amp)
        tr = linear_trainer()
        loss = None
        for _ in range(40):
            for f in feeds:
                loss = float(tr.train_batch(f))
        final[amp] = loss
    assert final[False] < 0.05                     # the f32 oracle converges
    assert final[True] < 0.1                       # amp converges too
    assert abs(final[True] - final[False]) < 0.1   # within bf16 tolerance


# ---------------------------------------------------------------------------
# the optimizer apply: update_leaf on every dense leaf, on its own
# ---------------------------------------------------------------------------


_APPLY_LEAVES = None


def _apply_fixtures():
    global _APPLY_LEAVES
    if _APPLY_LEAVES is None:
        rs = np.random.RandomState(0)
        shapes = [(4, 128), (8,), (3, 3, 128), (16,), (2, 256), (5, 128),
                  (7,), (4, 4, 128), (10,), (6, 384), (8, 128), (3,)]
        params = {f"p{i}": jnp.asarray(rs.randn(*s).astype(np.float32))
                  for i, s in enumerate(shapes)}
        grads = {k: jnp.asarray(rs.randn(*v.shape).astype(np.float32))
                 for k, v in params.items()}
        _APPLY_LEAVES = (params, grads)
    return _APPLY_LEAVES


def _hand_apply(opt, params, grads, opt_state, *, lr_scales=None,
                decays=None, statics=None, finite=None, **_):
    """What ``Optimizer.update`` has to equal on dense leaves, written out:
    ``update_leaf`` on each leaf alone, with that leaf's own scalars,
    parameter and slots (clipping first, over all the gradients), and
    under the bad-step guard's ``finite`` the old value where it is
    False."""
    from paddle_tpu.param.optimizers import clip_by_global_norm

    step = opt_state["step"] + 1
    lr = opt.lr_at(step)
    if opt.gradient_clipping_threshold > 0:
        grads, _ = clip_by_global_norm(grads,
                                       opt.gradient_clipping_threshold)
    new_params, new_slots = {}, {}
    for k, p in params.items():
        slots = opt_state["slots"][k]
        if (statics or {}).get(k):
            new_params[k], new_slots[k] = p, slots
            continue
        g = grads[k]
        decay = (decays or {}).get(k, 0.0) + opt.l2_rate
        if decay:
            g = g + decay * p
        p2, s2 = opt.update_leaf(
            p, g, slots, lr * (lr_scales or {}).get(k, 1.0), step)
        new_params[k], new_slots[k] = p2.astype(p.dtype), s2
        if finite is not None:
            new_params[k] = jnp.where(finite, new_params[k], p)
            new_slots[k] = tuple(jnp.where(finite, n, o)
                                 for n, o in zip(s2, slots))
    if finite is not None:
        step = jnp.where(finite, step, opt_state["step"])
    return new_params, {"step": step, "slots": new_slots}


def _assert_trees_bit_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("opt", [
    SGD(learning_rate=0.1),
    Momentum(learning_rate=0.05, momentum=0.9),
    Momentum(learning_rate=0.05, momentum=0.9, use_nesterov=True),
    AdaGrad(learning_rate=0.5),
    AdaDelta(learning_rate=5.0, rho=0.9),
    RMSProp(learning_rate=0.05),
    DecayedAdaGrad(learning_rate=0.1),
    Adam(learning_rate=0.2),
    Adam(learning_rate=0.2, gradient_clipping_threshold=1.0),
    Adam(learning_rate=0.2, slot_dtype="bfloat16"),
    AdaMax(learning_rate=0.2),
], ids=lambda o: f"{type(o).__name__}"
       f"{'_clip' if o.gradient_clipping_threshold else ''}"
       f"{'_bf16slots' if getattr(o, 'slot_dtype', None) else ''}"
       f"{'_nesterov' if getattr(o, 'use_nesterov', False) else ''}")
def test_update_is_update_leaf_on_each_leaf_alone(opt):
    """Every shipped optimizer's ``update`` over twelve mixed leaves (an lr
    scale, a decay and a static among them) == ``update_leaf`` called on
    each leaf alone, bit for bit, params AND slots, over three steps; the
    clipping case feeds the per-leaf side the clipped gradients."""
    params, grads = _apply_fixtures()
    kw = dict(lr_scales={"p1": 0.5, "p4": 2.0},
              decays={"p2": 0.01, "p9": 0.003}, statics={"p3": True})
    sa = sb = opt.init_state(params)
    pa = pb = dict(params)
    for _ in range(3):
        pa, sa = opt.update(pa, grads, sa, **kw)
        pb, sb = _hand_apply(opt, pb, grads, sb, **kw)
    _assert_trees_bit_equal((pa, sa), (pb, sb))


def test_update_of_dense_and_row_sparse_leaves_mixed_equals_each_alone():
    """Dense leaves, a row-sparse table on the K fast path and one on the
    masked path in ONE call == each leaf updated in a call of its own:
    no leaf's update depends on which other leaves ride along."""
    rs = np.random.RandomState(3)
    V, D = 50, 8
    params = {"emb": jnp.asarray(rs.randn(V, D).astype(np.float32)),
              "emb_masked": jnp.asarray(rs.randn(V, D).astype(np.float32)),
              "w": jnp.asarray(rs.randn(D, 4).astype(np.float32)),
              "b": jnp.asarray(rs.randn(4).astype(np.float32))}
    ge = np.zeros((V, D), np.float32)
    for r in (3, 7, 20):
        ge[r] = rs.randn(D)
    grads = {"emb": jnp.asarray(ge), "emb_masked": jnp.asarray(ge[::-1]),
             "w": jnp.asarray(rs.randn(D, 4).astype(np.float32)),
             "b": jnp.asarray(rs.randn(4).astype(np.float32))}
    sparse_rows = {"emb": 8, "emb_masked": True}
    opt = Adam(learning_rate=0.1)
    state = opt.init_state(params)
    together = opt.update(params, grads, state, sparse_rows=sparse_rows,
                          decays={"w": 0.01})
    for k in params:
        p1, s1 = opt.update(
            {k: params[k]}, {k: grads[k]},
            {"step": state["step"], "slots": {k: state["slots"][k]}},
            sparse_rows=sparse_rows, decays={"w": 0.01})
        _assert_trees_bit_equal((together[0][k], together[1]["slots"][k]),
                                (p1[k], s1["slots"][k]))
    # the untouched rows of both tables did not move
    for k in ("emb", "emb_masked"):
        still = np.all(np.asarray(grads[k]) == 0, axis=1)
        np.testing.assert_array_equal(np.asarray(together[0][k])[still],
                                      np.asarray(params[k])[still])


def _primitives(jaxpr):
    from collections import Counter

    from paddle_tpu.analysis.jaxpr_walk import walk_eqns

    return Counter(e.primitive.name for e, _ in walk_eqns(jaxpr))


def test_update_jaxpr_has_no_concatenate_and_one_chain_per_leaf():
    """Structure of the one path: nothing is gathered into a segment (no
    ``concatenate``, no ``slice``), and the update is one ``update_leaf``
    chain per leaf — every primitive ``Adam.update_leaf`` holds once
    appears once per leaf, no more."""
    params, grads = _apply_fixtures()
    opt = Adam(learning_rate=0.1)
    state = opt.init_state(params)
    whole = _primitives(jax.make_jaxpr(opt.update)(params, grads,
                                                   state).jaxpr)
    assert not {"concatenate", "slice", "dynamic_slice"} & set(whole)
    p = params["p0"]
    chain = _primitives(jax.make_jaxpr(
        lambda p, g, s, lr, t: opt.update_leaf(p, g, s, lr, t))(
        p, p, state["slots"]["p0"], 0.1, state["step"]).jaxpr)
    once = [name for name, n in chain.items() if n == 1]
    assert "sqrt" in once, chain
    for name in once:
        assert whole[name] == len(params), (name, whole[name])


def test_narrow_leaf_lowers_beside_wide_leaves_as_it_does_alone():
    """A leaf whose minor dimension does not fill whole lanes (an fc
    ``[512, 2]``: stored padded on the TPU, where reshaping it moves data
    and once cost a 351 s compile inside a concatenated segment) gets the
    same equations on the same shapes whether wide leaves ride along or
    not: nothing reshapes, ravels or joins it."""
    rs = np.random.RandomState(1)
    shapes = {"w": (64, 128), "b": (128,), "fc_w": (512, 2)}
    params = {k: jnp.asarray(rs.randn(*v).astype(np.float32))
              for k, v in shapes.items()}
    opt = Adam(learning_rate=0.1)

    def narrow_eqns(names):
        sub = {k: params[k] for k in names}
        jx = jax.make_jaxpr(opt.update)(sub, sub, opt.init_state(sub))
        return [(e.primitive.name,
                 tuple(v.aval.shape for v in e.invars),
                 tuple(v.aval.shape for v in e.outvars))
                for e in jx.jaxpr.eqns
                if any(v.aval.shape == shapes["fc_w"]
                       for v in list(e.invars) + list(e.outvars))]

    alone, beside = narrow_eqns(["fc_w"]), narrow_eqns(list(shapes))
    assert alone and alone == beside
    assert not {"reshape", "concatenate", "slice"} & {e[0] for e in beside}


def test_trainer_on_data_mesh_agrees_with_and_without_replicating_rules():
    """One path for every mesh: a trainer on a data mesh whose sharding
    rules replicate every parameter ends three steps where the trainer
    with no rules at all does."""
    import paddle_tpu.parallel as par
    from paddle_tpu.utils.devices import make_mesh

    feeds = _feeds(3, batch=8)
    mesh = make_mesh((8,), ("data",))
    tr_dp = _mse_trainer(mesh=mesh)
    nn.reset_naming()
    tr_rules = _mse_trainer(
        mesh=mesh, sharding_rules=par.ShardingRules([("*", par.P())]))
    for f in feeds:
        tr_dp.train_batch(f)
        tr_rules.train_batch(f)
    assert set(tr_dp.params) == set(tr_rules.params)
    for k in tr_dp.params:
        np.testing.assert_array_equal(np.asarray(tr_dp.params[k]),
                                      np.asarray(tr_rules.params[k]), k)


class _HandAppliedAdam(Adam):
    """Adam whose ``update`` is the per-leaf loop written out by hand."""

    def update(self, params, grads, opt_state, **kw):
        return _hand_apply(self, params, grads, opt_state, **kw)


def test_trainer_step_matches_hand_applied_per_leaf_update():
    """The real trainer's jitted step (guard, donation, masks) ends three
    batches bit-equal to the same step with the hand-written per-leaf
    loop in the optimizer's place."""
    feeds = _feeds(3)
    tr_a = _mse_trainer()
    nn.reset_naming()
    tr_b = _mse_trainer(opt=_HandAppliedAdam(learning_rate=0.05))
    for f in feeds:
        tr_a.train_batch(f)
        tr_b.train_batch(f)
    _assert_trees_bit_equal((tr_a.params, tr_a.opt_state),
                            (tr_b.params, tr_b.opt_state))


# ---------------------------------------------------------------------------
# remat
# ---------------------------------------------------------------------------


def test_remat_matches_plain_training_and_marks_jaxpr(monkeypatch):
    feeds = _feeds(3)
    tr_a = _mse_trainer(remat=False)
    losses_a = [float(tr_a.train_batch(f)) for f in feeds]
    nn.reset_naming()
    tr_b = _mse_trainer(remat=True)
    losses_b = [float(tr_b.train_batch(f)) for f in feeds]
    np.testing.assert_allclose(losses_a, losses_b, rtol=1e-6)
    for k in tr_a.params:
        np.testing.assert_allclose(np.asarray(tr_a.params[k]),
                                   np.asarray(tr_b.params[k]),
                                   rtol=1e-6, atol=1e-7)
    from paddle_tpu.analysis.jaxpr_walk import walk_eqns

    rng = jax.random.PRNGKey(0)
    closed = jax.make_jaxpr(tr_b._step_fn)(
        tr_b.params, tr_b.state, tr_b.opt_state, {}, rng, feeds[0])
    prims = {e.primitive.name for e, _ in walk_eqns(closed.jaxpr)}
    assert prims & {"remat", "remat2", "checkpoint"}, prims


# ---------------------------------------------------------------------------
# pserver lookups under --amp (ROADMAP item 2 follow-up)
# ---------------------------------------------------------------------------


def test_pserver_lookup_casts_bf16_under_amp(amp_on):
    """Gathered rows leave the lookup bf16 under --amp; the cast sits
    AFTER the grad-proxy add so row gradients stay f32 (masters and the
    row-sparse update path untouched — their bit-identity tests run
    without amp and are unchanged)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.pserver.lookup import TableProxy
    from paddle_tpu.utils.devices import make_mesh

    mesh = make_mesh((4,), ("model",))
    rs = np.random.RandomState(0)
    table = jax.device_put(
        jnp.asarray(rs.randn(32, 8).astype(np.float32)),
        NamedSharding(mesh, P("model", None)))
    ids = jnp.asarray(rs.randint(0, 32, (6,)), jnp.int32)
    proxies = {("t", "l"): jnp.zeros((6, 8), jnp.float32)}
    proxy = TableProxy("t", mesh, "model", table, proxies,
                       compute_dtype="bfloat16")
    rows = proxy.pserver_lookup(ids, layer="l")
    assert rows.dtype == jnp.bfloat16
    # gradient w.r.t. the zeros proxy comes back f32 (master precision)
    g = jax.grad(lambda px: proxy.__class__(
        "t", mesh, "model", table, {("t", "l"): px},
        compute_dtype="bfloat16").pserver_lookup(
            ids, layer="l").astype(jnp.float32).sum())(proxies[("t", "l")])
    assert g.dtype == jnp.float32


def test_tier_table_spec_defaults_bf16_compute_under_amp(amp_on):
    """PServerTier stamps compute_dtype='bfloat16' on its TableSpecs when
    --amp is on (and the trainer routes tables exactly as before)."""
    from paddle_tpu.utils.devices import make_mesh

    uid = nn.data("amp_uid", size=64, dtype="int32")
    lab = nn.data("amp_y", size=1)
    emb = nn.embedding(uid, 16, name="amp_emb", sparse_grad=True)
    pred = nn.fc(emb, 1, act="linear", name="amp_p")
    cost = nn.mse_cost(pred, lab, name="amp_cost")
    mesh = make_mesh((8,), ("model",))
    tr = SGDTrainer(cost, SGD(learning_rate=0.1), seed=1, mesh=mesh)
    assert tr.pserver is not None and tr.pserver.active
    spec = next(iter(tr.pserver.tables.values())).spec
    assert spec.compute_dtype == "bfloat16"
    assert spec.dtype == "float32"              # master stays f32
    # one amp step through the routed path runs and returns a finite loss
    rs = np.random.RandomState(0)
    feed = {"amp_uid": rs.randint(0, 64, (8, 1)).astype(np.int32),
            "amp_y": rs.randn(8, 1).astype(np.float32)}
    assert np.isfinite(float(tr.train_batch(feed)))
