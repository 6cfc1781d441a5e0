"""Sharded-embedding parameter-server tier (paddle_tpu/pserver).

Acceptance contracts, all on the 8-virtual-device CPU mesh:

- the all-to-all lookup is BIT-identical to the single-host dense gather,
  and its autodiff backward is the row-sparse scatter;
- the row-sparse apply (``Optimizer.sparse_apply_rows`` and its sharded
  all-to-all push ``sharded_row_update``) is BIT-identical — params AND
  optimizer slots — to the dense masked ``sparse_rows=True`` path, for
  every row-slot optimizer, including duplicate ids, zero-grad (masked)
  positions, and all-to-all padding sentinels;
- ``nn.embedding(..., sparse_grad=True)`` + a pserver-axis mesh routes the
  table out of the dense params and trains end-to-end
  (``models/recommender.py`` as the proving workload), tracking the dense
  oracle exactly when both start from the same table;
- a table too large for one device's budget trains once sharded (the
  100M-row contract, budget-simulated + a @slow real-size run), with
  ``lint --pserver`` proving no step materializes a dense [V, D] gradient
  or optimizer temp;
- incremental snapshots write ONLY dirty rows, CRC-validate, raise the
  typed ``SnapshotError`` on corruption, and fall back to the previous
  snapshot; ``TableReader.hot_reload`` serves the delta.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.nn as nn
import paddle_tpu.ops as O
import paddle_tpu.parallel as par
from paddle_tpu.param.optimizers import SGD, Adam, AdaGrad, Momentum
from paddle_tpu.pserver import (SnapshotError, TableReader, TableSpec,
                                ShardedTable, all_to_all_lookup,
                                audit_pserver, latest_snapshot,
                                load_table_host, pad_vocab,
                                save_table_snapshot, sharded_row_update,
                                validate_snapshot)
from paddle_tpu.trainer import SGDTrainer
from paddle_tpu.utils import FLAGS
from paddle_tpu.utils.devices import make_mesh
from paddle_tpu.utils.error import ConfigError
from tests.conftest import on_accelerator

pytestmark = pytest.mark.skipif(
    on_accelerator(), reason="assumes the 8-virtual-device CPU mesh")


@pytest.fixture(autouse=True)
def fresh_names():
    nn.reset_naming()
    yield


# ---------------------------------------------------------------------------
# vocab padding (satellite: the documented precondition, enforced)
# ---------------------------------------------------------------------------


def test_pad_vocab_rounds_up_and_typed_error_names_table():
    assert pad_vocab(64, 8) == 64
    assert pad_vocab(100, 8) == 104
    with pytest.raises(ConfigError, match="user_emb"):
        pad_vocab(100, 8, pad=False, name="user_emb")


def test_shard_table_pads_nondividing_vocab_and_lookup_still_exact(rng):
    V, D = 100, 8                       # 100 % 8 != 0: the old silent break
    mesh = make_mesh((8,), ("model",))
    table = jnp.asarray(rng.randn(V, D).astype(np.float32))
    t_sh = par.shard_table(mesh, table, name="u")
    assert t_sh.shape == (104, D)
    ids = jnp.asarray(rng.randint(0, V, (5, 7)).astype(np.int32))
    out = par.sharded_embedding_lookup(mesh, t_sh, ids)
    ref = O.embedding_lookup(table, ids)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    with pytest.raises(ConfigError, match="my_table"):
        par.shard_table(mesh, table, pad=False, name="my_table")


# ---------------------------------------------------------------------------
# all-to-all lookup: bit-identity + sparse backward
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(13,), (4, 7), (2, 3, 5)])
def test_a2a_lookup_bit_identical_to_dense_gather(rng, shape):
    V, D = 64, 16
    mesh = make_mesh((8,), ("model",))
    table = jnp.asarray(rng.randn(V, D).astype(np.float32))
    t_sh = par.shard_table(mesh, table)
    ids = jnp.asarray(rng.randint(0, V, shape).astype(np.int32))
    out = all_to_all_lookup(mesh, t_sh, ids)
    ref = jnp.take(table, ids, axis=0)
    assert out.shape == shape + (D,)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_a2a_lookup_single_shard_mesh_fast_path(rng):
    V, D = 32, 4
    mesh = make_mesh((1,), ("model",))
    table = jnp.asarray(rng.randn(V, D).astype(np.float32))
    ids = jnp.asarray(rng.randint(0, V, (9,)).astype(np.int32))
    out = all_to_all_lookup(mesh, jax.device_put(table), ids)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(jnp.take(table, ids, axis=0)))


def test_a2a_lookup_backward_is_row_sparse_scatter(rng):
    """The compat shim's autodiff contract: grad == the sorted scatter-add
    the single-host custom VJP produces (duplicates summed)."""
    V, D = 64, 8
    mesh = make_mesh((8,), ("model",))
    table = jnp.asarray(rng.randn(V, D).astype(np.float32))
    t_sh = par.shard_table(mesh, table)
    ids = jnp.asarray(np.array([[3, 17, 3, 60, 3]], np.int32))
    ct = jnp.asarray(rng.randn(1, 5, D).astype(np.float32))

    g = jax.grad(lambda t: jnp.sum(all_to_all_lookup(mesh, t, ids) * ct))(t_sh)
    g_ref = jax.grad(
        lambda t: jnp.sum(O.embedding_lookup(t, ids) * ct))(table)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# row-sparse apply: bit-identity against the dense masked path
# ---------------------------------------------------------------------------


def _segments(rng, V, D, N, zero_rows=()):
    ids = rng.randint(0, V, (N,)).astype(np.int32)
    g = rng.randn(N, D).astype(np.float32)
    for z in zero_rows:
        g[z] = 0.0
    return jnp.asarray(ids), jnp.asarray(g)


def _dense_grad(V, D, ids, g):
    """The dense gradient the masked path would see: the SAME stable-sorted
    scatter-add as ops/embedding's backward."""
    order = jnp.argsort(ids, stable=True)
    return jnp.zeros((V, D), jnp.float32).at[ids[order]].add(g[order])


@pytest.mark.parametrize("opt_cls", [SGD, Momentum, AdaGrad, Adam])
def test_sparse_apply_rows_bit_identical_params_and_slots(rng, opt_cls):
    V, D, N = 37, 8, 50
    p = jnp.asarray(rng.randn(V, D).astype(np.float32))
    ids, g = _segments(rng, V, D, N, zero_rows=(5, 17))
    # a2a padding sentinels must be dropped
    ids_pad = jnp.concatenate([ids, jnp.full((6,), V + 3, jnp.int32)])
    g_pad = jnp.concatenate([g, jnp.zeros((6, D))])

    a = opt_cls(learning_rate=0.1, l2_rate=0.01)
    b = opt_cls(learning_rate=0.1, l2_rate=0.01)
    sa, sb = a.init_state({"t": p}), b.init_state({"t": p})
    pa, pb = {"t": p}, p
    slb = sb["slots"]["t"]
    for _ in range(3):                    # multi-step: slots must track too
        gd = _dense_grad(V, D, ids, g)
        pa, sa = a.update(pa, {"t": gd}, sa, sparse_rows={"t": True})
        step = sb["step"] + 1
        pb, slb = b.sparse_apply_rows(
            pb, ids_pad, g_pad, slb, lr_eff=b.lr_at(step), step=step,
            decay=b.l2_rate)
        sb = {"step": step, "slots": {"t": slb}}
        np.testing.assert_array_equal(np.asarray(pa["t"]), np.asarray(pb),
                                      err_msg=opt_cls.__name__)
        for x, y in zip(jax.tree_util.tree_leaves(sa["slots"]["t"]),
                        jax.tree_util.tree_leaves(slb)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=f"{opt_cls.__name__} slot")


def test_sharded_row_update_matches_dense_oracle_and_marks_dirty(rng):
    """The full push path (bucket -> all_to_all -> dedup -> row kernel)
    over 8 shards == the dense masked update, bit for bit; touched rows'
    dirty bits set, zero-grad and sentinel rows untouched AND clean."""
    V, D, N = 64, 8, 40
    mesh = make_mesh((8,), ("model",))
    p = jnp.asarray(rng.randn(V, D).astype(np.float32))
    ids, g = _segments(rng, V, D, N, zero_rows=(3,))
    opt = Adam(learning_rate=0.05)
    st = opt.init_state({"t": p})

    gd = _dense_grad(V, D, ids, g)
    p_ref, s_ref = opt.update({"t": p}, {"t": gd}, st,
                              sparse_rows={"t": True})

    t_sh = par.shard_table(mesh, p)
    slots = jax.tree_util.tree_map(
        lambda s: jax.device_put(s, t_sh.sharding), st["slots"]["t"])
    dirty = jnp.zeros((V,), jnp.bool_)
    step = st["step"] + 1
    new_t, new_s, new_dirty = sharded_row_update(
        mesh, opt, t_sh, slots, dirty, ids, g,
        lr_eff=opt.lr_at(step), step=step)
    np.testing.assert_array_equal(np.asarray(new_t), np.asarray(p_ref["t"]))
    for x, y in zip(jax.tree_util.tree_leaves(s_ref["slots"]["t"]),
                    jax.tree_util.tree_leaves(new_s)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    touched = np.unique(np.asarray(ids)[np.any(np.asarray(g) != 0, axis=1)])
    expect = np.zeros(V, bool)
    expect[touched] = True
    np.testing.assert_array_equal(np.asarray(new_dirty), expect)


# ---------------------------------------------------------------------------
# trainer integration: the pserver tier end to end
# ---------------------------------------------------------------------------


def _toy_net(vocab=64, dim=16):
    uid = nn.data("uid", size=vocab, dtype="int32")
    lab = nn.data("y", size=1)
    emb = nn.embedding(uid, dim, name="u_emb", sparse_grad=True)
    h = nn.fc(emb, 8, act="relu", name="h")
    pred = nn.fc(h, 1, act="linear", name="p")
    return nn.mse_cost(pred, lab, name="cost")


def _toy_feeds(rng, vocab=64, n=4, b=16):
    return [{"uid": rng.randint(0, vocab, (b, 1)).astype(np.int32),
             "y": rng.randn(b, 1).astype(np.float32)} for _ in range(n)]


def test_trainer_routes_sparse_grad_tables_through_pserver(rng):
    mesh = make_mesh((8,), ("model",))
    t = SGDTrainer(_toy_net(), Adam(learning_rate=0.05), seed=1, mesh=mesh)
    assert t.pserver is not None and t.pserver.active
    assert "_u_emb.w0" not in t.params          # out of the dense pytree
    assert "_u_emb.w0" not in t.opt_state["slots"]
    assert "_u_emb.w0" in t.pserver.tables
    feeds = _toy_feeds(rng)
    l0 = float(t.train_batch(feeds[0]))
    for _ in range(15):
        l = float(t.train_batch(feeds[0]))
    assert l < l0                                # the table actually learns
    # eval + infer run through the proxy read path
    r = t.test(lambda: iter(feeds))
    assert np.isfinite(r["cost"])


def test_pserver_training_tracks_dense_oracle_from_same_table(rng):
    """Same init table => the pserver-sharded run reproduces the dense
    masked-path run: losses and final table to f32 round-off."""
    mesh = make_mesh((8,), ("model",))
    nn.reset_naming()
    t1 = SGDTrainer(_toy_net(), Adam(learning_rate=0.05), seed=3, mesh=mesh)
    name = "_u_emb.w0"
    table0 = np.asarray(t1.pserver.tables[name].data)

    nn.reset_naming()
    t0 = SGDTrainer(_toy_net(), Adam(learning_rate=0.05), seed=3)
    assert t0.pserver is None                    # no mesh: masked path
    t0.params[name] = jnp.asarray(table0)        # adopt the sharded init

    feeds = _toy_feeds(rng, n=5)
    l0 = [float(t0.train_batch(f)) for f in feeds]
    l1 = [float(t1.train_batch(f)) for f in feeds]
    np.testing.assert_allclose(l1, l0, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(t1.pserver.tables[name].data),
        np.asarray(t0.params[name]), rtol=1e-6, atol=1e-7)


def test_pserver_clipping_parity_with_dense_oracle(rng):
    """Review fix: gradient clipping must see the routed tables' (deduped)
    row-gradient mass and scale those grads too — the clipped pserver run
    tracks the clipped single-host run."""
    mesh = make_mesh((8,), ("model",))
    nn.reset_naming()
    t1 = SGDTrainer(_toy_net(), Adam(learning_rate=0.05,
                                     gradient_clipping_threshold=0.05),
                    seed=3, mesh=mesh)
    name = "_u_emb.w0"
    table0 = np.asarray(t1.pserver.tables[name].data)
    nn.reset_naming()
    t0 = SGDTrainer(_toy_net(), Adam(learning_rate=0.05,
                                     gradient_clipping_threshold=0.05),
                    seed=3)
    t0.params[name] = jnp.asarray(table0)
    feeds = _toy_feeds(rng, n=4)
    l0 = [float(t0.train_batch(f)) for f in feeds]
    l1 = [float(t1.train_batch(f)) for f in feeds]
    np.testing.assert_allclose(l1, l0, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(t1.pserver.tables[name].data),
        np.asarray(t0.params[name]), rtol=1e-5, atol=1e-7)
    # with a threshold this tight, clipping must actually have engaged
    assert not np.allclose(np.asarray(t1.pserver.tables[name].data), table0)


def test_pserver_tables_follow_trainer_seed(rng):
    """Review fix: table init derives from the TRAINER's seed, not the
    global flag — different seeds, different tables."""
    mesh = make_mesh((8,), ("model",))
    nn.reset_naming()
    a = SGDTrainer(_toy_net(), SGD(learning_rate=0.01), seed=1, mesh=mesh)
    nn.reset_naming()
    b = SGDTrainer(_toy_net(), SGD(learning_rate=0.01), seed=2, mesh=mesh)
    ta = np.asarray(a.pserver.tables["_u_emb.w0"].data)
    tb = np.asarray(b.pserver.tables["_u_emb.w0"].data)
    assert not np.array_equal(ta, tb)


def test_bad_step_guard_holds_tables_and_slots(rng):
    mesh = make_mesh((8,), ("model",))
    t = SGDTrainer(_toy_net(), Adam(learning_rate=0.05), seed=1, mesh=mesh,
                   guard_nonfinite=True)
    feeds = _toy_feeds(rng, n=1)
    t.train_batch(feeds[0])
    name = "_u_emb.w0"
    before = np.asarray(t.pserver.tables[name].data)
    slots_before = [np.asarray(x) for x in
                    jax.tree_util.tree_leaves(t.pserver._slots[name])]
    bad = dict(feeds[0])
    bad["y"] = np.full_like(feeds[0]["y"], np.nan)
    t.train_batch(bad)
    assert int(jax.device_get(t._last_extras["bad_step"])) == 1
    np.testing.assert_array_equal(
        np.asarray(t.pserver.tables[name].data), before)
    for x, y in zip(jax.tree_util.tree_leaves(t.pserver._slots[name]),
                    slots_before):
        np.testing.assert_array_equal(np.asarray(x), y)


@pytest.mark.parametrize("amp", [False, True], ids=["guard", "amp"])
@pytest.mark.parametrize("shape", [(8,), (1,)], ids=["mesh8", "mesh1"])
def test_bad_step_leaves_the_tier_bit_for_bit_and_no_trace(rng, monkeypatch,
                                                           shape, amp):
    """A NaN batch leaves the tier's tables, their slots, the dirty masks and
    its step count bit for bit, with and without ``--amp``, on the sharded
    push and on the one-shard path, and without ``--amp`` the next finite
    batch ends where a trainer that never saw the NaN batch ends."""
    from paddle_tpu.utils.flags import FLAGS

    monkeypatch.setattr(FLAGS, "amp", amp)
    mesh = make_mesh(shape, ("model",))
    feeds = _toy_feeds(rng, n=2)
    bad = dict(feeds[1])
    bad["y"] = np.full_like(feeds[1]["y"], np.nan)

    def host(t):
        return jax.tree_util.tree_map(lambda a: np.asarray(a).copy(),
                                      t.pserver.state())

    def equal(a, b):
        la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            np.testing.assert_array_equal(x, y)

    t = SGDTrainer(_toy_net(), Adam(learning_rate=0.05), seed=1, mesh=mesh,
                   guard_nonfinite=True)
    t.train_batch(feeds[0])
    held = host(t)
    assert held["dirty"]["_u_emb.w0"].any() and int(held["step"]) == 1
    t.train_batch(bad)
    assert int(jax.device_get(t._last_extras["bad_step"])) == 1
    equal(host(t), held)
    if amp:
        return      # the next step runs at the halved scale
    t.train_batch(feeds[1])
    nn.reset_naming()
    ref = SGDTrainer(_toy_net(), Adam(learning_rate=0.05), seed=1, mesh=mesh,
                     guard_nonfinite=True)
    ref.train_batch(feeds[0])
    ref.train_batch(feeds[1])
    equal(host(t), host(ref))
    equal(jax.tree_util.tree_map(np.asarray, (t.params, t.opt_state)),
          jax.tree_util.tree_map(np.asarray, (ref.params, ref.opt_state)))


def test_guarded_tier_step_selects_over_no_table(rng):
    """The guard's hold of a tier is the step's own ids turned into the
    sentinel the row scatter drops: the guarded step's jaxpr holds no
    ``select_n`` as large as a table or as a shard of one (a pass over a
    100 M-row table), and no ``cond`` that takes one."""
    from paddle_tpu.analysis.jaxpr_walk import walk_eqns

    mesh = make_mesh((8,), ("model",))
    t = SGDTrainer(_toy_net(vocab=104), Adam(learning_rate=0.05), seed=1,
                   mesh=mesh, guard_nonfinite=True)
    v_pad, dim = t.pserver.tables["_u_emb.w0"].data.shape
    assert (v_pad, dim) == (104, 16)           # 13 rows a shard: no other
    big = {(v_pad, dim), (v_pad // 8, dim), (v_pad,), (v_pad // 8,)}
    closed = jax.make_jaxpr(t._step_fn)(
        t.params, t.state, t.opt_state, t.pserver.state(),
        jax.random.PRNGKey(0), _toy_feeds(rng, vocab=104, n=1)[0])
    eqns = list(walk_eqns(closed.jaxpr))
    assert {"shard_map", "scatter"} <= {e.primitive.name for e, _ in eqns}
    assert not [p for e, p in eqns if e.primitive.name == "select_n"
                and e.outvars[0].aval.shape in big]
    assert not [p for e, p in eqns if e.primitive.name == "cond"
                and any(getattr(v.aval, "shape", None) in big
                        for v in e.invars)]


def test_trainer_surfaces_feeder_dropped_features(rng):
    """Satellite: sparse-bag truncation is observable in _last_extras."""
    from paddle_tpu.data.feeder import DataFeeder

    mesh = make_mesh((8,), ("model",))
    t = SGDTrainer(_toy_net(), SGD(learning_rate=0.01), seed=1, mesh=mesh)
    feeder = DataFeeder({"uid": "int", "y": "dense"},
                        {"uid": 0, "y": 1})
    feeder.dropped_features = 7                  # as if truncation happened
    rows = [[int(i % 64), [0.0]] for i in range(8)]
    t.train(lambda: iter([rows]), num_passes=1, feeder=feeder)
    assert t._last_extras["dropped_features"] == 7


def test_serving_healthz_surfaces_feeder_drops():
    """Satellite (serving side): attach_feeder -> healthz counter."""
    from paddle_tpu.data.feeder import DataFeeder
    from paddle_tpu.serving.server import InferenceServer

    def fwd(feed):
        return {"out": feed["x"]}

    srv = InferenceServer(fwd, max_batch=2)
    feeder = DataFeeder({"x": "dense"}, {"x": 0}, max_nnz=2)
    srv.attach_feeder(feeder)
    feeder.dropped_features = 3
    try:
        h = srv.healthz()
        assert h["dropped_features"] == 3
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# memory budget: the "too large for one device" contract
# ---------------------------------------------------------------------------


def test_budget_rejects_unsharded_but_admits_sharded():
    mesh8 = make_mesh((8,), ("model",))
    mesh1 = make_mesh((1,), ("model",))
    # full table 64 KiB, budget 16 KiB: only the 8-way shard (8 KiB) fits
    spec = TableSpec(name="big", vocab=1024, dim=16,
                     device_budget_bytes=16 * 1024)
    assert spec.table_bytes() > spec.device_budget_bytes
    with pytest.raises(ConfigError, match="big"):
        ShardedTable(spec, mesh1)
    t = ShardedTable(spec, mesh8)               # sharded: within budget
    assert t.shard_rows * 16 * 4 <= spec.device_budget_bytes


@pytest.mark.slow
def test_100m_row_table_trains_sharded():
    """The literal acceptance shape: a 100M-row table (too big for any
    single-device budget you'd grant a CPU test) trains end-to-end through
    the recommender workload on the 8-way mesh."""
    from paddle_tpu.models import recommender

    rng = np.random.RandomState(0)
    mesh = make_mesh((8,), ("model",))
    nn.reset_naming()
    cost, _ = recommender.movielens_net(
        n_users=100_000_000, n_movies=1024, emb_dim=2, hid_dim=8,
        sparse_grad=True)
    t = SGDTrainer(cost, SGD(learning_rate=0.1), seed=0, mesh=mesh)
    assert "_user_emb.w0" not in t.params
    feed = {"user_id": rng.randint(0, 100_000_000, (8, 1)).astype(np.int32),
            "movie_id": rng.randint(0, 1024, (8, 1)).astype(np.int32),
            "score": rng.rand(8, 1).astype(np.float32) * 5}
    l0 = float(t.train_batch(feed))
    l1 = float(t.train_batch(feed))
    assert np.isfinite(l0) and np.isfinite(l1)


def test_recommender_proving_workload_small(rng):
    """movielens_net(sparse_grad=True) on the mesh — the fast-size stand-in
    for the 100M @slow run, exercising TWO routed tables in one step."""
    from paddle_tpu.models import recommender

    mesh = make_mesh((8,), ("model",))
    cost, _ = recommender.movielens_net(n_users=200, n_movies=120,
                                        emb_dim=8, hid_dim=8,
                                        sparse_grad=True)
    t = SGDTrainer(cost, Adam(learning_rate=0.05), seed=0, mesh=mesh)
    assert set(t.pserver.tables) == {"_user_emb.w0", "_movie_emb.w0"}
    feed = {"user_id": rng.randint(0, 200, (16, 1)).astype(np.int32),
            "movie_id": rng.randint(0, 120, (16, 1)).astype(np.int32),
            "score": rng.rand(16, 1).astype(np.float32) * 5}
    l0 = float(t.train_batch(feed))
    for _ in range(20):
        l = float(t.train_batch(feed))
    assert l < l0


# ---------------------------------------------------------------------------
# the never-densify lint gate
# ---------------------------------------------------------------------------


def test_audit_pserver_clean():
    findings = audit_pserver()
    errors = [f for f in findings if f.severity == "ERROR"]
    assert errors == [], [f.message for f in errors]


def test_audit_pserver_rejects_shard_dim_vocab_collision():
    """Review fix: buffer dims the closures legitimately materialize
    (S, per, npad, N) colliding with a vocab dim (Vs/V_pad) must be
    rejected loudly, not let the scan flag a clean build."""
    # V=64, S=8 -> Vs=8 == S: the [S, per] exchange buckets would read as
    # per-shard dense temps
    findings = audit_pserver("64,16,32,8")
    assert any(f.check == "pserver-build" and f.severity == "ERROR"
               and "collides" in f.message for f in findings), \
        [f.message for f in findings]
    # N=512, S=4 on V=4096 -> per = 128, clean dims: no findings at all
    findings = audit_pserver("4096,16,512,4")
    assert [f for f in findings if f.severity == "ERROR"] == [], \
        [f.message for f in findings]


def test_audit_no_dense_rows_catches_densification():
    from paddle_tpu.analysis.jaxpr_audit import audit_no_dense_rows

    V, D, N = 4096, 32, 256

    def densify(t, ids, g):
        gd = jnp.zeros((V, D), jnp.float32).at[ids].add(g)
        return t - 0.1 * gd

    closed = jax.make_jaxpr(densify)(
        jax.ShapeDtypeStruct((V, D), jnp.float32),
        jax.ShapeDtypeStruct((N,), jnp.int32),
        jax.ShapeDtypeStruct((N, D), jnp.float32))
    f = audit_no_dense_rows(closed, full_rows=V, label="neg")
    assert any(x.check == "dense-table-temp" and x.severity == "ERROR"
               for x in f)


def test_trainer_step_jaxpr_never_densifies_routed_table(rng):
    """The acceptance gate on the REAL trainer step: trace the full
    forward/backward/update program and assert no [V, D] grad or temp."""
    from paddle_tpu.analysis.jaxpr_audit import audit_no_dense_rows

    V, D = 184, 16           # V, V_pad distinct from every batch dim
    mesh = make_mesh((8,), ("model",))
    t = SGDTrainer(_toy_net(vocab=V, dim=D), Adam(learning_rate=0.05),
                   seed=1, mesh=mesh)
    v_pad = t.pserver.tables["_u_emb.w0"].vocab_padded
    feed = t._shard_feed({
        "uid": rng.randint(0, V, (16, 1)).astype(np.int32),
        "y": rng.randn(16, 1).astype(np.float32)})
    ps = t.pserver.state()
    closed = jax.make_jaxpr(t._step_fn)(
        t.params, t.state, t.opt_state, ps, jax.random.PRNGKey(0), feed)
    findings = audit_no_dense_rows(closed, full_rows=v_pad,
                                   shard_rows=v_pad // 8, label="step")
    if V != v_pad:
        findings += audit_no_dense_rows(closed, full_rows=V, label="step")
    assert [f for f in findings if f.severity == "ERROR"] == [], \
        [f.message for f in findings]


# ---------------------------------------------------------------------------
# incremental snapshots + serving hot reload
# ---------------------------------------------------------------------------


def _snap_setup(rng, tmp_path, steps=2):
    mesh = make_mesh((8,), ("model",))
    t = SGDTrainer(_toy_net(), Adam(learning_rate=0.05), seed=2, mesh=mesh)
    feeds = _toy_feeds(rng, n=steps)
    for f in feeds:
        t.train_batch(f)
    d = str(tmp_path / "snaps")
    t.pserver.snapshot(d)
    return t, d, os.path.join(d, "u_emb.w0")


def test_snapshot_roundtrip_and_incremental_dirty_only(rng, tmp_path):
    t, root, d = _snap_setup(rng, tmp_path)
    name = "_u_emb.w0"
    tab = t.pserver.tables[name]
    reader = TableReader(d)
    np.testing.assert_array_equal(reader.table, np.asarray(tab.data))

    # next delta touches exactly ONE id -> snapshot stores only that row
    feed = {"uid": np.full((4, 1), 9, np.int32),
            "y": np.ones((4, 1), np.float32)}
    t.train_batch(feed)
    t.pserver.snapshot(root)
    from paddle_tpu.pserver.snapshot import read_snapshot_manifest, snap_dir

    m = read_snapshot_manifest(snap_dir(d, 1))
    assert m["dirty_rows"] == 1                  # incremental, not a dump
    replayed = reader.hot_reload()
    assert replayed == 1
    np.testing.assert_array_equal(reader.table, np.asarray(tab.data))
    assert reader.healthz()["version"] == 1
    # lookups serve the reconstructed rows
    np.testing.assert_array_equal(reader.lookup([9]),
                                  np.asarray(tab.data)[[9]])


def test_snapshot_corruption_typed_error_and_fallback(rng, tmp_path):
    t, root, d = _snap_setup(rng, tmp_path)
    name = "_u_emb.w0"
    before = np.asarray(t.pserver.tables[name].data).copy()
    reader = TableReader(d)

    t.train_batch(_toy_feeds(rng, n=1)[0])
    t.pserver.snapshot(root)
    # corrupt one shard member of the NEW snapshot
    from paddle_tpu.pserver.snapshot import snap_dir

    victim = os.path.join(snap_dir(d, 1), "shard-000.npz")
    with open(victim, "r+b") as f:
        f.seek(0)
        f.write(b"\xde\xad\xbe\xef")
    reason = validate_snapshot(snap_dir(d, 1))
    assert reason is not None and "shard-000.npz" in reason
    # direct load of the damaged snapshot raises the TYPED error...
    with pytest.raises(SnapshotError, match="shard-000.npz"):
        load_table_host(d, upto=1)
    # ...and the fallback path lands on the previous snapshot
    assert latest_snapshot(d) == 0
    spec, table, sid = load_table_host(d)
    assert sid == 0
    np.testing.assert_array_equal(table, before)
    # the live reader also stays on its last good view
    assert reader.hot_reload() == 0
    assert reader.version == 0
    np.testing.assert_array_equal(reader.table, before)


def test_snapshot_chain_middle_corruption_caps_at_valid_prefix(
        rng, tmp_path):
    """Reconstruction replays the chain in order, so a corrupt MIDDLE
    snapshot must cap the usable tip at its predecessor — never make the
    table unreconstructable (review fix)."""
    t, root, d = _snap_setup(rng, tmp_path)          # snap-00000
    state0 = np.asarray(t.pserver.tables["_u_emb.w0"].data).copy()
    t.train_batch(_toy_feeds(rng, n=1)[0])
    t.pserver.snapshot(root)                         # snap-00001
    t.train_batch(_toy_feeds(rng, n=1)[0])
    t.pserver.snapshot(root)                         # snap-00002 (valid tip)

    from paddle_tpu.pserver.snapshot import snap_dir, valid_chain_tip

    with open(os.path.join(snap_dir(d, 1), "shard-001.npz"), "r+b") as f:
        f.write(b"\x00\x00\xff\xff")                 # rot the MIDDLE snap
    assert valid_chain_tip(d) == 0
    spec, table, sid = load_table_host(d)            # no raise: prefix load
    assert sid == 0
    np.testing.assert_array_equal(table, state0)
    with pytest.raises(SnapshotError, match="shard-001.npz"):
        load_table_host(d, upto=2)                   # explicit chain: typed


def test_snapshot_retry_after_failed_validation_reuses_chain_slot(
        rng, tmp_path, monkeypatch):
    """Review fix: a snapshot that fails post-write validation must NOT
    keep its chain position — the retry reuses the same snap id so the
    kept-dirty rows land where valid-prefix readers can reach them."""
    import paddle_tpu.pserver.snapshot as snap_mod

    t, root, d = _snap_setup(rng, tmp_path)          # snap-00000
    t.train_batch(_toy_feeds(rng, n=1)[0])

    real_validate = snap_mod.validate_snapshot
    calls = {"n": 0}

    def flaky_validate(path):
        calls["n"] += 1
        return "synthetic bit-rot" if calls["n"] == 1 else real_validate(path)

    monkeypatch.setattr(snap_mod, "validate_snapshot", flaky_validate)
    with pytest.raises(SnapshotError, match="synthetic bit-rot"):
        t.pserver.snapshot(root)
    # the invalid dir is gone and the rows are still dirty
    assert not os.path.isdir(snap_mod.snap_dir(d, 1))
    assert int(np.asarray(t.pserver.tables["_u_emb.w0"].dirty).sum()) > 0
    # retry publishes into the SAME slot and the chain replays end-to-end
    t.pserver.snapshot(root)
    from paddle_tpu.pserver.snapshot import valid_chain_tip
    assert valid_chain_tip(d) == 1
    spec, table, sid = load_table_host(d)
    assert sid == 1
    np.testing.assert_array_equal(
        table, np.asarray(t.pserver.tables["_u_emb.w0"].data))


def test_snapshot_checkpoint_restores_tables_bit_exact(rng, tmp_path):
    mesh = make_mesh((8,), ("model",))
    t = SGDTrainer(_toy_net(), Adam(learning_rate=0.05), seed=4, mesh=mesh)
    feeds = _toy_feeds(rng, n=3)
    for f in feeds:
        t.train_batch(f)
    t.save(str(tmp_path), 0)
    nn.reset_naming()
    t2 = SGDTrainer(_toy_net(), Adam(learning_rate=0.05), seed=77, mesh=mesh)
    t2.load(str(tmp_path), 0)
    name = "_u_emb.w0"
    np.testing.assert_array_equal(
        np.asarray(t2.pserver.tables[name].data),
        np.asarray(t.pserver.tables[name].data))
    for x, y in zip(jax.tree_util.tree_leaves(t2.pserver._slots[name]),
                    jax.tree_util.tree_leaves(t.pserver._slots[name])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # resumed training continues identically
    extra = _toy_feeds(rng, n=1)[0]
    np.testing.assert_allclose(float(t.train_batch(extra)),
                               float(t2.train_batch(extra)), rtol=1e-6)
