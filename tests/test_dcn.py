"""Cross-pod resilience (docs/resilience.md "Cross-pod recovery"): the
pod-as-failure-unit model, the partition-tolerant DCN transport, and the
hierarchical (two-level) collectives over the ``dcn`` axis.

Acceptance proofs, mirroring tests/test_gang.py's real-process idiom:

- a 2x2-process "two-pod" CPU gang loses ONE rank mid-pass and the
  supervisor expels the whole pod (no whole-gang relaunch), shrinks the
  dcn axis, grows a replacement pod back, and the surviving pod's
  losses/params match an uninterrupted run to 1e-6;
- a DCN partition (black-holed transport files, heartbeats flowing) is
  attributed as ``DCNPartitioned`` — typed, bounded, naming the pod —
  and the supervisor expels the ACCUSED pod while the reporter survives;
- a merely-SLOW pod is absorbed by the transport's retry budget and
  never expelled;
- ``hierarchical_psum`` reassociates to the same sum as the flat
  allreduce (bit-identical on a single pod, by construction), the bf16
  DCN hop's error feedback telescopes exactly, and the two-level pserver
  a2a routes are bit-identical to their one-level/dense oracles.

Every multiprocess test runs under a hard ``signal.alarm`` timeout (no
pytest-timeout in the image) so a supervision bug can never hang tier-1.
"""

import json
import os
import signal
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu.nn as nn
import paddle_tpu.parallel as par
from paddle_tpu.parallel import compat
from paddle_tpu.parallel.hierarchical import (hierarchical_psum,
                                              hierarchical_psum_compressed,
                                              init_dcn_residuals,
                                              make_hierarchical_train_step)
from paddle_tpu.parallel.mesh import MeshConfig
from paddle_tpu.param.optimizers import Adam
from paddle_tpu.pserver import all_to_all_lookup, sharded_row_update
from paddle_tpu.resilience import (DCNPartitioned, DCNTimeout, GangContext,
                                   GangError, GangSupervisor, chaos)
from paddle_tpu.resilience.dcn import (DCNTransport, partition_marker,
                                       report_marker)
from paddle_tpu.resilience.integrity import (_fold_digest, sdc_vote,
                                             sdc_vote_pods)
from paddle_tpu.utils import FLAGS
from paddle_tpu.utils.devices import make_mesh
from paddle_tpu.utils.error import ConfigError
from tests.conftest import on_accelerator
from tests.test_gang import (ELASTIC_STUB, TRAIN_WORKER, _reference_run,
                             _supervisor)

HARD_TIMEOUT_S = 240

mesh_skip = pytest.mark.skipif(
    on_accelerator(), reason="assumes the 8-virtual-device CPU mesh")


@pytest.fixture(autouse=True)
def hard_timeout():
    """Hard per-test deadline: gang tests spawn and kill process trees —
    a supervision bug must fail loudly, never eat the tier-1 budget."""
    def _abort(signum, frame):
        raise RuntimeError(f"dcn test exceeded {HARD_TIMEOUT_S}s hard timeout")

    prev = signal.signal(signal.SIGALRM, _abort)
    signal.alarm(HARD_TIMEOUT_S)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, prev)


@pytest.fixture(autouse=True)
def fresh_names():
    nn.reset_naming()
    yield


# ---------------------------------------------------------------------------
# mesh pod topology: the dcn axis (docs/parallel.md "The dcn axis")
# ---------------------------------------------------------------------------


def test_pod_topology_helpers():
    cfg = MeshConfig(axes=(("dcn", 2), ("data", 4)), dcn_axis="dcn")
    assert cfg.dcn_size == 2 and cfg.pod_size == 4
    assert cfg.pod_of(0) == 0 and cfg.pod_of(3) == 0 and cfg.pod_of(4) == 1
    with pytest.raises(ConfigError):
        cfg.pod_of(8)
    # no dcn axis bound: a single-pod world IS a dcn_size-1 world
    flat = MeshConfig(axes=(("data", 8),))
    assert flat.dcn_size == 1 and flat.pod_size == 8
    assert flat.pod_of(7) == 0


def test_fit_world_shrinks_by_whole_pods():
    cfg = MeshConfig(axes=(("dcn", 3), ("data", 4)), dcn_axis="dcn")
    assert dict(cfg.fit_world(8).axes) == {"dcn": 2, "data": 4}
    # a partial pod's stragglers are dropped WITH their pod, never
    # resharded across pods
    assert dict(cfg.fit_world(7).axes) == {"dcn": 1, "data": 4}
    assert dict(cfg.fit_world(12).axes) == {"dcn": 3, "data": 4}
    with pytest.raises(ConfigError):
        cfg.fit_world(3)
    # without a dcn axis the elastic axis stays the data axis
    flat = MeshConfig(axes=(("data", 8),))
    assert dict(flat.fit_world(4).axes) == {"data": 4}


def test_mesh_json_roundtrip_keeps_dcn_axis():
    cfg = MeshConfig(axes=(("dcn", 2), ("data", 4)), dcn_axis="dcn")
    back = MeshConfig.from_json(cfg.to_json())
    assert back == cfg and back.dcn_axis == "dcn"


# ---------------------------------------------------------------------------
# pod-level SDC voting: the pod digest is the unit of agreement
# ---------------------------------------------------------------------------


def _pod2(r):
    return r // 2


def test_pod_vote_agreement():
    # ranks WITHIN a pod legitimately differ (shards of one replica);
    # pods agree when their rank-ordered digests match
    v = sdc_vote_pods({0: 7, 1: 8, 2: 7, 3: 8}, coordinator=0, pod_of=_pod2)
    assert v.agreed and v.minority == []
    assert v.presumed == _fold_digest((7, 8))


def test_pod_vote_minority_pod_expelled_as_unit():
    fps = {0: 1, 1: 2, 2: 1, 3: 2, 4: 9, 5: 9}
    v = sdc_vote_pods(fps, coordinator=0, pod_of=_pod2)
    assert not v.agreed and not v.tie
    assert v.minority == [4, 5]          # the WHOLE divergent pod
    assert v.presumed == _fold_digest((1, 2))


def test_pod_vote_tie_presumes_coordinator_pod():
    v = sdc_vote_pods({0: 1, 1: 2, 2: 3, 3: 4}, coordinator=0, pod_of=_pod2)
    assert not v.agreed and v.tie
    assert v.presumed == _fold_digest((1, 2))
    assert v.minority == [2, 3]


def test_pod_vote_podsize1_matches_rank_vote():
    fps = {0: 5, 1: 5, 2: 6}
    a = sdc_vote(fps, coordinator=0)
    b = sdc_vote_pods(fps, coordinator=0, pod_of=lambda r: r)
    assert (a.agreed, a.presumed, a.minority, a.tie) == \
        (b.agreed, b.presumed, b.minority, b.tie)


# ---------------------------------------------------------------------------
# DCNTransport: bounded retry, chaos markers, typed attribution
# ---------------------------------------------------------------------------


def test_transport_defaults_follow_flags(tmp_path):
    tr = DCNTransport(str(tmp_path), rank=0)
    assert tr.timeout_s == FLAGS.dcn_timeout_s
    assert tr.retries == FLAGS.dcn_retries
    assert tr.jitter == FLAGS.gang_backoff_jitter
    assert tr.watchdog_s == FLAGS.gang_watchdog_s


def test_attribute_same_pod_is_classic_gang_error(tmp_path):
    tr = DCNTransport(str(tmp_path), rank=0, pod_size=2)
    with pytest.raises(GangError) as ei:
        tr.attribute("exchange 'x'", [1], attempts=3)
    assert not isinstance(ei.value, (DCNTimeout, DCNPartitioned))
    assert "supervisor will relaunch" in str(ei.value)


def test_attribute_partition_vs_pod_death(tmp_path):
    d = str(tmp_path)
    tr = DCNTransport(d, rank=0, pod_size=2, watchdog_s=5.0)
    # fresh heartbeats from the unreachable pod: alive but cut off — a
    # partition, reported to the supervisor for pod-level expel
    for r in (2, 3):
        with open(os.path.join(d, f"hb-rank{r}"), "w") as f:
            f.write("x")
    with pytest.raises(DCNPartitioned) as ei:
        tr.attribute("exchange 'sdc'", [2, 3], attempts=3)
    assert ei.value.pod == 1 and ei.value.attempts == 3
    with open(report_marker(d, 0)) as f:
        rep = json.load(f)
    assert rep["pod"] == 1 and rep["pods"] == [1] and rep["attempts"] == 3
    # stale heartbeats: indistinguishable from pod death on this
    # evidence — DCNTimeout, the watchdog path owns it
    old = time.time() - 60.0
    for r in (2, 3):
        os.utime(os.path.join(d, f"hb-rank{r}"), (old, old))
    with pytest.raises(DCNTimeout) as ei:
        tr.attribute("exchange 'sdc'", [2, 3], attempts=3)
    assert ei.value.pod == 1
    # absent heartbeats: DCNTimeout too
    for r in (2, 3):
        os.remove(os.path.join(d, f"hb-rank{r}"))
    with pytest.raises(DCNTimeout):
        tr.attribute("exchange 'sdc'", [2, 3], attempts=3)


def test_partition_marker_blocks_symmetrically_and_heals(tmp_path):
    d = str(tmp_path)
    gang = types.SimpleNamespace(gang_dir=d)
    tr0 = DCNTransport(d, rank=0, pod_size=2)   # pod 0
    tr2 = DCNTransport(d, rank=2, pod_size=2)   # pod 1
    assert not tr0.blocked(2) and not tr2.blocked(0)
    chaos.partition_pod(gang, 1)
    assert tr0.blocked(2)          # pod 1 unreachable from pod 0
    assert tr2.blocked(0)          # and symmetrically, pod 0 from pod 1
    assert not tr0.blocked(1)      # same-pod traffic rides ICI
    assert chaos.heal_partition(gang) == 1
    assert not tr0.blocked(2) and not tr2.blocked(0)


def test_slow_pod_absorbed_by_retry_budget_not_expelled(tmp_path):
    d = str(tmp_path)
    gang = types.SimpleNamespace(gang_dir=d)
    chaos.slow_dcn(gang, 0.15)
    tr = DCNTransport(d, rank=0, pod_size=1, timeout_s=0.08, retries=3,
                      backoff_s=0.01)
    t0 = time.monotonic()
    out = tr.wait("exchange 'x'", lambda: "ok", [1])
    assert out == "ok"                       # absorbed, not raised
    assert time.monotonic() - t0 >= 0.15     # really paced past one attempt
    assert chaos.slow_dcn(gang, 0) is None   # lifted
    assert tr.pace_s() == 0.0


def test_retry_budget_and_explicit_timeout_semantics(tmp_path):
    tr = DCNTransport(str(tmp_path), rank=0, pod_size=1, timeout_s=0.03,
                      retries=2, backoff_s=0.01, max_backoff_s=0.02)
    with pytest.raises(DCNTimeout) as ei:    # cross-pod, no heartbeat
        tr.wait("exchange 'x'", lambda: None, [1])
    assert ei.value.attempts == 3            # 1 + retries
    # an explicit timeout means the CALLER owns the budget: one attempt,
    # no retries stacked on top — existing exchange_json(timeout_s=...)
    # call sites keep their exact semantics
    with pytest.raises(DCNTimeout) as ei:
        tr.wait("exchange 'x'", lambda: None, [1], timeout_s=0.05)
    assert ei.value.attempts == 1


# ---------------------------------------------------------------------------
# GangContext cross-pod waits (in-process, threads as ranks)
# ---------------------------------------------------------------------------


def _ctx(d, rank, size, **kw):
    kw.setdefault("heartbeat_s", 0.0)
    kw.setdefault("barrier_timeout_s", 30.0)
    return GangContext(str(d), rank, size, **kw)


def test_pod_barrier_is_pod_local(tmp_path):
    """Only the pod's own ranks meet: ranks 2/3 never arrive and the
    pod-0 barrier must complete anyway (it never crosses DCN)."""
    g0 = _ctx(tmp_path, 0, 4, pod_size=2)
    g1 = _ctx(tmp_path, 1, 4, pod_size=2)
    done = []

    def peer():
        g1.pod_barrier()
        done.append(1)

    t = threading.Thread(target=peer)
    t.start()
    g0.pod_barrier(timeout_s=10.0)
    t.join()
    assert done == [1]


def test_pod_barrier_single_member_pod_returns_immediately(tmp_path):
    g = _ctx(tmp_path, 0, 4, pod_size=1)
    t0 = time.monotonic()
    g.pod_barrier()
    assert time.monotonic() - t0 < 1.0


def test_exchange_attributes_partitioned_pod(tmp_path):
    """The transport's typed attribution through the real exchange path:
    pod 1 heartbeats but its DCN files are black-holed — the exhausted
    budget must surface as DCNPartitioned naming pod 1, with a report
    marker left for the supervisor."""
    g0 = _ctx(tmp_path, 0, 2, pod_size=1)     # two ranks, two pods
    g0._dcn.timeout_s, g0._dcn.retries = 0.15, 1
    g0._dcn.backoff_s = 0.01
    with open(os.path.join(str(tmp_path), "hb-rank1"), "w") as f:
        f.write("x")
    with open(partition_marker(str(tmp_path), 1), "w") as f:
        f.write("partitioned\n")
    with pytest.raises(DCNPartitioned) as ei:
        g0.exchange_json({"fp": 1}, name="sdc")
    assert ei.value.pod == 1 and ei.value.attempts == 2
    with open(report_marker(str(tmp_path), 0)) as f:
        assert json.load(f)["pod"] == 1


def test_broadcast_default_wait_is_bounded_and_typed(tmp_path):
    """The bugfix satellite: a follower waiting on a never-published
    decision gets the transport's bounded default budget (not the 600s
    barrier budget), typed against the coordinator's pod."""
    g1 = _ctx(tmp_path, 1, 2, pod_size=1)
    g1._dcn.timeout_s, g1._dcn.retries = 0.1, 1
    g1._dcn.backoff_s = 0.01
    t0 = time.monotonic()
    with pytest.raises(DCNTimeout) as ei:    # no heartbeat from pod 0
        g1.broadcast_json(None, name="resume")
    assert ei.value.pod == 0
    assert time.monotonic() - t0 < 5.0


# ---------------------------------------------------------------------------
# hierarchical collectives over the dcn axis
# ---------------------------------------------------------------------------


@mesh_skip
@pytest.mark.parametrize("shape", [(13,), (4, 6)])
def test_hierarchical_psum_matches_flat_two_pods(rng, shape):
    """ICI reduce-scatter -> DCN allreduce -> ICI allgather reassociates
    the SAME sum as the flat joint-axis psum ((13,) exercises the
    non-dividing pad path)."""
    mesh = make_mesh((2, 4), ("dcn", "data"))
    x = jnp.asarray(rng.randn(8, *shape).astype(np.float32))

    def flat(xs):
        return lax.psum(xs, ("dcn", "data"))

    def hier(xs):
        return hierarchical_psum(xs, "data", "dcn", ici_size=4, dcn_size=2)

    specs = dict(mesh=mesh, in_specs=(P(("dcn", "data")),), out_specs=P())
    a = jax.jit(compat.shard_map(flat, **specs))(x)
    b = jax.jit(compat.shard_map(hier, **specs))(x)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-6, atol=1e-6)


@mesh_skip
def test_hierarchical_psum_single_pod_bit_identical(rng):
    """The bit-compatibility pin: on a single pod (dcn_size == 1) the
    hierarchical path IS lax.psum by construction — bitwise equal, so
    binding --dcn_axis on a one-pod world changes nothing."""
    mesh = make_mesh((1, 8), ("dcn", "data"))
    x = jnp.asarray(rng.randn(8, 5).astype(np.float32))
    specs = dict(mesh=mesh, in_specs=(P(("dcn", "data")),), out_specs=P())
    flat = jax.jit(compat.shard_map(
        lambda v: lax.psum(v, "data"), **specs))(x)
    hier = jax.jit(compat.shard_map(
        lambda v: hierarchical_psum(v, "data", "dcn", ici_size=8,
                                    dcn_size=1), **specs))(x)
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(hier))


@mesh_skip
def test_compressed_error_feedback_telescopes(rng):
    """The error-feedback contract: per step, reduced = exact +
    psum(r_old) - psum(r_new), so over T steps the QUANTIZATION error
    telescopes — sum(reduced) + psum(r_T) == T * exact up to the one
    error source feedback does not carry: the DCN psum itself adds in
    bf16, rounding each step's sum by at most one bf16 ulp.  The bound
    is therefore T * ulp(exact), linear in T, never compounding."""
    mesh = make_mesh((2, 4), ("dcn", "data"))
    size, ici, pods, padded = 13, 4, 2, 16
    x = jnp.asarray(rng.randn(8, size).astype(np.float32))

    def body(xs, r):
        red, nr = hierarchical_psum_compressed(
            xs.reshape(size), r.reshape(padded // ici), "data", "dcn",
            ici_size=ici, dcn_size=pods)
        return red, nr.reshape(1, padded // ici)

    step = jax.jit(compat.shard_map(
        body, mesh=mesh,
        in_specs=(P(("dcn", "data")), P("dcn", "data")),
        out_specs=(P(), P("dcn", "data"))))

    r = jnp.zeros((pods, padded), jnp.float32)
    exact = np.asarray(x).sum(axis=0)
    total = np.zeros(size, np.float64)
    T = 8
    for _ in range(T):
        red, r = step(x, r)
        total += np.asarray(red)
    assert np.abs(np.asarray(r)).max() > 0   # bf16 really is lossy here
    in_flight = np.asarray(r).sum(axis=0)[:size]
    err = np.abs(total + in_flight - T * exact.astype(np.float64))
    bound = T * 2.0 ** -8 * (np.abs(exact) + 1.0)   # T bf16-sum roundings
    assert (err <= bound).all(), (err, bound)


def _toy_loss(params, batch):
    pred = batch["x"] @ params["w"] + params["b"]
    return jnp.mean((pred - batch["y"]) ** 2)


@mesh_skip
def test_hierarchical_train_step_matches_flat_and_api_dispatch(rng):
    """The two-level step == the flat GSPMD data-parallel step, and a
    dcn-bound MeshConfig makes make_parallel_train_step dispatch to it
    with the same signature."""
    cfg = MeshConfig(axes=(("dcn", 2), ("data", 4)), dcn_axis="dcn")
    built = cfg.build()
    params = {"w": rng.randn(4, 2).astype(np.float32),
              "b": rng.randn(2).astype(np.float32)}
    batch = {"x": rng.randn(16, 4).astype(np.float32),
             "y": rng.randn(16, 2).astype(np.float32)}
    opt = Adam(learning_rate=0.05)

    mesh8 = make_mesh((8,), ("data",))
    p0 = par.shard_params(mesh8, params)
    s0 = opt.init_state(p0)
    b0 = par.shard_batch(mesh8, batch)
    loss_ref, p_ref, _ = par.make_parallel_train_step(
        _toy_loss, opt, mesh8, donate=False)(p0, s0, b0)

    rep = NamedSharding(built, P())
    joint = NamedSharding(built, P(("dcn", "data")))
    ph = {k: jax.device_put(jnp.asarray(v), rep) for k, v in params.items()}
    sh = opt.init_state(ph)
    bh = {k: jax.device_put(jnp.asarray(v), joint)
          for k, v in batch.items()}
    step = make_hierarchical_train_step(_toy_loss, opt, cfg, donate=False)
    loss_h, p_h, _ = step(ph, sh, bh)
    np.testing.assert_allclose(float(loss_ref), float(loss_h), rtol=1e-5)
    for k in params:
        np.testing.assert_allclose(np.asarray(p_ref[k]), np.asarray(p_h[k]),
                                   rtol=1e-5, atol=1e-6, err_msg=k)

    pa = {k: jax.device_put(jnp.asarray(v), rep) for k, v in params.items()}
    sa = opt.init_state(pa)
    loss_a, p_a, _ = par.make_parallel_train_step(
        _toy_loss, opt, cfg, donate=False)(pa, sa, bh)
    np.testing.assert_allclose(float(loss_a), float(loss_h), rtol=1e-6)
    for k in params:
        np.testing.assert_allclose(np.asarray(p_a[k]), np.asarray(p_h[k]),
                                   rtol=1e-6, atol=1e-7, err_msg=k)


@mesh_skip
def test_hierarchical_step_token_mean_is_the_global_batchs(rng):
    """The two-level step shares the data-parallel body, so a loss that is
    a mean over real tokens divides by the count over BOTH axes: ragged
    lengths, two pods of four, one row a chip, against one device."""
    from tests.test_parallel import _one_device_step, _seq2seq_batch

    m, params, batch = _seq2seq_batch(rng)
    opt = Adam(learning_rate=1e-3)
    loss_ref, p_ref, _ = _one_device_step(m.loss, opt, params, batch)

    cfg = MeshConfig(axes=(("dcn", 2), ("data", 4)), dcn_axis="dcn")
    built = cfg.build()
    rep = NamedSharding(built, P())
    joint = NamedSharding(built, P(("dcn", "data")))
    ph = {k: jax.device_put(jnp.asarray(v), rep) for k, v in params.items()}
    bh = {k: jax.device_put(jnp.asarray(v), joint) for k, v in batch.items()}
    loss_h, p_h, _ = par.make_parallel_train_step(
        m.loss, opt, cfg, donate=False)(ph, opt.init_state(ph), bh)
    np.testing.assert_allclose(float(loss_ref), float(loss_h), rtol=1e-5)
    for k in p_ref:
        np.testing.assert_allclose(np.asarray(p_ref[k]), np.asarray(p_h[k]),
                                   rtol=1e-4, atol=1e-6, err_msg=k)


@mesh_skip
def test_compressed_step_error_feedback_converges(rng):
    """--dcn_compress end to end: the bf16-DCN step with error feedback
    still drives the loss down (the convergence-tier gate for the
    not-bit-exact path)."""
    cfg = MeshConfig(axes=(("dcn", 2), ("data", 4)), dcn_axis="dcn")
    built = cfg.build()
    x = rng.randn(16, 4).astype(np.float32)
    w_true = rng.randn(4, 2).astype(np.float32)
    params = {"w": (rng.randn(4, 2) * 0.5).astype(np.float32),
              "b": np.zeros(2, np.float32)}
    batch = {"x": x, "y": (x @ w_true).astype(np.float32)}
    opt = Adam(learning_rate=0.05)
    rep = NamedSharding(built, P())
    joint = NamedSharding(built, P(("dcn", "data")))
    ph = {k: jax.device_put(jnp.asarray(v), rep) for k, v in params.items()}
    sh = opt.init_state(ph)
    res = init_dcn_residuals(cfg, ph)
    bh = {k: jax.device_put(jnp.asarray(v), joint) for k, v in batch.items()}
    step = make_hierarchical_train_step(_toy_loss, opt, cfg, compress=True,
                                        donate=False)
    losses = []
    for _ in range(20):
        loss, ph, sh, res = step(ph, sh, res, bh)
        losses.append(float(loss))
    assert losses[-1] < 0.2 * losses[0]
    # the compressed hop really ran: some quantization error is in flight
    assert any(float(jnp.abs(r).max()) > 0
               for r in jax.tree_util.tree_leaves(res))


# ---------------------------------------------------------------------------
# two-level pserver routing: pod-local column hop, then cross-pod
# ---------------------------------------------------------------------------


@mesh_skip
def test_two_level_lookup_bit_identical_to_dense_gather(rng):
    V, D = 64, 8
    mesh = make_mesh((2, 4), ("dcn", "model"))
    table = jnp.asarray(rng.randn(V, D).astype(np.float32))
    t_sh = jax.device_put(table,
                          NamedSharding(mesh, P(("dcn", "model"), None)))
    ids = jnp.asarray(rng.randint(0, V, (4, 7)).astype(np.int32))
    out = all_to_all_lookup(mesh, t_sh, ids, dcn_axis="dcn")
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(jnp.take(table, ids, axis=0)))


@mesh_skip
def test_two_level_row_update_matches_dense_oracle(rng):
    """The two-hop push (pod-local column, then cross-pod) applies the
    SAME update as the dense masked sparse_rows path — params, slots,
    and dirty bits."""
    V, D, N = 64, 8, 40
    mesh = make_mesh((2, 4), ("dcn", "model"))
    p = jnp.asarray(rng.randn(V, D).astype(np.float32))
    ids = rng.randint(0, V, (N,)).astype(np.int32)
    g = rng.randn(N, D).astype(np.float32)
    g[3] = 0.0                                # zero-grad rows stay clean
    ids, g = jnp.asarray(ids), jnp.asarray(g)
    opt = Adam(learning_rate=0.05)
    st = opt.init_state({"t": p})

    order = jnp.argsort(ids, stable=True)
    gd = jnp.zeros((V, D), jnp.float32).at[ids[order]].add(g[order])
    p_ref, s_ref = opt.update({"t": p}, {"t": gd}, st,
                              sparse_rows={"t": True})

    row_sh = NamedSharding(mesh, P(("dcn", "model"), None))
    t_sh = jax.device_put(p, row_sh)
    slots = jax.tree_util.tree_map(lambda s: jax.device_put(s, row_sh),
                                   st["slots"]["t"])
    dirty = jax.device_put(jnp.zeros((V,), jnp.bool_),
                           NamedSharding(mesh, P(("dcn", "model"))))
    step = st["step"] + 1
    new_t, new_s, new_dirty = sharded_row_update(
        mesh, opt, t_sh, slots, dirty, ids, g,
        lr_eff=opt.lr_at(step), step=step, dcn_axis="dcn")
    np.testing.assert_allclose(np.asarray(new_t), np.asarray(p_ref["t"]),
                               rtol=1e-6, atol=1e-7)
    for a, b in zip(jax.tree_util.tree_leaves(s_ref["slots"]["t"]),
                    jax.tree_util.tree_leaves(new_s)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    touched = np.unique(np.asarray(ids)[np.any(np.asarray(g) != 0, axis=1)])
    expect = np.zeros(V, bool)
    expect[touched] = True
    np.testing.assert_array_equal(np.asarray(new_dirty), expect)


# ---------------------------------------------------------------------------
# supervisor: the pod is the failure unit (protocol stubs, 4 real procs)
# ---------------------------------------------------------------------------


def _pod_sup(tmp_path, *, horizon_s=8.0, die_rank=-1, die_after=0.5, **kw):
    script = tmp_path / "stub.py"
    script.write_text(ELASTIC_STUB)
    kw.setdefault("elastic", True)
    kw.setdefault("watchdog_s", 2.0)
    kw.setdefault("startup_grace_s", 10.0)
    kw.setdefault("max_restarts", 2)
    kw.setdefault("pod_size", 2)
    return _supervisor(
        4, script,
        [str(time.time() + horizon_s), str(die_rank), str(die_after)],
        gang_dir=str(tmp_path / "gang"), **kw)


def test_pod_kill_expels_whole_pod_one_attempt(tmp_path):
    """Rank 3 dies -> its pod PARTNER rank 2 is expelled with it
    (pod-killed attribution), the dcn axis shrinks by one pod, and a
    replacement pod grows back — all inside ONE attempt."""
    sup = _pod_sup(tmp_path, die_rank=3)
    result = sup.run()
    assert result.attempts == 1              # never relaunched the world
    assert result.shrinks == 1 and result.grows == 1
    assert result.resize_fallbacks == 0
    died = [x for x in result.reports if x.rank == 3 and x.exit_code == 9]
    assert died, result.reports
    podkilled = [x for x in result.reports if "pod-killed (pod 1" in x.reason]
    assert podkilled and podkilled[0].rank == 2
    # pod 0 was never touched
    assert not any(x.rank in (0, 1) for x in result.reports)


def test_partition_report_expels_accused_pod_reporter_survives(tmp_path):
    """A worker's DCNPartitioned report (every rank still heartbeating)
    expels the ACCUSED pod as a unit with partition attribution; the
    reporting pod stays alive and adopts the shrunken world."""
    sup = _pod_sup(tmp_path, die_rank=-1, horizon_s=8.0)
    fired = []

    def tick(s, attempt, elapsed):
        if not fired and all(s._hb_age(r, time.time()) is not None
                             for r in range(4)):
            with open(report_marker(s.attempt_dir, 0), "w") as f:
                json.dump({"pod": 1, "pods": [1], "op": "exchange 'sdc'",
                           "attempts": 3}, f)
            fired.append(True)

    sup._tick = tick
    result = sup.run()
    assert fired
    assert result.attempts == 1
    assert result.shrinks == 1 and result.grows == 1
    assert result.resize_fallbacks == 0
    part = [x for x in result.reports if "dcn-partitioned" in x.reason]
    assert {x.rank for x in part} == {2, 3}
    assert all("pod 1" in x.reason for x in part)
    # the reporter was held, not expelled
    assert not any(x.rank in (0, 1) for x in result.reports)


def test_slow_dcn_marker_alone_expels_nothing(tmp_path):
    """A merely-slow DCN (pacing marker, no report, no death) must be
    absorbed: no shrink, no expulsion, clean single-attempt finish."""
    sup = _pod_sup(tmp_path, die_rank=-1, horizon_s=4.0)
    paced = []

    def tick(s, attempt, elapsed):
        if not paced and s.attempt_dir and os.path.isdir(s.attempt_dir):
            chaos.slow_dcn(s, 0.2)
            paced.append(True)

    sup._tick = tick
    result = sup.run()
    assert paced
    assert result.attempts == 1
    assert result.shrinks == 0 and result.grows == 0
    assert result.reports == []


# ---------------------------------------------------------------------------
# acceptance: 2x2-process two-pod CPU training gang, pod loss mid-pass
# ---------------------------------------------------------------------------


def test_pod_sigkill_midpass_two_pod_gang_recovers_to_oracle(
        tmp_path, monkeypatch):
    """THE cross-pod acceptance proof: ONE rank of pod 1 in a 4-process
    (2 pods x 2 ranks) training gang is SIGKILLed mid-pass.  The
    supervisor expels the WHOLE pod (its partner with pod-killed
    attribution) — never relaunching the world — the survivors shrink
    the dcn axis and keep training, and a replacement pod grows back at
    a batch boundary.  The surviving pod's losses and final params match
    an uninterrupted run to 1e-6, and the regrown pod's tail matches the
    oracle through the end."""
    ref_losses, ref_params = _reference_run(monkeypatch)
    script = tmp_path / "worker.py"
    script.write_text(TRAIN_WORKER)
    out_dir = tmp_path / "out"
    out_dir.mkdir()
    sup = _supervisor(
        4, script,
        [str(tmp_path / "ckpts"), str(out_dir), "kill", "3", "0.1"],
        gang_dir=str(tmp_path / "gang"), max_restarts=2, elastic=True,
        pod_size=2)
    result = sup.run()

    assert result.attempts == 1              # NO whole-gang relaunch
    assert result.shrinks == 1 and result.grows == 1
    assert result.resize_fallbacks == 0
    assert (out_dir / "fault-fired").exists()
    shrunk = [r for r in result.reports if "elastic shrink" in r.reason]
    assert {r.rank for r in shrunk} == {2, 3}
    assert any(r.rank == 3 and r.exit_code == -signal.SIGKILL
               for r in shrunk), result.reports
    assert any(r.rank == 2 and "pod-killed (pod 1" in r.reason
               for r in shrunk), result.reports

    # the surviving pod trained EVERY batch, uninterrupted, to oracle
    with open(out_dir / "losses-rank0.json") as f:
        got = json.load(f)
    assert set(got) == set(ref_losses)
    for key, v in got.items():
        np.testing.assert_allclose(v, ref_losses[key], rtol=1e-6,
                                   err_msg=key)
    final = np.load(out_dir / "final-rank0.npz")
    for k, v in ref_params.items():
        np.testing.assert_allclose(final[k], v, rtol=1e-6, atol=1e-7)

    # the regrown pod joined from the resize checkpoint and its tail
    # matches the oracle wherever it trained, through the end
    with open(out_dir / "losses-rank3.json") as f:
        got3 = json.load(f)
    assert "2:5" in got3
    for key, v in got3.items():
        np.testing.assert_allclose(v, ref_losses[key], rtol=1e-6,
                                   err_msg=f"joiner {key}")
