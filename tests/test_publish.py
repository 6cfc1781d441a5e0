"""Continuous train->publish->reload (docs/publish.md).

The loop under test: a training run cuts gated, versioned deploy bundles
(paddle_tpu/publish) only from scrub-verified checkpoint bytes; a serving
replica hot-swaps to new versions with zero dropped requests and zero
fresh XLA compiles (publish-warmed shared cache + architecture-fingerprint
keys); a bad version — corrupt on disk, NaN-poisoned, failing warmup —
either never swaps in or is automatically rolled back within its
probation window; and the whole train-commit -> serving-ready freshness
SLO is reconstructable from the journal.
"""

import json
import os
import shutil

import numpy as np
import pytest

import paddle_tpu.nn as nn
from paddle_tpu.obs.journal import close_journal, journal_path, read_journal
from paddle_tpu.param.optimizers import Adam
from paddle_tpu.publish import (PublishRefused, freshness_from_journal,
                                publish_cache_dir, publish_from_checkpoints)
from paddle_tpu.resilience import chaos
from paddle_tpu.serving import InferenceFailed, InferenceServer
from paddle_tpu.serving.reload import HotSwapManager, load_published
from paddle_tpu.trainer import SGDTrainer
from paddle_tpu.utils.flags import FLAGS


def _tiny_trainer():
    nn.reset_naming()
    x = nn.data("x", size=6, is_seq=True)
    pool = nn.pooling(nn.fc(x, 8, act="relu", name="h"),
                      pooling_type="max", name="pool")
    logits = nn.fc(pool, 3, act="linear", name="logits")
    label = nn.data("label", size=1, dtype="int32")
    cost = nn.classification_cost(logits, label, name="cost")
    return SGDTrainer(cost, Adam(learning_rate=0.05), seed=0)


def _batch(rng):
    xs = rng.randn(4, 5, 6).astype(np.float32)
    lens = np.array([5, 3, 4, 5], np.int32)
    return {"x": (xs, lens), "label": np.zeros((4, 1), np.int32)}


def _req(batch):
    xs, lens = batch["x"]
    return {"x": (xs[:1], lens[:1])}


def _boot(pub, **mgr_kw):
    """Boot a server from the newest published version with the publish
    dir's shared warm cache, plus its HotSwapManager."""
    model, info, v = load_published(pub)
    srv = InferenceServer(model, outputs=["logits"], max_batch=4,
                          batch_delay_ms=1.0, max_queue=64,
                          default_deadline_ms=60000.0,
                          breaker_threshold=50)
    srv.start(compile_cache=publish_cache_dir(pub))
    mgr = HotSwapManager(srv, pub, **mgr_kw)
    mgr.attach_current(v, info)
    return srv, mgr


def _expected(pub, version, req):
    """The version's ground-truth reply, from its bundle directly."""
    from paddle_tpu.config import load_inference_model
    from paddle_tpu.publish import version_dir

    m = load_inference_model(
        os.path.join(version_dir(pub, version), "model.ptz"))
    return m.infer(req, outputs=["logits"])["logits"]


# ---------------------------------------------------------------------------
# the publication gate
# ---------------------------------------------------------------------------


def test_publish_gate_refusals_typed_and_journaled(tmp_path, monkeypatch,
                                                   rng):
    """An unverified or quarantined pass is unpublishable by
    construction, and every refusal is journaled with its machine
    signal."""
    monkeypatch.setattr(FLAGS, "obs_journal", str(tmp_path / "j"))
    tr = _tiny_trainer()
    batch = _batch(rng)
    save, pub = str(tmp_path / "ckpt"), str(tmp_path / "pub")

    # nothing checkpointed yet -> nothing publishable
    with pytest.raises(PublishRefused) as ei:
        publish_from_checkpoints(pub, tr.topology, save)
    assert ei.value.reason == "no_verified_pass"

    tr.train_batch(batch)
    tr.save(save, 0)
    tr.train_batch(batch)
    tr.save(save, 1)
    # the scrubber blessed only pass 0: pass 1 exists, CRC-validates,
    # and is still refused — verification is the gate, not validity
    with open(os.path.join(save, "scrub.json"), "w") as f:
        json.dump({"latest_verified_pass": 0,
                   "passes": {"0": "ok", "1": "ok"}}, f)
    with pytest.raises(PublishRefused) as ei:
        publish_from_checkpoints(pub, tr.topology, save, pass_id=1)
    assert ei.value.reason == "pass_not_verified"

    # the default pass follows the verified tip, not the newest save
    vdir = publish_from_checkpoints(pub, tr.topology, save)
    with open(os.path.join(vdir, "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["version"] == 1 and manifest["pass_id"] == 0
    assert manifest["train_commit_time"] > 0
    assert manifest["files"]["model.ptz"]["crc32"]

    # a later quarantine makes even an explicit request unpublishable
    from paddle_tpu.resilience.checkpoint_io import (pass_dir,
                                                     quarantine_checkpoint)

    quarantine_checkpoint(pass_dir(save, 0), "sdc quarantine (test)")
    with pytest.raises(PublishRefused) as ei:
        publish_from_checkpoints(pub, tr.topology, save, pass_id=0)
    assert ei.value.reason == "pass_quarantined"

    close_journal()
    recs, torn = read_journal(journal_path(str(tmp_path / "j"), 0))
    assert torn == 0
    refused = [r for r in recs if r["kind"] == "publish_refused"]
    assert [r["reason"] for r in refused] == [
        "no_verified_pass", "pass_not_verified", "pass_quarantined"]
    commits = [r for r in recs if r["kind"] == "publish_commit"]
    assert len(commits) == 1 and commits[0]["version"] == 1


def test_corrupt_publish_skipped_previous_version_keeps_serving(
        tmp_path, monkeypatch, rng):
    """chaos.corrupt_publish on the newest version: the reload manager
    journals publish_skipped_corrupt ONCE, never swaps, and the previous
    version keeps answering correctly; a republished good version then
    swaps in normally."""
    monkeypatch.setattr(FLAGS, "obs_journal", str(tmp_path / "j"))
    tr = _tiny_trainer()
    batch = _batch(rng)
    req = _req(batch)
    save, pub = str(tmp_path / "ckpt"), str(tmp_path / "pub")
    tr.train_batch(batch)
    tr.save(save, 0)
    publish_from_checkpoints(pub, tr.topology, save)
    srv, mgr = _boot(pub, probation_requests=2)
    try:
        want1 = _expected(pub, 1, req)
        tr.train_batch(batch)
        tr.save(save, 1)
        publish_from_checkpoints(pub, tr.topology, save)
        vdir = chaos.corrupt_publish(pub)
        assert vdir is not None and vdir.endswith("v-00002")

        assert mgr.poll() is None          # nothing swappable
        assert mgr.current_version == 1 and 2 in mgr.rejected
        out = srv.submit(req).result(60)["logits"]
        np.testing.assert_allclose(out, want1, rtol=1e-5, atol=1e-6)
        assert srv.metrics.count("reload_skipped_corrupt") == 1
        mgr.poll()                         # rejected versions never re-journal
        assert srv.metrics.count("reload_skipped_corrupt") == 1

        # the fix is a REPUBLISH (new version), which swaps in cleanly
        publish_from_checkpoints(pub, tr.topology, save)
        for _ in range(100):
            mgr.poll()
            if mgr.current_version == 3:
                break
            srv.submit(req).result(60)
        assert mgr.current_version == 3
        assert srv.healthz()["model"]["version"] == 3
    finally:
        srv.close()
    close_journal()
    recs, _ = read_journal(journal_path(str(tmp_path / "j"), 0))
    skipped = [r for r in recs if r["kind"] == "publish_skipped_corrupt"]
    assert len(skipped) == 1 and skipped[0]["version"] == 2
    assert "CRC mismatch" in skipped[0]["reason"]


# ---------------------------------------------------------------------------
# zero-downtime hot-swap
# ---------------------------------------------------------------------------


def test_zero_dropped_requests_across_three_reload_cycles(tmp_path, rng):
    """The acceptance contract: a continuous request stream rides three
    hot-reload cycles with zero shed/dropped requests, every reply
    correct for the version that served it (no torn half-loaded models),
    zero fresh compile-cache misses on reload, and zero XLA compiles by
    any swapped-in model."""
    tr = _tiny_trainer()
    batch = _batch(rng)
    req = _req(batch)
    save, pub = str(tmp_path / "ckpt"), str(tmp_path / "pub")
    tr.train_batch(batch)
    tr.save(save, 0)
    publish_from_checkpoints(pub, tr.topology, save)
    srv, mgr = _boot(pub, probation_requests=2)
    try:
        expected = {1: _expected(pub, 1, req)}
        miss0 = srv.metrics.count("compile_cache_misses")
        served = []
        for v in (2, 3, 4):
            for _ in range(3):
                tr.train_batch(batch)
            tr.save(save, v - 1)
            publish_from_checkpoints(pub, tr.topology, save)
            expected[v] = _expected(pub, v, req)
            # versions must be distinguishable for the correctness check
            assert not np.allclose(expected[v], expected[v - 1],
                                   rtol=1e-4, atol=1e-5)
            for _ in range(100):
                out = srv.submit(req).result(60)["logits"]
                ks = [k for k, e in expected.items()
                      if np.allclose(out, e, rtol=1e-5, atol=1e-6)]
                assert len(ks) == 1, \
                    f"reply matches versions {ks}: torn swap"
                served.append(ks[0])
                mgr.poll()
                if mgr.current_version == v and not mgr.in_probation:
                    break
            assert mgr.current_version == v
            # the swapped-in model never compiled: warm shared cache +
            # architecture-fingerprint keys made the reload pure
            # deserialization
            assert srv.model.compile_events == 0
        assert served == sorted(served)    # versions only move forward
        assert {2, 3, 4} <= set(served)
        hz = srv.healthz()
        c = hz["counters"]
        assert c["shed"] == 0
        assert c["submitted"] == c["accepted"] == c["completed"]
        assert srv.metrics.count("compile_cache_misses") == miss0
        assert hz["model"]["version"] == 4
        assert c["model_swaps"] == 3
    finally:
        srv.close()


def test_nan_poisoned_version_rolls_back_within_probation(
        tmp_path, monkeypatch, rng):
    """A published version whose weights are NaN-poisoned passes the CRC
    gate (the bytes are intact) but regresses the typed error rate the
    moment it serves — probation auto-reverts to the resident previous
    bundle and journals publish_rollback naming the signal."""
    monkeypatch.setattr(FLAGS, "obs_journal", str(tmp_path / "j"))
    import jax
    import jax.numpy as jnp

    tr = _tiny_trainer()
    batch = _batch(rng)
    req = _req(batch)
    save, pub = str(tmp_path / "ckpt"), str(tmp_path / "pub")
    tr.train_batch(batch)
    tr.save(save, 0)
    publish_from_checkpoints(pub, tr.topology, save)
    srv, mgr = _boot(pub, probation_requests=16)
    try:
        want1 = _expected(pub, 1, req)
        tr.params = jax.tree_util.tree_map(
            lambda a: jnp.full_like(a, jnp.nan), tr.params)
        tr.save(save, 1)
        publish_from_checkpoints(pub, tr.topology, save)

        act = mgr.poll()
        assert act is not None and act["action"] == "swapped"
        fails = 0
        for _ in range(6):
            err = srv.submit(req).error(60)
            assert isinstance(err, InferenceFailed)   # typed, non-finite
            fails += 1
            act = mgr.tick()
            if act is not None:
                break
        assert act is not None and act["action"] == "rolled_back"
        assert act["signal"] == "error_rate_regression"
        assert act["rolled_back_to"] == 1

        # v1 serves again, immediately and correctly (it stayed resident:
        # the rollback was one attribute swap, no reload, no compile)
        out = srv.submit(req).result(60)["logits"]
        np.testing.assert_allclose(out, want1, rtol=1e-5, atol=1e-6)
        hz = srv.healthz()
        assert hz["model"]["version"] == 1
        assert mgr.current_version == 1 and 2 in mgr.rejected
        assert mgr.poll() is None          # the bad version is never retried
        assert srv.metrics.count("reload_rollbacks") == 1
    finally:
        srv.close()
    close_journal()
    recs, _ = read_journal(journal_path(str(tmp_path / "j"), 0))
    rb = [r for r in recs if r["kind"] == "publish_rollback"]
    assert len(rb) == 1
    assert rb[0]["version"] == 2
    assert rb[0]["signal"] == "error_rate_regression"
    assert rb[0]["rolled_back_to"] == 1


def test_kill_worker_mid_reload_strands_no_requests(tmp_path, rng):
    """chaos.kill_worker while a swap is in flight: the supervisor
    restarts the worker, the swap completes, and EVERY submitted request
    resolves (reply or typed error) — none time out stranded."""
    tr = _tiny_trainer()
    batch = _batch(rng)
    req = _req(batch)
    save, pub = str(tmp_path / "ckpt"), str(tmp_path / "pub")
    tr.train_batch(batch)
    tr.save(save, 0)
    publish_from_checkpoints(pub, tr.topology, save)
    srv, mgr = _boot(pub, probation_requests=2)
    try:
        tr.train_batch(batch)
        tr.save(save, 1)
        publish_from_checkpoints(pub, tr.topology, save)

        futs = [srv.submit(req) for _ in range(6)]
        chaos.kill_worker(srv)
        act = mgr.poll()                 # swap while the worker is down
        assert act is not None and act["action"] == "swapped"
        futs += [srv.submit(req) for _ in range(6)]
        for i, f in enumerate(futs):
            try:
                f.error(60)              # resolves to None or typed error
            except TimeoutError:
                pytest.fail(f"request {i} stranded across the reload")
        assert srv.supervisor.restarts >= 1
        for _ in range(100):
            mgr.poll()
            if mgr.current_version == 2:
                break
            srv.submit(req).result(60)
        assert mgr.current_version == 2
        want2 = _expected(pub, 2, req)
        np.testing.assert_allclose(srv.submit(req).result(60)["logits"],
                                   want2, rtol=1e-5, atol=1e-6)
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# freshness SLO
# ---------------------------------------------------------------------------


def test_freshness_slo_reconstructed_from_journal_and_healthz(
        tmp_path, monkeypatch, rng):
    """train-commit wall-clock rides the bundle into healthz
    (model_freshness_seconds) and the merged journal reconstructs the
    full train-commit -> publish -> swap -> serving-ready latency chain
    per version."""
    monkeypatch.setattr(FLAGS, "obs_journal", str(tmp_path / "j"))
    tr = _tiny_trainer()
    batch = _batch(rng)
    req = _req(batch)
    save, pub = str(tmp_path / "ckpt"), str(tmp_path / "pub")
    tr.train_batch(batch)
    tr.save(save, 0)
    publish_from_checkpoints(pub, tr.topology, save)
    srv, mgr = _boot(pub, probation_requests=2)
    try:
        hz = srv.healthz()
        assert hz["model"]["version"] == 1
        assert hz["model"]["freshness_s"] >= 0
        tr.train_batch(batch)
        tr.save(save, 1)
        publish_from_checkpoints(pub, tr.topology, save)
        for _ in range(100):
            mgr.poll()
            if mgr.current_version == 2:
                break
            srv.submit(req).result(60)
        assert mgr.current_version == 2
        assert srv.healthz()["model"]["freshness_s"] >= 0
    finally:
        srv.close()
    close_journal()
    recs, torn = read_journal(journal_path(str(tmp_path / "j"), 0))
    assert torn == 0
    kinds = [r["kind"] for r in recs]
    for k in ("publish_commit", "reload_commit", "probation_passed"):
        assert k in kinds, k
    rows = freshness_from_journal(recs)
    assert [r["version"] for r in rows] == [1, 2]
    r2 = rows[1]
    assert not r2["rolled_back"]
    assert r2["published_at"] >= r2["train_commit_time"]
    assert r2["serving_ready_at"] >= r2["swapped_at"] >= r2["published_at"]
    assert r2["freshness_s"] is not None and r2["freshness_s"] >= 0
    # v1 booted a fresh server rather than hot-swapping into one — it has
    # a publish record but no serving-ready marker in THIS journal
    assert rows[0]["swapped_at"] is None


# ---------------------------------------------------------------------------
# pserver table ride-along (satellite)
# ---------------------------------------------------------------------------


def test_table_reader_reload_stop_typed_journaled_and_counted(
        tmp_path, monkeypatch):
    """TableReader.hot_reload that cannot reach the newest snapshot:
    last_stop carries the typed (snap, member, reason) record for the
    probation logic, the stop is journaled as snapshot_reload_stopped,
    counted in the registry, surfaced in healthz — and cleared by the
    next clean reload."""
    monkeypatch.setattr(FLAGS, "obs_journal", str(tmp_path / "j"))
    from paddle_tpu.obs import get_registry
    from paddle_tpu.pserver.snapshot import (TableReader,
                                             save_table_snapshot, snap_dir)
    from paddle_tpu.pserver.table import TableSpec

    spec = TableSpec(name="t_pub", vocab=16, dim=4)
    base = np.arange(64, dtype=np.float32).reshape(16, 4)
    dirty = np.ones((16,), bool)
    d = str(tmp_path / "snaps")
    save_table_snapshot(d, spec, base, dirty, 0, shards=2)
    reader = TableReader(d)
    assert reader.last_stop is None

    save_table_snapshot(d, spec, base + 1, dirty, 1, shards=2)
    save_table_snapshot(d, spec, base + 2, dirty, 2, shards=2)
    chaos.corrupt_file(os.path.join(snap_dir(d, 1), "shard-000.npz"))

    before = get_registry().counter(
        "pserver_reload_stopped_total",
        "table hot-reloads stopped by a corrupt snapshot",
        labels=("table",), table=spec.name).value
    assert reader.hot_reload() == 0
    assert reader.version == 0             # still on the last good view
    stop = reader.last_stop
    assert stop is not None and stop.snap == 1
    assert stop.member == "shard-000.npz"
    assert "shard-000.npz" in str(stop)
    assert reader.healthz()["last_stop"]
    after = get_registry().counter(
        "pserver_reload_stopped_total",
        "table hot-reloads stopped by a corrupt snapshot",
        labels=("table",), table=spec.name).value
    assert after == before + 1

    # repair (republish the snapshot) -> clean reload clears the stop
    shutil.rmtree(snap_dir(d, 1))
    save_table_snapshot(d, spec, base + 1, dirty, 1, shards=2)
    assert reader.hot_reload() > 0
    assert reader.version == 2 and reader.last_stop is None
    assert reader.healthz()["last_stop"] is None
    np.testing.assert_array_equal(reader.table, base + 2)

    close_journal()
    recs, _ = read_journal(journal_path(str(tmp_path / "j"), 0))
    stopped = [r for r in recs if r["kind"] == "snapshot_reload_stopped"]
    assert len(stopped) == 1
    assert stopped[0]["table"] == "t_pub"
    assert stopped[0]["snap"] == 1
    assert stopped[0]["member"] == "shard-000.npz"
