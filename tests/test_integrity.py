"""Silent-data-corruption firewall (paddle_tpu/resilience/integrity.py).

Unit tier of the SDC defense (docs/resilience.md "Silent corruption"):

- the in-jit fingerprint is BIT-STABLE — golden-pinned, identical to its
  host (numpy) twin for every supported dtype, invariant under jit
  recompiles, device placement and mesh shape —
  while being decisively sensitive to a single flipped bit (and to
  WHERE it flipped);
- the vote identifies a strict-majority minority exactly and falls back
  to the coordinator-presumed tie (the 2-replica case) deterministically;
- the in-trace agreement collective over the mesh data axis localizes a
  corrupted replica without a host round-trip for the state;
- the gang exchange channel rendezvouses digests and aborts into
  ``GangResized`` when the world changes mid-exchange;
- the scrubber quarantines newly-corrupt checkpoints OUT of
  ``latest_pass`` eligibility (journaled `ckpt_quarantined` /
  `scrub_fail`), marks the newest fully-verified pass, and ``fsck``
  names corrupt members; snapshot manifests carry the independent
  ``fp64`` digest;
- ``lint --sdc`` pins the check-off step equation-identical to a
  never-enabled build and the check-on step host-transfer-free.

The end-to-end detect → expel → heal proof on a real 2-process gang
lives in tests/test_sdc_gang.py.
"""

import json
import os
import threading
import time
import zipfile

import numpy as np
import pytest

import paddle_tpu.nn as nn
from paddle_tpu.parallel.mesh import MeshConfig
from paddle_tpu.resilience import (GangContext, GangResized, chaos,
                                   save_checkpoint)
from paddle_tpu.resilience.checkpoint_io import (latest_pass, pass_dir,
                                                 validate_checkpoint)
from paddle_tpu.resilience.integrity import (ScrubDaemon, fingerprint_hex,
                                             fingerprint_int,
                                             latest_verified_pass,
                                             make_agreement_check,
                                             np_tree_fingerprint,
                                             scrub_paths, sdc_vote,
                                             tree_fingerprint)
from paddle_tpu.utils.error import ConfigError
from paddle_tpu.utils.flags import FLAGS

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402


@pytest.fixture(autouse=True)
def fresh_names():
    nn.reset_naming()
    yield


def _golden_tree():
    return {
        "w": np.arange(12, dtype=np.float32).reshape(3, 4),
        "b": np.ones((4,), np.float32),
        "n": np.int32(7),
    }


#: the fingerprint constants are an on-disk/manifest contract (checkpoint
#: meta, snapshot fp64): a refactor that changes the fold silently turns
#: every cross-replica agreement check into a false alarm — pinned.
GOLDEN_HEX = "4f0510482f33b28f"


# ---------------------------------------------------------------------------
# fingerprint stability
# ---------------------------------------------------------------------------


def test_fingerprint_golden_pinned():
    assert fingerprint_hex(jax.jit(tree_fingerprint)(_golden_tree())) \
        == GOLDEN_HEX
    assert fingerprint_hex(np_tree_fingerprint(_golden_tree())) == GOLDEN_HEX


def test_fingerprint_jit_matches_host_twin_across_dtypes():
    import ml_dtypes

    rs = np.random.RandomState(0)
    tree = {
        "f32": rs.randn(5, 3).astype(np.float32),
        "bf16": rs.randn(7).astype(ml_dtypes.bfloat16),
        "f16": rs.randn(6).astype(np.float16),
        "i32": rs.randint(-100, 100, (4,)).astype(np.int32),
        "u8": rs.randint(0, 255, (9,)).astype(np.uint8),
        "bool": rs.rand(5) > 0.5,
        "scalar": np.float32(3.25),
        "empty": np.zeros((0, 4), np.float32),
    }
    fp_jit = np.asarray(jax.jit(tree_fingerprint)(tree))
    np.testing.assert_array_equal(fp_jit, np_tree_fingerprint(tree))


def test_fingerprint_sensitive_to_single_bit_and_its_position():
    base = fingerprint_int(np_tree_fingerprint(_golden_tree()))
    flips = []
    for byte in (10, 14, 30):
        t = _golden_tree()
        t["w"].view(np.uint8).ravel()[byte] ^= 0x04
        flips.append(fingerprint_int(np_tree_fingerprint(t)))
    assert all(f != base for f in flips)
    assert len(set(flips)) == len(flips)  # position-sensitive, not parity
    # leaf NAMES are part of the digest: same values under other keys
    # must not collide (a resize that renamed leaves would be caught)
    renamed = {k + "_x": v for k, v in _golden_tree().items()}
    assert fingerprint_int(np_tree_fingerprint(renamed)) != base


def test_fingerprint_stable_across_recompile_and_placement():
    tree = _golden_tree()
    host = fingerprint_int(np_tree_fingerprint(tree))
    # fresh jit closures (the process-restart proxy: nothing cached)
    assert fingerprint_int(jax.jit(tree_fingerprint)(tree)) == host
    assert fingerprint_int(jax.jit(tree_fingerprint)(tree)) == host
    # replicated placement under two different mesh shapes — the digest
    # is a property of the VALUES, not the world
    for shape in (8, 4):
        mesh = MeshConfig.of(data=shape).build()
        placed = {k: jax.device_put(jnp.asarray(v),
                                    NamedSharding(mesh, P()))
                  for k, v in tree.items()}
        assert fingerprint_int(jax.jit(tree_fingerprint)(placed)) == host
    # batch-sharded leaves (GSPMD partial sums) fold to the same digest
    mesh = MeshConfig.of(data=8).build()
    big = {"x": np.arange(8 * 16, dtype=np.float32).reshape(8, 16)}
    sharded = {"x": jax.device_put(jnp.asarray(big["x"]),
                                   NamedSharding(mesh, P("data", None)))}
    assert fingerprint_int(jax.jit(tree_fingerprint)(sharded)) \
        == fingerprint_int(np_tree_fingerprint(big))


def _tiny_trainer(seed=0, opt=None):
    from paddle_tpu.param.optimizers import Adam
    from paddle_tpu.trainer import SGDTrainer

    nn.reset_naming()
    x = nn.data("ix", size=4)
    y = nn.data("iy", size=2)
    cost = nn.mse_cost(input=nn.fc(x, 2, act="relu", name="ih"), label=y)
    return SGDTrainer(cost, opt or Adam(learning_rate=0.05), seed=seed)


def _feed(rs):
    return {"ix": rs.randn(4, 4).astype(np.float32),
            "iy": rs.randn(4, 2).astype(np.float32)}


def test_step_fingerprint_matches_hand_applied_per_leaf_update(monkeypatch):
    """Satellite pin: the in-step fingerprint of the trainer's state equals
    that of the same step with ``update_leaf`` applied to each leaf by hand
    in the optimizer's place — a refactor of ``Optimizer.update`` cannot
    quietly turn agreement checks into false alarms."""
    from paddle_tpu.param.optimizers import Adam

    class HandApplied(Adam):
        def update(self, params, grads, opt_state, *, finite, **_):
            # the trainer's guard is on: each new value under its select
            step = opt_state["step"] + 1
            out = {k: self.update_leaf(p, grads[k], opt_state["slots"][k],
                                       self.lr_at(step), step)
                   for k, p in params.items()}
            new = ({k: v[0] for k, v in out.items()},
                   {"step": step,
                    "slots": {k: v[1] for k, v in out.items()}})
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(finite, n, o), new,
                (params, opt_state))

    monkeypatch.setattr(FLAGS, "sdc_check_every", 2)
    fps = {}
    for opt in (Adam(learning_rate=0.05), HandApplied(learning_rate=0.05)):
        nn.reset_naming()
        tr = _tiny_trainer(opt=opt)
        rs = np.random.RandomState(7)
        tr.train_batch(_feed(rs))
        tr.train_batch(_feed(rs))
        fps[type(opt).__name__] = fingerprint_int(
            jax.device_get(tr._last_extras["sdc_fp"]))
    assert fps["Adam"] == fps["HandApplied"]


def test_step_fingerprint_detects_inprocess_bit_flip(monkeypatch):
    monkeypatch.setattr(FLAGS, "sdc_check_every", 1)
    rs_a, rs_b = np.random.RandomState(7), np.random.RandomState(7)
    tr_a, tr_b = _tiny_trainer(), _tiny_trainer()
    tr_a.train_batch(_feed(rs_a))
    tr_b.train_batch(_feed(rs_b))
    fp1a = fingerprint_int(jax.device_get(tr_a._last_extras["sdc_fp"]))
    fp1b = fingerprint_int(jax.device_get(tr_b._last_extras["sdc_fp"]))
    assert fp1a == fp1b                       # replicas agree while clean
    desc = chaos.flip_param_bit(tr_b, leaf="_ih.w0", index=1, bit=20)
    assert "_ih.w0" in desc
    tr_a.train_batch(_feed(rs_a))
    tr_b.train_batch(_feed(rs_b))
    fp2a = fingerprint_int(jax.device_get(tr_a._last_extras["sdc_fp"]))
    fp2b = fingerprint_int(jax.device_get(tr_b._last_extras["sdc_fp"]))
    assert fp2a != fp2b                       # the flip is visible


def test_step_without_sdc_flag_has_no_fingerprint(monkeypatch):
    monkeypatch.setattr(FLAGS, "sdc_check_every", 0)
    tr = _tiny_trainer()
    tr.train_batch(_feed(np.random.RandomState(0)))
    assert "sdc_fp" not in tr._last_extras


def test_flip_shard_row_perturbs_one_row():
    class _Tab:
        data = jnp.asarray(np.ones((4, 3), np.float32))

    t = _Tab()
    before = np.asarray(t.data).copy()
    chaos.flip_shard_row(t, row=2, col=1)
    after = np.asarray(t.data)
    diff = np.argwhere(before != after)
    assert diff.tolist() == [[2, 1]]


# ---------------------------------------------------------------------------
# the vote
# ---------------------------------------------------------------------------


def test_vote_agreement_and_strict_majority():
    assert sdc_vote({0: 5, 1: 5, 2: 5}, 0).agreed
    v = sdc_vote({0: 5, 1: 9, 2: 5}, 0)
    assert not v.agreed and not v.tie
    assert v.presumed == 5 and v.minority == [1]
    # the corrupt COORDINATOR is outvoted like anyone else
    v = sdc_vote({0: 9, 1: 5, 2: 5}, 0)
    assert v.minority == [0] and not v.tie


def test_vote_tie_presumes_coordinator():
    v = sdc_vote({0: 5, 1: 9}, 0)
    assert v.tie and v.presumed == 5 and v.minority == [1]
    # the published coordinator may be any surviving rank
    v = sdc_vote({0: 5, 1: 9}, 1)
    assert v.tie and v.presumed == 9 and v.minority == [0]
    # even split at 4 ranks: no strict majority
    v = sdc_vote({0: 5, 1: 5, 2: 9, 3: 9}, 0)
    assert v.tie and v.presumed == 5 and v.minority == [2, 3]


# ---------------------------------------------------------------------------
# in-trace agreement collective (mesh data axis)
# ---------------------------------------------------------------------------


def test_agreement_check_localizes_corrupt_replica():
    mesh = MeshConfig.of(data=8).build()
    check = make_agreement_check(mesh)
    rs = np.random.RandomState(0)
    base = rs.randn(6, 4).astype(np.float32)
    stacked = np.broadcast_to(base, (8, 6, 4)).copy()
    tree = {"w": jax.device_put(jnp.asarray(stacked),
                                NamedSharding(mesh, P("data")))}
    fps, minority = check(tree)
    assert not bool(np.any(np.asarray(minority)))
    assert len({fingerprint_int(r) for r in np.asarray(fps)}) == 1
    # flip one bit of replica 5's slice only
    stacked[5].view(np.uint8).ravel()[13] ^= 0x10
    tree = {"w": jax.device_put(jnp.asarray(stacked),
                                NamedSharding(mesh, P("data")))}
    fps, minority = check(tree)
    assert np.asarray(minority).tolist() == [False] * 5 + [True] + [False] * 2
    rows = [fingerprint_int(r) for r in np.asarray(fps)]
    assert rows[5] != rows[0] and len(set(rows)) == 2


def test_agreement_spec_rejects_missing_or_unit_axis():
    from paddle_tpu.parallel.api import agreement_spec

    with pytest.raises(ConfigError, match="not in mesh"):
        agreement_spec(MeshConfig.of(data=8).build(), "model")
    with pytest.raises(ConfigError, match=">=2 replicas"):
        agreement_spec(MeshConfig.of(data=1, model=8))
    mesh, axis, n = agreement_spec(MeshConfig.of(data=8))
    assert axis == "data" and n == 8


# ---------------------------------------------------------------------------
# gang exchange channel
# ---------------------------------------------------------------------------


def _ctx(d, rank, size, **kw):
    kw.setdefault("heartbeat_s", 0.0)
    kw.setdefault("barrier_timeout_s", 30.0)
    return GangContext(str(d), rank, size, **kw)


def test_exchange_json_rendezvous_two_ranks(tmp_path):
    g0, g1 = _ctx(tmp_path, 0, 2), _ctx(tmp_path, 1, 2)
    got = {}

    def peer():
        time.sleep(0.1)
        got[1] = g1.exchange_json(0xBEEF, name="sdc-p0-b1")

    t = threading.Thread(target=peer)
    t.start()
    got[0] = g0.exchange_json(0xCAFE, name="sdc-p0-b1")
    t.join()
    assert got[0] == {0: 0xCAFE, 1: 0xBEEF}
    assert got[1] == got[0]
    # a second exchange under a different name is a fresh rendezvous
    t = threading.Thread(
        target=lambda: g1.exchange_json(2, name="sdc-p0-b3"))
    t.start()
    out = g0.exchange_json(1, name="sdc-p0-b3")
    t.join()
    assert out == {0: 1, 1: 2}


def test_exchange_json_aborts_on_world_publish(tmp_path):
    g0 = _ctx(tmp_path, 0, 2)

    def publish():
        time.sleep(0.15)
        with open(os.path.join(str(tmp_path), "world.json"), "w") as f:
            json.dump({"epoch": 1, "ranks": [0], "coordinator": 0,
                       "size": 2, "reason": "peer died"}, f)

    t = threading.Thread(target=publish)
    t.start()
    t0 = time.monotonic()
    with pytest.raises(GangResized):
        g0.exchange_json(7, name="sdc-p0-b1")
    t.join()
    assert time.monotonic() - t0 < 10.0


# ---------------------------------------------------------------------------
# scrubber + quarantine + fsck
# ---------------------------------------------------------------------------


def _make_ckpts(root, n=2):
    params = {"w": np.arange(8, dtype=np.float32)}
    for pid in range(n):
        params = {"w": params["w"] + 1.0}
        save_checkpoint(str(root), pid, params=params)
    return params


def test_scrub_quarantines_and_marks_latest_verified(tmp_path):
    root = tmp_path / "ckpts"
    _make_ckpts(root, n=2)
    assert latest_pass(str(root)) == 1
    chaos.corrupt_checkpoint(pass_dir(str(root), 1))
    report = scrub_paths([str(root)], quarantine=True)
    assert not report.clean and report.checked == 2
    f = report.findings[0]
    assert f.kind == "checkpoint" and f.member == "params.npz"
    assert f.quarantined
    # the marker demotes the dir out of latest_pass eligibility...
    assert os.path.exists(os.path.join(pass_dir(str(root), 1),
                                       "QUARANTINED"))
    reason = validate_checkpoint(pass_dir(str(root), 1))
    assert reason is not None and "quarantined" in reason
    assert latest_pass(str(root)) == 0
    # ...and scrub.json marks the newest fully-verified pass
    with open(os.path.join(str(root), "scrub.json")) as fh:
        state = json.load(fh)
    assert state["latest_verified_pass"] == 0
    assert latest_verified_pass(str(root)) == 0
    # re-scrubbing an already-quarantined dir reports but re-journals
    # nothing new and stays idempotent
    report2 = scrub_paths([str(root)], quarantine=True)
    assert len(report2.findings) == 1
    assert report2.findings[0].already_quarantined


def test_latest_pass_journals_ckpt_quarantined(tmp_path, monkeypatch):
    """Satellite: the read path's silent skip now lands in the journal
    with the failing member named, so `obs merge` postmortems see WHEN a
    checkpoint went bad."""
    from paddle_tpu.obs import close_journal
    from paddle_tpu.obs.journal import read_journal

    root = tmp_path / "ckpts"
    _make_ckpts(root, n=2)
    chaos.corrupt_checkpoint(pass_dir(str(root), 1))
    jdir = tmp_path / "journal"
    monkeypatch.setattr(FLAGS, "obs_journal", str(jdir))
    try:
        assert latest_pass(str(root)) == 0
    finally:
        close_journal()
        monkeypatch.setattr(FLAGS, "obs_journal", "")
    recs, torn = read_journal(os.path.join(str(jdir),
                                           "events-r00000.jsonl"))
    assert torn == 0
    quar = [r for r in recs if r["kind"] == "ckpt_quarantined"]
    assert quar and quar[0]["member"] == "params.npz"
    assert "pass-00001" in quar[0]["dir"] and quar[0]["reason"]


def test_scrub_names_corrupt_bundle_member(tmp_path):
    bundle = tmp_path / "model.ptz"
    with zipfile.ZipFile(bundle, "w") as z:
        z.writestr("manifest.json", json.dumps({"magic": "x"}))
        z.writestr("params.npz", os.urandom(4096))
    report = scrub_paths([str(tmp_path)])
    assert report.clean
    chaos.corrupt_file(str(bundle), offset=200, nbytes=16)
    report = scrub_paths([str(tmp_path)])
    assert [f.kind for f in report.findings] == ["bundle"]
    assert report.findings[0].member  # zip names the failing member


def test_snapshot_manifest_carries_fp64_and_detects_mismatch(tmp_path):
    from paddle_tpu.pserver.snapshot import (read_snapshot_manifest,
                                             save_table_snapshot,
                                             snap_dir, validate_snapshot)
    from paddle_tpu.pserver.table import TableSpec

    spec = TableSpec(name="t", vocab=16, dim=4)
    data = jnp.asarray(np.arange(64, dtype=np.float32).reshape(16, 4))
    dirty = np.ones((16,), bool)
    d = save_table_snapshot(str(tmp_path / "snaps"), spec, data, dirty, 0,
                            shards=2)
    assert validate_snapshot(d) is None
    m = read_snapshot_manifest(d)
    assert all("fp64" in info for info in m["files"].values())
    # a stale/tampered manifest digest is a detection, not a pass: the
    # fp64 is an INDEPENDENT second detector next to the CRCs
    m["files"]["shard-000.npz"]["fp64"] ^= 1
    with open(os.path.join(d, "manifest.json"), "w") as fh:
        json.dump(m, fh)
    reason = validate_snapshot(d)
    assert reason is not None and "fp64 mismatch" in reason


def test_scrub_daemon_quarantines_in_background(tmp_path):
    root = tmp_path / "ckpts"
    _make_ckpts(root, n=1)
    chaos.corrupt_checkpoint(pass_dir(str(root), 0))
    daemon = ScrubDaemon(str(root), every_s=0.05).start()
    try:
        deadline = time.monotonic() + 10.0
        while (not os.path.exists(os.path.join(pass_dir(str(root), 0),
                                               "QUARANTINED"))
               and time.monotonic() < deadline):
            time.sleep(0.02)
    finally:
        daemon.stop()
    assert daemon.scrubs >= 1 and daemon.corrupt_found >= 1
    assert latest_pass(str(root)) == -1


def test_fsck_exit_codes_and_member_naming(tmp_path, capsys):
    from paddle_tpu.resilience.integrity import run_fsck

    root = tmp_path / "ckpts"
    _make_ckpts(root, n=2)
    assert run_fsck([str(root)]) == 0
    chaos.corrupt_checkpoint(pass_dir(str(root), 1), target="params.npz")
    capsys.readouterr()
    assert run_fsck([str(root)]) == 2
    out = capsys.readouterr().out
    assert "params.npz" in out and "pass-00001" in out


# ---------------------------------------------------------------------------
# the lint gate
# ---------------------------------------------------------------------------


def test_lint_sdc_gate_is_clean():
    """--sdc_check_every=0 compiles to today's exact step (equation
    identity across builds) and the enabled step's in-jit fingerprint
    audits host-transfer-free — the acceptance contract of the firewall."""
    from paddle_tpu.resilience.integrity import audit_sdc_step

    findings = audit_sdc_step()
    errors = [f for f in findings if f.severity == "ERROR"]
    assert not errors, [f.message for f in errors]


def test_checkpoint_meta_records_state_fingerprint(tmp_path, monkeypatch):
    monkeypatch.setattr(FLAGS, "sdc_check_every", 2)
    monkeypatch.setattr(FLAGS, "save_dir", "")
    tr = _tiny_trainer()
    tr.train_batch(_feed(np.random.RandomState(0)))
    d = tr.save(str(tmp_path), 0)
    from paddle_tpu.resilience.checkpoint_io import read_manifest

    meta = read_manifest(d)["meta"]
    assert meta["sdc_fp"] == fingerprint_hex(
        jax.device_get(tr._last_extras["sdc_fp"]))


def test_rollback_target_prefers_agreement_certified_checkpoint(
        tmp_path, monkeypatch):
    """A checkpoint saved from already-corrupt state hashes perfectly
    (its CRCs cover the corrupt bytes), so the tie rollback must prefer
    the newest pass whose manifest fingerprint the replicas actually
    AGREED on — the corruption cannot launder itself through the
    rollback — and fall back (journaled, not silent) only when nothing
    is certifiable."""
    monkeypatch.setattr(FLAGS, "sdc_check_every", 1)
    monkeypatch.setattr(FLAGS, "save_dir", "")
    tr = _tiny_trainer()
    rs = np.random.RandomState(0)
    tr.train_batch(_feed(rs))
    fp0 = fingerprint_int(jax.device_get(tr._last_extras["sdc_fp"]))
    tr.save(str(tmp_path), 0)                 # meta carries fp0
    tr.train_batch(_feed(rs))
    tr.save(str(tmp_path), 1)                 # meta carries fp1
    # only pass-0's fingerprint was vote-certified: pass-1 was saved
    # after the (hypothetical) flip and must be skipped even though it
    # CRC-validates
    tr._sdc_agreed_fps.append(fp0)
    assert tr._sdc_rollback_target(str(tmp_path), None) == 0
    # once pass-1's fp is certified too, the newest wins
    fp1 = fingerprint_int(jax.device_get(tr._last_extras["sdc_fp"]))
    tr._sdc_agreed_fps.append(fp1)
    assert tr._sdc_rollback_target(str(tmp_path), None) == 1
    # nothing certified (restart emptied the set): honest fallback to
    # the newest CRC-valid pass
    tr._sdc_agreed_fps.clear()
    assert tr._sdc_rollback_target(str(tmp_path), None) == 1


def test_exchange_json_retires_stale_round_files(tmp_path):
    g0, g1 = _ctx(tmp_path, 0, 2), _ctx(tmp_path, 1, 2)
    for i in range(4):
        t = threading.Thread(
            target=lambda i=i: g1.exchange_json(i, name=f"sdc-r{i}"))
        t.start()
        g0.exchange_json(i, name=f"sdc-r{i}")
        t.join()
    xchg = [n for n in os.listdir(str(tmp_path)) if n.startswith("xchg-")]
    # two-round retirement: at most the last two rounds' files remain
    # per rank (entering round k proves round k-2 is fully consumed)
    assert len(xchg) <= 2 * 2 * 2


def test_fsck_usage_error_is_not_corruption(capsys):
    """Exit 2 MEANS corrupt — a typo'd invocation must exit 1 so a CI
    wrapper never pages 'corruption' for a usage error."""
    from paddle_tpu.resilience.integrity import run_fsck

    assert run_fsck([]) == 1                  # missing paths
    assert run_fsck(["--no-such-flag", "/tmp"]) == 1
    capsys.readouterr()
