"""Gang-supervised cluster runtime (paddle_tpu/resilience/cluster.py).

The acceptance chaos proofs for multi-host failure recovery, on REAL
2-process CPU gangs (each rank is an OS process running the full trainer;
gang coordination rides the supervisor's shared-directory protocol, so no
``jax.distributed`` collectives are needed — those are unavailable on the
CPU backend):

- SIGKILL of a random rank mid-pass -> the supervisor kills the gang,
  relaunches it, ``--resume=auto`` restores the last gang-consistent
  checkpoint, and the completed run's losses/params match an
  uninterrupted single-process run to 1e-6;
- a heartbeat-stalled rank (wedged-in-a-collective model) is detected
  within the configured watchdog timeout and the gang restarts;
- a checkpoint corrupted BETWEEN restarts falls back (here: to a fresh
  start) and still converges to the uninterrupted run;
- an always-crashing gang exhausts its restart budget and surfaces a
  typed ``GangFailedError`` with per-rank exit attribution.

Every multiprocess test runs under a hard ``signal.alarm`` timeout (no
pytest-timeout in the image) so a supervision bug can never hang tier-1.
"""

import json
import os
import random
import signal
import textwrap
import threading
import time

import numpy as np
import pytest

import paddle_tpu.nn as nn
from paddle_tpu.param.optimizers import Adam
from paddle_tpu.resilience import (GangContext, GangError, GangFailedError,
                                   GangResized, GangSupervisor,
                                   PreemptionHandler, chaos)
from paddle_tpu.trainer import SGDTrainer, events as ev
from paddle_tpu.utils.flags import FLAGS

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HARD_TIMEOUT_S = 240


@pytest.fixture(autouse=True)
def hard_timeout():
    """Hard per-test deadline: gang tests spawn and kill process trees —
    a supervision bug must fail loudly, never eat the tier-1 budget."""
    def _abort(signum, frame):
        raise RuntimeError(f"gang test exceeded {HARD_TIMEOUT_S}s hard timeout")

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
# GangContext protocol units (in-process, threads as ranks)
# ---------------------------------------------------------------------------


def _ctx(d, rank, size, **kw):
    kw.setdefault("heartbeat_s", 0.0)
    kw.setdefault("barrier_timeout_s", 30.0)
    return GangContext(str(d), rank, size, **kw)


def test_barrier_rendezvous_two_ranks(tmp_path):
    g0, g1 = _ctx(tmp_path, 0, 2), _ctx(tmp_path, 1, 2)
    order = []

    def peer():
        time.sleep(0.15)
        order.append("r1-arrives")
        g1.barrier()

    t = threading.Thread(target=peer)
    t.start()
    g0.barrier()          # must block until rank 1 arrives
    order.append("r0-released")
    t.join()
    assert order == ["r1-arrives", "r0-released"]
    # sequence numbering: the NEXT barrier is a fresh rendezvous, not
    # satisfied by the previous round's arrival files
    t = threading.Thread(target=g1.barrier)
    t.start()
    g0.barrier()
    t.join()


def test_barrier_times_out_when_peer_never_arrives(tmp_path):
    g0 = _ctx(tmp_path, 0, 2, barrier_timeout_s=0.2)
    with pytest.raises(GangError, match="barrier"):
        g0.barrier()


def test_preemption_or_reduced_across_ranks(tmp_path):
    """A SIGTERM delivered to ONE host must checkpoint everyone: the
    handler's `requested` is the gang OR, evaluated at the boundary."""
    g0, g1 = _ctx(tmp_path, 0, 2), _ctx(tmp_path, 1, 2)
    h0 = PreemptionHandler(gang=g0)
    h1 = PreemptionHandler(gang=g1)
    assert not h0.poll() and not h1.poll()
    h0.request()                       # "signal" lands on rank 0 only
    assert h1.requested is False       # property is local + side-effect-free
    assert h0.poll()                   # rank 0's boundary poll publishes...
    assert h1.poll()                   # ...and rank 1 agrees at its boundary
    assert h1.requested                # the gang decision latched locally


def test_coordinator_broadcast_resume_decision(tmp_path):
    g0, g1 = _ctx(tmp_path, 0, 2), _ctx(tmp_path, 1, 2)
    got = {}

    def peer():
        got["decision"] = g1.broadcast_json(None, name="resume")

    t = threading.Thread(target=peer)
    t.start()
    time.sleep(0.05)
    g0.broadcast_json({"pass": 7, "start_pass": 8, "start_batch": 0},
                      name="resume")
    t.join()
    assert got["decision"]["pass"] == 7 and got["decision"]["start_pass"] == 8


def test_heartbeat_writes_and_throttles(tmp_path):
    g = GangContext(str(tmp_path), 0, 2, heartbeat_s=1000.0)
    g.heartbeat()
    hb = tmp_path / "hb-rank0"
    assert hb.read_text() == "1"
    g.heartbeat()                      # inside the throttle window: no-op
    assert hb.read_text() == "1"
    g.heartbeat(force=True)
    assert hb.read_text() == "2"


# ---------------------------------------------------------------------------
# elastic world protocol (docs/resilience.md "Elastic gang")
# ---------------------------------------------------------------------------


def _publish_world(d, epoch, ranks, coordinator=None, reason="test"):
    with open(os.path.join(str(d), "world.json"), "w") as f:
        json.dump({"epoch": epoch, "ranks": ranks,
                   "coordinator": coordinator if coordinator is not None
                   else min(ranks), "size": 2, "reason": reason}, f)


def test_world_poll_adopt_and_ack(tmp_path):
    g = _ctx(tmp_path, 0, 2)
    assert g.poll_world() is None and not g.degraded and g.world_size == 2
    _publish_world(tmp_path, 1, [0], reason="rank 1 died")
    w = g.poll_world()
    assert w is not None and w["epoch"] == 1
    g.adopt_world(w)
    assert g.epoch == 1 and g.world_size == 1 and g.degraded
    assert g.is_coordinator
    assert g.poll_world() is None        # same epoch never re-fires
    g.ack_resize()
    assert (tmp_path / "resize-ack-e001-rank0").exists()
    # a 1-rank barrier completes trivially under the new membership
    g.barrier()


def test_coordinator_follows_survivors(tmp_path):
    """Rank 0 (the original coordinator) died: the published world names a
    surviving coordinator and rank 1 takes over publish duties."""
    g = _ctx(tmp_path, 1, 2)
    assert not g.is_coordinator
    _publish_world(tmp_path, 1, [1], coordinator=1)
    g.adopt_world(g.poll_world())
    assert g.is_coordinator
    # decisions are epoch-namespaced so a joiner can never read a stale one
    g.broadcast_json({"pass": 3}, name="resume")
    assert (tmp_path / "pub-resume-e001.json").exists()


def test_barrier_aborts_with_gang_resized_when_world_changes(tmp_path):
    """A rank waiting in a barrier for a peer that just DIED must not wait
    out the timeout: the supervisor's world publish aborts the wait with
    GangResized so the trainer can run the resize protocol instead."""
    g0 = _ctx(tmp_path, 0, 2, barrier_timeout_s=30.0)

    def publish():
        time.sleep(0.2)
        _publish_world(tmp_path, 1, [0])

    t = threading.Thread(target=publish)
    t.start()
    t0 = time.monotonic()
    with pytest.raises(GangResized) as ei:
        g0.barrier()
    t.join()
    assert time.monotonic() - t0 < 10.0          # aborted, not timed out
    assert ei.value.world["epoch"] == 1 and ei.value.world["ranks"] == [0]
    # inside the resize protocol itself the same wait must NOT abort
    # (the grow path barriers under the old membership while the new
    # world is already published): suppressed via resizing()
    g1 = _ctx(tmp_path, 1, 2, barrier_timeout_s=2.0)
    g0b = _ctx(tmp_path, 0, 2, barrier_timeout_s=2.0)
    _publish_world(tmp_path, 2, [0, 1])

    def peer():
        with g1.resizing():
            g1.barrier()

    t = threading.Thread(target=peer)
    t.start()
    with g0b.resizing():
        g0b.barrier()                            # completes despite epoch 2
    t.join()


def test_joiner_requires_published_world(tmp_path):
    """A replacement launched into epoch E must find world.json at least
    that new — a missing/stale world is a typed error, never a silent
    fall-back to the full membership."""
    with pytest.raises(GangError, match="joiner"):
        GangContext(str(tmp_path), 1, 2, heartbeat_s=0.0, epoch=2)
    _publish_world(tmp_path, 2, [0, 1], coordinator=0)
    g = GangContext(str(tmp_path), 1, 2, heartbeat_s=0.0, epoch=2)
    assert g.epoch == 2 and g.world_size == 2 and not g.is_coordinator


# ---------------------------------------------------------------------------
# supervisor process control (cheap scripts, no jax import)
# ---------------------------------------------------------------------------


def _supervisor(n, script, args=(), **kw):
    kw.setdefault("heartbeat_s", 0.2)
    kw.setdefault("watchdog_s", 5.0)
    kw.setdefault("startup_grace_s", 180.0)
    kw.setdefault("backoff_s", 0.05)
    kw.setdefault("poll_s", 0.05)
    kw.setdefault("env", {"PYTHONPATH": REPO_ROOT + os.pathsep
                          + os.environ.get("PYTHONPATH", "")})
    return GangSupervisor(["localhost"] * n, str(script), list(args), **kw)


def test_supervisor_clean_gang_exits_first_attempt(tmp_path):
    script = tmp_path / "ok.py"
    script.write_text("import sys\nsys.exit(0)\n")
    sup = _supervisor(2, script, gang_dir=str(tmp_path / "gang"))
    result = sup.run()
    assert result.attempts == 1 and result.reports == []
    sup.cleanup()
    assert not os.path.exists(sup.gang_dir)


def test_restart_budget_exhausted_raises_typed_error_with_attribution(tmp_path):
    script = tmp_path / "crash.py"
    script.write_text("import sys\nsys.exit(3)\n")
    sup = _supervisor(2, script, gang_dir=str(tmp_path / "gang"),
                      max_restarts=1)
    with pytest.raises(GangFailedError) as ei:
        sup.run()
    err = ei.value
    assert "max_restarts=1" in str(err)
    # per-rank exit attribution across both attempts
    assert {r.attempt for r in err.reports} == {0, 1}
    exits = [r for r in err.reports if r.reason == "exit"]
    assert exits and all(r.exit_code == 3 for r in exits)
    assert all(r.rank in (0, 1) and r.pid > 0 for r in err.reports)
    assert "exit=3" in err.reports[0].describe()


def test_one_dead_rank_takes_whole_gang_down(tmp_path):
    """Gang semantics: rank 1 would sleep forever; rank 0's death must
    kill it (never leak an orphan) and attribute it as gang-killed."""
    script = tmp_path / "split.py"
    script.write_text(textwrap.dedent("""\
        import os, sys, time
        if os.environ["PADDLE_TPU_PROCESS_ID"] == "0":
            sys.exit(7)
        # rank 1 heartbeats so only rank 0's exit can fail the gang
        hb = os.path.join(os.environ["PADDLE_TPU_GANG_DIR"], "hb-rank1")
        for _ in range(600):
            with open(hb, "w") as f: f.write("x")
            time.sleep(0.1)
    """))
    sup = _supervisor(2, script, gang_dir=str(tmp_path / "gang"),
                      max_restarts=0)
    with pytest.raises(GangFailedError) as ei:
        sup.run()
    reasons = {r.rank: r.reason for r in ei.value.reports}
    assert reasons[0] == "exit" and reasons[1] == "gang-killed"
    # nothing left alive
    assert all(p.poll() is not None for p in sup.launcher.procs)


def test_straggler_after_clean_peer_exit_bounded_by_watchdog(tmp_path):
    """Review fix: a rank that exits 0 early (or is left waiting in a
    barrier by a peer that preempt-exited) keeps heartbeating, so neither
    death-poll nor staleness fires — the drain clock must bound the
    inconsistent gang at watchdog_s, not the 600s barrier timeout."""
    script = tmp_path / "straggle.py"
    script.write_text(textwrap.dedent("""\
        import os, sys, time
        if os.environ["PADDLE_TPU_PROCESS_ID"] == "0":
            sys.exit(0)
        hb = os.path.join(os.environ["PADDLE_TPU_GANG_DIR"], "hb-rank1")
        for _ in range(600):               # alive + heartbeating forever
            with open(hb, "w") as f: f.write("x")
            time.sleep(0.1)
    """))
    sup = _supervisor(2, script, gang_dir=str(tmp_path / "gang"),
                      max_restarts=0, watchdog_s=1.0)
    t0 = time.monotonic()
    with pytest.raises(GangFailedError) as ei:
        sup.run()
    assert time.monotonic() - t0 < 30.0    # watchdog-bounded, not 600s
    straggler = [r for r in ei.value.reports if "straggler" in r.reason]
    assert straggler and straggler[0].rank == 1


def test_successful_run_scrubs_attempt_dirs(tmp_path):
    script = tmp_path / "ok.py"
    script.write_text("import sys\nsys.exit(0)\n")
    gang_dir = tmp_path / "gang"
    sup = _supervisor(2, script, gang_dir=str(gang_dir))
    sup.run()
    assert not gang_dir.exists()  # no scratch left behind on success


def test_launcher_poll_and_kill_gang(tmp_path):
    from paddle_tpu.parallel import launch_local

    script = tmp_path / "sleep.py"
    script.write_text("import time\ntime.sleep(600)\n")
    l = launch_local(2, str(script))
    try:
        assert l.poll() == [None, None]
        # SIGSTOPped ranks ignore SIGTERM; kill_gang must still reap them
        chaos.hang_rank(l, 1)
        codes = l.kill_gang()
        assert all(c is not None for c in codes)
        assert l.poll() == codes
    finally:
        l.kill_gang()


# ---------------------------------------------------------------------------
# restart-backoff jitter (satellite: thundering-herd protection)
# ---------------------------------------------------------------------------


class _FixedRng:
    def __init__(self, vals):
        self._vals = list(vals)

    def random(self):
        return self._vals.pop(0)


def test_restart_backoff_jitter_bounds(tmp_path):
    """Jitter draws each restart delay from [(1-j)*delay, delay].  Pinned
    with an injected rng: delay_k = min(backoff * 2^k, cap) * (1 - j*u_k)."""
    script = tmp_path / "crash.py"
    script.write_text("import sys\nsys.exit(3)\n")
    sleeps = []
    sup = _supervisor(1, script, gang_dir=str(tmp_path / "gang"),
                      max_restarts=3, backoff_s=1.0, max_backoff_s=8.0,
                      backoff_jitter=0.5, rng=_FixedRng([0.0, 1.0, 0.5]),
                      sleep=sleeps.append)
    with pytest.raises(GangFailedError):
        sup.run()
    backoffs = [s for s in sleeps if s >= 0.4]   # drop poll-cadence sleeps
    assert backoffs == pytest.approx([1.0, 1.0, 3.0])
    # u=0 keeps the full delay, u=1 halves it at jitter 0.5: every draw
    # stays inside the documented band
    for k, s in enumerate(backoffs):
        base = min(1.0 * 2.0 ** k, 8.0)
        assert 0.5 * base <= s <= base


def test_backoff_jitter_defaults_to_flag(tmp_path, monkeypatch):
    monkeypatch.setattr(FLAGS, "gang_backoff_jitter", 0.25)
    monkeypatch.setattr(FLAGS, "gang_elastic", True)
    sup = GangSupervisor(["localhost"], str(tmp_path / "x.py"))
    assert sup.backoff_jitter == 0.25
    assert sup.elastic is True and sup.min_ranks == FLAGS.gang_min_ranks


# ---------------------------------------------------------------------------
# elastic supervisor machinery (cheap protocol stubs, no jax import)
# ---------------------------------------------------------------------------

# Each rank heartbeats, acks every world epoch it is a member of, and
# exits 0 at an ABSOLUTE wall-clock deadline (argv) so survivors and a
# late-launched joiner stop together.  Rank `die_rank` (argv) exits
# nonzero after `die_after` seconds — but only in its epoch-0
# incarnation, so its replacement survives.
ELASTIC_STUB = textwrap.dedent("""\
    import json, os, sys, time
    d = os.environ["PADDLE_TPU_GANG_DIR"]
    r = int(os.environ["PADDLE_TPU_PROCESS_ID"])
    epoch = int(os.environ.get("PADDLE_TPU_GANG_EPOCH", "0"))
    deadline_ts, die_rank, die_after = (
        float(sys.argv[1]), int(sys.argv[2]), float(sys.argv[3]))
    joiner = epoch > 0
    t0 = time.time()
    def ack(e):
        with open(os.path.join(d, f"resize-ack-e{e:03d}-rank{r}"), "w") as f:
            f.write("1")
    if joiner:
        ack(epoch)
    while time.time() < deadline_ts:
        with open(os.path.join(d, f"hb-rank{r}"), "w") as f:
            f.write("x")
        try:
            with open(os.path.join(d, "world.json")) as f:
                w = json.load(f)
            if w["epoch"] > epoch and r in w["ranks"]:
                epoch = w["epoch"]
                ack(epoch)
        except Exception:
            pass
        if (not joiner and r == die_rank
                and time.time() - t0 > die_after):
            os._exit(9)
        time.sleep(0.02)
    sys.exit(0)
""")


def _elastic_stub_sup(tmp_path, *, horizon_s=6.0, die_rank=1,
                      die_after=0.5, **kw):
    script = tmp_path / "stub.py"
    script.write_text(ELASTIC_STUB)
    kw.setdefault("elastic", True)
    kw.setdefault("watchdog_s", 2.0)
    kw.setdefault("startup_grace_s", 10.0)
    kw.setdefault("max_restarts", 2)
    return _supervisor(
        2, script,
        [str(time.time() + horizon_s), str(die_rank), str(die_after)],
        gang_dir=str(tmp_path / "gang"), **kw)


def test_elastic_shrink_then_grow_back_no_relaunch(tmp_path):
    """Supervisor half of the elastic path on protocol stubs: rank 1 dies
    -> world shrinks to rank 0 (no gang kill), then a replacement is
    relaunched and the world grows back — all inside ONE attempt."""
    sup = _elastic_stub_sup(tmp_path)
    result = sup.run()
    assert result.attempts == 1                  # never relaunched the world
    assert result.shrinks == 1 and result.grows == 1
    assert result.resize_fallbacks == 0
    assert "shrink" in result.last_resize_reason or (
        "grow" in result.last_resize_reason)
    shrunk = [x for x in result.reports if "elastic shrink" in x.reason]
    assert shrunk and shrunk[0].rank == 1 and shrunk[0].exit_code == 9


def test_elastic_respects_min_ranks(tmp_path):
    """Below --gang_min_ranks the elastic path must refuse to shrink and
    take the classic whole-gang relaunch instead."""
    sup = _elastic_stub_sup(tmp_path, min_ranks=2, max_restarts=0,
                            horizon_s=4.0)
    with pytest.raises(GangFailedError):
        sup.run()
    assert sup.shrinks == 0 and sup.grows == 0


def test_elastic_hang_is_expelled_by_kill(tmp_path):
    """A SIGSTOPped (wedged) rank can't be waited out: the shrink must
    SIGKILL it before publishing the smaller world (a half-alive host
    must never write into the new epoch)."""
    sup = _elastic_stub_sup(tmp_path, die_rank=-1, horizon_s=8.0)
    stopped = []

    def tick(s, attempt, elapsed):
        if not stopped and s._hb_age(1, time.time()) is not None:
            chaos.slow_rank(s, 1, stop_s=60.0)   # SIGCONT long after expel
            stopped.append(True)

    sup._tick = tick
    result = sup.run()
    assert result.attempts == 1
    assert result.shrinks == 1 and result.grows == 1
    hung = [x for x in result.reports if "hung" in x.reason]
    assert hung and hung[0].rank == 1


# ---------------------------------------------------------------------------
# end-to-end recovery on a 2-process CPU training gang
# ---------------------------------------------------------------------------

# Each rank runs the REAL trainer on one virtual CPU device.  Gang
# coordination (rank-0 publish + barrier, coordinator-resolved resume,
# heartbeats) rides the supervisor's shared gang dir.  Rank 0 dumps its
# per-(pass,batch) losses and final params on clean completion.
TRAIN_WORKER = textwrap.dedent("""\
    import json, os, sys, time

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("PADDLE_TPU_COMPUTE_DTYPE", "float32")

    import numpy as np

    import paddle_tpu.nn as nn
    from paddle_tpu.param.optimizers import Adam
    from paddle_tpu.resilience import chaos
    from paddle_tpu.trainer import SGDTrainer, events as ev
    from paddle_tpu.utils import FLAGS

    save_dir, out_dir, mode, chaos_rank = sys.argv[1:5]
    # optional per-batch pace: the elastic tests stretch the workload so
    # protocol latencies (supervisor poll, joiner warmup) land INSIDE
    # training instead of racing past its end
    pace = float(sys.argv[5]) if len(sys.argv) > 5 else 0.0
    # or, in place of a pace, the survivor of an elastic kill WAITS for what
    # the protocol does next (the victim's marker, then each world the
    # supervisor publishes), so that no resize races the end of training
    hold = len(sys.argv) > 6 and sys.argv[6] == "hold"
    rank = int(os.environ["PADDLE_TPU_PROCESS_ID"])
    FLAGS.save_dir = save_dir
    FLAGS.log_period = 0

    x = nn.data("x", size=4)
    y = nn.data("y", size=2)
    cost = nn.mse_cost(input=nn.fc(x, 2, act="relu", name="h"), label=y)
    tr = SGDTrainer(cost, Adam(learning_rate=0.05), seed=0)

    rs = np.random.RandomState(0)
    feeds = [{"x": rs.randn(4, 4).astype(np.float32),
              "y": rs.randn(4, 2).astype(np.float32)} for _ in range(6)]

    losses = {}
    def record(e):
        if isinstance(e, ev.EndIteration):
            losses[f"{e.pass_id}:{e.batch_id}"] = float(e.cost)
            if pace:
                time.sleep(pace)

    handler = record
    marker = os.path.join(out_dir, "fault-fired")

    def wait_for(what, happened):
        deadline = time.monotonic() + 120.0
        while not happened():
            assert time.monotonic() < deadline, f"rank {rank}: no {what}"
            tr._gang.heartbeat()     # waiting on the supervisor is no hang
            time.sleep(0.01)

    worlds_seen = 0
    def record_and_hold(e):
        # the victim dies as its batch 1:2 begins: this rank goes no
        # further than 1:1 before that, and then no further than one batch
        # beyond each world (the shrink's, the grow's) before it is published
        global worlds_seen
        record(e)
        if not isinstance(e, ev.EndIteration):
            return
        if (e.pass_id, e.batch_id) == (1, 1):
            wait_for("fault marker", lambda: os.path.exists(marker))
        if os.path.exists(marker) and worlds_seen < 2:
            worlds_seen += 1
            wait_for(f"world of epoch {worlds_seen}", lambda:
                     tr._gang.peek_world()["epoch"] >= worlds_seen)

    if hold and mode == "kill" and rank != int(chaos_rank):
        handler = record_and_hold
    if mode == "resize_die" and rank != int(chaos_rank):
        # the SURVIVOR dies the moment its elastic resize begins — the
        # mid-reshard fault that must fall back to whole-gang relaunch
        handler = chaos.die_during_resize(
            marker=os.path.join(out_dir, "resize-fault-fired"),
            inner=record)
    elif rank == int(chaos_rank):
        if mode in ("kill", "resize_die"):
            handler = chaos.die_at(pass_id=1, batch=2, marker=marker,
                                   inner=record)
        elif mode == "hang":
            handler = chaos.stall_at(pass_id=1, batch=1, marker=marker,
                                     inner=record)

    tr.train(lambda: iter(feeds), num_passes=3, event_handler=handler,
             resume="auto")

    with open(os.path.join(out_dir, f"losses-rank{rank}.json"), "w") as f:
        json.dump(losses, f)
    if rank == 0:
        np.savez(os.path.join(out_dir, "final-rank0.npz"),
                 **{k: np.asarray(v) for k, v in tr.params.items()})
""")


def _reference_run(monkeypatch):
    """The uninterrupted oracle: same model/seed/feeds, one process."""
    monkeypatch.setattr(FLAGS, "save_dir", "")
    monkeypatch.setattr(FLAGS, "log_period", 0)
    nn.reset_naming()
    x = nn.data("x", size=4)
    y = nn.data("y", size=2)
    cost = nn.mse_cost(input=nn.fc(x, 2, act="relu", name="h"), label=y)
    tr = SGDTrainer(cost, Adam(learning_rate=0.05), seed=0)
    rs = np.random.RandomState(0)
    feeds = [{"x": rs.randn(4, 4).astype(np.float32),
              "y": rs.randn(4, 2).astype(np.float32)} for _ in range(6)]
    losses = {}

    def record(e):
        if isinstance(e, ev.EndIteration):
            losses[f"{e.pass_id}:{e.batch_id}"] = float(e.cost)

    tr.train(lambda: iter(feeds), num_passes=3, event_handler=record)
    return losses, {k: np.asarray(v) for k, v in tr.params.items()}


def _train_gang(tmp_path, mode, chaos_rank, pace=0.0, save_dir=None,
                hold=False, **kw):
    script = tmp_path / "worker.py"
    script.write_text(TRAIN_WORKER)
    if save_dir is None:
        save_dir = str(tmp_path / "ckpts")
    out_dir = tmp_path / "out"
    out_dir.mkdir()
    sup = _supervisor(
        2, script,
        [save_dir, str(out_dir), mode, str(chaos_rank), str(pace),
         "hold" if hold else "-"],
        gang_dir=str(tmp_path / "gang"), max_restarts=2, **kw)
    return sup, out_dir


def _load_losses(out_dir, rank=0):
    with open(os.path.join(out_dir, f"losses-rank{rank}.json")) as f:
        return json.load(f)


def test_sigkill_random_rank_midpass_recovers_to_identical_losses(
        tmp_path, monkeypatch):
    """THE acceptance proof: a random rank of a 2-process gang is
    SIGKILLed mid-pass (pass 1, batch 2).  The supervisor kills the gang,
    relaunches, resume='auto' restores the last gang-consistent
    checkpoint (pass 0 — pass 1's save never passed the barrier), and the
    completed run reproduces the uninterrupted run's losses and final
    params to 1e-6."""
    ref_losses, ref_params = _reference_run(monkeypatch)
    victim = random.Random(0xC0FFEE).randrange(2)
    sup, out_dir = _train_gang(tmp_path, "kill", victim)
    result = sup.run()

    assert result.attempts == 2
    assert (out_dir / "fault-fired").exists()
    # attribution: the victim died (SIGKILL = -9), the peer was gang-killed
    victim_reports = [r for r in result.reports if r.rank == victim]
    assert any(r.reason == "exit" and r.exit_code == -signal.SIGKILL
               for r in victim_reports), result.reports

    got = _load_losses(out_dir)
    assert "2:5" in got                       # ran to the end
    for key, v in got.items():                # the resumed tail == oracle
        np.testing.assert_allclose(v, ref_losses[key], rtol=1e-6,
                                   err_msg=key)
    final = np.load(out_dir / "final-rank0.npz")
    for k, v in ref_params.items():
        np.testing.assert_allclose(final[k], v, rtol=1e-6, atol=1e-7)


def test_hung_rank_detected_by_watchdog_and_gang_restarted(
        tmp_path, monkeypatch):
    """Rank 1 stalls mid-pass (heartbeat silence = wedged-in-a-collective
    model).  The watchdog must flag it within the configured timeout and
    the relaunched gang must complete."""
    ref_losses, _ = _reference_run(monkeypatch)
    # headroom matters: under full-suite CPU load a relaunched rank's
    # post-resume JIT compile can exceed a tight watchdog before its first
    # heartbeat, buying a spurious extra restart (attempts == 3)
    watchdog_s = 10.0
    sup, out_dir = _train_gang(tmp_path, "hang", 1, watchdog_s=watchdog_s)
    result = sup.run()

    assert result.attempts == 2
    hung = [r for r in result.reports if r.reason == "hung" and r.rank == 1]
    assert hung, result.reports
    # detected within the watchdog budget: staleness at detection sits in
    # [watchdog_s, watchdog_s + slack] — slack covers poll cadence + fs
    assert watchdog_s <= hung[0].stale_s <= watchdog_s + 10.0
    got = _load_losses(out_dir)
    assert "2:5" in got
    for key, v in got.items():
        np.testing.assert_allclose(v, ref_losses[key], rtol=1e-6,
                                   err_msg=key)


def test_checkpoint_corrupted_between_restarts_falls_back(
        tmp_path, monkeypatch):
    """Cluster chaos: kill rank 0 mid-pass AND corrupt the newest gang
    checkpoint between the kill and the relaunch.  Auto-resume must skip
    the damaged pass (here falling back to a fresh start) and the rerun
    still matches the uninterrupted oracle everywhere."""
    ref_losses, ref_params = _reference_run(monkeypatch)
    corrupted = {}

    def on_restart(sup, attempt):
        corrupted[attempt] = chaos.corrupt_latest_checkpoint(
            str(tmp_path / "ckpts"))

    sup, out_dir = _train_gang(tmp_path, "kill", 0, on_restart=on_restart)
    result = sup.run()

    assert result.attempts == 2
    assert corrupted[0]                      # pass-0 really was damaged
    got = _load_losses(out_dir)
    assert set(got) == set(ref_losses)       # fresh start: every batch rerun
    for key, v in got.items():
        np.testing.assert_allclose(v, ref_losses[key], rtol=1e-6,
                                   err_msg=key)
    final = np.load(out_dir / "final-rank0.npz")
    for k, v in ref_params.items():
        np.testing.assert_allclose(final[k], v, rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# elastic gang: end-to-end on real 2-process CPU training gangs
# ---------------------------------------------------------------------------


def test_elastic_sigkill_midpass_shrinks_and_grows_back_to_oracle(
        tmp_path, monkeypatch):
    """THE elastic acceptance proof: rank 1 of a 2-process gang is
    SIGKILLed mid-pass with elastic mode on.  The supervisor does NOT
    relaunch the world: the survivor shrinks the gang (drain ->
    checkpoint-commit -> resume mid-pass) and keeps training, then a
    replacement is launched and the gang grows back at the next batch
    boundary — the joiner restores the resize checkpoint and finishes the
    run.  The surviving rank's losses and final params match an
    uninterrupted run to 1e-6, and the joiner's tail matches the oracle
    wherever it trained."""
    ref_losses, ref_params = _reference_run(monkeypatch)
    # paced batches (0.1s): the shrink->grow sequence must land while the
    # survivor still has work, so the joiner provably trains a real tail
    sup, out_dir = _train_gang(tmp_path, "kill", 1, elastic=True, pace=0.1)
    result = sup.run()

    assert result.attempts == 1              # never relaunched the world
    assert result.shrinks == 1 and result.grows == 1
    assert result.resize_fallbacks == 0
    assert (out_dir / "fault-fired").exists()
    shrunk = [r for r in result.reports if "elastic shrink" in r.reason]
    assert shrunk and shrunk[0].rank == 1
    assert shrunk[0].exit_code == -signal.SIGKILL

    # the survivor trained EVERY batch, uninterrupted, to oracle losses
    got = _load_losses(out_dir, rank=0)
    assert set(got) == set(ref_losses)
    for key, v in got.items():
        np.testing.assert_allclose(v, ref_losses[key], rtol=1e-6,
                                   err_msg=key)
    final = np.load(out_dir / "final-rank0.npz")
    for k, v in ref_params.items():
        np.testing.assert_allclose(final[k], v, rtol=1e-6, atol=1e-7)

    # the replacement joined from the resize checkpoint mid-pass and its
    # tail matches the oracle wherever it trained, through the end
    got1 = _load_losses(out_dir, rank=1)
    assert "2:5" in got1
    for key, v in got1.items():
        np.testing.assert_allclose(v, ref_losses[key], rtol=1e-6,
                                   err_msg=f"joiner {key}")


def test_die_during_resize_falls_back_to_whole_gang_relaunch(
        tmp_path, monkeypatch):
    """Chaos `die_during_resize`: rank 0 dies mid-pass, and the SURVIVOR
    is killed the moment its shrink begins (mid-reshard).  The elastic
    path must fall back to the classic whole-gang relaunch — within the
    existing restart budget — and the rerun still matches the oracle."""
    ref_losses, ref_params = _reference_run(monkeypatch)
    sup, out_dir = _train_gang(tmp_path, "resize_die", 0, elastic=True)
    result = sup.run()

    assert result.attempts == 2              # fallback relaunch, bounded
    assert result.resize_fallbacks >= 1
    assert (out_dir / "fault-fired").exists()
    assert (out_dir / "resize-fault-fired").exists()
    fell_back = [r for r in result.reports if "fallback" in r.reason]
    assert fell_back, result.reports

    got = _load_losses(out_dir)
    assert "2:5" in got                      # ran to the end after relaunch
    for key, v in got.items():
        np.testing.assert_allclose(v, ref_losses[key], rtol=1e-6,
                                   err_msg=key)
    final = np.load(out_dir / "final-rank0.npz")
    for k, v in ref_params.items():
        np.testing.assert_allclose(final[k], v, rtol=1e-6, atol=1e-7)


def test_elastic_grow_back_without_save_dir_still_completes(tmp_path):
    """Regression (review): the joiner's rendezvous (epoch resume
    decision -> join barrier -> ack) must run for EVERY epoch>0 launch,
    not only under resume=auto with a save_dir.  With no save_dir there
    is nothing durable to restore — the resize commit is a bare barrier
    and the grow decision broadcasts pass -1 — but the grow must still
    COMPLETE: the survivor shrinks, the replacement joins fresh, and no
    resize ever times out into the whole-gang-relaunch fallback.  Decided
    on counts: the survivor waits for each world the supervisor publishes
    (``hold``) where a pace per batch raced the end of training."""
    sup, out_dir = _train_gang(tmp_path, "kill", 1, elastic=True, hold=True,
                               save_dir="")
    result = sup.run()

    assert result.attempts == 1              # never relaunched the world
    assert result.shrinks == 1 and result.grows == 1
    assert result.resize_fallbacks == 0
    assert (out_dir / "fault-fired").exists()
    # the joiner trained a real (fresh-params, nothing to restore) tail
    # through the end of the run
    got1 = _load_losses(out_dir, rank=1)
    assert "2:5" in got1


def test_elastic_observability_in_worker_extras(tmp_path):
    """Satellite: the trainer surfaces world_size / degraded /
    resize_count / last_resize_reason next to its step extras when a gang
    is attached (single-rank gang here — cheap, no supervisor)."""
    import json as _json

    _publish_world(tmp_path, 0, [0])  # noop; ensures dir exists
    x = nn.data("x", size=4)
    y = nn.data("y", size=2)
    cost = nn.mse_cost(input=nn.fc(x, 2, act="relu", name="eo_h"), label=y)
    tr = SGDTrainer(cost, Adam(learning_rate=0.05), seed=0)
    feeds = [{"x": np.zeros((4, 4), np.float32),
              "y": np.zeros((4, 2), np.float32)}]
    os.environ["PADDLE_TPU_GANG_DIR"] = str(tmp_path)
    os.environ["PADDLE_TPU_GANG_SIZE"] = "1"
    os.environ["PADDLE_TPU_PROCESS_ID"] = "0"
    try:
        tr.train(lambda: iter(feeds), num_passes=1)
    finally:
        for k in ("PADDLE_TPU_GANG_DIR", "PADDLE_TPU_GANG_SIZE",
                  "PADDLE_TPU_PROCESS_ID"):
            os.environ.pop(k, None)
    ex = tr._last_extras
    assert ex["world_size"] == 1 and ex["degraded"] is False
    assert ex["resize_count"] == 0 and ex["last_resize_reason"] is None


# ---------------------------------------------------------------------------
# distributed init latch (satellite)
# ---------------------------------------------------------------------------


def test_shutdown_distributed_resets_the_latch():
    """Satellite: initialize_distributed is a one-shot latch; supervised
    re-entry and multi-scenario tests need shutdown_distributed to reopen
    it.  Single-host path: init no-ops but latches; shutdown unlatches
    without touching jax.distributed (nothing live)."""
    from paddle_tpu.parallel import distributed as dist

    prev = (dist._initialized, dist._live)
    try:
        dist._initialized = dist._live = False
        dist.initialize_distributed()        # single-host: latch only
        assert dist._initialized and not dist._live
        dist.shutdown_distributed()
        assert not dist._initialized and not dist._live
        dist.initialize_distributed()        # re-entry works
        assert dist._initialized
        dist.shutdown_distributed()
    finally:
        dist._initialized, dist._live = prev
