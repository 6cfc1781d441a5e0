"""Double-buffered async feeding (--prefetch_depth; data/feeder.py
BatchPrefetcher + trainer wiring).

Prepare + h2d of batch N+1 overlap the device step of batch N: batch N+1
has been asked of the reader before iteration N ends (tier-1, on counts),
and with the PR 9 step timeline as the instrument a paced reader
(chaos.slow_client — the trickling-input pattern) shows its pacing in
``data_wait`` WITHOUT prefetch and loses it (>=3x share drop) WITH it (the
``slow`` twin ``_timed``).  Semantics
are loop-equivalent: identical training trajectory, reader errors still
attributed to the data tier, bounded read-ahead, and clean drains at
preemption boundaries (resume stays batch-exact — the checkpoint records
batches the STEP consumed, not the read-ahead cursor).  The trainer takes
the next batch out of the queue when it DISPATCHES a step
(``BatchPrefetcher.take``), so the producer works under the device step and
sleeps across the turn-around; what is taken early is delivered where it
would have been.  Those tests wait on events the reader or the feeder sets,
never on a clock.
"""

import threading
import time

import numpy as np
import pytest

import paddle_tpu.nn as nn
from paddle_tpu.data.feeder import BatchPrefetcher, PreparedFeed
from paddle_tpu.param.optimizers import Adam
from paddle_tpu.resilience import PreemptionHandler, ReaderError, chaos
from paddle_tpu.trainer import SGDTrainer, events as ev
from paddle_tpu.utils.flags import FLAGS


@pytest.fixture(autouse=True)
def fresh_names():
    nn.reset_naming()
    yield


def _mse_trainer(seed=0, hidden=8, size=4, **kw):
    x = nn.data("x", size=size)
    y = nn.data("y", size=2)
    h = nn.fc(x, hidden, act="relu", name="h")
    cost = nn.mse_cost(input=nn.fc(h, 2, act="linear", name="o"), label=y)
    return SGDTrainer(cost, Adam(learning_rate=0.05), seed=seed, **kw)


def _feeds(n=6, batch=4, size=4):
    rs = np.random.RandomState(0)
    return [{"x": rs.randn(batch, size).astype(np.float32),
             "y": rs.randn(batch, 2).astype(np.float32)} for _ in range(n)]


def _host(params):
    return {k: np.asarray(v).copy() for k, v in params.items()}


# ---------------------------------------------------------------------------
# BatchPrefetcher unit behavior
# ---------------------------------------------------------------------------


def test_prefetcher_preserves_order_and_values():
    raw = list(range(20))
    seen = [b.feed for b in BatchPrefetcher(iter(raw),
                                            prepare=lambda r: r * 10,
                                            depth=3)]
    assert seen == [r * 10 for r in raw]


def test_prefetcher_wraps_in_prepared_feed_and_applies_transfer():
    pf = BatchPrefetcher(iter([1, 2]), prepare=lambda r: {"v": r},
                         transfer=lambda f: {**f, "t": True}, depth=2)
    items = list(pf)
    assert all(isinstance(i, PreparedFeed) for i in items)
    assert items[0].feed == {"v": 1, "t": True}


def test_prefetcher_propagates_reader_exception():
    def gen():
        yield 1
        raise IOError("disk gone")

    pf = BatchPrefetcher(iter(gen()), depth=2)
    assert next(pf).feed == 1
    with pytest.raises(IOError, match="disk gone"):
        next(pf)
    pf.close()


WAIT_S = 30.0   # an event that does not come is a failure, not a slow box


class _Pulls:
    """A reader that says how far the producer has read: ``wait(n)`` returns
    once item ``n`` (0-based) has been asked for, or the reader has ended."""

    def __init__(self, items, *, fail=None):
        self.items, self.fail = list(items), fail
        self.pulled = []
        self._events = [threading.Event() for _ in range(len(self.items) + 1)]

    def __iter__(self):
        for i, item in enumerate(self.items):
            self.pulled.append(i)
            self._events[i].set()
            yield item
        self._events[-1].set()
        if self.fail is not None:
            raise self.fail

    def wait(self, n):
        assert self._events[min(n, len(self.items))].wait(WAIT_S)


def _taken():
    from paddle_tpu.obs import get_registry

    return {when: get_registry().counter(
        "prefetch_taken_total", labels=("when",), when=when).value
        for when in ("dispatch", "late")}


@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize("take", [False, True], ids=["next_only", "take"])
def test_prefetcher_bounded_readahead(depth, take):
    """The producer reads at most depth (queued) + 1 (in its hands) batches
    ahead of a consumer that only calls ``next()``, and one more, the slot's,
    ahead of one that takes at dispatch — bounded abandoned work at a drain
    point.  The bound holds at every instant, not after a pause: the
    producer asks the reader for an item only once the one before is in the
    queue."""
    src = _Pulls(range(50))
    pf = BatchPrefetcher(iter(src), depth=depth)
    bound = depth + 1
    src.wait(bound - 1)             # it has read as far ahead as it can
    assert len(src.pulled) == bound
    if take:
        assert pf.take()            # frees a place: one more is read
        assert pf.take()            # the slot is filled: nothing moves
        bound += 1
        src.wait(bound - 1)
        assert len(src.pulled) == bound
        assert next(pf).feed == 0   # the slot's: the queue is not touched
        assert len(src.pulled) == bound
    assert next(pf).feed == int(take)       # the queue's head: a place free
    src.wait(bound)
    assert len(src.pulled) == bound + 1
    first = int(take) + 1
    assert [next(pf).feed for _ in range(3)] == [first, first + 1, first + 2]
    pf.close()


def test_take_at_dispatch_wakes_the_producer_and_next_lets_it_sleep():
    """``take()`` is what frees the queue's place: the producer, asleep in
    ``put`` with a prepared batch in its hands, puts it and goes on to
    prepare the next one; the ``next()`` that follows returns the slot and
    touches no queue, so nothing wakes."""
    prepared = []
    seen = [threading.Event() for _ in range(8)]

    def prepare(raw):
        prepared.append(raw)
        seen[raw].set()
        return raw

    before = _taken()
    pf = BatchPrefetcher(iter(range(8)), prepare=prepare, depth=1)
    assert seen[1].wait(WAIT_S)      # 0 queued, 1 in its hands: asleep
    assert prepared == [0, 1] and pf._q.full()
    assert pf.take()
    assert seen[2].wait(WAIT_S)      # woken by the take alone
    assert prepared == [0, 1, 2] and pf._q.full()
    assert next(pf).feed == 0        # from the slot
    assert pf._q.full() and prepared == [0, 1, 2]    # the queue untouched
    assert next(pf).feed == 1        # no take before it: from the queue
    assert seen[3].wait(WAIT_S)
    pf.close()
    after = _taken()
    assert after["dispatch"] - before["dispatch"] == 1
    assert after["late"] - before["late"] == 1


@pytest.mark.parametrize("end", ["end_marker", "reader_error",
                                 "prepare_error"])
def test_what_is_taken_early_is_delivered_at_its_own_next(end):
    """An exception or the end marker that ``take()`` finds at the queue's
    head waits in the slot: it surfaces at the ``next()`` where it would
    have without a take, after the batch before it was handed over."""
    from paddle_tpu.data.feeder import PrepareError

    src = _Pulls([1, 2],
                 fail=IOError("disk gone") if end == "reader_error" else None)

    def prepare(raw):
        if end == "prepare_error" and raw == 2:
            raise TypeError("bad slot")
        return raw

    pf = BatchPrefetcher(iter(src), prepare=prepare, depth=2)
    last = 1 if end == "prepare_error" else 2
    src.wait(last)                   # read to its end (or to the bad batch)
    for want in range(1, last + 1):
        assert pf.take()             # a batch goes into the slot
        assert next(pf).feed == want
    pf._thread.join(WAIT_S)          # the terminal item is in the queue
    assert pf.take()                 # taken early: nothing is raised here
    assert pf.take()
    with pytest.raises({"end_marker": StopIteration,
                        "reader_error": IOError,
                        "prepare_error": PrepareError}[end]):
        next(pf)
    assert pf._slot is None
    pf.close()


def test_close_drops_a_filled_slot_and_joins():
    src = _Pulls(range(50))
    pf = BatchPrefetcher(iter(src), depth=2)
    src.wait(2)
    assert pf.take() and pf._slot is not None
    pf.close()
    assert pf._slot is None and not pf._thread.is_alive()


def test_prefetcher_close_joins_producer_quickly():
    def slow_gen():
        for i in range(1000):
            time.sleep(0.005)
            yield i

    pf = BatchPrefetcher(iter(slow_gen()), depth=2)
    next(pf)
    t0 = time.monotonic()
    pf.close()
    assert time.monotonic() - t0 < 2.0
    assert not pf._thread.is_alive()


# ---------------------------------------------------------------------------
# trainer integration
# ---------------------------------------------------------------------------


def test_prefetch_training_trajectory_identical(monkeypatch):
    feeds = _feeds(6)
    losses = {}
    for depth in (0, 2):
        monkeypatch.setattr(FLAGS, "prefetch_depth", depth)
        nn.reset_naming()
        tr = _mse_trainer()
        got = []
        tr.train(lambda: iter(feeds), num_passes=2,
                 event_handler=lambda e: got.append(e.cost)
                 if isinstance(e, ev.EndIteration) else None)
        losses[depth] = (got, _host(tr.params))
    np.testing.assert_array_equal(losses[0][0], losses[2][0])
    for k in losses[0][1]:
        np.testing.assert_array_equal(losses[0][1][k], losses[2][1][k])


@pytest.mark.parametrize("reader_is", ["ahead", "behind"])
def test_trainer_takes_the_next_batch_at_dispatch(reader_is, monkeypatch):
    """``train_batch`` takes the next batch out of the queue right after it
    has dispatched the step.  Where the producer is ahead (batch k+1 queued
    before step k starts) every batch but a pass's first is taken at
    dispatch; where it is behind (batch k+1 is read only after step k's
    EndIteration) every batch is waited for in ``data_wait``: one count a
    batch either way, the same costs, in the same order of events."""
    monkeypatch.setattr(FLAGS, "prefetch_depth", 2)
    feeds, passes = _feeds(6), 2
    stepped = [threading.Event() for _ in feeds]
    src = None
    events = []

    def reader():
        nonlocal src
        for e in stepped:
            e.clear()
        src = _Pulls(feeds)
        if reader_is == "ahead":
            return iter(src)

        def paced():
            for i, feed in enumerate(src):
                if i:   # handed over only once the step before has ended
                    assert stepped[i - 1].wait(WAIT_S)
                yield feed
        return paced()

    def handler(e):
        events.append((type(e).__name__, getattr(e, "batch_id", None)))
        if isinstance(e, ev.BeginIteration) and reader_is == "ahead":
            src.wait(e.batch_id + 2)    # so batch_id + 1 is in the queue
        if isinstance(e, ev.EndIteration):
            costs.append(e.cost)
            stepped[e.batch_id].set()

    costs = []
    before = _taken()
    tr = _mse_trainer()
    tr.train(reader, num_passes=passes, event_handler=handler)
    after = _taken()
    n = len(feeds) * passes
    late = passes if reader_is == "ahead" else n
    assert after["late"] - before["late"] == late
    assert after["dispatch"] - before["dispatch"] == n - late
    # EndIteration of a batch still comes before BeginIteration of the next
    per_pass = [("BeginPass", None)] + [
        (kind, i) for i in range(len(feeds))
        for kind in ("BeginIteration", "EndIteration")] + [("EndPass", None)]
    assert events == per_pass * passes

    monkeypatch.setattr(FLAGS, "prefetch_depth", 0)
    nn.reset_naming()
    plain, want = _mse_trainer(), []
    plain.train(lambda: iter(feeds), num_passes=passes,
                event_handler=lambda e: want.append(e.cost)
                if isinstance(e, ev.EndIteration) else None)
    np.testing.assert_array_equal(costs, want)


def test_prefetch_reader_error_attributed_to_data_tier(monkeypatch):
    monkeypatch.setattr(FLAGS, "prefetch_depth", 2)
    feeds = _feeds(4)

    def bad_reader():
        yield feeds[0]
        yield feeds[1]
        raise IOError("socket reset")

    tr = _mse_trainer()
    passes_ended = []
    with pytest.raises(ReaderError, match="socket reset"):
        tr.train(lambda: bad_reader(), num_passes=1,
                 event_handler=lambda e: passes_ended.append(e)
                 if isinstance(e, ev.EndPass) else None)
    assert passes_ended  # pass teardown reached the handlers
    assert tr._prefetcher is None  # producer joined on the error path


def test_prefetch_keeps_feeder_error_identity(monkeypatch):
    """A PREPARE (DataFeeder) failure must keep its own exception type —
    not be misattributed to the reader tier as a ReaderError — exactly as
    it would raise from the prepare phase without prefetch."""
    monkeypatch.setattr(FLAGS, "prefetch_depth", 2)
    feeds = _feeds(4)

    calls = {"n": 0}

    def bad_feeder(batch):
        calls["n"] += 1
        if calls["n"] == 2:
            raise TypeError("slot 'x' has dtype object")
        return batch

    tr = _mse_trainer()
    with pytest.raises(TypeError, match="dtype object"):
        tr.train(lambda: iter(feeds), num_passes=1, feeder=bad_feeder)
    assert tr._prefetcher is None              # producer joined


def test_prefetch_attaches_after_resume_fast_forward(tmp_path, monkeypatch):
    """The prefetcher is built lazily AFTER the skip fast-forward, so a
    resume never pays prepare+h2d for batches the skip discards."""
    from paddle_tpu.data.feeder import BatchPrefetcher

    monkeypatch.setattr(FLAGS, "prefetch_depth", 2)
    monkeypatch.setattr(FLAGS, "save_dir", str(tmp_path))
    feeds = _feeds(6)

    def reader():
        return iter(feeds)

    tr = _mse_trainer()
    h = PreemptionHandler()
    tr.train(reader, num_passes=2, preemption=h,
             event_handler=chaos.preempt_at(h, batch=3, pass_id=0))
    assert tr.preempted

    prepared = []

    def counting_feeder(batch):
        prepared.append(1)
        return batch

    nn.reset_naming()
    tr2 = _mse_trainer()
    tr2.train(reader, num_passes=1, resume="auto", feeder=counting_feeder)
    # 6 batches per pass; the preemption polled at the batch-4 boundary so
    # 4 are skipped on resume: only the 2 STEPPED batches were prepared —
    # a prefetcher built before the fast-forward would have prepared all 6
    assert len(prepared) == 2, prepared


@pytest.mark.parametrize("slot", ["as_it_falls", "filled"])
def test_prefetch_preemption_drains_clean_and_resumes_batch_exact(
        slot, tmp_path, monkeypatch):
    """Acceptance: preemption mid-pass WITH prefetch on — no torn batch
    (the checkpoint's next_batch counts stepped batches, not read-ahead),
    and the resumed run matches the uninterrupted one exactly.  ``filled``:
    the batch after the last stepped one is in the consumer's slot, taken at
    dispatch, when the drain point drops it."""
    from paddle_tpu.resilience.checkpoint_io import pass_dir, read_manifest

    monkeypatch.setattr(FLAGS, "prefetch_depth", 2)
    feeds = _feeds(6)
    src = None

    def reader():
        nonlocal src
        src = _Pulls(feeds)
        return iter(src)

    held = []

    def fill_slot(e):
        # at the end of the last batch stepped before the drain point
        if (slot == "filled" and isinstance(e, ev.EndIteration)
                and (e.pass_id, e.batch_id) == (1, 2)):
            src.wait(4)                  # batch 3 has left the producer
            assert tr_b._prefetcher.take()
            held.append(tr_b._prefetcher._slot)

    monkeypatch.setattr(FLAGS, "save_dir", "")
    tr_a = _mse_trainer()
    tr_a.train(reader, num_passes=3)
    final_a = _host(tr_a.params)

    monkeypatch.setattr(FLAGS, "save_dir", str(tmp_path))
    nn.reset_naming()
    tr_b = _mse_trainer()
    h = PreemptionHandler()
    tr_b.train(reader, num_passes=3, preemption=h,
               event_handler=chaos.preempt_at(h, batch=2, pass_id=1,
                                              inner=fill_slot))
    assert tr_b.preempted
    assert [type(b) for b in held] == (
        [PreparedFeed] if slot == "filled" else [])
    assert tr_b._prefetcher is None            # drained at the boundary
    m = read_manifest(pass_dir(str(tmp_path), 1))
    assert m["meta"]["preempted"] and m["meta"]["next_batch"] == 3

    nn.reset_naming()
    tr_c = _mse_trainer()
    tr_c.train(reader, num_passes=3, resume="auto")
    for k in final_a:
        np.testing.assert_allclose(final_a[k], np.asarray(tr_c.params[k]),
                                   rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# the overlap proof (PR 9 timeline as the instrument)
# ---------------------------------------------------------------------------


def _heavy_trainer():
    """A trainer whose step is reliably >= a few ms of real device compute
    on any CI box (scaled up until it is), so a paced reader slower than
    the floor pacing can always hide behind the step."""
    for hidden in (256, 512, 1024, 2048):
        nn.reset_naming()
        tr = _mse_trainer(hidden=hidden, size=64)
        feeds = _feeds(3, batch=128, size=64)
        tr.train_batch(feeds[0])               # compile
        t0 = time.perf_counter()
        for f in feeds:
            tr.train_batch(f)
        step = (time.perf_counter() - t0) / len(feeds)
        if step >= 0.008:
            return tr, step
    return tr, step  # fastest box ever: use the largest net's numbers


def test_prefetch_collapses_data_wait_share(monkeypatch):
    """What collapses the share, as counts: with --prefetch_depth=2 batch
    k+1 has been asked of the reader before iteration k ends (the handler
    WAITS for it: with no producer beside the loop it would never come),
    without it the reader is asked only after; the timeline records the
    same phases as often either way.  How much wall-clock that hides is the
    ``_timed`` twin's to say."""
    monkeypatch.setattr(FLAGS, "obs_timeline", True)
    feeds = _feeds(6)
    tr = _mse_trainer()
    src, ahead = None, []

    def reader():
        nonlocal src
        src = _Pulls(feeds)
        return iter(src)

    def handler(e):
        if isinstance(e, ev.EndIteration):
            if FLAGS.prefetch_depth:
                src.wait(e.batch_id + 1)
            ahead.append(len(src.pulled) - (e.batch_id + 1))

    read_ahead, phases = {}, {}
    for depth in (0, 2):
        monkeypatch.setattr(FLAGS, "prefetch_depth", depth)
        ahead.clear()
        tr.train(reader, num_passes=1, event_handler=handler)
        read_ahead[depth] = list(ahead)
        phases[depth] = {k: v["count"] for k, v in
                         tr.timeline.last_pass_summary["phases"].items()}
    assert read_ahead[0] == [0] * len(feeds)
    assert all(a >= 1 for a in read_ahead[2][:-1]), read_ahead
    assert phases[0] == phases[2]
    assert phases[2]["step"] == len(feeds)


@pytest.mark.slow
def test_prefetch_collapses_data_wait_share_timed(monkeypatch):
    """Acceptance: (data_wait + h2d) share of the pass drops >=3x on a
    paced reader with --prefetch_depth=2 — the pacing hides behind the
    step instead of serializing with it."""
    monkeypatch.setattr(FLAGS, "obs_timeline", True)
    tr, step = _heavy_trainer()
    delay = min(max(0.5 * step, 0.004), 0.05)  # pacing strictly < step
    n = 8
    feeds = _feeds(n, batch=128, size=64)

    def reader():
        return chaos.slow_client(list(feeds), delay_s=delay)

    def measure():
        shares, waits = {}, {}
        for depth in (0, 2):
            monkeypatch.setattr(FLAGS, "prefetch_depth", depth)
            tr.train(reader, num_passes=1)
            s = tr.timeline.last_pass_summary
            ph = s["phases"]
            wait = (ph.get("data_wait", {"total": 0})["total"]
                    + ph.get("h2d", {"total": 0})["total"])
            waits[depth] = wait
            shares[depth] = wait / max(s["wall_s"], 1e-9)
        return shares, waits

    # wall-clock shares on a ~30ms pass are load-marginal under the full
    # suite (a single descheduled prefetch thread inflates the depth-2
    # share) — re-measure up to twice and judge the cleanest run
    for attempt in range(3):
        shares, waits = measure()
        if shares[0] >= 3 * shares[2] and waits[2] <= waits[0] / 3:
            break
    # unprefetched: the pacing is visible (most of it lands in data_wait)
    assert waits[0] >= (n - 1) * delay * 0.5
    # prefetched: the share collapses >=3x (typically >>10x)
    assert shares[0] >= 3 * shares[2], (shares, waits, delay, step)
    assert waits[2] <= waits[0] / 3, (shares, waits, delay, step)
