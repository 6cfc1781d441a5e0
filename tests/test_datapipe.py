"""Deterministic sharded data pipeline (paddle_tpu/datapipe; docs/data.md).

Covers the four tentpole layers and their acceptance criteria:

- indexed record shards: roundtrip, O(1) random access, CRC detection
  naming the exact shard file + record index, atomic publish, verify;
- deterministic shuffle: (seed, pass) permutations, disjoint-and-complete
  host splits, elastic re-split of the SAME permutation with no
  duplicated/dropped sample ids (pinned);
- checkpointable cursor: preempt mid-pass -> resume restores the cursor
  with ZERO replayed batches and losses/params bit-matching the
  uninterrupted run; a 2-process gang SIGKILL acceptance rides the
  test_gang harness;
- sequence packing: packed loss matches the unpacked oracle on the same
  samples (f32-ulp pinned), RNN carry resets (fwd + reverse), fenced
  context windows, and a >=2x pad-waste drop on the pad-heavy trace.
"""

import json
import os
import signal
import textwrap
import time
from collections import Counter

import numpy as np
import pytest

import paddle_tpu.nn as nn
from paddle_tpu.data.feeder import DataFeeder
from paddle_tpu.datapipe import (PackedDataFeeder, ShardDataset, ShardSource,
                                 is_checkpointable_source, pack_reader,
                                 pack_samples, pass_permutation,
                                 split_positions, write_shard_set)
from paddle_tpu.datapipe.shards import ShardCorruptError, ShardError
from paddle_tpu.param.optimizers import Adam
from paddle_tpu.resilience import PreemptionHandler, chaos
from paddle_tpu.trainer import SGDTrainer, events as ev
from paddle_tpu.utils.flags import FLAGS

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HARD_TIMEOUT_S = 240


@pytest.fixture(autouse=True)
def hard_timeout():
    def _abort(signum, frame):
        raise RuntimeError(f"datapipe test exceeded {HARD_TIMEOUT_S}s")

    prev = signal.signal(signal.SIGALRM, _abort)
    signal.alarm(HARD_TIMEOUT_S)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, prev)


@pytest.fixture(autouse=True)
def fresh_names():
    nn.reset_naming()
    yield


def _sample(i):
    return ([i, i + 1, i + 2], i % 3)


def _make_set(tmp_path, n=37, shards=3, name="set"):
    out = os.path.join(str(tmp_path), name)
    write_shard_set(out, lambda: iter(_sample(i) for i in range(n)),
                    num_shards=shards)
    return out


# ---------------------------------------------------------------------------
# shard format
# ---------------------------------------------------------------------------


def test_shard_roundtrip_preserves_stream_order(tmp_path):
    out = _make_set(tmp_path, n=37, shards=3)
    ds = ShardDataset(out)
    assert len(ds) == 37
    # global index == original stream position (round-robin layout)
    assert [ds.read(g) for g in range(37)] == [_sample(i) for i in range(37)]
    # O(1) random access: any record without touching the others
    assert ds.read(29) == _sample(29)
    summary = ds.validate()
    assert summary["records"] == 37 and summary["shards"] == 3


def test_shard_pack_is_atomic_and_refuses_overwrite(tmp_path):
    out = _make_set(tmp_path, name="s1")
    with pytest.raises(ShardError, match="already exists"):
        write_shard_set(out, lambda: iter([_sample(0)]))
    # a reader that raises mid-pack leaves NO half-published set
    def bad_reader():
        yield _sample(0)
        raise IOError("disk on fire")

    out2 = os.path.join(str(tmp_path), "s2")
    with pytest.raises(IOError):
        write_shard_set(out2, bad_reader)
    assert not os.path.exists(out2)
    assert not [d for d in os.listdir(str(tmp_path)) if d.startswith(".tmp-")]


def test_corrupt_record_raises_typed_error_naming_shard_and_record(tmp_path):
    out = _make_set(tmp_path, n=20, shards=2)
    path = chaos.corrupt_shard(out, shard=1, record=3)
    ds = ShardDataset(out)
    # shard 1, local record 3 is global stream position 3*2+1 = 7
    with pytest.raises(ShardCorruptError) as ei:
        ds.read(7)
    assert ei.value.path == path and ei.value.record == 3
    assert "record 3" in str(ei.value) and path in str(ei.value)
    # verify catches it too (whole-file CRC fails first, naming the file)
    with pytest.raises(ShardCorruptError) as ei:
        ShardDataset(out).validate()
    assert ei.value.path == path


def test_truncated_shard_fails_on_open(tmp_path):
    out = _make_set(tmp_path, n=20, shards=2)
    path = chaos.truncate_shard(out, shard=0)
    with pytest.raises(ShardCorruptError) as ei:
        ShardDataset(out).read(0)
    assert ei.value.path == path


def test_skip_corrupt_counts_dropped_records(tmp_path):
    out = _make_set(tmp_path, n=24, shards=2)
    chaos.corrupt_shard(out, shard=0, record=2)  # stream position 4
    src = ShardSource(out, batch_size=4, seed=0, shuffle=False,
                      skip_corrupt=True)
    got = [x for b in src() for x in b]
    assert src.dropped_records == 1
    assert len(got) == 23  # dropped, not silently replaced
    assert _sample(4) not in got


def test_fully_corrupt_batch_window_fails_loudly_not_silently(tmp_path):
    """Review fix: a window whose EVERY record is corrupt must raise (a
    suppressed empty batch would desync the stepped-batch count from the
    cursor arithmetic — a later resume would re-train consumed samples)."""
    out = _make_set(tmp_path, n=24, shards=2)
    # batch 1 (B=4, shuffle off) covers stream samples 4..7 =
    # shard0 locals 2,3 + shard1 locals 2,3 — corrupt all four
    for shard, rec in [(0, 2), (0, 3), (1, 2), (1, 3)]:
        chaos.corrupt_shard(out, shard=shard, record=rec)
    src = ShardSource(out, batch_size=4, seed=0, shuffle=False,
                      skip_corrupt=True)
    it = iter(src())
    assert len(next(it)) == 4  # batch 0 intact
    with pytest.raises(ShardCorruptError, match="every record"):
        next(it)
    assert src.dropped_records == 4


def test_slow_shard_paces_reads(tmp_path):
    out = _make_set(tmp_path, n=8, shards=1)
    src = ShardSource(out, batch_size=4, seed=0, shuffle=False)
    chaos.slow_shard(src, delay_s=0.02)
    t0 = time.monotonic()
    list(src())
    assert time.monotonic() - t0 >= 8 * 0.02


def test_shard_read_counters_land_in_registry(tmp_path):
    from paddle_tpu.obs import get_registry

    out = _make_set(tmp_path, n=10, shards=2)
    reg = get_registry()
    c = reg.counter("data_shard_records_total",
                    "records decoded from shard files")
    before = c.value
    ShardDataset(out).read(0)
    assert c.value == before + 1
    assert reg.counter("data_shard_read_bytes_total",
                       "payload bytes read from shard files").value > 0


# ---------------------------------------------------------------------------
# deterministic shuffle + host split
# ---------------------------------------------------------------------------


def test_pass_permutation_deterministic_per_seed_and_pass():
    p0 = pass_permutation(100, seed=5, pass_id=0)
    assert np.array_equal(p0, pass_permutation(100, seed=5, pass_id=0))
    assert not np.array_equal(p0, pass_permutation(100, seed=5, pass_id=1))
    assert not np.array_equal(p0, pass_permutation(100, seed=6, pass_id=0))
    assert np.array_equal(np.sort(p0), np.arange(100))
    assert np.array_equal(pass_permutation(10, 0, 0, shuffle=False),
                          np.arange(10))


def test_split_positions_disjoint_and_complete():
    W = 4
    seen = Counter()
    for r in range(W):
        seen.update(split_positions(103, 7, W, r))
    assert all(v == 1 for v in seen.values())
    assert set(seen) == set(range(7, 103))


def test_source_batches_deterministic_and_world_split_disjoint(tmp_path):
    out = _make_set(tmp_path, n=48, shards=3)
    a = [x for b in ShardSource(out, batch_size=4, seed=9)() for x in b]
    b = [x for b_ in ShardSource(out, batch_size=4, seed=9)() for x in b_]
    assert a == b
    ids = Counter()
    for r in range(4):
        s = ShardSource(out, batch_size=3, seed=9, world=4, index=r)
        for batch in s():
            ids.update(x[0][0] for x in batch)
    assert all(v == 1 for v in ids.values())
    assert len(ids) == 48


def test_elastic_reshard_resplits_same_permutation_no_dup_no_drop(tmp_path):
    """THE elastic acceptance invariant (pinned): shrink 2->1 mid-pass,
    then grow 1->2 later in the SAME pass via cursor restore — every
    consumed sample id appears exactly once across all phases, and the
    union is exactly the permutation prefix windows cover."""
    N = 64
    out = _make_set(tmp_path, n=N, shards=4)
    consumed = Counter()

    # phase 1: world=2, two ranks step 3 batches each (B=2)
    pair = [ShardSource(out, batch_size=2, seed=11, world=2, index=r)
            for r in range(2)]
    its = [iter(s()) for s in pair]
    for _ in range(3):
        for it in its:
            consumed.update(x[0][0] for x in next(it))
    # shrink: survivor rank 0 re-splits from the committed boundary
    survivor = pair[0]
    survivor.reshard(1, 0, pass_id=0, next_batch=3)
    it = iter(survivor())
    for _ in range(4):
        consumed.update(x[0][0] for x in next(it))
    # grow: both ranks restore the survivor's cursor and re-bind
    cur = survivor.cursor_for(0, 7)
    grown = []
    for r in range(2):
        s = ShardSource(out, batch_size=2, seed=11)
        s.restore(cur)
        s.bind_world(2, r)
        grown.append(s)
    for s in grown:
        for batch in s():
            consumed.update(x[0][0] for x in batch)
    assert all(v == 1 for v in consumed.values()), \
        {k: v for k, v in consumed.items() if v > 1}
    # coverage: 3*2*2 + 4*2*1 = 20 consumed before the grow, then the
    # remaining (64-20)//4 * 4 = 44 — the whole permutation, exactly once
    assert len(consumed) == N
    perm = pass_permutation(N, 11, 0)
    assert set(consumed) == {_sample(int(i))[0][0] for i in perm}


def test_cursor_for_is_read_ahead_proof(tmp_path):
    """cursor_for derives from the STEPPED count: pulling 3 extra batches
    of read-ahead must not move the cursor a checkpoint would record."""
    out = _make_set(tmp_path, n=40, shards=2)
    src = ShardSource(out, batch_size=4, seed=1)
    it = iter(src())
    for _ in range(5):   # 2 stepped + 3 read ahead
        next(it)
    cur = src.cursor_for(0, 2)
    assert cur["offset"] == 8 and cur["next_batch"] == 2
    # and restore from it replays nothing, continues at batch 2
    s2 = ShardSource(out, batch_size=4, seed=1)
    s2.restore(cur)
    ref = [x for b in ShardSource(out, batch_size=4, seed=1)() for x in b]
    got = [x for b in s2() for x in b]
    assert got == ref[8:]


def test_cursor_survives_read_ahead_pass_rollover(tmp_path):
    """Review fix: a prefetcher can exhaust the generator — rolling the
    cursor to pass+1 — while the trainer still STEPS the tail of pass p.
    cursor_for(p, ...) must keep answering from the stashed bases, and a
    reshard for pass p must un-roll instead of recomputing from zeroed
    bases."""
    out = _make_set(tmp_path, n=16, shards=2)  # 4 batches of B=4
    src = ShardSource(out, batch_size=4, seed=2)
    list(src())                      # full read-ahead: rolled to pass 1
    assert src.pass_id == 1
    cur = src.cursor_for(0, 3)       # ...but the trainer stepped only 3
    assert cur["offset"] == 12 and cur["pass"] == 0
    # and the end-of-pass save still works
    assert src.cursor_for(1, 0)["offset"] == 0
    # reshard for the rolled-from pass un-rolls and re-splits correctly
    src.reshard(2, 0, pass_id=0, next_batch=3)
    assert src.pass_id == 0
    assert src.cursor_for(0, 3)["offset"] == 12


def test_source_pass_rollover_and_seek(tmp_path):
    out = _make_set(tmp_path, n=16, shards=2)
    src = ShardSource(out, batch_size=4, seed=2)
    p0 = list(src())
    assert src.pass_id == 1
    p1 = list(src())
    assert p0 != p1  # reshuffled per pass
    src.seek(0)
    assert list(src()) == p0
    assert is_checkpointable_source(src)
    assert not is_checkpointable_source(lambda: iter([]))


# ---------------------------------------------------------------------------
# trainer integration: cursor resume (satellite 1)
# ---------------------------------------------------------------------------


def _xy_set(tmp_path, n=48):
    rs = np.random.RandomState(0)
    samples = [(rs.randn(4).astype(np.float32).tolist(),
                rs.randn(2).astype(np.float32).tolist()) for _ in range(n)]
    out = os.path.join(str(tmp_path), "xy")
    write_shard_set(out, lambda: iter(samples), num_shards=2)
    return out


def _xy_feeder(batch):
    return {"x": np.asarray([b[0] for b in batch], np.float32),
            "y": np.asarray([b[1] for b in batch], np.float32)}


def _xy_trainer():
    nn.reset_naming()
    x = nn.data("x", size=4)
    y = nn.data("y", size=2)
    cost = nn.mse_cost(input=nn.fc(x, 2, act="relu", name="h"), label=y)
    return SGDTrainer(cost, Adam(learning_rate=0.05), seed=0)


def _record_losses(losses):
    def rec(e):
        if isinstance(e, ev.EndIteration):
            losses[f"{e.pass_id}:{e.batch_id}"] = float(e.cost)

    return rec


def test_cursor_resume_zero_replay_bitwise_losses(tmp_path, monkeypatch):
    """Satellite 1 acceptance: preempt mid-pass with a datapipe source,
    resume=auto restores the CURSOR — zero fast-forwarded batches (the
    counter is pinned), zero re-read samples (the shard read counter is
    pinned), and the completed run's losses and params match the
    uninterrupted run bitwise."""
    from paddle_tpu.obs import get_registry

    out = _xy_set(tmp_path)
    monkeypatch.setattr(FLAGS, "log_period", 0)

    # oracle: uninterrupted
    monkeypatch.setattr(FLAGS, "save_dir", "")
    ref_losses = {}
    tr = _xy_trainer()
    tr.train(ShardSource(out, batch_size=4, seed=3), num_passes=2,
             event_handler=_record_losses(ref_losses), feeder=_xy_feeder)
    ref_params = {k: np.asarray(v) for k, v in tr.params.items()}

    # interrupted: preemption at pass 1 batch 2 -> checkpoint + exit
    monkeypatch.setattr(FLAGS, "save_dir", str(tmp_path / "ck"))
    got = {}
    tr1 = _xy_trainer()
    h = PreemptionHandler()
    tr1.train(ShardSource(out, batch_size=4, seed=3), num_passes=2,
              event_handler=chaos.preempt_at(h, batch=2, pass_id=1,
                                             inner=_record_losses(got)),
              feeder=_xy_feeder, preemption=h, resume="auto")
    assert tr1.preempted

    # resume: fresh trainer + fresh source; cursor restored, no replay
    reads = get_registry().counter("data_shard_records_total",
                                   "records decoded from shard files")
    reads_before = reads.value
    tr2 = _xy_trainer()
    tr2.train(ShardSource(out, batch_size=4, seed=3), num_passes=2,
              event_handler=_record_losses(got), feeder=_xy_feeder,
              resume="auto")
    assert tr2.resume_replayed_batches == 0
    # ZERO re-read samples: exactly the remaining batches of pass 1
    # (batches 3..11) are read, none of the already-trained 0..2
    remaining = len([k for k in ref_losses if k.startswith("1:")]) - 3
    assert reads.value - reads_before == remaining * 4

    assert set(got) == set(ref_losses)
    for k, v in ref_losses.items():
        assert got[k] == v, (k, got[k], v)  # bitwise: same feeds, same step
    for k, v in ref_params.items():
        np.testing.assert_array_equal(np.asarray(tr2.params[k]), v)


def test_plain_reader_keeps_fast_forward_fallback(tmp_path, monkeypatch):
    """The O(pass) fast-forward survives for plain readers — and the
    replay counter proves it ran (the datapipe path pins it to zero)."""
    monkeypatch.setattr(FLAGS, "save_dir", str(tmp_path / "ck"))
    monkeypatch.setattr(FLAGS, "log_period", 0)
    rs = np.random.RandomState(0)
    feeds = [{"x": rs.randn(4, 4).astype(np.float32),
              "y": rs.randn(4, 2).astype(np.float32)} for _ in range(6)]
    tr = _xy_trainer()
    h = PreemptionHandler()
    tr.train(lambda: iter(feeds), num_passes=2,
             event_handler=chaos.preempt_at(h, batch=3, pass_id=1),
             preemption=h, resume="auto")
    assert tr.preempted
    tr2 = _xy_trainer()
    tr2.train(lambda: iter(feeds), num_passes=2, resume="auto")
    assert tr2.resume_replayed_batches > 0


def test_dropped_records_surfaced_in_last_extras(tmp_path, monkeypatch):
    out = _xy_set(tmp_path, n=24)
    chaos.corrupt_shard(out, shard=0, record=1)
    monkeypatch.setattr(FLAGS, "save_dir", "")
    monkeypatch.setattr(FLAGS, "log_period", 0)
    tr = _xy_trainer()
    src = ShardSource(out, batch_size=4, seed=0, shuffle=False,
                      skip_corrupt=True)
    tr.train(src, num_passes=1, feeder=_xy_feeder)
    assert src.dropped_records == 1
    assert tr._last_extras["dropped_records"] == 1


def test_corrupt_record_without_skip_attributed_as_reader_error(
        tmp_path, monkeypatch):
    from paddle_tpu.resilience import ReaderError

    out = _xy_set(tmp_path, n=24)
    chaos.corrupt_shard(out, shard=0, record=1)
    monkeypatch.setattr(FLAGS, "save_dir", "")
    monkeypatch.setattr(FLAGS, "log_period", 0)
    tr = _xy_trainer()
    src = ShardSource(out, batch_size=4, seed=0, shuffle=False)
    with pytest.raises(ReaderError):
        tr.train(src, num_passes=1, feeder=_xy_feeder)


# ---------------------------------------------------------------------------
# sequence packing (tentpole part 4)
# ---------------------------------------------------------------------------


def _textclf_samples(n=10, vocab=50, seed=0, lo=2, hi=9):
    rs = np.random.RandomState(seed)
    return [(rs.randint(1, vocab, rs.randint(lo, hi)).tolist(),
             int(rs.randint(0, 2))) for _ in range(n)]


def test_pack_samples_respects_budgets_and_order():
    samples = _textclf_samples(20)
    rows = pack_samples(samples, max_len=16, max_segments=3)
    flat = [seq for seqs, _ in rows for seq in seqs]
    assert flat == [list(s[0])[:16] for s in samples]  # order preserved
    for seqs, rest in rows:
        assert len(seqs) <= 3 and sum(len(s) for s in seqs) <= 16
        assert len(rest) == len(seqs)
    # streaming packer agrees with the list packer
    assert list(pack_reader(lambda: iter(samples), max_len=16,
                            max_segments=3)()) == rows


def test_packed_feeder_shapes_and_segment_layout():
    samples = [([1, 2, 3], 0), ([4, 5], 1), ([6], 0)]
    rows = pack_samples(samples, max_len=8, max_segments=4)
    assert len(rows) == 1
    pf = PackedDataFeeder({"words": "ids_seq", "label": "int"},
                          max_segments=4)
    feed = pf(rows)
    ids, lengths, seg_ids, positions, seg_lengths = feed["words"]
    assert ids.shape == (1, 8) and seg_lengths.shape == (1, 4)
    assert list(ids[0]) == [1, 2, 3, 4, 5, 6, 0, 0]
    assert list(seg_ids[0]) == [0, 0, 0, 1, 1, 2, -1, -1]
    assert list(positions[0]) == [0, 1, 2, 0, 1, 0, 0, 0]
    assert list(seg_lengths[0]) == [3, 2, 1, 0]
    assert lengths[0] == 6
    assert feed["label"].shape == (1, 4)
    assert list(feed["label"][0]) == [0, 1, 0, 0]


@pytest.mark.parametrize("model", ["lstm", "stacked_reverse", "conv"])
def test_packed_loss_matches_unpacked_oracle(model):
    """THE packing acceptance: the packed batch computes the same
    per-sample math as one-row-per-sample — loss AND gradients match the
    unpacked oracle at f32 ulp (the conv path is exactly bitwise; the
    LSTM paths differ only by fused-vs-scan reduction order)."""
    import jax

    from paddle_tpu.models import (convolution_net, lstm_benchmark_net,
                                   stacked_lstm_net)

    VOCAB = 40
    samples = _textclf_samples(8, vocab=VOCAB, seed=1)
    nn.reset_naming()
    if model == "lstm":
        cost, _ = lstm_benchmark_net(VOCAB, emb_dim=8, hid_dim=16,
                                     num_layers=2)
    elif model == "stacked_reverse":
        # stacked_num=3 alternates a REVERSE lstm layer: packing must
        # reset the reversed carry at segment tails
        cost, _ = stacked_lstm_net(VOCAB, emb_dim=8, hid_dim=8,
                                   stacked_num=3)
    else:
        cost, _ = convolution_net(VOCAB, emb_dim=8, hid_dim=8)
    topo = nn.Topology([cost])
    params, state = topo.init(jax.random.PRNGKey(0))

    feed_u = DataFeeder({"words": "ids_seq", "label": "int"})(samples)
    rows = pack_samples(samples, max_len=16, max_segments=4)
    assert len(rows) < len(samples)  # it really packed
    feed_p = PackedDataFeeder({"words": "ids_seq", "label": "int"},
                              max_segments=4)(rows)

    def loss_fn(p, feed):
        outs, _ = topo.apply(p, state, feed, train=False)
        return outs[cost.name].value

    lu, gu = jax.value_and_grad(loss_fn)(params, feed_u)
    lp, gp = jax.value_and_grad(loss_fn)(params, feed_p)
    np.testing.assert_allclose(float(lp), float(lu), rtol=0, atol=2e-7)
    for k in gu:
        np.testing.assert_allclose(np.asarray(gp[k]), np.asarray(gu[k]),
                                   rtol=0, atol=5e-7, err_msg=k)


def test_packed_train_step_runs_and_converges():
    """End-to-end: SGDTrainer trains a packed pipeline (grad through
    segment pooling + carry resets) and the loss goes down."""
    from paddle_tpu.models import lstm_benchmark_net

    VOCAB = 30
    rs = np.random.RandomState(0)
    # learnable signal: label == first token parity
    samples = []
    for _ in range(64):
        L = rs.randint(2, 8)
        seq = rs.randint(1, VOCAB, L).tolist()
        samples.append((seq, seq[0] % 2))
    nn.reset_naming()
    cost, _ = lstm_benchmark_net(VOCAB, emb_dim=8, hid_dim=16, num_layers=1)
    tr = SGDTrainer(cost, Adam(learning_rate=0.05), seed=0)
    rows = pack_samples(samples, max_len=32, max_segments=8)
    pf = PackedDataFeeder({"words": "ids_seq", "label": "int"},
                          max_segments=8)
    feed = pf(rows)
    first = float(tr.train_batch(feed))
    for _ in range(30):
        last = float(tr.train_batch(feed))
    assert last < first


def test_pad_waste_drops_at_least_2x_and_gauge_updates():
    """Packing acceptance: on the pad-heavy trace the padded-but-dead
    token fraction drops >= 2x, and the data_pad_waste gauge reports it."""
    from paddle_tpu.obs import get_registry

    rs = np.random.RandomState(0)
    samples = [(rs.randint(1, 100, int(np.clip(rs.exponential(12) + 2, 2,
                                               96))).tolist(), 0)
               for _ in range(256)]
    feeder = DataFeeder({"words": "ids_seq", "label": "int"}, max_len=128)
    for i in range(0, 256, 64):
        feeder(samples[i:i + 64])
    pf = PackedDataFeeder({"words": "ids_seq", "label": "int"},
                          max_segments=16)
    rows = pack_samples(samples, max_len=128, max_segments=16)
    for i in range(0, len(rows), 64):
        pf(rows[i:i + 64])
    assert feeder.pad_waste >= 2 * pf.pad_waste, \
        (feeder.pad_waste, pf.pad_waste)
    g = get_registry().gauge("data_pad_waste",
                             "cumulative padded-but-dead token fraction")
    assert g.value == pytest.approx(pf.pad_waste)
    occ = get_registry().gauge(
        "data_bucket_occupancy",
        "real-token fraction of batches padded to this T bucket",
        labels=("bucket",), bucket=128)
    assert occ.value is not None and 0.0 < occ.value <= 1.0


def test_auto_pack_honors_feeder_max_len_and_source_batch_size(tmp_path):
    """Review fixes: auto_pack truncates where the FEEDER would (packed
    and bucketed training must clip identically), reads a cursor
    source's declared batch_size instead of consuming a batch, and
    defaults the packed row count to the source batch size."""
    from paddle_tpu.datapipe import auto_pack

    samples = _textclf_samples(24, lo=2, hi=12)
    feeder = DataFeeder({"words": "ids_seq", "label": "int"}, max_len=4)

    def reader():
        return iter([samples[i:i + 6] for i in range(0, 24, 6)])

    packed_reader, pf = auto_pack(reader, feeder)
    rows = [r for batch in packed_reader() for r in batch]
    assert all(len(seq) <= 4 for seqs, _ in rows for seq in seqs)
    batches = list(packed_reader())
    assert all(len(b) <= 6 for b in batches)  # source batch size kept

    # a ShardSource's cursor must NOT move: batch_size comes from the
    # attribute, not from iterating a batch
    out = os.path.join(str(tmp_path), "bs")
    write_shard_set(out, lambda: iter(samples), num_shards=2)
    src = ShardSource(out, batch_size=6, seed=0)
    auto_pack(src, feeder)
    assert src.cursor_for(0, 0)["offset"] == 0
    assert src.state()["next_batch"] == 0


def test_packed_input_rejected_by_unpackable_seq_layers():
    """Review fix: layers with no per-segment semantics (seq_reverse,
    seq_concat) refuse packed input with a typed ConfigError instead of
    silently crossing segment boundaries."""
    import jax

    from paddle_tpu.utils.error import ConfigError

    nn.reset_naming()
    words = nn.data("words", size=20, is_seq=True, dtype="int32")
    emb = nn.embedding(words, 4, name="emb")
    rev = nn.seq_reverse(emb, name="rev")
    pool = nn.pooling(rev, pooling_type="max", name="pool")
    label = nn.data("label", size=1, dtype="int32")
    logits = nn.fc(pool, 2, act="linear", name="logits")
    cost = nn.classification_cost(logits, label, name="cost")
    topo = nn.Topology([cost])
    params, state = topo.init(jax.random.PRNGKey(0))
    rows = pack_samples(_textclf_samples(4, vocab=20), max_len=16,
                        max_segments=4)
    feed = PackedDataFeeder({"words": "ids_seq", "label": "int"},
                            max_segments=4)(rows)
    with pytest.raises(ConfigError, match="seq_reverse.*packed"):
        topo.apply(params, state, feed, train=False)


def test_packed_feeder_rejects_unpackable_slots():
    from paddle_tpu.utils.error import ConfigError

    with pytest.raises(ConfigError, match="exactly one 'ids_seq'"):
        PackedDataFeeder({"a": "dense", "b": "int"})
    with pytest.raises(ConfigError, match="not packable"):
        PackedDataFeeder({"w": "ids_seq", "x": "sparse_ids"})


def test_trainer_gang_resize_reshards_bound_source(tmp_path, monkeypatch):
    """The trainer half of the elastic contract: a shard_by_gang source
    is re-split by ``_gang_resize`` at the drain boundary — new world,
    this rank's new index, the SAME (pass, stepped-batch) cursor — and
    the loop is told to rebuild its iterator (``_source_resharded``)."""
    from contextlib import contextmanager

    out = _xy_set(tmp_path, n=48)
    monkeypatch.setattr(FLAGS, "save_dir", "")

    class FakeGang:
        ranks, rank, epoch, world_size = [0, 1], 0, 0, 2
        is_coordinator = True

        @contextmanager
        def resizing(self):
            yield

        def adopt_world(self, world):
            self.ranks = sorted(world["ranks"])
            self.world_size = len(self.ranks)
            self.epoch = world["epoch"]

        def ack_resize(self):
            pass

        def barrier(self):
            pass

        def broadcast_json(self, payload, name):
            return payload

    tr = _xy_trainer()
    src = ShardSource(out, batch_size=4, seed=3, world=2, index=0,
                      shard_by_gang=True)
    tr._data_source = src
    gang = FakeGang()
    tr._gang = gang
    it = iter(src())
    next(it), next(it)  # 2 stepped batches under world=2
    tr._gang_resize(gang, {"ranks": [0], "epoch": 1, "reason": "test"},
                    0, 2, handler=None)
    assert tr._source_resharded
    assert src.world == 1 and src.index == 0
    cur = src.cursor_for(0, 2)
    assert cur["offset"] == 2 * 4 * 2  # committed under the OLD world


# ---------------------------------------------------------------------------
# gang acceptance: kill a 2-process gang mid-pass with a datapipe source
# ---------------------------------------------------------------------------

DATAPIPE_WORKER = textwrap.dedent("""\
    import json, os, sys

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("PADDLE_TPU_COMPUTE_DTYPE", "float32")

    import numpy as np

    import paddle_tpu.nn as nn
    from paddle_tpu.datapipe import ShardSource
    from paddle_tpu.param.optimizers import Adam
    from paddle_tpu.resilience import chaos
    from paddle_tpu.trainer import SGDTrainer, events as ev
    from paddle_tpu.utils import FLAGS

    shard_dir, save_dir, out_dir, chaos_rank = sys.argv[1:5]
    rank = int(os.environ["PADDLE_TPU_PROCESS_ID"])
    FLAGS.save_dir = save_dir
    FLAGS.log_period = 0

    x = nn.data("x", size=4)
    y = nn.data("y", size=2)
    cost = nn.mse_cost(input=nn.fc(x, 2, act="relu", name="h"), label=y)
    tr = SGDTrainer(cost, Adam(learning_rate=0.05), seed=0)

    src = ShardSource(shard_dir, batch_size=4, seed=3)

    def feeder(batch):
        return {"x": np.asarray([b[0] for b in batch], np.float32),
                "y": np.asarray([b[1] for b in batch], np.float32)}

    losses = {}
    def record(e):
        if isinstance(e, ev.EndIteration):
            losses[f"{e.pass_id}:{e.batch_id}"] = float(e.cost)

    handler = record
    marker = os.path.join(out_dir, "fault-fired")
    if rank == int(chaos_rank):
        handler = chaos.die_at(pass_id=1, batch=2, marker=marker,
                               inner=record)

    tr.train(src, num_passes=3, event_handler=handler, feeder=feeder,
             resume="auto")

    with open(os.path.join(out_dir, f"losses-rank{rank}.json"), "w") as f:
        json.dump({"losses": losses,
                   "replayed": tr.resume_replayed_batches}, f)
    if rank == 0:
        np.savez(os.path.join(out_dir, "final-rank0.npz"),
                 **{k: np.asarray(v) for k, v in tr.params.items()})
""")


def test_gang_sigkill_midpass_cursor_resume_matches_oracle(
        tmp_path, monkeypatch):
    """THE determinism acceptance (ISSUE criteria): SIGKILL a random rank
    of a REAL 2-process gang mid-pass with a datapipe source.  The
    supervisor relaunches, --resume=auto restores the CURSOR (the replay
    counter is pinned zero on every rank), and the completed run's
    losses and final params match the uninterrupted run @1e-6."""
    from paddle_tpu.resilience import GangSupervisor

    shard_dir = _xy_set(tmp_path)

    # oracle: uninterrupted single process, same source config
    monkeypatch.setattr(FLAGS, "save_dir", "")
    monkeypatch.setattr(FLAGS, "log_period", 0)
    ref_losses = {}
    tr = _xy_trainer()
    tr.train(ShardSource(shard_dir, batch_size=4, seed=3), num_passes=3,
             event_handler=_record_losses(ref_losses), feeder=_xy_feeder)
    ref_params = {k: np.asarray(v) for k, v in tr.params.items()}

    script = tmp_path / "worker.py"
    script.write_text(DATAPIPE_WORKER)
    out_dir = tmp_path / "out"
    out_dir.mkdir()
    sup = GangSupervisor(
        ["localhost"] * 2, str(script),
        [shard_dir, str(tmp_path / "ck"), str(out_dir), "1"],
        gang_dir=str(tmp_path / "gang"), max_restarts=2,
        heartbeat_s=0.2, watchdog_s=10.0, startup_grace_s=180.0,
        backoff_s=0.05, poll_s=0.05,
        env={"PYTHONPATH": REPO_ROOT + os.pathsep
             + os.environ.get("PYTHONPATH", "")})
    result = sup.run()
    assert result.attempts == 2
    assert (out_dir / "fault-fired").exists()

    for rank in (0, 1):
        with open(out_dir / f"losses-rank{rank}.json") as f:
            dump = json.load(f)
        # cursor restore, not fast-forward: ZERO replayed batches
        assert dump["replayed"] == 0
        got = dump["losses"]
        assert "2:11" in got  # 48 samples / B4 = 12 batches, 3 passes
        for key, v in got.items():
            np.testing.assert_allclose(v, ref_losses[key], rtol=1e-6,
                                       err_msg=key)
    final = np.load(out_dir / "final-rank0.npz")
    for k, v in ref_params.items():
        np.testing.assert_allclose(final[k], v, rtol=1e-6, atol=1e-7)
