"""Trainer integration tests — analog of test_Trainer / test_TrainerOnePass
(SURVEY.md §4): full train passes end-to-end, checkpoint round-trip, checkgrad."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.data as data
import paddle_tpu.nn as nn
from paddle_tpu.param.optimizers import Adam, Momentum, SGD
from paddle_tpu.trainer import SGDTrainer, check_gradients, events as ev
from paddle_tpu.trainer.checkpoint import latest_pass


@pytest.fixture(autouse=True)
def fresh_names():
    nn.reset_naming()
    yield


def _xor_reader():
    rng = np.random.RandomState(0)

    def reader():
        for _ in range(200):
            x = rng.randint(0, 2, 2).astype(np.float32)
            y = int(x[0]) ^ int(x[1])
            yield x + rng.randn(2).astype(np.float32) * 0.05, y

    return reader


def test_trainer_learns_xor():
    x = nn.data("x", size=2)
    lab = nn.data("label", size=1, dtype="int32")
    h = nn.fc(x, 16, act="relu")
    logits = nn.fc(h, 2, act="linear", name="logits")
    cost = nn.classification_cost(logits, lab, name="cost")
    trainer = SGDTrainer(cost, Adam(learning_rate=0.01), seed=0)
    feeder = data.DataFeeder({"x": "dense", "label": "int"})
    reader = data.batch(_xor_reader(), 32)
    seen = {"end_pass": 0, "costs": []}

    def handler(e):
        if isinstance(e, ev.EndIteration):
            seen["costs"].append(e.cost)
        elif isinstance(e, ev.EndPass):
            seen["end_pass"] += 1

    trainer.train(reader, num_passes=30, event_handler=handler, feeder=feeder)
    assert seen["end_pass"] == 30
    assert np.mean(seen["costs"][-5:]) < 0.2
    # inference accuracy
    xs = np.array([[0, 0], [0, 1], [1, 0], [1, 1]], np.float32)
    out = trainer.infer(trainer.topology.outputs[1] if len(trainer.topology.outputs) > 1 else
                        [l for l in trainer.topology.layers if l.name == "logits"][0],
                        {"x": xs})
    pred = out["logits"].argmax(-1)
    np.testing.assert_array_equal(pred, [0, 1, 1, 0])


def test_checkpoint_roundtrip(tmp_path):
    x = nn.data("x", size=4)
    lab = nn.data("label", size=1, dtype="int32")
    logits = nn.fc(x, 3, act="linear", name="logits")
    cost = nn.classification_cost(logits, lab, name="cost")
    t1 = SGDTrainer(cost, Momentum(learning_rate=0.1), seed=1)
    feed = {"x": np.random.RandomState(0).randn(8, 4).astype(np.float32),
            "label": np.random.RandomState(1).randint(0, 3, (8, 1))}
    for _ in range(3):
        t1.train_batch(feed)
    d = t1.save(str(tmp_path), 7)
    assert os.path.exists(os.path.join(d, "params.npz"))
    assert latest_pass(str(tmp_path)) == 7

    nn.reset_naming()
    x2 = nn.data("x", size=4)
    lab2 = nn.data("label", size=1, dtype="int32")
    logits2 = nn.fc(x2, 3, act="linear", name="logits")
    cost2 = nn.classification_cost(logits2, lab2, name="cost")
    t2 = SGDTrainer(cost2, Momentum(learning_rate=0.1), seed=99)
    t2.load(str(tmp_path), 7)
    for k in t1.params:
        np.testing.assert_array_equal(np.asarray(t1.params[k]), np.asarray(t2.params[k]))
    # optimizer slots restored too -> identical next step
    l1 = t1.train_batch(feed)
    l2 = t2.train_batch(feed)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)


def _dropout_trainer(seed, **kw):
    nn.reset_naming()
    x = nn.data("x", size=6)
    h = nn.dropout(nn.fc(x, 16, act="relu", name="h"), 0.5)
    cost = nn.mse_cost(input=nn.fc(h, 2, act="linear", name="o"),
                       label=nn.data("y", size=2))
    return SGDTrainer(cost, Adam(learning_rate=0.05), seed=seed, **kw)


@pytest.mark.parametrize("seed", [0, 11])
@pytest.mark.parametrize("guard", [True, False], ids=["guard", "no_guard"])
def test_key_split_inside_the_step_keeps_the_host_side_chain(
        seed, guard, tmp_path):
    """The compiled step splits the trainer's key itself and returns the
    half it does not use.  Every step sees the key that
    ``self._rng, key = jax.random.split(self._rng)`` on the host gave it,
    bit for bit: the losses of a net with dropout, ``save()``'s ``rng_key``
    after N steps, and the stream a ``load()`` continues."""
    from paddle_tpu.resilience.checkpoint_io import pass_dir, read_manifest

    rs = np.random.RandomState(seed)
    feeds = [{"x": rs.randn(8, 6).astype(np.float32),
              "y": rs.randn(8, 2).astype(np.float32)} for _ in range(6)]
    n = 4
    # the chain as the host made it: split before every step, hand the
    # un-jitted step (the one the auditors trace) its half
    ref = _dropout_trainer(seed, guard_nonfinite=guard)
    step = jax.jit(ref._step_fn)
    rng, params, state, opt = ref._rng, ref.params, ref.state, ref.opt_state
    want, chain = [], []
    for feed in feeds:
        rng, key = jax.random.split(rng)
        loss, params, state, opt = step(params, state, opt, {}, key, feed)[:4]
        want.append(np.asarray(loss))
        chain.append(np.asarray(rng))
    assert len({w.tobytes() for w in want}) == len(want)  # dropout is live

    tr = _dropout_trainer(seed, guard_nonfinite=guard)
    got = [np.asarray(tr.train_batch(f)) for f in feeds[:n]]
    np.testing.assert_array_equal(got, want[:n])
    np.testing.assert_array_equal(np.asarray(tr._rng), chain[n - 1])
    tr.save(str(tmp_path), 0)
    meta = read_manifest(pass_dir(str(tmp_path), 0))["meta"]
    assert meta["rng_key"] == [int(v) for v in chain[n - 1]]

    tr2 = _dropout_trainer(seed + 1, guard_nonfinite=guard)
    tr2.load(str(tmp_path), 0)
    got2 = [np.asarray(tr2.train_batch(f)) for f in feeds[n:]]
    np.testing.assert_array_equal(got2, want[n:])
    np.testing.assert_array_equal(np.asarray(tr2._rng), chain[-1])
    # the trainer that saved goes on along the same stream
    np.testing.assert_array_equal(
        [np.asarray(tr.train_batch(f)) for f in feeds[n:]], want[n:])


def test_checkgrad_mode(rng):
    x_val = jnp.asarray(rng.randn(4, 6).astype(np.float32))
    y_val = jnp.asarray(rng.randint(0, 3, (4, 1)))
    x = nn.data("x", size=6)
    lab = nn.data("label", size=1, dtype="int32")
    logits = nn.fc(x, 3, act="linear", name="logits")
    cost = nn.classification_cost(logits, lab, name="cost")
    topo = nn.Topology(cost)
    params, state = topo.init(jax.random.PRNGKey(0))

    def loss(p):
        outs, _ = topo.apply(p, state, {"x": x_val, "label": y_val})
        return outs["cost"].value

    report = check_gradients(loss, params, eps=1e-3)
    assert set(report) == set(params)


def test_feeder_and_reader_pipeline():
    feeder = data.DataFeeder({"words": "ids_seq", "label": "int"})
    rows = [([1, 2, 3], 0), ([4, 5], 1), ([6], 0)]
    feed = feeder(rows)
    ids, lengths = feed["words"]
    assert ids.shape == (3, 8)  # bucketed to 8
    np.testing.assert_array_equal(lengths, [3, 2, 1])
    assert ids[1, 2] == 0  # padded
    assert feed["label"].shape == (3, 1)

    r = data.batch(data.shuffle(lambda: iter(rows * 10), 16, seed=3), 4)
    batches = list(r())
    assert all(len(b) == 4 for b in batches)

    r2 = data.firstn(lambda: iter(range(100)), 5)
    assert list(r2()) == [0, 1, 2, 3, 4]

    r3 = data.buffered(lambda: iter(range(10)), 4)
    assert list(r3()) == list(range(10))

    r4 = data.cache(lambda: iter(range(5)))
    assert list(r4()) == list(r4()) == [0, 1, 2, 3, 4]


def test_synthetic_datasets_shapes():
    img, lab = next(data.datasets.mnist("train", n=4)())
    assert img.shape == (28, 28, 1) and 0 <= lab < 10
    img, lab = next(data.datasets.cifar10("train", n=4)())
    assert img.shape == (32, 32, 3)
    ids, lab = next(data.datasets.imdb("train", n=4)())
    assert isinstance(ids, list) and lab in (0, 1)
    src, trg, nxt = next(data.datasets.wmt14("train", n=4)())
    assert trg[0] == 0 and nxt[-1] == 1 and len(trg) == len(nxt)
    u, m, r = next(data.datasets.movielens("train", n=4)())
    assert 1.0 <= r <= 5.0


def test_stat_timers_populate(rng):
    """--enable_timers (the reference's Stat print-per-pass, Stat.h:70-247)
    keeps the step timeline on, even under --obs_timeline=false, and logs
    its per-phase table at the end of a pass; the phases fill during
    train() through the trainer's one phase wrapper."""
    from paddle_tpu.trainer import events as ev
    from paddle_tpu.utils.flags import FLAGS

    nn.reset_naming()
    x = nn.data("x", size=4)
    cost = nn.mse_cost(input=nn.fc(x, 2, name="o"), label=nn.data("y", size=2))
    tr = SGDTrainer(cost, Adam(learning_rate=0.01), seed=0)

    def reader():
        for _ in range(3):
            yield {"x": rng.rand(4, 4).astype(np.float32),
                   "y": rng.rand(4, 2).astype(np.float32)}

    seen = {}

    def at_end_of_pass(e):   # end_pass() resets the per-pass stats after it
        if isinstance(e, ev.EndPass):
            seen.update(tr.timeline.pass_stats())
            seen["table"] = tr.timeline.table()

    old = FLAGS.enable_timers, FLAGS.obs_timeline
    FLAGS.enable_timers, FLAGS.obs_timeline = True, False
    try:
        tr.train(reader, num_passes=1, event_handler=at_end_of_pass)
    finally:
        FLAGS.enable_timers, FLAGS.obs_timeline = old
    assert {"data_wait", "prepare", "step", "callback"} <= set(seen)
    assert seen["step"]["count"] == 3 and seen["step"]["total"] > 0
    assert seen["data_wait"]["count"] == 4   # the last finds the reader empty
    assert "step" in seen["table"] and "share" in seen["table"]


def test_trainer_test_with_wired_evaluators(rng):
    """SGDTrainer.test(evaluators=...) — device-accumulated metric matches a
    manual host-side eval over the same reader."""
    import paddle_tpu.nn as nn
    from paddle_tpu.evaluators import ClassificationError
    from paddle_tpu.param.optimizers import SGD
    from paddle_tpu.trainer import SGDTrainer

    x = nn.data("x", size=6)
    y = nn.data("y", size=1, dtype="int32")
    logits = nn.fc(x, size=3, act="linear", name="logits")
    cost = nn.classification_cost(logits, y)
    tr = SGDTrainer(cost=cost, optimizer=SGD(learning_rate=0.1), seed=3)

    feeds = []
    rs = np.random.RandomState(0)
    for _ in range(3):
        feeds.append({
            "x": rs.randn(8, 6).astype(np.float32),
            "y": rs.randint(0, 3, (8,)),
        })

    def reader():
        return iter(feeds)

    def wire(outs, feed):
        return {"logits": outs["logits"], "labels": feed["y"]}

    res = tr.test(reader, evaluators={ClassificationError(): wire})
    assert "cost" in res and "classification_error" in res

    host = ClassificationError()
    host.start()
    for f in feeds:
        out = tr.infer([logits], f)
        host.eval_batch(logits=out["logits"], labels=f["y"])
    assert abs(res["classification_error"] - host.result()) < 1e-6


def test_trainer_test_duplicate_evaluators_get_distinct_keys(rng):
    import paddle_tpu.nn as nn
    from paddle_tpu.evaluators import ClassificationError
    from paddle_tpu.param.optimizers import SGD
    from paddle_tpu.trainer import SGDTrainer

    nn.reset_naming()
    x = nn.data("x", size=4)
    y = nn.data("y", size=1, dtype="int32")
    logits = nn.fc(x, size=2, act="linear", name="lg")
    tr = SGDTrainer(cost=nn.classification_cost(logits, y),
                    optimizer=SGD(learning_rate=0.1), seed=5)
    feeds = [{"x": np.zeros((4, 4), np.float32), "y": np.zeros((4,), np.int64)}]

    def wire(outs, feed):
        return {"logits": outs["lg"], "labels": feed["y"]}

    res = tr.test(lambda: iter(feeds),
                  evaluators={ClassificationError(): wire,
                              ClassificationError(): wire})
    assert "classification_error" in res and "classification_error:2" in res

    # empty reader: evaluator keys present but nan (never a fake-perfect 0.0)
    res2 = tr.test(lambda: iter([]), evaluators={ClassificationError(): wire})
    assert np.isnan(res2["classification_error"])


def test_show_parameter_stats_period(rng):
    """--show_parameter_stats_period logs a per-parameter stats table
    (TrainerInternal.cpp showParameterStats analog)."""
    import logging

    import paddle_tpu.nn as nn
    from paddle_tpu.param.optimizers import SGD
    from paddle_tpu.trainer import SGDTrainer
    from paddle_tpu.utils.flags import FLAGS

    nn.reset_naming()
    x = nn.data("x", size=4)
    y = nn.data("y", size=1, dtype="int32")
    cost = nn.classification_cost(nn.fc(x, 2, act="linear", name="w0"), y)
    tr = SGDTrainer(cost=cost, optimizer=SGD(learning_rate=0.1), seed=2)
    feeds = [{"x": np.zeros((4, 4), np.float32), "y": np.zeros((4,), np.int64)}
             for _ in range(2)]
    records = []

    class Grab(logging.Handler):
        def emit(self, r):
            records.append(r.getMessage())

    from paddle_tpu.utils.log import logger as ptlog
    h = Grab(level=logging.INFO)
    ptlog.addHandler(h)
    old = FLAGS.show_parameter_stats_period
    try:
        FLAGS.show_parameter_stats_period = 2
        tr.train(lambda: iter(feeds), num_passes=1)
    finally:
        FLAGS.show_parameter_stats_period = old
        ptlog.removeHandler(h)
    assert any("absmax" in m for m in records)


def test_test_period_mid_pass_eval(rng):
    """--test_period runs a mid-pass eval every N batches (Trainer.cpp
    trainOneBatch testing branch)."""
    import logging

    import paddle_tpu.nn as nn
    from paddle_tpu.param.optimizers import SGD
    from paddle_tpu.trainer import SGDTrainer
    from paddle_tpu.utils.flags import FLAGS
    from paddle_tpu.utils.log import logger as ptlog

    nn.reset_naming()
    x = nn.data("x", size=4)
    y = nn.data("y", size=1, dtype="int32")
    tr = SGDTrainer(cost=nn.classification_cost(nn.fc(x, 2, act="linear"), y),
                    optimizer=SGD(learning_rate=0.1), seed=3)
    feeds = [{"x": np.zeros((2, 4), np.float32), "y": np.zeros((2,), np.int64)}
             for _ in range(4)]
    msgs = []
    h = logging.Handler()
    h.emit = lambda r: msgs.append(r.getMessage())
    ptlog.addHandler(h)
    old = FLAGS.test_period
    try:
        FLAGS.test_period = 2
        tr.train(lambda: iter(feeds), num_passes=1,
                 test_reader=lambda: iter(feeds[:1]))
    finally:
        FLAGS.test_period = old
        ptlog.removeHandler(h)
    mid = [m for m in msgs if "Test cost" in m]
    assert len(mid) == 2  # batches 2 and 4
