"""Parallelism tests on the 8-virtual-device CPU mesh — the analog of the
reference's in-process distributed tests (test_TrainerOnePass "trainer +
pserver on localhost", SURVEY.md §4): sharded execution must match
single-device results exactly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu.models as models
import paddle_tpu.nn as nn
import paddle_tpu.ops as O
import paddle_tpu.parallel as par
from paddle_tpu.param.optimizers import Adam
from paddle_tpu.utils.devices import make_mesh


@pytest.fixture(autouse=True)
def fresh_names():
    nn.reset_naming()
    yield


def _seq2seq_batch(rng, V=64, B=8, S=8, T=8):
    m = models.Seq2SeqAttention(src_vocab=V, trg_vocab=V, emb_dim=8,
                                enc_dim=8, dec_dim=8, att_dim=8)
    params = m.init(jax.random.PRNGKey(0))
    src = rng.randint(3, V, (B, S)).astype(np.int32)
    src_len = rng.randint(2, S + 1, B).astype(np.int32)
    trg_core = rng.randint(3, V, (B, T - 1)).astype(np.int32)
    batch = {
        "src_ids": src, "src_len": src_len,
        "trg_in": np.concatenate([np.zeros((B, 1), np.int32), trg_core], 1),
        "trg_next": np.concatenate([trg_core, np.ones((B, 1), np.int32)], 1),
        "trg_len": rng.randint(2, T + 1, B).astype(np.int32),
    }
    return m, params, batch


def test_data_parallel_matches_single_device(rng):
    """DP-sharded train step == single-device step (MultiGradientMachine
    equivalence)."""
    m, params, batch = _seq2seq_batch(rng)
    opt = Adam(learning_rate=1e-3)

    # single device
    s0 = opt.init_state(params)
    loss_ref, p_ref, _ = par.make_parallel_train_step(m.loss, opt, make_mesh((1,), ("data",)), donate=False)(
        {k: jnp.asarray(v) for k, v in params.items()}, s0,
        {k: jnp.asarray(v) for k, v in batch.items()},
    )

    # 8-way data parallel
    mesh = make_mesh((8,), ("data",))
    p8 = par.shard_params(mesh, params)
    s8 = opt.init_state(p8)
    b8 = par.shard_batch(mesh, batch)
    loss8, p8_new, _ = par.make_parallel_train_step(m.loss, opt, mesh, donate=False)(p8, s8, b8)

    np.testing.assert_allclose(float(loss_ref), float(loss8), rtol=1e-5)
    for k in p_ref:
        np.testing.assert_allclose(
            np.asarray(p_ref[k]), np.asarray(p8_new[k]), rtol=1e-4, atol=1e-6
        )


def _text_clf_batch(rng, V=40, B=16, T=8):
    """``lstm_benchmark_net`` at a toy size: its cost is a mean over rows."""
    cost, _ = models.lstm_benchmark_net(V, emb_dim=8, hid_dim=8, num_layers=1)
    topo = nn.Topology(cost)
    params, state = topo.init(jax.random.PRNGKey(0))
    batch = {"words": (rng.randint(3, V, (B, T)).astype(np.int32),
                       rng.randint(2, T + 1, B).astype(np.int32)),
             "label": rng.randint(0, 2, (B, 1)).astype(np.int32)}

    def loss(p, b):
        return topo.apply(p, state, b, train=True)[0]["cost"].value

    return loss, params, batch


def _one_device_step(loss_fn, opt, params, batch):
    put = lambda t: jax.tree_util.tree_map(jnp.asarray, t)  # noqa: E731
    step = par.make_parallel_train_step(
        loss_fn, opt, make_mesh((1,), ("data",)), donate=False)
    return step(put(params), opt.init_state(put(params)), put(batch))


@pytest.mark.parametrize("case", ["token_mean_two_rows_a_shard",
                                  "row_mean_two_rows_a_shard"])
def test_data_parallel_step_is_the_global_batchs(rng, case):
    """Ragged lengths, two rows a shard: the eight-way step's loss and
    update are the one-device step's on the whole batch, for a loss that is
    a mean over real tokens (a mean of the shards' token means is another
    number there) and for one that is a mean over rows."""
    if case.startswith("token_mean"):
        m, params, batch = _seq2seq_batch(rng, B=16)
        loss_fn = m.loss
    else:
        loss_fn, params, batch = _text_clf_batch(rng)
    opt = Adam(learning_rate=1e-3)
    loss_ref, p_ref, _ = _one_device_step(loss_fn, opt, params, batch)

    mesh = make_mesh((8,), ("data",))
    p8 = par.shard_params(mesh, params)
    loss8, p8_new, _ = par.make_parallel_train_step(
        loss_fn, opt, mesh, donate=False)(
            p8, opt.init_state(p8), par.shard_batch(mesh, batch))
    np.testing.assert_allclose(float(loss_ref), float(loss8), rtol=1e-5)
    for k in p_ref:
        np.testing.assert_allclose(np.asarray(p_ref[k]),
                                   np.asarray(p8_new[k]), rtol=1e-4,
                                   atol=1e-6, err_msg=k)


@pytest.mark.parametrize("lengths", [[3, 1, 0, 4, 2, 2, 0, 1], [0] * 8],
                         ids=["ragged", "no_real_token"])
def test_token_count_outside_and_inside_a_data_parallel_body(lengths):
    """Outside a data-parallel body the count is ``max(sum(mask), 1)`` to
    the bit; inside it is the mean over the shards of the global count, on
    every shard, so that shard totals over it average to the global mean."""
    from paddle_tpu.ops.losses import token_count, token_mean_over_shards

    mask = O.mask_from_lengths(jnp.asarray(lengths, jnp.int32), 4).astype(
        jnp.float32)
    want = jnp.maximum(jnp.sum(mask), 1.0)
    got = token_count(mask)
    assert got.dtype == want.dtype and np.array_equal(got, want)
    assert np.array_equal(jax.jit(token_count)(mask), want)

    mesh = make_mesh((4,), ("data",))

    def body(rows):
        with token_mean_over_shards("data"):
            return token_count(rows)[None]

    inside = par.compat.shard_map(body, mesh=mesh, in_specs=P("data"),
                                  out_specs=P("data"))(mask)
    np.testing.assert_array_equal(np.asarray(inside),
                                  np.full(4, float(want) / 4, np.float32))
    assert np.array_equal(token_count(mask), want)      # and closed again


@pytest.mark.parametrize("with_rules", [False, True],
                         ids=["data_parallel", "with_rules"])
def test_kernel_gates_inside_the_parallel_step(monkeypatch, with_rules):
    """At trace time, from inside ``loss_fn``: the pure data-parallel step
    leaves the gates' mesh half open and shows the loss one chip's rows
    (``shard_map``: a Mosaic kernel is an ordinary call there); a step with
    ``rules`` is partitioned by jit, sees the global batch and keeps the
    gates closed (``xla_paths_only``)."""
    from paddle_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_warned_kernels_off", True)   # keep it quiet
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seen = []

    def loss_fn(p, b):
        seen.append((pk.compiled_kernels(), b["x"].shape[0]))
        return jnp.mean((b["x"] @ p["w"]) ** 2)

    mesh = make_mesh((4,), ("data",))
    rules = par.ShardingRules([("*", P())]) if with_rules else None
    params = par.shard_params(mesh, {"w": np.ones((4, 2), np.float32)}, rules)
    opt = Adam(learning_rate=1e-3)
    batch = par.shard_batch(mesh, {"x": np.ones((16, 4), np.float32)})
    par.make_parallel_train_step(loss_fn, opt, mesh, rules=rules).lower(
        params, opt.init_state(params), batch)
    assert seen and set(seen) == ({(False, 16)} if with_rules
                                  else {(True, 4)})
    assert not getattr(pk._mesh_trace, "depth", 0)


def _four_way_seq2seq(rng):
    m, params, batch = _seq2seq_batch(rng, V=40, B=16, S=5, T=6)
    mesh = make_mesh((4,), ("data",))
    opt = Adam(learning_rate=1e-3)
    p4 = par.shard_params(mesh, params)
    step = par.make_parallel_train_step(m.loss, opt, mesh, donate=False)
    return step, p4, opt.init_state(p4), par.shard_batch(mesh, batch)


def test_data_parallel_step_reduces_the_embedding_as_a_table(rng):
    """The four-device step's compiled text: the source embedding's gradient
    crosses the chips as the ``[V, emb]`` table each chip scattered its own
    rows into, and no all-reduce carries the rows of the global batch
    (16 x 5 source positions; the partitioned step reduced those, 302 MB a
    step at the flagship's size) nor of one shard."""
    step, p4, s4, b4 = _four_way_seq2seq(rng)
    text = step.lower(p4, s4, b4).compile().as_text()
    reduces = [l for l in text.splitlines() if " all-reduce(" in l
               or " all-reduce-start(" in l]
    assert reduces
    assert any("f32[40,8]" in l for l in reduces), reduces
    for l in reduces:
        assert not any(rows in l for rows in ("[80,", "[16,5", "[20,",
                                              "[4,5")), l
    assert " all-gather(" not in text and " all-to-all(" not in text


def test_data_parallel_replicas_stay_bit_equal(rng):
    """Every chip applies the same reduced gradient to the same replica:
    after three steps the four copies of every leaf are equal to the bit."""
    step, p4, s4, b4 = _four_way_seq2seq(rng)
    p0 = p4
    for _ in range(3):
        _, p4, s4 = step(p4, s4, b4)
    for name, leaf in p4.items():
        copies = [np.asarray(s.data) for s in leaf.addressable_shards]
        assert len(copies) == 4 and copies[0].shape == leaf.shape, name
        for other in copies[1:]:
            assert np.array_equal(copies[0], other), name
    assert float(jnp.abs(p4["src_emb"] - p0["src_emb"]).max()) > 0


@pytest.mark.parametrize("with_rules", [False, True],
                         ids=["data_parallel", "with_rules"])
def test_parallel_step_is_traced_once(rng, with_rules):
    """``init_state`` places a state like the placed parameters it is made
    from, which is how the step hands it back: the second call, on the
    state the first returned, finds the first call's trace (a second one
    costs a trace, a lowering and an executable load in every set-up)."""
    m, params, batch = _seq2seq_batch(rng)
    opt = Adam(learning_rate=1e-3)
    if with_rules:
        mesh = make_mesh((4, 2), ("data", "model"))
        rules = par.ShardingRules([("out_w", P(None, "model")), ("*", P())])
    else:
        mesh, rules = make_mesh((4,), ("data",)), None
    p = par.shard_params(mesh, params, rules)
    s = opt.init_state(p)
    for slot in s["slots"]["out_w"]:
        assert slot.sharding == p["out_w"].sharding
    assert s["step"].sharding.is_fully_replicated
    assert len(s["step"].sharding.device_set) == mesh.size
    step = par.make_parallel_train_step(m.loss, opt, mesh, rules=rules,
                                        donate=False)
    b = par.shard_batch(mesh, batch)
    for _ in range(3):
        _, p, s = step(p, s, b)
    assert step._cache_size() == 1


def test_tensor_parallel_matches_single_device(rng):
    """DP x TP sharded step == single-device step (the ParallelNeuralNetwork /
    model-parallel equivalence, but via GSPMD)."""
    m, params, batch = _seq2seq_batch(rng)
    opt = Adam(learning_rate=1e-3)
    s0 = opt.init_state(params)
    step1 = par.make_parallel_train_step(m.loss, opt, make_mesh((1,), ("data",)), donate=False)
    loss_ref, p_ref, _ = step1(
        {k: jnp.asarray(v) for k, v in params.items()}, s0,
        {k: jnp.asarray(v) for k, v in batch.items()},
    )

    mesh = make_mesh((4, 2), ("data", "model"))
    rules = par.ShardingRules([
        ("*_emb", par.P(None, "model")),
        ("out_w", par.P(None, "model")),
        ("out_b", par.P("model")),
        ("*_wx", par.P(None, "model")),
        ("*", par.P()),
    ])
    pS = par.shard_params(mesh, params, rules)
    sS = opt.init_state(pS)
    bS = par.shard_batch(mesh, batch)
    lossS, pS_new, _ = par.make_parallel_train_step(m.loss, opt, mesh, rules=rules, donate=False)(pS, sS, bS)
    np.testing.assert_allclose(float(loss_ref), float(lossS), rtol=1e-5)
    for k in ("out_w", "src_emb", "dec_wh"):
        np.testing.assert_allclose(
            np.asarray(p_ref[k]), np.asarray(pS_new[k]), rtol=1e-4, atol=1e-6
        )


def test_ring_attention_matches_full_attention(rng):
    B, H, T, D = 2, 4, 32, 8
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    mesh = make_mesh((8,), ("seq",))
    out_ring = par.ring_attention_sharded(q, k, v, mesh, causal=False)
    out_ref = O.dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_ref),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_causal(rng):
    B, H, T, D = 1, 2, 16, 4
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    mesh = make_mesh((4,), ("seq",))
    out_ring = par.ring_attention_sharded(q, k, v, mesh, causal=True)
    causal_mask = jnp.tril(jnp.ones((T, T)))[None, None]
    out_ref = O.dot_product_attention(q, k, v, mask=causal_mask)
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_ref),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_grads(rng):
    B, H, T, D = 1, 1, 16, 4
    mesh = make_mesh((4,), ("seq",))
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))

    g_ring = jax.grad(lambda q: jnp.sum(par.ring_attention_sharded(q, k, v, mesh)))(q)
    g_ref = jax.grad(lambda q: jnp.sum(O.dot_product_attention(q, k, v)))(q)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_ref), rtol=1e-3, atol=1e-4)


def test_sharded_embedding_matches_dense(rng):
    V, D = 64, 8
    mesh = make_mesh((8,), ("model",))
    table = jnp.asarray(rng.randn(V, D).astype(np.float32))
    ids = jnp.asarray(rng.randint(0, V, (4, 7)).astype(np.int32))
    t_sh = par.shard_table(mesh, table)
    out = par.sharded_embedding_lookup(mesh, t_sh, ids)
    ref = O.embedding_lookup(table, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_sharded_embedding_grad_is_row_sparse_scatter(rng):
    V, D = 32, 4
    mesh = make_mesh((4,), ("model",))
    table = jnp.asarray(rng.randn(V, D).astype(np.float32))
    ids = jnp.asarray(np.array([[1, 5, 1]], np.int32))
    t_sh = par.shard_table(mesh, table)

    def f(t):
        return jnp.sum(par.sharded_embedding_lookup(mesh, t, ids))

    g = np.asarray(jax.grad(f)(t_sh))
    expect = np.zeros((V, D), np.float32)
    expect[1] = 2.0
    expect[5] = 1.0
    np.testing.assert_allclose(g, expect, atol=1e-6)


def test_trainer_with_mesh_runs(rng):
    """SGDTrainer(mesh=...) end-to-end on the virtual mesh."""
    x = nn.data("x", size=8)
    lab = nn.data("label", size=1, dtype="int32")
    logits = nn.fc(nn.fc(x, 16, act="relu"), 2, act="linear", name="logits")
    cost = nn.classification_cost(logits, lab, name="cost")
    from paddle_tpu.trainer import SGDTrainer

    mesh = make_mesh((8,), ("data",))
    trainer = SGDTrainer(cost, Adam(learning_rate=1e-2), mesh=mesh, seed=0)
    feed = {"x": rng.randn(16, 8).astype(np.float32),
            "label": rng.randint(0, 2, (16, 1))}
    l0 = float(trainer.train_batch(feed))
    for _ in range(20):
        l = float(trainer.train_batch(feed))
    assert l < l0


def test_sgdtrainer_tensor_parallel_matches_single(rng):
    """SGDTrainer(mesh=..., sharding_rules=...) — TP through the Topology
    trainer itself — produces the SAME losses as the single-device trainer
    (ParallelNeuralNetwork.h:34 analog, params sharded not just activations)."""
    from paddle_tpu.trainer import SGDTrainer

    def build():
        nn.reset_naming()
        x = nn.data("x", size=16)
        h = nn.fc(x, 32, act="relu", name="h")
        logits = nn.fc(h, 8, act="linear", name="out")
        lab = nn.data("label", size=8, dtype="int32")
        return nn.classification_cost(logits, lab, name="cost")

    feeds = [{"x": rng.rand(8, 16).astype(np.float32),
              "label": rng.randint(0, 8, (8,))} for _ in range(3)]

    t_single = SGDTrainer(build(), Adam(learning_rate=0.01), seed=5)
    losses_single = [float(t_single.train_batch(f)) for f in feeds]

    mesh = make_mesh((4, 2), ("data", "model"))
    rules = par.ShardingRules([
        ("_h.w0", P(None, "model")),     # column-parallel hidden
        ("_h.wbias", P("model")),
        ("_out.w0", P("model", None)),   # row-parallel readout
        ("*", P()),
    ])
    t_tp = SGDTrainer(build(), Adam(learning_rate=0.01), seed=5,
                      mesh=mesh, sharding_rules=rules)
    # params actually placed sharded (not replicated)
    sh = t_tp.params["_h.w0"].sharding
    assert sh.spec == P(None, "model")
    losses_tp = [float(t_tp.train_batch(f)) for f in feeds]

    np.testing.assert_allclose(losses_single, losses_tp, rtol=2e-5)
    for k in t_single.params:
        np.testing.assert_allclose(np.asarray(t_single.params[k]),
                                   np.asarray(t_tp.params[k]),
                                   rtol=2e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# pipeline parallelism (parallel/pipeline.py)
# ---------------------------------------------------------------------------


def _mlp_stage(w, x):
    """One homogeneous pipeline block: residual two-layer MLP."""
    h = jnp.tanh(x @ w["w1"] + w["b1"])
    return x + h @ w["w2"]


def _stage_params(rng, n_stages, d, hid):
    return [
        {"w1": jnp.asarray(rng.randn(d, hid).astype(np.float32) * 0.3),
         "b1": jnp.zeros((hid,), np.float32),
         "w2": jnp.asarray(rng.randn(hid, d).astype(np.float32) * 0.3)}
        for _ in range(n_stages)
    ]


def _sequential(per_stage, x):
    for w in per_stage:
        x = _mlp_stage(w, x)
    return x


def test_pipeline_forward_matches_sequential(rng):
    """GPipe shard_map schedule == running the stages one after another."""
    S, B, D, M = 4, 16, 12, 4
    per_stage = _stage_params(rng, S, D, 24)
    stacked = par.stack_stage_params(per_stage)
    mesh = make_mesh((S,), ("stage",))
    x = jnp.asarray(rng.randn(B, D).astype(np.float32))
    y_pp = par.pipeline_apply(_mlp_stage, stacked, x, mesh=mesh,
                              n_microbatches=M)
    y_ref = _sequential(per_stage, x)
    np.testing.assert_allclose(np.asarray(y_pp), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_single_microbatch_and_uneven_raises(rng):
    S, B, D = 2, 6, 8
    per_stage = _stage_params(rng, S, D, 8)
    stacked = par.stack_stage_params(per_stage)
    mesh = make_mesh((S,), ("stage",))
    x = jnp.asarray(rng.randn(B, D).astype(np.float32))
    y1 = par.pipeline_apply(_mlp_stage, stacked, x, mesh=mesh, n_microbatches=1)
    np.testing.assert_allclose(np.asarray(y1),
                               np.asarray(_sequential(per_stage, x)),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="not divisible"):
        par.pipeline_apply(_mlp_stage, stacked, x, mesh=mesh, n_microbatches=4)


def test_pipeline_dp_pp_train_step_matches_single_device(rng):
    """dp x pp (2 x 4 mesh): loss and updated stage weights must match the
    plain single-device step — the backward pipeline schedule is derived by
    autodiff, including the data-axis grad reduction."""
    S, B, D, M = 4, 16, 12, 4
    per_stage = _stage_params(rng, S, D, 24)
    stacked = par.stack_stage_params(per_stage)
    x = np.asarray(rng.randn(B, D), np.float32)
    target = np.asarray(rng.randn(B, D), np.float32)

    def loss_fn(y, t):
        return jnp.mean((y - t) ** 2)

    opt = Adam(learning_rate=1e-2)

    # reference: same stacked pytree, sequential stages, one device
    def ref_objective(w):
        y = _sequential([jax.tree_util.tree_map(lambda a, i=i: a[i], w)
                         for i in range(S)], jnp.asarray(x))
        return loss_fn(y, jnp.asarray(target))

    s_ref = opt.init_state(stacked)
    loss_ref, grads_ref = jax.value_and_grad(ref_objective)(stacked)
    p_ref, _ = opt.update(stacked, grads_ref, s_ref)

    mesh = make_mesh((2, 4), ("data", "stage"))
    p = par.shard_stage_params(mesh, stacked)
    s = opt.init_state(p)
    xb = jax.device_put(jnp.asarray(x), par.batch_sharding(mesh, 2))
    tb = jax.device_put(jnp.asarray(target), par.batch_sharding(mesh, 2))
    step = par.make_pipeline_train_step(
        _mlp_stage, loss_fn, opt, mesh, n_microbatches=M, data_axis="data",
        donate=False)
    loss_pp, p_pp, _ = step(p, s, xb, tb)

    np.testing.assert_allclose(float(loss_pp), float(loss_ref),
                               rtol=1e-5, atol=1e-6)
    for k in ("w1", "b1", "w2"):
        np.testing.assert_allclose(
            np.asarray(p_pp[k]), np.asarray(p_ref[k]), rtol=1e-4, atol=1e-5,
            err_msg=f"stage-stacked {k} diverged after one dp x pp step")
