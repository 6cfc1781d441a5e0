"""The decoder-only block (PR 28): every new DSL layer and the whole
``lfm2_moe_net`` against the plain reference of benchmark/reference on seeded
weights (loss and every gradient leaf), the share test, dropless routing,
blockwise attention off the block size, the kernels in interpret mode against
the XLA paths, recomputation blocks, and the routing counters.

Tolerances.  The suite computes in float32 (conftest), and the reference is
float32 too, so program and reference differ by rounding and the order of
sums only: gradients are held to 2e-4 of the leaf's norm (five layers of
float32 products of a few hundred terms; measured 1e-6), losses to 1e-5.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.nn as nn
from paddle_tpu.models import lfm2_moe_net
from paddle_tpu.ops import decoder_block as DB
from paddle_tpu.ops import moe as M
from paddle_tpu.utils.error import ConfigError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import correct, manifest  # noqa: E402

GRAD_TOL, LOSS_TOL = 2e-4, 1e-5
#: hidden 64, 8 experts with 2 held, top 2, 5 layers, T 64
CFG = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    conv_L_cache=3, intermediate_size=96, moe_intermediate_size=48,
    router_outputs=8, num_experts=2, first_expert=2, num_experts_per_tok=2,
    vocab_size=50, num_dense_layers=1, norm_eps=1e-5, norm_topk_prob=True,
    routed_scaling_factor=1.0,
    layer_types=["conv", "full_attention", "conv", "conv", "conv"],
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"})
B, T = 2, 64


@pytest.fixture(scope="module")
def ref():
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "reference", "lfm2-24b-a2b-ep8.py"), "lfm2_ref")


def build(cfg, **kw):
    nn.reset_naming()
    return lfm2_moe_net(
        cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=cfg["layer_types"],
        num_dense_layers=cfg["num_dense_layers"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["router_outputs"],
        experts_held=(cfg["first_expert"], cfg["num_experts"]),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], conv_kernel=cfg["conv_L_cache"],
        norm_eps=cfg["norm_eps"],
        rope_theta=cfg["rope_parameters"]["rope_theta"], **kw)


def feed(seed=0, t=T):
    ids = np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], (B, t + 1)).astype(np.int32)
    lengths = np.full((B,), t, np.int32)
    return {"tokens": (ids[:, :-1], lengths),
            "next_tokens": (ids[:, 1:], lengths)}


def rel(got, want):
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


# -- the whole model ---------------------------------------------------------


@pytest.fixture(scope="module")
def model_grads(ref):
    cost, extras = build(CFG)
    topo = nn.Topology([cost] + extras)
    params = correct.init_params(ref, CFG, 3)
    assert ({k: v.shape for k, v in topo.init(jax.random.PRNGKey(0))[0].items()}
            == {k: v.shape for k, v in params.items()})
    batch = feed()

    def program(p):
        outs, _ = topo.apply(p, {}, batch, train=True)
        return outs["cost"].value

    def reference(p):
        total, count = ref.loss_sum(CFG, p, batch)
        return total / count

    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(reference)(params)
        got = jax.jit(jax.value_and_grad(program))(params)
    return got, want


LEAVES = sorted(
    ["_emb.w0", "_norm_out.w"]
    + [f"_norm_{w}{i}.w" for i in range(5) for w in ("op", "ffn")]
    + [f"_conv{i}.{p}" for i in (0, 2, 3, 4)
       for p in ("w_in", "kernel", "w_out")]
    + [f"_attn1.{p}" for p in ("wq", "wk", "wv", "wo", "q_norm", "k_norm")]
    + [f"_mlp0.{p}" for p in ("w1", "w2", "w3")]
    + [f"_moe{i}.{p}" for i in (1, 2, 3, 4)
       for p in ("router", "expert_bias", "w1", "w2", "w3")])


def test_model_loss_matches_the_reference(model_grads, ref):
    (loss, grads), (want, want_grads) = model_grads
    assert sorted(grads) == LEAVES == sorted(ref.param_shapes(CFG))
    assert abs(float(loss) - float(want)) <= LOSS_TOL * abs(float(want))


@pytest.mark.parametrize("leaf", LEAVES)
def test_model_gradient_matches_the_reference(model_grads, leaf):
    (_, grads), (_, want) = model_grads
    if leaf.endswith("expert_bias"):
        # enters the selection only: exactly zero on both sides
        assert not np.asarray(grads[leaf]).any()
        assert not np.asarray(want[leaf]).any()
    else:
        assert float(jnp.linalg.norm(want[leaf])) > 0
        assert rel(grads[leaf], want[leaf]) <= GRAD_TOL


def test_recompute_blocks_change_no_number_and_are_in_the_program(ref):
    from paddle_tpu.analysis.jaxpr_walk import walk_eqns

    params = correct.init_params(ref, CFG, 5)
    batch = feed(1)
    values, prims = [], []
    for recompute in (True, False):
        cost, _ = build(CFG, recompute_layers=recompute)
        topo = nn.Topology(cost)

        def loss(p, topo=topo):
            return topo.apply(p, {}, batch, train=True)[0]["cost"].value

        values.append(jax.jit(jax.value_and_grad(loss))(params))
        closed = jax.make_jaxpr(jax.grad(loss))(params)
        prims.append([e.primitive.name for e, _ in walk_eqns(closed.jaxpr)
                      if e.primitive.name in ("remat", "remat2",
                                              "checkpoint")])
    (a, ga), (b, gb) = values
    assert float(a) == pytest.approx(float(b), rel=1e-6)
    assert all(rel(ga[k], gb[k]) <= 1e-5 for k in ga if ga[k].any())
    assert len(prims[0]) >= 5 and not prims[1]    # a block a decoder layer


def test_open_recompute_block_is_refused():
    nn.reset_naming()
    x = nn.data("x", size=4)
    a = nn.fc(x, 4, name="a")
    b = nn.fc(a, 4, name="b")
    c = nn.fc(b, 4, name="c")
    nn.remat_block([a, c], "outer")      # reads b, which reads a
    with pytest.raises(ConfigError, match="not closed"):
        nn.Topology(c).apply(*nn.Topology(c).init(jax.random.PRNGKey(0)),
                             {"x": np.ones((2, 4), np.float32)})
    with pytest.raises(ConfigError, match="data layer"):
        nn.remat_block([x], "inputs")


# -- layer by layer ------------------------------------------------------------


def _one_layer(kind):
    """(layer node over a [B, T, 64] sequence feed, the reference's function
    of (params, x) for it)."""
    nn.reset_naming()
    x = nn.data("x", size=CFG["hidden_size"], is_seq=True)
    if kind == "rms_norm":
        return nn.rms_norm(x, eps=1e-5, name="norm_op0"), \
            lambda ref, p, v: ref.rms_norm(v, p["_norm_op0.w"], 1e-5)
    if kind == "gated_short_conv":
        return nn.gated_short_conv(x, kernel_size=3, name="conv0"), \
            lambda ref, p, v: ref.short_conv(CFG, p, "_conv0", v)
    if kind == "causal_self_attention":
        return nn.causal_self_attention(
            x, num_heads=4, num_kv_heads=2, head_dim=16, rope_theta=1e6,
            name="attn1"), \
            lambda ref, p, v: ref.attention(CFG, p, "_attn1", v)
    if kind == "gated_mlp":
        return nn.gated_mlp(x, 96, name="mlp0"), \
            lambda ref, p, v: ref.gated_mlp(v, p["_mlp0.w1"], p["_mlp0.w3"],
                                            p["_mlp0.w2"])
    assert kind == "expert_mlp"
    return nn.expert_mlp(x, 48, num_experts=8, experts_held=(2, 2), top_k=2,
                         name="moe1"), \
        lambda ref, p, v: ref.experts(CFG, p, "_moe1", v)


@pytest.mark.parametrize("kind", ["rms_norm", "gated_short_conv",
                                  "causal_self_attention", "gated_mlp",
                                  "expert_mlp"])
def test_layer_matches_the_reference(kind, ref):
    node, plain = _one_layer(kind)
    topo = nn.Topology(node)
    shapes = ref.param_shapes(CFG)
    params = correct.init_params(
        type("R", (), {"param_shapes": staticmethod(
            lambda cfg: {k: shapes[k] for k in topo.param_specs})}), CFG, 11)
    rng = np.random.default_rng(4)
    x = rng.standard_normal((B, T, 64)).astype(np.float32)
    w = rng.standard_normal((B, T, 64)).astype(np.float32)
    lengths = np.full((B,), T, np.int32)

    def program(p, v):
        out = topo.apply(p, {}, {"x": (v, lengths)}, train=True)[0]
        return jnp.sum(out[node.name].value * w)

    def reference(p, v):
        return jnp.sum(plain(ref, p, v) * w)

    with jax.default_matmul_precision("highest"):
        want, want_g = jax.jit(jax.value_and_grad(
            reference, argnums=(0, 1)))(params, x)
        got, got_g = jax.jit(jax.value_and_grad(
            program, argnums=(0, 1)))(params, x)
    assert float(got) == pytest.approx(float(want), rel=1e-4, abs=1e-4)
    for g, wg in zip(jax.tree_util.tree_leaves(got_g),
                     jax.tree_util.tree_leaves(want_g)):
        if np.asarray(wg).any():
            assert rel(g, wg) <= GRAD_TOL


def test_lm_head_cost_is_the_tied_heads_cross_entropy(ref):
    nn.reset_naming()
    ids = nn.data("tokens", size=50, is_seq=True, dtype="int32")
    lab = nn.data("next_tokens", size=50, is_seq=True, dtype="int32")
    emb = nn.embedding(ids, 64, name="emb")
    cost = nn.lm_head_cost(emb, lab, embedding=emb, name="cost")
    topo = nn.Topology(cost)
    params, _ = topo.init(jax.random.PRNGKey(2))
    assert sorted(params) == ["_emb.w0"]          # one matrix, not two
    batch = feed(2)
    lengths = np.array([T, T // 2], np.int32)     # a padded row counts less
    batch = {k: (v[0], lengths) for k, v in batch.items()}

    def program(p):
        return topo.apply(p, {}, batch, train=True)[0]["cost"].value

    def plain(p):
        z = p["_emb.w0"][batch["tokens"][0]] @ p["_emb.w0"].T
        logp = jax.nn.log_softmax(z, -1)
        picked = jnp.take_along_axis(
            logp, batch["next_tokens"][0][..., None], -1)[..., 0]
        mask = np.arange(T)[None] < lengths[:, None]
        return -(picked * mask).sum() / mask.sum()

    want, wg = jax.value_and_grad(plain)(params)
    got, gg = jax.value_and_grad(program)(params)
    assert float(got) == pytest.approx(float(want), rel=LOSS_TOL)
    assert rel(gg["_emb.w0"], wg["_emb.w0"]) <= GRAD_TOL


# -- the expert layer ----------------------------------------------------------


def _expert_layer(first, held):
    nn.reset_naming()
    x = nn.data("x", size=64, is_seq=True)
    node = nn.expert_mlp(x, 48, num_experts=8, experts_held=(first, held),
                         top_k=2, name="moe1")
    return node, nn.Topology(node)


def _whole_layer_params(ref, seed=9):
    cfg = dict(CFG, num_experts=8, first_expert=0)
    shapes = {k: v for k, v in ref.param_shapes(cfg).items()
              if k.startswith("_moe1.")}
    return cfg, correct.init_params(
        type("R", (), {"param_shapes": staticmethod(lambda c: shapes)}),
        cfg, seed)


def test_shares_add_up_to_the_uncut_layer(ref):
    """The parts of the result that the four shares of two experts give add
    up to what the uncut reference (all eight experts) gives for the layer."""
    cfg, whole = _whole_layer_params(ref)
    x = np.random.default_rng(8).standard_normal((B, T, 64)).astype(np.float32)
    lengths = np.full((B,), T, np.int32)
    with jax.default_matmul_precision("highest"):
        want = ref.experts(cfg, whole, "_moe1", jnp.asarray(x))
        total, load = 0.0, []
        for first in range(0, 8, 2):
            node, topo = _expert_layer(first, 2)
            share = dict(whole)
            for leaf in ("w1", "w3", "w2"):
                share[f"_moe1.{leaf}"] = whole[f"_moe1.{leaf}"][first:first + 2]
            out = topo.apply(share, {}, {"x": (x, lengths)})[0][node.name]
            total = total + out.value
            load += list(np.asarray(out.state["expert_load"]))
            assert int(out.state["uncomputed"]) == 0
    assert rel(total, want) <= 1e-5
    assert sum(load) == B * T * 2       # every choice landed on one chip


@pytest.mark.parametrize("target", [0, 1])
def test_dropless_when_every_token_goes_to_one_expert(ref, target):
    """A bias that sends every token's first choice to one expert held: its
    group is the whole batch, nothing is dropped, and the result is still
    the reference's."""
    cfg = dict(CFG, num_experts=2, first_expert=4)
    shapes = {k: v for k, v in ref.param_shapes(cfg).items()
              if k.startswith("_moe1.")}
    params = correct.init_params(
        type("R", (), {"param_shapes": staticmethod(lambda c: shapes)}),
        cfg, 13)
    params["_moe1.expert_bias"] = jnp.zeros((8,)).at[4 + target].set(100.0)
    node, topo = _expert_layer(4, 2)
    x = np.random.default_rng(3).standard_normal((B, T, 64)).astype(np.float32)
    out = topo.apply(params, {}, {"x": (x, np.full((B,), T, np.int32))})[0][
        node.name]
    load = np.asarray(out.state["expert_load"])
    assert load[target] == B * T and int(out.state["uncomputed"]) == 0
    with jax.default_matmul_precision("highest"):
        want = ref.experts(cfg, params, "_moe1", jnp.asarray(x))
    assert rel(out.value, want) <= 1e-5


def test_padded_positions_are_no_tokens():
    node, topo = _expert_layer(0, 8)
    params, _ = topo.init(jax.random.PRNGKey(1))
    x = np.ones((B, T, 64), np.float32)
    lengths = np.array([T, 10], np.int32)
    out = topo.apply(params, {}, {"x": (x, lengths)})[0][node.name]
    assert int(np.asarray(out.state["expert_load"]).sum()) == (T + 10) * 2


@pytest.mark.parametrize("held,tm", [(3, 8), (8, 16), (1, 8)])
def test_grouping_places_every_assignment_held_once(held, tm):
    idx = jnp.asarray(np.random.default_rng(held).integers(0, 8, (40, 2)),
                      jnp.int32)
    counts, order = M.count_assignments(idx, first_expert=2, held=held)
    usual, worst = M.buffer_rows(40, 2, 8, held, tm)
    assert usual <= worst and worst >= 40 * min(2, held) + held * (tm - 1)
    g = M.group_assignments(counts, order, tm=tm, rows=worst)
    here = ((np.asarray(idx) >= 2) & (np.asarray(idx) < 2 + held)).reshape(-1)
    row_assign = np.asarray(g.row_assign)
    rows = np.flatnonzero(row_assign < here.size)
    # every assignment held has one row, in a tile of its own expert,
    # inside the active tiles; no other row holds anything
    assert sorted(row_assign[rows]) == list(np.flatnonzero(here))
    assert here.sum() == int(g.counts.sum())
    tile_of_row = np.asarray(g.tile_expert)[rows // tm]
    assert (tile_of_row == np.asarray(idx).reshape(-1)[row_assign[rows]]
            - 2).all()
    assert rows.max(initial=-1) < int(g.n_active[0]) * tm
    assert int(g.uncomputed) == 0
    # a buffer too small for the routing owns up to what it left out
    small = M.group_assignments(counts, order, tm=tm, rows=tm)
    assert int(small.uncomputed) == here.sum() - int(
        (np.asarray(small.row_assign) < here.size).sum()) > 0


# -- attention -----------------------------------------------------------------


def _plain_attention(q, k, v, scale):
    G = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    Tq = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((Tq, Tq), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def _qkv(t, h=4, hkv=2, dh=16, seed=0):
    key = jax.random.PRNGKey(seed)
    shape = lambda n: (1, t, n, dh)  # noqa: E731
    return (jax.random.normal(key, shape(h)),
            jax.random.normal(jax.random.fold_in(key, 1), shape(hkv)),
            jax.random.normal(jax.random.fold_in(key, 2), shape(hkv)),
            jax.random.normal(jax.random.fold_in(key, 3), shape(h)))


@pytest.mark.parametrize("length", [40, 48, 7])
def test_blockwise_attention_off_the_block_size(length, monkeypatch):
    """Blocks of 16 queries over rows of 40 (a short last block), 48 (whole
    blocks) and 7 (less than one): the same numbers as plain attention."""
    monkeypatch.setattr(DB, "ATTN_XLA_BLOCK", 16)
    q, k, v, w = _qkv(length)

    def run(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * w), argnums=(0, 1, 2)))(
                q, k, v)

    with jax.default_matmul_precision("highest"):
        got, got_g = run(lambda q, k, v: DB.causal_attention(q, k, v,
                                                             scale=0.25))
        want, want_g = run(lambda q, k, v: _plain_attention(q, k, v, 0.25))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert all(rel(a, b) <= 1e-5 for a, b in zip(got_g, want_g))


def test_flash_kernels_match_the_xla_path():
    """Interpret mode: the forward and the backward kernel against the XLA
    blocks (float32 operands here, so rounding only)."""
    from paddle_tpu.ops import pallas_kernels as PK

    q, k, v, w = _qkv(256, dh=64, seed=5)
    want, (dq, dk, dv) = jax.value_and_grad(
        lambda q, k, v: jnp.sum(DB.causal_attention(q, k, v, scale=0.125) * w),
        argnums=(0, 1, 2))(q, k, v)
    heads = lambda a: jnp.swapaxes(a, 1, 2)  # noqa: E731
    out, lse = PK.flash_attn_fwd_pallas(heads(q), heads(k), heads(v),
                                        scale=0.125, block_q=128, block_k=128)
    assert float(jnp.sum(heads(out) * w)) == pytest.approx(float(want),
                                                           rel=1e-5)
    got = PK.flash_attn_bwd_pallas(heads(q), heads(k), heads(v), out, lse,
                                   heads(w), scale=0.125, block_q=128,
                                   block_k=128)
    assert all(rel(heads(a), b) <= 1e-5 for a, b in zip(got, (dq, dk, dv)))


def test_grouped_product_kernels_match_the_masked_loop():
    """Interpret mode: moe_gmm / moe_tgmm against the loop over experts, on
    a routing that leaves one expert held without a token."""
    key = jax.random.PRNGKey(0)
    N, D, F, held = 96, 128, 256, 4
    x = jax.random.normal(key, (N, D))
    idx = jnp.asarray(np.random.default_rng(0).integers(0, 8, (N, 2)),
                      jnp.int32)
    idx = jnp.where(idx == 3, 0, idx)            # expert 3 (held) stays empty
    weights = jax.random.uniform(jax.random.fold_in(key, 1), (N, 2))
    ws = [jax.random.normal(jax.random.fold_in(key, i), s) * s[1] ** -0.5
          for i, s in ((2, (held, D, F)), (3, (held, D, F)),
                       (4, (held, F, D)))]
    counts, order = M.count_assignments(idx, first_expert=2, held=held)
    g = M.group_assignments(counts, order, tm=16,
                            rows=M.buffer_rows(N, 2, 8, held, 16)[1])
    assert int(g.counts[1]) == 0

    def run(kernels):
        return jax.value_and_grad(
            lambda x, wt, w1, w3, w2: jnp.sum(jnp.square(M.grouped_expert_mlp(
                x, wt, g, w1, w3, w2, tm=16, kernels=kernels))),
            argnums=(0, 1, 2, 3, 4))(x, weights, *ws)

    (want, want_g), (got, got_g) = run(False), run(True)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert all(rel(a, b) <= 1e-5 for a, b in zip(got_g, want_g))


# -- through the trainer ---------------------------------------------------------


def test_trainer_feeds_the_routing_counters(own_registry, ref):
    from paddle_tpu.obs import get_registry
    from paddle_tpu.param.optimizers import Adam
    from paddle_tpu.trainer import SGDTrainer

    def assigned():
        series = get_registry().snapshot().get("moe_assignments", {}).get(
            "series", [])
        return {(s["labels"]["layer"], s["labels"]["expert"]): s["value"]
                for s in series}

    before = assigned()
    cost, extras = build(CFG)
    trainer = SGDTrainer(cost, Adam(learning_rate=1e-3), extra_outputs=extras)
    batches = [feed(i) for i in range(3)]
    trainer.train(lambda: iter(batches), num_passes=1)
    after = assigned()
    gained = {k: after[k] - before.get(k, 0) for k in after}
    assert sorted(gained) == [(f"moe{i}", str(e)) for i in (1, 2, 3, 4)
                              for e in (2, 3)]
    # what the last step's extras say is what the last step added
    last = {k: np.asarray(v) for k, v in trainer._last_extras.items()
            if k.endswith("_load")}
    assert all(v.sum() <= B * T * 2 for v in last.values())
    total = sum(gained.values())
    assert 0 < total <= 3 * 4 * B * T * 2
    dropped = get_registry().snapshot()["moe_uncomputed_assignments"]
    assert sum(s["value"] for s in dropped["series"]) == 0
