"""Qwen3-Next-80B-A3B's block (PR 41): the gated delta net, the gated full
attention with a partial rotary embedding, softmax routing with a gated shared
expert and the ``1 + w`` norms, each alone and as the whole
``qwen3_next_net``, against the plain reference of benchmark/reference on
seeded weights (loss, every gradient leaf, three steps of Adam through
``SGDTrainer``); the share test with the shared expert counted once; the
defaults that leave the two older models what they were; recomputation
blocks; ``_refuse_packed``.

The reference computes the delta rule token by token and the program in
chunks of 64 (ops/delta_rule.py), so the two share no algebra.  Tolerances
as tests/test_lfm2.py: program and reference are both float32 here, so they
differ by rounding and the order of sums only (gradients 2e-4 of the leaf's
norm, losses 1e-5); the chunk's 64 x 64 solve adds nothing visible to that.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.nn as nn
from paddle_tpu.models import (decoder_stack, kanana2_moe_net, lfm2_moe_net,
                               qwen3_next_net)
from paddle_tpu.ops import moe as M

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import correct, manifest  # noqa: E402

GRAD_TOL, LOSS_TOL = 2e-4, 1e-5
#: hidden 64; delta nets of 2 key and 4 value heads of 16; attention of 4
#: heads of 16 over 2 key-value heads, rotary on the first 4 channels; 8
#: experts of 48 with 2 held, top 3, a shared expert of 32; one period of 4
#: layers; T 128 = two chunks of the scan
CFG = dict(
    hidden_size=64, full_attention_interval=4, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    partial_rotary_factor=0.25, moe_intermediate_size=48,
    shared_expert_intermediate_size=32, router_outputs=8, num_experts=2,
    first_expert=2, num_experts_per_tok=3, vocab_size=50,
    num_hidden_layers=4, rms_norm_eps=1e-6, rope_theta=10000000,
    norm_topk_prob=True)
B, T = 2, 128


@pytest.fixture(scope="module")
def ref():
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "reference", "qwen3-next-80b-a3b-ep32.py"),
        "qwen3next_ref")


@pytest.fixture(scope="module")
def program_file():
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "programs", "qwen3-next-80b-a3b-ep32.py"),
        "qwen3next_program")


def build(program_file, cfg, recompute_layers=True):
    return program_file.net(dict(cfg, recompute_layers=recompute_layers))


def feed(seed=0, t=T):
    ids = np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], (B, t + 1)).astype(np.int32)
    lengths = np.full((B,), t, np.int32)
    return {"tokens": (ids[:, :-1], lengths),
            "next_tokens": (ids[:, 1:], lengths)}


def rel(got, want):
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


def some_params(ref, cfg, names, seed):
    """Seeded weights for the leaves ``names`` alone."""
    shapes = ref.param_shapes(cfg)
    return correct.init_params(
        type("R", (), {"param_shapes": staticmethod(
            lambda c: {k: shapes[k] for k in names})}), cfg, seed)


# -- the whole model ---------------------------------------------------------


@pytest.fixture(scope="module")
def model_grads(ref, program_file):
    cost, extras = build(program_file, CFG)
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
        want = jax.jit(jax.value_and_grad(reference))(params)
        got = jax.jit(jax.value_and_grad(program))(params)
    return got, want


LEAVES = sorted(
    ["_emb.w0", "_norm_out.w", "_cost.w"]
    + [f"_norm_{w}{i}.w" for i in range(4) for w in ("op", "ffn")]
    + [f"_gdn{i}.{p}" for i in range(3)
       for p in ("w_qkvz", "w_ba", "kernel", "a_log", "dt_bias", "norm",
                 "w_out")]
    + [f"_attn3.{p}" for p in ("wq", "wk", "wv", "wo", "q_norm", "k_norm")]
    + [f"_moe{i}.{p}" for i in range(4)
       for p in ("router", "w1", "w2", "w3", "shared_w1", "shared_w2",
                 "shared_w3", "shared_gate")])


def test_model_loss_matches_the_reference(model_grads, ref):
    (loss, grads), (want, want_grads) = model_grads
    assert sorted(grads) == LEAVES == sorted(ref.param_shapes(CFG))
    assert abs(float(loss) - float(want)) <= LOSS_TOL * abs(float(want))


@pytest.mark.parametrize("leaf", LEAVES)
def test_model_gradient_matches_the_reference(model_grads, leaf):
    (_, grads), (_, want) = model_grads
    assert float(jnp.linalg.norm(want[leaf])) > 0
    assert rel(grads[leaf], want[leaf]) <= GRAD_TOL


def test_three_adam_steps_through_the_trainer_match_the_reference(
        ref, program_file, own_registry):
    """``SGDTrainer`` over the model, as the benchmark's runner drives it:
    the losses of the first three steps and every leaf's change after them
    against the reference's own Adam."""
    from paddle_tpu.utils.flags import FLAGS

    runner = manifest.runner("trainer_loop_large")
    cfg = dict(CFG, recompute_layers=True, optimizer={
        "kind": "adam", "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.999,
        "epsilon": 1e-8}, layer_types=["linear_attention"] * 3
        + ["full_attention"], num_dense_layers=0)
    batches = [feed(s) for s in (1, 2, 3)]
    kept = {k: getattr(FLAGS, k) for k in (
        "prefetch_depth", "guard_nonfinite", "obs_timeline", "save_dir",
        "log_period")}
    try:
        expected = runner.reference_steps(ref, cfg, 5, batches)
        trainer = program_file.trainer(cfg, {"prefetch_depth": 2},
                                       correct.init_params(ref, cfg, 5))
        got = runner.first_steps(trainer, ref, cfg, 5, batches, expected)
    finally:
        for k, v in kept.items():
            setattr(FLAGS, k, v)
    numbers = runner.compare(got, expected)
    assert numbers["loss_gap"] <= 1e-4
    assert numbers["delta_norm_gap"] <= 1e-3
    assert numbers["grad_diff_median"] <= GRAD_TOL
    assert trainer.bad_steps_total == 0
    assert program_file.uncomputed_assignments() == 0


def test_recompute_blocks_change_no_number_and_are_in_the_program(
        ref, program_file):
    from paddle_tpu.analysis.jaxpr_walk import walk_eqns

    params = correct.init_params(ref, CFG, 5)
    batch = feed(1)
    values, prims = [], []
    for recompute in ([0, 1, 2, 3], False):
        cost, _ = build(program_file, CFG, recompute_layers=recompute)
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
    # 3e-5 read: XLA fuses the recomputed scan's sums in another order
    assert all(rel(ga[k], gb[k]) <= 1e-4 for k in ga if ga[k].any())
    assert len(prims[0]) >= 4 and not prims[1]    # a block a layer marked


def test_the_model_is_the_one_stack_builder_with_two_mixers(monkeypatch):
    """``qwen3_next_net`` builds nothing itself but its two mixers, has no
    dense layer, and names its layers ``gdn<i>`` and ``attn<i>``."""
    import paddle_tpu.models.qwen3_next as Q

    seen = []

    def spy(vocab_size, **kw):
        seen.append((sorted(kw["mixers"]), kw["layer_types"],
                     kw["num_dense_layers"], kw["tie_head"], kw["scoring"],
                     kw["shared_gate"], kw["zero_centered_norm"]))
        return decoder_stack(vocab_size, **kw)

    monkeypatch.setattr(Q, "decoder_stack", spy)
    nn.reset_naming()
    keys = ("hidden_size", "num_hidden_layers", "full_attention_interval",
            "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim", "num_attention_heads",
            "num_key_value_heads", "head_dim", "partial_rotary_factor",
            "moe_intermediate_size", "shared_expert_intermediate_size",
            "num_experts_per_tok")
    cost, extras = qwen3_next_net(50, num_experts=8,
                                  **{k: CFG[k] for k in keys})
    assert seen == [(["full_attention", "linear_attention"],
                     ["linear_attention"] * 3 + ["full_attention"], 0, False,
                     "softmax", True, True)]
    names = {layer.name for layer in nn.Topology([cost] + extras).layers}
    assert {"gdn0", "gdn1", "gdn2", "attn3", "moe0", "moe3"} <= names
    assert not any(n.startswith("mlp") for n in names)
    assert len(extras) == 8                       # two counters a layer


# -- layer by layer ------------------------------------------------------------


def _one_layer(kind):
    """(layer node over a [B, T, 64] sequence feed, the reference's function
    of (params, x) for it)."""
    nn.reset_naming()
    x = nn.data("x", size=CFG["hidden_size"], is_seq=True)
    if kind == "gated_delta_net":
        return nn.gated_delta_net(
            x, num_key_heads=2, num_value_heads=4, key_head_dim=16,
            value_head_dim=16, conv_kernel_size=4, norm_eps=1e-6,
            name="gdn0"), \
            lambda ref, p, v: ref.gated_delta_net(CFG, p, "_gdn0", v)
    if kind == "gated_attention":
        return nn.causal_self_attention(
            x, num_heads=4, num_kv_heads=2, head_dim=16, rope_theta=1e7,
            norm_eps=1e-6, output_gate=True, rotary_dim=4,
            zero_centered_norm=True, name="attn3"), \
            lambda ref, p, v: ref.gated_attention(CFG, p, "_attn3", v)
    if kind == "zero_centered_rms_norm":
        return nn.rms_norm(x, eps=1e-6, zero_centered=True, name="norm_op0"), \
            lambda ref, p, v: ref.rms_norm(v, p["_norm_op0.w"], 1e-6)
    assert kind == "softmax_routed_experts_with_a_gated_shared_expert"
    return nn.expert_mlp(x, 48, num_experts=8, experts_held=(2, 2), top_k=3,
                         shared_size=32, scoring="softmax", shared_gate=True,
                         name="moe1"), \
        lambda ref, p, v: ref.expert_layer(CFG, p, "_moe1", v)


@pytest.mark.parametrize("kind", [
    "gated_delta_net", "gated_attention", "zero_centered_rms_norm",
    "softmax_routed_experts_with_a_gated_shared_expert"])
def test_layer_matches_the_reference(kind, ref):
    node, plain = _one_layer(kind)
    topo = nn.Topology(node)
    params = some_params(ref, CFG, list(topo.param_specs), 11)
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
    assert sorted(got_g[0]) == sorted(want_g[0])
    for g, wg in zip(jax.tree_util.tree_leaves(got_g),
                     jax.tree_util.tree_leaves(want_g)):
        assert np.asarray(wg).any()
        assert rel(g, wg) <= GRAD_TOL


def test_gated_delta_net_holds_the_leaves_the_reference_names(ref):
    node, _ = _one_layer("gated_delta_net")
    assert {k: s.shape for k, s in nn.Topology(node).param_specs.items()} == {
        "_gdn0.w_qkvz": (64, 32 + 32 + 64 + 64), "_gdn0.w_ba": (64, 8),
        "_gdn0.kernel": (4, 128), "_gdn0.a_log": (4,), "_gdn0.dt_bias": (4,),
        "_gdn0.norm": (16,), "_gdn0.w_out": (64, 64)}
    with pytest.raises(Exception, match="whole groups"):
        nn.gated_delta_net(nn.data("y", size=64, is_seq=True),
                           num_key_heads=3, num_value_heads=4,
                           key_head_dim=16, value_head_dim=16)


def test_gated_delta_net_carries_memory_across_chunks(ref):
    """Moving the first token's input moves the last token's output, 127
    tokens and one chunk boundary later (the convolution reaches 3 back):
    the state crosses chunks."""
    node, _ = _one_layer("gated_delta_net")
    topo = nn.Topology(node)
    params = some_params(ref, CFG, list(topo.param_specs), 2)
    params["_gdn0.a_log"] = jnp.full((4,), -4.0)       # decays near 1
    x = np.random.default_rng(1).standard_normal((B, T, 64)).astype(np.float32)
    lengths = np.full((B,), T, np.int32)
    moved = x.copy()
    moved[:, 0] += 1.0
    a = topo.apply(params, {}, {"x": (x, lengths)})[0][node.name].value
    b = topo.apply(params, {}, {"x": (moved, lengths)})[0][node.name].value
    assert rel(a[:, -1], b[:, -1]) > 1e-4


def test_gated_delta_net_refuses_packed_rows(ref):
    from paddle_tpu.nn.graph import Act
    from paddle_tpu.utils.error import ConfigError

    node, _ = _one_layer("gated_delta_net")
    topo = nn.Topology(node)
    params = some_params(ref, CFG, list(topo.param_specs), 2)
    x = jnp.zeros((B, T, 64))
    packed = Act(value=x, lengths=jnp.full((B,), T), mask=jnp.ones((B, T)),
                 state={"seg_ids": jnp.zeros((B, T), jnp.int32)})
    with pytest.raises(ConfigError, match="packed"):
        node.forward(None, params, packed)


def test_softmax_routing_has_no_bias_and_no_epsilon():
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (32, 16)).astype(np.float32))
    w = jnp.asarray(np.random.default_rng(1).standard_normal(
        (16, 8)).astype(np.float32))
    idx, weights = M.route_tokens(x, w, None, top_k=3, norm_topk=True,
                                  scaling=1.0, scoring="softmax")
    probs = jax.nn.softmax(x @ w, -1)
    want_p, want_i = jax.lax.top_k(probs, 3)
    np.testing.assert_array_equal(idx, want_i)
    np.testing.assert_allclose(weights, want_p / want_p.sum(-1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    with pytest.raises(ValueError, match="unknown scoring"):
        M.route_tokens(x, w, None, top_k=3, norm_topk=True, scaling=1.0,
                       scoring="tanh")
    nn.reset_naming()
    node = nn.expert_mlp(nn.data("x", size=16), 8, num_experts=8, top_k=3,
                         scoring="softmax", name="m")
    assert "_m.expert_bias" not in nn.Topology(node).param_specs
    with pytest.raises(Exception, match="gate without a shared expert"):
        nn.expert_mlp(nn.data("y", size=16), 8, num_experts=8, top_k=3,
                      shared_gate=True)


# -- the share -----------------------------------------------------------------


def test_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once(ref):
    """The routed parts that the four shares of two experts give, plus the
    gated shared expert that every chip computes alike, counted ONCE, add up
    to what the uncut reference (all eight experts, the shared expert)
    gives."""
    cfg = dict(CFG, num_experts=8, first_expert=0)
    names = [k for k in ref.param_shapes(cfg) if k.startswith("_moe1.")]
    whole = some_params(ref, cfg, names, 9)
    x = np.random.default_rng(8).standard_normal((B, T, 64)).astype(np.float32)
    lengths = np.full((B,), T, np.int32)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(cfg, whole, "_moe1", jnp.asarray(x))
        shared = ref.shared_expert(whole, "_moe1", jnp.asarray(x))
        total, load = 0.0, []
        for first in range(0, 8, 2):
            nn.reset_naming()
            node = nn.expert_mlp(
                nn.data("x", size=64, is_seq=True), 48, num_experts=8,
                experts_held=(first, 2), top_k=3, shared_size=32,
                scoring="softmax", shared_gate=True, name="moe1")
            share = dict(whole)
            for leaf in ("w1", "w3", "w2"):
                share[f"_moe1.{leaf}"] = whole[f"_moe1.{leaf}"][first:first + 2]
            out = nn.Topology(node).apply(
                share, {}, {"x": (x, lengths)})[0][node.name]
            total = total + (out.value - shared)     # this chip's routed part
            load += list(np.asarray(out.state["expert_load"]))
            assert int(out.state["uncomputed"]) == 0
    assert float(jnp.linalg.norm(shared)) > 0.1 * float(jnp.linalg.norm(want))
    assert rel(total + shared, want) <= 1e-5
    assert sum(load) == B * T * 3       # every choice landed on one chip


# -- the two older models are what they were -----------------------------------

#: loss and the sum of every gradient's absolute values on seeded weights,
#: read with this file's ``_older_model`` on the parent commit of the PR that
#: added the model after them (3c5f171 for the first two; 2fd814c, PR 43's
#: parent, for Qwen3-Next itself).  Qwen3-Next's gradient sum was read again
#: in PR 49 (5852.37060546875 before): the router's chosen scores are picked
#: by comparison, the same floats op by op (``jax.disable_jit()`` reads
#: 5852.40966796875 on both sides, tests/test_moe_index.py holds the router
#: to the bit), and under ``jit`` XLA:CPU fuses the softmax's backward with
#: the pick and rounds its sums in another order
#: LFM2's gradient sum was read again in PR 51 (226.93792724609375 before):
#: the rotary embedding turns a whole head in one pass, the same floats op by
#: op (``jax.disable_jit()`` reads 226.93792724609375 on both sides,
#: tests/test_rotary.py holds the function to the bit), and under ``jit``
#: XLA:CPU fuses its multiplies and adds differently
PARENT = {"lfm2": (3.9143424034118652, 226.9379119873047),
          "kanana2": (4.320387840270996, 1011.89697265625),
          "qwen3next": (4.521244049072266, 5852.3818359375)}


def _older_model(which):
    nn.reset_naming()
    if which == "lfm2":
        cost, _ = lfm2_moe_net(
            50, hidden_size=64, layer_types=["conv", "full_attention", "conv"],
            num_dense_layers=1, intermediate_size=96,
            moe_intermediate_size=48, num_experts=8, num_experts_per_tok=2,
            num_attention_heads=4, num_key_value_heads=2)
    elif which == "qwen3next":
        cost, _ = qwen3_next_net(
            50, hidden_size=64, num_hidden_layers=4,
            full_attention_interval=4, linear_num_key_heads=2,
            linear_num_value_heads=4, linear_key_head_dim=16,
            linear_value_head_dim=16, linear_conv_kernel_dim=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            partial_rotary_factor=0.25, moe_intermediate_size=48,
            shared_expert_intermediate_size=32, num_experts=8,
            num_experts_per_tok=3)
    else:
        cost, _ = kanana2_moe_net(
            50, hidden_size=64, num_hidden_layers=2, first_k_dense_replace=1,
            intermediate_size=96, moe_intermediate_size=48,
            n_routed_experts=8, num_experts_per_tok=3, n_shared_experts=2,
            num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16)
    topo = nn.Topology(cost)
    params, _ = topo.init(jax.random.PRNGKey(7))
    batch = feed(4, t=64)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: topo.apply(p, {}, batch, train=True)[0]["cost"].value))(
            params)
    return float(loss), float(sum(jnp.sum(jnp.abs(g))
                                  for g in jax.tree_util.tree_leaves(grads)))


@pytest.mark.parametrize("which", ["lfm2", "kanana2", "qwen3next"])
def test_older_models_are_bit_for_bit_what_they_were(which):
    """The arguments a later model's PR adds (PR 41's, then PR 43's: a layer
    of one sub-block, experts of two matrices, attention without QK-norm or
    rotary, a convolution's bias) default to what the older models ran
    before: their loss and gradients on the CPU are the parent commit's to
    the last bit."""
    assert _older_model(which) == PARENT[which]
