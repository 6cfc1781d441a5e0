"""Nemotron-H's block (PR 43): the Mamba-2 mixer, attention with no QK-norm
and no rotary embedding, sigmoid routing over experts of two matrices and a
squared ReLU beside a shared expert of the same form, each alone and as the
whole ``nemotron_h_net`` (a stack whose layers are ONE sub-block each),
against the plain reference of benchmark/reference on seeded weights (loss,
every gradient leaf, three steps of Adam through ``SGDTrainer``); the share
test with the shared expert counted once; recomputation blocks;
``_refuse_packed``.  (That the three older models are bit for bit what they
were is tests/test_qwen3_next.py's, Qwen3-Next now among them.)

The reference computes the recurrence token by token and the program in
chunks of 128 (ops/ssd_scan.py), so the two share no algebra.  Program and
reference are both float32 here, so they differ by rounding and the order of
sums only.  A layer alone holds tests/test_qwen3_next.py's tolerances
(gradients 2e-4 of the leaf's norm, losses 1e-5).  The whole model's
gradients are held to 1e-3 (4e-4 read, at the first mixer's leaves): the
chunked form takes DIFFERENCES of the running sums of ``dt A``, which reach
-1000 inside a chunk of 128 at the seeded decays, so a decay factor carries
6e-5 of float32 rounding that the reference, which multiplies token by
token, does not have, and nine blocks of backward pass lie between the loss
and layer 0.  (On the chip the operands are bf16, rounding 4e-3: the cell's
limits file has those readings.)
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.nn as nn
from paddle_tpu.models import decoder_stack, nemotron_h_net

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import correct, manifest  # noqa: E402

GRAD_TOL, MODEL_GRAD_TOL, LOSS_TOL = 2e-4, 1e-3, 1e-5
PATTERN = "MEMEM*EME"            # the cell's nine layers
#: hidden 64; mixers of 4 heads of 16 in 2 groups with a state of 32;
#: attention of 4 heads of 16 over 2 key-value heads; 8 experts of 48 with 2
#: held, top 3, a shared expert of 32; T 256 = two chunks of the scan
CFG = dict(
    hybrid_override_pattern=PATTERN, num_hidden_layers=len(PATTERN),
    hidden_size=64, mamba_num_heads=4, mamba_head_dim=16, n_groups=2,
    ssm_state_size=32, conv_kernel=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=32, router_outputs=8,
    n_routed_experts=2, first_expert=2, num_experts_per_tok=3,
    routed_scaling_factor=2.5, norm_topk_prob=True, layer_norm_epsilon=1e-5,
    vocab_size=50)
B, T = 2, 256
NAME = "nemotron-3-nano-30b-a3b-ep16"


@pytest.fixture(scope="module")
def ref():
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "reference", NAME + ".py"), "nemotron_ref")


@pytest.fixture(scope="module")
def program_file():
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "programs", NAME + ".py"), "nemotron_program")


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


MAMBA, ATTN, MOE = (0, 2, 4, 7), (5,), (1, 3, 6, 8)
LEAVES = sorted(
    ["_emb.w0", "_norm_out.w", "_cost.w"]
    + [f"_norm{i}.w" for i in range(9)]
    + [f"_mamba{i}.{p}" for i in MAMBA
       for p in ("w_in", "kernel", "conv_bias", "a_log", "dt_bias", "d",
                 "norm", "w_out")]
    + [f"_attn{i}.{p}" for i in ATTN for p in ("wq", "wk", "wv", "wo")]
    + [f"_moe{i}.{p}" for i in MOE
       for p in ("router", "expert_bias", "w1", "w2", "shared_w1",
                 "shared_w2")])


def test_model_loss_matches_the_reference(model_grads, ref):
    (loss, grads), (want, want_grads) = model_grads
    assert sorted(grads) == LEAVES == sorted(ref.param_shapes(CFG))
    assert abs(float(loss) - float(want)) <= LOSS_TOL * abs(float(want))


@pytest.mark.parametrize("leaf", LEAVES)
def test_model_gradient_matches_the_reference(model_grads, leaf):
    (_, grads), (_, want) = model_grads
    if leaf.endswith(".expert_bias"):
        # enters the selection only: exactly zero on both sides
        assert not np.asarray(grads[leaf]).any()
        assert not np.asarray(want[leaf]).any()
        return
    assert float(jnp.linalg.norm(want[leaf])) > 0
    assert rel(grads[leaf], want[leaf]) <= MODEL_GRAD_TOL


def test_three_adam_steps_through_the_trainer_match_the_reference(
        ref, program_file, own_registry):
    """``SGDTrainer`` over the model, as the benchmark's runner drives it:
    the losses of the first three steps and every leaf's change after them
    against the reference's own Adam; the runner asks the program for
    ``moe<i>`` of every layer and gets the four expert layers'."""
    from paddle_tpu.utils.flags import FLAGS

    runner = manifest.runner("trainer_loop_large")
    cfg = dict(CFG, recompute_layers=True, optimizer={
        "kind": "adam", "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.999,
        "epsilon": 1e-8}, num_dense_layers=0)
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
    assert numbers["grad_diff_median"] <= MODEL_GRAD_TOL
    assert trainer.bad_steps_total == 0
    assert program_file.uncomputed_assignments() == 0
    load = program_file.expert_load([f"moe{i}" for i in range(9)])
    assert sorted(load) == ["moe1", "moe3", "moe6", "moe8"]
    assert all(len(v) == 2 and sum(v) > 0 for v in load.values())


def test_recompute_blocks_change_no_number_and_are_in_the_program(
        ref, program_file):
    from paddle_tpu.analysis.jaxpr_walk import walk_eqns

    params = correct.init_params(ref, CFG, 5)
    batch = feed(1)
    values, prims = [], []
    for recompute in (list(range(9)), False):
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
    assert all(rel(ga[k], gb[k]) <= 1e-4 for k in ga if ga[k].any())
    assert len(prims[0]) >= 9 and not prims[1]    # a block a published layer


def test_the_model_is_the_one_stack_builder_with_one_sub_block_a_layer(
        monkeypatch):
    """``nemotron_h_net`` builds nothing itself but its two mixers; every
    layer has ONE norm and ONE residual add, named by the published index;
    two mixers stand side by side at ``M*`` and the model ends ``ME``."""
    import paddle_tpu.models.nemotron_h as NH

    seen = []

    def spy(vocab_size, **kw):
        seen.append((sorted(kw["mixers"]), kw["layer_types"],
                     kw["ffn_layer_type"], kw["num_dense_layers"],
                     kw["tie_head"], kw["expert_act"],
                     kw["routed_scaling_factor"]))
        return decoder_stack(vocab_size, **kw)

    monkeypatch.setattr(NH, "decoder_stack", spy)
    nn.reset_naming()
    keys = ("hybrid_override_pattern", "hidden_size", "mamba_num_heads",
            "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "moe_intermediate_size", "moe_shared_expert_intermediate_size",
            "num_experts_per_tok", "routed_scaling_factor")
    cost, extras = nemotron_h_net(50, n_routed_experts=8,
                                  **{k: CFG[k] for k in keys})
    kinds = ["mamba", "moe", "mamba", "moe", "mamba", "attention", "moe",
             "mamba", "moe"]
    assert seen == [(["attention", "mamba"], kinds, "moe", 0, False, "relu2",
                     2.5)]
    layers = nn.Topology([cost] + extras).layers
    names = {layer.name for layer in layers}
    assert {"mamba0", "moe1", "mamba2", "moe3", "mamba4", "attn5", "moe6",
            "mamba7", "moe8"} <= names
    assert {f"norm{i}" for i in range(9)} | {f"res{i}" for i in range(9)} \
        <= names
    assert not any(n.startswith(("mlp", "norm_op", "norm_ffn", "res_op",
                                 "res_ffn")) for n in names)
    assert len(extras) == 8                  # two counters an expert layer
    blocks = {}
    for layer in layers:
        if "remat" in layer.meta:
            blocks.setdefault(layer.meta["remat"], []).append(layer.name)
    assert sorted(blocks) == [f"layer{i}" for i in range(9)]
    assert blocks["layer5"] == ["norm5", "attn5", "res5"]
    assert sorted(blocks["layer1"]) == sorted([
        "norm1", "moe1", "moe1_load", "moe1_uncomputed", "res1"])
    with pytest.raises(ValueError, match="hybrid_override_pattern holds"):
        nemotron_h_net(50, n_routed_experts=8,
                       **dict({k: CFG[k] for k in keys},
                              hybrid_override_pattern="MXE"))


# -- layer by layer ------------------------------------------------------------


def _one_layer(kind):
    """(layer node over a [B, T, 64] sequence feed, the reference's function
    of (params, x) for it)."""
    nn.reset_naming()
    x = nn.data("x", size=CFG["hidden_size"], is_seq=True)
    if kind == "mamba2_mixer":
        return nn.mamba2_mixer(
            x, num_heads=4, head_dim=16, n_groups=2, state_size=32,
            conv_kernel_size=4, norm_eps=1e-5, name="mamba0"), \
            lambda ref, p, v: ref.mamba2(CFG, p, "_mamba0", v)
    if kind == "attention_without_qk_norm_or_rotary":
        return nn.causal_self_attention(
            x, num_heads=4, num_kv_heads=2, head_dim=16, qk_norm=False,
            rotary=False, name="attn5"), \
            lambda ref, p, v: ref.attention(CFG, p, "_attn5", v)
    if kind == "rms_norm":
        return nn.rms_norm(x, eps=1e-5, name="norm0"), \
            lambda ref, p, v: ref.rms_norm(v, p["_norm0.w"], 1e-5)
    assert kind == "relu2_experts_with_a_shared_expert"
    return nn.expert_mlp(x, 48, num_experts=8, experts_held=(2, 2), top_k=3,
                         routed_scaling_factor=2.5, shared_size=32,
                         expert_act="relu2", name="moe1"), \
        lambda ref, p, v: ref.expert_layer(CFG, p, "_moe1", v)


@pytest.mark.parametrize("kind", [
    "mamba2_mixer", "attention_without_qk_norm_or_rotary", "rms_norm",
    "relu2_experts_with_a_shared_expert"])
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
    for k in got_g[0]:
        if k.endswith(".expert_bias"):
            assert not np.asarray(got_g[0][k]).any()
            continue
        assert np.asarray(want_g[0][k]).any(), k
        assert rel(got_g[0][k], want_g[0][k]) <= GRAD_TOL, k
    assert rel(got_g[1], want_g[1]) <= GRAD_TOL


def test_layers_hold_the_leaves_the_reference_names(ref):
    node, _ = _one_layer("mamba2_mixer")
    assert {k: s.shape for k, s in nn.Topology(node).param_specs.items()} == {
        "_mamba0.w_in": (64, 64 + 64 + 64 + 64 + 4),
        "_mamba0.kernel": (4, 192), "_mamba0.conv_bias": (192,),
        "_mamba0.a_log": (4,), "_mamba0.dt_bias": (4,), "_mamba0.d": (4,),
        "_mamba0.norm": (64,), "_mamba0.w_out": (64, 64)}
    node, _ = _one_layer("attention_without_qk_norm_or_rotary")
    assert sorted(nn.Topology(node).param_specs) == [
        "_attn5.wk", "_attn5.wo", "_attn5.wq", "_attn5.wv"]
    node, _ = _one_layer("relu2_experts_with_a_shared_expert")
    assert {k: s.shape for k, s in nn.Topology(node).param_specs.items()} == {
        "_moe1.router": (64, 8), "_moe1.expert_bias": (8,),
        "_moe1.w1": (2, 64, 48), "_moe1.w2": (2, 48, 64),
        "_moe1.shared_w1": (64, 32), "_moe1.shared_w2": (32, 64)}
    with pytest.raises(Exception, match="whole groups"):
        nn.mamba2_mixer(nn.data("y", size=64, is_seq=True), num_heads=3,
                        head_dim=16, n_groups=2, state_size=32)
    with pytest.raises(Exception, match="unknown expert_act"):
        nn.expert_mlp(nn.data("z", size=16), 8, num_experts=8, top_k=3,
                      expert_act="gelu")


def test_attention_without_positions_ignores_none_but_order(ref):
    """No rotary embedding: the layer's only sense of position is the causal
    mask, so two rows that agree up to token ``t`` agree there."""
    node, _ = _one_layer("attention_without_qk_norm_or_rotary")
    topo = nn.Topology(node)
    params = some_params(ref, CFG, list(topo.param_specs), 2)
    x = np.random.default_rng(1).standard_normal((B, T, 64)).astype(np.float32)
    moved = x.copy()
    moved[:, 100:] += 1.0
    lengths = np.full((B,), T, np.int32)
    a = topo.apply(params, {}, {"x": (x, lengths)})[0][node.name].value
    b = topo.apply(params, {}, {"x": (moved, lengths)})[0][node.name].value
    np.testing.assert_allclose(a[:, :100], b[:, :100], atol=1e-5)
    assert rel(a[:, 100:], b[:, 100:]) > 1e-3


def test_mixer_carries_memory_across_chunks(ref):
    """Moving the first token's input moves the last token's output, 255
    tokens and one chunk boundary later (the convolution reaches 3 back):
    the state crosses chunks."""
    node, _ = _one_layer("mamba2_mixer")
    topo = nn.Topology(node)
    params = some_params(ref, CFG, list(topo.param_specs), 2)
    params["_mamba0.a_log"] = jnp.full((4,), -6.0)       # decays near 1
    x = np.random.default_rng(1).standard_normal((B, T, 64)).astype(np.float32)
    lengths = np.full((B,), T, np.int32)
    moved = x.copy()
    moved[:, 0] += 1.0
    a = topo.apply(params, {}, {"x": (x, lengths)})[0][node.name].value
    b = topo.apply(params, {}, {"x": (moved, lengths)})[0][node.name].value
    assert rel(a[:, -1], b[:, -1]) > 1e-4


def test_mixer_refuses_packed_rows(ref):
    from paddle_tpu.nn.graph import Act
    from paddle_tpu.utils.error import ConfigError

    node, _ = _one_layer("mamba2_mixer")
    topo = nn.Topology(node)
    params = some_params(ref, CFG, list(topo.param_specs), 2)
    x = jnp.zeros((B, T, 64))
    packed = Act(value=x, lengths=jnp.full((B,), T), mask=jnp.ones((B, T)),
                 state={"seg_ids": jnp.zeros((B, T), jnp.int32)})
    with pytest.raises(ConfigError, match="packed"):
        node.forward(None, params, packed)


# -- the share -----------------------------------------------------------------


def test_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once(ref):
    """The routed parts that the four shares of two experts give (the cell's
    sixteen of eight, at a toy size), plus the shared expert that every chip
    computes alike, counted ONCE, add up to what the uncut reference (all
    eight experts, the shared expert) gives."""
    cfg = dict(CFG, n_routed_experts=8, first_expert=0)
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
                experts_held=(first, 2), top_k=3, routed_scaling_factor=2.5,
                shared_size=32, expert_act="relu2", name="moe1")
            share = dict(whole)
            for leaf in ("w1", "w2"):
                share[f"_moe1.{leaf}"] = whole[f"_moe1.{leaf}"][first:first + 2]
            out = nn.Topology(node).apply(
                share, {}, {"x": (x, lengths)})[0][node.name]
            total = total + (out.value - shared)     # this chip's routed part
            load += list(np.asarray(out.state["expert_load"]))
            assert int(out.state["uncomputed"]) == 0
    assert float(jnp.linalg.norm(shared)) > 0.1 * float(jnp.linalg.norm(want))
    assert rel(total + shared, want) <= 1e-5
    assert sum(load) == B * T * 3       # every choice landed on one chip


# -- the grouped products at a width that is no multiple of 128 ---------------


@pytest.mark.parametrize("gated", [False, True])
def test_grouped_kernels_with_a_masked_edge_match_the_masked_loop(gated):
    """An expert width of 576 = 9 x 64 (as 1856 = 29 x 64, no multiple of
    128): the kernels (interpret mode here) tile it by 512 with the last
    block hanging over the edge where it is a result, and take it whole
    where it is summed over; values and every gradient are the masked XLA
    loop's."""
    from paddle_tpu.ops import moe as M

    D, F, N, k, held, E, tm = 128, 576, 64, 2, 3, 4, 8
    assert M._largest_tile(F, 512) == 512 and F % 512
    r = np.random.default_rng(0)
    x = jnp.asarray(r.standard_normal((N, D)).astype(np.float32))
    w1 = jnp.asarray(r.standard_normal((held, D, F)).astype(np.float32)) * 0.1
    w3 = (jnp.asarray(r.standard_normal((held, D, F)).astype(np.float32))
          * 0.1 if gated else None)
    w2 = jnp.asarray(r.standard_normal((held, F, D)).astype(np.float32)) * 0.1
    idx = jnp.asarray(r.integers(0, E, (N, k)).astype(np.int32))
    wts = jnp.asarray(r.random((N, k)).astype(np.float32))

    def loss(kernels):
        def f(x, wts, w1, w2, *w3):
            y = M.expert_layer(x, idx, wts, w1, w3[0] if w3 else None, w2,
                               num_experts=E, first_expert=0, tm=tm,
                               kernels=kernels, expert_act=(
                                   "gated_silu" if gated else "relu2"))[0]
            return jnp.sum(jnp.square(y))
        return f

    args = (x, wts, w1, w2) + ((w3,) if gated else ())
    nums = tuple(range(len(args)))
    want, gw = jax.value_and_grad(loss(False), argnums=nums)(*args)
    got, gg = jax.value_and_grad(loss(True), argnums=nums)(*args)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(gg, gw):
        assert rel(a, b) <= 1e-5
