"""Laguna-XS.2 (PR 50): ``laguna_net`` (window and full attention layers that
differ in their head count and their rotary embedding by layer, head-wise
gates, sigmoid routing with a scaling factor beside one shared expert)
against the plain reference of benchmark/reference on seeded weights: loss
and every leaf's gradient, on the XLA walks and on the flash kernels (the
window pair among them) in interpret mode; the layer's new arguments; the
share test of the model-configs guide, section 4; and the older models'
losses and gradients, which are what they were."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.nn as nn
from paddle_tpu.ops import decoder_block as DB
from paddle_tpu.utils.error import ConfigError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import correct, manifest  # noqa: E402

NAME = "laguna-xs.2-ep32"
PATHS = ["xla", "kernels"]
#: hidden 64; a full layer of 6 heads and a window layer of 8, both over 2
#: key-value heads of 16; a window of 48 in a row of 256; YaRN over half a
#: head with an original context of 64 (a quarter of the row: the ramp over
#: pairs 0..3 is live); 8 experts of 48 with 2 held, top 3, one shared of 32
CFG = dict(
    hidden_size=64, num_hidden_layers=3,
    layer_types=["full_attention", "sliding_attention", "full_attention"],
    mlp_layer_types=["dense", "sparse", "sparse"],
    num_attention_heads_per_layer=[6, 8, 6], num_attention_heads=6,
    num_key_value_heads=2, head_dim=16, sliding_window=48,
    rope_parameters={
        "full_attention": dict(
            rope_theta=100.0, rope_type="yarn", factor=4.0,
            original_max_position_embeddings=64, beta_slow=1, beta_fast=4,
            attention_factor=1.2, partial_rotary_factor=0.5),
        "sliding_attention": dict(rope_type="default", rope_theta=10000.0,
                                  partial_rotary_factor=1)},
    gating=True, intermediate_size=96, moe_intermediate_size=48,
    shared_expert_intermediate_size=32, router_outputs=8, num_experts=2,
    first_expert=2, num_experts_per_tok=3, moe_routed_scaling_factor=2.5,
    rms_norm_eps=1e-6, vocab_size=50)
B, T = 2, 256


@pytest.fixture
def path(request, monkeypatch):
    """``kernels``: the gate opens with blocks of 128 for the full layers and
    the window layer alike and the flash kernels run in interpret mode;
    ``xla``: the gate is as the CPU leaves it."""
    if request.param == "kernels":
        monkeypatch.setattr(
            DB, "attention_kernel_blocks",
            lambda T, dh, H, Hkv, dv=None, window=None: (128, 128))
    return request.param


@pytest.fixture(scope="module")
def ref():
    mod = manifest.load_module(os.path.join(
        ROOT, "benchmark", "reference", NAME + ".py"), "laguna_ref")
    mod.QUERY_BLOCK = 64        # four blocks of queries a row
    return mod


@pytest.fixture(scope="module")
def program_file():
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "programs", NAME + ".py"), "laguna_program")


def rel(got, want):
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


def feed(seed=0, t=T):
    ids = np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], (B, t + 1)).astype(np.int32)
    lengths = np.full((B,), t, np.int32)
    return {"tokens": (ids[:, :-1], lengths),
            "next_tokens": (ids[:, 1:], lengths)}


LEAVES = sorted(
    ["_emb.w0", "_norm_out.w", "_cost.w"]
    + [f"_norm_{s}{i}.w" for i in range(3) for s in ("op", "ffn")]
    + [f"_attn{i}.{p}" for i in range(3)
       for p in ("wq", "wk", "wv", "wo", "wg")]
    + [f"_mlp0.{p}" for p in ("w1", "w3", "w2")]
    + [f"_moe{i}.{p}" for i in (1, 2)
       for p in ("router", "w1", "w3", "w2", "shared_w1", "shared_w3",
                 "shared_w2")])


@pytest.mark.parametrize("path", PATHS, indirect=True)
def test_model_matches_the_reference(ref, program_file, path):
    """Loss and every leaf's gradient (no leaf is a head norm or a selection
    bias: the model has neither); the window layer's counter is the band's
    count."""
    cost, extras = program_file.net(dict(CFG, recompute_layers=[0, 1, 2]))
    topo = nn.Topology([cost] + extras)
    params = correct.init_params(ref, CFG, 3)
    assert ({k: v.shape for k, v in topo.init(jax.random.PRNGKey(0))[0].items()}
            == {k: v.shape for k, v in params.items()})
    assert sorted(params) == LEAVES
    batch = feed()

    def program(p):
        outs, _ = topo.apply(p, {}, batch, train=True)
        return outs["cost"].value, {e.name: outs[e.name].value
                                    for e in extras}

    def reference(p):
        total, count = ref.loss_sum(CFG, p, batch)
        return total / count

    with jax.default_matmul_precision("highest"):
        want_loss, want_grads = jax.jit(jax.value_and_grad(reference))(params)
        (loss, counts), grads = jax.jit(
            jax.value_and_grad(program, has_aux=True))(params)
    assert abs(float(loss) - float(want_loss)) <= 1e-5 * float(want_loss)
    for leaf in LEAVES:
        assert rel(grads[leaf], want_grads[leaf]) <= 1e-3, leaf
    assert sorted(counts) == ["attn1_pairs", "moe1_load", "moe1_uncomputed",
                              "moe2_load", "moe2_uncomputed"]
    pairs = 48 * 49 // 2 + (T - 48) * 48
    assert int(counts["attn1_pairs"]) == B * pairs == B * ref.seen_pairs(T, 48)
    assert ref.seen_pairs(16384, 512) == 8_257_792
    assert ref.seen_pairs(16384) == 134_225_920


def test_reference_in_blocks_is_the_reference_whole(ref):
    """The reference's attention a block of 64 queries at a time (a window
    layer's against the 112 positions that end with it) gives what it gives
    with the row as one block."""
    params = correct.init_params(ref, CFG, 5)
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (B, T, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        for i in (0, 1):
            blocks = ref.attention(CFG, params, f"_attn{i}", i, x)
            ref.QUERY_BLOCK = T
            try:
                whole = ref.attention(CFG, params, f"_attn{i}", i, x)
            finally:
                ref.QUERY_BLOCK = 64
            assert rel(blocks, whole) <= 1e-6, i


def test_layers_of_one_family_differ_by_layer(program_file):
    """The two kinds' leaves: 6 and 8 query heads over the same 2 key-value
    heads, a head-wise gate of as many columns, no norm over the heads; the
    window layer alone has the counter."""
    cost, extras = program_file.net(dict(CFG, recompute_layers=[]))
    specs = nn.Topology([cost] + extras).param_specs
    assert specs["_attn0.wq"].shape == (64, 96)
    assert specs["_attn1.wq"].shape == (64, 128)
    assert specs["_attn0.wk"].shape == specs["_attn1.wk"].shape == (64, 32)
    assert specs["_attn0.wg"].shape == (64, 6)
    assert specs["_attn1.wg"].shape == (64, 8)
    assert not [k for k in specs if "q_norm" in k or "expert_bias" in k]
    counters = {e.name: e.meta["obs_counter"] for e in extras}
    assert counters["attn1_pairs"] == {"name": "window_attn_pairs",
                                       "labels": {"layer": "attn1"}}
    assert "attn0_pairs" not in counters and "attn2_pairs" not in counters


def test_window_pairs_count_real_queries_only():
    nn.reset_naming()
    layer = nn.causal_self_attention(
        nn.data("x", size=32, is_seq=True), num_heads=2, num_kv_heads=1,
        head_dim=16, qk_norm=False, window=8, output_gate="head", name="a")
    topo = nn.Topology(layer)
    params, _ = topo.init(jax.random.PRNGKey(0))
    x = np.random.default_rng(0).standard_normal((2, 40, 32)).astype(
        np.float32)
    out = topo.apply(params, {}, {"x": (x, np.asarray([40, 13], np.int32))})[
        0]["a"]
    seen = lambda n: sum(min(t + 1, 8) for t in range(n))  # noqa: E731
    assert int(out.state["window_pairs"]) == seen(40) + seen(13)


def test_layer_refuses_what_it_cannot_build():
    x = nn.data("x", size=32, is_seq=True)
    with pytest.raises(ConfigError, match="output_gate"):
        nn.causal_self_attention(x, num_heads=2, num_kv_heads=1, head_dim=16,
                                 output_gate="channel")
    with pytest.raises(ConfigError, match="window"):
        nn.causal_self_attention(x, num_heads=2, num_kv_heads=1, head_dim=16,
                                 window=0)
    with pytest.raises(ConfigError, match="rope_type"):
        nn.causal_self_attention(x, num_heads=2, num_kv_heads=1, head_dim=16,
                                 rope_scaling={"rope_type": "linear"})


def test_net_refuses_lists_of_unequal_length():
    from paddle_tpu.models import laguna_net

    with pytest.raises(ValueError, match="head counts"):
        laguna_net(50, hidden_size=64, layer_types=CFG["layer_types"],
                   mlp_layer_types=CFG["mlp_layer_types"],
                   num_attention_heads_per_layer=[6, 8],
                   num_key_value_heads=2, head_dim=16, sliding_window=48,
                   rope_parameters=CFG["rope_parameters"],
                   intermediate_size=96, moe_intermediate_size=48,
                   shared_expert_intermediate_size=32, num_experts=8,
                   num_experts_per_tok=3)


def test_shares_add_up_to_the_uncut_layer_with_what_all_compute_counted_once(
        ref):
    """Section 4's share test: the routed parts that the four shares of two
    experts give (the cell's thirty-two of eight, at a toy size), with the
    window attention, the router's choice and the shared expert, which every
    chip computes alike on its own tokens, counted ONCE, add up to what the
    uncut reference (all eight experts) gives for the whole layer."""
    cfg = dict(CFG, num_experts=8, first_expert=0)
    shapes = ref.param_shapes(cfg)
    names = [k for k in shapes if k.startswith(("_attn1.", "_moe1.",
                                                "_norm_op1.", "_norm_ffn1."))]
    whole = correct.init_params(
        type("R", (), {"param_shapes": staticmethod(
            lambda c: {k: shapes[k] for k in names})}), cfg, 9)
    x = np.random.default_rng(8).standard_normal((B, T, 64)).astype(np.float32)
    lengths = np.full((B,), T, np.int32)
    with jax.default_matmul_precision("highest"):
        want = ref.layer(cfg, whole, 1, jnp.asarray(x))
        nn.reset_naming()
        data = nn.data("x", size=64, is_seq=True)
        attn = nn.causal_self_attention(
            nn.rms_norm(data, eps=1e-6, name="norm_op1"), num_heads=8,
            num_kv_heads=2, head_dim=16, rope_theta=10000.0, norm_eps=1e-6,
            output_gate="head", qk_norm=False, window=48,
            rope_scaling=CFG["rope_parameters"]["sliding_attention"],
            name="attn1")
        h = nn.addto([data, attn], name="h")
        once = nn.Topology(h).apply(whole, {}, {"x": (x, lengths)})[0][
            "h"].value
        hn = ref.rms_norm(once, whole["_norm_ffn1.w"], 1e-6)
        shared = ref.shared_expert(whole, "_moe1", hn)
        total, load = once + shared, []
        for first in range(0, 8, 2):
            nn.reset_naming()
            node = nn.expert_mlp(
                nn.rms_norm(nn.data("h", size=64, is_seq=True), eps=1e-6,
                            name="norm_ffn1"), 48, num_experts=8,
                experts_held=(first, 2), top_k=3, scoring="sigmoid",
                routed_scaling_factor=2.5, selection_bias=False,
                name="moe1")
            share = {k: v for k, v in whole.items() if "shared" not in k}
            for leaf in ("w1", "w3", "w2"):
                share[f"_moe1.{leaf}"] = whole[f"_moe1.{leaf}"][first:first + 2]
            out = nn.Topology(node).apply(
                share, {}, {"h": (once, lengths)})[0][node.name]
            total = total + out.value            # this chip's routed part
            load += list(np.asarray(out.state["expert_load"]))
            assert int(out.state["uncomputed"]) == 0
    assert float(jnp.linalg.norm(shared)) > 0.1 * float(jnp.linalg.norm(want))
    assert rel(total, want) <= 1e-5
    assert sum(load) == B * T * 3       # every choice landed on one chip


# -- the five older models are what they were --------------------------------

#: loss and the sum of every gradient's absolute values on seeded weights,
#: read with this file's ``_older_model`` on bf57d56, this PR's parent
#: (tests/test_qwen3_next.py pins LFM2, Kanana-2 and Qwen3-Next the same way)
#: Keye-VL-2.0's loss was read again in PR 51 (4.803853988647461 before): the
#: rotary embedding turns a whole head in one pass, the same floats op by op
#: (``jax.disable_jit()`` reads (4.803853511810303, 844.4287719726562) on
#: both sides, tests/test_rotary.py holds the function to the bit), and under
#: ``jit`` XLA:CPU fuses its multiplies and adds differently
PARENT = {"nemotron": (4.419660568237305, 1031.2261962890625),
          "keye": (4.803853511810303, 844.4287719726562)}


def _older_model(which):
    from paddle_tpu.models import keye_vl2_net, nemotron_h_net

    nn.reset_naming()
    if which == "nemotron":
        cost, _ = nemotron_h_net(
            50, hybrid_override_pattern="ME*E", hidden_size=64,
            mamba_num_heads=4, mamba_head_dim=16, n_groups=2,
            ssm_state_size=16, conv_kernel=4, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, moe_intermediate_size=48,
            moe_shared_expert_intermediate_size=32, n_routed_experts=8,
            num_experts_per_tok=3, routed_scaling_factor=2.5)
    else:
        cost, _ = keye_vl2_net(
            50, hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, indexer_num_heads=2,
            indexer_head_dim=16, topk=24, moe_intermediate_size=48,
            num_experts=8, num_experts_per_tok=3)
    topo = nn.Topology(cost)
    params, _ = topo.init(jax.random.PRNGKey(7))
    batch = feed(4, t=64)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: topo.apply(p, {}, batch, train=True)[0]["cost"].value))(
            params)
    return float(loss), float(sum(jnp.sum(jnp.abs(g))
                                  for g in jax.tree_util.tree_leaves(grads)))


@pytest.mark.parametrize("which", ["nemotron", "keye"])
def test_older_models_are_bit_for_bit_what_they_were(which):
    """``window=None``, no ``rope_scaling``, ``output_gate`` a bool and
    ``selection_bias`` at its default run every line that ran before: the
    two newest siblings' loss and gradients on the CPU are the parent
    commit's to the last bit (the three older ones' pins are in
    tests/test_qwen3_next.py)."""
    assert _older_model(which) == PARENT[which]
