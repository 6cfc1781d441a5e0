"""Kanana-2-30B-A3B's block (PR 35): latent attention, shared experts beside
the routed ones and an untied head, each alone and as the whole
``kanana2_moe_net``, against the plain reference of benchmark/reference on
seeded weights (loss and every gradient leaf); the share test with the shared
experts counted once; ``causal_attention`` with keys wider than values on the
XLA path off the block size and through the flash kernels in interpret mode
(192/128 and LFM2's 64/64); the gate's answers for both shapes; recomputation
blocks; and the routing counters through the trainer.

Tolerances as tests/test_lfm2.py: program and reference are both float32
here, so they differ by rounding and the order of sums only (gradients 2e-4
of the leaf's norm, losses 1e-5).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.nn as nn
from paddle_tpu.models import decoder_stack, kanana2_moe_net, lfm2_moe_net
from paddle_tpu.ops import decoder_block as DB

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import correct, manifest  # noqa: E402

GRAD_TOL, LOSS_TOL = 2e-4, 1e-5
#: hidden 64, 4 heads of 24 (16 + 8 rotary) with values of 16 from a latent
#: of 32, 8 experts of 48 with 2 held, top 3, 2 shared, 5 layers, T 64
CFG = dict(
    hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=48, router_outputs=8,
    n_routed_experts=2, first_expert=2, num_experts_per_tok=3,
    n_shared_experts=2, vocab_size=50, num_hidden_layers=5,
    first_k_dense_replace=1, rms_norm_eps=1e-6, rope_theta=1000000,
    norm_topk_prob=True, routed_scaling_factor=2.448)
B, T = 2, 64


@pytest.fixture(scope="module")
def ref():
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "reference", "kanana-2-30b-a3b-ep8.py"),
        "kanana2_ref")


@pytest.fixture(scope="module")
def program_file():
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "programs", "kanana-2-30b-a3b-ep8.py"),
        "kanana2_program")


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
        want = jax.value_and_grad(reference)(params)
        got = jax.jit(jax.value_and_grad(program))(params)
    return got, want


LEAVES = sorted(
    ["_emb.w0", "_norm_out.w", "_cost.w"]
    + [f"_norm_{w}{i}.w" for i in range(5) for w in ("op", "ffn")]
    + [f"_mla{i}.{p}" for i in range(5)
       for p in ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")]
    + [f"_mlp0.{p}" for p in ("w1", "w2", "w3")]
    + [f"_moe{i}.{p}" for i in (1, 2, 3, 4)
       for p in ("router", "expert_bias", "w1", "w2", "w3", "shared_w1",
                 "shared_w2", "shared_w3")])


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


def test_recompute_blocks_change_no_number_and_are_in_the_program(
        ref, program_file):
    from paddle_tpu.analysis.jaxpr_walk import walk_eqns

    params = correct.init_params(ref, CFG, 5)
    batch = feed(1)
    values, prims = [], []
    for recompute in ([1, 2, 3, 4], False):
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
    assert all(rel(ga[k], gb[k]) <= 1e-5 for k in ga if ga[k].any())
    assert len(prims[0]) >= 4 and not prims[1]    # a block a layer marked


def test_both_models_go_through_the_one_stack_builder(monkeypatch):
    """``lfm2_moe_net`` and ``kanana2_moe_net`` build nothing themselves but
    their mixers: the stack's loop is ``decoder_stack``'s."""
    import paddle_tpu.models.kanana2 as K
    import paddle_tpu.models.lfm2 as L

    seen = []

    def spy(vocab_size, **kw):
        seen.append((sorted(kw["mixers"]), kw["tie_head"],
                     kw.get("shared_size", 0)))
        return decoder_stack(vocab_size, **kw)

    monkeypatch.setattr(K, "decoder_stack", spy)
    monkeypatch.setattr(L, "decoder_stack", spy)
    nn.reset_naming()
    kanana2_moe_net(
        50, hidden_size=64, num_hidden_layers=2, first_k_dense_replace=1,
        intermediate_size=96, moe_intermediate_size=48, n_routed_experts=8,
        num_experts_per_tok=3, n_shared_experts=2, num_attention_heads=4,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16)
    nn.reset_naming()
    lfm2_moe_net(
        50, hidden_size=64, layer_types=["conv", "full_attention"],
        num_dense_layers=1, intermediate_size=96, moe_intermediate_size=48,
        num_experts=8, num_experts_per_tok=2, num_attention_heads=4,
        num_key_value_heads=2)
    assert seen == [(["latent_attention"], False, 96),
                    (["conv", "full_attention"], True, 0)]
    nn.reset_naming()
    with pytest.raises(ValueError, match="unknown layer type 'window'"):
        decoder_stack(50, hidden_size=64, layer_types=["window"], mixers={},
                      num_dense_layers=1, intermediate_size=96,
                      moe_intermediate_size=48, num_experts=8,
                      num_experts_per_tok=2)


# -- layer by layer ------------------------------------------------------------


def _one_layer(kind):
    """(layer node over a [B, T, 64] sequence feed, the reference's function
    of (params, x) for it)."""
    nn.reset_naming()
    x = nn.data("x", size=CFG["hidden_size"], is_seq=True)
    if kind == "latent_attention":
        return nn.latent_attention(
            x, num_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, rope_theta=1e6,
            norm_eps=1e-6, name="mla0"), \
            lambda ref, p, v: ref.latent_attention(CFG, p, "_mla0", v)
    assert kind == "expert_mlp_with_shared_experts"
    return nn.expert_mlp(x, 48, num_experts=8, experts_held=(2, 2), top_k=3,
                         routed_scaling_factor=2.448, shared_size=96,
                         name="moe1"), \
        lambda ref, p, v: ref.expert_layer(CFG, p, "_moe1", v)


@pytest.mark.parametrize("kind", ["latent_attention",
                                  "expert_mlp_with_shared_experts"])
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
        if np.asarray(wg).any():
            assert rel(g, wg) <= GRAD_TOL


def test_latent_attention_shares_one_rotary_key_over_the_heads(ref):
    """The down-projection's last ``qk_rope_head_dim`` columns make ONE key
    head: zeroing them leaves a layer whose scores come from the latent part
    alone, and the layer's parameters are the five the reference names."""
    node, _ = _one_layer("latent_attention")
    topo = nn.Topology(node)
    assert {k: s.shape for k, s in topo.param_specs.items()} == {
        "_mla0.wq": (64, 4 * 24), "_mla0.wkv_a": (64, 32 + 8),
        "_mla0.kv_norm": (32,), "_mla0.wkv_b": (32, 4 * 32),
        "_mla0.wo": (4 * 16, 64)}
    params = some_params(ref, CFG, list(topo.param_specs), 2)
    x = np.random.default_rng(1).standard_normal((B, T, 64)).astype(np.float32)
    feed_x = {"x": (x, np.full((B,), T, np.int32))}
    moved = dict(params)
    moved["_mla0.wkv_a"] = params["_mla0.wkv_a"].at[:, 32:].set(0.0)
    a = topo.apply(params, {}, feed_x)[0][node.name].value
    b = topo.apply(moved, {}, feed_x)[0][node.name].value
    assert rel(a, b) > 1e-3       # the shared rotary key takes part


def test_lm_head_cost_with_a_head_of_its_own(ref):
    nn.reset_naming()
    ids = nn.data("tokens", size=50, is_seq=True, dtype="int32")
    lab = nn.data("next_tokens", size=50, is_seq=True, dtype="int32")
    emb = nn.embedding(ids, 64, name="emb")
    cost = nn.lm_head_cost(emb, lab, name="cost")
    topo = nn.Topology(cost)
    params, _ = topo.init(jax.random.PRNGKey(2))
    assert {k: v.shape for k, v in params.items()} == {
        "_emb.w0": (50, 64), "_cost.w": (64, 50)}     # two matrices
    batch = feed(2)
    lengths = np.array([T, T // 2], np.int32)     # a padded row counts less
    batch = {k: (v[0], lengths) for k, v in batch.items()}

    def program(p):
        return topo.apply(p, {}, batch, train=True)[0]["cost"].value

    def plain(p):
        z = p["_emb.w0"][batch["tokens"][0]] @ p["_cost.w"]
        logp = jax.nn.log_softmax(z, -1)
        picked = jnp.take_along_axis(
            logp, batch["next_tokens"][0][..., None], -1)[..., 0]
        mask = np.arange(T)[None] < lengths[:, None]
        return -(picked * mask).sum() / mask.sum()

    want, wg = jax.value_and_grad(plain)(params)
    got, gg = jax.value_and_grad(program)(params)
    assert float(got) == pytest.approx(float(want), rel=LOSS_TOL)
    assert all(rel(gg[k], wg[k]) <= GRAD_TOL for k in wg)


# -- the share -----------------------------------------------------------------


def test_shares_add_up_to_the_uncut_layer_with_the_shared_experts_once(ref):
    """The routed parts that the four shares of two experts give, plus the
    shared experts that every chip computes alike, counted ONCE, add up to
    what the uncut reference (all eight experts, shared experts) gives."""
    cfg = dict(CFG, n_routed_experts=8, first_expert=0)
    names = [k for k in ref.param_shapes(cfg) if k.startswith("_moe1.")]
    whole = some_params(ref, cfg, names, 9)
    x = np.random.default_rng(8).standard_normal((B, T, 64)).astype(np.float32)
    lengths = np.full((B,), T, np.int32)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(cfg, whole, "_moe1", jnp.asarray(x))
        shared = ref.shared_experts(whole, "_moe1", jnp.asarray(x))
        total, load = 0.0, []
        for first in range(0, 8, 2):
            nn.reset_naming()
            node = nn.expert_mlp(
                nn.data("x", size=64, is_seq=True), 48, num_experts=8,
                experts_held=(first, 2), top_k=3,
                routed_scaling_factor=2.448, shared_size=96, name="moe1")
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


# -- attention with keys wider than values -------------------------------------


def _plain_attention(q, k, v, scale):
    G = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    Tq = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((Tq, Tq), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def _qkv(t, h, hkv, dqk, dv, seed=0):
    key = jax.random.PRNGKey(seed)
    return (jax.random.normal(key, (1, t, h, dqk)),
            jax.random.normal(jax.random.fold_in(key, 1), (1, t, hkv, dqk)),
            jax.random.normal(jax.random.fold_in(key, 2), (1, t, hkv, dv)),
            jax.random.normal(jax.random.fold_in(key, 3), (1, t, h, dv)))


@pytest.mark.parametrize("length", [40, 48, 7])
@pytest.mark.parametrize("hkv", [4, 2])
def test_blockwise_attention_with_a_value_width_of_its_own(length, hkv,
                                                           monkeypatch):
    """Keys of 24 and values of 16, blocks of 16 queries over rows of 40, 48
    and 7, every head its own key-value head or two to a group: the same
    numbers and gradients as plain attention."""
    monkeypatch.setattr(DB, "ATTN_XLA_BLOCK", 16)
    q, k, v, w = _qkv(length, 4, hkv, 24, 16)

    def run(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * w), argnums=(0, 1, 2)))(
                q, k, v)

    with jax.default_matmul_precision("highest"):
        got, got_g = run(lambda q, k, v: DB.causal_attention(
            q, k, v, scale=24 ** -0.5))
        want, want_g = run(lambda q, k, v: _plain_attention(
            q, k, v, 24 ** -0.5))
    assert DB.causal_attention(q, k, v, scale=1.0).shape == (1, length, 4, 16)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert all(rel(a, b) <= 1e-5 for a, b in zip(got_g, want_g))


@pytest.mark.parametrize("h,hkv,dqk,dv", [(2, 2, 192, 128), (4, 2, 64, 64)],
                         ids=["kanana2_192_128", "lfm2_64_64"])
def test_flash_kernels_match_the_xla_path(h, hkv, dqk, dv):
    """Interpret mode: the forward and the backward kernel against the XLA
    blocks (float32 operands here, so rounding only), at latent attention's
    widths and at grouped-query attention's."""
    from paddle_tpu.ops import pallas_kernels as PK

    scale = dqk ** -0.5
    q, k, v, w = _qkv(256, h, hkv, dqk, dv, seed=5)
    want, (dq, dk, d_v) = jax.value_and_grad(
        lambda q, k, v: jnp.sum(DB.causal_attention(q, k, v, scale=scale) * w),
        argnums=(0, 1, 2))(q, k, v)
    heads = lambda a: jnp.swapaxes(a, 1, 2)  # noqa: E731
    out, lse = PK.flash_attn_fwd_pallas(heads(q), heads(k), heads(v),
                                        scale=scale, block_q=128, block_k=128)
    assert out.shape == (1, h, 256, dv)
    assert float(jnp.sum(heads(out) * w)) == pytest.approx(float(want),
                                                           rel=1e-5)
    got = PK.flash_attn_bwd_pallas(heads(q), heads(k), heads(v), out, lse,
                                   heads(w), scale=scale, block_q=128,
                                   block_k=128)
    assert [a.shape[-1] for a in got] == [dqk, dqk, dv]
    assert all(rel(heads(a), b) <= 1e-5 for a, b in zip(got, (dq, dk, d_v)))


def _xla_grads(q, k, v, w, scale):
    return jax.grad(
        lambda q, k, v: jnp.sum(DB.causal_attention(q, k, v, scale=scale) * w),
        argnums=(0, 1, 2))(q, k, v)


def _kernel_grads(q, k, v, w, scale):
    from paddle_tpu.ops import pallas_kernels as PK

    heads = lambda a: jnp.swapaxes(a, 1, 2)  # noqa: E731
    out, lse = PK.flash_attn_fwd_pallas(heads(q), heads(k), heads(v),
                                        scale=scale, block_q=128, block_k=128)
    got = PK.flash_attn_bwd_pallas(heads(q), heads(k), heads(v), out, lse,
                                   heads(w), scale=scale, block_q=128,
                                   block_k=128)
    return [heads(a) for a in got]


@pytest.mark.parametrize("blocks", [2, 4])
@pytest.mark.parametrize("h,hkv,dqk,dv", [(8, 2, 64, 64), (2, 2, 192, 128),
                                          (4, 2, 128, 128)],
                         ids=["gqa_64_64", "mla_192_128", "gqa_128_128"])
def test_flash_backward_kernel_matches_the_xla_path(h, hkv, dqk, dv, blocks):
    """Interpret mode: ``flash_attn_bwd``, the one kernel that makes a block
    pair's probabilities once for dq, dk and dv, against the XLA path's
    gradients, over rows of 2 and of 4 blocks (dk and dv of the whole row
    resident, query blocks and the group's heads adding into them)."""
    scale = dqk ** -0.5
    q, k, v, w = _qkv(128 * blocks, h, hkv, dqk, dv, seed=11)
    got = _kernel_grads(q, k, v, w, scale)
    assert [a.shape for a in got] == [q.shape, k.shape, v.shape]
    assert all(a.dtype == jnp.float32 for a in got)
    assert all(rel(a, b) <= 1e-5
               for a, b in zip(got, _xla_grads(q, k, v, w, scale)))


def test_flash_backward_adds_the_groups_heads_into_one_dk_and_dv():
    """Four query heads on ONE key-value head: its resident dk and dv are the
    sums of what each query head alone gives (the same head run as its own
    key-value head), and each head's dq is that run's."""
    q, k, v, w = _qkv(384, 4, 1, 64, 64, seed=13)
    dq, dk, dv = _kernel_grads(q, k, v, w, 0.125)
    alone = [_kernel_grads(q[:, :, g:g + 1], k, v, w[:, :, g:g + 1], 0.125)
             for g in range(4)]
    assert rel(dq, jnp.concatenate([a[0] for a in alone], axis=2)) <= 1e-6
    assert rel(dk, sum(a[1] for a in alone)) <= 1e-6
    assert rel(dv, sum(a[2] for a in alone)) <= 1e-6
    assert rel(dk, alone[0][1]) > 0.1      # one head alone is not the sum


@pytest.mark.parametrize("key_rows", [256, 384, 128],
                         ids=["two_even", "uneven", "one_block_each"])
def test_flash_backward_in_super_blocks_of_keys(key_rows, monkeypatch):
    """A row too long for the resident dk and dv (here: told so) is cut into
    super-blocks of keys, one call each over the queries from its first key
    on; the calls' dq are summed, their dk and dv laid end to end: the whole
    row's numbers."""
    q, k, v, w = _qkv(512, 4, 2, 192, 128, seed=17)
    scale = 192 ** -0.5
    from paddle_tpu.ops import pallas_kernels as PK

    whole = _kernel_grads(q, k, v, w, scale)
    monkeypatch.setattr(PK, "flash_bwd_key_rows", lambda *shape: key_rows)
    text = str(jax.make_jaxpr(lambda: _kernel_grads(q, k, v, w, scale))())
    assert text.count("flash_attn_bwd") == -(-512 // key_rows)
    cut = _kernel_grads(q, k, v, w, scale)
    assert all(rel(a, b) <= 1e-6 for a, b in zip(cut, whole))
    assert all(rel(a, b) <= 1e-5
               for a, b in zip(cut, _xla_grads(q, k, v, w, scale)))


def test_custom_vjp_takes_the_kernels_where_the_gate_opens(monkeypatch):
    """With the gate opened by hand (interpret mode), ``causal_attention``
    itself runs the forward kernel and the ONE backward kernel at 192/128
    and gives the XLA path's numbers: residuals, layouts and dtypes of the
    kernel branch."""
    q, k, v, w = _qkv(256, 2, 2, 192, 128, seed=7)

    def run():
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(DB.causal_attention(
                q, k, v, scale=192 ** -0.5) * w), argnums=(0, 1, 2))(q, k, v)

    want, want_g = run()
    monkeypatch.setattr(DB, "attention_kernel_blocks",
                        lambda T, dh, H, Hkv, dv=None, window=None: (128, 128))
    text = str(jax.make_jaxpr(lambda: run())())
    assert text.count("flash_attn_fwd") == text.count("flash_attn_bwd") == 1
    assert "flash_attn_dq" not in text and "flash_attn_dkv" not in text
    got, got_g = run()
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert all(rel(a, b) <= 1e-5 for a, b in zip(got_g, want_g))


def test_gate_is_a_function_of_both_widths(monkeypatch):
    assert DB.attention_kernel_blocks(8192, 192, 32, 32, 128) is None  # CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # Kanana-2's shape and LFM2's: the blocks LFM2 took before this PR
    assert DB.attention_kernel_blocks(8192, 192, 32, 32, 128) == (1024, 1024)
    assert DB.attention_kernel_blocks(8192, 64, 32, 8) == (1024, 1024)
    assert DB.attention_kernel_blocks(8192, 64, 32, 8, 64) == (1024, 1024)
    assert DB.attention_kernel_blocks(256, 192, 32, 32, 128) == (128, 128)
    # a width off the 64-multiple on either side, a row the blocks do not
    # divide, heads that are no whole groups
    assert DB.attention_kernel_blocks(8192, 192, 32, 32, 96) is None
    assert DB.attention_kernel_blocks(8192, 160, 32, 32, 128) is None
    assert DB.attention_kernel_blocks(8200, 192, 32, 32, 128) is None
    assert DB.attention_kernel_blocks(8192, 192, 32, 5, 128) is None


@pytest.mark.parametrize("dqk,dv,hkv", [(192, 128, 32), (64, 64, 8)],
                         ids=["kanana2", "lfm2"])
def test_gate_and_the_backward_s_resident_keys(monkeypatch, dqk, dv, hkv):
    """At both cells' shapes: blocks of 1024 and ONE backward call with the
    whole row's dk and dv resident.  A row too long for that keeps the
    kernels (the parent gave it blocks; it never falls to the XLA path): the
    backward runs in super-blocks of whole key blocks whose dk and dv,
    double-buffered, fit the kernels' VMEM budget, fewer rows the wider the
    heads."""
    from paddle_tpu.ops import pallas_kernels as PK

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert DB.attention_kernel_blocks(8192, dqk, 32, hkv, dv) == (1024, 1024)
    assert PK.flash_bwd_key_rows(8192, dqk, dv, 1024, 1024) == 8192
    for T in (16384, 65536, 131072, 1 << 20):
        assert DB.attention_kernel_blocks(T, dqk, 32, hkv, dv) == (1024, 1024)
        rows = PK.flash_bwd_key_rows(T, dqk, dv, 1024, 1024)
        assert rows % 1024 == 0 and 0 < rows <= T
        lanes = -(-dqk // 128) * 128 + -(-dv // 128) * 128
        assert 2 * 4 * rows * lanes < PK.FLASH_VMEM_LIMIT_BYTES
    assert PK.flash_bwd_key_rows(16384, dqk, dv, 1024, 1024) == 16384
    assert PK.flash_bwd_key_rows(65536, dqk, dv, 1024, 1024) < 65536
    assert PK.flash_bwd_key_rows(65536, 64, 64, 1024, 1024) \
        > PK.flash_bwd_key_rows(65536, 192, 128, 1024, 1024)


# -- through the trainer ---------------------------------------------------------


def test_trainer_feeds_the_routing_counters(own_registry, ref, program_file):
    from paddle_tpu.obs import get_registry
    from paddle_tpu.param.optimizers import Adam
    from paddle_tpu.trainer import SGDTrainer

    def assigned():
        series = get_registry().snapshot().get("moe_assignments", {}).get(
            "series", [])
        return {(s["labels"]["layer"], s["labels"]["expert"]): s["value"]
                for s in series}

    before = assigned()
    cost, extras = build(program_file, CFG)
    trainer = SGDTrainer(cost, Adam(learning_rate=1e-3), extra_outputs=extras)
    first = float(np.asarray(trainer.params["_cost.w"]).sum())
    batches = [feed(i) for i in range(3)]
    trainer.train(lambda: iter(batches), num_passes=1)
    after = assigned()
    gained = {k: after[k] - before.get(k, 0) for k in after}
    moved = {k: v for k, v in gained.items() if v}
    assert set(moved) <= {(f"moe{i}", str(e)) for i in (1, 2, 3, 4)
                          for e in (2, 3)}
    assert 0 < sum(moved.values()) <= 3 * 4 * B * T * 3
    assert program_file.expert_load(["moe1"])["moe1"] == [
        after[("moe1", "2")], after[("moe1", "3")]]
    dropped = get_registry().snapshot()["moe_uncomputed_assignments"]
    assert sum(s["value"] for s in dropped["series"]) == 0
    assert program_file.uncomputed_assignments() == 0
    # the untied head is a leaf Adam moves
    assert float(np.asarray(trainer.params["_cost.w"]).sum()) != first
