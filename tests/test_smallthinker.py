"""SmallThinker-21BA3B-Instruct (PR 57): ``smallthinker_net`` (a router that
reads the attention's input, ReLU-gated experts, one full layer without
positions to three window layers with the rotary embedding, a group of seven
query heads a key-value head) against the plain reference of
benchmark/reference on seeded weights: loss and every leaf's gradient, on the
XLA walks and on the flash kernels in interpret mode; the early router's
gradient path; the ReLU gate's exact zeros; the share test of the
model-configs guide, section 4; and the older models' losses and gradients,
which are what they were."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.nn as nn
from paddle_tpu.ops import decoder_block as DB
from paddle_tpu.ops import moe as M
from paddle_tpu.utils.error import ConfigError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.dirname(os.path.abspath(__file__))):
    if path not in sys.path:
        sys.path.insert(0, path)

from benchmark import correct, manifest  # noqa: E402

NAME = "smallthinker-21b-a3b-ep8"
PATHS = ["xla", "kernels"]
#: hidden 64; 7 query heads over 1 key-value head of 16 (the group of seven);
#: one period of the two layouts; a window of 8 in a row of 32; 8 router
#: outputs, experts of 32, 3 a token, 4 held from expert 2 on
CFG = dict(
    hidden_size=64, num_hidden_layers=4, num_attention_heads=7,
    num_key_value_heads=1, head_dim=16, sliding_window_layout=[0, 1, 1, 1],
    rope_layout=[0, 1, 1, 1], sliding_window_size=8, rope_theta=1.5e6,
    moe_ffn_hidden_size=32, router_outputs=8, moe_num_primary_experts=4,
    first_expert=2, moe_num_active_primary_experts=3, rms_norm_eps=1e-6,
    vocab_size=50)
B, T = 2, 32


@pytest.fixture
def path(request, monkeypatch):
    """``kernels``: the attention gate opens with blocks of 8 (a band of two
    blocks a block of queries) and the flash kernels run in interpret mode;
    ``xla``: the gate is as the CPU leaves it."""
    if request.param == "kernels":
        monkeypatch.setattr(
            DB, "attention_kernel_blocks",
            lambda T, dh, H, Hkv, dv=None, window=None: (8, 8))
    return request.param


@pytest.fixture(scope="module")
def ref():
    mod = manifest.load_module(os.path.join(
        ROOT, "benchmark", "reference", NAME + ".py"), "smallthinker_ref")
    mod.QUERY_BLOCK = 8         # four blocks of queries a row
    return mod


@pytest.fixture(scope="module")
def program_file():
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "programs", NAME + ".py"), "smallthinker_program")


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
    + [f"_norm_{s}{i}.w" for i in range(4) for s in ("op", "ffn")]
    + [f"_attn{i}.{p}" for i in range(4) for p in ("wq", "wk", "wv", "wo")]
    + [f"_moe{i}.{p}" for i in range(4)
       for p in ("router", "w1", "w3", "w2")])


def _net(program_file, **over):
    return program_file.net({**CFG, "recompute_layers": [0, 1, 2, 3], **over})


def _loss_and_grads(topo, extras, params, batch):
    def program(p):
        outs, _ = topo.apply(p, {}, batch, train=True)
        return outs["cost"].value, {e.name: outs[e.name].value
                                    for e in extras}

    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(program, has_aux=True))(params)


@pytest.mark.parametrize("path", PATHS, indirect=True)
def test_model_matches_the_reference(ref, program_file, path):
    """Loss and every leaf's gradient, ten leaves a layer (the router's
    among them: its gradient comes through ``norm_op<i>``); the window
    layers' counters are the band's count, the expert layers' zero counts
    are within their rows."""
    cost, extras = _net(program_file)
    topo = nn.Topology([cost] + extras)
    params = correct.init_params(ref, CFG, 3)
    made = topo.init(jax.random.PRNGKey(0))[0]
    assert ({k: v.shape for k, v in made.items()}
            == {k: v.shape for k, v in params.items()})
    assert sorted(params) == LEAVES and len(LEAVES) == 43
    batch = feed()

    def reference(p):
        total, count = ref.loss_sum(CFG, p, batch)
        return total / count

    with jax.default_matmul_precision("highest"):
        want_loss, want_grads = jax.jit(jax.value_and_grad(reference))(params)
    (loss, counts), grads = _loss_and_grads(topo, extras, params, batch)
    assert abs(float(loss) - float(want_loss)) <= 1e-5 * float(want_loss)
    for leaf in LEAVES:
        assert rel(grads[leaf], want_grads[leaf]) <= 1e-3, leaf
    assert sorted(counts) == sorted(
        [f"attn{i}_pairs" for i in (1, 2, 3)]
        + [f"moe{i}_{c}" for i in range(4)
           for c in ("load", "uncomputed", "gate_zero")])
    pairs = 8 * 9 // 2 + (T - 8) * 8
    for i in (1, 2, 3):
        assert int(counts[f"attn{i}_pairs"]) == B * pairs \
            == B * ref.seen_pairs(T, 8)
    for i in range(4):
        rows = int(counts[f"moe{i}_load"].sum())
        assert int(counts[f"moe{i}_uncomputed"]) == 0
        assert 0.2 * rows * 32 < int(counts[f"moe{i}_gate_zero"]) \
            < 0.8 * rows * 32
    assert ref.seen_pairs(16384, 4096) == 58_722_304
    assert ref.seen_pairs(16384) == 134_225_920


def test_a_router_fed_from_norm_ffn_fails_the_routers_gradient(ref,
                                                               program_file,
                                                               monkeypatch):
    """The same stack with every router on the feed-forward's normed input
    (``early_router=False``: every sibling's place for it) is another model:
    against the reference its routers' gradients, and the loss, are off by
    far more than the comparison allows."""
    import paddle_tpu.models.smallthinker as st

    real = st.decoder_stack
    monkeypatch.setattr(st, "decoder_stack", lambda *a, **kw: real(
        *a, **dict(kw, early_router=False)))
    cost, extras = _net(program_file)
    topo = nn.Topology([cost] + extras)
    assert "moe0/moe_routing" not in [l.name for l in topo.layers]
    params = correct.init_params(ref, CFG, 3)
    batch = feed()
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(lambda p: (
            lambda s: s[0] / s[1])(ref.loss_sum(CFG, p, batch))))(params)
    _, grads = _loss_and_grads(topo, extras, params, batch)
    assert all(rel(grads[f"_moe{i}.router"], want[f"_moe{i}.router"]) > 0.3
               for i in range(4))


def test_reference_in_blocks_is_the_reference_whole(ref):
    """The reference's attention a block of 8 queries at a time (a window
    layer's against the 16 positions that end with it) gives what it gives
    with the row as one block, with positions and without."""
    params = correct.init_params(ref, CFG, 5)
    u = jnp.asarray(np.random.default_rng(2).standard_normal(
        (B, T, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        for i in (0, 1):
            blocks = ref.attention(CFG, params, f"_attn{i}", i, u)
            ref.QUERY_BLOCK = T
            try:
                whole = ref.attention(CFG, params, f"_attn{i}", i, u)
            finally:
                ref.QUERY_BLOCK = 8
            assert rel(blocks, whole) <= 1e-6, i


def test_layers_by_the_two_layouts(program_file):
    """Layer 0 is full and takes no positions, layers 1-3 see a window and
    turn q and k; a group of seven; no head norm, no bias, no shared expert;
    the router's layer runs ahead of its layer's attention and holds the
    router's leaf."""
    cost, extras = _net(program_file, recompute_layers=[])
    topo = nn.Topology([cost] + extras)
    specs = topo.param_specs
    assert specs["_attn0.wq"].shape == specs["_attn1.wq"].shape == (64, 112)
    assert specs["_attn0.wk"].shape == (64, 16)
    assert specs["_moe0.router"].shape == (64, 8)
    assert specs["_moe0.w1"].shape == specs["_moe0.w3"].shape == (4, 64, 32)
    assert not [k for k in specs if "q_norm" in k or "expert_bias" in k
                or "shared" in k or k.startswith("_mlp")]
    order = [l.name for l in topo.layers]
    by_name = {l.name: l for l in topo.layers}
    for i in range(4):
        assert order.index(f"norm_op{i}") + 1 == order.index(
            f"moe{i}/moe_routing") == order.index(f"attn{i}") - 1
        router = by_name[f"moe{i}/moe_routing"]
        assert [p.name for p in router.parents] == [f"norm_op{i}"]
        assert [s.name for s in router.param_specs] == [f"_moe{i}.router"]
        assert [p.name for p in by_name[f"moe{i}"].parents] == [
            f"norm_ffn{i}", f"moe{i}/moe_routing"]
    counters = {e.name: e.meta["obs_counter"] for e in extras}
    assert counters["attn1_pairs"] == {"name": "window_attn_pairs",
                                       "labels": {"layer": "attn1"}}
    assert "attn0_pairs" not in counters
    assert counters["moe2_gate_zero"] == {"name": "moe_gate_zero_units",
                                          "labels": {"layer": "moe2"}}


def test_net_refuses_layouts_that_differ(program_file):
    with pytest.raises(ValueError, match="rope_layout differs"):
        _net(program_file, rope_layout=[1, 1, 1, 1])
    with pytest.raises(ValueError, match="entries"):
        _net(program_file, rope_layout=[0, 1, 1])


# -- the early router --------------------------------------------------------

def _early_layer(early=True, held=(2, 4)):
    """``expert_mlp`` over two feeds: ``v`` for the experts, ``u`` for the
    router (``early=False``: the router reads ``v`` too)."""
    nn.reset_naming()
    v = nn.data("v", size=64, is_seq=True)
    u = nn.data("u", size=64, is_seq=True)
    node = nn.expert_mlp(
        v, 32, num_experts=8, experts_held=held, top_k=3, scoring="softmax",
        expert_act="gated_relu", name="moe0",
        **({"router_input": u} if early else {}))
    return node, nn.Topology(node)


def _layer_params(ref, seed=9, held=4, router_outputs=8):
    cfg = dict(CFG, moe_num_primary_experts=held,
               router_outputs=router_outputs)
    shapes = {k: s for k, s in ref.param_shapes(cfg).items()
              if k.startswith("_moe0.")}
    return correct.init_params(type("R", (), {"param_shapes": staticmethod(
        lambda c: shapes)}), cfg, seed)


def test_routers_gradient_reaches_its_own_input_alone(ref):
    """``y = experts(v)`` weighted by a router that reads ``u``: against
    ``jax.grad`` of the reference's dense formula the gradients to ``u``,
    to ``v`` and to the router's leaf agree; the one to ``u`` is not zero
    and is ALL the router's (it vanishes with the router's weights held
    constant); a layer whose router reads ``v`` gives ``u`` none."""
    params = _layer_params(ref)
    r = np.random.default_rng(4)
    u, v, c = (jnp.asarray(r.standard_normal((B, T, 64)), jnp.float32)
               for _ in range(3))
    lengths = np.full((B,), T, np.int32)

    def program(early):
        node, topo = _early_layer(early)
        def feeds(u, v):
            both = {"v": (v, lengths), "u": (u, lengths)}
            return both if early else {"v": (v, lengths)}

        return lambda p, u, v: jnp.sum(topo.apply(
            p, {}, feeds(u, v))[0][node.name].value * c)

    def reference(p, u, v, stop=False):
        cfg = dict(CFG, first_expert=2)
        if stop:
            idx, w = ref.route(cfg, p, "_moe0", u)
            w = jax.lax.stop_gradient(w)
            y = sum(jnp.sum(jnp.where(idx == 2 + e, w, 0.0), -1)[..., None]
                    * ref.reglu(v, p["_moe0.w1"][e], p["_moe0.w3"][e],
                                p["_moe0.w2"][e]) for e in range(4))
        else:
            y = ref.routed_experts(cfg, p, "_moe0", u, v)
        return jnp.sum(y * c)

    with jax.default_matmul_precision("highest"):
        want = jax.grad(reference, (0, 1, 2))(params, u, v)
        frozen = jax.grad(lambda p, u, v: reference(p, u, v, stop=True),
                          (1, 2))(params, u, v)
        got = jax.grad(program(True), (0, 1, 2))(params, u, v)
        late = jax.grad(lambda p, u, v: program(False)(p, u, v),
                        (1,), allow_int=True)(params, u, v)
    assert rel(got[1], want[1]) <= 1e-4 and rel(got[2], want[2]) <= 1e-4
    for leaf in params:
        assert rel(got[0][leaf], want[0][leaf]) <= 1e-4, leaf
    assert float(jnp.linalg.norm(got[1])) > 0.01 * float(
        jnp.linalg.norm(got[2]))
    # with the router's weights held constant u gets nothing, and v what it
    # got: none of the router's gradient goes to v
    assert float(jnp.max(jnp.abs(frozen[0]))) == 0.0
    assert rel(got[2], frozen[1]) <= 1e-4
    assert float(jnp.max(jnp.abs(late[0]))) == 0.0


def test_padded_positions_route_nowhere_under_an_early_router():
    node, topo = _early_layer()
    params, _ = topo.init(jax.random.PRNGKey(0))
    x = np.random.default_rng(0).standard_normal((2, 24, 64)).astype(
        np.float32)
    lengths = np.asarray([24, 9], np.int32)
    acts = topo.apply(params, {}, {"v": (x, lengths), "u": (x, lengths)})[0]
    out, routed = acts["moe0"], acts["moe0/moe_routing"]
    idx = np.asarray(routed.state["idx"]).reshape(2, 24, 3)
    assert (idx[1, 9:] == -1).all() and (idx[1, :9] >= 0).all()
    held = ((idx >= 2) & (idx < 6)).sum()
    assert int(out.state["expert_load"].sum()) == held
    assert int(routed.state["counts"].sum()) == held
    assert int(out.state["uncomputed"]) == 0


def test_layer_refuses_what_it_cannot_build():
    x = nn.data("x", size=32, is_seq=True)
    with pytest.raises(ConfigError, match="expert_act"):
        nn.expert_mlp(x, 16, num_experts=4, top_k=2, expert_act="gelu")
    with pytest.raises(ConfigError, match="router's input"):
        nn.expert_mlp(x, 16, num_experts=4, top_k=2,
                      router_input=nn.data("u", size=48, is_seq=True))
    with pytest.raises(ValueError, match="w3"):
        M.grouped_expert_mlp(None, None, None, None, None, None, tm=8,
                             kernels=False, expert_act="gated_relu")
    with pytest.raises(ValueError, match="early router"):
        from paddle_tpu.models import decoder_stack
        decoder_stack(50, hidden_size=32, layer_types=["a"], mixers={},
                      num_dense_layers=0, intermediate_size=0,
                      moe_intermediate_size=16, num_experts=4,
                      num_experts_per_tok=2, ffn_layer_type="a",
                      early_router=True)


# -- the ReLU gate ------------------------------------------------------------

@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_gated_relu_experts_against_the_dense_formula(kernels):
    """``expert_act="gated_relu"`` forward and backward against ``jax.grad``
    of ``sum_e w_e W2_e (relu(W1_e x) * W3_e x)``; a hidden unit whose ``W_1
    x`` is not positive for any token gives EXACT zeros to its column of
    ``d_W_1`` and ``d_W_3`` and its row of ``d_W_2``, and the zero count is
    the count of ``W_1 x <= 0`` over the rows computed."""
    N, D, F, E, k, held, tm = 48, 128, 64, 4, 2, 4, 16
    r = np.random.default_rng(3)
    x = jnp.asarray(np.abs(r.standard_normal((N, D))).astype(np.float32))
    w1 = r.standard_normal((held, D, F)).astype(np.float32) * 0.1
    w1[:, :, 5] = -np.abs(w1[:, :, 5])      # unit 5 never opens: x > 0
    w1, w3, w2 = (jnp.asarray(w1), jnp.asarray(
        r.standard_normal((held, D, F)).astype(np.float32)) * 0.1,
        jnp.asarray(r.standard_normal((held, F, D)).astype(np.float32)) * 0.1)
    idx = jnp.asarray(np.stack([r.permutation(E)[:k] for _ in range(N)])
                      .astype(np.int32))
    wts = jnp.asarray(r.random((N, k)).astype(np.float32))

    def layer(x, wts, w1, w3, w2):
        y, load, uncomputed, zeros = M.expert_layer(
            x, idx, wts, w1, w3, w2, num_experts=E, first_expert=0, tm=tm,
            kernels=kernels, expert_act="gated_relu")
        return jnp.sum(jnp.square(y)), (load, uncomputed, zeros)

    def dense(x, wts, w1, w3, w2):
        y = 0.0
        for e in range(held):
            gate = jnp.sum(jnp.where(idx == e, wts, 0.0), -1)[:, None]
            y = y + gate * ((jax.nn.relu(x @ w1[e]) * (x @ w3[e])) @ w2[e])
        return jnp.sum(jnp.square(y))

    with jax.default_matmul_precision("highest"):
        (got, (load, uncomputed, zeros)), got_g = jax.value_and_grad(
            layer, (0, 1, 2, 3, 4), has_aux=True)(x, wts, w1, w3, w2)
        want, want_g = jax.value_and_grad(dense, (0, 1, 2, 3, 4))(
            x, wts, w1, w3, w2)
        h1 = jnp.einsum("nd,edf->enf", x, w1)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert all(rel(a, b) <= 1e-5 for a, b in zip(got_g, want_g))
    assert int(uncomputed) == 0 and int(load.sum()) == N * k
    routed = (idx[None] == jnp.arange(held)[:, None, None]).any(-1)  # [e, N]
    assert int(zeros) == int(jnp.sum(routed[:, :, None] & (h1 <= 0)))
    for g in (got_g[2][:, :, 5], got_g[3][:, :, 5], got_g[4][:, 5, :]):
        assert float(jnp.max(jnp.abs(g))) == 0.0
    assert float(jnp.min(jnp.abs(got_g[2][:, :, 6]).max(1))) > 0.0


def test_forms_are_a_table():
    assert sorted(M.EXPERT_ACTS) == ["gated_relu", "gated_silu", "relu2"]
    assert [M.EXPERT_ACTS[k].gated for k in sorted(M.EXPERT_ACTS)] == [
        True, True, False]
    assert [k for k, f in M.EXPERT_ACTS.items() if f.zero_gates] == [
        "gated_relu"]


# -- the share test ----------------------------------------------------------

def test_shares_add_up_to_the_uncut_layer_with_what_all_compute_counted_once(
        ref):
    """Section 4's share test at the cell's own cut: the routed parts that
    the eight shares ``experts_held = (8 j, 8)`` of one expert layer at 64
    router outputs give, with the attention and the router's choice, which
    every chip computes alike on its own tokens, counted ONCE, add up to
    what the uncut reference (all 64 experts) gives for the whole layer."""
    cfg = dict(CFG, router_outputs=64, moe_num_primary_experts=64,
               first_expert=0, moe_num_active_primary_experts=6)
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
        normed = nn.rms_norm(data, eps=1e-6, name="norm_op1")
        attn = nn.causal_self_attention(
            normed, num_heads=7, num_kv_heads=1, head_dim=16,
            rope_theta=1.5e6, norm_eps=1e-6, qk_norm=False, window=8,
            name="attn1")
        h = nn.addto([data, attn], name="h")
        once = nn.Topology([h, normed]).apply(
            whole, {}, {"x": (x, lengths)})[0]
        total, load = once["h"].value, []
        for first in range(0, 64, 8):
            nn.reset_naming()
            node = nn.expert_mlp(
                nn.rms_norm(nn.data("h", size=64, is_seq=True), eps=1e-6,
                            name="norm_ffn1"), 32, num_experts=64,
                experts_held=(first, 8), top_k=6, scoring="softmax",
                expert_act="gated_relu",
                router_input=nn.data("u", size=64, is_seq=True), name="moe1")
            share = dict(whole)
            for leaf in ("w1", "w3", "w2"):
                name = f"_moe1.{leaf}"
                share[name] = whole[name][first:first + 8]
            out = nn.Topology(node).apply(
                share, {}, {"h": (once["h"].value, lengths),
                            "u": (once["norm_op1"].value, lengths)})[0][
                                node.name]
            total = total + out.value            # this chip's routed part
            load += list(np.asarray(out.state["expert_load"]))
            assert int(out.state["uncomputed"]) == 0
    assert rel(total, want) <= 1e-5
    assert len(load) == 64 and sum(load) == B * T * 6


# -- the older models are what they were -------------------------------------

@pytest.mark.parametrize("which", ["lfm2", "kanana2", "qwen3next", "nemotron",
                                   "keye", "laguna"])
def test_older_models_are_bit_for_bit_what_they_were(which):
    """``expert_mlp`` without ``router_input``, ``decoder_stack`` without
    ``early_router`` and the experts' form as a table entry run every line
    that ran before: the six older expert models' loss and gradient sums on
    the CPU are the parent commit's to the last bit.  The pins are their own
    files' (read on the parent, dea6d74, with this PR's tree they are read
    again here): tests/test_qwen3_next.py, tests/test_laguna.py and
    tests/test_ouro.py build the models and hold the numbers."""
    import test_laguna as tl
    import test_ouro as to
    import test_qwen3_next as tq

    if which in tq.PARENT:
        assert tq._older_model(which) == tq.PARENT[which]
    elif which in tl.PARENT:
        assert tl._older_model(which) == tl.PARENT[which]
    else:
        to.test_laguna_is_bit_for_bit_what_it_was()
