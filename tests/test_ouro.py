"""Ouro-2.6B (PR 54): ``ouro_net`` (a stack whose layers run several times
over ONE set of weights, sandwich norms, an exit after every pass, the
expected loss over the exits) against the plain reference of
benchmark/reference on seeded weights: the loss and every leaf's gradient
with and without recomputation; one pass is the plain stack; the shared
leaf's gradient is the sum of a twin's per-pass gradients; the exit
distribution and its corners; the per-token readout under the tiled kernels
and without; and the six older models' graphs, which are what they were.

No share-of-a-deployment test (model-configs guide, section 4) applies:
nothing of this model is sliced, the cut is depth alone."""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.nn as nn
from paddle_tpu.models import decoder_stack, ouro_net
from paddle_tpu.nn.layers_decoder import exit_distribution
from paddle_tpu.ops import losses as L
from paddle_tpu.utils.error import ConfigError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import correct, manifest  # noqa: E402

NAME = "ouro-2.6b-loop4"
#: hidden 64, two layers of 4 heads of 16 over 4 key-value heads, an MLP of
#: 96, four passes, a vocabulary of 50
CFG = dict(hidden_size=64, num_hidden_layers=2,
           layer_types=["full_attention"] * 2, num_attention_heads=4,
           num_key_value_heads=4, head_dim=16, intermediate_size=96,
           total_ut_steps=4, rope_theta=1e6, rms_norm_eps=1e-6,
           exit_beta=0.1, vocab_size=50)
B, T = 2, 64
R = CFG["total_ut_steps"]


@pytest.fixture(scope="module")
def ref():
    mod = manifest.load_module(os.path.join(
        ROOT, "benchmark", "reference", NAME + ".py"), "ouro_ref")
    mod.QUERY_BLOCK, mod.HEAD_BLOCK = 16, 32   # four blocks of queries, two
    return mod                                 # of positions a row


@pytest.fixture(scope="module")
def program_file():
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "programs", NAME + ".py"), "ouro_program")


def rel(got, want):
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


def feed(seed=0, lengths=(T, 41)):
    """One full row and one ragged one: the mask is the targets'."""
    ids = np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], (B, T + 1)).astype(np.int32)
    lengths = np.asarray(lengths, np.int32)
    return {"tokens": (ids[:, :-1], lengths),
            "next_tokens": (ids[:, 1:], lengths)}


LEAVES = sorted(
    ["_emb.w0", "_norm_out.w", "_cost.w", "_exit_gate.w", "_exit_gate.b"]
    + [f"_{n}{i}.w" for i in range(2)
       for n in ("norm_op", "post_op", "norm_ffn", "post_ffn")]
    + [f"_attn{i}.{p}" for i in range(2) for p in ("wq", "wk", "wv", "wo")]
    + [f"_mlp{i}.{p}" for i in range(2) for p in ("w1", "w3", "w2")])


def _program(topo, cost, extras, batch):
    def run(p):
        outs, _ = topo.apply(p, {}, batch, train=True)
        return outs[cost.name].value, {e.name: outs[e.name].value
                                       for e in extras}
    return run


@pytest.mark.parametrize("recompute", [[0, 1], []],
                         ids=["recomputed", "held"])
def test_model_matches_the_reference(ref, program_file, recompute):
    """The loss and EVERY leaf's gradient at four passes, float32 at
    ``highest``.  Tolerances: the loss to 1e-6 relative (two float32
    evaluations of one expression in another order of sums: equal to the
    bit is read); a leaf's gradient to 1e-4 of its norm (a leaf's gradient
    sums four uses and up to 105 positions in another order, and the
    reference takes ``1 - sigmoid(g)`` where the program takes
    ``sigmoid(-g)``: 2.4e-6 is the largest read, on the gate's bias; a
    dropped use reads 0.25).  The extras are the reference's own sums."""
    cost, extras = program_file.net(dict(CFG, recompute_layers=recompute))
    topo = nn.Topology([cost] + extras)
    params = correct.init_params(ref, CFG, 3)
    assert ({k: v.shape for k, v in
             topo.init(jax.random.PRNGKey(0))[0].items()}
            == {k: v.shape for k, v in params.items()})
    assert sorted(params) == LEAVES
    blocks = {l.meta.get("remat") for l in topo.layers} - {None}
    assert blocks == ({f"loop{t}/layer{i}" for t in range(R) for i in (0, 1)}
                      | {f"exit{t}" for t in range(R)} if recompute
                      else set())
    batch = feed()

    def reference(p):
        total, count = ref.loss_sum(CFG, p, batch)
        return total / count

    with jax.default_matmul_precision("highest"):
        want_loss, want_grads = jax.jit(jax.value_and_grad(reference))(params)
        (loss, sums), grads = jax.jit(jax.value_and_grad(
            _program(topo, cost, extras, batch), has_aux=True))(params)
        ce, dist, mask = jax.jit(lambda p: ref.exit_terms(CFG, p, batch))(
            params)
    assert abs(float(loss) - float(want_loss)) <= 1e-6 * float(want_loss)
    for leaf in LEAVES:
        assert rel(grads[leaf], want_grads[leaf]) <= 1e-4, leaf
    assert sorted(sums) == ["exit_ce", "exit_entropy", "exit_mass"]
    np.testing.assert_allclose(sums["exit_mass"], (dist * mask).sum((1, 2)),
                               rtol=1e-5)
    np.testing.assert_allclose(sums["exit_ce"], (ce * mask).sum((1, 2)),
                               rtol=1e-5)
    np.testing.assert_allclose(
        sums["exit_entropy"], (ref.entropy(list(dist)) * mask).sum(),
        rtol=1e-5)
    assert abs(float(sums["exit_mass"].sum()) - (T + 41)) < 1e-3


def test_one_pass_is_the_plain_stack():
    """At ``total_ut_steps`` 1 the exit takes all the mass (p_1 = 1, H = 0)
    and no gate is built: the cost is ``decoder_stack``'s plain mean
    cross-entropy of the same weights, layers and leaves named as a stack of
    one pass names them."""
    kw = {k: CFG[k] for k in ("hidden_size", "layer_types",
                              "num_attention_heads", "num_key_value_heads",
                              "head_dim", "intermediate_size")}
    nn.reset_naming()
    cost, extras = ouro_net(CFG["vocab_size"], total_ut_steps=1, **kw)
    looped = nn.Topology([cost] + extras)
    assert not [k for k in looped.param_specs if "exit_gate" in k]
    assert [l.name for l in looped.layers if "attn" in l.name] == [
        "attn0", "attn1"]

    def attention(normed, i):
        return nn.causal_self_attention(
            normed, num_heads=4, num_kv_heads=4, head_dim=16, rope_theta=1e6,
            norm_eps=1e-6, qk_norm=False, name=f"attn{i}")

    nn.reset_naming()
    plain_cost, _ = decoder_stack(
        CFG["vocab_size"], hidden_size=64, layer_types=CFG["layer_types"],
        mixers={"full_attention": attention}, num_dense_layers=2,
        intermediate_size=96, moe_intermediate_size=0, num_experts=0,
        num_experts_per_tok=0, norm_eps=1e-6, tie_head=False, post_norm=True)
    plain = nn.Topology(plain_cost)
    assert ({k: s.shape for k, s in plain.param_specs.items()}
            == {k: s.shape for k, s in looped.param_specs.items()})
    params, _ = plain.init(jax.random.PRNGKey(5))
    batch = feed(1)
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.value_and_grad(lambda p: plain.apply(
            p, {}, batch, train=True)[0]["cost"].value)(params)
        (got, sums), got_g = jax.value_and_grad(
            _program(looped, cost, extras, batch), has_aux=True)(params)
    assert abs(float(got) - float(want)) <= 1e-6 * float(want)
    for leaf in params:
        assert rel(got_g[leaf], want_g[leaf]) <= 1e-5, leaf
    assert float(sums["exit_entropy"]) == 0.0
    assert float(sums["exit_mass"][0]) == T + 41


def _twin(passes):
    """The looped stack built from the layers themselves with pass ``t``'s
    leaves named ``_p<t>.<leaf>``: the same graph, nothing shared."""
    nn.reset_naming()
    tokens = nn.data("tokens", size=50, is_seq=True, dtype="int32")
    targets = nn.data("next_tokens", size=50, is_seq=True, dtype="int32")
    x = nn.embedding(tokens, 64, name="emb")

    def norm(v, t, name):
        return nn.rms_norm(v, eps=1e-6, name=f"p{t}/{name}",
                           param_attr=nn.ParamAttr(name=f"_p{t}._{name}.w",
                                                   init="ones"))

    ces, gates = [], []
    for t in range(passes):
        for i in range(2):
            a = nn.causal_self_attention(
                norm(x, t, f"norm_op{i}"), num_heads=4, num_kv_heads=4,
                head_dim=16, rope_theta=1e6, norm_eps=1e-6, qk_norm=False,
                name=f"p{t}/attn{i}", param_name=f"p{t}._attn{i}")
            h = nn.addto([x, norm(a, t, f"post_op{i}")], name=f"p{t}/h{i}")
            m = nn.gated_mlp(norm(h, t, f"norm_ffn{i}"), 96,
                             name=f"p{t}/mlp{i}", param_name=f"p{t}._mlp{i}")
            x = nn.addto([h, norm(m, t, f"post_ffn{i}")], name=f"p{t}/y{i}")
        x = norm(x, t, "norm_out")
        ces.append(nn.lm_head_token_cost(x, targets, name=f"p{t}/head",
                                         param_name=f"p{t}._cost"))
        if t < passes - 1:
            gates.append(nn.token_gate(x, name=f"p{t}/gate",
                                       param_name=f"p{t}._exit_gate"))
    return nn.loop_exit_cost(ces, gates, targets, beta=CFG["exit_beta"],
                             name="cost")


def test_shared_leaf_gradient_is_the_sum_over_its_uses(ref, program_file):
    """The test that ties the loop to the model: a twin whose four passes
    own separate leaves, set to the same values, has the same loss, and its
    per-pass gradients ADD UP to the shared leaf's gradient (1e-5 of its
    norm: the same float32 products summed in another order); every pass
    moves every layer's leaves, so a program that dropped one use of a leaf
    would be a quarter off.  The embedding is used once in both."""
    cost, extras = program_file.net(dict(CFG, recompute_layers=[0, 1]))
    topo = nn.Topology([cost] + extras)
    params = correct.init_params(ref, CFG, 11)
    twin_cost = _twin(R)
    twin = nn.Topology(twin_cost)
    twin_params = {"_emb.w0": params["_emb.w0"]}
    for t in range(R):
        for leaf, value in params.items():
            if leaf != "_emb.w0" and (t < R - 1 or "exit_gate" not in leaf):
                twin_params[f"_p{t}.{leaf}"] = value
    assert sorted(twin_params) == sorted(twin.param_specs)
    assert len(twin_params) == 1 + R * (len(LEAVES) - 1) - 2
    batch = feed(2)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(lambda p: topo.apply(
            p, {}, batch, train=True)[0][cost.name].value))(params)
        twin_loss, twin_grads = jax.jit(jax.value_and_grad(
            lambda p: twin.apply(p, {}, batch, train=True)[0]["cost"].value))(
                twin_params)
    assert abs(float(loss) - float(twin_loss)) <= 1e-6 * float(loss)
    assert rel(grads["_emb.w0"], twin_grads["_emb.w0"]) <= 1e-5
    for leaf in LEAVES:
        if leaf == "_emb.w0":
            continue
        uses = [twin_grads[f"_p{t}.{leaf}"] for t in range(R)
                if f"_p{t}.{leaf}" in twin_grads]
        assert len(uses) == (R - 1 if "exit_gate" in leaf else R), leaf
        assert rel(grads[leaf], sum(uses)) <= 1e-5, leaf
        for t, use in enumerate(uses):       # no pass is idle for this leaf
            assert float(jnp.linalg.norm(use)) > 1e-3 * float(
                jnp.linalg.norm(grads[leaf])), (leaf, t)


def test_one_spec_and_one_array_a_shared_name(program_file):
    """The cell's sizes by shapes alone: 509,661,185 parameters, every leaf
    once whatever the passes (``jax.eval_shape`` of ``Topology.init``): 71
    leaves, those of a layer read by four layers of the graph."""
    cfg = manifest.cell("ouro-train-b1-t4096")["config"]
    cost, extras = program_file.net(cfg)
    topo = nn.Topology([cost] + extras)
    shapes, _ = jax.eval_shape(topo.init, jax.random.PRNGKey(0))
    count = {k: int(np.prod(v.shape)) for k, v in shapes.items()}
    assert sum(count.values()) == 509_661_185
    assert count["_emb.w0"] == count["_cost.w"] == 100_663_296
    assert sum(v for k, v in count.items()
               if k.split(".")[0].rstrip("012345") in (
                   "_attn", "_mlp", "_norm_op", "_post_op", "_norm_ffn",
                   "_post_ffn")) == 6 * 51_388_416
    assert count["_norm_out.w"] == 2048
    assert count["_exit_gate.w"] + count["_exit_gate.b"] == 2049
    assert len(count) == 5 + 6 * 11
    users = {}
    for layer in topo.layers:
        for spec in layer.param_specs:
            users.setdefault(spec.name, []).append(layer.name)
    assert users["_attn3.wq"] == [f"loop{t}/attn3" for t in range(4)]
    assert users["_cost.w"] == [f"exits/pass{t}/exit_head" for t in range(4)]
    assert users["_exit_gate.w"] == [f"exits/pass{t}/exit_gate"
                                     for t in range(3)]
    assert all(len(u) == 4 for k, u in users.items()
               if k not in ("_emb.w0", "_exit_gate.w", "_exit_gate.b"))


# -- the exit distribution -----------------------------------------------------

def _closed_form(g):
    """p_t by the definition, in float64."""
    lam = 0.5 * (1.0 + np.tanh(0.5 * np.asarray(g, np.float64)))
    p, stay = [], np.ones_like(lam[0])
    for row in lam:
        p.append(row * stay)
        stay = stay * (1.0 - row)
    return np.stack(p + [stay])


def test_exit_distribution_sums_to_one_and_matches_the_closed_form():
    g = np.random.default_rng(0).normal(0, 2.0, (3, 5, 7)).astype(np.float32)
    p = exit_distribution(g)
    assert p.shape == (4, 5, 7) and p.dtype == jnp.float32
    np.testing.assert_allclose(p.sum(0), 1.0, atol=3e-7)
    np.testing.assert_allclose(p, _closed_form(g), rtol=1e-5, atol=1e-9)


@pytest.mark.parametrize("g", [[200.0, 0.3, -0.2], [-200.0, 1e4, 0.5],
                               [0.1, -1e4, 1e4], [1e4, 1e4, 1e4],
                               [-1e4, -1e4, -1e4]])
def test_a_saturated_gate_gives_no_nan(g):
    """``lam_t`` exactly 0 or 1 in float32: the masses still add up to 1,
    an exit of no mass adds no entropy, and neither the cost nor its
    gradient (towards the gates and towards the cross-entropies) is NaN."""
    g = jnp.asarray(g, jnp.float32)[:, None, None] * jnp.ones((3, 1, 4))
    p = exit_distribution(g)
    np.testing.assert_allclose(p.sum(0), 1.0, atol=3e-7)
    np.testing.assert_allclose(p, _closed_form(g), atol=1e-7)

    nn.reset_naming()
    lab = nn.data("lab", size=9, is_seq=True, dtype="int32")
    ce = [nn.data(f"ce{t}", size=1, is_seq=True) for t in range(4)]
    gt = [nn.data(f"g{t}", size=1, is_seq=True) for t in range(3)]
    cost = nn.loop_exit_cost(ce, gt, lab, beta=0.1, name="cost")
    topo = nn.Topology(cost)
    lengths = np.asarray([4], np.int32)
    ces = jnp.asarray(np.random.default_rng(1).uniform(1, 5, (4, 1, 4)),
                      jnp.float32)

    def value(ces, g):
        batch = {"lab": (np.zeros((1, 4), np.int32), lengths)}
        batch.update({f"ce{t}": (ces[t], lengths) for t in range(4)})
        batch.update({f"g{t}": (g[t], lengths) for t in range(3)})
        return topo.apply({}, {}, batch)[0]["cost"].value

    cost_value, (d_ce, d_g) = jax.value_and_grad(value, argnums=(0, 1))(
        ces, g)
    assert np.isfinite(float(cost_value))
    assert np.isfinite(np.asarray(d_ce)).all()
    assert np.isfinite(np.asarray(d_g)).all()
    np.testing.assert_allclose(d_ce, p / 4, atol=1e-7)   # d cost / d CE_t


@pytest.mark.parametrize("exit_,bias", [(0, 1e4), (3, -1e4)])
def test_a_gate_forced_to_one_exit_gives_that_exits_cross_entropy(
        ref, program_file, exit_, bias):
    """``beta`` 0 and every ``lam_t`` 1 (the first exit) or 0 (the mass is
    left to the last): the cost is that exit's mean cross-entropy."""
    cost, extras = program_file.net(dict(CFG, exit_beta=0.0,
                                         recompute_layers=[]))
    topo = nn.Topology([cost] + extras)
    params = correct.init_params(ref, CFG, 4)
    params["_exit_gate.b"] = jnp.full((1,), bias, jnp.float32)
    batch = feed(6)
    with jax.default_matmul_precision("highest"):
        value, sums = jax.jit(_program(topo, cost, extras, batch))(params)
    tokens = T + 41
    np.testing.assert_allclose(sums["exit_mass"],
                               np.eye(4)[exit_] * tokens, atol=1e-4)
    assert abs(float(value) - float(sums["exit_ce"][exit_]) / tokens) \
        <= 1e-6 * float(value)


def test_layers_refuse_what_they_cannot_build():
    lab = nn.data("lab", size=9, is_seq=True, dtype="int32")
    ce = [nn.data(f"ce{t}", size=1, is_seq=True) for t in range(3)]
    with pytest.raises(ConfigError, match="gates"):
        nn.loop_exit_cost(ce, ce[:1], lab)
    kw = dict(hidden_size=64, layer_types=["full_attention"],
              mixers={"full_attention": lambda x, i, scope="": x},
              intermediate_size=96, moe_intermediate_size=48, num_experts=8,
              num_experts_per_tok=2)
    with pytest.raises(ValueError, match="looped"):
        decoder_stack(50, num_dense_layers=0, loops=2, **kw)
    with pytest.raises(ValueError, match="passes"):
        decoder_stack(50, num_dense_layers=1, loops=0, **kw)


# -- the per-token readout -----------------------------------------------------

def _readout_inputs(seed=0, B=2, T=8, D=128, V=300):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((B, T, D)), jnp.float32),
            jnp.asarray(rng.standard_normal((D, V)) * D ** -0.5, jnp.float32),
            jnp.asarray(rng.standard_normal((V,)) * 0.1, jnp.float32),
            jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32),
            jnp.asarray(np.arange(T)[None, :] < np.asarray([[T], [5]]),
                        jnp.float32))


@pytest.fixture
def ce_path(request, monkeypatch):
    """``kernels``: the tiled pair in interpret mode at row blocks of 8 and
    vocabulary tiles of 128; ``xla``: the gate as the CPU leaves it."""
    if request.param == "kernels":
        monkeypatch.setattr(L, "_tiled_ce_cfg", lambda B, T, D, V: (8, 128))
    return request.param


def _readout_before_pr_54(states, w, b, labels, mask):
    """``sequence_softmax_ce_readout`` on the kernels as it was written
    before there was a per-token form: the masked mean inside the
    ``custom_vjp``, its backward's scale ``d mask / count`` written out."""
    from paddle_tpu.ops.numerics import mxu_cast
    from paddle_tpu.ops.pallas_kernels import (ce_readout_bwd_pallas,
                                               ce_readout_fwd_pallas)

    V, vt, f32 = w.shape[1], 128, jnp.float32

    @jax.custom_vjp
    def tiled(states, w, b):
        return fwd(states, w, b)[0]

    def fwd(states, w, b):
        B, T, D = states.shape
        sc, wc = mxu_cast(states.reshape(B * T, D), w)
        Vp = -(-V // vt) * vt
        w_p = jnp.pad(wc, ((0, 0), (0, Vp - V)))
        b_p = jnp.pad(b.astype(f32).reshape(1, V), ((0, 0), (0, Vp - V)),
                      constant_values=-1e30)
        lab = labels.astype(jnp.int32).reshape(B * T, 1)
        per_tok, lse, logits = ce_readout_fwd_pallas(
            sc, w_p, b_p, lab, row_block=8, v_tile=vt)
        return (L.masked_token_mean(per_tok.reshape(B, T), mask),
                (sc, w, lab, lse, logits))

    def bwd(res, d):
        sc, w, lab, lse, logits = res
        w_p = jnp.pad(mxu_cast(w), ((0, 0), (0, logits.shape[1] - V)))
        scale = (d * mask / L.token_count(mask)).reshape(-1, 1)
        d_states, d_w_p, d_b_p = ce_readout_bwd_pallas(
            logits, sc, w_p, lab, lse, scale, v_tile=vt)
        return (d_states.reshape(states.shape), d_w_p[:, :V], d_b_p[0, :V])

    tiled.defvjp(fwd, bwd)
    return tiled(states, w, b)


@pytest.mark.parametrize("ce_path", ["xla", "kernels"], indirect=True)
def test_sequence_readout_is_unchanged_to_the_bit(ce_path):
    """``sequence_softmax_ce_readout`` is the masked mean of the per-token
    form: its loss and its three gradients are, to the last bit, what the
    function gave before (on the kernels: the masked mean and its scale
    inside the ``custom_vjp``; without: the same expression, which autodiff
    differentiates as it did)."""
    states, w, b, labels, mask = _readout_inputs()

    def before(states, w, b):
        if ce_path == "kernels":
            return _readout_before_pr_54(states, w, b, labels, mask)
        logits = L._readout_logits(states, w, b)
        lf32 = lambda: logits.astype(jnp.float32)         # noqa: E731
        m = jnp.max(lf32(), axis=-1, keepdims=True)
        lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(lf32() - m), axis=-1))
        tok = jnp.squeeze(jnp.take_along_axis(
            logits, labels[..., None], axis=-1), -1)
        return L.masked_token_mean(lse - tok.astype(jnp.float32), mask)

    want, want_g = jax.value_and_grad(before, argnums=(0, 1, 2))(states, w, b)
    got, got_g = jax.value_and_grad(
        lambda s, w, b: L.sequence_softmax_ce_readout(s, w, b, labels, mask),
        argnums=(0, 1, 2))(states, w, b)
    assert float(got) == float(want)
    for a, e in zip(got_g, want_g):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(e))


@pytest.mark.parametrize("ce_path", ["xla", "kernels"], indirect=True)
def test_per_token_readout_takes_a_cotangent_a_token(ce_path):
    """``[B, T]`` cross-entropies out, an arbitrary ``[B, T]`` cotangent in:
    values and the three gradients against ``jax.grad`` of the plain
    expression (float32 on the CPU: 1e-5 of the gradient's norm, the
    kernels' sums over vocabulary tiles in another order)."""
    states, w, b, labels, _ = _readout_inputs(3)
    weights = jnp.asarray(np.random.default_rng(9).standard_normal((2, 8)),
                          jnp.float32)

    def plain(states, w, b):
        logits = jnp.einsum("btd,dv->btv", states, w) + b
        return jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, labels[..., None], -1)[..., 0]

    with jax.default_matmul_precision("highest"):
        want = plain(states, w, b)
        got = L.softmax_ce_readout_per_token(states, w, b, labels)
        want_g = jax.grad(lambda *a: jnp.sum(plain(*a) * weights),
                          argnums=(0, 1, 2))(states, w, b)
        got_g = jax.grad(lambda *a: jnp.sum(
            L.softmax_ce_readout_per_token(*a, labels) * weights),
            argnums=(0, 1, 2))(states, w, b)
    assert got.shape == (2, 8) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for a, e in zip(got_g, want_g):
        assert rel(a, e) <= 1e-5


# -- the six older models are what they were -----------------------------------

#: (leaves: names and shapes; layers: names, kinds, sizes, parents,
#: recomputation blocks and captured constructor calls) of each older model's
#: graph, sha256 to 16 digits, read with ``_graph_digest`` on f42d165, this
#: PR's parent: LFM2 at the toy sizes of tests/test_qwen3_next.py (its program
#: file has no ``net``), the other five at their cells' sizes
OLDER_GRAPHS = {
    "lfm2": ("5cb0b7779b4bf135", "61157174246715d4"),
    "kanana2moe-train-b1-t8192": ("e5e6bfde18aa561b", "a50b99ba08998730"),
    "qwen3next-train-b1-t8192": ("dc5add92f90ed44d", "b4525a5dca3b9e30"),
    "nemotron3nano-train-b1-t4096": ("f1fba1a9acd5215c", "0e625e9c7b110b5f"),
    "keyevl2-train-b1-t16384": ("15a236a185dde634", "6bbcd3e263222b1b"),
    "lagunaxs2-train-b1-t16384": ("e464f68f5f17c476", "e3bcdaf73b135ec0"),
}


def _plain(v):
    if isinstance(v, nn.LayerOutput):
        return f"<{v.name}>"
    if isinstance(v, dict):
        return {str(k): _plain(x) for k, x in sorted(
            v.items(), key=lambda kv: str(kv[0]))}
    if isinstance(v, (list, tuple)):
        return [_plain(x) for x in v]
    if callable(v):
        return "<fn>"
    return v if isinstance(v, (int, float, str, bool, type(None))) else repr(v)


def _graph_digest(which):
    if which == "lfm2":
        from paddle_tpu.models import lfm2_moe_net

        nn.reset_naming()
        cost, extras = lfm2_moe_net(
            50, hidden_size=64, layer_types=["conv", "full_attention", "conv"],
            num_dense_layers=1, intermediate_size=96,
            moe_intermediate_size=48, num_experts=8, num_experts_per_tok=2,
            num_attention_heads=4, num_key_value_heads=2)
    else:
        cfg = manifest.cell(which)["config"]
        cost, extras = manifest.program(cfg).net(cfg)
    topo = nn.Topology([cost] + extras)
    leaves = sorted((k, list(s.shape)) for k, s in topo.param_specs.items())
    layers = [(l.name, l.layer_type, l.size, [p.name for p in l.parents],
               l.meta.get("remat"),
               _plain({k: v for k, v in l.meta.get("config", {}).items()
                       if k != "call_id"})) for l in topo.layers]
    sha = lambda o: hashlib.sha256(                            # noqa: E731
        json.dumps(o, sort_keys=True).encode()).hexdigest()[:16]
    return sha(leaves), sha(layers)


@pytest.mark.parametrize("which", sorted(OLDER_GRAPHS))
def test_older_models_graphs_are_what_they_were(which):
    """``param_name``, ``loops``, ``post_norm`` and ``exit_gate`` at their
    defaults build what was built: every parameter's name and shape, every
    layer's name, parents and recomputation block, and every captured
    constructor call of the six older models are the parent commit's."""
    assert _graph_digest(which) == OLDER_GRAPHS[which]


def test_laguna_is_bit_for_bit_what_it_was():
    """The newest sibling's loss and gradients on the CPU are the parent
    commit's to the last bit (the five older ones' pins are in
    tests/test_qwen3_next.py and tests/test_laguna.py): read with these
    lines on f42d165."""
    import test_laguna as tl

    prog = manifest.load_module(os.path.join(
        ROOT, "benchmark", "programs", "laguna-xs.2-ep32.py"), "laguna_prog")
    cost, _ = prog.net(dict(tl.CFG, recompute_layers=[0, 1, 2]))
    topo = nn.Topology(cost)
    params, _ = topo.init(jax.random.PRNGKey(7))
    batch = tl.feed(4, t=64)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: topo.apply(
        p, {}, batch, train=True)[0]["cost"].value))(params)
    assert (float(loss), float(sum(
        jnp.sum(jnp.abs(g)) for g in jax.tree_util.tree_leaves(grads)))) == (
            4.385963439941406, 1472.054931640625)
