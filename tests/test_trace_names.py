"""The names the program writes into a profiler trace (docs/observability.md
"Names on the device trace"): a literal ``name=`` on every ``pl.pallas_call``,
the model scopes inside both jitted training steps (names and metadata only:
the equations stay what they were), and the ``paddle_tpu.trainer.*`` host
spans of ``SGDTrainer.train``, read back from a trace recorded here on the
CPU.  The per-layer metrics that read them are tested in
tests/benchmark/test_trace_scopes.py."""

import ast
import contextlib
import glob
import os
import re

import numpy as np
import pytest

import jax

import paddle_tpu.nn as nn
from paddle_tpu.param.optimizers import Adam, Optimizer
from paddle_tpu.trainer import SGDTrainer
from paddle_tpu.trainer.trainer import SPAN_PREFIX

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KERNELS = ["lstm_seq_fwd", "gru_seq_fwd", "lstm_seq_bwd", "gru_seq_bwd",
           "lse_rows", "attn_dec_fwd", "attn_dec_bwd", "ce_readout_fwd",
           "ce_readout_bwd", "topk_lse_readout", "topk_lse_logits",
           "flash_attn_win_fwd", "flash_attn_fwd", "flash_attn_sel_fwd",
           "flash_attn_win_bwd", "flash_attn_bwd", "flash_attn_sel_bwd",
           "moe_gmm", "moe_tgmm",
           "gdn_chunk_fwd", "gdn_chunk_bwd", "ssd_chunk_fwd", "ssd_chunk_bwd",
           "gdn_prep_fwd", "gdn_prep_bwd", "mamba_prep_fwd", "mamba_prep_bwd",
           "indexer_scores", "topk_select", "indexer_loss", "rotary_turn"]


# -- (a) kernels ----------------------------------------------------------


def _pallas_call_sites():
    """``name=`` of every ``pl.pallas_call(...)`` in ops/pallas_kernels.py,
    in the file's order; ``None`` where a call passes none or no literal."""
    path = os.path.join(ROOT, "paddle_tpu", "ops", "pallas_kernels.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    sites = []
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "pallas_call"):
            name = next((kw.value for kw in node.keywords
                         if kw.arg == "name"), None)
            sites.append((node.lineno, name.value if isinstance(
                name, ast.Constant) and isinstance(name.value, str)
                else None))
    return [name for _, name in sorted(sites)]


@pytest.mark.parametrize("site", range(len(KERNELS)))
def test_every_pallas_call_passes_a_literal_unique_name(site):
    names = _pallas_call_sites()
    assert len(names) == len(KERNELS), "a pallas_call site came or went: " \
        "name it, and list it in docs/observability.md"
    assert names[site] == KERNELS[site]
    assert names.count(names[site]) == 1


# -- (b) scopes in the two jitted steps -----------------------------------


def _name_stacks(jaxpr, out=None):
    """Every equation's name stack, through sub-jaxprs."""
    out = set() if out is None else out
    for eqn in jaxpr.eqns:
        out.add(str(eqn.source_info.name_stack))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _name_stacks(sub, out)
    return out


def _scope_names(stacks):
    import re

    return {part for s in stacks for part in re.split(r"[/()]+", s) if part}


@contextlib.contextmanager
def _no_scopes(monkeypatch):
    """The program as it was before it named anything."""
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope",
                  lambda name: contextlib.nullcontext())
        m.setattr(Optimizer, "update", Optimizer.update.__wrapped__)
        yield


def _seq2seq_demo():
    from paddle_tpu.models import Seq2SeqAttention

    model = Seq2SeqAttention(src_vocab=50, trg_vocab=50, emb_dim=8,
                             enc_dim=8, dec_dim=8, att_dim=8)
    opt = Adam(learning_rate=1e-3)
    return model, opt, model.init(jax.random.PRNGKey(0))


def _seq2seq_step_jaxpr():
    from benchmark.manifest import load_module

    demo = load_module(os.path.join(ROOT, "demo", "seqToseq", "train.py"),
                       "trace_names_seqToseq_demo")
    model, opt, params = _seq2seq_demo()
    batch = {"src_ids": np.ones((4, 5), np.int32),
             "src_len": np.full((4,), 5, np.int32),
             "trg_in": np.ones((4, 6), np.int32),
             "trg_next": np.ones((4, 6), np.int32),
             "trg_len": np.full((4,), 6, np.int32)}
    step = demo.make_train_step(model, opt)
    return jax.make_jaxpr(step)(params, opt.init_state(params), batch)


def _lstm_trainer():
    from paddle_tpu.models import lstm_benchmark_net

    nn.reset_naming()
    cost, _ = lstm_benchmark_net(50, emb_dim=8, hid_dim=8, num_layers=2,
                                 num_classes=2)
    return SGDTrainer(cost, Adam(learning_rate=1e-3), seed=0)


def _trainer_step_jaxpr():
    tr = _lstm_trainer()
    feed = {name: value for name, value in zip(
        sorted(l.name for l in tr.topology.layers if l.is_data),
        [np.zeros((4,), np.int32),
         (np.ones((4, 6), np.int32), np.full((4,), 6, np.int32))])}
    return jax.make_jaxpr(tr._step_fn)(
        tr.params, tr.state, tr.opt_state, {}, jax.random.PRNGKey(0), feed)


@pytest.mark.parametrize("build, scopes", [
    (_seq2seq_step_jaxpr,
     {"encoder", "decoder", "readout_ce", "optimizer_apply"}),
    (_trainer_step_jaxpr,
     {"forward", "emb", "lstm0", "lstm1", "logits", "optimizer_apply"}),
], ids=["seqToseq_demo_step", "trainer_step"])
def test_jitted_step_holds_the_scopes_and_the_same_equations(
        build, scopes, monkeypatch):
    named = build()
    assert scopes <= _scope_names(_name_stacks(named.jaxpr))
    with _no_scopes(monkeypatch):
        bare = build()
    assert not scopes & _scope_names(_name_stacks(bare.jaxpr))
    # names and metadata only: the same equations on the same shapes
    assert str(named) == str(bare)


def test_nemotron_h_step_holds_its_layers_scopes():
    """The scopes the Nemotron-H cell's per-layer metrics read (PR 43): a
    layer's own (``mamba<i>``, ``attn<i>``, ``moe<i>``, ``i`` the published
    index) and, inside it, ``mamba_proj`` / ``ssd_scan``, ``attn_core`` and
    the expert layer's five."""
    from paddle_tpu.models import nemotron_h_net

    nn.reset_naming()
    cost, extras = nemotron_h_net(
        50, hybrid_override_pattern="ME*", hidden_size=16, mamba_num_heads=2,
        mamba_head_dim=4, n_groups=1, ssm_state_size=4, conv_kernel=4,
        num_attention_heads=2, num_key_value_heads=1, head_dim=4,
        moe_intermediate_size=8, moe_shared_expert_intermediate_size=8,
        n_routed_experts=4, num_experts_per_tok=2)
    topo = nn.Topology([cost] + extras)
    params, _ = topo.init(jax.random.PRNGKey(0))
    ids = (np.ones((2, 6), np.int32), np.full((2,), 6, np.int32))
    feed = {"tokens": ids, "next_tokens": ids}
    grad = jax.make_jaxpr(jax.grad(lambda p: topo.apply(
        p, {}, feed, train=True)[0]["cost"].value))(params)
    names = _scope_names(_name_stacks(grad.jaxpr))
    assert {"mamba0", "mamba_proj", "ssd_scan", "moe1", "moe_routing",
            "moe_grouping", "moe_experts", "moe_combine", "moe_shared",
            "attn2", "attn_core", "norm0", "norm1", "norm2", "norm_out",
            "cost"} <= names
    assert not {"norm_op0", "norm_ffn0", "mlp0", "moe0", "mamba1"} & names


def test_keye_vl2_step_holds_its_layers_scopes():
    """The scopes the Keye-VL-2.0 cell's per-layer metrics read (PR 47): a
    layer's own (``attn<i>``, ``moe<i>``) and, inside ``attn<i>``,
    ``indexer``, ``topk_select``, ``attn_core`` and ``indexer_loss``, in the
    forward and (``attn_core``, ``indexer_loss``) in the backward."""
    from paddle_tpu.models import keye_vl2_net

    nn.reset_naming()
    cost, extras = keye_vl2_net(
        50, hidden_size=16, num_hidden_layers=2, num_attention_heads=2,
        num_key_value_heads=1, head_dim=4, indexer_num_heads=2,
        indexer_head_dim=4, topk=3, moe_intermediate_size=8, num_experts=4,
        num_experts_per_tok=2)
    assert [e.name for e in extras if "attn" in e.name] == [
        "attn0_kept", "attn0_kl", "attn1_kept", "attn1_kl"]
    topo = nn.Topology([cost] + extras)
    params, _ = topo.init(jax.random.PRNGKey(0))
    ids = (np.ones((2, 6), np.int32), np.full((2,), 6, np.int32))
    feed = {"tokens": ids, "next_tokens": ids}
    grad = jax.make_jaxpr(jax.grad(lambda p: topo.apply(
        p, {}, feed, train=True)[0]["cost"].value))(params)
    names = _scope_names(_name_stacks(grad.jaxpr))
    assert {"attn0", "attn1", "indexer", "topk_select", "attn_core",
            "indexer_loss", "rotary", "moe0", "moe1", "moe_routing",
            "moe_experts", "norm_op0", "norm_ffn1", "norm_out",
            "cost"} <= names
    assert not {"mlp0", "moe_shared", "norm0"} & names


def test_laguna_step_holds_its_layers_scopes():
    """The scopes the Laguna-XS.2 cell's per-layer metrics read (PR 50): a
    layer's own (``attn<i>``, ``mlp0``, ``moe<i>``) and, inside ``attn<i>``,
    ``attn_core`` in a full layer and ``attn_window`` in a window layer,
    forward and backward; the window layers alone bring a counter."""
    from paddle_tpu.models import laguna_net

    nn.reset_naming()
    cost, extras = laguna_net(
        50, hidden_size=16,
        layer_types=["full_attention", "sliding_attention",
                     "full_attention"],
        mlp_layer_types=["dense", "sparse", "sparse"],
        num_attention_heads_per_layer=[2, 4, 2], num_key_value_heads=2,
        head_dim=4, sliding_window=3,
        rope_parameters={
            "full_attention": dict(
                rope_theta=100.0, rope_type="yarn", factor=4.0,
                original_max_position_embeddings=4, beta_slow=1,
                beta_fast=4, partial_rotary_factor=0.5),
            "sliding_attention": dict(rope_type="default", rope_theta=1e4,
                                      partial_rotary_factor=1)},
        intermediate_size=24, moe_intermediate_size=8,
        shared_expert_intermediate_size=8, num_experts=4,
        num_experts_per_tok=2, moe_routed_scaling_factor=2.5)
    assert [e.name for e in extras if "attn" in e.name] == ["attn1_pairs"]
    topo = nn.Topology([cost] + extras)
    params, _ = topo.init(jax.random.PRNGKey(0))
    ids = (np.ones((2, 6), np.int32), np.full((2,), 6, np.int32))
    feed = {"tokens": ids, "next_tokens": ids}
    grad = jax.make_jaxpr(jax.grad(lambda p: topo.apply(
        p, {}, feed, train=True)[0]["cost"].value))(params)
    stacks = _name_stacks(grad.jaxpr)
    names = _scope_names(stacks)
    assert {"attn0", "attn1", "attn2", "attn_core", "attn_window", "rotary",
            "mlp0", "moe1", "moe2", "moe_routing", "moe_experts",
            "moe_shared", "norm_op0", "norm_ffn2", "norm_out",
            "cost"} <= names
    assert not {"moe0", "mlp1", "indexer", "norm0"} & names
    # the window's core under the window layer alone, the full core under
    # the full layers alone
    assert not [s for s in stacks if "attn_window" in s and "attn1" not in s]
    assert not [s for s in stacks if "attn_core" in s and "attn1" in s]
    # the rotary embedding (PR 51) under every attention layer's own scope:
    # forward, recomputed in the layer's block, and backward
    # (the custom VJP's backward pass carries the layer's bare name),
    # and nowhere else
    turned = {s for s in stacks if "rotary" in _scope_names({s})}
    assert turned == {f"{way}/rotary" for i in range(3) for way in (
        f"jvp(attn{i})", f"rematted_computation/attn{i}", f"attn{i}")}


def test_smallthinker_step_holds_its_layers_scopes():
    """The scopes the SmallThinker cell's per-layer metrics read (PR 57):
    ``moe<i>/moe_routing`` is the early router's OWN layer (the sort by
    expert under ``moe_grouping`` inside it), traced AHEAD of ``attn<i>``;
    ``attn_core`` under the full layer alone, which holds no ``rotary``;
    ``attn_window`` and ``rotary`` under the window layers alone;
    ``moe_experts`` inside ``moe<i>``; and the counter
    ``moe_gate_zero_units`` an expert layer beside the older two."""
    from paddle_tpu.models import smallthinker_net

    nn.reset_naming()
    cost, extras = smallthinker_net(
        50, hidden_size=16, num_attention_heads=7, num_key_value_heads=1,
        head_dim=4, sliding_window_layout=[0, 1], rope_layout=[0, 1],
        sliding_window_size=3, rope_theta=1.5e6, moe_ffn_hidden_size=8,
        moe_num_primary_experts=4, moe_num_active_primary_experts=2)
    counters = [(e.name, e.meta["obs_counter"]["name"]) for e in extras]
    assert counters == [
        ("moe0_load", "moe_assignments"),
        ("moe0_uncomputed", "moe_uncomputed_assignments"),
        ("moe0_gate_zero", "moe_gate_zero_units"),
        ("attn1_pairs", "window_attn_pairs"),
        ("moe1_load", "moe_assignments"),
        ("moe1_uncomputed", "moe_uncomputed_assignments"),
        ("moe1_gate_zero", "moe_gate_zero_units")]
    topo = nn.Topology([cost] + extras)
    params, _ = topo.init(jax.random.PRNGKey(0))
    ids = (np.ones((2, 6), np.int32), np.full((2,), 6, np.int32))
    feed = {"tokens": ids, "next_tokens": ids}
    fwd = jax.make_jaxpr(lambda p: topo.apply(
        p, {}, feed, train=True)[0]["cost"].value)(params)
    grad = jax.make_jaxpr(jax.grad(lambda p: topo.apply(
        p, {}, feed, train=True)[0]["cost"].value))(params)
    stacks = _name_stacks(grad.jaxpr)
    names = _scope_names(stacks)
    assert {"attn0", "attn1", "attn_core", "attn_window", "rotary", "moe0",
            "moe1", "moe_routing", "moe_grouping", "moe_experts",
            "moe_combine", "norm_op0", "norm_ffn1", "norm_out",
            "cost"} <= names
    assert not {"mlp0", "moe_shared", "indexer", "norm0"} & names
    assert not [s for s in stacks if "attn_window" in s and "attn1" not in s]
    assert not [s for s in stacks if "attn_core" in s and "attn0" not in s]
    assert not [s for s in stacks if "rotary" in s and "attn1" not in s]
    # the router's layer: its scope is moe<i>/moe_routing, the sort inside
    routed = [s for s in stacks if "moe_routing" in _scope_names({s})]
    assert routed and all(re.search(r"moe[01]\)*/moe_routing", s)
                          for s in routed)
    assert any(re.search(r"moe0\)*/moe_routing\)*/moe_grouping", s)
               for s in routed)
    # ... and in the program's order it stands before its layer's attention
    order = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            order.append(str(eqn.source_info.name_stack))
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(fwd.jaxpr)
    first = lambda what: next(i for i, s in enumerate(order)   # noqa: E731
                              if what in s)
    assert first("moe0/moe_routing") < first("attn0")
    assert first("attn0") < first("moe1/moe_routing") < first("attn1")


def test_ouro_step_holds_its_passes_and_exits_scopes():
    """The scopes the Ouro-2.6B cell's per-layer metrics read (PR 54): the
    pass ``loop<t>`` OUTSIDE the layer's own scope (``attn<i>``, ``mlp<i>``
    and their norms), forward, recomputed and backward; ``exits`` with
    ``exit_head``, ``exit_gate`` and ``exit_mix`` inside and no pass's scope
    around them; the three counters ride the step as extra outputs."""
    from paddle_tpu.models import ouro_net

    nn.reset_naming()
    cost, extras = ouro_net(
        50, hidden_size=16, layer_types=["full_attention"] * 2,
        num_attention_heads=4, num_key_value_heads=4, head_dim=4,
        intermediate_size=24, total_ut_steps=4)
    assert cost.name == "exits/exit_mix"
    assert {e.name: e.meta["obs_counter"] for e in extras} == {
        "exit_mass": {"name": "loop_exit_mass", "index_label": "step",
                      "first_index": 1},
        "exit_ce": {"name": "loop_exit_ce", "index_label": "step",
                    "first_index": 1},
        "exit_entropy": {"name": "loop_exit_entropy"}}
    topo = nn.Topology([cost] + extras)
    params, _ = topo.init(jax.random.PRNGKey(0))
    ids = (np.ones((2, 6), np.int32), np.full((2,), 6, np.int32))
    feed = {"tokens": ids, "next_tokens": ids}
    grad = jax.make_jaxpr(jax.grad(lambda p: topo.apply(
        p, {}, feed, train=True)[0][cost.name].value))(params)
    stacks = _name_stacks(grad.jaxpr)
    names = _scope_names(stacks)
    assert {"loop0", "loop1", "loop2", "loop3", "attn0", "attn1", "mlp0",
            "mlp1", "attn_core", "rotary", "norm_op0", "post_op0",
            "norm_ffn1", "post_ffn1", "res_op0", "res_ffn1", "exits",
            "pass0", "pass3", "norm_out", "exit_head", "exit_gate",
            "exit_mix"} <= names
    assert not {"loop4", "moe0", "cost", "pass4"} & names
    # every layer's operation lies under a pass, the pass outside the layer
    for s in stacks:
        parts = [p for p in s.replace("(", "/").replace(")", "/").split("/")
                 if p]
        if any(p in ("attn0", "attn1", "mlp0", "mlp1") for p in parts):
            at = min(parts.index(p) for p in parts
                     if p in ("attn0", "attn1", "mlp0", "mlp1"))
            assert parts[at - 1] in ("loop0", "loop1", "loop2", "loop3"), s
        if "exits" in parts:         # an exit belongs to no pass's scope
            assert not {"loop0", "loop1", "loop2", "loop3"} & set(parts), s
    # each pass's core under that pass: forward, recomputed and backward
    for t in range(4):
        under = {s for s in stacks if f"loop{t}" in _scope_names({s})
                 and "attn_core" in _scope_names({s})}
        assert any("rematted_computation" in s for s in under), t
        assert any("jvp" in s for s in under), t
    # the gate is read after every pass but the last
    gated = {s.split("exits/")[1].split("/")[0] for s in stacks
             if "exit_gate" in s and "exits/" in s}
    assert gated == {"pass0", "pass1", "pass2"}


def _primitives_under(jaxpr, scope, inside=False, out=None):
    """The primitives of the equations ``scope`` encloses, through
    sub-jaxprs: an equation is inside when its own name stack holds the
    scope or the equation that carries its jaxpr is inside.  A carrier
    (``pjit``, ``cond``) counts through what it carries, not itself."""
    from collections import Counter

    out = Counter() if out is None else out
    for eqn in jaxpr.eqns:
        within = inside or scope in _scope_names(
            {str(eqn.source_info.name_stack)})
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        if within and not subs:
            out[eqn.primitive.name] += 1
        for sub in subs:
            _primitives_under(sub, scope, within, out)
    return out


def _seq2seq_update_jaxpr():
    _, opt, params = _seq2seq_demo()
    return jax.make_jaxpr(opt.update)(params, params, opt.init_state(params))


def _trainer_update_jaxpr():
    # with the bad-step guard's predicate, as the trainer's step hands it
    # down: the selects that hold a bad step are the update's own equations
    tr = _lstm_trainer()
    assert tr.guard_nonfinite
    return jax.make_jaxpr(lambda p, g, o, finite: tr.optimizer.update(
        p, g, o, lr_scales=tr.lr_scales, decays=tr.decays,
        statics=tr.statics, sparse_rows=tr.sparse_rows, finite=finite))(
        tr.params, tr.params, tr.opt_state, np.bool_(True))


@pytest.mark.parametrize("build_step, build_update", [
    (_seq2seq_step_jaxpr, _seq2seq_update_jaxpr),
    (_trainer_step_jaxpr, _trainer_update_jaxpr),
], ids=["seqToseq_demo_step", "trainer_step"])
def test_optimizer_apply_encloses_every_equation_of_the_update(
        build_step, build_update):
    """``device_ms_per_step.optimizer`` reads the scope ``optimizer_apply``:
    in both jitted steps the scope holds exactly the equations that
    ``Optimizer.update`` traces to on its own, every leaf's chain among
    them, so none of the update's device time goes unscoped."""
    update = build_update().jaxpr
    alone = _primitives_under(update, "optimizer_apply")
    assert alone == _primitives_under(update, "optimizer_apply", inside=True)
    assert alone["sqrt"] >= 2          # Adam's chain, once per leaf
    assert _primitives_under(build_step().jaxpr, "optimizer_apply") == alone


# -- (c) the trainer's host spans -------------------------------------------


#: what may lie between two ``with`` blocks of the loop: a test of a
#: condition, an assignment, the exit of one annotation and the entry of the
#: next, a phase's own record in ``_ph`` (1-13 us here, the smallest of five
#: readings); a line of work left without a span costs more (the key split,
#: while the host made it, was 400-600 us here)
GLUE_NS = 50_000


def _trace_of_one_pass(tmp_path, batches=5, feeder=None, **trainer_kw):
    """``SGDTrainer.train`` over a few toy batches under the profiler: the
    trace's file."""
    nn.reset_naming()
    x = nn.data("x", size=4)
    cost = nn.mse_cost(input=nn.fc(x, 2, name="o"),
                       label=nn.data("y", size=2))
    tr = SGDTrainer(cost, Adam(learning_rate=0.01), seed=0, **trainer_kw)
    rng = np.random.RandomState(0)

    def reader():
        for _ in range(batches):
            yield {"x": rng.rand(4, 4).astype(np.float32),
                   "y": rng.rand(4, 2).astype(np.float32)}

    tr.train(reader, num_passes=1, feeder=feeder)   # compiles outside
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        tr.train(reader, num_passes=1, feeder=feeder)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(found) == 1
    return found[0]


def _threads_with(path, prefix):
    """The events under ``prefix`` of every thread that has one, as
    ``(start, end, name without the prefix, stats)`` by start."""
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            spans = [(e.start_ns, e.start_ns + e.duration_ns,
                      e.name[len(prefix):], dict(e.stats))
                     for e in line.events if e.name.startswith(prefix)]
            if spans:
                lines.append(sorted(spans, key=lambda s: (s[0], -s[1])))
    return lines


def _loop_spans(path):
    """The ``paddle_tpu.trainer.*`` events of the thread that drove the
    loop."""
    lines = _threads_with(path, SPAN_PREFIX)
    assert len(lines) == 1, "the loop's spans are on one thread"
    return lines[0]


def _holes_of_one_pass(timeline, tmp_path, monkeypatch):
    """One traced pass: asserts which spans there are, their order and
    their nesting, and returns what no span covers, iteration by
    iteration (ns)."""
    from paddle_tpu.utils.flags import FLAGS

    monkeypatch.setattr(FLAGS, "obs_timeline", timeline)
    spans = _loop_spans(_trace_of_one_pass(tmp_path))

    def inside(outer):
        return [s for s in spans if s is not outer
                and outer[0] <= s[0] and s[1] <= outer[1]]

    iterations = [s for s in spans if s[2] == "iteration"]
    # one per batch, and the one that found the reader empty
    assert [s[3]["step_num"] for s in iterations] == [0, 1, 2, 3, 4, 5]
    assert [c[2] for c in inside(iterations[-1])] == ["poll", "data_wait"]
    holes = []
    for it in iterations[:-1]:
        children = [c[2] for c in inside(it)]
        assert children == ["poll", "data_wait", "callback", "prepare",
                            "step", "step.rng", "step.dispatch",
                            "step.post", "step.sync", "extras",
                            "callback", "close"]
        step = next(c for c in inside(it) if c[2] == "step")
        in_step = inside(step)
        assert [c[2] for c in in_step] == ["step.rng", "step.dispatch",
                                           "step.post", "step.sync"]
        # ONE blocking fetch a step (the guard is on by default): the flag
        # and the loss come together, under the first one waited for
        assert [c[3]["reason"] for c in in_step[3:]] == ["guard"]
        # the host's turn-around is named in full: from the end of one
        # step.dispatch to the start of the next, so all through the
        # iteration, what no child of ``iteration`` or of ``step`` covers
        # is the glue between two ``with`` blocks
        named = [c for c in inside(it) if c[2] != "step"]
        edges = [it[0]] + [t for c in named for t in c[:2]] + [it[1]]
        holes.append([edges[i + 1] - edges[i]
                      for i in range(0, len(edges), 2)])
    # what a pass does once (here the EndPass callback) is outside them
    outside = [s[2] for s in spans if s[2] != "iteration" and not any(
        it[0] <= s[0] and s[1] <= it[1] for it in iterations)]
    assert outside == ["callback"]
    return holes


@pytest.mark.parametrize("timeline", [True, False],
                         ids=["obs_timeline", "no_obs_timeline"])
def test_trainer_spans_nest_on_the_profilers_clock(timeline, tmp_path,
                                                   monkeypatch):
    holes = _holes_of_one_pass(timeline, tmp_path, monkeypatch)
    # the same holes every iteration, and none negative: the named spans
    # follow one another and none overlaps the next.  How long a hole is,
    # is the ``_timed`` twin's to say: under load it is the scheduler's.
    assert len({len(h) for h in holes}) == 1
    assert min(min(h) for h in holes) >= 0, holes


@pytest.mark.slow
@pytest.mark.parametrize("timeline", [True, False],
                         ids=["obs_timeline", "no_obs_timeline"])
def test_trainer_spans_nest_on_the_profilers_clock_timed(timeline, tmp_path,
                                                         monkeypatch):
    holes = _holes_of_one_pass(timeline, tmp_path, monkeypatch)
    # each hole's smallest reading, so that a thread switch inside one of
    # them does not decide (ns on the CPU)
    assert max(min(h) for h in zip(*holes)) < GLUE_NS, holes


@pytest.mark.parametrize("mode, reason", [
    ("guard", "guard"), ("no_guard", "loss"), ("amp", "amp")])
def test_a_step_is_one_executable_and_one_fetch(mode, reason, tmp_path,
                                                monkeypatch):
    """Between two steps the host launches ONE program (the key is split
    inside it) and blocks ONCE: whichever flags the step has, they and the
    loss start their copies at dispatch and one ``step.sync`` waits for
    them, named after the first one read."""
    from paddle_tpu.utils.flags import FLAGS

    monkeypatch.setattr(FLAGS, "amp", mode == "amp")
    path = _trace_of_one_pass(tmp_path,
                              guard_nonfinite=(mode != "no_guard"))
    spans = _loop_spans(path)
    iterations = [s for s in spans if s[2] == "iteration"][:-1]
    assert len(iterations) == 5
    # what the runtime itself records of a launch, on any thread
    launches = [s[0] for line in _threads_with(path, "PjRt") for s in line
                if s[2].endswith("::Execute")]
    jitted = [s for line in _threads_with(path, "PjitFunction(")
              for s in line]
    for it in iterations:
        assert len([t for t in launches if it[0] <= t <= it[1]]) == 1
        assert {s[2] for s in jitted if it[0] <= s[0] <= it[1]} == {
            "step_and_split)"}
        syncs = [s for s in spans
                 if s[2] == "step.sync" and it[0] <= s[0] <= it[1]]
        assert [s[3]["reason"] for s in syncs] == [reason]
        # the key's span is still there (the readers' marker), and empty
        rng = [s for s in spans
               if s[2] == "step.rng" and it[0] <= s[0] <= it[1]]
        assert len(rng) == 1 and rng[0][1] - rng[0][0] < GLUE_NS


@pytest.mark.parametrize("depth", [2, 0], ids=["prefetch_2", "prefetch_off"])
def test_prefetch_thread_names_its_work_on_a_thread_of_its_own(
        depth, tmp_path, monkeypatch):
    """With ``--prefetch_depth`` the loop's ``prepare`` is an attribute read:
    the feeder and the transfer run on the ``batch-prefetch`` thread, which
    records ``paddle_tpu.data.prefetch.prepare`` / ``.h2d`` / ``.put``, one of
    each a batch, under a prefix the loop's readers do not match."""
    from paddle_tpu.data.feeder import BatchPrefetcher
    from paddle_tpu.utils.flags import FLAGS

    monkeypatch.setattr(FLAGS, "prefetch_depth", depth)
    # the CPU aliases host buffers, so the trainer passes no transfer here:
    # make it pass the one it passes on an accelerator
    monkeypatch.setattr(SGDTrainer, "_h2d_measurable", True)
    prefix = BatchPrefetcher.SPAN_PREFIX
    assert not prefix.startswith(SPAN_PREFIX)
    path = _trace_of_one_pass(tmp_path, feeder=lambda batch: dict(batch))
    threads = _threads_with(path, prefix)
    loop = {s[2] for s in _loop_spans(path)}    # still on ONE thread
    assert {"iteration", "prepare", "step.rng"} <= loop
    # the transfer is the loop's own phase only while nothing prefetches
    assert "put" not in loop and ("h2d" in loop) == (not depth)
    if not depth:
        assert threads == []
        return
    assert len(threads) == 1
    assert [s[2] for s in threads[0]] == ["prepare", "h2d", "put"] * 5
