"""The decoder-only stack that the models of this package share: an
embedding, pre-norm residual layers whose sequence mixer is chosen by kind
and whose feed-forward is a gated MLP in the first ``num_dense_layers``
layers (a model may have none) and a dropless expert layer in the rest, a
final RMSNorm, and the next-token cost over a head that is the embedding or a
matrix of its own.

Two shapes of layer.  By default layer ``i`` has both sub-blocks: ``h = x +
Op_i(RMSNorm(x))``, ``y = h + FFN_i(RMSNorm(h))``.  With ``ffn_layer_type``
given, a layer is ONE sub-block, ``y = x + f_i(RMSNorm(x))``: the
feed-forward alone where its kind is ``ffn_layer_type``, its kind's mixer
alone elsewhere (two mixers may then stand side by side).

Layer names (and so the device trace's scopes and the parameters'
prefixes): what the mixer's builder names its layer, ``mlp<i>`` / ``moe<i>``,
``emb``, ``norm_out``, ``cost``; the norms ``norm_op<i>`` and ``norm_ffn<i>``
of a layer of two sub-blocks, ``norm<i>`` of a layer of one.

A stack may run its layers several times over ONE set of weights
(``loops``): pass ``t``'s layers are named ``loop<t>/attn<i>``,
``loop<t>/mlp<i>`` and so on (the device trace's scope path of an operation
then holds the pass AND the layer) while their leaves keep the names of a
stack of one pass (``_attn<i>.wq``): ``Topology`` holds one spec and one
array a name, and a leaf's gradient is the sum over its uses.  The final
norm runs after EVERY pass, its result the next pass's input.  With
``post_norm`` a sub-block's result is normed again before it is added
(sandwich: ``h = x + RMSNorm(Op_i(RMSNorm(x)))``; norms ``post_op<i>`` and
``post_ffn<i>``).  With ``exit_gate`` every pass is an exit: the head's
cross-entropy of every token after each pass, a gate's logit a token, and
the expected loss over the exits (``nn.loop_exit_cost``); the exits' layers
are ``exits/pass<t>/norm_out``, ``.../exit_head``, ``.../exit_gate`` and
``exits/exit_mix``, their leaves ``_norm_out.w``, ``_cost.w``,
``_exit_gate.w`` / ``.b``.

A mixer may bring terms of its own to the loss (a learned indexer's): its
builder then returns ``(layer, riders)``, and the stack's cost is ``(sum of
the cross-entropies + sum of the riders marked as terms) / tokens``.
"""

from __future__ import annotations

from typing import Callable, Mapping, Optional, Sequence

import paddle_tpu.nn as nn

__all__ = ["decoder_stack"]


def decoder_stack(vocab_size: int, *, hidden_size: int,
                  layer_types: Sequence[str],
                  mixers: Mapping[str, Callable], num_dense_layers: int,
                  intermediate_size: int, moe_intermediate_size: int,
                  num_experts: int, num_experts_per_tok: int,
                  norm_topk_prob: bool = True,
                  routed_scaling_factor: float = 1.0,
                  shared_size: int = 0, scoring: str = "sigmoid",
                  shared_gate: bool = False,
                  experts_held: Optional[Sequence[int]] = None,
                  norm_eps: float = 1e-5, zero_centered_norm: bool = False,
                  tie_head: bool = True, recompute_layers=True,
                  ffn_layer_type: Optional[str] = None,
                  expert_act: str = "gated_silu",
                  selection_bias: bool = True, loops: int = 1,
                  post_norm: bool = False, exit_gate: bool = False,
                  exit_beta: float = 0.1, early_router: bool = False):
    """Returns ``(cost, extras)``: the mean next-token cross-entropy over
    ``tokens`` / ``next_tokens`` (two int sequence feeds of one length), and
    two extra outputs per expert layer, marked for the counters
    ``moe_assignments`` (assignments per expert held) and
    ``moe_uncomputed_assignments`` (the trainer feeds them when the extras
    are passed as ``extra_outputs``).

    ``mixers[kind](normed, i)`` builds layer ``i``'s sequence mixer over the
    normed input, for every ``kind`` in ``layer_types``: the layer, or
    ``(layer, riders)`` where the mixer has auxiliary outputs of its own:
    a rider whose ``meta["loss_term"]`` is set is a term of the loss (its
    value a SUM over the batch's tokens, added to the cross-entropies' sum
    before the division by the tokens), one with ``meta["obs_counter"]``
    joins the extras; a rider may be both.  ``experts_held =
    (first, count)``: the share of the experts this chip holds (all by
    default); ``vocab_size`` may likewise be a slice of the published
    vocabulary, ids, logits and loss then being over the slice.
    ``shared_size``: hidden units of the shared experts beside the routed
    ones (0: none), ``shared_gate``: whether a sigmoid gate weighs them;
    ``scoring``: how the router scores (``nn.expert_mlp``).
    ``num_dense_layers`` may be 0: every layer is then an expert layer.
    ``zero_centered_norm``: the stack's RMSNorms are ``x / rms(x) * (1 +
    w)``.  ``ffn_layer_type``: every layer is one sub-block with one norm
    and one residual add, the feed-forward alone where ``layer_types[i]`` is
    this kind and the kind's mixer alone elsewhere (``num_dense_layers``
    counts layers as before: a feed-forward layer ``i`` below it is a gated
    MLP).  ``expert_act``: the experts' form, ``selection_bias``: whether
    sigmoid scoring has its ``expert_bias`` leaf (``nn.expert_mlp``).
    ``early_router``: layer ``i``'s router reads the MIXER's normed input
    (``norm_op<i>``), not the feed-forward's: its choice, its weights and
    its sort by expert are the layer ``moe<i>/moe_routing``, which runs
    ahead of the mixer, and its gradient reaches ``norm_op<i>`` and ``x``,
    not ``h``; the experts read ``norm_ffn<i>`` as before (a layer of two
    sub-blocks).  Where the experts' form counts them
    (``expert_act="gated_relu"``), the extras carry one more counter an
    expert layer, ``moe_gate_zero_units`` (the hidden units the ReLU gate
    made exactly zero).
    ``recompute_layers`` marks decoder layers as
    recomputation blocks, one block a layer: ``True`` for every layer, or
    the indices of the layers to recompute (the others hold their
    activations).

    ``loops``: the layers run this many times over the same leaves, the
    final norm after every pass; a mixer's builder is then called
    ``mixers[kind](normed, i, scope)`` with ``scope`` ``"loop<t>/"``, which
    it puts before its layer's name and NOT before its leaves' (``name=
    f"{scope}attn{i}", param_name=f"attn{i}"``); one recomputation block a
    (pass, layer).  Every feed-forward of a looped stack is a gated MLP
    (``nn.expert_mlp`` names its leaves after its layer), and a layer has
    both sub-blocks.  ``post_norm``: the sandwich norms.  ``exit_gate``: the
    cost is ``nn.loop_exit_cost`` over the passes' exits at ``exit_beta``,
    each exit (final norm, head, cross-entropy, gate) a recomputation block
    of its own where layers are recomputed, so that ONE exit's logits are
    alive at a time; the extras then carry the sums over the step's real
    tokens of each exit's mass, of each exit's cross-entropy and of the
    entropy, for the counters ``loop_exit_mass{step}``,
    ``loop_exit_ce{step}`` and ``loop_exit_entropy``."""
    if loops < 1:
        raise ValueError(f"a stack of {loops} passes")
    if loops > 1 and (ffn_layer_type is not None
                      or num_dense_layers < len(layer_types)):
        raise ValueError("a looped stack has layers of two sub-blocks whose "
                         "feed-forward is a gated MLP")
    if post_norm and ffn_layer_type is not None:
        raise ValueError("sandwich norms are of a layer of two sub-blocks")
    if early_router and ffn_layer_type is not None:
        raise ValueError("an early router reads the mixer's input: a layer "
                         "of two sub-blocks")
    tokens = nn.data("tokens", size=vocab_size, is_seq=True, dtype="int32")
    targets = nn.data("next_tokens", size=vocab_size, is_seq=True,
                      dtype="int32")
    emb = nn.embedding(tokens, hidden_size, name="emb",
                       param_attr=nn.ParamAttr(initial_std=0.02,
                                               init="normal"))
    x, extras, aux_costs = emb, [], []
    # passed only where asked for: the two older models' graphs (and their
    # captured configurations) stay what they were
    centred = {"zero_centered": True} if zero_centered_norm else {}
    routing = {}
    if scoring != "sigmoid":
        routing["scoring"] = scoring
    if shared_gate:
        routing["shared_gate"] = True
    if expert_act != "gated_silu":
        routing["expert_act"] = expert_act
    if not selection_bias:
        routing["selection_bias"] = False

    def feed_forward(normed, i, scope="", router_input=None):
        """Layer ``i``'s feed-forward over ``normed`` and what rides with it
        (an expert layer's counters, an early router's layer first)."""
        if i < num_dense_layers:
            shared = {"param_name": f"mlp{i}"} if scope else {}
            return nn.gated_mlp(normed, intermediate_size,
                                name=f"{scope}mlp{i}", **shared), []
        # passed only where asked for (the captured configurations, above)
        early = ({"router_input": router_input}
                 if router_input is not None else {})
        ffn = nn.expert_mlp(
            normed, moe_intermediate_size, num_experts=num_experts,
            experts_held=experts_held, top_k=num_experts_per_tok,
            norm_topk_prob=norm_topk_prob,
            routed_scaling_factor=routed_scaling_factor,
            shared_size=shared_size, name=f"moe{i}", **routing, **early)
        load = nn.get_output(ffn, "expert_load", size=1, name=f"moe{i}_load")
        load.meta["obs_counter"] = {
            "name": "moe_assignments", "labels": {"layer": f"moe{i}"},
            "index_label": "expert", "first_index": (experts_held
                                                     or (0,))[0]}
        dropped = nn.get_output(ffn, "uncomputed", size=1,
                                name=f"moe{i}_uncomputed")
        dropped.meta["obs_counter"] = {
            "name": "moe_uncomputed_assignments",
            "labels": {"layer": f"moe{i}"}}
        riders = [load, dropped]
        if ffn.meta.get("zero_gates"):
            zeros = nn.get_output(ffn, "gate_zero", size=1,
                                  name=f"moe{i}_gate_zero")
            zeros.meta["obs_counter"] = {"name": "moe_gate_zero_units",
                                         "labels": {"layer": f"moe{i}"}}
            riders.append(zeros)
        if router_input is not None:    # the router's own layer
            riders.insert(0, ffn.parents[-1])
        return ffn, riders

    def mixer(kind, normed, i, scope=""):
        """Layer ``i``'s mixer and what rides with it."""
        built = mixers[kind](normed, i, *([scope] if scope else []))
        layer, riders = built if isinstance(built, tuple) else (built, [])
        aux_costs.extend(r for r in riders if r.meta.get("loss_term"))
        extras.extend(r for r in riders if "obs_counter" in r.meta)
        return layer, riders

    def norm(x, name, scope=""):
        """An RMSNorm.  Under a ``scope`` the layer is ``<scope><name>`` and
        its leaf ``_<name>.w`` whatever the scope: one leaf for every pass."""
        shared = {"param_attr": nn.ParamAttr(
            name=f"_{name}.w", init="zeros" if zero_centered_norm else "ones")
        } if scope else {}
        return nn.rms_norm(x, eps=norm_eps, name=f"{scope}{name}", **centred,
                           **shared)

    def sub_block(result, name, scope):
        """A sub-block's layers, the last of them what the residual add
        takes: its result, and under ``post_norm`` that normed again."""
        return [result, norm(result, name, scope)] if post_norm else [result]

    outs = []
    for t in range(loops):
        scope = f"loop{t}/" if loops > 1 else ""
        for i, kind in enumerate(layer_types):
            if kind not in mixers and kind != ffn_layer_type:
                raise ValueError(f"layer {i}: unknown layer type {kind!r}")
            if ffn_layer_type is not None:      # one sub-block a layer
                normed = norm(x, f"norm{i}")
                if kind == ffn_layer_type:
                    sub, counters = feed_forward(normed, i)
                    extras += counters
                else:
                    sub, counters = mixer(kind, normed, i)
                x = nn.addto([x, sub], name=f"res{i}")
                block = [normed, *counters, sub, x]
            else:
                normed = norm(x, f"norm_op{i}", scope)
                op, riders = mixer(kind, normed, i, scope)
                op = sub_block(op, f"post_op{i}", scope)
                h = nn.addto([x, op[-1]], name=f"{scope}res_op{i}")
                normed2 = norm(h, f"norm_ffn{i}", scope)
                ffn, counters = feed_forward(
                    normed2, i, scope, normed if early_router else None)
                extras += [c for c in counters if "obs_counter" in c.meta]
                ffn = sub_block(ffn, f"post_ffn{i}", scope)
                x = nn.addto([h, ffn[-1]], name=f"{scope}res_ffn{i}")
                block = [normed, *op, *riders, h, normed2, *counters, *ffn, x]
            if recompute_layers is True or (recompute_layers
                                            and i in recompute_layers):
                nn.remat_block(block, f"{scope}layer{i}")
        x = norm(x, "norm_out", f"exits/pass{t}/" if exit_gate else scope)
        outs.append(x)
    head = emb if tie_head else None
    if not exit_gate:
        cost = nn.lm_head_cost(x, targets, embedding=head, name="cost",
                               **({"aux_costs": aux_costs}
                                  if aux_costs else {}))
        return cost, extras
    if aux_costs:
        raise ValueError("a mixer's own loss terms beside the exits' "
                         "expected loss: not built")
    ces, gates = [], []
    for t, out in enumerate(outs):
        ces.append(nn.lm_head_token_cost(
            out, targets, embedding=head, name=f"exits/pass{t}/exit_head",
            param_name="cost"))
        if t < loops - 1:        # the last exit takes the mass that is left
            gates.append(nn.token_gate(out, name=f"exits/pass{t}/exit_gate",
                                       param_name="exit_gate"))
        if recompute_layers:
            nn.remat_block([out, ces[t], *gates[t:]], f"exit{t}")
    cost = nn.loop_exit_cost(ces, gates, targets, beta=exit_beta,
                             name="exits/exit_mix")
    for key, counter, by_step in (("exit_mass", "loop_exit_mass", True),
                                  ("exit_ce", "loop_exit_ce", True),
                                  ("exit_entropy", "loop_exit_entropy",
                                   False)):
        rider = nn.get_output(cost, key, size=loops if by_step else 1,
                              name=key)
        rider.meta["obs_counter"] = {"name": counter, **(
            {"index_label": "step", "first_index": 1} if by_step else {})}
        extras.append(rider)
    return cost, extras
