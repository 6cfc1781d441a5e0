"""The decoder-only stack that the models of this package share: an
embedding, pre-norm residual layers whose sequence mixer is chosen by kind
and whose feed-forward is a gated MLP in the first ``num_dense_layers``
layers (a model may have none) and a dropless expert layer in the rest, a
final RMSNorm, and the next-token cost over a head that is the embedding or a
matrix of its own.

Layer ``i``: ``h = x + Op_i(RMSNorm(x))``, ``y = h + FFN_i(RMSNorm(h))``.
Layer names (and so the device trace's scopes and the parameters'
prefixes): what the mixer's builder names its layer, ``mlp<i>`` / ``moe<i>``,
``norm_op<i>``, ``norm_ffn<i>``, ``emb``, ``norm_out``, ``cost``.
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
                  tie_head: bool = True, recompute_layers=True):
    """Returns ``(cost, extras)``: the mean next-token cross-entropy over
    ``tokens`` / ``next_tokens`` (two int sequence feeds of one length), and
    two extra outputs per expert layer, marked for the counters
    ``moe_assignments`` (assignments per expert held) and
    ``moe_uncomputed_assignments`` (the trainer feeds them when the extras
    are passed as ``extra_outputs``).

    ``mixers[kind](normed, i)`` builds layer ``i``'s sequence mixer over the
    normed input, for every ``kind`` in ``layer_types``.  ``experts_held =
    (first, count)``: the share of the experts this chip holds (all by
    default); ``vocab_size`` may likewise be a slice of the published
    vocabulary, ids, logits and loss then being over the slice.
    ``shared_size``: hidden units of the shared experts beside the routed
    ones (0: none), ``shared_gate``: whether a sigmoid gate weighs them;
    ``scoring``: how the router scores (``nn.expert_mlp``).
    ``num_dense_layers`` may be 0: every layer is then an expert layer.
    ``zero_centered_norm``: the stack's RMSNorms are ``x / rms(x) * (1 +
    w)``.  ``recompute_layers`` marks decoder layers as
    recomputation blocks, one block a layer: ``True`` for every layer, or
    the indices of the layers to recompute (the others hold their
    activations)."""
    tokens = nn.data("tokens", size=vocab_size, is_seq=True, dtype="int32")
    targets = nn.data("next_tokens", size=vocab_size, is_seq=True,
                      dtype="int32")
    emb = nn.embedding(tokens, hidden_size, name="emb",
                       param_attr=nn.ParamAttr(initial_std=0.02,
                                               init="normal"))
    x, extras = emb, []
    # passed only where asked for: the two older models' graphs (and their
    # captured configurations) stay what they were
    centred = {"zero_centered": True} if zero_centered_norm else {}
    routing = {}
    if scoring != "sigmoid":
        routing["scoring"] = scoring
    if shared_gate:
        routing["shared_gate"] = True
    for i, kind in enumerate(layer_types):
        if kind not in mixers:
            raise ValueError(f"layer {i}: unknown layer type {kind!r}")
        normed = nn.rms_norm(x, eps=norm_eps, name=f"norm_op{i}", **centred)
        op = mixers[kind](normed, i)
        h = nn.addto([x, op], name=f"res_op{i}")
        normed2 = nn.rms_norm(h, eps=norm_eps, name=f"norm_ffn{i}",
                              **centred)
        block = [normed, op, h, normed2]
        if i < num_dense_layers:
            ffn = nn.gated_mlp(normed2, intermediate_size, name=f"mlp{i}")
        else:
            ffn = nn.expert_mlp(
                normed2, moe_intermediate_size, num_experts=num_experts,
                experts_held=experts_held, top_k=num_experts_per_tok,
                norm_topk_prob=norm_topk_prob,
                routed_scaling_factor=routed_scaling_factor,
                shared_size=shared_size, name=f"moe{i}", **routing)
            load = nn.get_output(ffn, "expert_load", size=1,
                                 name=f"moe{i}_load")
            load.meta["obs_counter"] = {
                "name": "moe_assignments", "labels": {"layer": f"moe{i}"},
                "index_label": "expert", "first_index": (experts_held
                                                         or (0,))[0]}
            dropped = nn.get_output(ffn, "uncomputed", size=1,
                                    name=f"moe{i}_uncomputed")
            dropped.meta["obs_counter"] = {
                "name": "moe_uncomputed_assignments",
                "labels": {"layer": f"moe{i}"}}
            extras += [load, dropped]
            block += [load, dropped]
        x = nn.addto([h, ffn], name=f"res_ffn{i}")
        block += [ffn, x]
        if recompute_layers is True or (recompute_layers
                                        and i in recompute_layers):
            nn.remat_block(block, f"layer{i}")
    out = nn.rms_norm(x, eps=norm_eps, name="norm_out", **centred)
    cost = nn.lm_head_cost(out, targets, embedding=emb if tie_head else None,
                           name="cost")
    return cost, extras
