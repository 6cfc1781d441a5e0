"""Laguna (``model_type`` ``laguna``; poolside's Laguna-XS.2): a decoder-only
stack whose attention layers are of ONE family and differ BY LAYER: three in
four see a window of the last ``sliding_window`` positions, with more query
heads and the plain rotary embedding over the whole head; the fourth sees
every past position with fewer query heads, and takes its positions from a
rotary embedding scaled by YaRN over a part of the head
(``partial_rotary_factor``).  ``num_attention_heads_per_layer`` gives each
layer's query heads (all over the same key-value heads), ``layer_types`` its
kind, ``rope_parameters`` one block a kind.  Every layer gates its heads'
results by one sigmoid a head and token (``gating``).  The feed-forward is a
gated MLP where ``mlp_layer_types`` says ``dense`` (the leading layers) and
elsewhere a dropless expert layer routed by sigmoid scores with the chosen
ones renormalised and scaled (``moe_routed_scaling_factor``), beside one
shared expert; pre-norm residual blocks, a final RMSNorm, a head of its own
(untied).  No head norms, no bias, no selection bias.

The first model of this package whose layers of one mixer family differ in
their head count and their rotary embedding by layer: ``decoder_stack`` hands
the mixer's builder the layer's index, and the builder reads the layer's
settings by it.

Built by ``models/decoder.py``'s ``decoder_stack``; the layers' names (and so
their scopes on the device trace and their parameters' prefixes) are
``attn<i>``, ``mlp<i>`` and ``moe<i>``; inside ``attn<i>`` a full layer's core
runs under ``attn_core`` and a window layer's under ``attn_window``.  The
extras carry, beside the expert layers' counters, one a window layer for the
registry's ``window_attn_pairs{layer}``.
"""

from __future__ import annotations

from typing import Mapping, Optional, Sequence

import paddle_tpu.nn as nn
from paddle_tpu.models.decoder import decoder_stack

__all__ = ["laguna_net"]


def laguna_net(vocab_size: int, *, hidden_size: int,
               layer_types: Sequence[str], mlp_layer_types: Sequence[str],
               num_attention_heads_per_layer: Sequence[int],
               num_key_value_heads: int, head_dim: int, sliding_window: int,
               rope_parameters: Mapping[str, Mapping],
               intermediate_size: int, moe_intermediate_size: int,
               shared_expert_intermediate_size: int, num_experts: int,
               num_experts_per_tok: int,
               moe_routed_scaling_factor: float = 1.0,
               gating: bool = True, rms_norm_eps: float = 1e-6,
               experts_held: Optional[Sequence[int]] = None,
               recompute_layers=True):
    """Returns ``(cost, extras)`` as ``decoder_stack`` does.  The keywords
    are the published ``config.json``'s, the three lists one entry a layer
    built; ``num_experts`` is the router's outputs, of which this chip holds
    ``experts_held = (first, count)`` (all by default)."""
    n = len(layer_types)
    if not (len(mlp_layer_types) == len(num_attention_heads_per_layer) == n):
        raise ValueError(f"{n} layer_types, {len(mlp_layer_types)} "
                         f"mlp_layer_types, "
                         f"{len(num_attention_heads_per_layer)} head counts")
    dense = [kind == "dense" for kind in mlp_layer_types]
    if dense != sorted(dense, reverse=True):
        raise ValueError("dense feed-forward layers after an expert layer: "
                         f"{list(mlp_layer_types)}")

    def attention(kind):
        rope = rope_parameters[kind]

        def build(normed, i):
            window = sliding_window if kind == "sliding_attention" else None
            layer = nn.causal_self_attention(
                normed, num_heads=num_attention_heads_per_layer[i],
                num_kv_heads=num_key_value_heads, head_dim=head_dim,
                rope_theta=rope["rope_theta"], norm_eps=rms_norm_eps,
                output_gate="head" if gating else False,
                rotary_dim=int(head_dim * rope.get("partial_rotary_factor",
                                                   1.0)),
                qk_norm=False, window=window, rope_scaling=rope,
                name=f"attn{i}")
            if window is None:
                return layer
            pairs = nn.get_output(layer, "window_pairs", size=1,
                                  name=f"attn{i}_pairs")
            pairs.meta["obs_counter"] = {"name": "window_attn_pairs",
                                         "labels": {"layer": f"attn{i}"}}
            return layer, [pairs]

        return build

    return decoder_stack(
        vocab_size, hidden_size=hidden_size, layer_types=list(layer_types),
        mixers={kind: attention(kind) for kind in set(layer_types)},
        num_dense_layers=sum(dense), intermediate_size=intermediate_size,
        moe_intermediate_size=moe_intermediate_size,
        num_experts=num_experts, num_experts_per_tok=num_experts_per_tok,
        norm_topk_prob=True,
        routed_scaling_factor=moe_routed_scaling_factor,
        shared_size=shared_expert_intermediate_size, scoring="sigmoid",
        selection_bias=False, experts_held=experts_held,
        norm_eps=rms_norm_eps, tie_head=False,
        recompute_layers=recompute_layers)
