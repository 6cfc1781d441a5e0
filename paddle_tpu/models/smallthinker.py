"""SmallThinker (``model_name`` ``smallthinker_21b_instruct``; PowerInfer's
SmallThinker-21BA3B-Instruct): a decoder-only stack of expert layers built
for a device that fetches its experts from slow storage.  Three things set it
apart from its siblings here:

- the ROUTER READS THE ATTENTION'S INPUT: layer ``i``'s router logits are
  ``RMSNorm_op(x) W_r``, not ``RMSNorm_ffn(h) W_r``, so that the choice of
  experts is known before attention runs (``decoder_stack(early_router=
  True)``: the choice, the weights and the sort by expert are the layer
  ``moe<i>/moe_routing``, ahead of ``attn<i>``);
- the experts are ReLU-gated, ``W_2 (relu(W_1 x) * W_3 x)`` (ReGLU): a hidden
  unit is exactly zero wherever ``W_1 x <= 0`` (``expert_act="gated_relu"``);
  the router's weights are the softmax over the CHOSEN logits
  (``moe_primary_router_apply_softmax``), which is the softmax over all of
  them renormalised over the chosen;
- two lists give each layer its attention: ``sliding_window_layout[i] == 0``
  sees every past position, ``1`` the last ``sliding_window_size``;
  ``rope_layout[i] == 0`` takes NO positions (NoPE), ``1`` turns q and k by
  the plain rotary embedding.  The published lists are equal (a full layer
  has no positions, a window layer has them); a layout where they differ is
  refused.

No head norms, no bias, no shared expert, no dense layer; pre-norm residual
blocks, a final RMSNorm, a head of its own (untied).

Built by ``models/decoder.py``'s ``decoder_stack``; the layers' names (and so
their scopes on the device trace and their parameters' prefixes) are
``attn<i>`` and ``moe<i>`` (the router's leaf is ``_moe<i>.router``); inside
``attn<i>`` a full layer's core runs under ``attn_core`` and a window layer's
under ``attn_window``.  The extras carry, beside the expert layers' counters
(``moe_gate_zero_units`` among them), one a window layer for the registry's
``window_attn_pairs{layer}``.
"""

from __future__ import annotations

from typing import Optional, Sequence

import paddle_tpu.nn as nn
from paddle_tpu.models.decoder import decoder_stack

__all__ = ["smallthinker_net"]

#: the two kinds of layer, named as ``decoder_stack``'s ``layer_types`` and
#: the benchmark's readers spell them
FULL, WINDOW = "full_attention", "sliding_attention"


def smallthinker_net(vocab_size: int, *, hidden_size: int,
                     num_attention_heads: int, num_key_value_heads: int,
                     head_dim: int, sliding_window_layout: Sequence[int],
                     rope_layout: Sequence[int], sliding_window_size: int,
                     rope_theta: float, moe_ffn_hidden_size: int,
                     moe_num_primary_experts: int,
                     moe_num_active_primary_experts: int,
                     rms_norm_eps: float = 1e-6,
                     experts_held: Optional[Sequence[int]] = None,
                     recompute_layers=True):
    """Returns ``(cost, extras)`` as ``decoder_stack`` does.  The keywords
    are the published ``config.json``'s, the two lists one entry a layer
    built; ``moe_num_primary_experts`` is the router's outputs, of which this
    chip holds ``experts_held = (first, count)`` (all by default)."""
    windows, ropes = list(sliding_window_layout), list(rope_layout)
    if len(windows) != len(ropes):
        raise ValueError(f"{len(windows)} entries of sliding_window_layout, "
                         f"{len(ropes)} of rope_layout")
    differ = [i for i, (w, r) in enumerate(zip(windows, ropes)) if w != r]
    if differ:
        raise ValueError(
            f"layers {differ}: rope_layout differs from "
            f"sliding_window_layout (a full layer with positions, or a "
            f"window layer without): not built")

    def attention(kind):
        window = sliding_window_size if kind == WINDOW else None

        def build(normed, i):
            layer = nn.causal_self_attention(
                normed, num_heads=num_attention_heads,
                num_kv_heads=num_key_value_heads, head_dim=head_dim,
                rope_theta=rope_theta, norm_eps=rms_norm_eps, qk_norm=False,
                rotary=window is not None, window=window, name=f"attn{i}")
            if window is None:
                return layer
            pairs = nn.get_output(layer, "window_pairs", size=1,
                                  name=f"attn{i}_pairs")
            pairs.meta["obs_counter"] = {"name": "window_attn_pairs",
                                         "labels": {"layer": f"attn{i}"}}
            return layer, [pairs]

        return build

    layer_types = [WINDOW if w else FULL for w in windows]
    return decoder_stack(
        vocab_size, hidden_size=hidden_size, layer_types=layer_types,
        mixers={kind: attention(kind) for kind in set(layer_types)},
        num_dense_layers=0, intermediate_size=0,
        moe_intermediate_size=moe_ffn_hidden_size,
        num_experts=moe_num_primary_experts,
        num_experts_per_tok=moe_num_active_primary_experts,
        norm_topk_prob=True, shared_size=0, scoring="softmax",
        expert_act="gated_relu", early_router=True,
        experts_held=experts_held, norm_eps=rms_norm_eps, tie_head=False,
        recompute_layers=recompute_layers)
