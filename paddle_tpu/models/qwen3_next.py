"""Qwen3-Next-80B-A3B (``model_type`` ``qwen3_next``): a decoder-only stack
with TWO kinds of sequence mixer that both carry state along the row.  Three
layers in four are gated delta nets (linear attention: 32 value heads, each a
128 x 128 state updated by the gated delta rule, behind a 4-tap causal
convolution), the fourth is full attention at heads of 256 with an output
gate, QK-norm and a rotary embedding on the first quarter of a head.  Every
layer's feed-forward is a dropless expert layer routed by a softmax over all
experts, beside one shared expert behind a sigmoid gate (no dense layer);
every RMSNorm of the stack is ``x / rms(x) * (1 + w)``; pre-norm residual
blocks, a final RMSNorm, a head of its own (untied).

Built by ``models/decoder.py``'s ``decoder_stack``; the mixers' layer names
(and so their scopes on the device trace and their parameters' prefixes) are
``gdn<i>`` and ``attn<i>``.
"""

from __future__ import annotations

from typing import Optional, Sequence

import paddle_tpu.nn as nn
from paddle_tpu.models.decoder import decoder_stack

__all__ = ["qwen3_next_net", "qwen3_next_layer_types"]


def qwen3_next_layer_types(num_hidden_layers: int,
                           full_attention_interval: int) -> list:
    """Layer ``i`` is full attention where ``(i + 1) %
    full_attention_interval == 0``, else linear attention."""
    return ["full_attention" if (i + 1) % full_attention_interval == 0
            else "linear_attention" for i in range(num_hidden_layers)]


def qwen3_next_net(vocab_size: int, *, hidden_size: int,
                   num_hidden_layers: int, full_attention_interval: int,
                   linear_num_key_heads: int, linear_num_value_heads: int,
                   linear_key_head_dim: int, linear_value_head_dim: int,
                   linear_conv_kernel_dim: int, num_attention_heads: int,
                   num_key_value_heads: int, head_dim: int,
                   partial_rotary_factor: float, moe_intermediate_size: int,
                   shared_expert_intermediate_size: int, num_experts: int,
                   num_experts_per_tok: int, rms_norm_eps: float = 1e-6,
                   rope_theta: float = 1e7, norm_topk_prob: bool = True,
                   experts_held: Optional[Sequence[int]] = None,
                   recompute_layers=True):
    """Returns ``(cost, extras)`` as ``decoder_stack`` does.  The keywords
    are the published ``config.json``'s; ``num_experts`` is the router's
    outputs, of which this chip holds ``experts_held = (first, count)`` (all
    by default)."""
    def gdn(normed, i):
        return nn.gated_delta_net(
            normed, num_key_heads=linear_num_key_heads,
            num_value_heads=linear_num_value_heads,
            key_head_dim=linear_key_head_dim,
            value_head_dim=linear_value_head_dim,
            conv_kernel_size=linear_conv_kernel_dim, norm_eps=rms_norm_eps,
            name=f"gdn{i}")

    def attn(normed, i):
        return nn.causal_self_attention(
            normed, num_heads=num_attention_heads,
            num_kv_heads=num_key_value_heads, head_dim=head_dim,
            rope_theta=rope_theta, norm_eps=rms_norm_eps, output_gate=True,
            rotary_dim=int(head_dim * partial_rotary_factor),
            zero_centered_norm=True, name=f"attn{i}")

    return decoder_stack(
        vocab_size, hidden_size=hidden_size,
        layer_types=qwen3_next_layer_types(num_hidden_layers,
                                           full_attention_interval),
        mixers={"linear_attention": gdn, "full_attention": attn},
        num_dense_layers=0, intermediate_size=0,
        moe_intermediate_size=moe_intermediate_size,
        num_experts=num_experts, num_experts_per_tok=num_experts_per_tok,
        norm_topk_prob=norm_topk_prob, scoring="softmax",
        shared_size=shared_expert_intermediate_size, shared_gate=True,
        experts_held=experts_held, norm_eps=rms_norm_eps,
        zero_centered_norm=True, tie_head=False,
        recompute_layers=recompute_layers)
