"""LFM2-MoE (``model_type`` ``lfm2_moe``, e.g. LFM2-24B-A2B): a decoder-only
stack whose sequence mixer is a gated short convolution in most layers and
QK-normed rotary grouped-query attention in the others, with a gated MLP in
the leading dense layers and a dropless sigmoid-routed expert layer in the
rest; pre-norm residual blocks, a final RMSNorm, the head tied to the
embedding.

Built by ``models/decoder.py``'s ``decoder_stack``; the mixers' layer names
(and so their scopes on the device trace and their parameters' prefixes) are
``conv<i>`` and ``attn<i>``.
"""

from __future__ import annotations

from typing import Optional, Sequence

import paddle_tpu.nn as nn
from paddle_tpu.models.decoder import decoder_stack

__all__ = ["lfm2_moe_net"]


def lfm2_moe_net(vocab_size: int, *, hidden_size: int,
                 layer_types: Sequence[str], num_dense_layers: int,
                 intermediate_size: int, moe_intermediate_size: int,
                 num_experts: int, num_experts_per_tok: int,
                 num_attention_heads: int, num_key_value_heads: int,
                 head_dim: Optional[int] = None, conv_kernel: int = 3,
                 norm_eps: float = 1e-5, rope_theta: float = 1e6,
                 norm_topk_prob: bool = True,
                 routed_scaling_factor: float = 1.0,
                 experts_held: Optional[Sequence[int]] = None,
                 recompute_layers=True):
    """Returns ``(cost, extras)`` as ``decoder_stack`` does: the mean
    next-token cross-entropy over ``tokens`` / ``next_tokens``, and the expert
    layers' counter outputs.  ``experts_held``, a sliced ``vocab_size`` and
    ``recompute_layers`` are ``decoder_stack``'s."""
    head_dim = head_dim or hidden_size // num_attention_heads
    mixers = {
        "conv": lambda normed, i: nn.gated_short_conv(
            normed, kernel_size=conv_kernel, name=f"conv{i}"),
        "full_attention": lambda normed, i: nn.causal_self_attention(
            normed, num_heads=num_attention_heads,
            num_kv_heads=num_key_value_heads, head_dim=head_dim,
            rope_theta=rope_theta, norm_eps=norm_eps, name=f"attn{i}"),
    }
    return decoder_stack(
        vocab_size, hidden_size=hidden_size, layer_types=layer_types,
        mixers=mixers, num_dense_layers=num_dense_layers,
        intermediate_size=intermediate_size,
        moe_intermediate_size=moe_intermediate_size, num_experts=num_experts,
        num_experts_per_tok=num_experts_per_tok,
        norm_topk_prob=norm_topk_prob,
        routed_scaling_factor=routed_scaling_factor,
        experts_held=experts_held, norm_eps=norm_eps, tie_head=True,
        recompute_layers=recompute_layers)
