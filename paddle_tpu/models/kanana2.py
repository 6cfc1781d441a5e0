"""Kanana-2-30B-A3B (``model_type`` ``deepseek_v3``, e.g.
kanana-2-30b-a3b-instruct-2601): a decoder-only stack whose every sequence
mixer is multi-head latent attention (keys of 192 = 128 from a 512-wide
latent + 64 rotary shared by all heads, values of 128), with a gated MLP in
the leading dense layer and, in the rest, a dropless sigmoid-routed expert
layer beside shared experts that every token passes through; pre-norm
residual blocks, a final RMSNorm, a head of its own (untied).

Built by ``models/decoder.py``'s ``decoder_stack``; the mixers' layer names
(and so their scopes on the device trace and their parameters' prefixes) are
``mla<i>``.
"""

from __future__ import annotations

from typing import Optional, Sequence

import paddle_tpu.nn as nn
from paddle_tpu.models.decoder import decoder_stack

__all__ = ["kanana2_moe_net"]


def kanana2_moe_net(vocab_size: int, *, hidden_size: int,
                    num_hidden_layers: int, first_k_dense_replace: int,
                    intermediate_size: int, moe_intermediate_size: int,
                    n_routed_experts: int, num_experts_per_tok: int,
                    n_shared_experts: int, num_attention_heads: int,
                    kv_lora_rank: int, qk_nope_head_dim: int,
                    qk_rope_head_dim: int, v_head_dim: int,
                    rms_norm_eps: float = 1e-6, rope_theta: float = 1e6,
                    norm_topk_prob: bool = True,
                    routed_scaling_factor: float = 1.0,
                    experts_held: Optional[Sequence[int]] = None,
                    recompute_layers=True):
    """Returns ``(cost, extras)`` as ``decoder_stack`` does.  The keywords
    are the published ``config.json``'s; ``n_routed_experts`` is the
    router's outputs, of which this chip holds ``experts_held = (first,
    count)`` (all by default), and the ``n_shared_experts`` shared experts
    are one gated MLP of ``n_shared_experts * moe_intermediate_size``."""
    def mla(normed, i):
        return nn.latent_attention(
            normed, num_heads=num_attention_heads, kv_lora_rank=kv_lora_rank,
            qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rope_theta=rope_theta, norm_eps=rms_norm_eps, name=f"mla{i}")

    return decoder_stack(
        vocab_size, hidden_size=hidden_size,
        layer_types=["latent_attention"] * num_hidden_layers,
        mixers={"latent_attention": mla},
        num_dense_layers=first_k_dense_replace,
        intermediate_size=intermediate_size,
        moe_intermediate_size=moe_intermediate_size,
        num_experts=n_routed_experts,
        num_experts_per_tok=num_experts_per_tok,
        norm_topk_prob=norm_topk_prob,
        routed_scaling_factor=routed_scaling_factor,
        shared_size=n_shared_experts * moe_intermediate_size,
        experts_held=experts_held, norm_eps=rms_norm_eps, tie_head=False,
        recompute_layers=recompute_layers)
