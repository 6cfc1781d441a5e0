"""Nemotron-H (``model_type`` ``nemotron_h``, e.g.
NVIDIA-Nemotron-3-Nano-30B-A3B): a decoder-only stack whose every layer is
ONE sub-block, ``x = x + f_i(RMSNorm_i(x))``, with ``f_i`` chosen by
``hybrid_override_pattern``, a letter a layer: ``M`` a Mamba-2 (SSD)
state-space mixer, ``*`` causal grouped-query attention with no QK-norm and
no rotary embedding (the state-space layers carry the order), ``E`` a
dropless sigmoid-routed expert layer whose experts are TWO matrices and a
squared ReLU, beside one shared expert of the same form.  Two mixers may
stand side by side (``M*``) and the model ends on an expert layer; a final
RMSNorm, a head of its own (untied).

Built by ``models/decoder.py``'s ``decoder_stack``; the layers' names (and so
their scopes on the device trace and their parameters' prefixes) are
``mamba<i>``, ``attn<i>`` and ``moe<i>``, ``i`` the published index.
"""

from __future__ import annotations

from typing import Optional, Sequence

import paddle_tpu.nn as nn
from paddle_tpu.models.decoder import decoder_stack

__all__ = ["nemotron_h_net", "nemotron_h_layer_types"]

_KINDS = {"M": "mamba", "*": "attention", "E": "moe"}


def nemotron_h_layer_types(hybrid_override_pattern: str) -> list:
    """A letter a layer: ``M`` -> ``mamba``, ``*`` -> ``attention``, ``E`` ->
    ``moe``."""
    unknown = sorted(set(hybrid_override_pattern) - set(_KINDS))
    if unknown:
        raise ValueError(f"hybrid_override_pattern holds {unknown}; a layer "
                         f"is one of {sorted(_KINDS)}")
    return [_KINDS[c] for c in hybrid_override_pattern]


def nemotron_h_net(vocab_size: int, *, hybrid_override_pattern: str,
                   hidden_size: int, mamba_num_heads: int,
                   mamba_head_dim: int, n_groups: int, ssm_state_size: int,
                   conv_kernel: int, num_attention_heads: int,
                   num_key_value_heads: int, head_dim: int,
                   moe_intermediate_size: int,
                   moe_shared_expert_intermediate_size: int,
                   n_routed_experts: int, num_experts_per_tok: int,
                   routed_scaling_factor: float = 1.0,
                   norm_topk_prob: bool = True,
                   layer_norm_epsilon: float = 1e-5,
                   experts_held: Optional[Sequence[int]] = None,
                   recompute_layers=True):
    """Returns ``(cost, extras)`` as ``decoder_stack`` does.  The keywords
    are the published ``config.json``'s; ``n_routed_experts`` is the
    router's outputs, of which this chip holds ``experts_held = (first,
    count)`` (all by default).  The mixer's inner width is
    ``mamba_num_heads * mamba_head_dim`` (the published ``expand`` is not
    read)."""
    def mamba(normed, i):
        return nn.mamba2_mixer(
            normed, num_heads=mamba_num_heads, head_dim=mamba_head_dim,
            n_groups=n_groups, state_size=ssm_state_size,
            conv_kernel_size=conv_kernel, norm_eps=layer_norm_epsilon,
            name=f"mamba{i}")

    def attn(normed, i):
        return nn.causal_self_attention(
            normed, num_heads=num_attention_heads,
            num_kv_heads=num_key_value_heads, head_dim=head_dim,
            qk_norm=False, rotary=False, name=f"attn{i}")

    return decoder_stack(
        vocab_size, hidden_size=hidden_size,
        layer_types=nemotron_h_layer_types(hybrid_override_pattern),
        mixers={"mamba": mamba, "attention": attn}, ffn_layer_type="moe",
        num_dense_layers=0, intermediate_size=0,
        moe_intermediate_size=moe_intermediate_size,
        num_experts=n_routed_experts,
        num_experts_per_tok=num_experts_per_tok,
        norm_topk_prob=norm_topk_prob,
        routed_scaling_factor=routed_scaling_factor,
        shared_size=moe_shared_expert_intermediate_size,
        expert_act="relu2", experts_held=experts_held,
        norm_eps=layer_norm_epsilon, tie_head=False,
        recompute_layers=recompute_layers)
