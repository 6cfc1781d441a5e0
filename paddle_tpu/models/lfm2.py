"""LFM2-MoE (``model_type`` ``lfm2_moe``, e.g. LFM2-24B-A2B): a decoder-only
stack whose sequence mixer is a gated short convolution in most layers and
QK-normed rotary grouped-query attention in the others, with a gated MLP in
the leading dense layers and a dropless sigmoid-routed expert layer in the
rest; pre-norm residual blocks, a final RMSNorm, the head tied to the
embedding.

Layer ``i``: ``h = x + Op_i(RMSNorm(x))``, ``y = h + FFN_i(RMSNorm(h))``.
Layer names (and so the device trace's scopes and the parameters'
prefixes): ``conv<i>`` / ``attn<i>``, ``mlp<i>`` / ``moe<i>``, ``norm_op<i>``,
``norm_ffn<i>``, ``emb``, ``norm_out``, ``cost``.
"""

from __future__ import annotations

from typing import Optional, Sequence

import paddle_tpu.nn as nn

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
    """Returns ``(cost, extras)``: the mean next-token cross-entropy over
    ``tokens`` / ``next_tokens`` (two int sequence feeds of one length), and
    one extra output per expert layer, its assignments per expert held,
    marked for the counter ``moe_assignments`` (the trainer feeds it when
    the extras are passed as ``extra_outputs``).

    ``experts_held = (first, count)``: the share of the experts this chip
    holds (all by default); ``vocab_size`` may likewise be a slice of the
    published vocabulary, ids, logits and loss then being over the slice.
    ``recompute_layers`` marks decoder layers as recomputation blocks, one
    block a layer: ``True`` for every layer, or the indices of the layers
    to recompute (the others hold their activations)."""
    head_dim = head_dim or hidden_size // num_attention_heads
    tokens = nn.data("tokens", size=vocab_size, is_seq=True, dtype="int32")
    targets = nn.data("next_tokens", size=vocab_size, is_seq=True,
                      dtype="int32")
    emb = nn.embedding(tokens, hidden_size, name="emb",
                       param_attr=nn.ParamAttr(initial_std=0.02,
                                               init="normal"))
    x, extras = emb, []
    for i, kind in enumerate(layer_types):
        normed = nn.rms_norm(x, eps=norm_eps, name=f"norm_op{i}")
        if kind == "conv":
            op = nn.gated_short_conv(normed, kernel_size=conv_kernel,
                                     name=f"conv{i}")
        elif kind == "full_attention":
            op = nn.causal_self_attention(
                normed, num_heads=num_attention_heads,
                num_kv_heads=num_key_value_heads, head_dim=head_dim,
                rope_theta=rope_theta, norm_eps=norm_eps, name=f"attn{i}")
        else:
            raise ValueError(f"layer {i}: unknown layer type {kind!r}")
        h = nn.addto([x, op], name=f"res_op{i}")
        normed2 = nn.rms_norm(h, eps=norm_eps, name=f"norm_ffn{i}")
        block = [normed, op, h, normed2]
        if i < num_dense_layers:
            ffn = nn.gated_mlp(normed2, intermediate_size, name=f"mlp{i}")
        else:
            ffn = nn.expert_mlp(
                normed2, moe_intermediate_size, num_experts=num_experts,
                experts_held=experts_held, top_k=num_experts_per_tok,
                norm_topk_prob=norm_topk_prob,
                routed_scaling_factor=routed_scaling_factor, name=f"moe{i}")
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
    out = nn.rms_norm(x, eps=norm_eps, name="norm_out")
    cost = nn.lm_head_cost(out, targets, embedding=emb, name="cost")
    return cost, extras
