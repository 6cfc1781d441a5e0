"""The language model of Keye-VL-2.0-30B-A3B (``model_type`` ``KeyeVL2``): a
decoder-only stack of identical layers, grouped-query attention with a norm
over every query and key head and a rotary embedding over the whole head,
STEERED BY A LEARNED INDEXER (``sa_config``: a few light heads over ONE key
head score every past position of a query, the ``topk`` best are kept, and
every head attends over those alone: DeepSeek Sparse Attention), and a
dropless expert layer routed by a softmax over all experts with the chosen
probabilities renormalised, no shared expert, no dense layer; pre-norm
residual blocks, a final RMSNorm, a head of its own (untied).

Text tokens only: the vision tower is not built, and for text the three
position streams of ``mrope_section`` are equal, so the rotary embedding is
the plain one.

The first model of this package whose layers bring terms of their own to the
loss: every attention layer adds its indexer's ``L_I`` (``nn.
indexed_self_attention``), and the cost is ``(sum of the cross-entropies +
sum over the layers of L_I) / tokens``.  The extras carry, beside the expert
layers' counters, two a layer for the registry's
``sparse_attn_kept_pairs{layer}`` and ``indexer_kl{layer}``.

Built by ``models/decoder.py``'s ``decoder_stack``; the layers' names (and so
their scopes on the device trace and their parameters' prefixes) are
``attn<i>`` and ``moe<i>``.
"""

from __future__ import annotations

from typing import Optional, Sequence

import paddle_tpu.nn as nn
from paddle_tpu.models.decoder import decoder_stack

__all__ = ["keye_vl2_net"]


def keye_vl2_net(vocab_size: int, *, hidden_size: int,
                 num_hidden_layers: int, num_attention_heads: int,
                 num_key_value_heads: int, head_dim: int,
                 indexer_num_heads: int, indexer_head_dim: int, topk: int,
                 moe_intermediate_size: int, num_experts: int,
                 num_experts_per_tok: int, norm_topk_prob: bool = True,
                 rms_norm_eps: float = 1e-6, rope_theta: float = 1e7,
                 experts_held: Optional[Sequence[int]] = None,
                 recompute_layers=True):
    """Returns ``(cost, extras)`` as ``decoder_stack`` does.  The keywords
    are the published ``config.json``'s (``indexer_num_heads``,
    ``indexer_head_dim`` and ``topk`` its ``sa_config``'s; the one indexer
    key head is the layer's own); ``num_experts`` is the router's outputs,
    of which this chip holds ``experts_held = (first, count)`` (all by
    default)."""
    def attn(normed, i):
        layer = nn.indexed_self_attention(
            normed, num_heads=num_attention_heads,
            num_kv_heads=num_key_value_heads, head_dim=head_dim,
            indexer_heads=indexer_num_heads,
            indexer_head_dim=indexer_head_dim, topk=topk,
            rope_theta=rope_theta, norm_eps=rms_norm_eps, name=f"attn{i}")
        kl = nn.get_output(layer, "indexer_kl", size=1, name=f"attn{i}_kl")
        kl.meta["loss_term"] = True
        kl.meta["obs_counter"] = {"name": "indexer_kl",
                                  "labels": {"layer": f"attn{i}"}}
        kept = nn.get_output(layer, "kept_pairs", size=1,
                             name=f"attn{i}_kept")
        kept.meta["obs_counter"] = {"name": "sparse_attn_kept_pairs",
                                    "labels": {"layer": f"attn{i}"}}
        return layer, [kept, kl]

    return decoder_stack(
        vocab_size, hidden_size=hidden_size,
        layer_types=["attention"] * num_hidden_layers,
        mixers={"attention": attn}, num_dense_layers=0, intermediate_size=0,
        moe_intermediate_size=moe_intermediate_size,
        num_experts=num_experts, num_experts_per_tok=num_experts_per_tok,
        norm_topk_prob=norm_topk_prob, scoring="softmax",
        experts_held=experts_held, norm_eps=rms_norm_eps, tie_head=False,
        recompute_layers=recompute_layers)
