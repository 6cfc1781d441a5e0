"""Ouro (``model_type`` ``ouro``; ByteDance's Ouro-1.4B / 2.6B, "Scaling
Latent Reasoning via Looped Language Models"): a dense decoder-only stack
whose layers run ``total_ut_steps`` times over ONE set of weights.  A layer
is a sandwich: a norm before AND after each sub-block, ``h = x +
RMSNorm(Attn(RMSNorm(x)))``, ``y = h + RMSNorm(MLP(RMSNorm(h)))``; attention
has as many key-value heads as query heads, the plain rotary embedding over
the whole head, no head norms, no bias, no gate; the MLP is gated SiLU.  The
final RMSNorm runs after EVERY pass: its result is an exit (the untied head's
logits, and a gate's logit a token) and the next pass's input.

Trained (the paper's stage I) on the expected loss over the exits: with
``lam_t = sigmoid(g_t)``, a token leaves after pass ``t`` with probability
``p_t = lam_t prod_(s<t) (1 - lam_s)`` and the last pass takes the mass that
is left; the cost is the mean over tokens of ``sum_t p_t CE_t - beta H(p)``,
the entropy term a uniform prior over the exits (``nn.loop_exit_cost``).
Stage II (the model frozen, a loss on the gate alone) is not built, nor is
inference's early exit (``early_exit_threshold``): training runs every pass.

Built by ``models/decoder.py``'s ``decoder_stack`` with ``loops``,
``post_norm`` and ``exit_gate``; pass ``t``'s layers are ``loop<t>/attn<i>``
and ``loop<t>/mlp<i>`` (the device trace's scopes), the leaves ``_attn<i>.wq``
and so on, stored once.  The extras carry each exit's mass, each exit's
cross-entropy and the entropy, summed over the step's tokens, for the
registry's ``loop_exit_mass{step}``, ``loop_exit_ce{step}`` and
``loop_exit_entropy``.
"""

from __future__ import annotations

from typing import Sequence

import paddle_tpu.nn as nn
from paddle_tpu.models.decoder import decoder_stack

__all__ = ["ouro_net"]


def ouro_net(vocab_size: int, *, hidden_size: int,
             layer_types: Sequence[str], num_attention_heads: int,
             num_key_value_heads: int, head_dim: int, intermediate_size: int,
             total_ut_steps: int = 4, rope_theta: float = 1e6,
             rms_norm_eps: float = 1e-6, exit_beta: float = 0.1,
             recompute_layers=True):
    """Returns ``(cost, extras)`` as ``decoder_stack`` does.  The keywords
    are the published ``config.json``'s, ``layer_types`` one entry a layer
    built (each ``full_attention``); ``exit_beta`` is the weight of the
    entropy term."""
    def attention(normed, i, scope=""):
        return nn.causal_self_attention(
            normed, num_heads=num_attention_heads,
            num_kv_heads=num_key_value_heads, head_dim=head_dim,
            rope_theta=rope_theta, norm_eps=rms_norm_eps, qk_norm=False,
            name=f"{scope}attn{i}", param_name=f"attn{i}")

    return decoder_stack(
        vocab_size, hidden_size=hidden_size, layer_types=list(layer_types),
        mixers={"full_attention": attention},
        num_dense_layers=len(layer_types),
        intermediate_size=intermediate_size, moe_intermediate_size=0,
        num_experts=0, num_experts_per_tok=0, norm_eps=rms_norm_eps,
        tie_head=False, recompute_layers=recompute_layers,
        loops=total_ut_steps, post_norm=True, exit_gate=True,
        exit_beta=exit_beta)
