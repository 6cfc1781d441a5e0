"""Layers of a decoder-only block: RMSNorm, a gated short causal convolution,
causal grouped-query self-attention with QK-norm and rotary embedding, latent
attention (keys and values made from one narrow latent a token), a gated MLP,
a dropless expert layer that holds a share of the experts (with shared
experts beside them, where the model has them), and a next-token cost over a
head that is the embedding (tied) or a matrix of its own.

The reference (2016) has none of these; they follow its DSL conventions all
the same (``input=`` first, ``name=``, parameters ``_<name>.<leaf>``, one
``jax.named_scope`` per layer through ``Topology.apply``).  None has a bias.
A stack marks the layers of one block with :func:`remat_block`, and
``Topology.apply`` then recomputes the block in the backward pass instead of
holding its activations.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

import paddle_tpu.ops as O
from paddle_tpu.nn.graph import Act, LayerOutput, ParamSpec, next_name
from paddle_tpu.nn.layers import AttrLike, _pa, _refuse_packed, _seq_like
from paddle_tpu.ops import decoder_block as DB
from paddle_tpu.ops import moe as M
from paddle_tpu.utils.error import ConfigError

__all__ = ["rms_norm", "gated_short_conv", "causal_self_attention",
           "latent_attention", "gated_mlp", "expert_mlp", "lm_head_cost",
           "remat_block"]


def _fan_in(name: str, fan_in: int):
    return _pa(None, name, init="normal", initial_std=fan_in ** -0.5)


def remat_block(layers: Sequence[LayerOutput], tag: str) -> None:
    """Mark ``layers`` as one recomputation block: ``Topology.apply`` runs
    them under one ``jax.checkpoint``, so the backward pass holds the block's
    inputs and recomputes the rest.  The block has to be closed: what its
    layers read from outside it was computed before its first layer."""
    for layer in layers:
        if layer.is_data:
            raise ConfigError(f"data layer {layer.name!r} cannot be "
                              f"recomputed: it has no computation")
        layer.meta["remat"] = str(tag)


def rms_norm(input: LayerOutput, *, eps: float = 1e-5,
             name: Optional[str] = None,
             param_attr: AttrLike = None) -> LayerOutput:
    """``x / rms(x) * w`` over the feature axis, statistics in float32."""
    name = name or next_name("rms_norm")
    pa = _pa(param_attr, f"_{name}.w", init="ones")
    spec = ParamSpec(name=pa.name, shape=(input.size,), attr=pa)

    def forward(ctx, params, a: Act) -> Act:
        out = DB.rms_norm(a.value, params[spec.name], eps)
        return _seq_like(a, out) if a.is_seq else Act(value=out)

    return LayerOutput(name, "rms_norm", input.size, [input], forward, [spec])


def gated_short_conv(input: LayerOutput, *, kernel_size: int = 3,
                     name: Optional[str] = None) -> LayerOutput:
    """``[B, C, u] = split3(x W_in)``; a depthwise causal convolution of
    ``kernel_size`` taps over ``B * u``; ``(C * conv) W_out``."""
    name = name or next_name("short_conv")
    D = input.size
    specs = [
        ParamSpec(f"_{name}.w_in", (D, 3 * D), _fan_in(f"_{name}.w_in", D)),
        ParamSpec(f"_{name}.kernel", (kernel_size, D),
                  _fan_in(f"_{name}.kernel", kernel_size)),
        ParamSpec(f"_{name}.w_out", (D, D), _fan_in(f"_{name}.w_out", D)),
    ]

    def forward(ctx, params, a: Act) -> Act:
        if not a.is_seq:
            raise ConfigError(f"gated_short_conv {name!r} needs a sequence")
        _refuse_packed(a, name, "gated_short_conv")
        b, c, u = jnp.split(O.linear(a.value, params[specs[0].name]), 3,
                            axis=-1)
        conv = DB.causal_short_conv(b * u, params[specs[1].name])
        return _seq_like(a, O.linear(c * conv, params[specs[2].name]))

    return LayerOutput(name, "gated_short_conv", D, [input], forward, specs)


def causal_self_attention(input: LayerOutput, *, num_heads: int,
                          num_kv_heads: int, head_dim: int,
                          rope_theta: float = 10000.0, norm_eps: float = 1e-5,
                          name: Optional[str] = None) -> LayerOutput:
    """Causal grouped-query self-attention: RMSNorm over every query head
    and every key head (one weight vector each), rotary embedding, softmax
    at scale ``head_dim ** -0.5`` computed blockwise, output projection."""
    name = name or next_name("self_attention")
    if num_heads % num_kv_heads:
        raise ConfigError(f"{name!r}: {num_heads} query heads are not whole "
                          f"groups over {num_kv_heads} key-value heads")
    D, H, Hkv, dh = input.size, num_heads, num_kv_heads, head_dim
    ones = lambda leaf: _pa(None, f"_{name}.{leaf}", init="ones")  # noqa: E731
    specs = [
        ParamSpec(f"_{name}.wq", (D, H * dh), _fan_in(f"_{name}.wq", D)),
        ParamSpec(f"_{name}.wk", (D, Hkv * dh), _fan_in(f"_{name}.wk", D)),
        ParamSpec(f"_{name}.wv", (D, Hkv * dh), _fan_in(f"_{name}.wv", D)),
        ParamSpec(f"_{name}.wo", (H * dh, D),
                  _fan_in(f"_{name}.wo", H * dh)),
        ParamSpec(f"_{name}.q_norm", (dh,), ones("q_norm")),
        ParamSpec(f"_{name}.k_norm", (dh,), ones("k_norm")),
    ]

    def forward(ctx, params, a: Act) -> Act:
        if not a.is_seq:
            raise ConfigError(f"causal_self_attention {name!r} needs a "
                              f"sequence")
        _refuse_packed(a, name, "causal_self_attention")
        p = {s.name.rsplit(".", 1)[1]: params[s.name] for s in specs}
        x = a.value
        B, T = x.shape[:2]
        q = O.linear(x, p["wq"]).reshape(B, T, H, dh)
        k = O.linear(x, p["wk"]).reshape(B, T, Hkv, dh)
        v = O.linear(x, p["wv"]).reshape(B, T, Hkv, dh)
        q = DB.rotary_embedding(DB.rms_norm(q, p["q_norm"], norm_eps),
                                rope_theta)
        k = DB.rotary_embedding(DB.rms_norm(k, p["k_norm"], norm_eps),
                                rope_theta)
        with jax.named_scope("attn_core"):
            o = DB.causal_attention(q, k, v, scale=dh ** -0.5)
        return _seq_like(a, O.linear(o.reshape(B, T, H * dh), p["wo"]))

    return LayerOutput(name, "causal_self_attention", D, [input], forward,
                       specs)


def latent_attention(input: LayerOutput, *, num_heads: int,
                     kv_lora_rank: int, qk_nope_head_dim: int,
                     qk_rope_head_dim: int, v_head_dim: int,
                     rope_theta: float = 10000.0, norm_eps: float = 1e-6,
                     name: Optional[str] = None) -> LayerOutput:
    """Causal multi-head latent attention in the expanded form that training
    uses.  A token's keys and values come from one latent of ``kv_lora_rank``
    channels: ``[c | k_rope] = x W_kv_a``; ``c`` is RMS-normed (a weight of
    its own) and projected up by ``W_kv_b`` to every head's ``k_nope``
    (``qk_nope_head_dim``) and ``v`` (``v_head_dim``); ``k_rope``
    (``qk_rope_head_dim``) is ONE head that every query head shares.  Queries
    are ``x W_q`` split per head into ``q_nope | q_rope`` (no query latent).
    The rotary embedding turns ``q_rope`` and ``k_rope`` only; scores are
    ``[q_nope | q_rope] . [k_nope | k_rope]`` at scale ``(qk_nope_head_dim
    + qk_rope_head_dim) ** -0.5``; no bias, no QK-norm.

    Scopes inside the layer's own: ``mla_proj`` (the query projection, the
    down- and up-projection, the latent's norm, rotary, assembling q and k)
    and ``attn_core`` (``causal_attention`` with keys wider than values);
    the output projection is the rest."""
    name = name or next_name("latent_attention")
    D, H = input.size, num_heads
    r, dn, dr, dv = (kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
                     v_head_dim)
    specs = [
        ParamSpec(f"_{name}.wq", (D, H * (dn + dr)),
                  _fan_in(f"_{name}.wq", D)),
        ParamSpec(f"_{name}.wkv_a", (D, r + dr),
                  _fan_in(f"_{name}.wkv_a", D)),
        ParamSpec(f"_{name}.kv_norm", (r,),
                  _pa(None, f"_{name}.kv_norm", init="ones")),
        ParamSpec(f"_{name}.wkv_b", (r, H * (dn + dv)),
                  _fan_in(f"_{name}.wkv_b", r)),
        ParamSpec(f"_{name}.wo", (H * dv, D), _fan_in(f"_{name}.wo", H * dv)),
    ]

    def forward(ctx, params, a: Act) -> Act:
        if not a.is_seq:
            raise ConfigError(f"latent_attention {name!r} needs a sequence")
        _refuse_packed(a, name, "latent_attention")
        p = {s.name.rsplit(".", 1)[1]: params[s.name] for s in specs}
        x = a.value
        B, T = x.shape[:2]
        with jax.named_scope("mla_proj"):
            q = O.linear(x, p["wq"]).reshape(B, T, H, dn + dr)
            ckv = O.linear(x, p["wkv_a"])
            c = DB.rms_norm(ckv[..., :r], p["kv_norm"], norm_eps)
            k_rope = DB.rotary_embedding(
                ckv[..., r:].reshape(B, T, 1, dr), rope_theta)
            kv = O.linear(c, p["wkv_b"]).reshape(B, T, H, dn + dv)
            q = jnp.concatenate(
                [q[..., :dn], DB.rotary_embedding(q[..., dn:], rope_theta)],
                axis=-1)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(k_rope, (B, T, H, dr))],
                axis=-1)
            v = kv[..., dn:]
        with jax.named_scope("attn_core"):
            o = DB.causal_attention(q, k, v, scale=(dn + dr) ** -0.5)
        return _seq_like(a, O.linear(o.reshape(B, T, H * dv), p["wo"]))

    return LayerOutput(name, "latent_attention", D, [input], forward, specs)


def _gated_mlp_specs(prefix: str, D: int, size: int):
    return [
        ParamSpec(f"{prefix}w1", (D, size), _fan_in(f"{prefix}w1", D)),
        ParamSpec(f"{prefix}w3", (D, size), _fan_in(f"{prefix}w3", D)),
        ParamSpec(f"{prefix}w2", (size, D), _fan_in(f"{prefix}w2", size)),
    ]


def _gated_mlp(x, w1, w3, w2):
    return O.linear(jax.nn.silu(O.linear(x, w1)) * O.linear(x, w3), w2)


def gated_mlp(input: LayerOutput, size: int, *,
              name: Optional[str] = None) -> LayerOutput:
    """``W_2(silu(W_1 x) * W_3 x)`` with ``size`` hidden units."""
    name = name or next_name("gated_mlp")
    D = input.size
    specs = _gated_mlp_specs(f"_{name}.", D, size)

    def forward(ctx, params, a: Act) -> Act:
        out = _gated_mlp(a.value, *(params[s.name] for s in specs))
        return _seq_like(a, out) if a.is_seq else Act(value=out)

    return LayerOutput(name, "gated_mlp", D, [input], forward, specs)


def expert_mlp(input: LayerOutput, size: int, *, num_experts: int,
               experts_held: Optional[Sequence[int]] = None, top_k: int,
               norm_topk_prob: bool = True, routed_scaling_factor: float = 1.0,
               shared_size: int = 0,
               name: Optional[str] = None) -> LayerOutput:
    """A dropless mixture of gated-MLP experts of ``size`` hidden units, as
    the chip that holds experts ``experts_held = (first, count)`` of
    ``num_experts`` computes it (default: all of them).  Every token is
    routed over all ``num_experts`` (sigmoid scores, the ``top_k`` largest
    of ``score + expert_bias``, weights normalised over the chosen), and the
    layer's value is the part of the result that the experts held give; no
    assignment to an expert held is dropped, whatever the routing.  On one
    chip there is no exchange, and nothing stands in for the other chips.

    ``shared_size``: the hidden units of the shared experts, ONE gated MLP
    (``_<name>.shared_w1`` / ``w3`` / ``w2``) that every token passes
    through, unweighted, added to the routed result; every chip of the
    deployment computes it whole on its own tokens.  Scope ``moe_shared``.

    ``Act.state`` carries ``expert_load`` (assignments per expert held,
    int32) and ``uncomputed`` (assignments to an expert held that no row
    was computed for: 0)."""
    name = name or next_name("expert_mlp")
    D, E = input.size, num_experts
    first, held = experts_held or (0, num_experts)
    if held < 1 or first < 0 or first + held > E or top_k > E:
        raise ConfigError(f"{name!r}: experts {first}..{first + held - 1} "
                          f"and {top_k} a token do not fit {E} experts")
    specs = [
        ParamSpec(f"_{name}.router", (D, E), _fan_in(f"_{name}.router", D)),
        ParamSpec(f"_{name}.expert_bias", (E,),
                  _pa(None, f"_{name}.expert_bias", init="zeros")),
        ParamSpec(f"_{name}.w1", (held, D, size), _fan_in(f"_{name}.w1", D)),
        ParamSpec(f"_{name}.w3", (held, D, size), _fan_in(f"_{name}.w3", D)),
        ParamSpec(f"_{name}.w2", (held, size, D),
                  _fan_in(f"_{name}.w2", size)),
    ]
    shared = (_gated_mlp_specs(f"_{name}.shared_", D, shared_size)
              if shared_size else [])

    def forward(ctx, params, a: Act) -> Act:
        p = {s.name.rsplit(".", 1)[1]: params[s.name] for s in specs}
        x = a.value.reshape(-1, D)
        with jax.named_scope("moe_routing"):
            idx, weights = M.route_tokens(
                x, p["router"], p["expert_bias"], top_k=top_k,
                norm_topk=norm_topk_prob, scaling=routed_scaling_factor)
            if a.is_seq:     # a padded position is no token: nothing held
                idx = jnp.where(a.mask.reshape(-1, 1) > 0, idx, -1)
        tm = M.moe_kernel_row_tile(D, size, idx.size)
        y, load, uncomputed = M.expert_layer(
            x, idx, weights, p["w1"], p["w3"], p["w2"], num_experts=E,
            first_expert=first, tm=tm or 8, kernels=tm is not None)
        if shared:
            with jax.named_scope("moe_shared"):
                y = y + _gated_mlp(x, *(params[s.name] for s in shared))
        y = y.reshape(a.value.shape)
        state = {"expert_load": load, "uncomputed": uncomputed}
        if a.is_seq:
            out = _seq_like(a, y)
            out.state.update(state)
            return out
        return Act(value=y, state=state)

    return LayerOutput(name, "expert_mlp", D, [input], forward,
                       specs + shared)


def lm_head_cost(input: LayerOutput, label: LayerOutput, *,
                 embedding: Optional[LayerOutput] = None,
                 name: Optional[str] = None) -> LayerOutput:
    """Mean next-token cross-entropy over the real positions.  With
    ``embedding`` the head is that layer's matrix (tied): ``logits = h
    E^T``.  Without, the head is a parameter of this layer, ``_<name>.w``
    ``[hidden, label.size]`` (untied): ``logits = h W``.  Through
    ``ops.sequence_softmax_ce_readout``, so the logits are held once, in the
    compute dtype."""
    name = name or next_name("lm_cost")
    if embedding is None:
        head = ParamSpec(f"_{name}.w", (input.size, label.size),
                         _fan_in(f"_{name}.w", input.size))
    else:
        head = embedding.param_specs[0]
        if head.shape[1] != input.size:
            raise ConfigError(f"{name!r}: the embedding is {head.shape[1]} "
                              f"wide, the hidden state {input.size}")

    def forward(ctx, params, h: Act, lab: Act) -> Act:
        w = params[head.name]
        w = w if embedding is None else w.T
        return Act(value=O.sequence_softmax_ce_readout(
            h.value, w, jnp.zeros((w.shape[1],), w.dtype), lab.value, h.mask))

    return LayerOutput(name, "lm_head_cost", 1, [input, label], forward,
                       [head])


from paddle_tpu.config.capture import wrap_module as _wrap_module  # noqa: E402

_wrap_module(globals(), [n for n in __all__ if n != "remat_block"])
