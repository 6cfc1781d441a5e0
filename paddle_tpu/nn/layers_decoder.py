"""Layers of a decoder-only block: RMSNorm, a gated short causal convolution,
causal grouped-query self-attention with QK-norm and rotary embedding (and,
where the model has one, an output gate), latent attention (keys and values
made from one narrow latent a token), a gated delta net (linear attention:
a state a head, updated by the gated delta rule), a Mamba-2 mixer (a
state-space layer: a state a head, decayed by one scalar a token), a gated
MLP,
a dropless expert layer that holds a share of the experts (with shared
experts beside them, where the model has them), and a next-token cost over a
head that is the embedding (tied) or a matrix of its own: the mean over the
tokens, or one cross-entropy a token for a cost that weighs them itself (the
expected loss over the exits of a stack whose layers run several times, with
the gate that gives each exit its weight).

The reference (2016) has none of these; they follow its DSL conventions all
the same (``input=`` first, ``name=``, parameters ``_<name>.<leaf>``, one
``jax.named_scope`` per layer through ``Topology.apply``).  None has a bias
but the Mamba-2 mixer's convolution.
A layer's leaves are named after the layer unless it is given a
``param_name`` (``rms_norm``: a ``param_attr`` with a name): layers that name
the same leaves are several applications of one set of weights.
A stack marks the layers of one block with :func:`remat_block`, and
``Topology.apply`` then recomputes the block in the backward pass instead of
holding its activations.
"""

from __future__ import annotations

from typing import Mapping, Optional, Sequence

import jax
import jax.numpy as jnp

import paddle_tpu.ops as O
from paddle_tpu.nn.graph import Act, LayerOutput, ParamSpec, next_name
from paddle_tpu.nn.layers import AttrLike, _pa, _refuse_packed, _seq_like
from paddle_tpu.ops import decoder_block as DB
from paddle_tpu.ops import delta_rule as DR
from paddle_tpu.ops import moe as M
from paddle_tpu.ops import sparse_attention as SA
from paddle_tpu.ops import ssd_scan as SS
from paddle_tpu.ops.numerics import mxu_cast
from paddle_tpu.utils.error import ConfigError

__all__ = ["rms_norm", "gated_short_conv", "causal_self_attention",
           "indexed_self_attention",
           "latent_attention", "gated_delta_net", "mamba2_mixer", "gated_mlp",
           "expert_mlp",
           "lm_head_cost", "lm_head_token_cost", "token_gate",
           "loop_exit_cost",
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
             zero_centered: bool = False, name: Optional[str] = None,
             param_attr: AttrLike = None) -> LayerOutput:
    """``x / rms(x) * w`` over the feature axis, statistics in float32.
    ``zero_centered``: ``x / rms(x) * (1 + w)``, the weight starting at 0."""
    name = name or next_name("rms_norm")
    pa = _pa(param_attr, f"_{name}.w",
             init="zeros" if zero_centered else "ones")
    spec = ParamSpec(name=pa.name, shape=(input.size,), attr=pa)

    def forward(ctx, params, a: Act) -> Act:
        out = DB.rms_norm(a.value, params[spec.name], eps, zero_centered)
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


def _attention_specs(name, D, H, Hkv, dh, dq, qk_norm=True,
                     zero_centered_norm=False, head_gate=False):
    """The leaves of grouped-query attention: four projections, with
    ``qk_norm`` one norm weight for the query heads and one for the key
    heads, and with ``head_gate`` the head-wise gate's ``wg`` ``[D, H]``."""
    norm_w = lambda leaf: _pa(  # noqa: E731
        None, f"_{name}.{leaf}", init="zeros" if zero_centered_norm
        else "ones")
    return [
        ParamSpec(f"_{name}.wq", (D, H * dq), _fan_in(f"_{name}.wq", D)),
        ParamSpec(f"_{name}.wk", (D, Hkv * dh), _fan_in(f"_{name}.wk", D)),
        ParamSpec(f"_{name}.wv", (D, Hkv * dh), _fan_in(f"_{name}.wv", D)),
        ParamSpec(f"_{name}.wo", (H * dh, D),
                  _fan_in(f"_{name}.wo", H * dh)),
    ] + ([ParamSpec(f"_{name}.q_norm", (dh,), norm_w("q_norm")),
          ParamSpec(f"_{name}.k_norm", (dh,), norm_w("k_norm"))]
         if qk_norm else []) + (
        [ParamSpec(f"_{name}.wg", (D, H), _fan_in(f"_{name}.wg", D))]
        if head_gate else [])


def _project_heads(x, p, H, Hkv, dh, dq):
    """``(q [B, T, H, dq], k [B, T, Hkv, dh], v [B, T, Hkv, dh])``."""
    B, T = x.shape[:2]
    return (O.linear(x, p["wq"]).reshape(B, T, H, dq),
            O.linear(x, p["wk"]).reshape(B, T, Hkv, dh),
            O.linear(x, p["wv"]).reshape(B, T, Hkv, dh))


def causal_self_attention(input: LayerOutput, *, num_heads: int,
                          num_kv_heads: int, head_dim: int,
                          rope_theta: float = 10000.0, norm_eps: float = 1e-5,
                          output_gate=False,
                          rotary_dim: Optional[int] = None,
                          zero_centered_norm: bool = False,
                          qk_norm: bool = True, rotary: bool = True,
                          window: Optional[int] = None,
                          rope_scaling: Optional[Mapping] = None,
                          name: Optional[str] = None,
                          param_name: Optional[str] = None) -> LayerOutput:
    """Causal grouped-query self-attention: RMSNorm over every query head
    and every key head (one weight vector each; ``zero_centered_norm``: the
    ``1 + w`` form), rotary embedding (on the first ``rotary_dim`` channels
    of a head where given, else on all), softmax at scale ``head_dim **
    -0.5`` computed blockwise, output projection.  ``output_gate=True``:
    ``W_q`` gives every head ``[q | gate]`` of ``head_dim`` each, and the
    attention's result is multiplied by ``sigmoid(gate)`` before the output
    projection; ``output_gate="head"``: the gate is ONE number a head and
    token, ``sigmoid(x W_g)`` with ``W_g`` ``[D, H]`` (leaf ``wg``).
    ``qk_norm=False``: no norm over the heads, and the layer has
    no ``q_norm`` / ``k_norm`` leaves; ``rotary=False``: the layer takes no
    positions (a model whose other mixers carry the order).  The rotary
    embedding's pass over q and over k runs under the scope ``rotary``
    inside the layer's own (``ops.decoder_block.rotary_embedding``: one pass
    over whole heads, the channels a ``rotary_dim`` passes through riding in
    it).

    ``window``: a query sees its last ``window`` positions alone, its own
    among them (``ops.causal_attention``); the core then runs under the
    scope ``attn_window`` where a full layer's keeps ``attn_core``, and
    ``Act.state`` carries ``window_pairs`` (the (query, position) pairs the
    mask lets through, real queries only, int32: ``sum_t min(t + 1,
    window)`` a full row).  ``rope_scaling``: a config's ``rope_parameters``
    block of ``rope_type`` ``yarn`` (``rope_theta``, ``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    ``attention_factor``): the rotary embedding's frequencies and the factor
    on its cos and sin are ``ops.decoder_block.yarn_frequencies``'s over the
    turned channels; ``rope_type`` ``default`` scales nothing.

    ``param_name``: what the leaves are named after, ``_<param_name>.wq``
    and so on (default: the layer's own name).  Two layers given the same
    one are two applications of ONE set of weights: ``Topology`` holds one
    spec and one array a name, and a leaf's gradient is the sum over its
    uses (a stack whose layers run several times)."""
    name = name or next_name("self_attention")
    if num_heads % num_kv_heads:
        raise ConfigError(f"{name!r}: {num_heads} query heads are not whole "
                          f"groups over {num_kv_heads} key-value heads")
    if output_gate not in (False, True, "head"):
        raise ConfigError(f"{name!r}: unknown output_gate {output_gate!r}")
    if window is not None and window < 1:
        raise ConfigError(f"{name!r}: a window of {window} positions")
    D, H, Hkv, dh = input.size, num_heads, num_kv_heads, head_dim
    rd = dh if rotary_dim is None else rotary_dim
    if rd % 2 or not 0 < rd <= dh:
        raise ConfigError(f"{name!r}: rotary width {rd} of a head of {dh}")
    scaled = {}
    kind = (rope_scaling or {}).get("rope_type", "default")
    if kind == "yarn":
        inv_freq, factor = DB.yarn_frequencies(rd, **{
            k: rope_scaling[k] for k in (
                "rope_theta", "factor", "original_max_position_embeddings",
                "beta_fast", "beta_slow", "attention_factor")
            if k in rope_scaling})
        scaled = {"inv_freq": inv_freq, "factor": factor}
    elif kind != "default":
        raise ConfigError(f"{name!r}: unknown rope_type {kind!r}")
    head_gate = output_gate == "head"
    dq = 2 * dh if output_gate is True else dh
    specs = _attention_specs(param_name or name, D, H, Hkv, dh, dq, qk_norm,
                             zero_centered_norm, head_gate)

    def forward(ctx, params, a: Act) -> Act:
        if not a.is_seq:
            raise ConfigError(f"causal_self_attention {name!r} needs a "
                              f"sequence")
        _refuse_packed(a, name, "causal_self_attention")
        p = {s.name.rsplit(".", 1)[1]: params[s.name] for s in specs}
        x = a.value
        B, T = x.shape[:2]
        q, k, v = _project_heads(x, p, H, Hkv, dh, dq)
        if output_gate is True:
            q, gate = q[..., :dh], q[..., dh:]
        elif head_gate:
            gate = O.linear(x, p["wg"])[..., None]

        def placed(h, norm):     # the head's norm, then its position
            if qk_norm:
                h = DB.rms_norm(h, p[norm], norm_eps, zero_centered_norm)
            return (DB.rotary_embedding(h, rope_theta, rotary_dim, **scaled)
                    if rotary else h)

        q, k = placed(q, "q_norm"), placed(k, "k_norm")
        if window is None:
            with jax.named_scope("attn_core"):
                o = DB.causal_attention(q, k, v, scale=dh ** -0.5)
        else:
            with jax.named_scope("attn_window"):
                o = DB.causal_attention(q, k, v, scale=dh ** -0.5,
                                        window=window)
        if output_gate:
            o = o * jax.nn.sigmoid(gate.astype(o.dtype))
        out = _seq_like(a, O.linear(o.reshape(B, T, H * dh), p["wo"]))
        if window is not None:
            seen = jnp.minimum(jnp.arange(1, T + 1), window)
            out.state["window_pairs"] = jnp.sum(
                jnp.where(a.mask > 0, seen[None, :], 0)).astype(jnp.int32)
        return out

    return LayerOutput(name, "causal_self_attention", D, [input], forward,
                       specs)


def indexed_self_attention(input: LayerOutput, *, num_heads: int,
                           num_kv_heads: int, head_dim: int,
                           indexer_heads: int, indexer_head_dim: int,
                           topk: int, rope_theta: float = 10000.0,
                           norm_eps: float = 1e-6,
                           name: Optional[str] = None) -> LayerOutput:
    """Causal grouped-query self-attention over the positions a learned
    indexer keeps (DeepSeek Sparse Attention's lightning indexer).  The main
    heads are :func:`causal_self_attention`'s: RMSNorm over every query and
    key head, rotary embedding over the whole head, scale ``head_dim **
    -0.5``.  The indexer reads the layer's input as a CONSTANT (no gradient
    leaves it towards the residual stream): ``qI = u W_Iq`` (``indexer_heads``
    heads of ``indexer_head_dim``), ``kI = LayerNorm(u W_Ik)`` (ONE key
    head; a weight and a bias), both turned by the rotary embedding, ``w = u
    W_Iw (indexer_heads * indexer_head_dim) ** -0.5``; query ``t`` scores
    position ``s <= t`` with ``sum_j w[t, j] relu(qI[t, j] . kI[s])``, keeps
    its ``min(t + 1, topk)`` best, and every head attends over those alone
    (``ops.sparse_attention``).  The cross-entropy moves every leaf but the
    indexer's five (``wiq``, ``wik``, ``wiw``, ``ik_norm``, ``ik_bias``);
    those learn from ``L_I`` alone, the sum over the queries of ``KL(mean of
    the heads' probabilities || softmax of the kept scores)``.

    ``Act.state`` carries ``indexer_kl`` (``L_I`` summed over the batch,
    float32: a term of the loss, see ``lm_head_cost``'s ``aux_costs``) and
    ``kept_pairs`` (the (query, position) pairs kept, int32).

    Scopes inside the layer's own: ``indexer`` (the indexer's projections,
    norm, rotary and scores), ``topk_select``, ``attn_core`` (the selected
    attention) and ``indexer_loss`` (the target, the KL and its gradient
    into the scores); ``rotary`` (every rotary pass: the main heads' in the
    layer's scope, the indexer's inside ``indexer``)."""
    name = name or next_name("indexed_attention")
    if num_heads % num_kv_heads:
        raise ConfigError(f"{name!r}: {num_heads} query heads are not whole "
                          f"groups over {num_kv_heads} key-value heads")
    if head_dim % 2 or indexer_head_dim % 2 or topk < 1:
        raise ConfigError(f"{name!r}: heads of {head_dim} and "
                          f"{indexer_head_dim}, {topk} kept")
    D, H, Hkv, dh = input.size, num_heads, num_kv_heads, head_dim
    J, di = indexer_heads, indexer_head_dim
    specs = _attention_specs(name, D, H, Hkv, dh, dh) + [
        ParamSpec(f"_{name}.wiq", (D, J * di), _fan_in(f"_{name}.wiq", D)),
        ParamSpec(f"_{name}.wik", (D, di), _fan_in(f"_{name}.wik", D)),
        ParamSpec(f"_{name}.wiw", (D, J), _fan_in(f"_{name}.wiw", D)),
        ParamSpec(f"_{name}.ik_norm", (di,),
                  _pa(None, f"_{name}.ik_norm", init="ones")),
        ParamSpec(f"_{name}.ik_bias", (di,),
                  _pa(None, f"_{name}.ik_bias", init="zeros")),
    ]

    def forward(ctx, params, a: Act) -> Act:
        if not a.is_seq:
            raise ConfigError(f"indexed_self_attention {name!r} needs a "
                              f"sequence")
        _refuse_packed(a, name, "indexed_self_attention")
        p = {s.name.rsplit(".", 1)[1]: params[s.name] for s in specs}
        x = a.value
        B, T = x.shape[:2]
        f32 = jnp.float32
        q, k, v = _project_heads(x, p, H, Hkv, dh, dh)
        q = DB.rotary_embedding(DB.rms_norm(q, p["q_norm"], norm_eps),
                                rope_theta)
        k = DB.rotary_embedding(DB.rms_norm(k, p["k_norm"], norm_eps),
                                rope_theta)
        with jax.named_scope("indexer"):
            u = jax.lax.stop_gradient(x)
            qI = DB.rotary_embedding(
                O.linear(u, p["wiq"]).reshape(B, T, J, di), rope_theta)
            kI = DB.layer_norm(O.linear(u, p["wik"]), p["ik_norm"],
                               p["ik_bias"], norm_eps)
            kI = DB.rotary_embedding(kI.reshape(B, T, 1, di),
                                     rope_theta).reshape(B, T, di)
            uc, wc = mxu_cast(u, p["wiw"])
            w = jnp.matmul(uc, wc, preferred_element_type=f32) \
                * (J * di) ** -0.5
        o, kl, kept = SA.sparse_attention(q, k, v, qI, kI, w,
                                          scale=dh ** -0.5, topk=topk,
                                          real=a.mask)
        out = _seq_like(a, O.linear(o.reshape(B, T, H * dh), p["wo"]))
        out.state.update(indexer_kl=jnp.sum(kl), kept_pairs=jnp.sum(kept))
        return out

    return LayerOutput(name, "indexed_self_attention", D, [input], forward,
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
    The rotary embedding turns ``q_rope`` and ``k_rope`` only: on the last
    ``qk_rope_head_dim`` channels of the whole query head, in place (the
    span ``(qk_nope_head_dim, qk_nope_head_dim + qk_rope_head_dim)`` of
    ``ops.decoder_block.rotary_embedding``: no slice, no concatenation), and
    on the lone key head before it is broadcast; scores are
    ``[q_nope | q_rope] . [k_nope | k_rope]`` at scale ``(qk_nope_head_dim
    + qk_rope_head_dim) ** -0.5``; no bias, no QK-norm.

    Scopes inside the layer's own: ``mla_proj`` (the query projection, the
    down- and up-projection, the latent's norm, rotary under its own scope
    ``rotary``, assembling k) and ``attn_core`` (``causal_attention`` with
    keys wider than values); the output projection is the rest."""
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
            q = DB.rotary_embedding(q, rope_theta, span=(dn, dn + dr))
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(k_rope, (B, T, H, dr))],
                axis=-1)
            v = kv[..., dn:]
        with jax.named_scope("attn_core"):
            o = DB.causal_attention(q, k, v, scale=(dn + dr) ** -0.5)
        return _seq_like(a, O.linear(o.reshape(B, T, H * dv), p["wo"]))

    return LayerOutput(name, "latent_attention", D, [input], forward, specs)


def gated_delta_net(input: LayerOutput, *, num_key_heads: int,
                    num_value_heads: int, key_head_dim: int,
                    value_head_dim: int, conv_kernel_size: int = 4,
                    norm_eps: float = 1e-6,
                    name: Optional[str] = None) -> LayerOutput:
    """Linear attention by the gated delta rule.  ``[q | k | v | z] = x
    W_qkvz`` (``Hk dk + Hk dk + Hv dv + Hv dv`` columns), ``[b | a] = x
    W_ba`` (``Hv + Hv``).  ``[q | k | v]`` goes through a depthwise causal
    convolution of ``conv_kernel_size`` taps (no bias), then SiLU.  ``q`` and
    ``k`` are L2-normalised over a head's channels (``x * rsqrt(sum x^2 +
    1e-6)``), every key head serves ``Hv / Hk`` value heads, and ``q`` is
    scaled by ``dk ** -0.5``.  ``beta = sigmoid(b)``, ``g = -exp(A_log) *
    softplus(a + dt_bias)`` (float32; ``A_log`` and ``dt_bias`` one a value
    head).  Every value head keeps a state ``[dk, dv]`` that
    ``ops.delta_rule`` carries along the row (zero at its start).  The
    result is RMS-normed over each head's ``dv`` channels (a plain weight)
    and gated, ``norm(o) * silu(z)``, then projected by ``W_out``.

    Scopes inside the layer's own: ``gdn_proj`` (both in-projections, the
    convolution and SiLU, the L2 norms, ``q``'s scale, the cast and the move
    to the scan's heads-major layout, ``g`` and ``beta``) and ``gdn_scan``
    (the delta rule: the kernels ``gdn_chunk_fwd`` / ``gdn_chunk_bwd`` on the
    TPU, which read a key head for each of its value heads, and the layout
    of ``g``, ``beta`` and the output around them); the gated norm and the
    output projection are the rest.  Where ``ops.delta_rule.prep_kernel_rows``
    opens (the TPU backend, head widths that are multiples of 128, a row of
    whole blocks of rows), what lies between the projection and the scan is
    the kernel pair ``gdn_prep_fwd`` / ``gdn_prep_bwd``, one pass over the
    projection each way (``ops.delta_rule.conv_delta_rule``): ``W_qkvz`` is
    then read as two products, its ``[q | k | v]`` columns grouped by key
    head (the kernels' block is a group) and its ``z`` columns, so the
    kernels' ``[B, T, 2 Hk dk + Hv dv]`` float32 array and its gradient are
    what the products write and read, and nothing is sliced or joined at
    the activations' size.  Elsewhere the chain below runs in ``jax.numpy``,
    with the key heads repeated inside ``ops.delta_rule.delta_rule``."""
    name = name or next_name("gated_delta_net")
    D = input.size
    Hk, Hv, dk, dv = (num_key_heads, num_value_heads, key_head_dim,
                      value_head_dim)
    if Hv % Hk:
        raise ConfigError(f"{name!r}: {Hv} value heads are not whole groups "
                          f"over {Hk} key heads")
    nk, nv = Hk * dk, Hv * dv
    conv = 2 * nk + nv
    normal = lambda leaf, std: _pa(None, f"_{name}.{leaf}",   # noqa: E731
                                   init="normal", initial_std=std)
    specs = [
        ParamSpec(f"_{name}.w_qkvz", (D, conv + nv),
                  _fan_in(f"_{name}.w_qkvz", D)),
        ParamSpec(f"_{name}.w_ba", (D, 2 * Hv), _fan_in(f"_{name}.w_ba", D)),
        ParamSpec(f"_{name}.kernel", (conv_kernel_size, conv),
                  _fan_in(f"_{name}.kernel", conv_kernel_size)),
        ParamSpec(f"_{name}.a_log", (Hv,), normal("a_log", 1.0)),
        ParamSpec(f"_{name}.dt_bias", (Hv,), normal("dt_bias", 1.0)),
        ParamSpec(f"_{name}.norm", (dv,),
                  _pa(None, f"_{name}.norm", init="ones")),
        ParamSpec(f"_{name}.w_out", (nv, D), _fan_in(f"_{name}.w_out", nv)),
    ]

    def forward(ctx, params, a: Act) -> Act:
        if not a.is_seq:
            raise ConfigError(f"gated_delta_net {name!r} needs a sequence")
        _refuse_packed(a, name, "gated_delta_net")
        p = {s.name.rsplit(".", 1)[1]: params[s.name] for s in specs}
        x = a.value
        B, T = x.shape[:2]
        f32 = jnp.float32
        w, kernel = p["w_qkvz"], p["kernel"]
        rows = DR.prep_kernel_rows(T, Hk, Hv, dk, dv, conv_kernel_size)
        with jax.named_scope("gdn_proj"):
            ba = O.linear(x, p["w_ba"]).astype(f32)
            beta = jax.nn.sigmoid(ba[..., :Hv])
            g = -jnp.exp(p["a_log"].astype(f32)) * jax.nn.softplus(
                ba[..., Hv:] + p["dt_bias"].astype(f32))
            if rows is None:
                qkvz = O.linear(x, w)
                qkv = jax.nn.silu(DB.causal_short_conv(qkvz[..., :conv],
                                                       kernel))
                z = qkvz[..., conv:]
                q = DB.unit_norm(qkv[..., :nk].reshape(B, T, Hk, dk)) \
                    * dk ** -0.5
                k = DB.unit_norm(qkv[..., nk:2 * nk].reshape(B, T, Hk, dk))
                v = qkv[..., 2 * nk:].reshape(B, T, Hv, dv)
            else:       # the kernels' array: no z, a key head's group a block
                qkv = O.linear(x, DR.group_columns(w[:, :conv], Hk, dk))
                z = O.linear(x, w[:, conv:])
        if rows is None:
            with jax.named_scope("gdn_scan"):
                o = DR.delta_rule(q, k, v, g, beta)
        else:
            o = DR.conv_delta_rule(qkv, DR.group_columns(kernel, Hk, dk), g,
                                   beta, key_head_dim=dk, value_head_dim=dv,
                                   rows=rows)
        z = z.reshape(B, T, Hv, dv)
        y = DB.rms_norm(o, p["norm"], norm_eps) * jax.nn.silu(z)
        return _seq_like(a, O.linear(y.reshape(B, T, nv), p["w_out"]))

    return LayerOutput(name, "gated_delta_net", D, [input], forward, specs)


def mamba2_mixer(input: LayerOutput, *, num_heads: int, head_dim: int,
                 n_groups: int, state_size: int, conv_kernel_size: int = 4,
                 norm_eps: float = 1e-5,
                 name: Optional[str] = None) -> LayerOutput:
    """A Mamba-2 (SSD) state-space mixer.  ``[z | x | B | C | dt] = u W_in``
    (``H P + H P + G N + G N + H`` columns, a head's and a group's channels
    contiguous; ``H`` heads of ``P`` channels, ``G`` groups of ``N`` state
    channels, no bias).  ``[x | B | C]`` goes through a depthwise causal
    convolution of ``conv_kernel_size`` taps WITH a bias, then SiLU.  ``dt =
    softplus(dt + dt_bias)`` (one a head and token, float32, no clamp), ``A =
    -exp(A_log)`` (one a head).  Every head keeps a state ``[N, P]`` that
    ``ops.ssd_scan`` carries along the row (zero at its start): ``S_t =
    exp(dt_t A) S_{t-1} + B_t (dt_t x_t)^T``, ``y_t = S_t^T C_t + D x_t``,
    head ``h`` reading group ``h // (H // G)``'s ``B`` and ``C``.  Then the
    gate FIRST and the norm after: ``y * silu(z)``, RMS-normalised over each
    group's ``H P / G`` channels, times a weight of ``H P``; ``y W_out``.

    Scopes inside the layer's own: ``mamba_proj`` (both projections, the
    convolution with its bias and SiLU, ``dt``, the skip, the gate and the
    group norm) and ``ssd_scan`` (the recurrence: the kernels
    ``ssd_chunk_fwd`` / ``ssd_chunk_bwd`` on the TPU, the sums of ``dt A``
    and the chunked layout of the scalars around them).  Where
    ``ops.ssd_scan.prep_kernel_block`` opens (the TPU backend, ``H P``, ``G
    N`` and the column where ``[x | B | C]`` start multiples of 128, a
    convolution within the halo, a row of whole blocks that the scan does
    not pad), what lies between ``W_in``'s product and the scan is the kernel
    pair ``mamba_prep_fwd`` / ``mamba_prep_bwd`` under ``mamba_proj``, one
    pass over the ``[x | B | C]`` columns each way
    (``ops.ssd_scan.conv_ssd_scan``): the forward reads them where the ONE
    product wrote them and writes x ``[B, T, H P]`` and ``[B | C]`` ``[B, T,
    2 G N]`` in the compute dtype, of which the scan's kernels read a
    group's blocks, and x once more as the product's dtype has it (float32
    unless ``--amp``) for the skip below, which reads what the chain's
    reads; the
    reverse takes the scan's ``dx``, ``dB``, ``dC`` and the skip's part of
    ``dx`` (in x's dtype), and ``W_in``'s gradient is two products (the
    ``z`` columns' and the ``[x | B | C]`` columns') joined at the weight's
    size, so nothing is sliced, joined, padded or cast at the activations'
    size.
    Elsewhere the chain below runs in ``jax.numpy``."""
    name = name or next_name("mamba2_mixer")
    D = input.size
    H, P, G, N = num_heads, head_dim, n_groups, state_size
    if H % G:
        raise ConfigError(f"{name!r}: {H} heads are not whole groups over "
                          f"{G}")
    inner, conv = H * P, H * P + 2 * G * N
    normal = lambda leaf, std: _pa(None, f"_{name}.{leaf}",   # noqa: E731
                                   init="normal", initial_std=std)
    specs = [
        ParamSpec(f"_{name}.w_in", (D, inner + conv + H),
                  _fan_in(f"_{name}.w_in", D)),
        ParamSpec(f"_{name}.kernel", (conv_kernel_size, conv),
                  _fan_in(f"_{name}.kernel", conv_kernel_size)),
        ParamSpec(f"_{name}.conv_bias", (conv,),
                  _pa(None, f"_{name}.conv_bias", init="zeros")),
        ParamSpec(f"_{name}.a_log", (H,), normal("a_log", 1.0)),
        ParamSpec(f"_{name}.dt_bias", (H,), normal("dt_bias", 1.0)),
        ParamSpec(f"_{name}.d", (H,), _pa(None, f"_{name}.d", init="ones")),
        ParamSpec(f"_{name}.norm", (inner,),
                  _pa(None, f"_{name}.norm", init="ones")),
        ParamSpec(f"_{name}.w_out", (inner, D),
                  _fan_in(f"_{name}.w_out", inner)),
    ]

    def forward(ctx, params, a: Act) -> Act:
        if not a.is_seq:
            raise ConfigError(f"mamba2_mixer {name!r} needs a sequence")
        _refuse_packed(a, name, "mamba2_mixer")
        p = {s.name.rsplit(".", 1)[1]: params[s.name] for s in specs}
        u = a.value
        B, T = u.shape[:2]
        f32 = jnp.float32
        w = p["w_in"]
        block = SS.prep_kernel_block(T, H, P, G, N, conv_kernel_size, inner)
        with jax.named_scope("mamba_proj"):
            # dt's 64 columns as a product of their own with a float32
            # result: a step that went through a bf16 result would carry
            # its rounding into every decay of the row
            uc, wd = mxu_cast(u, w[:, inner + conv:])
            dt = jax.nn.softplus(
                jnp.matmul(uc, wd, preferred_element_type=f32)
                + p["dt_bias"].astype(f32))
            A = -jnp.exp(p["a_log"].astype(f32))
        if block is None:
            with jax.named_scope("mamba_proj"):
                zxbc = O.linear(u, w[:, :inner + conv])
                z = zxbc[..., :inner]
                xbc = jax.nn.silu(DB.causal_short_conv(
                    zxbc[..., inner:].astype(f32), p["kernel"],
                    p["conv_bias"])).astype(zxbc.dtype)
                x = xbc[..., :inner].reshape(B, T, H, P)
                Bm = xbc[..., inner:inner + G * N].reshape(B, T, G, N)
                Cm = xbc[..., inner + G * N:].reshape(B, T, G, N)
            with jax.named_scope("ssd_scan"):
                y = SS.ssd_scan(x, Bm, Cm, dt, A)
        else:       # the product, the kernels and the scan under one vjp
            z, x, y = SS.conv_ssd_scan(
                u, w[:, :inner + conv], p["kernel"], p["conv_bias"], dt, A,
                groups=G, block=block)
        with jax.named_scope("mamba_proj"):
            y = y.astype(f32) + p["d"].astype(f32)[:, None] * x.astype(f32)
            y = y.reshape(B, T, inner) * jax.nn.silu(z.astype(f32))
            y = DB.rms_norm(y.reshape(B, T, G, inner // G),
                            p["norm"].reshape(G, inner // G), norm_eps)
            out = O.linear(y.reshape(B, T, inner).astype(z.dtype),
                           p["w_out"])
        return _seq_like(a, out)

    return LayerOutput(name, "mamba2_mixer", D, [input], forward, specs)


def _mlp_specs(prefix: str, D: int, size: int, gated: bool = True):
    return [
        ParamSpec(f"{prefix}w1", (D, size), _fan_in(f"{prefix}w1", D)),
        *([ParamSpec(f"{prefix}w3", (D, size), _fan_in(f"{prefix}w3", D))]
          if gated else []),
        ParamSpec(f"{prefix}w2", (size, D), _fan_in(f"{prefix}w2", size)),
    ]


def _gated_mlp(x, w1, w3, w2):
    return O.linear(jax.nn.silu(O.linear(x, w1)) * O.linear(x, w3), w2)


def _relu2_mlp(x, w1, w2):
    return O.linear(jnp.square(jax.nn.relu(O.linear(x, w1))), w2)


def _reglu_mlp(x, w1, w3, w2):
    return O.linear(jax.nn.relu(O.linear(x, w1)) * O.linear(x, w3), w2)


#: ``expert_act`` -> the shared expert's MLP, one an entry of
#: ``ops.moe.EXPERT_ACTS``
_SHARED_MLPS = {"gated_silu": _gated_mlp, "relu2": _relu2_mlp,
                "gated_relu": _reglu_mlp}


def gated_mlp(input: LayerOutput, size: int, *,
              name: Optional[str] = None,
              param_name: Optional[str] = None) -> LayerOutput:
    """``W_2(silu(W_1 x) * W_3 x)`` with ``size`` hidden units.
    ``param_name``: what the three leaves are named after (default: the
    layer's name); layers given the same one share them
    (:func:`causal_self_attention`)."""
    name = name or next_name("gated_mlp")
    D = input.size
    specs = _mlp_specs(f"_{param_name or name}.", D, size)

    def forward(ctx, params, a: Act) -> Act:
        out = _gated_mlp(a.value, *(params[s.name] for s in specs))
        return _seq_like(a, out) if a.is_seq else Act(value=out)

    return LayerOutput(name, "gated_mlp", D, [input], forward, specs)


def expert_mlp(input: LayerOutput, size: int, *, num_experts: int,
               experts_held: Optional[Sequence[int]] = None, top_k: int,
               norm_topk_prob: bool = True, routed_scaling_factor: float = 1.0,
               shared_size: int = 0, scoring: str = "sigmoid",
               shared_gate: bool = False, expert_act: str = "gated_silu",
               selection_bias: bool = True,
               router_input: Optional[LayerOutput] = None,
               name: Optional[str] = None) -> LayerOutput:
    """A dropless mixture of experts of ``size`` hidden units, as the chip
    that holds experts ``experts_held = (first, count)`` of ``num_experts``
    computes it (default: all of them).  ``expert_act`` names the experts'
    form (``ops.moe.EXPERT_ACTS``): ``"gated_silu"``, a gated MLP ``W_2(silu(
    W_1 x) * W_3 x)``; ``"gated_relu"`` (ReGLU), ``W_2(relu(W_1 x) * W_3
    x)``, a hidden unit exactly zero wherever ``W_1 x <= 0``; ``"relu2"``,
    TWO matrices and a squared ReLU, ``W_2 relu(W_1 x)^2``: the layer, and
    its shared expert, then have no ``w3`` leaf.  Every token is
    routed over all ``num_experts``: with ``scoring="sigmoid"`` by sigmoid
    scores, the ``top_k`` largest of ``score + expert_bias``; with
    ``scoring="softmax"`` by the softmax over all the router's outputs, the
    ``top_k`` largest, and the layer has no ``expert_bias`` (nor has it with
    ``selection_bias=False``: sigmoid scores chosen as they are); the
    weights are normalised over the chosen where ``norm_topk_prob``
    (``ops.moe.route_tokens``).  The layer's value is the part of the result
    that the experts held give; no assignment to an expert held is dropped,
    whatever the routing.  On one chip there is no exchange, and nothing
    stands in for the other chips.

    ``router_input``: the router reads ANOTHER layer's output than the
    experts do (an early router: the sequence mixer's normed input, where
    ``input`` is the feed-forward's).  The logits, the choice, the weights
    and the sort by expert are then a layer of their own, ``<name>/
    moe_routing`` (the router's leaves keep their names, ``_<name>.router``),
    which runs right after ``router_input`` does and so ahead of whatever
    else reads it; the router's gradient goes to ``router_input`` and none
    of it to ``input``.  Without it the router reads ``input``, inside this
    layer, under the scope ``moe_routing``.

    ``shared_size``: the hidden units of the shared experts, ONE MLP of the
    experts' form (``_<name>.shared_w1`` / ``w3`` / ``w2``) that every token
    passes through, added to the routed result: unweighted, or, with
    ``shared_gate``, times ``sigmoid(x . w_g)`` (``_<name>.shared_gate``,
    one weight a hidden channel); every chip of the deployment computes it
    whole on its own tokens.  Scope ``moe_shared``.

    ``Act.state`` carries ``expert_load`` (assignments per expert held,
    int32), ``uncomputed`` (assignments to an expert held that no row
    was computed for: 0) and ``gate_zero`` (of the computed rows' ``size``
    gate values, how many the ReLU made exactly zero: counted for
    ``"gated_relu"``, 0 for the other forms)."""
    name = name or next_name("expert_mlp")
    D, E = input.size, num_experts
    first, held = experts_held or (0, num_experts)
    if held < 1 or first < 0 or first + held > E or top_k > E:
        raise ConfigError(f"{name!r}: experts {first}..{first + held - 1} "
                          f"and {top_k} a token do not fit {E} experts")
    if scoring not in ("sigmoid", "softmax"):
        raise ConfigError(f"{name!r}: unknown scoring {scoring!r}")
    if shared_gate and not shared_size:
        raise ConfigError(f"{name!r}: a gate without a shared expert")
    if expert_act not in M.EXPERT_ACTS:
        raise ConfigError(f"{name!r}: unknown expert_act {expert_act!r}; "
                          f"have {sorted(M.EXPERT_ACTS)}")
    if router_input is not None and router_input.size != D:
        raise ConfigError(f"{name!r}: the router's input is "
                          f"{router_input.size} wide, the experts' {D}")
    gated = M.EXPERT_ACTS[expert_act].gated
    bias = [ParamSpec(f"_{name}.expert_bias", (E,),
                      _pa(None, f"_{name}.expert_bias", init="zeros"))
            ] if scoring == "sigmoid" and selection_bias else []
    router = [ParamSpec(f"_{name}.router", (D, E),
                        _fan_in(f"_{name}.router", D)), *bias]
    experts = [
        ParamSpec(f"_{name}.w1", (held, D, size), _fan_in(f"_{name}.w1", D)),
        *([ParamSpec(f"_{name}.w3", (held, D, size),
                     _fan_in(f"_{name}.w3", D))] if gated else []),
        ParamSpec(f"_{name}.w2", (held, size, D),
                  _fan_in(f"_{name}.w2", size)),
    ]
    shared = (_mlp_specs(f"_{name}.shared_", D, shared_size, gated)
              if shared_size else [])
    gate = [ParamSpec(f"_{name}.shared_gate", (D,),
                      _fan_in(f"_{name}.shared_gate", D))
            ] if shared_gate else []

    def leaves(params, specs):
        return {s.name.rsplit(".", 1)[1]: params[s.name] for s in specs}

    def route(p, a: Act):
        """``(experts [N, k], weights [N, k])`` of ``a``'s tokens; the caller
        opens the scope."""
        idx, weights = M.route_tokens(
            a.value.reshape(-1, D), p["router"], p.get("expert_bias"),
            top_k=top_k, norm_topk=norm_topk_prob,
            scaling=routed_scaling_factor, scoring=scoring)
        if a.is_seq:     # a padded position is no token: nothing held
            idx = jnp.where(a.mask.reshape(-1, 1) > 0, idx, -1)
        return idx, weights

    def route_ahead(ctx, params, a: Act) -> Act:
        """The early router's layer (named ``<name>/moe_routing``, which is
        its scope): the weights, and as state the choice and its sort."""
        idx, weights = route(leaves(params, router), a)
        with jax.named_scope("moe_grouping"):
            counts, order = M.count_assignments(idx, first_expert=first,
                                                held=held)
        return Act(value=weights,
                   state={"idx": idx, "counts": counts, "order": order})

    def forward(ctx, params, a: Act, routed: Optional[Act] = None) -> Act:
        p = leaves(params, experts if routed else router + experts)
        x = a.value.reshape(-1, D)
        if routed:
            idx, weights = routed.state["idx"], routed.value
            presorted = routed.state["counts"], routed.state["order"]
        else:
            with jax.named_scope("moe_routing"):
                idx, weights = route(p, a)
            presorted = None
        tm = M.moe_kernel_row_tile(D, size, idx.size)
        y, load, uncomputed, gate_zero = M.expert_layer(
            x, idx, weights, p["w1"], p.get("w3"), p["w2"], num_experts=E,
            first_expert=first, tm=tm or 8, kernels=tm is not None,
            expert_act=expert_act, sorted_by_expert=presorted)
        if shared:
            with jax.named_scope("moe_shared"):
                ys = _SHARED_MLPS[expert_act](
                    x, *(params[s.name] for s in shared))
                if gate:
                    wg = params[gate[0].name].astype(jnp.float32)
                    ys = ys * jax.nn.sigmoid(jnp.sum(
                        x.astype(jnp.float32) * wg, -1, keepdims=True)
                    ).astype(ys.dtype)
                y = y + ys
        y = y.reshape(a.value.shape)
        state = {"expert_load": load, "uncomputed": uncomputed,
                 "gate_zero": gate_zero}
        if a.is_seq:
            out = _seq_like(a, y)
            out.state.update(state)
            return out
        return Act(value=y, state=state)

    meta = {"zero_gates": True} if M.EXPERT_ACTS[expert_act].zero_gates \
        else {}
    if router_input is None:
        return LayerOutput(name, "expert_mlp", D, [input], forward,
                           router + experts + shared + gate, meta=meta)
    ahead = LayerOutput(f"{name}/moe_routing", "expert_router", top_k,
                        [router_input], route_ahead, router)
    router_input.meta.setdefault("run_next", []).append(ahead)
    return LayerOutput(name, "expert_mlp", D, [input, ahead], forward,
                       experts + shared + gate, meta=meta)


def _head_spec(name: str, input: LayerOutput, label: LayerOutput,
               embedding: Optional[LayerOutput]) -> ParamSpec:
    """The head's leaf: the embedding's matrix (tied) or ``_<name>.w``
    ``[hidden, label.size]``."""
    if embedding is None:
        return ParamSpec(f"_{name}.w", (input.size, label.size),
                         _fan_in(f"_{name}.w", input.size))
    head = embedding.param_specs[0]
    if head.shape[1] != input.size:
        raise ConfigError(f"{name!r}: the embedding is {head.shape[1]} "
                          f"wide, the hidden state {input.size}")
    return head


def lm_head_cost(input: LayerOutput, label: LayerOutput, *,
                 embedding: Optional[LayerOutput] = None,
                 aux_costs: Sequence[LayerOutput] = (),
                 name: Optional[str] = None) -> LayerOutput:
    """Mean next-token cross-entropy over the real positions.  With
    ``embedding`` the head is that layer's matrix (tied): ``logits = h
    E^T``.  Without, the head is a parameter of this layer, ``_<name>.w``
    ``[hidden, label.size]`` (untied): ``logits = h W``.  Through
    ``ops.sequence_softmax_ce_readout``, so the logits are held once, in the
    compute dtype.  ``aux_costs``: layers whose (scalar) value is a term of
    the loss SUMMED over the batch's tokens (an indexer's ``L_I``); the
    cost is then ``(sum of the cross-entropies + sum of the terms) /
    tokens``."""
    name = name or next_name("lm_cost")
    head = _head_spec(name, input, label, embedding)

    def forward(ctx, params, h: Act, lab: Act, *aux: Act) -> Act:
        w = params[head.name]
        w = w if embedding is None else w.T
        cost = O.sequence_softmax_ce_readout(
            h.value, w, jnp.zeros((w.shape[1],), w.dtype), lab.value, h.mask)
        if aux:
            from paddle_tpu.ops.losses import token_count

            cost = cost + sum(t.value.astype(cost.dtype) for t in aux) \
                / token_count(h.mask.astype(cost.dtype))
        return Act(value=cost)

    return LayerOutput(name, "lm_head_cost", 1, [input, label, *aux_costs],
                       forward, [head])


def lm_head_token_cost(input: LayerOutput, label: LayerOutput, *,
                       embedding: Optional[LayerOutput] = None,
                       name: Optional[str] = None,
                       param_name: Optional[str] = None) -> LayerOutput:
    """The next-token cross-entropy of EVERY position, ``[B, T]`` float32
    (a padded position's too: what reads it masks), over the head
    :func:`lm_head_cost` has: the embedding's matrix, or a leaf
    ``_<param_name>.w`` (default: the layer's name) that layers given the
    same ``param_name`` share.  Through
    ``ops.softmax_ce_readout_per_token``: the kernels and the logits'
    dtype are ``lm_head_cost``'s, and the backward pass takes a cotangent a
    token, so a cost may weigh each token's cross-entropy by something that
    has a gradient of its own (:func:`loop_exit_cost`)."""
    name = name or next_name("lm_token_cost")
    head = _head_spec(param_name or name, input, label, embedding)

    def forward(ctx, params, h: Act, lab: Act) -> Act:
        w = params[head.name]
        w = w if embedding is None else w.T
        return _seq_like(h, O.softmax_ce_readout_per_token(
            h.value, w, jnp.zeros((w.shape[1],), w.dtype), lab.value))

    return LayerOutput(name, "lm_head_token_cost", 1, [input, label],
                       forward, [head])


def token_gate(input: LayerOutput, *, name: Optional[str] = None,
               param_name: Optional[str] = None) -> LayerOutput:
    """One number a token, ``x . w + b`` in float32, ``[B, T]``: a gate's
    logit.  Leaves ``_<param_name>.w`` ``[hidden]`` and ``_<param_name>.b``
    ``[1]`` (default: the layer's name; layers given the same one share
    them)."""
    name = name or next_name("token_gate")
    pre = f"_{param_name or name}"
    specs = [ParamSpec(f"{pre}.w", (input.size,),
                       _fan_in(f"{pre}.w", input.size)),
             ParamSpec(f"{pre}.b", (1,), _pa(None, f"{pre}.b", init="zeros"))]

    def forward(ctx, params, a: Act) -> Act:
        f32 = jnp.float32
        g = jnp.sum(a.value.astype(f32) * params[specs[0].name].astype(f32),
                    -1) + params[specs[1].name].astype(f32)
        return _seq_like(a, g) if a.is_seq else Act(value=g)

    return LayerOutput(name, "token_gate", 1, [input], forward, specs)


def exit_distribution(gates):
    """``[R, ...]`` from the ``R - 1`` gate logits ``[R - 1, ...]`` of all
    exits but the last, float32: ``lam_t = sigmoid(g_t)``, ``S_t = S_(t-1)
    (1 - lam_t)`` from ``S_0 = 1``, ``p_t = lam_t S_(t-1)`` and the mass
    that is left, ``S_(R-1)``, on the last exit.  ``1 - lam_t`` is taken as
    ``sigmoid(-g_t)`` (no cancellation where a gate saturates), so a token's
    masses add up to 1 within rounding whatever the logits."""
    g = jnp.asarray(gates, jnp.float32)
    stay = jnp.cumprod(jax.nn.sigmoid(-g), axis=0)          # S_1 .. S_(R-1)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], 0)
    return jnp.concatenate([jax.nn.sigmoid(g) * before, stay[-1:]], 0)


def loop_exit_cost(exits: Sequence[LayerOutput],
                   gates: Sequence[LayerOutput], label: LayerOutput, *,
                   beta: float = 0.1,
                   name: Optional[str] = None) -> LayerOutput:
    """The expected loss of a stack that may leave after any of its ``R``
    passes.  ``exits``: the ``R`` per-token cross-entropies ``CE_t`` ``[B,
    T]`` (:func:`lm_head_token_cost`), ``gates``: the gate logits ``g_t``
    ``[B, T]`` of all exits but the last (:func:`token_gate`), ``label``:
    the targets (their mask says which tokens are real).  With ``p`` the
    token's :func:`exit_distribution`::

        cost = sum over real tokens [sum_t p_t CE_t - beta H(p)] / tokens

    ``H(p) = -sum_t p_t log p_t``, the entropy against a uniform prior over
    the exits (``beta`` 0: the expected cross-entropy alone).  All of it in
    float32; ``log p_t`` is taken of 1 where ``p_t`` is 0 in float32, so a
    saturated gate gives that exit no entropy and no NaN, forward or
    backward.  One exit and no gate: ``p_1 = 1``, the plain mean
    cross-entropy.

    ``Act.state`` carries the sums over the real tokens of ``p_t``
    (``exit_mass`` ``[R]``), of ``CE_t`` (``exit_ce`` ``[R]``) and of
    ``H(p)`` (``exit_entropy``)."""
    name = name or next_name("loop_exit_cost")
    exits, gates = list(exits), list(gates)
    if not exits or len(gates) != len(exits) - 1:
        raise ConfigError(f"{name!r}: {len(exits)} exits take "
                          f"{max(len(exits) - 1, 0)} gates, not {len(gates)}")

    def forward(ctx, params, lab: Act, *acts: Act) -> Act:
        from paddle_tpu.ops.losses import token_count

        f32 = jnp.float32
        mask = lab.mask.astype(f32)
        ce = jnp.stack([a.value.astype(f32) for a in acts[:len(exits)]])
        if gates:
            p = exit_distribution([a.value for a in acts[len(exits):]])
        else:
            p = jnp.ones_like(ce)
        entropy = -jnp.sum(p * jnp.log(jnp.where(p > 0, p, 1.0)), 0)
        per_tok = jnp.sum(p * ce, 0) - beta * entropy
        cost = jnp.sum(per_tok * mask) / token_count(mask)
        return Act(value=cost, state={
            "exit_mass": jnp.sum(p * mask, (1, 2)),
            "exit_ce": jnp.sum(ce * mask, (1, 2)),
            "exit_entropy": jnp.sum(entropy * mask)})

    return LayerOutput(name, "loop_exit_cost", 1, [label, *exits, *gates],
                       forward, [])


from paddle_tpu.config.capture import wrap_module as _wrap_module  # noqa: E402

_wrap_module(globals(), [n for n in __all__ if n != "remat_block"])
