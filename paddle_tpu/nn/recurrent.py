"""Recurrent groups — the RecurrentGradientMachine analog.

Reference: a "recurrent layer group" runs an arbitrary sub-network
frame-by-frame over a sequence with ``memory`` edges carrying state across
frames, plus boot layers for t=0, and beam-search generation over the same
step net (gserver/gradientmachines/RecurrentGradientMachine.{h,cpp};
config DSL recurrent_group / memory in
python/paddle/trainer_config_helpers/layers.py:3298, config_parser.py:393-427;
agent/gather/scatter layers route tensors in/out of the group).

TPU-native: the step sub-network is *itself a Topology* built from the same
layer DSL, with per-frame inputs declared as non-sequence data layers; the
group compiles to one ``lax.scan`` whose body applies the sub-topology.  The
reference's per-frame dynamic batching (shrinking active set, SequenceToBatch)
is replaced by masking: finished rows carry state through unchanged — same
semantics, static shapes, and the whole unroll is one XLA program.

``SequenceGenerator`` provides generation (greedy/beam) over a functional step
protocol; any recurrent_group whose step ends in a vocab softmax can be
wrapped into it.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

import paddle_tpu.ops as O
from paddle_tpu.nn.graph import Act, LayerOutput, Topology, next_name
from paddle_tpu.nn.layers import data as data_layer
from paddle_tpu.utils.error import ConfigError

__all__ = ["Memory", "StaticInput", "GeneratedInput", "recurrent_group",
           "beam_search", "SequenceGenerator"]


@dataclass
class Memory:
    """Recurrent state slot: carries the step output named ``link`` (or the
    step's returned memory-update layer) from frame t to t+1.  ``boot``
    (a LayerOutput producing [B, size]) seeds t=0; default zeros."""

    name: str
    size: int
    boot: Optional[LayerOutput] = None


@dataclass
class StaticInput:
    """Per-sequence (not per-frame) input visible to every step — the analog
    of the reference's StaticInput (layers.py)."""

    input: LayerOutput


@dataclass
class GeneratedInput:
    """Marks the generated-token slot of a ``beam_search`` step — the analog
    of the reference's GeneratedInput (trainer_config_helpers/layers.py:3556):
    at step t the slot carries the token chosen at t-1 (``bos_id`` at t=0).
    The step net embeds it itself (declare an ``embedding`` layer inside the
    step), rather than naming an external embedding parameter."""

    size: int          # vocabulary size
    bos_id: int = 0
    eos_id: int = 1


def recurrent_group(
    step: Callable[..., Sequence[LayerOutput]],
    input: Sequence[LayerOutput | StaticInput],
    memories: Sequence[Memory],
    *,
    reverse: bool = False,
    name: Optional[str] = None,
) -> LayerOutput:
    """Run ``step`` over the frames of the sequence inputs.

    ``step(*frame_layers, *static_layers, *memory_layers) -> [out, *mem_updates]``
    builds the per-frame sub-network symbolically; it is called ONCE at config
    time.  ``mem_updates[i]`` is the new value of ``memories[i]``.  The group's
    output is the sequence of ``out`` frames.
    """
    name = name or next_name("recurrent_group")
    seq_inputs = [i for i in input if isinstance(i, LayerOutput)]
    static_inputs = [i.input for i in input if isinstance(i, StaticInput)]
    if not seq_inputs:
        raise ConfigError("recurrent_group needs at least one sequence input")

    # ---- build the step sub-topology (config time) ----
    frame_layers = [
        data_layer(f"__{name}_frame{i}__", size=l.size) for i, l in enumerate(seq_inputs)
    ]
    static_layers = [
        data_layer(f"__{name}_static{i}__", size=l.size)
        for i, l in enumerate(static_inputs)
    ]
    mem_layers = [data_layer(f"__{name}_mem_{m.name}__", size=m.size) for m in memories]
    result = step(*frame_layers, *static_layers, *mem_layers)
    if isinstance(result, LayerOutput):
        result = [result]
    out_layer, mem_updates = result[0], list(result[1:])
    if len(mem_updates) != len(memories):
        raise ConfigError(
            f"step returned {len(mem_updates)} memory updates for "
            f"{len(memories)} memories"
        )
    sub_topo = Topology([out_layer, *mem_updates])

    # hoist sub-net parameters into the group layer
    specs = list(sub_topo.param_specs.values())
    parents = seq_inputs + static_inputs + [m.boot for m in memories if m.boot is not None]
    boot_ix: Dict[int, int] = {}
    k = len(seq_inputs) + len(static_inputs)
    for mi, m in enumerate(memories):
        if m.boot is not None:
            boot_ix[mi] = k
            k += 1

    def forward(ctx, params, *acts: Act) -> Act:
        seq_acts = acts[: len(seq_inputs)]
        static_acts = acts[len(seq_inputs) : len(seq_inputs) + len(static_inputs)]
        ref = seq_acts[0]
        B = ref.value.shape[0]
        mem0 = []
        for mi, m in enumerate(memories):
            if mi in boot_ix:
                mem0.append(acts[boot_ix[mi]].value)
            else:
                mem0.append(jnp.zeros((B, m.size), jnp.float32))

        def base_feed(mems):
            feed = {}
            for sl, sa in zip(static_layers, static_acts):
                # the whole Act passes through: a static input may be an
                # encoded sequence the step attends over (simple_attention),
                # so its lengths/mask/state must survive
                feed[sl.name] = sa
            for ml, mv in zip(mem_layers, mems):
                feed[ml.name] = Act(value=mv)
            return feed

        if ref.is_nested:
            # outer iteration over SUB-SEQUENCES: each frame is itself a
            # padded sequence [B, Ti, ...] with its own lengths — the
            # RecurrentGradientMachine nested-sequence mode (reference:
            # RecurrentGradientMachine.cpp; Argument.h:90 sub positions;
            # proven equivalent to the flat unroll in
            # test_RecurrentGradientMachine.cpp sequence_nest_rnn.conf)
            Ti = ref.value.shape[2]

            def step_fn(mems, inp):
                frames, sl_t = inp
                imask = O.mask_from_lengths(sl_t, Ti)
                feed = base_feed(mems)
                for fl, f_t in zip(frame_layers, frames):
                    feed[fl.name] = Act(value=f_t, lengths=sl_t, mask=imask)
                outs, _ = sub_topo.apply(params, {}, feed, train=ctx.train,
                                         rng=None)
                out_act = outs[out_layer.name]
                new_mems = tuple(outs[u.name].value for u in mem_updates)
                payload = {"v": out_act.value}
                if out_act.is_seq:
                    payload["l"] = out_act.lengths
                return new_mems, payload

            xs = (tuple(a.value for a in seq_acts), ref.sub_lengths)
            _, outs = O.scan_rnn(step_fn, tuple(mem0), xs, ref.mask,
                                 reverse=reverse)
            if "l" in outs:  # step emitted a sequence -> nested output
                return Act(value=outs["v"], lengths=ref.lengths, mask=ref.mask,
                           sub_lengths=outs["l"])
            return Act(value=outs["v"], lengths=ref.lengths, mask=ref.mask)

        def step_fn(mems, frames):
            feed = base_feed(mems)
            for fl, f_t in zip(frame_layers, frames):
                feed[fl.name] = Act(value=f_t)
            outs, _ = sub_topo.apply(params, {}, feed, train=ctx.train,
                                     rng=None)
            new_mems = tuple(outs[u.name].value for u in mem_updates)
            return new_mems, outs[out_layer.name].value

        xs = tuple(a.value for a in seq_acts)
        _, out_seq = O.scan_rnn(step_fn, tuple(mem0), xs, ref.mask, reverse=reverse)
        return Act(value=out_seq, lengths=ref.lengths, mask=ref.mask)

    return LayerOutput(name, "recurrent_group", out_layer.size, parents, forward, specs)


def beam_search(
    step: Callable[..., Sequence[LayerOutput]],
    input: Sequence[GeneratedInput | StaticInput],
    memories: Sequence[Memory],
    *,
    beam_size: int = 3,
    max_length: int = 50,
    length_penalty: float = 0.0,
    name: Optional[str] = None,
) -> LayerOutput:
    """Generation-mode recurrent group — the trainer_config_helpers
    ``beam_search`` analog (reference: layers.py:3693 + GeneratedInput
    :3556, RecurrentGradientMachine::generateSequence).

    ``step(gen_layer, *static_layers, *memory_layers) -> [vocab_logits,
    *mem_updates]`` builds the per-token sub-network ONCE at config time:
    ``gen_layer`` carries the previous token ids [N] (int32), the step must
    end in an un-normalized vocab-size logits layer.  Forward runs the whole
    jitted beam search (SequenceGenerator) on device.

    Output Act: ``value`` [B, beam_size, max_length] token ids best-first;
    ``state['scores']`` [B, beam_size] log-prob scores.
    """
    name = name or next_name("beam_search")
    gens = [i for i in input if isinstance(i, GeneratedInput)]
    static_inputs = [i.input for i in input if isinstance(i, StaticInput)]
    if len(gens) != 1:
        raise ConfigError("beam_search needs exactly one GeneratedInput")
    gen = gens[0]
    if not memories:
        raise ConfigError("beam_search needs at least one memory")

    if not static_inputs and all(m.boot is None for m in memories):
        raise ConfigError(
            "beam_search needs at least one StaticInput or a booted memory "
            "to derive the batch size (an unconditioned generator has no "
            "batch-shaped input)"
        )
    gen_layer = data_layer(f"__{name}_gen__", size=gen.size, dtype="int32")
    static_layers = [
        data_layer(f"__{name}_static{i}__", size=l.size)
        for i, l in enumerate(static_inputs)
    ]
    mem_layers = [data_layer(f"__{name}_mem_{m.name}__", size=m.size) for m in memories]
    result = step(gen_layer, *static_layers, *mem_layers)
    if isinstance(result, LayerOutput):
        result = [result]
    out_layer, mem_updates = result[0], list(result[1:])
    if len(mem_updates) != len(memories):
        raise ConfigError(
            f"step returned {len(mem_updates)} memory updates for "
            f"{len(memories)} memories"
        )
    if out_layer.size != gen.size:
        raise ConfigError(
            f"beam_search step must end in a vocab-size ({gen.size}) logits "
            f"layer, got size {out_layer.size}"
        )
    sub_topo = Topology([out_layer, *mem_updates])
    specs = list(sub_topo.param_specs.values())
    parents = static_inputs + [m.boot for m in memories if m.boot is not None]
    boot_ix: Dict[int, int] = {}
    k = len(static_inputs)
    for mi, m in enumerate(memories):
        if m.boot is not None:
            boot_ix[mi] = k
            k += 1

    def forward(ctx, params, *acts: Act) -> Act:
        static_acts = acts[: len(static_inputs)]
        if static_acts:
            B = static_acts[0].value.shape[0]
        else:
            B = acts[boot_ix[min(boot_ix)]].value.shape[0]
        K = beam_size

        # statics are per-sequence: tile rows per beam ([B,...] -> [B*K,...])
        tiled_statics = [
            Act(value=jnp.repeat(a.value, K, axis=0),
                lengths=(jnp.repeat(a.lengths, K, axis=0)
                         if a.lengths is not None else None),
                mask=(jnp.repeat(a.mask, K, axis=0)
                      if a.mask is not None else None))
            for a in static_acts
        ]

        mems0 = {}
        for mi, m in enumerate(memories):
            if mi in boot_ix:
                mems0[m.name] = acts[boot_ix[mi]].value
            else:
                mems0[m.name] = jnp.zeros((B, m.size), jnp.float32)

        def step_fn(p, tokens, mems):
            feed = {gen_layer.name: Act(value=tokens)}
            for sl, sa in zip(static_layers, tiled_statics):
                feed[sl.name] = sa
            for ml, m in zip(mem_layers, memories):
                feed[ml.name] = Act(value=mems[m.name])
            outs, _ = sub_topo.apply(p, {}, feed, train=False)
            logits = outs[out_layer.name].value
            new_mems = {m.name: outs[u.name].value
                        for m, u in zip(memories, mem_updates)}
            return logits, new_mems

        generator = SequenceGenerator(step_fn, vocab_size=gen.size,
                                      bos_id=gen.bos_id, eos_id=gen.eos_id)
        tokens, scores = generator.generate(
            params, mems0, batch_size=B, beam_size=K, max_len=max_length,
            length_penalty=length_penalty)
        return Act(value=tokens, state={"scores": scores})

    return LayerOutput(name, "beam_search", gen.size, parents, forward, specs)


# ---------------------------------------------------------------------------
# generation
# ---------------------------------------------------------------------------


class SequenceGenerator:
    """Greedy/beam generation over a functional step protocol — the analog of
    RecurrentGradientMachine::generateSequence + SWIG SequenceGenerator
    (paddle/api/PaddleAPI.h:1002).

    ``step_fn(params, tokens [N], mems) -> (logits [N, V], new_mems)`` where
    ``mems`` is a pytree with leading dim N.  ``init_fn(params, context) ->
    mems`` seeds per-sequence state from arbitrary context (e.g. encoder
    output).  Everything jits; beams live on-device.
    """

    def __init__(self, step_fn, *, vocab_size: int, bos_id: int = 0,
                 eos_id: int = 1):
        self.step_fn = step_fn
        self.V = vocab_size
        self.bos = bos_id
        self.eos = eos_id

    def generate(self, params, mems0, *, batch_size: int, beam_size: int = 3,
                 max_len: int = 50, length_penalty: float = 0.0,
                 candidate_adjust_fn=None, drop_fn=None, return_trace: bool = False,
                 early_exit=None, use_kernel=None):
        """mems0: pytree with leading dim B. Returns (tokens [B,K,max_len],
        scores [B,K]) best-first.

        Without beam-control callbacks the search runs on the fused decode
        engine (ops/decode.py): per-row top-k + logsumexp straight from the
        step logits (one HBM pass, no f32 log-softmax buffer; Pallas kernel
        on TPU where ``decode_kernel_config`` admits the shape),
        all-beams-finished early exit, packed beam-state gather — output-identical to the scan path.
        The callback/trace protocol below needs the full [B,K,V] per-step
        log-probs (and, for the trace, a record at every one of the
        ``max_len`` steps), so those runs keep the fixed-length scan.

        Beam-search control callbacks — the analog of the reference's
        ``registerBeamSearchControlCallbacks`` / ``...StatisticsCallbacks``
        (reference: RecurrentGradientMachine.h:73-188):

        - ``candidate_adjust_fn(step_logp [B,K,V], tokens, t)`` → adjusted
          per-candidate log-probs, applied before top-k each step
          (beamSearchCandidateAdjust: user re-scoring / constrained decoding).
          ``tokens`` is the FULL [B,K,max_len+1] buffer; slots ``> t`` are eos
          padding — index with ``t`` (e.g. ``tokens[:, :, t]`` is the last
          generated token), not ``-1``.
        - ``drop_fn(tokens [B,K,max_len+1], scores [B,K], t)`` → bool [B,K];
          True drops that beam after expansion (DropCallback).  Same padding
          caveat: the newest token is at slot ``t+1``.
        - ``return_trace=True`` additionally returns a per-step expansion
          record dict with ``parent`` [T,B,K] (beam each slot came from),
          ``token`` [T,B,K], ``score`` [T,B,K] — the statistics-callback
          analog, materialized as arrays instead of host callbacks so the
          whole search stays one XLA program.  Trace arrays are in the
          search's native (pre-sort) beam order; the returned tokens/scores
          are sorted best-first, and ``trace["order"]`` [B,K] maps output
          slot -> native slot (``trace["token"][T-1, b, order[b, 0]]`` is
          the last token of the best returned beam).
        """
        B, K, V = batch_size, beam_size, self.V
        step_fn = self.step_fn
        if candidate_adjust_fn is None and drop_fn is None and not return_trace:
            from paddle_tpu.ops.decode import (LogitsReadout, beam_decode)

            return beam_decode(
                lambda tokens, mems: step_fn(params, tokens, mems),
                LogitsReadout(), mems0, batch_size=B, beam_size=K,
                vocab_size=V, max_len=max_len, bos=self.bos, eos=self.eos,
                length_penalty=length_penalty, early_exit=early_exit,
                use_kernel=use_kernel)

        def tile(x):
            return jnp.repeat(x, K, axis=0)

        mems = jax.tree_util.tree_map(tile, mems0)
        logp = jnp.tile(jnp.asarray([0.0] + [-1e9] * (K - 1), jnp.float32)[None], (B, 1))
        tokens = jnp.full((B, K, max_len + 1), self.eos, jnp.int32)
        tokens = tokens.at[:, :, 0].set(self.bos)
        finished = jnp.zeros((B, K), bool)
        eos_only = jnp.full((V,), -1e9, jnp.float32).at[self.eos].set(0.0)

        def scan_step(carry, t):
            tokens, logp, mems, finished = carry
            y = lax.dynamic_index_in_dim(tokens, t, axis=2, keepdims=False)
            logits, mems_new = step_fn(params, y.reshape(B * K), mems)
            step_logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1).reshape(B, K, V)
            step_logp = jnp.where(finished[..., None], eos_only[None, None], step_logp)
            if candidate_adjust_fn is not None:
                step_logp = candidate_adjust_fn(step_logp, tokens, t)
                step_logp = jnp.where(finished[..., None], eos_only[None, None], step_logp)
            flat = (logp[..., None] + step_logp).reshape(B, K * V)
            new_logp, idx = lax.top_k(flat, K)
            beam_idx, tok = idx // V, (idx % V).astype(jnp.int32)

            def reorder(x):
                xb = x.reshape(B, K, *x.shape[1:])
                ix = beam_idx.reshape(B, K, *([1] * (xb.ndim - 2)))
                return jnp.take_along_axis(xb, ix, axis=1).reshape(B * K, *x.shape[1:])

            mems_new = jax.tree_util.tree_map(reorder, mems_new)
            tokens = jnp.take_along_axis(tokens, beam_idx[..., None], axis=1)
            tokens = tokens.at[:, :, t + 1].set(tok)
            finished = jnp.take_along_axis(finished, beam_idx, axis=1) | (tok == self.eos)
            if drop_fn is not None:
                dropped = drop_fn(tokens, new_logp, t)
                new_logp = jnp.where(dropped, -1e9, new_logp)
                finished = finished | dropped
            rec = (beam_idx, tok, new_logp) if return_trace else None
            return (tokens, new_logp, mems_new, finished), rec

        (tokens, logp, _, _), trace = lax.scan(
            scan_step, (tokens, logp, mems, finished), jnp.arange(max_len))
        out = tokens[:, :, 1:]
        if length_penalty > 0:
            lengths = jnp.sum((out != self.eos).astype(jnp.float32), -1) + 1.0
            scores = logp / jnp.power(lengths, length_penalty)
        else:
            scores = logp
        order = jnp.argsort(-scores, axis=1)
        out = jnp.take_along_axis(out, order[..., None], axis=1)
        scores = jnp.take_along_axis(scores, order, axis=1)
        if return_trace:
            parent, tok, sc = trace
            return out, scores, {"parent": parent, "token": tok, "score": sc,
                                 "order": order}
        return out, scores
