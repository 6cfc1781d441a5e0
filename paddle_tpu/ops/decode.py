"""Fused decode engine — ONE generation implementation for every surface.

The analog of ``RecurrentGradientMachine::generateSequence`` + ``--beam_size``
(reference: gserver/gradientmachines/RecurrentGradientMachine.cpp:383; SWIG
SequenceGenerator, paddle/api/PaddleAPI.h:1002).  Every user-facing
generation path — ``models/seq2seq.py`` ``beam_search``/``greedy_decode``,
the DSL ``SequenceGenerator`` behind ``nn/recurrent.py beam_search`` (and
through it ``v2.infer`` over a beam_search layer) — drives this engine.

What it replaces: a fixed-``max_len``
``lax.scan`` whose every step materialized the full [B*K, V] logits in HBM,
log-softmaxed them into a second f32 [B*K, V] buffer, and ``top_k``'d over
K*V.  Here:

- **Vocab-tiled readout kernel** (``ops/pallas_kernels.py``
  ``topk_lse_readout_pallas``): the same tiling discipline as the fused
  softmax-CE readout — ``out_w`` tiles stream through VMEM, a running
  top-k and running logsumexp per row are maintained on-chip, and neither
  the logits nor any f32 log-softmax buffer ever touches HBM.  Per row,
  k values + k indices + one logsumexp come back.  Opaque step nets that
  hand the engine pre-built logits get the one-HBM-pass variant
  (``topk_lse_logits_pallas``).
- **Early-exit driver**: a ``lax.while_loop`` that stops as soon as every
  beam has emitted EOS (finished beams only extend with EOS at zero cost
  and the token buffer is EOS-prefilled, so stopping early is
  output-identical to running all ``max_len`` steps).  ``early_exit=False``
  keeps a ``lax.scan`` driver — fixed trip count, unrollable for AOT
  export (``config/deploy`` ``unroll_scans`` cannot patch a while loop).
- **True greedy fast path**: ``greedy_decode`` runs B rows with a running
  argmax + logsumexp — no beam tiling, no K*V top-k — and is
  token-identical to ``beam_size=1`` beam search.
- **Packed beam reorder**: ``beam_gather`` reorders the whole carry
  (token buffer, state pytree, finished mask) with one fused
  ``take_along_axis`` per dtype group instead of one gather per leaf.

Per-row top-k + a small second-stage ``top_k`` over the K*k candidates is
exactly equivalent to the reference's ``top_k`` over K*V (the global top-K
is contained in the union of per-row top-Ks, and both stages tie-break
toward the lower flat index like ``lax.top_k``'s stable sort), so token
ids are bit-identical to the unfused path and scores match to float
re-association (~1e-7).

Kernel gating mirrors ``losses._tiled_ce_cfg``: TPU backend + tile-aligned
shapes, with the XLA ``top_k`` fallback otherwise.  The lowered
decode fn is auditable host-transfer-free via
``paddle_tpu.analysis.audit_decode``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "LinearReadout",
    "LogitsReadout",
    "beam_decode",
    "greedy_decode",
    "beam_gather",
    "decode_kernel_config",
    "decode_step",
    "spec_verify_step",
    "init_slot_carry",
    "write_slot",
    "release_slot",
    "extract_slot",
    "restore_slot",
    "finalize_slots",
]

#: the reference's kill score for impossible candidates (nn/recurrent.py
#: used -1e9 throughout; scores must match it exactly)
NEG = -1e9

#: static unroll bound for the kernel's running top-k (k masked-argmax
#: passes per tile; beyond this the XLA fallback is the better program)
_MAX_KERNEL_K = 16

_V_TILE = 512


def _row_block(n: int) -> Optional[int]:
    return next((r for r in (512, 256, 128, 64, 32, 16, 8) if n % r == 0),
                None)


def decode_kernel_config(n_rows: int, depth: Optional[int], vocab: int,
                         k: int) -> Optional[Tuple[int, int]]:
    """Gate for the vocab-tiled top-k readout kernel: (row_block, v_tile)
    or None for the XLA ``top_k`` fallback.  ``depth`` is the readout
    contraction dim (None for the pre-materialized-logits variant, which
    has no MXU operand to align).  Needs a TPU backend, lane-aligned depth,
    a sublane-aligned row block dividing the rows, and a small static k (the
    kernel unrolls k merge passes per tile)."""
    from paddle_tpu.ops.pallas_kernels import compiled_kernels

    if not compiled_kernels():
        return None
    return _forced_kernel_config(n_rows, depth, vocab, k)


def _forced_kernel_config(n_rows, depth, vocab, k):
    """Shape-only half of the gate (backend check skipped) — used by tests
    to exercise the kernel in interpret mode."""
    if depth is not None and depth % 128:
        return None
    if not 1 <= k <= _MAX_KERNEL_K or vocab < k:
        return None
    rb = _row_block(n_rows)
    if rb is None:
        return None
    return rb, _V_TILE


def _topk_lse_xla(logits, k):
    """XLA fallback: same (vals, idx, lse) statistics from materialized
    logits — identical math to the pre-engine ``log_softmax`` + ``top_k``
    path (log_softmax(x) = x - lse(x); the shift preserves order, so token
    selection is unchanged)."""
    lf = logits.astype(jnp.float32)
    m = jnp.max(lf, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(lf - m[..., None]), axis=-1))
    vals, idx = lax.top_k(lf, k)
    return vals, idx.astype(jnp.int32), lse


def _pad_cols(x, vp, value):
    v = x.shape[-1]
    if vp == v:
        return x
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, vp - v),),
                   constant_values=value)


@dataclass(frozen=True)
class LinearReadout:
    """Fused-capable readout: the step net returns pre-readout states
    [N, D]; the engine owns the [D, V] projection and never materializes
    the logits (kernel path)."""

    w: Any   # [D, V]
    b: Any   # [V]

    def __call__(self, states, k, *, use_kernel: Optional[bool] = None):
        from paddle_tpu.ops.matmul import linear
        from paddle_tpu.ops.numerics import mxu_cast

        N, D = states.shape
        V = int(self.w.shape[1])
        cfg = (_forced_kernel_config(N, D, V, k) if use_kernel
               else None if use_kernel is False
               else decode_kernel_config(N, D, V, k))
        if cfg is None:
            if use_kernel:
                raise ValueError(
                    f"decode kernel forced but shapes are gated: "
                    f"N={N}, D={D}, V={V}, k={k}")
            return _topk_lse_xla(linear(states, self.w, self.b), k)
        if use_kernel is not True and V < cfg[1] // 2:
            # tiny vocabularies: tile padding costs more than it saves
            # (same call LogitsReadout makes for the same shape class)
            return _topk_lse_xla(linear(states, self.w, self.b), k)
        from paddle_tpu.ops.pallas_kernels import topk_lse_readout_pallas

        rb, vt = cfg
        sc, wc = mxu_cast(states, self.w)
        vp = -(-V // vt) * vt
        w_p = _pad_cols(wc, vp, 0)
        b_p = _pad_cols(self.b.astype(jnp.float32).reshape(1, V), vp, -1e30)
        tv, ti, lse = topk_lse_readout_pallas(sc, w_p, b_p, vocab=V, k=k,
                                              row_block=rb, v_tile=vt)
        return tv[:, :k], ti[:, :k], lse[:, 0]


@dataclass(frozen=True)
class LogitsReadout:
    """Opaque-step readout: the step net returns full logits [N, V] (the
    DSL beam_search layer ends in an arbitrary logits layer).  The kernel
    still wins one pass over XLA's three (max, exp-sum, top-k) and skips
    the f32 log-softmax buffer."""

    def __call__(self, logits, k, *, use_kernel: Optional[bool] = None):
        N, V = logits.shape
        cfg = (_forced_kernel_config(N, None, V, k) if use_kernel
               else None if use_kernel is False
               else decode_kernel_config(N, None, V, k))
        if cfg is None:
            if use_kernel:
                raise ValueError(
                    f"decode kernel forced but shapes are gated: "
                    f"N={N}, V={V}, k={k}")
            return _topk_lse_xla(logits, k)
        if use_kernel is not True and V < cfg[1] // 2:
            # tiny vocabularies (DSL toy nets): tiling buys nothing
            return _topk_lse_xla(logits, k)
        from paddle_tpu.ops.pallas_kernels import topk_lse_logits_pallas

        rb, vt = cfg
        vp = -(-V // vt) * vt
        l_p = _pad_cols(logits, vp, -1e30)
        tv, ti, lse = topk_lse_logits_pallas(l_p, vocab=V, k=k,
                                             row_block=rb, v_tile=vt)
        return tv[:, :k], ti[:, :k], lse[:, 0]


# ---------------------------------------------------------------------------
# packed beam reorder
# ---------------------------------------------------------------------------


def beam_gather(tree, beam_idx):
    """Reorder every [B*K, ...] / [B, K, ...] leaf of ``tree`` by
    ``beam_idx`` [B, K] with ONE fused ``take_along_axis`` per dtype group:
    leaves are flattened to [B, K, F], concatenated along F per dtype,
    gathered once, and split back — instead of XLA emitting one gather per
    pytree leaf (the old per-leaf ``reorder`` tree_map)."""
    B, K = beam_idx.shape
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    flats = []
    for x in leaves:
        if x.ndim >= 2 and x.shape[0] == B and x.shape[1] == K:
            flats.append(x.reshape(B, K, -1))
        elif x.shape[0] == B * K:
            flats.append(x.reshape(B, K, -1))
        else:
            raise ValueError(
                f"beam_gather leaf has no beam axis: shape {x.shape} with "
                f"B={B}, K={K}")
    groups = {}
    for i, f in enumerate(flats):
        groups.setdefault(jnp.dtype(f.dtype), []).append(i)
    out = [None] * len(leaves)
    for dt, idxs in groups.items():
        packed = (flats[idxs[0]] if len(idxs) == 1 else
                  jnp.concatenate([flats[i] for i in idxs], axis=-1))
        packed = jnp.take_along_axis(packed, beam_idx[..., None], axis=1)
        off = 0
        for i in idxs:
            w = flats[i].shape[-1]
            out[i] = packed[..., off:off + w].reshape(leaves[i].shape)
            off += w
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# candidate helpers
# ---------------------------------------------------------------------------


def _eos_candidates(vocab: int, k: int, eos: int):
    """The per-row candidate list of a FINISHED beam, in ``lax.top_k``
    order over the reference's eos-only row (EOS at 0, everything else at
    NEG): EOS first at zero cost, then the lowest non-EOS token ids at the
    kill score.  Matches the unfused path's selection bit-for-bit when a
    finished beam's junk candidates reach the global top-K."""
    toks = [eos] + [v for v in range(vocab) if v != eos][:k - 1]
    toks += [eos] * (k - len(toks))          # k > vocab: EOS filler
    vals = [0.0] + [NEG] * (k - 1)
    return (jnp.asarray(toks, jnp.int32), jnp.asarray(vals, jnp.float32))


def _loop(cond_extra, body, carry, max_len: int, early_exit: bool):
    """Driver control flow: a ``while_loop`` with the all-finished early
    exit, or a fixed-trip ``scan`` (AOT-unrollable) when ``early_exit`` is
    off.  ``carry[0]`` is the step counter."""
    if early_exit:
        return lax.while_loop(
            lambda c: (c[0] < max_len) & cond_extra(c), body, carry)

    def scan_step(c, _):
        return body(c), None

    out, _ = lax.scan(scan_step, carry, None, length=max_len)
    return out


def _resolve_early_exit(early_exit: Optional[bool]) -> bool:
    if early_exit is not None:
        return bool(early_exit)
    from paddle_tpu.utils.flags import FLAGS

    return bool(FLAGS.decode_early_exit)


# ---------------------------------------------------------------------------
# the slot-table single-step API (continuous batching; docs/serving.md)
# ---------------------------------------------------------------------------
#
# The carry of a fixed-capacity decode table of S slots, each holding one
# request's K beams — the recurrent/attention state is the KV-cache
# analogue.  A dict pytree so the whole table jits as one argument:
#
#   tokens   [S, K, max_len+1] i32   EOS-prefilled token buffers, BOS at 0
#   logp     [S, K] f32              cumulative beam log-probs
#   state    pytree, leading dim S*K (beam-tiled model carry; leaves may
#                                     also be [S, K, ...])
#   finished [S, K] bool             per-beam EOS mask
#   active   [S] bool                slot occupancy (host-managed)
#   step     [S] i32                 per-slot step count
#
# ``decode_step`` advances every ACTIVE slot by one token — inactive slots
# are frozen bit-for-bit, so a harvested-but-not-yet-refilled slot holds
# its result untouched across steps.  Because every per-row computation in
# the engine (readout matmul, top-k, gather) is row-independent, an active
# slot advances exactly as the same request would inside a solo
# ``beam_decode`` batch: per-request outputs are bit-identical regardless
# of which other requests share the table (pinned by
# tests/test_serving_slots.py).


def decode_step(step_fn: Callable, readout, carry: dict, *, vocab_size: int,
                eos: int = 1, use_kernel: Optional[bool] = None) -> dict:
    """ONE fused decode step over a slot table (the reusable body of
    ``beam_decode``'s loop).  ``step_fn(tokens [S*K] i32, state) ->
    (readout_input, new_state)`` exactly as in ``beam_decode``; per-slot
    ``active``/``step`` masks freeze finished/unoccupied slots and let
    every slot run at its own position in its token buffer."""
    # named_scope: profiler captures (paddle_tpu/obs, --profile_steps)
    # show one legible "decode_step" block per token instead of raw ops
    with jax.named_scope("decode_step"):
        return _decode_step_inner(step_fn, readout, carry,
                                  vocab_size=vocab_size, eos=eos,
                                  use_kernel=use_kernel)


def _decode_step_inner(step_fn, readout, carry, *, vocab_size, eos,
                       use_kernel):
    tokens, logp = carry["tokens"], carry["logp"]
    state, finished = carry["state"], carry["finished"]
    active, step = carry["active"], carry["step"]
    S, K, Lp1 = tokens.shape
    kr = min(K, vocab_size)        # per-row candidates: top-K needs ≤ V
    fin_toks, fin_vals = _eos_candidates(vocab_size, kr, eos)

    # each slot reads the token at ITS OWN step position
    y = jnp.take_along_axis(
        tokens, jnp.broadcast_to(step[:, None, None], (S, K, 1)).astype(
            jnp.int32), axis=2)[..., 0]
    r_in, state_new = step_fn(y.reshape(S * K), state)
    vals, idx, lse = readout(r_in, kr, use_kernel=use_kernel)
    row_logp = (vals - lse[:, None]).reshape(S, K, kr)
    row_idx = idx.reshape(S, K, kr)
    # finished beams may only emit EOS at zero cost (per-slot EOS masking)
    row_logp = jnp.where(finished[..., None], fin_vals[None, None], row_logp)
    row_idx = jnp.where(finished[..., None], fin_toks[None, None], row_idx)
    flat = (logp[..., None] + row_logp).reshape(S, K * kr)
    new_logp, flat_ix = lax.top_k(flat, K)
    beam_ix = flat_ix // kr
    tok = jnp.take_along_axis(row_idx.reshape(S, K * kr), flat_ix, axis=1)
    # one packed gather reorders the whole carry
    tokens_g, state_g, finished_g = beam_gather(
        (tokens, state_new, finished), beam_ix)
    pos = (jnp.arange(Lp1, dtype=jnp.int32)[None, :]
           == (step + 1)[:, None])                      # [S, Lp1]
    tokens_g = jnp.where(pos[:, None, :], tok[:, :, None], tokens_g)
    finished_g = finished_g | (tok == eos)

    # freeze inactive slots bit-for-bit (state leaves may be [S*K, ...] or
    # [S, K, ...] — beam_gather's contract)
    row_keep = jnp.repeat(active, K)

    def _sel(new, old):
        if new.shape[0] == S * K:
            m = row_keep.reshape((S * K,) + (1,) * (new.ndim - 1))
        else:
            m = active.reshape((S,) + (1,) * (new.ndim - 1))
        return jnp.where(m, new, old)

    return {
        "tokens": jnp.where(active[:, None, None], tokens_g, tokens),
        "logp": jnp.where(active[:, None], new_logp, logp),
        "state": jax.tree_util.tree_map(_sel, state_g, state),
        "finished": jnp.where(active[:, None], finished_g, finished),
        "active": active,
        "step": jnp.where(active, step + 1, step),
    }


def init_slot_carry(state_template, *, slots: int, beam_size: int,
                    max_len: int, eos: int = 1) -> dict:
    """An EMPTY slot table: every slot inactive and finished, token buffers
    EOS-prefilled, state leaves zero-filled at the beam-tiled shapes.
    ``state_template`` is a per-sequence state pytree with leading dim 1 on
    every leaf (arrays or ``ShapeDtypeStruct``s — e.g. from
    ``jax.eval_shape`` over a prefill)."""
    S, K = int(slots), int(beam_size)

    def make(leaf):
        return jnp.zeros((S * K,) + tuple(leaf.shape[1:]), leaf.dtype)

    return {
        "tokens": jnp.full((S, K, max_len + 1), eos, jnp.int32),
        "logp": jnp.tile(
            jnp.asarray([0.0] + [NEG] * (K - 1), jnp.float32)[None], (S, 1)),
        "state": jax.tree_util.tree_map(make, state_template),
        "finished": jnp.ones((S, K), bool),
        "active": jnp.zeros((S,), bool),
        "step": jnp.zeros((S,), jnp.int32),
    }


def write_slot(carry: dict, slot, state0, *, bos: int = 0,
               eos: int = 1, row=0) -> dict:
    """Prefill: admit one request into slot ``slot`` WITHOUT recompiling —
    ``slot`` and ``row`` are traced scalars, so one compiled program serves
    every slot index.  ``state0`` is a prefill-output pytree with a leading
    batch dim; row ``row`` of it is beam-tiled to K rows and written over
    the slot's rows [slot*K, slot*K+K).  The slot's token buffer, scores,
    and masks are reset; it comes back active at step 0."""
    tokens, logp = carry["tokens"], carry["logp"]
    S, K, Lp1 = tokens.shape
    slot = jnp.asarray(slot, jnp.int32)
    row = jnp.asarray(row, jnp.int32)

    def put(table, leaf):
        one = lax.dynamic_slice_in_dim(leaf, row, 1, axis=0)
        tiled = jnp.repeat(one, K, axis=0).astype(table.dtype)
        return lax.dynamic_update_slice_in_dim(table, tiled, slot * K, axis=0)

    row_tokens = jnp.full((1, K, Lp1), eos, jnp.int32).at[:, :, 0].set(bos)
    row_logp = jnp.asarray([0.0] + [NEG] * (K - 1), jnp.float32)[None]
    return {
        "tokens": lax.dynamic_update_slice(tokens, row_tokens, (slot, 0, 0)),
        "logp": lax.dynamic_update_slice(logp, row_logp, (slot, 0)),
        "state": jax.tree_util.tree_map(put, carry["state"], state0),
        "finished": carry["finished"].at[slot].set(jnp.zeros((K,), bool)),
        "active": carry["active"].at[slot].set(True),
        "step": carry["step"].at[slot].set(0),
    }


def release_slot(carry: dict, slot) -> dict:
    """Free slot ``slot`` (harvest or eviction): inactive + all-finished,
    so ``decode_step`` freezes it until the next ``write_slot``."""
    K = carry["tokens"].shape[1]
    slot = jnp.asarray(slot, jnp.int32)
    return dict(
        carry,
        active=carry["active"].at[slot].set(False),
        finished=carry["finished"].at[slot].set(jnp.ones((K,), bool)),
    )


def spec_verify_step(step_fn: Callable, readout, carry: dict, drafts,
                     cap, *, vocab_size: int, eos: int = 1,
                     use_kernel: Optional[bool] = None):
    """ONE fused wide-verify step for speculative decoding over a GREEDY
    (``beam_size == 1``) slot table: per active slot, score the current
    token plus ``k`` host-proposed draft tokens in one call and emit the
    longest prefix the model itself would have produced — between 1 and
    ``k + 1`` tokens per slot per dispatch.

    ``drafts`` is ``[S, k] i32`` (host draft proposals per slot —
    ``ops/speculative.py``); ``cap`` is ``[S] i32``, the per-slot
    remaining decode budget (``limit - tokens_emitted``), which bounds
    emission so cumulative scores never accumulate past the request's
    own ``max_len``.  Returns ``(new_carry, aux)`` with ``aux =
    {"emitted": [S, k+1] i32, "n": [S] i32, "accepted": [S] i32}`` —
    the emitted tokens (EOS-filled past ``n``), tokens emitted, and
    draft tokens accepted.

    Bit-identity with the one-token path is *provable*, not
    approximate, because greedy verification IS the greedy decode rule:

    - position ``j``'s input is the previous emission in the solo run;
      a draft position only stays "emitting" while every earlier draft
      matched the model's own greedy emission (or the row already
      finished, where emissions are forced EOS at zero cost regardless
      of state), so every scored-and-accepted position saw exactly the
      state the solo run would have had — readout and ``step_fn`` are
      row-independent and batch-size-invariant (the same invariant that
      makes the slot table itself bit-identical to solo decode);
    - ``logp`` accumulates sequentially position by position in the
      same float-addition order as one-token stepping;
    - the carried state is SELECTED from the scoring sweep itself: the
      recurrence is row-independent, so row ``r``'s state after chain
      position ``j`` depends only on row ``r``'s inputs ``x_0..x_j`` —
      for a row that emitted ``n`` tokens those are exactly the tokens
      the solo run would have fed, so the sweep state at position
      ``n - 1`` IS the solo state, bit for bit (positions past ``n``
      are garbage for that row and are never selected).  One recurrence
      pass total; the cost is holding ``k + 1`` transient state copies
      through the select, which XLA frees within the step; the readout
      (the [D, V] matmul that dominates) runs ONCE per position,
      batched as a single ``(k+1)·S``-row call.

    Inactive slots are frozen bit-for-bit, as in :func:`decode_step`.
    Beam search (``beam_size > 1``) has no greedy-verify equivalent —
    callers fall back to the standard :func:`decode_step` path.
    """
    with jax.named_scope("spec_verify_step"):
        return _spec_verify_inner(step_fn, readout, carry, drafts, cap,
                                  vocab_size=vocab_size, eos=eos,
                                  use_kernel=use_kernel)


def _spec_verify_inner(step_fn, readout, carry, drafts, cap, *,
                       vocab_size, eos, use_kernel):
    tokens, logp = carry["tokens"], carry["logp"]
    state, finished = carry["state"], carry["finished"]
    active, step = carry["active"], carry["step"]
    S, K, Lp1 = tokens.shape
    if K != 1:
        raise ValueError(
            f"spec_verify_step is a greedy path: beam_size must be 1, "
            f"got K={K} (beam search falls back to decode_step)")
    drafts = jnp.asarray(drafts, jnp.int32)
    cap = jnp.asarray(cap, jnp.int32)
    k = int(drafts.shape[1])

    # position inputs: x_0 = each slot's current token, x_j = draft j-1
    y0 = jnp.take_along_axis(
        tokens, jnp.broadcast_to(step[:, None, None], (S, K, 1)).astype(
            jnp.int32), axis=2)[..., 0].reshape(S)
    xs = jnp.concatenate([y0[None, :], drafts.T], axis=0)   # [k+1, S]

    # scoring sweep: scan the recurrence through all k+1 positions
    # collecting readout inputs AND the state after each position, then
    # ONE wide readout over (k+1)*S rows at k=1 (greedy).  A scan (not
    # an unrolled loop) keeps the compiled program one step-body deep
    # regardless of k — at small step shapes the program's instruction
    # count, not its flops, is what the per-position overhead tracks.
    # Row independence makes each row's (vals, idx, lse) identical to
    # the solo per-step readout.
    #
    # Pass-through state leaves — ones step_fn returns UNMODIFIED (the
    # same traced value), e.g. encoder context / attention masks — are
    # detected by object identity during the single body trace and
    # excluded from the stacked scan outputs: by induction they equal
    # the initial state at every position, so the select below would
    # always return the original anyway, and stacking k+1 copies of an
    # [S, src_len, D] encoder costs more than the recurrence itself.
    changed: List[bool] = []

    def _sweep(st_c, x):
        r_in, st_n = step_fn(x, st_c)
        in_leaves = jax.tree_util.tree_leaves(st_c)
        out_leaves = jax.tree_util.tree_leaves(st_n)
        if not changed:
            changed.extend(o is not i
                           for o, i in zip(out_leaves, in_leaves))
        ys = tuple(o for o, c in zip(out_leaves, changed) if c)
        return st_n, (r_in, ys)

    _, (r_all, st_stack) = lax.scan(_sweep, state, xs)
    vals, idx, lse = readout(r_all.reshape((-1,) + r_all.shape[2:]), 1,
                             use_kernel=use_kernel)
    # barrier: without it XLA CPU duplicates the (k+1)*S-row argmax /
    # log-sum-exp reduction into every one of the ~k*S tiny accept-mask
    # consumers below (producer-fusion), turning one readout into tens —
    # measured ~8x the whole step.  The barrier pins the readout to run
    # once; outputs are bit-identical either way.
    vals, idx, lse = jax.lax.optimization_barrier((vals, idx, lse))
    g = idx[:, 0].reshape(k + 1, S)            # greedy token per position
    lp = (vals[:, 0] - lse).reshape(k + 1, S)  # its log-prob

    # accept/emit: 'emitting' is sticky per row — a position emits only
    # while every earlier draft input matched the row's own emission
    # (or the row is finished: forced EOS at zero cost, state-independent)
    # and the budget cap is not exhausted.
    fin = finished[:, 0]
    logp_new = logp[:, 0]
    emitting = active & (cap > 0)
    n = jnp.zeros((S,), jnp.int32)
    acc = jnp.zeros((S,), jnp.int32)
    em = []
    for j in range(k + 1):
        if j:
            matched = drafts[:, j - 1] == em[j - 1]
            emitting = emitting & (fin | matched) & (n < cap)
            acc = acc + (emitting & ~fin).astype(jnp.int32)
        e_j = jnp.where(fin, eos, g[j])
        # sequential accumulation in solo order (finished rows add the
        # same 0.0 the one-token path's EOS candidate adds)
        logp_new = jnp.where(emitting,
                             logp_new + jnp.where(fin, 0.0, lp[j]),
                             logp_new)
        em.append(jnp.where(emitting, e_j, eos))
        n = n + emitting.astype(jnp.int32)
        fin = fin | (emitting & (e_j == eos))
    em_arr = jnp.stack(em, axis=1)                       # [S, k+1]

    # token-buffer epilogue: write the n emitted tokens at each slot's
    # own position (offsets past n keep the old — EOS-prefilled — buffer)
    off = jnp.arange(Lp1, dtype=jnp.int32)[None, :] - (step[:, None] + 1)
    sel = (off >= 0) & (off < n[:, None])                # [S, Lp1]
    gathered = jnp.take_along_axis(em_arr, jnp.clip(off, 0, k), axis=1)
    tokens_new = jnp.where(sel[:, None, :], gathered[:, None, :], tokens)

    # state select: fold the sweep states down to each row's own stop
    # position.  Rows that emitted n tokens keep sweep state n-1 (their
    # inputs 0..n-1 were exactly the solo inputs — row independence);
    # rows with n == 0 keep the original state, frozen bit-for-bit.
    # One gather per CHANGING leaf; pass-through leaves keep the
    # original untouched (provably equal at every sweep position).
    pos = jnp.clip(n - 1, 0, k)                          # [S]
    live = n > 0

    def _pick(stacked, orig):
        il = pos.reshape((1, S) + (1,) * (orig.ndim - 1))
        sel = jnp.take_along_axis(stacked, il, axis=0)[0]
        m = live.reshape((S,) + (1,) * (orig.ndim - 1))
        return jnp.where(m, sel, orig)

    st_leaves, st_def = jax.tree_util.tree_flatten(state)
    it = iter(st_stack)
    st_leaves = [(_pick(next(it), leaf) if ch else leaf)
                 for leaf, ch in zip(st_leaves, changed)]
    st = jax.tree_util.tree_unflatten(st_def, st_leaves)

    new_carry = {
        "tokens": tokens_new,
        "logp": logp_new[:, None],
        "state": st,
        "finished": fin[:, None],
        "active": active,
        "step": step + n,
    }
    return new_carry, {"emitted": em_arr, "n": n, "accepted": acc}


def extract_slot(carry: dict, slot) -> dict:
    """Page-out: one slot's full decode context — token buffer, scores,
    state rows, finished mask, step — as a small per-slot pytree ready
    for a host round-trip (serving/paging.py).  ``slot`` is a traced
    scalar, mirroring :func:`write_slot`'s one-program-per-table
    discipline.  The d2h/h2d round trip preserves every bit, so a
    paged-out-and-restored slot decodes exactly as if it had never
    left the table (pinned by tests)."""
    tokens = carry["tokens"]
    S, K, Lp1 = tokens.shape
    slot = jnp.asarray(slot, jnp.int32)

    def take(leaf):
        if leaf.shape[0] == S * K:
            return lax.dynamic_slice_in_dim(leaf, slot * K, K, axis=0)
        if leaf.shape[0] == S:
            return lax.dynamic_slice_in_dim(leaf, slot, 1, axis=0)
        raise ValueError(
            f"extract_slot leaf has no slot axis: shape {leaf.shape} "
            f"with S={S}, K={K}")

    return {
        "tokens": lax.dynamic_slice(tokens, (slot, 0, 0), (1, K, Lp1)),
        "logp": lax.dynamic_slice(carry["logp"], (slot, 0), (1, K)),
        "state": jax.tree_util.tree_map(take, carry["state"]),
        "finished": lax.dynamic_slice(carry["finished"], (slot, 0), (1, K)),
        "step": lax.dynamic_slice(carry["step"], (slot,), (1,)),
    }


def restore_slot(carry: dict, slot, saved: dict) -> dict:
    """Page-in: write an :func:`extract_slot` snapshot back into slot
    ``slot`` (traced scalar) and re-activate it at its saved step — the
    re-admission half of host-paged slot state.  The inverse of
    :func:`extract_slot` up to bit identity."""
    tokens = carry["tokens"]
    S, K, Lp1 = tokens.shape
    slot = jnp.asarray(slot, jnp.int32)

    def put(table, piece):
        piece = piece.astype(table.dtype)
        if table.shape[0] == S * K:
            return lax.dynamic_update_slice_in_dim(table, piece, slot * K,
                                                   axis=0)
        return lax.dynamic_update_slice_in_dim(table, piece, slot, axis=0)

    return {
        "tokens": lax.dynamic_update_slice(
            tokens, saved["tokens"].astype(jnp.int32), (slot, 0, 0)),
        "logp": lax.dynamic_update_slice(
            carry["logp"], saved["logp"].astype(jnp.float32), (slot, 0)),
        "state": jax.tree_util.tree_map(put, carry["state"],
                                        saved["state"]),
        "finished": lax.dynamic_update_slice(
            carry["finished"], saved["finished"], (slot, 0)),
        "active": carry["active"].at[slot].set(True),
        "step": lax.dynamic_update_slice(
            carry["step"], saved["step"].astype(jnp.int32), (slot,)),
    }


def _finalize(tokens, logp, *, eos: int, length_penalty: float):
    """The shared decode epilogue: strip BOS, apply the length penalty,
    sort beams best-first.  ``beam_decode`` and the slot harvest MUST go
    through this one implementation — per-request bit-identity between the
    two paths is structural, not coincidental."""
    out = tokens[:, :, 1:]
    if length_penalty > 0:
        lengths = jnp.sum((out != eos).astype(jnp.float32), axis=-1) + 1.0
        scores = logp / jnp.power(lengths, length_penalty)
    else:
        scores = logp
    order = jnp.argsort(-scores, axis=1)
    out = jnp.take_along_axis(out, order[..., None], axis=1)
    scores = jnp.take_along_axis(scores, order, axis=1)
    return out, scores


def finalize_slots(carry: dict, *, eos: int = 1,
                   length_penalty: float = 0.0):
    """Harvest view of the WHOLE table: ``(tokens [S, K, max_len],
    scores [S, K])`` sorted best-first per slot — the slot analog of
    ``beam_decode``'s return.  Positions a slot never reached are
    EOS-prefilled, so slicing a harvested slot to its request's own
    ``max_len`` yields exactly the solo ``beam_decode(max_len=...)``
    output (length counts, and hence penalized scores, agree because the
    tail is all EOS)."""
    return _finalize(carry["tokens"], carry["logp"], eos=eos,
                     length_penalty=length_penalty)


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------


def beam_decode(step_fn: Callable, readout, state0, *, batch_size: int,
                beam_size: int, vocab_size: int, max_len: int,
                bos: int = 0, eos: int = 1, length_penalty: float = 0.0,
                early_exit: Optional[bool] = None,
                use_kernel: Optional[bool] = None):
    """Batched beam search over a functional step protocol.

    ``step_fn(tokens [B*K] i32, state) -> (readout_input, new_state)``
    where ``state`` is a pytree with leading dim B*K (``state0`` arrives
    per-sequence with leading dim B and is beam-tiled here) and
    ``readout_input`` is whatever ``readout`` consumes (pre-readout states
    for ``LinearReadout``, full logits for ``LogitsReadout``).

    Returns ``(tokens [B, K, max_len], scores [B, K])`` sorted best-first —
    the exact output contract (and, token-for-token, the exact output) of
    the pre-engine scan path.  ``early_exit``/``use_kernel`` default to
    FLAGS.decode_early_exit / the ``decode_kernel_config`` gate.

    The loop body IS :func:`decode_step` over an always-active slot table
    of B slots — the whole-batch and continuous-batching paths share one
    step implementation."""
    B, K, V = batch_size, beam_size, vocab_size
    early = _resolve_early_exit(early_exit)

    state = jax.tree_util.tree_map(lambda x: jnp.repeat(x, K, axis=0), state0)
    sc = {
        "tokens": jnp.full((B, K, max_len + 1), eos, jnp.int32)
                     .at[:, :, 0].set(bos),
        "logp": jnp.tile(
            jnp.asarray([0.0] + [NEG] * (K - 1), jnp.float32)[None], (B, 1)),
        "state": state,
        "finished": jnp.zeros((B, K), bool),
        "active": jnp.ones((B,), bool),
        "step": jnp.zeros((B,), jnp.int32),
    }

    def body(carry):
        t, sc = carry
        return t + 1, decode_step(step_fn, readout, sc, vocab_size=V,
                                  eos=eos, use_kernel=use_kernel)

    carry = (jnp.asarray(0, jnp.int32), sc)
    _, sc = _loop(
        lambda c: jnp.logical_not(jnp.all(c[1]["finished"])), body, carry,
        max_len, early)
    return _finalize(sc["tokens"], sc["logp"], eos=eos,
                     length_penalty=length_penalty)


def greedy_decode(step_fn: Callable, readout, state0, *, batch_size: int,
                  vocab_size: int, max_len: int, bos: int = 0, eos: int = 1,
                  early_exit: Optional[bool] = None,
                  use_kernel: Optional[bool] = None):
    """True greedy fast path: B rows (no beam tiling), running argmax +
    logsumexp via the same readout (k=1 — no K*V top-k anywhere), early
    exit when every row has emitted EOS.  Token-identical to
    ``beam_decode(beam_size=1)``'s best beam; returns
    ``(tokens [B, max_len], scores [B])``."""
    B, V = batch_size, vocab_size
    early = _resolve_early_exit(early_exit)

    tokens = jnp.full((B, max_len + 1), eos, jnp.int32).at[:, 0].set(bos)
    logp = jnp.zeros((B,), jnp.float32)
    finished = jnp.zeros((B,), bool)

    def body(carry):
        t, tokens, logp, state, finished = carry
        y = lax.dynamic_index_in_dim(tokens, t, axis=1, keepdims=False)
        r_in, state_new = step_fn(y, state)
        vals, idx, lse = readout(r_in, 1, use_kernel=use_kernel)
        tok = jnp.where(finished, eos, idx[:, 0])
        logp = logp + jnp.where(finished, 0.0, vals[:, 0] - lse)
        tokens = tokens.at[:, t + 1].set(tok)
        finished = finished | (tok == eos)
        return t + 1, tokens, logp, state_new, finished

    carry = (jnp.asarray(0, jnp.int32), tokens, logp, state0, finished)
    _, tokens, logp, _, _ = _loop(
        lambda c: jnp.logical_not(jnp.all(c[4])), body, carry, max_len,
        early)
    return tokens[:, 1:], logp
