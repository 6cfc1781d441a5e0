"""Slot-based continuous batching — iteration-level scheduling for the
decode engine (docs/serving.md "Continuous batching").

The bucket path coalesces whole requests and runs each batch lock-step to
completion, so one long request holds its entire batch hostage — fatal
for tail latency under generation traffic (a max_len straggler multiplies
every co-batched request's latency by the straggler's length).  Here the
unit of scheduling is ONE DECODE STEP, the Orca/vLLM discipline mapped
onto the TPU-native engine:

- a persistent fixed-capacity decode table of ``S`` slots (the
  recurrent/attention carry as the KV-cache analogue; each slot holds one
  request's ``K`` beams) lives across calls in ``SlotScheduler.carry``;
- ``decode_step`` (ops/decode.py) advances every occupied slot by one
  token in one compiled call — ONE program for any mix of requests;
- between steps the host harvests finished slots (all beams EOS, or the
  request's own ``max_len`` reached), recycles them to queued requests
  via ``write_slot`` (slot index is traced — no recompile per slot), and
  evicts slots whose deadline already passed;
- per-request outputs are **bit-identical** to a solo
  :func:`~paddle_tpu.ops.decode.beam_decode` run regardless of admission
  order or neighbors, because every per-row computation in the engine is
  row-independent and frozen slots are held bit-for-bit
  (tests/test_serving_slots.py pins this).

``SlotScheduler`` is the host-side driver consumed by
``InferenceServer(mode="generation")`` (serving/server.py); it owns no
futures and no metrics — it reports events and the server applies the
PR 5 admission/deadline/breaker machinery to them.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from paddle_tpu.serving.batching import Request, merge_feeds

__all__ = ["SlotBackend", "Seq2SeqSlotBackend", "SlotScheduler",
           "audit_slot_backend", "example_slot_backend"]

#: serving convention for the adversarial never-EOS fault
#: (resilience.chaos.straggler_request): backends that support it read
#: this feed key as an additive per-request EOS-logit bias
EOS_BIAS_KEY = "eos_bias"


class SlotBackend:
    """Protocol of a generation backend servable through the slot table.

    Concrete backends provide::

        beam_size       K — beams per slot (fixed for the table's lifetime)
        max_len         table depth: the longest decode any slot can run
        vocab_size      target vocabulary
        bos, eos        special token ids
        length_penalty  harvest-time score normalization (0 = off)
        readout         ops.decode LinearReadout / LogitsReadout instance

        prefill(feed)       canonical request feed -> per-sequence state
                            pytree, leading dim = the feed's rows (NOT
                            beam-tiled; the engine tiles at write_slot)
        step_fn(tokens, state) -> (readout_input, new_state)
                            the ops.decode step protocol over S*K rows
        example_feed(rows)  synthetic one-bucket feed for warmup/audit
    """

    beam_size: int = 3
    max_len: int = 32
    vocab_size: int = 0
    bos: int = 0
    eos: int = 1
    length_penalty: float = 0.0
    use_kernel: Optional[bool] = None

    def prefill(self, feed: Dict[str, Any]):
        raise NotImplementedError

    def step_fn(self, tokens, state):
        raise NotImplementedError

    def example_feed(self, rows: int = 1) -> Dict[str, Any]:
        raise NotImplementedError

    #: the weights as a pytree of arrays.  The scheduler hands them to its
    #: compiled closures as ARGUMENTS (through :meth:`with_params`), so the
    #: executables hold no copy of them.  The two defaults here — no
    #: weights, nothing to rebind — are for the tests' toy backends only,
    #: whose ``prefill``/``step_fn`` close over a few small arrays that
    #: then ride every executable as constants; a backend with a real
    #: model overrides both, as :class:`Seq2SeqSlotBackend` does.
    params: Any = ()

    def with_params(self, params) -> "SlotBackend":
        """This backend computing with ``params`` (same structure as
        :attr:`params`, possibly tracers) — what the scheduler's jitted
        closures call."""
        return self

    def fingerprint(self) -> Optional[str]:
        """Identity of the model behind the slot closures: keys their
        entries in the persistent compile cache (docs/deploy.md), the
        prefix cache and the draft corpus.  The cached prefills and
        learned completions depend on the parameter VALUES (and so do
        the executables of a backend that closes over its weights), so a
        correct fingerprint covers them — backends that cannot provide
        one return None and the scheduler skips caching rather than risk
        serving another model's state."""
        return None


class Seq2SeqSlotBackend(SlotBackend):
    """The flagship backend: :class:`~paddle_tpu.models.seq2seq
    .Seq2SeqAttention` behind the slot table.

    The per-slot state is the full decode context — attention GRU carry
    ``s`` plus the beam-tiled encoder outputs/projections/mask the step
    re-reads every token (the KV-cache analogue).  Prefill runs the
    encoder at a FIXED source length ``src_len`` (requests padded up to
    it; ``mask_from_lengths`` hides the padding exactly as in training),
    so every admitted request produces identically-shaped slot state and
    the step program never recompiles.
    """

    def __init__(self, model, params, *, src_len: int, beam_size: int = 3,
                 max_len: int = 32, length_penalty: float = 0.0,
                 use_kernel: Optional[bool] = None, feed_name: str = "src"):
        from paddle_tpu.data.feeder import bucket_length
        from paddle_tpu.models.seq2seq import BOS, EOS

        if src_len != bucket_length(src_len):
            # serving canonicalizes every request's sequence dim UP the
            # feeder bucket ladder — a table narrower than the smallest
            # bucket its own traffic lands in could never admit anything
            raise ValueError(
                f"src_len {src_len} is not a feeder bucket "
                f"(bucket_length -> {bucket_length(src_len)}); canonical "
                f"request feeds could never fit the slot table")
        self.model, self.params = model, params
        self.src_len = int(src_len)
        self.beam_size = int(beam_size)
        self.max_len = int(max_len)
        self.length_penalty = float(length_penalty)
        self.use_kernel = use_kernel
        self.feed_name = feed_name
        self.vocab_size = int(model.trg_vocab)
        self.bos, self.eos = BOS, EOS
        import paddle_tpu.ops as O

        self.readout = O.LinearReadout(params["out_w"], params["out_b"])

    def with_params(self, params):
        import copy

        import paddle_tpu.ops as O

        bound = copy.copy(self)
        bound.params = params
        bound.readout = O.LinearReadout(params["out_w"], params["out_b"])
        return bound

    def prefill(self, feed):
        import jax.numpy as jnp

        import paddle_tpu.ops as O

        ids, lens = feed[self.feed_name]
        ids = jnp.asarray(ids, jnp.int32)
        lens = jnp.asarray(lens, jnp.int32).reshape(-1)
        if ids.shape[1] > self.src_len:
            raise ValueError(
                f"request source length {ids.shape[1]} exceeds the slot "
                f"table's fixed src_len {self.src_len}")
        if ids.shape[1] < self.src_len:
            ids = jnp.pad(ids, ((0, 0), (0, self.src_len - ids.shape[1])),
                          constant_values=self.eos)
        mask = O.mask_from_lengths(lens, self.src_len)
        enc, enc_proj, s0 = self.model.encode(self.params, ids, mask)
        return {"s": s0, "enc": enc, "enc_proj": enc_proj, "mask": mask}

    def step_fn(self, tokens, state):
        import paddle_tpu.ops as O

        y_emb = O.embedding_lookup(self.params["trg_emb"], tokens)
        s_new, _ = self.model._dec_step(
            self.params, y_emb, state["s"], state["enc"], state["enc_proj"],
            state["mask"])
        return s_new, dict(state, s=s_new)

    def example_feed(self, rows: int = 1):
        ids = np.full((rows, self.src_len), 3, np.int32)
        lens = np.full((rows,), self.src_len, np.int32)
        return {self.feed_name: (ids, lens)}

    def fingerprint(self) -> str:
        # memoized: the value-level hash walks every weight's bytes, and
        # the params are immutable for the backend's lifetime — repeated
        # prime() calls must not re-pay a full-model hash inside the
        # cold-start path this cache exists to shrink
        fp = getattr(self, "_fingerprint", None)
        if fp is not None:
            return fp
        import hashlib

        h = hashlib.sha256()
        for name in sorted(self.params):
            a = np.asarray(self.params[name])
            h.update(f"{name}:{a.shape}:{a.dtype}".encode())
            h.update(np.ascontiguousarray(a).tobytes())
        h.update(f"{self.src_len}:{self.beam_size}:{self.max_len}:"
                 f"{self.length_penalty}:{self.use_kernel}:"
                 f"{self.feed_name}".encode())
        self._fingerprint = "seq2seq:" + h.hexdigest()[:32]
        return self._fingerprint


# ---------------------------------------------------------------------------
# the host-side slot table driver
# ---------------------------------------------------------------------------


@dataclass
class _SlotEntry:
    request: Request
    row: int          # which row of its (possibly multi-row) request
    limit: int        # per-request max_len, <= the table depth
    t_admit: float
    admit_step: int = 0   # steps_run at admission: per-request step
    #                       participation stays host-side (no device sync)
    history: List[int] = field(default_factory=list)
    #                       emission history (BOS-seeded) — the draft
    #                       proposer's input; maintained on the spec path
    tokens_done: int = 0  # emissions so far: the spec budget cap, and
    #                       the pager's remaining-work victim ranking
    pages: int = 0        # page-out round trips (anti-thrash bound)
    corpus_key: Optional[str] = None
    #                       request content hash scoping the draft
    #                       proposer's positional completion corpus


@dataclass
class _PendingRequest:
    request: Request
    rows: int
    results: List[Optional[Tuple[np.ndarray, np.ndarray]]] = field(
        default_factory=list)
    steps: int = 0    # max decode steps across the request's rows


class SlotScheduler:
    """Drive a :class:`SlotBackend` through the slot table.

    Owns the device carry plus the host bookkeeping (slot -> request/row,
    per-request result assembly, free list).  All compiled closures —
    step, write, release, finalize, prefill — are built once; prefill
    compiles per (row-bucket, seq-bucket) feed shape exactly like the
    bucket path, all primed by the server's warmup gate.

    Thread discipline: one worker drives the scheduler at a time; the
    short bookkeeping sections take ``_lock`` so a supervisor
    ``reset()`` (worker relaunch) can never interleave with them, and the
    device step is committed only when the caller's ``commit()`` check
    still holds — an abandoned (hung-then-replaced) worker that wakes up
    mid-step must not clobber the fresh worker's table.
    """

    def __init__(self, backend: SlotBackend, *, slots: int,
                 clock=time.monotonic, spec_k: int = 0,
                 draft: Optional[Any] = None,
                 prefix_cache_mb: float = 0.0,
                 page_pool_mb: float = 0.0):
        import jax

        from paddle_tpu.ops.decode import (decode_step, extract_slot,
                                           finalize_slots, init_slot_carry,
                                           release_slot, restore_slot,
                                           spec_verify_step, write_slot)
        from paddle_tpu.utils.log import logger

        if slots < 1:
            raise ValueError("slot table needs at least 1 slot")
        self.backend = backend
        self.slots = int(slots)
        self._clock = clock
        self._lock = threading.Lock()

        # speculative decoding rides the greedy-verify proof: beam>1 has
        # no greedy-verify equivalent, so it silently falls back to the
        # standard one-token step path (docs/decode.md)
        if spec_k > 0 and backend.beam_size != 1:
            logger.info("speculative decoding disabled: beam_size=%d "
                        "(greedy verify needs beam_size=1)",
                        backend.beam_size)
            spec_k = 0
        self.spec_k = int(spec_k)
        self.proposer = None
        if self.spec_k > 0:
            from paddle_tpu.ops.speculative import NGramProposer

            self.proposer = draft if draft is not None else NGramProposer()
        self.spec_drafted = 0    # draft tokens offered to verification
        self.spec_accepted = 0   # draft tokens the model confirmed
        self.last_spec: Optional[Tuple[np.ndarray, np.ndarray]] = None
        #: the dispatched-but-unsynced wide step: (aux, entry snapshot).
        #: The spec path pipelines one step deep — the device crunches
        #: wide step N while the host does harvest/admit/drafting for
        #: N+1; N's aux lands in host accounting at the top of the next
        #: step (by then the transfer is a no-wait read).  See
        #: _drain_spec for why every consumer of host accounting is
        #: sound against the one-step lag.
        self._spec_pending: Optional[Tuple[Any, List[Any]]] = None
        self.prefix_cache = None
        if prefix_cache_mb > 0:
            from paddle_tpu.serving.prefix_cache import PrefixCache

            self.prefix_cache = PrefixCache(prefix_cache_mb)
        self.pager = None
        if page_pool_mb > 0:
            from paddle_tpu.serving.paging import SlotPager

            self.pager = SlotPager(page_pool_mb)

        # step NEVER donates its carry: the commit-rejected (abandoned
        # worker) path discards the result and keeps the input.  Write and
        # release always commit, so on TPU the old table is donated and
        # the dynamic_update_slice lowers in place instead of copying the
        # whole table per admitted row (CPU ignores donation).
        donate = ((0,) if jax.default_backend() == "tpu" else ())
        # the closures that run the model (step, wide verify, prefill)
        # take its weights as their FIRST ARGUMENT and rebind the backend
        # to them while tracing: closed over, 30k x 512 tables would be
        # folded into each executable as constants — one more copy of the
        # model in HBM per program and per prefill bucket
        self._weights = backend.params
        bind = backend.with_params

        def step_prog(p, c):
            b = bind(p)
            return decode_step(
                b.step_fn, b.readout, c, vocab_size=backend.vocab_size,
                eos=backend.eos, use_kernel=backend.use_kernel)

        self._step_jit = jax.jit(step_prog)
        self._write_jit = jax.jit(
            lambda c, slot, s0, row: write_slot(
                c, slot, s0, bos=backend.bos, eos=backend.eos, row=row),
            donate_argnums=donate)
        # a fresh lambda, NOT the bare module function: jax.jit over the
        # same function identity shares the C++ call cache across
        # wrappers, which would make this table's _cache_size() (the
        # warmup_compiles measurement) count compiles other schedulers
        # in the process paid
        self._release_jit = jax.jit(lambda c, slot: release_slot(c, slot),
                                    donate_argnums=donate)
        self._final_jit = jax.jit(lambda c: finalize_slots(
            c, eos=backend.eos, length_penalty=backend.length_penalty))
        self._prefill_jit = jax.jit(lambda p, feed: bind(p).prefill(feed))
        #: the ORIGINAL jit closures, kept for (re-)priming: prime()
        #: swaps the working attributes for AOT executables, and a later
        #: prime against a fresh cache must lower from the real jits
        #: again (a Compiled object has no .lower)
        self._jit_src = {"step": self._step_jit, "write": self._write_jit,
                         "release": self._release_jit,
                         "final": self._final_jit,
                         "prefill": self._prefill_jit}
        if self.spec_k > 0:
            # the wide-verify step is a step: it must never donate (the
            # commit-rejected path keeps the input carry)
            def spec_prog(p, c, d, cap):
                b = bind(p)
                return spec_verify_step(
                    b.step_fn, b.readout, c, d, cap,
                    vocab_size=backend.vocab_size, eos=backend.eos,
                    use_kernel=backend.use_kernel)

            self._spec_jit = jax.jit(spec_prog)
            self._jit_src["spec"] = self._spec_jit
        if self.pager is not None:
            # extract must NOT donate — the table survives a page-out;
            # restore commits unconditionally once called, so it donates
            # like write
            self._extract_jit = jax.jit(
                lambda c, slot: extract_slot(c, slot))
            self._restore_jit = jax.jit(
                lambda c, slot, saved: restore_slot(c, slot, saved),
                donate_argnums=donate)
            self._jit_src["extract"] = self._extract_jit
            self._jit_src["restore"] = self._restore_jit

        tpl = jax.eval_shape(backend.prefill, backend.example_feed(1))
        self._state_treedef = jax.tree_util.tree_structure(tpl)
        self._init_carry = lambda: init_slot_carry(
            tpl, slots=self.slots, beam_size=backend.beam_size,
            max_len=backend.max_len, eos=backend.eos)
        self.carry = self._init_carry()  # tpu-lint: guarded-by=none - single stepping thread: only the worker (or boot) thread computes carry; writes take _lock purely for the abandoned-worker commit handshake, reads stay on the owning thread
        self._entries: List[Optional[_SlotEntry]] = [None] * self.slots
        self._free: List[int] = list(range(self.slots - 1, -1, -1))
        self._pending: Dict[int, _PendingRequest] = {}
        self.steps_run = 0
        self.recycled = 0       # slots freed (harvest + eviction)
        self.admitted = 0       # slots filled
        #: prime(): per-signature AOT prefill executables and per-rows
        #: write executables (step/release/finalize have one fixed carry
        #: shape for the table's lifetime and swap in place)
        self._prefill_aot: Dict[tuple, Any] = {}
        self._write_aot: Dict[int, Any] = {}
        self.cache_hits = 0
        self.cache_misses = 0

    def _prefill(self, feed):
        """The admit-side prefill: primed AOT executable when this exact
        feed signature was warmed, the jit closure otherwise."""
        if self._prefill_aot:
            from paddle_tpu.config.deploy import feed_signature

            fn = self._prefill_aot.get(feed_signature(feed))
            if fn is not None:
                try:
                    return fn(self._weights, feed)
                except TypeError:
                    pass  # aval drift: the jit path re-canonicalizes
        return self._prefill_jit(self._weights, feed)

    def _write(self, c, slot, s0, row):
        """write_slot dispatch: the s0 batch state's row count varies by
        admission bucket, so write executables are primed per-rows."""
        if self._write_aot:
            import jax

            rows = int(np.shape(jax.tree_util.tree_leaves(s0)[0])[0])
            fn = self._write_aot.get(rows)
            if fn is not None:
                try:
                    return fn(c, slot, s0, row)
                except TypeError:
                    pass
        return self._write_jit(c, slot, s0, row)

    def prime(self, cache, feeds: List[Dict[str, Any]], *,
              buckets: Optional[List[int]] = None) -> Dict[str, Any]:
        """Load-or-compile every compiled closure of the table from the
        persistent compile cache (docs/deploy.md): prefill at every
        admission bucket of every warmup feed shape, plus the four table
        closures (step / write / release / finalize).  Entries are keyed
        by the backend's value-level :meth:`SlotBackend.fingerprint`
        (conservative for a backend whose weights ride as arguments,
        required for one that closes over them); a backend without
        one skips caching (``{"skipped": True}``) and the server falls
        back to the synthetic-admission compile warmup."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.config.compile_cache import cache_key, compile_fresh
        from paddle_tpu.config.deploy import feed_signature
        from paddle_tpu.serving.batching import (batch_bucket,
                                                 warmup_bucket_feeds)
        from paddle_tpu.utils.log import logger

        counts = {"hits": 0, "misses": 0, "skipped": False}
        fp = self.backend.fingerprint()
        if cache is None or fp is None:
            if fp is None:
                logger.info("slot compile cache skipped: %s provides no "
                            "fingerprint()", type(self.backend).__name__)
            counts["skipped"] = True
            return counts
        b = self.backend
        table_sig = (self.slots, b.beam_size, b.max_len, b.vocab_size,
                     b.bos, b.eos, b.length_penalty, b.use_kernel)
        carry_sig = jax.tree_util.tree_map(
            lambda a: (tuple(np.shape(a)), str(np.asarray(a).dtype)),
            self.carry)

        def load_or_compile(kind, jit_fn, args, extra_sig=""):
            key = cache_key("slot_" + kind, fp, table_sig, carry_sig,
                            extra_sig)
            fn = cache.load(key)
            if fn is not None:
                try:
                    # smoke-call before trusting the entry, and wait for
                    # it: dispatch is asynchronous, and an executable that
                    # cannot run reports it only when its result is read
                    jax.block_until_ready(fn(*args))
                except Exception as e:  # noqa: BLE001 — degrade to compile
                    logger.warning("compile cache: slot %s executable "
                                   "rejected by its smoke call (%s: %s) — "
                                   "recompiling", kind, type(e).__name__, e)
                else:
                    counts["hits"] += 1
                    return fn
            compiled = compile_fresh(jit_fn.lower(*args))
            counts["misses"] += 1
            cache.store(key, compiled, label=f"slot_{kind}")
            return compiled

        # throwaway carries: write/release DONATE their carry on TPU, and
        # the smoke call must never consume the live table.  Lowering
        # always starts from _jit_src — the working attributes may
        # already hold AOT executables from an earlier prime
        self._step_jit = load_or_compile(
            "step", self._jit_src["step"],
            (self._weights, self._init_carry()))
        self._release_jit = load_or_compile(
            "release", self._jit_src["release"], (self._init_carry(), 0))
        self._final_jit = load_or_compile(
            "final", self._jit_src["final"], (self._init_carry(),))
        if self.spec_k > 0:
            # the wide-verify step joins the precompiled surface so the
            # first speculative step after boot never compiles
            self._spec_jit = load_or_compile(
                "spec", self._jit_src["spec"],
                (self._weights, self._init_carry(),
                 jnp.zeros((self.slots, self.spec_k), jnp.int32),
                 jnp.zeros((self.slots,), jnp.int32)),
                extra_sig=f"k={self.spec_k}")
        if self.pager is not None:
            self._extract_jit = load_or_compile(
                "extract", self._jit_src["extract"],
                (self._init_carry(), 0))
            saved0 = jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype),
                jax.eval_shape(self._jit_src["extract"],
                               self._init_carry(), 0))
            self._restore_jit = load_or_compile(
                "restore", self._jit_src["restore"],
                (self._init_carry(), 0, saved0))
        if buckets is None:
            buckets = sorted({batch_bucket(r, self.slots)
                              for r in range(1, self.slots + 1)})
        # dedup WITHIN this call only: a re-prime (e.g. against a fresh
        # cache dir) must re-process every signature so the new cache
        # gets populated, overwriting the instance tables as it goes
        for bucket in sorted(set(buckets)):
            # the s0 batch state scales with the admission bucket's rows
            s0 = jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype),
                jax.eval_shape(b.prefill, b.example_feed(bucket)))
            self._write_aot[bucket] = load_or_compile(
                "write", self._jit_src["write"],
                (self._init_carry(), 0, s0, 0), extra_sig=f"rows={bucket}")
        seen = set()
        for feed in feeds:
            for padded in warmup_bucket_feeds(feed, buckets):
                sig = feed_signature(padded)
                if sig in seen:
                    continue
                seen.add(sig)
                self._prefill_aot[sig] = load_or_compile(
                    "prefill", self._jit_src["prefill"],
                    (self._weights, padded),
                    extra_sig=str(sig))
        self.cache_hits += counts["hits"]
        self.cache_misses += counts["misses"]
        return counts

    def prime_step_programs(self) -> None:
        """Warm BOTH step programs against the live carry — the plain
        one-token step and, when speculation is armed, the wide verify.
        Speculation GATING picks between them per step from host-side
        proposer confidence, so a traffic-driven warmup can prove only
        whichever path its synthetic history happens to trigger; this
        makes zero-compiles-on-the-hot-path unconditional.  Results are
        discarded (neither step program donates its carry)."""
        import jax

        jax.block_until_ready(self._step_jit(self._weights, self.carry))
        if self.spec_k > 0:
            jax.block_until_ready(self._spec_jit(
                self._weights, self.carry,
                np.zeros((self.slots, self.spec_k), np.int32),
                np.zeros((self.slots,), np.int32)))

    def compiled_programs(self) -> int:
        """Distinct programs the ORIGINAL jit closures actually compiled
        in this process — the honest ``warmup_compiles`` count for an
        uncached boot (prime()'s AOT loads/compiles never enter these
        caches and are counted by its own hit/miss return)."""
        n = 0
        for fn in self._jit_src.values():
            size = getattr(fn, "_cache_size", None)
            if callable(size):
                try:
                    n += int(size())
                except Exception:  # noqa: BLE001 — jax-internal surface
                    pass
        return n

    # -- occupancy ---------------------------------------------------------

    def free_count(self) -> int:
        with self._lock:
            return len(self._free)

    def occupied(self) -> int:
        with self._lock:
            return self.slots - len(self._free)

    def resident_requests(self) -> List[Request]:
        """The distinct requests currently holding slots (oldest first) —
        the server's in-flight set for crash attribution."""
        with self._lock:
            return [p.request for p in self._pending.values()]

    def resident_view(self) -> List[Tuple[Request, List[int], int]]:
        """Per-resident ``(request, slots, steps_since_admit)`` — the
        attribution surface request tracing stamps onto each fused-step
        span (slot ids, per-request step participation).  Purely host-side
        bookkeeping: reading the device carry here would add one d2h sync
        per step."""
        with self._lock:
            by_req: Dict[int, List[Any]] = {}
            for slot, e in enumerate(self._entries):
                if e is None:
                    continue
                ent = by_req.setdefault(id(e.request), [e.request, [], 0])
                ent[1].append(slot)
                ent[2] = max(ent[2], self.steps_run - e.admit_step)
            return [(r, s, n) for r, s, n in by_req.values()]

    def reset(self) -> List[Request]:
        """Fresh table (worker relaunch): drops every resident request's
        state and returns those requests so the caller can fail them typed
        (usually already done by the crash handler — futures are
        set-once, so double-failing is a no-op)."""
        with self._lock:
            dropped = [p.request for p in self._pending.values()]
            self.carry = self._init_carry()
            self._entries = [None] * self.slots
            self._free = list(range(self.slots - 1, -1, -1))
            self._pending.clear()
            if self.pager is not None:
                self.pager.clear()  # parked requests are in _pending too
            self.last_spec = None
            self._spec_pending = None  # aux of a pre-reset carry: stale
            return dropped

    # -- admission ---------------------------------------------------------

    def _cache_key(self, req: Request) -> Optional[str]:
        """Prefix-cache key for a request, or None when uncacheable:
        content hash over the model fingerprint + the canonical feed
        bytes (+ the chat ``session_id`` when present, scoping chat
        turns to their own session).  Multi-row requests are not cached
        (their rows would need per-row keys for marginal benefit)."""
        if self.prefix_cache is None:
            return None
        if getattr(req, "rows", 1) != 1:
            return None
        fp = self.backend.fingerprint()
        if fp is None:
            return None
        parts: List[Any] = [fp]
        sid = getattr(req, "session_id", None)
        if sid is not None:
            parts.append(f"session:{sid}")
        for name in sorted(req.feed):
            v = req.feed[name]
            parts.append(name)
            if isinstance(v, (tuple, list)):
                parts.extend(np.asarray(x) for x in v)
            else:
                parts.append(np.asarray(v))
        return self.prefix_cache.key(*parts)

    def _corpus_key(self, req: Request, row: int) -> Optional[str]:
        """Content key scoping the draft proposer's positional
        completion corpus: model fingerprint + canonical feed bytes
        (+ ``session_id``) + the request row.  Greedy decode is
        deterministic, so a request with the same key emits the same
        sequence — the proposer replays an earlier completion
        positionally (acceptance ~1.0 on repeat/template traffic).
        The fingerprint scopes learned completions to the live model
        generation: a hot-swap changes every key, so stale-model
        trajectories can never be replayed (and the proposer's prefix
        check backstops even that).  Independent of the prefix cache —
        speculation is worth keying with or without cached prefills."""
        if self.spec_k <= 0:
            return None
        fp = self.backend.fingerprint()
        if fp is None:
            return None
        from paddle_tpu.serving.prefix_cache import feed_key

        parts: List[Any] = [fp, f"row:{row}"]
        sid = getattr(req, "session_id", None)
        if sid is not None:
            parts.append(f"session:{sid}")
        for name in sorted(req.feed):
            v = req.feed[name]
            parts.append(name)
            if isinstance(v, (tuple, list)):
                parts.extend(np.asarray(x) for x in v)
            else:
                parts.append(np.asarray(v))
        return feed_key(*parts)

    def admit(self, reqs: List[Request], *,
              limit_cap: Optional[int] = None,
              commit: Callable[[], bool] = lambda: True) -> int:
        """Prefill ``reqs`` in ONE merged encoder call and write each REAL
        row into a free slot.  ``merge_feeds`` pads rows by replication up
        to the batch bucket; the per-request ``slices`` (true row counts —
        the satellite contract) are what gets written, so a replicated pad
        row can never occupy a slot or be harvested as a result.  The
        caller guarantees ``sum(rows) <= free_count()``.  Returns slots
        filled (0 when ``commit()`` no longer holds after the device-bound
        prefill — an abandoned worker must not write into the fresh
        worker's table; its requests were already failed by the crash
        handler).  Raises on prefill failure (a model fault — nothing was
        admitted; the caller fails the batch typed).

        With a :class:`~paddle_tpu.serving.prefix_cache.PrefixCache`
        attached, single-row requests whose content key was prefilled
        before skip the encoder entirely: their cached state rows are
        written straight into slots (prefill is row-independent and
        batch-size-invariant, so a cached row is bit-identical to a
        fresh one).  Cache-missing rows are prefilled as one merged
        call and their state rows populate the cache post-commit."""
        if not reqs:
            return 0
        import jax

        hits: List[Tuple[Request, Dict[str, np.ndarray]]] = []
        misses: List[Request] = []
        keys: Dict[int, Optional[str]] = {}
        if self.prefix_cache is not None:
            for req in reqs:
                key = self._cache_key(req)
                keys[id(req)] = key
                payload = self.prefix_cache.get(key) if key else None
                if payload is not None:
                    hits.append((req, payload))
                else:
                    misses.append(req)
        else:
            misses = list(reqs)

        state0 = slices = None
        if misses:
            merged, slices, _rows = merge_feeds(misses, self.slots)
            state0 = self._prefill(merged)
        state_h = None
        if hits:
            from paddle_tpu.serving.batching import batch_bucket

            # stack cached rows and pad by replication up to the batch
            # bucket — the same primed _write_aot bucket surface the
            # merged-prefill path lands on, so a hit never recompiles
            nleaf = len(hits[0][1])
            cols = [np.concatenate([p[f"leaf{i}"] for _, p in hits],
                                   axis=0) for i in range(nleaf)]
            bucket = batch_bucket(len(hits), self.slots)
            if bucket > len(hits):
                cols = [np.concatenate(
                    [c] + [c[-1:]] * (bucket - len(hits)), axis=0)
                    for c in cols]
            state_h = jax.tree_util.tree_unflatten(
                self._state_treedef, cols)

        now = self._clock()
        n = 0
        with self._lock:
            if not commit():
                return 0
            need = ((sum(b - a for a, b in slices) if slices else 0)
                    + len(hits))
            if need > len(self._free):
                raise RuntimeError(
                    f"admit overflow: {need} rows into "
                    f"{len(self._free)} free slots")

            def _admit_rows(req, a, b, state):
                nonlocal n
                limit = min(req.max_len or self.backend.max_len,
                            self.backend.max_len,
                            limit_cap or self.backend.max_len)
                limit = max(1, int(limit))
                self._pending[id(req)] = _PendingRequest(
                    request=req, rows=b - a,
                    results=[None] * (b - a))
                # the helper is defined AND only ever called inside the
                # enclosing `with self._lock` block — the lock is held
                # for every access below (static race lint can't see
                # through the nested scope, hence the annotations)
                for row in range(a, b):
                    slot = self._free.pop()  # tpu-lint: guarded-by=_lock - called only from the admit() lock block
                    self.carry = self._write(self.carry, slot, state, row)
                    self._entries[slot] = _SlotEntry(  # tpu-lint: guarded-by=_lock - called only from the admit() lock block
                        req, row - a, limit, now, self.steps_run,  # tpu-lint: guarded-by=_lock - called only from the admit() lock block
                        history=[self.backend.bos],
                        corpus_key=self._corpus_key(req, row - a))
                    n += 1

            if misses:
                for req, (a, b) in zip(misses, slices):
                    _admit_rows(req, a, b, state0)
            for i, (req, _) in enumerate(hits):
                _admit_rows(req, i, i + 1, state_h)
            self.admitted += n
        # populate the cache from the rows just prefilled — post-commit,
        # so an abandoned worker's prefill can never seed the cache
        if self.prefix_cache is not None and misses and n:
            leaves = jax.tree_util.tree_leaves(state0)
            for req, (a, b) in zip(misses, slices):
                key = keys.get(id(req))
                if key is None or b - a != 1:
                    continue
                self.prefix_cache.put(key, {
                    f"leaf{i}": np.asarray(leaf[a:a + 1])
                    for i, leaf in enumerate(leaves)})
        return n

    # -- the fused step ----------------------------------------------------

    def step(self, commit: Callable[[], bool] = lambda: True) -> bool:
        """Run one fused decode step for every occupied slot.  The new
        carry is committed only if ``commit()`` still holds after the
        device call returns (abandoned-worker discipline).  With
        speculative decoding armed (``spec_k > 0`` over a greedy table)
        this is the wide-verify step: up to ``spec_k + 1`` tokens per
        slot per call, bit-identical to one-token stepping."""
        if self.spec_k > 0:
            return self._spec_step(commit)
        new = self._step_jit(self._weights, self.carry)
        with self._lock:
            if not commit():
                return False
            self.carry = new
            self.steps_run += 1
            for e in self._entries:
                if e is not None:
                    e.tokens_done += 1
        return True

    def _spec_step(self, commit: Callable[[], bool]) -> bool:
        """One speculative step: host-propose ``spec_k`` drafts per
        occupied slot from its emission history, verify all of them in
        ONE fused :func:`~paddle_tpu.ops.decode.spec_verify_step` call,
        and sync the per-slot emissions back into the histories the
        next round of drafting reads.  The per-slot ``cap`` (remaining
        request budget) keeps wide emission from stepping past each
        request's own ``max_len`` — the in-op form of the harvest-
        before-step bound the one-token path gets for free.

        Speculation is GATED per step: when no occupied slot has a
        *confident* draft (learned corpus / suffix match / draft model
        — see ``DraftProposer.propose_with_confidence``), the wide
        verify would pay ``k + 1`` recurrence positions for a
        guaranteed single emission, so the table runs the plain
        one-token step instead.  Both programs are compiled at prime
        time, so gating never triggers a new XLA compile on the hot
        path.  Gated steps offer no drafts, so they leave the
        acceptance-rate accounting untouched.

        The wide step is dispatched ASYNC and its aux outputs are NOT
        read back here: the sync is deferred to the top of the next
        step (``_drain_spec``), so the device computes wide step N
        while the host runs harvest / admission / drafting for N+1 —
        the same one-step overlap the plain path gets from jax's async
        dispatch for free.  Draining first means drafts and caps below
        are always computed from fully-synced accounting."""
        k = self.spec_k
        if not self._drain_spec(commit):
            return False
        with self._lock:
            entries = list(self._entries)
        drafts = np.zeros((self.slots, k), np.int32)
        cap = np.zeros((self.slots,), np.int32)
        any_conf = False
        for slot, e in enumerate(entries):
            if e is None:
                continue
            cap[slot] = max(0, e.limit - e.tokens_done)
            d, conf = self.proposer.propose_with_confidence(
                e.history, k, key=e.corpus_key)
            drafts[slot] = d
            any_conf = any_conf or conf
        budgets = [int(cap[slot]) for slot, e in enumerate(entries)
                   if e is not None]
        if not any(budgets):
            # every occupied slot has spent its request budget and only
            # waits for harvest (whose host fast path lagged the step
            # just drained): there is nothing to step
            return True
        if not any_conf and all(budgets):
            # (a slot at its limit rules the plain step out: only the
            # wide step honours ``cap``, and one more plain step would
            # add a token's log-prob past the request's ``max_len``)
            # cold table: nothing worth verifying — one-token step.
            # Histories are NOT extended here (that would cost a host
            # sync, the thing the wide step amortizes); the proposer
            # learns completed trajectories at harvest instead, so a
            # stale in-flight history only lowers acceptance, never
            # correctness.
            new = self._step_jit(self._weights, self.carry)
            with self._lock:
                if not commit():
                    return False
                self.carry = new
                self.steps_run += 1
                for slot, e in enumerate(self._entries):
                    if e is not None and e is entries[slot]:
                        e.tokens_done += 1
                self.last_spec = None
            return True
        new, aux = self._spec_jit(self._weights, self.carry, drafts, cap)
        with self._lock:
            if not commit():
                return False
            self.carry = new
            self.steps_run += 1
            self._spec_pending = (aux, entries)
        return True

    def _drain_spec(self, commit: Callable[[], bool] = lambda: True
                    ) -> bool:
        """Land the pending wide step's aux outputs (accepted counts,
        emitted tokens) into host accounting: histories, ``tokens_done``,
        the acceptance counters, ``last_spec``.  Called at the top of
        the next step — by then the device has finished the step, so
        the read-back costs a transfer, not a stall — and by any
        consumer that snapshots per-slot device state host-side
        (``page_out_victim``: its parked record must not be one step
        behind the carry it extracts).

        Every other consumer is sound against the one-step lag:
        ``done_slots``'s host fast path under-claims at worst (a slot
        looks unfinished for one extra cycle), harvest reads device
        truth for tokens/scores, and a finished slot is a fixed point
        of the wide step (its remaining cap is 0, so the pending step
        emits nothing into it).  A reset between dispatch and drain
        fails ``commit()`` and the stale aux is discarded — its entry
        snapshot no longer matches the table either way."""
        p = self._spec_pending  # tpu-lint: guarded-by=_lock - popped only by the single driving worker (step/page_out); a racing reset() fails commit() below and the stale aux is discarded
        if p is None:
            return True
        self._spec_pending = None  # tpu-lint: guarded-by=_lock - same single-driver discipline as the read above
        aux, entries = p
        k = self.spec_k
        n_arr = np.asarray(aux["n"])
        em = np.asarray(aux["emitted"])
        acc = np.asarray(aux["accepted"])
        with self._lock:
            if not commit():
                return False
            for slot, e in enumerate(self._entries):
                # identity check: a slot released (harvest/evict) and
                # possibly re-admitted since dispatch must not receive
                # the old request's emissions
                if e is None or e is not entries[slot]:
                    continue
                ni = int(n_arr[slot])
                e.history.extend(int(t) for t in em[slot, :ni])
                e.tokens_done += ni
                self.spec_drafted += k
            self.spec_accepted += int(acc.sum())
            self.last_spec = (n_arr, acc)
        return True

    # -- harvest + eviction ------------------------------------------------

    def _release(self, slot: int) -> None:
        # callers hold _lock
        self.carry = self._release_jit(self.carry, slot)
        self._entries[slot] = None
        self._free.append(slot)
        self.recycled += 1

    def _park(self, slot: int) -> None:
        # callers hold _lock: free the slot WITHOUT counting a recycle —
        # a paged-out request is still in flight, not completed, so the
        # recycled counter (one per finished/evicted slot, pinned by the
        # CLI smoke test) must not move
        self.carry = self._release_jit(self.carry, slot)
        self._entries[slot] = None
        self._free.append(slot)

    def _drop_request(self, req: Request) -> int:
        # callers hold _lock: release EVERY slot the request occupies,
        # resident or parked in the host page pool
        n = 0
        for slot, e in enumerate(self._entries):
            if e is not None and e.request is req:
                self._release(slot)
                n += 1
        if self.pager is not None:
            self.pager.drop_request(req)
        self._pending.pop(id(req), None)
        return n

    # -- host paging -------------------------------------------------------

    def page_out_victim(self,
                        commit: Callable[[], bool] = lambda: True) -> bool:
        """Host-evict the coldest occupied slot — the one with the MOST
        remaining decode budget (it will hold its slot longest), at
        least one step old (never page what was just admitted) and under
        the anti-thrash bound of 2 round trips.  Its full decode context
        d2h-copies into the pager pool and the slot frees for an
        admission; :meth:`page_in` restores it bit-for-bit later."""
        if self.pager is None:
            return False
        import jax

        from paddle_tpu.serving.paging import PagedSlot

        # land any in-flight wide step first: the parked record's
        # history/tokens_done must describe the same step the extracted
        # payload reflects, or the restored slot re-drafts stale
        if self.spec_k > 0 and not self._drain_spec(commit):
            return False
        with self._lock:
            best, best_rem = None, -1
            for slot, e in enumerate(self._entries):
                if (e is None or e.pages >= 2
                        or self.steps_run - e.admit_step <= 0):
                    continue
                rem = e.limit - e.tokens_done
                if rem > best_rem:
                    best_rem, best = rem, slot
            if best is None:
                return False
            ent = self._entries[best]
        saved = self._extract_jit(self.carry, best)
        payload = jax.tree_util.tree_map(np.asarray, saved)  # d2h copy
        rec = PagedSlot(request=ent.request, row=ent.row, limit=ent.limit,
                        t_admit=ent.t_admit, history=list(ent.history),
                        tokens_done=ent.tokens_done, payload=payload,
                        pages=ent.pages + 1, admit_step=ent.admit_step)
        with self._lock:
            if not commit() or self._entries[best] is not ent:
                return False
            if not self.pager.park(rec):
                return False  # pool full: the slot stays resident
            self._park(best)
        return True

    def page_in(self, commit: Callable[[], bool] = lambda: True) -> int:
        """Re-admit parked slots (FIFO — no starvation) while free slots
        remain, restoring each snapshot bit-for-bit via
        :func:`~paddle_tpu.ops.decode.restore_slot`.  Returns slots
        restored.  Runs BEFORE new admissions each cycle so parked work
        is never overtaken indefinitely by fresh arrivals."""
        if self.pager is None:
            return 0
        n = 0
        while True:
            with self._lock:
                if not self._free:
                    return n
            rec = self.pager.pop()
            if rec is None:
                return n
            with self._lock:
                if not commit():
                    # a reset is in flight — it clears the pager and
                    # fails every pending request, this record included
                    return n
                slot = self._free.pop()
                self.carry = self._restore_jit(self.carry, slot,
                                               rec.payload)
                self._entries[slot] = _SlotEntry(
                    rec.request, rec.row, rec.limit, rec.t_admit,
                    self.steps_run, history=list(rec.history),
                    tokens_done=rec.tokens_done, pages=rec.pages,
                    corpus_key=self._corpus_key(rec.request, rec.row))
                n += 1

    def evict_expired(self, now: float,
                      commit: Callable[[], bool] = lambda: True
                      ) -> List[Tuple[Request, int]]:
        """Release every slot whose request's deadline has passed
        mid-generation; returns ``(request, slots_freed)`` pairs (each
        request once) so the caller completes them with
        ``DeadlineExceeded``.  ``slots_freed`` counts the slots actually
        released NOW — rows of a multi-row request that already harvested
        are not re-counted."""
        with self._lock:
            if not commit():
                return []
            expired = []
            for e in self._entries:
                if (e is not None and e.request.deadline is not None
                        and now > e.request.deadline
                        and not any(r is e.request for r, _ in expired)):
                    expired.append((e.request, 0))
            if self.pager is not None:
                # the paged half of the sweep: a parked request's
                # deadline keeps ticking in the host pool
                for rec in self.pager.sweep_expired(
                        lambda r: r.request.deadline is not None
                        and now > r.request.deadline):
                    if not any(r is rec.request for r, _ in expired):
                        expired.append((rec.request, 0))
            return [(req, self._drop_request(req)) for req, _ in expired]

    def done_slots(self) -> List[int]:
        """Slots whose request finished: all beams EOS, or the request's
        own ``max_len`` reached.  One host sync over two tiny arrays —
        skipped entirely on an empty table (the sync would otherwise
        block on the previous step's async dispatch every idle cycle).

        On the speculative path the answer comes from HOST accounting
        alone — no device read.  ``tokens_done`` mirrors the device step
        counter exactly for every occupied slot (wide steps advance both
        by the emitted count, gated plain steps by one — including the
        EOS-padding emissions of finished rows), and an EOS in the
        drained emission history implies the device ``finished`` flag.
        Host evidence therefore never over-claims; it can lag device
        truth by at most the one undrained in-flight step, which only
        delays a harvest by a cycle (a done slot is a fixed point of the
        wide step: its cap is 0 once accounting catches up).  Skipping
        the read matters because this runs every serve cycle: a device
        sync here would stall the pipelined wide step ``_spec_step``
        just dispatched."""
        with self._lock:
            if not any(e is not None for e in self._entries):
                return []
            if self.spec_k > 0:
                eos = self.backend.eos
                return [i for i, e in enumerate(self._entries)
                        if e is not None
                        and (e.tokens_done >= e.limit
                             or eos in e.history[1:])]
        fin = np.asarray(self.carry["finished"]).all(axis=1)
        stepc = np.asarray(self.carry["step"])
        with self._lock:
            return [i for i, e in enumerate(self._entries)
                    if e is not None and (fin[i] or stepc[i] >= e.limit)]

    def harvest(self, commit: Callable[[], bool] = lambda: True
                ) -> List[Tuple[Request, Optional[Dict[str, Any]], int]]:
        """Collect finished slots, recycle them, and assemble completed
        requests.  Returns ``(request, outputs, steps)`` triples — outputs
        ``{"tokens": [rows, K, limit] i32, "scores": [rows, K] f32}``
        sliced to the request's own ``max_len`` and bit-identical to a
        solo ``beam_decode`` run of the same request."""
        done = self.done_slots()
        if not done:
            return []
        toks_d, scores_d = self._final_jit(self.carry)
        toks, scores = np.asarray(toks_d), np.asarray(scores_d)
        stepc = np.asarray(self.carry["step"])
        out: List[Tuple[Request, Optional[Dict[str, Any]], int]] = []
        with self._lock:
            if not commit():
                return []
            for slot in done:
                e = self._entries[slot]
                if e is None:       # raced with an eviction
                    continue
                pend = self._pending.get(id(e.request))
                if self.spec_k > 0 and stepc[slot] > 0:
                    # feed the completed trajectory back to the draft
                    # proposer: session/template traffic drafts the next
                    # identical request from this one (host dict insert,
                    # never touches the compiled surface).  Learned from
                    # the FINALIZED host tokens, not e.history — history
                    # is only maintained on wide steps, so gated (plain)
                    # steps would leave it stale
                    seq = [self.backend.bos] + [
                        int(t) for t in
                        toks[slot][0][:min(int(stepc[slot]), e.limit)]]
                    self.proposer.learn(seq, key=e.corpus_key)
                self._release(slot)
                if pend is None:
                    continue
                pend.results[e.row] = (toks[slot][:, :e.limit],
                                       scores[slot])
                pend.steps = max(pend.steps, int(stepc[slot]))
                if all(r is not None for r in pend.results):
                    self._pending.pop(id(e.request))
                    out.append((
                        pend.request,
                        {"tokens": np.stack([r[0] for r in pend.results]),
                         "scores": np.stack([r[1] for r in pend.results])},
                        pend.steps))
        return out


# ---------------------------------------------------------------------------
# audit + self-test helpers
# ---------------------------------------------------------------------------


def example_slot_backend(*, slots: int = 4, beam_size: int = 4,
                         src_len: int = 8, max_len: int = 8,
                         vocab: int = 1024, dim: int = 128,
                         use_kernel: Optional[bool] = None
                         ) -> Seq2SeqSlotBackend:
    """A compact flagship-shaped backend (lane-aligned dims — structure,
    not perf) for the lint audit and the CLI continuous smoke test."""
    import jax

    from paddle_tpu.models import Seq2SeqAttention

    m = Seq2SeqAttention(src_vocab=vocab, trg_vocab=vocab, emb_dim=dim,
                         enc_dim=dim, dec_dim=dim, att_dim=dim)
    params = m.init(jax.random.PRNGKey(0))
    return Seq2SeqSlotBackend(m, params, src_len=src_len,
                              beam_size=beam_size, max_len=max_len,
                              use_kernel=use_kernel)


def audit_slot_backend(backend: Optional[SlotBackend] = None, *,
                       slots: int = 4, label: str = "serve_slots",
                       spec_k: int = 0):
    """Audit the compiled ``decode_step`` closure over a slot table —
    same contract as ``analysis.audit_decode`` (host transfers inside the
    step are an ERROR: one per token per request at serving rates), used
    by ``python -m paddle_tpu lint --serve`` and the generation-mode
    server preflight.  Both readout variants are traced where the kernel
    gate admits the shape (the kernel in interpret mode off-TPU).  With
    ``spec_k > 0`` over a greedy (``beam_size == 1``) backend the
    compiled wide-verify closure is audited under the same contract —
    a host transfer inside the speculative step would fire once per
    wide step, exactly the hazard the one-token audit guards."""
    import jax

    from paddle_tpu.analysis import Finding, audit_decode
    from paddle_tpu.ops.decode import (_forced_kernel_config, decode_step,
                                       init_slot_carry, spec_verify_step)

    backend = backend or example_slot_backend(slots=slots)
    tpl = jax.eval_shape(backend.prefill, backend.example_feed(1))
    carry = init_slot_carry(tpl, slots=slots, beam_size=backend.beam_size,
                            max_len=backend.max_len, eos=backend.eos)
    depth = getattr(getattr(backend, "readout", None), "w", None)
    depth = None if depth is None else int(depth.shape[0])
    findings = []
    variants = [(False, "xla_topk")]
    if (depth is not None and _forced_kernel_config(
            slots * backend.beam_size, depth, backend.vocab_size,
            min(backend.beam_size, backend.vocab_size)) is not None):
        variants.insert(0, (True, "kernel"))
    for use_kernel, tag in variants:
        try:
            findings.extend(audit_decode(
                lambda c, uk=use_kernel: decode_step(
                    backend.step_fn, backend.readout, c,
                    vocab_size=backend.vocab_size, eos=backend.eos,
                    use_kernel=uk),
                carry, label=f"{label}[{tag}]"))
        except Exception as e:  # a step that fails to TRACE is a finding
            findings.append(Finding(
                check="serve-build", severity="ERROR",
                file=f"{label}[{tag}]",
                message=f"slot decode_step failed to trace: "
                        f"{type(e).__name__}: {e}"))
    if spec_k > 0 and backend.beam_size == 1:
        import jax.numpy as jnp

        drafts = jnp.zeros((slots, spec_k), jnp.int32)
        cap = jnp.full((slots,), backend.max_len, jnp.int32)
        try:
            findings.extend(audit_decode(
                lambda c: spec_verify_step(
                    backend.step_fn, backend.readout, c, drafts, cap,
                    vocab_size=backend.vocab_size, eos=backend.eos,
                    use_kernel=backend.use_kernel)[0],
                carry, label=f"{label}[spec_verify]"))
        except Exception as e:
            findings.append(Finding(
                check="serve-build", severity="ERROR",
                file=f"{label}[spec_verify]",
                message=f"spec_verify_step failed to trace: "
                        f"{type(e).__name__}: {e}"))
    return findings
