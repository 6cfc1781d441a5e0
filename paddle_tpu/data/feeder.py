"""DataFeeder: python samples -> padded device arrays.

Analog of the reference's DataProviderConverter / py_paddle feeder
(py_paddle/dataprovider_converter.py; Argument construction in
paddle/api/Arguments.cpp): converts a minibatch of python rows into the feed
dict ``Topology.apply`` expects.

TPU-first: sequences are padded to a *bucketed* max length (next power-of-two
style buckets by default) so XLA sees a small, finite set of shapes instead of
one shape per batch (the reference's flat layout has no padding at all; on TPU
bucketing is the shape-stability analog). Slot kinds mirror the reference's
input types (dense_vector, integer_value, integer_value_sequence,
dense_vector_sequence, sparse later).
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, \
    Tuple

import numpy as np

__all__ = ["DataFeeder", "bucket_length", "feeder_kind_for_layer",
           "BatchPrefetcher", "PreparedFeed", "PrepareError",
           "note_padding"]

_DEFAULT_BUCKETS = (8, 16, 32, 64, 128, 256, 512, 1024)


def bucket_length(n: int, buckets: Sequence[int] = _DEFAULT_BUCKETS) -> int:
    for b in buckets:
        if n <= b:
            return b
    return n


# per-bucket cumulative (real, padded) token totals behind the
# ``data_bucket_occupancy`` gauge — process-wide like the registry
# itself; lock-guarded because a ``BatchPrefetcher`` runs the feeder on
# its background thread
_BUCKET_TOTALS: Dict[int, List[int]] = {}
_BUCKET_LOCK = threading.Lock()


def note_padding(real: int, padded: int, bucket: int, *,
                 waste: float) -> None:
    """Record one padded batch on the pad-waste instruments
    (docs/observability.md): ``data_pad_waste`` (the cumulative
    padded-but-dead token fraction — the quantity ``--data_pack`` exists
    to crush) and per-bucket ``data_bucket_occupancy`` (how full the
    rows landing in each T-bucket actually are).  Host-side only; called
    by ``DataFeeder`` and ``datapipe.PackedDataFeeder`` so bucketed and
    packed pipelines report on the SAME series."""
    from paddle_tpu.obs import get_registry

    reg = get_registry()
    reg.gauge("data_pad_waste",
              "cumulative padded-but-dead token fraction").set(waste)
    with _BUCKET_LOCK:
        tot = _BUCKET_TOTALS.setdefault(int(bucket), [0, 0])
        tot[0] += int(real)
        tot[1] += int(padded)
        occ = tot[0] / max(tot[1], 1)
    reg.gauge("data_bucket_occupancy",
              "real-token fraction of batches padded to this T bucket",
              labels=("bucket",), bucket=int(bucket)).set(occ)


def feeder_kind_for_layer(layer) -> str:
    """Derive the feeder slot kind for a data LayerOutput — THE single
    mapping from data_spec/v2 input type to DataFeeder kinds (used by the
    v2 trainer's auto-feeder and paddle.v2.topology.data_type)."""
    t = layer.meta.get("v2_type")
    if t is not None:
        return t.feeder_kind
    spec = layer.data_spec or {}
    if spec.get("sparse") == "binary":
        return "sparse_ids_seq" if spec.get("is_seq") else "sparse_ids"
    if spec.get("sparse") == "float":
        return "sparse_pairs_seq" if spec.get("is_seq") else "sparse_pairs"
    is_int = spec.get("dtype") == "int32"
    if spec.get("nested"):
        return "ids_nested" if is_int else "dense_nested"
    if spec.get("is_seq"):
        return "ids_seq" if is_int else "dense_seq"
    return "int" if is_int else "dense"


class PrepareError(Exception):
    """A batch failed in the prefetcher's ``prepare`` (DataFeeder) or
    ``transfer`` (h2d) stage — NOT in the reader.  Raised at the
    consumer's ``next()`` with the original exception as ``__cause__``;
    the trainer unwraps it so a feeder bug keeps its own type instead of
    being misattributed to the data-reading tier as a ``ReaderError``."""


class PreparedFeed:
    """Marker wrapper for a batch the :class:`BatchPrefetcher` has already
    pushed through the feeder (and, when configured, host->device
    transfer): the trainer consumes ``.feed`` directly instead of paying
    ``prepare``/``h2d`` on the step critical path."""

    __slots__ = ("feed",)

    def __init__(self, feed: Any) -> None:
        self.feed = feed


class BatchPrefetcher:
    """Double-buffered async feeding (ROADMAP item 3; ``--prefetch_depth``).

    Wraps a raw batch iterator: a background thread pulls batch N+1..N+depth,
    runs ``prepare`` (the DataFeeder) and ``transfer`` (synced ``device_put``)
    on them, and parks the results in a bounded queue, so the training loop's
    ``data_wait`` / ``prepare`` / ``h2d`` phases collapse to a queue pop.

    WHEN the thread works is the consumer's choice.  The thread sleeps in
    ``put`` on a full queue and is woken by whoever frees a place.  A
    consumer that only calls ``next()`` frees it when it NEEDS a batch, which
    in a training loop is the turn-around between two device steps: the
    thread's ``prepare`` and ``h2d`` then run while the device waits, on the
    interpreter the loop needs to launch the next step (PERF.md section 6,
    PR 38).  ``take()`` is for that: the trainer calls it right after it
    has dispatched a step, the next item moves from the queue into ONE
    consumer-side slot without blocking, and the thread's work on a later
    batch runs under the device step; the following ``next()`` returns the
    slot and touches no queue, so the thread sleeps across the turn-around.
    ``prefetch_taken_total{when="dispatch"|"late"}`` counts, one a batch,
    whether ``next()`` found the slot filled or had to go to the queue
    (the queue was empty at ``take()``, ``take()`` was never called, or it
    is a pass's first batch).  Semantics are loop-equivalent to serial
    feeding:

    - order is preserved exactly (single producer, FIFO queue, and the slot
      holds the queue's head);
    - a reader/feeder exception is re-raised at the consumer's ``next()``,
      so the trainer's reader-attribution path is unchanged: one that
      ``take()`` finds waits in the slot, like the end marker, for the
      ``next()`` that would have met it;
    - read-ahead is bounded: at most ``depth`` prepared batches in the
      queue, one in the slot and the one in the producer's hands exist
      (``depth + 2``; ``depth + 1`` for a consumer that never calls
      ``take()``), so a preemption or resize at a batch boundary abandons a
      bounded amount of work and the resume point — which counts batches
      the STEP consumed, not batches read ahead — stays batch-exact;
    - ``close()`` drops the slot, stops the producer and joins it (called
      by the trainer at pass end, preemption exit, and on any loop
      exception).
    """

    _DONE = object()
    #: the producer thread's spans on a profiler trace: ``prepare``, ``h2d``
    #: (the ``transfer`` call) and ``put`` (waiting for room in the queue),
    #: one of each a batch.  Not under ``paddle_tpu.trainer.``: the loop's
    #: spans stay on one thread (docs/observability.md "Names on the
    #: device trace")
    SPAN_PREFIX = "paddle_tpu.data.prefetch."

    def __init__(self, it: Iterator, *, prepare: Optional[Callable] = None,
                 transfer: Optional[Callable] = None, depth: int = 2) -> None:
        from paddle_tpu.obs import get_registry

        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, int(depth)))
        self._slot: Any = None  # the consumer thread's alone
        self._taken = {
            when: get_registry().counter(
                "prefetch_taken_total",
                "batches by where the loop took them from the prefetch "
                "queue: at the step's dispatch, or late in data_wait",
                labels=("when",), when=when)
            for when in ("dispatch", "late")}
        self._stop = threading.Event()
        self._prepare = prepare
        self._transfer = transfer
        self._thread = threading.Thread(
            target=self._run, args=(it,), name="batch-prefetch", daemon=True)
        self._thread.start()

    def _put(self, item: Any) -> bool:
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _run(self, it: Iterator) -> None:
        from jax.profiler import TraceAnnotation  # a flag check when off

        prefix = self.SPAN_PREFIX
        try:
            for raw in it:
                if self._stop.is_set():
                    return
                try:
                    with TraceAnnotation(prefix + "prepare"):
                        feed = self._prepare(raw) if self._prepare else raw
                    if self._transfer is not None:
                        with TraceAnnotation(prefix + "h2d"):
                            feed = self._transfer(feed)
                except BaseException as e:
                    # prepare/h2d failures keep their own identity — the
                    # reader did NOT raise (see PrepareError)
                    raise PrepareError(
                        f"batch prepare/transfer failed: "
                        f"{type(e).__name__}: {e}") from e
                with TraceAnnotation(prefix + "put"):
                    room = self._put(PreparedFeed(feed))
                if not room:
                    return
            self._put(self._DONE)
        except BaseException as e:  # noqa: BLE001 — delivered to consumer
            self._put(e)

    def __iter__(self) -> "BatchPrefetcher":
        return self

    def take(self) -> bool:
        """Move the queue's head into the slot if there is one and the slot
        is free; never blocks.  Whatever it is (a batch, the end marker, an
        exception) is delivered by the next ``next()``.  True where the
        slot is filled afterwards."""
        if self._slot is None:
            try:
                self._slot = self._q.get_nowait()
            except queue.Empty:
                return False
        return True

    def __next__(self) -> PreparedFeed:
        item, self._slot, when = self._slot, None, "dispatch"
        if item is None:
            item, when = self._q.get(), "late"
        if item is self._DONE:
            raise StopIteration
        if isinstance(item, BaseException):
            raise item
        self._taken[when].inc()
        return item

    def close(self) -> None:
        """Stop the producer and join it; pending prepared batches, the
        slot's among them, are dropped (the consumer's batch counter, not
        the read-ahead cursor, is the resume point —
        docs/mixed_precision.md 'feeding')."""
        self._stop.set()
        self._slot = None
        while True:  # unblock a producer stuck in put()
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=5.0)


class DataFeeder:
    """feeding: {data_layer_name: slot_index}; types: {name: kind} with kind in
    'dense' | 'int' | 'ids_seq' | 'dense_seq'."""

    def __init__(
        self,
        types: Dict[str, str],
        feeding: Optional[Dict[str, int]] = None,
        *,
        buckets: Sequence[int] = _DEFAULT_BUCKETS,
        max_len: Optional[int] = None,
        max_nnz: Optional[int] = None,
        dtype: str = "float32",
    ) -> None:
        self.types = types
        self.feeding = feeding or {name: i for i, name in enumerate(types)}
        self.buckets = tuple(buckets)
        self.max_len = max_len
        # per-timestep feature-bag cap for sparse *sequence* slots — a
        # separate axis from max_len (which caps TIMESTEPS): reusing
        # max_len as the bag cap silently truncated bags whenever
        # sequences were capped.  None = bags never truncated (the nnz
        # width just buckets up).
        self.max_nnz = max_nnz
        self.dtype = dtype
        #: running count of sparse features dropped by max_len/max_nnz
        #: truncation (logged whenever a batch drops any).  Surfaced as an
        #: observable, not just a log line: the trainer mirrors it into
        #: ``_last_extras['dropped_features']`` each batch, and a serving
        #: process that ``attach_feeder()``s reports it in ``healthz()``.
        self.dropped_features = 0
        #: cumulative real/padded token totals over every padded seq slot
        #: — behind the ``data_pad_waste`` gauge (see ``note_padding``)
        self.tokens_real = 0
        self.tokens_padded = 0

    @property
    def pad_waste(self) -> float:
        """Cumulative padded-but-dead token fraction across every padded
        sequence slot this feeder has produced."""
        if not self.tokens_padded:
            return 0.0
        return 1.0 - self.tokens_real / self.tokens_padded

    def __call__(self, batch_rows: List[Tuple]) -> Dict[str, Any]:
        feed: Dict[str, Any] = {}
        for name, kind in self.types.items():
            idx = self.feeding.get(name)
            if idx is None:
                raise ValueError(
                    f"slot {name!r} is missing from the feeding map "
                    f"{self.feeding} — every typed slot needs a field "
                    f"index")
            try:
                col = [row[idx] for row in batch_rows]
            except (IndexError, KeyError) as e:
                raise ValueError(
                    f"input rows do not carry slot {name!r} (field index "
                    f"{idx}): a row has too few fields — feeding map is "
                    f"{self.feeding}") from e
            if kind == "dense":
                feed[name] = np.asarray(col, self.dtype)
            elif kind == "int":
                arr = np.asarray(col, np.int32)
                if arr.ndim == 1:
                    arr = arr[:, None]
                feed[name] = arr
            elif kind in ("ids_seq", "dense_seq"):
                feed[name] = self._pad_seq(col, kind)
            elif kind in ("sparse_ids", "sparse_pairs"):
                feed[name] = self._pad_sparse(col, kind)
            elif kind in ("sparse_ids_seq", "sparse_pairs_seq"):
                feed[name] = self._pad_sparse_seq(col, kind)
            elif kind in ("ids_nested", "dense_nested"):
                feed[name] = self._pad_nested(col, kind)
            else:
                raise ValueError(f"unknown slot kind {kind!r} for {name!r}")
        return feed

    def _pad_nested(self, col: List, kind: str):
        """Nested sequences (rows are lists of sub-sequences; the
        subSequenceStartPositions analog, Argument.h:90) -> padded
        (value [B, To, Ti(, D)], outer_lengths [B], sub_lengths [B, To])."""
        outer = np.asarray([len(s) for s in col], np.int32)
        n_outer = max(int(outer.max()) if len(outer) else 1, 1)
        ti_max = max((len(sub) for row in col for sub in row), default=1)
        if self.max_len:  # cap BOTH levels, like the flat _pad_seq path
            n_outer = min(n_outer, self.max_len)
            ti_max = min(max(ti_max, 1), self.max_len)
            outer = np.minimum(outer, self.max_len)
        To = bucket_length(n_outer, self.buckets)
        Ti = bucket_length(max(ti_max, 1), self.buckets)
        # buckets round To/Ti UP, so slice to the max_len caps themselves —
        # data beyond the cap must not survive (mirrors _pad_seq's lengths[i])
        row_cap = min(To, self.max_len) if self.max_len else To
        ti_cap = min(Ti, self.max_len) if self.max_len else Ti
        sub_lengths = np.zeros((len(col), To), np.int32)
        if kind == "ids_nested":
            out = np.zeros((len(col), To, Ti), np.int32)
            for i, row in enumerate(col):
                for j, sub in enumerate(list(row)[:row_cap]):
                    sub = list(sub)[:ti_cap]
                    out[i, j, : len(sub)] = sub
                    sub_lengths[i, j] = len(sub)
        else:
            D = next((len(sub[0]) for row in col for sub in row if len(sub)), 1)
            out = np.zeros((len(col), To, Ti, D), self.dtype)
            for i, row in enumerate(col):
                for j, sub in enumerate(list(row)[:row_cap]):
                    sub = np.asarray(sub, self.dtype).reshape(-1, D)[:ti_cap]
                    out[i, j, : len(sub)] = sub
                    sub_lengths[i, j] = len(sub)
        return out, outer, sub_lengths

    def _pad_sparse(self, col: List, kind: str):
        """Sparse rows -> padded COO: 'sparse_ids' rows are id lists
        (sparse_binary_vector), 'sparse_pairs' rows are (id, weight) lists
        (sparse_float_vector).  Returns (ids, nnz) or (ids, weights, nnz)
        with the nnz width bucketed like sequence lengths."""
        nnz = np.asarray([len(s) for s in col], np.int32)
        N = int(nnz.max()) if len(nnz) else 1
        if self.max_len:
            N = min(max(N, 1), self.max_len)
            nnz = np.minimum(nnz, self.max_len)
        N = bucket_length(max(N, 1), self.buckets)
        ids = np.zeros((len(col), N), np.int32)
        if kind == "sparse_ids":
            for i, s in enumerate(col):
                s = list(s)[: nnz[i]]
                ids[i, : len(s)] = s
            return ids, nnz
        weights = np.zeros((len(col), N), self.dtype)
        for i, s in enumerate(col):
            s = list(s)[: nnz[i]]
            for j, (idx, w) in enumerate(s):
                ids[i, j] = idx
                weights[i, j] = w
        return ids, weights, nnz

    def _pad_sparse_seq(self, col: List, kind: str):
        """Sparse *sequence* rows (one sparse bag per timestep, the
        reference's sparse_*_vector_sequence input types) -> padded
        (ids [B,T,N], nnz [B,T], lengths [B]) for 'sparse_ids_seq', with an
        extra weights [B,T,N] slot before nnz for 'sparse_pairs_seq'.  T and
        N are bucketed like sequence lengths; the bag width N is capped by
        ``max_nnz`` (NOT ``max_len`` — that caps timesteps), and any
        features dropped by either cap are counted in
        ``self.dropped_features`` and logged."""
        lengths = np.asarray([len(s) for s in col], np.int32)
        T = max(int(lengths.max()) if len(lengths) else 1, 1)
        if self.max_len:
            T = min(T, self.max_len)
            lengths = np.minimum(lengths, self.max_len)
        # width over SURVIVING timesteps only: a wide bag in a timestep
        # max_len discards must not inflate the padded feed shape
        n_max = max((len(bag) for i, row in enumerate(col)
                     for bag in list(row)[: lengths[i]]), default=1)
        if self.max_nnz:
            n_max = min(max(n_max, 1), self.max_nnz)
        T = bucket_length(T, self.buckets)
        N = bucket_length(max(n_max, 1), self.buckets)
        # buckets round N UP; the hard bag cap stays max_nnz itself
        cap = min(N, self.max_nnz) if self.max_nnz else N
        B = len(col)
        ids = np.zeros((B, T, N), np.int32)
        nnz = np.zeros((B, T), np.int32)
        dropped = 0
        weights = (np.zeros((B, T, N), self.dtype)
                   if kind != "sparse_ids_seq" else None)
        for i, row in enumerate(col):
            row = list(row)
            for t, bag in enumerate(row):
                bag = list(bag)
                if t >= lengths[i]:  # timestep beyond the max_len cap
                    dropped += len(bag)
                    continue
                if len(bag) > cap:
                    dropped += len(bag) - cap
                    bag = bag[:cap]
                if weights is None:
                    ids[i, t, : len(bag)] = bag
                else:
                    for j, (idx, w) in enumerate(bag):
                        ids[i, t, j] = idx
                        weights[i, t, j] = w
                nnz[i, t] = len(bag)
        if dropped:
            from paddle_tpu.utils.log import logger

            self.dropped_features += dropped
            logger.warning(
                "DataFeeder: dropped %d sparse feature(s) this batch "
                "(max_len=%s, max_nnz=%s; %d dropped total)",
                dropped, self.max_len, self.max_nnz, self.dropped_features)
        if weights is None:
            return ids, nnz, lengths
        return ids, weights, nnz, lengths

    def _pad_seq(self, col: List, kind: str) -> Tuple[np.ndarray, np.ndarray]:
        lengths = np.asarray([len(s) for s in col], np.int32)
        T = int(lengths.max()) if len(lengths) else 1
        T = max(T, 1)
        if self.max_len:
            T = min(max(T, 1), self.max_len)
            lengths = np.minimum(lengths, self.max_len)
        T = bucket_length(T, self.buckets)
        self.tokens_real += int(lengths.sum())
        self.tokens_padded += len(col) * T
        note_padding(int(lengths.sum()), len(col) * T, T,
                     waste=self.pad_waste)
        if kind == "ids_seq":
            from paddle_tpu.data import native

            if native.native_available():
                # C++ pad core (csrc/dataio.cc ptd_pad_batch_i32) — the
                # feeder's per-batch Python loop is host-CPU time stolen
                # from the input pipeline
                out, _ = native.pad_batch_i32(
                    [list(s)[: lengths[i]] for i, s in enumerate(col)], T)
                return out, lengths
            out = np.zeros((len(col), T), np.int32)
            for i, s in enumerate(col):
                s = list(s)[: lengths[i]]
                out[i, : len(s)] = s
        else:
            D = len(col[0][0])
            out = np.zeros((len(col), T, D), self.dtype)
            for i, s in enumerate(col):
                s = np.asarray(s, self.dtype)[: lengths[i]]
                out[i, : len(s)] = s
        return out, lengths
