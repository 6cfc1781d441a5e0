"""Optimizers — analog of the reference's optimizer tier.

Reference surface: SGD/momentum, SparseMomentum, AdaGrad, AdaDelta, RMSProp,
DecayedAdagrad, Adam, AdaMax (paddle/parameter/FirstOrderOptimizer.h:23-331),
gradient clipping (:331), regularizers (Regularizer.h), learning-rate
schedulers (LearningRateScheduler.cpp), and parameter averaging
(AverageOptimizer.cpp).  The same update rules also exist as device tensor
expressions (paddle/math/TrainingAlgorithmOp.cu) — here each rule is a pure
jnp expression tree-mapped over the params pytree, so it jits into the fused
update kernel XLA builds anyway, on any device, and shards with the params
under pjit.

Per-parameter attributes (lr scale, L2 decay, static) come from the
Topology's ParamSpecs — the analog of ParameterConfig fields.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from paddle_tpu.utils.registry import Registry

__all__ = [
    "dedup_rows",
    "Optimizer",
    "SGD",
    "Momentum",
    "AdaGrad",
    "AdaDelta",
    "RMSProp",
    "DecayedAdaGrad",
    "Adam",
    "AdaMax",
    "OPTIMIZERS",
    "LR_SCHEDULES",
    "lr_schedule",
    "clip_by_global_norm",
    "clip_by_value",
    "ParameterAverager",
]

OPTIMIZERS: Registry = Registry("optimizer")
LR_SCHEDULES: Registry = Registry("lr_schedule")


# ---------------------------------------------------------------------------
# learning-rate schedules (LearningRateScheduler.cpp analogs)
# ---------------------------------------------------------------------------


@LR_SCHEDULES.register("constant")
def _const(base, step, **kw):
    return base


@LR_SCHEDULES.register("poly")
def _poly(base, step, *, decay_a=1e-4, decay_b=0.75, **kw):
    # base * (1 + a*step)^(-b) — the reference's default 'poly' schedule
    return base * jnp.power(1.0 + decay_a * step, -decay_b)


@LR_SCHEDULES.register("exp")
def _exp(base, step, *, decay_a=0.99, decay_b=1000.0, **kw):
    return base * jnp.power(decay_a, step / decay_b)


@LR_SCHEDULES.register("discexp")
def _discexp(base, step, *, decay_a=0.99, decay_b=1000.0, **kw):
    return base * jnp.power(decay_a, jnp.floor(step / decay_b))


@LR_SCHEDULES.register("linear")
def _linear(base, step, *, decay_a=1e-6, decay_b=1e-4, **kw):
    return jnp.maximum(base - decay_a * step, decay_b)


@LR_SCHEDULES.register("warmup_cosine")
def _warmup_cosine(base, step, *, warmup_steps=1000, total_steps=100000, **kw):
    # modern addition (not in the reference): linear warmup + cosine decay
    warm = base * step / jnp.maximum(warmup_steps, 1)
    prog = jnp.clip((step - warmup_steps) / jnp.maximum(total_steps - warmup_steps, 1), 0, 1)
    cos = base * 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
    return jnp.where(step < warmup_steps, warm, cos)


def lr_schedule(name: str, base: float, **kwargs) -> Callable:
    fn = LR_SCHEDULES.get(name)
    return lambda step: fn(base, step, **kwargs)


# ---------------------------------------------------------------------------
# gradient clipping (OptimizerWithGradientClipping analog)
# ---------------------------------------------------------------------------


def clip_by_value(grads, threshold: float):
    return jax.tree_util.tree_map(lambda g: jnp.clip(g, -threshold, threshold), grads)


def clip_by_global_norm(grads, max_norm: float, extra_sq=0.0):
    """``extra_sq`` joins additional sum-of-squares mass into the norm
    without scaling it here — the pserver trainer passes the deduped
    row-gradient mass of its routed tables so the clip decision sees the
    SAME global norm the single-host dense path would, then scales the
    row grads by the same factor itself."""
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves) + extra_sq)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gnorm, 1e-12))
    return jax.tree_util.tree_map(lambda g: g * scale, grads), gnorm


def _regularize(p, g, decay, l1):
    """decay/l1 applied to the gradient (Regularizer analog) — shared by
    the dense, masked, row-fast, and pserver sparse paths so they cannot
    drift."""
    if decay:
        g = g + decay * p
    if l1:
        g = g + l1 * jnp.sign(p)
    return g


def dedup_rows(ids, row_grads, *, sentinel):
    """Stable-sorted segment-sum of duplicate row ids.

    Returns ``(uids [N] int32, ug [N, ...])``: unique ids packed to the
    front (``sentinel`` in unused slots) with their duplicate-summed
    gradients in the matching slots (zeros elsewhere).  The accumulation
    order is the stable id sort — the SAME order as the dense path's
    sorted scatter-add — and every consumer (the sparse apply, the
    clip-norm row mass) shares THIS implementation so their sums cannot
    drift apart bit-wise."""
    n = ids.shape[0]
    ids = ids.astype(jnp.int32)
    order = jnp.argsort(ids, stable=True)
    sids = ids[order]
    sg = row_grads[order]
    first = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sids[1:] != sids[:-1]])
    seg = jnp.cumsum(first) - 1                     # segment id per position
    uids = jnp.full((n,), sentinel, jnp.int32).at[seg].set(sids)
    ug = jnp.zeros((n,) + row_grads.shape[1:], row_grads.dtype)
    ug = ug.at[seg].add(sg)                         # sorted segment-sum
    return uids, ug


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------


@dataclass
class Optimizer:
    """Base: holds learning-rate schedule + clipping + weight decay config.

    ``update(step, params, grads, opt_state, lr_scales, decays)`` is pure and
    jit/pjit-safe. lr_scales/decays are per-param-name dicts from ParamSpecs.
    """

    learning_rate: float = 0.01
    learning_rate_schedule: str = "constant"
    schedule_args: Dict[str, Any] = field(default_factory=dict)
    gradient_clipping_threshold: float = 0.0  # 0 = off; clip by global norm
    l2_rate: float = 0.0  # global L2 weight decay (Regularizer analog)
    l1_rate: float = 0.0

    def lr_at(self, step):
        fn = LR_SCHEDULES.get(self.learning_rate_schedule)
        return fn(self.learning_rate, step, **self.schedule_args)

    # per-leaf rule: override in subclasses
    def init_leaf(self, p):
        return ()

    def update_leaf(self, p, g, s, lr):
        raise NotImplementedError

    def init_state(self, params) -> Dict[str, Any]:
        """The state for ``params``, placed like them: a slot of a parameter
        that lives on a mesh (``parallel.shard_params``) is put where its
        parameter is, and the step count replicated on that mesh.  A step
        over the mesh hands the state back so placed; a state that went in
        placed otherwise is another input type to jit, and the step's
        second call would trace, lower and load it all over again."""
        on_mesh = {k: p.sharding for k, p in params.items()
                   if isinstance(p, jax.Array)
                   and not isinstance(p, jax.core.Tracer)
                   and isinstance(p.sharding, NamedSharding)}
        slots = {k: self.init_leaf(p) for k, p in params.items()}
        for k, sharding in on_mesh.items():
            slots[k] = jax.device_put(slots[k], sharding)
        step = jnp.zeros((), jnp.int32)
        if on_mesh:
            mesh = next(iter(on_mesh.values())).mesh
            step = jax.device_put(step, NamedSharding(mesh, PartitionSpec()))
        return {"step": step, "slots": slots}

    # named_scope: every step that applies an optimizer (the trainer's, the
    # demos', parallel/) shows its update under this one name on a device
    # trace (docs/observability.md "Names on the device trace")
    @jax.named_scope("optimizer_apply")
    def update(
        self,
        params: Dict[str, Any],
        grads: Dict[str, Any],
        opt_state: Dict[str, Any],
        *,
        lr_scales: Optional[Dict[str, float]] = None,
        decays: Optional[Dict[str, float]] = None,
        statics: Optional[Dict[str, bool]] = None,
        sparse_rows: Optional[Dict[str, Any]] = None,  # bool mask path or int K
        clip: bool = True,  # False: caller already applied global-norm clip
        finite: Optional[Any] = None,  # device bool; False holds everything
    ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        """``finite`` is the bad-step guard's predicate (resilience/guard.py):
        where it is False, every parameter, every slot and ``step`` come back
        bit for bit as they went in.  The hold is a select in each leaf's own
        update and takes the form that leaf's path already holds rows by: a
        dense leaf ``where(finite, new, old)`` in the expression of
        ``update_leaf`` (one fused pass, which reads the old values anyway),
        the row-sparse paths ``finite`` joined to ``touched``, so that a
        ``K``-row leaf is never selected over at table size.  No leaf crosses
        a conditional for it: a conditional takes its operands in the default
        layout, and the chip keeps some leaves otherwise (PERF.md, PR 48).  A
        held step pays a whole update's time.  ``None`` (no guard) adds
        nothing to the program.

        ``sparse_rows`` marks row-sparse parameters (embedding tables with
        ParamAttr(sparse_grad=True)): rows a batch never touched keep their
        value AND optimizer slots unchanged — the reference's sparse-row
        update semantics (SparseRowCpuMatrix / SparseMomentum,
        paddle/math/SparseRowMatrix.h, FirstOrderOptimizer.h:52), where
        momentum decay and regularization do not advance untouched rows.

        Two implementations, chosen per-parameter by the dict value:

        - ``True`` — per-row touched mask over the dense scatter-add
          gradient (jnp.where); correct for any touched count but still
          reads/writes the FULL table and slots every step.
        - an int ``K`` — gather-update-scatter fast path: top_k selects up
          to K touched row indices, only those rows of the parameter and
          its slots are gathered, updated, and scattered back in place
          (donated buffers make this a true O(K·D) row update instead of
          O(V·D) — the SparseRowCpuMatrix locality argument, on HBM
          bandwidth instead of CPU cache).  ``K`` is a fast-path capacity:
          size it to the typical touched-row count (e.g. batch·seq_len per
          lookup of the table).  A batch touching MORE than K rows is
          still correct — a cond falls back to the full masked update for
          that step (paying the O(V·D) cost only when it happens).
        """
        step = opt_state["step"] + 1
        lr = self.lr_at(step)
        if self.gradient_clipping_threshold > 0 and clip:
            grads, _ = clip_by_global_norm(grads, self.gradient_clipping_threshold)

        def hold(new, old):
            if finite is None:
                return new
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(finite, n, o), new, old)

        def touched_rows(g):
            touched = jnp.any(g != 0, axis=tuple(range(1, g.ndim)))
            # a held step touches no row: the K path then takes its fast
            # branch with nothing live, not the full-table fallback
            return touched if finite is None else touched & finite

        def _masked_update(p, g, old_slots, touched, lr_eff):
            """Full-tensor update with untouched rows held — the ONE masked
            path shared by sparse_rows=True and the K fast path's overflow
            fallback (they must stay identical)."""
            p2, s2 = self.update_leaf(
                p, _regularize(p, g, decay, self.l1_rate), old_slots,
                lr_eff, step)
            row = touched.reshape((-1,) + (1,) * (p.ndim - 1))

            def sel(new, old):
                r = row.astype(jnp.bool_)
                r = r.reshape(r.shape + (1,) * (new.ndim - r.ndim))
                return jnp.where(r, new, old)

            p2 = sel(p2, p)
            s2 = jax.tree_util.tree_map(
                lambda n, o: sel(n, o)
                if getattr(n, "shape", None) == p.shape else hold(n, o),
                s2, old_slots)
            return p2.astype(p.dtype), s2

        new_params, new_slots = {}, {}
        for k, p in params.items():
            g = grads[k]
            if statics and statics.get(k):
                new_params[k], new_slots[k] = p, opt_state["slots"][k]
                continue
            decay = (decays.get(k, 0.0) if decays else 0.0) + self.l2_rate
            scale = lr_scales.get(k, 1.0) if lr_scales else 1.0
            old_slots = opt_state["slots"][k]
            kind = sparse_rows.get(k) if sparse_rows else None
            if (kind is not None and kind is not True and kind is not False
                    and isinstance(kind, int) and p.ndim >= 2
                    and 0 < kind < p.shape[0]):
                # ---- row fast path: touch only K candidate rows ----
                K = int(kind)
                touched = touched_rows(g)

                def _fast(_, p=p, g=g, touched=touched, K=K,
                          old_slots=old_slots, scale=scale, decay=decay):
                    live_score, rows = jax.lax.top_k(
                        touched.astype(jnp.float32), K)
                    # top_k indices are distinct -> unique scatter
                    return self.row_apply(
                        p, rows, g[rows], old_slots, live_score > 0,
                        lr * scale, step, decay=decay)

                def _overflow(_, p=p, g=g, touched=touched,
                              old_slots=old_slots, scale=scale):
                    return _masked_update(p, g, old_slots, touched, lr * scale)

                # a batch touching more than K rows would silently drop
                # gradient rows in the fast path; guard with a cond so only
                # the chosen branch executes at runtime
                n_touched = jnp.sum(touched.astype(jnp.int32))
                new_params[k], new_slots[k] = jax.lax.cond(
                    n_touched <= K, _fast, _overflow, None)
                continue
            if kind and p.ndim >= 2:  # sparse_rows=True: masked path
                new_params[k], new_slots[k] = _masked_update(
                    p, g, old_slots, touched_rows(g), lr * scale)
                continue
            p2, s2 = self.update_leaf(
                p, _regularize(p, g, decay, self.l1_rate), old_slots,
                lr * scale, step)
            new_params[k] = hold(p2.astype(p.dtype), p)
            new_slots[k] = hold(s2, old_slots)
        return new_params, {"step": hold(step, opt_state["step"]),
                            "slots": new_slots}

    def row_apply(self, p, rows, g_rows, old_slots, live, lr_eff, step, *,
                  decay: float = 0.0, oob_drop: bool = False):
        """THE shared gather-update-scatter row kernel: update ``rows`` of
        ``p`` and its row-shaped slots in place with already-gathered row
        gradients ``g_rows``; entries with ``live=False`` keep their value
        AND slots (lazy regularization — untouched rows never advance).

        ``rows`` must be distinct among live entries (callers: ``top_k``
        indices, or the deduped unique-id buffer of ``sparse_apply_rows``).
        ``oob_drop=True`` additionally drops out-of-range rows (the sparse
        apply parks dead entries past the end) and fill-gathers so no
        clamped garbage feeds ``update_leaf``.  O(K·D) reads/writes — the
        SparseRowCpuMatrix locality argument on HBM bandwidth.
        """
        kw = dict(unique_indices=True)
        if oob_drop:
            kw["mode"] = "drop"

            def gather(a):
                return a.at[rows].get(mode="fill", fill_value=0)
        else:
            def gather(a):
                return a[rows]

        live_col = live.reshape((-1,) + (1,) * (p.ndim - 1))
        p_r = gather(p)
        g_r = _regularize(p_r, g_rows, decay, self.l1_rate)
        s_r = jax.tree_util.tree_map(
            lambda s: gather(s)
            if getattr(s, "shape", None) == p.shape else s,
            old_slots)
        p2_r, s2_r = self.update_leaf(p_r, g_r, s_r, lr_eff, step)
        p2_r = jnp.where(live_col, p2_r, p_r)
        np_ = p.at[rows].set(p2_r.astype(p.dtype), **kw)
        ns_ = jax.tree_util.tree_map(
            lambda o, n2: o.at[rows].set(
                jnp.where(live_col, n2, gather(o)), **kw)
            if getattr(o, "shape", None) == p.shape else n2,
            old_slots, s2_r)
        return np_, ns_

    def sparse_apply_rows(self, p, ids, row_grads, old_slots, *, lr_eff,
                          step, decay: float = 0.0):
        """Row-sparse apply from (ids, row-grads) segments — the pserver
        gradient push (SparseRemoteParameterUpdater analog), and the sparse
        half of the contract ``lint --pserver`` gates: nothing here is
        [V, ...]-shaped except ``p`` and its slots themselves.

        Duplicates are segment-summed in stable id-sorted order — the SAME
        accumulation order as the sorted scatter-add in ops/embedding's
        backward — so the result is bit-identical to the dense masked path
        (``sparse_rows=True``) on the equivalent dense gradient.  Sentinel
        ids ``>= p.shape[0]`` (all-to-all padding) and zero-grad segments
        (masked/pad positions) are dropped: those rows and their slots do
        not advance.
        """
        v = p.shape[0]
        n = ids.shape[0]
        uids, ug = dedup_rows(ids, row_grads, sentinel=v)
        live = (uids < v) & jnp.any(
            ug != 0, axis=tuple(range(1, ug.ndim)))
        # dead entries park at distinct out-of-range rows: the scatter
        # drops them while the unique_indices claim stays honest
        rows = jnp.where(live, uids, v + jnp.arange(n, dtype=jnp.int32))
        return self.row_apply(p, rows, ug, old_slots, live, lr_eff, step,
                              decay=decay, oob_drop=True)


@OPTIMIZERS.register("sgd")
@dataclass
class SGD(Optimizer):
    """Plain SGD (SgdOptimizer, FirstOrderOptimizer.h:23)."""

    def update_leaf(self, p, g, s, lr, step):
        return p - lr * g, s


@OPTIMIZERS.register("momentum")
@dataclass
class Momentum(Optimizer):
    """Heavy-ball momentum (the reference folds momentum into SGD via
    ParameterConfig::momentum)."""

    momentum: float = 0.9
    use_nesterov: bool = False

    def init_leaf(self, p):
        return jnp.zeros_like(p)

    def update_leaf(self, p, g, v, lr, step):
        v2 = self.momentum * v - lr * g
        if self.use_nesterov:
            return p + self.momentum * v2 - lr * g, v2
        return p + v2, v2


@OPTIMIZERS.register("adagrad")
@dataclass
class AdaGrad(Optimizer):
    """AdaGrad (AdagradParameterOptimizer, FirstOrderOptimizer.h:100;
    math/TrainingAlgorithmOp.cu adagradApply)."""

    epsilon: float = 1e-6

    def init_leaf(self, p):
        return jnp.zeros_like(p)

    def update_leaf(self, p, g, acc, lr, step):
        acc2 = acc + jnp.square(g)
        return p - lr * g / (jnp.sqrt(acc2) + self.epsilon), acc2


@OPTIMIZERS.register("adadelta")
@dataclass
class AdaDelta(Optimizer):
    """AdaDelta (AdaDeltaParameterOptimizer, FirstOrderOptimizer.h:130)."""

    rho: float = 0.95
    epsilon: float = 1e-6

    def init_leaf(self, p):
        return (jnp.zeros_like(p), jnp.zeros_like(p))  # E[g^2], E[dx^2]

    def update_leaf(self, p, g, s, lr, step):
        eg, ed = s
        eg2 = self.rho * eg + (1 - self.rho) * jnp.square(g)
        dx = -jnp.sqrt((ed + self.epsilon) / (eg2 + self.epsilon)) * g
        ed2 = self.rho * ed + (1 - self.rho) * jnp.square(dx)
        return p + lr * dx, (eg2, ed2)


@OPTIMIZERS.register("rmsprop")
@dataclass
class RMSProp(Optimizer):
    """RMSProp with mean-centering (RMSPropParameterOptimizer,
    FirstOrderOptimizer.h:156 — tracks E[g^2] and E[g])."""

    rho: float = 0.95
    epsilon: float = 1e-6

    def init_leaf(self, p):
        return (jnp.zeros_like(p), jnp.zeros_like(p))  # E[g^2], E[g]

    def update_leaf(self, p, g, s, lr, step):
        eg2, eg = s
        eg2n = self.rho * eg2 + (1 - self.rho) * jnp.square(g)
        egn = self.rho * eg + (1 - self.rho) * g
        denom = jnp.sqrt(eg2n - jnp.square(egn) + self.epsilon)
        return p - lr * g / denom, (eg2n, egn)


@OPTIMIZERS.register("decayed_adagrad")
@dataclass
class DecayedAdaGrad(Optimizer):
    """Decayed AdaGrad (DecayedAdagradParameterOptimizer,
    FirstOrderOptimizer.h:199)."""

    rho: float = 0.95
    epsilon: float = 1e-6

    def init_leaf(self, p):
        return jnp.zeros_like(p)

    def update_leaf(self, p, g, acc, lr, step):
        acc2 = self.rho * acc + (1 - self.rho) * jnp.square(g)
        return p - lr * g / (jnp.sqrt(acc2) + self.epsilon), acc2


@OPTIMIZERS.register("adam")
@dataclass
class Adam(Optimizer):
    """Adam (AdamParameterOptimizer, FirstOrderOptimizer.h:244;
    TrainingAlgorithmOp.cu adamApply) with bias correction.

    ``slot_dtype`` (e.g. "bfloat16") stores the m/v moment slots at reduced
    width — the optimizer update is pure HBM bandwidth (7 full-width tensor
    streams per step), so half-width slots cut ~2/7 of it.  Moments are
    widened to f32 for the arithmetic each step; None (default) keeps
    full-width slots and the exact reference numerics."""

    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-8
    slot_dtype: Optional[str] = None

    def init_leaf(self, p):
        dt = jnp.dtype(self.slot_dtype) if self.slot_dtype else p.dtype
        return (jnp.zeros(p.shape, dt), jnp.zeros(p.shape, dt))

    def update_leaf(self, p, g, s, lr, step):
        m, v = s
        f32 = jnp.float32
        m2 = self.beta1 * m.astype(f32) + (1 - self.beta1) * g
        v2 = self.beta2 * v.astype(f32) + (1 - self.beta2) * jnp.square(g)
        t = step.astype(jnp.float32)
        mhat = m2 / (1 - jnp.power(self.beta1, t))
        vhat = v2 / (1 - jnp.power(self.beta2, t))
        return (p - lr * mhat / (jnp.sqrt(vhat) + self.epsilon),
                (m2.astype(m.dtype), v2.astype(v.dtype)))


@OPTIMIZERS.register("adamax")
@dataclass
class AdaMax(Optimizer):
    """AdaMax (AdamaxParameterOptimizer, FirstOrderOptimizer.h:275)."""

    beta1: float = 0.9
    beta2: float = 0.999

    def init_leaf(self, p):
        return (jnp.zeros_like(p), jnp.zeros_like(p))

    def update_leaf(self, p, g, s, lr, step):
        m, u = s
        m2 = self.beta1 * m + (1 - self.beta1) * g
        u2 = jnp.maximum(self.beta2 * u, jnp.abs(g))
        t = step.astype(jnp.float32)
        return p - lr / (1 - jnp.power(self.beta1, t)) * m2 / (u2 + 1e-12), (m2, u2)


# ---------------------------------------------------------------------------
# parameter averaging (AverageOptimizer analog)
# ---------------------------------------------------------------------------


@dataclass
class ParameterAverager:
    """Maintains an EMA of parameters for evaluation — analog of the
    reference's AverageOptimizer / SgdUpdaterWithCpuAverager
    (paddle/parameter/AverageOptimizer.cpp)."""

    average_window: float = 0.999

    def init_state(self, params):
        return jax.tree_util.tree_map(lambda p: p, params)

    def update(self, avg, params):
        w = self.average_window
        return jax.tree_util.tree_map(lambda a, p: w * a + (1 - w) * p, avg, params)
