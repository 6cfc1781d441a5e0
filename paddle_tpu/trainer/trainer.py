"""The training driver — analog of the reference's trainer tier.

Reference: the v2 SGD trainer drives GradientMachine.forwardBackward +
ParameterUpdater per batch from a Python loop
(python/paddle/v2/trainer.py:30-175), over the C++ Trainer/TrainerInternal
machinery (paddle/trainer/Trainer.cpp:261-576, TrainerInternal.cpp:66-172).

TPU-native: the whole batch step — forward, backward (autodiff), optimizer
update — is ONE jitted pure function; parameters, optimizer slots and BN state
are donated so updates are in-place in HBM.  Data parallelism is not a
separate "MultiGradientMachine": pass a ``Mesh`` and the same step function
runs SPMD with the batch sharded over the 'data' axis — XLA inserts the ICI
all-reduce for gradients (replacing both the reference's per-GPU TrainerThread
ring and the pserver tier; SURVEY.md §5.8).
"""

from __future__ import annotations

import json
import os
import time
from contextlib import ExitStack, contextmanager, nullcontext
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.data.feeder import PreparedFeed, PrepareError
from paddle_tpu.nn.graph import LayerOutput, Topology
from paddle_tpu.obs.timeline import (close_setup, in_setup_phase, setup_phase,
                                     setup_record)
from paddle_tpu.param.optimizers import Optimizer, ParameterAverager, SGD
from paddle_tpu.resilience import (DCNPartitioned, GangResized,
                                   PreemptionHandler, ReaderError,
                                   TooManyBadSteps, guarded_update,
                                   init_loss_scale, scaled_guarded_update)
from paddle_tpu.resilience.checkpoint_io import (latest_pass, load_checkpoint,
                                                 read_manifest, pass_dir,
                                                 save_checkpoint)
from paddle_tpu.resilience.cluster import current_gang
from paddle_tpu.trainer import events as ev
from paddle_tpu.utils import FLAGS, logger

__all__ = ["SGDTrainer"]

#: every host span of the loop carries this prefix on a profiler trace
#: (docs/observability.md "Names on the device trace")
SPAN_PREFIX = "paddle_tpu.trainer."


def _span(name: str, **stats):
    """A span that is on the profiler's trace ONLY: a part of a phase or of
    ``iteration``'s own time, named so that a device-idle instant has an
    owner (not a ``StepTimeline`` phase and not a child of the request
    tracer: those are ``SGDTrainer._ph``'s).  Outside a profiler session
    it is a flag check.  ``step.sync`` carries ``reason``: one span per
    blocking fetch from the device inside ``step``, so their count per
    ``iteration`` is the count of host syncs a step pays."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **stats)


#: the loop's phases in the set-up record (obs/timeline.py), while the first
#: iteration of the process's first pass runs: everything up to the first
#: feed on the device is ``data``, the first call of the step and the fetch
#: that ends it ``first_step``; a phase not named here keeps its name
_SETUP_PHASE_OF = {"data_wait": "data", "prepare": "data", "h2d": "data",
                   "step": "first_step"}


#: consecutive SDC rollbacks a survivor tolerates before declaring the
#: divergence persistent (a flaky host the vote cannot pin down) and
#: aborting with the typed error instead of looping forever
_SDC_MAX_ROLLBACKS = 4


class _SdcRollback(Exception):
    """Control flow, not a failure: the cross-replica vote found no
    strict majority, this survivor restored the last verified checkpoint
    (resilience/integrity.py), and the pass loop must re-enter at the
    restored position.  ``cursor_ready`` marks a data source already
    positioned (an elastic reshard mid-check) — no cursor restore or
    fast-forward needed."""

    def __init__(self, start_pass: int, start_batch: int, *,
                 cursor_ready: bool = False) -> None:
        super().__init__(f"sdc rollback to pass {start_pass} "
                         f"batch {start_batch}")
        self.start_pass = int(start_pass)
        self.start_batch = int(start_batch)
        self.cursor_ready = bool(cursor_ready)


class _PassSchedule:
    """Iterator over pass ids that an SDC rollback can REWIND: the pass
    loop runs ``for pass_id in schedule`` and a rollback sets the next
    yielded pass back to the restored checkpoint's — the loop body stays
    exactly the straight-line resume machinery it already was."""

    def __init__(self, start: int, stop: int) -> None:
        self.next_pass = int(start)
        self.stop = int(stop)

    def __iter__(self):
        return self

    def __next__(self) -> int:
        if self.next_pass >= self.stop:
            raise StopIteration
        p = self.next_pass
        self.next_pass += 1
        return p

    def rewind(self, pass_id: int) -> None:
        self.next_pass = int(pass_id)


class SGDTrainer:
    """v2-style trainer: ``SGDTrainer(cost=..., optimizer=...)``, then
    ``.train(reader, num_passes, event_handler, feeder)``."""

    @in_setup_phase("trainer_build")
    def __init__(
        self,
        cost,
        optimizer: Optional[Optimizer] = None,
        *,
        extra_outputs: Sequence[LayerOutput] = (),
        cost_weights: Optional[Sequence[float]] = None,
        mesh=None,
        data_axis: str = "data",
        seed: Optional[int] = None,
        averager: Optional[ParameterAverager] = None,
        device_specs: Optional[Dict[str, Any]] = None,
        sharding_rules=None,
        pipeline: Optional[Dict[str, Any]] = None,
        guard_nonfinite: Optional[bool] = None,
        max_bad_steps: Optional[int] = None,
        amp: Optional[bool] = None,
        remat: Optional[bool] = None,
    ) -> None:
        # several costs train jointly (MultiNetwork analog,
        # gserver/gradientmachines/MultiNetwork.h:24): total loss is the
        # (weighted) sum, parameters shared by name across sub-networks
        from paddle_tpu.parallel.mesh import MeshConfig, as_mesh

        # ONE world description: a parallel.MeshConfig is accepted wherever
        # a built Mesh is; keeping the config around is what makes elastic
        # resize possible (re-instantiate the config at the new world size
        # and re-place — _mesh_resize)
        self.mesh_config = mesh if isinstance(mesh, MeshConfig) else None
        mesh = as_mesh(mesh)
        if self.mesh_config is not None and data_axis == "data":
            data_axis = self.mesh_config.data_axis

        costs = [cost] if isinstance(cost, LayerOutput) else list(cost)
        self.cost_names = [c.name for c in costs]
        self.cost_weights = list(cost_weights) if cost_weights else [1.0] * len(costs)
        if len(self.cost_weights) != len(costs):
            raise ValueError("cost_weights must match the number of costs")
        self.cost_name = costs[0].name
        self.extra_names = [e.name for e in extra_outputs]
        # extra outputs a model marked for a counter (``meta["obs_counter"]``:
        # name, labels, and for a vector the label its index goes under):
        # the loop adds each step's value to the registry after its loss
        # fetch, when the step's work is done (_feed_counters)
        self._counter_feeds = [(e.name, e.meta["obs_counter"])
                               for e in extra_outputs
                               if "obs_counter" in e.meta]
        self._counter_children: Dict[str, list] = {}
        if pipeline is not None:
            # pp:<k> device_pin tags become GPipe stages over
            # mesh[pipeline['stage_axis']] (parallel/pipeline_dsl.py);
            # pipeline = dict(n_microbatches=..., stage_axis=..., data_axis=...)
            from paddle_tpu.parallel.pipeline_dsl import PipelinedTopology

            if mesh is None:
                raise ValueError("pipeline training requires a mesh")
            with setup_phase("topology"):
                self.topology = PipelinedTopology([*costs, *extra_outputs],
                                                  mesh=mesh, **pipeline)
        else:
            with setup_phase("topology"):
                self.topology = Topology([*costs, *extra_outputs])
        self.optimizer = optimizer or SGD(learning_rate=0.01)
        self.mesh = mesh
        self.data_axis = data_axis
        self.averager = averager
        self.device_specs = device_specs
        # parameter-placement plane: a parallel.ShardingRules mapping param
        # name globs to PartitionSpecs (tensor parallelism through the same
        # trainer — the ParallelNeuralNetwork analog for weights, see
        # paddle_tpu/parallel/sharding.py); None = replicate
        self.sharding_rules = sharding_rules
        if sharding_rules is not None and mesh is None:
            raise ValueError("sharding_rules requires a mesh")

        seed = FLAGS.seed if seed is None else seed
        self._rng = jax.random.PRNGKey(seed)
        self._rng, init_key = jax.random.split(self._rng)

        # per-parameter attrs from specs (ParameterConfig analog) — read
        # BEFORE init: pserver routing must be decided while no table has
        # been materialized yet
        self.lr_scales = {}
        self.decays = {}
        self.statics = {}
        self.sparse_rows = {}
        pruning_ratios = {}
        for name, spec in self.topology.param_specs.items():
            if spec.is_state:
                continue
            if spec.attr.learning_rate != 1.0:
                self.lr_scales[name] = spec.attr.learning_rate
            if spec.attr.l2_decay:
                self.decays[name] = spec.attr.l2_decay
            if spec.attr.is_static:
                self.statics[name] = True
            if spec.attr.sparse_grad:
                self.sparse_rows[name] = True
            if spec.attr.pruning_ratio:
                pruning_ratios[name] = spec.attr.pruning_ratio
        self.pruning_ratios = pruning_ratios

        # pserver tier (paddle_tpu/pserver): with a mesh carrying the
        # pserver axis, every sparse_grad table leaves the dense params
        # pytree and lives mesh-sharded — created shard-locally and
        # excluded from Topology.init, so a 100M-row table never exists
        # dense on one host (docs/pserver.md)
        self.pserver = None
        routed = set()
        ps_axis = (self.mesh_config.role_axis("pserver")
                   if self.mesh_config is not None else FLAGS.pserver_axis)
        if (mesh is not None and self.sparse_rows
                and ps_axis in mesh.axis_names):
            from paddle_tpu.pserver import PServerTier

            tier = PServerTier(mesh, self.topology, self.optimizer,
                               axis=ps_axis,
                               lr_scales=self.lr_scales, decays=self.decays,
                               seed=seed)
            if tier.active:
                self.pserver = tier
                routed = tier.param_names()
                for name in routed:
                    self.sparse_rows.pop(name, None)
                    self.lr_scales.pop(name, None)
                    self.decays.pop(name, None)

        # StaticPruningHook analog: masks fixed from initial magnitudes,
        # re-applied after every update inside the jitted step
        from paddle_tpu.param.hooks import apply_masks, build_masks

        with setup_phase("params"):
            self.params, self.state = self.topology.init(init_key,
                                                         skip=routed)
            self.masks = build_masks(self.params, self.pruning_ratios)
            self.params = apply_masks(self.params, self.masks)

        # mixed precision (--amp; docs/mixed_precision.md): forward and
        # backward run in bf16 end-to-end (ops/numerics dtype policy reads
        # the flag at trace time), while self.params — the MASTERS — stay
        # f32; the dynamic loss-scale state lives inside opt_state so it
        # is donated with the slots and checkpointed with them
        if amp is not None and bool(amp) != bool(FLAGS.amp):
            # the bf16 dtype policy (ops/numerics) reads FLAGS.amp at
            # trace time; a constructor override that disagrees would run
            # loss scaling without bf16 (no speedup) or bf16 without the
            # overflow machinery (spurious TooManyBadSteps) — refuse the
            # split-brain instead of training wrong
            raise ValueError(
                f"SGDTrainer(amp={amp!r}) disagrees with FLAGS.amp="
                f"{FLAGS.amp!r}: the compute dtype policy is flag-driven, "
                f"set FLAGS.amp (or --amp) to toggle mixed precision")
        self.amp = bool(FLAGS.amp if amp is None else amp)
        self.remat = bool(FLAGS.remat if remat is None else remat)
        self.amp_overflows_total = 0
        with setup_phase("opt_state"):
            self.opt_state = self.optimizer.init_state(self.params)
            if self.amp:
                self.opt_state["amp"] = init_loss_scale(FLAGS.loss_scale)
            self.avg_params = (self.averager.init_state(self.params)
                               if self.averager else None)
        if self.mesh is not None:
            with setup_phase("params"):   # their placement over the mesh
                self._place_sharded()
        # bad-step guard (resilience/guard.py): skip non-finite updates
        # inside the jitted step; counters live host-side on the trainer
        self.guard_nonfinite = (FLAGS.guard_nonfinite if guard_nonfinite is None
                                else bool(guard_nonfinite))
        self.max_bad_steps = (FLAGS.max_bad_steps if max_bad_steps is None
                              else int(max_bad_steps))
        self.bad_steps_total = 0
        self._bad_streak = 0
        # gang context (resilience/cluster.py) — bound per train() call
        self._gang = None
        # elastic-resize observability (mirrored into _last_extras and,
        # for supervised serving replicas, healthz())
        self._resize_count = 0
        self._last_resize_reason: Optional[str] = None
        # silent-data-corruption firewall (resilience/integrity.py;
        # docs/resilience.md "Silent corruption"): the cadence is latched
        # at construction because the step closure bakes the in-jit
        # fingerprint in (0 = the step compiles with no trace of it,
        # pinned by `lint --sdc`)
        self.sdc_check_every = int(FLAGS.sdc_check_every)
        self.sdc_mismatches_total = 0
        self._sdc_rollbacks = 0
        self._sdc_hold_epoch: Optional[int] = None
        self._sdc_last_agreed: Optional[tuple] = None
        # fingerprints the replicas AGREED on, newest last (bounded):
        # rollback prefers a checkpoint whose manifest fp is in here — a
        # checkpoint saved from already-corrupt state (flip before save,
        # detection after) carries a never-agreed fp and is skipped, so
        # the corruption cannot launder itself through the rollback
        from collections import deque

        self._sdc_agreed_fps: "deque[int]" = deque(maxlen=256)
        self._last_extras: Dict[str, Any] = {}
        # unified telemetry (paddle_tpu/obs; docs/observability.md):
        # the step timeline + event journal + profiler windows are bound
        # per train() call; the registry handles live for the whole
        # trainer so train_batch() outside train() still counts
        from paddle_tpu.obs import get_registry

        reg = get_registry()
        self._obs_gauges = {
            "cost": reg.gauge("train_last_cost", "cost of the last step"),
            "world": reg.gauge("train_world_size", "live gang world size"),
        }
        self._obs_counters = {
            "batches": reg.counter("train_batches_total",
                                   "optimizer steps taken"),
            "bad_steps": reg.counter("train_bad_steps_total",
                                     "guard-skipped non-finite steps"),
            "checkpoints": reg.counter("train_checkpoints_total",
                                       "checkpoint commits published"),
            "resizes": reg.counter("train_resizes_total",
                                   "elastic resizes adopted"),
        }
        self.timeline = None
        self._journal = None
        self._profiler = None
        self._prefetcher = None
        # checkpointable data source (paddle_tpu/datapipe; docs/data.md):
        # bound per train() call when the reader carries the cursor
        # protocol — its cursor rides checkpoint manifests so resume
        # restores it instead of replaying the pass
        self._data_source = None
        self._pending_cursor = None
        self._source_resharded = False
        #: batches re-read-and-discarded by the fast-forward fallback —
        #: ZERO whenever the source is a datapipe iterator (pinned by
        #: tests/test_datapipe.py)
        self.resume_replayed_batches = 0
        # request-level tracing (obs/trace.py): each batch becomes a
        # step-span trace with the timeline phases as children; bound per
        # train() call like the journal
        self._tracer = None
        self._step_span = None
        with setup_phase("step_build"):
            self._step = self._build_step()
        self._eval_fns: Dict[str, Callable] = {}

    # ------------------------------------------------------------------

    def _build_step(self):
        from paddle_tpu.param.hooks import apply_masks

        topo = self.topology
        cost_names = list(self.cost_names)
        cost_weights = list(self.cost_weights)
        extra_names = list(self.extra_names)
        opt = self.optimizer
        lr_scales, decays, statics = self.lr_scales, self.decays, self.statics
        sparse_rows, masks = self.sparse_rows, self.masks

        device_specs = self.device_specs
        guard = self.guard_nonfinite
        tier = self.pserver
        amp = self.amp
        remat = self.remat
        growth_interval = int(FLAGS.loss_scale_growth)
        max_scale = float(FLAGS.loss_scale_max)
        # SDC firewall: fold the post-update params + optimizer slots
        # (+ pserver tables) into one u64 fingerprint INSIDE the compiled
        # step — the state never crosses the host link, only its 8-byte
        # digest does, at the check cadence (resilience/integrity.py)
        sdc_fp_on = self.sdc_check_every > 0
        if sdc_fp_on:
            from paddle_tpu.resilience.integrity import tree_fingerprint
        else:
            tree_fingerprint = None

        def step(params, state, opt_state, ps, rng, feed):
            # ``ps`` is the pserver tier's pytree (tables/slots/dirty/step;
            # {} without a tier).  Tables enter the step OUTSIDE the
            # differentiated arguments; each routed lookup adds a zeros
            # proxy, and grads w.r.t. the proxies ARE the (ids, row-grads)
            # segments the sparse apply pushes — no [V, D] cotangent ever
            # exists (pserver/tier.py, gated by `lint --pserver`).
            proxies = tier.make_proxies(feed) if tier is not None else {}
            # --amp: the loss-scale state rides INSIDE opt_state (donated,
            # checkpointed); split it out so the optimizer sees only its
            # own keys; the scale's state machine runs beside the update
            # and advances on a skipped step too
            amp_state = opt_state.get("amp") if amp else None
            opt_core = {k: v for k, v in opt_state.items() if k != "amp"}

            def loss_fn(p, px):
                # named_scope: the backward ops XLA derives from this
                # trace inherit "transpose(jvp(forward))" provenance, so a
                # profiler capture (obs/profiler.py) reads as forward /
                # backward / optimizer_apply (the scope Optimizer.update
                # opens), each layer under its own name inside (nn/graph.py)
                with jax.named_scope("forward"):
                    overrides = (tier.make_overrides(ps["tables"], px)
                                 if tier is not None else None)
                    outs, new_state = topo.apply(
                        p, state, feed, train=True, rng=rng,
                        device_specs=device_specs,
                        param_overrides=overrides,
                        # --remat: every layer outside a recomputation
                        # block (nn.remat_block) becomes a block of its
                        # own, so the backward holds each layer's inputs
                        # and recomputes the rest: O(layers) activations
                        # for about a third more FLOPs
                        remat_layers=remat,
                    )
                    extras = {k: outs[k].value for k in extra_names}
                    total = sum(
                        w * outs[n].value
                        for n, w in zip(cost_names, cost_weights)
                    )
                # dynamic loss scaling: the DIFFERENTIATED value is
                # scale * loss so bf16 gradients use the representable
                # range; the reported loss (aux) stays unscaled
                scaled = total * amp_state["scale"] if amp else total
                return scaled, (total, new_state, extras)

            (_, (loss, new_state, extras)), (grads, px_grads) = (
                jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)(
                    params, proxies))

            def do_update(pack, gpack, o, finite=None):
                # ``finite`` (the guard's predicate, a device bool) holds a
                # bad step where each leaf is updated: the optimizer by a
                # select per leaf, the tier by dropping the step's rows
                p, ps_in = pack
                g, pxg = gpack
                clip = True
                if tier is not None and opt.gradient_clipping_threshold > 0:
                    # clipping parity with single-host training: the clip
                    # norm must include the routed tables' (deduped) row
                    # gradients, and the SAME scale must hit both trees
                    from paddle_tpu.param.optimizers import \
                        clip_by_global_norm

                    thr = opt.gradient_clipping_threshold
                    g, gnorm = clip_by_global_norm(
                        g, thr, extra_sq=tier.grad_norm_sq(feed, pxg))
                    scale = jnp.minimum(
                        1.0, thr / jnp.maximum(gnorm, 1e-12))
                    pxg = jax.tree_util.tree_map(lambda x: x * scale, pxg)
                    clip = False
                np_, no_ = opt.update(
                    p, g, o,
                    lr_scales=lr_scales, decays=decays, statics=statics,
                    sparse_rows=sparse_rows, clip=clip, finite=finite,
                )
                ps_out = (tier.apply_grads(ps_in, feed, pxg, finite=finite)
                          if tier is not None else ps_in)
                # a held leaf went in masked (__init__, rebuild_masks), and
                # a 0/1 mask over a masked leaf gives the same bits
                return (apply_masks(np_, masks), ps_out), no_

            if amp:
                # loss scaling REQUIRES the skip machinery: an overflow is
                # a normal rescale event, so the guard is always on under
                # --amp (scale halves + step skips; the scale advances
                # even on a skip)
                ((new_params, new_ps), new_opt, new_state, new_amp,
                 gextras) = scaled_guarded_update(
                    do_update, loss=loss, scaled_grads=(grads, px_grads),
                    amp_state=amp_state, params=(params, ps),
                    opt_state=opt_core, new_state=new_state,
                    old_state=state, growth_interval=growth_interval,
                    max_scale=max_scale)
                extras = {**extras, **gextras}
                new_opt = {**new_opt, "amp": new_amp}
            elif guard:
                # finite checks on loss + grad global-norm (row grads
                # included), a bad step held by a select in each leaf's
                # own update — on-device, no host round-trip (gated by
                # the audit in tests/test_resilience.py); a skip holds
                # pserver tables, slots, and dirty masks too
                (new_params, new_ps), new_opt, new_state, gextras = (
                    guarded_update(
                        do_update, loss=loss, grads=(grads, px_grads),
                        params=(params, ps), opt_state=opt_core,
                        new_state=new_state, old_state=state))
                extras = {**extras, **gextras}
            else:
                (new_params, new_ps), new_opt = do_update(
                    (params, ps), (grads, px_grads), opt_core)
            if sdc_fp_on:
                fp_tree = {"params": new_params, "opt": new_opt}
                if tier is not None:
                    fp_tree["pserver"] = new_ps
                extras = {**extras, "sdc_fp": tree_fingerprint(fp_tree)}
            return loss, new_params, new_state, new_opt, new_ps, extras

        # kept un-jitted for the lint auditor (audit() re-traces it)
        self._step_fn = step

        def step_and_split(params, state, opt_state, ps, rng, feed):
            # what jit compiles: the trainer's key is split INSIDE the
            # program (the same threefry split, so the same keys bit for
            # bit as a split on the host before every step) and the half
            # the step does not use comes back as the next ``self._rng``:
            # one executable a step, not two
            rng, key = jax.random.split(rng)
            return step(params, state, opt_state, ps, key, feed) + (rng,)

        # the function jit traces, for step_flops: tracing THIS one again
        # finds jit's own trace in the cache, where tracing ``step`` would
        # walk the whole model a second time (seconds of set-up)
        self._step_jitted_fn = step_and_split
        if self.mesh is not None:
            # params/opt slots were placed ONCE at init (or after load) with
            # their rule-derived shardings; the jitted step consumes and
            # donates them in place — no per-batch host re-placement
            # (jit partitions the step over the mesh by itself, which
            # Mosaic kernels do not survive: their gates keep to XLA paths)
            from paddle_tpu.ops.pallas_kernels import xla_paths_only

            traced = (xla_paths_only()(step_and_split)
                      if self.mesh.size > 1 else step_and_split)
            jitted = jax.jit(traced, donate_argnums=(0, 2, 3, 4))

            def run(params, state, opt_state, ps, rng, feed):
                feed = self._shard_feed(feed)
                return jitted(params, state, opt_state, ps, rng, feed)

            return run
        return jax.jit(step_and_split, donate_argnums=(0, 2, 3, 4))

    def _param_shardings(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        if self.sharding_rules is None:
            repl = NamedSharding(self.mesh, P())
            sh = {k: repl for k in self.params}
        else:
            sh = self.sharding_rules.shardings(self.mesh, self.params)
        # pipeline-stacked stage params live sharded over the stage axis
        # (each device holds exactly its stage's slice)
        stage_names = getattr(self.topology, "stage_param_names", None)
        if stage_names:
            axis = self.topology.stage_axis
            for name in stage_names:
                sh[name] = NamedSharding(self.mesh, P(axis))
        return sh

    def _place_sharded(self) -> None:
        """Place params at their rule shardings and every optimizer slot at
        its parameter's sharding; BN state, scalars and the key replicated."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = self._param_shardings()
        repl = NamedSharding(self.mesh, P())
        self.params = {k: jax.device_put(v, sh[k]) for k, v in self.params.items()}
        self.state = jax.device_put(self.state, repl)
        # the key the step returns is replicated over the mesh: a fresh one
        # (init, load) placed otherwise would show the second call another
        # sharding, and the step would compile again
        self._rng = jax.device_put(self._rng, repl)

        def put_like(name):
            def put(leaf):
                if hasattr(leaf, "shape") and tuple(leaf.shape) == tuple(
                    self.params[name].shape
                ):
                    return jax.device_put(leaf, sh[name])
                return jax.device_put(jnp.asarray(leaf), repl)

            return put

        if isinstance(self.opt_state, dict) and "slots" in self.opt_state:
            slots = {
                k: jax.tree_util.tree_map(put_like(k), v)
                for k, v in self.opt_state["slots"].items()
            }
            rest = {k: jax.device_put(v, repl)
                    for k, v in self.opt_state.items() if k != "slots"}
            self.opt_state = {**rest, "slots": slots}
        else:
            self.opt_state = jax.device_put(self.opt_state, repl)
        if getattr(self, "pserver", None) is not None:
            self.pserver.place()

    def _shard_feed(self, feed):
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self.mesh
        # a mesh without the data axis (e.g. a pure pserver 'model' mesh)
        # replicates the batch instead of erroring inside device_put
        axis = self.data_axis if self.data_axis in mesh.axis_names else None

        def put(v):
            v = jnp.asarray(v)
            spec = P(axis, *([None] * (v.ndim - 1)))
            return jax.device_put(v, NamedSharding(mesh, spec))

        out = {}
        for k, v in feed.items():
            if isinstance(v, tuple):
                out[k] = tuple(put(x) for x in v)
            else:
                out[k] = put(v)
        return out

    # -- telemetry helpers (paddle_tpu/obs) ----------------------------

    @contextmanager
    def _ph(self, name: str):
        """The ONE place a phase of the loop is recorded, three ways from
        one pair of boundaries: a ``jax.profiler.TraceAnnotation``
        ``paddle_tpu.trainer.<name>`` (always: a flag check while no
        profiler is attached, and the only record on the device trace's
        clock), the ``StepTimeline`` phase (``--obs_timeline``) and a child
        span of the current batch's trace (request tracing armed).  A
        phase that dispatches device work fetches its result inside the
        phase (``step`` ends in the loss fetch), so no record syncs."""
        tl, sp = self.timeline, self._step_span
        span = sp.child(name) if sp is not None else None
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
                yield
        finally:
            if tl is not None:
                tl.add(name, time.perf_counter() - t0)
            if span is not None:
                span.end()

    @contextmanager
    def _ph_setup(self, name: str):
        """``_ph`` while the set-up record is open (the loop's first
        iteration in a process): the same phase inside the record's
        (``_SETUP_PHASE_OF``).  The loop swaps back to ``_ph`` itself when
        the iteration has completed, so a later step pays nothing."""
        with setup_phase(_SETUP_PHASE_OF.get(name, name)), self._ph(name):
            yield

    @property
    def _h2d_measurable(self) -> bool:
        """Whether an explicit synced transfer would measure anything
        real: yes across a mesh (sharded placement) or to an
        accelerator; no on single-device CPU, where the backend aliases
        host buffers and an explicit ``device_put`` is a pure extra copy
        (measured: ~0.4ms/batch of fake 'transfer' for a 512 KiB feed)."""
        return self.mesh is not None or jax.default_backend() != "cpu"

    def _device_feed(self, feed: Dict[str, Any]) -> Dict[str, Any]:
        """Transfer the prepared feed host->device and BLOCK, so the
        timeline's ``h2d`` phase measures real transfer time and the
        ``step`` phase that follows is pure compute+dispatch.
        ``device_put`` + one tree-level block: the cheapest explicit
        transfer (no per-leaf op machinery, transfers overlap)."""
        if self.mesh is not None:
            out = self._shard_feed(feed)
        else:
            put = jax.device_put
            out = {k: (tuple(put(x) for x in v) if isinstance(v, tuple)
                       else put(v))
                   for k, v in feed.items()}
        try:
            jax.block_until_ready(out)
        except Exception:
            pass  # non-array leaves (host-side aux) pass through
        return out

    def step_flops(self, feed: Dict[str, Any]) -> Optional[float]:
        """Analytic matmul+conv FLOPs of ONE train step (forward +
        backward + optimizer), from the ``analysis.flops`` walker: what
        the live ``train_mfu`` gauge divides (pinned to the walker by
        tests/test_obs.py).  Traced through the
        function jit wraps (the step with the key's split before it, which
        adds no product), so after a first step the TRACE is a look-up
        (the set-up record's ``flops_trace`` reads ``trace`` 0.000 s, one
        event, on every cell) and what is left is the walk over the
        jaxpr's equations: 0.004 s on the LSTM cell, 0.017-0.045 on five of
        the decoder cells, 0.053-0.056 on Nemotron-3-Nano's, the largest
        read (PERF.md section 6, PR 52), once a ``train()`` call."""
        from paddle_tpu.analysis.flops import jaxpr_flops

        if self.mesh is not None:
            feed = self._shard_feed(feed)
        ps = self.pserver.state() if self.pserver is not None else {}
        rng = jax.random.PRNGKey(0)
        return jaxpr_flops(self._step_jitted_fn, self.params, self.state,
                           self.opt_state, ps, rng, feed)

    # ------------------------------------------------------------------

    def rebuild_masks(self) -> None:
        """Rebuild pruning masks from the CURRENT parameter values and refresh
        the cached jitted step (which closes over the masks).

        The reference builds the pruning mask from the parameter values
        actually in effect — initial or loaded
        (paddle/parameter/ParameterUpdaterHook.cpp:36-78) — so whenever
        ``self.params`` is swapped wholesale (checkpoint load, v2 parameter
        adoption) the magnitude pattern must be recomputed."""
        from paddle_tpu.param.hooks import apply_masks, build_masks

        if not self.pruning_ratios:
            return
        self.masks = build_masks(self.params, self.pruning_ratios)
        self.params = apply_masks(self.params, self.masks)
        self._step = self._build_step()

    def _log_parameter_stats(self) -> None:
        """Per-parameter mean/|max|/min table — the
        --show_parameter_stats_period plane (reference:
        TrainerInternal.cpp:162 showParameterStats, Stat printing of
        ParameterName.mean/max/min per period).  One jitted reduction per
        call; only scalars cross the host link."""
        fn = getattr(self, "_param_stats_fn", None)
        if fn is None:
            @jax.jit
            def fn(params):
                return {
                    k: (jnp.mean(v), jnp.max(jnp.abs(v)), jnp.min(v))
                    for k, v in params.items()
                }
            self._param_stats_fn = fn
        stats = fn(self.params)
        for k in sorted(stats):
            mean, amax, mn = (float(x) for x in stats[k])
            logger.info("param %-28s mean=% .5e absmax=% .5e min=% .5e",
                        k, mean, amax, mn)

    def audit(self, feed: Dict[str, Any], *, label: str = "train_step"):
        """Run the trace-time jaxpr auditor (paddle_tpu.analysis) over this
        trainer's full step — forward, backward, optimizer update — with
        the given prepared feed; returns the list of Findings.

        The hook behind ``python -m paddle_tpu lint --config CONF``: the
        auditor sees exactly the program ``train_batch`` compiles (same
        closure, same donation-free trace), so findings carry jaxpr-eqn
        provenance into the real step."""
        from paddle_tpu.analysis import audit_fn

        if self.mesh is not None:
            feed = self._shard_feed(feed)
        rng = jax.random.PRNGKey(0)
        ps = self.pserver.state() if self.pserver is not None else {}
        return audit_fn(self._step_fn, self.params, self.state,
                        self.opt_state, ps, rng, feed,
                        label=label, mesh=self.mesh)

    def train_batch(self, feed: Dict[str, Any]) -> float:
        """Run one optimizer step on a prepared feed dict; returns cost.

        Between the end of one step on the device and the launch of the
        next the host makes ONE dispatch and ONE fetch: the trainer's key
        is split inside the compiled step (``_build_step``; the program
        returns the next ``self._rng``), and everything the host reads of
        a step (the guard's flag, ``amp_overflow`` and the scale under
        ``--amp``, the loss, the counter outputs) starts its copy to the
        host at dispatch, so the one ``step.sync`` here waits for them
        together and the ``float()`` of the returned cost waits for
        nothing.  With neither guard nor ``--amp`` this does not block at
        all and the caller's ``float()`` is the fetch.  Inside ``train()``
        the prefetcher is told at dispatch to hand over the next batch
        (``BatchPrefetcher.take``), so its thread works under this step
        and sleeps across the turn-around.

        With the bad-step guard on, a non-finite loss/grad step leaves
        params, optimizer slots, and layer state untouched (the skip
        happens inside the jitted step — resilience/guard.py); the skip
        flag lands in ``_last_extras['bad_step']`` and the host-side
        counters ``bad_steps_total``/``bad_steps_streak`` advance.  After
        ``max_bad_steps`` CONSECUTIVE skips the step raises
        ``TooManyBadSteps`` — persistent non-finite training cannot
        recover by skipping."""
        with _span("step.rng"):
            # what the step takes besides the trainer's own state: the
            # pserver's tables (the key is split inside the program)
            ps = self.pserver.state() if self.pserver is not None else {}
        with _span("step.dispatch"):
            (loss, self.params, self.state, self.opt_state, new_ps, extras,
             self._rng) = self._step(self.params, self.state, self.opt_state,
                                     ps, self._rng, feed)
        amp = self.amp and "amp_overflow" in extras
        guard = (self.guard_nonfinite or self.amp) and "bad_step" in extras
        # what the host reads of every step, in the order it reads it
        fetched = ([extras["amp_overflow"], extras["loss_scale"]]
                   if amp else []) + ([extras["bad_step"]] if guard else [])
        fetched.append(loss)
        with _span("step.post"):
            # the device is running the step; the host's own lines up to
            # the fetch.  Every copy to the host starts now, behind the
            # step: the fetch below (or the caller's) is one wait, and
            # _feed_counters reads after it
            for arr in [*fetched,
                        *(extras[name] for name, _ in self._counter_feeds)]:
                arr.copy_to_host_async()
            if self._prefetcher is not None:
                # the next batch leaves the queue NOW, so the producer's
                # prepare and h2d of a later one run under this step
                self._prefetcher.take()
            if self.pserver is not None:
                self.pserver.adopt(new_ps)
            if self.averager is not None:
                self.avg_params = self.averager.update(self.avg_params,
                                                       self.params)
            self._obs_counters["batches"].inc()
            self._last_extras = extras
            if self._gang is not None:
                self._obs_gauges["world"].set(self._gang.world_size)
                # elastic observability: the live world, whether it is
                # running degraded (fewer ranks than configured), and the
                # resize story
                self._last_extras = {
                    **self._last_extras,
                    "world_size": self._gang.world_size,
                    "degraded": self._gang.degraded,
                    "resize_count": self._resize_count,
                    "last_resize_reason": self._last_resize_reason,
                }
        if amp or guard:
            # the ONE blocking fetch of a step: the flags and the loss
            # arrive together, so the reads below and the caller's
            # float(loss) find them on the host.  ``reason``: the first
            # thing waited for
            with _span("step.sync", reason="amp" if amp else "guard"):
                jax.device_get(fetched)
        if amp and bool(extras["amp_overflow"]):
            self.amp_overflows_total += 1
            scale = float(extras["loss_scale"])
            if self._journal is not None:
                # a rescale is part of the causal story of an --amp
                # run — journaled like bad_step, next to its context
                self._journal.record("amp_overflow", scale=scale,
                                     total=self.amp_overflows_total)
            logger.warning(
                "amp: non-finite scaled gradients — step skipped, "
                "loss scale halved to %g (overflow %d)", scale,
                self.amp_overflows_total)
        if guard:
            if bool(extras["bad_step"]):
                self.bad_steps_total += 1
                self._bad_streak += 1
                self._obs_counters["bad_steps"].inc()
                if self._step_span is not None:
                    # bad steps are incidents: their step traces are
                    # ALWAYS retained by tail sampling
                    self._step_span.retain("bad_step")
                    self._step_span.set(bad_step=True,
                                        bad_streak=self._bad_streak)
                if self._journal is not None:
                    # a skipped step is an incident, not a log line: it
                    # lands in the causal timeline with pass/batch context
                    self._journal.record("bad_step",
                                         streak=self._bad_streak,
                                         total=self.bad_steps_total)
                logger.warning(
                    "non-finite loss/grad: optimizer update skipped "
                    "(streak %d, total %d)", self._bad_streak,
                    self.bad_steps_total)
                if self.max_bad_steps and self._bad_streak >= self.max_bad_steps:
                    raise TooManyBadSteps(
                        f"{self._bad_streak} consecutive non-finite steps "
                        f"(max_bad_steps={self.max_bad_steps})")
            else:
                self._bad_streak = 0
        return loss

    def _feed_counters(self) -> None:
        """Add the last step's marked extras to their counters.  Called
        once the step's loss is on the host: the values were computed by
        the same program and their copies started at dispatch, so this
        waits for nothing the loop had not waited for already."""
        for name, spec in self._counter_feeds:
            values = np.asarray(self._last_extras[name]).reshape(-1)
            children = self._counter_children.get(name)
            if children is None:
                from paddle_tpu.obs import get_registry

                reg, children = get_registry(), []
                labels = dict(spec.get("labels", {}))
                for i in range(values.size):
                    if spec.get("index_label"):
                        labels[spec["index_label"]] = (
                            spec.get("first_index", 0) + i)
                    children.append(reg.counter(
                        spec["name"], spec.get("help", ""),
                        labels=tuple(sorted(labels)), **labels))
                self._counter_children[name] = children
            for child, v in zip(children, values):
                child.inc(float(v))

    @property
    def bad_steps_streak(self) -> int:
        return self._bad_streak

    def train(
        self,
        reader: Callable,
        *,
        num_passes: int = 1,
        event_handler: Optional[Callable] = None,
        feeder: Optional[Callable] = None,
        test_reader: Optional[Callable] = None,
        resume: Optional[str] = None,
        preemption: Optional[PreemptionHandler] = None,
    ) -> None:
        """Pass/batch loop with events — trainer.py:108-173 analog.

        Fault tolerance (docs/resilience.md):

        - ``resume="auto"`` (or ``--resume=auto``): restore params / state /
          opt_state / RNG / pass-id from the newest VALID checkpoint under
          ``FLAGS.save_dir`` and continue from there — including mid-pass,
          at the exact batch a preemption checkpoint recorded;
        - SIGTERM/SIGINT (or a ``preemption`` handler's ``request()``)
          triggers an atomic checkpoint at the next batch boundary and a
          clean return (``self.preempted`` is set);
        - a reader exception mid-pass emits ``EndPass`` (handlers see pass
          teardown on failure) and re-raises as ``ReaderError`` so the
          crash is attributed to the data tier, not the step.

        Gang mode (a supervised rank, or a live multi-process
        ``jax.distributed`` run — ``resilience.cluster.current_gang()``):
        the loop heartbeats at every batch boundary (a wedged collective
        goes silent and the supervisor restarts the gang), the preemption
        request is OR-reduced across ranks so everyone checkpoints at a
        consistent boundary, checkpoints are published by rank 0 behind
        an all-ranks barrier, and auto-resume follows the COORDINATOR's
        notion of the latest valid pass.

        Instrumentation (docs/observability.md): every phase of the loop
        goes through ``_ph``, which names it ``paddle_tpu.trainer.<phase>``
        on a profiler trace and feeds the step timeline (the reference's
        REGISTER_TIMER plane, TrainerInternal.cpp:118; ``--enable_timers``
        prints its per-pass table, Stat.h:70-247) and the request tracer;
        what the loop does between two phases, and the parts of ``step``,
        go through ``_span``, which names them on the trace and nowhere
        else; ``--profile_dir`` records the ``jax.profiler`` trace — the
        hl_profiler_start/end analog (hl_cuda.h:338-343), viewable in
        TensorBoard/XProf."""
        from paddle_tpu.obs import (ProfilerCapture, StepTimeline,
                                    ensure_metrics_server, get_journal)
        from paddle_tpu.obs.trace import get_tracer

        handler = event_handler or (lambda e: None)
        log_period = FLAGS.log_period
        # --profile_steps turns the whole-run trace into bounded windows
        profiling = bool(FLAGS.profile_dir) and not FLAGS.profile_steps

        gang = self._gang = current_gang()
        # unified telemetry (docs/observability.md): exposition endpoint,
        # step timeline, per-rank event journal, profiler windows
        ensure_metrics_server()
        tl = self.timeline = (StepTimeline(
            n_devices=(self.mesh.devices.size if self.mesh is not None
                       else 1))
            if FLAGS.obs_timeline or FLAGS.enable_timers else None)
        jr = self._journal = get_journal(
            rank=(getattr(gang, "rank", 0) if gang is not None else 0),
            world_size=(gang.world_size if gang is not None else 1))
        if jr is not None:
            if gang is not None:
                jr.set_context(epoch=gang.epoch)
            jr.record("train_start", num_passes=num_passes,
                      resume=resume or FLAGS.resume or "")
        # step-span tracing (docs/observability.md "Request tracing"):
        # armed with the journal; each batch becomes a trace whose
        # children are the timeline phases, with gang events attached
        tracer = self._tracer = get_tracer()
        self._step_span = None
        # the set-up record (obs/timeline.py; docs/observability.md
        # "Set-up") is open until the first iteration of the process's
        # first pass has completed; until then the loop's phases go into
        # it as well (_ph_setup).  The one look at it of a train() call
        setting_up = not setup_record().closed
        ph = self._ph_setup if setting_up else self._ph
        first_it = ExitStack()
        profiler = self._profiler = (
            ProfilerCapture(FLAGS.profile_dir, FLAGS.profile_steps)
            if FLAGS.profile_dir and FLAGS.profile_steps else None)
        if profiler is not None:
            profiler.install_signal()
        # background checkpoint scrubber (--scrub_every_s, rank 0 only —
        # one scrubber per save_dir; docs/resilience.md "Silent
        # corruption"): re-hash everything at rest on a cadence so a
        # checkpoint that rots AFTER its first read is quarantined and
        # the newest fully-verified pass stays marked for rollback
        scrubber = None
        if (FLAGS.scrub_every_s > 0 and FLAGS.save_dir
                and (gang is None or gang.is_coordinator)):
            from paddle_tpu.resilience.integrity import ScrubDaemon

            scrubber = ScrubDaemon(FLAGS.save_dir,
                                   every_s=FLAGS.scrub_every_s).start()
        resume = resume or FLAGS.resume or None
        # checkpointable data source (docs/data.md): a reader carrying the
        # cursor protocol gets cursor-based resume/resize instead of the
        # O(pass) re-read-and-discard fast-forward
        from paddle_tpu.datapipe import is_checkpointable_source

        src = reader if is_checkpointable_source(reader) else None
        self._data_source = src
        self._pending_cursor = None
        if (src is not None and getattr(src, "shard_by_gang", False)
                and gang is not None and gang.size > 1):
            ranks = sorted(int(r) for r in gang.ranks)
            src.bind_world(len(ranks), ranks.index(gang.rank))
        start_pass, start_batch = FLAGS.start_pass, 0
        if resume is not None and resume != "auto":
            raise ValueError(f"resume must be None or 'auto', got {resume!r}")
        if gang is not None and gang.size > 1 and gang.epoch > 0:
            # elastic JOINER: rendezvous with the survivors regardless of
            # resume mode or save_dir — the grow must complete (and the
            # survivors' join barrier release) even when there is nothing
            # durable to restore
            start_pass, start_batch = self._gang_join(gang)
        elif resume == "auto":
            start_pass, start_batch = self._auto_resume()
        cursor_restored = False
        if src is not None and self._pending_cursor is not None:
            # O(1) resume: point the source at the saved cursor — the
            # fast-forward loop below is skipped entirely (ZERO re-read
            # samples); it survives only as the plain-reader fallback
            src.restore(self._pending_cursor)
            cursor_restored = True
            self._pending_cursor = None
        if (preemption is None and FLAGS.save_dir
                and FLAGS.checkpoint_on_preemption):
            preemption = PreemptionHandler()
        if (preemption is not None and gang is not None
                and getattr(preemption, "gang", None) is None):
            # one host's SIGTERM becomes a gang-agreed checkpoint decision
            preemption.gang = gang
        self.preempted = False
        if preemption is not None:
            preemption.install()
        if profiling:
            jax.profiler.start_trace(FLAGS.profile_dir)
        # the pass loop iterates a REWINDABLE schedule: an SDC rollback
        # (no replica majority — every survivor's state is suspect)
        # restores the last verified checkpoint and rewinds the schedule
        # to its pass instead of exiting the loop
        schedule = _PassSchedule(start_pass, num_passes)
        first_it.enter_context(setup_phase("first_iteration"))
        try:
            for pass_id in schedule:
                handler(ev.BeginPass(pass_id))
                if jr is not None:
                    jr.set_context(pass_id=pass_id, batch_id=0)
                    jr.record("begin_pass")
                costs: List[float] = []
                loss = None
                rolled_back = False
                t0 = time.time()

                def _reader_failed(e: Exception):
                    # pass teardown reaches the handlers even on failure,
                    # and the crash is attributed to the reader tier
                    handler(ev.EndPass(pass_id))
                    if jr is not None:
                        jr.record("reader_error",
                                  error=f"{type(e).__name__}: {e}")
                    if isinstance(e, ReaderError):
                        return e
                    return ReaderError(
                        f"reader raised in pass {pass_id}: "
                        f"{type(e).__name__}: {e}")

                try:
                    with setup_phase("data"):
                        if src is not None:
                            src.seek(pass_id)
                        it = iter(reader())
                except Exception as e:
                    raise _reader_failed(e) from e
                self._prefetcher = None
                skip = start_batch if pass_id == start_pass else 0
                first_batch = 0
                if skip and cursor_restored:
                    # the restored cursor already points at this batch:
                    # batch numbering continues, nothing is re-read
                    first_batch, skip = skip, 0
                    logger.info("resuming pass %d at batch %d from the "
                                "data cursor (no replay)", pass_id,
                                first_batch)
                elif skip:
                    logger.info("resuming pass %d at batch %d "
                                "(fast-forward fallback)", pass_id, skip)

                def _wrap_prefetch():
                    # double-buffered async feeding (--prefetch_depth):
                    # prepare + h2d of a later batch run in a background
                    # thread, under the device step of batch N because
                    # train_batch takes batch N+1 out of the queue when it
                    # has dispatched N; the loop below sees PreparedFeed
                    # markers and skips its own prepare/h2d phases.  Built
                    # lazily AFTER the resume fast-forward (skipped batches
                    # are consumed raw — no prepare/h2d paid for batches
                    # the skip discards) and
                    # closed at every loop exit (pass end, preemption,
                    # exception) so a drain point never leaves a torn
                    # batch.  An elastic resize mid-pass needs no rebuild:
                    # ``transfer`` reads self.mesh at call time, and the
                    # jitted runner re-shards every feed per batch, so the
                    # <=depth+1 feeds prepared under the old mesh are
                    # re-placed exactly like the params themselves.
                    nonlocal it
                    if FLAGS.prefetch_depth > 0:
                        from paddle_tpu.data.feeder import BatchPrefetcher

                        it = self._prefetcher = BatchPrefetcher(
                            it, prepare=feeder,
                            transfer=(self._device_feed
                                      if self._h2d_measurable else None),
                            depth=FLAGS.prefetch_depth)

                if not skip:
                    with setup_phase("data"):
                        _wrap_prefetch()
                batch_id = first_batch
                while True:
                    # one StepTraceAnnotation per batch (XProf groups by
                    # step_num).  Everything the loop does in it lies in a
                    # phase (_ph) or in a trace-only part (_span: poll,
                    # extras, close), so its self time is the glue between
                    # two ``with`` blocks (docs/observability.md)
                    with jax.profiler.StepTraceAnnotation(
                            SPAN_PREFIX + "iteration", step_num=batch_id):
                        with _span("poll"):
                            if tracer.enabled and not skip \
                                    and self._step_span is None:
                                # the step-span opens BEFORE the gang poll so a
                                # resize adopted at this boundary lands inside
                                # the very trace whose latency it explains
                                self._step_span = tracer.start_trace(
                                    "train_step", batch=batch_id)
                            if gang is not None:
                                # liveness signal from the MAIN thread: a rank
                                # stuck in a collective stops heartbeating here
                                # and the supervisor's watchdog gang-restarts
                                # it
                                gang.heartbeat()
                                # elastic resize (docs/resilience.md): a
                                # published world change is adopted HERE, at
                                # the batch boundary — the natural drain point.
                                # While the reader is still fast-forwarding
                                # (skip > 0) the params already include every
                                # batch up to batch_id + skip — recording the
                                # skip cursor instead would make a restore
                                # re-apply batches the state has already seen
                                world = gang.poll_world()
                                if world is not None:
                                    self._gang_resize(gang, world, pass_id,
                                                      batch_id + skip, handler)
                                    if self._source_resharded:
                                        # the source re-split the permutation
                                        # for the new world: drop the old
                                        # split's read-ahead and re-enter the
                                        # pass at the same batch boundary.  The
                                        # reshard positioned the cursor at
                                        # batch_id+skip, so any remaining
                                        # fast-forward (a datapipe source
                                        # resuming without a manifest cursor)
                                        # is cancelled — the skip loop would
                                        # otherwise discard never-trained
                                        # batches
                                        self._source_resharded = False
                                        self._close_prefetcher()
                                        batch_id, skip = batch_id + skip, 0
                                        it = iter(reader())
                                        _wrap_prefetch()
                            if preemption is not None and preemption.poll():
                                if self._step_span is not None:
                                    # a preempted step is an incident: keep it
                                    self._step_span.retain("preempt")
                                    self._step_span.end(status="preempt")
                                    self._step_span = None
                                # the prefetcher's read-ahead is abandoned
                                # HERE, at the drain point: the checkpoint
                                # records the batches the STEP consumed, so
                                # resume re-reads the prepared-but-unstepped
                                # ones — batch-exact
                                self._close_prefetcher()
                                self._preempt_exit(pass_id, batch_id + skip,
                                                   preemption, handler)
                                return
                        with ph("data_wait"):
                            try:
                                data_batch = next(it, None)
                            except PrepareError as e:
                                # a prefetched batch failed in PREPARE/H2D,
                                # not in the reader: re-raise the original so
                                # a feeder bug keeps its own type, exactly as
                                # it would without prefetch
                                raise (e.__cause__ if e.__cause__ is not None
                                       else e)
                            except Exception as e:
                                raise _reader_failed(e) from e
                        if data_batch is None:
                            if self._step_span is not None:
                                # no batch behind this span: not a step, not
                                # a story — never reaches the journal
                                self._step_span.cancel()
                                self._step_span = None
                            break
                        if skip:
                            # fast-forward a deterministic reader to the batch
                            # the preemption checkpoint recorded (raw items —
                            # the prefetcher attaches once the skip is done).
                            # Plain-reader FALLBACK only: a datapipe source
                            # resumes by cursor and never enters this branch
                            skip -= 1
                            batch_id += 1
                            self.resume_replayed_batches += 1
                            if not skip:
                                _wrap_prefetch()
                            continue
                        if jr is not None:
                            jr.set_context(batch_id=batch_id)
                        with ph("callback"):
                            handler(ev.BeginIteration(pass_id, batch_id))
                        prefetched = isinstance(data_batch, PreparedFeed)
                        with ph("prepare"):
                            feed = (data_batch.feed if prefetched
                                    else feeder(data_batch) if feeder
                                    else data_batch)
                        if tl is not None and self._h2d_measurable \
                                and not prefetched:
                            # explicit, synced host->device transfer: the h2d
                            # phase is real transfer time, and the step phase
                            # that follows starts device-resident (on single-
                            # device CPU there is no boundary to measure —
                            # skipped, the alias-copy rides inside `step`)
                            with ph("h2d"):
                                feed = self._device_feed(feed)
                        if profiler is not None:
                            # BEFORE the step: a window armed at batch b
                            # traces batches b..b+N-1 exactly — ticking after
                            # the step would shift the capture one step late
                            # and make the first post-compile step untraceable
                            profiler.tick()
                        try:
                            with ph("step"):
                                loss = self.train_batch(feed)
                                # the phase ends with the step's device work
                                # done: where train_batch fetched a flag the
                                # loss came with it, else this is the fetch
                                with (nullcontext()
                                      if self.guard_nonfinite or self.amp
                                      else _span("step.sync", reason="loss")):
                                    cost = float(loss)
                                if self._counter_feeds:
                                    with _span("step.counters"):
                                        self._feed_counters()
                        except TooManyBadSteps:
                            if self._step_span is not None:
                                self._step_span.retain("train_abort")
                                self._step_span.end(status="train_abort")
                                self._step_span = None
                            handler(ev.EndPass(pass_id))
                            if jr is not None:
                                jr.record("train_abort",
                                          reason="too_many_bad_steps")
                            raise
                        with _span("extras"):
                            if tl is not None and tl.wants_mfu and \
                                    not tl.flops_attempted:
                                # ONE extra host-side trace per train()
                                # call (the timeline is the call's), only
                                # when a chip peak is resolvable — a failed
                                # trace (None) is not retried per batch.  A
                                # phase of set-up the first time, and on the
                                # profiler's trace every time
                                with setup_phase("flops_trace",
                                                 span_after_close=True):
                                    tl.set_flops(self.step_flops(feed))
                                tl.recompute_mfu()
                            if src is not None:
                                # corrupt shard records the source skipped
                                # under its skip-and-count policy
                                # (datapipe/iterator.py) — surfaced next to the
                                # step extras like dropped_features
                                self._last_extras = {
                                    **self._last_extras,
                                    "dropped_records": int(getattr(
                                        src, "dropped_records", 0))}
                            drops = getattr(feeder, "dropped_features", None)
                            if drops is not None:
                                # sparse-bag truncation is a data-loss event,
                                # not a debug log line: surface the feeder's
                                # counter next to the step extras (serving
                                # mirrors it in healthz())
                                self._last_extras = {
                                    **self._last_extras,
                                    "dropped_features": int(drops)}
                            costs.append(cost)
                            if tl is not None:
                                self._obs_gauges["cost"].set(cost)
                                self._last_extras = {
                                    **self._last_extras,
                                    "step_time_s": tl.last.get("step"),
                                    "mfu": tl.mfu,
                                }
                        with ph("callback"):
                            handler(ev.EndIteration(pass_id, batch_id, cost))
                        with _span("close"):
                            if self._step_span is not None:
                                # the root closes here: tail sampling decides —
                                # bad-step/resize/preempt marks always keep,
                                # the p99 reservoir keeps outlier-slow steps,
                                # the rest head-sample at --trace_sample
                                sp, self._step_span = self._step_span, None
                                sp.end(status="ok", cost=round(cost, 6))
                            if (gang is not None and self.sdc_check_every
                                    and gang.world_size > 1
                                    and (batch_id + 1)
                                    % self.sdc_check_every == 0):
                                # cross-replica integrity check (the SDC
                                # firewall): exchange the step's in-jit state
                                # fingerprint and majority-vote it
                                try:
                                    self._sdc_check(gang, pass_id, batch_id,
                                                    handler)
                                # invariant: _SdcRollback is not a one-rank
                                # escape — the vote itself is the collective,
                                # and _sdc_check raises on EVERY rank or on
                                # none, so no peer is left blocked in
                                # exchange_json
                                except _SdcRollback as rb:  # tpu-lint: disable=protocol-exception
                                    start_pass = rb.start_pass
                                    start_batch = rb.start_batch
                                    cursor_restored = False
                                    if rb.cursor_ready:
                                        cursor_restored = True
                                    elif (src is not None and
                                          self._pending_cursor is not None):
                                        src.restore(self._pending_cursor)
                                        cursor_restored = True
                                        self._pending_cursor = None
                                    schedule.rewind(start_pass)
                                    rolled_back = True
                                    break
                            if log_period and (batch_id + 1) % log_period == 0:
                                logger.info(
                                    "Pass %d, Batch %d, Cost %.5f "
                                    "(%.1f batch/s)",
                                    pass_id, batch_id + 1,
                                    float(np.mean(costs[-log_period:])),
                                    log_period / max(time.time() - t0, 1e-9),
                                )
                                t0 = time.time()
                            psp = FLAGS.show_parameter_stats_period
                            if psp and (batch_id + 1) % psp == 0:
                                self._log_parameter_stats()
                            tp = FLAGS.test_period
                            if (tp and test_reader is not None
                                    and (batch_id + 1) % tp == 0):
                                # mid-pass eval — test_period batches
                                # (Trainer.cpp trainOneBatch "testing" branch;
                                # 0 = per pass only)
                                with ph("eval"):
                                    mid = self.test(test_reader, feeder=feeder)
                                logger.info(
                                    "Pass %d, Batch %d, Test cost %.5f",
                                    pass_id, batch_id + 1, mid["cost"])
                    if setting_up:
                        # the process's first iteration has completed: the
                        # set-up record ends, publishes itself, and the loop
                        # goes back to _ph alone
                        first_it.close()
                        close_setup(jr)
                        setting_up, ph = False, self._ph
                    batch_id += 1
                self._close_prefetcher()
                if rolled_back:
                    # SDC rollback: the state was just restored from the
                    # last verified checkpoint — skip this pass's
                    # teardown (it never completed) and re-enter at the
                    # rewound pass/batch
                    continue
                result = {}
                if test_reader is not None:
                    with ph("eval"):
                        result = self.test(test_reader, feeder=feeder)
                with ph("callback"):
                    handler(ev.EndPass(pass_id, evaluator=result))
                if jr is not None:
                    jr.record("end_pass", batches=batch_id)
                if FLAGS.save_dir and FLAGS.saving_period and (
                    (pass_id + 1) % FLAGS.saving_period == 0
                ):
                    with ph("checkpoint"):
                        try:
                            self.save(FLAGS.save_dir, pass_id)
                        except GangResized as e:
                            # a peer died while this rank waited in the
                            # save barrier; the resize commit below IS the
                            # end-of-pass checkpoint
                            self._gang_resize(gang, e.world, pass_id,
                                              None, handler)
                if (FLAGS.publish_dir and FLAGS.publish_every
                        and FLAGS.save_dir
                        and (pass_id + 1) % FLAGS.publish_every == 0
                        and (gang is None or gang.is_coordinator)):
                    # continuous publication (docs/publish.md): export a
                    # gated deploy bundle from the newest VERIFIED
                    # checkpoint bytes — never from live memory, so an
                    # unverified or quarantined pass is unpublishable by
                    # construction; a refusal is journaled, never fatal
                    with ph("checkpoint"):
                        self.publish(FLAGS.publish_dir, FLAGS.save_dir)
                if tl is not None:
                    if FLAGS.enable_timers:
                        logger.info("step timeline (pass %d):\n%s",
                                    pass_id, tl.table())
                    tl.end_pass(pass_id, journal=jr)
            if gang is not None and num_passes > start_pass:
                # one last look before returning — and, while the gang is
                # running DEGRADED, a bounded linger.  The supervisor
                # publishes the grow-back within its poll cadence of the
                # last survivor's shrink ack; a survivor that exits inside
                # that window strands the joiner with no coordinator to
                # publish its join-epoch resume decision (the supervisor
                # would have to retire it).  Lingering a few seconds makes
                # the grow deterministic; a supervisor with grow_back off
                # just costs each survivor one bounded wait at the very
                # end of training.
                linger_until = time.monotonic() + 5.0
                while True:
                    world = gang.poll_world()
                    if world is not None:
                        self._gang_resize(gang, world, num_passes - 1,
                                          None, handler)
                        linger_until = time.monotonic() + 5.0
                    if not gang.degraded or time.monotonic() > linger_until:
                        break
                    gang.heartbeat()
                    time.sleep(0.05)
        finally:
            first_it.close()   # a pass that ran no iteration to its end
            if self._step_span is not None:
                # an exception mid-batch: the half-told step never
                # reaches the journal (incidents retain+end explicitly)
                self._step_span.cancel()
                self._step_span = None
            self._close_prefetcher()  # exception paths: join the producer
            if profiling:
                jax.profiler.stop_trace()
            if profiler is not None:
                profiler.close()
                profiler.uninstall_signal()
            if scrubber is not None:
                scrubber.stop()
            if jr is not None:
                jr.record("train_end", preempted=self.preempted)
            if preemption is not None:
                preemption.uninstall()

    def _close_prefetcher(self) -> None:
        """Stop and join the current pass's background feeding pipeline
        (no-op when ``--prefetch_depth`` is off or already closed)."""
        pf, self._prefetcher = self._prefetcher, None
        if pf is not None:
            pf.close()

    def _preempt_exit(self, pass_id: int, batch_id: int,
                      preemption: PreemptionHandler,
                      handler: Optional[Callable] = None) -> None:
        """Preemption landed: persist an atomically-written mid-pass
        checkpoint (manifest records ``next_batch`` so ``resume="auto"``
        re-enters this pass at this exact batch) and return cleanly."""
        self.preempted = True
        if self._journal is not None:
            self._journal.record("preempt", saving=bool(FLAGS.save_dir))
        if FLAGS.save_dir:
            try:
                d = self.save(FLAGS.save_dir, pass_id,
                              meta={"preempted": True, "next_batch": batch_id})
            except GangResized as e:
                # the gang resized under the preemption save; the resize
                # commit records the SAME resume point, so it doubles as
                # the preemption checkpoint
                self._gang_resize(self._gang, e.world, pass_id, batch_id,
                                  handler)
                d = pass_dir(FLAGS.save_dir, pass_id)
            logger.warning(
                "preemption: checkpoint saved to %s (pass %d, next batch "
                "%d); exiting", d, pass_id, batch_id)
        else:
            logger.warning(
                "preemption requested but --save_dir is unset: exiting "
                "WITHOUT a checkpoint")

    # -- silent-data-corruption check (resilience/integrity.py) ----------

    def _sdc_check(self, gang, pass_id: int, batch_id: int,
                   handler: Optional[Callable]) -> None:
        """One cross-replica agreement round at a batch boundary.

        The step already computed the u64 fingerprint of params +
        optimizer slots (+ pserver tables) on device; only those 8 bytes
        cross the gang channel here.  All replicas are bit-identical by
        construction (pinned resume equivalence), so ANY disagreement is
        silent corruption:

        - a unique strict majority → the minority rank(s) quarantine
          themselves (marker + journal) and exit via ``SDCDivergence``;
          the elastic supervisor expels them (shrink, never a whole-gang
          relaunch) and a replacement rejoins from a verified checkpoint;
        - no strict majority (the 2-replica tie) → the tie breaks against
          the non-coordinator ranks, AND every survivor rolls back to the
          last verified checkpoint — with a tie no rank can certify its
          own state, so correctness never depends on the attribution
          being right.

        Further checks hold until the expulsion lands (epoch change):
        re-voting against a quarantined peer's stale digest would only
        re-litigate the same incident."""
        from paddle_tpu.resilience.errors import SDCDivergence
        from paddle_tpu.resilience.integrity import sdc_vote, sdc_vote_pods

        if self._sdc_hold_epoch is not None:
            if gang.epoch == self._sdc_hold_epoch:
                return
            self._sdc_hold_epoch = None
        fp_dev = self._last_extras.get("sdc_fp")
        if fp_dev is None:
            return
        from paddle_tpu.resilience.integrity import fingerprint_int

        fp = fingerprint_int(jax.device_get(fp_dev))
        try:
            raw = gang.exchange_json(
                fp, name=f"sdc-p{pass_id:05d}-b{batch_id:06d}")
        except GangResized as e:
            # a peer died mid-exchange: run the resize protocol the same
            # way a save barrier would
            self._gang_resize(gang, e.world, pass_id, batch_id + 1,
                              handler)
            if self._source_resharded:
                self._source_resharded = False
                raise _SdcRollback(pass_id, batch_id + 1,
                                   cursor_ready=True)
            return
        except DCNPartitioned as e:
            # the peer pod is alive but unreachable over DCN: the
            # transport already reported it — hold for the supervisor's
            # pod-expel publish and resize into the shrunken world
            world = self._dcn_partition_hold(gang, e)
            self._gang_resize(gang, world, pass_id, batch_id + 1,
                              handler)
            if self._source_resharded:
                self._source_resharded = False
                raise _SdcRollback(pass_id, batch_id + 1,
                                   cursor_ready=True)
            return
        fps = {int(r): int(v) for r, v in raw.items()}
        if getattr(gang, "pod_size", 1) > 1:
            # dcn topology: pods (not ranks) are the bit-identical
            # replicas AND the failure unit — vote over pod digests so a
            # divergent pod is quarantined whole
            vote = sdc_vote_pods(fps, gang.coordinator, gang.pod_of)
        else:
            vote = sdc_vote(fps, gang.coordinator)
        if vote.agreed:
            self._sdc_last_agreed = (pass_id, batch_id, fp)
            self._sdc_agreed_fps.append(fp)
            return
        self.sdc_mismatches_total += 1
        jr = self._journal
        if jr is not None:
            # fsync'd: the incident anchor the merged postmortem orders
            # the expel/rollback/rejoin records against
            jr.record("sdc_mismatch", fsync=True,
                      fps={str(r): f"{v:016x}" for r, v in fps.items()},
                      minority=vote.minority, tie=vote.tie)
        if gang.rank in vote.minority:
            gdir = getattr(gang, "gang_dir", None)
            if gdir is not None:
                try:  # the supervisor folds this into expel attribution
                    with open(os.path.join(
                            gdir, f"sdc-quarantined-rank{gang.rank}"),
                            "w") as f:
                        json.dump({"pass": pass_id, "batch": batch_id,
                                   "fp": f"{fp:016x}",
                                   "presumed": f"{vote.presumed:016x}"},
                                  f)
                except OSError:
                    pass
            if jr is not None:
                jr.record("sdc_quarantine", fsync=True, fp=f"{fp:016x}",
                          presumed=f"{vote.presumed:016x}")
            logger.error(
                "SDC: rank %d fingerprint %016x lost the replica vote "
                "(presumed-good %016x) at pass %d batch %d — exiting "
                "for quarantine", gang.rank, fp, vote.presumed, pass_id,
                batch_id)
            raise SDCDivergence(
                f"rank {gang.rank} state fingerprint {fp:016x} diverged "
                f"from the replica vote ({vote.presumed:016x}) at pass "
                f"{pass_id} batch {batch_id}")
        # survivor: suppress re-checks until the expulsion lands
        self._sdc_hold_epoch = gang.epoch
        if not vote.tie:
            # a strict majority certified this state by agreement — no
            # rollback; the minority is being expelled
            logger.warning(
                "SDC: replica majority holds %016x; minority rank(s) %s "
                "diverged and will be expelled", vote.presumed,
                vote.minority)
            return
        # tie: attribution impossible — restore the last verified
        # checkpoint so correctness never rides on the tie-break
        if not FLAGS.save_dir:
            if jr is not None:
                jr.record("sdc_no_rollback", reason="no save_dir")
            logger.error(
                "SDC: replica tie with no --save_dir — cannot roll back "
                "to a verified checkpoint; continuing on suspect state")
            return
        p = self._sdc_rollback_target(FLAGS.save_dir, jr)
        if p < 0:
            if jr is not None:
                jr.record("sdc_no_rollback", reason="no valid checkpoint")
            logger.error(
                "SDC: replica tie but no verified checkpoint under %r — "
                "continuing on suspect state", FLAGS.save_dir)
            return
        self._sdc_rollbacks += 1
        if self._sdc_rollbacks > _SDC_MAX_ROLLBACKS:
            raise SDCDivergence(
                f"{self._sdc_rollbacks} SDC rollbacks without a clean "
                "check — divergence is persistent")
        manifest = self.load(FLAGS.save_dir, p, validate=True)
        sp, sb = self._resume_point(p, manifest)
        if jr is not None:
            jr.record("sdc_rollback", fsync=True, restored_pass=p,
                      start_pass=sp, start_batch=sb)
        logger.warning(
            "SDC: no replica majority — rolled back to verified "
            "checkpoint pass %d (re-entering pass %d batch %d)", p, sp,
            sb)
        raise _SdcRollback(sp, sb)

    def _sdc_rollback_target(self, save_dir: str, jr) -> int:
        """Resolve the rollback target: the newest CRC-valid pass whose
        manifest fingerprint the replicas actually AGREED on.

        CRC validation alone cannot reject a checkpoint that was saved
        from already-corrupt state (flip before the save, detection
        after — the CRCs are computed over the corrupt bytes and match
        perfectly), so preferring an agreement-certified fingerprint is
        what keeps the corruption from laundering itself through the
        rollback.  When no checkpoint is certifiable (no check coincided
        with a save boundary, or a restart emptied the agreed set), the
        newest CRC-valid pass is used and the uncertifiable fallback is
        journaled — honest, not silent."""
        from paddle_tpu.resilience.checkpoint_io import (_PASS_RE,
                                                         validate_checkpoint)
        from paddle_tpu.resilience.integrity import latest_verified_pass

        newest = latest_verified_pass(save_dir)
        if newest < 0:
            return -1
        agreed = set(self._sdc_agreed_fps)
        try:
            ids = sorted(
                (int(m.group(1)) for m in
                 (_PASS_RE.fullmatch(n) for n in os.listdir(save_dir))
                 if m), reverse=True)
        except OSError:
            ids = []
        for pid in ids:
            if pid > newest:
                continue
            d = pass_dir(save_dir, pid)
            if validate_checkpoint(d) is not None:
                continue
            try:
                fp_hex = (read_manifest(d).get("meta") or {}).get("sdc_fp")
            except Exception:  # noqa: BLE001 — unreadable meta: skip
                continue
            if fp_hex is not None and int(fp_hex, 16) in agreed:
                return pid
        if jr is not None:
            jr.record("sdc_rollback_unverified", fsync=True,
                      newest_valid=newest)
        logger.warning(
            "SDC: no checkpoint under %r carries an agreement-verified "
            "fingerprint — rolling back to the newest CRC-valid pass %d "
            "(cannot certify it predates the corruption; align "
            "--sdc_check_every with the pass length so end-of-pass "
            "checkpoints are certified)", save_dir, newest)
        return newest

    # -- elastic gang resize (worker half; docs/resilience.md) -----------

    def _dcn_partition_hold(self, gang, exc) -> Dict[str, Any]:
        """A DCN partition heals by the SUPERVISOR expelling the accused
        pod (elastic shrink), not by this rank dying: the transport left
        a report marker naming the pod, so hold here — keep heartbeating
        (this rank is healthy; dying would widen the blast radius to a
        whole-gang relaunch) and watch for the world publish — then hand
        the shrunken world to the normal resize protocol.  No publish
        within the budget means the supervisor disagreed (e.g. the
        accused pod's heartbeats went stale, so the watchdog owns it as a
        pod DEATH): re-raise and let the fallback relaunch attribute it."""
        budget = max(30.0, 4.0 * FLAGS.gang_watchdog_s)
        logger.warning(
            "DCN partition: pod %s unreachable after %d attempt(s) on %s "
            "— holding up to %.0fs for the supervisor's pod-expel "
            "publish", exc.pod, exc.attempts, exc.op or "?", budget)
        if self._journal is not None:
            self._journal.record("dcn_partition_hold", fsync=True,
                                 pod=exc.pod, op=exc.op,
                                 attempts=exc.attempts)
        deadline = time.monotonic() + budget
        while time.monotonic() < deadline:
            gang.heartbeat()
            world = gang.poll_world()
            if world is not None:
                return world
            time.sleep(0.05)
        raise exc

    def _gang_resize(self, gang, world: Dict[str, Any], pass_id: int,
                     next_batch: Optional[int],
                     handler: Optional[Callable] = None) -> None:
        """Carry this rank through one published world change, at a batch
        boundary (the drain point): barriered checkpoint-commit →
        re-instantiate the (one) mesh → resume.

        ``next_batch`` is the resume position inside ``pass_id`` (None =
        the pass just completed).  Shrink or grow, the new membership is
        adopted FIRST and the commit barriers under the NEW epoch (seq 0
        of its fresh barrier sequence): on a shrink that is the
        survivors; on a grow the joiner pairs the same barrier from
        ``_gang_join``, then the coordinator publishes the epoch's
        resume decision for it.  Adopt-first means a resize never
        consumes old-epoch barriers — a peer that was still blocked in a
        normal save barrier when the world changed aborts it via
        ``GangResized`` and re-enters here, landing on the SAME new-epoch
        commit barrier instead of desynchronizing the sequence.  Any
        failure in here surfaces as a nonzero exit and the supervisor
        falls back to the whole-gang relaunch."""
        new_ranks = sorted(int(r) for r in world["ranks"])
        grew = bool(set(new_ranks) - set(gang.ranks))
        epoch = int(world["epoch"])
        if handler is not None:
            handler(ev.Resize(pass_id,
                              -1 if next_batch is None else next_batch,
                              epoch, len(new_ranks), grew))
        meta: Dict[str, Any] = {"resize_epoch": epoch,
                                "resize_reason": world.get("reason", "")}
        if next_batch is None:
            start = (pass_id + 1, 0)
        else:
            meta.update(preempted=True, next_batch=next_batch)
            start = (pass_id, next_batch)
        with gang.resizing():
            gang.adopt_world(world)
            if getattr(gang, "pod_size", 1) > 1:
                # pod-LOCAL drain first, global commit second: this pod's
                # survivors rendezvous over ICI before entering the
                # cross-pod commit barrier, so a straggler inside a pod
                # is attributed pod-locally instead of wedging the global
                # barrier (lint --protocol pins this ordering)
                gang.pod_barrier()
            self._resize_commit(gang, pass_id, meta)
            # invariant: this one-sided send pairs the JOINER's
            # broadcast_json receive inside _gang_join (a different
            # process, mid-join), not this function's other branch —
            # survivors are not grew-side and never enter the collective
            if grew and gang.is_coordinator:  # tpu-lint: disable=protocol-unmatched
                gang.broadcast_json(
                    {"pass": pass_id if FLAGS.save_dir else -1,
                     "start_pass": start[0], "start_batch": start[1]},
                    name="resume")
            gang.ack_resize()
        self._mesh_resize()
        src = getattr(self, "_data_source", None)
        if src is not None and getattr(src, "shard_by_gang", False):
            # re-split the SAME permutation from the committed boundary
            # under the new membership: the commit above recorded the
            # cursor under the OLD world, so no sample is duplicated or
            # dropped (datapipe/iterator.py; pinned by test)
            src.reshard(len(new_ranks), new_ranks.index(gang.rank),
                        pass_id=start[0], next_batch=start[1])
            self._source_resharded = True
        self._resize_count += 1
        self._last_resize_reason = world.get("reason")
        self._obs_counters["resizes"].inc()
        if self._journal is not None:
            self._journal.set_context(epoch=epoch,
                                      world_size=len(new_ranks))
            self._journal.record(
                "gang_resize", fsync=True, epoch=epoch,
                new_world=len(new_ranks), grew=grew,
                reason=world.get("reason", ""),
                next_batch=-1 if next_batch is None else next_batch)
        if self._step_span is not None:
            # the resize rides the step-span it interrupted as an EVENT,
            # and that trace is retained: a latency spike at this batch
            # is attributable to the resize that caused it
            self._step_span.event("gang_resize", epoch=epoch,
                                  new_world=len(new_ranks), grew=grew,
                                  reason=world.get("reason", ""))
            self._step_span.retain("gang_resize")
        logger.warning(
            "elastic resize: %s to %d rank(s) (epoch %d) at pass %d%s — %s",
            "grew" if grew else "shrank", len(new_ranks), epoch, pass_id,
            "" if next_batch is None else f" batch {next_batch}",
            world.get("reason", ""))

    def _resize_commit(self, gang, pass_id: int, meta: Dict[str, Any]):
        """The drain's durable point: a normal (barriered, rank-0-publish)
        checkpoint — the state a joiner restores and a mid-resize failure
        falls back to.  Without a save_dir there is nothing durable to
        commit; the gang still rendezvouses so the resize stays barriered."""
        if FLAGS.save_dir:
            return self.save(FLAGS.save_dir, pass_id, meta=meta)
        gang.barrier()
        return None

    def _mesh_resize(self) -> None:
        """Re-instantiate the ONE MeshConfig for the current device world
        and re-place all state under the new shardings.

        On a supervised CPU gang every rank owns a single-process device
        world (this backend has no cross-process collectives), so the
        local mesh shape is unchanged and this is a no-op — resizing is
        purely membership.  On a live multi-host mesh the relaunched
        control plane exposes fewer (or restored) devices and the same
        call path rebuilds the mesh + re-places params/opt-state/pserver
        tables; checkpoint resharding needs no extra code because arrays
        are stored host-side and layout-free (the manifest records the
        mesh config for attribution — see tests/test_elastic_reshard.py)."""
        if self.mesh_config is None or self.mesh is None:
            return
        import jax as _jax

        cfg = self.mesh_config.fit_world(len(_jax.devices()))
        if cfg.shape == {n: int(self.mesh.shape[n])
                         for n in self.mesh.axis_names}:
            return
        # the config IS the world shape: keep it current so every
        # post-resize checkpoint manifest records the shape the state was
        # actually saved under, not the launch-time one
        self.mesh_config = cfg
        self.mesh = cfg.build()
        if self.pserver is not None:
            self.pserver.resize(self.mesh)
        self._place_sharded()
        self._step = self._build_step()
        if self.timeline is not None:
            # the program changed shape: stale FLOPs would skew the MFU
            # gauge — recompute lazily at the next step, against the
            # resized mesh's aggregate peak
            self.timeline.invalidate_flops()
            self.timeline.set_devices(
                self.mesh.devices.size if self.mesh is not None else 1)
        logger.info("mesh re-instantiated: %r", cfg)

    def _auto_resume(self) -> tuple:
        """Locate the newest valid checkpoint under FLAGS.save_dir and
        restore it; returns ``(start_pass, start_batch)``.

        In a gang, the checkpoint is resolved ON THE COORDINATOR and
        broadcast: a pass that happens to look newest/valid to one rank's
        local view but not the coordinator's can never fork the gang onto
        different restore points."""
        save_dir = FLAGS.save_dir
        if not save_dir:
            return FLAGS.start_pass, 0
        gang = getattr(self, "_gang", None)
        if gang is not None and gang.size > 1:
            return self._gang_auto_resume(gang, save_dir)
        p = latest_pass(save_dir)
        if p < 0:
            logger.info("resume=auto: no valid checkpoint under %r, "
                        "starting fresh", save_dir)
            return FLAGS.start_pass, 0
        # latest_pass just CRC-validated pass p: load without a second
        # decompress-and-hash pass (restart latency sits inside the
        # preemption grace window)
        manifest = self.load(save_dir, p, validate=False)
        return self._resume_point(p, manifest)

    @staticmethod
    def _resume_point(p: int, manifest) -> tuple:
        meta = (manifest or {}).get("meta", {})
        if meta.get("preempted"):
            nb = int(meta.get("next_batch", 0))
            logger.info("resume=auto: preemption checkpoint pass %d, "
                        "resuming at batch %d", p, nb)
            return p, nb
        logger.info("resume=auto: resuming after completed pass %d", p)
        return p + 1, 0

    def _gang_join(self, gang) -> tuple:
        """Elastic JOINER's half of the grow (docs/resilience.md): pair
        the survivors' resize-commit barrier (their FIRST barrier of this
        epoch — the adopt-first protocol in ``_gang_resize`` runs the
        commit under the NEW membership, joiner included), then follow
        the decision the coordinator publishes AFTER that commit
        (``broadcast_json`` epoch-namespaces the file), restore the
        committed checkpoint when there is one (pass -1 = no save_dir:
        nothing durable, membership only), and ack the grow — from that
        point this rank is an ordinary gang member.

        The barrier MUST come before the decision read: the decision is
        published only once the commit barrier releases, and that barrier
        waits for this rank — reading first would deadlock every grow
        into the whole-gang-relaunch fallback.

        Runs from ``train()`` for EVERY epoch>0 launch, independent of
        resume mode and save_dir — the survivors block in the commit
        barrier, so a joiner that skipped the rendezvous would time every
        grow out into the whole-gang-relaunch fallback."""
        gang.barrier()
        decision = gang.broadcast_json(None, name="resume")
        p = int(decision["pass"])
        if p >= 0:
            # the coordinator validated its OWN view of the resize commit,
            # not this rank's — CRC-verify on load
            self.load(FLAGS.save_dir, p, validate=True)
        gang.ack_resize()
        self._resize_count += 1
        self._last_resize_reason = "joined"
        if self._journal is not None:
            self._journal.set_context(epoch=gang.epoch,
                                      world_size=gang.world_size)
            self._journal.record("gang_join", epoch=gang.epoch,
                                 restored_pass=p)
        return int(decision["start_pass"]), int(decision["start_batch"])

    def _gang_auto_resume(self, gang, save_dir: str) -> tuple:
        """Coordinator resolves ``latest_valid_pass`` and broadcasts the
        decision; every rank restores that exact pass.  (An elastic
        joiner never reaches this — ``train()`` routes epoch>0 launches
        through ``_gang_join`` first.)"""
        if gang.is_coordinator:
            p = latest_pass(save_dir)
            if p < 0:
                sp, sb = FLAGS.start_pass, 0
                logger.info("resume=auto: coordinator found no valid "
                            "checkpoint under %r, gang starts fresh",
                            save_dir)
            else:
                manifest = self.load(save_dir, p, validate=False)
                sp, sb = self._resume_point(p, manifest)
            gang.broadcast_json({"pass": p, "start_pass": sp,
                                 "start_batch": sb}, name="resume")
            return sp, sb
        decision = gang.broadcast_json(None, name="resume")
        p = int(decision["pass"])
        if p >= 0:
            # peers did not run the coordinator's validating latest_pass —
            # CRC-verify their own view of the chosen checkpoint on load
            self.load(save_dir, p, validate=True)
        return int(decision["start_pass"]), int(decision["start_batch"])

    # ------------------------------------------------------------------

    def test(self, reader: Callable, *, feeder: Optional[Callable] = None,
             evaluators: Optional[Dict] = None) -> Dict[str, float]:
        """Eval loop — Tester analog (paddle/trainer/Tester.h:40).

        Reports the same weighted joint cost the train step optimizes (all
        cost heads, not just the first), plus per-cost values when training
        is multi-cost.

        Cost sums accumulate ON DEVICE (one jitted add per batch, async
        dispatch) and sync to the host exactly once at the end — no per-batch
        round-trip over the TPU link.  ``evaluators`` optionally maps
        ``{evaluator: wire_fn}`` where ``wire_fn(outs, feed) -> kwargs`` for
        the evaluator's ``batch_stats``; additive evaluators ride the same
        device-side accumulation (DeviceAccumulator), non-additive ones fall
        back to per-batch host pulls."""
        from paddle_tpu.evaluators import DeviceAccumulator

        evaluators = evaluators or {}
        # two cached variants: costs-only lets XLA dead-code-eliminate every
        # unused activation; the evaluator variant materializes all outputs
        want_outs = bool(evaluators)
        cache = getattr(self, "_test_fns", None)
        if cache is None:
            cache = self._test_fns = {}
        fn = cache.get(want_outs)
        if fn is None:
            topo, names = self.topology, self.cost_names
            tier = self.pserver

            @jax.jit
            def fn(params, state, tables, feed):
                overrides = (tier.make_overrides(tables, {})
                             if tier is not None else None)
                outs, _ = topo.apply(params, state, feed, train=False,
                                     param_overrides=overrides)
                costs = {k: outs[k].value for k in names}
                if want_outs:
                    return costs, {k: a.value for k, a in outs.items()}
                return costs, {}

            cache[want_outs] = fn
        tables = ({k: t.data for k, t in self.pserver.tables.items()}
                  if self.pserver is not None else {})
        params = self.avg_params if self.avg_params is not None else self.params
        accs = {ev: (DeviceAccumulator(ev) if ev.additive else None)
                for ev in evaluators}
        for ev, acc in accs.items():
            if acc is None:
                ev.start()
        totals = None  # device-side {name: (sum, count)} accumulators
        nb = 0
        for data_batch in reader():
            feed = feeder(data_batch) if feeder else data_batch
            costs, outs = fn(params, self.state, tables, feed)
            if totals is None:
                totals = costs
            else:
                totals = jax.tree_util.tree_map(jnp.add, totals, costs)
            nb += 1
            for ev, wire in evaluators.items():
                kw = wire(outs, feed)
                acc = accs[ev]
                if acc is not None:
                    acc.add(**kw)
                else:
                    ev.eval_batch(**kw)
        def ev_key(ev, seen):
            # instances of the same evaluator class get numbered keys so
            # multi-head eval never silently overwrites a metric
            k, i = ev.name, 2
            while k in seen:
                k, i = f"{ev.name}:{i}", i + 1
            return k

        if totals is None:  # empty reader: all keys present, nan-filled
            result = {"cost": float("nan")}
            if len(self.cost_names) > 1:
                for n in self.cost_names:
                    result[f"cost:{n}"] = float("nan")
            for ev in accs:
                result[ev_key(ev, result)] = float("nan")
            return result
        vals = {n: float(totals[n]) / nb for n in self.cost_names}
        result = {"cost": sum(w * vals[n]
                              for n, w in zip(self.cost_names, self.cost_weights))}
        if len(self.cost_names) > 1:
            for n, v in vals.items():
                result[f"cost:{n}"] = v
        for ev, acc in accs.items():
            result[ev_key(ev, result)] = (
                acc.result() if acc is not None else ev.result())
        return result

    def infer(self, output_layers, feed: Dict[str, Any]) -> Dict[str, np.ndarray]:
        """paddle.infer analog: run forward to the given layers."""
        if isinstance(output_layers, LayerOutput):
            output_layers = [output_layers]
        names = [l.name for l in output_layers]
        topo = self.topology

        overrides = None
        if self.pserver is not None:
            overrides = self.pserver.make_overrides(
                {k: t.data for k, t in self.pserver.tables.items()}, {})
        outs, _ = topo.apply(self.params, self.state, feed, train=False,
                             outputs=names, param_overrides=overrides)
        return {k: np.asarray(outs[k].value) for k in names}

    # ------------------------------------------------------------------

    def save(self, save_dir: str, pass_id: int,
             meta: Optional[Dict[str, Any]] = None) -> str:
        """Atomic, CRC-manifested checkpoint (resilience/checkpoint_io.py):
        params + state + optimizer slots + averaged params, with the RNG
        key in the manifest so a resumed run continues the exact random
        stream.  Retention (``FLAGS.keep_last_n``) prunes old passes.

        Gang mode: only rank 0 writes — replicas hold identical params,
        so N ranks writing N copies buys nothing but torn races — and the
        rename-publish happens behind an all-ranks barrier (every rank
        calls ``save()`` at the same loop point; non-coordinators just
        join the barrier).  A checkpoint therefore exists only if the
        WHOLE gang finished the pass: no rank can later auto-resume past
        a point a dead peer never reached."""
        gang = getattr(self, "_gang", None)
        if gang is not None and gang.size > 1 and not gang.is_coordinator:
            gang.barrier()  # matches the coordinator's pre-publish barrier
            return pass_dir(save_dir, pass_id)
        meta = dict(meta or {})
        meta.setdefault("rng_key", self._rng_to_list(self._rng))
        fp_dev = self._last_extras.get("sdc_fp")
        if fp_dev is not None and "sdc_fp" not in meta:
            # the state fingerprint at save time rides the manifest: the
            # scrubber and postmortems can tie a checkpoint to the exact
            # state the replicas agreed on (resilience/integrity.py)
            from paddle_tpu.resilience.integrity import fingerprint_hex

            try:
                meta["sdc_fp"] = fingerprint_hex(jax.device_get(fp_dev))
            except Exception:  # noqa: BLE001 — never fail a save for this
                pass
        src = getattr(self, "_data_source", None)
        if src is not None and "data_cursor" not in meta:
            # the input-pipeline cursor rides the manifest: a mid-pass
            # checkpoint records (pass, next_batch) -> the source derives
            # its O(1) cursor ARITHMETICALLY from the stepped-batch count
            # (prefetch read-ahead can never leak in); an end-of-pass
            # checkpoint records the next pass's start
            try:
                if meta.get("preempted"):
                    cur = src.cursor_for(pass_id,
                                         int(meta.get("next_batch", 0)))
                else:
                    cur = src.cursor_for(pass_id + 1, 0)
                meta["data_cursor"] = cur
            except Exception as e:  # noqa: BLE001 — never fail a save
                logger.warning("data cursor not recorded: %s", e)
        if self.mesh_config is not None:
            # record the world shape the state was saved under, so a
            # restore onto a different world can attribute the reshard
            # (the reshard itself needs no translation: arrays are stored
            # host-side and layout-free)
            meta.setdefault("mesh", self.mesh_config.to_json())
        if gang is not None:
            meta.setdefault("world_size", gang.world_size)
        extra = {}
        if self.avg_params is not None:
            extra["avg_params"] = self.avg_params
        if self.pserver is not None:
            # sharded tables + their slots/dirty masks/step ride the same
            # atomic CRC-manifested checkpoint: a lost shard rank restores
            # its rows from the manifest through the gang supervisor
            extra["pserver"] = self.pserver.state()
        d = save_checkpoint(
            save_dir, pass_id,
            params=self.params, state=self.state, opt_state=self.opt_state,
            extra=extra or None, meta=meta,
            barrier=(gang.barrier if gang is not None and gang.size > 1
                     else None),
        )
        self._obs_counters["checkpoints"].inc()
        if self._journal is not None:
            # fsync'd: the durable anchor a postmortem orders everything
            # against (torn-tail tolerance covers everything after it)
            self._journal.record("checkpoint_commit", fsync=True,
                                 saved_pass=pass_id, dir=d,
                                 preempted=bool(meta.get("preempted")))
        return d

    def publish(self, publish_dir: str, save_dir: str, *,
                pass_id: Optional[int] = None) -> Optional[str]:
        """Export a gated deploy bundle into the versioned publish dir
        (paddle_tpu.publish; docs/publish.md) from the newest VERIFIED
        checkpoint under ``save_dir`` — the train side of the continuous
        train->publish->reload loop.  A gate refusal (no verified pass,
        quarantined pass, corrupt checkpoint, quantize error budget) is
        journaled as ``publish_refused`` and returns None; it never
        fails training."""
        from paddle_tpu.publish import (PublishRefused,
                                        publish_from_checkpoints)

        try:
            vdir = publish_from_checkpoints(
                publish_dir, self.topology, save_dir, pass_id=pass_id,
                quantize=FLAGS.deploy_quantize or None)
        except PublishRefused as e:
            logger.warning("publish refused (%s): %s", e.reason, e)
            return None
        return vdir

    def load(self, save_dir: str, pass_id: int, *,
             validate: bool = True) -> Dict[str, Any]:
        """Validate + restore a checkpoint; raises
        ``resilience.CheckpointError`` on corruption.  Restores the RNG
        key when the manifest carries one; returns the manifest."""
        extra_like = {}
        if self.avg_params is not None:
            extra_like["avg_params"] = self.avg_params
        if self.pserver is not None:
            extra_like["pserver"] = self.pserver.state()
        out = load_checkpoint(
            save_dir, pass_id,
            params=self.params, state=self.state, opt_state=self.opt_state,
            extra_like=extra_like or None, validate=validate,
        )
        if not extra_like:
            self.params, self.state, self.opt_state = out
        else:
            self.params, self.state, self.opt_state, extras = out
            if "avg_params" in extras:
                self.avg_params = extras["avg_params"]
            if "pserver" in extras:
                self.pserver.adopt(extras["pserver"])
                self.pserver.place()
        try:
            manifest = read_manifest(pass_dir(save_dir, pass_id))
        except (FileNotFoundError, ValueError):
            manifest = {}
        rng_key = (manifest.get("meta") or {}).get("rng_key")
        if rng_key is not None:
            self._rng = jnp.asarray(np.asarray(rng_key, np.uint32))
        # the cached step fingerprint described the pre-load state — a
        # save (or SDC check) right after a restore must not read it
        self._last_extras.pop("sdc_fp", None)
        # input-pipeline cursor (docs/data.md): stashed for train() to
        # hand to a checkpointable source instead of fast-forwarding
        self._pending_cursor = (manifest.get("meta") or {}).get("data_cursor")
        if self.mesh is not None:
            self._place_sharded()
        self.rebuild_masks()
        return manifest

    @staticmethod
    def _rng_to_list(key) -> List[int]:
        try:
            raw = np.asarray(key)
        except TypeError:  # typed PRNG key arrays
            raw = np.asarray(jax.random.key_data(key))
        return [int(x) for x in raw.reshape(-1)]
