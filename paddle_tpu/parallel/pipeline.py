"""Pipeline parallelism over a 'stage' mesh axis — GPipe as one SPMD program.

The reference's closest machinery is the overlapped send/recv parameter
pipeline of RemoteParameterUpdater (paddle/trainer/RemoteParameterUpdater.h:
163-179) and the per-layer device placement of ParallelNeuralNetwork;
SURVEY.md §2 directs this framework to add modern pipeline parallelism as an
idiomatic jax.sharding feature instead.  The TPU-first design:

- Stage weights are STACKED on a leading [S, ...] axis and sharded over the
  ``stage`` mesh axis — every device holds exactly its stage's slice.
- All stages run ONE program under ``jax.shard_map``; activations hop to the
  next stage with ``lax.ppermute`` (ICI neighbor traffic, no host involvement).
- The GPipe fill/drain schedule is a ``lax.scan`` over ``S + M - 1`` ticks
  for M microbatches; stage 0 ingests microbatch t at tick t, the last stage
  emits microbatch t at tick t + S - 1.
- The whole loop is differentiable (ppermute transposes to the reverse
  permute, scan to the reverse scan), so ``jax.grad`` derives the backward
  pipeline schedule automatically — there is no hand-written backward pass,
  and cotangents for the stage-stacked weights arrive correctly reduced over
  any unmentioned data axis (shard_map inserts the psum from the in_specs).
- Composes with a ``data`` axis for dp x pp: microbatches carry their batch
  dim sharded over ``data`` while weights shard over ``stage``.

Constraints (by construction of the single-program schedule): all stages
share one ``stage_fn`` with equal input/output activation STRUCTURE (the
canonical homogeneous-block pipeline — transformer blocks, residual MLPs,
stacked RNN cells), and the microbatch count must divide the batch.
Activations may be arbitrary pytrees (every leaf with a leading batch dim)
— a sequence stage passes (value, mask, lengths) through the ppermute hops
as one tree.  The fill/drain ticks additionally require stage_fn's VJP to
be finite on a real microbatch (the carry is seeded with microbatch 0, not
zeros — see _gpipe_local).  ``parallel/pipeline_dsl.py`` drives this from
the ``nn`` DSL: ``device_pin`` stage tags partition a Topology into
head -> homogeneous stages -> tail.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.parallel import compat
from paddle_tpu.parallel.mesh import as_mesh
from paddle_tpu.param.optimizers import Optimizer

__all__ = ["stack_stage_params", "shard_stage_params", "pipeline_apply",
           "make_pipeline_train_step"]


def stack_stage_params(per_stage: Sequence[Any]):
    """[stage0_params, stage1_params, ...] (identical pytree structure) ->
    one pytree with leading stage dim S on every leaf."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_stage)


def shard_stage_params(mesh, stacked, *, stage_axis: str = "stage"):
    """Place a stage-stacked pytree with leading dim sharded over the stage
    mesh axis (each device holds its own stage's weights).  ``mesh`` may be
    a ``Mesh`` or a ``parallel.MeshConfig``."""
    sharding = NamedSharding(as_mesh(mesh), P(stage_axis))
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(a, sharding), stacked)


def _gpipe_local(stage_fn, w_stacked_local, x_mb, *, axis: str):
    """shard_map body: run the fill/drain schedule on this device's stage.

    ``w_stacked_local``: stage-stacked weights AFTER sharding — leading dim 1
    (this stage's slice).  ``x_mb``: pytree of [M, mb, ...] microbatch
    leaves (every stage receives them; only stage 0 reads them).  Returns
    the same tree with [M, mb, ...] outputs, psum-replicated over the
    stage axis."""
    tmap = jax.tree_util.tree_map
    S = compat.axis_size(axis)
    sid = lax.axis_index(axis)
    w_local = tmap(lambda a: a[0], w_stacked_local)
    M = jax.tree_util.tree_leaves(x_mb)[0].shape[0]
    perm = [(i, (i + 1) % S) for i in range(S)]

    def tick(prev, t):
        # stage 0 ingests microbatch t (clamped: ticks >= M feed a dummy
        # whose products drain past the last stage unrecorded); later
        # stages consume what ppermute delivered last tick
        i = jnp.clip(t, 0, M - 1)
        x_in = tmap(lambda full, p: jnp.where(sid == 0, full[i], p),
                    x_mb, prev)
        y = stage_fn(w_local, x_in)
        return tmap(lambda a: lax.ppermute(a, axis, perm), y), y

    # seed the carry with a REAL microbatch, not zeros: fill/drain ticks run
    # stage_fn (and, under grad, its VJP) on the carry with their output
    # cotangents masked to zero — but a derivative singular at 0 (sqrt,
    # x/||x||) makes inf intermediates and inf*0 = NaN would leak into the
    # weight grads accumulated over all ticks (ADVICE r4)
    _, ys = lax.scan(tick, tmap(lambda a: a[0], x_mb), jnp.arange(M + S - 1))
    # the last stage produced microbatch j at tick j + S - 1; replicate its
    # outputs across the stage axis (mask + psum — everyone else holds
    # intermediate activations, zeroed out here)
    return tmap(
        lambda a: lax.psum(
            jnp.where(sid == S - 1, a[S - 1:], jnp.zeros_like(a[S - 1:])),
            axis),
        ys)


def pipeline_apply(stage_fn: Callable[[Any, Any], Any],
                   stacked_params, x: Any, *, mesh,
                   n_microbatches: int, stage_axis: str = "stage",
                   data_axis: Optional[str] = None) -> Any:
    """Run ``x`` (array or pytree whose leaves all lead with [B, ...])
    through the S-stage pipeline; returns the stage output tree with [B]
    leading each leaf.

    ``stage_fn(stage_params, x_mb) -> y_mb`` is one stage's forward on a
    microbatch (equal in/out STRUCTURE across stages).  ``stacked_params``
    leaves carry the leading [S] stage dim (see ``stack_stage_params``).
    With ``data_axis`` the microbatch batch dim additionally shards over
    that mesh axis (dp x pp).  Fully differentiable — wrap in jax.grad for
    training."""
    mesh = as_mesh(mesh)
    tmap = jax.tree_util.tree_map
    x_leaves = jax.tree_util.tree_leaves(x)
    B = x_leaves[0].shape[0]
    M = n_microbatches
    if B % M:
        raise ValueError(f"batch {B} not divisible by n_microbatches {M}")
    for leaf in x_leaves:
        if leaf.shape[0] != B:
            raise ValueError(
                f"every activation leaf must lead with the batch dim {B}; "
                f"got shape {leaf.shape}")
    S = mesh.shape[stage_axis]
    leaves = jax.tree_util.tree_leaves(stacked_params)
    if leaves and leaves[0].shape[0] != S:
        # _gpipe_local reads slice [0] of each device's shard — a mismatch
        # would silently run a SUBSET of the stages
        raise ValueError(
            f"stacked_params carry {leaves[0].shape[0]} stages but mesh axis "
            f"{stage_axis!r} has {S} devices; they must be equal")
    x_mb = tmap(lambda a: a.reshape(M, B // M, *a.shape[1:]), x)
    mb_spec = P(None, data_axis) if data_axis else P()
    fn = functools.partial(_gpipe_local, stage_fn, axis=stage_axis)
    mapped = compat.shard_map(
        fn, mesh=mesh,
        in_specs=(P(stage_axis), mb_spec),
        out_specs=mb_spec,
        check_vma=False,
    )
    y_mb = mapped(stacked_params, x_mb)
    return tmap(lambda a: a.reshape(B, *a.shape[2:]), y_mb)


def make_pipeline_train_step(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    loss_fn: Callable[[jax.Array, Any], jax.Array],
    optimizer: Optimizer,
    mesh,
    *,
    n_microbatches: int,
    stage_axis: str = "stage",
    data_axis: Optional[str] = None,
    donate: bool = True,
) -> Callable:
    """``step(stacked_params, opt_state, x, labels) -> (loss, params, opt)``
    jitted dp x pp: pipeline forward, autodiff backward schedule, optimizer
    update on the stage-sharded stacks.  ``loss_fn(y [B, ...], labels) ->
    scalar`` runs on the pipeline output (replicated over stage, sharded
    over data — GSPMD inserts the data-axis mean reduction)."""
    mesh = as_mesh(mesh)

    def step(stacked_params, opt_state, x, labels):
        def objective(w):
            y = pipeline_apply(stage_fn, w, x, mesh=mesh,
                               n_microbatches=n_microbatches,
                               stage_axis=stage_axis, data_axis=data_axis)
            return loss_fn(y, labels)

        loss, grads = jax.value_and_grad(objective)(stacked_params)
        new_params, new_opt = optimizer.update(stacked_params, grads,
                                               opt_state)
        return loss, new_params, new_opt

    donate_argnums = (0, 1) if donate else ()
    return jax.jit(step, donate_argnums=donate_argnums)
