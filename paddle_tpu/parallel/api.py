"""High-level parallel training step builder.

Replaces the reference's updater/machine selection matrix (local vs remote vs
sparse-remote updaters, TrainerInternal.cpp:217-292; MultiGradientMachine) with
one function: give it a loss function (or Topology), a mesh, and sharding
rules — get back a compiled SPMD train step.  Who chooses the collectives
depends on the case:

- **pure data parallelism** (``rules is None``): the step is one
  ``shard_map`` over the mesh.  Every chip runs :func:`data_parallel_body`
  on its own rows, kernels and all, and the body reduces the gradients
  itself: one ``psum`` a leaf over the data axis, or the two-level schedule
  of ``parallel/hierarchical.py`` where the mesh binds a ``dcn`` axis.
- **a model axis** (``rules`` given): jit partitions a global step, and XLA
  GSPMD chooses the collectives from the operand shardings.  The Mosaic
  kernels do not survive that partitioner, so the step is traced inside
  ``ops/pallas_kernels.xla_paths_only()``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.ops.losses import token_mean_over_shards
from paddle_tpu.parallel import compat
from paddle_tpu.parallel.mesh import as_mesh
from paddle_tpu.parallel.sharding import ShardingRules, batch_sharding, replicated
from paddle_tpu.param.optimizers import Optimizer

__all__ = ["make_parallel_train_step", "data_parallel_body", "shard_batch",
           "agreement_spec"]


def agreement_spec(mesh, axis: Optional[str] = None):
    """Resolve the mesh + axis the cross-replica agreement collective
    (resilience/integrity.py) runs over: ``(built_mesh, axis_name,
    replica_count)``.

    ``mesh`` may be a ``Mesh`` or a ``parallel.MeshConfig``; ``axis``
    defaults to the config's DATA-role axis (the replica axis of
    data-parallel training — the one whose members are bit-identical by
    construction and therefore comparable).  A missing or size-1 axis is
    a config error: agreement over one replica compares nothing."""
    from paddle_tpu.parallel.mesh import MeshConfig
    from paddle_tpu.utils.error import ConfigError

    if isinstance(mesh, MeshConfig):
        name = axis or mesh.role_axis("data")
        built = mesh.build()
    else:
        built = mesh
        name = axis or "data"
    if name not in built.axis_names:
        raise ConfigError(
            f"agreement axis {name!r} not in mesh axes "
            f"{tuple(built.axis_names)}")
    n = int(built.shape[name])
    if n < 2:
        raise ConfigError(
            f"agreement over axis {name!r} needs >=2 replicas, mesh has "
            f"{n} — nothing to compare")
    return built, name, n


def shard_batch(mesh, feed: Dict[str, Any], axis: str = "data") -> Dict[str, Any]:
    """Place every array (or (value, lengths) tuple) batch-sharded on ``axis``.
    ``mesh`` may be a ``Mesh`` or a ``parallel.MeshConfig``."""
    mesh = as_mesh(mesh)

    def put(v):
        v = jnp.asarray(v)
        return jax.device_put(v, batch_sharding(mesh, v.ndim, axis))

    out: Dict[str, Any] = {}
    for k, v in feed.items():
        out[k] = tuple(put(x) for x in v) if isinstance(v, tuple) else put(v)
    return out


def _psum_leaves(axes):
    def reduce_grads(grads):
        return (jax.tree_util.tree_map(lambda g: lax.psum(g, axes), grads),)

    return reduce_grads


def data_parallel_body(loss_fn, optimizer: Optimizer, axes,
                       reduce_grads: Optional[Callable] = None) -> Callable:
    """What ONE chip does in a data-parallel step, for ``shard_map`` over a
    mesh whose ``axes`` (a name or a tuple of names) split the batch's rows:
    ``body(params, opt_state, *carry, batch) -> (loss, params, opt_state,
    *carry)``, with ``params`` and ``opt_state`` replicated.

    The body differentiates ``loss_fn`` on the chip's own rows, so the
    kernel gates see the chip's shape and a Mosaic kernel is an ordinary
    call; the embeddings' gradients are scattered over those rows alone and
    leave as ``[V, emb]`` tables.  ``reduce_grads(grads, *carry) -> (grads
    summed over the shards, *carry)`` is the exchange: ``lax.psum`` leaf by
    leaf unless the builder brings its own (``parallel/hierarchical.py``:
    the two-level schedule; its compressed variant carries residuals).
    Every chip then applies the same mean gradient to its replica, so the
    replicas stay equal to the bit.

    The loss and gradient are those of the GLOBAL batch.  A mean over rows
    is the mean of the shards' means, since shards hold equal rows; a mean
    over real tokens divides by ``ops.losses.token_count``, which is the
    mean count over the shards while this body is traced."""
    reduce_grads = reduce_grads or _psum_leaves(axes)

    def body(params, opt_state, *rest):
        *carry, batch = rest
        n = compat.axis_size(axes)
        with token_mean_over_shards(axes):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grads, *carry = reduce_grads(grads, *carry)
        grads = jax.tree_util.tree_map(lambda g: g / n, grads)
        new_params, new_opt = optimizer.update(params, grads, opt_state)
        return (lax.psum(loss, axes) / n, new_params, new_opt, *carry)

    return body


def make_parallel_train_step(
    loss_fn: Callable[[Dict[str, Any], Dict[str, Any]], jax.Array],
    optimizer: Optimizer,
    mesh,
    *,
    rules: Optional[ShardingRules] = None,
    donate: bool = True,
) -> Callable:
    """Build ``step(params, opt_state, batch) -> (loss, params, opt_state)``
    compiled SPMD over ``mesh`` (a ``Mesh`` or a ``parallel.MeshConfig``).

    ``loss_fn(params, batch) -> scalar`` must be pure. Params should be placed
    with ``shard_params(mesh, params, rules)`` and the batch with
    ``shard_batch``.

    Without ``rules`` the step is pure data parallel: one ``shard_map`` of
    :func:`data_parallel_body` over the mesh, the batch's rows split over
    the data axis, parameters and optimizer state replicated in and out (so
    the next call finds them placed as it left them).  A ``MeshConfig``
    that binds a ``dcn_axis`` (``--dcn_axis``) gets the same body with the
    two-level ICI-reduce-scatter / DCN-allreduce / ICI-allgather exchange
    (``parallel/hierarchical.py``) — same signature, same sum (bit-equal
    to flat on a single pod).  The bf16-compressed DCN variant changes
    the signature (it threads error-feedback residuals), so it is only
    available via ``make_hierarchical_train_step`` directly.

    With ``rules`` (a model axis) jit partitions the global step and infers
    all collectives (grad all-reduce over 'data', activation collectives
    over 'model') from the operand shardings; that step runs the XLA paths
    (``xla_paths_only``).
    """
    from paddle_tpu.parallel.mesh import MeshConfig

    if (rules is None and isinstance(mesh, MeshConfig) and mesh.dcn_axis
            and mesh.dcn_axis in mesh.shape):
        from paddle_tpu.parallel.hierarchical import \
            make_hierarchical_train_step

        return make_hierarchical_train_step(loss_fn, optimizer, mesh,
                                            compress=False, donate=donate)

    data = mesh.role_axis("data") if isinstance(mesh, MeshConfig) else "data"
    built = as_mesh(mesh)
    donate_argnums = (0, 1) if donate else ()
    if rules is None and built.size > 1 and data in built.axis_names:
        shm = compat.shard_map(
            data_parallel_body(loss_fn, optimizer, data), mesh=built,
            in_specs=(P(), P(), P(data)), out_specs=(P(), P(), P()))
        return jax.jit(shm, donate_argnums=donate_argnums)

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        new_params, new_opt = optimizer.update(params, grads, opt_state)
        # hand the state back placed as the rules place it: left to the
        # partitioner, the outputs come back in shardings of its own
        # choosing, and the next step recompiles for them
        return loss, placed(new_params, params), placed(new_opt, params)

    def placed(tree, params):
        def one(path, leaf):
            spec = P()
            for entry in reversed(path):
                name = getattr(entry, "key", None)
                if (rules is not None and name in params
                        and jnp.shape(leaf) == jnp.shape(params[name])):
                    spec = rules.spec_for(name, leaf.ndim)
                    break
            return jax.lax.with_sharding_constraint(
                leaf, NamedSharding(built, spec))

        return jax.tree_util.tree_map_with_path(one, tree)

    if built.size > 1:
        # jit partitions this step over the mesh by itself, which Mosaic
        # kernels do not survive: the gates keep to their XLA paths
        from paddle_tpu.ops.pallas_kernels import xla_paths_only

        step = xla_paths_only()(step)

    return jax.jit(step, donate_argnums=donate_argnums)
