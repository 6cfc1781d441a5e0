"""High-level parallel training step builder.

Replaces the reference's updater/machine selection matrix (local vs remote vs
sparse-remote updaters, TrainerInternal.cpp:217-292; MultiGradientMachine) with
one function: give it a loss function (or Topology), a mesh, and sharding
rules — get back a compiled SPMD train step.  Collectives are chosen by XLA
GSPMD from the shardings; there is no separate communication code path to
maintain.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.parallel.mesh import as_mesh
from paddle_tpu.parallel.sharding import ShardingRules, batch_sharding, replicated
from paddle_tpu.param.optimizers import Optimizer

__all__ = ["make_parallel_train_step", "shard_batch", "agreement_spec"]


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
    ``shard_batch`` — jit then infers all collectives (grad all-reduce over
    'data', activation collectives over 'model') from the operand shardings.

    A ``MeshConfig`` that binds a ``dcn_axis`` (``--dcn_axis``) routes the
    pure data-parallel case (``rules is None``) through the two-level
    ICI-reduce-scatter / DCN-allreduce / ICI-allgather schedule
    (``parallel/hierarchical.py``) — same signature, same sum (bit-equal
    to flat on a single pod).  The bf16-compressed DCN variant changes
    the signature (it threads error-feedback residuals), so it is only
    available via ``make_hierarchical_train_step`` directly.
    """
    from paddle_tpu.parallel.mesh import MeshConfig

    if (rules is None and isinstance(mesh, MeshConfig) and mesh.dcn_axis
            and mesh.dcn_axis in mesh.shape):
        from paddle_tpu.parallel.hierarchical import \
            make_hierarchical_train_step

        return make_hierarchical_train_step(loss_fn, optimizer, mesh,
                                            compress=False, donate=donate)

    built = as_mesh(mesh)

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

    donate_argnums = (0, 1) if donate else ()
    return jax.jit(step, donate_argnums=donate_argnums)
