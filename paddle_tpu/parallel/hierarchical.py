"""Hierarchical (two-level) gradient allreduce over the ``dcn`` axis.

A multi-pod mesh has two link classes: ICI inside a pod (fast, uniform)
and DCN between pods (an order of magnitude less bandwidth, higher and
noisier latency — the scaling-book multi-slice model).  A flat allreduce
over the joint ``(dcn, data)`` replica axes moves every gradient byte
across DCN once per hop of the ring it happens to land on; the
bandwidth-optimal schedule instead uses each tier for what it is good
at:

1. **ICI reduce-scatter** over the pod-local ``data`` axis — every
   device ends up owning the pod-local SUM of one ``1/ici_size`` shard
   of the gradient;
2. **DCN allreduce of the partials** — only ``1/ici_size`` of the bytes
   cross the slow tier, and the transfer parallelizes across the pod's
   devices (each device exchanges only its own shard with its
   same-index peers in other pods);
3. **ICI allgather** to rebuild the fully-reduced gradient on every
   device.

The sum is the SAME sum — the two-level schedule only reassociates it —
and on a single pod (``dcn_size == 1``) :func:`hierarchical_psum` IS
``lax.psum`` by construction, so the flat and hierarchical paths are
bit-compatible there (pinned by test).

Optionally the DCN hop compresses the partials to bfloat16 with **error
feedback** (:func:`hierarchical_psum_compressed`): each pod keeps the
quantization residual it introduced and adds it back into the next
step's partials, so the compression error accumulates into the model as
a one-step-delayed correction instead of a bias.  Not bit-exact with the
uncompressed path — gated by the convergence tier, not by the
bit-equality pins (``--dcn_compress``).

``make_hierarchical_train_step`` builds the data-parallel step of
``parallel.api`` (``data_parallel_body``: every chip differentiates the loss
on its own rows inside ``shard_map``) for dcn-bound meshes: the same body,
with the two-level schedule above in place of the flat ``psum`` as its
exchange.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.parallel import compat
from paddle_tpu.parallel.api import data_parallel_body
from paddle_tpu.parallel.mesh import MeshConfig
from paddle_tpu.param.optimizers import Optimizer
from paddle_tpu.utils import FLAGS
from paddle_tpu.utils.error import ConfigError

__all__ = ["hierarchical_psum", "hierarchical_psum_compressed",
           "init_dcn_residuals", "make_hierarchical_train_step"]


def _padded(size: int, ici_size: int) -> int:
    return size + (-size % ici_size)


def hierarchical_psum(x: jax.Array, ici_axis: str, dcn_axis: str, *,
                      ici_size: int, dcn_size: int) -> jax.Array:
    """Two-level allreduce of ``x`` from inside a shard_map body.

    ``dcn_size == 1`` returns the flat ``lax.psum`` — bit-compatible by
    construction, so a single-pod world pays zero schedule overhead and
    the hierarchical step builder needs no special-casing."""
    if dcn_size <= 1:
        return lax.psum(x, ici_axis)
    flat = x.reshape(-1)
    pad = _padded(flat.size, ici_size) - flat.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    # 1) ICI reduce-scatter: own the pod-local sum of one shard
    part = lax.psum_scatter(flat, ici_axis, scatter_dimension=0,
                            tiled=True)
    # 2) DCN allreduce of the 1/ici_size partials only
    part = lax.psum(part, dcn_axis)
    # 3) ICI allgather rebuilds the full reduced tensor
    full = lax.all_gather(part, ici_axis, tiled=True)
    if pad:
        full = full[:-pad]
    return full.reshape(x.shape)


def hierarchical_psum_compressed(x: jax.Array, residual: jax.Array,
                                 ici_axis: str, dcn_axis: str, *,
                                 ici_size: int, dcn_size: int):
    """:func:`hierarchical_psum` with the DCN hop in bfloat16 + error
    feedback.  ``residual`` is this device's carried quantization error
    (shape ``[padded_size // ici_size]``, the scattered-partial shape);
    returns ``(reduced, new_residual)``.  The ICI hops stay full
    precision — only the slow tier is compressed."""
    if dcn_size <= 1:
        return lax.psum(x, ici_axis), residual
    flat = x.reshape(-1)
    pad = _padded(flat.size, ici_size) - flat.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    part = lax.psum_scatter(flat, ici_axis, scatter_dimension=0,
                            tiled=True)
    # error feedback: fold last step's quantization error back in BEFORE
    # quantizing, so the error is a one-step delay, not a bias
    carried = part + residual.astype(part.dtype)
    q = carried.astype(jnp.bfloat16)
    new_residual = carried - q.astype(part.dtype)
    part = lax.psum(q, dcn_axis).astype(part.dtype)
    full = lax.all_gather(part, ici_axis, tiled=True)
    if pad:
        full = full[:-pad]
    return full.reshape(x.shape), new_residual


def _resolve(mesh) -> "tuple":
    """``(cfg, built, dcn_axis, data_axis, dcn_size, ici_size)`` from a
    MeshConfig (required — the role bindings live there)."""
    if not isinstance(mesh, MeshConfig):
        raise ConfigError(
            "make_hierarchical_train_step needs a MeshConfig (the dcn "
            "axis is a role binding, not a bare mesh property)")
    dcn = mesh.dcn_axis
    if not dcn or dcn not in mesh.shape:
        raise ConfigError(
            f"mesh {mesh!r} binds no dcn axis — use "
            "make_parallel_train_step (flat GSPMD reduction) instead")
    data = mesh.role_axis("data")
    if data == dcn:
        raise ConfigError(
            f"dcn axis {dcn!r} cannot also be the data axis — the ICI "
            "reduce-scatter needs a pod-local replica axis")
    if data not in mesh.shape:
        raise ConfigError(
            f"mesh {mesh!r} has no {data!r} axis to reduce-scatter over")
    built = mesh.build()
    return (mesh, built, dcn, data, int(built.shape[dcn]),
            int(built.shape[data]))


def init_dcn_residuals(mesh, params) -> Any:
    """Zero error-feedback state for ``--dcn_compress``: one residual
    leaf per param leaf, shaped ``[dcn_size, padded_size]`` and sharded
    ``P(dcn, data)`` — each device holds the residual of ITS scattered
    partial, each pod its own (pods quantize independent partial sums,
    so their errors are independent state)."""
    cfg, built, dcn, data, dcn_size, ici_size = _resolve(mesh)

    def leaf(p):
        shape = (dcn_size, _padded(int(jnp.size(p)), ici_size))
        z = jnp.zeros(shape, jnp.float32)
        return jax.device_put(z, NamedSharding(built, P(dcn, data)))

    return jax.tree_util.tree_map(leaf, params)


def make_hierarchical_train_step(
    loss_fn: Callable[[Dict[str, Any], Dict[str, Any]], jax.Array],
    optimizer: Optimizer,
    mesh,
    *,
    compress: Optional[bool] = None,
    donate: bool = True,
) -> Callable:
    """Build the dcn-aware data-parallel train step.

    Uncompressed: ``step(params, opt_state, batch) -> (loss, params,
    opt_state)`` — drop-in for ``make_parallel_train_step`` on a
    dcn-bound config.  With ``compress`` (default ``--dcn_compress``):
    ``step(params, opt_state, residuals, batch) -> (loss, params,
    opt_state, residuals)`` where ``residuals`` starts as
    :func:`init_dcn_residuals`.

    The step is ``parallel.api.data_parallel_body`` with the two-level
    schedule as its exchange — data-parallel only (params replicated;
    tensor-parallel rules need GSPMD's implicit reduction and keep using
    ``make_parallel_train_step``).  The batch's rows split over ``(dcn,
    data)`` jointly."""
    cfg, built, dcn, data, dcn_size, ici_size = _resolve(mesh)
    if compress is None:
        compress = bool(FLAGS.dcn_compress)
    sizes = dict(ici_size=ici_size, dcn_size=dcn_size)

    def two_level(grads):
        return (jax.tree_util.tree_map(
            lambda g: hierarchical_psum(g, data, dcn, **sizes), grads),)

    def two_level_compressed(grads, residuals):
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        out_g, out_r = [], []
        for g, r in zip(leaves, treedef.flatten_up_to(residuals)):
            red, nr = hierarchical_psum_compressed(
                g, r.reshape(-1), data, dcn, **sizes)
            out_g.append(red)
            out_r.append(nr.reshape(r.shape))
        return (jax.tree_util.tree_unflatten(treedef, out_g),
                jax.tree_util.tree_unflatten(treedef, out_r))

    # params/opt replicated across both axes, the batch's rows split over
    # them jointly; a residual leaf lives where its scattered partial does
    rep, rows, res = P(), P((dcn, data)), P(dcn, data)
    body = data_parallel_body(
        loss_fn, optimizer, (dcn, data),
        two_level_compressed if compress else two_level)
    carry = (res,) if compress else ()
    shm = compat.shard_map(body, mesh=built,
                           in_specs=(rep, rep, *carry, rows),
                           out_specs=(rep, rep, rep, *carry))
    donate_argnums = tuple(range(2 + len(carry))) if donate else ()
    return jax.jit(shm, donate_argnums=donate_argnums)
