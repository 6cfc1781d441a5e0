"""PServerTier — wires sharded tables into the SGDTrainer step.

The reference splits this across SparseRemoteParameterUpdater (prefetch /
push RPCs) and the trainer config (which parameters are remote); here the
split is: ``nn.embedding(..., sparse_grad=True)`` marks a parameter, and a
trainer constructed with a mesh carrying the pserver axis routes every such
parameter through this tier instead of the dense params dict:

- the table is created sharded (never on one host) and REMOVED from the
  trainer's ``params`` pytree — the dense optimizer neither stores nor
  updates it;
- inside the jitted step the topology sees a ``TableProxy`` for that
  parameter (``Topology.apply(param_overrides=...)``): lookups run the
  all-to-all exchange against the live sharded table, and each lookup adds
  a zeros proxy of the request shape;
- the step differentiates w.r.t. the proxies — the cotangents ARE the
  (ids, row-grads) segments — and ``apply_grads`` pushes them through
  ``sharded_row_update``.  Gradients for the table are never
  materialized at [V, D] (gated by ``lint --pserver``);
- optimizer slots for each table live sharded exactly like the table and
  advance only for touched rows (lazy regularization, the
  SparseRowMatrix semantics);
- the whole tier state (tables, slots, dirty masks, step counter) rides
  trainer checkpoints as an ``extra`` pytree, so gang recovery restores a
  lost shard's rows from the manifest like any other state.
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.param.optimizers import dedup_rows
from paddle_tpu.pserver.apply import sharded_row_update
from paddle_tpu.pserver.lookup import TableProxy
from paddle_tpu.pserver.table import ShardedTable, TableSpec
from paddle_tpu.utils import FLAGS, logger

__all__ = ["PServerTier", "Route"]


def _repad_rows(arr, vocab: int, v_pad_new: int):
    """Carry a row-dimensioned array across a shard-count change: keep the
    TRUE vocab rows, re-pad the tail with zeros to the new shard multiple.
    Works for [V_pad, D] tables/slots and [V_pad] dirty masks alike; exact
    because pad rows are zeros in every world (ids are always < vocab)."""
    arr = arr[:vocab]
    if v_pad_new > vocab:
        pad = jnp.zeros((v_pad_new - vocab,) + tuple(arr.shape[1:]),
                        arr.dtype)
        arr = jnp.concatenate([arr, pad])
    return arr


class Route(NamedTuple):
    """One embedding layer routed through the tier."""

    layer: str       # embedding layer name
    param: str       # table parameter name
    data: str        # feeding data layer name
    is_seq: bool     # sequence slot (ids [B, T]) vs scalar slot ([B, 1])
    dim: int


def discover_routes(topology) -> List[Route]:
    """Embedding layers whose table parameter is marked sparse_grad."""
    routes = []
    for layer in topology.layers:
        if layer.layer_type != "embedding" or not layer.param_specs:
            continue
        spec = layer.param_specs[0]
        if not spec.attr.sparse_grad:
            continue
        parent = layer.parents[0]
        routes.append(Route(
            layer=layer.name, param=spec.name, data=parent.name,
            is_seq=bool((parent.data_spec or {}).get("is_seq")),
            dim=layer.size))
    return routes


def _feed_ids(feed, route: Route):
    """The EXACT ids the embedding forward will look up for this route —
    mirrors nn/graph._coerce_feed + the embedding forward's [B,1] squeeze,
    so proxy shapes and pushed segments always line up with the lookup."""
    v = feed[route.data]
    value = v[0] if isinstance(v, tuple) else v
    ids = jnp.asarray(value).astype(jnp.int32)
    if not route.is_seq and ids.ndim == 2 and ids.shape[1] == 1:
        ids = ids[:, 0]
    return ids


class PServerTier:
    """Sharded-table store + step-integration hooks for one trainer."""

    def __init__(self, mesh, topology, optimizer, *,
                 axis: Optional[str] = None, pad: Optional[bool] = None,
                 lr_scales: Optional[Dict[str, float]] = None,
                 decays: Optional[Dict[str, float]] = None,
                 seed: Optional[int] = None) -> None:
        from paddle_tpu.parallel.mesh import MeshConfig, as_mesh

        if axis is None and isinstance(mesh, MeshConfig):
            axis = mesh.role_axis("pserver")
        self.mesh = as_mesh(mesh)
        self.axis = axis or FLAGS.pserver_axis
        self.dcn_axis = self._resolve_dcn(mesh, self.axis)
        self.optimizer = optimizer
        self.lr_scales = dict(lr_scales or {})
        self.decays = dict(decays or {})
        # the TRAINER's seed, not the global flag: table init must follow
        # the same reproducibility contract as the dense params
        seed = int(FLAGS.seed) if seed is None else int(seed)
        pad = FLAGS.pserver_pad_vocab if pad is None else pad
        self.routes = discover_routes(topology)
        self.tables: Dict[str, ShardedTable] = {}
        self._slots: Dict[str, Any] = {}
        self._step = jnp.zeros((), jnp.int32)
        by_param: Dict[str, List[Route]] = {}
        for r in self.routes:
            by_param.setdefault(r.param, []).append(r)
        self.routes_by_param = by_param
        for pname, rs in by_param.items():
            spec = topology.param_specs[pname]
            attr = spec.attr
            init = attr.init or "normal"
            if init not in ("normal", "uniform", "zeros"):
                init = "normal"   # xavier etc. have no row-local analog
            tspec = TableSpec(
                name=pname, vocab=spec.shape[0], dim=spec.shape[1],
                init=init,
                initial_std=(attr.initial_std
                             if attr.initial_std is not None else 0.01),
                initial_mean=attr.initial_mean,
                seed=seed,
                # --amp (ROADMAP item 2 follow-up): gathered rows leave the
                # lookup in bf16 — the cast sits AFTER the grad proxy add
                # (lookup.TableProxy), so masters, row gradients, and the
                # row-sparse update path stay f32 and bit-identical
                compute_dtype=("bfloat16" if FLAGS.amp else None))
            table = ShardedTable(tspec, mesh, axis=self.axis, pad=pad,
                                 dcn_axis=self.dcn_axis)
            self.tables[pname] = table
            slots = optimizer.init_leaf(table.data)
            self._slots[pname] = jax.tree_util.tree_map(
                lambda s: jax.device_put(s, table.sharding)
                if getattr(s, "shape", None) == table.data.shape else s,
                slots)
            logger.info("pserver: routed %s (%s) -> %r", pname,
                        ", ".join(r.layer for r in rs), table)

    @staticmethod
    def _resolve_dcn(mesh, axis: str) -> Optional[str]:
        """The dcn axis tables co-shard over, when the world is multi-pod:
        a MeshConfig's binding (or ``--dcn_axis``), present in the mesh,
        larger than 1, and distinct from the pserver axis.  None
        otherwise — a single-pod world keeps the one-hop a2a unchanged."""
        from paddle_tpu.parallel.mesh import MeshConfig

        if isinstance(mesh, MeshConfig):
            name, shape = mesh.dcn_axis, mesh.shape
        else:
            name = FLAGS.dcn_axis or None
            shape = {n: int(mesh.shape[n]) for n in mesh.axis_names}
        if name and name != axis and shape.get(name, 1) > 1:
            return name
        return None

    # ------------------------------------------------------------------
    # step-state plumbing (a plain pytree the jitted step donates)
    # ------------------------------------------------------------------

    @property
    def active(self) -> bool:
        return bool(self.tables)

    def param_names(self):
        return set(self.tables)

    def state(self) -> Dict[str, Any]:
        return {
            "step": self._step,
            "tables": {k: t.data for k, t in self.tables.items()},
            "slots": dict(self._slots),
            "dirty": {k: t.dirty for k, t in self.tables.items()},
        }

    def adopt(self, state: Dict[str, Any]) -> None:
        """Take ownership of a step's output (or a loaded checkpoint's)
        pserver pytree.

        Tolerates a WORLD-SIZE mismatch: a checkpoint taken under a
        different shard count stores tables at a different padded vocab
        ([V_pad_old, D]); the true rows carry over and the tail re-pads to
        this mesh's shard multiple (pad rows are zeros in every world —
        they can never be looked up or updated — so the reshard is
        bit-exact; tests/test_elastic_reshard.py)."""
        self._step = state["step"]
        new_slots: Dict[str, Any] = {}
        for k, t in self.tables.items():
            data = jnp.asarray(state["tables"][k])
            v_in = int(data.shape[0])
            if v_in == t.vocab_padded:
                t.data = data
                t.dirty = state["dirty"][k]
                new_slots[k] = state["slots"][k]
                continue
            logger.info(
                "pserver: resharding table %r from padded vocab %d to %d "
                "(%d shards)", k, v_in, t.vocab_padded, t.shards)
            t.data = _repad_rows(data, t.spec.vocab, t.vocab_padded)
            t.dirty = _repad_rows(
                jnp.asarray(state["dirty"][k], jnp.bool_),
                t.spec.vocab, t.vocab_padded)
            new_slots[k] = jax.tree_util.tree_map(
                lambda s: (_repad_rows(jnp.asarray(s), t.spec.vocab,
                                       t.vocab_padded)
                           if getattr(s, "shape", None) is not None
                           and jnp.ndim(s) >= 1
                           and int(jnp.shape(s)[0]) == v_in else s),
                state["slots"][k])
        self._slots = new_slots

    def resize(self, mesh) -> None:
        """Re-instantiate every table on a NEW mesh (the elastic resize:
        the pserver-axis size — hence shard count and padded vocab — may
        change).  Live rows, dirty bits, and optimizer slots carry over
        via ``_repad_rows``; nothing is re-initialized."""
        from paddle_tpu.parallel.mesh import as_mesh

        state = self.state()
        # re-resolve the pod axis BEFORE as_mesh: a MeshConfig carries the
        # binding; the dcn axis may have shrunk to one pod (or grown back)
        self.dcn_axis = self._resolve_dcn(mesh, self.axis)
        mesh = as_mesh(mesh)
        self.mesh = mesh
        for pname, old in list(self.tables.items()):
            # adopt() below overwrites data/dirty/slots from ``state``
            # (the same repad path a cross-world checkpoint load takes),
            # so hand the constructor the old rows as-is — each table is
            # copied ONCE, not twice, inside the latency-sensitive
            # resize window
            self.tables[pname] = ShardedTable(
                old.spec, mesh, axis=self.axis, data=old.data,
                dirty=None, dcn_axis=self.dcn_axis)
        # adopt() re-pads the carried rows, dirty bits, and slots into the
        # new shard multiple; place() re-pins everything to the new
        # mesh's shardings
        self.adopt(state)
        self.place()

    def place(self) -> None:
        """Re-pin every leaf to its sharding (after checkpoint load)."""
        self._step = jnp.asarray(self._step, jnp.int32)
        for k, t in self.tables.items():
            t.place()
            self._slots[k] = jax.tree_util.tree_map(
                lambda s: jax.device_put(jnp.asarray(s), t.sharding)
                if getattr(s, "shape", None) == tuple(t.data.shape)
                else jnp.asarray(s),
                self._slots[k])

    # ------------------------------------------------------------------
    # inside-the-step hooks (all traced)
    # ------------------------------------------------------------------

    def make_proxies(self, feed) -> Dict[Tuple[str, str], Any]:
        """Zeros of each routed lookup's request shape — the differentiable
        stand-ins whose cotangents are the row gradients."""
        out = {}
        for r in self.routes:
            ids = _feed_ids(feed, r)
            out[(r.param, r.layer)] = jnp.zeros(
                ids.shape + (r.dim,), jnp.float32)
        return out

    def make_overrides(self, tables: Dict[str, Any],
                       proxies: Dict[Tuple[str, str], Any]):
        return {
            name: TableProxy(name, self.mesh, self.axis, tables[name],
                             proxies,
                             compute_dtype=self.tables[name].spec.compute_dtype,
                             dcn_axis=self.dcn_axis)
            for name in self.tables
        }

    @staticmethod
    def _dedup_sq(ids, g):
        """Sum of squares of the PER-ROW (duplicate-summed) gradients —
        the mass the dense scatter-add gradient would contribute to a
        global-norm clip, computed without densifying.  Shares
        ``dedup_rows`` with ``Optimizer.sparse_apply_rows`` so the norm
        and the applied update see bit-identical sums."""
        _, sums = dedup_rows(ids, g, sentinel=jnp.iinfo(jnp.int32).max)
        return jnp.sum(jnp.square(sums.astype(jnp.float32)))

    def grad_norm_sq(self, feed, proxy_grads: Dict[Tuple[str, str], Any]):
        """Global-norm contribution of every routed table's row gradients
        (deduped, matching the dense path's norm) — feeds the trainer's
        joint clip so clipping parity holds with single-host training."""
        total = jnp.zeros((), jnp.float32)
        for pname, routes in self.routes_by_param.items():
            ids = jnp.concatenate(
                [_feed_ids(feed, r).reshape(-1) for r in routes])
            g = jnp.concatenate(
                [proxy_grads[(pname, r.layer)].reshape(-1, r.dim)
                 for r in routes])
            total = total + self._dedup_sq(ids, g)
        return total

    def apply_grads(self, state: Dict[str, Any], feed,
                    proxy_grads: Dict[Tuple[str, str], Any], *,
                    finite=None):
        """Push the proxy cotangents into the sharded tables; returns the
        next pserver state pytree.  Pure/traced — called inside the jitted
        step.  ``finite`` is the bad-step guard's predicate: where it is
        False every id of the step becomes the sentinel the row scatter
        already drops, so tables, slots and dirty masks come back unchanged
        at the cost of the step's own N rows, never a pass over a table."""
        step = state["step"] + 1
        lr = self.optimizer.lr_at(step)
        new_tables, new_slots, new_dirty = {}, {}, {}
        for pname, routes in self.routes_by_param.items():
            segs_ids, segs_g = [], []
            for r in routes:
                ids = _feed_ids(feed, r).reshape(-1)
                g = proxy_grads[(pname, r.layer)].reshape(-1, r.dim)
                segs_ids.append(ids)
                segs_g.append(g)
            ids = jnp.concatenate(segs_ids)
            g = jnp.concatenate(segs_g)
            if finite is not None:
                ids = jnp.where(finite, ids, state["tables"][pname].shape[0])
            scale = self.lr_scales.get(pname, 1.0)
            decay = self.decays.get(pname, 0.0) + self.optimizer.l2_rate
            new_tables[pname], new_slots[pname], new_dirty[pname] = (
                sharded_row_update(
                    self.mesh, self.optimizer, state["tables"][pname],
                    state["slots"][pname], state["dirty"][pname], ids, g,
                    axis=self.axis, lr_eff=lr * scale, step=step,
                    decay=decay, dcn_axis=self.dcn_axis))
        if finite is not None:
            step = jnp.where(finite, step, state["step"])
        return {"step": step, "tables": new_tables, "slots": new_slots,
                "dirty": new_dirty}

    # ------------------------------------------------------------------
    # snapshots (serving read path)
    # ------------------------------------------------------------------

    def snapshot(self, save_dir: str, *, reset_dirty: bool = True
                 ) -> Dict[str, str]:
        """Write one incremental snapshot per table under
        ``save_dir/<table>/snap-xxxxx`` (only rows dirty since the last
        snapshot) and clear the dirty masks.  Returns {table: snap_dir}."""
        import os
        import shutil

        from paddle_tpu.pserver.snapshot import (SnapshotError,
                                                 latest_snapshot,
                                                 save_table_snapshot,
                                                 validate_snapshot)

        out = {}
        for pname, t in self.tables.items():
            d = os.path.join(save_dir, pname.strip("_"))
            snap_id = latest_snapshot(d, validate=False) + 1
            out[pname] = save_table_snapshot(
                d, t.spec, t.data, t.dirty, snap_id, shards=t.shards)
            # clear dirty bits only once the published snapshot verifies:
            # rows whose delta never became durable must stay dirty so the
            # NEXT snapshot rewrites them
            reason = validate_snapshot(out[pname])
            if reason is not None:
                # the invalid dir must not keep its chain position, or the
                # retry would publish PAST it where no valid-prefix reader
                # can ever reach — drop it so the next attempt reuses the id
                shutil.rmtree(out[pname], ignore_errors=True)
                raise SnapshotError(
                    f"table {pname!r}: snapshot {out[pname]} failed "
                    f"post-write validation: {reason}")
            if reset_dirty:
                t.dirty = jax.device_put(
                    jnp.zeros_like(t.dirty), t.mask_sharding)
        return out
