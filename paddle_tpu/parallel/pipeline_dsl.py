"""Pipeline parallelism driven from the ``nn`` DSL — ``device_pin`` stage
tags partition a Topology into head -> homogeneous stages -> tail, and the
stages run as the GPipe SPMD program of ``parallel/pipeline.py``.

The reference's per-layer ``device`` attribute dispatches layers to device
threads inside ParallelNeuralNetwork (config_parser.py:1772-1848,
ParallelNeuralNetwork.h:34); here the same config surface — ``device_pin
(layer, "pp:<k>")`` — becomes a *pipeline* partitioning plane: tagged
layers form stage k of a GPipe schedule over a ``stage`` mesh axis, while
untagged layers before/after the pipelined region run replicated (head:
e.g. embeddings; tail: e.g. pooling + readout + cost).

Constraints (validated at construction, inherited from the single-program
GPipe schedule — parallel/pipeline.py):

- stages must be STRUCTURALLY IDENTICAL: same layer types, sizes and
  parameter shapes position-by-position (the canonical homogeneous stack —
  repeated LSTM/transformer blocks).  Flags invisible to the config (e.g.
  ``reverse=`` closures) must also match; only shapes/types are checkable,
  so an alternating-direction stack would silently use stage 0's direction
  — do not tag one.
- the activations crossing each stage boundary must match the head->stage0
  seam structure (same producing-layer positions, same shapes).
- no stateful layers (batch_norm) inside stages: stage state would need a
  per-stage reduction the schedule does not model.
- label/data layers feed the tail directly (they are not pipelined).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.nn.graph import (Act, ApplyContext, LayerOutput, ParamSpec,
                                 Topology, _coerce_feed)
from paddle_tpu.parallel.pipeline import pipeline_apply
from paddle_tpu.utils.error import ConfigError

__all__ = ["PipelinedTopology", "pp_stage"]


def pp_stage(node: LayerOutput, k: int) -> LayerOutput:
    """Tag ``node`` as belonging to pipeline stage ``k`` (sugar over
    ``device_pin(node, f"pp:{k}")``)."""
    node.meta["device"] = f"pp:{k}"
    return node


def _stage_of(layer: LayerOutput) -> Optional[int]:
    tag = layer.meta.get("device")
    if tag is None or not str(tag).startswith("pp:"):
        return None
    return int(str(tag).split(":", 1)[1])


class PipelinedTopology(Topology):
    """A Topology whose ``pp:<k>``-tagged layers execute as a GPipe
    pipeline over ``mesh[stage_axis]``.

    ``init`` returns the stage parameters STACKED on a leading [S] dim
    under stage-0 names (per-stage values keep their own random init);
    ``apply`` runs head -> pipeline_apply -> tail and is differentiable
    end-to-end, so ``SGDTrainer(cost, ..., mesh=mesh, pipeline=...)`` trains
    through it unchanged."""

    def __init__(self, outputs, *, mesh, n_microbatches: int,
                 stage_axis: str = "stage", data_axis: Optional[str] = None):
        from paddle_tpu.parallel.mesh import as_mesh

        super().__init__(outputs)
        self.mesh = mesh = as_mesh(mesh)
        self.n_microbatches = n_microbatches
        self.stage_axis = stage_axis
        self.data_axis = data_axis

        by_stage: Dict[int, List[LayerOutput]] = {}
        for l in self.layers:
            k = _stage_of(l)
            if k is not None:
                by_stage.setdefault(k, []).append(l)
        if not by_stage:
            raise ConfigError("PipelinedTopology: no pp:<k> tagged layers")
        K = len(by_stage)
        if sorted(by_stage) != list(range(K)):
            raise ConfigError(
                f"stage tags must be contiguous pp:0..pp:{K - 1}, got "
                f"{sorted(by_stage)}")
        if mesh.shape[stage_axis] != K:
            raise ConfigError(
                f"{K} stages but mesh axis {stage_axis!r} has "
                f"{mesh.shape[stage_axis]} devices")
        self.stage_layers: List[List[LayerOutput]] = [by_stage[k]
                                                     for k in range(K)]
        stage_set = {id(l) for ls in self.stage_layers for l in ls}

        # head = untagged layers none of whose ancestors are staged;
        # tail = untagged layers with a staged ancestor
        self.head_layers: List[LayerOutput] = []
        self.tail_layers: List[LayerOutput] = []
        downstream: set = set(stage_set)
        for l in self.layers:
            if id(l) in stage_set:
                continue
            if any(id(p) in downstream for p in l.parents):
                downstream.add(id(l))
                self.tail_layers.append(l)
            else:
                self.head_layers.append(l)

        self._validate_and_bind()

    # -- structure ------------------------------------------------------

    def _validate_and_bind(self) -> None:
        stage0 = self.stage_layers[0]
        pos0 = {id(l): i for i, l in enumerate(stage0)}
        for k, layers in enumerate(self.stage_layers[1:], start=1):
            if len(layers) != len(stage0):
                raise ConfigError(
                    f"stage {k} has {len(layers)} layers, stage 0 has "
                    f"{len(stage0)} — stages must be homogeneous")
            for a, b in zip(stage0, layers):
                if a.layer_type != b.layer_type or a.size != b.size:
                    raise ConfigError(
                        f"stage {k} layer {b.name!r} ({b.layer_type}/"
                        f"{b.size}) does not match stage 0's {a.name!r} "
                        f"({a.layer_type}/{a.size})")
                sa = [tuple(s.shape) for s in a.param_specs]
                sb = [tuple(s.shape) for s in b.param_specs]
                if sa != sb:
                    raise ConfigError(
                        f"stage {k} layer {b.name!r} param shapes {sb} != "
                        f"stage 0's {sa}")
                if any(s.is_state for s in a.param_specs):
                    raise ConfigError(
                        f"stateful layer {a.name!r} cannot be pipelined")

        # seam INTO stage 0: parents outside the stage, in first-use order
        def crossings(layers, inside_ids):
            seen, out = set(), []
            for i, l in enumerate(layers):
                for p in l.parents:
                    if id(p) not in inside_ids and id(p) not in seen:
                        seen.add(id(p))
                        out.append((i, p))
            return out

        ids0 = {id(l) for l in stage0}
        self.seam_in: List[Tuple[int, LayerOutput]] = crossings(stage0, ids0)
        # stage k>0 crossings must come from stage k-1 at consistent
        # positions; those positions define the seam OUT of every stage
        out_pos: Optional[List[int]] = None
        for k, layers in enumerate(self.stage_layers[1:], start=1):
            idsk = {id(l) for l in layers}
            cr = crossings(layers, idsk)
            if len(cr) != len(self.seam_in):
                raise ConfigError(
                    f"stage {k} has {len(cr)} boundary crossings, stage 0 "
                    f"has {len(self.seam_in)} — every stage must consume "
                    f"exactly the seam")
            prev_pos = {id(l): i for i, l in enumerate(self.stage_layers[k - 1])}
            pos = []
            for (i_use, p), (i_use0, _p0) in zip(cr, self.seam_in):
                if id(p) not in prev_pos:
                    raise ConfigError(
                        f"stage {k} consumes {p.name!r} which is not in "
                        f"stage {k - 1} — only neighbor-stage activations "
                        f"may cross a pipeline boundary")
                if i_use != i_use0:
                    raise ConfigError(
                        f"stage {k} seam use-position mismatch vs stage 0")
                pos.append(prev_pos[id(p)])
            if out_pos is None:
                out_pos = pos
            elif pos != out_pos:
                raise ConfigError("inconsistent seam positions across stages")
        last = self.stage_layers[-1]
        last_pos = {id(l): i for i, l in enumerate(last)}
        tail_pos = []  # last-stage positions the tail actually consumes
        seen = set()
        for l in self.tail_layers:
            for p in l.parents:
                if id(p) in last_pos and id(p) not in seen:
                    seen.add(id(p))
                    tail_pos.append(last_pos[id(p)])
        if out_pos is None:
            # single stage: the seam out of the pipeline is whatever the
            # tail consumes (there is no next stage to define it)
            out_pos = tail_pos
            if len(out_pos) != len(self.seam_in):
                raise ConfigError(
                    f"single-stage pipeline: tail consumes {len(out_pos)} "
                    f"stage activations but the seam in carries "
                    f"{len(self.seam_in)} — structures must match")
        self.seam_out_pos = out_pos

        # tail may consume only the LAST stage's seam-out layers (plus
        # head/data layers)
        allowed = {id(last[i]) for i in self.seam_out_pos}
        staged = {id(l) for ls in self.stage_layers for l in ls}
        for l in self.tail_layers:
            for p in l.parents:
                if id(p) in staged and id(p) not in allowed:
                    raise ConfigError(
                        f"tail layer {l.name!r} consumes stage-internal "
                        f"activation {p.name!r}; only the final seam "
                        f"crosses out of the pipeline")

        # positional param-name map: stage-0 spec name -> [per-stage names]
        self.stage_param_names: Dict[str, List[str]] = {}
        for li, l0 in enumerate(stage0):
            for si, spec in enumerate(l0.param_specs):
                names = [self.stage_layers[k][li].param_specs[si].name
                         for k in range(len(self.stage_layers))]
                self.stage_param_names[spec.name] = names

        # param_specs: stacked stage-0 specs (leading S), per-stage dropped
        S = len(self.stage_layers)
        dropped = {n for names in self.stage_param_names.values()
                   for n in names[1:]}
        new_specs: Dict[str, ParamSpec] = {}
        for name, spec in self.param_specs.items():
            if name in dropped:
                continue
            if name in self.stage_param_names:
                from dataclasses import replace as _replace

                spec = _replace(spec, shape=(S, *spec.shape))
            new_specs[name] = spec
        self._flat_param_specs = self.param_specs
        self.param_specs = new_specs

    # -- params ---------------------------------------------------------

    def init(self, rng, dtype=None, skip=()):
        # skip (pserver routing) is accepted for Topology-signature parity;
        # stage-stacked params are never routed, so it only affects
        # head/tail layers
        saved = self.param_specs
        self.param_specs = self._flat_param_specs
        try:
            args = (rng,) if dtype is None else (rng, dtype)
            params, state = Topology.init(self, *args, skip=skip)
        finally:
            self.param_specs = saved
        for name0, names in self.stage_param_names.items():
            params[name0] = jnp.stack([params.pop(n) if n != name0
                                       else params[name0] for n in names])
        return params, state

    def unstack_params(self, params: Dict[str, Any]) -> Dict[str, Any]:
        """Stacked params -> the flat per-stage dict of the plain Topology
        (checkpoint/serialization interop, and equivalence testing)."""
        out = dict(params)
        for name0, names in self.stage_param_names.items():
            stacked = out.pop(name0)
            for k, n in enumerate(names):
                out[n] = stacked[k]
        return out

    # -- execution ------------------------------------------------------

    def _run_layers(self, layers, env, all_params, ctx, feed):
        for layer in layers:
            if layer.is_data:
                env[layer.name] = _coerce_feed(layer, feed)
                continue
            parent_acts = [env[p.name] for p in layer.parents]
            local = {s.name: all_params[s.name] for s in layer.param_specs}
            env[layer.name] = layer.forward(ctx, local, *parent_acts)

    def apply(self, params, state, feed, *, train=False, rng=None,
              outputs=None, device_specs=None, param_overrides=None,
              remat_layers=False):
        # param_overrides (the pserver TableProxy hook) is accepted for
        # trainer-signature parity; pipelined stage layers consume plain
        # arrays, so overrides only reach head/tail layers.  remat_layers
        # (--remat): a stage is the recomputation block here
        ctx = ApplyContext(train, rng)
        env: Dict[str, Act] = {}
        stage0 = self.stage_layers[0]
        stacked = {n: params[n] for n in self.stage_param_names}
        flat_state = dict(state)
        all_params = {**params, **flat_state, **(param_overrides or {})}

        self._run_layers(self.head_layers, env, all_params, ctx, feed)

        # auxiliary Act.state (RNN final_h/final_c, attention probs) does
        # NOT cross pipeline boundaries: the seam-in and seam-out trees must
        # have identical structure for the ppermute carry swap, and a head
        # fc act has no state while a stage LSTM act does
        from dataclasses import replace as _dreplace

        def strip(act: Act) -> Act:
            return _dreplace(act, state={})

        xs = tuple(strip(env[p.name]) for _i, p in self.seam_in)

        def stage_fn(w, xs_mb):
            senv = {p.name: a for (_i, p), a in zip(self.seam_in, xs_mb)}
            for layer in stage0:
                parent_acts = [senv[p.name] for p in layer.parents]
                local = {s.name: w[s.name] for s in layer.param_specs}
                senv[layer.name] = layer.forward(ctx, local, *parent_acts)
            return tuple(strip(senv[stage0[i].name])
                         for i in self.seam_out_pos)

        if remat_layers:
            stage_fn = jax.checkpoint(stage_fn)
        ys = pipeline_apply(stage_fn, stacked, xs, mesh=self.mesh,
                            n_microbatches=self.n_microbatches,
                            stage_axis=self.stage_axis,
                            data_axis=self.data_axis)
        last = self.stage_layers[-1]
        for pos, y in zip(self.seam_out_pos, ys):
            env[last[pos].name] = y

        self._run_layers(self.tail_layers, env, all_params, ctx, feed)
        new_state = {**state, **ctx.updated_state}
        result = {name: act for name, act in env.items()}
        if outputs is not None:
            missing = set(outputs) - set(result)
            if missing:
                raise ConfigError(
                    f"unknown/unavailable output layers {sorted(missing)} "
                    f"(stage-internal activations are not exposed)")
        return result, new_state
