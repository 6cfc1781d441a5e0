"""The layer graph core — TPU-native analog of the reference's gserver engine.

Reference architecture: a Python DSL builds a protobuf ModelConfig
(python/paddle/trainer/config_parser.py), C++ instantiates a Layer object per
proto entry into a topologically-ordered NeuralNetwork, and forward/backward
walk that list mutating per-layer Argument buffers
(gserver/gradientmachines/NeuralNetwork.cpp:235-294; layer base
gserver/layers/Layer.h:56-231).

TPU-native architecture: layer functions build a symbolic DAG of
``LayerOutput`` nodes at Python time; ``Topology`` compiles the DAG **once**
into pure functions

    init(rng)                  -> (params, state)
    apply(params, state, feed, train, rng) -> (outputs, new_state)

which jit/grad/shard like any JAX function.  There is no mutable Argument and
no backward pass to write: autodiff derives it, and XLA fuses across layer
boundaries (the fusion the reference's expression templates only did within
one elementwise chain).  Activations between layers are immutable ``Act``
records — the Argument analog (reference: paddle/parameter/Argument.h:29-90)
carrying value + sequence lengths/mask.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.utils.error import ConfigError, ShapeError, layer_scope
from paddle_tpu.utils.registry import Registry

__all__ = [
    "Act",
    "ParamAttr",
    "ParamSpec",
    "LayerOutput",
    "Topology",
    "next_name",
    "reset_naming",
    "naming_scope",
    "device_pin",
    "LAYER_TYPES",
]


def device_pin(node: "LayerOutput", tag: str) -> "LayerOutput":
    """Pin a layer to a model-parallel device group — the per-layer
    ``device`` attribute of the reference's --parallel_nn mode.  ``tag`` is
    resolved to a sharding via ``Topology.apply(device_specs={tag: ...})``;
    the tag round-trips through ModelConfig serialization (LayerConf.device).
    """
    node.meta["device"] = str(tag)
    return node

LAYER_TYPES: Registry = Registry("layer_type")


# ---------------------------------------------------------------------------
# Runtime activation record (Argument analog)
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclass
class Act:
    """Value flowing between layers.

    value: [B, D] (non-seq), [B, T, D] (sequence) or int ids [B, T].
    lengths/mask present iff the activation is a sequence. ``state`` carries
    auxiliary outputs (e.g. RNN final cell state, attention weights).

    Nested (sub)sequences — the subSequenceStartPositions analog (reference:
    paddle/parameter/Argument.h:90,152): value is [B, To, Ti(, D)] with
    ``lengths``/``mask`` indexing the OUTER level (number of sub-sequences)
    and ``sub_lengths`` [B, To] the inner token counts per sub-sequence.
    """

    value: Any
    lengths: Optional[Any] = None
    mask: Optional[Any] = None
    sub_lengths: Optional[Any] = None
    state: Dict[str, Any] = field(default_factory=dict)

    @property
    def is_seq(self) -> bool:
        return self.lengths is not None

    @property
    def is_nested(self) -> bool:
        return self.sub_lengths is not None

    def tree_flatten(self):
        keys = tuple(sorted(self.state))
        children = (self.value, self.lengths, self.mask, self.sub_lengths) + tuple(
            self.state[k] for k in keys
        )
        return children, keys

    @classmethod
    def tree_unflatten(cls, keys, children):
        value, lengths, mask, sub_lengths = children[:4]
        state = dict(zip(keys, children[4:]))
        return cls(value=value, lengths=lengths, mask=mask,
                   sub_lengths=sub_lengths, state=state)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParamAttr:
    """Per-parameter attributes — analog of the reference's ParameterConfig
    (proto/ParameterConfig.proto; python ParamAttr): shared name, init scheme,
    per-param learning-rate scale, decay, static (frozen) flag."""

    name: Optional[str] = None
    initial_std: Optional[float] = None
    initial_mean: float = 0.0
    init: Optional[str] = None  # 'normal' | 'uniform' | 'xavier' | 'zeros' | 'ones'
    learning_rate: float = 1.0
    l2_decay: float = 0.0
    is_static: bool = False
    sparse_grad: bool = False
    # StaticPruningHook analog: fraction of smallest-|w| entries masked to 0
    # after every update (paddle/parameter/ParameterUpdaterHook.cpp:36-78)
    pruning_ratio: float = 0.0


@dataclass(frozen=True)
class ParamSpec:
    name: str
    shape: Tuple[int, ...]
    attr: ParamAttr
    is_state: bool = False  # True for running stats etc. (not optimized)

    def initializer(self) -> Callable:
        attr = self.attr
        kind = attr.init or ("normal" if attr.initial_std is not None else "xavier")

        def init(key, shape, dtype):
            if kind == "zeros":
                return jnp.zeros(shape, dtype)
            if kind == "ones":
                return jnp.ones(shape, dtype)
            if kind == "normal":
                std = attr.initial_std if attr.initial_std is not None else 0.01
                return attr.initial_mean + std * jax.random.normal(key, shape, dtype)
            if kind == "uniform":
                a = attr.initial_std if attr.initial_std is not None else 0.05
                return jax.random.uniform(key, shape, dtype, -a, a)
            # xavier/glorot: std = sqrt(2/(fan_in+fan_out)) — the reference's
            # default weight init is N(0, 1/sqrt(fan_in)); xavier is the
            # better modern default, selectable via attr.init='normal'.
            fan_in = shape[0] if len(shape) > 1 else shape[0]
            fan_out = shape[-1]
            if len(shape) == 4:  # HWIO conv kernels
                rf = shape[0] * shape[1]
                fan_in, fan_out = rf * shape[2], rf * shape[3]
            std = (2.0 / (fan_in + fan_out)) ** 0.5
            return std * jax.random.normal(key, shape, dtype)

        return init


# ---------------------------------------------------------------------------
# Symbolic layer node
# ---------------------------------------------------------------------------

_naming = threading.local()


def next_name(prefix: str) -> str:
    if not hasattr(_naming, "counters"):
        _naming.counters = {}
    c = _naming.counters.get(prefix, 0)
    _naming.counters[prefix] = c + 1
    return f"__{prefix}_{c}__"


def reset_naming() -> None:
    _naming.counters = {}


class naming_scope:
    """Context manager: fresh auto-name counters inside, caller's counters
    restored on exit — so config replay (build_topology) can't perturb a
    user's in-progress graph building."""

    def __enter__(self):
        self._saved = getattr(_naming, "counters", {})
        _naming.counters = {}
        return self

    def __exit__(self, *exc):
        _naming.counters = self._saved
        return False


@dataclass
class LayerOutput:
    """Symbolic node in the layer DAG (the config-time analog of the
    reference's per-layer proto entry + the runtime Layer object)."""

    name: str
    layer_type: str
    size: int
    parents: List["LayerOutput"]
    forward: Callable  # (ctx, params: Dict[str, Array], *parent_acts) -> Act
    param_specs: List[ParamSpec] = field(default_factory=list)
    is_data: bool = False
    data_spec: Optional[dict] = None
    # layer metadata: e.g. {'hw': (H, W)} for image layers so consumers can
    # compute flattened sizes (the reference tracks this in the proto's
    # img_size fields, config_parser.py)
    meta: dict = field(default_factory=dict)

    def __repr__(self) -> str:
        return f"<{self.layer_type} {self.name} size={self.size}>"

    # Arithmetic sugar on symbolic nodes
    def __add__(self, other: "LayerOutput") -> "LayerOutput":
        from paddle_tpu.nn.layers import addto

        return addto(input=[self, other])


class ApplyContext:
    """Per-apply runtime context: train flag and a split-on-demand RNG."""

    def __init__(self, train: bool, rng: Optional[jax.Array]):
        self.train = train
        self._rng = rng
        self.updated_state: Dict[str, Any] = {}

    def next_rng(self) -> jax.Array:
        if self._rng is None:
            self._rng = jax.random.PRNGKey(0)
        self._rng, out = jax.random.split(self._rng)
        return out


# ---------------------------------------------------------------------------
# Topology: DAG -> pure functions
# ---------------------------------------------------------------------------


class Topology:
    """Compiled view of a layer DAG.

    Analog of the reference's Topology over the ModelConfig proto
    (python/paddle/v2/topology.py:48) + the C++ NeuralNetwork executor — but
    compilation happens once at Python level and execution is a pure function
    suitable for jit/pjit/grad.
    """

    #: layer types with a sparse-input compute path; anything else consuming
    #: a sparse data layer is a config error (it would misread the id array)
    SPARSE_AWARE = frozenset({"fc", "selective_fc"})

    def __init__(self, outputs: Sequence[LayerOutput] | LayerOutput):
        if isinstance(outputs, LayerOutput):
            outputs = [outputs]
        self.outputs: List[LayerOutput] = list(outputs)
        self.layers: List[LayerOutput] = self._toposort(self.outputs)
        self.data_layers: List[LayerOutput] = [l for l in self.layers if l.is_data]
        for layer in self.layers:
            for p in layer.parents:
                if p.meta.get("sparse") and layer.layer_type not in self.SPARSE_AWARE:
                    raise ConfigError(
                        f"layer {layer.name!r} ({layer.layer_type}) cannot "
                        f"consume sparse input {p.name!r}; sparse-aware "
                        f"layers: {sorted(self.SPARSE_AWARE)}")
        self.param_specs: Dict[str, ParamSpec] = {}
        for layer in self.layers:
            for spec in layer.param_specs:
                prev = self.param_specs.get(spec.name)
                if prev is not None and prev.shape != spec.shape:
                    raise ConfigError(
                        f"shared parameter {spec.name!r} has conflicting shapes "
                        f"{prev.shape} vs {spec.shape}"
                    )
                self.param_specs.setdefault(spec.name, spec)

    @staticmethod
    def _toposort(outputs: Sequence[LayerOutput]) -> List[LayerOutput]:
        order: List[LayerOutput] = []
        seen: Dict[int, int] = {}  # id -> 0 visiting, 1 done

        def visit(node: LayerOutput) -> None:
            mark = seen.get(id(node))
            if mark == 1:
                return
            if mark == 0:
                raise ConfigError(f"cycle in layer graph at {node.name!r}")
            seen[id(node)] = 0
            for p in node.parents:
                visit(p)
            seen[id(node)] = 1
            order.append(node)
            # layers that asked to run right after this one, ahead of
            # whatever else reads it (an early router: nn.expert_mlp); one
            # that is being visited got here through this parent and
            # follows it anyway
            for nxt in node.meta.get("run_next", ()):
                if seen.get(id(nxt)) != 0:
                    visit(nxt)

        for out in outputs:
            visit(out)
        names = {}
        for l in order:
            if l.name in names and names[l.name] is not l:
                raise ConfigError(f"duplicate layer name {l.name!r}")
            names[l.name] = l
        return order

    # -- init ---------------------------------------------------------------

    def init(self, rng: jax.Array, dtype=None,
             skip: Sequence[str] = ()) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        """Create (params, state) pytrees.

        ``skip`` names parameters NOT to materialize (the pserver tier's
        hook: a mesh-sharded table must never exist dense on one host —
        the tier creates it shard-locally instead).  Key assignment stays
        identical either way: every spec still consumes its split, so the
        remaining params init to the same values with or without skips."""
        from paddle_tpu.ops.numerics import param_dtype

        dtype = dtype or param_dtype()
        skipped = set(skip)
        params: Dict[str, Any] = {}
        state: Dict[str, Any] = {}
        specs = sorted(self.param_specs.values(), key=lambda s: s.name)
        keys = jax.random.split(rng, max(len(specs), 1))
        for key, spec in zip(keys, specs):
            if spec.name in skipped:
                continue
            arr = spec.initializer()(key, spec.shape, dtype)
            (state if spec.is_state else params)[spec.name] = arr
        return params, state

    # -- apply --------------------------------------------------------------

    def apply(
        self,
        params: Dict[str, Any],
        state: Dict[str, Any],
        feed: Dict[str, Any],
        *,
        train: bool = False,
        rng: Optional[jax.Array] = None,
        outputs: Optional[Sequence[str]] = None,
        device_specs: Optional[Dict[str, Any]] = None,
        param_overrides: Optional[Dict[str, Any]] = None,
        remat_layers: bool = False,
    ) -> Tuple[Dict[str, Act], Dict[str, Any]]:
        """Run the graph. ``feed`` maps data-layer name -> Act | array |
        (value, lengths). Returns ({layer_name: Act}, new_state).

        ``param_overrides`` substitutes parameter VALUES by name for this
        apply — the pserver tier's hook: a sharded-table parameter is
        removed from ``params`` and handed in here as a ``TableProxy``
        (paddle_tpu/pserver/tier.py), so layers consume it without the
        table ever entering the differentiated pytree.

        ``device_specs`` is the model-parallel pinning plane — the analog of
        the reference's per-layer ``device`` attribute dispatched by
        ParallelNeuralNetwork (ParallelNeuralNetwork.h:34,
        config_parser.py:1772-1848).  Layers tagged via ``device_pin(node,
        tag)`` get ``lax.with_sharding_constraint(value, device_specs[tag])``
        on their output — XLA/GSPMD then places per-layer compute on the
        matching mesh shards instead of spawning per-device threads.

        Recomputation: layers marked as one block (``nn.remat_block``) run
        under one ``jax.checkpoint``; ``remat_layers`` makes every layer
        that is in no block a block of its own (``SGDTrainer(remat=True)``,
        ``--remat``)."""
        ctx = ApplyContext(train, rng)
        env: Dict[str, Act] = {}
        all_params = {**params, **state, **(param_overrides or {})}
        want = set(outputs) if outputs is not None else None
        needed = self.layers if want is None else self._needed_layers(want)

        def block_of(layer):
            if layer.is_data:
                return None
            return layer.meta.get("remat") or (
                layer.name if remat_layers else None)

        done = set()
        for layer in needed:
            tag = block_of(layer)
            if tag is None:
                env[layer.name] = self._run_layer(
                    layer, env, all_params, ctx, feed, device_specs)
            elif tag not in done:
                done.add(tag)
                self._run_remat_block(
                    tag, [l for l in needed if block_of(l) == tag], env,
                    all_params, ctx, feed, device_specs)
        new_state = {**state, **ctx.updated_state}
        result = {l.name: env[l.name] for l in self.layers if l.name in env}
        return result, new_state

    @staticmethod
    def _run_layer(layer, env, all_params, ctx, feed, device_specs) -> Act:
        # named_scope: the device trace names each operation by its layer
        with layer_scope(layer.name), jax.named_scope(layer.name):
            if layer.is_data:
                act = _coerce_feed(layer, feed)
            else:
                parent_acts = [env[p.name] for p in layer.parents]
                local = {s.name: all_params[s.name] for s in layer.param_specs}
                act = layer.forward(ctx, local, *parent_acts)
            tag = layer.meta.get("device")
            if device_specs and tag is not None and tag in device_specs:
                act = replace(
                    act,
                    value=jax.lax.with_sharding_constraint(
                        act.value, device_specs[tag]
                    ),
                )
        return act

    def _run_remat_block(self, tag, block, env, all_params, ctx, feed,
                         device_specs) -> None:
        """The layers of one recomputation block (``nn.remat_block``, or one
        layer under ``remat_layers``) under ONE ``jax.checkpoint``: the
        backward pass holds what the block reads from outside it and
        recomputes its layers, so the activations of one block at a time
        are live, not of the whole stack."""
        inside = {l.name for l in block}
        reads = sorted({p.name for l in block for p in l.parents
                        if p.name not in inside})
        late = [n for n in reads if n not in env]
        if late:
            raise ConfigError(
                f"recomputation block {tag!r} is not closed: it reads "
                f"{late}, computed after its first layer {block[0].name!r}")
        local = {s.name: all_params[s.name]
                 for l in block for s in l.param_specs}
        if any(hasattr(v, "pserver_lookup") for v in local.values()):
            # a table routed through the pserver tier is a proxy, not an
            # array jax.checkpoint could take: its layers run as they are
            for l in block:
                env[l.name] = self._run_layer(l, env, all_params, ctx, feed,
                                              device_specs)
            return

        def run(local, read, key):
            sub = ApplyContext(ctx.train, key)
            acts = dict(read)
            for l in block:
                acts[l.name] = self._run_layer(l, acts, local, sub, feed,
                                               device_specs)
            return {l.name: acts[l.name] for l in block}, sub.updated_state

        # what an op names "remat_keep" (attention's output, a routing's
        # sort) is held with the block's inputs and not computed twice
        keep = jax.checkpoint_policies.save_only_these_names("remat_keep")
        acts, updated = jax.checkpoint(run, policy=keep)(
            local, {n: env[n] for n in reads},
            ctx.next_rng() if ctx.train else None)
        env.update(acts)
        ctx.updated_state.update(updated)

    def _needed_layers(self, want: set) -> List[LayerOutput]:
        by_name = {l.name: l for l in self.layers}
        missing = want - set(by_name)
        if missing:
            raise ConfigError(f"unknown output layers {sorted(missing)}")
        return Topology._toposort([by_name[n] for n in want])

    # -- convenience --------------------------------------------------------

    def output_names(self) -> List[str]:
        return [o.name for o in self.outputs]

    def summary(self) -> str:
        rows = ["%-28s %-20s %8s  %s" % ("name", "type", "size", "parents")]
        for l in self.layers:
            rows.append(
                "%-28s %-20s %8d  %s"
                % (l.name, l.layer_type, l.size, ",".join(p.name for p in l.parents))
            )
        n_params = sum(
            int(jnp.prod(jnp.array(s.shape)))
            for s in self.param_specs.values()
            if not s.is_state
        )
        rows.append(f"total parameters: {n_params}")
        return "\n".join(rows)


def _coerce_feed(layer: LayerOutput, feed: Dict[str, Any]) -> Act:
    if layer.name not in feed:
        raise ConfigError(f"missing feed for data layer {layer.name!r}")
    v = feed[layer.name]
    sparse = (layer.data_spec or {}).get("sparse")
    if sparse and (layer.data_spec or {}).get("is_seq") and not isinstance(v, Act):
        # sparse SEQUENCE slots (one bag per timestep): (ids [B,T,N],
        # nnz [B,T], lengths [B]) for binary, + weights [B,T,N] before nnz
        # for float — reference sparse_*_vector_sequence
        # (python/paddle/trainer/PyDataProvider2.py:75-145)
        if not isinstance(v, tuple) or len(v) not in (3, 4):
            raise ConfigError(
                f"sparse sequence data layer {layer.name!r} expects "
                f"(ids, nnz, lengths) or (ids, weights, nnz, lengths), got "
                f"{type(v).__name__} of len "
                f"{len(v) if isinstance(v, tuple) else '?'}")
        ids = jnp.asarray(v[0])
        nnz = jnp.asarray(v[-2])
        lengths = jnp.asarray(v[-1])
        valid = (jnp.arange(ids.shape[-1])[None, None, :]
                 < nnz[:, :, None]).astype(jnp.float32)
        weights = jnp.asarray(v[1]) if len(v) == 4 else valid
        from paddle_tpu.ops.sequence import mask_from_lengths

        return Act(value=ids, lengths=lengths,
                   mask=mask_from_lengths(lengths, ids.shape[1]),
                   state={"weights": weights, "nnz_mask": valid})
    if sparse and not isinstance(v, Act):
        # padded COO rows: (ids, nnz) for binary, (ids, weights, nnz) for float
        if not isinstance(v, tuple) or len(v) not in (2, 3):
            raise ConfigError(
                f"sparse data layer {layer.name!r} expects (ids, nnz) or "
                f"(ids, weights, nnz), got {type(v).__name__}")
        ids = jnp.asarray(v[0])
        nnz = jnp.asarray(v[-1])
        valid = jnp.arange(ids.shape[1])[None, :] < nnz[:, None]
        if len(v) == 3:
            weights = jnp.asarray(v[1])
        else:
            weights = valid.astype(jnp.float32)
        return Act(value=ids, mask=valid.astype(jnp.float32),
                   state={"weights": weights})
    if isinstance(v, Act):
        act = v
    elif isinstance(v, tuple) and len(v) == 3 and (layer.data_spec or {}).get("nested"):
        value, lengths, sub_lengths = v
        act = Act(value=jnp.asarray(value), lengths=jnp.asarray(lengths),
                  sub_lengths=jnp.asarray(sub_lengths))
    elif isinstance(v, tuple) and len(v) == 5:
        # PACKED sequence slot (datapipe/packing.py, --data_pack): several
        # whole sequences share the row; seg_ids/positions/seg_lengths ride
        # Act.state and every packing-aware layer (RNN carry resets,
        # per-segment pooling, fenced context windows) reads them there
        value, lengths, seg_ids, positions, seg_lengths = v
        act = Act(value=jnp.asarray(value), lengths=jnp.asarray(lengths),
                  state={"seg_ids": jnp.asarray(seg_ids),
                         "positions": jnp.asarray(positions),
                         "seg_lengths": jnp.asarray(seg_lengths)})
    elif isinstance(v, tuple):
        value, lengths = v
        act = Act(value=jnp.asarray(value), lengths=jnp.asarray(lengths))
    else:
        act = Act(value=jnp.asarray(v))
    if act.is_seq and act.mask is None:
        from paddle_tpu.ops.sequence import mask_from_lengths

        T = act.value.shape[1]
        act = replace(act, mask=mask_from_lengths(act.lengths, T))
    return act
