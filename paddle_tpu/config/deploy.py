"""Deploy bundles — analog of the reference's MergeModel + inference path.

Reference: ``MergeModel`` packs the model config proto and all trained
parameters into one file for deployment (paddle/trainer/MergeModel.cpp); the
C API then loads it and runs forward (paddle/capi/gradient_machine.h:27-59).

Here a bundle is a single ``.ptz`` zip: ``model.pb`` (binary ModelConfig,
paddle_tpu/proto/model_config.proto) + ``params.npz``/``state.npz``.
``InferenceModel`` rebuilds the Topology from the proto (no user code needed)
and serves a jitted forward — consumed by the Python API below and by the C
inference API (csrc/capi.cc).
"""

from __future__ import annotations

import io
import json
import os
import threading
import zipfile
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.config.config_parser import build_topology, dump_model_config
from paddle_tpu.nn.graph import Topology
from paddle_tpu.proto import model_config_pb2 as pb

__all__ = ["merge_model", "InferenceModel", "load_inference_model",
           "export_aot", "export_aot_hlo", "load_exported",
           "BundleCorruptError", "quantize_params", "feed_signature"]

_MAGIC = "paddle_tpu.bundle.v1"


class BundleCorruptError(RuntimeError):
    """A ``.ptz`` bundle failed integrity validation: truncated/not a zip,
    a member missing, a member's CRC or compressed stream damaged, or a
    payload that no longer parses.  ``member`` names the failing zip
    member (None when the archive itself is unreadable) so storage-tier
    faults are attributed precisely — the serving tier's analog of the
    checkpoint manifest's CRC validation (docs/resilience.md)."""

    def __init__(self, message: str, *, path: str = "",
                 member: Optional[str] = None) -> None:
        super().__init__(message)
        self.path = path
        self.member = member


def _npz_bytes(tree: Dict[str, Any]) -> bytes:
    from paddle_tpu.trainer.checkpoint import npz_safe

    buf = io.BytesIO()
    np.savez_compressed(buf, **{k: npz_safe(v) for k, v in tree.items()})
    return buf.getvalue()


def _npz_load(data: bytes) -> Dict[str, np.ndarray]:
    return dict(np.load(io.BytesIO(data), allow_pickle=False))


# ---------------------------------------------------------------------------
# weight quantization (docs/deploy.md) — bundle export modes
# ---------------------------------------------------------------------------

_QUANT_MODES = ("bf16", "int8")
#: scale arrays ride the SAME npz as their quantized array, keyed by suffix
_SCALE_SUFFIX = "::scale"
#: int8 only pays for itself on matmul-sized tensors; smaller floats
#: (biases, gains, BN stats) go bf16 — their error budget is tighter and
#: their byte share is negligible
_INT8_MIN_SIZE = 256


def _bf16_dtype() -> np.dtype:
    import ml_dtypes

    return np.dtype(ml_dtypes.bfloat16)


def quantize_params(params: Dict[str, Any], mode: str):
    """Quantize a parameter tree for bundle storage.

    ``mode="bf16"`` stores every floating array as bfloat16 raw bits
    (uint16 in the npz — ``npz_safe`` would widen real bf16 back to f32);
    ``mode="int8"`` additionally stores matmul-sized floats (ndim>=2,
    size>=_INT8_MIN_SIZE) as symmetric per-channel int8:
    ``q = round(w / scale)`` clipped to [-127, 127] with
    ``scale = maxabs_channel / 127`` over the LAST axis (output features
    for fc and HWIO conv filters alike), the scale array stored alongside
    under ``<name>::scale``.  Integer arrays pass through.  Returns
    ``(stored, qmeta)`` where ``qmeta`` is the manifest's per-array
    dequantization recipe.
    """
    if mode not in _QUANT_MODES:
        raise ValueError(f"quantize mode must be one of {_QUANT_MODES}, "
                         f"got {mode!r}")
    stored: Dict[str, np.ndarray] = {}
    qmeta: Dict[str, dict] = {}
    for name, v in params.items():
        if _SCALE_SUFFIX in name:
            raise ValueError(f"parameter name {name!r} collides with the "
                             f"quantization scale suffix")
        arr = np.asarray(v)
        orig = str(arr.dtype)
        if arr.dtype.kind != "f" and orig in np.sctypeDict:
            stored[name] = arr  # integer / bool arrays pass through
            continue
        a = np.asarray(arr, dtype=np.float32)
        if mode == "int8" and a.ndim >= 2 and a.size >= _INT8_MIN_SIZE:
            absmax = np.max(np.abs(a), axis=tuple(range(a.ndim - 1)),
                            keepdims=True)
            scale = (absmax / 127.0).astype(np.float32)
            scale[scale == 0.0] = 1.0  # all-zero channels: q=0, any scale
            stored[name] = np.clip(np.round(a / scale), -127, 127
                                   ).astype(np.int8)
            stored[name + _SCALE_SUFFIX] = scale
            qmeta[name] = {"mode": "int8", "orig_dtype": orig}
        else:
            stored[name] = a.astype(_bf16_dtype()).view(np.uint16)
            qmeta[name] = {"mode": "bf16", "orig_dtype": orig}
    return stored, qmeta


def _dequantize_params(raw: Dict[str, np.ndarray], qmeta: Dict[str, dict],
                       *, path: str = "", member: str = "params.npz",
                       keep_int8: bool = False):
    """Stored npz dict -> f32 arrays, validating every quantized array's
    recipe: a missing/mis-shaped/non-finite scale member raises a typed
    :class:`BundleCorruptError` NAMING the failing member, exactly like
    the zip-level CRC attribution.  With ``keep_int8`` the int8 arrays
    stay quantized and are returned separately as ``{name: (q, scale)}``
    for in-trace dequantization (the HBM-resident-int8 serving mode)."""
    out: Dict[str, np.ndarray] = {}
    int8: Dict[str, tuple] = {}
    for name, arr in raw.items():
        if name.endswith(_SCALE_SUFFIX):
            continue
        meta = qmeta.get(name)
        if meta is None:
            out[name] = arr
            continue
        mode = meta.get("mode")
        if mode == "bf16":
            if arr.dtype != np.uint16:
                raise BundleCorruptError(
                    f"bundle {path!r}: bf16-quantized array {name!r} stored "
                    f"as {arr.dtype} (expected uint16 raw bits)",
                    path=path, member=f"{member}:{name}")
            out[name] = arr.view(_bf16_dtype()).astype(np.float32)
        elif mode == "int8":
            sname = name + _SCALE_SUFFIX
            smember = f"{member}:{sname}"
            scale = raw.get(sname)
            if scale is None:
                raise BundleCorruptError(
                    f"bundle {path!r}: int8-quantized array {name!r} is "
                    f"missing its scale member {sname!r}",
                    path=path, member=smember)
            if (scale.dtype != np.float32 or scale.ndim != arr.ndim
                    or scale.shape[-1] != arr.shape[-1]
                    or any(d != 1 for d in scale.shape[:-1])):
                raise BundleCorruptError(
                    f"bundle {path!r}: scale member {sname!r} has "
                    f"shape {scale.shape} dtype {scale.dtype} — expected "
                    f"f32 {(1,) * (arr.ndim - 1) + (arr.shape[-1],)}",
                    path=path, member=smember)
            if not np.all(np.isfinite(scale)) or np.any(scale <= 0):
                raise BundleCorruptError(
                    f"bundle {path!r}: scale member {sname!r} carries "
                    f"non-finite or non-positive values",
                    path=path, member=smember)
            if arr.dtype != np.int8:
                raise BundleCorruptError(
                    f"bundle {path!r}: int8-quantized array {name!r} "
                    f"stored as {arr.dtype}",
                    path=path, member=f"{member}:{name}")
            if keep_int8:
                int8[name] = (arr, scale)
                out[name] = arr  # placeholder; __init__ places q directly
            else:
                out[name] = arr.astype(np.float32) * scale
        else:
            raise BundleCorruptError(
                f"bundle {path!r}: unknown quantize mode {mode!r} for "
                f"array {name!r}", path=path, member=f"{member}:{name}")
    return out, int8


def _quant_error_gate(topology, params, deq, state, outs: List[str],
                      tol: float, mode: str) -> float:
    """Max-abs-error check of the dequantized forward against the f32
    oracle over a synthetic randomized feed sweep — every quantized
    export must pass this before the bundle is written (the deploy-time
    analog of the checkpoint CRC gate: the artifact is proven servable
    at export, not discovered broken at the first reply)."""
    from paddle_tpu.nn.feeds import example_feed

    def fwd(p, feed):
        acts, _ = topology.apply(p, state or {}, feed, train=False,
                                 outputs=outs)
        return tuple(acts[n].value for n in outs)

    fwd_j = jax.jit(fwd)
    worst = 0.0
    for i in range(3):
        feed = example_feed(topology, batch=2,
                            rng=np.random.RandomState(i))
        ref = fwd_j(params, feed)
        got = fwd_j(deq, feed)
        for a, b in zip(ref, got):
            err = float(np.max(np.abs(np.asarray(a, np.float32)
                                      - np.asarray(b, np.float32))))
            worst = max(worst, err)
    if not np.isfinite(worst) or worst > tol:
        raise ValueError(
            f"quantize={mode!r} export rejected: max abs output error "
            f"{worst:.4g} vs the f32 oracle exceeds tolerance {tol:g} "
            f"over the synthetic sweep — this model does not survive "
            f"{mode} weights (raise quantize_tol only if the serving "
            f"consumer tolerates it)")
    return worst


def feed_signature(feed: Dict[str, Any]) -> tuple:
    """Canonical (hashable) shape+dtype signature of a feed — the unit
    the compile cache and the AOT hot path key on.  Tuple feeds keep
    their arity so ``(values,)`` never aliases a bare array."""
    sig = []
    for k in sorted(feed):
        v = feed[k]
        parts = v if isinstance(v, tuple) else (v,)
        sig.append((k, len(parts) if isinstance(v, tuple) else 0,
                    tuple((tuple(np.shape(p)), str(np.asarray(p).dtype))
                          for p in parts)))
    return tuple(sig)


def merge_model(
    path: str,
    topology: Topology,
    params: Dict[str, Any],
    state: Optional[Dict[str, Any]] = None,
    *,
    name: str = "model",
    meta: Optional[dict] = None,
    example_feed: Optional[Dict[str, Any]] = None,
    quantize: Optional[str] = None,
    quantize_tol: float = 0.05,
) -> str:
    """Write config + parameters as one deployable file.

    With ``example_feed`` the inference forward is additionally traced
    through the lint auditor (paddle_tpu.analysis) and the findings ride
    the bundle manifest under ``"lint"`` — the deploy-time guardrail
    analog of the reference's eager config validation.

    ``quantize`` selects a weight-compression export mode (docs/deploy.md;
    ``None`` reads ``--deploy_quantize``): ``"bf16"`` halves the weight
    payload, ``"int8"`` stores matmul-sized tensors as symmetric
    per-channel int8 (~4x smaller) with their scales alongside.  Every
    quantized export is GATED: the dequantized forward must stay within
    ``quantize_tol`` max-abs output error of the f32 oracle over a
    synthetic randomized feed sweep, or the export raises instead of
    writing a bundle that would serve degraded predictions."""
    if quantize is None:
        from paddle_tpu.utils.flags import FLAGS

        quantize = FLAGS.deploy_quantize or None
    if quantize is not None and quantize not in _QUANT_MODES:
        raise ValueError(f"quantize must be one of {_QUANT_MODES} (or "
                         f"None/'' for f32), got {quantize!r}")
    mc = dump_model_config(topology, name)
    need = {n for n, s in topology.param_specs.items() if not s.is_state}
    missing = sorted(need - set(params))
    if missing:
        raise ValueError(f"merge_model: params dict is missing {missing}")
    need_state = {n for n, s in topology.param_specs.items() if s.is_state}
    missing_state = sorted(need_state - set(state or {}))
    if missing_state:
        raise ValueError(f"merge_model: state dict is missing {missing_state}")
    manifest = {
        **(meta or {}),
        # reserved keys win over user meta
        "magic": _MAGIC,
        "name": name,
        "outputs": list(mc.output_layer_names),
        "inputs": list(mc.input_layer_names),
    }
    if example_feed is not None:
        outs = list(mc.output_layer_names)

        def fwd(params, state, feed):
            acts, _ = topology.apply(params, state, feed, train=False,
                                     outputs=outs)
            return tuple(acts[n].value for n in outs)

        manifest["lint"] = _audit_export(
            fwd, (params, state or {}, example_feed), f"{name}:forward")
    stored = params
    if quantize is not None:
        stored, qmeta = quantize_params(params, quantize)
        # gate against the SAME dequantization the loader runs — the
        # recipe proven here is the recipe served
        deq, _ = _dequantize_params(stored, qmeta)
        err = _quant_error_gate(topology, params, deq, state,
                                list(mc.output_layer_names),
                                quantize_tol, quantize)
        manifest["quantize"] = {"mode": quantize, "tol": quantize_tol,
                                "max_abs_err": round(err, 8),
                                "arrays": qmeta}
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as z:
        z.writestr("manifest.json", json.dumps(manifest, indent=1))
        z.writestr("model.pb", mc.SerializeToString())
        z.writestr("params.npz", _npz_bytes(stored) if quantize is None
                   else _raw_npz_bytes(stored))
        if state:
            z.writestr("state.npz", _npz_bytes(state))
    return path


def _raw_npz_bytes(tree: Dict[str, np.ndarray]) -> bytes:
    """Quantized trees are already npz-storable (int8 / uint16 bits /
    f32) — ``npz_safe`` widening would undo the compression."""
    buf = io.BytesIO()
    np.savez_compressed(buf, **tree)
    return buf.getvalue()


class InferenceModel:
    """A rebuilt model serving jitted forward passes from a bundle.

    ``int8`` (``{name: (q, scale)}``) keeps those parameters quantized in
    HBM and dequantizes them *in-trace* to the compute dtype — the weights
    never materialize at f32 width on device.  ``fingerprint`` identifies
    the model for the compile cache; parameters ride every compiled call
    as ARGUMENTS, so the architecture-level default (config proto + leaf
    shapes/dtypes) is the correct executable identity."""

    def __init__(self, mc: pb.ModelConfig, params, state, manifest: dict,
                 *, fingerprint: Optional[str] = None,
                 int8: Optional[Dict[str, tuple]] = None):
        self.model_config = mc
        self.topology = build_topology(mc)
        self.manifest = manifest
        if mc.dtype_policy:
            from paddle_tpu.ops.numerics import compute_dtype
            from paddle_tpu.utils import logger

            local = str(np.dtype(compute_dtype()))
            if local != mc.dtype_policy:
                logger.warning(
                    "model bundle %r was exported under compute_dtype=%s but "
                    "this process uses %s — predictions may differ from "
                    "training; set FLAGS.compute_dtype=%r to match",
                    manifest.get("name", "?"), mc.dtype_policy, local,
                    mc.dtype_policy,
                )
        # abstract init: learn names/dtypes without materializing random
        # weights, then place loaded arrays on device once (resident across
        # infer() calls), cast to the topology's parameter dtype
        init_p, init_s = jax.eval_shape(
            lambda k: self.topology.init(k), jax.random.PRNGKey(0)
        )
        missing = sorted(set(init_p) - set(params))
        if missing:
            raise ValueError(
                f"model bundle is missing parameters {missing} — was it "
                "written by an older/incompatible build?"
            )
        missing_state = sorted(set(init_s) - set(state))
        if missing_state:
            raise ValueError(
                f"model bundle is missing state arrays {missing_state}"
            )
        int8 = int8 or {}
        self._int8 = tuple(sorted(int8))
        self.params = {}
        for k, v in init_p.items():
            if k in int8:
                q, scale = int8[k]
                # int8 stays int8 in HBM; the scale rides the params tree
                # (an argument of every compiled call, never a folded
                # constant) and _make_run dequantizes in-trace
                self.params[k] = jax.device_put(jnp.asarray(q, jnp.int8))
                self.params[k + _SCALE_SUFFIX] = jax.device_put(
                    jnp.asarray(scale, jnp.float32))
            else:
                self.params[k] = jax.device_put(
                    jnp.asarray(params[k], dtype=v.dtype))
        self.state = {
            k: jax.device_put(jnp.asarray(state[k], dtype=v.dtype))
            for k, v in init_s.items()
        }
        if fingerprint is None:
            import hashlib

            h = hashlib.sha256(mc.SerializeToString())
            for k in sorted(self.params):
                a = self.params[k]
                h.update(f"{k}:{tuple(a.shape)}:{a.dtype}".encode())
            fingerprint = h.hexdigest()[:32]
        self.fingerprint = fingerprint
        #: source artifact path (set by load_inference_model)
        self.bundle_path = ""
        #: XLA compiles this process actually paid (prime misses + cold
        #: infer signatures) — the cold-start acceptance counter
        self.compile_events = 0
        #: exact-signature AOT executables installed by prime(); the
        #: infer hot path consults this before the jit table
        self._aot: Dict[tuple, Any] = {}
        self._fns: Dict[tuple, Any] = {}
        #: required-input-slot sets per output tuple — the topology walk
        #: is a pure function of the names, so the serving hot path (one
        #: infer per coalesced batch) must not re-walk the graph per call
        self._needed_slots: Dict[tuple, frozenset] = {}
        #: zero-row replies per (names, per-row feed shapes) — eval_shape
        #: is a full trace; a trickle of empty requests must not re-pay it
        self._empty_cache: Dict[tuple, Dict[str, np.ndarray]] = {}
        # serializes compile-cache misses only: N threads hammering one
        # model (the serving worker + callers) race on dict insert and
        # would otherwise trace the same signature concurrently; the hot
        # path (cache hit) stays lock-free — dict reads are atomic and
        # jitted calls are thread-safe
        self._fns_lock = threading.Lock()

    @property
    def input_names(self) -> List[str]:
        return list(self.model_config.input_layer_names)

    @property
    def output_names(self) -> List[str]:
        return list(self.model_config.output_layer_names)

    def _check_feed(self, feed: Dict[str, Any], names: tuple) -> None:
        # only the data layers REACHABLE from the requested outputs are
        # required (a classifier bundle serves 'out' without its training
        # 'label' slot); a miss is named instead of surfacing as a
        # ConfigError deep inside the jitted apply.  The walk is cached
        # per output tuple — the serving worker calls infer once per
        # batch and must not pay O(graph) Python per call.
        need = self._needed_slots.get(names)
        if need is None:
            needed = self.topology._needed_layers(set(names))
            need = frozenset(l.name for l in needed if l.is_data)
            self._needed_slots[names] = need
        missing = sorted(need - set(feed))
        if missing:
            raise ValueError(
                f"feed is missing input slot(s) {missing}; outputs "
                f"{list(names)} need inputs {sorted(need)}")

    def _make_run(self, names: tuple):
        int8_names = self._int8

        def run(params, state, feed):
            if int8_names:
                from paddle_tpu.ops.numerics import compute_dtype

                cd = compute_dtype()
                params = dict(params)
                for n in int8_names:
                    scale = params.pop(n + _SCALE_SUFFIX)
                    params[n] = params[n].astype(cd) * scale.astype(cd)
            outs, _ = self.topology.apply(
                params, state, feed, train=False, outputs=list(names)
            )
            return {n: outs[n].value for n in names}

        return run

    def _int8_gate(self) -> bool:
        """The in-trace-dequantize admission gate: the compiled forward
        must audit clean for dtype-promotion and constant-bloat (an int8
        table accidentally materialized as f32 *constants* is exactly
        what the constant-bloat check catches), and under ``--amp`` the
        amp-matmul auditor must find no f32 MXU regression — otherwise
        the loader falls back to load-time dequantization."""
        from paddle_tpu.analysis import audit_fn, errors_summary
        from paddle_tpu.nn.feeds import example_feed
        from paddle_tpu.ops.numerics import amp_enabled
        from paddle_tpu.utils import logger

        names = tuple(self.output_names)
        run = self._make_run(names)
        feed = example_feed(self.topology)
        try:
            findings = audit_fn(run, self.params, self.state, feed,
                                label="int8_in_trace",
                                checks=["dtype-promotion", "constant-bloat"])
            if amp_enabled():
                from paddle_tpu.analysis.jaxpr_audit import audit_amp_matmuls

                closed = jax.make_jaxpr(run)(self.params, self.state, feed)
                findings += audit_amp_matmuls(closed, label="int8_in_trace")
        except Exception as e:  # noqa: BLE001 — an unauditable trace fails
            logger.warning("int8 in-trace gate could not audit the forward "
                           "(%s: %s)", type(e).__name__, e)
            return False
        bad = errors_summary(findings)
        if bad:
            logger.warning("int8 in-trace gate failed: %s", bad)
            return False
        return True

    def prime(self, feed: Dict[str, Any],
              outputs: Optional[Sequence[str]] = None,
              cache=None) -> str:
        """Compile-or-load the exact-signature AOT executable for this
        feed shape — the warmup unit of the serving readiness gate
        (docs/deploy.md).  With a compile ``cache`` a previously-warmed
        signature LOADS in milliseconds instead of re-running XLA;
        the loaded executable is smoke-called once before it is trusted
        (a stale or wrong entry becomes a fresh compile, never a wrong
        reply).  Returns ``"warm"`` (already primed), ``"hit"`` (cache
        load), ``"miss"`` (cache given, compiled + stored) or
        ``"compiled"`` (no cache)."""
        names = tuple(outputs) if outputs else tuple(self.output_names)
        self._check_feed(feed, names)
        sig = feed_signature(feed)
        k = (names, sig)
        if k in self._aot:
            return "warm"
        key = None
        if cache is not None:
            from paddle_tpu.config.compile_cache import cache_key

            key = cache_key("infer", self.fingerprint, names, sig)
            fn = cache.load(key)
            if fn is not None and self._install_aot(k, fn, feed):
                return "hit"
        lowered = jax.jit(self._make_run(names)).lower(
            self.params, self.state, feed)
        if cache is not None:
            from paddle_tpu.config.compile_cache import compile_fresh

            compiled = compile_fresh(lowered)
        else:
            compiled = lowered.compile()
        self.compile_events += 1
        self._aot[k] = compiled
        if cache is not None:
            cache.store(key, compiled,
                        label=f"infer:{self.manifest.get('name', 'model')}")
            return "miss"
        return "compiled"

    def _install_aot(self, k: tuple, fn, feed) -> bool:
        """Smoke-call a cache-loaded executable before trusting it with
        traffic: a wrong or stale program must degrade to a compile."""
        from paddle_tpu.utils import logger

        try:
            # wait for the result: dispatch is asynchronous, and an
            # executable that cannot run reports it only when read
            out = jax.block_until_ready(fn(self.params, self.state, feed))
            if set(out) != set(k[0]):
                raise ValueError(f"output names {sorted(out)} != "
                                 f"{sorted(k[0])}")
        except Exception as e:  # noqa: BLE001 — fall back to a compile
            logger.warning("compile cache: loaded executable rejected by "
                           "its smoke call (%s: %s) — recompiling",
                           type(e).__name__, e)
            return False
        self._aot[k] = fn
        return True

    def infer(
        self, feed: Dict[str, Any], outputs: Optional[Sequence[str]] = None
    ) -> Dict[str, np.ndarray]:
        names = tuple(outputs) if outputs else tuple(self.output_names)
        self._check_feed(feed, names)
        rows = {np.asarray(p).shape[0] if np.asarray(p).ndim else -1
                for v in feed.values()
                for p in (v if isinstance(v, tuple) else (v,))}
        if 0 in rows:
            if rows != {0}:
                # a zero-row part next to populated parts is a client bug,
                # not an empty request — silently replying empty would
                # discard the populated rows
                raise ValueError(
                    f"feed mixes zero-row and populated inputs (batch "
                    f"sizes {sorted(rows)}); an empty request must be "
                    f"empty in every slot")
            # zero input rows: shape-infer over a synthetic one-row feed
            # and reply with correctly-shaped empty arrays — never a
            # cryptic reshape error, never a degenerate B=0 compile.
            # Cached like _fns: eval_shape is a full O(graph) trace, and
            # the output shapes depend only on (names, per-row shapes)
            key = (names, tuple(
                (k, isinstance(v, tuple))
                + tuple((np.asarray(p).shape[1:], str(np.asarray(p).dtype))
                        for p in (v if isinstance(v, tuple) else (v,)))
                for k, v in sorted(feed.items())))
            res = self._empty_cache.get(key)
            if res is None:
                from paddle_tpu.nn.feeds import empty_outputs, zero_batch_like

                res = empty_outputs(self._make_run(names), self.params,
                                    self.state, zero_batch_like(feed))
                if len(self._empty_cache) >= 64:
                    # keys are client-controlled (per-row shapes): bound
                    # the cache so shape-diverse empty traffic cannot
                    # grow it without limit
                    self._empty_cache.clear()
                self._empty_cache[key] = res
            return {k: np.asarray(v) for k, v in res.items()}
        if self._aot:
            # primed signatures serve from the AOT table (the warmed
            # executables ARE the serving executables — the compile cache
            # would be pointless if the hot path re-jitted beside it)
            afn = self._aot.get((names, feed_signature(feed)))
            if afn is not None:
                try:
                    res = afn(self.params, self.state, feed)
                    return {k: np.asarray(v) for k, v in res.items()}
                except TypeError:
                    # aval/weak-type mismatch with the primed signature:
                    # fall through to the jit path rather than fail the
                    # request (jit re-canonicalizes)
                    pass
        fn = self._fns.get(names)
        if fn is None:
            with self._fns_lock:
                fn = self._fns.get(names)
                if fn is None:
                    fn = self._fns[names] = jax.jit(self._make_run(names))
        res = fn(self.params, self.state, feed)
        return {k: np.asarray(v) for k, v in res.items()}


def _read_member(z: zipfile.ZipFile, path: str, name: str) -> bytes:
    """Read one zip member with integrity attribution: a missing member,
    a bad CRC, or a torn compressed stream raises ``BundleCorruptError``
    naming the member instead of a raw ``KeyError``/``BadZipFile``.
    ``zipfile`` verifies the stored CRC-32 on every full read, so a
    bit-flip anywhere in the payload is caught here."""
    import zlib

    try:
        return z.read(name)
    except KeyError:
        raise BundleCorruptError(
            f"bundle {path!r} is missing member {name!r} (truncated or "
            f"damaged archive?)", path=path, member=name) from None
    except (zipfile.BadZipFile, zlib.error, EOFError) as e:
        raise BundleCorruptError(
            f"bundle {path!r} member {name!r} is corrupt: {e}",
            path=path, member=name) from e


def load_inference_model(path: str, *,
                         int8_in_trace: bool = False,
                         arch_fingerprint: bool = False) -> InferenceModel:
    """Load a ``.ptz`` bundle into a servable :class:`InferenceModel`.

    Quantized bundles (``merge_model(quantize=...)``) dequantize on load
    to the model's parameter dtype; with ``int8_in_trace`` the int8
    matmul weights instead stay quantized in HBM and dequantize inside
    the compiled forward (to the compute dtype), gated by the lint
    auditor — a gate failure logs and falls back to load-time
    dequantization, never a silently degraded program.

    ``arch_fingerprint`` keys the compile cache by the ARCHITECTURE
    (config proto + parameter shapes/dtypes) instead of the bundle's
    byte CRCs: parameters ride every compiled call as arguments, so two
    weight versions of one model share warmed executables — the hot-swap
    reload path (serving/reload.py) depends on this to pay zero XLA
    compiles when v2 replaces v1."""
    try:
        zf = zipfile.ZipFile(path, "r")
    except FileNotFoundError:
        raise  # a missing file is not a corrupt one
    except (zipfile.BadZipFile, OSError) as e:
        raise BundleCorruptError(
            f"{path!r} is not a readable zip archive: {e}", path=path) from e
    with zf as z:
        try:
            manifest = json.loads(_read_member(z, path, "manifest.json"))
        except json.JSONDecodeError as e:
            raise BundleCorruptError(
                f"bundle {path!r} manifest.json does not parse: {e}",
                path=path, member="manifest.json") from e
        if not isinstance(manifest, dict) or manifest.get("magic") != _MAGIC:
            raise ValueError(f"{path!r} is not a paddle_tpu model bundle")
        mc = pb.ModelConfig()
        try:
            mc.ParseFromString(_read_member(z, path, "model.pb"))
        except Exception as e:
            if isinstance(e, BundleCorruptError):
                raise
            raise BundleCorruptError(
                f"bundle {path!r} model.pb does not parse: {e}",
                path=path, member="model.pb") from e
        try:
            params = _npz_load(_read_member(z, path, "params.npz"))
        except BundleCorruptError:
            raise
        except Exception as e:  # np.load on a damaged npz payload
            raise BundleCorruptError(
                f"bundle {path!r} params.npz does not parse: {e}",
                path=path, member="params.npz") from e
        state = {}
        if "state.npz" in z.namelist():
            try:
                state = _npz_load(_read_member(z, path, "state.npz"))
            except BundleCorruptError:
                raise
            except Exception as e:
                raise BundleCorruptError(
                    f"bundle {path!r} state.npz does not parse: {e}",
                    path=path, member="state.npz") from e
        # executable identity for the compile cache: zip-level CRCs of
        # the config + weights (already verified by _read_member) — two
        # bundles with identical payloads share warmed executables
        crcs = {i.filename: i.CRC for i in z.infolist()}
    fp = "bundle:" + "-".join(
        f"{crcs.get(m, 0):08x}" for m in ("model.pb", "params.npz"))
    if arch_fingerprint:
        # fingerprint=None -> InferenceModel derives the architecture
        # hash (the int8 in-trace variant differs naturally: its params
        # tree carries the int8 arrays + scale leaves)
        fp = None
    qinfo = manifest.get("quantize") or {}
    qmeta = qinfo.get("arrays") or {}
    if qmeta:
        if int8_in_trace and any(m.get("mode") == "int8"
                                 for m in qmeta.values()):
            deq, int8 = _dequantize_params(params, qmeta, path=path,
                                           keep_int8=True)
            model = InferenceModel(
                mc, deq, state, manifest,
                fingerprint=None if fp is None else fp + ":int8t",
                int8=int8)
            if model._int8_gate():
                return model
            from paddle_tpu.utils import logger

            logger.warning("bundle %r: int8 in-trace dequantize failed "
                           "the lint gate — dequantizing at load instead",
                           path)
        params, _ = _dequantize_params(params, qmeta, path=path)
    model = InferenceModel(mc, params, state, manifest, fingerprint=fp)
    #: the artifact the model was loaded from (the reload/healthz surface
    #: names it; empty for models built in-process)
    model.bundle_path = path
    return model


# ---------------------------------------------------------------------------
# Python-free (framework-free) AOT export
# ---------------------------------------------------------------------------

_AOT_MAGIC = "paddle_tpu.aot.v1"

#: AOT exports close the trained weights over the trace on purpose —
#: constant-bloat would flag every parameter tensor
_AOT_CHECKS = ["dtype-promotion", "host-transfer", "unsharded-op",
               "unaligned-pallas-tile"]


def _audit_export(fn, args, label: str, checks: Optional[list] = None):
    """Deploy-side lint hook: audit the export trace with the analysis
    subsystem (docs/lint.md) and return finding dicts for the artifact
    manifest.  Gated by ``--deploy_lint``; never fails the export — a
    broken audit logs and returns [] so deployment is never blocked by
    the linter itself."""
    from paddle_tpu.utils import FLAGS, logger

    if not FLAGS.deploy_lint:
        return []
    try:
        from paddle_tpu.analysis import audit_fn

        findings = audit_fn(fn, *args, label=label, checks=checks)
    except Exception as e:  # noqa: BLE001 — advisory path
        logger.warning("deploy lint audit failed (%s: %s); exporting "
                       "without findings", type(e).__name__, e)
        return []
    for f in findings:
        if f.severity == "ERROR":
            logger.warning("deploy lint: %s", f.format())
    return [f.to_dict() for f in findings]


def export_aot(bundle_or_model, out_path: str, example_feed: Dict[str, Any],
               *, outputs: Optional[Sequence[str]] = None) -> str:
    """Serialize an inference bundle to a self-contained AOT artifact:
    StableHLO with the trained weights embedded as constants, plus a
    manifest describing the flat call signature.  The artifact needs NO
    paddle_tpu (and no model code) to run — only jax:

        import jax.export, zipfile, json
        z = zipfile.ZipFile("model.aot")
        exp = jax.export.deserialize(bytearray(z.read("fn.stablehlo")))
        outs = exp.call(*flat_inputs)   # order per manifest["inputs"]

    This is the TPU-native answer to the reference's Python-free C
    deployment (paddle/capi/gradient_machine.h:27-59 over the C++ engine):
    the compiler artifact replaces the engine, and the embedded-CPython
    capi (csrc/capi.cc) remains as the convenience binding.

    ``example_feed`` fixes the exported shapes/dtypes (AOT artifacts are
    shape-specialized, like the reference's merged model is
    config-specialized).  Sequence feeds may be (values, lengths, ...)
    tuples — they are flattened; the manifest records how many parts each
    input contributes.  Returns ``out_path``.
    """
    from jax import export as jexport

    m = (load_inference_model(bundle_or_model)
         if isinstance(bundle_or_model, str) else bundle_or_model)
    names, spec, flat_example, fn = _flat_signature(m, example_feed, outputs)

    requested = ("cpu", "tpu")
    try:  # portable artifact when this jax supports multi-platform export
        exporter = jexport.export(jax.jit(fn), platforms=requested)
    except TypeError:  # older jax.export signature without platforms=
        from paddle_tpu.utils import logger

        logger.warning(
            "export_aot: this jax's export() does not support "
            "platforms=%r — exporting for the CURRENT platform only; the "
            "artifact will refuse to load on other platforms (see the "
            "manifest's 'platforms' list)", list(requested))
        exporter = jexport.export(jax.jit(fn))
    exported = exporter(*flat_example)  # trace ONCE, outside the fallback
    # record what the artifact ACTUALLY targets (not what was asked for):
    # load_exported fails fast on a platform the artifact never compiled
    # for instead of dying mysteriously inside the runtime
    platforms = ([str(p).lower()
                  for p in getattr(exported, "platforms", ())]
                 or [jax.default_backend()])
    manifest = {
        "magic": _AOT_MAGIC,
        "platforms": platforms,
        "inputs": [
            {"name": k, "parts": n} for k, n in spec
        ],
        "flat_inputs": [
            {"shape": list(np.shape(a)), "dtype": str(np.asarray(a).dtype)}
            for a in flat_example
        ],
        "outputs": names,
        # constant-bloat is off: embedding the weights as constants is the
        # POINT of an AOT artifact (fn closes over the trained params)
        "lint": _audit_export(fn, flat_example, "aot_forward",
                              checks=_AOT_CHECKS),
    }
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with zipfile.ZipFile(out_path, "w", zipfile.ZIP_DEFLATED) as z:
        z.writestr("manifest.json", json.dumps(manifest, indent=1))
        z.writestr("fn.stablehlo", exported.serialize())
    return out_path


def load_exported(aot_path: str):
    """Deserialize an ``export_aot`` artifact WITH the platform gate:
    the manifest records the platforms the StableHLO was actually
    lowered for, and an artifact that never targeted this process's
    backend fails fast with the fix spelled out — instead of a
    mysterious runtime error deep inside the first call.  Returns
    ``(exported, manifest)``."""
    from jax import export as jexport

    with zipfile.ZipFile(aot_path) as z:
        try:
            manifest = json.loads(_read_member(z, aot_path, "manifest.json"))
        except json.JSONDecodeError as e:
            raise BundleCorruptError(
                f"AOT artifact {aot_path!r} manifest.json does not parse: "
                f"{e}", path=aot_path, member="manifest.json") from e
        blob = _read_member(z, aot_path, "fn.stablehlo")
    backend = jax.default_backend()
    platforms = [str(p).lower() for p in manifest.get("platforms") or []]
    if platforms and backend not in platforms:
        raise ValueError(
            f"AOT artifact {aot_path!r} was exported for platforms "
            f"{platforms} but this process runs on {backend!r} — "
            f"re-export it on a jax whose export() accepts "
            f"platforms=(..., {backend!r})")
    return jexport.deserialize(bytearray(blob)), manifest


_HLO_DTYPES = {"float32": "f32", "int32": "i32", "float64": "f64",
               "int64": "i64"}


def _flat_signature(m, example_feed: Dict[str, Any],
                    outputs: Optional[Sequence[str]]):
    """Shared AOT flattening: sorted feed keys, sequence tuples flattened
    to parts, and a flat-argument closure over the trained model — ONE
    definition so the StableHLO and HLO-proto artifact signatures can
    never drift."""
    names = list(outputs) if outputs else list(m.output_names)
    keys = sorted(example_feed)
    spec: List[tuple] = []
    flat_example: List[Any] = []
    for k in keys:
        v = example_feed[k]
        parts = v if isinstance(v, tuple) else (v,)
        spec.append((k, len(parts)))
        flat_example.extend(jnp.asarray(p) for p in parts)

    topology, params, state = m.topology, m.params, m.state

    def fn(*flat):
        feed: Dict[str, Any] = {}
        i = 0
        for key, n in spec:
            feed[key] = flat[i] if n == 1 else tuple(flat[i: i + n])
            i += n
        outs, _ = topology.apply(params, state, feed, train=False,
                                 outputs=names)
        return tuple(outs[n].value for n in names)

    return names, spec, flat_example, fn


class _unrolled_scans:
    """Trace-time ``lax.scan`` unrolling for AOT export: an inference
    artifact has static shapes, so a Python loop over the static trip
    count produces a straight-line (control-flow-free) module — useful for
    consumers that prefer or require loop-free HLO.  Patches
    ``jax.lax.scan`` for the duration of the export trace only.

    BEST-EFFORT, and process-global: the patch monkeypatches the module
    attribute, so (a) a class-level lock serializes concurrent exports —
    two threads entering at once would otherwise capture each other's
    patched ``scan`` as ``_orig`` and leave it installed forever; (b) code
    that bound ``lax.scan``/``fori_loop``/``while_loop`` *before* the
    patch (e.g. ``from jax.lax import scan`` at import time, or any
    ``while_loop``-based op) still lowers control flow.  ``export_aot_hlo``
    therefore verifies the lowered module afterwards (via the analysis
    subsystem's loop scan) and warns when residual while/conditional ops
    survive instead of silently shipping a non-straight-line artifact."""

    _lock = threading.Lock()

    def __enter__(self):
        from jax import lax as jlax

        type(self)._lock.acquire()
        self._orig = jlax.scan

        def scan(f, init, xs=None, length=None, reverse=False, **_kw):
            import jax as _jax

            leaves = _jax.tree_util.tree_leaves(xs)
            n = int(length) if xs is None or not leaves else leaves[0].shape[0]
            order = range(n - 1, -1, -1) if reverse else range(n)
            carry, ys = init, []
            for i in order:
                x_i = (None if xs is None else
                       _jax.tree_util.tree_map(lambda a: a[i], xs))
                carry, y = f(carry, x_i)
                ys.append(y)
            if reverse:
                ys.reverse()
            stacked = _jax.tree_util.tree_map(
                lambda *a: jnp.stack(a), *ys) if ys else None
            return carry, stacked

        jlax.scan = scan
        return self

    def __exit__(self, *exc):
        from jax import lax as jlax

        jlax.scan = self._orig
        type(self)._lock.release()
        return False


def export_aot_hlo(bundle_or_model, out_dir: str, example_feed: Dict[str, Any],
                   *, outputs: Optional[Sequence[str]] = None,
                   unroll_scans: bool = False) -> str:
    """Serialize an inference bundle for the PYTHON-FREE C++ host
    (csrc/aot_host.cc): an HloModuleProto with the trained weights embedded
    as constants, plus a flat-signature ``io.txt``.  The target process
    runs NO Python at all — it links the PJRT CPU client bundled in
    libtensorflow_cc and feeds raw row-major buffers:

        aot_host <out_dir>       # reads in<i>.bin, writes out<i>.bin

    This completes the reference's C-deployment story
    (paddle/capi/gradient_machine.h:27-59): where ``export_aot`` removes
    the framework dependency (artifact runs with jax alone), this removes
    the Python process entirely.  Shapes are fixed by ``example_feed``
    exactly as in ``export_aot``.  Returns ``out_dir``.
    """
    m = (load_inference_model(bundle_or_model)
         if isinstance(bundle_or_model, str) else bundle_or_model)
    names, spec, flat_example, fn = _flat_signature(m, example_feed, outputs)

    # validate dtypes BEFORE the (expensive) lowering so an unsupported
    # feed never leaves a partial bundle on disk
    lines = []
    for a in flat_example:
        a = np.asarray(a)
        dt = _HLO_DTYPES.get(str(a.dtype))
        if dt is None:
            raise ValueError(f"export_aot_hlo: unsupported input dtype "
                             f"{a.dtype}")
        dims = "x".join(str(d) for d in a.shape) or "scalar"
        lines.append(f"in {dt} {dims}")

    if unroll_scans:
        with _unrolled_scans():
            ir = jax.jit(fn).lower(*flat_example).compiler_ir(dialect="hlo")
        # the patch is best-effort (see _unrolled_scans): verify the
        # LOWERED module really is loop-free and warn otherwise, so a
        # consumer that requires straight-line HLO finds out at export
        # time, not at load time
        from paddle_tpu.analysis import hlo_control_flow
        from paddle_tpu.utils import logger

        try:
            residual = hlo_control_flow(ir.as_hlo_text())
        except Exception:  # noqa: BLE001 — verification is advisory
            residual = []
        if residual:
            logger.warning(
                "export_aot_hlo(unroll_scans=True): lowered module still "
                "contains %s op(s) — some control flow predates the scan "
                "patch (lax.while_loop, or scan bound before export); the "
                "artifact is correct but not straight-line",
                "/".join(residual))
    else:
        ir = jax.jit(fn).lower(*flat_example).compiler_ir(dialect="hlo")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "model.hlo.pb"), "wb") as f:
        f.write(ir.as_serialized_hlo_module_proto())
    manifest = {
        "inputs": [{"name": k, "parts": n} for k, n in spec],
        "outputs": names,
        "lint": _audit_export(fn, flat_example, "aot_hlo_forward",
                              checks=_AOT_CHECKS),
    }
    with open(os.path.join(out_dir, "io.txt"), "w") as f:
        f.write("\n".join(lines) + "\n")
    with open(os.path.join(out_dir, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return out_dir


def build_aot_host(*, force: bool = False, strict: bool = False
                   ) -> Optional[str]:
    """Compile csrc/aot_host.cc (the Python-free PJRT-CPU inference host)
    against the tensorflow wheel's bundled XLA; returns the binary path or
    None when the toolchain/wheel is unavailable.  Cached next to the
    native dataio library, rebuilt when the source is newer.  With
    ``strict=True`` a COMPILE failure raises (with the compiler's stderr)
    instead of returning None — so CI can distinguish "wheel absent"
    (None) from "host code broken" (raise)."""
    import importlib.util
    import subprocess

    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        return None
    tf_dir = list(spec.submodule_search_locations)[0]
    if not os.path.exists(os.path.join(tf_dir, "libtensorflow_cc.so.2")):
        return None
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    src = os.path.join(root, "csrc", "aot_host.cc")
    out_dir = os.path.join(root, "paddle_tpu", "_native")
    os.makedirs(out_dir, exist_ok=True)
    binary = os.path.join(out_dir, "aot_host")
    if not os.path.exists(src):
        # installed without the csrc/ tree: a stale cached binary is still
        # usable, but there is nothing to (re)build
        return binary if os.path.exists(binary) else None
    if (not force and os.path.exists(binary)
            and os.path.getmtime(binary) >= os.path.getmtime(src)):
        return binary
    inc = os.path.join(tf_dir, "include")
    cmd = [
        # -DNDEBUG is load-bearing: the wheel's absl is a release build and
        # the SwissTable layout differs under debug (see csrc/aot_host.cc)
        "g++", "-O2", "-std=c++17", "-w", "-DNDEBUG",
        "-D_GLIBCXX_USE_CXX11_ABI=1",
        src,
        "-I", os.path.join(root, "csrc", "shim"),
        "-I", inc,
        "-I", os.path.join(inc, "external", "highwayhash"),
        "-I", os.path.join(inc, "external", "farmhash_archive", "src"),
        "-L", tf_dir,
        "-l:libtensorflow_cc.so.2", "-l:libtensorflow_framework.so.2",
        f"-Wl,-rpath,{tf_dir}",
        "-o", binary,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=600)
    except subprocess.CalledProcessError as e:
        if strict:
            raise RuntimeError(
                f"aot_host compile failed:\n{e.stderr.decode()[-4000:]}"
            ) from e
        return None
    except Exception:
        if strict:
            raise
        return None
    return binary
