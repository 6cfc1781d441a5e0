"""Runtime flag system — the gflags plane of the reference.

The reference keeps ~117 global gflags (reference: paddle/utils/Flags.cpp:18-77)
controlling devices, trainer counts, ports, logging cadence, etc.  Here flags are
a typed registry parsed from argv and ``PADDLE_TPU_*`` environment variables.
TPU-relevant flags replace the CUDA ones (use_gpu -> use_tpu/platform), and the
pserver networking flags are replaced by mesh-shape flags (the pserver tier does
not exist on TPU; see parallel/).
"""

from __future__ import annotations

import os
import sys
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

__all__ = ["FLAGS", "define_flag", "parse_flags", "flags_snapshot",
           "flags_help"]

_ENV_PREFIX = "PADDLE_TPU_"


@dataclass
class _FlagSpec:
    name: str
    default: Any
    help: str
    type: type
    validator: Optional[Callable[[Any], bool]] = None


class _Flags:
    """Singleton typed flag store.

    Mirrors the role of the DEFINE_int32/DEFINE_bool/... globals in the
    reference (paddle/utils/Flags.cpp); values are attributes: ``FLAGS.log_period``.
    """

    def __init__(self) -> None:
        object.__setattr__(self, "_specs", {})
        object.__setattr__(self, "_values", {})
        object.__setattr__(self, "_lock", threading.Lock())

    def _define(self, spec: _FlagSpec) -> None:
        with self._lock:
            if spec.name in self._specs:
                raise ValueError(f"flag {spec.name!r} already defined")
            self._specs[spec.name] = spec
            env = os.environ.get(_ENV_PREFIX + spec.name.upper())
            self._values[spec.name] = (
                _coerce(env, spec.type) if env is not None else spec.default
            )

    def __getattr__(self, name: str) -> Any:
        try:
            return self._values[name]
        except KeyError:
            raise AttributeError(f"unknown flag {name!r}") from None

    def __setattr__(self, name: str, value: Any) -> None:
        if name not in self._specs:
            raise AttributeError(f"unknown flag {name!r}")
        spec = self._specs[name]
        value = _coerce(value, spec.type)
        if spec.validator is not None and not spec.validator(value):
            raise ValueError(f"invalid value {value!r} for flag {name!r}")
        self._values[name] = value

    def as_dict(self) -> Dict[str, Any]:
        return dict(self._values)


def _coerce(value: Any, typ: type) -> Any:
    if isinstance(value, typ):
        return value
    if typ is bool:
        if isinstance(value, str):
            return value.lower() in ("1", "true", "yes", "on")
        return bool(value)
    return typ(value)


FLAGS = _Flags()


def define_flag(
    name: str,
    default: Any,
    help: str = "",
    *,
    type: Optional[type] = None,
    validator: Optional[Callable[[Any], bool]] = None,
) -> None:
    FLAGS._define(
        _FlagSpec(
            name=name,
            default=default,
            help=help,
            type=type or (bool if isinstance(default, bool) else builtins_type(default)),
            validator=validator,
        )
    )


def builtins_type(v: Any) -> type:
    for t in (bool, int, float, str):
        if isinstance(v, t):
            return t
    return object


def parse_flags(argv: Optional[list] = None) -> list:
    """Parse ``--name=value`` / ``--name value`` args; returns leftover argv."""
    argv = list(sys.argv[1:] if argv is None else argv)
    rest = []
    i = 0
    while i < len(argv):
        arg = argv[i]
        if arg.startswith("--"):
            body = arg[2:]
            if "=" in body:
                name, value = body.split("=", 1)
            else:
                name = body
                if name in FLAGS._specs and FLAGS._specs[name].type is bool:
                    value = "true"
                elif i + 1 < len(argv):
                    value = argv[i + 1]
                    i += 1
                else:
                    value = "true"
            name = name.replace("-", "_")
            if name in FLAGS._specs:
                setattr(FLAGS, name, value)
            else:
                rest.append(arg)
        else:
            rest.append(arg)
        i += 1
    return rest


def flags_snapshot() -> Dict[str, Any]:
    return FLAGS.as_dict()


def flags_help() -> str:
    """One line per registered flag — the ``--help`` surface of the CLI
    (the reference printed its gflags table the same way)."""
    lines = []
    for name in sorted(FLAGS._specs):
        spec = FLAGS._specs[name]
        head = f"  --{name}={spec.default!r}"
        lines.append(f"{head:<40} {spec.help}" if spec.help else head)
    return "\n".join(lines)


# --- Core flag set (TPU-native analog of paddle/utils/Flags.cpp:18-77) ---

# Device / platform (replaces use_gpu, gpu_id, parallel_nn ...)
# CLI driver plane (paddle_trainer analog, trainer/TrainerMain.cpp:32-65)
define_flag("job", "train", "CLI mode: train | test | checkgrad | time")
define_flag("config", "", "python config file defining get_config()")
define_flag("num_passes", 1, "training passes for the CLI train job")
define_flag("test_pass", -1, "checkpoint pass to test (-1 = latest)")
define_flag("time_batches", 10, "batches to time in --job=time")

define_flag("platform", "", "jax platform override: '', 'tpu', 'cpu'")
define_flag("use_tpu", True, "prefer TPU devices when available")
define_flag("seed", 1, "global RNG seed (0 = nondeterministic)")
define_flag("dtype", "float32", "default parameter dtype")
define_flag("compute_dtype", "bfloat16", "preferred matmul/conv compute dtype on TPU")

# Mixed precision (docs/mixed_precision.md): end-to-end bf16 compute with
# f32 master weights + dynamic loss scaling wired into the bad-step guard
define_flag("amp", False, "mixed-precision training: activations and "
            "matmul/conv outputs run in bf16 end-to-end (f32 master "
            "weights, f32 optimizer state); BN statistics, softmax/"
            "logsumexp reductions, and the loss stay f32 (the allowlist); "
            "dynamic loss scaling rides the bad-step guard — an overflow "
            "skips the step and halves the scale instead of aborting "
            "(gated by `lint --amp`)")
define_flag("loss_scale", 65536.0, "initial dynamic loss scale under "
            "--amp (grads are computed on scale*loss and unscaled before "
            "the update; 1 = start unscaled)",
            validator=lambda v: v >= 1.0)
define_flag("loss_scale_growth", 2000, "double the loss scale after N "
            "consecutive finite steps (0 = never grow: static scale)",
            validator=lambda v: v >= 0)
define_flag("loss_scale_max", 16777216.0, "dynamic loss scale ceiling "
            "(growth never doubles past this; halving floors at 1.0)",
            validator=lambda v: v >= 1.0)
define_flag("remat", False, "rematerialize each layer inside the "
            "backward (one jax.checkpoint per layer that is in no "
            "nn.remat_block): trades ~1/3 more FLOPs for O(layer) "
            "activation memory, buying the larger batches the MFU-starved "
            "recurrent models need")

# Trainer loop (log_period, test_period, checkgrad ...)
define_flag("log_period", 100, "log every N batches")
define_flag("test_period", 0, "test every N batches (0 = per pass)")
define_flag("show_parameter_stats_period", 0, "print param stats every N batches")
# reference default was 1e-2 (f64 CPU); at f32 a smaller step is both safe
# (FD noise ~1e-4 at loss~O(1)) and far less likely to cross a relu/maxpool
# kink, which corrupts whole-model FD checks on conv nets
define_flag("checkgrad_eps", 1e-3, "epsilon for finite-difference gradient checks")
define_flag("save_dir", "", "checkpoint root; pass dirs saved under it ('' = no saving)")
define_flag("start_pass", 0, "resume training from this pass")
define_flag("saving_period", 1, "save checkpoint every N passes")
# Continuous publication (paddle_tpu/publish; docs/publish.md)
define_flag("publish_dir", "", "versioned publish directory for gated "
            "deploy bundles (v-%05d dirs + shared compile cache); '' "
            "disables publication")
define_flag("publish_every", 0, "publish a deploy bundle every N passes "
            "(coordinator only, from the newest VERIFIED checkpoint "
            "under --save_dir; 0 = never)",
            validator=lambda v: v >= 0)
define_flag("reload_probation", 32, "hot-reload probation window in "
            "completed requests before a swapped-in version is committed "
            "and its predecessor released (docs/publish.md)",
            validator=lambda v: v >= 1)

# Fault tolerance (paddle_tpu/resilience; docs/resilience.md)
define_flag("resume", "", "'' = --start_pass behavior; 'auto' = resume from the "
            "newest VALID checkpoint under --save_dir (self-locating)",
            validator=lambda v: v in ("", "auto"))
define_flag("keep_last_n", 0, "checkpoint retention: keep only the newest N "
            "pass dirs under --save_dir (0 = keep all)")
define_flag("guard_nonfinite", True, "bad-step guard: skip the optimizer "
            "update inside the jitted step when loss or grad global-norm is "
            "non-finite (lax.cond, no host syncs)")
define_flag("max_bad_steps", 8, "abort training after N CONSECUTIVE "
            "guard-skipped bad steps (0 = never abort)")
define_flag("checkpoint_on_preemption", True, "on SIGTERM/SIGINT, write an "
            "atomic checkpoint at the next batch boundary and exit cleanly "
            "(needs --save_dir; resume with --resume=auto)")
define_flag("reader_retries", 0, "CLI: wrap the config's reader in "
            "resilience.resilient_reader with this retry budget (0 = off)")

# Silent-data-corruption firewall (resilience/integrity.py;
# docs/resilience.md "Silent corruption")
define_flag("sdc_check_every", 0, "cross-replica integrity check cadence: "
            "every N batches the jitted step's in-device fingerprint of "
            "params + optimizer slots (+ pserver tables) is exchanged "
            "across the data-parallel replicas and majority-voted; the "
            "minority rank is quarantined and expelled via the elastic "
            "shrink, survivors roll back to the last verified checkpoint "
            "when no strict majority exists (0 = off; the compiled step "
            "is then equation-identical to the unchecked one — gated by "
            "`lint --sdc`)",
            validator=lambda v: v >= 0)
define_flag("scrub_every_s", 0.0, "background checkpoint scrubber cadence "
            "on rank 0: re-hash manifested CRCs of checkpoint chains, "
            "pserver shard snapshots, and deploy bundles at rest every N "
            "seconds; a newly-corrupt dir is QUARANTINED out of "
            "latest_pass eligibility, journaled as a scrub_fail anchor, "
            "and scrub.json marks the newest fully-verified pass "
            "(0 = off; `python -m paddle_tpu fsck DIR` is the one-shot "
            "form)",
            validator=lambda v: v >= 0)

# Gang supervision (resilience/cluster.py; docs/resilience.md multi-host)
define_flag("gang_max_restarts", 3, "gang supervisor: relaunch the whole "
            "gang at most N times after a rank dies or hangs before "
            "raising GangFailedError")
define_flag("gang_heartbeat_s", 5.0, "supervised ranks touch their "
            "heartbeat file at batch boundaries, at most every N seconds")
define_flag("gang_watchdog_s", 60.0, "gang supervisor: a rank whose "
            "heartbeat is older than N seconds is declared hung and the "
            "gang is restarted (JAX collectives deadlock, not error, when "
            "a peer dies)")
define_flag("gang_elastic", False, "elastic gang recovery: a dead or hung "
            "rank SHRINKS the surviving gang's device mesh (drain -> "
            "checkpoint-commit -> re-instantiate MeshConfig -> resume "
            "mid-pass) instead of relaunching the whole gang; the world "
            "GROWS back the same way when a replacement registers.  A "
            "failure during the resize itself falls back to the classic "
            "whole-gang relaunch within --gang_max_restarts")
define_flag("gang_min_ranks", 1, "elastic gang: never shrink below N "
            "surviving ranks — fewer survivors fall back to the "
            "whole-gang relaunch",
            validator=lambda v: v >= 1)
define_flag("gang_grow_back", True, "elastic gang: after a shrink "
            "completes, relaunch a replacement for each lost rank and "
            "grow the mesh back at the survivors' next batch boundary")
define_flag("gang_resize_timeout_s", 0.0, "elastic gang: budget for the "
            "survivors' shrink/grow protocol (drain + checkpoint-commit + "
            "barriers) before the supervisor falls back to the whole-gang "
            "relaunch; 0 = derived from the watchdog/startup budgets")
define_flag("gang_backoff_jitter", 0.5, "gang supervisor: restart backoff "
            "is drawn uniformly from [(1-jitter)*delay, delay] so many "
            "gangs sharing a scheduler never relaunch in lockstep "
            "(thundering herd); 0 = deterministic backoff",
            validator=lambda v: 0.0 <= v <= 1.0)

# Cross-pod (DCN) topology + transport (parallel/hierarchical.py,
# resilience/dcn.py; docs/parallel.md "The dcn axis")
define_flag("dcn_axis", "", "name of the mesh axis that crosses the "
            "data-center network (pod boundary).  Non-empty turns on the "
            "hierarchical gradient allreduce (ICI reduce-scatter -> DCN "
            "allreduce of partials -> ICI allgather) and pod-as-failure-"
            "unit elastic recovery; empty = single-pod flat collectives "
            "(bit-identical by construction when the dcn axis has size 1)")
define_flag("dcn_compress", False, "compress the DCN-crossing gradient "
            "partials to bf16 with an error-feedback residual (the "
            "quantization error is carried into the next step's partials, "
            "so the bias does not accumulate); ICI legs stay full "
            "precision.  Convergence-gated, not bit-exact")
define_flag("dcn_timeout_s", 30.0, "cross-pod transport: per-attempt "
            "timeout for one DCN exchange/broadcast before the transport "
            "retries; the total budget is dcn_timeout_s * (dcn_retries+1) "
            "plus backoff, after which the unreachable pod is attributed "
            "in a typed DCNTimeout/DCNPartitioned",
            validator=lambda v: v > 0)
define_flag("dcn_retries", 2, "cross-pod transport: bounded retry count "
            "per DCN exchange (exponential backoff between attempts, "
            "jittered by --gang_backoff_jitter); exhausting it raises "
            "DCNPartitioned when the peer pod still heartbeats (reachable "
            "via the supervisor, unreachable via DCN) and DCNTimeout "
            "otherwise",
            validator=lambda v: v >= 0)

# Serving runtime (paddle_tpu/serving; docs/serving.md) — the
# `python -m paddle_tpu serve` surface
define_flag("serve_bundle", "", "model bundle (.ptz) to serve with "
            "`python -m paddle_tpu serve`")
define_flag("serve_max_batch", 8, "serving: max rows coalesced into one "
            "compiled batch (batch buckets are powers of two up to this)")
define_flag("serve_batch_delay_ms", 2.0, "serving: micro-batching window — "
            "how long the worker waits to coalesce more same-shape requests")
define_flag("serve_queue_depth", 64, "serving: bounded queue depth; a full "
            "queue sheds new requests immediately (typed ShedError)")
define_flag("serve_deadline_ms", 1000.0, "serving: default per-request "
            "deadline; infeasible deadlines are rejected at admission "
            "(0 = no deadline)")
define_flag("serve_breaker_threshold", 5, "serving: consecutive batch "
            "failures that trip the circuit breaker OPEN")
define_flag("serve_breaker_cooldown_s", 5.0, "serving: seconds the breaker "
            "stays OPEN before letting a half-open probe through")
define_flag("serve_max_restarts", 3, "serving: worker restart budget before "
            "the server reports failed and drains with typed errors")
define_flag("serve_backoff_s", 0.5, "serving: base worker-restart backoff "
            "(exponential, doubled per restart)")
define_flag("serve_hang_timeout_s", 0.0, "serving: a batch in flight longer "
            "than this marks the worker hung and replaces it (0 = off)")
define_flag("serve_preflight", True, "serving: run the jaxpr auditor's "
            "host-transfer/constant-bloat checks over the serving closure "
            "at startup and fail fast on ERROR findings (lint --serve)")
define_flag("serve_smoke", 0, "serving CLI: push N synthetic requests "
            "through the server, print healthz, and exit (CI self-test; "
            "0 = serve until SIGTERM)")
define_flag("serve_nonfinite", "error", "serving: 'error' fails requests "
            "whose outputs contain NaN/Inf (counts toward the breaker); "
            "'allow' passes them through",
            validator=lambda v: v in ("error", "allow"))
define_flag("serve_watch", False, "serving CLI: serve from the newest "
            "valid version under --publish_dir and hot-reload newer "
            "publishes as they land (zero-downtime swap + probation "
            "rollback; docs/publish.md); with --serve_smoke=N runs the "
            "publish->reload self-test instead")
define_flag("serve_continuous", False, "serving: continuous slot-based "
            "batching for generation backends — finished requests' decode "
            "slots are recycled to queued requests between fused steps "
            "(docs/serving.md); bucket mode stays the default for one-shot "
            "forwards and AOT-unrollable deploys")
define_flag("serve_slots", 8, "serving: decode slot capacity of the "
            "continuous-batching table (each slot holds one request's "
            "beams; also the admission row bound in generation mode)",
            validator=lambda v: v >= 1)
define_flag("spec_decode", False, "serving: speculative decoding over the "
            "slot table — a host draft proposer offers --spec_k candidate "
            "tokens per slot and ONE fused wide-verify step accepts the "
            "longest prefix the model itself would emit; greedy "
            "(beam_size=1) backends only, outputs stay bit-identical to "
            "one-token stepping (docs/decode.md)")
define_flag("spec_k", 4, "serving: draft tokens per slot per speculative "
            "step (the wide verify scores k+1 positions; tune against "
            "healthz spec_accept_rate)", validator=lambda v: v >= 1)
define_flag("prefix_cache_mb", 0.0, "serving: host MiB budget for the "
            "prefix/session cache — requests repeating a source (or chat "
            "session) reuse the cached encoder state as slot prefill, "
            "keyed by content hash with LRU eviction (0 = off; "
            "docs/serving.md)", validator=lambda v: v >= 0.0)
define_flag("slot_page_pool", 0.0, "serving: host MiB budget for paged "
            "slot state — with the table full and work queued, cold slot "
            "carries are host-evicted and later restored bit-for-bit, so "
            "capacity stops being bounded by HBM (0 = off; "
            "docs/serving.md)", validator=lambda v: v >= 0.0)
define_flag("serve_fleet", False, "serving CLI: multi-model fleet mode — "
            "a model table keyed (name, version) with the whole "
            "breaker/ladder/warmup stack instantiated per entry, tenant "
            "quotas + weighted fair share in front, canary/shadow rollout "
            "with per-entry auto-rollback (docs/serving.md 'Fleet "
            "serving'); with --serve_smoke=N runs the two-model "
            "two-tenant isolation self-test")
define_flag("serve_canary_pct", 0.0, "fleet: percentage of a model's "
            "traffic routed to its canary candidate over the "
            "deterministic hash-of-request split (same request key -> "
            "same arm across retries)",
            validator=lambda v: 0.0 <= v <= 100.0)
define_flag("serve_probation_requests", 32, "fleet: resolved requests a "
            "canary must serve cleanly before it is promoted to "
            "incumbent; a breaker trip or error-rate regression inside "
            "the window auto-rolls it back (journaled publish_rollback "
            "naming the entry)", validator=lambda v: v >= 1)
define_flag("serve_shadow", False, "fleet: mirror traffic to the rollout "
            "candidate while every reply still comes from the incumbent; "
            "output divergence is counted and journaled "
            "(shadow_divergence), never served")
define_flag("tenant_spec", "", "fleet tenancy: comma-separated "
            "'name:weight:rate:burst' tenant contracts, e.g. "
            "'gold:3:100:20,free:1:10:5' — weight shares the fleet under "
            "contention, rate/burst bound the tenant's own token bucket "
            "(empty = untenanted); a zero weight is refused typed at "
            "construction")
define_flag("tenant_capacity_rate", 0.0, "fleet tenancy: aggregate "
            "requests/s the fleet admits before weighted fair-share "
            "shedding kicks in (0 = the sum of tenant rates)",
            validator=lambda v: v >= 0.0)
define_flag("tenant_credit", 1.0, "fleet tenancy: fair-queuing slack in "
            "weighted request units a tenant may run ahead of the global "
            "virtual clock before it is shed "
            "(QuotaExceeded(fair_share=True))",
            validator=lambda v: v > 0.0)

# Deterministic sharded data pipeline (paddle_tpu/datapipe; docs/data.md)
define_flag("data_pack", False, "sequence packing: several short "
            "sequences share one padded row (segment ids + position "
            "offsets plumbed through masking, the RNN carries, and the "
            "sequence losses) — crushes the pad-waste that keeps "
            "pad-heavy textclf/LSTM workloads MFU-starved; packed loss "
            "matches the unpacked oracle on the same samples (pinned)")
define_flag("data_shards", 8, "shard count for `python -m paddle_tpu "
            "data pack` (indexed record shards with per-record CRCs and "
            "a footer index; the shard set publishes atomically)",
            validator=lambda v: v >= 1)
define_flag("shuffle_seed", 0, "seed of the datapipe's deterministic "
            "global shuffle: each pass's record order is a permutation "
            "drawn from (seed, pass) and split per host — the whole "
            "shuffle state is this one integer, which is what makes the "
            "iterator cursor O(1) and restorable")

# Parallelism (replaces trainer_count, pservers, ports_num, nics, rdma_tcp ...)
define_flag("mesh_shape", "", "device mesh, e.g. '8' or '4x2' (empty = all devices, 1D)")
define_flag("mesh_axes", "data", "comma-separated mesh axis names, e.g. 'data,model'")
define_flag("num_virtual_devices", 0, "force N virtual CPU devices (tests/dry-runs)")

# Sharded-embedding parameter-server tier (paddle_tpu/pserver; docs/pserver.md)
define_flag("pserver_axis", "model", "mesh axis embedding tables marked "
            "sparse_grad shard their vocab over; a trainer mesh carrying "
            "this axis routes them through the pserver tier (all-to-all "
            "lookup + row-sparse updates that never densify)")
define_flag("pserver_pad_vocab", True, "pad table vocabs up to a shard "
            "multiple with masked tail rows; off = a non-dividing vocab "
            "raises a typed ConfigError naming the table")

# Sequence / generation (replaces beam_size, rnn_use_batch ...)
define_flag("beam_size", 3, "default beam width for sequence generation")
define_flag("max_gen_length", 100, "max generated sequence length")

# Kernel selection is not a flag: each kernel family's gate (listed at the
# top of ops/pallas_kernels.py) decides from the backend and the shape.
define_flag("decode_early_exit", True,
            "beam/greedy decode exits its token loop once every beam has "
            "emitted EOS (lax.while_loop); off = fixed-max_len lax.scan "
            "(AOT-unrollable)")

# Numeric traps — the feenableexcept(FE_INVALID|FE_DIVBYZERO|FE_OVERFLOW)
# analog (reference: paddle/trainer/TrainerMain.cpp:49 installs FP traps for
# the whole trainer process).  On XLA the equivalent is jax_debug_nans /
# jax_debug_infs: every jitted computation is re-run op-by-op when a
# nan/inf escapes, pinpointing the producing primitive.
define_flag("check_nan", False,
            "trap NaN/Inf escaping any jitted computation (jax_debug_nans; "
            "feenableexcept analog)")

# Trace-time lint subsystem (paddle_tpu/analysis; docs/lint.md)
define_flag("deploy_lint", True,
            "run the jaxpr auditor on every AOT/bundle export and attach "
            "findings to the artifact manifest")

# Deploy bundles + fleet cold-start (docs/deploy.md)
define_flag("deploy_quantize", "", "bundle export weight quantization: "
            "'' keeps f32; 'bf16' halves the weight payload; 'int8' "
            "stores matmul-sized tensors as symmetric per-channel int8 "
            "(~4x smaller) with scales alongside — every quantized "
            "export is gated by a max-abs-error check against the f32 "
            "oracle (merge_model quantize_tol)",
            validator=lambda v: v in ("", "bf16", "int8"))
define_flag("compile_cache_dir", "auto", "persistent compiled-executable "
            "cache directory shared across serving replicas: warmup "
            "bucket executables serialize here on first boot and LOAD "
            "(not compile) on every later boot — seconds-not-minutes "
            "fleet cold-start; bundles can also carry executables as "
            "aot/ members (config.warm_bundle).  'auto' (the default) "
            "lets the serve CLI derive a per-bundle cache next to the "
            "artifact (<bundle>.ccache — warm boots by default); pass "
            "an explicit empty value (--compile_cache_dir=) to opt out")

# Profiling / timers (replaces WITH_TIMER + log_barrier_* ...)
define_flag("enable_timers", False, "log the step timeline's per-phase "
            "table at the end of every pass")
define_flag("profile_dir", "", "write a jax.profiler trace here during train() "
            "(hl_profiler_start/end analog; view with TensorBoard/XProf)")
define_flag("profile_steps", 0, "capture bounded jax.profiler windows of N "
            "steps into --profile_dir instead of one whole-run trace "
            "(first window flag-armed after the compile step; SIGUSR2 "
            "arms another on a live job; 0 = whole-run behavior)",
            validator=lambda v: v >= 0)
define_flag("prefetch_depth", 0, "double-buffered async host->device "
            "feeding: a background thread runs the DataFeeder AND the "
            "h2d transfer for batch N+1..N+depth while the device steps "
            "batch N, so `data_wait`/`prepare`/`h2d` collapse out of the "
            "step critical path (0 = off; 2 = classic double buffering; "
            "drains cleanly at checkpoint/resize/preemption boundaries)",
            validator=lambda v: v >= 0)

# Unified telemetry (paddle_tpu/obs; docs/observability.md)
define_flag("metrics_port", 0, "serve the process-wide metrics registry "
            "over HTTP on this port (/metrics Prometheus text, "
            "/metrics.json snapshot; 0 = off)",
            validator=lambda v: 0 <= v <= 65535)
define_flag("obs_journal", "", "directory for the rank-tagged structured "
            "event journal (append-only events-r*.jsonl; merge ranks with "
            "`python -m paddle_tpu obs merge DIR`; '' = off)")
define_flag("obs_timeline", True, "instrument the training loop into "
            "phases (data-wait/prepare/h2d/step/callback/checkpoint/eval) "
            "aggregated per pass and into registry histograms, plus the "
            "live MFU gauge when a chip peak is known (host-side only — "
            "the compiled step is unchanged, gated by `lint --obs`)")
define_flag("obs_peak_flops", 0.0, "override the TOTAL peak FLOP/s the "
            "live MFU gauge divides by (0 = chip table x mesh size from "
            "the device kind; off-TPU there is no peak, so the gauge "
            "stays dark unless this is set)",
            validator=lambda v: v >= 0.0)
# Request-level distributed tracing (obs/trace.py; armed by --obs_journal)
define_flag("trace_sample", 1.0, "head-sample rate for request/step "
            "traces that no tail rule kept: 1 = keep every trace, 0 = "
            "keep only retained incidents (deadline-exceeded / shed / "
            "evicted / bad-step are ALWAYS kept — tail-based sampling; "
            "docs/observability.md 'Request tracing')",
            validator=lambda v: 0.0 <= v <= 1.0)
define_flag("trace_tail_p99", True, "tail sampling keeps any trace whose "
            "root latency reaches the rolling p99 of its kind (a "
            "per-root-name reservoir) even when --trace_sample would "
            "drop it — the outliers a latency histogram cannot explain")
