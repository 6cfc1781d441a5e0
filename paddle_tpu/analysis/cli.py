"""``python -m paddle_tpu lint`` — the CLI front of the analysis subsystem.

Usage:

    python -m paddle_tpu lint --path paddle_tpu --format json
    python -m paddle_tpu lint --config demo/mnist/conf.py --fail-on WARN
    python -m paddle_tpu lint --config conf.py --allowlist .tpu-lint-allow
    python -m paddle_tpu lint --decode B,S,K,L
    python -m paddle_tpu lint --serve model.ptz
    python -m paddle_tpu lint --deploy model.ptz
    python -m paddle_tpu lint --pserver V,D,N,S
    python -m paddle_tpu lint --obs
    python -m paddle_tpu lint --race --protocol --hbm
    python -m paddle_tpu lint --all --format sarif

``--path DIR`` runs the AST trace-safety linter over the tree;
``--config CONF.py`` additionally builds the config's trainer and audits
the closed jaxpr of its train step (the jaxpr auditor).  Both may repeat.
With neither, the installed ``paddle_tpu`` package itself is linted.

``--serve BUNDLE.ptz`` is the serving preflight: the bundle's inference
closure is audited with the serving check set (host transfers on the
request path, >1 MiB folded constants — weights must ride as arguments,
not baked into the executable), the same gate
``InferenceServer.start(preflight=True)`` applies before reporting ready.
A ``--serve`` run ALSO audits the continuous-batching ``decode_step``
closure (the slot-table fused step, serving/slots.py) with the decode
check set — a host transfer there fires once per token per resident
request, the same contract as ``audit_decode``; both readout variants
are traced (the kernel in interpret mode off-TPU).

``--deploy BUNDLE.ptz`` extends the offline preflight to QUANTIZED
bundles (docs/deploy.md): the dequantized forward — and, for int8
bundles, the in-trace-dequantize closure — is audited for
dtype-promotion and constant-bloat; an int8 table accidentally
materialized as f32 constants is exactly the constant-bloat check's job.

``--pserver [V,D,N,S]`` audits the sharded-embedding tier's compiled
all-to-all lookup and row-sparse apply closures (paddle_tpu/pserver) with
the serving check set, and additionally asserts the "never densify"
contract: no ``[V, D]``-shaped gradient or optimizer temp may appear in
the sparse-apply jaxpr, and no broadcast may conjure a per-shard dense
buffer (``analysis.audit_no_dense_rows``).

``--obs`` gates the telemetry contract (docs/observability.md): the
trainer's jitted step is traced with the step timeline / MFU plumbing
enabled, audited for host transfers and constant bloat (the
``audit_decode`` contract), and diffed equation-for-equation against the
telemetry-disabled trace — instrumentation must live in host-side Python
around the existing per-batch sync, never inside the compiled program.
The same gate covers request tracing (obs/trace.py): the train step AND
the continuous-batching ``decode_step`` are re-traced with tracing armed
(``--obs_journal`` + ``--trace_sample``) and must be equation-identical
to tracing-off — spans add ZERO compiled equations.

``--decode [B,S,K,L]`` audits the compiled decode closure of the flagship
generation path (Seq2SeqAttention.beam_search over the fused decode
engine, ops/decode.py) with the decode check set — host transfers inside
the token loop, >1 MiB folded constants, and the tile alignment of the
vocab-tiled top-k readout kernel's BlockSpecs.  Both the kernel and the
XLA-fallback variants are traced (the kernel in interpret mode off-TPU),
so a serving regression fails lint on any backend.

``--race [FILE]`` runs the host-concurrency lock-discipline checker over
the known concurrent classes (serving, feeder prefetch, obs registries,
the gang cluster): the guard lock of each mutable attribute is inferred
from ``with self._lock:`` usage, and any read/write reachable from a
cross-thread entry point outside the guard is flagged — intentionally
lock-free fields carry ``# tpu-lint: guarded-by=none - <invariant>``
annotations.  Lock-order inversions across classes are ERRORs.

``--protocol [FILE]`` runs the gang collective/barrier protocol checker
over trainer + cluster + checkpoint_io + integrity: on a rank-conditional
branch both sides must reach the SAME collectives in the SAME order (the
read-first-grow deadlock shape), and an except handler may not swallow or
exit past a collective its peers still block on.

``--hbm`` runs the static HBM audit over the real compiled train and
decode steps: peak-live-bytes (liveness walk, donation credited) vs the
chip HBM table, donated-buffer-use-after-donation, and f64/weak-type
constants that defeat the compile-cache key.

``--all`` runs every registered pass (tree lint + decode + pserver + obs
+ amp + sdc + race + protocol + hbm + the slot-step audit).

Exit status (uniform across every pass — docs/lint.md has the matrix):
0 = ran clean, 1 = findings at/above ``--fail-on`` (default ERROR)
survive suppression, 2 = usage error (unknown flag, unreadable
allowlist).  ``--fail-on NEVER`` always exits 0 after a successful run.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional

from paddle_tpu.analysis.findings import (Finding, apply_allowlist,
                                          format_findings, load_allowlist,
                                          severity_at_least)

__all__ = ["run"]


def _audit_config(conf_path: str) -> List[Finding]:
    """Build the config's trainer and audit its step jaxpr; AST-lint the
    config source as well (configs are user code running under trace)."""
    from paddle_tpu.__main__ import _build_trainer, _first_feed, _load_config
    from paddle_tpu.analysis.ast_lint import lint_file

    findings = lint_file(conf_path)
    try:
        conf = _load_config(conf_path)
        trainer = _build_trainer(conf)
        feed = _first_feed(conf)
    except Exception as e:
        findings.append(Finding(
            check="config-build", severity="ERROR", file=conf_path,
            message=f"config failed to build a trainer: "
                    f"{type(e).__name__}: {e}"))
        return findings
    label = os.path.basename(conf_path)
    try:
        findings.extend(trainer.audit(feed, label=f"{label}:train_step"))
    except Exception as e:  # a step that fails to TRACE is itself a finding
        findings.append(Finding(
            check="config-build", severity="ERROR", file=conf_path,
            message=f"train step failed to trace for auditing: "
                    f"{type(e).__name__}: {e}"))
    return findings


def _audit_decode_closure(spec: str) -> List[Finding]:
    """Trace the flagship decode at a compact flagship-shaped model
    (lane-aligned dims, tiled vocab — structure, not perf) and audit both
    readout variants.  ``spec``: 'B,S,K,L' (defaults 8,8,4,8 — B*K=32
    keeps the kernel variant inside its sublane-aligned row gate)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.analysis.jaxpr_audit import audit_decode
    from paddle_tpu.models import Seq2SeqAttention

    from paddle_tpu.ops.decode import _forced_kernel_config

    try:
        dims = [int(x) for x in spec.split(",")] if spec else []
    except ValueError:
        return [Finding(
            check="decode-build", severity="ERROR", file="--decode",
            message=f"malformed --decode spec {spec!r}: expected up to four "
                    f"comma-separated ints 'B,S,K,L'")]
    B, S, K, L = (dims + [8, 8, 4, 8][len(dims):])[:4]
    m = Seq2SeqAttention(src_vocab=1024, trg_vocab=1024, emb_dim=128,
                         enc_dim=128, dec_dim=128, att_dim=128)
    params = m.init(jax.random.PRNGKey(0))
    src = jnp.zeros((B, S), jnp.int32)
    src_len = jnp.full((B,), S, jnp.int32)
    findings: List[Finding] = []
    variants = [(False, "xla_topk")]
    if _forced_kernel_config(B * K, m.dec_dim, m.trg_vocab, K) is not None:
        variants.insert(0, (True, "kernel"))
    else:
        findings.append(Finding(
            check="decode-build", severity="INFO", file="decode[kernel]",
            message=f"kernel variant gated at B*K={B * K}, k={K} (needs a "
                    f"sublane-aligned row block and k<=16) — audited the "
                    f"XLA fallback only"))
    for use_kernel, tag in variants:
        try:
            findings.extend(audit_decode(
                lambda p, s, l, uk=use_kernel: m.beam_search(
                    p, s, l, beam_size=K, max_len=L, use_kernel=uk),
                params, src, src_len, label=f"decode[{tag}]:beam{K}"))
        except Exception as e:  # a decode that fails to TRACE is a finding
            findings.append(Finding(
                check="decode-build", severity="ERROR",
                file=f"decode[{tag}]",
                message=f"decode closure failed to trace: "
                        f"{type(e).__name__}: {e}"))
    return findings


def _audit_serving_bundle(bundle: str) -> List[Finding]:
    """``lint --serve BUNDLE.ptz``: load the deploy bundle and trace its
    serving closure through the auditor's host-transfer/constant-bloat
    checks — the same preflight ``InferenceServer.start(preflight=True)``
    runs before reporting ready (fail-fast, like ``v2.infer(audit=True)``).
    Bundle-integrity failures (BundleCorruptError) are findings too: a
    corrupt artifact must fail lint, not crash it."""
    try:
        from paddle_tpu.config.deploy import load_inference_model

        model = load_inference_model(bundle)
    except Exception as e:
        return [Finding(
            check="serve-build", severity="ERROR", file=bundle,
            message=f"bundle failed to load: {type(e).__name__}: {e}")]
    try:
        from paddle_tpu.serving.preflight import audit_serving

        return audit_serving(model, label=f"serve:{os.path.basename(bundle)}")
    except Exception as e:  # a closure that fails to TRACE is a finding
        return [Finding(
            check="serve-build", severity="ERROR", file=bundle,
            message=f"serving closure failed to trace: "
                    f"{type(e).__name__}: {e}")]


def _audit_serving_fleet(bundles: List[str]) -> List[Finding]:
    """``lint --serve A.ptz --serve B.ptz ...`` with SEVERAL bundles:
    the fleet preflight.  The bundles are loaded into a model table
    exactly as ``ModelFleet`` would serve them (one entry per bundle,
    servers never started) and ``ModelFleet.audit()`` traces the
    compiled serving closure of EVERY entry — each finding labeled
    ``fleet:<name>@v<version>``, so one bad entry in a fleet rollout
    is named, not averaged away.  A bundle that fails to load is an
    ERROR finding, and the remaining entries are still audited."""
    from paddle_tpu.serving.fleet import ModelFleet

    fleet = ModelFleet()
    findings: List[Finding] = []
    try:
        for bundle in bundles:
            name = os.path.splitext(os.path.basename(bundle))[0] or bundle
            try:
                from paddle_tpu.config.deploy import load_inference_model

                model = load_inference_model(bundle)
                fleet.add_model(name, model, start=False)
            except Exception as e:  # noqa: BLE001 — audit the rest
                findings.append(Finding(
                    check="serve-build", severity="ERROR", file=bundle,
                    message=f"bundle failed to load: "
                            f"{type(e).__name__}: {e}"))
        findings.extend(fleet.audit())
    finally:
        fleet.close()
    return findings


def _audit_deploy_bundle(bundle: str) -> List[Finding]:
    """``lint --deploy BUNDLE.ptz`` — the offline preflight extended to
    QUANTIZED bundles (docs/deploy.md): the dequantized forward is traced
    through the dtype-promotion and constant-bloat checks (params ride as
    arguments, so an int8 table accidentally materialized as f32
    *constants* is exactly what constant-bloat catches), and for int8
    bundles the in-trace-dequantize closure is audited too — the same
    gate ``load_inference_model(int8_in_trace=True)`` applies before it
    keeps weights quantized in HBM.  Bundle-integrity failures are ERROR
    findings, never crashes."""
    try:
        from paddle_tpu.config.deploy import load_inference_model
        from paddle_tpu.nn.feeds import example_feed

        model = load_inference_model(bundle)
    except Exception as e:
        return [Finding(
            check="deploy-build", severity="ERROR", file=bundle,
            message=f"bundle failed to load: {type(e).__name__}: {e}")]
    base = os.path.basename(bundle)
    qmode = (model.manifest.get("quantize") or {}).get("mode") or "f32"
    variants = [(model, f"deploy[{qmode}]:{base}")]
    if any(m.get("mode") == "int8" for m in
           (model.manifest.get("quantize") or {}).get("arrays", {}).values()):
        try:
            m8 = load_inference_model(bundle, int8_in_trace=True)
            if m8._int8:  # the gate admitted the in-trace closure
                variants.append((m8, f"deploy[int8_in_trace]:{base}"))
        except Exception as e:  # noqa: BLE001 — audited best-effort
            return [Finding(
                check="deploy-build", severity="ERROR", file=bundle,
                message=f"int8 in-trace load failed: "
                        f"{type(e).__name__}: {e}")]
    findings: List[Finding] = []
    for m, label in variants:
        try:
            from paddle_tpu.analysis.jaxpr_audit import audit_fn

            names = tuple(m.output_names)
            findings.extend(audit_fn(
                m._make_run(names), m.params, m.state,
                example_feed(m.topology), label=label,
                checks=["dtype-promotion", "constant-bloat"]))
        except Exception as e:  # a closure that fails to TRACE is a finding
            findings.append(Finding(
                check="deploy-build", severity="ERROR", file=bundle,
                message=f"{label} failed to trace: "
                        f"{type(e).__name__}: {e}"))
    return findings


def _audit_slot_step_closure() -> List[Finding]:
    """The continuous-batching half of ``--serve``: audit the compiled
    ``decode_step`` closure over a slot table at a compact flagship shape
    (serving.slots.audit_slot_backend — same check set and contract as
    ``--decode``), plus the speculative wide-verify closure over a greedy
    (``beam_size == 1``) table (docs/decode.md "Speculative decoding").
    One audit per lint run, independent of how many bundles were given:
    the step programs are the serving tier's, not a bundle's."""
    try:
        from paddle_tpu.serving.slots import (audit_slot_backend,
                                              example_slot_backend)

        findings = list(audit_slot_backend())
        findings.extend(audit_slot_backend(
            example_slot_backend(slots=4, beam_size=1),
            slots=4, label="serve_slots[greedy]", spec_k=4))
        return findings
    except Exception as e:  # a step that fails to BUILD is a finding
        return [Finding(
            check="serve-build", severity="ERROR", file="serve_slots",
            message=f"slot decode_step closure failed to build: "
                    f"{type(e).__name__}: {e}")]


def run(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu lint",
        description="Static trace-safety linter + jaxpr auditor "
                    "(docs/lint.md has the check catalog)")
    p.add_argument("--config", action="append", default=[], metavar="CONF",
                   help="audit the train step of this config (repeatable)")
    p.add_argument("--path", action="append", default=[], metavar="DIR",
                   help="AST-lint this file/tree (repeatable)")
    p.add_argument("--decode", nargs="?", const="", default=None,
                   metavar="B,S,K,L",
                   help="audit the flagship fused-decode closure "
                        "(kernel + XLA-fallback variants) at these shapes")
    p.add_argument("--pserver", nargs="?", const="", default=None,
                   metavar="V,D,N,S",
                   help="audit the pserver lookup/sparse-apply closures "
                        "and gate the never-densify contract")
    p.add_argument("--obs", action="store_true",
                   help="audit the telemetry contract: the compiled train "
                        "step with the timeline/MFU plumbing enabled must "
                        "be host-transfer-free AND identical to the "
                        "telemetry-off trace; also pins the train step "
                        "and decode_step identical with request tracing "
                        "armed (spans add zero compiled equations)")
    p.add_argument("--sdc", action="store_true",
                   help="audit the SDC-firewall contract: the compiled "
                        "step with --sdc_check_every=0 must be "
                        "equation-identical to a never-enabled build, "
                        "and the in-jit state fingerprint (check on) "
                        "must audit host-transfer-free "
                        "(docs/resilience.md 'Silent corruption')")
    p.add_argument("--amp", action="store_true",
                   help="audit the mixed-precision contract: the compiled "
                        "--amp train step (forward + backward + loss "
                        "scaling + optimizer apply) must contain ZERO "
                        "non-allowlisted all-f32 dot_general/conv eqns "
                        "(docs/mixed_precision.md)")
    p.add_argument("--serve", action="append", default=[],
                   metavar="BUNDLE.ptz",
                   help="serving preflight: audit a deploy bundle's "
                        "serving closure (host-transfer/constant-bloat; "
                        "repeatable — several bundles audit as a FLEET "
                        "model table, every entry traced and labeled "
                        "fleet:<name>@v<version>)")
    p.add_argument("--deploy", action="append", default=[],
                   metavar="BUNDLE.ptz",
                   help="deploy preflight incl. QUANTIZED bundles: audit "
                        "the dequantized forward (and the int8 in-trace "
                        "closure) for dtype-promotion and constant-bloat "
                        "(repeatable; docs/deploy.md)")
    p.add_argument("--race", nargs="?", const="", default=None,
                   metavar="FILE",
                   help="host-concurrency race lint: infer each mutable "
                        "attribute's guard lock and flag cross-thread "
                        "access outside it (default: the known concurrent "
                        "classes; FILE restricts to one module)")
    p.add_argument("--protocol", nargs="?", const="", default=None,
                   metavar="FILE",
                   help="gang collective/barrier protocol checker: both "
                        "sides of a rank-conditional branch must reach "
                        "the same collectives in the same order (default: "
                        "trainer + resilience tier; FILE restricts)")
    p.add_argument("--hbm", action="store_true",
                   help="static HBM audit of the real compiled train and "
                        "decode steps: peak-live-bytes vs the chip table, "
                        "donation honored, no f64/weak-type cache-key "
                        "poison")
    p.add_argument("--all", action="store_true",
                   help="run every registered pass (tree lint + decode + "
                        "pserver + obs + amp + sdc + race + protocol + "
                        "hbm + slot-step audit)")
    p.add_argument("--format", choices=("text", "json", "sarif"),
                   default="text")
    p.add_argument("--fail-on", default="ERROR", type=str.upper,
                   choices=("ERROR", "WARN", "INFO", "NEVER"),
                   help="exit 1 when findings at/above this severity remain")
    p.add_argument("--allowlist", metavar="FILE",
                   help="suppression file: '<check-id> [message substring]' "
                        "per line")
    try:
        ns = p.parse_args(argv)
    except SystemExit as e:
        if e.code in (0, None):  # --help: the documented SystemExit(0)
            raise
        return 2  # unknown flag / bad choice: usage error, uniformly 2

    allow_entries = None
    if ns.allowlist:
        try:  # validate BEFORE the passes run: a typo'd path is a usage
            # error, not a full lint run followed by a crash
            allow_entries = load_allowlist(ns.allowlist)
        except OSError as e:
            print(f"lint: cannot read allowlist {ns.allowlist!r}: {e}",
                  file=sys.stderr)
            return 2

    if ns.all:
        # every registered pass; explicit flags keep their given specs
        ns.decode = ns.decode if ns.decode is not None else ""
        ns.pserver = ns.pserver if ns.pserver is not None else ""
        ns.obs = ns.amp = ns.sdc = ns.hbm = True
        ns.race = ns.race if ns.race is not None else ""
        ns.protocol = ns.protocol if ns.protocol is not None else ""

    targets = list(ns.path)
    configs = list(ns.config)
    if (not targets and not configs and ns.decode is None
            and ns.pserver is None and not ns.serve and not ns.obs
            and not ns.amp and not ns.deploy and not ns.sdc
            and ns.race is None and ns.protocol is None and not ns.hbm):
        targets = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
    if ns.all and not ns.path:
        targets = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

    findings: List[Finding] = []
    from paddle_tpu.analysis.ast_lint import lint_path

    for path in targets:
        if not os.path.exists(path):
            findings.append(Finding(check="bad-target", severity="ERROR",
                                    file=path, message="no such file or "
                                    "directory"))
            continue
        findings.extend(lint_path(path))
    for conf in configs:
        findings.extend(_audit_config(conf))
    if ns.decode is not None:
        findings.extend(_audit_decode_closure(ns.decode))
    if ns.pserver is not None:
        from paddle_tpu.pserver import audit_pserver

        findings.extend(audit_pserver(ns.pserver))
    if ns.obs:
        from paddle_tpu.obs.audit import audit_telemetry_step

        findings.extend(audit_telemetry_step())
    if ns.amp:
        from paddle_tpu.analysis.amp_audit import audit_amp_step

        findings.extend(audit_amp_step())
    if ns.sdc:
        from paddle_tpu.resilience.integrity import audit_sdc_step

        findings.extend(audit_sdc_step())
    if ns.race is not None:
        from paddle_tpu.analysis.static import run_race

        findings.extend(run_race((ns.race,) if ns.race else ()))
    if ns.protocol is not None:
        from paddle_tpu.analysis.static import run_protocol

        findings.extend(run_protocol((ns.protocol,) if ns.protocol else ()))
    if ns.hbm:
        from paddle_tpu.analysis.static import run_hbm

        findings.extend(run_hbm())
    if len(ns.serve) > 1:
        # several bundles = a fleet: every model-table entry's closure
        # is audited, findings labeled fleet:<name>@v<version>
        findings.extend(_audit_serving_fleet(ns.serve))
    else:
        for bundle in ns.serve:
            findings.extend(_audit_serving_bundle(bundle))
    if ns.serve or ns.all:
        # --serve also gates the continuous path's fused step (once);
        # --all runs the bundle-independent half even with no bundle
        findings.extend(_audit_slot_step_closure())
    for bundle in ns.deploy:
        findings.extend(_audit_deploy_bundle(bundle))

    if allow_entries is not None:
        findings = apply_allowlist(findings, allow_entries)

    print(format_findings(findings, ns.format))
    if ns.fail_on == "NEVER":
        return 0
    return 1 if severity_at_least(findings, ns.fail_on) else 0


if __name__ == "__main__":
    sys.exit(run())
