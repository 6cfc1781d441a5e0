"""Trace-time lint subsystem (paddle_tpu/analysis; docs/lint.md).

Three tiers:
- unit tests per check: each auditor/AST check FIRES on a known-bad input
  and stays QUIET on a known-good one;
- the deliberately-bad fixture config (tests/fixtures/lint_bad_config.py)
  must report all five planted check ids through the real CLI with correct
  provenance;
- the CI step: ``python -m paddle_tpu lint --path paddle_tpu`` run
  in-process — the suite fails on new ERROR-severity findings in our own
  tree, and the golden nets must audit clean.
"""

import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.analysis import (audit_fn, eqn_subjaxprs, find_primitives,
                                 hlo_control_flow, lint_source,
                                 severity_at_least)
from paddle_tpu.analysis.flops import jaxpr_flops

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
FIXTURE = os.path.join(HERE, "fixtures", "lint_bad_config.py")


def _checks(findings):
    return {f.check for f in findings}


# ---------------------------------------------------------------------------
# jaxpr auditor units
# ---------------------------------------------------------------------------


def test_host_transfer_fires_on_live_device_put():
    fs = audit_fn(lambda x: jax.device_put(x) + 1.0, jnp.ones((4, 8)),
                  label="t")
    hits = [f for f in fs if f.check == "host-transfer"]
    assert hits and hits[0].severity == "ERROR"
    assert "device_put" in hits[0].where  # eqn provenance


def test_host_transfer_quiet_on_constant_placement():
    big = np.ones((64, 64), np.float32)  # const hoisting, not a transfer
    fs = audit_fn(lambda x: x + jnp.asarray(big), jnp.ones((64, 64)),
                  label="t")
    assert "host-transfer" not in _checks(fs)


def test_host_transfer_fires_on_callback():
    def f(x):
        return jax.pure_callback(
            lambda v: np.asarray(v) * 2,
            jax.ShapeDtypeStruct((4,), jnp.float32), x)

    assert "host-transfer" in _checks(audit_fn(f, jnp.ones(4), label="t"))


def test_constant_bloat_fires_above_1mib_only():
    big = np.ones((400_000,), np.float32)   # 1.5 MiB
    small = np.ones((1000,), np.float32)
    fs = audit_fn(lambda x: x + jnp.asarray(big).sum(), jnp.ones(()),
                  label="t")
    hits = [f for f in fs if f.check == "constant-bloat"]
    assert hits and "1.5 MiB" in hits[0].message
    fs2 = audit_fn(lambda x: x + jnp.asarray(small).sum(), jnp.ones(()),
                   label="t")
    assert "constant-bloat" not in _checks(fs2)


def test_dtype_promotion_fires_on_mixed_net_only():
    wb = jnp.ones((8, 8), jnp.bfloat16)
    wf = jnp.ones((8, 8), jnp.float32)

    def mixed(x):
        return (x.astype(jnp.bfloat16) @ wb).astype(jnp.float32).sum() + \
            (x @ wf).sum()

    fs = audit_fn(mixed, jnp.ones((4, 8)), label="t")
    hits = [f for f in fs if f.check == "dtype-promotion"]
    assert hits and "dot_general" in hits[0].where

    def pure_f32(x):
        return (x @ wf).sum()

    assert "dtype-promotion" not in _checks(
        audit_fn(pure_f32, jnp.ones((4, 8)), label="t"))


def _pallas_double(block_rows, n_rows):
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2

    def f(x):
        return pl.pallas_call(
            kern, grid=(n_rows // block_rows,),
            in_specs=[pl.BlockSpec((block_rows, 256), lambda n: (n, 0))],
            out_specs=pl.BlockSpec((block_rows, 256), lambda n: (n, 0)),
            out_shape=jax.ShapeDtypeStruct((n_rows, 256), jnp.float32),
            interpret=True)(x)

    return f


def test_pallas_tile_check_fires_on_sublane_violation():
    fs = audit_fn(_pallas_double(4, 12), jnp.ones((12, 256)), label="t")
    hits = [f for f in fs if f.check == "unaligned-pallas-tile"]
    assert hits and "sublane" in hits[0].message


def test_pallas_tile_check_exempts_aligned_and_full_dim():
    # aligned (8, 256) tile
    fs = audit_fn(_pallas_double(8, 16), jnp.ones((16, 256)), label="t")
    assert "unaligned-pallas-tile" not in _checks(fs)
    # block == full array dim (Mosaic pads): 3 rows, block 3
    fs2 = audit_fn(_pallas_double(3, 3), jnp.ones((3, 256)), label="t")
    assert "unaligned-pallas-tile" not in _checks(fs2)


def test_unsharded_op_fires_without_constraints_and_not_with():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("data",))

    def f(x):
        return x @ x.T

    x = jnp.ones((256, 256))
    fs = audit_fn(f, x, mesh=mesh, label="t")
    assert "unsharded-op" in _checks(fs)

    def g(x):
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("data")))
        return x @ x.T

    assert "unsharded-op" not in _checks(audit_fn(g, x, mesh=mesh, label="t"))
    # sharded INPUT also satisfies the check (GSPMD propagates from args)
    xs = jax.device_put(x, NamedSharding(mesh, P("data")))
    assert "unsharded-op" not in _checks(audit_fn(f, xs, mesh=mesh,
                                                  label="t"))
    # no mesh -> check is off entirely
    assert "unsharded-op" not in _checks(audit_fn(f, x, label="t"))


# ---------------------------------------------------------------------------
# shared jaxpr walker (what analysis.flops.jaxpr_flops walks with)
# ---------------------------------------------------------------------------


def test_flops_custom_vjp_counted_once():
    """Primitives carrying several sub-jaxprs (custom_vjp holds primal +
    fwd/bwd rules) must count the primal ONCE."""
    @jax.custom_vjp
    def f(x, w):
        return x @ w

    def fwd(x, w):
        return x @ w, (x, w)

    def bwd(res, g):
        x, w = res
        return g @ w.T, x.T @ g

    f.defvjp(fwd, bwd)
    x, w = jnp.ones((4, 8)), jnp.ones((8, 16))
    flops = jaxpr_flops(lambda c: f(*c), (x, w))
    assert flops == 2.0 * 4 * 16 * 8  # one M=4,N=16,K=8 matmul, exactly


def test_flops_scan_body_multiplied_by_trip_count():
    w = jnp.ones((8, 8))

    def fn(c):
        out, _ = jax.lax.scan(lambda c, _: (c @ w, None), c, None, length=10)
        return out

    assert jaxpr_flops(fn, jnp.ones((4, 8))) == 10 * 2.0 * 4 * 8 * 8


def test_flops_grad_of_custom_vjp_uses_bwd_rule_once():
    @jax.custom_vjp
    def f(x, w):
        return x @ w

    def fwd(x, w):
        return x @ w, (x, w)

    def bwd(res, g):
        x, w = res
        return g @ w.T, x.T @ g

    f.defvjp(fwd, bwd)
    x, w = jnp.ones((4, 8)), jnp.ones((8, 16))

    def loss(c):
        return f(*c).sum()

    flops = jaxpr_flops(lambda c: jax.grad(loss)(c), (x, w))
    # fwd matmul + the two bwd matmuls: 2*(4*16*8) each
    assert flops == 3 * (2.0 * 4 * 16 * 8)


def test_find_primitives_sees_nested_scan():
    def fn(c):
        out, _ = jax.lax.scan(lambda c, _: (c * 2, None), c, None, length=3)
        return out

    closed = jax.make_jaxpr(fn)(jnp.ones(4))
    names = [n for n, _ in find_primitives(closed.jaxpr, {"scan"})]
    assert names == ["scan"]


def test_hlo_control_flow_detects_while():
    def loopy(x):
        return jax.lax.fori_loop(0, 3, lambda i, c: c + 1.0, x)

    txt = jax.jit(loopy).lower(jnp.zeros(())).compiler_ir(
        dialect="hlo").as_hlo_text()
    assert "while" in hlo_control_flow(txt)
    txt2 = jax.jit(lambda x: x + 1).lower(jnp.zeros(())).compiler_ir(
        dialect="hlo").as_hlo_text()
    assert hlo_control_flow(txt2) == []


# ---------------------------------------------------------------------------
# AST trace-safety linter units
# ---------------------------------------------------------------------------


def _lint(src):
    return lint_source(src, "probe.py")


def test_ast_tracer_leak_variants():
    src = (
        "import jax\nimport numpy as np\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    a = float(x)\n"
        "    b = np.asarray(x)\n"
        "    c = x.item()\n"
        "    return a + b + c\n")
    fs = _lint(src)
    leaks = [f for f in fs if f.check == "tracer-leak"]
    assert len(leaks) == 3
    assert all(f.severity == "ERROR" for f in leaks)
    assert sorted(f.line for f in leaks) == [5, 6, 7]


def test_ast_tracer_leak_requires_jit_context_and_taint():
    # same calls OUTSIDE a jit context: clean
    assert not _lint("import numpy as np\ndef f(x):\n    return float(x)\n")
    # float() on a non-parameter value inside jit: clean
    src = ("import jax\n@jax.jit\n"
           "def f(x):\n"
           "    k = 3\n"
           "    return x * float(k)\n")
    assert not _lint(src)
    # taint propagates through assignment
    src2 = ("import jax\n@jax.jit\n"
            "def f(x):\n"
            "    y = x * 2\n"
            "    return float(y)\n")
    assert [f.check for f in _lint(src2)] == ["tracer-leak"]


def test_ast_non_jax_jit_decorators_are_not_jit_contexts():
    # import provenance wins: numba's jit is not a trace context
    src = ("from numba import jit\n"
           "@jit\n"
           "def f(x):\n"
           "    return float(x)\n")
    assert not _lint(src)


def test_ast_taint_flows_through_for_loop_targets():
    src = ("import jax\n@jax.jit\n"
           "def f(xs):\n"
           "    out = 0.0\n"
           "    for row in xs:\n"
           "        out = out + float(row)\n"
           "    return out\n")
    assert [f.check for f in _lint(src)] == ["tracer-leak"]


def test_ast_detects_jit_by_call_reference():
    src = ("import jax\n"
           "def step(x):\n"
           "    return float(x)\n"
           "run = jax.jit(step)\n")
    assert [f.check for f in _lint(src)] == ["tracer-leak"]


def test_ast_tracer_branch_and_static_exemptions():
    src = ("import jax\n@jax.jit\n"
           "def f(x, flag=None):\n"
           "    if x > 0:\n"
           "        x = x + 1\n"
           "    if flag is None:\n"
           "        x = x * 2\n"
           "    if x.shape[0] > 1:\n"
           "        x = x / 2\n"
           "    return x\n")
    fs = _lint(src)
    assert [f.check for f in fs] == ["tracer-branch"]
    assert fs[0].line == 4  # only the value branch; is-None/.shape exempt


def test_ast_impure_and_set_iter_and_jit_in_loop():
    src = ("import jax, time\nimport numpy as np\n"
           "@jax.jit\n"
           "def f(x):\n"
           "    t = time.time()\n"
           "    n = np.random.rand()\n"
           "    for s in {1, 2}:\n"
           "        x = x + s\n"
           "    return x + t + n\n"
           "def outer():\n"
           "    for i in range(3):\n"
           "        g = jax.jit(lambda v: v)\n"
           "    return g\n")
    checks = sorted(f.check for f in _lint(src))
    assert checks == ["impure-call", "impure-call", "jit-in-loop", "set-iter"]


def test_ast_suppression_line_and_function_scope():
    src = ("import jax\n"
           "@jax.jit\n"
           "def f(x):\n"
           "    return float(x)  # tpu-lint: disable=tracer-leak\n"
           "@jax.jit\n"
           "def g(x):  # tpu-lint: disable=all\n"
           "    if x > 0:\n"
           "        return float(x)\n"
           "    return x\n"
           "@jax.jit\n"
           "def h(x):  # tpu-lint: disable=tracer-branch\n"
           "    if x > 0:\n"
           "        return float(x)\n"
           "    return x\n")
    fs = _lint(src)
    # f and g fully silenced; h keeps only the tracer-leak
    assert [(f.check, f.line) for f in fs] == [("tracer-leak", 13)]


def test_allowlist_filters_findings(tmp_path):
    from paddle_tpu.analysis import Finding, apply_allowlist, load_allowlist

    allow = tmp_path / "allow"
    allow.write_text("# comment\nhost-transfer\ndtype-promotion bf16\n")
    entries = load_allowlist(str(allow))
    fs = [Finding("host-transfer", "ERROR", "m", where="a"),
          Finding("dtype-promotion", "WARN", "runs near bf16 net", where="b"),
          Finding("dtype-promotion", "WARN", "other", where="c"),
          Finding("constant-bloat", "WARN", "m", where="d")]
    kept = apply_allowlist(fs, entries)
    assert [(f.check, f.where) for f in kept] == [
        ("dtype-promotion", "c"), ("constant-bloat", "d")]
    # the substring matches the MESSAGE only — never the path/severity of
    # the formatted line ('tests' here must not suppress by file path)
    f_path = Finding("tracer-leak", "ERROR", "float() on a traced value",
                     file="tests/probe.py", line=3)
    assert apply_allowlist([f_path], [("tracer-leak", "tests")]) == [f_path]


# ---------------------------------------------------------------------------
# the deliberately-bad fixture through the real CLI
# ---------------------------------------------------------------------------


def test_cli_bad_fixture_reports_all_five_checks(capsys):
    from paddle_tpu.analysis.cli import run

    rc = run(["--config", FIXTURE, "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    checks = {f["check"] for f in out["findings"]}
    assert {"dtype-promotion", "host-transfer", "constant-bloat",
            "unaligned-pallas-tile", "tracer-leak"} <= checks
    assert rc == 1  # tracer-leak / host-transfer are ERRORs
    # provenance: AST finding -> fixture file:line; auditor -> eqn path
    tl = next(f for f in out["findings"] if f["check"] == "tracer-leak")
    assert tl["file"].endswith("lint_bad_config.py") and tl["line"] > 0
    ht = next(f for f in out["findings"] if f["check"] == "host-transfer")
    assert "train_step" in ht["where"] and "device_put" in ht["where"]
    pt = next(f for f in out["findings"]
              if f["check"] == "unaligned-pallas-tile")
    assert "pallas_call" in pt["where"]


def test_cli_allowlist_and_fail_on(tmp_path, capsys):
    from paddle_tpu.analysis.cli import run

    allow = tmp_path / "allow"
    allow.write_text("tracer-leak\nhost-transfer\n")
    rc = run(["--config", FIXTURE, "--format", "json",
              "--allowlist", str(allow)])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0  # remaining findings are WARN, default gate is ERROR
    assert not [f for f in out["findings"] if f["severity"] == "ERROR"]
    rc = run(["--config", FIXTURE, "--format", "json",
              "--allowlist", str(allow), "--fail-on", "WARN"])
    capsys.readouterr()
    assert rc == 1


# ---------------------------------------------------------------------------
# hooks: trainer.audit + deploy manifest
# ---------------------------------------------------------------------------


def _tiny_classifier():
    import paddle_tpu.nn as nn

    nn.reset_naming()
    x = nn.data("x", size=6)
    out = nn.fc(x, 3, act="softmax", name="out")
    label = nn.data("label", size=3, dtype="int32")
    cost = nn.classification_cost(out, label, name="cost")
    return cost


def test_trainer_audit_clean_on_golden_style_net(rng):
    from paddle_tpu.trainer import SGDTrainer

    tr = SGDTrainer(_tiny_classifier())
    feed = {"x": rng.rand(4, 6).astype(np.float32),
            "label": rng.randint(0, 3, (4, 1)).astype(np.int32)}
    fs = tr.audit(feed)
    assert not severity_at_least(fs, "ERROR")


def test_deploy_exports_attach_lint_manifest(tmp_path, rng):
    import zipfile

    import paddle_tpu.nn as nn
    from paddle_tpu.config import export_aot, merge_model
    from paddle_tpu.nn.graph import Topology

    cost = _tiny_classifier()
    topo = Topology(cost)
    params, state = topo.init(jax.random.PRNGKey(0))
    feed = {"x": rng.rand(2, 6).astype(np.float32),
            "label": rng.randint(0, 3, (2, 1)).astype(np.int32)}
    bundle = str(tmp_path / "m.ptz")
    merge_model(bundle, topo, params, state, name="lint_test",
                example_feed=feed)
    with zipfile.ZipFile(bundle) as z:
        manifest = json.loads(z.read("manifest.json"))
    assert isinstance(manifest["lint"], list)
    assert not [f for f in manifest["lint"] if f["severity"] == "ERROR"]

    aot = str(tmp_path / "m.aot")
    export_aot(bundle, aot, {"x": feed["x"]}, outputs=["out"])
    with zipfile.ZipFile(aot) as z:
        manifest = json.loads(z.read("manifest.json"))
    assert isinstance(manifest["lint"], list)


def test_deploy_lint_flag_disables_manifest_audit(tmp_path, monkeypatch, rng):
    import zipfile

    from paddle_tpu.config import merge_model
    from paddle_tpu.nn.graph import Topology
    from paddle_tpu.utils.flags import FLAGS

    monkeypatch.setattr(FLAGS, "deploy_lint", False)
    topo = Topology(_tiny_classifier())
    params, state = topo.init(jax.random.PRNGKey(0))
    feed = {"x": rng.rand(2, 6).astype(np.float32),
            "label": rng.randint(0, 3, (2, 1)).astype(np.int32)}
    bundle = str(tmp_path / "m.ptz")
    merge_model(bundle, topo, params, state, example_feed=feed)
    with zipfile.ZipFile(bundle) as z:
        manifest = json.loads(z.read("manifest.json"))
    assert manifest["lint"] == []


# ---------------------------------------------------------------------------
# CI gates: our own tree + the golden nets must be ERROR-free
# ---------------------------------------------------------------------------


def test_ci_lint_own_tree_is_error_free(capsys):
    """The tier-1 lint step: new ERROR-severity findings in paddle_tpu/
    fail the suite (use `# tpu-lint: disable=<check>` for justified
    exceptions — see docs/lint.md)."""
    from paddle_tpu.__main__ import main

    rc = main(["lint", "--path", os.path.join(ROOT, "paddle_tpu"),
               "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    errors = [f for f in out["findings"] if f["severity"] == "ERROR"]
    assert rc == 0 and not errors, errors


def test_golden_nets_audit_error_free():
    import paddle_tpu.nn as nn
    from golden_nets import GOLDEN_NETS

    rng = np.random.RandomState(0)
    for name, build in sorted(GOLDEN_NETS.items()):
        nn.reset_naming()
        topo, feed_fn = build()
        feed = feed_fn(rng)
        params, state = topo.init(jax.random.PRNGKey(0))

        def fwd(p, s, f):
            outs, _ = topo.apply(p, s, f, train=False)
            return {k: a.value for k, a in outs.items()}

        fs = audit_fn(fwd, params, state, feed, label=name)
        errs = severity_at_least(fs, "ERROR")
        assert not errs, (name, [f.format() for f in errs])


# ---------------------------------------------------------------------------
# decode-closure auditing (fused decode engine, ops/decode.py; docs/decode.md)
# ---------------------------------------------------------------------------


def test_decode_audit_flagship_closure_is_host_transfer_free(rng):
    """The acceptance bar for the decode engine: the lowered decode fn —
    early-exit while loop, packed gather, and (forced, interpret-mode)
    vocab-tiled top-k kernel included — carries no host transfer, no >1 MiB
    folded constant, and no unaligned kernel BlockSpec."""
    from paddle_tpu.analysis import audit_decode
    from paddle_tpu.models import Seq2SeqAttention

    m = Seq2SeqAttention(src_vocab=300, trg_vocab=300, emb_dim=32,
                         enc_dim=32, dec_dim=128, att_dim=32)
    params = m.init(jax.random.PRNGKey(0))
    src = jnp.asarray(rng.randint(3, 300, (8, 6)).astype(np.int32))
    src_len = jnp.full((8,), 6, jnp.int32)
    for use_kernel in (True, False):
        fs = audit_decode(
            lambda p, s, l, uk=use_kernel: m.beam_search(
                p, s, l, beam_size=4, max_len=5, use_kernel=uk),
            params, src, src_len, label=f"decode_uk{use_kernel}")
        errs = severity_at_least(fs, "ERROR")
        assert not errs, [f.format() for f in errs]
        assert not [f for f in fs if f.check == "unaligned-pallas-tile"], \
            [f.format() for f in fs]


def test_decode_audit_fires_on_planted_host_transfer(rng):
    """audit_decode must still SEE a host round-trip smuggled into the
    decode step (through the engine's while loop)."""
    from paddle_tpu.analysis import audit_decode
    from paddle_tpu.ops.decode import LogitsReadout, beam_decode

    V, H = 12, 8
    w = jnp.asarray(rng.randn(H, V).astype(np.float32))

    def leaky_step(tokens, state):
        h = jax.device_put(state["h"])  # the planted per-token transfer
        return h @ w, {"h": h + 1.0}

    fs = audit_decode(
        lambda m0: beam_decode(leaky_step, LogitsReadout(), m0,
                               batch_size=2, beam_size=3, vocab_size=V,
                               max_len=4),
        {"h": jnp.zeros((2, H))}, label="leaky")
    assert "host-transfer" in _checks(fs)


def test_cli_decode_audit_is_clean(capsys):
    """`python -m paddle_tpu lint --decode` — the CI surface of the decode
    audit (kernel + XLA-fallback variants)."""
    from paddle_tpu.__main__ import main

    rc = main(["lint", "--decode", "8,6,4,5", "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    errors = [f for f in out["findings"] if f["severity"] == "ERROR"]
    assert rc == 0 and not errors, errors


def test_cli_pserver_audit_is_clean(capsys):
    """`python -m paddle_tpu lint --pserver` — the CI gate of the sharded
    embedding tier: serving checks over the compiled lookup/apply closures
    PLUS the never-densify assertion (no [V, D] grad or optimizer temp in
    the sparse-apply jaxpr)."""
    from paddle_tpu.__main__ import main

    rc = main(["lint", "--pserver", "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    errors = [f for f in out["findings"] if f["severity"] == "ERROR"]
    assert rc == 0 and not errors, errors
    # the spec knob works and a collision is rejected loudly, not skewed
    rc = main(["lint", "--pserver", "2048,16,2048,4", "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1 and any(f["check"] == "pserver-build"
                           for f in out["findings"])


# ---------------------------------------------------------------------------
# deploy: _unrolled_scans lock (satellite config/deploy.py:283)
# ---------------------------------------------------------------------------


def test_unrolled_scans_lock_serializes_and_restores():
    from paddle_tpu.config.deploy import _unrolled_scans

    orig = jax.lax.scan
    patched_seen = []

    def worker():
        with _unrolled_scans():
            patched_seen.append(jax.lax.scan is not orig)
            time.sleep(0.01)
            # still OUR patch active at exit time: without the lock a
            # second thread would have captured the patch as its _orig
            # and re-installed it after we restore

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(patched_seen)
    assert jax.lax.scan is orig  # fully restored after concurrent exports


def test_lint_obs_gates_telemetry_contract(capsys):
    """`python -m paddle_tpu lint --obs` (docs/observability.md): the
    train step traced with telemetry enabled must be host-transfer-free
    AND equation-identical to the telemetry-off trace — exit 0 today,
    and any instrumentation leaking into the compiled program fails CI."""
    from paddle_tpu.analysis.cli import run

    assert run(["--obs"]) == 0
    assert "0 error(s)" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# static passes (docs/lint.md): --race / --protocol / --hbm
# ---------------------------------------------------------------------------

FIX = os.path.join(HERE, "fixtures")


def _by_check(findings):
    by = {}
    for f in findings:
        by.setdefault(f.check, []).append(f)
    return by


def test_race_planted_fixture_fires_every_check():
    from paddle_tpu.analysis.static import run_race

    fs = run_race((os.path.join(FIX, "race_planted.py"),))
    by = _by_check(fs)
    lo = by["race-lock-order"]
    assert lo[0].severity == "ERROR" and lo[0].line == 21
    assert lo[0].file.endswith("race_planted.py")
    ann = by["race-annotation"]  # guarded-by with no stated invariant
    assert ann[0].severity == "ERROR" and ann[0].line == 36
    wr = by["race-unguarded-write"]
    assert wr[0].severity == "ERROR" and wr[0].line == 43
    rd = by["race-unguarded-read"]
    assert rd[0].severity == "WARN" and rd[0].line == 46


def test_race_clean_fixture_quiet():
    from paddle_tpu.analysis.static import run_race

    assert run_race((os.path.join(FIX, "race_clean.py"),)) == []


def test_protocol_grow_deadlock_fixture_caught():
    """The PR 8 regression shape: the coordinator barriers before the
    broadcast the joiner is blocked on — a rank-conditional order skew."""
    from paddle_tpu.analysis.static import run_protocol

    fs = run_protocol((os.path.join(FIX, "protocol_grow_deadlock.py"),))
    hits = [f for f in fs if f.check == "protocol-order"]
    assert hits and hits[0].severity == "ERROR" and hits[0].line == 12


def test_protocol_abandoned_commit_fixture_caught():
    """The PR 6 regression shape: an exception path that exits the
    function past a collective its peers will still enter."""
    from paddle_tpu.analysis.static import run_protocol

    fs = run_protocol((os.path.join(FIX, "protocol_abandoned_commit.py"),))
    by = _by_check(fs)
    exc = sorted(by["protocol-exception"], key=lambda f: f.line)
    assert [(f.severity, f.line) for f in exc] == [("ERROR", 19),
                                                  ("WARN", 29)]
    un = by["protocol-unmatched"]
    assert un[0].severity == "ERROR" and un[0].line == 35


def test_protocol_clean_fixture_quiet():
    from paddle_tpu.analysis.static import run_protocol

    assert run_protocol((os.path.join(FIX, "protocol_clean.py"),)) == []


def test_ci_race_pass_clean_on_own_tree():
    """Pinned gate: every shared-mutable write in the concurrent classes
    is lock-held or carries a `guarded-by` annotation naming its
    invariant (docs/lint.md) — a new bare write fails the suite."""
    from paddle_tpu.analysis.static import run_race

    fs = run_race(())
    assert fs == [], [(f.file, f.line, f.check) for f in fs]


def test_ci_protocol_pass_clean_on_own_tree():
    """Pinned gate: trainer + resilience collectives stay order-aligned
    across rank-conditional branches and exception paths."""
    from paddle_tpu.analysis.static import run_protocol

    fs = run_protocol(())
    assert fs == [], [(f.file, f.line, f.check) for f in fs]


def test_ci_hbm_audit_error_free():
    """Pinned gate: the real compiled train/decode steps audit free of
    donation-reuse, f64 constants, and over-capacity peaks; the stats
    findings themselves must be present (both steps actually traced)."""
    from paddle_tpu.analysis.static import run_hbm

    fs = run_hbm()
    assert not severity_at_least(fs, "ERROR"), \
        [(f.check, f.message) for f in fs if f.severity == "ERROR"]
    labels = {f.where for f in fs if f.check == "hbm-peak"}
    assert any("train_step" in w for w in labels)
    assert any("decode_step" in w for w in labels)


# ---------------------------------------------------------------------------
# SARIF output + the uniform exit-code contract
# ---------------------------------------------------------------------------


def test_cli_sarif_shape(capsys):
    """--format sarif emits the SARIF 2.1.0 shape tooling expects:
    versioned log, tool.driver.rules covering every result's ruleId,
    physical locations for AST findings."""
    from paddle_tpu.analysis.cli import run

    rc = run(["--race", os.path.join(FIX, "race_planted.py"),
              "--format", "sarif"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert doc["version"] == "2.1.0"
    assert doc["$schema"].endswith("sarif-2.1.0.json")
    drv = doc["runs"][0]["tool"]["driver"]
    assert drv["name"] == "paddle-tpu-lint"
    rule_ids = {r["id"] for r in drv["rules"]}
    results = doc["runs"][0]["results"]
    assert results
    for res in results:
        assert res["ruleId"] in rule_ids
        assert res["level"] in ("error", "warning", "note")
        assert res["message"]["text"]
        loc = res["locations"][0]
        assert "physicalLocation" in loc or "logicalLocations" in loc
    phys = next(r["locations"][0]["physicalLocation"] for r in results
                if "physicalLocation" in r["locations"][0])
    assert phys["artifactLocation"]["uri"].endswith("race_planted.py")
    assert phys["region"]["startLine"] > 0


def test_cli_exit_code_contract(capsys, tmp_path):
    """The documented 0/1/2 contract (docs/lint.md): 0 clean, 1 findings
    at/above --fail-on, 2 usage error — and a usage error is reported
    before any pass burns time."""
    from paddle_tpu.analysis.cli import run

    assert run(["--no-such-flag"]) == 2  # argparse error -> rc 2
    capsys.readouterr()
    assert run(["--race", os.path.join(FIX, "race_clean.py"),
                "--allowlist", str(tmp_path / "missing")]) == 2
    capsys.readouterr()
    assert run(["--race", os.path.join(FIX, "race_clean.py")]) == 0
    capsys.readouterr()
    assert run(["--race", os.path.join(FIX, "race_planted.py")]) == 1
    capsys.readouterr()
    with pytest.raises(SystemExit) as ei:  # argparse's own --help exit
        run(["--help"])
    assert ei.value.code == 0
    capsys.readouterr()


@pytest.mark.slow
def test_cli_all_runs_every_pass_clean(capsys):
    """`lint --all` is the one-shot CI surface: every pass over the
    package tree, ERROR-free."""
    from paddle_tpu.analysis.cli import run

    rc = run(["--all", "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    checks = {f["check"] for f in out["findings"]}
    assert any(c.startswith("hbm-") for c in checks)  # hbm stats present
    assert rc == 0, [f for f in out["findings"]
                     if f["severity"] == "ERROR"]
