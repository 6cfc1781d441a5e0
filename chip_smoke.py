"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once, through the entry points a user calls,
at the flagship's full width (``paddle_tpu.models.Seq2SeqAttention()``: 30k/30k
vocabulary, 512-d embedding / encoder / decoder / attention), weights random
from ``--seed``:

    python chip_smoke.py             one chip: train -> generate -> serve
    python chip_smoke.py --chips 4   four chips: the data x model-parallel
                                     train step and the sharded embedding
                                     lookup against their one-chip twins,
                                     and no other phase

Every phase prints one JSON line of its own; the LAST line of standard output
is ``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``
with the device as JAX reports it.  Any failed check makes ``ok`` false and
the exit code non-zero.  Without a TPU the script exits at once, non-zero,
and prints no result.  It sets no ``JAX_PLATFORMS``, forces no virtual
devices, starts no process and needs no network; every input is generated
from the seed.  What it writes (bundle, checkpoints, the ``.aotx`` executable
cache) goes under ``<checkout>/.jax_cache/chip_smoke`` and is removed at the
start of each run; JAX's persistent compilation cache stays where
``JAX_COMPILATION_CACHE_DIR`` puts it, else in ``<checkout>/.jax_cache``, so
a second run is served from it and says so.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

#: four-chip losses against one chip: the sharded step runs the XLA scan
#: paths (Mosaic kernels cannot be partitioned by jit), the one-chip step the
#: kernels with bf16 residuals — same function, different rounding.  Seen on
#: four v5e chips: 4.4e-6 relative over three steps (PR 21); one Adam step
#: moves the loss by 4e-4 relative, so a trajectory that is half a step off
#: fails
FOUR_CHIP_RTOL = 2e-4


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything the phases size themselves by.  The defaults are the
    published widths (the reference's seqToseq demo and LSTM benchmark);
    tests/test_chip_smoke.py passes a tiny instance."""
    vocab: int = 30000
    dim: int = 512            # embedding = encoder = decoder = attention
    train_batch: int = 384
    seq_len: int = 32         # S = T, also the slot table's src_len
    train_steps: int = 5
    gen_batch: int = 64
    beam: int = 3
    max_len: int = 32
    requests: int = 4         # per server
    slots: int = 8
    lookup_ids: int = 4096    # --chips 4: ids per sharded lookup
    #: python -m paddle_tpu --job=train config (lstm_benchmark_net: vocab
    #: 30k, two layers, T=100, B=64, hidden 512) and its batches per pass
    trainer_config: str = os.path.join(ROOT, "demo", "chip_smoke",
                                       "train_conf.py")
    trainer_batches: int = 6
    #: at these widths every gate is expected to choose its kernel
    expect_kernels: bool = True


FULL = Sizes()


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(phase: str, t0: float, **fields) -> None:
    print(json.dumps({"phase": phase, "ok": True,
                      "seconds": round(time.perf_counter() - t0, 2),
                      **fields}), flush=True)


def require_tpu(chips: int) -> dict:
    """The device, or exit: no fallback hides a missing chip."""
    from paddle_tpu.utils.devices import device_report

    device = device_report()
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke: no TPU found (jax.devices()[0].platform == "
                 f"{device['platform']!r}); nothing was run")
    if device["count"] < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, "
                 f"jax reports {device['count']}")
    from paddle_tpu.ops.pallas_kernels import _interpret

    if _interpret():
        sys.exit("chip_smoke: Pallas interpret mode is on with a TPU attached")
    return device


def custom_calls(lowered) -> int:
    """Mosaic kernels in a lowered step, counted in its text."""
    return lowered.as_text().count("tpu_custom_call")


def _count_events(name: str) -> dict:
    """A running count (``["n"]``) of one of JAX's monitoring events."""
    import jax

    seen = {"n": 0}

    def on_event(event, **_):
        if event == name:
            seen["n"] += 1

    jax.monitoring.register_event_listener(on_event)
    return seen


def _all_finite(tree) -> bool:
    import jax

    return all(bool(np.isfinite(np.asarray(x)).all())
               for x in jax.tree_util.tree_leaves(tree))


def _seq2seq(sizes: Sizes):
    from paddle_tpu.models import Seq2SeqAttention

    return Seq2SeqAttention(
        src_vocab=sizes.vocab, trg_vocab=sizes.vocab, emb_dim=sizes.dim,
        enc_dim=sizes.dim, dec_dim=sizes.dim, att_dim=sizes.dim)


def _train_batch(sizes: Sizes, seed: int) -> dict:
    """A seq2seq batch: full-length random rows, <s> ... <e>."""
    rng = np.random.RandomState(seed)
    B, S, T, V = sizes.train_batch, sizes.seq_len, sizes.seq_len, sizes.vocab
    core = rng.randint(3, V, (B, T - 1)).astype(np.int32)
    return {
        "src_ids": rng.randint(3, V, (B, S)).astype(np.int32),
        "src_len": np.full((B,), S, np.int32),
        "trg_in": np.concatenate([np.zeros((B, 1), np.int32), core], 1),
        "trg_next": np.concatenate([core, np.ones((B, 1), np.int32)], 1),
        "trg_len": np.full((B,), T, np.int32),
    }


def _demo_train_step(m, opt):
    """The step users copy: demo/seqToseq/train.py's ``make_train_step``."""
    spec = importlib.util.spec_from_file_location(
        "_seqToseq_demo", os.path.join(ROOT, "demo", "seqToseq", "train.py"))
    demo = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(demo)
    return demo.make_train_step(m, opt)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def phase_train(sizes: Sizes, seed: int) -> dict:
    """A few Adam steps of the flagship on one repeated batch: loss finite
    on every step and lower after the last than after the first."""
    import jax

    from paddle_tpu.param.optimizers import Adam

    t0 = time.perf_counter()
    m = _seq2seq(sizes)
    params = m.init(jax.random.PRNGKey(seed))
    opt = Adam(learning_rate=1e-3)
    opt_state = opt.init_state(params)
    batch = jax.device_put(_train_batch(sizes, seed))
    lowered = _demo_train_step(m, opt).lower(params, opt_state, batch)
    kernels = custom_calls(lowered)
    step = lowered.compile()
    losses = []
    for _ in range(sizes.train_steps):
        loss, params, opt_state = step(params, opt_state, batch)
        losses.append(float(loss))
    check(all(np.isfinite(losses)), f"train: non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"train: loss did not fall on a repeated batch: {losses}")
    check(_all_finite(params), "train: non-finite parameter after training")
    if sizes.expect_kernels:
        check(kernels > 0, "train: the lowered step holds no tpu_custom_call "
                           "— every gate chose its XLA path")
    emit("train", t0, steps=sizes.train_steps, losses=losses,
         tpu_custom_calls=kernels,
         shape=f"B{sizes.train_batch},S{sizes.seq_len},T{sizes.seq_len},"
               f"{sizes.dim}d,vocab{sizes.vocab}")
    return {"model": m, "params": params, "batch": batch}


def phase_trainer_cli(sizes: Sizes, workdir: str) -> dict:
    """``python -m paddle_tpu --job=train`` in-process: SGDTrainer's donated
    step, prefetch and bad-step guard over a few batches, one checkpoint —
    then the checkpoint loaded back into a trainer built from the same
    config (what the serve phase bundles)."""
    import runpy

    import jax

    from paddle_tpu.__main__ import main as cli_main
    from paddle_tpu.obs import get_registry
    from paddle_tpu.trainer import SGDTrainer
    from paddle_tpu.trainer.checkpoint import latest_pass
    from paddle_tpu.utils.flags import FLAGS

    t0 = time.perf_counter()
    save_dir = os.path.join(workdir, "trainer")
    reg = get_registry()
    batches = reg.counter("train_batches_total")
    bad = reg.counter("train_bad_steps_total")
    before = (batches.value, bad.value)
    argv = ["--job=train", f"--config={sizes.trainer_config}",
            "--num_passes=1", f"--save_dir={save_dir}", "--log_period=2",
            "--prefetch_depth=2", "--guard_nonfinite=true"]
    keep = FLAGS.as_dict()  # flags are process-global: put them back
    try:
        rc = cli_main(argv)
    finally:
        for name, value in keep.items():
            setattr(FLAGS, name, value)
    check(rc == 0, f"trainer: --job=train returned {rc}")
    took = batches.value - before[0]
    skipped = bad.value - before[1]
    cost = reg.gauge("train_last_cost").value
    check(took == sizes.trainer_batches,
          f"trainer: {took} optimizer steps, expected {sizes.trainer_batches}")
    check(skipped == 0, f"trainer: bad-step guard skipped {skipped} steps")
    check(cost is not None and np.isfinite(cost),
          f"trainer: last cost {cost!r} is not finite")
    check(latest_pass(save_dir) == 0, "trainer: pass 0 was not checkpointed")
    conf = runpy.run_path(sizes.trainer_config)["get_config"]()
    tr = SGDTrainer(conf["cost"], conf["optimizer"])
    tr.load(save_dir, 0)
    check(_all_finite(tr.params), "trainer: non-finite parameter in pass 0")
    kernels = custom_calls(jax.jit(tr._step_fn).lower(
        tr.params, tr.state, tr.opt_state, {}, jax.random.PRNGKey(0),
        next(iter(conf["reader"]()))))
    if sizes.expect_kernels:
        check(kernels > 0, "trainer: SGDTrainer's step holds no "
                           "tpu_custom_call — the LSTM gates chose the scan")
    emit("trainer_cli", t0, argv=argv[:1] + argv[2:], batches=int(took),
         bad_steps=int(skipped), last_cost=float(cost),
         tpu_custom_calls=kernels)
    return {"trainer": tr, "reader": conf["reader"]}


def phase_generate(sizes: Sizes, trained: dict) -> dict:
    """Beam and greedy decode on the just-trained parameters."""
    import jax

    t0 = time.perf_counter()
    m, params = trained["model"], trained["params"]
    B, K, L = sizes.gen_batch, sizes.beam, sizes.max_len
    src = trained["batch"]["src_ids"][:B]
    src_len = trained["batch"]["src_len"][:B]
    check(src.shape[0] == B, "generate: train batch smaller than gen batch")

    beam = jax.jit(lambda p, s, n: m.beam_search(p, s, n, beam_size=K,
                                                 max_len=L))
    lowered = beam.lower(params, src, src_len)
    kernels = custom_calls(lowered)
    toks, scores = lowered.compile()(params, src, src_len)
    toks, scores = np.asarray(toks), np.asarray(scores)
    check(toks.shape == (B, K, L) and scores.shape == (B, K),
          f"generate: beam shapes {toks.shape} {scores.shape}")
    check(toks.min() >= 0 and toks.max() < sizes.vocab,
          "generate: beam token id out of range")
    check(np.isfinite(scores).all(), "generate: non-finite beam score")
    check((np.diff(scores, axis=1) <= 1e-5).all(),
          "generate: beams are not sorted best-first")

    g_toks, g_scores = m.greedy_decode(params, src, src_len, max_len=L)
    b1_toks, b1_scores = m.beam_search(params, src, src_len, beam_size=1,
                                       max_len=L)
    g_toks, b1_toks = np.asarray(g_toks), np.asarray(b1_toks)[:, 0]
    check(g_toks.min() >= 0 and g_toks.max() < sizes.vocab,
          "generate: greedy token id out of range")
    check(np.isfinite(np.asarray(g_scores)).all(),
          "generate: non-finite greedy score")
    check(np.array_equal(g_toks, b1_toks),
          f"generate: greedy != beam-1 on "
          f"{int((g_toks != b1_toks).any(axis=1).sum())}/{B} rows")
    check(np.allclose(np.asarray(g_scores), np.asarray(b1_scores)[:, 0],
                      rtol=1e-4, atol=1e-3),
          "generate: greedy and beam-1 scores differ")
    if sizes.expect_kernels:
        check(kernels > 0, "generate: the lowered decode holds no "
                           "tpu_custom_call — every gate chose its XLA path")
    emit("generate", t0, tpu_custom_calls=kernels, greedy_equals_beam1=True,
         best_score_mean=float(scores[:, 0].mean()),
         shape=f"B{B},beam{K},L{L}")
    return {"beam_tokens": toks, "beam_scores": scores}


def _served(server, feeds, **submit_kw):
    futures = [server.submit(f, deadline_ms=600000, **submit_kw)
               for f in feeds]
    return [f.result(600) for f in futures]


def phase_serve(sizes: Sizes, trained: dict, trainer: dict, workdir: str,
                seed: int) -> None:
    """The serve phase, with JAX's persistent cache keeping every program
    however quick its compile: from the second run of this script on,
    whatever the servers compile THROUGH that cache is served from it.  The
    executables they store as ``.aotx`` must not be
    (``config/compile_cache.compile_fresh``) — the second boot loads what
    the first one stored, so it is the check."""
    import jax

    keep = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        _serve(sizes, trained, trainer, workdir, seed)
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", keep)


def _serve(sizes: Sizes, trained: dict, trainer: dict, workdir: str,
           seed: int) -> None:
    """merge_model -> load_inference_model -> InferenceServer, a generation
    server over the flagship's slot backend, then both booted again over the
    same ``.aotx`` cache: loads, and no compile."""
    import jax

    from paddle_tpu.config import load_inference_model, merge_model
    from paddle_tpu.config.compile_cache import CompileCacheDir
    from paddle_tpu.serving.server import InferenceServer
    from paddle_tpu.serving.slots import Seq2SeqSlotBackend

    t0 = time.perf_counter()
    aotx = os.path.join(workdir, "aotx")
    rng = np.random.RandomState(seed + 1)
    jax_cache_hits = _count_events("/jax/compilation_cache/cache_hits")

    # -- bucket mode over the deploy bundle of the CLI-trained classifier --
    tr = trainer["trainer"]
    bundle = os.path.join(workdir, "textclf.ptz")
    merge_model(bundle, tr.topology, tr.params, tr.state, name="textclf")
    words, lens = next(iter(trainer["reader"]()))["words"]
    feeds = [{"words": (words[i:i + 1], lens[i:i + 1])}
             for i in range(sizes.requests)]

    def boot_bucket():
        model = load_inference_model(bundle)
        srv = InferenceServer(model, max_batch=8, outputs=["logits"],
                              default_deadline_ms=600000)
        srv.start(warmup_feed=feeds[0], compile_cache=CompileCacheDir(aotx))
        return model, srv

    model, srv = boot_bucket()
    try:
        direct = [model.infer(f, outputs=["logits"])["logits"] for f in feeds]
        served = [r["logits"] for r in _served(srv, feeds)]
    finally:
        srv.close()
    for d, s in zip(direct, served):
        check(np.isfinite(s).all(), "serve: non-finite logits from server")
        check(np.allclose(d, s, rtol=1e-3, atol=1e-3),
              "serve: InferenceServer answer differs from direct infer")
    bucket_cold = model.compile_events

    # -- generation mode: the flagship behind the slot table ---------------
    m, params = trained["model"], trained["params"]
    backend = Seq2SeqSlotBackend(m, params, src_len=sizes.seq_len,
                                 beam_size=sizes.beam, max_len=sizes.max_len)
    src = np.asarray(trained["batch"]["src_ids"][:sizes.requests])
    src_len = rng.randint(sizes.seq_len // 2, sizes.seq_len + 1,
                          sizes.requests).astype(np.int32)
    ref_toks, ref_scores = m.beam_search(
        params, src, src_len, beam_size=sizes.beam, max_len=sizes.max_len)
    ref_toks, ref_scores = np.asarray(ref_toks), np.asarray(ref_scores)
    gen_feeds = [{"src": (src[i:i + 1], src_len[i:i + 1])}
                 for i in range(sizes.requests)]

    def boot_generation():
        srv = InferenceServer(backend, mode="generation", slots=sizes.slots,
                              default_deadline_ms=600000)
        srv.start(compile_cache=CompileCacheDir(aotx))
        return srv, srv.healthz()["cold_start"]

    gsrv, cold = boot_generation()
    try:
        sched = gsrv._scheduler
        step_kernels = custom_calls(sched._jit_src["step"].lower(
            backend.params, jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                sched.carry)))
        answers = _served(gsrv, gen_feeds)
    finally:
        gsrv.close()
    for i, a in enumerate(answers):
        check(np.array_equal(a["tokens"][0], ref_toks[i]),
              f"serve: generation request {i} tokens differ from "
              f"beam_search for the same source")
        check(np.allclose(a["scores"][0], ref_scores[i], rtol=1e-4,
                          atol=1e-3),
              f"serve: generation request {i} scores differ from "
              f"beam_search: {a['scores'][0]} vs {ref_scores[i]}")
    check(cold["compile_cache_misses"] > 0,
          "serve: the first generation boot compiled nothing")
    first_boot_jax_hits = jax_cache_hits["n"]

    # -- second boot of both servers over the same cache directory ---------
    model2, srv2 = boot_bucket()
    try:
        hits2 = srv2.healthz()["cold_start"]["compile_cache_hits"]
        again = [r["logits"] for r in _served(srv2, feeds[:1])]
    finally:
        srv2.close()
    check(np.allclose(again[0], direct[0], rtol=1e-3, atol=1e-3),
          "serve: warm-booted server answer differs")
    check(model2.compile_events == 0 and hits2 > 0,
          f"serve: second bucket boot compiled ({model2.compile_events} "
          f"compile events, {hits2} cache loads)")
    gsrv2, warm = boot_generation()
    try:
        again = _served(gsrv2, gen_feeds[:1])
    finally:
        gsrv2.close()
    check(np.array_equal(again[0]["tokens"], answers[0]["tokens"]),
          "serve: warm-booted generation answer differs")
    check(warm["compile_cache_misses"] == 0 and warm["warmup_compiles"] == 0
          and warm["compile_cache_hits"] > 0,
          f"serve: second generation boot was not warm: {warm}")
    emit("serve", t0, bucket_requests=len(feeds),
         generation_requests=len(gen_feeds),
         slot_step_tpu_custom_calls=step_kernels,
         first_boot={"bucket_compile_events": bucket_cold,
                     "generation_cache_misses": cold["compile_cache_misses"],
                     "jax_cache_hits_meanwhile": first_boot_jax_hits},
         second_boot={"bucket_compile_events": model2.compile_events,
                      "bucket_cache_loads": hits2,
                      "generation_cache_loads": warm["compile_cache_hits"],
                      "generation_cache_misses": warm["compile_cache_misses"],
                      "generation_warmup_compiles": warm["warmup_compiles"]})


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def graft_rules():
    """The tensor-parallel rules of ``__graft_entry__.dryrun_multichip``."""
    import paddle_tpu.parallel as par

    return par.ShardingRules([
        ("*_emb", par.P(None, "model")),
        ("out_w", par.P(None, "model")),
        ("out_b", par.P("model")),
        ("*_wx", par.P(None, "model")),
        ("*", par.P()),
    ])


def _check_placement(name, arr, mesh, spec) -> None:
    """``arr`` lies on every device of the mesh, cut as ``spec`` says."""
    from jax.sharding import NamedSharding

    want = NamedSharding(mesh, spec)
    shards = arr.addressable_shards
    check({s.device for s in shards} == set(mesh.devices.flat),
          f"four_chips: {name} does not span all {mesh.size} devices")
    check(arr.sharding.is_equivalent_to(want, arr.ndim),
          f"four_chips: {name} is placed {arr.sharding}, rules say {spec}")
    check(all(s.data.shape == want.shard_shape(arr.shape) for s in shards),
          f"four_chips: {name} shard shapes disagree with {spec}")


def phase_sharded_train(sizes: Sizes, seed: int, devices) -> None:
    """The flagship's train step on a ("data","model") = (2,2) mesh against
    the same parameters and batch on one chip of the same host."""
    import jax
    from jax.sharding import Mesh

    import paddle_tpu.parallel as par
    from paddle_tpu.param.optimizers import Adam

    t0 = time.perf_counter()
    m = _seq2seq(sizes)
    host_params = jax.tree_util.tree_map(
        np.asarray, m.init(jax.random.PRNGKey(seed)))
    host_batch = _train_batch(sizes, seed)
    opt = Adam(learning_rate=1e-3)
    steps = 3

    # one chip of this host: the demo step, everything on devices[0]
    with jax.default_device(devices[0]):
        params = jax.device_put(host_params, devices[0])
        opt_state = opt.init_state(params)
        batch = jax.device_put(host_batch, devices[0])
        one = _demo_train_step(m, opt)
        ref = []
        for _ in range(steps):
            loss, params, opt_state = one(params, opt_state, batch)
            ref.append(float(loss))
        del params, opt_state, batch

    mesh = Mesh(np.asarray(devices[:4]).reshape(2, 2), ("data", "model"))
    rules = graft_rules()
    p = par.shard_params(mesh, host_params, rules)
    s = opt.init_state(p)
    b = par.shard_batch(mesh, host_batch)
    for name, arr in p.items():
        _check_placement(name, arr, mesh, rules.spec_for(name, arr.ndim))
    for name, arr in b.items():
        _check_placement(name, arr, mesh,
                         par.P("data", *([None] * (arr.ndim - 1))))
    step = par.make_parallel_train_step(m.loss, opt, mesh, rules=rules)
    lowered = step.lower(p, s, b)
    kernels = custom_calls(lowered)
    compiled = lowered.compile()
    text = compiled.as_text()
    all_reduces = text.count("all-reduce(") + text.count("all-reduce-start(")
    check(all_reduces > 0, "four_chips: the compiled sharded step holds no "
                           "all-reduce")
    got = []
    for _ in range(steps):
        loss, p, s = compiled(p, s, b)
        got.append(float(loss))
    check(all(np.isfinite(got)), f"four_chips: non-finite loss {got}")
    check(np.allclose(got, ref, rtol=FOUR_CHIP_RTOL),
          f"four_chips: sharded losses {got} vs one chip {ref} "
          f"(rtol {FOUR_CHIP_RTOL})")
    for name, arr in p.items():  # the step keeps the placement it was given
        _check_placement(name, arr, mesh, rules.spec_for(name, arr.ndim))
    emit("sharded_train", t0, mesh={"data": 2, "model": 2},
         losses=got, one_chip_losses=ref, rtol=FOUR_CHIP_RTOL,
         all_reduces=all_reduces, tpu_custom_calls=kernels,
         note="Mosaic kernels cannot be partitioned by jit: the sharded "
              "step runs the XLA paths")


def phase_sharded_lookup(sizes: Sizes, seed: int, devices) -> None:
    """``par.sharded_embedding_lookup`` on a ("model",) mesh of four against
    a plain gather."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    import paddle_tpu.parallel as par

    t0 = time.perf_counter()
    rng = np.random.RandomState(seed + 2)
    table = (0.01 * rng.randn(sizes.vocab, sizes.dim)).astype(np.float32)
    ids = rng.randint(0, sizes.vocab, (sizes.lookup_ids,)).astype(np.int32)
    mesh = Mesh(np.asarray(devices[:4]), ("model",))
    sharded = par.shard_table(mesh, table, "model")
    _check_placement("table", sharded, mesh, par.P("model", None))
    got = np.asarray(par.sharded_embedding_lookup(
        mesh, sharded, jnp.asarray(ids), axis="model"))
    want = table[ids]
    check(got.shape == want.shape, f"sharded_lookup: shape {got.shape}")
    check(np.array_equal(got, want),
          "sharded_lookup: differs from a plain gather")
    emit("sharded_lookup", t0, mesh={"model": 4}, ids=int(ids.size),
         table=list(table.shape), equals_plain_gather=True)


# ---------------------------------------------------------------------------


def run_one_chip(sizes: Sizes, seed: int, workdir: str) -> None:
    trained = phase_train(sizes, seed)
    trainer = phase_trainer_cli(sizes, workdir)
    phase_generate(sizes, trained)
    phase_serve(sizes, trained, trainer, workdir, seed)


def run_four_chips(sizes: Sizes, seed: int, workdir: str) -> None:
    import jax

    devices = jax.devices()
    phase_sharded_train(sizes, seed, devices)
    phase_sharded_lookup(sizes, seed, devices)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ns = ap.parse_args(argv)

    t0 = time.perf_counter()
    import jax

    from paddle_tpu.utils.devices import init

    init([])  # the framework's init: places JAX's persistent compile cache
    device = require_tpu(ns.chips)
    requests = _count_events(
        "/jax/compilation_cache/compile_requests_use_cache")
    hits = _count_events("/jax/compilation_cache/cache_hits")
    workdir = os.path.join(ROOT, ".jax_cache", "chip_smoke")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)

    ok = True
    try:
        (run_four_chips if ns.chips == 4 else run_one_chip)(
            FULL, ns.seed, workdir)
    except Exception as e:  # noqa: BLE001 — any failed phase fails the smoke
        import traceback

        traceback.print_exc()
        ok = False
        print(json.dumps({"phase": "failed", "ok": False,
                          "error": f"{type(e).__name__}: {e}"[:2000]}),
              flush=True)
    print(json.dumps({
        "phase": "compile_cache",
        "dir": jax.config.jax_compilation_cache_dir,
        "compile_requests": requests["n"], "cache_hits": hits["n"],
        "served_from_persistent_cache": hits["n"] > 0,
        "total_seconds": round(time.perf_counter() - t0, 2)}), flush=True)
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
