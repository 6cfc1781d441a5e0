"""Persistent compiled-executable cache (docs/deploy.md, ROADMAP item 5).

Fleet cold-start: a warm-cache server boot must reach ready with ZERO
XLA compiles (pinned by counter) and >=3x faster than the cold boot in
the same process; stale/corrupt/truncated entries degrade to a logged
fresh compile, never a crash or a wrong executable; the continuous
slot closures (prefill/step/write/release/finalize) cache too.
"""

import time

import numpy as np
import pytest

import paddle_tpu.nn as nn
from paddle_tpu.config import load_inference_model, merge_model, warm_bundle
from paddle_tpu.config.compile_cache import (BundleAotCache, CompileCacheDir,
                                             cache_key, open_cache)
from paddle_tpu.param.optimizers import Adam
from paddle_tpu.resilience import chaos
from paddle_tpu.serving.server import InferenceServer
from paddle_tpu.serving.slots import example_slot_backend
from paddle_tpu.trainer import SGDTrainer

def _bundle(tmp_path, rng, quantize=None, name="cc"):
    nn.reset_naming()
    x = nn.data("x", size=64)
    h = nn.fc(x, 128, act="tanh", name="h")
    out = nn.fc(h, 16, act="softmax", name="out")
    label = nn.data("label", size=1, dtype="int32")
    cost = nn.classification_cost(out, label, name="cost")
    tr = SGDTrainer(cost, Adam(learning_rate=0.01), seed=0)
    tr.train_batch({"x": rng.randn(8, 64).astype(np.float32),
                    "label": rng.randint(0, 16, (8, 1)).astype(np.int32)})
    path = str(tmp_path / f"{name}.ptz")
    merge_model(path, tr.topology, tr.params, tr.state, name=name,
                quantize=quantize)
    return path


def _boot(bundle, cache, *, int8_in_trace=False):
    model = load_inference_model(bundle, int8_in_trace=int8_in_trace)
    srv = InferenceServer(model, max_batch=8, outputs=["out"],
                          default_deadline_ms=60000)
    t0 = time.perf_counter()
    srv.start(warmup_feed={"x": np.zeros((1, 64), np.float32)},
              compile_cache=cache)
    dt = time.perf_counter() - t0
    return srv, model, dt


# ---------------------------------------------------------------------------
# the storage layer
# ---------------------------------------------------------------------------


def test_cache_dir_roundtrip_and_counters(tmp_path):
    import jax
    import jax.numpy as jnp

    cache = CompileCacheDir(str(tmp_path / "cache"))
    compiled = jax.jit(lambda x: x * 3).lower(jnp.ones((4,))).compile()
    key = cache_key("unit", "fp", "sig")
    assert cache.load(key) is None and cache.misses == 1
    assert cache.store(key, compiled, label="unit")
    fn = cache.load(key)
    assert fn is not None and cache.hits == 1
    np.testing.assert_array_equal(np.asarray(fn(jnp.ones((4,)))),
                                  np.full((4,), 3.0, np.float32))
    # a different key never returns this entry
    assert cache.load(cache_key("unit", "fp", "other")) is None


def test_one_device_entry_loads_on_a_many_device_backend(tmp_path):
    """The shape of the jax 0.9.0 bug: an executable compiled for ONE of the
    backend's eight devices must load for that device alone — loaded for
    every device it demands eight argument shards at call time and the
    sound entry is rejected.  Also holds for a device other than 0, and an
    entry naming a device this backend lacks is a stale miss."""
    import json

    import jax
    import jax.numpy as jnp

    assert len(jax.devices()) == 8
    cache = CompileCacheDir(str(tmp_path / "cache"))
    for dev in (jax.devices()[0], jax.devices()[5]):
        x = jax.device_put(jnp.ones((4,)), dev)
        compiled = jax.jit(lambda x: x * 3).lower(x).compile()
        key = cache_key("unit", "one-device", dev.id)
        assert cache.store(key, compiled, label="unit")
        head = json.loads(open(cache._path(key), "rb").read().split(b"\n")[0])
        assert head["devices"] == [dev.id]
        fn = cache.load(key)
        assert fn is not None
        out = fn(x)
        assert out.devices() == {dev}
        np.testing.assert_array_equal(np.asarray(out),
                                      np.full((4,), 3.0, np.float32))
    path = cache._path(key)
    head_raw, body = open(path, "rb").read().split(b"\n", 1)
    moved = dict(json.loads(head_raw), devices=[64])
    open(path, "wb").write(json.dumps(moved).encode() + b"\n" + body)
    assert cache.load(key) is None


def test_cache_entry_staleness_and_corruption(tmp_path):
    """Stale (other jax/platform) and damaged entries are LOGGED MISSES:
    load returns None, never raises, never returns a wrong callable."""
    import json

    import jax
    import jax.numpy as jnp

    cache = CompileCacheDir(str(tmp_path / "cache"))
    compiled = jax.jit(lambda x: x + 1).lower(jnp.ones((2,))).compile()
    key = cache_key("unit", "stale")
    cache.store(key, compiled)
    path = cache._path(key)
    blob = open(path, "rb").read()
    head_raw, body = blob.split(b"\n", 1)
    head = json.loads(head_raw)

    # stale jax version
    stale = dict(head, jax="0.0.1")
    open(path, "wb").write(json.dumps(stale).encode() + b"\n" + body)
    assert cache.load(key) is None

    # stale platform
    stale = dict(head, platform="tpu:TPU v9")
    open(path, "wb").write(json.dumps(stale).encode() + b"\n" + body)
    assert cache.load(key) is None

    # key mismatch (entry copied under the wrong name)
    open(path, "wb").write(blob)
    other = cache_key("unit", "other-model")
    import shutil

    shutil.copy(path, cache._path(other))
    assert cache.load(other) is None

    # chaos bit-flip and truncation
    assert chaos.corrupt_compile_cache(cache.root, key=key) == path
    assert cache.load(key) is None
    open(path, "wb").write(blob)
    chaos.corrupt_compile_cache(cache.root, key=key, mode="truncate")
    assert cache.load(key) is None

    # a pristine rewrite loads again (the validation is the only gate)
    open(path, "wb").write(blob)
    assert cache.load(key) is not None


# ---------------------------------------------------------------------------
# server cold-start acceptance
# ---------------------------------------------------------------------------


def test_warm_boot_zero_compiles_and_3x_faster(tmp_path, rng):
    """Acceptance: the warm-cache boot reaches ready with ZERO bucket
    compiles (pinned by the model's compile counter AND healthz) and
    >=3x faster than the cold boot in the same process; the quantized +
    cached path serves bit-identical outputs across two loads."""
    bundle = _bundle(tmp_path, rng, quantize="int8")
    cache_dir = str(tmp_path / "cache")
    feed = {"x": rng.randn(3, 64).astype(np.float32)}

    srv1, model1, cold = _boot(bundle, CompileCacheDir(cache_dir))
    hz1 = srv1.healthz()["cold_start"]
    out1 = srv1.infer(feed, deadline_ms=60000)["out"]
    srv1.close()
    assert model1.compile_events > 0
    assert hz1["compile_cache_misses"] == model1.compile_events
    assert hz1["cold_start_s"] is not None

    srv2, model2, warm = _boot(bundle, CompileCacheDir(cache_dir))
    hz2 = srv2.healthz()["cold_start"]
    out2 = srv2.infer(feed, deadline_ms=60000)["out"]
    srv2.close()
    assert model2.compile_events == 0, "warm boot paid an XLA compile"
    assert hz2["compile_cache_misses"] == 0
    assert hz2["warmup_compiles"] == 0
    assert hz2["compile_cache_hits"] == hz1["compile_cache_misses"]
    assert warm * 3 <= cold, f"warm {warm:.3f}s vs cold {cold:.3f}s"
    np.testing.assert_array_equal(out1, out2)  # quantized + cached path
    # the warmed executables ARE the serving executables: the hot-path
    # request above hit the AOT table, not a fresh jit
    assert model2._aot


def test_corrupt_cache_entry_falls_back_to_compile(tmp_path, rng):
    """Chaos: a damaged cached executable must produce a fresh compile
    (miss counter incremented) and correct replies — never a crash,
    never a wrong executable."""
    bundle = _bundle(tmp_path, rng)
    cache_dir = str(tmp_path / "cache")
    feed = {"x": rng.randn(2, 64).astype(np.float32)}

    srv1, _, _ = _boot(bundle, CompileCacheDir(cache_dir))
    ref = srv1.infer(feed, deadline_ms=60000)["out"]
    srv1.close()

    assert chaos.corrupt_compile_cache(cache_dir) is not None
    srv2, model2, _ = _boot(bundle, CompileCacheDir(cache_dir))
    hz = srv2.healthz()["cold_start"]
    got = srv2.infer(feed, deadline_ms=60000)["out"]
    srv2.close()
    assert hz["compile_cache_misses"] >= 1  # the damaged entry
    assert hz["compile_cache_hits"] >= 1    # the intact ones still load
    assert model2.compile_events == hz["compile_cache_misses"]
    np.testing.assert_array_equal(got, ref)

    # truncation: same contract
    chaos.corrupt_compile_cache(cache_dir, mode="truncate")
    srv3, _, _ = _boot(bundle, CompileCacheDir(cache_dir))
    got3 = srv3.infer(feed, deadline_ms=60000)["out"]
    srv3.close()
    np.testing.assert_array_equal(got3, ref)


def test_stale_entries_ignored_across_fingerprints(tmp_path, rng):
    """Two DIFFERENT models sharing one cache dir never serve each
    other's executables: the fingerprint keys them apart."""
    b1 = _bundle(tmp_path, rng, name="m1")
    b2 = _bundle(tmp_path, rng, name="m2")  # different weights (rng moved)
    cache = str(tmp_path / "cache")
    srv1, _, _ = _boot(b1, CompileCacheDir(cache))
    srv1.close()
    srv2, model2, _ = _boot(b2, CompileCacheDir(cache))
    hz = srv2.healthz()["cold_start"]
    srv2.close()
    # m2's boot found no entries for ITS fingerprint (all misses)...
    assert hz["compile_cache_hits"] == 0 and model2.compile_events > 0
    # ...but a same-payload reload of m2 hits them all
    srv3, model3, _ = _boot(b2, CompileCacheDir(cache))
    assert model3.compile_events == 0
    srv3.close()


# ---------------------------------------------------------------------------
# bundle-embedded executables (warm_bundle -> aot/ members)
# ---------------------------------------------------------------------------


def test_warm_bundle_embeds_and_serves(tmp_path, rng):
    """warm_bundle embeds the warmup executables as aot/ members; a
    replica serving the artifact (read-only cache) boots with zero
    compiles.  Corrupting a member falls back to compiling — the
    self-contained artifact is never less safe than compiling."""
    bundle = _bundle(tmp_path, rng, quantize="int8")
    # the warmed signatures must be the signatures the replica warms:
    # same feed shape, same outputs (defaults align with the serve CLI;
    # this test pins the in-process pairing explicitly)
    counts = warm_bundle(bundle, outputs=["out"],
                         feeds=[{"x": np.zeros((1, 64), np.float32)}])
    assert counts["misses"] == counts["buckets"] > 0
    assert BundleAotCache(bundle).has_entries()

    feed = {"x": rng.randn(2, 64).astype(np.float32)}
    # open_cache: read-only bundle layer (the serve CLI path)
    srv, model, _ = _boot(bundle, open_cache(bundle=bundle))
    hz = srv.healthz()["cold_start"]
    ref = srv.infer(feed, deadline_ms=60000)["out"]
    srv.close()
    assert model.compile_events == 0 and hz["compile_cache_misses"] == 0
    assert hz["compile_cache_hits"] == counts["buckets"]

    victim = chaos.corrupt_compile_cache(bundle)
    assert victim is not None and victim.startswith("aot/")
    srv2, model2, _ = _boot(bundle, open_cache(bundle=bundle))
    got = srv2.infer(feed, deadline_ms=60000)["out"]
    srv2.close()
    assert model2.compile_events >= 1  # the damaged member recompiled
    np.testing.assert_array_equal(got, ref)
    # the bundle's payload members survived the chaos rewrite: the model
    # itself still validates and loads
    load_inference_model(bundle)

    # re-running warm_bundle REPAIRS the damaged member (a store over an
    # existing entry replaces it, never first-writer-wins-forever): the
    # next replica boot is pure cache-hit again
    counts2 = warm_bundle(bundle, outputs=["out"],
                          feeds=[{"x": np.zeros((1, 64), np.float32)}])
    assert counts2["misses"] == 1 and counts2["hits"] == counts["buckets"] - 1
    srv3, model3, _ = _boot(bundle, open_cache(bundle=bundle))
    srv3.close()
    assert model3.compile_events == 0


# ---------------------------------------------------------------------------
# continuous mode: the slot closures
# ---------------------------------------------------------------------------


def _boot_generation(cache):
    backend = example_slot_backend(beam_size=2, src_len=8, max_len=8,
                                   vocab=256, dim=32)
    srv = InferenceServer(backend, mode="generation", slots=3,
                          default_deadline_ms=60000)
    t0 = time.perf_counter()
    srv.start(compile_cache=cache)
    dt = time.perf_counter() - t0
    return srv, dt


def test_generation_slot_closures_cache(tmp_path):
    """The continuous path's whole compile surface (prefill per bucket,
    step/write/release/finalize) loads from the cache on the second
    boot — zero misses, >=3x faster — and per-request outputs stay
    BIT-identical to the cold boot's."""
    cache_dir = str(tmp_path / "cache")
    feed = {"src": (np.full((1, 8), 3, np.int32),
                    np.asarray([5], np.int32))}

    srv1, cold = _boot_generation(CompileCacheDir(cache_dir))
    hz1 = srv1.healthz()["cold_start"]
    out1 = srv1.submit(feed, deadline_ms=60000, max_len=3).result(60)
    srv1.close()
    assert hz1["compile_cache_misses"] > 0

    srv2, warm = _boot_generation(CompileCacheDir(cache_dir))
    hz2 = srv2.healthz()["cold_start"]
    out2 = srv2.submit(feed, deadline_ms=60000, max_len=3).result(60)
    srv2.close()
    assert hz2["compile_cache_misses"] == 0
    assert hz2["warmup_compiles"] == 0
    assert hz2["compile_cache_hits"] == hz1["compile_cache_misses"]
    assert warm * 3 <= cold, f"warm {warm:.3f}s vs cold {cold:.3f}s"
    np.testing.assert_array_equal(out1["tokens"], out2["tokens"])
    np.testing.assert_array_equal(out1["scores"], out2["scores"])


@pytest.fixture
def jax_cache_serves_everything(tmp_path):
    """JAX's persistent compilation cache in a directory of the test's own
    with its store threshold at zero, so every program compiled through it
    is stored and served from then on; yields the hit counter."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    keep = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "jaxcc"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cc.reset_cache()
    seen = {"hits": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            seen["hits"] += 1

    jax.monitoring.register_event_listener(on_event)
    try:
        yield seen
    finally:
        from jax._src import monitoring

        monitoring.unregister_event_listener(on_event)
        jax.config.update("jax_compilation_cache_dir", keep[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          keep[1])
        cc.reset_cache()


@pytest.mark.parametrize("mode", ["bucket", "generation"])
def test_aotx_entries_sound_when_jax_cache_holds_the_programs(
        tmp_path, rng, jax_cache_serves_everything, mode):
    """An executable JAX's persistent cache SERVED re-serializes (XLA:CPU,
    jaxlib 0.9.0) into an entry that loads and fails its first call, so
    the next boot logged "rejected by its smoke call" and went cold.  The
    server's programs are therefore compiled past that cache
    (``compile_fresh``): with every one of them sitting in JAX's cache, the
    first ``.aotx`` boot still stores sound entries and the second boot
    loads them all."""
    seen = jax_cache_serves_everything
    if mode == "bucket":
        bundle = _bundle(tmp_path, rng)

        def boot(cache):
            srv, model, _ = _boot(bundle, cache)
            return srv, model
    else:
        def boot(cache):
            return _boot_generation(cache)[0], None

    # no .aotx cache: the programs go through JAX's cache, twice — the
    # second boot is served from it, so it does hold them
    boot(None)[0].close()
    before = seen["hits"]
    boot(None)[0].close()
    assert seen["hits"] > before, "JAX's cache served nothing"

    cache_dir = str(tmp_path / "aotx")
    srv1, _ = boot(CompileCacheDir(cache_dir))
    hz1 = srv1.healthz()["cold_start"]
    srv1.close()
    assert hz1["compile_cache_misses"] > 0

    srv2, model2 = boot(CompileCacheDir(cache_dir))
    hz2 = srv2.healthz()["cold_start"]
    srv2.close()
    assert hz2["compile_cache_misses"] == 0, "a stored entry was rejected"
    assert hz2["compile_cache_hits"] == hz1["compile_cache_misses"]
    assert hz2["warmup_compiles"] == 0
    if model2 is not None:
        assert model2.compile_events == 0


def test_slot_prime_is_idempotent_across_caches(tmp_path):
    """prime() twice — second time against a FRESH empty cache (fleet
    reconfig) — must recompile from the original jits, not crash on a
    Compiled object, and the uncached compile counter stays honest."""
    from paddle_tpu.serving.slots import SlotScheduler, example_slot_backend

    backend = example_slot_backend(beam_size=2, src_len=8, max_len=8,
                                   vocab=256, dim=32)
    sched = SlotScheduler(backend, slots=2)
    feeds = [backend.example_feed(1)]
    jit_before = sched.compiled_programs()
    c1 = sched.prime(CompileCacheDir(str(tmp_path / "a")), feeds)
    assert c1["misses"] > 0 and not c1["skipped"]
    c2 = sched.prime(CompileCacheDir(str(tmp_path / "a")), feeds)
    assert c2["misses"] == 0 and c2["hits"] > 0      # same cache: hits
    c3 = sched.prime(CompileCacheDir(str(tmp_path / "b")), feeds)
    assert c3["misses"] == c1["misses"]              # fresh cache: re-lowered
    # the AOT loads/compiles never entered the original jit caches
    # (delta: earlier tests in the process may share a closure's cache)
    assert sched.compiled_programs() == jit_before


def test_generation_corrupt_slot_entry_falls_back(tmp_path):
    cache_dir = str(tmp_path / "cache")
    feed = {"src": (np.full((1, 8), 3, np.int32),
                    np.asarray([5], np.int32))}
    srv1, _ = _boot_generation(CompileCacheDir(cache_dir))
    ref = srv1.submit(feed, deadline_ms=60000, max_len=3).result(60)
    srv1.close()
    assert chaos.corrupt_compile_cache(cache_dir) is not None
    srv2, _ = _boot_generation(CompileCacheDir(cache_dir))
    hz = srv2.healthz()["cold_start"]
    got = srv2.submit(feed, deadline_ms=60000, max_len=3).result(60)
    srv2.close()
    assert hz["compile_cache_misses"] >= 1
    np.testing.assert_array_equal(got["tokens"], ref["tokens"])
    np.testing.assert_array_equal(got["scores"], ref["scores"])
