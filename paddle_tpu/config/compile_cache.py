"""Persistent compiled-executable cache — seconds-not-minutes fleet
cold-start (docs/deploy.md).

The reference's deploy story is "one binary runs anywhere": a merged
model is ``dlopen``'d and runs immediately (paddle/capi).  Our TPU-native
analogue re-jit-compiles every warmup shape bucket at every replica boot,
which multiplies minutes of XLA compile time across a serving fleet.
This module persists the AOT executables themselves
(``jax.jit(...).lower().compile()`` serialized via
``jax.experimental.serialize_executable``) so a warm replica *loads*
instead of compiling:

- :class:`CompileCacheDir` — a shared ``--compile_cache_dir`` of
  ``<key>.aotx`` files (one fleet-wide NFS/GCS-fuse dir warms every
  replica after the first boot);
- :class:`BundleAotCache` — ``aot/<key>.aotx`` members embedded in the
  ``.ptz`` bundle itself (:func:`warm_bundle`), the closest analog of the
  reference's self-contained merged model: ship ONE artifact, boot ready.

Entries are keyed by model fingerprint + exact feed signature and
self-describe their platform + jax version; a stale or corrupt entry is a
LOGGED MISS that falls back to a fresh compile — never a crash, never a
wrong executable (the loaded callable is smoke-called once before it is
trusted).  An entry records the devices its executable was compiled for
and is loaded for exactly those, so a sound entry is never rejected on a
host with more devices than the program uses.  What goes into an entry is
always compiled here and now (:func:`compile_fresh`), never handed over by
JAX's own persistent compilation cache.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import tempfile
import threading
import zipfile
import zlib
from typing import Any, Callable, Dict, List, Optional

from paddle_tpu.utils.log import logger

__all__ = ["CompileCacheDir", "BundleAotCache", "ChainCache",
           "cache_key", "platform_fingerprint", "open_cache",
           "warm_bundle", "compile_fresh", "AOT_PREFIX"]

_AOTX_MAGIC = "paddle_tpu.aotx.v1"
#: zip member prefix for executables embedded in a .ptz bundle
AOT_PREFIX = "aot/"
_SUFFIX = ".aotx"


def platform_fingerprint() -> str:
    """Backend + device-kind the executable was compiled for — an
    executable must never cross this boundary (a CPU-compiled program
    loaded on TPU is garbage, not slow)."""
    import jax

    dev = jax.devices()[0]
    return f"{jax.default_backend()}:{dev.device_kind}"


_fresh_lock = threading.Lock()


def compile_fresh(lowered):
    """``lowered.compile()`` with JAX's persistent compilation cache out of
    the way — how every executable destined for an ``.aotx`` entry is
    compiled.  An executable that cache *served* is not the compiler's own
    output: XLA:CPU of jaxlib 0.9.0 re-serializes one into a payload that
    loads and then fails on its first call ("Function ... not found"), so
    the entry written from it was rejected by its smoke call on the next
    boot and a warm boot silently became a cold one.  ``.aotx`` is these
    programs' cache; JAX's is neither read nor written for them.  JAX
    memoizes whether its cache is in use, hence the resets; a compile
    that another thread runs inside the window merely skips that cache."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    with _fresh_lock:
        if not jax.config.jax_enable_compilation_cache:
            return lowered.compile()
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            return lowered.compile()
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            cc.reset_cache()


def cache_key(kind: str, *parts: Any) -> str:
    """Deterministic content key: closure kind + model fingerprint + the
    exact argument signature, hashed.  jax version and platform ride the
    entry HEADER (so a mismatch is a *logged* stale miss, attributable,
    instead of an unexplained key miss)."""
    blob = json.dumps([kind, *[str(p) for p in parts]], sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:32]


def _encode_entry(compiled, *, key: str, label: str) -> bytes:
    import jax
    from jax.experimental import serialize_executable as se

    payload, in_tree, out_tree = se.serialize(compiled)
    body = pickle.dumps((payload, in_tree, out_tree))
    header = {
        "magic": _AOTX_MAGIC,
        "key": key,
        "label": label,
        "platform": platform_fingerprint(),
        "jax": jax.__version__,
        # the devices the executable runs on, in assignment order — it is
        # loaded for these and no others (see _decode_entry)
        "devices": [d.id for d in
                    compiled._executable._unloaded_executable.device_list],
        "crc32": zlib.crc32(body),
    }
    return json.dumps(header).encode() + b"\n" + body


def _decode_entry(blob: bytes, *, key: str, where: str
                  ) -> Optional[Callable]:
    """One entry -> a loaded executable, or None with the miss reason
    logged.  Every failure mode — torn header, stale platform/jax, CRC
    mismatch, unpicklable body — degrades to a fresh compile."""
    import jax

    try:
        head_raw, body = blob.split(b"\n", 1)
        header = json.loads(head_raw)
    except Exception:
        logger.warning("compile cache: %s is corrupt (unparsable header) "
                       "— recompiling", where)
        return None
    if not isinstance(header, dict) or header.get("magic") != _AOTX_MAGIC:
        logger.warning("compile cache: %s is not an aotx entry — "
                       "recompiling", where)
        return None
    if header.get("key") != key:
        logger.warning("compile cache: %s key mismatch (stored for %r) — "
                       "recompiling", where, header.get("key"))
        return None
    stale = []
    if header.get("platform") != platform_fingerprint():
        stale.append(f"platform {header.get('platform')!r} != "
                     f"{platform_fingerprint()!r}")
    if header.get("jax") != jax.__version__:
        stale.append(f"jax {header.get('jax')!r} != {jax.__version__!r}")
    # load for the devices the executable was compiled for: left to its
    # default, jax loads it for EVERY device of the backend, and a
    # one-device program then demands one shard per device at call time
    by_id = {d.id: d for d in jax.devices()}
    device_ids = header.get("devices")
    if not device_ids or any(i not in by_id for i in device_ids):
        stale.append(f"compiled for devices {device_ids!r}, backend has "
                     f"{sorted(by_id)!r}")
    if stale:
        logger.warning("compile cache: %s is stale (%s) — recompiling",
                       where, "; ".join(stale))
        return None
    if zlib.crc32(body) != header.get("crc32"):
        logger.warning("compile cache: %s payload CRC mismatch (torn or "
                       "bit-flipped entry) — recompiling", where)
        return None
    try:
        from jax.experimental import serialize_executable as se

        payload, in_tree, out_tree = pickle.loads(body)
        return se.deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=[by_id[i] for i in device_ids])
    except Exception as e:  # noqa: BLE001 — a bad entry must never crash
        logger.warning("compile cache: %s failed to deserialize (%s: %s) "
                       "— recompiling", where, type(e).__name__, e)
        return None


class _CacheBase:
    """Shared counters + the load/store contract.  ``hits``/``misses``
    are about *entry presence*; ``stale``/``corrupt`` subdivide misses
    that found bytes but could not trust them."""

    def __init__(self) -> None:
        self.hits = 0
        self.misses = 0

    def _read(self, key: str) -> Optional[bytes]:
        raise NotImplementedError

    def _write(self, key: str, blob: bytes) -> bool:
        raise NotImplementedError

    def _where(self, key: str) -> str:
        raise NotImplementedError

    def load(self, key: str) -> Optional[Callable]:
        blob = self._read(key)
        if blob is None:
            self.misses += 1
            return None
        fn = _decode_entry(blob, key=key, where=self._where(key))
        if fn is None:
            self.misses += 1      # present-but-untrustworthy IS a miss
            return None
        self.hits += 1
        return fn

    def store(self, key: str, compiled, *, label: str = "") -> bool:
        try:
            blob = _encode_entry(compiled, key=key, label=label)
        except Exception as e:  # noqa: BLE001 — backend can't serialize
            logger.warning("compile cache: executable %r not serializable "
                           "on this backend (%s: %s) — not cached", label,
                           type(e).__name__, e)
            return False
        return self._write(key, blob)


class CompileCacheDir(_CacheBase):
    """A shared directory of ``<key>.aotx`` entries (``--compile_cache_dir``).
    Writes are atomic (temp + rename) so replicas racing on a cold fleet
    boot never read each other's torn entries."""

    def __init__(self, root: str) -> None:
        super().__init__()
        self.root = os.path.abspath(root)
        os.makedirs(self.root, exist_ok=True)

    def _path(self, key: str) -> str:
        return os.path.join(self.root, key + _SUFFIX)

    def _where(self, key: str) -> str:
        return self._path(key)

    def _read(self, key: str) -> Optional[bytes]:
        try:
            with open(self._path(key), "rb") as f:
                return f.read()
        except FileNotFoundError:
            return None
        except OSError as e:
            logger.warning("compile cache: %s unreadable (%s) — recompiling",
                           self._path(key), e)
            return None

    def _write(self, key: str, blob: bytes) -> bool:
        try:
            fd, tmp = tempfile.mkstemp(dir=self.root, suffix=".tmp")
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, self._path(key))
            return True
        except OSError as e:
            logger.warning("compile cache: could not write %s (%s)",
                           self._path(key), e)
            return False


class BundleAotCache(_CacheBase):
    """``aot/<key>.aotx`` members inside a ``.ptz`` bundle — the
    self-contained deploy artifact (:func:`warm_bundle` populates them).
    Reads keep the bundle's CRC attribution: a torn member is a logged
    miss, mirrored from ``BundleCorruptError``'s member naming.  Writes
    (``writable=True``) append members to the existing zip; replicas
    serving a shared read-only bundle leave ``writable`` off."""

    def __init__(self, bundle_path: str, *, writable: bool = False) -> None:
        super().__init__()
        self.bundle_path = bundle_path
        self.writable = writable
        try:
            with zipfile.ZipFile(bundle_path) as z:
                self._members = set(z.namelist())
        except Exception:
            self._members = set()

    def _member(self, key: str) -> str:
        return AOT_PREFIX + key + _SUFFIX

    def _where(self, key: str) -> str:
        return f"{self.bundle_path}!{self._member(key)}"

    def has_entries(self) -> bool:
        return any(m.startswith(AOT_PREFIX) for m in self._members)

    def _read(self, key: str) -> Optional[bytes]:
        name = self._member(key)
        if name not in self._members:
            return None
        try:
            with zipfile.ZipFile(self.bundle_path) as z:
                return z.read(name)
        except Exception as e:  # noqa: BLE001 — torn member = logged miss
            logger.warning("compile cache: bundle member %s unreadable "
                           "(%s: %s) — recompiling", self._where(key),
                           type(e).__name__, e)
            return None

    def _write(self, key: str, blob: bytes) -> bool:
        if not self.writable:
            return False
        name = self._member(key)
        try:
            if name in self._members:
                # a store over an existing member is a REPAIR (the entry
                # was corrupt or stale — that is why it missed and got
                # recompiled): rewrite the archive with the member
                # replaced, or re-running warm_bundle could never fix a
                # damaged artifact and every later boot would stay cold
                with zipfile.ZipFile(self.bundle_path) as z:
                    members = [(i.filename, z.read(i.filename))
                               for i in z.infolist() if i.filename != name]
                tmp = self.bundle_path + ".tmp"
                with zipfile.ZipFile(tmp, "w", zipfile.ZIP_DEFLATED) as z:
                    for mname, data in members:
                        z.writestr(mname, data)
                    z.writestr(name, blob)
                os.replace(tmp, self.bundle_path)
            else:
                with zipfile.ZipFile(self.bundle_path, "a",
                                     zipfile.ZIP_DEFLATED) as z:
                    z.writestr(name, blob)
            self._members.add(name)
            return True
        except Exception as e:  # noqa: BLE001
            logger.warning("compile cache: could not embed %s (%s: %s)",
                           self._where(key), type(e).__name__, e)
            return False


class ChainCache(_CacheBase):
    """Bundle-embedded entries first, then the shared dir; stores go to
    every writable layer so a dir-warmed boot also repairs a stale
    bundle when it owns it."""

    def __init__(self, caches: List[_CacheBase]) -> None:
        super().__init__()
        self.caches = [c for c in caches if c is not None]

    def load(self, key: str) -> Optional[Callable]:
        for c in self.caches:
            fn = c.load(key)
            if fn is not None:
                self.hits += 1
                return fn
        self.misses += 1
        return None

    def store(self, key: str, compiled, *, label: str = "") -> bool:
        return any([c.store(key, compiled, label=label)
                    for c in self.caches])


def open_cache(bundle: Optional[str] = None, cache_dir: str = ""
               ) -> Optional[_CacheBase]:
    """The serve-CLI policy: read bundle-embedded ``aot/`` members when
    the bundle carries any (read-only — a fleet shares the artifact),
    plus a writable ``--compile_cache_dir``.  Returns None when neither
    layer exists."""
    layers: List[_CacheBase] = []
    if bundle:
        b = BundleAotCache(bundle)
        if b.has_entries():
            layers.append(b)
    if cache_dir:
        layers.append(CompileCacheDir(cache_dir))
    if not layers:
        return None
    return layers[0] if len(layers) == 1 else ChainCache(layers)


def warm_bundle(bundle_path: str, *, max_batch: int = 8,
                feeds: Optional[List[Dict[str, Any]]] = None,
                outputs: Optional[List[str]] = None,
                cache: Optional[_CacheBase] = None) -> Dict[str, int]:
    """Pre-compile every warmup batch bucket of a bundle and embed the
    executables as ``aot/`` members (or into ``cache``) — run once after
    export, and every replica that serves the artifact boots ready in
    seconds.  The bucket ladder and row padding are the SAME primitives
    the serving hot path batches with (serving.batching), so the warmed
    signatures are exactly the shapes ``merge_feeds`` can produce."""
    from paddle_tpu.config.deploy import load_inference_model
    from paddle_tpu.serving.batching import batch_bucket, warmup_bucket_feeds
    from paddle_tpu.serving.feeds import example_feed

    model = load_inference_model(bundle_path)
    if cache is None:
        cache = BundleAotCache(bundle_path, writable=True)
    if feeds is None:
        feeds = [example_feed(model.topology)]
    buckets = sorted({batch_bucket(r, max_batch)
                      for r in range(1, max_batch + 1)})
    counts = {"hits": 0, "misses": 0, "buckets": 0}
    for feed in feeds:
        for padded in warmup_bucket_feeds(feed, buckets):
            r = model.prime(padded, outputs=outputs, cache=cache)
            counts["buckets"] += 1
            counts["hits" if r == "hit" else "misses"] += 1
    return counts
