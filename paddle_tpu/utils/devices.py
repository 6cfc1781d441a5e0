"""Device management — TPU-native analog of the reference's hl device layer.

The reference manages CUDA devices/streams/events explicitly (reference:
paddle/cuda/include/hl_cuda.h:34-343, src/hl_cuda_device.cc:86-162).  Under
XLA none of that is user-visible: devices come from ``jax.devices()``, streams
are the runtime's, and multi-device execution is expressed as a
``jax.sharding.Mesh``.  This module is the single place that touches global
device state: platform selection, virtual-device forcing for tests, and mesh
construction from flags.
"""

from __future__ import annotations

import os
from typing import List, Optional, Sequence, Tuple

__all__ = [
    "init",
    "devices",
    "device_count",
    "device_report",
    "default_backend",
    "make_mesh",
    "force_virtual_devices",
    "use_compilation_cache",
]

_initialized = False

#: JAX's persistent compilation cache when ``JAX_COMPILATION_CACHE_DIR``
#: does not place it: one fixed directory inside the checkout (the path is
#: part of the cache key, so a directory that moves never hits)
COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def use_compilation_cache() -> None:
    """Switch JAX's persistent compilation cache on.  Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache stays there — JAX
    reads the variable itself and no code of this repo sets another —
    otherwise it goes to :data:`COMPILATION_CACHE_DIR`.  The one place the
    repo's entry points (``init``: the CLI, chip_smoke.py; the
    tests' conftest) place that cache."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", COMPILATION_CACHE_DIR)


def force_virtual_devices(n: int) -> None:
    """Force N virtual CPU devices (must run before the first jax *backend*
    initialization; calling it before or after ``import jax`` both work).

    Test-only analog of a multi-chip pod; see SURVEY.md §4 (device-equivalence
    strategy) — used by tests/conftest.py and driver dry runs.  When jax was
    already imported, ``JAX_PLATFORMS`` has been read into jax.config, so
    the env vars alone are not enough and the config value is overridden
    too.
    """
    import sys

    flags = os.environ.get("XLA_FLAGS", "")
    token = f"--xla_force_host_platform_device_count={n}"
    kept = [t for t in flags.split()
            if "xla_force_host_platform_device_count" not in t]
    os.environ["XLA_FLAGS"] = " ".join(kept + [token])
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "jax" in sys.modules:
        import jax

        jax.config.update("jax_platforms", "cpu")


def init(argv: Optional[list] = None) -> list:
    """Framework init — analog of paddle.init()/initMain (reference:
    paddle/trainer/TrainerMain.cpp:32-49).  Parses flags, selects platform,
    seeds determinism. Returns leftover argv."""
    global _initialized
    from paddle_tpu.obs.timeline import setup_phase
    from paddle_tpu.utils.flags import FLAGS, parse_flags

    with setup_phase("init"):   # the set-up record (docs/observability.md)
        rest = parse_flags(argv)
        if not _initialized:
            if FLAGS.num_virtual_devices:
                force_virtual_devices(FLAGS.num_virtual_devices)
            if FLAGS.platform:
                os.environ["JAX_PLATFORMS"] = FLAGS.platform
            use_compilation_cache()
            _initialized = True
        apply_numeric_traps()
    return rest


def apply_numeric_traps() -> None:
    """Install/remove the NaN/Inf trap per --check_nan — the
    feenableexcept(FE_INVALID|...) analog (reference:
    paddle/trainer/TrainerMain.cpp:49).  jax_debug_nans re-runs the offending
    jitted program op-by-op and raises at the producing primitive."""
    import jax

    from paddle_tpu.utils.flags import FLAGS

    jax.config.update("jax_debug_nans", bool(FLAGS.check_nan))
    jax.config.update("jax_debug_infs", bool(FLAGS.check_nan))


def devices() -> List:
    import jax

    return jax.devices()


def device_count() -> int:
    return len(devices())


def device_report() -> dict:
    """The attached devices as JAX reports them — what every measurement
    and the chip smoke name in their output."""
    devs = devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def default_backend() -> str:
    import jax

    return jax.default_backend()


def _parse_mesh_shape(spec: str, ndev: int) -> Tuple[int, ...]:
    if not spec:
        return (ndev,)
    dims = tuple(int(d) for d in spec.replace(",", "x").split("x") if d)
    return dims


def make_mesh(
    shape: Optional[Sequence[int]] = None,
    axis_names: Optional[Sequence[str]] = None,
):
    """Build a ``jax.sharding.Mesh`` from flags or explicit shape.

    This replaces both the reference's per-GPU TrainerThread pool
    (gserver/gradientmachines/MultiGradientMachine.h:44-94) and its
    trainers-by-pservers network topology (pserver/): on TPU the set of chips is
    one SPMD mesh and collectives ride ICI.
    """
    import jax

    # one implementation of flag parsing, name defaulting, and device
    # reshaping: the declarative config plane (parallel/mesh.py) — this
    # stays the legacy Mesh-returning entry point over it
    from paddle_tpu.parallel.mesh import MeshConfig
    from paddle_tpu.utils.flags import FLAGS

    devs = jax.devices()
    if shape is None:
        shape = _parse_mesh_shape(FLAGS.mesh_shape, len(devs))
    if axis_names is None:
        axis_names = FLAGS.mesh_axes.split(",")
    return MeshConfig.named(shape, axis_names).build(devs)
