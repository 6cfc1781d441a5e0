import pytest

from paddle_tpu.utils.flags import FLAGS, define_flag, parse_flags
from paddle_tpu.utils.registry import Registry
from paddle_tpu.utils.error import PaddleTpuError, layer_scope
from paddle_tpu.utils import devices


def test_flag_defaults_and_parse():
    assert FLAGS.log_period == 100
    rest = parse_flags(["--log_period=7", "positional", "--beam_size", "5"])
    assert FLAGS.log_period == 7
    assert FLAGS.beam_size == 5
    assert rest == ["positional"]
    FLAGS.log_period = 100
    FLAGS.beam_size = 3


def test_flag_bool_coercion():
    parse_flags(["--enable_timers"])
    assert FLAGS.enable_timers is True
    parse_flags(["--enable_timers=false"])
    assert FLAGS.enable_timers is False


def test_unknown_flag_left_in_argv():
    rest = parse_flags(["--no_such_flag=1"])
    assert rest == ["--no_such_flag=1"]


def test_registry():
    reg = Registry("thing")

    @reg.register("a")
    def a():
        return 1

    assert reg.get("a") is a
    assert "a" in reg
    with pytest.raises(KeyError):
        reg.get("missing")
    with pytest.raises(ValueError):
        reg.register("a")(a)


def test_layer_scope_wraps_errors():
    with pytest.raises(PaddleTpuError, match=r"outer -> inner"):
        with layer_scope("outer"):
            with layer_scope("inner"):
                raise RuntimeError("boom")


def test_virtual_devices_mesh():
    from conftest import on_accelerator

    if on_accelerator():
        pytest.skip("assumes the 8-virtual-device CPU mesh")
    assert devices.device_count() == 8
    mesh = devices.make_mesh((4, 2), ("data", "model"))
    assert mesh.shape == {"data": 4, "model": 2}
    mesh1 = devices.make_mesh()
    assert mesh1.shape == {"data": 8}


def test_check_nan_flag_traps():
    """--check_nan installs the feenableexcept analog: a NaN escaping a
    jitted computation raises instead of propagating silently
    (reference: TrainerMain.cpp:49)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.utils.devices import apply_numeric_traps
    from paddle_tpu.utils.flags import FLAGS

    old = FLAGS.check_nan
    try:
        FLAGS.check_nan = True
        apply_numeric_traps()
        with pytest.raises(FloatingPointError):
            jax.jit(lambda x: jnp.log(x))(jnp.asarray(-1.0)).block_until_ready()
    finally:
        FLAGS.check_nan = old
        apply_numeric_traps()
    # trap removed: silent nan again
    out = jax.jit(lambda x: jnp.log(x))(jnp.asarray(-1.0))
    assert bool(jnp.isnan(out))


def test_compilation_cache_is_placed_from_outside(monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set, JAX's persistent cache stays
    there and no code sets another; unset, every entry point uses the one
    fixed directory inside the checkout."""
    import os

    import jax

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
        devices.use_compilation_cache()
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        devices.use_compilation_cache()
        placed = jax.config.jax_compilation_cache_dir
        assert placed == devices.COMPILATION_CACHE_DIR
        assert placed == os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
