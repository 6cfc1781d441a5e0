"""Test harness: force an 8-virtual-device CPU platform before jax initializes.

Mirrors the reference's test strategy of exercising distributed paths
in-process (SURVEY.md §4): multi-chip sharding logic runs on a virtual CPU
mesh; numerical checks compare against numpy and finite differences.
"""

import os

os.environ.setdefault("PADDLE_TPU_COMPUTE_DTYPE", "float32")

# PADDLE_TPU_TEST_BACKEND=tpu runs tests against an attached chip — meant for
# the op/kernel files (test_ops, test_rnn_fused, test_attention_decoder,
# test_crf_ctc): numeric tolerances widen and FD checks skip via
# on_accelerator(); mesh/device-count-dependent tests still assume the
# 8-virtual-device CPU mesh and are skipped on hardware.
if os.environ.get("PADDLE_TPU_TEST_BACKEND") != "tpu":
    # force_virtual_devices sets the env vars and, where jax was imported
    # already, the jax_platforms config too.
    from paddle_tpu.utils.devices import force_virtual_devices

    force_virtual_devices(8)

import numpy as np
import pytest

# Persistent XLA compilation cache for the suite.  The scheduler/server
# tiers deliberately build FRESH jit closures per instance (so per-table
# compile counters can't cross-talk), which means hundreds of tests
# recompile byte-identical XLA programs (same seeded weights folded in
# as constants).  The disk cache serves those recompiles — both across
# test runs AND across closures within one run — without touching any
# in-process jit-cache counter the tests pin (tracing still happens;
# only the XLA backend compile is skipped).  It is placed where the
# program places it: JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache.
import jax  # noqa: E402

from paddle_tpu.utils.devices import use_compilation_cache  # noqa: E402

use_compilation_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from tier-1 (-m 'not slow'); full-size acceptance "
        "runs like the 100M-row pserver table")


#: Pins of an earlier cell's test file that the benchmark's own rule makes
#: false.  A PR that adds a cell appends its entries to ``BENCHMARK.json`` and
#: may edit no file the benchmark already has (``tests/benchmark/`` is one of
#: its ``paths``), so a test there that says "the LAST entries are mine" holds
#: for the PR that wrote it and for no later one.  Marked expected to fail,
#: with the reason, until a ``benchmark`` PR rewrites the pin as a position
#: relative to its neighbours (PERF.md section 7); what else each asserted is
#: asserted again in ``tests/benchmark/test_qwen3next_cell.py`` (the first
#: two) and ``tests/benchmark/test_nemotron_cell.py`` (the third, whose own
#: assertions are relative and need no entry here).  (Here and
#: not in a ``tests/benchmark/conftest.py``: the tests import this file as
#: ``conftest``, and a second module of that name shadows it.)
OUTDATED_PINS = {
    "tests/benchmark/test_kanana2_cell.py::"
    "test_new_metrics_are_this_cells_alone":
        "pins Kanana-2's cell and configuration as the LAST entries of "
        "BENCHMARK.json; PR 41 appended qwen3next-train-b1-t8192 after them",
    "tests/benchmark/test_trace_spans.py::"
    "test_the_eight_come_last_and_the_cells_pinned_sets_do_not_hold_them":
        "pins PR 38's eight idle parts as the LAST per-layer metrics of "
        "BENCHMARK.json; PR 41 appended its six after them",
    "tests/benchmark/test_qwen3next_cell.py::"
    "test_new_metrics_are_this_cells_alone":
        "pins Qwen3-Next's cell, configuration and six metrics as the LAST "
        "entries of BENCHMARK.json; PR 43 appended "
        "nemotron3nano-train-b1-t4096 and its seven after them (what still "
        "holds of it is asserted again, by position relative to its "
        "neighbours, in tests/benchmark/test_nemotron_cell.py)",
    "tests/benchmark/test_setup_phase_s.py::"
    "test_only_the_lstm_cell_holds_them[ouro-train-b1-t4096]":
        "its last line pins the list of ALL cells as PR 52 found it; PR 54 "
        "appended ouro-train-b1-t4096 (that the cell holds none of the "
        "eight set-up metrics is held with ``==`` on its whole per-layer "
        "set in tests/benchmark/test_ouro_cell.py)",
    "tests/benchmark/test_setup_phase_s.py::"
    "test_only_the_lstm_cell_holds_them[smallthinker-train-b1-t16384]":
        "its last line pins the list of ALL cells as PR 52 found it; PR 57 "
        "appended smallthinker-train-b1-t16384 (that the cell holds none of "
        "the eight set-up metrics is held with ``==`` on its whole "
        "per-layer set in tests/benchmark/test_smallthinker_cell.py)",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        why = OUTDATED_PINS.get(item.nodeid)
        if why:
            item.add_marker(pytest.mark.xfail(reason=why, strict=False))


@pytest.fixture
def rng():
    return np.random.RandomState(0)


@pytest.fixture
def own_registry():
    """The metrics registry is the process's: a test that feeds counters
    under names other files use too (``moe_assignments{layer, expert}``: the
    benchmark's tiny cells hold experts 0 and 1 of the same layers) starts
    from an empty one and leaves none of its own for the worker's next."""
    from paddle_tpu.obs import reset_registry

    reset_registry()
    yield
    reset_registry()


def on_accelerator() -> bool:
    """True when the suite was launched in hardware mode
    (PADDLE_TPU_TEST_BACKEND=tpu): matmul precision is bf16-passes, FD
    checks are meaningless, and the 8-virtual-device mesh assumptions do
    not hold.  Keyed on the SAME env var as the conftest platform branch so
    the two can never disagree (a tpu-mode run that fell back to CPU still
    skips mesh tests and widens tolerances — harmless both ways)."""
    return os.environ.get("PADDLE_TPU_TEST_BACKEND") == "tpu"
