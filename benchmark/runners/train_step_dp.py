"""Runner ``train_step_dp``: ``runners/train_step.py`` over a mesh of the
cell's chips.  The step is the program's own data-parallel one,
``parallel_train_step(cfg, chips) -> (step, optimizer, mesh)`` of the program
file the traffic file names under ``program``; parameters and Adam state are
placed replicated and every batch of the ring split over its rows (``place``
of the same file: the program's ``shard_params`` / ``shard_batch``).  The
traffic file gives ``batch_per_chip``; the global batch, drawn in one piece
from the seed, is that times ``chips``.

``correct`` is the one-chip runner's: the step's first three calls on the
global batch against the plain float32 reference on the same rows, on one
chip, in blocks.  The step donates its arguments, and ``first_steps`` reads
two of them again (the parameters before the first step, the first moment
after it), so inside ``first_steps`` each call is handed copies to donate.
One more number, limit 0: ``replica_param_gap``, the largest difference of
any parameter leaf between any two chips after the three steps (every chip
applies the same all-reduced gradient to the same replica, so the copies
stay equal to the bit; a step whose exchange is left out fails it).

``memory_peak_bytes`` is the fullest chip's (``Context.memory_now``).  A
trace holds one device plane a chip: ``trace_reduce`` and ``trace_scopes``
average busy, idle, kernel and scope time over the planes, and
``benchmark/trace_chips.py`` keeps them apart for the metrics of the layer
``parallel/``."""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import correct, manifest, traffic

first_steps = manifest.runner("train_step").first_steps

#: faults planted in the reference put in the program's place, for the
#: upper ends of the limits (``benchmark/check_faults.py``): the rows of the
#: global batch the faulty step would take its mean over
FAULT_ROWS = {
    "half_batch": lambda rows, chips: rows // 2,
    "no_exchange": lambda rows, chips: rows // chips,
}


def global_traffic(cell: dict) -> dict:
    """The traffic file with ``batch``, the global batch the feed draws."""
    tr = cell["traffic"]
    return dict(tr, batch=tr["batch_per_chip"] * cell["chips"])


def program(cell: dict):
    return manifest.load_module(
        os.path.join(manifest.ROOT, cell["traffic"]["program"]),
        "bench_program_parallel")


def undonated(step):
    """``step`` on copies of its state: the caller's arrays outlive the
    call, and the same compiled program runs."""
    def call(params, opt_state, batch):
        fresh = jax.tree_util.tree_map(jnp.copy, (params, opt_state))
        return step(*fresh, batch)

    return call


_gap = jax.jit(lambda a, b: jnp.max(jnp.abs(a - b)))


def replica_param_gap(params: dict) -> float:
    """Largest |difference| of any element of any leaf between the first
    chip's copy and another chip's, the other copy moved to the first."""
    worst = 0.0
    for name, leaf in params.items():
        copies = [s.data for s in leaf.addressable_shards]
        if any(c.shape != leaf.shape for c in copies):
            raise ValueError(f"{name} is not replicated: {leaf.sharding}")
        home = copies[0]
        (device,) = home.devices()
        for other in copies[1:]:
            gap = float(_gap(home, jax.device_put(other, device)))
            if not np.isfinite(gap):
                return float("inf")
            worst = max(worst, gap)
    return worst


def _dp_steps(cell, cfg, params, host_ring):
    """The sharded step through its first steps: the readings ``compare``
    takes (the gradient on the one chip the reference's is on), the state
    after, the step and the placed ring."""
    prog = program(cell)
    step, opt, mesh = prog.parallel_train_step(cfg, cell["chips"])
    (home,) = jax.tree_util.tree_leaves(params)[0].devices()
    params, ring = prog.place(mesh, params, host_ring)
    readings, params, opt_state = first_steps(
        undonated(step), params, opt.init_state(params), ring,
        cfg["optimizer"]["beta1"])
    readings["grad"] = jax.device_put(readings["grad"], home)
    return readings, params, opt_state, step, ring


def correct_numbers(cell: dict, ref, seed: int, control=False) -> dict:
    """The numbers ``correct`` compares, with no window
    (benchmark/check_correct.py).  ``control``: ``False`` the program,
    ``True`` the reference with fp8 operands in its place, or the name of a
    fault of ``FAULT_ROWS`` planted in the reference in its place."""
    cfg, tr = cell["config"], global_traffic(cell)
    params = correct.init_params(ref, cfg, seed)
    host_ring = traffic.batches(ref, cfg, tr, seed, correct.STEPS)
    one_chip = [jax.device_put(b) for b in host_ring]
    block = tr["reference_rows_per_block"]
    expected = correct.reference_steps(ref, cfg, params, one_chip, block)
    if control is True:
        return correct.compare(correct.control_steps(
            ref, cfg, params, one_chip, block), expected)
    if control:
        rows = FAULT_ROWS[control](tr["batch"], cell["chips"])
        part = [correct._slice_rows(b, 0, rows) for b in one_chip]
        return correct.compare(correct.reference_steps(
            ref, cfg, params, part, block), expected)
    readings, params, *_ = _dp_steps(cell, cfg, params, host_ring)
    numbers = correct.compare(readings, expected)
    numbers["replica_param_gap"] = replica_param_gap(params)
    return numbers


def run(ctx) -> dict:
    cell = ctx.cell
    cfg, tr = cell["config"], global_traffic(cell)
    ref = ctx.reference
    params = correct.init_params(ref, cfg, ctx.seed)
    host_ring = traffic.batches(ref, cfg, tr, ctx.seed, tr["ring"])
    tokens_per_step = [ref.real_tokens(b) for b in host_ring]

    ctx.mark("weights_and_batches")
    with ctx.untimed("reference"):
        expected = correct.reference_steps(
            ref, cfg, params,
            [jax.device_put(b) for b in host_ring[:correct.STEPS]],
            tr["reference_rows_per_block"])
    ctx.mark("reference_done")
    ctx.note(memory_bytes_after_reference=ctx.memory_now())

    readings, *state, step, ring = _dp_steps(cell, cfg, params, host_ring)
    numbers = correct.compare(readings, expected)
    numbers["replica_param_gap"] = replica_param_gap(state[0])
    del readings, expected, params
    ctx.mark("first_steps_compared")
    every = tr["loss_fetch_every"]
    losses, done = [], {"steps": 0, "tokens": 0}

    def drive(expired):
        """The demo's loop: dispatch, and fetch every few steps' loss."""
        loss = None
        while not expired():
            at = done["steps"] % len(ring)
            with ctx.span("bench.dispatch_step"):
                loss, state[0], state[1] = step(state[0], state[1], ring[at])
            losses.append(loss)
            done["tokens"] += tokens_per_step[at]
            done["steps"] += 1
            if done["steps"] % every == 0:
                ctx.sample_memory()   # the most steps are in flight here
                with ctx.span("bench.loss_fetch"):
                    float(loss)
        return loss

    # warm-up through the window's own loop
    drive(lambda: done["steps"] >= tr["warmup_steps"])
    losses.clear()
    done.update(steps=0, tokens=0)
    with ctx.window() as w:
        loss = drive(w.expired)
        with ctx.span("bench.final_sync"):
            jax.block_until_ready((loss, state))
    steps, tokens = done["steps"], done["tokens"]
    numbers["nonfinite_losses"] = int(
        (~np.isfinite(np.asarray(jax.device_get(losses)))).sum())
    numbers["bad_steps"] = 0   # a bare step has no guard: nothing to skip
    return {"numbers": numbers, "attempted": steps,
            "failed": numbers["nonfinite_losses"],
            "metrics": {"train_tokens_per_s": tokens / ctx.window_s},
            "tokens": tokens, "steps": steps,
            "flops_per_step": ref.step_flops(cfg, tr)}
