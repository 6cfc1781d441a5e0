"""Runner ``train_step``: a jitted training step, ``train_step(cfg) ->
(step, optimizer)`` of the configuration's program file, over a ring of
seeded batches resident on the device, the loss fetched every few steps as
demo/seqToseq/train.py's loop does.  One object (the jitted step with its state)
is built in set-up, driven through its first three steps for ``correct``,
warmed, and handed to the window."""

from __future__ import annotations

import jax
import numpy as np

from benchmark import correct, manifest, traffic


def first_steps(step, params, opt_state, ring, beta1: float):
    """The step's own first ``correct.STEPS`` calls on the ring's first
    batches; the readings ``correct.compare`` takes, and the state after."""
    p0 = params
    readings = {"losses": []}
    for i in range(correct.STEPS):
        loss, params, opt_state = step(params, opt_state, ring[i])
        readings["losses"].append(float(loss))
        if i == 0:   # the gradient as Adam got it: m1 = (1 - beta1) * g
            readings["grad"] = {k: s[0] for k, s in
                                opt_state["slots"].items()}
            readings["grad_scale"] = 1.0 / (1.0 - beta1)
    readings["delta_norms"] = {
        k: float(v) for k, v in correct.delta_norms_jit(params, p0).items()}
    return readings, params, opt_state


def correct_numbers(cell: dict, ref, seed: int, control=False) -> dict:
    """The numbers ``correct`` compares, with no window: what
    benchmark/check_correct.py reads over seeds, for the program as
    configured and for its lower-precision control."""
    cfg, tr = cell["config"], cell["traffic"]
    params = correct.init_params(ref, cfg, seed)
    ring = [jax.device_put(b)
            for b in traffic.batches(ref, cfg, tr, seed, correct.STEPS)]
    expected = correct.reference_steps(ref, cfg, params, ring,
                                       tr["reference_rows_per_block"])
    if control:
        return correct.compare(correct.control_steps(
            ref, cfg, params, ring, tr["reference_rows_per_block"]), expected)
    step, opt = manifest.program(cfg).train_step(cfg)
    readings, _, _ = first_steps(step, params, opt.init_state(params), ring,
                                 cfg["optimizer"]["beta1"])
    return correct.compare(readings, expected)


def run(ctx) -> dict:
    cfg, tr = ctx.cell["config"], ctx.cell["traffic"]
    ref = ctx.reference
    params = correct.init_params(ref, cfg, ctx.seed)
    host_ring = traffic.batches(ref, cfg, tr, ctx.seed, tr["ring"])
    ring = [jax.device_put(b) for b in host_ring]
    tokens_per_step = [ref.real_tokens(b) for b in host_ring]

    ctx.mark("weights_and_batches")
    with ctx.untimed("reference"):
        expected = correct.reference_steps(
            ref, cfg, params, ring[:correct.STEPS],
            tr["reference_rows_per_block"])
    ctx.mark("reference_done")
    ctx.note(memory_bytes_after_reference=ctx.memory_now())

    step, opt = manifest.program(cfg).train_step(cfg)
    readings, *state = first_steps(step, params, opt.init_state(params), ring,
                                   cfg["optimizer"]["beta1"])
    numbers = correct.compare(readings, expected)
    # the step is not donated: drop every other hold on a state (0.64 GB
    # each), so that the chip holds what the demo's loop would
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
