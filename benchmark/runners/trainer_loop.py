"""Runner ``trainer_loop``: ``SGDTrainer.train`` as ``python -m paddle_tpu
--job=train`` builds it (``trainer(cfg, traffic, params)`` of the
configuration's program file: donated step, prefetch, bad-step guard,
``--obs_timeline``, no ``save_dir``) over an endless seeded reader that stops
at the deadline, so the window is one pass.  One trainer is built in set-up,
driven through its first three batches by ``train`` itself for ``correct``,
and handed to the window.  Step ends are taken in an ``EndIteration`` handler
of the benchmark's own."""

from __future__ import annotations

import jax
import numpy as np

from benchmark import correct, manifest, traffic


def first_steps(trainer, batches: list, beta1: float):
    """``trainer.train`` over the first ``correct.STEPS`` batches: the
    readings ``correct.compare`` takes."""
    from paddle_tpu.trainer import events as ev

    p0 = jax.tree_util.tree_map(lambda a: a + 0, trainer.params)  # a copy:
    readings = {"losses": []}                    # the step donates its state

    def handler(e):
        if not isinstance(e, ev.EndIteration):
            return
        readings["losses"].append(float(e.cost))
        if e.batch_id == 0:   # the gradient as Adam got it; a copy, since
            readings["grad"] = {      # the next step donates the slots
                k: s[0] + 0 for k, s in trainer.opt_state["slots"].items()}
            readings["grad_scale"] = 1.0 / (1.0 - beta1)

    trainer.train(lambda: iter(batches[:correct.STEPS]), num_passes=1,
                  event_handler=handler)
    readings["delta_norms"] = {
        k: float(v)
        for k, v in correct.delta_norms_jit(trainer.params, p0).items()}
    return readings


def correct_numbers(cell: dict, ref, seed: int, control=False) -> dict:
    """The numbers ``correct`` compares, with no window (see
    benchmark/check_correct.py)."""
    cfg, tr = cell["config"], cell["traffic"]
    params = correct.init_params(ref, cfg, seed)
    batches = traffic.batches(ref, cfg, tr, seed, correct.STEPS)
    on_device = [jax.device_put(b) for b in batches]
    expected = correct.reference_steps(ref, cfg, params, on_device,
                                       tr["reference_rows_per_block"])
    if control:
        return correct.compare(correct.control_steps(
            ref, cfg, params, on_device, tr["reference_rows_per_block"]),
            expected)
    trainer = manifest.program(cfg).trainer(cfg, tr, params)
    numbers = correct.compare(
        first_steps(trainer, batches, cfg["optimizer"]["beta1"]), expected)
    numbers["bad_steps"] = int(trainer.bad_steps_total)
    return numbers


def run(ctx) -> dict:
    from paddle_tpu.trainer import events as ev

    cfg, tr, ref = ctx.cell["config"], ctx.cell["traffic"], ctx.reference
    params = correct.init_params(ref, cfg, ctx.seed)
    ring = traffic.batches(ref, cfg, tr, ctx.seed, tr["ring"])
    tokens_of = [ref.real_tokens(b) for b in ring]

    ctx.mark("weights_and_batches")
    with ctx.untimed("reference"):
        expected = correct.reference_steps(
            ref, cfg, params,
            [jax.device_put(b) for b in ring[:correct.STEPS]],
            tr["reference_rows_per_block"])
    ctx.mark("reference_done")
    ctx.note(memory_bytes_after_reference=ctx.memory_now())

    trainer = manifest.program(cfg).trainer(cfg, tr, params)
    del params
    numbers = correct.compare(
        first_steps(trainer, ring, cfg["optimizer"]["beta1"]), expected)
    del expected   # holds the reference's first gradient
    ctx.mark("first_steps_compared")

    costs, done = [], {"steps": 0, "tokens": 0}

    def handler(e):
        if isinstance(e, ev.EndIteration):
            costs.append(e.cost)
            done["tokens"] += tokens_of[e.batch_id % len(ring)]
            done["steps"] += 1
            ctx.sample_memory()

    with ctx.window() as w:
        def reader():
            i = 0
            while not w.expired():
                yield ring[i % len(ring)]
                i += 1

        with ctx.span("bench.trainer_train"):
            trainer.train(reader, num_passes=1, event_handler=handler)
            jax.block_until_ready(trainer.params)
    timeline = (trainer.timeline.last_pass_summary or {}).get("phases")
    numbers["nonfinite_losses"] = int((~np.isfinite(costs)).sum())
    numbers["bad_steps"] = int(trainer.bad_steps_total)
    return {"numbers": numbers, "attempted": done["steps"],
            "failed": numbers["nonfinite_losses"] + numbers["bad_steps"],
            "metrics": {"train_tokens_per_s": done["tokens"] / ctx.window_s},
            "tokens": done["tokens"], "steps": done["steps"],
            "flops_per_step": ref.step_flops(cfg, tr),
            "facts": {"timeline": timeline}}
