"""Runner ``trainer_loop_large``: what ``trainer_loop`` does (one trainer
built in set-up from ``trainer(cfg, traffic, params)`` of the configuration's
program file, its first three batches through ``SGDTrainer.train`` itself for
``correct``, the window one pass over an endless seeded reader) for a
configuration whose state is most of the chip: 7.5 GB of parameters,
gradients and Adam slots where ``trainer_loop`` and ``correct.reference_steps``
were written for 0.1-0.6 GB and keep nine and more copies of the parameters.

What differs, and only that:

- the reference loop donates its state to its Adam step and holds ``p, m, v``
  and one gradient; its first gradient waits on the HOST; the starting
  parameters are made again from the seed when the change of the parameters
  is measured, and are never kept;
- the first gradient is compared leaf by leaf inside the trainer's first
  ``EndIteration`` (Adam's first moment over ``1 - beta1``, read before the
  next step donates it), so no copy of the gradient or of the parameters is
  made on the device;
- one more number at limit 0: ``uncomputed_assignments``, assignments to an
  expert held for which the program computed no row (the program's counter).

The numbers compared keep ``correct.py``'s names and meanings; ``correct.py``
still makes the weights, judges, and lends ``leaf_gaps`` and the fp8 control's
product.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import correct, manifest, traffic


@jax.jit
def _leaf_norms(got, want, scale):
    """(|want|, |got * scale - want|) of one leaf, float32."""
    want = want.astype(jnp.float32)
    diff = got.astype(jnp.float32) * scale - want
    return jnp.sqrt(jnp.sum(jnp.square(want))), jnp.sqrt(
        jnp.sum(jnp.square(diff)))


def reference_steps(ref, cfg: dict, seed: int, batches: list) -> dict:
    """The plain reference through its first ``correct.STEPS`` steps from the
    seed's weights: float32 at ``highest`` matmul precision, Adam written
    out and donating.  Returns the losses, the first gradient ON THE HOST
    and the norms of the parameters' change."""
    opt = cfg["optimizer"]
    b1, b2, eps, lr = (opt["beta1"], opt["beta2"], opt["epsilon"],
                       opt["learning_rate"])

    def adam_step(p, g, m, v, t, count):
        out_p, out_m, out_v = {}, {}, {}
        for k in p:
            gk = g[k] / count
            out_m[k] = b1 * m[k] + (1 - b1) * gk
            out_v[k] = b2 * v[k] + (1 - b2) * jnp.square(gk)
            mhat = out_m[k] / (1 - b1 ** t)
            vhat = out_v[k] / (1 - b2 ** t)
            out_p[k] = p[k] - lr * mhat / (jnp.sqrt(vhat) + eps)
        return out_p, out_m, out_v

    with jax.default_matmul_precision("highest"):
        grad = jax.jit(jax.value_and_grad(
            lambda p, batch: ref.loss_sum(cfg, p, batch), has_aux=True))
        adam = jax.jit(adam_step, donate_argnums=(0, 2, 3))
        zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
        p = correct.init_params(ref, cfg, seed)
        m, v = zeros(p), zeros(p)
        out = {"losses": []}
        for t in range(1, correct.STEPS + 1):
            (total, count), g = grad(p, batches[t - 1])
            out["losses"].append(float(total / count))
            if t == 1:
                n = float(count)
                out["grad"] = {k: np.asarray(x) / n for k, x in g.items()}
            p, m, v = adam(p, g, m, v, jnp.float32(t), count)
        del m, v, g
        p0 = correct.init_params(ref, cfg, seed)
        out["delta_norms"] = {k: float(x) for k, x in
                              correct.delta_norms_jit(p, p0).items()}
    return out


def grad_norms(got: dict, want: dict, scale: float) -> dict:
    """leaf -> (|want|, |got * scale - want|), one leaf on the device at a
    time where ``want`` (or ``got``) waits on the host."""
    out = {k: _leaf_norms(got[k], want[k], jnp.float32(scale))
           for k in sorted(want)}
    return {k: (float(a), float(b)) for k, (a, b) in out.items()}


def compare(readings: dict, expected: dict) -> dict:
    """``correct.compare``'s numbers from per-leaf norms: ``readings`` has
    ``losses``, ``grad_norms`` (of :func:`grad_norms` against the
    reference's first gradient) and ``delta_norms``."""
    numbers = {"loss_gap": max(
        (abs(a - b) / abs(b) if np.isfinite(a) else float("inf"))
        for a, b in zip(readings["losses"], expected["losses"]))}
    norms = readings["grad_norms"]
    floor = float(np.median([want for want, _ in norms.values()]))
    diffs = []
    for k, (want, diff) in sorted(norms.items()):
        d = diff / max(want, floor, 1e-30)
        diffs.append(d if np.isfinite(d) else float("inf"))
        numbers["grad_diff." + k] = diffs[-1]
    numbers["grad_diff_median"] = float(np.median(diffs))
    numbers["delta_norm_gap"] = max(correct.leaf_gaps(
        readings["delta_norms"], expected["delta_norms"]).values())
    return numbers


def control_steps(ref, cfg, seed, batches, expected) -> dict:
    """The control's readings: the reference in the program's place with fp8
    operands, the precision below the bf16 the configuration states."""
    plain = ref.mm
    ref.mm = correct.fp8_mm
    try:
        got = reference_steps(ref, cfg, seed, batches)
    finally:
        ref.mm = plain
    got["grad_norms"] = grad_norms(got.pop("grad"), expected["grad"], 1.0)
    return got


def first_steps(trainer, ref, cfg, seed, batches: list, expected: dict):
    """``trainer.train`` over the first ``correct.STEPS`` batches: the
    readings :func:`compare` takes."""
    from paddle_tpu.trainer import events as ev

    readings = {"losses": []}
    scale = 1.0 / (1.0 - cfg["optimizer"]["beta1"])
    pending = {}

    def handler(e):
        if not isinstance(e, ev.EndIteration):
            return
        readings["losses"].append(float(e.cost))
        if e.batch_id == 0:   # the gradient as Adam got it, read leaf by
            slots = trainer.opt_state["slots"]   # leaf before the next
            for k in sorted(expected["grad"]):   # step donates the slots
                pending[k] = _leaf_norms(slots[k][0], expected["grad"][k],
                                         jnp.float32(scale))

    trainer.train(lambda: iter(batches[:correct.STEPS]), num_passes=1,
                  event_handler=handler)
    readings["grad_norms"] = {k: (float(a), float(b))
                              for k, (a, b) in pending.items()}
    p0 = correct.init_params(ref, cfg, seed)
    readings["delta_norms"] = {
        k: float(v)
        for k, v in correct.delta_norms_jit(trainer.params, p0).items()}
    return readings


def _program_numbers(program, trainer) -> dict:
    return {"bad_steps": int(trainer.bad_steps_total),
            "uncomputed_assignments": float(program.uncomputed_assignments())}


def correct_numbers(cell: dict, ref, seed: int, control=False) -> dict:
    """The numbers ``correct`` compares, with no window (see
    benchmark/check_correct.py)."""
    cfg, tr = cell["config"], cell["traffic"]
    program = manifest.program(cfg)
    program.require()
    batches = traffic.batches(ref, cfg, tr, seed, correct.STEPS)
    expected = reference_steps(ref, cfg, seed,
                               [jax.device_put(b) for b in batches])
    if control:
        return compare(control_steps(
            ref, cfg, seed, [jax.device_put(b) for b in batches], expected),
            expected)
    trainer = program.trainer(cfg, tr, correct.init_params(ref, cfg, seed))
    numbers = compare(first_steps(trainer, ref, cfg, seed, batches, expected),
                      expected)
    numbers.update(_program_numbers(program, trainer))
    return numbers


def run(ctx) -> dict:
    from paddle_tpu.trainer import events as ev

    cfg, tr, ref = ctx.cell["config"], ctx.cell["traffic"], ctx.reference
    program = manifest.program(cfg)
    program.require()    # a checkout without the model fails here, at once
    ring = traffic.batches(ref, cfg, tr, ctx.seed, tr["ring"])
    tokens_of = [ref.real_tokens(b) for b in ring]

    ctx.mark("batches")
    with ctx.untimed("reference"):
        expected = reference_steps(
            ref, cfg, ctx.seed,
            [jax.device_put(b) for b in ring[:correct.STEPS]])
    ctx.mark("reference_done")
    ctx.note(memory_bytes_after_reference=ctx.memory_now())

    trainer = program.trainer(cfg, tr,
                              correct.init_params(ref, cfg, ctx.seed))
    ctx.mark("trainer_built")
    numbers = compare(first_steps(trainer, ref, cfg, ctx.seed, ring,
                                  expected), expected)
    del expected   # holds the reference's first gradient, on the host
    ctx.mark("first_steps_compared")

    moe_layers = [f"moe{i}" for i in range(len(cfg["layer_types"]))
                  if i >= cfg["num_dense_layers"]]
    load_before = program.expert_load(moe_layers)
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
    load = {layer: [after - before for after, before in
                    zip(counts, load_before.get(layer, [0] * len(counts)))]
            for layer, counts in program.expert_load(moe_layers).items()}
    timeline = (trainer.timeline.last_pass_summary or {}).get("phases")
    numbers["nonfinite_losses"] = int((~np.isfinite(costs)).sum())
    numbers.update(_program_numbers(program, trainer))
    return {"numbers": numbers, "attempted": done["steps"],
            "failed": numbers["nonfinite_losses"] + numbers["bad_steps"],
            "metrics": {"train_tokens_per_s": done["tokens"] / ctx.window_s},
            "tokens": done["tokens"], "steps": done["steps"],
            "flops_per_step": ref.step_flops(cfg, tr),
            "expert_load": load,
            "facts": {"timeline": timeline, "expert_load": load,
                      "config": cfg, "traffic": tr}}
