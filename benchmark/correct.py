"""What decides ``correct`` for the training cells: seeded weights, the plain
reference's first three steps (loss, gradients, Adam) in blocks of rows, and
the comparison of the timed path's own first three steps with them.

Numbers compared, each against a limit of its own (kept beside the cell's
traffic file; the readings each was set from are in PERF.md).  Every leaf's
gradient is held, whether or not the control moves it:

``loss_gap``         widest |loss - reference| / reference over the steps
``grad_diff.<leaf>`` |g - g_ref| of one parameter leaf over |g_ref| of that
                     leaf or of the median leaf, whichever is larger; g is
                     the first gradient as Adam got it, ``m1 / (1 - beta1)``.
                     The NORM OF THE DIFFERENCE, not the gap between norms:
                     on the chip the gap between norms read the same for the
                     program, for ``FLAGS.amp`` and for fp8 operands
                     (rounding noise adds to a norm in quadrature), so it
                     separated nothing (PERF.md, PR 23)
``grad_diff_median`` the median of those over the leaves
``delta_norm_gap``   worst leaf: | |dp| - |dp_ref| | over max(|dp_ref| of the
                     leaf, of the median leaf), dp the parameters' change
                     after three steps; there to catch a step that returns
                     its state unchanged
``nonfinite_losses``, ``bad_steps``, ``compiles_in_window``: counts, limit 0
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

STEPS = 3


def init_params(ref, cfg: dict, seed: int) -> dict:
    """Every weight on the device from the seed in ONE jitted call, float32."""
    shapes = ref.param_shapes(cfg)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, (shape, std)) in enumerate(sorted(shapes.items())):
            if std is None:   # Glorot, as the program's own init scales
                std = (2.0 / (shape[0] + shape[-1])) ** 0.5
            out[name] = std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
        return out

    return make(jax.random.PRNGKey(int(seed)))


def leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def delta_norms_jit(p_now, p0):
    return leaf_norms({k: p_now[k] - p0[k] for k in p0})


norms_jit = jax.jit(leaf_norms)


@jax.jit
def _diff_norms(got, want, scale):
    return leaf_norms({k: got[k] * scale - want[k] for k in want})


def fp8_mm(a, b):
    """The control's matrix multiplication: both operands rounded to
    float8 (e4m3, scaled per tensor to its range, as fp8 training does);
    the rounding is differentiated straight through."""
    def q(x):
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype)
        return x + jax.lax.stop_gradient(rounded * scale - x)

    return jnp.matmul(q(a), q(b))


def control_steps(ref, cfg, params, batches, rows_per_block) -> dict:
    """The control: the reference put in the program's place, computed in
    the nearest precision below the bf16 operands the configuration states
    (fp8 operands).  Its readings go through ``compare`` like the
    program's."""
    plain = ref.mm
    ref.mm = fp8_mm
    try:
        return reference_steps(ref, cfg, params, batches, rows_per_block)
    finally:
        ref.mm = plain


def _slice_rows(batch, lo, hi):
    return jax.tree_util.tree_map(lambda a: a[lo:hi], batch)


def reference_steps(ref, cfg: dict, params: dict, batches: list,
                    rows_per_block: int) -> dict:
    """The plain reference through its first ``STEPS`` steps from ``params``:
    float32 at ``highest`` matmul precision, gradients added up over blocks
    of rows so that the logits of a block, not of the batch, are held; Adam
    written out.  Returns floats, and the first gradient on the device."""
    opt = cfg["optimizer"]
    b1, b2, eps, lr = opt["beta1"], opt["beta2"], opt["epsilon"], \
        opt["learning_rate"]

    with jax.default_matmul_precision("highest"):
        grad_block = jax.jit(jax.value_and_grad(
            lambda p, block: ref.loss_sum(cfg, p, block), has_aux=True))

        @jax.jit
        def adam(p, g, m, v, t):
            out_p, out_m, out_v = {}, {}, {}
            for k in p:
                out_m[k] = b1 * m[k] + (1 - b1) * g[k]
                out_v[k] = b2 * v[k] + (1 - b2) * jnp.square(g[k])
                mhat = out_m[k] / (1 - b1 ** t)
                vhat = out_v[k] / (1 - b2 ** t)
                out_p[k] = p[k] - lr * mhat / (jnp.sqrt(vhat) + eps)
            return out_p, out_m, out_v

        add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
        scale = jax.jit(lambda a, c: jax.tree_util.tree_map(
            lambda x: x / c, a))
        zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))

        p, p0 = params, params
        m, v = zeros(params), zeros(params)
        out = {"losses": []}
        for t in range(1, STEPS + 1):
            batch = batches[t - 1]
            rows = jax.tree_util.tree_leaves(batch)[0].shape[0]
            g, total, count = None, 0.0, 0.0
            for lo in range(0, rows, rows_per_block):
                (s, n), gb = grad_block(p, _slice_rows(
                    batch, lo, min(rows, lo + rows_per_block)))
                g = gb if g is None else add(g, gb)
                total, count = total + s, count + n
            g = scale(g, count)
            out["losses"].append(float(total / count))
            if t == 1:
                out["grad"] = g
            p, m, v = adam(p, g, m, v, jnp.float32(t))
        out["delta_norms"] = {k: float(x) for k, x in
                              delta_norms_jit(p, p0).items()}
    return out


def leaf_gaps(got: dict, want: dict) -> dict:
    """leaf -> the gap between the two norms of a leaf, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some leaves hardly move)."""
    floor = float(np.median(list(want.values())))
    gaps = {}
    for k, w in want.items():
        gap = abs(float(got[k]) - w) / max(w, floor, 1e-30)
        gaps[k] = gap if np.isfinite(gap) else float("inf")
    return gaps


def compare(readings: dict, expected: dict) -> dict:
    """The numbers compared.  ``readings``: ``losses`` (floats), ``grad``
    (the first gradient, a tree on the device, times ``grad_scale``) and
    ``delta_norms`` (floats), of the timed path or of the control;
    ``expected``: what ``reference_steps`` returned."""
    numbers = {"loss_gap": max(
        (abs(a - b) / abs(b) if np.isfinite(a) else float("inf"))
        for a, b in zip(readings["losses"], expected["losses"]))}
    want = {k: float(v) for k, v in norms_jit(expected["grad"]).items()}
    diff = _diff_norms(readings["grad"], expected["grad"],
                       jnp.float32(readings.get("grad_scale", 1.0)))
    floor = float(np.median(list(want.values())))
    diffs = []
    for k, d in sorted(diff.items()):
        d = float(d) / max(want[k], floor, 1e-30)
        diffs.append(d if np.isfinite(d) else float("inf"))
        numbers["grad_diff." + k] = diffs[-1]
    numbers["grad_diff_median"] = float(np.median(diffs))
    gaps = leaf_gaps(readings["delta_norms"], expected["delta_norms"])
    numbers["delta_norm_gap"] = max(gaps.values())
    return numbers


def judge(numbers: dict, limits: dict) -> bool:
    """Print each number compared beside its limit; True when every number
    that has a limit is inside it."""
    ok = True
    rows = {}
    for name, limit in sorted(limits.items()):
        if name not in numbers:
            ok = False
            rows[name] = {"value": None, "limit": limit, "ok": False}
            continue
        value = numbers[name]
        inside = bool(np.isfinite(value) and value <= limit)
        rows[name] = {"value": value, "limit": limit, "ok": inside}
        ok = ok and inside
    print(json.dumps({"compared": rows}), flush=True)
    return ok
