"""Bad-step guard — skip non-finite optimizer updates inside the jitted step.

The mixed-precision-training discipline: one NaN/Inf loss or gradient must
not poison the parameters forever, so the finite checks run ON DEVICE
(``jnp.isfinite`` of the loss and of the gradient global-norm) and the
predicate goes down into the optimizer's own update, which holds each leaf
by a select where it computes the new value (``Optimizer.update``'s
``finite``): parameters, slots, the step count, layer state and a pserver
tier's tables come back bit for bit.  There is no ``lax.cond`` over the
state: a conditional takes and returns its operands in the default layout,
and the chip keeps some leaves otherwise, so each of those was copied seven
times a step (PERF.md, PR 48).  The price is that a skipped step pays a
whole update's time instead of none; skips are rare by construction
(``max_bad_steps`` aborts a run that strings them together).  Nothing here
crosses the host link — the trainer reads the skip
flag from the step's extras at the same cadence it already pulls the loss,
and ``analysis.audit_fn`` verifies the guarded step stays
host-transfer-free (tests/test_resilience.py gate).

The reference's analog was process-fatal FP traps
(``feenableexcept`` in TrainerMain.cpp) — correct for debugging, wrong for
a 10k-chip run where one flaky batch should cost one skipped step, not the
job.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

__all__ = ["global_grad_norm", "guarded_update", "init_loss_scale",
           "scaled_guarded_update"]


def global_grad_norm(grads) -> jnp.ndarray:
    """L2 norm over every gradient leaf, accumulated in f32 (bf16 squares
    overflow at ~256; the norm must be trustworthy or the finite check is
    theater)."""
    leaves = jax.tree_util.tree_leaves(grads)
    if not leaves:
        return jnp.zeros((), jnp.float32)
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in leaves))


def _hold(finite, new, old):
    """``new`` where the step is finite, ``old`` where not, leaf by leaf."""
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(finite, n, o), new, old)


def guarded_update(
    update_fn: Callable[[Any, Any, Any, Any], Tuple[Any, Any]],
    *,
    loss,
    grads,
    params,
    opt_state,
    new_state,
    old_state,
) -> Tuple[Any, Any, Any, Dict[str, jnp.ndarray]]:
    """Run ``update_fn(params, grads, opt_state, finite)`` with the step's
    finite predicate; where it is False the update holds params and
    optimizer slots unchanged (``Optimizer.update(finite=...)``, leaf by
    leaf), and layer state is held here the same way (a NaN forward also
    poisons BN running stats).

    Returns ``(new_params, new_opt_state, selected_state, extras)`` where
    extras carries device scalars: ``grad_norm`` and ``bad_step`` (1 when
    the update was skipped).  Pure and jit/pjit-safe; no conditional, so
    a skipped step runs the update's arithmetic and discards it.
    """
    gnorm = global_grad_norm(grads)
    finite = jnp.isfinite(loss) & jnp.isfinite(gnorm)
    # the update squares each gradient again (Adam's second moment), and
    # with no conditional between them XLA would share the norm's squares
    # with it: every g*g kept beside its g until the update runs
    grads = jax.lax.optimization_barrier(grads)
    new_params, new_opt = update_fn(params, grads, opt_state, finite)
    sel_state = _hold(finite, new_state, old_state)
    extras = {
        "grad_norm": gnorm,
        "bad_step": (~finite).astype(jnp.int32),
    }
    return new_params, new_opt, sel_state, extras


# ---------------------------------------------------------------------------
# dynamic loss scaling (--amp; docs/mixed_precision.md)
# ---------------------------------------------------------------------------


def init_loss_scale(scale: float, *,
                    growth_interval: int = 2000) -> Dict[str, Any]:
    """Fresh loss-scale state: the scale itself plus the consecutive-good-
    steps counter the growth schedule runs on.  Lives inside the trainer's
    ``opt_state['amp']`` so it is donated with the slots and rides
    checkpoints for free (a resumed ``--amp`` run continues the exact
    scale trajectory)."""
    del growth_interval  # static, read from flags at trace time
    return {"scale": jnp.asarray(float(scale), jnp.float32),
            "good_steps": jnp.zeros((), jnp.int32)}


def scaled_guarded_update(
    update_fn: Callable[[Any, Any, Any, Any], Tuple[Any, Any]],
    *,
    loss,
    scaled_grads,
    amp_state: Dict[str, Any],
    params,
    opt_state,
    new_state,
    old_state,
    growth_interval: int,
    max_scale: float,
    min_scale: float = 1.0,
) -> Tuple[Any, Any, Any, Dict[str, Any], Dict[str, jnp.ndarray]]:
    """The bad-step guard with dynamic loss scaling folded in — the
    mixed-precision state machine (Micikevicius et al.):

    - ``scaled_grads`` are d(scale * loss)/dp.  A finite step unscales
      them (f32 multiply by 1/scale) and applies ``update_fn``; the
      good-steps counter advances and, every ``growth_interval``
      consecutive finite steps, the scale DOUBLES (capped at
      ``max_scale``) to track the widest representable gradient range.
    - an overflow (non-finite scaled-grad norm, or a non-finite loss)
      skips the update — params, slots, and layer state held, exactly the
      plain guard's skip — and HALVES the scale (floored at
      ``min_scale``), so the next step retries in range instead of the
      process aborting.

    ``extras['bad_step']`` stays the abort signal and fires only when the
    LOSS itself is non-finite (a poisoned batch — same abort pressure as
    the unscaled guard); a pure gradient overflow is a normal
    loss-scaling event (``extras['amp_overflow']``) and must NOT count
    toward ``max_bad_steps``: a too-high initial scale legitimately takes
    several halvings to find range.  Pure and jit/pjit-safe.
    """
    scale = amp_state["scale"]
    gnorm_s = global_grad_norm(scaled_grads)
    loss_finite = jnp.isfinite(loss)
    finite = jnp.isfinite(gnorm_s) & loss_finite
    # unscale in f32; inv=0 on overflow zeroes what the update (run and
    # then discarded by its own select) is fed, wherever that was finite
    inv = jnp.where(finite, 1.0 / scale, 0.0)
    grads = jax.tree_util.tree_map(
        lambda g: (g.astype(jnp.float32) * inv).astype(g.dtype),
        scaled_grads)

    # (no barrier as in ``guarded_update``: the update squares the unscaled
    # gradients, other values than the norm squared)
    new_params, new_opt = update_fn(params, grads, opt_state, finite)
    sel_state = _hold(finite, new_state, old_state)

    good = jnp.where(finite, amp_state["good_steps"] + 1, 0)
    grow = (growth_interval > 0) & (good >= growth_interval)
    new_scale = jnp.where(
        finite,
        jnp.where(grow, jnp.minimum(scale * 2.0, max_scale), scale),
        jnp.maximum(scale * 0.5, min_scale))
    new_amp = {"scale": new_scale,
               "good_steps": jnp.where(grow, 0, good)}
    extras = {
        "grad_norm": jnp.where(finite, gnorm_s * inv, jnp.inf),
        "bad_step": (~loss_finite).astype(jnp.int32),
        "amp_overflow": (~finite).astype(jnp.int32),
        "loss_scale": new_scale,
    }
    return new_params, new_opt, sel_state, new_amp, extras
