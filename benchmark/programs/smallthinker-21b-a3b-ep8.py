"""The system under test for ``smallthinker-21b-a3b-ep8``: ``SGDTrainer`` as
``python -m paddle_tpu --job=train`` builds it (donated step, prefetch,
bad-step guard, ``--obs_timeline``, no ``save_dir``) around
``smallthinker_net``, built from the configuration file with the seeded
weights in place of its own; the expert layers' assignment counts and
ReLU-zeroed hidden units and the window layers' pairs ride the step as extra
outputs and feed the registry's ``moe_assignments``,
``moe_uncomputed_assignments``, ``moe_gate_zero_units`` and
``window_attn_pairs``.  The only file of this configuration that imports the
program."""

from __future__ import annotations

import functools
import os
import sys


def require() -> None:
    """Exit at once, before any weight is made or reference step run, where
    the checkout's program cannot build this configuration (the parent of
    the PR that added it: no ``smallthinker_net``)."""
    try:
        from paddle_tpu.models import smallthinker_net  # noqa: F401
    except ImportError as e:
        sys.exit(f"benchmark: this checkout's program cannot run "
                 f"smallthinker-21b-a3b-ep8 ({e})")


def net(cfg: dict):
    """``(cost, extras)`` of the configuration's model."""
    import paddle_tpu.nn as nn
    from paddle_tpu.models import smallthinker_net

    nn.reset_naming()
    return smallthinker_net(
        cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        sliding_window_layout=cfg["sliding_window_layout"],
        rope_layout=cfg["rope_layout"],
        sliding_window_size=cfg["sliding_window_size"],
        rope_theta=cfg["rope_theta"],
        moe_ffn_hidden_size=cfg["moe_ffn_hidden_size"],
        moe_num_primary_experts=cfg["router_outputs"],
        moe_num_active_primary_experts=cfg[
            "moe_num_active_primary_experts"],
        rms_norm_eps=cfg["rms_norm_eps"],
        experts_held=(cfg["first_expert"], cfg["moe_num_primary_experts"]),
        recompute_layers=cfg["recompute_layers"])


def trainer(cfg: dict, traffic: dict, params: dict):
    from paddle_tpu.param.optimizers import Adam
    from paddle_tpu.trainer import SGDTrainer
    from paddle_tpu.utils.flags import FLAGS

    FLAGS.prefetch_depth = traffic["prefetch_depth"]
    FLAGS.guard_nonfinite = True
    FLAGS.obs_timeline = True
    FLAGS.save_dir = ""
    FLAGS.log_period = 10 ** 9
    cost, extras = net(cfg)
    o = cfg["optimizer"]
    built = SGDTrainer(cost, Adam(
        learning_rate=o["learning_rate"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"]), extra_outputs=extras)
    have = {k: (tuple(v.shape), str(v.dtype))
            for k, v in built.params.items()}
    want = {k: (tuple(v.shape), str(v.dtype)) for k, v in params.items()}
    if have != want:
        raise RuntimeError(f"the reference's parameters {want} are not the "
                           f"program's {have}")
    built.params = {k: params[k] for k in built.params}
    return built


@functools.lru_cache(maxsize=None)
def _counters():
    """The two readers of the routing counters, which are the registry's and
    not a model's: LFM2's program file has them."""
    from benchmark import manifest

    return manifest.load_module(os.path.join(
        manifest.BENCH, "programs", "lfm2-24b-a2b-ep8.py"), "bench_counters")


def expert_load(layers) -> dict:
    """``{layer: [assignments of each expert held so far]}`` from the
    registry's counter ``moe_assignments`` (what the trainer has fed it)."""
    return _counters().expert_load(layers)


def uncomputed_assignments() -> float:
    """Assignments to an expert held that no row was computed for, so far."""
    return _counters().uncomputed_assignments()
