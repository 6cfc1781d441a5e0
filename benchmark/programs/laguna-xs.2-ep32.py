"""The system under test for ``laguna-xs.2-ep32``: ``SGDTrainer`` as ``python
-m paddle_tpu --job=train`` builds it (donated step, prefetch, bad-step
guard, ``--obs_timeline``, no ``save_dir``) around ``laguna_net``, built from
the configuration file with the seeded weights in place of its own; the
expert layers' assignment counts and the window layers' pairs ride the step
as extra outputs and feed the registry's ``moe_assignments``,
``moe_uncomputed_assignments`` and ``window_attn_pairs``.  The only file of
this configuration that imports the program."""

from __future__ import annotations

import functools
import os
import sys


def require() -> None:
    """Exit at once, before any weight is made or reference step run, where
    the checkout's program cannot build this configuration (the parent of
    the PR that added it: no ``laguna_net``)."""
    try:
        from paddle_tpu.models import laguna_net  # noqa: F401
    except ImportError as e:
        sys.exit(f"benchmark: this checkout's program cannot run "
                 f"laguna-xs.2-ep32 ({e})")


def net(cfg: dict):
    """``(cost, extras)`` of the configuration's model."""
    import paddle_tpu.nn as nn
    from paddle_tpu.models import laguna_net

    nn.reset_naming()
    return laguna_net(
        cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=cfg["layer_types"],
        mlp_layer_types=cfg["mlp_layer_types"],
        num_attention_heads_per_layer=cfg["num_attention_heads_per_layer"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], sliding_window=cfg["sliding_window"],
        rope_parameters=cfg["rope_parameters"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg[
            "shared_expert_intermediate_size"],
        num_experts=cfg["router_outputs"],
        experts_held=(cfg["first_expert"], cfg["num_experts"]),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_routed_scaling_factor=cfg["moe_routed_scaling_factor"],
        gating=cfg["gating"], rms_norm_eps=cfg["rms_norm_eps"],
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
