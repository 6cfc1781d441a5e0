"""The system under test for ``nemotron-3-nano-30b-a3b-ep16``: ``SGDTrainer``
as ``python -m paddle_tpu --job=train`` builds it (donated step, prefetch,
bad-step guard, ``--obs_timeline``, no ``save_dir``) around
``nemotron_h_net``, built from the configuration file with the seeded
weights in place of its own; the expert layers' assignment counts ride the
step as extra outputs and feed the registry's ``moe_assignments``.  The only
file of this configuration that imports the program."""

from __future__ import annotations

import functools
import os
import sys


def require() -> None:
    """Exit at once, before any weight is made or reference step run, where
    the checkout's program cannot build this configuration (the parent of
    the PR that added it: no ``nemotron_h_net``)."""
    try:
        from paddle_tpu.models import nemotron_h_net  # noqa: F401
    except ImportError as e:
        sys.exit(f"benchmark: this checkout's program cannot run "
                 f"nemotron-3-nano-30b-a3b-ep16 ({e})")


def net(cfg: dict):
    """``(cost, extras)`` of the configuration's model."""
    import paddle_tpu.nn as nn
    from paddle_tpu.models import nemotron_h_net

    nn.reset_naming()
    return nemotron_h_net(
        cfg["vocab_size"],
        hybrid_override_pattern=cfg["hybrid_override_pattern"],
        hidden_size=cfg["hidden_size"],
        mamba_num_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"], n_groups=cfg["n_groups"],
        ssm_state_size=cfg["ssm_state_size"], conv_kernel=cfg["conv_kernel"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=cfg[
            "moe_shared_expert_intermediate_size"],
        n_routed_experts=cfg["router_outputs"],
        experts_held=(cfg["first_expert"], cfg["n_routed_experts"]),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"],
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
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
    registry's counter ``moe_assignments`` (what the trainer has fed it).
    The runner asks for ``moe<i>`` of every layer; a layer here is one
    sub-block, and a name that is no expert layer's has no series in the
    registry: it is left out of the answer."""
    return _counters().expert_load(layers)


def uncomputed_assignments() -> float:
    """Assignments to an expert held that no row was computed for, so far."""
    return _counters().uncomputed_assignments()
