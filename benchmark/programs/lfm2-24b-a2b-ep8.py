"""The system under test for ``lfm2-24b-a2b-ep8``: ``SGDTrainer`` as ``python
-m paddle_tpu --job=train`` builds it (donated step, prefetch, bad-step
guard, ``--obs_timeline``, no ``save_dir``) around ``lfm2_moe_net``, built
from the configuration file with the seeded weights in place of its own; the
expert layers' assignment counts ride the step as extra outputs and feed the
registry's ``moe_assignments``.  The only file of this configuration that
imports the program."""

from __future__ import annotations

import sys


def require() -> None:
    """Exit at once, before any weight is made or reference step run, where
    the checkout's program cannot build this configuration (the parent of
    the PR that added it: no ``lfm2_moe_net``)."""
    try:
        from paddle_tpu.models import lfm2_moe_net  # noqa: F401
    except ImportError as e:
        sys.exit(f"benchmark: this checkout's program cannot run "
                 f"lfm2-24b-a2b-ep8 ({e})")


def trainer(cfg: dict, traffic: dict, params: dict):
    import paddle_tpu.nn as nn
    from paddle_tpu.models import lfm2_moe_net
    from paddle_tpu.param.optimizers import Adam
    from paddle_tpu.trainer import SGDTrainer
    from paddle_tpu.utils.flags import FLAGS

    FLAGS.prefetch_depth = traffic["prefetch_depth"]
    FLAGS.guard_nonfinite = True
    FLAGS.obs_timeline = True
    FLAGS.save_dir = ""
    FLAGS.log_period = 10 ** 9
    nn.reset_naming()
    cost, extras = lfm2_moe_net(
        cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=cfg["layer_types"],
        num_dense_layers=cfg["num_dense_layers"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["router_outputs"],
        experts_held=(cfg["first_expert"], cfg["num_experts"]),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], conv_kernel=cfg["conv_L_cache"],
        norm_eps=cfg["norm_eps"],
        rope_theta=cfg["rope_parameters"]["rope_theta"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        recompute_layers=cfg["recompute_layers"])
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


def expert_load(layers) -> dict:
    """``{layer: [assignments of each expert held so far]}`` from the
    registry's counter ``moe_assignments`` (what the trainer has fed it)."""
    from paddle_tpu.obs import get_registry

    series = get_registry().snapshot().get("moe_assignments", {}).get(
        "series", [])
    out = {}
    for s in series:
        lab = s["labels"]
        if lab.get("layer") in layers:
            out.setdefault(lab["layer"], {})[int(lab["expert"])] = s["value"]
    return {k: [v[e] for e in sorted(v)] for k, v in out.items()}


def uncomputed_assignments() -> float:
    """Assignments to an expert held that no row was computed for, so far."""
    from paddle_tpu.obs import get_registry

    series = get_registry().snapshot().get(
        "moe_uncomputed_assignments", {}).get("series", [])
    return float(sum(s["value"] for s in series))
