"""The system under test for ``ouro-2.6b-loop4``: ``SGDTrainer`` as ``python
-m paddle_tpu --job=train`` builds it (donated step, prefetch, bad-step
guard, ``--obs_timeline``, no ``save_dir``) around ``ouro_net``, built from
the configuration file with the seeded weights in place of its own; each
exit's mass, each exit's cross-entropy and the entropy ride the step as extra
outputs and feed the registry's ``loop_exit_mass{step}``,
``loop_exit_ce{step}`` and ``loop_exit_entropy``.  The only file of this
configuration that imports the program."""

from __future__ import annotations

import sys


def require() -> None:
    """Exit at once, before any weight is made or reference step run, where
    the checkout's program cannot build this configuration (the parent of
    the PR that added it: no ``ouro_net``)."""
    try:
        from paddle_tpu.models import ouro_net  # noqa: F401
    except ImportError as e:
        sys.exit(f"benchmark: this checkout's program cannot run "
                 f"ouro-2.6b-loop4 ({e})")


def net(cfg: dict):
    """``(cost, extras)`` of the configuration's model."""
    import paddle_tpu.nn as nn
    from paddle_tpu.models import ouro_net

    nn.reset_naming()
    return ouro_net(
        cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=cfg["layer_types"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        total_ut_steps=cfg["total_ut_steps"], rope_theta=cfg["rope_theta"],
        rms_norm_eps=cfg["rms_norm_eps"], exit_beta=cfg["exit_beta"],
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


def expert_load(layers) -> dict:
    """The runner's hook for the routing counters: a stack with no expert
    layer has none."""
    return {}


def uncomputed_assignments() -> float:
    """Assignments to an expert held that no row was computed for: there is
    no expert."""
    return 0.0
