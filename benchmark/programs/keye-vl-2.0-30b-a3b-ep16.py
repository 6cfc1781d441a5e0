"""The system under test for ``keye-vl-2.0-30b-a3b-ep16``: ``SGDTrainer`` as
``python -m paddle_tpu --job=train`` builds it (donated step, prefetch,
bad-step guard, ``--obs_timeline``, no ``save_dir``) around ``keye_vl2_net``,
built from the configuration file with the seeded weights in place of its
own; the expert layers' assignment counts and the attention layers' kept
pairs and indexer losses ride the step as extra outputs and feed the
registry's ``moe_assignments``, ``sparse_attn_kept_pairs`` and
``indexer_kl``.  The only file of this configuration that imports the
program."""

from __future__ import annotations

import functools
import os
import sys


def require() -> None:
    """Exit at once, before any weight is made or reference step run, where
    the checkout's program cannot build this configuration (the parent of
    the PR that added it: no ``keye_vl2_net``)."""
    try:
        from paddle_tpu.models import keye_vl2_net  # noqa: F401
    except ImportError as e:
        sys.exit(f"benchmark: this checkout's program cannot run "
                 f"keye-vl-2.0-30b-a3b-ep16 ({e})")


def net(cfg: dict):
    """``(cost, extras)`` of the configuration's model."""
    import paddle_tpu.nn as nn
    from paddle_tpu.models import keye_vl2_net

    nn.reset_naming()
    sa = cfg["sa_config"]
    return keye_vl2_net(
        cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        indexer_num_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"], topk=sa["topk"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["router_outputs"],
        experts_held=(cfg["first_expert"], cfg["num_experts"]),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
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
