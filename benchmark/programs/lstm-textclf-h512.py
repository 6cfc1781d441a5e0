"""The system under test for ``lstm-textclf-h512``: the trainer that
``python -m paddle_tpu --job=train`` builds for the benchmark LSTM net
(donated step, prefetch, bad-step guard, ``--obs_timeline``, no
``save_dir``), built from the configuration file with the seeded weights in
place of its own.  The only file of this configuration that imports the
program."""

from __future__ import annotations


def trainer(cfg: dict, traffic: dict, params: dict):
    import paddle_tpu.nn as nn
    from paddle_tpu.models import lstm_benchmark_net
    from paddle_tpu.param.optimizers import Adam
    from paddle_tpu.trainer import SGDTrainer
    from paddle_tpu.utils.flags import FLAGS

    FLAGS.prefetch_depth = traffic["prefetch_depth"]
    FLAGS.guard_nonfinite = True
    FLAGS.obs_timeline = True
    FLAGS.save_dir = ""
    FLAGS.log_period = 10 ** 9
    nn.reset_naming()
    cost, _ = lstm_benchmark_net(
        cfg["vocab"], emb_dim=cfg["emb_dim"], hid_dim=cfg["hid_dim"],
        num_layers=cfg["num_layers"], num_classes=cfg["num_classes"])
    o = cfg["optimizer"]
    built = SGDTrainer(cost, Adam(
        learning_rate=o["learning_rate"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"]))
    have = {k: (tuple(v.shape), str(v.dtype))
            for k, v in built.params.items()}
    want = {k: (tuple(v.shape), str(v.dtype)) for k, v in params.items()}
    if have != want:
        raise RuntimeError(f"the reference's parameters {want} are not the "
                           f"program's {have}")
    built.params = {k: params[k] for k in built.params}
    return built
