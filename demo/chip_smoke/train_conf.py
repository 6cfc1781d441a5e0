"""Trainer config of ``chip_smoke.py``'s trainer-lifecycle phase: the
benchmark LSTM text classifier (benchmark/paddle/rnn/rnn.py — 30k vocab, two
stacked LSTM layers, T=100, B=64; hidden 512 is one of its published rows)
over a few seeded random batches, so ``python -m paddle_tpu --job=train
--config=demo/chip_smoke/train_conf.py`` drives ``SGDTrainer``'s donated
step, prefetch and bad-step guard with nothing but this checkout."""

import numpy as np

import paddle_tpu.nn as nn
from paddle_tpu.models import lstm_benchmark_net
from paddle_tpu.param.optimizers import Adam


def make_config(*, vocab=30000, hidden=512, layers=2, seq_len=100, batch=64,
                batches=6, seed=0):
    nn.reset_naming()
    cost, _ = lstm_benchmark_net(vocab, hid_dim=hidden, num_layers=layers)

    def reader():
        rng = np.random.RandomState(seed)
        for _ in range(batches):
            yield {
                "words": (rng.randint(3, vocab, (batch, seq_len))
                          .astype(np.int32),
                          rng.randint(seq_len // 2, seq_len + 1, batch)
                          .astype(np.int32)),
                "label": rng.randint(0, 2, (batch, 1)).astype(np.int32),
            }

    return {"cost": cost, "optimizer": Adam(learning_rate=1e-3),
            "reader": reader}


def get_config():
    return make_config()
