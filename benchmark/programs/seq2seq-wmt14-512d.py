"""The system under test for ``seq2seq-wmt14-512d``: the program's own
model, optimizer and the demo's training step, built from the configuration
file.  The only file of this configuration that imports the program."""

from __future__ import annotations

import os

from benchmark.manifest import ROOT, load_module


def optimizer(cfg: dict):
    from paddle_tpu.param.optimizers import Adam

    o = cfg["optimizer"]
    return Adam(learning_rate=o["learning_rate"], beta1=o["beta1"],
                beta2=o["beta2"], epsilon=o["epsilon"])


def train_step(cfg: dict):
    """``(step, optimizer)``: demo/seqToseq/train.py's jitted
    ``step(params, opt_state, batch) -> (loss, params, opt_state)``."""
    from paddle_tpu.models import Seq2SeqAttention

    model = Seq2SeqAttention(
        src_vocab=cfg["src_vocab"], trg_vocab=cfg["trg_vocab"],
        emb_dim=cfg["emb_dim"], enc_dim=cfg["enc_dim"],
        dec_dim=cfg["dec_dim"], att_dim=cfg["att_dim"])
    opt = optimizer(cfg)
    demo = load_module(os.path.join(ROOT, "demo", "seqToseq", "train.py"),
                       "bench_seqToseq_demo")
    return demo.make_train_step(model, opt), opt
