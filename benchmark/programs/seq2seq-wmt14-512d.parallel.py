"""The system under test for ``seq2seq-wmt14-512d`` across chips: the
program's own data-parallel step, ``parallel.make_parallel_train_step`` with
its defaults (pure data parallel, ``rules=None``, ``donate=True``: what a
user gets), over a one-axis mesh of the cell's chips.  A file of its own
beside ``seq2seq-wmt14-512d.py`` because a cell that adds files leaves the
files of the cells that exist as they are; the cell's traffic file names it
under ``program``."""

from __future__ import annotations

from benchmark import manifest


def parallel_train_step(cfg: dict, chips: int):
    """``(step, optimizer, mesh)``: the jitted SPMD ``step(params,
    opt_state, batch) -> (loss, params, opt_state)`` over ``chips`` devices
    on the axis ``data``.  Model and optimizer as the one-chip program file
    builds them."""
    import jax

    from paddle_tpu import parallel
    from paddle_tpu.models import Seq2SeqAttention

    model = Seq2SeqAttention(
        src_vocab=cfg["src_vocab"], trg_vocab=cfg["trg_vocab"],
        emb_dim=cfg["emb_dim"], enc_dim=cfg["enc_dim"],
        dec_dim=cfg["dec_dim"], att_dim=cfg["att_dim"])
    opt = manifest.program(cfg).optimizer(cfg)
    mesh = parallel.MeshConfig.of(data=chips).build(jax.devices()[:chips])
    return parallel.make_parallel_train_step(model.loss, opt, mesh), opt, mesh


def place(mesh, params: dict, batches: list):
    """The state replicated and every batch split over its rows, through
    the program's own ``shard_params`` / ``shard_batch``."""
    from paddle_tpu import parallel

    return (parallel.shard_params(mesh, params),
            [parallel.shard_batch(mesh, b) for b in batches])
