"""Plain reference of the benchmark LSTM text classifier
(benchmark/paddle/rnn/rnn.py: embedding, stacked LSTM layers with peephole
("check") weights, max-pool over the real steps, a linear layer, softmax
cross-entropy averaged over the rows) in float32 ``jax.numpy``.  It imports
nothing of the program; parameter names are the program's so that one set of
seeded weights serves both.

LSTM cell as legacy Paddle's hl_lstm_ops: gates [i, f, o, g] from
``x @ wx + b + h @ w0``; i and f see ``c`` through their peepholes, o sees the
new ``c``; ``c' = f*c + i*tanh(g)``, ``h' = o*tanh(c')``; a padded step holds
the state and emits zeros.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def param_shapes(cfg: dict) -> dict:
    V, E, H, C = (cfg["vocab"], cfg["emb_dim"], cfg["hid_dim"],
                  cfg["num_classes"])
    shapes = {"_emb.w0": ((V, E), 0.01),
              "_logits.w0": ((H, C), None), "_logits.wbias": ((C,), 0.02)}
    for i in range(cfg["num_layers"]):
        d = E if i == 0 else H
        shapes.update({
            f"_lstm{i}.wx": ((d, 4 * H), None),
            f"_lstm{i}.w0": ((H, 4 * H), None),
            f"_lstm{i}.wbias": ((4 * H,), 0.02),
            f"_lstm{i}.check_i": ((H,), 0.05),
            f"_lstm{i}.check_f": ((H,), 0.05),
            f"_lstm{i}.check_o": ((H,), 0.05),
        })
    return shapes


def mm(a, b):
    """Every matrix multiplication of this file.  The lower-precision control
    (benchmark/correct.py) swaps it for one that rounds its operands."""
    return jnp.matmul(a, b)


def _lstm_layer(x, mask, p, pre):
    H = p[pre + ".w0"].shape[0]
    xp = jnp.moveaxis(mm(x, p[pre + ".wx"]) + p[pre + ".wbias"], 1, 0)
    m = jnp.moveaxis(mask, 1, 0)[..., None]

    def step(carry, inp):
        h, c = carry
        xp_t, m_t = inp
        i, f, o, g = jnp.split(xp_t + mm(h, p[pre + ".w0"]), 4, axis=-1)
        i = jax.nn.sigmoid(i + p[pre + ".check_i"] * c)
        f = jax.nn.sigmoid(f + p[pre + ".check_f"] * c)
        c_new = f * c + i * jnp.tanh(g)
        o = jax.nn.sigmoid(o + p[pre + ".check_o"] * c_new)
        h_new = o * jnp.tanh(c_new)
        h_keep = m_t * h_new + (1.0 - m_t) * h
        c_keep = m_t * c_new + (1.0 - m_t) * c
        return (h_keep, c_keep), h_new * m_t

    zeros = jnp.zeros((x.shape[0], H), x.dtype)
    _, out = jax.lax.scan(step, (zeros, zeros), (xp, m))
    return jnp.moveaxis(out, 0, 1)


def logits(cfg: dict, p: dict, batch: dict):
    ids, lengths = batch["words"]
    T = ids.shape[1]
    mask = (jnp.arange(T)[None, :] < lengths[:, None]).astype(jnp.float32)
    h = p["_emb.w0"][ids]
    for i in range(cfg["num_layers"]):
        h = _lstm_layer(h, mask, p, f"_lstm{i}")
    pooled = jnp.max(jnp.where(mask[..., None] > 0, h, -jnp.inf), axis=1)
    return mm(pooled, p["_logits.w0"]) + p["_logits.wbias"]


def loss_sum(cfg: dict, p: dict, batch: dict):
    """(sum of the rows' cross-entropies, the number of rows)."""
    z = logits(cfg, p, batch)
    logp = jax.nn.log_softmax(z, axis=-1)
    lab = batch["label"].reshape(-1)
    picked = jnp.take_along_axis(logp, lab[:, None], -1)[:, 0]
    return -picked.sum(), jnp.float32(lab.shape[0])


# -- what only this configuration knows about its traffic -------------------

def batch(cfg: dict, traffic: dict, gen) -> dict:
    """One feed from the cell's traffic file: ``words`` (ids, lengths) and a
    ``label``, the feed of demo/chip_smoke/train_conf.py.  ``label_shares``
    fixes how many rows each class gets (then shuffled): with balanced
    random labels the rows' gradients cancel at seeded weights and what is
    left is rounding.  ``gen`` is benchmark/traffic.py's generator."""
    B, T = traffic["batch"], traffic["seq_len"]
    lengths = gen.lengths(traffic["lengths"], B, T)
    counts = np.round(np.cumsum(traffic["label_shares"]) * B).astype(int)
    label = np.searchsorted(counts, np.arange(B), side="right")
    return {
        "words": (gen.ids(cfg["vocab"], lengths, T), lengths),
        "label": gen.rng.permutation(label).astype(np.int32).reshape(B, 1),
    }


def real_tokens(feed: dict) -> int:
    """What a step counts as its tokens: the real (unpadded) input words."""
    return int(feed["words"][1].sum())


def step_flops(cfg: dict, traffic: dict) -> float:
    """Operations one training step needs, from its shapes alone (copied from
    bench.py ``bench_lstm_textclf``): 3 x the forward pass's matrix
    multiplications; padded positions count."""
    B, T = traffic["batch"], traffic["seq_len"]
    E, H, L = cfg["emb_dim"], cfg["hid_dim"], cfg["num_layers"]
    fwd = (B * T * E * 4 * H * 2 + B * T * H * 4 * H * 2
           + (L - 1) * (B * T * H * 4 * H * 2 * 2)
           + B * H * cfg["num_classes"] * 2)
    return 3.0 * fwd
