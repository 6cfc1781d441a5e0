"""Plain reference of the attention seq2seq (demo/seqToseq/seqToseq_net.py:
bidirectional GRU encoder, Bahdanau-attention GRU decoder, softmax readout,
token-mean cross-entropy) in float32 ``jax.numpy``: no kernels, no cache, no
lower-precision operands.  It imports nothing of the program; the caller
traces it under ``jax.default_matmul_precision("highest")``.

GRU cell as the reference's GatedRecurrentLayer: gates [r, u, c] from
``x @ wx + b + h @ wh[:, :2H]``, candidate ``tanh(xc + (r*h) @ wh[:, 2H:])``,
``h' = u*h + (1-u)*cand``; a padded step holds the state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BOS, EOS = 0, 1   # the wmt14 convention: <s>, <e>, <unk>=2, words from 3


def param_shapes(cfg: dict) -> dict:
    """name -> (shape, standard deviation or None for Glorot, or 0)."""
    E, H, D, A = cfg["emb_dim"], cfg["enc_dim"], cfg["dec_dim"], cfg["att_dim"]
    Vs, Vt = cfg["src_vocab"], cfg["trg_vocab"]
    return {
        "src_emb": ((Vs, E), 0.01), "trg_emb": ((Vt, E), 0.01),
        "enc_fw_wx": ((E, 3 * H), None), "enc_fw_wh": ((H, 3 * H), None),
        "enc_fw_b": ((3 * H,), 0.02),
        "enc_bw_wx": ((E, 3 * H), None), "enc_bw_wh": ((H, 3 * H), None),
        "enc_bw_b": ((3 * H,), 0.02),
        "boot_w": ((H, D), None), "boot_b": ((D,), 0.02),
        "enc_proj_w": ((2 * H, A), None), "enc_proj_b": ((A,), 0.02),
        "att_dec_w": ((D, A), None), "att_v": ((A,), 0.05),
        "dec_wx": ((E + 2 * H, 3 * D), None), "dec_wh": ((D, 3 * D), None),
        "dec_b": ((3 * D,), 0.02),
        "out_w": ((D, Vt), None), "out_b": ((Vt,), 0.02),
    }


def mm(a, b):
    """Every matrix multiplication of this file.  The lower-precision control
    (benchmark/correct.py) swaps it for one that rounds its operands."""
    return jnp.matmul(a, b)


def _gru(xp, h, wh):
    H = h.shape[-1]
    zr = xp[..., :2 * H] + mm(h, wh[:, :2 * H])
    r, u = jnp.split(jax.nn.sigmoid(zr), 2, axis=-1)
    cand = jnp.tanh(xp[..., 2 * H:] + mm(r * h, wh[:, 2 * H:]))
    return u * h + (1.0 - u) * cand


def _gru_layer(x, mask, wx, wh, b, reverse=False):
    """[B,T,E] -> (outputs [B,T,H], zero at padded steps; final state)."""
    xp = jnp.moveaxis(mm(x, wx) + b, 1, 0)
    m = jnp.moveaxis(mask, 1, 0)[..., None]

    def step(h, inp):
        xp_t, m_t = inp
        h_new = m_t * _gru(xp_t, h, wh) + (1.0 - m_t) * h
        return h_new, h_new * m_t

    h0 = jnp.zeros((x.shape[0], wh.shape[0]), x.dtype)
    h_fin, out = jax.lax.scan(step, h0, (xp, m), reverse=reverse)
    return jnp.moveaxis(out, 0, 1), h_fin


def decoder_states(p, batch):
    """Teacher-forced decoder states [B,T,D] before the readout."""
    S, T = batch["src_ids"].shape[1], batch["trg_in"].shape[1]
    f32 = jnp.float32
    src_mask = (jnp.arange(S)[None, :] < batch["src_len"][:, None]).astype(f32)
    trg_mask = (jnp.arange(T)[None, :] < batch["trg_len"][:, None]).astype(f32)
    emb = p["src_emb"][batch["src_ids"]] * src_mask[..., None]
    h_fw, _ = _gru_layer(emb, src_mask, p["enc_fw_wx"], p["enc_fw_wh"],
                         p["enc_fw_b"])
    h_bw, h_bw_fin = _gru_layer(emb, src_mask, p["enc_bw_wx"], p["enc_bw_wh"],
                                p["enc_bw_b"], reverse=True)
    enc = jnp.concatenate([h_fw, h_bw], -1)
    enc_proj = mm(enc, p["enc_proj_w"]) + p["enc_proj_b"]
    s0 = jnp.tanh(mm(h_bw_fin, p["boot_w"]) + p["boot_b"])
    y_emb = jnp.moveaxis(p["trg_emb"][batch["trg_in"]], 1, 0)
    m_tb = jnp.moveaxis(trg_mask, 1, 0)[..., None]

    def step(s, inp):
        y_t, m_t = inp
        e = jnp.tanh(enc_proj + mm(s, p["att_dec_w"])[:, None, :])
        scores = jnp.where(src_mask > 0, mm(e, p["att_v"]), -jnp.inf)
        w = jax.nn.softmax(scores, axis=-1)
        ctx = mm(w[:, None, :], enc)[:, 0, :]
        xp = mm(jnp.concatenate([y_t, ctx], -1), p["dec_wx"]) + p["dec_b"]
        s_new = m_t * _gru(xp, s, p["dec_wh"]) + (1.0 - m_t) * s
        return s_new, s_new

    _, states = jax.lax.scan(step, s0, (y_emb, m_tb))
    return jnp.moveaxis(states, 0, 1), trg_mask


def loss_sum(cfg: dict, p: dict, batch: dict):
    """(sum of the target words' negative log-probabilities, their count):
    the loss is the quotient, so blocks of rows add."""
    states, trg_mask = decoder_states(p, batch)
    logits = mm(states, p["out_w"]) + p["out_b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    tok = jnp.take_along_axis(logp, batch["trg_next"][..., None], -1)[..., 0]
    return -(tok * trg_mask).sum(), trg_mask.sum()


# -- what only this configuration knows about its traffic -------------------

def batch(cfg: dict, traffic: dict, gen) -> dict:
    """One feed from the cell's traffic file: source rows and teacher-forced
    target rows, ``<s> w..`` in and ``w.. <e>`` out (chip_smoke.py's and
    bench.py's batch).  ``gen`` is benchmark/traffic.py's generator."""
    B, S, T = traffic["batch"], traffic["src_len"], traffic["trg_len"]
    src_len = gen.lengths(traffic["lengths"], B, S)
    trg_len = gen.lengths(traffic["lengths"], B, T)
    core = gen.ids(cfg["trg_vocab"], np.full((B,), T - 1), T - 1)
    trg_in = np.concatenate([np.full((B, 1), BOS, np.int32), core], 1)
    trg_next = np.concatenate([core, np.zeros((B, 1), np.int32)], 1)
    trg_next[np.arange(B), trg_len - 1] = EOS
    live = np.arange(T)[None, :] < trg_len[:, None]
    return {
        "src_ids": gen.ids(cfg["src_vocab"], src_len, S),
        "src_len": src_len,
        "trg_in": np.where(live, trg_in, 0).astype(np.int32),
        "trg_next": np.where(live, trg_next, 0).astype(np.int32),
        "trg_len": trg_len,
    }


def real_tokens(feed: dict) -> int:
    """What a step counts as its tokens: the target words."""
    return int(feed["trg_len"].sum())


def step_flops(cfg: dict, traffic: dict) -> float:
    """Operations one training step needs, from its shapes alone (copied from
    bench.py ``bench_seq2seq``): 3 x the forward pass's matrix
    multiplications (2*M*N*K each).  Recomputation does not count, and XLA's
    own count misses scan bodies."""
    B, S, T = traffic["batch"], traffic["src_len"], traffic["trg_len"]
    E, H, D, A = cfg["emb_dim"], cfg["enc_dim"], cfg["dec_dim"], cfg["att_dim"]
    V = cfg["trg_vocab"]
    fwd = (2 * B * S * E * 3 * H * 2          # encoder input projections
           + 2 * B * S * H * 3 * H * 2        # encoder recurrences
           + B * S * 2 * H * A * 2            # attention projection of enc
           + T * (B * D * A * 2 + B * S * A * 2 + B * S * 2 * H * 2
                  + B * (E + 2 * H) * 3 * D * 2 + B * D * 3 * D * 2)
           + B * T * D * V * 2)               # readout
    return 3.0 * fwd
