"""Attention seq2seq (WMT14 NMT) — the flagship model.

Capability analog of the reference's hardest path: demo/seqToseq attention NMT
(reference: demo/seqToseq/api_train_v2.py:90-189 — 512-dim bidirectional GRU
encoder, Bahdanau-attention GRU decoder, beam-search generation) built on
RecurrentGradientMachine (gserver/gradientmachines/RecurrentGradientMachine.cpp:383
generateSequence; beam callbacks .h:73-188) and simple_attention
(trainer_config_helpers/networks.py).

TPU-first re-design (SURVEY.md §7 hard part (a)): the dynamic per-sequence
unroll becomes a static-shape ``lax.scan`` over bucketed padded targets with
masking; generation drives the fused decode engine (ops/decode.py) — a
vocab-tiled Pallas top-k/logsumexp readout under an early-exit while loop
(no host round-trips — the whole decode jits onto the chip).  The encoder's
input projections and the decoder's readout are big batched MXU matmuls; the
per-step recurrent matmuls are [B*K, H] x [H, 3H].

Special token ids follow the reference's wmt14 convention: <s>=0, <e>=1,
<unk>=2 (python/paddle/v2/dataset/wmt14.py).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

import paddle_tpu.ops as O
from paddle_tpu.ops.attention_decoder import attention_gru_decoder

__all__ = ["Seq2SeqAttention"]

BOS, EOS, UNK = 0, 1, 2


@dataclass
class Seq2SeqAttention:
    src_vocab: int = 30000
    trg_vocab: int = 30000
    emb_dim: int = 512
    enc_dim: int = 512       # per-direction encoder GRU width
    dec_dim: int = 512
    att_dim: int = 512

    # ------------------------------------------------------------------

    def init(self, rng: jax.Array, dtype=jnp.float32) -> Dict[str, Any]:
        E, H, D, A = self.emb_dim, self.enc_dim, self.dec_dim, self.att_dim
        ks = jax.random.split(rng, 16)

        def nrm(k, shape, scale=None):
            scale = scale or (2.0 / (shape[0] + shape[-1])) ** 0.5
            return scale * jax.random.normal(k, shape, dtype)

        return {
            "src_emb": nrm(ks[0], (self.src_vocab, E), 0.01),
            "trg_emb": nrm(ks[1], (self.trg_vocab, E), 0.01),
            "enc_fw_wx": nrm(ks[2], (E, 3 * H)),
            "enc_fw_wh": nrm(ks[3], (H, 3 * H)),
            "enc_fw_b": jnp.zeros((3 * H,), dtype),
            "enc_bw_wx": nrm(ks[4], (E, 3 * H)),
            "enc_bw_wh": nrm(ks[5], (H, 3 * H)),
            "enc_bw_b": jnp.zeros((3 * H,), dtype),
            "boot_w": nrm(ks[6], (H, D)),
            "boot_b": jnp.zeros((D,), dtype),
            "enc_proj_w": nrm(ks[7], (2 * H, A)),
            "enc_proj_b": jnp.zeros((A,), dtype),
            "att_dec_w": nrm(ks[8], (D, A)),
            "att_v": nrm(ks[9], (A,), 0.05),
            "dec_wx": nrm(ks[10], (E + 2 * H, 3 * D)),
            "dec_wh": nrm(ks[11], (D, 3 * D)),
            "dec_b": jnp.zeros((3 * D,), dtype),
            "out_w": nrm(ks[12], (D, self.trg_vocab)),
            "out_b": jnp.zeros((self.trg_vocab,), dtype),
        }

    # ------------------------------------------------------------------

    def encode(self, params, src_ids, src_mask):
        """[B,S] ids -> (enc [B,S,2H], enc_proj [B,S,A], s0 [B,D])."""
        emb = O.embedding_lookup(params["src_emb"], src_ids)
        emb = emb * src_mask[..., None].astype(emb.dtype)
        # a forward and a reversed GRU over the same embeddings; the two
        # time loops run one after the other on the single core
        h_fw, h_bw, h_bw_fin = O.bigru_layer(
            emb, src_mask, params["enc_fw_wx"], params["enc_fw_wh"],
            params["enc_fw_b"], params["enc_bw_wx"], params["enc_bw_wh"],
            params["enc_bw_b"])
        enc = jnp.concatenate([h_fw, h_bw], axis=-1)
        enc_proj = O.linear(enc, params["enc_proj_w"], params["enc_proj_b"])
        s0 = jnp.tanh(O.linear(h_bw_fin, params["boot_w"], params["boot_b"]))
        # enc/enc_proj are re-read on every decode step from inside the scan;
        # store them in the bf16 compute dtype once so the attention tier's
        # bandwidth-bound reads are halved (no-op when compute dtype is f32)
        enc, enc_proj = O.mxu_cast(enc, enc_proj)
        return enc, enc_proj, s0

    def _dec_step(self, params, y_emb, s, enc, enc_proj, src_mask):
        """One decoder step: attention with current state, GRU advance.
        Returns (s_new [.., D], ctx [.., 2H]).

        Note: keeping the full concat-then-project ([.., E+2H] x [E+2H, 3D])
        INSIDE the scan measured FASTER end-to-end than pre-projecting the
        teacher-forced y_emb half outside it (paired A/B on v5e: 16.4 vs
        18.4 ms/step) — the hoisted [B,T,3D] f32 buffer costs more scan
        read/write bandwidth than the smaller per-step matmul saves."""
        scores = O.additive_attention_scores(enc_proj, s, params["att_dec_w"],
                                             params["att_v"])
        ctx, _ = O.attend(scores, enc, src_mask)
        x = jnp.concatenate([y_emb, ctx], axis=-1)
        xp = O.linear(x, params["dec_wx"], params["dec_b"])
        s_new = O.gru_step(xp, s, params["dec_wh"])
        return s_new, ctx

    # ------------------------------------------------------------------

    def loss(self, params, batch: Dict[str, Any]):
        """Teacher-forced token CE. batch: src_ids [B,S], src_len [B],
        trg_in [B,T] (starts with <s>), trg_next [B,T] (ends with <e>),
        trg_len [B]."""
        src_ids, src_len = batch["src_ids"], batch["src_len"]
        trg_in, trg_next, trg_len = batch["trg_in"], batch["trg_next"], batch["trg_len"]
        S, T = src_ids.shape[1], trg_in.shape[1]
        src_mask = O.mask_from_lengths(src_len, S)
        trg_mask = O.mask_from_lengths(trg_len, T)
        # named_scope: the device trace names every operation of the step by
        # these three (and their transpose(jvp(...)) forms in the backward);
        # benchmark/trace_scopes.py reads them (docs/observability.md)
        with jax.named_scope("encoder"):
            enc, enc_proj, s0 = self.encode(params, src_ids, src_mask)
        with jax.named_scope("decoder"):
            y_emb = O.embedding_lookup(params["trg_emb"], trg_in)  # [B,T,E]
            # fused-backward decoder: same math as scanning _dec_step, but
            # with a hand-written VJP that batches the big cotangent
            # contractions after the reverse scan (see
            # ops/attention_decoder.py; ~2x faster backward at WMT14 shapes
            # on v5e than XLA's scan autodiff)
            states = attention_gru_decoder(
                y_emb, s0, enc, enc_proj, src_mask, trg_mask,
                params["att_dec_w"], params["att_v"], params["dec_wx"],
                params["dec_b"], params["dec_wh"])  # [B,T,D]
        # fused readout+CE: the [B,T,30k] logits buffer stays in the bf16
        # compute dtype (the f32 version dominates HBM traffic otherwise)
        with jax.named_scope("readout_ce"):
            return O.sequence_softmax_ce_readout(
                states, params["out_w"], params["out_b"], trg_next, trg_mask)

    # ------------------------------------------------------------------
    # generation — both paths drive the fused decode engine (ops/decode.py):
    # vocab-tiled Pallas top-k+logsumexp readout (the [B*K, V] logits and
    # the f32 log-softmax buffer never touch HBM), all-beams-finished early
    # exit, packed beam-state gather.  docs/decode.md has the design.
    # ------------------------------------------------------------------

    def _decode_step_fn(self, params, enc, enc_proj, src_mask):
        """Engine step protocol: embed the previous token, advance the
        attention-GRU cell, hand the pre-readout states to the engine."""

        def step_fn(tokens, state):
            y_emb = O.embedding_lookup(params["trg_emb"], tokens)
            s_new, _ = self._dec_step(params, y_emb, state["s"], enc,
                                      enc_proj, src_mask)
            return s_new, {"s": s_new}

        return step_fn

    def greedy_decode(self, params, src_ids, src_len, *, max_len: int = 50,
                      early_exit=None, use_kernel=None):
        """Argmax decode — returns (tokens [B, max_len], scores [B]).
        True fast path: B rows (no beam tiling), running argmax +
        logsumexp; token-identical to ``beam_search(beam_size=1)``."""
        B, S = src_ids.shape
        src_mask = O.mask_from_lengths(src_len, S)
        enc, enc_proj, s0 = self.encode(params, src_ids, src_mask)
        return O.greedy_decode(
            self._decode_step_fn(params, enc, enc_proj, src_mask),
            O.LinearReadout(params["out_w"], params["out_b"]), {"s": s0},
            batch_size=B, vocab_size=self.trg_vocab, max_len=max_len,
            bos=BOS, eos=EOS, early_exit=early_exit, use_kernel=use_kernel)

    def beam_search(self, params, src_ids, src_len, *, beam_size: int = 3,
                    max_len: int = 50, length_penalty: float = 0.0,
                    early_exit=None, use_kernel=None):
        """Batched beam search, fully jitted: returns (tokens [B,K,max_len],
        scores [B,K]) sorted best-first.  The analog of
        RecurrentGradientMachine::generateSequence + --beam_size.
        """
        B, S = src_ids.shape
        K = beam_size
        src_mask = O.mask_from_lengths(src_len, S)
        enc, enc_proj, s0 = self.encode(params, src_ids, src_mask)

        # statics tile per-beam once: [B,K,...] flattened to [B*K,...]
        def tile(x):
            return jnp.repeat(x, K, axis=0)

        step_fn = self._decode_step_fn(params, tile(enc), tile(enc_proj),
                                       tile(src_mask))
        return O.beam_decode(
            step_fn, O.LinearReadout(params["out_w"], params["out_b"]),
            {"s": s0}, batch_size=B, beam_size=K,
            vocab_size=self.trg_vocab, max_len=max_len, bos=BOS, eos=EOS,
            length_penalty=length_penalty, early_exit=early_exit,
            use_kernel=use_kernel)
