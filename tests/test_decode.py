"""Fused decode engine (paddle_tpu/ops/decode.py; docs/decode.md).

Three tiers:
- kernel units: the vocab-tiled top-k+logsumexp kernels (both variants,
  interpret mode) must match ``lax.top_k`` + two-pass logsumexp BIT-EXACT
  on indices and within 1e-5 on values, at several (N, D, V, k, alignment)
  shapes;
- decode semantics: engine vs the pre-engine scan reference on the
  flagship seq2seq model — tokens identical, scores within 1e-5 — plus
  finished-beam EOS-only masking, early-exit ≡ full-length decode,
  greedy ≡ beam_size=1, and the packed beam gather;
- surface equivalence: ``SequenceGenerator``'s engine path vs its legacy
  scan (callback) path; ``v2.infer(audit=True)`` preflight.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.models as models
import paddle_tpu.nn as nn
import paddle_tpu.ops as O
from paddle_tpu.ops.decode import (NEG, LinearReadout, LogitsReadout,
                                   _forced_kernel_config, beam_decode,
                                   beam_gather, decode_kernel_config,
                                   greedy_decode)
from paddle_tpu.ops.pallas_kernels import (topk_lse_logits_pallas,
                                           topk_lse_readout_pallas)


# ---------------------------------------------------------------------------
# kernel units
# ---------------------------------------------------------------------------

#: (N rows, D depth, V vocab, k) — V deliberately includes tile-unaligned
#: and sub-tile values; N includes the smallest legal row block
_KERNEL_SHAPES = [(16, 128, 300, 3), (8, 128, 512, 1), (32, 256, 1000, 5),
                  (40, 128, 515, 4), (8, 128, 2048, 8)]


def _ref_topk_lse(logits, k):
    lf = logits.astype(jnp.float32)
    vals, idx = jax.lax.top_k(lf, k)
    m = jnp.max(lf, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(lf - m[..., None]), axis=-1))
    return np.asarray(vals), np.asarray(idx), np.asarray(lse)


@pytest.mark.parametrize("N,D,V,k", _KERNEL_SHAPES)
def test_topk_readout_kernel_bit_exact_vs_reference(rng, N, D, V, k):
    s = jnp.asarray(rng.randn(N, D).astype(np.float32))
    w = jnp.asarray(0.1 * rng.randn(D, V).astype(np.float32))
    b = jnp.asarray(0.1 * rng.randn(V).astype(np.float32))
    rb, vt = _forced_kernel_config(N, D, V, k)
    vp = -(-V // vt) * vt
    w_p = jnp.pad(w, ((0, 0), (0, vp - V)))
    b_p = jnp.pad(b.reshape(1, V), ((0, 0), (0, vp - V)),
                  constant_values=-1e30)
    tv, ti, lse = topk_lse_readout_pallas(s, w_p, b_p, vocab=V, k=k,
                                          row_block=rb, v_tile=vt)
    rv, ri, rlse = _ref_topk_lse(s @ w + b, k)
    np.testing.assert_array_equal(np.asarray(ti[:, :k]), ri)  # bit-exact ids
    np.testing.assert_allclose(np.asarray(tv[:, :k]), rv, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse[:, 0]), rlse, rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("N,V,k", [(16, 300, 3), (8, 512, 1), (32, 999, 5)])
def test_topk_logits_kernel_bit_exact_vs_reference(rng, N, V, k):
    logits = jnp.asarray(rng.randn(N, V).astype(np.float32))
    rb, vt = _forced_kernel_config(N, None, V, k)
    vp = -(-V // vt) * vt
    l_p = jnp.pad(logits, ((0, 0), (0, vp - V)), constant_values=-1e30)
    tv, ti, lse = topk_lse_logits_pallas(l_p, vocab=V, k=k, row_block=rb,
                                         v_tile=vt)
    rv, ri, rlse = _ref_topk_lse(logits, k)
    np.testing.assert_array_equal(np.asarray(ti[:, :k]), ri)
    np.testing.assert_allclose(np.asarray(tv[:, :k]), rv, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse[:, 0]), rlse, rtol=1e-5,
                               atol=1e-5)


def test_kernel_tie_break_prefers_lowest_vocab_index():
    """Equal logits across tile boundaries must resolve exactly as
    lax.top_k's stable sort (lowest index first)."""
    N, V, k = 8, 1200, 4
    logits = np.zeros((N, V), np.float32)       # ALL-ties row
    logits[:, 700] = 1.0                        # one winner in tile 2
    lj = jnp.asarray(logits)
    rb, vt = _forced_kernel_config(N, None, V, k)
    l_p = jnp.pad(lj, ((0, 0), (0, -(-V // vt) * vt - V)),
                  constant_values=-1e30)
    _, ti, _ = topk_lse_logits_pallas(l_p, vocab=V, k=k, row_block=rb,
                                      v_tile=vt)
    _, ri = jax.lax.top_k(lj, k)
    np.testing.assert_array_equal(np.asarray(ti[:, :k]), np.asarray(ri))


def test_kernel_masked_rows_never_leak_pad_indices(rng):
    """Constrained-decoding logits (-inf on banned tokens, possibly fewer
    than k finite entries per row) must still match lax.top_k exactly —
    in particular the returned ids must stay < vocab (a -1e30 PAD column
    must never beat a real -inf logit, and a consumed winner must never be
    re-selected)."""
    N, V, k = 8, 600, 4
    logits = np.full((N, V), -np.inf, np.float32)
    logits[:, 10] = 1.0
    logits[:, 300] = 0.5           # only two finite entries per row
    lj = jnp.asarray(logits)
    rb, vt = _forced_kernel_config(N, None, V, k)
    l_p = jnp.pad(lj, ((0, 0), (0, -(-V // vt) * vt - V)),
                  constant_values=-1e30)
    tv, ti, _ = topk_lse_logits_pallas(l_p, vocab=V, k=k, row_block=rb,
                                       v_tile=vt)
    rv, ri = jax.lax.top_k(lj, k)
    np.testing.assert_array_equal(np.asarray(ti[:, :k]), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(tv[:, :k]), np.asarray(rv))
    assert np.asarray(ti[:, :k]).max() < V
    # and through the fused readout variant: a -inf BIAS bans a token
    D = 128
    s = jnp.asarray(rng.randn(N, D).astype(np.float32))
    w = jnp.asarray(0.1 * rng.randn(D, V).astype(np.float32))
    b = np.zeros((V,), np.float32)
    b[::2] = -np.inf               # ban half the vocabulary
    rb2, vt2 = _forced_kernel_config(N, D, V, k)
    vp = -(-V // vt2) * vt2
    w_p = jnp.pad(w, ((0, 0), (0, vp - V)))
    b_p = jnp.pad(jnp.asarray(b).reshape(1, V), ((0, 0), (0, vp - V)),
                  constant_values=-1e30)
    tv2, ti2, _ = topk_lse_readout_pallas(s, w_p, b_p, vocab=V, k=k,
                                          row_block=rb2, v_tile=vt2)
    rv2, ri2 = jax.lax.top_k(s @ w + jnp.asarray(b), k)
    np.testing.assert_array_equal(np.asarray(ti2[:, :k]), np.asarray(ri2))


def test_kernel_all_inf_leading_tile_keeps_lse_finite():
    """A row whose entire FIRST vocab tile is -inf (ban-prefix constrained
    decoding) must not NaN the online statistics: the lse must equal the
    two-pass reference computed over the finite tail."""
    N, V, k = 8, 1100, 2
    logits = np.full((N, V), -np.inf, np.float32)
    logits[:, 900:] = np.random.RandomState(0).randn(N, 200)  # tile 2 only
    lj = jnp.asarray(logits)
    rb, vt = _forced_kernel_config(N, None, V, k)
    l_p = jnp.pad(lj, ((0, 0), (0, -(-V // vt) * vt - V)),
                  constant_values=-1e30)
    tv, ti, lse = topk_lse_logits_pallas(l_p, vocab=V, k=k, row_block=rb,
                                         v_tile=vt)
    rv, ri, rlse = _ref_topk_lse(lj, k)
    assert np.isfinite(np.asarray(lse)).all()
    np.testing.assert_array_equal(np.asarray(ti[:, :k]), ri)
    np.testing.assert_allclose(np.asarray(lse[:, 0]), rlse, rtol=1e-5,
                               atol=1e-5)


def test_logits_readout_forced_kernel_raises_on_gated_shapes():
    """use_kernel=True must never silently fall back (finding parity with
    LinearReadout): a forced-but-gated shape is an error, not a quiet
    wrong-variant measurement."""
    with pytest.raises(ValueError):
        LogitsReadout()(jnp.zeros((12, 300)), 3, use_kernel=True)  # rows%8


def test_readout_gate_cpu_defaults_to_fallback():
    # backend gate: CPU never selects the kernel implicitly...
    assert decode_kernel_config(32, 128, 300, 3) is None
    # ...but the shape-only half drives forced/interpret runs
    assert _forced_kernel_config(32, 128, 300, 3) == (32, 512)
    assert _forced_kernel_config(32, 130, 300, 3) is None   # depth unaligned
    assert _forced_kernel_config(12, 128, 300, 3) is None   # rows unaligned
    assert _forced_kernel_config(32, 128, 300, 17) is None  # k too large
    with pytest.raises(ValueError):
        LinearReadout(jnp.zeros((130, 64)), jnp.zeros(64))(
            jnp.zeros((8, 130)), 2, use_kernel=True)


# ---------------------------------------------------------------------------
# decode semantics vs the pre-engine reference
# ---------------------------------------------------------------------------


def _reference_beam_search(m, params, src_ids, src_len, *, beam_size,
                           max_len, length_penalty=0.0):
    """The pre-engine fixed-max_len scan path (models/seq2seq.py @5c3c807),
    kept verbatim as the equivalence oracle."""
    from paddle_tpu.models.seq2seq import BOS, EOS

    B, S = src_ids.shape
    K, V = beam_size, m.trg_vocab
    src_mask = O.mask_from_lengths(src_len, S)
    enc, enc_proj, s0 = m.encode(params, src_ids, src_mask)
    tile = lambda x: jnp.repeat(x, K, axis=0)
    enc_t, enc_proj_t, mask_t = tile(enc), tile(enc_proj), tile(src_mask)
    state = tile(s0)
    logp = jnp.tile(jnp.asarray([0.0] + [NEG] * (K - 1), jnp.float32)[None],
                    (B, 1))
    tokens = jnp.full((B, K, max_len + 1), EOS, jnp.int32).at[:, :, 0].set(BOS)
    finished = jnp.zeros((B, K), bool)

    def step(carry, t):
        tokens, logp, state, finished = carry
        y = jax.lax.dynamic_index_in_dim(tokens, t, axis=2, keepdims=False)
        y_emb = O.embedding_lookup(params["trg_emb"], y.reshape(B * K))
        s_new, _ = m._dec_step(params, y_emb, state, enc_t, enc_proj_t,
                               mask_t)
        step_logits = O.linear(s_new, params["out_w"], params["out_b"])
        step_logp = jax.nn.log_softmax(step_logits.astype(jnp.float32), -1)
        step_logp = step_logp.reshape(B, K, V)
        eos_only = jnp.full((V,), NEG, jnp.float32).at[EOS].set(0.0)
        step_logp = jnp.where(finished[..., None], eos_only[None, None],
                              step_logp)
        flat = (logp[..., None] + step_logp).reshape(B, K * V)
        new_logp, flat_idx = jax.lax.top_k(flat, K)
        beam_idx = flat_idx // V
        tok = (flat_idx % V).astype(jnp.int32)
        tokens = jnp.take_along_axis(tokens, beam_idx[..., None], axis=1)
        tokens = tokens.at[:, :, t + 1].set(tok)
        state_bk = jnp.take_along_axis(s_new.reshape(B, K, -1),
                                       beam_idx[..., None], axis=1)
        finished = jnp.take_along_axis(finished, beam_idx, axis=1) | (tok == EOS)
        return (tokens, new_logp, state_bk.reshape(B * K, -1), finished), None

    (tokens, logp, _, _), _ = jax.lax.scan(
        step, (tokens, logp, state, finished), jnp.arange(max_len))
    out = tokens[:, :, 1:]
    if length_penalty > 0:
        lengths = jnp.sum((out != EOS).astype(jnp.float32), -1) + 1.0
        scores = logp / jnp.power(lengths, length_penalty)
    else:
        scores = logp
    order = jnp.argsort(-scores, axis=1)
    return (jnp.take_along_axis(out, order[..., None], axis=1),
            jnp.take_along_axis(scores, order, axis=1))


def _aligned_model_and_src(rng, B=8, S=6, V=300):
    """Kernel-eligible flagship-in-miniature: dec_dim lane-aligned, B*K a
    sublane multiple, tile-unaligned vocab."""
    m = models.Seq2SeqAttention(src_vocab=V, trg_vocab=V, emb_dim=32,
                                enc_dim=32, dec_dim=128, att_dim=32)
    params = m.init(jax.random.PRNGKey(1))
    src = jnp.asarray(rng.randint(3, V, (B, S)).astype(np.int32))
    src_len = jnp.asarray(rng.randint(2, S + 1, (B,)).astype(np.int32))
    return m, params, src, src_len


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla_fallback", "pallas_kernel"])
@pytest.mark.parametrize("K,L,lp", [(4, 7, 0.0), (1, 5, 0.0), (3, 6, 0.6)])
def test_beam_search_matches_pre_engine_reference(rng, use_kernel, K, L, lp):
    m, params, src, src_len = _aligned_model_and_src(rng)
    if use_kernel and _forced_kernel_config(src.shape[0] * K, m.dec_dim,
                                            m.trg_vocab, K) is None:
        pytest.skip("shape gated")
    toks, scores = m.beam_search(params, src, src_len, beam_size=K,
                                 max_len=L, length_penalty=lp,
                                 use_kernel=use_kernel)
    ref_t, ref_s = _reference_beam_search(m, params, src, src_len,
                                          beam_size=K, max_len=L,
                                          length_penalty=lp)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(ref_t))
    np.testing.assert_allclose(np.asarray(scores), np.asarray(ref_s),
                               rtol=1e-5, atol=1e-5)


def test_greedy_fast_path_equals_beam1(rng):
    m, params, src, src_len = _aligned_model_and_src(rng)
    for uk in (False, True):
        g_toks, g_scores = m.greedy_decode(params, src, src_len, max_len=6,
                                           use_kernel=uk)
        b_toks, b_scores = m.beam_search(params, src, src_len, beam_size=1,
                                         max_len=6, use_kernel=uk)
        np.testing.assert_array_equal(np.asarray(g_toks),
                                      np.asarray(b_toks[:, 0]))
        np.testing.assert_allclose(np.asarray(g_scores),
                                   np.asarray(b_scores[:, 0]),
                                   rtol=1e-5, atol=1e-5)


def _eos_prone_lm(rng, V=12, H=8, eos_boost=3.0):
    """Toy GRU LM whose EOS logit is boosted — beams actually finish, so
    the early-exit and EOS-masking branches are exercised for real."""
    params = {
        "emb": jnp.asarray(0.5 * rng.randn(V, H).astype(np.float32)),
        "wx": jnp.asarray(0.5 * rng.randn(H, 3 * H).astype(np.float32)),
        "wh": jnp.asarray(0.5 * rng.randn(H, 3 * H).astype(np.float32)),
        "out": jnp.asarray(rng.randn(H, V).astype(np.float32)),
        "outb": jnp.asarray(np.eye(1, V, 1)[0].astype(np.float32) * eos_boost),
    }

    def step_fn(tokens, state):
        e = jnp.take(params["emb"], tokens, axis=0)
        h2 = O.gru_step(O.linear(e, params["wx"]), state["h"], params["wh"])
        return O.linear(h2, params["out"], params["outb"]), {"h": h2}

    return params, step_fn


def test_early_exit_equals_full_length_decode(rng):
    _, step_fn = _eos_prone_lm(rng)
    mems0 = {"h": jnp.asarray(rng.randn(3, 8).astype(np.float32))}
    kw = dict(batch_size=3, beam_size=3, vocab_size=12, max_len=15)
    t_early, s_early = beam_decode(step_fn, LogitsReadout(), mems0,
                                   early_exit=True, **kw)
    t_full, s_full = beam_decode(step_fn, LogitsReadout(), mems0,
                                 early_exit=False, **kw)
    # every beam finishes well before max_len (the point of the test)
    assert np.all(np.asarray(t_early) == 1, axis=-1).any()
    np.testing.assert_array_equal(np.asarray(t_early), np.asarray(t_full))
    np.testing.assert_allclose(np.asarray(s_early), np.asarray(s_full),
                               rtol=1e-6, atol=1e-6)
    # greedy driver too
    g_early = greedy_decode(step_fn, LogitsReadout(), mems0, batch_size=3,
                            vocab_size=12, max_len=15, early_exit=True)
    g_full = greedy_decode(step_fn, LogitsReadout(), mems0, batch_size=3,
                           vocab_size=12, max_len=15, early_exit=False)
    np.testing.assert_array_equal(np.asarray(g_early[0]),
                                  np.asarray(g_full[0]))
    np.testing.assert_allclose(np.asarray(g_early[1]), np.asarray(g_full[1]),
                               rtol=1e-6, atol=1e-6)


def test_finished_beams_emit_eos_only_at_zero_cost(rng):
    """Once a beam emits EOS it must (a) extend only with EOS and (b) stop
    accumulating score — the EOS-only candidate masking."""
    _, step_fn = _eos_prone_lm(rng, eos_boost=8.0)  # finish almost at once
    mems0 = {"h": jnp.asarray(rng.randn(2, 8).astype(np.float32))}
    toks, scores = beam_decode(step_fn, LogitsReadout(), mems0,
                               batch_size=2, beam_size=3, vocab_size=12,
                               max_len=10)
    toks = np.asarray(toks)
    for b in range(2):
        for k in range(3):
            row = toks[b, k]
            if (row == 1).any():
                first = int(np.argmax(row == 1))
                assert np.all(row[first:] == 1), (b, k, row)
    # score of a finished beam == sum of its pre-EOS step log-probs: the
    # reference scan over the same step net must agree exactly
    gen = nn.SequenceGenerator(lambda p, t, m: step_fn(t, m), vocab_size=12)
    ref_t, ref_s, _ = gen.generate({}, mems0, batch_size=2, beam_size=3,
                                   max_len=10, return_trace=True)
    np.testing.assert_array_equal(toks, np.asarray(ref_t))
    np.testing.assert_allclose(np.asarray(scores), np.asarray(ref_s),
                               rtol=1e-5, atol=1e-5)


def test_sequence_generator_engine_matches_legacy_scan(rng):
    """generate() without callbacks runs the engine; return_trace=True
    forces the legacy scan — the two must produce identical searches."""
    params, step_fn = _eos_prone_lm(rng, eos_boost=0.0)
    gen = nn.SequenceGenerator(lambda p, t, m: step_fn(t, m), vocab_size=12)
    mems0 = {"h": jnp.asarray(rng.randn(3, 8).astype(np.float32))}
    toks, scores = gen.generate(params, mems0, batch_size=3, beam_size=4,
                                max_len=8, length_penalty=0.3)
    ref_t, ref_s, _ = gen.generate(params, mems0, batch_size=3, beam_size=4,
                                   max_len=8, length_penalty=0.3,
                                   return_trace=True)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(ref_t))
    np.testing.assert_allclose(np.asarray(scores), np.asarray(ref_s),
                               rtol=1e-5, atol=1e-5)


def test_beam_gather_packs_per_dtype(rng):
    B, K = 3, 4
    beam_idx = jnp.asarray(rng.randint(0, K, (B, K)).astype(np.int32))
    tree = {
        "s": jnp.asarray(rng.randn(B * K, 5).astype(np.float32)),
        "tokens": jnp.asarray(rng.randint(0, 9, (B, K, 7)).astype(np.int32)),
        "h2": jnp.asarray(rng.randn(B * K, 2, 3).astype(np.float32)),
        "fin": jnp.asarray(rng.rand(B, K) > 0.5),
    }
    got = beam_gather(tree, beam_idx)
    for name, x in tree.items():
        xb = x.reshape(B, K, -1)
        ix = beam_idx[..., None]
        want = jnp.take_along_axis(xb, ix, axis=1).reshape(x.shape)
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(want), err_msg=name)
    with pytest.raises(ValueError):
        beam_gather({"bad": jnp.zeros((B * K + 1, 2))}, beam_idx)


def test_decode_jits_and_is_stable_under_jit(rng):
    m, params, src, src_len = _aligned_model_and_src(rng, B=4)
    eager = m.beam_search(params, src, src_len, beam_size=3, max_len=5)
    jitted = jax.jit(lambda p, s, l: m.beam_search(p, s, l, beam_size=3,
                                                   max_len=5))(params, src,
                                                               src_len)
    np.testing.assert_array_equal(np.asarray(eager[0]), np.asarray(jitted[0]))


# ---------------------------------------------------------------------------
# v2.infer preflight
# ---------------------------------------------------------------------------


def test_v2_infer_audit_preflight_on_generation_topology():
    import paddle_tpu.v2 as paddle

    nn.reset_naming()
    V, H = 16, 8
    ctx_l = paddle.layer.data("ctx",
                              type=paddle.data_type.dense_vector(H))

    def step(prev_tok, ctx, mem):
        e = nn.embedding(prev_tok, 5)
        h = nn.fc(nn.concat([e, ctx, mem]), H, act="tanh")
        return [nn.fc(h, V, act="linear"), h]

    gen = paddle.layer.beam_search(
        step, input=[paddle.layer.GeneratedInput(size=V),
                     paddle.layer.StaticInput(ctx_l)],
        memories=[paddle.layer.memory("m", H, boot=ctx_l)],
        beam_size=3, max_length=5)
    params = paddle.parameters.create(gen)
    rows = [(np.random.RandomState(i).randn(H).astype(np.float32),)
            for i in range(2)]
    ids = paddle.infer(output_layer=gen, parameters=params, input=rows,
                       field="id", audit=True)   # preflight must pass clean
    assert ids.shape == (2, 3, 5)
