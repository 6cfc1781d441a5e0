"""Finite-difference gradient sweep over EVERY registered layer constructor —
the testLayerGrad analog (reference: paddle/gserver/tests/test_LayerGrad.cpp,
LayerGradUtil.h:258-272: every layer type is FD-checked against backward()).

Each case builds a minimal net around one layer (with an upstream fc where
the layer itself has no parameters, so the check exercises the layer's VJP),
takes a fixed random-weighted sum of the output as the loss, and compares
``jax.grad`` against central finite differences at sampled coordinates.
A completeness assertion pins the sweep to the public constructor list, so
adding a layer without adding a case fails the suite.
"""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.nn as nn
from paddle_tpu.trainer.checkgrad import check_gradients

B, D, T, V = 3, 6, 5, 12
IMG_H, IMG_W, IMG_C = 6, 6, 3


def _dense(rng, name="x", size=D):
    return nn.data(name, size=size), {name: rng.randn(B, size).astype(np.float32)}


def _seq(rng, name="xs", size=D, t=T):
    lay = nn.data(name, size=size, is_seq=True)
    lengths = rng.randint(2, t + 1, B).astype(np.int32)
    vals = rng.randn(B, t, size).astype(np.float32)
    return lay, {name: (vals, lengths)}


def _ids(rng, name="ids", t=T, vocab=V):
    lay = nn.data(name, size=0, is_seq=True, dtype="int32")
    lengths = rng.randint(2, t + 1, B).astype(np.int32)
    return lay, {name: (rng.randint(0, vocab, (B, t)).astype(np.int32), lengths)}


def _img(rng, name="img"):
    lay = nn.data(name, size=IMG_C, height=IMG_H, width=IMG_W)
    return lay, {name: rng.randn(B, IMG_H, IMG_W, IMG_C).astype(np.float32)}


def _pre_fc(lay, size=D, name="pre"):
    """fc in front so param-less layers still get their VJP exercised."""
    return nn.fc(lay, size, act="tanh", name=name, bias_attr=False)


def _pre_conv(img, name="prec"):
    return nn.img_conv(img, filter_size=3, num_filters=IMG_C, padding="SAME",
                       act="tanh", name=name)


# each builder: rng -> (output LayerOutput, feed dict)
# mode "grad" FD-checks params; "forward" only checks finite forward
# (argmax/sampling/constant outputs have zero or undefined gradients)

def case_fc(rng):
    x, feed = _dense(rng)
    return nn.fc(x, 4, act="tanh"), feed


def case_fc_seq(rng):
    xs, feed = _seq(rng)
    return nn.fc(xs, 4, act="tanh"), feed


def case_embedding(rng):
    ids, feed = _ids(rng)
    return nn.embedding(ids, 4, vocab_size=V), feed


def case_addto(rng):
    x, feed = _dense(rng)
    h = _pre_fc(x)
    return nn.addto([h, h], act="tanh", bias_attr=True), feed


def case_concat(rng):
    x, feed = _dense(rng)
    return nn.concat([_pre_fc(x, name="p1"), _pre_fc(x, name="p2")]), feed


def case_dropout(rng):
    x, feed = _dense(rng)
    return nn.dropout(_pre_fc(x), 0.5), feed  # eval mode: identity


def case_error_clip(rng):
    # FD uses a large threshold so clipping is inactive and the FD check
    # remains exact; the clipped-backward behavior itself is pinned in
    # test_layers_extra2.test_error_clip_identity_forward_clipped_backward
    x, feed = _dense(rng)
    return nn.error_clip(_pre_fc(x), 1e6), feed


def case_mixed(rng):
    # full_matrix + identity + bias + nonlinearity in one mixed layer
    x, feed = _dense(rng)
    return nn.mixed(size=D, act="tanh", bias_attr=True, input=[
        nn.full_matrix_projection(x),
        nn.identity_projection(x),
    ]), feed


def case_mixed_trans_table(rng):
    ids_flat = nn.data("id1", size=V, dtype="int32")
    x, fx = _dense(rng)
    feed = {**fx, "id1": rng.randint(0, V, (B, 1)).astype(np.int32)}
    return nn.mixed(size=4, input=[
        nn.trans_full_matrix_projection(x, size=4),
        nn.table_projection(ids_flat),
    ]), feed


def case_mixed_identity_offset(rng):
    x, feed = _dense(rng)
    h = _pre_fc(x)
    return nn.mixed(size=3, input=[nn.identity_projection(h, offset=2, size=3)]), feed


def case_mixed_dotmul_scaling(rng):
    x, feed = _dense(rng)
    h = _pre_fc(x)
    return nn.mixed(size=D, input=[
        nn.dotmul_projection(h),
        nn.scaling_projection(h),
        nn.dotmul_operator(a=h, b=h, scale=0.5),
    ]), feed


def case_mixed_context(rng):
    xs, feed = _seq(rng)
    proj = nn.context_projection_input(
        _pre_fc(xs), context_len=3,
        padding_attr=nn.ParamAttr(init="normal", initial_std=0.1))
    return nn.pooling(nn.mixed(input=[proj]), pooling_type="sum"), feed


def case_mixed_conv(rng):
    img, feed = _img(rng)
    return nn.mixed(input=[
        nn.conv_projection(img, filter_size=3, num_filters=2, padding=1),
        nn.conv_projection(img, filter_size=5, num_filters=2, padding=2),
    ]), feed


def case_mixed_conv_operator(rng):
    img, fi = _img(rng)
    fsz = 3 * 3 * IMG_C * 2
    flt = nn.data("flt", size=fsz)
    feed = {**fi, "flt": rng.randn(B, fsz).astype(np.float32)}
    return nn.mixed(input=[
        nn.conv_operator(img=img, filter=_pre_fc(flt, fsz, "pf"),
                         filter_size=3, num_filters=2, padding=1),
    ]), feed


def case_tensor(rng):
    a, fa = _dense(rng, "a", 4)
    b, fb = _dense(rng, "b", 3)
    return nn.tensor(a, b, 5), {**fa, **fb}


def case_scaling(rng):
    w, fw = _dense(rng, "w", 1)
    x, fx = _dense(rng, "x")
    return nn.scaling(w, _pre_fc(x)), {**fw, **fx}


def case_power(rng):
    w, fw = _dense(rng, "w", 1)
    x, fx = _dense(rng, "x")
    fx["x"] = np.abs(fx["x"]) + 0.5  # positive base keeps x**w finite
    return nn.power(_pre_fc(w, 1, "pw"), x), {**fw, **fx}


def case_slope_intercept(rng):
    x, feed = _dense(rng)
    return nn.slope_intercept(_pre_fc(x), slope=2.0, intercept=0.5), feed


def case_sum_to_one_norm(rng):
    x, feed = _dense(rng)
    feed["x"] = np.abs(feed["x"]) + 0.1
    return nn.sum_to_one_norm(_pre_fc(x)), feed


def case_interpolation(rng):
    w, fw = _dense(rng, "w", 1)
    a, fa = _dense(rng, "a")
    b, fb = _dense(rng, "b")
    return nn.interpolation(w, a, b), {**fw, **fa, **fb}


def case_outer_prod(rng):
    a, fa = _dense(rng, "a", 3)
    b, fb = _dense(rng, "b", 4)
    return nn.outer_prod(_pre_fc(a, 3, "pa"), _pre_fc(b, 4, "pb")), {**fa, **fb}


def case_cos_sim(rng):
    a, fa = _dense(rng, "a")
    b, fb = _dense(rng, "b")
    return nn.cos_sim(a, b), {**fa, **fb}


def case_cos_vm(rng):
    v, fv = _dense(rng, "v", 4)
    m, fm = _dense(rng, "m", 12)
    return nn.cos_vm(_pre_fc(v, 4, "pv"), m), {**fv, **fm}


def case_linear_comb(rng):
    w, fw = _dense(rng, "w", 3)
    m, fm = _dense(rng, "m", 12)
    return nn.linear_comb(_pre_fc(w, 3, "pw"), m, 4), {**fw, **fm}


def case_convex_comb(rng):
    w, fw = _dense(rng, "w", 3)
    m, fm = _dense(rng, "m", 12)
    return nn.convex_comb(_pre_fc(w, 3, "pw"), m, 4), {**fw, **fm}


def case_conv_shift(rng):
    a, fa = _dense(rng, "a", 8)
    b, fb = _dense(rng, "b", 3)
    return nn.conv_shift(_pre_fc(a, 8, "pa"), b), {**fa, **fb}


def case_multiplex(rng):
    idx = nn.data("idx", size=1, dtype="int32")
    a, fa = _dense(rng, "a", 4)
    b, fb = _dense(rng, "b", 4)
    feed = {**fa, **fb, "idx": rng.randint(0, 2, (B, 1)).astype(np.int32)}
    return nn.multiplex(idx, [_pre_fc(a, 4, "pa"), _pre_fc(b, 4, "pb")]), feed


def case_prelu(rng):
    x, feed = _dense(rng)
    return nn.prelu(_pre_fc(x)), feed


def case_data_norm(rng):
    x, feed = _dense(rng)
    return nn.data_norm(x), feed


def case_resize(rng):
    x, feed = _dense(rng)
    return nn.resize(_pre_fc(x), 3), feed


def case_trans(rng):
    x = nn.data("x", size=9)
    return nn.trans(_pre_fc(x, 9, "pre")), {"x": rng.randn(B, 9).astype(np.float32)}


def case_get_output(rng):
    ids, feed = _ids(rng)
    lstm = nn.lstmemory(nn.embedding(ids, 4, vocab_size=V), 4, name="l")
    key = "cell"  # final cell state aux output
    probe = nn.Topology(lstm)
    p, s = probe.init(jax.random.PRNGKey(0))
    acts, _ = probe.apply(p, s, feed)
    key = sorted(acts[lstm.name].state)[0]
    return nn.get_output(lstm, key), feed


# ---- sequence layers -------------------------------------------------------

def case_pooling(rng):
    xs, feed = _seq(rng)
    return nn.pooling(_pre_fc(xs), pooling_type="avg"), feed


def case_last_seq(rng):
    xs, feed = _seq(rng)
    return nn.last_seq(_pre_fc(xs)), feed


def case_first_seq(rng):
    xs, feed = _seq(rng)
    return nn.first_seq(_pre_fc(xs)), feed


def case_expand(rng):
    x, fx = _dense(rng, "v", D)
    xs, fs = _seq(rng)
    return nn.expand(_pre_fc(x, D, "pv"), xs), {**fx, **fs}


def case_seq_reverse(rng):
    xs, feed = _seq(rng)
    return nn.pooling(nn.seq_reverse(_pre_fc(xs)), pooling_type="sum"), feed


def case_seq_concat(rng):
    a, fa = _seq(rng, "a")
    b, fb = _seq(rng, "b")
    return nn.pooling(nn.seq_concat(_pre_fc(a, D, "pa"), b), pooling_type="sum"), {**fa, **fb}


def case_seq_reshape(rng):
    xs = nn.data("xs", size=4, is_seq=True)
    vals = rng.randn(B, 4, 4).astype(np.float32)
    lengths = np.full((B,), 4, np.int32)  # full rows: reshape is exact
    return nn.pooling(nn.seq_reshape(_pre_fc(xs, 4, "pre"), 8),
                      pooling_type="sum"), {"xs": (vals, lengths)}


def case_sub_seq(rng):
    xs, feed = _seq(rng)
    off = nn.data("off", size=1, dtype="int32")
    sz = nn.data("sz", size=1, dtype="int32")
    feed["off"] = np.zeros((B, 1), np.int32)
    feed["sz"] = np.full((B, 1), 2, np.int32)
    return nn.pooling(nn.sub_seq(_pre_fc(xs), off, sz), pooling_type="sum"), feed


def case_context_projection(rng):
    xs, feed = _seq(rng)
    return nn.pooling(nn.context_projection(_pre_fc(xs), context_len=3),
                      pooling_type="sum"), feed


def case_lstmemory(rng):
    xs, feed = _seq(rng)
    return nn.pooling(nn.lstmemory(xs, 4), pooling_type="sum"), feed


def case_grumemory(rng):
    xs, feed = _seq(rng)
    return nn.pooling(nn.grumemory(xs, 4), pooling_type="sum"), feed


def case_bidirectional_rnn(rng):
    xs, feed = _seq(rng)
    return nn.pooling(nn.bidirectional_rnn(xs, 4), pooling_type="sum"), feed


def case_recurrent_group(rng):
    xs, feed = _seq(rng)

    def step(x_t, mem):
        s = nn.fc([x_t, mem], 4, act="tanh", name="cell", bias_attr=False)
        return [s, s]

    return nn.pooling(nn.recurrent_group(step, [xs], [nn.Memory("m", 4)]),
                      pooling_type="sum"), feed


def case_featmap_expand(rng):
    xs, feed = _seq(rng)
    return nn.featmap_expand(_pre_fc(xs), num_filters=2), feed


# ---- image layers ----------------------------------------------------------

def case_img_conv(rng):
    img, feed = _img(rng)
    return nn.img_conv(img, filter_size=3, num_filters=4, act="tanh"), feed


def case_img_conv_transpose(rng):
    img, feed = _img(rng)
    return nn.img_conv_transpose(img, filter_size=3, num_filters=2, stride=2), feed


def case_img_pool(rng):
    img, feed = _img(rng)
    return nn.img_pool(_pre_conv(img), pool_size=2), feed


def case_img_cmrnorm(rng):
    img, feed = _img(rng)
    return nn.img_cmrnorm(_pre_conv(img), size=3), feed


def case_batch_norm(rng):
    img, feed = _img(rng)
    return nn.batch_norm(_pre_conv(img), act="relu"), feed


def case_maxout(rng):
    img, feed = _img(rng)
    c = nn.img_conv(img, filter_size=3, num_filters=4, padding="SAME",
                    act="linear", name="prec")
    return nn.maxout(c, groups=2), feed


def case_pad(rng):
    img, feed = _img(rng)
    return nn.pad(_pre_conv(img), pad_h=(1, 1), pad_w=(0, 1)), feed


def case_rotate(rng):
    img, feed = _img(rng)
    return nn.rotate(_pre_conv(img)), feed


def case_slice_channels(rng):
    img, feed = _img(rng)
    c = nn.img_conv(img, filter_size=3, num_filters=6, padding="SAME",
                    act="linear", name="prec")
    return nn.slice_channels(c, 1, 4), feed


def case_bilinear_interp(rng):
    img, feed = _img(rng)
    return nn.bilinear_interp(_pre_conv(img), out_h=4, out_w=8), feed


def case_block_expand(rng):
    img, feed = _img(rng)
    return nn.pooling(nn.block_expand(_pre_conv(img), block_x=2, block_y=2,
                                      stride_x=2, stride_y=2),
                      pooling_type="sum"), feed


def case_spp(rng):
    img, feed = _img(rng)
    return nn.spp(_pre_conv(img), pyramid_height=2), feed


def case_priorbox(rng):
    img, feed = _img(rng)
    feat = nn.img_pool(_pre_conv(img), pool_size=2)
    return nn.priorbox(feat, img, min_size=[4], max_size=[8]), feed


def case_mdlstmemory(rng):
    img, feed = _img(rng)
    return nn.mdlstmemory(img, 3), feed


# ---- cost layers ------------------------------------------------------------

def _label_int(rng, n=4, name="lab"):
    return (nn.data(name, size=n, dtype="int32"),
            {name: rng.randint(0, n, (B,)).astype(np.int32)})


def case_classification_cost(rng):
    x, feed = _dense(rng)
    lab, fl = _label_int(rng)
    return nn.classification_cost(nn.fc(x, 4, act="softmax"), lab), {**feed, **fl}


def case_cross_entropy_cost(rng):
    x, feed = _dense(rng)
    lab, fl = _label_int(rng)
    return nn.cross_entropy_cost(nn.fc(x, 4, act="softmax"), lab), {**feed, **fl}


def case_cross_entropy_with_selfnorm(rng):
    x, feed = _dense(rng)
    lab, fl = _label_int(rng)
    return nn.cross_entropy_with_selfnorm(nn.fc(x, 4, act="softmax"), lab), {**feed, **fl}


def case_soft_cross_entropy_cost(rng):
    x, feed = _dense(rng)
    lab = nn.data("lab", size=4)
    p = np.abs(rng.rand(B, 4)).astype(np.float32)
    feed["lab"] = p / p.sum(1, keepdims=True)
    return nn.soft_cross_entropy_cost(nn.fc(x, 4, act="softmax"), lab), feed


def case_mse_cost(rng):
    x, feed = _dense(rng)
    lab = nn.data("lab", size=4)
    feed["lab"] = rng.randn(B, 4).astype(np.float32)
    return nn.mse_cost(nn.fc(x, 4), lab), feed


def case_huber_cost(rng):
    x, feed = _dense(rng)
    lab = nn.data("lab", size=1)
    feed["lab"] = rng.randn(B, 1).astype(np.float32)
    return nn.huber_cost(nn.fc(x, 1), lab), feed


def case_smooth_l1_cost(rng):
    x, feed = _dense(rng)
    lab = nn.data("lab", size=4)
    feed["lab"] = rng.randn(B, 4).astype(np.float32)
    return nn.smooth_l1_cost(nn.fc(x, 4), lab), feed


def case_multi_binary_label_cross_entropy(rng):
    x, feed = _dense(rng)
    lab = nn.data("lab", size=4)
    feed["lab"] = (rng.rand(B, 4) > 0.5).astype(np.float32)
    return nn.multi_binary_label_cross_entropy(nn.fc(x, 4), lab), feed


def case_sum_cost(rng):
    x, feed = _dense(rng)
    return nn.sum_cost(nn.fc(x, 4)), feed


def case_rank_cost(rng):
    l, fl = _dense(rng, "l")
    r, fr = _dense(rng, "r")
    lab = nn.data("lab", size=1)
    feed = {**fl, **fr, "lab": (rng.rand(B, 1) > 0.5).astype(np.float32)}
    return nn.rank_cost(nn.fc(l, 1, name="fl"), nn.fc(r, 1, name="fr"), lab), feed


def case_lambda_cost(rng):
    s = nn.data("s", size=1, is_seq=True)
    l = nn.data("l", size=1, is_seq=True)
    lens = np.full((B,), 4, np.int32)
    feed = {"s": (rng.randn(B, 4, 1).astype(np.float32), lens),
            "l": (np.abs(rng.randn(B, 4, 1)).astype(np.float32), lens)}
    return nn.lambda_cost(nn.fc(s, 1, name="fs", bias_attr=False), l,
                          NDCG_num=3), feed


def case_crf_cost(rng):
    xs, feed = _seq(rng)
    lab = nn.data("lab", size=4, is_seq=True, dtype="int32")
    lengths = feed["xs"][1]
    feed["lab"] = (rng.randint(0, 4, (B, T)).astype(np.int32), lengths)
    return nn.crf_cost(nn.fc(xs, 4, name="emit", bias_attr=False), lab), feed


def case_ctc_cost(rng):
    xs, feed = _seq(rng, t=8)
    lab = nn.data("lab", size=4, is_seq=True, dtype="int32")
    feed["lab"] = (rng.randint(1, 4, (B, 3)).astype(np.int32),
                   np.full((B,), 2, np.int32))
    feed["xs"] = (feed["xs"][0], np.full((B,), 8, np.int32))
    return nn.ctc_cost(nn.fc(xs, 5, act="linear", name="emit"), lab), feed


def case_warp_ctc(rng):
    # warp-ctc conventions: blank=0, labels in [1, C)
    xs, feed = _seq(rng, t=8)
    lab = nn.data("wlab", size=4, is_seq=True, dtype="int32")
    feed["wlab"] = (rng.randint(1, 4, (B, 3)).astype(np.int32),
                    np.full((B,), 2, np.int32))
    feed["xs"] = (feed["xs"][0], np.full((B,), 8, np.int32))
    return nn.warp_ctc(nn.fc(xs, 5, act="linear", name="wemit"), lab), feed


def case_nce_cost(rng):
    x, feed = _dense(rng)
    lab, fl = _label_int(rng, n=V)
    fl["lab"] = fl["lab"][:, None]
    return nn.nce_cost(x, lab, num_classes=V, num_neg_samples=4), {**feed, **fl}


def case_hsigmoid_cost(rng):
    x, feed = _dense(rng)
    lab, fl = _label_int(rng, n=8)
    fl["lab"] = fl["lab"][:, None]
    return nn.hsigmoid_cost(x, lab, num_classes=8), {**feed, **fl}


def case_lstm_step(rng):
    # single-frame cell: pre-summed [B,4H] gates + explicit c state
    x, fx = _dense(rng, "x", 8)  # 4H, H=2
    c = nn.data("c", size=2)
    fx["c"] = rng.randn(B, 2).astype(np.float32) * 0.5
    return nn.lstm_step(x, c, 2), fx


def case_gru_step(rng):
    x, fx = _dense(rng, "x", 6)  # 3H, H=2
    h = nn.data("h", size=2)
    fx["h"] = rng.randn(B, 2).astype(np.float32) * 0.5
    return nn.gru_step(x, h, 2), fx


def case_selective_fc(rng):
    x, fx = _dense(rng)
    sel = nn.data("sel", size=4)
    fx["sel"] = (rng.rand(B, 4) > 0.3).astype(np.float32)
    return nn.selective_fc(x, sel, 4, act="linear"), fx


# ---- forward-only layers (no useful gradient) ------------------------------

def case_maxid(rng):
    x, feed = _dense(rng)
    return nn.maxid(nn.fc(x, 4, act="softmax")), feed


def case_sampling_id(rng):
    x, feed = _dense(rng)
    return nn.sampling_id(nn.fc(x, 4, act="softmax")), feed


def case_eos_id(rng):
    ids, feed = _ids(rng)
    return nn.eos_id(ids, eos_id=1), feed


def case_eos_trim(rng):
    ids, feed = _ids(rng)
    return nn.eos_trim(ids, eos_id=1), feed


def case_crf_decoding(rng):
    xs, feed = _seq(rng)
    cost_lab = nn.data("lab", size=4, is_seq=True, dtype="int32")
    lengths = feed["xs"][1]
    feed["lab"] = (rng.randint(0, 4, (B, T)).astype(np.int32), lengths)
    emit = nn.fc(xs, 4, name="emit", bias_attr=False)
    nn.crf_cost(emit, cost_lab, name="crf", param_attr=nn.ParamAttr(name="crf_w"))
    return nn.crf_decoding(emit, share_with="crf_w"), feed




def case_cross_channel_norm(rng):
    img, feed = _img(rng)
    return nn.cross_channel_norm(_pre_conv(img)), feed


def case_print_value(rng):
    # identity dataflow; FD-checks the upstream fc's params THROUGH it
    x, feed = _dense(rng)
    return nn.print_value(_pre_fc(x)), feed


# -- the decoder-only block (PR 28) ------------------------------------------
# each behind an fc so that the layer's own VJP carries a parameter's gradient


def case_rms_norm(rng):
    xs, feed = _seq(rng)
    return nn.rms_norm(_pre_fc(xs)), feed


def case_gated_short_conv(rng):
    xs, feed = _seq(rng)
    return nn.gated_short_conv(_pre_fc(xs), kernel_size=3), feed


def case_causal_self_attention(rng):
    xs, feed = _seq(rng)
    return nn.causal_self_attention(_pre_fc(xs, size=8), num_heads=4,
                                    num_kv_heads=2, head_dim=2), feed


def case_window_self_attention(rng):
    # a window of 2 positions in a row of up to 5, a head-wise gate, no head
    # norms, the rotary embedding under YaRN on half a head (PR 50)
    xs, feed = _seq(rng)
    return nn.causal_self_attention(
        _pre_fc(xs, size=8), num_heads=4, num_kv_heads=2, head_dim=4,
        qk_norm=False, output_gate="head", rotary_dim=2, window=2,
        rope_scaling=dict(rope_type="yarn", rope_theta=100.0, factor=4.0,
                          original_max_position_embeddings=2)), feed


def case_indexed_self_attention(rng):
    # attention over the 3 positions an indexer of 2 heads of 4 keeps of a
    # row of up to 5 (PR 47): the value's gradient reaches every leaf but the
    # indexer's five, whose finite differences are 0 too while no selection
    # flips
    xs, feed = _seq(rng)
    return nn.indexed_self_attention(_pre_fc(xs, size=8), num_heads=4,
                                     num_kv_heads=2, head_dim=2,
                                     indexer_heads=2, indexer_head_dim=4,
                                     topk=3), feed


def case_latent_attention(rng):
    # keys of 4 + 2 rotary channels beside values of 3, from a latent of 4
    xs, feed = _seq(rng)
    return nn.latent_attention(_pre_fc(xs, size=8), num_heads=2,
                               kv_lora_rank=4, qk_nope_head_dim=4,
                               qk_rope_head_dim=2, v_head_dim=3), feed


def case_gated_delta_net(rng):
    # 1 key head serving 2 value heads, a state of 4 x 3 a head (PR 41)
    xs, feed = _seq(rng)
    return nn.gated_delta_net(_pre_fc(xs, size=8), num_key_heads=1,
                              num_value_heads=2, key_head_dim=4,
                              value_head_dim=3), feed


def case_mamba2_mixer(rng):
    # 2 heads of 3 channels in 1 group with a state of 4, a convolution with
    # a bias (PR 43)
    xs, feed = _seq(rng)
    return nn.mamba2_mixer(_pre_fc(xs, size=8), num_heads=2, head_dim=3,
                           n_groups=1, state_size=4), feed


def case_self_attention_without_qk_norm_or_rotary(rng):
    # the layer takes no positions and has no q_norm / k_norm leaves (PR 43)
    xs, feed = _seq(rng)
    return nn.causal_self_attention(_pre_fc(xs, size=8), num_heads=4,
                                    num_kv_heads=2, head_dim=2,
                                    qk_norm=False, rotary=False), feed


def case_gated_self_attention(rng):
    # an output gate, rotary on 2 of a head's 4 channels, 1 + w norms (PR 41)
    xs, feed = _seq(rng)
    return nn.causal_self_attention(_pre_fc(xs, size=8), num_heads=4,
                                    num_kv_heads=2, head_dim=4,
                                    output_gate=True, rotary_dim=2,
                                    zero_centered_norm=True), feed


def case_zero_centered_rms_norm(rng):
    xs, feed = _seq(rng)
    return nn.rms_norm(_pre_fc(xs), zero_centered=True), feed


def case_gated_mlp(rng):
    xs, feed = _seq(rng)
    return nn.gated_mlp(_pre_fc(xs), 8), feed


def case_expert_mlp(rng):
    # every expert chosen (top_k = num_experts): a finite difference must not
    # move a token to another expert; the share of two of three is computed
    xs, feed = _seq(rng)
    return nn.expert_mlp(_pre_fc(xs), 8, num_experts=3, experts_held=(1, 2),
                         top_k=3), feed


def case_softmax_expert_mlp_with_a_gated_shared_expert(rng):
    # softmax scores, every expert chosen, and the shared expert behind its
    # sigmoid gate (PR 41)
    xs, feed = _seq(rng)
    return nn.expert_mlp(_pre_fc(xs), 8, num_experts=3, experts_held=(1, 2),
                         top_k=3, shared_size=6, scoring="softmax",
                         shared_gate=True), feed


def case_relu2_expert_mlp_with_a_shared_expert(rng):
    # experts of TWO matrices and a squared ReLU, the shared one too; every
    # expert chosen (PR 43)
    xs, feed = _seq(rng)
    return nn.expert_mlp(_pre_fc(xs), 8, num_experts=3, experts_held=(1, 2),
                         top_k=3, shared_size=6, routed_scaling_factor=2.5,
                         expert_act="relu2"), feed


def case_gated_relu_expert_mlp_with_an_early_router(rng):
    # ReLU-gated experts whose router reads ANOTHER layer's output (PR 57):
    # every expert chosen, so a finite difference moves no token; the
    # router's leaf and the layer in front of it get their gradient through
    # the router's layer, the experts' through the experts' input; the
    # shared expert takes the experts' form too
    xs, feed = _seq(rng)
    return nn.expert_mlp(_pre_fc(xs), 8, num_experts=3, experts_held=(1, 2),
                         top_k=3, scoring="softmax", expert_act="gated_relu",
                         shared_size=6,
                         router_input=_pre_fc(xs, name="pre_router")), feed


def case_lm_head_cost(rng):
    ids, feed = _ids(rng)
    lab = nn.data("next", size=0, is_seq=True, dtype="int32")
    emb = nn.embedding(ids, D, vocab_size=V, name="tied")
    feed["next"] = (rng.randint(0, V, (B, T)).astype(np.int32),
                    feed["ids"][1])
    return nn.lm_head_cost(nn.rms_norm(emb), lab, embedding=emb), feed


def _token_costs(rng, exits=3):
    """``exits`` normed states over one embedding, the targets, the feed."""
    ids, feed = _ids(rng)
    lab = nn.data("next", size=V, is_seq=True, dtype="int32")
    feed["next"] = (rng.randint(0, V, (B, T)).astype(np.int32),
                    feed["ids"][1])
    emb = nn.embedding(ids, D, vocab_size=V, name="emb")
    states = [nn.rms_norm(_pre_fc(emb, name=f"pre{t}"), name=f"n{t}")
              for t in range(exits)]
    return states, lab, feed


def case_lm_head_token_cost(rng):
    # one cross-entropy a position over an untied head (PR 54); the sweep's
    # random weights on the output are the per-token cotangent
    (state,), lab, feed = _token_costs(rng, exits=1)
    return nn.lm_head_token_cost(state, lab), feed


def case_token_gate(rng):
    xs, feed = _seq(rng)
    return nn.token_gate(_pre_fc(xs)), feed


def case_loop_exit_cost(rng):
    # three exits through ONE head, two gates through ONE vector and bias:
    # the expected loss with its entropy term (PR 54)
    states, lab, feed = _token_costs(rng)
    ces = [nn.lm_head_token_cost(s, lab, name=f"ce{t}", param_name="head")
           for t, s in enumerate(states)]
    gates = [nn.token_gate(s, name=f"g{t}", param_name="gate")
             for t, s in enumerate(states[:-1])]
    return nn.loop_exit_cost(ces, gates, lab, beta=0.1), feed


def _sandwich(x, scope, eps=1e-6):
    """A layer with a norm before AND after each sub-block, its leaves named
    without ``scope``."""
    shared = lambda n: nn.ParamAttr(name=f"_{n}.w", init="ones")  # noqa: E731
    norm = lambda v, n: nn.rms_norm(v, eps=eps, name=scope + n,   # noqa: E731
                                    param_attr=shared(n))
    a = nn.causal_self_attention(norm(x, "n1"), num_heads=4, num_kv_heads=4,
                                 head_dim=2, qk_norm=False,
                                 name=scope + "attn", param_name="attn")
    h = nn.addto([x, norm(a, "n2")], name=scope + "h")
    m = nn.gated_mlp(norm(h, "n3"), 8, name=scope + "mlp", param_name="mlp")
    return nn.addto([h, norm(m, "n4")], name=scope + "y")


def case_sandwich_layer(rng):
    # h = x + RMS(Attn(RMS(x))), y = h + RMS(MLP(RMS(h))) (PR 54)
    xs, feed = _seq(rng)
    return _sandwich(_pre_fc(xs, size=8), ""), feed


def case_layer_pair_sharing_its_leaves(rng):
    # the same layer applied twice under two scopes: eleven leaves, each
    # used twice, and a leaf's gradient the sum over its uses (PR 54)
    xs, feed = _seq(rng)
    return _sandwich(_sandwich(_pre_fc(xs, size=8), "loop0/"), "loop1/"), feed


FORWARD_ONLY = {"maxid", "sampling_id", "eos_id", "eos_trim", "crf_decoding",
                "priorbox"}

# constructors that are not standalone computable layers (or are exercised
# by their own dedicated suites in ways the generic harness cannot):
EXCLUDED = {
    "data",            # input declaration, no compute
    "reset_naming",    # naming utility
    "device_pin",      # sharding annotation wrapper (test_sparse_hooks)
    "classification_cost",  # included below via CASES
    "beam_search",     # emits int token ids — no gradient path by design
}


def _collect_cases():
    cases = {}
    g = globals()
    for name, fn in list(g.items()):
        if name.startswith("case_"):
            cases[name[len("case_"):]] = fn
    return cases


CASES = _collect_cases()


def test_sweep_is_complete():
    """Every public nn constructor has a sweep case or a justified exclusion."""
    public = set()
    for n in dir(nn):
        if n.startswith("_"):
            continue
        f = getattr(nn, n)
        if inspect.isfunction(f):
            try:
                ret = inspect.signature(f).return_annotation
            except (ValueError, TypeError):
                continue
            if "LayerOutput" in str(ret):
                public.add(n)
    missing = public - set(CASES) - EXCLUDED
    assert not missing, f"layers without a grad-sweep case: {sorted(missing)}"


@pytest.mark.parametrize("layer_name", sorted(CASES))
def test_layer_grad(layer_name, rng):
    nn.reset_naming()
    out, feed = CASES[layer_name](rng)
    topo = nn.Topology(out)
    params, state = topo.init(jax.random.PRNGKey(7))

    o, _ = topo.apply(params, state, feed)
    val = np.asarray(o[out.name].value)
    assert np.isfinite(val.astype(np.float64)).all(), "non-finite forward"
    if layer_name in FORWARD_ONLY:
        return

    w = jnp.asarray(np.asarray(np.random.RandomState(11).randn(*val.shape),
                              dtype=np.float32))

    def loss(p):
        outs, _ = topo.apply(p, state, feed)
        v = outs[out.name].value
        return jnp.sum(v * w)

    if not params:
        pytest.skip("no parameters upstream (pure reshaping layer)")
    check_gradients(loss, params, samples_per_param=2, eps=1e-3,
                    rtol=5e-2, atol=5e-3)
