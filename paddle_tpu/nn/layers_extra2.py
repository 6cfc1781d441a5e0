"""Remaining reference layer inventory — the long tail of REGISTER_LAYER
types (SURVEY.md §2 item 26) not covered by layers.py/layers_extra.py:

prelu, trans, resize, data_norm, conv_shift, convex_comb (linear_comb),
cos_vm, get_output, lambda_cost, selective_fc, spp, priorbox, eos_id,
img_conv_transpose (exconvt), mdlstmemory.

Each cites its reference implementation; all are TPU-native (static shapes,
masked semantics, MXU-friendly).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Union

import jax
import jax.numpy as jnp

import paddle_tpu.ops as O
from paddle_tpu.nn.graph import (
    Act,
    LayerOutput,
    ParamAttr,
    ParamSpec,
    next_name,
)
from paddle_tpu.nn.layers import (AttrLike, _bias_attr, _inherit_meta, _pa,
                                  _seq_like, _spatial)
from paddle_tpu.utils.error import ConfigError

__all__ = [
    "prelu",
    "trans",
    "resize",
    "data_norm",
    "conv_shift",
    "linear_comb",
    "convex_comb",
    "cos_vm",
    "get_output",
    "lambda_cost",
    "selective_fc",
    "spp",
    "priorbox",
    "eos_id",
    "img_conv_transpose",
    "mdlstmemory",
    "cross_channel_norm",
    "print_value",
]


def prelu(input: LayerOutput, *, name: Optional[str] = None,
          param_attr: AttrLike = None,
          channel_shared: bool = False) -> LayerOutput:
    """Parametric ReLU — analog of ParameterReluLayer (PReluLayer.cpp):
    out = max(0,x) + a * min(0,x) with a learned per-feature slope."""
    name = name or next_name("prelu")
    pa = _pa(param_attr, f"_{name}.w0", init="normal", initial_std=0.0)
    shape = (1,) if channel_shared else (input.size,)
    spec = ParamSpec(name=pa.name, shape=shape, attr=pa)

    def forward(ctx, params, a: Act) -> Act:
        x = a.value
        slope = params[spec.name].astype(x.dtype)
        y = jnp.maximum(x, 0) + slope * jnp.minimum(x, 0)
        return _seq_like(a, y) if a.is_seq else Act(value=y)

    return LayerOutput(name, "prelu", input.size, [input], forward, [spec])


def trans(input: LayerOutput, *, name: Optional[str] = None) -> LayerOutput:
    """Transpose each sample's [H, W] matrix — analog of TransLayer
    (TransLayer.cpp; hl batch transpose kernels). Requires spatial meta or a
    square feature size."""
    name = name or next_name("trans")
    if "hw" in input.meta:
        h, w = input.meta["hw"]
        c = input.size
    else:
        side = int(round(input.size ** 0.5))
        if side * side != input.size:
            raise ConfigError("trans needs spatial meta or a square size")
        h = w = side
        c = None

    def forward(ctx, params, a: Act) -> Act:
        x = a.value
        if c is not None:  # [B,H,W,C] -> [B,W,H,C]
            return Act(value=jnp.swapaxes(x, 1, 2))
        b = x.shape[0]
        return Act(value=jnp.swapaxes(x.reshape(b, h, w), 1, 2).reshape(b, h * w))

    out = LayerOutput(name, "trans", input.size, [input], forward, [])
    if c is not None:
        out.meta["hw"] = (w, h)
    return out


def resize(input: LayerOutput, size: int, *, name: Optional[str] = None) -> LayerOutput:
    """Reshape the batch's flat values into rows of ``size`` — analog of
    ResizeLayer (ResizeLayer.cpp: total elements preserved, row width
    changed)."""
    name = name or next_name("resize")

    def forward(ctx, params, a: Act) -> Act:
        return Act(value=a.value.reshape(-1, size))

    return LayerOutput(name, "resize", size, [input], forward, [])


def data_norm(input: LayerOutput, *, strategy: str = "z-score",
              name: Optional[str] = None) -> LayerOutput:
    """Normalize features by running statistics — analog of DataNormLayer
    (DataNormLayer.cpp: z-score / min-max / decimal-scaling using stats
    shipped with the model).  Stats live in model state: during training an
    EMA of batch stats updates them; at inference they are fixed."""
    if strategy not in ("z-score", "min-max", "decimal-scaling"):
        raise ConfigError(f"unknown data_norm strategy {strategy!r}")
    name = name or next_name("data_norm")
    D = input.size
    mean_s = ParamSpec(f"_{name}.mean", (D,), ParamAttr(init="zeros"), is_state=True)
    var_s = ParamSpec(f"_{name}.var", (D,), ParamAttr(init="ones"), is_state=True)
    min_s = ParamSpec(f"_{name}.min", (D,), ParamAttr(init="zeros"), is_state=True)
    max_s = ParamSpec(f"_{name}.max", (D,), ParamAttr(init="ones"), is_state=True)

    def forward(ctx, params, a: Act) -> Act:
        x = a.value
        mean, var = params[mean_s.name], params[var_s.name]
        mn, mx = params[min_s.name], params[max_s.name]
        if ctx.train:
            m = jnp.mean(x, axis=0)
            v = jnp.var(x, axis=0)
            bmn, bmx = jnp.min(x, axis=0), jnp.max(x, axis=0)
            mom = 0.99
            ctx.updated_state[mean_s.name] = mom * mean + (1 - mom) * m
            ctx.updated_state[var_s.name] = mom * var + (1 - mom) * v
            ctx.updated_state[min_s.name] = jnp.minimum(mn, bmn)
            ctx.updated_state[max_s.name] = jnp.maximum(mx, bmx)
            mean, var, mn, mx = m, v, jnp.minimum(mn, bmn), jnp.maximum(mx, bmx)
        if strategy == "z-score":
            y = (x - mean) / jnp.sqrt(var + 1e-6)
        elif strategy == "min-max":
            y = (x - mn) / jnp.maximum(mx - mn, 1e-6)
        else:  # decimal-scaling
            scale = jnp.power(
                10.0, jnp.ceil(jnp.log10(jnp.maximum(
                    jnp.maximum(jnp.abs(mn), jnp.abs(mx)), 1e-6)))
            )
            y = x / scale
        return Act(value=y)

    return LayerOutput(name, "data_norm", D, [input], forward,
                       [mean_s, var_s, min_s, max_s])


def conv_shift(a: LayerOutput, b: LayerOutput, *,
               name: Optional[str] = None) -> LayerOutput:
    """Circular convolution of a [B,M] with kernel b [B,N] (N odd) — analog
    of ConvShiftLayer (ConvShiftLayer.cpp; the NTM shift operation):
    out[i] = sum_j b[j] * a[(i + j - (N-1)/2) mod M]."""
    name = name or next_name("conv_shift")
    N = b.size
    if N % 2 == 0:
        raise ConfigError("conv_shift kernel size must be odd")

    def forward(ctx, params, xa: Act, xb: Act) -> Act:
        x, k = xa.value, xb.value
        half = (N - 1) // 2
        shifted = [jnp.roll(x, -(j - half), axis=1) for j in range(N)]
        y = sum(k[:, j : j + 1] * shifted[j] for j in range(N))
        return Act(value=y)

    return LayerOutput(name, "conv_shift", a.size, [a, b], forward, [])


def linear_comb(weights: LayerOutput, input: LayerOutput, size: int, *,
                name: Optional[str] = None) -> LayerOutput:
    """Weighted combination of K sub-vectors — analog of
    LinearChainCombLayer/ConvexCombinationLayer (LinearChainCRF... see
    ConvexCombinationLayer.cpp): input [B, K*size] viewed as K vectors,
    weights [B, K] -> sum_k w_k * v_k [B, size]."""
    name = name or next_name("linear_comb")
    if input.size % size != 0:
        raise ConfigError("linear_comb: input.size must be K*size")
    K = input.size // size

    def forward(ctx, params, wa: Act, va: Act) -> Act:
        w = wa.value  # [B,K]
        v = va.value.reshape(-1, K, size)
        return Act(value=jnp.einsum("bk,bkd->bd", w, v))

    return LayerOutput(name, "linear_comb", size, [weights, input], forward, [])


def convex_comb(weights: LayerOutput, input: LayerOutput, size: int, *,
                name: Optional[str] = None) -> LayerOutput:
    """convex_comb alias of linear_comb (reference registers both names)."""
    return linear_comb(weights, input, size, name=name)


def cos_vm(vec: LayerOutput, mat: LayerOutput, *, scale: float = 1.0,
           name: Optional[str] = None) -> LayerOutput:
    """Cosine similarity of a vector with K sub-vectors — analog of
    CosSimVecMatLayer (cos_vm): vec [B,D], mat [B,K*D] -> [B,K]."""
    name = name or next_name("cos_vm")
    D = vec.size
    if mat.size % D != 0:
        raise ConfigError("cos_vm: mat.size must be K*vec.size")
    K = mat.size // D

    def forward(ctx, params, va: Act, ma: Act) -> Act:
        v = va.value  # [B,D]
        m = ma.value.reshape(-1, K, D)
        num = jnp.einsum("bd,bkd->bk", v, m)
        den = (jnp.linalg.norm(v, axis=-1, keepdims=True)
               * jnp.linalg.norm(m, axis=-1) + 1e-8)
        return Act(value=scale * num / den)

    return LayerOutput(name, "cos_vm", K, [vec, mat], forward, [])


def get_output(input: LayerOutput, key: str, *, size: Optional[int] = None,
               name: Optional[str] = None) -> LayerOutput:
    """Select an auxiliary output of a layer — analog of GetOutputLayer
    (config 'get_output'; e.g. an LSTM's cell state).  ``key`` indexes the
    producing layer's Act.state."""
    name = name or next_name("get_output")

    def forward(ctx, params, a: Act) -> Act:
        if key not in a.state:
            raise ConfigError(
                f"get_output: {input.name!r} has no aux output {key!r}; "
                f"available: {sorted(a.state)}"
            )
        return Act(value=a.state[key])

    return LayerOutput(name, "get_output", size or input.size, [input],
                       forward, [])


def lambda_cost(score: LayerOutput, label: LayerOutput, *,
                NDCG_num: int = 5, name: Optional[str] = None) -> LayerOutput:
    """LambdaRank listwise cost — analog of LambdaCost (LambdaCost.cpp):
    pairwise logistic loss over documents of one query (a sequence), each
    pair weighted by its |ΔNDCG@k|."""
    name = name or next_name("lambda_cost")

    def forward(ctx, params, sa: Act, la: Act) -> Act:
        s = sa.value  # [B,T] or [B,T,1]
        rel = la.value
        if s.ndim == 3:
            s = s[..., 0]
        if rel.ndim == 3:
            rel = rel[..., 0]
        mask = sa.mask if sa.mask is not None else jnp.ones_like(s)
        T = s.shape[1]
        gain = (jnp.power(2.0, rel) - 1.0) * mask
        # ideal DCG from the top-NDCG_num gains per row
        k = min(NDCG_num, T)
        top = jax.lax.top_k(gain, k)[0]
        disc = 1.0 / jnp.log2(jnp.arange(2, k + 2).astype(jnp.float32))
        idcg = jnp.maximum(jnp.sum(top * disc, axis=1, keepdims=True), 1e-6)
        # pairwise: swap positions i,j — |ΔNDCG| ≈ |g_i-g_j|*|1/log(ri)-1/log(rj)|
        # with ranks from current scores
        order = jnp.argsort(-s, axis=1)
        ranks = jnp.argsort(order, axis=1).astype(jnp.float32)  # 0-based
        dfac = 1.0 / jnp.log2(ranks + 2.0)
        dg = gain[:, :, None] - gain[:, None, :]          # [B,T,T]
        dd = dfac[:, :, None] - dfac[:, None, :]
        dndcg = jnp.abs(dg * dd) / idcg[:, :, None]
        ds = s[:, :, None] - s[:, None, :]
        rel_gt = (rel[:, :, None] > rel[:, None, :]).astype(s.dtype)
        pair_mask = mask[:, :, None] * mask[:, None, :]
        loss = jnp.log1p(jnp.exp(-jnp.clip(ds, -30, 30))) * rel_gt * dndcg * pair_mask
        return Act(value=jnp.sum(loss) / O.token_count(mask))

    return LayerOutput(name, "lambda_cost", 1, [score, label], forward, [])


def selective_fc(input: LayerOutput, select: LayerOutput, size: int, *,
                 act: str = "tanh", name: Optional[str] = None,
                 param_attr: AttrLike = None,
                 bias_attr: AttrLike = True,
                 select_mode: str = "mask") -> LayerOutput:
    """FC evaluated only on selected output columns — analog of
    SelectiveFullyConnectedLayer (SelectiveFullyConnectedLayer.cpp: skip
    unselected columns for huge softmax fronts).

    Two TPU-native compute paths:
    - ``select_mode='mask'``: ``select`` is a dense 0/1 vector [B, size];
      compute densely on the MXU and mask — same semantics (unselected
      outputs are exactly 0), no dynamic shapes.  Right when the selected
      fraction is large.
    - ``select_mode='ids'``: ``select`` carries integer candidate ids
      [B, C] (C = select.size); only those C columns of the weight are
      gathered and multiplied — the reference's sparse-selection path
      (SelectiveFullyConnectedLayer.cpp with a sparse selection matrix),
      right when C << size.  Output is [B, C], column j scoring candidate
      ``select[b, j]``.
    """
    if select_mode not in ("mask", "ids"):
        raise ConfigError(f"select_mode must be 'mask' or 'ids', got {select_mode!r}")
    name = name or next_name("selective_fc")
    inputs = [input] if isinstance(input, LayerOutput) else list(input)
    if select_mode == "ids":
        return _selective_fc_ids(inputs, select, size, act=act, name=name,
                                 param_attr=param_attr, bias_attr=bias_attr)
    if inputs[0].meta.get("sparse"):
        return _selective_fc_sparse_input(inputs, select, size, act=act,
                                          name=name, param_attr=param_attr,
                                          bias_attr=bias_attr)
    # multiple inputs get separate weight matrices summed, as in fc
    # (SelectiveFullyConnectedLayer.cpp iterates all inputs)
    wspecs = []
    for i, ipt in enumerate(inputs):
        pa = _pa(param_attr if len(inputs) == 1 else None, f"_{name}.w{i}")
        wspecs.append(ParamSpec(name=pa.name, shape=(ipt.size, size), attr=pa))
    specs = list(wspecs)
    ba = _bias_attr(bias_attr, f"_{name}.wbias")
    if ba:
        specs.append(ParamSpec(name=ba.name, shape=(size,), attr=ba))
    act_fn = O.get_activation(act)

    def forward(ctx, params, *acts: Act) -> Act:
        sel = acts[-1]
        y = None
        for spec, a in zip(wspecs, acts[:-1]):
            z = O.linear(a.value, params[spec.name])
            y = z if y is None else y + z
        if ba:
            y = y + params[ba.name].astype(y.dtype)
        y = act_fn(y) * sel.value.astype(y.dtype)
        return Act(value=y)

    return LayerOutput(name, "selective_fc", size, [*inputs, select],
                       forward, specs)


def _selective_fc_ids(inputs, select, size, *, act, name, param_attr, bias_attr):
    """selective_fc sparse-selection path: gather only the candidate columns."""
    wspecs = []
    for i, ipt in enumerate(inputs):
        pa = _pa(param_attr if len(inputs) == 1 else None, f"_{name}.w{i}")
        wspecs.append(ParamSpec(name=pa.name, shape=(ipt.size, size), attr=pa))
    specs = list(wspecs)
    ba = _bias_attr(bias_attr, f"_{name}.wbias")
    if ba:
        specs.append(ParamSpec(name=ba.name, shape=(size,), attr=ba))
    act_fn = O.get_activation(act)

    def forward(ctx, params, *acts: Act) -> Act:
        sel = acts[-1]
        sel_ids = sel.value
        y = None
        for i, (spec, a) in enumerate(zip(wspecs, acts[:-1])):
            z = O.selective_columns_matmul(
                a.value, sel_ids, params[spec.name],
                params[ba.name] if (ba and i == 0) else None)
            y = z if y is None else y + z
        y = act_fn(y)
        if sel.mask is not None:
            y = y * sel.mask.astype(y.dtype)
        return Act(value=y, state={"sel_ids": sel_ids})

    out = LayerOutput(name, "selective_fc", select.size, [*inputs, select],
                      forward, specs)
    out.meta["select_mode"] = "ids"
    return out


def _selective_fc_sparse_input(inputs, select, size, *, act, name, param_attr,
                               bias_attr):
    """selective_fc over a sparse (bag-of-features) input: sparse gather
    matmul for the forward, dense 0/1 selection mask on the output."""
    wspecs = []
    for i, ipt in enumerate(inputs):
        pa = _pa(param_attr if len(inputs) == 1 else None, f"_{name}.w{i}")
        wspecs.append(ParamSpec(name=pa.name, shape=(ipt.size, size), attr=pa))
    specs = list(wspecs)
    ba = _bias_attr(bias_attr, f"_{name}.wbias")
    if ba:
        specs.append(ParamSpec(name=ba.name, shape=(size,), attr=ba))
    act_fn = O.get_activation(act)
    sparse_kinds = [ipt.meta.get("sparse") for ipt in inputs]

    def forward(ctx, params, *acts: Act) -> Act:
        sel = acts[-1]
        y = None
        for spec, a, sparse in zip(wspecs, acts[:-1], sparse_kinds):
            if sparse:
                # sparse sequences carry per-slot validity in state
                # (Act.mask is the [B,T] sequence mask there) — see fc
                z = O.sparse_gather_matmul(
                    a.value, a.state["weights"],
                    a.state.get("nnz_mask", a.mask), params[spec.name])
            else:
                z = O.linear(a.value, params[spec.name])
            y = z if y is None else y + z
        if ba:
            y = y + params[ba.name].astype(y.dtype)
        y = act_fn(y) * sel.value.astype(y.dtype)
        return Act(value=y)

    return LayerOutput(name, "selective_fc", size, [*inputs, select],
                       forward, specs)


def spp(input: LayerOutput, *, pyramid_height: int = 3,
        pool_type: str = "max", name: Optional[str] = None) -> LayerOutput:
    """Spatial pyramid pooling — analog of SppLayer (SpatialPyramidPoolLayer
    .cpp): pool the feature map into 1x1, 2x2, ... 2^(h-1) grids and concat,
    giving a fixed-size vector for any input size."""
    name = name or next_name("spp")
    h, w = _spatial(input)
    C = input.size
    bins = [2 ** i for i in range(pyramid_height)]
    out_size = C * sum(b * b for b in bins)

    def forward(ctx, params, a: Act) -> Act:
        x = a.value  # [B,H,W,C]
        parts: List = []
        for b in bins:
            # adaptive pooling: split H/W into b nearly-even chunks
            hs = [h * i // b for i in range(b + 1)]
            ws = [w * i // b for i in range(b + 1)]
            for i in range(b):
                for j in range(b):
                    cell = x[:, hs[i]:max(hs[i + 1], hs[i] + 1),
                             ws[j]:max(ws[j + 1], ws[j] + 1), :]
                    red = jnp.max if pool_type == "max" else jnp.mean
                    parts.append(red(cell, axis=(1, 2)))
        return Act(value=jnp.concatenate(parts, axis=-1))

    return LayerOutput(name, "spp", out_size, [input], forward, [])


def priorbox(input: LayerOutput, image: LayerOutput, *,
             min_size: Sequence[int], max_size: Sequence[int] = (),
             aspect_ratio: Sequence[float] = (2.0,),
             variance: Sequence[float] = (0.1, 0.1, 0.2, 0.2),
             name: Optional[str] = None) -> LayerOutput:
    """SSD prior (anchor) boxes — analog of PriorBoxLayer (PriorBox.cpp):
    for each feature-map cell emit default boxes (sizes x aspect ratios) in
    normalized image coordinates, plus their variances.
    Output value: [1, 2, K*4] with row 0 = boxes, row 1 = variances."""
    name = name or next_name("priorbox")
    fh, fw = _spatial(input)
    ih, iw = _spatial(image)
    ratios = [1.0]
    for ar in aspect_ratio:
        ratios.extend((ar, 1.0 / ar))
    num_priors = len(ratios) * len(min_size) + len(max_size)
    K = fh * fw * num_priors

    import numpy as _np

    boxes = _np.zeros((fh, fw, num_priors, 4), _np.float32)
    for i in range(fh):
        for j in range(fw):
            cx, cy = (j + 0.5) / fw, (i + 0.5) / fh
            p = 0
            for ms in min_size:
                for r in ratios:
                    bw = ms * (r ** 0.5) / iw
                    bh = ms / (r ** 0.5) / ih
                    boxes[i, j, p] = [cx - bw / 2, cy - bh / 2,
                                      cx + bw / 2, cy + bh / 2]
                    p += 1
            for k, Ms in enumerate(max_size):
                s = (min_size[min(k, len(min_size) - 1)] * Ms) ** 0.5
                boxes[i, j, p] = [cx - s / 2 / iw, cy - s / 2 / ih,
                                  cx + s / 2 / iw, cy + s / 2 / ih]
                p += 1
    boxes = _np.clip(boxes, 0.0, 1.0).reshape(-1)
    var = _np.tile(_np.asarray(variance, _np.float32), K)
    const = jnp.asarray(_np.stack([boxes, var])[None])  # [1,2,K*4]

    def forward(ctx, params, a: Act, img: Act) -> Act:
        return Act(value=const)

    return LayerOutput(name, "priorbox", K * 4, [input, image], forward, [])


def eos_id(input: LayerOutput, *, eos_id: int = 1,
           name: Optional[str] = None) -> LayerOutput:
    """1 where the id equals EOS — analog of EosIdCheckLayer (eos_id)."""
    name = name or next_name("eos_id")

    def forward(ctx, params, a: Act) -> Act:
        flag = (a.value == eos_id).astype(jnp.float32)
        return _seq_like(a, flag * a.mask) if a.is_seq else Act(value=flag)

    return LayerOutput(name, "eos_id", 1, [input], forward, [])


def img_conv_transpose(input: LayerOutput, *, filter_size: int,
                       num_filters: int, stride: int = 1,
                       act: str = "relu", name: Optional[str] = None,
                       param_attr: AttrLike = None,
                       bias_attr: AttrLike = True) -> LayerOutput:
    """Transposed convolution — analog of exconvt/cudnn_convt
    (ConvTransLayerBase).  SAME padding: output H,W = input * stride."""
    name = name or next_name("convt")
    h, w = _spatial(input)
    pa = _pa(param_attr, f"_{name}.w0")
    wspec = ParamSpec(
        name=pa.name, shape=(filter_size, filter_size, input.size, num_filters),
        attr=pa)
    specs = [wspec]
    ba = _bias_attr(bias_attr, f"_{name}.wbias")
    if ba:
        specs.append(ParamSpec(name=ba.name, shape=(num_filters,), attr=ba))
    act_fn = O.get_activation(act)

    def forward(ctx, params, a: Act) -> Act:
        y = O.conv2d_transpose(a.value, params[wspec.name],
                               stride=(stride, stride), padding="SAME")
        if ba:
            y = y + params[ba.name].astype(y.dtype)
        return Act(value=act_fn(y))

    out = LayerOutput(name, "convt", num_filters, [input], forward, specs)
    out.meta["hw"] = (h * stride, w * stride)
    return out


def mdlstmemory(input: LayerOutput, size: int, *, act: str = "tanh",
                name: Optional[str] = None,
                param_attr: AttrLike = None,
                bias_attr: AttrLike = True) -> LayerOutput:
    """2-D multi-dimensional LSTM over a feature map — analog of MDLstmLayer
    (MDLstmLayer.cpp): each cell state depends on its LEFT and TOP neighbors
    with separate forget gates.  Scan over rows (lax.scan), vectorized over
    columns inside a row via a column scan — two nested scans, fully jitted.
    Gate layout: [i, f_left, f_top, o, g] (5 blocks)."""
    name = name or next_name("mdlstm")
    h, w = _spatial(input)
    C = input.size
    H = size
    pa = _pa(param_attr, f"_{name}.w0")
    wx = ParamSpec(f"_{name}.wx", (C, 5 * H), pa)
    wl = ParamSpec(f"_{name}.wl", (H, 5 * H), _pa(param_attr, f"_{name}.wl"))
    wt = ParamSpec(f"_{name}.wt", (H, 5 * H), _pa(param_attr, f"_{name}.wt"))
    specs = [wx, wl, wt]
    ba = _bias_attr(bias_attr, f"_{name}.wbias")
    if ba:
        specs.append(ParamSpec(name=ba.name, shape=(5 * H,), attr=ba))
    act_fn = O.get_activation(act)

    def forward(ctx, params, a: Act) -> Act:
        x = a.value  # [B,Hh,Ww,C]
        B = x.shape[0]
        xp = O.linear(x, params[wx.name],
                      params[ba.name] if ba else None)  # [B,h,w,5H]
        w_l, w_t = params[wl.name], params[wt.name]

        def cell(xp_ij, h_left, c_left, h_top, c_top):
            z = (xp_ij + O.linear(h_left, w_l) + O.linear(h_top, w_t))
            i, fl, ft, o, g = jnp.split(z, 5, axis=-1)
            sig = jax.nn.sigmoid
            c = sig(fl) * c_left + sig(ft) * c_top + sig(i) * act_fn(g)
            hh = sig(o) * act_fn(c)
            return hh, c

        def row_step(carry, xp_row):
            h_top_row, c_top_row = carry  # [B,w,H]

            def col_step(cl, inp):
                h_left, c_left = cl
                xp_ij, h_top, c_top = inp
                hh, cc = cell(xp_ij, h_left, c_left, h_top, c_top)
                return (hh, cc), (hh, cc)

            z = jnp.zeros((B, H), xp_row.dtype)
            (_, _), (h_row, c_row) = jax.lax.scan(
                col_step, (z, z),
                (jnp.moveaxis(xp_row, 1, 0),
                 jnp.moveaxis(h_top_row, 1, 0),
                 jnp.moveaxis(c_top_row, 1, 0)),
            )
            h_row = jnp.moveaxis(h_row, 0, 1)  # [B,w,H]
            c_row = jnp.moveaxis(c_row, 0, 1)
            return (h_row, c_row), h_row

        z_row = jnp.zeros((B, x.shape[2], H), xp.dtype)
        _, h_all = jax.lax.scan(row_step, (z_row, z_row),
                                jnp.moveaxis(xp, 1, 0))
        return Act(value=jnp.moveaxis(h_all, 0, 1))  # [B,h,w,H]

    out = LayerOutput(name, "mdlstm", H, [input], forward, specs)
    out.meta["hw"] = (h, w)
    return out


def cross_channel_norm(input: LayerOutput, *, name: Optional[str] = None,
                       param_attr: AttrLike = None) -> LayerOutput:
    """Per-pixel L2 normalization across channels with a trainable per-channel
    scale — analog of cross_channel_norm_layer (CrossChannelNormLayer.cpp;
    the SSD normalization block, layers.py cross_channel_norm_layer)."""
    name = name or next_name("cross_channel_norm")
    C = input.size
    pa = _pa(param_attr, f"_{name}.w0", init="ones")
    sspec = ParamSpec(name=pa.name, shape=(C,), attr=pa)

    def forward(ctx, params, a: Act) -> Act:
        x = a.value  # [B,H,W,C]
        norm = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=-1,
                                keepdims=True) + 1e-12)
        y = (x / norm.astype(x.dtype)) * params[sspec.name].astype(x.dtype)
        return Act(value=y)

    out = LayerOutput(name, "cross_channel_norm", C, [input], forward, [sspec])
    _inherit_meta(out, input)
    return out


def print_value(input: LayerOutput, *, message: Optional[str] = None,
                name: Optional[str] = None) -> LayerOutput:
    """Debug layer printing its input's values at forward time — analog of
    print_layer (PrintLayer.cpp).  Identity in the dataflow (unlike the
    reference's sink, it passes through so it can sit mid-graph); the print
    happens on-device via jax.debug.print, so it works under jit."""
    name = name or next_name("print")
    # the label is literal text, not a format spec: escape braces so a
    # message like "step {t}" can't crash the jax.debug.print formatter
    msg = (message or name).replace("{", "{{").replace("}", "}}")

    def forward(ctx, params, a: Act) -> Act:
        jax.debug.print(msg + ": {}", a.value)
        return a

    out = LayerOutput(name, "print", input.size, [input], forward, [])
    _inherit_meta(out, input)
    return out


from paddle_tpu.config.capture import wrap_module as _wrap_module

_wrap_module(globals(), __all__)
