"""The main path's kernels, compiled at real widths for a TPU v5e that is
described, not attached (section 2 of the on-chip-measurement guide).

Nothing runs: each case asserts that the installed TPU compiler accepts the
program and that a Mosaic kernel (``tpu_custom_call``) is in the compiled
text — interpret mode passes shapes the chip's compiler refuses (scoped VMEM,
tile alignment).  The topology is described inside a module-scoped fixture
(never at import: only one process may load libtpu, and every xdist worker
imports this file), the compiles run in the test's own process, and JAX's
persistent cache is off around them (such an entry cannot be read back
without a chip).  The gates ask ``jax.default_backend()``; the tests steer
them with monkeypatch, and compile under the production bf16 policy.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture
def no_persistent_jax_cache():
    """These compiles ask the TPU compiler a question; an answer served
    from JAX's persistent cache would not be the compiler's."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


pytestmark = pytest.mark.usefixtures("no_persistent_jax_cache")

T_RNN = 100
#: the reference's published LSTM rows (BASELINE.md) + a remat row's B512
RNN_SHAPES = [(64, 256), (64, 512), (64, 1280), (128, 256), (256, 256),
              (512, 256)]
B, S, T, D, V = 384, 32, 32, 512, 30000   # the flagship train step


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Gates take their TPU branch, under the production compute policy."""
    from paddle_tpu.utils.flags import FLAGS

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(FLAGS, "compute_dtype", "bfloat16")


def _struct(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _first_result(call: str) -> str:
    """``bf16[20,256,2048]`` of ``%name = (bf16[20,256,2048]{...}, ...) op(``."""
    return call.split(" = (")[1].split("{")[0]


def _kernels(fn, *args) -> int:
    """Compile ``fn`` for the described chip; Mosaic kernels in its text."""
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


@pytest.mark.parametrize("batch,hidden", RNN_SHAPES,
                         ids=[f"b{b}h{h}" for b, h in RNN_SHAPES])
@pytest.mark.parametrize("cell", ["lstm", "gru"])
def test_rnn_forward_and_gradient(cell, batch, hidden, one_chip, on_tpu):
    """Every published row: both gates admit it, and the forward and the
    reverse time-loop kernels compile (peepholes live — the widest LSTM
    variant)."""
    from paddle_tpu.ops import rnn_fused

    gates = 4 if cell == "lstm" else 3
    assert rnn_fused.rnn_kernel_ok(batch, hidden, gates)
    assert rnn_fused.rnn_kernel_ok(batch, hidden, gates, backward=True)
    zeros = jnp.zeros((batch, hidden), jnp.float32)

    def lstm_loss(xp, mask, w_h, pi, pf, po):
        h, h_f, c_f = rnn_fused.lstm_sequence_fused(
            xp, jnp.zeros((4 * hidden,), jnp.float32), mask, w_h, zeros,
            zeros, pi, pf, po, True, True)
        return h.sum() + h_f.sum() + c_f.sum()

    def gru_loss(xp, mask, w_h):
        h, h_f = rnn_fused.gru_sequence_fused(xp, mask, w_h, zeros, True)
        return h.sum() + h_f.sum()

    s = lambda *shape: _struct(one_chip, shape)  # noqa: E731
    args = [s(batch, T_RNN, gates * hidden), s(batch, T_RNN),
            s(hidden, gates * hidden)]
    if cell == "lstm":
        args += [s(hidden)] * 3
        fn = jax.value_and_grad(lstm_loss, argnums=(0, 2, 3, 4, 5))
    else:
        fn = jax.value_and_grad(gru_loss, argnums=(0, 2))
    assert _kernels(fn, *args) == 2


def test_rnn_gate_bounds_the_resident_weight(on_tpu):
    """What the gate admits it has counted: the [H, gates*H] weight grows
    with H^2 and is refused once it alone outgrows the scoped limit, however
    small B*H is; the estimate matches what the compiler reports for the
    LSTM reverse kernel, peepholes live and ``d_z`` float32 (its widest
    variant, which the gate counts), when asked to fit it into 2 MiB: 33.45
    MiB at B64 H1280 (float32 residuals), and with bf16 residuals 19.88 MiB
    at the gate's corner B384 H512 and 14.63 MiB at the benchmark cell's
    tile B256 H512 (with ``d_z`` in bf16 too, as where the op owns the
    projection, the compiler says 16.88 and 12.63)."""
    from paddle_tpu.ops.pallas_kernels import (RNN_VMEM_LIMIT_BYTES,
                                               rnn_vmem_bytes)
    from paddle_tpu.ops.rnn_fused import rnn_kernel_ok

    for batch, hidden, itemsize, compiler_mib in [
            (64, 1280, 4, 33.45), (384, 512, 2, 19.88), (256, 512, 2, 14.63)]:
        need = rnn_vmem_bytes(batch, hidden, 4, backward=True,
                              residual_itemsize=itemsize)
        assert abs(need / 2**20 - compiler_mib) < 0.01 * compiler_mib
        assert need < RNN_VMEM_LIMIT_BYTES
    assert rnn_kernel_ok(72, 1792, 4, backward=True)
    assert not rnn_kernel_ok(8, 2048, 4)                  # 64 MiB of weight
    assert rnn_kernel_ok(96, 2048, 3, backward=True)
    assert not rnn_kernel_ok(392, 512, 3)                 # past B*H cap
    assert not rnn_kernel_ok(64, 200, 4)                  # lane-misaligned


@pytest.mark.parametrize("batch,hidden,cell", [
    (152, 1280, "lstm"), (80, 1792, "lstm"), (384, 512, "lstm"),
    (96, 2048, "gru"), (16, 2304, "gru")])
def test_rnn_gate_edge_compiles(batch, hidden, cell, one_chip, on_tpu):
    """The largest shapes the reverse-kernel gate admits at a few widths:
    every shape a gate admits must compile, not only the published ones."""
    from paddle_tpu.ops import rnn_fused

    gates = 4 if cell == "lstm" else 3
    assert rnn_fused.rnn_kernel_ok(batch, hidden, gates, backward=True)
    assert not rnn_fused.rnn_kernel_ok(batch + 8, hidden, gates,
                                       backward=True)
    zeros = jnp.zeros((batch, hidden), jnp.float32)
    s = lambda *shape: _struct(one_chip, shape)  # noqa: E731

    def loss(xp, mask, w_h, *peep):
        if cell == "lstm":
            out = rnn_fused.lstm_sequence_fused(
                xp, jnp.zeros((4 * hidden,), jnp.float32), mask, w_h, zeros,
                zeros, *peep, True, True)
        else:
            out = rnn_fused.gru_sequence_fused(xp, mask, w_h, zeros, True)
        return sum(o.sum() for o in out)

    args = [s(batch, 20, gates * hidden), s(batch, 20),
            s(hidden, gates * hidden)]
    args += [s(hidden)] * 3 if cell == "lstm" else []
    assert _kernels(jax.value_and_grad(loss, argnums=(0, 2)), *args) == 2


@pytest.mark.parametrize("peepholes", [True, False],
                         ids=["peepholes", "plain"])
@pytest.mark.parametrize("batch", [256, 384])
def test_lstm_reverse_kernel_reduces_bias_and_peepholes(batch, peepholes,
                                                        one_chip, on_tpu):
    """The LSTM benchmark cell's tile (B256 H512) and the gate's corner
    (B384 H512), both variants of the reverse kernel: it compiles, the bias
    gradient leaves it as ``f32[1,4H]`` and the peephole gradients as
    ``f32[3,H]``, ``d_z``, its first result, leaves as ``bf16[T,B,4H]`` when
    asked for so, and it writes no ``[T,B,H]`` stream beside it."""
    from paddle_tpu.ops import pallas_kernels, rnn_fused

    hidden, steps = 512, 20
    assert rnn_fused.rnn_kernel_ok(batch, hidden, 4, backward=True)
    s = lambda *shape, dt=jnp.float32: _struct(one_chip, shape, dt)  # noqa: E731
    rd = rnn_fused.residual_dtype(hidden)
    assert rd == jnp.bfloat16

    def reverse(*args):
        return pallas_kernels._lstm_bwd_pallas_raw(
            *args, has_peepholes=peepholes, dz_dtype=rd)

    text = jax.jit(reverse).lower(
        s(steps, batch, hidden), s(steps, batch),
        s(steps, batch, 4 * hidden, dt=rd), s(steps, batch, hidden, dt=rd),
        s(4 * hidden, hidden), s(1, hidden), s(1, hidden), s(1, hidden),
        s(batch, hidden), s(batch, hidden)).compile().as_text()
    call = next(line for line in text.splitlines()
                if "tpu_custom_call" in line and "lstm_seq_bwd" in line)
    results = call.split(" custom-call(")[0]
    assert _first_result(call) == f"bf16[{steps},{batch},{4 * hidden}]"
    assert f"f32[1,{4 * hidden}]" in results
    assert (f"f32[3,{hidden}]" in results) == peepholes
    assert f"f32[{steps},{batch},{hidden}]" not in results


def test_lstm_stack_backward_keeps_d_z_narrow(one_chip, on_tpu):
    """Two LSTM layers at the benchmark cell's tile (B256 H512, embedding
    128), each with its input projection: in the optimised program both
    ``lstm_seq_bwd`` calls hand ``d_z`` on as ``bf16[T,B,4H]``, and nothing
    in the whole step produces a float32 array of that size, in either
    layout.  Forward: both ``lstm_seq_fwd`` calls take the layer's input as
    ``f32[T,B,D]`` and make the projection themselves.  Backward: the
    widening that the projection's transpose asks of ``d_xp`` fuses into the
    operand reads of ``dx`` and ``dW_x``."""
    from paddle_tpu.ops.rnn import lstm_layer

    batch, steps, emb, hidden = 256, 24, 128, 512
    s = lambda *shape: _struct(one_chip, shape)  # noqa: E731
    layer = lambda d: dict(  # noqa: E731
        wx=s(d, 4 * hidden), wh=s(hidden, 4 * hidden), b=s(4 * hidden),
        pi=s(hidden), pf=s(hidden), po=s(hidden))

    def loss(x, mask, layers):
        for p in layers:
            x, _ = lstm_layer(x, mask, p["wx"], p["wh"], p["b"],
                              peep_i=p["pi"], peep_f=p["pf"], peep_o=p["po"])
        return (x * x).sum()

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 2))).lower(
        s(batch, steps, emb), s(batch, steps),
        [layer(emb), layer(hidden)]).compile().as_text()
    entry = text[text.index("\nENTRY "):].splitlines()
    calls = lambda name: [  # noqa: E731
        line for line in entry if "tpu_custom_call" in line and name in line]
    reverse, forward = calls("lstm_seq_bwd"), calls("lstm_seq_fwd")
    assert len(reverse) == 2 and len(forward) == 2
    for call in reverse:
        assert _first_result(call) == f"bf16[{steps},{batch},{4 * hidden}]"
    for call, d in zip(forward, (emb, hidden)):
        operands = call.split(" custom-call(")[1]
        assert f"f32[{steps},{batch},{d}]" in operands
        assert f"[{steps},{batch},{4 * hidden}]" not in operands
    wide = (f"f32[{steps},{batch},{4 * hidden}]",
            f"f32[{batch},{steps},{4 * hidden}]")
    made = sum(any(w in line.split(" = ")[1].split("(%")[0] for w in wide)
               for line in text.splitlines() if " = " in line)
    assert made == 0


@pytest.mark.parametrize("batch,hidden,in_dim", [
    (256, 512, 128), (256, 512, 512), (384, 512, 512), (64, 1280, 128)],
    ids=["cell_lstm0", "cell_lstm1", "gate_corner", "h1280"])
def test_lstm_forward_kernel_makes_the_projection(batch, hidden, in_dim,
                                                  one_chip, on_tpu,
                                                  monkeypatch):
    """The forward kernel that takes ``x`` [T,B,D], ``w_x`` and ``b`` in
    place of the projection, at the benchmark cell's two layers, the gate's
    corner and the widest published row (its first layer, on the embedding):
    the gate admits it, the compiler accepts it in a differentiated step
    (forward and reverse kernel; ``x`` and ``w_x`` into the forward one and
    no [T,B,4H] operand), and, asked to fit it into 2 MiB, reports the
    scoped VMEM that ``rnn_vmem_bytes`` counted, to within 1%."""
    from paddle_tpu.ops import pallas_kernels, rnn_fused

    steps = 20
    assert rnn_fused.rnn_kernel_ok(batch, hidden, 4, proj_dim=in_dim)
    zeros = jnp.zeros((batch, hidden), jnp.float32)
    s = lambda *shape: _struct(one_chip, shape)  # noqa: E731

    def loss(x, w_x, b, mask, w_h, *peep):
        return sum(o.sum() for o in rnn_fused.lstm_sequence_fused(
            x, b, mask, w_h, zeros, zeros, *peep, True, True, w_x))

    args = [s(batch, steps, in_dim), s(in_dim, 4 * hidden), s(4 * hidden),
            s(batch, steps), s(hidden, 4 * hidden)] + [s(hidden)] * 3
    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 4, 5, 6, 7)))
    text = step.lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    forward = next(line for line in text.splitlines()
                   if "tpu_custom_call" in line and "lstm_seq_fwd" in line)
    operands = forward.split(" custom-call(")[1]
    assert f"f32[{steps},{batch},{in_dim}]" in operands
    assert f"f32[{in_dim},{4 * hidden}]" in operands
    assert f"[{steps},{batch},{4 * hidden}]" not in operands

    need = pallas_kernels.rnn_vmem_bytes(
        batch, hidden, 4, backward=False, proj_dim=in_dim,
        residual_itemsize=jnp.dtype(rnn_fused.residual_dtype(hidden)).itemsize)
    monkeypatch.setattr(pallas_kernels, "RNN_VMEM_LIMIT_BYTES", 2 * 2**20)

    def kernel(x_tb, mask_tb, w_h, w_x, b, *peep):
        return pallas_kernels._lstm_pallas_raw(x_tb, mask_tb, w_h, *peep,
                                               w_x=w_x, b=b)

    with pytest.raises(Exception, match="exceeded scoped vmem limit") as e:
        jax.jit(kernel).lower(
            s(steps, batch, in_dim), s(steps, batch), s(hidden, 4 * hidden),
            s(in_dim, 4 * hidden), s(4 * hidden), *[s(hidden)] * 3).compile()
    said = float(re.search(r"Scoped allocation with size ([\d.]+)M",
                           str(e.value)).group(1))
    assert abs(need / 2**20 - said) < 0.01 * said


def test_lstm_projection_gate_is_a_function_of_the_shape(on_tpu):
    """Where the forward kernel makes the projection: from B = D/4 rows on
    (below that, streaming the input matrix through the MXU on every time
    step costs more than the [B,4H] block's way through HBM: measured, PR
    34), and where the [D,4H] matrix fits beside the recurrent one.  A shape
    it refuses keeps the kernel it had, handed the projection."""
    from paddle_tpu.ops.rnn_fused import rnn_kernel_ok

    for batch, hidden, in_dim, makes_it in [
            (256, 512, 128, True), (256, 512, 512, True),
            (64, 256, 256, True), (64, 1280, 128, True),
            (64, 512, 512, False), (64, 1280, 1280, False),
            (16, 1024, 1024, False),
            (152, 1280, 608, True),       # 50.9 MiB
            (144, 1280, 3072, False)]:    # 4 B < D; and 101 MiB
        assert rnn_kernel_ok(batch, hidden, 4)
        assert rnn_kernel_ok(batch, hidden, 4, proj_dim=in_dim) == makes_it
    assert rnn_kernel_ok(72, 1792, 4, proj_dim=128)
    assert not rnn_kernel_ok(72, 1792, 4, proj_dim=256)   # 65.0 MiB
    assert not rnn_kernel_ok(392, 512, 4, proj_dim=128)   # past B*H cap


def test_attention_decoder_forward_and_backward(one_chip, on_tpu):
    """The fused attention-GRU decoder at the flagship's train shape."""
    from paddle_tpu.ops.attention_decoder import (_attn_pallas_block,
                                                  attention_gru_decoder)

    assert _attn_pallas_block(B, S, D, D, 2 * D) is not None
    s = lambda *shape, dt=jnp.float32: _struct(one_chip, shape, dt)  # noqa: E731

    def loss(y_emb, s0, enc, enc_proj, att_w, att_v, wx, b, wh, smask, tmask):
        return attention_gru_decoder(y_emb, s0, enc, enc_proj, smask, tmask,
                                     att_w, att_v, wx, b, wh).sum()

    n = _kernels(
        jax.value_and_grad(loss, argnums=tuple(range(9))),
        s(B, T, D), s(B, D), s(B, S, 2 * D, dt=jnp.bfloat16),
        s(B, S, D, dt=jnp.bfloat16), s(D, D), s(D), s(D + 2 * D, 3 * D),
        s(3 * D), s(D, 3 * D), s(B, S), s(B, T))
    assert n >= 2


def test_vocab_tiled_ce_forward_and_backward(one_chip, on_tpu):
    """The fused readout + token CE over the 30k vocabulary."""
    from paddle_tpu.ops.losses import (_tiled_ce_cfg,
                                       sequence_softmax_ce_readout)

    assert _tiled_ce_cfg(B, T, D, V) is not None
    n = _kernels(
        jax.value_and_grad(sequence_softmax_ce_readout, argnums=(0, 1, 2)),
        _struct(one_chip, (B, T, D)), _struct(one_chip, (D, V)),
        _struct(one_chip, (V,)), _struct(one_chip, (B, T), jnp.int32),
        _struct(one_chip, (B, T)))
    assert n >= 2


def test_ce_gate_counts_the_weight_tiles(one_chip, on_tpu):
    """The backward keeps a double-buffered w tile and d_w tile that grow
    with D, not with N: the two shapes the compiler refuses (130.9 and
    131.1 MiB of a 128 MiB VMEM) are gated off, and the largest D=2048
    shape still admitted compiles."""
    from paddle_tpu.ops.losses import (_tiled_ce_cfg,
                                       sequence_softmax_ce_readout)

    assert _tiled_ce_cfg(192, 32, 2048, V) is None     # N=6144
    assert _tiled_ce_cfg(96, 32, 4096, V) is None      # N=3072
    # Nemotron-3-Nano's cell (PR 43): the compiler asked 113.6 MiB of 112
    assert _tiled_ce_cfg(1, 4096, 2688, 16384) is None
    assert _tiled_ce_cfg(160, 32, 2048, V) is not None  # N=5120
    n = _kernels(
        jax.value_and_grad(sequence_softmax_ce_readout, argnums=(0, 1, 2)),
        _struct(one_chip, (160, 32, 2048)), _struct(one_chip, (2048, V)),
        _struct(one_chip, (V,)), _struct(one_chip, (160, 32), jnp.int32),
        _struct(one_chip, (160, 32)))
    assert n >= 2


def test_topk_lse_readout(one_chip, on_tpu):
    """Beam-3 over B=64: the top-k + logsumexp readout at B*K = 192 rows."""
    from paddle_tpu.ops.decode import LinearReadout, decode_kernel_config

    assert decode_kernel_config(192, D, V, 3) is not None
    n = _kernels(lambda st, w, b: LinearReadout(w, b)(st, 3),
                 _struct(one_chip, (192, D)), _struct(one_chip, (D, V)),
                 _struct(one_chip, (V,)))
    assert n == 1


def test_slot_table_decode_step(one_chip, on_tpu):
    """The generation server's step program over the full-width flagship:
    weights as arguments, 8 slots x beam 3 (SlotScheduler's step_prog)."""
    from paddle_tpu.models import Seq2SeqAttention
    from paddle_tpu.ops.decode import decode_step, init_slot_carry
    from paddle_tpu.serving.slots import Seq2SeqSlotBackend

    m = Seq2SeqAttention()
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    backend = Seq2SeqSlotBackend(m, shapes, src_len=S, beam_size=3,
                                 max_len=32)
    state = jax.eval_shape(lambda p, f: backend.with_params(p).prefill(f),
                           shapes, backend.example_feed(1))
    carry = jax.eval_shape(lambda: init_slot_carry(
        state, slots=8, beam_size=3, max_len=32, eos=backend.eos))

    def step(p, c):
        b = backend.with_params(p)
        return decode_step(b.step_fn, b.readout, c,
                           vocab_size=backend.vocab_size, eos=backend.eos,
                           use_kernel=backend.use_kernel)

    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: _struct(one_chip, a.shape, a.dtype), tree)
    compiled = jax.jit(step).lower(place(shapes), place(carry)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 1
    # the weights ride as arguments: none is folded into the executable
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(shapes))
    assert compiled.memory_analysis().generated_code_size_in_bytes \
        < weights // 8


# -- the decoder-only block (PR 28): LFM2-24B-A2B's published widths, the ----
# -- benchmark cell's row of 8192 tokens and its share of 8 of 64 experts ----

LFM2 = dict(T=8192, D=2048, H=32, Hkv=8, dh=64, F=1536, held=8, top_k=4)


def test_causal_attention_kernels(one_chip, on_tpu):
    """The gate admits the cell's row, and the forward and the one backward
    flash kernel (dk and dv of a key-value head's 8192 rows resident, its
    four query heads adding into them) compile with the heads of 64 the
    configuration has."""
    from paddle_tpu.ops import decoder_block as DB

    c = LFM2
    assert DB.attention_kernel_blocks(c["T"], c["dh"], c["H"], c["Hkv"])
    assert DB.attention_kernel_blocks(c["T"] + 8, c["dh"], c["H"],
                                      c["Hkv"]) is None

    def loss(q, k, v):
        return DB.causal_attention(q, k, v, scale=c["dh"] ** -0.5).sum()

    q = _struct(one_chip, (1, c["T"], c["H"], c["dh"]))
    kv = _struct(one_chip, (1, c["T"], c["Hkv"], c["dh"]))
    assert _kernels(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                    q, kv, kv) == 2


def test_grouped_expert_products(one_chip, on_tpu):
    """The dropless expert layer at the cell's sizes, with both of its row
    buffers (the usual one, twice the even share, and the worst routing's:
    every token sends all its choices here): in each, moe_gmm three times
    forward and three backward (transposed: the gate's input gradient, the
    two halves of dx) and moe_tgmm for the three weight gradients."""
    from paddle_tpu.ops import moe as M

    c = LFM2
    N, k = c["T"], c["top_k"]
    tm = M.moe_kernel_row_tile(c["D"], c["F"], N * k)
    assert tm == 256
    assert M.buffer_rows(N, k, 64, c["held"], tm) == (10240, 34816)

    def loss(x, w, w1, w3, w2, idx):
        return M.expert_layer(x, idx, w, w1, w3, w2, num_experts=64,
                              first_expert=0, tm=tm, kernels=True)[0].sum()

    s = lambda *shape: _struct(one_chip, shape)  # noqa: E731
    args = [s(N, c["D"]), s(N, k), s(c["held"], c["D"], c["F"]),
            s(c["held"], c["D"], c["F"]), s(c["held"], c["F"], c["D"]),
            _struct(one_chip, (N, k), jnp.int32)]
    assert _kernels(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)),
                    *args) == 18


# -- latent attention and 16 narrow experts (PR 35): Kanana-2-30B-A3B's ------
# -- published widths at the benchmark cell's row of 8192 tokens -------------

KANANA2 = dict(T=8192, D=2048, H=32, dqk=192, dv=128, F=768, held=16,
               top_k=6)


def test_causal_attention_kernels_with_a_value_width_of_its_own(one_chip,
                                                                on_tpu):
    """Keys and queries of 192 (one and a half lane tiles) beside values of
    128: the gate is a function of both widths, and the forward and the one
    backward kernel compile with every head its own key-value head, dk
    ``[8192, 192]`` and dv ``[8192, 128]`` resident.  A row of 65,536 keeps
    the kernels: its backward is three calls, each with as many keys' dk and
    dv resident as the reckoning allows (the longest the compiler is shown)."""
    from paddle_tpu.ops import decoder_block as DB
    from paddle_tpu.ops import pallas_kernels as PK

    c = KANANA2
    assert DB.attention_kernel_blocks(c["T"], c["dqk"], c["H"], c["H"],
                                      c["dv"])
    assert DB.attention_kernel_blocks(c["T"], c["dqk"], c["H"], c["H"],
                                      100) is None
    assert DB.attention_kernel_blocks(c["T"], 100, c["H"], c["H"],
                                      c["dv"]) is None

    def loss(q, k, v):
        return DB.causal_attention(q, k, v, scale=c["dqk"] ** -0.5).sum()

    qk = _struct(one_chip, (1, c["T"], c["H"], c["dqk"]))
    v = _struct(one_chip, (1, c["T"], c["H"], c["dv"]))
    assert _kernels(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                    qk, qk, v) == 2
    T = 65536
    assert PK.flash_bwd_key_rows(T, c["dqk"], c["dv"], 1024, 1024) == 22528
    assert DB.attention_kernel_blocks(T, c["dqk"], 2, 2, c["dv"])
    qk, v = (_struct(one_chip, (1, T, 2, w)) for w in (c["dqk"], c["dv"]))
    assert _kernels(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                    qk, qk, v) == 4


def test_grouped_expert_products_sixteen_narrow_experts(one_chip, on_tpu):
    """16 experts of 768 held of 128, 6 a token: 384 assignments an expert
    a step against the row tile of 256, both row buffers."""
    from paddle_tpu.ops import moe as M

    c = KANANA2
    N, k = c["T"], c["top_k"]
    tm = M.moe_kernel_row_tile(c["D"], c["F"], N * k)
    assert tm == 256
    assert M.buffer_rows(N, k, 128, c["held"], tm) == (16384, 53248)

    def loss(x, w, w1, w3, w2, idx):
        return M.expert_layer(x, idx, w, w1, w3, w2, num_experts=128,
                              first_expert=0, tm=tm, kernels=True)[0].sum()

    s = lambda *shape: _struct(one_chip, shape)  # noqa: E731
    args = [s(N, c["D"]), s(N, k), s(c["held"], c["D"], c["F"]),
            s(c["held"], c["D"], c["F"]), s(c["held"], c["F"], c["D"]),
            _struct(one_chip, (N, k), jnp.int32)]
    assert _kernels(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)),
                    *args) == 18


QWEN3NEXT = dict(T=8192, Hv=32, dk=128, dv=128, H=16, Hkv=2, dh=256)


def test_delta_rule_scan_kernels(one_chip, on_tpu):
    """The gate admits the cell's row of 8192 at heads of 128, and the
    forward and the reverse scan kernel (a head's 128 x 128 state, and its
    gradient, in VMEM scratch across 16 grid steps of 8 chunks) compile;
    interpret mode passed a broadcast from lane 63 that Mosaic refuses."""
    from paddle_tpu.ops import delta_rule as DR

    c = QWEN3NEXT
    assert DR.delta_rule_kernel_chunk(c["T"], c["dk"], c["dv"]) == 64
    assert DR.delta_rule_kernel_chunk(c["T"], 64, c["dv"]) is None
    assert DR.delta_rule_kernel_chunk(c["T"] + 8, c["dk"], c["dv"]) is None

    def loss(q, k, v, g, beta):
        return DR.delta_rule(q, k, v, g, beta).sum()

    wide = _struct(one_chip, (1, c["T"], c["Hv"], c["dk"]))
    one = _struct(one_chip, (1, c["T"], c["Hv"]))
    assert _kernels(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)),
                    wide, wide, wide, one, one) == 2


def _products(jaxpr, out=None):
    """Every ``dot_general`` of a jaxpr and its sub-jaxprs (a kernel's body
    is the ``jaxpr`` of its ``pallas_call``): ``[is it at HIGHEST]``."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            precision = eqn.params["precision"]
            out.append(jax.lax.Precision.HIGHEST in (
                precision if isinstance(precision, tuple) else (precision,)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _products(sub, out)
    return out


def test_delta_rule_reverse_kernel_solves_nothing(on_tpu):
    """A chunk's 64 x 64 solve is ten float32 products at ``highest``, six
    passes of the MXU each, and the forward kernel's body holds them: 17
    products a chunk, ten of them the solve's, in a block of 8 chunks.  The
    reverse kernel reads the forward's ``T`` (PR 55): 18 products a chunk,
    one pass each, and not one at ``highest``."""
    from paddle_tpu.ops import delta_rule as DR
    from paddle_tpu.ops import pallas_kernels as PK

    c = QWEN3NEXT
    per, n = DR.KERNEL_BLOCK_CHUNKS, c["T"] // DR.CHUNK
    bf16 = jnp.bfloat16
    key = jax.ShapeDtypeStruct((1, c["Hv"] // 2, c["T"], c["dk"]), bf16)
    wide = jax.ShapeDtypeStruct((1, c["Hv"], c["T"], c["dv"]), bf16)
    row = jax.ShapeDtypeStruct((1, c["Hv"], n, DR.CHUNK), jnp.float32)
    forward = jax.make_jaxpr(PK.gdn_chunk_fwd_pallas)(key, key, wide, row, row)
    _, states, solves = forward.out_avals
    assert solves.shape == (1, c["Hv"], DR.CHUNK, c["T"])      # lane-dense
    products = _products(forward.jaxpr)
    assert (len(products), sum(products)) == (17 * per, 10 * per)
    reverse = jax.make_jaxpr(PK.gdn_chunk_bwd_pallas)(
        key, key, wide, row, row, states, solves, wide)
    products = _products(reverse.jaxpr)
    assert (len(products), sum(products)) == (18 * per, 0)


def test_delta_net_prep_kernels(one_chip, on_tpu):
    """The gate of ``gdn_prep_fwd`` / ``gdn_prep_bwd`` opens at the cell's
    shape (16 key and 32 value heads of 128, 4 taps, a row of 8192 in blocks
    of 512 rows), and the pair compiles with forward and gradient beside
    the scan's: a block of 512 x 512 float32 with its halo rows, the taps
    read from VMEM scratch at row offsets that are not multiples of a tile,
    which interpret mode cannot refuse."""
    from paddle_tpu.ops import delta_rule as DR

    c = QWEN3NEXT
    Hk, taps = c["Hv"] // 2, 4
    rows = DR.prep_kernel_rows(c["T"], Hk, c["Hv"], c["dk"], c["dv"], taps)
    assert rows == 512
    assert DR.prep_kernel_rows(c["T"], Hk, c["Hv"], 64, c["dv"], taps) is None
    assert DR.prep_kernel_rows(c["T"] + 64, Hk, c["Hv"], c["dk"], c["dv"],
                               taps) is None
    columns = 2 * Hk * c["dk"] + c["Hv"] * c["dv"]

    def loss(x, kernel, g, beta):
        return DR.conv_delta_rule(x, kernel, g, beta, key_head_dim=c["dk"],
                                  value_head_dim=c["dv"], rows=rows).sum()

    one = _struct(one_chip, (1, c["T"], c["Hv"]))
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3))).lower(
        _struct(one_chip, (1, c["T"], columns)),
        _struct(one_chip, (taps, columns)), one, one).compile().as_text()
    assert text.count("tpu_custom_call") == 4
    for name in ("gdn_prep_fwd", "gdn_prep_bwd", "gdn_chunk_fwd",
                 "gdn_chunk_bwd"):
        assert name in text, name


def test_causal_attention_kernels_at_heads_of_256(one_chip, on_tpu):
    """Qwen3-Next's full-attention layer: 16 query heads of 256 over 2
    key-value heads, through the same two flash kernels."""
    from paddle_tpu.ops import decoder_block as DB

    c = QWEN3NEXT
    assert DB.attention_kernel_blocks(c["T"], c["dh"], c["H"], c["Hkv"])

    def loss(q, k, v):
        return DB.causal_attention(q, k, v, scale=c["dh"] ** -0.5).sum()

    q = _struct(one_chip, (1, c["T"], c["H"], c["dh"]))
    kv = _struct(one_chip, (1, c["T"], c["Hkv"], c["dh"]))
    assert _kernels(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                    q, kv, kv) == 2


def test_qwen3next_cell_step_fits_the_chip(one_chip, on_tpu):
    """The cell's whole step as its trainer builds it (``_cell_step``,
    below) compiled for the described v5e from shapes alone: the compiler's
    own count of arguments, results and temporaries stays under 15 GB of the
    chip's 16, with the delta rule's, the delta nets' prep and the
    attention's kernels in the program, and every chunk's solve kept for the
    reverse walk beside the states (PR 55: 67 MB a delta net, ``f32[1, 32,
    64, 8192]``, a result of ``gdn_chunk_fwd`` and an operand of
    ``gdn_chunk_bwd``).  (That no ``q`` or ``k`` is repeated
    to 32 heads is asserted on the layer's jaxpr in tests/test_gdn_prep.py,
    at widths where the shape tells them from ``v``, ``z`` and ``o``: here
    all five are ``[1, 8192, 32, 128]``.)"""
    compiled = _cell_step(one_chip, "qwen3next-train-b1-t8192")
    text = compiled.as_text()
    assert "gdn_chunk_fwd" in text and "gdn_chunk_bwd" in text
    assert "gdn_prep_fwd" in text and "gdn_prep_bwd" in text
    assert "flash_attn_fwd" in text and "flash_attn_bwd" in text
    calls = _kernel_calls(text)
    assert calls["gdn_chunk_fwd"] == calls["gdn_chunk_bwd"] == 3
    results = [line.split(" custom-call(")[0] for line in text.split("\n")
               if "gdn_chunk_fwd" in line.split(" = ")[0]
               and " custom-call(" in line]
    assert len(results) == 3 and all("f32[1,32,64,8192]{" in r
                                     for r in results), results
    m = compiled.memory_analysis()
    assert 3 * 4 * 424_340_544 < m.argument_size_in_bytes     # p, m, v
    assert _held_bytes(compiled) < 15e9, _held_bytes(compiled)


# -- a state-space scan and experts of 1856 (PR 43): Nemotron-3-Nano-30B-A3B's
# -- published widths at the benchmark cell's row of 4096 tokens --------------

NEMOTRON = dict(T=4096, D=2688, H=64, P=64, G=8, N=128, F=1856, held=8,
                top_k=6, Hq=32, Hkv=2, dh=128)


def test_ssd_scan_kernels(one_chip, on_tpu):
    """The gate admits the cell's row of 4096 at 64 heads of 64 in 8 groups
    with a state of 128, and the forward and the reverse scan kernel (a
    group's 128 x 512 state, and its gradient, in VMEM scratch across 8 grid
    steps of 4 chunks of 128) compile, with x, B and C read where the layer
    keeps them (a group a block of lanes)."""
    from paddle_tpu.ops import ssd_scan as SS

    c = NEMOTRON
    assert SS.ssd_kernel_chunk(c["T"], c["P"], c["H"] // c["G"],
                               c["N"]) == 128
    assert SS.ssd_kernel_chunk(c["T"] + 8, c["P"], c["H"] // c["G"],
                               c["N"]) is None

    def loss(x, Bm, Cm, dt, A):
        return SS.ssd_scan(x, Bm, Cm, dt, A).astype(jnp.float32).sum()

    bf16 = jnp.bfloat16
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        _struct(one_chip, (1, c["T"], c["H"], c["P"]), bf16),
        _struct(one_chip, (1, c["T"], c["G"], c["N"]), bf16),
        _struct(one_chip, (1, c["T"], c["G"], c["N"]), bf16),
        _struct(one_chip, (1, c["T"], c["H"])),
        _struct(one_chip, (c["H"],))).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "ssd_chunk_fwd" in text and "ssd_chunk_bwd" in text


@pytest.mark.parametrize("proj", ["float32", "bfloat16"])
def test_mamba_prep_kernels(proj, one_chip, on_tpu):
    """The gate of ``mamba_prep_fwd`` / ``mamba_prep_bwd`` opens at the
    cell's shape (``[x | B | C]`` 6144 columns from column 4096 of the
    in-projection, 4 taps, a row of 4096 in blocks of 512 rows by 512
    columns), and a mixer from its input to the scan's output compiles with
    forward and gradient: the pair beside the scan's kernels, which read x
    and B and C as blocks of the pair's arrays.  The projection is float32 as
    the cell's product leaves it (halo blocks of 8 rows; x for the skip and
    the skip's gradient float32 beside bf16 arrays) or bfloat16 (a tile of
    16: ``--amp``, which no cell runs), the taps read from VMEM scratch at
    row offsets that are not multiples of a tile, which interpret mode
    cannot refuse."""
    from paddle_tpu.ops import pallas_kernels as PK
    from paddle_tpu.ops import ssd_scan as SS

    c = NEMOTRON
    inner, gn, taps = c["H"] * c["P"], c["G"] * c["N"], 4
    block = SS.prep_kernel_block(c["T"], c["H"], c["P"], c["G"], c["N"], taps,
                                 inner)
    assert block == (512, 512)
    assert SS.prep_kernel_block(c["T"], c["H"], c["P"], c["G"], c["N"], taps,
                                inner + 64) is None
    assert SS.prep_kernel_block(c["T"] + 128, c["H"], c["P"], c["G"], c["N"],
                                taps, inner) is None
    conv = inner + 2 * gn
    bf16 = jnp.bfloat16
    if proj == "bfloat16":      # the pair alone on a bf16 projection
        kw = dict(offset=inner, rows=block[0], cols=block[1], out_dtype=bf16)
        wide = _struct(one_chip, (1, c["T"], inner), bf16)
        narrow = _struct(one_chip, (1, c["T"], gn), bf16)

        def pair(zxbc, wb, dx, dskip, dB, dC):
            return (PK.mamba_prep_fwd_pallas(zxbc, wb, width=inner, **kw),
                    PK.mamba_prep_bwd_pallas(zxbc, wb, dx, dskip, dB, dC,
                                             **kw))

        text = jax.jit(pair).lower(
            _struct(one_chip, (1, c["T"], inner + conv), bf16),
            _struct(one_chip, (1, taps + 1, conv)), wide, wide, narrow,
            narrow).compile().as_text()
        assert text.count("tpu_custom_call") == 2
        assert "mamba_prep_fwd" in text and "mamba_prep_bwd" in text
        return

    def loss(u, w, kernel, bias, dt, A):
        z, x, y = SS.conv_ssd_scan(u, w, kernel, bias, dt, A, groups=c["G"],
                                   block=block)
        return sum(v.astype(jnp.float32).sum() for v in (z, x, y))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
        _struct(one_chip, (1, c["T"], c["D"]), bf16),
        _struct(one_chip, (c["D"], inner + conv)),
        _struct(one_chip, (taps, conv)), _struct(one_chip, (conv,)),
        _struct(one_chip, (1, c["T"], c["H"])),
        _struct(one_chip, (c["H"],))).compile().as_text()
    assert text.count("tpu_custom_call") == 4
    for name in ("mamba_prep_fwd", "mamba_prep_bwd", "ssd_chunk_fwd",
                 "ssd_chunk_bwd"):
        assert name in text, name
    assert not _wide_joins(text, f"{c['T']},{conv}]")


def _wide_joins(text: str, shape: str, scope: str = ""):
    """The compiled module's ``concatenate`` and ``pad`` instructions (fused
    ones too) that touch an array of ``shape`` under ``scope``."""
    return [line.strip()[:200] for line in text.split("\n")
            if re.search(r"[\]}] (concatenate|pad)\(", line)
            and shape in line and scope in line]


def test_grouped_expert_products_at_a_width_of_1856(one_chip, on_tpu):
    """8 experts of TWO matrices 2688 x 1856 held of 128, 6 a token: 1856 is
    29 x 64, no multiple of 128.  The gate opens, the width is tiled with a
    masked edge where it is a product's result (512 of 1856, 1024 of 1856)
    and taken whole where it is summed over, and in each row buffer moe_gmm
    runs twice forward and twice backward and moe_tgmm for the two weight
    gradients."""
    from paddle_tpu.ops import moe as M

    c = NEMOTRON
    N, k = c["T"], c["top_k"]
    tm = M.moe_kernel_row_tile(c["D"], c["F"], N * k)
    assert tm == 256
    assert M.moe_kernel_row_tile(c["D"], c["F"] + 32, N * k) is None
    assert M._largest_tile(c["F"], 512) == 512
    assert M._largest_tile(c["F"], 1024) == 1024
    assert M._largest_tile(c["D"], 512) == 384          # 2688 = 7 x 384
    assert M.buffer_rows(N, k, 128, c["held"], tm) == (5120, 26624)

    def loss(x, w, w1, w2, idx):
        return M.expert_layer(x, idx, w, w1, None, w2, num_experts=128,
                              first_expert=0, tm=tm, kernels=True,
                              expert_act="relu2")[0].sum()

    s = lambda *shape: _struct(one_chip, shape)  # noqa: E731
    args = [s(N, c["D"]), s(N, k), s(c["held"], c["D"], c["F"]),
            s(c["held"], c["F"], c["D"]),
            _struct(one_chip, (N, k), jnp.int32)]
    assert _kernels(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)),
                    *args) == 12


def _w1_layout_copies(text: str):
    """The compiled module's ``copy`` instructions that move the relu^2
    experts' first matrix between layouts: any of its stored shape
    ``[8, 2688, 1856]`` (kept ``{1,2,0}``, so one to ``{2,1,0}`` feeds a
    Mosaic call and one to ``{1,2,0}`` comes from one), and any of its view
    ``[8, 1856, 2688]`` into another layout than the default (``w2`` has the
    view's shape: a copy of its new value into the donated buffer, default
    to default, moves no layout and is the toy step's own)."""
    return [line.strip()[:160] for line in text.split("\n") if re.search(
        r"= f32\[8,(2688,1856\]\{|1856,2688\]\{(?!2,1,0))[^}]*\} copy\(",
        line)]


def _expert_layer_step(one_chip, D, F):
    """One relu^2 expert layer as the Nemotron cell steps it, lowered for
    the described chip: 4096 tokens, top 6 of 128 with 8 held, value and
    gradient under ``jax.checkpoint``, per-leaf Adam, the leaves and their
    slots donated."""
    from paddle_tpu.ops import moe as M
    from paddle_tpu.param.optimizers import Adam

    c = NEMOTRON
    N, k, held = c["T"], c["top_k"], c["held"]
    tm = M.moe_kernel_row_tile(D, F, N * k)
    assert tm == 256
    opt = Adam(learning_rate=1e-3)

    @jax.checkpoint
    def layer(p, x, w, idx):
        return M.expert_layer(x, idx, w, p["w1"], None, p["w2"],
                              num_experts=128, first_expert=0, tm=tm,
                              kernels=True, expert_act="relu2")[0]

    def step(p, state, x, w, idx):
        value, grads = jax.value_and_grad(
            lambda p: layer(p, x, w, idx).sum())(p)
        return (value,) + opt.update(p, grads, state)

    params = {"w1": _struct(one_chip, (held, D, F)),
              "w2": _struct(one_chip, (held, F, D))}
    state = jax.tree_util.tree_map(
        lambda a: _struct(one_chip, a.shape, a.dtype),
        jax.eval_shape(opt.init_state, params))
    return jax.jit(step, donate_argnums=(0, 1)).lower(
        params, state, _struct(one_chip, (N, D)), _struct(one_chip, (N, k)),
        _struct(one_chip, (N, k), jnp.int32))


def test_expert_layer_reads_w1_where_the_chip_keeps_it(one_chip, on_tpu,
                                                       monkeypatch):
    """PR 45.  The chip keeps ``w1`` ``f32[8, 2688, 1856]``, its Adam slots
    and the new values with the axis of 2688 minor (entry layout
    ``{1,2,0}``: 1856 is 14.5 lane tiles), and a Mosaic call takes the
    default layout: handed the leaf as stored, the step copies it before
    every grouped product and ``d_w1`` on its way to the update.  Through the
    transposed view it copies nothing of that shape; the same 12 kernels
    either way."""
    from paddle_tpu.ops import moe as M

    c = NEMOTRON
    text = _expert_layer_step(one_chip, c["D"], c["F"]).compile().as_text()
    entry = re.search(r"entry_computation_layout=\{[^\n]*", text).group(0)
    kept = set(re.findall(r"f32\[8,2688,1856\]\{(\d,\d,\d)", entry))
    assert kept == {"1,2,0"}, kept
    assert not _w1_layout_copies(text)
    assert text.count("tpu_custom_call") == 12
    monkeypatch.setattr(M, "_kept_transposed", lambda w: False)
    stored = _expert_layer_step(one_chip, c["D"], c["F"]).compile().as_text()
    assert len(_w1_layout_copies(stored)) >= 4
    assert stored.count("tpu_custom_call") == 12


def test_expert_layer_of_whole_lane_tiles_is_handed_its_leaves_as_stored(
        one_chip, on_tpu, monkeypatch):
    """An expert width that is whole lane tiles (LFM2's: D 2048, F 1536)
    never takes the view: the layer lowers to the same StableHLO with the
    choice switched off."""
    from paddle_tpu.ops import moe as M

    def lowered():
        text = _expert_layer_step(one_chip, 2048, 1536).as_text()
        assert text.count("tpu_custom_call") == 12
        # a Mosaic call's serialized body carries its call's source lines
        return re.sub(r'backend_config = "[^"]*"', "", text)

    assert not M._kept_transposed(jnp.zeros((8, 2048, 1536)))
    texts = []
    for choose in (M._kept_transposed, lambda w: False):
        monkeypatch.setattr(M, "_kept_transposed", choose)
        texts.append(lowered())
    assert texts[0] == texts[1]


def test_causal_attention_kernels_at_sixteen_query_heads_a_key_head(one_chip,
                                                                    on_tpu):
    """Nemotron-H's attention layer: 32 query heads of 128 over 2 key-value
    heads (16 a group; LFM2 runs 4, Qwen3-Next 8), through the same two
    flash kernels."""
    from paddle_tpu.ops import decoder_block as DB

    c = NEMOTRON
    assert DB.attention_kernel_blocks(c["T"], c["dh"], c["Hq"], c["Hkv"])

    def loss(q, k, v):
        return DB.causal_attention(q, k, v, scale=c["dh"] ** -0.5).sum()

    q = _struct(one_chip, (1, c["T"], c["Hq"], c["dh"]))
    kv = _struct(one_chip, (1, c["T"], c["Hkv"], c["dh"]))
    assert _kernels(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                    q, kv, kv) == 2


_cell_steps = {}


def _cell_step(one_chip, name):
    """A cell's whole step as its trainer builds it (the model's loss and
    gradient under its recomputation blocks, per-leaf Adam inside the
    bad-step guard, state donated) compiled for the
    described v5e from shapes alone, once a cell for the tests below (each
    asks for ``on_tpu``, so whichever runs first compiles under the same
    gates)."""
    if name in _cell_steps:
        return _cell_steps[name]
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import manifest

    import paddle_tpu.nn as nn
    from paddle_tpu.param.optimizers import Adam
    from paddle_tpu.resilience.guard import guarded_update

    cell = manifest.cell(name)
    cfg, T = cell["config"], cell["traffic"]["seq_len"]
    cost, extras = manifest.program(cfg).net(cfg)
    topo = nn.Topology([cost] + extras)
    params = {k: _struct(one_chip, spec.shape)
              for k, spec in topo.param_specs.items()}
    o = cfg["optimizer"]
    opt = Adam(learning_rate=o["learning_rate"], beta1=o["beta1"],
               beta2=o["beta2"], epsilon=o["epsilon"])
    opt_state = jax.tree_util.tree_map(
        lambda a: _struct(one_chip, a.shape, a.dtype),
        jax.eval_shape(opt.init_state, params))
    ids = (_struct(one_chip, (1, T), jnp.int32),
           _struct(one_chip, (1,), jnp.int32))

    def step(params, opt_state, feed):
        def loss(p):
            outs, _ = topo.apply(p, {}, feed, train=True)
            return outs[cost.name].value, [outs[e.name].value for e in extras]

        (value, counts), grads = jax.value_and_grad(loss, has_aux=True)(params)
        # the bad-step guard around the update, as ``SGDTrainer._build_step``
        # has it: every trainer cell sets ``guard_nonfinite``
        new_params, new_opt, _, gextras = guarded_update(
            lambda p, g, o, finite: opt.update(p, g, o, finite=finite),
            loss=value, grads=grads, params=params, opt_state=opt_state,
            new_state={}, old_state={})
        return value, counts, gextras, new_params, new_opt

    _cell_steps[name] = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt_state, {"tokens": ids, "next_tokens": ids}).compile()
    return _cell_steps[name]


def _nemotron_cell_step(one_chip):
    return _cell_step(one_chip, "nemotron3nano-train-b1-t4096")


def _held_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def test_nemotron_cell_step_fits_the_chip(one_chip, on_tpu):
    """The cell's whole step, the trainer's guard around its update: the
    compiler's own count of arguments, results and temporaries stays under
    13.3 GB of the chip's 16 (13.20 read, PR 48; 11.4 without the guard, PR
    43 and PR 44: the guard's norm needs every gradient before the first
    leaf is updated, 2.67 GB alive together; the guard as a ``lax.cond``
    read 13.27), with the scan's, the attention's and the grouped products'
    kernels in the program: no gate is closed.  Every parameter and both of
    its Adam slots come back in the buffer they went in by: the select that
    holds a bad step keeps no second copy of the state."""
    compiled = _nemotron_cell_step(one_chip)
    text = compiled.as_text()
    for name in ("ssd_chunk_fwd", "ssd_chunk_bwd", "flash_attn_fwd",
                 "flash_attn_bwd", "moe_gmm", "moe_tgmm"):
        assert name in text, name
    m = compiled.memory_analysis()
    state = 3 * 4 * 666_963_456                               # p, m, v
    assert state < m.argument_size_in_bytes
    assert state <= m.alias_size_in_bytes, m.alias_size_in_bytes
    assert _held_bytes(compiled) < 13.3e9, _held_bytes(compiled)


def _layout_copies(text: str, shape: str):
    """The compiled module's ``copy`` instructions of an ``f32`` array of
    ``shape`` (``"2688,10304"``), whatever the two layouts."""
    return [line.strip()[:160] for line in text.split("\n") if re.search(
        r"= f32\[" + shape + r"\]\{[^}]*\} copy\(", line)]


def test_nemotron_cell_step_copies_no_expert_matrix(one_chip, on_tpu):
    """PR 45: the step without the trainer's guard held 24 ``copy``
    instructions of ``w1``'s shape in the parent, between the layout the leaf
    is kept in and the one a Mosaic call takes; through the transposed view
    there is none, and ``moe_gmm`` / ``moe_tgmm`` are called as often (8
    expert-layer row buffers of 4 and 2: both branches of a layer's
    ``lax.cond``).  PR 48: this IS the trainer's step, guard and all, and it
    holds none of the mixers' ``W_in`` ``[2688, 10304]`` either: the guard
    holds a bad step by a select inside each leaf's update, where as a
    ``lax.cond`` over parameters, gradients and slots it took each leaf the
    chip keeps transposed in the default layout and handed it back in it,
    seven copies a leaf (28 + 28 in this module, 21.6 ms a step on the
    chip)."""
    text = _nemotron_cell_step(one_chip).as_text()
    assert not _w1_layout_copies(text)
    assert not _layout_copies(text, "2688,10304")
    assert not _layout_copies(text, "10304,2688")
    calls = re.findall(r"%(moe_gmm|moe_tgmm)[.\d]* = [^\n]*? custom-call\(",
                       text)
    assert (calls.count("moe_gmm"), calls.count("moe_tgmm")) == (48, 16)


def test_nemotron_cell_step_keeps_the_mixers_way_to_the_scan_on_kernels(
        one_chip, on_tpu):
    """PR 44: the four mixers' way from ``W_in``'s product to the scan is
    ``mamba_prep_fwd`` (8 calls: every block is recomputed) and
    ``mamba_prep_bwd`` (4) beside the scan's ``ssd_chunk_fwd`` (4: its
    outputs are kept across the block) and ``ssd_chunk_bwd`` (4), and in the
    mixers' scopes nothing concatenates or pads an array of the convolved
    columns' ``[1, 4096, 6144]`` or of the projection's ``[1, 4096, 10240]``
    (the parent's step pads ``dz`` and the convolution's gradient to 10240
    columns and the convolution's input by its history)."""
    text = _nemotron_cell_step(one_chip).as_text()
    calls = re.findall(r"%\S+ = [^\n]*? custom-call\([^\n]*?"
                       r'custom_call_target="tpu_custom_call"[^\n]*?'
                       r'op_name="([^"]*)"', text)
    by_kernel = {}
    for op_name in calls:
        kernel = op_name.rsplit("/", 2)[-2]
        by_kernel.setdefault(kernel, []).append(op_name)
    assert {k: len(v) for k, v in by_kernel.items() if k.startswith(
        ("mamba_prep", "ssd_chunk"))} == {
            "mamba_prep_fwd": 8, "mamba_prep_bwd": 4, "ssd_chunk_fwd": 4,
            "ssd_chunk_bwd": 4}
    for kernel, scope in (("mamba_prep_fwd", "mamba_proj"),
                          ("mamba_prep_bwd", "mamba_proj"),
                          ("ssd_chunk_fwd", "ssd_scan"),
                          ("ssd_chunk_bwd", "ssd_scan")):
        assert all("mamba" in op.partition(f"/{scope}/")[0]
                   and op.partition(f"/{scope}/")[2]
                   for op in by_kernel[kernel]), (kernel, by_kernel[kernel])
    for shape in ("4096,6144]", "4096,10240]"):
        assert not _wide_joins(text, shape, "mamba"), shape


#: Keye-VL-2.0-30B-A3B's attention at the cell's row (benchmark/configs/
#: keye-vl-2.0-30b-a3b-ep16.json): 32 query heads over 4 key-value heads of
#: 128, an indexer of 16 heads of 64 over one key head, 2048 kept
KEYE = dict(T=16384, D=2048, H=32, Hkv=4, dh=128, J=16, d=64, topk=2048)


def test_sparse_attention_kernels(one_chip, on_tpu):
    """The gate of learned sparse attention opens at the cell's shapes (tiles
    of 1024), and forward and gradient compile as five Mosaic calls: the
    indexer's scores, the selection (rows of 16384 scores, 128 rows a step,
    47 counting passes), the two flash kernels under the selection (the
    first row past 8192 through them: dk and dv of 16384 keys resident at
    128/128) and the indexer's loss with its gradient.  A row the selection's
    blocks do not divide, or an indexer head that is no multiple of 64,
    keeps to the XLA path."""
    from paddle_tpu.ops import sparse_attention as SA

    c = KEYE
    assert SA.sparse_kernel_blocks(c["T"], c["dh"], c["H"], c["Hkv"],
                                   c["d"]) == 1024
    assert SA.sparse_kernel_blocks(c["T"], c["dh"], c["H"], c["Hkv"],
                                   c["d"] + 32) is None
    assert SA.sparse_kernel_blocks(c["T"] + 64, c["dh"], c["H"], c["Hkv"],
                                   c["d"]) is None

    def loss(q, k, v, qI, kI, w):
        out, kl, kept = SA.sparse_attention(q, k, v, qI, kI, w,
                                            scale=c["dh"] ** -0.5,
                                            topk=c["topk"])
        return out.sum() + kl.sum(), kept

    args = (_struct(one_chip, (1, c["T"], c["H"], c["dh"])),
            _struct(one_chip, (1, c["T"], c["Hkv"], c["dh"])),
            _struct(one_chip, (1, c["T"], c["Hkv"], c["dh"])),
            _struct(one_chip, (1, c["T"], c["J"], c["d"])),
            _struct(one_chip, (1, c["T"], c["d"])),
            _struct(one_chip, (1, c["T"], c["J"])))
    text = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(6)), has_aux=True)).lower(
            *args).compile().as_text()
    assert text.count("tpu_custom_call") == 5
    for name in ("indexer_scores", "topk_select", "flash_attn_sel_fwd",
                 "flash_attn_sel_bwd", "indexer_loss"):
        assert name in text, name


def test_keye_cell_step_fits_the_chip(one_chip, on_tpu):
    """The cell ``keyevl2-train-b1-t16384``'s whole step compiled for the
    described v5e from shapes alone: the five kernels of learned sparse
    attention ONCE a layer (their results are kept across the recomputation
    block: the backward's second forward recomputes the projections, not the
    indexer, the selection, the attention or the indexer's loss), the
    grouped products beside them, and the compiler's own count of arguments,
    results and temporaries under 15.5 GB of the chip's 16.9 (15.05 read, PR
    47: of it 3-4 GB the expert layer's worst-routing branch, which is
    reserved and not run; the chip's own peak read 13.64)."""
    compiled = _cell_step(one_chip, "keyevl2-train-b1-t16384")
    calls = re.findall(r"%\S+ = [^\n]*? custom-call\([^\n]*?"
                       r'custom_call_target="tpu_custom_call"[^\n]*?'
                       r'op_name="([^"]*)"', compiled.as_text())
    by_kernel = {}
    for op_name in calls:
        kernel = op_name.rsplit("/", 2)[-2]
        by_kernel[kernel] = by_kernel.get(kernel, 0) + 1
    assert {k: by_kernel.get(k) for k in (
        "indexer_scores", "topk_select", "flash_attn_sel_fwd",
        "flash_attn_sel_bwd", "indexer_loss")} == {
            "indexer_scores": 4, "topk_select": 4, "flash_attn_sel_fwd": 4,
            "flash_attn_sel_bwd": 4, "indexer_loss": 4}
    assert "moe_gmm" in by_kernel and "moe_tgmm" in by_kernel
    m = compiled.memory_analysis()
    assert 3 * 4 * 314_396_160 < m.argument_size_in_bytes     # p, m, v
    assert _held_bytes(compiled) < 15.5e9, _held_bytes(compiled)


#: Laguna-XS.2's window layers at the cell's row (benchmark/configs/
#: laguna-xs.2-ep32.json): 64 query heads over 8 key-value heads of 128, a
#: window of 512
LAGUNA = dict(T=16384, H=64, Hkv=8, dh=128, window=512)


def _kernel_calls(text: str) -> dict:
    """kernel name -> Mosaic calls of that name in a compiled module."""
    calls = re.findall(r"%\S+ = [^\n]*? custom-call\([^\n]*?"
                       r'custom_call_target="tpu_custom_call"[^\n]*?'
                       r'op_name="([^"]*)"', text)
    by_kernel = {}
    for op_name in calls:
        kernel = op_name.rsplit("/", 2)[-2]
        by_kernel[kernel] = by_kernel.get(kernel, 0) + 1
    return by_kernel


def test_window_attention_kernels(one_chip, on_tpu):
    """The gate's band rule gives the cell's window layers blocks of 512
    (1024 without a window), and the two window kernels compile at 64 query
    heads over 8 key-value heads of 128 and a row of 16384 (dk and dv of
    16384 keys resident at 128/128), their grids holding the band's blocks
    alone: 2 key blocks a block of queries where the unwindowed kernels at
    the same blocks would walk 32 (a count of grid steps, not a timing)."""
    from paddle_tpu.ops import decoder_block as DB
    from paddle_tpu.ops import pallas_kernels as PK

    c = LAGUNA
    assert DB.attention_kernel_blocks(c["T"], c["dh"], c["H"],
                                      c["Hkv"]) == (1024, 1024)
    assert DB.attention_kernel_blocks(c["T"], c["dh"], c["H"], c["Hkv"],
                                      window=c["window"]) == (512, 512)
    assert PK.flash_bwd_key_rows(c["T"], c["dh"], c["dh"], 512,
                                 512) == c["T"]

    def loss(q, k, v):
        return DB.causal_attention(q, k, v, scale=c["dh"] ** -0.5,
                                   window=c["window"]).sum()

    step = jax.value_and_grad(loss, argnums=(0, 1, 2))
    q = _struct(one_chip, (1, c["T"], c["H"], c["dh"]))
    kv = _struct(one_chip, (1, c["T"], c["Hkv"], c["dh"]))
    grids = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids[eqn.params["name"]] = tuple(
                    eqn.params["grid_mapping"].grid)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(step)(q, kv, kv).jaxpr)
    nq, G = c["T"] // 512, c["H"] // c["Hkv"]
    assert grids == {"flash_attn_win_fwd": (1, c["H"], nq, 2),
                     "flash_attn_win_bwd": (1, c["Hkv"], G * nq, 2)}
    text = jax.jit(step).lower(q, kv, kv).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "flash_attn_win_fwd" in text and "flash_attn_win_bwd" in text


def test_laguna_cell_step_fits_the_chip(one_chip, on_tpu):
    """The cell ``lagunaxs2-train-b1-t16384``'s whole step compiled for the
    described v5e from shapes alone: the window kernels ONCE a window layer
    and the unwindowed flash kernels once a full layer (their results are
    kept across the recomputation block), the grouped products beside them,
    and the compiler's own count of arguments, results and temporaries
    under 16.3 GB of the chip's 16.9 (16.01 read, PR 50; the chip's own peak
    read 14.67: as on Keye's cell, the expert layer's worst-routing branch is
    reserved and not run)."""
    compiled = _cell_step(one_chip, "lagunaxs2-train-b1-t16384")
    by_kernel = _kernel_calls(compiled.as_text())
    assert {k: by_kernel.get(k) for k in (
        "flash_attn_win_fwd", "flash_attn_win_bwd", "flash_attn_fwd",
        "flash_attn_bwd")} == {
            "flash_attn_win_fwd": 3, "flash_attn_win_bwd": 3,
            "flash_attn_fwd": 2, "flash_attn_bwd": 2}
    assert "flash_attn_sel_fwd" not in by_kernel
    assert "moe_gmm" in by_kernel and "moe_tgmm" in by_kernel
    m = compiled.memory_analysis()
    assert 3 * 4 * 389_634_048 < m.argument_size_in_bytes     # p, m, v
    assert _held_bytes(compiled) < 16.3e9, _held_bytes(compiled)


def _instructions(text: str, scope: str):
    """``(op, result, operand shapes, op_name)`` of the compiled module's
    ``gather``, ``scatter`` and ``sort`` instructions and ``kCustom`` fusions
    (the TPU's gathers and scatters run as such) whose ``op_name`` lies
    under ``scope``.  Shapes without their layouts; an operand is looked up
    by its name, which is the module's own."""
    bare = lambda s: re.sub(r"\{[^}]*\}", "", s)  # noqa: E731
    shape_of, found = {}, []
    for line in text.split("\n"):
        m = re.match(r"\s*(?:ROOT )?(%\S+) = (.*?) ([\w-]+)\((.*)", line)
        if not m:
            continue
        name, result, op, rest = m.groups()
        shape_of[name] = bare(result)
        on = re.search(r'op_name="([^"]*)"', rest)
        if on is None or f"/{scope}/" not in on.group(1):
            continue
        if op == "fusion" and "kind=kCustom" in rest:
            op = "kCustom"
        if op in ("gather", "scatter", "sort", "kCustom"):
            found.append((op, bare(result), re.findall(
                r"%[^\s,)]+", rest.split(")")[0]), on.group(1)))
    return [(op, result, [shape_of.get(o, "?") for o in operands], on)
            for op, result, operands, on in found]


@pytest.mark.parametrize("cell,tokens,k,layers", [
    ("qwen3next-train-b1-t8192", 8192, 10, 4),
    ("keyevl2-train-b1-t16384", 16384, 8, 4),
    ("lagunaxs2-train-b1-t16384", 16384, 8, 4)])
def test_expert_cell_step_walks_no_assignments(cell, tokens, k, layers,
                                               one_chip, on_tpu):
    """PR 49.  A gather or a scatter of scalars runs on the chip as a walk,
    5-10 ns an element, and the expert layer's index work held three over
    the ``A = N k`` assignments a layer and pass: ``take_along_axis`` for
    the chosen scores (and a scatter-add into ``[N, E]`` behind a sort of
    ``A`` indices for its gradient), ``key[order]`` for the sorted keys and
    a scatter of ``order`` to the rows.  In the cell's compiled step nothing
    under ``moe_routing`` is a gather, a scatter or a ``kCustom`` fusion;
    under ``moe_grouping`` no gather makes ``A`` elements, no scatter takes
    ``A`` indices or updates and no ``kCustom`` fusion makes ``A`` elements
    (the buffer's rows are gathered from ``order`` ``[A]`` and the tokens'
    rows move as before: both by the buffer's ``M`` rows, which is what
    is left); and the selection's sort and the grouping's run once a layer,
    in the forward, not again in the recomputed one."""
    A = f"[{tokens * k}"
    text = _cell_step(one_chip, cell).as_text()
    routing = _instructions(text, "moe_routing")
    assert routing and all(i[0] == "sort" for i in routing), [
        i for i in routing if i[0] != "sort"][:4]
    grouping = _instructions(text, "moe_grouping")
    assert any(op == "gather" and operands[0].startswith("s32" + A)
               for op, _, operands, _ in grouping)      # the rows' one gather
    walks = [i for i in grouping
             if i[0] in ("gather", "kCustom") and A in i[1]
             or i[0] == "scatter" and any(A in o for o in i[2][1:])]
    assert not walks, walks[:4]
    sorts = [(result, on) for op, result, _, on in routing + grouping
             if op == "sort"]
    assert not [s for s in sorts if "rematted_computation" in s[1]]
    forward = [s for s in sorts if "transpose(" not in s[1]]
    assert len([s for s in forward if "/moe_routing/top_k" in s[1]]) == layers
    assert len([s for s in forward if "/moe_grouping/" in s[1]]) == layers
    # the backward's sorts are the row scatter-adds' own, over a buffer's rows
    assert not [s for s in sorts if "transpose(" in s[1] and A in s[0]]


def _top_level(text: str):
    """``(name, op, result, operand names, op_name)`` of the compiled
    module's instructions outside the fusions' bodies (what the chip runs
    one by one), shapes without their layouts."""
    bare = lambda s: re.sub(r"\{[^}]*\}", "", s)  # noqa: E731
    found, inside_fusion = [], False
    for line in text.split("\n"):
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            inside_fusion = head.group(1).startswith("fused_computation")
            continue
        m = re.match(r"\s*(?:ROOT )?(%\S+) = (.*?) ([\w-]+)\((.*)", line)
        if inside_fusion or not m:
            continue
        name, result, op, rest = m.groups()
        on = re.search(r'op_name="([^"]*)"', rest)
        found.append((name, op, bare(result), re.findall(
            r"%[^\s,)]+", rest.split(")")[0]), on.group(1) if on else ""))
    return found


def _turns(text: str, parts: str):
    """``(rotary_turn calls, what produced each call's operand, the other
    instructions under the scope rotary at an activation's size)`` of a
    compiled module, after asserting that no instruction the chip runs
    makes an array of one of the shapes ``parts`` (a part of a head of q or
    k, as the slices the rotary embedding was made of did)."""
    rows = _top_level(text)
    sliced = [r for r in rows if re.search(r"f32\[(%s)\]" % parts, r[2])]
    assert not sliced, sliced[:4]
    op_of = {name: op for name, op, _, _, _ in rows}
    under = [r for r in rows if "/rotary/" in r[4]
             and r[1] not in ("bitcast", "get-tuple-element")]
    turns = [r for r in under if "/rotary_turn/" in r[4]]
    assert all(r[1] == "custom-call" for r in turns)
    assert all(re.search(r"(attn|mla)\d+\)*/(mla_proj/)?rotary/", r[4])
               for r in turns), [r[4] for r in turns][:4]
    big = [r for r in under if r not in turns and re.search(
        r"\[1,\d+,\d+,\d+\]|\[1,\d+,\d{4,}\]", r[2])]
    return turns, [op_of.get(r[3][0]) for r in turns], big


def test_laguna_cell_step_turns_whole_heads_in_one_pass(one_chip, on_tpu):
    """PR 51.  The rotary embedding was slices of half a head (a quarter
    under YaRN's partial span), a negation and concatenations in float32,
    which the chip ran as ``slice_negate_fusion``s through half-lane reads
    and writes of the whole ``[T, H, dh]`` array, six a layer (3.16 ms each
    on the window layers, 2.44 on the full ones).  In the cell's compiled
    step it is the kernel ``rotary_turn`` under the scope ``rotary`` inside
    its layer's own, one call a pass (forward, recomputed, backward) for q
    and one for k: 5 layers x 2 x 3; no instruction makes a part of a head
    (64 of 128 in a window layer; 32 or 96 in a full one), and nothing is
    transposed on the way in: a head of 128 is whole lane tiles, the kernel
    takes its operand heads-major, so a forward call reads what the
    projection's fusion wrote and a backward call what the attention's
    backward kernel wrote.  What else runs under ``rotary`` at the
    activations' size is the backward's one convert-and-transpose an
    operand, which hands the projection's two backward products their bf16
    operand (XLA names it after the kernel's ``swapaxes``)."""
    text = _cell_step(one_chip, "lagunaxs2-train-b1-t16384").as_text()
    assert _kernel_calls(text).get("rotary_turn") == 30
    turns, sources, big = _turns(
        text, r"1,16384,(64|48|8),(64|32|96)|1,(64|48|8),16384,(64|32|96)")
    assert len(turns) == 30
    assert set(sources) <= {"fusion", "get-tuple-element"}, sources
    assert all(r[1] == "copy" for r in big) and len(big) <= 10, big[:4]


def test_latent_attention_turns_its_queries_in_place(one_chip, on_tpu):
    """PR 51.  Kanana-2's latent attention at the cell's shapes (32 query
    heads of 128 + 64, the rotary embedding on the last 64; a row of 8192):
    the layer's value and gradient compiled for the described chip hold two
    ``rotary_turn`` calls (forward, backward) over the WHOLE 192-wide query
    heads, where the layer sliced ``q_rope`` off, turned its halves
    (``[8192, 32, 32]`` slices: fourteen fusions of 0.82 ms a layer in the
    cell's step) and concatenated ``q_nope`` back.  A head of 192 is no
    whole lane tiles, so the kernel takes the token-major ``[T, 32 x 192]``
    in slabs of 384 lanes: the forward call reads what the projection
    wrote; the backward's ``dq``, which the attention's kernel writes
    heads-major, is transposed to it once.  The lone key head of 64 fills
    no slab and stays in XLA (2 MB a pass)."""
    import paddle_tpu.nn as nn
    from paddle_tpu.ops import decoder_block as DB

    T, D = 8192, 2048
    assert DB.rotary_kernel_blocks(T, 32, 192) == (False, 384, 512, 768)
    assert DB.rotary_kernel_blocks(T, 1, 64) is None
    nn.reset_naming()
    x = nn.data("x", size=D, is_seq=True)
    layer = nn.latent_attention(
        x, num_heads=32, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, rope_theta=1e6, name="mla0")
    topo = nn.Topology([layer])
    params = {k: _struct(one_chip, spec.shape)
              for k, spec in topo.param_specs.items()}
    feed = {"x": (_struct(one_chip, (1, T, D)),
                  _struct(one_chip, (1,), jnp.int32))}

    def loss(p, feed):
        return topo.apply(p, {}, feed, train=True)[0]["mla0"].value.sum()

    text = jax.jit(jax.value_and_grad(loss)).lower(
        params, feed).compile().as_text()
    assert _kernel_calls(text).get("rotary_turn") == 2
    turns, sources, big = _turns(text, r"1,8192,32,(32|64)|1,32,8192,(32|64)")
    assert all("[1,8192,6144]" in r[2] for r in turns)
    assert sorted(sources) == ["copy", "fusion"], sources
    assert [r[1] for r in big] == ["copy"], big[:4]



def test_ouro_cell_step_fits_the_chip(one_chip, on_tpu):
    """PR 54.  The cell ``ouro-train-b1-t4096``'s whole step compiled for the
    described v5e from shapes alone: six layers run four times over ONE set
    of weights.  The compiled step takes each leaf ONCE (71 parameters, 71
    x 2 Adam slots, the step counter and the feed: a leaf that four layers
    of the graph read is one argument) and every parameter and slot comes
    back in the buffer it went in by; 24 applications a step call the flash
    kernels once each (their results are kept across a recomputation
    block); each of the four exits is a recomputation block, so the head's
    forward kernel runs twice an exit and its backward once, the first
    decoder cell whose head runs the tiled pair at all; no instruction makes
    an exit's logits in float32.  The compiler's own count of arguments,
    results and temporaries stays under 14.0e9 bytes."""
    compiled = _cell_step(one_chip, "ouro-train-b1-t4096")
    text = compiled.as_text()
    by_kernel = _kernel_calls(text)
    assert {k: by_kernel.get(k) for k in (
        "flash_attn_fwd", "flash_attn_bwd", "ce_readout_fwd",
        "ce_readout_bwd")} == {
            "flash_attn_fwd": 24, "flash_attn_bwd": 24, "ce_readout_fwd": 8,
            "ce_readout_bwd": 4}
    assert by_kernel.get("rotary_turn") == 24 * 2 * 3
    assert not re.search(r"f32\[(1,)?4096,49152\]", text)
    m = compiled.memory_analysis()
    state = 3 * 4 * 509_661_185                               # p, m, v
    assert state < m.argument_size_in_bytes < state + 1e6
    assert state <= m.alias_size_in_bytes, m.alias_size_in_bytes
    entry = re.search(r"ENTRY [^\n]*\{\n(.*?)\n\}", text, re.S).group(1)
    assert len(re.findall(r" parameter\(\d+\)", entry)) == 3 * 71 + 1 + 4
    assert _held_bytes(compiled) < 14.0e9, _held_bytes(compiled)


def test_smallthinker_cell_step_fits_the_chip(one_chip, on_tpu):
    """PR 57.  The cell ``smallthinker-train-b1-t16384``'s whole step compiled
    for the described v5e from shapes alone: the window kernels ONCE a window
    layer and the unwindowed flash kernels once in the full layer (their
    results are kept across the recomputation block), at 28 query heads over
    4 key-value heads (a group of seven) and blocks of 1024, the band FIVE
    blocks a block of queries where Laguna-XS.2's is two; the grouped
    products beside them; no rotary pass in ``attn0`` (the full layer takes
    no positions) and q's and k's in each window layer, a call a pass; the
    router's layer ahead of its layer's attention in the program's order;
    and the compiler's own count of arguments, results and temporaries under
    the chip's 16.9 GB (the count is in PERF.md section 4)."""
    from paddle_tpu.ops import decoder_block as DB
    from paddle_tpu.ops import pallas_kernels as PK

    assert DB.attention_kernel_blocks(16384, 128, 28, 4) == (1024, 1024)
    assert DB.attention_kernel_blocks(16384, 128, 28, 4, window=4096) == (
        1024, 1024)
    assert PK.flash_band_blocks(16384, 1024, 1024, 4096) == 5
    assert PK.flash_band_blocks(16384, 512, 512, 512) == 2      # Laguna's
    compiled = _cell_step(one_chip, "smallthinker-train-b1-t16384")
    text = compiled.as_text()
    by_kernel = _kernel_calls(text)
    assert {k: by_kernel.get(k) for k in (
        "flash_attn_win_fwd", "flash_attn_win_bwd", "flash_attn_fwd",
        "flash_attn_bwd")} == {
            "flash_attn_win_fwd": 3, "flash_attn_win_bwd": 3,
            "flash_attn_fwd": 1, "flash_attn_bwd": 1}
    assert "moe_gmm" in by_kernel and "moe_tgmm" in by_kernel
    # q and k, forward, recomputed and backward, in three window layers
    assert by_kernel.get("rotary_turn") == 3 * 2 * 3
    turns = re.findall(r'op_name="([^"]*/rotary_turn/[^"]*)"', text)
    assert turns and not [t for t in turns if "attn0" in t]
    assert all(re.search(r"attn[123]\)*/rotary/", t) for t in turns)
    # the early router's own layer, its sort inside it
    names = re.findall(r'op_name="([^"]*)"', text)
    assert any("/moe0/moe_routing/" in n for n in names)
    assert any("/moe3/moe_routing/moe_grouping/" in n for n in names)
    m = compiled.memory_analysis()
    assert 3 * 4 * 370_547_200 < m.argument_size_in_bytes     # p, m, v
    print("smallthinker cell step: held bytes", _held_bytes(compiled),
          "argument", m.argument_size_in_bytes, "temp", m.temp_size_in_bytes)
    assert _held_bytes(compiled) < 16.3e9, _held_bytes(compiled)
