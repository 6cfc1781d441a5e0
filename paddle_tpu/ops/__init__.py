"""paddle_tpu.ops — the kernel tier.

TPU-native equivalent of the reference's `hl_*` CUDA kernel library +
device-polymorphic Matrix ops (reference: paddle/cuda/, paddle/math/,
paddle/function/ — see SURVEY.md §1.1-1.3).  Pure JAX functions; hot fused
variants live in ops/pallas_kernels.py and are selected automatically on TPU.
"""

from paddle_tpu.ops.numerics import param_dtype, compute_dtype, acc_dtype, mxu_cast
from paddle_tpu.ops.matmul import matmul, linear
from paddle_tpu.ops.activations import ACTIVATIONS, get_activation, softmax, sequence_softmax
from paddle_tpu.ops.losses import (
    cross_entropy,
    soft_cross_entropy,
    binary_cross_entropy,
    multi_binary_label_cross_entropy,
    mse,
    huber,
    smooth_l1,
    rank_cost,
    token_count,
    masked_token_mean,
    sequence_cross_entropy,
    sequence_softmax_ce_readout,
    softmax_ce_readout_per_token,
)
from paddle_tpu.ops.sequence import (
    PACK_KEYS,
    segment_starts,
    segment_valid,
    segment_pool,
    segment_last,
    segment_first,
    segment_expand,
    mask_from_lengths,
    seq_pool_sum,
    seq_pool_avg,
    seq_pool_sqrt,
    seq_pool_max,
    seq_last,
    seq_first,
    seq_expand,
    seq_reverse,
    seq_concat,
    context_projection,
    context_projection_trainable,
)
from paddle_tpu.ops.conv import (
    conv2d,
    conv2d_transpose,
    max_pool2d,
    avg_pool2d,
    batch_norm,
    cmr_norm,
    bilinear_interp,
    maxout,
    global_avg_pool,
)
from paddle_tpu.ops.rnn import (lstm_step, gru_step, lstm_layer,
                               gru_layer, bigru_layer, scan_rnn)
from paddle_tpu.ops.attention import (
    additive_attention_scores,
    attend,
    dot_product_attention,
)
from paddle_tpu.ops.attention_decoder import attention_gru_decoder
from paddle_tpu.ops.decode import (
    LinearReadout,
    LogitsReadout,
    beam_decode,
    greedy_decode,
    beam_gather,
    decode_kernel_config,
    decode_step,
    spec_verify_step,
    init_slot_carry,
    write_slot,
    release_slot,
    extract_slot,
    restore_slot,
    finalize_slots,
)
from paddle_tpu.ops.speculative import (
    DraftProposer,
    NGramProposer,
    CallableDraftProposer,
    AdversarialProposer,
)
from paddle_tpu.ops.embedding import embedding_lookup, one_hot
from paddle_tpu.ops.sparse import (
    sparse_gather_matmul,
    sparse_to_dense,
    selective_columns_matmul,
    CsrMatrix,
    CscMatrix,
    csr_matmul,
    matmul_dense_csc,
)
from paddle_tpu.ops.crf import crf_log_likelihood, crf_nll, crf_decode
from paddle_tpu.ops.ctc import ctc_loss
from paddle_tpu.ops.misc import (
    row_sum,
    row_max,
    col_sum,
    top_k,
    max_id,
    batch_transpose,
    cos_sim,
    interpolation,
    outer_prod,
    tensor_bilinear,
    sum_cost,
    scaling,
    slope_intercept,
    power_op,
    dropout,
)
