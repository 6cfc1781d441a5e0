from paddle_tpu.obs.timeline import setup_phase as _setup_phase

with _setup_phase("import"):   # the set-up record: this package's import
    from paddle_tpu.models.vision import (lenet5, smallnet, resnet_cifar,
                                          vgg_cifar)
    from paddle_tpu.models.text import (stacked_lstm_net, stacked_lstm_pp_net,
                                        convolution_net, lstm_benchmark_net)
    from paddle_tpu.models.seq2seq import Seq2SeqAttention
    from paddle_tpu.models.recommender import (movielens_net,
                                               movielens_feature_net)
    from paddle_tpu.models.image_bench import alexnet, googlenet
    from paddle_tpu.models.lfm2 import lfm2_moe_net
    from paddle_tpu.models.decoder import decoder_stack
    from paddle_tpu.models.kanana2 import kanana2_moe_net
    from paddle_tpu.models.qwen3_next import qwen3_next_net
    from paddle_tpu.models.nemotron_h import nemotron_h_net
    from paddle_tpu.models.keye_vl2 import keye_vl2_net
    from paddle_tpu.models.laguna import laguna_net
    from paddle_tpu.models.ouro import ouro_net
    from paddle_tpu.models.smallthinker import smallthinker_net
