"""paddle_tpu.nn — symbolic layer DSL + graph compiler.

TPU-native replacement for the reference's gserver engine + Python layer DSL
(SURVEY.md §1.5, §1.10).  Build a DAG with layer functions, compile with
``Topology``, run the resulting pure functions under jit/pjit.
"""

from paddle_tpu.obs.timeline import setup_phase as _setup_phase

with _setup_phase("import"):   # the set-up record: this package's import
    from paddle_tpu.nn.graph import (
        Act,
        ParamAttr,
        ParamSpec,
        LayerOutput,
        Topology,
        reset_naming,
        naming_scope,
        device_pin,
    )
    from paddle_tpu.nn.layers import *  # noqa: F401,F403
    from paddle_tpu.nn.layers_extra import *  # noqa: F401,F403
    from paddle_tpu.nn.layers_extra2 import *  # noqa: F401,F403
    from paddle_tpu.nn.projections import *  # noqa: F401,F403
    from paddle_tpu.nn.layers_decoder import *  # noqa: F401,F403
    from paddle_tpu.nn.recurrent import (Memory, StaticInput, GeneratedInput,
                                         recurrent_group, beam_search,
                                         SequenceGenerator)
    from paddle_tpu.nn.steps import lstm_step, gru_step
    from paddle_tpu.nn import layers as layer
