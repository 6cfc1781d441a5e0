"""paddle_tpu — a TPU-native deep-learning framework.

Brand-new framework with the capabilities of legacy PaddlePaddle
(dzhwinter/Paddle; see SURVEY.md): a declarative layer-graph front-end with
first-class variable-length sequence support (LSTM/GRU, attention NMT with
beam-search generation), CNNs, sparse embeddings, a full trainer / optimizer /
evaluator / checkpoint lifecycle, and distributed training — re-architected for
TPU: ops are JAX/XLA/Pallas, graphs compile to jitted pure functions, and the
reference's MultiGradientMachine + parameter-server tier becomes SPMD sharding
over a ``jax.sharding.Mesh`` with ICI collectives.
"""

import time as _time

_T0 = _time.perf_counter()   # the set-up record's first phase begins here

__version__ = "0.1.0"

from paddle_tpu.utils import FLAGS, logger
from paddle_tpu.utils.devices import init

__all__ = ["FLAGS", "logger", "init", "__version__"]

# ... and ends here: ``import``, from this file's first line to its last
# (obs/timeline.py SetupRecord; the subpackages that import the most,
# ``trainer``, ``nn`` and ``models``, add their own to the same phase)
from paddle_tpu.obs.timeline import setup_record as _setup_record

_setup_record().add("import", _T0)
