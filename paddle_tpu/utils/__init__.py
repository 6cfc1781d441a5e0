from paddle_tpu.utils.flags import FLAGS, define_flag, parse_flags
from paddle_tpu.utils.log import logger, set_verbosity
from paddle_tpu.utils.registry import Registry
from paddle_tpu.utils.error import (
    PaddleTpuError,
    ConfigError,
    ShapeError,
    layer_scope,
)
from paddle_tpu.utils import devices

__all__ = [
    "FLAGS",
    "define_flag",
    "parse_flags",
    "logger",
    "set_verbosity",
    "Registry",
    "PaddleTpuError",
    "ConfigError",
    "ShapeError",
    "layer_scope",
    "devices",
]
