"""The two SPMD names the parallel tier imports from one place.

``shard_map`` defaults ``check_vma`` off: the parallel bodies use manual
collectives whose replication the checker cannot prove.
"""

from __future__ import annotations

import functools

import jax
from jax import lax

__all__ = ["shard_map", "axis_size"]

shard_map = functools.partial(jax.shard_map, check_vma=False)
axis_size = lax.axis_size
