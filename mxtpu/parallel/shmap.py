"""``shard_map`` for the parallel layer (ring attention, pipeline): one
call form over ``jax.shard_map``, replica checking off by default."""
from __future__ import annotations

import jax

__all__ = ["shard_map"]


def shard_map(fn, mesh, in_specs, out_specs, check_vma=False):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
