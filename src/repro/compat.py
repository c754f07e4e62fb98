"""``shard_map`` with the repo's default of replication checking off.

The collectives in the streaming executor and the MoE layer produce
values that are replicated by construction (psum/pmax results), which
the checker cannot always prove; every call site opts out the same way.
"""

from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with replication checking off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)
