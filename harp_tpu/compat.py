"""The one jax spelling this package pins for every SPMD program.

``shard_map`` with the varying-manual-axes check off: collectives here
return values the checker cannot prove replicated (a psum'd carry re-enters
a scan as a replicated input), and every program states its own
``out_specs``. One installed jax (0.9.0) is supported; there are no
version branches.
"""

from __future__ import annotations

import functools

import jax

shard_map = functools.partial(jax.shard_map, check_vma=False)
