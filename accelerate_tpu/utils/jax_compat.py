"""The handful of jax names this package reaches for that have moved between
releases, bound ONCE to the installed API (jax 0.9) so a future rename is a
one-line edit here instead of a sweep through ``ops/``, ``parallel/`` and ``models/``.
"""

from __future__ import annotations

import jax

__all__ = ["axis_size", "current_abstract_mesh", "shard_map"]

#: The ambient mesh set by ``parallel.mesh.mesh_context`` (an EMPTY mesh — ``.empty``
#: True, no axis names — when no context is active).
current_abstract_mesh = jax.sharding.get_abstract_mesh

#: ``shard_map(f, mesh=, in_specs=, out_specs=, check_vma=, axis_names=)``.
shard_map = jax.shard_map

#: Static size of a named mesh axis inside a manual region.
axis_size = jax.lax.axis_size
