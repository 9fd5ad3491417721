"""ZeRO/FSDP-equivalent parameter sharding via GSPMD (SURVEY.md §2.2 ZeRO + FSDP rows).

The reference delegates ZeRO to DeepSpeed's C++ partitioned optimizer and FSDP to torch's C++
flat-parameter sharder. On TPU both collapse into *sharding annotations*: placing each param
leaf with a ``NamedSharding`` that splits one axis over the ``fsdp`` mesh axis makes XLA emit
the exact FSDP communication schedule (all-gather params for forward/backward, reduce-scatter
grads) automatically inside the jitted step — there is no wrapper class, no hooks, no flat
parameters. ZeRO stages map to *which* pytrees get the fsdp sharding:

- stage 1: optimizer state only (params/grads replicated)
- stage 2: optimizer state + grads (reduce-scatter; params replicated)
- stage 3: params too (== torch FULL_SHARD)

``min_weight_size`` mirrors FSDP's size-based auto-wrap policy (reference
``fsdp_utils.py``/``dataclasses.py:1449``): small leaves stay replicated since sharding them
costs more in collective latency than it saves in HBM.
"""

from __future__ import annotations

from typing import Any, Optional

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..utils.constants import FSDP_AXIS
from ..utils.dataclasses import FullyShardedDataParallelPlugin

__all__ = [
    "infer_fsdp_spec",
    "get_fsdp_shardings",
    "get_zero_specs",
    "shard_tree",
    "shard_params",
    "gather_full_params",
]


def infer_fsdp_spec(
    shape: tuple[int, ...],
    fsdp_size: int,
    min_weight_size: int = 2**10,
    existing_spec: Optional[PartitionSpec] = None,
) -> PartitionSpec:
    """Choose which axis of a param to shard over the fsdp mesh axis.

    Strategy (standard JAX FSDP recipe, cf. maxtext/t5x partitioning): shard the **largest**
    dimension divisible by ``fsdp_size`` that is not already sharded by another axis; leave
    small or indivisible params replicated. Composes with an existing (e.g. tensor-parallel)
    spec by filling the first free slot.
    """
    if fsdp_size <= 1 or int(np.prod(shape)) < min_weight_size:
        return existing_spec if existing_spec is not None else PartitionSpec()
    base = list(existing_spec) if existing_spec is not None else [None] * len(shape)
    # Already fsdp-sharded (possibly inside a multi-axis tuple entry): nothing to add.
    for entry in base:
        axes = entry if isinstance(entry, tuple) else (entry,)
        if FSDP_AXIS in axes:
            return PartitionSpec(*base)
    while len(base) < len(shape):
        base.append(None)
    # Largest-first axis order.
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for i in order:
        if base[i] is None and shape[i] % fsdp_size == 0:
            base[i] = FSDP_AXIS
            return PartitionSpec(*base)
    return PartitionSpec(*base) if existing_spec is not None else PartitionSpec()


def get_fsdp_shardings(
    params: Any,
    mesh: Mesh,
    plugin: Optional[FullyShardedDataParallelPlugin] = None,
    specs: Any = None,
) -> Any:
    """Tree of ``NamedSharding`` for a param pytree.

    ``specs`` optionally provides model-supplied PartitionSpecs (tensor-parallel plans); fsdp
    sharding is layered on top of them.
    """
    plugin = plugin or FullyShardedDataParallelPlugin()
    fsdp_size = mesh.shape[FSDP_AXIS] if plugin.shards_params else 1

    def _leaf(path, leaf, spec=None):
        shape = np.shape(leaf)
        pspec = infer_fsdp_spec(shape, fsdp_size, plugin.min_weight_size, existing_spec=spec)
        return NamedSharding(mesh, pspec)

    if specs is not None:
        return jax.tree_util.tree_map(
            lambda leaf, spec: _leaf(None, leaf, spec), params, specs
        )
    return jax.tree_util.tree_map(lambda leaf: _leaf(None, leaf), params)


def get_zero_specs(
    tree: Any,
    mesh: Mesh,
    plugin: Optional[FullyShardedDataParallelPlugin] = None,
) -> Any:
    """PartitionSpec tree sharding *any* state pytree over the fsdp axis (ZeRO-1/2).

    Unlike ``get_fsdp_shardings`` this ignores ``plugin.shards_params`` — it is the mechanism
    behind ZeRO stages 1/2, where params stay replicated but optimizer state (stage 1) and
    gradient buffers (stage 2) are partitioned along the data/fsdp axis (the DeepSpeed
    partitioned-optimizer analog, reference ``utils/dataclasses.py:1019-1448``). Each leaf's
    existing sharding (e.g. tensor-parallel dims) is composed with, not overwritten.
    """
    plugin = plugin or FullyShardedDataParallelPlugin()
    fsdp_size = mesh.shape[FSDP_AXIS]

    def _leaf(leaf):
        existing = None
        if isinstance(leaf, jax.Array) and isinstance(leaf.sharding, NamedSharding):
            existing = leaf.sharding.spec
        return infer_fsdp_spec(
            np.shape(leaf), fsdp_size, plugin.min_weight_size, existing_spec=existing
        )

    return jax.tree_util.tree_map(_leaf, tree)


def shard_tree(tree: Any, mesh: Mesh, specs: Any) -> Any:
    """Re-place a pytree of arrays according to a PartitionSpec tree (fresh buffers)."""

    def _put(leaf, spec):
        sharding = NamedSharding(mesh, spec)
        if isinstance(leaf, jax.Array):
            return jax.jit(lambda x: x, out_shardings=sharding)(leaf)
        return jax.device_put(leaf, sharding)

    return jax.tree_util.tree_map(_put, tree, specs)


def _log_sharding_summary(params: Any, shardings: Any, mesh: Mesh) -> None:
    """Report how many bytes actually got partitioned vs silently replicated.

    ``infer_fsdp_spec`` leaves indivisible/small leaves replicated by
    design, but silently — on a wide fsdp axis that makes "why is HBM full" undebuggable.
    """
    from ..logging import get_logger

    sharded = replicated = 0
    n_repl = 0
    for leaf, sh in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(shardings)):
        nbytes = int(np.prod(np.shape(leaf))) * np.dtype(getattr(leaf, "dtype", np.float32)).itemsize
        if isinstance(sh, NamedSharding) and sh.is_fully_replicated:
            replicated += nbytes
            n_repl += 1
        else:
            sharded += nbytes
    if sharded or replicated:
        get_logger(__name__).info(
            f"param sharding over mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}: "
            f"{sharded / 2**20:.1f} MiB partitioned, {replicated / 2**20:.1f} MiB replicated "
            f"({n_repl} leaves stay replicated — small or indivisible)"
        )


def shard_params(
    params: Any,
    mesh: Mesh,
    plugin: Optional[FullyShardedDataParallelPlugin] = None,
    specs: Any = None,
    dtype=None,
) -> Any:
    """Place a param pytree onto the mesh with FSDP sharding (the ``prepare_model`` analog)."""
    shardings = get_fsdp_shardings(params, mesh, plugin, specs)
    _log_sharding_summary(params, shardings, mesh)

    def _put(leaf, sharding):
        if dtype is not None and hasattr(leaf, "astype"):
            leaf = np.asarray(leaf).astype(dtype) if isinstance(leaf, np.ndarray) else leaf.astype(dtype)
        if isinstance(leaf, jax.Array):
            # device_put may alias the source buffers; a train step later donating the state
            # would then delete the caller's original arrays. A jitted identity with
            # out_shardings always produces fresh buffers (device-side reshard, no host copy).
            return jax.jit(lambda x: x, out_shardings=sharding)(leaf)
        return jax.device_put(leaf, sharding)

    return jax.tree_util.tree_map(_put, params, shardings)


def gather_full_params(params: Any) -> Any:
    """All-gather sharded params to host numpy (the ``merge_fsdp_weights`` analog,
    reference ``utils/fsdp_utils.py:275``)."""

    def _gather(leaf):
        if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
            from jax.experimental import multihost_utils

            return np.asarray(multihost_utils.process_allgather(leaf, tiled=True))
        return np.asarray(leaf)

    return jax.tree_util.tree_map(_gather, params)
