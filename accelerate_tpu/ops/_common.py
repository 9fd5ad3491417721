"""Shared helpers for the Pallas kernel modules (flash/paged/fused_optim/fused_xent)."""

from __future__ import annotations

import math

import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..utils.constants import BATCH_AXES, TENSOR_AXIS
from ..utils.imports import is_tpu_available

LANES = 128  # native VPU lane count: softmax state is replicated across lanes


def interpret_default() -> bool:
    """Pallas interpret mode is the CPU tests' stand-in for Mosaic: on exactly when
    the default backend is not a TPU (``utils.imports.is_tpu_available``)."""
    return not is_tpu_available()


def lane_tile(x, cols):
    """Broadcast lane-replicated state [rows, LANES] across a tile [rows, cols] —
    full-register tile then slice, never a 1-lane relayout. Handles any cols (ceil-tile
    + slice for non-multiples of 128, e.g. head_dim 192)."""
    if cols == LANES:
        return x
    if cols < LANES:
        return x[:, :cols]
    tiled = jnp.tile(x, (1, -(-cols // LANES)))
    return tiled if tiled.shape[1] == cols else tiled[:, :cols]


def attention_shard_spec(mesh, q, k, seq_axis=None, heads: bool = True) -> PartitionSpec:
    """The ``shard_map`` spec under which an attention kernel over q [B,S,H,hd] /
    k,v [B,S,K,hd] runs on ``mesh``. Mosaic kernels cannot be partitioned by GSPMD: on
    a multi-device mesh every axis has to be manual around them, so the spec names where
    each dim lives — batch over the batch axes (dp×fsdp) and heads over ``tp`` when they
    divide evenly (else that dim is replicated inside the map), the sequence over
    ``seq_axis`` (the sp modes) or whole. ``heads=False`` keeps the heads whole (ulysses
    re-splits them over sp itself)."""
    batch = tuple(a for a in BATCH_AXES if a in mesh.shape)
    if q.shape[0] % math.prod(mesh.shape[a] for a in batch):
        batch = ()
    n_tp = mesh.shape.get(TENSOR_AXIS, 1)
    tp = heads and n_tp > 1 and q.shape[2] % n_tp == 0 and k.shape[2] % n_tp == 0
    return PartitionSpec(batch or None, seq_axis, TENSOR_AXIS if tp else None, None)
