"""Device-mesh factory — the substrate every parallelism mode shards over.

This replaces the reference's process-group machinery (``state.py:734-799`` backend selection,
NCCL/gloo group init): on TPU there are no process groups to create — a single
``jax.sharding.Mesh`` with named axes is laid over the ICI/DCN topology and every strategy
(DP/ZeRO/FSDP/TP/PP/SP/EP) is a PartitionSpec over its axes (SURVEY.md §7).

Axis order is (dp, fsdp, tp, sp, pp, ep) — outermost-to-innermost in communication intensity:
tensor/sequence-parallel collectives are the most latency-sensitive so they get the innermost
(fastest-ICI-neighbor) axes from ``mesh_utils.create_device_mesh``; dp/fsdp gradient reductions
amortize over the step; pp only nearest-neighbor-permutes activations.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np
import jax
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..utils.constants import (
    BATCH_AXES,
    DATA_AXIS,
    EXPERT_AXIS,
    FSDP_AXIS,
    MESH_AXIS_NAMES,
    PIPELINE_AXIS,
    SEQUENCE_AXIS,
    TENSOR_AXIS,
)

__all__ = [
    "MeshConfig",
    "build_mesh",
    "batch_pspec",
    "batch_sharding",
    "mesh_context",
    "replicated",
    "mesh_batch_size_divisor",
]


def mesh_context(mesh: Mesh):
    """The ambient-mesh context letting jitted code use bare ``PartitionSpec``s in
    sharding constraints (``jax.set_mesh``). Every mesh context in this package
    routes through here so a jax API move is a one-line edit."""
    return jax.set_mesh(mesh)


@dataclass
class MeshConfig:
    """Degrees of each parallelism axis. ``-1`` on exactly one axis means "fill remaining".

    The product of all axis sizes must equal ``jax.device_count()`` (after -1 resolution).
    Defaults put every device on the data axis — plain DDP-equivalent.
    """

    dp: int = -1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    pp: int = 1
    ep: int = 1
    # Multi-slice: how many of the dp replicas live on DIFFERENT slices (connected by DCN,
    # not ICI). build_mesh places this factor of the dp axis across slice boundaries via
    # mesh_utils.create_hybrid_device_mesh, so DCN carries ONLY the dp gradient
    # all-reduce — fsdp/tp/sp/pp/ep collectives stay on intra-slice ICI. 1 = single slice.
    # Must divide dp (after -1 resolution).
    dcn_dp: int = 1
    # Optional explicit device list (tests); None = all global devices.
    devices: Optional[Sequence[jax.Device]] = None
    allow_split_physical_axes: bool = False

    def resolved_sizes(self, num_devices: Optional[int] = None) -> dict[str, int]:
        if num_devices is None:
            num_devices = len(self.devices) if self.devices is not None else jax.device_count()
        sizes = {
            DATA_AXIS: self.dp,
            FSDP_AXIS: self.fsdp,
            TENSOR_AXIS: self.tp,
            SEQUENCE_AXIS: self.sp,
            PIPELINE_AXIS: self.pp,
            EXPERT_AXIS: self.ep,
        }
        unknown = [k for k, v in sizes.items() if v == -1]
        if len(unknown) > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {unknown}")
        known_product = math.prod(v for v in sizes.values() if v != -1)
        if unknown:
            if num_devices % known_product != 0:
                raise ValueError(
                    f"cannot fill axis {unknown[0]!r}: {num_devices} devices not divisible by "
                    f"product of fixed axes {known_product}"
                )
            sizes[unknown[0]] = num_devices // known_product
        elif known_product != num_devices:
            raise ValueError(
                f"mesh axis sizes {sizes} multiply to {known_product} but there are "
                f"{num_devices} devices"
            )
        return sizes

    @classmethod
    def from_env(cls) -> Optional["MeshConfig"]:
        """Deserialize ``ACCELERATE_MESH_{DP,FSDP,TP,SP,PP,EP}`` set by the launcher.

        Returns None when no mesh env var is present (the launcher wire protocol,
        ``utils/launch.py``). ``-1`` keeps its fill-remaining meaning.
        """
        import os

        values = {}
        for field_name in ("dp", "fsdp", "tp", "sp", "pp", "ep", "dcn_dp"):
            raw = os.environ.get(f"ACCELERATE_MESH_{field_name.upper()}")
            if raw is not None:
                values[field_name] = int(raw)
        # Unset axes keep their dataclass defaults (dp=-1 fill-remaining, others 1).
        return cls(**values) if values else None

    @classmethod
    def from_plugins(
        cls,
        fsdp_plugin=None,
        tp_plugin=None,
        pp_plugin=None,
        sp_plugin=None,
        ep_plugin=None,
        num_devices: Optional[int] = None,
    ) -> "MeshConfig":
        """Derive the mesh from the active plugin set (Accelerator.__init__ path)."""
        cfg = cls(
            tp=tp_plugin.tp_size if tp_plugin else 1,
            pp=pp_plugin.pp_size if pp_plugin else 1,
            sp=sp_plugin.sp_size if sp_plugin else 1,
            ep=ep_plugin.ep_size if ep_plugin else 1,
        )
        if num_devices is None:
            num_devices = jax.device_count()
        fixed = cfg.tp * cfg.pp * cfg.sp * cfg.ep
        if num_devices % fixed != 0:
            raise ValueError(
                f"tp*pp*sp*ep = {fixed} does not divide the {num_devices} available devices "
                f"(tp={cfg.tp}, pp={cfg.pp}, sp={cfg.sp}, ep={cfg.ep})"
            )
        rest = num_devices // fixed
        if fsdp_plugin is not None and fsdp_plugin.zero_stage > 0:
            from ..utils.dataclasses import FSDPShardingStrategy

            if fsdp_plugin.sharding_strategy in (
                FSDPShardingStrategy.HYBRID_SHARD,
                FSDPShardingStrategy.HYBRID_SHARD_ZERO2,
            ):
                # Shard within a host's local slice (ICI), replicate across hosts (DCN).
                local = max(1, jax.local_device_count())
                fsdp_size = math.gcd(rest, local)
                cfg.fsdp = fsdp_size
                cfg.dp = rest // fsdp_size
            else:
                cfg.fsdp = rest
                cfg.dp = 1
        else:
            cfg.dp = rest
            cfg.fsdp = 1
        return cfg


def build_mesh(config: Optional[MeshConfig] = None) -> Mesh:
    """Build a named Mesh over the physical topology.

    Uses ``mesh_utils.create_device_mesh`` so axis neighbors are ICI neighbors (the analog of
    NCCL ring/tree tuning, which the reference delegates entirely to NCCL).
    """
    config = config or MeshConfig()
    devices = list(config.devices) if config.devices is not None else jax.devices()
    sizes = config.resolved_sizes(len(devices))
    shape = tuple(sizes[name] for name in MESH_AXIS_NAMES)
    if config.dcn_dp > 1:
        # Multi-slice: split the dp axis into (dcn factor) × (per-slice remainder) and let
        # create_hybrid_device_mesh place the dcn factor across slice boundaries. Only the
        # dp gradient all-reduce crosses DCN; every other axis stays on ICI.
        dp_idx = MESH_AXIS_NAMES.index(DATA_AXIS)
        if shape[dp_idx] % config.dcn_dp:
            raise ValueError(
                f"dcn_dp={config.dcn_dp} must divide the dp axis size {shape[dp_idx]}"
            )
        ici_shape = list(shape)
        ici_shape[dp_idx] //= config.dcn_dp
        dcn_shape = [1] * len(shape)
        dcn_shape[dp_idx] = config.dcn_dp
        try:
            device_array = mesh_utils.create_hybrid_device_mesh(
                ici_shape, dcn_shape, devices=devices,
                allow_split_physical_axes=config.allow_split_physical_axes,
            )
        except (ValueError, NotImplementedError, AttributeError):
            # No slice metadata (CPU simulator / single-slice): plain reshape keeps the
            # same global shape and axis order, so programs still compile identically.
            device_array = np.array(devices).reshape(shape)
        return Mesh(device_array, MESH_AXIS_NAMES)
    if len(devices) == 1:
        device_array = np.array(devices).reshape(shape)
    else:
        try:
            device_array = mesh_utils.create_device_mesh(
                shape,
                devices=devices,
                allow_split_physical_axes=config.allow_split_physical_axes,
            )
        except (ValueError, NotImplementedError):
            device_array = np.array(devices).reshape(shape)
    return Mesh(device_array, MESH_AXIS_NAMES)


def batch_pspec(mesh: Mesh, extra_leading: int = 0) -> PartitionSpec:
    """PartitionSpec sharding the leading (batch) dim over the (dp, fsdp) axes."""
    del mesh
    return PartitionSpec(*([None] * extra_leading), BATCH_AXES)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(BATCH_AXES))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def mesh_batch_size_divisor(mesh: Mesh) -> int:
    """Global batch must be divisible by this (dp*fsdp)."""
    return mesh.shape[DATA_AXIS] * mesh.shape[FSDP_AXIS]
