"""Process & device state singletons (L0) — everything else reads from here.

TPU-native analog of reference ``state.py`` (/root/reference/src/accelerate/state.py):
``PartialState`` (:123), ``AcceleratorState`` (:850), ``GradientState`` (:1181), the
shared-dict singleton trick (:162,871,1181), and the process-control context managers
(``main_process_first`` :496, ``split_between_processes`` :407).

Key divergence from the reference: there is no backend selection / process-group creation
(``_prepare_backend`` :734 picks among 10 comm libraries). Under JAX there is exactly one
runtime; multi-host rendezvous is ``jax.distributed.initialize`` and every collective is an XLA
HLO op over ICI/DCN. A "process" here is a **host process** (one per TPU VM host), which drives
``jax.local_device_count()`` chips; ``num_processes`` therefore equals ``jax.process_count()``,
and per-chip parallelism lives in the mesh (``parallel/mesh.py``), not in process ranks.
"""

from __future__ import annotations

import functools
import logging
import os
from contextlib import contextmanager
from typing import Any, Callable, Optional

import numpy as np
import jax

from .utils.constants import (
    DATA_AXIS,
    EXPERT_AXIS,
    FSDP_AXIS,
    MESH_AXIS_NAMES,
    PIPELINE_AXIS,
    SEQUENCE_AXIS,
    TENSOR_AXIS,
)
from .utils.dataclasses import (
    CompileCacheConfig,
    DistributedInitKwargs,
    DistributedType,
    FaultConfig,
    GatewayConfig,
    GradientAccumulationPlugin,
    MixedPrecisionPolicy,
    PrecisionType,
    TelemetryConfig,
)
from .utils.environment import parse_choice_from_env, parse_flag_from_env

logger = logging.getLogger(__name__)

__all__ = ["PartialState", "AcceleratorState", "GradientState", "is_initialized"]


def _maybe_init_distributed(kwargs: Optional[DistributedInitKwargs]) -> None:
    """Multi-host rendezvous. No-op unless coordinator env/kwargs are present.

    Replaces the reference's ``init_process_group`` call tree (``state.py:226,267``): the JAX
    distributed service doubles as NCCL-rendezvous + torchrun-store (SURVEY.md §2.7).
    """
    coordinator = None
    num_processes = process_id = None
    local_device_ids = timeout_secs = None
    if kwargs is not None and kwargs.coordinator_address:
        coordinator = kwargs.coordinator_address
        num_processes = kwargs.num_processes
        process_id = kwargs.process_id
        local_device_ids = kwargs.local_device_ids
        timeout_secs = int(kwargs.timeout.total_seconds())
    elif os.environ.get("ACCELERATE_COORDINATOR_ADDRESS"):
        coordinator = os.environ["ACCELERATE_COORDINATOR_ADDRESS"]
        num_processes = int(os.environ.get("ACCELERATE_NUM_PROCESSES", "1"))
        process_id = int(os.environ.get("ACCELERATE_PROCESS_ID", "0"))
    if coordinator is None:
        return
    try:
        already = jax._src.distributed.global_state.client is not None  # noqa: SLF001
    except Exception:
        already = False
    if not already:
        init_kwargs: dict[str, Any] = dict(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
        if local_device_ids is not None:
            init_kwargs["local_device_ids"] = local_device_ids
        if timeout_secs is not None:
            init_kwargs["initialization_timeout"] = timeout_secs
        jax.distributed.initialize(**init_kwargs)


class PartialState:
    """Singleton holding process/device topology + process-control helpers.

    Shared-dict singleton exactly like reference ``state.py:162``: every instantiation binds
    ``__dict__`` to one class-level dict, so ``PartialState()`` anywhere observes the same state.
    """

    _shared_state: dict[str, Any] = {}
    _known_attrs = [
        "_cpu",
        "debug",
        "device",
        "distributed_type",
        "fork_launched",
        "num_processes",
        "process_index",
        "local_process_index",
    ]

    def __init__(self, cpu: bool = False, **kwargs):
        self.__dict__ = self._shared_state
        if self.initialized:
            return
        init_kwargs = kwargs.pop("distributed_init_kwargs", None)
        if isinstance(init_kwargs, dict):
            init_kwargs = DistributedInitKwargs(**init_kwargs)
        self._cpu = cpu or parse_flag_from_env("ACCELERATE_USE_CPU")
        self.debug = parse_flag_from_env("ACCELERATE_DEBUG_MODE")
        self.fork_launched = parse_flag_from_env("FORK_LAUNCHED")
        _maybe_init_distributed(init_kwargs)
        if self._cpu:
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
            # jax read JAX_PLATFORMS when it was imported (above), so the env var only
            # reaches child processes; for THIS process the config update is what counts.
            # It wins as long as no backend has initialized; if one has, we must not
            # (and cannot) switch it.
            try:
                jax.config.update("jax_platforms", "cpu")
            except Exception:  # pragma: no cover - backend already up; keep it
                pass
        self.num_processes = jax.process_count()
        self.process_index = jax.process_index()
        # One JAX process per host ⇒ every process is its node's local-main.
        self.local_process_index = 0
        self.device = self._default_device()
        if self.num_processes > 1:
            self.distributed_type = DistributedType.MULTI_HOST
        elif jax.device_count() > 1:
            self.distributed_type = DistributedType.MULTI_DEVICE
        else:
            self.distributed_type = DistributedType.NO

    def _default_device(self) -> jax.Device:
        if self._cpu:
            cpus = [d for d in jax.devices() if d.platform == "cpu"]
            if cpus:
                return cpus[0]
        return jax.local_devices()[0]

    # ------------------------------------------------------------------ topology
    @property
    def initialized(self) -> bool:
        return "num_processes" in self.__dict__ and self.__dict__["num_processes"] is not None

    @property
    def num_devices(self) -> int:
        """Global chip count — the reference's ``num_processes`` analog for sharding math."""
        return jax.device_count()

    @property
    def local_device_count(self) -> int:
        return jax.local_device_count()

    @property
    def local_devices(self) -> list[jax.Device]:
        return jax.local_devices()

    @property
    def use_distributed(self) -> bool:
        return self.num_processes > 1 or jax.device_count() > 1

    @property
    def is_main_process(self) -> bool:
        return self.process_index == 0

    @property
    def is_local_main_process(self) -> bool:
        return self.local_process_index == 0

    @property
    def is_last_process(self) -> bool:
        return self.process_index == self.num_processes - 1

    # ------------------------------------------------------------- process control
    def wait_for_everyone(self) -> None:
        """Cross-host barrier (reference ``state.py:378``; torch.distributed.barrier analog)."""
        if self.num_processes > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("accelerate_tpu.wait_for_everyone")

    @contextmanager
    def main_process_first(self):
        """Main host runs the body first, then the rest (reference ``state.py:496``)."""
        if not self.is_main_process:
            self.wait_for_everyone()
        try:
            yield
        finally:
            if self.is_main_process:
                self.wait_for_everyone()

    @contextmanager
    def local_main_process_first(self):
        # One process per host ⇒ each process is local-main; body runs immediately.
        if not self.is_local_main_process:
            self.wait_for_everyone()
        try:
            yield
        finally:
            if self.is_local_main_process:
                self.wait_for_everyone()

    @contextmanager
    def split_between_processes(self, inputs, apply_padding: bool = False):
        """Yield this process's slice of ``inputs`` (reference ``state.py:407``).

        Splits lists/tuples/dicts/arrays evenly across host processes; the final process gets
        the remainder unless ``apply_padding``, in which case short slices are padded with the
        last element so all processes see equal lengths (needed before cross-host gathers).
        """
        if self.num_processes == 1:
            yield inputs
            return
        if isinstance(inputs, dict):
            # Split each value; all values must share length.
            lengths = {k: len(v) for k, v in inputs.items()}
            if len(set(lengths.values())) != 1:
                raise ValueError(f"all dict values must have equal length, got {lengths}")
            split = {}
            for key, value in inputs.items():
                with self.split_between_processes(value, apply_padding) as v:
                    split[key] = v
            yield split
            return
        length = len(inputs)
        num_per = length // self.num_processes
        remainder = length % self.num_processes
        start = self.process_index * num_per + min(self.process_index, remainder)
        end = start + num_per + (1 if self.process_index < remainder else 0)
        chunk = inputs[start:end]
        if apply_padding and length > 0:
            target = num_per + (1 if remainder > 0 else 0)
            if isinstance(chunk, np.ndarray) or hasattr(chunk, "shape"):
                chunk = np.asarray(chunk)
                if chunk.shape[0] < target:
                    # Pad with the *global* last element so empty chunks are fillable.
                    fill = np.broadcast_to(
                        np.asarray(inputs[-1:]), (target - chunk.shape[0],) + chunk.shape[1:]
                    )
                    chunk = np.concatenate([chunk, fill], axis=0)
            else:
                chunk = list(chunk)
                while len(chunk) < target:
                    chunk.append(chunk[-1] if chunk else inputs[-1])
        yield chunk

    def on_main_process(self, function: Callable) -> Callable:
        @functools.wraps(function)
        def wrapper(*args, **kwargs):
            if self.is_main_process:
                return function(*args, **kwargs)
            return None

        return wrapper

    def on_local_main_process(self, function: Callable) -> Callable:
        @functools.wraps(function)
        def wrapper(*args, **kwargs):
            if self.is_local_main_process:
                return function(*args, **kwargs)
            return None

        return wrapper

    def on_process(self, function: Callable = None, process_index: int = None):
        if function is None:
            return functools.partial(self.on_process, process_index=process_index)

        @functools.wraps(function)
        def wrapper(*args, **kwargs):
            if self.process_index == process_index:
                return function(*args, **kwargs)
            return None

        return wrapper

    def on_last_process(self, function: Callable) -> Callable:
        @functools.wraps(function)
        def wrapper(*args, **kwargs):
            if self.is_last_process:
                return function(*args, **kwargs)
            return None

        return wrapper

    def print(self, *args, **kwargs) -> None:
        if self.is_main_process:
            print(*args, **kwargs)

    def destroy_process_group(self) -> None:
        """Tear down the distributed client (reference ``state.py:827``)."""
        if self.num_processes > 1:
            try:
                jax.distributed.shutdown()
            except Exception:  # pragma: no cover - best effort at exit
                pass

    def __repr__(self) -> str:
        return (
            f"PartialState(distributed_type={getattr(self, 'distributed_type', None)}, "
            f"num_processes={getattr(self, 'num_processes', None)}, "
            f"process_index={getattr(self, 'process_index', None)}, "
            f"num_devices={jax.device_count()}, device={getattr(self, 'device', None)})"
        )

    @classmethod
    def _reset_state(cls) -> None:
        """Reset the singleton (test helper; reference ``state.py:843``)."""
        cls._shared_state.clear()


class AcceleratorState:
    """PartialState + precision policy + plugin set + the device mesh.

    Reference ``state.py:850``. The ``distributed_type`` refinement the reference does by
    inspecting env/plugins (:949-970) happens here from the plugin set; the built mesh is the
    single source of truth for all sharding.
    """

    _shared_state: dict[str, Any] = {}

    def __init__(
        self,
        mixed_precision: Optional[str] = None,
        cpu: bool = False,
        mesh_config=None,
        fsdp_plugin=None,
        tp_plugin=None,
        pp_plugin=None,
        sp_plugin=None,
        ep_plugin=None,
        megatron_lm_plugin=None,
        telemetry_config: Optional[TelemetryConfig] = None,
        compile_cache_config: Optional[CompileCacheConfig] = None,
        gateway_config: Optional[GatewayConfig] = None,
        fault_config: Optional[FaultConfig] = None,
        _from_accelerator: bool = False,
        **kwargs,
    ):
        self.__dict__ = self._shared_state
        if self.initialized:
            if mixed_precision is not None and mixed_precision != self._mixed_precision:
                raise ValueError(
                    "AcceleratorState already initialized with mixed_precision="
                    f"{self._mixed_precision!r}; cannot re-init with {mixed_precision!r}. "
                    "Call AcceleratorState._reset_state() first (tests) or create the "
                    "Accelerator once."
                )
            return
        self._partial = PartialState(cpu=cpu, **kwargs)
        if mixed_precision is None:
            mixed_precision = parse_choice_from_env("ACCELERATE_MIXED_PRECISION", "no")
        self._mixed_precision = str(PrecisionType(mixed_precision))
        self.mixed_precision_policy = MixedPrecisionPolicy.from_precision(self._mixed_precision)
        self.fsdp_plugin = fsdp_plugin
        self.tp_plugin = tp_plugin
        self.pp_plugin = pp_plugin
        self.sp_plugin = sp_plugin
        self.ep_plugin = ep_plugin
        self.megatron_lm_plugin = megatron_lm_plugin
        # Telemetry rides on the state singleton (like the precision policy) so every
        # layer — Accelerator, serving, bench consumers — reads ONE resolved config;
        # the default constructor applies the ACCELERATE_TELEMETRY env override.
        self.telemetry_config = (
            telemetry_config if telemetry_config is not None else TelemetryConfig()
        )
        # Like telemetry, the AOT compile-cache config is state-resident so the
        # Accelerator, serving engines and warmup CLI all resolve ONE config; the
        # default constructor applies the ACCELERATE_COMPILE_CACHE env override.
        self.compile_cache_config = (
            compile_cache_config
            if compile_cache_config is not None
            else CompileCacheConfig()
        )
        # And the serving-gateway config: every serving layer (gateway builder,
        # serve-bench CLI, bench serving rows) resolves the ONE state-resident
        # config; the default constructor applies the ACCELERATE_GATEWAY env
        # override (a policy-name value both enables and selects the policy).
        self.gateway_config = (
            gateway_config if gateway_config is not None else GatewayConfig()
        )
        # Fault-injection config rides the state singleton too: the train
        # step, serving engines, checkpointing and chaos bench all resolve the
        # ONE plan; the default constructor applies the ACCELERATE_FAULTS env
        # override (a clause-string value both enables and defines the plan).
        self.fault_config = (
            fault_config if fault_config is not None else FaultConfig()
        )
        from .parallel.mesh import MeshConfig, build_mesh

        no_plugins = all(
            p is None for p in (fsdp_plugin, tp_plugin, pp_plugin, sp_plugin, ep_plugin)
        )
        if mesh_config is None and no_plugins:
            # Launcher wire protocol: ACCELERATE_MESH_* env takes effect only when neither an
            # explicit mesh nor plugins were passed in Python (explicit args > env, §5 order).
            mesh_config = MeshConfig.from_env()
        if mesh_config is None:
            mesh_config = MeshConfig.from_plugins(
                fsdp_plugin=fsdp_plugin,
                tp_plugin=tp_plugin,
                pp_plugin=pp_plugin,
                sp_plugin=sp_plugin,
                ep_plugin=ep_plugin,
            )
        self.mesh_config = mesh_config
        self.mesh = build_mesh(mesh_config)
        self.distributed_type = self._refine_distributed_type()

    def _refine_distributed_type(self) -> DistributedType:
        shape = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        active = {name for name in MESH_AXIS_NAMES if shape.get(name, 1) > 1}
        if self.megatron_lm_plugin is not None:
            return DistributedType.HYBRID
        if not active:
            return (
                DistributedType.MULTI_HOST
                if self._partial.num_processes > 1
                else DistributedType.NO
            )
        if active == {DATA_AXIS}:
            return DistributedType.MULTI_DEVICE
        # dp×fsdp (hybrid-shard) still *is* FSDP from the user's perspective.
        if FSDP_AXIS in active and active <= {DATA_AXIS, FSDP_AXIS}:
            return DistributedType.FSDP
        if len(active) == 1:
            return {
                TENSOR_AXIS: DistributedType.TP,
                PIPELINE_AXIS: DistributedType.PP,
                SEQUENCE_AXIS: DistributedType.SP,
                EXPERT_AXIS: DistributedType.EP,
            }[next(iter(active))]
        return DistributedType.HYBRID

    # Delegate topology/process-control to PartialState (reference does the same via getattr).
    def __getattr__(self, name: str):
        if name.startswith("__"):
            raise AttributeError(name)
        partial = self.__dict__.get("_partial")
        if partial is not None and hasattr(partial, name):
            return getattr(partial, name)
        raise AttributeError(f"AcceleratorState has no attribute {name!r}")

    @property
    def initialized(self) -> bool:
        return "_partial" in self.__dict__

    @property
    def mixed_precision(self) -> str:
        return self._mixed_precision

    def __repr__(self) -> str:
        return (
            f"AcceleratorState(distributed_type={self.distributed_type}, "
            f"mixed_precision={self._mixed_precision!r}, "
            f"mesh={dict(zip(self.mesh.axis_names, self.mesh.devices.shape))})"
        )

    @classmethod
    def _reset_state(cls, reset_partial_state: bool = False) -> None:
        cls._shared_state.clear()
        if reset_partial_state:
            PartialState._reset_state()


class GradientState:
    """Gradient-accumulation bookkeeping singleton (reference ``state.py:1181``).

    Tracks ``sync_gradients`` (is this step an optimizer-apply step), end-of-dataloader and
    batch remainder (consumed by ``gather_for_metrics``), and the active-dataloader stack.
    """

    _shared_state: dict[str, Any] = {}

    def __init__(self, gradient_accumulation_plugin: Optional[GradientAccumulationPlugin] = None):
        self.__dict__ = self._shared_state
        if not self.initialized:
            self.sync_gradients = True
            self.active_dataloader = None
            self.dataloader_references = [None]
            self.plugin_kwargs = (
                gradient_accumulation_plugin.to_kwargs()
                if gradient_accumulation_plugin is not None
                else {}
            )
            self._is_xla_gradients_synced = False
        if gradient_accumulation_plugin is not None:
            self.plugin_kwargs = gradient_accumulation_plugin.to_kwargs()

    @property
    def initialized(self) -> bool:
        return "sync_gradients" in self.__dict__

    @property
    def num_steps(self) -> int:
        return self.plugin_kwargs.get("num_steps", 1)

    @property
    def adjust_scheduler(self) -> bool:
        return self.plugin_kwargs.get("adjust_scheduler", True)

    @property
    def sync_with_dataloader(self) -> bool:
        return self.plugin_kwargs.get("sync_with_dataloader", True)

    @property
    def sync_each_batch(self) -> bool:
        return self.plugin_kwargs.get("sync_each_batch", False)

    @property
    def end_of_dataloader(self) -> bool:
        if not self.in_dataloader:
            return False
        return self.active_dataloader.end_of_dataloader

    @property
    def remainder(self) -> int:
        if not self.in_dataloader:
            return -1
        return self.active_dataloader.remainder

    @property
    def in_dataloader(self) -> bool:
        return self.active_dataloader is not None

    def _set_sync_gradients(self, sync_gradients: bool) -> None:
        self.sync_gradients = sync_gradients

    def _add_dataloader(self, dataloader) -> None:
        self.active_dataloader = dataloader
        self.dataloader_references.append(dataloader)

    def _remove_dataloader(self, dataloader) -> None:
        if dataloader in self.dataloader_references:
            self.dataloader_references.remove(dataloader)
        self.active_dataloader = self.dataloader_references[-1]

    def __repr__(self) -> str:
        return (
            f"GradientState(sync_gradients={self.sync_gradients}, num_steps={self.num_steps}, "
            f"end_of_dataloader={self.end_of_dataloader}, remainder={self.remainder})"
        )

    @classmethod
    def _reset_state(cls) -> None:
        cls._shared_state.clear()


def is_initialized() -> bool:
    """True once an ``AcceleratorState`` exists (reference ``state.py`` module helper)."""
    return AcceleratorState._shared_state != {}
