"""The Accelerator facade (L3) — one object that prepares everything for the mesh.

TPU-native analog of reference ``accelerator.py`` (/root/reference/src/accelerate/accelerator.py,
3769 LoC): ``__init__`` (:266), ``prepare`` (:1283), ``backward`` (:2357), ``accumulate``
(:1116), ``clip_grad_norm_`` (:2485), ``gather_for_metrics`` (:2601), ``autocast`` (:3587).

**The central design inversion** (SURVEY.md §7): the reference mutates user objects — wraps the
model in DDP, patches ``forward``, wraps the optimizer so ``step()`` no-ops during
accumulation. Under jit that object-graph choreography cannot exist; instead the Accelerator
owns a **functional train step compiled once over the mesh**:

    accelerator = Accelerator(mixed_precision="bf16", gradient_accumulation_steps=4)
    params, optimizer, dataloader = accelerator.prepare(params, optax.adamw(1e-4), dataloader)
    state = accelerator.create_train_state(params, optimizer)
    step = accelerator.build_train_step(loss_fn)     # jitted; GSPMD handles DP/FSDP/TP comms
    for batch in dataloader:
        state, metrics = step(state, batch)          # grad-accum & clipping inside

Gradient synchronization is *not* an explicit collective: batches are sharded over the
``(dp, fsdp)`` mesh axes while params are replicated (DDP) or fsdp-sharded (ZeRO-3), so XLA
derives the all-reduce / reduce-scatter from the shardings — the entire DDP reducer +
DeepSpeed engine + FSDP wrapper surface of the reference collapses into ``jax.device_put``
placements plus one ``jax.jit``.
"""

from __future__ import annotations

import contextlib
import functools
import inspect
import os
from dataclasses import dataclass, replace as dataclass_replace
from typing import Any, Callable, Optional, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .data_loader import DataLoaderDispatcher, DataLoaderShard, prepare_data_loader, skip_first_batches
from .logging import get_logger
from .optimizer import AcceleratedOptimizer
from .parallel.fsdp import shard_params
from .parallel.mesh import MeshConfig, mesh_context, replicated as _mesh_replicated
from .scheduler import AcceleratedScheduler
from .state import AcceleratorState, GradientState
from .telemetry.tracing import step_phase
from .utils.constants import BATCH_AXES
from .utils.dataclasses import (
    DataLoaderConfiguration,
    DistributedType,
    FullyShardedDataParallelPlugin,
    GradientAccumulationPlugin,
    MixedPrecisionPolicy,
    ProjectConfiguration,
)
from .utils.operations import (
    convert_to_fp32,
    gather,
    gather_object,
    pad_across_processes,
    recursively_apply,
    reduce,
    send_to_device,
    slice_tensors,
)

logger = get_logger(__name__)

__all__ = ["Accelerator", "TrainState", "cast_floating"]


def cast_floating(tree: Any, dtype) -> Any:
    """Cast floating leaves of a pytree to ``dtype`` (ints/bools untouched)."""

    def _cast(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x

    return jax.tree_util.tree_map(_cast, tree)


@jax.tree_util.register_dataclass
@dataclass
class TrainState:
    """The sharded training carry: everything a train step reads and writes.

    The functional replacement for the reference's (model, optimizer, scaler) object trio.
    ``grad_accum`` holds the running gradient sum between sync steps (the ``no_sync``
    mechanism, reference ``accelerator.py:1001``); ``step`` counts *optimizer* steps only.
    """

    params: Any
    opt_state: Any
    step: jax.Array
    grad_accum: Any = None
    rng: Any = None
    micro: jax.Array = None  # micro-steps since last apply (unique RNG per micro-batch)
    fp8_state: Any = None    # DelayedScalingState when the fp8 recipe uses delayed scaling

    def replace(self, **kwargs) -> "TrainState":
        import dataclasses

        return dataclasses.replace(self, **kwargs)


def _poison_float_leaves(batch):
    """Fault-injection helper: NaN out every float leaf of a batch (integer
    token ids pass through — NaN has no integer spelling)."""
    def poison(leaf):
        arr = np.asarray(leaf)
        if np.issubdtype(arr.dtype, np.floating):
            return np.full_like(arr, np.nan)
        return leaf

    return jax.tree_util.tree_map(poison, batch)


class _TrainStep:
    """Callable produced by ``Accelerator.build_train_step``.

    Two compiled variants — accumulate-only and accumulate+apply — dispatched host-side from
    the gradient-accumulation counter. This keeps each variant free of data-dependent control
    flow (XLA-friendly) while preserving the reference's ``sync_gradients`` semantics exactly.
    """

    def __init__(self, accelerator: "Accelerator", micro_fn, apply_fn, optimizer=None,
                 skip_nonfinite_steps: int = 0):
        self.accelerator = accelerator
        self.micro_fn = micro_fn
        self.apply_fn = apply_fn
        self.optimizer = optimizer
        self.micro_count = 0
        # Non-finite guard (docs/resilience.md): 0 = off (no host sync, byte-
        # identical to the unguarded step). K > 0 = the compiled step gates its
        # own update on all-finite loss+grads (params/opt state pass through
        # unchanged on a bad apply; a bad micro's contribution is zeroed) and
        # the host fetches ONE boolean per call. K consecutive non-finite
        # calls — micro OR apply — raise NonFiniteStepError.
        self.skip_nonfinite_steps = skip_nonfinite_steps
        self.nonfinite_total = 0
        self.nonfinite_consecutive = 0

    def __call__(self, state: TrainState, batch) -> tuple[TrainState, Any]:
        acc = self.accelerator
        # Fault injection (disabled = one attribute read): a "nonfinite" fault
        # poisons the batch's float leaves with NaN — the REAL guard path, not
        # a simulated exception — and an "error" fault raises at the boundary.
        plan = getattr(acc, "fault_plan", None)
        if plan is not None:
            spec = plan.draw("train.step")
            if spec is not None:
                if spec.kind == "nonfinite":
                    batch = _poison_float_leaves(batch)
                elif spec.kind == "crash":
                    # Whole-gang death: raises PAST the step boundary the way
                    # EngineCrashed does for serving — nothing in-process may
                    # catch it; the gang-of-gangs supervisor converts it into a
                    # budgeted gang restart + checkpoint replay.
                    from .resilience.faults import StageCrashed

                    raise StageCrashed("train.step",
                                       gang_id=plan.scope or "gang0")
                else:
                    raise plan.fault_for(spec, "train.step")
        # Telemetry bracket: when off this is two attribute reads — no syncs, no
        # allocation. When on, the record fences on the 1-element loss (telemetry.fence
        # never fetches the full result) so step time includes the device work.
        tel = acc.telemetry
        tel_on = tel is not None and tel.enabled
        if tel_on:
            tel._step_begin()
        try:
            with step_phase("train.step", acc.step):
                state, metrics = self._dispatch(acc, tel if tel_on else None, state, batch)
        except BaseException:
            if tel_on:
                tel._step_abort()  # a failed step must not leak the compile label
            raise
        if self.skip_nonfinite_steps:
            self._check_nonfinite(acc, metrics)
        return state, metrics

    def _check_nonfinite(self, acc, metrics) -> None:
        """One boolean fetch per guarded step: count skipped (non-finite)
        updates, telemeter them, abort after the consecutive budget."""
        nf = bool(np.asarray(metrics.get("nonfinite", False)))
        if not nf:
            self.nonfinite_consecutive = 0
            return
        self.nonfinite_total += 1
        self.nonfinite_consecutive += 1
        tel = acc.telemetry
        if tel is not None and tel.enabled:
            from .telemetry import FAULT_SCHEMA

            tel.emit({
                "schema": FAULT_SCHEMA, "site": "train.step",
                "kind": "nonfinite", "step": acc.step,
                "consecutive": self.nonfinite_consecutive,
                "total": self.nonfinite_total,
            })
        if self.nonfinite_consecutive >= self.skip_nonfinite_steps:
            from .resilience.faults import NonFiniteStepError

            raise NonFiniteStepError(self.nonfinite_consecutive, self.nonfinite_total)

    def _dispatch(self, acc, tel, state: TrainState, batch) -> tuple[TrainState, Any]:
        gs = acc.gradient_state
        if acc._in_accumulate_ctx:
            do_sync = gs.sync_gradients  # accumulate() ctx already decided
        else:
            at_end = gs.sync_with_dataloader and gs.end_of_dataloader
            do_sync = ((self.micro_count + 1) % acc.gradient_accumulation_steps == 0) or at_end
            gs._set_sync_gradients(do_sync)
        offload = acc._opt_device_shardings is not None
        # Mesh context lets model code use bare PartitionSpecs in sharding constraints.
        with mesh_context(acc.mesh):
            state = acc._offload_fetch(state, opt=do_sync)
            if do_sync:
                state, metrics = self.apply_fn(state, batch)
                self.micro_count = 0
            else:
                # Micro steps never touch the optimizer state: detach it so the host-resident
                # moments neither transit PCIe nor occupy HBM during the activation-heavy
                # fwd/bwd (and the jit never sees host-memory-kind inputs).
                host_opt = state.opt_state if offload else None
                if offload:
                    state = state.replace(opt_state=None)
                state, metrics = self.micro_fn(state, batch)
                if offload:
                    state = state.replace(opt_state=host_opt)
                self.micro_count += 1
            state = acc._offload_stash(state, opt=do_sync)
        acc.step += 1
        if self.optimizer is not None:
            self.optimizer.step()
        if tel is not None:
            tel._step_end(fence_on=metrics, batch=batch)
        return state, metrics

    def warm(self, state: TrainState, batch) -> list:
        """Prime the AOT compile cache for this step's programs without executing
        (``compile_cache.warmup``). Mirrors ``_dispatch``'s argument shaping — the
        cpu_offload opt-state detach included — so the fingerprints match live
        steps. Returns the manifest entries (empty when the cache is disabled)."""
        acc = self.accelerator
        if not hasattr(self.apply_fn, "warm"):
            return []
        offload = acc._opt_device_shardings is not None
        entries = []
        with mesh_context(acc.mesh):
            apply_state = acc._offload_fetch(state, opt=True)
            entries.append(self.apply_fn.warm(apply_state, batch))
            if acc.gradient_accumulation_steps > 1:
                micro_state = acc._offload_fetch(state, opt=False)
                if offload:
                    micro_state = micro_state.replace(opt_state=None)
                entries.append(self.micro_fn.warm(micro_state, batch))
        return entries


class _FusedTrainStep:
    """M train steps per dispatch via ``lax.scan`` (``build_train_step(fused_steps=M)``).

    One compiled program advances M micro-steps (optimizer applies every
    ``gradient_accumulation_steps``-th) — amortizing host dispatch over M steps, which on TPU
    removes the host-side bottleneck the reference's per-batch Python loop suffers from.
    Call with a list of M batches or a pytree stacked on a leading M dim; metrics come back
    stacked [M, ...].
    """

    def __init__(self, accelerator: "Accelerator", fused_fn, fused_steps: int, optimizer=None):
        self.accelerator = accelerator
        self.fused_fn = fused_fn
        self.fused_steps = fused_steps
        self.optimizer = optimizer

    def _stack(self, batches):
        if isinstance(batches, (list, tuple)):
            if len(batches) != self.fused_steps:
                raise ValueError(f"expected {self.fused_steps} batches, got {len(batches)}")
            import numpy as _np

            stacked = jax.tree_util.tree_map(
                lambda *leaves: _np.stack([_np.asarray(l) for l in leaves]), *batches
            )
        else:
            stacked = batches
            for leaf in jax.tree_util.tree_leaves(stacked):
                if np.ndim(leaf) < 1 or np.shape(leaf)[0] != self.fused_steps:
                    raise ValueError(
                        f"pre-stacked batch leaves must have leading dim {self.fused_steps}, "
                        f"got shape {np.shape(leaf)}"
                    )
        sharding = NamedSharding(self.accelerator.mesh, PartitionSpec(None, BATCH_AXES))

        def _put(leaf):
            if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
                return leaf
            if np.ndim(leaf) < 2:
                # Scalars / per-step vectors can't take the (step, batch) sharding.
                return jax.device_put(
                    leaf, NamedSharding(self.accelerator.mesh, PartitionSpec())
                )
            return jax.device_put(leaf, sharding)

        return jax.tree_util.tree_map(_put, stacked)

    def __call__(self, state: TrainState, batches) -> tuple[TrainState, Any]:
        acc = self.accelerator
        tel = acc.telemetry
        tel_on = tel is not None and tel.enabled
        if tel_on:
            tel._step_begin()
        try:
            with step_phase("train.step", acc.step):
                stacked = self._stack(batches)
                with mesh_context(acc.mesh):
                    state = acc._offload_fetch(state, opt=True)
                    state, metrics = self.fused_fn(state, stacked)
                    state = acc._offload_stash(state, opt=True)
        except BaseException:
            if tel_on:
                tel._step_abort()  # a failed step must not leak the compile label
            raise
        acc.step += self.fused_steps
        applies = self.fused_steps // acc.gradient_accumulation_steps
        if self.optimizer is not None:
            self.optimizer._step_count += applies
        acc.gradient_state._set_sync_gradients(
            self.fused_steps % acc.gradient_accumulation_steps == 0
        )
        if tel_on:
            # One record per dispatch window of M steps; batch shapes sit behind the
            # stacked [M, B, ...] leading dim.
            tel._step_end(
                fence_on=metrics, batch=stacked, n_steps=self.fused_steps, drop_leading=1
            )
        return state, metrics

    def warm(self, state: TrainState, batches) -> list:
        """Prime the AOT compile cache for the fused program without executing
        (``compile_cache.warmup``); batches take the same list/stacked forms as
        ``__call__``. Returns the manifest entries (empty when cache disabled)."""
        acc = self.accelerator
        if not hasattr(self.fused_fn, "warm"):
            return []
        stacked = self._stack(batches)
        with mesh_context(acc.mesh):
            fetched = acc._offload_fetch(state, opt=True)
            return [self.fused_fn.warm(fetched, stacked)]


class Accelerator:
    """One facade for device placement, parallelism, precision, accumulation and IO."""

    def __init__(
        self,
        device_placement: bool = True,
        split_batches: bool = False,
        mixed_precision: Optional[str] = None,
        gradient_accumulation_steps: Optional[int] = None,
        cpu: bool = False,
        dataloader_config: Optional[DataLoaderConfiguration] = None,
        mesh_config: Optional[MeshConfig] = None,
        fsdp_plugin: Optional[FullyShardedDataParallelPlugin] = None,
        tp_plugin=None,
        pp_plugin=None,
        sp_plugin=None,
        ep_plugin=None,
        megatron_lm_plugin=None,
        rng_types: Optional[list[str]] = None,
        log_with=None,
        project_dir: Optional[str] = None,
        project_config: Optional[ProjectConfiguration] = None,
        gradient_accumulation_plugin: Optional[GradientAccumulationPlugin] = None,
        step_scheduler_with_optimizer: bool = True,
        kwargs_handlers: Optional[list] = None,
        dynamo_plugin=None,
        telemetry_config=None,
        compile_cache_config=None,
        gateway_config=None,
        fault_config=None,
    ):
        self.project_configuration = project_config or ProjectConfiguration(project_dir=project_dir)
        if project_dir is not None and self.project_configuration.project_dir is None:
            self.project_configuration.set_directories(project_dir)

        # Plugins may also arrive via the env wire protocol (launcher sets ACCELERATE_*).
        if fsdp_plugin is None and os.environ.get("ACCELERATE_USE_FSDP", "false").lower() == "true":
            fsdp_plugin = FullyShardedDataParallelPlugin()

        # A MegatronLMPlugin is a bundle: expand it into the individual plugins it implies
        # (reference _prepare_megatron_lm, accelerator.py:2011; our mesh subsumes the engine).
        self._megatron_grad_clip = None
        if megatron_lm_plugin is not None:
            from .utils.dataclasses import (
                PipelineParallelPlugin,
                SequenceParallelPlugin,
                TensorParallelPlugin,
            )

            if tp_plugin is None and megatron_lm_plugin.tp_degree > 1:
                tp_plugin = TensorParallelPlugin(tp_size=megatron_lm_plugin.tp_degree)
            if pp_plugin is None and megatron_lm_plugin.pp_degree > 1:
                pp_plugin = PipelineParallelPlugin(
                    pp_size=megatron_lm_plugin.pp_degree,
                    num_microbatches=megatron_lm_plugin.num_micro_batches,
                    schedule=megatron_lm_plugin.pp_schedule,
                    virtual_stages=megatron_lm_plugin.virtual_pipeline_stages,
                )
            if sp_plugin is None and megatron_lm_plugin.sp_degree > 1:
                sp_plugin = SequenceParallelPlugin(sp_size=megatron_lm_plugin.sp_degree)
            if fsdp_plugin is None and megatron_lm_plugin.use_distributed_optimizer:
                fsdp_plugin = FullyShardedDataParallelPlugin(zero_stage=1)
            if (
                megatron_lm_plugin.pp_degree == 1
                and megatron_lm_plugin.num_micro_batches
                and gradient_accumulation_steps is None
                and gradient_accumulation_plugin is None
            ):
                # Megatron micro-batching implies gradient accumulation independent of
                # pipeline depth; without a pipe the microbatches become accum steps.
                gradient_accumulation_steps = megatron_lm_plugin.num_micro_batches
            self._megatron_grad_clip = megatron_lm_plugin.gradient_clipping

        # Kwargs handler dispatch (reference accelerator.py:425-450).
        self.fp8_recipe = None
        self.autocast_handler = None
        self.profile_handler = None
        self.scaler_handler = None
        distributed_init_kwargs = None
        ddp_kwargs = None
        for handler in kwargs_handlers or []:
            from .utils.dataclasses import (
                AutocastKwargs,
                DistributedDataParallelKwargs,
                DistributedInitKwargs,
                FP8RecipeKwargs,
                GradScalerKwargs,
                ProfileKwargs,
            )

            if isinstance(handler, FP8RecipeKwargs):
                self.fp8_recipe = handler
            elif isinstance(handler, AutocastKwargs):
                self.autocast_handler = handler
            elif isinstance(handler, ProfileKwargs):
                self.profile_handler = handler
            elif isinstance(handler, GradScalerKwargs):
                self.scaler_handler = handler  # API parity; moot under bf16/fp8 on TPU
            elif isinstance(handler, DistributedInitKwargs):
                distributed_init_kwargs = handler
            elif isinstance(handler, DistributedDataParallelKwargs):
                ddp_kwargs = handler  # comm_hook → reduce_dtype, applied post-state-init
            else:
                raise ValueError(f"Unsupported kwargs handler: {handler!r}")
        if mixed_precision == "fp8" and self.fp8_recipe is None:
            from .utils.dataclasses import FP8RecipeKwargs

            self.fp8_recipe = FP8RecipeKwargs()
        if self.fp8_recipe is not None:
            # Install the recipe as the process default consulted by ops.fp8.fp8_dot.
            # Delayed scaling is wired automatically: create_train_state seeds a
            # DelayedScalingState into TrainState.fp8_state and build_train_step threads it
            # through every fp8_dot via ops.fp8.autoscale_ctx.
            from .ops.fp8 import set_default_recipe

            set_default_recipe(self.fp8_recipe.fp8_format, self.fp8_recipe.margin)

        self.state = AcceleratorState(
            **({"distributed_init_kwargs": distributed_init_kwargs} if distributed_init_kwargs else {}),
            mixed_precision=mixed_precision,
            cpu=cpu,
            mesh_config=mesh_config,
            fsdp_plugin=fsdp_plugin,
            tp_plugin=tp_plugin,
            pp_plugin=pp_plugin,
            sp_plugin=sp_plugin,
            ep_plugin=ep_plugin,
            megatron_lm_plugin=megatron_lm_plugin,
            telemetry_config=telemetry_config,
            compile_cache_config=compile_cache_config,
            gateway_config=gateway_config,
            fault_config=fault_config,
        )

        # Step-level telemetry (off by default; ACCELERATE_TELEMETRY=1 or an enabled
        # TelemetryConfig turns it on). The disabled object costs two attribute reads
        # per train step — no listeners, no files, no host syncs.
        from .telemetry import Telemetry

        self.telemetry = Telemetry(self.state.telemetry_config)
        if self.telemetry.enabled:
            self.telemetry.sinks.append(self._telemetry_tracker_sink)

        # Persistent AOT executable cache (off by default; ACCELERATE_COMPILE_CACHE=1
        # or an enabled CompileCacheConfig turns it on). Disabled, wrap() is the
        # identity and every step dispatches through plain jax.jit as before.
        from .compile_cache import AotCache

        self.compile_cache = AotCache(self.state.compile_cache_config)

        # Deterministic fault injection (off by default; ACCELERATE_FAULTS or an
        # enabled FaultConfig turns it on). Disabled, the plan is None and every
        # instrumented site pays one attribute read (docs/resilience.md).
        self.fault_plan = self.state.fault_config.build_plan()

        if ddp_kwargs is not None and ddp_kwargs.reduce_dtype is not None:
            # DDP comm_hook analog: compress cross-device gradient reductions.
            # build_train_step only honors it when it EQUALS the compute dtype (the
            # compressed reduce is exact there); per this handler's own
            # accepted-but-ignored-is-worse-than-an-error policy, any other combination
            # raises instead of silently running uncompressed.
            import dataclasses as _dc

            compute_dtype = self.state.mixed_precision_policy.compute_dtype
            if ddp_kwargs.reduce_dtype != compute_dtype:
                raise ValueError(
                    f"DistributedDataParallelKwargs comm_hook compression dtype "
                    f"{ddp_kwargs.reduce_dtype.__name__} does not match the mixed-"
                    f"precision compute dtype {compute_dtype.__name__}: the hook would "
                    "be accepted but never applied. Use the comm_hook matching "
                    "mixed_precision (bf16 ↔ 'bf16'), or drop the handler."
                )
            self.state.mixed_precision_policy = _dc.replace(
                self.state.mixed_precision_policy, reduce_dtype=ddp_kwargs.reduce_dtype
            )
            # Distinguishes an EXPLICIT comm_hook from the bf16/fp16 policy's default
            # reduce_dtype: only the former hard-errors when a build_train_step option
            # later disables compression (the default silently not compressing under
            # cast_params=False is expected behavior, not a dropped user request).
            self._explicit_comm_hook = True

        if gradient_accumulation_plugin is None:
            # Priority: explicit Python arg (any int, including 1) > env wire protocol > 1.
            if gradient_accumulation_steps is None:
                env_steps = int(os.environ.get("ACCELERATE_GRADIENT_ACCUMULATION_STEPS", "-1"))
                gradient_accumulation_steps = env_steps if env_steps > 0 else 1
            gradient_accumulation_plugin = GradientAccumulationPlugin(
                num_steps=gradient_accumulation_steps
            )
        self.gradient_state = GradientState(gradient_accumulation_plugin)

        self.device_placement = device_placement
        self.split_batches = split_batches
        self.dataloader_config = dataloader_config or DataLoaderConfiguration(
            split_batches=split_batches
        )
        self.rng_types = rng_types or ["generator"]
        self.step_scheduler_with_optimizer = step_scheduler_with_optimizer
        if log_with is None and os.environ.get("ACCELERATE_LOG_WITH"):
            log_with = os.environ["ACCELERATE_LOG_WITH"]
        self.log_with = log_with
        self.trackers: list = []

        self.step = 0
        # Param-layout record for the fused-optimizer fast path. None = unknown (no
        # create_train_state yet — user-managed TrainStates stay on the safe optax path
        # when sharding machinery is configured); set to ground truth by create_train_state.
        self._params_cross_sharded: Optional[bool] = None
        self._param_spec_tree = None
        # ZeRO-1/2 spec trees, filled by create_train_state when the fsdp plugin requests
        # optimizer/gradient sharding with replicated params (zero_stage 1/2).
        self._zero_opt_specs = None
        self._zero_grad_specs = None
        self._zero_param_specs = None
        # cpu_offload sharding trees (host/device variants), filled by create_train_state.
        self._opt_host_shardings = None
        self._opt_device_shardings = None
        self._accum_host_shardings = None
        self._accum_device_shardings = None
        self._in_accumulate_ctx = False
        self._accumulate_count = 0
        self._max_grad_norm: Optional[float] = (
            float(self._megatron_grad_clip) if self._megatron_grad_clip is not None else None
        )
        self._max_grad_value: Optional[float] = None
        self._models: list = []
        self._optimizers: list[AcceleratedOptimizer] = []
        self._schedulers: list = []
        self._dataloaders: list = []
        self._custom_objects: list = []
        self._save_model_hooks: list[Callable] = []
        self._load_model_hooks: list[Callable] = []

        self.flag_tensor = None

    # ------------------------------------------------------------------------ properties
    @property
    def mesh(self) -> Mesh:
        return self.state.mesh

    @property
    def distributed_type(self) -> DistributedType:
        return self.state.distributed_type

    @property
    def num_processes(self) -> int:
        return self.state.num_processes

    @property
    def process_index(self) -> int:
        return self.state.process_index

    @property
    def local_process_index(self) -> int:
        return self.state.local_process_index

    @property
    def device(self):
        return self.state.device

    @property
    def is_main_process(self) -> bool:
        return self.state.is_main_process

    @property
    def is_local_main_process(self) -> bool:
        return self.state.is_local_main_process

    @property
    def is_last_process(self) -> bool:
        return self.state.is_last_process

    @property
    def mixed_precision(self) -> str:
        return self.state.mixed_precision

    @property
    def mixed_precision_policy(self) -> MixedPrecisionPolicy:
        return self.state.mixed_precision_policy

    @property
    def use_distributed(self) -> bool:
        return self.state.use_distributed

    @property
    def num_microbatches(self) -> int:
        """Pipeline microbatch count: plugin value > launcher env > n_stages (min full pipe)."""
        from .utils.constants import PIPELINE_AXIS

        plugin = self.state.pp_plugin
        if plugin is not None and plugin.num_microbatches is not None:
            return plugin.num_microbatches
        env_mb = os.environ.get("ACCELERATE_PP_MICROBATCHES")
        if env_mb:
            return int(env_mb)
        return self.mesh.shape[PIPELINE_AXIS]

    @property
    def pp_schedule(self) -> str:
        """Pipeline schedule from the plugin ("gpipe" | "1f1b") — pass to the model's
        ``loss_fn_pp(..., schedule=accelerator.pp_schedule)`` so
        ``PipelineParallelPlugin(schedule=...)`` actually takes effect; env override
        ACCELERATE_PP_SCHEDULE mirrors the launcher protocol."""
        env_s = os.environ.get("ACCELERATE_PP_SCHEDULE")
        if env_s:
            if env_s not in ("gpipe", "1f1b"):
                raise ValueError(
                    f"ACCELERATE_PP_SCHEDULE={env_s!r}: expected 'gpipe' or '1f1b'"
                )
            return env_s
        plugin = self.state.pp_plugin
        return plugin.schedule if plugin is not None else "gpipe"

    @property
    def virtual_stages(self) -> int:
        """Interleaved virtual-pipeline chunks per device from the plugin (the Megatron
        ``virtual_pipeline`` analog) — pass to the model's
        ``loss_fn_pp(..., virtual_stages=accelerator.virtual_stages)``; env override
        ACCELERATE_PP_VIRTUAL_STAGES mirrors the launcher protocol."""
        env_v = os.environ.get("ACCELERATE_PP_VIRTUAL_STAGES")
        if env_v:
            v = int(env_v)
            if v < 1:
                # Mirror PipelineParallelPlugin.__post_init__ — an invalid env value
                # must fail here, not as an opaque modulo-by-zero at split time.
                raise ValueError(f"ACCELERATE_PP_VIRTUAL_STAGES={env_v!r} must be >= 1")
            return v
        plugin = self.state.pp_plugin
        return plugin.virtual_stages if plugin is not None else 1

    @property
    def gradient_accumulation_steps(self) -> int:
        return self.gradient_state.num_steps

    @gradient_accumulation_steps.setter
    def gradient_accumulation_steps(self, value: int):
        self.gradient_state.plugin_kwargs.update({"num_steps": value})

    @property
    def sync_gradients(self) -> bool:
        return self.gradient_state.sync_gradients

    @property
    def project_dir(self):
        return self.project_configuration.project_dir

    # ------------------------------------------------------------------- process control
    def wait_for_everyone(self):
        self.state.wait_for_everyone()

    def print(self, *args, **kwargs):
        self.state.print(*args, **kwargs)

    def split_between_processes(self, inputs, apply_padding: bool = False):
        return self.state.split_between_processes(inputs, apply_padding=apply_padding)

    def main_process_first(self):
        """Main host runs the body first, then the rest (reference ``accelerator.py:957``)."""
        return self.state.main_process_first()

    def local_main_process_first(self):
        """Per-node variant of :meth:`main_process_first` (reference ``accelerator.py:979``)."""
        return self.state.local_main_process_first()

    def on_main_process(self, function):
        return self.state.on_main_process(function)

    def on_local_main_process(self, function):
        return self.state.on_local_main_process(function)

    def on_process(self, function=None, process_index=None):
        return self.state.on_process(function, process_index=process_index)

    def on_last_process(self, function):
        return self.state.on_last_process(function)

    # ------------------------------------------------------------------------- prepare
    def prepare(self, *args, device_placement: Optional[list[bool]] = None):
        """Prepare each object for the mesh, preserving order (reference ``:1283``).

        Dispatch by duck type: dataloaders are sharded; optax transformations become
        ``AcceleratedOptimizer``; param pytrees are sharded per the FSDP plugin; stateful
        schedulers become ``AcceleratedScheduler``; flax modules pass through (their params
        are what need preparing).
        """
        if device_placement is None:
            device_placement = [None] * len(args)
        result = tuple(
            self._prepare_one(obj, device_placement=dp) for obj, dp in zip(args, device_placement)
        )
        return result if len(result) > 1 else result[0]

    def _prepare_one(self, obj, device_placement=None):
        if _is_dataloader_like(obj):
            return self.prepare_data_loader(obj)
        # Before the optax duck-type check: AcceleratedOptimizer itself has init/update,
        # so the order decides whether re-prepare is idempotent or double-wraps.
        if isinstance(obj, AcceleratedOptimizer):
            if obj not in self._optimizers:
                self._optimizers.append(obj)
            return obj
        if _is_optax_transformation(obj):
            return self.prepare_optimizer(obj, device_placement=device_placement)
        if _is_stateful_scheduler(obj):
            return self.prepare_scheduler(obj)
        if _is_flax_module(obj):
            self._models.append(obj)
            return obj
        if _is_torch_module(obj):
            raise NotImplementedError(
                "A live torch nn.Module cannot run under the mesh/jit runtime; migrate its "
                "STATE instead: accelerate_tpu.interop.torch_module_to_pytree(module) for "
                "generic state dicts, or models.hf_interop for exact llama/gpt2 conversion "
                "— then pass the pytree with a JAX forward."
            )
        if _is_params_pytree(obj):
            return self.prepare_params(obj)
        return obj

    def prepare_params(self, params, partition_specs=None):
        """Shard a param pytree over the mesh (the ``prepare_model`` analog, reference :1421).

        Casts to the policy's param dtype (fp32 master weights) and applies the combined
        sharding: model TP specs (``partition_specs``, e.g. ``models.llama.partition_specs``)
        first, ZeRO-3/FSDP on the remaining free axes, replicated otherwise (DDP layout).
        """
        policy = self.mixed_precision_policy
        params = cast_floating(params, policy.param_dtype)
        if partition_specs is not None:
            from .parallel.tp import apply_tensor_parallel

            return apply_tensor_parallel(
                params, self.mesh, specs=partition_specs, fsdp_plugin=self.state.fsdp_plugin
            )
        return shard_params(params, self.mesh, self.state.fsdp_plugin)

    prepare_model = prepare_params  # reference-name alias for pytree models

    def prepare_data_loader(self, data_loader, device_placement=None, slice_fn_for_dispatch=None):
        if isinstance(data_loader, (DataLoaderShard, DataLoaderDispatcher)):
            self._dataloaders.append(data_loader)
            return data_loader
        cfg = self.dataloader_config
        device = self.mesh if (device_placement if device_placement is not None else self.device_placement) else None
        prepared = prepare_data_loader(
            data_loader,
            device=device,
            split_batches=cfg.split_batches,
            put_on_device=device is not None,
            rng_types=self.rng_types,
            dispatch_batches=cfg.dispatch_batches,
            even_batches=cfg.even_batches,
            use_seedable_sampler=cfg.use_seedable_sampler,
            data_seed=cfg.data_seed,
            non_blocking=cfg.non_blocking,
            use_stateful_dataloader=cfg.use_stateful_dataloader,
            prefetch_depth=cfg.prefetch_depth,
        )
        self._dataloaders.append(prepared)
        return prepared

    def prepare_optimizer(self, optimizer, device_placement=None) -> AcceleratedOptimizer:
        if isinstance(optimizer, AcceleratedOptimizer):  # idempotent re-prepare
            if optimizer not in self._optimizers:
                self._optimizers.append(optimizer)
            return optimizer
        optimizer = self._apply_fp8_opt_level(optimizer)
        if device_placement is None:
            device_placement = True  # None = unspecified; an explicit False must stick
        wrapped = AcceleratedOptimizer(optimizer, device_placement=device_placement)
        self._optimizers.append(wrapped)
        return wrapped

    def _apply_fp8_opt_level(self, optimizer):
        """MS-AMP ``opt_level="O2"`` analog (reference ``accelerator.py:2164``): store the
        AdamW moments as scaled-fp8. Takes effect on a ``FusedAdamW`` whose moment dtypes
        were left unset; the bandwidth-bound apply then moves 4x fewer moment bytes (its
        end-to-end effect on the current chip tool is not measured)."""
        recipe = self.fp8_recipe
        if recipe is None or getattr(recipe, "opt_level", "O1") != "O2":
            return optimizer
        from .ops.fused_optim import FusedAdamW

        if isinstance(optimizer, FusedAdamW):
            if optimizer.mu_dtype is None and optimizer.nu_dtype is None:
                return dataclass_replace(
                    optimizer,
                    mu_dtype=jnp.float8_e4m3fn,
                    nu_dtype=jnp.float8_e4m3fn,
                )
            return optimizer  # explicit user dtypes win over the recipe
        logger.warning(
            "FP8RecipeKwargs(opt_level='O2') requires the fused optimizer "
            "(accelerate_tpu.ops.fused_optim.fused_adamw) to carry low-precision "
            "moments; %s keeps fp32 optimizer state.",
            type(optimizer).__name__,
        )
        return optimizer

    def prepare_scheduler(self, scheduler) -> AcceleratedScheduler:
        wrapped = AcceleratedScheduler(
            scheduler,
            optimizers=self._optimizers,
            step_with_optimizer=self.step_scheduler_with_optimizer,
            split_batches=self.dataloader_config.split_batches,
        )
        self._schedulers.append(wrapped)
        return wrapped

    # -------------------------------------------------------------------- train state/step
    def _offload_fetch(self, state: TrainState, opt: bool) -> TrainState:
        """cpu_offload: stream host-resident optimizer/accum state into device HBM for one
        step dispatch. Transfers happen OUTSIDE jit (XLA CPU cannot annotate host placement
        on jit outputs); between steps the state lives in pinned host RAM, so HBM holds the
        optimizer moments only during the (activation-free) apply phase."""
        if self._opt_device_shardings is None:
            return state
        updates = {}
        if opt:
            # Single device_put over the whole tree: the runtime batches/overlaps the
            # transfers instead of serializing one PCIe copy per leaf.
            updates["opt_state"] = jax.device_put(state.opt_state, self._opt_device_shardings)
        if state.grad_accum is not None and self._accum_device_shardings is not None:
            updates["grad_accum"] = jax.device_put(
                state.grad_accum, self._accum_device_shardings
            )
        return state.replace(**updates) if updates else state

    def _offload_stash(self, state: TrainState, opt: bool) -> TrainState:
        if self._opt_device_shardings is None:
            return state
        updates = {}
        if opt:
            updates["opt_state"] = jax.device_put(state.opt_state, self._opt_host_shardings)
        if state.grad_accum is not None and self._accum_host_shardings is not None:
            updates["grad_accum"] = jax.device_put(
                state.grad_accum, self._accum_host_shardings
            )
        return state.replace(**updates) if updates else state

    def create_train_state(
        self,
        params,
        optimizer: Union[AcceleratedOptimizer, Any],
        rng: Optional[jax.Array] = None,
        partition_specs=None,
    ) -> TrainState:
        """Build the sharded training carry.

        Params are prepared (cast + sharded); optimizer state is initialized *from the sharded
        params*, so each opt-state leaf inherits its param's sharding. ZeRO stages 1/2
        (``zero_stage`` on the fsdp plugin, reference DeepSpeed partitioned optimizer
        ``utils/dataclasses.py:1019-1448``) additionally shard the optimizer state (stage 1)
        and the gradient-accumulation buffers (stage 2) over the fsdp axis while params stay
        replicated — the train step then reduce-scatters grads and all-gathers updates.
        """
        if not isinstance(optimizer, AcceleratedOptimizer):
            optimizer = self.prepare_optimizer(optimizer)
        params = self.prepare_params(params, partition_specs=partition_specs)
        opt_state = optimizer.init(params)
        # Scalar opt-state leaves (optax step counts) come out of init on ONE device
        # while the compiled step returns them mesh-replicated — without this commit
        # the second step call would silently retrace (found by the ISSUE-3
        # compiles-exactly-once regression guard: every train loop paid the compile
        # twice). Array-valued leaves already inherit their param's sharding.
        _replicated_scalar = _mesh_replicated(self.mesh)
        opt_state = jax.tree_util.tree_map(
            lambda l: jax.device_put(l, _replicated_scalar)
            if isinstance(l, jax.Array)
            and l.ndim == 0
            and not isinstance(l.sharding, NamedSharding)
            else l,
            opt_state,
        )

        from .utils.constants import FSDP_AXIS

        plugin = self.state.fsdp_plugin
        # Ground-truth record of the params' cross-device layout (TP plans, FSDP/ZeRO-3,
        # user partition_specs all included): the fused-optimizer fast path runs sharded
        # leaves under shard_map with exactly these specs (opt-state moments share the
        # param layout in this default path).
        self._params_cross_sharded = any(
            isinstance(l, jax.Array) and not l.sharding.is_fully_replicated
            for l in jax.tree_util.tree_leaves(params)
        )
        self._param_spec_tree = jax.tree_util.tree_map(
            # "opaque" = a layout we can't express as a PartitionSpec; the fused optimizer
            # routes such leaves through plain (partitionable) XLA math, never the kernel.
            lambda l: (
                l.sharding.spec
                if isinstance(l.sharding, NamedSharding)
                else (PartitionSpec() if l.sharding.is_fully_replicated else "opaque")
            )
            if isinstance(l, jax.Array)
            else PartitionSpec(),
            params,
        )
        self._zero_opt_specs = None
        self._zero_grad_specs = None
        if (
            plugin is not None
            and plugin.shards_optimizer
            and not plugin.shards_params
            and self.mesh.shape[FSDP_AXIS] > 1
        ):
            from .parallel.fsdp import get_zero_specs, shard_tree

            self._zero_opt_specs = get_zero_specs(opt_state, self.mesh, plugin)
            opt_state = shard_tree(opt_state, self.mesh, self._zero_opt_specs)
            # Pin the param layout in the apply step: without this, GSPMD propagates the
            # sharded updates into the output params, silently turning stage 1/2 into 3.
            self._zero_param_specs = jax.tree_util.tree_map(
                lambda leaf: leaf.sharding.spec
                if isinstance(leaf, jax.Array) and isinstance(leaf.sharding, NamedSharding)
                else PartitionSpec(),
                params,
            )
            if plugin.shards_grads:
                self._zero_grad_specs = get_zero_specs(params, self.mesh, plugin)

        accum = None
        if self.gradient_accumulation_steps > 1:
            accum = jax.tree_util.tree_map(jnp.zeros_like, params)
            if self._zero_grad_specs is not None:
                from .parallel.fsdp import shard_tree

                accum = shard_tree(accum, self.mesh, self._zero_grad_specs)

        if plugin is not None and plugin.cpu_offload:
            # ZeRO-Offload layout (reference DeepSpeed offload fields, dataclasses.py:1078):
            # optimizer state and accumulation buffers live in pinned host RAM; the apply
            # step streams them through device HBM (SURVEY.md §7 equivalence table).
            def _kinds(tree):
                def _spec(leaf):
                    sh = getattr(leaf, "sharding", None)
                    return sh.spec if isinstance(sh, NamedSharding) else PartitionSpec()

                dev = jax.tree_util.tree_map(
                    lambda l: NamedSharding(self.mesh, _spec(l), memory_kind="device"), tree
                )
                host = jax.tree_util.tree_map(
                    lambda l: NamedSharding(self.mesh, _spec(l), memory_kind="pinned_host"),
                    tree,
                )
                return host, dev

            self._opt_host_shardings, self._opt_device_shardings = _kinds(opt_state)
            opt_state = jax.device_put(opt_state, self._opt_host_shardings)
            if accum is not None:
                self._accum_host_shardings, self._accum_device_shardings = _kinds(accum)
                accum = jax.device_put(accum, self._accum_host_shardings)

        fp8_state = None
        if self.fp8_recipe is not None and self.fp8_recipe.use_delayed_scaling:
            from .ops.fp8 import DelayedScalingState

            fp8_state = DelayedScalingState.init(self.fp8_recipe.amax_history_len)

        optimizer._opt_state_ref = opt_state
        # Scalars are committed mesh-replicated, not left on one device: a checkpoint
        # restore templates its shardings on these leaves (`_abstractify`), and a
        # single-device `step` restored into a >1-device mesh context is an error at the
        # next jitted call (caught by tests/test_elastic.py preemption-resume parity).
        replicated = _mesh_replicated(self.mesh)

        def _counter():
            # Distinct buffers: two leaves sharing one donated buffer would alias.
            return jax.device_put(jnp.zeros((), dtype=jnp.int32), replicated)

        def _replicate(tree):
            # rng keys / fp8 amax histories get the same treatment as the counters.
            return jax.tree_util.tree_map(
                lambda leaf: jax.device_put(leaf, replicated)
                if isinstance(leaf, (jax.Array, np.ndarray))
                else leaf,
                tree,
            )

        return TrainState(
            params=params,
            opt_state=opt_state,
            step=_counter(),
            grad_accum=accum,
            rng=_replicate(rng),
            micro=_counter(),
            fp8_state=_replicate(fp8_state),
        )

    def build_train_step(
        self,
        loss_fn: Callable,
        optimizer: Optional[Union[AcceleratedOptimizer, Any]] = None,
        max_grad_norm: Optional[float] = None,
        max_grad_value: Optional[float] = None,
        has_aux: bool = False,
        donate: bool = True,
        fused_steps: int = 1,
        cast_params: bool = True,
        skip_nonfinite_steps: int = 0,
    ) -> _TrainStep:
        """Compile the training step (the reference hot loop, SURVEY.md §3.4, as one XLA program).

        ``loss_fn(params, batch)`` or ``loss_fn(params, batch, rng)`` returns a scalar loss
        (or ``(loss, aux)`` with ``has_aux=True``). Mixed precision: params are cast to the
        compute dtype *inside* the step so gradients/master weights stay fp32 (the
        autocast + GradScaler-free equivalent of reference ``:1462-1473``).

        ``cast_params=False`` skips that whole-tree cast — pass it when the model casts each
        weight at its point of use (``models.llama`` does, via ``cfg.dtype``): the upfront cast
        materializes a full low-precision copy of the parameters in HBM (and, with scanned
        layers, matching zero-init buffers in the scan backward), which on a 16 GB chip is the
        difference between fitting a ~1B-param adamw step and OOM.

        ``skip_nonfinite_steps=K`` (0 = off, the byte-identical default) arms
        the non-finite guard: an APPLY step whose loss or gradients contain
        NaN/inf skips its update inside the compiled program (params and
        optimizer state pass through unchanged); a non-finite MICRO step's
        contribution is zeroed before it can poison the accumulation window.
        The host counts every guarded call that observed non-finite compute —
        micro or apply; consecutive non-finite COMPUTE is the divergence
        signal, wherever the accumulation boundary falls — and ``K``
        consecutive raise :class:`~.resilience.faults.NonFiniteStepError`
        instead of silently training on divergence (docs/resilience.md). The
        guard costs one boolean device fetch per step.
        """
        if skip_nonfinite_steps < 0:
            raise ValueError(
                f"skip_nonfinite_steps={skip_nonfinite_steps} must be >= 0 (0 = off)"
            )
        if skip_nonfinite_steps and fused_steps > 1:
            raise ValueError(
                "skip_nonfinite_steps needs the per-step host check; with "
                "fused_steps>1 the applies run inside one XLA program where the "
                "host cannot abort between them — use fused_steps=1"
            )
        if optimizer is None:
            if not self._optimizers:
                raise ValueError("No optimizer prepared; pass one to build_train_step.")
            optimizer = self._optimizers[-1]
        if not isinstance(optimizer, AcceleratedOptimizer):
            optimizer = self.prepare_optimizer(optimizer)
        tx = optimizer.optimizer
        policy = self.mixed_precision_policy
        if max_grad_norm is None:
            max_grad_norm = self._max_grad_norm
        if max_grad_value is None:
            max_grad_value = self._max_grad_value
        accum_steps = self.gradient_accumulation_steps
        wants_rng = _loss_fn_wants_rng(loss_fn)
        # Low-precision cross-device gradient reduction (DDP comm-hook analog): honored
        # when the declared reduce_dtype equals the compute dtype — the grad w.r.t. the
        # cast tree is bit-identical to the grad w.r.t. master params pre-upcast, so the
        # only change is where GSPMD places the all-reduce.
        compress_reduce = (
            cast_params
            and policy.reduce_dtype is not None
            and policy.reduce_dtype == policy.compute_dtype
            and policy.compute_dtype != jnp.float32
        )
        if not cast_params and getattr(self, "_explicit_comm_hook", False):
            # The comm_hook passed __init__'s dtype check, but compression rides the
            # whole-tree pre-cast — with cast_params=False it cannot apply. Same
            # accepted-but-ignored policy as the constructor: raise, don't silently
            # reduce uncompressed. (The bf16/fp16 policy's DEFAULT reduce_dtype is not
            # a user request and does not trigger this.)
            raise ValueError(
                "a gradient-compression comm_hook is configured (reduce_dtype="
                f"{policy.reduce_dtype.__name__}) but build_train_step(cast_params="
                "False) disables the parameter pre-cast it rides on — drop the "
                "comm_hook or keep cast_params=True"
            )
        self._reduce_compressed = compress_reduce  # introspection/testing

        def compute(state: TrainState, batch):
            step_rng = None
            if state.rng is not None:
                # Unique key per micro-batch: step alone would repeat dropout masks across
                # an accumulation window.
                micro = state.micro if state.micro is not None else 0
                step_rng = jax.random.fold_in(state.rng, state.step * accum_steps + micro)

            def wrapped(params):
                cparams = cast_floating(params, policy.compute_dtype) if cast_params else params
                out = loss_fn(cparams, batch, step_rng) if wants_rng else loss_fn(cparams, batch)
                loss, aux = out if has_aux else (out, None)
                return jnp.asarray(loss, dtype=jnp.float32), aux

            if state.fp8_state is not None:
                # Delayed-scaling fp8: thread the rolling-history scales into every fp8_dot.
                # Forward x/w amaxes are observed exactly (global-per-role granularity vs
                # TE's per-module buffers); the GRAD role stays on current scaling — the
                # output cotangent g is quantized inside the custom_vjp, so no faithfully
                # observed g-amax exists at this level, and any proxy (e.g. the dw amax,
                # ~10^3× larger) would underflow small cotangents to zero in e5m2.
                from .ops.fp8 import autoscale_ctx, delayed_scales

                recipe = self.fp8_recipe
                scales = delayed_scales(
                    state.fp8_state, recipe.fp8_format, recipe.margin,
                    recipe.amax_compute_algo,
                ).at[2].set(jnp.nan)  # NaN → fp8_dot falls back to current scaling for g

                def wrapped_fp8(params):
                    # The ctx must open INSIDE the differentiated function: its collected
                    # amaxes are inner-trace values and must leave as aux outputs, not by
                    # escaping through the context dict (tracer leak).
                    with autoscale_ctx(scales) as ctx:
                        loss, aux = wrapped(params)
                        return loss, (aux, ctx["amax"])

                (loss, (aux, fwd_amax)), grads = jax.value_and_grad(
                    wrapped_fp8, has_aux=True
                )(state.params)
                new_fp8 = state.fp8_state.update(
                    fwd_amax[0], fwd_amax[1], jnp.zeros((), jnp.float32)
                )
            elif compress_reduce:
                # reduce_dtype consumer (the DDP bf16 comm-hook analog): differentiate
                # w.r.t. the CAST (compute-dtype) tree and upcast to the master dtype
                # afterwards. Mathematically identical — the backward of the cast IS that
                # upcast — but GSPMD now attaches the cross-device gradient all-reduce to
                # the low-precision tensors, halving the reduction bytes on ICI/DCN.
                cparams = cast_floating(state.params, policy.compute_dtype)

                def inner(cp):
                    out = loss_fn(cp, batch, step_rng) if wants_rng else loss_fn(cp, batch)
                    loss, aux = out if has_aux else (out, None)
                    return jnp.asarray(loss, dtype=jnp.float32), aux

                (loss, aux), gradsc = jax.value_and_grad(inner, has_aux=True)(cparams)
                grads = jax.tree_util.tree_map(
                    lambda g, p: g.astype(p.dtype) if jnp.issubdtype(p.dtype, jnp.floating)
                    else g,
                    gradsc,
                    state.params,
                )
                new_fp8 = None
            else:
                (loss, aux), grads = jax.value_and_grad(wrapped, has_aux=True)(state.params)
                new_fp8 = None
            if self._zero_grad_specs is not None:
                # ZeRO-2: constrain grads onto the fsdp axis — GSPMD lowers the data-axis
                # all-reduce into a reduce-scatter and keeps grads partitioned.
                from .ops.collectives import maybe_shard

                grads = jax.tree_util.tree_map(
                    lambda g, s: maybe_shard(g, s), grads, self._zero_grad_specs
                )
            return loss, aux, grads, new_fp8

        nonfinite_guard = skip_nonfinite_steps > 0

        def _all_finite(loss, grads):
            # One fused reduction over loss + every float grad leaf; int leaves
            # (none today) cannot be non-finite and are skipped.
            finite = jnp.isfinite(loss)
            for leaf in jax.tree_util.tree_leaves(grads):
                if jnp.issubdtype(leaf.dtype, jnp.inexact):
                    finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(leaf)))
            return finite

        def micro_step(state: TrainState, batch):
            loss, aux, grads, new_fp8 = compute(state, batch)
            if nonfinite_guard:
                # A non-finite micro contribution would poison the whole
                # accumulation window: zero it out and flag the step.
                finite = _all_finite(loss, grads)
                grads = jax.tree_util.tree_map(
                    lambda g: jnp.where(finite, g, jnp.zeros_like(g)), grads
                )
            if state.grad_accum is None:
                # First no_sync() use with accumulation disabled: adopt grads as the buffer
                # (structure change → one retrace, then stable).
                accum = grads
            else:
                accum = jax.tree_util.tree_map(jnp.add, state.grad_accum, grads)
            metrics = {"loss": loss}
            if nonfinite_guard:
                metrics["nonfinite"] = jnp.logical_not(finite)
            if has_aux:
                metrics["aux"] = aux
            micro = (state.micro if state.micro is not None else 0) + 1
            return (
                state.replace(
                    grad_accum=accum, micro=jnp.asarray(micro, jnp.int32), fp8_state=new_fp8
                ),
                metrics,
            )

        def apply_step(state: TrainState, batch):
            with jax.named_scope("loss_and_grad"):
                loss, aux, grads, new_fp8 = compute(state, batch)
            if state.grad_accum is not None:
                grads = jax.tree_util.tree_map(jnp.add, state.grad_accum, grads)
            if accum_steps > 1:
                grads = jax.tree_util.tree_map(lambda g: g / accum_steps, grads)
            finite = _all_finite(loss, grads) if nonfinite_guard else None
            metrics = {"loss": loss}
            # Fused single-pass optimizers (ops/fused_optim.FusedAdamW) take the clip
            # factor as a scalar and fold it into their one HBM pass over the grads —
            # pre-scaling the tree here would cost an extra full read+write.
            # Sharded states: a pallas_call cannot partition under GSPMD, so sharded
            # leaves run the kernel under shard_map with the recorded param specs (valid
            # when moments share the param layout — the create_train_state default, i.e.
            # FSDP/ZeRO-3/TP). ZeRO-1/2 (opt layout differs from params) falls back to
            # tx.update, which FusedAdamW also provides.
            fused_opt = getattr(tx, "fused_apply", None)
            fused_specs = None
            if fused_opt is not None:
                plugin = self.state.fsdp_plugin
                if self._zero_opt_specs is not None or self._zero_param_specs is not None:
                    fused_opt = None
                elif self._params_cross_sharded:
                    fused_specs = self._param_spec_tree
                    if fused_specs is None:
                        fused_opt = None
                elif self._params_cross_sharded is None:
                    # User-managed TrainState (no create_train_state record): the layout
                    # is unknown, so on ANY multi-device mesh assume leaves may be
                    # cross-device sharded (manual NamedShardings, TP without the plugin,
                    # ...) and fall back to tx.update — an unmapped pallas_call would
                    # force GSPMD to gather the full param+moment trees onto one device.
                    if self.mesh is not None and self.mesh.size > 1:
                        fused_opt = None
            grad_scale = None
            with jax.named_scope("clip"):
                if max_grad_value is not None:
                    # Elementwise clamp BEFORE the norm clip (a torch user calls
                    # clip_grad_value_ then clip_grad_norm_ in that order between
                    # backward and step; the norm below is the norm of the clamped
                    # tree). Unlike the norm clip this cannot fold into the fused
                    # apply's scalar grad_scale — it materializes a clipped tree
                    # either way.
                    v = jnp.asarray(max_grad_value, jnp.float32)
                    grads = jax.tree_util.tree_map(
                        lambda g: jnp.clip(g, -v.astype(g.dtype), v.astype(g.dtype)), grads
                    )
                if max_grad_norm is not None:
                    gnorm = _global_norm(grads)
                    scale = jnp.minimum(1.0, max_grad_norm / (gnorm + 1e-6))
                    metrics["grad_norm"] = jnp.asarray(gnorm, jnp.float32)
                    if fused_opt is None:
                        grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
                    else:
                        grad_scale = scale
            import optax

            with jax.named_scope("optimizer"):
                if fused_opt is not None:
                    new_params, new_opt_state = fused_opt(
                        grads, state.opt_state, state.params,
                        grad_scale=1.0 if grad_scale is None else grad_scale,
                        specs=fused_specs,
                        mesh=self.mesh if fused_specs is not None else None,
                    )
                    updates = None
                else:
                    updates, new_opt_state = tx.update(grads, state.opt_state, state.params)
                if self._zero_opt_specs is not None:
                    # ZeRO-1/2: keep optimizer state partitioned over the fsdp axis
                    # across steps (params replicated; GSPMD all-gathers the sharded
                    # updates below).
                    from .ops.collectives import maybe_shard

                    new_opt_state = jax.tree_util.tree_map(
                        lambda o, s: maybe_shard(o, s), new_opt_state, self._zero_opt_specs
                    )
                if updates is not None:
                    new_params = optax.apply_updates(state.params, updates)
            if self._zero_param_specs is not None:
                from .ops.collectives import maybe_shard

                new_params = jax.tree_util.tree_map(
                    lambda p, s: maybe_shard(p, s), new_params, self._zero_param_specs
                )
            new_accum = state.grad_accum
            if new_accum is not None:
                new_accum = jax.tree_util.tree_map(jnp.zeros_like, new_accum)
                if self._zero_grad_specs is not None:
                    from .ops.collectives import maybe_shard

                    new_accum = jax.tree_util.tree_map(
                        lambda a, s: maybe_shard(a, s), new_accum, self._zero_grad_specs
                    )
            if has_aux:
                metrics["aux"] = aux
            step_inc = 1
            if nonfinite_guard:
                # Skip-don't-apply: a non-finite update passes the old params/
                # opt state (and fp8 scales) through unchanged inside the SAME
                # compiled program — no second "skip" executable, no retrace.
                # The window's accumulated garbage is dropped with the reset
                # below; the host counts the skip off metrics["nonfinite"].
                def _keep(new, old):
                    return jax.tree_util.tree_map(
                        lambda n, o: jnp.where(finite, n, o), new, old
                    )

                new_params = _keep(new_params, state.params)
                new_opt_state = _keep(new_opt_state, state.opt_state)
                if state.fp8_state is not None:
                    new_fp8 = _keep(new_fp8, state.fp8_state)
                step_inc = jnp.where(finite, 1, 0)
                metrics["nonfinite"] = jnp.logical_not(finite)
            return (
                state.replace(
                    params=new_params,
                    opt_state=new_opt_state,
                    step=state.step + step_inc,
                    grad_accum=new_accum,
                    # Reset derived from the input, not a fresh constant: XLA cannot
                    # alias a constant output into the donated buffer, so zeros(())
                    # here left state.micro's donation dead (graftaudit dead-donation).
                    # int32 counter — multiply-by-zero is exact.
                    micro=state.micro * 0 if state.micro is not None else None,
                    fp8_state=new_fp8,
                ),
                metrics,
            )

        donate_args = (0,) if donate else ()
        if fused_steps > 1:
            if fused_steps % accum_steps != 0:
                raise ValueError(
                    f"fused_steps ({fused_steps}) must be a multiple of "
                    f"gradient_accumulation_steps ({accum_steps})"
                )
            if self._schedulers:
                raise ValueError(
                    "fused_steps>1 compiles the optimizer applies into one XLA program, so a "
                    "host-stepped scheduler cannot fire between them. Encode the schedule in "
                    "the optimizer instead (e.g. optax.warmup_cosine_decay_schedule passed to "
                    "adamw) — it is traced per-step from the optimizer state's count."
                )

            def micro_step_padded(st, batch):
                # lax.cond branches need identical metric structures; pad the micro branch
                # with the keys only apply_step produces.
                new_st, metrics = micro_step(st, batch)
                if max_grad_norm is not None:
                    metrics["grad_norm"] = jnp.zeros((), jnp.float32)
                return new_st, metrics

            def fused(state: TrainState, batches):
                def body(st, batch):
                    if accum_steps == 1:
                        new_st, metrics = apply_step(st, batch)
                    else:
                        micro = st.micro if st.micro is not None else jnp.zeros((), jnp.int32)
                        is_sync = (micro + 1) % accum_steps == 0
                        new_st, metrics = jax.lax.cond(
                            is_sync, apply_step, micro_step_padded, st, batch
                        )
                    return new_st, metrics

                return jax.lax.scan(body, state, batches)

            jit_fused = self.compile_cache.wrap(
                jax.jit(fused, donate_argnums=donate_args), "train_step.fused"
            )
            return _FusedTrainStep(self, jit_fused, fused_steps, optimizer=optimizer)

        jit_micro = self.compile_cache.wrap(
            jax.jit(micro_step, donate_argnums=donate_args), "train_step.micro"
        )
        jit_apply = self.compile_cache.wrap(
            jax.jit(apply_step, donate_argnums=donate_args), "train_step.apply"
        )
        return _TrainStep(self, jit_micro, jit_apply, optimizer=optimizer,
                          skip_nonfinite_steps=skip_nonfinite_steps)

    def build_eval_step(self, eval_fn: Callable, donate: bool = False) -> Callable:
        """Jit an eval function ``eval_fn(params, batch) -> outputs`` with compute-dtype cast."""
        policy = self.mixed_precision_policy

        def wrapped(params, batch):
            cparams = cast_floating(params, policy.compute_dtype)
            out = eval_fn(cparams, batch)
            if policy.output_dtype == jnp.float32:
                out = cast_floating(out, jnp.float32)
            return out

        jitted = self.compile_cache.wrap(jax.jit(wrapped), "eval_step")
        mesh = self.mesh

        @functools.wraps(wrapped)
        def with_mesh(params, batch):
            with mesh_context(mesh):
                return jitted(params, batch)

        def warm(params, batch):
            # Warmup-manifest hook: prime the AOT cache for this signature without
            # executing the eval (no-op live entry when the cache is disabled).
            if not hasattr(jitted, "warm"):
                return {"label": "eval_step", "key": None, "status": "live", "seconds": 0.0}
            with mesh_context(mesh):
                return jitted.warm(params, batch)

        with_mesh.warm = warm
        return with_mesh

    # -------------------------------------------------------- accumulation / sync contexts
    @contextlib.contextmanager
    def accumulate(self, *models):
        """Gradient-accumulation context (reference ``:1116``).

        Counts entries; ``sync_gradients`` is True every ``gradient_accumulation_steps``-th
        entry or at end-of-dataloader (``sync_with_dataloader``). The jitted step built by
        ``build_train_step`` reads the flag host-side to pick the accumulate vs apply program.
        """
        self._accumulate_count += 1
        at_end = self.gradient_state.sync_with_dataloader and self.gradient_state.end_of_dataloader
        do_sync = (
            (self._accumulate_count % self.gradient_accumulation_steps == 0)
            or at_end
            or self.gradient_state.sync_each_batch
        )
        self.gradient_state._set_sync_gradients(do_sync)
        self._in_accumulate_ctx = True
        try:
            yield
        finally:
            self._in_accumulate_ctx = False

    @contextlib.contextmanager
    def no_sync(self, model=None):
        """Force-skip gradient sync (reference ``:1001``). Under GSPMD this only toggles the
        host flag — the compiled accumulate-variant performs no cross-device grad traffic."""
        prev = self.gradient_state.sync_gradients
        self.gradient_state._set_sync_gradients(False)
        self._in_accumulate_ctx = True
        try:
            yield
        finally:
            self.gradient_state._set_sync_gradients(prev)
            self._in_accumulate_ctx = False

    @contextlib.contextmanager
    def autocast(self, autocast_handler=None):
        """API-parity context (reference ``:3587``): under JAX the compute-dtype cast happens
        inside the compiled step; this context exists so reference-style code runs unchanged."""
        yield

    @contextlib.contextmanager
    def profile(self, profile_handler=None):
        """Profile the enclosed block with ``jax.profiler`` (reference ``:3614``).

        Two modes, decided by ``ProfileKwargs.schedule_option``:

        - **Scheduled** (``schedule_option`` set): yields a
          ``telemetry.ScheduledProfiler`` — call its ``step()`` once per train step
          and traces cover exactly the wait/warmup/active/repeat windows (one
          ``cycle<N>`` trace directory per repeat), the torch
          ``torch.profiler.schedule`` semantics. ``on_trace_ready(path)`` fires per
          window.
        - **Whole-block** (no schedule): the block is captured with one
          ``jax.profiler`` trace (TensorBoard/perfetto-compatible, includes XLA HLO +
          TPU device timelines); ``on_trace_ready(trace_dir)`` fires on exit.

        ``profile_memory`` writes a pprof device-memory profile beside each trace in
        both modes.
        """
        from .utils.dataclasses import ProfileKwargs

        handler = profile_handler or getattr(self, "profile_handler", None) or ProfileKwargs()
        if handler.schedule_option is not None:
            from .telemetry import ScheduledProfiler

            profiler = ScheduledProfiler.from_profile_kwargs(handler)
            if not self.is_main_process:
                # Same contract as the whole-block branch below: the user callback
                # fires once per window, not once per process.
                profiler.on_trace_ready = None
            try:
                yield profiler
            finally:
                profiler.close()
            return
        trace_dir = handler.output_trace_dir
        if trace_dir is None:
            import tempfile

            trace_dir = tempfile.mkdtemp(prefix="accelerate_tpu_trace_")
        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(trace_dir)
        try:
            yield handler
        finally:
            jax.profiler.stop_trace()
            if handler.profile_memory:
                try:
                    jax.profiler.save_device_memory_profile(
                        os.path.join(trace_dir, "device_memory.prof")
                    )
                except Exception:  # backends without a memory profile: trace stands
                    pass
            if handler.on_trace_ready is not None and self.is_main_process:
                handler.on_trace_ready(trace_dir)

    def build_serving_gateway(self, engine, clock=None, tracer=None,
                              engine_factory=None):
        """Front a ``ContinuousBatcher`` with the SLO-aware request gateway
        (``serving_gateway.ServingGateway``), resolved from the state-resident
        ``GatewayConfig`` (``Accelerator(gateway_config=...)`` or
        ``ACCELERATE_GATEWAY`` env) and sharing this accelerator's telemetry
        pipeline. With the config disabled (the default) the engine is returned
        unchanged — callers drive one object either way (both expose
        ``submit``/``step``/``run``/``stats``).

        ``engine`` may also be a LIST of engine replicas: the result is then a
        ``serving_gateway.fleet.FleetRouter`` — the same submit/step/run
        contract over the whole fleet, with health-driven routing, per-replica
        circuit breakers and lossless failover (docs/resilience.md).
        ``engine_factory(rid)`` (fleet only) builds replacement engines for
        replica restarts.

        ``tracer`` threads a request-scoped ``telemetry.tracing.Tracer``
        through gateway AND engine (the gateway hands it to an engine that has
        none), so per-request spans cover the whole lifecycle
        (docs/telemetry.md)."""
        config = self.state.gateway_config
        is_fleet = isinstance(engine, (list, tuple))
        if not config.enabled:
            if is_fleet:
                raise ValueError(
                    "a fleet of engines needs the gateway enabled: there is no "
                    "bare-engine equivalent of a multi-replica router (set "
                    "GatewayConfig(enabled=True) or ACCELERATE_GATEWAY=1)"
                )
            return engine
        kwargs = {} if clock is None else {"clock": clock}
        if is_fleet:
            if config.replica_roles is not None:
                # Role-split fleet (docs/disaggregated_serving.md): prefill
                # replicas export KV page handoffs, decode replicas adopt them.
                from .serving_gateway import DisaggRouter

                return DisaggRouter(list(engine), config,
                                    telemetry=self.telemetry, tracer=tracer,
                                    engine_factory=engine_factory, **kwargs)
            from .serving_gateway import FleetRouter

            return FleetRouter(list(engine), config, telemetry=self.telemetry,
                               tracer=tracer, engine_factory=engine_factory,
                               **kwargs)
        from .serving_gateway import ServingGateway

        return ServingGateway(engine, config, telemetry=self.telemetry,
                              tracer=tracer, **kwargs)

    @contextlib.contextmanager
    def join_uneven_inputs(self, joinables, even_batches: Optional[bool] = None):
        """Reference ``:1197``: with mesh-global batches, uneven inputs are already handled by
        the dataloader's even_batches padding; honor an override for this block."""
        cfg = self.dataloader_config
        prev = cfg.even_batches
        if even_batches is not None:
            cfg.even_batches = even_batches
        try:
            yield
        finally:
            cfg.even_batches = prev

    # ----------------------------------------------------------------- gradient utilities
    def backward(self, loss, **kwargs):
        raise RuntimeError(
            "JAX has no backward tape: gradients are computed inside the compiled train step. "
            "Use `step = accelerator.build_train_step(loss_fn)` and call "
            "`state, metrics = step(state, batch)` — or `accelerator.value_and_grad(loss_fn)` "
            "for manual loops."
        )

    def value_and_grad(self, loss_fn: Callable, has_aux: bool = False) -> Callable:
        """Mixed-precision-aware ``jax.value_and_grad`` for manual training loops."""
        policy = self.mixed_precision_policy

        def wrapped(params, *args, **kwargs):
            def inner(p):
                return loss_fn(cast_floating(p, policy.compute_dtype), *args, **kwargs)

            return jax.value_and_grad(inner, has_aux=has_aux)(params)

        return wrapped

    def clip_grad_norm_(self, max_grad_norm: float):
        """Record the global-norm clip applied inside subsequently-built train steps
        (reference ``:2485``; returns None — the realized norm is in step metrics)."""
        self._max_grad_norm = float(max_grad_norm)

    def clip_grad_value_(self, clip_value: float):
        """Record an elementwise gradient clamp to ``[-clip_value, clip_value]`` applied
        inside subsequently-built train steps (reference ``accelerator.py:2542``
        ``clip_grad_value_`` → ``torch.nn.utils.clip_grad_value_``; here the clamp is
        traced into the step, before any ``clip_grad_norm_`` norm scaling — the order a
        torch user would call the pair in)."""
        self._max_grad_value = float(clip_value)

    # ---------------------------------------------------------------------- metrics / ops
    def set_trigger(self):
        """Arm the cross-process breakpoint flag (reference ``accelerator.py:2569``):
        any process may set it; ``check_trigger`` fires on ALL processes."""
        self.flag_tensor = 1

    def check_trigger(self) -> bool:
        """True on every process if any process called ``set_trigger`` since the last check
        (reference ``:2583``) — the synchronized early-stopping primitive."""
        local = np.asarray([self.flag_tensor or 0], dtype=np.float32)
        fired = float(np.asarray(reduce(local, reduction="sum")).reshape(-1)[0]) > 0
        self.flag_tensor = None
        return fired

    def gather(self, tensor):
        return gather(tensor)

    def gather_for_metrics(self, input_data, use_gather_object: bool = False):
        """Gather + drop the duplicate tail samples of the final batch (reference ``:2601``).

        The dataloader's even_batches padding duplicates samples in the last global batch;
        ``GradientState.remainder`` (set by the prepared dataloader) says how many are real.
        """
        try:
            recursively_apply(lambda x: x, input_data, error_on_other_type=True)
            all_tensors = True
        except TypeError:
            all_tensors = False

        if use_gather_object or not all_tensors:
            data = gather_object(input_data)
        else:
            data = gather(input_data)

        if self.gradient_state.end_of_dataloader:
            remainder = self.gradient_state.remainder
            if remainder > 0:

                def _trim(tensor):
                    return tensor[:remainder]

                try:
                    if use_gather_object or not all_tensors:
                        return data[:remainder]
                    return recursively_apply(_trim, data)
                except (TypeError, IndexError):
                    # Unsliceable payload (objects without __getitem__ → TypeError, 0-d
                    # scalar tensors → IndexError): fall back to untrimmed, matching the
                    # reference's behavior of only trimming indexable containers. Real
                    # errors propagate.
                    logger.warning(
                        "gather_for_metrics could not trim the duplicate tail of the last "
                        "batch; returning untrimmed data"
                    )
                    return data
        return data

    def reduce(self, tensor, reduction: str = "sum", scale: float = 1.0):
        return reduce(tensor, reduction=reduction, scale=scale)

    def pad_across_processes(self, tensor, dim: int = 0, pad_index: int = 0, pad_first: bool = False):
        return pad_across_processes(tensor, dim=dim, pad_index=pad_index, pad_first=pad_first)

    # ----------------------------------------------------------------------- model utils
    def unwrap_model(self, model, keep_fp32_wrapper: bool = True):
        return model

    def get_state_dict(self, model, unwrap: bool = True):
        """Full (unsharded) host state dict of a param pytree (reference ``:3500``)."""
        from .parallel.fsdp import gather_full_params

        return gather_full_params(model)

    def free_memory(self, *objects):
        """Release references + device buffers (reference ``:3545``)."""
        self._models.clear()
        self._optimizers.clear()
        self._schedulers.clear()
        self._dataloaders.clear()
        self.step = 0
        jax.clear_caches()
        return objects

    def clear(self, *objects):
        return self.free_memory(*objects)

    # ------------------------------------------------------------------- checkpoint hooks
    def register_for_checkpointing(self, *objects):
        """Register custom stateful objects for save_state/load_state (reference ``:3067``)."""
        invalid = [o for o in objects if not (hasattr(o, "state_dict") and hasattr(o, "load_state_dict"))]
        if invalid:
            raise ValueError(
                f"Objects {invalid} lack state_dict/load_state_dict and cannot be registered."
            )
        self._custom_objects.extend(objects)

    def register_save_state_pre_hook(self, hook: Callable):
        self._save_model_hooks.append(hook)
        return _RemovableHandle(self._save_model_hooks, hook)

    def register_load_state_pre_hook(self, hook: Callable):
        self._load_model_hooks.append(hook)
        return _RemovableHandle(self._load_model_hooks, hook)

    def save_state(self, output_dir: Optional[str] = None, train_state: Optional[TrainState] = None, **save_kwargs):
        from .checkpointing import save_accelerator_state

        return save_accelerator_state(self, output_dir, train_state=train_state, **save_kwargs)

    def load_state(self, input_dir: Optional[str] = None, train_state: Optional[TrainState] = None, **load_kwargs):
        from .checkpointing import load_accelerator_state

        return load_accelerator_state(self, input_dir, train_state=train_state, **load_kwargs)

    def skip_first_batches(self, dataloader, num_batches: int = 0):
        return skip_first_batches(dataloader, num_batches=num_batches)

    # ------------------------------------------------------------------------ trackers
    def init_trackers(self, project_name: str, config: Optional[dict] = None, init_kwargs: dict = None):
        from .tracking import filter_trackers

        self.trackers = filter_trackers(self.log_with, self.logging_dir, project_name, config, init_kwargs or {})

    @property
    def logging_dir(self):
        return self.project_configuration.logging_dir

    def _telemetry_tracker_sink(self, record: dict) -> None:
        """Fan a telemetry record out to every configured tracker (JSONL gets the raw
        record; scalar backends get it flattened — see tracking.log_telemetry_record)."""
        if self.is_main_process and self.trackers:
            from .tracking import log_telemetry_record

            log_telemetry_record(self.trackers, record, step=record.get("step"))

    def log(self, values: dict, step: Optional[int] = None, log_kwargs: dict = None):
        if self.telemetry.enabled and self.telemetry.config.merge_into_log:
            # Auto-merge the latest step's telemetry columns (prefixed telemetry/, so
            # user keys can never collide; explicit values always win regardless).
            merged = self.telemetry.log_columns()
            if merged:
                values = {**merged, **values}
        if self.is_main_process:
            for tracker in self.trackers:
                tracker.log(values, step=step, **(log_kwargs or {}).get(tracker.name, {}))

    def log_images(self, values: dict, step: Optional[int] = None, log_kwargs: dict = None):
        """Fan ``{name: image array}`` out to every tracker that supports images
        (reference ``tracking.py:251``; unsupported backends warn and skip)."""
        if self.is_main_process:
            for tracker in self.trackers:
                tracker.log_images(
                    values, step=step, **(log_kwargs or {}).get(tracker.name, {})
                )

    def log_table(
        self,
        table_name: str,
        columns: Optional[list] = None,
        data: Optional[list] = None,
        dataframe=None,
        step: Optional[int] = None,
        log_kwargs: dict = None,
    ):
        """Fan a table (``columns`` + ``data`` rows, or a pandas ``dataframe``) out to
        every tracker that supports tables (reference ``tracking.py:360``)."""
        if self.is_main_process:
            for tracker in self.trackers:
                tracker.log_table(
                    table_name, columns=columns, data=data, dataframe=dataframe,
                    step=step, **(log_kwargs or {}).get(tracker.name, {})
                )

    def log_artifact(self, file_path: str, name: Optional[str] = None):
        """Upload a file to every tracker with an artifact store (MLflow/ClearML/WandB
        analog of the reference's artifact logging)."""
        if self.is_main_process:
            for tracker in self.trackers:
                tracker.log_artifact(file_path, name=name)

    def get_tracker(self, name: str, unwrap: bool = False):
        for tracker in self.trackers:
            if tracker.name == name:
                return tracker.tracker if unwrap else tracker
        raise ValueError(f"Tracker {name} not initialized")

    def wait_for_checkpoint(self):
        """Join any in-flight ``save_state(async_save=True)`` disk write."""
        from .checkpointing import wait_for_async_save

        wait_for_async_save()

    def end_training(self):
        self.wait_for_checkpoint()
        self.telemetry.close()
        for tracker in self.trackers:
            tracker.finish()
        self.wait_for_everyone()

    def __repr__(self):
        return (
            f"Accelerator(distributed_type={self.distributed_type}, "
            f"mixed_precision={self.mixed_precision!r}, "
            f"grad_accum={self.gradient_accumulation_steps}, "
            f"mesh={dict(zip(self.mesh.axis_names, self.mesh.devices.shape))})"
        )


class _RemovableHandle:
    def __init__(self, container: list, item):
        self.container = container
        self.item = item

    def remove(self):
        if self.item in self.container:
            self.container.remove(self.item)


# ------------------------------------------------------------------------- type sniffing
def _is_dataloader_like(obj) -> bool:
    if isinstance(obj, (DataLoaderShard, DataLoaderDispatcher)):
        return True
    if type(obj).__module__.startswith("torch.utils.data"):
        return True
    return hasattr(obj, "__iter__") and (hasattr(obj, "batch_sampler") or hasattr(obj, "dataset"))


def _is_optax_transformation(obj) -> bool:
    return (
        hasattr(obj, "init")
        and hasattr(obj, "update")
        and not hasattr(obj, "apply")
        and not isinstance(obj, type)
        and not _is_params_pytree(obj)
    )


def _is_stateful_scheduler(obj) -> bool:
    return hasattr(obj, "step") and hasattr(obj, "state_dict") and not hasattr(obj, "update")


def _is_flax_module(obj) -> bool:
    mod = type(obj).__module__
    return mod.startswith("flax") and hasattr(obj, "apply")


def _is_torch_module(obj) -> bool:
    mod = type(obj).__module__
    return mod.startswith("torch") and hasattr(obj, "forward")


def _is_params_pytree(obj) -> bool:
    if not isinstance(obj, dict) or not obj:
        return False
    leaves = jax.tree_util.tree_leaves(obj)
    return len(leaves) > 0 and all(isinstance(l, (jax.Array, np.ndarray)) for l in leaves)


def _loss_fn_wants_rng(loss_fn) -> bool:
    try:
        sig = inspect.signature(loss_fn)
    except (TypeError, ValueError):
        return False
    params = [
        p
        for p in sig.parameters.values()
        if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
    ]
    return len(params) >= 3 or "rng" in sig.parameters


def _global_norm(tree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves))
