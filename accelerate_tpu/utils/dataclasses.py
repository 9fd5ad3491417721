"""Enums, plugin dataclasses and kwargs handlers — the config layer (L4).

TPU-native analog of reference ``utils/dataclasses.py``
(/root/reference/src/accelerate/utils/dataclasses.py): ``DistributedType`` (:552),
``GradientAccumulationPlugin`` (:920), ``FullyShardedDataParallelPlugin`` (:1449),
``TorchTensorParallelPlugin`` (:1863), ``DeepSpeedPlugin`` (:1019), ``ProjectConfiguration``
(:857), ``DataLoaderConfiguration`` (:762), kwargs handlers (:62-551).

Where the reference's plugins configure external engines (DeepSpeed JSON, FSDP wrap policies,
Megatron args), ours configure **mesh axes and GSPMD sharding rules** — the single TPU-native
mechanism that subsumes DDP/ZeRO/FSDP/TP/PP/SP/EP (SURVEY.md §7 equivalence table).
"""

from __future__ import annotations

import copy
import enum
import os
from dataclasses import dataclass, field
from datetime import timedelta
from typing import Any, Callable, Optional

import jax.numpy as jnp

from .environment import parse_flag_from_env


class KwargsHandler:
    """Base mixin for kwargs dataclasses; mirrors reference ``dataclasses.py:62``."""

    def to_dict(self) -> dict[str, Any]:
        return copy.deepcopy(self.__dict__)

    def to_kwargs(self) -> dict[str, Any]:
        """Return only the fields that differ from the dataclass defaults."""
        default = self.__class__()
        return {k: v for k, v in self.to_dict().items() if getattr(default, k) != v}


class EnumWithContains(enum.EnumMeta):
    def __contains__(cls, item):
        try:
            cls(item)
        except ValueError:
            return False
        return True


class BaseEnum(str, enum.Enum, metaclass=EnumWithContains):
    def __str__(self):
        return self.value

    @classmethod
    def list(cls):
        return list(map(str, cls))


class DistributedType(BaseEnum):
    """Which parallelism mode the Accelerator is driving.

    Reference enum at ``dataclasses.py:552-586`` enumerates *device kinds*
    (MULTI_GPU/MULTI_NPU/...); on TPU there is a single device kind, so ours enumerates
    *sharding strategies*. ``MULTI_DEVICE`` is plain data parallelism (the DDP analog).
    """

    NO = "NO"
    MULTI_DEVICE = "MULTI_DEVICE"
    FSDP = "FSDP"
    TP = "TP"
    PP = "PP"
    SP = "SP"
    EP = "EP"
    HYBRID = "HYBRID"  # any >=2-axis combination (the Megatron-LM 3D analog)
    MULTI_HOST = "MULTI_HOST"


class PrecisionType(BaseEnum):
    NO = "no"
    BF16 = "bf16"
    FP16 = "fp16"
    FP8 = "fp8"


class RNGType(BaseEnum):
    JAX = "jax"
    NUMPY = "numpy"
    PYTHON = "python"
    GENERATOR = "generator"  # torch CPU generator (data-order RNG when torch is present)
    TORCH = "torch"


class LoggerType(BaseEnum):
    """Tracker names accepted by ``Accelerator(log_with=...)`` (reference
    ``utils/dataclasses.py:584``); each maps to a class in ``tracking.py``."""

    ALL = "all"
    TENSORBOARD = "tensorboard"
    WANDB = "wandb"
    COMETML = "comet_ml"
    MLFLOW = "mlflow"
    AIM = "aim"
    CLEARML = "clearml"
    DVCLIVE = "dvclive"


class ComputeEnvironment(BaseEnum):
    """Where the job runs (reference ``utils/dataclasses.py:565``). The TPU-native values
    mirror the ``accelerate-tpu config`` questionnaire (``commands/config.py:52``):
    SageMaker is a justified non-port; TPU pods and the CPU simulator take its place."""

    LOCAL_MACHINE = "LOCAL_MACHINE"
    TPU_POD = "TPU_POD"
    CPU_SIMULATOR = "CPU_SIMULATOR"


class ZeroStage(enum.IntEnum):
    """DeepSpeed-ZeRO stage analog: what gets sharded along the fsdp axis.

    Stage 1 shards optimizer state; stage 2 additionally uses reduce-scatter for gradients;
    stage 3 additionally shards parameters (== torch FSDP FULL_SHARD). On TPU all three are
    sharding annotations on the train-state pytree (SURVEY.md §2.2 ZeRO row).
    """

    ZERO_0 = 0  # pure replication (DDP)
    ZERO_1 = 1
    ZERO_2 = 2
    ZERO_3 = 3


class FSDPShardingStrategy(BaseEnum):
    """Reference FSDP strategy names (``utils/constants.py:36``) → mesh layouts."""

    FULL_SHARD = "FULL_SHARD"          # ZeRO-3 on the fsdp axis
    SHARD_GRAD_OP = "SHARD_GRAD_OP"    # ZeRO-2
    NO_SHARD = "NO_SHARD"              # DDP
    HYBRID_SHARD = "HYBRID_SHARD"      # shard within ICI slice, replicate across DCN
    HYBRID_SHARD_ZERO2 = "HYBRID_SHARD_ZERO2"


@dataclass
class AutocastKwargs(KwargsHandler):
    """Reference ``dataclasses.py:107``. Controls the compute-dtype cast inside the step."""

    enabled: bool = True
    cache_enabled: bool = True  # graftlint: disable=dead-knob(torch-autocast parity; cast caching is XLA's job)


@dataclass
class GradScalerKwargs(KwargsHandler):
    """Dynamic loss-scaling config (reference ``dataclasses.py:226``).

    On TPU fp16 is rare — bf16 needs no loss scaling — so the scaling schedule fields
    are recorded for API parity only; a functional dynamic-scale step is future work.
    """

    init_scale: float = 65536.0  # graftlint: disable=dead-knob(torch-AMP parity; bf16 TPU training needs no loss scaling)
    growth_factor: float = 2.0  # graftlint: disable=dead-knob(torch-AMP parity; bf16 TPU training needs no loss scaling)
    backoff_factor: float = 0.5  # graftlint: disable=dead-knob(torch-AMP parity; bf16 TPU training needs no loss scaling)
    growth_interval: int = 2000  # graftlint: disable=dead-knob(torch-AMP parity; bf16 TPU training needs no loss scaling)
    enabled: bool = True


@dataclass
class DistributedInitKwargs(KwargsHandler):
    """``jax.distributed.initialize`` arguments (reference ``InitProcessGroupKwargs`` :257)."""

    coordinator_address: Optional[str] = None
    num_processes: Optional[int] = None
    process_id: Optional[int] = None
    local_device_ids: Optional[list[int]] = None
    timeout: timedelta = field(default_factory=lambda: timedelta(seconds=1800))


@dataclass
class DistributedDataParallelKwargs(KwargsHandler):
    """Reference ``utils/dataclasses.py:128`` (torch-DDP construction knobs).

    On TPU, gradient reduction is GSPMD's psum over the mesh — there are no buckets, no
    graph re-tracing, no unused-parameter scans. The one knob with a real equivalent is
    ``comm_hook``: bf16/fp16 gradient compression == ``MixedPrecisionPolicy.reduce_dtype``
    (the Accelerator applies it when this handler is passed). The remaining fields are
    accepted at their defaults only — setting them raises, because an accepted-but-ignored
    flag is worse than an error.
    """

    comm_hook: str = "none"  # none | bf16 | fp16
    bucket_cap_mb: int = 25
    find_unused_parameters: bool = False
    gradient_as_bucket_view: bool = False
    static_graph: bool = False

    def __post_init__(self):
        if self.comm_hook not in ("none", "bf16", "fp16"):
            raise ValueError(
                f"comm_hook={self.comm_hook!r}: TPU supports 'none', 'bf16', 'fp16' "
                "(gradient-compression dtype for the cross-device reduce)"
            )
        # Explicit reads (not a getattr loop) so the dead-knob lint can prove each
        # field is consumed: setting any of these raises, never silently no-ops.
        torch_only = {
            "find_unused_parameters": self.find_unused_parameters,
            "gradient_as_bucket_view": self.gradient_as_bucket_view,
            "static_graph": self.static_graph,
        }
        for name, value in torch_only.items():
            if value:
                raise ValueError(
                    f"DistributedDataParallelKwargs.{name} is torch-DDP-specific and has "
                    "no GSPMD equivalent on TPU (reductions are compiled into the step)"
                )
        if self.bucket_cap_mb != 25:
            raise ValueError(
                "bucket_cap_mb has no GSPMD equivalent: XLA fuses and schedules gradient "
                "reductions itself"
            )

    @property
    def reduce_dtype(self):
        return {"none": None, "bf16": jnp.bfloat16, "fp16": jnp.float16}[self.comm_hook]


@dataclass
class FP8RecipeKwargs(KwargsHandler):
    """fp8 recipe knobs (reference ``dataclasses.py:295-434`` TE/ao/msamp recipe kwargs).

    Consumed by ``ops/fp8.py`` instead of a CUDA library: ``fp8_format`` picks the dtype pair
    (HYBRID = e4m3 fwd / e5m2 bwd), ``margin`` backs the scale off by 2^margin,
    ``amax_history_len``/``amax_compute_algo`` parameterize delayed scaling
    (``DelayedScalingState``). ``use_delayed_scaling=False`` = stateless current scaling.

    ``opt_level`` is the MS-AMP optimization-level analog (reference
    ``dataclasses.py:1235-1242``, ``accelerator.py:2164``): ``"O1"`` keeps optimizer
    state fp32; ``"O2"`` stores the AdamW moments as scaled-fp8 (e4m3 with per-tensor
    fp32 scales — ``ops/fused_optim.ScaledAdamState``), 4x less moment traffic in the
    bandwidth-bound apply and ~4x less standing optimizer HBM. O2 takes effect when the
    optimizer is a ``FusedAdamW`` whose moment dtypes were left unset;
    ``Accelerator.prepare`` upgrades it in place (a warning is logged for other
    optimizers, whose state stays fp32).
    """

    fp8_format: Optional[str] = None       # HYBRID | E4M3; None → env > HYBRID
    margin: Optional[int] = None           # None → env > 0
    interval: int = 1  # graftlint: disable=dead-knob(TransformerEngine parity; delayed-scale amax updates every step here)
    amax_history_len: Optional[int] = None  # None → env > 16
    amax_compute_algo: str = "max"  # max | most_recent
    use_delayed_scaling: Optional[bool] = None  # None → env > False
    opt_level: Optional[str] = None        # O1 | O2; None → env > O1

    def __post_init__(self):
        # Explicit arg > ACCELERATE_FP8_* env > built-in (None is the unset sentinel).
        if self.fp8_format is None:
            self.fp8_format = os.environ.get("ACCELERATE_FP8_FORMAT", "HYBRID")
        if self.margin is None:
            self.margin = int(os.environ.get("ACCELERATE_FP8_MARGIN", 0))
        if self.amax_history_len is None:
            self.amax_history_len = int(os.environ.get("ACCELERATE_FP8_AMAX_HISTORY_LEN", 16))
        if self.use_delayed_scaling is None:
            self.use_delayed_scaling = parse_flag_from_env("ACCELERATE_FP8_DELAYED_SCALING")
        if self.opt_level is None:
            self.opt_level = os.environ.get("ACCELERATE_FP8_OPT_LEVEL", "O1")
        self.fp8_format = self.fp8_format.upper()
        self.opt_level = self.opt_level.upper()
        if self.fp8_format not in ("HYBRID", "E4M3"):
            raise ValueError("`fp8_format` must be HYBRID or E4M3.")
        if self.amax_compute_algo not in ("max", "most_recent"):
            raise ValueError("`amax_compute_algo` must be max or most_recent.")
        if self.opt_level not in ("O1", "O2"):
            raise ValueError("`opt_level` must be O1 or O2.")


@dataclass
class GradientAccumulationPlugin(KwargsHandler):
    """Reference ``dataclasses.py:920``."""

    num_steps: int = 1
    adjust_scheduler: bool = True
    sync_with_dataloader: bool = True
    sync_each_batch: bool = False


@dataclass
class ProfileKwargs(KwargsHandler):
    """Profiler configuration → ``jax.profiler`` (reference ``dataclasses.py:436``).

    ``schedule_option`` is the torch ``torch.profiler.schedule`` dict
    (``{"wait", "warmup", "active", "repeat", "skip_first"}``): when set,
    ``Accelerator.profile`` yields a ``telemetry.ScheduledProfiler`` — call its
    ``step()`` once per train step and ``jax.profiler`` traces cover exactly the
    active windows, one ``cycle<N>`` trace directory per repeat. Without a schedule
    the whole block is traced (the pre-schedule behavior). ``profile_memory``
    additionally writes a pprof device-memory profile at each window end.
    """

    activities: Optional[list[str]] = None  # graftlint: disable=dead-knob(torch-profiler parity; a jax trace always captures host+device+HLO — there is no activity selection to apply)
    schedule_option: Optional[dict[str, int]] = None
    on_trace_ready: Optional[Callable] = None
    record_shapes: bool = False  # graftlint: disable=dead-knob(torch-profiler parity; the xplane trace records shapes unconditionally)
    profile_memory: bool = False
    with_stack: bool = False  # graftlint: disable=dead-knob(torch-profiler parity; jax traces have no python-stack mode to toggle)
    with_flops: bool = False  # graftlint: disable=dead-knob(torch-profiler parity; the xplane trace carries HLO cost analysis unconditionally)
    with_modules: bool = False  # graftlint: disable=dead-knob(torch-profiler parity; module attribution is a torch.nn concept with no pytree analog)
    output_trace_dir: Optional[str] = None

    def __post_init__(self):
        if self.schedule_option is not None:
            # Fail at construction, not at the first profiled step: an invalid
            # schedule silently accepted is the dead-knob bug in a new costume.
            from ..telemetry.profiler import validate_schedule_option

            validate_schedule_option(self.schedule_option)


@dataclass
class TelemetryConfig(KwargsHandler):
    """Step-level telemetry pipeline config (``accelerate_tpu.telemetry``).

    **Off by default and free when off**: the disabled path adds two attribute reads
    per train step — no host syncs, no listeners, no files (asserted by
    ``tests/test_telemetry.py``). Enable explicitly or via ``ACCELERATE_TELEMETRY=1``
    (explicit arg > env > built-in, the §5 priority order; ``None`` is the unset
    sentinel). ``jsonl_dir`` (env ``ACCELERATE_TELEMETRY_DIR``) makes the pipeline
    self-sufficient: records land in ``<jsonl_dir>/telemetry.jsonl`` even with no
    tracker configured.

    ``steady_*`` parameterize the rev-2 steady-state rule (``telemetry.steady``): warm
    until ``steady_k`` consecutive steps agree within ``steady_rtol``, cap
    ``steady_cap`` steps. ``flops_per_step``/``tokens_per_step``/``examples_per_step``
    are static per-step costs for the derived rates; tokens/examples fall back to
    host-visible batch shapes, MFU stays absent until a FLOP cost is declared.
    """

    enabled: Optional[bool] = None          # None → env ACCELERATE_TELEMETRY > False
    jsonl_dir: Optional[str] = None         # None → env ACCELERATE_TELEMETRY_DIR
    # Size-based JSONL rotation: when > 0 and the active telemetry.jsonl
    # crosses this many bytes, it is renamed telemetry.<n>.jsonl (n ascending,
    # zero-padded — lexical sort IS chronological) and a fresh file opened, so
    # a long chaos run never produces one unbounded file. 0 = never rotate
    # (the historical behavior). Readers (trace-report, metrics-dump) accept
    # the whole rotated set.
    rotate_bytes: int = 0
    steady_k: int = 2
    steady_rtol: float = 0.10
    steady_cap: int = 50                    # 0 = never cap the warmup
    compile_events: bool = True             # jax.monitoring compile counters
    memory_stats: bool = True               # device allocator live/peak bytes
    device_index: int = 0                   # which local device to sample
    max_records: int = 4096                 # in-memory history cap (JSONL is unbounded)
    merge_into_log: bool = True             # Accelerator.log gains telemetry/ columns
    flops_per_step: Optional[float] = None
    tokens_per_step: Optional[float] = None
    examples_per_step: Optional[float] = None
    # Flight-recorder tier (telemetry/recorder.py): an always-on bounded
    # in-memory ring of recent records + periodic metrics snapshots, the
    # buffer tail-sampled tracing promotes from, and — when ``capsule_dir``
    # is set (env ACCELERATE_CAPSULE_DIR) — automatic incident capsules with
    # per-trigger cooldown/dedupe. Free when the pipeline is disabled.
    recorder: bool = False
    recorder_ring: int = 2048               # flight-ring capacity (records)
    recorder_snapshot_every: int = 256      # metrics snapshot period (records; 0 = never)
    capsule_dir: Optional[str] = None       # None → env ACCELERATE_CAPSULE_DIR
    capsule_cooldown_s: float = 30.0        # per-trigger capsule dedupe window
    # Trace head sampling (telemetry/tracing.py): every-Kth (1 = trace all,
    # the historical behavior) or seeded probability; unsampled requests
    # buffer spans in the flight ring and tail-promote when they end badly.
    trace_sample_every: int = 1
    trace_sample_prob: Optional[float] = None
    trace_sample_seed: int = 0

    def __post_init__(self):
        if self.enabled is None:
            self.enabled = parse_flag_from_env("ACCELERATE_TELEMETRY")
        if self.jsonl_dir is None:
            self.jsonl_dir = os.environ.get("ACCELERATE_TELEMETRY_DIR") or None
        if self.capsule_dir is None:
            self.capsule_dir = os.environ.get("ACCELERATE_CAPSULE_DIR") or None
        if self.steady_k < 2:
            raise ValueError(f"steady_k={self.steady_k}: agreement needs >= 2 windows")
        if self.steady_rtol <= 0:
            raise ValueError(f"steady_rtol={self.steady_rtol} must be > 0")
        if self.steady_cap < 0:
            raise ValueError(f"steady_cap={self.steady_cap} must be >= 0 (0 = no cap)")
        if self.rotate_bytes < 0:
            raise ValueError(
                f"rotate_bytes={self.rotate_bytes} must be >= 0 (0 = never rotate)"
            )
        if self.recorder_ring < 1:
            raise ValueError(f"recorder_ring={self.recorder_ring} must be >= 1")
        if self.recorder_snapshot_every < 0:
            raise ValueError(
                f"recorder_snapshot_every={self.recorder_snapshot_every} "
                "must be >= 0 (0 = never snapshot)"
            )
        if self.capsule_cooldown_s < 0:
            raise ValueError(
                f"capsule_cooldown_s={self.capsule_cooldown_s} must be >= 0"
            )
        if self.trace_sample_every < 1:
            raise ValueError(
                f"trace_sample_every={self.trace_sample_every} must be >= 1 "
                "(1 = trace every request)"
            )
        if self.trace_sample_prob is not None and not (
                0.0 <= self.trace_sample_prob <= 1.0):
            raise ValueError(
                f"trace_sample_prob={self.trace_sample_prob} must be in [0, 1]"
            )


#: Env values that toggle ACCELERATE_COMPILE_CACHE on/off; anything else is a path.
_CACHE_ENV_TRUE = frozenset({"1", "true", "yes", "on"})
_CACHE_ENV_FALSE = frozenset({"", "0", "false", "no", "off"})


@dataclass
class CompileCacheConfig(KwargsHandler):
    """AOT compile-cache config (``accelerate_tpu.compile_cache``).

    **Off by default and free when off**: a disabled config makes
    ``AotCache.wrap`` the identity, so train/eval/serving steps dispatch through
    plain ``jax.jit`` exactly as before. Enable explicitly or via
    ``ACCELERATE_COMPILE_CACHE=1`` (explicit arg > env > built-in, the §5 priority
    order; a path-valued env both enables the cache and names its directory).

    When enabled, every executable the ``Accelerator`` builds (train step, eval
    step, serving prefill/decode) is content-addressed by a fingerprint of its
    lowered program + jax/jaxlib versions + backend topology + compiler flags and
    serialized to ``cache_dir`` — a later process start deserializes instead of
    re-paying XLA compile. Any stale/poisoned/mismatched entry falls back to live
    compile (never fails a step).

    ``serving_buckets`` / ``bucket_min`` / ``bucket_growth`` parameterize
    shape-bucketed serving: ``ContinuousBatcher`` prefill pads prompts up to a
    geometric bucket ladder (``bucket_min``, ``bucket_min*growth``, ... capped at
    the engine ``max_len``) so prefill compiles once per bucket instead of once
    per prompt length; explicit ``serving_buckets`` override the ladder.
    """

    enabled: Optional[bool] = None      # None → env ACCELERATE_COMPILE_CACHE > False
    cache_dir: Optional[str] = None     # None → env ACCELERATE_COMPILE_CACHE_DIR > default
    serving_buckets: Optional[tuple] = None  # explicit prefill bucket ladder (ascending)
    bucket_min: int = 64                # geometric ladder start
    bucket_growth: float = 2.0          # geometric ladder ratio
    bucket_serving: bool = True         # batcher uses the ladder when cache config attached

    def __post_init__(self):
        raw = os.environ.get("ACCELERATE_COMPILE_CACHE")
        raw_is_path = raw is not None and raw.strip().lower() not in (
            _CACHE_ENV_TRUE | _CACHE_ENV_FALSE
        )
        if self.enabled is None:
            if raw is None:
                self.enabled = False
            else:
                self.enabled = raw_is_path or raw.strip().lower() in _CACHE_ENV_TRUE
        if self.cache_dir is None:
            self.cache_dir = (
                os.environ.get("ACCELERATE_COMPILE_CACHE_DIR")
                or (raw if raw_is_path else None)
                or os.path.join(
                    os.path.expanduser("~"), ".cache", "accelerate_tpu", "aot_cache"
                )
            )
        if self.bucket_min < 1:
            raise ValueError(f"bucket_min={self.bucket_min} must be >= 1")
        if self.bucket_growth <= 1.0:
            raise ValueError(
                f"bucket_growth={self.bucket_growth} must be > 1 (the ladder must grow)"
            )
        if self.serving_buckets is not None:
            buckets = tuple(int(b) for b in self.serving_buckets)
            if not buckets or any(b < 1 for b in buckets) or list(buckets) != sorted(set(buckets)):
                raise ValueError(
                    f"serving_buckets={self.serving_buckets!r} must be a strictly "
                    "ascending sequence of positive ints"
                )
            self.serving_buckets = buckets

    def ladder(self, max_len: int) -> tuple:
        """The prefill bucket ladder for an engine of cache length ``max_len``.

        Rungs stay strictly BELOW ``max_len``: a bucket is also the decode start
        position, so a ``max_len``-wide rung leaves no room for even one
        generated token and could never be selected (``bucket + max_new_tokens
        <= max_len``). Prompts beyond the top rung use the chunked-prefill
        fallback. May be EMPTY (``bucket_min >= max_len``) — the engine then
        treats bucketing as off rather than carrying an unreachable rung.
        Explicit ``serving_buckets`` are the user's to cap (rungs > max_len are
        dropped; a rung == max_len is kept as stated even though only
        ``max_new_tokens == 0`` requests could use it — none exist)."""
        if self.serving_buckets is not None:
            return tuple(b for b in self.serving_buckets if b <= max_len)
        buckets = []
        b = self.bucket_min
        while b < max_len:
            buckets.append(b)
            # int truncation under growth < 2 could repeat a rung; always advance
            # so the ladder keeps the strictly-ascending invariant the explicit
            # serving_buckets path enforces.
            b = max(int(b * self.bucket_growth), b + 1)
        return tuple(buckets)


@dataclass
class FaultConfig(KwargsHandler):
    """Deterministic fault-injection config (``accelerate_tpu.resilience``).

    **Off by default and free when off**: with the config disabled nothing is
    constructed and every instrumented site pays one ``is None`` attribute
    read (the Telemetry contract). Enable explicitly or via
    ``ACCELERATE_FAULTS`` (explicit arg > env > built-in, the §5 priority
    order): any non-boolean env value is parsed as the fault clause string
    (``resilience.faults.parse_fault_spec`` grammar, e.g.
    ``"seed=7; serving.decode:error:0.1,max=3"``) and both enables injection
    and defines the plan.

    ``spec`` is the clause string; ``seed`` seeds the plan's per-spec RNG
    streams (a ``seed=N`` clause inside ``spec`` wins). Build the resolved
    plan with :meth:`build_plan` — the ``Accelerator`` does this once and
    exposes it as ``accelerator.fault_plan``.
    """

    enabled: Optional[bool] = None   # None → env ACCELERATE_FAULTS > False
    spec: Optional[str] = None       # None → env clause string (when non-boolean)
    seed: int = 0

    def __post_init__(self):
        raw = os.environ.get("ACCELERATE_FAULTS")
        raw_norm = raw.strip().lower() if raw is not None else None
        raw_is_spec = raw_norm is not None and raw_norm not in (
            _CACHE_ENV_TRUE | _CACHE_ENV_FALSE
        )
        if self.enabled is None:
            if raw_norm is None:
                self.enabled = False
            else:
                self.enabled = raw_is_spec or raw_norm in _CACHE_ENV_TRUE
        if self.spec is None and raw_is_spec:
            self.spec = raw
        if self.enabled and not self.spec:
            raise ValueError(
                "fault injection enabled with no fault clauses: pass spec= "
                "(or set ACCELERATE_FAULTS to a clause string like "
                "'serving.decode:error:0.1') — an empty plan would silently "
                "inject nothing"
            )
        if self.spec:
            # Validate the grammar at construction, not at the first draw.
            from ..resilience.faults import parse_fault_spec

            parse_fault_spec(self.spec)

    def build_plan(self):
        """The resolved ``FaultPlan`` (None when disabled)."""
        if not self.enabled:
            return None
        from ..resilience.faults import FaultPlan

        return FaultPlan.from_spec(self.spec, seed=self.seed)


#: Env values that toggle ACCELERATE_GATEWAY on/off; anything else must be a policy name.
_GATEWAY_POLICIES = frozenset({"fifo", "priority", "edf", "wfq"})


@dataclass
class GatewayConfig(KwargsHandler):
    """SLO-aware serving-gateway config (``accelerate_tpu.serving_gateway``).

    **Off by default and invisible when off**: the gateway is a wrapper *above*
    ``ContinuousBatcher`` — with no gateway constructed, the engine's behavior and
    compile counts are exactly the pre-gateway ones (asserted by
    ``tests/test_serving_gateway.py`` via ``CompileMonitor``). Enable explicitly or
    via ``ACCELERATE_GATEWAY=1`` (explicit arg > env > built-in, the §5 priority
    order); a policy-name-valued env (``ACCELERATE_GATEWAY=edf``) both enables the
    gateway and selects the policy.

    ``policy`` picks the queue discipline (``serving_gateway.policies``):
    ``fifo`` (seed-equivalent default), ``priority`` (strict priority with aging —
    a request gains one effective priority level per ``aging_s`` seconds waited, so
    low-priority work is starvation-free), ``edf`` (earliest deadline first) or
    ``wfq`` (start-time weighted fair queueing across tenants,
    ``tenant_weights``). ``max_queue`` / ``max_queued_tokens`` bound admission
    (0 = unbounded); over the bound, ``overload`` picks between rejecting the new
    request (``"reject"``) and shedding the least-urgent queued one
    (``"shed"``, lowest-priority-first). ``deadline_s`` applies a default relative
    deadline to every request; ``preempt`` lets a strictly more urgent queued
    request evict the least urgent running one (evictees retry up to
    ``max_retries`` times, from scratch). ``emit_per_request`` controls the
    per-terminal-request telemetry record (the aggregate SLO record is always
    emitted by ``ServingGateway.emit_slo_record``).
    """

    enabled: Optional[bool] = None      # None → env ACCELERATE_GATEWAY > False
    policy: Optional[str] = None        # None → env policy name > "fifo"
    max_queue: int = 0                  # queued-request cap; 0 = unbounded
    max_queued_tokens: int = 0          # cost-estimated queued-token budget; 0 = unbounded
    overload: str = "reject"            # "reject" the newcomer | "shed" least-urgent queued
    aging_s: float = 10.0               # priority policy: +1 effective level per aging_s waited
    default_priority: int = 0
    tenant_weights: Optional[dict] = None  # wfq: tenant → weight (missing tenants weigh 1.0)
    deadline_s: Optional[float] = None  # default relative deadline applied at submit
    preempt: bool = False               # evict least-urgent running for more urgent queued
    max_retries: int = 0                # default retry budget for preemption-evicted requests
    emit_per_request: bool = True       # telemetry record per terminal request
    max_terminal: int = 4096            # terminal-request history cap (SLO window; 0 = unbounded)
    # Circuit breaker (docs/resilience.md): after ``breaker_threshold`` engine
    # step-failures inside ``breaker_window_s``, the breaker OPENS — new
    # submissions are shed-and-rejected with the machine-readable reason
    # ``circuit_open`` until ``breaker_cooldown_s`` passes, then ONE probe
    # request is admitted (half-open); its success closes the breaker, its
    # failure re-opens. 0 disables the breaker entirely.
    breaker_threshold: int = 0          # step failures in the window that trip it; 0 = off
    breaker_window_s: float = 60.0      # sliding failure-count window
    breaker_cooldown_s: float = 30.0    # open → half-open probe delay
    # Graceful degradation rungs: each breaker OPEN (re-opens included)
    # escalates one rung (1: disable speculative decoding on the engine;
    # 2: halve the admission bounds); a CLOSE — a proven-healthy probe —
    # restores the full configuration. Repeated pressure sheds optional
    # throughput machinery before it sheds requests.
    degrade: bool = False
    # Fleet routing (``serving_gateway.fleet.FleetRouter`` — ignored by the
    # single-engine gateway): ``drain_deadline_s`` bounds how long drain()
    # waits for in-flight requests before migrating them (None = wait
    # forever); ``replica_restarts`` / ``replica_restart_backoff`` are the
    # per-replica (per-gang) restart budget and base backoff handed to the
    # default ``elastic.FleetSupervisor``.
    drain_deadline_s: Optional[float] = 30.0
    replica_restarts: int = 2
    replica_restart_backoff: float = 0.0
    # Disaggregated prefill/decode serving (``serving_gateway.disagg``): a
    # comma-separated role per replica (``"prefill,decode,decode"``; roles:
    # prefill / decode / mixed). When set, ``Accelerator.build_serving_gateway``
    # with a LIST of engines builds a ``DisaggRouter`` — prefill replicas
    # chunk-prefill and export KV page handoffs, decode replicas adopt them and
    # run decode-only lanes (docs/disaggregated_serving.md). None = homogeneous
    # FleetRouter.
    replica_roles: Optional[str] = None
    # Live metrics plane (``telemetry.metrics.MetricsPlane``): when True AND a
    # telemetry object is attached and enabled, the gateway builds a plane as
    # a telemetry sink (zero new emit sites) sharing the gateway's clock, and
    # ``stats()``/bench rows expose its snapshot. Off by default; with
    # telemetry disabled the knob is inert (the plane's disabled contract is
    # the two-attr-read one, like Tracer's).
    metrics: bool = False
    # Sliding-window horizon (seconds, on the gateway clock) for the plane's
    # histograms / SLO event window / counter-increase reads.
    metrics_window_s: float = 300.0
    # Incident-capsule state hook (``telemetry.recorder.FlightRecorder``):
    # when True AND the attached telemetry carries a flight recorder, the
    # gateway registers its ``stats()`` snapshot (queue/counters, engine lane
    # table + BlockManager occupancy, breaker state, fault-plan fire history)
    # as a capsule state provider and binds the recorder to its metrics plane.
    # Inert without a recorder.
    capsule_state: bool = True
    # Streaming-granularity knob (docs/multistep_decode.md): the multi-step
    # decode depth the gateway EXPECTS of its engine. The engine owns the knob
    # (``ContinuousBatcher(decode_steps=N)`` — it shapes compiled programs);
    # the gateway only validates the pairing at construction, so a config
    # stamped ``decode_steps=4`` can never silently run against a classic
    # one-token engine (or vice versa). 1 = inherit whatever the engine runs.
    # Trade-off this stamps: tokens stream in bursts of up to N per dispatch
    # (TPOT jitter), and a running deadline can overshoot by up to N-1 tokens
    # mid-dispatch — the engine clamps emissions to each request's budget on
    # drain, and the gateway checks deadlines at super-step boundaries.
    decode_steps: int = 1

    def __post_init__(self):
        raw = os.environ.get("ACCELERATE_GATEWAY")
        raw_norm = raw.strip().lower() if raw is not None else None
        raw_is_policy = raw_norm in _GATEWAY_POLICIES
        if raw_norm is not None and not raw_is_policy and raw_norm not in (
            _CACHE_ENV_TRUE | _CACHE_ENV_FALSE
        ):
            # A typo'd policy name must not silently run with the gateway OFF —
            # that disables admission control/deadlines in production with no error.
            raise ValueError(
                f"ACCELERATE_GATEWAY={raw!r}: expected a boolean "
                f"({'/'.join(sorted(_CACHE_ENV_TRUE))} or "
                f"{'/'.join(sorted(v for v in _CACHE_ENV_FALSE if v))}) "
                f"or a policy name ({'/'.join(sorted(_GATEWAY_POLICIES))})"
            )
        if self.enabled is None:
            if raw_norm is None:
                self.enabled = False
            else:
                self.enabled = raw_is_policy or raw_norm in _CACHE_ENV_TRUE
        if self.policy is None:
            self.policy = raw_norm if raw_is_policy else "fifo"
        if self.policy not in _GATEWAY_POLICIES:
            raise ValueError(
                f"policy={self.policy!r} must be one of {sorted(_GATEWAY_POLICIES)}"
            )
        if self.max_queue < 0:
            raise ValueError(f"max_queue={self.max_queue} must be >= 0 (0 = unbounded)")
        if self.max_queued_tokens < 0:
            raise ValueError(
                f"max_queued_tokens={self.max_queued_tokens} must be >= 0 (0 = unbounded)"
            )
        if self.overload not in ("reject", "shed"):
            raise ValueError(f"overload={self.overload!r} must be 'reject' or 'shed'")
        if self.aging_s <= 0:
            raise ValueError(
                f"aging_s={self.aging_s} must be > 0 (aging is what makes the "
                "priority policy starvation-free; disable aging by raising it, not zeroing it)"
            )
        if self.deadline_s is not None and self.deadline_s <= 0:
            raise ValueError(f"deadline_s={self.deadline_s} must be > 0 when set")
        if self.max_retries < 0:
            raise ValueError(f"max_retries={self.max_retries} must be >= 0")
        if self.max_terminal < 0:
            raise ValueError(
                f"max_terminal={self.max_terminal} must be >= 0 (0 = unbounded)"
            )
        if self.breaker_threshold < 0:
            raise ValueError(
                f"breaker_threshold={self.breaker_threshold} must be >= 0 (0 = off)"
            )
        if self.breaker_window_s <= 0:
            raise ValueError(
                f"breaker_window_s={self.breaker_window_s} must be > 0"
            )
        if self.breaker_cooldown_s <= 0:
            raise ValueError(
                f"breaker_cooldown_s={self.breaker_cooldown_s} must be > 0"
            )
        if self.drain_deadline_s is not None and self.drain_deadline_s <= 0:
            raise ValueError(
                f"drain_deadline_s={self.drain_deadline_s} must be > 0 "
                "(None = wait for in-flight requests forever)"
            )
        if self.metrics_window_s <= 0:
            raise ValueError(
                f"metrics_window_s={self.metrics_window_s} must be > 0"
            )
        if self.decode_steps < 1:
            raise ValueError(
                f"decode_steps={self.decode_steps} must be >= 1 "
                "(1 = classic one-token decode)"
            )
        if self.replica_restarts < 0:
            raise ValueError(
                f"replica_restarts={self.replica_restarts} must be >= 0"
            )
        if self.replica_restart_backoff < 0:
            raise ValueError(
                f"replica_restart_backoff={self.replica_restart_backoff} "
                "must be >= 0"
            )
        if self.replica_roles is not None:
            roles = [r.strip() for r in self.replica_roles.split(",")]
            bad = [r for r in roles if r not in ("prefill", "decode", "mixed")]
            if bad or not roles:
                raise ValueError(
                    f"replica_roles={self.replica_roles!r}: expected a comma-"
                    "separated list of prefill/decode/mixed, one per replica"
                )
        if self.tenant_weights is not None:
            for tenant, weight in self.tenant_weights.items():
                if weight <= 0:
                    raise ValueError(
                        f"tenant_weights[{tenant!r}]={weight} must be > 0"
                    )


@dataclass
class DataLoaderConfiguration(KwargsHandler):
    """Reference ``dataclasses.py:762``. None-sentinel fields resolve launcher env
    (``ACCELERATE_DISPATCH_BATCHES``/``EVEN_BATCHES``/``USE_SEEDABLE_SAMPLER``) > built-in."""

    split_batches: bool = False
    dispatch_batches: Optional[bool] = None
    even_batches: Optional[bool] = None         # built-in True
    use_seedable_sampler: Optional[bool] = None  # built-in True
    data_seed: Optional[int] = None
    non_blocking: bool = False      # async host→device transfer
    use_stateful_dataloader: bool = False
    prefetch_size: int = 2  # graftlint: disable=dead-knob(reference-launcher config compat; prefetch_depth below is the live knob)
    # Device-prefetch lookahead of the prepared shard loader: up to ``prefetch_depth``
    # batches are placed on device ahead of the one being consumed (depth 1 = the
    # historical one-batch lookahead the end_of_dataloader contract needs; deeper
    # overlaps more H2D transfer with compute at the cost of extra device memory).
    prefetch_depth: int = 1

    def __post_init__(self):
        if self.prefetch_depth < 1:
            raise ValueError(
                f"prefetch_depth={self.prefetch_depth} must be >= 1 (the one-batch "
                "lookahead is required to detect end_of_dataloader before the final "
                "batch is yielded)"
            )
        if self.dispatch_batches is None and "ACCELERATE_DISPATCH_BATCHES" in os.environ:
            self.dispatch_batches = parse_flag_from_env("ACCELERATE_DISPATCH_BATCHES")
        if self.even_batches is None:
            self.even_batches = (
                parse_flag_from_env("ACCELERATE_EVEN_BATCHES")
                if "ACCELERATE_EVEN_BATCHES" in os.environ
                else True
            )
        if self.use_seedable_sampler is None:
            self.use_seedable_sampler = (
                parse_flag_from_env("ACCELERATE_USE_SEEDABLE_SAMPLER")
                if "ACCELERATE_USE_SEEDABLE_SAMPLER" in os.environ
                else True
            )


@dataclass
class ProjectConfiguration(KwargsHandler):
    """Checkpoint/output folder layout + rotation (reference ``dataclasses.py:857``)."""

    project_dir: Optional[str] = None
    logging_dir: Optional[str] = None
    automatic_checkpoint_naming: bool = False
    total_limit: Optional[int] = None
    iteration: int = 0
    save_on_each_node: bool = False

    def set_directories(self, project_dir: Optional[str] = None):
        self.project_dir = project_dir
        if self.logging_dir is None:
            self.logging_dir = project_dir

    def __post_init__(self):
        if self.project_dir is None and os.environ.get("ACCELERATE_PROJECT_DIR"):
            self.project_dir = os.environ["ACCELERATE_PROJECT_DIR"]
        if self.total_limit is None and os.environ.get("ACCELERATE_CHECKPOINT_TOTAL_LIMIT"):
            self.total_limit = int(os.environ["ACCELERATE_CHECKPOINT_TOTAL_LIMIT"])
        if self.logging_dir is None:
            self.logging_dir = self.project_dir


@dataclass
class MixedPrecisionPolicy(KwargsHandler):
    """The dtype quadruple governing a jitted step.

    Replaces torch autocast + GradScaler (reference ``accelerator.py:528-576``): params are kept
    in ``param_dtype`` (master weights), cast to ``compute_dtype`` for the forward/backward,
    outputs cast to ``output_dtype`` (the ``convert_outputs_to_fp32`` analog,
    reference ``operations.py:815``), and cross-device gradient reductions run in
    ``reduce_dtype`` (the DDP bf16-compression-hook analog, reference ``dataclasses.py:128``).
    """

    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    output_dtype: Any = jnp.float32
    reduce_dtype: Any = jnp.float32

    @classmethod
    def from_precision(cls, precision: str | PrecisionType) -> "MixedPrecisionPolicy":
        precision = PrecisionType(str(precision))
        if precision == PrecisionType.NO:
            return cls()
        if precision == PrecisionType.BF16:
            return cls(compute_dtype=jnp.bfloat16, reduce_dtype=jnp.bfloat16)
        if precision == PrecisionType.FP16:
            return cls(compute_dtype=jnp.float16, reduce_dtype=jnp.float16)
        if precision == PrecisionType.FP8:
            # fp8 matmul inputs; accumulation still bf16. Fine-grained control in ops/fp8.py.
            return cls(compute_dtype=jnp.bfloat16, reduce_dtype=jnp.bfloat16)
        raise ValueError(f"unknown precision {precision}")


@dataclass
class FullyShardedDataParallelPlugin(KwargsHandler):
    """ZeRO/FSDP sharding along the ``fsdp`` mesh axis (reference ``dataclasses.py:1449``).

    One plugin covers both the reference's DeepSpeed-ZeRO and torch-FSDP paths: on TPU both are
    GSPMD sharding of the (param, grad, opt-state) pytrees. ``min_weight_size`` is the analog of
    FSDP's size-based auto-wrap policy: parameters smaller than it stay replicated.
    """

    sharding_strategy: FSDPShardingStrategy | str = FSDPShardingStrategy.FULL_SHARD
    zero_stage: Optional[int] = None          # overrides sharding_strategy if set
    # None defaults resolve env > built-in in __post_init__ (None-sentinel pattern: an
    # EXPLICIT value, even one equal to the built-in default, always beats launcher env).
    min_weight_size: Optional[int] = None     # built-in 1024; smaller params stay replicated
    shard_axis: str = "fsdp"  # graftlint: disable=dead-knob(mesh axis name is fixed by parallel.mesh topology; knob reserved for custom meshes)
    # Checkpoint layout on save_state: SHARDED keeps orbax per-shard tensorstore files;
    # FULL gathers to a single consolidated state on rank 0 (reference FSDP StateDictType,
    # utils/constants.py:39). Consumed by checkpointing.save_accelerator_state.
    state_dict_type: Optional[str] = None     # built-in SHARDED_STATE_DICT
    # ZeRO-Offload: optimizer state + grad-accum buffers live in pinned host RAM and are
    # streamed through HBM inside the apply step (consumed by create_train_state /
    # build_train_step). Reference: DeepSpeed offload fields, dataclasses.py:1078-1093.
    cpu_offload: bool = False
    use_orig_params: bool = True  # graftlint: disable=dead-knob(torch-FSDP parity; functional pytrees make it always true)
    cpu_ram_efficient_loading: bool = True  # graftlint: disable=dead-knob(HF config compat; interop/big_modeling always stream host shards to devices)
    sync_module_states: bool = True  # graftlint: disable=dead-knob(torch-FSDP parity; GSPMD replication broadcasts state implicitly)
    # NOTE deliberately absent vs the reference plugin (accepted-but-ignored flags are worse
    # than errors): ``backward_prefetch`` (XLA's scheduler owns prefetch; nothing to toggle)
    # and ``activation_checkpointing`` (a model-definition concern under jax — use
    # ``jax.checkpoint``/``LlamaConfig.remat``/``remat_policy``).

    def __post_init__(self):
        self.sharding_strategy = FSDPShardingStrategy(str(self.sharding_strategy))
        env_stage = os.environ.get("ACCELERATE_FSDP_ZERO_STAGE")
        if self.zero_stage is None and env_stage is not None:
            self.zero_stage = int(env_stage)
        # Launcher wire protocol for the remaining fsdp knobs (explicit arg > env > built-in,
        # §5 priority order — None is the "unset" sentinel).
        if not self.cpu_offload and parse_flag_from_env("ACCELERATE_FSDP_CPU_OFFLOAD"):
            self.cpu_offload = True
        if self.state_dict_type is None:
            self.state_dict_type = os.environ.get(
                "ACCELERATE_FSDP_STATE_DICT_TYPE", "SHARDED_STATE_DICT"
            )
        if self.min_weight_size is None:
            self.min_weight_size = int(os.environ.get("ACCELERATE_FSDP_MIN_WEIGHT_SIZE", 2**10))
        if self.zero_stage is None:
            self.zero_stage = {
                FSDPShardingStrategy.FULL_SHARD: 3,
                FSDPShardingStrategy.SHARD_GRAD_OP: 2,
                FSDPShardingStrategy.NO_SHARD: 0,
                FSDPShardingStrategy.HYBRID_SHARD: 3,
                FSDPShardingStrategy.HYBRID_SHARD_ZERO2: 2,
            }[self.sharding_strategy]

    @property
    def shards_params(self) -> bool:
        return self.zero_stage >= 3

    @property
    def shards_grads(self) -> bool:
        return self.zero_stage >= 2

    @property
    def shards_optimizer(self) -> bool:
        return self.zero_stage >= 1


@dataclass
class TensorParallelPlugin(KwargsHandler):
    """Megatron-style tensor parallelism along the ``tp`` axis
    (reference ``TorchTensorParallelPlugin`` ``dataclasses.py:1863``)."""

    tp_size: int = 1
    plan: Optional[str] = None  # graftlint: disable=dead-knob(TP plan selection rides models.partition_specs today; Accelerator routing is future work)


@dataclass
class PipelineParallelPlugin(KwargsHandler):
    """Pipeline parallelism along the ``pp`` axis (reference ``inference.py``; Megatron
    schedule intent ``dataclasses.py:2024``).

    Two schedules (``parallel/pp.py``):

    - ``"gpipe"`` — one differentiable ``lax.scan`` whose backward jax AD derives;
      activation residuals grow with ``num_microbatches``.
    - ``"1f1b"`` — hand-scheduled custom-VJP one-forward-one-backward: in-flight
      activations bounded by ``pp_size + 2`` per stage regardless of
      ``num_microbatches``, which is what lets M grow to amortize the (n-1)/(M+n-1)
      bubble. MoE models are supported on BOTH schedules: per-(stage, microbatch)
      load-balancing aux is carried through the 1f1b replay with the same /M
      normalization as GPipe (``llama.loss_fn_pp`` with_aux/aux_weight;
      ``tests/test_pipeline.py::test_llama_pp_moe_1f1b_matches_single``).
    """

    pp_size: int = 1
    num_microbatches: Optional[int] = None  # None → n_stages (min for a full pipe)
    schedule: str = "gpipe"
    # Interleaved virtual-pipeline chunks per device (Megatron virtual_pipeline analog,
    # reference dataclasses.py:2024): >1 requires schedule="1f1b"; device s hosts the
    # strided virtual stages {s, n+s, ...} and the bubble amortizes ~v x.
    virtual_stages: int = 1

    def __post_init__(self):
        if self.schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"schedule={self.schedule!r} is not supported: expected 'gpipe' or '1f1b' "
                "(parallel/pp.py)"
            )
        if self.virtual_stages < 1:
            raise ValueError(f"virtual_stages={self.virtual_stages} must be >= 1")
        if self.virtual_stages > 1 and self.schedule != "1f1b":
            raise ValueError(
                "virtual_stages > 1 (interleaved virtual pipeline) requires "
                "schedule='1f1b' (parallel/pp.py _simulate_interleaved)"
            )


@dataclass
class SequenceParallelPlugin(KwargsHandler):
    """Context/sequence parallelism along the ``sp`` axis.

    The reference has NO native implementation (SURVEY.md §5 long-context gap) — only a Megatron
    flag. Here it is first-class: ``mode='ring'`` rotates KV blocks around the ICI ring
    (ring attention via ppermute), ``mode='ulysses'`` all-to-alls heads↔sequence.
    """

    sp_size: int = 1
    mode: Optional[str] = None  # "ring" | "ulysses" | "allgather"; None → env > "ring"

    def __post_init__(self):
        if self.mode is None:
            self.mode = os.environ.get("ACCELERATE_SP_MODE", "ring")
        if self.mode not in ("ring", "ulysses", "allgather"):
            raise ValueError(f"sp mode must be ring|ulysses|allgather, got {self.mode!r}")


@dataclass
class ExpertParallelPlugin(KwargsHandler):
    """MoE expert parallelism along the ``ep`` axis (reference: DeepSpeed-MoE fields only)."""

    ep_size: int = 1
    num_experts: int = 1  # graftlint: disable=dead-knob(MoEConfig owns expert hyperparams; plugin records mesh topology intent)
    capacity_factor: float = 1.25  # graftlint: disable=dead-knob(MoEConfig owns expert hyperparams; plugin records mesh topology intent)


@dataclass
class MegatronLMPlugin(KwargsHandler):
    """3D-parallel trainer config (reference ``dataclasses.py:1899``): one object bundling
    the tp/pp/sp degrees + distributed optimizer + clipping of the integrated mesh trainer.

    Consumed by ``Accelerator.__init__``, which expands it into the individual plugins:
    ``tp_degree``→TensorParallelPlugin, ``pp_degree``/``num_micro_batches``→
    PipelineParallelPlugin, ``sp_degree``→SequenceParallelPlugin,
    ``use_distributed_optimizer``→ZeRO-1 (fsdp plugin, reference ``dataclasses.py:2015``),
    ``gradient_clipping``→the default max_grad_norm of built train steps.

    Divergence from Megatron: its sequence parallelism reuses the tp ranks for norm/dropout
    activations only; here ``sp_degree`` is a real context-parallel mesh axis (ring/Ulysses
    attention, ``parallel/sequence.py``) — strictly more capable.
    """

    tp_degree: int = 1
    pp_degree: int = 1
    sp_degree: int = 1
    num_micro_batches: Optional[int] = None
    # Pipeline schedule for pp_degree > 1 ("gpipe" | "1f1b") — the knob behind the
    # reference's virtual-pipeline/1F1B intent (``dataclasses.py:2024``); validated by
    # the expanded PipelineParallelPlugin.
    pp_schedule: str = "gpipe"
    # Interleaved virtual-pipeline chunks per device (reference virtual_pipeline,
    # ``dataclasses.py:2024``); >1 requires pp_schedule="1f1b".
    virtual_pipeline_stages: int = 1
    gradient_clipping: Optional[float] = 1.0
    use_distributed_optimizer: bool = True  # == ZeRO-1 on the data axis

    @property
    def sequence_parallelism(self) -> bool:
        return self.sp_degree > 1


@dataclass
class TorchDynamoPlugin(KwargsHandler):
    """API-parity stub (reference ``dataclasses.py:969``): under JAX, ``jax.jit`` is always on.

    ``backend`` and modes are accepted and recorded; ``use_regional_compilation`` maps to
    per-block ``jax.checkpoint``/scan-compilation of repeated layers.
    """

    backend: str = "inductor"  # graftlint: disable=dead-knob(torch.compile parity stub; jit is unconditional under JAX)
    mode: Optional[str] = None
    fullgraph: bool = True  # graftlint: disable=dead-knob(torch.compile parity stub; jit is unconditional under JAX)
    dynamic: Optional[bool] = None  # graftlint: disable=dead-knob(torch.compile parity stub; jit is unconditional under JAX)
    use_regional_compilation: bool = False  # graftlint: disable=dead-knob(torch.compile parity stub; scan-compilation is the model's remat/scan_layers choice)


class TensorInformation:
    """Shape/dtype record used by object-collectives (reference ``dataclasses.py``)."""

    def __init__(self, shape, dtype):
        self.shape = tuple(shape)
        self.dtype = dtype

    def __repr__(self):
        return f"TensorInformation(shape={self.shape}, dtype={self.dtype})"

    def __eq__(self, other):
        return (
            isinstance(other, TensorInformation)
            and self.shape == other.shape
            and self.dtype == other.dtype
        )


def add_model_config_to_megatron_parser(*args, **kwargs):  # pragma: no cover
    raise NotImplementedError("Megatron arg-parsing has no TPU analog; use MegatronLMPlugin.")
