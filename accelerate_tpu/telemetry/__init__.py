"""Step-level telemetry: trustworthy in-framework metrics (L9).

This package turns the hard-won bench_rev-2 measurement lessons (a post-compile
allocator transient was once averaged into every scoring number; a 128 MB host fetch
was once timed as device work) into a reusable pipeline instead of bench-script
folklore:

- :func:`fence` / :class:`StepTimer` — timing correct by construction (1-element
  fenced sync, monotonic clock, wall/dispatch/fence split).
- :class:`SteadyStateDetector` — the rev-2 warm-until-steady rule; transients are
  labeled (``warmup_steps_detected``), never averaged in.
- :class:`CompileMonitor` — XLA recompile count + cumulative compile seconds via
  ``jax.monitoring`` (graceful no-op where unsupported).
- :func:`device_memory_stats` — live/peak HBM bytes from the allocator ledger.
- :func:`derived_rates` / :data:`PEAK_TFLOPS` — MFU, tokens/sec, examples/sec from a
  static FLOP model (bench.py consumes the same table).
- :class:`ScheduledProfiler` — ``ProfileKwargs.schedule_option`` wait/warmup/active/
  repeat windows over ``jax.profiler.start_trace``/``stop_trace``.
- :class:`Telemetry` — the aggregate the ``Accelerator`` carries; per-step records
  flow to JSONL + all configured trackers. Off by default; zero host syncs when off.

Enable via ``Accelerator(telemetry_config=TelemetryConfig(enabled=True, ...))`` or
``ACCELERATE_TELEMETRY=1`` in the environment (docs/telemetry.md).
"""

from .alerts import AlertEngine, AlertRule, default_alert_rules
from .compile_monitor import CompileMonitor, compile_label
from .core import STEP_RECORD_SCHEMA, Telemetry
from .derived import PEAK_TFLOPS, derived_rates, peak_tflops
from .exporter import MetricsExporter, prometheus_text
from .memory import device_memory_stats
from .metrics import METRIC_REGISTRY, MetricsPlane, registered_metrics
from .profiler import ScheduledProfiler
from .provenance import config_fingerprint, git_commit, provenance_stamp
from .recorder import FlightRecorder, list_capsules, load_capsule
from .schemas import (
    ALERT_SCHEMA,
    AUDIT_PROGRAM_SCHEMA,
    CAPSULE_SCHEMA,
    FAULT_SCHEMA,
    FLEET_ROUTE_SCHEMA,
    METRICS_SNAPSHOT_SCHEMA,
    MPMD_BARRIER_SCHEMA,
    MPMD_STAGE_STEP_SCHEMA,
    MPMD_TRANSFER_SCHEMA,
    RECOVERY_SCHEMA,
    REPLICA_HEALTH_SCHEMA,
    SCHEMA_REGISTRY,
    SERVING_KV_SCHEMA,
    SERVING_SCHEMA,
    SERVING_SPEC_SCHEMA,
    SERVING_THROUGHPUT_SCHEMA,
    TRACE_SPAN_SCHEMA,
    registered_schemas,
    validate_record,
)
from .slo import (
    ELASTIC_RESTART_SCHEMA,
    GATEWAY_REQUEST_SCHEMA,
    GATEWAY_SLO_SCHEMA,
    latency_summary,
    percentile,
    slo_attainment,
    slo_summary,
)
from .steady import SteadyStateDetector, TELEMETRY_REV
from .timing import StepTimer, StepTiming, fence
from .tracing import PHASES, EnginePhase, Tracer, TraceHandle, phase, step_phase

__all__ = [
    "AlertEngine",
    "AlertRule",
    "default_alert_rules",
    "CompileMonitor",
    "compile_label",
    "MetricsExporter",
    "prometheus_text",
    "METRIC_REGISTRY",
    "MetricsPlane",
    "registered_metrics",
    "ALERT_SCHEMA",
    "METRICS_SNAPSHOT_SCHEMA",
    "MPMD_STAGE_STEP_SCHEMA",
    "STEP_RECORD_SCHEMA",
    "Telemetry",
    "PEAK_TFLOPS",
    "derived_rates",
    "peak_tflops",
    "device_memory_stats",
    "ScheduledProfiler",
    "config_fingerprint",
    "git_commit",
    "provenance_stamp",
    "FlightRecorder",
    "list_capsules",
    "load_capsule",
    "AUDIT_PROGRAM_SCHEMA",
    "CAPSULE_SCHEMA",
    "FAULT_SCHEMA",
    "FLEET_ROUTE_SCHEMA",
    "MPMD_BARRIER_SCHEMA",
    "MPMD_TRANSFER_SCHEMA",
    "RECOVERY_SCHEMA",
    "REPLICA_HEALTH_SCHEMA",
    "SCHEMA_REGISTRY",
    "SERVING_KV_SCHEMA",
    "SERVING_SCHEMA",
    "SERVING_SPEC_SCHEMA",
    "SERVING_THROUGHPUT_SCHEMA",
    "TRACE_SPAN_SCHEMA",
    "registered_schemas",
    "validate_record",
    "ELASTIC_RESTART_SCHEMA",
    "GATEWAY_REQUEST_SCHEMA",
    "GATEWAY_SLO_SCHEMA",
    "latency_summary",
    "percentile",
    "slo_attainment",
    "slo_summary",
    "SteadyStateDetector",
    "TELEMETRY_REV",
    "StepTimer",
    "StepTiming",
    "fence",
    "Tracer",
    "TraceHandle",
    "EnginePhase",
    "PHASES",
    "phase",
    "step_phase",
]
