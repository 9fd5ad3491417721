"""The telemetry schema registry: every record schema id, in ONE place.

Every record the pipeline emits carries a ``"schema"`` column naming its format
(``accelerate_tpu.telemetry.<stream>/v<rev>``). Before this module those ids were
string literals scattered across the emit sites — a typo'd stream name shipped
silently, and nothing enumerated what a consumer could expect to find in a JSONL
run directory. This registry is the single source of truth:

- Every schema id is a **constant here** (emit sites import it; graftlint's
  ``telemetry-schema-literal`` rule flags a bare string-literal schema anywhere
  else in the library sources).
- Each registration carries its **required key set** — the columns a consumer may
  rely on unconditionally — plus the emitter and a one-line description.
  :func:`validate_record` checks a record against its registration (tests pin
  every emit site through it).
- The schema table in ``docs/telemetry.md`` is **generated** from this registry
  (:func:`schema_table_markdown`) and drift-gated by ``scripts/check.sh``
  (``python -m accelerate_tpu.telemetry.schemas --check``; ``--write`` refreshes
  the docs block).

Stdlib-only by design: the registry must be importable from stripped CLI
contexts (trace-report, the docs gate) without jax.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping

__all__ = [
    "STEP_RECORD_SCHEMA",
    "SERVING_SCHEMA",
    "SERVING_THROUGHPUT_SCHEMA",
    "SERVING_KV_SCHEMA",
    "SERVING_SPEC_SCHEMA",
    "SERVING_HANDOFF_SCHEMA",
    "GATEWAY_REQUEST_SCHEMA",
    "GATEWAY_SLO_SCHEMA",
    "REPLICA_HEALTH_SCHEMA",
    "FLEET_ROUTE_SCHEMA",
    "FLEET_SCALE_SCHEMA",
    "ELASTIC_RESTART_SCHEMA",
    "MPMD_TRANSFER_SCHEMA",
    "MPMD_BARRIER_SCHEMA",
    "MPMD_STAGE_STEP_SCHEMA",
    "AUDIT_PROGRAM_SCHEMA",
    "TRACE_SPAN_SCHEMA",
    "FAULT_SCHEMA",
    "RECOVERY_SCHEMA",
    "ALERT_SCHEMA",
    "METRICS_SNAPSHOT_SCHEMA",
    "CAPSULE_SCHEMA",
    "RecordSchema",
    "SCHEMA_REGISTRY",
    "registered_schemas",
    "validate_record",
    "schema_table_markdown",
]

# --------------------------------------------------------------------- schema ids
#: Per-step training/eval record (``Telemetry._step_end``); bump on breaking
#: column changes.
STEP_RECORD_SCHEMA = "accelerate_tpu.telemetry.step/v1"

#: Per-decode-step serving engine counter record (``ContinuousBatcher``).
SERVING_SCHEMA = "accelerate_tpu.telemetry.serving/v1"

#: One aggregate per ``ContinuousBatcher.run(report_throughput=True)`` drain.
SERVING_THROUGHPUT_SCHEMA = "accelerate_tpu.telemetry.serving.throughput/v1"

#: Per-decode-step page-pool record (paged KV engines only).
SERVING_KV_SCHEMA = "accelerate_tpu.telemetry.serving.kv/v1"

#: Per-decode-step speculative-decoding record (``spec_k > 0`` engines only).
SERVING_SPEC_SCHEMA = "accelerate_tpu.telemetry.serving.spec/v1"

#: One record per cross-engine KV page handoff (disaggregated serving,
#: ``ops.collectives.kv_page_transfer``): which prefill replica exported, which
#: decode replica adopted, the request uid, page count, wire bytes and
#: synchronously-measured transfer latency — joined into trace-report timelines
#: as the ``handoff`` span.
SERVING_HANDOFF_SCHEMA = "accelerate_tpu.telemetry.serving.handoff/v1"

#: One record per gateway request reaching a terminal state (done/rejected/shed/
#: expired/cancelled/evicted): uid, status, machine-readable reason, tenant,
#: priority, queue_wait_s / ttft_s / tpot_s, tokens generated, deadline_met.
GATEWAY_REQUEST_SCHEMA = "accelerate_tpu.telemetry.gateway.request/v1"

#: Aggregate gateway summary: terminal counts by status plus the per-metric
#: p50/p95/p99 blocks produced by ``telemetry.slo.slo_summary``.
GATEWAY_SLO_SCHEMA = "accelerate_tpu.telemetry.gateway.slo/v1"

#: One record per fleet replica per router step: health score, replica state
#: (active/draining/restarting/retired), breaker state, load (active lanes,
#: internal queue) and the failure counters the score is computed from —
#: the per-replica signal behind health-driven routing (``serving_gateway.fleet``).
REPLICA_HEALTH_SCHEMA = "accelerate_tpu.telemetry.replica.health/v1"

#: One record per fleet routing decision: which replica got the request and why
#: (``dispatch``/``probe``), plus the health/free-lane snapshot it won on —
#: and one per migration (``migrate``) when failover moves a request away.
FLEET_ROUTE_SCHEMA = "accelerate_tpu.telemetry.fleet.route/v1"

#: One record per autoscaler decision (``serving_gateway.autoscaler.
#: Autoscaler``): ``action`` is ``scale_up``/``scale_down``/``rebalance``,
#: ``reason`` the alert rule or forecast that triggered it, ``replicas`` the
#: fleet size AFTER the action, plus the per-role census, cumulative
#: replica-hours and the router-clock timestamp — the decision audit trail
#: the autoscale bench replays deterministically under a virtual clock.
FLEET_SCALE_SCHEMA = "accelerate_tpu.telemetry.fleet.scale/v1"

#: Emitted on every gang restart (attempt index, the exit codes that triggered
#: the teardown, the restart budget) by ``ElasticSupervisor`` — ``gang_id``
#: names WHICH gang, so one record stream can carry a whole fleet's restarts
#: (``FleetSupervisor`` keeps independent per-gang budgets).
ELASTIC_RESTART_SCHEMA = "accelerate_tpu.telemetry.elastic.restart/v1"

#: One record per inter-stage DCN transfer in MPMD multi-slice training
#: (``ops.collectives.stage_transfer``): which stage boundary the payload
#: crossed (``src_stage``/``dst_stage``), the direction (``fwd`` activation /
#: ``bwd`` cotangent), bytes and synchronously-measured latency, causally
#: joined to the training step/microbatch.
MPMD_TRANSFER_SCHEMA = "accelerate_tpu.telemetry.mpmd.transfer/v1"

#: One record per gang-of-gangs barrier action (``elastic.GangOfGangs``): a
#: healthy stage gang HOLDING at the recovery barrier while a crashed peer
#: restarts, and its RELEASE when the pipeline replays — ``gang_id`` names the
#: holding gang, ``peer`` the crashed one, ``action`` is ``hold``/``release``,
#: ``step`` the global training step the pipeline held at.
MPMD_BARRIER_SCHEMA = "accelerate_tpu.telemetry.mpmd.barrier/v1"

#: One record per MPMD stage per training step (``parallel.mpmd.StageProcess``):
#: host-fenced per-phase compute seconds (``fwd_s``/``bwd_s``/``apply_s``,
#: summed as ``busy_s``) between the step's wall-clock bounds ``t0``/``t1`` —
#: the per-stage timeline ``trace-report --train`` reconstructs pipeline
#: bubbles and straggler attribution from.
MPMD_STAGE_STEP_SCHEMA = "accelerate_tpu.telemetry.mpmd.stage_step/v1"

#: One record per warmup-precompiled program: graftaudit collective inventory,
#: donation effectiveness, and the graftmem static memory/comms estimate
#: (``compile_cache.warmup``).
AUDIT_PROGRAM_SCHEMA = "accelerate_tpu.telemetry.audit.program/v1"

#: One span per request-lifecycle phase (``telemetry.tracing``): queue wait,
#: admission, prefill, each decode round, retries/preemptions, terminal state —
#: causally linked to the step/kv/spec records via the engine ``step`` index.
TRACE_SPAN_SCHEMA = "accelerate_tpu.telemetry.trace.span/v1"

#: One record per fault observed by a recovery boundary (injected OR real):
#: the site it fired at, the fault kind/reason, the attributed request uid
#: (None when attribution needed bisection) and the engine step index.
FAULT_SCHEMA = "accelerate_tpu.telemetry.fault/v1"

#: One record per recovery action: poison-request quarantine, survivor
#: rebuild, bisection round, circuit-breaker transition, checkpoint fallback.
#: ``action`` is machine-readable; the other columns are action-specific.
RECOVERY_SCHEMA = "accelerate_tpu.telemetry.recovery/v1"

#: One record per alert-state transition (``telemetry.alerts.AlertEngine``):
#: ``rule`` names the :class:`~.alerts.AlertRule`, ``state`` is
#: ``firing``/``resolved``, ``kind`` is ``threshold``/``burn_rate``, ``value``
#: the observed aggregate and ``threshold`` the bound it crossed — the live
#: trigger surface an SLO-driven autoscaler subscribes to (ROADMAP item 5).
ALERT_SCHEMA = "accelerate_tpu.telemetry.alert/v1"

#: One point-in-time dump of the whole metrics plane
#: (``telemetry.metrics.MetricsPlane.snapshot_record``): every counter, gauge
#: and sliding-window histogram summary plus the SLO event-window block —
#: what bench rows stamp and ``metrics-dump`` prints.
METRICS_SNAPSHOT_SCHEMA = "accelerate_tpu.telemetry.metrics.snapshot/v1"

#: The manifest of one incident capsule (``telemetry.recorder.FlightRecorder``):
#: what triggered the dump (``trigger`` is a stable dedupe key like
#: ``alert:step-failure-burst`` or ``fault:serving.decode``), the triggering
#: record itself, when (recorder clock), how much of the flight ring was
#: captured vs dropped, which state snapshots rode along and the provenance
#: stamp — everything ``capsule-report`` needs to rebuild the incident from the
#: capsule directory alone.
CAPSULE_SCHEMA = "accelerate_tpu.telemetry.capsule/v1"


# --------------------------------------------------------------------- registry
@dataclasses.dataclass(frozen=True)
class RecordSchema:
    """One registered record format: id, the key set a consumer may rely on
    unconditionally, who emits it, and what it is for. Emitters may add optional
    columns freely (memory stats, derived rates, kind-specific span attrs);
    required keys only ratchet UP within a ``/v<rev>``."""

    schema: str
    required: frozenset
    emitter: str
    description: str


def _reg(schema: str, required, emitter: str, description: str) -> RecordSchema:
    return RecordSchema(schema, frozenset(required) | {"schema"}, emitter, description)


#: Every record format the pipeline emits, keyed by schema id.
SCHEMA_REGISTRY: Dict[str, RecordSchema] = {
    s.schema: s
    for s in (
        _reg(
            STEP_RECORD_SCHEMA,
            ("telemetry_rev", "step", "wall_s", "dispatch_s", "fence_s", "steady",
             "warmup_steps_detected", "compiles_total", "compile_s_total",
             "compiles_delta"),
            "Telemetry._step_end",
            "fenced per-step timing, steadiness, compile counters",
        ),
        _reg(
            SERVING_SCHEMA,
            ("telemetry_rev", "queued", "active_slots", "max_slots",
             "slot_occupancy", "admitted", "evicted", "decode_steps",
             "decode_tokens"),
            "ContinuousBatcher.step",
            "per-decode-step engine counters (queue, lanes, prefix cache)",
        ),
        _reg(
            SERVING_THROUGHPUT_SCHEMA,
            ("wall_s", "tokens_generated", "requests_finished", "tokens_per_sec"),
            "ContinuousBatcher.run",
            "aggregate tokens/s for one drained workload",
        ),
        _reg(
            SERVING_KV_SCHEMA,
            ("telemetry_rev", "step", "page_size", "pages_total", "pages_in_use",
             "page_occupancy", "kv_bytes_in_use", "kv_bytes_total",
             "kv_shared_pages", "kv_alloc_count", "kv_free_count", "kv_cow_count",
             "kv_adopt_count", "kv_defer_count"),
            "ContinuousBatcher.step (paged)",
            "page-pool occupancy/bytes/sharing/churn per decode step",
        ),
        _reg(
            SERVING_SPEC_SCHEMA,
            ("telemetry_rev", "step", "spec_k", "rounds", "active_slots",
             "step_proposed", "step_accepted", "step_tokens", "proposed_total",
             "accepted_total"),
            "ContinuousBatcher._count_spec (_spec_step / _spec_multi)",
            "speculative proposal/acceptance per dispatch (rounds=1 host loop; "
            "rounds=N fused super-step)",
        ),
        _reg(
            SERVING_HANDOFF_SCHEMA,
            ("src_replica", "dst_replica", "uid", "pages", "nbytes", "dur_s"),
            "ops.collectives.kv_page_transfer",
            "one cross-engine KV page handoff (prefill -> decode replica)",
        ),
        _reg(
            GATEWAY_REQUEST_SCHEMA,
            ("uid", "status", "reason", "tenant", "priority", "n_tokens",
             "retries_used", "queue_wait_s", "ttft_s", "tpot_s", "deadline_met"),
            "ServingGateway._finalize",
            "one record per request reaching a terminal state",
        ),
        _reg(
            GATEWAY_SLO_SCHEMA,
            ("policy", "submitted", "admitted", "done", "rejected", "shed",
             "cancelled", "expired", "evicted", "retried", "failed",
             "replayed", "slo"),
            "ServingGateway.emit_slo_record",
            "aggregate SLO percentiles + admission accounting",
        ),
        _reg(
            REPLICA_HEALTH_SCHEMA,
            ("replica", "state", "role", "health", "breaker_state",
             "active_slots", "queued", "step_failures"),
            "FleetRouter.step",
            "per-replica health score, state, role and load per router step",
        ),
        _reg(
            FLEET_ROUTE_SCHEMA,
            ("uid", "replica", "reason", "health", "free_lanes"),
            "FleetRouter",
            "one routing decision: request -> replica (dispatch/probe/migrate)",
        ),
        _reg(
            FLEET_SCALE_SCHEMA,
            ("action", "reason", "replicas", "t"),
            "serving_gateway.autoscaler.Autoscaler",
            "one autoscaler decision (scale_up/scale_down/rebalance) with the "
            "post-action fleet census",
        ),
        _reg(
            ELASTIC_RESTART_SCHEMA,
            ("gang_id", "attempt", "attempts_used", "max_restarts",
             "exit_codes"),
            "ElasticSupervisor / FleetSupervisor",
            "one record per gang restart (gang_id names which gang)",
        ),
        _reg(
            MPMD_TRANSFER_SCHEMA,
            ("src_stage", "dst_stage", "direction", "nbytes", "dur_s", "step",
             "microbatch"),
            "ops.collectives.stage_transfer",
            "one inter-stage DCN transfer (activation fwd / cotangent bwd)",
        ),
        _reg(
            MPMD_BARRIER_SCHEMA,
            ("gang_id", "peer", "action", "step"),
            "elastic.GangOfGangs",
            "a healthy gang holding at / released from the recovery barrier",
        ),
        _reg(
            MPMD_STAGE_STEP_SCHEMA,
            ("gang_id", "stage", "step", "t0", "t1", "busy_s", "fwd_s",
             "bwd_s", "apply_s", "microbatches"),
            "parallel.mpmd.StageProcess",
            "one stage's fenced per-phase compute seconds for one train step",
        ),
        _reg(
            AUDIT_PROGRAM_SCHEMA,
            # "memory" rode a required-key ratchet-UP within /v1 (the allowed
            # direction): the graftmem static peak-HBM + priced ICI/DCN block.
            ("label", "collectives", "donation", "memory"),
            "compile_cache.warmup",
            "per-program graftaudit inventory (collectives, donation, memory)",
        ),
        _reg(
            TRACE_SPAN_SCHEMA,
            ("trace_id", "uid", "span", "t0", "t1", "dur_s"),
            "telemetry.tracing.Tracer",
            "request-scoped lifecycle span (queue/admit/prefill/decode/terminal)",
        ),
        _reg(
            FAULT_SCHEMA,
            ("site", "kind"),
            "recovery boundaries (serving/training/checkpointing)",
            "one fault observed at a recovery boundary (injected or real)",
        ),
        _reg(
            RECOVERY_SCHEMA,
            ("action",),
            "recovery boundaries (engine/gateway/checkpointing)",
            "one recovery action (quarantine/rebuild/bisect/circuit/fallback)",
        ),
        _reg(
            ALERT_SCHEMA,
            ("rule", "state", "severity", "kind", "t"),
            "telemetry.alerts.AlertEngine",
            "one alert-state transition (firing/resolved) over plane aggregates",
        ),
        _reg(
            METRICS_SNAPSHOT_SCHEMA,
            ("t", "counters", "gauges", "histograms", "slo"),
            "telemetry.metrics.MetricsPlane.snapshot_record",
            "one point-in-time dump of every live counter/gauge/histogram",
        ),
        _reg(
            CAPSULE_SCHEMA,
            ("trigger", "t", "ring_records", "ring_dropped", "state_keys",
             "provenance"),
            "telemetry.recorder.FlightRecorder",
            "one incident capsule manifest (trigger, ring/state accounting)",
        ),
    )
}


def registered_schemas() -> List[str]:
    """Every registered schema id, sorted."""
    return sorted(SCHEMA_REGISTRY)


def validate_record(record: Mapping) -> List[str]:
    """Problems with one record against its registration (empty = valid):
    unknown/missing schema id, or registered required keys the record lacks."""
    schema = record.get("schema")
    if schema is None:
        return ["record has no 'schema' key"]
    reg = SCHEMA_REGISTRY.get(schema)
    if reg is None:
        return [f"unregistered schema {schema!r} (register it in telemetry/schemas.py)"]
    missing = sorted(reg.required - set(record))
    return [f"{schema}: missing required keys {missing}"] if missing else []


# ------------------------------------------------------------------- docs drift
#: Markers bounding the generated block in docs/telemetry.md.
_DOCS_BEGIN = "<!-- BEGIN GENERATED SCHEMA TABLE (python -m accelerate_tpu.telemetry.schemas --write) -->"
_DOCS_END = "<!-- END GENERATED SCHEMA TABLE -->"


def schema_table_markdown() -> str:
    """The generated registry table (including its drift-gate markers)."""
    lines = [
        _DOCS_BEGIN,
        "| schema | emitter | required keys | purpose |",
        "|---|---|---|---|",
    ]
    for sid in registered_schemas():
        reg = SCHEMA_REGISTRY[sid]
        keys = ", ".join(f"`{k}`" for k in sorted(reg.required - {"schema"}))
        lines.append(f"| `{sid}` | {reg.emitter} | {keys} | {reg.description} |")
    lines.append(_DOCS_END)
    return "\n".join(lines) + "\n"


def _docs_path() -> str:
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(root, "docs", "telemetry.md")


def docs_table_is_fresh(path: str = None) -> bool:
    """True when docs/telemetry.md's generated block matches this registry."""
    return _splice_docs(path or _docs_path(), write=False)


def write_docs_table(path: str = None) -> None:
    """Refresh docs/telemetry.md's generated block in place."""
    _splice_docs(path or _docs_path(), write=True)


def _splice_docs(path: str, write: bool) -> bool:
    with open(path, "r", encoding="utf-8") as f:
        text = f.read()
    begin = text.find(_DOCS_BEGIN)
    end = text.find(_DOCS_END)
    if begin < 0 or end < 0:
        raise RuntimeError(
            f"{path} lacks the generated schema-table markers "
            f"({_DOCS_BEGIN!r} ... {_DOCS_END!r})"
        )
    end += len(_DOCS_END) + 1  # the block's trailing newline
    fresh = text[:begin] + schema_table_markdown() + text[end:]
    if write:
        with open(path, "w", encoding="utf-8") as f:
            f.write(fresh)
        return True
    return fresh == text


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        "python -m accelerate_tpu.telemetry.schemas",
        description="Telemetry schema registry: list, check or regenerate the "
        "generated table in docs/telemetry.md.",
    )
    parser.add_argument("--check", action="store_true",
                        help="exit 1 when the docs table drifted from the registry")
    parser.add_argument("--write", action="store_true",
                        help="rewrite the docs table from the registry")
    args = parser.parse_args(argv)
    if args.write:
        write_docs_table()
        print(f"schema table written to {_docs_path()}")
        return 0
    if args.check:
        if docs_table_is_fresh():
            print(f"schema table: {len(SCHEMA_REGISTRY)} registered schemas, docs fresh")
            return 0
        print("schema table in docs/telemetry.md drifted — run "
              "`python -m accelerate_tpu.telemetry.schemas --write`")
        return 1
    for sid in registered_schemas():
        print(f"{sid}  [{SCHEMA_REGISTRY[sid].emitter}]")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
