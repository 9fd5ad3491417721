"""Telemetry subsystem tests (CPU backend): the bench_rev-2 lessons as a library.

Covers the ISSUE-2 acceptance surface: SteadyStateDetector semantics on synthetic
series including the settling-transient shape, fenced-timer correctness (fence on a
1-element target, never the full result), compile-counter increments across an
intentional recompile, JSONL record schema round-trip, disabled-mode zero-overhead
(zero records AND zero extra ``block_until_ready`` calls), bench/library detector
agreement on canned series, and the end-to-end JSONL run-directory contract on a
CPU train loop.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.telemetry import (
    STEP_RECORD_SCHEMA,
    TELEMETRY_REV,
    CompileMonitor,
    ScheduledProfiler,
    SteadyStateDetector,
    StepTimer,
    device_memory_stats,
    fence,
    peak_tflops,
)
from accelerate_tpu.utils.dataclasses import ProfileKwargs, TelemetryConfig


# ------------------------------------------------------------- SteadyStateDetector

#: The settling-transient shape: ~10 s allocator-settling first round(s), then steady
#: ~0.46 s steps. Pre-rev-2 timing averaged the 10 s into the metric (2.4x under).
SETTLING_SERIES = [10.2, 2.1, 0.47, 0.46, 0.465, 0.47]


def test_detector_perf_notes_transient_labeled_not_averaged():
    det = SteadyStateDetector(k=2, rtol=0.10, max_windows=5)
    results = [det.observe(dt) for dt in SETTLING_SERIES]
    # Steady exactly when the first agreeing pair completes (0.47, 0.46).
    assert results == [False, False, False, True, True, True]
    assert det.steady and not det.capped
    # The 10.2 and 2.1 rounds are labeled warmup; the agreeing pair is steady.
    assert det.warmup_steps_detected == 2
    mean = det.steady_mean_s()
    assert 0.4 < mean < 0.5  # the transient never pollutes the steady estimate


def test_detector_immediate_agreement():
    det = SteadyStateDetector(k=2, rtol=0.10, max_windows=5)
    assert not det.observe(1.0)
    assert det.observe(1.05)
    assert det.warmup_steps_detected == 0


def test_detector_cap_labels_everything_warmup():
    det = SteadyStateDetector(k=2, rtol=0.10, max_windows=4)
    series = [8.0, 4.0, 2.0, 1.0]  # halves every round: never agrees
    results = [det.observe(dt) for dt in series]
    assert results == [False, False, False, True]
    assert det.steady and det.capped
    assert det.warmup_steps_detected == 4  # no window was provably steady
    assert det.steady_mean_s() is None


def test_detector_k3_needs_three_agreeing_windows():
    det = SteadyStateDetector(k=3, rtol=0.10, max_windows=0)
    for dt in [5.0, 1.0, 1.02]:
        assert not det.observe(dt)
    assert det.observe(1.01)
    assert det.warmup_steps_detected == 1


def test_detector_validates_params():
    with pytest.raises(ValueError):
        SteadyStateDetector(k=1)
    with pytest.raises(ValueError):
        SteadyStateDetector(rtol=0.0)
    with pytest.raises(ValueError):
        SteadyStateDetector(max_windows=-1)


def test_detector_cap_below_k_allowed_caps_immediately():
    """bench's BENCH_MAX_SETTLE_ROUNDS=1 contract: a cap smaller than k runs that
    many rounds, never settles, and labels them all warmup — no crash."""
    det = SteadyStateDetector(k=2, rtol=0.10, max_windows=1)
    assert det.observe(1.0)
    assert det.capped and det.warmup_steps_detected == 1


def _bench_rev2_inline_warmup(series, cap=5, rtol=0.10):
    """The exact inline loop bench.py shipped as bench_rev 2 (pre-extraction):
    run up to ``cap`` rounds, stop after the first pair agreeing within ``rtol``.
    Returns the number of rounds consumed."""
    prev = None
    rounds = 0
    for dt in series[:cap]:
        rounds += 1
        settled = prev is not None and abs(dt - prev) <= rtol * max(dt, prev)
        prev = dt
        if settled:
            break
    return rounds


@pytest.mark.parametrize(
    "series",
    [
        SETTLING_SERIES,
        [1.0, 1.0, 1.0],
        [5.0, 3.0, 2.0, 1.5, 1.45, 1.44],
        [8.0, 4.0, 2.0, 1.0, 0.5, 0.25],  # never settles: cap behavior
        [0.5, 0.51],
    ],
)
def test_bench_and_library_detector_agree_on_canned_series(series):
    """Tier-1 satellite gate: the library detector consumes exactly as many warmup
    rounds as bench.py's historical inline rev-2 loop on every canned series —
    one implementation, same semantics."""
    cap = 5
    det = SteadyStateDetector(k=2, rtol=0.10, max_windows=cap)
    rounds = 0
    for dt in series:
        rounds += 1
        if det.observe(dt):
            break
    assert rounds == _bench_rev2_inline_warmup(series, cap=cap)


def test_bench_imports_the_library_detector():
    """bench.py must consume telemetry's detector (and its rev constant), not keep a
    private fork of the warm-until-steady rule."""
    import bench

    src = open(bench.__file__).read()
    assert "SteadyStateDetector" in src
    assert "telemetry_rev" in src
    assert bench._BENCH_REV == TELEMETRY_REV


# ----------------------------------------------------------------- fenced timing


def test_fence_returns_input_and_blocks(monkeypatch):
    calls = []
    real_block = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready", lambda x: calls.append(x) or real_block(x))
    out = {"loss": jnp.ones(()), "big": jnp.ones((64, 64))}
    got = fence(out)
    assert got is out
    # Exactly one block, on the SMALLEST leaf (the designated 1-element output).
    assert len(calls) == 1
    assert np.asarray(calls[0]).size == 1


def test_fence_noop_on_host_values():
    assert fence({"a": 1.0, "b": [2, 3]}) == {"a": 1.0, "b": [2, 3]}


def test_step_timer_measures_fenced_call():
    timer = StepTimer()
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    out, timing = timer.time(f, x)
    assert timing.wall_s > 0
    assert timing.wall_s == pytest.approx(timing.dispatch_s + timing.fence_s, rel=1e-6)
    assert not timer.running


def test_step_timer_stop_without_start_raises():
    with pytest.raises(RuntimeError):
        StepTimer().stop(fence_on=jnp.ones(()))


# ------------------------------------------------------------- compile counters


def test_compile_counter_increments_across_intentional_recompile():
    mon = CompileMonitor().start()
    try:
        if not mon.supported:
            pytest.skip("this jax exposes no jax.monitoring API")

        @jax.jit
        def f(x):
            return x * 2 + 1

        f(jnp.ones((4,)))
        after_first = mon.count
        f(jnp.ones((4,)))  # cache hit: no new compile
        assert mon.count == after_first
        f(jnp.ones((8,)))  # new shape: intentional recompile
        assert mon.count > after_first
        assert mon.seconds > 0
    finally:
        mon.stop()


def test_compile_counter_label_attribution():
    from accelerate_tpu.telemetry import compile_label

    mon = CompileMonitor().start()
    try:
        if not mon.supported:
            pytest.skip("this jax exposes no jax.monitoring API")
        with compile_label("labeled_fn"):
            jax.jit(lambda x: x - 3)(jnp.ones((5,)))
        assert "labeled_fn" in mon.by_label
        assert mon.by_label["labeled_fn"]["count"] >= 1
    finally:
        mon.stop()


def test_compile_counter_stop_detaches():
    mon = CompileMonitor().start()
    mon.stop()
    before = mon.count
    jax.jit(lambda x: x / 7)(jnp.ones((6,)))
    assert mon.count == before


# ------------------------------------------------------------------ memory stats


def test_memory_stats_graceful_on_cpu():
    stats = device_memory_stats()
    assert isinstance(stats, dict)  # CPU backend: {} (no allocator ledger) — no crash
    for v in stats.values():
        assert isinstance(v, int)


def test_peak_tflops_table():
    assert peak_tflops(device_kind="TPU v5 lite") == 196.6
    assert peak_tflops(device_kind="TPU v5p") == 459.0
    assert peak_tflops(device_kind="TPU v5") == 459.0  # longest-match wins over v5*
    for kind in ("cpu", "unknown"):  # no row, no default: an error, never a guess
        with pytest.raises(KeyError):
            peak_tflops(device_kind=kind)


# ------------------------------------------------------------ record schema / JSONL


def test_step_record_jsonl_round_trip(tmp_path):
    from accelerate_tpu.telemetry.core import REQUIRED_STEP_COLUMNS, Telemetry

    cfg = TelemetryConfig(enabled=True, jsonl_dir=str(tmp_path), steady_cap=5)
    tel = Telemetry(cfg)
    f = jax.jit(lambda x: {"loss": x.sum()})
    x = jnp.ones((4, 8))
    for _ in range(3):
        tel._step_begin()
        out = f(x)
        tel._step_end(fence_on=out, batch={"input_ids": np.zeros((4, 8), np.int32)})
    tel.close()

    lines = (tmp_path / "telemetry.jsonl").read_text().splitlines()
    assert len(lines) == 3
    for line in lines:
        rec = json.loads(line)
        round_tripped = json.loads(json.dumps(rec))
        assert round_tripped == rec
        for col in REQUIRED_STEP_COLUMNS:
            assert col in rec, f"missing column {col}"
        assert rec["schema"] == STEP_RECORD_SCHEMA
        assert rec["telemetry_rev"] == TELEMETRY_REV
        assert rec["tokens_per_sec_per_chip"] > 0  # inferred from batch shape
    assert [json.loads(l)["step"] for l in lines] == [1, 2, 3]


def test_telemetry_config_env_override(monkeypatch):
    assert TelemetryConfig().enabled is False  # off by default
    monkeypatch.setenv("ACCELERATE_TELEMETRY", "1")
    monkeypatch.setenv("ACCELERATE_TELEMETRY_DIR", "/tmp/tel_env_dir")
    cfg = TelemetryConfig()
    assert cfg.enabled is True
    assert cfg.jsonl_dir == "/tmp/tel_env_dir"
    # Explicit arg beats env (the §5 priority order).
    assert TelemetryConfig(enabled=False).enabled is False


def test_telemetry_config_validation():
    with pytest.raises(ValueError):
        TelemetryConfig(steady_k=1)
    with pytest.raises(ValueError):
        TelemetryConfig(steady_rtol=-0.1)
    with pytest.raises(ValueError):
        TelemetryConfig(steady_cap=-1)
    TelemetryConfig(steady_k=3, steady_cap=2)  # cap < k: caps early, never crashes


# -------------------------------------------------- integration: train step records


def _tiny_training(telemetry_config, n_steps=4, log_with=None, project_dir=None):
    import optax

    from accelerate_tpu import Accelerator

    acc = Accelerator(telemetry_config=telemetry_config, log_with=log_with,
                      project_dir=project_dir)
    params = {"w": np.ones((16, 4), np.float32)}
    state = acc.create_train_state(params, optax.sgd(0.1))
    step = acc.build_train_step(
        lambda p, b: (b["input_ids"].astype(jnp.float32) @ p["w"]).mean()
    )
    batch = {"input_ids": np.ones((8, 16), np.int32)}
    for _ in range(n_steps):
        state, metrics = step(state, batch)
    return acc, state, metrics


def test_enabled_train_loop_writes_jsonl_run_dir(tmp_path):
    """The ISSUE-2 acceptance criterion: telemetry enabled on the CPU-backend train
    loop → a JSONL run directory with per-step records carrying step time,
    steady-state flag, compile count, memory stats (where the backend has them),
    and tokens/sec."""
    acc, _, _ = _tiny_training(
        TelemetryConfig(enabled=True, jsonl_dir=str(tmp_path)), n_steps=5
    )
    acc.telemetry.close()
    recs = [json.loads(l) for l in (tmp_path / "telemetry.jsonl").read_text().splitlines()]
    assert len(recs) == 5
    last = recs[-1]
    assert last["wall_s"] > 0 and last["fence_s"] >= 0
    assert isinstance(last["steady"], bool)
    assert last["compiles_total"] >= 1  # the train step compiled at least once
    assert last["tokens_per_sec_per_chip"] > 0
    # Memory stats are backend-dependent: when present they carry live bytes.
    if "memory" in last:
        assert last["memory"]["bytes_in_use"] > 0
    assert [r["step"] for r in recs] == [1, 2, 3, 4, 5]


def test_enabled_records_flow_to_jsonl_tracker(tmp_path):
    acc, _, _ = _tiny_training(
        TelemetryConfig(enabled=True), n_steps=3,
        log_with="jsonl", project_dir=str(tmp_path),
    )
    acc.init_trackers("telemetry_run")
    # Records emitted after tracker init flow through log_telemetry_record.
    acc.telemetry.emit(dict(acc.telemetry.last_step_record))
    # Accelerator.log auto-merges telemetry columns under the telemetry/ prefix.
    acc.log({"loss": 1.23}, step=3)
    acc.end_training()
    metrics = [
        json.loads(l)
        for l in (tmp_path / "telemetry_run" / "metrics.jsonl").read_text().splitlines()
    ]
    assert any("wall_s" in m for m in metrics)  # the raw telemetry record
    merged = [m for m in metrics if "loss" in m]
    assert merged and any(k.startswith("telemetry/") for k in merged[-1])


def test_flop_hint_reports_tflops_and_no_mfu_without_a_peak(tmp_path):
    cfg = TelemetryConfig(enabled=True, flops_per_step=1e6)
    acc, _, _ = _tiny_training(cfg, n_steps=3)
    rec = acc.telemetry.last_step_record
    assert rec["achieved_tflops_per_chip"] > 0
    assert "mfu" not in rec  # the CPU has no datasheet peak to be a share of
    acc.telemetry.close()


def test_disabled_mode_zero_records_zero_syncs(monkeypatch):
    """Acceptance: with telemetry disabled (the default), build_train_step adds no
    host syncs — zero records and zero extra block_until_ready calls."""
    import optax

    from accelerate_tpu import Accelerator

    acc = Accelerator()
    assert acc.telemetry.enabled is False
    params = {"w": np.ones((16, 4), np.float32)}
    state = acc.create_train_state(params, optax.sgd(0.1))
    step = acc.build_train_step(
        lambda p, b: (b["input_ids"].astype(jnp.float32) @ p["w"]).mean()
    )
    batch = {"input_ids": np.ones((8, 16), np.int32)}
    state, _ = step(state, batch)  # compile outside the counted window

    blocks = []
    monkeypatch.setattr(jax, "block_until_ready", lambda x: blocks.append(x) or x)
    for _ in range(3):
        state, _ = step(state, batch)
    assert blocks == []  # not one block_until_ready on the disabled hot path
    assert acc.telemetry.records == []
    assert acc.telemetry.last_step_record is None


def test_step_exception_unwinds_compile_label():
    """A step body that raises must not leak the compile-attribution label (a leaked
    label would credit every later compile to 'train_step' forever)."""
    from accelerate_tpu.telemetry.compile_monitor import _current_label

    import optax

    from accelerate_tpu import Accelerator

    acc = Accelerator(telemetry_config=TelemetryConfig(enabled=True))
    params = {"w": np.ones((16, 4), np.float32)}
    state = acc.create_train_state(params, optax.sgd(0.1))
    step = acc.build_train_step(
        lambda p, b: (b["input_ids"].astype(jnp.float32) @ p["w"]).mean()
    )
    with pytest.raises(Exception):
        step(state, {"input_ids": np.ones((8, 5), np.int32)})  # wrong inner dim
    assert _current_label() is None
    assert not acc.telemetry.timer.running
    # The bracket recovers: a good step afterwards records normally.
    state2 = acc.create_train_state(params, optax.sgd(0.1))
    state2, _ = step(state2, {"input_ids": np.ones((8, 16), np.int32)})
    assert acc.telemetry.last_step_record is not None
    acc.telemetry.close()


def test_fused_step_emits_one_record_per_dispatch(tmp_path):
    import optax

    from accelerate_tpu import Accelerator

    acc = Accelerator(telemetry_config=TelemetryConfig(enabled=True))
    params = {"w": np.ones((16, 4), np.float32)}
    state = acc.create_train_state(params, optax.sgd(0.1))
    step = acc.build_train_step(
        lambda p, b: (b["input_ids"].astype(jnp.float32) @ p["w"]).mean(),
        fused_steps=2,
    )
    batch = {"input_ids": np.ones((2, 8, 16), np.int32)}
    state, _ = step(state, batch)
    state, _ = step(state, batch)
    recs = [r for r in acc.telemetry.records if r.get("schema") == STEP_RECORD_SCHEMA]
    assert len(recs) == 2  # one record per fused dispatch window
    assert recs[-1]["step"] == 4  # but the step counter advances by fused_steps
    acc.telemetry.close()


# ----------------------------------------------------------- scheduled profiler


def test_schedule_option_validation():
    with pytest.raises(ValueError):
        ProfileKwargs(schedule_option={"wait": 1})  # no active
    with pytest.raises(ValueError):
        ProfileKwargs(schedule_option={"active": 2, "bogus": 1})
    with pytest.raises(ValueError):
        ProfileKwargs(schedule_option={"active": 1, "wait": -1})
    ProfileKwargs(schedule_option={"wait": 1, "warmup": 1, "active": 2, "repeat": 1})


def test_scheduled_profiler_windows(tmp_path, monkeypatch):
    """The schedule drives start/stop at exactly the window edges (profiler calls
    stubbed out: windowing logic is host-side and backend-free)."""
    events = []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: events.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: events.append(("stop",)))

    ready_dirs = []
    prof = ScheduledProfiler(
        trace_dir=str(tmp_path), wait=1, warmup=1, active=2, repeat=2,
        on_trace_ready=ready_dirs.append,
    )
    for _ in range(10):
        prof.step()
    prof.close()
    # Cycle = wait 1 + warmup 1 + active 2 → traces cover steps [2,3] and [6,7].
    starts = [e for e in events if e[0] == "start"]
    stops = [e for e in events if e[0] == "stop"]
    assert len(starts) == 2 and len(stops) == 2
    assert starts[0][1].endswith("cycle0") and starts[1][1].endswith("cycle1")
    assert ready_dirs == prof.traces_written
    assert prof.done


def test_scheduled_profiler_via_accelerator_profile(tmp_path, monkeypatch):
    from accelerate_tpu import Accelerator

    events = []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: events.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: events.append(("stop",)))

    acc = Accelerator()
    handler = ProfileKwargs(
        schedule_option={"wait": 1, "active": 1, "repeat": 1},
        output_trace_dir=str(tmp_path),
    )
    with acc.profile(handler) as prof:
        assert isinstance(prof, ScheduledProfiler)
        for _ in range(3):
            prof.step()
    assert [e[0] for e in events] == ["start", "stop"]


# ------------------------------------------------------------------ serving pipeline


def test_serving_counters_and_telemetry_records():
    import dataclasses

    from accelerate_tpu.models import llama
    from accelerate_tpu.serving import ContinuousBatcher
    from accelerate_tpu.telemetry import Telemetry

    cfg = dataclasses.replace(llama.CONFIGS["tiny"], dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tel = Telemetry(TelemetryConfig(enabled=True))
    engine = ContinuousBatcher(params, cfg, max_slots=2, max_len=128,
                               prompt_bucket=16, telemetry=tel)
    for prompt in ([1, 2, 3], [4, 5], [6, 7, 8, 9]):
        engine.submit(np.array(prompt, np.int32), max_new_tokens=3)
    out, tps = engine.run(report_throughput=True)
    assert len(out) == 3 and tps > 0

    stats = engine.stats()
    assert stats["admitted"] == 3
    assert stats["evicted"] == 3
    assert stats["active_slots"] == 0 and stats["queued"] == 0
    assert 0.0 <= stats["slot_occupancy"] <= 1.0

    serving_recs = [
        r for r in tel.records
        if str(r.get("schema", "")).startswith("accelerate_tpu.telemetry.serving")
    ]
    assert serving_recs, "serving counters must flow through the telemetry pipeline"
    tput = [r for r in serving_recs if r["schema"].endswith("throughput/v1")]
    assert len(tput) == 1
    assert tput[0]["tokens_generated"] == sum(len(r.tokens) for r in out)
    assert tput[0]["requests_finished"] == 3
    tel.close()
