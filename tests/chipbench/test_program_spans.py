"""CPU tests of the program's own spans (``accelerate_tpu/telemetry/tracing.py::phase``)
and of the readers built on them (``benchmarks/chipbench/program_spans.py`` and the
``metrics/*.py`` it serves): a toy engine and a toy train step driven inside a
``jax.profiler`` session yield every span of docs/telemetry.md's table; outside a session
the same drive records nothing and the ``Tracer``'s records are what they were; each
reader gives a hand-computed value on hand-built spans; the traced dry runs report the
host-clock metrics; and a slice of the chat cell recorded on a TPU v5 lite reads what the
chip run that recorded it printed. Nothing here is a measurement.
"""

import dataclasses
import gzip
import json
import os
import shutil
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chipbench import program_spans, run, trace_reduce
from benchmarks.chipbench.program_spans import Span

CHAT, LONG = "serve_mistral7b_chat", "serve_mistral7b_longprompt"
DECODE_PHASES = {"engine.decode.prepare", "engine.decode.dispatch", "engine.decode.fetch",
                 "engine.decode.drain"}
ENGINE_SPANS = {"engine.step", "engine.admit", "engine.prefill", "engine.prefill.fetch",
                "engine.defer", "engine.decode"} | DECODE_PHASES
PARENT = {"engine.admit": "engine.step", "engine.decode": "engine.step",
          "engine.prefill": "engine.admit", "engine.defer": "engine.admit",
          "engine.prefill.fetch": "engine.prefill",
          **{name: "engine.decode" for name in DECODE_PHASES}}


# ----------------------------------------------------------- the program, driven on the CPU
@pytest.fixture(scope="module")
def toy():
    from accelerate_tpu.models import llama

    cfg = dataclasses.replace(llama.CONFIGS["tiny"], dtype=jnp.float32)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32) for n in (5, 9, 3, 7)]
    return cfg, llama.init_params(cfg), prompts


def drive(toy, session_dir=None):
    """Four requests through a gateway onto a paged engine whose pool holds two of them
    (so the head request is deferred), 4-step decode; → the Tracer's span records."""
    from accelerate_tpu.serving import ContinuousBatcher
    from accelerate_tpu.serving_gateway import ServingGateway
    from accelerate_tpu.telemetry import Telemetry, Tracer
    from accelerate_tpu.telemetry.schemas import TRACE_SPAN_SCHEMA
    from accelerate_tpu.utils.dataclasses import GatewayConfig, TelemetryConfig

    cfg, params, prompts = toy
    tel = Telemetry(TelemetryConfig(enabled=True, compile_events=False, memory_stats=False))
    tracer = Tracer(tel)
    eng = ContinuousBatcher(params, cfg, max_slots=3, max_len=64, prompt_bucket=16,
                            page_size=8, kv_pages=8, decode_steps=4, tracer=tracer)
    gw = ServingGateway(eng, GatewayConfig(enabled=True), telemetry=tel, tracer=tracer)
    if session_dir is not None:
        jax.profiler.start_trace(session_dir)
    try:
        for p in prompts:
            gw.submit(p, max_new_tokens=10)
        out = gw.run()
    finally:
        if session_dir is not None:
            jax.profiler.stop_trace()
    assert all(r.status == "done" for r in out) and eng.stats()["kv_defer_count"] > 0
    return [r for r in tel.records if r.get("schema") == TRACE_SPAN_SCHEMA]


@pytest.fixture(scope="module")
def traced(toy, tmp_path_factory):
    """(the ``atpu.`` spans of a profiled drive, its Tracer records, those of the same
    drive with no session and the spans a session opened afterwards then holds)."""
    where = tmp_path_factory.mktemp("session")
    drive(toy)                                                # compile outside the session
    records = drive(toy, str(where / "on"))
    records_off = drive(toy)
    jax.profiler.start_trace(str(where / "after"))
    jax.profiler.stop_trace()
    return (program_spans.load(str(where / "on")), records, records_off,
            program_spans.load(str(where / "after")))


def test_engine_yields_every_span_of_the_table_each_inside_its_parent(traced):
    spans = traced[0]
    assert {s.name for s in spans} == ENGINE_SPANS
    for s in spans:
        assert (s.parent.name if s.parent else None) == PARENT.get(s.name), s.name
        if s.parent is not None:
            assert s.parent.t0 <= s.t0 and s.t1 <= s.parent.t1
    by = lambda name: [s for s in spans if s.name == name]                    # noqa: E731
    assert all({"queued", "lanes"} <= set(s.attrs) for s in by("engine.step"))
    assert all("lanes" in s.attrs for s in by("engine.admit"))
    assert all(s.attrs["lanes"] > 0 and s.attrs["n_steps"] == 4 for s in by("engine.decode"))
    assert sum(s.attrs["tokens"] for s in by("engine.decode.drain")) == 4 * (10 - 1)
    assert all(s.dur == 0 or s.dur < 1e6 for s in by("engine.defer"))


def test_a_request_has_one_prefill_and_its_fetch_shares_the_uid(traced):
    prefills = [s for s in traced[0] if s.name == "engine.prefill"]
    assert sorted(s.attrs["uid"] for s in prefills) == [0, 1, 2, 3]       # one per admission
    for s in prefills:
        assert {"prompt_len", "width", "queue_wait_ms"} <= set(s.attrs)
        assert s.attrs["width"] == 16 and s.attrs["queue_wait_ms"] >= 0
        (fetch,) = [c for c in s.children if c.name == "engine.prefill.fetch"]
        assert fetch.attrs["uid"] == s.attrs["uid"]
    deferred = {s.attrs["uid"] for s in traced[0] if s.name == "engine.defer"}
    assert deferred and deferred <= {2, 3}          # the pool holds two: a later one waited


def test_self_times_sum_to_the_step(traced):
    steps = [s for s in traced[0] if s.name == "engine.step"]
    assert len(steps) >= 4
    for step in steps:
        assert step.self_ns >= 0
        assert sum(s.self_ns for s in (step, *step.descendants())) == step.dur
    table = program_spans.self_time_table(traced[0])
    assert "engine.decode.fetch" in table and f"{len(steps)} whole spans" in table


def test_no_session_no_spans_and_the_tracer_records_what_it_did(traced):
    _, records, records_off, after = traced
    assert after == []                        # nothing was kept for a later session to find
    key = lambda r: (r["span"], r["uid"], r.get("step"), r.get("tokens"), r.get("width"),  # noqa: E731
                     r.get("kv_defer_retries"))
    assert [key(r) for r in records] == [key(r) for r in records_off]
    assert {"queue", "admit", "prefill", "decode", "first_token", "terminal"} <= {
        r["span"] for r in records}
    assert any(r.get("kv_defer_retries") for r in records if r["span"] == "admit")


def test_train_step_span_carries_the_step_number(tmp_path):
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import llama
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    for singleton in (AcceleratorState, GradientState, PartialState):
        singleton._reset_state()
    cfg = dataclasses.replace(llama.CONFIGS["tiny"], dtype=jnp.float32)
    acc = Accelerator()
    state = acc.create_train_state(llama.init_params(cfg), optax.sgd(1e-2))
    step = acc.build_train_step(lambda p, b: llama.loss_fn(p, b, cfg))
    batch = {"tokens": np.ones((acc.mesh.size, 9), np.int32)}
    state, _ = step(state, batch)
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(2):
        state, m = step(state, batch)
    jax.block_until_ready(m)
    jax.profiler.stop_trace()
    for singleton in (AcceleratorState, GradientState, PartialState):
        singleton._reset_state()
    spans = [s for s in program_spans.load(str(tmp_path)) if s.name == "train.step"]
    assert [s.attrs["step_num"] for s in spans] == [1, 2]


# ------------------------------------------------------------- the readers, on hand-built spans
def spans_by_hand():
    """Three ``step()``s of 100 us, on a slice of 1 ms: each waits 60 us for the device in
    decode and, where it admits, 10 us more in a prefill of 30 us."""
    out = []
    for i, (lanes, wait) in enumerate([(0, 5.0), (2, 7.5), (3, 40.0)]):
        t = 100_000 + 200_000 * i
        out += [Span("engine.step", t, t + 100_000, {"queued": 1, "lanes": lanes}),
                Span("engine.admit", t + 1_000, t + 33_000, {"lanes": lanes}),
                Span("engine.prefill", t + 2_000, t + 32_000,
                     {"uid": i, "prompt_len": 40, "width": 64, "queue_wait_ms": wait}),
                Span("engine.prefill.fetch", t + 20_000, t + 30_000, {"uid": i}),
                Span("engine.decode", t + 35_000, t + 99_000, {"lanes": lanes + 1, "n_steps": 4}),
                Span("engine.decode.fetch", t + 37_000, t + 97_000, {})]
    return program_spans.nest(out)


def run_with(spans, window_s=1e-3):
    return types.SimpleNamespace(program_spans=spans, obs={}, config={}, peak=None,
                                 trace=types.SimpleNamespace(window_s=window_s, devices={}))


def test_host_span_readers_by_hand(capsys):
    spans = spans_by_hand()
    assert [s.self_ns for s in spans if s.name == "engine.step"] == [4_000] * 3
    assert [s.self_ns for s in spans if s.name == "engine.prefill"] == [20_000] * 3
    r = run_with(spans)
    # 100 us - (60 + 10) us of fetches; admits that began with lanes running: 2 x 32 us;
    # the median wait of 5, 7.5 and 40 ms
    assert run.read_metric("engine_host_ms_per_step.latency", r) == pytest.approx(0.030)
    assert run.read_metric("queue_wait_ms_p50.latency", r) == pytest.approx(7.5)
    assert run.read_metric("engine_admit_wall_share.throughput", r) is None     # two samples
    err = capsys.readouterr().err
    assert "engine_host_ms_per_step: 3 sample(s)" in err and "engine.decode.fetch" in err
    assert "engine_admit_wall_share: 2 sample(s)" in err
    for s in spans:
        if s.name == "engine.admit":
            s.attrs["lanes"] = 1
    assert run.read_metric("engine_admit_wall_share.throughput", r) == pytest.approx(9.6)


@pytest.mark.parametrize("name", ["engine_host_ms_per_step.throughput",
                                  "engine_admit_wall_share.latency",
                                  "queue_wait_ms_p50.latency",
                                  "paged_attn_ms_per_dispatch.latency",
                                  "flash_bwd_ms_per_step"])
def test_readers_return_none_under_three_samples_and_on_a_program_without_spans(name):
    two = [s for s in spans_by_hand() if s.t0 < 500_000]
    trace = trace_reduce.Trace.__new__(trace_reduce.Trace)
    trace.begin, trace.end = 0, 1_000_000
    trace.modules = {"d": [(0, 400_000, "jit_m"), (500_000, 900_000, "jit_m")]}
    trace.devices = {"d": [(10, 110, "jit_m/paged_attention.3_bf16_32_32_128__mosaic_"),
                           (500_010, 500_110, "jit_m/flash_bwd_dq.2_f32_4__mosaic_")]}
    r = run_with(two)
    r.trace = trace
    assert run.read_metric(name, r) is None
    trace.devices = {"d": [(10, 110, "jit_m/closed_call.14_bf16_32_32_128__mosaic_")]}
    r.program_spans = []                   # the parent commit: no names, no ``atpu.`` spans
    assert run.read_metric(name, r) is None


def test_kernel_time_per_whole_execution_by_hand():
    """Four executions of 400 us, the last cut to 100 us where the trace stops: three
    whole ones, each with its kernel ops; the ops of the cut one, and those of a module
    that spends less time in the kernel, do not count."""
    trace = trace_reduce.Trace.__new__(trace_reduce.Trace)
    trace.begin, trace.end = 0, 2_000_000
    trace.modules = {"d": [(i * 500_000, i * 500_000 + 400_000, "jit_step") for i in range(3)]
                     + [(1_500_000, 1_600_000, "jit_step"), (1_700_000, 1_800_000, "jit_other")]}
    ops = []
    for i in range(4):
        t = i * 500_000
        ops += [(t + 10_000, t + 40_000, "jit_step/flash_bwd_dkv.11_f32_4_8__mosaic_"),
                (t + 50_000, t + 60_000, "jit_step/flash_bwd_dq.11_f32_4_32__mosaic_"),
                (t + 70_000, t + 75_000, "jit_step/flash_fwd.17_bf16_4_32__mosaic_"),
                (t + 80_000, t + 90_000, "jit_step/paged_attention.8_bf16_32__mosaic_")]
    ops.append((1_710_000, 1_715_000, "jit_other/paged_attention.2_bf16_32__mosaic_"))
    trace.devices = {"d": sorted(ops)}
    r = run_with([])
    r.trace = trace
    assert run.read_metric("flash_bwd_ms_per_step", r) == pytest.approx(0.040)
    assert run.read_metric("paged_attn_ms_per_dispatch.throughput", r) == pytest.approx(0.010)


def test_idle_seconds_go_to_the_innermost_span_open_by_hand():
    """Three idle intervals around two ops; a gap that spans two phases is shared out."""
    trace = trace_reduce.Trace.__new__(trace_reduce.Trace)
    trace.begin, trace.end = 0, 1_000_000
    trace.devices = {"d": [(100_000, 200_000, "m/a"), (500_000, 600_000, "m/b"),
                           (600_005, 600_010, "m/c")]}
    spans = program_spans.nest([Span("engine.step", 50_000, 900_000, {}),
                                Span("engine.decode", 150_000, 700_000, {}),
                                Span("engine.decode.fetch", 300_000, 650_000, {})])
    idle = program_spans.idle_by_span(trace, spans)
    assert idle == {"_no_span_": pytest.approx(150e-6), "engine.step": pytest.approx(250e-6),
                    "engine.decode": pytest.approx(150e-6),
                    "engine.decode.fetch": pytest.approx(250e-6 - 10e-9),
                    "_gaps_under_10_us_": pytest.approx(5e-9)}
    assert sum(idle.values()) == pytest.approx(1e-3 - 200e-6 - 5e-9)


# ------------------------------------------------------------------- run.py, traced dry runs
@pytest.mark.parametrize("workload,suffix,also", [
    (CHAT, "latency", {"queue_wait_ms_p50.latency"}), (LONG, "throughput", set())])
def test_traced_dry_run_reports_the_host_clock_metrics(capsys, monkeypatch, tmp_path,
                                                       workload, suffix, also):
    # the trace goes to a directory of this test's own: test_chipbench.py's traced dry runs
    # write ROOT/.cb_trace from another xdist worker at the same time
    root = run.ROOT
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    monkeypatch.setattr(program_spans, "ROOT", str(tmp_path))
    rc = run.main(["--workload", workload, "--seed", "2147483659", "--seconds", "4",
                   "--trace", "1", "--cpu-dry-run", "--root", root])
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1])
    want = {f"engine_host_ms_per_step.{suffix}", f"engine_admit_wall_share.{suffix}"} | also
    assert rc == 0 and want <= set(line["metrics"])
    assert all(line["metrics"][m]["value"] >= 0 for m in want)
    assert not any(m.startswith(("paged_attn_ms", "flash_bwd_ms")) for m in line["metrics"])
    assert "self time per engine.step" in captured.err


# -------------------------------------------------------- a slice recorded on the chip
RECORDED = os.path.join(run.HERE, "testdata", "chat_short_slice.xplane.pb.gz")
PRINTED = os.path.join(run.HERE, "testdata", "chat_short_slice.json")


def test_recorded_chat_slice_reads_what_the_chip_run_printed(tmp_path, monkeypatch):
    """A slice of the chat cell recorded on a TPU v5 lite (PR 25), and beside it the
    per-layer metrics the run that recorded it printed."""
    with open(PRINTED) as f:
        printed = json.load(f)
    with gzip.open(RECORDED) as src, open(tmp_path / "chat.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    monkeypatch.setattr(program_spans, "ROOT", str(tmp_path))
    os.makedirs(tmp_path / ".cb_trace")
    os.replace(tmp_path / "chat.xplane.pb", tmp_path / ".cb_trace" / "chat.xplane.pb")
    r = types.SimpleNamespace(trace=trace_reduce.Trace(str(tmp_path / ".cb_trace")))
    names = ["engine_host_ms_per_step.latency", "engine_admit_wall_share.latency",
             "queue_wait_ms_p50.latency", "paged_attn_ms_per_dispatch.latency"]
    for name in names:
        assert run.read_metric(name, r) == pytest.approx(printed[name], rel=1e-9), name
    assert run.read_metric("flash_bwd_ms_per_step", r) is None      # no train step in it
    ops = r.trace.op_seconds()
    assert any("/paged_attention." in k and k.endswith("__mosaic_") for k in ops)
    steps = [s for s in program_spans.in_slice(r) if s.name == "engine.step"]
    assert len(steps) >= 3 and {s.name for s in program_spans.in_slice(r)} >= DECODE_PHASES
