"""Benchmark: training MFU of the framework's compiled train step on real TPU hardware.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.

Workload: a llama-architecture causal LM — ~0.9B params (llama3-8B-shaped slice: d_model
2048, GQA 16q/8kv, SwiGLU ff 8192, scanned layers), seq 2048, remat ON, Pallas flash
attention, bf16 compute with fp32 master weights, adamw, global-norm clipping, fused
multi-step dispatch (``build_train_step(fused_steps=N)``) with donated buffers.

Metric: **MFU** — model FLOP/s divided by the chip's peak bf16 FLOP/s.  Model FLOPs per token
use the standard 6·N + 6·L·S·D causal-attention accounting (PaLM appendix B convention, causal
halves the 12·L·S·D full-attention term).  ``vs_baseline`` is MFU / 0.40, the BASELINE.md
north-star target (the reference publishes no trainable-throughput numbers of its own —
its published baselines are big-model inference only, covered by examples/inference).

Failure is failure: a missing chip, an OOM, a kernel Mosaic refuses or any other exception
is a traceback and a non-zero exit. Nothing is retried with another configuration, no
earlier result is replayed, and a non-smoke run refuses the CPU backend. ``python
chip_smoke.py`` is the quick check that the chip and the kernels are there at all.
"""

from __future__ import annotations

import dataclasses
import json
import os as _os
import sys
import time

import numpy as np

NORTH_STAR_MFU = 0.40  # BASELINE.md: Llama-3-8B FSDP fine-tune target on v5e

# Measurement-methodology revision stamped into every row (rev 2 = warm-until-steady:
# earlier rows timed the allocator-settling transient into the step).
_BENCH_REV = 2


def _make_config(S: int, preset: str | None):
    import os

    from accelerate_tpu.models import llama
    from accelerate_tpu.utils.imports import is_tpu_available

    cfg = dataclasses.replace(
        llama.CONFIGS["llama3-8b"],
        vocab_size=32768,
        d_model=2048,
        n_layers=12,
        n_heads=16,
        n_kv_heads=8,
        d_ff=8192,
        max_seq=S,
        remat=os.environ.get("BENCH_REMAT", "1") == "1",
        remat_policy=os.environ.get("BENCH_REMAT_POLICY", "full"),
        remat_prevent_cse=(
            {"0": False, "1": True}[os.environ["BENCH_PREVENT_CSE"]]
            if "BENCH_PREVENT_CSE" in os.environ
            else None  # auto: False under scan_layers
        ),
        scan_layers=True,
        scan_unroll=int(os.environ.get("BENCH_SCAN_UNROLL", "1")),
        loss_chunk=int(os.environ.get("BENCH_LOSS_CHUNK", "0")),  # 0 auto, -1 off
        loss_impl=os.environ.get("BENCH_LOSS_IMPL", "auto"),  # auto | fused (Pallas CE)
        attn_impl=os.environ.get("BENCH_ATTN", "flash" if is_tpu_available() else "xla"),
    )
    if preset == "smoke":  # CI/CPU logic check, not a perf number
        cfg = dataclasses.replace(
            cfg, d_model=128, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=256, vocab_size=512
        )
    return cfg


def _measured_matmul_ceiling() -> float:
    """Chip's practically-attainable bf16 matmul TFLOP/s (chained MXU-shaped matmuls).
    Emitted beside the datasheet ``peak_tflops_assumed``: datasheet-MFU is the
    conservative headline, but a reader should also see how close the run is to what
    the chip actually sustains. Cheap (~seconds; one small pure-XLA compile)."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.telemetry import SteadyStateDetector

    M, k = 8192, 8
    a = jnp.ones((M, M), jnp.bfloat16)
    w = jnp.ones((M, M), jnp.bfloat16)

    @jax.jit
    def chain(a, w):
        for _ in range(k):
            a = a @ w
        return a

    # Warm until two consecutive rounds agree within 10% (cap 4): the first dispatches
    # of a cold process pay an allocator-settling transient. The rule lives in ONE
    # place: telemetry.SteadyStateDetector.
    det = SteadyStateDetector(k=2, rtol=0.10, max_windows=4)
    while not det.steady:
        t0 = time.perf_counter()
        jax.block_until_ready(chain(a, w))
        det.observe(time.perf_counter() - t0)
    t0 = time.perf_counter()
    n = 3
    out = None
    for _ in range(n):
        out = chain(a, w)
    jax.block_until_ready(out)  # fence only: a fetch here would be timed as matmul
    dt = time.perf_counter() - t0
    return n * k * 2 * M**3 / dt / 1e12


def _make_optimizer(name: str):
    """BENCH_OPT: optimizer variants for attributing the step-time gap between fwd_bwd
    alone and the full train step. The metric label carries the variant's name, except
    "fused_adamw" — the identical AdamW math as a Pallas kernel — which keeps the
    default label."""
    import jax.numpy as jnp
    import optax

    from accelerate_tpu.ops.fused_optim import fused_adamw

    return {
        "adamw": lambda: optax.adamw(1e-4),
        "adamw_mu_bf16": lambda: optax.adamw(1e-4, mu_dtype=jnp.bfloat16),
        "fused_adamw": lambda: fused_adamw(1e-4),
        "fused_adamw_mu_bf16": lambda: fused_adamw(1e-4, mu_dtype=jnp.bfloat16),
        # MS-AMP analog: scaled-fp8 moments (ScaledAdamState) — 4x less moment traffic
        # in the bandwidth-bound apply; state dtype changes the update trajectory.
        "fused_adamw_f8": lambda: fused_adamw(
            1e-4, mu_dtype=jnp.float8_e4m3fn, nu_dtype=jnp.float8_e4m3fn
        ),
        "sgd": lambda: optax.sgd(1e-4),
        "adafactor": lambda: optax.adafactor(1e-4),
        "lion": lambda: optax.lion(1e-5),
    }[name]()


def run(B: int, S: int, fuse: int, preset: str | None):
    import os

    import jax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import llama
    from accelerate_tpu.telemetry import (
        TELEMETRY_REV, CompileMonitor, SteadyStateDetector, fence,
    )

    cfg = _make_config(S, preset)
    n_params = llama.num_params(cfg)
    metric = _metric_label(B, S, fuse, preset, cfg)

    accum = int(os.environ.get("BENCH_ACCUM", "1"))
    ceiling = None
    if not preset and os.environ.get("BENCH_MEASURE_CEILING", "1") == "1":
        ceiling = _measured_matmul_ceiling()

    # Cold-start attribution window: everything from Accelerator construction through
    # the first completed step (compiles included) is the per-process tax a warm
    # compile cache removes — stamp it on every row so compile spend is attributable.
    cold_monitor = CompileMonitor().start()
    t_cold = time.perf_counter()
    try:
        acc = Accelerator(mixed_precision="bf16", gradient_accumulation_steps=accum)
        # Arm graftaudit program capture: when the AOT compile cache is enabled
        # (ACCELERATE_COMPILE_CACHE) every lowered program records its jaxpr +
        # StableHLO, and the row below stamps collective counts/bytes + donation
        # effectiveness — bench rows then diff comms across PRs.
        acc.compile_cache.capture = []
        state = acc.create_train_state(
            llama.init_params(cfg), _make_optimizer(os.environ.get("BENCH_OPT", "adamw"))
        )
        step = acc.build_train_step(
            lambda p, b: llama.loss_fn(p, b, cfg), max_grad_norm=1.0, fused_steps=fuse,
            # cast_params=False skips the whole-tree bf16 pre-cast (the model casts each
            # weight at point of use): one bf16 param copy less standing HBM, at the cost
            # of fp32 scan grad carries.
            cast_params=os.environ.get("BENCH_CAST_PARAMS", "1") == "1",
        )

        rng = np.random.default_rng(0)
        stacked = {"tokens": rng.integers(0, cfg.vocab_size, size=(fuse, B, S + 1)).astype(np.int32)}
        # fused_steps=1 builds the NON-fused _TrainStep, whose contract is a single
        # {'tokens': [B, S+1]} batch (no leading dispatch dim) and a scalar loss.
        if fuse == 1:
            stacked = {k: v[0] for k, v in stacked.items()}

        # Warmup / compile. The step donates its input state, so a failed dispatch
        # cannot be replayed: any failure ends the run.
        state, metrics = step(state, stacked)
        fence(metrics)
        cold_start_s = time.perf_counter() - t_cold
    finally:
        cold_monitor.stop()
    cold = cold_monitor.snapshot()
    if preset:
        # A CPU logic check proves the step compiles and runs; it reports no rate.
        print(json.dumps({
            "metric": metric, "value": None, "unit": "MFU", "vs_baseline": None,
            "preset": preset, "batch": B, "seq": S, "fused_steps": fuse,
            "loss": float(np.asarray(metrics["loss"]).reshape(-1)[-1]),
            "provenance": _provenance(cfg),
        }))
        return

    # Warm until steady: the first 1-2 post-compile apply rounds pay a large one-time
    # allocator/settling cost. Training runs for hours; a seconds-scale process-start
    # transient doesn't belong in the metric. The rule (two consecutive rounds within
    # 10%, cap 5) is the library's SteadyStateDetector — one implementation shared with
    # the in-framework telemetry; tests/test_telemetry.py pins bench/library agreement.
    settle_rounds = int(os.environ.get("BENCH_MAX_SETTLE_ROUNDS", "5"))
    settle = None
    if settle_rounds:
        settle = SteadyStateDetector(k=2, rtol=0.10, max_windows=settle_rounds)
        while not settle.steady:
            t0 = time.perf_counter()
            state, metrics = step(state, stacked)
            fence(metrics)
            settle.observe(time.perf_counter() - t0)

    n_rounds = 3
    profile_dir = os.environ.get("BENCH_PROFILE")
    if profile_dir:
        # One traced round for attribution, separate from the timed rounds so profiling
        # overhead never pollutes the reported MFU.
        jax.profiler.start_trace(profile_dir)
        try:
            state, metrics = step(state, stacked)
            fence(metrics)
        finally:
            jax.profiler.stop_trace()
        print(f"bench: profiler trace written to {profile_dir}", file=sys.stderr)
    t0 = time.perf_counter()
    for _ in range(n_rounds):
        state, metrics = step(state, stacked)
    fence(metrics)  # executions on a device are ordered: the last output fences them all
    dt = time.perf_counter() - t0

    n_steps = n_rounds * fuse
    n_chips = jax.device_count()
    tokens_per_sec = B * S * n_steps / dt / n_chips
    samples_per_sec = B * n_steps / dt / n_chips
    # FLOP model (keep stable round-over-round; MFU history depends on it):
    #   6N per token = fwd (2N) + bwd (4N) matmul MACs over all params, plus
    #   6·L·S·D causal attention = 2 score+context matmuls · 3 (fwd+bwd) · S/2
    #   (causal halves the square; written as 6·L·S·D per token with D=d_model and
    #   hd·H=D absorbed). DELIBERATELY conservative: no remat recompute credit, no
    #   vocab-head CE flops beyond the 6N share, no exp/softmax vector work — reported
    #   MFU errs LOW. peak_tflops_assumed is the datasheet bf16 number, not a measured
    #   matmul ceiling; a device kind without a datasheet row is an error.
    from accelerate_tpu.telemetry import peak_tflops

    flops_per_token = 6 * n_params + 6 * cfg.n_layers * S * cfg.d_model
    peak = peak_tflops(jax.devices()[0]) * 1e12
    tflops = tokens_per_sec * flops_per_token / 1e12
    mfu = tflops * 1e12 / peak
    out = {
        "metric": metric,
        "value": round(mfu, 4),
        "unit": "MFU",
        "vs_baseline": round(mfu / NORTH_STAR_MFU, 3),
        "model_params": n_params,
        "batch": B,
        "seq": S,
        "fused_steps": fuse,
        "tokens_per_sec_per_chip": round(tokens_per_sec, 1),
        "samples_per_sec_per_chip": round(samples_per_sec, 2),
        "achieved_tflops_per_chip": round(tflops, 2),
        "peak_tflops_assumed": round(peak / 1e12, 1),
        "device_kind": str(getattr(jax.devices()[0], "device_kind", "unknown")),
        # Cold-start attribution (setup → first step done): with a warm compile cache
        # the compile seconds collapse and cache hits account for the difference.
        "cold_start_s": round(cold_start_s, 3),
        "cold_compiles": cold["compiles_total"],
        "cold_compile_s": cold["compile_s_total"],
        "compile_cache": acc.compile_cache.stats(),
        # Reproducibility stamp: which commit,
        # config and backend produced this number — same block serve-bench rows
        # and BENCH_TRACE.json curves carry.
        "provenance": _provenance(cfg),
    }
    if acc.compile_cache.capture:
        from accelerate_tpu.analysis.program import audit_summaries

        summaries = audit_summaries(acc.compile_cache.capture)
        out["program_audit"] = [
            {
                "label": s["label"],
                # Compiled view when it exists ({} = compiled, genuinely no
                # comms); jaxpr view only for lower-only captures.
                "collectives": (
                    s["collectives"]["compiled"]
                    if s["collectives"]["compiled"] is not None
                    else s["collectives"]["jaxpr"]
                ),
                "collective_bytes": s["collectives"]["total_bytes"],
                "donation": s["donation"],
                "memory": s["memory"],
            }
            for s in summaries
        ]
        # graftmem estimate vs allocator ground truth: the worst
        # per-program static peak beside the runtime's measured peak, plus the
        # relative estimator error — bench_diff bands the error so the static
        # model can't silently rot while TPU rows keep both columns honest.
        # (CPU has no allocator ledger; measured columns are absent there.)
        from accelerate_tpu.telemetry import device_memory_stats

        out["hbm_peak_estimated_bytes"] = max(
            (s["memory"]["peak_bytes"] for s in summaries), default=0
        )
        measured_peak = device_memory_stats().get("peak_bytes_in_use")
        if measured_peak and out["hbm_peak_estimated_bytes"]:
            out["hbm_peak_measured_bytes"] = int(measured_peak)
            out["hbm_estimate_rel_error"] = round(
                abs(out["hbm_peak_estimated_bytes"] - measured_peak) / measured_peak, 4
            )
    if ceiling is not None:
        out["matmul_peak_measured_tflops"] = round(ceiling, 1)
        out["mfu_of_measured_peak"] = round(tflops / ceiling, 4)
    out["bench_rev"] = _BENCH_REV
    # The library detector owns the warm-until-steady semantics; stamp its revision so
    # a telemetry-methodology bump is visible in every row independently of bench_rev.
    out["telemetry_rev"] = TELEMETRY_REV
    if settle is not None:
        out["warmup_rounds_detected"] = settle.warmup_steps_detected
        if settle.capped:
            out["warmup_capped"] = True  # never settled within the cap: label, don't hide
    print(json.dumps(out))


def _metric_label(B: int, S: int, fuse: int, preset: str | None, cfg) -> str:
    """Label encodes the actual benchmarked config (env overrides included) so rows of
    different configurations stay distinguishable."""
    import os

    if preset:
        return f"train_mfu [{preset} preset — not a perf number]"
    remat = f"remat-{cfg.remat_policy}" if cfg.remat else "noremat"
    # fused_adamw is the identical AdamW update as a Pallas kernel — same workload, same
    # metric series, so it keeps the default label.
    opt = os.environ.get("BENCH_OPT", "adamw")
    opt_tag = "" if opt in ("adamw", "fused_adamw") else f" {opt}"
    accum = os.environ.get("BENCH_ACCUM", "1")
    accum_tag = "" if accum == "1" else f" accum{accum}"  # workload change: labeled
    return (
        f"train_mfu (llama-0.9B b{B} seq{S} bf16 {cfg.attn_impl} {remat} fused{fuse}"
        f"{opt_tag}{accum_tag})"
    )


def _provenance(cfg=None) -> dict:
    """The shared provenance block (git commit + config fingerprint + backend),
    from the ONE implementation serve-bench and the trace curves use."""
    from accelerate_tpu.telemetry.provenance import provenance_stamp

    return provenance_stamp(cfg)


def _run_trace_curves_row() -> int:
    """SLO-attainment-vs-offered-load artifact (``BENCH_TRACE=1``): one
    ``run_trace_curves`` sweep (bursty Poisson + adversarial tenant-flood
    generators × every gateway policy × the load ladder) written to
    ``BENCH_TRACE.json`` (override with ``BENCH_TRACE_OUT``); every curve is
    stamped with the workload-trace hash and run provenance."""
    _os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    from accelerate_tpu.commands.serve_bench import run_trace_curves

    artifact = run_trace_curves(
        requests=int(_os.environ.get("BENCH_TRACE_REQUESTS", "64")),
        max_slots=int(_os.environ.get("BENCH_TRACE_SLOTS", "4")),
        seed=int(_os.environ.get("BENCH_TRACE_SEED", "0")),
    )
    out = _os.environ.get("BENCH_TRACE_OUT", "BENCH_TRACE.json")
    with open(out, "w") as f:
        json.dump(artifact, f, indent=2)
    for curve in artifact["curves"]:
        print(json.dumps({
            "metric": f"serve_trace/{curve['generator']}/{curve['policy']}",
            "workload_trace_hash": curve["workload_trace_hash"],
            "loads": artifact["loads"],
            "attainment": [p["attainment"] for p in curve["points"]],
            "attainment_high": [p["attainment_high"] for p in curve["points"]],
        }))
    return 0


def _run_serving_rows(preset: str | None) -> int:
    """Serving-tier SLO rows (``BENCH_SERVE=1``): replay the serve-bench synthetic
    overload once per gateway policy and print one JSON row each — the SAME
    percentile blocks ``accelerate-tpu serve-bench`` stamps (ttft/tpot/queue_wait
    p50/p95/p99, admission accounting), from the one shared implementation
    (``commands.serve_bench.run_serve_bench``). The smoke preset pins the CPU
    backend exactly like the training smoke row does."""
    if (preset or "smoke") == "smoke":
        _os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")
    from accelerate_tpu.commands.serve_bench import run_serve_bench
    from accelerate_tpu.telemetry import MetricsPlane, Telemetry
    from accelerate_tpu.utils.dataclasses import TelemetryConfig

    # Live metrics plane over the whole serving bench: every row additionally
    # stamps the plane's end-of-bench snapshot (the ISSUE-13 surface) so a
    # bench artifact carries the same aggregates a live scrape would. The
    # default 300 s window covers the whole smoke bench on the wall clock, so
    # the derived rates (tokens/s) are real recent-rates, not totals divided
    # by an absurd horizon.
    tel = Telemetry(TelemetryConfig(enabled=True, compile_events=False,
                                    memory_stats=False))
    plane = MetricsPlane(tel)
    rows = run_serve_bench(
        telemetry=tel,
        preset=preset or "smoke",
        requests=int(_os.environ.get("BENCH_SERVE_REQUESTS", "48")),
        max_slots=int(_os.environ.get("BENCH_SERVE_SLOTS", "4")),
        max_len=int(_os.environ.get("BENCH_SERVE_LEN", "128")),
        max_new=int(_os.environ.get("BENCH_SERVE_NEW", "16")),
        overload=float(_os.environ.get("BENCH_SERVE_OVERLOAD", "4.0")),
        # Speculative rows: BENCH_SERVE_SPEC_K=3 re-runs every policy with batched
        # speculative decoding (output-identical; rows stamp spec_accept_rate and
        # tokens_per_step). Drafter: ngram (default) / half / oracle.
        spec_k=int(_os.environ.get("BENCH_SERVE_SPEC_K", "0")),
        spec_draft=_os.environ.get("BENCH_SERVE_DRAFTER", "ngram"),
        workload=_os.environ.get("BENCH_SERVE_WORKLOAD", "mixed"),
        # Paged-KV rows: BENCH_SERVE_PAGE_SIZE=16 re-runs every policy on the
        # paged engine (token-identical; rows stamp page-pool occupancy,
        # kv_bytes_per_request and max_concurrent_at_fixed_mem).
        page_size=int(_os.environ.get("BENCH_SERVE_PAGE_SIZE", "0")),
        # Multi-step rows: BENCH_SERVE_DECODE_STEPS=4 re-runs every policy with
        # the fused N-step decode super-step (bitwise-identical output by
        # construction — tests/test_multistep_decode.py).
        decode_steps=int(_os.environ.get("BENCH_SERVE_DECODE_STEPS", "1")),
        kv_pages=(int(_os.environ["BENCH_SERVE_KV_PAGES"])
                  if _os.environ.get("BENCH_SERVE_KV_PAGES") else None),
    )
    snapshot = plane.snapshot_record()
    for row in rows:
        row["metrics_snapshot"] = snapshot
        print(json.dumps(row))
    return 0


def _run_paged_compare_row() -> int:
    """Fixed-KV-budget dense-vs-paged artifact (``BENCH_PAGED=1``): one
    ``run_paged_compare`` pass written to ``BENCH_PAGED.json`` (override with
    ``BENCH_PAGED_OUT``) — max concurrency at fixed memory, decode tokens/s at
    high occupancy, per-request KV bytes, prefix-hit memory cost."""
    _os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    from accelerate_tpu.commands.serve_bench import run_paged_compare

    artifact = run_paged_compare(
        requests=int(_os.environ.get("BENCH_PAGED_REQUESTS", "48")),
        page_size=int(_os.environ.get("BENCH_PAGED_PAGE_SIZE", "16")),
        budget_rows=int(_os.environ.get("BENCH_PAGED_BUDGET_ROWS", "2")),
    )
    out = _os.environ.get("BENCH_PAGED_OUT", "BENCH_PAGED.json")
    with open(out, "w") as f:
        json.dump(artifact, f, indent=2)
    for row in artifact["rows"]:
        print(json.dumps(row))
    print(json.dumps({
        "metric": "serve/paged_compare",
        "concurrency_ratio": artifact["concurrency_ratio"],
        "prefix_memory_ratio": artifact["prefix_memory_ratio"],
        "kv_budget_bytes": artifact["kv_budget_bytes"],
    }))
    return 0


def _run_multistep_row() -> int:
    """Multi-step decode sweep artifact (``BENCH_MULTISTEP=1``): one
    ``run_multistep_bench`` pass — the N=1 baseline vs fused super-steps at
    high occupancy, decode-only tokens/s + host-share columns per depth —
    written to ``BENCH_MULTISTEP.json`` (override with ``BENCH_MULTISTEP_OUT``).
    Non-zero when any row's token streams differ from the N=1 baseline (the
    bitwise parity gate)."""
    _os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    from accelerate_tpu.commands.serve_bench import run_multistep_bench

    steps = tuple(int(n) for n in
                  _os.environ.get("BENCH_MULTISTEP_STEPS", "1,2,4,8").split(","))
    artifact = run_multistep_bench(
        requests=int(_os.environ.get("BENCH_MULTISTEP_REQUESTS", "32")),
        max_slots=int(_os.environ.get("BENCH_MULTISTEP_SLOTS", "8")),
        max_new=int(_os.environ.get("BENCH_MULTISTEP_NEW", "32")),
        page_size=int(_os.environ.get("BENCH_MULTISTEP_PAGE_SIZE", "0")),
        decode_steps=steps,
        seed=int(_os.environ.get("BENCH_MULTISTEP_SEED", "0")),
    )
    out = _os.environ.get("BENCH_MULTISTEP_OUT", "BENCH_MULTISTEP.json")
    with open(out, "w") as f:
        json.dump(artifact, f, indent=2)
    for row in artifact["rows"]:
        print(json.dumps({k: row[k] for k in row if k != "provenance"}))
    print(json.dumps({
        "metric": "serve/multistep",
        "decode_speedup_best": artifact["decode_speedup_best"],
        "best_decode_steps": artifact["best_decode_steps"],
        "host_share_n1": artifact["host_share_n1"],
        "host_share_best": artifact["host_share_best"],
        "all_identical": artifact["all_identical"],
    }))
    return 0 if artifact["all_identical"] else 1


def _run_elastic_row() -> int:
    """Elastic MPMD training chaos artifact (``BENCH_ELASTIC=1``): one
    ``run_chaos_train`` pass — clean vs crash-injected gang-of-gangs training
    on the CPU 2-process-mesh simulation — written to ``BENCH_ELASTIC.json``
    (override with ``BENCH_ELASTIC_OUT``). Non-zero when any invariant (zero
    lost/double-applied steps, bitwise recovery, budgeted restarts) fails."""
    _os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    from accelerate_tpu.commands.chaos_train import run_chaos_train
    from accelerate_tpu.telemetry import MetricsPlane, Telemetry
    from accelerate_tpu.utils.dataclasses import TelemetryConfig

    # Metrics plane over the chaos-train record stream: the artifact stamps
    # the live-aggregate snapshot (MPMD stage-step latency windows, DCN bytes,
    # per-gang restart budgets) beside the post-hoc invariants. Default
    # window: the run fits inside it on the wall clock.
    tel = Telemetry(TelemetryConfig(enabled=True, compile_events=False,
                                    memory_stats=False))
    plane = MetricsPlane(tel)
    artifact = run_chaos_train(
        steps=int(_os.environ.get("BENCH_ELASTIC_STEPS", "24")),
        stages=int(_os.environ.get("BENCH_ELASTIC_STAGES", "2")),
        crash_rate=float(_os.environ.get("BENCH_ELASTIC_CRASH_RATE", "0.12")),
        seed=int(_os.environ.get("BENCH_ELASTIC_SEED", "0")),
        telemetry=tel,
    )
    artifact["metrics_snapshot"] = plane.snapshot_record()
    out = _os.environ.get("BENCH_ELASTIC_OUT", "BENCH_ELASTIC.json")
    with open(out, "w") as f:
        json.dump(artifact, f, indent=2)
    print(json.dumps({
        "metric": "train/elastic_chaos",
        "stage_crashes": artifact["chaos"]["stage_crashes"],
        "replayed_steps": artifact["chaos"]["replayed_steps"],
        "restarts_by_gang": artifact["supervisor"]["restarts_by_gang"],
        "invariants": artifact["invariants"],
    }))
    return 0 if all(artifact["invariants"].values()) else 1


def _run_disagg_row() -> int:
    """Disaggregated prefill/decode artifact (``BENCH_DISAGG=1``): one
    ``run_disagg_bench`` pass — P prefill + D decode replicas behind the
    DisaggRouter vs a same-chip mixed fleet at sustained overload, plus the
    chaos arm — written to ``BENCH_DISAGG.json`` (override with
    ``BENCH_DISAGG_OUT``). Non-zero when any invariant fails (zero silent
    losses, byte-identical streams, decode-stall/TTFT improvement)."""
    _os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    from accelerate_tpu.commands.serve_bench import run_disagg_bench

    artifact = run_disagg_bench(
        prefill_replicas=int(_os.environ.get("BENCH_DISAGG_PREFILL", "1")),
        decode_replicas=int(_os.environ.get("BENCH_DISAGG_DECODE", "2")),
        requests=int(_os.environ.get("BENCH_DISAGG_REQUESTS", "48")),
        max_slots=int(_os.environ.get("BENCH_DISAGG_SLOTS", "4")),
        load=float(_os.environ.get("BENCH_DISAGG_LOAD", "2.0")),
        seed=int(_os.environ.get("BENCH_DISAGG_SEED", "0")),
    )
    out = _os.environ.get("BENCH_DISAGG_OUT", "BENCH_DISAGG.json")
    with open(out, "w") as f:
        json.dump(artifact, f, indent=2)
    print(json.dumps({
        "metric": "serve/disagg",
        "decode_stall_share_mixed": artifact["decode_stall_share_mixed"],
        "decode_stall_share_disagg": artifact["decode_stall_share_disagg"],
        "ttft_p95_ratio_vs_mixed": artifact["ttft_p95_ratio_vs_mixed"],
        "handoffs": artifact["disagg"]["handoffs"],
        "streams_identical_vs_mixed": artifact["streams_identical_vs_mixed"],
        "chaos_streams_identical": artifact["chaos_streams_identical"],
    }))
    ok = (artifact["stall_improved"] and artifact["ttft_p95_improved"]
          and artifact["streams_identical_vs_mixed"]
          and artifact["chaos_streams_identical"]
          and not artifact["disagg"]["silently_lost"]
          and not artifact["disagg_chaos"]["silently_lost"])
    return 0 if ok else 1


def main():
    import os

    from accelerate_tpu.utils.environment import place_compile_cache

    place_compile_cache()

    preset = os.environ.get("BENCH_PRESET")
    if os.environ.get("BENCH_ELASTIC"):
        return _run_elastic_row()
    if os.environ.get("BENCH_TRACE"):
        return _run_trace_curves_row()
    if os.environ.get("BENCH_DISAGG"):
        return _run_disagg_row()
    if os.environ.get("BENCH_PAGED"):
        return _run_paged_compare_row()
    if os.environ.get("BENCH_MULTISTEP"):
        return _run_multistep_row()
    if os.environ.get("BENCH_SERVE"):
        # Serving rows are a separate, self-contained mode: no train state — the
        # gateway drains deterministically or raises.
        return _run_serving_rows(preset)
    B = int(os.environ.get("BENCH_B", "4"))
    S = int(os.environ.get("BENCH_S", "2048"))
    fuse = int(os.environ.get("BENCH_FUSE", "4"))

    import jax

    if preset == "smoke":
        # The smoke preset is a CI/CPU logic check by definition.
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() == "cpu":
        print("bench: refusing a non-smoke run on the cpu backend — an MFU is a share "
              "of a chip's peak (BENCH_PRESET=smoke is the CPU logic check)",
              file=sys.stderr)
        return 2
    run(B, S, fuse, preset)
    return 0


if __name__ == "__main__":
    sys.exit(main())
