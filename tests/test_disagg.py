"""Disaggregated prefill/decode serving (serving_gateway/disagg.py, ISSUE 12).

Acceptance pins: cross-engine adoption parity — the disagg fleet's output is
token-for-token the mixed baseline's (greedy AND sampled, spec_k>0 and chunked
prefill included); handoff refcount conservation (pools drain to exactly zero
pages in use after every run — the soak harness in test_paged_kv.py covers the
randomized lifecycle); a dead prefill replica re-prefills on a peer and a dead
decode replica RE-ADOPTS from the still-refcounted source pages, streams
byte-identical either way with zero silent losses; the role-aware admission
cost prices a decode admission at adopted-pages + budget (a prompt-only
prefill pool no longer causes spurious ``kv_budget`` rejects); and the
``serving.handoff/v1`` record + ``handoff`` trace span validate and land in
trace-report's critical path.
"""

import dataclasses
import json
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from accelerate_tpu.models import llama
from accelerate_tpu.generation import GenerationConfig
from accelerate_tpu.serving import ContinuousBatcher
from accelerate_tpu.serving_gateway import DisaggRouter, FleetRouter
from accelerate_tpu.utils.dataclasses import GatewayConfig

CFG = dataclasses.replace(llama.CONFIGS["tiny"], dtype=jnp.float32)


@pytest.fixture(scope="module")
def setup():
    params = llama.init_params(CFG)
    rng = np.random.default_rng(0)
    # mixed lengths, one multi-chunk prompt (21 > prompt_bucket=16)
    prompts = [rng.integers(1, CFG.vocab_size, int(n)).astype(np.int32)
               for n in (5, 9, 3, 21, 7, 4)]
    return params, prompts


def make_engine(params, role="mixed", **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("prompt_bucket", 16)
    kw.setdefault("page_size", 8)
    return ContinuousBatcher(params, CFG, role=role, **kw)


def make_disagg(params, roles=("prefill", "decode"), telemetry=None,
                tracer=None, factory=False, plans=None, engine_kw=None,
                **cfg_kwargs):
    cfg_kwargs.setdefault("enabled", True)
    engine_kw = engine_kw or {}

    def build(rid, role):
        per = dict(engine_kw.get(role, {}))
        if plans is not None:
            per["faults"] = plans[rid]
        return make_engine(params, role=role, **per)

    engines = [build(rid, role) for rid, role in enumerate(roles)]
    return DisaggRouter(
        engines, GatewayConfig(**cfg_kwargs), telemetry=telemetry,
        tracer=tracer, roles=list(roles),
        engine_factory=(lambda rid, role: build(rid, role)) if factory else None,
    )


def drain(router, max_steps=600):
    out = []
    steps = 0
    while router.queue_depth or router.running_count:
        out.extend(router.step())
        steps += 1
        assert steps < max_steps, "disagg router stalled"
    return out


def baseline(params, prompts, max_new=6, gens=None, rngs=None):
    eng = make_engine(params)
    for i, p in enumerate(prompts):
        eng.submit(p, gen=gens[i] if gens else None,
                   max_new_tokens=None if gens else max_new,
                   rng=rngs[i] if rngs else None)
    return {tuple(r.prompt.tolist()): list(r.tokens) for r in eng.run()}


def assert_pools_clean(router):
    """Handoff refcount conservation, end-to-end: every pool fully free and
    no live handoff record remains once the workload drains."""
    assert not router._live_handoffs and not router._handoffs
    for rep in router.replicas:
        if getattr(rep.engine, "crashed", False):
            continue  # dead pool died with its engine
        ms = rep.engine.block_mgr.stats()
        assert ms["pages_in_use"] == 0, (rep.rid, ms)


# ------------------------------------------------------------------ validation
def test_role_validation(setup):
    params, _ = setup
    with pytest.raises(ValueError, match="role"):
        make_engine(params, role="oracle")
    with pytest.raises(ValueError, match="paged"):
        make_engine(params, role="prefill", page_size=0)
    with pytest.raises(ValueError, match="spec_k"):
        make_engine(params, role="prefill", spec_k=2)
    with pytest.raises(ValueError, match="prefix_cache"):
        make_engine(params, role="decode", prefix_cache=2)
    with pytest.raises(RuntimeError, match="adopt_handoff"):
        make_engine(params, role="decode").submit(np.array([1, 2], np.int32),
                                                  max_new_tokens=4)
    with pytest.raises(ValueError, match="prefill-capable"):
        DisaggRouter([make_engine(params, role="decode")],
                     GatewayConfig(enabled=True), roles=["decode"])
    with pytest.raises(ValueError, match="preempt"):
        make_disagg(setup[0], preempt=True, max_retries=1)
    with pytest.raises(ValueError, match="replica_roles"):
        GatewayConfig(enabled=True, replica_roles="prefill,oracle")


# --------------------------------------------------------------------- parity
def test_disagg_parity_greedy_incl_chunked(setup):
    params, prompts = setup
    refs = baseline(params, prompts, max_new=6)
    router = make_disagg(params)
    greqs = [router.submit(p, max_new_tokens=6) for p in prompts]
    drain(router)
    for g in greqs:
        assert g.status == "done", (g.uid, g.status, g.reason)
        assert g.tokens == refs[tuple(g.prompt.tolist())]
    # every request decoded via a handoff (budget > 1, so none finished at
    # the prefill replica)
    assert router.counters["handoffs"] == len(prompts)
    assert_pools_clean(router)


def test_disagg_parity_sampled(setup):
    params, prompts = setup
    gens = [GenerationConfig(max_new_tokens=6, temperature=0.8, top_p=0.9)
            for _ in prompts]
    rngs = [jax.random.PRNGKey(100 + i) for i in range(len(prompts))]
    refs = baseline(params, prompts, gens=gens, rngs=rngs)
    router = make_disagg(params)
    greqs = [router.submit(p, gen=gens[i], rng=rngs[i])
             for i, p in enumerate(prompts)]
    drain(router)
    for g in greqs:
        assert g.status == "done"
        # The emission-indexed key schedule survives the handoff: emission 0
        # drew on the prefill replica, 1.. on the decode replica.
        assert g.tokens == refs[tuple(g.prompt.tolist())]
    assert_pools_clean(router)


def test_disagg_parity_spec_decode(setup):
    params, prompts = setup
    refs = baseline(params, prompts, max_new=6)
    router = make_disagg(params, engine_kw={"decode": {"spec_k": 2}})
    greqs = [router.submit(p, max_new_tokens=6) for p in prompts]
    drain(router)
    for g in greqs:
        assert g.status == "done"
        assert g.tokens == refs[tuple(g.prompt.tolist())]
    dec = router.replicas[1].engine
    assert dec.spec_proposed > 0  # speculation really ran on the decode side
    assert_pools_clean(router)


def test_disagg_spec_model_drafter(setup):
    """A MODEL drafter on the decode replica: adoption mirrors the engine
    lane's left-padded layout onto the draft cache (one synthesized bucket
    plan — regression for the plan=None crash), and outputs stay the
    baseline's token for token."""
    from accelerate_tpu.compile_cache.warmup import build_drafter

    params, prompts = setup
    refs = baseline(params, prompts, max_new=6)
    drafter = build_drafter("half", params, CFG)
    router = make_disagg(
        params, engine_kw={"decode": {"spec_k": 2, "drafter": drafter}})
    greqs = [router.submit(p, max_new_tokens=6) for p in prompts]
    drain(router)
    for g in greqs:
        assert g.status == "done", (g.uid, g.status, g.reason)
        assert g.tokens == refs[tuple(g.prompt.tolist())]
    assert router.replicas[1].engine.spec_proposed > 0
    assert_pools_clean(router)


def test_disagg_mixed_replica_hybrid(setup):
    """A mixed replica in a disagg fleet serves BOTH phases locally; outputs
    stay the baseline's either way."""
    params, prompts = setup
    refs = baseline(params, prompts, max_new=6)
    router = make_disagg(params, roles=("prefill", "decode", "mixed"))
    greqs = [router.submit(p, max_new_tokens=6) for p in prompts]
    drain(router)
    for g in greqs:
        assert g.status == "done"
        assert g.tokens == refs[tuple(g.prompt.tolist())]
    assert_pools_clean(router)


# ------------------------------------------------------------------ admission
def test_kv_demand_role_pricing(setup):
    params, _ = setup
    mixed = make_engine(params)
    pre = make_engine(params, role="prefill")
    dec = make_engine(params, role="decode")
    # prompt 5 → one 16-wide chunk; budget 8.
    assert pre.kv_demand(5, 8) == 16       # context pages only (2 pages × 8)
    assert mixed.kv_demand(5, 8) == 24     # context + budget (3 pages)
    assert dec.kv_demand(5, 8) == 32       # adoption: context+budget+COW page


def test_prompt_only_prefill_pool_not_rejected(setup):
    """The disagg admission-cost fix: a prefill replica provisioned for
    CONTEXT pages only (4 pages = 32 tokens; prompt+budget would need more)
    must not produce spurious kv_budget rejects — the budget pages live on
    the decode replica."""
    params, prompts = setup
    refs = baseline(params, prompts[:4], max_new=6)
    # mixed pricing against this pool would raise for a 21-token prompt:
    # 2 chunks (32) + 6 budget → 5 pages > 4.
    tight = make_engine(params, role="prefill", kv_pages=4)
    with pytest.raises(Exception):
        # sanity: a MIXED engine with this pool rejects the same request
        make_engine(params, kv_pages=4).kv_demand(21, 6)
    router = DisaggRouter(
        [tight, make_engine(params, role="decode")],
        GatewayConfig(enabled=True), roles=["prefill", "decode"],
    )
    greqs = [router.submit(p, max_new_tokens=6) for p in prompts[:4]]
    drain(router)
    for g in greqs:
        assert g.status == "done", (g.uid, g.status, g.reason)
        assert g.tokens == refs[tuple(g.prompt.tolist())]
    assert_pools_clean(router)


def test_adoption_defers_on_decode_pool_pressure(setup):
    """A decode pool with room for ~one adoption at a time backpressures the
    handoff queue (FIFO defers) instead of failing or losing requests."""
    params, prompts = setup
    refs = baseline(params, prompts, max_new=6)
    router = make_disagg(params, engine_kw={"decode": {"kv_pages": 4}})
    greqs = [router.submit(p[:5], max_new_tokens=6) for p in prompts]
    drain(router)
    for g in greqs:
        assert g.status == "done"
    # pressure actually deferred adoptions — counted at the router, which
    # defers BEFORE paying the page-block transfer
    assert router.counters["handoff_defers"] > 0
    assert_pools_clean(router)


# ------------------------------------------------------------------ telemetry
def test_handoff_records_span_and_trace_report(setup):
    from accelerate_tpu.telemetry import Telemetry
    from accelerate_tpu.telemetry.schemas import (
        SERVING_HANDOFF_SCHEMA,
        FLEET_ROUTE_SCHEMA,
        TRACE_SPAN_SCHEMA,
        validate_record,
    )
    from accelerate_tpu.telemetry.tracing import Tracer
    from accelerate_tpu.utils.dataclasses import TelemetryConfig
    from accelerate_tpu.commands.trace_report import trace_report

    params, prompts = setup
    tel = Telemetry(TelemetryConfig(enabled=True, compile_events=False,
                                    memory_stats=False))
    tracer = Tracer(tel)
    router = make_disagg(params, telemetry=tel, tracer=tracer)
    greqs = [router.submit(p, max_new_tokens=6) for p in prompts]
    drain(router)
    assert all(g.status == "done" for g in greqs)

    handoffs = [r for r in tel.records
                if r.get("schema") == SERVING_HANDOFF_SCHEMA]
    assert len(handoffs) == router.counters["handoffs"] > 0
    assert all(validate_record(r) == [] for r in handoffs)
    assert all(r["src_replica"] == 0 and r["dst_replica"] == 1
               and r["nbytes"] > 0 and r["dur_s"] >= 0 for r in handoffs)
    routes = [r for r in tel.records if r.get("schema") == FLEET_ROUTE_SCHEMA]
    assert {"dispatch", "handoff"} <= {r["reason"] for r in routes}
    # transfer accounting matches the per-record stream
    assert router.transfer_stats.count == len(handoffs)
    assert router.transfer_stats.bytes == sum(r["nbytes"] for r in handoffs)

    spans = [r for r in tel.records if r.get("schema") == TRACE_SPAN_SCHEMA]
    handoff_spans = [s for s in spans if s["span"] == "handoff"]
    assert len(handoff_spans) == len(handoffs)
    report = trace_report(spans)
    assert "handoff_s" in report["breakdown"]
    assert "handoff_s" in report["critical_path_share"]
    # per-role stall split: every done trace here went through a handoff
    assert report["stall_by_role"]["n_requests"] == len(prompts)
    for t in report["traces"]:
        assert t["handoffs"] == 1 + 0  # exactly one handoff per request
        assert t["stall_prefill_s"] is not None
        assert t["stall_decode_s"] is not None


# ------------------------------------------------------------------- failover
def _stream_capture():
    streams = {}

    def cbs(i):
        streams[i] = []

        def on_token(tok, i=i):
            streams[i].append(int(tok))

        def on_retry(i=i):
            streams[i].clear()

        return on_token, on_retry

    return streams, cbs


def test_decode_replica_death_readopts_byte_identical(setup):
    """A dead decode replica's requests RE-ADOPT from the still-refcounted
    source pages (prefill never re-runs), streams byte-identical at zero
    preemption-retry-budget spend."""
    params, prompts = setup
    refs = baseline(params, prompts, max_new=8)
    streams, cbs = _stream_capture()
    router = make_disagg(params, roles=("prefill", "decode", "decode"),
                         factory=True, replica_restarts=2)
    greqs = []
    for i, p in enumerate(prompts):
        ot, orr = cbs(i)
        greqs.append(router.submit(p, max_new_tokens=8,
                                   on_token=ot, on_retry=orr))
    for _ in range(3):
        router.step()
    pre_admitted = router.replicas[0].engine.admitted
    router.kill(1)
    drain(router)
    for i, g in enumerate(greqs):
        assert g.status == "done", (g.uid, g.status, g.reason)
        assert streams[i] == refs[tuple(g.prompt.tolist())]
        assert g.retries_used == 0
    assert router.counters["readopted"] > 0
    # re-adoption never re-prefilled: the prefill replica's admission count
    # is untouched by the decode-side failover.
    assert router.replicas[0].engine.admitted == pre_admitted
    assert_pools_clean(router)


def test_prefill_replica_death_reprefills_zero_loss(setup):
    """A dead prefill replica (mid-handoff: exported records die with its
    pool) re-prefills on the restarted replica — zero silent losses, streams
    byte-identical."""
    params, prompts = setup
    refs = baseline(params, prompts, max_new=8)
    streams, cbs = _stream_capture()
    router = make_disagg(params, factory=True, replica_restarts=2)
    greqs = []
    for i, p in enumerate(prompts):
        ot, orr = cbs(i)
        greqs.append(router.submit(p, max_new_tokens=8,
                                   on_token=ot, on_retry=orr))
    router.step()  # prefills land, handoffs exported / some adopted
    router.kill(0)
    drain(router)
    for i, g in enumerate(greqs):
        assert g.status == "done", (g.uid, g.status, g.reason)
        assert streams[i] == refs[tuple(g.prompt.tolist())]
    assert router.counters["replica_restarts"] >= 1
    assert_pools_clean(router)


def test_injected_crash_faults_failover(setup):
    """The FaultPlan spelling of the same failovers: seeded crash clauses at
    serving.prefill and serving.decode kill replicas mid-trace; everything
    still terminates, streams byte-identical to the undisturbed baseline."""
    from accelerate_tpu.resilience.faults import FaultPlan, FaultSpec

    params, prompts = setup
    refs = baseline(params, prompts, max_new=8)
    plans = [
        FaultPlan([FaultSpec("serving.prefill", "crash", prob=0.2,
                             max_fires=1)], seed=11),
        FaultPlan([FaultSpec("serving.decode", "crash", prob=0.15,
                             max_fires=1)], seed=12),
        None,
    ]
    streams, cbs = _stream_capture()
    router = make_disagg(params, roles=("prefill", "decode", "decode"),
                         factory=True, plans=plans, replica_restarts=3)
    greqs = []
    for i, p in enumerate(prompts):
        ot, orr = cbs(i)
        greqs.append(router.submit(p, max_new_tokens=8,
                                   on_token=ot, on_retry=orr))
    drain(router)
    fired = sum(len(p.fired) for p in plans if p is not None)
    assert fired >= 1, "no fault fired — tune seeds"
    for i, g in enumerate(greqs):
        assert g.status == "done", (g.uid, g.status, g.reason)
        assert streams[i] == refs[tuple(g.prompt.tolist())]


def test_cancel_in_handoff_limbo(setup):
    """A request cancelled between export and adoption releases its handoff
    record (source pages free) and finalizes with the streamed prefix."""
    params, prompts = setup
    # 2 decode lanes, 5 long-budget requests: by the second step both decode
    # lanes are held and freshly exported handoffs sit in limbo.
    router = make_disagg(params)
    greqs = [router.submit(p, max_new_tokens=8) for p in prompts[:5]]
    router.step()
    router.step()
    limbo = [g for g in greqs
             if g.status == "running" and g._rid is None
             and g.uid in router._live_handoffs]
    assert limbo, "no request in handoff limbo — geometry drifted"
    victim = limbo[0]
    assert router.cancel(victim.uid)
    assert victim.status == "cancelled" and victim.reason == "cancelled_handoff"
    assert len(victim.tokens) == 1  # the prefill's first token was streamed
    drain(router)
    assert_pools_clean(router)


# ------------------------------------------------------------------ CI surface
def test_decode_only_warm_surface():
    """The decode-role program surface is DECODE-ONLY: warming it produces no
    prefill/insert program, and the prefill-role surface has no decode."""
    from accelerate_tpu.analysis.program import LowerOnlyCache
    from accelerate_tpu.compile_cache.warmup import run_warmup

    cache = LowerOnlyCache()
    manifest = run_warmup(cache=cache, emit_manifest=False, preset="smoke",
                          batch_size=4, seq_len=128, train=False,
                          eval_step=False, serve=True, max_slots=2,
                          max_new_tokens=16, page_size=8, role="decode")
    labels = {c.label for c in cache.capture}
    assert manifest["role"] == "decode"
    assert {"serving.decode_multi_paged", "serving.import_pages",
            "serving.copy_page", "serving.lane_valid"} <= labels, labels
    assert not any("prefill" in l or "insert" in l for l in labels), labels

    cache2 = LowerOnlyCache()
    run_warmup(cache=cache2, emit_manifest=False, preset="smoke",
               batch_size=4, seq_len=128, train=False, eval_step=False,
               serve=True, max_slots=2, max_new_tokens=16, page_size=8,
               role="prefill")
    labels2 = {c.label for c in cache2.capture}
    assert {"serving.export_pages", "serving.insert_paged"} <= labels2, labels2
    assert any(l.startswith("serving.prefill") for l in labels2), labels2
    assert not any("decode" in l or "verify" in l for l in labels2), labels2


def test_accelerator_builder_roles(setup):
    from accelerate_tpu import Accelerator

    params, prompts = setup
    acc = Accelerator(gateway_config=GatewayConfig(
        enabled=True, replica_roles="prefill,decode"))
    router = acc.build_serving_gateway(
        [make_engine(params, role="prefill"),
         make_engine(params, role="decode")])
    assert isinstance(router, DisaggRouter)
    g = router.submit(prompts[0], max_new_tokens=4)
    drain(router)
    assert g.status == "done"


def test_disagg_bench_cli_smoke(tmp_path):
    """Tier-1: the serve-bench --disagg proof runs end to end — zero
    silently-lost requests, disagg streams byte-identical to the mixed
    baseline (clean AND chaos arms), handoffs actually happened."""
    out = tmp_path / "BENCH_DISAGG.json"
    proc = subprocess.run(
        [sys.executable, "-m", "accelerate_tpu", "serve-bench",
         "--disagg", "1:1", "--smoke", "--disagg-out", str(out)],
        capture_output=True, text=True, timeout=900,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    artifact = json.loads(out.read_text())
    assert artifact["schema"] == "accelerate_tpu.bench.disagg/v1"
    assert artifact["streams_identical_vs_mixed"]
    assert artifact["chaos_streams_identical"]
    assert artifact["disagg"]["silently_lost"] == 0
    assert artifact["disagg_chaos"]["silently_lost"] == 0
    assert artifact["disagg"]["handoffs"] > 0
    assert artifact["disagg"]["handoff_transfer"]["transfer_bytes"] > 0
    assert artifact["mixed"]["decode_stall_share"] is not None
