"""Paged-vs-dense engine parity (ISSUE 7): token-for-token identical outputs.

f32 fixtures throughout (the PR-4 bf16-tie lesson: exactness contracts are defined
at f32, where the CPU gather fallback is BITWISE the dense path). Every suite runs
the same workload through a dense engine and a paged one and asserts identical
token streams — greedy, sampled, speculative, chunked prefill, prefix-cache hits,
and the evict/cancel/lane-reuse edges — plus the paged-only behaviors: pool
exhaustion defers admission (FIFO, no starvation), COW on prefix divergence,
page-priced gateway admission with the ``kv_budget`` reject reason, and the
``serving.kv/v1`` telemetry record.
"""

import dataclasses
import json

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from accelerate_tpu.generation import GenerationConfig
from accelerate_tpu.models import llama
from accelerate_tpu.serving import ContinuousBatcher, KVBudgetError

CFG = dataclasses.replace(llama.CONFIGS["tiny"], dtype=jnp.float32)


@pytest.fixture(scope="module")
def setup():
    params = llama.init_params(CFG)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, CFG.vocab_size, int(n)).astype(np.int32)
               for n in (5, 9, 3, 7, 6, 4)]
    return params, prompts


def _run_pair(params, submits, dense_kw=None, paged_kw=None, steps=None):
    """Run the same submit list through a dense and a paged engine → token lists."""
    outs = []
    for kw in (dense_kw or {}, {"page_size": 8, **(paged_kw or {})}):
        eng = ContinuousBatcher(params, CFG, max_slots=2, max_len=64,
                                prompt_bucket=16, **kw)
        reqs = [eng.submit(*a, **k) for a, k in submits]
        eng.run()
        outs.append(([r.tokens for r in reqs], eng))
    (dense_tokens, dense_eng), (paged_tokens, paged_eng) = outs
    return dense_tokens, paged_tokens, dense_eng, paged_eng


def test_greedy_parity(setup):
    params, prompts = setup
    submits = [((p,), dict(max_new_tokens=n))
               for p, n in zip(prompts, (6, 4, 8, 3, 5, 7))]
    dense, paged, _, ep = _run_pair(params, submits)
    assert dense == paged
    s = ep.stats()
    assert s["paged"] and s["kv_alloc_count"] > 0
    assert s["pages_in_use"] == 0  # everything released after drain
    assert s["kv_free_count"] == s["kv_alloc_count"]


def test_sampled_parity(setup):
    """Sampled lanes too: same per-request key schedule → bitwise-equal draws on
    the CPU gather path (identical logits in, identical sampler out)."""
    params, prompts = setup
    gen = GenerationConfig(max_new_tokens=6, temperature=0.8, top_k=12)
    submits = [((p,), dict(gen=gen, rng=jax.random.PRNGKey(s)))
               for p, s in zip(prompts[:3], (11, 22, 33))]
    dense, paged, _, _ = _run_pair(params, submits)
    assert dense == paged


def test_spec_parity(setup):
    """spec_k > 0: the paged fused verify accepts the same prefixes (greedy AND
    sampled lanes), token-for-token the dense spec engine — which is itself
    token-for-token spec_k=0 (tests/test_serving_spec.py)."""
    params, prompts = setup
    gen = GenerationConfig(max_new_tokens=6, temperature=0.7, top_k=8)
    submits = (
        [((p,), dict(max_new_tokens=7)) for p in prompts[:3]]
        + [((prompts[3],), dict(gen=gen, rng=jax.random.PRNGKey(5)))]
    )
    dense, paged, ed, ep = _run_pair(
        params, submits, dense_kw={"spec_k": 2}, paged_kw={"spec_k": 2})
    assert dense == paged
    assert ep.stats()["spec_accept_rate"] == ed.stats()["spec_accept_rate"]


def test_chunked_prefill_parity(setup):
    """A prompt longer than every bucket takes the chunked prefill path; the paged
    scatter must land all chunks' pages correctly."""
    params, _ = setup
    rng = np.random.default_rng(7)
    long_prompt = rng.integers(1, CFG.vocab_size, 40).astype(np.int32)  # 3 chunks
    submits = [((long_prompt,), dict(max_new_tokens=8))]
    dense, paged, _, _ = _run_pair(params, submits)
    assert dense == paged


def test_evict_cancel_lane_reuse_parity(setup):
    """Cancel a queued request, evict an in-flight one; the freed lane (and its
    PAGES) must serve the next request with identical output."""
    params, prompts = setup

    def run(page_size):
        eng = ContinuousBatcher(params, CFG, max_slots=1, max_len=64,
                                prompt_bucket=16, page_size=page_size)
        r0 = eng.submit(prompts[0], max_new_tokens=10)
        r1 = eng.submit(prompts[1], max_new_tokens=4)   # queued behind r0
        r2 = eng.submit(prompts[2], max_new_tokens=5)
        eng.step(); eng.step()
        assert eng.cancel(r1.uid)        # still queued
        assert eng.evict_slot(r0.uid)    # in flight — lane + pages free NOW
        eng.run()
        return r0, r1, r2, eng

    d0, d1, d2, de = run(0)
    p0, p1, p2, pe = run(8)
    assert (d0.tokens, d1.tokens, d2.tokens) == (p0.tokens, p1.tokens, p2.tokens)
    assert not p0.done and not p1.done and p2.done
    s = pe.stats()
    assert s["pages_in_use"] == 0, s  # eviction released the evicted lane's pages
    assert s["evicted_external"] == 1


def test_pool_exhaustion_defers_fifo(setup):
    """A pool too small for two concurrent requests serves them SEQUENTIALLY —
    admission defers (counted), output unchanged, nothing deadlocks."""
    params, prompts = setup
    # Each request: 16-token bucket + 8 budget → 3 pages of 8. Pool of 3 pages
    # holds exactly one request at a time.
    eng = ContinuousBatcher(params, CFG, max_slots=2, max_len=64,
                            prompt_bucket=16, page_size=8, kv_pages=3)
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts[:3]]
    eng.run()
    base = ContinuousBatcher(params, CFG, max_slots=2, max_len=64, prompt_bucket=16)
    want = [base.submit(p, max_new_tokens=8) for p in prompts[:3]]
    base.run()
    assert [r.tokens for r in reqs] == [r.tokens for r in want]
    s = eng.stats()
    assert s["kv_defer_count"] > 0
    assert s["peak_active_slots"] == 1  # memory held concurrency to 1 lane


def test_oversized_request_rejected_kv_budget(setup):
    """A request whose page demand exceeds the WHOLE pool raises KVBudgetError at
    submit (deferring it would deadlock the FIFO queue forever)."""
    params, prompts = setup
    eng = ContinuousBatcher(params, CFG, max_slots=2, max_len=64,
                            prompt_bucket=16, page_size=8, kv_pages=2)
    with pytest.raises(KVBudgetError):
        eng.submit(prompts[0], max_new_tokens=8)  # needs 3 pages > 2
    # KVBudgetError is a ValueError: existing callers that catch ValueError keep
    # refusing it gracefully.
    assert issubclass(KVBudgetError, ValueError)


def test_prefix_cache_parity_and_page_sharing(setup):
    """Shared system prompt with the prefix cache on: identical tokens, and the
    paged registry holds PAGES (refcounted, shared) instead of row snapshots."""
    params, _ = setup
    rng = np.random.default_rng(1)
    sys_prompt = rng.integers(1, CFG.vocab_size, 32).astype(np.int32)  # 2 chunks
    prompts = [np.concatenate([sys_prompt,
                               rng.integers(1, CFG.vocab_size, k).astype(np.int32)])
               for k in (5, 9, 3, 13)]

    def run(**kw):
        eng = ContinuousBatcher(params, CFG, max_slots=2, max_len=96,
                                prompt_bucket=16, prefix_cache=4, **kw)
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run()
        return [r.tokens for r in reqs], eng

    dense, ed = run()
    paged, ep = run(page_size=8)
    assert dense == paged
    sd, sp = ed.stats(), ep.stats()
    assert sp["prefix_hits"] == sd["prefix_hits"] > 0
    # After drain only registry references remain; nested entries share pages.
    assert sp["pages_in_use"] > 0
    assert sp["kv_shared_pages"] > 0
    assert sp["kv_adopt_count"] > 0
    assert sp["kv_cow_count"] == 0  # 16-token chunks align with 8-token pages


def test_prefix_cow_on_divergence(setup):
    """Page size NOT dividing the chunk width: the prefix boundary cuts a page
    mid-way, so registration copies the partial page and adoption re-materializes
    it — COW on divergence, identical tokens."""
    params, _ = setup
    rng = np.random.default_rng(2)
    sys_prompt = rng.integers(1, CFG.vocab_size, 16).astype(np.int32)  # 1 chunk
    prompts = [np.concatenate([sys_prompt,
                               rng.integers(1, CFG.vocab_size, k).astype(np.int32)])
               for k in (5, 9, 3)]

    def run(**kw):
        eng = ContinuousBatcher(params, CFG, max_slots=1, max_len=96,
                                prompt_bucket=16, prefix_cache=4, **kw)
        reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run()
        return [r.tokens for r in reqs], eng

    dense, _ = run()
    paged, ep = run(page_size=12)  # 16 % 12 != 0 → partial boundary page
    assert dense == paged
    s = ep.stats()
    assert s["kv_cow_count"] > 0, s
    assert s["prefix_hits"] > 0


def test_prefix_eviction_capacity_miss_observable(setup):
    """The small fix: LRU eviction counts, and a re-miss on an EVICTED key reports
    as a capacity miss, distinguishable from a cold key — in both layouts."""
    params, _ = setup
    rng = np.random.default_rng(3)
    a = rng.integers(1, CFG.vocab_size, 16).astype(np.int32)
    b = rng.integers(1, CFG.vocab_size, 16).astype(np.int32)
    for kw in ({}, {"page_size": 8}):
        eng = ContinuousBatcher(params, CFG, max_slots=1, max_len=64,
                                prompt_bucket=16, prefix_cache=1, **kw)
        eng.submit(np.concatenate([a, a[:3]]), max_new_tokens=2)
        eng.run()   # registers prefix a
        eng.submit(np.concatenate([b, b[:3]]), max_new_tokens=2)
        eng.run()   # cold miss on b; registering b evicts a
        s1 = eng.stats()
        assert s1["prefix_evictions"] == 1, s1
        assert s1["prefix_key_misses"] == 2, s1  # a and b were both cold once
        eng.submit(np.concatenate([a, a[:5]]), max_new_tokens=2)
        eng.run()   # a was evicted → CAPACITY miss, not a cold key
        s2 = eng.stats()
        assert s2["prefix_capacity_misses"] == 1, s2
        assert s2["prefix_key_misses"] == 2, s2


def test_registry_pages_reclaimed_under_pressure(setup):
    """Deadlock regression: with every lane drained, pages held ONLY by the
    prefix registry must yield to a new admission (LRU eviction under pool
    pressure) — otherwise deferral would wait forever on lanes that don't
    exist."""
    params, _ = setup
    rng = np.random.default_rng(4)
    a = rng.integers(1, CFG.vocab_size, 16).astype(np.int32)
    b = rng.integers(1, CFG.vocab_size, 16).astype(np.int32)
    # Pool: 4 pages of 8. A 16-token (one-chunk) prompt + 2 budget needs
    # ceil(18/8) = 3 pages; registering prefix a retains 2 pages after the lane
    # drains, leaving 2 free < 3 needed for prompt b.
    eng = ContinuousBatcher(params, CFG, max_slots=1, max_len=64,
                            prompt_bucket=16, page_size=8, kv_pages=4,
                            prefix_cache=4)
    eng.submit(a, max_new_tokens=2)
    eng.run()
    assert eng.stats()["pages_in_use"] > 0  # registry holds prefix-a pages
    req = eng.submit(b, max_new_tokens=2)
    eng.run()  # must terminate: registry yields, admission proceeds
    assert req.done
    s = eng.stats()
    assert s["prefix_evictions"] > 0, s


def test_paged_stats_and_bytes_accounting(setup):
    params, prompts = setup
    eng = ContinuousBatcher(params, CFG, max_slots=2, max_len=64,
                            prompt_bucket=16, page_size=8)
    req = eng.submit(prompts[0], max_new_tokens=8)
    eng.step()
    s = eng.stats()
    assert s["paged"] is True and s["page_size"] == 8
    assert s["pages_in_use"] == 3  # ceil((16 + 8) / 8)
    assert s["kv_bytes_in_use"] == 3 * s["kv_page_bytes"]
    assert s["kv_bytes_total"] == s["pages_total"] * s["kv_page_bytes"]
    assert 0 < s["page_occupancy"] <= 1
    # dense-equivalent pool by default: 2 slots × (64/8) pages
    assert s["pages_total"] == 16
    eng.run()
    assert req.done


def test_kv_demand_prices_pages_not_padded_width(setup):
    """kv_demand: dense charges padded width + budget for the max_len-row layout;
    paged charges actual pages — the gateway's admission numerator."""
    params, _ = setup
    dense = ContinuousBatcher(params, CFG, max_slots=2, max_len=64, prompt_bucket=16)
    paged = ContinuousBatcher(params, CFG, max_slots=2, max_len=64,
                              prompt_bucket=16, page_size=8)
    assert dense.kv_demand(5, 8) == 16 + 8
    assert paged.kv_demand(5, 8) == 24          # 3 pages × 8 — same here
    assert dense.kv_capacity_tokens() == 2 * 64
    assert paged.kv_capacity_tokens() == 16 * 8
    # page granularity shows when prompt+budget straddles a page boundary
    assert paged.kv_demand(16, 10) == 32        # ceil(26/8)=4 pages


def test_gateway_kv_budget_reject(setup):
    """Gateway on a paged engine: admission prices pages, and a request the pool
    can never hold is terminally rejected with the machine-readable kv_budget
    reason (not unservable, not an exception)."""
    from accelerate_tpu.serving_gateway import ServingGateway
    from accelerate_tpu.utils.dataclasses import GatewayConfig

    params, prompts = setup
    eng = ContinuousBatcher(params, CFG, max_slots=2, max_len=64,
                            prompt_bucket=16, page_size=8, kv_pages=3)
    gw = ServingGateway(eng, GatewayConfig(enabled=True, max_queued_tokens=64))
    big = gw.submit(prompts[0], max_new_tokens=16)  # 4 pages > 3-page pool
    assert big.status == "rejected" and big.reason.startswith("kv_budget")
    ok = gw.submit(prompts[1], max_new_tokens=8)    # 3 pages — admissible
    assert ok.status == "queued"
    assert ok.cost == 24  # page-granular: 3 pages × 8 tokens
    while gw.queue_depth or gw.running_count:
        gw.step()
    assert ok.status == "done"


def test_serving_kv_telemetry_record(setup, tmp_path):
    """Paged engines emit accelerate_tpu.telemetry.serving.kv/v1 per step with
    pool occupancy, bytes, sharing and churn counters."""
    from accelerate_tpu.telemetry import Telemetry
    from accelerate_tpu.utils.dataclasses import TelemetryConfig

    params, prompts = setup
    tel = Telemetry(TelemetryConfig(enabled=True, jsonl_dir=str(tmp_path)))
    eng = ContinuousBatcher(params, CFG, max_slots=2, max_len=64,
                            prompt_bucket=16, page_size=8, telemetry=tel)
    eng.submit(prompts[0], max_new_tokens=4)
    eng.run()
    tel.close()
    records = []
    for f in tmp_path.glob("*.jsonl"):
        with open(f) as fh:
            records += [json.loads(line) for line in fh if line.strip()]
    kv = [r for r in records
          if r.get("schema") == "accelerate_tpu.telemetry.serving.kv/v1"]
    assert kv, "no serving.kv/v1 records emitted"
    for key in ("page_size", "pages_total", "pages_in_use", "page_occupancy",
                "kv_bytes_in_use", "kv_bytes_total", "kv_shared_pages",
                "kv_alloc_count", "kv_free_count", "kv_cow_count",
                "kv_defer_count", "prefix_evictions"):
        assert key in kv[0], key


def test_serve_bench_paged_row_columns():
    """serve-bench paged rows stamp the KV-memory columns (page geometry,
    kv_bytes_per_request, max_concurrent_at_fixed_mem); dense rows stamp the
    dense equivalents — bench artifacts can diff layouts."""
    from accelerate_tpu.commands.serve_bench import run_serve_bench

    rows = run_serve_bench(
        policies=("fifo",), requests=6, max_slots=2, max_len=64,
        prompt_bucket=16, max_new=4, page_size=8,
    )
    row = rows[0]
    assert row["page_size"] == 8 and row["kv_pages"] == 16
    assert row["max_concurrent_at_fixed_mem"] >= 1
    assert row["kv_bytes_per_request"] > 0
    dense = run_serve_bench(
        policies=("fifo",), requests=6, max_slots=2, max_len=64,
        prompt_bucket=16, max_new=4,
    )[0]
    assert dense["page_size"] == 0 and dense["kv_pages"] is None
    assert dense["kv_bytes_per_request"] > row["kv_bytes_per_request"]


def test_paged_compare_artifact_shape():
    """The BENCH_PAGED.json generator: ≥2× concurrency at a fixed KV budget is
    the acceptance geometry — assert the artifact demonstrates it on the tiny CI
    shape (short requests against a 2-row budget)."""
    from accelerate_tpu.commands.serve_bench import run_paged_compare

    artifact = run_paged_compare(
        max_len=128, prompt_bucket=16, max_new=8, requests=12,
        budget_rows=1, page_size=16, max_slots=4, prefix_cache=2,
    )
    assert artifact["schema"] == "accelerate_tpu.bench.paged/v1"
    dense_row, paged_row = artifact["rows"]
    assert dense_row["layout"] == "dense" and paged_row["layout"] == "paged"
    assert dense_row["kv_budget_bytes"] == paged_row["kv_budget_bytes"]
    assert artifact["concurrency_ratio"] >= 2.0, artifact
    assert paged_row["kv_bytes_per_request"] < dense_row["kv_bytes_per_request"]
    assert paged_row["prefix_hit_memory_bytes"] < dense_row["prefix_hit_memory_bytes"]


def test_scan_layers_paged_parity(setup):
    """cfg.scan_layers stacks pool planes on a leading layer dim; the scatter /
    gather index paths differ, so pin parity there too."""
    params_scan = None
    cfg_scan = dataclasses.replace(CFG, scan_layers=True)
    params_scan = llama.init_params(cfg_scan)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, CFG.vocab_size, int(n)).astype(np.int32)
               for n in (5, 9)]

    def run(page_size):
        eng = ContinuousBatcher(params_scan, cfg_scan, max_slots=2, max_len=64,
                                prompt_bucket=16, page_size=page_size)
        reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run()
        return [r.tokens for r in reqs]

    assert run(0) == run(8)


def test_kv_quant_paged_parity(setup):
    """int8 pools: pages quantize with the same per-slot scales as the dense int8
    cache, so paged kv_quant decode equals dense kv_quant decode token-for-token."""
    cfg_q = dataclasses.replace(CFG, kv_quant=True)
    params = llama.init_params(cfg_q)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, CFG.vocab_size, int(n)).astype(np.int32)
               for n in (5, 9)]

    def run(page_size):
        eng = ContinuousBatcher(params, cfg_q, max_slots=2, max_len=64,
                                prompt_bucket=16, page_size=page_size)
        reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run()
        return [r.tokens for r in reqs]

    assert run(0) == run(8)


@pytest.mark.parametrize("prefix_cache", [0, 4], ids=["left_padded", "prefix_layout"])
def test_dispatch_span_counts_the_pages_the_kernel_walks(setup, prefix_cache, tmp_path):
    """``pages_live`` / ``pages_walked`` on ``atpu.engine.decode.dispatch`` come from the
    host's positions and recorded valid ranges; the kernel's wrapper derives its walk from
    the device's valid rows and tables. Before every multi-step dispatch the two agree,
    lane by lane, and inside a profiler session the span carries the sums."""
    from accelerate_tpu.ops.paged_attention import block_pages, walk_range
    from benchmarks.chipbench import program_spans

    params, prompts = setup
    cfg = dataclasses.replace(CFG, sliding_window=12)
    eng = ContinuousBatcher(params, cfg, max_slots=3, max_len=64, prompt_bucket=16,
                            page_size=8, decode_steps=4, prefix_cache=prefix_cache)
    for p, n in zip(prompts, (9, 6, 12, 5, 8, 7)):
        eng.submit(p, max_new_tokens=n)
    block = block_pages(8, cfg.n_kv_heads, cfg.head_dim, 4, eng.block_mgr.max_pages)
    kw = dict(T=1, window=12, page_size=8, block=block)
    seen, inner = [], eng._paged_walk

    def checked(active):
        got = inner(active)
        valid = np.asarray(eng.cache["valid"])[active]
        valid[np.arange(len(active)), eng.positions[active]] = True  # the step's own write
        C = valid.shape[1]
        last = C - 1 - valid[:, ::-1].argmax(1)
        allocated = (eng.block_mgr.tables[active] < eng.block_mgr.SENTINEL).sum(1) * 8 - 1
        _, blocks, pages = walk_range(eng.positions[active], valid.argmax(1),
                                      np.minimum(last, allocated), **kw)
        assert got == {"pages_live": int(pages.sum()),
                       "pages_walked": int(blocks.sum()) * block}
        seen.append(got)
        return got

    eng._paged_walk = checked
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    assert seen and all(0 < s["pages_live"] <= s["pages_walked"] for s in seen)
    spans = [s for s in program_spans.load(str(tmp_path)) if s.name == "engine.decode.dispatch"]
    assert [{k: int(s.attrs[k]) for k in ("pages_live", "pages_walked")} for s in spans] == seen


# The cache rides the layer scan's carry (ISSUE 29). Four layers, so that window_every=2
# scans two groups; a window shorter than the sequences, so that the banding matters.
_SCAN_VARIANTS = {
    "plain": dict(),
    "window_every2": dict(sliding_window=6, window_every=2),
    "kv_quant": dict(kv_quant=True),
    "window_every2_kv_quant": dict(sliding_window=6, window_every=2, kv_quant=True),
}


def _scan_cfg(variant):
    return dataclasses.replace(CFG, n_layers=4, scan_layers=True, **_SCAN_VARIANTS[variant])


@pytest.mark.parametrize("spec_k", [0, 2], ids=["decode", "spec_verify"])
@pytest.mark.parametrize("variant", ["window_every2", "kv_quant", "window_every2_kv_quant"])
def test_scan_layers_variants_paged_parity(variant, spec_k):
    """Paged = dense token for token under ``scan_layers`` where the carried cache has
    more than the plain bf16 planes: grouped layers (every second one banded), int8
    planes with their scale planes, and both — for the T == 1 decode and for the
    T == spec_k + 1 verify, which writes several slots a lane into its layer's plane."""
    cfg = _scan_cfg(variant)
    params = llama.init_params(cfg)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, CFG.vocab_size, int(n)).astype(np.int32) for n in (5, 9, 12)]

    def run(page_size):
        eng = ContinuousBatcher(params, cfg, max_slots=2, max_len=64,
                                prompt_bucket=16, page_size=page_size, spec_k=spec_k)
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run()
        return [r.tokens for r in reqs]

    assert run(0) == run(8)


def _scans(jaxpr):
    """Every ``scan`` equation of a jaxpr, those nested in other equations included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


@pytest.mark.parametrize("layout", ["paged", "dense"])
@pytest.mark.parametrize("variant", list(_SCAN_VARIANTS))
@pytest.mark.parametrize("program", ["forward_slots", "forward_slots_multi"])
def test_no_scan_takes_the_cache_as_xs_or_returns_it_as_ys(program, variant, layout):
    """The stacked cache is a CARRY of every scan it passes through, never a scan's
    ``xs`` or ``ys``: a scan slices its xs a layer at a time and stacks its ys into a
    new buffer, which on the chip was six passes over the whole pool every decode step
    (147 ms of a 195 ms dispatch). Planes are told by size and dtype, so the grouped
    ``[L/2, 2, ...]`` view of them would be caught too."""
    cfg = _scan_cfg(variant)
    B, max_len, ps = 2, 40, 8
    params = jax.eval_shape(lambda: llama.init_params(cfg))
    if layout == "paged":
        cache = jax.eval_shape(lambda: llama.init_paged_cache(cfg, B, max_len, 10, ps))
        paged = dict(tables=jnp.zeros((B, max_len // ps), jnp.int32), page_size=ps)
    else:
        cache = jax.eval_shape(lambda: llama.init_cache(cfg, B, max_len))
        paged = {}
    leaves = jax.tree_util.tree_leaves(cache["layers"])
    planes = {(int(np.prod(x.shape)), x.dtype) for x in leaves}
    assert not planes & {(int(np.prod(x.shape)), x.dtype)
                         for x in jax.tree_util.tree_leaves(params)}
    tok, pos = jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32)
    if program == "forward_slots":
        fn = lambda p, c: llama.forward_slots(p, tok[:, None], c, pos, cfg, **paged)  # noqa: E731
    else:
        fn = lambda p, c: llama.forward_slots_multi(  # noqa: E731
            p, c, tok, pos, jnp.ones((B,), bool), jnp.full((B,), 9, jnp.int32),
            jnp.full((B,), -1, jnp.int32),
            lambda logits, _: jnp.argmax(logits, axis=-1).astype(jnp.int32), None, 4, cfg,
            **paged)
    is_plane = lambda v: (int(np.prod(v.aval.shape)), v.aval.dtype) in planes  # noqa: E731
    carried = 0
    for eqn in _scans(jax.make_jaxpr(fn)(params, cache).jaxpr):
        consts, carry = eqn.params["num_consts"], eqn.params["num_carry"]
        assert not any(map(is_plane, eqn.invars[consts + carry:])), "the cache is a scan's xs"
        assert not any(map(is_plane, eqn.outvars[carry:])), "the cache is a scan's ys"
        carried += all(
            sum(map(is_plane, vs)) == len(leaves)
            for vs in (eqn.invars[consts:consts + carry], eqn.outvars[:carry]))
    # the layer scan, and around it the scan over decode steps
    assert carried == (1 if program == "forward_slots" else 2)
