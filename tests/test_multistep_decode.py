"""Device-resident multi-step decode (docs/multistep_decode.md): the same tokens
at every scan length.

The contract under test: ``decode_steps = N`` NEVER changes emitted tokens —
greedy and sampled (temperature/top-k/top-p, fixed PRNG) decode are token-for-
token identical to ``decode_steps = 1`` (the same scan at N = 1, itself pinned
against ``llama.generate`` by tests/test_serving.py), dense and paged, across staggered
admission, EOS mid-super-step, budgets that are not a multiple of N, cancel/
evict between super-steps, prefix-cache reuse, handoff-adopted lanes, and
chaos-injected super-step faults (survivors bitwise via replay recovery). The
knob only changes how many tokens one dispatch produces.

Parity fixtures are f32 (the bf16-rope greedy-tie lesson, CHANGES PR 4:
exactness contracts don't survive bf16 rounding noise).
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from accelerate_tpu.generation import (
    GenerationConfig,
    sampling_core,
    sampling_core_dyn_k,
)
from accelerate_tpu.models import llama
from accelerate_tpu.resilience.faults import FaultPlan, FaultSpec
from accelerate_tpu.serving import ContinuousBatcher
from accelerate_tpu.serving_gateway import DisaggRouter, FleetRouter, ServingGateway
from accelerate_tpu.utils.dataclasses import GatewayConfig

CFG = dataclasses.replace(llama.CONFIGS["tiny"], dtype=jnp.float32)


@pytest.fixture(scope="module")
def setup():
    params = llama.init_params(CFG)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, CFG.vocab_size, int(n)).astype(np.int32)
               for n in (5, 9, 3, 7, 6, 4)]
    return params, prompts


def make_engine(params, decode_steps=1, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("prompt_bucket", 16)
    return ContinuousBatcher(params, CFG, decode_steps=decode_steps, **kw)


def run_workload(engine, prompts, budgets=None, gens=None, rngs=None,
                 eos=None):
    reqs = []
    for i, p in enumerate(prompts):
        if gens is not None:
            reqs.append(engine.submit(p, gen=gens[i],
                                      rng=rngs[i] if rngs else None))
        else:
            reqs.append(engine.submit(
                p, max_new_tokens=budgets[i] if budgets else 8,
                eos_token_id=eos))
    engine.run()
    return reqs


_GENERATED: dict = {}


def generated(workload, params, prompts, gens, rngs=None):
    """What ``llama.generate`` gives each request alone, prompt left-padded to the
    engine's bucket: the reference outside the engine that every scan length,
    N = 1 included, is held to (made once per ``workload``, its cases share it)."""
    if workload not in _GENERATED:
        out = []
        for i, (p, g) in enumerate(zip(prompts, gens)):
            row = np.zeros((1, 16), np.int32)
            row[0, 16 - len(p):] = p
            out.append(np.asarray(llama.generate(
                params, jnp.asarray(row), CFG, g, prompt_mask=jnp.asarray(row != 0),
                rng=None if rngs is None else rngs[i]))[0].tolist())
        _GENERATED[workload] = out
    return _GENERATED[workload]


# --------------------------------------------------------------------- parity
@pytest.mark.parametrize("n_steps", [1, 2, 4, 8])
def test_greedy_parity_dense(setup, n_steps):
    """Staggered admission (more requests than lanes), varied budgets
    including ones that are NOT a multiple of N: ``generate()``'s output at
    every N."""
    params, prompts = setup
    budgets = [6, 11, 8, 3, 5, 7]
    want = generated("greedy", params, prompts,
                     [GenerationConfig(max_new_tokens=b, temperature=0.0)
                      for b in budgets])
    reqs = run_workload(make_engine(params, decode_steps=n_steps),
                        prompts, budgets=budgets)
    for r, w, b in zip(reqs, want, budgets):
        assert r.done and len(r.tokens) == b
        assert r.tokens == w, r.uid


@pytest.mark.parametrize("n_steps", [1, 2, 4])
def test_sampled_parity_dense(setup, n_steps):
    """temperature/top-k/top-p lanes mixed with a greedy lane in ONE
    super-step program: the per-lane emission-indexed key schedule makes the
    scan's draws bitwise ``generate()``'s."""
    params, prompts = setup
    gens = [
        GenerationConfig(max_new_tokens=7, temperature=0.8, top_k=7),
        GenerationConfig(max_new_tokens=9, temperature=0.7, top_p=0.9),
        GenerationConfig(max_new_tokens=6, temperature=0.0),  # greedy lane
        GenerationConfig(max_new_tokens=5, temperature=1.1, top_p=0.8, top_k=12),
    ]
    rngs = [jax.random.PRNGKey(100 + i) if g.temperature > 0 else None
            for i, g in enumerate(gens)]
    want = generated("sampled", params, prompts[:4], gens, rngs)
    reqs = run_workload(make_engine(params, decode_steps=n_steps),
                        prompts[:4], gens=gens, rngs=rngs)
    for r, w in zip(reqs, want):
        assert r.tokens == w, (r.uid, r.tokens, w)


@pytest.mark.parametrize("n_steps", [1, 2, 4])
def test_parity_paged(setup, n_steps):
    """Paged KV engine: the super-step writes through the device-resident
    block table (one table upload per dispatch) and stays bitwise."""
    params, prompts = setup
    gens = [
        GenerationConfig(max_new_tokens=8, temperature=0.0),
        GenerationConfig(max_new_tokens=7, temperature=0.8, top_p=0.9),
        GenerationConfig(max_new_tokens=10, temperature=0.9, top_k=9),
    ]
    rngs = [None, jax.random.PRNGKey(7), jax.random.PRNGKey(8)]
    want = generated("paged", params, prompts[:3], gens, rngs)
    eng = make_engine(params, decode_steps=n_steps, page_size=8)
    reqs = run_workload(eng, prompts[:3], gens=gens, rngs=rngs)
    for r, w in zip(reqs, want):
        assert r.tokens == w, r.uid
    assert eng.stats()["paged"] is True
    assert eng.stats()["multi_step"] == n_steps
    assert eng.block_mgr.stats()["pages_in_use"] == 0


def test_eos_mid_superstep(setup):
    """A lane hitting EOS inside the super-step freezes on-device: no tokens
    past EOS, and the other lanes keep decoding — exactly the N=1 stream."""
    params, prompts = setup
    # Probe an EOS-free greedy run for a token some lane emits mid-stream at
    # an offset that is NOT a super-step boundary, then re-run with that id
    # as EOS: it must cut that lane short at the same offset for every N.
    probe = [r.tokens for r in
             run_workload(make_engine(params), prompts, budgets=[12] * 6)]
    eos = next(t[j] for t in probe for j in (1, 2, 3, 5) if j < len(t))

    def run(n):
        return [r.tokens for r in run_workload(
            make_engine(params, decode_steps=n), prompts, budgets=[12] * 6,
            eos=eos)]

    want = run(1)
    assert any(t and t[-1] == eos and len(t) < 12 for t in want), \
        "fixture regression: no lane hit EOS early"
    for n in (2, 4, 8):
        assert run(n) == want, n


def test_cancel_and_evict_between_supersteps(setup):
    """cancel() and evict_slot() at a super-step boundary free the lane; the
    survivors' streams are untouched (bitwise the undisturbed N=1 run)."""
    params, prompts = setup
    want = [r.tokens for r in
            run_workload(make_engine(params), prompts[:3], budgets=[12] * 3)]
    eng = make_engine(params, decode_steps=4)
    reqs = [eng.submit(p, max_new_tokens=12) for p in prompts[:3]]
    eng.step()   # admit (prefill emits token 0) + first super-step
    eng.step()
    assert eng.cancel(reqs[1].uid)
    assert eng.evict_slot(reqs[2].uid)
    eng.run()
    # cancel/evict contract (unchanged by N): not marked done, prefix kept —
    # and NOTHING was emitted past the boundary where the lane was freed.
    for i in (1, 2):
        assert not reqs[i].done and 0 < len(reqs[i].tokens) < 12
        assert reqs[i].tokens == want[i][:len(reqs[i].tokens)], i
    assert reqs[0].done and reqs[0].tokens == want[0]


def test_prefix_cache_lanes(setup):
    """Prefix-cache-adopted lanes (shared paged prefix, COW boundary copy)
    feed the same super-step program and keep parity."""
    params, prompts = setup
    rng = np.random.default_rng(9)
    shared = rng.integers(1, CFG.vocab_size, 32).astype(np.int32)  # 2 chunks
    work = [np.concatenate([shared, p]) for p in prompts[2:5]]

    def run(n):
        eng = ContinuousBatcher(params, CFG, max_slots=2, max_len=96,
                                prompt_bucket=16, page_size=8, prefix_cache=4,
                                decode_steps=n)
        toks = [r.tokens for r in run_workload(eng, work, budgets=[7, 9, 6])]
        return toks, eng.stats()

    want, _ = run(1)
    got, stats = run(4)
    assert got == want
    assert stats["prefix_hits"] > 0, "fixture regression: prefix never reused"


# ---------------------------------------------------------- chaos / recovery
def test_fault_quarantines_at_superstep_granularity(setup):
    """An injected decode fault lands on the super-step dispatch (the fault
    site stays ``serving.decode``): quarantine + rebuild + replay, then the
    survivors finish BITWISE — replay recovery composes with decode_steps>1."""
    params, prompts = setup
    clean = [r.tokens for r in
             run_workload(make_engine(params), prompts, budgets=[8] * 6)]
    plan = FaultPlan([FaultSpec("serving.decode", "error", prob=1.0,
                                match_uid=1, max_fires=1)])
    eng = make_engine(params, decode_steps=4, faults=plan)
    reqs = run_workload(eng, prompts, budgets=[8] * 6)
    assert reqs[1].done and reqs[1].failed == "step_fault:error"
    for i, r in enumerate(reqs):
        if i != 1:
            assert r.failed is None
            assert r.tokens == clean[i], f"survivor {i} diverged"
    s = eng.stats()
    assert s["step_failures"] == 1 and s["quarantined"] == 1
    assert s["multi_step"] == 4


# ------------------------------------------------------------- fleet / disagg
def test_fleet_smoke_with_decode_steps(setup):
    """A homogeneous fleet of multi-step engines behind the gateway config
    knob routes and drains; outputs equal the single-engine N=1 run."""
    params, prompts = setup
    want = [r.tokens for r in
            run_workload(make_engine(params), prompts, budgets=[6] * 6)]
    router = FleetRouter(
        [make_engine(params, decode_steps=2, max_slots=2) for _ in range(2)],
        GatewayConfig(enabled=True, decode_steps=2),
    )
    greqs = [router.submit(p, max_new_tokens=6) for p in prompts]
    steps = 0
    while router.queue_depth or router.running_count:
        router.step()
        steps += 1
        assert steps < 600, "fleet stalled"
    for g, w in zip(greqs, want):
        assert g.status == "done" and g.tokens == w


def test_disagg_handoff_adopted_lanes(setup):
    """Disaggregated prefill/decode with a multi-step DECODE replica: lanes
    adopted from a KV page handoff decode in super-steps, bitwise the plain
    engine (the emission-indexed key schedule survives the handoff)."""
    params, prompts = setup
    gens = [GenerationConfig(max_new_tokens=6, temperature=0.8, top_p=0.9)
            if i % 2 else GenerationConfig(max_new_tokens=6)
            for i in range(4)]
    rngs = [jax.random.PRNGKey(40 + i) if g.temperature > 0 else None
            for i, g in enumerate(gens)]
    want = [r.tokens for r in run_workload(
        make_engine(params, page_size=8, max_slots=2),
        prompts[:4], gens=gens, rngs=rngs)]
    pre = ContinuousBatcher(params, CFG, max_slots=2, max_len=64,
                            prompt_bucket=16, page_size=8, role="prefill")
    dec = ContinuousBatcher(params, CFG, max_slots=2, max_len=64,
                            prompt_bucket=16, page_size=8, role="decode",
                            decode_steps=2)
    router = DisaggRouter([pre, dec], GatewayConfig(enabled=True),
                          roles=["prefill", "decode"])
    greqs = [router.submit(p, gen=gens[i], rng=rngs[i])
             for i, p in enumerate(prompts[:4])]
    steps = 0
    while router.queue_depth or router.running_count:
        router.step()
        steps += 1
        assert steps < 600, "disagg router stalled"
    assert router.counters["handoffs"] == 4
    for g, w in zip(greqs, want):
        assert g.status == "done" and g.tokens == w


# ------------------------------------------------------------------ plumbing
def test_ctor_validation(setup):
    params, _ = setup
    with pytest.raises(ValueError, match="decode_steps"):
        make_engine(params, decode_steps=0)
    with pytest.raises(TypeError, match="decode_steps"):
        make_engine(params, decode_steps=2.5)
    with pytest.raises(ValueError, match="prefill"):
        ContinuousBatcher(params, CFG, max_slots=2, max_len=64,
                          prompt_bucket=16, page_size=8, role="prefill",
                          decode_steps=2)
    with pytest.raises(ValueError, match="decode_steps"):
        GatewayConfig(enabled=True, decode_steps=0)


def test_gateway_engine_mismatch_raises(setup):
    """A gateway stamped decode_steps=N must refuse an engine running a
    different depth — mis-paired deployments fail at construction, not with
    wrong streaming granularity in production."""
    params, _ = setup
    with pytest.raises(ValueError, match="decode_steps"):
        ServingGateway(make_engine(params),
                       GatewayConfig(enabled=True, decode_steps=4))
    # matched pairing constructs and serves
    gw = ServingGateway(make_engine(params, decode_steps=2),
                        GatewayConfig(enabled=True, decode_steps=2))
    greq = gw.submit(np.asarray([3, 1, 4], np.int32), max_new_tokens=5)
    while not greq.terminal:
        gw.step()
    assert greq.status == "done" and len(greq.tokens) == 5


def test_spec_engine_degrades_to_multistep(setup):
    """spec_k and decode_steps COEXIST: speculation wins while enabled; when
    the gateway's degradation rung disables it, decode falls back to the
    multi-step super-step, not to one-token dispatch — and stays bitwise."""
    params, prompts = setup
    want = [r.tokens for r in
            run_workload(make_engine(params), prompts[:3], budgets=[8] * 3)]
    eng = make_engine(params, decode_steps=4, spec_k=2)
    assert eng.spec_enabled
    eng.spec_enabled = False  # the degradation rung's exact switch
    steps0 = eng.decode_steps
    reqs = run_workload(eng, prompts[:3], budgets=[8] * 3)
    for r, w in zip(reqs, want):
        assert r.tokens == w
    # 8-token budgets at N=4: the super-step path really ran (few dispatches)
    assert eng.decode_steps - steps0 <= 4
    assert eng.stats()["spec_proposed"] == 0


def test_superstep_trace_spans_account_n_tokens(setup):
    """Each decode span carries the super-step's accounted token count,
    n_steps=N, and the measured host-side inter-dispatch gap."""
    from accelerate_tpu.telemetry import Telemetry
    from accelerate_tpu.telemetry.tracing import TRACE_SPAN_SCHEMA, Tracer
    from accelerate_tpu.utils.dataclasses import TelemetryConfig

    params, prompts = setup
    tel = Telemetry(TelemetryConfig(enabled=True, compile_events=False,
                                    memory_stats=False))
    tracer = Tracer(tel)
    eng = make_engine(params, decode_steps=4, tracer=tracer)
    gw = ServingGateway(eng, GatewayConfig(enabled=True, decode_steps=4),
                        telemetry=tel, tracer=tracer)
    greqs = [gw.submit(p, max_new_tokens=6) for p in prompts[:2]]
    while not all(g.terminal for g in greqs):
        gw.step()
    spans = [s for s in tel.records
             if s.get("schema") == TRACE_SPAN_SCHEMA and s["span"] == "decode"]
    assert spans
    assert all(s["n_steps"] == 4 and s["host_s"] >= 0.0 for s in spans)
    # 6-token budgets: prefill emits token 0, decode super-steps the other 5
    # per lane (N=4 then a budget-clamped 1)
    assert sum(s["tokens"] for s in spans) == 10


# ------------------------------------------- one loop: N = 1 is the scan too
@pytest.mark.parametrize("page_size", [0, 8], ids=["dense", "paged"])
def test_default_engine_dispatches_the_scan_and_warms_what_it_runs(
        setup, monkeypatch, page_size):
    """``decode_steps=1`` (the default) has no loop of its own: it dispatches
    ``serving.decode_multi[_paged]`` at N = 1, and ``warm_programs()`` lists the
    scan's two ``sample`` variants and no one-token program — at any N an
    engine warms nothing it cannot dispatch."""
    import contextlib

    from accelerate_tpu import serving
    from accelerate_tpu.analysis.program import LowerOnlyCache

    params, prompts = setup
    scan = "serving.decode_multi" + ("_paged" if page_size else "")
    seen = []

    @contextlib.contextmanager
    def recording(label):
        seen.append(label)
        yield

    monkeypatch.setattr(serving, "compile_label", recording)
    eng = make_engine(params, page_size=page_size)
    assert eng.multi_step == 1
    run_workload(eng, prompts[:3], budgets=[4, 3, 5])
    assert seen and set(seen) == {scan}, seen

    for n_steps in (1, 4):
        warm = make_engine(params, decode_steps=n_steps, page_size=page_size,
                           compile_cache=LowerOnlyCache())
        labels = [e["label"] for e in warm.warm_programs(max_new_tokens=4)]
        assert [l for l in labels if "decode" in l] == [scan, scan], labels


def test_one_step_of_the_default_engine_shows_the_four_decode_phases(setup):
    """The readers built on the engine's phases (docs/telemetry.md) see the default
    engine like any other: one ``step()`` is ``decode.prepare`` → ``dispatch`` →
    ``fetch`` → ``drain`` under an ``engine.decode`` with ``n_steps == 1``.
    Read from the phase ledger: no profiler session, nothing stood in."""
    from accelerate_tpu.telemetry.tracing import PHASES, PHASE_CLOCK_NS

    params, prompts = setup
    eng = make_engine(params)
    reqs = [eng.submit(p, max_new_tokens=5) for p in prompts[:2]]
    since = PHASE_CLOCK_NS()
    eng.step()
    records = sorted(PHASES.records(since_ns=since), key=lambda r: r.t0_ns)
    names = [r.name for r in records]
    inside = names[names.index("engine.decode") + 1:]
    assert inside == ["engine.decode.prepare", "engine.decode.dispatch",
                      "engine.decode.fetch", "engine.decode.drain"], names
    by = {r.name: r.attrs for r in records}
    assert by["engine.decode"] == {"lanes": 2, "n_steps": 1}
    assert by["engine.decode.drain"]["tokens"] == 2
    assert [len(r.tokens) for r in reqs] == [2, 2]   # the prefill's token + one


def test_sampling_core_dyn_k_matches_static():
    """The traced-``top_k`` sampling core is bitwise ``sampling_core`` for
    every k (including 0 = disabled): descending-sort (k-1)-th element is the
    same exact selection as ``lax.top_k``'s kth value."""
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(size=(4, 64)).astype(np.float32))
    for k in (0, 1, 3, 7, 64):
        for seed in (0, 1, 2):
            key = jax.random.PRNGKey(seed)
            want = sampling_core(logits, key, 0.8, 0.9, k)
            got = sampling_core_dyn_k(
                logits, key, jnp.float32(0.8), jnp.float32(0.9),
                jnp.int32(k))
            assert np.array_equal(np.asarray(want), np.asarray(got)), k


@pytest.mark.parametrize("variant", ["plain", "window_every2", "kv_quant"])
def test_scan_layers_paged_multistep_parity(variant):
    """``decode_steps=4`` = ``decode_steps=1`` token for token under ``scan_layers`` on
    the paged layout, where the stacked pool is the carry of the layer scan INSIDE the
    carry of the scan over steps — plain, with grouped (alternately banded) layers, and
    with int8 planes. More requests than lanes, budgets that are no multiple of 4."""
    over = {"plain": {}, "window_every2": dict(sliding_window=6, window_every=2),
            "kv_quant": dict(kv_quant=True)}[variant]
    cfg = dataclasses.replace(CFG, n_layers=4, scan_layers=True, **over)
    params = llama.init_params(cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, CFG.vocab_size, int(n)).astype(np.int32)
               for n in (5, 9, 3, 12, 6)]
    budgets = [6, 11, 8, 3, 5]

    def run(decode_steps):
        eng = ContinuousBatcher(params, cfg, max_slots=3, max_len=64, prompt_bucket=16,
                                page_size=8, decode_steps=decode_steps)
        reqs = [eng.submit(p, max_new_tokens=b) for p, b in zip(prompts, budgets)]
        eng.run()
        assert eng.block_mgr.stats()["pages_in_use"] == 0
        return [r.tokens for r in reqs]

    assert run(4) == run(1)
