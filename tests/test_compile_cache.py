"""AOT compile cache (ISSUE 3): executable round-trip, poisoned-entry fallback,
warmup manifests, shape-bucketed serving, and recompile-regression guards.

The round-trip tests prove the tentpole contract on the CPU backend: a second
"process" (singletons reset + ``jax.clear_caches()``) re-building the same
train step performs ZERO XLA compiles (asserted via ``CompileMonitor``), and a
poisoned cache entry falls back to live compile without error. The guards pin
the compile surface: the fused train step compiles exactly once across a
3-dispatch run, and serving decode/prefill compiles are bounded by the bucket
ladder across varied prompt lengths.

Note: conftest's persistent jax compilation cache only stores compiles taking
> 0.5 s — the deliberately tiny programs here always recompile, so exact
compile counting is deterministic across suite re-runs.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu import Accelerator, CompileCacheConfig
from accelerate_tpu.compile_cache import AotCache, pick_bucket
from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
from accelerate_tpu.telemetry import CompileMonitor

optax = pytest.importorskip("optax")


@pytest.fixture(autouse=True)
def _no_jax_persistent_cache():
    """Disable conftest's jax persistent compilation cache for this module: an
    executable LOADED from it serializes to an incomplete payload (no object
    code), so AotCache entries must come from genuinely cold compiles here to
    make hit/miss/compile counting deterministic across suite re-runs.
    (``AotCache._store`` validates-and-skips such payloads in production.)"""
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def _fresh_process():
    """Simulate a new process: drop singletons and every in-memory jit cache, so
    only the on-disk AOT cache can avoid a compile."""
    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    jax.clear_caches()


def _toy_setup(cache_dir, d=16):
    cc = CompileCacheConfig(enabled=True, cache_dir=str(cache_dir))
    acc = Accelerator(compile_cache_config=cc)
    params = {"w": np.full((d, d), 0.5, np.float32)}
    state = acc.create_train_state(params, optax.adamw(1e-3))
    step = acc.build_train_step(
        lambda p, b: jnp.mean((b["x"] @ p["w"]) ** 2), max_grad_norm=1.0
    )
    batch = {"x": np.ones((8, d), np.float32)}
    return acc, state, step, batch


# ------------------------------------------------------------------ config / buckets


def test_config_env_resolution(monkeypatch):
    monkeypatch.delenv("ACCELERATE_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("ACCELERATE_COMPILE_CACHE_DIR", raising=False)
    assert CompileCacheConfig().enabled is False
    monkeypatch.setenv("ACCELERATE_COMPILE_CACHE", "1")
    assert CompileCacheConfig().enabled is True
    monkeypatch.setenv("ACCELERATE_COMPILE_CACHE", "off")
    assert CompileCacheConfig().enabled is False
    # A path value both enables the cache and names the directory.
    monkeypatch.setenv("ACCELERATE_COMPILE_CACHE", "/tmp/some/cache")
    cfg = CompileCacheConfig()
    assert cfg.enabled is True and cfg.cache_dir == "/tmp/some/cache"
    # Explicit dir env wins over the path value.
    monkeypatch.setenv("ACCELERATE_COMPILE_CACHE_DIR", "/tmp/other")
    assert CompileCacheConfig().cache_dir == "/tmp/other"
    # Explicit Python arg wins over everything (§5 priority order).
    assert CompileCacheConfig(enabled=False).enabled is False


def test_bucket_ladder_and_pick():
    cfg = CompileCacheConfig(enabled=True, bucket_min=64, bucket_growth=2.0)
    # Rungs stay below max_len: a max_len-wide bucket leaves no decode room
    # (bucket + max_new <= max_len can never hold) and would be unreachable.
    assert cfg.ladder(512) == (64, 128, 256)
    assert cfg.ladder(100) == (64,)
    assert cfg.ladder(64) == ()  # bucket_min >= max_len: bucketing off
    # growth < 2 must still strictly ascend (no int-truncation duplicate rungs)
    slow = CompileCacheConfig(enabled=True, bucket_min=4, bucket_growth=1.2)
    rungs = slow.ladder(16)
    assert list(rungs) == sorted(set(rungs))
    assert CompileCacheConfig(enabled=True, serving_buckets=(32, 64)).ladder(48) == (32,)
    assert pick_bucket(5, (64, 128)) == 64
    assert pick_bucket(65, (64, 128)) == 128
    assert pick_bucket(200, (64, 128)) is None
    with pytest.raises(ValueError):
        CompileCacheConfig(serving_buckets=(64, 32))
    with pytest.raises(ValueError):
        CompileCacheConfig(bucket_growth=1.0)


def test_disabled_wrap_is_identity(tmp_path):
    cache = AotCache(CompileCacheConfig(enabled=False, cache_dir=str(tmp_path)))
    jitted = jax.jit(lambda x: x + 1)
    assert cache.wrap(jitted, "f") is jitted
    assert not os.path.exists(str(tmp_path / "anything"))


# ------------------------------------------------------------------ round trip


def test_train_step_round_trip_zero_compiles(tmp_path):
    """Acceptance: a warm-cache second 'process' building the same train step
    performs zero XLA compiles and still computes the identical loss."""
    acc, state, step, batch = _toy_setup(tmp_path)
    state, metrics = step(state, batch)
    first_loss = float(np.asarray(metrics["loss"]))
    assert acc.compile_cache.misses >= 1
    assert any(f.endswith(".aotx") for f in os.listdir(tmp_path))

    _fresh_process()
    acc2, state2, step2, batch2 = _toy_setup(tmp_path)
    mon = CompileMonitor().start()
    try:
        state2, metrics2 = step2(state2, batch2)
    finally:
        mon.stop()
    if not mon.supported:
        pytest.skip("this jax exposes no jax.monitoring API")
    assert mon.count == 0, f"warm start paid {mon.count} XLA compiles"
    assert acc2.compile_cache.hits >= 1
    assert acc2.compile_cache.misses == 0
    assert float(np.asarray(metrics2["loss"])) == pytest.approx(first_loss)
    # Hit + deserialize time surfaced through the telemetry monitor too.
    snap = mon.snapshot()
    assert snap["cache_hit"] >= 1 and snap["cache_miss"] == 0


def test_poisoned_entry_falls_back_to_live_compile(tmp_path):
    acc, state, step, batch = _toy_setup(tmp_path)
    state, metrics = step(state, batch)
    want = float(np.asarray(metrics["loss"]))
    for name in os.listdir(tmp_path):
        if name.endswith(".aotx"):
            with open(tmp_path / name, "wb") as f:
                f.write(b"not an executable")

    _fresh_process()
    acc2, state2, step2, batch2 = _toy_setup(tmp_path)
    state2, metrics2 = step2(state2, batch2)  # must NOT raise
    assert acc2.compile_cache.failures >= 1
    assert acc2.compile_cache.misses >= 1  # recompiled live + entry rewritten
    assert float(np.asarray(metrics2["loss"])) == pytest.approx(want)

    # The rewritten entry is healthy again: a third process hits.
    _fresh_process()
    acc3, state3, step3, batch3 = _toy_setup(tmp_path)
    step3(state3, batch3)
    assert acc3.compile_cache.hits >= 1 and acc3.compile_cache.failures == 0


def test_mismatched_signature_falls_back(tmp_path):
    """A cached executable that rejects its inputs pins the signature to the
    live jit path instead of failing the step."""
    cache = AotCache(CompileCacheConfig(enabled=True, cache_dir=str(tmp_path)))
    wrapped = cache.wrap(jax.jit(lambda x, n=1: x * n), "mul")
    out = wrapped(jnp.ones((4,)))
    assert float(out[0]) == 1.0
    # Poison the in-memory executable table with a function that always rejects.
    sig = list(wrapped._execs)[0]

    def reject(*a, **k):
        raise TypeError("wrong avals")

    wrapped._execs[sig] = reject
    out2 = wrapped(jnp.ones((4,)))  # falls back, does not raise
    assert float(out2[0]) == 1.0
    from accelerate_tpu.compile_cache.cache import _LIVE

    assert wrapped._execs[sig] is _LIVE


# ------------------------------------------------------------------ recompile guards


def test_fused_train_step_compiles_exactly_once():
    """Regression guard (ISSUE 3 satellite): the fused train step compiles ONE
    program on its first dispatch and zero thereafter across a 3-dispatch run."""
    d = 24  # distinct shape so no other test's in-memory executable is reused
    acc = Accelerator()
    params = {"w": np.full((d, d), 0.1, np.float32)}
    state = acc.create_train_state(params, optax.adamw(1e-3))
    step = acc.build_train_step(
        lambda p, b: jnp.mean((b["x"] @ p["w"]) ** 2), fused_steps=2
    )
    batches = [{"x": np.ones((8, d), np.float32)} for _ in range(2)]
    mon = CompileMonitor().start()
    try:
        state, _ = step(state, batches)
        if not mon.supported:
            pytest.skip("this jax exposes no jax.monitoring API")
        after_first = mon.count
        for _ in range(2):
            state, _ = step(state, batches)
    finally:
        mon.stop()
    assert after_first == 1, f"first dispatch compiled {after_first} programs"
    assert mon.count == after_first, (
        f"steps 2-3 recompiled: {mon.count - after_first} extra compiles"
    )


def test_serving_decode_compiles_bounded_by_buckets():
    """Regression guard: across varied prompt lengths, a greedy workload compiles
    at most one decode scan (its greedy ``sample`` variant; a sampled lane would
    add the other) + the zero key window a greedy dispatch hands it + one prefill
    per bucket + one insert per slot — and a second varied-length workload
    compiles NOTHING new."""
    from accelerate_tpu.models import llama
    from accelerate_tpu.serving import ContinuousBatcher

    # Distinct geometry so no other serving test's executables are reused.
    cfg = dataclasses.replace(
        llama.CONFIGS["tiny"], dtype=jnp.float32, d_model=48, n_heads=3, n_kv_heads=3
    )
    params = llama.init_params(cfg)
    buckets = (8, 16, 32)
    engine = ContinuousBatcher(
        params, cfg, max_slots=2, max_len=64, prompt_buckets=buckets
    )
    rng = np.random.default_rng(1)
    mon = CompileMonitor().start()
    try:
        for n in (3, 5, 9, 12, 20, 30):
            engine.submit(rng.integers(1, cfg.vocab_size, n).astype(np.int32),
                          max_new_tokens=3)
        engine.run()
        if not mon.supported:
            pytest.skip("this jax exposes no jax.monitoring API")
        first_workload = mon.count
        for n in (2, 7, 11, 19, 28, 31):
            engine.submit(rng.integers(1, cfg.vocab_size, n).astype(np.int32),
                          max_new_tokens=3)
        engine.run()
    finally:
        mon.stop()
    # prefill/bucket + serving.decode_multi (sample=False) + the jnp.zeros key
    # filler (a broadcast program) + inserts: 3 + 1 + 1 + 2 = 7
    bound = len(buckets) + 1 + 1 + engine.max_slots
    assert first_workload <= bound, (first_workload, bound)
    assert mon.count == first_workload, (
        f"second varied-length workload recompiled {mon.count - first_workload} programs"
    )
    stats = engine.stats()
    assert stats["bucket_misses"] == len(buckets)
    assert stats["bucket_hits"] == 12 - len(buckets)


def test_serving_bucketed_matches_greedy_reference():
    """Bucketed prefill must not change outputs: parity with per-prompt greedy
    generate, including a prompt that overflows every bucket (chunk fallback)."""
    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.models import llama
    from accelerate_tpu.serving import ContinuousBatcher

    cfg = dataclasses.replace(llama.CONFIGS["tiny"], dtype=jnp.float32)
    params = llama.init_params(cfg)
    engine = ContinuousBatcher(
        params, cfg, max_slots=2, max_len=64, prompt_bucket=8, prompt_buckets=(8, 16)
    )
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 13, 24)]  # bucket 8, bucket 16, chunk fallback (24 > 16)
    reqs = [engine.submit(p, max_new_tokens=4) for p in prompts]
    engine.run()
    for req, prompt in zip(reqs, prompts):
        want = np.asarray(llama.generate(
            params, prompt[None], cfg, GenerationConfig(max_new_tokens=4, temperature=0.0)
        ))[0].tolist()
        assert req.tokens == want, (req.uid, req.tokens, want)
    assert engine.stats()["bucket_misses"] == 2  # 24-token prompt went chunked


def test_spec_serving_compiles_once_and_second_run_zero():
    """Spec-mode regression guard (ISSUE 6 satellite): a speculative engine
    compiles one fused verify + one prefill per bucket + one insert per slot on
    its first varied workload, and a second varied workload compiles ZERO new
    programs — per-request k or proposal contents must never mint a new shape."""
    from accelerate_tpu.models import llama
    from accelerate_tpu.serving import ContinuousBatcher

    # Distinct geometry so no other serving test's executables are reused.
    cfg = dataclasses.replace(
        llama.CONFIGS["tiny"], dtype=jnp.float32, d_model=56, n_heads=2, n_kv_heads=2
    )
    params = llama.init_params(cfg)
    buckets = (8, 16, 32)
    engine = ContinuousBatcher(
        params, cfg, max_slots=2, max_len=64, prompt_buckets=buckets, spec_k=2
    )
    rng = np.random.default_rng(1)
    mon = CompileMonitor().start()
    try:
        for n in (3, 5, 9, 12, 20, 30):
            engine.submit(rng.integers(1, cfg.vocab_size, n).astype(np.int32),
                          max_new_tokens=3)
        engine.run()
        if not mon.supported:
            pytest.skip("this jax exposes no jax.monitoring API")
        first_workload = mon.count
        for n in (2, 7, 11, 19, 28, 31):
            engine.submit(rng.integers(1, cfg.vocab_size, n).astype(np.int32),
                          max_new_tokens=5)
        engine.run()
    finally:
        mon.stop()
    bound = len(buckets) + 1 + engine.max_slots  # prefill/bucket + verify + inserts
    assert first_workload <= bound, (first_workload, bound)
    assert mon.count == first_workload, (
        f"second spec workload recompiled {mon.count - first_workload} programs"
    )
    # Output still the plain engine's: every request equals standalone greedy.
    assert engine.stats()["spec_k"] == 2


def test_paged_serving_second_varied_workload_compiles_zero():
    """Paged-engine compile surface (ISSUE 7): per-request page allocation, block
    tables, slot choice and pool occupancy are DATA — a second varied workload on
    a paged engine (different prompts, lengths, budgets, lane churn) compiles
    zero new programs. First-workload bound: one paged decode scan (greedy
    variant) + its zero key window + one prefill per touched bucket + ONE
    dynamic-slot page scatter (the paged insert needs no per-slot variants)."""
    from accelerate_tpu.models import llama
    from accelerate_tpu.serving import ContinuousBatcher

    # Distinct geometry so no other serving test's executables are reused.
    cfg = dataclasses.replace(
        llama.CONFIGS["tiny"], dtype=jnp.float32, d_model=40, n_heads=2, n_kv_heads=2
    )
    params = llama.init_params(cfg)
    buckets = (8, 16, 32)
    engine = ContinuousBatcher(
        params, cfg, max_slots=2, max_len=64, prompt_buckets=buckets, page_size=8
    )
    rng = np.random.default_rng(2)
    mon = CompileMonitor().start()
    try:
        for n in (3, 5, 9, 12, 20, 30):
            engine.submit(rng.integers(1, cfg.vocab_size, n).astype(np.int32),
                          max_new_tokens=3)
        engine.run()
        if not mon.supported:
            pytest.skip("this jax exposes no jax.monitoring API")
        first_workload = mon.count
        for n in (2, 7, 11, 19, 28, 31):
            engine.submit(rng.integers(1, cfg.vocab_size, n).astype(np.int32),
                          max_new_tokens=5)
        engine.run()
    finally:
        mon.stop()
    # prefill/bucket + serving.decode_multi_paged (sample=False) + the jnp.zeros
    # key filler + the page scatter: 3 + 1 + 1 + 1 = 6
    bound = len(buckets) + 1 + 1 + 1
    assert first_workload <= bound, (first_workload, bound)
    assert mon.count == first_workload, (
        f"second paged workload recompiled {mon.count - first_workload} programs"
    )
    assert engine.stats()["paged"] is True


def test_multistep_serving_second_varied_workload_compiles_zero():
    """Multi-step compile surface (docs/multistep_decode.md): super-step depth
    N and the sample flag are STATIC (two programs per layout); lane count,
    budgets, EOS, key schedules and admission order are DATA — a second varied
    workload on a decode_steps=4 engine (different prompts, lengths, budgets,
    sampled AND greedy lanes, lane churn) compiles zero new programs.

    One pre-existing carve-out, shared with the N=1 engine: a sampled request's
    key SCHEDULE (``jax.random.split(rng, max_new_tokens)`` + the window
    gather) mints a few tiny host-side programs per distinct sampled budget —
    so the second workload's sampled budgets reuse first-workload values while
    everything else (prompts, lengths, greedy budgets, order) varies."""
    import jax

    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.models import llama
    from accelerate_tpu.serving import ContinuousBatcher

    # Distinct geometry so no other serving test's executables are reused.
    cfg = dataclasses.replace(
        llama.CONFIGS["tiny"], dtype=jnp.float32, d_model=48, n_heads=2, n_kv_heads=2
    )
    params = llama.init_params(cfg)
    engine = ContinuousBatcher(
        params, cfg, max_slots=2, max_len=64, prompt_buckets=(16,),
        decode_steps=4,
    )
    rng = np.random.default_rng(5)

    def workload(lens, budgets, seed):
        for i, (n, b) in enumerate(zip(lens, budgets)):
            prompt = rng.integers(1, cfg.vocab_size, n).astype(np.int32)
            if i % 2:
                engine.submit(prompt, gen=GenerationConfig(
                    max_new_tokens=b, temperature=0.8, top_p=0.9, top_k=7,
                ), rng=jax.random.PRNGKey(seed + i))
            else:
                engine.submit(prompt, max_new_tokens=b)
        engine.run()

    mon = CompileMonitor().start()
    try:
        workload((3, 5, 9, 12), (3, 6, 11, 2), seed=0)   # sampled budgets 6, 2
        if not mon.supported:
            pytest.skip("this jax exposes no jax.monitoring API")
        first_workload = mon.count
        workload((2, 7, 11, 6), (7, 2, 5, 6), seed=40)   # sampled budgets 2, 6
    finally:
        mon.stop()
    # Loose first-workload bound (prefill + per-slot inserts + the two
    # super-step variants + key-schedule plumbing); the pin is the ZERO below.
    assert first_workload <= 30, first_workload
    assert mon.count == first_workload, (
        f"second multi-step workload recompiled {mon.count - first_workload} programs"
    )
    assert engine.stats()["multi_step"] == 4


def test_fused_spec_serving_second_varied_workload_compiles_zero():
    """Fused speculative super-step compile surface (ISSUE 18): round count N,
    spec_k, the drafter's max_ngram and the sample flag are STATIC (two
    programs per layout); lane count, budgets, EOS, token history, key-cursor
    tables and admission order are DATA — a second varied workload on a
    spec_k=2 + decode_steps=4 engine (different prompts, lengths, budgets,
    sampled AND greedy lanes, lane churn) compiles zero new programs.

    Same sampled-budget carve-out as the plain multi-step test: the key
    SCHEDULE mints a few tiny host-side programs per distinct sampled budget,
    so the second workload reuses first-workload sampled budgets."""
    import jax

    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.models import llama
    from accelerate_tpu.serving import ContinuousBatcher

    # Distinct geometry so no other serving test's executables are reused.
    cfg = dataclasses.replace(
        llama.CONFIGS["tiny"], dtype=jnp.float32, d_model=72, n_heads=2, n_kv_heads=2
    )
    params = llama.init_params(cfg)
    engine = ContinuousBatcher(
        params, cfg, max_slots=2, max_len=64, prompt_buckets=(16,),
        spec_k=2, decode_steps=4,
    )
    assert engine._spec_fused()
    rng = np.random.default_rng(9)

    def workload(lens, budgets, seed):
        for i, (n, b) in enumerate(zip(lens, budgets)):
            prompt = rng.integers(1, cfg.vocab_size, n).astype(np.int32)
            if i % 2:
                engine.submit(prompt, gen=GenerationConfig(
                    max_new_tokens=b, temperature=0.8, top_p=0.9, top_k=7,
                ), rng=jax.random.PRNGKey(seed + i))
            else:
                engine.submit(prompt, max_new_tokens=b)
        engine.run()

    mon = CompileMonitor().start()
    try:
        workload((3, 5, 9, 12), (3, 6, 11, 2), seed=0)   # sampled budgets 6, 2
        if not mon.supported:
            pytest.skip("this jax exposes no jax.monitoring API")
        first_workload = mon.count
        workload((2, 7, 11, 6), (7, 2, 5, 6), seed=40)   # sampled budgets 2, 6
    finally:
        mon.stop()
    # Loose first-workload bound (prefill + per-slot inserts + the two fused
    # spec variants + key-schedule plumbing); the pin is the ZERO below.
    assert first_workload <= 30, first_workload
    assert mon.count == first_workload, (
        f"second fused-spec workload recompiled {mon.count - first_workload} programs"
    )
    assert engine.stats()["multi_step"] == 4 and engine.stats()["spec_k"] == 2


def test_warmup_enumerates_multistep_programs(tmp_path):
    """run_warmup(decode_steps=4) lists BOTH super-step sample variants in the
    manifest and stamps the depth — a cache directory is auditable for which
    decode granularity it is warm FOR (dense here, paged via page_size)."""
    from accelerate_tpu.analysis.program import LowerOnlyCache
    from accelerate_tpu.compile_cache.warmup import run_warmup

    manifest = run_warmup(
        cache=LowerOnlyCache(), manifest_path=str(tmp_path / "m.json"),
        preset="smoke", batch_size=2, seq_len=16, train=False, eval_step=False,
        serve=True, max_slots=2, max_len=128, max_new_tokens=4, decode_steps=4,
    )
    assert manifest["decode_steps"] == 4
    labels = {e["label"] for e in manifest["programs"]}
    assert "serving.decode_multi" in labels, labels
    assert "serving.decode" not in labels  # the scan is the only decode program
    paged = run_warmup(
        cache=LowerOnlyCache(), emit_manifest=False,
        preset="smoke", batch_size=2, seq_len=16, train=False, eval_step=False,
        serve=True, max_slots=2, max_len=128, max_new_tokens=4, decode_steps=2,
        page_size=24,
    )
    assert {e["label"] for e in paged["programs"]} >= {"serving.decode_multi_paged"}
    # decode_steps without serve would warm nothing — must be loud.
    with pytest.raises(ValueError, match="serve"):
        run_warmup(cache=LowerOnlyCache(), emit_manifest=False,
                   preset="smoke", batch_size=2, seq_len=16, train=False,
                   serve=False, decode_steps=4)


def test_warmup_enumerates_fused_spec_programs(tmp_path):
    """run_warmup(spec_k, decode_steps>1, ngram drafter) lists BOTH sample
    variants of the fused speculative super-step in the manifest and stamps
    ``spec_fused`` — a cache directory is auditable for whether its spec
    surface is the fused scan or the host round-trip loop. A half-depth
    ModelDrafter is NOT device-resident, so the same geometry with
    spec_draft='half' stamps spec_fused=False and warms no fused program."""
    from accelerate_tpu.analysis.program import LowerOnlyCache
    from accelerate_tpu.compile_cache.warmup import run_warmup

    manifest = run_warmup(
        cache=LowerOnlyCache(), manifest_path=str(tmp_path / "m.json"),
        preset="smoke", batch_size=2, seq_len=16, train=False, eval_step=False,
        serve=True, max_slots=2, max_len=128, max_new_tokens=4,
        spec_k=2, spec_draft="ngram", decode_steps=4,
    )
    assert manifest["spec_fused"] is True
    assert manifest["decode_steps"] == 4 and manifest["spec_k"] == 2
    labels = [e["label"] for e in manifest["programs"]]
    assert labels.count("serving.spec_multi") == 2, labels  # greedy + sampled
    assert "serving.spec_verify" in labels   # host-loop fallback stays warm
    assert "serving.decode_multi" in labels  # spec-off degradation target
    paged = run_warmup(
        cache=LowerOnlyCache(), emit_manifest=False,
        preset="smoke", batch_size=2, seq_len=16, train=False, eval_step=False,
        serve=True, max_slots=2, max_len=128, max_new_tokens=4,
        spec_k=2, spec_draft="ngram", decode_steps=2, page_size=24,
    )
    assert paged["spec_fused"] is True
    assert {e["label"] for e in paged["programs"]} >= {"serving.spec_multi_paged"}
    half = run_warmup(
        cache=LowerOnlyCache(), emit_manifest=False,
        preset="smoke", batch_size=2, seq_len=16, train=False, eval_step=False,
        serve=True, max_slots=2, max_len=128, max_new_tokens=4,
        spec_k=2, spec_draft="half", decode_steps=4,
    )
    assert half["spec_fused"] is False
    assert "serving.spec_multi" not in {e["label"] for e in half["programs"]}


def test_warmup_enumerates_paged_programs(tmp_path):
    """run_warmup(page_size=...) lists the paged decode/verify, the dynamic-slot
    page scatter, and (with prefix_cache) the page gather + partial-page copy in
    the manifest — and stamps the page geometry, so a cache directory is
    auditable for which KV layout it is warm FOR."""
    from accelerate_tpu.analysis.program import LowerOnlyCache
    from accelerate_tpu.compile_cache.warmup import run_warmup

    cache = LowerOnlyCache()
    manifest = run_warmup(
        cache=cache, manifest_path=str(tmp_path / "m.json"),
        preset="smoke", batch_size=2, seq_len=16, train=False, eval_step=False,
        serve=True, max_slots=2, max_len=128, max_new_tokens=4,
        spec_k=2, spec_draft="ngram", page_size=24, prefix_cache=2,
    )
    assert manifest["page_size"] == 24
    assert manifest["kv_pages"] == 2 * -(-128 // 24)
    assert manifest["prefix_cache"] == 2
    labels = {e["label"] for e in manifest["programs"]}
    assert {"serving.decode_multi_paged", "serving.spec_verify_paged",
            "serving.insert_paged", "serving.gather_row_paged",
            "serving.copy_page"} <= labels, labels
    # paged args without serve would warm nothing — must be loud.
    with pytest.raises(ValueError, match="serve"):
        run_warmup(cache=LowerOnlyCache(), emit_manifest=False,
                   preset="smoke", batch_size=2, seq_len=16, train=False,
                   serve=False, page_size=8)


def test_warmup_enumerates_spec_and_draft_programs(tmp_path):
    """run_warmup(spec_k=2, spec_draft='half') lists the fused verify AND the
    draft model's prefill/decode/insert programs in the manifest — a spec-enabled
    replica restart consumes them instead of compiling (CompileMonitor-gated via
    the zero-compile guard above; this asserts the manifest surface)."""
    from accelerate_tpu.analysis.program import LowerOnlyCache
    from accelerate_tpu.compile_cache.warmup import run_warmup

    cache = LowerOnlyCache()
    manifest = run_warmup(
        cache=cache, manifest_path=str(tmp_path / "m.json"),
        preset="smoke", batch_size=2, seq_len=16, train=False, eval_step=False,
        serve=True, max_slots=2, max_len=128, max_new_tokens=4,
        spec_k=2, spec_draft="half",
    )
    assert manifest["spec_k"] == 2 and manifest["spec_draft"] == "half"
    labels = {e["label"] for e in manifest["programs"]}
    assert "serving.spec_verify" in labels, labels
    assert "serving.decode_multi" in labels  # spec-off restarts stay warm too
    assert {"serving.draft.decode", "serving.draft.prefill",
            "serving.draft.prefill_chunk", "serving.draft.insert_row"} <= labels, labels
    # spec_k without serve would warm nothing and stamp spec_k=0 — must be loud.
    with pytest.raises(ValueError, match="serve"):
        run_warmup(cache=LowerOnlyCache(), emit_manifest=False,
                   preset="smoke", batch_size=2, seq_len=16, train=False,
                   serve=False, spec_k=2)


# ------------------------------------------------------------------ warmup manifest


def test_warmup_cli_help():
    from accelerate_tpu.commands.accelerate_cli import get_parser

    with pytest.raises(SystemExit) as exc:
        get_parser().parse_args(["warmup", "--help"])
    assert exc.value.code == 0


_CONSUME_SCRIPT = r"""
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np, optax
from accelerate_tpu import Accelerator, CompileCacheConfig
from accelerate_tpu.compile_cache import build_model_config
from accelerate_tpu.data_loader import assemble_global_batch
from accelerate_tpu.models import llama
from accelerate_tpu.serving import ContinuousBatcher
from accelerate_tpu.telemetry import CompileMonitor

cc = CompileCacheConfig(enabled=True, cache_dir=sys.argv[1], serving_buckets=(8, 16))
cfg = build_model_config("smoke", 16)
acc = Accelerator(compile_cache_config=cc)
params = llama.init_params(cfg)
state = acc.create_train_state(params, optax.adamw(1e-4))
step = acc.build_train_step(lambda p, b: llama.loss_fn(p, b, cfg), max_grad_norm=1.0)
batch = assemble_global_batch({"tokens": np.zeros((2, 17), np.int32)}, acc.mesh)
mon = CompileMonitor().start()
state, _ = step(state, batch)
mon.stop()
train_stats = dict(acc.compile_cache.stats())
engine = ContinuousBatcher(llama.init_params(cfg), cfg, max_slots=2, max_len=48,
                           compile_cache=acc.compile_cache)
engine.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=4)
engine.run()
print("RESULT " + json.dumps({
    "train": train_stats,
    "final": acc.compile_cache.stats(),
    "train_compiles": mon.count if mon.supported else None,
}))
"""

_WARMUP_SCRIPT = r"""
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
from accelerate_tpu.compile_cache import CompileCacheConfig, run_warmup

cc = CompileCacheConfig(enabled=True, cache_dir=sys.argv[1], serving_buckets=(8, 16))
manifest = run_warmup(preset="smoke", batch_size=2, seq_len=16, serve=True,
                      max_slots=2, max_len=48, max_new_tokens=4, cache_config=cc)
print("RESULT " + json.dumps(manifest))
"""


def _run_isolated(script, cache_dir):
    """Run a driver in a FRESH interpreter: real process isolation (the thing
    the cache exists for), and no in-memory jax persistent-cache layer from
    earlier suite tests — an executable served by that layer serializes without
    object code, which AotCache._store correctly refuses to persist."""
    import subprocess
    import sys as _sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (
        " ".join(f for f in env.get("XLA_FLAGS", "").split()
                 if "host_platform_device_count" not in f)
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([_sys.executable, "-c", script, str(cache_dir)],
                         capture_output=True, text=True, timeout=500, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_warmup_populates_cache_consumed_by_fresh_run(tmp_path):
    """Acceptance: a warmup run (own process) populates entries that a
    subsequent Accelerator + serving construction in a FRESH process consume
    (hit counters > 0, zero XLA compiles for the train step)."""
    manifest = _run_isolated(_WARMUP_SCRIPT, tmp_path)
    assert manifest["programs"], "warmup enumerated no programs"
    assert all(e["status"] in ("miss", "hit", "memo") for e in manifest["programs"])
    with open(tmp_path / "warmup_manifest.json") as f:
        assert json.load(f)["schema"].startswith("accelerate_tpu.compile_cache.warmup")

    result = _run_isolated(_CONSUME_SCRIPT, tmp_path)
    assert result["train"]["hits"] > 0, result
    if result["train_compiles"] is not None:
        assert result["train_compiles"] == 0, result
    assert result["final"]["hits"] > result["train"]["hits"], result  # serving hit too
    assert result["final"]["misses"] == 0, result


# ------------------------------------------------------------------ telemetry fields


def test_compile_monitor_cache_fields():
    from accelerate_tpu.telemetry.compile_monitor import dispatch_cache_event

    mon = CompileMonitor().start()
    try:
        if not mon.supported:
            pytest.skip("this jax exposes no jax.monitoring API")
        dispatch_cache_event(hit=True, deserialize_s=0.002)
        dispatch_cache_event(hit=False)
        snap = mon.snapshot()
        assert snap["cache_hit"] == 1
        assert snap["cache_miss"] == 1
        assert snap["deserialize_ms"] == pytest.approx(2.0)
    finally:
        mon.stop()
    dispatch_cache_event(hit=True)  # detached: no effect
    assert mon.cache_hits == 1
