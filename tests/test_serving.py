"""Continuous-batching engine: staggered admission must reproduce per-prompt greedy decode."""

import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from accelerate_tpu.generation import GenerationConfig
from accelerate_tpu.models import llama
from accelerate_tpu.serving import ContinuousBatcher

CFG = dataclasses.replace(llama.CONFIGS["tiny"], dtype=jnp.float32)


@pytest.fixture(scope="module")
def setup():
    params = llama.init_params(CFG)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, CFG.vocab_size, int(n)).astype(np.int32)
               for n in (5, 9, 3, 7, 6, 4)]
    return params, prompts


def reference_greedy(params, prompt, n):
    gen = GenerationConfig(max_new_tokens=n, temperature=0.0)
    return np.asarray(llama.generate(params, prompt[None], CFG, gen))[0].tolist()


def test_staggered_requests_match_individual_greedy(setup):
    """More requests than slots, admitted as lanes free: every output must equal the
    prompt's standalone greedy decode."""
    params, prompts = setup
    engine = ContinuousBatcher(params, CFG, max_slots=2, max_len=64, prompt_bucket=16)
    n_new = [6, 4, 8, 3, 5, 7]
    reqs = [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts, n_new)]
    done = engine.run()
    assert len(done) == len(reqs)
    for req, prompt, n in zip(reqs, prompts, n_new):
        assert req.done
        want = reference_greedy(params, prompt, n)
        assert req.tokens == want, (req.uid, req.tokens, want)


def test_mid_flight_submission(setup):
    """Submitting while other requests are mid-decode must not disturb them."""
    params, prompts = setup
    engine = ContinuousBatcher(params, CFG, max_slots=2, max_len=64, prompt_bucket=16)
    r0 = engine.submit(prompts[0], max_new_tokens=8)
    for _ in range(3):
        engine.step()
    r1 = engine.submit(prompts[1], max_new_tokens=5)  # admitted into the free slot
    done = engine.run()
    assert {r.uid for r in done} == {r0.uid, r1.uid}
    assert r0.tokens == reference_greedy(params, prompts[0], 8)
    assert r1.tokens == reference_greedy(params, prompts[1], 5)


def test_eos_frees_slot(setup):
    """A request hitting EOS finishes early and its lane admits the next request."""
    params, prompts = setup
    # Find what the first decode token is, use it as "EOS" to force immediate finish.
    first = reference_greedy(params, prompts[2], 1)[0]
    engine = ContinuousBatcher(params, CFG, max_slots=1, max_len=64, prompt_bucket=16)
    r_eos = engine.submit(prompts[2], max_new_tokens=10, eos_token_id=first)
    r_next = engine.submit(prompts[3], max_new_tokens=4)
    done = engine.run()
    assert r_eos.done and r_eos.tokens == [first]
    assert r_next.done and r_next.tokens == reference_greedy(params, prompts[3], 4)
    assert len(done) == 2


def test_oversized_prompt_rejected(setup):
    """Long prompts chunk-prefill, so rejection only happens when chunks + generation
    budget exceed the cache length."""
    params, _ = setup
    engine = ContinuousBatcher(params, CFG, max_slots=1, max_len=64, prompt_bucket=8)
    with pytest.raises(ValueError):
        engine.submit(np.arange(1, 62, dtype=np.int32) % CFG.vocab_size,
                      max_new_tokens=4)  # 8 chunks * 8 + 4 > 64


def test_prefix_cache_matches_generate_and_hits(setup):
    """Prefix caching (right-aligned layout): prompts sharing full-chunk prefixes reuse
    the registered snapshot, and every output still equals standalone greedy decode."""
    params, _ = setup
    rng = np.random.default_rng(7)
    system = rng.integers(1, CFG.vocab_size, 16).astype(np.int32)  # exactly 2 buckets
    suffix_a = rng.integers(1, CFG.vocab_size, 5).astype(np.int32)
    suffix_b = rng.integers(1, CFG.vocab_size, 9).astype(np.int32)
    engine = ContinuousBatcher(params, CFG, max_slots=2, max_len=64, prompt_bucket=8,
                               prefix_cache=4)

    pa = np.concatenate([system, suffix_a])
    ra = engine.submit(pa, max_new_tokens=5)
    engine.run()
    assert engine.prefix_hits == 0
    assert ra.tokens == reference_greedy(params, pa, 5)

    pb = np.concatenate([system, suffix_b])
    rb = engine.submit(pb, max_new_tokens=5)
    engine.run()
    assert engine.prefix_hits >= 1  # the 2-bucket system prefix was reused
    assert rb.tokens == reference_greedy(params, pb, 5)

    # Whole prompt == a registered prefix (exact multiple of the bucket).
    rc = engine.submit(system, max_new_tokens=5)
    engine.run()
    assert rc.tokens == reference_greedy(params, system, 5)


def test_prefix_cache_eviction_bounded(setup):
    params, _ = setup
    rng = np.random.default_rng(11)
    engine = ContinuousBatcher(params, CFG, max_slots=1, max_len=64, prompt_bucket=8,
                               prefix_cache=2)
    for _ in range(5):
        p = rng.integers(1, CFG.vocab_size, 10).astype(np.int32)
        req = engine.submit(p, max_new_tokens=3)
        engine.run()
        assert req.tokens == reference_greedy(params, p, 3)
    assert len(engine._prefix_reg) <= 2


def test_long_prompt_chunked_prefill_matches_generate(setup):
    """A prompt spanning 2.5 buckets prefills through the shared chunk-append executable
    and must still equal the standalone greedy decode."""
    params, _ = setup
    rng = np.random.default_rng(42)
    prompt = rng.integers(1, CFG.vocab_size, 20).astype(np.int32)  # 2.5 buckets of 8
    engine = ContinuousBatcher(params, CFG, max_slots=2, max_len=64, prompt_bucket=8)
    req = engine.submit(prompt, max_new_tokens=6)
    engine.run()
    assert req.done
    assert req.tokens == reference_greedy(params, prompt, 6)


def test_scan_layers_variant(setup):
    """The engine must handle the stacked-layer (scan_layers) cache layout too."""
    import jax

    params, prompts = setup
    cfg_scan = dataclasses.replace(CFG, scan_layers=True)
    params_scan = dict(params)
    params_scan["layers"] = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *params["layers"])
    engine = ContinuousBatcher(params_scan, cfg_scan, max_slots=2, max_len=64, prompt_bucket=16)
    reqs = [engine.submit(p, max_new_tokens=5) for p in prompts[:4]]
    engine.run()
    gen = GenerationConfig(max_new_tokens=5, temperature=0.0)
    for req, prompt in zip(reqs, prompts[:4]):
        want = np.asarray(llama.generate(params_scan, prompt[None], cfg_scan, gen))[0].tolist()
        assert req.tokens == want


def test_moe_engine_decode(setup):
    """MoE configs ride llama._block_cached's dense decode branch through the engine.

    Parity is against generate() at the SAME left-padded bucket width: MoE capacity
    pooling is shape-sensitive, so prefill at a different padded width routes tokens
    differently (a property of pooled MoE, not of the engine)."""
    _, prompts = setup
    moe_cfg = dataclasses.replace(llama.CONFIGS["moe-tiny"], dtype=jnp.float32)
    moe_params = llama.init_params(moe_cfg)
    bucket = 8
    engine = ContinuousBatcher(moe_params, moe_cfg, max_slots=2, max_len=48, prompt_bucket=bucket)
    gen = GenerationConfig(max_new_tokens=4, temperature=0.0)
    reqs = [engine.submit(p[:6], max_new_tokens=4) for p in prompts[:2]]
    engine.run()
    for req, prompt in zip(reqs, prompts[:2]):
        p = prompt[:6]
        padded = np.zeros((1, bucket), np.int32)
        padded[0, bucket - len(p):] = p
        pmask = np.zeros((1, bucket), bool)
        pmask[0, bucket - len(p):] = True
        want = np.asarray(llama.generate(
            moe_params, jnp.asarray(padded), moe_cfg, gen,
            prompt_mask=jnp.asarray(pmask),
        ))[0].tolist()
        assert req.tokens == want


def test_zero_new_tokens_rejected(setup):
    params, prompts = setup
    engine = ContinuousBatcher(params, CFG, max_slots=1, max_len=64, prompt_bucket=8)
    with pytest.raises(ValueError):
        engine.submit(prompts[2], max_new_tokens=0)


def test_sampled_request_matches_generate(setup):
    """A temperature/top-k request with a fixed key reproduces generate() exactly —
    the engine consumes the identical per-step key schedule."""
    import jax

    params, prompts = setup
    gen = GenerationConfig(max_new_tokens=6, temperature=0.8, top_k=12)
    rngs = [jax.random.PRNGKey(s) for s in (11, 22)]
    engine = ContinuousBatcher(params, CFG, max_slots=2, max_len=64, prompt_bucket=16)
    reqs = [engine.submit(p, gen=gen, rng=r) for p, r in zip(prompts[:2], rngs)]
    engine.run()
    for req, prompt, rng in zip(reqs, prompts[:2], rngs):
        pad = 16 - len(prompt)
        padded = np.zeros((1, 16), np.int32); padded[0, pad:] = prompt
        pmask = np.zeros((1, 16), bool); pmask[0, pad:] = True
        want = np.asarray(llama.generate(
            params, jnp.asarray(padded), CFG, gen,
            rng=rng, prompt_mask=jnp.asarray(pmask),
        ))[0].tolist()
        assert req.tokens == want, (req.tokens, want)


def test_sampled_top_p_matches_generate(setup):
    """top_p < 1 exercises the nucleus filter off its identity point."""
    import jax

    params, prompts = setup
    gen = GenerationConfig(max_new_tokens=5, temperature=0.7, top_p=0.8)
    rng = jax.random.PRNGKey(77)
    engine = ContinuousBatcher(params, CFG, max_slots=1, max_len=64, prompt_bucket=16)
    req = engine.submit(prompts[0], gen=gen, rng=rng)
    engine.run()
    pad = 16 - len(prompts[0])
    padded = np.zeros((1, 16), np.int32); padded[0, pad:] = prompts[0]
    pmask = np.zeros((1, 16), bool); pmask[0, pad:] = True
    want = np.asarray(llama.generate(
        params, jnp.asarray(padded), CFG, gen, rng=rng, prompt_mask=jnp.asarray(pmask)
    ))[0].tolist()
    assert req.tokens == want


def test_full_slot_table_admit_on_free(setup):
    """Cache-full admission with in-flight requests. With every slot
    busy, queued requests must wait (stats() reflects the pressure), admit the same step
    a lane frees, and still reproduce their standalone greedy decode."""
    params, prompts = setup
    engine = ContinuousBatcher(params, CFG, max_slots=2, max_len=64, prompt_bucket=16)
    n_new = [3, 6, 4, 5, 2]
    reqs = [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts[:5], n_new)]

    stats = engine.stats()
    assert stats["queued"] == 5 and stats["active_slots"] == 0

    all_done = []
    steps = 0
    while len(all_done) < len(reqs):
        done = engine.step()
        steps += 1
        stats = engine.stats()
        # The slot table never overfills; while work remains queued the table is full
        # except for lanes freed by THIS step's finishers (step() admits at its start,
        # so those lanes refill on the next call — the allowed one-step latency).
        assert stats["active_slots"] <= engine.max_slots
        if stats["queued"] > 0 and not done:
            assert stats["active_slots"] == engine.max_slots, (
                f"step {steps}: queue {stats['queued']} waiting on a free slot"
            )
        all_done += done
        assert steps < 60, "engine wedged"
    for req, prompt, n in zip(reqs, prompts[:5], n_new):
        assert req.tokens == reference_greedy(params, prompt, n), req.uid


def test_prefix_eviction_mid_flight_recompute(setup):
    """Prefix-cache eviction under pressure at compiled-shape
    boundaries. A prompt that IS a registered full-chunk prefix (no partial tail) whose
    penultimate-chunk snapshot has been LRU-evicted must take the _recompute_all path
    and still match the standalone decode — with other requests mid-decode."""
    params, _ = setup
    bucket = 16
    rng = np.random.default_rng(7)
    x = rng.integers(1, CFG.vocab_size, 2 * bucket).astype(np.int32)  # 2 full chunks
    y = rng.integers(1, CFG.vocab_size, bucket).astype(np.int32)      # 1 full chunk
    z = rng.integers(1, CFG.vocab_size, bucket + 3).astype(np.int32)  # chunk + tail

    engine = ContinuousBatcher(
        params, CFG, max_slots=2, max_len=64, prompt_bucket=bucket, prefix_cache=2
    )
    # 1) x registers prefixes [x[:16], x[:32]] (capacity 2 → registry full).
    r_x = engine.submit(x, max_new_tokens=4)
    engine.step()  # admit + first decode; x stays IN FLIGHT
    # 2) y registers y[:16], evicting x[:16] (LRU) while x still decodes.
    r_y = engine.submit(y, max_new_tokens=6)
    engine.step()
    assert engine.stats()["prefix_entries"] == 2
    # 3) Resubmit x: longest hit is x[:32] (the whole prompt, no tail) but the
    #    penultimate snapshot x[:16] is GONE → the last-chunk logits recovery must fall
    #    back to _recompute_all, not crash or corrupt the shared cache.
    r_x2 = engine.submit(x, max_new_tokens=5)
    # 4) z (chunk + partial tail) keeps the admission mix crossing shape boundaries.
    r_z = engine.submit(z, max_new_tokens=3)
    done = engine.run()
    assert {r.uid for r in done} == {r_x.uid, r_y.uid, r_x2.uid, r_z.uid}
    assert r_x.tokens == reference_greedy(params, x, 4)
    assert r_x2.tokens == reference_greedy(params, x, 5)
    assert r_y.tokens == reference_greedy(params, y, 6)
    assert r_z.tokens == reference_greedy(params, z, 3)
    stats = engine.stats()
    assert stats["prefix_hits"] >= 1  # the x[:32] whole-prompt hit
    assert stats["prefix_entries"] <= 2  # capacity respected under churn


def test_stats_queue_wait_and_enqueue_timestamps(setup):
    """Queue latency is observable without the gateway: every request records its
    enqueue time and stats() reports the oldest queued request's age."""
    params, prompts = setup
    engine = ContinuousBatcher(params, CFG, max_slots=1, max_len=64, prompt_bucket=16)
    assert engine.stats()["queue_wait_s"] == 0.0
    r0 = engine.submit(prompts[0], max_new_tokens=3)
    r1 = engine.submit(prompts[1], max_new_tokens=3)
    assert r0.enqueued_at > 0.0 and r1.enqueued_at >= r0.enqueued_at
    # Backdate the OLDEST request: stats must report ITS age, not the newest's.
    r0.enqueued_at -= 5.0
    wait = engine.stats()["queue_wait_s"]
    assert wait >= 5.0, wait
    engine.run()
    assert engine.stats()["queue_wait_s"] == 0.0  # empty queue again


def test_non_integral_max_new_tokens_rejected(setup):
    """A fractional/bool budget must raise at submit, not silently overrun its
    validated cache window and truncate at the slot boundary."""
    params, prompts = setup
    engine = ContinuousBatcher(params, CFG, max_slots=1, max_len=64, prompt_bucket=16)
    gen = GenerationConfig(max_new_tokens=3.5, temperature=0.0)
    with pytest.raises(TypeError, match="must be an int"):
        engine.submit(prompts[0], gen=gen)
    with pytest.raises(TypeError, match="must be an int"):
        engine.submit(prompts[0], gen=GenerationConfig(max_new_tokens=True))
    with pytest.raises(ValueError, match="max_new_tokens=-2"):
        engine.submit(prompts[0], max_new_tokens=-2)
    with pytest.raises(ValueError, match="empty prompt"):
        engine.submit(np.zeros((0,), np.int32), max_new_tokens=3)


def test_engine_cancel_queued_and_inflight(setup):
    """cancel(): queued requests never touch a slot; an in-flight request frees its
    lane for the very next step and keeps its partial tokens (done stays False)."""
    params, prompts = setup
    engine = ContinuousBatcher(params, CFG, max_slots=1, max_len=64, prompt_bucket=16)
    r0 = engine.submit(prompts[0], max_new_tokens=8)
    r1 = engine.submit(prompts[1], max_new_tokens=4)
    engine.step()  # r0 in flight, r1 queued
    assert engine.cancel(r1.uid)            # queued: removed outright
    assert engine.stats()["queued"] == 0
    engine.step()
    partial = len(r0.tokens)
    assert engine.cancel(r0.uid)            # in flight: lane freed immediately
    assert engine.stats()["active_slots"] == 0
    assert engine.stats()["evicted_external"] == 1
    assert not r0.done and len(r0.tokens) == partial
    assert not engine.cancel(r0.uid)        # already gone
    # The freed lane serves new work correctly.
    r2 = engine.submit(prompts[2], max_new_tokens=3)
    engine.run()
    assert r2.tokens == reference_greedy(params, prompts[2], 3)


def test_engine_on_token_streaming_parity(setup):
    """on_token delivers every token in generation order: the streamed transcript
    equals the final tokens list equals the standalone greedy decode."""
    params, prompts = setup
    engine = ContinuousBatcher(params, CFG, max_slots=2, max_len=64, prompt_bucket=16)
    streamed = {}
    reqs = []
    for i, (p, n) in enumerate(zip(prompts[:4], (6, 4, 8, 3))):
        streamed[i] = []
        reqs.append(engine.submit(p, max_new_tokens=n,
                                  on_token=streamed[i].append))
    engine.run()
    for i, (req, p, n) in enumerate(zip(reqs, prompts[:4], (6, 4, 8, 3))):
        assert streamed[i] == req.tokens == reference_greedy(params, p, n)
