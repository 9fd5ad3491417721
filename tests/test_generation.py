"""Generation: KV-cache decode parity, sampling, EOS masking, streamed decode.

Reference analog: the s/token decode path behind
``/root/reference/benchmarks/big_model_inference/README.md:25-37`` (transformers
``model.generate`` over dispatched models). The done-criterion: cached decode
== uncached argmax decode on the tiny config.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.generation import GenerationConfig, sample_logits
from accelerate_tpu.models import llama
from accelerate_tpu.test_utils.testing import slow, slow_mark


@pytest.fixture(scope="module")
def tiny():
    # f32, not the config default bf16: these tests assert EXACT token equality
    # between different programs (cached vs uncached, padded vs unpadded). That
    # equality holds in exact arithmetic (rope is relative), but under bf16 the
    # rotation tables round differently at shifted absolute positions (~3e-2
    # logit noise on this config) and greedy argmax near-ties flip — the
    # left-padded parity failure root-caused in ISSUE 4. Exactness contracts get
    # f32; bf16 behavior is covered by the tolerance-based tests.
    cfg = dataclasses.replace(
        llama.CONFIGS["tiny"], attn_impl="xla", dtype=jnp.float32
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


def _uncached_argmax_decode(params, prompt, cfg, steps):
    """Oracle: full re-forward per step, argmax over the last position."""
    tokens = jnp.asarray(prompt, jnp.int32)
    out = []
    for _ in range(steps):
        logits = llama.forward(params, tokens, cfg, shard_activations=False)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        out.append(nxt)
        tokens = jnp.concatenate([tokens, nxt[:, None]], axis=1)
    return jnp.stack(out, axis=1)


class TestCachedDecodeParity:
    @slow
    def test_cached_equals_uncached_argmax(self, tiny):
        cfg, params = tiny
        prompt = jnp.asarray(
            np.random.default_rng(0).integers(1, cfg.vocab_size, size=(2, 9)), jnp.int32
        )
        want = _uncached_argmax_decode(params, prompt, cfg, steps=6)
        got = llama.generate(
            params, prompt, cfg, GenerationConfig(max_new_tokens=6, temperature=0.0)
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @slow
    def test_cached_equals_uncached_with_scan_layers(self, tiny):
        cfg, _ = tiny
        scfg = dataclasses.replace(cfg, scan_layers=True)
        params = llama.init_params(scfg, jax.random.PRNGKey(7))
        prompt = jnp.asarray(
            np.random.default_rng(1).integers(1, scfg.vocab_size, size=(2, 5)), jnp.int32
        )
        want = _uncached_argmax_decode(params, prompt, scfg, steps=4)
        got = llama.generate(
            params, prompt, scfg, GenerationConfig(max_new_tokens=4, temperature=0.0)
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_left_padded_prompt_matches_unpadded(self, tiny):
        """Left-pads must not change the continuation (rope is relative; pads are masked)."""
        cfg, params = tiny
        rng = np.random.default_rng(2)
        core = rng.integers(1, cfg.vocab_size, size=(1, 7))
        prompt = jnp.asarray(core, jnp.int32)
        padded = jnp.concatenate([jnp.zeros((1, 3), jnp.int32), prompt], axis=1)
        mask = jnp.concatenate(
            [jnp.zeros((1, 3), jnp.bool_), jnp.ones((1, 7), jnp.bool_)], axis=1
        )
        gen = GenerationConfig(max_new_tokens=5, temperature=0.0)
        want = llama.generate(params, prompt, cfg, gen)
        got = llama.generate(params, padded, cfg, gen, prompt_mask=mask)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_prefill_logits_match_forward(self, tiny):
        """forward_cached over the prompt must reproduce forward()'s logits."""
        cfg, params = tiny
        tokens = jnp.asarray(
            np.random.default_rng(3).integers(1, cfg.vocab_size, size=(2, 8)), jnp.int32
        )
        want = llama.forward(params, tokens, cfg, shard_activations=False)
        cache = llama.init_cache(cfg, 2, 16)
        got, new_cache = llama.forward_cached(params, tokens, cache, cfg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2)
        assert int(new_cache["index"]) == 8
        assert bool(jnp.all(new_cache["valid"][:, :8]))
        assert not bool(jnp.any(new_cache["valid"][:, 8:]))


@slow_mark()
class TestMoECachedDecode:
    def test_moe_cached_equals_uncached_when_nothing_drops(self):
        """Decode uses drop-free dense routing; with a capacity factor generous enough that
        the pooled training path never drops either, the two must agree exactly."""
        cfg = dataclasses.replace(
            llama.CONFIGS["moe-tiny"], attn_impl="xla", moe_capacity_factor=16.0
        )
        params = llama.init_params(cfg, jax.random.PRNGKey(9))
        prompt = jnp.asarray(
            np.random.default_rng(8).integers(1, cfg.vocab_size, size=(3, 6)), jnp.int32
        )
        want = _uncached_argmax_decode(params, prompt, cfg, steps=4)
        got = llama.generate(
            params, prompt, cfg, GenerationConfig(max_new_tokens=4, temperature=0.0)
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestEosAndSampling:
    def test_eos_masks_tail(self, tiny):
        cfg, params = tiny
        prompt = jnp.asarray(
            np.random.default_rng(4).integers(1, cfg.vocab_size, size=(2, 6)), jnp.int32
        )
        ref = llama.generate(params, prompt, cfg, GenerationConfig(max_new_tokens=6))
        eos = int(np.asarray(ref)[0, 2])  # force EOS at the 3rd generated token of row 0
        got = np.asarray(
            llama.generate(
                params, prompt, cfg,
                GenerationConfig(max_new_tokens=6, eos_token_id=eos, pad_token_id=0),
            )
        )
        row = got[0]
        hits = np.where(row == eos)[0]
        assert len(hits) >= 1
        first = hits[0]
        assert (row[first + 1 :] == 0).all(), f"tail after EOS not padded: {row}"

    def test_temperature_sampling_reproducible_and_valid(self, tiny):
        cfg, params = tiny
        prompt = jnp.asarray(
            np.random.default_rng(5).integers(1, cfg.vocab_size, size=(3, 4)), jnp.int32
        )
        gen = GenerationConfig(max_new_tokens=5, temperature=0.8, top_k=20)
        a = llama.generate(params, prompt, cfg, gen, rng=jax.random.PRNGKey(11))
        b = llama.generate(params, prompt, cfg, gen, rng=jax.random.PRNGKey(11))
        c = llama.generate(params, prompt, cfg, gen, rng=jax.random.PRNGKey(12))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert np.asarray(a).shape == (3, 5)
        assert (np.asarray(a) >= 0).all() and (np.asarray(a) < cfg.vocab_size).all()
        assert not np.array_equal(np.asarray(a), np.asarray(c))  # different key, diff draw

    def test_top_k_restricts_support(self):
        logits = jnp.asarray([[0.0, 1.0, 2.0, 3.0, 4.0]])
        gen = GenerationConfig(temperature=1.0, top_k=2)
        draws = {
            int(sample_logits(logits, gen, jax.random.PRNGKey(i))[0]) for i in range(50)
        }
        assert draws <= {3, 4}

    def test_top_p_keeps_best_token(self):
        logits = jnp.asarray([[10.0, 0.0, 0.0, 0.0]])
        gen = GenerationConfig(temperature=1.0, top_p=0.1)
        tok = int(sample_logits(logits, gen, jax.random.PRNGKey(0))[0])
        assert tok == 0


class TestStreamedGeneration:
    def test_streamed_matches_in_memory(self, tiny, tmp_path):
        cfg, params = tiny
        from accelerate_tpu.big_modeling import dispatch_model

        n_top = len(params)
        device_map = {"embed": "cpu", "layers": "disk", "ln_f": 0, "lm_head": 0}
        assert n_top == len(device_map)
        dispatched = dispatch_model(params, device_map, offload_dir=str(tmp_path))
        prompt = jnp.asarray(
            np.random.default_rng(6).integers(1, cfg.vocab_size, size=(2, 5)), jnp.int32
        )
        gen = GenerationConfig(max_new_tokens=4, temperature=0.0)
        want = llama.generate(params, prompt, cfg, gen)
        got = llama.generate_streamed(dispatched, prompt, cfg, gen)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestSpeculative:
    """Greedy speculative decoding must equal plain greedy target decode exactly."""

    def _models(self):
        target_cfg = dataclasses.replace(llama.CONFIGS["tiny"], dtype=jnp.float32)
        draft_cfg = dataclasses.replace(
            llama.CONFIGS["tiny"], dtype=jnp.float32, n_layers=1, d_model=64,
            n_heads=2, n_kv_heads=1, d_ff=128,
        )
        return (llama.init_params(target_cfg, jax.random.PRNGKey(0)), target_cfg,
                llama.init_params(draft_cfg, jax.random.PRNGKey(1)), draft_cfg)

    @slow
    def test_matches_plain_greedy(self):
        tp, tc, dp, dc = self._models()
        rng = np.random.default_rng(0)
        for trial, (plen, n_new, k) in enumerate(((7, 12, 4), (3, 9, 2), (10, 15, 6))):
            prompt = rng.integers(1, tc.vocab_size, plen).astype(np.int32)
            got = np.asarray(llama.generate_speculative(
                tp, tc, dp, dc, prompt, max_new_tokens=n_new, k=k
            ))[0].tolist()
            want = np.asarray(llama.generate(
                tp, prompt[None], tc, GenerationConfig(max_new_tokens=n_new, temperature=0.0)
            ))[0].tolist()
            assert got == want, (trial, got, want)

    @slow
    def test_perfect_draft_accepts_everything(self):
        """Draft == target: every round accepts all k and emits k+1 tokens per target call."""
        tp, tc, _, _ = self._models()
        rng = np.random.default_rng(1)
        prompt = rng.integers(1, tc.vocab_size, 6).astype(np.int32)
        got = np.asarray(llama.generate_speculative(
            tp, tc, tp, tc, prompt, max_new_tokens=13, k=4
        ))[0].tolist()
        want = np.asarray(llama.generate(
            tp, prompt[None], tc, GenerationConfig(max_new_tokens=13, temperature=0.0)
        ))[0].tolist()
        assert got == want

    def test_eos_stops(self):
        tp, tc, dp, dc = self._models()
        rng = np.random.default_rng(2)
        prompt = rng.integers(1, tc.vocab_size, 5).astype(np.int32)
        full = np.asarray(llama.generate(
            tp, prompt[None], tc, GenerationConfig(max_new_tokens=10, temperature=0.0)
        ))[0].tolist()
        eos = full[3]
        got = np.asarray(llama.generate_speculative(
            tp, tc, dp, dc, prompt, max_new_tokens=10, k=3, eos_token_id=eos
        ))[0].tolist()
        assert got == full[:got.index(eos) + 1] if eos in got else got == full
        assert got[-1] == eos or len(got) == 10

    def test_accept_primitive_preserves_target_distribution(self):
        """The Leviathan accept/reject must output EXACTLY the target distribution p,
        whatever q the draft proposed from — asserted empirically over 200k vmapped
        trials (per-bucket tolerance ≈ 10σ of the binomial noise ≈ 0.004)."""
        from accelerate_tpu.generation import speculative_accept

        p = jnp.asarray([0.45, 0.30, 0.20, 0.05])
        q = jnp.asarray([0.10, 0.10, 0.40, 0.40])  # badly-matched draft

        n = 200_000
        keys = jax.random.split(jax.random.PRNGKey(0), n)
        draft_toks = jax.random.categorical(
            jax.random.PRNGKey(1), jnp.log(q), shape=(n,)
        )
        _, tokens = jax.vmap(lambda t, k: speculative_accept(p, q, t, k))(
            draft_toks, keys
        )
        counts = np.bincount(np.asarray(tokens), minlength=4) / n
        np.testing.assert_allclose(counts, np.asarray(p), atol=0.005)

    def test_accept_batch_vectorizes_the_same_math(self):
        """speculative_accept_batch (the serving engine's residual accept) is the
        scalar primitive vmapped: identical verdicts/tokens row-for-row, and the
        marginal output distribution stays exactly p — including the one-hot q a
        deterministic drafter induces (accept w.p. p(draft), residual = p minus
        the draft's mass)."""
        from accelerate_tpu.generation import (
            speculative_accept,
            speculative_accept_batch,
        )

        p_row = jnp.asarray([0.45, 0.30, 0.20, 0.05])
        q_row = jnp.asarray([0.10, 0.10, 0.40, 0.40])
        n = 4096
        keys = jax.random.split(jax.random.PRNGKey(2), n)
        drafts = jax.random.categorical(jax.random.PRNGKey(3), jnp.log(q_row), shape=(n,))
        acc_b, tok_b = speculative_accept_batch(
            jnp.broadcast_to(p_row, (n, 4)), jnp.broadcast_to(q_row, (n, 4)),
            drafts, keys,
        )
        acc_s, tok_s = jax.vmap(lambda t, k: speculative_accept(p_row, q_row, t, k))(
            drafts, keys
        )
        np.testing.assert_array_equal(np.asarray(acc_b), np.asarray(acc_s))
        np.testing.assert_array_equal(np.asarray(tok_b), np.asarray(tok_s))

        # One-hot q (deterministic drafter, the serving residual mode): output
        # distribution is still exactly p. 100k trials → binomial 10σ ≈ 0.005.
        m = 100_000
        keys = jax.random.split(jax.random.PRNGKey(4), m)
        drafts = jnp.full((m,), 2, jnp.int32)  # point mass on token 2
        q_onehot = jax.nn.one_hot(drafts, 4, dtype=jnp.float32)
        _, tokens = speculative_accept_batch(
            jnp.broadcast_to(p_row, (m, 4)), q_onehot, drafts, keys
        )
        counts = np.bincount(np.asarray(tokens), minlength=4) / m
        np.testing.assert_allclose(counts, np.asarray(p_row), atol=0.006)

    @slow
    def test_sampled_speculative_runs_and_needs_rng(self):
        tp, tc, dp, dc = self._models()
        prompt = np.asarray([3, 5, 7], np.int32)
        gen = GenerationConfig(max_new_tokens=8, temperature=0.8, top_k=16)
        with pytest.raises(ValueError, match="rng"):
            llama.generate_speculative(tp, tc, dp, dc, prompt, max_new_tokens=8, k=3,
                                       gen=gen)
        toks, stats = llama.generate_speculative(
            tp, tc, dp, dc, prompt, max_new_tokens=8, k=3, gen=gen,
            rng=jax.random.PRNGKey(7), return_stats=True,
        )
        toks = np.asarray(toks)[0]
        assert toks.shape == (8,)
        assert ((toks >= 0) & (toks < tc.vocab_size)).all()
        assert stats["target_dispatches"] == stats["rounds"] + 1

    def test_sampled_speculative_deterministic_per_key(self):
        tp, tc, dp, dc = self._models()
        prompt = np.asarray([3, 5, 7], np.int32)
        gen = GenerationConfig(max_new_tokens=6, temperature=0.7)
        a = np.asarray(llama.generate_speculative(
            tp, tc, dp, dc, prompt, max_new_tokens=6, k=3, gen=gen,
            rng=jax.random.PRNGKey(11),
        ))
        b = np.asarray(llama.generate_speculative(
            tp, tc, dp, dc, prompt, max_new_tokens=6, k=3, gen=gen,
            rng=jax.random.PRNGKey(11),
        ))
        np.testing.assert_array_equal(a, b)


class TestSpeculativeGPT:
    """Speculative decoding is family-generic: gpt targets/drafts (and cross-family
    pairs) ride the same cached-decode contract."""

    def _gpt_models(self):
        from accelerate_tpu.models import gpt

        tc = dataclasses.replace(gpt.CONFIGS["tiny"], dtype=jnp.float32)
        dc = dataclasses.replace(
            gpt.CONFIGS["tiny"], dtype=jnp.float32, n_layers=1, d_model=64, n_heads=2,
            d_ff=128,
        )
        return (gpt.init_params(tc, jax.random.PRNGKey(0)), tc,
                gpt.init_params(dc, jax.random.PRNGKey(1)), dc)

    def test_gpt_matches_plain_greedy(self):
        from accelerate_tpu.models import gpt

        tp, tc, dp, dc = self._gpt_models()
        rng = np.random.default_rng(0)
        prompt = rng.integers(1, tc.vocab_size, 7).astype(np.int32)
        got = np.asarray(gpt.generate_speculative(
            tp, tc, dp, dc, prompt, max_new_tokens=10, k=3
        ))[0].tolist()
        want = np.asarray(gpt.generate(
            tp, prompt[None], tc, GenerationConfig(max_new_tokens=10, temperature=0.0)
        ))[0].tolist()
        assert got == want

    @slow
    def test_cross_family_llama_draft(self):
        """A llama draft speculating for a gpt target (vocabularies match at 256):
        greedy output still equals the gpt target's own greedy decode."""
        from accelerate_tpu.models import gpt

        tp, tc, _, _ = self._gpt_models()
        dc = dataclasses.replace(
            llama.CONFIGS["tiny"], dtype=jnp.float32, n_layers=1, d_model=64,
            n_heads=2, n_kv_heads=1, d_ff=128,
        )
        assert dc.vocab_size == tc.vocab_size
        dp = llama.init_params(dc, jax.random.PRNGKey(2))
        rng = np.random.default_rng(1)
        prompt = rng.integers(1, tc.vocab_size, 6).astype(np.int32)
        got = np.asarray(gpt.generate_speculative(
            tp, tc, dp, dc, prompt, max_new_tokens=9, k=3
        ))[0].tolist()
        want = np.asarray(gpt.generate(
            tp, prompt[None], tc, GenerationConfig(max_new_tokens=9, temperature=0.0)
        ))[0].tolist()
        assert got == want


class TestStreamedPassTimes:
    def test_pass_times_contract(self, tiny, tmp_path):
        """The streamed-timing contract the big-model bench relies on (single-run
        s/token from the decode tail): pass_times receives prefill + one entry per
        decode pass, every entry positive, and collecting times does not change the
        decoded tokens."""
        cfg, params = tiny
        from accelerate_tpu.big_modeling import cpu_offload

        dispatched = cpu_offload(params)
        prompt = jnp.asarray(
            np.random.default_rng(6).integers(1, cfg.vocab_size, size=(2, 5)), jnp.int32
        )
        gen = GenerationConfig(max_new_tokens=4, temperature=0.0)
        want = llama.generate_streamed(dispatched, prompt, cfg, gen)
        times: list = []
        got = llama.generate_streamed(dispatched, prompt, cfg, gen, pass_times=times)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # llama/gpt loop: prefill emits token 1, then max_new_tokens-1 decode passes.
        assert len(times) == gen.max_new_tokens
        assert all(t > 0 for t in times)

    def test_pass_times_contract_t5(self):
        """t5's own loop: encoder pass first, then one entry per decode step."""
        from accelerate_tpu.big_modeling import cpu_offload
        from accelerate_tpu.models import t5

        cfg = dataclasses.replace(t5.CONFIGS["tiny"], dtype=jnp.float32)
        params = t5.init_params(cfg)
        inp = jnp.asarray(
            np.random.default_rng(0).integers(2, cfg.vocab_size, size=(1, 7)), jnp.int32
        )
        times: list = []
        out = t5.generate_streamed(cpu_offload(params), inp, cfg, max_new_tokens=5,
                                   pass_times=times)
        assert out.shape == (1, 5)
        assert len(times) == 1 + 5 and all(t > 0 for t in times)
