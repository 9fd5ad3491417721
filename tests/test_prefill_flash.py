"""A prefill chunk's attention against the row cache through the flash forward kernel
(``models/common.py::cached_prefill_attention``, ISSUE 31), on the CPU in interpret mode at
tiny widths: the kernel path agrees with ``llama._attention_cached`` on every query row that
has a live key; ``forward_cached`` chunk by chunk gives the same logits with
``attn_impl="flash"`` as with ``"xla"`` and as ``forward()`` over the whole prompt; only a
prefill's shapes reach the kernel; and a ``ContinuousBatcher`` serves the same greedy tokens
either way. Interpret mode proves the math, not that Mosaic takes the kernel: that compile
is in tests/test_kernel_names_tpu.py, the run on the chip in PERF.md.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import common, llama
from accelerate_tpu.serving import ContinuousBatcher

T, C, H, K, HD, PAD = 128, 512, 4, 1, 16, 40        # GQA 4:1; row 0 is left-padded by 40
TINY = dataclasses.replace(llama.CONFIGS["tiny"], dtype=jnp.float32, max_seq=512)


def attn_cfg(window, softcap):
    return dataclasses.replace(TINY, n_heads=H, n_kv_heads=K, d_model=H * HD,
                               sliding_window=window, attn_softcap=softcap)


def row_cache(index, seed=0, batch=2):
    """q for a chunk at ``index`` and a row cache that is live on [pad, index + T) and holds
    noise above (slots a later chunk will overwrite: causality must hide them)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (batch, T, H, HD), jnp.float32)
    ck = jax.random.normal(ks[1], (batch, C, K, HD), jnp.float32)
    cv = jax.random.normal(ks[2], (batch, C, K, HD), jnp.float32)
    slots = jnp.arange(C)[None]
    pads = jnp.array([PAD] + [0] * (batch - 1))[:, None]
    return q, ck, cv, (slots >= pads) & (slots < index + T)


def prefill(q, ck, cv, index, valid, cfg, impl="flash"):
    first = index[:, None] if jnp.ndim(index) else index
    positions = first + jnp.broadcast_to(jnp.arange(q.shape[1], dtype=jnp.int32), q.shape[:2])
    return common.cached_prefill_attention(
        q, ck, cv, index, valid, impl=impl, sm_scale=llama._sm_scale(cfg),
        window=cfg.sliding_window, softcap=cfg.attn_softcap,
        xla_attention=lambda: llama._attention_cached(q, ck, cv, positions, valid, cfg))


# --------------------------------------------------------------- (a) kernel against XLA
@pytest.mark.parametrize("softcap", [0.0, 30.0], ids=["nocap", "softcap"])
@pytest.mark.parametrize("window", [0, 96], ids=["full", "window96"])
@pytest.mark.parametrize("index", [0, 128, 384], ids=["first", "mid", "last"])
def test_kernel_agrees_with_attention_cached(index, window, softcap):
    cfg = attn_cfg(window, softcap)
    q, ck, cv, valid = row_cache(index, seed=index + window)
    got = jax.jit(lambda *a: prefill(*a, cfg))(q, ck, cv, jnp.int32(index), valid)
    want = prefill(q, ck, cv, jnp.int32(index), valid, cfg, impl="xla")
    slots, pos = jnp.arange(C)[None, None], (index + jnp.arange(T))[None, :, None]
    seen = valid[:, None] & (slots <= pos) & ((slots > pos - window) if window else True)
    has_key = seen.any(-1)                                             # [B, T]
    assert int((~has_key).sum()) == (PAD if index == 0 else 0)          # the left pad's rows
    np.testing.assert_allclose(np.where(has_key[..., None, None], got, 0),
                               np.where(has_key[..., None, None], want, 0), atol=2e-6)
    assert not np.any(np.where(has_key[..., None, None], 0, got))       # no key: zeros


def test_band_of_a_long_row_is_all_the_kernel_sees():
    """Window 96, chunk 128: the kernel is handed 224 of the row's 512 slots, whatever
    lies outside them."""
    cfg = attn_cfg(96, 0.0)
    q, ck, cv, valid = row_cache(384)
    want = prefill(q, ck, cv, jnp.int32(384), valid, cfg)
    outside = (jnp.arange(C) < 512 - 224)[None, :, None, None]
    got = prefill(q, jnp.where(outside, jnp.nan, ck), jnp.where(outside, jnp.nan, cv),
                  jnp.int32(384), valid, cfg)
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------------ (b) forward_cached, chunk by chunk
@pytest.mark.parametrize("scan_layers", [False, True], ids=["loop", "scan"])
def test_forward_cached_chunks_give_the_logits_of_xla_and_of_forward(scan_layers):
    base = dataclasses.replace(TINY, sliding_window=160, scan_layers=scan_layers)
    params = llama.init_params(base)
    rng = np.random.default_rng(3)
    n_chunks, pad = 3, 40
    tokens = jnp.asarray(rng.integers(1, base.vocab_size, (1, n_chunks * T)), jnp.int32)
    mask = jnp.arange(n_chunks * T)[None] >= pad

    def chunked(cfg):
        cache, out = llama.init_cache(cfg, 1, C), []
        for c in range(n_chunks):
            sl = slice(c * T, (c + 1) * T)
            logits, cache = llama.forward_cached(params, tokens[:, sl], cache, cfg,
                                                 token_mask=mask[:, sl])
            out.append(logits)
        return jnp.concatenate(out, axis=1)[:, pad:]

    flash = chunked(dataclasses.replace(base, attn_impl="flash"))
    xla = chunked(dataclasses.replace(base, attn_impl="xla"))
    whole = llama.forward(params, tokens[:, pad:], dataclasses.replace(base, attn_impl="xla"))
    np.testing.assert_allclose(flash, xla, atol=2e-4)
    np.testing.assert_allclose(flash, whole, atol=2e-4)


# ------------------------------------------------------------------------ (c) the switch
def pallas_calls(fn, *args) -> int:
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


@pytest.mark.parametrize("impl,chunk,vector,kernel", [
    ("flash", 128, False, True), ("flash", 256, False, True),
    ("xla", 128, False, False), ("auto", 128, False, False),       # auto: no TPU here
    ("ring", 128, False, False),                                   # an sp mode counts as auto
    ("flash", 1, False, False), ("flash", 4, False, False), ("flash", 100, False, False),
    ("flash", 128, True, False),
], ids=["T128", "T256", "xla", "auto-cpu", "ring-cpu", "T1", "T4", "T100", "vector-index"])
def test_only_a_prefill_reaches_the_kernel(impl, chunk, vector, kernel):
    cfg = attn_cfg(96, 0.0)
    q, ck, cv, valid = row_cache(128)
    q = jnp.resize(q, (2, chunk, H, HD))
    index = jnp.array([128, 130], jnp.int32) if vector else jnp.int32(128)
    assert pallas_calls(lambda *a: prefill(*a, cfg, impl), q, ck, cv, index, valid) == int(kernel)


@pytest.mark.parametrize("chunk", [1, 4], ids=["decode", "verify"])
def test_the_engines_per_lane_forward_never_reaches_the_kernel(chunk):
    cfg = dataclasses.replace(TINY, attn_impl="flash")
    params = llama.init_params(cfg)
    cache = llama.init_cache(cfg, 2, C)
    tokens = jnp.ones((2, chunk), jnp.int32)
    positions = jnp.array([5, 9], jnp.int32)
    assert pallas_calls(lambda c: llama.forward_slots(params, tokens, c, positions, cfg),
                        cache) == 0
    assert pallas_calls(lambda c: llama.forward_cached(params, tokens, c, cfg), cache) == 0
    full = jnp.ones((2, T), jnp.int32)
    assert pallas_calls(lambda c: llama.forward_cached(params, full, c, cfg),
                        cache) == cfg.n_layers


# ------------------------------------------------------------------- (d) the served tokens
def test_paged_engine_serves_the_same_tokens_with_flash_prefill():
    """A chunked prompt (3 chunks of 128), one bucket-wide prompt and a short one through a
    paged ``ContinuousBatcher``: the greedy tokens of ``attn_impl="flash"`` are those of
    ``"xla"``."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, TINY.vocab_size, n).astype(np.int32) for n in (300, 128, 9)]
    served = {}
    for impl in ("flash", "xla"):
        cfg = dataclasses.replace(TINY, attn_impl=impl, sliding_window=160)
        eng = ContinuousBatcher(llama.init_params(cfg), cfg, max_slots=2, max_len=C,
                                prompt_bucket=T, page_size=16, decode_steps=4)
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run()
        assert all(len(r.tokens) == 6 for r in reqs)
        served[impl] = [list(r.tokens) for r in reqs]
    assert served["flash"] == served["xla"]


# ------------------------------------------------------------------- under a device mesh
def test_under_a_mesh_the_kernel_runs_inside_shard_map():
    """A Mosaic call cannot be partitioned by GSPMD: under a multi-device mesh the prefill
    kernel runs under ``shard_map`` (rows over dp, heads over tp), as the training call does."""
    from accelerate_tpu.parallel import MeshConfig, build_mesh
    from accelerate_tpu.parallel.mesh import mesh_context

    cfg = dataclasses.replace(attn_cfg(96, 0.0), n_kv_heads=2)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (2, T, H, HD), jnp.float32)
    ck = jax.random.normal(ks[1], (2, C, 2, HD), jnp.float32)
    cv = jax.random.normal(ks[2], (2, C, 2, HD), jnp.float32)
    valid = jnp.broadcast_to((jnp.arange(C) >= 3) & (jnp.arange(C) < 384), (2, C))
    want = prefill(q, ck, cv, jnp.int32(256), valid, cfg, impl="xla")
    mesh = build_mesh(MeshConfig(dp=2, tp=2, devices=jax.devices()[:4]))
    with mesh_context(mesh):
        fn = jax.jit(lambda *a: prefill(*a, cfg))
        assert "shard_map" in str(jax.make_jaxpr(fn)(q, ck, cv, jnp.int32(256), valid))
        got = fn(q, ck, cv, jnp.int32(256), valid)
    np.testing.assert_allclose(got, want, atol=2e-6)
