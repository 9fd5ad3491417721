"""Sliding-window (Mistral-style) attention: kernel band masking, model wiring, decode.

The flash kernels' grids do not VISIT kv tiles outside the (i-window, i] band — these
tests pin the numerics against an explicitly-masked XLA reference, including gradients
(the tiles left out must contribute exactly zero), the grids' extents at the train cell's
shapes, the model forward (flash vs xla impl parity), and the KV-cache decode path
(windowed cached logits == windowed uncached logits).
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from accelerate_tpu.models import llama
from accelerate_tpu.ops.flash_attention import flash_attention
from accelerate_tpu.test_utils.testing import slow

CFG = dataclasses.replace(
    llama.CONFIGS["tiny"], dtype=jnp.float32, sliding_window=24, max_seq=128
)


def _band_mask(S, window):
    i = np.arange(S)
    return ((i[None, :] <= i[:, None]) & (i[None, :] > i[:, None] - window))[None]


def _ref_attention(q, k, v, mask):
    H, K = q.shape[2], k.shape[2]
    if H != K:
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.asarray(mask)[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhst,bthd->bshd", p, v)


@pytest.mark.parametrize("S,window,block", [
    (96, 24, None), (128, 64, None), (64, 200, None),
    # several tiles a row, so the kv walk is shorter than the row of tiles: the window a
    # multiple of the tile, not a multiple with a ragged S, and narrower than one tile
    (1024, 256, 128), (640, 130, 128), (700, 50, 64),
])
def test_flash_window_matches_masked_reference(S, window, block):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, S, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, S, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, S, 2, 32)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window, block_q=block, block_k=block)
    ref = _ref_attention(q, k, v, _band_mask(S, window))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


@slow
def test_flash_window_gradients_match():
    rng = np.random.default_rng(1)
    S, window = 96, 24
    q = jnp.asarray(rng.normal(size=(1, S, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, S, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, S, 2, 32)), jnp.float32)
    mask = _band_mask(S, window)

    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, window=window) ** 2)

    def g(q, k, v):
        return jnp.sum(_ref_attention(q, k, v, mask) ** 2)

    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-3, err_msg=f"d{name}"
        )


def _pallas_grids(jaxpr, found=None):
    """{kernel name: grid} of every ``pallas_call`` in a jaxpr, nested ones included."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = tuple(eqn.params["grid_mapping"].grid)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_grids(sub, found)
    return found


@pytest.mark.parametrize("causal,window,fwd_dq,dkv", [
    (True, 4096, 10, 4 * 10),     # the band: 9 tiles, + 1 because the offsets are traced
    (True, 0, 16, 4 * 16),        # a triangle's widest row is the whole rectangle
    (False, 4096, 16, 4 * 16),    # a window alone bounds one side only
    (False, 0, 16, 4 * 16),
], ids=["causal_window", "causal", "window", "neither"])
def test_grids_walk_the_band_at_the_train_cell_shapes(causal, window, fwd_dq, dkv):
    """The counter that says the mechanism engages, read where it is static: the inner
    extent of each kernel's grid at [4, 8192] x 32 q / 8 kv heads, tiles of 512 (tracing
    only: nothing runs). The rectangle had 16 and 4 x 16 whatever the band."""
    B, S, H, K, hd = 4, 8192, 32, 8, 128
    q = jax.ShapeDtypeStruct((B, S, H, hd), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, S, K, hd), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window,
                               block_q=512, block_k=512).astype(jnp.float32).sum()

    grids = _pallas_grids(
        jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, kv, kv).jaxpr)
    assert grids == {
        "flash_fwd": (B, H, 16, fwd_dq),
        "flash_bwd_dq": (B, H, 16, fwd_dq),
        "flash_bwd_dkv": (B, K, 16, dkv),
    }


def test_model_forward_flash_equals_xla():
    params = llama.init_params(CFG)
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, size=(2, 48)), jnp.int32)
    flash_logits = llama.forward(
        params, tokens, dataclasses.replace(CFG, attn_impl="flash"), shard_activations=False
    )
    xla_logits = llama.forward(
        params, tokens, dataclasses.replace(CFG, attn_impl="xla"), shard_activations=False
    )
    np.testing.assert_allclose(
        np.asarray(flash_logits), np.asarray(xla_logits), atol=2e-4
    )


def test_window_changes_logits():
    """The window must actually bite: positions beyond it see different context."""
    params = llama.init_params(CFG)
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, size=(1, 64)), jnp.int32)
    narrow = llama.forward(params, tokens, dataclasses.replace(CFG, sliding_window=8),
                           shard_activations=False)
    full = llama.forward(params, tokens, dataclasses.replace(CFG, sliding_window=0),
                         shard_activations=False)
    # Early positions (< window) identical; late positions differ.
    np.testing.assert_allclose(np.asarray(narrow[:, :8]), np.asarray(full[:, :8]), atol=2e-5)
    assert float(jnp.max(jnp.abs(narrow[:, -1] - full[:, -1]))) > 1e-3


@slow
def test_cached_decode_matches_uncached_window():
    """Windowed KV-cache decode == windowed full forward at every step (greedy argmax and
    logits both)."""
    params = llama.init_params(CFG)
    rng = np.random.default_rng(4)
    S0 = 40  # > window so the band actually truncates context
    prompt = jnp.asarray(rng.integers(0, CFG.vocab_size, size=(1, S0)), jnp.int32)
    cache = llama.init_cache(CFG, 1, 64)
    logits_c, cache = llama.forward_cached(params, prompt, cache, CFG)
    logits_f = llama.forward(params, prompt, CFG, shard_activations=False)
    np.testing.assert_allclose(np.asarray(logits_c), np.asarray(logits_f), atol=3e-4)
    # two decode steps
    toks = prompt
    for _ in range(2):
        nxt = jnp.argmax(logits_f[:, -1:], axis=-1).astype(jnp.int32)
        toks = jnp.concatenate([toks, nxt], axis=1)
        logits_c, cache = llama.forward_cached(params, nxt, cache, CFG)
        logits_f = llama.forward(params, toks, CFG, shard_activations=False)
        np.testing.assert_allclose(
            np.asarray(logits_c[:, -1]), np.asarray(logits_f[:, -1]), atol=3e-4
        )


def test_mistral_logits_match_transformers():
    """Mistral == llama keys + sliding window: the llama converter plus
    cfg.sliding_window must reproduce transformers' MistralForCausalLM logits."""
    transformers = pytest.importorskip("transformers")
    import torch

    from accelerate_tpu.models.hf_interop import llama_config_from_hf, llama_from_hf

    hf_cfg = transformers.MistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=256,
        sliding_window=16, rms_norm_eps=1e-5, tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    model = transformers.MistralForCausalLM(hf_cfg).eval()
    cfg = llama_config_from_hf(
        hf_cfg, dtype=jnp.float32, remat=False, sliding_window=hf_cfg.sliding_window
    )
    params = llama_from_hf(model.state_dict(), cfg)
    tokens = np.random.default_rng(7).integers(0, hf_cfg.vocab_size, size=(2, 48))
    with torch.no_grad():
        hf_logits = model(torch.tensor(tokens)).logits.float().numpy()
    ours = np.asarray(
        llama.forward(params, jnp.asarray(tokens, jnp.int32), cfg, shard_activations=False)
    )
    np.testing.assert_allclose(ours, hf_logits, atol=2e-4, rtol=1e-3)


def test_sliding_window_works_with_sp_modes():
    """Sliding windows flow into the SP kernels with global offsets: a ring-attention
    model over an sp=8 mesh must equal the single-device banded forward."""
    import jax.sharding

    from accelerate_tpu.parallel import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(dp=1, sp=8))
    cfg = dataclasses.replace(CFG, attn_impl="ring", sliding_window=24)
    params = llama.init_params(cfg)
    tokens = jnp.asarray(
        np.random.default_rng(8).integers(0, cfg.vocab_size, size=(1, 64)), jnp.int32
    )
    ref = llama.forward(
        params, tokens, dataclasses.replace(cfg, attn_impl="xla"), shard_activations=False
    )
    with jax.set_mesh(mesh):
        out = jax.jit(
            lambda p, t: llama.forward(p, t, cfg, shard_activations=True)
        )(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-4)
