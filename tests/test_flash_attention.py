"""Flash-attention kernel tests (interpret mode on CPU): forward + gradient parity vs the
pure-XLA reference attention, causal + non-causal, GQA, ragged lengths."""

import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from accelerate_tpu.ops.flash_attention import flash_attention


def reference_attention(q, k, v, causal=True):
    B, S, H, hd = q.shape
    K = k.shape[2]
    if H != K:
        reps = H // K
        k = jnp.repeat(k, reps, axis=2)
        v = jnp.repeat(v, reps, axis=2)
    T = k.shape[1]
    scores = jnp.einsum("bshd,bthd->bhst", q, k) / math.sqrt(hd)
    if causal:
        mask = jnp.tril(jnp.ones((S, T), dtype=bool))
        scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def make_qkv(B=2, S=128, H=4, K=4, hd=64, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), dtype=dtype)
    k = jnp.asarray(rng.normal(size=(B, S, K, hd)), dtype=dtype)
    v = jnp.asarray(rng.normal(size=(B, S, K, hd)), dtype=dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    q, k, v = make_qkv()
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_forward_gqa():
    q, k, v = make_qkv(H=8, K=2)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_forward_ragged_seq_len():
    # S=100 not a multiple of the block size → padding + masking path.
    q, k, v = make_qkv(S=100)
    out = flash_attention(q, k, v, causal=True, interpret=True, block_q=32, block_k=32)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_multiple_kv_blocks():
    q, k, v = make_qkv(S=256)
    out = flash_attention(q, k, v, causal=True, interpret=True, block_q=64, block_k=64)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_reference(causal):
    q, k, v = make_qkv(B=1, S=64, H=2, K=2, hd=32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, interpret=True, block_q=32, block_k=32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, err_msg=f"d{name} mismatch"
        )


def test_gradients_gqa():
    q, k, v = make_qkv(B=1, S=64, H=4, K=2, hd=32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, interpret=True, block_q=32, block_k=32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, err_msg=f"d{name} mismatch"
        )


def test_bf16_io_dtype():
    q, k, v = make_qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = reference_attention(q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32), np.asarray(ref), atol=3e-2)


def _segmented_reference(q, k, v, seg):
    """XLA reference with per-segment causal mask (fp32)."""
    import math as _math

    S = q.shape[1]
    hd = q.shape[-1]
    causal = np.tril(np.ones((S, S), bool))[None]
    same = (np.asarray(seg)[:, :, None] == np.asarray(seg)[:, None, :])
    live = (np.asarray(seg) != 0)[:, None, :]
    mask = jnp.asarray(causal & same & live)[:, None]  # [B,1,S,S]
    scores = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32), k.astype(jnp.float32))
    scores = jnp.where(mask, scores / _math.sqrt(hd), -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs, v.astype(jnp.float32))
    # Fully-masked rows (padding): softmax over all -1e30 gives a uniform distribution in
    # the reference; the flash kernel emits exact zeros there. Zero them to compare.
    any_live = (causal & same & live).any(-1)              # [B, S]
    return jnp.where(jnp.asarray(any_live)[:, :, None, None], out, 0.0)


def test_segment_forward_matches_reference():
    rng = np.random.default_rng(7)
    B, S, H, hd = 2, 96, 4, 64
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    seg = np.zeros((B, S), np.int32)
    seg[0, :40] = 1; seg[0, 40:77] = 2            # two segments + pad tail
    seg[1, :96] = 1                               # one full-row segment
    out = flash_attention(q, k, v, causal=True, segment_ids=jnp.asarray(seg))
    ref = _segmented_reference(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_segment_gradients_match_reference():
    rng = np.random.default_rng(8)
    B, S, H, hd = 1, 64, 2, 32
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    seg = np.zeros((B, S), np.int32)
    seg[0, :20] = 1; seg[0, 20:50] = 2
    segj = jnp.asarray(seg)
    w = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, segment_ids=segj) * w).sum()

    def f_ref(q, k, v):
        return (_segmented_reference(q, k, v, seg) * w).sum()

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, err_msg=f"d{name} mismatch"
        )


# ----------------------------------------------------------------- the band walk (PR 33)
# The kernels' inner grid dimension walks the band's tiles only, placed by index maps
# that compute the band's ends out of the traced offsets. Each case pins forward, dq, dk
# and dv against an explicitly masked XLA reference in GLOBAL positions.
def _band_reference(q, k, v, q_off, kv_off, causal, window, segs):
    S, T = q.shape[1], k.shape[1]
    row = q_off + np.arange(S)[:, None]
    col = kv_off + np.arange(T)[None, :]
    mask = np.ones((S, T), bool)
    if causal:
        mask &= col <= row
    if window:
        mask &= col > row - window
    mask = np.broadcast_to(mask, (q.shape[0], S, T))
    if segs is not None:
        q_seg, kv_seg = (np.asarray(s) for s in segs)
        mask = mask & (q_seg[:, :, None] == kv_seg[:, None, :]) & (kv_seg != 0)[:, None, :]
    reps = q.shape[2] // k.shape[2]
    kr, vr = jnp.repeat(k, reps, axis=2), jnp.repeat(v, reps, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q, kr) / math.sqrt(q.shape[-1])
    s = jnp.where(jnp.asarray(mask)[:, None], s, -1e30)
    out = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, axis=-1), vr)
    # a row with no live key: the kernel's contract is exact zeros
    return jnp.where(jnp.asarray(mask.any(-1))[:, :, None, None], out, 0.0)


def _prefill_segs(S, T):
    """The serving prefill's pair: every query live, the band's first slots not yet."""
    kv_seg = np.ones((1, T), np.int32)
    kv_seg[0, :37] = 0
    return np.ones((1, S), np.int32), kv_seg


BAND_CASES = {
    # name: (S, T, q_offset, kv_offset, causal, window, segments)
    "aligned_window": (1024, 1024, 0, 0, True, 256, None),
    "window_not_a_tile_multiple": (512, 512, 0, 0, True, 200, None),
    # q_offset - kv_offset = 301: not a multiple of the 128-wide tile
    "prefill_chunk_offsets_and_segment_pair": (256, 512, 837, 536, True, 256,
                                               _prefill_segs(256, 512)),
    "ragged_lengths_under_the_window": (300, 420, 120, 0, True, 130, None),
    "causal_without_window": (512, 512, 0, 0, True, 0, None),
    "causal_offset_rectangle": (256, 512, 200, 0, True, 0, None),
    "non_causal": (256, 384, 0, 0, False, 0, None),
    "window_without_causal": (384, 384, 0, 0, False, 160, None),
}


@pytest.mark.parametrize("name", list(BAND_CASES))
def test_band_walk_matches_reference(name):
    from accelerate_tpu.ops.flash_attention import _flash_bhsd_offset

    S, T, q_off, kv_off, causal, window, segs = BAND_CASES[name]
    rng = np.random.default_rng(len(name))
    q = jnp.asarray(rng.normal(size=(1, S, 4, 128)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, T, 2, 128)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, T, 2, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=q.shape), jnp.float32)

    def flash(q, k, v):
        # offsets traced, as the ring and the serving prefill pass them
        return jax.jit(lambda qo, ko: _flash_bhsd_offset(
            q, k, v, q_offset=qo, kv_offset=ko, causal=causal, window=window,
            block_q=128, block_k=128, interpret=True,
            segments=None if segs is None else tuple(jnp.asarray(s) for s in segs),
        ))(jnp.int32(q_off), jnp.int32(kv_off))

    def ref(q, k, v):
        return _band_reference(q, k, v, q_off, kv_off, causal, window, segs)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)), np.asarray(ref(q, k, v)), atol=2e-5)
    gf = jax.grad(lambda *a: (flash(*a) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: (ref(*a) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, err_msg=f"d{n}")


@pytest.mark.parametrize("q_off,kv_off", [(0, 512), (2048, 0)],
                         ids=["kv_block_in_the_future", "kv_block_behind_the_window"])
def test_a_kv_block_outside_the_band_still_writes_zeros(q_off, kv_off):
    """A ring step whose whole kv block no query may see has NO needed grid step, and
    ``ops/ring_attention.py`` merges what it returns: ``o`` = 0 and ``lse`` = ``_NEG_INF``
    from the forward, zeros from all three gradients — written by the walk's first and
    last step, whatever lies between."""
    from accelerate_tpu.ops.flash_attention import _NEG_INF, _bwd_dkv, _bwd_dq, _fwd

    rng = np.random.default_rng(5)
    q, do = (jnp.asarray(rng.normal(size=(1, 4, 256, 128)), jnp.float32) for _ in range(2))
    k, v = (jnp.asarray(rng.normal(size=(1, 2, 256, 128)), jnp.float32) for _ in range(2))
    args = (True, 128 ** -0.5, 128, 128, True)
    offs = dict(q_offset=jnp.int32(q_off), kv_offset=jnp.int32(kv_off), window=256)
    o, lse = jax.jit(lambda: _fwd(q, k, v, *args, **offs))()
    assert not np.asarray(o).any()
    np.testing.assert_array_equal(np.asarray(lse), np.float32(_NEG_INF))
    delta = jnp.zeros(lse.shape, jnp.float32)
    dq = jax.jit(lambda: _bwd_dq(q, k, v, do, lse, delta, *args, **offs))()
    dk, dv = jax.jit(lambda: _bwd_dkv(q, k, v, do, lse, delta, *args, **offs))()
    for g, n in zip((dq, dk, dv), "qkv"):
        assert g.shape == dict(q=q, k=k, v=v)[n].shape and not np.asarray(g).any(), f"d{n}"
