"""Paged KV plumbing: block manager (host), pool planes, and the Pallas kernel.

The engine-level parity suite lives in tests/test_serving_paged.py; this file covers
the pieces in isolation — free-list/refcount/COW accounting without jax, paged
write/read round-trips against the dense planes, and the paged-attention kernel
(interpret mode) against its jnp reference across GQA/quantized/window/softcap/T>1.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from accelerate_tpu.paged_kv import (
    BlockManager,
    KVBudgetError,
    PagePoolExhausted,
    pages_for,
)


# ------------------------------------------------------------------ block manager
def test_pages_for_ceil():
    assert pages_for(1, 8) == 1
    assert pages_for(8, 8) == 1
    assert pages_for(9, 8) == 2
    assert pages_for(0, 8) == 0


def test_admit_release_roundtrip():
    mgr = BlockManager(num_pages=8, page_size=4, max_slots=2, max_len=32)
    ids = mgr.admit(0, 10)  # 3 pages
    assert len(ids) == 3 and mgr.pages_in_use == 3
    assert (mgr.tables[0, :3] == ids).all()
    assert (mgr.tables[0, 3:] == mgr.SENTINEL).all()
    assert mgr.release_slot(0) == 3
    assert mgr.pages_in_use == 0 and (mgr.tables[0] == mgr.SENTINEL).all()
    # released pages are reusable
    ids2 = mgr.admit(1, 32)  # 8 pages — the whole pool
    assert len(ids2) == 8 and mgr.free_pages == 0


def test_free_list_exhaustion():
    mgr = BlockManager(num_pages=4, page_size=4, max_slots=3, max_len=32)
    mgr.admit(0, 12)  # 3 pages
    assert not mgr.can_admit(8)          # needs 2, has 1
    assert mgr.can_admit(4)              # needs 1
    with pytest.raises(PagePoolExhausted):
        mgr.admit(1, 8)
    # a request bigger than the whole pool is a budget error, not a wait
    with pytest.raises(KVBudgetError):
        mgr.demand(17)                   # 5 pages > 4
    with pytest.raises(KVBudgetError):
        mgr.can_admit(17)


def test_double_admit_same_slot_rejected():
    mgr = BlockManager(num_pages=4, page_size=4, max_slots=2, max_len=16)
    mgr.admit(0, 4)
    with pytest.raises(RuntimeError, match="still holds"):
        mgr.admit(0, 4)


def test_refcount_sharing_and_release():
    """Registry retain/release: shared pages survive lane release and free only
    when the last reference drops."""
    mgr = BlockManager(num_pages=8, page_size=4, max_slots=2, max_len=32)
    ids = mgr.admit(0, 16)               # 4 pages
    shared = ids[:2]
    mgr.retain(shared)                   # registry entry holds the first 2
    assert mgr.shared_pages() == 2
    assert mgr.release_slot(0) == 2      # only the unshared 2 freed
    assert mgr.pages_in_use == 2
    # an adopter increfs again; its release keeps the registry's pages live
    mgr.admit(1, 16, adopted=list(shared))
    assert mgr.shared_pages() == 2 and mgr.adopt_count == 2
    mgr.release_slot(1)
    assert mgr.pages_in_use == 2
    assert mgr.release(shared) == 2      # registry eviction frees them
    assert mgr.pages_in_use == 0


def test_cow_accounting():
    mgr = BlockManager(num_pages=8, page_size=4, max_slots=2, max_len=32)
    ids = mgr.admit(0, 16)
    mgr.retain(ids[:2])
    # adoption across a mid-page divergence counts a COW re-materialization
    mgr.release_slot(0)
    mgr.admit(1, 16, adopted=list(ids[:1]), cow_partial=True)
    assert mgr.cow_count == 1
    # registry-side partial copy draws a fresh owned page and counts too
    page = mgr.take_copy_page()
    assert page is not None and mgr.refcount[page] == 1
    assert mgr.cow_count == 2


def test_stats_shape():
    mgr = BlockManager(num_pages=4, page_size=8, max_slots=1, max_len=32)
    s = mgr.stats()
    for key in ("pages_total", "pages_free", "pages_in_use", "page_occupancy",
                "shared_pages", "alloc_count", "free_count", "cow_count",
                "adopt_count", "defer_count"):
        assert key in s, key


# ------------------------------------------------------------------ pool planes
def test_paged_write_read_roundtrip_matches_dense():
    """write_kv_paged + read_kv_paged reconstruct exactly what the dense planes
    hold at the same logical positions — including int8 quantization (bit-identical
    quantized values, same quant path)."""
    from accelerate_tpu.models.common import (
        kv_planes, paged_kv_planes, read_kv, read_kv_paged, write_kv,
        write_kv_paged,
    )

    rng = np.random.default_rng(0)
    B, C, K, hd, ps = 2, 24, 2, 8, 8
    P = B * C // ps
    for quantized in (False, True):
        dense = kv_planes(B, C, K, hd, jnp.float32, quantized)
        pool = paged_kv_planes(P, ps, K, hd, jnp.float32, quantized)
        tables = np.arange(P, dtype=np.int32).reshape(B, C // ps)
        positions = np.array([5, 11], np.int32)
        val = jnp.asarray(rng.standard_normal((B, 1, K, hd)).astype(np.float32))
        dense = write_kv(dense, "k", val, jnp.asarray(positions))
        pages = jnp.asarray(tables[np.arange(B), positions // ps])[:, None]
        offs = jnp.asarray(positions % ps)[:, None]
        pool = write_kv_paged(pool, "k", val, pages, offs)
        want = read_kv(dense, "k", jnp.float32)
        got = read_kv_paged(pool, "k", jnp.asarray(tables), C, jnp.float32)
        rows = np.arange(B)
        assert np.array_equal(np.asarray(want)[rows, positions],
                              np.asarray(got)[rows, positions]), quantized


def test_paged_write_sentinel_drops():
    """Writes through a SENTINEL table entry (unallocated logical page) must drop
    instead of corrupting page 0 — the engine's stale-entry safety contract."""
    from accelerate_tpu.models.common import paged_kv_planes, write_kv_paged

    pool = paged_kv_planes(2, 4, 1, 4, jnp.float32, False)
    val = jnp.ones((1, 1, 1, 4), jnp.float32)
    out = write_kv_paged(pool, "k", val, jnp.full((1, 1), 2, jnp.int32),
                         jnp.zeros((1, 1), jnp.int32))
    assert float(jnp.abs(out["k"]).sum()) == 0.0


def _stacked_planes(rng, one: dict, n_layers: int) -> dict:
    """``n_layers`` planes shaped like ``one``'s, stacked, each with bytes of its own."""
    def fill(x):
        vals = rng.integers(-100, 100, (n_layers, *x.shape))
        return jnp.asarray(vals.astype(np.float32)).astype(x.dtype)

    return {k: fill(v) for k, v in one.items()}


@pytest.mark.parametrize("layout", ["paged", "dense"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_stacked_write_lands_in_its_layer_and_a_dropped_one_nowhere(layout, quantized, layer):
    """A layer scan that carries the cache writes plane ``layer`` of the STACKED planes
    (``write_kv_paged`` / ``write_kv`` with ``layer``): the write changes that plane as
    the per-layer write would and no byte of another; through a sentinel page id (dense:
    a slot past the row's end) it changes no byte of ANY layer."""
    from accelerate_tpu.models.common import (
        kv_planes, paged_kv_planes, write_kv, write_kv_paged,
    )

    rng = np.random.default_rng(layer)
    L, B, K, hd, ps, P = 3, 2, 2, 8, 4, 6
    if layout == "paged":
        stack = _stacked_planes(rng, paged_kv_planes(P, ps, K, hd, jnp.bfloat16, quantized), L)
        write = lambda kv, where, at: write_kv_paged(  # noqa: E731
            kv, "k", val, jnp.asarray(where, jnp.int32)[:, None],
            jnp.asarray([1, 3], jnp.int32)[:, None], at)
        live, dropped = [4, 2], [P, P]
    else:
        stack = _stacked_planes(rng, kv_planes(B, P * ps, K, hd, jnp.bfloat16, quantized), L)
        write = lambda kv, where, at: write_kv(  # noqa: E731
            kv, "k", val, jnp.asarray(where, jnp.int32), at)
        live, dropped = [5, 17], [P * ps, P * ps]
    val = jnp.asarray(rng.standard_normal((B, 1, K, hd)), jnp.bfloat16)
    one = {k: v[layer] for k, v in stack.items()}
    want = jax.jit(lambda kv: write(kv, live, None))(one)
    got = jax.jit(lambda kv, at: write(kv, live, at))(stack, jnp.int32(layer))
    none = jax.jit(lambda kv, at: write(kv, dropped, at))(stack, jnp.int32(layer))
    for key in want:
        assert not np.array_equal(np.asarray(want[key]), np.asarray(one[key])), key
        for l in range(L):
            np.testing.assert_array_equal(
                np.asarray(got[key][l]), np.asarray(want[key] if l == layer else stack[key][l]))
            np.testing.assert_array_equal(np.asarray(none[key][l]), np.asarray(stack[key][l]))


# ------------------------------------------------------------------ Pallas kernel
def _build_pool(rng, B, K, hd, ps, P, MP, lens, quantized, dtype=jnp.float32):
    from accelerate_tpu.models.common import paged_kv_planes, write_kv_paged

    C = MP * ps
    pool = paged_kv_planes(P, ps, K, hd, dtype, quantized)
    tables = np.full((B, MP), P, np.int32)
    free = list(range(P))
    valid = np.zeros((B, C), bool)
    for b, L in enumerate(lens):
        for j in range(pages_for(L, ps)):
            tables[b, j] = free.pop()
        valid[b, :L] = True
    kv_k = rng.standard_normal((B, C, K, hd)).astype(np.float32)
    kv_v = rng.standard_normal((B, C, K, hd)).astype(np.float32)
    pos = np.arange(C)
    pages = np.where(valid, tables[np.arange(B)[:, None],
                                   np.minimum(pos // ps, MP - 1)], P)
    offs = (pos % ps)[None, :].repeat(B, 0)
    pool = {
        **write_kv_paged(pool, "k", jnp.asarray(kv_k), jnp.asarray(pages),
                         jnp.asarray(offs)),
        **write_kv_paged(pool, "v", jnp.asarray(kv_v), jnp.asarray(pages),
                         jnp.asarray(offs)),
    }
    return pool, jnp.asarray(tables), jnp.asarray(valid)


@pytest.mark.parametrize("T", [1, 3])
@pytest.mark.parametrize("quantized", [False, True])
def test_kernel_matches_reference(T, quantized):
    from accelerate_tpu.ops.paged_attention import (
        paged_attention, paged_attention_reference,
    )

    rng = np.random.default_rng(0)
    B, H, K, hd, ps, P, MP = 3, 4, 2, 16, 8, 10, 3
    lens = np.array([5, 20, 11])
    pool, tables, valid = _build_pool(rng, B, K, hd, ps, P, MP, lens, quantized)
    q = jnp.asarray(rng.standard_normal((B, T, H, hd)).astype(np.float32))
    positions = jnp.asarray((lens - T).astype(np.int32))
    kw = dict(page_size=ps, sm_scale=hd ** -0.5)
    ref = paged_attention_reference(q, pool, tables, positions, valid, **kw)
    out = paged_attention(q, pool, tables, positions, valid, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


@pytest.mark.parametrize("T", [1, 3])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_kernel_reads_its_layer_of_a_stacked_pool(T, quantized):
    """``paged_attention(..., layer=l)`` on the stacked pools of all layers is the
    kernel on ``pool[l]`` bit for bit, and the reference on ``pool[l]``, for every l —
    with a sentinel entry inside a lane's walked range, which is clamped into layer l's
    OWN pages (against P, not L·P). Every other layer is poisoned with NaN (an int8
    pool: NaN scales), so one page fetched from a wrong layer shows in the output."""
    from accelerate_tpu.ops.paged_attention import (
        paged_attention, paged_attention_reference,
    )

    rng = np.random.default_rng(11)
    L, B, H, K, hd, ps, P, MP = 3, 3, 4, 2, 16, 8, 10, 3
    lens = np.array([5, 20, 11])
    pools = [_build_pool(rng, B, K, hd, ps, P, MP, lens, quantized, jnp.bfloat16)
             for _ in range(L)]
    tables, valid = np.array(pools[0][1]), np.array(pools[0][2])
    # Lane 1's middle page was never allocated (a prefix-layout hole): its entry is the
    # sentinel, its slots are not valid, and the walk from page 0 to page 2 crosses it.
    tables[1, 1] = P
    valid[1, ps:2 * ps] = False
    q = jnp.asarray(rng.standard_normal((B, T, H, hd)), jnp.bfloat16)
    positions = jnp.asarray((lens - T).astype(np.int32))
    args = (jnp.asarray(tables), positions, jnp.asarray(valid))
    kw = dict(page_size=ps, sm_scale=hd ** -0.5)
    stacked_kernel = jax.jit(lambda pool, l: paged_attention(q, pool, *args, layer=l, **kw))
    poisoned = "k_scale" if quantized else "k", "v_scale" if quantized else "v"
    for l in range(L):
        own = pools[l][0]
        stack = {
            key: jnp.stack([
                pools[m][0][key] if m == l or key not in poisoned
                else jnp.full_like(pools[m][0][key], jnp.nan) for m in range(L)])
            for key in own
        }
        out = np.asarray(stacked_kernel(stack, jnp.int32(l)).astype(jnp.float32))
        alone = np.asarray(paged_attention(q, own, *args, **kw).astype(jnp.float32))
        ref = np.asarray(paged_attention_reference(q, own, *args, **kw).astype(jnp.float32))
        np.testing.assert_array_equal(out, alone)
        np.testing.assert_allclose(out, ref, atol=3e-2)
        # The gather path reads the same layer without slicing it out: bitwise pool[l].
        from accelerate_tpu.ops.paged_attention import gather_pages

        np.testing.assert_array_equal(
            np.asarray(gather_pages(stack, "k", args[0], MP * ps, jnp.float32, layer=l)),
            np.asarray(gather_pages(own, "k", args[0], MP * ps, jnp.float32)))


@pytest.mark.parametrize("window,softcap", [(7, 0.0), (0, 30.0), (5, 20.0)])
def test_kernel_window_and_softcap(window, softcap):
    from accelerate_tpu.ops.paged_attention import (
        paged_attention, paged_attention_reference,
    )

    rng = np.random.default_rng(1)
    B, H, K, hd, ps, P, MP = 2, 2, 1, 8, 8, 8, 4
    lens = np.array([9, 29])
    pool, tables, valid = _build_pool(rng, B, K, hd, ps, P, MP, lens, False)
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)).astype(np.float32))
    positions = jnp.asarray((lens - 1).astype(np.int32))
    kw = dict(page_size=ps, sm_scale=0.25, window=window, softcap=softcap)
    ref = paged_attention_reference(q, pool, tables, positions, valid, **kw)
    out = paged_attention(q, pool, tables, positions, valid, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


# The walk over a lane's live range (ISSUE 26). Toy geometry: pages of 8 slots, 7 table
# entries a lane (C = 56), blocks forced to 2 pages (16 slots) so a walk has several
# iterations and 7 is not a multiple of the block. A lane is (first valid slot, length,
# first query position); ``None`` is a freed lane as the multi-step engine leaves it —
# stale valid row, all-sentinel table row, parked at position C — and "empty" one whose
# valid row is empty.
_WALK_PS, _WALK_MP, _WALK_BLOCK = 8, 7, 2
_WALK_CASES = {
    "freed_lane_between_live_ones": dict(lanes=[(0, 20, 19), None, (0, 11, 10), "empty"]),
    "length_1": dict(lanes=[(0, 1, 0), (0, 30, 29)]),
    "length_on_block_boundary_and_one_past": dict(
        lanes=[(0, 16, 15), (0, 17, 16), (0, 32, 31), (0, 33, 32)]),
    "left_pad_crosses_block_boundary": dict(lanes=[(19, 45, 44), (5, 23, 22)]),
    "window_start_mid_block": dict(lanes=[(0, 50, 49), (3, 30, 29)], window=21),
    "T3_rows_straddle_block_boundary": dict(lanes=[(0, 34, 31), (2, 18, 15)], T=3),
    "table_not_a_multiple_of_block": dict(lanes=[(0, 56, 55), (41, 56, 55)]),
    "int8_with_window": dict(lanes=[(0, 50, 49), (9, 27, 26)], window=13, quantized=True),
    "sentinel_entries_above_hi": dict(lanes=[(0, 20, 19), (0, 4, 3)]),
}


def _walk_case(name):
    """(pool, tables, valid, positions, live mask, kwargs) of one case above."""
    case = _WALK_CASES[name]
    T, quantized = case.get("T", 1), case.get("quantized", False)
    ps, MP = _WALK_PS, _WALK_MP
    C = ps * MP
    rng = np.random.default_rng(sorted(_WALK_CASES).index(name))
    lanes = case["lanes"]
    B = len(lanes)
    K, hd, P = 2, 16, B * MP
    lens = np.array([l[1] if isinstance(l, tuple) else 25 for l in lanes])
    pool, tables, valid = _build_pool(rng, B, K, hd, ps, P, MP, lens, quantized)
    tables, valid = np.array(tables), np.array(valid)
    positions = np.zeros((B,), np.int32)
    live = np.zeros((B,), bool)
    for b, lane in enumerate(lanes):
        if isinstance(lane, tuple):
            valid[b, :lane[0]] = False      # the K/V under the pad stays in the pool
            positions[b], live[b] = lane[2], True
        else:
            tables[b], positions[b] = P, C
            if lane == "empty":
                valid[b] = False
    kw = dict(page_size=ps, sm_scale=hd ** -0.5, window=case.get("window", 0))
    return pool, tables, valid, positions, live, T, kw


def _force_block(monkeypatch, quantized, K=2, hd=16):
    from accelerate_tpu.ops import paged_attention as mod

    page_bytes = 2 * _WALK_PS * K * hd * (1 if quantized else 4)
    monkeypatch.setattr(mod, "_BLOCK_BYTES", _WALK_BLOCK * page_bytes)
    assert mod.block_pages(_WALK_PS, K, hd, 1 if quantized else 4, _WALK_MP) == _WALK_BLOCK


@pytest.mark.parametrize("name", list(_WALK_CASES))
def test_kernel_walks_live_range(name, monkeypatch):
    """The kernel against the reference on every live lane; zeros on a freed one."""
    from accelerate_tpu.ops.paged_attention import (
        paged_attention, paged_attention_reference,
    )

    pool, tables, valid, positions, live, T, kw = _walk_case(name)
    _force_block(monkeypatch, "k_scale" in pool)
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((len(live), T, 4, 16)).astype(np.float32))
    args = (q, pool, jnp.asarray(tables), jnp.asarray(positions), jnp.asarray(valid))
    ref = np.asarray(paged_attention_reference(*args, **kw))
    out = np.asarray(paged_attention(*args, **kw))
    np.testing.assert_allclose(out[live], ref[live], atol=2e-6)
    assert np.all(out[~live] == 0.0)


@pytest.mark.parametrize("name", list(_WALK_CASES))
def test_walk_range_covers_exactly_the_visible_pages(name):
    """``walk_range`` against the mask itself: the walk starts at the page of the first
    slot any query may see, holds the page of the last, and is as short as whole blocks
    allow. The host form (numpy) and the wrapper's (jax) agree."""
    from accelerate_tpu.ops.paged_attention import walk_range

    _, tables, valid, positions, live, T, kw = _walk_case(name)
    ps, window, C = kw["page_size"], kw["window"], valid.shape[1]
    slots = np.arange(C)
    first_valid = np.where(valid.any(1), valid.argmax(1), C)
    last_live = np.where(valid.any(1), C - 1 - valid[:, ::-1].argmax(1), -1)
    last_live = np.minimum(last_live, (tables < len(tables) * _WALK_MP).sum(1) * ps - 1)
    kwargs = dict(T=T, window=window, page_size=ps, block=_WALK_BLOCK)
    first, blocks, pages = walk_range(positions, first_valid, last_live, **kwargs)
    for b in range(len(live)):
        seen = np.zeros((C,), bool)
        for t in range(T):
            q_pos = positions[b] + t
            row = valid[b] & (slots <= q_pos) & (slots <= last_live[b])
            if window:
                row &= slots > q_pos - window
            seen |= row
        if not seen.any():
            assert blocks[b] == 0 and pages[b] == 0, (name, b)
            continue
        lo, hi = slots[seen].min() // ps, slots[seen].max() // ps
        assert (first[b], pages[b]) == (lo, hi - lo + 1), (name, b)
        assert blocks[b] == -(-(hi - lo + 1) // _WALK_BLOCK), (name, b)
    # an active lane has written its last query slot: the host needs no last_live
    host = walk_range(positions[live], first_valid[live], **kwargs)
    on_device = walk_range(jnp.asarray(positions), jnp.asarray(first_valid),
                           jnp.asarray(last_live), **kwargs)
    for got, dev, want in zip(host, on_device, (first, blocks, pages)):
        assert np.array_equal(got, want[live]) and np.array_equal(np.asarray(dev), want)


def test_kernel_compiles_once_for_every_length(monkeypatch):
    """Positions, valid rows and tables are data: two calls with other lengths, one
    trace and one program."""
    from accelerate_tpu.ops.paged_attention import paged_attention

    _force_block(monkeypatch, False)
    fn = jax.jit(lambda *a: paged_attention(
        *a, page_size=_WALK_PS, sm_scale=0.25, window=21, interpret=True))
    rng = np.random.default_rng(3)
    for name in ("length_1", "left_pad_crosses_block_boundary"):
        pool, tables, valid, positions, live, T, _ = _walk_case(name)
        q = jnp.asarray(rng.standard_normal((2, 1, 4, 16)).astype(np.float32))
        out = fn(q, pool, jnp.asarray(tables), jnp.asarray(positions), jnp.asarray(valid))
        assert np.isfinite(np.asarray(out)).all()
    assert fn._cache_size() == 1


def test_reference_matches_dense_attention_exactly():
    """The gather fallback is BITWISE the dense cached-attention math on the
    occupied slots — the foundation of the engine-level paged/dense parity."""
    import dataclasses

    from accelerate_tpu.models import llama
    from accelerate_tpu.ops.paged_attention import gather_pages

    cfg = dataclasses.replace(llama.CONFIGS["tiny"], dtype=jnp.float32)
    rng = np.random.default_rng(2)
    K, hd = cfg.n_kv_heads, cfg.head_dim
    B, ps, MP, P = 2, 8, 3, 6
    C = MP * ps
    lens = np.array([7, 19])
    pool, tables, valid = _build_pool(rng, B, K, hd, ps, P, MP, lens, False)
    q = jnp.asarray(rng.standard_normal((B, 1, cfg.n_heads, hd)).astype(np.float32))
    positions = jnp.asarray((lens - 1).astype(np.int32))
    ck = gather_pages(pool, "k", tables, C, jnp.float32)
    cv = gather_pages(pool, "v", tables, C, jnp.float32)
    got = llama._attention_cached(q, ck, cv, positions[:, None], valid, cfg)
    # dense layout of the same values
    dense_k = np.zeros((B, C, K, hd), np.float32)
    dense_v = np.zeros((B, C, K, hd), np.float32)
    dense_k[np.asarray(valid)] = np.asarray(ck)[np.asarray(valid)]
    dense_v[np.asarray(valid)] = np.asarray(cv)[np.asarray(valid)]
    want = llama._attention_cached(
        q, jnp.asarray(dense_k), jnp.asarray(dense_v), positions[:, None], valid, cfg
    )
    assert np.array_equal(np.asarray(got)[:, 0], np.asarray(want)[:, 0])


def test_forward_slots_paged_bitwise_dense():
    """llama.forward_slots_paged == forward_slots bitwise on CPU (gather path),
    T = 1 and T = 3, fp32 — the model-layer parity contract."""
    import dataclasses

    from accelerate_tpu.models import llama

    cfg = dataclasses.replace(llama.CONFIGS["tiny"], dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    B, max_len, ps = 2, 32, 8
    MP = max_len // ps
    dense = llama.init_cache(cfg, B, max_len)
    paged = llama.init_paged_cache(cfg, B, max_len, B * MP, ps)
    tables = np.arange(B * MP, dtype=np.int32).reshape(B, MP)
    rng = np.random.default_rng(0)
    pos = np.zeros((B,), np.int32)
    for _ in range(4):
        tok = rng.integers(1, cfg.vocab_size, (B, 1)).astype(np.int32)
        ld, dense = llama.forward_slots(params, tok, dense, jnp.asarray(pos), cfg)
        lp, paged = llama.forward_slots_paged(
            params, tok, paged, jnp.asarray(tables), jnp.asarray(pos), cfg, ps)
        assert np.array_equal(np.asarray(ld), np.asarray(lp))
        pos += 1
    seq = rng.integers(1, cfg.vocab_size, (B, 3)).astype(np.int32)
    ld, _ = llama.forward_slots(params, seq, dense, jnp.asarray(pos), cfg)
    lp, _ = llama.forward_slots_paged(
        params, seq, paged, jnp.asarray(tables), jnp.asarray(pos), cfg, ps)
    assert np.array_equal(np.asarray(ld), np.asarray(lp))


def test_sliding_window_paged_bitwise_dense():
    """Alternating banded/full layers (sliding_window + window_every) through the
    paged layout: the shared forward must band-limit exactly the layers the dense
    path bands — bitwise, both per-layer-loop and grouped-scan variants."""
    import dataclasses

    from accelerate_tpu.models import llama

    base = dataclasses.replace(
        llama.CONFIGS["tiny"], dtype=jnp.float32, sliding_window=8, window_every=2,
    )
    for scan in (False, True):
        cfg = dataclasses.replace(base, scan_layers=scan)
        params = llama.init_params(cfg, jax.random.PRNGKey(1))
        B, max_len, ps = 2, 32, 8
        MP = max_len // ps
        dense = llama.init_cache(cfg, B, max_len)
        paged = llama.init_paged_cache(cfg, B, max_len, B * MP, ps)
        tables = np.arange(B * MP, dtype=np.int32).reshape(B, MP)
        rng = np.random.default_rng(4)
        pos = np.zeros((B,), np.int32)
        for _ in range(12):  # run past the window so banding actually bites
            tok = rng.integers(1, cfg.vocab_size, (B, 1)).astype(np.int32)
            ld, dense = llama.forward_slots(params, tok, dense, jnp.asarray(pos), cfg)
            lp, paged = llama.forward_slots_paged(
                params, tok, paged, jnp.asarray(tables), jnp.asarray(pos), cfg, ps)
            assert np.array_equal(np.asarray(ld), np.asarray(lp)), scan
            pos += 1


def test_gpt_forward_slots_paged_bitwise_dense():
    """The gpt family shares the paged contract (cross-family drafts stay viable
    on a paged engine)."""
    import dataclasses

    from accelerate_tpu.models import gpt

    cfg = dataclasses.replace(
        gpt.CONFIGS["tiny"] if "tiny" in gpt.CONFIGS else gpt.GPTConfig(
            vocab_size=128, d_model=32, n_layers=2, n_heads=2, max_seq=64),
        dtype=jnp.float32)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    B, max_len, ps = 2, 16, 4
    MP = max_len // ps
    dense = gpt.init_cache(cfg, B, max_len)
    paged = gpt.init_paged_cache(cfg, B, max_len, B * MP, ps)
    tables = np.arange(B * MP, dtype=np.int32).reshape(B, MP)
    rng = np.random.default_rng(3)
    pos = np.zeros((B,), np.int32)
    for _ in range(3):
        tok = rng.integers(1, cfg.vocab_size, (B, 1)).astype(np.int32)
        ld, dense = gpt.forward_slots(params, tok, dense, jnp.asarray(pos), cfg)
        lp, paged = gpt.forward_slots_paged(
            params, tok, paged, jnp.asarray(tables), jnp.asarray(pos), cfg, ps)
        assert np.array_equal(np.asarray(ld), np.asarray(lp))
        pos += 1


# ---------------------------------------------------------------------- soak
def test_block_manager_soak_randomized_lifecycle():
    """ISSUE 10 satellite: randomized property test driving thousands of
    admit / prefix-register (retain + COW copy) / adopt / release / registry-
    evict / recovery-rebuild ops against one BlockManager, asserting after
    EVERY op: refcount conservation (each page's refcount equals exactly the
    references the mirrored lanes + registry hold), zero leaked pages (every
    page is free xor referenced; the free list and refcounts agree), and
    free-list integrity (no duplicates, all ids in range, nothing referenced).
    After every recovery rebuild — the registry drained FIRST, then the lanes,
    the ordering whose inversion caused the PR-9 negative-refcount regression
    — the pool must be exactly fully free."""
    rng = np.random.default_rng(7)
    mgr = BlockManager(num_pages=24, page_size=4, max_slots=4, max_len=48)
    lanes = {}      # slot → mirrored page-id list (what the lane references)
    registry = []   # mirrored page-id lists (what prefix entries reference)
    handoffs = []   # mirrored page-id lists detached into handoff records
    rebuilds = 0
    detaches = adoptions = 0

    def check_invariants():
        free = mgr._free
        assert len(set(free)) == len(free), "free list holds duplicates"
        assert all(0 <= p < mgr.num_pages for p in free)
        assert all(mgr.refcount[p] == 0 for p in free), "referenced page in free list"
        expect = np.zeros(mgr.num_pages, np.int64)
        for ids in lanes.values():
            for p in ids:
                expect[p] += 1
        for ids in registry:
            for p in ids:
                expect[p] += 1
        for ids in handoffs:
            for p in ids:
                expect[p] += 1
        assert (mgr.refcount == expect).all(), (
            f"refcount drift: manager {mgr.refcount.tolist()} vs "
            f"mirror {expect.tolist()}"
        )
        assert len(free) + int((expect > 0).sum()) == mgr.num_pages, "leaked pages"

    for step in range(4000):
        # ISSUE 12 satellite: the disagg handoff lifecycle rides the same
        # ledger — detach (lane → handoff record, refcounts conserved),
        # handoff release (terminal state), and the decode-side
        # import → adopt-read-only → import-release cycle.
        op = rng.choice(
            ["admit", "release", "register", "evict", "detach",
             "handoff_release", "import_adopt", "rebuild"],
            p=[0.24, 0.2, 0.14, 0.14, 0.08, 0.06, 0.09, 0.05],
        )
        if op == "admit":
            free_slots = [s for s in range(mgr.max_slots) if s not in lanes]
            if free_slots:
                slot = int(rng.choice(free_slots))
                n_tokens = int(rng.integers(1, mgr.max_len + 1))
                adopted = []
                cow = False
                if registry and rng.random() < 0.5:
                    entry = registry[int(rng.integers(len(registry)))]
                    max_adopt = min(len(entry),
                                    mgr.pages_for(n_tokens))
                    if max_adopt:
                        adopted = list(entry[: int(rng.integers(1, max_adopt + 1))])
                        cow = bool(rng.random() < 0.3)
                try:
                    if mgr.can_admit(n_tokens, n_adopted=len(adopted)):
                        ids = mgr.admit(slot, n_tokens, adopted=adopted,
                                        cow_partial=cow)
                        lanes[slot] = [int(p) for p in ids]
                except KVBudgetError:
                    pass
        elif op == "release" and lanes:
            slot = int(rng.choice(list(lanes)))
            mgr.release_slot(slot)
            del lanes[slot]
        elif op == "register" and lanes:
            slot = int(rng.choice(list(lanes)))
            lane = lanes[slot]
            k = int(rng.integers(1, len(lane) + 1))
            pages = lane[:k]
            mgr.retain(pages)
            entry = list(pages)
            if rng.random() < 0.4:
                dst = mgr.take_copy_page()  # partial-boundary COW copy
                if dst is not None:
                    entry.append(int(dst))
            registry.append(entry)
        elif op == "evict" and registry:
            entry = registry.pop(int(rng.integers(len(registry))))
            mgr.release(entry)
        elif op == "detach" and lanes:
            # Prefill-role export: the lane empties, its pages move to a
            # handoff record with refcounts CONSERVED (nothing freed).
            slot = int(rng.choice(list(lanes)))
            in_use_before = mgr.pages_in_use
            pages = mgr.detach_slot(slot)
            assert mgr.pages_in_use == in_use_before, "detach freed pages"
            assert [int(p) for p in pages] == lanes[slot]
            handoffs.append(lanes.pop(slot))
            detaches += 1
        elif op == "handoff_release" and handoffs:
            mgr.release(handoffs.pop(int(rng.integers(len(handoffs)))))
        elif op == "import_adopt":
            # Decode-side adoption: stage an import, the lane adopts the full
            # context pages read-only (+COW boundary), the import releases —
            # exactly ContinuousBatcher.adopt_handoff's accounting.
            free_slots = [s for s in range(mgr.max_slots) if s not in lanes]
            if free_slots:
                slot = int(rng.choice(free_slots))
                n_ctx = int(rng.integers(1, mgr.max_len // 2 + 1))
                n_src = mgr.pages_for(n_ctx)
                n_lane_tokens = min(mgr.max_len,
                                    n_ctx + int(rng.integers(1, 17)))
                n_full = n_ctx // mgr.page_size
                n_lane = mgr.pages_for(n_lane_tokens)
                if n_src + (n_lane - n_full) <= mgr.free_pages:
                    imp = mgr.import_pages(n_src)
                    ids = mgr.admit(
                        slot, n_lane_tokens, adopted=imp[:n_full],
                        cow_partial=n_ctx % mgr.page_size != 0,
                    )
                    mgr.release(imp)
                    lanes[slot] = [int(p) for p in ids]
                    adoptions += 1
        elif op == "rebuild":
            # The engine's recovery ordering: drain the registry against the
            # OLD pool FIRST, then handoff records, then the lanes — then
            # nothing may remain in use.
            rebuilds += 1
            while registry:
                mgr.release(registry.pop())
            while handoffs:
                mgr.release(handoffs.pop())
            for slot in list(lanes):
                mgr.release_slot(slot)
                del lanes[slot]
            assert mgr.pages_in_use == 0, "recovery leaked pages"
            assert len(mgr._free) == mgr.num_pages
            assert (mgr.refcount == 0).all()
        check_invariants()
    assert rebuilds >= 50  # the 0.05 arm actually exercised recovery
    assert detaches >= 50 and adoptions >= 50  # the handoff arms really ran


# ----------------------------------------- ownership adversarial scenarios
# Runtime twins of the graftflow flow-ownership fixtures (tests/
# test_graftflow.py): each static finding shape, driven against a real
# BlockManager to show the concrete damage the rule is guarding against.


def test_exception_mid_handoff_finally_releases():
    """The GOOD_FINALLY_RELEASE shape: a fault injected mid-handoff still
    returns every page because the release sits on the exception edge too."""
    mgr = BlockManager(num_pages=8, page_size=4, max_slots=2, max_len=32)
    mgr.admit(0, 12)
    with pytest.raises(RuntimeError):
        ids = mgr.detach_slot(0)
        try:
            raise RuntimeError("fault injected mid-handoff")
        finally:
            mgr.release(ids)
    assert mgr.pages_in_use == 0
    assert len(mgr._free) == mgr.num_pages


def test_exception_mid_handoff_without_release_leaks():
    """The BAD_EXCEPTION_EDGE_LEAK shape at runtime: a handler that swallows
    the fault without releasing leaves referenced pages no lane or record can
    reach — exactly what the static exception-edge check reports."""
    mgr = BlockManager(num_pages=8, page_size=4, max_slots=2, max_len=32)
    mgr.admit(0, 12)
    ids = mgr.detach_slot(0)
    try:
        raise RuntimeError("fault injected mid-handoff")
    except RuntimeError:
        pass  # forgot the release
    assert mgr.pages_in_use == 3  # leaked: referenced, but ownerless
    assert not mgr.can_admit(mgr.max_len)  # the pool is silently smaller
    mgr.release(ids)  # only the leaked local could ever repair it
    assert mgr.pages_in_use == 0


def test_double_release_trips_refcount_invariant():
    """The BAD_DOUBLE_RELEASE shape: the second release drives a refcount
    negative and the PR-9 invariant assertion fires at runtime — graftflow
    reports the same pair statically, before any pool sees it."""
    mgr = BlockManager(num_pages=8, page_size=4, max_slots=2, max_len=32)
    mgr.admit(0, 12)
    ids = [int(p) for p in mgr.detach_slot(0)]
    mgr.release(ids)
    with pytest.raises(AssertionError):
        mgr.release(ids)


def test_use_after_transfer_steals_new_owners_reference():
    """The BAD_USE_AFTER_TRANSFER shape: after ownership moved (registry
    entry), the old holder's release consumes the new owner's reference —
    the new owner's own legitimate finalize then corrupts the refcounts.
    Transfers are linear; the new owner's copy is the only live one."""
    mgr = BlockManager(num_pages=8, page_size=4, max_slots=2, max_len=32)
    mgr.admit(0, 12)
    ids = mgr.detach_slot(0)
    registry_entry = list(int(p) for p in ids)  # ownership transferred
    mgr.release(ids)  # old holder uses the moved value anyway
    with pytest.raises(AssertionError):
        mgr.release(registry_entry)  # new owner's finalize now goes negative


def test_zombie_lane_starves_the_pool():
    """The BAD_ZOMBIE_LANE_CLASS shape: lanes that admit but never finalize
    hold the pool hostage — no fault, no error, just a pool that can never
    admit again (PR-10). Finalizing restores every page."""
    mgr = BlockManager(num_pages=8, page_size=4, max_slots=4, max_len=32)
    mgr.admit(0, 16)
    mgr.admit(1, 16)
    assert mgr.free_pages == 0
    assert not mgr.can_admit(1)
    mgr.release_slot(0)
    mgr.release_slot(1)
    assert mgr.free_pages == mgr.num_pages
