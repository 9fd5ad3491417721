"""``models/keye.py`` over ``models/deepseek.py``'s grouped-query kind (QK-norm, rotary from three
position streams, a learned sparse selection over K/V pages, a softmax router in front of
the grouped expert product), the index kernel at key widths under a lane tile, the flash
forward kernel's per-pair mask, and the engine's landing of K, V and index-key planes — on
the CPU at toy widths (the published ratios: 8 query / 2 K-V heads of 16, 4 index heads
that keep 64 keys, 16 experts of which 4 a token, depth 2), against the plain reference in
``benchmarks/chipbench/families/KeyeVL2.py`` (float32 ``jax.numpy``, no cache, the whole
``[T, T]`` index-score matrix, its own top-k mask, its own routing). Everything runs in
float32 here, so the tolerances are reassociation only; what bfloat16 adds is the chip's
reading (PERF.md §2).
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import common, keye
from accelerate_tpu.models import deepseek as ds
from accelerate_tpu.ops import flash_attention as flash
from accelerate_tpu.ops import moe as moe_ops
from accelerate_tpu.ops import sparse_attention as sa
from accelerate_tpu.serving import ContinuousBatcher, _insert_row_paged
from benchmarks.chipbench import program_spans, run

FAMILY = run.load_family("KeyeVL2")
NAME = "keye-vl2-serve-d5"
SEED = 11
# float32 on both sides: what is left is the order of the sums (a grouped product against a
# per-expert loop, a softmax over gathered rows in score order against one under a mask)
ATOL = 2e-4


def toy(**over) -> dict:
    """The cell's configuration under its dry-run sizes (float32; 64 keys kept, pages of
    8, 32-value index keys: four to a pool row), with ``over`` on top."""
    c = {}
    for kind in ("configs", "dry_run"):
        with open(os.path.join(run.HERE, kind, f"{NAME}.json")) as f:
            c.update(json.load(f))
    return {**c, **over}


def reference_logits(c, ids, pos3=None) -> np.ndarray:
    """The plain reference's logits at every position of ``ids``."""
    rows = [(ids[:1], np.concatenate([ids[1:], [0]]).astype(np.int32))]
    return FAMILY.serve_reference(c, SEED, rows, len(ids), len(ids), pos3=pos3)[0]


def program(c):
    return FAMILY.program_config(c), FAMILY.gen_params(c, SEED, jnp.float32)


def tokens(c, n, seed=0):
    return np.random.default_rng(seed).integers(0, c["vocab_size"], size=(n,)).astype(np.int32)


# ------------------------------------------------------- (a) forward against reference
@pytest.mark.parametrize("over", [
    {}, {"assumed": {"qk_norm": False, "index_rope_dim": 16}},
    {"sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 2, "topk": 32}}],
    ids=["the_cut", "no_qk_norm", "two_index_heads_of_64"])
def test_forward_matches_the_plain_reference(over):
    """200 positions: three times ``topk`` 64, so the selection is live from the 65th
    query on and most queries drop most keys."""
    c = toy(**over)
    cfg, params = program(c)
    ids = tokens(c, 200)
    got = np.asarray(keye.forward(params, jnp.asarray(ids)[None], cfg))[0]
    np.testing.assert_allclose(got, reference_logits(c, ids), atol=ATOL)


def test_switches_off_give_another_function():
    """QK-norm, the selection, the position streams' sections and the router's
    renormalisation each move the logits, so the agreement above is of all of them."""
    c = toy()
    cfg, params = program(c)
    ids = jnp.asarray(tokens(c, 120, 1))[None]
    base = keye.forward(params, ids, cfg)
    for change in ({"qk_norm": False}, {"index_topk": 256}, {"norm_topk_prob": False},
                   {"rope_theta": 1e4}):
        other = keye.forward(params, ids, dataclasses.replace(cfg, **change))
        assert float(jnp.abs(other - base).max()) > 1e-3, change


# ------------------------------------- (b) chunked prefill, then paged decode, by hand
@pytest.mark.parametrize("n_prompt,n_served", [(23, 12), (230, 26)],
                         ids=["fewer_keys_than_topk", "several_times_topk"])
def test_prefill_in_chunks_then_paged_decode_match_the_reference_forward(n_prompt, n_served):
    """A left-padded prompt prefilled 16 tokens a chunk (index scores against the row,
    the radix cut, masked attention), the row landed — K, V and the four-keys-a-row index
    plane through one block table in random order — then one token a step (the index
    oracle over pages, the key-value sort, the gather, attention over the chosen rows),
    on both sides of the cut: a context that never reaches ``topk`` 64 keys, and one
    that starts at 3.6 times it."""
    c = toy()
    cfg, params = program(c)
    prompt, served = tokens(c, n_prompt, 1), tokens(c, n_served, 2)
    ref = reference_logits(c, np.concatenate([prompt, served]))
    bucket, ps, max_len, P = 16, 8, 288, 48
    total = -(-len(prompt) // bucket) * bucket
    pad = total - len(prompt)
    row = np.zeros((1, total), np.int32)
    row[0, pad:] = prompt
    mask = np.arange(total)[None] >= pad
    cache = keye.init_cache(cfg, 1, max_len)
    for s in range(0, total, bucket):
        logits, cache = keye.forward_cached(
            params, jnp.asarray(row[:, s:s + bucket]), cache, cfg,
            token_mask=jnp.asarray(mask[:, s:s + bucket]))
    np.testing.assert_allclose(np.asarray(logits)[0, 0], ref[len(prompt) - 1], atol=ATOL)
    pages = np.random.default_rng(2).permutation(P)[:max_len // ps].astype(np.int32)
    paged = _insert_row_paged(keye.init_paged_cache(cfg, 2, max_len, P, ps), cache,
                              jnp.asarray(pages), 1, page_size=ps, scan_layers=False)
    assert sorted(paged["layers"][0]) == ["index_k", "k", "v"]
    assert paged["layers"][0]["index_k"].shape == (P, 2, 128)
    tables = np.full((2, max_len // ps), P, np.int32)
    tables[1] = pages                                           # lane 0 stays free
    for j, tok in enumerate(served):
        pos = np.array([max_len, total + j], np.int32)          # the free lane is parked
        logits, paged = ds.forward_slots_paged(
            params, jnp.asarray([[0], [tok]], jnp.int32), paged, jnp.asarray(tables),
            jnp.asarray(pos), cfg, ps)
        np.testing.assert_allclose(np.asarray(logits)[1, 0], ref[len(prompt) + j], atol=ATOL)


def test_a_grouped_query_layer_without_an_indexer_attends_every_key():
    """``index_topk`` 0 is data like the other switches: no index plane, no index
    weights, prefill under the causal mask alone and decode straight through the block
    table — chunked prefill then paged decode equal the same config's whole forward."""
    cfg = dataclasses.replace(keye.CONFIGS["tiny"], index_topk=0)
    params = keye.init_params(cfg, jax.random.PRNGKey(2))
    assert "idx_wq" not in params["layers"][0]
    ids = tokens({"vocab_size": cfg.vocab_size}, 44, 7)
    want = np.asarray(keye.forward(params, jnp.asarray(ids)[None], cfg))[0]
    ps, max_len, P, n = 8, 64, 12, 32
    cache = keye.init_cache(cfg, 1, max_len)
    for s in range(0, n, 16):
        logits, cache = keye.forward_cached(params, jnp.asarray(ids[None, s:s + 16]), cache, cfg)
    np.testing.assert_allclose(np.asarray(logits)[0, 0], want[n - 1], atol=ATOL)
    pages = np.random.default_rng(3).permutation(P)[:max_len // ps].astype(np.int32)
    paged = _insert_row_paged(keye.init_paged_cache(cfg, 1, max_len, P, ps), cache,
                              jnp.asarray(pages), 0, page_size=ps, scan_layers=False)
    assert sorted(paged["layers"][0]) == ["k", "v"]
    for j in range(n, len(ids)):
        logits, paged = ds.forward_slots_paged(
            params, jnp.asarray(ids[None, j:j + 1]), paged, jnp.asarray(pages)[None],
            jnp.asarray([j], jnp.int32), cfg, ps)
        np.testing.assert_allclose(np.asarray(logits)[0, 0], want[j], atol=ATOL)


# ------------------------------------------------------------------ (c) the selection
def layer0(c, T, seed=3):
    """Layer 0's normed input on both sides (the embedding's norm), its reference weights,
    and the program's spec and layer."""
    cfg, params = program(c)
    fc = dict(FAMILY.freeze(c))
    w = FAMILY.gen_layer(fc, jax.random.fold_in(FAMILY.seed_key(SEED), 0), jnp.float32, False)
    x = FAMILY.gen_ends(fc, FAMILY.seed_key(SEED), jnp.float32)["embed"][tokens(c, T, seed)]
    return FAMILY._rms(x, w["input_layernorm"], c["rms_norm_eps"]), w, fc, cfg, params


def test_the_programs_selected_sets_are_the_references():
    """Every query's SET: prefill's ``_select_mask`` (a radix cut, no sort) for all 150
    queries, and decode's (the oracle's scores over pages, ``_top_rows``) for the last."""
    c, T = toy(), 150
    h, w, fc, cfg, params = layer0(c, T)
    pos3 = jnp.broadcast_to(jnp.arange(T), (3, T))
    want = np.asarray(FAMILY.selection(h, w, fc, pos3))
    topk = c["sa_config"]["topk"]
    assert want.sum(1).tolist() == [min(t + 1, topk) for t in range(T)]

    spec, layer = cfg.attn_spec(0), params["layers"][0]
    pos = jnp.arange(T)[None]
    q_idx, k_idx, w_idx = ds._index_project(h[None], h[None], layer, pos, spec)
    got = ds._select_mask(q_idx, w_idx, k_idx, pos, jnp.ones((1, T), bool), jnp.int32(T), spec)
    assert (np.asarray(got)[0] == want).all()

    ps, P, C = 8, 24, 152
    pages = np.random.default_rng(4).permutation(P)[:C // ps].astype(np.int32)
    pool = sa.write_index_paged(
        jnp.zeros(sa.index_pool_shape(P, ps, spec.index_dim)), k_idx,
        jnp.asarray(pages)[None, np.arange(T) // ps], jnp.asarray(np.arange(T) % ps)[None])
    scores = sa.dsa_index_scores_reference(
        q_idx[:, -1], w_idx[:, -1], pool, jnp.asarray(pages)[None],
        jnp.asarray([T - 1]), jnp.ones((1, C), bool), page_size=ps)
    live, page, off = ds._top_rows(scores, jnp.asarray(pages)[None], ps, topk, P)
    slot = {int(p) * ps + o: t for t, (p, o) in enumerate(
        zip(pages[np.arange(T) // ps], np.arange(T) % ps))}
    chosen = {slot[int(p) * ps + int(o)] for p, o in zip(np.asarray(page)[0], np.asarray(off)[0])}
    assert bool(np.asarray(live).all()) and chosen == set(np.flatnonzero(want[-1]).tolist())


@pytest.mark.parametrize("n_keys", [1, 5, 64, 65, 700, 1024, 1025, 2049, 4096, 8192])
def test_the_radix_cut_is_the_sorted_cut_ties_to_the_earlier_key(n_keys):
    """``_top_k_mask`` (prefill's cut) over rounded scores — ties abound, at the cut too,
    and -0.0 beside 0.0 — keeps exactly what a stable sort keeps, at every fill of the row
    and on every branch of its narrowing (C/8, C/4, C/2, C)."""
    C, k, T = 8192, 64, 3
    rng = np.random.default_rng(n_keys)
    scores = np.full((1, T, C), -np.inf, np.float32)
    scores[..., :n_keys] = rng.normal(size=(1, T, n_keys)).round(1) * rng.choice(
        [1.0, -0.0], size=(1, T, n_keys)) + 0.0 * rng.choice([1.0, -1.0], size=(1, T, n_keys))
    order = np.argsort(-scores, axis=-1, kind="stable")[..., :k]
    want = np.zeros(scores.shape, bool)
    np.put_along_axis(want, order, True, -1)
    want &= np.isfinite(scores)
    got = jax.jit(ds._top_k_mask, static_argnums=2)(jnp.asarray(scores), jnp.int32(n_keys), k)
    assert (np.asarray(got) == want).all()


def test_top_rows_is_top_k_through_the_table():
    """``_top_rows``: the slots ``lax.top_k`` picks (ties to the earlier slot), as physical
    rows through a table in random order; a lane with fewer live slots than ``k`` marks
    its filler dead."""
    rng = np.random.default_rng(8)
    B, C, ps, P, k = 3, 64, 8, 20, 16
    scores = rng.normal(size=(B, C)).round(1).astype(np.float32)
    scores[1, 9:] = -np.inf                                   # nine live slots
    tables = np.stack([rng.permutation(P)[:C // ps] for _ in range(B)]).astype(np.int32)
    live, page, off = map(np.asarray, ds._top_rows(
        jnp.asarray(scores), jnp.asarray(tables), ps, k, P))
    vals, top = map(np.asarray, jax.lax.top_k(jnp.asarray(scores), k))
    assert (live == np.isfinite(vals)).all() and live[1].sum() == 9
    want = np.take_along_axis(tables, top // ps, 1) * ps + top % ps
    assert ((page * ps + off)[live] == want[live]).all()


# --------------------------------------------------------------- (d) position streams
def test_unequal_position_rows_match_the_reference():
    """An image in the middle of a text: 24 tokens whose (t, h, w) rows differ (one time
    step, a 4 x 6 grid), text before and after. The program's rotary takes the three rows
    (``forward(positions=)``) and agrees with the reference given the same rows; the
    selection's rotary reads row 0 on both sides."""
    c = toy()
    cfg, params = program(c)
    ids = tokens(c, 120, 5)
    t = np.concatenate([np.arange(40), np.full(24, 40), 41 + np.arange(56)])
    h = np.concatenate([np.arange(40), 40 + np.repeat(np.arange(4), 6), 41 + np.arange(56)])
    w = np.concatenate([np.arange(40), 40 + np.tile(np.arange(6), 4), 41 + np.arange(56)])
    pos3 = np.stack([t, h, w]).astype(np.int32)
    got = np.asarray(keye.forward(params, jnp.asarray(ids)[None], cfg,
                                  positions=jnp.asarray(pos3)[:, None]))[0]
    np.testing.assert_allclose(got, reference_logits(c, ids, pos3), atol=ATOL)
    text = np.asarray(keye.forward(params, jnp.asarray(ids)[None], cfg))[0]
    assert np.abs(got - text)[64:].max() > 1e-3          # the rows are read


def test_three_equal_rows_are_plain_rope():
    """Equal streams: every section reads the same position, which is RoPE over the whole
    head with one base — written out here pair by pair."""
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(2, 7, 3, 16)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, 500, size=(2, 7)))
    got = ds._mrope(x, jnp.broadcast_to(pos, (3, 2, 7)), 1e7, (2, 3, 3))
    freq = 1.0 / (1e7 ** (np.arange(0, 16, 2) / 16))
    ang = np.asarray(pos)[..., None, None] * freq
    x1, x2 = np.split(np.asarray(x), 2, -1)
    want = np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                           x2 * np.cos(ang) + x1 * np.sin(ang)], -1)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(ds._mrope(x, pos[None], 1e7, (2, 3, 3))),
                                  np.asarray(got))
    with pytest.raises(ValueError, match="mrope_section"):
        ds._mrope(x, pos[None], 1e7, (2, 3))


# ----------------------------------------------------------------------- (e) the router
@pytest.mark.parametrize("norm_topk", [True, False], ids=["renormalised", "raw"])
def test_softmax_router_in_front_of_the_grouped_product_is_a_per_token_loop(norm_topk):
    """``moe_mlp_grouped(router="softmax")``: 40 tokens, 16 experts, 4 a token, no shared
    expert and no bias — against a loop over tokens and their chosen experts."""
    rng = np.random.default_rng(7)
    T, D, F, E, k = 40, 32, 24, 16, 4
    draw = lambda *s: jnp.asarray(rng.normal(size=s) / np.sqrt(s[-2]), jnp.float32)  # noqa: E731
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    moe = {"router": draw(D, E), "experts": {"w_gate": draw(E, D, F), "w_up": draw(E, D, F),
                                             "w_down": draw(E, F, D)}}
    got, counts = moe_ops.moe_mlp_grouped(x, moe, top_k=k, norm_topk=norm_topk,
                                          compute_dtype=jnp.float32, router="softmax")
    gates, idx = moe_ops.router_softmax_topk(x, moe["router"], top_k=k, norm_topk=norm_topk)
    p = np.asarray(jax.nn.softmax(np.asarray(x) @ np.asarray(moe["router"]), -1))
    want = np.zeros((T, D), np.float32)
    for t in range(T):
        top = np.argsort(-p[t], kind="stable")[:k]
        assert sorted(np.asarray(idx)[t].tolist()) == sorted(top.tolist())
        g = p[t, top] / (p[t, top].sum() if norm_topk else 1.0)
        for e, ge in zip(top, g):
            ex = {n: np.asarray(v[e]) for n, v in moe["experts"].items()}
            a = np.asarray(x[t]) @ ex["w_gate"]
            want[t] += ge * ((a / (1 + np.exp(-a))) * (np.asarray(x[t]) @ ex["w_up"])) @ ex["w_down"]
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0 if norm_topk else p[
        np.arange(T)[:, None], np.asarray(idx)].sum(-1), atol=1e-6)
    assert np.asarray(counts).tolist()[:2] == [T * k, T]
    with pytest.raises(ValueError, match="router="):
        moe_ops.moe_mlp_grouped(x, moe, top_k=k, router="argmax")


# ------------------------------------------------------------- (f) the index kernel
WALKS = {"one_block": (1024, [37, 63, 64]), "blocks_of_two_pages": (16, [37, 63, 64]),
         "a_block_past_the_table": (24, [5, 40, 64]), "an_empty_lane": (16, [64, 64, 9])}


@pytest.mark.parametrize("index_dim", [64, 32, 128], ids=["two_a_row", "four_a_row", "one_a_row"])
@pytest.mark.parametrize("name", list(WALKS))
def test_index_kernel_at_keys_under_a_lane_tile_matches_its_oracle(name, index_dim, monkeypatch):
    """``dsa_index_scores`` over a pool whose rows hold 128 // ``index_dim`` keys side by
    side (Keye-VL-2.0's 64-value keys: two; dots3's 128: one — the same kernel): lanes with
    a left pad, tables in random order, a parked lane. Scores and ``-inf`` pattern are the
    oracle's at every block size; the pool was written key by key by ``write_index_paged``."""
    block, positions = WALKS[name]
    monkeypatch.setattr(sa, "_BLOCK_KEYS", block)
    rng = np.random.default_rng(5)
    B, Hi, ps, C, P = 3, 4, 8, 64, 40
    q = jnp.asarray(rng.normal(size=(B, Hi, index_dim)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(B, Hi)), jnp.float32)
    keys = jnp.asarray(rng.normal(size=(P, ps, index_dim)), jnp.float32)
    shape = sa.index_pool_shape(P, ps, index_dim)
    assert shape == (P, ps * index_dim // 128, 128)
    slot = np.arange(P * ps)
    pool = sa.write_index_paged(jnp.zeros(shape), keys.reshape(1, P * ps, index_dim),
                                jnp.asarray(slot // ps)[None], jnp.asarray(slot % ps)[None])
    np.testing.assert_array_equal(np.asarray(pool).reshape(P, ps, index_dim), np.asarray(keys))
    tables, valid = np.full((B, C // ps), P, np.int32), np.zeros((B, C), bool)
    perm = rng.permutation(P)
    for b, pos in enumerate(positions):
        if pos < C:
            n = pos // ps + 1
            tables[b, :n], perm = perm[:n], perm[n:]
            valid[b, 3 * b:pos + 1] = True
    args = (q, w, pool, jnp.asarray(tables), jnp.asarray(positions, jnp.int32),
            jnp.asarray(valid))
    got = np.asarray(sa.dsa_index_scores(*args, page_size=ps))
    want = np.asarray(sa.dsa_index_scores_reference(*args, page_size=ps))
    unpacked = np.asarray(sa.dsa_index_scores_reference(
        q, w, keys, *args[3:], page_size=ps))              # a key a row: the same scores
    assert (np.isfinite(got) == np.isfinite(want)).all()
    assert np.isfinite(want).sum(1).tolist() == [
        pos + 1 - 3 * b if pos < C else 0 for b, pos in enumerate(positions)]
    fin = np.isfinite(want)
    np.testing.assert_allclose(got[fin], want[fin], atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(want[fin], unpacked[fin])


def test_a_sentinel_page_drops_an_index_key_and_a_pool_of_another_width_is_refused():
    pool = jnp.zeros(sa.index_pool_shape(4, 8, 64))
    k = jnp.ones((1, 2, 64))
    out = sa.write_index_paged(pool, k, jnp.asarray([[4, 2]]), jnp.asarray([[1, 5]]))
    assert float(out.sum()) == 64 and float(out[2, 2, 64:].sum()) == 64   # key 5: row 2, right half
    with pytest.raises(ValueError, match="no pages of 8 keys of 64 values"):
        sa.dsa_index_scores(jnp.zeros((1, 2, 64)), jnp.zeros((1, 2)), jnp.zeros((4, 8, 128)),
                            jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
                            jnp.ones((1, 16), bool), page_size=8)
    with pytest.raises(ValueError, match="does not divide"):
        sa.index_pool_shape(4, 8, 48)


# ------------------------------------------------------- the flash kernel's pair mask
def dense_attention(q, k, v, seen):
    """[B,S,H,hd] x [B,T,K,hd] under ``seen`` [B,S,T], every head alike."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    s = np.einsum("bskgd,btkd->bkgst", np.asarray(q).reshape(B, S, K, H // K, hd),
                  np.asarray(k)) * hd ** -0.5
    s = np.where(seen[:, None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True)) * seen[:, None, None]
    p = p / np.maximum(p.sum(-1, keepdims=True), 1e-30)
    return np.einsum("bkgst,btkd->bskgd", p, np.asarray(v)).reshape(B, S, H, hd)


@pytest.mark.parametrize("index,window", [(0, 0), (256, 0), (384, 0), (256, 200)],
                         ids=["first_chunk", "third_chunk", "last_chunk", "under_a_window"])
def test_flash_forward_under_a_pair_mask_matches_masked_attention(index, window):
    """The forward kernel's masked specialisation in the prefill's call shape (a chunk of
    128 queries at ``q_offset`` against a 512-slot row, grouped heads, the valid mask as
    the segment pair, tiles of 128): a random per-pair mask — every head alike, some
    query rows with no key at all — ANDed with the causal band."""
    rng = np.random.default_rng(9)
    B, S, T, H, K, hd = 2, 128, 512, 4, 2, 16
    q, k, v = (jnp.asarray(rng.normal(size=s), jnp.float32)
               for s in ((B, S, H, hd), (B, T, K, hd), (B, T, K, hd)))
    valid = np.ones((B, T), bool)
    valid[1, :37] = False                                           # a left pad
    pair = rng.random((B, S, T)) < 0.3
    pair[0, 5] = False                                              # a query with no key
    got = flash._flash_bhsd_offset(
        q, k, v, q_offset=index, kv_offset=0, causal=True, window=window, block_q=128,
        block_k=128, interpret=True,
        segments=(jnp.ones((B, S), jnp.int32), jnp.asarray(valid, jnp.int32)),
        mask=jnp.asarray(pair, jnp.int8))
    row, col = index + np.arange(S)[:, None], np.arange(T)[None, :]
    band = (col <= row) & ((col > row - window) if window else True)
    seen = pair & band[None] & valid[:, None, :]
    np.testing.assert_allclose(np.asarray(got), dense_attention(q, k, v, seen), atol=2e-5)
    assert not np.asarray(got)[0, 5].any()


def test_a_call_without_a_mask_builds_the_kernel_it_built_before(monkeypatch):
    """The mask is a static specialisation: with it the kernel is ``flash_fwd_masked`` and
    takes one more operand; without it the name, the operands and the statics are the
    ones an unmasked call always had (``has_mask`` False, no pair ref)."""
    calls = []
    real = flash.pl.pallas_call

    def spy(kernel, *a, name=None, **kw):
        fn = real(kernel, *a, name=name, **kw)
        return lambda *ops: calls.append((name, len(ops), kernel.keywords["has_mask"])) or fn(*ops)

    monkeypatch.setattr(flash.pl, "pallas_call", spy)
    q = jnp.ones((1, 128, 2, 16))
    kv = jnp.ones((1, 256, 1, 16))
    seg = (jnp.ones((1, 128), jnp.int32), jnp.ones((1, 256), jnp.int32))
    flash._flash_bhsd_offset(q, kv, kv, q_offset=128, interpret=True, segments=seg)
    flash._flash_bhsd_offset(q, kv, kv, q_offset=128, interpret=True, segments=seg,
                             mask=jnp.ones((1, 128, 256), jnp.int8))
    assert calls == [("flash_fwd", 6, False), ("flash_fwd_masked", 7, True)]


def test_cached_prefill_attention_hands_the_selection_to_the_kernel():
    """``common.cached_prefill_attention(select=)`` with the kernel forced: the band of the
    row, the valid mask and the selection reach the kernel together and equal the
    family's masked attention; without ``impl='flash'`` off-TPU the call keeps XLA's."""
    rng = np.random.default_rng(10)
    B, T, C, H, K, hd = 1, 128, 512, 8, 2, 16
    q = jnp.asarray(rng.normal(size=(B, T, H, hd)), jnp.float32)
    ck, cv = (jnp.asarray(rng.normal(size=(B, C, K, hd)), jnp.float32) for _ in range(2))
    index = jnp.int32(256)
    valid = jnp.asarray(np.arange(C)[None] >= 9)
    slots = 256 + np.arange(T)
    seen = (rng.random((B, T, C)) < 0.2) & (np.arange(C)[None, None] <= slots[None, :, None])
    seen = jnp.asarray(seen) & valid[:, None, :]
    xla = lambda: ds._gqa_attend_dense(q, ck, cv, seen)                  # noqa: E731
    got = common.cached_prefill_attention(q, ck, cv, index, valid, impl="flash",
                                          sm_scale=hd ** -0.5, select=seen, xla_attention=xla)
    np.testing.assert_allclose(np.asarray(got), np.asarray(xla()), atol=2e-5)
    auto = common.cached_prefill_attention(q, ck, cv, index, valid, impl="auto",
                                           sm_scale=hd ** -0.5, select=seen, xla_attention=xla)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(xla()))


# ---------------------------------------------------------------------------- the engine
def engine(cfg, params, **kw):
    kw = {"max_slots": 4, "max_len": 384, "prompt_bucket": 16, "page_size": 8,
          "kv_pages": 120, "decode_steps": 4, **kw}
    return ContinuousBatcher(params, cfg, **kw)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Four prompts (5 to 300 tokens; 14 more each) through ``ContinuousBatcher`` on the toy
    configuration inside a profiler session → (config, prompts, requests, spans)."""
    c = toy()
    cfg, params = program(c)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, c["vocab_size"], size=(n,)).astype(np.int32)
               for n in (5, 58, 150, 300)]
    where = str(tmp_path_factory.mktemp("profile"))
    eng = engine(cfg, params)
    jax.profiler.start_trace(where)
    try:
        reqs = [eng.submit(p, max_new_tokens=14) for p in prompts]
        eng.run()
    finally:
        jax.profiler.stop_trace()
    return c, prompts, reqs, program_spans.load(where)


def test_engine_serves_the_references_greedy_tokens(served):
    """(b) through the engine: submit/step, ``BlockManager``'s one table under K, V and
    index-key planes, chunked prefill, the landing, 4-step paged decode — contexts of 5 to
    314 tokens, so a lane that never reaches ``topk`` 64 keys, one that crosses it while it
    decodes (58 + 14) and lanes at 2.5 and 5 times it decode side by side."""
    c, prompts, reqs, _ = served
    for prompt, req in zip(prompts, reqs):
        out = []
        for _ in range(14):     # one shape for every step: the same compiled reference
            rows = [(prompt, np.asarray(out + [0], np.int32))]
            logits = FAMILY.serve_reference(c, SEED, rows, 320, 15)[0, len(out)]
            out.append(int(logits.argmax()))
        assert list(req.tokens) == out


def test_engine_reports_the_selection_and_expert_counters(served):
    c, prompts, reqs, spans = served
    drains = [s for s in spans if s.name == "engine.decode.drain"]
    assert drains and set(keye.DECODE_COUNTERS) <= set(drains[0].attrs)
    assert keye.DECODE_COUNTERS[:3] == ds.DECODE_COUNTERS
    total = lambda n: sum(s.attrs[n] for s in drains)                          # noqa: E731
    steps = sum(len(r.tokens) - 1 for r in reqs)        # a lane's decode steps
    L, topk = c["num_hidden_layers"], c["sa_config"]["topk"]
    live = sum(len(p) + j + 1 for p, r in zip(prompts, reqs) for j in range(len(r.tokens) - 1))
    assert total("dsa_keys_scored") == L * live         # every live key, every layer
    kept = sum(min(len(p) + j + 1, topk) for p, r in zip(prompts, reqs)
               for j in range(len(r.tokens) - 1))
    assert total("dsa_keys_attended") == L * kept and total("window_keys_attended") == 0
    # the expert layer sees every lane of a step, live or parked: 4 lanes a step and layer
    assert total("moe_tokens") % (4 * L) == 0 and total("moe_tokens") >= L * steps
    assert total("moe_pairs") == c["num_experts_per_tok"] * total("moe_tokens")   # all held


@pytest.mark.parametrize("kw,what", [
    ({"page_size": 0, "kv_pages": None}, r"has no forward_slots: the engine's dense rows"),
    ({"spec_k": 2}, r"has no forward_slots: the engine's spec_k path"),
    ({"prefix_cache": 2}, r"has no forward_cached_logits: the engine's prefix_cache path"),
], ids=["dense_rows", "spec_k", "prefix_cache"])
def test_engine_refuses_by_name_the_paths_the_model_has_no_forward_for(kw, what):
    with pytest.raises(NotImplementedError, match=r"accelerate_tpu\.models\.keye .*" + what):
        engine(keye.CONFIGS["tiny"], {"not": "touched"}, **kw)


def test_the_seam_and_the_kind():
    """The engine reaches Keye through its config's module; every layer is the
    grouped-query kind, at the published sizes by default; ``kv_quant`` has no field."""
    from accelerate_tpu import serving

    assert serving._model(keye.CONFIGS["tiny"]) is keye
    spec = keye.KeyeConfig().attn_spec(47)
    assert isinstance(spec, ds.GqaSpec) and spec.kind == "gqa" and ds.CONFIGS["tiny"].kind == "latent"
    assert (spec.n_heads, spec.n_kv_heads, spec.head_dim) == (32, 4, 128)
    assert (spec.index_heads, spec.index_dim, spec.index_topk, spec.index_rope_dim) == (16, 64, 2048, 32)
    assert spec.mrope_section == (16, 24, 24) and sum(spec.mrope_section) == spec.head_dim // 2
    assert not hasattr(keye.KeyeConfig(), "kv_quant")
    cfg = keye.CONFIGS["tiny"]
    params = jax.eval_shape(lambda: keye.init_params(cfg, jax.random.PRNGKey(0)))
    assert sorted(params["layers"][0]["moe"]) == ["experts", "router"]     # no shared, no bias
    assert ds.paged_walk_shape(cfg, 8, 4, 48) == (sa.index_block_pages(8, 48), 0)
