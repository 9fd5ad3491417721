"""``models/dots3.py`` over ``models/deepseek.py`` (layer kinds as data: full latent layers
with a learned sparse selection, sliding latent layers over a ring of pages, head-wise
gates, the latents' rescale), ``ops/sparse_attention.py``, and the engine's two kinds of
cache state — on the CPU at toy widths, against the plain reference in
``benchmarks/chipbench/families/dots3_note.py`` (float32 ``jax.numpy``, no cache, selects by
its own index scores, routes by its own scores). Everything runs in float32 here, so the
tolerances are reassociation only; what bfloat16 adds is the chip's reading (PERF.md §2).
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import common, dots3
from accelerate_tpu.models import deepseek as ds
from accelerate_tpu.ops import moe as moe_ops
from accelerate_tpu.ops import sparse_attention as sa
from accelerate_tpu.serving import ContinuousBatcher, _insert_row_paged
from benchmarks.chipbench import program_spans, run

FAMILY = run.load_family("dots3_note")
NAME = "dots3-note-serve-ep8-d5"
SEED = 11
# float32 on both sides: what is left is the order of the sums (absorbed against
# up-projected attention, a grouped product against a per-token loop, a softmax over the
# selected rows in score order against one over all keys under a mask)
ATOL = 2e-4


def toy(**over) -> dict:
    """The cell's configuration under its dry-run sizes (float32; window 9, 16 keys kept,
    pages of 8), with ``over`` on top."""
    c = {}
    for kind in ("configs", "dry_run"):
        with open(os.path.join(run.HERE, kind, f"{NAME}.json")) as f:
            c.update(json.load(f))
    return {**c, **over}


def reference_logits(c, ids) -> np.ndarray:
    """The plain reference's logits at every position of ``ids``."""
    rows = [(ids[:1], np.concatenate([ids[1:], [0]]).astype(np.int32))]
    return FAMILY.serve_reference(c, SEED, rows, len(ids), len(ids))[0]


def program(c):
    return FAMILY.program_config(c), FAMILY.gen_params(c, SEED, jnp.float32)


KINDS = {"full": ["full_attention"], "sliding": ["sliding_attention"],
         "both": ["full_attention", "full_attention", "sliding_attention",
                  "sliding_attention", "sliding_attention"]}


# ------------------------------------------------------------ forward against reference
@pytest.mark.parametrize("kinds,dense", [("full", 1), ("sliding", 0), ("both", 1)],
                         ids=["full_dense", "sliding_experts", "the_cut"])
def test_forward_matches_the_plain_reference(kinds, dense):
    """90 positions: past ``index_topk`` 16 (the selection is live from the 17th query
    on) and ten windows of 9."""
    c = toy(layer_types=KINDS[kinds], num_hidden_layers=len(KINDS[kinds]),
            first_k_dense_replace=dense)
    cfg, params = program(c)
    ids = np.random.default_rng(0).integers(0, c["vocab_size"], size=(90,)).astype(np.int32)
    got = np.asarray(dots3.forward(params, jnp.asarray(ids)[None], cfg))[0]
    np.testing.assert_allclose(got, reference_logits(c, ids), atol=ATOL)


def test_switches_off_give_another_function():
    """The gate, the rescale and the selection each move the logits (none is a no-op at
    these widths), so the agreement above is of all of them."""
    c = toy()
    cfg, params = program(c)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, c["vocab_size"], size=(1, 40)))
    base = dots3.forward(params, ids, cfg)
    for change in ({"attn_gate": False}, {"lora_rescale": False}, {"index_topk": 64},
                   {"window": 64}):
        other = dots3.forward(params, ids, dataclasses.replace(cfg, **change))
        assert float(jnp.abs(other - base).max()) > 1e-3, change


def test_prefill_in_chunks_then_paged_decode_match_the_reference_forward():
    """Both forms over both kinds of cache state: a left-padded prompt prefilled 16
    tokens a chunk (sparse layers: selection per query, then the gathered absorbed form;
    sliding layers: the band of the dense row), the row landed — latent and index-key
    pages through the block table, the last window into the lane's ring — then one token
    a step (index kernel's oracle, top-k, gather, the decode form; the ring through its
    computed table) while the context crosses ``index_topk``, several windows and, with a
    ring of 3 pages of 8 against 26 new positions, a ring wrap."""
    c = toy()
    cfg, params = program(c)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, c["vocab_size"], size=(37,)).astype(np.int32)
    served = rng.integers(0, c["vocab_size"], size=(26,)).astype(np.int32)
    ids = np.concatenate([prompt, served])
    ref = reference_logits(c, ids)
    bucket, ps, max_len, P = 16, 8, 96, 16
    total = -(-len(prompt) // bucket) * bucket
    pad = total - len(prompt)
    row = np.zeros((1, total), np.int32)
    row[0, pad:] = prompt
    mask = np.arange(total)[None] >= pad
    cache = dots3.init_cache(cfg, 1, max_len)
    for s in range(0, total, bucket):
        logits, cache = dots3.forward_cached(
            params, jnp.asarray(row[:, s:s + bucket]), cache, cfg,
            token_mask=jnp.asarray(mask[:, s:s + bucket]))
    np.testing.assert_allclose(np.asarray(logits)[0, 0], ref[len(prompt) - 1], atol=ATOL)
    pages = np.random.default_rng(2).permutation(P)[:max_len // ps].astype(np.int32)
    paged = _insert_row_paged(dots3.init_paged_cache(cfg, 2, max_len, P, ps), cache,
                              jnp.asarray(pages), 1, page_size=ps, scan_layers=False)
    tables = np.full((2, max_len // ps), P, np.int32)
    tables[1] = pages                                           # lane 0 stays free
    for j, tok in enumerate(served):
        pos = np.array([max_len, total + j], np.int32)          # the free lane is parked
        logits, paged = ds.forward_slots_paged(
            params, jnp.asarray([[0], [tok]], jnp.int32), paged, jnp.asarray(tables),
            jnp.asarray(pos), cfg, ps)
        np.testing.assert_allclose(np.asarray(logits)[1, 0], ref[len(prompt) + j], atol=ATOL)


# ------------------------------------------------------------------------ the selection
def test_the_programs_selected_sets_are_the_references():
    """Layer 0 sees the same input on both sides (the embedding's norm), so its selection
    can be compared set by set: prefill's ``_select_rows`` for every query, and decode's
    (the oracle's scores over pages, ``lax.top_k``) for the last one."""
    c = toy()
    cfg, params = program(c)
    fc = dict(FAMILY.freeze(c))
    T = 60
    ids = np.random.default_rng(3).integers(0, c["vocab_size"], size=(T,)).astype(np.int32)
    w = FAMILY.gen_layer(fc, jax.random.fold_in(FAMILY.seed_key(SEED), 0), "full_attention",
                         True, jnp.float32)
    x = FAMILY.gen_ends(fc, FAMILY.seed_key(SEED), jnp.float32)["embed"][ids]
    h = FAMILY._rms(x, w["input_layernorm"], c["rms_norm_eps"])
    z = FAMILY.sizes(fc, "full_attention")
    c_q = FAMILY._rms(FAMILY._mm(h, w["q_a_proj"], None), w["q_a_layernorm"],
                      c["rms_norm_eps"]) * z["rho_q"]
    want = np.asarray(FAMILY.select(FAMILY.index_scores(h, c_q, w, fc), c["index_topk"]))
    assert want.sum(1).tolist() == [min(t + 1, c["index_topk"]) for t in range(T)]

    spec, layer = cfg.attn_spec(0), params["layers"][0]
    pos = jnp.arange(T)[None]
    _, _, _, cq = ds._mla_project(h[None], layer, pos, spec)
    q_idx, k_idx, w_idx = ds._index_project(h[None], cq, layer, pos, spec)
    sel, ok = ds._select_rows(q_idx, w_idx, k_idx, pos, jnp.ones((1, T), bool), T, spec)
    got = np.zeros((T, T), bool)
    for t in range(T):
        got[t, np.asarray(sel)[0, t][np.asarray(ok)[0, t]]] = True
    assert (got == want).all()

    ps, P = 8, 12
    pages = np.random.default_rng(4).permutation(P)[:8].astype(np.int32)
    pool = jnp.zeros((P, ps, spec.index_dim)).at[pages].set(
        jnp.pad(k_idx[0], ((0, 64 - T), (0, 0))).reshape(8, ps, -1))
    scores = sa.dsa_index_scores_reference(
        q_idx[:, -1], w_idx[:, -1], pool, jnp.asarray(pages)[None],
        jnp.asarray([T - 1]), jnp.ones((1, 64), bool), page_size=ps)
    vals, top = jax.lax.top_k(scores, c["index_topk"])
    assert set(np.asarray(top)[0].tolist()) == set(np.flatnonzero(want[-1]).tolist())


def test_a_tie_at_the_cut_goes_to_the_earlier_key():
    scores = jnp.asarray([[0.0] * 8, [3, 1, 2, 2, 2, 0, 2, 9.0]], jnp.float32)
    chosen = np.asarray(FAMILY.select(jnp.pad(scores, ((6, 0), (0, 0))), 3))[6:]
    assert chosen[1].tolist() == [True, False, True, False, False, False, False, True]
    assert np.asarray(jax.lax.top_k(scores[1], 3)[1]).tolist() == [7, 0, 2]   # the program's


def test_top_k_over_the_live_columns_is_top_k(monkeypatch):
    """``_top_k_live`` sorts the narrowest of C/8, C/4, C/2, C columns that holds the live
    ones: the same values and indices as ``lax.top_k`` over the whole row at every fill,
    the boundaries included."""
    C, k = 8192, 1024
    rng = np.random.default_rng(6)
    for n in (1, 700, 1024, 1025, 2048, 2049, 4096, 4097, 8192):
        row = np.full((2, 3, C), -np.inf, np.float32)
        row[..., :n] = rng.normal(size=(2, 3, n)).round(1)           # ties abound
        got = jax.jit(ds._top_k_live, static_argnums=2)(jnp.asarray(row), jnp.int32(n), k)
        want = jax.lax.top_k(jnp.asarray(row), k)
        live = np.isfinite(np.asarray(want[0]))
        assert (np.asarray(got[0]) == np.asarray(want[0])).all(), n
        assert (np.asarray(got[1])[live] == np.asarray(want[1])[live]).all(), n


WALKS = {"one_block": (1024, [37, 63, 64]), "blocks_of_two_pages": (16, [37, 63, 64]),
         "a_block_past_the_table": (24, [5, 40, 64]), "an_empty_lane": (16, [64, 64, 9])}


@pytest.mark.parametrize("name", list(WALKS))
def test_index_kernel_matches_its_oracle_in_interpret_mode(name, monkeypatch):
    """Lanes with a left pad, tables in random order, a parked lane (position at the
    row's end, no valid slot): the kernel's scores and its ``-inf`` pattern are the
    oracle's at every block size."""
    block, positions = WALKS[name]
    monkeypatch.setattr(sa, "_BLOCK_KEYS", block)
    rng = np.random.default_rng(5)
    B, Hi, Di, ps, C, P = 3, 4, 16, 8, 64, 40
    q = jnp.asarray(rng.normal(size=(B, Hi, Di)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(B, Hi)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(P, ps, Di)), jnp.float32)
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
    assert (np.isfinite(got) == np.isfinite(want)).all()
    assert np.isfinite(want).sum(1).tolist() == [
        pos + 1 - 3 * b if pos < C else 0 for b, pos in enumerate(positions)]
    np.testing.assert_allclose(got[np.isfinite(want)], want[np.isfinite(want)], atol=1e-5)


# ------------------------------------------------------------------- two kinds of state
def test_ring_tables_by_hand():
    """Window 9, pages of 8: a window touches at most 2 pages, the ring has 3. Lane 1 at
    position 21 holds keys 13..21 = logical pages 1 and 2, at ring slots 3 + 1 and 3 + 2."""
    assert common.ring_pages(9, 8) == 3 and common.ring_pages(513, 16) == 34
    t = np.asarray(common.ring_tables(jnp.asarray([4, 21, 48]), 8, 8, 9))
    assert t.tolist() == [[0, 9, 9, 9, 9, 9, 9, 9], [9, 4, 5, 9, 9, 9, 9, 9],
                          [9, 9, 9, 9, 9, 8, 6, 9]]     # page 5 at 6 + 5 % 3, page 6 at 6 + 0


def test_window_layers_cache_does_not_grow_with_max_len():
    cfg = dots3.CONFIGS["tiny"]
    small, large = (dots3.init_paged_cache(cfg, 4, n, 64, 8)["layers"] for n in (128, 1024))
    rings = [l["ring"].shape for l in small if "ring" in l]
    assert rings == [l["ring"].shape for l in large if "ring" in l]
    assert rings == [(4 * common.ring_pages(5, 8), 8, 128)] * 3       # O(window) a lane
    full = [l for l in small if "ring" not in l]
    assert [sorted(l) for l in full] == [["index_k", "latent"]] * 2
    # the toy's 16-value index keys lie 8 to a 128-lane row: a page of 8 keys is one row
    assert full[0]["latent"].shape == (64, 8, 128) and full[0]["index_k"].shape == (64, 1, 128)
    assert sa.index_pool_shape(64, 16, 128) == (64, 16, 128)         # dots3's: a key a row


# ------------------------------------------------------------------ the chip's share
def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """One expert layer over 16 published experts with NO groups: the routed parts that
    eight shares of two experts compute plus the shared expert ONCE equal the reference's
    layer with all 16 held; and each share's program output equals the reference given
    the same share."""
    c = toy(n_routed_experts=16)
    fc = dict(FAMILY.freeze(c))
    key = jax.random.fold_in(FAMILY.seed_key(SEED), 1)
    h = jax.random.normal(jax.random.PRNGKey(3), (40, c["hidden_size"]), jnp.float32)
    uncut = FAMILY.gen_layer(fc, key, "full_attention", False, jnp.float32)
    whole, shared = FAMILY.moe(h, uncut, fc), FAMILY.swiglu(h, uncut["shared_experts"])
    kw = dict(top_k=c["num_experts_per_tok"], n_group=1, topk_group=1,
              scale=c["routed_scaling_factor"], compute_dtype=jnp.float32)
    routed, pairs = 0.0, 0
    for offset in range(0, 16, 2):
        share = {**fc, "n_routed_experts": 2, "expert_offset": offset}
        w = FAMILY.gen_layer(share, key, "full_attention", False, jnp.float32)
        y, counts = moe_ops.moe_mlp_grouped(
            h, FAMILY.program_layer(share, w, "full_attention")["moe"],
            expert_offset=offset, **kw)
        np.testing.assert_allclose(y, FAMILY.moe(h, w, share), atol=ATOL)
        routed = routed + (y - shared)
        pairs += int(counts[0])
    np.testing.assert_allclose(routed + shared, whole, atol=ATOL)
    assert pairs == 40 * c["num_experts_per_tok"]      # every pair was some chip's


# ---------------------------------------------------------------------------- the engine
def engine(cfg, params, **kw):
    kw = {"max_slots": 4, "max_len": 128, "prompt_bucket": 16, "page_size": 8,
          "kv_pages": 48, "decode_steps": 4, **kw}
    return ContinuousBatcher(params, cfg, **kw)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Four prompts (5 to 70 tokens; 20 more each) through ``ContinuousBatcher`` on the toy
    configuration inside a profiler session → (config, prompts, requests, spans)."""
    c = toy()
    cfg, params = program(c)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, c["vocab_size"], size=(n,)).astype(np.int32)
               for n in (5, 23, 40, 70)]
    where = str(tmp_path_factory.mktemp("profile"))
    eng = engine(cfg, params)
    jax.profiler.start_trace(where)
    try:
        reqs = [eng.submit(p, max_new_tokens=20) for p in prompts]
        eng.run()
    finally:
        jax.profiler.stop_trace()
    return c, prompts, reqs, program_spans.load(where)


def test_engine_serves_the_references_greedy_tokens(served):
    """submit/step, ``BlockManager`` and the programs of the other two models: chunked
    prefill, the landing into pages AND ring, 4-step decode — contexts from 5 to 90
    tokens, so lanes below and above ``index_topk`` 16 and window 9 decode side by side
    and the longer ones wrap their ring."""
    c, prompts, reqs, _ = served
    for prompt, req in zip(prompts, reqs):
        out = []
        for _ in range(20):     # one shape for every step: the same compiled reference
            rows = [(prompt, np.asarray(out + [0], np.int32))]
            logits = FAMILY.serve_reference(c, SEED, rows, 96, 21)[0, len(out)]
            out.append(int(logits.argmax()))
        assert list(req.tokens) == out


def test_engine_reports_the_selection_and_window_counters(served):
    c, prompts, reqs, spans = served
    drains = [s for s in spans if s.name == "engine.decode.drain"]
    assert drains and set(dots3.DECODE_COUNTERS) <= set(drains[0].attrs)
    assert dots3.DECODE_COUNTERS[:3] == ds.DECODE_COUNTERS       # DeepSeek-V3 keeps its three
    scored = sum(s.attrs["dsa_keys_scored"] for s in drains)
    attended = sum(s.attrs["dsa_keys_attended"] for s in drains)
    window = sum(s.attrs["window_keys_attended"] for s in drains)
    steps = sum(len(r.tokens) - 1 for r in reqs)        # a lane's decode steps
    assert 0 < attended < scored                        # something was sparse
    assert attended <= 2 * c["index_topk"] * steps      # <= index_topk rows a lane, 2 layers
    assert 0 < window <= 3 * c["sliding_window_size"] * steps
    # every live key was scored: lane by lane, step by step, over the two sparse layers
    live = sum(len(p) + j + 1 for p, r in zip(prompts, reqs) for j in range(len(r.tokens) - 1))
    assert scored == 2 * live


@pytest.mark.parametrize("kw,what", [
    ({"page_size": 0, "kv_pages": None}, r"has no forward_slots: the engine's dense rows"),
    ({"spec_k": 2}, r"has no forward_slots_spec_multi: the engine's spec_k path"),
    ({"prefix_cache": 2}, r"has no forward_cached_logits: the engine's prefix_cache path"),
    ({"role": "prefill", "decode_steps": 1}, r"prefill/decode hand-off path \(role='prefill'\)"),
    ({"role": "decode"}, r"prefill/decode hand-off path \(role='decode'\)"),
], ids=["dense_rows", "spec_k", "prefix_cache", "prefill_role", "decode_role"])
def test_engine_refuses_by_name_the_paths_that_cannot_carry_window_state(kw, what):
    with pytest.raises(NotImplementedError, match=r"accelerate_tpu\.models\.dots3 .*" + what):
        engine(dots3.CONFIGS["tiny"], {"not": "touched"}, **kw)


def test_the_seam_and_the_instance():
    """The engine reaches dots3 through its config's module; DeepSeek-V3 is the instance
    of the shared decoder with every layer full, no indexer, no gate — its own spec."""
    from accelerate_tpu import serving

    assert serving._model(dots3.CONFIGS["tiny"]) is dots3
    v3 = ds.CONFIGS["tiny"]
    assert v3.attn_spec(2) is v3 and not (v3.window or v3.index_topk or v3.attn_gate)
    kinds = [dots3.CONFIGS["tiny"].attn_spec(l) for l in range(5)]
    assert [bool(k.index_topk) for k in kinds] == [True, True, False, False, False]
    assert [k.window for k in kinds] == [0, 0, 5, 5, 5]
    full = dots3.Dots3Config().attn_spec(0)
    assert (full.q_rescale, full.kv_rescale) == (5 ** 0.5, 10 ** 0.5)
    assert ds.sm_scale(full) == 192 ** -0.5 and ds.sm_scale(dots3.Dots3Config().attn_spec(2)) == 256 ** -0.5
    with pytest.raises(ValueError, match="layer_types"):
        dots3.Dots3Config(n_layers=3)
