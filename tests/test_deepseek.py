"""``models/deepseek.py`` (latent attention over one cache in two forms, a dense first
layer, expert layers over the experts held), ``ops/mla_attention.py``, ``ops/moe.py``'s
grouped expert layer, and the engine's seam to a second decoder — on the CPU at toy widths,
against the plain reference in ``benchmarks/chipbench/families/deepseek_v3.py`` (float32
``jax.numpy``, prefill form only, routes by its own scores). Everything runs in float32
here, so the tolerances below are reassociation only; what bfloat16 adds is the chip's
reading (PERF.md §2).
"""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import deepseek as ds
from accelerate_tpu.models import llama
from accelerate_tpu.ops import moe as moe_ops
from accelerate_tpu.serving import ContinuousBatcher
from benchmarks.chipbench import program_spans, run

FAMILY = run.load_family("deepseek_v3")
NAME = "deepseek-v3-serve-ep16-d5"
SEED = 11
# float32 on both sides: what is left is the order of the sums (absorbed against
# up-projected attention, a grouped product against a per-token loop)
ATOL = 2e-4


def toy(**over) -> dict:
    """The cell's configuration under its dry-run sizes (float32), with ``over`` on top."""
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


# ------------------------------------------------------------ forward against reference
@pytest.mark.parametrize("layers,dense", [(1, 1), (1, 0), (3, 1)],
                         ids=["dense_layer", "expert_layer", "both"])
def test_forward_matches_the_plain_reference(layers, dense):
    c = toy(num_hidden_layers=layers, first_k_dense_replace=dense)
    cfg, params = program(c)
    ids = np.random.default_rng(0).integers(0, c["vocab_size"], size=(48,)).astype(np.int32)
    got = np.asarray(ds.forward(params, jnp.asarray(ids)[None], cfg))[0]
    np.testing.assert_allclose(got, reference_logits(c, ids), atol=ATOL)


def test_prefill_in_chunks_then_paged_decode_match_the_reference_forward():
    """The two attention forms over ONE cache: a left-padded prompt prefilled 16 tokens a
    chunk (up-projected form against the dense latent row), the row scattered into
    pages, then one token a step through ``forward_slots_paged`` (absorbed form over the
    pages) — every step's logits against the reference's full forward at that position.
    Tolerance: float32 reassociation (q·W_kb then ·c_kv against q·(c_kv·W_kb))."""
    from accelerate_tpu.serving import _insert_row_paged

    c = toy()
    cfg, params = program(c)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, c["vocab_size"], size=(37,)).astype(np.int32)
    served = rng.integers(0, c["vocab_size"], size=(6,)).astype(np.int32)
    ids = np.concatenate([prompt, served])
    ref = reference_logits(c, ids)
    bucket, ps, max_len, P = 16, 8, 64, 12
    total = -(-len(prompt) // bucket) * bucket
    pad = total - len(prompt)
    row = np.zeros((1, total), np.int32)
    row[0, pad:] = prompt
    mask = np.arange(total)[None] >= pad
    cache = ds.init_cache(cfg, 1, max_len)
    for s in range(0, total, bucket):
        logits, cache = ds.forward_cached(
            params, jnp.asarray(row[:, s:s + bucket]), cache, cfg,
            token_mask=jnp.asarray(mask[:, s:s + bucket]))
    np.testing.assert_allclose(np.asarray(logits)[0, 0], ref[len(prompt) - 1], atol=ATOL)
    pages = np.random.default_rng(2).permutation(P)[:max_len // ps].astype(np.int32)
    paged = _insert_row_paged(ds.init_paged_cache(cfg, 2, max_len, P, ps), cache,
                              jnp.asarray(pages), 1, page_size=ps, scan_layers=False)
    tables = np.full((2, max_len // ps), P, np.int32)
    tables[1] = pages                                           # lane 0 stays free
    for j, tok in enumerate(served):
        pos = np.array([max_len, total + j], np.int32)          # the free lane is parked
        logits, paged = ds.forward_slots_paged(
            params, jnp.asarray([[0], [tok]], jnp.int32), paged, jnp.asarray(tables),
            jnp.asarray(pos), cfg, ps)
        np.testing.assert_allclose(np.asarray(logits)[1, 0], ref[len(prompt) + j], atol=ATOL)


# ------------------------------------------------------------------ the chip's share
def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """One expert layer over 16 published experts: the routed parts that four shares of
    four experts compute (the program's ``moe_mlp_grouped``, weights by published index)
    plus the shared expert ONCE equal the reference's layer with all 16 held; and each
    share's program output equals the reference given the same share."""
    c = toy(num_hidden_layers=1, first_k_dense_replace=0, n_routed_experts=16)
    fc = dict(FAMILY.freeze(c))
    key = jax.random.fold_in(FAMILY.seed_key(SEED), 0)
    h = jax.random.normal(jax.random.PRNGKey(3), (40, c["hidden_size"]), jnp.float32)
    whole = FAMILY.moe(h, FAMILY.gen_layer(fc, key, False, jnp.float32), fc)
    shared = FAMILY.swiglu(h, FAMILY.gen_layer(fc, key, False, jnp.float32)["shared_experts"])
    kw = dict(top_k=c["num_experts_per_tok"], n_group=c["n_group"],
              topk_group=c["topk_group"], scale=c["routed_scaling_factor"],
              compute_dtype=jnp.float32)
    routed, pairs = 0.0, 0
    for offset in range(0, 16, 4):
        share = {**fc, "n_routed_experts": 4, "expert_offset": offset}
        w = FAMILY.gen_layer(share, key, False, jnp.float32)
        y, counts = moe_ops.moe_mlp_grouped(
            h, FAMILY.program_layer(share, w)["moe"], expert_offset=offset, **kw)
        np.testing.assert_allclose(y, FAMILY.moe(h, w, share), atol=ATOL)
        routed = routed + (y - shared)
        pairs += int(counts[0])
    np.testing.assert_allclose(routed + shared, whole, atol=ATOL)
    assert pairs == 40 * c["num_experts_per_tok"]      # every pair was some chip's


# ---------------------------------------------------------------------------- router
def _route(scores, bias, **kw):
    """Route ONE token whose sigmoid scores are ``scores``: x is a one-hot row and the
    router's weights the logits."""
    E = len(scores)
    logits = np.log(np.asarray(scores) / (1 - np.asarray(scores)))
    x = np.zeros((1, E), np.float32)
    x[0, 0] = 1.0
    w = np.zeros((E, E), np.float32)
    w[0] = logits
    gates, idx = moe_ops.router_sigmoid_grouped(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(bias, jnp.float32),
        top_k=2, n_group=4, topk_group=2, scale=2.5, **kw)
    order = np.argsort(np.asarray(idx)[0])
    return np.asarray(idx)[0][order].tolist(), np.asarray(gates)[0][order]


def test_router_by_hand():
    # four groups of two; a group scores the sum of its two (largest) members
    scores = [0.90, 0.01, 0.60, 0.62, 0.50, 0.55, 0.10, 0.20]
    idx, gates = _route(scores, np.zeros(8))
    # groups score 0.91, 1.22, 1.05, 0.30: groups 1 and 2 stay, expert 0 (the largest
    # single score) sits in a group that does not and contributes nothing
    assert idx == [2, 3]
    np.testing.assert_allclose(gates, 2.5 * np.array([0.60, 0.62]) / 1.22, rtol=1e-5)
    assert gates.sum() == pytest.approx(2.5, rel=1e-5)
    # a bias flips the choice (5 passes 2) and never enters a gate
    bias = np.zeros(8)
    bias[5] = 0.06
    idx, gates = _route(scores, bias)
    assert idx == [3, 5]
    np.testing.assert_allclose(gates, 2.5 * np.array([0.62, 0.55]) / 1.17, rtol=1e-5)
    # without the normalisation the gates are the scores themselves, scaled
    _, gates = _route(scores, np.zeros(8), norm_topk=False)
    np.testing.assert_allclose(gates, 2.5 * np.array([0.60, 0.62]), rtol=1e-5)


# ------------------------------------------------------------------------------ YaRN
def test_yarn_frequencies_and_scale_by_hand():
    """DeepSeek-V3's rope_scaling: 64 rotary dims, base 1e4, 4096 → ×40, beta 32 / 1.
    The correction range is pairs 10 … 23: 64·ln(4096/(32·2π))/(2·ln 1e4) = 10.47,
    64·ln(4096/2π)/(2·ln 1e4) = 22.51."""
    cfg = ds.DeepseekConfig()
    base = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    want = base / 40 * ramp + base * (1 - ramp)
    np.testing.assert_allclose(ds.yarn_inv_freq(cfg), want, rtol=1e-6)
    assert float(ds.yarn_inv_freq(cfg)[10]) == pytest.approx(base[10])
    assert float(ds.yarn_inv_freq(cfg)[23]) == pytest.approx(base[23] / 40)
    assert float(ds.yarn_inv_freq(cfg)[16]) == pytest.approx(base[16] * (7 / 13 + 6 / 13 / 40))
    m = 0.1 * math.log(40) + 1
    assert m == pytest.approx(1.36889, abs=1e-5)
    assert ds.sm_scale(cfg) == pytest.approx(192 ** -0.5 * m * m) == pytest.approx(0.135234, abs=1e-6)
    published = {"qk_rope_head_dim": 64, "qk_nope_head_dim": 128, "rope_theta": 10000,
                 "rope_scaling": toy()["rope_scaling"] | {"factor": 40, "original_max_position_embeddings": 4096}}
    np.testing.assert_allclose(FAMILY.yarn_inv_freq(published), want, rtol=1e-6)
    assert FAMILY.softmax_scale(published) == pytest.approx(ds.sm_scale(cfg))


# ---------------------------------------------------------------- the latent kernel
# Toy geometry as tests/test_paged_kv.py's walk cases: pages of 8 slots, 7 table entries
# a lane (C = 56), blocks forced to 2 pages. A lane is (first valid slot, length); None
# is a freed lane (stale valid row, all-sentinel table row, parked at C), "empty" one
# whose valid row is empty.
PS, MP, BLOCK, RANK, ROPE, HEADS = 8, 7, 2, 32, 8, 4
WALK_CASES = {
    "freed_lane_between_live_ones": [(0, 20), None, (0, 11), "empty"],
    "length_1": [(0, 1), (0, 30)],
    "length_on_block_boundary_and_one_past": [(0, 16), (0, 17), (0, 32), (0, 33)],
    "left_pad_crosses_block_boundary": [(19, 45), (5, 23)],
    "table_not_a_multiple_of_block": [(0, 56), (41, 56)],
    "sentinel_entries_above_hi": [(0, 20), (0, 4)],
}


def latent_case(lanes, seed=0):
    from accelerate_tpu.models.common import latent_width

    rng = np.random.default_rng(seed)
    B, C = len(lanes), PS * MP
    P = B * MP
    pool = rng.standard_normal((P, PS, latent_width(RANK + ROPE))).astype(np.float32)
    tables = np.full((B, MP), P, np.int32)
    valid = np.zeros((B, C), bool)
    positions = np.full((B,), C, np.int32)
    live = np.zeros((B,), bool)
    free = list(rng.permutation(P))
    for b, lane in enumerate(lanes):
        if isinstance(lane, tuple):
            first, n = lane
            tables[b, :-(-n // PS)] = [free.pop() for _ in range(-(-n // PS))]
            valid[b, first:n] = True
            positions[b], live[b] = n - 1, True
        elif lane is None:
            valid[b, :9] = True                     # a stale row the walk must not follow
    q_lat = rng.standard_normal((B, HEADS, RANK)).astype(np.float32)
    q_rope = rng.standard_normal((B, HEADS, ROPE)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (q_lat, q_rope, pool, tables, positions, valid)), live


def force_block(monkeypatch):
    from accelerate_tpu.models.common import latent_width
    from accelerate_tpu.ops import mla_attention, paged_attention

    monkeypatch.setattr(paged_attention, "_BLOCK_BYTES",
                        BLOCK * PS * latent_width(RANK + ROPE) * 4)
    assert mla_attention.mla_block_pages(PS, latent_width(RANK + ROPE), 4, MP) == BLOCK


@pytest.mark.parametrize("name", list(WALK_CASES))
def test_latent_kernel_walks_the_live_range(name, monkeypatch):
    """The kernel (interpret mode) against its jnp form on every live lane; zeros on a
    freed or empty one."""
    from accelerate_tpu.ops.mla_attention import (
        mla_paged_attention, mla_paged_attention_reference)

    force_block(monkeypatch)
    args, live = latent_case(WALK_CASES[name])
    kw = dict(page_size=PS, sm_scale=0.2)
    out = np.asarray(mla_paged_attention(*args, interpret=True, **kw))
    ref = np.asarray(mla_paged_attention_reference(*args, **kw))
    np.testing.assert_allclose(out[live], ref[live], atol=2e-6)
    assert np.all(out[~live] == 0.0) and np.all(ref[~live] == 0.0)


def test_latent_kernel_is_one_program_for_every_length(monkeypatch):
    from accelerate_tpu.ops.mla_attention import mla_paged_attention

    force_block(monkeypatch)
    kernel = jax.jit(lambda *a: mla_paged_attention(*a, page_size=PS, sm_scale=0.2,
                                                    interpret=True))
    for name in ("length_1", "left_pad_crosses_block_boundary",
                 "table_not_a_multiple_of_block"):
        kernel(*latent_case(WALK_CASES[name][:2])[0])
    assert kernel._cache_size() == 1


# ------------------------------------------------------------- the grouped expert layer
def _loop_moe(x, moe, idx, gates, offset):
    """Per token, per chosen expert, if it is held: the straightforward sum."""
    def ffn(v, w):
        return (jax.nn.silu(v @ w["w_gate"]) * (v @ w["w_up"])) @ w["w_down"]

    E = moe["experts"]["w_gate"].shape[0]
    out = []
    for t in range(x.shape[0]):
        y = ffn(x[t], moe["shared"])
        for e, g in zip(np.asarray(idx[t]), np.asarray(gates[t])):
            if offset <= e < offset + E:
                y = y + g * ffn(x[t], jax.tree_util.tree_map(lambda a: a[e - offset],
                                                             moe["experts"]))
        out.append(y)
    return jnp.stack(out)


@pytest.mark.parametrize("case", ["as_routed", "an_empty_expert", "all_on_one_expert"])
def test_grouped_expert_layer_drops_nothing(case):
    """``moe_mlp_grouped`` against the per-token loop, with an expert nobody chose and
    with every token on ONE expert (a capacity-based layer would drop most of them)."""
    cfg = ds.CONFIGS["tiny"]
    moe = ds.init_params(cfg, jax.random.PRNGKey(5))["layers"][1]["moe"]
    bias = np.zeros((cfg.n_routed_experts,), np.float32)
    if case == "an_empty_expert":
        bias[3] = -10.0                              # held, never chosen
    if case == "all_on_one_expert":
        bias[2] = 10.0                               # held, chosen by everyone
    moe = {**moe, "router_bias": jnp.asarray(bias)}
    x = jax.random.normal(jax.random.PRNGKey(6), (24, cfg.d_model), jnp.float32)
    kw = dict(top_k=cfg.experts_per_tok, n_group=cfg.n_group, topk_group=cfg.topk_group,
              scale=cfg.routed_scaling)
    gates, idx = moe_ops.router_sigmoid_grouped(x, moe["router"], moe["router_bias"], **kw)
    y, counts = moe_ops.moe_mlp_grouped(x, moe, compute_dtype=jnp.float32, **kw)
    np.testing.assert_allclose(y, _loop_moe(x, moe, idx, gates, 0), atol=ATOL)
    held = (np.asarray(idx) < cfg.experts_held)
    per_expert = np.bincount(np.asarray(idx)[held], minlength=cfg.experts_held)
    assert counts.tolist() == [held.sum(), 24, per_expert.max()]
    if case == "an_empty_expert":
        assert per_expert[3] == 0
    if case == "all_on_one_expert":
        assert per_expert[2] == 24


# ---------------------------------------------------------------------------- the engine
def engine(cfg, params, **kw):
    kw = {"max_slots": 4, "max_len": 128, "prompt_bucket": 16, "page_size": 8,
          "kv_pages": 40, "decode_steps": 4, **kw}
    return ContinuousBatcher(params, cfg, **kw)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Three prompts through ``ContinuousBatcher`` on the toy configuration inside a
    profiler session → (config, prompts, requests, the ``atpu.`` spans)."""
    c = toy()
    cfg, params = program(c)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, c["vocab_size"], size=(n,)).astype(np.int32)
               for n in (5, 23, 40)]
    where = str(tmp_path_factory.mktemp("profile"))
    eng = engine(cfg, params)
    jax.profiler.start_trace(where)
    try:
        reqs = [eng.submit(p, max_new_tokens=7) for p in prompts]
        eng.run()
    finally:
        jax.profiler.stop_trace()
    return c, prompts, reqs, program_spans.load(where)


def test_engine_serves_the_references_greedy_tokens(served):
    c, prompts, reqs, _ = served
    for prompt, req in zip(prompts, reqs):
        out = []
        for _ in range(7):      # one shape for every step: the same compiled reference
            rows = [(prompt, np.asarray(out + [0], np.int32))]
            logits = FAMILY.serve_reference(c, SEED, rows, 64, 8)[0, len(out)]
            out.append(int(logits.argmax()))
        assert list(req.tokens) == out


def test_engine_reports_the_expert_and_page_counters(served):
    c, _, reqs, spans = served
    drains = [s for s in spans if s.name == "engine.decode.drain"]
    dispatches = [s for s in spans if s.name == "engine.decode.dispatch"]
    assert drains and len(drains) == len(dispatches)
    expert_layers = c["num_hidden_layers"] - c["first_k_dense_replace"]
    for s in drains:                    # every lane of every step enters every expert layer
        assert s.attrs["moe_tokens"] == 4 * 4 * expert_layers
        assert 0 <= s.attrs["moe_max_on_one_expert"] <= s.attrs["moe_pairs"] \
            <= s.attrs["moe_tokens"] * c["num_experts_per_tok"]
    assert sum(s.attrs["moe_pairs"] for s in drains) > 0
    for s in dispatches:
        assert 0 < s.attrs["pages_live"] <= s.attrs["pages_walked"]


@pytest.mark.parametrize("kw,fn", [
    ({"page_size": 0, "kv_pages": None}, "forward_slots"),
    ({"spec_k": 2}, "forward_slots"),
    ({"prefix_cache": 2}, "forward_cached_logits"),
], ids=["dense_rows", "spec_k", "prefix_cache"])
def test_engine_refuses_what_the_model_module_lacks(kw, fn):
    cfg = ds.CONFIGS["tiny"]
    with pytest.raises(NotImplementedError,
                       match=rf"accelerate_tpu\.models\.deepseek has no {fn}\b"):
        engine(cfg, {"not": "touched"}, **kw)


def test_engine_reaches_each_model_through_its_configs_module():
    """The seam: the module that defines the config's class, nothing else; and the engine
    itself names no model."""
    from accelerate_tpu import serving

    assert serving._model(ds.CONFIGS["tiny"]) is ds
    assert serving._model(llama.CONFIGS["tiny"]) is llama
    src = open(serving.__file__).read()
    code = [l.split("#")[0] for l in src.split('"""')[0::2] for l in l.splitlines()]
    assert not any("llama." in l or "deepseek" in l for l in code)
    subclass = dataclasses.replace(llama.CONFIGS["tiny"], n_layers=1)
    assert serving._model(subclass) is llama
