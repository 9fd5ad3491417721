"""``models/deepseek_v32.py``: DeepSeek-V3.2's decoder (V3's, with the indexer on every
layer) and its prediction module served as the speculative drafter — on the CPU at toy
widths, against the plain reference in ``benchmarks/chipbench/families/deepseek_v32.py``
(float32 ``jax.numpy``, prefill form only, selects and routes by its own scores).
Everything runs in float32 here, so the tolerances below are reassociation only; what
bfloat16 adds is the chip's reading (PERF.md §2).
"""

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import deepseek as ds
from accelerate_tpu.models import deepseek_v32 as v32
from accelerate_tpu.models import dots3, keye
from accelerate_tpu.ops import moe as moe_ops
from accelerate_tpu.serving import ContinuousBatcher, _insert_row_paged
from accelerate_tpu.spec_decode import ModuleDrafter, NgramDrafter
from accelerate_tpu.telemetry.tracing import PHASES
from benchmarks.chipbench import run

FAMILY = run.load_family("deepseek_v32")
NAME = "deepseek-v32-serve-ep32-d5"
SEED = 13
# float32 on both sides: what is left is the order of the sums (absorbed against
# up-projected attention, a grouped product against a per-token loop, the indexer's
# scores in another order — no selection cut fell on a near-tie at these seeds)
ATOL = 2e-4


def toy(**over) -> dict:
    """The cell's configuration under its dry-run sizes (float32), with ``over`` on top."""
    c = {}
    for kind in ("configs", "dry_run"):
        with open(os.path.join(run.HERE, kind, f"{NAME}.json")) as f:
            c.update(json.load(f))
    return {**c, **over}


def program(c):
    return FAMILY.program_config(c), FAMILY.gen_params(c, SEED, jnp.float32)


def reference(c, ids):
    """The plain reference's (main logits, module logits) at every position of ``ids``."""
    main, module = FAMILY.forward(jnp.asarray(ids), FAMILY.seed_key(SEED), FAMILY.freeze(c))
    return np.asarray(main), np.asarray(module)


forward_mtp = jax.jit(ds.forward_mtp, static_argnums=(2,))
forward_cached = jax.jit(ds.forward_cached, static_argnames=("cfg",))
forward_slots_paged = jax.jit(ds.forward_slots_paged, static_argnums=(5, 6))


def engine(cfg, params, **kw):
    kw = {"max_slots": 2, "max_len": 96, "prompt_bucket": 16, "page_size": 8,
          "decode_steps": 3, **kw}
    return ContinuousBatcher(params, cfg, **kw)


# ------------------------------------------------------------ forward against reference
def test_the_main_stack_and_the_module_match_the_plain_reference():
    """Every position's logits, and the module's at every row that has a next token (row
    ``i`` reads ``h_i`` and ``ids[i + 1]``): 48 positions, past ``index_topk`` (16), so the
    selection is live in every layer and in the module."""
    c = toy(num_hidden_layers=2)
    cfg, params = program(c)
    ids = np.random.default_rng(0).integers(0, c["vocab_size"], size=(48,)).astype(np.int32)
    main, module = (np.asarray(a)[0] for a in forward_mtp(params, jnp.asarray(ids)[None], cfg))
    ref_main, ref_module = reference(c, ids)
    np.testing.assert_allclose(main, ref_main, atol=ATOL)
    np.testing.assert_allclose(module[:-1], ref_module[:-1], atol=ATOL)


def test_prefill_in_chunks_then_a_two_row_verify_match_the_reference():
    """A left-padded prompt prefilled 16 tokens a chunk (the module's rows start one slot
    back in each chunk), the row scattered into pages; the prefill's draft is the reference
    module's first choice at the prompt's last row. Then verify rounds of TWO rows a lane —
    the right token and a wrong one, as a rejected draft leaves it — advancing one position
    a round: the first row's logits equal the reference's, so the wrong row a round wrote
    was written again before anything read it."""
    c = toy(num_hidden_layers=2)
    cfg, params = program(c)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, c["vocab_size"], size=(37,)).astype(np.int32)
    bucket, ps, max_len, P = 16, 8, 64, 12
    total = -(-len(prompt) // bucket) * bucket
    pad = total - len(prompt)
    row = np.zeros((1, total), np.int32)
    row[0, pad:] = prompt
    mask = np.arange(total)[None] >= pad
    cache = ds.init_cache(cfg, 1, max_len)
    for s in range(0, total, bucket):
        logits, cache = forward_cached(
            params, jnp.asarray(row[:, s:s + bucket]), cache, cfg=cfg,
            token_mask=jnp.asarray(mask[:, s:s + bucket]))
    first = int(np.argmax(np.asarray(logits)[0, 0]))
    served = np.concatenate([[first], rng.integers(0, c["vocab_size"], size=(5,))]).astype(np.int32)
    ref_main, ref_module = reference(c, np.concatenate([prompt, served]))
    n = len(prompt)
    np.testing.assert_allclose(np.asarray(logits)[0, 0], ref_main[n - 1], atol=ATOL)
    assert int(cache["draft"][0]) == int(ref_module[n - 1].argmax())
    pages = np.random.default_rng(2).permutation(P)[:max_len // ps].astype(np.int32)
    paged = _insert_row_paged(v32.init_paged_cache(cfg, 2, max_len, P, ps), cache,
                              jnp.asarray(pages), 1, page_size=ps, scan_layers=False)
    assert int(paged["draft"][1]) == int(cache["draft"][0])     # a lane's own leaf
    tables = np.full((2, max_len // ps), P, np.int32)
    tables[1] = pages                                           # lane 0 stays free
    for j in range(len(served) - 1):
        wrong = (served[j + 1] + 1) % c["vocab_size"]
        pos = np.array([max_len, total + j], np.int32)          # the free lane is parked
        logits, paged = forward_slots_paged(
            params, jnp.asarray([[0, 0], [served[j], wrong]], jnp.int32), paged,
            jnp.asarray(tables), jnp.asarray(pos), cfg, ps)
        np.testing.assert_allclose(np.asarray(logits)[1, 0], ref_main[n + j], atol=ATOL)


# ------------------------------------------------------------------ the chip's share
@pytest.mark.parametrize("which", ["expert_layer", "module_layer"])
def test_the_shares_of_all_chips_add_up_to_the_uncut_layer(which):
    """An expert layer over 16 published experts — a main one, and the module's: the routed
    parts that four shares of four experts compute (the program's ``moe_mlp_grouped``,
    weights by published index) plus the shared expert ONCE equal the reference's layer
    with all 16 held; and each share's program output equals the reference given the same
    share."""
    c = toy(n_routed_experts=16)
    fc = dict(FAMILY.freeze(c))
    gen = ((lambda cc: FAMILY.gen_layer(cc, jax.random.fold_in(FAMILY.seed_key(SEED), 1),
                                        False, jnp.float32)) if which == "expert_layer"
           else (lambda cc: FAMILY.gen_module(cc, FAMILY.seed_key(SEED), jnp.float32)))
    h = jax.random.normal(jax.random.PRNGKey(3), (40, c["hidden_size"]), jnp.float32)
    whole = FAMILY.moe(h, gen(fc), fc)
    shared = FAMILY.swiglu(h, gen(fc)["shared_experts"])
    kw = dict(top_k=c["num_experts_per_tok"], n_group=c["n_group"],
              topk_group=c["topk_group"], scale=c["routed_scaling_factor"],
              compute_dtype=jnp.float32)
    routed, pairs = 0.0, 0
    for offset in range(0, 16, 4):
        share = {**fc, "n_routed_experts": 4, "expert_offset": offset}
        w = gen(share)
        y, counts = moe_ops.moe_mlp_grouped(
            h, FAMILY.program_layer(share, w)["moe"], expert_offset=offset, **kw)
        np.testing.assert_allclose(y, FAMILY.moe(h, w, share), atol=ATOL)
        routed = routed + (y - shared)
        pairs += int(counts[0])
    np.testing.assert_allclose(routed + shared, whole, atol=ATOL)
    assert pairs == 40 * c["num_experts_per_tok"]      # every pair was some chip's


# ----------------------------------------------------------------------------- engine
@pytest.fixture(scope="module")
def drafted():
    """Three prompts through the engine with the module drafting (the config's default),
    over a vocabulary of 8 so that drafts are both accepted and rejected; after every
    ``step()`` each running lane's draft and its latest token are read → (config, params,
    prompts, requests, [(draft, the tokens so far)], the drain phases, stats())."""
    cfg = dataclasses.replace(v32.CONFIGS["tiny"], vocab_size=8)
    params = v32.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 8, size=(n,)).astype(np.int32) for n in (5, 37, 20)]
    eng = engine(cfg, params)
    since = time.perf_counter_ns()
    reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
    seen = []
    while any(not r.done for r in reqs):
        eng.step()
        for slot, r in enumerate(eng.slot_req):
            if r is not None:
                seen.append((int(eng.cache["draft"][slot]),
                             np.concatenate([r.prompt, r.tokens]).astype(np.int32)))
    drains = [r for r in PHASES.records(since, 2 ** 62) if r.name == "engine.decode.drain"]
    return cfg, params, prompts, reqs, seen, drains, eng.stats()


def test_module_drafting_emits_what_spec_k_0_emits(drafted):
    cfg, params, prompts, reqs, *_ = drafted
    eng = engine(cfg, params, spec_k=0)
    plain = [eng.submit(p, max_new_tokens=12) for p in prompts]
    eng.run()
    assert [list(r.tokens) for r in reqs] == [list(r.tokens) for r in plain]
    assert eng.drafter is None and eng.spec_k == 0


def test_every_draft_is_the_modules_first_choice_over_a_fresh_forward(drafted):
    """After each dispatch a lane's draft is what the module, run afresh over the prompt and
    every token so far, puts first at the row before the pending token: the rows a rejected
    draft left were written again before they were read, and the module's rows agree with
    its whole-sequence form. Both kinds of round happened."""
    cfg, params, prompts, reqs, seen, _, stats = drafted
    assert 0.0 < stats["spec_accept_rate"] < 1.0
    # one forward over each request's whole sequence, right-padded to one width: every
    # draft's row sees only tokens before it (causal attention, a causal selection)
    seqs = [np.concatenate([p, r.tokens]).astype(np.int32) for p, r in zip(prompts, reqs)]
    width = max(len(q) for q in seqs)
    batch = np.stack([np.pad(q, (0, width - len(q))) for q in seqs])
    _, module = forward_mtp(params, jnp.asarray(batch), cfg)
    module = np.asarray(module)
    for draft, seq in seen:
        i = next(i for i, q in enumerate(seqs) if np.array_equal(q[:len(seq)], seq))
        assert draft == int(np.argmax(module[i, len(seq) - 2]))


def test_the_drain_counts_drafts_acceptances_and_lane_rounds(drafted):
    """``engine.decode.drain`` carries ``spec_proposed`` / ``spec_accepted`` /
    ``spec_lane_rounds`` (and the model's counters) beside the tokens; the engine's
    acceptance totals are their sums; one draft a running lane and round."""
    *_, drains, stats = drafted
    assert drains and all({"spec_proposed", "spec_accepted", "spec_lane_rounds", "moe_pairs",
                           "dsa_keys_scored"} <= set(d.attrs) for d in drains)
    proposed = sum(d.attrs["spec_proposed"] for d in drains)
    assert proposed == stats["spec_proposed"] == sum(d.attrs["spec_lane_rounds"] for d in drains)
    assert sum(d.attrs["spec_accepted"] for d in drains) == stats["spec_accepted"]
    sums = stats["phases"]["engine.decode.drain"]["sums"]
    assert sums["spec_lane_rounds"] >= proposed > 0


def test_the_draft_count_comes_from_the_config():
    """The engine's default ``spec_k`` is the config's ``mtp_draft`` — the module drafting
    — and 0 for every config without one (V3's, dots3's and Keye's programs unchanged)."""
    for mod in (ds, dots3, keye):
        cfg = mod.CONFIGS["tiny"]
        eng = engine(cfg, mod.init_params(cfg, jax.random.PRNGKey(0)), page_size=16)
        assert eng.spec_k == 0 and eng.drafter is None and "draft" not in eng.cache
    cfg = v32.CONFIGS["tiny"]
    eng = engine(cfg, v32.init_params(cfg, jax.random.PRNGKey(0)))
    assert eng.spec_k == 1 and isinstance(eng.drafter, ModuleDrafter) and eng._spec_fused()


@pytest.mark.parametrize("kw,what", [
    ({"spec_k": 2}, r"drafts 1 token\(s\) a round; spec_k=2"),
    ({"spec_accept": "residual"}, r"spec_accept='replay'"),
    ({"spec_k": 1, "drafter": ModuleDrafter(), "page_size": 0, "kv_pages": None},
     r"has no forward_slots"),
], ids=["draft_count", "residual", "dense_rows"])
def test_the_module_drafter_refuses_what_it_cannot_do(kw, what):
    cfg = v32.CONFIGS["tiny"]
    with pytest.raises((ValueError, NotImplementedError), match=what):
        engine(cfg, {"not": "touched"} if "page_size" in kw else
               v32.init_params(cfg, jax.random.PRNGKey(0)), **kw)
    with pytest.raises(ValueError, match="no prediction module"):
        engine(ds.CONFIGS["tiny"], ds.init_params(ds.CONFIGS["tiny"], jax.random.PRNGKey(0)),
               spec_k=1, drafter=ModuleDrafter())


def test_a_paged_engine_asks_only_for_paged_spec_calls():
    """``spec_k`` over pages needs the fused rounds and the paged verify, not dense rows:
    DeepSeek-V3 (no ``forward_slots``) now speculates with the n-gram drafter and emits what
    it emits without; a module that lacks the fused rounds is still refused by name."""
    cfg = ds.CONFIGS["tiny"]
    params = ds.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32) for n in (9, 30)]
    outs = []
    for kw in ({}, {"spec_k": 2}, {"spec_k": 2, "decode_steps": 1}):
        eng = engine(cfg, params, **kw)
        reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
        eng.run()
        outs.append([list(r.tokens) for r in reqs])
        assert isinstance(eng.drafter, NgramDrafter) or not kw
    assert outs[0] == outs[1] == outs[2]
    with pytest.raises(NotImplementedError,
                       match=r"models\.dots3 has no forward_slots_spec_multi: the engine's "
                             r"spec_k path"):
        engine(dots3.CONFIGS["tiny"], {"not": "touched"}, spec_k=2)
