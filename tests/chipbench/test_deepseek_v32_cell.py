"""CPU tests of what ``families/deepseek_v32.py`` and its cell add to the benchmark: the
configuration against the catalog's row outside ``reduced``, the cut's parameters and the
kernels' work by hand at the published widths, the traffic's seed properties, the cell's
entries, and the new readers on a synthetic phase ledger and on a program without them.
Nothing here is a measurement; no topology or TPU call anywhere.
"""

import json
import os
import types

import numpy as np

from accelerate_tpu.telemetry.tracing import PhaseRecord
from benchmarks.chipbench import program_phases, run, schema, traffic, work

FAMILY = run.load_family("deepseek_v32")
CELL, NAME, TRAFFIC = "serve_deepseekv32_mtp_reason", "deepseek-v32-serve-ep32-d5", "reasoning_backlog"
BENCH = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))

# deepseek-ai/DeepSeek-V3.2 config.json, every key that says something of the language
# model's shape (the catalog row's ``config``)
SOURCE = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3, "hidden_act": "silu",
    "hidden_size": 7168, "index_head_dim": 128, "index_n_heads": 64, "index_topk": 2048,
    "intermediate_size": 18432, "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v32", "moe_intermediate_size": 2048, "moe_layer_freq": 1,
    "n_group": 8, "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 128, "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280,
}
NEW_METRICS = {"spec_accept_share_window.v32", "spec_drafted_share_window.v32",
               "dsa_index_roofline.v32", "mla_attn_roofline.v32"}
MS = 1_000_000


def config():
    with open(os.path.join(run.HERE, "configs", f"{NAME}.json")) as f:
        return json.load(f)


# ------------------------------------------------------------------------ configuration
def test_the_configuration_is_the_source_outside_reduced_and_states_its_cut():
    c = config()
    assert c["source"] == "https://huggingface.co/deepseek-ai/DeepSeek-V3.2/blob/main/config.json"
    cut = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size"]
    assert c["reduced"] == cut and c["published"] == {k: SOURCE[k] for k in cut}
    assert {k: c[k] for k in SOURCE if k not in cut} == {
        k: v for k, v in SOURCE.items() if k not in cut}
    # one chip of EP32: 1 dense + 4 expert layers, 8 of 256 experts, an eighth of the vocabulary
    assert [c[k] for k in cut] == [5, 1, 8, 129280 // 8]
    assert {"eh_proj", "module_hidden", "module_layer", "shared_head", "index_keys",
            "indexer", "rope_pairing", "router_bias_std", "weights"} <= set(c["assumed"])
    assert "EP32" in c["deployment"] and "drafter" in c["deployment"]
    assert c["serve"] == {"dtype": "bfloat16", "max_slots": 16, "max_len": 8704,
                          "page_size": 16, "kv_pages": 8704, "prompt_bucket": 512,
                          "decode_steps": 4, "draft_tokens": 1}
    assert set(c["limits"]) == {"served_logit_gap_p90", "served_logit_gap_mean"}
    assert schema.config_problems(run.ROOT) == []
    cfg = FAMILY.program_config(c)
    assert (cfg.mtp_draft, cfg.experts_held, cfg.n_routed_experts, cfg.index_topk) == (1, 8, 256, 2048)


def test_the_cell_is_entries_and_files_the_benchmark_did_not_have():
    reported = {"serve_tokens_per_s", "setup_s"}
    assert schema.cell_problems(BENCH, CELL, NAME, TRAFFIC, 1, NEW_METRICS, reported) == []
    assert schema.window_entry_problems(BENCH, run.ROOT) == []
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    shared = {m["name"] for m in BENCH["per_layer"] if run.applies(m, cell)} - NEW_METRICS
    assert shared == {"device_idle_share.throughput", "hbm_peak_gb.throughput",
                      "kv_page_occupancy_peak.throughput", "serve_mfu.throughput",
                      *schema.WINDOW[1::2], "moe_pairs_per_token_window.throughput",
                      "moe_max_over_mean_load_window.throughput",
                      "dsa_selected_share_window.throughput", "prefill_ms_per_chunk.throughput"}
    assert "decode_occupancy_mean.throughput" not in shared    # one token a lane a step: not here
    assert not hasattr(FAMILY, "loss")        # serving rows only: no training cell on it


# ------------------------------------------------------------------------------- counts
def test_the_cuts_parameters_by_hand():
    c = config()
    attention = 7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 + 512 * 128 * 256 + 128 * 128 * 7168
    indexer = 1536 * 64 * 128 + 7168 * 128 + 7168 * 64
    assert (attention, indexer) == (187_105_280, 13_959_168)
    assert FAMILY.attention_params(c) == attention + indexer
    expert = 3 * 7168 * 2048
    assert expert == FAMILY.expert_params(c) == 44_040_192
    dense = attention + indexer + 3 * 7168 * 18432
    moe = attention + indexer + 7168 * 256 + expert + 8 * expert
    module = 2 * 7168 * 7168 + moe
    ends = 2 * 16160 * 7168
    assert (dense, moe, module, ends) == (597_426_176, 599_261_184, 702_021_632, 231_669_760)
    assert dense + 4 * moe + module + ends == 3_928_162_304           # 7.86 GB in bfloat16
    # the same count without the module and the indexer at 16 experts: the V3 file's figure
    assert dense - indexer + 4 * (moe - indexer + 8 * expert) + ends == 4_565_630_976
    per_moe = 7168 * 256 + expert
    want = 6 * (attention + indexer) + 3 * 7168 * 18432 + 5 * per_moe + 2 * 7168 ** 2 + 7168 * 16160
    assert FAMILY.matmul_params(c) == want
    # 8 of 256 experts here: a token meets 8 × 8 / 256 = 0.25 of one an expert layer
    assert FAMILY.serve_flops_per_token(c) == 2.0 * (want + 5 * 0.25 * expert)


def test_the_kernels_work_by_hand_and_which_bound_each_meets():
    """A round hands the kernels TWO query rows a lane (the pending token and the draft) in
    each of 6 layers (5 and the module): the index kernel scores every live key of each,
    the latent kernel reads the rows each selected."""
    c, peak = config(), work.peaks("TPU v5 lite")
    pad = c["serve"]["prompt_bucket"] - 1
    lens = [3072 + pad, 6000 + pad, pad + 1000]        # the last lane holds 1000 live keys
    rows = [3072, 3073, 6000, 6001, 1000, 1001]
    flops, nbytes = FAMILY.paged_attn_work(c, lens, 16)
    chosen = 4 * 2048 + 1000 + 1001
    per_q = 128 * (2 * 512 + 64)
    assert flops == 6 * 2 * per_q * chosen
    assert nbytes == 6 * (chosen * 576 * 2 + len(rows) * per_q * 2)
    # 242 FLOP a key's byte against the v5e's ridge of 240.5; q and o a row tip it to bytes
    assert work.least_seconds(flops, nbytes, peak) == nbytes / 819e9 > flops / 197e12
    iflops, ibytes = FAMILY.dsa_index_work(c, lens, 16)
    assert iflops == 6 * 2 * 64 * 128 * sum(rows)
    assert ibytes == 6 * (sum(rows) * (128 * 2 + 4) + len(rows) * (64 * 128 * 2 + 64 * 4))
    assert work.least_seconds(iflops, ibytes, peak) == ibytes / 819e9 > iflops / 197e12
    # past topk the rows handed on stay, the scores grow
    far = [n + 2048 for n in lens[:2]]
    assert FAMILY.paged_attn_work(c, far, 16) == FAMILY.paged_attn_work(c, lens[:2], 16)
    assert FAMILY.dsa_index_work(c, far, 16)[0] > FAMILY.dsa_index_work(c, lens[:2], 16)[0]


# ------------------------------------------------------------------------------ traffic
def test_the_mix_is_the_same_work_for_any_seed_and_fits_the_lanes():
    spec, c = traffic.load("traffic", TRAFFIC, root=run.HERE), config()
    assert (spec["kind"], spec["requests"], spec["block"], spec["check_requests"]) == (
        "serve_backlog", 160, 8, 3)
    assert (spec["trace_after_s"], spec["trace_seconds"]) == (20, 6)
    with open(os.path.join(run.HERE, "traffic", f"{TRAFFIC}.json")) as f:
        assert "dry_run" in json.load(f)                  # toy sizes for the CPU's dry run
    assert (spec["prompt"], spec["output"]) == (
        {"dist": "uniform", "min": 3072, "max": 6144}, {"dist": "uniform", "min": 1024, "max": 2048})
    a, b, other = (traffic.serve_requests(spec, c["vocab_size"], s, 51) for s in (7, 7, 2 ** 31 + 12))
    assert all((x["prompt"] == y["prompt"]).all() and x["max_new"] == y["max_new"]
               for x, y in zip(a, b))
    for reqs in (a, other):
        assert sorted(len(r["prompt"]) for r in reqs) == sorted(len(r["prompt"]) for r in a)
        assert sorted(r["max_new"] for r in reqs) == sorted(r["max_new"] for r in a)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in other]
    lens = np.array([len(r["prompt"]) for r in a])
    # every context past topk; the longest, padded to whole chunks, fits a lane
    assert lens.min() > 2048 and all(r["max_new"] <= 2048 for r in a)
    bucket, sv = c["serve"]["prompt_bucket"], c["serve"]
    assert max(-(-n // bucket) * bucket for n in lens) + 2048 <= sv["max_len"]
    # the pool holds 16 whole lanes: admission never defers
    assert sv["kv_pages"] * sv["page_size"] == sv["max_slots"] * sv["max_len"]


# ------------------------------------------------------------------------------ readers
def drains_by_hand(rounds_live=((16, 16), (16, 15), (15, 15), (14, 16))):
    """Four drains in a window [0, 1 s], a dispatch of two rounds each: ``spec_lane_rounds``
    the live lanes summed over its rounds, one draft each, an acceptance on every fifth."""
    out = []
    for i, live in enumerate(rounds_live):
        t = (100 + 200 * i) * MS
        out.append(PhaseRecord("engine.decode.drain", t, t + 5 * MS, 5 * MS, 2,
                               {"tokens": sum(live) + sum(live) // 5,
                                "spec_proposed": sum(live), "spec_accepted": sum(live) // 5,
                                "spec_lane_rounds": sum(live)}, 7))
    return out


def run_with(records):
    return types.SimpleNamespace(program_phases=program_phases.spans(records),
                                 obs={"t0": 0.0, "t_close": 1.0}, config=config(), trace=None)


def test_the_spec_readers_on_a_synthetic_phase_ledger():
    records = drains_by_hand()
    proposed = sum(r.attrs["spec_proposed"] for r in records)
    accepted = sum(r.attrs["spec_accepted"] for r in records)
    assert run.read_metric("spec_accept_share_window.v32", run_with(records)) == accepted / proposed
    assert run.read_metric("spec_drafted_share_window.v32", run_with(records)) == 1.0
    # a change that drafts for one lane in four fewer rounds reads it
    fewer = [PhaseRecord(r.name, r.t0_ns, r.t1_ns, r.self_ns, r.depth,
                         {**r.attrs, "spec_proposed": r.attrs["spec_proposed"] * 3 // 4},
                         r.thread) for r in records]
    assert run.read_metric("spec_drafted_share_window.v32", run_with(fewer)) < 0.8
    # under three drains, nothing
    assert run.read_metric("spec_accept_share_window.v32", run_with(records[:2])) is None


def test_the_new_readers_find_nothing_in_a_program_without_the_counts_or_the_program():
    """On a program without the counters (the parent's, or a cell that does not speculate)
    and without a trace each new reader returns nothing and does not raise."""
    plain = [PhaseRecord("engine.decode.drain", t * MS, (t + 5) * MS, 5 * MS, 2,
                         {"tokens": 64, "moe_pairs": 10, "moe_tokens": 40}, 7)
             for t in (100, 300, 500, 700)]
    empty = types.SimpleNamespace(**{**vars(run_with(plain)), "family": FAMILY,
                                     "slice_host": [0.0, 1.0], "peak": None})
    for name in sorted(NEW_METRICS):
        assert run.read_metric(name, empty) is None, name
