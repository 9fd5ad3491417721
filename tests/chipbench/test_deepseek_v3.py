"""CPU tests of what ``families/deepseek_v3.py`` and its cell add to the benchmark: the
family's counts by hand at the published widths, the configuration against its source's
sizes, the traffic's seed properties, the cell's dry run with its float8 control, and the
message ``run.load_family`` gives for a model the benchmark lacks. Nothing here is a
measurement; no topology or TPU call anywhere.
"""

import json
import os

import numpy as np
import pytest

from benchmarks.chipbench import run, schema, traffic, work

FAMILY = run.load_family("deepseek_v3")
CELL, NAME, TRAFFIC = "serve_deepseekv3_longctx", "deepseek-v3-serve-ep16-d5", "longctx_backlog"
BENCH = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))

# deepseek-ai/DeepSeek-V3 config.json, every key that says something of the model's shape
SOURCE = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3, "hidden_act": "silu",
    "hidden_size": 7168, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "model_type": "deepseek_v3",
    "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280,
}


def config():
    with open(os.path.join(run.HERE, "configs", f"{NAME}.json")) as f:
        return json.load(f)


def dry(capsys, *extra, seconds="3"):
    rc = run.main(["--workload", CELL, "--seed", "2147483659", "--seconds", seconds,
                   "--cpu-dry-run", *extra])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# ------------------------------------------------------------------------ configuration
def test_the_configuration_is_the_source_outside_reduced_and_states_its_cut():
    c = config()
    assert c["source"] == "https://huggingface.co/deepseek-ai/DeepSeek-V3/blob/main/config.json"
    assert sorted(c["reduced"]) == sorted(
        ["n_routed_experts", "vocab_size", "num_hidden_layers", "first_k_dense_replace"])
    assert {k: c[k] for k in SOURCE if k not in c["reduced"]} == {
        k: v for k, v in SOURCE.items() if k not in c["reduced"]}
    assert c["published"] == {k: SOURCE[k] for k in c["reduced"]}
    # the chip's share: 16 of 256 experts, an eighth of the vocabulary, 1 dense + 4 expert layers
    assert (c["n_routed_experts"], c["vocab_size"]) == (16, 129280 // 8)
    assert (c["num_hidden_layers"], c["first_k_dense_replace"]) == (5, 1)
    assert {"assumed", "deployment", "serve", "limits"} <= set(c)
    assert schema.config_problems(run.ROOT) == []


def test_the_cell_is_entries_and_files_the_benchmark_did_not_have():
    reported = {"serve_tokens_per_s", "setup_s"}
    own = {"mla_attn_roofline", "mla_attn_ms_per_dispatch", "moe_pairs_per_token",
           "moe_max_over_mean_load"}
    assert schema.cell_problems(BENCH, CELL, NAME, TRAFFIC, 1, own, reported) == []
    assert not hasattr(FAMILY, "loss")        # serving rows only: no training cell on it


# ------------------------------------------------------------------------------- counts
def test_attention_and_matmul_params_by_hand():
    c = config()
    # q_a 7168·1536 + q_b 1536·128·192 + kv_a 7168·576 + kv_b 512·128·256 + o 16384·7168
    attn = 7168 * 1536 + 1536 * 24576 + 7168 * 576 + 512 * 32768 + 16384 * 7168
    assert attn == 187_105_280 == FAMILY.attention_params(c)
    expert = 3 * 7168 * 2048
    assert expert == 44_040_192 == FAMILY.expert_params(c)
    want = (5 * attn + 3 * 7168 * 18432            # five attentions, the dense layer
            + 4 * (7168 * 256 + expert)            # router at its published width + shared
            + 7168 * 16160)                        # the head over the slice
    assert FAMILY.matmul_params(c) == want
    # a token meets 8 · 16/256 = half a routed expert an expert layer, in expectation
    assert FAMILY.serve_flops_per_token(c) == pytest.approx(2 * (want + 4 * 0.5 * expert))


def test_latent_attention_work_by_hand_and_which_bound_it_meets():
    c, peak = config(), work.peaks("TPU v5 lite")
    bucket = c["serve"]["prompt_bucket"]
    one = FAMILY.paged_attn_work(c, [bucket], 16)           # one lane, one live key
    two = FAMILY.paged_attn_work(c, [bucket + 1], 16)       # ... and one more
    # a key a layer: the two score products and p·c_kv, 2·128·(576 + 512); 576 bf16 values
    assert (two[0] - one[0]) / 5 == 278_528 == 2 * 128 * 1088
    assert (two[1] - one[1]) / 5 == 1_152
    assert one[1] == 5 * (1_152 + 128 * 1088 * 2)           # + q_lat, q_rope and o_lat a lane
    # lanes are counted from their first valid slot: the pad a chunk layout may hold is off
    lens = [4096 + 300, 9000, 13000]
    keys = sum(n - (bucket - 1) for n in lens)
    flops, nbytes = FAMILY.paged_attn_work(c, lens, 16)
    assert flops == 5 * 278_528 * keys
    assert nbytes == 5 * (1_152 * keys + 3 * 128 * 1088 * 2)
    # 242 FLOP/B a key against the v5e's ridge of 240: the two bounds lie within 3 % of each
    # other (q and o a lane tip these lanes to the memory side), and the least is the larger
    assert 278_528 / 1_152 == pytest.approx(241.8, abs=0.1) and 197e12 / 819e9 == pytest.approx(240.5, abs=0.1)
    assert flops / 197e12 == pytest.approx(nbytes / 819e9, rel=0.03)
    assert work.least_seconds(flops, nbytes, peak) == max(flops / 197e12, nbytes / 819e9)


# ------------------------------------------------------------------------------ traffic
def test_longctx_backlog_same_seed_same_requests_any_seed_same_work():
    spec = traffic.load("traffic", TRAFFIC)
    a, b = (traffic.serve_requests(spec, 16160, 77, 51.0) for _ in range(2))
    assert len(a) == len(b) == spec["requests"] == 160
    for x, y in zip(a, b):
        assert x["max_new"] == y["max_new"] and np.array_equal(x["prompt"], y["prompt"])
    other = traffic.serve_requests(spec, 16160, 2**31 + 11, 51.0)
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_new"]):
        assert sorted(map(key, a)) == sorted(map(key, other))
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in other]
    lens, outs = [len(r["prompt"]) for r in a], [r["max_new"] for r in a]
    assert 4096 <= min(lens) and max(lens) <= 12288 and 256 <= min(outs) and max(outs) <= 768
    assert all(r["prompt"].max() < 16160 for r in a)        # ids from the vocabulary's slice
    # every request fits a lane of the engine the configuration builds
    assert max(lens) + max(outs) <= config()["serve"]["max_len"]


# ------------------------------------------------------------------------- the dry run
def test_dry_run_is_correct_and_its_float8_control_is_not(capsys):
    line = dry(capsys, "--control", "1", seconds="4")
    assert line["dry_run"] is True and line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert line["readings"]["tokens_compared"] > 10
    gap = line["compared"]["served_logit_gap"]
    assert gap["value"] <= gap["limit"] < line["readings"]["control_fp8.served_logit_gap"]


def test_dry_run_traced_reports_the_expert_counters(capsys, monkeypatch, tmp_path):
    # Every traced run writes <ROOT>/.cb_trace, and the tests of other files trace too, on
    # other workers: this one keeps its trace in a directory of its own.
    from benchmarks.chipbench import program_spans

    root = run.ROOT
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    monkeypatch.setattr(program_spans, "ROOT", str(tmp_path))
    line = dry(capsys, "--trace", "1", "--root", root, seconds="4")
    assert {"moe_pairs_per_token", "moe_max_over_mean_load",
            "decode_occupancy_mean.throughput"} <= set(line["metrics"])
    # the dry run holds 8 of 16 experts at 4 a token: 2 pairs a token in expectation
    assert 1.0 < line["metrics"]["moe_pairs_per_token"]["value"] < 3.0
    assert line["metrics"]["moe_max_over_mean_load"]["value"] >= 1.0
    assert "mla_attn_roofline" not in line["metrics"]       # a roofline is a chip's


# ------------------------------------------------------------------- the stale message
def test_a_model_the_benchmark_lacks_names_the_family_file_it_looked_for(tmp_path):
    """What ``test_chipbench.py::test_a_model_type_without_a_family_names_the_file_it_
    looked_for`` guarded with ``deepseek_v3``, which the benchmark now has: here with a
    ``model_type`` no catalog row carries."""
    with pytest.raises(FileNotFoundError, match=r"families/no_such_model_type\.py"):
        run.load_family("no_such_model_type")
    with pytest.raises(FileNotFoundError, match=str(tmp_path)):
        run.load_family("deepseek_v3", str(tmp_path))
