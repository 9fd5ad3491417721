"""CPU tests of what ``families/dots3_note.py`` and its cell add to the benchmark: the
family's counts by hand at the published widths, the configuration against the catalog's
row outside ``reduced``, the traffic's seed properties, and the cell's dry run with its
float8 control. Nothing here is a measurement; no topology or TPU call anywhere.
"""

import json
import os

import numpy as np
import pytest

from benchmarks.chipbench import run, schema, traffic, work

FAMILY = run.load_family("dots3_note")
CELL, NAME, TRAFFIC = "serve_dots3_sparse16k", "dots3-note-serve-ep8-d5", "sparsectx_backlog"
BENCH = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))

PERIOD = ["full_attention"] + ["sliding_attention"] * 3
# dots-studio/dots3-note-prev config.json, every key that says something of the model's shape
SOURCE = {
    "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
    "attention_gate_type": "headwise", "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 5120, "index_head_dim": 128, "index_n_heads": 64, "index_topk": 2048,
    "intermediate_size": 13824, "kv_lora_rank": 512,
    "layer_types": ["full_attention"] + PERIOD * 11 + ["full_attention"],
    "max_position_embeddings": 524288, "model_type": "dots3_note",
    "moe_intermediate_size": 1536, "moe_layer_freq": 1, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 46, "num_key_value_heads": 128,
    "q_lora_rank": 1024, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 80000000,
    "routed_scaling_factor": 1, "scoring_func": "sigmoid", "sliding_window_size": 513,
    "swa_attention_gate_type": "headwise", "swa_kv_lora_rank": 1024,
    "swa_num_attention_heads": 64, "swa_num_key_value_heads": 64, "swa_q_lora_rank": 1024,
    "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64, "swa_rope_theta": 50000,
    "swa_v_head_dim": 128, "tie_word_embeddings": False, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 152064,
}


def config():
    with open(os.path.join(run.HERE, "configs", f"{NAME}.json")) as f:
        return json.load(f)


def dry(capsys, *extra, seconds="3", with_err=False):
    rc = run.main(["--workload", CELL, "--seed", "2147483659", "--seconds", seconds,
                   "--cpu-dry-run", *extra])
    assert rc == 0
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1])
    return (line, captured.err) if with_err else line


# ------------------------------------------------------------------------ configuration
def test_the_configuration_is_the_source_outside_reduced_and_states_its_cut():
    c = config()
    assert len(SOURCE["layer_types"]) == 46 and SOURCE["layer_types"].count("full_attention") == 13
    assert c["source"] == "https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json"
    assert sorted(c["reduced"]) == sorted(
        ["num_hidden_layers", "layer_types", "n_routed_experts", "vocab_size"])
    assert {k: c[k] for k in SOURCE if k not in c["reduced"]} == {
        k: v for k, v in SOURCE.items() if k not in c["reduced"]}
    assert c["published"] == {k: SOURCE[k] for k in c["reduced"]}
    # the chip's share: 32 of 256 experts, an eighth of the vocabulary; the dense layer and
    # one whole period, the first five of the published forty-six layer types
    assert (c["n_routed_experts"], c["vocab_size"]) == (32, 152064 // 8)
    assert c["num_hidden_layers"] == 5 and c["layer_types"] == SOURCE["layer_types"][:5]
    assert c["layer_types"][1:] == PERIOD and c["first_k_dense_replace"] == 1
    assert {"attention_gate_type", "apply_mla_qkv_lora_rescale", "sliding_window_size",
            "indexer", "index_keys", "left_out"} <= set(c["assumed"])
    assert {"deployment", "serve", "limits", "expert_offset"} <= set(c)
    assert schema.config_problems(run.ROOT) == []


def test_the_cell_is_entries_and_files_the_benchmark_did_not_have():
    reported = {"serve_tokens_per_s", "setup_s"}
    own = {"dsa_index_roofline", "dsa_index_ms_per_dispatch", "dsa_selected_share",
           "mla_attn_roofline.sparse16k", "mla_attn_ms_per_dispatch.sparse16k",
           "moe_pairs_per_token.sparse16k", "moe_max_over_mean_load.sparse16k"}
    assert schema.cell_problems(BENCH, CELL, NAME, TRAFFIC, 1, own, reported) == []
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    shared = {m["name"] for m in BENCH["per_layer"] if run.applies(m, cell)} - own
    assert shared and all(n.endswith(".throughput") for n in shared)
    assert not hasattr(FAMILY, "loss")        # serving rows only: no training cell on it


# ------------------------------------------------------------------------------- counts
def test_attention_and_matmul_params_by_hand():
    c = config()
    # q_a 5120·1024 + q_b 1024·128·192 + kv_a 5120·576 + kv_b 512·128·256 + o 16384·5120 + gate
    full = 5120 * 1024 + 1024 * 24576 + 5120 * 576 + 512 * 32768 + 16384 * 5120 + 5120 * 128
    index = 1024 * 64 * 128 + 5120 * 128 + 5120 * 64
    assert (full, index) == (134_676_480, 9_371_648)
    assert FAMILY.attention_params(c, "full_attention") == full + index
    # q_a 5120·1024 + q_b 1024·64·256 + kv_a 5120·1088 + kv_b 1024·64·320 + o 8192·5120 + gate
    sliding = 5120 * 1024 + 1024 * 16384 + 5120 * 1088 + 1024 * 20480 + 8192 * 5120 + 5120 * 64
    assert sliding == 90_832_896 == FAMILY.attention_params(c, "sliding_attention")
    expert = 3 * 5120 * 1536
    assert expert == 23_592_960 == FAMILY.expert_params(c)
    want = (2 * (full + index) + 3 * sliding + 3 * 5120 * 13824     # attentions, the dense layer
            + 4 * (5120 * 256 + expert)            # router at its published width + shared
            + 5120 * 19008)                        # the head over the slice
    assert FAMILY.matmul_params(c) == want
    # a token meets 8 · 32/256 = one routed expert an expert layer, in expectation
    assert FAMILY.serve_flops_per_token(c) == pytest.approx(2 * (want + 4 * 1.0 * expert))
    # the whole cut: + the 32 experts a layer and the embedding (4.087 B; the program's
    # tree holds 67 072 norm gains, biases and router biases more)
    assert want + 4 * 32 * expert + 5120 * 19008 == 4_087_087_104


def test_the_kernels_work_by_hand_and_which_bound_each_meets():
    c, peak = config(), work.peaks("TPU v5 lite")
    bucket = c["serve"]["prompt_bucket"]
    live = lambda n: n - (bucket - 1)                                    # noqa: E731
    lens = [8192 + 300, 17000, 24000, bucket + 99]      # the last lane holds 100 live keys
    flops, nbytes = FAMILY.paged_attn_work(c, lens, 16)
    # the rows the kernel is HANDED: <= 2048 a lane in a full layer, <= 513 in a sliding one
    full_rows, window_rows = 3 * 2048 + 100, 3 * 513 + 100
    per_full, per_window = 128 * (2 * 512 + 64), 64 * (2 * 1024 + 64)
    assert flops == 2 * (2 * per_full * full_rows) + 3 * (2 * per_window * window_rows)
    assert nbytes == (2 * (full_rows * 576 * 2 + 4 * per_full * 2)
                      + 3 * (window_rows * 1088 * 2 + 4 * per_window * 2))
    # the indexer: one product a live key and full layer, 256 B read and one score written
    iflops, ibytes = FAMILY.dsa_index_work(c, lens, 16)
    keys = sum(live(n) for n in lens)
    assert iflops == 2 * 2 * 64 * 128 * keys
    assert ibytes == 2 * (keys * 260 + 4 * (64 * 128 * 2 + 64 * 4))
    # 63 FLOP/B against the v5e's ridge of 240: the indexer is bandwidth-bound
    assert work.least_seconds(iflops, ibytes, peak) == ibytes / 819e9 > iflops / 197e12
    # far past index_topk the selected rows do not grow with the context, the scores do
    far = [n + 8192 for n in lens[:3]]
    assert FAMILY.paged_attn_work(c, far, 16) == FAMILY.paged_attn_work(c, lens[:3], 16)
    assert FAMILY.dsa_index_work(c, far, 16)[0] > FAMILY.dsa_index_work(c, lens[:3], 16)[0]


# ------------------------------------------------------------------------------ traffic
def test_sparsectx_backlog_same_seed_same_requests_any_seed_same_work():
    spec = traffic.load("traffic", TRAFFIC)
    c = config()
    a, b = (traffic.serve_requests(spec, c["vocab_size"], 77, 51.0) for _ in range(2))
    assert len(a) == len(b) == spec["requests"] == 160 and spec["block"] == 8
    for x, y in zip(a, b):
        assert x["max_new"] == y["max_new"] and np.array_equal(x["prompt"], y["prompt"])
    other = traffic.serve_requests(spec, c["vocab_size"], 2**31 + 11, 51.0)
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_new"]):
        assert sorted(map(key, a)) == sorted(map(key, other))
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in other]
    lens, outs = [len(r["prompt"]) for r in a], [r["max_new"] for r in a]
    assert 8192 <= min(lens) and max(lens) <= 24576 and 256 <= min(outs) and max(outs) <= 768
    assert all(r["prompt"].max() < c["vocab_size"] for r in a)   # ids from the vocabulary's slice
    # every context is 4-12x index_topk, and fits a lane of the engine the configuration builds
    assert 4 * c["index_topk"] <= min(lens) and max(lens) <= 12 * c["index_topk"]
    assert max(lens) + max(outs) <= c["serve"]["max_len"]
    # 32 lanes at the mean context fill about seven tenths of the pool's pages
    mean_pages = -(-(sum(lens) + sum(outs)) // (160 * 16))
    assert 0.6 < 32 * mean_pages / c["serve"]["kv_pages"] < 0.8


# ------------------------------------------------------------------------- the dry run
def test_dry_run_is_correct_and_its_float8_control_is_not(capsys):
    """The compared number is the gap's 90th percentile (the family's ``compare_serve`` says
    why); the control's comes from the family's stderr line, the harness keeps its maximum."""
    import ast
    import re

    line, err = dry(capsys, "--control", "1", seconds="4", with_err=True)
    assert line["dry_run"] is True and line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert line["readings"]["tokens_compared"] > 10
    assert set(line["compared"]) == set(config()["limits"]) == {"served_logit_gap_p90"}
    gap = line["compared"]["served_logit_gap_p90"]
    control = ast.literal_eval(re.search(r"compare_serve \(control, \d+ tokens\): (\{.*\})", err).group(1))
    assert gap["value"] <= gap["limit"] < control["served_logit_gap_p90"]
    assert control["served_logit_gap"] == line["readings"]["control_fp8.served_logit_gap"]
    assert {"served_logit_gap", "served_logit_gap_mean"} <= set(line["readings"])   # read, not judged


def test_dry_run_traced_reports_the_selection_and_expert_counters(capsys, monkeypatch, tmp_path):
    # Every traced run writes <ROOT>/.cb_trace, and the tests of other files trace too, on
    # other workers: this one keeps its trace in a directory of its own.
    from benchmarks.chipbench import program_spans

    root = run.ROOT
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    monkeypatch.setattr(program_spans, "ROOT", str(tmp_path))
    line = dry(capsys, "--trace", "1", "--root", root, seconds="4")
    assert {"dsa_selected_share", "moe_pairs_per_token.sparse16k",
            "moe_max_over_mean_load.sparse16k",
            "decode_occupancy_mean.throughput"} <= set(line["metrics"])
    # prompts of 20-120 against 16 keys kept: most of what is scored is not attended
    assert 0.1 < line["metrics"]["dsa_selected_share"]["value"] < 0.6
    # the dry run holds 8 of 16 experts at 4 a token: 2 pairs a token in expectation
    assert 1.0 < line["metrics"]["moe_pairs_per_token.sparse16k"]["value"] < 3.0
    assert not {"dsa_index_roofline", "mla_attn_roofline.sparse16k"} & set(line["metrics"])


def test_the_new_readers_find_nothing_in_a_program_without_the_kernel_or_the_count():
    """On the parent's program (no ``dsa_index_scores`` op, no ``dsa_keys_*`` attribute) and
    on a family without ``dsa_index_work`` each new reader returns nothing and does not raise."""
    import types

    empty = types.SimpleNamespace(trace=None, family=types.SimpleNamespace(), obs={},
                                  config=config(), slice_host=[0.0, 1.0], peak=None)
    for name in ("dsa_index_roofline", "dsa_index_ms_per_dispatch", "dsa_selected_share"):
        assert run.read_metric(name, empty) is None
