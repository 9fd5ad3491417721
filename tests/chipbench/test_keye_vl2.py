"""CPU tests of what ``families/KeyeVL2.py`` and its cell add to the benchmark: the family's
counts by hand at the published widths, the configuration against the catalog's row
outside ``reduced``, the traffic's seed properties, and the cell's dry run — its last line,
its float8 control and its new per-layer metrics. Nothing here is a measurement; no
topology or TPU call anywhere.
"""

import ast
import json
import os
import re
import types

import numpy as np
import pytest

from benchmarks.chipbench import run, schema, traffic, work

FAMILY = run.load_family("KeyeVL2")
CELL, NAME, TRAFFIC = "serve_keyevl2_sparse16k", "keye-vl2-serve-d5", "sparsectx_longout_backlog"
BENCH = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))

# Kwai-Keye/Keye-VL-2.0-30B-A3B config.json, every key that says something of the language
# model's shape (the catalog row's ``config``)
SOURCE = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 262144,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                  "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}
NEW_METRICS = {"dsa_index_roofline.keye16k", "dsa_index_ms_per_dispatch.keye16k",
               "dsa_selected_share.keye16k", "moe_pairs_per_token.keye16k",
               "moe_max_over_mean_load.keye16k", "sparse_kv_attn_roofline",
               "sparse_kv_attn_ms_per_dispatch", "sparse_select_ms_per_dispatch"}


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
def test_the_configuration_is_the_source_but_for_its_depth_and_states_its_cut():
    c = config()
    assert c["source"] == "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json"
    assert c["reduced"] == ["num_hidden_layers"] and c["published"] == {"num_hidden_layers": 48}
    assert {k: c[k] for k in SOURCE if k != "num_hidden_layers"} == {
        k: v for k, v in SOURCE.items() if k != "num_hidden_layers"}
    assert c["num_hidden_layers"] == 5             # the only cut: 128 experts, the whole vocabulary
    assert c["n_routed_experts"] == c["num_experts"]     # the accepted reader's spelling
    assert {"qk_norm", "index_rope_dim", "indexer", "chunk_sizes", "index_keys", "mrope",
            "rope_pairing", "weights", "left_out", "n_routed_experts"} <= set(c["assumed"])
    assert "vision tower" in c["assumed"]["left_out"]
    assert {"deployment", "serve", "limits", "counts"} <= set(c)
    assert c["serve"] == {"dtype": "bfloat16", "max_slots": 16, "max_len": 32768,
                          "page_size": 16, "kv_pages": 24576, "prompt_bucket": 512,
                          "decode_steps": 4}
    assert schema.config_problems(run.ROOT) == []
    entry = next(e for e in BENCH["configs"] if e["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers"] and entry["source"] == c["source"]


def test_the_cell_is_entries_and_files_the_benchmark_did_not_have():
    reported = {"serve_tokens_per_s", "setup_s"}
    assert schema.cell_problems(BENCH, CELL, NAME, TRAFFIC, 1, NEW_METRICS, reported) == []
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    shared = {m["name"] for m in BENCH["per_layer"] if run.applies(m, cell)} - NEW_METRICS
    assert shared and all(n.endswith(".throughput") for n in shared)
    assert not hasattr(FAMILY, "loss")        # serving rows only: no training cell on it


# ------------------------------------------------------------------------------- counts
def test_params_and_flops_a_token_by_hand():
    c = config()
    attention = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    indexer = 2048 * 16 * 64 + 2048 * 64 + 2048 * 16
    assert (attention, indexer) == (18_874_368, 2_260_992)
    assert FAMILY.attention_params(c) == attention + indexer
    expert = 3 * 2048 * 768
    assert expert == 4_718_592 == FAMILY.expert_params(c)
    want = 5 * (attention + indexer + 2048 * 128) + 2048 * 151936
    assert FAMILY.matmul_params(c) == want == 418_152_448
    # the whole cut: + 128 experts a layer and the embedding: 3.749 B parameters, 7.50 GB
    assert want + 5 * 128 * expert + 2048 * 151936 == 3_749_216_256
    # a request of the mix's mean lengths, 16384 + 1024 tokens: a token at position p scores
    # p + 1 index keys and attends min(p + 1, 2048)
    live, attended = FAMILY.mean_keys(c)
    n = 17408
    assert live == (n + 1) / 2 and attended == pytest.approx(
        sum(min(p + 1, 2048) for p in range(n)) / n)
    cache = 5 * (2 * 16 * 64 * live + 4 * 32 * 128 * attended)
    assert FAMILY.serve_flops_per_token(c) == pytest.approx(
        2 * (want + 5 * 8 * expert) + cache)
    # the index and score products are a fifth of the experts' and projections' FLOPs
    assert 0.15 < cache / (2 * (want + 5 * 8 * expert)) < 0.25


def test_the_kernels_work_by_hand_and_which_bound_each_meets():
    c, peak = config(), work.peaks("TPU v5 lite")
    bucket = c["serve"]["prompt_bucket"]
    lens = [8192 + 300, 17000, 24000, bucket + 99]      # the last lane holds 100 live keys
    flops, nbytes = FAMILY.paged_attn_work(c, lens, 16)
    rows = 3 * 2048 + 100                # the rows the kernel is HANDED: <= topk a lane
    assert flops == 5 * 4 * 32 * 128 * rows
    assert nbytes == 5 * (rows * 2 * 4 * 128 * 2 + 4 * 2 * 32 * 128 * 2)
    # 8 FLOP/B (every K/V byte serves a group of 8 query heads): bandwidth-bound
    assert work.least_seconds(flops, nbytes, peak) == nbytes / 819e9 > flops / 197e12
    iflops, ibytes = FAMILY.dsa_index_work(c, lens, 16)
    keys = sum(n - (bucket - 1) for n in lens)
    assert iflops == 5 * 2 * 16 * 64 * keys
    assert ibytes == 5 * (keys * (64 * 2 + 4) + 4 * (16 * 64 * 2 + 16 * 4))   # 128 B a key
    # 15.5 FLOP/B, half of dots3's 31 a byte: bandwidth-bound, further from the ridge
    assert work.least_seconds(iflops, ibytes, peak) == ibytes / 819e9 > iflops / 197e12
    # far past topk the chosen rows do not grow with the context, the scores do
    far = [n + 8192 for n in lens[:3]]
    assert FAMILY.paged_attn_work(c, far, 16) == FAMILY.paged_attn_work(c, lens[:3], 16)
    assert FAMILY.dsa_index_work(c, far, 16)[0] > FAMILY.dsa_index_work(c, lens[:3], 16)[0]


# ------------------------------------------------------------------------------ traffic
def test_the_mix_is_the_same_work_for_any_seed_and_fills_the_pool():
    spec, c = traffic.load("traffic", TRAFFIC, root=run.HERE), config()
    assert (spec["kind"], spec["requests"], spec["block"], spec["check_requests"]) == (
        "serve_backlog", 160, 8, 3)
    assert (spec["prompt"], spec["output"]) == (
        {"dist": "uniform", "min": 8192, "max": 24576}, {"dist": "uniform", "min": 512, "max": 1536})
    a, b, other = (traffic.serve_requests(spec, c["vocab_size"], s, 51) for s in (7, 7, 2 ** 31 + 12))
    assert all((x["prompt"] == y["prompt"]).all() and x["max_new"] == y["max_new"]
               for x, y in zip(a, b))
    for reqs in (a, other):
        assert sorted(len(r["prompt"]) for r in reqs) == sorted(len(r["prompt"]) for r in a)
        assert sorted(r["max_new"] for r in reqs) == sorted(r["max_new"] for r in a)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in other]
    lens = np.array([len(r["prompt"]) for r in a])
    assert lens.min() >= 8192 and lens.max() <= 24576 and 16300 < lens.mean() < 16500
    assert max(len(r["prompt"]) + r["max_new"] for r in a) <= c["serve"]["max_len"]
    # every context is 4 to 13 times topk; 16 lanes of a mean request hold 2/3 of the pool
    assert lens.min() / 2048 >= 4 and (lens.max() + 1536) / 2048 < 13
    held = 16 * -(-(lens.mean() + 512) // 16)
    assert 0.6 < held / c["serve"]["kv_pages"] < 0.8
    assert (c["counts"]["mean_prompt_tokens"], c["counts"]["mean_output_tokens"]) == (16384, 1024)


# ------------------------------------------------------------------------- the dry run
def test_dry_run_is_correct_and_its_float8_control_is_not(capsys):
    """The last line of a ``--cpu-dry-run`` through ``run.py``: correct, nothing failed, the
    two end-to-end metrics; each compared number under its limit, and the float8 control
    (the family's stderr line) over at least one of them."""
    line, err = dry(capsys, "--control", "1", seconds="4", with_err=True)
    assert line["dry_run"] is True and line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert line["readings"]["tokens_compared"] > 10
    assert set(line["compared"]) == set(config()["limits"])
    control = ast.literal_eval(
        re.search(r"compare_serve \(control, \d+ tokens\): (\{.*\})", err).group(1))
    assert all(v["value"] <= v["limit"] for v in line["compared"].values())
    assert any(control[k] > v["limit"] for k, v in line["compared"].items())
    assert control["served_logit_gap"] == line["readings"]["control_fp8.served_logit_gap"]


def test_dry_run_traced_reports_the_new_per_layer_metrics(capsys, monkeypatch, tmp_path):
    # Every traced run writes <ROOT>/.cb_trace, and the tests of other files trace too, on
    # other workers: this one keeps its trace in a directory of its own.
    from benchmarks.chipbench import program_spans

    root = run.ROOT
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    monkeypatch.setattr(program_spans, "ROOT", str(tmp_path))
    line = dry(capsys, "--trace", "1", "--root", root, seconds="4")
    assert line["correct"] is True
    counters = {"dsa_selected_share.keye16k", "moe_pairs_per_token.keye16k",
                "moe_max_over_mean_load.keye16k"}
    assert counters | {"decode_occupancy_mean.throughput"} <= set(line["metrics"])
    # prompts of 100-300 against 64 keys kept: most of what is scored is not attended
    assert 0.15 < line["metrics"]["dsa_selected_share.keye16k"]["value"] < 0.6
    # every expert is held: a token meets all 4 it chose
    assert line["metrics"]["moe_pairs_per_token.keye16k"]["value"] == 4.0
    assert 1.0 <= line["metrics"]["moe_max_over_mean_load.keye16k"]["value"] < 8.0
    # the device-trace ones are the chip's: a dry run leaves them out
    assert not (NEW_METRICS - counters) & set(line["metrics"])


def test_the_new_readers_find_nothing_in_a_program_without_the_kernels_or_the_counts():
    """On a program without the ops and counters (the parent's) and on a configuration
    without ``sa_config`` each new reader returns nothing and does not raise."""
    empty = types.SimpleNamespace(trace=None, family=types.SimpleNamespace(), obs={},
                                  config=config(), slice_host=[0.0, 1.0], peak=None)
    for name in sorted(NEW_METRICS):
        assert run.read_metric(name, empty) is None, name
    other = types.SimpleNamespace(**{**vars(empty), "config": {"serve": {}}})
    assert run.read_metric("sparse_select_ms_per_dispatch", other) is None


def test_the_selection_ops_are_found_by_shape():
    """``sparse_select_ms_per_dispatch`` names the decode program's sort and gathers by the
    shapes the configuration gives (a v5e trace of PR 34 has these names), and neither of
    the two kernels beside them."""
    reader = run.load_by_path("cb_metric_select", os.path.join(
        run.HERE, "metrics", "sparse_select_ms_per_dispatch.py"))
    rx = re.compile(reader.pattern(config()))
    mod = "jit__decode_multi_step_paged/"
    for op in ("sort.187_f32_16_32768", "fusion.1283_bf16_32768_4_128", "fusion.1175_s32_32768"):
        assert rx.search(mod + op), op
    for op in ("dsa_index_scores.40_f32_16_32_1024__mosaic_", "gmm.5_bf16_128_2048__mosaic_",
               "paged_attention.44_bf16_16_32_128__mosaic_", "rev.89_pred_16_32768",
               "fusion.1158_bf16_25165824"):
        assert not rx.search(mod + op), op
    assert not rx.search("jit__prefill_chunk_jit/sort.1_f32_16_32768")
