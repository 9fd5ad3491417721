"""CPU tests of the benchmark's own code (benchmarks/chipbench): the traffic generator,
the work functions, the trace reduction on a recorded TPU trace, ``run.py --cpu-dry-run``
end to end for each traffic kind, the control and the planted faults, the seam through which
a second model family comes in as files, and the schema of ``BENCHMARK.json`` and of the
configurations' files. Nothing here is a measurement; no topology or TPU call anywhere.
"""

import copy
import json
import os
import re
import shutil
import types

import numpy as np
import pytest

from benchmarks.chipbench import reference, run, schema, serve_window, trace_reduce, traffic
from benchmarks.chipbench import train_window, work

ROOT = run.ROOT
MISTRAL = run.load_family("mistral")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
TRAIN, CHAT, LONG = "train_mistral7b_s8k", "serve_mistral7b_chat", "serve_mistral7b_longprompt"
NAME = schema.NAME
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def config(name):
    with open(os.path.join(run.HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def dry(capsys, workload, *extra, seconds="3"):
    rc = run.main(["--workload", workload, "--seed", "2147483659", "--seconds", seconds,
                   "--cpu-dry-run", *extra])
    assert rc == 0
    return last_line(capsys)


# ------------------------------------------------------------------------------ traffic
@pytest.mark.parametrize("name", ["chat_open_loop", "longprompt_backlog"])
def test_same_seed_same_requests(name):
    spec = traffic.load("traffic", name)
    a, b = (traffic.serve_requests(spec, 32000, 77, 45.0) for _ in range(2))
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x["due"] == y["due"] and x["max_new"] == y["max_new"]
        assert np.array_equal(x["prompt"], y["prompt"])


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 11])
@pytest.mark.parametrize("name", ["chat_open_loop", "longprompt_backlog"])
def test_any_seed_same_multiset_of_work(name, seed):
    spec = traffic.load("traffic", name)
    base = traffic.serve_requests(spec, 32000, 12345, 45.0)
    other = traffic.serve_requests(spec, 32000, seed, 45.0)
    assert len(base) == len(other)
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_new"]):
        assert sorted(map(key, base)) == sorted(map(key, other))
    assert [len(r["prompt"]) for r in base] != [len(r["prompt"]) for r in other]


@pytest.mark.parametrize("name,uneven", [("chat_open_loop", 0.02), ("longprompt_backlog", 0.0)])
def test_every_block_of_requests_carries_the_same_work(name, uneven):
    """Whatever the seed, each block's prompt tokens (and output tokens) sum alike."""
    spec = traffic.load("traffic", name)
    reqs = [r for r in traffic.serve_requests(spec, 32000, 2**31 + 3, 51.0) if r["due"] >= 0
            or spec["kind"] == "serve_backlog"]
    assert len(reqs) % spec["block"] == 0
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_new"]):
        sums = [sum(map(key, reqs[i:i + spec["block"]])) for i in range(0, len(reqs), spec["block"])]
        assert max(sums) - min(sums) <= uneven * np.mean(sums) + 8      # + rounding of 8 lengths


def test_chat_arrival_count_is_fixed_by_the_rate():
    spec = traffic.load("traffic", "chat_open_loop")
    reqs = traffic.serve_requests(spec, 32000, 5, 45.0)
    due = [r["due"] for r in reqs if r["due"] >= 0]
    assert len(due) == round(spec["rate_per_s"] * (45.0 - spec["quiet_tail_s"]))
    assert max(due) <= 45.0 - spec["quiet_tail_s"] and due == sorted(due)
    assert sum(r["due"] < 0 for r in reqs) == spec["warm_in"]
    lens = [len(r["prompt"]) for r in reqs if r["due"] >= 0]
    assert spec["prompt"]["min"] <= min(lens) and max(lens) <= spec["prompt"]["max"]
    assert abs(np.median(lens) - spec["prompt"]["median"]) < 0.05 * spec["prompt"]["median"]


def test_train_batches_differ_row_by_row_and_repeat_by_seed():
    spec = traffic.load("traffic", "train_fixed_8k", dry=True)
    a, b = traffic.train_batches(spec, 512, 9), traffic.train_batches(spec, 512, 9)
    assert len(a) == spec["ring"] and all(np.array_equal(x, y) for x, y in zip(a, b))
    rows = np.concatenate(a)
    assert rows.shape == (spec["ring"] * spec["batch"], spec["seq"] + 1)
    assert len({r.tobytes() for r in rows}) == len(rows)


def test_serve_tokens_per_s_counts_every_token_emitted_in_the_window():
    """Tokens of unfinished requests count; tokens before the window's start do not."""
    w = serve_window.Window.__new__(serve_window.Window)
    w.spec = {"kind": "serve_backlog"}
    w.sv = {"max_slots": 4, "decode_steps": 4}
    w.ctx = types.SimpleNamespace(seconds=10.0)
    w.clock0, w.steps = 100.0, []
    req = types.SimpleNamespace(failed=None, done=False)
    w.requests = [
        {"due": -1, "prompt": np.zeros(7), "req": req, "times": [99.0, 100.5, 101.0, 111.0]},
        {"due": -1, "prompt": np.zeros(5), "req": req, "times": [104.0, 109.9]},
        {"due": -1, "prompt": np.zeros(5)},                      # never submitted
    ]
    obs = w.observe(100.0, 110.0)
    assert obs["end_to_end"]["serve_tokens_per_s"] == pytest.approx(4 / 10.0)
    assert obs["values"]["tokens_processed_per_s"] == pytest.approx((4 + 5) / 10.0)


# --------------------------------------------------------------------------------- work
@pytest.mark.parametrize("name,params", [
    ("mistral-7b-train-d2", 2 * 218_103_808 + 131_072_000),
    ("mistral-7b-serve-d16", 16 * 218_103_808 + 131_072_000),
])
def test_matmul_params_by_hand(name, params):
    # a layer: wq 4096² + wk, wv 2·4096·1024 + wo 4096² + 3·4096·14336; head 4096·32000
    assert 2 * 4096**2 + 2 * 4096 * 1024 + 3 * 4096 * 14336 == 218_103_808
    assert work.matmul_params(config(name)) == params


def test_train_flops_per_token_by_hand():
    c = config("mistral-7b-train-d2")
    assert work.mean_keys(8192, 4096) == pytest.approx(3072.25)
    assert work.mean_keys(1024, 4096) == pytest.approx(512.5)
    want = 6 * (2 * 218_103_808 + 131_072_000) + 12 * 2 * 4096 * 3072.25
    assert work.train_flops_per_token(c, 8192) == pytest.approx(want)
    assert work.serve_flops_per_token(config("mistral-7b-serve-d16")) == pytest.approx(
        2 * (16 * 218_103_808 + 131_072_000))


def test_kernel_work_by_hand_and_share_at_the_peak_is_100():
    c, peak = config("mistral-7b-train-d2"), work.peaks("TPU v5 lite")
    flops, nbytes = work.flash_work(c, 4, 8192)
    assert flops == pytest.approx(2 * 6 * 2 * 32 * 128 * 4 * 8192 * 3072.25)
    assert nbytes == 2 * 6 * (4 * 8192 * 32 * 128 * 2 + 4 * 8192 * 8 * 128 * 2)
    least = work.least_seconds(flops, nbytes, peak)
    assert least == pytest.approx(flops / 197e12)            # compute-bound
    at_peak = trace_reduce.Trace.__new__(trace_reduce.Trace)  # one step, its kernel at the peak
    at_peak.begin, at_peak.end = 0, int(2e9 * least)
    at_peak.devices = {"d": [(1000, 1000 + int(1e9 * least), "m/k__mosaic_")]}
    at_peak.modules = {"d": [(0, int(2e9 * least), "m")]}
    share = trace_reduce.train_kernel_roofline(types.SimpleNamespace(
        trace=at_peak, obs={"values": {"batch": 4, "seq": 8192}}, config=c, family=MISTRAL,
        peak=peak),
        "__mosaic_", "flash_work")
    assert share == pytest.approx(100.0, abs=1e-4) and share <= 100.0 + 1e-4
    c = config("mistral-7b-serve-d16")
    flops, nbytes = work.paged_attn_work(c, [40, 5000], 16)
    assert nbytes == 16 * ((48 + 4096) * 2 * 8 * 128 * 2 + 2 * 2 * 32 * 128 * 2)
    assert work.least_seconds(flops, nbytes, peak) == pytest.approx(nbytes / 819e9)  # memory-bound
    with pytest.raises(KeyError):
        work.peaks("cpu")


# ------------------------------------------------------------------------- trace_reduce
@pytest.fixture(scope="module")
def recorded():
    """Five train steps of cell 1 recorded on a TPU v5 lite in PR 24 (1.4 MB)."""
    return trace_reduce.Trace(os.path.join(run.HERE, "testdata", "train_5steps.xplane.pb"))


def test_recorded_trace_busy_union_and_slice(recorded):
    assert list(recorded.devices) == ["/device:TPU:0"]
    assert 5.9 < recorded.window_s < 6.0
    assert 0.0 < recorded.busy_s <= recorded.window_s
    merged = recorded._merged(recorded.devices["/device:TPU:0"])
    assert all(a[1] < b[0] for a, b in zip(merged, merged[1:]))
    assert recorded.busy_s == pytest.approx(sum(b - a for a, b in merged) / 1e9)


def test_recorded_trace_op_matching_leaves_out_containers(recorded):
    ops = recorded.op_seconds()
    assert not any(re.search(r"/while\.\d+", k) for k in ops)
    mosaic = recorded.op_seconds("jit_apply_step/.*__mosaic_")
    assert len(mosaic) == 4 and all(k.startswith("jit_apply_step/") for k in mosaic)
    assert trace_reduce.op_name("%while.17 = (s32[]) while(...)") is None
    assert trace_reduce.op_name(
        '%checkpoint.22 = (f32[4,8,8192,128]{3}) custom-call(), custom_call_target="tpu_custom_call"'
    ) == "checkpoint.22_f32_4_8_8192_128__mosaic_"


def test_recorded_trace_gap_attribution_and_roofline(recorded):
    gaps = recorded.idle_gaps()
    assert sum(gaps.values()) == pytest.approx(recorded.window_s - recorded.busy_s, abs=1e-6)
    assert all(re.match(r"^(_gaps_under_10_us_|\w[\w.]*_(before|inside)_\w+)$", k) for k in gaps)
    fake = trace_reduce.Trace.__new__(trace_reduce.Trace)
    fake.begin, fake.end = 0, 1000_000
    fake.devices = {"d": [(0, 100_000, "m/a"), (400_000, 500_000, "m/b")]}
    fake.modules = {"d": [(0, 150_000, "m"), (390_000, 600_000, "m")]}
    fake.spans = [(90_000, 300_000, "loss_read"), (300_000, 450_000, "dispatch")]
    assert fake.idle_gaps() == {"loss_read_before_m": pytest.approx(3e-4),
                                "_no_span__before__end_of_slice_": pytest.approx(5e-4)}
    run_ = types.SimpleNamespace(
        trace=recorded, obs={"values": {"batch": 4, "seq": 8192}},
        config=config("mistral-7b-train-d2"), family=MISTRAL, peak=work.peaks("TPU v5 lite"))
    share = trace_reduce.train_kernel_roofline(run_, "jit_apply_step/.*__mosaic_", "flash_work")
    # four whole steps; the fifth, cut short where the trace stops, is not counted as one:
    # 4 mosaic ops x 2 calls = 0.2323 s a step against 9.90e12 FLOPs / 197e12 = 0.0502 s
    assert share == pytest.approx(21.63, abs=0.05)


# ---------------------------------------------------------------------- run.py, dry runs
@pytest.mark.parametrize("workload", CELLS)
def test_dry_run_prints_a_well_formed_line_marked_not_a_chip_run(capsys, workload):
    line = dry(capsys, workload, "--trace", "0")
    assert line["dry_run"] is True and line["device"]["platform"] == "cpu"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert list(line)[-1] == "compared" and line["correct"] is True and line["failed"] == 0
    cell = next(w for w in BENCH["workloads"] if w["name"] == workload)
    want = {m["name"] for m in BENCH["end_to_end"] if run.applies(m, cell)}
    assert set(line["metrics"]) == want and "setup_s" in want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())


@pytest.mark.parametrize("workload,metrics", [
    (CHAT, {"ttft_ms_p90", "decode_occupancy_mean.latency"}),
    (LONG, {"kv_defer_count.throughput", "prefill_wall_share.throughput"}),   # slice opens later
])
def test_dry_run_traced_reports_per_layer_metrics_and_breakdown(capsys, workload, metrics):
    line = dry(capsys, workload, "--trace", "1", seconds="4")
    assert {"busy_s", "window_s"} <= set(line["device"]) and line["device"]["busy_s"] > 0
    assert 1.0 < line["device"]["window_s"] < 4.0 and metrics <= set(line["metrics"])
    assert "paged_attn_roofline" not in line["metrics"]      # a roofline is a chip's
    assert len(line["breakdown"]["device_ops"]) <= 10


def test_no_cpu_fallback_without_a_tpu(capsys):
    assert run.main(["--workload", TRAIN, "--seed", "1", "--seconds", "1"]) == 3
    assert capsys.readouterr().out == ""


# A family of another ``model_type`` whose configuration spells every size otherwise than
# Mistral's does: its own program config, its own counts, and a reference that renames the
# keys and follows the same equations (the program it drives is the same ``models/llama.py``).
TOY_FAMILY = '''
from benchmarks.chipbench import reference as ref

KEYS = {"width": "hidden_size", "ff_width": "intermediate_size", "heads": "num_attention_heads",
        "kv_heads": "num_key_value_heads", "head_width": "head_dim", "depth": "num_hidden_layers",
        "band": "sliding_window", "rope_base": "rope_theta", "norm_eps": "rms_norm_eps"}


def _renamed(c):
    return {**c, **{theirs: c[ours] for ours, theirs in KEYS.items()}}


def program_config(c, **over):
    from accelerate_tpu.models import llama

    return llama.LlamaConfig(
        vocab_size=c["vocab_size"], d_model=c["width"], n_layers=c["depth"], n_heads=c["heads"],
        n_kv_heads=c["kv_heads"], head_dim_override=c["head_width"], d_ff=c["ff_width"],
        rope_theta=c["rope_base"], norm_eps=c["norm_eps"], max_seq=c["positions"],
        sliding_window=c["band"], tie_embeddings=False, scan_layers=True, **over)


def loss(params, batch, cfg):
    from accelerate_tpu.models import llama

    return llama.loss_fn(params, batch, cfg)


def gen_params(c, seed, dtype):
    return ref.gen_params(_renamed(c), seed, dtype)


def leaf_norms(tree):
    return ref._flat(ref.leaf_norms(tree))


def change_norms(params, c, seed):
    return ref._flat(ref.change_norms(params, ref.seed_key(seed), ref.freeze(_renamed(c))))


def train_reference(c, *args, **kw):
    return ref.train_reference(_renamed(c), *args, **kw)


def serve_reference(c, *args, **kw):
    return ref.serve_reference(_renamed(c), *args, **kw)


compare_train, compare_serve = ref.compare_train, ref.compare_serve


def serve_flops_per_token(c):
    return 7.0 * c["width"] * c["depth"]
'''
TOY_SIZES = {"model_type": "toy", "width": 128, "ff_width": 256, "heads": 4, "kv_heads": 2,
             "head_width": 32, "depth": 2, "band": 48, "vocab_size": 512, "rope_base": 10000.0,
             "norm_eps": 1e-05, "positions": 32768}


def toy_tree(tmp_path):
    """A benchmark of its own under ``tmp_path``: the toy family, a training and a serving
    configuration on it, a traffic mix and a cell for each, and four per-layer metrics — every
    piece a file plus a BENCHMARK.json entry, no file of the harness among them."""
    bench = tmp_path / "bench"
    for kind in ("configs", "traffic", "metrics", "dry_run", "families"):
        (bench / kind).mkdir(parents=True)
    (bench / "families" / "toy.py").write_text(TOY_FAMILY)
    train_hp = config("mistral-7b-train-d2")["train"]
    serve_sv = traffic.load("dry_run", "mistral-7b-serve-d16")["serve"]
    for name, own in (
            ("toy-train", {"train": train_hp,
                           "limits": {"grad_norm_gap": 0.002, "change_norm_gap": 0.01}}),
            ("toy-serve", {"serve": serve_sv, "limits": {"served_logit_gap": 0.06}})):
        (bench / "configs" / f"{name}.json").write_text(json.dumps({**TOY_SIZES, **own}))
        (bench / "dry_run" / f"{name}.json").write_text("{}")
    shutil.copy(os.path.join(run.HERE, "traffic", "train_fixed_8k.json"),
                bench / "traffic" / "tmp_traffic.json")
    shutil.copy(os.path.join(run.HERE, "traffic", "chat_open_loop.json"),
                bench / "traffic" / "tmp_chat.json")
    (bench / "metrics" / "tmp_steps.json").write_text(
        json.dumps({"reader": "value", "args": {"key": "steps"}}))
    (bench / "metrics" / "tmp_own.py").write_text(
        "def read(run):\n    return float(run.obs['attempted'])\n")
    (bench / "metrics" / "tmp_rate.json").write_text(
        json.dumps({"reader": "value", "args": {"key": "tokens_processed_per_s"}}))
    # a dry run has no chip and so no peak: the reader states one, and ``mfu`` does the rest
    (bench / "metrics" / "tmp_mfu.py").write_text(
        "from benchmarks.chipbench import trace_reduce\n\n\n"
        "def read(run):\n"
        "    run.peak = {'bf16_flops': 1e9}\n"
        "    return trace_reduce.mfu(run, rate='tokens_processed_per_s',\n"
        "                            flops='serve_flops_per_token')\n")
    cells = {"tmp_cell": "train_tokens_per_s_per_chip", "tmp_serve": "tpot_ms_p90"}
    e2e = [{**m, "workloads": [cell]} for cell, moved in cells.items()
           for m in BENCH["end_to_end"] if m["name"] == moved]
    e2e.append(next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s"))
    layer = {"unit": "count", "better": "higher", "source": "program_counter", "layer": "any"}
    per_layer = [{"name": name, **layer, "moves": cells[cell], "workloads": [cell]}
                 for name, cell in (("tmp_steps", "tmp_cell"), ("tmp_own", "tmp_cell"),
                                    ("tmp_rate", "tmp_serve"), ("tmp_mfu", "tmp_serve"))]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "paths": ["bench"],
        "configs": [{"name": n, "file": f"bench/configs/{n}.json"}
                    for n in ("toy-train", "toy-serve")],
        "workloads": [{"name": "tmp_cell", "config": "toy-train", "traffic": "tmp_traffic",
                       "chips": 4},
                      {"name": "tmp_serve", "config": "toy-serve", "traffic": "tmp_chat",
                       "chips": 1}],
        "end_to_end": e2e, "per_layer": per_layer}))
    return bench


def toy_run(tmp_path, capsys, workload, seconds):
    rc = run.main(["--workload", workload, "--seed", "3", "--seconds", seconds, "--trace", "1",
                   "--cpu-dry-run", "--root", str(tmp_path)])
    return rc, last_line(capsys)


def test_a_throw_away_cell_is_files_plus_entries_and_runs_on_four_devices(tmp_path, capsys):
    """A family, a configuration, a traffic mix, a cell and a per-layer metric, each added as
    a file plus a BENCHMARK.json entry — no harness edit; here on 4 virtual devices."""
    toy_tree(tmp_path)
    rc, line = toy_run(tmp_path, capsys, "tmp_cell", "2")
    assert rc == 0 and line["device"]["count"] == 4 and line["correct"] is True
    assert line["metrics"]["tmp_steps"]["value"] == line["attempted"]
    assert line["metrics"]["tmp_own"]["value"] == line["attempted"]
    assert set(line["compared"]) == {"grad_norm_gap", "change_norm_gap"}


def test_a_throw_away_family_serves_and_its_own_count_is_what_mfu_reads(tmp_path, capsys):
    toy_tree(tmp_path)
    rc, line = toy_run(tmp_path, capsys, "tmp_serve", "4")
    assert rc == 0 and line["correct"] is True and line["readings"]["tokens_compared"] > 10
    rate = line["metrics"]["tmp_rate"]["value"]
    # the toy's count, 7 × width 128 × depth 2 a token, against the stated 1e9 FLOP/s — not
    # Mistral's 2 × matmul parameters, whose keys this configuration does not even have
    assert line["metrics"]["tmp_mfu"]["value"] == pytest.approx(100.0 * rate * 7 * 128 * 2 / 1e9)
    with pytest.raises(KeyError):
        work.serve_flops_per_token(TOY_SIZES)


def test_windows_and_readers_name_no_model():
    """Everything of the model comes through the family resolved from ``model_type``."""
    for module in (run, serve_window, train_window, trace_reduce):
        with open(module.__file__) as f:
            source = f.read()
        for banned in (r"llama", r"import reference", r"\breference\.\w", r"getattr\(work",
                       r"work\.(matmul|paged|flash|serve_flops|train_flops)"):
            assert not re.search(banned, source, re.I), (module.__name__, banned)


def test_a_model_type_without_a_family_names_the_file_it_looked_for(tmp_path):
    with pytest.raises(FileNotFoundError, match=r"families/deepseek_v3\.py"):
        run.load_family("deepseek_v3")
    with pytest.raises(FileNotFoundError, match=str(tmp_path)):
        run.load_family("mistral", str(tmp_path))


def test_a_family_that_only_serves_refuses_a_training_cell(tmp_path):
    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "half.py").write_text(
        "program_config = gen_params = serve_reference = compare_serve = print\n")
    half = run.load_family("half", str(tmp_path))
    assert run.load_window("serve_window", half) is serve_window
    with pytest.raises(NotImplementedError, match="half.py has no loss, leaf_norms.*train_window"):
        run.load_window("train_window", half)
    assert run.load_window("train_window", MISTRAL) is train_window


# ------------------------------------------------------------- the control and the faults
def limits_failed(line, tag):
    """The numbers of ``line`` that ``tag``'s readings would fail, by the limits the run used
    (a dry run's own: dry_run/<config>.json)."""
    return [k for k, c in line["compared"].items() if line["readings"][f"{tag}.{k}"] > c["limit"]]


def test_train_control_in_float8_and_half_batch_come_out_not_correct(capsys):
    line = dry(capsys, TRAIN, "--control", "1", seconds="1")
    assert line["correct"] is True
    assert limits_failed(line, "control_fp8") and limits_failed(line, "fault_half_batch")


def test_serve_control_in_float8_comes_out_not_correct(capsys):
    line = dry(capsys, CHAT, "--control", "1", seconds="4")
    assert line["correct"] is True and line["readings"]["tokens_compared"] > 10
    assert limits_failed(line, "control_fp8")


def test_fault_step_returns_its_state_unchanged(capsys, monkeypatch):
    import jax
    import jax.numpy as jnp

    build = train_window.Window.build

    def stuck_build(self):
        step, state = build(self)

        def stuck(st, batch):
            _, metrics = step(jax.tree_util.tree_map(jnp.copy, st), batch)
            return st, metrics
        return stuck, state

    monkeypatch.setattr(train_window.Window, "build", stuck_build)
    line = dry(capsys, TRAIN, seconds="1")
    assert line["correct"] is False
    assert line["compared"]["grad_norm_gap"]["value"] == pytest.approx(1.0)
    assert line["compared"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_fault_half_of_the_batch_left_out(capsys, monkeypatch):
    from accelerate_tpu.models import llama

    loss_fn = llama.loss_fn
    monkeypatch.setattr(llama, "loss_fn", lambda p, b, cfg: loss_fn(
        p, {"tokens": b["tokens"][: b["tokens"].shape[0] // 2]}, cfg))
    assert dry(capsys, TRAIN, seconds="1")["correct"] is False


@pytest.mark.parametrize("workload", [CHAT, LONG])
def test_fault_a_token_altered_where_it_is_produced(capsys, monkeypatch, workload):
    build = serve_window.Window.build

    def altering_build(self):
        engine = build(self)
        step, vocab = engine.step, self.c["vocab_size"]

        def altered():
            done = step()
            for req in [r for r in engine.slot_req if r is not None] + list(done):
                if len(req.tokens) > 1:
                    req.tokens[-1] = (req.tokens[-1] + 1) % vocab
            return done
        engine.step = altered
        return engine

    monkeypatch.setattr(serve_window.Window, "build", altering_build)
    assert dry(capsys, workload, seconds="4")["correct"] is False


def test_weights_from_the_seed_are_the_same_layer_by_layer():
    """The whole tree (what the program is given) equals the layers made one at a time
    (what the serving reference uses), and another seed gives other weights."""
    import jax
    import jax.numpy as jnp

    c = {**config("mistral-7b-serve-d16"), **traffic.load("dry_run", "mistral-7b-serve-d16")}
    tree = reference.gen_params(c, 2**31 + 5, jnp.bfloat16)
    key = reference.seed_key(2**31 + 5)
    for l in range(c["num_hidden_layers"]):
        one = reference.gen_layer(c, jax.random.fold_in(key, l), jnp.bfloat16)
        for name, w in one.items():
            assert jnp.array_equal(tree["layers"][name][l], w), (l, name)
    other = reference.gen_params(c, 5, jnp.bfloat16)
    assert not jnp.array_equal(tree["embed"], other["embed"])


# -------------------------------------------------------------------------------- schema
def test_benchmark_json_names_units_and_arrows():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert "setup_s" in e2e and 1 <= BENCH["run_seconds"] <= 51
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        target = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert run.applies(target, cells[cell]), (m["name"], cell)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and len(w["why"]) <= 200
        assert w["chips"] in (1, 4)
        reported = [m for m in BENCH["end_to_end"] if run.applies(m, w)]
        assert len(reported) >= 2
        assert any(run.applies(m, w) for m in BENCH["per_layer"])
    assert len({(w["config"], w["traffic"]) for w in BENCH["workloads"]}) == len(cells)


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_files_hold_published_widths_and_list_what_was_cut(entry):
    """The rules follow the configuration (schema.py); Mistral's published widths are
    pinned there by its source, which both accepted configurations name."""
    assert schema.entry_problems(BENCH, entry, ROOT) == []


def accepted_tree(tmp_path):
    """A copy of the accepted configurations, their family and BENCHMARK.json in ``tmp_path``."""
    for sub in ("configs", "families"):
        shutil.copytree(os.path.join(run.HERE, sub), tmp_path / BENCH["paths"][0] / sub)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(BENCH))
    return tmp_path / BENCH["configs"][0]["file"]


@pytest.mark.parametrize("key,value,problem", [
    (None, None, None),
    ("hidden_size", 2048, "published sizes changed"),
    ("num_hidden_layers", 32, "is no cut of the published 32"),
    ("reduced", ["num_hidden_layers", "vocab_size"], "in the entry"),
    ("published", {"num_hidden_layers": 32, "vocab_size": 64000}, "exactly the keys of reduced"),
    ("source", "https://example.org/config.json", "source differs"),
    ("limits", {"grad_norm_gap": None}, "limits is empty or holds a null"),
    ("model_type", "mixtral", "families/mixtral.py"),
])
def test_schema_rules_bite_on_a_copy_of_the_accepted_tree(tmp_path, key, value, problem):
    file = accepted_tree(tmp_path)
    if key is not None:
        file.write_text(json.dumps({**json.loads(file.read_text()), key: value}))
    problems = schema.config_problems(str(tmp_path))
    if problem is None:
        assert problems == []
    else:
        assert len(problems) >= 1 and any(problem in p for p in problems), problems
        assert all(p.startswith(BENCH["configs"][0]["name"] + ": ") for p in problems)


# The catalog's DeepSeek-V3 row (model-configs guide; source below), cut as ISSUE 27 sizes it
# for one of 16 chips that share each layer: 16 of 256 experts, an eighth of the vocabulary,
# one of the three leading dense layers and four MoE layers. Every width is as published.
DEEPSEEK_V3 = {
    "source": "https://huggingface.co/deepseek-ai/DeepSeek-V3/blob/main/config.json",
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 7168, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "model_type": "deepseek_v3",
    "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 16,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 5, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 16160,
    "reduced": ["n_routed_experts", "vocab_size", "num_hidden_layers", "first_k_dense_replace"],
    "published": {"n_routed_experts": 256, "vocab_size": 129280, "num_hidden_layers": 61,
                  "first_k_dense_replace": 3},
    "deployment": "one of 16 chips that share each layer by expert parallelism",
    "serve": {"dtype": "bfloat16"}, "limits": {"served_logit_gap": 0.25},
}


@pytest.mark.parametrize("change,problem", [
    ({}, None),
    ({"n_routed_experts": 4}, "fewer than 8 routed experts held"),
    ({"published": {"n_routed_experts": 256, "vocab_size": 129280, "num_hidden_layers": 61}},
     "exactly the keys of reduced"),
    ({"model_type": "deepseek_v4"}, "families/deepseek_v4.py"),
], ids=["as_cut", "four_experts", "reduced_key_not_published", "unknown_model_type"])
def test_a_deepseek_v3_shaped_configuration_passes_the_schema_rules(tmp_path, change, problem):
    """The next ``model_config`` PR's configuration is files and entries: the rules read its
    own keys (experts held, vocabulary share, layers after the leading dense one)."""
    for sub in ("configs", "families"):
        (tmp_path / "bench" / sub).mkdir(parents=True)
    (tmp_path / "bench" / "families" / "deepseek_v3.py").write_text("# a stub: the file is there\n")
    c = {**copy.deepcopy(DEEPSEEK_V3), **change}
    (tmp_path / "bench" / "configs" / "deepseek-v3-serve.json").write_text(json.dumps(c))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({"paths": ["bench"], "configs": [{
        "name": "deepseek-v3-serve", "source": DEEPSEEK_V3["source"],
        "file": "bench/configs/deepseek-v3-serve.json", "reduced": DEEPSEEK_V3["reduced"],
        "why": "MLA, sigmoid router over 256 experts of which 16 live here"}]}))
    problems = schema.config_problems(str(tmp_path))
    assert (problems == []) if problem is None else any(problem in p for p in problems), problems


def test_every_per_layer_metric_has_a_reader_file():
    for m in BENCH["per_layer"]:
        stems = (m["name"], m["name"].split(".")[0])
        assert any(os.path.exists(os.path.join(run.HERE, "metrics", s + ext))
                   for s in stems for ext in (".json", ".py")), m["name"]
