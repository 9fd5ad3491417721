"""CPU tests of the readers that read the program's phase ledger over the WHOLE window
(``benchmarks/chipbench/program_phases.py`` and the ``metrics/*_window.py``,
``decode_gap_ms_p99.py``, ``prefill_ms_per_chunk.py`` it serves): each gives a hand-computed
value on hand-made records, nothing under three samples, nothing on a program that keeps
no ledger, and clips at the window's edges; a traced dry run of a serving cell prints the
new entries from more samples than its slice holds. Nothing here is a measurement.
"""

import json
import os
import sys
import types

import pytest

from accelerate_tpu.telemetry.tracing import PhaseRecord
from benchmarks.chipbench import program_phases, program_spans, run, schema

SPARSE, CHAT = "serve_dots3_sparse16k", "serve_mistral7b_chat"
with open(f"{run.ROOT}/BENCHMARK.json") as f:
    BENCH = json.load(f)
with open(f"{run.HERE}/testdata/window_entries.json") as f:
    ENTRIES = json.load(f)["per_layer"]
NEW = ["engine_admit_share_window.latency", "engine_admit_share_window.throughput",
       "engine_host_ms_per_step_window.latency", "engine_host_ms_per_step_window.throughput",
       "decode_gap_ms_p99.latency", "decode_gap_ms_p99.throughput",
       "prefill_ms_per_chunk.throughput", "moe_pairs_per_token_window.throughput",
       "moe_max_over_mean_load_window.throughput", "dsa_selected_share_window.throughput"]
MS = 1_000_000


def records_by_hand():
    """Five ``step()``s on one thread in a window [0, 1 s] (ns on the ledger's clock). Step 0
    began before the window and step 4 ends after it. Steps 1, 2 and 3 admit a prompt of 4, 2
    and 6 chunks while 2 lanes run (20, 10 and 30 ms of prefill, 5 ms of it the first token's
    fetch); every step decodes for 40 ms, 25 of them waiting in the fetch.
    → records in the order they close, as the ledger hands them out."""
    out = []
    for i, t in enumerate((-20 * MS, 100 * MS, 300 * MS, 500 * MS, 970 * MS)):
        admit = {1: 20 * MS, 2: 10 * MS, 3: 30 * MS}.get(i, 0)
        lanes = 0 if i == 0 else 2
        rec = lambda name, t0, t1, self_ns, depth, **attrs: out.append(       # noqa: E731
            PhaseRecord(name, t0, t1, self_ns, depth, attrs, 7))
        a0 = t + 1 * MS                                      # the admit phase opens
        if admit:
            rec("engine.prefill.fetch", a0 + admit - 5 * MS, a0 + admit, 5 * MS, 3, uid=i)
            rec("engine.prefill", a0, a0 + admit, admit - 5 * MS, 2, uid=i, prompt_len=2000,
                width=2048, chunks={1: 4, 2: 2, 3: 6}[i], mode="chunk", queue_wait_ms=10.0 * i)
        a1 = a0 + admit + 1 * MS
        rec("engine.admit", a0, a1, 1 * MS, 1, lanes=lanes)
        d0 = a1 + 1 * MS
        rec("engine.decode.prepare", d0, d0 + 4 * MS, 4 * MS, 2)
        rec("engine.decode.dispatch", d0 + 4 * MS, d0 + 5 * MS, 1 * MS, 2, pages_live=10,
            pages_walked=16)
        rec("engine.decode.fetch", d0 + 5 * MS, d0 + 30 * MS, 25 * MS, 2)
        rec("engine.decode.drain", d0 + 30 * MS, d0 + 40 * MS, 10 * MS, 2, tokens=8,
            moe_pairs=60 + i, moe_tokens=32, moe_max_on_one_expert=20,
            dsa_keys_scored=1000 * (i + 1), dsa_keys_attended=250)
        rec("engine.decode", d0, d0 + 40 * MS, 0, 1, lanes=lanes + bool(admit), n_steps=4)
        rec("engine.step", t, d0 + 41 * MS, 3 * MS, 0, queued=5 - i, lanes=lanes)
    return out


def run_with(records, t0=0.0, t_close=1.0, **config):
    """A run whose ledger held ``records``: those that overlap the window, as
    ``PHASES.records(t0, t_close)`` hands them out."""
    inside = [r for r in records if r.t1_ns >= t0 * 1e9 and r.t0_ns <= t_close * 1e9]
    return types.SimpleNamespace(program_phases=program_phases.spans(inside),
                                 obs={"t0": t0, "t_close": t_close}, config=config, trace=None)


def test_records_become_nested_spans_with_the_ledgers_self_times():
    records = records_by_hand()
    spans = program_phases.spans(records)
    assert [s.name for s in spans if s.parent is None] == ["engine.step"] * 5
    assert sorted((s.name, s.t0, s.self_ns) for s in spans) == sorted(
        (r.name, r.t0_ns, r.self_ns) for r in records)
    r = run_with(records)
    assert program_phases.window(r) == (0, 1_000 * MS)
    assert [s.attrs["queued"] for s in program_phases.whole(r, "engine.step")] == [4, 3, 2]
    first, last = spans[0], [s for s in spans if s.name == "engine.step"][-1]
    assert program_phases.clipped(r, first) == first.t1 and first.t0 < 0         # cut at t0
    assert program_phases.clipped(r, last) == 30 * MS < last.dur                 # cut at t_close
    drain = [s for s in spans if s.name == "engine.decode.drain"][2]
    assert program_phases.root(drain).attrs == {"queued": 3, "lanes": 2}
    # two threads that overlap in time: each nests on its own
    other = [PhaseRecord("other", 90 * MS, 700 * MS, 610 * MS, 0, {}, 8)]
    mixed = program_phases.spans(records + other)
    assert [s.parent for s in mixed if s.name == "other"] == [None]
    assert all(program_phases.root(s).name == "engine.step" for s in mixed if s.name != "other")


def test_window_readers_by_hand(capsys):
    r = run_with(records_by_hand(), n_routed_experts=16)
    # admits that began with lanes running: steps 1–4 (step 0 began with none), a prefill
    # + 1 ms each: 21 + 11 + 31 + 1 = 64 ms of 1000
    assert run.read_metric("engine_admit_share_window.throughput", r) == pytest.approx(6.4)
    # whole steps 1, 2, 3: 44 ms + the prefill, less both fetches: 64 − 30 = 34, 54 − 30 = 24,
    # 74 − 30 = 44; the median
    assert run.read_metric("engine_host_ms_per_step_window.latency", r) == pytest.approx(34.0)
    # the whole drains (steps 0…3) end at 23, 163, 353, 573 ms: gaps 140, 190, 220 (every
    # later step began with lanes running); the 99th percentile by numpy's rule
    assert run.read_metric("decode_gap_ms_p99.throughput", r) == pytest.approx(219.4)
    # (20 + 10 + 30) ms over 4 + 2 + 6 chunks
    assert run.read_metric("prefill_ms_per_chunk.throughput", r) == pytest.approx(5.0)
    # drains 0…3 (the last one ends past the window): (60+61+62+63) / (4 × 32); 16 × 80 / 246
    assert run.read_metric("moe_pairs_per_token_window.throughput", r) == pytest.approx(246 / 128)
    assert run.read_metric("moe_max_over_mean_load_window.throughput", r) == pytest.approx(
        16 * 80 / 246)
    assert run.read_metric("dsa_selected_share_window.throughput", r) == pytest.approx(
        1000 / 10000)
    err = capsys.readouterr().err
    for line in ("engine_admit_share_window: 4 sample(s)", "engine_host_ms_per_step_window: 3 sample(s)",
                 "decode_gap_ms_p99: 3 sample(s)", "prefill_ms_per_chunk: 3 sample(s)",
                 "prefill_ms_per_chunk: 12 chunk(s)", "moe_pairs_per_token_window: 4 sample(s)"):
        assert line in err, line
    # what a run that stalls leaves behind: the longest steps, each with its phases
    assert "the 3 longest of 3 engine.step in the window" in err
    table = err[err.index("the 3 longest"):].splitlines()
    assert "74.000" in table[1] and "'lanes': 2" in table[1]                 # step 3 first
    assert any("engine.prefill " in ln and "'chunks': 6" in ln and "25.000" in ln for ln in table)


def test_an_admission_longer_than_the_window_is_clipped_not_left_out():
    """Three admissions of 400 ms, lanes running, the first begun before the window and the
    last ending after it: a window of 1 s holds 100 + 400 + 300 ms of them."""
    records = [PhaseRecord("engine.admit", t, t + 400 * MS, 400 * MS, 1, {"lanes": 3}, 7)
               for t in (-300 * MS, 300 * MS, 700 * MS)]
    r = run_with(records)
    assert run.read_metric("engine_admit_share_window.latency", r) == pytest.approx(80.0)
    assert [program_phases.clipped(r, s) // MS for s in r.program_phases] == [100, 400, 300]
    assert program_phases.whole(r, "engine.admit") == [r.program_phases[1]]
    r = run_with(records, t0=0.35, t_close=0.45)              # inside ONE admission: all of it
    assert run.read_metric("engine_admit_share_window.latency", r) is None      # one sample
    r.program_phases = r.program_phases * 3
    assert run.read_metric("engine_admit_share_window.latency", r) == pytest.approx(300.0)


def test_a_gap_counts_only_where_a_lane_was_running():
    """Chat: the engine idles between requests. A step that began with no lane running
    ends no client's wait, so its gap (here 600 ms of idling) is left out."""
    out = []
    for i, (t, lanes) in enumerate([(0, 1), (50, 1), (100, 1), (700, 0), (750, 1), (800, 1)]):
        out += [PhaseRecord("engine.decode.drain", (t + 40) * MS, (t + 45) * MS, 5 * MS, 2,
                            {"tokens": 4}, 7),
                PhaseRecord("engine.step", t * MS, (t + 46) * MS, 41 * MS, 0,
                            {"queued": 0, "lanes": lanes}, 7)]
    r = run_with(out)
    from benchmarks.chipbench.metrics import decode_gap_ms_p99 as reader

    drains = program_phases.whole(r, "engine.decode.drain")
    assert [g // MS for g in reader.gaps_ns(drains)] == [50, 50, 50, 50]
    assert run.read_metric("decode_gap_ms_p99.latency", r) == pytest.approx(50.0)


@pytest.mark.parametrize("name", NEW)
def test_readers_return_none_under_three_samples_and_without_a_ledger(name, monkeypatch):
    two = [r for r in records_by_hand() if 100 * MS <= r.t0_ns < 400 * MS]   # steps 1 and 2
    assert run.read_metric(name, run_with(two, n_routed_experts=16)) is None
    # a program whose phases carry none of the attributes (its prefill no chunks, its drain
    # no counters), at any number of samples
    bare = [PhaseRecord(r.name, r.t0_ns + k * 2_000 * MS, r.t1_ns + k * 2_000 * MS, r.self_ns,
                        r.depth, {}, r.thread) for k in range(4) for r in records_by_hand()]
    if name.split("_")[0] in ("prefill", "moe", "dsa"):
        assert run.read_metric(name, run_with(bare, t_close=10.0, n_routed_experts=16)) is None
    # the parent commit: an accelerate_tpu whose tracing module keeps no ledger
    r = types.SimpleNamespace(obs={"t0": 0.0, "t_close": 1.0}, config={"n_routed_experts": 16},
                              trace=None)
    monkeypatch.setitem(sys.modules, "accelerate_tpu.telemetry.tracing",
                        types.ModuleType("accelerate_tpu.telemetry.tracing"))
    assert run.read_metric(name, r) is None and r.program_phases == []


def test_the_ten_entries_are_data_beside_the_benchmark_which_stays_as_it_was():
    """``BENCHMARK.json`` may only gain entries at its end, and ``test_keye_vl2.py`` pins the
    keye cell's own eight as its last: the ten wait in ``testdata/window_entries.json`` for a
    ``benchmark`` PR (PERF.md §7), spelt as they are to be appended."""
    assert [m["name"] for m in ENTRIES] == NEW
    assert not {m["name"] for m in BENCH["per_layer"]} & set(NEW)
    cells = [w["name"] for w in BENCH["workloads"]]
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    layers = {m["layer"] for m in BENCH["per_layer"]}
    for m in ENTRIES:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert schema.NAME.match(m["name"]) and m["better"] == "lower" and m["layer"] in layers
        assert m["workloads"] == [c for c in cells if c in m["workloads"]]      # the file's order
        assert all(run.applies(e2e[m["moves"]], {"name": c}) for c in m["workloads"])
        assert m["source"] == ("program_counter" if m["name"].split("_")[0] in ("moe", "dsa")
                               else "host_clock")
        reader = m["name"].split(".")[0]
        assert (reader.endswith("_window") or reader in ("decode_gap_ms_p99", "prefill_ms_per_chunk"))
        assert os.path.exists(f"{run.HERE}/metrics/{reader}.py")
    by = {m["name"]: m["workloads"] for m in ENTRIES}
    assert by["moe_pairs_per_token_window.throughput"] == cells[3:]           # several cells, one entry
    assert by["dsa_selected_share_window.throughput"] == cells[4:]
    assert by["decode_gap_ms_p99.latency"] == [CHAT] and by["prefill_ms_per_chunk.throughput"] == cells[2:]


def test_traced_dry_run_prints_the_new_entries_from_the_whole_window(capsys, monkeypatch, tmp_path):
    """One serving cell end to end on the CPU, under a ``BENCHMARK.json`` with the ten entries
    appended: its seven are in the line, each read from the ledger over the whole window, and
    the five longest steps are on stderr."""
    root = tmp_path / "root"
    root.mkdir()
    (root / "BENCHMARK.json").write_text(
        json.dumps({**BENCH, "per_layer": BENCH["per_layer"] + ENTRIES}))
    os.symlink(f"{run.ROOT}/benchmarks", root / "benchmarks")
    monkeypatch.setattr(run, "ROOT", str(tmp_path))          # the trace: a directory of its own
    monkeypatch.setattr(program_spans, "ROOT", str(tmp_path))
    rc = run.main(["--workload", SPARSE, "--seed", "3100000017", "--seconds", "4",
                   "--trace", "1", "--cpu-dry-run", "--root", str(root)])
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1])
    want = {n for n in NEW if n.endswith(".throughput")}
    assert rc == 0 and line["correct"] and want <= set(line["metrics"])
    value = lambda n: line["metrics"][n]["value"]                          # noqa: E731
    assert 0 < value("engine_admit_share_window.throughput") <= 100
    assert value("decode_gap_ms_p99.throughput") > value("engine_host_ms_per_step_window.throughput") > 0
    assert value("prefill_ms_per_chunk.throughput") > 0
    # the window's counts and the slice's are the same program's: alike, not equal
    for whole_window, slice_bound in (("moe_pairs_per_token_window.throughput", "moe_pairs_per_token.sparse16k"),
                                      ("dsa_selected_share_window.throughput", "dsa_selected_share")):
        assert value(whole_window) == pytest.approx(value(slice_bound), rel=0.5)
    err = captured.err
    assert "longest of" in err and "engine.prefill" in err and "'chunks':" in err
    samples = {ln.split(":")[0]: int(ln.split()[1]) for ln in err.splitlines()
               if ln.endswith("sample(s)")}
    assert samples["engine_host_ms_per_step_window"] >= samples["engine_host_ms_per_step"] >= 3
    assert samples["moe_pairs_per_token_window"] >= samples["moe_pairs_per_token"]
