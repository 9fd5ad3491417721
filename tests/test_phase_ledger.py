"""CPU tests of the phase ledger (``accelerate_tpu/telemetry/tracing.py::PHASES``): what
``phase()`` keeps of the program's own work with no profiler anywhere — nesting and self
time, attributes and their sums, the ring's bound, a stack a thread, two reads of its clock
a phase — that a profiler session sees the same phases with the same edges, and what the
serving engine and the train step put there. Nothing here is a measurement.
"""

import dataclasses
import itertools
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.telemetry import tracing
from accelerate_tpu.telemetry.tracing import (PHASES, EnginePhase, PhaseLedger, PhaseRecord,
                                              phase, step_phase)
from benchmarks.chipbench import program_phases, program_spans

DECODE_PHASES = {"engine.decode.prepare", "engine.decode.dispatch", "engine.decode.fetch",
                 "engine.decode.drain"}
# docs/telemetry.md's table: every engine span, and the span that encloses it
PARENT = {"engine.step": None, "engine.admit": "engine.step", "engine.decode": "engine.step",
          "engine.prefill": "engine.admit", "engine.defer": "engine.admit",
          "engine.prefill.fetch": "engine.prefill",
          **{name: "engine.decode" for name in DECODE_PHASES}}


@pytest.fixture
def ledger(monkeypatch):
    """A ledger of this test's own in the process's place."""
    fresh = PhaseLedger()
    monkeypatch.setattr(tracing, "PHASES", fresh)
    return fresh


# ------------------------------------------------------------------------- the ledger alone
def test_children_sum_to_the_parent_and_an_exception_closes_the_record(ledger):
    with pytest.raises(KeyError):
        with phase("outer", a=1):
            with phase("inner.first"):
                with phase("inner.first.leaf"):
                    pass
            with phase("inner.second"):
                raise KeyError("inside a phase")
    recs = {r.name: r for r in ledger.records()}
    assert [r.name for r in ledger.records()] == [
        "inner.first.leaf", "inner.first", "inner.second", "outer"]        # as they closed
    assert all(isinstance(r, PhaseRecord) for r in recs.values())
    assert {n: r.depth for n, r in recs.items()} == {
        "outer": 0, "inner.first": 1, "inner.second": 1, "inner.first.leaf": 2}
    dur = lambda r: r.t1_ns - r.t0_ns                                      # noqa: E731
    outer, first, second, leaf = (recs[n] for n in (
        "outer", "inner.first", "inner.second", "inner.first.leaf"))
    assert outer.self_ns == dur(outer) - dur(first) - dur(second) >= 0
    assert first.self_ns == dur(first) - dur(leaf) and leaf.self_ns == dur(leaf)
    assert sum(r.self_ns for r in recs.values()) == dur(outer)
    assert outer.t0_ns <= first.t0_ns <= first.t1_ns <= second.t0_ns <= second.t1_ns <= outer.t1_ns
    assert ledger._open.stack == []                 # the exception left nothing open
    with phase("after"):                            # and the next phase is a root again
        pass
    assert ledger.records()[-1].depth == 0
    # the helper nests them as the stack did, and its self time is the ledger's
    spans = program_phases.spans(ledger.records())
    assert {s.name: (s.parent.name if s.parent else None) for s in spans} == {
        "outer": None, "inner.first": "outer", "inner.second": "outer",
        "inner.first.leaf": "inner.first", "after": None}
    assert {s.name: s.self_ns for s in spans} == {
        r.name: r.self_ns for r in ledger.records()}


def test_set_metadata_reaches_the_record_and_the_sums(ledger):
    for i, tokens in enumerate((3, 4, 5)):
        with phase("drain", lane=i) as ph:
            ph.set_metadata(tokens=tokens, share=0.5, mode="chunk")
    assert [r.attrs for r in ledger.records()] == [
        {"lane": i, "tokens": t, "share": 0.5, "mode": "chunk"} for i, t in enumerate((3, 4, 5))]
    tot = ledger.totals()["drain"]
    assert tot["count"] == 3 and tot["sums"] == {"lane": 3, "tokens": 12, "share": 1.5}
    durs = [r.t1_ns - r.t0_ns for r in ledger.records()]
    assert tot["total_ns"] == tot["self_ns"] == sum(durs) and tot["max_ns"] == max(durs)
    longest = ledger.records()[durs.index(max(durs))]
    assert tot["max_t0_ns"] == longest.t0_ns and tot["max_attrs"] == longest.attrs
    # a copy as of the call: the next phase moves the ledger's, not the one handed out
    with phase("drain", tokens=100):
        pass
    assert tot["count"] == 3 and tot["sums"]["tokens"] == 12
    assert ledger.totals()["drain"]["sums"]["tokens"] == 112
    assert set(ledger.totals("dr")) == {"drain"} and ledger.totals("engine.") == {}


def test_records_are_cut_by_overlap_and_kept_whole(ledger, monkeypatch):
    ticks = itertools.count(0, 10)
    monkeypatch.setattr(tracing, "PHASE_CLOCK_NS", lambda: next(ticks))
    for name in ("a", "b", "c"):            # [0, 10], [20, 30], [40, 50]
        with phase(name):
            pass
    names = lambda **kw: [r.name for r in ledger.records(**kw)]            # noqa: E731
    assert names() == ["a", "b", "c"]
    assert names(since_ns=25) == ["b", "c"] and names(until_ns=25) == ["a", "b"]
    assert names(since_ns=11, until_ns=19) == [] and names(since_ns=10, until_ns=20) == ["a", "b"]
    (b,) = ledger.records(since_ns=25, until_ns=26)
    assert (b.t0_ns, b.t1_ns) == (20, 30)                                  # whole: the caller clips


def test_the_ring_is_bounded_and_counts_what_fell_out(monkeypatch):
    class Small(PhaseLedger):
        RING = 8

    small = Small()
    monkeypatch.setattr(tracing, "PHASES", small)
    for i in range(20):
        with phase("tick", i=i):
            pass
    assert len(small.records()) == 8 and small.dropped == 12
    assert [r.attrs["i"] for r in small.records()] == list(range(12, 20))       # the newest
    assert small.totals()["tick"]["count"] == 20 and small.totals()["tick"]["sums"]["i"] == 190
    assert PhaseLedger.RING == 65536 and PHASES._ring.maxlen == 65536


def test_every_thread_has_a_stack_of_its_own(ledger):
    """Two threads hold a phase open at the same time: neither becomes the other's parent,
    and totals add up across them (more threads than this sandbox has cores to spare)."""
    n_threads, n_each = 8, 200
    inside = threading.Barrier(n_threads)

    def work(k):
        with phase("thread.outer", k=k):
            inside.wait(timeout=60)                  # every thread is inside its outer phase
            for _ in range(n_each):
                with phase("thread.inner", one=1):
                    pass

    threads = [threading.Thread(target=work, args=(k,)) for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    recs = ledger.records()
    outers = [r for r in recs if r.name == "thread.outer"]
    assert len(outers) == n_threads and all(r.depth == 0 for r in outers)
    assert len({r.thread for r in outers}) == n_threads
    assert all(r.depth == 1 for r in recs if r.name == "thread.inner")
    tot = ledger.totals()
    assert tot["thread.inner"]["count"] == tot["thread.inner"]["sums"]["one"] == n_threads * n_each
    for o in outers:                                 # an outer's self time counts ITS inners alone
        mine = [r for r in recs if r.name == "thread.inner" and r.thread == o.thread]
        assert len(mine) == n_each
        assert o.self_ns == (o.t1_ns - o.t0_ns) - sum(r.t1_ns - r.t0_ns for r in mine)
    spans = program_phases.spans(recs)
    assert all(s.parent is None for s in spans if s.name == "thread.outer")
    assert all(s.parent.attrs["k"] is not None and len(s.parent.children) == n_each
               for s in spans if s.name == "thread.inner")


def test_a_phase_reads_the_ledgers_clock_exactly_twice(ledger, monkeypatch):
    reads = []

    def clock():
        reads.append(1)
        return 1000 * len(reads)

    monkeypatch.setattr(tracing, "PHASE_CLOCK_NS", clock)
    with phase("p", a=1) as ph:
        ph.set_metadata(b=2)
    assert len(reads) == 2
    with EnginePhase(None, "q"):
        with step_phase("r", 7):
            pass
    assert len(reads) == 6
    assert [(r.name, r.t0_ns, r.t1_ns, r.self_ns) for r in ledger.records()] == [
        ("p", 1000, 2000, 1000), ("r", 4000, 5000, 1000), ("q", 3000, 6000, 2000)]
    assert ledger.records()[1].attrs == {"step_num": 7}
    ledger.records(), ledger.totals()                       # reading reads no clock
    assert len(reads) == 6


def test_the_ledgers_clock_is_the_one_the_windows_read():
    import time

    from accelerate_tpu.telemetry import clocks

    assert clocks.PHASE_CLOCK_NS is time.perf_counter_ns is tracing.PHASE_CLOCK_NS
    a = time.perf_counter()
    with phase("test.clock"):
        pass
    b = time.perf_counter()
    rec = PHASES.records(since_ns=int(a * 1e9))[-1]
    assert rec.name == "test.clock" and a * 1e9 - 1e3 <= rec.t0_ns <= rec.t1_ns <= b * 1e9 + 1e3


# ------------------------------------------------------------------ the engine, on the CPU
@pytest.fixture(scope="module")
def toy():
    from accelerate_tpu.models import llama

    cfg = dataclasses.replace(llama.CONFIGS["tiny"], dtype=jnp.float32)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32) for n in (5, 40, 3, 7)]
    return cfg, llama.init_params(cfg), prompts


def drive(toy, session_dir=None, **engine):
    """Four requests (one of three chunks) onto a paged engine whose pool holds two of them,
    4-step decode → (the ledger's records of the drive, the engine)."""
    from accelerate_tpu.serving import ContinuousBatcher

    cfg, params, prompts = toy
    eng = ContinuousBatcher(params, cfg, max_slots=3, max_len=64, prompt_bucket=16,
                            page_size=8, kv_pages=10, decode_steps=4, **engine)
    since = tracing.PHASE_CLOCK_NS()
    if session_dir is not None:
        jax.profiler.start_trace(session_dir)
    try:
        for p in prompts:
            eng.submit(p, max_new_tokens=10)
        eng.run()
    finally:
        if session_dir is not None:
            jax.profiler.stop_trace()
    return PHASES.records(since_ns=since), eng


@pytest.fixture(scope="module")
def driven(toy):
    drive(toy)                                                             # compile first
    return drive(toy)


def test_the_window_holds_every_span_of_the_table_inside_its_parent(driven):
    """With no profiler anywhere in the process's drive."""
    records, eng = driven
    spans = program_phases.spans(records)
    assert {s.name for s in spans} == set(PARENT)
    for s in spans:
        assert (s.parent.name if s.parent else None) == PARENT[s.name], s.name
        if s.parent is not None:
            assert s.parent.t0 <= s.t0 and s.t1 <= s.parent.t1
    for step in (s for s in spans if s.name == "engine.step"):
        assert sum(s.self_ns for s in (step, *step.descendants())) == step.dur
    by = lambda name: [s for s in spans if s.name == name]                 # noqa: E731
    assert all({"queued", "lanes"} <= set(s.attrs) for s in by("engine.step"))
    assert all(s.attrs["lanes"] > 0 and s.attrs["n_steps"] == 4 for s in by("engine.decode"))
    assert sum(s.attrs["tokens"] for s in by("engine.decode.drain")) == 4 * (10 - 1)
    assert all({"pages_live", "pages_walked"} <= set(s.attrs) for s in by("engine.decode.dispatch"))
    prefills = by("engine.prefill")
    assert sorted(s.attrs["uid"] for s in prefills) == [0, 1, 2, 3]
    # 5, 40, 3 and 7 tokens in chunks of 16: the second prompt takes three programs
    assert {s.attrs["uid"]: (s.attrs["chunks"], s.attrs["mode"], s.attrs["width"])
            for s in prefills} == {0: (1, "chunk", 16), 1: (3, "chunk", 48),
                                   2: (1, "chunk", 16), 3: (1, "chunk", 16)}
    assert all(s.attrs["queue_wait_ms"] >= 0 for s in prefills)
    assert eng.stats()["prefill_chunks"] == 6 == sum(s.attrs["chunks"] for s in prefills)
    assert by("engine.defer") and all(s.dur < 1e6 for s in by("engine.defer"))


def test_stats_carry_the_engines_totals_and_counters_with_no_profiler(toy):
    before = PHASES.totals("engine.")
    records, eng = drive(toy)
    phases = eng.stats()["phases"]
    assert set(phases) == set(PARENT) and phases == PHASES.totals("engine.")
    moved = lambda name, key: phases[name][key] - before.get(name, {}).get(key, 0)    # noqa: E731
    n = lambda name: sum(r.name == name for r in records)                  # noqa: E731
    for name in PARENT:
        assert moved(name, "count") == n(name) > 0
        assert moved(name, "total_ns") == sum(r.t1_ns - r.t0_ns for r in records if r.name == name)
        assert moved(name, "self_ns") == sum(r.self_ns for r in records if r.name == name)
    sums = lambda name, key: (phases[name]["sums"][key]                    # noqa: E731
                              - before.get(name, {}).get("sums", {}).get(key, 0))
    assert sums("engine.decode.drain", "tokens") == 4 * (10 - 1) == eng.decode_tokens
    assert sums("engine.prefill", "chunks") == 6 and sums("engine.prefill", "prompt_len") == 55
    assert sums("engine.decode.dispatch", "pages_live") == sum(
        r.attrs["pages_live"] for r in records if r.name == "engine.decode.dispatch") > 0
    longest = max((r for r in records if r.name == "engine.step"), key=lambda r: r.t1_ns - r.t0_ns)
    assert phases["engine.step"]["max_ns"] >= longest.t1_ns - longest.t0_ns
    assert PHASES.dropped == 0 or len(PHASES.records()) == PhaseLedger.RING


def test_the_paged_walk_is_counted_inside_decode_prepare(toy, monkeypatch):
    from accelerate_tpu.serving import ContinuousBatcher

    walk = ContinuousBatcher._paged_walk

    def marked(self, active):
        with phase("test.walk"):
            return walk(self, active)

    monkeypatch.setattr(ContinuousBatcher, "_paged_walk", marked)
    records, _ = drive(toy)
    walks = [s for s in program_phases.spans(records) if s.name == "test.walk"]
    assert walks and all(s.parent.name == "engine.decode.prepare" for s in walks)
    assert len(walks) == sum(r.name == "engine.decode.dispatch" for r in records)


def test_the_serving_record_does_not_carry_the_ledger(toy):
    from accelerate_tpu.telemetry import Telemetry
    from accelerate_tpu.telemetry.schemas import SERVING_SCHEMA
    from accelerate_tpu.utils.dataclasses import TelemetryConfig

    tel = Telemetry(TelemetryConfig(enabled=True, compile_events=False, memory_stats=False))
    _, eng = drive(toy, telemetry=tel)
    serving = [r for r in tel.records if r["schema"] == SERVING_SCHEMA]
    assert serving and all("phases" not in r for r in serving)
    assert serving[-1]["prefill_chunks"] == 6 and "phases" in eng.stats()


def test_a_session_sees_the_same_phases_with_the_same_edges(toy, tmp_path):
    """Every ledger record of a profiled drive has its ``atpu.`` span in the session's
    trace, one for one and in the same order, as long to 50 us and ONE offset apart (nine
    in ten; all to 5 ms): the two sinks are fed by the same two statements."""
    drive(toy)
    records, _ = drive(toy, str(tmp_path))
    ledger = program_phases.spans(records)
    traced = program_spans.load(str(tmp_path))
    assert [s.name for s in traced] == [s.name for s in ledger] and len(ledger) > 40
    assert [s.attrs for s in traced] == [s.attrs for s in ledger]
    assert [(s.parent.name if s.parent else None) for s in traced] == [
        (s.parent.name if s.parent else None) for s in ledger]
    offsets = sorted(t.t0 - s.t0 for t, s in zip(traced, ledger))
    offset = offsets[len(offsets) // 2]
    # the two statements are a few hundred ns apart; with a session live the annotation's
    # own enter and exit now and then take tens of us on a loaded CPU, so a tenth may stray
    far = [s.name for t, s in zip(traced, ledger)
           if abs(t.dur - s.dur) > 50_000 or abs(t.t0 - s.t0 - offset) > 50_000]
    assert len(far) <= len(ledger) // 10, far
    assert all(abs(t.t0 - s.t0 - offset) < 5_000_000 and abs(t.dur - s.dur) < 5_000_000
               for t, s in zip(traced, ledger))


def test_train_step_phase_carries_the_step_number():
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import llama
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    for singleton in (AcceleratorState, GradientState, PartialState):
        singleton._reset_state()
    cfg = dataclasses.replace(llama.CONFIGS["tiny"], dtype=jnp.float32)
    acc = Accelerator()
    state = acc.create_train_state(llama.init_params(cfg), optax.sgd(1e-2))
    step = acc.build_train_step(lambda p, b: llama.loss_fn(p, b, cfg))
    batch = {"tokens": np.ones((acc.mesh.size, 9), np.int32)}
    since = tracing.PHASE_CLOCK_NS()
    for _ in range(3):
        state, m = step(state, batch)
    jax.block_until_ready(m)
    for singleton in (AcceleratorState, GradientState, PartialState):
        singleton._reset_state()
    steps = [r for r in PHASES.records(since_ns=since) if r.name == "train.step"]
    assert [r.attrs["step_num"] for r in steps] == [steps[0].attrs["step_num"] + i for i in range(3)]
    assert all(r.depth == 0 and r.t1_ns > r.t0_ns for r in steps)
