"""The program's own phases over the WHOLE measured window, from its in-process ledger.

``accelerate_tpu/telemetry/tracing.py::phase`` feeds every phase into ``PHASES``, a bounded
ledger the program keeps with or without a profiler session, on ``time.perf_counter_ns`` —
the clock the windows read — so ``obs["t0"]`` and ``obs["t_close"]`` cut it with no
conversion. ``program_spans`` reads the same phases from the traced SLICE (5–6 s of 51),
which holds too few whole steps of a cell whose admissions take seconds; a reader built on
this module sees all of them. The records come back as ``program_spans.Span`` objects, nested
thread by thread, so a reader uses the same ``children`` / ``self_ns`` / ``descendants`` on
either source. A program without a ledger — the parent of the PR that added it — gives an
empty list, and every reader built on this returns ``None``.

The readers' ten ``per_layer`` entries are ``testdata/window_entries.json`` until a
``benchmark`` PR appends them to ``BENCHMARK.json`` (PERF.md 36.5); ``run.py --root <dir>``
with such a file in ``<dir>`` reports them today.
"""

from __future__ import annotations

import statistics
import sys

from . import program_spans
from .program_spans import Span, enough, nest  # noqa: F401  (readers say enough() through here)


def spans(records) -> list:
    """Ledger records → nested ``Span`` s in start order (names as the ledger has them: no
    prefix; ``t0`` / ``t1`` in ns on the ledger's clock). Each thread nests on its own."""
    by_thread: dict = {}
    for r in records:
        by_thread.setdefault(r.thread, []).append(Span(r.name, r.t0_ns, r.t1_ns, r.attrs))
    out = [s for thread in by_thread.values() for s in nest(thread)]
    return sorted(out, key=lambda s: (s.t0, -s.t1))


def window(run) -> tuple:
    """The measured window ``(t0, t_close)`` in ns on the ledger's clock."""
    return int(run.obs["t0"] * 1e9), int(run.obs["t_close"] * 1e9)


def in_window(run) -> list:
    """Every phase that overlaps the window, nested (read once a run). A phase that
    straddles an edge is there whole: ``clipped`` gives the part inside, ``whole`` tells."""
    if not hasattr(run, "program_phases"):
        try:
            from accelerate_tpu.telemetry.tracing import PHASES
        except ImportError:                       # a program that keeps no ledger
            run.program_phases = []
        else:
            run.program_phases = spans(PHASES.records(*window(run)))
            print(f"program phases: {len(run.program_phases)} record(s) overlap the window; "
                  f"the ring dropped {PHASES.dropped} since the process began", file=sys.stderr)
    return run.program_phases


def whole(run, name: str) -> list:
    """The ``name`` phases that lie wholly inside the window, in start order."""
    t0, t1 = window(run)
    return [s for s in in_window(run) if s.name == name and s.t0 >= t0 and s.t1 <= t1]


def clipped(run, span) -> int:
    """The ns of ``span`` that lie inside the window."""
    t0, t1 = window(run)
    return max(0, min(span.t1, t1) - max(span.t0, t0))


def root(span):
    """The outermost phase around ``span`` (itself, if none)."""
    while span.parent is not None:
        span = span.parent
    return span


def longest_table(steps: list, n: int = 5) -> str:
    """The ``n`` longest of ``steps`` with every phase inside them: self time and
    attributes. What a run that stalls leaves behind (a stall shows as one long step and
    the phase the time sat in)."""
    lines = [f"the {min(n, len(steps))} longest of {len(steps)} engine.step in the window "
             "(ms; indented: self ms of each phase inside):"]
    first = min((s.t0 for s in steps), default=0)
    for step in sorted(steps, key=lambda s: -s.dur)[:n]:
        lines.append(f"  +{(step.t0 - first) / 1e9:8.3f} s  {step.dur / 1e6:10.3f}  {step.attrs}")
        for s in (step, *step.descendants()):
            lines.append(f"      {s.name:<26}{s.self_ns / 1e6:10.3f}  {s.attrs if s is not step else ''}")
    return "\n".join(lines)


def slice_check(run) -> str:
    """The two sinks of ``phase()`` against each other where both recorded: every ``atpu.``
    span of the traced slice paired with the ledger's phase of the same name that starts
    nearest (the clocks are brought together at the slice's first mark, to some us) — how
    many pair, how far the pairs' offsets and durations stray, and the ledger's phases well
    inside the slice that found no span. One line, for stderr."""
    traced, host = program_spans.in_slice(run), getattr(run, "slice_host", None)
    if not traced or not host or host[0] is None or not in_window(run):
        return "ledger vs slice: nothing to compare"
    rough = run.trace.begin - int(host[0] * 1e9)
    by_name: dict = {}
    for s in in_window(run):
        by_name.setdefault(s.name, []).append(s)
    pairs = []
    for t in traced:
        near = min(by_name.get(t.name, ()), key=lambda s: abs(s.t0 + rough - t.t0), default=None)
        if near is not None and abs(near.t0 + rough - t.t0) < 1_000_000:
            pairs.append((t, near))
    if not pairs:
        return f"ledger vs slice: none of {len(traced)} span(s) has a ledger phase within 1 ms"
    offset = int(statistics.median(t.t0 - s.t0 for t, s in pairs))
    paired = {id(s) for _, s in pairs}
    margin, (b, e) = 100_000, (run.trace.begin, run.trace.end)
    alone = [s for s in in_window(run) if id(s) not in paired
             and b + margin <= s.t0 + offset and s.t1 + offset <= e - margin]
    return (f"ledger vs slice: {len(pairs)} of {len(traced)} atpu span(s) pair with a ledger "
            f"phase ({len(paired)} distinct); start offsets within "
            f"{max(abs(t.t0 - s.t0 - offset) for t, s in pairs) / 1e3:.1f} us of one, durations "
            f"within {max(abs(t.dur - s.dur) for t, s in pairs) / 1e3:.1f} us (median "
            f"{statistics.median(abs(t.dur - s.dur) for t, s in pairs) / 1e3:.2f}); "
            f"{len(alone)} ledger phase(s) inside the slice without a span")
