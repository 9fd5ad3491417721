"""The program's own spans in the traced slice: ``atpu.*`` host spans with their
attributes, parents and self times, and device time of a NAMED kernel per whole execution
of the program that runs it.

``accelerate_tpu/telemetry/tracing.py::phase`` writes the spans with
``jax.profiler.TraceAnnotation``, so they lie in the slice's ``.xplane.pb`` on the
profiler's clock beside the device's operations and the benchmark's ``cb.`` spans; an
attribute is one of the event's stats. ``trace_reduce.Trace`` keeps only ``cb.`` spans, so
this module reads the file again (host planes only). A program without such spans — the
parent of the PR that added them — gives an empty list, and every reader built on this
returns ``None``.
"""

from __future__ import annotations

import glob
import os
import re
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))            # run.ROOT: where .cb_trace lies
PREFIX = "atpu."
MIN_SAMPLES = 3


class Span:
    """One ``atpu.<name>`` span: ns on the profiler's clock, the event's stats as
    ``attrs``, the span that encloses it on its thread, and the spans it encloses."""

    __slots__ = ("name", "t0", "t1", "attrs", "parent", "children")

    def __init__(self, name: str, t0: int, t1: int, attrs: dict):
        self.name, self.t0, self.t1, self.attrs = name, t0, t1, attrs
        self.parent, self.children = None, []

    @property
    def dur(self) -> int:
        return self.t1 - self.t0

    @property
    def self_ns(self) -> int:
        """Duration less the part the children cover (one thread: they do not overlap)."""
        return self.dur - sum(c.dur for c in self.children)

    def descendants(self):
        for c in self.children:
            yield c
            yield from c.descendants()


def nest(spans: list) -> list:
    """Set ``parent``/``children`` by containment in time and return the spans in start
    order. The spans of one thread nest like its ``with`` blocks."""
    spans = sorted(spans, key=lambda s: (s.t0, -s.t1))
    open_: list = []
    for s in spans:
        while open_ and s.t0 >= open_[-1].t1:
            open_.pop()
        if open_ and s.t1 <= open_[-1].t1:
            s.parent = open_[-1]
            open_[-1].children.append(s)
        open_.append(s)
    return spans


def load(path: str) -> list:
    """Every ``atpu.`` span of an ``.xplane.pb`` (or of the newest one under a directory),
    nested thread by thread, in start order; names come without the prefix."""
    import jax

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
        if not found:
            return []
        path = found[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            thread = [Span(ev.name[len(PREFIX):], int(ev.start_ns),
                           int(ev.start_ns + ev.duration_ns), dict(ev.stats))
                      for ev in line.events if ev.name.startswith(PREFIX)]
            out += nest(thread)
    return sorted(out, key=lambda s: (s.t0, -s.t1))


def in_slice(run) -> list:
    """The spans that lie wholly inside the traced slice of ``run`` (read once a run)."""
    if getattr(run, "trace", None) is None:
        return []
    if not hasattr(run, "program_spans"):
        t = run.trace
        run.program_spans = [s for s in load(os.path.join(ROOT, ".cb_trace"))
                             if s.t0 >= t.begin and s.t1 <= t.end]
    return run.program_spans


def enough(metric: str, n: int) -> bool:
    """A reader says how many samples it found, and reads nothing from under three."""
    print(f"{metric}: {n} sample(s)", file=sys.stderr)
    return n >= MIN_SAMPLES


def self_time_table(spans: list, per: str = "engine.step") -> str:
    """Self time by span name, as ms per whole ``per`` span: what the host did with a
    ``step()``, phase by phase. The lines sum to the mean duration of ``per``."""
    roots = [s for s in spans if s.name == per]
    rows: dict = {}
    for root in roots:
        for s in (root, *root.descendants()):
            n, ns = rows.get(s.name, (0, 0))
            rows[s.name] = (n + 1, ns + s.self_ns)
    lines = [f"self time per {per} ({len(roots)} whole spans), ms:"]
    for name, (n, ns) in sorted(rows.items(), key=lambda kv: -kv[1][1]):
        lines.append(f"  {name:<28}{ns / 1e6 / max(1, len(roots)):10.3f}  x{n / max(1, len(roots)):.2f}")
    return "\n".join(lines)


def idle_by_span(trace, spans: list, floor_ns: int = 10_000) -> dict:
    """The first device's idle seconds in the slice by the innermost ``atpu.`` span open
    while the device idled (``_no_span_`` outside any): each idle interval is cut at the
    spans' edges, so a gap that begins in one phase and ends in another is shared out.
    Gaps under 10 us (between two ops of one program) are summed apart, as in
    ``trace_reduce.Trace.idle_gaps``."""
    if trace is None or not trace.devices:
        return {}
    busy = trace._merged(trace.devices[sorted(trace.devices)[0]])
    edges = [trace.begin] + [t for iv in busy for t in iv] + [trace.end]
    out: dict = {}
    for g0, g1 in zip(edges[::2], edges[1::2]):
        if g1 <= g0:
            continue
        if g1 - g0 < floor_ns:
            out["_gaps_under_10_us_"] = out.get("_gaps_under_10_us_", 0.0) + (g1 - g0) / 1e9
            continue
        inside = [s for s in spans if s.t0 < g1 and s.t1 > g0]
        cuts = sorted({g0, g1, *(t for s in inside for t in (s.t0, s.t1) if g0 < t < g1)})
        for a, b in zip(cuts, cuts[1:]):
            open_ = [s for s in inside if s.t0 <= a and b <= s.t1]
            name = max(open_, key=lambda s: s.t0).name if open_ else "_no_span_"
            out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def kernel_ms_per_execution(metric: str, trace, pattern: str):
    """Device ms of the ops matching ``pattern`` (over ``<module>/<op>``) per WHOLE
    execution of the module that runs them, averaged over the devices. A whole execution
    is one inside the slice that lasts at least 0.98 of the median one: the execution
    under way when the trace stops is recorded cut short
    (``trace_reduce.train_kernel_roofline``'s rule)."""
    if trace is None or not trace.modules:
        return None
    rx, per_device, counts = re.compile(pattern), [], []
    for dev, ops in trace.devices.items():
        mine = [(t0, t1, name.split("/")[0]) for t0, t1, name in ops if rx.search(name)]
        by_module: dict = {}
        for t0, t1, module in mine:
            by_module[module] = by_module.get(module, 0) + t1 - t0
        if not by_module:
            continue
        module = max(by_module, key=by_module.get)
        whole = [m for m in trace.modules.get(dev, [])
                 if m[2] == module and m[0] >= trace.begin and m[1] <= trace.end]
        if not whole:
            continue
        full = 0.98 * statistics.median(b - a for a, b, _ in whole)
        whole = [m for m in whole if m[1] - m[0] >= full]
        ns = sum(t1 - t0 for t0, t1, m in mine
                 if m == module and any(a <= t0 < b for a, b, _ in whole))
        counts.append(len(whole))
        per_device.append(ns / 1e6 / len(whole))
    if not per_device or not enough(metric, min(counts)):
        return None
    return sum(per_device) / len(per_device)
