"""What the host itself costs a ``step()``: the median, over the whole
``atpu.engine.step`` spans of the slice, of the step's duration less its ``*.fetch``
descendants (the blocking reads in which the host only waits for the device). Also prints
on stderr the per-phase self-time table of ``step()`` and the device's idle seconds by the
innermost span open while it idled."""

import statistics
import sys

from benchmarks.chipbench import program_spans

NAME = "engine_host_ms_per_step"


def host_ns(step) -> int:
    return step.dur - sum(d.dur for d in step.descendants() if d.name.endswith(".fetch"))


def read(run):
    spans = program_spans.in_slice(run)
    steps = [s for s in spans if s.name == "engine.step"]
    if steps:
        print(program_spans.self_time_table(spans), file=sys.stderr)
        idle = program_spans.idle_by_span(run.trace, spans)
        print("device idle s by innermost span:", {k: round(v, 6) for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])}, file=sys.stderr)
    if not program_spans.enough(NAME, len(steps)):
        return None
    return statistics.median(host_ns(s) for s in steps) / 1e6
