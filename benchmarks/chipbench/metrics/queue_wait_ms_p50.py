"""Median wait in the engine's queue, submit to admission, of the requests admitted in
the slice: the ``queue_wait_ms`` attribute of their ``atpu.engine.prefill`` spans (the
value the engine's own ``queue_waits`` takes)."""

import statistics

from benchmarks.chipbench import program_spans

NAME = "queue_wait_ms_p50"


def read(run):
    waits = [s.attrs["queue_wait_ms"] for s in program_spans.in_slice(run)
             if s.name == "engine.prefill" and "queue_wait_ms" in s.attrs]
    return float(statistics.median(waits)) if program_spans.enough(NAME, len(waits)) else None
