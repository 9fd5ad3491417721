"""The longest a streaming client waits between two bursts of tokens: the 99th percentile,
over the WHOLE window, of (end of an ``engine.decode.drain`` − end of the one before it)
where the later one's ``engine.step`` began with ``lanes`` > 0 — some request was running
and stood still from the earlier drain to this one, through whatever ``step()`` did in
between (an admission's prefill, as a rule). From the program's ledger. Also prints on
stderr the window's five longest ``engine.step`` phases with the phases inside them: what
a run that stalls leaves behind."""

import sys

import numpy as np

from benchmarks.chipbench import program_phases

NAME = "decode_gap_ms_p99"


def gaps_ns(drains: list) -> list:
    """Drain end to drain end, for each drain (in start order) but the first whose step
    began with a lane running."""
    return [b.t1 - a.t1 for a, b in zip(drains, drains[1:])
            if program_phases.root(b).attrs.get("lanes", 0) > 0]


def read(run):
    steps = program_phases.whole(run, "engine.step")
    if steps:
        print(program_phases.longest_table(steps), file=sys.stderr)
    gaps = gaps_ns(program_phases.whole(run, "engine.decode.drain"))
    if not program_phases.enough(NAME, len(gaps)):
        return None
    return float(np.percentile(gaps, 99)) / 1e6
