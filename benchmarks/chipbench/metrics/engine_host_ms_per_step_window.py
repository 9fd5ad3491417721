"""What the host itself costs a ``step()``, over the WHOLE window: the median, over the
window's whole ``engine.step`` phases in the program's ledger, of the step's duration less
its ``*.fetch`` descendants (``engine_host_ms_per_step``'s own ``host_ns``). Hundreds of
steps where the traced slice of a cell with long admissions holds none to a handful. Also
prints on stderr how the ledger's phases inside the traced slice match the trace's spans."""

import statistics
import sys

from benchmarks.chipbench import program_phases
from benchmarks.chipbench.metrics.engine_host_ms_per_step import host_ns

NAME = "engine_host_ms_per_step_window"


def median_ms(steps: list) -> str:
    if not steps:
        return "- (0)"
    return f"{statistics.median(host_ns(s) for s in steps) / 1e6:.3f} ({len(steps)})"


def by_kind(run, steps: list) -> str:
    """The same median over the steps that admitted a prompt and those that did not, and
    over those inside the traced slice (``run.slice_host``, the ledger's clock) and outside
    it: whether the slice-bound reading differs by WHICH steps a slice holds."""
    a, b = (int(t * 1e9) if t is not None else 0
            for t in getattr(run, "slice_host", None) or (None, None))
    halves = []
    for test in (lambda s: any(d.name == "engine.prefill" for d in s.descendants()),
                 lambda s: a <= s.t0 and s.t1 <= b):
        marks = [test(s) for s in steps]
        halves += [median_ms([s for s, m in zip(steps, marks) if m == want]) for want in (True, False)]
    return ("{}: median ms (steps): with a prefill {}, without {}; inside the traced slice {}, "
            "outside it {}".format(NAME, *halves))


def read(run):
    steps = program_phases.whole(run, "engine.step")
    if steps:
        print(program_phases.slice_check(run), file=sys.stderr)
        print(by_kind(run, steps), file=sys.stderr)
    if not program_phases.enough(NAME, len(steps)):
        return None
    return statistics.median(host_ns(s) for s in steps) / 1e6
