"""The share of the slice in which running lanes stood still for admission: the summed
duration of the ``atpu.engine.admit`` spans that began with ``lanes`` > 0, over the
slice. Measured where it happens, unlike ``prefill_wall_share``'s guess from outside."""

from benchmarks.chipbench import program_spans

NAME = "engine_admit_wall_share"


def read(run):
    admits = [s for s in program_spans.in_slice(run)
              if s.name == "engine.admit" and s.attrs.get("lanes", 0) > 0]
    if not program_spans.enough(NAME, len(admits)) or not run.trace.window_s:
        return None
    return 100.0 * sum(s.dur for s in admits) / 1e9 / run.trace.window_s
