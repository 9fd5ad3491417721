"""The share of the WHOLE window in which running lanes stood still for admission: the
part inside the window of every ``engine.admit`` phase that began with ``lanes`` > 0, over
the window. ``engine_admit_wall_share``'s quantity from the program's own ledger: an
admission may last longer than any traced slice, so it is clipped at the window's edges
and never left out."""

from benchmarks.chipbench import program_phases

NAME = "engine_admit_share_window"


def read(run):
    admits = [s for s in program_phases.in_window(run)
              if s.name == "engine.admit" and s.attrs.get("lanes", 0) > 0]
    t0, t1 = program_phases.window(run)
    if not program_phases.enough(NAME, len(admits)) or t1 <= t0:
        return None
    return 100.0 * sum(program_phases.clipped(run, s) for s in admits) / (t1 - t0)
