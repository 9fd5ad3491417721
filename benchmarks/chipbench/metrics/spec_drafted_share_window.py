"""The drafts verified a running lane and round, over the WHOLE window: Σ ``spec_proposed``
÷ Σ ``spec_lane_rounds`` over the window's whole ``engine.decode.drain`` phases in the
program's ledger. ``spec_k`` when every running lane drafts every round (1 for a module
that drafts one token); a change that drafts for fewer lanes or rounds reads less. A
program that does not count them gives nothing."""

from benchmarks.chipbench import program_phases

NAME = "spec_drafted_share_window"


def read(run):
    drains = [s for s in program_phases.whole(run, "engine.decode.drain")
              if s.attrs.get("spec_lane_rounds", 0) > 0]
    if not program_phases.enough(NAME, len(drains)):
        return None
    return (sum(s.attrs.get("spec_proposed", 0) for s in drains)
            / sum(s.attrs["spec_lane_rounds"] for s in drains))
