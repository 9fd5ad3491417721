"""The share of the drafts that the verify accepted, over the WHOLE window: Σ
``spec_accepted`` ÷ Σ ``spec_proposed`` over the window's whole ``engine.decode.drain``
phases in the program's ledger (the speculative program's own counts, a dispatch's rounds
and lanes summed). A program that does not count them, or a window in which nothing was
drafted, gives nothing."""

from benchmarks.chipbench import program_phases

NAME = "spec_accept_share_window"


def read(run):
    drains = [s for s in program_phases.whole(run, "engine.decode.drain")
              if s.attrs.get("spec_proposed", 0) > 0]
    if not program_phases.enough(NAME, len(drains)):
        return None
    return (sum(s.attrs["spec_accepted"] for s in drains)
            / sum(s.attrs["spec_proposed"] for s in drains))
