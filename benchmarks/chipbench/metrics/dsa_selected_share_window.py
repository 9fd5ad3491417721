"""``dsa_selected_share`` over the WHOLE window: Σ ``dsa_keys_attended`` ÷ Σ
``dsa_keys_scored`` over the window's whole ``engine.decode.drain`` phases in the program's
ledger (1 = nothing was sparse). One entry for every cell whose model selects."""

from benchmarks.chipbench import program_phases

NAME = "dsa_selected_share_window"


def read(run):
    drains = [s for s in program_phases.whole(run, "engine.decode.drain")
              if s.attrs.get("dsa_keys_scored", 0) > 0]
    if not program_phases.enough(NAME, len(drains)):
        return None
    return (sum(s.attrs["dsa_keys_attended"] for s in drains)
            / sum(s.attrs["dsa_keys_scored"] for s in drains))
