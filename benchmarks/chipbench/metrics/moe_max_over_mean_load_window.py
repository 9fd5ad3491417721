"""``moe_max_over_mean_load`` over the WHOLE window: Σ ``moe_max_on_one_expert`` × experts
held ÷ Σ ``moe_pairs`` over the window's whole ``engine.decode.drain`` phases in the
program's ledger (1 = even load). One entry for every cell whose model counts them."""

from benchmarks.chipbench import program_phases

NAME = "moe_max_over_mean_load_window"


def read(run):
    drains = [s for s in program_phases.whole(run, "engine.decode.drain")
              if s.attrs.get("moe_pairs", 0) > 0]
    if not program_phases.enough(NAME, len(drains)):
        return None
    return (run.config["n_routed_experts"] * sum(s.attrs["moe_max_on_one_expert"] for s in drains)
            / sum(s.attrs["moe_pairs"] for s in drains))
