"""``moe_pairs_per_token`` over the WHOLE window: Σ ``moe_pairs`` ÷ Σ ``moe_tokens`` over
the window's whole ``engine.decode.drain`` phases in the program's ledger (the decode
program's own counts, a dispatch's steps and expert layers summed). One entry for every
cell whose model counts them."""

from benchmarks.chipbench import program_phases

NAME = "moe_pairs_per_token_window"


def read(run):
    drains = [s for s in program_phases.whole(run, "engine.decode.drain")
              if s.attrs.get("moe_tokens", 0) > 0]
    if not program_phases.enough(NAME, len(drains)):
        return None
    return sum(s.attrs["moe_pairs"] for s in drains) / sum(s.attrs["moe_tokens"] for s in drains)
