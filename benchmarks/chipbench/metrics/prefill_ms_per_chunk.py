"""Wall ms of an admission a prefill program it dispatched: Σ duration ÷ Σ ``chunks`` over
the window's whole ``engine.prefill`` phases in the program's ledger (page reservation done
→ first token handed on; ``chunks`` = programs dispatched for the prompt: one a 512-token
chunk in every cell). A program whose prefill phase carries no ``chunks`` gives nothing."""

import sys

from benchmarks.chipbench import program_phases

NAME = "prefill_ms_per_chunk"


def read(run):
    prefills = [s for s in program_phases.whole(run, "engine.prefill")
                if s.attrs.get("chunks", 0) > 0]
    if not program_phases.enough(NAME, len(prefills)):
        return None
    chunks = sum(s.attrs["chunks"] for s in prefills)
    print(f"{NAME}: {chunks} chunk(s)", file=sys.stderr)
    return sum(s.dur for s in prefills) / 1e6 / chunks
