"""Device ms of the kernel named ``dsa_index_scores`` per whole execution of the decode
program in the slice (one dispatch = ``decode_steps`` decode steps x sparse layers calls)."""

from benchmarks.chipbench import program_spans

NAME = "dsa_index_ms_per_dispatch"
PATTERN = r"/[^/]*dsa_index_scores"


def read(run):
    return program_spans.kernel_ms_per_execution(NAME, run.trace, PATTERN)
