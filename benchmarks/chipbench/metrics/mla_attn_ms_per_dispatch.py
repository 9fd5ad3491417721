"""Device ms of the kernel named ``mla_paged_attention`` per whole execution of the decode
program in the slice (one dispatch = ``decode_steps`` decode steps x layers calls)."""

from benchmarks.chipbench import program_spans

NAME = "mla_attn_ms_per_dispatch"
PATTERN = r"/[^/]*mla_paged_attention"


def read(run):
    return program_spans.kernel_ms_per_execution(NAME, run.trace, PATTERN)
