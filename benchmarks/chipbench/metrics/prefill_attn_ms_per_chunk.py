"""Device ms of the kernel named ``flash_fwd`` per whole execution of the prefill program
that spends most time in it (the chunk-append program: one execution = one prompt chunk x
layers calls). No op of that name in a prefill program: the prefill's attention is not the
flash kernel, and the metric is left out."""

from benchmarks.chipbench import program_spans

NAME = "prefill_attn_ms_per_chunk"
PATTERN = r"prefill[^/]*/[^/]*flash_fwd"


def read(run):
    return program_spans.kernel_ms_per_execution(NAME, run.trace, PATTERN)
