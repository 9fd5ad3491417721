"""Device ms of the flash backward kernels, named ``flash_bwd_dq`` and ``flash_bwd_dkv``,
per whole train step in the slice (each runs once a layer)."""

from benchmarks.chipbench import program_spans

NAME = "flash_bwd_ms_per_step"
PATTERN = r"/[^/]*flash_bwd_(dq|dkv)"


def read(run):
    return program_spans.kernel_ms_per_execution(NAME, run.trace, PATTERN)
