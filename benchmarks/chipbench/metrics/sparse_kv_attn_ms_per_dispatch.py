"""Device ms of the kernel named ``paged_attention`` per whole execution of the decode
program in the slice, in a cell whose decode reads the K/V rows a learned selection chose
(one dispatch = ``decode_steps`` decode steps x layers calls, each over at most ``topk``
gathered rows a lane). The index kernel of the same program is another op, read by
``dsa_index_ms_per_dispatch``."""

from benchmarks.chipbench import program_spans

NAME = "sparse_kv_attn_ms_per_dispatch"
PATTERN = r"decode_multi_step_paged/[^/]*paged_attention"


def read(run):
    return program_spans.kernel_ms_per_execution(NAME, run.trace, PATTERN)
