"""Device ms per whole execution of the decode program that the SELECTION costs between
the index kernel and the attention kernel: the exact top-``topk`` of each lane's scores
and the gather of the chosen K and V rows (in dots3 this part cost four times the two
kernels it stands between; PERF.md §5). The trace names XLA's ops by what they compute
and the shape of their result, not by ``jax.named_scope``, so the ops are found by shape
(``pattern``): the sort over the ``[lanes, max_len]`` scores, and every op whose result
is the gathered block of ``lanes · topk`` rows of ``[kv heads, head_dim]`` or an int32
vector of as many row numbers. A configuration without ``sa_config``, or a program
without such ops, gives nothing."""

from benchmarks.chipbench import program_spans

NAME = "sparse_select_ms_per_dispatch"


def pattern(c: dict) -> str:
    sv, rows = c["serve"], c["serve"]["max_slots"] * c["sa_config"]["topk"]
    scores = rf"sort[^/]*_f32_{sv['max_slots']}_{sv['max_len']}"
    gathered = rf"_bf16_{rows}_{c['num_key_value_heads']}_{c['head_dim']}"
    return rf"decode_multi_step_paged/[^/]*({scores}|{gathered}|_s32_{rows})$"


def read(run):
    if "sa_config" not in run.config:
        return None
    return program_spans.kernel_ms_per_execution(NAME, run.trace, pattern(run.config))
