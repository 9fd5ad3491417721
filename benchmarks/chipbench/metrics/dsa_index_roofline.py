"""The indexer kernel's share of its roofline: the least seconds the chip needs for the
index scores of the slice's decode dispatches — the family's ``dsa_index_work(c, lens,
page_size)`` at each lane's ACTUAL length, step by step (the loop of
``trace_reduce.paged_attn_roofline``) — over the device time of the ops named
``dsa_index_scores`` in the decode program. A family without that count, or a decode
program without the kernel, gives nothing."""

from benchmarks.chipbench import work

NAME = "dsa_index_roofline"
PATTERN = r"decode_multi_step_paged/[^/]*dsa_index_scores"


def read(run):
    t, count = run.trace, getattr(run.family, "dsa_index_work", None)
    if t is None or count is None:
        return None
    secs = sum(t.op_seconds(PATTERN).values())
    sv, least = run.config["serve"], 0.0
    for s in run.obs.get("decode_steps", ()):
        if run.slice_host[0] <= s["t0"] and s["t1"] <= run.slice_host[1]:
            for j in range(sv["decode_steps"]):
                lens = [max(1, n - j) for n in s["lens"]]
                least += work.least_seconds(*count(run.config, lens, sv["page_size"]),
                                            run.peak)
    return 100.0 * least / secs if secs and least else None
