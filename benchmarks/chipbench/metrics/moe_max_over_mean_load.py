"""What imbalance costs a grouped product: the largest number of pairs on ONE held expert
over the mean on a held expert, Σ ``moe_max_on_one_expert`` × experts held ÷ Σ ``moe_pairs``
over the slice's ``atpu.engine.decode.drain`` spans (1 = even load; a grouped product's
longest group sets its tail)."""

from benchmarks.chipbench import program_spans

NAME = "moe_max_over_mean_load"


def read(run):
    spans = [s for s in program_spans.in_slice(run)
             if s.name == "engine.decode.drain" and s.attrs.get("moe_pairs", 0) > 0]
    if not program_spans.enough(NAME, len(spans)):
        return None
    held = run.config["n_routed_experts"]
    return (held * sum(s.attrs["moe_max_on_one_expert"] for s in spans)
            / sum(s.attrs["moe_pairs"] for s in spans))
