"""The share of the live keys that the sparse layers' attention read: Σ
``dsa_keys_attended`` ÷ Σ ``dsa_keys_scored`` over the slice's ``atpu.engine.decode.drain``
spans (the decode program's own counts, summed over a dispatch's lanes, sparse layers and
steps). 1 means nothing was sparse: every lane held no more keys than ``index_topk``. A
program whose drain span carries no such attribute gives nothing."""

from benchmarks.chipbench import program_spans

NAME = "dsa_selected_share"


def read(run):
    spans = [s for s in program_spans.in_slice(run)
             if s.name == "engine.decode.drain" and s.attrs.get("dsa_keys_scored", 0) > 0]
    if not program_spans.enough(NAME, len(spans)):
        return None
    return (sum(s.attrs["dsa_keys_attended"] for s in spans)
            / sum(s.attrs["dsa_keys_scored"] for s in spans))
