"""(token, expert) pairs the grouped expert product computed per token that entered an
expert layer: Σ ``moe_pairs`` ÷ Σ ``moe_tokens`` over the slice's
``atpu.engine.decode.drain`` spans (the decode program's own counts, summed over a
dispatch's steps and expert layers). ``num_experts_per_tok`` × held ÷ published in
expectation (0.5 at 16 of 256 experts and 8 a token). A program whose drain span carries no
such attribute gives nothing."""

from benchmarks.chipbench import program_spans

NAME = "moe_pairs_per_token"


def drains(run):
    return [s for s in program_spans.in_slice(run)
            if s.name == "engine.decode.drain" and s.attrs.get("moe_tokens", 0) > 0]


def read(run):
    spans = drains(run)
    if not program_spans.enough(NAME, len(spans)):
        return None
    return sum(s.attrs["moe_pairs"] for s in spans) / sum(s.attrs["moe_tokens"] for s in spans)
