"""CPU test of ``metrics/prefill_attn_ms_per_chunk.py`` on hand-built traces: the flash
forward's device ms per WHOLE execution of the chunk-append prefill program. Nothing here is
a measurement."""

import types

import pytest

from benchmarks.chipbench import run, trace_reduce

NAME = "prefill_attn_ms_per_chunk.throughput"
CHUNK, FIRST, DECODE = "jit__prefill_chunk_jit", "jit__prefill_jit", "jit__decode_multi_step_paged"


def traced(modules, ops):
    trace = trace_reduce.Trace.__new__(trace_reduce.Trace)
    trace.begin, trace.end = 0, 3_000_000
    trace.modules, trace.devices = {"d": modules}, {"d": sorted(ops)}
    return types.SimpleNamespace(trace=trace, program_spans=[], obs={}, config={}, peak=None)


def chunk_ops(t, module=CHUNK, layers=2):
    """``layers`` kernel calls of 30 us and the products around them, from ``t`` on."""
    ops = []
    for l in range(layers):
        ops += [(t + l * 100_000 + 10_000, t + l * 100_000 + 40_000,
                 f"{module}/flash_fwd.1_bf16_1_32_512_128__mosaic_"),
                (t + l * 100_000 + 50_000, t + l * 100_000 + 90_000,
                 f"{module}/fusion.126_bf16_512_14336")]
    return ops


def test_three_whole_chunks_and_one_cut_short_read_the_whole_ones():
    """Three chunk executions of 400 us with two kernel calls of 30 us each, a fourth cut to
    100 us where the trace stops (one call), one first-chunk program and a decode program
    with a kernel of its own: 0.060 ms a chunk."""
    modules = [(i * 500_000, i * 500_000 + 400_000, CHUNK) for i in range(3)]
    modules += [(1_500_000, 1_600_000, CHUNK), (1_700_000, 1_900_000, FIRST),
                (2_000_000, 2_400_000, DECODE)]
    ops = [op for i in range(3) for op in chunk_ops(i * 500_000)]
    ops += chunk_ops(1_500_000, layers=1) + chunk_ops(1_700_000, FIRST)
    ops.append((2_010_000, 2_300_000, f"{DECODE}/paged_attention.11_bf16_32_32_128__mosaic_"))
    assert run.read_metric(NAME, traced(modules, ops)) == pytest.approx(0.060)


def test_two_whole_chunks_are_too_few(capsys):
    modules = [(i * 500_000, i * 500_000 + 400_000, CHUNK) for i in range(2)]
    ops = [op for i in range(2) for op in chunk_ops(i * 500_000)]
    assert run.read_metric(NAME, traced(modules, ops)) is None
    assert "prefill_attn_ms_per_chunk: 2 sample(s)" in capsys.readouterr().err


@pytest.mark.parametrize("kernel_in", [None, DECODE, "jit_train_step"])
def test_no_flash_fwd_in_a_prefill_program_reads_nothing(kernel_in):
    """The parent commit: the prefill's attention is three XLA fusions. A ``flash_fwd`` in
    another program (the train step's, say) is not the prefill's."""
    modules = [(i * 500_000, i * 500_000 + 400_000, CHUNK) for i in range(4)]
    ops = [(i * 500_000 + 10_000, i * 500_000 + 300_000,
            f"{CHUNK}/fusion.121_bf16_8_4_512") for i in range(4)]
    if kernel_in:
        modules += [(2_000_000 + i * 100_000, 2_090_000 + i * 100_000, kernel_in)
                    for i in range(4)]
        ops += [(2_000_000 + i * 100_000 + 5_000, 2_000_000 + i * 100_000 + 50_000,
                 f"{kernel_in}/flash_fwd.17_bf16_4_32__mosaic_") for i in range(4)]
    assert run.read_metric(NAME, traced(modules, ops)) is None


def test_no_trace_reads_nothing():
    assert run.read_metric(NAME, types.SimpleNamespace(trace=None)) is None
