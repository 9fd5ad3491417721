"""Tier-1 gate: graftmem over the real program set stays clean (ISSUE 16).

The memaudit analog of ``test_audit_clean.py``: lowers the full default audit
surface through the SAME enumerator and fails on any memory finding beyond the
committed (empty) ``graftmem_baseline.json`` — the budget rule, the
replicated-optimizer-state rule, and the DCN hot-path rule all hold on the
real train/eval/serving/paged/disagg/MPMD programs. Plus the estimator
contract: every surface label gets a positive per-device estimate under the
chip budget, the estimate tracks the allocator's measured peak within
:data:`MEASURED_TOLERANCE` where a ledger exists (CPU has none — there the
model-state floor anchors it), and the warmup manifest stamps the block.
"""

import json

import pytest

from accelerate_tpu.analysis.baseline import apply_baseline, load_baseline
from accelerate_tpu.analysis.program import (
    DEFAULT_CHIP_BUDGET_BYTES,
    MEM_BASELINE_FILE,
    capture_default_programs,
    run_memaudit,
)
from accelerate_tpu.analysis.program.memory import (
    MEASURED_TOLERANCE,
    estimate_program_memory,
    load_estimates,
)


@pytest.fixture(scope="module")
def default_captures():
    return capture_default_programs()


def test_memaudit_clean_beyond_baseline(default_captures):
    findings, _estimates, stale_sups, _notices = run_memaudit(
        captures=default_captures, baseline_estimates=load_estimates()
    )
    baseline = load_baseline(MEM_BASELINE_FILE)
    new, _grandfathered, _stale = apply_baseline(findings, baseline)
    listing = "\n".join(f.format() for f in new)
    assert not new, (
        f"{len(new)} graftmem finding(s) beyond graftmem_baseline.json:\n{listing}\n"
        "Shard/donate the program, or add a reasoned entry to "
        "analysis/program/suppressions.MEM_SUPPRESSIONS. Do not add baseline "
        "entries — the ratchet only shrinks (docs/graftmem.md)."
    )
    assert not stale_sups, (
        f"stale memaudit suppressions (matched nothing): {stale_sups}"
    )


def test_mem_baseline_is_empty_at_head():
    with open(MEM_BASELINE_FILE) as f:
        data = json.load(f)
    assert data["tool"] == "memaudit"
    assert data["findings"] == [], (
        "graftmem_baseline.json findings must stay empty: fix or suppress with a reason"
    )
    assert data["estimates"] == {}, (
        "the estimate ratchet table is opt-in per deployment — HEAD ships it "
        "empty (regenerate with `python -m accelerate_tpu memaudit --baseline` "
        "to arm it)"
    )


def test_estimates_cover_the_default_surface(default_captures):
    _findings, estimates, _stale, _notices = run_memaudit(
        captures=default_captures
    )
    for label in ("train_step.apply", "eval_step", "serving.decode_multi",
                  "serving.decode_multi_paged", "mpmd.stage0.fwd"):
        assert label in estimates, sorted(estimates)
        assert estimates[label]["peak_bytes"] > 0, label
        assert estimates[label]["peak_bytes"] < DEFAULT_CHIP_BUDGET_BYTES, label
    # The MPMD stage programs carry their host-level DCN payload; the SPMD
    # smoke surface (single-axis mesh, no 'dcn' axis) prices zero DCN.
    assert estimates["mpmd.stage0.fwd"]["dcn_bytes"] > 0
    assert estimates["train_step.apply"]["dcn_bytes"] == 0


def test_fused_spec_budget_row_no_hbm_regression(default_captures):
    """The fused speculative super-step's budget row (ISSUE 18): both fused
    programs get a positive per-device estimate under the chip budget, and the
    scan carry the fusion adds (token history, key-cursor table, per-round
    counters — O(slots × max_len) int32) must not regress peak HBM against the
    plain multi-step super-step it degrades into. 2% is the band: the carry is
    bookkeeping, not a second activation footprint."""
    _findings, estimates, _stale, _notices = run_memaudit(
        captures=default_captures
    )
    for fused, fallback in (("serving.spec_multi", "serving.decode_multi"),
                            ("serving.spec_multi_paged",
                             "serving.decode_multi_paged")):
        assert fused in estimates, sorted(estimates)
        peak = estimates[fused]["peak_bytes"]
        assert 0 < peak < DEFAULT_CHIP_BUDGET_BYTES, fused
        base = estimates[fallback]["peak_bytes"]
        assert peak <= 1.02 * base, (
            f"{fused} peak {peak} regressed > 2% vs {fallback} peak {base}: "
            "the fused carry should be bookkeeping-sized"
        )


def test_estimate_tracks_measured_peak(default_captures):
    """The stated estimate-vs-measured contract. Where the backend keeps an
    allocator ledger (TPU/GPU), the static estimate for the biggest program
    must sit within ±MEASURED_TOLERANCE of measured peak. CPU returns no
    ledger — there the anchor is analytic: the estimate must cover the bytes
    the arguments alone pin live (model + optimizer state), the floor no
    correct allocator can beat."""
    from accelerate_tpu.telemetry import device_memory_stats

    train = [c for c in default_captures if c.label == "train_step.apply"]
    assert train
    est = estimate_program_memory(train[0])
    stats = device_memory_stats()
    measured = stats.get("peak_bytes_in_use")
    if measured:
        rel_error = abs(est["peak_bytes"] - measured) / measured
        assert rel_error <= MEASURED_TOLERANCE, (
            f"static estimate {est['peak_bytes']} vs measured {measured}: "
            f"rel error {rel_error:.2f} > {MEASURED_TOLERANCE}"
        )
    else:
        assert est["peak_bytes"] >= est["args_bytes"] > 0
        assert est["temp_peak_bytes"] > 0, (
            "train step with zero live intermediates: the sweep went blind"
        )


def test_warmup_manifest_stamps_memory_estimates(tmp_path):
    from accelerate_tpu.analysis.program import LowerOnlyCache
    from accelerate_tpu.compile_cache.warmup import run_warmup

    manifest = run_warmup(
        cache=LowerOnlyCache(),
        manifest_path=str(tmp_path / "m.json"),
        preset="smoke", batch_size=4, seq_len=32, serve=False, eval_step=False,
    )
    audit = manifest["program_audit"]
    assert audit
    for entry in audit:
        mem = entry["memory"]
        assert mem["peak_bytes"] > 0, entry["label"]
        assert {"args_bytes", "temp_peak_bytes", "donation_credit_bytes",
                "ici_bytes", "dcn_bytes"} <= set(mem), entry["label"]
    with open(tmp_path / "m.json") as f:
        on_disk = json.load(f)
    assert on_disk["program_audit"] == audit


def test_memcli_smoke(capsys):
    from accelerate_tpu.analysis.program.memcli import main

    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule_id in ("hbm-budget-exceeded", "replicated-optimizer-state",
                    "dcn-on-hot-path"):
        assert rule_id in out
