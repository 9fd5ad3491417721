"""Tier-1 gate: graftaudit over the real program set stays clean (ISSUE 4).

Lowers every program the warmup path enumerates for the default config —
train, eval, prefill buckets, chunk-append, decode, row inserts — through the
SAME enumerator the AOT cache warmup uses, and fails on any finding beyond the
committed (empty) ``graftaudit_baseline.json``. The contract mirrors
graftlint's: the baseline only shrinks; fix the program or add a reasoned
entry to ``analysis/program/suppressions.SUPPRESSIONS``.
"""

import json
import os

import pytest

from accelerate_tpu.analysis.baseline import apply_baseline, load_baseline
from accelerate_tpu.analysis.program import (
    AUDIT_BASELINE_FILE,
    audit_findings,
    capture_default_programs,
)


@pytest.fixture(scope="module")
def default_captures():
    return capture_default_programs()


def test_audit_clean_beyond_baseline(default_captures):
    findings, stale_sups = audit_findings(default_captures)
    baseline = load_baseline(AUDIT_BASELINE_FILE)
    new, _grandfathered, _stale = apply_baseline(findings, baseline)
    listing = "\n".join(f.format() for f in new)
    assert not new, (
        f"{len(new)} graftaudit finding(s) beyond graftaudit_baseline.json:\n{listing}\n"
        "Fix the program, or add a reasoned entry to "
        "analysis/program/suppressions.SUPPRESSIONS. Do not add baseline entries — "
        "the ratchet only shrinks (docs/graftaudit.md)."
    )
    assert not stale_sups, (
        f"stale audit suppressions (matched nothing): {stale_sups}"
    )


def test_audit_baseline_is_empty_at_head():
    with open(AUDIT_BASELINE_FILE) as f:
        data = json.load(f)
    assert data["tool"] == "graftaudit"
    assert data["findings"] == [], (
        "graftaudit_baseline.json must stay empty: fix or suppress with a reason"
    )


def test_default_enumeration_covers_the_warmup_surface(default_captures):
    """The audit lowers the SAME labels the warmup path compiles: both train
    step variants' coverage comes from the same enumerator, so auditing the
    default geometry means auditing what a warm cache directory serves."""
    labels = {c.label for c in default_captures}
    assert "train_step.apply" in labels
    assert "eval_step" in labels
    assert "serving.decode_multi" in labels
    assert any(l.startswith("serving.prefill") for l in labels), labels
    assert any("insert" in l for l in labels), labels
    # The speculative surface (ISSUE 6): the fused [B, k+1] verify and the draft
    # model's programs are lowered and inventoried like everything else — the
    # clean-beyond-baseline gate above therefore covers them too.
    assert "serving.spec_verify" in labels, labels
    assert "serving.draft.decode" in labels, labels
    assert "serving.draft.prefill" in labels, labels
    # The paged-KV surface (ISSUE 7): the default sweep lowers the paged replica
    # layout alongside the dense one — block-table decode/verify, the
    # dynamic-slot page scatter, and the prefix gather/copy pair — so the empty
    # ratchet baselines cover both layouts.
    assert {"serving.decode_multi_paged", "serving.spec_verify_paged",
            "serving.insert_paged", "serving.gather_row_paged",
            "serving.copy_page"} <= labels, labels
    # The fused speculative super-step pair (ISSUE 18): the dense program rides
    # the ngram-drafter SPEC_FUSED pass (the default pass's half-depth drafter
    # is not resident), the paged twin rides the paged pass — both under the
    # same empty ratchet baselines.
    assert {"serving.spec_multi", "serving.spec_multi_paged"} <= labels, labels
    # The decode scan, both layouts: what every decoding engine dispatches.
    assert {"serving.decode_multi", "serving.decode_multi_paged"} <= labels, labels
    # The MPMD stage-program surface (ISSUE 11): the alternative TRAINING
    # layout is lowered alongside the SPMD step, and the inventory audits the
    # inter-stage DCN payload bytes of every transfer-bearing program.
    assert {"mpmd.stage0.fwd", "mpmd.stage0.bwd", "mpmd.stage1.loss_bwd",
            "mpmd.stage0.apply", "mpmd.stage1.zero"} <= labels, labels
    # The disaggregated-serving role slices (ISSUE 12): the handoff
    # export/import pair + adoption lane setup are lowered and inventoried,
    # and the decode-only surface really IS decode-only — lowering it never
    # produces a prefill program.
    assert {"serving.export_pages", "serving.import_pages",
            "serving.lane_valid"} <= labels, labels
    from accelerate_tpu.analysis.program.inventory import collective_inventory

    for c in default_captures:
        if c.label == "mpmd.stage0.fwd":
            assert collective_inventory(c)["stage_transfer_bytes"] > 0
    # Every capture actually lowered: the StableHLO text parses a @main.
    for c in default_captures:
        assert "@main" in c.hlo_text, c.label


def test_warmup_manifest_stamps_audit_provenance(tmp_path):
    """run_warmup writes per-program collective counts + donation effectiveness
    into the manifest (cached executables carry their audit provenance)."""
    from accelerate_tpu.analysis.program import LowerOnlyCache
    from accelerate_tpu.compile_cache.warmup import run_warmup

    cache = LowerOnlyCache()
    manifest = run_warmup(
        cache=cache,
        manifest_path=str(tmp_path / "m.json"),
        preset="smoke", batch_size=4, seq_len=32, serve=False, eval_step=False,
    )
    audit = manifest["program_audit"]
    assert audit, "manifest carries no program_audit entries"
    by_label = {a["label"]: a for a in audit}
    apply = by_label["train_step.apply"]
    assert apply["donation"]["donated"] > 0
    assert apply["donation"]["dead"] == 0, (
        "train-step donation regressed: "
        f"{apply['donation']} — see the micro-counter incident in docs/graftaudit.md"
    )
    assert "collectives" in apply and "jaxpr" in apply["collectives"]
    with open(tmp_path / "m.json") as f:
        on_disk = json.load(f)
    assert on_disk["program_audit"] == audit


def test_cli_smoke(capsys):
    from accelerate_tpu.analysis.program.cli import main

    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule_id in ("dtype-promotion", "replicated-sharding", "dead-donation",
                    "host-transfer"):
        assert rule_id in out
