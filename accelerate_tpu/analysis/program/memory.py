"""graftmem: static per-device HBM + comms-cost estimation over captured programs.

The third audit tier. graftlint reads source, graftaudit reads the traced
program for *rule violations* — this module computes what a captured program
**costs**: a per-device peak-HBM estimate and a priced communication volume,
from lowering artifacts alone (no TPU, no execution, no allocator). The model:

- **Arguments / constants** — aval bytes divided by each leaf's actual sharding
  (``sharding.shard_shape``): a ``P("dp", None)`` input on 8 devices counts an
  eighth, a replicated optimizer moment counts in full on every chip.
- **Donation / aliasing** — credited through the same machinery graftaudit's
  dead-donation rule uses: ``tf.aliasing_output = N`` on a kept ``@main``
  parameter (translated through ``kept_var_idx``) zeroes output ``N``'s charge
  (the buffer is reused); deferred multi-device donors (``jax.buffer_donor``)
  form a credit pool consumed by output definitions.
- **Intermediates** — a live-range sweep over the root jaxpr: each equation
  output allocates at definition and frees after its last use; the estimate is
  the peak of the running sum. Temporaries are divided by ``temp_division``
  (default: the largest division factor among the inputs — batch-sharded
  activations dominate temp footprint; a replicated-everything program gets 1).
- **Collectives** — each jaxpr collective is priced at
  ``payload × (axis_size − 1) / axis_size`` (one ring pass over ICI), where
  ``axis_size`` resolves the equation's named axes against the input mesh.
  Axes in ``dcn_axes`` are classified DCN and priced at full payload (no ring
  locality credit across slices). Host-level DCN payloads — MPMD
  ``stage_transfer`` and the disaggregated-serving KV page handoff — are priced
  at full payload too (they cross the wire outside any jit, so no collective
  op ever records them).

This is an **estimator**, not an allocator replay: XLA fuses, rematerializes
and buffer-shares in ways a jaxpr sweep cannot see. The contract (tested in
``tests/test_memaudit_clean.py``, stated in ``docs/graftmem.md``) is that the
estimate is a *stable, direction-faithful* proxy — within
:data:`MEASURED_TOLERANCE` of ``device_memory_stats`` peak where a backend has
an allocator ledger — good enough to ratchet in CI and to rank layout changes
(ZeRO-1 sharding, paged vs dense KV) before a TPU window.
"""

from __future__ import annotations

import fnmatch
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from ..engine import REPO_ROOT, Finding
from .capture import ProgramCapture, flat_inputs, main_arg_attributes
from .inventory import _PRIM_KINDS, stage_transfer_bytes
from .rules import ProgramRule
from .suppressions import MEM_SUPPRESSIONS, apply_audit_suppressions

__all__ = [
    "MEM_BASELINE_FILE",
    "DEFAULT_CHIP_BUDGET_BYTES",
    "DEFAULT_ESTIMATE_BAND",
    "MEASURED_TOLERANCE",
    "estimate_program_memory",
    "comms_cost",
    "program_memory_summary",
    "program_estimates",
    "estimate_drift_findings",
    "load_estimates",
    "sharding_division",
    "live_range_peak",
    "HbmBudgetRule",
    "ReplicatedOptimizerStateRule",
    "DcnHotPathRule",
    "all_memory_rules",
    "memory_rule_by_id",
    "known_memaudit_rule_ids",
    "memaudit_findings",
    "run_memaudit",
]

MEM_BASELINE_FILE = os.path.join(REPO_ROOT, "graftmem_baseline.json")

#: Per-chip HBM ceiling the budget rule gates against when no ``--budget`` is
#: given: 16 GiB (v5e/v5p-lite class).
DEFAULT_CHIP_BUDGET_BYTES = 16 << 30

#: Relative tolerance band on ratcheted per-label estimates: growth beyond
#: ``(1 + band)`` is a finding, shrink beyond ``(1 - band)`` a ratchet-down
#: notice, anything inside the band is benign drift (re-lowering jitter,
#: constant folding differences across jax point releases).
DEFAULT_ESTIMATE_BAND = 0.10

#: Stated estimate-vs-measured contract where an allocator ledger exists
#: (``device_memory_stats()["peak_bytes_in_use"]``): the static estimate is
#: within ±50% of measured peak on the bench smoke shape. Wide on purpose —
#: XLA rematerialization and fusion move real peaks both ways — but tight
#: enough that a doubled footprint (a lost donation, a replicated moment tree)
#: can never hide inside it.
MEASURED_TOLERANCE = 0.5

_ALIAS_RE = re.compile(r"tf\.aliasing_output\s*=\s*(\d+)")
_MHLO_SHARDING_RE = re.compile(r'mhlo\.sharding\s*=\s*"([^"]*)"')
_DEVICES_RE = re.compile(r"devices=\[([0-9,]+)\]")


# ------------------------------------------------------------- sharding division

def sharding_division(mhlo_sharding: str) -> int:
    """How many ways an ``mhlo.sharding`` attribute divides a buffer.

    ``"{replicated}"`` (and ``{maximal...}``) -> 1; ``"{devices=[8,1]<=[8]}"``
    -> 8; a trailing ``last_tile_dim_replicate`` group does not divide, so its
    dimension is excluded from the product."""
    if not mhlo_sharding or "devices=" not in mhlo_sharding:
        return 1
    m = _DEVICES_RE.search(mhlo_sharding)
    if m is None:
        return 1
    dims = [int(d) for d in m.group(1).split(",") if d]
    if "last_tile_dim_replicate" in mhlo_sharding and dims:
        dims = dims[:-1]
    division = 1
    for d in dims:
        division *= d
    return max(division, 1)


def _leaf_bytes(leaf) -> Tuple[int, int]:
    """(full_bytes, per_device_bytes) for one call-argument leaf.

    jax.Arrays divide by their actual placement via ``shard_shape`` (exact for
    NamedSharding, including uneven partial tiles); anything else (numpy, python
    scalars) is host data about to be committed replicated — full bytes."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        return 0, 0
    itemsize = int(getattr(dtype, "itemsize", 4))
    full = itemsize
    for d in shape:
        full *= int(d)
    sharding = getattr(leaf, "sharding", None)
    if sharding is not None and hasattr(sharding, "shard_shape"):
        try:
            local = sharding.shard_shape(tuple(shape))
            per_dev = itemsize
            for d in local:
                per_dev *= int(d)
            return full, per_dev
        except Exception:  # noqa: BLE001 - exotic sharding types
            pass
    return full, full


def _aval_bytes(aval) -> int:
    if aval is None or not hasattr(aval, "size"):
        return 0
    return int(aval.size) * int(getattr(aval.dtype, "itemsize", 4))


def _donated_effective(capture: ProgramCapture) -> Tuple[Dict[int, int], int]:
    """(explicit aliases, deferred-donor credit) from the lowered ``@main``.

    Returns ``({output_index: donated_arg_flat_index}, pool_bytes)``: outputs
    explicitly aliased by ``tf.aliasing_output = N`` reuse their donor's buffer
    outright; multi-device donors (``jax.buffer_donor``, alias assigned by XLA
    at compile time) contribute their per-device bytes to a credit pool the
    sweep consumes as outputs materialize. A donated-but-unusable arg (dead
    donation) carries neither attribute and earns no credit — the estimator
    charges its outputs in full, exactly the cost the dead donation causes."""
    donated = capture.donate_argnums
    if not donated:
        return {}, 0
    attrs = main_arg_attributes(capture.hlo_text)
    leaves = flat_inputs(capture)
    kept = capture.kept_var_idx
    kept_pos = (
        {flat: pos for pos, flat in enumerate(kept)} if kept is not None else None
    )
    aliases: Dict[int, int] = {}
    pool = 0
    for i in donated:
        if kept_pos is None:
            attr = attrs.get(i, "")
        elif i in kept_pos:
            attr = attrs.get(kept_pos[i], "")
        else:
            attr = ""  # donated AND pruned: dead by construction
        m = _ALIAS_RE.search(attr)
        if m is not None:
            aliases[int(m.group(1))] = i
        elif "jax.buffer_donor" in attr and i < len(leaves):
            _, per_dev = _leaf_bytes(leaves[i][1])
            pool += per_dev
    return aliases, pool


def live_range_peak(
    closed_jaxpr,
    temp_division: int = 1,
    charged_outputs: Optional[Dict[int, int]] = None,
) -> int:
    """Peak live intermediate bytes of a jaxpr: def-to-last-use sweep.

    Walks the ROOT equations in order (each is one primitive after tracing —
    sub-jaxprs of scan/while hold their carries in the root vars this sweep
    already sees). Every equation output allocates its aval bytes divided by
    ``temp_division`` at definition; a value frees after the equation of its
    last use, except jaxpr outputs, which stay live to the end.
    ``charged_outputs`` overrides the charge of specific output positions —
    the donation credit path passes 0 for explicitly-aliased outputs."""
    if closed_jaxpr is None:
        return 0
    root = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    eqns = getattr(root, "eqns", None)
    if eqns is None:
        return 0
    division = max(int(temp_division), 1)
    charged = charged_outputs or {}
    out_index = {}
    for pos, v in enumerate(root.outvars):
        if hasattr(v, "aval"):
            out_index[id(v)] = pos
    invar_ids = {id(v) for v in root.invars}
    invar_ids |= {id(v) for v in getattr(root, "constvars", ())}
    last_use: Dict[int, int] = {}
    for idx, eqn in enumerate(eqns):
        for v in eqn.invars:
            if hasattr(v, "aval"):
                last_use[id(v)] = idx
    live = 0
    peak = 0
    alloc: Dict[int, int] = {}
    frees: Dict[int, List[int]] = {}
    for vid, idx in last_use.items():
        frees.setdefault(idx, []).append(vid)
    for idx, eqn in enumerate(eqns):
        for v in eqn.outvars:
            vid = id(v)
            if vid in invar_ids or vid in alloc:
                continue  # an arg buffer, or a duplicate outvar
            pos = out_index.get(vid)
            if pos is not None and pos in charged:
                b = charged[pos]
            else:
                b = _aval_bytes(getattr(v, "aval", None)) // division
            alloc[vid] = b
            live += b
        peak = max(peak, live)
        for vid in frees.get(idx, ()):
            if vid in alloc and vid not in out_index:
                live -= alloc.pop(vid)
        # A DropVar output is never used: its buffer dies with the op.
        for v in eqn.outvars:
            vid = id(v)
            if vid in alloc and vid not in last_use and vid not in out_index:
                live -= alloc.pop(vid)
    return peak


def estimate_program_memory(
    capture: ProgramCapture, temp_division: Optional[int] = None
) -> dict:
    """Static per-device peak-HBM estimate for one captured program.

    ``peak_bytes = args + consts + live-range peak``, with donation credited:
    explicitly-aliased outputs charge nothing (the donor's buffer, already in
    ``args``, is reused) and deferred donors form a pool consumed as outputs
    materialize. All components are per-device bytes."""
    args_bytes = 0
    max_input_division = 1
    for _, leaf in flat_inputs(capture):
        full, per_dev = _leaf_bytes(leaf)
        args_bytes += per_dev
        if per_dev:
            max_input_division = max(max_input_division, full // max(per_dev, 1))
    const_bytes = 0
    consts = list(getattr(capture.jaxpr, "consts", []) or [])
    for c in consts:
        _, per_dev = _leaf_bytes(c)
        const_bytes += per_dev
    division = (
        max(int(temp_division), 1) if temp_division else max_input_division
    )

    aliases, pool = _donated_effective(capture)
    charged: Dict[int, int] = {pos: 0 for pos in aliases}
    out_bytes = 0
    donation_credit = 0
    root = getattr(capture.jaxpr, "jaxpr", capture.jaxpr)
    outvars = list(getattr(root, "outvars", []) or []) if root is not None else []
    for pos, v in enumerate(outvars):
        b = _aval_bytes(getattr(v, "aval", None)) // division
        if pos in aliases:
            donation_credit += b
            continue
        if pool > 0:
            credit = min(pool, b)
            pool -= credit
            donation_credit += credit
            charged[pos] = b - credit
            out_bytes += b - credit
        else:
            out_bytes += b
    sweep_peak = live_range_peak(
        capture.jaxpr, temp_division=division, charged_outputs=charged
    )
    if sweep_peak == 0 and capture.jaxpr is None:
        sweep_peak = out_bytes  # no jaxpr on this build: I/O-only fallback
    return {
        "peak_bytes": int(args_bytes + const_bytes + sweep_peak),
        "args_bytes": int(args_bytes),
        "const_bytes": int(const_bytes),
        "out_bytes": int(out_bytes),
        "temp_peak_bytes": int(sweep_peak),
        "donation_credit_bytes": int(donation_credit),
        "temp_division": int(division),
    }


# ----------------------------------------------------------------- comms pricing

#: Default DCN axis names: nothing in the single-slice default mesh — a future
#: multi-slice MeshConfig that names its cross-slice axis ``dcn`` is classified
#: automatically; anything else is declared per call (tests, TPU configs).
DEFAULT_DCN_AXES = frozenset({"dcn"})

#: Handoff programs whose outputs are the cross-replica KV page payload
#: (disaggregated serving): the transfer is a host-level device_put between
#: engines, priced as full-payload DCN at each endpoint program.
_KV_HANDOFF_LABELS = ("serving.export_pages", "serving.import_pages")


def _capture_mesh_shape(capture: ProgramCapture) -> Dict[str, int]:
    """axis name -> size, from the first mesh-placed input leaf."""
    for _, leaf in flat_inputs(capture):
        sharding = getattr(leaf, "sharding", None)
        mesh = getattr(sharding, "mesh", None)
        shape = getattr(mesh, "shape", None)
        if shape:
            return dict(shape)
    return {}


def _walk_jaxprs(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                yield from _walk_jaxprs(sub)


def _sub_jaxprs(val):
    inner = getattr(val, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return [inner]
    if hasattr(val, "eqns"):
        return [val]
    if isinstance(val, (tuple, list)):
        out = []
        for v in val:
            out.extend(_sub_jaxprs(v))
        return out
    return []


def _eqn_axes(eqn) -> Tuple[str, ...]:
    axes = eqn.params.get("axes", None)
    if axes is None:
        axes = eqn.params.get("axis_name", None)
    if axes is None:
        return ()
    if isinstance(axes, (tuple, list)):
        return tuple(str(a) for a in axes)
    return (str(axes),)


def comms_cost(capture: ProgramCapture, dcn_axes=DEFAULT_DCN_AXES) -> dict:
    """Priced communication volume of one program: ICI vs DCN bytes.

    Each jaxpr collective is one entry: ``payload_bytes`` is the summed output
    aval size (inside shard_map bodies that is already the per-device block),
    ``priced_bytes`` applies the ring factor ``(n-1)/n`` over the product of
    the equation's mesh axis sizes for ICI, or the full payload for DCN. A
    1-sized (or unresolvable) axis prices to 0 — a collective over one device
    moves nothing. Host-level DCN payloads (MPMD stage transfers, KV page
    handoff programs) are appended as full-payload DCN entries."""
    dcn = frozenset(dcn_axes)
    mesh_shape = _capture_mesh_shape(capture)
    entries: List[dict] = []
    root = getattr(capture.jaxpr, "jaxpr", capture.jaxpr)
    if root is not None and hasattr(root, "eqns"):
        for jaxpr in _walk_jaxprs(root):
            for eqn in jaxpr.eqns:
                kind = _PRIM_KINDS.get(eqn.primitive.name)
                if kind is None:
                    continue
                payload = sum(
                    _aval_bytes(getattr(v, "aval", None)) for v in eqn.outvars
                )
                axes = _eqn_axes(eqn)
                axis_size = 1
                for a in axes:
                    axis_size *= int(mesh_shape.get(a, 1))
                fabric = "dcn" if any(a in dcn for a in axes) else "ici"
                if axis_size <= 1:
                    priced = 0
                elif fabric == "dcn":
                    priced = payload
                else:
                    priced = payload * (axis_size - 1) // axis_size
                entries.append({
                    "kind": kind,
                    "axes": list(axes),
                    "axis_size": axis_size,
                    "payload_bytes": int(payload),
                    "priced_bytes": int(priced),
                    "fabric": fabric,
                })
    st = stage_transfer_bytes(capture)
    if st:
        entries.append({
            "kind": "stage_transfer", "axes": [], "axis_size": 0,
            "payload_bytes": int(st), "priced_bytes": int(st), "fabric": "dcn",
        })
    if capture.label in _KV_HANDOFF_LABELS:
        out_avals = list(getattr(capture.jaxpr, "out_avals", []) or [])
        payload = sum(_aval_bytes(a) for a in out_avals)
        if payload:
            entries.append({
                "kind": "kv_page_handoff", "axes": [], "axis_size": 0,
                "payload_bytes": int(payload), "priced_bytes": int(payload),
                "fabric": "dcn",
            })
    return {
        "ici_bytes": sum(e["priced_bytes"] for e in entries if e["fabric"] == "ici"),
        "dcn_bytes": sum(e["priced_bytes"] for e in entries if e["fabric"] == "dcn"),
        "entries": entries,
    }


def program_memory_summary(
    capture: ProgramCapture, dcn_axes=DEFAULT_DCN_AXES
) -> dict:
    """The per-program block manifests/telemetry/bench rows stamp: the HBM
    estimate components plus the priced ICI/DCN communication totals."""
    est = estimate_program_memory(capture)
    comms = comms_cost(capture, dcn_axes=dcn_axes)
    est["ici_bytes"] = comms["ici_bytes"]
    est["dcn_bytes"] = comms["dcn_bytes"]
    return est


def program_estimates(
    captures: Sequence[ProgramCapture], dcn_axes=DEFAULT_DCN_AXES
) -> Dict[str, dict]:
    """label -> ``{peak_bytes, ici_bytes, dcn_bytes}``, worst case per label.

    Labels recur across geometry passes (the paged/disagg sweeps re-lower
    shared serving programs); the ratchet tracks the maximum — the number a
    chip must actually survive."""
    out: Dict[str, dict] = {}
    for c in captures:
        s = program_memory_summary(c, dcn_axes=dcn_axes)
        row = {
            "peak_bytes": s["peak_bytes"],
            "ici_bytes": s["ici_bytes"],
            "dcn_bytes": s["dcn_bytes"],
        }
        prev = out.get(c.label)
        if prev is None:
            out[c.label] = row
        else:
            out[c.label] = {k: max(prev[k], row[k]) for k in row}
    return out


# ------------------------------------------------------------------------- rules

class HbmBudgetRule(ProgramRule):
    id = "hbm-budget-exceeded"
    severity = "error"
    description = (
        "static per-device peak-HBM estimate exceeds the chip budget "
        "(chip_budget_bytes; default 16 GiB)"
    )

    def __init__(self, budget_bytes: int = DEFAULT_CHIP_BUDGET_BYTES):
        self.budget_bytes = int(budget_bytes)

    def check_program(self, prog: ProgramCapture) -> List[Finding]:
        est = estimate_program_memory(prog)
        peak = est["peak_bytes"]
        if peak <= self.budget_bytes:
            return []
        return [self.make(
            prog,
            f"estimated per-device peak {peak / (1 << 20):.1f} MiB exceeds the "
            f"chip budget {self.budget_bytes / (1 << 20):.1f} MiB "
            f"(args {est['args_bytes'] / (1 << 20):.1f} MiB + temps "
            f"{est['temp_peak_bytes'] / (1 << 20):.1f} MiB at 1/"
            f"{est['temp_division']} division) — shard, donate, or raise the "
            "budget with the reason the chip can take it",
            code="peak exceeds chip budget",
        )]


class ReplicatedOptimizerStateRule(ProgramRule):
    id = "replicated-optimizer-state"
    severity = "error"
    description = (
        "adamw moment (mu/nu) leaf fully replicated on a >1-device mesh — the "
        "ZeRO-1 target: optimizer state is the cheapest thing to shard"
    )

    #: Sharper than the generic >=1 MiB replicated-input rule: moments are
    #: pure overhead (never read by the forward pass), so even half-MiB leaves
    #: are worth flagging — while the smoke-preset test surface (largest moment
    #: 256 KiB) stays clean by construction.
    def __init__(self, min_bytes: int = 1 << 19):
        self.min_bytes = int(min_bytes)

    def check_program(self, prog: ProgramCapture) -> List[Finding]:
        import jax

        findings = []
        for path, leaf in flat_inputs(prog):
            if "opt_state" not in path:
                continue
            if "'mu'" not in path and "'nu'" not in path:
                continue
            if not isinstance(leaf, jax.Array):
                continue
            sharding = getattr(leaf, "sharding", None)
            if sharding is None:
                continue
            try:
                n_dev = len(sharding.device_set)
                replicated = sharding.is_fully_replicated
            except Exception:  # noqa: BLE001 - exotic sharding types
                continue
            nbytes = leaf.size * leaf.dtype.itemsize
            if n_dev > 1 and replicated and nbytes >= self.min_bytes:
                shape = "x".join(str(d) for d in leaf.shape)
                findings.append(self.make(
                    prog,
                    f"optimizer moment {path} ({leaf.dtype}[{shape}], "
                    f"{nbytes / (1 << 20):.2f} MiB) is fully replicated over "
                    f"{n_dev} devices — ZeRO-1 shards exactly this "
                    "(arXiv:2004.13336); shard the moment tree or suppress "
                    "with the reason it must stay replicated",
                    code=f"replicated moment {leaf.dtype}[{shape}] {path}",
                ))
        return findings


class DcnHotPathRule(ProgramRule):
    id = "dcn-on-hot-path"
    severity = "error"
    description = (
        "DCN-priced collective inside a per-step program — cross-slice traffic "
        "on the step critical path (host-level stage/page transfers excluded: "
        "those boundaries are the design)"
    )

    #: Programs that run every step: a DCN collective inside one is paid per
    #: step, unlike setup/handoff programs that run once per request or epoch.
    hot_globs = (
        "train_step.*", "eval_step", "serving.decode*", "serving.prefill*",
        "serving.spec_verify*", "mpmd.*",
    )

    def __init__(self, dcn_axes=DEFAULT_DCN_AXES):
        self.dcn_axes = frozenset(dcn_axes)

    def check_program(self, prog: ProgramCapture) -> List[Finding]:
        label = prog.label or ""
        if not any(fnmatch.fnmatch(label, g) for g in self.hot_globs):
            return []
        findings = []
        for e in comms_cost(prog, dcn_axes=self.dcn_axes)["entries"]:
            if e["fabric"] != "dcn" or e["priced_bytes"] <= 0:
                continue
            if e["kind"] in ("stage_transfer", "kv_page_handoff"):
                continue  # sanctioned host-level boundaries, outside the jit
            findings.append(self.make(
                prog,
                f"{e['kind']} over DCN axes {e['axes']} moves "
                f"{e['priced_bytes'] / (1 << 20):.2f} MiB per step inside a "
                "hot-path program — restructure so only activation/page "
                "boundaries cross slices, or suppress with the measured "
                "step-time cost",
                code=f"dcn {e['kind']} axes={','.join(e['axes'])}",
            ))
        return findings


def all_memory_rules(
    budget_bytes: Optional[int] = None, dcn_axes=None
) -> List[ProgramRule]:
    """Fresh memaudit rule instances (thresholds are caller-overridable)."""
    return [
        HbmBudgetRule(budget_bytes=budget_bytes or DEFAULT_CHIP_BUDGET_BYTES),
        ReplicatedOptimizerStateRule(),
        DcnHotPathRule(dcn_axes=dcn_axes if dcn_axes is not None
                       else DEFAULT_DCN_AXES),
    ]


def memory_rule_by_id(rule_id: str):
    for r in all_memory_rules():
        if r.id == rule_id:
            return r
    raise KeyError(f"unknown graftmem rule: {rule_id}")


def known_memaudit_rule_ids(rules=None) -> set:
    if rules is None:
        rules = all_memory_rules()
    return {r.id for r in rules} | {"bad-suppression", "mem-estimate-regressed"}


# ---------------------------------------------------------------------- ratchet

def load_estimates(path: str = MEM_BASELINE_FILE) -> Dict[str, dict]:
    """The ratcheted per-label estimate table from the graftmem baseline
    (empty when the file or the table is absent)."""
    import json

    if not os.path.isfile(path):
        return {}
    with open(path, "r", encoding="utf-8") as f:
        data = json.load(f)
    return dict(data.get("estimates", {}))


def estimate_drift_findings(
    current: Dict[str, dict],
    baseline: Dict[str, dict],
    band: float = DEFAULT_ESTIMATE_BAND,
) -> Tuple[List[Finding], List[str]]:
    """(findings, ratchet-down notices) of current estimates vs the baseline.

    A field grown beyond ``(1 + band)`` of its baselined value is a
    ``mem-estimate-regressed`` finding; one shrunk below ``(1 - band)`` (or a
    baselined label that vanished) is a notice to re-run ``--baseline`` so the
    ratchet tightens. Inside the band nothing fires — benign drift."""
    findings: List[Finding] = []
    notices: List[str] = []
    for label, base in sorted(baseline.items()):
        cur = current.get(label)
        if cur is None:
            notices.append(f"{label}: no longer lowered")
            continue
        for field in ("peak_bytes", "ici_bytes", "dcn_bytes"):
            b = int(base.get(field, 0))
            c = int(cur.get(field, 0))
            if c > b * (1 + band) and c - b > 1024:
                findings.append(Finding(
                    rule="mem-estimate-regressed",
                    severity="error",
                    path=f"program:{label}",
                    line=0,
                    message=(
                        f"{field} grew {b / (1 << 20):.2f} -> "
                        f"{c / (1 << 20):.2f} MiB ({(c / b - 1) * 100 if b else 100:.0f}%, "
                        f"band ±{band * 100:.0f}%) — justify and re-baseline "
                        "with `python -m accelerate_tpu memaudit --baseline`, "
                        "or fix the regression"
                    ),
                    code=f"{field} regressed",
                ))
            elif b and c < b * (1 - band):
                notices.append(
                    f"{label}: {field} shrank {b / (1 << 20):.2f} -> "
                    f"{c / (1 << 20):.2f} MiB"
                )
    return findings, notices


def memaudit_findings(
    captures: Sequence[ProgramCapture],
    rules=None,
    suppressions=MEM_SUPPRESSIONS,
    baseline_estimates: Optional[Dict[str, dict]] = None,
    band: float = DEFAULT_ESTIMATE_BAND,
    dcn_axes=DEFAULT_DCN_AXES,
) -> Tuple[List[Finding], list, List[str]]:
    """(findings, stale_suppressions, ratchet_notices) over captured programs.

    The memaudit analog of ``audit_findings``: rule findings plus estimate
    drift against a ratcheted baseline table, all through the declarative
    suppression machinery (unknown rule / missing reason entries become
    ``bad-suppression`` findings, unmatched entries are reported stale)."""
    if rules is None:
        rules = all_memory_rules(dcn_axes=dcn_axes)
    findings: List[Finding] = []
    for rule in rules:
        for prog in captures:
            findings.extend(rule.check_program(prog))
    notices: List[str] = []
    if baseline_estimates:
        drift, notices = estimate_drift_findings(
            program_estimates(captures, dcn_axes=dcn_axes),
            baseline_estimates, band=band,
        )
        findings.extend(drift)
    kept, errors, stale = apply_audit_suppressions(
        findings, suppressions, known_rules=known_memaudit_rule_ids(rules)
    )
    kept.extend(errors)
    kept.sort(key=lambda f: (f.path, f.rule, f.code, f.message))
    return kept, stale, notices


def run_memaudit(
    captures: Optional[Sequence[ProgramCapture]] = None,
    budget_bytes: Optional[int] = None,
    band: float = DEFAULT_ESTIMATE_BAND,
    dcn_axes=DEFAULT_DCN_AXES,
    baseline_estimates: Optional[Dict[str, dict]] = None,
    **geometry,
) -> Tuple[List[Finding], Dict[str, dict], list, List[str]]:
    """(findings, estimates, stale_suppressions, notices) for one config.

    With no ``captures``, lowers the full default audit surface (the same
    train/eval/serving/paged/disagg/MPMD enumeration graftaudit checks)."""
    if captures is None:
        from .lowering import capture_default_programs

        captures = capture_default_programs(**geometry)
    rules = all_memory_rules(budget_bytes=budget_bytes, dcn_axes=dcn_axes)
    findings, stale, notices = memaudit_findings(
        captures, rules=rules, baseline_estimates=baseline_estimates,
        band=band, dcn_axes=dcn_axes,
    )
    return findings, program_estimates(captures, dcn_axes=dcn_axes), stale, notices
