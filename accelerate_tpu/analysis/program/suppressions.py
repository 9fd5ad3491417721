"""Declarative suppressions for program-level findings.

graftlint suppressions live as comments on the offending source line; a
program finding has no source line — it lives in a traced artifact. So audit
suppressions are declared HERE, in one reviewed table, with the same contract
as the comment form: the rule id must exist, the reason is mandatory, and an
entry that stops matching anything is reported stale (the ratchet direction —
suppressions only shrink).

Match semantics: ``program`` is an ``fnmatch`` glob over the program label
(``train_step.*``, ``serving.decode_multi``); ``match`` is a substring of the
finding's stable ``code`` string ("" matches any finding of that rule in that
program).
"""

from __future__ import annotations

import dataclasses
import fnmatch
from typing import Iterable, List, Sequence, Tuple

from ..engine import Finding

__all__ = ["AuditSuppression", "SUPPRESSIONS", "MEM_SUPPRESSIONS",
           "apply_audit_suppressions"]


@dataclasses.dataclass(frozen=True)
class AuditSuppression:
    rule: str
    program: str  # fnmatch glob over the program label
    match: str    # substring of Finding.code ("" = any)
    reason: str

    def covers(self, f: Finding) -> bool:
        label = f.path[len("program:"):] if f.path.startswith("program:") else f.path
        return (
            f.rule == self.rule
            and fnmatch.fnmatch(label, self.program)
            and (self.match in f.code)
        )


#: The reviewed suppression table. Every entry needs a reason a reviewer can
#: check; delete entries the moment the underlying finding is fixed (stale
#: entries are themselves reported).
SUPPRESSIONS: Tuple[AuditSuppression, ...] = (
)

#: graftmem's table, separate because the tiers have different rule-id sets
#: (an entry naming an audit rule would be flagged unknown by the memaudit
#: validator, and vice versa). Same contract, same stale reporting.
MEM_SUPPRESSIONS: Tuple[AuditSuppression, ...] = (
)


def apply_audit_suppressions(
    findings: Iterable[Finding],
    suppressions: Sequence[AuditSuppression] = SUPPRESSIONS,
    known_rules: Sequence[str] = (),
) -> Tuple[List[Finding], List[Finding], List[AuditSuppression]]:
    """(kept, errors, stale) — drop suppressed findings, validate the table.

    ``errors`` are ``bad-suppression`` findings for entries naming an unknown
    rule or carrying no reason (mirrors the engine's comment-suppression
    validation). ``stale`` lists entries that matched nothing this run.
    """
    known = set(known_rules)
    errors: List[Finding] = []
    usable: List[AuditSuppression] = []
    for s in suppressions:
        if known and s.rule not in known:
            from ..engine import format_rule_catalog

            errors.append(Finding(
                rule="bad-suppression",
                severity="error",
                path="analysis/program/suppressions.py",
                line=0,
                message=f"audit suppression names unknown rule '{s.rule}' "
                f"(known here: {', '.join(sorted(known))}; "
                f"all tiers — {format_rule_catalog()})",
                code=f"suppression {s.rule}:{s.program}:{s.match}",
            ))
        elif not s.reason.strip():
            errors.append(Finding(
                rule="bad-suppression",
                severity="error",
                path="analysis/program/suppressions.py",
                line=0,
                message=f"audit suppression for '{s.rule}' on '{s.program}' has "
                "no reason — write why the finding is safe",
                code=f"suppression {s.rule}:{s.program}:{s.match}",
            ))
        else:
            usable.append(s)

    kept: List[Finding] = []
    used = set()
    for f in findings:
        hit = next((s for s in usable if s.covers(f)), None)
        if hit is None:
            kept.append(f)
        else:
            used.add(hit)
    stale = [s for s in usable if s not in used]
    return kept, errors, stale
