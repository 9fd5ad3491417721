"""graftlint engine: file loading, rule driving, suppression handling.

Design constraints (ISSUE 1 tentpole):

- **No runtime import of analyzed modules.** Everything here is stdlib ``ast`` over
  source text; the linter runs on a laptop without jax or a TPU.
- **Findings are stable baseline keys.** A finding is keyed by
  ``(rule, path, stripped source line)`` — not the line *number* — so unrelated edits
  that shift code don't churn ``graftlint_baseline.json`` (see ``baseline.py``).
- **Suppressions carry reasons.** ``# graftlint: disable=<rule>(<reason>)`` on the
  finding's line (or on a comment-only line directly above it). A suppression with an
  unknown rule id, or with no reason, is itself a finding (``bad-suppression``) — an
  unexplained silence is the accepted-but-ignored-knob bug all over again.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Iterable, List, Optional, Sequence

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: What ``run_lint`` covers when no explicit paths are given (mirrors
#: tests/test_lint_clean.py — the tier-1 gate).
DEFAULT_PATHS = ("accelerate_tpu", "benchmarks", "bench.py", "chip_smoke.py")

SEVERITIES = ("error", "warning")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation at a source location."""

    rule: str
    severity: str
    path: str  # repo-relative, posix separators
    line: int  # 1-based
    message: str
    code: str = ""  # stripped source line — the stable part of the baseline key

    def key(self):
        """Baseline identity: survives line-number churn, dies with the code line."""
        return (self.rule, self.path, self.code)

    def format(self) -> str:
        return f"{self.path}:{self.line}: {self.severity}: [{self.rule}] {self.message}"


@dataclasses.dataclass
class FileUnit:
    """One parsed source file handed to every rule."""

    path: str  # repo-relative
    abspath: str
    source: str
    tree: ast.AST
    lines: List[str]  # source split per line, 0-based
    is_test: bool  # tests/, test_utils/, conftest — library-only rules skip these

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""


class Rule:
    """Base class: subclasses set ``id``/``severity``/``description`` and override
    ``check_file`` (per-file) and/or ``finalize`` (whole-project, e.g. dead-knob)."""

    id = ""
    severity = "error"
    description = ""

    def check_file(self, unit: FileUnit) -> Iterable[Finding]:
        return ()

    def finalize(self, units: Sequence[FileUnit]) -> Iterable[Finding]:
        return ()

    def make(self, unit: FileUnit, node_or_line, message: str) -> Finding:
        line = getattr(node_or_line, "lineno", node_or_line)
        return Finding(
            rule=self.id,
            severity=self.severity,
            path=unit.path,
            line=line,
            message=message,
            code=unit.line_text(line),
        )


# --------------------------------------------------------------------- suppressions

#: Both AST tiers share one suppression grammar: ``# graftlint: disable=...``
#: and ``# graftflow: disable=...`` parse identically (each tier validates
#: against the union of both tiers' rule ids, so a flow suppression is never
#: a lint ``bad-suppression`` and vice versa).
_SUPPRESS_RE = re.compile(r"#\s*graft(?:lint|flow):\s*disable=(.*)$")
_ITEM_RE = re.compile(r"\s*([A-Za-z][\w-]*)\s*(?:\(([^()]*)\))?\s*(?:,|$)")


@dataclasses.dataclass(frozen=True)
class Suppression:
    rule: str
    reason: str
    line: int
    whole_line: bool  # comment-only line: also covers the next source line


def _iter_items(text: str):
    """``rule-a(reason a), rule-b(reason b)`` → pairs; stops at the first non-item."""
    pos = 0
    while pos < len(text):
        m = _ITEM_RE.match(text, pos)
        if not m:
            break
        yield m.group(1), (m.group(2) or "").strip()
        pos = m.end()


def parse_suppressions(unit: FileUnit) -> List[Suppression]:
    """Real COMMENT tokens only — the syntax quoted in a docstring is not a suppression."""
    import io
    import tokenize

    out = []
    try:
        tokens = list(tokenize.generate_tokens(io.StringIO(unit.source).readline))
    except (tokenize.TokenError, IndentationError):  # ast already parsed it; belt & braces
        return out
    for tok in tokens:
        if tok.type != tokenize.COMMENT:
            continue
        m = _SUPPRESS_RE.search(tok.string)
        if not m:
            continue
        lineno = tok.start[0]
        whole = unit.line_text(lineno).startswith("#")
        for rule, reason in _iter_items(m.group(1)):
            out.append(
                Suppression(rule=rule, reason=reason, line=lineno, whole_line=whole)
            )
    return out


def _suppression_errors(unit: FileUnit, sups: List[Suppression], known: set) -> List[Finding]:
    errs = []
    for s in sups:
        if s.rule not in known:
            errs.append(
                Finding(
                    rule="bad-suppression",
                    severity="error",
                    path=unit.path,
                    line=s.line,
                    message=f"suppression names unknown rule '{s.rule}' "
                    f"({format_rule_catalog()})",
                    code=unit.line_text(s.line),
                )
            )
        elif not s.reason:
            errs.append(
                Finding(
                    rule="bad-suppression",
                    severity="error",
                    path=unit.path,
                    line=s.line,
                    message=f"suppression for '{s.rule}' has no reason — write "
                    f"# graftlint: disable={s.rule}(<why this is safe>)",
                    code=unit.line_text(s.line),
                )
            )
    return errs


def _is_suppressed(f: Finding, by_line: dict) -> bool:
    for s in by_line.get(f.line, ()):
        if s.rule == f.rule and s.reason:
            return True
    # A comment-only suppression line covers the next source line.
    for s in by_line.get(f.line - 1, ()):
        if s.whole_line and s.rule == f.rule and s.reason:
            return True
    return False


# ------------------------------------------------------------------------- loading


def _is_test_path(relpath: str) -> bool:
    parts = relpath.split("/")
    base = parts[-1]
    return (
        "tests" in parts
        or "test_utils" in parts
        or base.startswith("test_")
        or base == "conftest.py"
    )


def iter_py_files(paths: Sequence[str], root: str = REPO_ROOT):
    """Yield absolute paths of .py files under ``paths`` (files or directories).

    A nonexistent path raises: a typo'd CI target must fail loudly, not report a
    clean lint of zero files forever.
    """
    for p in paths:
        ap = p if os.path.isabs(p) else os.path.join(root, p)
        if not os.path.exists(ap):
            raise FileNotFoundError(f"graftlint: no such lint path: {p} (resolved {ap})")
        if os.path.isfile(ap):
            yield ap
        else:
            for dirpath, dirnames, filenames in os.walk(ap):
                dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        yield os.path.join(dirpath, fn)


def load_unit(abspath: str, root: str = REPO_ROOT):
    """Parse one file into a FileUnit, or a parse-error Finding."""
    rel = os.path.relpath(abspath, root).replace(os.sep, "/")
    with open(abspath, "r", encoding="utf-8") as f:
        source = f.read()
    try:
        tree = ast.parse(source, filename=rel)
    except SyntaxError as e:
        return Finding(
            rule="parse-error",
            severity="error",
            path=rel,
            line=e.lineno or 1,
            message=f"cannot parse: {e.msg}",
        )
    return FileUnit(
        path=rel,
        abspath=abspath,
        source=source,
        tree=tree,
        lines=source.splitlines(),
        is_test=_is_test_path(rel),
    )


def collect_units(paths: Sequence[str] = DEFAULT_PATHS, root: str = REPO_ROOT):
    """(units, parse_error_findings) over every .py file under ``paths``."""
    units, errors = [], []
    for ap in iter_py_files(paths, root):
        got = load_unit(ap, root)
        if isinstance(got, Finding):
            errors.append(got)
        else:
            units.append(got)
    return units, errors


# ------------------------------------------------------------------------- driving


def run_lint(
    paths: Sequence[str] = DEFAULT_PATHS,
    root: str = REPO_ROOT,
    rules: Optional[Sequence[Rule]] = None,
) -> List[Finding]:
    """Run ``rules`` (default: the full registry) over ``paths``; return surviving findings.

    Suppressed findings are dropped; malformed suppressions surface as
    ``bad-suppression`` findings. Output is sorted by (path, line, rule).
    """
    if rules is None:
        from .rules import all_rules

        rules = all_rules()
    units, findings = collect_units(paths, root)

    for rule in rules:
        for unit in units:
            for f in rule.check_file(unit):
                findings.append(f)
        for f in rule.finalize(units):
            findings.append(f)

    # Validate suppressions against the FULL registry, not the subset being run —
    # running only dead-knob must not flag a host-sync suppression as unknown.
    known = known_rule_ids()
    kept = []
    sups_by_path = {u.path: parse_suppressions(u) for u in units}
    for unit in units:
        findings.extend(_suppression_errors(unit, sups_by_path[unit.path], known))
    by_unit = {}
    for f in findings:
        by_unit.setdefault(f.path, []).append(f)
    unit_by_path = {u.path: u for u in units}
    for path, fs in by_unit.items():
        unit = unit_by_path.get(path)
        if unit is None:  # parse errors have no unit — keep as-is
            kept.extend(fs)
            continue
        by_line = {}
        for s in sups_by_path[unit.path]:
            by_line.setdefault(s.line, []).append(s)
        for f in fs:
            if f.rule != "bad-suppression" and _is_suppressed(f, by_line):
                continue
            if not f.code:
                f = dataclasses.replace(f, code=unit.line_text(f.line))
            kept.append(f)
    kept.sort(key=lambda f: (f.path, f.line, f.rule, f.message))
    return kept


def known_rule_ids(rules: Optional[Sequence[Rule]] = None) -> set:
    """Every id a suppression comment may legally name: the graftlint registry,
    the graftflow registry (the two tiers share one comment grammar, so each
    must recognize the other's ids), plus the engine-level ids."""
    if rules is None:
        from .rules import all_rules

        rules = all_rules()
    from .flow import flow_rules

    return (
        {r.id for r in rules}
        | {r.id for r in flow_rules()}
        | {"parse-error", "bad-suppression"}
    )


def rule_catalog() -> dict:
    """tier name → sorted rule ids, across all four analysis tiers.

    Stdlib-only by construction, so error messages anywhere in the stack can
    point a misdirected suppression at the tier that owns the rule. The
    program-tier registries (``program/rules.py``, ``program/memory.py``) are
    themselves stdlib modules, but ``program/__init__`` imports jax via
    ``.lowering`` — so when the package isn't already loaded, a stub package
    (same trick as ``graftlint.py``'s repo-root stub) lets the registry
    modules import without executing that ``__init__``.
    """
    import sys
    import types

    from .flow import flow_rules
    from .rules import all_rules

    pkg = __package__ + ".program"
    stubbed = pkg not in sys.modules
    if stubbed:
        stub = types.ModuleType(pkg)
        stub.__path__ = [os.path.join(os.path.dirname(__file__), "program")]
        sys.modules[pkg] = stub
    try:
        from .program.memory import all_memory_rules
        from .program.rules import all_program_rules
    finally:
        if stubbed:
            # Drop the stub so a later real `import ...program` still runs the
            # package __init__ (the cached registry submodules stay valid).
            sys.modules.pop(pkg, None)

    return {
        "graftlint": sorted(
            {r.id for r in all_rules()} | {"parse-error", "bad-suppression"}
        ),
        "graftflow": sorted(r.id for r in flow_rules()),
        "graftaudit": sorted(r.id for r in all_program_rules()),
        "graftmem": sorted(r.id for r in all_memory_rules()),
    }


def format_rule_catalog() -> str:
    """One-line ``tier: id, id, ...; tier: ...`` listing for error messages."""
    return "; ".join(
        f"{tier}: {', '.join(ids)}" for tier, ids in rule_catalog().items()
    )
