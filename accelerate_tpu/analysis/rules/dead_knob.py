"""dead-knob: a config field that is only ever *defined* is worse than an error.

Incident: "dead/misleading plugin knobs" — a dataclass field the
user sets and the package silently ignores. ``tests/test_no_dead_knobs.py`` guarded
five hardcoded config classes with a regex grep; this rule is the generalization: every
``@dataclass`` in the linted non-test sources, checked against every attribute access
(and ``getattr``/``hasattr`` string literal) in the whole linted file set. A field
nobody reads must be wired, deleted, or suppressed with a reason on its own line."""

from __future__ import annotations

import ast

from ..astutil import dataclass_fields, dotted, is_dataclass_def
from ..engine import FileUnit, Rule

#: getattr/hasattr/setattr-style consumption via a string literal field name.
_GETATTR_FNS = frozenset({"getattr", "hasattr", "setattr", "delattr"})
#: dataclasses.replace(cfg, field=...) keyword use also proves the field is live.
_REPLACE_FNS = frozenset({"replace", "dataclasses.replace"})


class DeadKnobRule(Rule):
    id = "dead-knob"
    severity = "error"
    description = "dataclass field defined but never read anywhere in the linted sources"

    def finalize(self, units):
        consumed = set()
        for unit in units:
            for node in ast.walk(unit.tree):
                if isinstance(node, ast.Attribute):
                    consumed.add(node.attr)
                elif isinstance(node, ast.Call):
                    name = dotted(node.func)
                    if name in _GETATTR_FNS and len(node.args) >= 2:
                        a = node.args[1]
                        if isinstance(a, ast.Constant) and isinstance(a.value, str):
                            consumed.add(a.value)
                    elif name in _REPLACE_FNS:
                        for kw in node.keywords:
                            if kw.arg:
                                consumed.add(kw.arg)

        findings = []
        for unit in units:
            if unit.is_test:
                continue
            for node in ast.walk(unit.tree):
                if not (isinstance(node, ast.ClassDef) and is_dataclass_def(node)):
                    continue
                for fname, stmt in dataclass_fields(node):
                    if fname.startswith("_") or fname in consumed:
                        continue
                    findings.append(
                        self.make(
                            unit,
                            stmt,
                            f"{node.name} field '{fname}' defined but never read anywhere "
                            "in the linted sources — wire it or delete it (an "
                            "accepted-but-ignored flag is worse than an error)",
                        )
                    )
        return findings
