"""jit-impurity: host side effects inside jit-traced code run at TRACE time, not step time.

Incident: the bench probe classes — a ``time.time()`` or ``print``
inside a jitted step executes once during tracing and never again, so the "measurement"
measures compilation, and an ``np.random`` call bakes one constant sample into the
compiled graph. Flags impure calls and ``global`` mutation inside functions that are
jit-decorated, wrapped via ``name = jax.jit(fn, ...)``, or constructed inside a
``build_*step`` builder (the ``accelerator.build_train_step`` pattern)."""

from __future__ import annotations

import ast
import re

from ..astutil import decorator_jit_kwargs, dotted, jit_wrap_info
from ..engine import FileUnit, Rule

#: Exact call names that are host side effects (traced once, silently wrong).
IMPURE_CALLS = frozenset(
    {
        "time.time",
        "time.perf_counter",
        "time.monotonic",
        "time.process_time",
        "time.sleep",
        "print",
        "input",
        "breakpoint",
        "datetime.datetime.now",
        "datetime.datetime.utcnow",
    }
)
#: Prefix matches: the whole host-RNG namespaces (jax.random is fine — it's traced).
IMPURE_PREFIXES = ("np.random.", "numpy.random.", "random.")

_BUILDER_NAME = re.compile(r"^build_\w*step\w*$")


class JitImpurityRule(Rule):
    id = "jit-impurity"
    severity = "error"
    description = (
        "host side effect (time/print/np.random/global mutation) inside a jit-traced function"
    )

    def check_file(self, unit: FileUnit):
        jit_assigned = _jit_assigned_names(unit.tree)
        findings = []
        seen = set()

        def scan_context(fn: ast.AST, ctx_name: str):
            for node in ast.walk(fn):
                if isinstance(node, ast.Global):
                    key = (node.lineno, "global")
                    if key not in seen:
                        seen.add(key)
                        findings.append(
                            self.make(
                                unit,
                                node,
                                f"'global {', '.join(node.names)}' inside jit-traced "
                                f"'{ctx_name}' — mutation happens at trace time only",
                            )
                        )
                elif isinstance(node, ast.Call):
                    name = dotted(node.func)
                    if name and (
                        name in IMPURE_CALLS or name.startswith(IMPURE_PREFIXES)
                    ):
                        key = (node.lineno, name)
                        if key not in seen:
                            seen.add(key)
                            findings.append(
                                self.make(
                                    unit,
                                    node,
                                    f"impure call '{name}' inside jit-traced '{ctx_name}' — "
                                    "runs once at trace time, not per step",
                                )
                            )

        def visit(node: ast.AST, parent_is_builder: bool):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    is_ctx = (
                        any(
                            decorator_jit_kwargs(d) is not None
                            for d in child.decorator_list
                        )
                        or child.name in jit_assigned
                        or parent_is_builder
                    )
                    if is_ctx:
                        scan_context(child, child.name)
                        # Everything under a traced function is traced; no need to
                        # recurse for more context roots.
                        continue
                    visit(child, _BUILDER_NAME.match(child.name) is not None)
                else:
                    visit(child, parent_is_builder)

        visit(unit.tree, False)
        return findings


def _jit_assigned_names(tree: ast.AST) -> set:
    """Function names wrapped via ``anything = jax.jit(fn, ...)`` in this module."""
    wrapped = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            info = jit_wrap_info(node)
            if info and isinstance(info["fn"], ast.Name):
                wrapped.add(info["fn"].id)
    return wrapped
