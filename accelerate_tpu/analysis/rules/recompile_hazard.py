"""recompile-hazard: static args that defeat the jit cache (or crash it).

Incident: every jit cache miss costs seconds of XLA compile; a static arg bound to a value that varies per call recompiles on *every*
step, and an unhashable static (list/dict/set) is a ``TypeError`` at the first call.
Four checks, all within one module:

1. a static parameter (``static_argnames`` OR ``static_argnums``) receiving a
   list/dict/set (or comprehension) at a call site;
2. a static parameter bound to the induction variable of an enclosing loop —
   a guaranteed recompile per iteration;
3. ``static_argnames`` naming a parameter the wrapped function doesn't have
   (silently ignored by jax < 0.4.27, TypeError after — dead knob either way);
4. ``jax.jit`` (or ``partial(jax.jit, ...)``) constructed inside a loop body — the
   serving/per-request incident shape: each iteration builds a FRESH jit wrapper
   with an empty cache, so every request re-pays trace + XLA compile. Hoist the
   jit to module/init scope. A ``for`` loop's iterator expression evaluates once
   and is exempt; a decorated ``def`` inside a loop body re-runs its decorators
   per iteration and is not; nested ``def`` bodies delay execution and reset the
   context (the def may be a factory called once)."""

from __future__ import annotations

import ast

from ..astutil import (
    JIT_NAMES,
    PARTIAL_NAMES,
    const_int_seq,
    const_str_seq,
    decorator_jit_kwargs,
    dotted,
    func_all_param_names,
    func_param_names,
    jit_wrap_info,
)
from ..engine import FileUnit, Rule

_UNHASHABLE = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp, ast.SetComp)


class RecompileHazardRule(Rule):
    id = "recompile-hazard"
    severity = "error"
    description = "per-call-varying or unhashable value bound to a jit static argument"

    def check_file(self, unit: FileUnit):
        findings = []
        # jitted name -> {"static_names": [...], "static_nums": [...],
        #                 "params": [...] or None}
        jitted = {}

        for node in ast.walk(unit.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    kw = decorator_jit_kwargs(dec)
                    if kw is None:
                        continue
                    statics = const_str_seq(kw.get("static_argnames"))
                    nums = const_int_seq(kw.get("static_argnums"))
                    params = func_param_names(node)
                    # Positional statics resolve to their parameter names so call
                    # sites passing them by keyword are checked too.
                    for i in nums:
                        if 0 <= i < len(params) and params[i] not in statics:
                            statics = statics + [params[i]]
                    jitted[node.name] = {
                        "static_names": statics, "static_nums": nums, "params": params,
                    }
                    all_params = func_all_param_names(node)
                    for s in const_str_seq(kw.get("static_argnames")):
                        if s not in all_params:
                            findings.append(
                                self.make(
                                    unit,
                                    node,
                                    f"static_argnames names '{s}' but '{node.name}' has no "
                                    "such parameter — the static marking is a dead knob",
                                )
                            )
            elif isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                info = jit_wrap_info(node.value)
                if info is None:
                    continue
                statics = const_str_seq(info["kwargs"].get("static_argnames"))
                nums = const_int_seq(info["kwargs"].get("static_argnums"))
                if not statics and not nums:
                    continue
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        jitted[t.id] = {
                            "static_names": statics, "static_nums": nums, "params": None,
                        }

        findings.extend(self._scan_jit_in_loops(unit))
        if jitted:
            findings.extend(self._scan_call_sites(unit, jitted))
        return findings

    def _scan_jit_in_loops(self, unit: FileUnit):
        """Check 4: a ``jax.jit``/``partial(jax.jit, ...)`` CALL that RUNS once per
        loop iteration builds a fresh wrapper (empty jit cache) every time — the
        per-request serving recompile incident. Per-iteration regions: loop bodies,
        ``while`` tests, decorators of defs inside loops. Once-only regions: a
        ``for``'s iterator/target expressions, nested def/lambda bodies (the def
        may be a factory called once)."""
        findings = []

        def is_jit_construction(call: ast.Call) -> bool:
            if dotted(call.func) in JIT_NAMES:
                return True
            # partial(jax.jit, ...) — the codebase's decorator spelling, but as a
            # plain call it constructs a jit wrapper just the same.
            return (
                dotted(call.func) in PARTIAL_NAMES
                and bool(call.args)
                and dotted(call.args[0]) in JIT_NAMES
            )

        def visit(node: ast.AST, in_loop: bool):
            if in_loop and isinstance(node, ast.Call) and is_jit_construction(node):
                findings.append(
                    self.make(
                        unit,
                        node,
                        "jax.jit constructed inside a loop body — every iteration "
                        "(request) builds a fresh wrapper with an EMPTY jit cache, "
                        "re-paying trace + XLA compile; hoist the jit out of the "
                        "loop (module scope or engine __init__)",
                    )
                )
            if isinstance(node, (ast.For, ast.AsyncFor)):
                # Iterator and target evaluate ONCE, and the else clause runs at
                # most once after normal completion; only the body re-runs.
                visit(node.target, in_loop)
                visit(node.iter, in_loop)
                for stmt in node.body:
                    visit(stmt, True)
                for stmt in node.orelse:
                    visit(stmt, in_loop)
                return
            if isinstance(node, ast.While):
                visit(node.test, True)  # the test re-evaluates every iteration
                for stmt in node.body:
                    visit(stmt, True)
                for stmt in node.orelse:  # at most once, on normal completion
                    visit(stmt, in_loop)
                return
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # Decorators and argument defaults run AT DEF TIME — per iteration
                # when the def sits in a loop; the body only when called.
                for dec in node.decorator_list:
                    if in_loop and not isinstance(dec, ast.Call) and dotted(dec) in JIT_NAMES:
                        # Bare `@jax.jit` has no Call node for the generic walk to
                        # catch, but applying it still constructs a fresh wrapper
                        # per iteration.
                        findings.append(
                            self.make(
                                unit,
                                dec,
                                "jax.jit constructed inside a loop body — every "
                                "iteration (request) builds a fresh wrapper with an "
                                "EMPTY jit cache, re-paying trace + XLA compile; "
                                "hoist the jit out of the loop (module scope or "
                                "engine __init__)",
                            )
                        )
                    visit(dec, in_loop)
                visit(node.args, in_loop)
                for stmt in node.body:
                    visit(stmt, False)
                return
            if isinstance(node, ast.Lambda):
                visit(node.args, in_loop)
                visit(node.body, False)
                return
            for child in ast.iter_child_nodes(node):
                visit(child, in_loop)

        visit(unit.tree, False)
        return findings

    def _scan_call_sites(self, unit: FileUnit, jitted: dict):
        findings = []

        def visit(node: ast.AST, loop_vars: frozenset):
            for child in ast.iter_child_nodes(node):
                child_loops = loop_vars
                if isinstance(child, (ast.For, ast.AsyncFor)):
                    new = set()
                    for n in ast.walk(child.target):
                        if isinstance(n, ast.Name):
                            new.add(n.id)
                    child_loops = loop_vars | frozenset(new)
                if isinstance(child, ast.Call) and isinstance(child.func, ast.Name):
                    spec = jitted.get(child.func.id)
                    if spec:
                        findings.extend(
                            self._check_site(unit, child, child.func.id, spec, loop_vars)
                        )
                visit(child, child_loops)

        visit(unit.tree, frozenset())
        return findings

    def _check_site(self, unit: FileUnit, call: ast.Call, name: str, spec, loop_vars):
        bound = {}
        for kw in call.keywords:
            if kw.arg in spec["static_names"]:
                bound[kw.arg] = kw.value
        if spec["params"]:
            for i, arg in enumerate(call.args):
                if i < len(spec["params"]) and spec["params"][i] in spec["static_names"]:
                    bound[spec["params"][i]] = arg
        else:
            # Assignment-form jit (no wrapped-function AST): static_argnums positions
            # are all we know — check the positional args at those indices.
            for i in spec.get("static_nums") or ():
                if 0 <= i < len(call.args):
                    bound[f"argnum {i}"] = call.args[i]
        for pname, value in bound.items():
            if isinstance(value, _UNHASHABLE):
                yield self.make(
                    unit,
                    call,
                    f"unhashable {type(value).__name__.lower()} passed to static arg "
                    f"'{pname}' of jitted '{name}' — TypeError at call time; pass a tuple "
                    "or mark the arg non-static",
                )
            elif isinstance(value, ast.Name) and value.id in loop_vars:
                yield self.make(
                    unit,
                    call,
                    f"static arg '{pname}' of jitted '{name}' bound to loop variable "
                    f"'{value.id}' — recompiles every iteration",
                )
