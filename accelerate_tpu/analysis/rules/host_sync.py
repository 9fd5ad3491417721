"""host-sync-in-hot-path: a device→host fetch inside a decode/train/serving loop.

Incident: ``bench.py``'s matmul-ceiling probe once fetched a 128 MB result inside
its timed region and recorded the fetch as the matmul time. The same shape hides in hot loops: ``np.asarray`` /
``jax.device_get`` / ``.item()`` / ``int(x[...])`` / ``block_until_ready`` on a jax
value stalls the dispatch pipeline once per iteration. ``llama.py``'s speculative
accept chain and ``generation.py``'s pass-timing helper are the two allow-listed
suppressions (each reads back a value the host genuinely needs per step)."""

from __future__ import annotations

import ast
import re

from ..astutil import dotted
from ..engine import FileUnit, Rule

#: Function names considered hot paths (decode/train/serving loops).
HOT_NAME = re.compile(r"(decode|generat|serv|train|stream|sampl|infer)", re.IGNORECASE)

SYNC_CALLS = frozenset(
    {
        "np.asarray",
        "numpy.asarray",
        "np.array",
        "numpy.array",
        "jax.device_get",
        "jax.block_until_ready",
    }
)
SYNC_METHODS = frozenset({"item", "block_until_ready"})

#: ``int(name.split("/")[1])`` subscripts a host string, not a device array.
_HOST_STR_METHODS = frozenset({"split", "rsplit", "partition", "rpartition", "groups", "findall"})

#: Packages whose internals ARE the sanctioned sync (same mechanism as the
#: ``fence`` name allowlist, by path): the telemetry package implements the fence
#: helpers themselves (1-element target, ~4-byte read-back; ``telemetry/timing.py``),
#: and the serving gateway's timing path (SLO timestamps around the engine's
#: streamed per-token reads — each already a sanctioned 4-byte fetch inside
#: ``serving.py``'s compiled-step machinery) sits directly in serve-named hot
#: loops by design. Everywhere else the rule still fires.
SANCTIONED_PATH_PREFIXES = (
    "accelerate_tpu/telemetry/",
    "accelerate_tpu/serving_gateway/",
)

#: Step-loop scopes for the wall-sleep check: gateway/router/fleet classes and
#: workload-replay functions are the code that must run on an injectable clock
#: (virtual-clock replays, serve-bench) — a ``time.sleep`` in one of their
#: loops stalls every replica the loop drives AND breaks virtual-time replay.
#: Scoped by content, not path, so it applies INSIDE the sanctioned prefixes
#: too (those were sanctioned for fence reads, not for blocking the loop).
_STEP_LOOP_CLASS = re.compile(r"(Gateway|Router|Fleet)")
_REPLAY_FN = re.compile(r"replay", re.IGNORECASE)


def _is_sanctioned_sync(name: str) -> bool:
    """Telemetry fence helpers, allowlisted by qualified name: ``fence(...)`` (the
    bare import), or any ``...telemetry.fence`` / ``...timing.fence`` qualification
    (``telemetry.fence(out)``, ``acc.telemetry.fence(out)``). Fenced timing built on
    these is correct by construction — instrumented hot loops need no suppressions."""
    parts = name.split(".")
    if parts[-1] != "fence":
        return False
    return len(parts) == 1 or "telemetry" in parts or "timing" in parts


def _is_host_string_subscript(sub: ast.Subscript) -> bool:
    base = sub.value
    return (
        isinstance(base, ast.Call)
        and isinstance(base.func, ast.Attribute)
        and base.func.attr in _HOST_STR_METHODS
    )


def _is_fenced_subscript(sub: ast.Subscript) -> bool:
    """``int(fence(x)[0])``: the value was already synced by the sanctioned fence —
    the subscript fetch is the ~4-byte post-fence read, not a hidden full-tree pull."""
    base = sub.value
    if not isinstance(base, ast.Call):
        return False
    name = dotted(base.func)
    return bool(name) and _is_sanctioned_sync(name)


class HostSyncRule(Rule):
    id = "host-sync-in-hot-path"
    severity = "warning"
    description = (
        "host-device sync (np.asarray/device_get/.item()/block_until_ready) "
        "in a hot loop, or wall time.sleep in a gateway/fleet/replay step loop"
    )

    def check_file(self, unit: FileUnit):
        if unit.is_test:  # test scripts fetch values to assert on them — that's the point
            return []
        # The wall-sleep check runs unconditionally — the sanctioned prefixes
        # below cover fence-style reads, not blocking a serving/replay loop.
        findings = list(self._scan_wall_sleep(unit))
        if unit.path.startswith(SANCTIONED_PATH_PREFIXES):
            return findings  # sanctioned timing internals (see SANCTIONED_PATH_PREFIXES)
        for fn in ast.walk(unit.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not HOT_NAME.search(fn.name):
                continue
            findings.extend(self._scan_hot_function(unit, fn))
        # A function can be nested in a hot function; dedupe by line+message.
        uniq = {}
        for f in findings:
            uniq[(f.line, f.message)] = f
        return [uniq[k] for k in sorted(uniq)]

    def _scan_wall_sleep(self, unit: FileUnit):
        """``time.sleep`` inside a loop of a gateway/router/fleet class or a
        replay-named function: a step loop that blocks on the wall clock
        stalls every request/replica it drives, and a virtual-clock replay of
        the same loop deadlocks (virtual time never advances while the host
        sleeps). Wait on the injected ``sleep``/``clock``
        (``telemetry.clocks``) or turn the wait into a schedule the caller
        polls (``FleetSupervisor.restart_at``)."""
        scopes = []
        for node in ast.walk(unit.tree):
            if isinstance(node, ast.ClassDef) and _STEP_LOOP_CLASS.search(node.name):
                scopes.append((node.name, node))
            elif isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef)
            ) and _REPLAY_FN.search(node.name):
                scopes.append((node.name, node))
        findings = {}
        for scope_name, scope in scopes:
            for call in self._loop_calls(scope):
                if dotted(call.func) == "time.sleep":
                    f = self.make(
                        unit,
                        call,
                        f"wall 'time.sleep' in a step loop of '{scope_name}' — "
                        "blocks the serving/replay loop and deadlocks "
                        "virtual-clock replays; use the injected sleep "
                        "(telemetry.clocks) or a restart_at-style schedule",
                    )
                    findings[(f.line, f.message)] = f
        return [findings[k] for k in sorted(findings)]

    def _loop_calls(self, root: ast.AST):
        """Every Call node lexically inside a loop under ``root``."""
        out = []

        def visit(node: ast.AST, in_loop: bool):
            for child in ast.iter_child_nodes(node):
                inside = in_loop or isinstance(
                    child, (ast.For, ast.AsyncFor, ast.While)
                )
                if inside and isinstance(child, ast.Call):
                    out.append(child)
                visit(child, inside)

        visit(root, False)
        return out

    def _scan_hot_function(self, unit: FileUnit, fn: ast.AST):
        findings = []

        def visit(node: ast.AST, in_loop: bool, in_nested_def: bool):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.For, ast.AsyncFor, ast.While)):
                    visit(child, True, in_nested_def)
                elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)) and child is not fn:
                    # A helper defined inside a hot function is (almost always)
                    # called from its loop — generation.py's per-pass ``timed``.
                    visit(child, in_loop, True)
                else:
                    if (in_loop or in_nested_def) and isinstance(child, ast.Call):
                        f = self._check_call(unit, fn.name, child)
                        if f is not None:
                            findings.append(f)
                    visit(child, in_loop, in_nested_def)

        visit(fn, False, False)
        return findings

    def _check_call(self, unit: FileUnit, fn_name: str, call: ast.Call):
        name = dotted(call.func)
        where = f"in hot path '{fn_name}'"
        if name in SYNC_CALLS:
            return self.make(
                unit,
                call,
                f"'{name}' {where} forces a device→host sync each iteration — "
                "keep the value on device or hoist the fetch out of the loop",
            )
        if (
            isinstance(call.func, ast.Attribute)
            and call.func.attr in SYNC_METHODS
            and not call.args
        ):
            return self.make(
                unit,
                call,
                f"'.{call.func.attr}()' {where} forces a device→host sync each iteration",
            )
        if (
            isinstance(call.func, ast.Name)
            and call.func.id == "int"
            and len(call.args) == 1
            and isinstance(call.args[0], ast.Subscript)
            and not _is_host_string_subscript(call.args[0])
            and not _is_fenced_subscript(call.args[0])
        ):
            return self.make(
                unit,
                call,
                f"'int(...[...])' {where} materializes a device value on host each "
                "iteration — keep the index as a traced array",
            )
        return None
