"""Program capture: the lowered-program records every graftaudit rule reads.

graftlint (the AST tier) sees Python source; this tier sees the *traced
program* — the jaxpr and StableHLO that XLA actually receives. A
:class:`ProgramCapture` is one warmed call signature of one program label
(``train_step.fused``, ``serving.decode_multi`` …) with everything a rule needs:

- the ``jax.stages.Lowered`` object and its StableHLO text,
- the closed jaxpr (via ``jitted.trace``; ``None`` on jax builds without it),
- the concrete call ``(args, kwargs)`` — real mesh-placed arrays, so input
  shardings are inspectable without executing anything,
- every warning raised during tracing/lowering (jax reports unusable buffer
  donation here and nowhere else).

Captures are produced by :func:`capture_lowering`, which
``compile_cache.AotCache._lower`` calls whenever a cache has its ``capture``
list armed — so the SAME enumeration that warms the AOT cache
(``compile_cache/warmup.py``) feeds the auditor, and the fingerprints audited
are exactly the fingerprints served.
"""

from __future__ import annotations

import dataclasses
import re
import warnings as _warnings
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["ProgramCapture", "capture_lowering", "flat_inputs", "main_arg_attributes"]


@dataclasses.dataclass
class ProgramCapture:
    """One lowered call signature of one program, plus its lowering context."""

    label: str
    lowered: Any                      # jax.stages.Lowered
    args: tuple
    kwargs: dict
    jaxpr: Any = None                 # ClosedJaxpr from jitted.trace, or None
    warnings: List[str] = dataclasses.field(default_factory=list)
    compiled_text: Optional[str] = None  # post-SPMD HLO when the warmup path compiled

    _hlo_text: Optional[str] = None

    @property
    def hlo_text(self) -> str:
        """Lowered StableHLO text (cached — ``as_text`` re-prints each call)."""
        if self._hlo_text is None:
            self._hlo_text = self.lowered.as_text()
        return self._hlo_text

    @property
    def donate_argnums(self) -> tuple:
        """Flat indices of donated arguments (empty on jax builds without it)."""
        return tuple(getattr(self.lowered, "donate_argnums", ()) or ())

    @property
    def kept_var_idx(self) -> Optional[tuple]:
        """Sorted flat indices of call leaves KEPT as lowered-main parameters, or
        None when this jax doesn't expose them. jax prunes inputs that don't feed
        any output (e.g. the lm_head of a program that discards its logits), so
        ``@main``'s arg numbering is positions within THIS list, not flat call
        order — every rule matching flat indices against ``main_arg_attributes``
        must translate through it or it misreads any pruned program."""
        try:
            kept = self.lowered._lowering.compile_args["kept_var_idx"]
        except Exception:  # noqa: BLE001 - private API; absent on some jax builds
            return None
        return tuple(sorted(kept))


def capture_lowering(jitted, args, kwargs, label: str) -> Tuple[Any, ProgramCapture]:
    """Trace + lower one call, recording the jaxpr and all lowering warnings.

    Returns ``(lowered, capture)``. Warnings are recorded, not swallowed: the
    ``simplefilter("always")`` guarantees jax's once-per-process donation
    warning is seen for EVERY program, not just the first one lowered.
    """
    with _warnings.catch_warnings(record=True) as caught:
        _warnings.simplefilter("always")
        jaxpr = None
        if hasattr(jitted, "trace"):
            traced = jitted.trace(*args, **kwargs)
            jaxpr = getattr(traced, "jaxpr", None)
            lowered = traced.lower()
        else:  # pragma: no cover - pre-trace-API jax
            lowered = jitted.lower(*args, **kwargs)
    return lowered, ProgramCapture(
        label=label,
        lowered=lowered,
        args=args,
        kwargs=kwargs,
        jaxpr=jaxpr,
        warnings=[str(w.message) for w in caught],
    )


def flat_inputs(capture: ProgramCapture) -> List[Tuple[str, Any]]:
    """``(pytree_path, leaf)`` for every call-argument leaf, in flat order.

    Paths read like ``args[0].params['layers']['wq']`` — stable across runs, so
    they are usable inside baseline keys and suppression match strings.
    """
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path((capture.args, capture.kwargs))
    out = []
    for path, leaf in flat:
        out.append((_format_path(path), leaf))
    return out


def _format_path(path) -> str:
    parts = []
    for p in path:
        key = getattr(p, "key", None)
        if key is None:
            key = getattr(p, "idx", None)
        if key is None:
            key = getattr(p, "name", None)
        parts.append(repr(key) if isinstance(key, str) else str(key))
    return "/".join(parts)


#: One ``%argN: tensor<...>`` (optionally with an attribute dict) in @main's
#: signature. Attribute values may be quoted strings containing braces
#: (``mhlo.sharding = "{replicated}"``), so the dict body matches either
#: non-brace runs or whole quoted strings.
_ARG_RE = re.compile(
    r"%arg(\d+):\s*tensor<[^>]*>\s*(?:loc\([^)]*\)\s*)?(\{(?:[^{}\"]|\"[^\"]*\")*\})?"
)


def main_arg_attributes(hlo_text: str) -> Dict[int, str]:
    """argnum -> attribute-dict text for ``func.func public @main``'s parameters.

    Donation that lowering could actually use shows up here as
    ``tf.aliasing_output = N``; sharding annotations as ``mhlo.sharding``. The
    signature can span lines, so the scan runs from ``@main(`` to the first
    ``) ->`` at paren balance."""
    start = hlo_text.find("@main(")
    if start < 0:
        return {}
    # Walk to the matching close-paren of the argument list.
    depth = 0
    end = start + len("@main")
    for i in range(end, len(hlo_text)):
        c = hlo_text[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    sig = hlo_text[start:end]
    return {int(m.group(1)): (m.group(2) or "") for m in _ARG_RE.finditer(sig)}
