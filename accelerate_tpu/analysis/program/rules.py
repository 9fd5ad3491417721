"""graftaudit rules: checks over the traced program (jaxpr + StableHLO).

Each rule descends from an incident class that is INVISIBLE to the AST tier
(graftlint) because it only exists after tracing:

- ``dtype-promotion`` — a bf16/f16 tensor silently upcast to f32 and then
  *computed on* at full width (the half-speed-matmul class). Upcasts whose
  result feeds only a reduction are the sanctioned stable-accumulation
  pattern and are allowed.
- ``replicated-sharding`` — a large parameter/optimizer/gradient-accumulator
  input living fully replicated on a >1-device mesh (the
  wasted-HBM-per-chip class; arXiv:2004.13336 shards exactly these).
- ``dead-donation`` — ``donate_argnums`` that lowering could not alias to any
  output: the caller's buffer is consumed but the memory saving never
  happens (jax only warns, once, at trace time — in an unattended run nobody
  sees it). The flip side of the PR 3 retrace incident: donation semantics
  silently diverging from what the code claims.
- ``host-transfer`` — callbacks / infeed / outfeed / host-placement custom
  calls inside a hot-path program: each one is a device→host round-trip per
  step (the fetch-inside-the-timed-region class from PR 1, now caught in
  the program itself).

Rules emit the engine's :class:`~..engine.Finding` with
``path="program:<label>"`` and a stable ``code`` string (no line numbers, no
pointers) so the ratcheting baseline and suppression machinery apply
unchanged.
"""

from __future__ import annotations

import re
from typing import Iterable, List

from ..engine import Finding
from .capture import ProgramCapture, flat_inputs, main_arg_attributes

__all__ = ["ProgramRule", "all_program_rules", "program_rule_by_id"]


class ProgramRule:
    """Base: subclasses set ``id``/``severity``/``description`` and override
    ``check_program`` (called once per captured program)."""

    id = ""
    severity = "error"
    description = ""

    def check_program(self, prog: ProgramCapture) -> Iterable[Finding]:
        return ()

    def make(self, prog: ProgramCapture, message: str, code: str) -> Finding:
        return Finding(
            rule=self.id,
            severity=self.severity,
            path=f"program:{prog.label}",
            line=0,
            message=message,
            code=code,
        )


# ------------------------------------------------------------------ dtype promotion

#: Reductions for which an upcast input is the *correct* f32-accumulation idiom.
_REDUCTION_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "argmax", "argmin", "reduce_precision",
})
_LOW_DTYPES = ("bfloat16", "float16")
_WIDE_DTYPES = ("float32", "float64")


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


class DtypePromotionRule(ProgramRule):
    id = "dtype-promotion"
    severity = "error"
    description = (
        "large low-precision tensor upcast to f32 and computed on at full width "
        "(upcasts feeding only reductions are the sanctioned accumulation pattern)"
    )

    def __init__(self, min_elements: int = 65536):
        self.min_elements = min_elements

    def check_program(self, prog: ProgramCapture) -> List[Finding]:
        if prog.jaxpr is None:
            return []
        findings = []
        root = getattr(prog.jaxpr, "jaxpr", prog.jaxpr)
        for jaxpr in _walk_jaxprs(root):
            # Keyed by id(): jaxpr Vars are unique objects and Literals are
            # unhashable by design.
            consumers: dict = {}
            for eqn in jaxpr.eqns:
                for var in eqn.invars:
                    if hasattr(var, "aval"):
                        consumers.setdefault(id(var), []).append(eqn)
            for eqn in jaxpr.eqns:
                if eqn.primitive.name != "convert_element_type":
                    continue
                src = eqn.invars[0]
                dst = eqn.outvars[0]
                src_aval = getattr(src, "aval", None)
                dst_aval = getattr(dst, "aval", None)
                if src_aval is None or dst_aval is None:
                    continue
                if str(src_aval.dtype) not in _LOW_DTYPES:
                    continue
                if str(dst_aval.dtype) not in _WIDE_DTYPES:
                    continue
                if src_aval.size < self.min_elements:
                    continue
                used_by = consumers.get(id(dst), [])
                if used_by and all(
                    u.primitive.name in _REDUCTION_PRIMS for u in used_by
                ):
                    continue  # upcast-then-reduce: stable accumulation, sanctioned
                shape = "x".join(str(d) for d in src_aval.shape)
                compute = sorted({u.primitive.name for u in used_by}) or ["<output>"]
                findings.append(
                    self.make(
                        prog,
                        f"{src_aval.dtype}[{shape}] upcast to {dst_aval.dtype} and "
                        f"consumed by non-reduction ops ({', '.join(compute)}) — "
                        "full-width compute on a low-precision path",
                        code=f"convert {src_aval.dtype}->{dst_aval.dtype} [{shape}] "
                        f"-> {','.join(compute)}",
                    )
                )
        return findings


# ------------------------------------------------------------- replicated sharding


class ReplicatedShardingRule(ProgramRule):
    id = "replicated-sharding"
    severity = "error"
    description = (
        "large input (param / optimizer moment / gradient accumulator) fully "
        "replicated across a >1-device mesh"
    )

    def __init__(self, min_bytes: int = 1 << 20):
        self.min_bytes = min_bytes

    def check_program(self, prog: ProgramCapture) -> List[Finding]:
        import jax

        findings = []
        for path, leaf in flat_inputs(prog):
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
                findings.append(
                    self.make(
                        prog,
                        f"input {path} ({leaf.dtype}[{shape}], "
                        f"{nbytes / (1 << 20):.1f} MiB) is fully replicated over "
                        f"{n_dev} devices — that is {nbytes * (n_dev - 1) / (1 << 20):.1f} "
                        "MiB of duplicate HBM; shard it or suppress with the "
                        "reason it must stay replicated",
                        code=f"replicated {leaf.dtype}[{shape}] {path}",
                    )
                )
        return findings


# ------------------------------------------------------------------- dead donation

_UNUSED_DONATION_RE = re.compile(r"donated buffers were not usable", re.IGNORECASE)


class DeadDonationRule(ProgramRule):
    id = "dead-donation"
    severity = "error"
    description = (
        "donated argument never aliased to an output: the caller's buffer is "
        "consumed but the in-place reuse never happens"
    )

    def check_program(self, prog: ProgramCapture) -> List[Finding]:
        donated = prog.donate_argnums
        if not donated:
            return []
        attrs = main_arg_attributes(prog.hlo_text)
        findings = []
        # Flat call leaves give pytree paths instead of bare arg numbers;
        # donate_argnums are flat indices. @main's parameters, however, are only
        # the KEPT inputs — jax prunes args no output depends on (a program that
        # discards its logits drops the whole lm_head) — so flat indices must be
        # translated to kept positions before reading arg attributes, or every
        # donated arg after a pruned one is misread as unaliased.
        leaves = flat_inputs(prog)
        kept = prog.kept_var_idx
        kept_pos = (
            {flat: pos for pos, flat in enumerate(kept)} if kept is not None else None
        )
        for i in donated:
            if kept_pos is None:
                attr = attrs.get(i, "")
            elif i in kept_pos:
                attr = attrs.get(kept_pos[i], "")
            else:
                # Donated AND pruned: the program never reads the buffer, yet jit
                # dispatch still consumes (deletes) donated inputs — the caller
                # loses the array for a program that ignores it. Dead by
                # construction; fall through with no attributes.
                attr = ""
            if "tf.aliasing_output" in attr:
                continue  # lowering established the alias
            if "jax.buffer_donor" in attr:
                # Multi-device path: jax defers alias assignment to XLA, so
                # dead-or-not is undecidable from the lowered text alone. The
                # warmup path (which compiles) reports effectiveness in the
                # manifest's donation summary instead.
                continue
            if i < len(leaves):
                path, leaf = leaves[i]
                shape = "x".join(str(d) for d in getattr(leaf, "shape", ()))
                desc = f"{path} {getattr(leaf, 'dtype', '?')}[{shape}]"
            else:
                desc = f"arg {i}"
            findings.append(
                self.make(
                    prog,
                    f"donated arg {i} ({desc}) has no aliased output — donation "
                    "is dead: the caller loses the buffer, the program saves "
                    "nothing (jax warned once at trace time; this gate makes it "
                    "a finding)",
                    code=f"dead donation {desc}",
                )
            )
        return findings


# ------------------------------------------------------------------- host transfer

_CUSTOM_CALL_RE = re.compile(r"stablehlo\.custom_call\s+@([\w.]+)")
_INOUT_FEED_RE = re.compile(r"stablehlo\.(infeed|outfeed)\b")

#: Custom-call targets that are part of normal device-side lowering.
_BENIGN_TARGETS = frozenset({
    "Sharding",
    "SPMDFullToShardShape",
    "SPMDShardToFullShape",
    "cu_threefry2x32",  # rng lowering detail, fully on device
    "Eigh", "Qr", "Cholesky", "LuDecomposition",  # linalg kernels, on device
})
#: Targets that are device→host (or host→device) transfers per invocation.
_TRANSFER_HINTS = ("callback", "infeed", "outfeed", "py_func", "debug")


class HostTransferRule(ProgramRule):
    id = "host-transfer"
    severity = "error"
    description = (
        "host callback / infeed / outfeed / host-placement op inside a hot-path "
        "program — a device-host round-trip every step"
    )

    def check_program(self, prog: ProgramCapture) -> List[Finding]:
        findings = []
        seen = set()
        text = prog.hlo_text
        for m in _CUSTOM_CALL_RE.finditer(text):
            target = m.group(1)
            if target in _BENIGN_TARGETS or target in seen:
                continue
            is_transfer = any(h in target.lower() for h in _TRANSFER_HINTS)
            if target == "annotate_device_placement":
                # Host memory-kind placement: a transfer unless this program is
                # explicitly an offload fetch/stash (which would be suppressed
                # with that reason).
                is_transfer = True
            if not is_transfer:
                continue
            seen.add(target)
            findings.append(
                self.make(
                    prog,
                    f"custom_call @{target} in hot-path program — every dispatch "
                    "pays a device-host round-trip (use the telemetry fence "
                    "pattern outside the program, or suppress with the reason "
                    "the transfer is intentional)",
                    code=f"custom_call @{target}",
                )
            )
        for m in _INOUT_FEED_RE.finditer(text):
            kind = m.group(1)
            if kind in seen:
                continue
            seen.add(kind)
            findings.append(
                self.make(
                    prog,
                    f"stablehlo.{kind} in hot-path program — host transfer every step",
                    code=f"stablehlo.{kind}",
                )
            )
        return findings


# ----------------------------------------------------------------------- registry


def all_program_rules():
    """Fresh rule instances (constructor thresholds are test-overridable)."""
    return [
        DtypePromotionRule(),
        ReplicatedShardingRule(),
        DeadDonationRule(),
        HostTransferRule(),
    ]


def program_rule_by_id(rule_id: str):
    for r in all_program_rules():
        if r.id == rule_id:
            return r
    raise KeyError(f"unknown graftaudit rule: {rule_id}")
