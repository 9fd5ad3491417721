"""Tripwire: every model/plugin config field must be CONSUMED somewhere in the package.

An early review called out accepted-but-ignored flags as worse than errors
("dead/misleading plugin knobs"). Originally a regex grep over five hardcoded config
classes; now a call into graftlint's dead-knob rule (``accelerate_tpu/analysis/``),
which covers EVERY ``@dataclass`` in the package via real AST attribute-access
analysis — a field that is only ever *defined* fails, forcing the author to either
wire it, delete it, or suppress it on its own line with a written reason.
"""

from accelerate_tpu.analysis.engine import DEFAULT_PATHS, run_lint
from accelerate_tpu.analysis.rules.dead_knob import DeadKnobRule


def test_config_fields_are_consumed():
    # Same universe as the CLI gate (accelerate_tpu/ + benchmarks/ + bench.py), so a
    # field consumed only by bench code counts as consumed in BOTH gates — the two
    # must never disagree on the same rule.
    dead = run_lint(paths=DEFAULT_PATHS, rules=[DeadKnobRule()])
    listing = "\n".join(f.format() for f in dead)
    assert not dead, (
        f"dataclass fields defined but never read anywhere in accelerate_tpu/:\n{listing}\n"
        "— wire them or delete them (an accepted-but-ignored flag is worse than an error)"
    )
