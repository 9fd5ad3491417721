"""The ONE sanctioned wall-clock source for clock-injectable components.

Incident history (PR 17): the flight recorder stamped ring entries with
``time.monotonic`` while the metrics plane it fed ran on an injected virtual
clock — wall seconds met virtual seconds inside the plane's window trim and
silently purged every live window. The root cause was structural, not a typo:
each clock-injectable component (gateway, fleet, recorder, metrics plane,
tracer, supervisors, watchdog) *individually* defaulted ``clock=`` to
``time.monotonic``, so composing them re-introduced the wall domain at every
layer a caller forgot to thread the clock through.

This module is the fix's anchor and graftflow's allowlist
(``flow-clock-domain`` treats this file, and only this file, as a sanctioned
wall reference — the analogue of graftlint's fence-spelling allowlist):

- Components default ``clock=None`` / ``sleep=None`` and resolve through
  :func:`resolve_clock` / :func:`resolve_sleep`, optionally inheriting the
  domain of an already-bound collaborator (a recorder adopts its metrics
  plane's clock; a tracer adopts its recorder's) before falling back to
  :data:`WALL_CLOCK`.
- Any OTHER ``time.time``/``time.monotonic``/``time.sleep`` reference inside
  a clock-injectable component is a ``flow-clock-domain`` finding.

Stdlib-only by design — the analysis tier and stripped CLI contexts import it
without jax.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

__all__ = ["WALL_CLOCK", "WALL_SLEEP", "PHASE_CLOCK_NS", "resolve_clock",
           "resolve_sleep"]

#: The sanctioned wall clock: monotonic, so backoff schedules and deadline
#: arithmetic survive NTP steps. Components fall back to this — they never
#: spell ``time.monotonic`` themselves.
WALL_CLOCK: Callable[[], float] = time.monotonic

#: The phase ledger's clock (``telemetry.tracing.PHASES``): integer nanoseconds of
#: ``time.perf_counter``, the clock a caller that times the program from outside
#: reads (the benchmark's windows do), so a ledger record is cut at such a caller's
#: marks with no conversion. Not injectable: the ledger times the real program.
PHASE_CLOCK_NS: Callable[[], int] = time.perf_counter_ns

#: The sanctioned wall sleep, paired with :data:`WALL_CLOCK` (a component
#: that waits must wait in the same domain it measures).
WALL_SLEEP: Callable[[float], None] = time.sleep


def resolve_clock(
    clock: Optional[Callable[[], float]] = None,
    *inherit: Optional[Callable[[], float]],
) -> Callable[[], float]:
    """Resolve a component's time domain: the explicitly injected ``clock``
    wins; otherwise the first non-None ``inherit`` candidate (an
    already-bound collaborator's clock, so composition keeps ONE domain);
    otherwise :data:`WALL_CLOCK`.
    """
    if clock is not None:
        return clock
    for candidate in inherit:
        if candidate is not None:
            return candidate
    return WALL_CLOCK


def resolve_sleep(
    sleep: Optional[Callable[[float], None]] = None,
) -> Callable[[float], None]:
    """Resolve a component's sleep: injected wins, else :data:`WALL_SLEEP`."""
    return WALL_SLEEP if sleep is None else sleep
