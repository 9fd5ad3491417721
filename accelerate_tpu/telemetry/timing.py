"""Fenced step timing — correct by construction.

Two measurement bugs are designed out here and in :mod:`.steady`: timing a host fetch
of a large result as if it were device work, and averaging a one-time post-compile
allocator transient into the step time.

The fencing rule (single source of truth): ``jax.block_until_ready`` on a designated
**small** output — never a fetch of the full result — waits for the dispatch chain
without moving data. Executions on one device are serialized in dispatch order, so
fencing the last output fences everything before it.

``fence`` is the sanctioned host-sync point graftlint's ``host-sync-in-hot-path`` rule
allowlists: instrumentation built on it needs no suppressions.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional

__all__ = ["fence", "StepTimer", "StepTiming"]


def fence(out: Any) -> Any:
    """Block until ``out`` is computed, moving no data to the host.

    Picks the SMALLEST array leaf of ``out`` (typically the scalar loss) as the fence
    target and calls ``block_until_ready`` on it. Never fetches the result — a fetch
    inside a timed region is recorded as device time. Non-array inputs pass through
    untouched, so ``fence`` is safe on arbitrary metric pytrees.

    Returns ``out`` so it can wrap an expression in place.
    """
    import jax

    leaves = [
        leaf for leaf in jax.tree_util.tree_leaves(out) if isinstance(leaf, jax.Array)
    ]
    if leaves:
        jax.block_until_ready(min(leaves, key=lambda leaf: leaf.size))
    return out


@dataclasses.dataclass(frozen=True)
class StepTiming:
    """One fenced step measurement.

    ``dispatch_s`` is the host time to *enqueue* the step (the jitted call returning);
    ``fence_s`` is the wait until the device actually finished; ``wall_s`` their sum.
    A large ``dispatch_s`` means host-side overhead (tracing, data feeding); a large
    ``fence_s`` means device work — the wall/device split the profiler schedule uses
    to decide what to trace.
    """

    wall_s: float
    dispatch_s: float
    fence_s: float


class StepTimer:
    """Monotonic-clock step timer with explicit fencing.

    Usage (the shape ``Accelerator.build_train_step`` instrumentation uses)::

        timer.start()
        state, metrics = step(state, batch)   # async dispatch returns immediately
        timing = timer.stop(fence_on=metrics["loss"])

    ``stop`` fences on the designated 1-element output via :func:`fence`, so the
    measurement includes the device work — not just the dispatch.
    """

    def __init__(self):
        self._t0: Optional[float] = None

    def start(self) -> None:
        self._t0 = time.perf_counter()

    @property
    def running(self) -> bool:
        return self._t0 is not None

    def stop(self, fence_on: Any) -> StepTiming:
        if self._t0 is None:
            raise RuntimeError("StepTimer.stop() without start()")
        t_dispatched = time.perf_counter()
        fence(fence_on)
        t_done = time.perf_counter()
        t0, self._t0 = self._t0, None
        return StepTiming(
            wall_s=t_done - t0,
            dispatch_s=t_dispatched - t0,
            fence_s=t_done - t_dispatched,
        )

    def time(self, fn, *args, **kwargs):
        """Convenience: ``(out, StepTiming)`` for one fenced call of ``fn``."""
        self.start()
        out = fn(*args, **kwargs)
        return out, self.stop(fence_on=out)
