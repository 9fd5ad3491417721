"""Shared fencing/timing and row bookkeeping for the on-chip harnesses
(decompose.py, step_attrib.py, quant_microbench.py, ...).

The fence is ``jax.block_until_ready`` on the last output: executions on one chip are
serialized in dispatch order, so it fences the whole timed loop without moving data
(a value fetch inside a timed region would be recorded as device time). Keep that rule
here, in exactly one place. Every harness calls
``accelerate_tpu.utils.environment.place_compile_cache()`` first, so repeated runs in
one checkout reuse compiled programs.
"""

from __future__ import annotations

import os
import sys
import time


def force_cpu_for_smoke() -> bool:
    """BENCH_PRESET=smoke is a CPU logic check by definition — select the CPU backend.
    Returns whether smoke mode is active. Call before any other jax use."""
    smoke = os.environ.get("BENCH_PRESET") == "smoke"
    if smoke:
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")
    return smoke


def refuse_non_smoke_cpu(tool: str, smoke: bool) -> bool:
    """True → caller must bail (rc 2) BEFORE writing any results row.

    A non-smoke row measured on the CPU backend would sit in a results file under the
    name of a device metric. Shared so every row-writing bench script gets the guard
    by default."""
    import jax

    if smoke or jax.default_backend() != "cpu":
        return False
    print(f"{tool}: refusing non-smoke run on the cpu backend — no row written",
          file=sys.stderr, flush=True)
    return True


def materialize(out):
    """Wait for ``out`` (the fence; moves no data)."""
    import jax

    return jax.block_until_ready(out)


def timed(fn, *args, n=3, warmup=1):
    """Average seconds per call for a side-effect-free fn (args re-used every call)."""
    for _ in range(warmup):
        materialize(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(n):
        out = fn(*args)
    materialize(out)
    return (time.perf_counter() - t0) / n


def exc_line(e: BaseException, width: int = 160) -> str:
    """First line of an exception message, safe for empty messages (bare MemoryError)."""
    return (str(e).splitlines() or [type(e).__name__])[0][:width]


class RowRunner:
    """Failure-scoped benchmark rows: one crashing row (OOM, Mosaic lowering error) is
    recorded with its error and the section continues, so one bad row does not cost the
    others their measurement — and ``finish`` returns non-zero when any row failed, so
    the run as a whole never reads as a success."""

    def __init__(self):
        self.rows = []
        self.failed = []

    def row(self, name, thunk):
        """Run thunk() -> dict of fields; record `{"name", **fields}` or the error."""
        import gc

        failed = False
        try:
            rec = thunk() or {}
            self.rows.append({"name": name, **rec})
            return rec
        except Exception as e:
            msg = f"{type(e).__name__}: {exc_line(e, 160)}"
            print(f"{name}: {msg}", flush=True)
            self.rows.append({"name": name, "error": msg})
            self.failed.append(name)
            failed = True
            return None
        finally:
            if failed:
                # Outside the except block the exception (and its traceback's grip on
                # the thunk frame's device buffers) is dead, so this collect actually
                # frees them before the next row.
                gc.collect()

    def section(self, name, thunk):
        """Guard shared setup for a group of rows: failure is recorded as `<name>`
        (the inner rows never ran); success adds no row of its own."""
        import gc

        failed = False
        try:
            thunk()
        except Exception as e:
            msg = f"{type(e).__name__}: {exc_line(e, 160)}"
            print(f"{name}: {msg}", flush=True)
            self.rows.append({"name": name, "error": msg})
            self.failed.append(name)
            failed = True
        finally:
            if failed:
                gc.collect()

    def finish(self, **config):
        """Emit the JSON line (partial rows included); exit code 1 if any row failed."""
        import json

        out = {"rows": self.rows, "config": config}
        if self.failed:
            out["failed_rows"] = self.failed
        print(json.dumps(out), flush=True)
        return 1 if self.failed else 0
