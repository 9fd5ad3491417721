"""Persistent AOT executable cache: compile once per fleet, not once per process.

Every process start re-pays every XLA compile. This module closes that hole at
the executable level:

- :class:`AotCache` — a content-addressed store of serialized compiled
  executables under ``CompileCacheConfig.cache_dir``. Keys come from
  :mod:`.fingerprint` (lowered StableHLO + jax/jaxlib versions + backend
  topology + compiler flags), so a key hit is safe to execute and anything
  environment-drifted is a clean miss.
- :class:`CachedFunction` — the callable ``AotCache.wrap`` returns around a
  ``jax.jit`` object. First call per signature lowers the program (cheap —
  tracing, no XLA), consults the cache, and thereafter dispatches straight to
  the loaded/compiled executable. Any deserialize/topology/dispatch mismatch
  falls back to the live ``jax.jit`` path — a stale cache can never fail a
  step.

Cache events (hit/miss + deserialize time) flow into the telemetry pipeline via
``telemetry.compile_monitor.dispatch_cache_event`` so ``CompileMonitor``
snapshots attribute cold-start spend.
"""

from __future__ import annotations

import os
import pickle
import tempfile
import time
from typing import Any, Callable, Optional

from jax.experimental import serialize_executable as _ser

from ..logging import get_logger
from ..utils.dataclasses import CompileCacheConfig
from .fingerprint import backend_environment, fingerprint, signature_key

logger = get_logger(__name__)

__all__ = ["AotCache", "CachedFunction"]

#: On-disk entry schema; bump on any layout change (old entries become misses).
ENTRY_SCHEMA = "accelerate_tpu.compile_cache/v1"

#: Per-signature sentinel: this signature permanently uses the live jit path.
_LIVE = object()


def _dispatch_cache_event(hit: bool, deserialize_s: float = 0.0) -> None:
    """Route a cache event into live CompileMonitors (no-op without telemetry)."""
    try:
        from ..telemetry.compile_monitor import dispatch_cache_event
    except ImportError:  # pragma: no cover - telemetry always ships alongside
        return
    dispatch_cache_event(hit, deserialize_s)


class AotCache:
    """Content-addressed persistent store of serialized XLA executables.

    Construction is cheap and never touches disk; the directory is created on
    the first write. A disabled config makes :meth:`wrap` the identity — zero
    overhead, zero behavior change.
    """

    def __init__(self, config: Optional[CompileCacheConfig] = None):
        self.config = config or CompileCacheConfig()
        self.enabled = bool(self.config.enabled)
        self.cache_dir = self.config.cache_dir
        # Counters (mirrored into telemetry CompileMonitor snapshots).
        self.hits = 0
        self.misses = 0
        self.failures = 0          # poisoned/mismatched entries that fell back
        self.deserialize_ms = 0.0
        self.compile_s = 0.0
        self._memo: dict = {}      # fingerprint -> loaded executable (cross-wrapper)
        #: When a list, every lowering routed through this cache appends a
        #: ``analysis.program.ProgramCapture`` — the hook the program auditor
        #: (graftaudit) and the warmup manifest's audit stamp hang off.
        self.capture = None

    # ------------------------------------------------------------------ public API
    def stats(self) -> dict:
        """JSON-serializable counter snapshot (bench rows, telemetry records)."""
        return {
            "enabled": self.enabled,
            "hits": self.hits,
            "misses": self.misses,
            "failures": self.failures,
            "deserialize_ms": round(self.deserialize_ms, 3),
            "compile_s": round(self.compile_s, 3),
        }

    def wrap(self, jitted, label: str, static_argnames: tuple = ()):
        """Wrap a ``jax.jit`` callable so its executables round-trip the cache.

        Disabled caches return ``jitted`` unchanged (the hot path stays the
        C++ jit dispatch). ``static_argnames`` must list the jit's static
        parameters — at call sites they are expected as keywords (the package
        convention), and are stripped before dispatching to the AOT executable
        (statics are baked into it).
        """
        if not self.enabled:
            return jitted
        return CachedFunction(jitted, self, label=label, static_argnames=static_argnames)

    def warm(self, cached_fn: "CachedFunction", *args, **kwargs) -> dict:
        """Populate the cache for one call signature WITHOUT executing.

        Returns the manifest entry: ``{label, key, status, seconds}`` where
        status is ``hit`` (already cached), ``miss`` (compiled + stored) or
        ``live`` (could not be cached; would live-compile at first call).
        """
        return cached_fn.warm(*args, **kwargs)

    def entry_path(self, key: str) -> str:
        return os.path.join(self.cache_dir, f"{key}.aotx")

    # ------------------------------------------------------------------ lowering
    def _lower(self, jitted, args, kwargs, label: str):
        """Lower one call signature, feeding the program-capture hook when armed.

        With ``self.capture`` set (a list), the traced jaxpr and any lower-time
        warnings (jax's "donated buffers were not usable" fires here) are
        recorded alongside the lowered program — the raw material of the
        graftaudit rules (``analysis/program/``)."""
        if self.capture is None:
            return jitted.lower(*args, **kwargs)
        from ..analysis.program.capture import capture_lowering

        lowered, entry = capture_lowering(jitted, args, kwargs, label)
        self.capture.append(entry)
        return lowered

    # ------------------------------------------------------------------ internals
    def _load_or_compile(self, jitted, args, kwargs, label: str):
        """(executable_or_None, manifest_info). Never raises: every failure path
        degrades to live compile (None) or a fresh compile overwriting the bad
        entry."""
        try:
            lowered = self._lower(jitted, args, kwargs, label)
            key = fingerprint(lowered.as_text())
        except Exception as exc:  # noqa: BLE001 - any unlowerable call goes live
            logger.warning("compile cache: lowering %s failed (%s); using live jit",
                           label, type(exc).__name__)
            return None, {"label": label, "key": None, "status": "live", "seconds": 0.0}
        memo = self._memo.get(key)
        if memo is not None:
            return memo, {"label": label, "key": key, "status": "memo", "seconds": 0.0}

        path = self.entry_path(key)
        if os.path.exists(path):
            t0 = time.perf_counter()
            try:
                with open(path, "rb") as f:
                    entry = pickle.load(f)
                if entry.get("schema") != ENTRY_SCHEMA or entry.get("key") != key:
                    raise ValueError("entry schema/key mismatch")
                exe = _ser.deserialize_and_load(
                    entry["payload"], entry["in_tree"], entry["out_tree"]
                )
                dt = time.perf_counter() - t0
                self.hits += 1
                self.deserialize_ms += dt * 1e3
                self._memo[key] = exe
                self._attach_compiled(lowered, exe)
                _dispatch_cache_event(hit=True, deserialize_s=dt)
                return exe, {
                    "label": label, "key": key, "status": "hit",
                    "seconds": round(dt, 6),
                }
            except Exception as exc:  # noqa: BLE001 - poisoned entry: fall through
                self.failures += 1
                logger.warning(
                    "compile cache: entry %s for %s unusable (%s: %s); recompiling",
                    key, label, type(exc).__name__, exc,
                )
        t0 = time.perf_counter()
        try:
            compiled = lowered.compile()
        except Exception as exc:  # noqa: BLE001 - AOT compile refused: live path
            logger.warning("compile cache: AOT compile of %s failed (%s); using live jit",
                           label, type(exc).__name__)
            return None, {"label": label, "key": key, "status": "live", "seconds": 0.0}
        dt = time.perf_counter() - t0
        self.misses += 1
        self.compile_s += dt
        _dispatch_cache_event(hit=False)
        self._memo[key] = compiled
        self._attach_compiled(lowered, compiled)
        self._store(key, label, compiled)
        return compiled, {
            "label": label, "key": key, "status": "miss", "seconds": round(dt, 6),
        }

    def _attach_compiled(self, lowered, executable) -> None:
        """Hand the post-SPMD executable text to the matching capture entry —
        the only representation in which GSPMD-inserted collectives exist."""
        if self.capture is None:
            return
        for entry in reversed(self.capture):
            if entry.lowered is lowered and entry.compiled_text is None:
                try:
                    entry.compiled_text = executable.as_text()
                except Exception:  # noqa: BLE001 - e.g. deserialized exe w/o HLO
                    pass
                return

    def _store(self, key: str, label: str, compiled) -> None:
        """Serialize + atomic-write one entry; storage failures only cost
        persistence, never correctness."""
        try:
            payload, in_tree, out_tree = _ser.serialize(compiled)
            # Validate before persisting: an executable that was itself LOADED from
            # jax's persistent compilation cache serializes to an incomplete payload
            # on the CPU backend (object code absent — "Symbols not found" at load).
            # Writing it would poison every later process; skipping just means this
            # program stays served by jax's own cache.
            _ser.deserialize_and_load(payload, in_tree, out_tree)
            entry = {
                "schema": ENTRY_SCHEMA,
                "key": key,
                "label": label,
                "payload": payload,
                "in_tree": in_tree,
                "out_tree": out_tree,
                "env": backend_environment(),
            }
            os.makedirs(self.cache_dir, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=self.cache_dir, suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    pickle.dump(entry, f)
                os.replace(tmp, self.entry_path(key))  # atomic vs concurrent writers
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
        except Exception as exc:  # noqa: BLE001 - e.g. unserializable backend
            logger.warning("compile cache: could not persist %s (%s: %s)",
                           label, type(exc).__name__, exc)


class CachedFunction:
    """Callable facade over (jitted, AotCache): per-signature AOT dispatch.

    The first call with a new abstract signature lowers the program and asks
    the cache for its executable; subsequent calls with that signature dispatch
    directly to it. Signatures that cannot be cached (unlowerable, statics
    passed positionally, aval/sharding drift at dispatch) permanently fall back
    to the wrapped ``jax.jit`` for correctness.
    """

    def __init__(self, jitted, cache: AotCache, label: str, static_argnames: tuple = ()):
        self._jitted = jitted
        self._cache = cache
        self.label = label
        self._static = tuple(static_argnames)
        self._execs: dict = {}  # signature key -> executable | _LIVE

    def _dynamic(self, args, kwargs):
        """Strip static keywords (baked into the executable). Returns None when a
        static was passed positionally — we cannot identify it, so the caller
        must use the live path."""
        if not self._static:
            return args, kwargs
        if any(name not in kwargs for name in self._static):
            return None
        return args, {k: v for k, v in kwargs.items() if k not in self._static}

    def _lookup(self, args, kwargs):
        sig = signature_key(args, kwargs)
        exe = self._execs.get(sig)
        if exe is None:
            if self._dynamic(args, kwargs) is None:
                logger.warning(
                    "compile cache: %s called with static args passed positionally; "
                    "using live jit for this signature", self.label,
                )
                exe = _LIVE
            else:
                loaded, _ = self._cache._load_or_compile(
                    self._jitted, args, kwargs, self.label
                )
                exe = loaded if loaded is not None else _LIVE
            self._execs[sig] = exe
        return sig, exe

    def __call__(self, *args, **kwargs):
        sig, exe = self._lookup(args, kwargs)
        if exe is _LIVE:
            return self._jitted(*args, **kwargs)
        dyn = self._dynamic(args, kwargs)
        try:
            return exe(*dyn[0], **dyn[1])
        except (TypeError, ValueError) as exc:
            # Dispatch-time aval/sharding mismatch (raised before execution, so
            # donated buffers are intact): pin this signature to the live path.
            logger.warning(
                "compile cache: cached executable for %s rejected its inputs "
                "(%s: %s); falling back to live jit", self.label,
                type(exc).__name__, exc,
            )
            self._execs[sig] = _LIVE
            return self._jitted(*args, **kwargs)

    def warm(self, *args, **kwargs) -> dict:
        """Prime cache + in-memory dispatch for this signature without executing."""
        sig = signature_key(args, kwargs)
        exe = self._execs.get(sig)
        if exe is not None and exe is not _LIVE:
            return {"label": self.label, "key": None, "status": "memo", "seconds": 0.0}
        if self._dynamic(args, kwargs) is None:
            return {"label": self.label, "key": None, "status": "live", "seconds": 0.0}
        loaded, info = self._cache._load_or_compile(self._jitted, args, kwargs, self.label)
        self._execs[sig] = loaded if loaded is not None else _LIVE
        return info

    # Introspection parity with jax.jit objects used around the codebase.
    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def __repr__(self):
        return f"CachedFunction({self.label!r}, signatures={len(self._execs)})"


def as_cached(fn: Callable, cache: Optional[AotCache], label: str,
              static_argnames: tuple = ()) -> Any:
    """``cache.wrap`` that tolerates ``cache=None`` (returns ``fn`` unchanged)."""
    if cache is None:
        return fn
    return cache.wrap(fn, label, static_argnames=static_argnames)
