"""Environment-variable parsing and manipulation helpers.

TPU-native analog of the reference's ``utils/environment.py``
(/root/reference/src/accelerate/utils/environment.py:59-99 for the parsers,
:291-361 for the context managers). The ``ACCELERATE_*`` env-var namespace is
the wire protocol between the launcher CLI and the library (SURVEY.md §1), and
these helpers are the single place it is parsed.
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from typing import Any

__all__ = [
    "str_to_bool",
    "get_int_from_env",
    "parse_flag_from_env",
    "parse_choice_from_env",
    "are_libraries_initialized",
    "clear_environment",
    "patch_environment",
    "purge_accelerate_environment",
    "get_tpu_info",
    "place_compile_cache",
]

_TRUE = {"1", "true", "yes", "y", "on"}
_FALSE = {"0", "false", "no", "n", "off", ""}


def str_to_bool(value: str) -> int:
    """Convert a string to 1/0, raising on unrecognized values."""
    value = str(value).lower().strip()
    if value in _TRUE:
        return 1
    if value in _FALSE:
        return 0
    raise ValueError(f"invalid truth value {value!r}")


def get_int_from_env(env_keys, default: int) -> int:
    """Return the first defined integer value among ``env_keys``."""
    for key in env_keys:
        val = int(os.environ.get(key, -1))
        if val >= 0:
            return val
    return default


def parse_flag_from_env(key: str, default: bool = False) -> bool:
    value = os.environ.get(key, str(default))
    try:
        return bool(str_to_bool(value))
    except ValueError:
        return default


def parse_choice_from_env(key: str, default: str = "no") -> str:
    return os.environ.get(key, str(default))


def are_libraries_initialized(*library_names: str) -> list[str]:
    """Return the subset of ``library_names`` already imported in this process."""
    import sys

    return [lib for lib in library_names if lib in sys.modules]


@contextmanager
def clear_environment():
    """Temporarily run with a completely empty ``os.environ``."""
    saved = dict(os.environ)
    os.environ.clear()
    try:
        yield
    finally:
        os.environ.clear()
        os.environ.update(saved)


@contextmanager
def patch_environment(**kwargs: Any):
    """Temporarily set env vars (upper-cased keys); restores prior values on exit."""
    saved: dict[str, str | None] = {}
    for key, value in kwargs.items():
        key = key.upper()
        saved[key] = os.environ.get(key)
        os.environ[key] = str(value)
    try:
        yield
    finally:
        for key, value in saved.items():
            if value is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = value


def purge_accelerate_environment(func):
    """Decorator: run ``func`` with every ``ACCELERATE_*`` env var removed, then restore.

    Mirrors the hermetic-test helper at reference ``utils/environment.py:362``.
    """
    import functools

    @functools.wraps(func)
    def wrapper(*args, **kwargs):
        saved = {k: v for k, v in os.environ.items() if k.startswith("ACCELERATE_")}
        for k in saved:
            del os.environ[k]
        try:
            return func(*args, **kwargs)
        finally:
            for k, v in saved.items():
                os.environ[k] = v

    return wrapper


# ------------------------------------------------------------ persistent compile cache
def place_compile_cache(default_dir: str | None = None) -> str | None:
    """Turn on JAX's persistent compilation cache at a place the caller can predict.

    ``JAX_COMPILATION_CACHE_DIR`` set: do NOTHING in code — jax reads the variable
    itself, and whoever set it (a launcher, the chip tool) decides where the cache
    lives. Unset: ``<checkout>/.jax_cache`` (or ``default_dir``), a FIXED path derived
    from this file's location — the directory is part of the cache key, so a path made
    from ``tempfile``, a pid or the clock would never hit. In that case every program is
    kept, however quick its compile (jax's default skips those under a second, and a
    process start is mostly made of them). Call before the first compile; returns the
    directory set in code, or None when the environment owns it.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax

    if default_dir is None:
        package_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        default_dir = os.path.join(os.path.dirname(package_dir), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", default_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return default_dir


# ------------------------------------------------------------------- TPU hardware probes
def get_tpu_info() -> dict:
    """TPU topology/metadata introspection (reference's nvidia-smi/NUMA probe analog,
    ``utils/environment.py:101-290``).

    Sources: live jax devices (kind, coords, memory stats — a backend that cannot
    initialize is reported as ``backend_error``), the TPU_*/JAX_* env contract a TPU VM
    image sets, and the GCE metadata server when reachable (accelerator-type / pod
    hostnames — a bounded 1 s probe, skipped offline). Initializes the backend in THIS
    process: a chip belongs to one process at a time, so do not call it from a parent
    that is about to start workers.
    """
    info: dict = {}
    try:
        import jax

        devices = jax.devices()
        info["backend"] = jax.default_backend()
        info["device_count"] = jax.device_count()
        info["local_device_count"] = jax.local_device_count()
        info["process_count"] = jax.process_count()
        if devices:
            d = devices[0]
            info["device_kind"] = getattr(d, "device_kind", "unknown")
            info["platform_version"] = getattr(d, "client", None) and getattr(
                d.client, "platform_version", "unknown"
            )
            coords = getattr(d, "coords", None)
            if coords is not None:
                info["chip_coords_sample"] = tuple(coords)
            core = getattr(d, "core_on_chip", None)
            if core is not None:
                info["core_on_chip_sample"] = core
            stats = d.memory_stats() or {}
            if "bytes_limit" in stats:
                info["hbm_bytes_limit"] = int(stats["bytes_limit"])
            if "bytes_in_use" in stats:
                info["hbm_bytes_in_use"] = int(stats["bytes_in_use"])
    except RuntimeError as e:  # no usable backend (chip held by another process, ...)
        info["backend_error"] = (str(e).splitlines() or [type(e).__name__])[0][:200]

    tpu_env = {
        k: v
        for k, v in os.environ.items()
        if k.startswith(("TPU_", "JAX_", "LIBTPU", "XLA_FLAGS"))
    }
    if tpu_env:
        info["tpu_env"] = tpu_env

    # Only the TPU-specific attribute: a machine-type fallback would mislabel plain GCE
    # VMs as TPU hardware in bug reports.
    meta = _gce_metadata("instance/attributes/accelerator-type")
    if meta:
        info["gce_accelerator"] = meta.rsplit("/", 1)[-1]
        workers = _gce_metadata("instance/attributes/worker-network-endpoints")
        if workers:
            info["pod_workers"] = workers
    return info


def _gce_metadata(path: str, timeout: float = 1.0):
    """Bounded GCE metadata-server read; None when unreachable (non-GCE / offline)."""
    import threading

    result: list = []

    def _probe():
        try:
            import urllib.request

            req = urllib.request.Request(
                f"http://metadata.google.internal/computeMetadata/v1/{path}",
                headers={"Metadata-Flavor": "Google"},
            )
            with urllib.request.urlopen(req, timeout=timeout) as resp:  # noqa: S310
                result.append(resp.read().decode())
        except Exception:
            pass

    t = threading.Thread(target=_probe, daemon=True)
    t.start()
    t.join(timeout + 0.5)
    return result[0] if result else None
