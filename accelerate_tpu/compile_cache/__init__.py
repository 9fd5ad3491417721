"""Persistent AOT compilation cache (L10): kill the cold-start recompile tax.

Every process start re-pays every XLA compile (a training job its step
programs; serving cold starts re-jit prefill/decode per prompt length). This package makes compiled
executables a durable artifact instead:

- :class:`AotCache` / :class:`CachedFunction` (``cache.py``) — content-addressed
  store of serialized executables keyed by lowered-program fingerprint +
  backend environment; wraps the jits built by ``Accelerator.build_train_step``
  / ``build_eval_step`` and the serving programs. Stale entries fall back to
  live compile, never fail a step.
- :mod:`.fingerprint` — the cache key anatomy (docs/compile_cache.md).
- :mod:`.buckets` — shape-bucket selection for bucketed serving prefill.
- :mod:`.warmup` — ``python -m accelerate_tpu warmup``: enumerate + pre-compile
  a config's programs so a training job or serving replica starts hot.

Enable via ``Accelerator(compile_cache_config=CompileCacheConfig(enabled=True))``
or ``ACCELERATE_COMPILE_CACHE=1`` (a path value also sets the directory).
"""

from ..utils.dataclasses import CompileCacheConfig
from .buckets import pick_bucket
from .cache import AotCache, CachedFunction, as_cached
from .fingerprint import backend_environment, fingerprint, signature_key
from .warmup import build_drafter, build_model_config, run_warmup

__all__ = [
    "AotCache",
    "CachedFunction",
    "CompileCacheConfig",
    "as_cached",
    "backend_environment",
    "build_drafter",
    "build_model_config",
    "fingerprint",
    "pick_bucket",
    "run_warmup",
    "signature_key",
]
