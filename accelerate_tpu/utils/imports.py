"""Optional-dependency availability registry.

Analog of reference ``utils/imports.py`` (/root/reference/src/accelerate/utils/imports.py, ~55
``is_*_available`` probes). Every optional integration is gated through one of these probes so the
core framework never hard-imports anything beyond jax/numpy.
"""

from __future__ import annotations

import importlib.metadata
import importlib.util
import functools

__all__ = [
    "is_available",
    "is_torch_available",
    "is_flax_available",
    "is_optax_available",
    "is_orbax_available",
    "is_safetensors_available",
    "is_tensorboard_available",
    "is_wandb_available",
    "is_mlflow_available",
    "is_comet_ml_available",
    "is_clearml_available",
    "is_aim_available",
    "is_dvclive_available",
    "is_swanlab_available",
    "is_transformers_available",
    "is_peft_available",
    "is_datasets_available",
    "is_tqdm_available",
    "is_rich_available",
    "is_pandas_available",
    "is_einops_available",
    "is_chex_available",
    "is_yaml_available",
    "is_tpu_available",
    "is_multihost",
    "is_bf16_available",
    "is_fp8_available",
    "compare_versions",
    "is_jax_version",
]


@functools.lru_cache(maxsize=None)
def is_available(name: str) -> bool:
    """True if module ``name`` is importable (spec found, not imported)."""
    try:
        return importlib.util.find_spec(name) is not None
    except (ImportError, ValueError, ModuleNotFoundError):
        return False


def _probe(module_name: str):
    def probe() -> bool:
        return is_available(module_name)

    probe.__name__ = f"is_{module_name}_available"
    return probe


is_torch_available = _probe("torch")
is_flax_available = _probe("flax")
is_optax_available = _probe("optax")
is_orbax_available = _probe("orbax.checkpoint")
is_safetensors_available = _probe("safetensors")
is_tensorboard_available = _probe("tensorboard")
is_wandb_available = _probe("wandb")
is_mlflow_available = _probe("mlflow")
is_comet_ml_available = _probe("comet_ml")
is_clearml_available = _probe("clearml")
is_aim_available = _probe("aim")
is_dvclive_available = _probe("dvclive")
is_swanlab_available = _probe("swanlab")
is_transformers_available = _probe("transformers")
is_peft_available = _probe("peft")
is_datasets_available = _probe("datasets")
is_tqdm_available = _probe("tqdm")
is_rich_available = _probe("rich")
is_pandas_available = _probe("pandas")
is_einops_available = _probe("einops")
is_chex_available = _probe("chex")
is_yaml_available = _probe("yaml")


def is_tpu_available() -> bool:
    """True when JAX's default backend is a TPU — the ONE place the package asks.

    Kernel dispatch (flash / paged attention), Pallas interpret mode and the benchmark
    entry points all route through here; nothing else may decide between a compiled
    Mosaic kernel and its CPU stand-in. Initializes the backend on first call."""
    import jax

    return jax.default_backend() == "tpu"


def is_multihost() -> bool:
    import jax

    return jax.process_count() > 1


def is_bf16_available(ignore_tpu: bool = False) -> bool:
    """bf16 capability probe (reference ``imports.py:137``). TPUs compute bf16 natively and
    the CPU simulator emulates it, so this is effectively always True here; the signature
    (incl. the vestigial ``ignore_tpu``) is kept for reference API compatibility."""
    return True


def is_fp8_available() -> bool:
    """fp8 capability probe (reference ``imports.py`` TE/ao/MS-AMP checks). Here fp8 is
    native (``jnp.float8_e4m3fn`` scaled matmuls in ``ops/fp8.py``), so the probe checks the
    dtype exists in the installed jax rather than any vendor library."""
    import jax.numpy as jnp

    return hasattr(jnp, "float8_e4m3fn")


def compare_versions(library_or_version, operation: str, requirement_version: str) -> bool:
    """Compare an installed library's version against ``requirement_version`` (reference
    ``utils/versions.py:compare_versions``). ``library_or_version`` is a module name or an
    already-resolved version string; ``operation`` is one of <, <=, ==, !=, >=, >."""
    import operator

    from packaging.version import parse

    ops = {"<": operator.lt, "<=": operator.le, "==": operator.eq,
           "!=": operator.ne, ">=": operator.ge, ">": operator.gt}
    if operation not in ops:
        raise ValueError(f"operation must be one of {sorted(ops)}, got {operation!r}")
    if isinstance(library_or_version, str):
        try:
            library_or_version = importlib.metadata.version(library_or_version)
        except importlib.metadata.PackageNotFoundError:
            pass  # already a version string (or will fail clearly in parse below)
    return ops[operation](parse(str(library_or_version)), parse(requirement_version))


def is_jax_version(operation: str, version: str) -> bool:
    """``is_torch_version`` analog for the runtime that actually matters here."""
    import jax

    return compare_versions(jax.__version__, operation, version)
