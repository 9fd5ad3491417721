"""Pytree ↔ safetensors interchange.

One shared flattening convention across the framework (checkpointing, `utils.other.save`,
big-model loading): nested dict keys are joined with ``/``; list/tuple indices become their
decimal string. ``safetensors.flax`` is used so bf16 arrays round-trip natively (the numpy
backend cannot represent bf16); it falls back to the numpy backend with an fp32 upcast when
flax's variant is unavailable.

Reference analog: ``accelerate.utils.other.save`` (``other.py:186``) +
``modeling.load_state_dict`` (``modeling.py:1615``) — torch state_dicts with dotted keys; here
the state_dict *is* the pytree.
"""

from __future__ import annotations

from pathlib import Path
from typing import Any

import numpy as np

from .imports import is_safetensors_available


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


def flatten_pytree(tree: Any, sep: str = "/") -> dict[str, Any]:
    """Flatten a pytree of arrays into ``{joined_key: leaf}``."""
    import jax

    flat = {}
    for keypath, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        flat[sep.join(_key_str(k) for k in keypath)] = leaf
    return flat


def unflatten_to_nested_dict(flat: dict[str, Any], sep: str = "/") -> dict:
    """Rebuild a nested dict from joined keys (inverse of :func:`flatten_pytree` for dicts)."""
    nested: dict = {}
    for key, value in flat.items():
        parts = key.split(sep)
        node = nested
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = value
    return nested


def save_pytree_safetensors(tree: Any, file_path: str | Path, metadata: dict | None = None) -> None:
    if not is_safetensors_available():  # pragma: no cover - baked into the image
        raise ImportError("safetensors is required for safe serialization")
    import jax

    flat = {k: np.asarray(jax.device_get(v)) for k, v in flatten_pytree(tree).items()}
    try:
        from safetensors.flax import save_file

        import jax.numpy as jnp

        save_file({k: jnp.asarray(v) for k, v in flat.items()}, str(file_path), metadata=metadata)
    except ImportError:  # numpy fallback: bf16 upcasts to fp32
        from safetensors.numpy import save_file

        flat = {
            k: (v.astype(np.float32) if v.dtype.name == "bfloat16" else v) for k, v in flat.items()
        }
        save_file(flat, str(file_path), metadata=metadata)


def load_flat_safetensors(file_path: str | Path) -> dict[str, np.ndarray]:
    """Load a safetensors file as a flat ``{joined_key: np.ndarray}`` dict (bf16 preserved).

    Values are zero-copy read-only memmap views (``modeling.iter_safetensors``) — the
    ``safetensors.flax`` path would materialize the WHOLE file as jax arrays, a second
    copy of every weight. Copy before mutating."""
    from .modeling import iter_safetensors  # function-level: modeling imports this module

    return dict(iter_safetensors(file_path))


def load_pytree_safetensors(file_path: str | Path) -> dict:
    return unflatten_to_nested_dict(load_flat_safetensors(file_path))
