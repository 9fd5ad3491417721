"""Model-surgery utilities for big-model inference (L6).

TPU-native re-design of reference ``utils/modeling.py`` (/root/reference/src/accelerate/utils/
modeling.py): ``compute_module_sizes`` (:656), ``get_max_memory`` (:749), ``get_balanced_memory``
(:923), ``infer_auto_device_map`` (:1281), ``find_tied_parameters`` (:559), sharded
``load_checkpoint_in_model`` (:1787), lazy safetensors ``load_state_dict`` (:1615).

The torch version operates on ``nn.Module`` trees addressed by dotted names; here a model is a
params **pytree** addressed by ``/``-joined key paths (the framework-wide flattening convention of
``utils/serialization.py``). "Module" granularity is a key-path *prefix*: ``layers/3`` names the
pytree subtree of block 3. Device maps are ``{prefix: placement}`` where a placement is a
``jax.Device``, an int device ordinal, ``"cpu"`` (host RAM as numpy), or ``"disk"``
(memmap offload store, ``utils/offload.py``).

Meta-device init ≈ ``jax.eval_shape``: an abstract model is a pytree of
``jax.ShapeDtypeStruct`` — zero bytes, full structure, exactly what the greedy placement
algorithm needs.
"""

from __future__ import annotations

import json
import os
import re
from collections import defaultdict
from pathlib import Path
from typing import Any, Optional, Union

import numpy as np

from .constants import SAFE_WEIGHTS_INDEX_NAME, SAFE_WEIGHTS_NAME
from .serialization import flatten_pytree, unflatten_to_nested_dict

__all__ = [
    "dtype_byte_size",
    "named_parameters",
    "compute_module_sizes",
    "calculate_maximum_sizes",
    "get_max_memory",
    "get_balanced_memory",
    "infer_auto_device_map",
    "find_tied_parameters",
    "load_state_dict",
    "load_checkpoint_in_model",
    "save_sharded_checkpoint",
    "check_device_map",
    "get_module_leaves",
]

Placement = Union[str, int, Any]  # jax.Device | int ordinal | "cpu" | "disk"


# ------------------------------------------------------------------------------- size math
def dtype_byte_size(dtype) -> float:
    """Bytes per element of ``dtype`` (fractional for sub-byte types).

    Reference analog: ``modeling.py:124`` (``dtype_byte_size``).
    """
    name = getattr(dtype, "name", None) or str(dtype)
    if name in ("bool", "bool_"):
        return 1 / 8
    # First digit group = the bit width ("float8_e4m3fn" → 8, not the e4m3 suffix digits).
    m = re.search(r"[^\d](\d+)", name)
    if m is None:
        raise ValueError(f"`dtype` is not a valid dtype: {dtype}.")
    return int(m.group(1)) / 8


def named_parameters(tree: Any) -> dict[str, Any]:
    """Flatten a params pytree to ``{'a/b/c': leaf}`` (leaves may be abstract)."""
    return flatten_pytree(tree)


def _leaf_size(leaf, dtype=None) -> int:
    shape = getattr(leaf, "shape", ())
    d = dtype if dtype is not None else getattr(leaf, "dtype", np.float32)
    n = 1
    for s in shape:
        n *= int(s)
    return int(n * dtype_byte_size(d))


def compute_module_sizes(tree: Any, dtype=None) -> dict[str, int]:
    """Byte size of every key-path prefix ('' = whole model).

    Reference analog: ``compute_module_sizes`` (``modeling.py:656``) — dotted-name prefixes over
    an nn.Module; here ``/``-joined prefixes over the pytree. ``dtype`` overrides per-leaf dtypes
    (the reference's ``special_dtypes`` generalization is done by passing an abstract tree whose
    leaves already carry the target dtypes).
    """
    sizes: dict[str, int] = defaultdict(int)
    for name, leaf in named_parameters(tree).items():
        size = _leaf_size(leaf, dtype)
        parts = name.split("/")
        for i in range(len(parts) + 1):
            sizes["/".join(parts[:i])] += size
    return dict(sizes)


def calculate_maximum_sizes(tree: Any) -> tuple[int, tuple[int, list[str]]]:
    """(total_size, (largest_layer_size, largest_layer_names)).

    Reference analog: ``calculate_maximum_sizes`` (``modeling.py:701``), used by the memory
    estimator CLI.
    """
    sizes = compute_module_sizes(tree)
    total = sizes.get("", 0)
    no_split = get_module_leaves(sizes)
    largest = max((sizes[k] for k in no_split), default=0)
    names = [k for k in no_split if sizes[k] == largest]
    return total, (largest, names)


def get_module_leaves(sizes: dict[str, int]) -> list[str]:
    """Key-path prefixes that have no strict sub-prefix in ``sizes`` (leaf tensors)."""
    leaves = []
    for k in sizes:
        if k and not any(other != k and other.startswith(k + "/") for other in sizes):
            leaves.append(k)
    return leaves


# -------------------------------------------------------------------------- memory probing
def _device_memory_bytes(device) -> int:
    """Total accelerator memory of one jax device, via PJRT memory_stats when available."""
    try:
        stats = device.memory_stats()
        if stats:
            for key in ("bytes_limit", "bytes_reservable_limit"):
                if key in stats and stats[key]:
                    return int(stats[key])
    except Exception:  # pragma: no cover - backend without memory_stats
        pass
    # CPU backend / unknown: treat each virtual device as a slice of host RAM.
    return _host_memory_bytes() // max(1, _device_count())


def _host_memory_bytes() -> int:
    try:
        import psutil  # type: ignore

        return int(psutil.virtual_memory().available)
    except Exception:
        pages = os.sysconf("SC_PHYS_PAGES")
        page_size = os.sysconf("SC_PAGE_SIZE")
        return int(pages * page_size)


def _device_count() -> int:
    import jax

    return jax.local_device_count()


def get_max_memory(max_memory: Optional[dict] = None) -> dict[Placement, int]:
    """Per-placement byte budget: every local jax device ordinal plus ``"cpu"``.

    Reference analog: ``get_max_memory`` (``modeling.py:749``) — probes each CUDA device and host
    RAM, honors user overrides (str sizes like ``"1GB"`` accepted). Device keys are local device
    ordinals; ``"disk"`` is implicitly unbounded and never listed.
    """
    import jax

    if max_memory is None:
        out: dict[Placement, int] = {
            i: _device_memory_bytes(d) for i, d in enumerate(jax.local_devices())
        }
        out["cpu"] = _host_memory_bytes()
        return out
    parsed: dict[Placement, int] = {}
    for key, value in max_memory.items():
        parsed[key] = convert_file_size_to_int(value) if isinstance(value, str) else int(value)
    # Keep declaration order (the reference sorts GPU keys then appends cpu/disk).
    ordered = {k: parsed[k] for k in sorted((k for k in parsed if isinstance(k, int)))}
    for k in parsed:
        if not isinstance(k, int):
            ordered[k] = parsed[k]
    return ordered


def convert_file_size_to_int(size: Union[int, str]) -> int:
    """``"6GB"``/``"6GiB"``-style strings → bytes (reference ``modeling.py:87``)."""
    if isinstance(size, int):
        return size
    mult = {
        "TIB": 2**40, "GIB": 2**30, "MIB": 2**20, "KIB": 2**10,
        "TB": 10**12, "GB": 10**9, "MB": 10**6, "KB": 10**3,
    }
    s = size.upper().strip()
    for suffix, m in mult.items():
        if s.endswith(suffix):
            return int(float(s[: -len(suffix)]) * m)
    try:
        return int(s)
    except ValueError:
        raise ValueError(f"`size` {size!r} is not in a valid format.") from None


def get_balanced_memory(
    tree: Any,
    max_memory: Optional[dict] = None,
    no_split_module_classes=None,
    dtype=None,
    low_zero: bool = False,
) -> dict[Placement, int]:
    """Cap per-device budgets so layers spread evenly instead of greedily filling device 0.

    Reference analog: ``get_balanced_memory`` (``modeling.py:923``): budget ≈ total_size /
    num_devices, rounded up to a multiple of the mean leaf size, with a buffer; ``low_zero``
    reserves device 0 for generation workspace.
    """
    max_memory = get_max_memory(max_memory)
    device_keys = [k for k in max_memory if isinstance(k, int)]
    num_devices = len([k for k in device_keys if max_memory[k] > 0])
    if num_devices == 0:
        return max_memory
    if num_devices == 1:
        low_zero = False

    sizes = compute_module_sizes(tree, dtype=dtype)
    total = sizes.get("", 0)
    per_device = total // (num_devices - 1 if low_zero else num_devices)

    leaves = get_module_leaves(sizes)
    leaf_sizes = [sizes[k] for k in leaves] or [0]
    mean_leaf = int(sum(leaf_sizes) / max(len(leaf_sizes), 1))
    buffer = int(1.25 * max(leaf_sizes, default=0))
    per_device = per_device + buffer if mean_leaf == 0 else ((per_device + mean_leaf - 1) // mean_leaf) * mean_leaf + buffer

    out = dict(max_memory)
    for k in device_keys:
        out[k] = min(0 if low_zero and k == device_keys[0] else per_device, max_memory[k])
    if low_zero:
        out[device_keys[0]] = min(total - sum(out[k] for k in device_keys[1:]), max_memory[device_keys[0]])
        out[device_keys[0]] = max(out[device_keys[0]], 0)
    return out


# --------------------------------------------------------------------------- tied weights
def find_tied_parameters(tree: Any) -> list[list[str]]:
    """Groups of key paths whose leaves alias the same buffer.

    Reference analog: ``find_tied_parameters`` (``modeling.py:559``) — discovers parameters shared
    between modules (e.g. tied embed/lm_head). In JAX tying is *aliasing*: the same ``jax.Array``
    (or numpy array) object appearing at several key paths.
    """
    by_id: dict[int, list[str]] = defaultdict(list)
    for name, leaf in named_parameters(tree).items():
        if hasattr(leaf, "shape"):
            by_id[id(leaf)].append(name)
    return sorted([sorted(v) for v in by_id.values() if len(v) > 1])


# ------------------------------------------------------------------------- device mapping
def _placement_order(max_memory: dict[Placement, int]) -> list[Placement]:
    devices = sorted(k for k in max_memory if isinstance(k, int))
    order: list[Placement] = list(devices)
    if "cpu" in max_memory:
        order.append("cpu")
    order.append("disk")
    return order


def infer_auto_device_map(
    tree: Any,
    max_memory: Optional[dict] = None,
    no_split_prefixes: Optional[list[str]] = None,
    dtype=None,
    clean_result: bool = True,
    offload_buffers: bool = False,
) -> dict[str, Placement]:
    """Greedy layer placement across device ordinals → "cpu" → "disk".

    Reference analog: ``infer_auto_device_map`` (``modeling.py:1281``). Walks top-level pytree
    entries in order; an entry that does not fit the current placement's remaining budget is
    split into its children (unless its prefix matches ``no_split_prefixes``, the analog of
    ``no_split_module_classes`` — e.g. a transformer block that must stay whole); an unsplittable
    non-fitting entry advances to the next placement. Tied groups are placed together: the size
    charged for an entry includes tied partners outside it, and partners are mapped to the same
    placement (reference ``:1394-1464``).
    """
    max_memory = get_max_memory(max_memory)
    no_split = set(no_split_prefixes or [])
    sizes = compute_module_sizes(tree, dtype=dtype)
    tied_groups = find_tied_parameters(tree)

    order = _placement_order(max_memory)
    budgets = {p: max_memory.get(p, 0) for p in order if p != "disk"}
    budgets["disk"] = float("inf")

    # Work queue of prefixes, splitting on demand. Top-level entries first, in pytree order.
    flat = list(named_parameters(tree).items())

    def children(prefix: str) -> list[str]:
        depth = prefix.count("/") + 1 if prefix else 0
        out, seen = [], set()
        for name, _ in flat:
            if prefix and not name.startswith(prefix + "/"):
                continue
            child = "/".join(name.split("/")[: depth + 1])
            if child not in seen:
                seen.add(child)
                out.append(child)
        return out

    def tied_partners(prefix: str) -> list[str]:
        partners = []
        for group in tied_groups:
            inside = [n for n in group if n == prefix or n.startswith(prefix + "/") or prefix == ""]
            outside = [n for n in group if n not in inside]
            if inside and outside:
                partners.extend(outside)
        return partners

    queue = children("")
    device_map: dict[str, Placement] = {}
    pos = 0
    while queue:
        prefix = queue.pop(0)
        if prefix in {n for g in tied_groups for n in g} and any(
            prefix == p or prefix.startswith(p + "/") for p in device_map
        ):
            continue  # already placed with its tied partner
        partners = tied_partners(prefix)
        size = sizes[prefix] + sum(sizes[p] for p in partners)
        placed = False
        while pos < len(order):
            placement = order[pos]
            if size <= budgets[placement]:
                budgets[placement] -= size
                device_map[prefix] = placement
                for p in partners:
                    device_map[p] = placement
                placed = True
                break
            kids = children(prefix)
            splittable = prefix not in no_split and not any(
                prefix == ns or prefix.endswith("/" + ns) for ns in no_split
            )
            if splittable and len(kids) > 1:
                queue = kids + queue
                placed = True
                break
            # Doesn't fit and can't split: close out this placement.
            pos += 1
        if not placed and pos >= len(order):  # pragma: no cover - disk is unbounded
            raise ValueError(f"{prefix} does not fit anywhere (size {size}).")

    if clean_result:
        device_map = _clean_device_map(device_map)
    return device_map


def _clean_device_map(device_map: dict[str, Placement], prefix: str = "") -> dict[str, Placement]:
    """Collapse sibling entries that share a placement (reference ``modeling.py:1173``)."""
    values = [v for k, v in device_map.items() if k == prefix or k.startswith(prefix + "/") or prefix == ""]
    if prefix and len(set(map(str, values))) == 1 and len(values) > 1:
        for k in [k for k in device_map if k == prefix or k.startswith(prefix + "/")]:
            del device_map[k]
        device_map[prefix] = values[0]
    children = {
        (k[len(prefix) + 1 :] if prefix else k).split("/")[0]
        for k in device_map
        if (k.startswith(prefix + "/") or prefix == "") and k != prefix
    }
    for child in sorted(children):
        _clean_device_map(device_map, prefix=f"{prefix}/{child}" if prefix else child)
    return device_map


def check_device_map(tree: Any, device_map: dict[str, Placement]) -> None:
    """Every leaf must be covered by exactly one device-map prefix (reference ``modeling.py:1556``)."""
    names = list(named_parameters(tree))
    uncovered = [
        n for n in names if not any(n == p or n.startswith(p + "/") or p == "" for p in device_map)
    ]
    if uncovered:
        raise ValueError(
            f"The device_map provided does not cover all parameters: {uncovered[:5]}"
            + ("..." if len(uncovered) > 5 else "")
        )


def placement_for(name: str, device_map: dict[str, Placement]) -> Placement:
    """Longest-prefix match of a leaf key path in a device map."""
    best, best_len = None, -1
    for prefix, placement in device_map.items():
        if prefix == "" or name == prefix or name.startswith(prefix + "/"):
            if len(prefix) > best_len:
                best, best_len = placement, len(prefix)
    if best is None:
        raise ValueError(f"{name} not covered by device_map")
    return best


# -------------------------------------------------------------------- checkpoint IO (sharded)
def save_sharded_checkpoint(
    tree: Any, save_dir: Union[str, Path], max_shard_size: Union[int, str] = "5GB"
) -> dict:
    """Write a HF-convention sharded safetensors checkpoint with an index json.

    Produces ``model.safetensors`` for a single shard, else ``model-00001-of-0000N.safetensors``
    + ``model.safetensors.index.json`` (``weight_map`` keyed by ``/``-joined paths). This is the
    format ``load_checkpoint_in_model`` streams.
    """
    from .serialization import save_pytree_safetensors

    save_dir = Path(save_dir)
    save_dir.mkdir(parents=True, exist_ok=True)
    limit = convert_file_size_to_int(max_shard_size)
    flat = named_parameters(tree)

    shards: list[dict[str, Any]] = [{}]
    shard_bytes = 0
    for name, leaf in flat.items():
        size = _leaf_size(leaf)
        if shard_bytes + size > limit and shards[-1]:
            shards.append({})
            shard_bytes = 0
        shards[-1][name] = leaf
        shard_bytes += size

    if len(shards) == 1:
        save_pytree_safetensors(shards[0], save_dir / SAFE_WEIGHTS_NAME)
        return {"weight_map": {k: SAFE_WEIGHTS_NAME for k in flat}}

    weight_map = {}
    total = sum(_leaf_size(v) for v in flat.values())
    for i, shard in enumerate(shards):
        fname = f"model-{i + 1:05d}-of-{len(shards):05d}.safetensors"
        save_pytree_safetensors(shard, save_dir / fname)
        for k in shard:
            weight_map[k] = fname
    index = {"metadata": {"total_size": total}, "weight_map": weight_map}
    with open(save_dir / SAFE_WEIGHTS_INDEX_NAME, "w") as f:
        json.dump(index, f, indent=2)
    return index


def _in_device_map(name: str, device_map) -> bool:
    return device_map is None or any(
        name == p or name.startswith(p + "/") or p == "" for p in device_map
    )


def _safetensors_np_dtype(tag: str):
    """Safetensors dtype tag → numpy dtype, extended types via ml_dtypes (jax bundles it)."""
    table = {
        "F64": np.float64, "F32": np.float32, "F16": np.float16,
        "I64": np.int64, "I32": np.int32, "I16": np.int16, "I8": np.int8,
        "U64": np.uint64, "U32": np.uint32, "U16": np.uint16, "U8": np.uint8,
        "BOOL": np.bool_,
    }
    if tag in table:
        return np.dtype(table[tag])
    import ml_dtypes

    ext = {"BF16": ml_dtypes.bfloat16, "F8_E4M3": ml_dtypes.float8_e4m3fn,
           "F8_E5M2": ml_dtypes.float8_e5m2}
    if tag in ext:
        return np.dtype(ext[tag])
    raise ValueError(f"Unsupported safetensors dtype tag {tag!r}")


def iter_safetensors(checkpoint_file: Union[str, Path], device_map=None):
    """Yield ``(name, tensor)`` one at a time as zero-copy read-only views into one mmap.

    The bounded-residency primitive of the big-model load path: the
    file is parsed directly (8-byte LE header length + JSON of
    ``{name: {dtype, shape, data_offsets}}``, the public safetensors layout), each tensor
    is a ``.view()`` into a single ``np.memmap`` — file-backed pages, no per-shard dict,
    no jax in the read path (materializing a whole file as device arrays first holds a
    second copy of every weight on the host).
    bf16/f8 come out as ml_dtypes views, which ``jax.device_put`` accepts directly.
    """
    with open(checkpoint_file, "rb") as f:
        header_len = int.from_bytes(f.read(8), "little")
        header = json.loads(f.read(header_len))
    header.pop("__metadata__", None)
    data_start = 8 + header_len
    raw = np.memmap(checkpoint_file, dtype=np.uint8, mode="r")
    for name, info in header.items():
        if not _in_device_map(name, device_map):
            continue
        dt = _safetensors_np_dtype(info["dtype"])
        begin, end = info["data_offsets"]
        view = raw[data_start + begin : data_start + end].view(dt)
        yield name, view.reshape(tuple(info["shape"]))


def load_state_dict(checkpoint_file: Union[str, Path], device_map=None) -> dict[str, np.ndarray]:
    """Load one safetensors file flat; lazy per-tensor filtering when a device_map is given.

    Reference analog: ``load_state_dict`` (``modeling.py:1615``). Values are zero-copy
    read-only memmap views (see :func:`iter_safetensors`) — copy before mutating.
    """
    return dict(iter_safetensors(checkpoint_file, device_map=device_map))


def load_checkpoint_in_model(
    abstract_tree: Any,
    checkpoint: Union[str, Path],
    device_map: Optional[dict[str, Placement]] = None,
    offload_folder: Optional[Union[str, Path]] = None,
    dtype=None,
    strict: bool = True,
) -> Any:
    """Stream a (possibly sharded) checkpoint into a placed params pytree.

    Reference analog: ``load_checkpoint_in_model`` (``modeling.py:1787``), with a tighter
    residency invariant than the reference's per-shard one (its README.md:39-46 bounds host
    RAM by max(largest shard, resident portion)): tensors stream ONE AT A TIME as memmap
    views (:func:`iter_safetensors`), so peak anonymous host RSS is the resident
    ("cpu"-placed, dtype-converted) portion plus O(one tensor) of conversion scratch —
    never a whole-shard dict, regardless of shard size. Placement per the device map:
    int ordinal → ``jax.device_put`` on that device, ``"cpu"`` → numpy in host RAM
    (a file-backed view when no dtype conversion is needed), ``"disk"`` → memmap offload
    store in ``offload_folder``. Enforced by ``tests/test_big_modeling.py::
    test_load_checkpoint_bounded_residency``.

    Returns a pytree with the structure of ``abstract_tree`` whose leaves are jax arrays, numpy
    arrays, or :class:`~accelerate_tpu.utils.offload.OffloadedWeight` handles.
    """
    import jax

    from .offload import offload_weight, save_offload_index

    checkpoint = Path(checkpoint)
    if checkpoint.is_dir():
        index_file = checkpoint / SAFE_WEIGHTS_INDEX_NAME
        if index_file.exists():
            with open(index_file) as f:
                index = json.load(f)
            shard_files = sorted(set(index["weight_map"].values()))
            shard_paths = [checkpoint / s for s in shard_files]
        else:
            single = checkpoint / SAFE_WEIGHTS_NAME
            if not single.exists():
                raise FileNotFoundError(f"No safetensors checkpoint found under {checkpoint}")
            shard_paths = [single]
    else:
        shard_paths = [checkpoint]

    expected = named_parameters(abstract_tree)
    devices = {i: d for i, d in enumerate(jax.local_devices())}
    offload_index: dict[str, dict] = {}
    loaded: dict[str, Any] = {}

    for shard in shard_paths:
        for name, value in iter_safetensors(shard, device_map=device_map):
            if name not in expected:
                if strict:
                    raise KeyError(f"Checkpoint key {name!r} not in model structure.")
                continue
            want = expected[name]
            if tuple(value.shape) != tuple(want.shape):
                raise ValueError(
                    f"Shape mismatch for {name}: checkpoint {tuple(value.shape)} vs model "
                    f"{tuple(want.shape)}"
                )
            value = _astype_np(value, dtype or want.dtype)
            placement = placement_for(name, device_map) if device_map else 0
            if placement == "disk":
                if offload_folder is None:
                    raise ValueError("device_map contains 'disk' but no offload_folder given.")
                loaded[name] = offload_weight(value, name, offload_folder, index=offload_index)
            elif placement == "cpu":
                loaded[name] = value
            else:
                device = placement if not isinstance(placement, int) else devices[placement]
                loaded[name] = jax.device_put(value, device)

    missing = set(expected) - set(loaded)
    if missing and strict:
        raise KeyError(f"Missing keys in checkpoint: {sorted(missing)[:5]}")
    if offload_index:
        save_offload_index(offload_index, offload_folder)

    if missing:
        # Partial (non-strict) load: return what was found as a nested dict.
        return unflatten_to_nested_dict(loaded)
    # Rebuild the original container types (lists etc.) from the abstract tree's structure.
    treedef = jax.tree_util.tree_structure(abstract_tree)
    return jax.tree_util.tree_unflatten(treedef, [loaded[name] for name in expected])


def _astype_np(value: np.ndarray, target_dtype) -> np.ndarray:
    """Numpy-side dtype conversion honoring bf16 (via ml_dtypes, which jax bundles)."""
    nd = np.dtype(target_dtype)  # ml_dtypes registers bfloat16 etc. with numpy
    return value if value.dtype == nd else value.astype(nd)
