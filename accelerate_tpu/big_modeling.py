"""Big-model inference (L6): run models larger than one chip's HBM.

TPU-native re-design of reference ``big_modeling.py`` + ``hooks.py`` (/root/reference/src/
accelerate/big_modeling.py:58,170,260,306,511; hooks.py:226,329,374):

- ``init_empty_weights`` (:58) patched torch meta-device init → here ``jax.eval_shape`` over the
  model's init function: a pytree of ``ShapeDtypeStruct`` with zero bytes allocated.
- ``dispatch_model`` (:306) + ``AlignDevicesHook`` (hooks.py:226) intercepted ``module.forward``
  to page weights HBM↔host per call → here a functional :class:`DispatchedParams` store plus a
  :func:`stream_blocks` executor that **double-buffers host→device transfers on a background
  thread** while the previous block computes on the MXU. The reference loads layer weights
  synchronously in ``pre_forward`` (hooks.py:329) — the prefetch pipeline is the design reason
  this path can beat its disk-offload numbers (BASELINE.md).
- ``load_checkpoint_and_dispatch`` (:511) → same-name function: infer/validate a device map,
  stream safetensors shards straight to their placement.

Placements: int jax-device ordinal | ``"cpu"`` (host numpy) | ``"disk"`` (memmap store).
"""

from __future__ import annotations

import contextlib
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Any, Callable, Optional, Union

import numpy as np

from .utils.modeling import (
    check_device_map,
    compute_module_sizes,
    get_balanced_memory,
    get_max_memory,
    infer_auto_device_map,
    load_checkpoint_in_model,
    named_parameters,
    placement_for,
)
from .utils.offload import OffloadedWeight, as_jax_array, offload_state_dict
from .utils.serialization import unflatten_to_nested_dict

__all__ = [
    "init_empty_weights",
    "init_on_device",
    "cpu_offload",
    "cpu_offload_with_hook",
    "disk_offload",
    "dispatch_model",
    "load_checkpoint_and_dispatch",
    "DispatchedParams",
    "stream_blocks",
    "consume_block",
    "UserOffloadHook",
]


# ----------------------------------------------------------------------------- abstract init
def init_empty_weights(init_fn: Callable, *args, **kwargs) -> Any:
    """Build a model's parameter *structure* without allocating any memory.

    Reference analog: ``init_empty_weights`` (``big_modeling.py:58``) — a context manager that
    reroutes ``nn.Parameter`` allocation to the meta device. JAX already has the right
    primitive: ``jax.eval_shape`` traces ``init_fn`` abstractly, so this is a function, not a
    patch::

        abstract = init_empty_weights(llama.init_params, cfg)

    Returns a pytree of ``jax.ShapeDtypeStruct``.
    """
    import jax

    return jax.eval_shape(lambda: init_fn(*args, **kwargs))


@contextlib.contextmanager
def init_on_device(device):
    """Run param initializers with jax's default device pinned (reference ``:94``)."""
    import jax

    with jax.default_device(device):
        yield


# --------------------------------------------------------------------------- dispatch store
class DispatchedParams:
    """A placed parameter store: flat ``{key_path: storage}`` + the device map that placed it.

    ``storage`` per leaf is a jax array (already on its device), a numpy array (host RAM), or an
    :class:`OffloadedWeight` (disk). :meth:`fetch` materializes any key-path prefix onto a target
    device as a nested pytree — asynchronously when called via :func:`stream_blocks`.
    """

    def __init__(self, weights: dict[str, Any], device_map: dict[str, Any], main_device=None):
        import jax

        self.weights = OrderedDict(weights)
        self.device_map = dict(device_map)
        self.main_device = main_device if main_device is not None else jax.local_devices()[0]

    @classmethod
    def from_tree(cls, tree: Any, device_map: dict[str, Any], offload_dir=None, main_device=None):
        """Place an in-memory params pytree according to ``device_map``."""
        import jax

        check_device_map(tree, device_map)
        devices = jax.local_devices()
        flat = named_parameters(tree)
        weights: dict[str, Any] = {}
        disk_items: dict[str, Any] = {}
        for name, leaf in flat.items():
            placement = placement_for(name, device_map)
            if placement == "disk":
                disk_items[name] = np.asarray(leaf)
            elif placement == "cpu":
                weights[name] = np.asarray(leaf)
            else:
                device = devices[placement] if isinstance(placement, int) else placement
                weights[name] = jax.device_put(leaf, device)
        if disk_items:
            if offload_dir is None:
                raise ValueError("device_map contains 'disk' but no offload_dir given.")
            index = offload_state_dict(offload_dir, disk_items)
            for name in disk_items:
                info = index[name]
                weights[name] = OffloadedWeight(name, offload_dir, info["dtype"], tuple(info["shape"]))
        # Preserve original ordering.
        ordered = OrderedDict((name, weights[name]) for name in flat)
        return cls(ordered, device_map, main_device=main_device)

    def prefixes(self, depth: int = 1) -> list[str]:
        out, seen = [], set()
        for name in self.weights:
            p = "/".join(name.split("/")[:depth])
            if p not in seen:
                seen.add(p)
                out.append(p)
        return out

    def subkeys(self, prefix: str) -> list[str]:
        if prefix == "":
            return list(self.weights)
        return [k for k in self.weights if k == prefix or k.startswith(prefix + "/")]

    def fetch(self, prefix: str, device=None) -> Any:
        """Materialize the subtree under ``prefix`` on ``device`` (default: main device).

        The AlignDevicesHook ``pre_forward`` analog (reference ``hooks.py:329``) — but returns a
        fresh pytree instead of mutating a module, so there is no ``post_forward`` re-offload
        step: the previous block's device arrays are simply dropped and freed by reference
        counting once its computation is consumed.
        """
        import jax

        device = device or self.main_device
        sub: dict[str, Any] = {}
        for key in self.subkeys(prefix):
            value = self.weights[key]
            if isinstance(value, OffloadedWeight):
                arr = as_jax_array(value)
                value = jax.device_put(arr, device)
            elif isinstance(value, np.ndarray):
                value = jax.device_put(value, device)
            elif hasattr(value, "sharding"):  # jax array, possibly on another device
                # Already on the target device: return the store's own array UNCHANGED.
                # device_put can return a fresh wrapper aliasing the same buffer, and
                # consume_block's owned-leaf protection is by object identity — an alias
                # would be deleted, killing the resident weight for every later pass.
                try:
                    on_target = value.devices() == {device}
                except Exception:
                    on_target = False
                if not on_target:
                    value = jax.device_put(value, device)
            rel = key[len(prefix) + 1 :] if prefix and key != prefix else ("" if key == prefix else key)
            sub[rel] = value
        if list(sub) == [""]:
            return sub[""]
        nested = unflatten_to_nested_dict(sub)
        return _listify_int_dicts(nested)

    def memory_footprint(self) -> dict[str, int]:
        """Bytes resident per placement kind — mirrors the reference README's memory claims."""
        sizes = {"device": 0, "cpu": 0, "disk": 0}
        for value in self.weights.values():
            n = int(np.prod(value.shape)) if value.shape else 1
            if isinstance(value, OffloadedWeight):
                itemsize = 2 if value.dtype in ("bfloat16", "float16") else np.dtype(value.dtype).itemsize
                sizes["disk"] += n * itemsize
            elif isinstance(value, np.ndarray):
                sizes["cpu"] += value.nbytes
            else:
                sizes["device"] += n * np.dtype(value.dtype).itemsize
        return sizes


def _listify_int_dicts(node):
    """Convert ``{'0': x, '1': y}`` dicts back into lists (pytree lists flatten to indices)."""
    if isinstance(node, dict):
        conv = {k: _listify_int_dicts(v) for k, v in node.items()}
        if conv and all(k.isdigit() for k in conv):
            return [conv[str(i)] for i in range(len(conv))]
        return conv
    return node


# ------------------------------------------------------------------------ streaming executor
def stream_blocks(
    dispatched: DispatchedParams,
    block_prefixes: list[str],
    device=None,
    prefetch: int = 2,
):
    """Yield ``(prefix, on_device_params)`` with background double-buffered prefetch.

    While block *i* computes, a worker thread reads block *i+1* (memmap → host → HBM via
    ``jax.device_put``), hiding host/disk latency behind MXU time. ``prefetch`` bounds
    resident off-schedule blocks so HBM use stays ≈ ``prefetch`` blocks.

    The worker BLOCKS until its transfer has actually landed (``block_until_ready``) —
    this is the backpressure that makes the bound real. ``jax.device_put`` is
    asynchronous: without the fence, a host-driven consumer loop (whose per-block
    compute dispatch is also asynchronous) laps the transport and every remaining
    block's staged host copy + HBM allocation piles up in flight (a gpt-neox-20b
    host-streamed decode was OOM-killed at 130 GB RSS exactly this way); with the fence
    the python loop advances at transfer speed and in-flight memory stays ≈ ``prefetch``
    blocks on both sides.
    """
    import jax

    device = device or dispatched.main_device

    def fetch_sync(p):
        params = dispatched.fetch(p, device)
        jax.block_until_ready(params)  # graftlint: disable=host-sync-in-hot-path(prefetch handoff fence; blocks the worker thread, not the compute stream)
        return params

    with ThreadPoolExecutor(max_workers=1) as pool:
        futures = []
        it = iter(block_prefixes)
        try:
            for _ in range(max(1, prefetch)):
                p = next(it)
                futures.append((p, pool.submit(fetch_sync, p)))
        except StopIteration:
            pass
        while futures:
            prefix, fut = futures.pop(0)
            params = fut.result()
            nxt = next(it, None)
            if nxt is not None:
                futures.append((nxt, pool.submit(fetch_sync, nxt)))
            yield prefix, params


def consume_block(
    x_like: Any, block_params: Any,
    dispatched: Optional[DispatchedParams] = None, prefix: Optional[str] = None,
) -> None:
    """Fence compute through this block, then free the block's device buffers NOW.

    The companion discipline to :func:`stream_blocks` for host-driven streamed loops:
    after dispatching block *i*'s compute, call ``consume_block(x, layer, dispatched,
    prefix)`` before moving on. It (1) waits for ``x_like`` — block *i*'s compute (and
    therefore its transfer) is then complete — and (2) explicitly ``delete()``s the
    block's param buffers.

    Dropping the python reference is not enough when the async frontier runs ahead of
    the garbage collector. The fence in :func:`stream_blocks` bounds the transfer side;
    THIS call is the compute-side complement: explicit deletion bounds retention to
    ~prefetch blocks regardless of GC behavior, and transfer/compute overlap is
    preserved because the prefetch worker keeps fetching while the consumer waits.

    ``dispatched``/``prefix``: for DEVICE-RESIDENT placements ``fetch`` returns the
    store's own array UNCHANGED — deliberately, not via ``device_put``, which may
    return a fresh wrapper aliasing the same buffer and so defeat the id()-based
    ownership check below — and deleting it
    would corrupt the resident weights for every later pass — passing the store lets
    the fence skip any leaf the store itself owns. Streamed (host/disk) leaves are
    always fresh per-fetch copies and safe to free."""
    import jax

    jax.block_until_ready(x_like)  # graftlint: disable=host-sync-in-hot-path(per-block residency fence of the streamed executor: the next block's buffers may only land once this one's are freed)
    owned: set = set()
    if dispatched is not None and prefix is not None:
        for key in dispatched.subkeys(prefix):
            stored = dispatched.weights[key]
            if not isinstance(stored, (np.ndarray, OffloadedWeight)):
                owned.add(id(stored))
    for leaf in jax.tree_util.tree_leaves(block_params):
        if hasattr(leaf, "delete") and id(leaf) not in owned:
            try:
                leaf.delete()
            except Exception:  # pragma: no cover - already deleted / not deletable
                pass


# ------------------------------------------------------------------------- user-facing API
def cpu_offload(tree: Any, main_device=None) -> DispatchedParams:
    """Keep every weight in host RAM; stream to device per block (reference ``:170``)."""
    device_map = {p: "cpu" for p in _top_prefixes(tree)}
    return DispatchedParams.from_tree(tree, device_map, main_device=main_device)


def disk_offload(tree: Any, offload_dir: Union[str, Path], main_device=None) -> DispatchedParams:
    """Spill every weight to the memmap store; stream per block (reference ``:260``)."""
    device_map = {p: "disk" for p in _top_prefixes(tree)}
    return DispatchedParams.from_tree(tree, device_map, offload_dir=offload_dir, main_device=main_device)


class UserOffloadHook:
    """Manual-control offload handle for one model's params (reference ``hooks.py:726``).

    ``fetch()`` returns a device-resident copy of the params (transferring from the
    pinned host copy on first call, cached until offloaded); ``offload()`` frees the
    HBM copy NOW — jax buffer ``delete()``, not GC — invalidating every previously
    fetched tree (fetch again for a fresh one). A ``prev_module_hook`` is offloaded
    automatically when this hook fetches, which is what chains a multi-model pipeline
    through one chip's HBM."""

    def __init__(self, host_tree: Any, main_device=None, prev_module_hook: "UserOffloadHook" = None):
        self._host = host_tree
        self._main_device = main_device
        self._prev = prev_module_hook
        self._on_device: Any = None

    def fetch(self) -> Any:
        import jax

        if self._prev is not None:
            self._prev.offload()
        if self._on_device is None:
            device = self._main_device or jax.devices()[0]
            self._on_device = jax.device_put(self._host, device)
        return self._on_device

    def offload(self) -> None:
        if self._on_device is not None:
            import jax

            for leaf in jax.tree_util.tree_leaves(self._on_device):
                if hasattr(leaf, "delete"):
                    leaf.delete()
            self._on_device = None


def cpu_offload_with_hook(
    tree: Any, main_device=None, prev_module_hook: Optional[UserOffloadHook] = None,
) -> tuple[Callable[[], Any], UserOffloadHook]:
    """Offload a whole model's params to host RAM with MANUAL reload control — the
    multi-model-pipeline variant of :func:`cpu_offload` (reference ``big_modeling.py:216``).

    Unlike :func:`cpu_offload` (which streams block-by-block every forward), the params
    move to the device **whole** on ``fetch()`` and STAY until ``hook.offload()`` — the
    right trade when a model is invoked many times in a row before the pipeline moves
    on (the reference's example is exactly this). Chain hooks via ``prev_module_hook``
    so fetching stage N+1 evicts stage N::

        fetch_1, hook_1 = cpu_offload_with_hook(encoder_params)
        fetch_2, hook_2 = cpu_offload_with_hook(unet_params, prev_module_hook=hook_1)
        fetch_3, hook_3 = cpu_offload_with_hook(vae_params,  prev_module_hook=hook_2)
        enc = encode(fetch_1(), batch)       # encoder in HBM
        for _ in range(steps):
            x = denoise(fetch_2(), enc)      # first fetch_2() evicts the encoder
        img = decode(fetch_3(), x)           # evicts the unet
        hook_3.offload()

    Returns ``(fetch, hook)``: ``fetch()`` is the device-params getter to pass into the
    model's functional forward; ``hook`` exposes ``offload()`` (and is what you thread
    into the next stage's ``prev_module_hook``)."""
    import jax

    host = jax.tree_util.tree_map(lambda x: np.asarray(x), tree)
    hook = UserOffloadHook(host, main_device=main_device, prev_module_hook=prev_module_hook)
    return hook.fetch, hook


def dispatch_model(
    tree: Any,
    device_map: Union[str, dict],
    max_memory: Optional[dict] = None,
    offload_dir=None,
    no_split_prefixes: Optional[list[str]] = None,
    main_device=None,
) -> DispatchedParams:
    """Place a params pytree per a device map (``"auto"``/``"balanced"`` infer one).

    Reference analog: ``dispatch_model`` (``big_modeling.py:306``).
    """
    if isinstance(device_map, str):
        if device_map not in ("auto", "balanced", "balanced_low_0", "sequential"):
            raise ValueError(f"Unknown device_map policy {device_map!r}")
        if device_map.startswith("balanced"):
            max_memory = get_balanced_memory(
                tree, max_memory, low_zero=device_map.endswith("low_0")
            )
        device_map = infer_auto_device_map(
            tree, max_memory=max_memory, no_split_prefixes=no_split_prefixes
        )
    return DispatchedParams.from_tree(tree, device_map, offload_dir=offload_dir, main_device=main_device)


def load_checkpoint_and_dispatch(
    abstract_tree: Any,
    checkpoint: Union[str, Path],
    device_map: Union[str, dict, None] = "auto",
    max_memory: Optional[dict] = None,
    offload_dir=None,
    no_split_prefixes: Optional[list[str]] = None,
    dtype=None,
    main_device=None,
) -> DispatchedParams:
    """Abstract structure + checkpoint on disk → placed, ready-to-stream params.

    Reference analog: ``load_checkpoint_and_dispatch`` (``big_modeling.py:511``). Never holds
    more than one shard of the checkpoint in host memory (shard-streaming load), and tensors
    destined for ``"disk"`` flow checkpoint→memmap without a device hop.
    """
    if isinstance(device_map, str):
        if device_map not in ("auto", "balanced", "balanced_low_0", "sequential"):
            raise ValueError(f"Unknown device_map policy {device_map!r}")
        if device_map.startswith("balanced"):
            max_memory = get_balanced_memory(
                abstract_tree, max_memory, low_zero=device_map.endswith("low_0")
            )
        device_map = infer_auto_device_map(
            abstract_tree, max_memory=max_memory, no_split_prefixes=no_split_prefixes, dtype=dtype
        )
    placed = load_checkpoint_in_model(
        abstract_tree, checkpoint, device_map=device_map, offload_folder=offload_dir, dtype=dtype
    )
    flat_placed = named_parameters(placed)
    weights = OrderedDict(flat_placed)
    return DispatchedParams(weights, device_map or {"": 0}, main_device=main_device)


def _top_prefixes(tree: Any) -> list[str]:
    out, seen = [], set()
    for name in named_parameters(tree):
        p = name.split("/")[0]
        if p not in seen:
            seen.add(p)
            out.append(p)
    return out
