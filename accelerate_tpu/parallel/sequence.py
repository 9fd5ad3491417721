"""Sequence/context parallelism (first-class — the reference's biggest gap, SURVEY.md §5).

Three interchangeable strategies over the ``sp`` mesh axis, all exact:

- **ring**: ``ops/ring_attention.py`` — kv rotates around the ICI ring; O(S_local²·n) compute,
  O(S_local) memory per device, comm overlapped. Best for very long context.
- **ulysses**: all-to-all head↔sequence reshard (DeepSpeed-Ulysses): each device attends the
  FULL sequence for H/n of the heads; two all-to-alls per attention. Best when heads ≥ ring
  size and moderate context.
- **allgather**: naive — all-gather kv along ``sp`` and attend locally. What GSPMD does for a
  seq-sharded attention by default; kept as the fallback and correctness oracle.

``sequence_parallel_attention`` dispatches by mode and is shard_map-ready; wrap it with
``make_sp_attention`` to embed into a GSPMD-jitted model.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.flash_attention import flash_attention
from ..ops.ring_attention import ring_attention
from ..utils.constants import SEQUENCE_AXIS
from ..utils.jax_compat import axis_size as _axis_size, shard_map as _shard_map

__all__ = [
    "ulysses_attention",
    "allgather_attention",
    "sequence_parallel_attention",
    "make_sp_attention",
]


def _repeat_gqa(q, k, v):
    H, K = q.shape[2], k.shape[2]
    if H != K:
        reps = H // K
        k = jnp.repeat(k, reps, axis=2)
        v = jnp.repeat(v, reps, axis=2)
    return q, k, v


def _a2a_ppermute(x, axis_name, split_axis: int, concat_axis: int):
    """``lax.all_to_all(tiled=True)`` decomposed into n-1 neighbor ``ppermute`` hops.

    Semantically identical (member i's output chunk k along ``concat_axis`` is member
    k's chunk i along ``split_axis``) and bandwidth-equivalent on a ring ICI topology
    (an all-to-all decomposes into ring steps anyway). Exists because the all_to_all
    PRIMITIVE fails to finish lowering inside the hand-scheduled pipeline replay's
    per-tick VJP (>9 min; ``ppermute`` — which the ring schedule and the replay itself
    use — lowers in seconds): this is the workaround that lets ulysses run under
    schedule='1f1b' and virtual stages.
    """
    n = _axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    chunks = jnp.stack(jnp.split(x, n, axis=split_axis))  # [n, ...chunk...]
    # Rotate the full stack around the ring. After hop s, member i holds the stack that
    # ORIGINATED at k = (i - s) mod n; the all_to_all contract (out chunk k = member
    # k's chunk i) means we take the visiting stack's row i and file it under k. The
    # s=0 row is local (no comm), so exactly n-1 hops run. Bandwidth: (n-1) hops x
    # full stack ≈ 2x a minimal-distance ring all-to-all — fine for the
    # lowering-workaround role; the primitive stays the default elsewhere.
    out0 = jax.lax.dynamic_update_index_in_dim(
        jnp.zeros_like(chunks), jnp.take(chunks, idx, axis=0), idx, axis=0
    )

    def body(carry, s):
        visiting, out = carry
        visiting = lax.ppermute(visiting, axis_name, [(i, (i + 1) % n) for i in range(n)])
        origin = (idx - s) % n
        row = jnp.take(visiting, idx, axis=0)
        out = jax.lax.dynamic_update_index_in_dim(out, row, origin, axis=0)
        return (visiting, out), None

    (_, out), _ = lax.scan(body, (chunks, out0), jnp.arange(1, n))
    return jnp.concatenate([out[i] for i in range(n)], axis=concat_axis)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = SEQUENCE_AXIS,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    window: int = 0,
    softcap: float = 0.0,
    segment_ids: Optional[jax.Array] = None,
    via_ppermute: bool = False,
) -> jax.Array:
    """DeepSpeed-Ulysses: all-to-all seq↔head reshard, then full-sequence flash attention.

    ``via_ppermute`` replaces the ``lax.all_to_all`` primitive with the
    ppermute-decomposed equivalent (``_a2a_ppermute``) — the form that lowers inside
    the hand-scheduled pipeline replay where the primitive hangs (mode
    "ulysses_ppermute" in the dispatchers).

    Inside shard_map: q/k/v [B, S_local, H, hd] (seq-sharded) → out [B, S_local, H, hd].
    Requires n_heads % axis_size == 0.

    GQA: when the kv-head count divides the sp size's head split (K % n == 0), the
    UNREPEATED kv rides the all-to-all — each device ends up with H/n q heads and K/n kv
    heads whose group mapping lines up exactly with the flash kernels' native h → h//(H/K)
    indexing, so the payload shrinks by H/K vs repeating. Otherwise (K < n after split)
    kv is repeated up to H first — correct, just bigger.
    """
    n = _axis_size(axis_name)
    H, K = q.shape[2], k.shape[2]
    if H % n != 0:
        raise ValueError(f"ulysses needs n_heads ({H}) divisible by sp size ({n})")
    if K % n != 0:
        q, k, v = _repeat_gqa(q, k, v)
    # [B, S_loc, H, hd] → [B, S_global, H/n, hd]: split heads, gather sequence.
    a2a = (
        (lambda x, sa, ca: _a2a_ppermute(x, axis_name, sa, ca)) if via_ppermute
        else (lambda x, sa, ca: lax.all_to_all(
            x, axis_name, split_axis=sa, concat_axis=ca, tiled=True))
    )
    qg = a2a(q, 2, 1)
    kg = a2a(k, 2, 1)
    vg = a2a(v, 2, 1)
    # Packing: after the seq->head reshard every device holds the FULL sequence, so the
    # full segment-id row (one cheap [B, S_loc] int all-gather) keeps same-segment
    # masking exact in the local flash call.
    seg_full = (
        lax.all_gather(segment_ids, axis_name, axis=1, tiled=True)
        if segment_ids is not None else None
    )
    og = flash_attention(qg, kg, vg, causal=causal, sm_scale=sm_scale, interpret=interpret,
                         window=window, softcap=softcap, segment_ids=seg_full)
    # back: split sequence, gather heads.
    return a2a(og, 1, 2)


def allgather_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = SEQUENCE_AXIS,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    window: int = 0,
    softcap: float = 0.0,
    segment_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """Naive SP: all-gather kv, attend local q chunk against the full sequence.

    GQA needs no repeat on this path: the flash kernels take unrepeated [B, S, K, hd] kv,
    so the all-gather moves H/K× fewer bytes over ICI."""
    idx = lax.axis_index(axis_name)
    S_local = q.shape[1]
    kg = lax.all_gather(k, axis_name, axis=1, tiled=True)
    vg = lax.all_gather(v, axis_name, axis=1, tiled=True)
    # Packing: local q segment slice vs the all-gathered full kv segment row — the
    # (q_seg, kv_seg) pair form of the kernels keeps same-segment masking exact.
    segments = None
    if segment_ids is not None:
        seg_full = lax.all_gather(segment_ids, axis_name, axis=1, tiled=True)
        segments = (segment_ids, seg_full)
    if not causal and not window and segments is None:
        return flash_attention(q, kg, vg, causal=False, sm_scale=sm_scale, interpret=interpret,
                               softcap=softcap)
    # Causal (or windowed) with a global row offset: flash_attention assumes q starts at
    # position 0, so route through the raw kernel path with this shard's global offset —
    # the band/causal masks both use global positions.
    from ..ops.flash_attention import _fit_block, _flash_bhsd_offset

    return _flash_bhsd_offset(
        q, kg, vg, q_offset=idx * S_local, causal=causal, sm_scale=sm_scale,
        interpret=interpret, window=window, softcap=softcap, segments=segments,
    )


def sequence_parallel_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mode: str = "ring",
    axis_name: str = SEQUENCE_AXIS,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    window: int = 0,
    softcap: float = 0.0,
    segment_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """Dispatch by mode ("ring" | "ulysses" | "allgather"); shard_map-context required.

    ``window``/``softcap`` flow into the flash kernels with GLOBAL position offsets, so
    sliding-window (Mistral) and score-capped (Gemma) attention work across the
    sequence-sharded mesh axis too."""
    kwargs = dict(axis_name=axis_name, causal=causal, sm_scale=sm_scale,
                  interpret=interpret, window=window, softcap=softcap,
                  segment_ids=segment_ids)
    if mode == "ring":
        return ring_attention(q, k, v, **kwargs)
    if mode == "ulysses":
        return ulysses_attention(q, k, v, **kwargs)
    if mode == "ulysses_ppermute":
        return ulysses_attention(q, k, v, via_ppermute=True, **kwargs)
    if mode == "allgather":
        return allgather_attention(q, k, v, **kwargs)
    raise ValueError(f"unknown sequence-parallel mode {mode!r}")


def make_sp_attention(mesh, mode: str = "ring", axis_name: str = SEQUENCE_AXIS, causal: bool = True,
                      window: int = 0, softcap: float = 0.0, sm_scale: Optional[float] = None):
    """Wrap ``sequence_parallel_attention`` for use inside a GSPMD-jitted model.

    Returns ``attn(q, k, v) -> o`` over GLOBAL [B, S, H, hd] arrays. The shard_map is
    manual over EVERY mesh axis — the flash kernels inside are Mosaic custom calls, which
    GSPMD cannot partition, so nothing may be left automatic around them: the sequence
    rides ``sp``, the batch the batch axes, and (ring/allgather) the heads ``tp``
    (``ops._common.attention_shard_spec``). Called from inside somebody else's manual
    region it adds only ``sp`` to the manual set, as nesting requires.
    """
    from jax.sharding import PartitionSpec as P

    from ..ops._common import attention_shard_spec

    def attn(q, k, v, segment_ids=None):
        nested = bool(mesh.manual_axes)
        spec = P(None, axis_name, None, None) if nested else attention_shard_spec(
            mesh, q, k, seq_axis=axis_name, heads=mode in ("ring", "allgather")
        )
        seg_spec = P(spec[0], axis_name)
        fn = functools.partial(
            sequence_parallel_attention, mode=mode, axis_name=axis_name, causal=causal,
            window=window, softcap=softcap, sm_scale=sm_scale,
        )
        # Packing: the GLOBAL [B, S] segment ids shard along sp like the sequence; each
        # mode re-derives what it needs (ring rotates the kv slice, ulysses/allgather
        # gather the full row) from its local slice.
        packed = segment_ids is not None
        mapped = _shard_map(
            (lambda q, k, v, seg: fn(q, k, v, segment_ids=seg)) if packed else fn,
            mesh=mesh,
            in_specs=(spec, spec, spec) + ((seg_spec,) if packed else ()),
            out_specs=spec,
            axis_names={axis_name} if nested else set(mesh.axis_names),
            # pallas_call out_shapes don't carry vma annotations; skip the check.
            check_vma=False,
        )
        return mapped(q, k, v, segment_ids) if packed else mapped(q, k, v)

    return attn
