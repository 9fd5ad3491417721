"""FlashAttention-2 for TPU in Pallas (forward + backward custom VJP).

The reference outsources attention entirely to torch/CUDA libraries; on TPU this kernel is the
framework's hot-path attention (SURVEY.md §7: "Pallas flash/splash attention"). Standard
online-softmax tiling: the (S×T) score matrix never materializes in HBM — per-block partial
maxima/sums ride in VMEM scratch across the kv-grid dimension (FlashAttention-2 schedule).

Layout: q [B, H, S, hd], k/v [B, K, T, hd] with K dividing H (the public wrapper handles the
user-facing [B, S, H, hd] layout). GQA is native: the kernels' BlockSpec index maps send q
head h to kv head h // (H//K), and the dk/dv kernel accumulates each kv head's gradient over
its whole query group in VMEM — repeated K/V never exist in HBM. Sequence lengths are padded
to block multiples; padded keys are masked via global column indices, padded query rows
sliced off by the wrapper.

**Position offsets**: the kernels take traced ``q_offset``/``kv_offset`` scalars giving the
global position of the local block — this is what lets ``ops/ring_attention.py`` reuse
these exact kernels per ring step with correct cross-device causal masking. The raw ``_fwd`` /
``_bwd_dq`` / ``_bwd_dkv`` entry points (returning/consuming lse and delta) are the building
blocks for the ring; ``flash_attention`` is the single-device public API. The serving
prefill is the offsets' second caller (``models/common.py::cached_prefill_attention`` through
``_flash_bhsd_offset``): a chunk of queries at ``q_offset`` = the cache's write index against
the band of its row cache at ``kv_offset``, S != T, the cache's valid mask as the
``(q_seg, kv_seg)`` pair — the forward kernel alone, named ``flash_fwd`` in the prefill programs.

**A per-pair mask** (``_fwd(..., mask=)``, through ``_flash_bhsd_offset``): an int8 [B, S, T]
operand, non-zero where query ``s`` may attend key ``t``, shared by every head — a learned
sparse selection's per-query key set in a serving prefill chunk (``models/deepseek.py``, the
grouped-query kind). It is a STATIC specialisation of the forward kernel builder, as
``window`` and ``has_segments`` are: a call without it builds the kernel it built before,
under the name it had; a call with it builds ``flash_fwd_masked`` — one more [block_q,
block_k] operand on the kv walk's index map, ANDed into the tile mask, and no tile is
interior. Forward only (no VJP): prefill does not differentiate.

**The grids walk the band, not the rectangle** (``_BandWalk``). Under ``causal`` AND a
``window`` an outer tile needs at most ⌈(window + block − 2) / block⌉ + 1 inner tiles
(10 of 16 at 8192 tokens under a 4096 window with tiles of 512), and that — static — is
the inner grid dimension's extent: the kv tiles of a q tile in ``flash_fwd`` and
``flash_bwd_dq``, ``reps ×`` the q tiles of a kv tile in ``flash_bwd_dkv``. The two offsets
are ONE scalar-prefetch operand (``pltpu.PrefetchScalarGridSpec``), so the BlockSpec index
maps place each walk on the band: step ``t`` stands for tile
``last(outer, offsets) − (extent − 1) + t``, and the kernel derives the same tile index
for its masks. Tiles outside the band are not visited. A step that still falls outside
it (a causal triangle's first rows, the band's ragged ends, a ring step whose whole kv
block lies in the future or behind the window; with one bound only the extent stays the
rectangle's) comes BEFORE the walk's needed steps and asks for the first block they
need: the pipeline holds it when they come to it, so such a step fetches nothing and
computes nothing, and every walk's last step computes — the next walk's first blocks
arrive behind it (on a TPU v5e a walk that ended on idle steps stalled on them: 3.5 ms
of a 25.8 ms dq call at [4, 32, 8192, 128]). Init and finalize stay on the walk's first
and last step, so an outer tile with NO needed step still writes zeros (``lse`` =
``_NEG_INF``) — the ring merges those.

TPU-specific structure (the same three choices the official jax flash kernel makes):

- **Lane-replicated softmax state.** The running max ``m`` and sum ``l`` live in VMEM as
  [block_q, 128] with every lane carrying the same value, so the per-step rescale math runs
  on full native (8,128) VPU registers and broadcasting into the [block_q, block_k] score
  tile is a cheap ``jnp.tile`` of a native register instead of a 1-lane → 128-lane relayout.
  The backward kernels read lse/delta lane-replicated the same way.
- **Mask-free interior tiles.** For causal attention only the tiles the diagonal actually
  crosses need the iota row/col mask; tiles entirely below the diagonal (the majority at
  long S) skip mask construction, the select, and the zero-fill entirely — splash-attention
  style tile classing, decided per grid step from the prefetched offsets.
- **Grid semantics + cost estimate.** (batch, head, q-block) grid dimensions are declared
  PARALLEL (only the kv dimension carries scratch state and stays ARBITRARY), and each
  ``pallas_call`` carries a ``pl.CostEstimate`` that counts the band's tiles, so XLA's
  scheduler sees the real arithmetic intensity. ``ACCEL_FLASH_DIMSEM=0`` disables the
  semantics for A/B measurement.

Runs in interpreter mode on CPU (tests) and compiled on TPU. Block sizes default to 512×512
(see ``_DEFAULT_BLOCK_Q/K``); hd should be a multiple of 128 for peak efficiency (llama3:
hd=128). Sweep overrides: ACCEL_FLASH_BLOCK_Q / ACCEL_FLASH_BLOCK_K.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import LANES as _LANES
from ._common import interpret_default as _interpret_default
from ._common import lane_tile as _lane_tile

__all__ = ["flash_attention"]

_NEG_INF = -1e30


# Default tile sizes. The grid iterates sequentially on the TensorCore, so per-step fixed
# overhead (semaphores, block DMA setup) is paid nq*nk times per (batch, head): 128x128 tiles
# at S=2048 mean 256 steps/head of mostly overhead. At 512x512 the working set (q/k/v
# 3x128KB bf16 at hd=128 + fp32 acc/s ~1.3MB) stays well under VMEM.
# Env overrides allow per-chip tuning without code changes.
def _env_block(name: str, default: int) -> int:
    raw = os.environ.get(name, "")
    try:
        return int(raw) if raw else default
    except ValueError:
        import warnings

        warnings.warn(f"{name}={raw!r} is not an int; using default {default}")
        return default


_DEFAULT_BLOCK_Q = _env_block("ACCEL_FLASH_BLOCK_Q", 512)
_DEFAULT_BLOCK_K = _env_block("ACCEL_FLASH_BLOCK_K", 512)


def _dim_semantics(n_parallel: int, n_arbitrary: int):
    """Mosaic grid-dimension semantics: the leading (batch/head/row-block) dims carry no
    scratch state and may be reordered/pipelined freely (PARALLEL); the trailing dims
    accumulate into VMEM scratch across iterations and must stay sequential (ARBITRARY).
    Default ON (the official jax flash kernel ships this unconditionally);
    ACCEL_FLASH_DIMSEM=0 turns it off for A/B measurement."""
    if os.environ.get("ACCEL_FLASH_DIMSEM", "1") == "0":
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel + ("arbitrary",) * n_arbitrary
    )


def _cost(flops: float, bytes_accessed: float, transcendentals: float):
    return pl.CostEstimate(
        flops=int(flops), bytes_accessed=int(bytes_accessed),
        transcendentals=int(transcendentals),
    )


def _offsets(q_offset, kv_offset) -> jax.Array:
    """The two traced positions as ONE int32[2] scalar-prefetch operand: the index maps read
    them (to place each walk on the band) before the kernel body does."""
    return jnp.stack([jnp.asarray(q_offset, jnp.int32).reshape(()),
                      jnp.asarray(kv_offset, jnp.int32).reshape(())])


class _BandWalk:
    """How an inner grid dimension walks the band. An outer tile at positions
    p .. p + block_outer - 1 pairs with the inner positions p - back ..
    p + block_outer - 1 + ahead (``None``: no bound on that side). For the kv tiles of a q
    tile ``back`` is the window's reach and ``ahead`` the causal 0; for the q tiles of a kv
    tile it is the other way round.

    ``extent`` — static — is the most inner tiles one outer tile can touch: the inner grid
    dimension's size (``n_inner`` unless both sides are bounded). The walk ENDS on the last
    tile the band touches, so the steps it has to spare come first and its last step
    computes: the pipeline fetches the next walk's first block behind that."""

    def __init__(self, back, ahead, block_outer, block_inner, n_inner):
        self.back, self.ahead = back, ahead
        self.block_outer, self.block_inner, self.n_inner = block_outer, block_inner, n_inner
        self.extent = n_inner
        if back is not None and ahead is not None:
            self.extent = min(n_inner, -(-(back + ahead + block_outer - 1) // block_inner) + 1)

    def _span(self, p, xp):
        """First and last inner tile the band of the outer tile at ``p`` touches, each
        clamped into the array (``xp=np`` counts statically)."""
        def tile(position):
            return xp.minimum(xp.maximum(position, 0) // self.block_inner, self.n_inner - 1)
        first = 0 if self.back is None else tile(p - self.back)
        last = self.n_inner - 1 if self.ahead is None else tile(
            p + self.block_outer - 1 + self.ahead)
        return first, last

    def step(self, p, t):
        """``(tile, fetch)`` of step ``t`` for an outer tile whose first position is ``p`` in
        the inner array's own coordinates (traced: the offsets are). ``tile`` is what the
        step stands for; before the band's first (or negative) it is not needed, and the
        index maps ask for ``fetch`` — the first tile the walk does need, which the
        pipeline then already holds when it comes to it: such a step copies nothing."""
        first, last = self._span(p, jnp)
        tile = last - (self.extent - 1) + t
        return tile, jnp.maximum(tile, first)

    def fetched(self, n_outer, shift):
        """(outer, inner) tile pairs the walks fetch when outer position 0 sits at inner
        position ``shift`` — the cost estimates' count; the real offsets are traced."""
        first, last = self._span(np.arange(n_outer) * self.block_outer + shift, np)
        return int(np.broadcast_to(np.maximum(last - first + 1, 1), (n_outer,)).sum())


def _kv_walk(causal, window, block_q, block_k, nk):
    """The kv tiles one q tile needs (forward, dq): behind it by the window, ahead by none."""
    return _BandWalk(window - 1 if window else None, 0 if causal else None,
                     block_q, block_k, nk)


def _q_walk(causal, window, block_q, block_k, nq):
    """The q tiles one kv tile needs (dk/dv): none behind it, the window ahead."""
    return _BandWalk(0 if causal else None, window - 1 if window else None,
                     block_k, block_q, nq)


def _tile_mask(*, causal, window, has_segments, kv_pad, block_q, block_k,
               q_global, k_global, k_local, kv_len, q_seg_ref=None, kv_seg_ref=None):
    """Build the [block_q, block_k] validity mask for a tile whose top-left element sits at
    global (q_global, k_global) and local kv column ``k_local`` (padding is local).
    Returns None when no constraint applies (interior tile)."""
    mask = None

    def _and(m, c):
        return c if m is None else jnp.logical_and(m, c)

    if kv_pad:
        col_local = k_local + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = _and(mask, col_local < kv_len)
    if causal or window:
        row = q_global + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        col = k_global + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        if causal:
            mask = _and(mask, col <= row)
        if window:
            mask = _and(mask, col > row - window)
    if has_segments:
        sq = q_seg_ref[...]    # [block_q, 1]: one id per row, along sublanes
        sk = kv_seg_ref[...]   # [1, block_k]: one id per column, along lanes
        mask = _and(mask, jnp.logical_and(sq == sk, sk != 0))
    return mask


# ------------------------------------------------------------------------------ forward
def _fwd_kernel(
    offs_ref, *refs,
    sm_scale, causal, block_q, block_k, kv_len, kv_pad, has_segments, window, softcap,
    walk, has_mask=False,
):
    refs = list(refs)
    q_seg_ref, kv_seg_ref = (refs.pop(0), refs.pop(0)) if has_segments else (None, None)
    pair_ref = refs.pop(0) if has_mask else None
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    i = pl.program_id(2)  # q block
    t = pl.program_id(3)  # step of the walk over this q block's band of kv blocks
    nt = pl.num_programs(3)

    @pl.when(t == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = i * block_q
    q_off = offs_ref[0]
    kv_off = offs_ref[1]
    j, _ = walk.step(q_off + q_start - kv_off, t)   # kv block, by the index maps' arithmetic
    k_start = j * block_k
    q_global = q_off + q_start        # global position of this tile's first row
    k_global = kv_off + k_start       # global position of this tile's first col
    # The walk ends on the band's last kv tile, but its extent is static and the band is
    # not: its first steps may stand before the array or BELOW the band (col <= row -
    # window for every pair in the tile), and a kv array wholly in the future leaves steps
    # above the diagonal (causal, in global positions). They fetch and compute nothing.
    needed = jnp.logical_and(
        j >= 0, jnp.logical_or(jnp.asarray(not causal), k_global <= q_global + block_q - 1)
    )
    if window:
        needed = jnp.logical_and(needed, k_global + block_k - 1 > q_global - window)

    # Tile classing: interior tiles (diagonal doesn't cross, window band doesn't clip,
    # no kv padding, no segment ids) take the mask-free fast path.
    interior = jnp.asarray(not (has_segments or kv_pad or has_mask))
    if causal:
        interior = jnp.logical_and(interior, k_global + block_k - 1 <= q_global)
    if window:
        interior = jnp.logical_and(interior, k_global > q_global + block_q - 1 - window)

    def _accumulate(s, mask):
        """Online-softmax update; all state lane-replicated [block_q, _LANES]."""
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[:]                                   # [bq, LANES]
        m_curr = jnp.max(s, axis=1)[:, None]                # [bq, 1]
        m_next = jnp.maximum(m_prev, m_curr)                # [bq, LANES]
        p = jnp.exp(s - _lane_tile(m_next, block_k))        # [bq, bk] fp32
        if mask is not None:
            # On a FULLY-masked row (packed-padding slots) every s equals _NEG_INF and so
            # does m_next, making exp(s - m_next) = 1 — the row sum l must still be 0 so
            # the finalize step emits zeros / -inf lse.
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_next)                    # [bq, LANES]
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1)[:, None]
        v = v_ref[0, 0]
        acc_ref[:] = acc_ref[:] * _lane_tile(alpha, acc_ref.shape[1]) + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_next

    def _scores():
        # Dots run in the INPUT dtype with fp32 accumulation (preferred_element_type):
        # bf16 inputs hit the MXU at full bf16 rate (an upfront fp32 cast would halve it);
        # fp32 inputs keep full-precision parity with the XLA reference path.
        q = q_ref[0, 0]                      # [block_q, hd]
        k = k_ref[0, 0]                      # [block_k, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [block_q, block_k] fp32
        if softcap:  # Gemma-style capping: s = cap*tanh(s/cap)
            s = softcap * jnp.tanh(s / softcap)
        return s

    @pl.when(jnp.logical_and(needed, interior))
    def _compute_fast():
        _accumulate(_scores(), None)

    @pl.when(jnp.logical_and(needed, jnp.logical_not(interior)))
    def _compute_masked():
        mask = _tile_mask(
            causal=causal, window=window, has_segments=has_segments, kv_pad=kv_pad,
            block_q=block_q, block_k=block_k, q_global=q_global, k_global=k_global,
            k_local=k_start, kv_len=kv_len, q_seg_ref=q_seg_ref, kv_seg_ref=kv_seg_ref,
        )
        if has_mask:          # the caller's per-pair mask: one byte a (query, key) pair
            pair = pair_ref[...].astype(jnp.int32) != 0
            mask = pair if mask is None else jnp.logical_and(mask, pair)
        _accumulate(_scores(), mask)

    @pl.when(t == nt - 1)
    def _finalize():
        l = l_ref[:]                                        # [bq, LANES] replicated
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / _lane_tile(l_safe, acc_ref.shape[1])).astype(o_ref.dtype)
        # lse = -inf where no key attended (fully-masked row) so ring merging ignores it.
        lse = jnp.where(l == 0.0, _NEG_INF, m_ref[:] + jnp.log(l_safe))
        lse_ref[0, 0] = lse                                  # [bq, LANES] replicated


def _seg_blocks(segments, Sp, Tp):
    """Pad + split packed segment ids into (q_seg [B,Sp,1], kv_seg [B,1,Tp]) int32 (pad
    = 0): the q side as a column and the kv side as a row, so each kernel block's last two
    dims are (block, 1) / (1, block) — shapes Mosaic tiles — and the in-kernel
    ``q == kv`` compare is a plain broadcast with no lane/sublane relayout.

    ``segments`` is either one [B,S] array (self-attention: both sides share it) or a
    ``(q_seg [B,S], kv_seg [B,T])`` pair — the ring/allgather SP case, where the kv block
    comes from another sequence shard and carries its own segment ids."""
    if isinstance(segments, (tuple, list)):
        q_raw, kv_raw = segments
    else:
        q_raw = kv_raw = segments
    q_raw = jnp.asarray(q_raw, jnp.int32)
    kv_raw = jnp.asarray(kv_raw, jnp.int32)
    q_seg = jnp.pad(q_raw, ((0, 0), (0, Sp - q_raw.shape[1])))
    kv_seg = jnp.pad(kv_raw, ((0, 0), (0, Tp - kv_raw.shape[1])))
    return q_seg[:, :, None], kv_seg[:, None, :]


def _q_major_maps(walk, reps, block_q, block_k, segments, Sp, Tp):
    """Index maps of the kernels whose grid is (b, q head, q block, step of the kv walk) —
    forward and dq: the q side's, the kv side's (GQA resolved here; a step outside the
    band asks for the walk's ``fetch``), and the segment ids' specs and arrays riding the
    same maps."""

    def q_map(b, h, i, t, offs):
        return (b, h, i, 0)

    def kv_tile(i, t, offs):
        return walk.step(offs[0] + i * block_q - offs[1], t)[1]

    def kv_map(b, h, i, t, offs):
        return (b, h // reps, kv_tile(i, t, offs), 0)

    if segments is None:
        return q_map, kv_map, [], []
    seg_specs = [
        pl.BlockSpec((None, block_q, 1), lambda b, h, i, t, offs: (b, i, 0)),
        pl.BlockSpec((None, 1, block_k), lambda b, h, i, t, offs: (b, 0, kv_tile(i, t, offs))),
    ]
    return q_map, kv_map, seg_specs, list(_seg_blocks(segments, Sp, Tp))


def _fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret, q_offset=0, kv_offset=0,
         segments=None, window=0, softcap=0.0, mask=None):
    """Raw forward: q [B,H,S,hd], k/v [B,K,T,hd] (K divides H — GQA resolved IN the BlockSpec
    index maps, never via a materialized head repeat) → (o [B,H,S,hd], lse [B,H,S] fp32).
    Differentiation-free. ``mask`` [B,S,T] (non-zero: the pair may attend; every head
    shares it) builds the kernel's masked specialisation, ``flash_fwd_masked``."""
    B, H, S, hd = q.shape
    K = k.shape[1]
    reps = H // K
    T = k.shape[2]
    nq = pl.cdiv(S, block_q)
    nk = pl.cdiv(T, block_k)
    Sp, Tp = nq * block_q, nk * block_k
    q = _pad_seq(q, Sp)
    k = _pad_seq(k, Tp)
    v = _pad_seq(v, Tp)
    has_segments = segments is not None
    has_mask = mask is not None
    walk = _kv_walk(causal, window, block_q, block_k, nk)

    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale, causal=causal, block_q=block_q, block_k=block_k, kv_len=T,
        kv_pad=(Tp != T), has_segments=has_segments, window=window, softcap=softcap,
        walk=walk, has_mask=has_mask,
    )
    q_map, kv_map, seg_specs, seg_args = _q_major_maps(
        walk, reps, block_q, block_k, segments, Sp, Tp)
    if has_mask:        # rides behind the segment ids, on the q tile and the kv walk's tile
        seg_specs = seg_specs + [pl.BlockSpec(
            (None, block_q, block_k),
            lambda b, h, i, t, offs: (b, i, kv_map(b, h, i, t, offs)[2]))]
        seg_args = seg_args + [jnp.pad(mask.astype(jnp.int8),
                                       ((0, 0), (0, Sp - S), (0, Tp - T)))]
    # fwd cost: the qk^T + pv dots and the exp of the band's tiles; K and V once a tile.
    tiles = B * H * walk.fetched(nq, T - S)
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd_masked" if has_mask else "flash_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, nq, walk.extent),
            in_specs=[
                *seg_specs,
                pl.BlockSpec((1, 1, block_q, hd), q_map),
                pl.BlockSpec((1, 1, block_k, hd), kv_map),
                pl.BlockSpec((1, 1, block_k, hd), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, hd), q_map),
                pl.BlockSpec((1, 1, block_q, _LANES), q_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, hd), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sp, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sp, _LANES), jnp.float32),
        ],
        compiler_params=_dim_semantics(3, 1),
        cost_estimate=_cost(
            4 * tiles * block_q * block_k * hd,
            2 * q.size * q.dtype.itemsize + B * H * Sp * _LANES * 4
            + 2 * tiles * block_k * hd * k.dtype.itemsize
            + has_mask * tiles * block_q * block_k,
            tiles * block_q * block_k,
        ),
        interpret=interpret,
    )(_offsets(q_offset, kv_offset), *seg_args, q, k, v)
    return o[:, :, :S], lse[:, :, :S, 0]


# ------------------------------------------------------------------------------ backward
def _bwd_dq_kernel(
    offs_ref, *refs,
    sm_scale, causal, block_q, block_k, kv_len, kv_pad, has_segments, window, softcap,
    walk,
):
    if has_segments:
        (q_seg_ref, kv_seg_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_acc) = refs
    else:
        q_seg_ref = kv_seg_ref = None
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc = refs
    i = pl.program_id(2)
    t = pl.program_id(3)  # step of the walk over this q block's band, as in the forward
    nt = pl.num_programs(3)

    @pl.when(t == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = i * block_q
    q_off = offs_ref[0]
    kv_off = offs_ref[1]
    j, _ = walk.step(q_off + q_start - kv_off, t)
    k_start = j * block_k
    q_global = q_off + q_start
    k_global = kv_off + k_start
    needed = jnp.logical_and(
        j >= 0, jnp.logical_or(jnp.asarray(not causal), k_global <= q_global + block_q - 1)
    )
    if window:
        needed = jnp.logical_and(needed, k_global + block_k - 1 > q_global - window)
    interior = jnp.asarray(not (has_segments or kv_pad))
    if causal:
        interior = jnp.logical_and(interior, k_global + block_k - 1 <= q_global)
    if window:
        interior = jnp.logical_and(interior, k_global > q_global + block_q - 1 - window)

    def _compute(mask):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]                    # [block_q, LANES] lane-replicated
        delta = delta_ref[0, 0]                # [block_q, LANES] lane-replicated
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        if softcap:  # recompute the capped scores AND the cap's local slope
            t = jnp.tanh(s / softcap)
            s = softcap * t
        p = jnp.exp(s - _lane_tile(lse, block_k))
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - _lane_tile(delta, block_k)) * sm_scale
        if softcap:  # chain rule through s = cap*tanh(s_raw/cap): d/ds_raw = 1 - t^2
            ds = ds * (1.0 - t * t)
        ds = ds.astype(k.dtype)
        dq_acc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(jnp.logical_and(needed, interior))
    def _compute_fast():
        _compute(None)

    @pl.when(jnp.logical_and(needed, jnp.logical_not(interior)))
    def _compute_masked():
        _compute(_tile_mask(
            causal=causal, window=window, has_segments=has_segments, kv_pad=kv_pad,
            block_q=block_q, block_k=block_k, q_global=q_global, k_global=k_global,
            k_local=k_start, kv_len=kv_len, q_seg_ref=q_seg_ref, kv_seg_ref=kv_seg_ref,
        ))

    @pl.when(t == nt - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    offs_ref, *refs,
    sm_scale, causal, block_q, block_k, kv_len, kv_pad, q_len, q_pad, walk,
    has_segments, window, softcap,
):
    if has_segments:
        (q_seg_ref, kv_seg_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        q_seg_ref = kv_seg_ref = None
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    j = pl.program_id(2)  # kv block (outer)
    # Inner dim walks (GQA group rep, step of the walk over this kv block's band of q
    # blocks) pairs: g = r*extent + t. dk/dv for one kv head accumulate over every q head in
    # its group, entirely in VMEM scratch.
    g = pl.program_id(3)
    ng = pl.num_programs(3)

    @pl.when(g == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    k_start = j * block_k
    q_off = offs_ref[0]
    kv_off = offs_ref[1]
    i, _ = walk.step(kv_off + k_start - q_off, jax.lax.rem(g, walk.extent))   # q block
    q_start = i * block_q
    q_global = q_off + q_start
    k_global = kv_off + k_start
    needed = jnp.logical_and(
        i >= 0, jnp.logical_or(jnp.asarray(not causal), q_global + block_q - 1 >= k_global)
    )
    if window:
        needed = jnp.logical_and(needed, k_global + block_k - 1 > q_global - window)
    # Padded q rows (q_pad) matter here: ds/p for padded rows must be zero before they
    # accumulate into dk/dv, so those tiles are never "interior".
    interior = jnp.asarray(not (has_segments or kv_pad or q_pad))
    if causal:
        interior = jnp.logical_and(interior, k_global + block_k - 1 <= q_global)
    if window:
        interior = jnp.logical_and(interior, k_global > q_global + block_q - 1 - window)

    def _compute(mask):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]                    # [block_q, LANES]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        if softcap:
            t = jnp.tanh(s / softcap)
            s = softcap * t
        p = jnp.exp(s - _lane_tile(lse, block_k))
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - _lane_tile(delta, block_k)) * sm_scale
        if softcap:  # chain rule through s = cap*tanh(s_raw/cap)
            ds = ds * (1.0 - t * t)
        ds = ds.astype(q.dtype)
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    def _mask_with_qpad():
        mask = _tile_mask(
            causal=causal, window=window, has_segments=has_segments, kv_pad=kv_pad,
            block_q=block_q, block_k=block_k, q_global=q_global, k_global=k_global,
            k_local=k_start, kv_len=kv_len, q_seg_ref=q_seg_ref, kv_seg_ref=kv_seg_ref,
        )
        if q_pad:
            row_local = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            qmask = row_local < q_len
            mask = qmask if mask is None else jnp.logical_and(mask, qmask)
        return mask

    @pl.when(jnp.logical_and(needed, interior))
    def _compute_fast():
        _compute(None)

    @pl.when(jnp.logical_and(needed, jnp.logical_not(interior)))
    def _compute_masked():
        _compute(_mask_with_qpad())

    @pl.when(g == ng - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _rep_lanes(x, Sp):
    """[B,H,S] fp32 → [B,H,Sp,_LANES] lane-replicated (for in-kernel full-register math)."""
    x = _pad_seq(x[..., None], Sp)
    return jnp.broadcast_to(x, (*x.shape[:3], _LANES))


def _bwd_dq(q, k, v, do, lse, delta, causal, sm_scale, block_q, block_k, interpret,
            q_offset=0, kv_offset=0, segments=None, window=0, softcap=0.0):
    """dq for local q against one kv block (ring building block). GQA (K < H kv heads)
    resolved via the k/v index maps, matching ``_fwd``."""
    B, H, S, hd = q.shape
    K = k.shape[1]
    reps = H // K
    T = k.shape[2]
    nq = pl.cdiv(S, block_q)
    nk = pl.cdiv(T, block_k)
    Sp, Tp = nq * block_q, nk * block_k
    qp, dop = _pad_seq(q, Sp), _pad_seq(do, Sp)
    kp, vp = _pad_seq(k, Tp), _pad_seq(v, Tp)
    lsep = _rep_lanes(lse, Sp)
    deltap = _rep_lanes(delta, Sp)
    has_segments = segments is not None
    walk = _kv_walk(causal, window, block_q, block_k, nk)
    q_map, kv_map, seg_specs, seg_args = _q_major_maps(
        walk, reps, block_q, block_k, segments, Sp, Tp)
    kernel = functools.partial(
        _bwd_dq_kernel,
        sm_scale=sm_scale, causal=causal, block_q=block_q, block_k=block_k, kv_len=T,
        kv_pad=(Tp != T), has_segments=has_segments, window=window, softcap=softcap,
        walk=walk,
    )
    tiles = B * H * walk.fetched(nq, T - S)
    dq = pl.pallas_call(
        kernel,
        name="flash_bwd_dq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, nq, walk.extent),
            in_specs=[
                *seg_specs,
                pl.BlockSpec((1, 1, block_q, hd), q_map),
                pl.BlockSpec((1, 1, block_k, hd), kv_map),
                pl.BlockSpec((1, 1, block_k, hd), kv_map),
                pl.BlockSpec((1, 1, block_q, hd), q_map),
                pl.BlockSpec((1, 1, block_q, _LANES), q_map),
                pl.BlockSpec((1, 1, block_q, _LANES), q_map),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, hd), q_map),
            scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, hd), jnp.float32),
        compiler_params=_dim_semantics(3, 1),
        cost_estimate=_cost(
            6 * tiles * block_q * block_k * hd,
            (qp.size + dop.size) * q.dtype.itemsize + (lsep.size + deltap.size) * 4
            + B * H * Sp * hd * 4 + 2 * tiles * block_k * hd * k.dtype.itemsize,
            tiles * block_q * block_k,
        ),
        interpret=interpret,
    )(_offsets(q_offset, kv_offset), *seg_args, qp, kp, vp, dop, lsep, deltap)
    return dq[:, :, :S]


def _bwd_dkv(q, k, v, do, lse, delta, causal, sm_scale, block_q, block_k, interpret,
             q_offset=0, kv_offset=0, segments=None, window=0, softcap=0.0):
    """(dk, dv) [B,K,T,hd] for one kv block against local q (ring building block).

    GQA: the inner grid dim runs ``reps ×`` the q walk's extent (``nq`` without both a
    window and ``causal``) — every (q head in the kv head's group, step over the q blocks
    this kv block's band touches) pair — so each kv head's gradient accumulates over its
    whole group in VMEM scratch, without materializing per-q-head dk/dv."""
    B, H, S, hd = q.shape
    K = k.shape[1]
    reps = H // K
    T = k.shape[2]
    nq = pl.cdiv(S, block_q)
    nk = pl.cdiv(T, block_k)
    Sp, Tp = nq * block_q, nk * block_k
    qp, dop = _pad_seq(q, Sp), _pad_seq(do, Sp)
    kp, vp = _pad_seq(k, Tp), _pad_seq(v, Tp)
    lsep = _rep_lanes(lse, Sp)
    deltap = _rep_lanes(delta, Sp)
    has_segments = segments is not None
    walk = _q_walk(causal, window, block_q, block_k, nq)
    nt = walk.extent

    # Grid order here is (b, kh, j, g): kv block outer, (group rep, step of the q walk)
    # inner. A step outside the band asks for the walk's ``fetch``, as in ``_q_major_maps``.
    def q_tile(j, g, offs):
        return walk.step(offs[1] + j * block_k - offs[0], g % nt)[1]

    def q_map(b, kh, j, g, offs):
        return (b, kh * reps + g // nt, q_tile(j, g, offs), 0)

    def kv_map(b, kh, j, g, offs):
        return (b, kh, j, 0)

    seg_specs, seg_args = [], []
    if has_segments:
        seg_specs = [
            pl.BlockSpec((None, block_q, 1), lambda b, kh, j, g, offs: (b, q_tile(j, g, offs), 0)),
            pl.BlockSpec((None, 1, block_k), lambda b, kh, j, g, offs: (b, 0, j)),
        ]
        seg_args = list(_seg_blocks(segments, Sp, Tp))
    kernel = functools.partial(
        _bwd_dkv_kernel,
        sm_scale=sm_scale, causal=causal, block_q=block_q, block_k=block_k,
        kv_len=T, kv_pad=(Tp != T), q_len=S, q_pad=(Sp != S), walk=walk,
        has_segments=has_segments, window=window, softcap=softcap,
    )
    tiles = B * H * walk.fetched(nk, S - T)
    dk, dv = pl.pallas_call(
        kernel,
        name="flash_bwd_dkv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, K, nk, reps * nt),
            in_specs=[
                *seg_specs,
                pl.BlockSpec((1, 1, block_q, hd), q_map),
                pl.BlockSpec((1, 1, block_k, hd), kv_map),
                pl.BlockSpec((1, 1, block_k, hd), kv_map),
                pl.BlockSpec((1, 1, block_q, hd), q_map),
                pl.BlockSpec((1, 1, block_q, _LANES), q_map),
                pl.BlockSpec((1, 1, block_q, _LANES), q_map),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_k, hd), kv_map),
                pl.BlockSpec((1, 1, block_k, hd), kv_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, hd), jnp.float32),
                pltpu.VMEM((block_k, hd), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, K, Tp, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, K, Tp, hd), jnp.float32),
        ],
        compiler_params=_dim_semantics(3, 1),
        cost_estimate=_cost(
            8 * tiles * block_q * block_k * hd,
            (kp.size + vp.size) * k.dtype.itemsize + 2 * B * K * Tp * hd * 4
            + 2 * tiles * block_q * (hd * q.dtype.itemsize + _LANES * 4),
            tiles * block_q * block_k,
        ),
        interpret=interpret,
    )(_offsets(q_offset, kv_offset), *seg_args, qp, kp, vp, dop, lsep, deltap)
    return dk[:, :, :T], dv[:, :, :T]


def _pad_seq(x, target):
    S = x.shape[2]
    if S == target:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, target - S), (0, 0)))


def _fit_block(block: int, seq: int) -> int:
    if seq >= block:
        return block
    return max(16, 1 << (seq - 1).bit_length())


# ----------------------------------------------------------------------------- public API
# Offsets travel as float32 scalars so the custom_vjp has well-defined (zero) cotangents for
# them; kernels receive them as int32. This is what lets shard_map callers (ring/allgather SP)
# pass traced global positions.
def _seg_pair_f32(segments):
    """Normalize ``segments`` (None | [B,S] array | (q_seg, kv_seg) pair) to the fixed
    (q, kv) float32 pair the custom_vjp carries, plus the has_segments flag."""
    if segments is None:
        return (jnp.zeros((1, 1), jnp.float32),) * 2, False
    if not isinstance(segments, (tuple, list)):
        segments = (segments, segments)
    return tuple(jnp.asarray(s, jnp.float32) for s in segments), True


def _seg_pair_i32(seg_f32, has_segments):
    """``seg_f32`` travels through the custom_vjp as a (q_seg, kv_seg) float32 pair
    (identical arrays in the self-attention case) so the cotangent structure is fixed;
    kernels receive int32."""
    if not has_segments:
        return None
    return tuple(s.astype(jnp.int32) for s in seg_f32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11, 12, 13))
def _flash_bhsd(q, k, v, q_off, kv_off, seg_f32, causal, sm_scale, block_q, block_k,
                interpret, has_segments, window, softcap):
    segs = _seg_pair_i32(seg_f32, has_segments)
    o, _ = _fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                q_offset=q_off.astype(jnp.int32), kv_offset=kv_off.astype(jnp.int32),
                segments=segs, window=window, softcap=softcap)
    return o


def _flash_bhsd_fwd(q, k, v, q_off, kv_off, seg_f32, causal, sm_scale, block_q, block_k,
                    interpret, has_segments, window, softcap):
    segs = _seg_pair_i32(seg_f32, has_segments)
    o, lse = _fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                  q_offset=q_off.astype(jnp.int32), kv_offset=kv_off.astype(jnp.int32),
                  segments=segs, window=window, softcap=softcap)
    return o, (q, k, v, q_off, kv_off, seg_f32, o, lse)


def _flash_bhsd_bwd(causal, sm_scale, block_q, block_k, interpret, has_segments, window,
                    softcap, residuals, do):
    q, k, v, q_off, kv_off, seg_f32, o, lse = residuals
    qo = q_off.astype(jnp.int32)
    ko = kv_off.astype(jnp.int32)
    segs = _seg_pair_i32(seg_f32, has_segments)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # [B,H,S]
    dq = _bwd_dq(q, k, v, do, lse, delta, causal, sm_scale, block_q, block_k, interpret,
                 q_offset=qo, kv_offset=ko, segments=segs, window=window, softcap=softcap)
    dk, dv = _bwd_dkv(q, k, v, do, lse, delta, causal, sm_scale, block_q, block_k, interpret,
                      q_offset=qo, kv_offset=ko, segments=segs, window=window,
                      softcap=softcap)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
            jax.tree_util.tree_map(jnp.zeros_like, seg_f32))


_flash_bhsd.defvjp(_flash_bhsd_fwd, _flash_bhsd_bwd)


def _flash_bhsd_offset(q, k, v, q_offset=0, kv_offset=0, causal=True, sm_scale=None,
                       block_q=None, block_k=None, interpret=None, window=0, softcap=0.0,
                       segments=None, mask=None):
    """Offset-aware flash attention over user layout [B, S, H, hd] (shard_map helper).

    ``segments``: None, a shared [B,S] array, or a ``(q_seg [B,S], kv_seg [B,T])`` pair —
    the pair form is how the SP modes keep packing exact when kv spans other shards.
    ``mask`` [B,S,T] (non-zero: the pair may attend): the forward kernel's masked
    specialisation, forward only."""
    B, S, H, hd = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    if interpret is None:
        interpret = _interpret_default()
    qT = q.transpose(0, 2, 1, 3)
    kT = k.transpose(0, 2, 1, 3)
    vT = v.transpose(0, 2, 1, 3)
    bq = _fit_block(block_q or _DEFAULT_BLOCK_Q, S)
    bk = _fit_block(block_k or _DEFAULT_BLOCK_K, k.shape[1])
    if mask is not None:
        o, _ = _fwd(qT, kT, vT, causal, sm_scale, bq, bk, interpret, q_offset=q_offset,
                    kv_offset=kv_offset, segments=segments, window=int(window),
                    softcap=float(softcap), mask=mask)
        return o.transpose(0, 2, 1, 3)
    seg_f32, has_segments = _seg_pair_f32(segments)
    o = _flash_bhsd(qT, kT, vT,
                    jnp.asarray(q_offset, jnp.float32), jnp.asarray(kv_offset, jnp.float32),
                    seg_f32,
                    causal, sm_scale, bq, bk, interpret, has_segments, int(window),
                    float(softcap))
    return o.transpose(0, 2, 1, 3)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    segment_ids: Optional[jax.Array] = None,
    window: int = 0,
    softcap: float = 0.0,
) -> jax.Array:
    """Flash attention over user layout q [B, S, H, hd], k/v [B, T, K, hd] (GQA: K ≤ H).

    Returns [B, S, H, hd] in q's dtype. Differentiable (custom VJP with flash backward).

    ``segment_ids`` [B, S] (sample packing, ``ops/packing.py``: 0 = pad, 1..k = packed
    sequences) restricts attention to same-segment pairs IN-KERNEL — packed training keeps
    the flash memory/compute profile instead of falling back to masked XLA attention.
    Requires self-attention shapes (T == S).

    ``window`` > 0 adds Mistral-style sliding-window masking (position i attends
    (i-window, i]): kv tiles entirely outside the band are NOT VISITED — the kernels' grids
    walk the band's tiles only, and a grid step left outside it fetches nothing — so
    long-context compute and traffic scale with S·window instead of S².

    ``softcap`` > 0 applies Gemma-style score capping cap·tanh(s/cap) in-kernel, with the
    exact chain rule (1 − tanh²) in both backward kernels — Gemma-2 trains on the flash
    path instead of falling back to masked XLA attention.
    """
    B, S, H, hd = q.shape
    K = k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    if interpret is None:
        interpret = _interpret_default()
    if segment_ids is not None and k.shape[1] != S:
        raise ValueError("segment_ids requires self-attention shapes (kv length == q length)")
    if H % K:
        raise ValueError(f"q heads ({H}) must be a multiple of kv heads ({K})")
    # GQA needs no head repeat: the kernels map q head h → kv head h // (H//K) in their
    # BlockSpec index maps, so the repeated K/V never exist in HBM.
    # [B, S, H, hd] → [B, H, S, hd]
    qT = q.transpose(0, 2, 1, 3)
    kT = k.transpose(0, 2, 1, 3)
    vT = v.transpose(0, 2, 1, 3)
    block_q = _fit_block(block_q or _DEFAULT_BLOCK_Q, S)
    block_k = _fit_block(block_k or _DEFAULT_BLOCK_K, k.shape[1])
    zero = jnp.zeros((), jnp.float32)
    seg_f32, has_segments = _seg_pair_f32(segment_ids)
    o = _flash_bhsd(qT, kT, vT, zero, zero, seg_f32, causal, sm_scale, block_q, block_k,
                    interpret, has_segments, int(window), float(softcap))
    return o.transpose(0, 2, 1, 3)
