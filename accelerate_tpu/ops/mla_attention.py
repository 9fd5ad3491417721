"""Latent-attention (MLA) decode over a paged LATENT pool, in Pallas (+ a jnp reference).

Multi-head latent attention caches, per token and layer, one compressed row shared by all
heads: ``c_kv`` (``kv_lora_rank`` values, the keys' and the values' common latent) and
``k_rope`` (the one rotary key every head shares) — 512 + 64 = 576 values where a GQA
cache of the same model would hold ``2 · H · hd``. Decode never up-projects the cache:
the key up-projection is absorbed into the query (``q_lat = q_nope · W_kbᵀ``, done by the
caller) and the value up-projection into the output (``o = o_lat · W_vb``, the caller
again), so what is left between them is attention of ``H`` query rows of width 512 (+ 64
rotary) over the latent rows themselves:

    scores = (q_lat · c_kvᵀ + q_rope · k_ropeᵀ) · sm_scale;   o_lat = softmax(scores) · c_kv

``mla_paged_attention`` is that, through block tables (``paged_kv.BlockManager``) into
the pool ``[num_pages, page_size, 640]`` (``models.common.paged_latent_planes``: the 576
values in a row of whole 128-lane tiles, as the chip's memory holds a 576-wide row
anyway — Mosaic refuses to slice a page out of a plane declared 576 wide). It is
the sibling of ``ops.paged_attention.paged_attention`` and shares its walk
(:func:`~.paged_attention.walk_range`, :func:`~.paged_attention.block_pages`): the grid
is ``(batch,)``, a ``fori_loop`` with a RUNTIME trip count walks only the lane's live
range a block of pages an iteration, the range rides as scalar-prefetch operands (one
compiled program for every length), the pool stays in HBM and a block's pages come by
one ``make_async_copy`` each, double-buffered by hand. A freed lane runs no iteration
and emits zeros.

What differs from the GQA kernel, and why. All ``H`` heads read the SAME latent rows,
so the heads are the row dimension of every product (128 rows on the MXU where a GQA
lane has ``H / K`` = 4), and a block's pages are laid side by side in ONE VMEM tile
``[block · page_size, 640]`` (a page is 16 rows — one bf16 sublane tile — so page ``j``
lands at row ``16 j``): a block costs three products (``q_lat · c_kvᵀ``, ``q_rope ·
k_ropeᵀ``, ``p · c_kv``) and one online-softmax update, not three per page. Per (query,
key) that is 2 · H · (576 + 512) FLOPs against 1 152 bytes read: 242 FLOP/B, the v5e's
ridge — the kernel is compute- and bandwidth-bound at once. The lane's live range is one
run ``[first valid slot, last written slot]`` (the engine's layouts: a left pad, then the
prompt, then what was decoded), so visibility is two scalar bounds on the key position
and no mask array rides along; a valid mask with holes is not representable here.
Queries are the decode shape only: one token a lane.

``mla_paged_attention_reference`` is the same contract in jnp (gather through the table)
— the kernel's test oracle and the path the engine takes off-TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import LANES as _LANES
from ._common import interpret_default as _interpret_default
from ._common import lane_tile as _lane_tile
from .paged_attention import block_pages, walk_range

__all__ = ["mla_paged_attention", "mla_paged_attention_reference", "mla_block_pages",
           "live_range"]

_NEG_INF = -1e30
_M_INIT = -1e29      # where the running max starts: above the mask value, below any score


def mla_block_pages(page_size: int, width: int, itemsize: int, max_pages: int) -> int:
    """Table entries a walk iteration fetches: :func:`~.paged_attention.block_pages` of
    a page that holds ONE plane of ``width`` values a slot — half of it counted as the
    "K" and half as the "V" the shared rule prices — in whole groups of 8 pages where
    there are that many, so that a block's keys fill whole 128-lane score tiles at
    page 16 (48 pages = 768 keys at page 16 × 640 in bf16)."""
    n = block_pages(page_size, 1, width // 2, itemsize, max_pages)
    return n - n % 8 if n >= 8 else n


def live_range(valid: jax.Array, tables: jax.Array, num_pages: int, page_size: int):
    """Per lane ``(first valid slot, last slot that is valid AND allocated)`` from the
    valid mask [B, C] and block tables [B, MP]; an empty lane gives ``(C, -1)``."""
    C, MP = valid.shape[1], tables.shape[1]
    any_valid = valid.any(axis=1)
    first = jnp.where(any_valid, jnp.argmax(valid, axis=1), C)
    last = jnp.where(any_valid, C - 1 - jnp.argmax(valid[:, ::-1], axis=1), -1)
    allocated = tables < num_pages
    last_allocated = jnp.where(
        allocated.any(axis=1),
        (MP - jnp.argmax(allocated[:, ::-1], axis=1)) * page_size - 1, -1)
    return first.astype(jnp.int32), jnp.minimum(last, last_allocated).astype(jnp.int32)


def mla_paged_attention_reference(q_lat, q_rope, pool, tables, positions, valid, *,
                                  page_size: int, sm_scale: float):
    """Pure-jnp oracle: q_lat [B,H,R], q_rope [B,H,r] against the latent pool
    [P, page_size, W >= R + r] (a row is c_kv | k_rope | lanes never read) gathered
    through ``tables`` [B,MP]; lane b's query sits at ``positions[b]`` and sees the
    valid, allocated slots at or before it. fp32 scores and softmax; → o_lat [B,H,R] in
    q_lat's dtype. A lane that sees no key emits zeros."""
    B, H, R = q_lat.shape
    P = pool.shape[0]
    C = valid.shape[1]
    pages = jnp.take(pool, jnp.minimum(tables, P - 1), axis=0)      # [B, MP, ps, W]
    lat = pages.reshape(B, -1, pages.shape[-1])[:, :C]
    ckv, kr = lat[..., :R], lat[..., R:R + q_rope.shape[-1]]
    s = (jnp.einsum("bhc,bkc->bhk", q_lat, ckv, preferred_element_type=jnp.float32)
         + jnp.einsum("bhr,bkr->bhk", q_rope, kr, preferred_element_type=jnp.float32))
    s = s * sm_scale
    allocated = jnp.repeat(tables < P, pool.shape[1], axis=1)[:, :C]   # a sentinel's slots hold nothing
    seen = valid & allocated & (jnp.arange(C)[None, :] <= positions[:, None])   # [B, C]
    s = jnp.where(seen[:, None, :], s, _NEG_INF)
    p = jnp.exp(s - jnp.maximum(s.max(-1, keepdims=True), _M_INIT))
    p = jnp.where(seen[:, None, :], p, 0.0)
    l = p.sum(-1, keepdims=True)
    p = (p / jnp.where(l == 0.0, 1.0, l)).astype(q_lat.dtype)
    return jnp.einsum("bhk,bkc->bhc", p, ckv,
                      preferred_element_type=jnp.float32).astype(q_lat.dtype)


def _kernel(first_ref, count_ref, tab_ref, lo_ref, hi_ref, ql_ref, qr_ref, lat_hbm,
            o_ref, lat_buf, sems, acc_ref, m_ref, l_ref, *, page_size, block,
            table_width, rank, sm_scale):
    b = pl.program_id(0)
    first, count = first_ref[b], count_ref[b]
    lo, hi = lo_ref[b], hi_ref[b]
    cols = block * page_size

    def copy_block(buf, page0, wait=False):
        """Start (or wait for) the copies that bring table entries ``page0 ..
        page0+block-1`` of this lane side by side into buffer ``buf``: one copy a page,
        since a block's pages are not neighbours in the pool."""
        def one(j, carry):
            page = tab_ref[b * table_width + page0 + j]
            c = pltpu.make_async_copy(
                lat_hbm.at[page], lat_buf.at[buf, pl.ds(j * page_size, page_size)],
                sems.at[buf])
            c.wait() if wait else c.start()
            return carry

        # Unrolled by the lowering, not by Python: traced once (PERF.md 26.5).
        jax.lax.fori_loop(0, block, one, None, unroll=True)

    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, _M_INIT)
    l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(count > 0)
    def _first_fetch():
        copy_block(0, first)

    q_lat = ql_ref[...]                                    # [H, R]
    q_rope = qr_ref[...]                                   # [H, r]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)

    def body(i, carry):
        page0 = first + i * block
        buf = i % 2

        @pl.when(i + 1 < count)
        def _next_fetch():
            copy_block(1 - buf, page0 + block)

        copy_block(buf, page0, wait=True)
        ckv = lat_buf[buf, :, pl.ds(0, rank)]              # [cols, R]
        kr = lat_buf[buf, :, pl.ds(rank, q_rope.shape[-1])]  # [cols, r]
        contract_last = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(q_lat, ckv, contract_last,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(q_rope, kr, contract_last,
                                   preferred_element_type=jnp.float32)) * sm_scale
        # Column c of the block is logical slot page0*page_size + c: the block's table
        # entries are consecutive logical pages. Dead pages behind the last live one and
        # sentinel-table garbage lie outside [lo, hi] and mask out entirely.
        key_pos = page0 * page_size + col                  # [1, cols]
        s = jnp.where((key_pos >= lo) & (key_pos <= hi), s, _NEG_INF)
        m_prev = m_ref[:]                                  # [H, LANES] replicated
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        # m never falls below _M_INIT > _NEG_INF: a masked column's exp() is an exact 0.
        p = jnp.exp(s - _lane_tile(m_next, cols))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1)[:, None]
        acc_ref[:] = acc_ref[:] * _lane_tile(alpha, rank) + jax.lax.dot_general(
            p.astype(ckv.dtype), ckv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_next
        return carry

    jax.lax.fori_loop(0, count, body, None)

    l = l_ref[:]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[...] = (acc_ref[:] / _lane_tile(l_safe, rank)).astype(o_ref.dtype)


def mla_paged_attention(q_lat, q_rope, pool, tables, positions, valid, *, page_size: int,
                        sm_scale: float, interpret=None):
    """Latent-attention decode: one query a lane, q_lat [B,H,R] (the key up-projection
    already absorbed) and q_rope [B,H,r], against ``pool`` [P, page_size, W >= R + r] through
    ``tables`` [B, MP] (sentinel == P for unallocated entries).

    ``positions`` [B] is each lane's query position; ``valid`` [B, C] marks live slots
    and must be ONE run a lane (see the module docstring) — the kernel sees the slots
    ``first valid .. min(positions, last valid and allocated)``. Returns o_lat [B,H,R]
    in q_lat's dtype: softmax over the visible latent rows, the value up-projection
    still to be applied by the caller. A lane with nothing to see (a freed slot, an
    empty valid row) runs no iteration and emits zeros."""
    B, H, R = q_lat.shape
    r = q_rope.shape[-1]
    P, ps, W = pool.shape
    if ps != page_size:
        raise ValueError(f"pool page_size {ps} != page_size argument {page_size}")
    if W < R + r:
        raise ValueError(f"pool rows hold {W} values, the queries need {R} + {r}")
    MP = tables.shape[1]
    itemsize = pool.dtype.itemsize
    n = mla_block_pages(ps, W, itemsize, MP)
    if interpret is None:
        interpret = _interpret_default()

    positions = positions.astype(jnp.int32)
    lo, last_live = live_range(valid, tables, P, ps)
    hi = jnp.minimum(positions, last_live)
    first_page, n_blocks, _ = walk_range(
        positions, lo, last_live, T=1, window=0, page_size=ps, block=n)
    # The last block of a walk may run up to n-1 entries past the table: pad it
    # (sentinels clamp to a real page for the fetch; their slots lie past ``hi``).
    tables = jnp.pad(jnp.minimum(tables.astype(jnp.int32), P - 1), ((0, 0), (0, n)))

    def _lane(b, *_):
        return (b, 0, 0)

    kernel = functools.partial(
        _kernel, page_size=ps, block=n, table_width=MP + n, rank=R, sm_scale=sm_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B,),
        in_specs=[pl.BlockSpec((None, H, R), _lane), pl.BlockSpec((None, H, r), _lane),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, H, R), _lane),
        scratch_shapes=[
            pltpu.VMEM((2, n * ps, W), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((H, R), jnp.float32),
            pltpu.VMEM((H, _LANES), jnp.float32),
            pltpu.VMEM((H, _LANES), jnp.float32),
        ],
    )
    # An upper bound on what the walk can touch, from what is static: the whole table.
    keys = B * -(-MP // n) * n * ps
    return pl.pallas_call(
        kernel,
        name="mla_paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, R), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * H * (2 * R + r) * keys),
            bytes_accessed=int(keys * W * itemsize
                               + B * H * (2 * R + r) * q_lat.dtype.itemsize),
            transcendentals=int(H * keys),
        ),
        interpret=interpret,
    )(first_page.astype(jnp.int32), n_blocks.astype(jnp.int32), tables.reshape(-1),
      lo, hi, q_lat, q_rope, pool)
