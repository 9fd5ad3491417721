"""The indexer of a learned sparse attention (DeepSeek-V3.2's "lightning indexer"), over
a paged INDEX-KEY pool, in Pallas (+ a jnp reference).

A sparse attention layer caches, beside its attended row (a latent, or K and V), one
small index key a token (``k_idx``, ``Di`` values: 128 in dots3, 64 in Keye-VL-2.0). A
query carries ``Hi`` index heads ``q_idx`` [Hi, Di] and one weight a head ``w`` [Hi]; its
score of a key is

    I = Σ_head  w_head · ReLU(q_idx_head · k_idx)

and the layer attends only to the ``index_topk`` keys of largest ``I`` (the caller's
``lax.top_k`` and gather; ``models/deepseek.py::_attend_selected_pages``). What this
module computes is ``I`` for every live key of every lane, one query a lane (decode).

**The pool's rows are whole 128-lane tiles** (:func:`index_pool_shape`): a page of
``page_size`` keys is stored ``[page_size / r, r · Di]`` with ``r = 128 // Di`` keys side
by side in a row — the row-major view of ``[page_size, Di]``, so key ``t`` of a page
lies in row ``t // r`` at lanes ``(t % r) · Di ..``. At ``Di`` = 128, ``r`` = 1 and the page
is ``[page_size, 128]``. (A plane declared 64 wide is laid out 128 wide on the chip anyway,
and Mosaic cannot cut a page out of it: "slice shape must be aligned to tiling (128)".)

``dsa_index_scores`` walks a lane's pages of the pool through its block-table row as
``ops.mla_attention`` walks the latent pool — the grid is ``(batch,)``, a ``fori_loop``
with a RUNTIME trip count over blocks of pages, a block's pages brought side by side into
one VMEM tile by one ``make_async_copy`` each, double-buffered by hand; the pool stays in
HBM. A block costs ONE product ``[r · Hi, r · Di] × [r · Di, rows]`` — the query laid out
block-diagonally, so head ``h`` of group ``g`` meets only the ``g``-th key of each row — a
ReLU and, a group at a time, the weighted sum over its heads; group ``g``'s scores of the
block go to lanes ``g · rows ..`` of row ``i`` of the lane's output tile, and the wrapper
puts them back in key order. Per live key the ALGORITHM needs 2 · Hi · Di FLOPs against
2 · Di bytes read (``Hi`` FLOP/B: bandwidth-bound at either width, and at 2–4 KB a page
the copies' issue rate counts); the block-diagonal product spends ``r`` times the FLOPs
on zeros. Slots outside the lane's live range ``[first valid, min(position, last valid
and allocated)]`` read ``-inf``, as do the blocks the walk never reaches.

``dsa_index_scores_reference`` is the same contract in jnp (gather through the table):
the kernel's oracle and the engine's path off-TPU. ``index_scores`` is the formula
itself on dense keys — prefill's chunks use it.
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
from .mla_attention import live_range

__all__ = ["dsa_index_scores", "dsa_index_scores_reference", "index_scores",
           "index_block_pages", "index_pool_shape", "write_index_paged"]

# Keys a walk iteration scores (64 pages of 16): the [r·Hi, rows] score tile stays a few
# hundred KB whatever the key width (rows = 1024 / r), and a block's copies are in
# flight together (64 of 4 KB at Di 128, 64 of 2 KB at Di 64).
_BLOCK_KEYS = 1024


def _keys_a_row(index_dim: int) -> int:
    """``r``: index keys side by side in one 128-lane row of the pool."""
    if index_dim > _LANES:
        if index_dim % _LANES:
            raise ValueError(f"index_dim {index_dim}: wider than a lane tile and no multiple of it")
        return 1
    if _LANES % index_dim:
        raise ValueError(f"index_dim {index_dim} does not divide the {_LANES}-lane tile")
    return _LANES // index_dim


def index_pool_shape(num_pages: int, page_size: int, index_dim: int) -> tuple:
    """The index-key pool of ``num_pages`` pages of ``page_size`` keys of ``index_dim``
    values: ``[num_pages, page_size / r, r · index_dim]`` (module docstring)."""
    r = _keys_a_row(index_dim)
    if page_size % r:
        raise ValueError(f"page_size {page_size} holds no whole rows of {r} keys")
    return (num_pages, page_size // r, r * index_dim)


def write_index_paged(pool, k_idx, pages, offs):
    """Write index keys ``k_idx`` [B,T,Di] at physical slots ``(pages[b,t], offs[b,t])`` of
    the pool (:func:`index_pool_shape`), in place on a donated carry; a sentinel page id
    is out of bounds and DROPS (``models.common.write_kv_paged``'s contract)."""
    Di = k_idx.shape[-1]
    r = pool.shape[-1] // Di
    k_idx = k_idx.astype(pool.dtype)
    if r == 1:
        return pool.at[pages, offs].set(k_idx)
    lanes = (offs % r)[..., None] * Di + jnp.arange(Di)
    return pool.at[pages[..., None], (offs // r)[..., None], lanes].set(k_idx)


def index_scores(q_idx, w, k_idx):
    """``Σ_head w · ReLU(q · k)``: q_idx [..., Hi, Di], w [..., Hi], k_idx [B, K, Di]
    (the leading dims of q start with B) → float32 [..., K]."""
    one = q_idx.ndim == 3                                            # one query a lane
    if one:
        q_idx, w = q_idx[:, None], w[:, None]
    s = jnp.einsum("bthd,bkd->bthk", q_idx, k_idx, preferred_element_type=jnp.float32)
    out = (jax.nn.relu(s) * w.astype(jnp.float32)[..., None]).sum(-2)
    return out[:, 0] if one else out


def index_block_pages(page_size: int, max_pages: int) -> int:
    """Table entries a walk iteration fetches: ``_BLOCK_KEYS`` keys' pages, never more
    than the table has."""
    return max(1, min(_BLOCK_KEYS // page_size, max_pages))


def dsa_index_scores_reference(q_idx, w, pool, tables, positions, valid, *,
                               page_size: int):
    """Pure-jnp oracle: q_idx [B,Hi,Di], w [B,Hi] against the index-key pool
    (:func:`index_pool_shape`) gathered through ``tables`` [B,MP] → float32 [B, C] (``C`` =
    the valid mask's width): the score of every slot that is valid, allocated and at or
    before ``positions[b]``, ``-inf`` elsewhere."""
    B = q_idx.shape[0]
    P, C = pool.shape[0], valid.shape[1]
    pages = jnp.take(pool, jnp.minimum(tables, P - 1), axis=0)      # [B, MP, ps/r, r·Di]
    keys = pages.reshape(B, -1, q_idx.shape[-1])[:, :C]             # row-major: key order
    lo, last_live = live_range(valid, tables, P, page_size)
    hi = jnp.minimum(positions.astype(jnp.int32), last_live)
    slot = jnp.arange(C)[None, :]
    seen = (slot >= lo[:, None]) & (slot <= hi[:, None])
    return jnp.where(seen, index_scores(q_idx, w, keys), -jnp.inf)


def _kernel(count_ref, tab_ref, lo_ref, hi_ref, q_ref, w_ref, k_hbm, o_ref, k_buf, sems,
            *, page_size, block, table_width, groups):
    b = pl.program_id(0)
    count = count_ref[b]
    lo, hi = lo_ref[b], hi_ref[b]
    rows_a_page = page_size // groups          # a page is [page_size / r, r·Di] (r = groups)
    cols = block * rows_a_page                 # rows of a block's tile: keys ÷ r

    def copy_block(buf, page0, wait=False):
        """Start (or wait for) the copies of table entries ``page0 .. page0+block-1`` of
        this lane, side by side into buffer ``buf``."""
        def one(j, carry):
            page = tab_ref[b * table_width + page0 + j]
            c = pltpu.make_async_copy(
                k_hbm.at[page], k_buf.at[buf, pl.ds(j * rows_a_page, rows_a_page)],
                sems.at[buf])
            c.wait() if wait else c.start()
            return carry

        jax.lax.fori_loop(0, block, one, None, unroll=True)

    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)

    @pl.when(count > 0)
    def _first_fetch():
        copy_block(0, 0)

    q = q_ref[...]                                         # [r·Hi, r·Di], block-diagonal
    w = _lane_tile(w_ref[...], cols)                       # [r·Hi, cols], lane-replicated
    heads = q.shape[0] // groups
    col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)

    def body(i, carry):
        page0 = i * block
        buf = i % 2

        @pl.when(i + 1 < count)
        def _next_fetch():
            copy_block(1 - buf, page0 + block)

        copy_block(buf, page0, wait=True)
        s = jax.lax.dot_general(q, k_buf[buf], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)     # [r·Hi, cols]
        s = jnp.maximum(s, 0.0) * w
        for g in range(groups):      # row c of the tile holds keys r·c .. r·c + r − 1
            score = jnp.sum(s[g * heads:(g + 1) * heads], axis=0, keepdims=True)  # [1, cols]
            key_pos = page0 * page_size + col * groups + g
            o_ref[pl.ds(i, 1), pl.ds(g * cols, cols)] = jnp.where(
                (key_pos >= lo) & (key_pos <= hi), score, -jnp.inf)
        return carry

    jax.lax.fori_loop(0, count, body, None)


def dsa_index_scores(q_idx, w, pool, tables, positions, valid, *, page_size: int,
                     interpret=None):
    """The indexer's scores, one query a lane: q_idx [B,Hi,Di], w [B,Hi] (float32)
    against the index-key ``pool`` ``[P, page_size / r, r · Di]`` (``r`` keys a row, read off
    its shape: :func:`index_pool_shape` gives the ``r`` that fills a lane tile, the only
    one Mosaic takes below 128 values) through ``tables`` [B, MP] (sentinel == P for
    unallocated entries). ``positions`` [B] is each lane's query
    position and ``valid`` [B, C] its live slots (ONE run a lane, as for
    ``mla_paged_attention``). Returns float32 [B, C]: slot ``s`` of lane ``b`` holds its
    score if ``first valid <= s <= min(positions[b], last valid and allocated)``, else
    ``-inf``. The walk starts at the lane's page 0 (a left pad is under a prompt bucket
    long) and ends with the block that holds ``hi``."""
    B, Hi, Di = q_idx.shape
    P, ps = pool.shape[0], page_size
    r = pool.shape[-1] // Di       # keys a row, as the pool is laid out (1: [P, ps, Di])
    if pool.shape[1:] != (ps // r, r * Di):
        raise ValueError(f"pool {pool.shape} holds no pages of {ps} keys of {Di} values, "
                         f"{r} a row")
    C, MP = valid.shape[1], tables.shape[1]
    n = index_block_pages(ps, MP)
    blocks = -(-MP // n)
    if interpret is None:
        interpret = _interpret_default()

    lo, last_live = live_range(valid, tables, P, ps)
    hi = jnp.minimum(positions.astype(jnp.int32), last_live)
    count = jnp.where(hi >= lo, hi // (n * ps) + 1, 0).astype(jnp.int32)
    # whole blocks of table entries; sentinels clamp to a real page for the fetch (their
    # slots lie outside [lo, hi])
    tables = jnp.pad(jnp.minimum(tables.astype(jnp.int32), P - 1),
                     ((0, 0), (0, blocks * n - MP)))
    w_lanes = jnp.broadcast_to(w.astype(jnp.float32)[..., None], (B, Hi, _LANES))
    if r > 1:
        # r keys lie side by side in a pool row: lay the query out block-diagonally, so that
        # row g·Hi + h of the product scores the g-th key of each pool row with head h
        q_idx = jnp.einsum("gf,bhd->bghfd", jnp.eye(r, dtype=q_idx.dtype),
                           q_idx).reshape(B, r * Hi, r * Di)
        w_lanes = jnp.tile(w_lanes, (1, r, 1))

    def _lane(b, *_):
        return (b, 0, 0)

    kernel = functools.partial(_kernel, page_size=ps, block=n, table_width=blocks * n,
                               groups=r)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[pl.BlockSpec((None, r * Hi, r * Di), _lane),
                  pl.BlockSpec((None, r * Hi, _LANES), _lane),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, blocks, n * ps), _lane),
        scratch_shapes=[pltpu.VMEM((2, n * ps // r, r * Di), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    keys = B * blocks * n * ps            # an upper bound from what is static
    scores = pl.pallas_call(
        kernel,
        name="dsa_index_scores",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, blocks, n * ps), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * Hi * Di * keys),
            bytes_accessed=int(keys * Di * pool.dtype.itemsize + keys * 4),
            transcendentals=0),
        interpret=interpret,
    )(count, tables.reshape(-1), lo, hi, q_idx, w_lanes, pool)
    if r > 1:       # a block's row holds group 0's keys, then group 1's: back to key order
        scores = scores.reshape(B, blocks, r, -1).swapaxes(2, 3)
    return scores.reshape(B, -1)[:, :C]
