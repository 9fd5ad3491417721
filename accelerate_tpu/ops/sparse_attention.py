"""The indexer of a learned sparse attention (DeepSeek-V3.2's "lightning indexer"), over
a paged INDEX-KEY pool, in Pallas (+ a jnp reference).

A sparse latent-attention layer caches, beside its latent row, one small index key a
token (``k_idx``, 128 values). A query carries ``Hi`` index heads ``q_idx`` [Hi, 128] and
one weight a head ``w`` [Hi]; its score of a key is

    I = Σ_head  w_head · ReLU(q_idx_head · k_idx)

and the layer attends only to the ``index_topk`` keys of largest ``I`` (the caller's
``lax.top_k`` and gather; ``models/deepseek.py::_attend_selected_pages``). What this
module computes is ``I`` for every live key of every lane, one query a lane (decode):

``dsa_index_scores`` walks a lane's pages of the pool ``[num_pages, page_size, 128]``
through its block-table row as ``ops.mla_attention`` walks the latent pool — the grid is
``(batch,)``, a ``fori_loop`` with a RUNTIME trip count over blocks of pages, a block's
pages brought side by side into one VMEM tile by one ``make_async_copy`` each, double-
buffered by hand; the pool stays in HBM. A block costs ONE product ``[Hi, 128] × [128,
keys]``, a ReLU and the weighted sum over heads; its scores go to row ``i`` of the lane's
output tile ``[blocks, keys a block]``. Per live key: 2 · Hi · 128 FLOPs against 256 bytes
read (64 FLOP/B: bandwidth-bound, and at 4 KB a page the copies' issue rate counts).
Slots outside the lane's live range ``[first valid, min(position, last valid and
allocated)]`` read ``-inf``, as do the blocks the walk never reaches.

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
           "index_block_pages"]

_BLOCK_KEYS = 1024     # keys a walk iteration scores (64 pages of 16)


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
    """Pure-jnp oracle: q_idx [B,Hi,Di], w [B,Hi] against the index-key pool [P,
    page_size, Di] gathered through ``tables`` [B,MP] → float32 [B, C] (``C`` = the valid
    mask's width): the score of every slot that is valid, allocated and at or before
    ``positions[b]``, ``-inf`` elsewhere."""
    B = q_idx.shape[0]
    P, C = pool.shape[0], valid.shape[1]
    pages = jnp.take(pool, jnp.minimum(tables, P - 1), axis=0)      # [B, MP, ps, Di]
    keys = pages.reshape(B, -1, pages.shape[-1])[:, :C]
    lo, last_live = live_range(valid, tables, P, page_size)
    hi = jnp.minimum(positions.astype(jnp.int32), last_live)
    slot = jnp.arange(C)[None, :]
    seen = (slot >= lo[:, None]) & (slot <= hi[:, None])
    return jnp.where(seen, index_scores(q_idx, w, keys), -jnp.inf)


def _kernel(count_ref, tab_ref, lo_ref, hi_ref, q_ref, w_ref, k_hbm, o_ref, k_buf, sems,
            *, page_size, block, table_width):
    b = pl.program_id(0)
    count = count_ref[b]
    lo, hi = lo_ref[b], hi_ref[b]
    cols = block * page_size

    def copy_block(buf, page0, wait=False):
        """Start (or wait for) the copies of table entries ``page0 .. page0+block-1`` of
        this lane, side by side into buffer ``buf``."""
        def one(j, carry):
            page = tab_ref[b * table_width + page0 + j]
            c = pltpu.make_async_copy(
                k_hbm.at[page], k_buf.at[buf, pl.ds(j * page_size, page_size)],
                sems.at[buf])
            c.wait() if wait else c.start()
            return carry

        jax.lax.fori_loop(0, block, one, None, unroll=True)

    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)

    @pl.when(count > 0)
    def _first_fetch():
        copy_block(0, 0)

    q = q_ref[...]                                         # [Hi, Di]
    w = _lane_tile(w_ref[...], cols)                       # [Hi, cols], lane-replicated
    col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)

    def body(i, carry):
        page0 = i * block
        buf = i % 2

        @pl.when(i + 1 < count)
        def _next_fetch():
            copy_block(1 - buf, page0 + block)

        copy_block(buf, page0, wait=True)
        s = jax.lax.dot_general(q, k_buf[buf], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)     # [Hi, cols]
        score = jnp.sum(jnp.maximum(s, 0.0) * w, axis=0, keepdims=True)  # [1, cols]
        key_pos = page0 * page_size + col
        o_ref[pl.ds(i, 1), :] = jnp.where((key_pos >= lo) & (key_pos <= hi), score,
                                          -jnp.inf)
        return carry

    jax.lax.fori_loop(0, count, body, None)


def dsa_index_scores(q_idx, w, pool, tables, positions, valid, *, page_size: int,
                     interpret=None):
    """The indexer's scores, one query a lane: q_idx [B,Hi,Di], w [B,Hi] (float32)
    against the index-key ``pool`` [P, page_size, Di] through ``tables`` [B, MP]
    (sentinel == P for unallocated entries). ``positions`` [B] is each lane's query
    position and ``valid`` [B, C] its live slots (ONE run a lane, as for
    ``mla_paged_attention``). Returns float32 [B, C]: slot ``s`` of lane ``b`` holds its
    score if ``first valid <= s <= min(positions[b], last valid and allocated)``, else
    ``-inf``. The walk starts at the lane's page 0 (a left pad is under a prompt bucket
    long) and ends with the block that holds ``hi``."""
    B, Hi, Di = q_idx.shape
    P, ps, _ = pool.shape
    if ps != page_size:
        raise ValueError(f"pool page_size {ps} != page_size argument {page_size}")
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

    def _lane(b, *_):
        return (b, 0, 0)

    kernel = functools.partial(_kernel, page_size=ps, block=n, table_width=blocks * n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[pl.BlockSpec((None, Hi, Di), _lane), pl.BlockSpec((None, Hi, _LANES), _lane),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, blocks, n * ps), _lane),
        scratch_shapes=[pltpu.VMEM((2, n * ps, Di), pool.dtype),
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
    return scores.reshape(B, -1)[:, :C]
