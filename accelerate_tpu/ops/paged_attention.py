"""Paged-attention decode kernel for TPU in Pallas (+ a pure-jnp gather reference).

Dense continuous-batching decode reads a ``[B, max_len, K, hd]`` cache row per lane even
when the lane holds a 40-token chat turn. With the paged KV layout
(``models.common.paged_kv_planes`` / ``paged_kv.BlockManager``) K/V lives in a shared pool
``[num_pages, page_size, K, hd]`` and each lane maps logical pages to physical pages
through an int32 **block table** — this module is the attention read through that
indirection.

``paged_attention`` is the Pallas kernel. Its grid is ``(batch,)``: one grid step serves
one lane, and inside it a ``fori_loop`` with a RUNTIME trip count walks only the **live
range** of the lane's table — from the entry that holds the first key a query may see to
the one that holds the last — a **block** of ``block_pages(...)`` consecutive entries an
iteration (16 pages = 256 tokens = 1 MiB of K+V at the Mistral widths).
:func:`walk_range` turns each lane's scalars — first query position, first and last
valid slot, last allocated table entry, the sliding window — into ``(first page, number
of blocks)``; they ride with the flattened table as **scalar-prefetch** operands, so one
compiled program serves every length. Nothing before the range is fetched or computed,
and behind it at most the rest of the last block: a lane with nothing to read — a freed
slot (its table row is all sentinel), an empty valid row, a lane parked at ``max_len``
whose valid slots all lie behind the window — runs zero iterations, costs one grid step
(its q and valid-mask blocks still move, 0.3 MB at the Mistral widths) and emits zeros.

The pool stays in HBM (``memory_space=pl.ANY``). A block's pages are not neighbours
there, so the kernel issues one ``pltpu.make_async_copy`` per page and plane
(``table[b, first + i·n + j]`` → page ``j`` of a VMEM buffer) and double-buffers by hand:
block ``i + 1`` is in flight while block ``i`` is computed. The first block of a lane
is the one exposed fetch.

Tile shapes are what Mosaic accepts: the last two dims of every block are either the
array's full dims or (8·k, 128·k). A page is therefore fetched WHOLE — all K kv heads —
as a ``[page_size·K, hd]`` tile (a free row-major view of the pool: row ``c`` is slot
``c // K`` of kv head ``c % K``), and all ``T·H`` query rows of a lane ride one
``[T·H, hd]`` tile. Per page of the block one MXU dot gives the ``[T·H, page_size·K]``
scores of every query head against every kv head; the mask keeps the entries whose heads
belong together (GQA: query head h reads kv head ``h // (H/K)``) on top of the
causal/window/valid terms on the global key position, so the second dot against the V
tile lands each head's output directly — no per-head slicing, no in-kernel reshape.
The block's pages share ONE online-softmax update — a pass over the pages for the
scores and their max, a second for the exponentials and the V dots, the scores waiting
in VMEM scratch in between; both are ``fori_loop(unroll=True)``, so the lowering unrolls
them and Python traces a page's work once. The running max / sum (lane-replicated like
``flash_attention``) and the fp32 accumulator live in VMEM scratch across the
iterations. Queries are the decode shapes: ``T == 1`` (the engine's one-token step) or
``T == spec_k+1`` (the batched speculative verify), with per-row causal masking against
the lane's scalar-prefetched start position. int8 pools (``kv_quant``) stay int8 into
the MXU operand; their per-slot scales arrive as lane-dense ``[1, page_size·K]`` rows
and multiply the score columns (K) and the probability columns (V) — the fp32 cache
never exists in HBM *or* VMEM.

A decode program that scans its layers carries the pools of ALL layers stacked
(``[L, num_pages, ...]``) and must not slice one out (that is a copy of the pool a
layer): ``paged_attention(..., layer=l)`` views the stack as ``L·num_pages`` pages and
offsets the clamped table by ``l·num_pages`` — same kernel, same walk.

``paged_attention_reference`` is the same contract in pure jnp (gather through the table,
mask, softmax) — the kernel's test oracle. The serving engine's CPU path instead gathers
into the family's ``_attention_cached`` (``models.common.paged_attention_dispatch``) so
paged decode stays BITWISE the dense engine on the tier-1 host; this reference exists so
ops-level kernel tests need no model.

Sentinel table entries (== num_pages, unallocated logical pages) past a lane's last
allocated entry end the walk; one inside the walked range is clamped into the pool for
the fetch and masked out of the softmax by the valid/causal mask — the kernel never
reads through an uninitialized indirection. Runs in interpreter mode on CPU (tests) and
compiled on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import LANES as _LANES
from ._common import interpret_default as _interpret_default
from ._common import lane_tile as _lane_tile

__all__ = ["paged_attention", "paged_attention_reference", "gather_pages",
           "block_pages", "walk_range"]

_NEG_INF = -1e30
# Where the running max starts: above the mask value, far below any score.
_M_INIT = -1e29
# K+V bytes one iteration of the walk holds in flight (one of the two VMEM buffers).
_BLOCK_BYTES = 1 << 20


def gather_pages(pool: dict, name: str, tables: jax.Array, length: int, dtype,
                 layer=None):
    """Dense ``[B, length, K, hd]`` view of pool plane ``name`` through block tables
    ``[B, MP]`` — sentinel entries clamp to a real page (callers mask those slots).
    int8 planes dequantize against their scale pages (the convert+scale fuses into the
    consuming einsum, so the fp32 copy never lands in HBM). ``layer``: the planes are
    stacked ``[L, P, ...]`` and the pages come from plane ``layer`` (one gather at
    ``[layer, ids]``; the plane is never sliced out of the stack)."""
    P, ps = pool[name].shape[-4:-2]
    ids = jnp.minimum(tables, P - 1)
    B, MP = ids.shape

    def rows(plane):                                           # [B, MP, ps, K, *] pages
        pages = jnp.take(plane, ids, axis=0) if layer is None else plane[layer, ids]
        return pages.reshape(B, MP * ps, *pages.shape[3:])[:, :length]

    x = rows(pool[name])
    if f"{name}_scale" in pool:
        return x.astype(dtype) * rows(pool[f"{name}_scale"]).astype(dtype)
    return x.astype(dtype)


def paged_attention_reference(q, pool, tables, positions, valid, *, page_size,
                              sm_scale, window: int = 0, softcap: float = 0.0):
    """Pure-jnp oracle: q [B,T,H,hd] against the paged pool via gather — identical
    math to the dense cached-attention path (GQA contraction against the unrepeated
    cache, fp32 softmax). ``positions`` [B] is each lane's first query position;
    ``valid`` [B,C] marks live, non-pad cache slots."""
    B, T, H, hd = q.shape
    C = valid.shape[1]
    ck = gather_pages(pool, "k", tables, C, q.dtype)
    cv = gather_pages(pool, "v", tables, C, q.dtype)
    K = ck.shape[2]
    G = H // K
    qg = q.reshape(B, T, K, G, hd)
    scores = jnp.einsum("btkgd,bckd->bkgtc", qg, ck) * sm_scale
    if softcap:
        scores = softcap * jnp.tanh(scores / softcap)
    q_pos = positions[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]   # [B,T]
    slots = jnp.arange(C)[None, None, :]
    causal = slots <= q_pos[:, :, None]                                    # [B,T,C]
    if window:
        causal = causal & (slots > q_pos[:, :, None] - window)
    mask = (causal & valid[:, None, :])[:, None, None, :, :]               # [B,1,1,T,C]
    scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bkgtc,bckd->btkgd", probs, cv).reshape(B, T, H, hd)


def block_pages(page_size: int, kv_heads: int, head_dim: int, itemsize: int,
                max_pages: int) -> int:
    """Table entries the kernel fetches and scores per iteration: as many whole pages
    as ``_BLOCK_BYTES`` of K+V hold (16 at page 16 × 8 kv heads × 128 in bf16, 32 for
    the int8 pool), never more than the table has."""
    page_bytes = 2 * page_size * kv_heads * head_dim * itemsize
    return max(1, min(_BLOCK_BYTES // page_bytes, max_pages))


def walk_range(positions, first_valid, last_live=None, *, T: int, window: int,
               page_size: int, block: int):
    """The part of each lane's block table the kernel walks, per lane:
    ``(first page, number of blocks, live pages)`` — the walk starts at the lane's first
    live table entry and takes ``block`` entries an iteration, so it covers
    ``blocks * block`` entries of which ``live pages`` hold a key some query may see.

    A lane's queries sit at ``positions .. positions+T-1``; the keys any of them may
    see lie in ``[lo, hi]`` with ``hi = positions + T - 1`` (capped at ``last_live``,
    the last slot that is both valid and allocated, where the caller knows it) and
    ``lo`` = the first valid slot, or the start of the first query's window if that is
    later. ``hi < lo`` is an empty range: zero blocks, zero pages. Takes numpy arrays
    (the engine's host-side ``pages_live`` / ``pages_walked`` counters) or jax arrays
    (the kernel's wrapper) — one function, so the counter counts what the kernel does."""
    xp = jnp if isinstance(positions, jax.Array) else np
    hi = positions + (T - 1)
    if last_live is not None:
        hi = xp.minimum(hi, last_live)
    lo = xp.maximum(first_valid, positions - (window - 1)) if window else first_valid
    first_page = xp.maximum(lo, 0) // page_size
    pages = xp.where(hi >= lo, hi // page_size - first_page + 1, 0)
    return first_page, (pages + (block - 1)) // block, pages


def _divmod(x, n: int):
    """``(x // n, x % n)`` for non-negative int32 vectors: shift/mask when ``n`` is a
    power of two (every shipped head layout), the general ops otherwise."""
    if n & (n - 1) == 0:
        return x >> (n.bit_length() - 1), x & (n - 1)
    return x // n, x % n


def _kernel(first_ref, count_ref, tab_ref, pos_ref, *refs, page_size, block, table_width,
            T, H, K, sm_scale, window, softcap, quantized):
    if quantized:
        (q_ref, k_hbm, ks_hbm, v_hbm, vs_hbm, valid_ref, o_ref,
         k_buf, v_buf, ks_buf, vs_buf, sems, s_buf, acc_ref, m_ref, l_ref) = refs
    else:
        ks_hbm = vs_hbm = ks_buf = vs_buf = None
        (q_ref, k_hbm, v_hbm, valid_ref, o_ref,
         k_buf, v_buf, sems, s_buf, acc_ref, m_ref, l_ref) = refs
    b = pl.program_id(0)
    first, count = first_ref[b], count_ref[b]
    R, W = T * H, page_size * K
    hd = q_ref.shape[-1]

    def copy_block(buf, page0, wait=False):
        """Start (or wait for) the async copies that bring table entries ``page0 ..
        page0+block-1`` of this lane into buffer ``buf``: one copy per page and plane,
        since the pages of a block are not neighbours in the pool."""
        def one(j, carry):
            page = tab_ref[b * table_width + page0 + j]
            copies = [
                pltpu.make_async_copy(k_hbm.at[page], k_buf.at[buf, j], sems.at[buf, 0]),
                pltpu.make_async_copy(v_hbm.at[page], v_buf.at[buf, j], sems.at[buf, 1]),
            ]
            if quantized:
                copies += [
                    pltpu.make_async_copy(
                        ks_hbm.at[page], ks_buf.at[buf, pl.ds(j, 1)], sems.at[buf, 0]),
                    pltpu.make_async_copy(
                        vs_hbm.at[page], vs_buf.at[buf, pl.ds(j, 1)], sems.at[buf, 1]),
                ]
            for c in copies:
                c.wait() if wait else c.start()
            return carry

        # Unrolled by the lowering, not by Python: the body is traced once (the engine's
        # decode program traces this kernel several times over, and set-up pays it).
        jax.lax.fori_loop(0, block, one, None, unroll=True)

    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, _M_INIT)
    l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(count > 0)
    def _first_fetch():
        copy_block(0, first)

    q = q_ref[...]                                         # [R, hd]  row r = t*H + h
    # What the mask needs that no iteration changes: column c of a page is slot c // K
    # of kv head c % K; row r is query t = r // H of head h = r % H. The position terms
    # are per column alone when T == 1 (every row is the one query).
    slot, col_kh = _divmod(jax.lax.broadcasted_iota(jnp.int32, (1, W), 1), K)
    t, h = _divmod(jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0), H)
    heads_pair = col_kh == _divmod(h, H // K)[0]           # [R, W]
    q_pos = pos_ref[b] + t if T > 1 else pos_ref[b]        # [R, 1] | scalar

    def body(i, carry):
        page0 = first + i * block
        buf = i % 2

        @pl.when(i + 1 < count)
        def _next_fetch():
            copy_block(1 - buf, page0 + block)

        copy_block(buf, page0, wait=True)

        # Scores of the block, a page at a time: [R, W] each, every query head against
        # every kv head of the page's slots.
        # Mask: a column is visible to a row iff the heads belong together, key <=
        # pos[b] + t, inside the window, and marked valid — the dead pages behind the
        # last live one and sentinel-table garbage land here too and mask out entirely.
        # The causal bound is also the speculative rewind contract: rejected drafts
        # leave stale K/V at slots above pos[b] (once per round under the fused
        # super-step, which rewinds and rewrites in-scan), and those slots are exactly
        # the ones this mask makes unreachable until a later round's writes replace them.
        def score(j, top):
            # int8 pages widen to the query dtype on the way into the MXU (|code| <= 127
            # is exact in bf16); their scales apply to the score / probability COLUMNS.
            k = k_buf[buf, j].astype(q.dtype)              # [W, hd]  row c = slot*K + kh
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * sm_scale                                   # [R, W] fp32
            if quantized:
                s = s * ks_buf[buf, pl.ds(j, 1)]           # [1, W] per-(slot, kv head)
            if softcap:
                s = softcap * jnp.tanh(s / softcap)
            key_pos = (page0 + j) * page_size + slot       # [1, W]
            seen = (key_pos <= q_pos) & (valid_ref[pl.ds(page0 + j, 1), :] > 0)
            if window:
                seen = seen & (key_pos > q_pos - window)
            s = jnp.where(heads_pair & seen, s, _NEG_INF)
            s_buf[j] = s
            return jnp.maximum(top, s)

        top = jax.lax.fori_loop(0, block, score, jnp.full((R, W), _NEG_INF, jnp.float32),
                                unroll=True)
        m_prev = m_ref[:]                                  # [R, LANES] replicated
        m_next = jnp.maximum(m_prev, jnp.max(top, axis=1)[:, None])
        # m never falls below _M_INIT > _NEG_INF, so a masked column's exp() is an exact
        # 0 even in a row that has seen no key yet: its sum stays 0 and the last lines
        # emit zeros for it.
        m_cols = _lane_tile(m_next, W)

        def weigh(j, carry):
            l_cols, pv = carry
            p = jnp.exp(s_buf[j] - m_cols)
            l_cols = l_cols + p
            if quantized:
                p = p * vs_buf[buf, pl.ds(j, 1)]
            v = v_buf[buf, j].astype(q.dtype)
            return l_cols, pv + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        l_cols, pv = jax.lax.fori_loop(
            0, block, weigh,
            (jnp.zeros((R, W), jnp.float32), jnp.zeros((R, hd), jnp.float32)),
            unroll=True)
        alpha = jnp.exp(m_prev - m_next)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(l_cols, axis=1)[:, None]
        acc_ref[:] = acc_ref[:] * _lane_tile(alpha, hd) + pv
        m_ref[:] = m_next
        return carry

    jax.lax.fori_loop(0, count, body, None)

    # A lane that walked nothing, or saw no key on the way (l == 0), emits zeros.
    l = l_ref[:]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[...] = (acc_ref[:] / _lane_tile(l_safe, hd)).astype(o_ref.dtype)


def paged_attention(q, pool, tables, positions, valid, *, page_size, sm_scale,
                    window: int = 0, softcap: float = 0.0, layer=None, interpret=None):
    """Paged-attention decode: q [B,T,H,hd] against pool pages through block tables.

    - ``pool``: ``{"k","v": [P, page_size, K, hd]}`` (+ ``k_scale``/``v_scale``
      [P, page_size, K, 1] fp32 when int8-quantized). With ``layer`` (an int32 scalar,
      traced or not) the planes are the STACKED pools of all layers ``[L, P, ...]`` and
      the read is of plane ``layer``: the stack is viewed as ``L·P`` pages and every
      table entry offset by ``layer·P``, so no plane is sliced out of the stack (a
      decode program carries the stack through its layer scan and must not copy it)
      and the kernel itself is the same.
    - ``tables`` [B, MP] int32 physical page per logical page (sentinel == P for
      unallocated entries: the walk stops at a lane's last allocated entry; one inside
      the walked range is clamped for the fetch and masked from the softmax).
    - ``positions`` [B] int32: the lane's first query position (query t sits at
      ``positions[b] + t``); ``valid`` [B, C] bool marks live cache slots.

    Returns [B, T, H, hd] in q's dtype. T is 1 for plain decode, spec_k+1 for the
    speculative verify; every lane walks the blocks of its live range
    (:func:`walk_range`) sequentially with online-softmax scratch, so output matches the
    dense one-shot softmax to fp32 accumulation order (int8 pools: to the rounding of
    scaling the score instead of each cached element). A lane with an empty range — no
    valid slot, no allocated page, or every valid slot behind the window — emits zeros."""
    B, T, H, hd = q.shape
    P, ps, K = pool["k"].shape[-4:-1]
    if ps != page_size:
        raise ValueError(f"pool page_size {ps} != page_size argument {page_size}")
    if H % K:
        raise ValueError(f"H={H} must be a multiple of KV heads K={K}")
    MP = tables.shape[1]
    C = valid.shape[1]
    R, W = T * H, ps * K
    quantized = "k_scale" in pool
    kv_itemsize = pool["k"].dtype.itemsize
    n = block_pages(ps, K, hd, kv_itemsize, MP)
    if interpret is None:
        interpret = _interpret_default()

    # Each lane's walk, from runtime scalars: one program for every length.
    positions = positions.astype(jnp.int32)
    any_valid = valid.any(axis=1)
    first_valid = jnp.where(any_valid, jnp.argmax(valid, axis=1), C)
    last_valid = jnp.where(any_valid, C - 1 - jnp.argmax(valid[:, ::-1], axis=1), -1)
    allocated = tables < P
    last_allocated = jnp.where(
        allocated.any(axis=1), (MP - jnp.argmax(allocated[:, ::-1], axis=1)) * ps - 1, -1)
    first_page, n_blocks, _ = walk_range(
        positions, first_valid, jnp.minimum(last_valid, last_allocated),
        T=T, window=window, page_size=ps, block=n)

    # The last block of a walk may run up to n-1 entries past the table: pad the table
    # (sentinels clamped to a real page for the fetch) and the valid mask with dead slots
    # (logical slots past max_len can never be written; they mask out like any other dead
    # slot). The mask has one entry per score column: column slot*K + kh of page i carries
    # valid[b, i*ps + slot].
    tables = jnp.pad(jnp.minimum(tables.astype(jnp.int32), P - 1), ((0, 0), (0, n)))
    if layer is not None:
        tables = tables + jnp.asarray(layer, jnp.int32) * P
    valid_cols = jnp.repeat(
        jnp.pad(valid.astype(jnp.int32), ((0, 0), (0, (MP + n) * ps - C))), K, axis=-1,
    ).reshape(B, MP + n, W)

    def _lane(b, *_):
        return (b, 0, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((None, R, hd), _lane), hbm]
    args = [q.reshape(B, R, hd), pool["k"].reshape(-1, W, hd)]
    scratch = [pltpu.VMEM((2, n, W, hd), pool["k"].dtype),
               pltpu.VMEM((2, n, W, hd), pool["v"].dtype)]
    if quantized:
        in_specs.append(hbm)
        args.append(pool["k_scale"].reshape(-1, 1, W))
    in_specs.append(hbm)
    args.append(pool["v"].reshape(-1, W, hd))
    if quantized:
        in_specs.append(hbm)
        args.append(pool["v_scale"].reshape(-1, 1, W))
        scratch += [pltpu.VMEM((2, n, W), jnp.float32)] * 2
    in_specs.append(pl.BlockSpec((None, MP + n, W), _lane))
    args.append(valid_cols)
    scratch += [
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.VMEM((n, R, W), jnp.float32),
        pltpu.VMEM((R, hd), jnp.float32),
        pltpu.VMEM((R, _LANES), jnp.float32),
        pltpu.VMEM((R, _LANES), jnp.float32),
    ]

    kernel = functools.partial(
        _kernel, page_size=ps, block=n, table_width=MP + n, T=T, H=H, K=K,
        sm_scale=sm_scale, window=window, softcap=softcap, quantized=quantized,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, R, hd), _lane),
        scratch_shapes=scratch,
    )
    # Decode is HBM-bound. The cost is an upper bound on what the walk can touch, from
    # what is static: the whole table without a window, else the pages a window of
    # `window + T - 1` keys can straddle, in whole blocks; all K kv heads per query row.
    pages = MP if not window else min(MP, -(-(window + T - 1) // ps) + 1)
    walked = -(-pages // n) * n
    out = pl.pallas_call(
        kernel,
        name="paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, R, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        cost_estimate=pl.CostEstimate(
            flops=int(4 * B * R * walked * W * hd),
            bytes_accessed=int(
                B * walked * W * hd * kv_itemsize * 2 + 2 * q.size * q.dtype.itemsize
            ),
            transcendentals=int(B * R * walked * W),
        ),
        interpret=interpret,
    )(first_page.astype(jnp.int32), n_blocks.astype(jnp.int32), tables.reshape(-1),
      positions, *args)
    return out.reshape(B, T, H, hd)
