"""Paged-attention decode kernel for TPU in Pallas (+ a pure-jnp gather reference).

Dense continuous-batching decode reads a ``[B, max_len, K, hd]`` cache row per lane even
when the lane holds a 40-token chat turn. With the paged KV layout
(``models.common.paged_kv_planes`` / ``paged_kv.BlockManager``) K/V lives in a shared pool
``[num_pages, page_size, K, hd]`` and each lane maps logical pages to physical pages
through an int32 **block table** — this module is the attention read through that
indirection.

``paged_attention`` is the Pallas kernel: grid ``(batch, logical_page)``, the block table
rides as a **scalar-prefetch** operand so each grid step's BlockSpec index map resolves
``table[b, i]`` to the physical pool page the pipeline DMAs next (double-buffered by the
pipeline machinery itself — the whole indirection lives in the index map).

Tile shapes are what Mosaic accepts: the last two dims of every block are either the
array's full dims or (8·k, 128·k). A page is therefore fetched WHOLE — all K kv heads —
as a ``[page_size·K, hd]`` tile (a free row-major view of the pool: row ``c`` is slot
``c // K`` of kv head ``c % K``), and all ``T·H`` query rows of a lane ride one
``[T·H, hd]`` tile. One MXU dot gives the ``[T·H, page_size·K]`` scores of every query
head against every kv head; the mask keeps the entries whose heads belong together
(GQA: query head h reads kv head ``h // (H/K)``) on top of the causal/window/valid
terms, so the second dot against the V tile lands each head's output directly — no
per-head slicing, no in-kernel reshape. Decode is bound by the page bytes, not by these
K× redundant MXU columns. Online-softmax state (running max / sum, lane-replicated like
``flash_attention``) accumulates in VMEM scratch across the sequential page dimension.
Queries are the decode shapes: ``T == 1`` (the engine's one-token step) or
``T == spec_k+1`` (the batched speculative verify), with per-row causal masking against
the lane's scalar-prefetched start position. int8 pools (``kv_quant``) stay int8 into
the MXU operand; their per-slot scales arrive as lane-dense ``[1, page_size·K]`` rows and
multiply the score columns (K) and the probability columns (V) — the fp32 cache never
exists in HBM *or* VMEM.

``paged_attention_reference`` is the same contract in pure jnp (gather through the table,
mask, softmax) — the kernel's test oracle. The serving engine's CPU path instead gathers
into the family's ``_attention_cached`` (``models.common.paged_attention_dispatch``) so
paged decode stays BITWISE the dense engine on the tier-1 host; this reference exists so
ops-level kernel tests need no model.

Sentinel table entries (== num_pages, unallocated logical pages) are clamped into range
for the fetch and masked out of the softmax by the valid/causal mask — the kernel never
reads through an uninitialized indirection. Runs in interpreter mode on CPU (tests) and
compiled on TPU.
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

__all__ = ["paged_attention", "paged_attention_reference", "gather_pages"]

_NEG_INF = -1e30


def gather_pages(pool: dict, name: str, tables: jax.Array, length: int, dtype):
    """Dense ``[B, length, K, hd]`` view of pool plane ``name`` through block tables
    ``[B, MP]`` — sentinel entries clamp to a real page (callers mask those slots).
    int8 planes dequantize against their scale pages (the convert+scale fuses into the
    consuming einsum, so the fp32 copy never lands in HBM)."""
    P, ps = pool[name].shape[0], pool[name].shape[1]
    ids = jnp.minimum(tables, P - 1)
    pages = jnp.take(pool[name], ids, axis=0)                  # [B, MP, ps, K, hd]
    B, MP = ids.shape
    x = pages.reshape(B, MP * ps, *pages.shape[3:])[:, :length]
    if f"{name}_scale" in pool:
        scales = jnp.take(pool[f"{name}_scale"], ids, axis=0)
        scales = scales.reshape(B, MP * ps, *scales.shape[3:])[:, :length]
        return x.astype(dtype) * scales.astype(dtype)
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


def _divmod(x, n: int):
    """``(x // n, x % n)`` for non-negative int32 vectors: shift/mask when ``n`` is a
    power of two (every shipped head layout), the general ops otherwise."""
    if n & (n - 1) == 0:
        return x >> (n.bit_length() - 1), x & (n - 1)
    return x // n, x % n


def _kernel(tab_ref, pos_ref, *refs, page_size, max_pages, T, H, K, sm_scale,
            window, softcap, quantized):
    if quantized:
        (q_ref, k_ref, ks_ref, v_ref, vs_ref, valid_ref,
         o_ref, acc_ref, m_ref, l_ref) = refs
    else:
        ks_ref = vs_ref = None
        q_ref, k_ref, v_ref, valid_ref, o_ref, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    i = pl.program_id(1)
    R, W = T * H, page_size * K
    hd = q_ref.shape[-1]

    @pl.when(i == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q = q_ref[...]                                         # [R, hd]  row r = t*H + h
    # int8 pages widen to the query dtype on the way into the MXU (|code| <= 127 is
    # exact in bf16); their scales apply to the score / probability COLUMNS below.
    k = k_ref[...].astype(q.dtype)                         # [W, hd]  row c = slot*K + kh
    v = v_ref[...].astype(q.dtype)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale                                           # [R, W] fp32
    if quantized:
        s = s * ks_ref[...]                                # [1, W] per-(slot, kv head)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    # Mask: column c (slot c // K of kv head c % K, global key position i*ps + slot) is
    # visible to row r (query t = r // H, head h = r % H) iff the heads belong together
    # (kv head == h // G), key <= pos[b] + t, inside the window, and marked valid —
    # sentinel-table garbage pages land here too and mask out entirely.
    # The causal bound is also the speculative rewind contract: rejected drafts leave
    # stale K/V at slots above pos[b] (once per round under the fused super-step,
    # which rewinds and rewrites in-scan), and those slots are exactly the ones
    # this mask makes unreachable until a later round's writes replace them.
    slot, col_kh = _divmod(jax.lax.broadcasted_iota(jnp.int32, (R, W), 1), K)
    t, h = _divmod(jax.lax.broadcasted_iota(jnp.int32, (R, W), 0), H)
    key_pos = i * page_size + slot
    q_pos = pos_ref[b] + t
    mask = (col_kh == _divmod(h, H // K)[0]) & (key_pos <= q_pos)
    mask = mask & (jnp.broadcast_to(valid_ref[...], (R, W)) > 0)
    if window:
        mask = mask & (key_pos > q_pos - window)
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[:]                                      # [R, LANES] replicated
    m_curr = jnp.max(s, axis=1)[:, None]
    m_next = jnp.maximum(m_prev, m_curr)
    p = jnp.exp(s - _lane_tile(m_next, W))
    # Fully-masked rows have every s == _NEG_INF == m_next, making exp() == 1; the
    # row sum must still be 0 so finalize emits zeros for never-written lanes.
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_next)
    l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1)[:, None]
    if quantized:
        p = p * vs_ref[...]
    acc_ref[:] = acc_ref[:] * _lane_tile(alpha, hd) + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[:] = m_next

    @pl.when(i == max_pages - 1)
    def _finalize():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[:] / _lane_tile(l_safe, hd)).astype(o_ref.dtype)


def paged_attention(q, pool, tables, positions, valid, *, page_size, sm_scale,
                    window: int = 0, softcap: float = 0.0, interpret=None):
    """Paged-attention decode: q [B,T,H,hd] against pool pages through block tables.

    - ``pool``: ``{"k","v": [P, page_size, K, hd]}`` (+ ``k_scale``/``v_scale``
      [P, page_size, K, 1] fp32 when int8-quantized).
    - ``tables`` [B, MP] int32 physical page per logical page (sentinel == P for
      unallocated entries — clamped for the fetch, masked from the softmax).
    - ``positions`` [B] int32: the lane's first query position (query t sits at
      ``positions[b] + t``); ``valid`` [B, C] bool marks live cache slots.

    Returns [B, T, H, hd] in q's dtype. T is 1 for plain decode, spec_k+1 for the
    speculative verify; every lane processes its pages sequentially with
    online-softmax scratch, so output matches the dense one-shot softmax to fp32
    accumulation order (int8 pools: to the rounding of scaling the score instead of
    each cached element)."""
    B, T, H, hd = q.shape
    P, ps, K = pool["k"].shape[0], pool["k"].shape[1], pool["k"].shape[2]
    if ps != page_size:
        raise ValueError(f"pool page_size {ps} != page_size argument {page_size}")
    if H % K:
        raise ValueError(f"H={H} must be a multiple of KV heads K={K}")
    MP = tables.shape[1]
    C = valid.shape[1]
    R, W = T * H, ps * K
    quantized = "k_scale" in pool
    if interpret is None:
        interpret = _interpret_default()

    # Valid mask padded to the table-covered extent (logical slots past max_len can
    # never be written; they mask out like any other dead slot), one entry per score
    # column: column slot*K + kh of page i carries valid[b, i*ps + slot].
    valid_cols = jnp.repeat(
        jnp.pad(valid.astype(jnp.int32), ((0, 0), (0, MP * ps - C))).reshape(B, MP, ps),
        K, axis=-1,
    ).reshape(B, MP, 1, W)

    def _q_idx(b, i, tabs, pos):
        return (b, 0, 0)

    def _page_idx(b, i, tabs, pos):
        return (jnp.minimum(tabs[b * MP + i], P - 1), 0, 0)

    def _valid_idx(b, i, tabs, pos):
        return (b, i, 0, 0)

    page_spec = pl.BlockSpec((None, W, hd), _page_idx)
    scale_spec = pl.BlockSpec((None, 1, W), _page_idx)
    in_specs = [pl.BlockSpec((None, R, hd), _q_idx), page_spec]
    args = [q.reshape(B, R, hd), pool["k"].reshape(P, W, hd)]
    if quantized:
        in_specs.append(scale_spec)
        args.append(pool["k_scale"].reshape(P, 1, W))
    in_specs.append(page_spec)
    args.append(pool["v"].reshape(P, W, hd))
    if quantized:
        in_specs.append(scale_spec)
        args.append(pool["v_scale"].reshape(P, 1, W))
    in_specs.append(pl.BlockSpec((None, None, 1, W), _valid_idx))
    args.append(valid_cols)

    kernel = functools.partial(
        _kernel, page_size=ps, max_pages=MP, T=T, H=H, K=K,
        sm_scale=sm_scale, window=window, softcap=softcap, quantized=quantized,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, MP),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, R, hd), _q_idx),
        scratch_shapes=[
            pltpu.VMEM((R, hd), jnp.float32),
            pltpu.VMEM((R, _LANES), jnp.float32),
            pltpu.VMEM((R, _LANES), jnp.float32),
        ],
    )
    # Decode is HBM-bound: bytes = every pool page each lane's table covers (+q/out);
    # flops = the two dots over the covered extent (all K kv heads per query row).
    kv_itemsize = pool["k"].dtype.itemsize
    out = pl.pallas_call(
        kernel,
        name="paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, R, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(4 * B * R * MP * W * hd),
            bytes_accessed=int(
                B * MP * W * hd * kv_itemsize * 2 + 2 * q.size * q.dtype.itemsize
            ),
            transcendentals=int(B * R * MP * W),
        ),
        interpret=interpret,
    )(tables.reshape(-1).astype(jnp.int32), positions.astype(jnp.int32), *args)
    return out.reshape(B, T, H, hd)
