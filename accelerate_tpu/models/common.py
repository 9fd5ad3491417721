"""Shared model-family machinery: remat policy resolution + KV-cache plane helpers.

Also home to the cross-family fused-CE dispatch (``fused_ce_allowed`` /
``fused_ce_single_shard``) used by the ``loss_impl="fused"`` branches of llama/gpt/t5.

One implementation of the remat knobs every family config exposes (``remat``,
``remat_policy``, ``remat_prevent_cse``), so llama/gpt/t5 cannot drift: the reference
gets the analogous single point from torch's ``checkpoint_wrapper`` applied in
``accelerator.py:1594-1608``; here the policy maps onto ``jax.checkpoint`` policies.

The KV helpers implement the optional int8 cache shared by the decoder families: caches
are plane dicts (``k``/``v`` [B,C,heads,hd], plus ``k_scale``/``v_scale`` [B,C,heads,1]
when quantized); ``write_kv`` quantizes at the write slot, ``read_kv`` dequantizes into
the attention einsum (XLA fuses the convert+scale, so a full-precision copy never
materializes in HBM).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from ..utils.jax_compat import current_abstract_mesh, shard_map as _shard_map

__all__ = [
    "remat_wrap", "kv_planes", "write_kv", "read_kv", "quant_kv",
    "paged_kv_planes", "write_kv_paged", "read_kv_paged", "paged_write_coords",
    "paged_attention_dispatch", "multi_step_decode",
    "fused_ce_allowed", "fused_ce_single_shard",
    "resolve_loss_chunk", "chunked_ce", "ce_sum", "ce_sum_dispatch",
    "sp_active", "sp_manual", "resolve_sp_pipeline", "attention_dispatch",
    "cached_prefill_attention",
]


def remat_wrap(
    fn: Callable,
    *,
    remat: bool,
    policy: str = "full",
    prevent_cse: Optional[bool] = None,
    scan_layers: bool = False,
    static_argnums: Sequence[int] = (),
) -> Callable:
    """``fn`` under the config's activation-checkpointing policy (validated).

    ``policy``: "full" recomputes everything (min memory); "dots" saves matmul outputs and
    recomputes only elementwise ops; "offload" parks the saved dots in pinned host memory.
    ``prevent_cse=None`` resolves automatically: False under ``scan_layers`` (the scan
    boundary already isolates the block, and checkpoint's anti-CSE barriers only pessimize
    XLA's scheduling inside it), True for an unrolled python-loop stack where CSE could
    silently defeat rematerialization.
    """
    if not remat:
        return fn
    if policy == "full":
        jax_policy = None
    elif policy == "dots":
        jax_policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    elif policy == "offload":
        jax_policy = jax.checkpoint_policies.offload_dot_with_no_batch_dims(
            "device", "pinned_host"
        )
    else:
        raise ValueError(f"remat_policy={policy!r}: expected 'full', 'dots' or 'offload'")
    if prevent_cse is None:
        prevent_cse = not scan_layers
    return jax.checkpoint(
        fn, static_argnums=tuple(static_argnums), policy=jax_policy, prevent_cse=prevent_cse
    )


# ------------------------------------------------------------------------ KV cache planes
def kv_planes(batch: int, max_len: int, heads: int, head_dim: int, dtype, quantized: bool):
    """One layer's empty cache planes: {k, v} (+ {k_scale, v_scale} when int8)."""
    shape = (batch, max_len, heads, head_dim)
    if quantized:
        scale = (batch, max_len, heads, 1)
        return {
            "k": jnp.zeros(shape, jnp.int8), "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(scale, jnp.float32),
            "v_scale": jnp.zeros(scale, jnp.float32),
        }
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def quant_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 quantization per (batch, token, head): x [B,T,K,hd] →
    (int8 values, fp32 scales [B,T,K,1]). Scale floor keeps all-zero rows exact."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0, 1e-8)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def write_kv(kv: dict, name: str, val: jax.Array, index, layer=None) -> dict:
    """Write ``val`` [B,T,...] into cache plane ``name`` at ``index`` (scalar slot for all
    rows, or per-row vector: row b's tokens land at slots ``index[b] .. index[b]+T-1`` —
    the continuous-batching decode (T == 1) and the batched speculative verify (T == k)
    share this path), quantizing when the cache is int8. Per-row writes past the cache
    end are dropped (jax scatter OOB semantics); the serving engine's budget capping
    guarantees no emitted token ever depends on a dropped slot.

    ``layer`` (per-row ``index`` only): the planes are STACKED ``[L, B, max_len, ...]``
    and the write lands in plane ``layer`` of the stack — the form a layer scan that
    carries the whole cache writes through (:func:`write_kv_paged`)."""
    out = {}
    if f"{name}_scale" in kv:
        q, scale = quant_kv(val)
        planes = ((name, q), (f"{name}_scale", scale))
    else:
        planes = ((name, val.astype(kv[name].dtype)),)
    if layer is not None and jnp.ndim(index) == 0:
        raise ValueError("write_kv: a layer of stacked planes is written at per-row slots")
    for key, plane in planes:
        if jnp.ndim(index) == 0:
            out[key] = jax.lax.dynamic_update_slice(
                kv[key], plane.astype(kv[key].dtype), (0, index, 0, 0)
            )
        else:
            rows = jnp.arange(plane.shape[0])
            T = plane.shape[1]
            if T == 1:
                at, new = (rows, index), plane[:, 0]
            else:
                slots = index[:, None] + jnp.arange(T, dtype=index.dtype)[None, :]
                at, new = (rows[:, None], slots), plane
            if layer is not None:
                at = (layer, *at)
            out[key] = kv[key].at[at].set(new.astype(kv[key].dtype))
    return out


def read_kv(new_kv: dict, name: str, dtype) -> jax.Array:
    """Cache plane as compute dtype; int8 planes dequantize (the convert+scale fuses into
    the attention einsum, so the full-precision cache never materializes in HBM)."""
    if f"{name}_scale" in new_kv:
        return new_kv[name].astype(dtype) * new_kv[f"{name}_scale"].astype(dtype)
    return new_kv[name]


# ---------------------------------------------------------------- paged KV cache planes
def paged_kv_planes(num_pages: int, page_size: int, heads: int, head_dim: int, dtype,
                    quantized: bool):
    """One layer's empty paged pool: {k, v} [P, page_size, K, hd] (+ fp32 scales
    [P, page_size, K, 1] when int8) — the shared-pool counterpart of
    :func:`kv_planes`, indexed by (physical page, slot) instead of (lane, position).
    ``paged_kv.BlockManager`` owns which lane references which page."""
    shape = (num_pages, page_size, heads, head_dim)
    if quantized:
        scale = (num_pages, page_size, heads, 1)
        return {
            "k": jnp.zeros(shape, jnp.int8), "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(scale, jnp.float32),
            "v_scale": jnp.zeros(scale, jnp.float32),
        }
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def write_kv_paged(kv: dict, name: str, val: jax.Array, pages: jax.Array,
                   offs: jax.Array, layer=None) -> dict:
    """Write ``val`` [B,T,K,hd] into pool plane ``name`` at physical slots
    ``(pages[b,t], offs[b,t])``, quantizing when the pool is int8 (same per-slot
    quantization as the dense :func:`write_kv`, so paged and dense caches hold
    bit-identical values). Sentinel page ids (== num_pages) are out of bounds and
    the scatter DROPS them — stale/unallocated block-table entries and past-budget
    draft writes vanish instead of corrupting another lane's pages.

    ``layer``: the planes are the STACKED pool ``[L, P, page_size, K, hd]`` and the
    write lands at ``[layer, pages, offs]`` — a scatter into the stack, so a layer scan
    that CARRIES the stack updates it in place (a scan that takes the stack as ``xs``
    and returns the written layers as ``ys`` slices, restacks and copies the whole
    pool every step). A sentinel page id drops the update whatever the layer."""
    out = {}
    if f"{name}_scale" in kv:
        q, scale = quant_kv(val)
        planes = ((name, q), (f"{name}_scale", scale))
    else:
        planes = ((name, val.astype(kv[name].dtype)),)
    at = (pages, offs) if layer is None else (layer, pages, offs)
    for key, plane in planes:
        out[key] = kv[key].at[at].set(plane.astype(kv[key].dtype))
    return out


def read_kv_paged(new_kv: dict, name: str, tables: jax.Array, length: int,
                  dtype, layer=None) -> jax.Array:
    """Dense ``[B, length, K, hd]`` compute-dtype view of pool plane ``name``
    gathered through block tables [B, MP] — the jnp gather read the CPU tier-1
    suite exercises (sentinel entries clamp to a real page; the caller's
    valid/causal mask hides those slots). int8 pools dequantize like
    :func:`read_kv`. ``layer`` gathers ``pool[layer, ids]`` from the STACKED pool.
    ONE implementation shared with the kernel's test oracle
    (``ops.paged_attention.gather_pages``) — the CPU gather path and the reference
    the kernel is pinned against can never diverge."""
    from ..ops.paged_attention import gather_pages

    return gather_pages(new_kv, name, tables, length, dtype, layer=layer)


# ------------------------------------------------------------- latent (MLA) cache planes
def latent_width(values: int) -> int:
    """Width of a latent cache row that holds ``values`` (c_kv | k_rope): whole 128-lane
    tiles. The chip's memory lays a 576-wide row out as 640 anyway, and Mosaic cannot
    cut a page out of a plane declared 576 wide; the lanes past ``values`` are never
    written with anything but zeros and never read."""
    return -(-values // 128) * 128


def latent_planes(batch: int, max_len: int, values: int, dtype) -> dict:
    """One layer's empty dense LATENT cache: ``{"latent": [B, max_len, latent_width]}``
    — the K/V-free counterpart of :func:`kv_planes` for latent attention: one row of
    ``values`` a token, shared by every head."""
    return {"latent": jnp.zeros((batch, max_len, latent_width(values)), dtype)}


def paged_latent_planes(num_pages: int, page_size: int, values: int, dtype) -> dict:
    """One layer's empty paged LATENT pool: ``{"latent": [P, page_size,
    latent_width]}`` — the second page layout beside :func:`paged_kv_planes`'s
    ``{k, v} [P, page_size, K, hd]``; ``paged_kv.BlockManager`` (pages and tables only)
    serves both."""
    return {"latent": jnp.zeros((num_pages, page_size, latent_width(values)), dtype)}


def write_latent_paged(kv: dict, val: jax.Array, pages: jax.Array,
                       offs: jax.Array) -> dict:
    """Write latent rows ``val`` [B,T,values] at physical slots ``(pages[b,t],
    offs[b,t])`` of the pool plane, in place on a donated carry; sentinel page ids are
    out of bounds and DROP (:func:`write_kv_paged`'s contract)."""
    pool = kv["latent"]
    row = jnp.pad(val.astype(pool.dtype),
                  ((0, 0), (0, 0), (0, pool.shape[-1] - val.shape[-1])))
    return {"latent": pool.at[pages, offs].set(row)}


def ring_pages(window: int, page_size: int) -> int:
    """Pages of a lane's ring for a sliding layer of ``window`` keys: the most pages a
    window can touch (it need not start on a page boundary), and one to spare."""
    return -(-(window + page_size - 1) // page_size) + 1


def ring_tables(positions: jax.Array, max_pages: int, page_size: int,
                window: int) -> jax.Array:
    """The COMPUTED block tables [B, max_pages] of a sliding layer's ring pool ``[B ·
    R, page_size, W]``: logical page ``j`` of lane ``b`` lies at ``b · R + j mod R`` while
    it holds a key of the window that ends at ``positions[b]``, and is the sentinel (``B
    · R``) otherwise — so :func:`paged_write_coords`, :func:`write_latent_paged` and
    the paged kernels' walks serve a ring as they serve allocated pages, and no
    allocator knows of it. A page that leaves the window is overwritten ``R`` pages on."""
    B, R = positions.shape[0], ring_pages(window, page_size)
    j = jnp.arange(max_pages, dtype=jnp.int32)[None, :]
    first = jnp.maximum(positions - (window - 1), 0) // page_size
    inside = (j >= first[:, None]) & (j <= (positions // page_size)[:, None])
    lane = jnp.arange(B, dtype=jnp.int32)[:, None]
    return jnp.where(inside, lane * R + j % R, jnp.int32(B * R))


def paged_read_impl() -> str:
    """``"kernel"`` or ``"gather"``: which paged-attention read a decoder family takes
    — the Pallas kernel on a TPU backend (or when forced), else the gather through the
    table. ``ACCEL_PAGED_ATTN`` ∈ {auto, kernel, gather} picks it at trace time."""
    import os

    from ..utils.imports import is_tpu_available

    impl = os.environ.get("ACCEL_PAGED_ATTN", "auto")
    if impl not in ("auto", "kernel", "gather"):
        raise ValueError(
            f"ACCEL_PAGED_ATTN={impl!r}: expected 'auto', 'kernel' or 'gather'"
        )
    if impl == "auto":
        impl = "kernel" if is_tpu_available() else "gather"
    return impl


def paged_write_coords(tables: jax.Array, pos_grid: jax.Array, page_size: int,
                       max_len: int, num_pages: int):
    """Physical (page, slot) write coordinates for logical positions
    ``pos_grid`` [B,T] through block tables [B,MP] — the ONE copy of the
    logical→physical routing both decoder families' paged forwards share.
    Positions at/past ``max_len`` (idle-lane clamps, past-budget draft tails) and
    unallocated logical pages route to the SENTINEL page id (== ``num_pages``,
    out of bounds for the pool's page axis) so the scatter DROPS them — the
    paged spelling of the dense out-of-bounds-write contract."""
    logical = jnp.minimum(pos_grid // page_size, tables.shape[1] - 1)
    pages = jnp.where(
        pos_grid < max_len,
        jnp.take_along_axis(tables, logical, axis=1),
        jnp.int32(num_pages),
    )
    return pages, pos_grid % page_size


def multi_step_decode(forward_one: Callable, cache, tokens: jax.Array,
                      positions: jax.Array, active: jax.Array, budgets: jax.Array,
                      eos_ids: jax.Array, select_token: Callable, xs, n_steps: int,
                      max_len: int):
    """N cached decode steps as ONE ``lax.scan`` — the device-resident super-step
    both decoder families' ``forward_slots_multi`` wrappers share.

    Per scan step the carried ``tokens`` [B] (each lane's PENDING token — emitted
    by the previous step but not yet written, exactly the engine's host-loop
    invariant) are written+attended at ``positions``, one new token per live lane
    is selected by ``select_token(logits [B,V], x)`` (argmax for greedy; the
    sampled program folds per-lane emission-indexed keys in via ``xs``), and
    EOS/budget masking freezes finished lanes IN-SCAN: a frozen lane's write
    position is clamped to ``max_len`` so the dense scatter and the paged
    sentinel route both DROP the write (see :func:`write_kv` /
    :func:`paged_write_coords`) — which is also why the final emitted token of a
    finishing lane is never written: the engine frees the lane before the next
    dispatch, at every N.

    ``active`` bool[B] marks live lanes (idle lanes start frozen and never write
    — their host-side position stays put; a lane is fully re-initialized at
    admit). ``budgets``
    int32[B] is each lane's REMAINING token budget (emission stops at exactly
    ``budgets`` tokens — the drain clamps again host-side, belt and braces).
    ``eos_ids`` int32[B] uses −1 for "no EOS".

    Returns ``(cache, tok_buf [N,B], counts [B])``: the token buffer is
    step-major (drain order), ``counts[b]`` is how many of lane b's rows are
    real emissions; the lane's final position is ``positions[b] + counts[b]``."""
    done0 = ~active
    count0 = jnp.zeros(tokens.shape, jnp.int32)

    def body(carry, x):
        cache, tok, pos, done, count = carry
        write_pos = jnp.where(done, jnp.int32(max_len), pos)
        logits, cache = forward_one(cache, tok, write_pos)
        with jax.named_scope("sample"):
            nxt = select_token(logits, x)
        nxt = jnp.where(done, tok, nxt)
        emit = ~done
        count = count + emit.astype(jnp.int32)
        hit_eos = (eos_ids >= 0) & (nxt == eos_ids)
        done = done | (emit & (hit_eos | (count >= budgets)))
        pos = jnp.where(emit, pos + 1, pos)
        return (cache, nxt, pos, done, count), nxt

    (cache, _, _, _, counts), tok_buf = jax.lax.scan(
        body, (cache, tokens, positions, done0, count0), xs, length=n_steps
    )
    return cache, tok_buf, counts


def spec_multi_step_decode(forward_verify: Callable, propose: Callable,
                           select_ref: Callable, cache, tokens: jax.Array,
                           positions: jax.Array, active: jax.Array,
                           budgets: jax.Array, eos_ids: jax.Array,
                           key_tab: jax.Array, history: jax.Array,
                           hist_lens: jax.Array, n_steps: int, spec_k: int,
                           max_len: int):
    """N speculative rounds (draft → verify → accept) as ONE ``lax.scan`` — the
    device-resident speculative super-step both decoder families'
    ``forward_slots_spec_multi`` wrappers share. Composes :func:`multi_step_decode`'s
    lane-freezing carry with the serving engine's host spec round
    (``serving._spec_step``), eliminating the per-round host round-trip.

    Per scan step: ``propose(history, hist_lens) -> proposals [B, spec_k]``
    drafts on device from the carried token history (prompt + all emissions so
    far, packed from column 0 — the resident NgramDrafter is a pure gather);
    the carried pending ``tokens`` [B] and the proposals form the ``[B, spec_k+1]``
    verify sequence, written+attended at ``positions`` via
    ``forward_verify(cache, seq, write_pos) -> (logits [B, spec_k+1, V], cache)``;
    ``select_ref(logits, keys) -> ref [B, spec_k+1]`` picks the reference tokens
    (argmax for greedy lanes, the engine's replay sampler for sampled lanes);
    acceptance is :func:`generation.speculative_prefix_accept`.

    The bitwise-parity linchpin is the per-lane emission-key CURSOR: sampled
    draws consume keys indexed by EMISSION count, and acceptance makes that
    count lane-varying, so ``xs``-style key threading cannot work. Instead
    ``key_tab`` [B, K, 2] holds each lane's next K emission keys (K ≥
    n_steps·(spec_k+1) covers the worst case) and the carried ``count`` is the
    cursor: round keys are ``key_tab[b, count[b] + j]`` — exactly the keys the
    host loop's ``_step_keys_window(req, len(req.tokens), spec_k+1)`` would
    fetch at the same point, because ``len(req.tokens)`` grows by the SAME
    per-lane ``n_emit``.

    Lane freezing, the pending-token invariant, and the frozen-lane write-drop
    (position clamped to ``max_len`` → dense OOB scatter / paged sentinel both
    drop) carry over from :func:`multi_step_decode` verbatim. Rejected-draft
    writes above the accepted prefix leave garbage KV, masked by causality
    until the NEXT round's window (which starts exactly at the first garbage
    slot and spans ``spec_k+1 ≥`` the garbage run) overwrites it — the PR-6
    garbage-above-rewind contract, now applied per scan round.

    Accepted emissions are appended to the carried ``history`` in-scan (OOB
    columns drop), so round r+1 drafts from a context that includes round r's
    tokens — no host involvement at any point.

    Returns ``(cache, tok_buf [N, B, spec_k+1], emits [N, B], counts [B],
    proposed [B], accepted [B])``: per round, ``tok_buf[r, b, :emits[r, b]]``
    are lane b's real emissions (drain round-major, lane-minor to match the
    host loop's streaming order); ``counts`` is the per-lane emission total
    (final position is ``positions[b] + counts[b]``); ``proposed``/``accepted``
    are the telemetry accept-rate counters (spec_k per live lane per round /
    accepted-prefix lengths), summed on device in the carry."""
    from ..generation import speculative_prefix_accept

    B = tokens.shape[0]
    S = history.shape[1]
    k1 = spec_k + 1
    done0 = ~active
    zeros = jnp.zeros((B,), jnp.int32)

    def body(carry, _):
        cache, hist, lens, tok, pos, done, count, proposed, accepted = carry
        live = ~done
        props = propose(hist, lens)
        seq = jnp.concatenate([tok[:, None], props], axis=1)
        write_pos = jnp.where(done, jnp.int32(max_len), pos)
        logits, cache = forward_verify(cache, seq, write_pos)
        # Emission-key cursor: lane b's j-th key this round is its (count+j)-th
        # emission key. The clip only guards the table edge — a live lane never
        # reads past n_steps*(spec_k+1)-1, and the window itself already clamps
        # at the request's key-schedule end like the host loop's does.
        ki = jnp.clip(
            count[:, None] + jnp.arange(k1, dtype=jnp.int32)[None, :],
            0, key_tab.shape[1] - 1,
        )
        keys = jnp.take_along_axis(key_tab, ki[:, :, None], axis=1)
        ref = select_ref(logits, keys)
        n_emit, last, hit_eos, n_acc = speculative_prefix_accept(
            props, ref, live, budgets - count, eos_ids
        )
        # Append this round's emissions to the drafting history (columns past
        # n_emit route to S — out of bounds, the scatter drops them).
        wi = jnp.where(
            jnp.arange(k1, dtype=jnp.int32)[None, :] < n_emit[:, None],
            lens[:, None] + jnp.arange(k1, dtype=jnp.int32)[None, :],
            jnp.int32(S),
        )
        hist = hist.at[jnp.arange(B)[:, None], wi].set(ref)
        lens = lens + n_emit
        tok = jnp.where(n_emit > 0, last, tok)
        count = count + n_emit
        pos = pos + n_emit
        done = done | (live & (hit_eos | (count >= budgets)))
        proposed = proposed + jnp.where(live, jnp.int32(spec_k), 0)
        accepted = accepted + n_acc
        carry = (cache, hist, lens, tok, pos, done, count, proposed, accepted)
        return carry, (ref, n_emit)

    carry0 = (cache, history, hist_lens, tokens, positions, done0, zeros,
              zeros, zeros)
    (cache, _, _, _, _, _, counts, proposed, accepted), (tok_buf, emits) = (
        jax.lax.scan(body, carry0, None, length=n_steps)
    )
    return cache, tok_buf, emits, counts, proposed, accepted


def paged_attention_dispatch(q, pool, tables, positions, valid, *, page_size: int,
                             sm_scale: float, window: int = 0, softcap: float = 0.0,
                             dtype, dense_attention, layer=None):
    """Family-shared paged-attention read: the Pallas kernel on a TPU backend (or when
    forced), else gather-through-the-table into the family's own dense cached-attention
    math — which makes CPU paged decode BITWISE the dense engine (the tier-1 parity
    contract; the kernel path matches to fp32 accumulation order).

    ``ACCEL_PAGED_ATTN`` ∈ {auto, kernel, gather} picks the path (trace-time, like the
    backend probe in :func:`attention_dispatch`); ``dense_attention(ck, cv)`` is the
    family's gather-path closure over its q/positions/valid/cfg. A kernel the compiler
    refuses is an error at the call — there is no fallback from one path to the other.
    ``layer``: ``pool`` is the STACKED pool ``[L, P, ...]`` and the read is of plane
    ``layer`` — neither path slices that plane out of the stack."""
    if paged_read_impl() == "kernel":
        from ..ops.paged_attention import paged_attention

        return paged_attention(
            q, pool, tables, positions, valid, page_size=page_size,
            sm_scale=sm_scale, window=window, softcap=softcap, layer=layer,
        )
    ck = read_kv_paged(pool, "k", tables, valid.shape[1], dtype, layer=layer)
    cv = read_kv_paged(pool, "v", tables, valid.shape[1], dtype, layer=layer)
    return dense_attention(ck, cv)


def _softcap(scores: jax.Array, cap: float) -> jax.Array:
    """Gemma-style logit capping: cap·tanh(x/cap) (identity when cap == 0)."""
    return cap * jnp.tanh(scores / cap) if cap else scores


def cached_decode_family(cfg):
    """Resolve the family module owning a config's cached-decode contract
    (``init_cache`` / ``forward_cached`` over ``{layers, valid, index}``): llama or
    gpt. Raises for families without one (bert/t5) — the same loud failure
    ``inference.prepare_pippy`` gives unknown configs."""
    from . import gpt as _gpt
    from . import llama as _llama

    if isinstance(cfg, _gpt.GPTConfig):
        return _gpt
    if isinstance(cfg, _llama.LlamaConfig):
        return _llama
    raise TypeError(
        f"no cached-decode family for {type(cfg).__name__}: expected a LlamaConfig "
        "or GPTConfig (bert/t5 have no KV-cache decode contract)"
    )


# ------------------------------------------------------------- attention dispatch (shared)
_SP_MODES = ("ring", "ulysses", "ulysses_ppermute", "allgather")


def sp_active(mesh) -> bool:
    """Does this mesh (concrete or abstract; may be None) engage the sp axis? The ONE
    copy of the sequence-parallel activation predicate — shared by the family attention
    dispatchers (on the ambient mesh) and the pp sp-under-pp routing (on the mesh arg)."""
    from ..utils.constants import SEQUENCE_AXIS

    return mesh is not None and not mesh.empty and mesh.shape.get(SEQUENCE_AXIS, 1) > 1


def sp_manual(mesh) -> bool:
    """Is the sp axis already MANUAL in this context — i.e. are we inside a shard_map
    whose manual axes include sp (the pipeline's sp×pp composition)? Then the sp
    collectives (``lax.ppermute`` KV rotation / all_to_all) must be issued directly;
    wrapping another shard_map would nest, which fails to lower on the backward."""
    from ..utils.constants import SEQUENCE_AXIS

    return SEQUENCE_AXIS in mesh.manual_axes


def resolve_sp_pipeline(cfg, mesh, schedule: str, virtual_stages: int):
    """Family-shared sp×pp routing decision for ``loss_fn_pp`` → ``(sp_pipeline, cfg)``.

    ``sp_pipeline=True`` when ``cfg.attn_impl`` is an sp mode AND the sp axis is live —
    checked on the mesh ARGUMENT (the one the pipeline's shard_map will run under, which
    callers may pass without ``jax.set_mesh``) and on the ambient context. The pipeline
    then goes manual over sp: activations ride sequence-sliced, the stage body issues
    the ring/ulysses collectives flat (nesting ``make_sp_attention``'s own shard_map
    inside the pipeline's fails MLIR verification on the backward).

    Empirical lowering wall (r4, shared by every family): the ``all_to_all`` PRIMITIVE
    inside the hand-scheduled replay's per-tick ``jax.grad`` does not finish lowering
    (ring/allgather compile in seconds on the same config; ulysses hangs >9 min), so
    under 1f1b or virtual stages the returned cfg substitutes the ppermute-decomposed
    all-to-all (``sequence._a2a_ppermute``) — same math (equivalence-tested), ~2x the
    minimal ring bytes. Users who want the primitive's comm schedule can stay on gpipe
    or ring. ONE copy of both the predicate and the substitution, so the families
    cannot drift when the wall moves."""
    import dataclasses

    if cfg.attn_impl not in _SP_MODES:
        return False, cfg
    if not (sp_active(mesh) or sp_active(current_abstract_mesh())):
        return False, cfg
    if cfg.attn_impl == "ulysses" and (schedule == "1f1b" or virtual_stages > 1):
        cfg = dataclasses.replace(cfg, attn_impl="ulysses_ppermute")
    return True, cfg


def _mosaic_sharded(local, q, k, v, rows=(), scalars=()):
    """``local(q, k, v, *rows, *scalars)`` — an attention kernel over q [B,S,H,hd] and
    k/v [B,T,K,hd] — under a multi-device mesh. A Mosaic custom call has no partitioning
    rule (jax refuses to lower one GSPMD would have to partition), so when the ambient
    mesh has more than one device the call runs under ``shard_map``, each device on its
    own rows and heads (``ops._common.attention_shard_spec``), as ``loss_impl="fused_dp"``
    does for the loss; ``rows`` are per-row arrays [B, ...] that follow the batch,
    ``scalars`` are replicated. Inside an already-manual region (pipeline stages, sp) the
    caller owns the layout."""
    from jax.sharding import PartitionSpec as P

    from ..ops._common import attention_shard_spec

    mesh = current_abstract_mesh()
    if mesh.empty or mesh.size == 1 or mesh.manual_axes:
        return local(q, k, v, *rows, *scalars)
    qkv = attention_shard_spec(mesh, q, k)
    return _shard_map(
        local, mesh=mesh,
        in_specs=(qkv, qkv, qkv) + (P(qkv[0]),) * len(rows) + (P(),) * len(scalars),
        out_specs=qkv, check_vma=False,  # pallas_call outputs carry no vma info
    )(q, k, v, *rows, *scalars)


def _flash_sharded(q, k, v, segment_ids, **kw):
    """``flash_attention`` (causal self-attention) through :func:`_mosaic_sharded`."""
    from ..ops.flash_attention import flash_attention

    def local(q, k, v, *seg):
        return flash_attention(
            q, k, v, causal=True, segment_ids=seg[0] if seg else None, **kw
        )

    return _mosaic_sharded(
        local, q, k, v, rows=() if segment_ids is None else (segment_ids,)
    )


def _local_attn_impl(impl: str) -> str:
    """The single-device form of ``impl``: ``auto`` — and an sp mode where no sp axis is
    live, or in a cached forward, which has no sp form — is ``flash`` on a TPU backend
    and ``xla`` elsewhere (trace-time, like :func:`paged_read_impl`). The ONE copy of the
    rule, for the self-attention and the cached-prefill dispatch alike."""
    if impl == "auto" or impl in _SP_MODES:
        from ..utils.imports import is_tpu_available

        return "flash" if is_tpu_available() else "xla"
    return impl


def cached_prefill_attention(q, ck, cv, index, valid, *, impl: str, sm_scale: float,
                             window: int = 0, softcap: float = 0.0, xla_attention,
                             select=None):
    """Family-shared attention of a cached forward's queries q [B,T,H,hd] against the row
    cache ck/cv [B,C,K,hd] they were just written into at slots ``index .. index+T-1``
    (slot j IS position j; ``valid`` [B,C] marks the live slots).

    A PREFILL chunk takes the flash forward kernel, positioned by the cache index; every
    other call keeps ``xla_attention()``, the family's masked-softmax math over the whole
    row. What makes a call a prefill is read off its arguments, never set:

    - ``index`` is a scalar: every row writes at the same slot (``forward_cached``). The
      engine's per-lane decode and the speculative verify pass a vector.
    - ``T`` is a multiple of 128: whole kernel tiles (the engine's prompt buckets are;
      ``T = 1`` decode is an HBM-bandwidth gather and ``T = spec_k`` a few rows, XLA's
      shapes both).
    - ``impl`` resolves to ``flash`` as in :func:`attention_dispatch` (``auto`` = a TPU
      backend; the sp modes have no cached form and count as ``auto``; ``flash`` forces
      the kernel, interpreted off-TPU; ``xla`` never takes it).

    The kernel sees only the BAND of the row a chunk can attend — the last ``window + T``
    slots up to the chunk's end (the whole row without a window) — so the relayout into
    its [B,K,T,hd] form and its key grid cover 4 608 of an 8 192-slot Mistral row, and a
    key's global position reaches it as ``kv_offset``. ``valid`` rides as the segment
    pair ``(ones, valid)``: the kernel's ``sq == sk ∧ sk ≠ 0`` is the validity mask, its
    ``col ≤ row`` / ``col > row − window`` on global positions the causal band. Equal to
    ``xla_attention()`` on every query row with a live key; a row with none (a left-pad
    position, which no live query attends) reads zeros where XLA reads a mean. A kernel
    the compiler refuses is an error at the call, never a quiet switch.

    ``select`` [B,T,C] bool (a learned sparse selection: the slots each query keeps, the
    same for every head) rides into the kernel as its per-pair mask — the kernel's
    ``flash_fwd_masked`` specialisation; a call without it builds the kernel it built
    before. ``xla_attention()`` has to apply the same selection itself."""
    T, C = q.shape[1], ck.shape[1]
    if jnp.ndim(index) or T % 128 or _local_attn_impl(impl) != "flash":
        return xla_attention()
    from ..ops.flash_attention import _flash_bhsd_offset

    span = min(C, window + T) if window else C
    start = jnp.clip(index + T - span, 0, C - span).astype(jnp.int32)
    ck, cv, valid = (
        jax.lax.dynamic_slice_in_dim(a, start, span, axis=1) for a in (ck, cv, valid)
    )
    rows = (valid,)
    if select is not None:
        rows += (jax.lax.dynamic_slice_in_dim(select, start, span, axis=2).astype(jnp.int8),)

    def local(q, k, v, valid, *rest):
        *pair, index, start = rest
        return _flash_bhsd_offset(
            q, k, v, q_offset=index, kv_offset=start, causal=True, sm_scale=sm_scale,
            window=window, softcap=softcap,
            segments=(jnp.ones(q.shape[:2], jnp.int32), valid.astype(jnp.int32)),
            mask=pair[0] if pair else None,
        )

    return _mosaic_sharded(local, q, ck, cv, rows=rows, scalars=(index, start))


def attention_dispatch(q, k, v, mask, *, impl: str, sm_scale: float, window: int = 0,
                       softcap: float = 0.0, segment_ids=None, xla_attention=None):
    """Family-shared causal self-attention dispatch (llama/gpt): ``impl`` in
    ``auto | flash | xla | ring | ulysses | allgather`` over q [B,S,H,hd],
    k/v [B,S,K,hd] (GQA: K ≤ H).

    - sp modes need an active mesh with sp > 1; inside a manual-sp shard_map (the
      pipeline's sp×pp composition) the collectives are issued flat, else the call is
      wrapped in ``make_sp_attention``'s own shard_map. Without sp, they fall back to
      local attention. Packed rows (``segment_ids``) compose with every impl.
    - ``xla_attention(q, k, v, mask)`` is the family's reference path (``impl="xla"``,
      and what ``auto`` resolves to off-TPU). A flash kernel the compiler refuses is an
      error at the call, never a quiet switch to this path."""
    from ..utils.constants import SEQUENCE_AXIS

    if impl in _SP_MODES:
        mesh = current_abstract_mesh()
        if sp_active(mesh):
            if sp_manual(mesh):
                from ..parallel.sequence import sequence_parallel_attention

                return sequence_parallel_attention(
                    q, k, v, mode=impl, axis_name=SEQUENCE_AXIS, causal=True,
                    window=window, softcap=softcap, sm_scale=sm_scale,
                    segment_ids=segment_ids,
                )
            from ..parallel.sequence import make_sp_attention

            attn = make_sp_attention(
                mesh, mode=impl, axis_name=SEQUENCE_AXIS, causal=True,
                window=window, softcap=softcap, sm_scale=sm_scale,
            )
            return attn(q, k, v, segment_ids=segment_ids)
    impl = _local_attn_impl(impl)
    if impl == "flash":
        # Packed rows stay on the flash path: the kernels take segment ids directly.
        return _flash_sharded(
            q, k, v, segment_ids, window=window, sm_scale=sm_scale, softcap=softcap
        )
    if impl != "xla":
        raise ValueError(
            f"attn_impl={impl!r}: expected 'auto', 'flash', 'xla', 'ring', 'ulysses', "
            "'ulysses_ppermute' or 'allgather'"
        )
    return xla_attention(q, k, v, mask)


def resolve_loss_chunk(loss_chunk: int, S: int, vocab_size: int) -> int:
    """Resolve the chunked-CE chunk length (0 tokens = don't chunk).

    An explicit ``loss_chunk`` is always honored (``chunked_ce`` pads S up to a chunk
    multiple, so divisibility never silently disables it). Auto mode (``loss_chunk=0``)
    chunks at 512 only when the fp32 logits would be large enough to matter (> 64 MB per
    example row); ``-1`` disables chunking outright.
    """
    if loss_chunk == -1:
        return 0
    if loss_chunk > 0:
        return min(loss_chunk, S)
    # auto: threshold on S*V; 2**24 elements = 64 MB of fp32 logits per example row.
    if S * vocab_size <= 2**24:
        return 0
    return min(512, S)


def _chunk_logits(xc, hd, bias, softcap: float):
    """One chunk's fp32 logits [B, c, V] (head product, + fp32 bias, softcap)."""
    logits = (xc @ hd).astype(jnp.float32)
    if bias is not None:
        logits = logits + bias
    return _softcap(logits, softcap)


def _chunk_nll(logits, tc, mc):
    """(logsumexp [B, c], one-hot of the targets [B, c, V], masked sum of -log p(target))
    of one chunk's logits. The target's logit is a one-hot reduction, not a gather: it
    fuses into the ``exp`` sum's pass over the logits (a gather made the TPU write the
    chunk's logits in float32 beside the bfloat16 the passes read) and partitions over a
    sharded vocabulary as a sum does."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jnp.arange(logits.shape[-1], dtype=tc.dtype) == tc[..., None]
    tgt = jnp.where(onehot, logits, 0.0).sum(axis=-1)
    return lse, onehot, -((tgt - lse) * mc).sum()


def _chunks(a, chunk: int):
    """[B, S, ...] -> [S // chunk, B, chunk, ...]: the chunk scan's ``xs`` layout."""
    B, S = a.shape[:2]
    return a.reshape(B, S // chunk, chunk, *a.shape[2:]).swapaxes(0, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _chunked_ce_sum(x, hd, bias, targets, mask, chunk: int, softcap: float):
    """The primal: the chunk scan alone (evaluation, a loss read without ``grad``).
    ``hd`` is the head in the compute dtype, ``bias`` fp32 or None, S a chunk multiple."""

    def body(total, xtm):
        xc, tc, mc = xtm
        _, _, nll = _chunk_nll(_chunk_logits(xc, hd, bias, softcap), tc, mc)
        return total + nll, None

    total, _ = jax.lax.scan(
        body, jnp.zeros((), jnp.float32), [_chunks(a, chunk) for a in (x, targets, mask)])
    return total


def _chunked_ce_sum_fwd(x, hd, bias, targets, mask, chunk, softcap):
    """Loss AND its gradients in one scan: a chunk's logits are formed once and give the
    loss term, ``dlogits`` and from it ``dx`` (the scan's ``ys``) and ``dW`` / ``dbias``
    (the carry). All three are per unit of the loss's cotangent."""

    def body(carry, xtm):
        total, dw, db = carry
        xc, tc, mc = xtm
        logits = _chunk_logits(xc, hd, bias, softcap)
        lse, onehot, nll = _chunk_nll(logits, tc, mc)
        dl = (jnp.exp(logits - lse[..., None]) - onehot) * mc[..., None].astype(jnp.float32)
        if softcap:
            dl = dl * (1.0 - jnp.square(logits / softcap))       # d cap·tanh(z/cap) / dz
        if db is not None:
            db = db + dl.sum(axis=(0, 1))
        dl = dl.astype(hd.dtype)                                 # [B, c, V]
        dw = dw + jnp.einsum("bcd,bcv->dv", xc, dl).astype(hd.dtype)
        return (total + nll, dw, db), (dl @ hd.T).astype(xc.dtype)

    (total, dw, db), dxs = jax.lax.scan(
        body,
        (jnp.zeros((), jnp.float32), jnp.zeros_like(hd),
         None if bias is None else jnp.zeros_like(bias)),
        [_chunks(a, chunk) for a in (x, targets, mask)],
    )
    return total, (dxs.swapaxes(0, 1).reshape(x.shape), dw, db)


def _chunked_ce_sum_bwd(chunk, softcap, res, g):
    # ``targets`` and ``mask`` take no gradient: None is the zero cotangent.
    return (*((None if r is None else (g * r).astype(r.dtype)) for r in res), None, None)


_chunked_ce_sum.defvjp(_chunked_ce_sum_fwd, _chunked_ce_sum_bwd)


def chunked_ce(x, head, targets, mask, chunk: int, dtype, final_softcap: float = 0.0,
               bias=None):
    """Memory-efficient cross-entropy: per-chunk head matmul + logsumexp, differentiated
    in the same pass.

    ``x`` [B,S,D] (post-final-norm hidden), ``head`` [D,V]; returns the sum of
    -log p(target) over unmasked positions. The fp32 [B,S,V] logits are never
    materialized — each scan step computes one [B,chunk,V] block, so peak memory drops
    from O(S·V) to O(chunk·V). S is padded up to a chunk multiple with masked positions,
    so any chunk works for any sequence length. ``bias`` [V] (gpt-j's lm_head bias) is
    added pre-softmax.

    A ``jax.custom_vjp`` over ``(x, head, bias)``. The loss is a scalar, so its cotangent
    is one number ``g`` and ``dlogits = g · mask · (softmax − onehot)`` is known up to
    ``g`` the moment a chunk's logits exist: under differentiation ONE scan forms the
    logits once and from them the loss term, ``dx`` and ``dW`` (``dbias``) — three
    head-sized products where a backward that recomputes the chunk's logits spends four,
    and no second scan. The residuals are those gradients per unit ``g`` —
    ``dx`` [B,S,D] in ``x``'s dtype, ``dW`` [D,V] accumulated in ``dtype``, ``dbias`` [V]
    in fp32 — and the backward only scales them by ``g``. ``targets`` and ``mask`` take no
    gradient (a float ``mask`` gets zeros, not ``-log p``), and there is no second-order
    or forward-mode derivative through the loss (a ``custom_vjp`` has none). Called
    without differentiation it runs the plain scan.
    """
    S = x.shape[1]
    if S % chunk:
        pad = chunk - S % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    # The casts stay outside the custom_vjp, so each gradient returns in its operand's dtype.
    return _chunked_ce_sum(
        x, head.astype(dtype), None if bias is None else bias.astype(jnp.float32),
        targets, mask, chunk, final_softcap,
    )


def ce_sum(x, head, targets, mask, *, dtype, chunk: int = 0, softcap: float = 0.0,
           bias=None) -> jax.Array:
    """SUM-style chunked/dense CE core — the ONE copy of the softcap + log_softmax +
    target-gather math shared by the model families' normalized loss paths and the 1F1B
    last-stage heads (where sums across microbatch groups must add up exactly)."""
    if chunk > 0:
        return chunked_ce(x, head, targets, mask, chunk, dtype, final_softcap=softcap,
                          bias=bias)
    logits = (x @ head.astype(dtype)).astype(jnp.float32)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    logits = _softcap(logits, softcap)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1).squeeze(-1)
    return -(ll * mask).sum()


def ce_sum_dispatch(x, head, targets, mask, *, loss_impl: str, dtype,
                    chunk: int = 0, softcap: float = 0.0, bias=None) -> jax.Array:
    """SUM-style CE dispatcher — the ONE place every ``loss_impl`` routes through,
    shared across model families (llama/gpt) and across execution modes (single, GPipe,
    and the 1F1B last-stage head, where sums across microbatch groups must add up
    exactly).

    ``bias`` (gpt-j's lm_head bias): the fused kernels have no bias term, so a non-None
    bias always takes the chunked/dense path regardless of ``loss_impl`` — the same
    silent-fallback contract as ``gpt.loss_fn``'s single-device kernel gate.
    """
    S = x.shape[1]
    if loss_impl not in ("auto", "fused", "fused_dp", "fused_tp"):
        raise ValueError(
            f"loss_impl={loss_impl!r}: expected 'auto', 'fused', 'fused_dp', or "
            "'fused_tp' (a typo would otherwise silently run the chunked path)"
        )
    if bias is not None:
        loss_impl = "auto"
    if loss_impl == "fused_tp":
        # Megatron-layout fused CE: the head stays VOCAB-SHARDED over tp (never
        # gathered), each tp shard runs the Pallas kernel on its vocab slice, and the
        # logsumexp merges across tp in fp32 (ops/fused_xent.fused_cross_entropy_tp).
        # Tokens stay sharded over the batch axes. For batch-only layouts use
        # "fused_dp"; single device "fused".
        from jax.sharding import PartitionSpec as P

        from ..ops.fused_xent import fused_cross_entropy_tp
        from ..utils.constants import BATCH_AXES, TENSOR_AXIS as _TP

        mesh = current_abstract_mesh()
        if not getattr(mesh, "axis_names", ()):
            raise ValueError(
                "loss_impl='fused_tp' needs an active mesh context "
                "(Accelerator.build_train_step provides one; or wrap in jax.set_mesh)."
            )
        D = x.shape[-1]

        def _local(xl, tl, ml, hd):
            Bl = xl.shape[0]
            nll = fused_cross_entropy_tp(
                xl.reshape(Bl * S, D), hd, tl.reshape(Bl * S), axis_name=_TP,
                softcap=softcap,
            )
            return (nll * ml.reshape(Bl * S)).sum()[None]

        partials = _shard_map(
            _local,
            mesh=mesh,
            in_specs=(P(BATCH_AXES), P(BATCH_AXES), P(BATCH_AXES), P(None, _TP)),
            out_specs=P(BATCH_AXES),
            check_vma=False,  # pallas_call outputs carry no vma info (kernel contract)
        )(x, targets, mask, head.astype(dtype))
        return partials.sum()
    if loss_impl == "fused_dp":
        # Multi-chip fused CE: shard_map over the batch axes — each device runs the
        # kernel on ITS tokens against a replicated head (in_spec P() makes shard_map's
        # transpose psum the head gradient). For batch-sharded layouts (dp/fsdp); under
        # tp-sharded heads or sp-sharded sequences prefer the chunked path (this one
        # would all-gather the head / sequence into every shard).
        from jax.sharding import PartitionSpec as P

        from ..ops.fused_xent import fused_cross_entropy
        from ..utils.constants import BATCH_AXES

        mesh = current_abstract_mesh()
        if not getattr(mesh, "axis_names", ()):
            raise ValueError(
                "loss_impl='fused_dp' needs an active mesh context "
                "(Accelerator.build_train_step provides one; or wrap in jax.set_mesh)."
            )
        D = x.shape[-1]

        def _local(xl, tl, ml, hd):
            Bl = xl.shape[0]
            nll = fused_cross_entropy(
                xl.reshape(Bl * S, D), hd, tl.reshape(Bl * S), softcap=softcap,
            )
            return (nll * ml.reshape(Bl * S)).sum()[None]

        partials = _shard_map(
            _local,
            mesh=mesh,
            in_specs=(P(BATCH_AXES), P(BATCH_AXES), P(BATCH_AXES), P()),
            out_specs=P(BATCH_AXES),
            check_vma=False,  # pallas_call outputs carry no vma info
        )(x, targets, mask, head.astype(dtype))
        return partials.sum()
    if loss_impl == "fused":
        # Single-shard path: on a real multi-chip mesh fused_ce_single_shard returns
        # None — fall through to the chunked path (or use "fused_dp").
        loss = fused_ce_single_shard(x, head.astype(dtype), targets, mask,
                                     softcap=softcap)
        if loss is not None:
            # fused_ce_single_shard returns the masked MEAN; convert back to SUM so
            # every branch of this dispatcher has identical (sum) semantics.
            return loss * jnp.maximum(mask.sum(), 1.0)
    return ce_sum(x, head, targets, mask, dtype=dtype, chunk=chunk, softcap=softcap,
                  bias=bias)


def fused_ce_allowed() -> bool:
    """True when the single-shard fused-CE kernel may run: one device, or interpret
    mode (CPU tests — lowers to partitionable XLA). On a real multi-device mesh the
    pallas_call would force GSPMD to gather the batch-sharded activations."""
    from ..ops._common import interpret_default

    return jax.device_count() == 1 or interpret_default()


def fused_ce_single_shard(x, head, targets, mask, softcap: float = 0.0):
    """Masked-mean fused cross-entropy over [B, S, D] hidden states, or None.

    Shared dispatch for the model families' ``loss_impl="fused"`` branches: returns None
    when :func:`fused_ce_allowed` says the kernel must not run. ``mask`` [B, S] float;
    ``head`` [D, V] already in compute dtype.
    """
    if not fused_ce_allowed():
        return None
    from ..ops.fused_xent import fused_cross_entropy

    B, S, D = x.shape
    nll = fused_cross_entropy(
        x.reshape(B * S, D), head, targets.reshape(B * S), softcap=softcap
    )
    mask1d = mask.reshape(B * S)
    return (nll * mask1d).sum() / jnp.maximum(mask1d.sum(), 1.0)
