"""DeepSeek-V3's decoder, as the serving engine runs it: multi-head latent attention (MLA)
over ONE latent cache with two attention forms, a dense SwiGLU first layer, and expert
layers of a sigmoid group-limited router beside a shared expert, computed over the
experts THIS chip holds.

Published model (deepseek-ai/DeepSeek-V3 ``config.json`` / ``modeling_deepseek.py``),
per layer with ``h = RMSNorm(x)``:

- **MLA.** ``c_q = RMSNorm(h W_qa)``; ``[q_nope | q_rope] = c_q W_qb`` per head;
  ``[c_kv | k_rope] = h W_kva``; ``c_kv = RMSNorm(c_kv)``; YaRN-scaled RoPE on ``q_rope``
  and on the one ``k_rope`` all heads share. **The cache holds** ``(c_kv, k_rope)`` —
  ``kv_lora_rank + qk_rope_dim`` values a token (``models.common.latent_planes``).
  *Prefill form* (``forward_cached``: a chunk of queries against the cached latent):
  ``[k_nope | v] = c_kv W_kvb`` per head, ``o = softmax(q kᵀ · s) v``. *Decode form*
  (``forward_slots_paged``: one query a lane): ``W_kvb`` is stored split by head into
  ``w_kb`` / ``w_vb``, the key half is absorbed into the query (``q_lat = q_nope
  W_kbᵀ``), attention runs over the latent rows themselves
  (``ops.mla_attention.mla_paged_attention``) and the value half is applied to its
  output. The same function of the same cache; ``s = (nope + rope)^-½ · m²`` with YaRN's
  ``m = 0.1 · mscale_all_dim · ln(factor) + 1``.
- **Dense layers** (the first ``n_dense_layers``): SwiGLU. **Expert layers**:
  ``ops.moe.moe_mlp_grouped`` — the router keeps its published width
  (``n_routed_experts``), this chip holds ``experts_held`` of them from
  ``expert_offset`` and computes their part of the result; what the absent experts
  would add is left out and the partial sum goes on to the next layer (one chip of an
  expert-parallel deployment, without its exchange).

The engine's surface (``serving.ContinuousBatcher`` reaches a model through the module
of its config's class): ``init_cache`` + ``forward_cached`` (chunked prefill of one dense
latent row), ``init_paged_cache`` + ``forward_slots_paged`` / ``forward_slots_multi``
(paged decode), ``paged_walk_shape``, ``DECODE_COUNTERS``. Not here, so the engine
refuses them for this model: dense decode rows (``forward_slots``), speculative verify
(``forward_slots_spec_multi``), per-position prefill logits for the prefix cache
(``forward_cached_logits``). Layers are a Python list (``scan_layers`` is False: the
first layer differs, and a pool carried through a scan's ``xs``/``ys`` is copied whole —
here every write is a scatter on the donated carry).
"""

from __future__ import annotations

import dataclasses
import math
from typing import ClassVar, Optional

import jax
import jax.numpy as jnp

from .common import (latent_planes, latent_width, multi_step_decode, paged_latent_planes,
                     paged_read_impl, paged_write_coords, write_latent_paged)
from .llama import _rms_norm

#: What ``forward_slots_multi`` returns beside the token buffers (one int32 array, in
#: this order, summed over the dispatch's steps and expert layers); the engine hands
#: them to its ``engine.decode.drain`` span under these names.
DECODE_COUNTERS = ("moe_pairs", "moe_tokens", "moe_max_on_one_expert")


@dataclasses.dataclass(frozen=True)
class DeepseekConfig:
    vocab_size: int = 129280
    d_model: int = 7168
    n_layers: int = 61
    n_dense_layers: int = 3           # first_k_dense_replace
    n_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 18432                 # the dense layers' width
    moe_d_ff: int = 2048              # an expert's width (routed and shared)
    n_routed_experts: int = 256       # the router's width, as published
    experts_held: int = 256           # routed experts this chip holds ...
    expert_offset: int = 0            # ... from this published index on
    n_shared_experts: int = 1
    experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling: float = 2.5
    norm_topk_prob: bool = True
    rope_theta: float = 10000.0
    rope_factor: float = 40.0         # YaRN
    rope_orig_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    norm_eps: float = 1e-6
    max_seq: int = 163840
    dtype: jnp.dtype = jnp.bfloat16

    scan_layers: ClassVar[bool] = False   # the cache's layers are a list (module docstring)

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_dim


CONFIGS = {
    # every mechanism at toy widths: a dense first layer, two groups of experts of
    # which one is held, a shared expert, rope and nope parts
    "tiny": DeepseekConfig(
        vocab_size=256, d_model=64, n_layers=3, n_dense_layers=1, n_heads=4,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
        d_ff=128, moe_d_ff=32, n_routed_experts=16, experts_held=8, expert_offset=0,
        experts_per_tok=4, n_group=4, topk_group=2, rope_orig_max=64, rope_factor=4.0,
        max_seq=256, dtype=jnp.float32),
}


def init_params(cfg: DeepseekConfig, key: jax.Array) -> dict:
    """Random weights (variance 1/fan_in, norm gains 1, router bias at a hundredth of
    the scores' spread) in the tree the forwards read: ``{"embed", "lm_head", "ln_f",
    "layers": [per-layer dict]}``; an expert layer holds ``"moe"``, a dense one
    ``w_gate/w_up/w_down``."""
    D, H, dt = cfg.d_model, cfg.n_heads, cfg.dtype

    def mat(k, *shape, fan_in=None):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(fan_in or shape[0])).astype(dt)

    def mlp(k, width, lead=()):
        ks = jax.random.split(k, 3)
        return {"w_gate": mat(ks[0], *lead, D, width, fan_in=D),
                "w_up": mat(ks[1], *lead, D, width, fan_in=D),
                "w_down": mat(ks[2], *lead, width, D, fan_in=width)}

    layers = []
    for l in range(cfg.n_layers):
        ks = jax.random.split(jax.random.fold_in(key, l), 12)
        layer = {
            "ln_attn": jnp.ones((D,), dt), "ln_mlp": jnp.ones((D,), dt),
            "w_qa": mat(ks[0], D, cfg.q_lora_rank), "q_norm": jnp.ones((cfg.q_lora_rank,), dt),
            "w_qb": mat(ks[1], cfg.q_lora_rank, H * (cfg.qk_nope_dim + cfg.qk_rope_dim)),
            "w_kva": mat(ks[2], D, cfg.latent_dim),
            "kv_norm": jnp.ones((cfg.kv_lora_rank,), dt),
            "w_kb": mat(ks[3], cfg.kv_lora_rank, H, cfg.qk_nope_dim),
            "w_vb": mat(ks[4], cfg.kv_lora_rank, H, cfg.v_head_dim),
            "wo": mat(ks[5], H * cfg.v_head_dim, D),
        }
        if l < cfg.n_dense_layers:
            layer.update(mlp(ks[6], cfg.d_ff))
        else:
            layer["moe"] = {
                "router": mat(ks[7], D, cfg.n_routed_experts).astype(jnp.float32),
                "router_bias": 0.01 * jax.random.normal(
                    ks[8], (cfg.n_routed_experts,), jnp.float32),
                "shared": mlp(ks[9], cfg.moe_d_ff * cfg.n_shared_experts),
                "experts": mlp(ks[10], cfg.moe_d_ff, lead=(cfg.experts_held,)),
            }
        layers.append(layer)
    ke, kh = jax.random.split(jax.random.fold_in(key, 1 << 20))
    return {"embed": mat(ke, cfg.vocab_size, D, fan_in=D),
            "lm_head": mat(kh, D, cfg.vocab_size), "ln_f": jnp.ones((D,), dt),
            "layers": layers}


# ------------------------------------------------------------------------------- rotary
def yarn_inv_freq(cfg: DeepseekConfig) -> jax.Array:
    """YaRN's per-pair rotary frequencies [qk_rope_dim / 2]: pairs that turn more than
    ``beta_fast`` times over the original context keep their frequency, pairs that turn
    fewer than ``beta_slow`` times are slowed by ``factor``, a linear ramp between."""
    dim, base = cfg.qk_rope_dim, cfg.rope_theta
    freq = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))

    def correction_dim(turns):
        return dim * math.log(cfg.rope_orig_max / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return freq / cfg.rope_factor * ramp + freq * (1.0 - ramp)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def sm_scale(cfg: DeepseekConfig) -> float:
    """``(nope + rope)^-½ · m²``, ``m = 0.1 · mscale_all_dim · ln(factor) + 1``."""
    m = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5 * m * m


def _rope(x: jax.Array, positions: jax.Array, cfg: DeepseekConfig) -> jax.Array:
    """Rotate ``x`` [..., T, (heads,) rope_dim] at ``positions`` [..., T]: pairs are the
    two halves of the last dim (``assumed``: the checkpoint's interleaved pairs are a
    fixed permutation of these)."""
    ang = positions[..., None].astype(jnp.float32) * yarn_inv_freq(cfg)
    scale = (_yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
             / _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    if x.ndim == positions.ndim + 2:                  # a heads axis before the last
        cos, sin = cos[..., None, :], sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


# ---------------------------------------------------------------------------- the block
def _mla_project(h, layer, positions, cfg: DeepseekConfig):
    """h [B,T,D] at ``positions`` [B,T] → (q_nope [B,T,H,nope], q_rope [B,T,H,rope],
    latent [B,T,rank+rope] = the cache row ``c_kv | k_rope``)."""
    B, T, _ = h.shape
    dt, H = cfg.dtype, cfg.n_heads
    c_q = _rms_norm(h @ layer["w_qa"].astype(dt), layer["q_norm"], cfg.norm_eps)
    q = (c_q @ layer["w_qb"].astype(dt)).reshape(B, T, H, cfg.qk_nope_dim + cfg.qk_rope_dim)
    q_nope, q_rope = q[..., :cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
    kva = h @ layer["w_kva"].astype(dt)
    c_kv = _rms_norm(kva[..., :cfg.kv_lora_rank], layer["kv_norm"], cfg.norm_eps)
    k_rope = _rope(kva[..., cfg.kv_lora_rank:], positions, cfg)
    return q_nope, _rope(q_rope, positions, cfg), jnp.concatenate([c_kv, k_rope], -1)


_KEY_BLOCK = 1024     # cached keys the prefill form up-projects and scores an iteration


def _attend_latent_rows(q_nope, q_rope, latent, q_positions, valid, n_keys, layer,
                        cfg: DeepseekConfig):
    """Prefill form: queries [B,T,H,·] at ``q_positions`` [B,T] against the dense latent
    rows ``latent`` [B,C,W], of which the first ``n_keys`` (traced) can hold a key some
    query sees. Walks the live keys a block at a time (a loop with a RUNTIME trip count:
    one program for every fill of the row): up-project the block to per-head keys and
    values, score, one online-softmax update. fp32 scores and accumulation.
    → o [B,T,H,v_head_dim]."""
    B, T, H, _ = q_nope.shape
    C = latent.shape[1]
    dt, R, r = cfg.dtype, cfg.kv_lora_rank, cfg.qk_rope_dim
    kb = _KEY_BLOCK if C % _KEY_BLOCK == 0 else C
    w_kb, w_vb = layer["w_kb"].astype(dt), layer["w_vb"].astype(dt)
    scale = sm_scale(cfg)

    def body(i, carry):
        m, l, acc = carry
        lat = jax.lax.dynamic_slice_in_dim(latent, i * kb, kb, axis=1)      # [B,kb,W]
        ok = jax.lax.dynamic_slice_in_dim(valid, i * kb, kb, axis=1)        # [B,kb]
        ckv, kr = lat[..., :R], lat[..., R:R + r]
        k_nope = jnp.einsum("bkc,chd->bkhd", ckv, w_kb)
        v = jnp.einsum("bkc,chd->bkhd", ckv, w_vb)
        s = (jnp.einsum("bthd,bkhd->bhtk", q_nope, k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bthr,bkr->bhtk", q_rope, kr,
                          preferred_element_type=jnp.float32)) * scale
        key_pos = i * kb + jnp.arange(kb)
        seen = ok[:, None, :] & (key_pos[None, None, :] <= q_positions[:, :, None])
        s = jnp.where(seen[:, None], s, -1e30)
        m_next = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_next[..., None])      # a masked score's exp() is an exact 0
        alpha = jnp.exp(m - m_next)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhtk,bkhd->bhtd", p.astype(dt), v, preferred_element_type=jnp.float32)
        return m_next, l, acc

    init = (jnp.full((B, H, T), -1e29, jnp.float32), jnp.zeros((B, H, T), jnp.float32),
            jnp.zeros((B, H, T, cfg.v_head_dim), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, (n_keys + kb - 1) // kb, body, init)
    o = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return o.transpose(0, 2, 1, 3).astype(dt)


def _attend_latent_pages(q_nope, q_rope, pool, tables, positions, valid, page_size,
                         layer, cfg: DeepseekConfig):
    """Decode form: one query a lane, q_nope/q_rope [B,H,·], against the latent pool
    through the block tables — the key up-projection absorbed into the query, the
    value up-projection applied to the kernel's output. → o [B,H,v_head_dim]."""
    from ..ops.mla_attention import mla_paged_attention, mla_paged_attention_reference

    dt = cfg.dtype
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope, layer["w_kb"].astype(dt))
    attend = (mla_paged_attention if paged_read_impl() == "kernel"
              else mla_paged_attention_reference)
    o_lat = attend(q_lat, q_rope, pool, tables, positions, valid,
                   page_size=page_size, sm_scale=sm_scale(cfg))
    return jnp.einsum("bhc,chd->bhd", o_lat, layer["w_vb"].astype(dt))


def _mlp(x, layer, cfg: DeepseekConfig):
    """The layer's feed-forward on x [B,T,D] → (y, counts int32[3] — zeros for a dense
    layer; ``ops.moe.moe_mlp_grouped``'s for an expert layer)."""
    from ..ops.moe import _swiglu, moe_mlp_grouped

    h = _rms_norm(x, layer["ln_mlp"], cfg.norm_eps)
    if "moe" not in layer:
        with jax.named_scope("mlp"):
            return _swiglu(h, layer, cfg.dtype), jnp.zeros((3,), jnp.int32)
    B, T, D = h.shape
    with jax.named_scope("moe"):
        y, counts = moe_mlp_grouped(
            h.reshape(B * T, D), layer["moe"], top_k=cfg.experts_per_tok,
            n_group=cfg.n_group, topk_group=cfg.topk_group, scale=cfg.routed_scaling,
            norm_topk=cfg.norm_topk_prob, expert_offset=cfg.expert_offset,
            compute_dtype=cfg.dtype)
    return y.reshape(B, T, D), counts


def _head(x, params, cfg: DeepseekConfig):
    x = _rms_norm(x, params["ln_f"], cfg.norm_eps)
    with jax.named_scope("head"):
        return (x @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)


# ------------------------------------------------------------- dense latent row: prefill
def init_cache(cfg: DeepseekConfig, batch_size: int, max_len: int, dtype=None) -> dict:
    """An empty dense latent cache: ``{"layers": [{"latent": [B, C, W]}, ...], "valid":
    [B, C] bool, "index": int32}`` — the row chunked prefill fills and the engine then
    scatters into pool pages."""
    dtype = dtype or cfg.dtype
    return {"layers": [latent_planes(batch_size, max_len, cfg.latent_dim, dtype)
                       for _ in range(cfg.n_layers)],
            "valid": jnp.zeros((batch_size, max_len), jnp.bool_),
            "index": jnp.zeros((), jnp.int32)}


def _forward_rows(params, tokens, cache, cfg: DeepseekConfig, token_mask, last_only):
    B, T = tokens.shape
    index = cache["index"]
    positions = index + jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    if token_mask is None:
        token_mask = jnp.ones((B, T), jnp.bool_)
    valid = jax.lax.dynamic_update_slice(cache["valid"], token_mask, (0, index))
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
    new_layers = []
    for layer, kv in zip(params["layers"], cache["layers"]):
        with jax.named_scope("mla"):
            h = _rms_norm(x, layer["ln_attn"], cfg.norm_eps)
            q_nope, q_rope, row = _mla_project(h, layer, positions, cfg)
            with jax.named_scope("kv_write"):
                W = kv["latent"].shape[-1]
                row = jnp.pad(row.astype(kv["latent"].dtype),
                              ((0, 0), (0, 0), (0, W - row.shape[-1])))
                latent = jax.lax.dynamic_update_slice(kv["latent"], row, (0, index, 0))
            o = _attend_latent_rows(q_nope, q_rope, latent, positions, valid, index + T,
                                    layer, cfg)
            x = x + o.reshape(B, T, -1) @ layer["wo"].astype(cfg.dtype)
        y, _ = _mlp(x, layer, cfg)
        x = x + y
        new_layers.append({"latent": latent})
    if last_only:
        x = x[:, -1:, :]
    return _head(x, params, cfg), {"layers": new_layers, "valid": valid, "index": index + T}


def forward_cached(params: dict, tokens: jax.Array, cache: dict, cfg: DeepseekConfig,
                   token_mask: Optional[jax.Array] = None, last_only: bool = True):
    """Write ``tokens`` [B,T] into the dense latent cache at its index and return (the
    LAST position's logits [B,1,V] fp32, the updated cache) — the engine's prefill
    chunk: the prompt left-padded with ``token_mask`` False on the pad, a chunk at a
    time against what the row already holds. ``last_only`` is always on here (per-
    position logits are ``forward``'s)."""
    if not last_only:
        raise NotImplementedError(
            "deepseek.forward_cached returns the last position's logits only")
    return _forward_rows(params, tokens, cache, cfg, token_mask, True)


def forward(params: dict, tokens: jax.Array, cfg: DeepseekConfig) -> jax.Array:
    """Logits [B,S,V] fp32 of a whole sequence: the prefill form over a fresh row."""
    cache = init_cache(cfg, tokens.shape[0], tokens.shape[1])
    return _forward_rows(params, tokens, cache, cfg, None, False)[0]


# ------------------------------------------------------------------ latent pages: decode
def init_paged_cache(cfg: DeepseekConfig, batch_size: int, max_len: int, num_pages: int,
                     page_size: int, dtype=None) -> dict:
    """An empty paged latent cache: ``{"layers": [{"latent": [P, page_size, W]}, ...],
    "valid": [B, max_len] bool}``; which lane owns which page is the host-side
    ``paged_kv.BlockManager``'s, as for the K/V layout."""
    dtype = dtype or cfg.dtype
    return {"layers": [paged_latent_planes(num_pages, page_size, cfg.latent_dim, dtype)
                       for _ in range(cfg.n_layers)],
            "valid": jnp.zeros((batch_size, max_len), jnp.bool_)}


def paged_walk_shape(cfg: DeepseekConfig, page_size: int, itemsize: int,
                     max_pages: int) -> tuple:
    """(table entries the decode kernel fetches an iteration, its window — none) for
    the engine's ``pages_live`` / ``pages_walked`` counters."""
    from ..ops.mla_attention import mla_block_pages

    return mla_block_pages(page_size, latent_width(cfg.latent_dim), itemsize, max_pages), 0


def _forward_slots(params, tokens, cache, tables, positions, cfg: DeepseekConfig,
                   page_size: int):
    """One decode step: lane b's token written and attended at ``positions[b]`` →
    (logits [B,V] fp32, cache, MoE counts int32[3] summed over the expert layers)."""
    B = tokens.shape[0]
    max_len = cache["valid"].shape[1]
    valid = cache["valid"].at[jnp.arange(B), positions].set(True)
    num_pages = cache["layers"][0]["latent"].shape[0]
    pages, offs = paged_write_coords(tables, positions[:, None], page_size, max_len,
                                     num_pages)
    with jax.named_scope("embed"):
        x = params["embed"][tokens[:, None]].astype(cfg.dtype)              # [B,1,D]
    counts = jnp.zeros((3,), jnp.int32)
    new_layers = []
    for layer, kv in zip(params["layers"], cache["layers"]):
        with jax.named_scope("mla"):
            h = _rms_norm(x, layer["ln_attn"], cfg.norm_eps)
            q_nope, q_rope, row = _mla_project(h, layer, positions[:, None], cfg)
            with jax.named_scope("kv_write"):
                kv = write_latent_paged(kv, row, pages, offs)
            o = _attend_latent_pages(q_nope[:, 0], q_rope[:, 0], kv["latent"], tables,
                                     positions, valid, page_size, layer, cfg)
            x = x + (o.reshape(B, -1) @ layer["wo"].astype(cfg.dtype))[:, None]
        y, c = _mlp(x, layer, cfg)
        x = x + y
        counts = counts + c
        new_layers.append(kv)
    return _head(x, params, cfg)[:, 0], {"layers": new_layers, "valid": valid}, counts


def forward_slots_paged(params: dict, tokens: jax.Array, cache: dict, tables: jax.Array,
                        positions: jax.Array, cfg: DeepseekConfig, page_size: int):
    """Per-lane decode step over the paged latent cache: ``tokens`` [B,1] written at each
    lane's own ``positions[b]`` through its block-table row (sentinel entries and
    positions at ``max_len`` DROP) → (logits [B,1,V] fp32, new cache)."""
    if tokens.shape[1] != 1:
        raise NotImplementedError(
            "deepseek.forward_slots_paged decodes one token a lane (the latent kernel "
            "takes one query); a multi-token verify is not implemented")
    logits, cache, _ = _forward_slots(params, tokens[:, 0], cache, tables, positions, cfg,
                                      page_size)
    return logits[:, None], cache


def forward_slots_multi(params: dict, cache: dict, tokens: jax.Array,
                        positions: jax.Array, active: jax.Array, budgets: jax.Array,
                        eos_ids: jax.Array, select_token, xs, n_steps: int,
                        cfg: DeepseekConfig, tables: Optional[jax.Array] = None,
                        page_size: int = 0):
    """``n_steps`` paged decode steps as one scan (``common.multi_step_decode``: the
    freeze/emission contract is the shared one). The latent pool rides in the scan's
    CARRY and is written in place. Returns ``(cache, tok_buf [n_steps, B], counts [B],
    moe_counts int32[3])`` — the last is :data:`DECODE_COUNTERS`, summed over the steps."""
    if tables is None:
        raise NotImplementedError("deepseek decodes over the paged latent cache only")
    max_len = cache["valid"].shape[1]

    def forward_one(c, tok, write_pos):
        logits, new, counts = _forward_slots(
            params, tok, {"layers": c["layers"], "valid": c["valid"]}, tables, write_pos,
            cfg, page_size)
        return logits, {**new, "moe_counts": c["moe_counts"] + counts}

    carry = {**cache, "moe_counts": jnp.zeros((3,), jnp.int32)}
    carry, tok_buf, counts = multi_step_decode(
        forward_one, carry, tokens, positions, active, budgets, eos_ids, select_token,
        xs, n_steps, max_len)
    moe_counts = carry.pop("moe_counts")
    return carry, tok_buf, counts, moe_counts
