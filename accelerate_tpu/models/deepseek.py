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

**Layer kinds are data.** Every function below reads a layer's attention from
``cfg.attn_spec(l)`` — head count, ranks, nope/rope/v widths, RoPE base, and four switches:
``window`` (a sliding layer: query ``t`` sees keys ``t - window < s <= t`` and its cache
is a ring of pages a lane, ``common.ring_tables``), ``index_topk`` (a learned sparse
selection: an indexer scores every live key, the ``index_topk`` best are attended,
``ops/sparse_attention.py``; the layer also caches one index key a token), ``attn_gate``
(a head-wise sigmoid gate on the attention's output) and ``q_rescale`` / ``kv_rescale``
(the latents' rescale). :class:`DeepseekConfig` is the instance with every layer full,
no indexer, no gate and YaRN — it is its own spec; ``models/dots3.py`` holds a config
whose layers differ (:class:`AttnSpec` a kind).

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
                     paged_read_impl, paged_write_coords, ring_pages, ring_tables,
                     write_latent_paged)
from .llama import _rms_norm

#: What ``forward_slots_multi`` returns beside the token buffers (one int32 array, in
#: this order, summed over the dispatch's steps and expert layers); the engine hands
#: them to its ``engine.decode.drain`` span under these names.
DECODE_COUNTERS = ("moe_pairs", "moe_tokens", "moe_max_on_one_expert")
#: What a config with ``counts_attention`` adds to them (``models/dots3.py``): the live
#: keys the indexer scored and the latent rows the attention then read, summed over
#: lanes, sparse layers and steps; and the rows the window layers' attention read.
ATTENTION_COUNTERS = ("dsa_keys_scored", "dsa_keys_attended", "window_keys_attended")


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
    # every layer is one kind, and the config is its spec (module docstring)
    window: ClassVar[int] = 0
    index_topk: ClassVar[int] = 0
    attn_gate: ClassVar[bool] = False
    q_rescale: ClassVar[float] = 1.0
    kv_rescale: ClassVar[float] = 1.0
    counts_attention: ClassVar[bool] = False

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_dim

    def attn_spec(self, layer: int) -> "DeepseekConfig":
        return self


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    """One KIND of latent-attention layer, for a config whose layers differ: what
    :class:`DeepseekConfig` holds for all of its layers at once (same names), with the
    switches of the module docstring. No YaRN (``rope_factor`` 1)."""
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    rope_theta: float
    norm_eps: float
    dtype: jnp.dtype
    window: int = 0               # > 0: a sliding layer over a ring of pages
    index_heads: int = 0          # the indexer (index_topk > 0)
    index_dim: int = 0
    index_topk: int = 0
    attn_gate: bool = False
    q_rescale: float = 1.0
    kv_rescale: float = 1.0

    rope_factor: ClassVar[float] = 1.0
    rope_orig_max: ClassVar[int] = 4096
    rope_beta_fast: ClassVar[float] = 32.0
    rope_beta_slow: ClassVar[float] = 1.0
    rope_mscale: ClassVar[float] = 1.0
    rope_mscale_all_dim: ClassVar[float] = 1.0

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_dim


def layer_specs(cfg) -> list:
    return [cfg.attn_spec(l) for l in range(cfg.n_layers)]


def _pool_spec(cfg):
    """The spec of the layers whose cache lies in pool pages under the block tables."""
    return next(s for s in layer_specs(cfg) if not s.window)


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


def init_params(cfg, key: jax.Array) -> dict:
    """Random weights (variance 1/fan_in, norm gains 1, router bias at a hundredth of
    the scores' spread) in the tree the forwards read: ``{"embed", "lm_head", "ln_f",
    "layers": [per-layer dict]}``; an expert layer holds ``"moe"``, a dense one
    ``w_gate/w_up/w_down``; a gated layer ``w_g``, an indexed one ``idx_wq`` / ``idx_wk``
    / ``idx_ww`` and its key norm. A layer's attention shapes follow ITS spec."""
    D, dt = cfg.d_model, cfg.dtype

    def mat(k, *shape, fan_in=None):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(fan_in or shape[0])).astype(dt)

    def mlp(k, width, lead=()):
        ks = jax.random.split(k, 3)
        return {"w_gate": mat(ks[0], *lead, D, width, fan_in=D),
                "w_up": mat(ks[1], *lead, D, width, fan_in=D),
                "w_down": mat(ks[2], *lead, width, D, fan_in=width)}

    layers = []
    for l, sp in enumerate(layer_specs(cfg)):
        ks = jax.random.split(jax.random.fold_in(key, l), 12)
        kx = jax.random.split(jax.random.fold_in(key, (1 << 10) + l), 4)
        H = sp.n_heads
        layer = {
            "ln_attn": jnp.ones((D,), dt), "ln_mlp": jnp.ones((D,), dt),
            "w_qa": mat(ks[0], D, sp.q_lora_rank), "q_norm": jnp.ones((sp.q_lora_rank,), dt),
            "w_qb": mat(ks[1], sp.q_lora_rank, H * (sp.qk_nope_dim + sp.qk_rope_dim)),
            "w_kva": mat(ks[2], D, sp.latent_dim),
            "kv_norm": jnp.ones((sp.kv_lora_rank,), dt),
            "w_kb": mat(ks[3], sp.kv_lora_rank, H, sp.qk_nope_dim),
            "w_vb": mat(ks[4], sp.kv_lora_rank, H, sp.v_head_dim),
            "wo": mat(ks[5], H * sp.v_head_dim, D),
        }
        if sp.attn_gate:
            layer["w_g"] = mat(kx[0], D, H)
        if sp.index_topk:
            layer.update(
                idx_wq=mat(kx[1], sp.q_lora_rank, sp.index_heads * sp.index_dim),
                idx_wk=mat(kx[2], D, sp.index_dim), idx_ww=mat(kx[3], D, sp.index_heads),
                idx_k_gain=jnp.ones((sp.index_dim,), dt),
                idx_k_bias=jnp.zeros((sp.index_dim,), dt))
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
def yarn_inv_freq(cfg) -> jax.Array:
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


def sm_scale(cfg) -> float:
    """``(nope + rope)^-½ · m²``, ``m = 0.1 · mscale_all_dim · ln(factor) + 1``."""
    m = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5 * m * m


def _rope(x: jax.Array, positions: jax.Array, cfg) -> jax.Array:
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
def _mla_project(h, layer, positions, spec):
    """h [B,T,D] at ``positions`` [B,T] → (q_nope [B,T,H,nope], q_rope [B,T,H,rope],
    latent [B,T,rank+rope] = the cache row ``c_kv | k_rope``, c_q [B,T,q_lora] — the
    query latent the indexer projects from)."""
    B, T, _ = h.shape
    dt, H = spec.dtype, spec.n_heads
    c_q = _rms_norm(h @ layer["w_qa"].astype(dt), layer["q_norm"], spec.norm_eps)
    kva = h @ layer["w_kva"].astype(dt)
    c_kv = _rms_norm(kva[..., :spec.kv_lora_rank], layer["kv_norm"], spec.norm_eps)
    if spec.q_rescale != 1.0:
        c_q = (c_q * spec.q_rescale).astype(dt)
    if spec.kv_rescale != 1.0:
        c_kv = (c_kv * spec.kv_rescale).astype(dt)
    q = (c_q @ layer["w_qb"].astype(dt)).reshape(B, T, H, spec.qk_nope_dim + spec.qk_rope_dim)
    q_nope, q_rope = q[..., :spec.qk_nope_dim], q[..., spec.qk_nope_dim:]
    k_rope = _rope(kva[..., spec.kv_lora_rank:], positions, spec)
    return (q_nope, _rope(q_rope, positions, spec), jnp.concatenate([c_kv, k_rope], -1),
            c_q)


def _index_project(h, c_q, layer, positions, spec):
    """The indexer's side of a sparse layer → (q_idx [B,T,Hi,Di] and k_idx [B,T,Di],
    RoPE on their first ``qk_rope_dim`` dims; w [B,T,Hi] float32, the heads' weights
    with ``Hi^-½ · Di^-½`` folded in). ``k_idx = LayerNorm(h W_k)`` is the row the index
    cache holds."""
    B, T, _ = h.shape
    dt, Hi, Di, r = spec.dtype, spec.index_heads, spec.index_dim, spec.qk_rope_dim
    q = (c_q @ layer["idx_wq"].astype(dt)).reshape(B, T, Hi, Di)
    q = jnp.concatenate([_rope(q[..., :r], positions, spec), q[..., r:]], -1)
    k = (h @ layer["idx_wk"].astype(dt)).astype(jnp.float32)
    k = k - k.mean(-1, keepdims=True)
    k = k * jax.lax.rsqrt((k * k).mean(-1, keepdims=True) + _INDEX_NORM_EPS)
    k = (k * layer["idx_k_gain"].astype(jnp.float32)
         + layer["idx_k_bias"].astype(jnp.float32)).astype(dt)
    k = jnp.concatenate([_rope(k[..., :r], positions, spec), k[..., r:]], -1)
    w = (h @ layer["idx_ww"].astype(dt)).astype(jnp.float32) * (Hi ** -0.5 * Di ** -0.5)
    return q, k, w


def _gate(o, h, layer, spec):
    """The head-wise output gate: ``o[..., head, :] · sigmoid(h W_g)[..., head]``."""
    if not spec.attn_gate:
        return o
    g = jax.nn.sigmoid((h @ layer["w_g"].astype(spec.dtype)).astype(jnp.float32))
    return o * g.astype(o.dtype)[..., None]


_INDEX_NORM_EPS = 1e-6    # the indexer's LayerNorm (DeepSeek-V3.2's inference code)
_KEY_BLOCK = 1024     # cached keys the prefill form up-projects and scores an iteration
_QUERY_BLOCK = 128    # queries whose selected rows the sparse prefill form gathers at once


def _attend_latent_rows(q_nope, q_rope, latent, q_positions, valid, n_keys, layer, spec):
    """Prefill form: queries [B,T,H,·] at ``q_positions`` [B,T] against the dense latent
    rows ``latent`` [B,C,W], of which the first ``n_keys`` (traced) can hold a key some
    query sees. Walks the live keys a block at a time (a loop with a RUNTIME trip count:
    one program for every fill of the row): up-project the block to per-head keys and
    values, score, one online-softmax update. fp32 scores and accumulation. A sliding
    layer (``spec.window``) starts the walk at the block of the first query's oldest key
    and masks keys ``window`` or more behind a query. → o [B,T,H,v_head_dim]."""
    B, T, H, _ = q_nope.shape
    C = latent.shape[1]
    dt, R, r = spec.dtype, spec.kv_lora_rank, spec.qk_rope_dim
    kb = _KEY_BLOCK if C % _KEY_BLOCK == 0 else C
    w_kb, w_vb = layer["w_kb"].astype(dt), layer["w_vb"].astype(dt)
    scale = sm_scale(spec)

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
        if spec.window:
            seen &= key_pos[None, None, :] > q_positions[:, :, None] - spec.window
        s = jnp.where(seen[:, None], s, -1e30)
        m_next = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_next[..., None])      # a masked score's exp() is an exact 0
        alpha = jnp.exp(m - m_next)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhtk,bkhd->bhtd", p.astype(dt), v, preferred_element_type=jnp.float32)
        return m_next, l, acc

    init = (jnp.full((B, H, T), -1e29, jnp.float32), jnp.zeros((B, H, T), jnp.float32),
            jnp.zeros((B, H, T, spec.v_head_dim), jnp.float32))
    first = (jnp.maximum(q_positions.min() - (spec.window - 1), 0) // kb
             if spec.window else 0)
    _, l, acc = jax.lax.fori_loop(first, (n_keys + kb - 1) // kb, body, init)
    o = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return o.transpose(0, 2, 1, 3).astype(dt)


def _select_rows(q_idx, w_idx, index_k, q_positions, valid, n_keys, spec):
    """Prefill's selection: the indexer's score ``Σ_head w · ReLU(q_head · k)`` of every
    live key ``<=`` each query (a block of keys at a time, RUNTIME trip count), then an
    exact top ``index_topk`` a query (ties to the earlier key: ``lax.top_k``). q_idx
    [B,T,Hi,Di], w_idx [B,T,Hi], index_k [B,C,Di] → (sel [B,T,K] int32 row slots, ok
    [B,T,K]: the slot is a selected key, not filler behind a query with fewer)."""
    from ..ops.sparse_attention import index_scores

    B, T = q_positions.shape
    C = index_k.shape[1]
    kb = _KEY_BLOCK if C % _KEY_BLOCK == 0 else C

    def body(i, scores):
        k = jax.lax.dynamic_slice_in_dim(index_k, i * kb, kb, axis=1)      # [B,kb,Di]
        ok = jax.lax.dynamic_slice_in_dim(valid, i * kb, kb, axis=1)
        s = index_scores(q_idx, w_idx, k)                                  # [B,T,kb]
        key_pos = i * kb + jnp.arange(kb)
        seen = ok[:, None, :] & (key_pos[None, None, :] <= q_positions[:, :, None])
        return jax.lax.dynamic_update_slice_in_dim(
            scores, jnp.where(seen, s, -jnp.inf), i * kb, axis=2)

    scores = jax.lax.fori_loop(0, (n_keys + kb - 1) // kb, body,
                               jnp.full((B, T, C), -jnp.inf, jnp.float32))
    vals, sel = _top_k_live(scores, n_keys, min(spec.index_topk, C))
    return sel.astype(jnp.int32), vals > -jnp.inf


def _top_k_live(scores, n_keys, k: int):
    """``lax.top_k(scores, k)`` over the last axis, of which only the first ``n_keys``
    (traced) columns can be finite: the sort runs over the narrowest of C/8, C/4, C/2, C
    columns that holds them (``lax.switch``: still one program for every fill). On a
    v5e the sort of [512, 32768] takes 17.9 ms and that of [512, 8192] 1.65 ms (PERF.md,
    PR 32), and most of a prompt's chunks are early ones."""
    C = scores.shape[-1]
    widths = [w for w in (C // 8, C // 4, C // 2) if w >= max(k, 1024) and w % 128 == 0] + [C]
    if len(widths) == 1:
        return jax.lax.top_k(scores, k)
    branch = sum((n_keys > w).astype(jnp.int32) for w in widths[:-1])
    return jax.lax.switch(
        branch, [lambda s, w=w: jax.lax.top_k(s[..., :w], k) for w in widths], scores)


def _attend_selected_rows(q_nope, q_rope, latent, sel, ok, layer, spec):
    """Prefill form of a sparse layer: each query against ITS selected latent rows
    ``latent[b, sel[b,t]]`` in the absorbed form (the rows are per query, so there is
    nothing to up-project once for all), ``_QUERY_BLOCK`` queries' rows gathered at a
    time. The cost does not grow with the row's fill. → o [B,T,H,v_head_dim]."""
    B, T, H, _ = q_nope.shape
    dt, R, r = spec.dtype, spec.kv_lora_rank, spec.qk_rope_dim
    scale = sm_scale(spec)
    qb = _QUERY_BLOCK if T % _QUERY_BLOCK == 0 else T
    q_lat = jnp.einsum("bthd,chd->bthc", q_nope, layer["w_kb"].astype(dt))

    def per_row(q_lat, q_rope, latent, sel, ok):          # one batch row

        def block(xs):
            ql, qr, idx, live = xs                        # [qb,H,R] [qb,H,r] [qb,K] [qb,K]
            rows = latent[idx]                            # [qb,K,W]
            ckv, kr = rows[..., :R], rows[..., R:R + r]
            s = (jnp.einsum("thc,tkc->thk", ql, ckv, preferred_element_type=jnp.float32)
                 + jnp.einsum("thr,tkr->thk", qr, kr, preferred_element_type=jnp.float32))
            s = jnp.where(live[:, None, :], s * scale, -1e30)
            p = jnp.where(live[:, None, :], jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
            l = p.sum(-1, keepdims=True)
            p = (p / jnp.where(l == 0.0, 1.0, l)).astype(dt)
            return jnp.einsum("thk,tkc->thc", p, ckv,
                              preferred_element_type=jnp.float32).astype(dt)

        split = lambda a: a.reshape(T // qb, qb, *a.shape[1:])          # noqa: E731
        o = jax.lax.map(block, (split(q_lat), split(q_rope), split(sel), split(ok)))
        return o.reshape(T, H, R)

    o_lat = jax.vmap(per_row)(q_lat, q_rope, latent, sel, ok)
    return jnp.einsum("bthc,chd->bthd", o_lat, layer["w_vb"].astype(dt))


def _attend_latent_pages(q_nope, q_rope, pool, tables, positions, valid, page_size,
                         layer, spec):
    """Decode form: one query a lane, q_nope/q_rope [B,H,·], against the latent pool
    through the block tables — the key up-projection absorbed into the query, the
    value up-projection applied to the kernel's output. → o [B,H,v_head_dim]."""
    from ..ops.mla_attention import mla_paged_attention, mla_paged_attention_reference

    dt = spec.dtype
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope, layer["w_kb"].astype(dt))
    attend = (mla_paged_attention if paged_read_impl() == "kernel"
              else mla_paged_attention_reference)
    o_lat = attend(q_lat, q_rope, pool, tables, positions, valid,
                   page_size=page_size, sm_scale=sm_scale(spec))
    return jnp.einsum("bhc,chd->bhd", o_lat, layer["w_vb"].astype(dt))


def _attend_selected_pages(q_nope, q_rope, q_idx, w_idx, kv, tables, positions, valid,
                           page_size, layer, spec):
    """Decode form of a sparse layer, one query a lane: the indexer's score of every
    live key through the block tables (``ops.sparse_attention.dsa_index_scores``), an
    exact top ``index_topk`` a lane, a gather of the chosen latent rows into a pool of
    their own — ``index_topk / page_size`` pages a lane under an identity table — and
    the decode form over that. A lane with no more live keys than ``index_topk`` attends
    to all of them."""
    from ..ops.sparse_attention import dsa_index_scores, dsa_index_scores_reference

    pool = kv["latent"]
    P, B = pool.shape[0], positions.shape[0]
    score = (dsa_index_scores if paged_read_impl() == "kernel"
             else dsa_index_scores_reference)
    scores = score(q_idx, w_idx, kv["index_k"], tables, positions, valid,
                   page_size=page_size)                                      # [B, C]
    K = min(spec.index_topk, scores.shape[1])
    with jax.named_scope("dsa_topk"):
        vals, sel = jax.lax.top_k(scores, K)
    with jax.named_scope("dsa_gather"):
        pages = jnp.take_along_axis(tables, sel // page_size, axis=1)
        rows = pool[jnp.minimum(pages, P - 1), sel % page_size]               # [B,K,W]
    chosen = rows.reshape(B * (K // page_size), page_size, rows.shape[-1])
    identity = jnp.arange(B * (K // page_size), dtype=jnp.int32).reshape(B, -1)
    return _attend_latent_pages(
        q_nope, q_rope, chosen, identity, jnp.full((B,), K - 1, jnp.int32),
        vals > -jnp.inf, page_size, layer, spec)


def _mlp(x, layer, cfg):
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


def _head(x, params, cfg):
    x = _rms_norm(x, params["ln_f"], cfg.norm_eps)
    with jax.named_scope("head"):
        return (x @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)


# ------------------------------------------------------------- dense latent row: prefill
def _row_planes(spec, batch: int, max_len: int, dtype) -> dict:
    """One layer's empty dense row: ``latent`` (+ ``index_k`` for a sparse layer); a
    sliding layer's row is named ``ring`` — the engine lands only its last window's
    rows, into the lane's ring of pages."""
    planes = latent_planes(batch, max_len, spec.latent_dim, dtype)
    if spec.window:
        return {"ring": planes["latent"]}
    if spec.index_topk:
        planes["index_k"] = jnp.zeros((batch, max_len, spec.index_dim), dtype)
    return planes


def init_cache(cfg, batch_size: int, max_len: int, dtype=None) -> dict:
    """An empty dense latent cache: ``{"layers": [{"latent": [B, C, W]}, ...], "valid":
    [B, C] bool, "index": int32}`` — the row chunked prefill fills and the engine then
    scatters into pool pages (``_row_planes`` has a layer's other leaves)."""
    dtype = dtype or cfg.dtype
    return {"layers": [_row_planes(sp, batch_size, max_len, dtype)
                       for sp in layer_specs(cfg)],
            "valid": jnp.zeros((batch_size, max_len), jnp.bool_),
            "index": jnp.zeros((), jnp.int32)}


def _write_row(plane, row, index):
    row = jnp.pad(row.astype(plane.dtype),
                  ((0, 0), (0, 0), (0, plane.shape[-1] - row.shape[-1])))
    return jax.lax.dynamic_update_slice(plane, row, (0, index, 0))


def _forward_rows(params, tokens, cache, cfg, token_mask, last_only):
    B, T = tokens.shape
    index = cache["index"]
    positions = index + jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    if token_mask is None:
        token_mask = jnp.ones((B, T), jnp.bool_)
    valid = jax.lax.dynamic_update_slice(cache["valid"], token_mask, (0, index))
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
    new_layers = []
    for l, (layer, kv) in enumerate(zip(params["layers"], cache["layers"])):
        spec = cfg.attn_spec(l)
        with jax.named_scope("mla"):
            h = _rms_norm(x, layer["ln_attn"], cfg.norm_eps)
            q_nope, q_rope, row, c_q = _mla_project(h, layer, positions, spec)
            name = "ring" if spec.window else "latent"
            with jax.named_scope("kv_write"):
                new = {name: _write_row(kv[name], row, index)}
            if spec.index_topk:
                with jax.named_scope("dsa_select"):
                    q_idx, k_idx, w_idx = _index_project(h, c_q, layer, positions, spec)
                    new["index_k"] = _write_row(kv["index_k"], k_idx, index)
                    sel, ok = _select_rows(q_idx, w_idx, new["index_k"], positions, valid,
                                           index + T, spec)
                o = _attend_selected_rows(q_nope, q_rope, new["latent"], sel, ok, layer, spec)
            else:
                o = _attend_latent_rows(q_nope, q_rope, new[name], positions, valid,
                                        index + T, layer, spec)
            o = _gate(o, h, layer, spec)
            x = x + o.reshape(B, T, -1) @ layer["wo"].astype(cfg.dtype)
        y, _ = _mlp(x, layer, cfg)
        x = x + y
        new_layers.append(new)
    if last_only:
        x = x[:, -1:, :]
    return _head(x, params, cfg), {"layers": new_layers, "valid": valid, "index": index + T}


def forward_cached(params: dict, tokens: jax.Array, cache: dict, cfg,
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


def forward(params: dict, tokens: jax.Array, cfg) -> jax.Array:
    """Logits [B,S,V] fp32 of a whole sequence: the prefill form over a fresh row."""
    cache = init_cache(cfg, tokens.shape[0], tokens.shape[1])
    return _forward_rows(params, tokens, cache, cfg, None, False)[0]


# ------------------------------------------------------------------ latent pages: decode
def init_paged_cache(cfg, batch_size: int, max_len: int, num_pages: int,
                     page_size: int, dtype=None) -> dict:
    """An empty paged latent cache: ``{"layers": [...], "valid": [B, max_len] bool}``.
    A full layer holds ``{"latent": [P, page_size, W]}`` (a sparse one also ``"index_k":
    [P, page_size, index_dim]``, the same pages) — which lane owns which page is the
    host-side ``paged_kv.BlockManager``'s, as for the K/V layout. A sliding layer holds
    ``{"ring": [B · R, page_size, W]}``: ``R = common.ring_pages(window, page_size)``
    pages a lane whatever ``max_len``, reached through a COMPUTED table
    (``common.ring_tables``), so no allocator knows of them."""
    dtype = dtype or cfg.dtype

    def planes(sp):
        if sp.window:
            ring = batch_size * ring_pages(sp.window, page_size)
            return {"ring": paged_latent_planes(ring, page_size, sp.latent_dim,
                                                dtype)["latent"]}
        out = paged_latent_planes(num_pages, page_size, sp.latent_dim, dtype)
        if sp.index_topk:
            out["index_k"] = jnp.zeros((num_pages, page_size, sp.index_dim), dtype)
        return out

    return {"layers": [planes(sp) for sp in layer_specs(cfg)],
            "valid": jnp.zeros((batch_size, max_len), jnp.bool_)}


def paged_walk_shape(cfg, page_size: int, itemsize: int, max_pages: int) -> tuple:
    """(table entries the decode kernel fetches an iteration, its window — none) for
    the engine's ``pages_live`` / ``pages_walked`` counters: the walk of a full layer
    over the block tables (a sparse layer's indexer walks the same range)."""
    from ..ops.mla_attention import mla_block_pages

    width = latent_width(_pool_spec(cfg).latent_dim)
    return mla_block_pages(page_size, width, itemsize, max_pages), 0


def _forward_slots(params, tokens, cache, tables, positions, cfg, page_size: int):
    """One decode step: lane b's token written and attended at ``positions[b]`` →
    (logits [B,V] fp32, cache, counts int32[3 or 6]: the MoE counts summed over the
    expert layers, then :data:`ATTENTION_COUNTERS` if the config counts them)."""
    B = tokens.shape[0]
    max_len = cache["valid"].shape[1]
    valid = cache["valid"].at[jnp.arange(B), positions].set(True)
    live = positions < max_len                      # a frozen lane is parked at max_len
    n_live = jnp.where(live, positions - jnp.argmax(valid, axis=1) + 1, 0)
    with jax.named_scope("embed"):
        x = params["embed"][tokens[:, None]].astype(cfg.dtype)              # [B,1,D]
    counts = jnp.zeros((3,), jnp.int32)
    attn_counts = [jnp.int32(0)] * 3
    routes = {}                 # window → (tables, valid, write coordinates), once a kind
    new_layers = []
    for l, (layer, kv) in enumerate(zip(params["layers"], cache["layers"])):
        spec = cfg.attn_spec(l)
        name = "ring" if spec.window else "latent"
        if spec.window not in routes:
            tab, seen = tables, valid
            if spec.window:
                tab = ring_tables(positions, tables.shape[1], page_size, spec.window)
                seen = valid & (jnp.arange(max_len)[None, :]
                                > positions[:, None] - spec.window)
            routes[spec.window] = (tab, seen, paged_write_coords(
                tab, positions[:, None], page_size, max_len, kv[name].shape[0]))
        tab, seen, (pages, offs) = routes[spec.window]
        with jax.named_scope("mla"):
            h = _rms_norm(x, layer["ln_attn"], cfg.norm_eps)
            q_nope, q_rope, row, c_q = _mla_project(h, layer, positions[:, None], spec)
            with jax.named_scope("kv_write"):
                new = {name: write_latent_paged({"latent": kv[name]}, row, pages,
                                                offs)["latent"]}
            if spec.index_topk:
                q_idx, k_idx, w_idx = _index_project(h, c_q, layer, positions[:, None], spec)
                new["index_k"] = kv["index_k"].at[pages, offs].set(
                    k_idx.astype(kv["index_k"].dtype))
                o = _attend_selected_pages(q_nope[:, 0], q_rope[:, 0], q_idx[:, 0],
                                           w_idx[:, 0], new, tab, positions, seen,
                                           page_size, layer, spec)
                attn_counts[0] += n_live.sum()
                attn_counts[1] += jnp.minimum(n_live, spec.index_topk).sum()
            else:
                o = _attend_latent_pages(q_nope[:, 0], q_rope[:, 0], new[name], tab,
                                         positions, seen, page_size, layer, spec)
                if spec.window:
                    attn_counts[2] += jnp.minimum(n_live, spec.window).sum()
            o = _gate(o, h[:, 0], layer, spec)
            x = x + (o.reshape(B, -1) @ layer["wo"].astype(cfg.dtype))[:, None]
        y, c = _mlp(x, layer, cfg)
        x = x + y
        counts = counts + c
        new_layers.append(new)
    if cfg.counts_attention:
        counts = jnp.concatenate([counts, jnp.stack(attn_counts).astype(jnp.int32)])
    return _head(x, params, cfg)[:, 0], {"layers": new_layers, "valid": valid}, counts


def forward_slots_paged(params: dict, tokens: jax.Array, cache: dict, tables: jax.Array,
                        positions: jax.Array, cfg, page_size: int):
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
                        cfg, tables: Optional[jax.Array] = None,
                        page_size: int = 0):
    """``n_steps`` paged decode steps as one scan (``common.multi_step_decode``: the
    freeze/emission contract is the shared one). The latent pool rides in the scan's
    CARRY and is written in place. Returns ``(cache, tok_buf [n_steps, B], counts [B],
    model counts int32[3 or 6])`` — the last is the module's ``DECODE_COUNTERS``,
    summed over the steps."""
    if tables is None:
        raise NotImplementedError("deepseek decodes over the paged latent cache only")
    max_len = cache["valid"].shape[1]
    n_counts = 3 + 3 * cfg.counts_attention

    def forward_one(c, tok, write_pos):
        logits, new, counts = _forward_slots(
            params, tok, {"layers": c["layers"], "valid": c["valid"]}, tables, write_pos,
            cfg, page_size)
        return logits, {**new, "moe_counts": c["moe_counts"] + counts}

    carry = {**cache, "moe_counts": jnp.zeros((n_counts,), jnp.int32)}
    carry, tok_buf, counts = multi_step_decode(
        forward_one, carry, tokens, positions, active, budgets, eos_ids, select_token,
        xs, n_steps, max_len)
    moe_counts = carry.pop("moe_counts")
    return carry, tok_buf, counts, moe_counts
