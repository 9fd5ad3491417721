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

**The grouped-query kind** (:class:`GqaSpec`, ``kind == "gqa"``; ``models/keye.py`` is its
config): the layer caches K and V heads themselves — ``{"k", "v"}`` planes, a dense row
in prefill and pool pages under the same block tables in decode — and, under the same
learned selection, one index key a token beside them. ``q = RMSNorm_hd(h W_q)``, ``k =
RMSNorm_hd(h W_k)`` per head (QK-norm), ``v = h W_v``; rotary on ALL ``head_dim`` dims of q
and k from THREE position streams (``mrope_section``: frequency pair ``i`` takes its angle
from the stream whose section holds ``i``; the engine feeds three equal rows, which is
plain RoPE); ``o = softmax(q kᵀ · head_dim^-½ over the selected keys) v``, the same key set
for every head of a query. *Prefill* scores the chunk's index queries against the row's
index keys, cuts each query's ``index_topk`` largest EXACTLY (a radix select on the
scores' bit patterns, no sort; a tie to the earlier key) and hands the flash forward
kernel the cut as its per-pair mask (``common.cached_prefill_attention(select=)``): at
these row widths gathering 2048 K/V rows a query costs 50 times attending every key
under the mask. *Decode* is the latent kind's: index kernel over the lane's pages →
``lax.top_k`` → a gather of the chosen K and V rows into a pool of their own under an
identity table → ``ops.paged_attention`` over that. The experts' router is data too
(``cfg.router``: ``"sigmoid_grouped"`` or ``"softmax"``), as is the shared expert
(``n_shared_experts`` 0: none).

The engine's surface (``serving.ContinuousBatcher`` reaches a model through the module
of its config's class): ``init_cache`` + ``forward_cached`` (chunked prefill of one dense
latent row), ``init_paged_cache`` + ``forward_slots_paged`` / ``forward_slots_multi``
(paged decode), ``forward_slots_spec_multi`` (speculative rounds over the pages: a
verify of ``k + 1`` rows a lane, run as ``(k + 1) · B`` queries of the kernels that share
their lane's table row, each with its own length), ``paged_walk_shape``,
``DECODE_COUNTERS``. Not here, so the engine refuses them for this model: dense decode
rows (``forward_slots``), per-position prefill logits for the prefix cache
(``forward_cached_logits``). Layers are a Python list (``scan_layers`` is False: the
first layer differs, and a pool carried through a scan's ``xs``/``ys`` is copied whole —
here every write is a scatter on the donated carry).

**A prediction module** (``mtp_draft``; ``models/deepseek_v32.py`` has the equations):
its weights under ``params["mtp"]``, its latent and index-key planes as one more layer of
the cache (:func:`cache_specs`), its rows written by prefill one slot behind the chunk's
and, in decode, by the speculative rounds it drafts for (:func:`_module_draft`).
"""

from __future__ import annotations

import dataclasses
import math
from typing import ClassVar, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.sparse_attention import (dsa_index_scores, dsa_index_scores_reference,
                                    index_block_pages, index_pool_shape, index_scores,
                                    write_index_paged)
from .common import (SpecDraft, cached_prefill_attention, kv_planes, latent_planes,
                     latent_width, multi_step_decode, paged_attention_dispatch,
                     paged_kv_planes, paged_latent_planes, paged_read_impl,
                     paged_write_coords, ring_pages, ring_tables, write_kv, write_kv_paged,
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
    router: ClassVar[str] = "sigmoid_grouped"
    # every layer is one kind, and the config is its spec (module docstring)
    kind: ClassVar[str] = "latent"
    window: ClassVar[int] = 0
    index_topk: ClassVar[int] = 0
    attn_gate: ClassVar[bool] = False
    q_rescale: ClassVar[float] = 1.0
    kv_rescale: ClassVar[float] = 1.0
    counts_attention: ClassVar[bool] = False
    #: prediction modules served as the drafter (``models/deepseek_v32.py``): 0 — none
    #: is built, and the engine does not speculate unless asked to
    mtp_draft: ClassVar[int] = 0

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

    kind: ClassVar[str] = "latent"
    rope_factor: ClassVar[float] = 1.0
    rope_orig_max: ClassVar[int] = 4096
    rope_beta_fast: ClassVar[float] = 32.0
    rope_beta_slow: ClassVar[float] = 1.0
    rope_mscale: ClassVar[float] = 1.0
    rope_mscale_all_dim: ClassVar[float] = 1.0

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_dim


@dataclasses.dataclass(frozen=True)
class GqaSpec:
    """One KIND of grouped-query layer (module docstring): ``n_kv_heads`` K and V heads of
    ``head_dim`` a token in the cache, shared by ``n_heads / n_kv_heads`` query heads each;
    QK-norm; rotary over the whole head from the position streams ``mrope_section`` names
    (``()``: one stream); and the switches a latent kind has, of which a grouped-query
    layer has the selection (``index_topk`` with the indexer's sizes; ``index_rope_dim``
    leading dims of an index query and key are rotated, by stream 0)."""
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float
    norm_eps: float
    dtype: jnp.dtype
    mrope_section: tuple = ()
    qk_norm: bool = True
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    index_rope_dim: int = 0

    kind: ClassVar[str] = "gqa"
    window: ClassVar[int] = 0
    attn_gate: ClassVar[bool] = False


def layer_specs(cfg) -> list:
    return [cfg.attn_spec(l) for l in range(cfg.n_layers)]


def cache_specs(cfg) -> list:
    """The specs of the cache's layers: the decoder's, then each prediction module's
    (a layer of the model's own kind, ``cfg.attn_spec(n_layers)``) with planes of its own."""
    return layer_specs(cfg) + [cfg.attn_spec(cfg.n_layers) for _ in range(mtp_draft(cfg))]


def mtp_draft(cfg) -> int:
    """The prediction modules ``cfg`` builds and drafts with (0: none; a config of another
    class than :class:`DeepseekConfig` — dots3's, Keye's — has none)."""
    return getattr(cfg, "mtp_draft", 0)


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
    / ``idx_ww`` and its key norm; a grouped-query layer ``wq`` / ``wk`` / ``wv`` / ``wo`` and
    its two head norms. A layer's attention shapes follow ITS spec. A config with a
    prediction module (``mtp_draft``) also holds ``"mtp"``: an expert layer's dict plus
    ``enorm`` / ``hnorm`` / ``eh_proj`` [2D, D] (the embedding's half first) / ``ln_out``."""
    D, dt = cfg.d_model, cfg.dtype
    layers = [_init_layer(cfg, sp, key, l, l < cfg.n_dense_layers)
              for l, sp in enumerate(layer_specs(cfg))]
    ke, kh = jax.random.split(jax.random.fold_in(key, 1 << 20))
    params = {"embed": _mat(ke, cfg.vocab_size, D, fan_in=D, dtype=dt),
              "lm_head": _mat(kh, D, cfg.vocab_size, dtype=dt), "ln_f": jnp.ones((D,), dt),
              "layers": layers}
    if mtp_draft(cfg):
        l = cfg.n_layers
        params["mtp"] = {
            **_init_layer(cfg, cfg.attn_spec(l), key, l, False),
            "enorm": jnp.ones((D,), dt), "hnorm": jnp.ones((D,), dt),
            "eh_proj": _mat(jax.random.fold_in(key, 1 << 21), 2 * D, D, dtype=dt),
            "ln_out": jnp.ones((D,), dt)}
    return params


def _mat(k, *shape, fan_in=None, dtype):
    return (jax.random.normal(k, shape, jnp.float32)
            / math.sqrt(fan_in or shape[0])).astype(dtype)


def _init_layer(cfg, sp, key, l: int, dense: bool) -> dict:
    """Layer ``l``'s weights from ``key`` (:func:`init_params`)."""
    D, dt = cfg.d_model, cfg.dtype

    def mat(k, *shape, fan_in=None):
        return _mat(k, *shape, fan_in=fan_in, dtype=dt)

    def mlp(k, width, lead=()):
        ks = jax.random.split(k, 3)
        return {"w_gate": mat(ks[0], *lead, D, width, fan_in=D),
                "w_up": mat(ks[1], *lead, D, width, fan_in=D),
                "w_down": mat(ks[2], *lead, width, D, fan_in=width)}

    ks = jax.random.split(jax.random.fold_in(key, l), 12)
    kx = jax.random.split(jax.random.fold_in(key, (1 << 10) + l), 4)
    H = sp.n_heads
    layer = {"ln_attn": jnp.ones((D,), dt), "ln_mlp": jnp.ones((D,), dt)}
    if sp.kind == "gqa":
        hd = sp.head_dim
        layer.update(
            wq=mat(ks[0], D, H * hd), wk=mat(ks[1], D, sp.n_kv_heads * hd),
            wv=mat(ks[2], D, sp.n_kv_heads * hd), wo=mat(ks[5], H * hd, D),
            q_norm=jnp.ones((hd,), dt), k_norm=jnp.ones((hd,), dt))
        idx_from = D                      # no query latent: the index queries come from h
    else:
        layer.update(
            w_qa=mat(ks[0], D, sp.q_lora_rank), q_norm=jnp.ones((sp.q_lora_rank,), dt),
            w_qb=mat(ks[1], sp.q_lora_rank, H * (sp.qk_nope_dim + sp.qk_rope_dim)),
            w_kva=mat(ks[2], D, sp.latent_dim),
            kv_norm=jnp.ones((sp.kv_lora_rank,), dt),
            w_kb=mat(ks[3], sp.kv_lora_rank, H, sp.qk_nope_dim),
            w_vb=mat(ks[4], sp.kv_lora_rank, H, sp.v_head_dim),
            wo=mat(ks[5], H * sp.v_head_dim, D))
        idx_from = sp.q_lora_rank
    if sp.attn_gate:
        layer["w_g"] = mat(kx[0], D, H)
    if sp.index_topk:
        layer.update(
            idx_wq=mat(kx[1], idx_from, sp.index_heads * sp.index_dim),
            idx_wk=mat(kx[2], D, sp.index_dim), idx_ww=mat(kx[3], D, sp.index_heads),
            idx_k_gain=jnp.ones((sp.index_dim,), dt),
            idx_k_bias=jnp.zeros((sp.index_dim,), dt))
    if dense:
        layer.update(mlp(ks[6], cfg.d_ff))
    else:
        layer["moe"] = {
            "router": mat(ks[7], D, cfg.n_routed_experts).astype(jnp.float32),
            "experts": mlp(ks[10], cfg.moe_d_ff, lead=(cfg.experts_held,)),
        }
        if cfg.router == "sigmoid_grouped":
            layer["moe"]["router_bias"] = 0.01 * jax.random.normal(
                ks[8], (cfg.n_routed_experts,), jnp.float32)
        if cfg.n_shared_experts:
            layer["moe"]["shared"] = mlp(ks[9], cfg.moe_d_ff * cfg.n_shared_experts)
    return layer


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


def _rotate(x, ang):
    """x [..., T, (heads,) dim] by angles ``ang`` [..., T, dim / 2]; pairs are the two
    halves of the last dim."""
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if x.ndim == ang.ndim + 1:                        # a heads axis before the last
        cos, sin = cos[..., None, :], sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def _mrope(x, streams, theta: float, section=()):
    """Rotary over ALL of x's last dim from position streams ``streams`` [S, ..., T]:
    frequency pair ``i`` (of ``dim / 2``, base ``theta``) takes its angle from the stream
    whose entry of ``section`` holds ``i`` — ``section`` (16, 24, 24): pairs 0–15 from
    stream 0 (time), 16–39 from stream 1 (height), 40–63 from stream 2 (width). Equal
    streams are plain RoPE; ``section`` ``()`` reads stream 0 alone."""
    dim = x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    section = tuple(section) or (dim // 2,)
    if sum(section) != dim // 2:
        raise ValueError(f"mrope_section {section} does not cover {dim // 2} frequency pairs")
    owner = np.repeat(np.arange(len(section)), section)
    if streams.shape[0] == 1:                                # text: every stream alike
        streams = jnp.broadcast_to(streams, (len(section),) + streams.shape[1:])
    pos = jnp.moveaxis(streams.astype(jnp.float32), 0, -1)[..., owner]   # [..., T, dim/2]
    return _rotate(x, pos * freq)


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
    RoPE on their first rope dims; w [B,T,Hi] float32, the heads' weights with ``Hi^-½ ·
    Di^-½`` folded in). ``k_idx = LayerNorm(h W_k)`` is the row the index cache holds. The
    index queries come from ``c_q`` — a latent layer's query latent, a grouped-query
    layer's ``h`` itself; a latent layer rotates ``qk_rope_dim`` dims as its ``k_rope``, a
    grouped-query layer ``index_rope_dim`` by plain RoPE at ``positions`` [B,T]."""
    B, T, _ = h.shape
    dt, Hi, Di = spec.dtype, spec.index_heads, spec.index_dim
    if spec.kind == "gqa":
        r, rope = spec.index_rope_dim, lambda x: _mrope(x, positions[None], spec.rope_theta)
    else:
        r, rope = spec.qk_rope_dim, lambda x: _rope(x, positions, spec)
    q = (c_q @ layer["idx_wq"].astype(dt)).reshape(B, T, Hi, Di)
    q = jnp.concatenate([rope(q[..., :r]), q[..., r:]], -1)
    k = (h @ layer["idx_wk"].astype(dt)).astype(jnp.float32)
    k = k - k.mean(-1, keepdims=True)
    k = k * jax.lax.rsqrt((k * k).mean(-1, keepdims=True) + _INDEX_NORM_EPS)
    k = (k * layer["idx_k_gain"].astype(jnp.float32)
         + layer["idx_k_bias"].astype(jnp.float32)).astype(dt)
    k = jnp.concatenate([rope(k[..., :r]), k[..., r:]], -1)
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


def _score_rows(q_idx, w_idx, index_k, q_positions, valid, n_keys):
    """The indexer's score ``Σ_head w · ReLU(q_head · k)`` of every live key ``<=`` each
    query of a prefill chunk (a block of keys at a time, RUNTIME trip count), ``-inf``
    elsewhere. q_idx [B,T,Hi,Di], w_idx [B,T,Hi], index_k [B,C,Di] → float32 [B,T,C]."""
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

    return jax.lax.fori_loop(0, (n_keys + kb - 1) // kb, body,
                             jnp.full((B, T, C), -jnp.inf, jnp.float32))


def _select_rows(q_idx, w_idx, index_k, q_positions, valid, n_keys, spec):
    """Prefill's selection as ROW SLOTS (the latent kind gathers them): :func:`_score_rows`,
    then an exact top ``index_topk`` a query (ties to the earlier key: ``lax.top_k``) →
    (sel [B,T,K] int32 row slots, ok [B,T,K]: the slot is a selected key, not filler
    behind a query with fewer)."""
    scores = _score_rows(q_idx, w_idx, index_k, q_positions, valid, n_keys)
    vals, sel = _top_k_live(scores, n_keys, min(spec.index_topk, scores.shape[-1]))
    return sel.astype(jnp.int32), vals > -jnp.inf


def _live_widths(C: int, k: int) -> list:
    """The column counts a selection over a row of ``C`` slots may be cut to: the
    narrowest of C/8, C/4, C/2, C that holds the live keys is enough."""
    return [w for w in (C // 8, C // 4, C // 2) if w >= max(k, 1024) and w % 128 == 0] + [C]


def _kth_largest(scores, k: int):
    """The ``k``-th largest of each row of float32 ``scores`` [..., W], EXACTLY and without
    a sort: floats map to uint32 in their own order, and the answer's 32 bits are settled
    from the top, each by one count of the entries at or above a candidate (a radix
    select: 32 compare-and-count passes, where ``lax.top_k`` of [512, 32768] sorts for
    17.9 ms on a v5e). → (the row's keys uint32 [..., W], the k-th largest key [..., 1])."""
    bits = jax.lax.bitcast_convert_type(scores + 0.0, jnp.uint32)    # -0.0 + 0.0 is +0.0
    key = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    kth = jnp.zeros(scores.shape[:-1] + (1,), jnp.uint32)
    for bit in range(31, -1, -1):
        cand = kth | jnp.uint32(1 << bit)
        kth = jnp.where((key >= cand).sum(-1, keepdims=True) >= k, cand, kth)
    return key, kth


def _top_k_mask(scores, n_keys, k: int):
    """bool like ``scores`` [B,T,C] (``-inf`` on a key a query does not see; only the first
    ``n_keys`` — traced — columns can be finite): true at each row's ``k`` largest, at all
    of its finite entries while it has no more; a tie at the cut goes to the earlier
    column. The cut is :func:`_kth_largest` over the narrowest columns that hold the live
    keys (``lax.switch``: one program for every fill); ties AT the cut are counted, and
    only a call in which some row has more of them than places left pays the running
    count that hands them out in column order."""
    C = scores.shape[-1]
    seen = scores > -jnp.inf

    def cut(width):
        def select(scores):
            key, kth = _kth_largest(scores[..., :width], k)
            above = key > kth
            tie = (key == kth) & seen[..., :width]
            room = k - above.sum(-1, keepdims=True)
            keep = jax.lax.cond(
                jnp.any(tie.sum(-1, keepdims=True) > room),
                lambda: above | (tie & (jnp.cumsum(tie, -1) <= room)),
                lambda: above | tie)
            return jnp.pad(keep, ((0, 0), (0, 0), (0, C - width)))
        return select

    widths = _live_widths(C, k)
    branch = sum((n_keys > w).astype(jnp.int32) for w in widths[:-1])
    chosen = jax.lax.cond(
        n_keys > k, lambda: jax.lax.switch(branch, [cut(w) for w in widths], scores),
        lambda: seen)
    return chosen & seen


def _select_mask(q_idx, w_idx, index_k, q_positions, valid, n_keys, spec):
    """Prefill's selection as a MASK (the grouped-query kind attends every key under
    it): bool [B,T,C], true at the ``index_topk`` live keys of largest index score of
    each query (:func:`_score_rows`, :func:`_top_k_mask`)."""
    scores = _score_rows(q_idx, w_idx, index_k, q_positions, valid, n_keys)
    return _top_k_mask(scores, n_keys, min(spec.index_topk, scores.shape[-1]))


def _top_k_live(scores, n_keys, k: int):
    """``lax.top_k(scores, k)`` over the last axis, of which only the first ``n_keys``
    (traced) columns can be finite: the sort runs over the narrowest of C/8, C/4, C/2, C
    columns that holds them (``lax.switch``: still one program for every fill). On a
    v5e the sort of [512, 32768] takes 17.9 ms and that of [512, 8192] 1.65 ms (PERF.md,
    PR 32), and most of a prompt's chunks are early ones."""
    widths = _live_widths(scores.shape[-1], k)
    if len(widths) == 1:
        return jax.lax.top_k(scores, k)
    branch = sum((n_keys > w).astype(jnp.int32) for w in widths[:-1])
    return jax.lax.switch(
        branch, [lambda s, w=w: jax.lax.top_k(s[..., :w], k) for w in widths], scores)


def _attend_selected_rows(q_nope, q_rope, latent, sel, ok, layer, spec):
    """Prefill form of a sparse layer: each query against ITS selected latent rows
    ``latent[b, sel[b,t]]`` in the absorbed form (the rows are per query, so there is
    nothing to up-project once for all), ``_QUERY_BLOCK`` queries' rows gathered at a
    time — a chunk of another length (a prediction module's, one row longer) padded to
    whole blocks, whose filler queries select nothing. The cost does not grow with the
    row's fill. → o [B,T,H,v_head_dim]."""
    B, T, H, _ = q_nope.shape
    dt, R, r = spec.dtype, spec.kv_lora_rank, spec.qk_rope_dim
    scale = sm_scale(spec)
    qb = min(_QUERY_BLOCK, T)
    pad = -T % qb
    if pad:
        grow = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))  # noqa: E731
        q_nope, q_rope, sel, ok = grow(q_nope), grow(q_rope), grow(sel), grow(ok)
    T_in, T = T, T + pad
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

    o_lat = jax.vmap(per_row)(q_lat, q_rope, latent, sel, ok)[:, :T_in]
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


def _scores_paged(q_idx, w_idx, index_pool, tables, positions, valid, page_size: int):
    """The indexer's score of every live key of every lane through the block tables → [B,
    C]: the Pallas kernel on a TPU backend (or when forced), else its jnp oracle
    (``common.paged_read_impl``, as for the attention's read)."""
    score = dsa_index_scores if paged_read_impl() == "kernel" else dsa_index_scores_reference
    return score(q_idx, w_idx, index_pool, tables, positions, valid, page_size=page_size)


def _top_rows(scores, tables, page_size: int, k: int, num_pages: int):
    """Decode's selection: the ``k`` (at most C) best-scored slots of each lane (scores
    [B,C], ``-inf`` on a dead slot; a tie to the earlier slot) as PHYSICAL pool rows → (live
    [B,k]: the entry is a scored slot, not filler behind a lane with fewer; pages, offs [B,k]).
    ONE stable key-value sort whose payload is each slot's physical row (its table entry
    × page_size + offset, an elementwise map of the table), where ``lax.top_k`` — the
    same sort with the slot as payload — left 32 768 table look-ups a layer behind it
    (1.3 ms a call on a v5e, more than the sort: PERF.md, PR 34)."""
    B, C = scores.shape
    k = min(k, C)
    with jax.named_scope("dsa_topk"):
        page = jnp.minimum(jnp.repeat(tables, page_size, axis=1)[:, :C], num_pages - 1)
        row = page * page_size + jnp.arange(C, dtype=jnp.int32) % page_size
        neg, row = jax.lax.sort((-scores, row), dimension=1, num_keys=1, is_stable=True)
    neg, row = neg[:, :k], row[:, :k]
    return neg < jnp.inf, row // page_size, row % page_size


def _attend_selected_pages(q_nope, q_rope, q_idx, w_idx, kv, tables, positions, valid,
                           page_size, layer, spec):
    """Decode form of a sparse layer, one query a lane: the indexer's score of every
    live key through the block tables (``ops.sparse_attention.dsa_index_scores``), an
    exact top ``index_topk`` a lane, a gather of the chosen latent rows into a pool of
    their own — ``index_topk / page_size`` pages a lane under an identity table — and
    the decode form over that. A lane with no more live keys than ``index_topk`` attends
    to all of them."""
    pool = kv["latent"]
    P, B = pool.shape[0], positions.shape[0]
    scores = _scores_paged(q_idx, w_idx, kv["index_k"], tables, positions, valid, page_size)
    live, pages, offs = _top_rows(scores, tables, page_size, spec.index_topk, P)
    K = live.shape[1]
    with jax.named_scope("dsa_gather"):
        rows = pool[pages, offs]                                              # [B,K,W]
    chosen = rows.reshape(B * (K // page_size), page_size, rows.shape[-1])
    identity = jnp.arange(B * (K // page_size), dtype=jnp.int32).reshape(B, -1)
    return _attend_latent_pages(
        q_nope, q_rope, chosen, identity, jnp.full((B,), K - 1, jnp.int32),
        live, page_size, layer, spec)


def _gqa_project(h, layer, streams, spec):
    """h [B,T,D] → (q [B,T,H,hd], k [B,T,K,hd], v [B,T,K,hd]) of a grouped-query layer:
    per-head RMSNorm on q and k (``qk_norm``), rotary on the whole head from the position
    ``streams`` [S,B,T] (:func:`_mrope`); ``k`` and ``v`` are the rows the cache holds."""
    B, T, _ = h.shape
    dt, hd = spec.dtype, spec.head_dim
    q = (h @ layer["wq"].astype(dt)).reshape(B, T, spec.n_heads, hd)
    k = (h @ layer["wk"].astype(dt)).reshape(B, T, spec.n_kv_heads, hd)
    v = (h @ layer["wv"].astype(dt)).reshape(B, T, spec.n_kv_heads, hd)
    if spec.qk_norm:
        q = _rms_norm(q, layer["q_norm"], spec.norm_eps)
        k = _rms_norm(k, layer["k_norm"], spec.norm_eps)
    return (_mrope(q, streams, spec.rope_theta, spec.mrope_section),
            _mrope(k, streams, spec.rope_theta, spec.mrope_section), v)


def _gqa_attend_dense(q, ck, cv, seen):
    """Plain masked attention of q [B,T,H,hd] over dense K/V rows ck/cv [B,C,K,hd] under
    ``seen`` [B,T,C] (every head alike), each K/V head against its own group of query
    heads, fp32 softmax: the grouped-query kind's path off-TPU, for a chunk that is no
    whole kernel tile, and under decode's gather read. A query that sees nothing reads 0."""
    B, T, H, hd = q.shape
    K = ck.shape[2]
    qg = q.reshape(B, T, K, H // K, hd)
    s = jnp.einsum("btkgd,bckd->bkgtc", qg, ck.astype(q.dtype),
                   preferred_element_type=jnp.float32) * hd ** -0.5
    m = seen[:, None, None]
    s = jnp.where(m, s, -1e30)
    p = jnp.where(m, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    l = p.sum(-1, keepdims=True)
    p = (p / jnp.where(l == 0.0, 1.0, l)).astype(q.dtype)
    return jnp.einsum("bkgtc,bckd->btkgd", p, cv.astype(q.dtype)).reshape(B, T, H, hd)


def _gqa_attend_pages(q, q_idx, w_idx, kv, tables, positions, valid, page_size, spec):
    """Decode form of a sparse grouped-query layer, one query a lane (q [B,H,hd]): the
    indexer's score of every live key through the block tables, an exact top
    ``index_topk`` a lane (:func:`_top_rows`), a gather of the chosen K and V rows into a
    pool of their own — ``index_topk / page_size`` pages a lane under an identity table — and
    ``ops.paged_attention`` over that (the chosen rows lie in score order: no causal term
    is left, their validity is the mask). A lane with no more live keys than
    ``index_topk`` attends to all of them. → o [B,H,hd]."""
    P, B = kv["k"].shape[0], positions.shape[0]
    scores = _scores_paged(q_idx, w_idx, kv["index_k"], tables, positions, valid, page_size)
    live, pages, offs = _top_rows(scores, tables, page_size, spec.index_topk, P)
    K = live.shape[1]
    with jax.named_scope("dsa_gather"):
        chosen = {n: kv[n][pages, offs].reshape(
            B * (K // page_size), page_size, *kv[n].shape[2:]) for n in ("k", "v")}
    identity = jnp.arange(B * (K // page_size), dtype=jnp.int32).reshape(B, -1)
    with jax.named_scope("sparse_kv_attention"):
        o = paged_attention_dispatch(
            q[:, None], chosen, identity, jnp.full((B,), K - 1, jnp.int32), live,
            page_size=page_size, sm_scale=spec.head_dim ** -0.5, dtype=spec.dtype,
            dense_attention=lambda ck, cv: _gqa_attend_dense(
                q[:, None], ck, cv, live[:, None, :]))
    return o[:, 0]


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
            compute_dtype=cfg.dtype, router=cfg.router)
    return y.reshape(B, T, D), counts


def _head(x, params, cfg, norm=None):
    """Logits fp32 of hidden states x: the final norm (``norm``: a prediction module's
    own, before the SHARED head), then the head."""
    x = _rms_norm(x, params["ln_f"] if norm is None else norm, cfg.norm_eps)
    with jax.named_scope("head"):
        return (x @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)


def _module_input(params, hidden, next_tokens, cfg):
    """A prediction module's input rows: ``u_i = W_eh [RMSNorm_e(E t_{i+1});
    RMSNorm_h(h_i)]`` from the main stack's last hidden states ``hidden`` [B,T,D] (before
    its final norm) and the tokens that follow them ``next_tokens`` [B,T]."""
    m = params["mtp"]
    with jax.named_scope("embed"):
        e = params["embed"][next_tokens].astype(cfg.dtype)
    eh = jnp.concatenate([_rms_norm(e, m["enorm"], cfg.norm_eps),
                          _rms_norm(hidden.astype(cfg.dtype), m["hnorm"], cfg.norm_eps)], -1)
    return eh @ m["eh_proj"].astype(cfg.dtype)


# ------------------------------------------------------------- dense latent row: prefill
def _row_planes(spec, batch: int, max_len: int, dtype) -> dict:
    """One layer's empty dense row: ``latent`` — a grouped-query layer's ``k`` and ``v`` —
    (+ ``index_k`` for a sparse layer); a sliding layer's row is named ``ring`` — the
    engine lands only its last window's rows, into the lane's ring of pages."""
    if spec.kind == "gqa":
        planes = kv_planes(batch, max_len, spec.n_kv_heads, spec.head_dim, dtype, False)
    else:
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
    cache = {"layers": [_row_planes(sp, batch_size, max_len, dtype)
                        for sp in cache_specs(cfg)],
             "valid": jnp.zeros((batch_size, max_len), jnp.bool_),
             "index": jnp.zeros((), jnp.int32)}
    if mtp_draft(cfg):
        # the main stack's last hidden state before this chunk (a module row needs the
        # token AFTER its position, so a chunk's module rows start one slot back), and
        # the draft the module made at the last position
        cache["mtp_h"] = jnp.zeros((batch_size, cfg.d_model), dtype)
        cache["draft"] = jnp.zeros((batch_size,), jnp.int32)
    return cache


def _write_row(plane, row, index, slots=None):
    """Rows ``row`` [B,T,·] into the dense row ``plane`` [B,C,W] at ``index ..`` — or, given
    ``slots`` [B,T], at those slots, where a negative one DROPS (a prediction module's rows
    start one slot before the chunk's)."""
    row = jnp.pad(row.astype(plane.dtype),
                  ((0, 0), (0, 0), (0, plane.shape[-1] - row.shape[-1])))
    if slots is None:
        return jax.lax.dynamic_update_slice(plane, row, (0, index, 0))
    slots = jnp.where(slots >= 0, slots, plane.shape[1])
    return plane.at[jnp.arange(plane.shape[0])[:, None], slots].set(row, mode="drop")


def _gqa_rows(h, layer, kv, slots, streams, valid, index, spec):
    """A grouped-query layer's attention over a prefill chunk h [B,T,D] written at row
    slots ``slots`` [B,T] (= ``index ..``): K, V and the index key into the dense row, the
    selection's mask, then the flash forward kernel under it (``common.
    cached_prefill_attention``; plain masked attention where that declines).
    → (o [B,T,H,hd], the layer's new planes)."""
    T, C = h.shape[1], valid.shape[1]
    q, k, v = _gqa_project(h, layer, streams, spec)
    with jax.named_scope("kv_write"):
        new = {**write_kv(kv, "k", k, index), **write_kv(kv, "v", v, index)}
    select = None
    seen = valid[:, None, :] & (jnp.arange(C)[None, None, :] <= slots[:, :, None])
    if spec.index_topk:
        with jax.named_scope("dsa_select"):
            q_idx, k_idx, w_idx = _index_project(h, h, layer, streams[0], spec)
            new["index_k"] = _write_row(kv["index_k"], k_idx, index)
            select = seen = _select_mask(q_idx, w_idx, new["index_k"], slots, valid,
                                         index + T, spec)
    with jax.named_scope("sparse_kv_attention"):
        o = cached_prefill_attention(
            q, new["k"], new["v"], index, valid, impl="auto",
            sm_scale=spec.head_dim ** -0.5, select=select,
            xla_attention=lambda: _gqa_attend_dense(q, new["k"], new["v"], seen))
    return o, new


def _forward_rows(params, tokens, cache, cfg, token_mask, last_only, positions=None):
    B, T = tokens.shape
    index = cache["index"]
    slots = index + jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    # rotary's position streams [S,B,T]: a row slot IS a text position; a caller with
    # image or video tokens hands its own (t, h, w) rows (a grouped-query kind reads them)
    streams = slots[None] if positions is None else positions
    if token_mask is None:
        token_mask = jnp.ones((B, T), jnp.bool_)
    valid = jax.lax.dynamic_update_slice(cache["valid"], token_mask, (0, index))
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
    new_layers = []
    for l, (layer, kv) in enumerate(zip(params["layers"], cache["layers"])):
        spec = cfg.attn_spec(l)
        with jax.named_scope("gqa" if spec.kind == "gqa" else "mla"):
            h = _rms_norm(x, layer["ln_attn"], cfg.norm_eps)
            if spec.kind == "gqa":
                o, new = _gqa_rows(h, layer, kv, slots, streams, valid, index, spec)
            else:
                o, new = _latent_rows(h, layer, kv, slots, valid, index, spec)
            o = _gate(o, h, layer, spec)
            x = x + o.reshape(B, T, -1) @ layer["wo"].astype(cfg.dtype)
        y, _ = _mlp(x, layer, cfg)
        x = x + y
        new_layers.append(new)
    logits = _head(x[:, -1:, :] if last_only else x, params, cfg)
    out = {**cache, "layers": new_layers, "valid": valid, "index": index + T}
    if not mtp_draft(cfg):
        return logits, out
    # The prediction module, a row a position: row i reads h_i and the token at i + 1, so
    # this chunk's rows are the slots index - 1 .. index + T - 1 — the first from the
    # hidden state the previous chunk left, the last from the token the chunk's last
    # logits put first (the engine's first token where this is the prompt's last chunk;
    # a later chunk writes that row again with the prompt's own token).
    first = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    hidden = jnp.concatenate([cache["mtp_h"][:, None].astype(x.dtype), x], 1)
    nxt = jnp.concatenate([tokens, first[:, None]], 1)
    m_slots = index - 1 + jnp.broadcast_to(jnp.arange(T + 1, dtype=jnp.int32), (B, T + 1))
    spec = cfg.attn_spec(cfg.n_layers)
    with jax.named_scope("mtp"):
        u = _module_input(params, hidden, nxt, cfg)
        h = _rms_norm(u, params["mtp"]["ln_attn"], cfg.norm_eps)
        o, new = _latent_rows(h, params["mtp"], cache["layers"][cfg.n_layers], m_slots,
                              valid, index - 1, spec, scatter=True)
        u = u + o.reshape(B, T + 1, -1) @ params["mtp"]["wo"].astype(cfg.dtype)
        u = u + _mlp(u, params["mtp"], cfg)[0]
        m_logits = _head(u[:, -1:] if last_only else u, params, cfg, params["mtp"]["ln_out"])
    out.update(layers=new_layers + [new], mtp_h=x[:, -1].astype(cache["mtp_h"].dtype),
               draft=jnp.argmax(m_logits[:, -1], axis=-1).astype(jnp.int32))
    if not last_only:
        out["mtp_logits"] = m_logits[:, 1:]       # the rows of slots index .. index + T - 1
    return logits, out


def _latent_rows(h, layer, kv, positions, valid, index, spec, scatter=False):
    """A latent layer's attention over a prefill chunk h [B,T,D] at ``positions`` [B,T]
    (= the row slots it is written at, from ``index``; ``scatter``: a slot below 0 is
    dropped) → (o [B,T,H,v_head_dim], the layer's new planes)."""
    T = h.shape[1]
    slots = positions if scatter else None
    q_nope, q_rope, row, c_q = _mla_project(h, layer, positions, spec)
    name = "ring" if spec.window else "latent"
    with jax.named_scope("kv_write"):
        new = {name: _write_row(kv[name], row, index, slots)}
    if spec.index_topk:
        with jax.named_scope("dsa_select"):
            q_idx, k_idx, w_idx = _index_project(h, c_q, layer, positions, spec)
            new["index_k"] = _write_row(kv["index_k"], k_idx, index, slots)
            sel, ok = _select_rows(q_idx, w_idx, new["index_k"], positions, valid,
                                   index + T, spec)
        return _attend_selected_rows(q_nope, q_rope, new["latent"], sel, ok, layer, spec), new
    return _attend_latent_rows(q_nope, q_rope, new[name], positions, valid, index + T,
                               layer, spec), new


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


def forward(params: dict, tokens: jax.Array, cfg, positions=None) -> jax.Array:
    """Logits [B,S,V] fp32 of a whole sequence: the prefill form over a fresh row.
    ``positions`` [3,B,S]: a grouped-query model's (t, h, w) rotary position rows where
    they differ (image and video tokens); the default is text — every row the index."""
    cache = init_cache(cfg, tokens.shape[0], tokens.shape[1])
    return _forward_rows(params, tokens, cache, cfg, None, False, positions)[0]


def forward_mtp(params: dict, tokens: jax.Array, cfg) -> tuple:
    """(logits [B,S,V], the prediction module's logits [B,S,V]) fp32 of a whole sequence:
    module row ``i`` reads the main stack's ``h_i`` and ``tokens[i + 1]`` and scores the
    token at ``i + 2``; the last row reads the token the main logits put first."""
    cache = init_cache(cfg, tokens.shape[0], tokens.shape[1])
    logits, cache = _forward_rows(params, tokens, cache, cfg, None, False)
    return logits, cache["mtp_logits"]


# ------------------------------------------------------------------ latent pages: decode
def init_paged_cache(cfg, batch_size: int, max_len: int, num_pages: int,
                     page_size: int, dtype=None) -> dict:
    """An empty paged latent cache: ``{"layers": [...], "valid": [B, max_len] bool}``.
    A full layer holds ``{"latent": [P, page_size, W]}`` — a grouped-query one ``{"k", "v":
    [P, page_size, K, hd]}`` — (a sparse one also ``"index_k"``, the same pages in whole
    128-lane rows: ``ops.sparse_attention.index_pool_shape``) — which lane owns which page is the
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
        if sp.kind == "gqa":
            out = paged_kv_planes(num_pages, page_size, sp.n_kv_heads, sp.head_dim, dtype,
                                  False)
        else:
            out = paged_latent_planes(num_pages, page_size, sp.latent_dim, dtype)
        if sp.index_topk:
            out["index_k"] = jnp.zeros(
                index_pool_shape(num_pages, page_size, sp.index_dim), dtype)
        return out

    cache = {"layers": [planes(sp) for sp in cache_specs(cfg)],
             "valid": jnp.zeros((batch_size, max_len), jnp.bool_)}
    if mtp_draft(cfg):
        cache["draft"] = jnp.zeros((batch_size,), jnp.int32)   # each lane's next draft
    return cache


def paged_walk_shape(cfg, page_size: int, itemsize: int, max_pages: int) -> tuple:
    """(table entries the decode kernel fetches an iteration, its window — none) for
    the engine's ``pages_live`` / ``pages_walked`` counters: the walk of a full layer
    over the block tables (a sparse layer's indexer walks the same range)."""
    from ..ops.mla_attention import mla_block_pages

    spec = _pool_spec(cfg)
    if spec.kind == "gqa":       # the walk over the tables is the indexer's
        return index_block_pages(page_size, max_pages), 0
    return mla_block_pages(page_size, latent_width(spec.latent_dim), itemsize, max_pages), 0


def _read_route(tables, positions, valid, spec, T: int, page_size: int, num_pages: int):
    """How a layer of ``spec``'s window reaches its pages for ``T`` rows a lane at
    ``positions`` .. ``+ T - 1``: (the write coordinates [B,T], and for the reads — ONE row
    of the kernels' a query, ``B · T`` of them sharing their lane's table row — the tables,
    the valid rows, the positions and each row's live keys: 0 past ``max_len``)."""
    B, max_len = valid.shape
    grid = positions[:, None] + jnp.arange(T, dtype=jnp.int32)
    pos = grid.reshape(B * T)
    n_live = jnp.where(grid < max_len, grid - jnp.argmax(valid, axis=1)[:, None] + 1, 0)
    tab, seen = tables, valid
    if spec.window:
        if T > 1:
            raise NotImplementedError("a sliding layer's ring reads one query a lane")
        tab = ring_tables(positions, tables.shape[1], page_size, spec.window)
        seen = valid & (jnp.arange(max_len)[None, :] > positions[:, None] - spec.window)
    coords = paged_write_coords(tab, grid, page_size, max_len, num_pages)
    if T > 1:
        tab, seen = jnp.repeat(tab, T, axis=0), jnp.repeat(seen, T, axis=0)
    return coords, tab, seen, pos, n_live.reshape(B * T)


def _decode_layer(x, layer, kv, spec, route, page_size: int, max_len: int):
    """One layer's attention over T tokens a lane, x [B,T,D] — the rows written through
    ``route`` (:func:`_read_route`) and each attended at its own position → (x plus the
    attention's output, the layer's new planes, its counts [3]: live keys the indexer
    scored, rows the selection handed on, rows a window read)."""
    B, T, _ = x.shape
    (pages, offs), tab, seen, pos, n_live = route
    gqa = spec.kind == "gqa"
    name = "k" if gqa else "ring" if spec.window else "latent"
    rows = lambda a: a.reshape(B * T, *a.shape[2:])                    # noqa: E731
    counts = [jnp.int32(0)] * 3
    with jax.named_scope("gqa" if gqa else "mla"):
        h = _rms_norm(x, layer["ln_attn"], spec.norm_eps)
        if gqa:
            q, k, v = _gqa_project(h, layer, (pos.reshape(B, T))[None], spec)
            c_q = h
            with jax.named_scope("kv_write"):
                new = {**write_kv_paged(kv, "k", k, pages, offs),
                       **write_kv_paged(kv, "v", v, pages, offs)}
        else:
            q_nope, q_rope, row, c_q = _mla_project(h, layer, pos.reshape(B, T), spec)
            with jax.named_scope("kv_write"):
                new = {name: write_latent_paged({"latent": kv[name]}, row, pages,
                                                offs)["latent"]}
        if spec.index_topk:
            q_idx, k_idx, w_idx = _index_project(h, c_q, layer, pos.reshape(B, T), spec)
            new["index_k"] = write_index_paged(kv["index_k"], k_idx, pages, offs)
            if gqa:
                o = _gqa_attend_pages(rows(q), rows(q_idx), rows(w_idx), new, tab, pos,
                                      seen, page_size, spec)
            else:
                o = _attend_selected_pages(rows(q_nope), rows(q_rope), rows(q_idx),
                                           rows(w_idx), new, tab, pos, seen, page_size,
                                           layer, spec)
            counts[0] = n_live.sum()
            counts[1] = jnp.minimum(n_live, spec.index_topk).sum()
        elif gqa:
            qr = rows(q)[:, None]
            o = paged_attention_dispatch(
                qr, new, tab, pos, seen, page_size=page_size,
                sm_scale=spec.head_dim ** -0.5, dtype=spec.dtype,
                dense_attention=lambda ck, cv: _gqa_attend_dense(
                    qr, ck, cv, (seen & (jnp.arange(max_len)[None, :]
                                         <= pos[:, None]))[:, None, :]))[:, 0]
        else:
            o = _attend_latent_pages(rows(q_nope), rows(q_rope), new[name], tab, pos, seen,
                                     page_size, layer, spec)
            if spec.window:
                counts[2] = jnp.minimum(n_live, spec.window).sum()
        o = _gate(o.reshape(B, T, *o.shape[1:]), h, layer, spec)
        x = x + o.reshape(B, T, -1) @ layer["wo"].astype(spec.dtype)
    return x, new, jnp.stack(counts).astype(jnp.int32)


def _forward_slots(params, tokens, cache, tables, positions, cfg, page_size: int):
    """One decode forward of T tokens a lane, tokens [B,T]: lane b's written and attended
    at ``positions[b] ..`` ``+ T - 1`` (each row sees the rows before it and itself) →
    (logits [B,T,V] fp32, cache, counts int32[3 or 6]: the MoE counts summed over the
    expert layers, then :data:`ATTENTION_COUNTERS` if the config counts them; the main
    stack's last hidden states x [B,T,D], before the final norm — a prediction module
    reads them). Cache leaves past the decoder's layers (a module's planes, the lanes'
    drafts) pass through untouched."""
    B, T = tokens.shape
    max_len = cache["valid"].shape[1]
    grid = positions[:, None] + jnp.arange(T, dtype=jnp.int32)
    valid = cache["valid"].at[jnp.arange(B)[:, None], grid].set(True)
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.dtype)                        # [B,T,D]
    counts = jnp.zeros((3,), jnp.int32)
    attn_counts = jnp.zeros((3,), jnp.int32)
    routes = {}                 # window → _read_route(...), once a kind
    new_layers = []
    for l, (layer, kv) in enumerate(zip(params["layers"], cache["layers"])):
        spec = cfg.attn_spec(l)
        if spec.window not in routes:
            name = "k" if spec.kind == "gqa" else "ring" if spec.window else "latent"
            routes[spec.window] = _read_route(tables, positions, valid, spec, T, page_size,
                                              kv[name].shape[0])
        x, new, c = _decode_layer(x, layer, kv, spec, routes[spec.window], page_size,
                                  max_len)
        attn_counts = attn_counts + c
        y, c = _mlp(x, layer, cfg)
        x = x + y
        counts = counts + c
        new_layers.append(new)
    if cfg.counts_attention:
        counts = jnp.concatenate([counts, attn_counts])
    new = {**cache, "layers": new_layers + cache["layers"][len(new_layers):], "valid": valid}
    return _head(x, params, cfg), new, counts, x


def _module_slots(params, hidden, next_tokens, cache, tables, positions, cfg,
                  page_size: int):
    """The prediction module over the rows a verify just wrote, T a lane: row ``j`` of
    lane b at ``positions[b] + j`` from the main stack's hidden state there ``hidden``
    [B,T,D] and the token that follows it ``next_tokens`` [B,T], through the module's own
    latent and index-key planes (``cache["layers"][n_layers]``, the valid rows the verify
    left) → (the module's hidden states [B,T,D] before its norm, the cache with its planes
    written, counts as :func:`_forward_slots`'s)."""
    B, T, _ = hidden.shape
    L, max_len = cfg.n_layers, cache["valid"].shape[1]
    spec, kv = cfg.attn_spec(L), cache["layers"][L]
    route = _read_route(tables, positions, cache["valid"], spec, T, page_size,
                        kv["latent"].shape[0])
    with jax.named_scope("mtp"):
        u = _module_input(params, hidden, next_tokens, cfg)
        u, new, attn_counts = _decode_layer(u, params["mtp"], kv, spec, route, page_size,
                                            max_len)
        y, counts = _mlp(u, params["mtp"], cfg)
    if cfg.counts_attention:
        counts = jnp.concatenate([counts, attn_counts])
    layers = list(cache["layers"])
    layers[L] = new
    return u + y, {**cache, "layers": layers}, counts


def forward_slots_paged(params: dict, tokens: jax.Array, cache: dict, tables: jax.Array,
                        positions: jax.Array, cfg, page_size: int):
    """Per-lane decode over the paged latent cache: ``tokens`` [B,T] written at each lane's
    own ``positions[b] ..`` through its block-table row (sentinel entries and positions at
    or past ``max_len`` DROP), each row attended at its own position — T > 1 is the
    speculative verify, its rows run as ``B · T`` queries of the kernels, each with its
    own length over its lane's table row → (logits [B,T,V] fp32, new cache)."""
    logits, cache, _, _ = _forward_slots(params, tokens, cache, tables, positions, cfg,
                                         page_size)
    return logits, cache


def forward_slots_multi(params: dict, cache: dict, tokens: jax.Array,
                        positions: jax.Array, active: jax.Array, budgets: jax.Array,
                        eos_ids: jax.Array, select_token, xs, n_steps: int,
                        cfg, tables: Optional[jax.Array] = None,
                        page_size: int = 0):
    """``n_steps`` paged decode steps as one scan (``common.multi_step_decode``: the
    freeze/emission contract is the shared one). The latent pool rides in the scan's
    CARRY and is written in place. Returns ``(cache, tok_buf [n_steps, B], counts [B],
    model counts int32[3 or 6])`` — the last is the module's ``DECODE_COUNTERS``,
    summed over the steps. A prediction module's planes and drafts ride along
    untouched: they are the speculative path's."""
    if tables is None:
        raise NotImplementedError("deepseek decodes over the paged latent cache only")
    max_len = cache["valid"].shape[1]
    n_counts = 3 + 3 * cfg.counts_attention

    def forward_one(c, tok, write_pos):
        logits, new, counts, _ = _forward_slots(params, tok[:, None], c, tables, write_pos,
                                                cfg, page_size)
        return logits[:, 0], {**new, "moe_counts": c["moe_counts"] + counts}

    carry = {**cache, "moe_counts": jnp.zeros((n_counts,), jnp.int32)}
    carry, tok_buf, counts = multi_step_decode(
        forward_one, carry, tokens, positions, active, budgets, eos_ids, select_token,
        xs, n_steps, max_len)
    moe_counts = carry.pop("moe_counts")
    return carry, tok_buf, counts, moe_counts


def _module_draft(params, cfg, tables, page_size: int) -> SpecDraft:
    """The prediction module as the fused scan's drafter: the round's draft is the lane's
    carried ``cache["draft"]``; after the accept, the module runs over the rows the verify
    wrote (``p .. p + k``) with the hidden states there and the ACCEPTED tokens that follow
    them — a row past the accepted prefix reads a rejected token and is written again the
    next round, whose rows start at it — and the next draft is the module's first choice
    at row ``p + n - 1``, the row before the new pending token. A lane that emitted nothing
    keeps its draft; its writes dropped at ``max_len``."""
    def propose(_state, c):
        return c["draft"][:, None]

    def advance(_state, c, hidden, ref, n_emit, write_pos):
        u, c, counts = _module_slots(params, hidden, ref, c, tables, write_pos, cfg,
                                     page_size)
        at = jnp.maximum(n_emit - 1, 0)[:, None, None]
        last = jnp.take_along_axis(u, at, axis=1)                          # [B,1,D]
        with jax.named_scope("mtp"):
            logits = _head(last, params, cfg, params["mtp"]["ln_out"])[:, 0]
        draft = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return None, {**c, "draft": jnp.where(n_emit > 0, draft, c["draft"]),
                      "moe_counts": c["moe_counts"] + counts}

    return SpecDraft(None, propose, advance)


def forward_slots_spec_multi(params: dict, cache: dict, tokens: jax.Array,
                             positions: jax.Array, active: jax.Array, budgets: jax.Array,
                             eos_ids: jax.Array, propose, select_ref, key_tab: jax.Array,
                             history, hist_lens, n_steps: int, spec_k: int, cfg,
                             tables: Optional[jax.Array] = None, page_size: int = 0):
    """``n_steps`` speculative rounds as one scan over the paged latent pool
    (``common.spec_multi_step_decode``: the accept / key-cursor / freeze contract is the
    shared one). Each round's verify is :func:`forward_slots_paged` over ``[B, spec_k +
    1]``. ``propose`` None drafts with the model's own prediction module
    (:func:`_module_draft`, ``spec_k`` = ``cfg.mtp_draft``; ``history`` unused), else
    ``propose(history, hist_lens)`` over the carried token history. Returns ``(cache,
    tok_buf [n_steps, B, spec_k+1], emits [n_steps, B], counts [B], proposed [B],
    accepted [B], model counts int32[3 or 6])`` — the last summed over the rounds, the
    verify's and the module's rows alike."""
    from .common import history_draft, spec_multi_step_decode

    if tables is None:
        raise NotImplementedError("deepseek decodes over the paged latent cache only")
    max_len = cache["valid"].shape[1]
    n_counts = 3 + 3 * cfg.counts_attention

    def forward_verify(c, seq, write_pos):
        logits, new, counts, hidden = _forward_slots(params, seq, c, tables, write_pos, cfg,
                                                     page_size)
        return logits, {**new, "moe_counts": c["moe_counts"] + counts}, hidden

    if propose is None:
        if spec_k != cfg.mtp_draft:
            raise ValueError(f"the prediction module drafts {cfg.mtp_draft} token(s) a "
                             f"round; spec_k={spec_k}")
        draft = _module_draft(params, cfg, tables, page_size)
    else:
        draft = history_draft(propose, history, hist_lens)
    carry = {**cache, "moe_counts": jnp.zeros((n_counts,), jnp.int32)}
    carry, tok_buf, emits, counts, proposed, accepted = spec_multi_step_decode(
        forward_verify, draft, select_ref, carry, tokens, positions, active, budgets,
        eos_ids, key_tab, n_steps, spec_k, max_len)
    moe_counts = carry.pop("moe_counts")
    return carry, tok_buf, emits, counts, proposed, accepted, moe_counts
