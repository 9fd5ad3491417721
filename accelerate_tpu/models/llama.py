"""Llama-family decoder LM — the flagship model (BASELINE.md north star: Llama-3-8B FSDP
fine-tune at ≥0.4 MFU on v5e-256).

The reference framework ships no models (it prepares arbitrary ``transformers`` modules); this
framework ships first-class model families because the TPU-native path needs models whose
**sharding is part of their definition**. Every param leaf here has a matching
``PartitionSpec`` in ``partition_specs()`` implementing the Megatron tensor-parallel layout
(column-parallel up-projections, row-parallel down-projections — the torch-TP plan analog,
reference ``dataclasses.py:1863`` / ``accelerator.py:1545-1554``), composable with fsdp-axis
sharding (``parallel/fsdp.py``) and sequence-axis activation sharding.

Pure-functional: ``init_params(cfg, key) -> pytree``; ``forward(params, tokens, cfg)``.
Attention dispatches to the Pallas flash kernel on TPU (``ops/flash_attention.py``) and a pure
XLA reference path elsewhere (``attn_impl``).
"""

from __future__ import annotations

import dataclasses
import math
from collections import OrderedDict
from functools import partial
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..utils.constants import BATCH_AXES, SEQUENCE_AXIS, TENSOR_AXIS
from .common import cached_prefill_attention as _cached_prefill_attention
from .common import kv_planes as _kv_planes
from .common import paged_attention_dispatch as _paged_attention
from .common import paged_kv_planes as _paged_kv_planes
from .common import quant_kv as _quant_kv
from .common import read_kv as _read_cache
from .common import write_kv as _write_cache
from .common import write_kv_paged as _write_cache_paged

__all__ = [
    "LlamaConfig",
    "init_params",
    "forward",
    "forward_hidden",
    "forward_pp",
    "head_logits",
    "forward_streamed",
    "loss_fn",
    "loss_fn_pp",
    "score",
    "perplexity",
    "packed_target_mask",
    "segment_mask",
    "segment_positions",
    "partition_specs",
    "CONFIGS",
    "init_cache",
    "init_paged_cache",
    "forward_cached",
    "forward_slots",
    "forward_slots_paged",
    "generate",
    "generate_speculative",
    "generate_streamed",
]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    max_seq: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    tie_embeddings: bool = False
    attn_impl: str = "auto"  # "auto" | "flash" | "xla"
    remat: bool = True       # jax.checkpoint each block (activation checkpointing)
    # Remat policy: "full" recomputes everything (min memory), "dots" saves matmul outputs
    # and recomputes only cheap elementwise ops (jax.checkpoint_policies — trades HBM for
    # ~25-30% less recompute FLOPs), "offload" offloads block inputs to host memory.
    remat_policy: str = "full"
    # jax.checkpoint's prevent_cse. None = auto: False under scan_layers (the scan boundary
    # already isolates the block, and prevent_cse's anti-CSE barriers pessimize XLA's
    # scheduling inside it — the standard setting for scanned transformer stacks), True
    # for the unrolled python-loop stack where CSE could defeat rematerialization.
    remat_prevent_cse: Optional[bool] = None
    scan_layers: bool = False  # lax.scan over stacked layer params (fast compile)
    # lax.scan unroll for the layer stack: >1 gives XLA a bigger basic block to overlap
    # DMA with compute across layer boundaries, costing compile time and program size.
    scan_unroll: int = 1
    use_fp8: bool = False    # fp8-quantized projections (ops/fp8.py, the TE-swap analog)
    fp8_format: Optional[str] = None  # None → the process recipe (FP8RecipeKwargs) decides
    # Mixture-of-Experts (Mixtral-style): 0 = dense MLP. Experts shard over the mesh "ep" axis.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # Cross-entropy chunking (memory): compute logits+logsumexp — and, under grad, the
    # chunk's dx and dW in the same pass — per sequence chunk of this many tokens instead
    # of materializing fp32 [B,S,V] logits. 0 = auto (chunk only when S*V is large enough
    # to matter), -1 = never chunk.
    loss_chunk: int = 0
    # "auto": loss_chunk logic above. "fused": ops/fused_xent Pallas kernel — the score
    # tiles never leave VMEM (no [tokens, V] logits in HBM at all, fwd or bwd);
    # single-device (multi-device meshes fall back to auto). "fused_dp": the multi-chip
    # variant — shard_map over the batch axes with a replicated head (for dp/fsdp-batch
    # layouts; needs an active mesh context).
    loss_impl: str = "auto"
    # int8 KV cache (inference): store cached k/v as int8 with a per-(token, kv-head)
    # scale — half the cache bytes of bf16, so decode (an HBM gather over the cache)
    # reads half the bytes and a serving engine fits 2× the slots. Dequantization fuses
    # into the attention einsums; no repeated or fp16 copy ever materializes.
    kv_quant: bool = False
    # Sliding-window attention (Mistral-style): position i attends only (i-window, i].
    # 0 = full causal. The flash kernels SKIP kv tiles outside the band, so long-context
    # compute scales with S·window instead of S². Not composable with the sp attention
    # modes (ring/ulysses/allgather) — those raise.
    sliding_window: int = 0
    # Apply the sliding window to every Nth layer only (Gemma-2 alternates banded and
    # full-attention layers: window_every=2 → even layers banded, odd layers full).
    # >1 requires scan_layers=False (the layers are no longer a uniform scan body).
    window_every: int = 1
    # ---- Gemma-family architectural knobs (all default to llama behavior) ----
    head_dim_override: Optional[int] = None  # per-head dim when != d_model // n_heads
    mlp_act: str = "silu"       # "silu" (SwiGLU) | "gelu" (GeGLU, tanh approximation)
    post_norm: bool = False     # extra RMSNorm on each sublayer OUTPUT before the residual
    norm_plus_one: bool = False  # RMSNorm weight stored zero-centered: out = x̂·(1 + w)
    embed_scale: bool = False   # multiply token embeddings by sqrt(d_model)
    attn_scale: Optional[float] = None  # softmax scale override (query_pre_attn_scalar)
    attn_softcap: float = 0.0   # tanh-cap attention scores (in-kernel on the flash path)
    final_softcap: float = 0.0  # tanh-cap output logits
    # Qwen2-style biases on the q/k/v projections (o/MLP stay bias-free).
    qkv_bias: bool = False
    # RoPE frequency scaling for context extension. "llama3" = the Llama-3.1 scheme
    # (per-band scaling: high-frequency bands kept, low-frequency bands divided by
    # ``rope_scaling_factor``, smooth ramp between) — required to load 3.1+ checkpoints.
    rope_scaling: Optional[str] = None
    rope_scaling_factor: float = 8.0
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max: int = 8192
    # ---- LoRA (the reference's peft-integration analog, TPU-native) ----
    # rank>0 adds frozen-base low-rank adapters on ``lora_targets``: the forward computes
    # x@W + (x@A)@B·(alpha/rank) — the base weight is never materialized in adapted form,
    # so memory stays base + O(rank) and the optimizer (``models.lora.lora_optimizer``)
    # holds state only for adapter leaves. Dense projections only (moe experts excluded).
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_targets: tuple = ("wq", "wk", "wv", "wo")

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or self.d_model // self.n_heads

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads


CONFIGS = {
    "llama3-8b": LlamaConfig(
        vocab_size=128256, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8, d_ff=14336
    ),
    "llama3.1-8b": LlamaConfig(
        vocab_size=128256, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        d_ff=14336, max_seq=131072, rope_scaling="llama3",
    ),
    "llama3-70b": LlamaConfig(
        vocab_size=128256, d_model=8192, n_layers=80, n_heads=64, n_kv_heads=8, d_ff=28672
    ),
    "llama2-7b": LlamaConfig(
        vocab_size=32000, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=32, d_ff=11008,
        rope_theta=10000.0, max_seq=4096,
    ),
    "tiny": LlamaConfig(
        vocab_size=256, d_model=128, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=256,
        max_seq=128, remat=False,
    ),
    "debug": LlamaConfig(
        vocab_size=512, d_model=256, n_layers=4, n_heads=8, n_kv_heads=4, d_ff=512,
        max_seq=512, remat=False,
    ),
    "mistral-7b": LlamaConfig(
        vocab_size=32000, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8, d_ff=14336,
        rope_theta=10000.0, max_seq=32768, sliding_window=4096,
    ),
    "gemma2-9b": LlamaConfig(
        vocab_size=256000, d_model=3584, n_layers=42, n_heads=16, n_kv_heads=8,
        d_ff=14336, head_dim_override=256, rope_theta=10000.0, max_seq=8192,
        tie_embeddings=True, mlp_act="gelu", post_norm=True, norm_plus_one=True,
        embed_scale=True, attn_scale=224.0**-0.5, attn_softcap=50.0, final_softcap=30.0,
        sliding_window=4096, window_every=2, norm_eps=1e-6,
    ),
    "qwen2-7b": LlamaConfig(
        vocab_size=152064, d_model=3584, n_layers=28, n_heads=28, n_kv_heads=4,
        d_ff=18944, rope_theta=1e6, max_seq=32768, qkv_bias=True, norm_eps=1e-6,
    ),
    "mixtral-8x7b": LlamaConfig(
        vocab_size=32000, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8, d_ff=14336,
        rope_theta=1e6, max_seq=32768, moe_experts=8, moe_top_k=2,
    ),
    "moe-tiny": LlamaConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
        max_seq=128, remat=False, moe_experts=4, moe_top_k=2,
    ),
}


# --------------------------------------------------------------------------------- params
def _layer_params(cfg: LlamaConfig, key) -> dict:
    # fold_in (not split) so the base-weight stream is bit-identical with lora off/on.
    lora_key = jax.random.fold_in(key, 0x10A4)
    k = jax.random.split(key, 8)
    D, H, K, hd, F = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff
    s_in = 1.0 / math.sqrt(D)
    s_ff = 1.0 / math.sqrt(F)
    norm_init = jnp.zeros if cfg.norm_plus_one else jnp.ones  # zero-centered Gemma weights
    params = {
        "ln_attn": norm_init((D,), jnp.float32),
        "wq": jax.random.normal(k[0], (D, H * hd), jnp.float32) * s_in,
        "wk": jax.random.normal(k[1], (D, K * hd), jnp.float32) * s_in,
        "wv": jax.random.normal(k[2], (D, K * hd), jnp.float32) * s_in,
        "wo": jax.random.normal(k[3], (H * hd, D), jnp.float32) * s_in,
        "ln_mlp": norm_init((D,), jnp.float32),
    }
    if cfg.post_norm:
        params["ln_attn_post"] = norm_init((D,), jnp.float32)
        params["ln_mlp_post"] = norm_init((D,), jnp.float32)
    if cfg.qkv_bias:
        params["bq"] = jnp.zeros((H * hd,), jnp.float32)
        params["bk"] = jnp.zeros((K * hd,), jnp.float32)
        params["bv"] = jnp.zeros((K * hd,), jnp.float32)
    if cfg.moe_experts > 0:
        E = cfg.moe_experts
        params["moe"] = {
            "w_router": jax.random.normal(k[7], (D, E), jnp.float32) * s_in,
            "w_gate": jax.random.normal(k[4], (E, D, F), jnp.float32) * s_in,
            "w_up": jax.random.normal(k[5], (E, D, F), jnp.float32) * s_in,
            "w_down": jax.random.normal(k[6], (E, F, D), jnp.float32) * s_ff,
        }
    else:
        params.update({
            "w_gate": jax.random.normal(k[4], (D, F), jnp.float32) * s_in,
            "w_up": jax.random.normal(k[5], (D, F), jnp.float32) * s_in,
            "w_down": jax.random.normal(k[6], (F, D), jnp.float32) * s_ff,
        })
    if cfg.lora_rank > 0:
        r = cfg.lora_rank
        for i, name in enumerate(_lora_target_names(cfg)):
            d_in, d_out = params[name].shape
            # Standard LoRA init: A ~ N(0, 1/d_in), B = 0 → the adapted forward starts
            # exactly equal to the base model.
            params[f"{name}_lora_a"] = (
                jax.random.normal(jax.random.fold_in(lora_key, i), (d_in, r), jnp.float32)
                / math.sqrt(d_in)
            )
            params[f"{name}_lora_b"] = jnp.zeros((r, d_out), jnp.float32)
    return params


def _lora_target_names(cfg: LlamaConfig) -> tuple:
    """The subset of ``cfg.lora_targets`` that exists as dense projections."""
    dense = {"wq", "wk", "wv", "wo"} | (set() if cfg.moe_experts > 0 else {"w_gate", "w_up", "w_down"})
    unknown = set(cfg.lora_targets) - {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}
    if unknown:
        raise ValueError(f"lora_targets {sorted(unknown)} are not dense projection names")
    return tuple(t for t in cfg.lora_targets if t in dense)


def init_params(cfg: LlamaConfig, key: Optional[jax.Array] = None) -> dict:
    if key is None:
        key = jax.random.PRNGKey(0)  # graftlint: disable=rng-key-reuse(deterministic default init; callers pass a key for real entropy)
    keys = jax.random.split(key, cfg.n_layers + 2)
    scale = 1.0 / math.sqrt(cfg.d_model)
    params = {
        "embed": jax.random.normal(keys[0], (cfg.vocab_size, cfg.d_model), jnp.float32) * scale,
        "layers": [_layer_params(cfg, keys[i + 1]) for i in range(cfg.n_layers)],
        "ln_f": (jnp.zeros if cfg.norm_plus_one else jnp.ones)((cfg.d_model,), jnp.float32),
    }
    if cfg.scan_layers:
        params["layers"] = jax.tree_util.tree_map(
            lambda *ls: jnp.stack(ls), *params["layers"]
        )
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(keys[-1], (cfg.d_model, cfg.vocab_size), jnp.float32) * scale
        )
    return params


def partition_specs(cfg: LlamaConfig, pp: bool = False, virtual_stages: int = 1) -> dict:
    """Megatron-layout PartitionSpecs, same structure as the params pytree.

    Column-parallel: wq/wk/wv/w_gate/w_up split their output dim over ``tp``.
    Row-parallel: wo/w_down split their input dim over ``tp`` (GSPMD inserts the psum).
    Embedding/lm_head shard the vocab dim (logits stay tp-sharded until the loss psum).

    ``pp=True``: layer params are stage-stacked ``[n_stages, L/n_stages, ...]``
    (``parallel.pp.split_params_into_stages``) with the stage dim sharded over ``pp`` — each
    pipeline stage holds only its own blocks. Embed/ln_f/head stay outside the pipeline
    (replicated over pp; the reference pins them to first/last rank instead —
    ``inference.py:164-168`` — but under GSPMD replicating the cheap ends costs less than the
    extra transfer ticks).
    """
    layer = {
        "ln_attn": P(),
        "wq": P(None, TENSOR_AXIS),
        "wk": P(None, TENSOR_AXIS),
        "wv": P(None, TENSOR_AXIS),
        "wo": P(TENSOR_AXIS, None),
        "ln_mlp": P(),
    }
    if cfg.post_norm:
        layer["ln_attn_post"] = P()
        layer["ln_mlp_post"] = P()
    if cfg.qkv_bias:
        layer["bq"] = P(TENSOR_AXIS)
        layer["bk"] = P(TENSOR_AXIS)
        layer["bv"] = P(TENSOR_AXIS)
    if cfg.moe_experts > 0:
        from ..ops.moe import expert_partition_specs

        layer["moe"] = expert_partition_specs()
    else:
        layer.update({
            "w_gate": P(None, TENSOR_AXIS),
            "w_up": P(None, TENSOR_AXIS),
            "w_down": P(TENSOR_AXIS, None),
        })
    if cfg.lora_rank > 0:
        for name in _lora_target_names(cfg):
            base = layer[name]
            # A inherits the base's INPUT-dim placement, B its OUTPUT-dim placement, so the
            # low-rank path reads the same activation shardings as the base matmul (and the
            # rank dim — tiny — stays unsharded).
            layer[f"{name}_lora_a"] = P(base[0], None)
            layer[f"{name}_lora_b"] = P(None, base[1])
    if pp:
        if not cfg.scan_layers:
            raise ValueError("pipeline parallelism requires cfg.scan_layers=True")
        from ..utils.constants import PIPELINE_AXIS

        # virtual_stages > 1 → interleaved layout [v, n_stages, L/(n·v), ...]: the pp
        # axis on dim 1 so device s hosts the STRIDED virtual stages (see
        # split_params_into_stages).
        from ..parallel.pp import stage_spec_prefix

        layer = jax.tree_util.tree_map(
            lambda spec: P(*stage_spec_prefix(virtual_stages), *spec),
            layer,
            is_leaf=lambda s: isinstance(s, P),
        )
        layers: Any = layer
    elif cfg.scan_layers:
        # Leading stacked-layer dim on every leaf spec (handles the nested moe subtree).
        layer = jax.tree_util.tree_map(
            lambda spec: P(None, *spec), layer, is_leaf=lambda s: isinstance(s, P)
        )
        layers = layer
    else:
        layers = [dict(layer) for _ in range(cfg.n_layers)]
    from ..utils.constants import FSDP_AXIS

    # Vocab dim sharded over (tp, fsdp) together: Megatron vocab-parallel embedding composed
    # with ZeRO-3 memory sharding on the SAME dim. Sharding d_model instead (what fsdp
    # auto-composition would pick) makes the token-lookup gather unshardable — XLA's SPMD
    # partitioner falls back to "involuntary full rematerialization" (replicate + repartition)
    # on every embedding lookup under a dp×fsdp×tp×sp mesh.
    # Under pp, fold the pipeline axis into the same vocab sharding: embed/head sit
    # OUTSIDE the pipeline (every stage runs them), and replicating the untied head costs
    # ~1 GB/device at 8B scale — vocab-sharding over pp makes them cost HBM like one
    # shard, with GSPMD inserting the gather/psum at the lookup / logits matmul.
    vocab_axes = (TENSOR_AXIS, FSDP_AXIS, PIPELINE_AXIS) if pp else (TENSOR_AXIS, FSDP_AXIS)
    specs = {
        "embed": P(vocab_axes, None),
        "layers": layers,
        "ln_f": P(),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, vocab_axes)
    return specs


# -------------------------------------------------------------------------------- forward
def _maybe_shard(x: jax.Array, spec: P) -> jax.Array:
    from ..ops.collectives import maybe_shard

    return maybe_shard(x, spec)


def _rms_norm(x: jax.Array, gamma: jax.Array, eps: float, plus_one: bool = False) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    normed = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    g = gamma.astype(jnp.float32)
    if plus_one:  # Gemma convention: weights stored zero-centered
        g = g + 1.0
    return (normed * g).astype(x.dtype)


def _rope_freqs(cfg: LlamaConfig, hd: int) -> jax.Array:
    """Per-band inverse wavelengths, with optional Llama-3.1 context-extension scaling."""
    freqs = 1.0 / (cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    if cfg.rope_scaling is None:
        return freqs
    if cfg.rope_scaling != "llama3":
        raise ValueError(f"rope_scaling={cfg.rope_scaling!r}: expected None or 'llama3'")
    factor = cfg.rope_scaling_factor
    low_wl = cfg.rope_original_max / cfg.rope_low_freq_factor
    high_wl = cfg.rope_original_max / cfg.rope_high_freq_factor
    wavelen = 2.0 * math.pi / freqs
    smooth = (cfg.rope_original_max / wavelen - cfg.rope_low_freq_factor) / (
        cfg.rope_high_freq_factor - cfg.rope_low_freq_factor
    )
    scaled = jnp.where(
        wavelen > low_wl,
        freqs / factor,  # long-wavelength (low-freq) bands: fully scaled
        jnp.where(
            wavelen < high_wl,
            freqs,  # short-wavelength bands: untouched
            (1.0 - smooth) * freqs / factor + smooth * freqs,  # smooth ramp between
        ),
    )
    return scaled


def _rope(x: jax.Array, positions: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """Rotary embedding: x [B, S, H, hd], positions [B, S]."""
    hd = x.shape[-1]
    freqs = _rope_freqs(cfg, hd)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, hd/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _sm_scale(cfg: LlamaConfig) -> float:
    """Softmax scale: 1/sqrt(head_dim) unless the config overrides it (Gemma-2's
    query_pre_attn_scalar)."""
    return cfg.attn_scale if cfg.attn_scale is not None else 1.0 / math.sqrt(cfg.head_dim)


def _softcap(scores: jax.Array, cap: float) -> jax.Array:
    """Gemma-style logit capping: cap·tanh(x/cap) (identity when cap == 0)."""
    return cap * jnp.tanh(scores / cap) if cap else scores


def _attention_xla(q, k, v, mask, cfg: LlamaConfig):
    """Reference attention path: q [B,S,H,hd], kv [B,S,K,hd] → [B,S,H,hd].

    GQA stays grouped: q reshapes to [B,S,K,G,hd] and both einsums contract against the
    UNREPEATED kv — the repeated K/V tensors never materialize."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k) * _sm_scale(cfg)
    scores = _softcap(scores, cfg.attn_softcap)
    scores = jnp.where(mask[:, None, None, :, :], scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bkgst,btkd->bskgd", probs, v).reshape(B, S, H, hd)


def _attention(q, k, v, mask, cfg: LlamaConfig, segment_ids=None):
    """Family attention via the shared dispatcher (``common.attention_dispatch``):
    sliding windows, Gemma score capping, packing, and the sp modes all flow through;
    the XLA fallback keeps llama's grouped-GQA einsum."""
    from .common import attention_dispatch

    return attention_dispatch(
        q, k, v, mask, impl=cfg.attn_impl, sm_scale=_sm_scale(cfg),
        window=cfg.sliding_window, softcap=cfg.attn_softcap, segment_ids=segment_ids,
        xla_attention=lambda q, k, v, m: _attention_xla(q, k, v, m, cfg),
    )


def _proj(h, w, cfg: LlamaConfig):
    """Projection matmul: plain bf16, fp8-quantized (cfg.use_fp8, the TE-swap analog), or a
    fused dequant-matmul when the weight leaf is int8/int4-quantized (the bnb-swap analog)."""
    from ..ops.quantization import QuantizedWeight, quant_matmul

    if isinstance(w, QuantizedWeight):
        return quant_matmul(h, w, out_dtype=cfg.dtype)
    if cfg.use_fp8:
        from ..ops.fp8 import fp8_dot

        return fp8_dot(h, w, cfg.fp8_format)
    return h @ w.astype(cfg.dtype)


def _proj_l(h, layer, name, cfg: LlamaConfig):
    """``_proj`` + the layer's LoRA delta when adapters exist for ``name``.

    The delta is computed low-rank — ``(h @ A) @ B`` — never as a materialized ``W + AB``,
    so adapted training costs base-weights + O(rank) memory (``models/lora.py``).
    """
    out = _proj(h, layer[name], cfg)
    if cfg.lora_rank > 0 and f"{name}_lora_a" in layer:
        a = layer[f"{name}_lora_a"].astype(cfg.dtype)
        b = layer[f"{name}_lora_b"].astype(cfg.dtype)
        out = out + ((h @ a) @ b) * (cfg.lora_alpha / cfg.lora_rank)
    return out


def _mlp_gate_act(h: jax.Array, cfg: LlamaConfig) -> jax.Array:
    if cfg.mlp_act == "silu":
        return jax.nn.silu(h)
    if cfg.mlp_act == "gelu":  # GeGLU (tanh approximation — Gemma convention)
        return jax.nn.gelu(h, approximate=True)
    raise ValueError(f"mlp_act={cfg.mlp_act!r}: expected 'silu' or 'gelu'")


#: The projections :func:`decode_layout` stores as ``{name}_t`` = ``[..., out, in]``.
_DECODE_LAYOUT = ("wq", "wk", "wv")


def _qkv_proj(h, layer, cfg: LlamaConfig):
    """q/k/v projections (+ Qwen2-style biases when ``cfg.qkv_bias``). A projection the
    tree holds as ``{name}_t`` (:func:`decode_layout`) contracts ``h`` against its LAST
    axis; every other goes through ``_proj_l`` as training stores it."""

    def proj(name):
        w_t = layer.get(f"{name}_t")
        if w_t is None:
            return _proj_l(h, layer, name, cfg)
        return jax.lax.dot_general(h, w_t.astype(cfg.dtype), (((h.ndim - 1,), (1,)), ((), ())))

    q, k, v = (proj(name) for name in _DECODE_LAYOUT)
    if cfg.qkv_bias:
        q = q + layer["bq"].astype(q.dtype)
        k = k + layer["bk"].astype(k.dtype)
        v = v + layer["bv"].astype(v.dtype)
    return q, k, v


def _block(x, layer, positions, mask, cfg: LlamaConfig, segment_ids=None):
    """One transformer block → (x, moe_aux_loss) (aux is 0.0 for dense MLPs)."""
    B, S, D = x.shape
    p1 = cfg.norm_plus_one
    with jax.named_scope("attn"):
        h = _rms_norm(x, layer["ln_attn"], cfg.norm_eps, p1)
        q, k, v = _qkv_proj(h, layer, cfg)
        q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
        k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
        v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
        q = _rope(q, positions, cfg)
        k = _rope(k, positions, cfg)
        attn = _attention(q, k, v, mask, cfg, segment_ids).reshape(
            B, S, cfg.n_heads * cfg.head_dim
        )
        attn_out = _proj_l(attn, layer, "wo", cfg)
        if cfg.post_norm:  # Gemma-2: normalize the sublayer OUTPUT before the residual add
            attn_out = _rms_norm(attn_out, layer["ln_attn_post"], cfg.norm_eps, p1)
        x = x + attn_out
    with jax.named_scope("mlp"):
        h = _rms_norm(x, layer["ln_mlp"], cfg.norm_eps, p1)
        if cfg.moe_experts > 0:
            from ..ops.moe import moe_mlp

            y, aux = moe_mlp(
                h, layer["moe"], layer["moe"]["w_router"],
                top_k=cfg.moe_top_k, capacity_factor=cfg.moe_capacity_factor,
                compute_dtype=cfg.dtype,
                # Packing: pad slots neither claim expert capacity nor bias the aux stat.
                token_mask=None if segment_ids is None else (segment_ids != 0),
            )
            return x + y, aux
        gate = _mlp_gate_act(_proj_l(h, layer, "w_gate", cfg), cfg)
        up = _proj_l(h, layer, "w_up", cfg)
        mlp_out = _proj_l(gate * up, layer, "w_down", cfg)
        if cfg.post_norm:
            mlp_out = _rms_norm(mlp_out, layer["ln_mlp_post"], cfg.norm_eps, p1)
        x = x + mlp_out
    return x, jnp.zeros((), jnp.float32)


def _maybe_remat_block(cfg: LlamaConfig):
    """The block fn under the config's activation-checkpointing policy (validated)."""
    from .common import remat_wrap

    return remat_wrap(
        _block, remat=cfg.remat, policy=cfg.remat_policy,
        prevent_cse=cfg.remat_prevent_cse, scan_layers=cfg.scan_layers,
        static_argnums=(4,),
    )


def packed_target_mask(segment_ids: jax.Array) -> jax.Array:
    """Float mask [B, S-1] of valid next-token targets in packed rows: position t's target
    (slot t+1) counts only when it continues the SAME segment and is not padding."""
    seg = segment_ids
    return ((seg[:, 1:] == seg[:, :-1]) & (seg[:, 1:] != 0)).astype(jnp.float32)


def segment_positions(segment_ids: jax.Array) -> jax.Array:
    """Per-segment 0-based positions [B, S] from contiguous ``segment_ids`` (packed rows):
    position = index - index_of_segment_start."""
    B, S = segment_ids.shape
    idx = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    change = jnp.concatenate(
        [jnp.ones((B, 1), bool), segment_ids[:, 1:] != segment_ids[:, :-1]], axis=1
    )
    starts = jax.lax.associative_scan(jnp.maximum, jnp.where(change, idx, 0), axis=1)
    return jnp.where(segment_ids != 0, idx - starts, 0)


def segment_mask(segment_ids: jax.Array) -> jax.Array:
    """Packed-row attention mask [B, S, S]: causal AND same-segment AND not padding.

    ``segment_ids`` [B, S] as produced by ``ops.packing.pack_sequences`` (0 = pad,
    1..k = packed sequences). Sequences in one row cannot attend to each other.
    """
    S = segment_ids.shape[1]
    causal = jnp.tril(jnp.ones((S, S), dtype=jnp.bool_))[None]
    same = segment_ids[:, :, None] == segment_ids[:, None, :]
    live = (segment_ids != 0)[:, None, :]
    return causal & same & live


def forward_hidden(
    params: dict,
    tokens: jax.Array,
    cfg: LlamaConfig,
    positions: Optional[jax.Array] = None,
    shard_activations: bool = True,
    segment_ids: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """Backbone: tokens [B, S] → (final hidden states [B, S, D] after ln_f, MoE aux loss).

    Activation sharding constraints pin the batch dim to ``(dp, fsdp)`` and the sequence dim
    to ``sp`` so GSPMD propagates a consistent layout through every block (naive sequence
    parallelism; ring attention in ``ops/ring_attention.py`` upgrades the attention part).

    ``segment_ids`` (sample packing, ``ops/packing.py``): attention is restricted to the
    block-diagonal per-segment causal mask — in-kernel on the flash path, via the explicit
    mask on the XLA path — and positions default to per-segment RoPE restarts (derived from
    the segment ids when not given). The sequence-parallel modes take no mask and fall back.
    """
    B, S = tokens.shape
    dtype = cfg.dtype
    if positions is None:
        positions = (
            # Continuous arange positions would silently run RoPE across segment boundaries.
            segment_positions(segment_ids)
            if segment_ids is not None
            else jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        )
    with jax.named_scope("embed"):
        x = params["embed"].astype(dtype)[tokens]
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)
    if shard_activations:
        x = _maybe_shard(x, P(BATCH_AXES, SEQUENCE_AXIS, None))
    if segment_ids is not None:
        # Packing composes with every attention impl: flash takes segment ids IN-KERNEL,
        # xla takes the block-diagonal mask, and the sp modes shard the ids over the sp
        # axis (ring rotates the kv-side slice with its kv block).
        mask = segment_mask(segment_ids)
    else:
        mask = jnp.tril(jnp.ones((S, S), dtype=jnp.bool_))[None, :, :]
    full_mask = mask
    if cfg.sliding_window:
        # Band-limit the XLA-path mask to (i-window, i]; the flash kernels apply the same
        # band in-kernel (and skip out-of-band tiles entirely).
        idx = jnp.arange(S, dtype=jnp.int32)
        mask = mask & (idx[None, :] > idx[:, None] - cfg.sliding_window)[None]

    block = _maybe_remat_block(cfg)

    aux_total = jnp.zeros((), jnp.float32)
    alternating = bool(cfg.sliding_window) and cfg.window_every > 1
    if cfg.scan_layers and alternating:
        # Gemma-2 style alternation under scan: group ``window_every`` consecutive layers
        # into one scan body (layer j of a group is banded iff j == 0 — global index
        # g·per + j keeps j's parity). Compile time stays O(group), not O(L).
        per = cfg.window_every
        if cfg.n_layers % per:
            raise ValueError(
                f"window_every={per} must divide n_layers={cfg.n_layers} under scan_layers"
            )
        full_cfg = dataclasses.replace(cfg, sliding_window=0)
        grouped = jax.tree_util.tree_map(
            lambda a: a.reshape(cfg.n_layers // per, per, *a.shape[1:]), params["layers"]
        )

        def scan_body(carry, group):
            out = carry
            aux_g = jnp.zeros((), jnp.float32)
            for j in range(per):
                layer_j = jax.tree_util.tree_map(lambda a, j=j: a[j], group)
                banded = j == 0
                out, aux_j = block(
                    out, layer_j, positions,
                    mask if banded else full_mask,
                    cfg if banded else full_cfg,
                    segment_ids,
                )
                if shard_activations:
                    out = _maybe_shard(out, P(BATCH_AXES, SEQUENCE_AXIS, None))
                aux_g = aux_g + aux_j
            return out, aux_g

        x, auxes = jax.lax.scan(scan_body, x, grouped, unroll=cfg.scan_unroll)
        aux_total = jnp.sum(auxes)
    elif cfg.scan_layers:
        def scan_body(carry, layer):
            out, aux = block(carry, layer, positions, mask, cfg, segment_ids)
            if shard_activations:
                out = _maybe_shard(out, P(BATCH_AXES, SEQUENCE_AXIS, None))
            return out, aux

        x, auxes = jax.lax.scan(scan_body, x, params["layers"], unroll=cfg.scan_unroll)
        aux_total = jnp.sum(auxes)
    else:
        full_cfg = dataclasses.replace(cfg, sliding_window=0)
        for i, layer in enumerate(params["layers"]):
            banded = cfg.sliding_window and i % cfg.window_every == 0
            x, aux = block(
                x, layer, positions,
                mask if banded else full_mask,
                cfg if banded else full_cfg,
                segment_ids,
            )
            aux_total = aux_total + aux
            if shard_activations:
                x = _maybe_shard(x, P(BATCH_AXES, SEQUENCE_AXIS, None))
    x = _rms_norm(x, params["ln_f"], cfg.norm_eps, cfg.norm_plus_one)
    return x, aux_total


def forward(
    params: dict,
    tokens: jax.Array,
    cfg: LlamaConfig,
    positions: Optional[jax.Array] = None,
    shard_activations: bool = True,
    return_aux: bool = False,
):
    """Causal LM: tokens [B, S] → logits [B, S, V] (fp32); with ``return_aux``, also the summed
    MoE load-balancing loss."""
    x, aux_total = forward_hidden(params, tokens, cfg, positions, shard_activations)
    logits = head_logits(x, params, cfg)
    if return_aux:
        return logits, aux_total
    return logits


def head_logits(x, params: dict, cfg: LlamaConfig) -> jax.Array:
    """Final-hidden → fp32 logits, incl. the Gemma final softcap — part of the model
    family's pipeline contract (``inference.prepare_pippy`` calls it per family)."""
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x @ head.astype(cfg.dtype)).astype(jnp.float32)
    return _softcap(logits, cfg.final_softcap)


def _loss_chunk_size(cfg: LlamaConfig, S: int) -> int:
    """Resolve the chunked-CE chunk length for this config (see
    ``common.resolve_loss_chunk`` — the shared single copy of the auto rule)."""
    from .common import resolve_loss_chunk

    return resolve_loss_chunk(cfg.loss_chunk, S, cfg.vocab_size)


def _chunked_ce(x, head, targets, mask, chunk: int, dtype, final_softcap: float = 0.0):
    """Memory-efficient chunked CE (moved to ``common.chunked_ce``; kept as the
    family-local name for callers like ``benchmarks/decompose.py``)."""
    from .common import chunked_ce

    return chunked_ce(x, head, targets, mask, chunk, dtype, final_softcap=final_softcap)


def _ce_from_hidden(x, params, targets, mask, cfg: LlamaConfig) -> jax.Array:
    """Cross-entropy from post-ln_f hidden states (chunked when ``cfg.loss_chunk`` says so)."""
    with jax.named_scope("head_ce"):
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        denom = jnp.maximum(mask.sum(), 1.0)
        return _ce_sum_impl(x, head, targets, mask, cfg) / denom


def _ce_sum_impl(x, head, targets, mask, cfg: LlamaConfig) -> jax.Array:
    """SUM-style CE dispatcher for this family — delegates to the cross-family
    ``common.ce_sum_dispatch`` (the ONE place every loss_impl routes through), used by
    both the normalized single/GPipe path (``_ce_from_hidden``) and the 1F1B head
    (``_head_ce_sum``, where sums across microbatch groups must add up exactly)."""
    from .common import ce_sum_dispatch

    return ce_sum_dispatch(
        x, head, targets, mask, loss_impl=cfg.loss_impl, dtype=cfg.dtype,
        chunk=_loss_chunk_size(cfg, x.shape[1]), softcap=cfg.final_softcap,
    )


def loss_fn(
    params: dict,
    batch: dict,
    cfg: LlamaConfig,
    rng: Optional[jax.Array] = None,
) -> jax.Array:
    """Next-token cross-entropy over batch {'tokens': [B, S+1]} with optional 'mask'.

    Large-vocab models use the chunked-CE path (``cfg.loss_chunk``): the reference's torch
    loop materializes full fp32 logits, which alone OOMs a 16 GB chip at B8/S2048/V32k.
    """
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    B, S = inputs.shape
    if "segment_ids" in batch:
        # Packed rows (ops/packing.py): a position's next-token target is valid only
        # when the next slot continues the SAME segment (never across a boundary or
        # into padding), and attention/positions are per-segment.
        seg = batch["segment_ids"]
        mask = packed_target_mask(seg)
        if "mask" in batch:
            mask = mask * batch["mask"][:, 1:].astype(jnp.float32)
        positions = (
            batch["positions"][:, :-1]
            if "positions" in batch
            # Without explicit positions, derive them — continuous arange positions would
            # silently run RoPE across segment boundaries.
            else segment_positions(seg[:, :-1])
        )
        x, aux = forward_hidden(
            params, inputs, cfg, positions=positions, segment_ids=seg[:, :-1]
        )
    else:
        mask = (
            batch["mask"][:, 1:].astype(jnp.float32)
            if "mask" in batch
            else jnp.ones((B, S), jnp.float32)
        )
        x, aux = forward_hidden(params, inputs, cfg)
    ce = _ce_from_hidden(x, params, targets, mask, cfg)
    if cfg.moe_experts > 0:
        return ce + cfg.moe_aux_weight * aux
    return ce


def score(
    params: dict,
    tokens: jax.Array,
    cfg: LlamaConfig,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Per-token log-probabilities log p(token[t+1] | tokens[:t+1]) → [B, S-1] fp32.

    The evaluation companion to ``loss_fn`` (which returns their masked mean negated):
    use for perplexity, answer scoring, or re-ranking. ``mask`` [B, S] marks real tokens
    (False on pads); masked target positions score 0.0.
    """
    tokens = jnp.asarray(tokens, jnp.int32)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits = forward(params, inputs, cfg, shard_activations=False)  # final_softcap applied
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1).squeeze(-1)
    if mask is not None:
        ll = ll * mask[:, 1:].astype(ll.dtype)
    return ll


def perplexity(
    params: dict,
    tokens: jax.Array,
    cfg: LlamaConfig,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """exp(mean negative log-likelihood over real target positions) — scalar fp32."""
    ll = score(params, tokens, cfg, mask)
    if mask is not None:
        denom = jnp.maximum(mask[:, 1:].sum(), 1)
    else:
        denom = ll.size
    return jnp.exp(-ll.sum() / denom)


# --------------------------------------------------------------- pipeline-parallel training
def _pp_microbatches(mesh, num_microbatches) -> int:
    """Resolve M (None → n_stages, make_pipeline_fn's default) — the ONE copy of the
    default both forward_pp's aux normalization and loss_fn_pp's 1f1b aux_weight use,
    so GPipe and 1F1B cannot drift to differently-scaled MoE aux objectives."""
    from ..utils.constants import PIPELINE_AXIS as _PP

    return num_microbatches if num_microbatches is not None else mesh.shape[_PP]


def _pp_stage_fn(
    cfg: LlamaConfig, S: int, with_aux: bool, packed: bool = False,
    sp_manual: bool = False,
):
    """One pipeline stage body, shared by the GPipe (forward_pp) and 1F1B (loss_fn_pp)
    schedules so their numerics cannot drift: scan this stage's blocks over one
    microbatch [B_m, S, D], positions/causal mask rebuilt locally (identical rows).
    ``with_aux`` returns the stage's summed MoE aux alongside the activation.

    ``packed`` (sample packing): the stage takes a third ``side`` argument — the
    pipeline's per-microbatch constants ``{"positions", "segment_ids"}`` [B_m, S]
    (``parallel.pp``'s side-input contract: indexed by microbatch id inside the
    schedule, never ppermuted, non-differentiable) — and restricts attention to the
    block-diagonal per-segment causal mask exactly like ``forward_hidden``."""
    block = _maybe_remat_block(cfg)

    def body_scan(x, stage_layers, pos, mask, seg):
        def body(carry, layer):
            out, aux = block(carry, layer, pos, mask, cfg, seg)
            return out, aux

        out, auxes = jax.lax.scan(body, x, stage_layers)
        if with_aux:
            return out, jnp.sum(auxes)
        return out

    if packed and sp_manual:
        # packing × sp × pp: activations AND the side constants arrive sequence-sliced
        # ([B_m, S/sp, D] and [B_m, S/sp] — loss_fn_pp passes the matching side_spec).
        # Positions are the pre-computed per-segment RoPE restarts (global array,
        # sliced); attention dispatches to the flat ring/ulysses collectives inside
        # _attention with the LOCAL segment slice (ring rotates the kv-side ids).
        def stage_fn(stage_layers, x, side):
            return body_scan(
                x, stage_layers, side["positions"], None, side["segment_ids"]
            )

        return stage_fn

    if packed:
        def stage_fn(stage_layers, x, side):
            seg = side["segment_ids"]
            return body_scan(x, stage_layers, side["positions"], segment_mask(seg), seg)

        return stage_fn

    if sp_manual:
        # sp×pp: the pipeline's shard_map is manual over sp too, so x arrives
        # SEQUENCE-SLICED [B_m, S/sp, D]. RoPE needs the slice's global positions;
        # attention dispatches to the flat ring/ulysses collectives inside _attention
        # (no mask — the sp kernels handle causality with global offsets in-kernel).
        def stage_fn(stage_layers, x):
            S_loc = x.shape[1]
            offs = jax.lax.axis_index(SEQUENCE_AXIS) * S_loc
            pos = jnp.broadcast_to(
                offs + jnp.arange(S_loc, dtype=jnp.int32), (x.shape[0], S_loc)
            )
            return body_scan(x, stage_layers, pos, None, None)

        return stage_fn

    def stage_fn(stage_layers, x):
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (x.shape[0], S))
        mask = jnp.tril(jnp.ones((S, S), dtype=jnp.bool_))[None, :, :]
        return body_scan(x, stage_layers, pos, mask, None)

    return stage_fn


def forward_pp(
    params: dict,
    tokens: jax.Array,
    cfg: LlamaConfig,
    mesh,
    num_microbatches: Optional[int] = None,
    shard_activations: bool = True,
    return_aux: bool = False,
    segment_ids: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
):
    """Causal LM forward with the transformer blocks run as a GPipe pipeline over ``pp``.

    ``params["layers"]`` must be stage-stacked ``[n_stages, L/n, ...]`` (scan_layers params
    through ``parallel.pp.split_params_into_stages``; specs from ``partition_specs(cfg,
    pp=True)``). Embed and head run outside the pipeline on every device (vocab-dim sharded
    over pp×tp by ``partition_specs(pp=True)`` so they cost HBM like one shard, not one
    replica). The whole schedule is one differentiable scan, so the same function trains —
    unlike the reference, whose pipelining is inference-only (``inference.py:82-121``).

    MoE configs run through the pipeline too (the reference's engine runs MoE models,
    ``/root/reference/src/accelerate/utils/dataclasses.py:1105``): the expert dispatch
    lives inside the stage body with ``ep``/``tp`` left to GSPMD (the pp shard_map is
    manual over ``pp`` only), and per-(stage, microbatch) load-balancing aux losses are
    masked to real ticks and summed across the pipeline. Routing/capacity are
    per-microbatch, so MoE aux/dropping match a non-pipelined run only in the no-drop
    regime (capacity_factor high enough) — same caveat as any GPipe MoE.
    Returns hidden states [B, S, D]; MoE aux is returned when ``return_aux``.
    """
    from ..parallel.pp import make_pipeline_fn

    B, S = tokens.shape
    dtype = cfg.dtype
    is_moe = cfg.moe_experts > 0
    packed = segment_ids is not None
    stage_fn = _pp_stage_fn(cfg, S, with_aux=is_moe, packed=packed)
    side = None
    if packed:
        if positions is None:
            positions = segment_positions(segment_ids)
        side = {"positions": positions, "segment_ids": segment_ids}

    x = params["embed"].astype(dtype)[tokens]
    if shard_activations:
        x = _maybe_shard(x, P(BATCH_AXES, None, None))
    pipe = make_pipeline_fn(
        mesh, stage_fn, num_microbatches=num_microbatches, with_aux=is_moe
    )
    if is_moe:
        x, aux = pipe(params["layers"], x, side=side)
        # load_balancing_loss is a batch-size-invariant MEAN statistic (~1 at balance):
        # the pipeline sums one value per (stage, microbatch), so divide by M to keep
        # moe_aux_weight meaning the same thing as the non-pipelined path — otherwise
        # retuning num_microbatches (a throughput knob) would silently rescale the
        # training objective.
        aux = aux / _pp_microbatches(mesh, num_microbatches)
    else:
        x, aux = pipe(params["layers"], x, side=side), jnp.zeros((), jnp.float32)
    x = _rms_norm(x, params["ln_f"], cfg.norm_eps, cfg.norm_plus_one)
    if return_aux:
        return x, aux
    return x


def _head_ce_sum(hp: dict, y: jax.Array, ex: dict, cfg: LlamaConfig) -> jax.Array:
    """SUM-style ln_f + CE head over one microbatch (the 1F1B last-stage loss):
    ``hp = {"ln_f", "head" [D, V]}``, ``ex = {"targets", "mask"}``. Sums across
    microbatches add up to the full-batch numerator; normalization stays outside.
    Delegates to ``_ce_sum_impl`` so the CE math (including the fused kernel variants)
    cannot drift from the GPipe/sequential paths."""
    x = _rms_norm(y, hp["ln_f"], cfg.norm_eps, cfg.norm_plus_one)
    return _ce_sum_impl(x, hp["head"], ex["targets"], ex["mask"], cfg)


def loss_fn_pp(
    params: dict,
    batch: dict,
    cfg: LlamaConfig,
    mesh,
    num_microbatches: Optional[int] = None,
    rng: Optional[jax.Array] = None,
    schedule: str = "gpipe",
    virtual_stages: int = 1,
) -> jax.Array:
    """Pipeline-parallel next-token cross-entropy (same contract as ``loss_fn``,
    including sample packing: ``segment_ids`` ride the pipeline as per-microbatch side
    constants — ``parallel.pp``'s side-input contract — restricting attention to the
    block-diagonal per-segment mask with per-segment RoPE restarts, both schedules).

    ``virtual_stages=v > 1`` (interleaved virtual pipeline, 1f1b only): layers in the
    ``split_params_into_stages(..., virtual_stages=v)`` layout with specs from
    ``partition_specs(pp=True, virtual_stages=v)`` — the bubble amortizes ≈ v×.

    ``schedule="1f1b"`` routes through ``parallel.pp.make_pipeline_loss_fn``: the custom
    VJP's hand-scheduled one-forward-one-backward keeps in-flight activations bounded by
    the stage count instead of ``num_microbatches``. ln_f + the CE head run OUTSIDE the
    pipeline on the full batch (ordinary GSPMD — every ``loss_impl`` incl. the fused
    kernels works); MoE stages carry their load-balancing aux through the replay with
    the same /num_microbatches normalization as GPipe."""
    if schedule not in ("gpipe", "1f1b"):
        # Mirrors PipelineParallelPlugin's validation: an unrecognized schedule (e.g. a
        # typo'd ACCELERATE_PP_SCHEDULE) must not silently run GPipe.
        raise ValueError(f"schedule={schedule!r}: expected 'gpipe' or '1f1b'")
    # sp×pp: family-shared routing (see common.resolve_sp_pipeline for
    # the full rationale + the ulysses→ppermute substitution under 1f1b). MoE composes
    # too: each sp member routes/dispatches its OWN sequence slice (per-slice capacity —
    # exact parity in the no-drop regime, the standard MoE-under-resharding caveat) and
    # the aux statistic is psum-meaned over sp.
    from .common import resolve_sp_pipeline

    sp_pipeline, cfg = resolve_sp_pipeline(cfg, mesh, schedule, virtual_stages)
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    B, S = inputs.shape
    if "segment_ids" in batch:
        # Packed rows — same target-mask / per-segment-position semantics as loss_fn.
        seg = batch["segment_ids"]
        mask = packed_target_mask(seg)
        if "mask" in batch:
            mask = mask * batch["mask"][:, 1:].astype(jnp.float32)
        positions = (
            batch["positions"][:, :-1]
            if "positions" in batch
            else segment_positions(seg[:, :-1])
        )
        seg_in = seg[:, :-1]
        side = {"positions": positions, "segment_ids": seg_in}
    else:
        mask = (
            batch["mask"][:, 1:].astype(jnp.float32)
            if "mask" in batch
            else jnp.ones((B, S), jnp.float32)
        )
        seg_in = None
        side = None
    if virtual_stages > 1 and schedule != "1f1b":
        # (packing, sp-in-pp, and MoE all compose with virtual stages — only the
        # schedule restriction remains.)
        raise NotImplementedError(
            "virtual_stages > 1 requires schedule='1f1b' (parallel/pp.py)"
        )
    if schedule == "1f1b" or sp_pipeline:
        from ..parallel.pp import make_pipeline_loss_fn

        dtype = cfg.dtype
        is_moe = cfg.moe_experts > 0
        M = _pp_microbatches(mesh, num_microbatches)
        stage_fn = _pp_stage_fn(
            cfg, S, with_aux=is_moe, packed=side is not None, sp_manual=sp_pipeline
        )
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        hp = {"ln_f": params["ln_f"], "head": head}

        def head_loss(h, y, ex):
            # MEAN-normalized inside (the head runs on the FULL batch, so the denom is
            # exact here) — the aux term must NOT be divided by the token count.
            return _head_ce_sum(h, y, ex, cfg=cfg) / jnp.maximum(ex["mask"].sum(), 1.0)

        pipe_loss = make_pipeline_loss_fn(
            mesh, stage_fn, head_loss,
            num_microbatches=num_microbatches, schedule=schedule,
            with_aux=is_moe,
            # Same normalization as the GPipe path: aux is a mean statistic summed over
            # (stage, microbatch) pairs → divide by M so moe_aux_weight keeps its
            # non-pipelined meaning.
            aux_weight=(cfg.moe_aux_weight / M) if is_moe else 0.0,
            # sp×pp: activations ride sequence-sliced through a pipeline that is manual
            # over sp too (microbatch layout [M, B_m, S, D] → sp on dim 2). Packed
            # batches slice their side constants the same way (side_spec): each sp
            # member's stage sees its own [B_m, S/sp] positions/segment ids, and the
            # ring rotates the kv-side segment slice with its kv block.
            act_spec=P(None, None, SEQUENCE_AXIS, None) if sp_pipeline else None,
            extra_manual_axes=(SEQUENCE_AXIS,) if sp_pipeline else (),
            virtual_stages=virtual_stages,
            side_spec=(
                {"positions": P(None, None, SEQUENCE_AXIS),
                 "segment_ids": P(None, None, SEQUENCE_AXIS)}
                if (sp_pipeline and side is not None) else None
            ),
        )
        x = params["embed"].astype(dtype)[inputs]
        return pipe_loss(
            params["layers"], hp, x, {"targets": targets, "mask": mask}, side=side
        )
    x, aux = forward_pp(
        params, inputs, cfg, mesh, num_microbatches=num_microbatches, return_aux=True,
        segment_ids=seg_in, positions=side["positions"] if side else None,
    )
    ce = _ce_from_hidden(x, params, targets, mask, cfg)
    if cfg.moe_experts > 0:
        return ce + cfg.moe_aux_weight * aux
    return ce


@partial(jax.jit, static_argnames=("cfg",))
def _block_jit(x, layer, positions, mask, cfg):
    """Module-level jit: stable identity → one compilation per (config, shapes) across
    repeated forward_streamed calls."""
    return _block(x, layer, positions, mask, cfg)


def forward_streamed(
    dispatched,
    tokens: jax.Array,
    cfg: LlamaConfig,
    positions: Optional[jax.Array] = None,
    prefetch: int = 2,
) -> jax.Array:
    """Big-model inference forward: block weights streamed from host RAM / disk.

    The L6 path (``big_modeling.dispatch_model`` + ``stream_blocks``): runs a model whose
    params exceed HBM by fetching one transformer block at a time onto the main device, with a
    background thread prefetching the next block while the current one computes. Equivalent in
    role to the reference's ``AlignDevicesHook`` forward (``hooks.py:329``), functional instead
    of module-patching. Requires ``cfg.scan_layers=False`` (blocks addressed as ``layers/<i>``).
    """
    from ..big_modeling import consume_block, stream_blocks

    if cfg.scan_layers:
        raise ValueError("forward_streamed requires per-layer (non-scanned) params.")
    B, S = tokens.shape
    dtype = cfg.dtype
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    mask = jnp.tril(jnp.ones((S, S), dtype=jnp.bool_))[None, :, :]

    embed = dispatched.fetch("embed")
    x = embed[tokens].astype(dtype)  # gather then cast (host-driven loop; see generate_streamed)
    prefixes = [f"layers/{i}" for i in range(cfg.n_layers)]
    for name, layer in stream_blocks(dispatched, prefixes, prefetch=prefetch):
        x, _ = _block_jit(x, layer, positions, mask, cfg=cfg)
        consume_block(x, layer, dispatched, name)  # fence + free (big_modeling.consume_block)
    ln_f = dispatched.fetch("ln_f")
    x = _rms_norm(x, ln_f, cfg.norm_eps)
    head = embed if cfg.tie_embeddings else dispatched.fetch("lm_head")
    eq = "bsd,vd->bsv" if cfg.tie_embeddings else "bsd,dv->bsv"
    return jnp.einsum(eq, x, head.astype(dtype)).astype(jnp.float32)


# ----------------------------------------------------------------------- cached generation
def init_cache(
    cfg: LlamaConfig, batch_size: int, max_len: int, dtype=None,
    quantized: Optional[bool] = None,
) -> dict:
    """Allocate an empty KV cache for ``batch_size`` sequences of up to ``max_len`` tokens.

    Layout: ``{"layers": [{"k": [B,C,K,hd], "v": ...}, ...], "valid": [B,C] bool,
    "index": int32}`` — ``valid`` marks filled, non-pad slots (False on left-pads), ``index``
    is the next write slot.  With ``cfg.scan_layers`` the per-layer dicts are stacked on a
    leading layer dim, matching the stacked param layout.  The reference's decode baselines
    come from transformers' cache via hook dispatch (``benchmarks/big_model_inference``);
    here the cache is an explicit pytree so the whole decode loop jits.

    ``quantized`` (default ``cfg.kv_quant``): int8 k/v plus per-(token, kv-head) fp32
    scales — half the cache HBM of bf16. ``_block_cached`` quantizes on write and fuses
    dequantization into the attention reads.
    """
    quantized = cfg.kv_quant if quantized is None else quantized
    dtype = dtype or cfg.dtype
    one = lambda: _kv_planes(  # noqa: E731
        batch_size, max_len, cfg.n_kv_heads, cfg.head_dim, dtype, quantized
    )
    if cfg.scan_layers:
        layers = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (cfg.n_layers, *x.shape)), one()
        )
    else:
        layers = [one() for _ in range(cfg.n_layers)]
    return {
        "layers": layers,
        "valid": jnp.zeros((batch_size, max_len), jnp.bool_),
        "index": jnp.zeros((), jnp.int32),
    }


def init_paged_cache(
    cfg: LlamaConfig, batch_size: int, max_len: int, num_pages: int, page_size: int,
    dtype=None, quantized: Optional[bool] = None,
) -> dict:
    """Allocate an empty PAGED KV cache: a shared pool of ``num_pages`` fixed-size
    pages instead of a dense ``[B, max_len]`` row per lane.

    Layout: ``{"layers": [{"k": [P,ps,K,hd], "v": ...}, ...], "valid": [B,max_len]
    bool}`` — per-layer pool planes (stacked on a leading layer dim under
    ``cfg.scan_layers``), plus the per-lane valid mask, which stays DENSE by logical
    position (bools are ~1/2(head_dim·heads·bytes·layers)00th of the K/V bytes; the
    pool is where the memory goes). Which lane owns which page lives OUTSIDE the
    pytree in the host-side ``paged_kv.BlockManager`` block table, uploaded per step —
    so page allocation/release never rebuilds device state. ``quantized`` (default
    ``cfg.kv_quant``): int8 pages with per-slot fp32 scale pages — half the pool HBM.
    """
    quantized = cfg.kv_quant if quantized is None else quantized
    dtype = dtype or cfg.dtype
    one = lambda: _paged_kv_planes(  # noqa: E731
        num_pages, page_size, cfg.n_kv_heads, cfg.head_dim, dtype, quantized
    )
    if cfg.scan_layers:
        layers = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (cfg.n_layers, *x.shape)), one()
        )
    else:
        layers = [one() for _ in range(cfg.n_layers)]
    return {
        "layers": layers,
        "valid": jnp.zeros((batch_size, max_len), jnp.bool_),
    }


def _attention_cached(q, ck, cv, q_positions, valid, cfg: LlamaConfig):
    """q [B,T,H,hd] against the full cache ck/cv [B,C,K,hd]; ``valid`` [B,C] marks live keys.

    Causality: key slot j may be seen by the query at absolute slot p iff ``j <= p``.
    Scores every slot of the row in plain XLA, whatever the row holds. That is the right
    form for the shapes that keep it: single-token decode (T=1, a pure HBM-bandwidth
    gather), the speculative verify (T=k, a few rows), the gather fallback of the paged
    read, and every cached call off-TPU. A PREFILL chunk (scalar write index, T a
    multiple of 128) on a TPU goes through the flash forward kernel instead
    (``common.cached_prefill_attention``: 1.2 ms → 0.2–0.5 ms a layer for 512 queries
    against an 8 192-slot row, PERF.md PR 31); this function is then its reference, equal
    on every query row that has a live key.
    """
    B, T, H, hd = q.shape
    C = ck.shape[1]
    K = ck.shape[2]
    G = H // K
    # Grouped-query decode: contract against the UNREPEATED cache. Decode (T=1) is an
    # HBM-bandwidth gather over the cache, so never repeating it reads H/K× fewer bytes.
    qg = q.reshape(B, T, K, G, hd)
    scores = jnp.einsum("btkgd,bckd->bkgtc", qg, ck) * _sm_scale(cfg)
    scores = _softcap(scores, cfg.attn_softcap)
    slots = jnp.arange(C)[None, None, :]
    causal = slots <= q_positions[:, :, None]  # [B,T,C]
    if cfg.sliding_window:
        causal = causal & (slots > q_positions[:, :, None] - cfg.sliding_window)
    mask = (causal & valid[:, None, :])[:, None, None, :, :]  # [B,1,1,T,C]
    scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bkgtc,bckd->btkgd", probs, cv).reshape(B, T, H, hd)


def _block_cached(x, layer, kv, index, positions, valid, cfg: LlamaConfig,
                  moe_dense: Optional[bool] = None, paged=None, at_layer=None):
    """One block with KV-cache read/write → (x, new_kv).

    ``index`` is the write slot: a SCALAR advances every row together (generate's
    prefill/decode), a VECTOR [B] gives each row its own slot (the continuous-batching
    engine, ``serving.py`` — T == 1 decode, or T == k for the batched speculative
    verify, where row b writes slots ``index[b] .. index[b]+T-1``).

    ``moe_dense`` forces the drop-free dense MoE routing regardless of T (default:
    dense iff T == 1). The speculative verify passes True — every verified position
    must route exactly like the T == 1 decode it replaces, or acceptance would compare
    against capacity-pooled logits and break decode parity.

    ``paged`` — ``(tables, pages, offs, start_positions, page_size, plans)`` switches the
    KV side to the paged pool layout (``kv`` then holds [P, page_size, K, hd] pool planes;
    ``index`` is unused): writes scatter through the precomputed physical (page, slot)
    grid, reads go through ``common.paged_attention_dispatch`` (Pallas kernel on TPU,
    gather into THIS function's own ``_attention_cached`` on CPU — bitwise the dense
    path there). ``plans`` is the step's ``common.paged_read_plans`` (None on the
    gather path): the kernel's read takes the plan of this layer's window.

    ``at_layer`` — ``kv`` holds the STACKED planes of every layer (``[L, ...]``, the
    ``scan_layers`` cache as ``init_cache`` / ``init_paged_cache`` build it) and this
    block writes and reads plane ``at_layer`` of them, returning the whole stack:
    :func:`forward_slots`'s layer scan carries the cache and no layer is ever sliced
    out of it or stacked back (docs/paged_kv.md, "The cache rides the carry").
    """
    B, T, D = x.shape
    if moe_dense is None:
        moe_dense = T == 1
    p1 = cfg.norm_plus_one
    with jax.named_scope("attn"):
        h = _rms_norm(x, layer["ln_attn"], cfg.norm_eps, p1)
        q, k, v = _qkv_proj(h, layer, cfg)
        q = q.reshape(B, T, cfg.n_heads, cfg.head_dim)
        k = k.reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
        v = v.reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
        q = _rope(q, positions, cfg)
        k = _rope(k, positions, cfg)
        if paged is not None:
            tables, pages, offs, start_pos, page_size, plans = paged
            with jax.named_scope("kv_write"):
                new_kv = {**_write_cache_paged(kv, "k", k, pages, offs, at_layer),
                          **_write_cache_paged(kv, "v", v, pages, offs, at_layer)}
            attn = _paged_attention(
                q, new_kv, tables, start_pos, valid, page_size=page_size,
                sm_scale=_sm_scale(cfg), window=cfg.sliding_window,
                softcap=cfg.attn_softcap, dtype=cfg.dtype,
                dense_attention=lambda ck, cv: _attention_cached(
                    q, ck, cv, positions, valid, cfg
                ),
                layer=at_layer, plan=None if plans is None else plans[cfg.sliding_window],
            )
        else:
            with jax.named_scope("kv_write"):
                new_kv = {**_write_cache(kv, "k", k, index, at_layer),
                          **_write_cache(kv, "v", v, index, at_layer)}
            # The dense read takes its layer's planes out of a carried stack: one layer's
            # bytes, what the attention reads anyway.
            own = new_kv if at_layer is None else {n: p[at_layer] for n, p in new_kv.items()}
            ck, cv = _read_cache(own, "k", cfg.dtype), _read_cache(own, "v", cfg.dtype)
            # A prefill chunk (scalar index, T a multiple of 128) takes the flash kernel
            # on a TPU; decode, verify and the CPU keep ``_attention_cached``.
            attn = _cached_prefill_attention(
                q, ck, cv, index, valid, impl=cfg.attn_impl, sm_scale=_sm_scale(cfg),
                window=cfg.sliding_window, softcap=cfg.attn_softcap,
                xla_attention=lambda: _attention_cached(q, ck, cv, positions, valid, cfg),
            )
        attn_out = _proj_l(attn.reshape(B, T, cfg.n_heads * cfg.head_dim), layer, "wo", cfg)
        if cfg.post_norm:
            attn_out = _rms_norm(attn_out, layer["ln_attn_post"], cfg.norm_eps, p1)
        x = x + attn_out
    with jax.named_scope("mlp"):
        h = _rms_norm(x, layer["ln_mlp"], cfg.norm_eps, p1)
        if cfg.moe_experts > 0:
            from ..ops.moe import moe_mlp, moe_mlp_dense

            if moe_dense:
                # Decode: drop-free dense routing — capacity pooling over a single-token
                # step would drop tokens whenever a step's rows collide on an expert
                # (training's fixed-shape load-management artifact, wrong for inference).
                y = moe_mlp_dense(
                    h, layer["moe"], layer["moe"]["w_router"],
                    top_k=cfg.moe_top_k, compute_dtype=cfg.dtype,
                )
            else:
                # Prefill: identical pooled formulation (and token set) as the training
                # forward.
                y, _ = moe_mlp(
                    h, layer["moe"], layer["moe"]["w_router"],
                    top_k=cfg.moe_top_k, capacity_factor=cfg.moe_capacity_factor,
                    compute_dtype=cfg.dtype,
                )
            return x + y, new_kv
        gate = _mlp_gate_act(_proj_l(h, layer, "w_gate", cfg), cfg)
        up = _proj_l(h, layer, "w_up", cfg)
        mlp_out = _proj_l(gate * up, layer, "w_down", cfg)
        if cfg.post_norm:
            mlp_out = _rms_norm(mlp_out, layer["ln_mlp_post"], cfg.norm_eps, p1)
        x = x + mlp_out
    return x, new_kv


def _cache_advance(cache: dict, tokens: jax.Array, token_mask: Optional[jax.Array]):
    """Shared cache bookkeeping for the in-memory and streamed cached-forward paths:
    (write index, absolute rope positions [B,T], updated valid mask [B,C])."""
    B, T = tokens.shape
    index = cache["index"]
    positions = index + jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    if token_mask is None:
        token_mask = jnp.ones((B, T), jnp.bool_)
    valid = jax.lax.dynamic_update_slice(cache["valid"], token_mask, (0, index))
    return index, positions, valid


def forward_cached(
    params: dict,
    tokens: jax.Array,
    cache: dict,
    cfg: LlamaConfig,
    token_mask: Optional[jax.Array] = None,
    last_only: bool = False,
) -> tuple[jax.Array, dict]:
    """Write ``tokens`` [B,T] into the cache at its current index and return
    (logits fp32, updated cache) — logits [B,T,V], or [B,1,V] with ``last_only`` (prefill
    wants only the final position; skipping the [B,T,V] vocab matmul saves S0× head compute
    and HBM).

    Prefill passes the left-padded prompt with ``token_mask`` False on pads; decode passes a
    single token per row (T=1, mask omitted).  Rope positions are the absolute cache slots —
    rotary attention only depends on position *differences*, so left-pad offsets cancel.
    """
    B, T = tokens.shape
    dtype = cfg.dtype
    index, positions, valid = _cache_advance(cache, tokens, token_mask)

    with jax.named_scope("embed"):
        x = params["embed"].astype(dtype)[tokens]
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)
    alternating = bool(cfg.sliding_window) and cfg.window_every > 1
    if cfg.scan_layers and alternating:
        # Same grouped scan as forward_hidden: layer j of each group is banded iff j == 0.
        per = cfg.window_every
        if cfg.n_layers % per:
            raise ValueError(
                f"window_every={per} must divide n_layers={cfg.n_layers} under scan_layers"
            )
        full_cfg = dataclasses.replace(cfg, sliding_window=0)
        regroup = lambda a: a.reshape(cfg.n_layers // per, per, *a.shape[1:])  # noqa: E731
        grouped = jax.tree_util.tree_map(
            regroup, (params["layers"], cache["layers"])
        )

        def scan_body(carry, group):
            layers_g, kv_g = group
            out = carry
            new_kvs = []
            for j in range(per):
                layer_j = jax.tree_util.tree_map(lambda a, j=j: a[j], layers_g)
                kv_j = jax.tree_util.tree_map(lambda a, j=j: a[j], kv_g)
                out, new_kv = _block_cached(
                    out, layer_j, kv_j, index, positions, valid,
                    cfg if j == 0 else full_cfg,
                )
                new_kvs.append(new_kv)
            stacked = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *new_kvs)
            return out, stacked

        x, new_grouped = jax.lax.scan(scan_body, x, grouped)
        new_layers = jax.tree_util.tree_map(
            lambda a: a.reshape(cfg.n_layers, *a.shape[2:]), new_grouped
        )
    elif cfg.scan_layers:
        def scan_body(carry, layer_and_kv):
            layer, kv = layer_and_kv
            out, new_kv = _block_cached(carry, layer, kv, index, positions, valid, cfg)
            return out, new_kv

        x, new_layers = jax.lax.scan(scan_body, x, (params["layers"], cache["layers"]))
    else:
        full_cfg = dataclasses.replace(cfg, sliding_window=0)
        new_layers = []
        for i, (layer, kv) in enumerate(zip(params["layers"], cache["layers"])):
            banded = cfg.sliding_window and i % cfg.window_every == 0
            x, new_kv = _block_cached(
                x, layer, kv, index, positions, valid, cfg if banded else full_cfg
            )
            new_layers.append(new_kv)
    x = _rms_norm(x, params["ln_f"], cfg.norm_eps, cfg.norm_plus_one)
    if last_only:
        x = x[:, -1:, :]
    logits = head_logits(x, params, cfg)
    new_cache = {"layers": new_layers, "valid": valid, "index": index + T}
    return logits, new_cache


def forward_cached_logits(params: dict, tokens: jax.Array, cache: dict, cfg: LlamaConfig,
                          token_mask: Optional[jax.Array] = None):
    """:func:`forward_cached` with the logits of EVERY position [B,T,V] — what the
    serving engine's prefix-cache prefill calls (a right-aligned prompt's last real
    token may sit before trailing pads)."""
    return forward_cached(params, tokens, cache, cfg, token_mask=token_mask)


def decode_layout(params: dict, cfg: LlamaConfig) -> tuple[dict, int]:
    """``(params, n)``: the serving engine's tree, with each plain ``wq``/``wk``/``wv``
    (an array: not int8/int4-quantized, not LoRA-adapted, not under ``cfg.use_fp8``)
    stored once as ``{name}_t`` ``[..., out, in]`` in its place, and ``n`` the arrays so
    re-laid. Contracted on their first axis, the decode program re-lays the three
    stacks to ``in``-minor in every dispatch (2.5 ms of a 47.5 ms one at the Mistral
    serve cell, and 0.81 GB of temporaries; PERF.md, PR 37); :func:`_qkv_proj` reads
    either form. Training and :func:`generate` keep the ``[..., in, out]`` tree; a tree
    already re-laid comes back as it is. The engine calls this once, at construction:
    one device program re-lays every array (one compile in the engine's set-up)."""
    layers = params["layers"]
    stacked = isinstance(layers, dict)
    layers = [dict(layers)] if stacked else [dict(layer) for layer in layers]
    plain = [] if cfg.use_fp8 else [
        (layer, name) for layer in layers for name in _DECODE_LAYOUT
        if isinstance(layer.get(name), (jax.Array, np.ndarray))
        and f"{name}_lora_a" not in layer]
    if not plain:
        return params, 0
    for (layer, name), w_t in zip(plain, _swap_last_axes([l.pop(n) for l, n in plain])):
        layer[f"{name}_t"] = w_t
    return {**params, "layers": layers[0] if stacked else layers}, len(plain)


@jax.jit
def _swap_last_axes(ws: list) -> list:
    return [jnp.swapaxes(w, -1, -2) for w in ws]


def paged_walk_shape(cfg: LlamaConfig, page_size: int, itemsize: int,
                     max_pages: int) -> tuple:
    """(table entries the paged-attention kernel fetches an iteration, its window) for
    the serving engine's ``pages_live`` / ``pages_walked`` counters."""
    from ..ops.paged_attention import block_pages

    return (block_pages(page_size, cfg.n_kv_heads, cfg.head_dim, itemsize, max_pages),
            cfg.sliding_window)


def forward_slots(
    params: dict,
    tokens: jax.Array,
    cache: dict,
    positions: jax.Array,
    cfg: LlamaConfig,
    tables: Optional[jax.Array] = None,
    page_size: int = 0,
) -> tuple[jax.Array, dict]:
    """Per-slot cached forward: ``tokens`` [B,T] written at each row's own cache slots
    ``positions[b] .. positions[b]+T-1`` → (logits fp32 [B,T,V], new cache).

    The continuous-batching counterpart of :func:`forward_cached` (whose single scalar
    ``index`` advances all rows together): every lane carries its own write position, so
    one compiled program serves a batch of requests at arbitrary, different sequence
    lengths. T == 1 is the engine's decode step; T == k+1 is the batched speculative
    VERIFY — one fused target forward scoring a pending token plus k draft proposals
    per lane, each position's logits exactly the distribution the T == 1 decode would
    have produced there (same rope positions, same causal/valid masking, dense MoE
    routing — decode-parity is what makes speculative acceptance lossless). Slots past
    a lane's rewound position may hold garbage K/V from rejected drafts; the causal
    mask (``slot <= q_position``) makes them unreachable until overwritten.

    ``tables``/``page_size`` switch the KV side to the PAGED layout (``cache`` from
    :func:`init_paged_cache`): writes scatter through each lane's block-table row into
    shared pool pages (sentinel/out-of-range positions drop), reads go through the
    paged-attention dispatch. ONE forward implementation for both layouts — the
    alternating-sliding-window grouping, per-layer banding and MoE routing literally
    cannot drift between them (the dense/paged token-parity contract,
    tests/test_serving_paged.py).

    Under ``cfg.scan_layers`` the stacked cache of all layers is the layer scan's CARRY,
    written in place a layer at a time (``_block_cached(..., at_layer=l)``), never its
    ``xs``/``ys`` — so the engine's decode programs, which carry the cache through
    their own scan over steps and donate it at the jit boundary, move no pool plane.
    """
    from .common import paged_read_plans, paged_write_coords

    B, T = tokens.shape
    rows = jnp.arange(B)
    pos_grid = positions[:, None] + jnp.arange(T, dtype=positions.dtype)[None, :]  # [B,T]
    if T == 1:
        valid = cache["valid"].at[rows, positions].set(True)
    else:
        valid = cache["valid"].at[rows[:, None], pos_grid].set(True)
    paged = None
    if tables is not None:
        num_pages = jax.tree_util.tree_leaves(cache["layers"])[0].shape[
            1 if cfg.scan_layers else 0
        ]
        pages, offs = paged_write_coords(
            tables, pos_grid, page_size, cache["valid"].shape[1], num_pages
        )
        # The paged read's plan (walk, padded table, valid mask by score column) is the
        # same for every layer of this step: built once here, not in each layer's read.
        pool = cache["layers"] if cfg.scan_layers else cache["layers"][0]
        windows = {cfg.sliding_window} | ({0} if cfg.window_every > 1 else set())
        plans = paged_read_plans(pool, tables, positions, valid, T=T,
                                 page_size=page_size, windows=windows)
        paged = (tables, pages, offs, positions, page_size, plans)
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.dtype)
    if cfg.scan_layers:
        # ONE scan for the plain and the alternating-window stack: it walks groups of
        # `per` layers (per == 1 when every layer has the same band), layer j of a group
        # banded iff j == 0 as in forward_cached (without this, decode would band-limit
        # the full-attention layers and diverge from generate()). The cache of ALL layers
        # is the scan's CARRY and each block writes its plane of it in place; as the
        # scan's xs/ys it would be sliced, restacked and copied whole every step.
        per = cfg.window_every if cfg.sliding_window else 1
        full_cfg = dataclasses.replace(cfg, sliding_window=0)
        grouped = jax.tree_util.tree_map(
            lambda a: a.reshape(cfg.n_layers // per, per, *a.shape[1:]), params["layers"]
        )

        def body(carry, group):
            out, kv = carry
            layers_g, first = group
            for j in range(per):
                layer_j = jax.tree_util.tree_map(lambda a, j=j: a[j], layers_g)
                # vector index → per-row write slots (_block_cached handles both)
                out, kv = _block_cached(
                    out, layer_j, kv, positions, pos_grid, valid,
                    cfg if j == 0 else full_cfg, moe_dense=True, paged=paged,
                    at_layer=first + j,
                )
            return (out, kv), None

        firsts = jnp.arange(0, cfg.n_layers, per, dtype=jnp.int32)
        (x, new_layers), _ = jax.lax.scan(body, (x, cache["layers"]), (grouped, firsts))
    else:
        # Mirror forward_cached's per-layer banded/full alternation (cfg.window_every).
        full_cfg = dataclasses.replace(cfg, sliding_window=0)
        new_layers = []
        for i, (layer, kv) in enumerate(zip(params["layers"], cache["layers"])):
            banded = cfg.sliding_window and i % cfg.window_every == 0
            x, new_kv = _block_cached(
                x, layer, kv, positions, pos_grid, valid,
                cfg if banded else full_cfg, moe_dense=True, paged=paged,
            )
            new_layers.append(new_kv)
    x = _rms_norm(x, params["ln_f"], cfg.norm_eps, cfg.norm_plus_one)
    logits = head_logits(x, params, cfg)
    if paged is not None:
        return logits, {"layers": new_layers, "valid": valid}
    return logits, {"layers": new_layers, "valid": valid, "index": cache["index"]}


def forward_slots_paged(
    params: dict,
    tokens: jax.Array,
    cache: dict,
    tables: jax.Array,
    positions: jax.Array,
    cfg: LlamaConfig,
    page_size: int,
) -> tuple[jax.Array, dict]:
    """:func:`forward_slots` over the PAGED cache (``init_paged_cache``) — a thin
    delegate: the serving engine's stable entry point for the paged layout.
    ``tables`` [B, MP] int32 maps each lane's logical pages to physical pool pages
    (SENTINEL == num_pages marks unallocated entries; writes through them, and any
    position at/past max_len, DROP). The forward itself is the ONE shared
    implementation in :func:`forward_slots`, so the two layouts cannot drift."""
    return forward_slots(
        params, tokens, cache, positions, cfg, tables=tables, page_size=page_size
    )


def forward_slots_multi(
    params: dict,
    cache: dict,
    tokens: jax.Array,
    positions: jax.Array,
    active: jax.Array,
    budgets: jax.Array,
    eos_ids: jax.Array,
    select_token,
    xs,
    n_steps: int,
    cfg: LlamaConfig,
    tables: Optional[jax.Array] = None,
    page_size: int = 0,
) -> tuple[dict, jax.Array, jax.Array]:
    """N :func:`forward_slots` decode steps (T == 1) as ONE ``lax.scan`` — the
    scan-friendly super-step the serving engine's ``decode_steps=N`` path
    dispatches. Each scan step is literally a T == 1 ``forward_slots`` call (same
    rope positions, same valid/causal masking, same paged routing), so per-step
    logits are bitwise the host-loop's; see
    :func:`~.common.multi_step_decode` for the freeze/emission contract.
    Returns ``(cache, tok_buf [n_steps, B], counts [B])``."""
    from .common import multi_step_decode

    max_len = cache["valid"].shape[1]

    def forward_one(c, tok, write_pos):
        logits, c = forward_slots(
            params, tok[:, None], c, write_pos, cfg, tables=tables,
            page_size=page_size,
        )
        return logits[:, -1, :], c

    return multi_step_decode(
        forward_one, cache, tokens, positions, active, budgets, eos_ids,
        select_token, xs, n_steps, max_len,
    )


def forward_slots_spec_multi(
    params: dict,
    cache: dict,
    tokens: jax.Array,
    positions: jax.Array,
    active: jax.Array,
    budgets: jax.Array,
    eos_ids: jax.Array,
    propose,
    select_ref,
    key_tab: jax.Array,
    history: jax.Array,
    hist_lens: jax.Array,
    n_steps: int,
    spec_k: int,
    cfg: LlamaConfig,
    tables: Optional[jax.Array] = None,
    page_size: int = 0,
):
    """N speculative draft→verify→accept rounds as ONE ``lax.scan`` — the fused
    super-step the serving engine's ``spec_k > 0, decode_steps=N`` path
    dispatches (``serving.spec_multi[_paged]``). Each scan round's verify is
    literally a T == spec_k+1 :func:`forward_slots` call (the PR-6
    ``_spec_verify_step`` body: same rope positions, same valid/causal masking,
    same paged routing), so per-round logits are bitwise the host loop's; see
    :func:`~.common.spec_multi_step_decode` for the accept/key-cursor/freeze
    contract. Returns ``(cache, tok_buf [n_steps, B, spec_k+1], emits
    [n_steps, B], counts [B], proposed [B], accepted [B])``."""
    from .common import history_draft, spec_multi_step_decode

    max_len = cache["valid"].shape[1]

    def forward_verify(c, seq, write_pos):
        return (*forward_slots(
            params, seq, c, write_pos, cfg, tables=tables, page_size=page_size
        ), None)

    return spec_multi_step_decode(
        forward_verify, history_draft(propose, history, hist_lens), select_ref, cache,
        tokens, positions, active, budgets, eos_ids, key_tab, n_steps, spec_k, max_len,
    )


def _make_gen_fns(cfg: LlamaConfig, max_len: int):
    """Stable-identity (prefill, decode) pair for ``generation.generate_loop`` (jit-static)."""

    def prefill_fn(params, prompt, prompt_mask):
        cache = init_cache(cfg, prompt.shape[0], max_len)
        logits, cache = forward_cached(
            params, prompt, cache, cfg, token_mask=prompt_mask, last_only=True
        )
        return logits[:, -1, :], cache

    def decode_fn(params, cache, token):
        logits, cache = forward_cached(params, token[:, None], cache, cfg)
        return logits[:, -1, :], cache

    return prefill_fn, decode_fn


# Bounded cache of (prefill, decode) closure pairs: stable identities keep generate_loop's
# jit cache warm, the bound keeps a long-running server from pinning one executable pair per
# distinct prompt length forever (max_len is bucketed below for the same reason).
_GEN_FNS: OrderedDict = OrderedDict()
_GEN_FNS_MAX = 16


def generate(
    params: dict,
    prompt: jax.Array,
    cfg: LlamaConfig,
    gen=None,
    rng: Optional[jax.Array] = None,
    prompt_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Autoregressive generation: one compiled prefill + decode-scan program.

    ``prompt`` [B,S0] int32 (left-padded; pass ``prompt_mask`` False on pads).  Returns
    [B, max_new_tokens].  The reference-side analog is ``model.generate()`` over a dispatched
    model (``/root/reference/benchmarks/big_model_inference/README.md:25``).
    """
    from ..generation import GenerationConfig, generate_loop

    gen = gen or GenerationConfig()
    prompt = jnp.asarray(prompt, jnp.int32)
    if prompt_mask is None:
        prompt_mask = jnp.ones(prompt.shape, jnp.bool_)
    # Bucket the cache length so nearby prompt lengths share one compiled program (the
    # valid-mask/index machinery makes an over-long cache semantically identical).
    max_len = prompt.shape[1] + gen.max_new_tokens
    max_len = -(-max_len // 64) * 64
    key = (cfg, max_len)
    if key not in _GEN_FNS:
        _GEN_FNS[key] = _make_gen_fns(cfg, max_len)
        while len(_GEN_FNS) > _GEN_FNS_MAX:
            _GEN_FNS.popitem(last=False)
    _GEN_FNS.move_to_end(key)
    prefill_fn, decode_fn = _GEN_FNS[key]
    return generate_loop(prefill_fn, decode_fn, params, prompt, prompt_mask, gen, rng)


def generate_streamed(
    dispatched,
    prompt: jax.Array,
    cfg: LlamaConfig,
    gen=None,
    rng: Optional[jax.Array] = None,
    prompt_mask: Optional[jax.Array] = None,
    prefetch: int = 2,
    pass_times: Optional[list] = None,
) -> jax.Array:
    """Generation for models bigger than HBM: every forward streams blocks from host/disk.

    The reference's offloaded ``generate`` re-loads each layer per *token* through
    ``AlignDevicesHook.pre_forward`` (hooks.py:329) — its OPT-30B disk number is 33.9 s/token
    (BASELINE.md).  This path does the same amount of traffic but overlaps each block's H2D
    copy with the previous block's compute (``stream_blocks`` double-buffering).  Use
    ``generate`` whenever the params fit — streamed decode is HBM-bandwidth-bound by design.
    """
    from ..big_modeling import consume_block, stream_blocks
    from ..generation import GenerationConfig, streamed_generate_loop

    if cfg.scan_layers:
        raise ValueError("generate_streamed requires per-layer (non-scanned) params.")
    gen = gen or GenerationConfig()
    B, S0 = jnp.asarray(prompt).shape
    max_len = S0 + gen.max_new_tokens
    prefixes = [f"layers/{i}" for i in range(cfg.n_layers)]
    # Hoist always-resident leaves out of the loop: only transformer BLOCKS stream per
    # pass; re-fetching the embedding from host/disk per token would dominate the traffic.
    embed = dispatched.fetch("embed")
    ln_f = dispatched.fetch("ln_f")
    head = embed if cfg.tie_embeddings else dispatched.fetch("lm_head")

    def one_pass(tokens, cache, token_mask):
        if cache is None:
            cache = init_cache(cfg, B, max_len)
        index, positions, valid = _cache_advance(cache, tokens, token_mask)
        # Gather THEN cast: this loop is host-driven (un-jitted between blocks), so
        # embed.astype(...)[tokens] would eagerly convert the full [V, D] matrix per pass.
        x = embed[tokens].astype(cfg.dtype)
        new_layers = []
        for i, layer in stream_blocks(dispatched, prefixes, prefetch=prefetch):
            idx = int(i.split("/")[1])
            x, new_kv = _block_cached_jit(
                x, layer, cache["layers"][idx], index, positions, valid, cfg=cfg
            )
            # Fence + free this block's buffers NOW (big_modeling.consume_block).
            consume_block(x, layer, dispatched, i)
            new_layers.append(new_kv)
        x = _rms_norm(x, ln_f, cfg.norm_eps)
        logits = _streamed_head_jit(x[:, -1, :], head, transpose=cfg.tie_embeddings)
        return logits, {"layers": new_layers, "valid": valid, "index": index + tokens.shape[1]}

    return streamed_generate_loop(one_pass, prompt, prompt_mask, gen, rng,
                                  pass_times=pass_times)


@partial(jax.jit, static_argnames=("transpose",))
def _streamed_head_jit(x_last, head, transpose: bool):
    """Final-position vocab projection for streamed decode, fused under one jit so the
    head-matrix cast/transpose never materializes eagerly ([V,D] when tied, [D,V] when not)."""
    eq = "bd,vd->bv" if transpose else "bd,dv->bv"
    return jnp.einsum(eq, x_last, head.astype(x_last.dtype)).astype(jnp.float32)


@partial(jax.jit, static_argnames=("cfg",))
def _block_cached_jit(x, layer, kv, index, positions, valid, cfg):
    """Module-level jit identity: one compile per shape across streamed decode steps."""
    return _block_cached(x, layer, kv, index, positions, valid, cfg)


def num_params(cfg: LlamaConfig) -> int:
    """Analytic parameter count (used by MFU computation in bench)."""
    D, F, V, H, K, hd = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    mlp = 3 * D * F if cfg.moe_experts == 0 else cfg.moe_experts * 3 * D * F + D * cfg.moe_experts
    per_layer = D * H * hd + 2 * D * K * hd + H * hd * D + mlp + 2 * D
    total = V * D + cfg.n_layers * per_layer + D
    if not cfg.tie_embeddings:
        total += D * V
    return total


# -------------------------------------------------------------------- speculative decoding
def _cached_family(cfg):
    """Family module for a config — ``common.cached_decode_family`` (llama or gpt,
    which share the cached-decode contract; gpt reuses llama's ``_cache_advance``).
    Lets the speculative decoder drive either family, including cross-family
    draft/target pairs (e.g. a gpt target with a small llama draft) as long as the
    vocabularies match. Raises TypeError for families without a decode contract."""
    from .common import cached_decode_family

    return cached_decode_family(cfg)


def _cache_rewind(cache: dict, to_index) -> dict:
    """Roll a cache back to ``to_index`` written tokens: later slots become invalid (their
    k/v are garbage from rejected drafts and are masked; the next writes overwrite them)."""
    C = cache["valid"].shape[1]
    keep = jnp.arange(C)[None, :] < to_index
    return {
        "layers": cache["layers"],
        "valid": cache["valid"] & keep,
        "index": jnp.asarray(to_index, jnp.int32),
    }


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(2,))
def _spec_forward_jit(params, tokens, cache, cfg):
    """forward_cached + per-position argmax (used for both the T=K verify and T=1 steps).
    The input cache is donated — callers always replace their reference with the output."""
    logits, cache = _cached_family(cfg).forward_cached(params, tokens, cache, cfg)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache


@partial(jax.jit, static_argnames=("t_cfg", "d_cfg", "k"), donate_argnums=(2, 3))
def _spec_round_greedy_jit(t_params, d_params, t_cache, d_cache, pending, *, t_cfg, d_cfg, k):
    """ONE fused greedy speculative round: k-1 draft steps (``lax.scan``), the T=k
    target verify, prefix acceptance, both cache rewinds, and the full-acceptance
    draft catch-up — a single compiled program per round.

    The unfused loop costs ~k+3 host->device dispatches per round, each a round-trip
    (ruinous through a network-attached device, and measurable even host-attached:
    the CPU smoke of ``benchmarks/big_model_inference/speculative_tpu.py`` put
    per-round host overhead at ~50x the tiny-model step cost). Fused, the Python
    loop makes ONE dispatch and ONE result read per round. Control flow lives
    on-device: acceptance length ``n`` = leading-match count via ``cumprod``; the
    draft catch-up runs under ``lax.cond``. Token-for-token identical to the
    unfused greedy path (same argmax/accept math; parity-tested).

    Returns ``(emitted[k], count, t_cache, d_cache)``: ``emitted[:count]`` =
    accepted drafts + the target's correction (the new pending token is
    ``emitted[count-1]``, sliced on-device by the caller's next round)."""
    fam_t, fam_d = _cached_family(t_cfg), _cached_family(d_cfg)
    base_t = t_cache["index"]            # emitted length - 1 (pending unwritten)
    base_d = d_cache["index"]

    def draft_step(carry, _):
        tok, cache = carry
        logits, cache = fam_d.forward_cached(d_params, tok[None, None], cache, d_cfg)
        nxt = jnp.argmax(logits[0, -1]).astype(jnp.int32)
        return (nxt, cache), nxt

    pending = jnp.asarray(pending, jnp.int32)
    (_, d_cache), drafts = jax.lax.scan(draft_step, (pending, d_cache), None, length=k - 1)

    seq = jnp.concatenate([pending[None], drafts])[None]          # [1, k]
    logits, t_cache = fam_t.forward_cached(t_params, seq, t_cache, t_cfg)
    ys = jnp.argmax(logits[0], axis=-1).astype(jnp.int32)         # [k]
    matches = (drafts == ys[: k - 1]).astype(jnp.int32)
    n = jnp.sum(jnp.cumprod(matches))                             # leading agreements
    correction = ys[n]
    emitted = jnp.where(
        jnp.arange(k) < n, jnp.concatenate([drafts, jnp.zeros((1,), jnp.int32)]), 0
    )
    emitted = emitted.at[n].set(correction)
    t_cache = _cache_rewind(t_cache, base_t + 1 + n)

    def full_acceptance(cache):
        # The draft never processed its own last proposal (it wrote pending +
        # drafts[:-1]); one catch-up step so the next round's cache has no hole.
        cache = _cache_rewind(cache, base_d + n)
        _, cache = fam_d.forward_cached(d_params, drafts[-1][None, None], cache, d_cfg)
        return cache

    d_cache = jax.lax.cond(
        n == k - 1, full_acceptance, lambda c: _cache_rewind(c, base_d + 1 + n), d_cache
    )
    # Pack emitted+count into one vector: the caller reads the round result in a
    # single device->host transfer; ``correction`` feeds the next round's pending
    # as a device scalar (never synced).
    packed = jnp.concatenate([emitted, (n + 1)[None]])
    return packed, correction, t_cache, d_cache


@partial(jax.jit, static_argnames=("cfg", "top_k", "apply_top_p"), donate_argnums=(2,))
def _spec_probs_jit(params, tokens, cache, cfg, temperature, top_p, top_k, apply_top_p):
    """forward_cached + the SAME temperature/top-k/top-p filtering ``generate`` samples
    from, as per-position probability rows [B, T, V] — speculative sampling's accept test
    compares draft and target over these exact distributions. Only the shape-affecting
    knobs (top_k, apply_top_p) are static; temperature/top_p trace as scalars so varying
    sampling-irrelevant GenerationConfig fields never recompiles the model."""
    from ..generation import filtered_logits

    logits, cache = _cached_family(cfg).forward_cached(params, tokens, cache, cfg)
    fl = filtered_logits(logits, temperature, top_p, top_k, apply_top_p)
    return jax.nn.softmax(fl, axis=-1), cache


def generate_speculative(
    target_params: dict,
    target_cfg,   # LlamaConfig | GPTConfig (see _cached_family)
    draft_params: dict,
    draft_cfg,    # LlamaConfig | GPTConfig
    prompt: jax.Array,
    max_new_tokens: int = 32,
    k: int = 4,
    eos_token_id: Optional[int] = None,
    prompt_mask: Optional[jax.Array] = None,
    return_stats: bool = False,
    gen=None,
    rng: Optional[jax.Array] = None,
):
    """Speculative decoding: ONE target dispatch per round verifies the pending token
    plus ``k-1`` draft proposals and emits 1..k tokens (accepted prefix + the target's
    correction). Greedy by default — output PROVABLY identical to the target's plain
    greedy decode (tested token-for-token). With a ``GenerationConfig`` whose
    ``temperature > 0`` (plus ``rng``), it runs LOSSLESS SPECULATIVE SAMPLING (Leviathan
    et al. 2022): each proposal is accepted with min(1, p/q) and rejections re-draw from
    the residual norm(max(p − q, 0)), so the output distribution is exactly the target's
    own temperature/top-k/top-p sampling distribution (``generation.speculative_accept``;
    distribution asserted in tests). The draft only changes how many target forwards it
    takes. The reference has no speculative path. Single sequence (B=1): speculation is a
    latency tool for individual streams; batch throughput is ``serving.ContinuousBatcher``.

    Family-generic over the shared cached-decode contract (``_cached_family``): target
    and draft may each be llama or gpt configs — including cross-family pairs (e.g. a
    gpt-family target speculated by a small llama draft, as the tests do) — as long as
    the vocabularies match.

    Round invariant: both caches hold the emitted sequence EXCEPT the newest token
    (``pending``), which rides as the first input of the next round's forwards — so the
    correction never costs its own target dispatch. Verified drafts' k/v already sit in
    the caches; acceptance is a cache REWIND plus bookkeeping.

    ``return_stats=True`` also returns ``{"rounds", "target_dispatches", "tokens"}``
    (dispatches = rounds + 1 prefill) for tokens-per-dispatch accounting.
    """
    from ..generation import sample_logits, speculative_accept

    if target_cfg.vocab_size != draft_cfg.vocab_size:
        raise ValueError("draft and target must share a vocabulary")
    if k < 2:
        raise ValueError("k must be >= 2 (k-1 draft proposals per round)")
    sampled = gen is not None and gen.temperature > 0.0
    if sampled and rng is None:
        raise ValueError("speculative sampling (gen.temperature > 0) needs an rng key")
    _key_n = [0]

    def next_key():
        _key_n[0] += 1
        return jax.random.fold_in(rng, _key_n[0])
    prompt = jnp.asarray(prompt, jnp.int32)
    if prompt.ndim == 1:
        prompt = prompt[None]
    if prompt.shape[0] != 1:
        raise ValueError("generate_speculative is single-sequence (B=1)")
    if prompt_mask is None:
        prompt_mask = jnp.ones(prompt.shape, jnp.bool_)
    else:
        prompt_mask = jnp.asarray(prompt_mask, jnp.bool_)
        if prompt_mask.ndim == 1:  # mirror the prompt's auto batch dim
            prompt_mask = prompt_mask[None]
    S0 = prompt.shape[1]
    # Bucketed like generate(): nearby prompt/k/max_new combinations share one compiled
    # program per token shape (the valid-mask machinery makes an over-long cache identical).
    max_len = -(-(S0 + max_new_tokens + k + 1) // 64) * 64

    fam_t = _cached_family(target_cfg)
    fam_d = _cached_family(draft_cfg)
    t_cache = fam_t.init_cache(target_cfg, 1, max_len)
    d_cache = fam_d.init_cache(draft_cfg, 1, max_len)
    t_logits, t_cache = fam_t.forward_cached(
        target_params, prompt, t_cache, target_cfg, token_mask=prompt_mask, last_only=True
    )
    _, d_cache = fam_d.forward_cached(
        draft_params, prompt, d_cache, draft_cfg, token_mask=prompt_mask, last_only=True
    )
    # ``pending``: emitted but not yet written to either cache.
    if sampled:
        pending = int(np.asarray(sample_logits(t_logits[:, -1, :], gen, next_key()))[0])
    else:
        pending = int(np.asarray(jnp.argmax(t_logits[0, -1])))
    out: list[int] = [pending]
    rounds = 0

    def finish():
        toks = jnp.asarray([out[:max_new_tokens]], jnp.int32)
        if return_stats:
            return toks, {
                "rounds": rounds, "target_dispatches": rounds + 1, "tokens": min(len(out), max_new_tokens),
            }
        return toks

    if eos_token_id is not None and pending == eos_token_id:
        return finish()

    pending_dev = jnp.asarray(pending, jnp.int32)  # greedy path: device-resident pending
    while len(out) < max_new_tokens:
        rounds += 1
        if not sampled:
            # Greedy: the WHOLE round is one fused program (_spec_round_greedy_jit —
            # draft scan + T=k verify + acceptance + rewinds + catch-up); the loop
            # makes one dispatch and one packed result read per round.
            packed, pending_dev, t_cache, d_cache = _spec_round_greedy_jit(
                target_params, draft_params, t_cache, d_cache, pending_dev,
                t_cfg=target_cfg, d_cfg=draft_cfg, k=k,
            )
            # graftlint: disable=host-sync-in-hot-path(one fused round = ONE result read; the host must see the accepted tokens)
            arr = np.asarray(packed)  # [k+1]: emitted slots + count
            for tok in arr[: int(arr[k])].tolist():  # graftlint: disable=host-sync-in-hot-path(arr is host-side numpy already; no device fetch here)
                out.append(int(tok))
                if len(out) >= max_new_tokens or (
                    eos_token_id is not None and tok == eos_token_id
                ):
                    return finish()
            continue
        # ---- lossless speculative sampling: host-side sequential accept (each accept
        # consumes an rng key and can end the round, so this path keeps the unfused
        # per-step dispatches; fusing it needs the accept chain as a lax.scan over
        # carried keys — future work, the greedy path above shows the shape).
        # 1. draft k-1 proposals; the draft's first input is the pending token itself.
        drafts: list[int] = []
        q_rows = []  # the draft's filtered distribution per proposal
        tok = pending
        for _ in range(k - 1):
            qp, d_cache = _spec_probs_jit(
                draft_params, jnp.asarray([[tok]], jnp.int32), d_cache,
                cfg=draft_cfg, temperature=gen.temperature, top_p=gen.top_p,
                top_k=gen.top_k, apply_top_p=gen.top_p < 1.0,
            )
            q_rows.append(qp[0, -1])
            # graftlint: disable=host-sync-in-hot-path(sampled accept chain is host-side by design; see the future-work note above)
            tok = int(np.asarray(jax.random.categorical(
                next_key(), jnp.log(jnp.maximum(qp[0, -1], 1e-30))
            )))
            drafts.append(tok)
        base_t = int(np.asarray(t_cache["index"]))      # emitted length - 1 (pending unwritten)  # graftlint: disable=host-sync-in-hot-path(rewind bookkeeping; 4-byte reads once per round)
        base_d = int(np.asarray(d_cache["index"])) - (k - 1)  # draft wrote pending + drafts[:-1]  # graftlint: disable=host-sync-in-hot-path(rewind bookkeeping; 4-byte reads once per round)
        # 2. ONE target dispatch (T=k): verify pending + ALL proposals. Position i of the
        # output is the target's prediction after input i — it checks drafts[i] for
        # i < k-1, and position k-1 (after the last proposal) backs the bonus token on
        # full acceptance.
        pp, t_cache = _spec_probs_jit(
            target_params, jnp.asarray([[pending, *drafts]], jnp.int32), t_cache,
            cfg=target_cfg, temperature=gen.temperature, top_p=gen.top_p,
            top_k=gen.top_k, apply_top_p=gen.top_p < 1.0,
        )
        # 3. stochastic prefix acceptance: accept proposal n w.p. min(1, p/q);
        # first rejection re-draws from the residual and ends the round.
        n = 0
        correction = None
        while n < k - 1:
            acc, token = speculative_accept(
                pp[0, n], q_rows[n], drafts[n], next_key()
            )
            if not bool(np.asarray(acc)):  # graftlint: disable=host-sync-in-hot-path(accept verdict must reach the host to end the round)
                correction = int(np.asarray(token))  # graftlint: disable=host-sync-in-hot-path(rejected-draft correction token crosses to host once)
                break
            n += 1
        if correction is None:  # full acceptance: bonus token from the target's own row
            # graftlint: disable=host-sync-in-hot-path(bonus-token draw; one 4-byte read per fully-accepted round)
            correction = int(np.asarray(jax.random.categorical(
                next_key(), jnp.log(jnp.maximum(pp[0, k - 1], 1e-30))
            )))
        emitted = drafts[:n] + [correction]  # correction becomes the new pending token
        # 4. rewind to written-emitted length: target wrote pending+accepted (base_t+1+n);
        # draft wrote the same prefix (its extra proposal writes are invalidated).
        t_cache = _cache_rewind(t_cache, base_t + 1 + n)
        if n == k - 1:
            # Full acceptance: the draft never processed its own last proposal (it wrote
            # pending + drafts[:-1]); catch it up with one cheap draft step so the next
            # round's cache has no invalid hole. Its output is discarded.
            d_cache = _cache_rewind(d_cache, base_d + n)
            _, d_cache = _spec_forward_jit(
                draft_params, jnp.asarray([[drafts[-1]]], jnp.int32), d_cache, cfg=draft_cfg
            )
        else:
            d_cache = _cache_rewind(d_cache, base_d + 1 + n)
        pending = emitted[-1]
        for tok in emitted:
            out.append(tok)
            if len(out) >= max_new_tokens or (
                eos_token_id is not None and tok == eos_token_id
            ):
                return finish()
    return finish()
