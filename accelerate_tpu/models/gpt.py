"""GPT family (GPT-2 / GPT-J / GPT-NeoX shaped) — the reference's inference-baseline models.

Every published reference baseline is a GPT-family model (GPT-J-6B, GPT-NeoX-20B —
``/root/reference/benchmarks/big_model_inference/README.md:25-37``), so the framework ships
the family natively: same functional contract as ``llama.py`` (init_params / forward /
loss_fn / partition_specs / cached generate), with the GPT architectural differences:

- LayerNorm with bias (not RMSNorm); biased projections.
- GELU MLP (not SwiGLU) — 2 matmuls per MLP instead of 3.
- Positions: learned embeddings (``pos="learned"``, GPT-2) or rotary (GPT-J/NeoX).
- Optional parallel residual (``parallel_residual``, GPT-J/NeoX): attention and MLP both
  read the same layernorm and add into the residual together — one fewer serial dependency,
  which on TPU lets XLA overlap the two matmul chains.

Sharding: Megatron column/row layout identical to llama's, composable with fsdp/ZeRO.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..utils.constants import BATCH_AXES, FSDP_AXIS, SEQUENCE_AXIS, TENSOR_AXIS
from ..utils.jax_compat import current_abstract_mesh

__all__ = [
    "GPTConfig",
    "CONFIGS",
    "init_params",
    "forward",
    "loss_fn",
    "score",
    "perplexity",
    "partition_specs",
    "forward_pp",
    "loss_fn_pp",
    "generate_speculative",
    "head_logits",
    "init_cache",
    "init_paged_cache",
    "forward_cached",
    "forward_slots",
    "forward_slots_paged",
    "generate",
    "generate_streamed",
    "num_params",
]


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    max_seq: int = 2048
    pos: str = "learned"          # "learned" (gpt2) | "rotary" (gpt-j/neox)
    rope_theta: float = 10000.0
    rotary_dim: Optional[int] = None  # partial rotary: rope the first N dims only
                                      # (gpt-j rotary_dim, neox rotary_pct); None = full
    rope_style: str = "half"      # "half" (neox rotate-half) | "interleaved" (gpt-j)
    parallel_residual: bool = False  # gpt-j/neox style
    activation: str = "gelu_new"  # "gelu_new" (gpt2/gpt-j tanh) | "gelu" (neox exact) | "relu" (OPT)
    lm_head_bias: bool = False    # gpt-j's lm_head carries a bias
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # "auto": flash on TPU, xla elsewhere. "ring"/"ulysses"/"allgather": sequence-
    # parallel attention over an sp mesh axis (same dispatcher as llama; packing
    # composes). sp modes train under pp too — loss_fn_pp goes manual over sp exactly
    # like llama's sp_pipeline (forward_pp's hidden-state path is the one sp×pp hole).
    attn_impl: str = "auto"
    remat: bool = True
    remat_policy: str = "full"            # "full" | "dots" | "offload" (see models/common.py)
    remat_prevent_cse: Optional[bool] = None  # None = auto (False under scan_layers)
    scan_layers: bool = False
    scan_unroll: int = 1                  # lax.scan unroll for the layer stack
    tie_embeddings: bool = True   # gpt2 ties lm_head to wte
    kv_quant: bool = False        # int8 KV cache (see models/common.py kv helpers)
    # "auto": dense/chunked CE. "fused": ops/fused_xent Pallas kernel (single-device);
    # "fused_dp"/"fused_tp": the batch-sharded / vocab-sharded multi-chip kernels (same
    # contract as llama, via common.ce_sum_dispatch). A biased lm_head (gpt-j) always
    # falls back to the dense/chunked path — the kernels have no bias term.
    loss_impl: str = "auto"
    loss_chunk: int = 0           # chunked-CE length: 0 auto, -1 off (common.resolve_loss_chunk)


CONFIGS = {
    "gpt2": GPTConfig(),
    "gpt2-xl": GPTConfig(d_model=1600, n_layers=48, n_heads=25, d_ff=6400),
    "gptj-6b": GPTConfig(
        vocab_size=50400, d_model=4096, n_layers=28, n_heads=16, d_ff=16384,
        pos="rotary", rotary_dim=64, rope_style="interleaved",
        parallel_residual=True, tie_embeddings=False, lm_head_bias=True,
    ),
    "gpt-neox-20b": GPTConfig(
        vocab_size=50432, d_model=6144, n_layers=44, n_heads=64, d_ff=24576,
        pos="rotary", rotary_dim=24, rope_style="half", activation="gelu",
        parallel_residual=True, tie_embeddings=False,
    ),
    # OPT-30B shape (the reference's biggest offload baseline, README.md:36-37): OPT is a
    # plain GPT decoder with learned positions, sequential residual, ReLU-family MLP —
    # architecturally GPT-2-shaped at 30B scale.
    "opt-30b": GPTConfig(
        vocab_size=50272, d_model=7168, n_layers=48, n_heads=56, d_ff=28672,
        pos="learned", activation="relu", tie_embeddings=True, max_seq=2048,
    ),
    "tiny": GPTConfig(
        vocab_size=256, d_model=128, n_layers=2, n_heads=4, d_ff=256, max_seq=128,
        remat=False,
    ),
}


def _layer_params(cfg: GPTConfig, key) -> dict:
    k = jax.random.split(key, 4)
    D, F = cfg.d_model, cfg.d_ff
    s = 1.0 / math.sqrt(D)
    return {
        "ln_attn": {"scale": jnp.ones((D,), jnp.float32), "bias": jnp.zeros((D,), jnp.float32)},
        "wqkv": jax.random.normal(k[0], (D, 3 * D), jnp.float32) * s,
        "b_qkv": jnp.zeros((3 * D,), jnp.float32),
        "wo": jax.random.normal(k[1], (D, D), jnp.float32) * s,
        "b_o": jnp.zeros((D,), jnp.float32),
        "ln_mlp": {"scale": jnp.ones((D,), jnp.float32), "bias": jnp.zeros((D,), jnp.float32)},
        "w_up": jax.random.normal(k[2], (D, F), jnp.float32) * s,
        "b_up": jnp.zeros((F,), jnp.float32),
        "w_down": jax.random.normal(k[3], (F, D), jnp.float32) / math.sqrt(F),
        "b_down": jnp.zeros((D,), jnp.float32),
    }


def init_params(cfg: GPTConfig, key: Optional[jax.Array] = None) -> dict:
    if key is None:
        key = jax.random.PRNGKey(0)  # graftlint: disable=rng-key-reuse(deterministic default init; callers pass a key for real entropy)
    keys = jax.random.split(key, cfg.n_layers + 3)
    scale = 1.0 / math.sqrt(cfg.d_model)
    params: dict = {
        "wte": jax.random.normal(keys[0], (cfg.vocab_size, cfg.d_model), jnp.float32) * scale,
        "layers": [_layer_params(cfg, keys[i + 2]) for i in range(cfg.n_layers)],
        "ln_f": {
            "scale": jnp.ones((cfg.d_model,), jnp.float32),
            "bias": jnp.zeros((cfg.d_model,), jnp.float32),
        },
    }
    if cfg.pos == "learned":
        params["wpe"] = (
            jax.random.normal(keys[1], (cfg.max_seq, cfg.d_model), jnp.float32) * scale * 0.1
        )
    if cfg.scan_layers:
        params["layers"] = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *params["layers"])
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(keys[-1], (cfg.d_model, cfg.vocab_size), jnp.float32) * scale
        )
        if cfg.lm_head_bias:
            params["b_lm_head"] = jnp.zeros((cfg.vocab_size,), jnp.float32)
    return params


def partition_specs(cfg: GPTConfig, pp: bool = False, virtual_stages: int = 1) -> dict:
    """Megatron layout: qkv/up column-parallel, o/down row-parallel, vocab over (tp, fsdp).

    ``pp=True``: layer specs gain the stage-stacked leading dims sharded over ``pp``
    (``parallel.pp.split_params_into_stages`` layout) and embed/head fold the pipeline
    axis into the vocab sharding — same design as ``llama.partition_specs(pp=True)``.
    ``virtual_stages=v > 1``: the interleaved [v, n, L/(n·v), ...] layout (pp on dim 1)."""
    ln = {"scale": P(), "bias": P()}
    layer = {
        "ln_attn": dict(ln),
        "wqkv": P(None, TENSOR_AXIS),
        "b_qkv": P(TENSOR_AXIS),
        "wo": P(TENSOR_AXIS, None),
        "b_o": P(),
        "ln_mlp": dict(ln),
        "w_up": P(None, TENSOR_AXIS),
        "b_up": P(TENSOR_AXIS),
        "w_down": P(TENSOR_AXIS, None),
        "b_down": P(),
    }
    from ..utils.constants import PIPELINE_AXIS

    if pp:
        if not cfg.scan_layers:
            raise ValueError("pipeline parallelism requires cfg.scan_layers=True")
        from ..parallel.pp import stage_spec_prefix

        layer = jax.tree_util.tree_map(
            lambda spec: P(*stage_spec_prefix(virtual_stages), *spec),
            layer,
            is_leaf=lambda s: isinstance(s, P),
        )
        layers: Any = layer
    elif cfg.scan_layers:
        layer = jax.tree_util.tree_map(
            lambda spec: P(None, *spec), layer, is_leaf=lambda s: isinstance(s, P)
        )
        layers = layer
    else:
        layers = [dict(layer) for _ in range(cfg.n_layers)]
    vocab_axes = (TENSOR_AXIS, FSDP_AXIS, PIPELINE_AXIS) if pp else (TENSOR_AXIS, FSDP_AXIS)
    specs = {
        "wte": P(vocab_axes, None),
        "layers": layers,
        "ln_f": dict(ln),
    }
    if cfg.pos == "learned":
        specs["wpe"] = P(None, None)
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, vocab_axes)
        if cfg.lm_head_bias:
            specs["b_lm_head"] = P(vocab_axes)
    return specs


def _layer_norm(x, ln, eps):
    xf = x.astype(jnp.float32)
    mean = xf.mean(axis=-1, keepdims=True)
    var = xf.var(axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (out * ln["scale"] + ln["bias"]).astype(x.dtype)


def _rope(x, positions, theta, style="half", rotary_dim=None):
    """Rotary embedding, both lineages: "half" rotates [x1|x2] halves (GPT-NeoX
    rotate_half), "interleaved" rotates (even, odd) pairs (GPT-J rotate_every_two).
    ``rotary_dim`` < head_dim ropes only the leading dims (gpt-j 64/256, neox pct)."""
    hd = x.shape[-1]
    rd = rotary_dim or hd
    x_pass = None
    if rd < hd:
        x, x_pass = x[..., :rd], x[..., rd:]
    freqs = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    xf = x.astype(jnp.float32)
    if style == "interleaved":
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
        rot = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        out = rot.reshape(*x.shape)
    else:
        x1, x2 = jnp.split(xf, 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    out = out.astype(x.dtype)
    return out if x_pass is None else jnp.concatenate([out, x_pass], axis=-1)


def _qkv(h, layer, positions, cfg: GPTConfig):
    B, T, D = h.shape
    hd = cfg.d_model // cfg.n_heads
    qkv = h @ layer["wqkv"].astype(h.dtype) + layer["b_qkv"].astype(h.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, T, cfg.n_heads, hd)
    k = k.reshape(B, T, cfg.n_heads, hd)
    v = v.reshape(B, T, cfg.n_heads, hd)
    if cfg.pos == "rotary":
        q = _rope(q, positions, cfg.rope_theta, cfg.rope_style, cfg.rotary_dim)
        k = _rope(k, positions, cfg.rope_theta, cfg.rope_style, cfg.rotary_dim)
    return q, k, v


def _attn_out(probs_v, layer, cfg: GPTConfig, B, T):
    out = probs_v.reshape(B, T, cfg.d_model)
    return out @ layer["wo"].astype(out.dtype) + layer["b_o"].astype(out.dtype)


def _attention_xla(q, k, v, mask):
    """gpt's reference attention path (H == K, no GQA): q/k/v [B,S,H,hd]."""
    hd = q.shape[-1]
    scores = jnp.einsum("bshd,bthd->bhst", q, k) / math.sqrt(hd)
    scores = jnp.where(mask[:, None, :, :], scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def _attention(q, k, v, mask, cfg: "GPTConfig", segment_ids=None):
    """Family attention via the shared dispatcher (``common.attention_dispatch``):
    flash on TPU (segment ids in-kernel for packed rows), the sp modes over an sp
    mesh, xla fallback elsewhere."""
    from .common import attention_dispatch

    return attention_dispatch(
        q, k, v, mask, impl=cfg.attn_impl, sm_scale=1.0 / math.sqrt(q.shape[-1]),
        segment_ids=segment_ids, xla_attention=_attention_xla,
    )


def _mlp(h, layer, dtype, activation="gelu_new"):
    up = h @ layer["w_up"].astype(dtype) + layer["b_up"].astype(dtype)
    if activation == "relu":
        act = jax.nn.relu(up)  # OPT's MLP nonlinearity
    else:
        act = jax.nn.gelu(up, approximate=(activation == "gelu_new"))
    return act @ layer["w_down"].astype(dtype) + layer["b_down"].astype(dtype)


def _block(x, layer, positions, mask, cfg: GPTConfig, segment_ids=None):
    B, T, D = x.shape
    h = _layer_norm(x, layer["ln_attn"], cfg.norm_eps)
    q, k, v = _qkv(h, layer, positions, cfg)
    attn = _attn_out(_attention(q, k, v, mask, cfg, segment_ids), layer, cfg, B, T)
    if cfg.parallel_residual:
        # GPT-J/NeoX: MLP reads the SAME pre-norm stream; both branches add at once.
        h2 = _layer_norm(x, layer["ln_mlp"], cfg.norm_eps)
        return x + attn + _mlp(h2, layer, x.dtype, cfg.activation)
    x = x + attn
    h2 = _layer_norm(x, layer["ln_mlp"], cfg.norm_eps)
    return x + _mlp(h2, layer, x.dtype, cfg.activation)


def _embed(params, tokens, positions, cfg: GPTConfig):
    x = params["wte"].astype(cfg.dtype)[tokens]
    if cfg.pos == "learned":
        x = x + params["wpe"].astype(cfg.dtype)[positions]
    return x


def forward(
    params: dict,
    tokens: jax.Array,
    cfg: GPTConfig,
    positions: Optional[jax.Array] = None,
    shard_activations: bool = True,
    segment_ids: Optional[jax.Array] = None,
    return_hidden: bool = False,
) -> jax.Array:
    """Causal LM: tokens [B, S] → logits [B, S, V] fp32 (post-ln_f hidden states when
    ``return_hidden`` — the fused-CE path applies the head inside its kernel).

    ``segment_ids`` (sample packing, ``ops/packing.py``): attention restricts to the
    per-segment causal block diagonal and positions default to per-segment restarts —
    learned position embeddings then index 0.. within each packed sequence, rotary
    variants restart their phase, matching unpacked behavior exactly.
    """
    from .llama import _maybe_shard, segment_mask, segment_positions

    B, S = tokens.shape
    if positions is None:
        positions = (
            segment_positions(segment_ids)
            if segment_ids is not None
            else jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        )
    x = _embed(params, tokens, positions, cfg)
    if shard_activations:
        x = _maybe_shard(x, P(BATCH_AXES, SEQUENCE_AXIS, None))
    mask = (
        segment_mask(segment_ids)
        if segment_ids is not None
        else jnp.tril(jnp.ones((S, S), dtype=jnp.bool_))[None, :, :]
    )
    from .common import remat_wrap

    block = remat_wrap(
        _block, remat=cfg.remat, policy=cfg.remat_policy,
        prevent_cse=cfg.remat_prevent_cse, scan_layers=cfg.scan_layers, static_argnums=(4,),
    )
    if cfg.scan_layers:
        def body(carry, layer):
            out = block(carry, layer, positions, mask, cfg, segment_ids)
            if shard_activations:
                out = _maybe_shard(out, P(BATCH_AXES, SEQUENCE_AXIS, None))
            return out, None

        x, _ = jax.lax.scan(body, x, params["layers"], unroll=cfg.scan_unroll)
    else:
        for layer in params["layers"]:
            x = block(x, layer, positions, mask, cfg, segment_ids)
    x = _layer_norm(x, params["ln_f"], cfg.norm_eps)
    if return_hidden:
        return x
    return head_logits(x, params, cfg)


def _head_weight(params: dict, cfg: GPTConfig) -> jax.Array:
    return params["wte"].T if cfg.tie_embeddings else params["lm_head"]


def head_logits(x, params: dict, cfg: GPTConfig) -> jax.Array:
    """Final-hidden → fp32 logits incl. the optional lm_head bias — family pipeline
    contract (see ``llama.head_logits``)."""
    logits = (x @ _head_weight(params, cfg).astype(cfg.dtype)).astype(jnp.float32)
    if cfg.lm_head_bias and "b_lm_head" in params:
        logits = logits + params["b_lm_head"].astype(jnp.float32)
    return logits


def loss_fn(params: dict, batch: dict, cfg: GPTConfig, rng=None) -> jax.Array:
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    user_mask = batch["mask"][:, 1:].astype(jnp.float32) if "mask" in batch else None
    if "segment_ids" in batch:
        # Packed rows: targets valid only when the next slot continues the SAME segment.
        from .llama import packed_target_mask

        seg = batch["segment_ids"]
        m = packed_target_mask(seg)
        if user_mask is not None:
            m = m * user_mask
        positions = batch["positions"][:, :-1] if "positions" in batch else None
        seg_in = seg[:, :-1]
    else:
        m = user_mask
        positions = None
        seg_in = None
    from .common import ce_sum_dispatch, resolve_loss_chunk

    x = forward(
        params, inputs, cfg, positions=positions, segment_ids=seg_in,
        return_hidden=True,
    )
    mask2d = m if m is not None else jnp.ones(targets.shape, jnp.float32)
    bias = params.get("b_lm_head") if cfg.lm_head_bias else None
    total = ce_sum_dispatch(
        x, _head_weight(params, cfg), targets, mask2d,
        loss_impl=cfg.loss_impl, dtype=cfg.dtype,
        chunk=resolve_loss_chunk(cfg.loss_chunk, targets.shape[1], cfg.vocab_size),
        bias=bias,
    )
    return total / jnp.maximum(mask2d.sum(), 1.0)


# --------------------------------------------------------------- pipeline-parallel training
def _pp_stage_fn(cfg: GPTConfig, S: int, packed: bool = False, sp_manual: bool = False):
    """One pipeline stage body (gpt analog of ``llama._pp_stage_fn``): scan this stage's
    blocks over one microbatch [B_m, S, D]; positions/causal mask rebuilt locally.
    ``packed``: 3-arg form taking the pipeline's ``{"positions", "segment_ids"}`` side
    constants (sample packing — block-diagonal per-segment attention). ``sp_manual``
    (sp×pp): the pipeline's shard_map is manual over sp too, activations arrive
    sequence-sliced [B_m, S/sp, D]; attention dispatches to the flat ring/ulysses
    collectives inside ``_attention`` (rotary variants rebuild the slice's GLOBAL
    positions; gpt2's learned positions were already added at the embed, outside the
    pipeline, on the full sequence)."""
    from .common import remat_wrap

    block = remat_wrap(
        _block, remat=cfg.remat, policy=cfg.remat_policy,
        prevent_cse=cfg.remat_prevent_cse, scan_layers=True, static_argnums=(4,),
    )

    def body_scan(x, stage_layers, pos, mask, seg=None):
        def body(carry, layer):
            return block(carry, layer, pos, mask, cfg, seg), None

        out, _ = jax.lax.scan(body, x, stage_layers)
        return out

    if packed and sp_manual:
        # packing × sp × pp: activations AND the side constants arrive sequence-sliced
        # ([B_m, S/sp, D] and [B_m, S/sp] — loss_fn_pp passes the matching side_spec).
        # No mask — the sp kernels take the LOCAL segment slice (ring rotates the
        # kv-side ids with its kv block); positions are the pre-computed per-segment
        # restarts (global array, sliced).
        def stage_fn(stage_layers, x, side):
            return body_scan(
                x, stage_layers, side["positions"], None, side["segment_ids"]
            )

        return stage_fn

    if packed:
        from .llama import segment_mask

        def stage_fn(stage_layers, x, side):
            seg = side["segment_ids"]
            return body_scan(x, stage_layers, side["positions"], segment_mask(seg), seg)

        return stage_fn

    if sp_manual:
        # sp×pp: x arrives SEQUENCE-SLICED; rotary needs the slice's global positions,
        # and the sp kernels handle causality with global offsets in-kernel (no mask).
        def stage_fn(stage_layers, x):
            S_loc = x.shape[1]
            offs = jax.lax.axis_index(SEQUENCE_AXIS) * S_loc
            pos = jnp.broadcast_to(
                offs + jnp.arange(S_loc, dtype=jnp.int32), (x.shape[0], S_loc)
            )
            return body_scan(x, stage_layers, pos, None)

        return stage_fn

    def stage_fn(stage_layers, x):
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (x.shape[0], S))
        mask = jnp.tril(jnp.ones((S, S), dtype=jnp.bool_))[None, :, :]
        return body_scan(x, stage_layers, pos, mask)

    return stage_fn


def _guard_sp_under_pp(cfg: "GPTConfig", mesh) -> None:
    """``forward_pp``'s GPipe hidden-state path does not go manual over sp: an sp
    attention mode inside its shard_map would nest ``make_sp_attention``'s own
    shard_map, which fails to lower on the backward. Training composes sp×pp through
    ``loss_fn_pp`` (which routes through the manual-over-sp ``make_pipeline_loss_fn``
    exactly like llama); fail loudly here with the supported alternatives."""
    from .common import sp_active

    if cfg.attn_impl in ("ring", "ulysses", "ulysses_ppermute", "allgather") and (
        sp_active(mesh) or sp_active(current_abstract_mesh())
    ):
        raise NotImplementedError(
            "gpt forward_pp does not go manual over sp. For sp x pp training use "
            "loss_fn_pp (any schedule); for this forward, drop the pp axis or use "
            "attn_impl='auto'."
        )


def forward_pp(
    params: dict,
    tokens: jax.Array,
    cfg: GPTConfig,
    mesh,
    num_microbatches: Optional[int] = None,
    shard_activations: bool = True,
    segment_ids: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Causal LM hidden states with the transformer blocks as a GPipe pipeline over
    ``pp`` (reference Megatron engine runs GPT with pp; its own pipelining is
    inference-only). ``params["layers"]`` stage-stacked [n_stages, L/n, ...]; embed and
    ln_f/head outside the pipe, vocab-sharded over (tp, fsdp, pp) by
    ``partition_specs(pp=True)``. Dense attention path (no packing)."""
    _guard_sp_under_pp(cfg, mesh)
    from .llama import _maybe_shard
    from ..parallel.pp import make_pipeline_fn

    B, S = tokens.shape
    packed = segment_ids is not None
    if positions is None:
        if packed:
            from .llama import segment_positions

            # Continuous arange positions would run learned/rotary positions across
            # packed segment boundaries.
            positions = segment_positions(segment_ids)
        else:
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    side = {"positions": positions, "segment_ids": segment_ids} if packed else None
    x = _embed(params, tokens, positions, cfg)
    if shard_activations:
        x = _maybe_shard(x, P(BATCH_AXES, None, None))
    pipe = make_pipeline_fn(
        mesh, _pp_stage_fn(cfg, S, packed=packed), num_microbatches=num_microbatches
    )
    x = pipe(params["layers"], x, side=side)
    return _layer_norm(x, params["ln_f"], cfg.norm_eps)


def _ce_sum_gpt(x, head, bias, targets, mask, cfg: GPTConfig) -> jax.Array:
    """SUM-style CE from post-ln_f hidden states, honoring the optional lm_head bias —
    the ONE copy of the gpt head math shared by loss_fn, loss_fn_pp (both schedules) and
    the 1F1B head so the paths cannot drift. Routes through ``common.ce_sum_dispatch``,
    so every ``loss_impl`` (incl. the fused_dp/fused_tp multi-chip kernels) works; a
    non-None bias falls back to the dense/chunked path (the kernels lack a bias term)."""
    from .common import ce_sum_dispatch, resolve_loss_chunk

    return ce_sum_dispatch(
        x, head, targets, mask, loss_impl=cfg.loss_impl, dtype=cfg.dtype,
        chunk=resolve_loss_chunk(cfg.loss_chunk, x.shape[1], cfg.vocab_size),
        bias=bias,
    )


def _head_ce_sum_gpt(hp: dict, y: jax.Array, ex: dict, cfg: GPTConfig) -> jax.Array:
    """SUM-style ln_f + head CE over one microbatch group (1F1B last-stage loss)."""
    x = _layer_norm(y, hp["ln_f"], cfg.norm_eps)
    return _ce_sum_gpt(x, hp["head"], hp.get("b_lm_head"), ex["targets"], ex["mask"], cfg)


def loss_fn_pp(
    params: dict,
    batch: dict,
    cfg: GPTConfig,
    mesh,
    num_microbatches: Optional[int] = None,
    rng=None,
    schedule: str = "gpipe",
    virtual_stages: int = 1,
) -> jax.Array:
    """Pipeline-parallel next-token CE for the gpt family (same contract as
    ``llama.loss_fn_pp``, including ``virtual_stages`` — the interleaved virtual
    pipeline, 1f1b only). Every ``loss_impl`` works — ln_f + the CE head run OUTSIDE
    the pipeline (1F1B) or after it (GPipe) on the full batch, ordinary GSPMD, so the
    fused kernel variants dispatch exactly as on the non-pipelined path. Sample packing
    (``segment_ids``) rides the pipeline as per-microbatch side constants, exactly like
    ``llama.loss_fn_pp``. sp attention modes (ring/ulysses/allgather over an active sp
    mesh) train inside the pipeline exactly like llama's sp_pipeline: the pipeline's
    shard_map goes manual over sp, activations ride sequence-sliced, and the stage
    body issues the collectives flat (no shard_map nesting)."""
    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"schedule={schedule!r}: expected 'gpipe' or '1f1b'")
    if virtual_stages > 1 and schedule != "1f1b":
        raise NotImplementedError(
            "virtual_stages > 1 requires schedule='1f1b' (parallel/pp.py)"
        )
    from .common import resolve_sp_pipeline

    sp_pipeline, cfg = resolve_sp_pipeline(cfg, mesh, schedule, virtual_stages)
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    B, S = inputs.shape
    if "segment_ids" in batch:
        from .llama import packed_target_mask, segment_positions

        seg = batch["segment_ids"]
        mask = packed_target_mask(seg)
        if "mask" in batch:
            mask = mask * batch["mask"][:, 1:].astype(jnp.float32)
        positions = (
            batch["positions"][:, :-1]
            if "positions" in batch
            else segment_positions(seg[:, :-1])
        )
        side = {"positions": positions, "segment_ids": seg[:, :-1]}
    else:
        mask = (
            batch["mask"][:, 1:].astype(jnp.float32)
            if "mask" in batch
            else jnp.ones((B, S), jnp.float32)
        )
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        side = None
    denom = jnp.maximum(mask.sum(), 1.0)
    if schedule == "1f1b" or sp_pipeline:
        from ..parallel.pp import make_pipeline_loss_fn

        hp = {"ln_f": params["ln_f"], "head": _head_weight(params, cfg)}
        if cfg.lm_head_bias and "b_lm_head" in params:
            hp["b_lm_head"] = params["b_lm_head"]
        pipe_loss = make_pipeline_loss_fn(
            mesh, _pp_stage_fn(cfg, S, packed=side is not None, sp_manual=sp_pipeline),
            lambda h, y, ex: _head_ce_sum_gpt(h, y, ex, cfg),
            num_microbatches=num_microbatches, schedule=schedule,
            virtual_stages=virtual_stages,
            # sp×pp: microbatch layout [M, B_m, S, D] → sequence on dim 2; packed side
            # constants slice the same way (same contract as llama.loss_fn_pp).
            act_spec=P(None, None, SEQUENCE_AXIS, None) if sp_pipeline else None,
            extra_manual_axes=(SEQUENCE_AXIS,) if sp_pipeline else (),
            side_spec=(
                {"positions": P(None, None, SEQUENCE_AXIS),
                 "segment_ids": P(None, None, SEQUENCE_AXIS)}
                if (sp_pipeline and side is not None) else None
            ),
        )
        x = _embed(params, inputs, positions, cfg)
        total = pipe_loss(
            params["layers"], hp, x, {"targets": targets, "mask": mask}, side=side
        )
        return total / denom
    x = forward_pp(
        params, inputs, cfg, mesh, num_microbatches=num_microbatches,
        segment_ids=side["segment_ids"] if side else None,
        positions=positions if side else None,
    )
    bias = params.get("b_lm_head") if cfg.lm_head_bias else None
    return _ce_sum_gpt(x, _head_weight(params, cfg), bias, targets, mask, cfg) / denom


def score(params: dict, tokens, cfg: GPTConfig, mask=None) -> jax.Array:
    """Per-token log-probabilities log p(token[t+1] | tokens[:t+1]) → [B, S-1] fp32
    (same contract as ``llama.score``; masked target positions score 0.0)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits = forward(params, inputs, cfg, shard_activations=False)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1).squeeze(-1)
    if mask is not None:
        ll = ll * mask[:, 1:].astype(ll.dtype)
    return ll


def perplexity(params: dict, tokens, cfg: GPTConfig, mask=None) -> jax.Array:
    """exp(mean negative log-likelihood over real target positions) — scalar fp32."""
    ll = score(params, tokens, cfg, mask)
    denom = jnp.maximum(mask[:, 1:].sum(), 1) if mask is not None else ll.size
    return jnp.exp(-ll.sum() / denom)


# ----------------------------------------------------------------------- cached generation
def init_cache(
    cfg: GPTConfig, batch_size: int, max_len: int, dtype=None,
    quantized: Optional[bool] = None,
) -> dict:
    from .common import kv_planes

    quantized = cfg.kv_quant if quantized is None else quantized
    dtype = dtype or cfg.dtype
    hd = cfg.d_model // cfg.n_heads
    one = lambda: kv_planes(batch_size, max_len, cfg.n_heads, hd, dtype, quantized)  # noqa: E731
    layers = (
        jax.tree_util.tree_map(lambda x: jnp.broadcast_to(x, (cfg.n_layers, *x.shape)), one())
        if cfg.scan_layers
        else [one() for _ in range(cfg.n_layers)]
    )
    return {
        "layers": layers,
        "valid": jnp.zeros((batch_size, max_len), jnp.bool_),
        "index": jnp.zeros((), jnp.int32),
    }


def init_paged_cache(
    cfg: GPTConfig, batch_size: int, max_len: int, num_pages: int, page_size: int,
    dtype=None, quantized: Optional[bool] = None,
) -> dict:
    """Paged pool cache, llama-identical contract (``llama.init_paged_cache``):
    ``{"layers": [{k,v: [P,ps,H,hd]}, ...], "valid": [B,max_len]}`` — page ownership
    lives in the host-side ``paged_kv.BlockManager``."""
    from .common import paged_kv_planes

    quantized = cfg.kv_quant if quantized is None else quantized
    dtype = dtype or cfg.dtype
    hd = cfg.d_model // cfg.n_heads
    one = lambda: paged_kv_planes(  # noqa: E731
        num_pages, page_size, cfg.n_heads, hd, dtype, quantized
    )
    layers = (
        jax.tree_util.tree_map(lambda x: jnp.broadcast_to(x, (cfg.n_layers, *x.shape)), one())
        if cfg.scan_layers
        else [one() for _ in range(cfg.n_layers)]
    )
    return {
        "layers": layers,
        "valid": jnp.zeros((batch_size, max_len), jnp.bool_),
    }


def _attention_cached(q, new_k, new_v, positions, valid, cfg: GPTConfig):
    """Attention probabilities [B,H,T,C] for q [B,T,H,hd] against the full dense
    cache view [B,C,H,hd] (``valid`` [B,C] marks live keys) — the one copy of gpt's
    cached-attention masking/softmax, shared by the dense write path and the paged
    gather fallback (bitwise parity between them)."""
    C = new_k.shape[1]
    hd = q.shape[-1]
    scores = jnp.einsum("bthd,bchd->bhtc", q, new_k) / math.sqrt(hd)
    causal = jnp.arange(C)[None, None, :] <= positions[:, :, None]
    m = (causal & valid[:, None, :])[:, None, :, :]
    return jax.nn.softmax(
        jnp.where(m, scores, jnp.finfo(scores.dtype).min).astype(jnp.float32), axis=-1
    ).astype(q.dtype)


def _block_cached(x, layer, kv, index, positions, valid, cfg: GPTConfig, paged=None):
    from .common import paged_attention_dispatch, read_kv, write_kv, write_kv_paged

    B, T, D = x.shape
    h = _layer_norm(x, layer["ln_attn"], cfg.norm_eps)
    q, k, v = _qkv(h, layer, positions, cfg)
    hd = q.shape[-1]
    if paged is not None:
        # Paged pool layout (llama._block_cached's paged contract): scatter writes
        # through the precomputed physical (page, slot) grid, read via the paged
        # dispatch (Pallas kernel on TPU, gather into this family's own
        # _attention_cached on CPU).
        tables, pages, offs, start_pos, page_size = paged
        new_kv = {**write_kv_paged(kv, "k", k, pages, offs),
                  **write_kv_paged(kv, "v", v, pages, offs)}
        probs_v = paged_attention_dispatch(
            q, new_kv, tables, start_pos, valid, page_size=page_size,
            sm_scale=1.0 / math.sqrt(hd), dtype=cfg.dtype,
            dense_attention=lambda ck, cv: jnp.einsum(
                "bhtc,bchd->bthd",
                _attention_cached(q, ck, cv, positions, valid, cfg), cv,
            ),
        )
        attn = _attn_out(probs_v, layer, cfg, B, T)
    else:
        new_kv = {**write_kv(kv, "k", k, index), **write_kv(kv, "v", v, index)}
        new_k = read_kv(new_kv, "k", cfg.dtype)
        new_v = read_kv(new_kv, "v", cfg.dtype)
        probs = _attention_cached(q, new_k, new_v, positions, valid, cfg)
        attn = _attn_out(jnp.einsum("bhtc,bchd->bthd", probs, new_v), layer, cfg, B, T)
    if cfg.parallel_residual:
        h2 = _layer_norm(x, layer["ln_mlp"], cfg.norm_eps)
        out = x + attn + _mlp(h2, layer, x.dtype, cfg.activation)
    else:
        x = x + attn
        h2 = _layer_norm(x, layer["ln_mlp"], cfg.norm_eps)
        out = x + _mlp(h2, layer, x.dtype, cfg.activation)
    return out, new_kv


def forward_cached(
    params: dict,
    tokens: jax.Array,
    cache: dict,
    cfg: GPTConfig,
    token_mask: Optional[jax.Array] = None,
    last_only: bool = False,
) -> tuple[jax.Array, dict]:
    from .llama import _cache_advance

    B, T = tokens.shape
    index, positions, valid = _cache_advance(cache, tokens, token_mask)
    x = _embed(params, tokens, positions, cfg)
    if cfg.scan_layers:
        def body(carry, layer_and_kv):
            layer, kv = layer_and_kv
            out, new_kv = _block_cached(carry, layer, kv, index, positions, valid, cfg)
            return out, new_kv

        x, new_layers = jax.lax.scan(body, x, (params["layers"], cache["layers"]))
    else:
        new_layers = []
        for layer, kv in zip(params["layers"], cache["layers"]):
            x, new_kv = _block_cached(x, layer, kv, index, positions, valid, cfg)
            new_layers.append(new_kv)
    x = _layer_norm(x, params["ln_f"], cfg.norm_eps)
    if last_only:
        x = x[:, -1:, :]
    head = params["wte"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x @ head.astype(cfg.dtype)).astype(jnp.float32)
    if cfg.lm_head_bias and "b_lm_head" in params:
        logits = logits + params["b_lm_head"].astype(jnp.float32)
    return logits, {"layers": new_layers, "valid": valid, "index": index + T}


def forward_slots(
    params: dict,
    tokens: jax.Array,
    cache: dict,
    positions: jax.Array,
    cfg: GPTConfig,
    tables: Optional[jax.Array] = None,
    page_size: int = 0,
) -> tuple[jax.Array, dict]:
    """Per-slot cached forward, llama-identical contract (``llama.forward_slots``):
    ``tokens`` [B,T] written at each row's own slots ``positions[b] ..
    positions[b]+T-1`` → (logits fp32 [B,T,V], new cache). T == 1 is continuous-batching
    decode; T == k+1 is the batched speculative verify. Lets a gpt-family draft model
    ride the serving engine's speculative decoder (cross-family draft/target pairs share
    this contract through ``common.cached_decode_family``). ``tables``/``page_size``
    switch the KV side to the paged pool layout — one forward for both layouts."""
    from .common import paged_write_coords

    B, T = tokens.shape
    rows = jnp.arange(B)
    pos_grid = positions[:, None] + jnp.arange(T, dtype=positions.dtype)[None, :]
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
        paged = (tables, pages, offs, positions, page_size)
    x = _embed(params, tokens, pos_grid, cfg)
    if cfg.scan_layers:
        def body(carry, layer_and_kv):
            layer, kv = layer_and_kv
            out, new_kv = _block_cached(
                carry, layer, kv, positions, pos_grid, valid, cfg, paged=paged
            )
            return out, new_kv

        x, new_layers = jax.lax.scan(body, x, (params["layers"], cache["layers"]))
    else:
        new_layers = []
        for layer, kv in zip(params["layers"], cache["layers"]):
            x, new_kv = _block_cached(
                x, layer, kv, positions, pos_grid, valid, cfg, paged=paged
            )
            new_layers.append(new_kv)
    x = _layer_norm(x, params["ln_f"], cfg.norm_eps)
    head = params["wte"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x @ head.astype(cfg.dtype)).astype(jnp.float32)
    if cfg.lm_head_bias and "b_lm_head" in params:
        logits = logits + params["b_lm_head"].astype(jnp.float32)
    if paged is not None:
        return logits, {"layers": new_layers, "valid": valid}
    return logits, {"layers": new_layers, "valid": valid, "index": cache["index"]}


def forward_slots_paged(
    params: dict,
    tokens: jax.Array,
    cache: dict,
    tables: jax.Array,
    positions: jax.Array,
    cfg: GPTConfig,
    page_size: int,
) -> tuple[jax.Array, dict]:
    """:func:`forward_slots` over the paged pool cache, llama-identical contract
    (``llama.forward_slots_paged``) — a thin delegate into the ONE shared forward,
    so the dense and paged layouts cannot drift. Keeps a gpt-family draft/target
    viable on a paged serving engine."""
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
    cfg: GPTConfig,
    tables: Optional[jax.Array] = None,
    page_size: int = 0,
) -> tuple[dict, jax.Array, jax.Array]:
    """N T == 1 :func:`forward_slots` decode steps as ONE ``lax.scan``,
    llama-identical contract (``llama.forward_slots_multi``) — the serving
    engine's ``decode_steps=N`` super-step for a gpt-family model. See
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
    cfg: GPTConfig,
    tables: Optional[jax.Array] = None,
    page_size: int = 0,
):
    """N speculative draft→verify→accept rounds as ONE ``lax.scan``,
    llama-identical contract (``llama.forward_slots_spec_multi``) — each round's
    verify is a T == spec_k+1 :func:`forward_slots` call. See
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


def _make_gen_fns(cfg: GPTConfig, max_len: int):
    def prefill_fn(p, pr, pm):
        cache = init_cache(cfg, pr.shape[0], max_len)
        logits, cache = forward_cached(p, pr, cache, cfg, token_mask=pm, last_only=True)
        return logits[:, -1, :], cache

    def decode_fn(p, cache, token):
        logits, cache = forward_cached(p, token[:, None], cache, cfg)
        return logits[:, -1, :], cache

    return prefill_fn, decode_fn


# Stable (prefill, decode) closure identities per (cfg, bucketed max_len): generate_loop
# jit-caches by function identity, so fresh closures per call would recompile every time
# (same bounded-LRU pattern as llama._GEN_FNS).
from collections import OrderedDict  # noqa: E402

_GEN_FNS: OrderedDict = OrderedDict()
_GEN_FNS_MAX = 16


def generate(
    params: dict,
    prompt: jax.Array,
    cfg: GPTConfig,
    gen=None,
    rng: Optional[jax.Array] = None,
    prompt_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Autoregressive generation (one compiled prefill + decode scan), llama-identical API."""
    from ..generation import GenerationConfig, generate_loop

    gen = gen or GenerationConfig()
    prompt = jnp.asarray(prompt, jnp.int32)
    if prompt_mask is None:
        prompt_mask = jnp.ones(prompt.shape, jnp.bool_)
    max_len = -(-(prompt.shape[1] + gen.max_new_tokens) // 64) * 64
    key = (cfg, max_len)
    if key not in _GEN_FNS:
        _GEN_FNS[key] = _make_gen_fns(cfg, max_len)
        while len(_GEN_FNS) > _GEN_FNS_MAX:
            _GEN_FNS.popitem(last=False)
    _GEN_FNS.move_to_end(key)
    prefill_fn, decode_fn = _GEN_FNS[key]
    return generate_loop(prefill_fn, decode_fn, params, prompt, prompt_mask, gen, rng)


def generate_speculative(target_params, target_cfg, draft_params, draft_cfg, prompt,
                         **kwargs):
    """Speculative decoding for gpt-family targets/drafts — delegates to the
    family-generic implementation (``llama.generate_speculative``; both families
    share the cached-decode contract). Cross-family pairs work too."""
    from .llama import generate_speculative as _generic

    return _generic(target_params, target_cfg, draft_params, draft_cfg, prompt, **kwargs)


def generate_streamed(
    dispatched,
    prompt: jax.Array,
    cfg: GPTConfig,
    gen=None,
    rng: Optional[jax.Array] = None,
    prompt_mask: Optional[jax.Array] = None,
    prefetch: int = 2,
    pass_times: Optional[list] = None,
) -> jax.Array:
    """Generation for GPT models bigger than HBM (gpt-neox-20b bf16 = 40 GB, opt-30b = 60 GB):
    block weights stream from host RAM / disk with double-buffered prefetch.

    Same contract as ``llama.generate_streamed``; this is the TPU-native counterpart of the
    reference's offloaded ``generate`` over ``AlignDevicesHook`` (``hooks.py:329``) that
    produced the OPT-30B / GPT-NeoX-20B offload baselines
    (``benchmarks/big_model_inference/README.md:33-37``).
    """
    from .llama import _cache_advance, _streamed_head_jit
    from ..big_modeling import consume_block, stream_blocks
    from ..generation import GenerationConfig, streamed_generate_loop

    if cfg.scan_layers:
        raise ValueError("generate_streamed requires per-layer (non-scanned) params.")
    gen = gen or GenerationConfig()
    B, S0 = jnp.asarray(prompt).shape
    max_len = S0 + gen.max_new_tokens
    prefixes = [f"layers/{i}" for i in range(cfg.n_layers)]
    # Hoist the always-resident leaves out of the loop: only transformer BLOCKS stream
    # per pass; re-fetching wte from disk would cost ~690 MB of I/O per token at opt-30b.
    wte = dispatched.fetch("wte")
    wpe = dispatched.fetch("wpe") if cfg.pos == "learned" else None
    ln_f = dispatched.fetch("ln_f")
    head = wte if cfg.tie_embeddings else dispatched.fetch("lm_head")
    head_bias = (
        dispatched.fetch("b_lm_head")
        if cfg.lm_head_bias and not cfg.tie_embeddings and "b_lm_head" in dispatched.weights
        else None
    )

    def one_pass(tokens, cache, token_mask):
        if cache is None:
            cache = init_cache(cfg, B, max_len)
        index, positions, valid = _cache_advance(cache, tokens, token_mask)
        # Gather THEN cast — the loop is host-driven, so casting the whole [V, D] matrix
        # per pass would dominate.
        x = wte[tokens].astype(cfg.dtype)
        if wpe is not None:
            x = x + wpe[positions].astype(cfg.dtype)
        new_layers = []
        for i, layer in stream_blocks(dispatched, prefixes, prefetch=prefetch):
            idx = int(i.split("/")[1])
            x, new_kv = _block_cached_jit(
                x, layer, cache["layers"][idx], index, positions, valid, cfg=cfg
            )
            # Fence + free this block's buffers NOW (big_modeling.consume_block).
            consume_block(x, layer, dispatched, i)
            new_layers.append(new_kv)
        x = _layer_norm(x, ln_f, cfg.norm_eps)
        logits = _streamed_head_jit(x[:, -1, :], head, transpose=cfg.tie_embeddings)
        if head_bias is not None:
            logits = logits + jnp.asarray(head_bias, jnp.float32)
        return logits, {"layers": new_layers, "valid": valid, "index": index + tokens.shape[1]}

    return streamed_generate_loop(one_pass, prompt, prompt_mask, gen, rng,
                                  pass_times=pass_times)


@partial(jax.jit, static_argnames=("cfg",))
def _block_cached_jit(x, layer, kv, index, positions, valid, cfg):
    """Module-level jit identity: one compile per shape across streamed decode steps."""
    return _block_cached(x, layer, kv, index, positions, valid, cfg)


def num_params(cfg: GPTConfig) -> int:
    """Analytic parameter count — never materializes the model (gpt-neox-20b is 80 GB fp32)."""
    D, F, V, L = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.n_layers
    per_layer = (
        D * 3 * D + 3 * D      # wqkv + bias
        + D * D + D            # wo + bias
        + 2 * D * F + F + D    # w_up/w_down + biases
        + 4 * D                # two layernorms (scale + bias)
    )
    total = V * D + L * per_layer + 2 * D  # wte + layers + ln_f
    if cfg.pos == "learned":
        total += cfg.max_seq * D
    if not cfg.tie_embeddings:
        total += D * V
        if cfg.lm_head_bias:
            total += V
    return total
