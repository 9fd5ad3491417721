"""T5 encoder-decoder family — the reference baseline table's T0pp (11B) architecture.

Reference baselines cover decoder-only (GPT-J/NeoX) AND encoder-decoder models (T0pp,
``/root/reference/benchmarks/big_model_inference/README.md:35``); this module supplies the
latter natively with the T5 conventions that differ from the other families:

- T5 LayerNorm: RMS, scale-only, NO mean subtraction and NO bias, computed in fp32.
- Relative position bias (bucketed, log-spaced): a [num_buckets, n_heads] table held by the
  FIRST block of the encoder and of the decoder, shared by all their blocks; no positional
  embeddings anywhere else.
- Attention scores are NOT scaled by 1/sqrt(head_dim) (absorbed into init).
- Feed-forward: gated-GELU (``wi_0``·gelu × ``wi_1`` → ``wo``, T5 v1.1/T0 lineage) or ReLU.
- Tied embeddings rescale decoder output by ``d_model**-0.5`` before the vocab projection.

``hf_interop.t5_from_hf`` maps transformers ``T5ForConditionalGeneration`` weights; parity
is asserted against transformers itself in ``tests/test_hf_interop.py``.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..utils.constants import BATCH_AXES, FSDP_AXIS, TENSOR_AXIS

__all__ = [
    "T5Config",
    "CONFIGS",
    "init_params",
    "encode",
    "decode",
    "forward",
    "loss_fn",
    "score",
    "perplexity",
    "partition_specs",
    "stack_pp_params",
    "forward_pp",
    "loss_fn_pp",
    "generate",
    "generate_streamed",
    "num_params",
]


@dataclasses.dataclass(frozen=True)
class T5Config:
    vocab_size: int = 32128
    d_model: int = 512
    d_kv: int = 64            # per-head dim (NOT d_model // n_heads in general!)
    d_ff: int = 1024
    n_layers: int = 6         # encoder depth
    n_decoder_layers: Optional[int] = None  # None → n_layers
    n_heads: int = 8
    rel_buckets: int = 32
    rel_max_distance: int = 128
    gated_ff: bool = True     # gated-gelu (v1.1/T0); False → relu
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    tie_embeddings: bool = True
    # "auto": dense CE. "fused": ops/fused_xent kernel (single device; multi-device
    # meshes fall back to dense).
    loss_impl: str = "auto"
    remat: bool = False                       # jax.checkpoint each enc/dec block
    remat_policy: str = "full"                # "full" | "dots" | "offload" (models/common.py)
    remat_prevent_cse: Optional[bool] = None  # None = auto (True: python-loop stack)
    decoder_start_token_id: int = 0

    @property
    def dec_layers(self) -> int:
        return self.n_decoder_layers or self.n_layers


CONFIGS = {
    "t5-small-v1_1": T5Config(),
    "t5-base-v1_1": T5Config(d_model=768, d_ff=2048, n_layers=12, n_heads=12),
    # T0pp / t5-v1.1-xxl shape — the reference's 11B baseline model.
    "t0pp": T5Config(d_model=4096, d_kv=64, d_ff=10240, n_layers=24, n_heads=64),
    "tiny": T5Config(vocab_size=128, d_model=32, d_kv=8, d_ff=64, n_layers=2, n_heads=4),
}


def _attn_params(cfg: T5Config, key, with_rel_bias: bool) -> dict:
    k = jax.random.split(key, 5)
    D, inner = cfg.d_model, cfg.n_heads * cfg.d_kv
    p = {
        "q": jax.random.normal(k[0], (D, inner), jnp.float32) * (D * cfg.d_kv) ** -0.5,
        "k": jax.random.normal(k[1], (D, inner), jnp.float32) * D**-0.5,
        "v": jax.random.normal(k[2], (D, inner), jnp.float32) * D**-0.5,
        "o": jax.random.normal(k[3], (inner, D), jnp.float32) * inner**-0.5,
    }
    if with_rel_bias:
        p["rel_bias"] = jax.random.normal(
            k[4], (cfg.rel_buckets, cfg.n_heads), jnp.float32
        ) * 0.1
    return p


def _ff_params(cfg: T5Config, key) -> dict:
    k = jax.random.split(key, 3)
    D, F = cfg.d_model, cfg.d_ff
    p = {"wo": jax.random.normal(k[2], (F, D), jnp.float32) * F**-0.5}
    if cfg.gated_ff:
        p["wi_0"] = jax.random.normal(k[0], (D, F), jnp.float32) * D**-0.5
        p["wi_1"] = jax.random.normal(k[1], (D, F), jnp.float32) * D**-0.5
    else:
        p["wi"] = jax.random.normal(k[0], (D, F), jnp.float32) * D**-0.5
    return p


def init_params(cfg: T5Config, key: Optional[jax.Array] = None) -> dict:
    if key is None:
        key = jax.random.PRNGKey(0)  # graftlint: disable=rng-key-reuse(deterministic default init; callers pass a key for real entropy)
    n_enc, n_dec = cfg.n_layers, cfg.dec_layers
    keys = jax.random.split(key, 2 + 2 * n_enc + 3 * n_dec)
    ki = iter(range(len(keys)))
    params: dict = {
        "shared": jax.random.normal(keys[next(ki)], (cfg.vocab_size, cfg.d_model), jnp.float32),
        "encoder": {"blocks": [], "ln_f": jnp.ones((cfg.d_model,), jnp.float32)},
        "decoder": {"blocks": [], "ln_f": jnp.ones((cfg.d_model,), jnp.float32)},
    }
    for i in range(n_enc):
        params["encoder"]["blocks"].append({
            "ln_attn": jnp.ones((cfg.d_model,), jnp.float32),
            "attn": _attn_params(cfg, keys[next(ki)], with_rel_bias=(i == 0)),
            "ln_ff": jnp.ones((cfg.d_model,), jnp.float32),
            "ff": _ff_params(cfg, keys[next(ki)]),
        })
    for i in range(n_dec):
        params["decoder"]["blocks"].append({
            "ln_attn": jnp.ones((cfg.d_model,), jnp.float32),
            "attn": _attn_params(cfg, keys[next(ki)], with_rel_bias=(i == 0)),
            "ln_cross": jnp.ones((cfg.d_model,), jnp.float32),
            "cross": _attn_params(cfg, keys[next(ki)], with_rel_bias=False),
            "ln_ff": jnp.ones((cfg.d_model,), jnp.float32),
            "ff": _ff_params(cfg, keys[next(ki)]),
        })
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            keys[next(ki)], (cfg.d_model, cfg.vocab_size), jnp.float32
        ) * cfg.d_model**-0.5
    return params


def partition_specs(cfg: T5Config, pp: bool = False, virtual_stages: int = 1) -> dict:
    """Megatron layout: q/k/v/wi column-parallel, o/wo row-parallel, vocab over (tp,fsdp).

    ``pp=True``: specs for the :func:`stack_pp_params` layout — encoder/decoder block
    stacks ``[n_stages, L/n, ...]`` with the stage dim over ``pp`` (each stage holds only
    its blocks), rel-bias tables lifted out of block 0 and replicated, vocab folded over
    (tp, fsdp, pp) like the llama/gpt pipeline layouts."""
    def attn_spec(with_rel: bool) -> dict:
        s = {"q": P(None, TENSOR_AXIS), "k": P(None, TENSOR_AXIS),
             "v": P(None, TENSOR_AXIS), "o": P(TENSOR_AXIS, None)}
        if with_rel:
            s["rel_bias"] = P(None, TENSOR_AXIS)
        return s

    def ff_spec() -> dict:
        s = {"wo": P(TENSOR_AXIS, None)}
        if cfg.gated_ff:
            s.update({"wi_0": P(None, TENSOR_AXIS), "wi_1": P(None, TENSOR_AXIS)})
        else:
            s["wi"] = P(None, TENSOR_AXIS)
        return s

    if pp:
        from ..utils.constants import PIPELINE_AXIS

        from ..parallel.pp import stage_spec_prefix

        def stage_stack(spec_tree, v=1):
            # [n_stages, L/n, ...] (or interleaved [v, n, L/(n·v), ...] — pp on dim 1).
            return jax.tree_util.tree_map(
                lambda s: P(*stage_spec_prefix(v), *s), spec_tree,
                is_leaf=lambda s: isinstance(s, P),
            )

        vocab_axes = (TENSOR_AXIS, FSDP_AXIS, PIPELINE_AXIS)
        enc_blk = {"ln_attn": P(), "attn": attn_spec(False), "ln_ff": P(), "ff": ff_spec()}
        dec_blk = {"ln_attn": P(), "attn": attn_spec(False), "ln_cross": P(),
                   "cross": attn_spec(False), "ln_ff": P(), "ff": ff_spec()}
        specs = {
            "shared": P(vocab_axes, None),
            "enc_rel": P(None, TENSOR_AXIS),
            "dec_rel": P(None, TENSOR_AXIS),
            "encoder": {"stages": stage_stack(enc_blk), "ln_f": P()},
            "decoder": {"stages": stage_stack(dec_blk, virtual_stages), "ln_f": P()},
        }
        if not cfg.tie_embeddings:
            specs["lm_head"] = P(None, vocab_axes)
        return specs

    enc = [
        {"ln_attn": P(), "attn": attn_spec(i == 0), "ln_ff": P(), "ff": ff_spec()}
        for i in range(cfg.n_layers)
    ]
    dec = [
        {"ln_attn": P(), "attn": attn_spec(i == 0), "ln_cross": P(),
         "cross": attn_spec(False), "ln_ff": P(), "ff": ff_spec()}
        for i in range(cfg.dec_layers)
    ]
    specs = {
        "shared": P((TENSOR_AXIS, FSDP_AXIS), None),
        "encoder": {"blocks": enc, "ln_f": P()},
        "decoder": {"blocks": dec, "ln_f": P()},
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, (TENSOR_AXIS, FSDP_AXIS))
    return specs


def _segment_pair_mask(q_seg, k_seg):
    """[B,1,Q,K] bool: query/key in the SAME segment AND key not padding (segment 0)."""
    same = q_seg[:, :, None] == k_seg[:, None, :]
    live = (k_seg != 0)[:, None, :]
    return (same & live)[:, None]


def _t5_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def _relative_bucket(rel_pos, bidirectional: bool, num_buckets: int, max_distance: int):
    """HF T5's bucketing: half the buckets for sign (bidirectional), log-spaced far bins."""
    ret = jnp.zeros_like(rel_pos)
    n = rel_pos
    if bidirectional:
        num_buckets //= 2
        ret = ret + (n > 0).astype(jnp.int32) * num_buckets
        n = jnp.abs(n)
    else:
        n = -jnp.minimum(n, 0)
    max_exact = num_buckets // 2
    is_small = n < max_exact
    large = max_exact + (
        jnp.log(n.astype(jnp.float32) / max_exact + 1e-6)
        / math.log(max_distance / max_exact)
        * (num_buckets - max_exact)
    ).astype(jnp.int32)
    large = jnp.minimum(large, num_buckets - 1)
    return ret + jnp.where(is_small, n, large)


def _rel_bias(table, q_len: int, k_len: int, bidirectional: bool, cfg: T5Config):
    """[1, heads, q_len, k_len] additive attention bias from the bucket table."""
    ctx = jnp.arange(q_len)[:, None]
    mem = jnp.arange(k_len)[None, :]
    buckets = _relative_bucket(
        mem - ctx, bidirectional, cfg.rel_buckets, cfg.rel_max_distance
    )
    bias = table[buckets]  # [q, k, heads]
    return jnp.transpose(bias, (2, 0, 1))[None].astype(jnp.float32)


def _attention(h_q, h_kv, p, cfg: T5Config, bias, mask):
    """T5 attention: UNscaled scores + additive (rel + mask) fp32 bias."""
    B, Q, D = h_q.shape
    K = h_kv.shape[1]
    dtype = h_q.dtype
    q = (h_q @ p["q"].astype(dtype)).reshape(B, Q, cfg.n_heads, cfg.d_kv)
    k = (h_kv @ p["k"].astype(dtype)).reshape(B, K, cfg.n_heads, cfg.d_kv)
    v = (h_kv @ p["v"].astype(dtype)).reshape(B, K, cfg.n_heads, cfg.d_kv)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    if bias is not None:
        scores = scores + bias
    if mask is not None:
        scores = jnp.where(mask, scores, -1e9)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, Q, cfg.n_heads * cfg.d_kv)
    return out @ p["o"].astype(dtype)


def _ff(h, p, cfg: T5Config):
    dtype = h.dtype
    if cfg.gated_ff:
        inner = jax.nn.gelu(h @ p["wi_0"].astype(dtype), approximate=False) * (
            h @ p["wi_1"].astype(dtype)
        )
    else:
        inner = jax.nn.relu(h @ p["wi"].astype(dtype))
    return inner @ p["wo"].astype(dtype)


def _enc_block(x, blk, bias, mask, cfg: T5Config):
    """One encoder block (self-attention + FF, pre-norm residuals)."""
    h = _t5_norm(x, blk["ln_attn"], cfg.norm_eps)
    x = x + _attention(h, h, blk["attn"], cfg, bias, mask)
    h = _t5_norm(x, blk["ln_ff"], cfg.norm_eps)
    return x + _ff(h, blk["ff"], cfg)


def _dec_block(x, blk, enc_out, bias, causal, cmask, cfg: T5Config):
    """One decoder block (causal self-attention + cross-attention + FF)."""
    h = _t5_norm(x, blk["ln_attn"], cfg.norm_eps)
    x = x + _attention(h, h, blk["attn"], cfg, bias, causal)
    h = _t5_norm(x, blk["ln_cross"], cfg.norm_eps)
    x = x + _attention(h, enc_out, blk["cross"], cfg, None, cmask)
    h = _t5_norm(x, blk["ln_ff"], cfg.norm_eps)
    return x + _ff(h, blk["ff"], cfg)


def encode(params: dict, input_ids: jax.Array, cfg: T5Config,
           attention_mask: Optional[jax.Array] = None,
           segment_ids: Optional[jax.Array] = None) -> jax.Array:
    """Encoder: input_ids [B, S] → hidden [B, S, D].

    ``segment_ids`` (seq2seq packing, ``ops/packing.pack_seq2seq``): bidirectional
    attention restricted to same-segment pairs; segment 0 is padding. T5's relative-
    position bias needs no change — within a contiguous segment, relative distances are
    shift-invariant, and cross-segment pairs are masked.
    """
    from .llama import _maybe_shard

    B, S = input_ids.shape
    x = params["shared"].astype(cfg.dtype)[input_ids]
    x = _maybe_shard(x, P(BATCH_AXES, None, None))
    rel_table = params["encoder"]["blocks"][0]["attn"]["rel_bias"]
    bias = _rel_bias(rel_table, S, S, bidirectional=True, cfg=cfg)
    mask = None
    if segment_ids is not None:
        mask = _segment_pair_mask(segment_ids, segment_ids)
        if attention_mask is not None:
            mask = mask & attention_mask[:, None, None, :].astype(bool)
    elif attention_mask is not None:
        mask = attention_mask[:, None, None, :].astype(bool)
    from .common import remat_wrap

    enc_block = remat_wrap(
        _enc_block, remat=cfg.remat, policy=cfg.remat_policy,
        prevent_cse=cfg.remat_prevent_cse, static_argnums=(4,),
    )
    for blk in params["encoder"]["blocks"]:
        x = enc_block(x, blk, bias, mask, cfg)
    return _t5_norm(x, params["encoder"]["ln_f"], cfg.norm_eps)


def decode(params: dict, decoder_input_ids: jax.Array, enc_out: jax.Array, cfg: T5Config,
           enc_mask: Optional[jax.Array] = None,
           dec_segment_ids: Optional[jax.Array] = None,
           enc_segment_ids: Optional[jax.Array] = None,
           return_hidden: bool = False) -> jax.Array:
    """Decoder: ids [B, T] + encoder hidden → logits [B, T, V] fp32 (or the post-ln_f
    [B, T, D] compute-dtype hidden states — tied-head scaling included — when
    ``return_hidden``; the fused-CE path applies the head inside its kernel).

    Packed rows (``dec_segment_ids``/``enc_segment_ids``): self-attention restricts to
    per-segment causal; cross-attention lets decoder segment k attend ONLY encoder
    segment k (pack_seq2seq assigns pairs the same number on both sides).
    """
    B, T = decoder_input_ids.shape
    x = params["shared"].astype(cfg.dtype)[decoder_input_ids]
    rel_table = params["decoder"]["blocks"][0]["attn"]["rel_bias"]
    bias = _rel_bias(rel_table, T, T, bidirectional=False, cfg=cfg)
    causal = jnp.tril(jnp.ones((T, T), bool))[None, None]
    if (dec_segment_ids is None) != (enc_segment_ids is None):
        # One side alone would leave cross-attention unmasked across packed segments —
        # silently wrong logits, the exact failure packing support exists to prevent.
        raise ValueError(
            "packed decode requires BOTH dec_segment_ids and enc_segment_ids"
        )
    cmask = None
    if dec_segment_ids is not None:
        causal = causal & _segment_pair_mask(dec_segment_ids, dec_segment_ids)
        cmask = _segment_pair_mask(dec_segment_ids, enc_segment_ids)
        if enc_mask is not None:
            cmask = cmask & enc_mask[:, None, None, :].astype(bool)
    elif enc_mask is not None:
        cmask = enc_mask[:, None, None, :].astype(bool)
    from .common import remat_wrap

    dec_block = remat_wrap(
        _dec_block, remat=cfg.remat, policy=cfg.remat_policy,
        prevent_cse=cfg.remat_prevent_cse, static_argnums=(6,),
    )
    for blk in params["decoder"]["blocks"]:
        x = dec_block(x, blk, enc_out, bias, causal, cmask, cfg)
    x = _t5_norm(x, params["decoder"]["ln_f"], cfg.norm_eps)
    if cfg.tie_embeddings:
        x = x * (cfg.d_model**-0.5)  # tied-head scaling lives on the hidden side
    if return_hidden:
        return x
    return (x @ _t5_head(params, cfg).astype(cfg.dtype)).astype(jnp.float32)


def _t5_head(params: dict, cfg: T5Config) -> jax.Array:
    return params["shared"].T if cfg.tie_embeddings else params["lm_head"]


def forward(params: dict, input_ids: jax.Array, decoder_input_ids: jax.Array,
            cfg: T5Config, attention_mask: Optional[jax.Array] = None) -> jax.Array:
    enc = encode(params, input_ids, cfg, attention_mask)
    return decode(params, decoder_input_ids, enc, cfg, attention_mask)


def loss_fn(params: dict, batch: dict, cfg: T5Config, rng=None) -> jax.Array:
    """Seq2seq cross-entropy over {'input_ids', 'labels'} (+optional 'attention_mask').

    Decoder inputs are the labels shifted right with ``decoder_start_token_id`` (the HF
    ``_shift_right`` convention); label positions equal to -100 are ignored.

    Packed batches (``ops/packing.pack_seq2seq``: +'enc_segment_ids'/'dec_segment_ids'):
    the shift-right restarts at every decoder segment boundary (each packed pair begins
    with the start token), attention restricts per segment on both sides, and
    cross-attention pairs decoder segment k with encoder segment k.
    """
    if "segment_ids" in batch:
        raise ValueError(
            "seq2seq packing uses pack_seq2seq ('enc_segment_ids'/'dec_segment_ids'), "
            "not the decoder-only 'segment_ids' layout"
        )
    if cfg.loss_impl not in ("auto", "fused"):
        raise ValueError(f"loss_impl={cfg.loss_impl!r}: expected 'auto' or 'fused'")
    from .common import fused_ce_allowed

    want_fused = cfg.loss_impl == "fused" and fused_ce_allowed()
    labels = batch["labels"]
    start = jnp.full((labels.shape[0], 1), cfg.decoder_start_token_id, labels.dtype)
    if "dec_segment_ids" in batch:
        dec_seg = batch["dec_segment_ids"]
        enc_seg = batch["enc_segment_ids"]
        prev = jnp.concatenate([start, jnp.maximum(labels[:, :-1], 0)], axis=1)
        is_start = jnp.concatenate(
            [jnp.ones((labels.shape[0], 1), bool), dec_seg[:, 1:] != dec_seg[:, :-1]],
            axis=1,
        )
        dec_in = jnp.where(is_start, jnp.asarray(cfg.decoder_start_token_id, labels.dtype), prev)
        enc_out = encode(
            params, batch["input_ids"], cfg, batch.get("attention_mask"), segment_ids=enc_seg
        )
        out = decode(
            params, dec_in, enc_out, cfg, batch.get("attention_mask"),
            dec_segment_ids=dec_seg, enc_segment_ids=enc_seg, return_hidden=want_fused,
        )
        mask = ((labels >= 0) & (dec_seg != 0)).astype(jnp.float32)
    else:
        dec_in = jnp.concatenate([start, jnp.maximum(labels[:, :-1], 0)], axis=1)
        enc_out = encode(params, batch["input_ids"], cfg, batch.get("attention_mask"))
        out = decode(
            params, dec_in, enc_out, cfg, batch.get("attention_mask"),
            return_hidden=want_fused,
        )
        mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    if want_fused:
        # want_fused == fused_ce_allowed(), so the helper cannot return None here
        # (and `out` is hidden states, not logits — the dense tail must not run).
        from .common import fused_ce_single_shard

        return fused_ce_single_shard(
            out, _t5_head(params, cfg).astype(cfg.dtype), safe, mask
        )
    logp = jax.nn.log_softmax(out, axis=-1)
    ll = jnp.take_along_axis(logp, safe[..., None], axis=-1).squeeze(-1)
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


# --------------------------------------------------------------- pipeline-parallel training
def stack_pp_params(
    params: dict, cfg: T5Config, n_stages: int, virtual_stages: int = 1
) -> dict:
    """Canonical params → the pipeline layout (the enc-dec analog of llama's
    stage-stacked layers; reference Megatron pipelines T5 too,
    ``/root/reference/src/accelerate/utils/megatron_lm.py:720``).

    The rel-bias tables live in block 0 only, which makes the raw block lists
    structurally heterogeneous and unstackable — they are LIFTED to top-level
    ``enc_rel``/``dec_rel`` leaves (shared by all blocks anyway), and the now-homogeneous
    blocks stack to ``[n_stages, L/n, ...]`` under ``encoder.stages``/``decoder.stages``.
    Specs: ``partition_specs(cfg, pp=True)``.

    ``virtual_stages=v > 1`` (interleaved, 1f1b): the DECODER stacks to the
    interleaved ``[v, n, L/(n·v), ...]`` layout (its pipeline is the hand-scheduled
    half); the encoder keeps ``[n, L/n, ...]`` (it runs AD-GPipe either way).
    """
    if cfg.n_layers % n_stages or cfg.dec_layers % (n_stages * virtual_stages):
        raise ValueError(
            f"encoder depth ({cfg.n_layers}) must be divisible by n_stages={n_stages} "
            f"and decoder depth ({cfg.dec_layers}) by n_stages x "
            f"virtual_stages={virtual_stages}"
        )

    def strip_stack(blocks, v=1):
        first = dict(blocks[0])
        first["attn"] = {k: v2 for k, v2 in first["attn"].items() if k != "rel_bias"}
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), first, *blocks[1:])
        from ..parallel.pp import split_params_into_stages

        return split_params_into_stages(stacked, n_stages, virtual_stages=v)

    out = {
        "shared": params["shared"],
        "enc_rel": params["encoder"]["blocks"][0]["attn"]["rel_bias"],
        "dec_rel": params["decoder"]["blocks"][0]["attn"]["rel_bias"],
        "encoder": {"stages": strip_stack(params["encoder"]["blocks"]),
                    "ln_f": params["encoder"]["ln_f"]},
        "decoder": {"stages": strip_stack(params["decoder"]["blocks"], virtual_stages),
                    "ln_f": params["decoder"]["ln_f"]},
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = params["lm_head"]
    return out


def _enc_stage_fn(cfg: T5Config):
    """Encoder pipeline stage: scan this stage's blocks over one microbatch. The shared
    rel bias rides as a per-stage param slice (``sp["bias"]``, same value every stage —
    broadcast at trace time, so AD sums the per-stage grads back into the one table);
    the optional attention mask is a per-microbatch side constant."""
    from .common import remat_wrap

    block = remat_wrap(
        _enc_block, remat=cfg.remat, policy=cfg.remat_policy,
        prevent_cse=cfg.remat_prevent_cse, scan_layers=True, static_argnums=(4,),
    )

    def stage_fn(sp, x, side):
        mask = None
        if "enc_seg" in side:
            # seq2seq packing: bidirectional attention restricted to same-segment pairs.
            mask = _segment_pair_mask(side["enc_seg"], side["enc_seg"])
        if "enc_mask" in side:
            am = side["enc_mask"][:, None, None, :].astype(bool)
            mask = am if mask is None else mask & am

        def body(carry, blk):
            # sp["bias"] is [1, H, S, S] here: pipeline_apply already stripped the
            # leading stage dim from every stage-param leaf.
            return block(carry, blk, sp["bias"], mask, cfg), None

        out, _ = jax.lax.scan(body, x, sp["blocks"])
        return out

    return stage_fn


def _dec_stage_fn(cfg: T5Config, T: int):
    """Decoder pipeline stage: causal self-attention + cross-attention against the
    frozen encoder output, which rides as a per-microbatch side constant — indexed by
    microbatch id, never ppermuted. Under the AD-derived GPipe schedule the side input
    IS differentiable, so encoder grads flow back through cross-attention."""
    from .common import remat_wrap

    block = remat_wrap(
        _dec_block, remat=cfg.remat, policy=cfg.remat_policy,
        prevent_cse=cfg.remat_prevent_cse, scan_layers=True, static_argnums=(6,),
    )

    def stage_fn(sp, x, side):
        causal = jnp.tril(jnp.ones((T, T), bool))[None, None]
        cmask = None
        if "dec_seg" in side:
            # seq2seq packing: per-segment causal self-attention; cross-attention pairs
            # decoder segment k with encoder segment k only (pack_seq2seq numbering).
            causal = causal & _segment_pair_mask(side["dec_seg"], side["dec_seg"])
            cmask = _segment_pair_mask(side["dec_seg"], side["enc_seg"])
        if "enc_mask" in side:
            am = side["enc_mask"][:, None, None, :].astype(bool)
            cmask = am if cmask is None else cmask & am

        def body(carry, blk):
            return block(carry, blk, side["enc_out"], sp["bias"], causal, cmask, cfg), None

        out, _ = jax.lax.scan(body, x, sp["blocks"])
        return out

    return stage_fn


def forward_pp(
    params: dict,
    input_ids: jax.Array,
    decoder_input_ids: jax.Array,
    cfg: T5Config,
    mesh,
    num_microbatches: Optional[int] = None,
    attention_mask: Optional[jax.Array] = None,
    return_hidden: bool = False,
    enc_segment_ids: Optional[jax.Array] = None,
    dec_segment_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """Seq2seq forward with BOTH stacks pipelined over ``pp`` — the enc-dec pipeline
    shape the reference's Megatron engine drives for T5 (``megatron_lm.py:720``).

    Two chained GPipe pipelines over the same ``pp`` axis: encoder stages first
    (microbatches stream through all of them), then decoder stages, with the completed
    ``enc_out`` delivered to every decoder stage's cross-attention as a per-microbatch
    side constant (``parallel.pp`` side-input contract — indexed, never ppermuted).
    Params in :func:`stack_pp_params` layout; embed/ln_f/head outside the pipelines,
    vocab-sharded over (tp, fsdp, pp) by ``partition_specs(pp=True)``.
    """
    enc_out = _encode_pp(
        params, input_ids, cfg, mesh, num_microbatches, attention_mask, enc_segment_ids,
        dec_segment_ids,
    )
    xd, sp_d, side_d = _dec_pp_inputs(
        params, decoder_input_ids, cfg, mesh, enc_out, attention_mask,
        enc_segment_ids, dec_segment_ids,
    )
    from ..parallel.pp import make_pipeline_fn

    T = decoder_input_ids.shape[1]
    pipe_d = make_pipeline_fn(
        mesh, _dec_stage_fn(cfg, T), num_microbatches=num_microbatches
    )
    xd = pipe_d(sp_d, xd, side=side_d)
    xd = _t5_norm(xd, params["decoder"]["ln_f"], cfg.norm_eps)
    if cfg.tie_embeddings:
        xd = xd * (cfg.d_model**-0.5)
    if return_hidden:
        return xd
    return (xd @ _t5_head(params, cfg).astype(cfg.dtype)).astype(jnp.float32)


def _encode_pp(
    params, input_ids, cfg: T5Config, mesh, num_microbatches, attention_mask,
    enc_segment_ids, dec_segment_ids,
):
    """The encoder half of the t5 pipeline: GPipe over the encoder stages → post-ln_f
    encoder output (shared by the GPipe and 1F1B decoder paths)."""
    from ..parallel.pp import make_pipeline_fn
    from ..utils.constants import PIPELINE_AXIS
    from .llama import _maybe_shard

    if (dec_segment_ids is None) != (enc_segment_ids is None):
        raise ValueError("packed forward_pp requires BOTH enc_ and dec_segment_ids")
    n = mesh.shape[PIPELINE_AXIS]
    B, S = input_ids.shape
    x = params["shared"].astype(cfg.dtype)[input_ids]
    x = _maybe_shard(x, P(BATCH_AXES, None, None))
    bias_e = _rel_bias(params["enc_rel"], S, S, bidirectional=True, cfg=cfg)
    sp_e = {
        "blocks": params["encoder"]["stages"],
        # [n, 1, H, S, S]: one (identical) slice per stage; sliced back to [1,H,S,S] in
        # the stage body. Broadcast inside the traced fn → AD sums per-stage grads.
        "bias": jnp.broadcast_to(bias_e[None], (n, *bias_e.shape)),
    }
    side_e = {"enc_mask": attention_mask} if attention_mask is not None else {}
    if enc_segment_ids is not None:
        side_e["enc_seg"] = enc_segment_ids
    pipe_e = make_pipeline_fn(mesh, _enc_stage_fn(cfg), num_microbatches=num_microbatches)
    # side={} still routes through the side path (3-arg stage_fn), just with no leaves.
    enc_out = pipe_e(sp_e, x, side=side_e)
    return _t5_norm(enc_out, params["encoder"]["ln_f"], cfg.norm_eps)


def _dec_pp_inputs(
    params, decoder_input_ids, cfg: T5Config, mesh, enc_out, attention_mask,
    enc_segment_ids, dec_segment_ids, virtual_stages: int = 1,
):
    """Decoder-pipeline inputs shared by the GPipe and 1F1B paths: embedded decoder
    activations, decoder stage params (blocks + broadcast rel bias), and the side tree
    (enc_out + masks/segments — enc_out is the FLOAT side leaf whose cotangent both
    schedules propagate back into the encoder pipeline)."""
    from ..utils.constants import PIPELINE_AXIS
    from .llama import _maybe_shard

    n = mesh.shape[PIPELINE_AXIS]
    T = decoder_input_ids.shape[1]
    xd = params["shared"].astype(cfg.dtype)[decoder_input_ids]
    xd = _maybe_shard(xd, P(BATCH_AXES, None, None))
    bias_d = _rel_bias(params["dec_rel"], T, T, bidirectional=False, cfg=cfg)
    # One (identical) bias slice per stage — per (chunk, stage) in the interleaved
    # layout; AD sums the broadcast's per-slice grads back into the one table.
    bias_st = (
        jnp.broadcast_to(bias_d[None, None], (virtual_stages, n, *bias_d.shape))
        if virtual_stages > 1
        else jnp.broadcast_to(bias_d[None], (n, *bias_d.shape))
    )
    sp_d = {
        "blocks": params["decoder"]["stages"],
        "bias": bias_st,
    }
    side_d = {"enc_out": enc_out}
    if attention_mask is not None:
        side_d["enc_mask"] = attention_mask
    if dec_segment_ids is not None:
        side_d["dec_seg"] = dec_segment_ids
        side_d["enc_seg"] = enc_segment_ids
    return xd, sp_d, side_d


def loss_fn_pp(
    params: dict,
    batch: dict,
    cfg: T5Config,
    mesh,
    num_microbatches: Optional[int] = None,
    rng=None,
    schedule: str = "gpipe",
    virtual_stages: int = 1,
) -> jax.Array:
    """Pipeline-parallel seq2seq CE (params in :func:`stack_pp_params` layout; same
    batch contract as ``loss_fn``, INCLUDING seq2seq packing — enc/dec segment ids ride
    both pipelines as per-microbatch side constants). Every ``loss_impl`` works — the
    head runs after the pipelines via ``common.ce_sum_dispatch``.

    ``virtual_stages=v > 1`` (with 1f1b): the DECODER pipeline runs interleaved
    (params from ``stack_pp_params(..., virtual_stages=v)``) — enc_out's cotangent
    accumulates through the virtual-stage replay exactly as in the flat 1f1b.

    ``schedule="1f1b"`` hand-schedules the DECODER pipeline (the deeper, heavier half —
    self + cross attention per block) through ``make_pipeline_loss_fn``; the replay
    computes the TRUE ``enc_out`` cotangent (float side leaves accumulate across stages
    and microbatches), which jax AD then chains back through the encoder's GPipe
    pipeline. The encoder half stays AD-GPipe — its activations are the cheap half, and
    a fully hand-scheduled enc+dec interleave would buy little for the added table
    complexity."""
    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"schedule={schedule!r}: expected 'gpipe' or '1f1b'")
    if virtual_stages > 1 and schedule != "1f1b":
        raise NotImplementedError(
            "virtual_stages > 1 requires schedule='1f1b' (parallel/pp.py)"
        )
    if "segment_ids" in batch:
        raise ValueError(
            "seq2seq packing uses pack_seq2seq ('enc_segment_ids'/'dec_segment_ids'), "
            "not the decoder-only 'segment_ids' layout"
        )
    from .common import ce_sum_dispatch, resolve_loss_chunk

    labels = batch["labels"]
    start = jnp.full((labels.shape[0], 1), cfg.decoder_start_token_id, labels.dtype)
    if "dec_segment_ids" in batch:
        # Same packed conventions as loss_fn: the shift-right restarts at every decoder
        # segment boundary, and targets count only inside real decoder segments.
        dec_seg = batch["dec_segment_ids"]
        enc_seg = batch["enc_segment_ids"]
        prev = jnp.concatenate([start, jnp.maximum(labels[:, :-1], 0)], axis=1)
        is_start = jnp.concatenate(
            [jnp.ones((labels.shape[0], 1), bool), dec_seg[:, 1:] != dec_seg[:, :-1]],
            axis=1,
        )
        dec_in = jnp.where(
            is_start, jnp.asarray(cfg.decoder_start_token_id, labels.dtype), prev
        )
        mask = ((labels >= 0) & (dec_seg != 0)).astype(jnp.float32)
    else:
        dec_seg = enc_seg = None
        dec_in = jnp.concatenate([start, jnp.maximum(labels[:, :-1], 0)], axis=1)
        mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    if schedule == "1f1b":
        from ..parallel.pp import make_pipeline_loss_fn

        T = labels.shape[1]
        am = batch.get("attention_mask")
        enc_out = _encode_pp(
            params, batch["input_ids"], cfg, mesh, num_microbatches, am,
            enc_seg, dec_seg,
        )
        xd, sp_d, side_d = _dec_pp_inputs(
            params, dec_in, cfg, mesh, enc_out, am, enc_seg, dec_seg,
            virtual_stages=virtual_stages,
        )
        hp = {"ln_f": params["decoder"]["ln_f"], "head": _t5_head(params, cfg)}

        def head_loss(h, y, ex):
            xh = _t5_norm(y, h["ln_f"], cfg.norm_eps)
            if cfg.tie_embeddings:
                xh = xh * (cfg.d_model**-0.5)
            total = ce_sum_dispatch(
                xh, h["head"], ex["targets"], ex["mask"],
                loss_impl=cfg.loss_impl, dtype=cfg.dtype,
                chunk=resolve_loss_chunk(0, T, cfg.vocab_size),
            )
            return total / jnp.maximum(ex["mask"].sum(), 1.0)

        pipe_loss = make_pipeline_loss_fn(
            mesh, _dec_stage_fn(cfg, T), head_loss,
            num_microbatches=num_microbatches, schedule="1f1b",
            virtual_stages=virtual_stages,
        )
        return pipe_loss(
            sp_d, hp, xd, {"targets": safe, "mask": mask}, side=side_d
        )
    hidden = forward_pp(
        params, batch["input_ids"], dec_in, cfg, mesh,
        num_microbatches=num_microbatches,
        attention_mask=batch.get("attention_mask"), return_hidden=True,
        enc_segment_ids=enc_seg, dec_segment_ids=dec_seg,
    )
    total = ce_sum_dispatch(
        hidden, _t5_head(params, cfg), safe, mask,
        loss_impl=cfg.loss_impl, dtype=cfg.dtype,
        chunk=resolve_loss_chunk(0, labels.shape[1], cfg.vocab_size),
    )
    return total / jnp.maximum(mask.sum(), 1.0)


def score(params: dict, input_ids, labels, cfg: T5Config,
          attention_mask=None) -> jax.Array:
    """Per-target-token log-probabilities log p(label[t] | inputs, labels[:t]) → [B, T]
    fp32 (seq2seq; ignored -100 labels score 0.0). Same contract as ``llama.score``."""
    labels = jnp.asarray(labels, jnp.int32)
    start = jnp.full((labels.shape[0], 1), cfg.decoder_start_token_id, labels.dtype)
    dec_in = jnp.concatenate([start, jnp.maximum(labels[:, :-1], 0)], axis=1)
    logits = forward(params, input_ids, dec_in, cfg, attention_mask)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None], axis=-1).squeeze(-1)
    return ll * (labels >= 0).astype(ll.dtype)


def perplexity(params: dict, input_ids, labels, cfg: T5Config,
               attention_mask=None) -> jax.Array:
    """exp(mean negative log-likelihood over real label positions) — scalar fp32."""
    labels = jnp.asarray(labels, jnp.int32)
    ll = score(params, input_ids, labels, cfg, attention_mask)
    denom = jnp.maximum((labels >= 0).sum(), 1)
    return jnp.exp(-ll.sum() / denom)


def generate(params: dict, input_ids: jax.Array, cfg: T5Config,
             max_new_tokens: int = 32, attention_mask: Optional[jax.Array] = None,
             eos_token_id: int = 1) -> jax.Array:
    """Greedy seq2seq generation: encoder runs once, decoder re-runs on the growing prefix
    (O(T²) decode — adequate for eval loops; a cached incremental decoder is the llama/gpt
    families' pattern and can be grafted when T5 decode becomes a hot path)."""
    enc = encode(params, input_ids, cfg, attention_mask)
    B = input_ids.shape[0]
    dec = jnp.full((B, 1), cfg.decoder_start_token_id, jnp.int32)
    done = jnp.zeros((B,), bool)
    for _ in range(max_new_tokens):
        logits = decode(params, dec, enc, cfg, attention_mask)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        nxt = jnp.where(done, eos_token_id, nxt)
        done = done | (nxt == eos_token_id)
        dec = jnp.concatenate([dec, nxt[:, None]], axis=1)
        if bool(jnp.all(done)):
            break
    return dec[:, 1:]


def generate_streamed(
    dispatched,
    input_ids: jax.Array,
    cfg: T5Config,
    max_new_tokens: int = 32,
    attention_mask: Optional[jax.Array] = None,
    eos_token_id: int = 1,
    prefetch: int = 2,
    pass_times: Optional[list] = None,
) -> jax.Array:
    """Greedy seq2seq generation with encoder/decoder blocks streamed from host/disk.

    Completes the big-model story for the reference's T0pp baseline (11B — 22 GB even in
    bf16, beyond a single v5e's HBM; the reference spreads it over two 24 GB GPUs,
    ``benchmarks/big_model_inference/README.md:35``). The encoder streams once; each decode
    step re-runs the decoder over a FIXED-width padded prefix buffer so the per-block jit
    compiles exactly twice (one encoder, one decoder shape) regardless of step count —
    causality makes the garbage tail positions unobservable to position t. Weight streaming,
    not the O(T²) prefix recompute, dominates at these scales.
    """
    from ..big_modeling import consume_block, stream_blocks
    from .llama import _streamed_head_jit

    import time as _time

    t_pass = _time.perf_counter()
    input_ids = jnp.asarray(input_ids, jnp.int32)
    B, S = input_ids.shape
    shared = dispatched.fetch("shared")
    # Gather then cast: this loop is host-driven, so .astype on the full [V, D] matrix
    # would eagerly convert ~0.5 GB per pass at T0pp scale.
    x = shared[input_ids].astype(cfg.dtype)
    mask = None
    if attention_mask is not None:
        mask = jnp.asarray(attention_mask)[:, None, None, :].astype(bool)
    bias = None
    for name, blk in stream_blocks(
        dispatched, [f"encoder/blocks/{i}" for i in range(cfg.n_layers)], prefetch=prefetch
    ):
        if bias is None:  # block 0 carries the shared relative-position table
            bias = _rel_bias(blk["attn"]["rel_bias"], S, S, bidirectional=True, cfg=cfg)
        x = _enc_block_jit(x, blk, bias, mask, cfg=cfg)
        # Fence + free (bounds resident blocks — big_modeling.consume_block). bias survives: _rel_bias built a NEW
        # array from block 0's table before this point.
        consume_block(x, blk, dispatched, name)
    enc_out = _t5_norm(x, dispatched.fetch("encoder/ln_f"), cfg.norm_eps)
    if pass_times is not None:
        # Same contract as streamed_generate_loop: entry 0 is the prefill analog (the
        # streamed encoder), then one entry per decode step, each blocked on its tokens.
        jax.block_until_ready(enc_out)
        pass_times.append(_time.perf_counter() - t_pass)

    T = 1 + max_new_tokens
    dec = jnp.full((B, T), cfg.decoder_start_token_id, jnp.int32)
    causal = jnp.tril(jnp.ones((T, T), bool))[None, None]
    cmask = mask
    head = shared if cfg.tie_embeddings else dispatched.fetch("lm_head")
    dec_prefixes = [f"decoder/blocks/{i}" for i in range(cfg.dec_layers)]
    dec_ln_f = dispatched.fetch("decoder/ln_f")
    done = jnp.zeros((B,), bool)
    out = []
    dbias = None
    for t in range(max_new_tokens):
        t_pass = _time.perf_counter()
        y = shared[dec].astype(cfg.dtype)
        for name, blk in stream_blocks(dispatched, dec_prefixes, prefetch=prefetch):
            if dbias is None:
                dbias = _rel_bias(blk["attn"]["rel_bias"], T, T, bidirectional=False, cfg=cfg)
            y = _dec_block_jit(y, blk, enc_out, dbias, causal, cmask, cfg=cfg)
            consume_block(y, blk, dispatched, name)  # fence + free (see encoder loop note)
        y_t = _t5_norm(y[:, t, :], dec_ln_f, cfg.norm_eps)
        if cfg.tie_embeddings:
            y_t = y_t * (cfg.d_model**-0.5)
        logits = _streamed_head_jit(y_t, head, transpose=cfg.tie_embeddings)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(done, eos_token_id, nxt)
        done = done | (nxt == eos_token_id)
        if pass_times is not None:
            jax.block_until_ready(nxt)  # graftlint: disable=host-sync-in-hot-path(pass_times contract: per-pass wall time blocked on the step output)
            pass_times.append(_time.perf_counter() - t_pass)
        out.append(nxt)
        dec = dec.at[:, t + 1].set(nxt)
        if bool(jnp.all(done)):
            out.extend([jnp.full((B,), eos_token_id, jnp.int32)] * (max_new_tokens - len(out)))
            break
    return jnp.stack(out, axis=1)


@partial(jax.jit, static_argnames=("cfg",))
def _enc_block_jit(x, blk, bias, mask, cfg):
    return _enc_block(x, blk, bias, mask, cfg)


@partial(jax.jit, static_argnames=("cfg",))
def _dec_block_jit(x, blk, enc_out, bias, causal, cmask, cfg):
    return _dec_block(x, blk, enc_out, bias, causal, cmask, cfg)


def num_params(cfg: T5Config) -> int:
    D, F, V, H, kv = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.n_heads, cfg.d_kv
    inner = H * kv
    attn = 3 * D * inner + inner * D
    ff = (2 * D * F if cfg.gated_ff else D * F) + F * D
    enc = cfg.n_layers * (attn + ff + 2 * D) + D + cfg.rel_buckets * H
    dec = cfg.dec_layers * (2 * attn + ff + 3 * D) + D + cfg.rel_buckets * H
    total = V * D + enc + dec
    if not cfg.tie_embeddings:
        total += D * V
    return total
