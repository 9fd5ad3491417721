"""Keye-VL-2.0-30B-A3B's language model, as the serving engine runs it: ``models/deepseek.py``'s
decoder with its layers of the grouped-query kind (Kwai-Keye/Keye-VL-2.0-30B-A3B
``config.json``, ``model_type`` ``KeyeVL2``; the vision tower is not built).

48 identical layers at hidden 2048. Layer ``l``, with ``h = RMSNorm(x)`` (eps 1e-6):

- **Attention.** ``q = RMSNorm_128(h W_q)`` per head (32), ``k = RMSNorm_128(h W_k)`` per
  head (4), ``v = h W_v`` (4 heads of 128; no bias). Rotary on all 128 dims of q and k from
  a position array ``[3, T]``: frequency pair ``i`` of the 64 takes its angle from stream 0
  for ``i < 16``, stream 1 for ``16 <= i < 40``, stream 2 for ``40 <= i < 64``
  (``mrope_section`` [16, 24, 24]), base 1e7. The engine feeds three equal rows (text),
  which is plain RoPE; ``forward(..., positions=)`` takes unequal (t, h, w) rows.
- **Indexer** (``sa_config``: 16 index heads of 64, ONE index key a token, ``topk`` 2048):
  ``q_idx = h W_qi`` as 16 heads × 64, ``k_idx = LayerNorm_64(h W_ki)``, rotary on the first 32
  dims of both (stream 0), ``w = h W_w · 16^-½ · 64^-½``; ``I[t, s] = Σ_j w[t, j] · ReLU(q_idx[t,
  j] · k_idx[s])`` for ``s <= t``; query ``t`` attends the 2048 keys of largest ``I`` (all of
  them while ``t < 2048``), a tie at the cut to the earlier key; the SAME set for all 32
  heads. ``o = softmax(q kᵀ · 128^-½ over the set) v``, ``x += o W_o``.
- **Experts** (every layer: ``decoder_sparse_step`` 1, ``mlp_only_layers`` []): ``p =
  softmax(h W_r)`` over 128 in float32, the 8 largest, renormalised to sum 1
  (``norm_topk_prob``), ``x += Σ p_e · SwiGLU_e(h)``; drop-free (``ops.moe.moe_mlp_grouped``
  behind ``router_softmax_topk``), no shared expert, no auxiliary term in serving.

Departures from what the config spells (the benchmark's configuration file lists each
under ``assumed``): QK-norm (the config has no key; the 30B-A3B base whose sizes these
are has it); the indexer's three projections from ``h`` (there is no query latent), its
LayerNorm (eps 1e-6, gain 1, bias 0), its partial rotary and weight scale, after
DeepSeek-V3.2's; ``q_chunk_size`` / ``kv_chunk_size`` read as the published code's tiling of
the score computation, which changes no score; index keys in the serving precision;
rotary pairs are the two halves of a head.

**Which decoder carries it, and why.** This module is the config and the engine's surface
(``serving._model``: the module of the config's class); every forward is
``models/deepseek.py``'s — its layer loop reads ``cfg.attn_spec(l)``, and
:class:`~accelerate_tpu.models.deepseek.GqaSpec` is one more kind of layer there, beside
the latent kinds, with the SAME index plane, index kernel, landing, expert layer and
engine surface (``models/llama.py`` has none of them, and its cached forwards drop
tokens at an expert's capacity). The cache is K, V and index-key planes under
``BlockManager``'s one table. Not here, so the engine refuses them by name
(``_PATH_CALLS``): dense decode rows, speculative verify, the prefix cache; there is no
``kv_quant`` field to set.
"""

from __future__ import annotations

import dataclasses
from typing import ClassVar

import jax.numpy as jnp

from .deepseek import (ATTENTION_COUNTERS, GqaSpec, forward,  # noqa: F401
                       forward_cached, forward_slots_multi, init_cache, init_paged_cache,
                       init_params, paged_walk_shape)
from .deepseek import DECODE_COUNTERS as _MOE_COUNTERS

#: ``forward_slots_multi``'s counts for this config: the expert layers' three, then the
#: selection's (live keys scored, keys attended; the window's count stays 0).
DECODE_COUNTERS = _MOE_COUNTERS + ATTENTION_COUNTERS


@dataclasses.dataclass(frozen=True)
class KeyeConfig:
    vocab_size: int = 151936
    d_model: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    mrope_section: tuple = (16, 24, 24)
    qk_norm: bool = True
    # sa_config
    index_heads: int = 16
    index_dim: int = 64
    index_topk: int = 2048
    index_rope_dim: int = 32
    # experts, every layer
    moe_d_ff: int = 768
    n_routed_experts: int = 128       # the router's width, as published
    experts_held: int = 128           # routed experts this chip holds ...
    expert_offset: int = 0            # ... from this published index on
    experts_per_tok: int = 8
    norm_topk_prob: bool = True
    norm_eps: float = 1e-6
    max_seq: int = 262144
    dtype: jnp.dtype = jnp.bfloat16

    scan_layers: ClassVar[bool] = False
    counts_attention: ClassVar[bool] = True
    router: ClassVar[str] = "softmax"  # softmax over all experts, the top 8 renormalised
    n_dense_layers: ClassVar[int] = 0
    n_shared_experts: ClassVar[int] = 0
    n_group: ClassVar[int] = 1
    topk_group: ClassVar[int] = 1
    routed_scaling: ClassVar[float] = 1.0

    def attn_spec(self, layer: int) -> GqaSpec:
        """Every layer is one kind."""
        return GqaSpec(
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
            rope_theta=self.rope_theta, norm_eps=self.norm_eps, dtype=self.dtype,
            mrope_section=self.mrope_section, qk_norm=self.qk_norm,
            index_heads=self.index_heads, index_dim=self.index_dim,
            index_topk=self.index_topk, index_rope_dim=self.index_rope_dim)


CONFIGS = {
    # the published ratios at toy widths: 8 query / 2 K-V heads of 16, 4 index heads of 8
    # (16 keys a 128-lane pool row) that keep 64 keys, 16 experts of which 4 a token
    "tiny": KeyeConfig(
        vocab_size=256, d_model=128, n_layers=2, n_heads=8, n_kv_heads=2, head_dim=16,
        mrope_section=(2, 3, 3), index_heads=4, index_dim=8, index_topk=64,
        index_rope_dim=4, moe_d_ff=32, n_routed_experts=16, experts_held=16,
        experts_per_tok=4, max_seq=1024, dtype=jnp.float32),
}
