"""dots3-note-prev's language model, as the serving engine runs it: ``models/deepseek.py``'s
decoder with layer kinds as data (dots-studio/dots3-note-prev ``config.json``).

Two kinds of latent-attention layer, named by ``layer_types``:

- **full** (128 heads, ``q_lora_rank`` 1024, ``kv_lora_rank`` 512, nope/rope/v 128/64/128,
  ``rope_theta`` 8e7): latent attention over pool pages, restricted by a learned sparse
  selection — an indexer (``index_heads`` heads of ``index_dim``) scores every live key
  from a per-token index key the layer caches beside its latent row, and the query
  attends to the ``index_topk`` best (``ops/sparse_attention.py``);
- **sliding** (``swa_*``: 64 heads, ranks 1024/1024, nope/rope/v 192/64/128, theta 5e4):
  the same latent attention over the last ``window`` keys, cached in a ring of pages a
  lane whose size does not grow with the context (``common.ring_tables``).

Both rescale their latents (``c_q · (d_model / q_lora_rank)^½``, ``c_kv · (d_model /
kv_lora_rank)^½``: ``apply_mla_qkv_lora_rescale``) and gate each head's output by
``sigmoid(h W_g)`` (``attention_gate_type: headwise``); no YaRN. The feed-forward side is
DeepSeek-V3's with no expert groups: a dense SwiGLU first layer, then a sigmoid router's
8 of 256 beside a shared expert, over the experts this chip holds.

This module is the config and the engine's surface (``serving._model``: the module of
the config's class); every forward is ``models/deepseek.py``'s. Not here, so the engine
refuses them by name: dense decode rows, speculative verify, the prefix cache; and a
prefill/decode hand-off, whose page lists cannot carry a lane's ring
(``lane_state_in_cache``).
"""

from __future__ import annotations

import dataclasses
from typing import ClassVar

import jax.numpy as jnp

from .deepseek import (ATTENTION_COUNTERS, AttnSpec, forward,  # noqa: F401
                       forward_cached, forward_slots_multi, init_cache, init_paged_cache,
                       init_params, paged_walk_shape)
from .deepseek import DECODE_COUNTERS as _MOE_COUNTERS

#: ``forward_slots_multi``'s counts for this config: the expert layers' three, then the
#: attention's (``counts_attention``).
DECODE_COUNTERS = _MOE_COUNTERS + ATTENTION_COUNTERS


@dataclasses.dataclass(frozen=True)
class Dots3Config:
    vocab_size: int = 152064
    d_model: int = 5120
    n_layers: int = 46
    layer_types: tuple = ("full",) * 2 + ("sliding", "sliding", "sliding", "full") * 11
    n_dense_layers: int = 1           # first_k_dense_replace
    # full layers
    n_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    index_heads: int = 64
    index_dim: int = 128
    index_topk: int = 2048
    # sliding layers
    window: int = 513
    swa_n_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_dim: int = 192
    swa_qk_rope_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    attn_gate: bool = True            # attention_gate_type: headwise
    lora_rescale: bool = True         # apply_mla_qkv_lora_rescale
    # feed-forward
    d_ff: int = 13824
    moe_d_ff: int = 1536
    n_routed_experts: int = 256       # the router's width, as published
    experts_held: int = 256           # routed experts this chip holds ...
    expert_offset: int = 0            # ... from this published index on
    n_shared_experts: int = 1
    experts_per_tok: int = 8
    routed_scaling: float = 1.0
    norm_topk_prob: bool = True
    norm_eps: float = 1e-5
    max_seq: int = 524288
    dtype: jnp.dtype = jnp.bfloat16

    scan_layers: ClassVar[bool] = False
    counts_attention: ClassVar[bool] = True
    router: ClassVar[str] = "sigmoid_grouped"
    n_group: ClassVar[int] = 1        # the router has no groups
    topk_group: ClassVar[int] = 1

    def __post_init__(self):
        if len(self.layer_types) != self.n_layers or set(self.layer_types) - {"full", "sliding"}:
            raise ValueError(f"layer_types {self.layer_types} does not name {self.n_layers} "
                             "layers, each 'full' or 'sliding'")

    def attn_spec(self, layer: int) -> AttnSpec:
        """The attention of layer ``layer``: its kind's sizes and switches."""
        def rescale(rank):
            return (self.d_model / rank) ** 0.5 if self.lora_rescale else 1.0

        common = dict(norm_eps=self.norm_eps, dtype=self.dtype, attn_gate=self.attn_gate)
        if self.layer_types[layer] == "sliding":
            return AttnSpec(
                n_heads=self.swa_n_heads, q_lora_rank=self.swa_q_lora_rank,
                kv_lora_rank=self.swa_kv_lora_rank, qk_nope_dim=self.swa_qk_nope_dim,
                qk_rope_dim=self.swa_qk_rope_dim, v_head_dim=self.swa_v_head_dim,
                rope_theta=self.swa_rope_theta, window=self.window,
                q_rescale=rescale(self.swa_q_lora_rank),
                kv_rescale=rescale(self.swa_kv_lora_rank), **common)
        return AttnSpec(
            n_heads=self.n_heads, q_lora_rank=self.q_lora_rank,
            kv_lora_rank=self.kv_lora_rank, qk_nope_dim=self.qk_nope_dim,
            qk_rope_dim=self.qk_rope_dim, v_head_dim=self.v_head_dim,
            rope_theta=self.rope_theta, index_heads=self.index_heads,
            index_dim=self.index_dim, index_topk=self.index_topk,
            q_rescale=rescale(self.q_lora_rank), kv_rescale=rescale(self.kv_lora_rank),
            **common)


def lane_state_in_cache(cfg: Dots3Config) -> bool:
    """The sliding layers keep a ring of pages a LANE, outside the block tables: a list
    of pages does not describe a lane's cache, so the engine refuses the paths that
    move or share caches by page list (a prefill/decode hand-off; the prefix cache and
    speculative verify are refused already, for the forwards they call)."""
    return "sliding" in cfg.layer_types


CONFIGS = {
    # every mechanism at toy widths: two layer kinds (window 5, a ring of 3 pages of 8),
    # an indexer that keeps 8 keys, gates and rescale, a dense first layer, 16 experts of
    # which 8 are held, a shared expert
    "tiny": Dots3Config(
        vocab_size=256, d_model=64, n_layers=5,
        layer_types=("full", "full", "sliding", "sliding", "sliding"), n_dense_layers=1,
        n_heads=4, q_lora_rank=32, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=16, index_heads=4, index_dim=16, index_topk=8, window=5,
        swa_n_heads=2, swa_q_lora_rank=32, swa_kv_lora_rank=48, swa_qk_nope_dim=24,
        swa_qk_rope_dim=8, swa_v_head_dim=16, d_ff=128, moe_d_ff=32,
        n_routed_experts=16, experts_held=8, expert_offset=0, experts_per_tok=4,
        max_seq=256, dtype=jnp.float32),
}
