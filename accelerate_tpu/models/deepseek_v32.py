"""DeepSeek-V3.2's language model, as the serving engine runs it: ``models/deepseek.py``'s
decoder with V3.2's learned sparse selection on every layer, and its multi-token
prediction module served as the speculative drafter (deepseek-ai/DeepSeek-V3.2
``config.json``, ``model_type`` ``deepseek_v32``).

**The main stack** is V3's (MLA with YaRN, a dense SwiGLU first layer, then a sigmoid
router's 8 of 256 in 8 groups of which 4 are kept, × 2.5, beside a shared expert, over the
experts this chip holds), and every layer — the dense one too — carries V3.2's indexer:
``q^I = c_q W^I_q`` (64 heads of 128), ``k^I = LayerNorm(h W^I_k)``, RoPE (the layer's
YaRN) on the first ``qk_rope_dim`` dims of both, ``w = h W^I_w · 64^-½ · 128^-½``,
``I[t, s] = Σ_j w_j · ReLU(q^I_j · k^I_s)``; the query attends to the ``index_topk`` keys of
largest ``I`` (``ops/sparse_attention.py``). No gate, no rescale.

**The prediction module** (``num_nextn_predict_layers`` 1), for position ``i``: ``u_i =
W_eh [RMSNorm_e(E t_{i+1}); RMSNorm_h(h_i)]`` with ``h_i`` the main stack's last hidden
state (before its final norm), then one block of the model's own kind — latent attention
with its indexer over the module's OWN latent and index-key rows, then an expert layer —
then ``RMSNorm_s`` and the SHARED head: logits for ``t_{i+2}``. The cache holds the
module's planes as one more layer (``deepseek.cache_specs``).

**Served as the drafter** (``mtp_draft`` = 1, the engine's default ``spec_k``): a round
holds each lane's pending token ``x_p`` and its draft ``d``, verifies ``[x_p, d]`` at ``p, p +
1`` in one forward, accepts greedily (1 or 2 tokens), runs the module over rows ``p, p +
1`` with the ACCEPTED tokens, and takes the next draft from row ``p + n − 1``
(``deepseek._module_draft``); a row above the accepted prefix is written again the next
round. Prefill writes the module's rows ``0 .. n − 2`` from the prompt, and row ``n − 1``
and the first draft from the token the prompt's last logits put first, in the prefill
program itself. All of it inside the fused decode scan: no host round-trip a round.

This module is the config and the engine's surface (``serving._model``: the module of the
config's class); every forward is ``models/deepseek.py``'s. Not here, so the engine
refuses them by name: dense decode rows, the prefix cache.
"""

from __future__ import annotations

import dataclasses
from typing import ClassVar

import jax.numpy as jnp

from .deepseek import (ATTENTION_COUNTERS, DeepseekConfig, forward,  # noqa: F401
                       forward_cached, forward_mtp, forward_slots_multi,
                       forward_slots_paged, forward_slots_spec_multi, init_cache,
                       init_paged_cache, init_params, paged_walk_shape)
from .deepseek import DECODE_COUNTERS as _MOE_COUNTERS

#: ``forward_slots_multi``'s and ``forward_slots_spec_multi``'s counts for this config:
#: the expert layers' three, then the selection's (the window's count stays 0) — the
#: module's layer counted with the others.
DECODE_COUNTERS = _MOE_COUNTERS + ATTENTION_COUNTERS


@dataclasses.dataclass(frozen=True)
class DeepseekV32Config(DeepseekConfig):
    index_heads: int = 64
    index_dim: int = 128
    index_topk: int = 2048
    mtp_draft: int = 1                # num_nextn_predict_layers, served as the drafter

    counts_attention: ClassVar[bool] = True

    def __post_init__(self):
        if self.mtp_draft not in (0, 1):
            raise ValueError(f"mtp_draft={self.mtp_draft}: the module drafts one token a "
                             "round (0: not built)")


CONFIGS = {
    # every mechanism at toy widths: a dense first layer and two expert layers (16 experts
    # in 4 groups, 8 held), YaRN, an indexer that keeps 8 keys on every layer, the module
    "tiny": DeepseekV32Config(
        vocab_size=256, d_model=64, n_layers=3, n_dense_layers=1, n_heads=4,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
        d_ff=128, moe_d_ff=32, n_routed_experts=16, experts_held=8, expert_offset=0,
        experts_per_tok=4, n_group=4, topk_group=2, rope_orig_max=64, rope_factor=4.0,
        index_heads=4, index_dim=16, index_topk=8, max_seq=256, dtype=jnp.float32),
}
