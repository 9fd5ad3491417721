"""The family of ``"model_type": "deepseek_v32"``: DeepSeek-V3.2's language model and its
multi-token prediction module — the plain reference of both, their seeded weights in the
reference's and in the program's tree, and the counts the readers need. Serving rows only
(``program_config``, ``gen_params``, ``serve_reference``, ``compare_serve``): a training
cell on this family is refused by ``run.load_window``.

**The plain reference** follows the configuration's keys (deepseek-ai/DeepSeek-V3.2
``config.json``) in float32 ``jax.numpy`` at ``highest`` precision; it imports nothing of
``accelerate_tpu``, keeps no cache, never absorbs an up-projection, selects by its own
float32 index scores and routes by its own float32 router scores. With ``h = RMSNorm(x)``,
every layer:

- **MLA** (V3's): ``c_q = RMSNorm(h W_qa)``; ``[q_nope | q_rope] = c_q W_qb`` per head;
  ``[c_kv | k_rope] = h W_kva``; ``c_kv = RMSNorm(c_kv)``; YaRN RoPE on ``q_rope`` and on the
  one ``k_rope`` all heads share; ``[k_nope | v] = c_kv W_kvb`` per head; softmax scale
  ``(nope + rope)^-½ · m²``, ``m = 0.1 · mscale_all_dim · ln(factor) + 1``; ``W_o``.
- **The indexer** (``index_n_heads`` × ``index_head_dim``, on EVERY layer): ``q^I = c_q
  W^I_q`` per index head, ``k^I = LayerNorm(h W^I_k)``, the layer's YaRN RoPE on the first
  ``qk_rope_head_dim`` dims of each; ``w = h W^I_w · heads^-½ · dim^-½``; ``I[t, s] = Σ_j w[t,
  j] · ReLU(q^I[t, j] · k^I[s])``; query ``t`` attends the ``min(index_topk, t + 1)`` keys
  ``s <= t`` of largest ``I[t, s]``, a tie at the cut to the earlier key.
- the first ``first_k_dense_replace`` layers: SwiGLU; the others: ``s = sigmoid(h W_r)`` over
  ALL published experts, selection by ``s + b``: ``n_group`` groups, a group scores the sum
  of its two largest, the ``topk_group`` best stay, the ``num_experts_per_tok`` largest
  inside them are chosen; gates = the chosen ``s`` ÷ their sum × ``routed_scaling_factor``;
  ``y = SwiGLU_shared(h) + Σ g_e · SwiGLU_e(h)`` over the chosen experts THIS chip holds.
- **the prediction module** (``num_nextn_predict_layers`` 1), row ``i``: ``u_i = W_eh
  [RMSNorm_e(E t_{i+1}); RMSNorm_h(h_i)]`` (``h_i`` the last layer's output before the final
  norm), one layer of the kind above (an expert layer) over the module's own rows at
  positions ``0 ..``, then ``RMSNorm_s`` and the SHARED head: the logits of ``t_{i+2}``.

Departures (the file's ``assumed`` has each): ``eh_proj``'s columns take the embedding's
half first; ``h_i`` before the final norm (immaterial under unit gains); the module's
layer carries its indexer; shared embedding and head; index keys in the serving precision
where FP8 after a Hadamard rotation is published; RoPE pairs are the two halves; the
indexer's LayerNorm ε 1e-6, gain 1, bias 0; ``e_score_correction_bias`` and the weights are
drawn from the seed; what the absent experts would add is left out and the partial sum
goes on (the chip's share, ``model-configs`` guide §4).

What is no model's comes from ``reference.py`` (the seed's key, the bell-shaped draw,
float8 rounding); ``compare_serve`` is this family's own (its docstring says why).
"""

from __future__ import annotations

import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chipbench.reference import HIGHEST, _bell, _fq, _mm, _rms, gen_ends, seed_key

INDEX_NORM_EPS = 1e-6
MODULE_KEY = 1 << 16          # the module's weights are drawn from fold_in(seed key, this)


# ------------------------------------------------------------------------ configuration
def freeze(c: dict) -> tuple:
    """The sizes the equations need, hashable (a jit static argument)."""
    keys = ("hidden_size", "intermediate_size", "moe_intermediate_size",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "num_hidden_layers", "first_k_dense_replace",
            "n_routed_experts", "n_shared_experts", "num_experts_per_tok", "n_group",
            "topk_group", "routed_scaling_factor", "norm_topk_prob", "vocab_size",
            "rope_theta", "rms_norm_eps", "index_n_heads", "index_head_dim", "index_topk",
            "num_nextn_predict_layers")
    return tuple((k, c[k]) for k in keys) + (
        ("experts_published", c["published"]["n_routed_experts"]),
        ("expert_offset", c.get("expert_offset", 0)),
        ("router_bias_std", c["assumed"]["router_bias_std"]),
        ("dtype", c["serve"]["dtype"]),
        ("rope_scaling", tuple(sorted(c["rope_scaling"].items()))))


def program_config(c: dict, **over):
    """The configuration file's sizes as the program's own config object; the module
    drafts ``serve.draft_tokens`` a round."""
    from accelerate_tpu.models import deepseek_v32

    rope = c["rope_scaling"]
    over.setdefault("dtype", getattr(jnp, c["serve"]["dtype"]))
    return deepseek_v32.DeepseekV32Config(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_dense_layers=c["first_k_dense_replace"],
        n_heads=c["num_attention_heads"], q_lora_rank=c["q_lora_rank"],
        kv_lora_rank=c["kv_lora_rank"], qk_nope_dim=c["qk_nope_head_dim"],
        qk_rope_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        d_ff=c["intermediate_size"], moe_d_ff=c["moe_intermediate_size"],
        n_routed_experts=c["published"]["n_routed_experts"],
        experts_held=c["n_routed_experts"], expert_offset=c.get("expert_offset", 0),
        n_shared_experts=c["n_shared_experts"], experts_per_tok=c["num_experts_per_tok"],
        n_group=c["n_group"], topk_group=c["topk_group"],
        routed_scaling=c["routed_scaling_factor"], norm_topk_prob=c["norm_topk_prob"],
        rope_theta=float(c["rope_theta"]), rope_factor=float(rope["factor"]),
        rope_orig_max=rope["original_max_position_embeddings"],
        rope_beta_fast=float(rope["beta_fast"]), rope_beta_slow=float(rope["beta_slow"]),
        rope_mscale=float(rope["mscale"]), rope_mscale_all_dim=float(rope["mscale_all_dim"]),
        norm_eps=c["rms_norm_eps"], max_seq=c["max_position_embeddings"],
        index_heads=c["index_n_heads"], index_dim=c["index_head_dim"],
        index_topk=c["index_topk"], mtp_draft=c["serve"]["draft_tokens"], **over)


# ------------------------------------------------------------------------------ weights
def _attn_shapes(c: dict) -> dict:
    """A layer's attention and indexer matrices in the published layout, drawing order."""
    D, H = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return {"q_a_proj": (D, c["q_lora_rank"]), "q_b_proj": (c["q_lora_rank"], H * qk),
            "kv_a_proj_with_mqa": (D, c["kv_lora_rank"] + c["qk_rope_head_dim"]),
            "kv_b_proj": (c["kv_lora_rank"], H * (c["qk_nope_head_dim"] + c["v_head_dim"])),
            "o_proj": (H * c["v_head_dim"], D),
            "indexer_wq_b": (c["q_lora_rank"], c["index_n_heads"] * c["index_head_dim"]),
            "indexer_wk": (D, c["index_head_dim"]),
            "indexer_weights_proj": (D, c["index_n_heads"])}


def gen_mlp(c: dict, key, width: int, dtype) -> dict:
    """One SwiGLU's three matrices (the dense layer's, the shared expert's, one expert's)."""
    D = c["hidden_size"]
    shapes = {"gate_proj": (D, width), "up_proj": (D, width), "down_proj": (width, D)}
    return {n: _bell(jax.random.fold_in(key, i), s, 1.0 / math.sqrt(s[0]), dtype)
            for i, (n, s) in enumerate(shapes.items())}


def gen_expert(c: dict, layer_key, e, dtype) -> dict:
    """Published expert ``e`` of a layer: the same weights whichever chip holds it."""
    return gen_mlp(c, jax.random.fold_in(jax.random.fold_in(layer_key, 64), e),
                   c["moe_intermediate_size"], dtype)


def gen_layer(c: dict, key, dense: bool, dtype) -> dict:
    """One decoder layer in the published layout from the LAYER's key: variance 1/fan_in,
    norm gains 1 (the indexer's LayerNorm: gain 1, bias 0); an expert layer (not ``dense``)
    holds the router over ALL published experts, its selection bias, the shared expert and
    the experts held here, stacked ``[held, ...]``."""
    D = c["hidden_size"]
    w = {"input_layernorm": jnp.ones((D,), dtype),
         "post_attention_layernorm": jnp.ones((D,), dtype),
         "q_a_layernorm": jnp.ones((c["q_lora_rank"],), dtype),
         "kv_a_layernorm": jnp.ones((c["kv_lora_rank"],), dtype),
         "indexer_k_norm_weight": jnp.ones((c["index_head_dim"],), dtype),
         "indexer_k_norm_bias": jnp.zeros((c["index_head_dim"],), dtype)}
    for i, (name, shape) in enumerate(_attn_shapes(c).items()):
        w[name] = _bell(jax.random.fold_in(key, i), shape, 1.0 / math.sqrt(shape[0]), dtype)
    if dense:
        return {**w, **gen_mlp(c, jax.random.fold_in(key, 32), c["intermediate_size"], dtype)}
    E = c["experts_published"]
    w["gate"] = _bell(jax.random.fold_in(key, 33), (D, E), 1.0 / math.sqrt(D), dtype)
    w["e_score_correction_bias"] = _bell(
        jax.random.fold_in(key, 34), (E,), c["router_bias_std"], jnp.float32)
    w["shared_experts"] = gen_mlp(
        c, jax.random.fold_in(key, 35), c["moe_intermediate_size"] * c["n_shared_experts"],
        dtype)
    w["experts"] = jax.lax.map(
        lambda e: gen_expert(c, key, e, dtype),
        c["expert_offset"] + jnp.arange(c["n_routed_experts"]))
    return w


def gen_module(c: dict, key, dtype) -> dict:
    """The prediction module in the published layout (``model.layers.{L}``'s extra
    leaves): an expert layer from ``fold_in(key, MODULE_KEY)``, ``enorm`` / ``hnorm`` /
    ``shared_head.norm`` gains 1 and ``eh_proj`` [2D, D] (the embedding's half first)."""
    D, k = c["hidden_size"], jax.random.fold_in(key, MODULE_KEY)
    return {**gen_layer(c, k, False, dtype), "enorm": jnp.ones((D,), dtype),
            "hnorm": jnp.ones((D,), dtype), "shared_head_norm": jnp.ones((D,), dtype),
            "eh_proj": _bell(jax.random.fold_in(k, 99), (2 * D, D), 1.0 / math.sqrt(2 * D),
                             dtype)}


def program_layer(c: dict, w: dict) -> dict:
    """A published-layout layer in the program's tree (``models/deepseek.py``): other
    names, and ``kv_b_proj`` split by head into its key and value halves."""
    H, nope = c["num_attention_heads"], c["qk_nope_head_dim"]
    kvb = w["kv_b_proj"].reshape(c["kv_lora_rank"], H, nope + c["v_head_dim"])
    mlp = lambda m: {"w_gate": m["gate_proj"], "w_up": m["up_proj"],  # noqa: E731
                     "w_down": m["down_proj"]}
    out = {"ln_attn": w["input_layernorm"], "ln_mlp": w["post_attention_layernorm"],
           "w_qa": w["q_a_proj"], "q_norm": w["q_a_layernorm"], "w_qb": w["q_b_proj"],
           "w_kva": w["kv_a_proj_with_mqa"], "kv_norm": w["kv_a_layernorm"],
           "w_kb": kvb[..., :nope], "w_vb": kvb[..., nope:], "wo": w["o_proj"],
           "idx_wq": w["indexer_wq_b"], "idx_wk": w["indexer_wk"],
           "idx_ww": w["indexer_weights_proj"], "idx_k_gain": w["indexer_k_norm_weight"],
           "idx_k_bias": w["indexer_k_norm_bias"]}
    if "gate" not in w:
        return {**out, **mlp(w)}
    out["moe"] = {"router": w["gate"], "router_bias": w["e_score_correction_bias"],
                  "shared": mlp(w["shared_experts"]), "experts": mlp(w["experts"])}
    return out


@functools.partial(jax.jit, static_argnames=("cfg", "dtype"))
def _gen_params(key, cfg, dtype):
    c = dict(cfg)
    layers = [program_layer(c, gen_layer(c, jax.random.fold_in(key, l),
                                         l < c["first_k_dense_replace"], dtype))
              for l in range(c["num_hidden_layers"])]
    out = {**gen_ends(c, key, dtype), "layers": layers}
    if c["num_nextn_predict_layers"]:
        m = gen_module(c, key, dtype)
        out["mtp"] = {**program_layer(c, m), "enorm": m["enorm"], "hnorm": m["hnorm"],
                      "eh_proj": m["eh_proj"], "ln_out": m["shared_head_norm"]}
    return out


def gen_params(c: dict, seed: int, dtype):
    """The seeded weights in the program's tree, ONE jitted call on the device."""
    import accelerate_tpu.models.deepseek_v32  # noqa: F401  (a program without it fails here)

    return _gen_params(seed_key(seed), freeze(c), dtype)


# ---------------------------------------------------------------------------- equations
def yarn_inv_freq(c: dict):
    """Per-pair rotary frequencies under YaRN (``DeepseekV3YarnRotaryEmbedding``)."""
    rope = dict(c["rope_scaling"])
    dim, base = c["qk_rope_head_dim"], c["rope_theta"]
    freq = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))

    def correction_dim(turns):
        return (dim * math.log(rope["original_max_position_embeddings"]
                               / (turns * 2 * math.pi)) / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low if high > low else 0.001), 0.0, 1.0)
    return freq / rope["factor"] * ramp + freq * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(c: dict) -> float:
    rope = dict(c["rope_scaling"])
    m = yarn_mscale(rope["factor"], rope["mscale_all_dim"])
    return (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, pos, c):
    """x [T, (heads,) rope] at positions ``pos`` [T]; pairs are the two halves."""
    rope = dict(c["rope_scaling"])
    ang = pos[:, None].astype(jnp.float32) * yarn_inv_freq(c)
    m = yarn_mscale(rope["factor"], rope["mscale"]) / yarn_mscale(
        rope["factor"], rope["mscale_all_dim"])
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    if x.ndim == 3:
        cos, sin = cos[:, None, :], sin[:, None, :]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rope_head(x, pos, c):
    """The YaRN RoPE on the first ``qk_rope_head_dim`` dims of the last axis."""
    r = c["qk_rope_head_dim"]
    return jnp.concatenate([_rope(x[..., :r], pos, c), x[..., r:]], -1)


def _index_inputs(h, c_q, w, c, fq):
    """The indexer's q^I [T, Hi, Di], k^I [T, Di] (RoPE on their first rope dims) and the
    heads' weights [T, Hi] of one row h [T, D]."""
    T = h.shape[0]
    Hi, Di = c["index_n_heads"], c["index_head_dim"]
    pos = jnp.arange(T)
    q = _rope_head(_mm(c_q, w["indexer_wq_b"], fq).reshape(T, Hi, Di), pos, c)
    k = _mm(h, w["indexer_wk"], fq)
    k = k - k.mean(-1, keepdims=True)
    k = k * jax.lax.rsqrt((k * k).mean(-1, keepdims=True) + INDEX_NORM_EPS)
    k = (k * w["indexer_k_norm_weight"].astype(jnp.float32)
         + w["indexer_k_norm_bias"].astype(jnp.float32))
    return q, _rope_head(k, pos, c), _mm(h, w["indexer_weights_proj"], fq) * (
        Hi ** -0.5 * Di ** -0.5)


def _scores_of(q, wt, k, fq, heads_block: int = 16):
    """``I[t, s] = Σ_j wt[t, j] · ReLU(q[t, j] · k[s])`` for a block of queries q [qc, Hi,
    Di], a block of index heads at a time (no mask yet) → [qc, T] float32."""
    qc, Hi, Di = q.shape
    hb = min(heads_block, Hi)

    def heads(b):
        s = jnp.einsum("qhd,td->qht", _fq(b[0], fq), _fq(k, fq), precision=HIGHEST)
        return (jax.nn.relu(s) * b[1][..., None]).sum(1)

    total, _ = jax.lax.scan(
        lambda acc, b: (acc + heads(b), None), jnp.zeros((qc, k.shape[0]), jnp.float32),
        (jnp.moveaxis(q.reshape(qc, Hi // hb, hb, Di), 1, 0),
         jnp.moveaxis(wt.reshape(qc, Hi // hb, hb), 1, 0)))
    return total


def _select_of(s, start, topk: int):
    """s [qc, T], the scores of queries ``start ..`` → bool [qc, T]: for query ``t`` the
    ``min(topk, t + 1)`` keys ``s <= t`` of largest score, a tie at the cut to the earlier."""
    qc, T = s.shape
    k = min(topk, T)
    causal = jnp.arange(T)[None, :] <= (start + jnp.arange(qc))[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    cut = jax.lax.top_k(s, k)[0][:, -1:]                # the k-th largest (-inf: fewer live)
    above = s > cut
    tie = (s == cut) & causal
    room = k - above.sum(-1, keepdims=True)
    return above | (tie & (jnp.cumsum(tie, -1) <= room))


def _by_query_blocks(fn, T: int, q_chunk: int, *per_query):
    """``fn(start, *blocks)`` over blocks of ``q_chunk`` queries → rows [T, ...]."""
    qc = min(q_chunk, T)
    pad = -T % qc
    blocks = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(-1, qc, *a.shape[1:])
              for a in per_query]
    out = jax.lax.map(lambda a: fn(a[0], *a[1:]), (jnp.arange((T + pad) // qc) * qc, *blocks))
    return out.reshape(T + pad, *out.shape[2:])[:T]


def selection(h, c_q, w, c, fq=None, q_chunk: int = 256):
    """The selection of every query of one row: bool [T, T], a block of queries at a time,
    so that only the mask is ever held whole."""
    q, k, wt = _index_inputs(h, c_q, w, c, fq)
    return _by_query_blocks(
        lambda start, qb, wb: _select_of(_scores_of(qb, wb, k, fq), start, c["index_topk"]),
        h.shape[0], q_chunk, q, wt)


def attention(h, w, c, fq=None, heads_block: int = 8, q_chunk: int = 256):
    """Latent attention of one row h [T, D] at positions 0..T-1 under the indexer's
    selection, a block of heads and a block of queries at a time → the layer's output [T, D]."""
    T = h.shape[0]
    H, nope, r, vd = (c["num_attention_heads"], c["qk_nope_head_dim"],
                      c["qk_rope_head_dim"], c["v_head_dim"])
    R, eps, scale = c["kv_lora_rank"], c["rms_norm_eps"], softmax_scale(c)
    pos = jnp.arange(T)
    c_q = _rms(_mm(h, w["q_a_proj"], fq), w["q_a_layernorm"], eps)
    kva = _mm(h, w["kv_a_proj_with_mqa"], fq)
    c_kv = _rms(kva[:, :R], w["kv_a_layernorm"], eps)
    k_rope = _rope(kva[:, R:], pos, c)                                   # [T, r]
    hb, qc = min(heads_block, H), min(q_chunk, T)
    pad = -T % qc
    mask = jnp.pad(selection(h, c_q, w, c, fq), ((0, pad), (0, 0))).reshape(-1, qc, T)

    def one(qb, k, v, m):                  # qb [qc,hb,nope+r], k [T,hb,nope+r], v [T,hb,vd]
        s = jnp.einsum("qhd,thd->hqt", _fq(qb, fq), _fq(k, fq), precision=HIGHEST) * scale
        p = jax.nn.softmax(jnp.where(m[None], s, -1e30), -1)
        return jnp.einsum("hqt,thd->qhd", _fq(p, fq), _fq(v, fq), precision=HIGHEST)

    def per_heads(ws):
        w_qb, w_kvb, w_o = ws
        q = _mm(c_q, w_qb, fq).reshape(T, hb, nope + r)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, c)], -1)
        kv = _mm(c_kv, w_kvb, fq).reshape(T, hb, nope + vd)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope[:, None, :], (T, hb, r))], -1)
        qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qc, hb, nope + r)
        o = jax.lax.map(lambda a: one(a[0], k, kv[..., nope:], a[1]), (qp, mask))
        return _mm(o.reshape(T + pad, hb * vd)[:T], w_o, fq)

    by_heads = lambda a, axis: jnp.moveaxis(                              # noqa: E731
        a.reshape(*a.shape[:axis], H // hb, -1, *a.shape[axis + 1:]), axis, 0)
    out, _ = jax.lax.scan(            # summed as it goes: [H / hb, T, D] stacked would be GBs
        lambda acc, ws: (acc + per_heads(ws), None), jnp.zeros_like(h),
        (by_heads(w["q_b_proj"], 1), by_heads(w["kv_b_proj"], 1), by_heads(w["o_proj"], 0)))
    return out


def swiglu(h, w, fq=None, chunk: int = 4096):
    """SwiGLU over blocks of tokens, so the wide tensors stay small."""
    T, D = h.shape
    cs = min(chunk, T)
    pad = -T % cs

    def one(hb):
        return _mm(jax.nn.silu(_mm(hb, w["gate_proj"], fq)) * _mm(hb, w["up_proj"], fq),
                   w["down_proj"], fq)

    return jax.lax.map(one, jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, cs, D)
                       ).reshape(T + pad, D)[:T]


def route(h, w, c, fq=None):
    """→ gates [T, E_published] float32: a chosen expert's gate, 0 elsewhere. The
    ``topk_group`` groups of largest two-best sum stay; inside them the largest ``s + b``,
    one at a time (a tie: the lower index) — no sort over the experts."""
    E, k, G = c["experts_published"], c["num_experts_per_tok"], c["n_group"]
    T = h.shape[0]
    s = jax.nn.sigmoid(_mm(h, w["gate"], fq))
    pick = s + w["e_score_correction_bias"]
    groups = pick.reshape(T, G, E // G)
    group_score = jax.lax.top_k(groups, 2)[0].sum(-1)                    # two largest
    kept = jax.lax.top_k(group_score, c["topk_group"])[1]
    keep = jnp.zeros((T, G), bool).at[jnp.arange(T)[:, None], kept].set(True)
    masked = jnp.where(keep[:, :, None], groups, -jnp.inf).reshape(T, E)
    chosen = jnp.zeros((T, E), bool)
    for _ in range(k):
        best = jnp.argmax(jnp.where(chosen, -jnp.inf, masked), -1)
        chosen = chosen | (jnp.arange(E)[None, :] == best[:, None])
    g = jnp.where(chosen, s, 0.0)
    if c["norm_topk_prob"]:
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    return g * c["routed_scaling_factor"]


def moe(h, w, c, fq=None):
    """Shared expert + the chosen experts held here (``w["experts"]`` stacked ``[held,
    ...]``, published indices from ``expert_offset``), one held expert at a time over EVERY
    token, weighted by its gate (0 where the token did not choose it). With 8 of 256
    experts held a layer's held experts cost as much as the shared one does 8 times over,
    and no load can overflow a capacity: a cap of a few times the expected load turned a
    prediction module's rows into NaN at 3 584 tokens (PR 40)."""
    held = c["n_routed_experts"]
    gates = jax.lax.dynamic_slice_in_dim(route(h, w, c, fq), c["expert_offset"], held, 1)

    def add(acc, eg):
        we, g = eg                                   # one expert's weights, its gates [T]
        return acc + g[:, None] * swiglu(h, we, fq), None

    routed, _ = jax.lax.scan(add, jnp.zeros_like(h), (w["experts"], gates.T))
    return swiglu(h, w["shared_experts"], fq) + routed


def attention_part(x, w, c, fq=None):
    """x + the attention of one layer on one row x [T, D]."""
    return x + attention(_rms(x, w["input_layernorm"], c["rms_norm_eps"]), w, c, fq)


def mlp_part(x, w, c, fq=None):
    """x + the layer's feed-forward: the expert layer if it has a router, else SwiGLU."""
    h = _rms(x, w["post_attention_layernorm"], c["rms_norm_eps"])
    return x + (moe(h, w, c, fq) if "gate" in w else swiglu(h, w, fq))


def block(x, w, c, fq=None):
    """One decoder layer on one row x [T, D] at positions 0..T-1."""
    return mlp_part(attention_part(x, w, c, fq), w, c, fq)


def module_input(hidden, next_embed, m, c, fq=None):
    """``u_i = W_eh [RMSNorm_e(E t_{i+1}); RMSNorm_h(h_i)]`` for one row."""
    eps = c["rms_norm_eps"]
    eh = jnp.concatenate([_rms(next_embed, m["enorm"], eps), _rms(hidden, m["hnorm"], eps)], -1)
    return _mm(eh, m["eh_proj"], fq)


@functools.partial(jax.jit, static_argnames=("cfg", "fq"))
def forward(ids, key, cfg, fq=None):
    """Whole-row reference on one device, ``cfg`` = :func:`freeze`'s: (main logits [T, V],
    module logits [T, V] — row ``i`` from ``h_i`` and ``ids[i + 1]``, the last row from
    ``ids[0]``, which scores nothing; read rows ``< T - 1``). Small sizes only (the tests):
    every weight is held."""
    c = dict(cfg)
    dtype = getattr(jnp, c["dtype"])
    ends = gen_ends(c, key, dtype)
    x = ends["embed"][ids].astype(jnp.float32)
    for l in range(c["num_hidden_layers"]):
        x = block(x, gen_layer(c, jax.random.fold_in(key, l), l < c["first_k_dense_replace"],
                               dtype), c, fq)
    eps = c["rms_norm_eps"]
    logits = _mm(_rms(x, ends["ln_f"], eps), ends["lm_head"], fq)
    m = gen_module(c, key, dtype)
    nxt = ends["embed"][jnp.roll(ids, -1)].astype(jnp.float32)
    u = block(module_input(x, nxt, m, c, fq), m, c, fq)
    return logits, _mm(_rms(u, m["shared_head_norm"], eps), ends["lm_head"], fq)


# ------------------------------------------------------------------------------ serving
def _layer_weights(cfg, key, layer, dense):
    c = dict(cfg)
    return c, gen_layer(c, jax.random.fold_in(key, layer), dense, getattr(jnp, c["dtype"]))


@functools.partial(jax.jit, static_argnames=("cfg", "fq"), donate_argnums=(0,))
def _serve_attention(x, key, layer, cfg, fq):
    """A layer's attention half on ONE row x [T, D] (donated: rows go one at a time and
    nothing is held twice). ``layer`` is traced: the layers share one compiled program;
    the halves are programs of their own so that the expert layer, the slowest to
    compile, is compiled once. What a half does not read of the weights is never made."""
    c, w = _layer_weights(cfg, key, layer, True)
    return attention_part(x, w, c, fq)


@functools.partial(jax.jit, static_argnames=("cfg", "dense", "fq"), donate_argnums=(0,))
def _serve_mlp(x, key, layer, cfg, dense, fq):
    c, w = _layer_weights(cfg, key, layer, dense)
    return mlp_part(x, w, c, fq)


@functools.partial(jax.jit, static_argnames=("cfg", "fq"))
def _serve_module(x, ids_next, key, cfg, fq):
    """The module's rows of one row: its input from x [T, D] (the last layer's output) and
    the ids that follow, its layer → u [T, D] before its norm."""
    c = dict(cfg)
    dtype = getattr(jnp, c["dtype"])
    m = gen_module(c, key, dtype)
    nxt = gen_ends(c, key, dtype)["embed"][ids_next].astype(jnp.float32)
    return block(module_input(x, nxt, m, c, fq), m, c, fq)


@functools.partial(jax.jit, static_argnames=("cfg", "fq", "module"))
def _serve_logits(x, at, key, cfg, fq, module):
    c = dict(cfg)
    dtype = getattr(jnp, c["dtype"])
    ends = gen_ends(c, key, dtype)
    norm = gen_module(c, key, dtype)["shared_head_norm"] if module else ends["ln_f"]
    return _mm(_rms(x[at], norm, c["rms_norm_eps"]), ends["lm_head"], fq)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _serve_embed(ids, key, cfg):
    c = dict(cfg)
    return gen_ends(c, key, getattr(jnp, c["dtype"]))["embed"][ids].astype(jnp.float32)


def _rows_through(c: dict, seed: int, rows, width: int, n_out: int, fq, module_at=None):
    """Each row's prompt + served tokens through the main stack, row by row and layer by
    layer (a layer's weights are made from the seed when it is due, rounded to
    ``serve.dtype`` as the program's are) → main logits [n, n_out, V] at the positions that
    produced each served token; with ``module_at`` (per row, positions [n_out]) also the
    module's logits there."""
    cfg, key = freeze(c), seed_key(seed)
    filler = np.random.default_rng([seed & 0x7FFFFFFF, 5])
    out, mod = [], []
    for i, (prompt, served) in enumerate(rows):
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        # Behind the sequence: seeded random ids, not one id repeated (a tail of one token
        # sends every position to the same experts, past ``moe``'s ``cap``).
        ids = filler.integers(0, c["vocab_size"], size=(width,)).astype(np.int32)
        ids[:len(seq)] = seq
        at = np.zeros((n_out,), np.int32)
        at[:len(served)] = len(prompt) - 1 + np.arange(len(served))
        x = _serve_embed(jnp.asarray(ids), key, cfg)
        for l in range(c["num_hidden_layers"]):
            x = _serve_attention(x, key, l, cfg, fq)
            x = _serve_mlp(x, key, l, cfg, l < c["first_k_dense_replace"], fq)
        out.append(_serve_logits(x, jnp.asarray(at), key, cfg, fq, False))
        if module_at is not None:
            u = _serve_module(x, jnp.asarray(np.roll(ids, -1)), key, cfg, fq)
            mod.append(_serve_logits(u, jnp.asarray(module_at[i]), key, cfg, fq, True))
    return np.asarray(jnp.stack(out)), (np.asarray(jnp.stack(mod)) if mod else None)


def serve_reference(c: dict, seed: int, rows, width: int, n_out: int, fq=None):
    """One full forward over each row's prompt + served tokens; ``rows`` = [(prompt ids,
    served ids)]; → logits [n, n_out, V] at the positions that produced each served token."""
    return _rows_through(c, seed, rows, width, n_out, fq)[0]


def module_reference(c: dict, seed: int, rows, width: int, at, fq=None):
    """The prediction module's logits [n, len(at[i]), V] at positions ``at`` of each row's
    prompt + served tokens (row ``i`` reads ``h_i`` and the token at ``i + 1``: a position
    below the sequence's last), for the drafts a served run made there."""
    n_out = max(1, max(len(r[1]) for r in rows))
    return _rows_through(c, seed, rows, width, n_out, fq, [np.asarray(a) for a in at])[1]


def compare_serve(rows, ref_logits, picked=None) -> dict:
    """The gap by which a served token's reference logit lies below the reference's best,
    over every served token of ``rows``: its maximum, its 90th percentile and its mean.
    ``picked`` [n, n_out] (the control) reads the tokens a lower precision puts first in
    place of the served ones. **Why more than the maximum:** every layer attends to the keys
    ITS index scores rank first, and under random weights a query's softmax is sharp, so a
    key that bfloat16 noise swaps at the selection's cut can carry a head's whole output:
    the program and the float32 reference part ways on a share of the positions, whatever
    the kernels do (the program in float32 agrees, ``tests/test_deepseek_v32.py``), and the
    maximum over a thousand tokens reads like the float8 control's. The body of the
    distribution does not: all three go to stderr, and ``limits`` names the compared ones."""
    gaps = []
    for i, (_, served) in enumerate(rows):
        n = len(served)
        tok = served if picked is None else picked[i][:n]
        lg = ref_logits[i, :n]
        gaps.append(lg.max(-1) - lg[np.arange(n), tok])
    gaps = np.concatenate(gaps)
    out = {"served_logit_gap": float(gaps.max()),
           "served_logit_gap_p90": float(np.quantile(gaps, 0.9)),
           "served_logit_gap_mean": float(gaps.mean())}
    print(f"compare_serve ({'control' if picked is not None else 'served'}, {len(gaps)} "
          f"tokens): {out}", file=sys.stderr)
    return out


# ------------------------------------------------------------------------------- counts
def attention_params(c: dict) -> int:
    """One layer's attention and indexer matrices (187 105 280 + 13 959 168 at the
    published widths)."""
    return sum(a * b for a, b in _attn_shapes(c).values())


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def module_params(c: dict) -> int:
    """The module's matrices beside its layer's: ``eh_proj``."""
    return 2 * c["hidden_size"] ** 2


def matmul_params(c: dict) -> int:
    """Parameters EVERY token meets in a matrix product: each layer's attention and
    indexer, the dense layer, each expert layer's router (its published width) and shared
    expert, the module (``eh_proj``, its layer's attention, router and shared expert) and
    the head over the vocabulary's slice. The embedding is a lookup; the routed experts are
    counted apart (``serve_flops_per_token``)."""
    D, L, dense = c["hidden_size"], c["num_hidden_layers"], c["first_k_dense_replace"]
    per_moe = D * c["published"]["n_routed_experts"] + c["n_shared_experts"] * expert_params(c)
    mtp = c["num_nextn_predict_layers"]
    return ((L + mtp) * attention_params(c) + dense * 3 * D * c["intermediate_size"]
            + (L - dense + mtp) * per_moe + mtp * module_params(c) + D * c["vocab_size"])


def serve_flops_per_token(c: dict) -> float:
    """2 × (``matmul_params`` + the routed experts a token meets HERE in expectation:
    ``num_experts_per_tok`` × held ÷ published = 0.25 an expert layer, the module's
    counted) per token processed, prompt or output: a FLOOR for a token that speculation
    emits, whose round also verified a draft and ran the module twice. Left out, so the
    share errs low: attention's score products over the cache and the indexer's over every
    live key."""
    met = c["num_experts_per_tok"] * c["n_routed_experts"] / c["published"]["n_routed_experts"]
    moe_layers = c["num_hidden_layers"] - c["first_k_dense_replace"] + c["num_nextn_predict_layers"]
    return 2.0 * (matmul_params(c) + moe_layers * met * expert_params(c))


def _rows(c: dict, lens) -> list:
    """The query rows a decode round hands the kernels: each lane's ``k + 1`` verify rows
    (``serve.draft_tokens`` + 1) at its position and after, with their live keys — ``lens``
    counts the prompt's left pad, which the harness does not hand over, so the most the
    engine's chunk layout can pad (``prompt_bucket`` − 1) is taken off every lane: a share
    errs low by that, never high."""
    pad = c["serve"]["prompt_bucket"] - 1
    return [max(1, int(n) - pad) + j for n in lens
            for j in range(c["serve"]["draft_tokens"] + 1)]


def paged_attn_work(c: dict, lens, page_size: int) -> tuple:
    """(FLOPs, bytes) ONE speculative round's calls of ``mla_paged_attention`` need over
    the main stack's layers and the module's for lanes at positions ``lens``: the
    selected rows each verify row is HANDED (``min(index_topk, live keys)``) — the module
    runs over the same rows a round. Per row 2 · H · (rank + rope + rank) FLOPs against
    (rank + rope) bfloat16 values read, plus q and o a query row."""
    H, R, r = c["num_attention_heads"], c["kv_lora_rank"], c["qk_rope_head_dim"]
    layers = c["num_hidden_layers"] + c["num_nextn_predict_layers"]
    q_rows = _rows(c, lens)
    keys = sum(min(c["index_topk"], n) for n in q_rows)
    per_q = H * (2 * R + r)
    return (layers * 2 * per_q * keys,
            layers * (keys * (R + r) * 2 + len(q_rows) * per_q * 2))


def dsa_index_work(c: dict, lens, page_size: int) -> tuple:
    """(FLOPs, bytes) ONE speculative round's calls of ``dsa_index_scores`` need over the
    main stack's layers and the module's for lanes at positions ``lens``: per verify row
    and live key 2 · heads · dim FLOPs (the one product; the ReLU and the weighted sum are
    not counted) against ``dim`` bfloat16 values read and one float32 score written, plus q
    (bfloat16) and w (float32) a query row."""
    Hi, Di = c["index_n_heads"], c["index_head_dim"]
    layers = c["num_hidden_layers"] + c["num_nextn_predict_layers"]
    q_rows = _rows(c, lens)
    keys = sum(q_rows)
    return (layers * 2 * Hi * Di * keys,
            layers * (keys * (Di * 2 + 4) + len(q_rows) * (Hi * Di * 2 + Hi * 4)))
