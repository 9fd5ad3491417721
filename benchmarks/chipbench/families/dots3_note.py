"""The family of ``"model_type": "dots3_note"``: dots3-note-prev's language model — its plain
reference, its seeded weights in the reference's and in the program's tree, and the counts
the readers need. Serving rows only (``program_config``, ``gen_params``,
``serve_reference``, ``compare_serve``): a training cell on this family is refused by
``run.load_window``.

**The plain reference** follows the configuration's keys (dots-studio/dots3-note-prev
``config.json``) in float32 ``jax.numpy`` at ``highest`` precision; it imports nothing of
``accelerate_tpu``, keeps no cache, never absorbs an up-projection, selects by its own
float32 index scores and routes by its own float32 router scores. Layer ``l`` is
``layer_types[l]``; with ``h = RMSNorm(x)``:

- **full_attention** (``num_attention_heads`` heads, ``q_lora_rank``, ``kv_lora_rank``,
  ``qk_nope_head_dim`` / ``qk_rope_head_dim`` / ``v_head_dim``, ``rope_theta``, no scaling):
  ``c_q = ρ_q · RMSNorm(h W_qa)``; ``[q_nope | q_rope] = c_q W_qb`` per head; ``[c_kv | k_rope]
  = h W_kva``; ``c_kv = ρ_kv · RMSNorm(c_kv)``; RoPE on ``q_rope`` and on the one ``k_rope``
  all heads share; ``[k_nope | v] = c_kv W_kvb`` per head; ``ρ_q = (hidden / q_lora_rank)^½``,
  ``ρ_kv = (hidden / kv_lora_rank)^½`` (``apply_mla_qkv_lora_rescale``).
  *Indexer* (``index_n_heads`` heads of ``index_head_dim``, DeepSeek-V3.2's): ``q^I = c_q
  W^I_q`` per index head and ``k^I = LayerNorm(h W^I_k)``, RoPE on the first
  ``qk_rope_head_dim`` dims of each; ``w = h W^I_w · heads^-½ · dim^-½``; ``I[t, s] = Σ_j
  w[t, j] · ReLU(q^I[t, j] · k^I[s])``; ``S_t`` = the ``min(index_topk, t + 1)`` keys ``s <= t``
  of largest ``I[t, s]``, a tie at the cut to the earlier key. ``o[t, head] = Σ_{s ∈ S_t}
  softmax_s(q_t · k_s · (nope + rope)^-½) v_s``.
  *Gate* (``attention_gate_type: headwise``): ``o[t, head] ← sigmoid(h_t W_g)[head] · o[t,
  head]``; then ``W_o``.
- **sliding_attention** (the ``swa_*`` keys): the same latent attention with its own
  sizes and RoPE base, no indexer; query ``t`` sees keys ``t - sliding_window_size < s <= t``.
- layer 0 (``first_k_dense_replace`` 1): SwiGLU; the others: ``s = sigmoid(h W_r)`` over ALL
  published experts, the ``num_experts_per_tok`` largest ``s + b`` chosen (no groups), gates
  = the chosen ``s`` ÷ their sum × ``routed_scaling_factor``; ``y = SwiGLU_shared(h) + Σ g_e ·
  SwiGLU_e(h)`` over the chosen experts THIS chip holds.

Departures (the file's ``assumed`` has each): the two scalar keys' readings (the gate is
computed from ``h`` by its own ``[hidden, heads]`` matrix; the rescale factors above); the
window's convention (513 keys with the query itself); index keys in the serving
precision (the published indexer holds them in FP8 after a Hadamard rotation, which is
orthogonal and changes no score in exact arithmetic); the indexer's LayerNorm ε 1e-6,
gain 1, bias 0; RoPE pairs are the two halves; ``e_score_correction_bias`` and the weights
are drawn from the seed; the vision and audio towers and the prediction module have no
key in the language model's config and are left out; what the absent experts would add
is left out and the partial sum goes on (the chip's share, ``model-configs`` guide §4).

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

from benchmarks.chipbench.reference import (
    HIGHEST, _bell, _fq, _mm, _rms, gen_ends, seed_key)

KINDS = {"full_attention": "", "sliding_attention": "swa_"}
INDEX_NORM_EPS = 1e-6


# ------------------------------------------------------------------------ configuration
def freeze(c: dict) -> tuple:
    """The sizes the equations need, hashable (a jit static argument)."""
    keys = ("hidden_size", "intermediate_size", "moe_intermediate_size",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "rope_theta", "swa_num_attention_heads",
            "swa_q_lora_rank", "swa_kv_lora_rank", "swa_qk_nope_head_dim",
            "swa_qk_rope_head_dim", "swa_v_head_dim", "swa_rope_theta",
            "sliding_window_size", "index_n_heads", "index_head_dim", "index_topk",
            "apply_mla_qkv_lora_rescale", "attention_gate_type", "swa_attention_gate_type",
            "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
            "n_shared_experts", "num_experts_per_tok", "routed_scaling_factor",
            "norm_topk_prob", "vocab_size", "rms_norm_eps")
    return tuple((k, c[k]) for k in keys) + (
        ("layer_types", tuple(c["layer_types"])),
        ("experts_published", c["published"]["n_routed_experts"]),
        ("expert_offset", c.get("expert_offset", 0)),
        ("router_bias_std", c["assumed"]["router_bias_std"]),
        ("dtype", c["serve"]["dtype"]))


def sizes(c: dict, kind: str) -> dict:
    """One layer kind's attention sizes under short names (``kind`` = a ``layer_types``
    entry; the sliding kind reads the ``swa_`` keys)."""
    p = KINDS[kind]
    out = {"H": c[p + "num_attention_heads"], "q_lora": c[p + "q_lora_rank"],
           "R": c[p + "kv_lora_rank"], "nope": c[p + "qk_nope_head_dim"],
           "r": c[p + "qk_rope_head_dim"], "vd": c[p + "v_head_dim"],
           "theta": float(c[p + "rope_theta"]),
           "gate": c[p + "attention_gate_type"] == "headwise",
           "window": c["sliding_window_size"] if p else 0, "indexed": not p}
    rescale = c["apply_mla_qkv_lora_rescale"]
    out["rho_q"] = math.sqrt(c["hidden_size"] / out["q_lora"]) if rescale else 1.0
    out["rho_kv"] = math.sqrt(c["hidden_size"] / out["R"]) if rescale else 1.0
    return out


def program_config(c: dict, **over):
    """The configuration file's sizes as the program's own config object."""
    from accelerate_tpu.models import dots3

    short = {"full_attention": "full", "sliding_attention": "sliding"}
    over.setdefault("dtype", getattr(jnp, c["serve"]["dtype"]))
    return dots3.Dots3Config(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
        layer_types=tuple(short[t] for t in c["layer_types"]),
        n_dense_layers=c["first_k_dense_replace"], n_heads=c["num_attention_heads"],
        q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_dim=c["qk_nope_head_dim"], qk_rope_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], rope_theta=float(c["rope_theta"]),
        index_heads=c["index_n_heads"], index_dim=c["index_head_dim"],
        index_topk=c["index_topk"], window=c["sliding_window_size"],
        swa_n_heads=c["swa_num_attention_heads"], swa_q_lora_rank=c["swa_q_lora_rank"],
        swa_kv_lora_rank=c["swa_kv_lora_rank"], swa_qk_nope_dim=c["swa_qk_nope_head_dim"],
        swa_qk_rope_dim=c["swa_qk_rope_head_dim"], swa_v_head_dim=c["swa_v_head_dim"],
        swa_rope_theta=float(c["swa_rope_theta"]),
        attn_gate=c["attention_gate_type"] == "headwise",
        lora_rescale=c["apply_mla_qkv_lora_rescale"], d_ff=c["intermediate_size"],
        moe_d_ff=c["moe_intermediate_size"],
        n_routed_experts=c["published"]["n_routed_experts"],
        experts_held=c["n_routed_experts"], expert_offset=c.get("expert_offset", 0),
        n_shared_experts=c["n_shared_experts"], experts_per_tok=c["num_experts_per_tok"],
        routed_scaling=float(c["routed_scaling_factor"]), norm_topk_prob=c["norm_topk_prob"],
        norm_eps=c["rms_norm_eps"], max_seq=c["max_position_embeddings"], **over)


# ------------------------------------------------------------------------------ weights
def _attn_shapes(c: dict, kind: str) -> dict:
    """A layer kind's attention matrices in the published layout, in drawing order."""
    z, D = sizes(c, kind), c["hidden_size"]
    out = {"q_a_proj": (D, z["q_lora"]),
           "q_b_proj": (z["q_lora"], z["H"] * (z["nope"] + z["r"])),
           "kv_a_proj_with_mqa": (D, z["R"] + z["r"]),
           "kv_b_proj": (z["R"], z["H"] * (z["nope"] + z["vd"])),
           "o_proj": (z["H"] * z["vd"], D)}
    if z["gate"]:
        out["g_proj"] = (D, z["H"])
    if z["indexed"]:
        out.update({"indexer_wq_b": (z["q_lora"], c["index_n_heads"] * c["index_head_dim"]),
                    "indexer_wk": (D, c["index_head_dim"]),
                    "indexer_weights_proj": (D, c["index_n_heads"])})
    return out


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


def gen_layer(c: dict, key, kind: str, dense: bool, dtype) -> dict:
    """One decoder layer in the published layout from the LAYER's key: variance 1/fan_in,
    norm gains 1 (the indexer's LayerNorm: gain 1, bias 0); an expert layer (not
    ``dense``) holds the router over ALL published experts, its selection bias, the shared
    expert and the experts held here, stacked ``[held, ...]``."""
    D, z = c["hidden_size"], sizes(c, kind)
    w = {"input_layernorm": jnp.ones((D,), dtype),
         "post_attention_layernorm": jnp.ones((D,), dtype),
         "q_a_layernorm": jnp.ones((z["q_lora"],), dtype),
         "kv_a_layernorm": jnp.ones((z["R"],), dtype)}
    for i, (name, shape) in enumerate(_attn_shapes(c, kind).items()):
        w[name] = _bell(jax.random.fold_in(key, i), shape, 1.0 / math.sqrt(shape[0]), dtype)
    if z["indexed"]:
        w["indexer_k_norm_weight"] = jnp.ones((c["index_head_dim"],), dtype)
        w["indexer_k_norm_bias"] = jnp.zeros((c["index_head_dim"],), dtype)
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


def program_layer(c: dict, w: dict, kind: str) -> dict:
    """A published-layout layer in the program's tree (``models/deepseek.py``): other
    names, and ``kv_b_proj`` split by head into its key and value halves."""
    z = sizes(c, kind)
    kvb = w["kv_b_proj"].reshape(z["R"], z["H"], z["nope"] + z["vd"])
    mlp = lambda m: {"w_gate": m["gate_proj"], "w_up": m["up_proj"],  # noqa: E731
                     "w_down": m["down_proj"]}
    out = {"ln_attn": w["input_layernorm"], "ln_mlp": w["post_attention_layernorm"],
           "w_qa": w["q_a_proj"], "q_norm": w["q_a_layernorm"], "w_qb": w["q_b_proj"],
           "w_kva": w["kv_a_proj_with_mqa"], "kv_norm": w["kv_a_layernorm"],
           "w_kb": kvb[..., :z["nope"]], "w_vb": kvb[..., z["nope"]:], "wo": w["o_proj"]}
    if z["gate"]:
        out["w_g"] = w["g_proj"]
    if z["indexed"]:
        out.update(idx_wq=w["indexer_wq_b"], idx_wk=w["indexer_wk"],
                   idx_ww=w["indexer_weights_proj"], idx_k_gain=w["indexer_k_norm_weight"],
                   idx_k_bias=w["indexer_k_norm_bias"])
    if "gate" not in w:
        return {**out, **mlp(w)}
    out["moe"] = {"router": w["gate"], "router_bias": w["e_score_correction_bias"],
                  "shared": mlp(w["shared_experts"]), "experts": mlp(w["experts"])}
    return out


@functools.partial(jax.jit, static_argnames=("cfg", "dtype"))
def _gen_params(key, cfg, dtype):
    c = dict(cfg)
    layers = [program_layer(c, gen_layer(c, jax.random.fold_in(key, l), kind,
                                         l < c["first_k_dense_replace"], dtype), kind)
              for l, kind in enumerate(c["layer_types"])]
    return {**gen_ends(c, key, dtype), "layers": layers}


def gen_params(c: dict, seed: int, dtype):
    """The seeded weights in the program's tree, ONE jitted call on the device."""
    import accelerate_tpu.models.dots3  # noqa: F401  (a program without it fails here)

    return _gen_params(seed_key(seed), freeze(c), dtype)


# ---------------------------------------------------------------------------- equations
def _rope(x, pos, theta: float):
    """x [T, (heads,) rope] at positions ``pos`` [T]; pairs are the two halves."""
    dim = x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = pos[:, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if x.ndim == 3:
        cos, sin = cos[:, None, :], sin[:, None, :]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rope_head(x, pos, theta: float, r: int):
    """RoPE on the first ``r`` dims of the last axis."""
    return jnp.concatenate([_rope(x[..., :r], pos, theta), x[..., r:]], -1)


def _index_inputs(h, c_q, w, c, fq):
    """The indexer's q^I [T, Hi, Di], k^I [T, Di] (RoPE on their first rope dims) and the
    heads' weights [T, Hi] of one row h [T, D]."""
    T = h.shape[0]
    Hi, Di, r = c["index_n_heads"], c["index_head_dim"], c["qk_rope_head_dim"]
    theta, pos = float(c["rope_theta"]), jnp.arange(T)
    q = _rope_head(_mm(c_q, w["indexer_wq_b"], fq).reshape(T, Hi, Di), pos, theta, r)
    k = _mm(h, w["indexer_wk"], fq)
    k = k - k.mean(-1, keepdims=True)
    k = k * jax.lax.rsqrt((k * k).mean(-1, keepdims=True) + INDEX_NORM_EPS)
    k = (k * w["indexer_k_norm_weight"].astype(jnp.float32)
         + w["indexer_k_norm_bias"].astype(jnp.float32))
    return q, _rope_head(k, pos, theta, r), _mm(h, w["indexer_weights_proj"], fq) * (
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


def index_scores(h, c_q, w, c, fq=None, q_chunk: int = 256):
    """The indexer of one row h [T, D] → I [T, T] float32 (no mask yet)."""
    q, k, wt = _index_inputs(h, c_q, w, c, fq)
    return _by_query_blocks(lambda _, qb, wb: _scores_of(qb, wb, k, fq), h.shape[0], q_chunk,
                            q, wt)


def select(scores, topk: int, q_chunk: int = 256):
    """scores [T, T] → bool [T, T]: the selection of every query (``_select_of``)."""
    return _by_query_blocks(lambda start, s: _select_of(s, start, topk), scores.shape[0],
                            q_chunk, scores)


def selection(h, c_q, w, c, fq=None, q_chunk: int = 256):
    """``select(index_scores(...))`` a block of queries at a time, so that only the mask
    [T, T] is ever held whole."""
    q, k, wt = _index_inputs(h, c_q, w, c, fq)
    return _by_query_blocks(
        lambda start, qb, wb: _select_of(_scores_of(qb, wb, k, fq), start, c["index_topk"]),
        h.shape[0], q_chunk, q, wt)


def attention(h, w, c, kind: str, fq=None, heads_block: int = 8, q_chunk: int = 256):
    """Latent attention of one row h [T, D] at positions 0..T-1 for a layer of ``kind``, a
    block of heads and a block of queries at a time. A sliding layer scores only the band
    of keys a block of queries can see; a full layer scores every key under the
    selection's mask. → (the layer's output [T, D], the mask [T, T] or None)."""
    T, z = h.shape[0], sizes(c, kind)
    H, nope, r, vd, R = z["H"], z["nope"], z["r"], z["vd"], z["R"]
    eps, scale = c["rms_norm_eps"], (nope + r) ** -0.5
    pos = jnp.arange(T)
    c_q = _rms(_mm(h, w["q_a_proj"], fq), w["q_a_layernorm"], eps) * z["rho_q"]
    kva = _mm(h, w["kv_a_proj_with_mqa"], fq)
    c_kv = _rms(kva[:, :R], w["kv_a_layernorm"], eps) * z["rho_kv"]
    k_rope = _rope(kva[:, R:], pos, z["theta"])                          # [T, r]
    hb, qc = min(heads_block, H), min(q_chunk, T)
    pad = -T % qc
    starts = jnp.arange((T + pad) // qc) * qc
    if z["indexed"]:               # every key, under the selection's mask
        chosen = selection(h, c_q, w, c, fq)
        lead, band, back = 0, T, 0
        mask = jnp.pad(chosen, ((0, pad), (0, 0))).reshape(-1, qc, T)
    else:                          # the band of keys a block of queries can see
        chosen = None
        lead = min(-(-(z["window"] - 1) // 8) * 8, T)        # keys before a block's first query
        band, back = qc + lead, pad
        mask = jnp.zeros((starts.shape[0], 1, 1), bool)                  # not read

    def one(qb, k, v, start, m):           # qb [qc,hb,nope+r], k [lead+T+back,hb,nope+r]
        first = 0 if z["indexed"] else start     # column j of the band: key first - lead + j
        kb = jax.lax.dynamic_slice_in_dim(k, first, band, 0)
        vb = jax.lax.dynamic_slice_in_dim(v, first, band, 0)
        s = jnp.einsum("qhd,thd->hqt", _fq(qb, fq), _fq(kb, fq), precision=HIGHEST) * scale
        if z["indexed"]:
            ok = m
        else:
            kp = start - lead + jnp.arange(band)[None, :]
            qp = (start + jnp.arange(qc))[:, None]
            ok = (kp >= 0) & (kp <= qp) & (kp > qp - z["window"])
        p = jax.nn.softmax(jnp.where(ok[None], s, -1e30), -1)
        return jnp.einsum("hqt,thd->qhd", _fq(p, fq), _fq(vb, fq), precision=HIGHEST)

    def per_heads(ws):
        w_qb, w_kvb, w_o, w_g = ws
        q = _mm(c_q, w_qb, fq).reshape(T, hb, nope + r)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, z["theta"])], -1)
        kv = _mm(c_kv, w_kvb, fq).reshape(T, hb, nope + vd)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope[:, None, :], (T, hb, r))], -1)
        grow = lambda a: jnp.pad(a, ((lead, back), (0, 0), (0, 0)))  # noqa: E731
        qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qc, hb, nope + r)
        kk, vv = grow(k), grow(kv[..., nope:])
        o = jax.lax.map(lambda a: one(a[0], kk, vv, a[1], a[2]), (qp, starts, mask))
        o = o.reshape(T + pad, hb, vd)[:T]
        if z["gate"]:
            o = o * jax.nn.sigmoid(_mm(h, w_g, fq))[:, :, None]
        return _mm(o.reshape(T, hb * vd), w_o, fq)

    by_heads = lambda a, axis: jnp.moveaxis(                              # noqa: E731
        a.reshape(*a.shape[:axis], H // hb, -1, *a.shape[axis + 1:]), axis, 0)
    gate = by_heads(w["g_proj"], 1) if z["gate"] else jnp.zeros((H // hb, 1, 1))
    out, _ = jax.lax.scan(            # summed as it goes: [H / hb, T, D] stacked would be GBs
        lambda acc, ws: (acc + per_heads(ws), None), jnp.zeros_like(h),
        (by_heads(w["q_b_proj"], 1), by_heads(w["kv_b_proj"], 1), by_heads(w["o_proj"], 0), gate))
    return out, chosen


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
    """→ gates [T, E_published] float32: a chosen expert's gate, 0 elsewhere. No groups:
    the ``num_experts_per_tok`` largest ``s + b`` over all experts."""
    E, k = c["experts_published"], c["num_experts_per_tok"]
    T = h.shape[0]
    s = jax.nn.sigmoid(_mm(h, w["gate"], fq))
    pick = s + w["e_score_correction_bias"]
    chosen = jnp.zeros((T, E), bool)
    for _ in range(k):           # the largest left, k times (a tie: the lower index): no sort
        best = jnp.argmax(jnp.where(chosen, -jnp.inf, pick), -1)
        chosen = chosen | (jnp.arange(E)[None, :] == best[:, None])
    g = jnp.where(chosen, s, 0.0)
    if c["norm_topk_prob"]:
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    return g * c["routed_scaling_factor"]


def moe(h, w, c, fq=None):
    """Shared expert + the chosen experts held here (``w["experts"]`` stacked ``[held,
    ...]``, published indices from ``expert_offset``), one held expert at a time over the
    tokens that chose it, gathered into ``cap`` rows (four times the expected load and
    never under 256; rows beyond its load carry gate 0 and are dropped); an expert that
    more tokens chose than ``cap`` holds turns the result into NaN rather than dropping
    one. No sort anywhere: a sort of 25 600 gates inside the scan took 30 s to compile."""
    T, held = h.shape[0], c["n_routed_experts"]
    gates = jax.lax.dynamic_slice_in_dim(route(h, w, c, fq), c["expert_offset"], held, 1)
    expected = T * c["num_experts_per_tok"] / c["experts_published"]
    cap = min(T, max(256, int(4 * expected)))

    def add(acc, eg):
        we, g = eg                                   # one expert's weights, its gates [T]
        rows = jnp.nonzero(g > 0, size=cap, fill_value=T)[0]     # the tokens that chose it
        gate = jnp.where(rows < T, g[jnp.minimum(rows, T - 1)], 0.0)
        out = gate[:, None] * swiglu(h[jnp.minimum(rows, T - 1)], we, fq)
        fits = jnp.where((g > 0).sum() <= cap, 1.0, jnp.nan)
        return acc.at[rows].add(out * fits), None        # a filler row lies past the end: dropped

    routed, _ = jax.lax.scan(add, jnp.zeros_like(h), (w["experts"], gates.T))
    return swiglu(h, w["shared_experts"], fq) + routed


def attention_part(x, w, c, kind: str, fq=None):
    """x + the attention of one decoder layer of ``kind`` on one row x [T, D]."""
    return x + attention(_rms(x, w["input_layernorm"], c["rms_norm_eps"]), w, c, kind, fq)[0]


def mlp_part(x, w, c, fq=None):
    """x + the layer's feed-forward: the expert layer if it has a router, else SwiGLU."""
    h = _rms(x, w["post_attention_layernorm"], c["rms_norm_eps"])
    return x + (moe(h, w, c, fq) if "gate" in w else swiglu(h, w, fq))


def block(x, w, c, kind: str, fq=None):
    """One decoder layer of ``kind`` on one row x [T, D] at positions 0..T-1."""
    return mlp_part(attention_part(x, w, c, kind, fq), w, c, fq)


# ------------------------------------------------------------------------------ serving
def _layer_weights(cfg, key, layer, kind, dense):
    c = dict(cfg)
    return c, gen_layer(c, jax.random.fold_in(key, layer), kind, dense, getattr(jnp, c["dtype"]))


@functools.partial(jax.jit, static_argnames=("cfg", "kind", "fq"), donate_argnums=(0,))
def _serve_attention(x, key, layer, cfg, kind, fq):
    """A layer's attention half on ONE row x [T, D] (donated: a layer's temporaries at
    25 600 tokens are some 5 GB, so rows go one at a time and nothing is held twice).
    ``layer`` is traced: the layers of one kind share one compiled program; the halves
    are programs of their own so that the expert layer, the slowest to compile, is
    compiled once and not once a kind. What a half does not read of the layer's
    weights is never made."""
    c, w = _layer_weights(cfg, key, layer, kind, True)     # attention's weights: either way
    return attention_part(x, w, c, kind, fq)


@functools.partial(jax.jit, static_argnames=("cfg", "dense", "fq"), donate_argnums=(0,))
def _serve_mlp(x, key, layer, cfg, dense, fq):
    c, w = _layer_weights(cfg, key, layer, "full_attention", dense)   # ... and the MLP's
    return mlp_part(x, w, c, fq)


@functools.partial(jax.jit, static_argnames=("cfg", "fq"))
def _serve_logits(x, at, key, cfg, fq):
    c = dict(cfg)
    ends = gen_ends(c, key, getattr(jnp, c["dtype"]))
    return _mm(_rms(x[at], ends["ln_f"], c["rms_norm_eps"]), ends["lm_head"], fq)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _serve_embed(ids, key, cfg):
    c = dict(cfg)
    return gen_ends(c, key, getattr(jnp, c["dtype"]))["embed"][ids].astype(jnp.float32)


def serve_reference(c: dict, seed: int, rows, width: int, n_out: int, fq=None):
    """One full forward over each row's prompt + served tokens, row by row and layer by
    layer (a layer's weights are made from the seed when it is due, rounded to
    ``serve.dtype`` as the program's are). ``rows`` = [(prompt ids, served ids)]; → logits
    [n, n_out, V] at the positions that produced each served token."""
    cfg, key = freeze(c), seed_key(seed)
    filler = np.random.default_rng([seed & 0x7FFFFFFF, 5])
    out = []
    for prompt, served in rows:
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        # Behind the sequence: seeded random ids, not one id repeated. No position before
        # them sees them, but a tail of thousands of one token sends every one of them
        # to the same experts, past ``moe``'s ``cap`` — and that turns the row into NaN.
        ids = filler.integers(0, c["vocab_size"], size=(width,)).astype(np.int32)
        ids[:len(seq)] = seq
        at = np.zeros((n_out,), np.int32)
        at[:len(served)] = len(prompt) - 1 + np.arange(len(served))
        x = _serve_embed(jnp.asarray(ids), key, cfg)
        for l, kind in enumerate(c["layer_types"]):     # one compiled program a layer KIND
            x = _serve_attention(x, key, l, cfg, kind, fq)  # graftlint: disable=recompile-hazard(two kinds of layer, a program each: the layer index is traced)
            x = _serve_mlp(x, key, l, cfg, l < c["first_k_dense_replace"], fq)
        out.append(_serve_logits(x, jnp.asarray(at), key, cfg, fq))
    return np.asarray(jnp.stack(out))


def compare_serve(rows, ref_logits, picked=None) -> dict:
    """The gap by which a served token's reference logit lies below the reference's best,
    over every served token of ``rows``: its maximum (``served_logit_gap``, the measure of
    ``reference.compare_serve``), its 90th percentile and its mean. ``picked`` [n, n_out]
    (the control) reads the tokens a lower precision puts first in place of the served
    ones. **Why more than the maximum:** a sparse layer attends to the keys ITS index
    scores rank first, and under random weights a query's softmax is sharp (logits of
    standard deviation ≈ 6), so a key that bfloat16 noise swaps at the selection's cut
    can carry a head's whole output: the program and the float32 reference part ways on
    a share of the positions, whatever the kernels do (the program in float32 agrees to
    the last token, ``tests/test_dots3.py``), and the maximum over a thousand tokens
    reads like the float8 control's. The body of the distribution does not: all three go
    to stderr for both calls, and ``limits`` names the ones that are compared."""
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
def attention_params(c: dict, kind: str) -> int:
    """One layer's attention matrices (full: 134 676 480 + the indexer's 9 371 648;
    sliding: 90 832 896, at the published widths)."""
    return sum(a * b for a, b in _attn_shapes(c, kind).values())


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def matmul_params(c: dict) -> int:
    """Parameters EVERY token meets in a matrix product: each layer's attention (its
    kind's, gate and indexer projections included), the dense layer, each expert layer's
    router (its published width) and shared expert, and the head over the vocabulary's
    slice. The embedding is a lookup; the routed experts are counted apart
    (``serve_flops_per_token``)."""
    D, L, dense = c["hidden_size"], c["num_hidden_layers"], c["first_k_dense_replace"]
    per_moe = D * c["published"]["n_routed_experts"] + c["n_shared_experts"] * expert_params(c)
    return (sum(attention_params(c, kind) for kind in c["layer_types"])
            + dense * 3 * D * c["intermediate_size"] + (L - dense) * per_moe
            + D * c["vocab_size"])


def serve_flops_per_token(c: dict) -> float:
    """2 × (``matmul_params`` + the routed experts a token meets HERE in expectation:
    ``num_experts_per_tok`` × held ÷ published = 1 an expert layer) per token processed,
    prompt or output. Left out, so the share errs low: attention's score products over
    the cache and the indexer's score products over every live key."""
    met = c["num_experts_per_tok"] * c["n_routed_experts"] / c["published"]["n_routed_experts"]
    moe_layers = c["num_hidden_layers"] - c["first_k_dense_replace"]
    return 2.0 * (matmul_params(c) + moe_layers * met * expert_params(c))


def _live_keys(c: dict, lens) -> list:
    """A lane's live keys from its position: ``lens`` counts the prompt's left pad, which
    the harness does not hand over, so the most the engine's chunk layout can pad
    (``prompt_bucket`` − 1) is taken off every lane — a share errs low by that, never high."""
    return [max(1, int(n) - (c["serve"]["prompt_bucket"] - 1)) for n in lens]


def paged_attn_work(c: dict, lens, page_size: int) -> tuple:
    """(FLOPs, bytes) ONE decode step's calls of ``mla_paged_attention`` need over all
    layers for lanes at positions ``lens`` — the rows the kernel is HANDED: a full layer's
    selected rows (``min(index_topk, live keys)`` a lane), a sliding layer's window
    (``min(sliding_window_size, live keys)``). Per row 2 · H · (rank + rope + rank) FLOPs
    against (rank + rope) bfloat16 values read, plus q and o a lane."""
    flops = nbytes = 0
    for kind in c["layer_types"]:
        z = sizes(c, kind)
        most = z["window"] if z["window"] else c["index_topk"]
        rows = sum(min(most, n) for n in _live_keys(c, lens))
        per_q = z["H"] * (2 * z["R"] + z["r"])
        flops += 2 * per_q * rows
        nbytes += rows * (z["R"] + z["r"]) * 2 + len(lens) * per_q * 2
    return flops, nbytes


def dsa_index_work(c: dict, lens, page_size: int) -> tuple:
    """(FLOPs, bytes) ONE decode step's calls of ``dsa_index_scores`` need over the full
    layers for lanes at positions ``lens``: per live key 2 · heads · dim FLOPs (the one
    product; the ReLU and the weighted sum are not counted) against ``dim`` bfloat16
    values read and one float32 score written, plus q (bfloat16) and w (float32) a lane."""
    Hi, Di = c["index_n_heads"], c["index_head_dim"]
    layers = sum(1 for kind in c["layer_types"] if sizes(c, kind)["indexed"])
    keys = sum(_live_keys(c, lens))
    return (layers * 2 * Hi * Di * keys,
            layers * (keys * (Di * 2 + 4) + len(lens) * (Hi * Di * 2 + Hi * 4)))
