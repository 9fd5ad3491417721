"""The family of ``"model_type": "deepseek_v3"``: DeepSeek-V3's plain reference, its seeded
weights in the reference's and in the program's tree, and the counts the readers need.
Serving rows only (``program_config``, ``gen_params``, ``serve_reference``,
``compare_serve``): a training cell on this family is refused by ``run.load_window``.

**The plain reference** follows the published equations (deepseek-ai/DeepSeek-V3
``config.json`` and ``modeling_deepseek.py``) in float32 ``jax.numpy`` at ``highest``
precision; it imports nothing of ``accelerate_tpu``, keeps no cache, never absorbs an
up-projection and always routes by its own float32 scores. With ``h = RMSNorm(x)``:

- MLA, the prefill form only: ``c_q = RMSNorm(h W_qa)``; ``[q_nope | q_rope] = c_q W_qb``
  per head; ``[c_kv | k_rope] = h W_kva``; ``c_kv = RMSNorm(c_kv)``; YaRN RoPE on
  ``q_rope`` and on the one ``k_rope`` all heads share; ``[k_nope | v] = c_kv W_kvb`` per
  head; ``o = softmax([q_nope | q_rope] [k_nope | k_rope]ᵀ · s) v`` under the causal mask,
  ``s = (nope + rope)^-½ · m²``, ``m = 0.1 · mscale_all_dim · ln(factor) + 1``; ``W_o``.
- the first ``first_k_dense_replace`` layers: SwiGLU; the others: ``s = sigmoid(h W_r)``
  over ALL published experts, selection by ``s + b``: ``n_group`` groups, a group scores
  the sum of its two largest, the ``topk_group`` best stay, the ``num_experts_per_tok``
  largest inside them are chosen; gates = the chosen ``s`` ÷ their sum ×
  ``routed_scaling_factor``; ``y = SwiGLU_shared(h) + Σ g_e · SwiGLU_e(h)`` over the chosen
  experts THIS chip holds (``n_routed_experts`` of ``published.n_routed_experts``, from
  ``expert_offset``).

Departures, all forced by the cut or by random weights (the file's ``assumed``):
(1) what the absent experts would add is left out and the partial sum goes on (the
chip's share of an expert-parallel deployment, ``model-configs`` guide §4); (2) embedding
and head over the vocabulary's slice; (3) the multi-token-prediction module follows the
last published layer and is cut with the depth; (4) RoPE pairs are the two halves of the
rotary dims, not the checkpoint's interleaved pairs (a fixed permutation of ``W_qb`` /
``W_kva`` columns, immaterial for random weights); (5) ``e_score_correction_bias`` is
drawn from the seed (std ``assumed.router_bias_std``), weights at variance 1 / fan_in.

What is no model's comes from ``reference.py`` (the seed's key, the bell-shaped draw,
float8 rounding, ``compare_serve``) and ``work.py``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chipbench.reference import (  # noqa: F401  (compare_serve is handed out)
    HIGHEST, _bell, _fq, _mm, _rms, compare_serve, gen_ends, seed_key)

ATTN_LEAVES = ("q_a_proj", "q_b_proj", "kv_a_proj_with_mqa", "kv_b_proj", "o_proj")
MLP_LEAVES = ("gate_proj", "up_proj", "down_proj")


# ------------------------------------------------------------------------ configuration
def freeze(c: dict) -> tuple:
    """The sizes the equations need, hashable (a jit static argument)."""
    keys = ("hidden_size", "intermediate_size", "moe_intermediate_size",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "num_hidden_layers", "first_k_dense_replace",
            "n_routed_experts", "n_shared_experts", "num_experts_per_tok", "n_group",
            "topk_group", "routed_scaling_factor", "norm_topk_prob", "vocab_size",
            "rope_theta", "rms_norm_eps")
    rope = c["rope_scaling"]
    return tuple((k, c[k]) for k in keys) + (
        ("experts_published", c["published"]["n_routed_experts"]),
        ("expert_offset", c.get("expert_offset", 0)),
        ("router_bias_std", c["assumed"]["router_bias_std"]),
        ("dtype", c["serve"]["dtype"]),          # the precision the weights are served in
        ("rope_scaling", tuple(sorted(rope.items()))))


def program_config(c: dict, **over):
    """The configuration file's sizes as the program's own config object."""
    from accelerate_tpu.models import deepseek

    rope = c["rope_scaling"]
    over.setdefault("dtype", getattr(jnp, c["serve"]["dtype"]))
    return deepseek.DeepseekConfig(
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
        norm_eps=c["rms_norm_eps"], max_seq=c["max_position_embeddings"], **over)


# ------------------------------------------------------------------------------ weights
def _attn_shapes(c: dict) -> dict:
    D, H = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return {"q_a_proj": (D, c["q_lora_rank"]), "q_b_proj": (c["q_lora_rank"], H * qk),
            "kv_a_proj_with_mqa": (D, c["kv_lora_rank"] + c["qk_rope_head_dim"]),
            "kv_b_proj": (c["kv_lora_rank"], H * (c["qk_nope_head_dim"] + c["v_head_dim"])),
            "o_proj": (H * c["v_head_dim"], D)}


def gen_mlp(c: dict, key, width: int, dtype) -> dict:
    """One SwiGLU's three matrices (a dense layer's, the shared expert's, one expert's)."""
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
    norm gains 1; an expert layer (not ``dense``) holds the router over ALL published
    experts, its selection bias, the shared expert and the experts held here, stacked
    ``[held, ...]``."""
    D = c["hidden_size"]
    w = {"input_layernorm": jnp.ones((D,), dtype),
         "post_attention_layernorm": jnp.ones((D,), dtype),
         "q_a_layernorm": jnp.ones((c["q_lora_rank"],), dtype),
         "kv_a_layernorm": jnp.ones((c["kv_lora_rank"],), dtype)}
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
           "w_kb": kvb[..., :nope], "w_vb": kvb[..., nope:], "wo": w["o_proj"]}
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
    return {**gen_ends(c, key, dtype), "layers": layers}


def gen_params(c: dict, seed: int, dtype):
    """The seeded weights in the program's tree, ONE jitted call on the device."""
    import accelerate_tpu.models.deepseek  # noqa: F401  (a program without it fails here)

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


def mla(h, w, c, fq=None, heads_block: int = 16, q_chunk: int = 1024):
    """Latent attention of one row h [T, D] at positions 0..T-1, a block of heads and a
    block of queries at a time: the [heads_block, q_chunk, T] scores are
    all that is ever held."""
    T = h.shape[0]
    H, nope, r, vd = (c["num_attention_heads"], c["qk_nope_head_dim"],
                      c["qk_rope_head_dim"], c["v_head_dim"])
    R, eps, scale = c["kv_lora_rank"], c["rms_norm_eps"], softmax_scale(c)
    pos = jnp.arange(T)
    c_q = _rms(_mm(h, w["q_a_proj"], fq), w["q_a_layernorm"], eps)
    kva = _mm(h, w["kv_a_proj_with_mqa"], fq)
    c_kv = _rms(kva[:, :R], w["kv_a_layernorm"], eps)
    k_rope = _rope(kva[:, R:], pos, c)                                   # [T, r]
    hb = min(heads_block, H)
    qc = min(q_chunk, T)
    pad = -T % qc
    starts = jnp.arange((T + pad) // qc) * qc

    def one(qb, k, v, start):              # qb [qc,hb,nope+r], k [T,hb,nope+r], v [T,hb,vd]
        s = jnp.einsum("qhd,thd->hqt", _fq(qb, fq), _fq(k, fq), precision=HIGHEST) * scale
        ok = pos[None, :] <= (start + jnp.arange(qc))[:, None]
        p = jax.nn.softmax(jnp.where(ok[None], s, -1e30), -1)
        return jnp.einsum("hqt,thd->qhd", _fq(p, fq), _fq(v, fq), precision=HIGHEST)

    def per_heads(ws):
        w_qb, w_kvb, w_o = ws              # [q_lora, hb*(nope+r)], [R, hb*(nope+vd)], [hb*vd, D]
        q = _mm(c_q, w_qb, fq).reshape(T, hb, nope + r)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, c)], -1)
        kv = _mm(c_kv, w_kvb, fq).reshape(T, hb, nope + vd)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope[:, None, :], (T, hb, r))], -1)
        qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qc, hb, nope + r)
        o = jax.lax.map(lambda a: one(a[0], k, kv[..., nope:], a[1]), (qp, starts))
        return _mm(o.reshape(T + pad, hb * vd)[:T], w_o, fq)

    by_heads = lambda a, axis: jnp.moveaxis(                              # noqa: E731
        a.reshape(*a.shape[:axis], H // hb, -1, *a.shape[axis + 1:]), axis, 0)
    return jax.lax.map(per_heads, (by_heads(w["q_b_proj"], 1), by_heads(w["kv_b_proj"], 1),
                                   by_heads(w["o_proj"], 0))).sum(0)


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
    """→ gates [T, E_published] float32: a chosen expert's gate, 0 elsewhere."""
    E, k, G = c["experts_published"], c["num_experts_per_tok"], c["n_group"]
    T = h.shape[0]
    s = jax.nn.sigmoid(_mm(h, w["gate"], fq))
    pick = s + w["e_score_correction_bias"]
    groups = pick.reshape(T, G, E // G)
    group_score = jnp.sort(groups, -1)[..., -2:].sum(-1)                  # two largest
    kept = jnp.argsort(-group_score, -1)[:, :c["topk_group"]]
    keep = jnp.zeros((T, G), bool).at[jnp.arange(T)[:, None], kept].set(True)
    masked = jnp.where(keep[:, :, None], groups, -jnp.inf).reshape(T, E)
    chosen = jnp.argsort(-masked, -1)[:, :k]
    g = jnp.take_along_axis(s, chosen, 1)
    if c["norm_topk_prob"]:
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    return jnp.zeros((T, E), jnp.float32).at[jnp.arange(T)[:, None], chosen].set(
        g * c["routed_scaling_factor"])


def moe(h, w, c, fq=None):
    """Shared expert + the chosen experts held here (``w["experts"]`` stacked
    ``[held, ...]``, published indices from ``expert_offset``), one held expert at a
    time over the tokens that chose it. So that the block fits and ends, an expert's
    tokens are gathered into ``cap`` rows (four times the expected load and never under
    256; rows beyond its load carry gate 0), and an expert that more tokens chose than
    ``cap`` holds turns the result into NaN rather than dropping one."""
    T, held = h.shape[0], c["n_routed_experts"]
    gates = jax.lax.dynamic_slice_in_dim(route(h, w, c, fq), c["expert_offset"], held, 1)
    expected = T * c["num_experts_per_tok"] / c["experts_published"]
    cap = min(T, max(256, int(4 * expected)))

    def add(acc, eg):
        we, g = eg                                   # one expert's weights, its gates [T]
        rows = jnp.argsort(-g)[:cap]                 # the tokens that chose it come first
        out = g[rows, None] * swiglu(h[rows], we, fq)
        fits = jnp.where((g > 0).sum() <= cap, 1.0, jnp.nan)
        return acc.at[rows].add(out * fits), None

    routed, _ = jax.lax.scan(add, jnp.zeros_like(h), (w["experts"], gates.T))
    return swiglu(h, w["shared_experts"], fq) + routed


def block(x, w, c, fq=None):
    """One decoder layer on one row x [T, D] at positions 0..T-1."""
    eps = c["rms_norm_eps"]
    x = x + mla(_rms(x, w["input_layernorm"], eps), w, c, fq)
    h = _rms(x, w["post_attention_layernorm"], eps)
    return x + (moe(h, w, c, fq) if "gate" in w else swiglu(h, w, fq))


# ------------------------------------------------------------------------------ serving
@functools.partial(jax.jit, static_argnames=("cfg", "dense", "fq"))
def _serve_layer(x, key, layer, cfg, dense, fq):
    """``layer`` is traced: the expert layers share one compiled program."""
    c = dict(cfg)
    w = gen_layer(c, jax.random.fold_in(key, layer), dense, getattr(jnp, c["dtype"]))
    return jax.lax.map(lambda r: block(r, w, c, fq), x)


@functools.partial(jax.jit, static_argnames=("cfg", "fq"))
def _serve_logits(x, at, key, cfg, fq):
    c = dict(cfg)
    ends = gen_ends(c, key, getattr(jnp, c["dtype"]))
    h = jnp.take_along_axis(x, at[:, :, None], 1)
    return _mm(_rms(h, ends["ln_f"], c["rms_norm_eps"]), ends["lm_head"], fq)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _serve_embed(ids, key, cfg):
    c = dict(cfg)
    return gen_ends(c, key, getattr(jnp, c["dtype"]))["embed"][ids].astype(jnp.float32)


def serve_reference(c: dict, seed: int, rows, width: int, n_out: int, fq=None):
    """One full forward over each row's prompt + served tokens, layer by layer (a layer's
    weights are made from the seed when it is due, rounded to ``serve.dtype`` as the
    program's are). ``rows`` = [(prompt ids, served
    ids)]; → logits [n, n_out, V] at the positions that produced each served token."""
    cfg = freeze(c)
    ids = np.zeros((len(rows), width), np.int32)
    at = np.zeros((len(rows), n_out), np.int32)
    for i, (prompt, served) in enumerate(rows):
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        ids[i, :len(seq)] = seq
        at[i, :len(served)] = len(prompt) - 1 + np.arange(len(served))
    key = seed_key(seed)
    x = _serve_embed(jnp.asarray(ids), key, cfg)
    for l in range(c["num_hidden_layers"]):
        x = _serve_layer(x, key, l, cfg, l < c["first_k_dense_replace"], fq)
    return np.asarray(_serve_logits(x, jnp.asarray(at), key, cfg, fq))


# ------------------------------------------------------------------------------- counts
def attention_params(c: dict) -> int:
    """One layer's five attention matrices (187 105 280 at the published widths)."""
    return sum(a * b for a, b in _attn_shapes(c).values())


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def matmul_params(c: dict) -> int:
    """Parameters EVERY token meets in a matrix product: attention, the dense layers,
    each expert layer's router (its published width) and shared expert, and the head
    over the vocabulary's slice. The embedding is a lookup; the routed experts are
    counted apart (``serve_flops_per_token``)."""
    D, L, dense = c["hidden_size"], c["num_hidden_layers"], c["first_k_dense_replace"]
    per_moe = D * c["published"]["n_routed_experts"] + c["n_shared_experts"] * expert_params(c)
    return (L * attention_params(c) + dense * 3 * D * c["intermediate_size"]
            + (L - dense) * per_moe + D * c["vocab_size"])


def serve_flops_per_token(c: dict) -> float:
    """2 × (``matmul_params`` + the routed experts a token meets HERE in expectation:
    ``num_experts_per_tok`` × held ÷ published = 0.5 an expert layer) per token
    processed, prompt or output. Attention's score products over the cache are NOT
    counted (as in Mistral's count), so the share errs low."""
    met = c["num_experts_per_tok"] * c["n_routed_experts"] / c["published"]["n_routed_experts"]
    moe_layers = c["num_hidden_layers"] - c["first_k_dense_replace"]
    return 2.0 * (matmul_params(c) + moe_layers * met * expert_params(c))


def paged_attn_work(c: dict, lens, page_size: int) -> tuple:
    """(FLOPs, bytes) ONE decode step's latent attention needs over all layers for lanes
    at positions ``lens``: per live key 2 · H · (rank + rope + rank) FLOPs (the two score
    products and p · c_kv) against (rank + rope) bfloat16 values read, plus q and o a
    lane. A lane's keys are counted from its first valid slot: ``lens`` counts the
    prompt's left pad, which the harness does not hand over, so the most the engine's
    chunk layout can pad (``prompt_bucket`` − 1) is taken off every lane — the share
    errs low by at most that, never high."""
    H, R, r, L = (c["num_attention_heads"], c["kv_lora_rank"], c["qk_rope_head_dim"],
                  c["num_hidden_layers"])
    keys = sum(max(1, int(n) - (c["serve"]["prompt_bucket"] - 1)) for n in lens)
    flops = L * 2 * H * (2 * R + r) * keys
    return flops, L * (keys * (R + r) * 2 + len(lens) * H * (2 * R + r) * 2)
