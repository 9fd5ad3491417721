"""The family of ``"model_type": "KeyeVL2"``: Keye-VL-2.0-30B-A3B's language model — its
plain reference, its seeded weights in the reference's and in the program's tree, and the
counts the readers need. Serving rows only (``program_config``, ``gen_params``,
``serve_reference``, ``compare_serve``): a training cell on this family is refused by
``run.load_window``. The vision tower has no key in the language model's config and is
not built.

**The plain reference** follows the configuration's keys (Kwai-Keye/Keye-VL-2.0-30B-A3B
``config.json``) in float32 ``jax.numpy`` at ``highest`` precision; it imports nothing of
``accelerate_tpu``, keeps no cache, computes the whole ``[T, T]`` index-score matrix a
block of queries at a time, cuts its own top-``topk`` mask, attends densely under it and
routes by its own float32 scores. Every layer is the same; with ``h = RMSNorm(x)``:

- ``q = RMSNorm_hd(h W_q)`` per head (``num_attention_heads``), ``k = RMSNorm_hd(h W_k)`` per
  head (``num_key_value_heads``), ``v = h W_v``; rotary on all ``head_dim`` dims of q and k
  from a position array ``[3, T]``: frequency pair ``i`` takes its angle from the stream
  whose entry of ``rope_scaling.mrope_section`` holds ``i`` ([16, 24, 24]: 0–15 from stream 0,
  16–39 from stream 1, 40–63 from stream 2), base ``rope_theta``.
- *Indexer* (``sa_config``): ``q^I = h W^I_q`` as ``indexer_num_heads`` heads of
  ``indexer_head_dim``, ``k^I = LayerNorm(h W^I_k)`` (one key a token), rotary on the first
  ``index_rope_dim`` dims of both by stream 0, ``w = h W^I_w · heads^-½ · dim^-½``; ``I[t, s] = Σ_j
  w[t, j] · ReLU(q^I[t, j] · k^I[s])``; ``S_t`` = the ``min(topk, t + 1)`` keys ``s <= t`` of
  largest ``I[t, s]``, a tie at the cut to the earlier key; the SAME set for every head.
  ``o[t, head] = Σ_{s ∈ S_t} softmax_s(q_t · k_s · head_dim^-½) v_s``; then ``W_o``.
- Experts: ``p = softmax(h W_r)`` over ``num_experts``, the ``num_experts_per_tok`` largest
  (a tie to the lower index), renormalised to sum 1 (``norm_topk_prob``); ``y = Σ p_e ·
  SwiGLU_e(h)``. No shared expert, no bias.

Departures (the file's ``assumed`` has each): QK-norm; the indexer's projections from
``h``, its LayerNorm ε 1e-6 with gain 1 and bias 0, its partial rotary (32 of 64 dims) and
weight scale, after DeepSeek-V3.2's; ``q_chunk_size`` / ``kv_chunk_size`` are the published
code's tiling of the score computation and change no score; index keys in the serving
precision; RoPE pairs are the two halves; weights drawn from the seed.

What is no model's comes from ``reference.py`` (the seed's key, the bell-shaped draw,
float8 rounding); ``compare_serve`` reads the gap's maximum, 90th percentile and mean.
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

INDEX_NORM_EPS = 1e-6


# ------------------------------------------------------------------------ configuration
def freeze(c: dict) -> tuple:
    """The sizes the equations need, hashable (a jit static argument)."""
    keys = ("hidden_size", "moe_intermediate_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "rope_theta", "num_hidden_layers",
            "num_experts", "num_experts_per_tok", "norm_topk_prob", "vocab_size",
            "rms_norm_eps")
    sa = c["sa_config"]
    return tuple((k, c[k]) for k in keys) + (
        ("mrope_section", tuple(c["rope_scaling"]["mrope_section"])),
        ("index_heads", sa["indexer_num_heads"]), ("index_dim", sa["indexer_head_dim"]),
        ("index_topk", sa["topk"]), ("index_rope_dim", c["assumed"]["index_rope_dim"]),
        ("qk_norm", c["assumed"]["qk_norm"]), ("dtype", c["serve"]["dtype"]))


def program_config(c: dict, **over):
    """The configuration file's sizes as the program's own config object."""
    from accelerate_tpu.models import keye

    z = dict(freeze(c))
    over.setdefault("dtype", getattr(jnp, c["serve"]["dtype"]))
    return keye.KeyeConfig(
        vocab_size=z["vocab_size"], d_model=z["hidden_size"], n_layers=z["num_hidden_layers"],
        n_heads=z["num_attention_heads"], n_kv_heads=z["num_key_value_heads"],
        head_dim=z["head_dim"], rope_theta=float(z["rope_theta"]),
        mrope_section=z["mrope_section"], qk_norm=z["qk_norm"],
        index_heads=z["index_heads"], index_dim=z["index_dim"], index_topk=z["index_topk"],
        index_rope_dim=z["index_rope_dim"], moe_d_ff=z["moe_intermediate_size"],
        n_routed_experts=z["num_experts"], experts_held=z["num_experts"],
        experts_per_tok=z["num_experts_per_tok"], norm_topk_prob=z["norm_topk_prob"],
        norm_eps=z["rms_norm_eps"], max_seq=c["max_position_embeddings"], **over)


# ------------------------------------------------------------------------------ weights
def _attn_shapes(c: dict) -> dict:
    """A layer's attention and indexer matrices in the published layout, in drawing order."""
    D, hd = c["hidden_size"], c["head_dim"]
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    return {"q_proj": (D, H * hd), "k_proj": (D, K * hd), "v_proj": (D, K * hd),
            "o_proj": (H * hd, D), "indexer_wq": (D, c["index_heads"] * c["index_dim"]),
            "indexer_wk": (D, c["index_dim"]), "indexer_weights_proj": (D, c["index_heads"])}


def gen_expert(c: dict, layer_key, e, dtype) -> dict:
    """Expert ``e`` of a layer: its three SwiGLU matrices from a key of its own."""
    D, F = c["hidden_size"], c["moe_intermediate_size"]
    key = jax.random.fold_in(jax.random.fold_in(layer_key, 64), e)
    shapes = {"gate_proj": (D, F), "up_proj": (D, F), "down_proj": (F, D)}
    return {n: _bell(jax.random.fold_in(key, i), s, 1.0 / math.sqrt(s[0]), dtype)
            for i, (n, s) in enumerate(shapes.items())}


def gen_layer(c: dict, key, dtype, experts: bool = True) -> dict:
    """One decoder layer in the published layout from the LAYER's key: variance 1/fan_in,
    norm gains 1 (the indexer's LayerNorm: gain 1, bias 0), the router over all experts
    and (``experts``) the experts stacked ``[num_experts, ...]``."""
    D, hd = c["hidden_size"], c["head_dim"]
    w = {"input_layernorm": jnp.ones((D,), dtype),
         "post_attention_layernorm": jnp.ones((D,), dtype),
         "q_norm": jnp.ones((hd,), dtype), "k_norm": jnp.ones((hd,), dtype),
         "indexer_k_norm_weight": jnp.ones((c["index_dim"],), dtype),
         "indexer_k_norm_bias": jnp.zeros((c["index_dim"],), dtype)}
    for i, (name, shape) in enumerate(_attn_shapes(c).items()):
        w[name] = _bell(jax.random.fold_in(key, i), shape, 1.0 / math.sqrt(shape[0]), dtype)
    w["gate"] = _bell(jax.random.fold_in(key, 33), (D, c["num_experts"]),
                      1.0 / math.sqrt(D), dtype)
    if experts:
        w["experts"] = jax.lax.map(lambda e: gen_expert(c, key, e, dtype),
                                   jnp.arange(c["num_experts"]))
    return w


def program_layer(w: dict) -> dict:
    """A published-layout layer in the program's tree (``models/deepseek.py``, the
    grouped-query kind): other names, nothing reshaped."""
    e = w["experts"]
    return {"ln_attn": w["input_layernorm"], "ln_mlp": w["post_attention_layernorm"],
            "wq": w["q_proj"], "wk": w["k_proj"], "wv": w["v_proj"], "wo": w["o_proj"],
            "q_norm": w["q_norm"], "k_norm": w["k_norm"],
            "idx_wq": w["indexer_wq"], "idx_wk": w["indexer_wk"],
            "idx_ww": w["indexer_weights_proj"], "idx_k_gain": w["indexer_k_norm_weight"],
            "idx_k_bias": w["indexer_k_norm_bias"],
            "moe": {"router": w["gate"],
                    "experts": {"w_gate": e["gate_proj"], "w_up": e["up_proj"],
                                "w_down": e["down_proj"]}}}


@functools.partial(jax.jit, static_argnames=("cfg", "dtype"))
def _gen_params(key, cfg, dtype):
    c = dict(cfg)
    layers = [program_layer(gen_layer(c, jax.random.fold_in(key, l), dtype))
              for l in range(c["num_hidden_layers"])]
    return {**gen_ends(c, key, dtype), "layers": layers}


def gen_params(c: dict, seed: int, dtype):
    """The seeded weights in the program's tree, ONE jitted call on the device."""
    import accelerate_tpu.models.keye  # noqa: F401  (a program without it fails here)

    return _gen_params(seed_key(seed), freeze(c), dtype)


# ---------------------------------------------------------------------------- equations
def _rotate(x, ang):
    """x [T, (heads,) dim] by angles ``ang`` [T, dim / 2]; pairs are the two halves."""
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if x.ndim == 3:
        cos, sin = cos[:, None, :], sin[:, None, :]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mrope(x, pos3, theta: float, section) -> jax.Array:
    """Rotary over all of x's last dim from the position rows ``pos3`` [3, T]: pair ``i``
    reads the row whose entry of ``section`` holds ``i``."""
    dim = x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    owner = np.repeat(np.arange(len(section)), section)          # [dim / 2]
    return _rotate(x, pos3.astype(jnp.float32).T[:, owner] * freq)


def rope_head(x, pos, theta: float, r: int):
    """Plain RoPE at ``pos`` [T] on the first ``r`` dims of the last axis (their own
    ``r / 2`` frequencies)."""
    freq = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    return jnp.concatenate(
        [_rotate(x[..., :r], pos.astype(jnp.float32)[:, None] * freq), x[..., r:]], -1)


def _index_inputs(h, w, c, pos3, fq):
    """The indexer's q^I [T, Hi, Di], k^I [T, Di] (rotary on their first rope dims, by
    stream 0) and the heads' weights [T, Hi] of one row h [T, D]."""
    T = h.shape[0]
    Hi, Di, r, theta = c["index_heads"], c["index_dim"], c["index_rope_dim"], float(c["rope_theta"])
    q = rope_head(_mm(h, w["indexer_wq"], fq).reshape(T, Hi, Di), pos3[0], theta, r)
    k = _mm(h, w["indexer_wk"], fq)
    k = k - k.mean(-1, keepdims=True)
    k = k * jax.lax.rsqrt((k * k).mean(-1, keepdims=True) + INDEX_NORM_EPS)
    k = (k * w["indexer_k_norm_weight"].astype(jnp.float32)
         + w["indexer_k_norm_bias"].astype(jnp.float32))
    return q, rope_head(k, pos3[0], theta, r), _mm(h, w["indexer_weights_proj"], fq) * (
        Hi ** -0.5 * Di ** -0.5)


def _scores_of(q, wt, k, fq):
    """``I[t, s] = Σ_j wt[t, j] · ReLU(q[t, j] · k[s])`` for a block of queries q [qc, Hi,
    Di] (no mask yet) → [qc, T] float32."""
    s = jnp.einsum("qhd,td->qht", _fq(q, fq), _fq(k, fq), precision=HIGHEST)
    return (jax.nn.relu(s) * wt[..., None]).sum(1)


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


def index_scores(h, w, c, pos3, fq=None, q_chunk: int = 256):
    """The indexer of one row h [T, D] → the whole I [T, T] float32 (no mask yet)."""
    q, k, wt = _index_inputs(h, w, c, pos3, fq)
    return _by_query_blocks(lambda _, qb, wb: _scores_of(qb, wb, k, fq), h.shape[0], q_chunk,
                            q, wt)


def selection(h, w, c, pos3, fq=None, q_chunk: int = 256):
    """The top-``topk`` mask [T, T] of every query of one row, the scores a block of
    queries at a time so that only the mask is ever held whole (682 MB at 26 112 tokens,
    where the float32 scores would be 2.7 GB)."""
    q, k, wt = _index_inputs(h, w, c, pos3, fq)
    return _by_query_blocks(
        lambda start, qb, wb: _select_of(_scores_of(qb, wb, k, fq), start, c["index_topk"]),
        h.shape[0], q_chunk, q, wt)


def attention(h, w, c, pos3, fq=None, q_chunk: int = 256):
    """Grouped-query attention of one row h [T, D] under the selection's mask, one K/V
    head's group of query heads and one block of queries at a time (the [group, q_chunk,
    T] scores are all that is held). → (the layer's output [T, D], the mask [T, T])."""
    T = h.shape[0]
    H, K, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    G, eps, theta, section = H // K, c["rms_norm_eps"], float(c["rope_theta"]), c["mrope_section"]
    q = _mm(h, w["q_proj"], fq).reshape(T, H, hd)
    k = _mm(h, w["k_proj"], fq).reshape(T, K, hd)
    v = _mm(h, w["v_proj"], fq).reshape(T, K, hd)
    if c["qk_norm"]:
        q, k = _rms(q, w["q_norm"], eps), _rms(k, w["k_norm"], eps)
    q, k = mrope(q, pos3, theta, section), mrope(k, pos3, theta, section)
    chosen = selection(h, w, c, pos3, fq, q_chunk)
    qc = min(q_chunk, T)
    pad = -T % qc
    mask = jnp.pad(chosen, ((0, pad), (0, 0))).reshape(-1, qc, T)

    def one(qb, kg, vg, m):                  # qb [qc, G, hd], kg/vg [T, hd], m [qc, T]
        s = jnp.einsum("qgd,td->gqt", _fq(qb, fq), _fq(kg, fq), precision=HIGHEST) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(m[None], s, -1e30), -1)
        p = jnp.where(m[None], p, 0.0)       # a padded query row sees nothing
        return jnp.einsum("gqt,td->qgd", _fq(p, fq), _fq(vg, fq), precision=HIGHEST)

    def per_group(args):
        qg, kg, vg = args                    # qg [T, G, hd]
        qp = jnp.pad(qg, ((0, pad), (0, 0), (0, 0))).reshape(-1, qc, G, hd)
        return jax.lax.map(lambda a: one(a[0], kg, vg, a[1]), (qp, mask)).reshape(
            T + pad, G, hd)[:T]

    o = jax.lax.map(per_group, (jnp.moveaxis(q.reshape(T, K, G, hd), 1, 0),
                                jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))  # [K,T,G,hd]
    return _mm(jnp.moveaxis(o, 0, 1).reshape(T, H * hd), w["o_proj"], fq), chosen


def swiglu(h, w, fq=None):
    return _mm(jax.nn.silu(_mm(h, w["gate_proj"], fq)) * _mm(h, w["up_proj"], fq),
               w["down_proj"], fq)


def route(h, w, c, fq=None):
    """→ gates [T, num_experts] float32: a chosen expert's renormalised softmax
    probability, 0 elsewhere. The largest left, ``num_experts_per_tok`` times (a tie: the
    lower index): no sort."""
    E, k = c["num_experts"], c["num_experts_per_tok"]
    p = jax.nn.softmax(_mm(h, w["gate"], fq), -1)
    chosen = jnp.zeros(p.shape, bool)
    for _ in range(k):
        best = jnp.argmax(jnp.where(chosen, -jnp.inf, p), -1)
        chosen = chosen | (jnp.arange(E)[None, :] == best[:, None])
    g = jnp.where(chosen, p, 0.0)
    return g / (g.sum(-1, keepdims=True) + 1e-20) if c["norm_topk_prob"] else g


def moe(h, w, c, fq=None):
    """``Σ p_e · SwiGLU_e(h)`` over the chosen experts, one expert at a time over the
    tokens that chose it, ``cap`` of them a pass (twice the expected load, never under
    256) and as many passes as its load needs: nothing is dropped however uneven the
    routing (under random weights the hidden states of a row share a direction after
    the first layer, and an expert can draw many times its share)."""
    T = h.shape[0]
    gates = route(h, w, c, fq)
    cap = min(T, max(256, int(2 * T * c["num_experts_per_tok"] / c["num_experts"])))

    def add(acc, eg):
        we, g = eg                                   # one expert's weights, its gates [T]
        mine = jnp.nonzero(g > 0, size=T, fill_value=T)[0]       # its tokens, then filler
        mine = jnp.pad(mine, (0, cap), constant_values=T)

        def a_pass(i, acc):
            rows = jax.lax.dynamic_slice_in_dim(mine, i * cap, cap)
            gate = jnp.where(rows < T, g[jnp.minimum(rows, T - 1)], 0.0)
            out = gate[:, None] * swiglu(h[jnp.minimum(rows, T - 1)], we, fq)
            return acc.at[rows].add(out)             # a filler row lies past the end: dropped

        return jax.lax.fori_loop(0, ((g > 0).sum() + cap - 1) // cap, a_pass, acc), None

    return jax.lax.scan(add, jnp.zeros_like(h), (w["experts"], gates.T))[0]


def attention_part(x, w, c, pos3, fq=None):
    """x + the attention of one decoder layer on one row x [T, D]."""
    return x + attention(_rms(x, w["input_layernorm"], c["rms_norm_eps"]), w, c, pos3, fq)[0]


def mlp_part(x, w, c, fq=None):
    return x + moe(_rms(x, w["post_attention_layernorm"], c["rms_norm_eps"]), w, c, fq)


def block(x, w, c, pos3, fq=None):
    """One decoder layer on one row x [T, D] at the position rows ``pos3`` [3, T]."""
    return mlp_part(attention_part(x, w, c, pos3, fq), w, c, fq)


# ------------------------------------------------------------------------------ serving
def _layer_weights(cfg, key, layer, experts):
    c = dict(cfg)
    return c, gen_layer(c, jax.random.fold_in(key, layer), getattr(jnp, c["dtype"]), experts)


@functools.partial(jax.jit, static_argnames=("cfg", "fq"), donate_argnums=(0,))
def _serve_attention(x, pos3, key, layer, cfg, fq):
    """A layer's attention half on ONE row x [T, D] (donated; ``layer`` is traced: every
    layer shares one compiled program; the halves are programs of their own so that what
    a half does not read of the layer's weights — here the 604 M expert parameters — is
    never made)."""
    c, w = _layer_weights(cfg, key, layer, False)
    return attention_part(x, w, c, pos3, fq)


@functools.partial(jax.jit, static_argnames=("cfg", "fq"), donate_argnums=(0,))
def _serve_mlp(x, key, layer, cfg, fq):
    c, w = _layer_weights(cfg, key, layer, True)
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


def forward_row(c: dict, seed: int, ids, pos3=None, fq=None):
    """The final hidden states [T, D] of one row of token ids at the position rows
    ``pos3`` [3, T] (text by default: three times 0..T-1), layer by layer (a layer's
    weights are made from the seed when it is due, rounded to ``serve.dtype`` as the
    program's are)."""
    cfg, key = freeze(c), seed_key(seed)
    T = len(ids)
    pos3 = jnp.asarray(np.broadcast_to(np.arange(T), (3, T)) if pos3 is None else pos3)
    x = _serve_embed(jnp.asarray(ids), key, cfg)
    for l in range(c["num_hidden_layers"]):          # one compiled program for all layers
        x = _serve_attention(x, pos3, key, l, cfg, fq)
        x = _serve_mlp(x, key, l, cfg, fq)
    return x


def serve_reference(c: dict, seed: int, rows, width: int, n_out: int, fq=None, pos3=None):
    """One full forward over each row's prompt + served tokens, row by row (``forward_row``).
    ``rows`` = [(prompt ids, served ids)]; → logits [n, n_out, V] at the positions that
    produced each served token."""
    cfg, key = freeze(c), seed_key(seed)
    filler = np.random.default_rng([seed & 0x7FFFFFFF, 5])
    out = []
    for prompt, served in rows:
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        # Behind the sequence: seeded random ids (no position before them sees them).
        ids = filler.integers(0, c["vocab_size"], size=(width,)).astype(np.int32)
        ids[:len(seq)] = seq
        at = np.zeros((n_out,), np.int32)
        at[:len(served)] = len(prompt) - 1 + np.arange(len(served))
        x = forward_row(c, seed, ids, pos3, fq)
        out.append(_serve_logits(x, jnp.asarray(at), key, cfg, fq))
    return np.asarray(jnp.stack(out))


def compare_serve(rows, ref_logits, picked=None) -> dict:
    """The gap by which a served token's reference logit lies below the reference's best,
    over every served token of ``rows``: its maximum (``served_logit_gap``, the measure of
    ``reference.compare_serve``), its 90th percentile and its mean. ``picked`` [n, n_out]
    (the control) reads the tokens a lower precision puts first in place of the served
    ones. All three go to stderr for both calls; ``limits`` names the ones compared
    (PERF.md §2 has the readings: a key that bfloat16 noise swaps at the selection's cut,
    or an expert it swaps at the router's, moves a position's logits whatever the kernels
    do, and such positions make the maximum)."""
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
def _sizes(c: dict) -> dict:
    return dict(freeze(c))


def attention_params(c: dict) -> int:
    """One layer's attention and indexer matrices (18 874 368 + 2 260 992 at the
    published widths)."""
    return sum(a * b for a, b in _attn_shapes(_sizes(c)).values())


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def matmul_params(c: dict) -> int:
    """Parameters EVERY token meets in a matrix product: each layer's attention, indexer
    and router, and the head. The embedding is a lookup; the routed experts are counted
    apart (``serve_flops_per_token``)."""
    per_layer = attention_params(c) + c["hidden_size"] * c["num_experts"]
    return c["num_hidden_layers"] * per_layer + c["hidden_size"] * c["vocab_size"]


def mean_keys(c: dict) -> tuple:
    """(live keys, attended keys) a processed token meets a layer, in the mean over the
    positions 0 .. P + O − 1 of a request of the mix's mean lengths (``counts`` in the
    file: P prompt and O output tokens): a token at position ``p`` scores ``p + 1`` index
    keys and attends ``min(p + 1, topk)``."""
    n = c["counts"]["mean_prompt_tokens"] + c["counts"]["mean_output_tokens"]
    k = min(c["sa_config"]["topk"], n)
    return (n + 1) / 2, (k * (k + 1) / 2 + (n - k) * k) / n


def serve_flops_per_token(c: dict) -> float:
    """FLOPs a processed token (prompt or output) needs: 2 × (``matmul_params`` + the
    ``num_experts_per_tok`` routed experts it meets in each layer) + WITH the products over
    the cache, at :func:`mean_keys`: the indexer's 2 · heads · dim a live key and the
    attention's 4 · H · head_dim (QKᵀ and PV) an attended key, a layer."""
    z = _sizes(c)
    live, attended = mean_keys(c)
    per_layer = (2 * z["index_heads"] * z["index_dim"] * live
                 + 4 * z["num_attention_heads"] * z["head_dim"] * attended)
    routed = c["num_hidden_layers"] * c["num_experts_per_tok"] * expert_params(c)
    return 2.0 * (matmul_params(c) + routed) + c["num_hidden_layers"] * per_layer


def _live_keys(c: dict, lens) -> list:
    """A lane's live keys from its position: ``lens`` counts the prompt's left pad, which
    the harness does not hand over, so the most the engine's chunk layout can pad
    (``prompt_bucket`` − 1) is taken off every lane — a share errs low by that, never high."""
    return [max(1, int(n) - (c["serve"]["prompt_bucket"] - 1)) for n in lens]


def paged_attn_work(c: dict, lens, page_size: int) -> tuple:
    """(FLOPs, bytes) ONE decode step's calls of ``paged_attention`` need over all layers
    for lanes at positions ``lens`` — the rows the kernel is HANDED: ``min(topk, live
    keys)`` chosen K and V rows a lane and layer. Per row 4 · H · head_dim FLOPs against
    2 · K · head_dim bfloat16 values read, plus q and o a lane."""
    z = _sizes(c)
    H, K, hd, L = (z["num_attention_heads"], z["num_key_value_heads"], z["head_dim"],
                   z["num_hidden_layers"])
    rows = sum(min(z["index_topk"], n) for n in _live_keys(c, lens))
    return (L * 4 * H * hd * rows,
            L * (rows * 2 * K * hd * 2 + len(lens) * 2 * H * hd * 2))


def dsa_index_work(c: dict, lens, page_size: int) -> tuple:
    """(FLOPs, bytes) ONE decode step's calls of ``dsa_index_scores`` need over all layers
    for lanes at positions ``lens``: per live key 2 · heads · dim FLOPs (the one product;
    the ReLU and the weighted sum are not counted, nor the zeros of the kernel's
    block-diagonal query) against ``dim`` bfloat16 values read (128 B at 64 values) and
    one float32 score written, plus q (bfloat16) and w (float32) a lane."""
    z = _sizes(c)
    Hi, Di, L = z["index_heads"], z["index_dim"], z["num_hidden_layers"]
    keys = sum(_live_keys(c, lens))
    return (L * 2 * Hi * Di * keys,
            L * (keys * (Di * 2 + 4) + len(lens) * (Hi * Di * 2 + Hi * 4)))
