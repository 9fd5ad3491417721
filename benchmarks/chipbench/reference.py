"""The plain reference: Mistral-7B's equations in float32 ``jax.numpy``.

Imports nothing of the program and takes nothing the program made. It makes the
weights from the seed (``gen_params`` — the benchmark hands the same tree to the
program), follows the first optimizer steps of a training cell (``train_reference``) or
the logits of served tokens (``serve_reference``), and holds the comparison that decides
``correct`` (``compare_train`` / ``compare_serve``). Matrix products run at ``highest``
precision; ``fq="fp8"`` rounds every product's operands to float8_e4m3 (per-tensor
scaled) — the precision below bfloat16, used only by the control (``--control 1``, tests).

Published equations (mistralai/Mistral-7B-v0.1, modeling_mistral.py): pre-norm decoder,
RMSNorm, rotary embedding on half-split head dims, grouped-query attention, causal mask
banded to ``sliding_window`` keys, SwiGLU, untied output head. No departures.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LAYER_LEAVES = ("ln_attn", "wq", "wk", "wv", "wo", "ln_mlp", "w_gate", "w_up", "w_down")


# --------------------------------------------------------------------------- weights
def seed_key(seed: int) -> jax.Array:
    """A key from any whole number (the driver's seeds pass 2**31)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _shapes(c: dict) -> dict:
    D, F = c["hidden_size"], c["intermediate_size"]
    H, K, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    return {"wq": (D, H * hd), "wk": (D, K * hd), "wv": (D, K * hd), "wo": (H * hd, D),
            "w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)}


def _bell(key, shape, std: float, dtype):
    """Zero mean, standard deviation ``std``, bell-shaped: the sum of the four bytes of
    each random word, centred and scaled. Integer sums and ONE float product, so the
    same key gives the same bits whatever program the call is compiled into (a normal
    drawn through erf⁻¹ differed in the last place between two programs)."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    total = sum(((bits >> (8 * i)) & 0xFF).astype(jnp.int32) for i in range(4)) - 510
    return (total.astype(jnp.float32) * (std / math.sqrt(21845.0))).astype(dtype)


def gen_layer(c: dict, key, dtype) -> dict:
    """One decoder layer's weights: variance 1/fan_in, norm gains 1."""
    out = {"ln_attn": jnp.ones((c["hidden_size"],), dtype),
           "ln_mlp": jnp.ones((c["hidden_size"],), dtype)}
    for i, (name, shape) in enumerate(_shapes(c).items()):
        out[name] = _bell(jax.random.fold_in(key, i), shape, 1.0 / math.sqrt(shape[0]), dtype)
    return out


def gen_ends(c: dict, k, dtype) -> dict:
    D, V = c["hidden_size"], c["vocab_size"]
    s = 1.0 / math.sqrt(D)
    return {"embed": _bell(jax.random.fold_in(k, 1 << 20), (V, D), s, dtype),
            "lm_head": _bell(jax.random.fold_in(k, (1 << 20) + 1), (D, V), s, dtype),
            "ln_f": jnp.ones((D,), dtype)}


@functools.partial(jax.jit, static_argnames=("cfg", "dtype"))
def _gen_params(key, cfg, dtype):
    c = dict(cfg)
    layers = jax.lax.map(lambda l: gen_layer(c, jax.random.fold_in(key, l), dtype),
                         jnp.arange(c["num_hidden_layers"]))
    return {**gen_ends(c, key, dtype), "layers": layers}


def freeze(c: dict) -> tuple:
    """The sizes the equations need, hashable (a jit static argument)."""
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "num_hidden_layers", "vocab_size",
            "sliding_window", "rope_theta", "rms_norm_eps")
    return tuple((k, c[k]) for k in keys)


def gen_params(c: dict, seed: int, dtype):
    """The whole tree in ONE jitted call on the device, layers stacked [L, ...]:
    ``{"embed", "lm_head", "ln_f", "layers": {leaf: [L, ...]}}``."""
    return _gen_params(seed_key(seed), freeze(c), dtype)


# ------------------------------------------------------------------------- equations
def _fq(x, fq):
    """Round to float8_e4m3 with one scale per tensor; gradients pass straight through."""
    if fq is None:
        return x
    s = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, b, fq):
    return jnp.matmul(_fq(a, fq), _fq(b.astype(jnp.float32), fq), precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g.astype(jnp.float32)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, c, fq, q_chunk=2048):
    """q [T,H,hd], k/v [T,K,hd] → [T,H,hd]; one KV group and one block of queries at a
    time (under remat), so the [G, q_chunk, T] scores are all that is ever held."""
    T, H, hd = q.shape
    K = k.shape[1]
    G, W = H // K, c["sliding_window"]
    qc = min(q_chunk, T)
    pad = -T % qc
    qg = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape((T + pad) // qc, qc, K, G, hd)
    kpos = jnp.arange(T)

    @jax.checkpoint
    def one(qb, kg, vg, start):              # qb [qc,G,hd], kg/vg [T,hd]
        qpos = start + jnp.arange(qc)
        s = jnp.einsum("qgd,td->gqt", _fq(qb, fq), _fq(kg, fq), precision=HIGHEST)
        s = s / math.sqrt(hd)
        ok = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] > qpos[:, None] - W)
        p = jax.nn.softmax(jnp.where(ok[None], s, -1e30), -1)
        return jnp.einsum("gqt,td->qgd", _fq(p, fq), _fq(vg, fq), precision=HIGHEST)

    def per_group(args):
        qk, kg, vg = args                    # qk [n,qc,G,hd]
        starts = jnp.arange(qk.shape[0]) * qc
        return jax.lax.map(lambda a: one(a[0], kg, vg, a[1]), (qk, starts))

    out = jax.lax.map(per_group, (qg.transpose(2, 0, 1, 3, 4), k.transpose(1, 0, 2),
                                  v.transpose(1, 0, 2)))      # [K,n,qc,G,hd]
    return out.transpose(1, 2, 0, 3, 4).reshape(T + pad, H, hd)[:T]


def _mlp(h, w, fq, chunk=4096):
    """SwiGLU over blocks of tokens, so the d_ff-wide tensors stay small."""
    T, D = h.shape
    cs = min(chunk, T)
    pad = -T % cs

    @jax.checkpoint
    def one(hb):
        return _mm(jax.nn.silu(_mm(hb, w["w_gate"], fq)) * _mm(hb, w["w_up"], fq),
                   w["w_down"], fq)

    return jax.lax.map(one, jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, cs, D)
                       ).reshape(T + pad, D)[:T]


def block(x, w, c, fq=None):
    """One decoder layer on one row x [T, D] at positions 0..T-1."""
    T = x.shape[0]
    H, K, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    pos = jnp.arange(T)
    h = _rms(x, w["ln_attn"], c["rms_norm_eps"])
    q = _rope(_mm(h, w["wq"], fq).reshape(T, H, hd), pos, c["rope_theta"])
    k = _rope(_mm(h, w["wk"], fq).reshape(T, K, hd), pos, c["rope_theta"])
    v = _mm(h, w["wv"], fq).reshape(T, K, hd)
    x = x + _mm(_attention(q, k, v, c, fq).reshape(T, H * hd), w["wo"], fq)
    return x + _mlp(_rms(x, w["ln_mlp"], c["rms_norm_eps"]), w, fq)


# ------------------------------------------------------------------------- training
def _row_loss(params, row, c, fq):
    """Summed next-token cross-entropy of one row [S+1]."""
    x = params["embed"][row[:-1]].astype(jnp.float32)
    blk = jax.checkpoint(functools.partial(block, c=c, fq=fq))
    for l in range(c["num_hidden_layers"]):
        x = blk(x, jax.tree_util.tree_map(lambda a: a[l], params["layers"]))
    x = _rms(x, params["ln_f"], c["rms_norm_eps"])
    S = x.shape[0]
    cs = min(1024, S)
    pad = -S % cs

    @jax.checkpoint
    def ce(args):
        xb, tb, mb = args
        logits = _mm(xb, params["lm_head"], fq)
        return ((jax.nn.logsumexp(logits, -1)
                 - jnp.take_along_axis(logits, tb[:, None], -1)[:, 0]) * mb).sum()

    parts = jax.lax.map(ce, (jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, cs, x.shape[1]),
                             jnp.pad(row[1:], (0, pad)).reshape(-1, cs),
                             jnp.pad(jnp.ones((S,)), (0, pad)).reshape(-1, cs)))
    return parts.sum()


@functools.partial(jax.jit, static_argnames=("cfg", "fq"), donate_argnums=(1,))
def _accumulate(params, acc, row, cfg, fq):
    loss, g = jax.value_and_grad(_row_loss)(params, row, dict(cfg), fq)
    return loss, jax.tree_util.tree_map(jnp.add, acc, g)


@functools.partial(jax.jit, static_argnames=("cfg", "fq"))
def _loss_only(params, row, cfg, fq):
    return _row_loss(params, row, dict(cfg), fq)


@jax.jit
def leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree)


@functools.partial(jax.jit, static_argnames=("cfg",))
def change_norms(params, key, cfg):
    """‖p − p0‖ per leaf; p0 is made again from the seed's key inside the jitted call."""
    p0 = _gen_params(key, cfg, jnp.float32)
    return jax.tree_util.tree_map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b))), params, p0)


def _flat(tree) -> dict:
    return {"/".join(str(getattr(k, "key", k)) for k in path): float(v)
            for path, v in jax.tree_util.tree_leaves_with_path(tree)}


def train_reference(c: dict, hp: dict, batches, seed: int, fq=None, rows=None) -> dict:
    """Follow the first steps on ``batches`` (three [B, S+1] id arrays): full AdamW steps
    1 and 2 (clip by global norm, decoupled weight decay — optax.adamw's equations) and
    the forward of step 3. → losses [3], the first clipped gradient's norm per leaf, and
    ‖p2 − p0‖ per leaf. ``rows`` (a fault for the readings) keeps only those rows."""
    cfg = freeze(c)
    b1, b2, eps, lr, wd = hp["b1"], hp["b2"], hp["eps"], hp["lr"], hp["weight_decay"]
    params = gen_params(c, seed, jnp.float32)
    losses = []

    def kept(batch):                 # numpy ids, as traffic.train_batches makes them
        return batch if rows is None else batch[list(rows)]

    def grads_of(batch):
        batch = kept(batch)
        acc = jax.tree_util.tree_map(jnp.zeros_like, params)
        total = 0.0
        for row in batch:
            loss, acc = _accumulate(params, acc, jnp.asarray(row), cfg, fq)
            total += float(loss)
        n = batch.shape[0] * (batch.shape[1] - 1)
        losses.append(total / n)
        return _clip(acc, 1.0 / n, hp["max_grad_norm"])

    g1 = grads_of(batches[0])
    grad_norms = _flat(leaf_norms(g1))
    params = _adam1(params, g1, lr, eps, wd)
    g1 = jax.device_get(g1)          # to the host while step 2's gradients need the room
    g2 = grads_of(batches[1])
    params = _adam2(params, jax.device_put(g1), g2, b1, b2, lr, eps, wd)
    del g1, g2
    change = _flat(change_norms(params, seed_key(seed), cfg))
    last = kept(batches[2])
    total = sum(float(_loss_only(params, jnp.asarray(r), cfg, fq)) for r in last)
    losses.append(total / (last.shape[0] * (last.shape[1] - 1)))
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}


@functools.partial(jax.jit, donate_argnums=(0,))
def _clip(acc, scale, max_norm):
    g = jax.tree_util.tree_map(lambda a: a * scale, acc)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(a)) for a in jax.tree_util.tree_leaves(g)))
    k = jnp.minimum(1.0, max_norm / (norm + 1e-6))
    return jax.tree_util.tree_map(lambda a: a * k, g)


@functools.partial(jax.jit, donate_argnums=(0,))
def _adam1(p, g1, lr, eps, wd):
    """Step 1: the bias-corrected moments are g and g² themselves."""
    return jax.tree_util.tree_map(
        lambda p, g: p - lr * (g / (jnp.abs(g) + eps) + wd * p), p, g1)


@functools.partial(jax.jit, donate_argnums=(0,))
def _adam2(p, g1, g2, b1, b2, lr, eps, wd):
    def upd(p, g1, g2):
        m = (b1 * (1 - b1) * g1 + (1 - b1) * g2) / (1 - b1 ** 2)
        v = (b2 * (1 - b2) * g1 * g1 + (1 - b2) * g2 * g2) / (1 - b2 ** 2)
        return p - lr * (m / (jnp.sqrt(v) + eps) + wd * p)
    return jax.tree_util.tree_map(upd, p, g1, g2)


def compare_train(got: dict, ref: dict) -> dict:
    """The numbers compared, by name. A loss gap is relative; a norm gap is the WORST
    leaf's |‖got‖ − ‖ref‖| over the larger of that leaf's and the median leaf's ‖ref‖.
    Leaves whose reference gradient is under a thousandth of the median leaf's are left
    out of the change (they move by round-off alone under Adam)."""
    out = {f"loss{i + 1}_gap": abs(g - r) / abs(r)
           for i, (g, r) in enumerate(zip(got["losses"], ref["losses"]))}

    def worst(a, b, leaves):
        med = float(np.median([b[k] for k in leaves]))
        return max(abs(a[k] - b[k]) / max(b[k], med) for k in leaves)

    leaves = list(ref["grad_norms"])
    gmed = float(np.median(list(ref["grad_norms"].values())))
    out["grad_norm_gap"] = worst(got["grad_norms"], ref["grad_norms"], leaves)
    moved = [k for k in leaves if ref["grad_norms"][k] >= 1e-3 * gmed]
    out["change_norm_gap"] = worst(got["change_norms"], ref["change_norms"], moved)
    return out


# -------------------------------------------------------------------------- serving
@functools.partial(jax.jit, static_argnames=("cfg", "fq"))
def _serve_layer(x, key, layer, cfg, fq):
    c = dict(cfg)
    w = gen_layer(c, jax.random.fold_in(key, layer), jnp.bfloat16)
    return jax.lax.map(lambda r: block(r, w, c, fq), x)


@functools.partial(jax.jit, static_argnames=("cfg", "fq"))
def _serve_logits(x, at, key, cfg, fq):
    c = dict(cfg)
    ends = gen_ends(c, key, jnp.bfloat16)
    h = jnp.take_along_axis(x, at[:, :, None], 1)
    return _mm(_rms(h, ends["ln_f"], c["rms_norm_eps"]), ends["lm_head"], fq)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _serve_embed(ids, key, cfg):
    return gen_ends(dict(cfg), key, jnp.bfloat16)["embed"][ids].astype(jnp.float32)


def serve_reference(c: dict, seed: int, rows, width: int, n_out: int, fq=None):
    """One full forward over each row's prompt + served tokens, layer by layer (a layer's
    weights are made from the seed when it is due, so the model need not fit whole).
    ``rows`` = [(prompt ids, served ids)]; → logits [n, n_out, V] at the positions that
    produced each served token (row i's entries past its own token count are padding)."""
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
        x = _serve_layer(x, key, l, cfg, fq)
    return np.asarray(_serve_logits(x, jnp.asarray(at), key, cfg, fq))


def compare_serve(rows, ref_logits, picked=None) -> dict:
    """Widest gap by which a served token's reference logit lies below the reference's
    best, over every served token of ``rows``. ``picked`` [n, n_out] (the control) reads
    the tokens a lower precision puts first in place of the served ones."""
    widest = 0.0
    for i, (_, served) in enumerate(rows):
        n = len(served)
        tok = served if picked is None else picked[i][:n]
        lg = ref_logits[i, :n]
        widest = max(widest, float((lg.max(-1) - lg[np.arange(n), tok]).max()))
    return {"served_logit_gap": widest}
