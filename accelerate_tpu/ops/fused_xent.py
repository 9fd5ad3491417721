"""Fused cross-entropy — logits never touch HBM (Pallas, custom VJP).

The reference computes ``lm_head`` logits then ``torch.nn.CrossEntropyLoss`` — at
V=32k, S=2048, B=4 that is a ~1 GB fp32 tensor materialized twice per step (forward
and backward). ``models/common.chunked_ce`` already bounds this by chunking over the
sequence and forms each chunk's gradients from the logits it holds (one scan, no
recompute), but each [B, chunk, V] block still round-trips HBM. This kernel goes the rest
of the way (the CCE / Liger-kernel idea, TPU-style): the score tile ``x_tile @ w_tile``
lives only in VMEM, reduced on the fly into an online logsumexp (exactly the
FlashAttention recurrence with the kv axis replaced by the vocab axis), and the
backward recomputes score tiles while accumulating ``dx``/``dw`` in VMEM scratch —
HBM traffic is just the inputs, outputs, and one fp32 [T] logsumexp residual.

API: ``fused_cross_entropy(x, w, targets)`` → per-token nll ``[T]`` (fp32). Mask and
mean OUTSIDE — autodiff threads the cotangent ``g = mask/denom`` into the kernels.
Optional ``softcap`` matches Gemma-2's final-logit capping (exact 1−tanh² backward).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import interpret_default as _interpret_default
from ..utils.jax_compat import axis_size as _axis_size

__all__ = ["fused_cross_entropy", "fused_cross_entropy_tp"]

_NEG_INF = -1e30


def _raw_scores(x_ref, w_ref):
    return jax.lax.dot_general(
        x_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _scores(x_ref, w_ref, softcap):
    s = _raw_scores(x_ref, w_ref)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    return s


def _col_mask(j, block_v, vocab, bt):
    cols = j * block_v + jax.lax.broadcasted_iota(jnp.int32, (bt, block_v), 1)
    return cols, cols < vocab


def _online_tile(j, t_ref, x_ref, w_ref, m_ref, l_ref, tgt_ref, *, block_v, vocab, softcap):
    """Shared forward tile: fold one [bt, bv] score tile into the online (m, l, tgt)."""

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        tgt_ref[:] = jnp.zeros_like(tgt_ref)

    s = _scores(x_ref, w_ref, softcap)                    # [bt, bv] fp32
    bt = s.shape[0]
    cols, valid = _col_mask(j, block_v, vocab, bt)
    s = jnp.where(valid, s, _NEG_INF)

    m_prev = m_ref[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    l_ref[:] = l_ref[:] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.where(valid, jnp.exp(s - m_new), 0.0), axis=1, keepdims=True
    )
    m_ref[:] = m_new
    # The target column lands in exactly one vocab tile; accumulate its (capped) score.
    # `valid` matters for the tp variant: a target id outside this shard's vocab slice
    # must not match a padded column (whose masked score is -inf).
    match = jnp.logical_and(cols == t_ref[:], valid)      # t_ref [bt, 1] broadcasts
    tgt_ref[:] = tgt_ref[:] + jnp.sum(jnp.where(match, s, 0.0), axis=1, keepdims=True)


def _fwd_kernel(t_ref, x_ref, w_ref, nll_ref, lse_ref, m_ref, l_ref, tgt_ref,
                *, block_v, vocab, softcap):
    j = pl.program_id(1)
    nv = pl.num_programs(1)
    _online_tile(j, t_ref, x_ref, w_ref, m_ref, l_ref, tgt_ref,
                 block_v=block_v, vocab=vocab, softcap=softcap)

    @pl.when(j == nv - 1)
    def _finalize():
        lse = m_ref[:] + jnp.log(l_ref[:])
        lse_ref[:] = lse
        nll_ref[:] = lse - tgt_ref[:]


def _fwd_partial_kernel(t_ref, x_ref, w_ref, m_out, l_out, tgt_out, m_ref, l_ref, tgt_ref,
                        *, block_v, vocab, softcap):
    """Partial-statistics variant for vocab-sharded heads: emits the raw online
    (max, sumexp-at-max, target-score) so the caller can merge across shards."""
    j = pl.program_id(1)
    nv = pl.num_programs(1)
    _online_tile(j, t_ref, x_ref, w_ref, m_ref, l_ref, tgt_ref,
                 block_v=block_v, vocab=vocab, softcap=softcap)

    @pl.when(j == nv - 1)
    def _finalize():
        m_out[:] = m_ref[:]
        l_out[:] = l_ref[:]
        tgt_out[:] = tgt_ref[:]


def _bwd_common(s_raw, lse, g, cols, t_ref, vocab, softcap):
    """dlogits for one tile: ``(softmax − onehot) · g``, with the softcap chain rule."""
    if softcap:
        capped = softcap * jnp.tanh(s_raw / softcap)
        chain = 1.0 - (capped / softcap) ** 2             # d(cap·tanh(s/cap))/ds
    else:
        capped, chain = s_raw, None
    valid = cols < vocab
    p = jnp.where(valid, jnp.exp(capped - lse), 0.0)
    onehot = jnp.logical_and(cols == t_ref[:], valid).astype(jnp.float32)
    d = (p - onehot) * g
    if chain is not None:
        d = d * chain
    return d


def _bwd_dx_kernel(t_ref, x_ref, w_ref, lse_ref, g_ref, dx_ref, acc_ref,
                   *, block_v, vocab, softcap):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    s = _raw_scores(x_ref, w_ref)
    bt = s.shape[0]
    cols, _ = _col_mask(j, block_v, vocab, bt)
    d = _bwd_common(s, lse_ref[:], g_ref[:], cols, t_ref, vocab, softcap)
    acc_ref[:] = acc_ref[:] + jax.lax.dot_general(
        d.astype(w_ref.dtype), w_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(j == nv - 1)
    def _finalize():
        dx_ref[:] = acc_ref[:].astype(dx_ref.dtype)


def _bwd_dw_kernel(t_ref, x_ref, w_ref, lse_ref, g_ref, dw_ref, acc_ref,
                   *, block_v, vocab, softcap):
    # grid (nv, nt): token tiles iterate INNER so dw accumulates in VMEM scratch.
    j = pl.program_id(0)
    i = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    s = _raw_scores(x_ref, w_ref)
    bt = s.shape[0]
    cols, _ = _col_mask(j, block_v, vocab, bt)
    d = _bwd_common(s, lse_ref[:], g_ref[:], cols, t_ref, vocab, softcap)
    acc_ref[:] = acc_ref[:] + jax.lax.dot_general(
        x_ref[:], d.astype(x_ref.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(i == nt - 1)
    def _finalize():
        dw_ref[:] = acc_ref[:].astype(dw_ref.dtype)


def fused_cross_entropy(
    x: jax.Array,
    w: jax.Array,
    targets: jax.Array,
    softcap: float = 0.0,
    block_t: int = 256,
    block_v: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Per-token ``-log p(target)`` for ``logits = x @ w`` without materializing logits.

    x [T, D] (any float dtype; dots run in it), w [D, V], targets [T] int32 → nll [T]
    fp32. Pad/ignored positions: mask the RESULT (a −1 target never matches any column,
    its nll is just lse — finite, safe to mask).
    """
    if interpret is None:
        interpret = _interpret_default()
    T, D = x.shape
    V = w.shape[1]
    Tp = pl.cdiv(T, block_t) * block_t
    Vp = pl.cdiv(V, block_v) * block_v
    # Padding happens OUTSIDE the custom_vjp: jnp.pad is differentiable, so autodiff
    # slices the padded cotangents back down and the kernels only see exact grids.
    if Tp != T:
        x = jnp.pad(x, ((0, Tp - T), (0, 0)))
        targets = jnp.pad(jnp.asarray(targets, jnp.int32), (0, Tp - T),
                          constant_values=-1)
    if Vp != V:
        w = jnp.pad(w, ((0, 0), (0, Vp - V)))
    t2 = jnp.asarray(targets, jnp.int32).reshape(Tp, 1)
    nll = _fce(x, w, t2, V, softcap, block_t, block_v, interpret)
    return nll[:T]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fce(x, w, t2, vocab, softcap, block_t, block_v, interpret):
    nll, _ = _fce_fwd(x, w, t2, vocab, softcap, block_t, block_v, interpret)
    return nll


def _compiler_params(x, w, block_t, block_v, resident_bytes=0):
    """Grid semantics + the scoped-VMEM limit this launch needs. The pipeline holds two
    copies of every streamed block ([block_t, D] of x, [D, block_v] of w), the kernel
    body a few fp32 [block_t, block_v] score temporaries, and the backward kernels a
    resident accumulator + output block (``resident_bytes``). At d_model 4096 that is
    past the 16 MiB default of a v5e, so say so instead of shrinking the tiles."""
    D = x.shape[1]
    streamed = 2 * D * (block_t * x.dtype.itemsize + block_v * w.dtype.itemsize)
    need = streamed + resident_bytes + 6 * block_t * block_v * 4
    return pltpu.CompilerParams(
        dimension_semantics=(pltpu.PARALLEL, pltpu.ARBITRARY),
        vmem_limit_bytes=int(need * 1.25) + (4 << 20),
    )


def _launch_fwd(kernel_fn, n_outputs, x, w, t2, *, vocab, softcap, block_t, block_v,
                interpret):
    """Shared forward launch (same grid/specs/scratch for both fwd kernel variants —
    they differ only in the kernel fn and how many [Tp, 1] statistics they emit)."""
    Tp, D = x.shape
    Vp = w.shape[1]
    nt, nv = Tp // block_t, Vp // block_v
    stat_spec = pl.BlockSpec((block_t, 1), lambda i, j: (i, 0))
    return pl.pallas_call(
        functools.partial(kernel_fn, block_v=block_v, vocab=vocab, softcap=softcap),
        name="fused_xent_fwd",
        grid=(nt, nv),
        in_specs=[
            stat_spec,
            pl.BlockSpec((block_t, D), lambda i, j: (i, 0)),
            pl.BlockSpec((D, block_v), lambda i, j: (0, j)),
        ],
        out_specs=[stat_spec] * n_outputs,
        out_shape=[jax.ShapeDtypeStruct((Tp, 1), jnp.float32)] * n_outputs,
        scratch_shapes=[pltpu.VMEM((block_t, 1), jnp.float32)] * 3,
        compiler_params=_compiler_params(x, w, block_t, block_v),
        interpret=interpret,
    )(t2, x, w)


def _fce_fwd(x, w, t2, vocab, softcap, block_t, block_v, interpret):
    nll, lse = _launch_fwd(
        _fwd_kernel, 2, x, w, t2, vocab=vocab, softcap=softcap,
        block_t=block_t, block_v=block_v, interpret=interpret,
    )
    return nll[:, 0], (x, w, t2, lse)


def _fce_bwd(vocab, softcap, block_t, block_v, interpret, res, g):
    x, w, t2, lse = res                # padded shapes throughout
    Tp, D = x.shape
    Vp = w.shape[1]
    nt, nv = Tp // block_t, Vp // block_v
    g2 = jnp.asarray(g, jnp.float32).reshape(Tp, 1)

    common = dict(block_v=block_v, vocab=vocab, softcap=softcap)
    dx = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, **common),
        name="fused_xent_bwd_dx",
        grid=(nt, nv),
        in_specs=[
            pl.BlockSpec((block_t, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_t, D), lambda i, j: (i, 0)),
            pl.BlockSpec((D, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((block_t, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_t, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, D), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Tp, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_t, D), jnp.float32)],
        compiler_params=_compiler_params(
            x, w, block_t, block_v,
            resident_bytes=block_t * D * (4 + 2 * x.dtype.itemsize),
        ),
        interpret=interpret,
    )(t2, x, w, lse, g2)

    dw = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, **common),
        name="fused_xent_bwd_dw",
        grid=(nv, nt),
        in_specs=[
            pl.BlockSpec((block_t, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_t, D), lambda j, i: (i, 0)),
            pl.BlockSpec((D, block_v), lambda j, i: (0, j)),
            pl.BlockSpec((block_t, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_t, 1), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((D, block_v), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((D, Vp), w.dtype),
        scratch_shapes=[pltpu.VMEM((D, block_v), jnp.float32)],
        compiler_params=_compiler_params(
            x, w, block_t, block_v,
            resident_bytes=D * block_v * (4 + 2 * w.dtype.itemsize),
        ),
        interpret=interpret,
    )(t2, x, w, lse, g2)

    return dx, dw, None


_fce.defvjp(_fce_fwd, _fce_bwd)


# ------------------------------------------------------------ vocab-sharded (tp) variant
def fused_cross_entropy_tp(
    x: jax.Array,
    w_shard: jax.Array,
    targets: jax.Array,
    axis_name,
    softcap: float = 0.0,
    block_t: int = 256,
    block_v: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused CE for a TENSOR-PARALLEL (vocab-sharded) head — call INSIDE shard_map,
    which MUST be built with ``check_vma=False`` (pallas outputs carry no vma info, and
    the backward compensates for that mode's split-cotangent adjoint convention — under
    ``check_vma=True`` gradients would come back scaled by the axis size).

    Each shard holds ``w_shard`` [D, V/ntp] (vocab-major order along ``axis_name``) and
    the full ``targets`` (global ids). Shards compute local online statistics with the
    kernel, then merge across ``axis_name``: ``lse = pmax/psum`` logsumexp merge, target
    score via psum (exactly one shard owns each target id). The backward runs the local
    dx/dw kernels against the GLOBAL lse — dw stays shard-local, dx partials are summed
    by shard_map's transpose (x enters replicated over ``axis_name``).
    """
    if interpret is None:
        interpret = _interpret_default()
    T, D = x.shape
    Vl = w_shard.shape[1]
    idx = jax.lax.axis_index(axis_name)
    t_local = jnp.asarray(targets, jnp.int32) - idx * Vl  # non-owners go out of range
    Tp = pl.cdiv(T, block_t) * block_t
    Vp = pl.cdiv(Vl, block_v) * block_v
    if Tp != T:
        x = jnp.pad(x, ((0, Tp - T), (0, 0)))
        t_local = jnp.pad(t_local, (0, Tp - T), constant_values=-1)
    if Vp != Vl:
        w_shard = jnp.pad(w_shard, ((0, 0), (0, Vp - Vl)))
    t2 = t_local.reshape(Tp, 1)
    nll = _fce_tp(x, w_shard, t2, Vl, softcap, block_t, block_v, interpret, axis_name)
    return nll[:T]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _fce_tp(x, w, t2, vocab, softcap, block_t, block_v, interpret, axis_name):
    nll, _ = _fce_tp_fwd(x, w, t2, vocab, softcap, block_t, block_v, interpret, axis_name)
    return nll


def _fce_tp_fwd(x, w, t2, vocab, softcap, block_t, block_v, interpret, axis_name):
    m, l, tgt = _launch_fwd(
        _fwd_partial_kernel, 3, x, w, t2, vocab=vocab, softcap=softcap,
        block_t=block_t, block_v=block_v, interpret=interpret,
    )

    # Cross-shard logsumexp merge (the ring-attention recurrence over the tp axis).
    m_g = jax.lax.pmax(m, axis_name)
    l_g = jax.lax.psum(l * jnp.exp(m - m_g), axis_name)
    lse = m_g + jnp.log(l_g)
    tgt_g = jax.lax.psum(tgt, axis_name)  # exactly one shard owns each target id
    nll = (lse - tgt_g)[:, 0]
    return nll, (x, w, t2, lse)


def _fce_tp_bwd(vocab, softcap, block_t, block_v, interpret, axis_name, res, g):
    # The local backward is IDENTICAL to the single-shard one once lse is global:
    # each shard differentiates only its vocab slice; shard_map's transpose psums the
    # x-cotangents (x is replicated over axis_name), dw stays local.
    #
    # check_vma=False adjoint convention: a replicated (out_specs P()) output's
    # cotangent arrives SPLIT across the axis (g/n per shard — the psum adjoint).
    # Scale it back so dx = psum(partials·g) and the shard-local dw see the true g.
    # tests/test_fused_xent.py::test_tp_variant_matches_dense pins this convention.
    g = g * _axis_size(axis_name)
    dx, dw, _ = _fce_bwd(vocab, softcap, block_t, block_v, interpret, res, g)
    return dx, dw, None


_fce_tp.defvjp(_fce_tp_fwd, _fce_tp_bwd)
