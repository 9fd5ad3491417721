"""Fused AdamW — single-pass Pallas optimizer kernel (the TPU-native FusedAdam).

Reference delegation points this replaces: the reference ecosystem leans on fused CUDA
optimizers for the apply step — DeepSpeed's FusedAdam/cpu-Adam behind
``utils/dataclasses.py:1019-1448`` (DeepSpeedPlugin) and apex ``FusedAdam`` in Megatron mode
(``utils/megatron_lm.py``).  On TPU the optimizer apply is pure HBM bandwidth: the ideal
schedule reads each of p/m/v/g exactly once and writes p/m/v exactly once (7 passes over
param bytes with fp32 moments).  ``optax.adamw`` expresses the update as a chain of
whole-tree transforms; XLA usually fuses them, but the fusion is at the compiler's mercy.
This kernel makes the single pass explicit: one Pallas grid over each leaf computes m', v', bias corrections,
decoupled weight decay, and the parameter update in VMEM, streaming HBM at full rate.

Integration: :class:`FusedAdamW` quacks like an ``optax.GradientTransformation`` (``init`` /
``update``) so every existing code path works, and additionally exposes
``fused_apply(grads, state, params) -> (new_params, new_state)`` which
``Accelerator.build_train_step`` uses when present — fusing what optax's API forces apart
(``update`` then ``apply_updates`` = one extra full read+write of the update tree).

Layout: a leaf is processed by the kernel when its trailing dimension work-reshapes to
lanes of 128 (any leaf with ``size % 1024 == 0`` — all matmul weights; stacked scan leaves
included).  Small/odd leaves (norm gains, biases) fall back to the identical jnp math —
negligible traffic.  ``mu_dtype=bfloat16`` stores the first moment in bf16 (t5x-style),
cutting standing optimizer HBM by 25%.

Low-precision optimizer STATE (the MS-AMP analog — the reference's third fp8 backend
keeps fp8 master weights / optimizer state, ``/root/reference/src/accelerate/accelerator.py:2164``,
``dataclasses.py:1235-1242``): ``mu_dtype``/``nu_dtype`` may be ``float8_e4m3fn`` /
``float8_e5m2``.  fp8 moments are stored with a per-tensor fp32 scale living beside them
in :class:`ScaledAdamState` (the ``DelayedScalingState`` pattern from ``ops/fp8.py``,
but with CURRENT scaling — the true amax of the freshly computed moment, available for
free since the moment is in registers when quantizing).  fp8-stated leaves take the
plain-XLA path rather than the Pallas kernel: the per-leaf math is a single fused
map+amax-reduce XLA program (one read of p/m/v/g, one write of p/m/v + a scalar), and
GSPMD partitions it under any sharding — including FSDP/TP layouts — without shard_map.
At 0.9B params, fp8 mu + fp8 nu cut standing optimizer HBM from ~7.1 GB (fp32) to
~1.8 GB and the apply's moment traffic by 4x (byte counts from shapes; the time this
buys on a chip is not measured).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import optax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import interpret_default as _interpret_default
from ..utils.jax_compat import shard_map as _shard_map

__all__ = ["FusedAdamW", "fused_adamw", "ScaledAdamState"]


class ScaledAdamState(NamedTuple):
    """AdamW state whose moments may be stored in fp8 with per-tensor fp32 scales
    living beside them (the MS-AMP low-precision-optimizer-state analog; reference
    ``accelerator.py:2164``). ``mu_scale``/``nu_scale`` mirror the param tree with one
    fp32 scalar per leaf, or are ``None`` when that moment is full/bf16 precision.
    Same leading fields as ``optax.ScaleByAdamState`` so ``state[0].mu``-style
    introspection and checkpointing (a plain pytree) work unchanged."""

    count: Any
    mu: Any
    nu: Any
    mu_scale: Any = None
    nu_scale: Any = None


_F8_MAX = {
    jnp.dtype(jnp.float8_e4m3fn): 448.0,
    jnp.dtype(jnp.float8_e5m2): 57344.0,
}


def _is_f8(dt) -> bool:
    return dt is not None and jnp.dtype(dt) in _F8_MAX


def _quant_f8(x32: jax.Array, dt) -> tuple[jax.Array, jax.Array]:
    """Per-tensor CURRENT scaling: scale = amax/emax of the value being stored (the
    value is already in registers — no extra HBM pass, unlike delayed scaling which
    exists to avoid exactly that pass for activations)."""
    emax = _F8_MAX[jnp.dtype(dt)]
    amax = jnp.max(jnp.abs(x32))
    scale = (jnp.maximum(amax, 1e-30) / emax).astype(jnp.float32)
    return (x32 / scale).astype(dt), scale


def _dequant_f8(x: jax.Array, scale: jax.Array) -> jax.Array:
    return x.astype(jnp.float32) * scale

_LANES = 1024  # 8 sublanes x 128 lanes: the fp32 VMEM tile; every kernel row is one tile


def _adamw_kernel(
    sc_ref, p_ref, m_ref, v_ref, g_ref, po_ref, mo_ref, vo_ref, *, b1, b2, eps, wd
):
    """One block: m' = b1*m + (1-b1)*g; v' = b2*v + (1-b2)*g^2;
    p' = p - lr*(mhat/(sqrt(vhat)+eps) + wd*p)  (decoupled AdamW decay).

    ``sc_ref`` (SMEM, [4]) carries the traced scalars: [grad_scale (clip), lr,
    (1-b1^t), (1-b2^t)] — hyperparameters that vary per step stay out of the
    compiled kernel constant pool.

    Expression order mirrors ``optax.adamw`` exactly (incl. division by the bias
    correction), making fp32-moment trajectories bit-identical.  With
    ``mu_dtype=bfloat16`` the TPU VPU keeps the ``b1 * m`` product in fp32 where optax
    rounds it to bf16 first — one rounding tighter, so trajectories agree only to bf16
    ulp (see tests/test_fused_optim.py tolerances).
    """
    gscale = sc_ref[0]
    lr = sc_ref[1]
    bc1 = sc_ref[2]
    bc2 = sc_ref[3]
    g = g_ref[:].astype(jnp.float32) * gscale
    p = p_ref[:].astype(jnp.float32)
    m_new = (1.0 - b1) * g + b1 * m_ref[:]   # promotion order = optax update_moment
    v_new = (1.0 - b2) * (g * g) + b2 * v_ref[:]
    mhat = m_new / bc1
    vhat = v_new / bc2
    update = mhat / (jnp.sqrt(vhat) + eps) + wd * p
    po_ref[:] = (p - lr * update).astype(po_ref.dtype)
    mo_ref[:] = m_new.astype(mo_ref.dtype)
    vo_ref[:] = v_new.astype(vo_ref.dtype)


_VMEM_BUDGET = 12 * 2**20  # bytes a block's refs may claim; v5e VMEM is ~16 MB total


def _leaf_fused(p, m, v, g, scalars, *, b1, b2, eps, wd, block_rows, interpret):
    """Run the kernel over one leaf reshaped to [rows, 1024].

    Rows that don't divide by a near-``block_rows`` factor are PADDED up to a multiple
    (the update math is elementwise, so padded rows compute garbage that is sliced off) —
    the old largest-divisor rule degraded to block_rows=1 for prime row counts, turning
    one launch into thousands of [1, 1024] grid steps.

    ``block_rows`` is additionally capped by a VMEM budget: the grid streams 7 refs
    (p/m/v/g in, p/m/v out) and Pallas double-buffers each, so an all-fp32 512-row
    block claims 2 x 512 x 1024 x 28 B ~= 29 MB — past the v5e's 16 MB scoped-VMEM
    default. The cap is dtype-aware, so bf16 moments earn proportionally taller
    blocks."""
    shape, dtype = p.shape, p.dtype
    rows = p.size // _LANES
    bytes_per_row = _LANES * (
        2 * p.dtype.itemsize + 2 * m.dtype.itemsize + 2 * v.dtype.itemsize
        + g.dtype.itemsize
    )
    vmem_rows = max(8, _VMEM_BUDGET // (2 * bytes_per_row) // 8 * 8)
    cap = min(block_rows, rows, vmem_rows)
    br = cap
    pad = 0
    while rows % br:  # largest divisor <= cap keeps the grid exact (no masking)
        br -= 1
    if br < cap // 4:
        # No decent divisor (prime-ish rows): pad to a cap multiple instead.
        br = cap
        pad = (-rows) % br
    grid = ((rows + pad) // br,)

    def _prep(a):
        a2 = a.reshape(rows, _LANES)
        if pad:
            a2 = jnp.pad(a2, ((0, pad), (0, 0)))
        return a2

    p2, m2, v2, g2 = _prep(p), _prep(m), _prep(v), _prep(g)
    rows += pad
    kernel = functools.partial(_adamw_kernel, b1=b1, b2=b2, eps=eps, wd=wd)
    spec = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    po, mo, vo = pl.pallas_call(
        kernel,
        name="fused_adamw",
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            spec, spec, spec, spec,
        ],
        out_specs=[spec, spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows, _LANES), dtype),
            jax.ShapeDtypeStruct((rows, _LANES), m.dtype),
            jax.ShapeDtypeStruct((rows, _LANES), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL,),
        ),
        interpret=interpret,
    )(scalars, p2, m2, v2, g2)
    if pad:
        po, mo, vo = po[:-pad], mo[:-pad], vo[:-pad]
    return po.reshape(shape), mo.reshape(shape), vo.reshape(shape)


def _leaf_xla(p, m, v, g, scalars, *, b1, b2, eps, wd):
    """Identical math for leaves the kernel layout doesn't cover (small/odd shapes)."""
    gscale, lr, bc1, bc2 = scalars[0], scalars[1], scalars[2], scalars[3]
    g = g.astype(jnp.float32) * gscale
    p32 = p.astype(jnp.float32)
    m_new = (1.0 - b1) * g + b1 * m     # promotion order = optax update_moment
    v_new = (1.0 - b2) * (g * g) + b2 * v
    update = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps) + wd * p32
    p_new = (p32 - lr * update).astype(p.dtype)
    return p_new, m_new.astype(m.dtype), v_new.astype(v.dtype)


def _leaf_xla_scaled(p, m, v, g, scalars, m_scale, v_scale, *, b1, b2, eps, wd):
    """AdamW update for a leaf whose moments are stored scaled-fp8.

    One fused XLA map+amax-reduce over the leaf (GSPMD-partitionable under any layout,
    so fp8-stated leaves never need shard_map): dequantize the incoming moments with
    last step's per-tensor scale, do the fp32 update, requantize with the fresh amax.
    Returns ``(p', m', v', m_scale', v_scale')`` — scale entries are None for a moment
    that isn't fp8."""
    gscale, lr, bc1, bc2 = scalars[0], scalars[1], scalars[2], scalars[3]
    g = g.astype(jnp.float32) * gscale
    p32 = p.astype(jnp.float32)
    m32 = _dequant_f8(m, m_scale) if m_scale is not None else m
    v32 = _dequant_f8(v, v_scale) if v_scale is not None else v
    m_new = (1.0 - b1) * g + b1 * m32
    v_new = (1.0 - b2) * (g * g) + b2 * v32
    update = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps) + wd * p32
    p_new = (p32 - lr * update).astype(p.dtype)
    if m_scale is not None:
        m_out, m_scale_out = _quant_f8(m_new, m.dtype)
    else:
        m_out, m_scale_out = m_new.astype(m.dtype), None
    if v_scale is not None:
        v_out, v_scale_out = _quant_f8(v_new, v.dtype)
    else:
        v_out, v_scale_out = v_new.astype(v.dtype), None
    return p_new, m_out, v_out, m_scale_out, v_scale_out


@dataclasses.dataclass
class FusedAdamW:
    """Drop-in AdamW with a fused Pallas apply.

    Quacks like ``optax.GradientTransformation`` (``init``/``update``) so
    ``Accelerator.prepare`` / checkpointing / schedulers work unchanged, while
    ``build_train_step`` detects ``fused_apply`` and uses the single-pass kernel.
    ``learning_rate`` may be a float or an optax schedule (called on the step count).
    """

    learning_rate: Union[float, Callable[[Any], Any]] = 1e-3
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 1e-4
    mu_dtype: Optional[Any] = None
    nu_dtype: Optional[Any] = None
    block_rows: int = 512
    interpret: Optional[bool] = None

    # -------------------------------------------------------------- optax-compatible API
    def init(self, params):
        mu_dtype = self.mu_dtype or None
        nu_dtype = self.nu_dtype or None

        # zeros_LIKE, not zeros: each moment leaf must inherit its param's sharding —
        # create_train_state relies on that invariant, and at 0.9B params an unsharded
        # fp32 mu+nu is ~7 GB landing on one device.
        mu = jax.tree_util.tree_map(
            lambda p: jnp.zeros_like(p, dtype=mu_dtype or p.dtype), params
        )
        nu = jax.tree_util.tree_map(
            lambda p: jnp.zeros_like(p, dtype=nu_dtype or p.dtype), params
        )
        count = jnp.zeros((), jnp.int32)
        if not (_is_f8(mu_dtype) or _is_f8(nu_dtype)):
            return optax.ScaleByAdamState(count=count, mu=mu, nu=nu)
        ones = lambda: jax.tree_util.tree_map(  # noqa: E731
            lambda _: jnp.ones((), jnp.float32), params
        )
        return ScaledAdamState(
            count=count, mu=mu, nu=nu,
            mu_scale=ones() if _is_f8(mu_dtype) else None,
            nu_scale=ones() if _is_f8(nu_dtype) else None,
        )

    def _scalars(self, count, grad_scale):
        count_f = (count + 1).astype(jnp.float32)
        lr = self.learning_rate(count) if callable(self.learning_rate) else self.learning_rate
        return jnp.stack([
            jnp.asarray(grad_scale, jnp.float32),
            jnp.asarray(lr, jnp.float32),
            1.0 - jnp.asarray(self.b1, jnp.float32) ** count_f,
            1.0 - jnp.asarray(self.b2, jnp.float32) ** count_f,
        ])

    def update(self, grads, state, params=None):
        """optax-protocol path (returns an update tree) in PURE XLA — no Pallas.

        This is the route ``build_train_step`` takes for layouts the kernel cannot
        partition (ZeRO-1/2, where opt state and params have different shardings), so it
        must stay an ordinary partitionable XLA program: same math via ``_leaf_xla`` on
        every leaf, GSPMD free to shard it however the state is laid out.
        """
        if params is None:
            raise ValueError("FusedAdamW.update requires params (AdamW decays weights).")
        scalars = self._scalars(state.count, 1.0)
        kw = dict(b1=self.b1, b2=self.b2, eps=self.eps, wd=self.weight_decay)

        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_ms, flat_vs = self._flat_scales(state, treedef, len(flat_p))

        def one(p, m, v, g, ms, vs):
            if ms is not None or vs is not None:
                return _leaf_xla_scaled(p, m, v, g, scalars, ms, vs, **kw)
            return (*_leaf_xla(p, m, v, g, scalars, **kw), None, None)

        out = [
            one(p, m, v, g, ms, vs)
            for p, m, v, g, ms, vs in zip(
                flat_p,
                treedef.flatten_up_to(state.mu),
                treedef.flatten_up_to(state.nu),
                treedef.flatten_up_to(grads),
                flat_ms, flat_vs,
            )
        ]
        updates = treedef.unflatten(
            [
                (n.astype(jnp.float32) - p.astype(jnp.float32)).astype(p.dtype)
                for (n, *_), p in zip(out, flat_p)
            ]
        )
        return updates, self._rebuild_state(state, treedef, out)

    def _flat_scales(self, state, treedef, n):
        """Per-leaf (mu_scale, nu_scale) lists — all-None for plain ScaleByAdamState."""
        mu_scale = getattr(state, "mu_scale", None)
        nu_scale = getattr(state, "nu_scale", None)
        flat_ms = treedef.flatten_up_to(mu_scale) if mu_scale is not None else [None] * n
        flat_vs = treedef.flatten_up_to(nu_scale) if nu_scale is not None else [None] * n
        return flat_ms, flat_vs

    def _rebuild_state(self, state, treedef, out):
        """Reassemble the state from per-leaf (p', m', v', m_scale', v_scale') rows,
        preserving the incoming state's type (plain vs scaled)."""
        mu = treedef.unflatten([o[1] for o in out])
        nu = treedef.unflatten([o[2] for o in out])
        if getattr(state, "mu_scale", None) is None and getattr(
            state, "nu_scale", None
        ) is None and not isinstance(state, ScaledAdamState):
            return optax.ScaleByAdamState(count=state.count + 1, mu=mu, nu=nu)
        return ScaledAdamState(
            count=state.count + 1, mu=mu, nu=nu,
            mu_scale=(
                treedef.unflatten([o[3] for o in out])
                if getattr(state, "mu_scale", None) is not None
                else None
            ),
            nu_scale=(
                treedef.unflatten([o[4] for o in out])
                if getattr(state, "nu_scale", None) is not None
                else None
            ),
        )

    # ------------------------------------------------------------------ fused fast path
    def fused_apply(self, grads, state, params, grad_scale=1.0, specs=None, mesh=None):
        """Single-pass apply: ``(new_params, new_state)``.

        ``grad_scale`` folds an already-computed global-norm clip factor into the same
        pass (``build_train_step`` passes it instead of pre-scaling the grad tree, saving
        one full read+write of the gradients).

        ``specs``/``mesh``: per-leaf ``PartitionSpec`` tree for cross-device-sharded
        states (FSDP/ZeRO-3, TP — where p/m/v/g share one layout, the default produced by
        ``create_train_state``). Sharded leaves run the kernel under ``shard_map``: each
        device updates exactly its own shard, no gather, no replication — the fused apply
        IS the ZeRO-3 optimizer step. Leaves whose spec is None/empty run unmapped.
        """
        interpret = self.interpret if self.interpret is not None else _interpret_default()
        scalars = self._scalars(state.count, grad_scale)
        kw = dict(b1=self.b1, b2=self.b2, eps=self.eps, wd=self.weight_decay)

        def local(sc, p, m, v, g):
            # Kernel-vs-fallback decided on the LOCAL (per-shard) shape.
            if p.size % _LANES == 0 and p.size > 0:
                return _leaf_fused(
                    p, m, v, g, sc,
                    block_rows=self.block_rows, interpret=interpret, **kw,
                )
            return _leaf_xla(p, m, v, g, sc, **kw)

        def _evenly_divisible(shape, spec) -> bool:
            for dim, axes in zip(shape, spec):
                if axes is None:
                    continue
                axes = axes if isinstance(axes, tuple) else (axes,)
                n = 1
                for a in axes:
                    n *= mesh.shape[a]
                if dim % n:
                    return False
            return True

        def one(p, m, v, g, spec=None, ms=None, vs=None):
            if ms is not None or vs is not None:
                # fp8-stated leaf: one fused XLA map+amax-reduce — GSPMD partitions it
                # under any spec (the amax collective included), so no shard_map and no
                # Pallas here by design (see module docstring).
                return _leaf_xla_scaled(p, m, v, g, scalars, ms, vs, **kw)
            if isinstance(spec, str):  # "opaque": un-expressible layout — plain XLA only
                return (*_leaf_xla(p, m, v, g, scalars, **kw), None, None)
            if spec is not None and mesh is not None and any(a for a in spec):
                if not _evenly_divisible(p.shape, spec):
                    # shard_map needs even shards; GSPMD pads NamedShardings (legal), so
                    # uneven leaves take the identical partitionable XLA math instead.
                    return (*_leaf_xla(p, m, v, g, scalars, **kw), None, None)
                from jax.sharding import PartitionSpec

                mapped = _shard_map(
                    local,
                    mesh=mesh,
                    in_specs=(PartitionSpec(), spec, spec, spec, spec),
                    out_specs=(spec, spec, spec),
                    check_vma=False,  # pallas_call outputs carry no vma info
                )
                return (*mapped(scalars, p, m, v, g), None, None)
            return (*local(scalars, p, m, v, g), None, None)

        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_m = treedef.flatten_up_to(state.mu)
        flat_v = treedef.flatten_up_to(state.nu)
        flat_g = treedef.flatten_up_to(grads)
        flat_s = (
            treedef.flatten_up_to(specs) if specs is not None else [None] * len(flat_p)
        )
        flat_ms, flat_vs = self._flat_scales(state, treedef, len(flat_p))
        out = [
            one(p, m, v, g, s, ms, vs)
            for p, m, v, g, s, ms, vs in zip(
                flat_p, flat_m, flat_v, flat_g, flat_s, flat_ms, flat_vs
            )
        ]
        new_params = treedef.unflatten([o[0] for o in out])
        return new_params, self._rebuild_state(state, treedef, out)


def fused_adamw(
    learning_rate: Union[float, Callable] = 1e-3,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 1e-4,
    mu_dtype=None,
    nu_dtype=None,
) -> FusedAdamW:
    """``optax.adamw``-shaped constructor for the fused kernel optimizer.

    ``mu_dtype``/``nu_dtype`` accept ``jnp.bfloat16`` (plain low-precision moment) or
    ``jnp.float8_e4m3fn``/``float8_e5m2`` (scaled-fp8 moment with a per-tensor scale in
    :class:`ScaledAdamState` — the MS-AMP low-precision-optimizer-state analog)."""
    return FusedAdamW(
        learning_rate=learning_rate, b1=b1, b2=b2, eps=eps,
        weight_decay=weight_decay, mu_dtype=mu_dtype, nu_dtype=nu_dtype,
    )
