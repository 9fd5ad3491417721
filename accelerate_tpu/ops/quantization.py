"""Weight-only int8 / int4(+nf4) quantization — the TPU-native bitsandbytes replacement.

Reference delegation points this file replaces (``utils/bnb.py``: ``load_and_quantize_model``
:44, layer swap :277-374; config ``dataclasses.py:2450``; guard rails ``accelerator.py:
1479-1516``): bnb swaps ``nn.Linear`` for CUDA ``Linear8bitLt``/``Linear4bit`` modules. Here a
weight is a pytree leaf, so quantization is a *leaf transform*: ``quantize_weight`` produces a
:class:`QuantizedWeight` (itself a pytree node carrying packed codes + per-block scales) and
matmuls go through :func:`quant_matmul`, whose Pallas kernel dequantizes **inside the tile
loop** — HBM reads stay int8/int4, dequant happens in VMEM right before the MXU, which is the
entire memory-bandwidth win of weight-only quantization on TPU.

Schemes (bnb parity):
- ``int8``: per-output-channel absmax (bnb's vectorwise Linear8bitLt analog).
- ``int4``: blockwise absmax linear codes, two nibbles packed per uint8 (bnb FP4 analog).
- ``nf4``: blockwise absmax with the NormalFloat-4 codebook (QLoRA's data type; same 16-entry
  table as bnb's nf4).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ._common import interpret_default

__all__ = [
    "BnbQuantizationConfig",
    "QuantizedWeight",
    "quantize_weight",
    "dequantize_weight",
    "quant_matmul",
    "load_and_quantize_model",
    "dequantize_model",
    "NF4_CODEBOOK",
]

# NormalFloat-4: quantiles of N(0,1) normalized to [-1, 1] (QLoRA paper, bnb's nf4 table).
# A NumPy constant: a ``jnp`` array here would initialize the backend (and claim the
# chip) the moment any module of the package is imported.
NF4_CODEBOOK = np.asarray(
    [
        -1.0, -0.6961928009986877, -0.5250730514526367, -0.39491748809814453,
        -0.28444138169288635, -0.18477343022823334, -0.09105003625154495, 0.0,
        0.07958029955625534, 0.16093020141124725, 0.24611230194568634, 0.33791524171829224,
        0.44070982933044434, 0.5626170039176941, 0.7229568362236023, 1.0,
    ],
    dtype=np.float32,
)


@dataclasses.dataclass
class BnbQuantizationConfig:
    """Quantization knobs (reference ``dataclasses.py:2450`` BnbQuantizationConfig)."""

    load_in_8bit: bool = False
    load_in_4bit: bool = False
    bnb_4bit_quant_type: str = "int4"  # int4 | nf4
    block_size: int = 64               # int4/nf4 scaling-block length
    torch_dtype: Any = jnp.bfloat16  # graftlint: disable=dead-knob(HF BnB config parity; dequant compute dtype follows the param tree)
    skip_modules: Optional[list[str]] = None
    keep_in_fp32_modules: Optional[list[str]] = None
    min_weight_size: int = 4096        # leaves smaller than this stay unquantized

    def __post_init__(self):
        if self.load_in_8bit and self.load_in_4bit:
            raise ValueError("load_in_8bit and load_in_4bit can't be both True")
        if not (self.load_in_8bit or self.load_in_4bit):
            raise ValueError("load_in_8bit and load_in_4bit can't be both False")
        if self.bnb_4bit_quant_type not in ("int4", "nf4"):
            raise ValueError(f"unsupported 4-bit quant type {self.bnb_4bit_quant_type!r}")

    @property
    def scheme(self) -> str:
        return "int8" if self.load_in_8bit else self.bnb_4bit_quant_type


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QuantizedWeight:
    """Packed codes + scales; a pytree node, so it checkpoints/shards like any leaf pair.

    int8: ``data`` int8 [in, out], ``scales`` fp32 [out] (per-output-channel absmax).
    int4/nf4: ``data`` uint8 [in*out/2] (two nibbles per byte, row-major), ``scales`` fp32
    [n_blocks] (per-block absmax); ``shape``/``scheme``/``block_size`` are static metadata.
    """

    data: jax.Array
    scales: jax.Array
    shape: tuple = dataclasses.field(metadata={"static": True})
    scheme: str = dataclasses.field(metadata={"static": True})
    block_size: int = dataclasses.field(metadata={"static": True})

    @property
    def dtype(self):  # quacks like an array for size accounting
        return self.data.dtype

    @property
    def nbytes(self) -> int:
        return int(self.data.size * self.data.dtype.itemsize + self.scales.size * 4)


def quantize_weight(w: jax.Array, scheme: str = "int8", block_size: int = 64) -> QuantizedWeight:
    """Quantize one 2-D weight. ``scheme``: int8 | int4 | nf4."""
    w = jnp.asarray(w)
    if w.ndim != 2:
        raise ValueError(f"weight-only quantization expects 2-D weights, got {w.shape}")
    shape = tuple(w.shape)
    wf = w.astype(jnp.float32)
    if scheme == "int8":
        absmax = jnp.max(jnp.abs(wf), axis=0)  # per output channel
        scales = jnp.maximum(absmax, 1e-8) / 127.0
        q = jnp.clip(jnp.round(wf / scales), -127, 127).astype(jnp.int8)
        return QuantizedWeight(q, scales, shape, "int8", block_size)

    flat = wf.reshape(-1)
    pad = (-flat.size) % block_size
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, block_size)
    absmax = jnp.maximum(jnp.max(jnp.abs(blocks), axis=1, keepdims=True), 1e-8)
    normed = blocks / absmax  # [-1, 1]
    if scheme == "int4":
        codes = jnp.clip(jnp.round(normed * 7.0) + 8, 0, 15).astype(jnp.uint8)
    elif scheme == "nf4":
        codes = jnp.argmin(jnp.abs(normed[..., None] - NF4_CODEBOOK), axis=-1).astype(jnp.uint8)
    else:
        raise ValueError(f"unknown scheme {scheme!r}")
    flat_codes = codes.reshape(-1)
    packed = (flat_codes[0::2] | (flat_codes[1::2] << 4)).astype(jnp.uint8)
    return QuantizedWeight(packed, absmax[:, 0], shape, scheme, block_size)


def _unpack_codes(qw: QuantizedWeight) -> jax.Array:
    lo = (qw.data & 0x0F).astype(jnp.uint8)
    hi = (qw.data >> 4).astype(jnp.uint8)
    return jnp.stack([lo, hi], axis=1).reshape(-1)


def dequantize_weight(qw: QuantizedWeight, dtype=jnp.float32) -> jax.Array:
    if qw.scheme == "int8":
        return (qw.data.astype(jnp.float32) * qw.scales).astype(dtype).reshape(qw.shape)
    codes = _unpack_codes(qw)
    if qw.scheme == "int4":
        values = (codes.astype(jnp.float32) - 8.0) / 7.0
    else:  # nf4
        values = jnp.asarray(NF4_CODEBOOK)[codes]
    blocks = values.reshape(-1, qw.block_size) * qw.scales[:, None]
    n = int(np.prod(qw.shape))
    return blocks.reshape(-1)[:n].reshape(qw.shape).astype(dtype)


# -------------------------------------------------------------------------- pallas matmul
def _int8_matmul_kernel(x_ref, w_ref, s_ref, o_ref):
    """Tile matmul dequantizing int8 w in VMEM: HBM traffic stays int8."""
    from jax.experimental import pallas as pl

    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    o_ref[...] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _scale():
        o_ref[...] *= s_ref[...].astype(jnp.float32)


def _quant_matmul_pallas_int8(x, qw: QuantizedWeight, block_m=128, block_k=128, block_n=128):
    from jax.experimental import pallas as pl

    K, N = qw.shape
    B = int(np.prod(x.shape[:-1]))
    x2 = x.reshape(B, K).astype(jnp.float32)
    interpret = interpret_default()

    bm, bk, bn = min(block_m, B), min(block_k, K), min(block_n, N)
    pad_m, pad_k, pad_n = (-B) % bm, (-K) % bk, (-N) % bn
    xp = jnp.pad(x2, ((0, pad_m), (0, pad_k)))
    wp = jnp.pad(qw.data, ((0, pad_k), (0, pad_n)))
    sp = jnp.pad(qw.scales, (0, pad_n))

    grid = (xp.shape[0] // bm, wp.shape[1] // bn, xp.shape[1] // bk)
    out = pl.pallas_call(
        _int8_matmul_kernel,
        name="quant_matmul",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], wp.shape[1]), jnp.float32),
        interpret=interpret,
    )(xp, wp, sp[None, :])
    return out[:B, :N].reshape(*x.shape[:-1], N)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _int8_matmul_diffable(x, data, scales, shape_0: int, shape_1: int):
    qw = QuantizedWeight(data, scales, (shape_0, shape_1), "int8", 0)
    return _quant_matmul_pallas_int8(x, qw)


def _int8_mm_fwd(x, data, scales, shape_0, shape_1):
    qw = QuantizedWeight(data, scales, (shape_0, shape_1), "int8", 0)
    return _quant_matmul_pallas_int8(x, qw), (x, data, scales)


def _int8_mm_bwd(shape_0, shape_1, residuals, g):
    x, data, scales = residuals
    w = (data.astype(jnp.float32) * scales).astype(x.dtype)  # dequant for the backward
    dx = jnp.einsum("...n,kn->...k", g.astype(x.dtype), w)
    # Quantized weights are frozen (weight-only inference/fine-tune); int data gets a
    # symbolic-zero cotangent, scales a real zero.
    d_data = np.zeros(data.shape, jax.dtypes.float0)
    d_scales = jnp.zeros_like(scales)
    return dx, d_data, d_scales


_int8_matmul_diffable.defvjp(_int8_mm_fwd, _int8_mm_bwd)


def quant_matmul(x: jax.Array, qw: QuantizedWeight, out_dtype=None, use_pallas: bool = True):
    """``x @ dequant(qw)`` with the dequant fused into the kernel (int8 Pallas path).

    Differentiable w.r.t. ``x`` (custom VJP over the kernel — the quantized weight is frozen,
    which is the weight-only fine-tuning contract). int4/nf4 fall back to XLA dequant-then-dot
    — XLA fuses the unpack+scale into the matmul prologue, so codes still stream from HBM
    packed.
    """
    out_dtype = out_dtype or x.dtype
    if qw.scheme == "int8" and use_pallas and x.ndim >= 2:
        y = _int8_matmul_diffable(x, qw.data, qw.scales, qw.shape[0], qw.shape[1])
        return y.astype(out_dtype)
    w = dequantize_weight(qw, dtype=x.dtype)
    return (x @ w).astype(out_dtype)


# ------------------------------------------------------------------------ model transform
def load_and_quantize_model(
    params: Any,
    quantization_config: BnbQuantizationConfig,
) -> Any:
    """Quantize every eligible 2-D weight leaf of a params pytree.

    Reference analog: ``load_and_quantize_model`` (``bnb.py:44``) + ``replace_with_bnb_layers``
    (:277) — module swap becomes a leaf transform. Eligibility mirrors bnb's rules: 2-D, at
    least ``min_weight_size`` elements, key path not in ``skip_modules`` /
    ``keep_in_fp32_modules``.
    """
    from ..utils.modeling import named_parameters
    from ..utils.serialization import unflatten_to_nested_dict

    cfg = quantization_config
    skip = set(cfg.skip_modules or []) | set(cfg.keep_in_fp32_modules or [])
    flat = named_parameters(params)
    out = {}
    for name, leaf in flat.items():
        eligible = (
            hasattr(leaf, "ndim")
            and leaf.ndim == 2
            and leaf.size >= cfg.min_weight_size
            and not any(name == s or name.startswith(s + "/") or name.endswith("/" + s) for s in skip)
        )
        out[name] = quantize_weight(leaf, cfg.scheme, cfg.block_size) if eligible else leaf
    nested = unflatten_to_nested_dict(out)
    from ..big_modeling import _listify_int_dicts

    return _listify_int_dicts(nested)


def dequantize_model(params: Any, dtype=jnp.float32) -> Any:
    """Inverse transform: QuantizedWeight leaves → dense arrays."""
    return jax.tree_util.tree_map(
        lambda leaf: dequantize_weight(leaf, dtype) if isinstance(leaf, QuantizedWeight) else leaf,
        params,
        is_leaf=lambda leaf: isinstance(leaf, QuantizedWeight),
    )
