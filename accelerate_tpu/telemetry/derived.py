"""Derived throughput rates: MFU, tokens/sec, examples/sec.

The numbers TPU training/serving reports lead with (pjit-scaling and Gemma-serving
papers both headline MFU and tokens/sec) — computed from a *static* per-step FLOP
cost and a fenced step time, never from device-side counters (which would add host
syncs to the hot path).

``PEAK_TFLOPS`` is the single source of truth for datasheet bf16 peaks; bench.py
imports it from here. A device that is not in the table has no peak: asking for one
is an error, and a utilization is never computed against a guessed chip.
"""

from __future__ import annotations

from typing import Optional

__all__ = ["PEAK_TFLOPS", "peak_tflops", "derived_rates"]

#: Peak dense bf16 TFLOP/s per chip by device kind (public cloud.google.com/tpu docs;
#: per-chip, i.e. both cores/tensorcores of the chip where applicable).
PEAK_TFLOPS = {
    "TPU v2": 22.5,
    "TPU v3": 61.5,
    "TPU v4": 275.0,
    "TPU v5 lite": 196.6,
    "TPU v5e": 196.6,
    "TPU v5p": 459.0,
    "TPU v5": 459.0,
    "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
}


def _lookup(device_kind: str) -> Optional[float]:
    """Longest device-kind match wins ("TPU v5 lite" over "TPU v5"); None = unknown."""
    kind = device_kind.lower()
    keys = [key for key in PEAK_TFLOPS if key.lower() in kind]
    return PEAK_TFLOPS[max(keys, key=len)] if keys else None


def _kind(device) -> str:
    return str(getattr(device, "device_kind", None))


def peak_tflops(device=None, device_kind: Optional[str] = None) -> float:
    """Datasheet bf16 peak for a device; raises ``KeyError`` for a device kind the
    table does not list (the CPU included)."""
    if device_kind is None:
        device_kind = _kind(device)
    peak = _lookup(device_kind)
    if peak is None:
        raise KeyError(
            f"no datasheet peak for device kind {device_kind!r}; known: "
            f"{sorted(PEAK_TFLOPS)}"
        )
    return peak


def derived_rates(
    step_time_s: float,
    *,
    tokens_per_step: Optional[float] = None,
    examples_per_step: Optional[float] = None,
    flops_per_step: Optional[float] = None,
    peak_flops: Optional[float] = None,
    device=None,
    n_chips: int = 1,
) -> dict:
    """Per-chip rates for one step window; absent inputs yield absent columns.

    ``flops_per_step`` is the static model cost (e.g. ``6N + 6LSD`` per token times
    tokens/step — the caller's accounting convention, kept out of this module so the
    MFU history stays tied to one documented FLOP model). ``peak_flops`` (FLOP/s)
    defaults to the datasheet peak of ``device``; with neither (no device, or one
    ``PEAK_TFLOPS`` does not list) the ``mfu`` column is left out.
    """
    out: dict = {}
    if step_time_s <= 0:
        return out
    chips = max(n_chips, 1)
    if tokens_per_step is not None:
        out["tokens_per_sec_per_chip"] = tokens_per_step / step_time_s / chips
    if examples_per_step is not None:
        out["examples_per_sec_per_chip"] = examples_per_step / step_time_s / chips
    if flops_per_step is not None:
        tflops = flops_per_step / step_time_s / chips / 1e12
        out["achieved_tflops_per_chip"] = tflops
        if peak_flops is None:
            peak = _lookup(_kind(device))
            peak_flops = None if peak is None else peak * 1e12
        if peak_flops is not None:
            out["peak_tflops_assumed"] = peak_flops / 1e12
            out["mfu"] = tflops * 1e12 / peak_flops
    return out
