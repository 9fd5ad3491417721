"""Dequant-matmul microbench: int8/int4/nf4 weight-only kernels vs bf16 matmul.

The quantization kernels (``ops/quantization.py``) in two regimes:

- prefill (M=4096): MXU-bound — 8 chained square matmuls per dispatch (the
  decompose.py matmul_peak protocol) so per-dispatch overhead is amortized.
- decode (M=8): HBM-bandwidth-bound — 8 DISTINCT layers' weights per dispatch (one
  reused weight would sit in VMEM and hide the HBM traffic the row exists to measure).

Per scheme, the row reports time, speedup vs the bf16 baseline, speedup vs a NAIVE
dequantize-then-matmul of the same scheme, and the weight-bytes footprint (the "GB
saved" column: int8 halves bf16, 4-bit quarters it plus scales). Any fused kernel
slower than its own naive path is flagged in ``losers`` — a fused kernel that loses
to dequant-then-dot has no reason to exist (reference analog: bnb's int8/4-bit
matmuls, ``utils/bnb.py:44``).

Usage:
  python benchmarks/quant_microbench.py               # real chip; appends a row under chiprun_out/
  BENCH_PRESET=smoke python benchmarks/quant_microbench.py   # CPU logic check (tiny, interpret)
"""

from __future__ import annotations

import datetime
import json
import os
import sys

import numpy as np

_here = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.dirname(_here), _here):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench_timing import (  # noqa: E402
    RowRunner, force_cpu_for_smoke, refuse_non_smoke_cpu, timed,
)

# Result files are not committed: chiprun_out/ is git-ignored.
LEDGER = os.path.join(os.path.dirname(_here), "chiprun_out", "quant_microbench.jsonl")


def main() -> int:
    from accelerate_tpu.utils.environment import place_compile_cache

    place_compile_cache()
    smoke = force_cpu_for_smoke()
    if refuse_non_smoke_cpu("quant_microbench", smoke):
        return 2

    import jax
    import jax.numpy as jnp

    from accelerate_tpu.ops.quantization import quant_matmul, quantize_weight

    K = 256 if smoke else 4096          # square weights so matmuls chain
    M_prefill = 256 if smoke else 4096
    M_decode = 8
    depth = 2 if smoke else 8           # chained layers per dispatch
    n_timed = 1 if smoke else 3

    rng = np.random.default_rng(0)
    ws = [
        jnp.asarray(rng.standard_normal((K, K), dtype=np.float32) / np.sqrt(K), jnp.bfloat16)
        for _ in range(depth)
    ]
    qws = {s: [quantize_weight(w, scheme=s) for w in ws] for s in ("int8", "int4", "nf4")}
    x_prefill = jnp.asarray(rng.standard_normal((M_prefill, K), dtype=np.float32), jnp.bfloat16)
    x_decode = jnp.asarray(rng.standard_normal((M_decode, K), dtype=np.float32), jnp.bfloat16)

    def chain_bf16(x):
        for w in ws:
            x = (x @ w).astype(jnp.bfloat16)
        return x

    def chain_quant(scheme, use_pallas):
        def f(x):
            for qw in qws[scheme]:
                x = quant_matmul(x, qw, out_dtype=jnp.bfloat16, use_pallas=use_pallas)
            return x
        return f

    flops = {"prefill": depth * 2 * M_prefill * K * K, "decode": depth * 2 * M_decode * K * K}
    w_bytes = {
        "bf16": depth * 2 * K * K,
        "int8": depth * (K * K + 4 * K),                 # int8 codes + fp32 per-col scales
        "int4": depth * (K * K // 2 + 4 * (K * K // 64)),  # packed nibbles + block scales
        "nf4": depth * (K * K // 2 + 4 * (K * K // 64)),
    }

    rr = RowRunner()
    times: dict[str, float] = {}

    def bench(name, fn, x, regime):
        def thunk():
            jf = __import__("jax").jit(fn)
            t = timed(jf, x, n=n_timed, warmup=1)
            times[name] = t
            tf = flops[regime] / t / 1e12
            return {"s_per_call": round(t, 5), "tflops": round(tf, 2), "regime": regime}
        rr.row(name, thunk)

    for regime, x in (("prefill", x_prefill), ("decode", x_decode)):
        bench(f"bf16_{regime}", chain_bf16, x, regime)
        bench(f"int8_pallas_{regime}", chain_quant("int8", True), x, regime)
        bench(f"int8_naive_{regime}", chain_quant("int8", False), x, regime)
        # int4/nf4 quant_matmul IS the XLA dequant-then-dot path (packed codes stream
        # from HBM; XLA fuses unpack+scale into the matmul prologue) — one row each.
        bench(f"int4_xla_{regime}", chain_quant("int4", True), x, regime)
        bench(f"nf4_xla_{regime}", chain_quant("nf4", True), x, regime)

    losers = []
    for regime in ("prefill", "decode"):
        base, fused, naive = (times.get(f"{k}_{regime}")
                              for k in ("bf16", "int8_pallas", "int8_naive"))
        for row in rr.rows:
            if row.get("regime") == regime and base and row.get("s_per_call"):
                row["speedup_vs_bf16"] = round(base / row["s_per_call"], 3)
        if fused and naive and fused > naive:
            losers.append(f"int8_pallas_{regime}")

    dev = None
    try:
        import jax

        dev = str(getattr(jax.devices()[0], "device_kind", "unknown"))
    except Exception:
        pass
    record = {
        "metric": f"quant_matmul microbench (K={K}, depth={depth}, bf16 baseline)",
        "weight_bytes": w_bytes,
        "losers_flagged": losers,
        "device_kind": dev,
        "smoke": smoke,
        "recorded_at": datetime.datetime.now(datetime.timezone.utc).isoformat(),
    }
    rc = rr.finish(**record)
    if not smoke:
        with open(LEDGER, "a") as f:
            f.write(json.dumps({"rows": rr.rows, **record}) + "\n")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
