"""BASELINE.md north-star row: samples/sec/chip on the ported ``examples/nlp_example.py``
workload (BERT-base, MRPC shape: batch 32, seq 128, bf16, AdamW) on the real chip.

Reuses the example's own model/config/facade path (not a reimplementation) with the
synthetic offline MRPC set at the REAL sequence length, times steady-state training
steps, and prints one JSON line (appended to ``chiprun_out/nlp_bench_results.jsonl``,
which git ignores: result files are not committed).

    python benchmarks/nlp_bench.py            # real chip
    BENCH_PRESET=smoke python benchmarks/nlp_bench.py   # CPU logic check
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

REPO = __import__("os").path.dirname(__import__("os").path.dirname(__import__("os").path.abspath(__file__)))
for p in (REPO, REPO + "/examples", REPO + "/benchmarks"):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench_timing import force_cpu_for_smoke  # noqa: E402


def main() -> int:
    import os

    from accelerate_tpu.utils.environment import place_compile_cache

    place_compile_cache()
    smoke = force_cpu_for_smoke()
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import bert
    from accelerate_tpu.utils import set_seed

    from nlp_example import SyntheticMRPC  # the example's own dataset fallback

    from bench_timing import refuse_non_smoke_cpu

    if refuse_non_smoke_cpu("nlp_bench", smoke):
        return 2

    B = int(os.environ.get("BENCH_NLP_B", "4" if smoke else "32"))
    seq = int(os.environ.get("BENCH_NLP_SEQ", "32" if smoke else "128"))
    n_steps = 3 if smoke else 30
    warmup = 1 if smoke else 5

    set_seed(42)
    cfg = bert.CONFIGS["tiny"] if smoke else bert.CONFIGS["bert-base"]
    acc = Accelerator(mixed_precision=None if smoke else "bf16")
    params = bert.init_params(cfg, jax.random.PRNGKey(42))  # graftlint: disable=rng-key-reuse(fixed seed keeps bench runs comparable)
    tx = optax.adamw(2e-5, weight_decay=0.01)
    state = acc.create_train_state(params, tx, partition_specs=bert.partition_specs(cfg))
    step = acc.build_train_step(lambda p, b: bert.loss_fn(p, b, cfg))

    ds = SyntheticMRPC(cfg, n=B, seed=0, seq_len=seq)
    batch = {k: np.stack([ds[i][k] for i in range(B)]) for k in ds[0]}
    batch = {k: jnp.asarray(v) for k, v in batch.items()}

    for _ in range(warmup):
        state, metrics = step(state, batch)
    jax.block_until_ready(metrics)
    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, metrics = step(state, batch)
    jax.block_until_ready(metrics)  # the last output fences the whole chain
    dt = time.perf_counter() - t0

    samples_per_sec = B * n_steps / dt / jax.device_count()
    row = {
        "metric": f"nlp_example samples/sec/chip (bert-{'tiny' if smoke else 'base'} "
                  f"b{B} seq{seq} {'fp32' if smoke else 'bf16'} adamw)",
        "value": round(samples_per_sec, 2),
        "unit": "samples/sec/chip",
        "ms_per_step": round(dt / n_steps * 1e3, 1),
        "device_kind": str(getattr(jax.devices()[0], "device_kind", "cpu")),
        "smoke": smoke,
    }
    print(json.dumps(row), flush=True)
    if not smoke:
        os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
        with open(os.path.join(REPO, "chiprun_out", "nlp_bench_results.jsonl"), "a") as f:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
