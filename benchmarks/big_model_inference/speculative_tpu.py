"""On-chip cost model for speculative decoding (gptj-6b target + gpt2-124M draft).

The reference has no speculative path (its big-model story stops at offloaded
``generate``, ``benchmarks/big_model_inference/README.md``); this row measures the
MECHANISM's cost on the chip, not a speedup claim: weights are random at real shapes
(same rationale as ``inference_tpu.py`` — timing is shape-dependent only), so the
measured acceptance rate is meaningless-by-construction (~0 for greedy random-weight
models with a 50k vocab). What IS transferable to real checkpoints:

- ``plain_s_per_token``  — the target's plain greedy decode step (two-run protocol).
- ``round_s``            — one speculative round: 1 target dispatch verifying k-1
                           draft proposals + the draft's k-1 cached forwards + the
                           accept/rewind bookkeeping.
- ``breakeven_accept``   — the per-proposal acceptance rate a at which speculative
                           matches plain decode: tokens/round = 1 + a*(k-1), so
                           a* = (round_s / plain_s_per_token - 1) / (k - 1).
                           Below a*, plain decode wins on this hardware; above, the
                           speedup is round_s-linear in a.

Usage:
  python benchmarks/big_model_inference/speculative_tpu.py              # real chip
  BENCH_PRESET=smoke python benchmarks/big_model_inference/speculative_tpu.py
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

_here = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.dirname(os.path.dirname(_here)), _here, os.path.dirname(_here)):
    if p not in sys.path:
        sys.path.insert(0, p)

from inference_tpu import _numpy_random_init  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--k", type=int, default=4)
    p.add_argument("--new-tokens", type=int, default=32)
    p.add_argument("--prompt-len", type=int, default=64)
    args = p.parse_args()
    from bench_timing import force_cpu_for_smoke  # benchmarks/ is on sys.path above

    from accelerate_tpu.utils.environment import place_compile_cache

    place_compile_cache()
    smoke = force_cpu_for_smoke()

    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import gpt

    from bench_timing import refuse_non_smoke_cpu

    if refuse_non_smoke_cpu("speculative_tpu", smoke):
        return 2

    target_name = "tiny" if smoke else "gptj-6b"
    t_cfg = dataclasses.replace(gpt.CONFIGS[target_name], dtype=jnp.bfloat16, attn_impl="xla")
    # Draft: gpt2-124M-shaped, vocab forced to the target's (speculative_accept needs one
    # token space; a real deployment pads gpt2's 50257 head to gpt-j's 50400 the same way).
    # Smoke uses a STRUCTURALLY different draft (half-depth tiny): identical target/draft
    # params would measure accept=1.0 and exercise only the full-acceptance branch.
    if smoke:
        draft_name = "tiny-half"
        d_base = gpt.CONFIGS["tiny"]
        d_cfg = dataclasses.replace(
            d_base, dtype=jnp.bfloat16, attn_impl="xla", vocab_size=t_cfg.vocab_size,
            n_layers=max(1, d_base.n_layers // 2),
        )
    else:
        draft_name = "gpt2"
        d_cfg = dataclasses.replace(
            gpt.CONFIGS["gpt2"],
            dtype=jnp.bfloat16, attn_impl="xla", vocab_size=t_cfg.vocab_size,
        )

    t0 = time.perf_counter()
    dev = jax.devices()[0]
    t_params = jax.device_put(_numpy_random_init(gpt, t_cfg, jnp.bfloat16), dev)
    d_params = jax.device_put(_numpy_random_init(gpt, d_cfg, jnp.bfloat16), dev)
    jax.block_until_ready((t_params, d_params))
    load_s = time.perf_counter() - t0

    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, t_cfg.vocab_size, size=(1, args.prompt_len)), jnp.int32
    )
    M, k = args.new_tokens, args.k

    # Plain greedy decode baseline: two-run protocol (first absorbs compiles).
    from accelerate_tpu.generation import GenerationConfig

    gen = GenerationConfig(max_new_tokens=M, temperature=0.0)
    out = np.asarray(gpt.generate(t_params, prompt, t_cfg, gen))
    t0 = time.perf_counter()
    out = np.asarray(gpt.generate(t_params, prompt, t_cfg, gen))
    plain_s = time.perf_counter() - t0
    assert out.shape == (1, M)
    plain_s_per_token = plain_s / M

    # Speculative: same two-run protocol; stats give rounds for per-round cost.
    def spec():
        return gpt.generate_speculative(
            t_params, t_cfg, d_params, d_cfg, prompt,
            max_new_tokens=M, k=k, return_stats=True,
        )

    spec()
    t0 = time.perf_counter()
    out_s, stats = spec()
    spec_s = time.perf_counter() - t0
    tokens = int(stats["tokens"])
    rounds = max(int(stats["rounds"]), 1)
    round_s = spec_s / rounds  # prefill amortized into the round cost (noted in docs)
    # ADVICE r4: stats["tokens"] includes the prefill-emitted first token, which is not
    # a round-accepted proposal — count round-emitted tokens (tokens - 1) or accept is
    # inflated by ~1/(rounds*(k-1)).
    accept = max(((tokens - 1) / rounds - 1.0) / (k - 1), 0.0)
    breakeven = (round_s / plain_s_per_token - 1.0) / (k - 1)

    row = {
        "metric": f"speculative_cycle ({target_name} target + {draft_name} draft, "
                  f"k={k}, greedy)",
        "plain_s_per_token": round(plain_s_per_token, 4),
        "round_s": round(round_s, 4),
        "spec_s_per_token_at_measured_accept": round(spec_s / max(tokens, 1), 4),
        "measured_accept": round(accept, 3),
        "breakeven_accept": round(breakeven, 3),
        "rounds": rounds,
        "tokens": tokens,
        "target_dispatches": int(stats["target_dispatches"]),
        "k": k,
        "new_tokens": M,
        "load_s": round(load_s, 1),
        "device_kind": dev.device_kind,
        "smoke": smoke,
    }
    print(json.dumps(row), flush=True)
    if not smoke:
        out_dir = os.path.join(os.path.dirname(os.path.dirname(_here)), "chiprun_out")
        os.makedirs(out_dir, exist_ok=True)  # git-ignored: result files are not committed
        with open(os.path.join(out_dir, "speculative_results.jsonl"), "a") as f:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
