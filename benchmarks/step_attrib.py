"""Attribute the train-step MFU gap between fwd_bwd alone (benchmarks/decompose.py) and
the full bench step — the share of step time that is NOT the model math. This times the
bench's exact step pipeline with components toggled:

  grad_fp32cast   — value_and_grad of the bench loss with fp32 master params + in-step
                    bf16 cast (the bench's `compute`), no optimizer
  grad_bf16       — same but params stored bf16, no cast (decompose's fwd_bwd baseline)
  grad_clip       — + global-norm clip
  full_sgd        — build_train_step(fuse=1) with optax.sgd (isolates adamw bandwidth)
  full_adamw_f1   — build_train_step(fuse=1) with adamw (the real thing, unfused)
  full_adamw_f4   — build_train_step(fuse=4) (the bench config; per-step time reported)
  full_fused_adamw_f1 / _f4 — the same with the Pallas fused AdamW kernel
  full_fused_adamw_lossfused_f4 — fused AdamW + fused Pallas CE (the candidate scoring
                    config)

Every row is failure-scoped (bench_timing.RowRunner): one OOM/compile failure records
the row and continues; the final JSON always prints, and the exit code is non-zero when
any row failed. Run on the real chip.
"""

from __future__ import annotations

import dataclasses
import sys
import time

import numpy as np

REPO = __import__("os").path.dirname(__import__("os").path.dirname(__import__("os").path.abspath(__file__)))
sys.path.insert(0, REPO)

from bench_timing import RowRunner  # noqa: E402
from bench_timing import materialize as _materialize  # noqa: E402  (the fence)


def timed_state(fn, state, batch, n=3):
    """Time a state-donating step honestly: state threads through (donation-safe)."""
    state, out = fn(state, batch)  # warmup/compile
    _materialize(out)
    t0 = time.perf_counter()
    for _ in range(n):
        state, out = fn(state, batch)
    _materialize(out)
    return (time.perf_counter() - t0) / n, state


def main() -> int:
    from bench_timing import force_cpu_for_smoke

    from accelerate_tpu.utils.environment import place_compile_cache

    place_compile_cache()
    smoke = force_cpu_for_smoke()
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import llama

    B, S, FUSE = (1, 256, 2) if smoke else (4, 2048, 4)
    cfg = dataclasses.replace(
        llama.CONFIGS["llama3-8b"],
        vocab_size=512 if smoke else 32768,
        d_model=128 if smoke else 2048,
        n_layers=2 if smoke else 12,
        n_heads=4 if smoke else 16,
        n_kv_heads=2 if smoke else 8,
        d_ff=256 if smoke else 8192,
        max_seq=S, remat=True, remat_policy="full", scan_layers=True,
        attn_impl="xla" if smoke else "flash",
    )
    n_params = llama.num_params(cfg)
    flops_per_token = 6 * n_params + 6 * cfg.n_layers * S * cfg.d_model
    model_tflop_per_step = flops_per_token * B * S / 1e12
    rr = RowRunner()

    def record(name, dt_step):
        tf = model_tflop_per_step / dt_step
        print(f"{name:28s} {dt_step*1e3:9.1f} ms/step   {tf:8.2f} model-TFLOP/s", flush=True)
        return {"ms_per_step": round(dt_step * 1e3, 1), "model_tflops": round(tf, 2)}

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S + 1)), jnp.int32)
    batch = {"tokens": tokens}

    from accelerate_tpu.accelerator import cast_floating

    # --- grad with bf16-stored params (decompose parity point)
    def grad_bf16_row():
        params_bf16 = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.bfloat16), llama.init_params(cfg)
        )
        g = jax.jit(jax.grad(lambda p, b: llama.loss_fn(p, b, cfg)), donate_argnums=())
        dt, _ = timed_state(lambda s, b: (s, g(s, b)), params_bf16, batch)
        return record("grad_bf16", dt)

    rr.row("grad_bf16", grad_bf16_row)

    # --- grad with fp32 master params + in-step cast (bench's compute, no optimizer)
    def loss_cast(p, b):
        return llama.loss_fn(cast_floating(p, jnp.bfloat16), b, cfg)

    def grad_cast_row():
        params32 = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.float32), llama.init_params(cfg)
        )
        g = jax.jit(jax.grad(loss_cast))
        dt, _ = timed_state(lambda s, b: (s, g(s, b)), params32, batch)
        return record("grad_fp32cast", dt)

    rr.row("grad_fp32cast", grad_cast_row)

    # --- + global-norm clip
    def grad_clip_row():
        params32 = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.float32), llama.init_params(cfg)
        )

        def grad_clipped(p, b):
            g = jax.grad(loss_cast)(p, b)
            gnorm = optax.global_norm(g)
            scale = jnp.minimum(1.0, 1.0 / (gnorm + 1e-6))
            return jax.tree_util.tree_map(lambda x: x * scale, g)

        g = jax.jit(grad_clipped)
        dt, _ = timed_state(lambda s, b: (s, g(s, b)), params32, batch)
        return record("grad_clip", dt)

    rr.row("grad_clip", grad_clip_row)

    # --- full framework steps through the facade
    def full_row(name, tx, fuse, fused_optimizer=False, fused_loss=False):
        def thunk():
            from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

            AcceleratorState._reset_state()
            GradientState._reset_state()
            PartialState._reset_state()
            acc = Accelerator(mixed_precision="bf16")
            if fused_optimizer:
                from accelerate_tpu.ops.fused_optim import fused_adamw

                state = acc.create_train_state(llama.init_params(cfg), fused_adamw(1e-4))
            else:
                state = acc.create_train_state(llama.init_params(cfg), tx)
            loss = (
                (lambda p, b: llama.loss_fn(p, b, dataclasses.replace(cfg, loss_impl="fused")))
                if fused_loss else (lambda p, b: llama.loss_fn(p, b, cfg))
            )
            step = acc.build_train_step(loss, max_grad_norm=1.0, fused_steps=fuse)
            if fuse > 1:
                stacked = {"tokens": np.asarray(
                    rng.integers(0, cfg.vocab_size, (fuse, B, S + 1)), np.int32)}
                dt, _state = timed_state(step, state, stacked)
                return record(name, dt / fuse)
            dt, _state = timed_state(step, state, batch)
            return record(name, dt)

        rr.row(name, thunk)

    full_row("full_sgd_f1", optax.sgd(1e-4), 1)
    full_row("full_adamw_f1", optax.adamw(1e-4), 1)
    full_row(f"full_adamw_f{FUSE}", optax.adamw(1e-4), FUSE)
    full_row("full_fused_adamw_f1", None, 1, fused_optimizer=True)
    full_row(f"full_fused_adamw_f{FUSE}", None, FUSE, fused_optimizer=True)
    full_row(f"full_fused_adamw_lossfused_f{FUSE}", None, FUSE,
             fused_optimizer=True, fused_loss=True)

    return rr.finish(B=B, S=S, FUSE=FUSE, n_params=n_params)


if __name__ == "__main__":
    sys.exit(main())
