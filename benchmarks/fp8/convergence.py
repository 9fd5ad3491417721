"""FP8 convergence benchmark — loss-parity of fp8 training vs the bf16 baseline.

The reference's fp8 benchmarks (``/root/reference/benchmarks/fp8/{transformer_engine,
torchao,ms_amp}``) publish no speed numbers; they exist to assert that fp8 training
*converges like the native implementation* across DDP/FSDP/DeepSpeed wrappings. This is the
TPU-native analog: the same llama slice trains under

  1. bf16 mixed precision (baseline),
  2. fp8 current scaling (``use_fp8`` with per-call amax),
  3. fp8 delayed scaling (``FP8RecipeKwargs(amax_history_len>0)`` threaded by the
     Accelerator through ``TrainState.fp8_state``),

on identical data/init/optimizer, and the script reports the final-loss gap. Pass/fail is
relative: fp8 must end within ``--tolerance`` (default 5%) of the bf16 final loss —
the same "matches native convergence" contract the reference CI enforces.

Runs on the 8-device CPU simulator (default, CI-safe) or a real chip (--device tpu).
Prints one JSON line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

# Launched as a script (python benchmarks/fp8/convergence.py): the interpreter puts
# THIS file's directory on sys.path, not the repo root — bootstrap it.
_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--tolerance", type=float, default=0.05)
    p.add_argument("--device", default="cpu", choices=["cpu", "tpu"])
    args = p.parse_args()

    import jax

    from accelerate_tpu.utils.environment import place_compile_cache

    place_compile_cache()
    if args.device == "cpu":
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import llama
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.utils import send_to_device
    from accelerate_tpu.utils.dataclasses import FP8RecipeKwargs

    base_cfg = dataclasses.replace(
        llama.CONFIGS["debug"], attn_impl="xla", remat=False
    )
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, base_cfg.vocab_size, size=(args.steps, args.batch, args.seq + 1))
    tokens = tokens.astype(np.int32)

    def train(use_fp8: bool, recipe=None):
        AcceleratorState._reset_state()
        GradientState._reset_state()
        PartialState._reset_state()
        kwargs = dict(mixed_precision="fp8" if use_fp8 else "bf16")
        if recipe is not None:
            kwargs["kwargs_handlers"] = [recipe]
        acc = Accelerator(**kwargs)
        cfg = dataclasses.replace(base_cfg, use_fp8=use_fp8)
        state = acc.create_train_state(llama.init_params(cfg), optax.adamw(args.lr))
        step = acc.build_train_step(lambda p, b: llama.loss_fn(p, b, cfg))
        losses = []
        for i in range(args.steps):
            batch = send_to_device({"tokens": tokens[i]}, acc.mesh)
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        return losses

    bf16 = train(False)
    fp8_current = train(True)
    fp8_delayed = train(
        True, FP8RecipeKwargs(fp8_format="HYBRID", amax_history_len=16, margin=0, use_delayed_scaling=True)
    )

    def gap(ls):
        return abs(ls[-1] - bf16[-1]) / abs(bf16[-1])

    out = {
        "bench": "fp8_convergence",
        "steps": args.steps,
        "bf16_final_loss": round(bf16[-1], 4),
        "fp8_current_final_loss": round(fp8_current[-1], 4),
        "fp8_delayed_final_loss": round(fp8_delayed[-1], 4),
        "fp8_current_gap": round(gap(fp8_current), 4),
        "fp8_delayed_gap": round(gap(fp8_delayed), 4),
        "tolerance": args.tolerance,
        "pass": gap(fp8_current) < args.tolerance and gap(fp8_delayed) < args.tolerance,
    }
    print(json.dumps(out))
    return 0 if out["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
