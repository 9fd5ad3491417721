"""The training window: ``Accelerator.create_train_state`` → ``build_train_step``'s
callable, driven as a JAX trainer drives it — two steps in flight, a loss fetched every
``log_every`` steps. Set-up builds ONE compiled step with its state, drives it from the
seed through its first three steps (reading what ``correct`` compares on the way) and
hands that same object to the window. Everything of the model (the program's config and
loss, the seeded weights, the reference and the comparison) is the family's, ``ctx.family``.
"""

from __future__ import annotations

import collections
import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import traffic

# what this window calls on the family (run.load_window refuses a family without them)
NEEDS = ("program_config", "loss", "gen_params", "leaf_norms", "change_norms",
         "train_reference", "compare_train")


class Window:
    def __init__(self, ctx):
        self.ctx, self.family = ctx, ctx.family
        self.c, self.spec, self.hp = ctx.config, ctx.traffic, ctx.config["train"]
        self.batches = traffic.train_batches(self.spec, self.c["vocab_size"], ctx.seed)

    def build(self):
        """The program's train step and its state, from the benchmark's seeded weights."""
        import optax

        from accelerate_tpu import Accelerator
        from accelerate_tpu.parallel import MeshConfig
        from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
        from accelerate_tpu.utils.dataclasses import FullyShardedDataParallelPlugin

        hp, n, family = self.hp, len(self.ctx.devices), self.family
        for singleton in (AcceleratorState, GradientState, PartialState):
            singleton._reset_state()
        acc = Accelerator(
            mixed_precision=hp["mixed_precision"],
            mesh_config=MeshConfig(dp=1, fsdp=n, devices=self.ctx.devices),
            fsdp_plugin=FullyShardedDataParallelPlugin() if n > 1 else None)
        cfg = family.program_config(self.c)
        tx = optax.adamw(hp["lr"], b1=hp["b1"], b2=hp["b2"], eps=hp["eps"],
                         weight_decay=hp["weight_decay"])
        state = acc.create_train_state(
            family.gen_params(self.c, self.ctx.seed, jnp.float32), tx)
        step = acc.build_train_step(lambda p, b: family.loss(p, b, cfg),
                                    max_grad_norm=hp["max_grad_norm"])
        return step, state

    def feed(self, k: int) -> dict:
        return {"tokens": self.batches[k % len(self.batches)]}

    def warm(self):
        """Steps 1–3 through the window's own call and feed; what ``correct`` compares is
        read between them: the first clipped gradient from Adam's first moment after
        step 1, the parameters' change after step 2, and the three losses."""
        from accelerate_tpu.telemetry import fence

        self.fence = fence
        self.step, state = self.build()
        self.ctx.mark("state_built")
        family, losses = self.family, []
        state, m = self.step(state, self.feed(0))
        losses.append(float(fence(m)["loss"]))
        self.ctx.mark("first_step")
        adam = jax.tree_util.tree_leaves(state.opt_state, is_leaf=lambda s: hasattr(s, "mu"))[0]
        grads = {k: v / (1 - self.hp["b1"]) for k, v in family.leaf_norms(adam.mu).items()}
        state, m = self.step(state, self.feed(1))
        losses.append(float(fence(m)["loss"]))
        change = family.change_norms(state.params, self.c, self.ctx.seed)
        state, m = self.step(state, self.feed(2))
        self.state, self.last, self.k = state, m, 3
        self.got = {"losses": losses, "grad_norms": grads, "change_norms": change}

    def measure(self, seconds: float, tracer) -> dict:
        """Dispatch step k+1 before fencing step k; stop at the first fence at or after
        ``seconds``. The rate divides by the time to that fence."""
        span, fence, spec = self.ctx.span, self.fence, self.spec
        pending = collections.deque([self.last])
        state, k = self.state, self.k
        for _ in range(spec["in_flight"] - 1):
            state, m = self.step(state, self.feed(k))
            pending.append(m)
            k += 1
        fence(pending.popleft())                     # the last warm-up step
        self.got["losses"].append(float(self.last["loss"]))
        tracer.begin()
        t0 = last = time.perf_counter()
        step_s, fetched = [], []
        while True:
            with span("dispatch"):
                state, m = self.step(state, self.feed(k))
            pending.append(m)
            k += 1
            with span("fence"):
                done = fence(pending.popleft())
            now = time.perf_counter()
            step_s.append(now - last)
            last = now
            if len(step_s) % spec["log_every"] == 0:
                with span("loss_read"):
                    fetched.append(float(done["loss"]))
            tracer.poll()
            if now - t0 >= seconds:
                break
        tracer.end()
        fence(pending.popleft())                     # in flight at the close; not counted
        self.state = state
        tokens = len(step_s) * spec["batch"] * spec["seq"]
        window_s = last - t0
        rate = tokens / window_s / len(self.ctx.devices)
        bad = sum(not math.isfinite(x) for x in fetched)
        return {
            "attempted": len(step_s), "failed": bad, "window_s": window_s, "t0": t0,
            "end_to_end": {"train_tokens_per_s_per_chip": rate},
            "samples": {"step_ms": [1e3 * s for s in step_s]},
            "values": {"tokens_per_s_per_chip": rate,
                       "steps": len(step_s), "batch": spec["batch"], "seq": spec["seq"]},
            "notes": {"steps": len(step_s), "step_ms_max": 1e3 * max(step_s),
                      "window_s": window_s, "fetched_losses": fetched},
        }

    def check(self, obs: dict, control: bool = False) -> tuple:
        """Free the program's state, then follow the same three batches in the plain
        reference and compare. ``control``: also the reference in float8 and the reference
        over half of each batch, each put in the program's place."""
        del self.state, self.step, self.last
        gc.collect()
        family, args = self.family, (self.c, self.hp, self.batches[:3], self.ctx.seed)
        ref = family.train_reference(*args)
        readings = {}
        if control:
            half = range(self.spec["batch"] // 2)
            for tag, kw in (("control_fp8", {"fq": "fp8"}), ("fault_half_batch", {"rows": half})):
                other = family.compare_train(family.train_reference(*args, **kw), ref)
                readings.update({f"{tag}.{k}": v for k, v in other.items()})
        return family.compare_train(self.got, ref), readings
