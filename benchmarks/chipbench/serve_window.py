"""The serving window: ``ContinuousBatcher.submit`` / ``step()`` driven from ONE thread.

The engine admits only at the start of a ``step()``, so a request submitted at the step
boundary after it fell due is served exactly as one submitted mid-step would be; its
latencies count from the DUE time, and ``loadgen_lag`` reports the wait for the boundary.
``serve_open_loop`` offers arrivals on a clock (warm-in before t = 0); ``serve_backlog``
submits the whole list first and opens the window once the page pool first defers an
admission. Every token's host time is taken in the engine's own ``on_token`` hook.
Everything of the model (the program's config, the seeded weights, the reference and the
comparison) is the family's, ``ctx.family``.
"""

from __future__ import annotations

import gc
import time

import jax.numpy as jnp
import numpy as np

from . import traffic

# what this window calls on the family (run.load_window refuses a family without them)
NEEDS = ("program_config", "gen_params", "serve_reference", "compare_serve")


class Window:
    def __init__(self, ctx):
        self.ctx, self.family = ctx, ctx.family
        self.c, self.spec, self.sv = ctx.config, ctx.traffic, ctx.config["serve"]
        self.requests = traffic.serve_requests(
            self.spec, self.c["vocab_size"], ctx.seed, ctx.seconds)

    def build(self):
        from accelerate_tpu.serving import ContinuousBatcher

        sv, family = self.sv, self.family
        params = family.gen_params(self.c, self.ctx.seed, getattr(jnp, sv["dtype"]))
        return ContinuousBatcher(
            params, family.program_config(self.c), max_slots=sv["max_slots"],
            max_len=sv["max_len"], prompt_bucket=sv["prompt_bucket"], page_size=sv["page_size"],
            kv_pages=sv["kv_pages"], decode_steps=sv["decode_steps"])

    def submit(self, r: dict, now: float):
        r["times"], r["submitted"] = [], now

        def on_token(_tok, ts=r["times"]):
            ts.append(time.perf_counter())
            if len(ts) == 1:                 # a first token closes its request's prefill
                self.prefill_end = ts[0]

        with self.ctx.span("submit"):
            r["req"] = self.engine.submit(r["prompt"], max_new_tokens=r["max_new"],
                                          on_token=on_token)

    def step(self):
        """One ``engine.step()`` with what the per-layer readers need, read from outside."""
        eng = self.engine
        t0 = self.prefill_end = time.perf_counter()
        with self.ctx.span("engine.step"):
            eng.step()
        s = eng.stats()
        now = {k: s.get(k, 0) for k in ("admitted", "decode_steps", "decode_tokens",
                                        "kv_defer_count")}
        rec = {"t0": t0, "t1": time.perf_counter(), "queued": s["queued"],
               "prefill_s": self.prefill_end - t0,
               "page_occupancy": 100.0 * s.get("page_occupancy", 0.0),
               **{k: now[k] - self.counts.get(k, 0) for k in now},
               "lens": [int(eng.positions[i]) for i, q in enumerate(eng.slot_req)
                        if q is not None]}
        self.counts = now
        self.steps.append(rec)
        return rec

    def warm(self):
        """Build the engine and run one two-chunk request to its end: first-chunk
        prefill, chunk append, paged row insert and the multi-step decode all compile
        here. Then the traffic's own warm-in (backlog: until the pool first defers)."""
        self.engine = self.build()
        self.ctx.mark("engine_built")
        self.steps, self.counts = [], {}
        rng = np.random.default_rng([self.ctx.seed, 3])
        warm = {"prompt": rng.integers(0, self.c["vocab_size"],
                                       size=(self.sv["prompt_bucket"] + 1,)).astype(np.int32),
                "max_new": self.sv["decode_steps"] + 2}
        self.submit(warm, time.perf_counter())
        while not warm["req"].done:
            self.step()
        self.ctx.mark("programs_warm")
        self.todo = sorted(self.requests, key=lambda r: r["due"])
        if self.spec["kind"] == "serve_backlog":
            now = time.perf_counter()
            for r in self.todo:
                self.submit(r, now)
            self.todo = []
            full = self.sv["max_slots"]
            while True:
                rec = self.step()
                if rec["kv_defer_count"] or len(rec["lens"]) >= full or not rec["queued"]:
                    break
            self.clock0 = time.perf_counter()
        else:
            self.clock0 = time.perf_counter() - self.todo[0]["due"] + 0.05
            self.drive(until=0.0)
        self.steps = []

    def drive(self, until: float, tracer=None):
        """Submit what is due, step; return at the first step boundary at or after
        ``until`` (seconds on the traffic's clock)."""
        eng = self.engine
        while True:
            now = time.perf_counter()
            t = now - self.clock0
            if t >= until:
                return now
            while self.todo and self.todo[0]["due"] <= t:
                self.submit(self.todo.pop(0), now)
            if eng.queue or any(q is not None for q in eng.slot_req):
                self.step()
            else:
                nxt = self.todo[0]["due"] if self.todo else until
                time.sleep(max(0.0, min(nxt, until) - t))
            if tracer is not None:
                tracer.poll()

    def measure(self, seconds: float, tracer) -> dict:
        tracer.begin()
        if self.spec["kind"] == "serve_backlog":
            self.clock0 = time.perf_counter()
        t_open = self.drive(until=0.0)
        t_close = self.drive(until=seconds, tracer=tracer)
        tracer.end()
        return self.observe(t_open, t_close)

    def observe(self, t_open: float, t_close: float) -> dict:
        window_s = t_close - t_open
        open_loop = self.spec["kind"] == "serve_open_loop"
        due = [r for r in self.requests if "req" in r and (r["due"] >= 0 or not open_loop)]
        worst = 1e3 * self.ctx.seconds
        tpot, ttft, lag, failed = [], [], [], 0
        for r in due:
            ts = [x for x in r["times"] if x <= t_close]
            failed += r["req"].failed is not None    # late is late (the worst sample), not failed
            if open_loop:
                bad = r["req"].failed is not None or len(ts) < 2
                tpot.append(worst if bad else 1e3 * (ts[-1] - ts[0]) / (len(ts) - 1))
                ttft.append(worst if not ts else 1e3 * (ts[0] - self.clock0 - r["due"]))
                lag.append(1e3 * (r["submitted"] - self.clock0 - r["due"]))
        emitted = sum(t_open <= x <= t_close for r in self.requests for x in r.get("times", ()))
        prompt_tokens = sum(len(r["prompt"]) for r in self.requests
                            if r.get("times") and t_open <= r["times"][0] <= t_close)
        steps = [s for s in self.steps if s["t0"] >= t_open and s["t1"] <= t_close + 1e-9]
        cap = self.sv["max_slots"] * self.sv["decode_steps"]
        occ = [100.0 * s["decode_tokens"] / cap for s in steps if s["decode_steps"]]
        prefill_s = sum(s["prefill_s"] for s in steps)
        walls = [s["t1"] - s["t0"] for s in steps]
        e2e = {"serve_tokens_per_s": emitted / window_s}
        if open_loop:
            e2e["tpot_ms_p90"] = float(np.percentile(tpot, 90))
        return {
            "attempted": len(due), "failed": failed, "window_s": window_s, "t0": t_open,
            "end_to_end": e2e,
            "samples": {"tpot_ms": tpot, "ttft_ms": ttft, "loadgen_lag_ms": lag,
                        "decode_occupancy": occ,
                        "page_occupancy": [s["page_occupancy"] for s in steps]},
            "values": {"prefill_wall_share": 100.0 * prefill_s / window_s,
                       "tokens_processed_per_s": (emitted + prompt_tokens) / window_s,
                       "kv_defer_count": sum(s["kv_defer_count"] for s in steps),
                       "queue_emptied": int(not open_loop and any(
                           not s["queued"] for s in steps))},
            "decode_steps": [s for s in steps if s["decode_steps"]], "t_close": t_close,
            # for whoever has to explain a run that reads far off: a host stall shows as one
            # long step, a changed schedule as another count of steps or admissions
            "notes": {"steps": len(steps), "admitted": sum(s["admitted"] for s in steps),
                      "step_ms_p50": 1e3 * float(np.median(walls)) if walls else None,
                      "step_ms_max": 1e3 * max(walls, default=0.0),
                      "emitted": emitted, "window_s": window_s},
        }

    def check(self, obs: dict, control: bool = False) -> tuple:
        """A seeded sample of the requests the window finished, the longest among them:
        the reference runs once over each prompt with its served tokens."""
        done = [r for r in self.requests if "req" in r and r["req"].done
                and r["req"].failed is None and r["times"] and r["times"][-1] <= obs["t_close"]]
        rng = np.random.default_rng([self.ctx.seed, 4])
        done.sort(key=lambda r: -(len(r["prompt"]) + len(r["req"].tokens)))
        n = min(self.spec["check_requests"], max(0, len(done) - 1))
        pick = done[:1] + [done[1 + i] for i in rng.permutation(len(done) - 1)[:n]]
        rows = [(r["prompt"], np.asarray(r["req"].tokens, np.int32)) for r in pick]
        del self.engine
        gc.collect()
        if not rows:
            return {"served_logit_gap": float("inf")}, {}
        width = -(-(self.spec["prompt"]["max"] + self.spec["output"]["max"]) // 512) * 512
        family, args = self.family, (self.c, self.ctx.seed, rows, width, self.spec["output"]["max"])
        logits = family.serve_reference(*args)
        readings = {"tokens_compared": sum(len(t) for _, t in rows)}
        if control:     # the tokens the reference in float8 puts first, at the same positions
            picked = family.serve_reference(*args, fq="fp8").argmax(-1)
            readings["control_fp8.served_logit_gap"] = family.compare_serve(
                rows, logits, picked)["served_logit_gap"]
        return family.compare_serve(rows, logits), readings
