"""Seed → inputs. One general generator reads ``traffic/<name>.json``; a new mix is a
new data file. Every seed offers the SAME multiset of work (stratified lengths, a fixed
count of arrivals): the seed sets token ids, the pairing and order of lengths, and the
arrival times. A copy of nothing in the program (``commands/serve_bench.py`` draws
lengths independently per seed, which is what this file exists to avoid).
"""

from __future__ import annotations

import json
import math
import os
from statistics import NormalDist

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(kind: str, name: str, dry: bool = False, root: str = HERE) -> dict:
    """``<root>/<kind>/<name>.json``; a dry run overlays the file's ``dry_run`` sizes."""
    with open(os.path.join(root, kind, f"{name}.json")) as f:
        spec = json.load(f)
    over = spec.pop("dry_run", {})
    return {**spec, **over} if dry else spec


def lengths(dist: dict, n: int) -> np.ndarray:
    """The n quantile mid-points of ``dist``, clipped — the same multiset for any seed."""
    q = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in q])
        v = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        v = dist["min"] + (dist["max"] - dist["min"]) * q
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(int)


def train_batches(spec: dict, vocab: int, seed: int) -> list:
    """A ring of ``ring`` distinct [batch, seq+1] int32 batches (every row differs)."""
    rng = np.random.default_rng([seed, 1])
    return [rng.integers(0, vocab, size=(spec["batch"], spec["seq"] + 1)).astype(np.int32)
            for _ in range(spec["ring"])]


def _requests(spec: dict, n: int, vocab: int, rng, due) -> list:
    """n requests at the ``due`` times. Each run of ``block`` consecutive requests carries
    an equal share of the prompt and of the output lengths — as many of them, and as near
    the same sum as the multiset allows (largest first, each to the lightest block with
    room) — so the load is even along the list for every seed; the seed orders the blocks,
    the lengths inside each block (prompts and outputs apart) and draws the token ids."""
    nb = -(-n // spec.get("block", n))

    def spread_out(sizes):
        strata, room = [[] for _ in range(nb)], -(-n // nb)
        for size in sorted(sizes, reverse=True):
            min((s for s in strata if len(s) < room), key=sum).append(size)
        return np.concatenate([rng.permutation(strata[b]) for b in rng.permutation(nb)])

    prompts = spread_out(lengths(spec["prompt"], n))
    outputs = spread_out(lengths(spec["output"], n))
    return [{"due": float(d), "prompt": rng.integers(0, vocab, size=(int(p),)).astype(np.int32),
             "max_new": int(o)} for d, p, o in zip(due, prompts, outputs)]


def serve_requests(spec: dict, vocab: int, seed: int, seconds: float) -> list:
    """Requests with their due times (seconds from the window's start; warm-in < 0).

    ``serve_open_loop``: N = round(rate × (seconds − quiet_tail_s)) arrivals, a Poisson
    process conditioned on its count (N sorted uniforms), none due in the quiet tail, and
    ``warm_in`` more of the same process before t = 0. The arrival TIMES come from the
    file's ``arrival_seed``, the same for every run seed: a seed changes which request
    comes when, not when requests come. ``serve_backlog``: ``requests`` of them, all due
    before the window (due = −1)."""
    rng = np.random.default_rng([seed, 2])
    if spec["kind"] == "serve_backlog":
        return _requests(spec, spec["requests"], vocab, rng, [-1.0] * spec["requests"])
    span = seconds - spec["quiet_tail_s"]
    n = max(1, round(spec["rate_per_s"] * span))
    warm = spec["warm_in"]
    clock = np.random.default_rng([spec["arrival_seed"], n])
    due_warm = np.sort(-clock.uniform(0, warm / spec["rate_per_s"], warm))
    return (_requests(spec, warm, vocab, rng, due_warm)
            + _requests(spec, n, vocab, rng, np.sort(clock.uniform(0, span, n))))
