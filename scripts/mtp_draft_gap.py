"""The prediction module's drafts against the plain reference module, at a benchmark
configuration's sizes, on the chip:

    python3 scripts/mtp_draft_gap.py --seed <n> [--workload serve_deepseekv32_mtp_reason]
        [--requests 16] [--prompt 3072] [--output 256] [--cpu-dry-run]

Serves ``--requests`` seeded prompts of ``--prompt`` tokens, ``--output`` tokens each,
through ``ContinuousBatcher`` with the cell's engine settings and the module drafting, and
reads every running lane's next draft after each ``step()``. Then the configuration's
family computes the reference module's logits at the row each draft came from (row ``i``
reads ``h_i`` and the token at ``i + 1``: ``module_reference``), in float32 and, as the
control, with every product's operands in float8; each draft's gap is by how much its
reference logit lies below the reference's best. Prints one JSON line: the number of
drafts (and of those whose reference row is not finite, left out), the gap's maximum, 90th
percentile and mean, and the share of drafts that are the reference's first choice — for
the served drafts and for the control's. ``--cpu-dry-run``
walks it at the configuration's dry-run sizes on the CPU (a check of the script).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def gaps(ref, drafts) -> dict:
    """``ref`` [n, V] float32 reference logits at the drafts' rows, ``drafts`` [n]; a row the
    reference did not give finite logits for is counted under ``not_finite`` and left out."""
    g = ref.max(-1) - ref[np.arange(len(drafts)), drafts]
    ok = np.isfinite(g)
    g = g[ok]
    return {"drafts": int(len(ok)), "not_finite": int((~ok).sum()), "gap_max": float(g.max()),
            "gap_p90": float(np.quantile(g, 0.9)), "gap_mean": float(g.mean()),
            "first_choice_share": float((g == 0).mean())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="serve_deepseekv32_mtp_reason")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt", type=int, default=3072)
    ap.add_argument("--output", type=int, default=256)
    ap.add_argument("--cpu-dry-run", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks.chipbench import run

    _, _, c, _, where = run.load_cell(args.workload, args.cpu_dry_run)
    family = run.load_family(c["model_type"], where)
    import jax
    import jax.numpy as jnp

    if args.cpu_dry_run:
        jax.config.update("jax_platforms", "cpu")
    elif jax.devices()[0].platform != "tpu":
        print("mtp_draft_gap: needs a TPU (or --cpu-dry-run)", file=sys.stderr)
        return 3
    from accelerate_tpu.serving import ContinuousBatcher

    sv = c["serve"]
    eng = ContinuousBatcher(
        family.gen_params(c, args.seed, getattr(jnp, sv["dtype"])), family.program_config(c),
        max_slots=sv["max_slots"], max_len=sv["max_len"], prompt_bucket=sv["prompt_bucket"],
        page_size=sv["page_size"], kv_pages=sv["kv_pages"], decode_steps=sv["decode_steps"])
    rng = np.random.default_rng([args.seed & 0x7FFFFFFF, 9])
    prompts = [rng.integers(0, c["vocab_size"], size=(args.prompt,)).astype(np.int32)
               for _ in range(args.requests)]
    reqs = [eng.submit(p, max_new_tokens=args.output) for p in prompts]
    index = {r.uid: i for i, r in enumerate(reqs)}
    seen = []                   # (request, the draft's row, the draft)
    while any(not r.done for r in reqs):
        eng.step()
        drafts = np.asarray(eng.cache["draft"])
        for slot, r in enumerate(eng.slot_req):
            if r is not None:
                seen.append((index[r.uid], len(r.prompt) + len(r.tokens) - 2, int(drafts[slot])))
    accept = eng.stats()["spec_accept_rate"]
    del eng
    gc.collect()
    rows = [(p, np.asarray(r.tokens, np.int32)) for p, r in zip(prompts, reqs)]
    at = [[a for i, a, _ in seen if i == k] for k in range(len(rows))]
    most = max(len(a) for a in at)
    at = [np.asarray(a + a[-1:] * (most - len(a)), np.int32) for a in at]
    width = -(-(args.prompt + args.output) // 512) * 512
    out = {"seed": args.seed, "spec_accept_rate": accept}
    ref = family.module_reference(c, args.seed, rows, width, at)
    for name, fq in (("served", None), ("control_fp8", "fp8")):
        lg = ref if fq is None else family.module_reference(c, args.seed, rows, width, at, fq=fq)
        pos = {k: 0 for k in range(len(rows))}
        chosen, rows_ref = [], []
        for k, _, d in seen:
            j = pos[k]
            pos[k] += 1
            rows_ref.append(ref[k, j])
            chosen.append(d if fq is None else int(lg[k, j].argmax()))
        out[name] = gaps(np.stack(rows_ref), np.asarray(chosen))
    out["device"] = {"platform": jax.devices()[0].platform,
                     "kind": jax.devices()[0].device_kind}
    if args.cpu_dry_run:
        out["dry_run"] = True
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
