"""Big-model inference benchmark — TPU-native counterpart of the reference's headline table.

The reference's only published numbers are big-model-inference baselines
(``/root/reference/benchmarks/big_model_inference/README.md:25-37``): load time, s/token and
memory for GPT-J-6B, GPT-NeoX-20B, T0pp and OPT-30B across GPU/CPU/disk placements. This
script produces the same table on TPU through this framework's L6 stack:

- fits in HBM        → ``jax.device_put`` + one compiled prefill/decode-scan (``gpt.generate``)
- exceeds HBM        → ``cpu_offload``/``disk_offload`` + ``generate_streamed`` (per-block
                       double-buffered H2D streaming — the AlignDevicesHook analog)

Weights are randomly initialized at the real shapes: generation timing is shape-dependent,
not value-dependent, and this environment has no network egress for checkpoints. To measure a
real checkpoint instead, pass ``--checkpoint <safetensors dir>`` (loads through
``load_checkpoint_and_dispatch``; load time then includes the shard-streaming read).

Examples:
    python inference_tpu.py gptj-6b --dtype bf16
    python inference_tpu.py gpt-neox-20b --offload host
    python inference_tpu.py opt-30b --offload disk
    python inference_tpu.py --smoke          # tiny shapes, CPU-safe (CI)

Prints one JSON line per run; ``--markdown`` appends a table row to
``chiprun_out/results.md`` (git-ignored: result files are not committed).
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time

# Launched as a script from the repo root: the interpreter puts THIS file's directory
# on sys.path, not the repo root — bootstrap it or `import accelerate_tpu` fails.
_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import numpy as np

FAMILIES = {
    "gptj-6b": "gpt",
    "gpt-neox-20b": "gpt",
    "opt-30b": "gpt",
    "gpt2-xl": "gpt",
    "t0pp": "t5",
    "llama3-8b": "llama",
    "tiny": "gpt",
}


def device_mem_gb() -> float:
    import jax

    try:
        stats = jax.local_devices()[0].memory_stats() or {}
        return stats.get("bytes_in_use", stats.get("bytes_in_use_total", 0)) / 2**30
    except Exception:
        return float("nan")


def host_rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20  # KB → GB (linux)


def hbm_limit_gb() -> float:
    import jax

    try:
        stats = jax.local_devices()[0].memory_stats() or {}
        if "bytes_limit" in stats:
            return stats["bytes_limit"] / 2**30
    except Exception:
        pass
    return 16.0  # v5e


def _numpy_random_init(mod, cfg, dtype):
    """init_params-shaped pytree of NUMPY leaves filled by numpy's PCG64.

    jax.random on a single host core is the hidden load-time sink at these scales:
    generating 6B threefry normals on one CPU takes minutes. The serving metric (s/token) is invariant to the weight VALUES, only the
    shapes/dtypes matter; keep the same safe magnitudes init_params uses — norm
    'scale'-like leaves = 1, biases = 0, matrices = N(0, 1/sqrt(fan_in)), embeddings
    = N(0, 0.02) — so random-weight forwards stay finite through deep stacks.

    The leaves are numpy (ml_dtypes bf16), NOT jax arrays: a ``jnp`` materialization
    would put every weight on the default device first. ``DispatchedParams.from_tree``
    stores host placements via ``np.asarray`` (zero-copy for numpy) and
    ``jax.device_put`` accepts numpy bf16 directly, so nothing downstream needs
    jax-array leaves."""
    import jax
    import jax.numpy as jnp

    abstract = jax.eval_shape(lambda: mod.init_params(cfg))
    rng = np.random.default_rng(0)
    np_out = np.dtype(dtype)  # jnp.bfloat16 -> ml_dtypes.bfloat16

    def fill(path, leaf):
        name = "/".join(
            str(getattr(k, "key", getattr(k, "idx", k))) for k in path
        ).lower()
        shape, ld = leaf.shape, leaf.dtype
        if not jnp.issubdtype(ld, jnp.floating):
            return np.zeros(shape, np.dtype(ld))
        if "scale" in name.rsplit("/", 1)[-1]:
            return np.ones(shape, np_out)
        if len(shape) <= 1 or name.rsplit("/", 1)[-1].startswith(("b_", "bias")):
            return np.zeros(shape, np_out)
        if any(k in name for k in ("embed", "wte", "wpe", "shared", "rel_bias")):
            std = 0.02
        else:
            std = 1.0 / float(np.sqrt(shape[-2] if len(shape) >= 2 else shape[0]))
        a = rng.standard_normal(size=shape, dtype=np.float32)
        a *= std
        return a.astype(np_out, copy=False)

    return jax.tree_util.tree_map_with_path(fill, abstract)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("model", nargs="?", default="gptj-6b", choices=sorted(FAMILIES))
    p.add_argument("--dtype", default="bf16", choices=["bf16", "f32"])
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--prompt-len", type=int, default=64)
    p.add_argument("--new-tokens", type=int, default=16)
    p.add_argument("--offload", default="auto", choices=["auto", "none", "host", "disk"])
    p.add_argument("--offload-dir", default="/tmp/accel_tpu_offload")
    p.add_argument("--checkpoint", default=None, help="safetensors dir (else random init)")
    p.add_argument("--init", default="numpy", choices=["numpy", "model"],
                   help="random-init generator: 'numpy' (fast PCG64 host fill; s/token-"
                        "invariant) or 'model' (the family's jax init_params — ~12 min "
                        "of single-core threefry at 6B)")
    p.add_argument("--smoke", action="store_true", help="tiny shapes (CI / CPU)")
    p.add_argument("--kv-quant", action="store_true",
                   help="int8 KV cache (half the decode cache bytes; in-HBM path only)")
    p.add_argument("--markdown", action="store_true",
                   help="append a row to chiprun_out/results.md (git-ignored)")
    args = p.parse_args()

    import jax

    from accelerate_tpu.utils.environment import place_compile_cache

    place_compile_cache()
    if args.smoke:
        # CI/CPU. NB: uses the module-level ``import os`` — a local import here would
        # shadow it for the WHOLE function and break the branches below.
        os.environ["JAX_PLATFORMS"] = "cpu"
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from accelerate_tpu.big_modeling import cpu_offload, disk_offload
    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.models import gpt, llama, t5

    family = FAMILIES[args.model]
    model = "tiny" if args.smoke else args.model  # every family ships a "tiny" config
    mod = {"gpt": gpt, "t5": t5, "llama": llama}[family]
    import dataclasses

    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    cfg = dataclasses.replace(mod.CONFIGS[model], dtype=dtype)
    if args.kv_quant:
        if family == "t5":
            raise SystemExit("--kv-quant applies to the decoder families (gpt/llama)")
        cfg = dataclasses.replace(cfg, kv_quant=True)
    n_params = mod.num_params(cfg)
    bytes_per = 2 if args.dtype == "bf16" else 4
    param_gb = n_params * bytes_per / 2**30

    # Placement decision (the reference's device_map="auto" analog at whole-model scale).
    offload = args.offload
    if offload == "auto":
        offload = "none" if param_gb < 0.75 * hbm_limit_gb() else "host"

    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(args.batch, args.prompt_len)), jnp.int32
    )
    gen = GenerationConfig(max_new_tokens=args.new_tokens, temperature=0.0)

    # ---- load: init at shape (cast to target dtype), then place --------------------------
    t0 = time.perf_counter()
    if args.checkpoint:
        from accelerate_tpu.big_modeling import load_checkpoint_and_dispatch

        abstract = jax.eval_shape(lambda: mod.init_params(cfg))
        device_map = "auto" if offload == "none" else (
            {"": "cpu"} if offload == "host" else {"": "disk"}
        )
        dispatched = load_checkpoint_and_dispatch(
            abstract, args.checkpoint, device_map=device_map,
            offload_dir=args.offload_dir, dtype=dtype,
        )
        # In-HBM placement decodes through the in-memory generate path: materialize the
        # whole tree on the chip (fetch("") = full pytree on the main device).
        params = dispatched.fetch("") if offload == "none" else None
    else:
        if args.init == "model":
            with jax.default_device(jax.devices("cpu")[0]):
                params = jax.tree.map(
                    lambda x: x.astype(dtype) if x.dtype == jnp.float32 else x,
                    mod.init_params(cfg),
                )
        else:
            # numpy leaves on purpose — see _numpy_random_init.
            params = _numpy_random_init(mod, cfg, dtype)
        if offload == "none":
            # Fence the transfer: an unfenced multi-GB H2D would land inside the first
            # generate call — load_s must own it, not first_call_s.
            params = jax.block_until_ready(jax.device_put(params, jax.devices()[0]))
            dispatched = None
        elif offload == "host":
            dispatched = cpu_offload(params)
            params = None
        else:
            dispatched = disk_offload(params, args.offload_dir)
            params = None
    load_s = time.perf_counter() - t0

    # ---- generate ------------------------------------------------------------------------
    # In-HBM: one compiled program — run twice, first call absorbs compile, second is the
    # steady-state measurement (cheap: no weight traffic). Streamed: every pass re-streams
    # the WHOLE model from the host, so a second full run doubles a 40-60 GB/pass
    # workload for nothing — instead collect per-pass wall times from ONE run and take the
    # tail decode passes (drop the prefill and the compile-laden first decode).
    pass_times: list = []

    def run(collect: bool = False):
        pt = pass_times if collect else None
        if family == "t5":
            # seq2seq: the "prompt" is the encoder input; decode greedily.
            if offload == "none":
                dec = mod.generate(params, prompt, cfg, max_new_tokens=args.new_tokens)
            else:
                dec = mod.generate_streamed(
                    dispatched, prompt, cfg, max_new_tokens=args.new_tokens,
                    pass_times=pt,
                )
            out = np.asarray(dec)
            # greedy seq2seq may stop at EOS before new_tokens; pad for the shape assert
            if out.shape[1] < args.new_tokens:
                out = np.pad(out, ((0, 0), (0, args.new_tokens - out.shape[1])))
            return out
        if offload == "none":
            return np.asarray(mod.generate(params, prompt, cfg, gen))
        return np.asarray(mod.generate_streamed(dispatched, prompt, cfg, gen, pass_times=pt))

    timed_passes = None  # None = in-HBM two-run protocol (see row field)
    if offload != "none" and args.new_tokens < 2:
        raise SystemExit(
            "--new-tokens must be >= 2 for streamed placements: s/token comes from the "
            "decode-pass tail of one run, and a single token leaves no decode pass to time"
        )
    if offload == "none":
        t0 = time.perf_counter()
        out = run()
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = run()
        steady_s = time.perf_counter() - t0
        s_per_token = steady_s / args.new_tokens
    else:
        t0 = time.perf_counter()
        out = run(collect=True)
        first_s = time.perf_counter() - t0
        # pass_times[0] = prefill, [1] = first decode (carries remaining compiles).
        decode_tail = pass_times[2:] if len(pass_times) > 2 else pass_times[1:]
        timed_passes = len(decode_tail)
        s_per_token = sum(decode_tail) / max(timed_passes, 1)
    assert out.shape == (args.batch, args.new_tokens)
    row = {
        "model": model,
        "family": family,
        "params_b": round(n_params / 1e9, 2),
        "dtype": args.dtype,
        "offload": offload,
        "kv_quant": bool(args.kv_quant),
        "load_s": round(load_s, 2),
        "s_per_token": round(s_per_token, 4),
        "first_call_s": round(first_s, 2),
        "batch": args.batch,
        "prompt_len": args.prompt_len,
        "new_tokens": args.new_tokens,
        "timed_passes": timed_passes,  # None = in-HBM two-run protocol
        "hbm_in_use_gb": round(device_mem_gb(), 2),
        "host_rss_gb": round(host_rss_gb(), 2),
        "device": str(getattr(jax.devices()[0], "device_kind", "cpu")),
    }
    print(json.dumps(row))
    if args.markdown:
        import pathlib

        path = pathlib.Path(_REPO) / "chiprun_out" / "results.md"
        path.parent.mkdir(exist_ok=True)
        new = not path.exists()
        with open(path, "a") as f:
            if new:
                f.write("| Model | dtype | Placement | Load | s/token | HBM | Host RSS |\n")
                f.write("|---|---|---|---|---|---|---|\n")
            label = model + ("-kvq" if args.kv_quant else "")
            f.write(
                f"| {label} | {args.dtype} | {offload} | {row['load_s']}s "
                f"| {row['s_per_token']}s | {row['hbm_in_use_gb']}GB "
                f"| {row['host_rss_gb']}GB |\n"
            )
    return 0


if __name__ == "__main__":
    sys.exit(main())
