"""``accelerate-tpu warmup`` — pre-compile a config's programs into the AOT cache.

Enumerates the (train step, eval step, prefill buckets, decode, row-insert)
programs for a model/serving config and pushes each through
``compile_cache.AotCache`` without executing anything, writing a warmup
manifest beside the cache entries. A training job or serving replica started
afterwards deserializes executables instead of paying XLA compile
(docs/compile_cache.md).
"""

from __future__ import annotations

import argparse

__all__ = ["warmup_command", "warmup_command_parser"]


def warmup_command_parser(subparsers=None) -> argparse.ArgumentParser:
    description = (
        "Pre-compile the train/eval/serving executables for a config into the "
        "persistent AOT compile cache, and write a warmup manifest."
    )
    if subparsers is not None:
        parser = subparsers.add_parser("warmup", description=description)
    else:
        parser = argparse.ArgumentParser("accelerate-tpu warmup", description=description)
    parser.add_argument(
        "--preset", default="smoke",
        help="model preset: 'smoke' (tiny CI shape) or a models.llama.CONFIGS key",
    )
    parser.add_argument("--batch-size", type=int, default=8, help="global train batch size")
    parser.add_argument("--seq-len", type=int, default=128, help="train sequence length")
    parser.add_argument("--fused-steps", type=int, default=1,
                        help="build_train_step(fused_steps=N) program shape")
    parser.add_argument("--grad-accum", type=int, default=1,
                        help="gradient accumulation steps (warms micro+apply when > 1)")
    parser.add_argument("--mixed-precision", default=None,
                        choices=(None, "no", "bf16", "fp16", "fp8"),
                        help="Accelerator mixed_precision for the warmed programs")
    parser.add_argument("--no-train", action="store_true",
                        help="skip the train-step programs")
    parser.add_argument("--eval", action="store_true", dest="eval_step",
                        help="also warm the eval-step program")
    parser.add_argument("--serve", action="store_true",
                        help="warm the serving programs (prefill buckets + decode)")
    parser.add_argument("--max-slots", type=int, default=4, help="serving decode lanes")
    parser.add_argument("--max-len", type=int, default=None,
                        help="serving cache length (default: --seq-len)")
    parser.add_argument("--max-new-tokens", type=int, default=32,
                        help="serving generation budget used for bucket validation")
    parser.add_argument("--spec-k", type=int, default=0,
                        help="speculative proposals per slot per step (adds the fused "
                             "[B, k+1] verify program; combined with --decode-steps N "
                             "and an ngram drafter also the fused speculative "
                             "super-step pair serving.spec_multi[_paged]; 0 = plain "
                             "decode only)")
    parser.add_argument("--spec-draft", default=None, choices=("ngram", "half"),
                        help="draft source for the speculative surface: 'ngram' "
                             "(model-free, default) or 'half' (half-depth draft model "
                             "— also warms its prefill/decode/insert programs)")
    parser.add_argument("--page-size", type=int, default=0,
                        help="paged KV cache page size (tokens per page); > 0 warms "
                             "the paged serving surface — block-table decode/verify, "
                             "page scatter, prefix gather/copy — and stamps the page "
                             "geometry into the manifest (0 = dense layout)")
    parser.add_argument("--kv-pages", type=int, default=None,
                        help="page-pool size for --page-size (default: dense-"
                             "equivalent capacity, max_slots × pages-per-row)")
    parser.add_argument("--decode-steps", type=int, default=1,
                        help="multi-step decode depth: > 1 warms the fused N-step "
                             "super-step pair (both sample variants; dense or paged "
                             "per --page-size) and stamps the depth into the "
                             "manifest; with --spec-k and an ngram drafter it also "
                             "warms the fused speculative super-step pair and stamps "
                             "spec_fused (1 = classic one-token decode)")
    parser.add_argument("--prefix-cache", type=int, default=0,
                        help="prefix-cache capacity: > 0 warms the prefix-serving "
                             "programs (right-aligned prefill/chunk pair; with "
                             "--page-size also the page gather/copy programs)")
    parser.add_argument("--cache-dir", default=None,
                        help="AOT cache directory (default: ACCELERATE_COMPILE_CACHE_DIR "
                             "or ~/.cache/accelerate_tpu/aot_cache)")
    parser.add_argument("--buckets", default=None,
                        help="comma-separated prefill bucket ladder, e.g. 64,128,256")
    parser.add_argument("--manifest", default=None,
                        help="manifest output path (default: <cache_dir>/warmup_manifest.json)")
    if subparsers is not None:
        parser.set_defaults(func=warmup_command)
    return parser


def warmup_command(args) -> int:
    import json

    from ..compile_cache import CompileCacheConfig, run_warmup

    buckets = None
    if args.buckets:
        buckets = tuple(int(b) for b in args.buckets.split(","))
    config = CompileCacheConfig(
        enabled=True, cache_dir=args.cache_dir, serving_buckets=buckets
    )
    manifest = run_warmup(
        preset=args.preset,
        batch_size=args.batch_size,
        seq_len=args.seq_len,
        fused_steps=args.fused_steps,
        grad_accum=args.grad_accum,
        mixed_precision=args.mixed_precision,
        train=not args.no_train,
        eval_step=args.eval_step,
        serve=args.serve,
        max_slots=args.max_slots,
        max_len=args.max_len,
        max_new_tokens=args.max_new_tokens,
        spec_k=args.spec_k,
        spec_draft=args.spec_draft,
        page_size=args.page_size,
        kv_pages=args.kv_pages,
        prefix_cache=args.prefix_cache,
        decode_steps=args.decode_steps,
        cache_config=config,
        manifest_path=args.manifest,
    )
    stats = manifest["cache_stats"]
    print(json.dumps({
        "programs": len(manifest["programs"]),
        "compiled": stats["misses"],
        "already_cached": stats["hits"],
        "compile_s": stats["compile_s"],
        "cache_dir": manifest["cache_dir"],
    }))
    return 0
