"""Warmup manifests: enumerate + pre-compile a config's programs into the cache.

``python -m accelerate_tpu warmup`` drives this. For one (model config, batch
geometry, serving geometry) it builds the exact programs a training run or a
serving replica would compile lazily — train micro/apply (or fused) step, eval
step, one prefill per shape bucket, the chunk-append program, the decode step,
the per-slot row inserts — and pushes each through ``AotCache`` WITHOUT
executing them (``lower().compile()`` + serialize, never dispatch). A job
or replica that starts afterwards deserializes instead of compiling:
cold start stops scaling with program count.

The resulting manifest (``<cache_dir>/warmup_manifest.json`` by default) lists
every program's label, cache key and status — the auditable record of what a
cache directory is warm FOR.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional

import numpy as np

from ..logging import get_logger
from ..utils.dataclasses import CompileCacheConfig

logger = get_logger(__name__)

__all__ = ["build_model_config", "build_drafter", "run_warmup", "write_manifest"]

MANIFEST_SCHEMA = "accelerate_tpu.compile_cache.warmup/v1"
MANIFEST_NAME = "warmup_manifest.json"


def build_model_config(preset: str, seq_len: int):
    """A llama config for ``preset`` (a ``llama.CONFIGS`` key, or ``smoke`` — the
    bench.py CI shape) with ``max_seq`` set for the warmed geometry."""
    import jax.numpy as jnp

    from ..models import llama

    if preset == "smoke":
        cfg = dataclasses.replace(
            llama.CONFIGS["tiny"], vocab_size=512, d_model=128, n_layers=2,
            n_heads=4, n_kv_heads=2, d_ff=256,
        )
    elif preset in llama.CONFIGS:
        cfg = llama.CONFIGS[preset]
    else:
        raise ValueError(
            f"unknown preset {preset!r}; expected 'smoke' or one of "
            f"{sorted(llama.CONFIGS)}"
        )
    if cfg.dtype == jnp.bfloat16 and preset == "smoke":
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    return dataclasses.replace(cfg, max_seq=seq_len)


def build_drafter(spec_draft: Optional[str], target_params, target_cfg):
    """A ``spec_decode.DraftSource`` for one warmup/bench geometry: ``None``/"ngram"
    → the model-free prompt-lookup drafter (no extra programs); ``"half"`` → a
    half-depth copy of the target config with fresh params (vocabulary-compatible by
    construction — the standard CI shape for exercising the draft-model program
    surface without a second checkpoint)."""
    from ..spec_decode import ModelDrafter, NgramDrafter

    if spec_draft in (None, "ngram"):
        return NgramDrafter()
    if spec_draft == "half":
        from ..models import llama

        d_cfg = dataclasses.replace(
            target_cfg, n_layers=max(1, target_cfg.n_layers // 2)
        )
        return ModelDrafter(llama.init_params(d_cfg), d_cfg)
    raise ValueError(f"spec_draft={spec_draft!r}: expected 'ngram' or 'half'")


def run_warmup(
    *,
    preset: str = "smoke",
    batch_size: int = 8,
    seq_len: int = 128,
    fused_steps: int = 1,
    grad_accum: int = 1,
    mixed_precision: Optional[str] = None,
    train: bool = True,
    eval_step: bool = False,
    serve: bool = False,
    max_slots: int = 4,
    max_len: Optional[int] = None,
    max_new_tokens: int = 32,
    spec_k: int = 0,
    spec_draft: Optional[str] = None,
    page_size: int = 0,
    kv_pages: Optional[int] = None,
    prefix_cache: int = 0,
    role: str = "mixed",
    decode_steps: int = 1,
    cache_config: Optional[CompileCacheConfig] = None,
    manifest_path: Optional[str] = None,
    cache=None,
    emit_manifest: bool = True,
) -> dict:
    """Pre-compile the programs for one config into the AOT cache.

    Returns the manifest dict (also written to ``manifest_path`` /
    ``<cache_dir>/warmup_manifest.json``). Uses concrete dummy inputs placed
    through the SAME data paths the real run uses (mesh-sharded batches, engine
    cache layouts), so the fingerprints match what ``Accelerator`` /
    ``ContinuousBatcher`` will look up.

    ``cache`` injects a pre-built ``AotCache`` (the program auditor passes a
    ``LowerOnlyCache`` so the SAME enumeration feeds graftaudit without
    compiling anything); ``emit_manifest=False`` skips the manifest file for
    such in-memory uses. Every program's audit provenance (collective
    inventory, donation effectiveness) is stamped into the manifest under
    ``program_audit`` and emitted as telemetry records when telemetry is on.
    """
    from ..accelerator import Accelerator
    from ..models import llama

    config = cache.config if cache is not None else (
        cache_config or CompileCacheConfig(enabled=True)
    )
    if not config.enabled:
        raise ValueError("warmup needs an enabled CompileCacheConfig")

    if spec_k and not serve:
        raise ValueError(
            "spec_k was given but serve=False: no verify/draft programs would be "
            "warmed and the manifest would silently stamp spec_k=0 — pass "
            "serve=True (--serve) to warm the speculative surface"
        )
    if (page_size or prefix_cache) and not serve:
        raise ValueError(
            "page_size/prefix_cache were given but serve=False: no paged/prefix "
            "serving programs would be warmed — pass serve=True (--serve)"
        )
    if role != "mixed" and not serve:
        raise ValueError(
            f"role={role!r} was given but serve=False: no role-sliced serving "
            "programs would be warmed — pass serve=True (--serve)"
        )
    if decode_steps > 1 and not serve:
        raise ValueError(
            f"decode_steps={decode_steps} was given but serve=False: no multi-"
            "step super-step programs would be warmed — pass serve=True (--serve)"
        )
    cfg = build_model_config(preset, seq_len)
    entries: list = []

    accelerator = Accelerator(
        mixed_precision=mixed_precision,
        gradient_accumulation_steps=grad_accum,
        compile_cache_config=config,
    )
    if cache is not None:
        # Injected cache (audit / tests): every jit the accelerator wraps from
        # here on routes through it instead of the one built from the config.
        accelerator.compile_cache = cache
    else:
        cache = accelerator.compile_cache
    if cache.capture is None:
        cache.capture = []  # arm program capture: the manifest stamps audit provenance
    if not cache.enabled:
        # A warmup whose whole purpose is priming the cache must fail loudly,
        # not exit 0 with an empty manifest.
        raise RuntimeError(
            "warmup cannot populate the compile cache: it is disabled "
            "(CompileCacheConfig.enabled / ACCELERATE_COMPILE_CACHE)"
        )
    params = llama.init_params(cfg)

    eval_params = None
    if train:
        import optax

        state = accelerator.create_train_state(params, optax.adamw(1e-4))
        step = accelerator.build_train_step(
            lambda p, b: llama.loss_fn(p, b, cfg),
            max_grad_norm=1.0,
            fused_steps=fused_steps,
        )
        tokens = np.zeros((batch_size, seq_len + 1), np.int32)
        if fused_steps > 1:
            batches = [{"tokens": tokens} for _ in range(fused_steps)]
            entries.extend(step.warm(state, batches))
        else:
            from ..data_loader import assemble_global_batch

            batch = assemble_global_batch({"tokens": tokens}, accelerator.mesh)
            entries.extend(step.warm(state, batch))
        eval_params = state.params
    if eval_step:
        from ..data_loader import assemble_global_batch

        if eval_params is None:
            # --no-train: prepare params exactly as create_train_state would, so
            # the eval fingerprint matches a real run's state.params.
            eval_params = accelerator.prepare_params(params)
        evaluate = accelerator.build_eval_step(lambda p, b: llama.loss_fn(p, b, cfg))
        batch = assemble_global_batch(
            {"tokens": np.zeros((batch_size, seq_len + 1), np.int32)},
            accelerator.mesh,
        )
        entries.append(evaluate.warm(eval_params, batch))

    if serve:
        from ..serving import ContinuousBatcher

        engine_len = max_len if max_len is not None else seq_len
        # Speculative serving surface: ``spec_k > 0`` adds the fused [B, spec_k+1]
        # verify program and — with ``spec_draft="half"`` — a half-depth draft model's
        # prefill/decode/insert programs. Both ride the same bucket ladder and land in
        # this manifest, so a spec-enabled replica restart compiles nothing.
        drafter = build_drafter(spec_draft, params, cfg) if spec_k else None
        # ``page_size > 0`` warms the PAGED serving surface (block-table decode/
        # verify, dynamic-slot page scatter, prefix gather/copy) — the manifest
        # stamps the page geometry so a cache directory is auditable for which
        # KV layout it is warm FOR.
        # ``role`` warms one DISAGG slice of the surface (docs/
        # disaggregated_serving.md): a decode-role replica's directory holds
        # NO prefill programs at all (handoff import + COW copy + lane-valid
        # setup instead), a prefill-role one swaps decode/verify for the page
        # export gather — the manifest records which slice it is warm FOR.
        # ``decode_steps`` is the depth of the decode scan, warmed in both sample
        # variants (dense or paged per the layout above); above 1 and combined
        # with ``spec_k > 0`` and a resident drafter it ALSO warms the
        # fused speculative super-step pair (``serving.spec_multi[_paged]``) —
        # the manifest's ``spec_fused`` records which geometry that is.
        engine = ContinuousBatcher(
            params, cfg, max_slots=max_slots, max_len=engine_len,
            compile_cache=cache, spec_k=spec_k, drafter=drafter,
            page_size=page_size, kv_pages=kv_pages, prefix_cache=prefix_cache,
            role=role, decode_steps=decode_steps,
        )
        entries.extend(engine.warm_programs(max_new_tokens=max_new_tokens))

    # Per-program audit provenance: the captures recorded at lowering carry the
    # jaxpr + StableHLO (and compiled HLO on misses), so the manifest records
    # what the cached executables actually DO — collective counts/bytes and
    # whether donation aliased — not just that they exist.
    from ..analysis.program.audit import audit_summaries

    summaries = audit_summaries(cache.capture)
    _emit_audit_telemetry(accelerator, summaries)

    manifest = {
        "schema": MANIFEST_SCHEMA,
        "preset": preset,
        "batch_size": batch_size,
        "seq_len": seq_len,
        "fused_steps": fused_steps,
        "grad_accum": grad_accum,
        "mixed_precision": mixed_precision,
        "serve": serve,
        "max_slots": max_slots,
        "max_len": max_len if max_len is not None else seq_len,
        "spec_k": spec_k if serve else 0,
        "spec_draft": (spec_draft or "ngram") if serve and spec_k else None,
        # Fused speculative super-step geometry: True when this cache directory
        # is warm for ``serving.spec_multi[_paged]`` (spec_k > 0, decode_steps
        # > 1, resident drafter) — the program such an engine dispatches.
        "spec_fused": engine._spec_fused() if serve and spec_k else False,
        "page_size": page_size if serve else 0,
        "kv_pages": (
            engine.block_mgr.num_pages if serve and page_size else None
        ),
        "prefix_cache": prefix_cache if serve else 0,
        "role": role if serve else "mixed",
        "decode_steps": decode_steps if serve else 1,
        "cache_dir": cache.cache_dir,
        "cache_stats": cache.stats(),
        "programs": [e for e in entries if e],
        "program_audit": summaries,
    }
    if emit_manifest:
        write_manifest(
            manifest, manifest_path or os.path.join(cache.cache_dir, MANIFEST_NAME)
        )
    return manifest


def _emit_audit_telemetry(accelerator, summaries: list) -> None:
    """Route per-program audit summaries into telemetry (bench rows diff comms
    across PRs from these records). No-op when telemetry is off."""
    telemetry = getattr(accelerator, "telemetry", None)
    if telemetry is None or not getattr(telemetry, "enabled", False):
        return
    from ..telemetry.schemas import AUDIT_PROGRAM_SCHEMA

    for s in summaries:
        telemetry.emit({
            "schema": AUDIT_PROGRAM_SCHEMA,
            "label": s["label"],
            "collectives": s["collectives"],
            "donation": s["donation"],
            "memory": s["memory"],
        })


def write_manifest(manifest: dict, path: str) -> None:
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(manifest, f, indent=2)
    logger.info("warmup manifest written to %s (%d programs)",
                path, len(manifest["programs"]))

