"""Generate the package API reference (docs/api/*.md) from docstrings.

Counterpart of the reference's hand-maintained ``docs/source/package_reference/`` tree —
here it is generated, so it cannot drift from the code. Run from the repo root:

    python docs/gen_api.py

Stdlib-only; imports the package on the CPU backend.
"""

from __future__ import annotations

import importlib
import inspect
import os
import re
import sys
import textwrap

# Generating docs must never claim a chip: pin the CPU backend before jax is imported.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
OUT = os.path.join(REPO, "docs", "api")

# (module, page title) — one page per module, grouped like the reference's tree.
MODULES = [
    ("accelerate_tpu.accelerator", "Accelerator"),
    ("accelerate_tpu.state", "Process state"),
    ("accelerate_tpu.data_loader", "Data loading"),
    ("accelerate_tpu.optimizer", "Optimizer wrapper"),
    ("accelerate_tpu.scheduler", "Scheduler wrapper"),
    ("accelerate_tpu.big_modeling", "Big-model inference"),
    ("accelerate_tpu.generation", "Generation"),
    ("accelerate_tpu.serving", "Serving engine"),
    ("accelerate_tpu.spec_decode", "Speculative-decoding draft sources"),
    ("accelerate_tpu.serving_gateway.gateway", "Serving gateway"),
    ("accelerate_tpu.serving_gateway.fleet", "Fleet router (multi-replica serving)"),
    ("accelerate_tpu.serving_gateway.disagg", "Disaggregated prefill/decode router"),
    ("accelerate_tpu.serving_gateway.autoscaler", "Autoscaler (closed-loop fleet sizing)"),
    ("accelerate_tpu.serving_gateway.policies", "Gateway scheduling policies"),
    ("accelerate_tpu.inference", "Pipeline inference"),
    ("accelerate_tpu.checkpointing", "Checkpointing"),
    ("accelerate_tpu.tracking", "Experiment trackers"),
    ("accelerate_tpu.logging", "Logging"),
    ("accelerate_tpu.launchers", "Function launchers"),
    ("accelerate_tpu.elastic", "Elastic supervision"),
    ("accelerate_tpu.local_sgd", "Local SGD"),
    ("accelerate_tpu.interop", "HF checkpoint interop"),
    ("accelerate_tpu.parallel.mesh", "Device mesh"),
    ("accelerate_tpu.parallel.fsdp", "FSDP / ZeRO sharding"),
    ("accelerate_tpu.parallel.tp", "Tensor parallelism"),
    ("accelerate_tpu.parallel.pp", "Pipeline parallelism"),
    ("accelerate_tpu.parallel.mpmd", "MPMD multi-slice pipeline training"),
    ("accelerate_tpu.parallel.sequence", "Sequence parallelism"),
    ("accelerate_tpu.paged_kv", "Paged KV block manager"),
    ("accelerate_tpu.ops.flash_attention", "Flash attention"),
    ("accelerate_tpu.ops.paged_attention", "Paged attention"),
    ("accelerate_tpu.ops.mla_attention", "Latent (MLA) paged attention"),
    ("accelerate_tpu.ops.sparse_attention", "Sparse-attention indexer over paged index keys"),
    ("accelerate_tpu.ops.ring_attention", "Ring attention"),
    ("accelerate_tpu.ops.moe", "Mixture of experts"),
    ("accelerate_tpu.ops.fp8", "FP8"),
    ("accelerate_tpu.ops.fused_optim", "Fused optimizers"),
    ("accelerate_tpu.ops.fused_xent", "Fused cross-entropy"),
    ("accelerate_tpu.ops.quantization", "Quantization"),
    ("accelerate_tpu.ops.packing", "Sample packing"),
    ("accelerate_tpu.lm_dataset", "Indexed LM dataset"),
    ("accelerate_tpu.ops.collectives", "Collective ops"),
    ("accelerate_tpu.utils.dataclasses", "Plugins & kwargs handlers"),
    ("accelerate_tpu.utils.operations", "Pytree operations"),
    ("accelerate_tpu.utils.modeling", "Model surgery"),
    ("accelerate_tpu.utils.offload", "Disk offload"),
    ("accelerate_tpu.utils.memory", "Memory utilities"),
    ("accelerate_tpu.utils.random", "RNG control"),
    ("accelerate_tpu.utils.jax_compat", "JAX API bindings"),
    ("accelerate_tpu.analysis.engine", "Static analysis (graftlint) engine"),
    ("accelerate_tpu.analysis.baseline", "Static analysis ratcheting baseline"),
    ("accelerate_tpu.analysis.flow", "Interprocedural dataflow tier (graftflow)"),
    ("accelerate_tpu.analysis.flow.callgraph", "graftflow: module call graph"),
    ("accelerate_tpu.analysis.flow.cfg", "graftflow: CFGs with exception edges"),
    ("accelerate_tpu.analysis.flow.absint", "graftflow: worklist abstract interpreter"),
    ("accelerate_tpu.analysis.flow.clock_domain", "graftflow: clock-domain rule pack"),
    ("accelerate_tpu.analysis.flow.ownership", "graftflow: page-ownership rule pack"),
    ("accelerate_tpu.analysis.flow.key_schedule", "graftflow: key-schedule rule pack"),
    ("accelerate_tpu.analysis.program.capture", "Program audit: lowering capture"),
    ("accelerate_tpu.analysis.program.lowering", "Program audit: lower-only enumeration"),
    ("accelerate_tpu.analysis.program.rules", "Program audit rules (graftaudit)"),
    ("accelerate_tpu.analysis.program.inventory", "Program audit: collective inventory"),
    ("accelerate_tpu.analysis.program.suppressions", "Program audit suppressions"),
    ("accelerate_tpu.analysis.program.audit", "Program audit driver"),
    ("accelerate_tpu.analysis.program.memory", "Memory/comms estimator (graftmem)"),
    ("accelerate_tpu.compile_cache.cache", "AOT compile cache"),
    ("accelerate_tpu.compile_cache.fingerprint", "Compile-cache fingerprints"),
    ("accelerate_tpu.compile_cache.buckets", "Serving shape buckets"),
    ("accelerate_tpu.compile_cache.warmup", "Warmup manifests"),
    ("accelerate_tpu.telemetry.core", "Telemetry pipeline"),
    ("accelerate_tpu.telemetry.clocks", "Clock-domain resolution protocol"),
    ("accelerate_tpu.telemetry.timing", "Fenced step timing"),
    ("accelerate_tpu.telemetry.steady", "Steady-state detection"),
    ("accelerate_tpu.telemetry.compile_monitor", "Compile-event counters"),
    ("accelerate_tpu.telemetry.derived", "Derived throughput rates"),
    ("accelerate_tpu.telemetry.profiler", "Scheduled profiler windows"),
    ("accelerate_tpu.telemetry.slo", "SLO summaries and record schemas"),
    ("accelerate_tpu.telemetry.schemas", "Telemetry schema registry"),
    ("accelerate_tpu.telemetry.tracing", "Request-scoped tracing"),
    ("accelerate_tpu.telemetry.metrics", "Live metrics plane & metric registry"),
    ("accelerate_tpu.telemetry.alerts", "Alert rules & burn-rate engine"),
    ("accelerate_tpu.telemetry.exporter", "Prometheus exporter"),
    ("accelerate_tpu.telemetry.provenance", "Artifact provenance"),
    ("accelerate_tpu.serving_gateway.workload", "Workload traces & replay"),
    ("accelerate_tpu.commands.trace_report", "Trace report CLI"),
    ("accelerate_tpu.commands.metrics_dump", "Metrics dump CLI"),
    ("accelerate_tpu.resilience.faults", "Fault injection & recovery primitives"),
    ("accelerate_tpu.commands.chaos_train", "Elastic training chaos bench (chaos-train)"),
    ("accelerate_tpu.models.llama", "Llama family"),
    ("accelerate_tpu.models.deepseek", "DeepSeek family (latent attention, routed experts)"),
    ("accelerate_tpu.models.dots3", "dots3 family (full and sliding latent layers, sparse selection)"),
    ("accelerate_tpu.models.keye", "Keye-VL-2.0 language model (grouped-query layers under a sparse selection, 128 experts)"),
    ("accelerate_tpu.models.lora", "LoRA fine-tuning"),
    ("accelerate_tpu.models.gpt", "GPT family"),
    ("accelerate_tpu.models.t5", "T5 family"),
]


def _sig(obj) -> str:
    try:
        sig = str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"
    # Default values whose repr embeds a memory address are not reproducible across runs,
    # and ones that embed the checkout's own path are not reproducible across checkouts
    # (the drift gate in tests/test_docs.py runs wherever the repo was cloned).
    return re.sub(r" at 0x[0-9a-f]+", "", sig).replace(REPO, "<repo>")


def _doc(obj, full: bool = False) -> str:
    doc = inspect.getdoc(obj) or ""
    if not full:
        doc = doc.split("\n\n", 1)[0]
    return doc.strip()


def _public_members(mod):
    names = getattr(mod, "__all__", None)
    if names is None:
        names = [n for n in vars(mod) if not n.startswith("_")]
    out = []
    for n in names:
        obj = getattr(mod, n, None)
        if obj is None or inspect.ismodule(obj):
            continue
        # Only objects defined in this module (skip re-exports / imports).
        if getattr(obj, "__module__", mod.__name__) != mod.__name__:
            continue
        if inspect.isclass(obj) or inspect.isfunction(obj):
            out.append((n, obj))
    return out


def _render_class(name: str, cls) -> list[str]:
    lines = [f"### `class {name}{_sig(cls)}`", ""]
    doc = _doc(cls, full=True)
    if doc:
        lines += [doc, ""]
    for mname, meth in sorted(vars(cls).items()):
        if mname.startswith("_"):
            continue
        if isinstance(meth, property):
            d = _doc(meth.fget) if meth.fget else ""
            lines.append(f"- `.{mname}` *(property)* — {d}")
        elif inspect.isfunction(meth):
            lines.append(f"- `.{mname}{_sig(meth)}` — {_doc(meth)}")
    if lines[-1] != "":
        lines.append("")
    return lines


def main(out: str = OUT) -> int:
    os.makedirs(out, exist_ok=True)
    index = ["# API reference", "",
             "Generated from docstrings by `docs/gen_api.py`; do not edit by hand.", ""]
    for modname, title in MODULES:
        mod = importlib.import_module(modname)
        page = modname.split("accelerate_tpu.", 1)[1].replace(".", "_") + ".md"
        lines = [f"# {title} (`{modname}`)", ""]
        mdoc = _doc(mod, full=True)
        if mdoc:
            lines += [mdoc, ""]
        members = _public_members(mod)
        for name, obj in members:
            if inspect.isclass(obj):
                lines += _render_class(name, obj)
            else:
                lines += [f"### `{name}{_sig(obj)}`", ""]
                d = _doc(obj, full=True)
                if d:
                    lines += [d, ""]
        with open(os.path.join(out, page), "w") as f:
            f.write("\n".join(lines).rstrip() + "\n")
        summary = textwrap.shorten(_doc(mod) or title, 100)
        index.append(f"- [{title}]({page}) — `{modname}` · {len(members)} public symbols. {summary}")
    with open(os.path.join(out, "README.md"), "w") as f:
        f.write("\n".join(index) + "\n")
    print(f"wrote {len(MODULES)} pages to {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else OUT))
