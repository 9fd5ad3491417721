"""Import hygiene — the reference's ``tests/test_imports.py`` analog.

The reference asserts ``import accelerate`` stays cheap and lazy (its CI budget test);
here the contract is the same: importing the package must not drag in the heavy
optional stacks (torch, transformers, orbax — all function-level imports at their use
sites), must stay within a wall-clock budget measured as a DELTA over interpreter
startup, and must not initialize a jax backend: a chip belongs to one process at a time,
so a launcher that merely imported the package would take it from its own workers.
"""

import os
import subprocess
import sys
import time

import pytest

_ENV = {
    "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
    "PYTHONPATH": os.pathsep.join(
        p for p in (
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            os.environ.get("PYTHONPATH", ""),
        ) if p
    ),
    "JAX_PLATFORMS": "cpu",
}


def _wall(code: str) -> float:
    t0 = time.perf_counter()
    subprocess.run([sys.executable, "-c", code], check=True, env=_ENV)
    return time.perf_counter() - t0


def test_import_pulls_no_heavy_deps_and_initializes_no_backend():
    """torch / transformers / orbax / tensorboard are use-site imports, never
    top-level: a user who only wants the facade must not pay for them. And no module
    a parent process imports before starting workers (the kernels, the serving engine,
    the gateway, the CLI) may touch a device at import — ``ops.quantization`` once
    built its NF4 codebook with ``jnp.asarray`` at module level and claimed the chip."""
    r = subprocess.run(
        [sys.executable, "-c", (
            "import sys; import accelerate_tpu; "
            "leaked = [m for m in ('torch', 'transformers', 'tensorflow', 'orbax',"
            " 'tensorboard', 'wandb') if m in sys.modules]; "
            "import accelerate_tpu.ops, accelerate_tpu.serving, "
            "accelerate_tpu.serving_gateway, accelerate_tpu.commands.accelerate_cli; "
            "from jax._src import xla_bridge; "
            "sys.exit(repr((leaked, list(xla_bridge._backends)))) "
            "if leaked or xla_bridge._backends else None"
        )],
        capture_output=True, text=True, env=_ENV,
    )
    assert r.returncode == 0, (
        f"(heavy modules at package import, backends initialized by imports): {r.stderr}"
    )


def test_tpu_backend_helper_is_the_default_backend(monkeypatch):
    """``is_tpu_available`` is the ONE backend question (kernel dispatch, interpret
    mode, bench refusal): exactly ``jax.default_backend() == "tpu"``."""
    import jax

    from accelerate_tpu.ops._common import interpret_default
    from accelerate_tpu.utils.imports import is_tpu_available

    for backend, on_tpu in (("tpu", True), ("cpu", False), ("gpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda backend=backend: backend)
        assert is_tpu_available() is on_tpu
        assert interpret_default() is (not on_tpu)


def test_top_level_migration_surface():
    """Every name a migrating user can import from the reference's package root
    (``/root/reference/src/accelerate/__init__.py``) has a top-level analog here,
    modulo the documented non-ports (DeepSpeed/Megatron torch engines ride plugins,
    ddp_kwargs handlers live in utils). Caught live: ``skip_first_batches`` was
    importable only from ``accelerate_tpu.data_loader``, not the package root."""
    import accelerate_tpu as at

    surface = [
        "Accelerator", "PartialState", "AcceleratorState", "GradientState",
        "skip_first_batches", "notebook_launcher", "debug_launcher",
        "cpu_offload", "cpu_offload_with_hook", "disk_offload", "dispatch_model",
        "init_empty_weights", "init_on_device", "load_checkpoint_and_dispatch",
        "prepare_pippy", "find_executable_batch_size", "DistributedType",
        "DataLoaderConfiguration", "FullyShardedDataParallelPlugin",
        "GradientAccumulationPlugin", "ProjectConfiguration", "get_logger",
        "LocalSGD", "infer_auto_device_map", "load_checkpoint_in_model",
        "synchronize_rng_states", "is_rich_available",
    ]
    if at.is_rich_available():  # reference exports `rich` conditionally the same way
        surface.append("rich")
    missing = [n for n in surface if not hasattr(at, n)]
    assert not missing, f"top-level names missing from accelerate_tpu: {missing}"


@pytest.mark.parametrize("attempts", [3])
def test_import_time_budget(attempts):
    """``import accelerate_tpu`` adds < 2 s over bare interpreter startup (measured
    0.17 s on this machine; the generous budget absorbs CI load spikes)."""
    base = min(_wall("pass") for _ in range(attempts))
    with_pkg = min(_wall("import accelerate_tpu") for _ in range(attempts))
    delta = with_pkg - base
    assert delta < 2.0, f"import delta {delta:.2f}s exceeds the 2s budget"


def test_no_local_import_shadows_module_level():
    """A function-local ``import X`` of a name also imported at module level makes X
    function-local for the WHOLE function — any use on a path that skips the import
    raises UnboundLocalError. This killed the gptj6b s/token row in the 2026-08-01
    TPU window: ``inference_tpu.py::main`` locally imported ``os`` inside its CPU
    branch, so the real-TPU branch (which no CPU test walks) crashed at
    ``os.environ``. AST-scan every entry point and package module for the pattern."""
    import ast
    import pathlib

    root = pathlib.Path(__file__).resolve().parent.parent
    targets = (
        sorted((root / "accelerate_tpu").rglob("*.py"))
        + sorted((root / "benchmarks").rglob("*.py"))
        + sorted((root / "examples").rglob("*.py"))
        + [root / "bench.py", root / "chip_smoke.py", root / "__graft_entry__.py"]
    )
    def bound_names(node):
        for a in node.names:
            if a.name == "*":
                continue
            yield a.asname or (
                a.name.split(".")[0] if isinstance(node, ast.Import) else a.name
            )

    def own_imports(fn):
        # This function's OWN import statements only: a nested def/lambda is its own
        # scope (it is scanned as its own FunctionDef), so its imports must be neither
        # attributed to the enclosing function nor reported twice.
        stack = list(ast.iter_child_nodes(fn))
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                continue
            if isinstance(n, (ast.Import, ast.ImportFrom)):
                yield n
            stack.extend(ast.iter_child_nodes(n))

    offenders = []
    for path in targets:
        tree = ast.parse(path.read_text())
        top = set()
        for n in tree.body:
            if isinstance(n, (ast.Import, ast.ImportFrom)):
                top.update(bound_names(n))
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for n in own_imports(fn):
                for name in bound_names(n):
                    if name in top:
                        offenders.append(
                            f"{path.relative_to(root)}:{n.lineno} "
                            f"{fn.name}() shadows module-level '{name}'"
                        )
    assert not offenders, "\n".join(offenders)
