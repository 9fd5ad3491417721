"""Example-regression tier (reference tests/test_examples.py): every shipped example must run
end-to-end in smoke mode.

One flagship script runs as a real subprocess (fresh interpreter — the exact path a user hits);
the rest run in-process via runpy for speed (the conftest fixture resets the state singletons
between tests, reference ``AccelerateTestCase`` semantics).
"""

import os
import runpy
import subprocess
import sys
from pathlib import Path

import pytest

# Example runs recompile XLA programs per script (~20-90 s each): slow tier, like the
# reference's example-regression CI. RUN_SLOW=1 enables.
from accelerate_tpu.test_utils.testing import slow_mark

pytestmark = slow_mark()

EXAMPLES = Path(__file__).parent.parent / "examples"


@pytest.fixture(autouse=True)
def _examples_on_path(monkeypatch):
    monkeypatch.syspath_prepend(str(EXAMPLES))


def _run_inline(script: Path, *flags: str, capsys=None, monkeypatch=None) -> str:
    monkeypatch.setattr(sys, "argv", [script.name, "--smoke", "--cpu", *flags])
    runpy.run_path(str(script), run_name="__main__")
    return capsys.readouterr().out


def test_nlp_example_subprocess():
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "ACCELERATE_USE_CPU": "true",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "PYTHONPATH": str(EXAMPLES.parent) + ":" + os.environ.get("PYTHONPATH", ""),
    }
    result = subprocess.run(
        [sys.executable, str(EXAMPLES / "nlp_example.py"), "--smoke", "--cpu"],
        capture_output=True, text=True, timeout=420, env=env, cwd=str(EXAMPLES.parent),
    )
    assert result.returncode == 0, f"nlp_example failed:\n{result.stdout}\n{result.stderr}"
    assert "accuracy=" in result.stdout


def test_complete_nlp_example(tmp_path, capsys, monkeypatch):
    out = _run_inline(
        EXAMPLES / "complete_nlp_example.py",
        "--checkpointing_steps", "epoch", "--project_dir", str(tmp_path),
        capsys=capsys, monkeypatch=monkeypatch,
    )
    assert "accuracy=" in out
    assert (tmp_path / "epoch_0").exists()


@pytest.mark.parametrize(
    "name, expect",
    [
        ("checkpointing.py", "resume verified"),
        ("gradient_accumulation.py", "optimizer steps"),
        ("tracking.py", "logged"),
        ("memory.py", "executable batch size"),
        ("profiler.py", "profiled 3 steps"),
        ("multi_process_metrics.py", "evaluated"),
        ("fsdp_with_peak_mem_tracking.py", "loss="),
        ("local_sgd.py", "final loss="),
        ("early_stopping.py", "early stopping at epoch"),
        ("cross_validation.py", "cross-validation accuracy="),
        ("automatic_gradient_accumulation.py", "optimizer_steps="),
        ("gradient_accumulation_for_autoregressive_models.py", "window tokens="),
        ("schedule_free.py", "schedule-free eval params"),
        ("ddp_comm_hook.py", "gradient reduction dtype: bfloat16"),
        ("sequence_parallelism.py", "long-context training OK"),
        ("pipeline_parallelism.py", "pipeline training OK"),
        ("megatron_lm_gpt_pretraining.py", "3D pretraining OK"),
        ("sample_packing.py", "packed rows"),
    ],
)
def test_by_feature(name, expect, capsys, monkeypatch):
    out = _run_inline(EXAMPLES / "by_feature" / name, capsys=capsys, monkeypatch=monkeypatch)
    assert expect in out, out


def test_serving_example(capsys, monkeypatch):
    out = _run_inline(EXAMPLES / "inference" / "serving.py", "--requests", "10",
                      capsys=capsys, monkeypatch=monkeypatch)
    assert "served 10 requests" in out and "tokens/s" in out


def test_speculative_example(capsys, monkeypatch):
    out = _run_inline(EXAMPLES / "inference" / "speculative.py",
                      capsys=capsys, monkeypatch=monkeypatch)
    assert "== plain greedy" in out


def test_cv_example(capsys, monkeypatch):
    out = _run_inline(EXAMPLES / "cv_example.py", capsys=capsys, monkeypatch=monkeypatch)
    assert "accuracy=" in out


def test_complete_cv_example(tmp_path, capsys, monkeypatch):
    out = _run_inline(
        EXAMPLES / "complete_cv_example.py",
        "--checkpointing_steps", "epoch", "--project_dir", str(tmp_path),
        capsys=capsys, monkeypatch=monkeypatch,
    )
    assert "accuracy=" in out
    assert (tmp_path / "epoch_0").exists()


def test_automatic_grad_accum_recovers_from_oom(capsys, monkeypatch):
    """The OOM-retry path: simulated OOM above batch 16 → halves and compensates."""
    out = _run_inline(
        EXAMPLES / "by_feature" / "automatic_gradient_accumulation.py",
        "--simulate_oom_above", "16",
        capsys=capsys, monkeypatch=monkeypatch,
    )
    assert "auto-recovered to batch_size=16" in out


def test_big_model_inference_example(capsys, monkeypatch):
    monkeypatch.setattr(sys, "argv", ["big_model_inference.py", "--smoke"])
    runpy.run_path(str(EXAMPLES / "by_feature" / "big_model_inference.py"), run_name="__main__")
    out = capsys.readouterr().out
    assert "streamed forward" in out
