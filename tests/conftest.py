"""Test backbone: run everything on an 8-device virtual CPU mesh.

This is the faithful multi-device simulator the reference lacks (SURVEY.md §4): XLA's
``--xla_force_host_platform_device_count=8`` gives 8 real XLA devices on one CPU host, so
sharding, collectives and mesh logic run exactly as on an 8-chip TPU slice.

Env vars MUST be set before jax initializes its backends — hence module top, before imports.
"""

import os

# Tests exercise the 8-device simulator whatever accelerator the host has;
# chip_smoke.py is what runs on the real chip.
os.environ["JAX_PLATFORMS"] = "cpu"
prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = f"{prev} --xla_force_host_platform_device_count=8".strip()

import jax  # noqa: E402

from accelerate_tpu.utils.environment import place_compile_cache  # noqa: E402

# Persistent compilation cache: identical HLO recompiled across tests (and across suite
# runs) hits disk instead of XLA. First run pays full compile; reruns of the compile-heavy
# model tests drop from tens of seconds to milliseconds. JAX_COMPILATION_CACHE_DIR, when
# set, places it; otherwise it lives beside the tests.
place_compile_cache(os.path.join(os.path.dirname(__file__), ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)  # keeps the dir small
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_singletons():
    """Keep the shared-dict singletons hermetic between tests
    (reference ``AccelerateTestCase``, testing.py:595-605)."""
    yield
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()


@pytest.fixture
def mesh8():
    import jax
    from accelerate_tpu.parallel import MeshConfig, build_mesh

    assert jax.device_count() == 8, "conftest failed to create 8 virtual devices"
    return build_mesh(MeshConfig())
