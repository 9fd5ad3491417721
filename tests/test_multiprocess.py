"""True multi-process collectives tier.

2 spawned processes × 4 virtual CPU devices each = a faithful 2-host 8-chip pod simulation:
``process_count() == 2``, so every host-level collective takes its real cross-process
transport instead of the single-process short-circuit the unit tests exercise. The children
run the ENTIRE bundled self-test (``test_utils/scripts/test_script.py`` — ops, object
collectives, dataloader shard/dispatch union coverage, RNG sync, training parity).

Reference analog: ``tests/test_multigpu.py`` launching
``src/accelerate/test_utils/scripts/test_script.py`` over real process groups.
"""

import pytest

from accelerate_tpu import notebook_launcher
from accelerate_tpu.test_utils.scripts.test_notebook import (
    run_full_self_test,
    run_ops_and_metrics_self_tests,
    run_sync_and_data_loop_self_tests,
)
from accelerate_tpu.test_utils.testing import slow
from accelerate_tpu.utils.environment import patch_environment


def test_full_self_test_two_processes_eight_devices():
    with patch_environment(ACCELERATE_USE_CPU="true", JAX_PLATFORMS="cpu"):
        notebook_launcher(
            run_full_self_test, num_processes=2, devices_per_process=4
        )


@slow
def test_sync_and_data_loop_two_processes():
    """The shipped test_sync/test_distributed_data_loop suites over real 2-process
    transport (their standalone forms run in the CLI path: ``accelerate-tpu test --suite all``)."""
    with patch_environment(ACCELERATE_USE_CPU="true", JAX_PLATFORMS="cpu"):
        notebook_launcher(
            run_sync_and_data_loop_self_tests, num_processes=2, devices_per_process=4
        )


def test_ops_metrics_checkpointing_two_processes():
    """The shipped ops/metrics/checkpointing suites over real 2-process transport —
    cross-process gather_object flattening, gather_for_metrics duplicate trimming, and
    checkpoint resume parity all exercised with process_count() == 2. Default tier
    (not slow) deliberately: without it, a default run never touches cross-process
    checkpoint-resume; ~49 s."""
    with patch_environment(ACCELERATE_USE_CPU="true", JAX_PLATFORMS="cpu"):
        notebook_launcher(
            run_ops_and_metrics_self_tests, num_processes=2, devices_per_process=4
        )
