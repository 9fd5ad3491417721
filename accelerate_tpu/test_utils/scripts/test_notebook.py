"""Spawn targets for notebook/debug launcher tests (reference ``test_utils/scripts/test_notebook.py``).

Functions here are module-level so ``multiprocessing`` spawn children can unpickle them by
import path from the installed package.
"""

from __future__ import annotations


def basic_function():
    """Child body: init the distributed state and verify the rendezvous topology."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from accelerate_tpu import PartialState

    state = PartialState()
    assert state.num_processes == jax.process_count()
    print(f"child process {state.process_index}/{state.num_processes} OK", flush=True)


def function_with_args(value: int):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from accelerate_tpu import PartialState

    state = PartialState()
    assert value == 42, value
    print(f"child {state.process_index} got value {value}", flush=True)


def run_full_self_test():
    """Child body for the multi-process tier: the ENTIRE bundled self-test suite with
    ``process_count() > 1`` — collectives take the real cross-process transport
    (``_allgather_bytes``/``broadcast_object_list``), the dispatcher broadcasts batches,
    RNG sync crosses ranks, and training parity holds against the 1-process baseline."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from accelerate_tpu import PartialState
    from accelerate_tpu.test_utils.scripts import test_script

    import os

    PartialState()  # initializes jax.distributed from the launcher's rendezvous env
    assert jax.process_count() > 1, "multi-process tier ran single-process"
    per_proc = int(os.environ.get("ACCELERATE_DEVICES_PER_PROCESS", "0"))
    if per_proc:
        expected = per_proc * jax.process_count()
        assert jax.device_count() == expected, (
            f"pod-sim topology wrong: {jax.device_count()} global devices, expected {expected}"
        )
    test_script.main()


def run_sync_and_data_loop_self_tests():
    """Child body: the bundled sync + distributed-data-loop suites under process_count()>1
    (reference ships these as separate launchable scripts: ``test_sync.py``,
    ``test_distributed_data_loop.py``)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from accelerate_tpu import PartialState
    from accelerate_tpu.test_utils.scripts import test_distributed_data_loop, test_sync

    PartialState()
    assert jax.process_count() > 1, "multi-process tier ran single-process"
    test_sync.main()
    test_distributed_data_loop.main()
    from accelerate_tpu.test_utils.scripts import test_performance

    test_performance.main()


def run_ops_and_metrics_self_tests():
    """Child body: the bundled ops/metrics/checkpointing suites under process_count()>1 —
    real cross-process gather/reduce/broadcast/gather_object, duplicate-trimmed metrics,
    and a multi-process checkpoint resume (reference test_ops.py / external_deps
    test_metrics.py / test_checkpointing.py)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from accelerate_tpu import PartialState
    from accelerate_tpu.test_utils.scripts import test_checkpointing, test_metrics, test_ops

    PartialState()
    assert jax.process_count() > 1, "multi-process tier ran single-process"
    test_ops.main()
    test_metrics.main()
    test_checkpointing.main()


def run_dryrun_train_2proc():
    """Child body for the driver dryrun's 2-process section: a real
    distributed train step on a dp×fsdp mesh spanning 2 processes × 4 devices — the
    cross-process collective transport (grad psum, global-norm clip, fsdp all-gathers)
    exercised inside the driver-scored artifact, not just the pytest tier."""
    import dataclasses

    import numpy as np

    import jax

    jax.config.update("jax_platforms", "cpu")
    import optax

    from accelerate_tpu import Accelerator, PartialState
    from accelerate_tpu.models import llama
    from accelerate_tpu.parallel import MeshConfig
    from accelerate_tpu.utils import FullyShardedDataParallelPlugin, send_to_device

    PartialState()  # initializes jax.distributed from the launcher's rendezvous env
    assert jax.process_count() == 2, f"expected 2 processes, got {jax.process_count()}"
    assert jax.device_count() == 8, f"expected 8 global devices, got {jax.device_count()}"
    acc = Accelerator(
        mixed_precision="bf16",
        mesh_config=MeshConfig(dp=4, fsdp=2),
        fsdp_plugin=FullyShardedDataParallelPlugin(min_weight_size=1),
    )
    cfg = dataclasses.replace(llama.CONFIGS["tiny"], attn_impl="xla")
    state = acc.create_train_state(
        llama.init_params(cfg), optax.adamw(1e-3),
        partition_specs=llama.partition_specs(cfg), rng=jax.random.PRNGKey(0),
    )
    assert not state.params["embed"].sharding.is_fully_replicated, "fsdp not applied"
    step = acc.build_train_step(lambda p, b: llama.loss_fn(p, b, cfg), max_grad_norm=1.0)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(8, 17)
    ).astype(np.int32)
    state, metrics = step(state, send_to_device({"tokens": tokens}, acc.mesh))
    loss = float(metrics["loss"])
    assert np.isfinite(loss), f"non-finite loss {loss}"
    if acc.is_main_process:
        print(
            f"dryrun_multichip procs=2: OK loss={loss:.4f} "
            f"mesh=dp4xfsdp2 over {jax.process_count()} processes", flush=True,
        )


if __name__ == "__main__":
    basic_function()
