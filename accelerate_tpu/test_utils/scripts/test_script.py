"""Flagship bundled self-test (reference ``test_utils/scripts/test_script.py``, 901 LoC).

Run via ``accelerate-tpu test`` (defaults to the 8-virtual-device CPU simulator) or directly
under any backend. Covers the reference script's invariants, re-expressed for the mesh runtime:

- state/topology init and ``split_between_processes`` (:665)
- host-RNG synchronization across processes (:174)
- collective ops correctness: gather / broadcast / pad / reduce (test_ops.py)
- dataloader sharding: every sample seen exactly once, shard + dispatch modes (:192,252)
- seedable-sampler reproducibility across epoch reseeds (:363)
- **training parity: the mesh-distributed run must match the single-device baseline** (:454,
  baseline ``mock_training`` :436) — the highest-value invariant in the reference suite.
- gradient-accumulation semantics: sync only at boundaries (test_sync.py)
"""

from __future__ import annotations

import os
import sys


def _ensure_backend():
    """Default to the 8-device CPU simulator unless explicitly told to stay on-device.

    ``accelerate-tpu test --on-device`` sets ACCELERATE_SELF_TEST_ON_DEVICE; otherwise — bare
    runs included — the suite exercises real 8-way mesh/collective behavior on CPU. The
    device-count XLA flag takes effect at backend-client creation, so setting it here works
    even when jax was imported earlier, as long as no devices were touched yet.
    """
    if os.environ.get("ACCELERATE_SELF_TEST_ON_DEVICE"):
        return
    # Make the *host* platform 8-wide regardless — this flag does not force the cpu backend,
    # it only sizes the CPU platform if that is what jax ends up on (read at client init).
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} --xla_force_host_platform_device_count=8".strip()
    # Force cpu only when the launch context asked for it (accelerate-tpu test default /
    # --cpu); a bare run on a TPU VM keeps validating the real device backend.
    if os.environ.get("ACCELERATE_USE_CPU") or os.environ.get("JAX_PLATFORMS") == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")


_ensure_backend()

import numpy as np  # noqa: E402


def test_state_and_split():
    from accelerate_tpu import Accelerator

    acc = Accelerator()
    assert acc.num_processes >= 1
    assert acc.process_index < acc.num_processes
    with acc.split_between_processes(list(range(7))) as mine:
        assert len(mine) >= 7 // max(acc.num_processes, 1)
    print("state + split_between_processes: OK")
    return acc


def test_rng_sync(acc):
    from accelerate_tpu.utils import gather_object, set_seed, synchronize_rng_states

    set_seed(42)
    before = np.random.random(4)
    set_seed(42)
    after = np.random.random(4)
    assert np.array_equal(before, after), "set_seed not reproducible"
    # Deliberately desync each rank, then broadcast rank 0's state and check convergence
    # (reference test_script.py:174 rng_sync_check).
    set_seed(1000 + acc.process_index)
    synchronize_rng_states(["numpy", "python"])
    draws = gather_object([np.random.random(4).tolist()])  # list-in, flattened-out
    assert all(d == draws[0] for d in draws), f"numpy RNG desynced after sync: {draws}"
    print("rng sync: OK")


def test_ops(acc):
    import jax.numpy as jnp

    from accelerate_tpu.utils import (
        broadcast,
        broadcast_object_list,
        gather,
        gather_object,
        pad_across_processes,
        reduce,
        send_to_device,
    )

    n = acc.num_processes
    x = jnp.arange(8, dtype=jnp.float32).reshape(2, 4) + acc.process_index
    g = gather(x)
    assert g.shape[0] == 2 * n, f"gather shape {g.shape} for {n} processes"
    if n > 1:
        # Row block i must carry rank i's +i offset (exercises _allgather_bytes transport).
        for rank in range(n):
            block = np.asarray(g[2 * rank : 2 * rank + 2])
            assert np.allclose(block, np.arange(8, dtype=np.float32).reshape(2, 4) + rank), (
                f"gather block for rank {rank} wrong"
            )
    r = reduce(x, reduction="sum")
    assert r.shape[-1] == 4
    if n > 1:
        want = np.arange(8, dtype=np.float32).reshape(2, 4) * n + sum(range(n))
        assert np.allclose(np.asarray(r), want), "cross-process reduce incorrect"
    b = broadcast(x)
    # After broadcast every rank holds rank 0's tensor (offset 0).
    assert np.allclose(np.asarray(b), np.arange(8, dtype=np.float32).reshape(2, 4)), (
        "broadcast did not propagate rank 0's tensor"
    )
    p = pad_across_processes(jnp.ones((2, 3 + acc.process_index)), dim=1)
    assert p.shape[1] == 3 + (n - 1), "pad_across_processes wrong target length"
    # Object (pickle) collectives over the distributed KV store / allgather transport.
    objs = gather_object([{"rank": acc.process_index, "payload": [acc.process_index] * 2}])
    assert [o["rank"] for o in objs] == list(range(n)), objs
    blist = broadcast_object_list(
        ["from-rank-0", acc.process_index] if acc.is_main_process else [None, None]
    )
    assert blist[0] == "from-rank-0" and blist[1] == 0, blist
    batch = send_to_device({"x": np.ones((4, 2), np.float32)}, acc.device)
    assert batch["x"].shape == (4, 2)
    print("collective ops: OK")


def test_dataloader_sharding(acc):
    from accelerate_tpu.data_loader import DataLoader, prepare_data_loader

    class Dataset:
        def __len__(self):
            return 30

        def __getitem__(self, i):
            return {"idx": np.int32(i)}

    from accelerate_tpu.utils import gather_object

    dl = DataLoader(Dataset(), batch_size=4)
    prepared = prepare_data_loader(dl, device=acc.device, put_on_device=False)
    seen = []
    for batch in prepared:
        seen.extend(np.asarray(batch["idx"]).reshape(-1).tolist())
    # Every sample must be seen across the union of ranks (each rank may also carry
    # even_batches padding duplicates at the tail).
    union = sorted(set(gather_object(seen)))  # flattened across ranks
    assert union == list(range(30)), f"shard mode lost samples: {union[:10]}"
    dispatched = prepare_data_loader(dl, device=acc.device, dispatch_batches=True, put_on_device=False)
    seen_d = []
    for batch in dispatched:
        seen_d.extend(np.asarray(batch["idx"]).reshape(-1).tolist())
    union_d = sorted(set(gather_object(seen_d)))
    assert union_d == list(range(30)), "dispatch mode lost samples"
    print("dataloader shard + dispatch: OK")


def test_seedable_sampler():
    from accelerate_tpu.data_loader import DataLoader, SeedableRandomSampler

    class Dataset:
        def __len__(self):
            return 16

        def __getitem__(self, i):
            return {"idx": np.int32(i)}

    ds = Dataset()
    orders = []
    for _trial in range(2):
        sampler = SeedableRandomSampler(ds, seed=7)
        sampler.set_epoch(3)
        dl = DataLoader(ds, batch_size=4, sampler=sampler)
        orders.append([int(i) for b in dl for i in np.asarray(b["idx"]).reshape(-1)])
    assert orders[0] == orders[1], "seedable sampler not reproducible"
    print("seedable sampler: OK")


def mock_training(n_steps: int = 8, accumulate: int = 1):
    """Single-device baseline (reference ``mock_training`` :436): plain optax loop."""
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu.test_utils.training import linear_regression_loss, make_regression_state

    rng = np.random.default_rng(0)
    xs = rng.normal(size=(n_steps * accumulate, 16)).astype(np.float32)
    ys = (2.0 * xs + 1.0).astype(np.float32)
    params = make_regression_state()
    tx = optax.sgd(0.1)
    opt_state = tx.init(params)
    grad_fn = jax.grad(linear_regression_loss)
    for step in range(n_steps):
        grads = jax.tree_util.tree_map(jnp.zeros_like, params)
        for micro in range(accumulate):
            batch = {
                "x": jnp.asarray(xs[step * accumulate + micro]),
                "y": jnp.asarray(ys[step * accumulate + micro]),
            }
            g = grad_fn(params, batch)
            grads = jax.tree_util.tree_map(lambda a, b: a + b, grads, g)
        grads = jax.tree_util.tree_map(lambda g: g / accumulate, grads)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
    return params, (xs, ys)


def training_check(acc):
    """Distributed-vs-baseline parity (reference ``training_check`` :454)."""
    import jax.numpy as jnp
    import optax

    from accelerate_tpu.test_utils.training import linear_regression_loss, make_regression_state

    n_steps, accumulate = 8, 2
    baseline_params, (xs, ys) = mock_training(n_steps, accumulate)

    state = acc.create_train_state(make_regression_state(), optax.sgd(0.1))
    step = acc.build_train_step(linear_regression_loss)
    for s in range(n_steps):
        for micro in range(accumulate):
            i = s * accumulate + micro
            batch = {"x": jnp.asarray(xs[i]), "y": jnp.asarray(ys[i])}
            state, _ = step(state, batch)
    for key in ("a", "b"):
        got = float(np.asarray(state.params[key]))
        want = float(np.asarray(baseline_params[key]))
        assert abs(got - want) < 1e-4, f"parity broken for {key}: {got} vs {want}"
    assert int(state.step) == n_steps, f"expected {n_steps} optimizer steps, got {int(state.step)}"
    print("training parity (distributed == single-process baseline): OK")


def main():
    print(f"accelerate-tpu self-test starting (argv={sys.argv[1:]})")
    import jax

    print(f"backend={jax.default_backend()} devices={jax.device_count()} processes={jax.process_count()}")
    from accelerate_tpu import Accelerator  # noqa: F401 - import sanity
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    acc = test_state_and_split()
    test_rng_sync(acc)
    test_ops(acc)
    test_dataloader_sharding(acc)
    test_seedable_sampler()

    # Fresh accelerator with accumulation for the parity check.
    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    from accelerate_tpu import Accelerator as A

    acc2 = A(gradient_accumulation_steps=2)
    training_check(acc2)
    print("All self-tests passed.")


if __name__ == "__main__":
    main()
