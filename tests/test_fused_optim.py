"""Fused AdamW Pallas kernel (ops/fused_optim.py) — parity with optax.adamw.

The kernel must be bit-for-bit-equivalent math to ``optax.adamw`` (same chain:
scale_by_adam → add_decayed_weights → scale(-lr)); these tests lock that in on CPU
(interpret mode) across leaf layouts, moment dtypes, schedules, and the full
``build_train_step`` integration incl. global-norm clipping.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from accelerate_tpu.ops.fused_optim import FusedAdamW, fused_adamw


def _params_mixed():
    """Kernel-eligible leaves (size % 1024 == 0) + odd fallback leaves."""
    k = jax.random.PRNGKey(0)
    ks = jax.random.split(k, 4)
    return {
        "w_stacked": jax.random.normal(ks[0], (3, 64, 128), jnp.float32),  # 24576 % 1024 == 0
        "w2": jax.random.normal(ks[1], (8, 128), jnp.float32),             # 1024
        "bias": jax.random.normal(ks[2], (17,), jnp.float32),              # odd → XLA path
        "scale": jax.random.normal(ks[3], (128,), jnp.float32),            # odd (128 < 1024)
    }


def _grads_like(params, seed=1):
    leaves, treedef = jax.tree_util.tree_flatten(params)
    ks = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return treedef.unflatten(
        [jax.random.normal(k, l.shape, l.dtype) for k, l in zip(ks, leaves)]
    )


@pytest.mark.parametrize("mu_dtype", [None, jnp.bfloat16])
def test_fused_apply_matches_optax_adamw(mu_dtype):
    params = _params_mixed()
    lr, wd = 3e-3, 1e-2
    ours = fused_adamw(lr, weight_decay=wd, mu_dtype=mu_dtype)
    ref = optax.adamw(lr, weight_decay=wd, mu_dtype=mu_dtype)
    s_ours = ours.init(params)
    s_ref = ref.init(params)
    p_ours = p_ref = params
    for step in range(4):
        g = _grads_like(params, seed=step)
        p_ours, s_ours = jax.jit(ours.fused_apply)(g, s_ours, p_ours)
        u, s_ref = ref.update(g, s_ref, p_ref)
        p_ref = optax.apply_updates(p_ref, u)
    # fp32 moments: bit-identical expression order. bf16 mu: the kernel keeps b1*m in
    # fp32 where optax rounds to bf16 first (one rounding tighter) → bf16-ulp drift.
    rtol, atol = (2e-5, 2e-6) if mu_dtype is None else (6e-4, 6e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p_ours), jax.tree_util.tree_leaves(p_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol, atol=atol)


def test_f8_state_structure_and_protocol_parity():
    """MS-AMP analog: fp8 moments live in ScaledAdamState with one fp32
    scale per leaf; fused_apply and the optax-protocol update land on identical params
    (same math path for scaled leaves)."""
    import optax as _optax

    from accelerate_tpu.ops.fused_optim import ScaledAdamState

    params = _params_mixed()
    g = _grads_like(params)
    ours = fused_adamw(1e-3, mu_dtype=jnp.float8_e4m3fn, nu_dtype=jnp.float8_e4m3fn)
    state = ours.init(params)
    assert isinstance(state, ScaledAdamState)
    assert state.mu["w2"].dtype == jnp.float8_e4m3fn
    assert state.nu["w2"].dtype == jnp.float8_e4m3fn
    assert state.mu_scale["w2"].shape == () and state.mu_scale["w2"].dtype == jnp.float32

    p_fused, s_fused = jax.jit(ours.fused_apply)(g, state, params)
    updates, s_two = ours.update(g, state, params)
    p_two = _optax.apply_updates(params, updates)
    assert isinstance(s_fused, ScaledAdamState) and isinstance(s_two, ScaledAdamState)
    for a, b in zip(jax.tree_util.tree_leaves(p_fused), jax.tree_util.tree_leaves(p_two)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7)
    # Scales track the stored moment: dequantized mu must reconstruct near the fp32
    # moment of a reference fp32 run (first step: mu_ref = (1-b1)*g).
    m_ref = (1.0 - ours.b1) * np.asarray(g["w2"], np.float64)
    deq = np.asarray(s_fused.mu["w2"], np.float32) * float(s_fused.mu_scale["w2"])
    amax = np.abs(m_ref).max()
    np.testing.assert_allclose(deq, m_ref, atol=amax / 448 * 1.5, rtol=0.08)


def test_f8_state_convergence_matches_fp32_state():
    """Convergence parity: training with fp8 optimizer
    state tracks the fp32-state trajectory through the full facade (clip active), and
    the standing moment HBM is 1/4 the fp32 state's."""
    from accelerate_tpu import Accelerator
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    def loss_fn(params, batch):
        pred = jnp.tanh(batch["x"] @ params["w1"]) @ params["w2"]
        return jnp.mean((pred - batch["y"]) ** 2)

    rng = np.random.default_rng(0)
    batch = {
        "x": jnp.asarray(rng.normal(size=(32, 8)), jnp.float32),
        "y": jnp.asarray(rng.normal(size=(32, 128)), jnp.float32),
    }
    results = {}
    for name, tx in (
        ("f8", fused_adamw(3e-3, mu_dtype=jnp.float8_e4m3fn, nu_dtype=jnp.float8_e4m3fn)),
        ("fp32", fused_adamw(3e-3)),
    ):
        AcceleratorState._reset_state()
        GradientState._reset_state()
        PartialState._reset_state()
        acc = Accelerator()
        rng = np.random.default_rng(1)  # reset BEFORE drawing: identical init both runs
        params = {
            "w1": jnp.asarray(rng.normal(size=(8, 64)) * 0.3, jnp.float32),
            "w2": jnp.zeros((64, 128), jnp.float32),
        }
        state = acc.create_train_state(params, tx)
        step = acc.build_train_step(loss_fn, max_grad_norm=1.0)
        losses = []
        for _ in range(40):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        results[name] = (losses, state)
    f8_losses, f8_state = results["f8"]
    fp_losses, _ = results["fp32"]
    # Both must converge, and the fp8-state trajectory stays within quantization drift.
    assert f8_losses[-1] < f8_losses[0] * 0.7
    np.testing.assert_allclose(f8_losses, fp_losses, rtol=0.05, atol=5e-3)
    mu = getattr(f8_state.opt_state, "mu", None)
    assert mu is not None and mu["w2"].dtype == jnp.float8_e4m3fn


def test_grad_scale_folds_clip():
    params = _params_mixed()
    g = _grads_like(params)
    ours = fused_adamw(1e-3)
    state = ours.init(params)
    scale = 0.37
    p_a, _ = ours.fused_apply(g, state, params, grad_scale=scale)
    g_scaled = jax.tree_util.tree_map(lambda x: x * scale, g)
    p_b, _ = ours.fused_apply(g_scaled, state, params)
    for a, b in zip(jax.tree_util.tree_leaves(p_a), jax.tree_util.tree_leaves(p_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)


def test_schedule_learning_rate():
    params = {"w": jnp.ones((8, 128), jnp.float32)}
    sched = optax.linear_schedule(1e-2, 1e-3, transition_steps=10)
    ours = fused_adamw(sched)
    ref = optax.adamw(sched)
    s_ours, s_ref = ours.init(params), ref.init(params)
    p_ours = p_ref = params
    for step in range(5):
        g = _grads_like(params, seed=step)
        p_ours, s_ours = ours.fused_apply(g, s_ours, p_ours)
        u, s_ref = ref.update(g, s_ref, p_ref)
        p_ref = optax.apply_updates(p_ref, u)
    np.testing.assert_allclose(
        np.asarray(p_ours["w"]), np.asarray(p_ref["w"]), rtol=2e-5, atol=2e-6
    )


def test_two_phase_update_protocol():
    """The optax-protocol path (update → apply_updates) must land on the same params."""
    params = _params_mixed()
    g = _grads_like(params)
    ours = fused_adamw(1e-3)
    state = ours.init(params)
    p_fused, s_fused = ours.fused_apply(g, state, params)
    updates, s_two = ours.update(g, state, params)
    p_two = optax.apply_updates(params, updates)
    assert int(s_two.count) == int(s_fused.count) == 1
    for a, b in zip(jax.tree_util.tree_leaves(p_fused), jax.tree_util.tree_leaves(p_two)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7)


def test_build_train_step_uses_fused_apply():
    """Full integration: identical training trajectory fused vs optax, clip active."""
    from accelerate_tpu import Accelerator
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2)

    rng = np.random.default_rng(0)
    batch = {
        "x": jnp.asarray(rng.normal(size=(16, 8)), jnp.float32),
        "y": jnp.asarray(rng.normal(size=(16, 128)), jnp.float32),
    }
    results = {}
    for name, tx in (("fused", fused_adamw(1e-2, weight_decay=1e-3)),
                     ("optax", optax.adamw(1e-2, weight_decay=1e-3))):
        AcceleratorState._reset_state()
        GradientState._reset_state()
        PartialState._reset_state()
        acc = Accelerator()
        params = {"w": jnp.zeros((8, 128), jnp.float32)}
        state = acc.create_train_state(params, tx)
        step = acc.build_train_step(loss_fn, max_grad_norm=0.5)
        losses, gnorms = [], []
        for _ in range(5):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
            gnorms.append(float(m["grad_norm"]))
        results[name] = (losses, gnorms, np.asarray(state.params["w"]))
    np.testing.assert_allclose(results["fused"][0], results["optax"][0], rtol=1e-5)
    np.testing.assert_allclose(results["fused"][1], results["optax"][1], rtol=1e-5)
    np.testing.assert_allclose(results["fused"][2], results["optax"][2], rtol=1e-5, atol=1e-7)


def test_fused_shard_map_under_fsdp():
    """FSDP/ZeRO-3-sharded states run the kernel under shard_map (each device updates its
    own shard) and must match the optax trajectory AND preserve the sharded layout."""
    from accelerate_tpu import Accelerator
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.utils.dataclasses import FullyShardedDataParallelPlugin

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

    rng = np.random.default_rng(0)
    batch = {
        "x": jnp.asarray(rng.normal(size=(16, 64)), jnp.float32),
        "y": jnp.asarray(rng.normal(size=(16, 128)), jnp.float32),
    }
    results = {}
    for name, tx in (("fused", fused_adamw(1e-2)), ("optax", optax.adamw(1e-2))):
        AcceleratorState._reset_state()
        GradientState._reset_state()
        PartialState._reset_state()
        acc = Accelerator(
            fsdp_plugin=FullyShardedDataParallelPlugin(zero_stage=3, min_weight_size=0)
        )
        params = {"w": jnp.zeros((64, 128), jnp.float32)}
        state = acc.create_train_state(params, tx)
        assert acc._params_cross_sharded or acc.mesh.size == 1
        step = acc.build_train_step(loss_fn, max_grad_norm=1.0)
        for _ in range(3):
            state, m = step(state, batch)
        if name == "fused" and acc.mesh.size > 1:
            # The fused path must not have silently replicated the moments.
            mu_leaf = jax.tree_util.tree_leaves(state.opt_state.mu)[0]
            assert not mu_leaf.sharding.is_fully_replicated
        results[name] = (float(m["loss"]), np.asarray(state.params["w"]))
    assert results["fused"][0] == pytest.approx(results["optax"][0], rel=1e-5)
    np.testing.assert_allclose(results["fused"][1], results["optax"][1], rtol=1e-5, atol=1e-7)


def test_fused_uneven_shard_spec_falls_back_to_xla_math():
    """A spec whose sharded dim doesn't divide the mesh axis must not reach shard_map
    (which would raise at trace time) — such leaves take the identical XLA math. The
    framework's prepare path rejects uneven layouts upstream (parallel/tp.py), so this
    guards direct fused_apply callers. Opaque layout sentinels take the same route."""
    from jax.sharding import PartitionSpec

    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.parallel import MeshConfig

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    from accelerate_tpu import Accelerator

    acc = Accelerator(mesh_config=MeshConfig(tp=8))
    params = {"w": jnp.ones((64, 100), jnp.float32),   # 100 % 8 != 0 → XLA fallback
              "q": jnp.ones((64, 128), jnp.float32)}   # opaque sentinel → XLA fallback
    g = _grads_like(params)
    ours = fused_adamw(1e-2)
    ref = optax.adamw(1e-2)
    state = ours.init(params)
    p_fused, _ = ours.fused_apply(
        g, state, params,
        specs={"w": PartitionSpec(None, "tp"), "q": "opaque"},
        mesh=acc.mesh,
    )
    u, _ = ref.update(g, ref.init(params), params)
    p_ref = optax.apply_updates(params, u)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(p_fused[k]), np.asarray(p_ref[k]), rtol=2e-5, atol=2e-6
        )
    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()


def test_bf16_policy_compresses_gradient_reduce():
    """With the bf16 policy (reduce_dtype == compute_dtype == bf16), build_train_step
    must take the compressed-reduce formulation; the trajectory still matches the
    uncompressed fp32-reduce policy within bf16 reduction rounding."""
    import dataclasses as dc

    from accelerate_tpu import Accelerator
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

    rng = np.random.default_rng(0)
    batch = {
        "x": jnp.asarray(rng.normal(size=(16, 64)), jnp.float32),
        "y": jnp.asarray(rng.normal(size=(16, 128)), jnp.float32),
    }
    losses = {}
    for mode in ("compressed", "fp32_reduce"):
        AcceleratorState._reset_state()
        GradientState._reset_state()
        PartialState._reset_state()
        acc = Accelerator(mixed_precision="bf16")
        if mode == "fp32_reduce":
            acc.state.mixed_precision_policy = dc.replace(
                acc.state.mixed_precision_policy, reduce_dtype=jnp.float32
            )
        params = {"w": jnp.zeros((64, 128), jnp.float32)}
        state = acc.create_train_state(params, optax.adamw(1e-2))
        step = acc.build_train_step(loss_fn, max_grad_norm=1.0)
        assert acc._reduce_compressed is (mode == "compressed")
        run = []
        for _ in range(4):
            state, m = step(state, batch)
            run.append(float(m["loss"]))
        losses[mode] = run
    np.testing.assert_allclose(losses["compressed"], losses["fp32_reduce"], rtol=2e-2)


def test_fused_falls_back_under_zero1():
    """ZeRO-1 (opt state sharded, params replicated — layouts differ) must route through
    the optax-protocol fallback and still match plain optax adamw losses."""
    from accelerate_tpu import Accelerator
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.utils.dataclasses import FullyShardedDataParallelPlugin

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

    rng = np.random.default_rng(0)
    batch = {
        "x": jnp.asarray(rng.normal(size=(16, 64)), jnp.float32),
        "y": jnp.asarray(rng.normal(size=(16, 128)), jnp.float32),
    }
    losses = {}
    for name, tx in (("fused", fused_adamw(1e-2)), ("optax", optax.adamw(1e-2))):
        AcceleratorState._reset_state()
        GradientState._reset_state()
        PartialState._reset_state()
        acc = Accelerator(
            fsdp_plugin=FullyShardedDataParallelPlugin(zero_stage=1, min_weight_size=0)
        )
        params = {"w": jnp.zeros((64, 128), jnp.float32)}
        state = acc.create_train_state(params, tx)
        step = acc.build_train_step(loss_fn, max_grad_norm=1.0)
        run = []
        for _ in range(3):
            state, m = step(state, batch)
            run.append(float(m["loss"]))
        losses[name] = run
    np.testing.assert_allclose(losses["fused"], losses["optax"], rtol=1e-5)


def test_fused_step_checkpoint_roundtrip(tmp_path):
    """FusedAdamW state (ScaleByAdamState) must save/restore through the checkpoint engine."""
    from accelerate_tpu import Accelerator
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"]) ** 2)

    acc = Accelerator()
    params = {"w": jnp.ones((8, 128), jnp.float32)}
    state = acc.create_train_state(params, fused_adamw(1e-2))
    step = acc.build_train_step(loss_fn)
    batch = {"x": jnp.ones((4, 8), jnp.float32)}
    state, _ = step(state, batch)
    acc.save_state(str(tmp_path / "ckpt"), state)
    restored = acc.load_state(str(tmp_path / "ckpt"), state)
    for a, b in zip(jax.tree_util.tree_leaves(state), jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_prime_row_leaf_takes_pad_branch_and_matches_optax():
    """A leaf whose row count (size/1024) is prime has no divisor near block_rows:
    _leaf_fused must PAD to a block multiple (not degrade to block_rows=1) and stay
    bit-equivalent to optax. rows=127 (prime) with the default block_rows forces the
    pad branch; rows=16 rides the exact-divisor branch as control."""
    k = jax.random.PRNGKey(9)
    params = {
        "prime_rows": jax.random.normal(k, (127, 1024), jnp.float32),  # rows=127, prime
        "even_rows": jax.random.normal(k, (16, 1024), jnp.float32),
    }
    lr, wd = 3e-3, 1e-2
    ours = fused_adamw(lr, weight_decay=wd)
    ref = optax.adamw(lr, weight_decay=wd)
    s_ours, s_ref = ours.init(params), ref.init(params)
    p_ours = p_ref = params
    for step in range(3):
        g = _grads_like(params, seed=step)
        p_ours, s_ours = jax.jit(ours.fused_apply)(g, s_ours, p_ours)
        u, s_ref = ref.update(g, s_ref, p_ref)
        p_ref = optax.apply_updates(p_ref, u)
    for a, b in zip(jax.tree_util.tree_leaves(p_ours), jax.tree_util.tree_leaves(p_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6)
