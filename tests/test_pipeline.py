"""Pipeline-parallelism tests: GPipe schedule == sequential layer application, forward and
backward (training step through the pipeline)."""

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from accelerate_tpu.test_utils.testing import slow
from accelerate_tpu.parallel import MeshConfig, build_mesh
from accelerate_tpu.parallel.pp import (
    make_pipeline_fn,
    split_params_into_stages,
    stack_stage_params,
)


def mlp_stage(params, x):
    """One stage = two residual MLP layers: params pytree with stacked leading layer dim."""
    def layer(x, p):
        return x + jnp.tanh(x @ p["w"] + p["b"]), None

    out, _ = jax.lax.scan(layer, x, params)
    return out


def make_layer_params(n_layers, d, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(size=(n_layers, d, d)) * 0.1, dtype=jnp.float32),
        "b": jnp.zeros((n_layers, d), dtype=jnp.float32),
    }


def sequential_apply(layer_params, x):
    def layer(x, p):
        return x + jnp.tanh(x @ p["w"] + p["b"]), None

    out, _ = jax.lax.scan(layer, x, layer_params)
    return out


@pytest.fixture
def pp_mesh():
    return build_mesh(MeshConfig(dp=2, pp=4))


@pytest.mark.parametrize("num_microbatches", [4, 8])
def test_pipeline_forward_matches_sequential(pp_mesh, num_microbatches):
    d, L, B = 16, 8, 16
    layer_params = make_layer_params(L, d)
    stage_params = split_params_into_stages(layer_params, 4)  # [4, 2, d, d]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(B, d)), dtype=jnp.float32)

    pipe = make_pipeline_fn(pp_mesh, mlp_stage, num_microbatches=num_microbatches)
    sharded = jax.tree_util.tree_map(
        lambda l: jax.device_put(l, NamedSharding(pp_mesh, P("pp"))), stage_params
    )
    with jax.set_mesh(pp_mesh):
        out = jax.jit(pipe)(sharded, x)
    ref = sequential_apply(layer_params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_pipeline_gradient_matches_sequential(pp_mesh):
    d, L, B = 8, 4, 8
    layer_params = make_layer_params(L, d)
    stage_params = split_params_into_stages(layer_params, 4)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(B, d)), dtype=jnp.float32)
    y = jnp.asarray(np.random.default_rng(2).normal(size=(B, d)), dtype=jnp.float32)

    pipe = make_pipeline_fn(pp_mesh, mlp_stage, num_microbatches=4)

    def loss_pipe(sp):
        return jnp.mean((pipe(sp, x) - y) ** 2)

    def loss_seq(lp):
        return jnp.mean((sequential_apply(lp, x) - y) ** 2)

    sharded = jax.tree_util.tree_map(
        lambda l: jax.device_put(l, NamedSharding(pp_mesh, P("pp"))), stage_params
    )
    with jax.set_mesh(pp_mesh):
        g_pipe = jax.jit(jax.grad(loss_pipe))(sharded)
    g_seq = jax.grad(loss_seq)(layer_params)
    g_seq_staged = split_params_into_stages(g_seq, 4)
    for a, b in zip(jax.tree_util.tree_leaves(g_pipe), jax.tree_util.tree_leaves(g_seq_staged)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_pipeline_training_through_accelerator(pp_mesh):
    """Train a pipelined model through build_train_step; losses match sequential training."""
    from accelerate_tpu import Accelerator
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    d, L, B = 8, 4, 16
    rng = np.random.default_rng(0)
    x = rng.normal(size=(B, d)).astype(np.float32)
    y = rng.normal(size=(B, d)).astype(np.float32)
    layer_params = make_layer_params(L, d)

    # Sequential baseline.
    def seq_loss(params, batch):
        return jnp.mean((sequential_apply(params, batch["x"]) - batch["y"]) ** 2)

    tx = optax.sgd(0.1)
    p = layer_params
    opt = tx.init(p)
    seq_losses = []
    for _ in range(3):
        l, g = jax.value_and_grad(seq_loss)(p, {"x": jnp.asarray(x), "y": jnp.asarray(y)})
        u, opt = tx.update(g, opt, p)
        p = optax.apply_updates(p, u)
        seq_losses.append(float(l))

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    acc = Accelerator(mesh_config=MeshConfig(dp=2, pp=4))
    pipe = make_pipeline_fn(acc.mesh, mlp_stage, num_microbatches=4)

    stage_params = split_params_into_stages(layer_params, 4)
    specs = jax.tree_util.tree_map(lambda _: P("pp"), stage_params)
    state = acc.create_train_state(stage_params, optax.sgd(0.1), partition_specs=specs)

    def pipe_loss(params, batch):
        return jnp.mean((pipe(params, batch["x"]) - batch["y"]) ** 2)

    step = acc.build_train_step(pipe_loss)
    batch = {"x": jnp.asarray(x), "y": jnp.asarray(y)}
    pipe_losses = []
    for _ in range(3):
        state, m = step(state, batch)
        pipe_losses.append(float(m["loss"]))
    np.testing.assert_allclose(pipe_losses, seq_losses, rtol=1e-5)


# ------------------------------------------------------------------ llama pipeline training
def _llama_pp_setup():
    import dataclasses

    from accelerate_tpu.models import llama
    from accelerate_tpu.parallel.pp import split_params_into_stages

    cfg = dataclasses.replace(
        llama.CONFIGS["tiny"], dtype=jnp.float32, attn_impl="xla", scan_layers=True,
        n_layers=4,
    )
    params = llama.init_params(cfg)
    rng = np.random.default_rng(0)
    batch = {"tokens": rng.integers(0, cfg.vocab_size, size=(8, 17)).astype(np.int32)}
    return cfg, params, batch


@slow
def test_llama_pp_loss_matches_single():
    """forward_pp over a pp=4 mesh == plain forward, for loss and one SGD step."""
    import optax as _optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import llama
    from accelerate_tpu.parallel.pp import split_params_into_stages
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    cfg, params, batch = _llama_pp_setup()
    jbatch = {"tokens": jnp.asarray(batch["tokens"])}

    # Single-device baseline (no pipeline).
    base_loss = float(llama.loss_fn(params, jbatch, cfg))
    base_grads = jax.grad(lambda p: llama.loss_fn(p, jbatch, cfg))(params)

    for s in (AcceleratorState, GradientState, PartialState):
        s._reset_state()
    acc = Accelerator(mesh_config=MeshConfig(dp=2, pp=4))
    stage_params = dict(params)
    stage_params["layers"] = split_params_into_stages(params["layers"], 4)
    specs = llama.partition_specs(cfg, pp=True)
    state = acc.create_train_state(stage_params, _optax.sgd(0.1), partition_specs=specs)
    assert state.params["layers"]["wq"].sharding.spec[0] == "pp"

    step = acc.build_train_step(
        lambda p, b: llama.loss_fn_pp(p, b, cfg, acc.mesh, num_microbatches=4)
    )
    state, metrics = step(state, jbatch)
    np.testing.assert_allclose(float(metrics["loss"]), base_loss, rtol=1e-5)

    # Gradients must match too: compare the pipeline-trained first-step params against a
    # manual SGD step on the baseline grads.
    expected = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params, base_grads)
    expected["layers"] = split_params_into_stages(expected["layers"], 4)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5
        ),
        state.params, expected,
    )


@slow
def test_llama_pp_moe_loss_matches_single():
    """MoE blocks run THROUGH the pipeline (reference runs MoE models in its engine,
    dataclasses.py:1105): CE parity vs non-pipelined forward in the no-drop regime.
    Routing/capacity are per-microbatch under GPipe, so aux_weight=0 + ample capacity is
    the exact-parity configuration; aux flow is asserted separately."""
    import dataclasses

    import optax as _optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import llama
    from accelerate_tpu.parallel.pp import split_params_into_stages
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    cfg = dataclasses.replace(
        llama.CONFIGS["moe-tiny"], dtype=jnp.float32, attn_impl="xla", scan_layers=True,
        moe_aux_weight=0.0, moe_capacity_factor=8.0,  # nothing drops → exact CE
    )
    params = llama.init_params(cfg)
    rng = np.random.default_rng(0)
    jbatch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(8, 17)).astype(np.int32))}
    base_loss = float(llama.loss_fn(params, jbatch, cfg))

    for s in (AcceleratorState, GradientState, PartialState):
        s._reset_state()
    acc = Accelerator(mesh_config=MeshConfig(dp=2, ep=2, pp=2))
    stage_params = dict(params)
    stage_params["layers"] = split_params_into_stages(params["layers"], 2)
    specs = llama.partition_specs(cfg, pp=True)
    state = acc.create_train_state(stage_params, _optax.sgd(0.1), partition_specs=specs)

    step = acc.build_train_step(
        lambda p, b: llama.loss_fn_pp(p, b, cfg, acc.mesh, num_microbatches=4)
    )
    state, metrics = step(state, jbatch)
    np.testing.assert_allclose(float(metrics["loss"]), base_loss, rtol=1e-5)

    # Aux loss flows through the pipeline AND keeps the non-pipelined scale: aux is a
    # mean statistic, so the per-(stage, microbatch) sum must be normalized by M or
    # moe_aux_weight would silently mean M× more under pp (and change with the
    # num_microbatches throughput knob).
    cfg_aux = dataclasses.replace(cfg, moe_aux_weight=1.0)
    base_with_aux = float(llama.loss_fn(params, jbatch, cfg_aux))
    base_aux_term = base_with_aux - base_loss
    with jax.set_mesh(acc.mesh):
        pp_with_aux = float(jax.jit(
            lambda p, b: llama.loss_fn_pp(p, b, cfg_aux, acc.mesh, num_microbatches=4)
        )(dict(stage_params), jbatch))
        pp_no_aux = float(jax.jit(
            lambda p, b: llama.loss_fn_pp(p, b, cfg, acc.mesh, num_microbatches=4)
        )(dict(stage_params), jbatch))
    pp_aux_term = pp_with_aux - pp_no_aux
    assert pp_aux_term > 0, "MoE aux loss did not flow through the pipeline"
    # Per-microbatch routing statistics differ slightly from full-batch ones, but the
    # SCALE must match (ratio ~1, nowhere near M=4).
    assert 0.7 < pp_aux_term / base_aux_term < 1.3, (
        f"pp aux term {pp_aux_term:.4f} vs non-pp {base_aux_term:.4f} — "
        "normalization by num_microbatches lost"
    )


@slow
def test_llama_pp_composed_with_fsdp_tp_and_fused_kernels():
    """The reference's Megatron engine runs tp×pp×dp in ONE job (megatron_lm.py:926);
    this is that composition through the facade: fsdp2 × tp2 × pp2 llama training with
    the fused Pallas optimizer (FusedAdamW) and the vocab-sharded fused CE (fused_tp:
    the head is never gathered over tp) — not raw optax.sgd. Loss parity vs a
    single-device step, and per-device embed/head bytes shrink by the vocab sharding."""
    import dataclasses

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import llama
    from accelerate_tpu.ops.fused_optim import fused_adamw
    from accelerate_tpu.parallel.pp import split_params_into_stages
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    cfg = dataclasses.replace(
        llama.CONFIGS["tiny"], dtype=jnp.float32, attn_impl="xla", scan_layers=True,
        n_layers=4, tie_embeddings=False, loss_impl="fused_tp",
    )
    cfg_base = dataclasses.replace(cfg, loss_impl="auto")
    params = llama.init_params(cfg)
    rng = np.random.default_rng(0)
    jbatch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(8, 17)).astype(np.int32))}

    # Single-device baseline: same loss math, optax.adamw (the rule FusedAdamW implements).
    import optax as _optax

    base_loss = float(llama.loss_fn(params, jbatch, cfg_base))
    tx = _optax.adamw(1e-2)
    opt = tx.init(params)
    g = jax.grad(lambda p: llama.loss_fn(p, jbatch, cfg_base))(params)
    u, opt = tx.update(g, opt, params)
    expected = _optax.apply_updates(params, u)
    expected["layers"] = split_params_into_stages(expected["layers"], 2)

    for s in (AcceleratorState, GradientState, PartialState):
        s._reset_state()
    acc = Accelerator(mesh_config=MeshConfig(fsdp=2, tp=2, pp=2))
    stage_params = dict(params)
    stage_params["layers"] = split_params_into_stages(params["layers"], 2)
    specs = llama.partition_specs(cfg, pp=True)
    state = acc.create_train_state(
        stage_params, fused_adamw(1e-2, weight_decay=1e-4), partition_specs=specs
    )
    # Vocab sharded over (tp, fsdp, pp): each device holds 1/8 of embed and lm_head.
    assert state.params["embed"].sharding.shard_shape(
        state.params["embed"].shape
    )[0] == cfg.vocab_size // 8
    assert state.params["lm_head"].sharding.shard_shape(
        state.params["lm_head"].shape
    )[1] == cfg.vocab_size // 8

    step = acc.build_train_step(
        lambda p, b: llama.loss_fn_pp(p, b, cfg, acc.mesh, num_microbatches=4)
    )
    state, metrics = step(state, jbatch)
    np.testing.assert_allclose(float(metrics["loss"]), base_loss, rtol=1e-4)

    # AdamW's step-1 update m̂/(√v̂+ε) is ill-conditioned where gradients are ~0: the
    # mesh's different psum reduction order turns 1e-8 gradient deltas into ~1e-3 update
    # deltas on isolated elements. Bound the bulk tightly and the tail loosely — a wrong
    # lr / bias correction / weight decay shifts EVERY element by O(lr)=1e-2, which both
    # bounds catch.
    def _compare(a, b):
        diff = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
        assert diff.max() < 5e-3, f"max diff {diff.max()}"
        assert np.quantile(diff, 0.999) < 1e-4, f"p99.9 diff {np.quantile(diff, 0.999)}"

    jax.tree_util.tree_map(_compare, state.params, expected)


def test_llama_pp_requires_scan_layers():
    import dataclasses

    from accelerate_tpu.models import llama

    cfg = dataclasses.replace(llama.CONFIGS["tiny"], scan_layers=False)
    with pytest.raises(ValueError, match="scan_layers"):
        llama.partition_specs(cfg, pp=True)


def test_pp_plugin_schedules():
    from accelerate_tpu.utils.dataclasses import PipelineParallelPlugin

    PipelineParallelPlugin(pp_size=4, schedule="1f1b")  # supported since round 3
    PipelineParallelPlugin(pp_size=4, schedule="gpipe")
    with pytest.raises(ValueError, match="interleaved"):
        PipelineParallelPlugin(pp_size=4, schedule="interleaved")


# ------------------------------------------------------------------------- 1F1B schedule
@pytest.mark.parametrize("n,M", [(2, 2), (2, 8), (4, 4), (4, 8), (4, 32), (8, 16)])
def test_1f1b_schedule_tables_well_formed(n, M):
    """The static simulator must schedule every (stage, microbatch) F and B exactly once,
    respect data dependencies, and prove its own buffer-slot safety (it asserts slot
    collisions internally — this exercises those assertions across shapes)."""
    from accelerate_tpu.parallel.pp import _simulate_1f1b

    s = _simulate_1f1b(n, M)
    T = s.fwd.shape[0]
    for stage in range(n):
        fs = [int(s.fwd[t, stage]) for t in range(T) if s.fwd[t, stage] >= 0]
        bs = [int(s.bwd[t, stage]) for t in range(T) if s.bwd[t, stage] >= 0]
        assert fs == list(range(M)), f"stage {stage} forward order {fs}"
        assert bs == list(range(M)), f"stage {stage} backward order {bs}"
    # Dependency spot check: stage s forwards m only after s-1 did (strictly earlier).
    f_tick = {(stage, int(s.fwd[t, stage])): t
              for t in range(T) for stage in range(n) if s.fwd[t, stage] >= 0}
    for stage in range(1, n):
        for m in range(M):
            assert f_tick[(stage, m)] > f_tick[(stage - 1, m)]
    # In-flight bound: the whole point of 1F1B vs GPipe.
    for stage in range(n):
        live = 0
        for t in range(T):
            live += int(s.fwd[t, stage] >= 0) - int(s.bwd[t, stage] >= 0)
            assert live <= n, f"stage {stage} holds {live} > n in-flight at tick {t}"


def test_1f1b_bf16_head_params(pp_mesh):
    """Regression: lax.cond branches must agree on dtypes when head params are bf16
    (plain_branch zero-fills in hp's own dtype)."""
    from accelerate_tpu.parallel.pp import make_pipeline_loss_fn

    d, L, B = 8, 4, 8
    rng = np.random.default_rng(3)
    layer_params = make_layer_params(L, d)
    head_params = {"wout": jnp.asarray(rng.normal(size=(d, d)) * 0.1, jnp.bfloat16)}
    x = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)
    tgt = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)

    def head_loss(hp, y, extras):
        return jnp.sum((y @ hp["wout"].astype(jnp.float32) - extras["tgt"]) ** 2)

    loss_fn = make_pipeline_loss_fn(
        pp_mesh, mlp_stage, head_loss, num_microbatches=4, schedule="1f1b"
    )
    stage_params = split_params_into_stages(layer_params, 4)
    with jax.set_mesh(pp_mesh):
        l, grads = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1)))(
            stage_params, head_params, x, {"tgt": tgt}
        )
    assert np.isfinite(float(l))
    assert grads[1]["wout"].dtype == jnp.bfloat16
    assert float(jnp.abs(grads[1]["wout"].astype(jnp.float32)).sum()) > 0


def test_pp_schedule_property():
    """PipelineParallelPlugin(schedule=...) must be readable through the facade —
    configuring 1f1b on the plugin and getting GPipe silently would be a dead knob."""
    from accelerate_tpu import Accelerator
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.utils import PipelineParallelPlugin

    for s in (AcceleratorState, GradientState, PartialState):
        s._reset_state()
    acc = Accelerator(
        mesh_config=MeshConfig(dp=2, pp=4),
        pp_plugin=PipelineParallelPlugin(
            pp_size=4, num_microbatches=8, schedule="1f1b", virtual_stages=2
        ),
    )
    assert acc.pp_schedule == "1f1b"
    assert acc.num_microbatches == 8
    assert acc.virtual_stages == 2
    with pytest.raises(ValueError, match="virtual_stages"):
        PipelineParallelPlugin(pp_size=4, schedule="gpipe", virtual_stages=2)


@slow
def test_llama_pp_interleaved_matches_single():
    """Interleaved virtual pipeline on the flagship family: llama at pp=2 with v=2
    chunks per device (strided layer assignment, circular activation flow) matches the
    non-pipelined loss and grads under 1f1b."""
    import dataclasses as _dc

    from accelerate_tpu.models import llama

    cfg = _dc.replace(
        llama.CONFIGS["tiny"], dtype=jnp.float32, attn_impl="xla", scan_layers=True,
        n_layers=8,
    )
    params = llama.init_params(cfg)
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 17)), jnp.int32)}
    base = float(llama.loss_fn(params, batch, cfg))
    base_g = jax.grad(lambda p: llama.loss_fn(p, batch, cfg))(params)

    mesh = build_mesh(MeshConfig(dp=4, pp=2))
    sp = dict(params)
    sp["layers"] = split_params_into_stages(params["layers"], 2, virtual_stages=2)
    with jax.set_mesh(mesh):
        l, g = jax.jit(jax.value_and_grad(
            lambda p, b: llama.loss_fn_pp(
                p, b, cfg, mesh, num_microbatches=8, schedule="1f1b",
                virtual_stages=2)
        ))(sp, batch)
    np.testing.assert_allclose(float(l), base, rtol=1e-5)
    expected = dict(base_g)
    expected["layers"] = split_params_into_stages(
        base_g["layers"], 2, virtual_stages=2
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5
        ),
        dict(g), expected,
    )


def test_1f1b_grads_match_sequential(pp_mesh):
    """make_pipeline_loss_fn('1f1b'): loss and ALL grads (stage params, head params,
    input cotangent) equal the sequential model."""
    from accelerate_tpu.parallel.pp import make_pipeline_loss_fn

    d, L, B, n, M = 8, 8, 16, 4, 8
    rng = np.random.default_rng(0)
    layer_params = make_layer_params(L, d)
    head_params = {"wout": jnp.asarray(rng.normal(size=(d, d)) * 0.1, jnp.float32)}
    x = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)
    tgt = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)

    def head_loss(hp, y, extras):
        return jnp.sum((y @ hp["wout"] - extras["tgt"]) ** 2)

    def seq_loss(lp, hp, x):
        return head_loss(hp, sequential_apply(lp, x), {"tgt": tgt})

    ref_loss, ref_grads = jax.value_and_grad(seq_loss, argnums=(0, 1, 2))(
        layer_params, head_params, x
    )
    stage_params = split_params_into_stages(layer_params, n)
    loss_fn = make_pipeline_loss_fn(
        pp_mesh, mlp_stage, head_loss, num_microbatches=M, schedule="1f1b"
    )
    with jax.set_mesh(pp_mesh):
        l, grads = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1, 2)))(
            stage_params, head_params, x, {"tgt": tgt}
        )
    np.testing.assert_allclose(float(l), float(ref_loss), rtol=1e-6)
    gp, gh, gx = grads
    rp, rh, rx = ref_grads
    for a, b in zip(
        jax.tree_util.tree_leaves(gp),
        jax.tree_util.tree_leaves(split_params_into_stages(rp, n)),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    np.testing.assert_allclose(np.asarray(gh["wout"]), np.asarray(rh["wout"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), atol=1e-5)


@pytest.mark.parametrize("n,v,M", [(4, 2, 8), (2, 4, 8), (2, 2, 8)])
def test_interleaved_1f1b_grads_match_sequential(n, v, M):
    """Interleaved/virtual-pipeline 1F1B (the Megatron virtual_pipeline analog,
    reference dataclasses.py:2024): device s hosts the STRIDED virtual stages
    {s, n+s, ...}, activations wrap circularly, and loss + ALL grads (stage params,
    head params, input cotangent) equal the sequential model."""
    from accelerate_tpu.parallel.pp import make_pipeline_loss_fn

    d, L, B = 8, n * v * 2, 16
    rng = np.random.default_rng(0)
    layer_params = make_layer_params(L, d)
    head_params = {"wout": jnp.asarray(rng.normal(size=(d, d)) * 0.1, jnp.float32)}
    x = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)
    tgt = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)

    def head_loss(hp, y, extras):
        return jnp.sum((y @ hp["wout"] - extras["tgt"]) ** 2)

    ref_loss, ref_grads = jax.value_and_grad(
        lambda lp, hp, xx: head_loss(hp, sequential_apply(lp, xx), {"tgt": tgt}),
        argnums=(0, 1, 2),
    )(layer_params, head_params, x)

    mesh = build_mesh(MeshConfig(dp=8 // n, pp=n))
    stage_params = split_params_into_stages(layer_params, n, virtual_stages=v)
    loss_fn = make_pipeline_loss_fn(
        mesh, mlp_stage, head_loss, num_microbatches=M, schedule="1f1b",
        virtual_stages=v,
    )
    with jax.set_mesh(mesh):
        l, grads = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1, 2)))(
            stage_params, head_params, x, {"tgt": tgt}
        )
    np.testing.assert_allclose(float(l), float(ref_loss), rtol=1e-6)
    gp, gh, gx = grads
    rp = split_params_into_stages(ref_grads[0], n, virtual_stages=v)
    for a, b in zip(jax.tree_util.tree_leaves(gp), jax.tree_util.tree_leaves(rp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(gh["wout"]), np.asarray(ref_grads[1]["wout"]), atol=1e-5
    )
    np.testing.assert_allclose(np.asarray(gx), np.asarray(ref_grads[2]), atol=1e-5)


def test_1f1b_float_extras_cotangent_matches_sequential(pp_mesh):
    """ADVICE r3: the loss genuinely depends on float extras (targets, loss masks) —
    differentiating w.r.t. them must give the TRUE head-VJP cotangent (the custom VJP
    used to return silent zeros)."""
    from accelerate_tpu.parallel.pp import make_pipeline_loss_fn

    d, L, B, n, M = 8, 8, 16, 4, 8
    rng = np.random.default_rng(7)
    layer_params = make_layer_params(L, d)
    head_params = {"wout": jnp.asarray(rng.normal(size=(d, d)) * 0.1, jnp.float32)}
    x = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)
    tgt = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)

    def head_loss(hp, y, extras):
        return jnp.sum((y @ hp["wout"] - extras["tgt"]) ** 2)

    ref = jax.grad(
        lambda ex: head_loss(head_params, sequential_apply(layer_params, x), ex)
    )({"tgt": tgt})
    loss_fn = make_pipeline_loss_fn(
        pp_mesh, mlp_stage, head_loss, num_microbatches=M, schedule="1f1b"
    )
    with jax.set_mesh(pp_mesh):
        got = jax.jit(jax.grad(loss_fn, argnums=3))(
            split_params_into_stages(layer_params, n), head_params, x, {"tgt": tgt}
        )
    assert float(jnp.abs(got["tgt"]).sum()) > 0  # the old contract returned zeros
    np.testing.assert_allclose(np.asarray(got["tgt"]), np.asarray(ref["tgt"]), atol=1e-5)


@slow
def test_llama_pp_1f1b_matches_single():
    """llama loss_fn_pp(schedule='1f1b') == plain loss_fn, loss and one full train step
    through the facade (tied embeddings: the embed grad sums the lookup AND head paths
    through the custom VJP's dx / d_head outputs)."""
    import optax as _optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import llama
    from accelerate_tpu.parallel.pp import split_params_into_stages
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    cfg, params, batch = _llama_pp_setup()
    jbatch = {"tokens": jnp.asarray(batch["tokens"])}
    base_loss = float(llama.loss_fn(params, jbatch, cfg))
    base_grads = jax.grad(lambda p: llama.loss_fn(p, jbatch, cfg))(params)

    for s in (AcceleratorState, GradientState, PartialState):
        s._reset_state()
    acc = Accelerator(mesh_config=MeshConfig(dp=2, pp=4))
    stage_params = dict(params)
    stage_params["layers"] = split_params_into_stages(params["layers"], 4)
    state = acc.create_train_state(
        stage_params, _optax.sgd(0.1),
        partition_specs=llama.partition_specs(cfg, pp=True),
    )
    step = acc.build_train_step(
        lambda p, b: llama.loss_fn_pp(
            p, b, cfg, acc.mesh, num_microbatches=8, schedule="1f1b"
        )
    )
    state, metrics = step(state, jbatch)
    np.testing.assert_allclose(float(metrics["loss"]), base_loss, rtol=1e-5)
    expected = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params, base_grads)
    expected["layers"] = split_params_into_stages(expected["layers"], 4)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5
        ),
        state.params, expected,
    )


def test_prepare_pippy_logits_match_plain_forward():
    """prepare_pippy (the reference inference.py analog): pipelined logits == plain."""
    import dataclasses

    from accelerate_tpu import prepare_pippy
    from accelerate_tpu.models import llama
    from accelerate_tpu.parallel import build_mesh

    cfg = dataclasses.replace(
        llama.CONFIGS["tiny"], dtype=jnp.float32, attn_impl="xla", n_layers=4,
        scan_layers=False,  # per-layer list input: prepare_pippy stage-stacks it
    )
    params = llama.init_params(cfg)
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, size=(8, 16)).astype(np.int32)
    plain = llama.forward(params, jnp.asarray(tokens), cfg, shard_activations=False)

    mesh = build_mesh(MeshConfig(dp=2, pp=4))
    pp_params, forward = prepare_pippy(params, cfg, mesh=mesh, num_microbatches=4)
    assert pp_params["layers"]["wq"].sharding.spec[0] == "pp"
    piped = forward(tokens)
    np.testing.assert_allclose(np.asarray(piped), np.asarray(plain), atol=2e-4, rtol=1e-4)


# --------------------------------------------------------------------- gpt family pp
@slow
@pytest.mark.parametrize("schedule,M", [("gpipe", 4), ("1f1b", 8)])
def test_gpt_pp_matches_single(schedule, M):
    """The reference's Megatron engine runs GPT with pp; our gpt family gets the same
    pipeline contract as llama (both schedules), including the gpt-j-style untied,
    BIASED lm_head through the 1F1B last-stage loss."""
    import dataclasses as _dc

    from accelerate_tpu.models import gpt

    cfg = _dc.replace(
        gpt.CONFIGS["tiny"], dtype=jnp.float32, scan_layers=True, n_layers=4,
        tie_embeddings=False, lm_head_bias=True, pos="rotary",
        parallel_residual=True,
    )
    params = gpt.init_params(cfg)
    # A nonzero head bias so the biased path is actually load-bearing in the parity.
    params["b_lm_head"] = jnp.asarray(
        np.random.default_rng(2).normal(size=(cfg.vocab_size,)) * 0.1, jnp.float32
    )
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (8, 17)), jnp.int32)}
    base = float(gpt.loss_fn(params, batch, cfg))
    base_g = jax.grad(lambda p: gpt.loss_fn(p, batch, cfg))(params)

    mesh = build_mesh(MeshConfig(dp=2, pp=4))
    sp = dict(params)
    sp["layers"] = split_params_into_stages(params["layers"], 4)
    with jax.set_mesh(mesh):
        l, g = jax.jit(jax.value_and_grad(
            lambda p, b: gpt.loss_fn_pp(
                p, b, cfg, mesh, num_microbatches=M, schedule=schedule)
        ))(sp, batch)
    np.testing.assert_allclose(float(l), base, rtol=1e-5)
    expected = dict(base_g)
    expected["layers"] = split_params_into_stages(base_g["layers"], 4)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5
        ),
        dict(g), expected,
    )


def _packed_batch(vocab: int, B: int, seq_len: int, seed: int) -> dict:
    """A sample-packed batch (ops/packing.py) tiled/truncated to exactly B rows (the
    pipeline needs B % num_microbatches == 0, which raw packing doesn't guarantee)."""
    from accelerate_tpu.ops import packing

    rng = np.random.default_rng(seed)
    seqs = [
        rng.integers(1, vocab, size=int(n)).astype(np.int32)
        for n in rng.integers(3, seq_len, size=4 * B)
    ]
    packed = packing.pack_sequences(seqs, seq_len=seq_len, use_native=False)
    return {
        k: jnp.asarray(np.resize(v, (B, v.shape[1]))) for k, v in packed.items()
    }


@slow
@pytest.mark.parametrize("family", ["llama", "gpt"])
@pytest.mark.parametrize("schedule,M", [("gpipe", 4), ("1f1b", 8)])
def test_pp_packed_matches_single(family, schedule, M):
    """Sample packing composes with pipeline parallelism: segment ids /
    per-segment positions ride the pipeline as per-microbatch side constants (indexed by
    microbatch id, never ppermuted), restricting attention to the block-diagonal mask in
    every stage. Parity of loss AND grads vs the non-pipelined packed path, both
    schedules, llama + gpt."""
    import dataclasses as _dc

    import importlib

    mod = importlib.import_module(f"accelerate_tpu.models.{family}")
    cfg = _dc.replace(
        mod.CONFIGS["tiny"], dtype=jnp.float32, scan_layers=True, n_layers=4,
        **({"attn_impl": "xla"} if family == "llama" else {}),
    )
    params = mod.init_params(cfg)
    batch = _packed_batch(cfg.vocab_size, 8, 17, seed=5)
    base = float(mod.loss_fn(params, batch, cfg))
    base_g = jax.grad(lambda p: mod.loss_fn(p, batch, cfg))(params)

    mesh = build_mesh(MeshConfig(dp=2, pp=4))
    sp = dict(params)
    sp["layers"] = split_params_into_stages(params["layers"], 4)
    with jax.set_mesh(mesh):
        l, g = jax.jit(jax.value_and_grad(
            lambda p, b: mod.loss_fn_pp(
                p, b, cfg, mesh, num_microbatches=M, schedule=schedule)
        ))(sp, batch)
    np.testing.assert_allclose(float(l), base, rtol=1e-5)
    expected = dict(base_g)
    expected["layers"] = split_params_into_stages(base_g["layers"], 4)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5
        ),
        dict(g), expected,
    )


@slow
@pytest.mark.parametrize("schedule,M", [("gpipe", 4), ("1f1b", 8)])
@pytest.mark.parametrize("loss_impl", ["fused", "fused_tp"])
def test_gpt_pp_fused_loss_matches_single(schedule, M, loss_impl):
    """gpt's pipeline carries the FULL loss_impl contract: the fused Pallas CE kernels dispatch
    from the gpt head on both schedules, because ln_f + head run outside the pipe on the
    full batch. fused_tp keeps the head vocab-sharded over tp (Megatron layout,
    reference megatron_lm.py:588's GPT loss)."""
    import dataclasses as _dc

    from accelerate_tpu.models import gpt

    cfg = _dc.replace(
        gpt.CONFIGS["tiny"], dtype=jnp.float32, scan_layers=True, n_layers=4,
        tie_embeddings=False, loss_impl=loss_impl,
    )
    cfg_base = _dc.replace(cfg, loss_impl="auto")
    params = gpt.init_params(cfg)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 17)), jnp.int32)}
    base = float(gpt.loss_fn(params, batch, cfg_base))
    base_g = jax.grad(lambda p: gpt.loss_fn(p, batch, cfg_base))(params)

    mesh = build_mesh(
        MeshConfig(dp=2, tp=2, pp=2) if loss_impl == "fused_tp"
        else MeshConfig(dp=2, pp=4)
    )
    n_stages = mesh.shape["pp"]
    sp = dict(params)
    sp["layers"] = split_params_into_stages(params["layers"], n_stages)
    with jax.set_mesh(mesh):
        l, g = jax.jit(jax.value_and_grad(
            lambda p, b: gpt.loss_fn_pp(
                p, b, cfg, mesh, num_microbatches=M, schedule=schedule)
        ))(sp, batch)
    np.testing.assert_allclose(float(l), base, rtol=1e-5)
    expected = dict(base_g)
    expected["layers"] = split_params_into_stages(base_g["layers"], n_stages)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5
        ),
        dict(g), expected,
    )


@slow
@pytest.mark.parametrize("family", ["llama", "gpt"])
def test_pp_interleaved_packed_matches_single(family):
    """Sample packing composes with the interleaved pipeline: segment ids / positions
    ride as int side constants through the virtual-stage replay — both families (the
    packed stage bodies differ per family even though the pp machinery is shared)."""
    import dataclasses as _dc
    import importlib

    mod = importlib.import_module(f"accelerate_tpu.models.{family}")
    cfg = _dc.replace(
        mod.CONFIGS["tiny"], dtype=jnp.float32, scan_layers=True, n_layers=8,
        **({"attn_impl": "xla"} if family == "llama" else {}),
    )
    params = mod.init_params(cfg)
    batch = _packed_batch(cfg.vocab_size, 8, 17, seed=5)
    base = float(mod.loss_fn(params, batch, cfg))
    base_g = jax.grad(lambda p: mod.loss_fn(p, batch, cfg))(params)

    mesh = build_mesh(MeshConfig(dp=4, pp=2))
    sp = dict(params)
    sp["layers"] = split_params_into_stages(params["layers"], 2, virtual_stages=2)
    with jax.set_mesh(mesh):
        l, g = jax.jit(jax.value_and_grad(
            lambda p, b: mod.loss_fn_pp(
                p, b, cfg, mesh, num_microbatches=8, schedule="1f1b",
                virtual_stages=2)
        ))(sp, batch)
    np.testing.assert_allclose(float(l), base, rtol=1e-5)
    expected = dict(base_g)
    expected["layers"] = split_params_into_stages(base_g["layers"], 2, virtual_stages=2)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5
        ),
        dict(g), expected,
    )


@slow
@pytest.mark.parametrize("virtual_stages", [1, 2])
def test_llama_pp_sp_ulysses_replay_matches_single(virtual_stages):
    """ulysses inside the hand-scheduled replay (formerly a NotImplementedError: the
    all_to_all PRIMITIVE hangs at lowering there) now runs via the ppermute-decomposed
    all-to-all (sequence._a2a_ppermute, substituted automatically): loss + all grads
    match the non-pipelined, non-sp run at dp2 x sp2 x pp2, flat AND interleaved."""
    import dataclasses as _dc

    from accelerate_tpu.models import llama

    cfg = _dc.replace(
        llama.CONFIGS["tiny"], dtype=jnp.float32, attn_impl="ulysses", scan_layers=True,
        n_layers=4,
    )
    params = llama.init_params(cfg)
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 17)), jnp.int32)}
    base = float(llama.loss_fn(params, batch, cfg))
    base_g = jax.grad(lambda p: llama.loss_fn(p, batch, cfg))(params)

    sp = dict(params)
    sp["layers"] = split_params_into_stages(
        params["layers"], 2, virtual_stages=virtual_stages
    )
    mesh = build_mesh(MeshConfig(dp=2, sp=2, pp=2))
    with jax.set_mesh(mesh):
        l, g = jax.jit(jax.value_and_grad(
            lambda p, b: llama.loss_fn_pp(
                p, b, cfg, mesh, num_microbatches=4, schedule="1f1b",
                virtual_stages=virtual_stages)
        ))(sp, batch)
    np.testing.assert_allclose(float(l), base, rtol=1e-5)
    expected = dict(base_g)
    expected["layers"] = split_params_into_stages(
        base_g["layers"], 2, virtual_stages=virtual_stages
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5
        ),
        dict(g), expected,
    )


@slow
@pytest.mark.parametrize("mode", ["ring", "allgather"])
def test_llama_pp_sp_interleaved_matches_single(mode):
    """sp-attention composes with the interleaved pipeline: sequence-sliced
    activations through the virtual-stage replay, sp collectives issued flat inside
    each chunk's stage body, dp psum'd over sp — parity at dp2 x sp2 x pp2 with v=2."""
    import dataclasses as _dc

    from accelerate_tpu.models import llama

    cfg = _dc.replace(
        llama.CONFIGS["tiny"], dtype=jnp.float32, attn_impl=mode, scan_layers=True,
        n_layers=8,
    )
    params = llama.init_params(cfg)
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 17)), jnp.int32)}
    base = float(llama.loss_fn(params, batch, cfg))
    base_g = jax.grad(lambda p: llama.loss_fn(p, batch, cfg))(params)

    sp = dict(params)
    sp["layers"] = split_params_into_stages(params["layers"], 2, virtual_stages=2)
    mesh = build_mesh(MeshConfig(dp=2, sp=2, pp=2))
    with jax.set_mesh(mesh):
        l, g = jax.jit(jax.value_and_grad(
            lambda p, b: llama.loss_fn_pp(
                p, b, cfg, mesh, num_microbatches=8, schedule="1f1b",
                virtual_stages=2)
        ))(sp, batch)
    np.testing.assert_allclose(float(l), base, rtol=1e-5)
    expected = dict(base_g)
    expected["layers"] = split_params_into_stages(base_g["layers"], 2, virtual_stages=2)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5
        ),
        dict(g), expected,
    )


@slow
def test_llama_pp_moe_interleaved_matches_single():
    """MoE through the interleaved pipeline: exact CE parity in the no-drop regime
    with aux_weight=0, and the aux term at ~1x the non-pipelined scale with a real
    weight (aux accumulates over M * n * v live (chunk-stage, microbatch) pairs)."""
    import dataclasses as _dc

    from accelerate_tpu.models import llama

    cfg = _dc.replace(
        llama.CONFIGS["moe-tiny"], dtype=jnp.float32, attn_impl="xla", scan_layers=True,
        n_layers=4, moe_aux_weight=0.0, moe_capacity_factor=8.0,
    )
    params = llama.init_params(cfg)
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 17)), jnp.int32)}
    base = float(llama.loss_fn(params, batch, cfg))
    base_g = jax.grad(lambda p: llama.loss_fn(p, batch, cfg))(params)

    mesh = build_mesh(MeshConfig(dp=2, ep=2, pp=2))
    sp = dict(params)
    sp["layers"] = split_params_into_stages(params["layers"], 2, virtual_stages=2)
    with jax.set_mesh(mesh):
        l, g = jax.jit(jax.value_and_grad(
            lambda p, b: llama.loss_fn_pp(
                p, b, cfg, mesh, num_microbatches=4, schedule="1f1b",
                virtual_stages=2)
        ))(sp, batch)
    np.testing.assert_allclose(float(l), base, rtol=1e-5)
    expected = dict(base_g)
    expected["layers"] = split_params_into_stages(base_g["layers"], 2, virtual_stages=2)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5
        ),
        dict(g), expected,
    )

    # Aux scale with a real weight stays ~1x the non-pipelined value, and the router
    # weights get nonzero grads THROUGH the interleaved replay's aux_ct term (they
    # also touch the loss via CE, so check the aux-specific DELTA of the router grad).
    cfg_aux = _dc.replace(cfg, moe_aux_weight=1.0)
    base_aux_term = float(llama.loss_fn(params, batch, cfg_aux)) - base
    with jax.set_mesh(mesh):
        l_aux, g_aux = jax.jit(jax.value_and_grad(
            lambda p, b: llama.loss_fn_pp(
                p, b, cfg_aux, mesh, num_microbatches=4, schedule="1f1b",
                virtual_stages=2)
        ))(sp, batch)
    ratio = (float(l_aux) - float(l)) / base_aux_term
    assert 0.7 < ratio < 1.4, f"aux scale ratio {ratio}"
    router_delta = np.abs(
        np.asarray(g_aux["layers"]["moe"]["w_router"])
        - np.asarray(g["layers"]["moe"]["w_router"])
    ).max()
    assert router_delta > 1e-6, "aux cotangent dropped from the interleaved replay"


@slow
def test_llama_pp_moe_sp_interleaved_matches_single():
    """The full stack in one job: MoE x sp-attention x interleaved virtual pipeline
    (with_aux + extra_manual_axes + v>1 together — the aux psum-mean over sp and the
    /sp aux cotangent interact only here). Exact CE parity in the no-drop regime."""
    import dataclasses as _dc

    from accelerate_tpu.models import llama

    cfg = _dc.replace(
        llama.CONFIGS["moe-tiny"], dtype=jnp.float32, attn_impl="ring", scan_layers=True,
        n_layers=8, moe_aux_weight=0.0, moe_capacity_factor=8.0,
    )
    params = llama.init_params(cfg)
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 17)), jnp.int32)}
    base = float(llama.loss_fn(params, batch, cfg))
    base_g = jax.grad(lambda p: llama.loss_fn(p, batch, cfg))(params)

    sp = dict(params)
    sp["layers"] = split_params_into_stages(params["layers"], 2, virtual_stages=2)
    mesh = build_mesh(MeshConfig(dp=2, sp=2, pp=2))
    with jax.set_mesh(mesh):
        l, g = jax.jit(jax.value_and_grad(
            lambda p, b: llama.loss_fn_pp(
                p, b, cfg, mesh, num_microbatches=4, schedule="1f1b",
                virtual_stages=2)
        ))(sp, batch)
    np.testing.assert_allclose(float(l), base, rtol=1e-5)
    expected = dict(base_g)
    expected["layers"] = split_params_into_stages(base_g["layers"], 2, virtual_stages=2)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5
        ),
        dict(g), expected,
    )

    # Aux scale: with the /sp cotangent and psum-mean both active, the aux term still
    # reads ~1x (a double /sp would read ~0.5x, a missing one ~2x).
    cfg_aux = _dc.replace(cfg, moe_aux_weight=1.0)
    base_aux_term = float(llama.loss_fn(params, batch, cfg_aux)) - base
    with jax.set_mesh(mesh):
        l_aux = jax.jit(
            lambda p, b: llama.loss_fn_pp(
                p, b, cfg_aux, mesh, num_microbatches=4, schedule="1f1b",
                virtual_stages=2)
        )(sp, batch)
    ratio = (float(l_aux) - float(l)) / base_aux_term
    assert 0.7 < ratio < 1.4, f"aux scale ratio {ratio}"


@slow
def test_gpt_pp_interleaved_matches_single():
    """gpt carries virtual_stages too (llama is not special): pp=2 v=2 strided chunks
    under 1f1b match the non-pipelined run."""
    import dataclasses as _dc

    from accelerate_tpu.models import gpt

    cfg = _dc.replace(
        gpt.CONFIGS["tiny"], dtype=jnp.float32, scan_layers=True, n_layers=8,
    )
    params = gpt.init_params(cfg)
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 17)), jnp.int32)}
    base = float(gpt.loss_fn(params, batch, cfg))
    base_g = jax.grad(lambda p: gpt.loss_fn(p, batch, cfg))(params)

    mesh = build_mesh(MeshConfig(dp=4, pp=2))
    sp = dict(params)
    sp["layers"] = split_params_into_stages(params["layers"], 2, virtual_stages=2)
    with jax.set_mesh(mesh):
        l, g = jax.jit(jax.value_and_grad(
            lambda p, b: gpt.loss_fn_pp(
                p, b, cfg, mesh, num_microbatches=8, schedule="1f1b",
                virtual_stages=2)
        ))(sp, batch)
    np.testing.assert_allclose(float(l), base, rtol=1e-5)
    expected = dict(base_g)
    expected["layers"] = split_params_into_stages(base_g["layers"], 2, virtual_stages=2)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5
        ),
        dict(g), expected,
    )


@slow
def test_llama_pp_1f1b_with_tensor_parallel():
    """Regression: 1F1B on a tp x pp mesh. The first 1F1B kernel branched the head/stage
    VJP per stage with lax.cond; GSPMD's tp collectives inside the branch then
    deadlocked the mesh (only last-stage devices arrived at the rendezvous). The
    restructure runs the head VJP OUTSIDE the pipeline and keeps the per-tick program
    uniform — this test deadlocks (times out) if that regresses. The head loss is the
    vocab-sharded fused_tp kernel, legal under 1f1b since that restructure."""
    import dataclasses as _dc
    import optax as _optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import llama
    from accelerate_tpu.parallel.pp import split_params_into_stages
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    cfg = _dc.replace(
        llama.CONFIGS["tiny"], dtype=jnp.float32, attn_impl="xla", scan_layers=True,
        n_layers=4, tie_embeddings=False, loss_impl="fused_tp",
    )
    cfg_base = _dc.replace(cfg, loss_impl="auto")
    params = llama.init_params(cfg)
    rng = np.random.default_rng(0)
    jbatch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (8, 17)).astype(np.int32))}
    base_loss = float(llama.loss_fn(params, jbatch, cfg_base))

    for s in (AcceleratorState, GradientState, PartialState):
        s._reset_state()
    acc = Accelerator(mesh_config=MeshConfig(dp=2, tp=2, pp=2))
    stage_params = dict(params)
    stage_params["layers"] = split_params_into_stages(params["layers"], 2)
    state = acc.create_train_state(
        stage_params, _optax.sgd(0.1),
        partition_specs=llama.partition_specs(cfg, pp=True),
    )
    assert state.params["layers"]["wq"].sharding.spec[3] == "tp"
    step = acc.build_train_step(
        lambda p, b: llama.loss_fn_pp(
            p, b, cfg, acc.mesh, num_microbatches=4, schedule="1f1b"
        )
    )
    state, metrics = step(state, jbatch)
    np.testing.assert_allclose(float(metrics["loss"]), base_loss, rtol=1e-5)


def test_prepare_pippy_gpt_logits_match_plain_forward():
    """prepare_pippy is family-generic (the reference's is model-generic): gpt params
    route to gpt.forward_pp + biased head."""
    import dataclasses

    from accelerate_tpu import prepare_pippy
    from accelerate_tpu.models import gpt

    cfg = dataclasses.replace(
        gpt.CONFIGS["tiny"], dtype=jnp.float32, n_layers=4,
        scan_layers=False,  # per-layer list input: prepare_pippy stage-stacks it
        tie_embeddings=False, lm_head_bias=True,
    )
    params = gpt.init_params(cfg)
    params["b_lm_head"] = jnp.asarray(
        np.random.default_rng(3).normal(size=(cfg.vocab_size,)) * 0.1, jnp.float32
    )
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(8, 16)).astype(np.int32)
    plain = gpt.forward(params, jnp.asarray(tokens), cfg, shard_activations=False)

    mesh = build_mesh(MeshConfig(dp=2, pp=4))
    pp_params, forward = prepare_pippy(params, cfg, mesh=mesh, num_microbatches=4)
    assert pp_params["layers"]["wqkv"].sharding.spec[0] == "pp"
    piped = forward(tokens)
    np.testing.assert_allclose(np.asarray(piped), np.asarray(plain), atol=2e-4, rtol=1e-4)


def test_prepare_pippy_softcap_and_unknown_config():
    """Gemma-style final_softcap must survive the pipelined head (regression: the old
    inline head skipped it), and non-llama/gpt configs fail fast with a clear error."""
    import dataclasses

    from accelerate_tpu import prepare_pippy
    from accelerate_tpu.models import llama

    cfg = dataclasses.replace(
        llama.CONFIGS["tiny"], dtype=jnp.float32, attn_impl="xla", n_layers=4,
        scan_layers=True, final_softcap=5.0,
    )
    params = llama.init_params(cfg)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(8, 16)).astype(np.int32)
    plain = llama.forward(params, jnp.asarray(tokens), cfg, shard_activations=False)
    mesh = build_mesh(MeshConfig(dp=2, pp=4))
    _, forward = prepare_pippy(params, cfg, mesh=mesh, num_microbatches=4)
    np.testing.assert_allclose(
        np.asarray(forward(tokens)), np.asarray(plain), atol=2e-4, rtol=1e-4
    )

    with pytest.raises(TypeError, match="llama/gpt"):
        prepare_pippy({}, object(), mesh=mesh)


@slow
@pytest.mark.parametrize(
    "mode,schedule,M",
    [("ring", "gpipe", 4), ("ring", "1f1b", 4),
     ("ulysses", "gpipe", 4), ("allgather", "1f1b", 4)],
)
def test_llama_pp_sp_attention_matches_single(mode, schedule, M):
    """sp attention TRAINS inside the pipeline: the pipeline's shard_map goes manual over sp too, activations
    ride sequence-sliced, and the stage body issues the ring/ulysses collectives
    directly (flat shard_map, no nesting — the nested form failed MLIR verification on
    the backward). Loss and ALL grads match the non-pipelined, non-sp run at
    dp2 x sp2 x pp2, both schedules."""
    import dataclasses as _dc

    from accelerate_tpu.models import llama

    cfg = _dc.replace(
        llama.CONFIGS["tiny"], dtype=jnp.float32, attn_impl=mode, scan_layers=True,
        n_layers=4,
    )
    # Baseline: same math, no mesh context → the sp modes fall back to local attention.
    params = llama.init_params(cfg)
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 17)), jnp.int32)}
    base = float(llama.loss_fn(params, batch, cfg))
    base_g = jax.grad(lambda p: llama.loss_fn(p, batch, cfg))(params)

    sp = dict(params)
    sp["layers"] = split_params_into_stages(params["layers"], 2)
    mesh = build_mesh(MeshConfig(dp=2, sp=2, pp=2))
    with jax.set_mesh(mesh):
        l, g = jax.jit(jax.value_and_grad(
            lambda p, b: llama.loss_fn_pp(
                p, b, cfg, mesh, num_microbatches=M, schedule=schedule)
        ))(sp, batch)
    np.testing.assert_allclose(float(l), base, rtol=1e-5)
    expected = dict(base_g)
    expected["layers"] = split_params_into_stages(base_g["layers"], 2)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5
        ),
        dict(g), expected,
    )


@slow
@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_llama_pp_sp_moe_matches_single(schedule):
    """MoE composes with sp-attention-in-pp: each sp member routes its own sequence
    slice, the aux statistic is psum-meaned over sp, and the 1f1b replay's aux
    cotangent is scaled to match. Exact CE parity in the no-drop regime with
    aux_weight=0 (the aux stat is nonlinear in its token population, so sp slicing —
    like pp microbatching — shifts it slightly: the same caveat the plain MoE-pp test
    documents); with a real weight the aux term stays ~1x the non-pipelined scale."""
    import dataclasses as _dc

    from accelerate_tpu.models import llama

    cfg = _dc.replace(
        llama.CONFIGS["moe-tiny"], dtype=jnp.float32, attn_impl="ring", scan_layers=True,
        moe_aux_weight=0.0, moe_capacity_factor=8.0,
    )
    params = llama.init_params(cfg)
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 17)), jnp.int32)}
    base = float(llama.loss_fn(params, batch, cfg))
    base_g = jax.grad(lambda p: llama.loss_fn(p, batch, cfg))(params)

    sp = dict(params)
    sp["layers"] = split_params_into_stages(params["layers"], 2)
    mesh = build_mesh(MeshConfig(dp=2, sp=2, pp=2))
    with jax.set_mesh(mesh):
        l, g = jax.jit(jax.value_and_grad(
            lambda p, b: llama.loss_fn_pp(
                p, b, cfg, mesh, num_microbatches=4, schedule=schedule)
        ))(sp, batch)
    np.testing.assert_allclose(float(l), base, rtol=1e-5)
    expected = dict(base_g)
    expected["layers"] = split_params_into_stages(base_g["layers"], 2)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5
        ),
        dict(g), expected,
    )

    # Aux scale with a real weight: the sp-meaned, /M-normalized aux term must stay
    # ~1x the non-pipelined value. The per-(microbatch, sp-slice) stat is nonlinear in
    # its token population, so a ±30% shift on tiny shapes is expected (same band as
    # the plain MoE-pp test) — but a MISSING /sp mean would read ~2x, well outside it.
    cfg_aux = _dc.replace(cfg, moe_aux_weight=1.0)
    base_aux_term = float(llama.loss_fn(params, batch, cfg_aux)) - base
    with jax.set_mesh(mesh):
        l_aux = jax.jit(
            lambda p, b: llama.loss_fn_pp(
                p, b, cfg_aux, mesh, num_microbatches=4, schedule=schedule)
        )(sp, batch)
    ratio = (float(l_aux) - float(l)) / base_aux_term
    assert 0.7 < ratio < 1.4, f"aux scale ratio {ratio}"


def test_1f1b_aux_cotangent_scale_under_sp_matches_gpipe():
    """Pin the 1f1b replay's aux cotangent scaling under extra manual axes (the
    ``aux_ct / extra_size`` in loss_bwd): with a SMOOTH synthetic aux (no top-k
    routing discontinuities), the 1f1b grads must equal the AD-derived GPipe grads of
    the IDENTICAL construction — a missing /sp reads ~2x on the aux-sensitive leaves."""
    from accelerate_tpu.parallel.pp import make_pipeline_loss_fn

    d, S, L, B, n, M = 8, 8, 4, 8, 2, 4
    rng = np.random.default_rng(0)
    layer_params = {
        "w": jnp.asarray(rng.normal(size=(L, d, d)) * 0.1, jnp.float32),
    }

    def stage_fn(params, x):
        def layer(x, p):
            return x + jnp.tanh(x @ p["w"]), None

        out, _ = jax.lax.scan(layer, x, params)
        aux = jnp.sum(out.astype(jnp.float32) ** 2)  # smooth per-slice statistic
        return out, aux

    head_params = {"wout": jnp.asarray(rng.normal(size=(d, d)) * 0.1, jnp.float32)}
    x = jnp.asarray(rng.normal(size=(B, S, d)), jnp.float32)
    tgt = jnp.asarray(rng.normal(size=(B, S, d)), jnp.float32)

    def head_loss(hp, y, extras):
        return jnp.mean((y @ hp["wout"] - extras["tgt"]) ** 2)

    mesh = build_mesh(MeshConfig(dp=2, sp=2, pp=2))
    stage_params = split_params_into_stages(layer_params, n)
    grads = {}
    for schedule in ("gpipe", "1f1b"):
        loss_fn = make_pipeline_loss_fn(
            mesh, stage_fn, head_loss, num_microbatches=M, schedule=schedule,
            with_aux=True, aux_weight=0.5,
            act_spec=P(None, None, "sp", None), extra_manual_axes=("sp",),
        )
        with jax.set_mesh(mesh):
            l, g = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1)))(
                stage_params, head_params, x, {"tgt": tgt}
            )
        grads[schedule] = (float(l), g)
    np.testing.assert_allclose(grads["1f1b"][0], grads["gpipe"][0], rtol=1e-6)
    for a, b in zip(
        jax.tree_util.tree_leaves(grads["1f1b"][1]),
        jax.tree_util.tree_leaves(grads["gpipe"][1]),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@slow
def test_llama_pp_moe_1f1b_matches_single():
    """MoE under the 1F1B schedule: exact CE parity in the no-drop regime, aux term at
    the non-pipelined SCALE (masked per-tick aux, /M normalization), and router grads
    actually flowing through the replay's aux_ct term."""
    import dataclasses

    from accelerate_tpu.models import llama

    cfg = dataclasses.replace(
        llama.CONFIGS["moe-tiny"], dtype=jnp.float32, attn_impl="xla", scan_layers=True,
        moe_aux_weight=0.0, moe_capacity_factor=8.0,
    )
    params = llama.init_params(cfg)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (8, 17)), jnp.int32)}
    base = float(llama.loss_fn(params, batch, cfg))
    base_g = jax.grad(lambda p: llama.loss_fn(p, batch, cfg))(params)

    mesh = build_mesh(MeshConfig(dp=2, ep=2, pp=2))
    sp = dict(params)
    sp["layers"] = split_params_into_stages(params["layers"], 2)
    with jax.set_mesh(mesh):
        l, g = jax.jit(jax.value_and_grad(
            lambda p, b: llama.loss_fn_pp(
                p, b, cfg, mesh, num_microbatches=4, schedule="1f1b")
        ))(sp, batch)
    np.testing.assert_allclose(float(l), base, rtol=1e-5)
    expected = dict(base_g)
    expected["layers"] = split_params_into_stages(base_g["layers"], 2)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5
        ),
        dict(g), expected,
    )

    # Aux scale + gradient flow with a real weight: the aux term stays ~1x the
    # non-pipelined value (never ~M x), and the router weights get nonzero grads
    # through the replay (they only touch the loss via the aux term here... via CE too,
    # so check the aux-specific DELTA of the router grad instead of absolute).
    cfg_aux = dataclasses.replace(cfg, moe_aux_weight=1.0)
    base_aux_term = float(llama.loss_fn(params, batch, cfg_aux)) - base
    with jax.set_mesh(mesh):
        l_aux, g_aux = jax.jit(jax.value_and_grad(
            lambda p, b: llama.loss_fn_pp(
                p, b, cfg_aux, mesh, num_microbatches=4, schedule="1f1b")
        ))(sp, batch)
    ratio = (float(l_aux) - float(l)) / base_aux_term
    assert 0.7 < ratio < 1.3, f"aux scale ratio {ratio}"
    router_delta = np.abs(
        np.asarray(g_aux["layers"]["moe"]["w_router"], np.float64)
        - np.asarray(g["layers"]["moe"]["w_router"], np.float64)
    ).max()
    assert router_delta > 1e-6, "aux gradient did not flow through the 1F1B replay"


@slow
@pytest.mark.parametrize("schedule,virtual_stages", [
    ("gpipe", 1), ("1f1b", 1), ("1f1b", 2),
])
def test_llama_pp_sp_packed_matches_single(schedule, virtual_stages):
    """Sample packing x sp attention x pipeline, every schedule (formerly raised: side
    inputs under extra_manual_axes): the side constants (per-segment positions +
    segment ids) ride SEQUENCE-SLICED through the manual-sp pipeline via
    make_pipeline_fn's side_spec, each sp member's stage attends its own slice with
    the local segment ids, and the ring rotates the kv-side ids with its kv block.
    Loss and ALL grads match the packed, non-pipelined, non-sp run at dp2 x sp2 x pp2."""
    import dataclasses as _dc

    from accelerate_tpu.models import llama

    cfg = _dc.replace(
        llama.CONFIGS["tiny"], dtype=jnp.float32, attn_impl="ring", scan_layers=True,
        n_layers=4,
    )
    params = llama.init_params(cfg)
    rng = np.random.default_rng(0)
    B, S = 8, 33  # inputs S-1 = 32 → sp2 slices of 16
    tokens = rng.integers(0, cfg.vocab_size, (B, S))
    seg = np.zeros((B, S), np.int32)
    for b in range(B):
        cut = int(rng.integers(8, 24))
        seg[b, :cut] = 1
        seg[b, cut:28] = 2  # slots 28: stay 0 = pad
    batch = {"tokens": jnp.asarray(tokens, jnp.int32), "segment_ids": jnp.asarray(seg)}

    # Baseline: packed, no mesh context → ring falls back to local flash with segments.
    base = float(llama.loss_fn(params, batch, cfg))
    base_g = jax.grad(lambda p: llama.loss_fn(p, batch, cfg))(params)

    sp = dict(params)
    sp["layers"] = split_params_into_stages(
        params["layers"], 2, virtual_stages=virtual_stages
    ) if virtual_stages > 1 else split_params_into_stages(params["layers"], 2)
    mesh = build_mesh(MeshConfig(dp=2, sp=2, pp=2))
    with jax.set_mesh(mesh):
        l, g = jax.jit(jax.value_and_grad(
            lambda p, b: llama.loss_fn_pp(
                p, b, cfg, mesh, num_microbatches=4, schedule=schedule,
                virtual_stages=virtual_stages)
        ))(sp, batch)
    np.testing.assert_allclose(float(l), base, rtol=1e-5)
    expected = dict(base_g)
    expected["layers"] = split_params_into_stages(
        base_g["layers"], 2, virtual_stages=virtual_stages
    ) if virtual_stages > 1 else split_params_into_stages(base_g["layers"], 2)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5
        ),
        dict(g), expected,
    )


def test_gpt_pp_sp_attention_matches_single_ring_gpipe():
    """gpt trains sp attention inside the pipeline (formerly a NotImplementedError —
    the last family exception in the sp×pp matrix): loss_fn_pp goes manual over sp
    exactly like llama's sp_pipeline. Rotary positions are rebuilt per sequence slice
    with GLOBAL offsets inside the stage body. Loss and ALL grads match the
    non-pipelined, non-sp run at dp2 x sp2 x pp2. (Default tier: the cheapest mode;
    the full mode x schedule sweep is the slow test below.)"""
    _check_gpt_pp_sp("ring", "gpipe", 1)


@slow
@pytest.mark.parametrize(
    "mode,schedule,virtual_stages",
    [("ring", "1f1b", 1), ("ring", "1f1b", 2),
     ("ulysses", "gpipe", 1), ("ulysses", "1f1b", 1), ("allgather", "1f1b", 1)],
)
def test_gpt_pp_sp_attention_matches_single(mode, schedule, virtual_stages):
    """Full gpt sp×pp sweep: every sp mode through both schedules incl. the
    interleaved virtual pipeline (ulysses under 1f1b substitutes the
    ppermute-decomposed all-to-all, same wall as llama)."""
    _check_gpt_pp_sp(mode, schedule, virtual_stages)


def _check_gpt_pp_sp(mode, schedule, virtual_stages):
    import dataclasses as _dc

    from accelerate_tpu.models import gpt

    cfg = _dc.replace(
        gpt.CONFIGS["tiny"], dtype=jnp.float32, attn_impl=mode, scan_layers=True,
        n_layers=4, pos="rotary",
    )
    params = gpt.init_params(cfg)
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 17)), jnp.int32)}
    # Baseline: same math, no mesh context → the sp modes fall back to local attention.
    base = float(gpt.loss_fn(params, batch, cfg))
    base_g = jax.grad(lambda p: gpt.loss_fn(p, batch, cfg))(params)

    def split(tree):
        return (split_params_into_stages(tree, 2, virtual_stages=virtual_stages)
                if virtual_stages > 1 else split_params_into_stages(tree, 2))

    sp = dict(params)
    sp["layers"] = split(params["layers"])
    mesh = build_mesh(MeshConfig(dp=2, sp=2, pp=2))
    with jax.set_mesh(mesh):
        l, g = jax.jit(jax.value_and_grad(
            lambda p, b: gpt.loss_fn_pp(
                p, b, cfg, mesh, num_microbatches=4, schedule=schedule,
                virtual_stages=virtual_stages)
        ))(sp, batch)
    np.testing.assert_allclose(float(l), base, rtol=1e-5)
    expected = dict(base_g)
    expected["layers"] = split(base_g["layers"])
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5
        ),
        dict(g), expected,
    )


@slow
@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_gpt_pp_sp_packed_matches_single(schedule):
    """Sample packing x sp x pipeline for the gpt family (learned positions: the wpe
    lookup happens at the embed OUTSIDE the pipeline on per-segment restart positions;
    the sequence-sliced side constants feed the in-stage segment masks). Loss and ALL
    grads match the packed, non-pipelined, non-sp run at dp2 x sp2 x pp2."""
    import dataclasses as _dc

    from accelerate_tpu.models import gpt

    cfg = _dc.replace(
        gpt.CONFIGS["tiny"], dtype=jnp.float32, attn_impl="ring", scan_layers=True,
        n_layers=4,
    )
    params = gpt.init_params(cfg)
    rng = np.random.default_rng(0)
    B, S = 8, 33  # inputs S-1 = 32 → sp2 slices of 16
    tokens = rng.integers(0, cfg.vocab_size, (B, S))
    seg = np.zeros((B, S), np.int32)
    for b in range(B):
        cut = int(rng.integers(8, 24))
        seg[b, :cut] = 1
        seg[b, cut:28] = 2  # slots 28: stay 0 = pad
    batch = {"tokens": jnp.asarray(tokens, jnp.int32), "segment_ids": jnp.asarray(seg)}

    base = float(gpt.loss_fn(params, batch, cfg))
    base_g = jax.grad(lambda p: gpt.loss_fn(p, batch, cfg))(params)

    sp = dict(params)
    sp["layers"] = split_params_into_stages(params["layers"], 2)
    mesh = build_mesh(MeshConfig(dp=2, sp=2, pp=2))
    with jax.set_mesh(mesh):
        l, g = jax.jit(jax.value_and_grad(
            lambda p, b: gpt.loss_fn_pp(
                p, b, cfg, mesh, num_microbatches=4, schedule=schedule)
        ))(sp, batch)
    np.testing.assert_allclose(float(l), base, rtol=1e-5)
    expected = dict(base_g)
    expected["layers"] = split_params_into_stages(base_g["layers"], 2)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5
        ),
        dict(g), expected,
    )


def test_prepare_pippy_bert_and_t5_match_plain_forward():
    """prepare_pippy covers the reference's full pippy example set (llama/gpt2/bert/t5,
    ``/root/reference/examples/inference/pippy/``): bert (encoder, classification
    logits) and t5 (enc-dec, seq2seq LM logits) pipelined == their plain forwards."""
    import dataclasses as _dc

    from accelerate_tpu import prepare_pippy
    from accelerate_tpu.models import bert, t5

    rng = np.random.default_rng(0)
    mesh = build_mesh(MeshConfig(dp=4, pp=2))

    bcfg = _dc.replace(bert.CONFIGS["tiny"], dtype=jnp.float32)
    bparams = bert.init_params(bcfg)
    ids = jnp.asarray(rng.integers(0, bcfg.vocab_size, (8, 16)), jnp.int32)
    amask = jnp.asarray(rng.integers(0, 2, (8, 16)).astype(bool) | np.eye(1, 16, dtype=bool))
    plain = bert.forward(bparams, ids, attention_mask=amask, cfg=bcfg)
    _, fwd = prepare_pippy(bparams, bcfg, mesh=mesh, num_microbatches=2)
    np.testing.assert_allclose(
        np.asarray(fwd(ids, amask)), np.asarray(plain), atol=2e-4, rtol=1e-4
    )

    tcfg = _dc.replace(t5.CONFIGS["tiny"], dtype=jnp.float32)
    tparams = t5.init_params(tcfg)
    enc_ids = jnp.asarray(rng.integers(0, tcfg.vocab_size, (8, 12)), jnp.int32)
    dec_ids = jnp.asarray(rng.integers(0, tcfg.vocab_size, (8, 10)), jnp.int32)
    plain = t5.forward(tparams, enc_ids, dec_ids, tcfg)
    _, fwd = prepare_pippy(tparams, tcfg, mesh=mesh, num_microbatches=2)
    np.testing.assert_allclose(
        np.asarray(fwd(enc_ids, dec_ids)), np.asarray(plain), atol=2e-4, rtol=1e-4
    )
