"""T5 family: training on the mesh, TP parity, seq2seq loss conventions."""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from accelerate_tpu import Accelerator
from accelerate_tpu.models import t5
from accelerate_tpu.parallel import MeshConfig
from accelerate_tpu.utils import send_to_device
from accelerate_tpu.test_utils.testing import slow

CFG = dataclasses.replace(t5.CONFIGS["tiny"], dtype=jnp.float32)


def make_batch(n=8, src=12, tgt=8, seed=0):
    rng = np.random.default_rng(seed)
    labels = rng.integers(2, CFG.vocab_size, size=(n, tgt)).astype(np.int32)
    labels[:, -2:] = -100  # ignored positions (HF convention)
    return {
        "input_ids": rng.integers(2, CFG.vocab_size, size=(n, src)).astype(np.int32),
        "labels": labels,
    }


@slow
def test_training_decreases_loss():
    acc = Accelerator(mesh_config=MeshConfig())
    state = acc.create_train_state(
        t5.init_params(CFG), optax.adam(3e-3), partition_specs=t5.partition_specs(CFG)
    )
    step = acc.build_train_step(lambda p, b: t5.loss_fn(p, b, CFG))
    batch = send_to_device(make_batch(), acc.mesh)
    losses = []
    for _ in range(5):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses


@slow
@pytest.mark.parametrize("with_mask", [False, True])
@pytest.mark.parametrize("schedule,M", [("gpipe", 2), ("gpipe", 4), ("1f1b", 4)])
def test_t5_pp_matches_single(with_mask, schedule, M):
    """T5 through the pipeline: encoder stages then decoder stages chained over the same pp
    axis, enc_out delivered to cross-attention as a differentiable side constant.
    Loss AND full grads (incl. the lifted rel-bias tables, whose per-stage broadcast
    grads must sum back into one table) match the non-pipelined run."""
    from accelerate_tpu.parallel.mesh import build_mesh

    params = t5.init_params(CFG)
    batch = {k: jnp.asarray(v) for k, v in make_batch(n=8, src=12, tgt=8).items()}
    if with_mask:
        am = np.ones((8, 12), np.int32)
        am[:, -3:] = 0  # padded encoder tail
        batch["attention_mask"] = jnp.asarray(am)
    base = float(t5.loss_fn(params, batch, CFG))
    base_g = jax.grad(lambda p: t5.loss_fn(p, batch, CFG))(params)

    mesh = build_mesh(MeshConfig(dp=4, pp=2))
    pp_params = t5.stack_pp_params(params, CFG, 2)
    with jax.set_mesh(mesh):
        l, g = jax.jit(jax.value_and_grad(
            lambda p, b: t5.loss_fn_pp(
                p, b, CFG, mesh, num_microbatches=M, schedule=schedule)
        ))(pp_params, batch)
    np.testing.assert_allclose(float(l), base, rtol=1e-5)
    # stack_pp_params is structural — applying it to the grad tree yields exactly the
    # expected pipeline-layout grads (rel tables lifted, blocks stage-stacked). Under
    # 1f1b the encoder grads exist only because the replay computed the TRUE enc_out
    # cotangent (float side leaves) and AD chained it through the encoder pipeline.
    expected = t5.stack_pp_params(base_g, CFG, 2)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5
        ),
        g, expected,
    )


@slow
def test_t5_pp_interleaved_matches_single():
    """t5's decoder pipeline runs INTERLEAVED (virtual_stages=2) under 1f1b, with the
    float enc_out cotangent accumulated through the virtual-stage replay — loss and
    full grads (incl. encoder params, reached only via that cotangent) match."""
    from accelerate_tpu.parallel.mesh import build_mesh

    cfg = dataclasses.replace(CFG, n_layers=4)
    params = t5.init_params(cfg)
    batch = {k: jnp.asarray(v) for k, v in make_batch(n=8, src=12, tgt=8).items()}
    base = float(t5.loss_fn(params, batch, cfg))
    base_g = jax.grad(lambda p: t5.loss_fn(p, batch, cfg))(params)

    mesh = build_mesh(MeshConfig(dp=4, pp=2))
    pp_params = t5.stack_pp_params(params, cfg, 2, virtual_stages=2)
    with jax.set_mesh(mesh):
        l, g = jax.jit(jax.value_and_grad(
            lambda p, b: t5.loss_fn_pp(
                p, b, cfg, mesh, num_microbatches=4, schedule="1f1b",
                virtual_stages=2)
        ))(pp_params, batch)
    np.testing.assert_allclose(float(l), base, rtol=1e-5)
    expected = t5.stack_pp_params(base_g, cfg, 2, virtual_stages=2)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5
        ),
        g, expected,
    )


@slow
@pytest.mark.parametrize("schedule,M", [("gpipe", 2), ("gpipe", 4), ("1f1b", 4)])
def test_t5_pp_seq2seq_packed_matches_single(schedule, M):
    """Seq2seq packing composes with the enc-dec pipeline: enc/dec segment ids ride
    both pipelines as side constants (per-segment bidirectional, per-segment causal,
    and segment-paired cross-attention), matching the non-pipelined packed loss AND
    grads."""
    from accelerate_tpu.ops import packing
    from accelerate_tpu.parallel.mesh import build_mesh

    params = t5.init_params(CFG)
    rng = np.random.default_rng(9)
    pairs = [
        (rng.integers(1, CFG.vocab_size, int(a)).astype(np.int32),
         rng.integers(1, CFG.vocab_size, int(b)).astype(np.int32))
        for a, b in ((7, 5), (4, 8), (9, 3), (5, 4), (6, 6), (3, 7), (8, 4), (5, 5))
    ]
    packed = packing.pack_seq2seq(
        [p[0] for p in pairs], [p[1] for p in pairs], enc_len=12, dec_len=10
    )
    batch = {k: jnp.asarray(np.resize(v, (8, v.shape[1]))) for k, v in packed.items()}
    base = float(t5.loss_fn(params, batch, CFG))
    base_g = jax.grad(lambda p: t5.loss_fn(p, batch, CFG))(params)

    mesh = build_mesh(MeshConfig(dp=4, pp=2))
    pp_params = t5.stack_pp_params(params, CFG, 2)
    with jax.set_mesh(mesh):
        l, g = jax.jit(jax.value_and_grad(
            lambda p, b: t5.loss_fn_pp(
                p, b, CFG, mesh, num_microbatches=M, schedule=schedule)
        ))(pp_params, batch)
    np.testing.assert_allclose(float(l), base, rtol=1e-5)
    expected = t5.stack_pp_params(base_g, CFG, 2)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5
        ),
        g, expected,
    )


@slow
def test_tp_sharded_matches_single():
    params = t5.init_params(CFG)
    batch = make_batch()
    base = float(t5.loss_fn(params, {k: jnp.asarray(v) for k, v in batch.items()}, CFG))
    acc = Accelerator(mesh_config=MeshConfig(dp=2, fsdp=2, tp=2))
    state = acc.create_train_state(
        params, optax.sgd(0.1), partition_specs=t5.partition_specs(CFG)
    )
    assert not state.params["encoder"]["blocks"][0]["attn"]["q"].sharding.is_fully_replicated
    step = acc.build_train_step(lambda p, b: t5.loss_fn(p, b, CFG))
    state, m = step(state, send_to_device(batch, acc.mesh))
    np.testing.assert_allclose(float(m["loss"]), base, rtol=2e-5)


@slow
def test_ignored_labels_do_not_contribute():
    params = t5.init_params(CFG)
    b1 = make_batch(2, 8, 6, seed=1)
    b2 = {k: v.copy() for k, v in b1.items()}
    b2["labels"][:, -2:] = 7  # same ignored slots, different values → must change loss
    l1 = float(t5.loss_fn(params, {k: jnp.asarray(v) for k, v in b1.items()}, CFG))
    b1_ignored = {k: v.copy() for k, v in b1.items()}
    b1_ignored["labels"][:, -2:] = -100
    l_same = float(t5.loss_fn(params, {k: jnp.asarray(v) for k, v in b1_ignored.items()}, CFG))
    assert np.isclose(l1, l_same), "positions marked -100 must be ignored"
    l2 = float(t5.loss_fn(params, {k: jnp.asarray(v) for k, v in b2.items()}, CFG))
    assert not np.isclose(l1, l2)


@slow
def test_remat_matches_no_remat():
    """cfg.remat (now consumed via models/common.remat_wrap) must be numerically inert:
    identical loss with and without activation checkpointing, and grads must flow."""
    params = t5.init_params(CFG)
    batch = make_batch(n=2)
    loss_plain = t5.loss_fn(params, batch, CFG)
    cfg_r = dataclasses.replace(CFG, remat=True)
    loss_remat, grads = jax.value_and_grad(lambda p: t5.loss_fn(p, batch, cfg_r))(params)
    np.testing.assert_allclose(
        float(loss_plain), float(loss_remat), rtol=1e-6
    )
    gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree_util.tree_leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


def test_num_params_analytic():
    counted = sum(int(np.prod(np.shape(l))) for l in jax.tree_util.tree_leaves(t5.init_params(CFG)))
    assert t5.num_params(CFG) == counted


@slow
def test_generate_streamed_matches_in_memory():
    """Streamed (host-offloaded) greedy seq2seq decode == in-memory decode."""
    from accelerate_tpu.big_modeling import cpu_offload

    params = t5.init_params(CFG)
    rng = np.random.default_rng(5)
    inp = jnp.asarray(rng.integers(0, CFG.vocab_size, (2, 9)), jnp.int32)
    am = jnp.asarray([[1] * 9, [1] * 6 + [0] * 3], jnp.int32)
    want = np.asarray(t5.generate(params, inp, CFG, max_new_tokens=6, attention_mask=am))
    got = np.asarray(
        t5.generate_streamed(cpu_offload(params), inp, CFG, max_new_tokens=6, attention_mask=am)
    )
    # in-memory generate early-exits at all-EOS; streamed pads to max_new_tokens with EOS
    n = want.shape[1]
    np.testing.assert_array_equal(want, got[:, :n])
    assert np.all(got[:, n:] == 1)


def test_score_matches_loss_fn():
    params = t5.init_params(CFG)
    batch = make_batch(n=2)
    ll = t5.score(params, batch["input_ids"], batch["labels"], CFG)
    loss = t5.loss_fn(params, batch, CFG)
    labels = np.asarray(batch["labels"])
    denom = (labels >= 0).sum()
    np.testing.assert_allclose(
        -float(np.asarray(ll).sum()) / denom, float(np.asarray(loss)), rtol=1e-5
    )
