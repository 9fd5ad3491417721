"""Flagship model tests: correctness, TP/FSDP/hybrid sharded-training parity, scan/remat."""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import PartitionSpec as P

from accelerate_tpu import Accelerator
from accelerate_tpu.models import llama
from accelerate_tpu.parallel import MeshConfig
from accelerate_tpu.parallel.tp import apply_tensor_parallel, plan_from_rules
from accelerate_tpu.utils import FullyShardedDataParallelPlugin, send_to_device
from accelerate_tpu.test_utils.testing import slow, slow_mark

CFG = dataclasses.replace(llama.CONFIGS["tiny"], dtype=jnp.float32)  # fp32 for parity


def make_batch(n=16, seq=32, seed=0):
    rng = np.random.default_rng(seed)
    return {"tokens": rng.integers(0, CFG.vocab_size, size=(n, seq + 1)).astype(np.int32)}


def test_forward_shapes_and_finite():
    params = llama.init_params(CFG)
    tokens = jnp.asarray(make_batch(2, 16)["tokens"][:, :-1])
    logits = llama.forward(params, tokens, CFG, shard_activations=False)
    assert logits.shape == (2, 16, CFG.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_causality():
    """Changing future tokens must not affect past logits."""
    params = llama.init_params(CFG)
    t1 = jnp.asarray(make_batch(1, 16)["tokens"][:, :-1])
    t2 = t1.at[:, 10:].set((t1[:, 10:] + 1) % CFG.vocab_size)
    l1 = llama.forward(params, t1, CFG, shard_activations=False)
    l2 = llama.forward(params, t2, CFG, shard_activations=False)
    np.testing.assert_allclose(np.asarray(l1[:, :10]), np.asarray(l2[:, :10]), atol=1e-5)
    assert not np.allclose(np.asarray(l1[:, 10:]), np.asarray(l2[:, 10:]))


def test_gqa_heads_differ_from_mha():
    cfg_mha = dataclasses.replace(CFG, n_kv_heads=CFG.n_heads)
    p = llama.init_params(CFG)
    assert p["layers"][0]["wk"].shape == (CFG.d_model, CFG.n_kv_heads * CFG.head_dim)
    p2 = llama.init_params(cfg_mha)
    assert p2["layers"][0]["wk"].shape == (CFG.d_model, CFG.d_model)


def test_partition_specs_structure_matches_params():
    params = llama.init_params(CFG)
    specs = llama.partition_specs(CFG)
    jax.tree_util.tree_map(lambda p, s: None, params, specs)  # same structure or raises
    assert specs["layers"][0]["wq"] == P(None, "tp")
    assert specs["layers"][0]["wo"] == P("tp", None)


def train_losses(acc, cfg, n_steps=4, specs=None, lr=0.05):
    params = llama.init_params(cfg)
    state = acc.create_train_state(params, optax.sgd(lr), partition_specs=specs)
    step = acc.build_train_step(lambda p, b: llama.loss_fn(p, b, cfg))
    batch = send_to_device(make_batch(), acc.mesh)
    losses = []
    for _ in range(n_steps):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    return losses, state


def baseline_losses(cfg, n_steps=4, lr=0.05):
    params = llama.init_params(cfg)
    tx = optax.sgd(lr)
    opt = tx.init(params)
    batch = {k: jnp.asarray(v) for k, v in make_batch().items()}
    losses = []
    for _ in range(n_steps):
        loss, grads = jax.value_and_grad(lambda p: llama.loss_fn(p, batch, cfg))(params)
        losses.append(float(loss))
        updates, opt = tx.update(grads, opt, params)
        params = optax.apply_updates(params, updates)
    return losses


# Default tier runs the 3-axis case (covers dp+fsdp+tp propagation in one compile);
# the single-axis and sp layouts run under RUN_SLOW=1.
_slow_param = slow_mark()


@pytest.mark.parametrize(
    "mesh_kwargs",
    [
        pytest.param(dict(dp=8), marks=_slow_param),
        pytest.param(dict(dp=1, tp=8), marks=_slow_param),
        dict(dp=2, fsdp=2, tp=2),
        pytest.param(dict(dp=2, tp=2, sp=2), marks=_slow_param),
    ],
    ids=["dp8", "tp8", "dp2fsdp2tp2", "dp2tp2sp2"],
)
def test_sharded_training_parity(mesh_kwargs):
    """Every mesh layout must reproduce single-device training losses."""
    acc = Accelerator(
        mesh_config=MeshConfig(**mesh_kwargs),
        fsdp_plugin=FullyShardedDataParallelPlugin(min_weight_size=1)
        if mesh_kwargs.get("fsdp", 1) > 1
        else None,
    )
    specs = llama.partition_specs(CFG)
    losses, state = train_losses(acc, CFG, specs=specs)
    expected = baseline_losses(CFG)
    np.testing.assert_allclose(losses, expected, rtol=2e-4)
    # TP actually sharded the params.
    if mesh_kwargs.get("tp", 1) > 1:
        assert not state.params["layers"][0]["wq"].sharding.is_fully_replicated


def test_scan_layers_equivalent():
    cfg_scan = dataclasses.replace(CFG, scan_layers=True)
    params = llama.init_params(CFG, jax.random.PRNGKey(1))
    params_scan = {
        "embed": params["embed"],
        "layers": jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *params["layers"]),
        "ln_f": params["ln_f"],
        "lm_head": params["lm_head"],
    }
    tokens = jnp.asarray(make_batch(2, 16)["tokens"][:, :-1])
    l1 = llama.forward(params, tokens, CFG, shard_activations=False)
    l2 = llama.forward(params_scan, tokens, cfg_scan, shard_activations=False)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=2e-5)


@slow
def test_remat_equivalent():
    cfg_remat = dataclasses.replace(CFG, remat=True)
    params = llama.init_params(CFG)
    batch = {k: jnp.asarray(v) for k, v in make_batch(4, 16).items()}
    g1 = jax.grad(lambda p: llama.loss_fn(p, batch, CFG))(params)
    g2 = jax.grad(lambda p: llama.loss_fn(p, batch, cfg_remat))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_plan_from_rules():
    params = {"wq": jnp.ones((8, 16)), "other": jnp.ones((4,))}
    plan = plan_from_rules([(r"wq", P(None, "tp"))])
    specs = plan(params)
    assert specs["wq"] == P(None, "tp")
    assert specs["other"] == P(None)


def test_apply_tensor_parallel_with_fsdp_compose(mesh8):
    from accelerate_tpu.parallel import build_mesh

    mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    params = {"w": jnp.ones((64, 32))}
    sharded = apply_tensor_parallel(
        params,
        mesh,
        specs={"w": P(None, "tp")},
        fsdp_plugin=FullyShardedDataParallelPlugin(min_weight_size=1),
    )
    spec = sharded["w"].sharding.spec
    # tp on dim 1 (from plan), fsdp filled onto dim 0 (free, largest).
    assert spec == P("fsdp", "tp")


def test_num_params_analytic():
    params = llama.init_params(CFG)
    counted = sum(np.prod(np.shape(l)) for l in jax.tree_util.tree_leaves(params))
    assert llama.num_params(CFG) == counted


def test_loss_mask():
    params = llama.init_params(CFG)
    batch = make_batch(2, 16)
    batch["mask"] = np.ones_like(batch["tokens"])
    l_full = llama.loss_fn(params, {k: jnp.asarray(v) for k, v in batch.items()}, CFG)
    batch["mask"][:, 8:] = 0
    l_half = llama.loss_fn(params, {k: jnp.asarray(v) for k, v in batch.items()}, CFG)
    assert not np.isclose(float(l_full), float(l_half))


@slow
def test_chunked_ce_matches_full():
    """Chunked cross-entropy (memory path) must equal the full-logits path, incl. grads."""
    params = llama.init_params(CFG)
    batch = make_batch(2, 32)
    batch["mask"] = np.ones_like(batch["tokens"])
    batch["mask"][:, 20:] = 0
    jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
    cfg_chunk = dataclasses.replace(CFG, loss_chunk=8)
    cfg_full = dataclasses.replace(CFG, loss_chunk=-1)
    l_chunk, g_chunk = jax.value_and_grad(lambda p: llama.loss_fn(p, jbatch, cfg_chunk))(params)
    l_full, g_full = jax.value_and_grad(lambda p: llama.loss_fn(p, jbatch, cfg_full))(params)
    np.testing.assert_allclose(float(l_chunk), float(l_full), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5),
        g_chunk, g_full,
    )


def _ce_case(S=32, mask="zeros", dtype=jnp.float32, seed=0, B=2, D=16, V=64):
    """x [B,S,D], head [D,V], bias [V], targets, mask for the chunked-CE cases below."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (B, S, D), dtype)
    head = (0.3 * jax.random.normal(k[1], (D, V))).astype(dtype)
    bias = jax.random.normal(k[2], (V,), dtype)
    targets = jax.random.randint(k[3], (B, S), 0, V)
    m = jax.random.uniform(k[4], (B, S)) > 0.3 if mask == "zeros" else jnp.ones((B, S), bool)
    return x, head, bias, targets, m.astype(jnp.float32)


def _count_primitives(jaxpr, name: str) -> int:
    """Equations named ``name`` in a jaxpr and every jaxpr nested in it (scan bodies...)."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count_primitives(sub, name)
    return n


def _assert_close(got, want, rel):
    """Every leaf within ``rel`` of the reference's largest element."""
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=rel * max(np.abs(b).max(), 1e-6), rtol=0)


@pytest.mark.parametrize("mask", ["zeros", "ones"])
@pytest.mark.parametrize("S", [32, 30])                       # a chunk multiple / padded
@pytest.mark.parametrize("with_bias", [False, True])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_chunked_ce_one_pass_matches_dense(softcap, with_bias, S, mask):
    """Value and gradients wrt x, head (and bias) of the one-pass chunked loss equal the
    dense ``ce_sum(chunk=0)``'s, under jit, in float32."""
    from accelerate_tpu.models.common import ce_sum

    x, head, bias, targets, m = _ce_case(S, mask)
    args = (x, head, bias) if with_bias else (x, head)

    def loss(chunk):
        def f(x, head, bias=None):
            return ce_sum(x, head, targets, m, dtype=jnp.float32, chunk=chunk,
                          softcap=softcap, bias=bias)
        return jax.jit(jax.value_and_grad(f, argnums=tuple(range(len(args)))))

    (l_chunk, g_chunk), (l_full, g_full) = loss(8)(*args), loss(0)(*args)
    np.testing.assert_allclose(float(l_chunk), float(l_full), rtol=1e-6)
    _assert_close(g_chunk, g_full, 2e-6)
    assert all(g.dtype == a.dtype and g.shape == a.shape for g, a in zip(g_chunk, args))


@pytest.mark.parametrize("scale", ["over_denom", "times_3"])
def test_chunked_ce_scales_by_the_cotangent(scale):
    """The residuals are gradients per unit cotangent; the backward multiplies by ``g``."""
    from accelerate_tpu.models.common import ce_sum

    x, head, bias, targets, m = _ce_case(30)
    post = (lambda l: l / jnp.maximum(m.sum(), 1.0)) if scale == "over_denom" else (lambda l: 3.0 * l)

    def grads(chunk):
        return jax.grad(lambda x, h, b: post(ce_sum(
            x, h, targets, m, dtype=jnp.float32, chunk=chunk, bias=b)), argnums=(0, 1, 2))(x, head, bias)

    _assert_close(grads(8), grads(0), 2e-6)


def test_chunked_ce_gradient_reaches_tied_embedding_twice():
    """Tied embeddings: ``embed`` is the lookup table AND (transposed) the head."""
    from accelerate_tpu.models.common import ce_sum

    _, head, _, targets, m = _ce_case(32)
    tokens = (targets + 1) % head.shape[1]

    def grad(chunk):
        return jax.grad(lambda e: ce_sum(e[tokens], e.T, targets, m, dtype=jnp.float32, chunk=chunk))(head.T)

    _assert_close(grad(8), grad(0), 2e-6)


def test_chunked_ce_bfloat16_matches_dense():
    from accelerate_tpu.models.common import ce_sum

    x, head, bias, targets, m = _ce_case(30, dtype=jnp.bfloat16)

    def vg(chunk):
        return jax.jit(jax.value_and_grad(lambda x, h, b: ce_sum(
            x, h, targets, m, dtype=jnp.bfloat16, chunk=chunk, bias=b) / 32.0, argnums=(0, 1, 2)))(x, head, bias)

    (l_chunk, g_chunk), (l_full, g_full) = vg(8), vg(0)
    np.testing.assert_allclose(float(l_chunk), float(l_full), rtol=1e-5)   # fp32 from bf16 logits
    assert [g.dtype for g in g_chunk] == [jnp.bfloat16] * 3
    _assert_close(g_chunk, g_full, 2e-2)


def test_chunked_ce_primal_equals_differentiated_value():
    """Without ``grad`` the plain scan runs; its value is the differentiated call's."""
    from accelerate_tpu.models.common import chunked_ce

    x, head, bias, targets, m = _ce_case(30)
    f = lambda x: chunked_ce(x, head, targets, m, 8, jnp.float32, final_softcap=30.0, bias=bias)  # noqa: E731
    assert float(f(x)) == float(jax.value_and_grad(f)(x)[0])
    assert _count_primitives(jax.make_jaxpr(f)(x).jaxpr, "dot_general") == 1


def test_chunked_ce_differentiates_in_one_scan_of_three_products():
    """The gradient program holds ONE scan with THREE head-sized products (a recomputing
    backward holds two scans and four), and no remat."""
    from accelerate_tpu.models.common import chunked_ce

    x, head, _, targets, m = _ce_case(32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda x, h: chunked_ce(x, h, targets, m, 8, jnp.float32), argnums=(0, 1)))(x, head).jaxpr
    assert _count_primitives(jaxpr, "scan") == 1
    assert _count_primitives(jaxpr, "dot_general") == 3
    assert _count_primitives(jaxpr, "checkpoint") == 0
    assert _count_primitives(jaxpr, "gather") == 0      # the target's logit is a one-hot sum


def test_chunked_ce_mask_and_targets_take_no_gradient():
    """A float mask's cotangent is zeros (plain autodiff would hand it -log p, which no
    caller reads)."""
    from accelerate_tpu.models.common import chunked_ce

    x, head, _, targets, m = _ce_case(32)
    g = jax.grad(lambda m: chunked_ce(x, head, targets, m, 8, jnp.float32))(m)
    assert g.shape == m.shape and not np.asarray(g).any()


def test_chunked_ce_vocab_sharded_head_matches_unsharded(mesh8):
    """GSPMD: a head sharded over the vocabulary (and x over the batch) gives the
    unsharded call's value and gradients."""
    from jax.sharding import Mesh, NamedSharding

    from accelerate_tpu.models.common import chunked_ce

    x, head, bias, targets, m = _ce_case(30, B=4)
    f = jax.jit(jax.value_and_grad(lambda x, h, b: chunked_ce(
        x, h, targets, m, 8, jnp.float32, bias=b), argnums=(0, 1, 2)))
    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "vocab"))
    put = lambda a, *spec: jax.device_put(a, NamedSharding(mesh, P(*spec)))  # noqa: E731
    l_sh, g_sh = f(put(x, "data"), put(head, None, "vocab"), put(bias, "vocab"))
    l_one, g_one = f(x, head, bias)
    np.testing.assert_allclose(float(l_sh), float(l_one), rtol=1e-6)
    _assert_close(g_sh, g_one, 2e-6)
    assert g_sh[1].sharding.spec == P(None, "vocab")


def test_chunked_ce_tied_embeddings():
    cfg = dataclasses.replace(CFG, tie_embeddings=True, loss_chunk=8)
    params = llama.init_params(cfg)
    batch = {k: jnp.asarray(v) for k, v in make_batch(2, 16).items()}
    loss = llama.loss_fn(params, batch, cfg)
    assert np.isfinite(float(loss))


def test_chunk_size_resolution():
    from accelerate_tpu.models.llama import _loss_chunk_size

    cfg = dataclasses.replace(CFG, loss_chunk=512)
    assert _loss_chunk_size(cfg, 1000) == 512  # explicit request honored (S padded)
    assert _loss_chunk_size(dataclasses.replace(CFG, loss_chunk=8), 32) == 8
    cfg_auto = dataclasses.replace(CFG, vocab_size=32768, loss_chunk=0)
    assert _loss_chunk_size(cfg_auto, 2047) == 512  # awkward S: padded, not per-token
    assert _loss_chunk_size(cfg_auto, 2048) == 512
    assert _loss_chunk_size(dataclasses.replace(CFG, loss_chunk=-1), 4096) == 0


@slow
def test_chunked_ce_nondivisible_seq_matches_full():
    """Odd S with an explicit chunk: the padded chunked path equals full logits exactly."""
    params = llama.init_params(CFG)
    batch = make_batch(2, 30)  # S=30, chunk=8 → padded to 32
    jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
    cfg_chunk = dataclasses.replace(CFG, loss_chunk=8)
    cfg_full = dataclasses.replace(CFG, loss_chunk=-1)
    l_chunk, g_chunk = jax.value_and_grad(lambda p: llama.loss_fn(p, jbatch, cfg_chunk))(params)
    l_full, g_full = jax.value_and_grad(lambda p: llama.loss_fn(p, jbatch, cfg_full))(params)
    np.testing.assert_allclose(float(l_chunk), float(l_full), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5),
        g_chunk, g_full,
    )


def test_remat_policy_validated():
    cfg = dataclasses.replace(CFG, remat=True, remat_policy="dot")  # typo
    params = llama.init_params(cfg)
    tokens = jnp.asarray(make_batch(1, 8)["tokens"][:, :-1])
    with pytest.raises(ValueError, match="remat_policy"):
        llama.forward(params, tokens, cfg, shard_activations=False)


def test_score_matches_loss_fn():
    """score() log-probs must be consistent with loss_fn (its masked mean, negated) and
    perplexity must equal exp(loss)."""
    import dataclasses

    cfg = dataclasses.replace(llama.CONFIGS["tiny"], dtype=jnp.float32, loss_chunk=-1)
    params = llama.init_params(cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(2, 17)), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, size=(2, 17)), jnp.bool_).at[:, 0].set(True)

    ll = llama.score(params, tokens, cfg, mask)
    loss = llama.loss_fn(params, {"tokens": tokens, "mask": mask}, cfg)
    denom = float(np.asarray(mask[:, 1:].sum()))
    np.testing.assert_allclose(
        -float(np.asarray(ll).sum()) / denom, float(np.asarray(loss)), rtol=1e-5
    )
    ppl = llama.perplexity(params, tokens, cfg, mask)
    np.testing.assert_allclose(float(np.asarray(ppl)), float(np.exp(np.asarray(loss))), rtol=1e-5)
