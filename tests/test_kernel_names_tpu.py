"""Kernel names survive the TPU compiler: ``flash_attention`` forward and backward,
``paged_attention``, ``mla_paged_attention`` and ``dsa_index_scores`` compiled for a DESCRIBED
v5e chip (none is attached) at the benchmark cells' widths, and the ``tpu_custom_call``
instructions carry the names the trace readers look for. So does the serving engine's whole decode program,
whose compiled form must write the KV pool in place (ISSUE 29), and its chunk-append
prefill program, whose attention is the flash forward (ISSUE 31). These are compiles, not
runs: nothing here is a measurement.

The topology is described inside a module-scoped fixture and only there (never at
import: every xdist worker imports this file, and only one process may load libtpu).
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from accelerate_tpu.ops import flash_attention as flash_mod
from accelerate_tpu.ops import mla_attention as mla_mod
from accelerate_tpu.ops import paged_attention as paged_mod

# Mistral-7B: 32 q heads / 8 kv heads x 128; train cell 4 x 8192, window 4096;
# serve cell 32 lanes, pages of 16, max_len 8192 (512 table entries), 3840 pages.
B_TRAIN, SEQ, H, K, HD, WINDOW = 4, 8192, 32, 8, 128, 4096
LANES, PAGE, MAX_LEN, PAGES, LAYERS = 32, 16, 8192, 3840, 16
# DeepSeek-V3: 128 heads over latent rows of 512 + 64 (planes 640 wide); serve cell 32
# lanes, pages of 16, max_len 16384 (1024 table entries), 26624 pages.
MLA_H, MLA_RANK, MLA_ROPE, MLA_WIDTH, MLA_MAX_LEN, MLA_PAGES = 128, 512, 64, 640, 16384, 26624
CUSTOM_CALL = re.compile(r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"")


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever stops the description skips, not fails
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but cannot be
    # read back without a chip (the next run would warn and compile again): keep it off.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def shape(dims, dtype, sharding):
    return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)


KERNEL = re.compile(r"flash_fwd_masked|flash_fwd|flash_bwd_dq|flash_bwd_dkv|mla_paged_attention"
                    r"|paged_attention|dsa_index_scores|gmm")


def kernels(compiled) -> list:
    """The kernel name each Mosaic instruction of the program carries (the instruction's
    own name where it carries none). Directly under ``jax.grad`` the compiler wraps the
    name in the transform's (``transpose_jvp_flash_bwd_dq__.1``); inside the train step's
    scan and remat it stands alone (``flash_bwd_dq.11``). A trace reader searches for it."""
    names = CUSTOM_CALL.findall(compiled.as_text())
    return sorted(m.group(0) if (m := KERNEL.search(n)) else n for n in names)


def flash(q, k, v):
    return flash_mod.flash_attention(q, k, v, causal=True, window=WINDOW, interpret=False)


def paged(q, pool_k, pool_v, tables, positions, valid, k_scale=None, v_scale=None,
          layer=None):
    pool = {"k": pool_k, "v": pool_v}
    if k_scale is not None:
        pool.update(k_scale=k_scale, v_scale=v_scale)
    return paged_mod.paged_attention(
        q, pool, tables, positions, valid, page_size=PAGE,
        sm_scale=HD ** -0.5, window=WINDOW, layer=layer, interpret=False)


def flash_args(s):
    q = shape((B_TRAIN, SEQ, H, HD), jnp.bfloat16, s)
    kv = shape((B_TRAIN, SEQ, K, HD), jnp.bfloat16, s)
    return q, kv, kv


def paged_args(s, pool_dtype=jnp.bfloat16, stack=()):
    """``stack=(LAYERS,)``: the pools of all layers stacked, as the decode program carries
    them; the layer to read then rides as the last argument."""
    pool = shape((*stack, PAGES, PAGE, K, HD), pool_dtype, s)
    args = (shape((LANES, 1, H, HD), jnp.bfloat16, s), pool, pool,
            shape((LANES, MAX_LEN // PAGE), jnp.int32, s), shape((LANES,), jnp.int32, s),
            shape((LANES, MAX_LEN), jnp.bool_, s))
    if pool_dtype == jnp.int8:      # the kv_quant pool: per-slot fp32 scale pages
        args += (shape((*stack, PAGES, PAGE, K, 1), jnp.float32, s),) * 2
    else:
        args += (None, None)
    return args + ((shape((), jnp.int32, s),) if stack else ())


def mla(q_lat, q_rope, pool, tables, positions, valid):
    return mla_mod.mla_paged_attention(
        q_lat, q_rope, pool, tables, positions, valid, page_size=PAGE, sm_scale=0.135,
        interpret=False)


def mla_args(s):
    return (shape((LANES, MLA_H, MLA_RANK), jnp.bfloat16, s),
            shape((LANES, MLA_H, MLA_ROPE), jnp.bfloat16, s),
            shape((MLA_PAGES, PAGE, MLA_WIDTH), jnp.bfloat16, s),
            shape((LANES, MLA_MAX_LEN // PAGE), jnp.int32, s), shape((LANES,), jnp.int32, s),
            shape((LANES, MLA_MAX_LEN), jnp.bool_, s))


def test_flash_forward_is_named(one_chip):
    compiled = jax.jit(flash).lower(*flash_args(one_chip)).compile()
    assert kernels(compiled) == ["flash_fwd"]


def test_flash_backward_kernels_are_named(one_chip):
    def loss(q, k, v):
        return flash(q, k, v).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*flash_args(one_chip)).compile()
    assert kernels(compiled) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]


@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.int8], ids=["bf16", "int8"])
def test_paged_attention_is_named(one_chip, pool_dtype):
    compiled = jax.jit(paged).lower(*paged_args(one_chip, pool_dtype)).compile()
    assert kernels(compiled) == ["paged_attention"]


STACKED = re.compile(rf"= (?:bf16|s8)\[{LAYERS},{PAGES},{PAGE},{K},{HD}\]\S* ([\w\-]+)\(")


def pool_shaped(compiled) -> list:
    """The opcode of every instruction whose result has the shape of the stacked K or V
    pool (2 GB in bf16), less those that move no byte. (An int8 pool's fp32 scale planes,
    ``[..., K, 1]``, are 31 MB a stack and ARE laid out anew for the kernel's lane-dense
    ``[1, page_size * K]`` rows, as a layer's were before: no cell runs them.)"""
    free = {"parameter", "get-tuple-element", "bitcast"}
    return sorted(op for op in STACKED.findall(compiled.as_text()) if op not in free)


@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.int8], ids=["bf16", "int8"])
def test_paged_attention_reads_its_layer_of_the_stacked_pool(one_chip, pool_dtype):
    """The layer-indexed read at the serve cell's shapes (16 layers x 3840 pages, 32
    lanes): Mosaic accepts it, it is still ONE kernel named ``paged_attention``, and no
    instruction around it has the stack's shape: the layer is never sliced out."""
    compiled = jax.jit(paged).lower(*paged_args(one_chip, pool_dtype, (LAYERS,))).compile()
    assert kernels(compiled) == ["paged_attention"]
    assert pool_shaped(compiled) == []


def serve_cfg(**over):
    """The Mistral serve cell's model as the engine's programs see it (depth 16)."""
    from accelerate_tpu.models import llama

    return llama.LlamaConfig(
        vocab_size=32000, d_model=4096, n_layers=LAYERS, n_heads=H, n_kv_heads=K,
        head_dim_override=HD, d_ff=14336, max_seq=32768, sliding_window=WINDOW,
        tie_embeddings=False, scan_layers=True, **over)


def on_chip(make, sharding, dtype=None):
    """The shapes of ``make()``'s tree, placed on the described chip."""
    return jax.tree_util.tree_map(
        lambda x: shape(x.shape, dtype or x.dtype, sharding), jax.eval_shape(make))


def test_decode_program_writes_the_pool_in_place(one_chip, monkeypatch):
    """``serving._decode_multi_step_paged`` at the Mistral serve cell's shapes (depth 16,
    4 steps a dispatch): in the compiled program the only instructions of the pool's
    shape are the two in-place scatters of a layer's new K and V (the scatter and the
    fusion that holds it) — no ``copy``, ``dynamic-slice`` or ``dynamic-update-slice``
    of a 2 GB plane, which the layer scan's xs/ys form cost six times a decode step —
    and its temporaries are a fraction of one plane (5.34 GB before, 0.81 GB now)."""
    from accelerate_tpu import serving
    from accelerate_tpu.models import llama

    monkeypatch.setenv("ACCEL_PAGED_ATTN", "kernel")       # no chip here to choose it
    monkeypatch.setattr(paged_mod, "_interpret_default", lambda: False)
    cfg = serve_cfg()
    params = on_chip(lambda: llama.init_params(cfg), one_chip, jnp.bfloat16)
    cache = on_chip(lambda: llama.init_paged_cache(cfg, LANES, MAX_LEN, PAGES, PAGE), one_chip)
    lanes = lambda dtype, *more: shape((LANES, *more), dtype, one_chip)  # noqa: E731
    compiled = serving._decode_multi_step_paged.lower(
        params, cache, lanes(jnp.int32, MAX_LEN // PAGE), lanes(jnp.int32),
        lanes(jnp.int32), lanes(jnp.bool_), lanes(jnp.int32), lanes(jnp.int32),
        lanes(jnp.uint32, 4, 2), lanes(jnp.float32), lanes(jnp.float32), lanes(jnp.int32),
        cfg=cfg, n_steps=4, sample=False, page_size=PAGE).compile()
    assert kernels(compiled) == ["paged_attention"]
    assert pool_shaped(compiled) == ["fusion", "fusion", "scatter", "scatter"]
    plane = LAYERS * PAGES * PAGE * K * HD * 2
    assert compiled.memory_analysis().temp_size_in_bytes < plane // 2


def test_prefill_chunk_program_takes_the_flash_forward(one_chip, monkeypatch):
    """``serving._prefill_chunk_jit`` at the Mistral serve cell's shapes (depth 16, a
    512-token chunk against the 8 192-slot row, window 4096; ISSUE 31): Mosaic accepts the
    combination the prefill brings (a segment pair, traced offsets, a window, S != T), the
    program holds ONE kernel named ``flash_fwd`` (the layer scan's), and no instruction has
    the shape of ``_attention_cached``'s score tensor."""
    from accelerate_tpu import serving
    from accelerate_tpu.models import llama

    monkeypatch.setattr(flash_mod, "_interpret_default", lambda: False)
    cfg = serve_cfg(attn_impl="flash")                     # no chip here for auto to find
    params = on_chip(lambda: llama.init_params(cfg), one_chip, jnp.bfloat16)
    cache = on_chip(lambda: llama.init_cache(cfg, 1, MAX_LEN), one_chip)
    compiled = serving._prefill_chunk_jit.lower(
        params, shape((1, 512), jnp.int32, one_chip), shape((1, 512), jnp.bool_, one_chip),
        cache, cfg=cfg).compile()
    assert kernels(compiled) == ["flash_fwd"]
    assert not re.search(rf"\[1,{K},{H // K},512,{MAX_LEN}\]", compiled.as_text())


def test_mla_paged_attention_is_named(one_chip):
    """The latent kernel at the DeepSeek-V3 cell's shapes: Mosaic accepts its blocks and
    its VMEM (interpret mode proves neither), and the instruction carries its name."""
    compiled = jax.jit(mla).lower(*mla_args(one_chip)).compile()
    assert kernels(compiled) == ["mla_paged_attention"]


@pytest.mark.parametrize("module,fn,args", [
    (flash_mod, flash, flash_args), (paged_mod, paged, paged_args), (mla_mod, mla, mla_args)],
    ids=["flash", "paged", "mla"])
def test_a_name_changes_nothing_but_the_name(one_chip, monkeypatch, module, fn, args):
    """The same kernel compiled without ``name=``: same instruction count, same memory."""
    named = jax.jit(fn).lower(*args(one_chip)).compile()
    pallas_call = module.pl.pallas_call
    monkeypatch.setattr(
        module.pl, "pallas_call",
        lambda kernel, *a, name=None, **kw: pallas_call(kernel, *a, **kw))
    bare = jax.jit(lambda *xs: fn(*xs)).lower(*args(one_chip)).compile()
    assert kernels(bare) != kernels(named)
    count = lambda c: len(re.findall(r"^\s*(ROOT )?%[\w.\-]+ = ", c.as_text(), re.M))  # noqa: E731
    assert count(bare) == count(named)
    for field in ("argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes",
                  "generated_code_size_in_bytes"):
        assert getattr(bare.memory_analysis(), field) == getattr(named.memory_analysis(), field)


# --------------------------------------------------------------------------- Keye-VL-2.0
# 32 q / 4 kv heads x 128, 16 index heads x 64 (two keys a 128-lane pool row), 2048 keys
# kept; serve cell 16 lanes, pages of 16, max_len 32768, 24576 pages, depth 5.
KEYE_LANES, KEYE_K, KEYE_LEN, KEYE_PAGES, KEYE_TOPK = 16, 4, 32768, 24576, 2048


@pytest.mark.parametrize("index_dim,heads", [(64, 16), (128, 64)], ids=["keye_64", "dots3_128"])
def test_index_kernel_takes_keys_of_half_a_lane_tile(one_chip, index_dim, heads):
    """``dsa_index_scores`` over the pool ``index_pool_shape`` gives: Mosaic accepts 64-value
    keys laid two a row (it refuses a page cut out of a plane declared 64 wide), dots3's 128
    still compile, and both are the one kernel of that name."""
    from accelerate_tpu.ops import sparse_attention as sa

    def scores(q, w, pool, tables, positions, valid):
        return sa.dsa_index_scores(q, w, pool, tables, positions, valid, page_size=PAGE,
                                   interpret=False)

    s = one_chip
    pool = sa.index_pool_shape(KEYE_PAGES, PAGE, index_dim)
    assert pool == (KEYE_PAGES, PAGE * index_dim // 128, 128)
    compiled = jax.jit(scores).lower(
        shape((KEYE_LANES, heads, index_dim), jnp.bfloat16, s),
        shape((KEYE_LANES, heads), jnp.float32, s), shape(pool, jnp.bfloat16, s),
        shape((KEYE_LANES, KEYE_LEN // PAGE), jnp.int32, s), shape((KEYE_LANES,), jnp.int32, s),
        shape((KEYE_LANES, KEYE_LEN), jnp.bool_, s)).compile()
    assert kernels(compiled) == ["dsa_index_scores"]


def test_flash_forward_under_a_pair_mask_is_its_own_kernel(one_chip):
    """The prefill chunk of a sparse grouped-query layer: 512 queries against the 32768-slot
    row, 32 / 4 heads, the valid mask as the segment pair and the selection as an int8
    per-pair mask — Mosaic accepts the (512, 512) int8 block, and the kernel is named apart
    from the unmasked ``flash_fwd`` the other cells compile."""
    def masked(q, k, v, valid, pair, index):
        return flash_mod._flash_bhsd_offset(
            q, k, v, q_offset=index, kv_offset=0, causal=True, interpret=False,
            segments=(jnp.ones(q.shape[:2], jnp.int32), valid.astype(jnp.int32)), mask=pair)

    s = one_chip
    kv = shape((1, KEYE_LEN, KEYE_K, HD), jnp.bfloat16, s)
    compiled = jax.jit(masked).lower(
        shape((1, 512, H, HD), jnp.bfloat16, s), kv, kv, shape((1, KEYE_LEN), jnp.bool_, s),
        shape((1, 512, KEYE_LEN), jnp.int8, s), shape((), jnp.int32, s)).compile()
    assert kernels(compiled) == ["flash_fwd_masked"]


def keye_programs(one_chip, monkeypatch):
    """Keye-VL-2.0's serve cell as the engine's programs see it (depth 5), every trace-time
    probe answering as on the chip."""
    import accelerate_tpu.ops._common as ops_common
    import accelerate_tpu.utils.imports as imports
    from accelerate_tpu.models import keye

    monkeypatch.setattr(imports, "is_tpu_available", lambda: True)
    monkeypatch.setattr(ops_common, "is_tpu_available", lambda: True)
    cfg = keye.KeyeConfig(n_layers=5)
    params = on_chip(lambda: keye.init_params(cfg, jax.random.PRNGKey(0)), one_chip)
    return keye, cfg, params


def test_keye_decode_program_holds_the_index_and_the_attention_kernel(one_chip, monkeypatch):
    """``serving._decode_multi_step_paged`` at the Keye cell's shapes (7.5 GB of weights
    beside a 4.28 GB pool of K, V and index-key planes): the index kernel and
    ``paged_attention`` over the gathered rows once a layer, the experts' grouped
    products, and temporaries of a fraction of a GB."""
    from accelerate_tpu import serving

    keye, cfg, params = keye_programs(one_chip, monkeypatch)
    cache = on_chip(lambda: keye.init_paged_cache(cfg, KEYE_LANES, KEYE_LEN, KEYE_PAGES, PAGE),
                    one_chip)
    lanes = lambda dtype, *more: shape((KEYE_LANES, *more), dtype, one_chip)  # noqa: E731
    compiled = serving._decode_multi_step_paged.lower(
        params, cache, lanes(jnp.int32, KEYE_LEN // PAGE), lanes(jnp.int32),
        lanes(jnp.int32), lanes(jnp.bool_), lanes(jnp.int32), lanes(jnp.int32),
        lanes(jnp.uint32, 4, 2), lanes(jnp.float32), lanes(jnp.float32), lanes(jnp.int32),
        cfg=cfg, n_steps=4, sample=False, page_size=PAGE).compile()
    assert kernels(compiled) == ["dsa_index_scores"] * 5 + ["gmm"] * 15 + ["paged_attention"] * 5
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 29


def test_keye_prefill_chunk_program_takes_the_masked_flash_forward(one_chip, monkeypatch):
    from accelerate_tpu import serving

    keye, cfg, params = keye_programs(one_chip, monkeypatch)
    cache = on_chip(lambda: keye.init_cache(cfg, 1, KEYE_LEN), one_chip)
    compiled = serving._prefill_chunk_jit.lower(
        params, shape((1, 512), jnp.int32, one_chip), shape((1, 512), jnp.bool_, one_chip),
        cache, cfg=cfg).compile()
    assert kernels(compiled) == ["flash_fwd_masked"] * 5 + ["gmm"] * 15
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 29
