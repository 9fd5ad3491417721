"""L6 big-model inference: abstract init, device-map inference, offload, streamed dispatch.

Mirrors reference test coverage: ``tests/test_modeling_utils.py`` (device-map math on tiny
models), ``tests/test_offload.py`` (memmap roundtrip), ``tests/test_big_modeling.py``
(dispatch + forward equivalence).
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.big_modeling import (
    DispatchedParams,
    cpu_offload,
    disk_offload,
    dispatch_model,
    init_empty_weights,
    load_checkpoint_and_dispatch,
    stream_blocks,
)
from accelerate_tpu.models import llama
from accelerate_tpu.utils.modeling import (
    calculate_maximum_sizes,
    compute_module_sizes,
    convert_file_size_to_int,
    dtype_byte_size,
    find_tied_parameters,
    get_balanced_memory,
    get_max_memory,
    infer_auto_device_map,
    load_checkpoint_in_model,
    named_parameters,
    placement_for,
    save_sharded_checkpoint,
)
from accelerate_tpu.utils.offload import (
    OffloadedWeight,
    OffloadedWeightsLoader,
    extract_submodule_state,
    load_offloaded_weight,
    offload_state_dict,
    offload_weight,
)

TINY = dataclasses.replace(llama.CONFIGS["tiny"], attn_impl="xla")


def tiny_params():
    return llama.init_params(TINY, jax.random.PRNGKey(0))


# ----------------------------------------------------------------------------- size math
def test_dtype_byte_size():
    assert dtype_byte_size(jnp.float32.dtype) == 4
    assert dtype_byte_size(jnp.bfloat16.dtype) == 2
    assert dtype_byte_size(np.dtype("int8")) == 1
    assert dtype_byte_size(np.dtype("bool")) == 1 / 8
    # fp8: the bit width is the FIRST digit group, not the e4m3/e5m2 suffix digits.
    assert dtype_byte_size(jnp.float8_e4m3fn.dtype) == 1
    assert dtype_byte_size(jnp.float8_e5m2.dtype) == 1
    assert dtype_byte_size(np.dtype("int4")) == 0.5


def test_compute_module_sizes_abstract_matches_concrete():
    params = tiny_params()
    abstract = init_empty_weights(llama.init_params, TINY, jax.random.PRNGKey(0))
    assert compute_module_sizes(params) == compute_module_sizes(abstract)
    sizes = compute_module_sizes(params)
    assert sizes[""] == sum(v for k, v in sizes.items() if k.count("/") == 0 and k)
    # embed: vocab 256 × d 128 × 4 bytes
    assert sizes["embed"] == 256 * 128 * 4


def test_calculate_maximum_sizes():
    total, (largest, names) = calculate_maximum_sizes(tiny_params())
    assert total == compute_module_sizes(tiny_params())[""]
    assert largest == 256 * 128 * 4  # embed / lm_head are the largest leaves
    assert any("embed" in n or "lm_head" in n for n in names)


def test_convert_file_size():
    assert convert_file_size_to_int("1KB") == 1000
    assert convert_file_size_to_int("1KiB") == 1024
    assert convert_file_size_to_int("2GB") == 2 * 10**9
    assert convert_file_size_to_int(77) == 77
    with pytest.raises(ValueError):
        convert_file_size_to_int("bogus")


def test_get_max_memory_defaults_and_overrides():
    mm = get_max_memory()
    assert "cpu" in mm and 0 in mm and mm[0] > 0
    mm2 = get_max_memory({0: "1KiB", "cpu": 4096})
    assert mm2 == {0: 1024, "cpu": 4096}


# ----------------------------------------------------------------------------- tied params
def test_find_tied_parameters():
    params = tiny_params()
    assert find_tied_parameters(params) == []
    params["lm_head_tied"] = params["embed"]
    assert find_tied_parameters(params) == [["embed", "lm_head_tied"]]


# ------------------------------------------------------------------------- device mapping
def test_infer_auto_device_map_single_fit():
    params = tiny_params()
    total = compute_module_sizes(params)[""]
    dm = infer_auto_device_map(params, {0: 2 * total, "cpu": 0})
    assert set(dm.values()) == {0}


def test_infer_auto_device_map_spills_in_order():
    params = tiny_params()
    sizes = compute_module_sizes(params)
    # Device 0 fits the embed only; everything else spills to cpu, then disk.
    dm = infer_auto_device_map(
        params,
        {0: sizes["embed"] + 1, "cpu": sizes["layers/0"] + 1},
        no_split_prefixes=["layers/0", "layers/1"],
    )
    assert placement_for("embed", dm) == 0
    assert placement_for("layers/0/wq", dm) == "cpu"
    assert placement_for("layers/1/wq", dm) == "disk"
    assert placement_for("lm_head", dm) == "disk"


def test_infer_auto_device_map_no_split_keeps_blocks_whole():
    params = tiny_params()
    sizes = compute_module_sizes(params)
    half_block = sizes["layers/0"] // 2
    dm = infer_auto_device_map(
        params,
        {0: sizes["embed"] + half_block, "cpu": 10 * sizes[""]},
        no_split_prefixes=["layers/0", "layers/1"],
    )
    # The block could not be split to fill device 0's leftover space.
    assert placement_for("layers/0/wq", dm) == "cpu"
    assert placement_for("layers/0/w_down", dm) == "cpu"


def test_infer_auto_device_map_places_tied_weights_together():
    params = tiny_params()
    params["lm_head"] = params["embed"]  # tie
    sizes = compute_module_sizes(params)
    dm = infer_auto_device_map(params, {0: int(1.5 * sizes["embed"]), "cpu": 10 * sizes[""]})
    assert placement_for("embed", dm) == placement_for("lm_head", dm)


def test_get_balanced_memory_spreads_budget():
    params = tiny_params()
    mm = get_balanced_memory(params, {0: 10**9, 1: 10**9, "cpu": 0})
    assert mm[0] < 10**9 and mm[1] < 10**9
    total = compute_module_sizes(params)[""]
    assert mm[0] + mm[1] >= total  # both devices together still fit the model


# ----------------------------------------------------------------------------- offload IO
def test_offload_weight_roundtrip(tmp_path):
    w = np.random.default_rng(0).normal(size=(5, 7)).astype(np.float32)
    index = {}
    handle = offload_weight(w, "block/wq", tmp_path, index=index)
    assert index["block/wq"]["shape"] == [5, 7]
    got = handle.load()
    np.testing.assert_array_equal(np.asarray(got), w)
    # raw file + info load path
    got2 = load_offloaded_weight(tmp_path / "block--wq.dat", index["block/wq"])
    np.testing.assert_array_equal(np.asarray(got2), w)


def test_offload_bf16_roundtrip(tmp_path):
    w = jnp.asarray(np.random.default_rng(0).normal(size=(4, 4)), dtype=jnp.bfloat16)
    handle = offload_weight(np.asarray(w), "w", tmp_path)
    assert handle.dtype == "bfloat16"
    from accelerate_tpu.utils.offload import as_jax_array

    restored = as_jax_array(handle)
    assert restored.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(restored.astype(jnp.float32)), np.asarray(w.astype(jnp.float32))
    )


def test_offload_scalar(tmp_path):
    handle = offload_weight(np.float32(3.5), "s", tmp_path)
    assert np.asarray(handle.load()) == np.float32(3.5)


def test_offloaded_weights_loader(tmp_path):
    sd = {"a": np.ones((2, 2), np.float32), "b": np.zeros((3,), np.float32)}
    offload_state_dict(tmp_path, {"b": sd["b"]})
    loader = OffloadedWeightsLoader(state_dict={"a": sd["a"]}, save_folder=tmp_path)
    assert sorted(loader) == ["a", "b"]
    assert len(loader) == 2
    np.testing.assert_array_equal(np.asarray(loader["b"]), sd["b"])
    sub = extract_submodule_state(loader, "")
    assert set(sub) == {"a", "b"}


# --------------------------------------------------------------------- dispatch + stream
def test_dispatched_params_fetch_nested(tmp_path):
    params = tiny_params()
    dm = {"embed": 0, "layers": "cpu", "ln_f": 0, "lm_head": "disk"}
    dp = dispatch_model(params, dm, offload_dir=tmp_path)
    assert isinstance(dp.weights["layers/0/wq"], np.ndarray)
    assert isinstance(dp.weights["lm_head"], OffloadedWeight)
    layer0 = dp.fetch("layers/0")
    assert set(layer0) == set(params["layers"][0])
    np.testing.assert_allclose(
        np.asarray(layer0["wq"]), np.asarray(params["layers"][0]["wq"]), rtol=1e-6
    )
    fp = dp.memory_footprint()
    assert fp["cpu"] > 0 and fp["disk"] > 0 and fp["device"] > 0


def test_stream_blocks_order_and_prefetch(tmp_path):
    params = tiny_params()
    dp = cpu_offload(params)
    prefixes = [f"layers/{i}" for i in range(TINY.n_layers)]
    seen = [p for p, _ in stream_blocks(dp, prefixes, prefetch=2)]
    assert seen == prefixes


@pytest.mark.parametrize("mode", ["cpu", "disk"])
def test_streamed_forward_matches_plain(tmp_path, mode):
    params = tiny_params()
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, TINY.vocab_size, size=(2, 16)), dtype=jnp.int32
    )
    expected = llama.forward(params, tokens, TINY, shard_activations=False)
    dp = cpu_offload(params) if mode == "cpu" else disk_offload(params, tmp_path)
    got = llama.forward_streamed(dp, tokens, TINY)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=0, atol=0.1)


def test_streamed_forward_repeats_with_device_resident_blocks():
    """Regression: a second streamed pass over a MIXED placement must not hit deleted
    resident weights. fetch() must return the store's own array for device-resident
    leaves (a device_put alias would be freed by consume_block's explicit delete,
    killing the resident block for every later pass — found via the by_feature
    big_model_inference example, which streams twice)."""
    params = tiny_params()
    dm = {"embed": 0, "layers/0": 0, "layers/1": "cpu", "ln_f": 0, "lm_head": 0}
    dp = dispatch_model(params, dm)
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, TINY.vocab_size, size=(2, 16)), dtype=jnp.int32
    )
    expected = llama.forward(params, tokens, TINY, shard_activations=False)
    first = llama.forward_streamed(dp, tokens, TINY)
    second = llama.forward_streamed(dp, tokens, TINY)  # raised "Array has been deleted"
    np.testing.assert_allclose(np.asarray(first), np.asarray(expected), rtol=0, atol=0.1)
    np.testing.assert_array_equal(np.asarray(first), np.asarray(second))


def test_dispatch_model_auto_policy(tmp_path):
    params = tiny_params()
    sizes = compute_module_sizes(params)
    dp = dispatch_model(
        params,
        "auto",
        max_memory={0: sizes["embed"] + sizes["layers/0"] + 1, "cpu": 10 * sizes[""]},
        no_split_prefixes=["layers/0", "layers/1"],
    )
    fp = dp.memory_footprint()
    assert fp["device"] > 0 and fp["cpu"] > 0


# ----------------------------------------------------------- checkpoint load + dispatch
def test_save_sharded_checkpoint_and_index(tmp_path):
    params = tiny_params()
    index = save_sharded_checkpoint(params, tmp_path, max_shard_size="64KiB")
    files = sorted(p.name for p in tmp_path.glob("*.safetensors"))
    assert len(files) > 1, "tiny model should shard at 64KiB"
    assert (tmp_path / "model.safetensors.index.json").exists()
    with open(tmp_path / "model.safetensors.index.json") as f:
        on_disk = json.load(f)
    assert on_disk["weight_map"] == index["weight_map"]
    assert set(on_disk["weight_map"]) == set(named_parameters(params))


def test_load_checkpoint_in_model_roundtrip(tmp_path):
    params = tiny_params()
    save_sharded_checkpoint(params, tmp_path, max_shard_size="64KiB")
    abstract = init_empty_weights(llama.init_params, TINY, jax.random.PRNGKey(0))
    restored = load_checkpoint_in_model(abstract, tmp_path, device_map={"": 0})
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)), params, restored
    )


def test_load_checkpoint_and_dispatch_streams(tmp_path):
    params = tiny_params()
    ckpt_dir = tmp_path / "ckpt"
    save_sharded_checkpoint(params, ckpt_dir, max_shard_size="64KiB")
    abstract = init_empty_weights(llama.init_params, TINY, jax.random.PRNGKey(0))
    sizes = compute_module_sizes(params)
    dp = load_checkpoint_and_dispatch(
        abstract,
        ckpt_dir,
        device_map="auto",
        max_memory={0: sizes["embed"] + sizes["layers/0"] + 1, "cpu": sizes["layers/1"] + 1},
        offload_dir=tmp_path / "offload",
        no_split_prefixes=["layers/0", "layers/1"],
    )
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, TINY.vocab_size, size=(2, 8)), dtype=jnp.int32
    )
    expected = llama.forward(params, tokens, TINY, shard_activations=False)
    got = llama.forward_streamed(dp, tokens, TINY)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=0, atol=0.1)


def test_load_checkpoint_nonstrict_partial(tmp_path):
    params = tiny_params()
    partial = {k: v for k, v in params.items() if k != "lm_head"}
    save_sharded_checkpoint(partial, tmp_path)
    abstract = init_empty_weights(llama.init_params, TINY, jax.random.PRNGKey(0))
    restored = load_checkpoint_in_model(abstract, tmp_path, device_map={"": 0}, strict=False)
    assert "lm_head" not in restored
    np.testing.assert_array_equal(np.asarray(restored["embed"]), np.asarray(params["embed"]))
    with pytest.raises(KeyError):
        load_checkpoint_in_model(abstract, tmp_path, device_map={"": 0}, strict=True)


def test_load_checkpoint_dtype_override_all_placements(tmp_path):
    params = tiny_params()
    save_sharded_checkpoint(params, tmp_path)
    abstract = init_empty_weights(llama.init_params, TINY, jax.random.PRNGKey(0))
    dm = {"embed": 0, "layers": "cpu", "ln_f": 0, "lm_head": "disk"}
    restored = load_checkpoint_in_model(
        abstract, tmp_path, device_map=dm, offload_folder=tmp_path / "off", dtype=jnp.bfloat16
    )
    assert restored["embed"].dtype == jnp.bfloat16
    assert str(restored["layers"][0]["wq"].dtype) == "bfloat16"  # cpu numpy, ml_dtypes bf16
    assert restored["lm_head"].dtype == "bfloat16"  # OffloadedWeight handle


def test_load_checkpoint_bounded_residency(tmp_path):
    """Streaming the checkpoint must hold the resident
    ("cpu"-placed, converted) portion plus O(one tensor) of scratch — never a whole-shard
    dict. 16 x 1 MiB fp32 tensors in 4 MiB shards, half placed cpu (converted to bf16,
    0.5 MiB each resident), half disk; anonymous allocation peak (tracemalloc — memmap
    pages are file-backed and excluded by design) must stay under resident + 3 tensors,
    well below any shard-dict bound."""
    import tracemalloc

    n, shape = 16, (256, 1024)  # 1 MiB per fp32 tensor
    rng = np.random.default_rng(0)
    params = {f"w{i:02d}": rng.standard_normal(shape, dtype=np.float32) for i in range(n)}
    save_sharded_checkpoint(params, tmp_path, max_shard_size="4MB")
    abstract = {k: jax.ShapeDtypeStruct(shape, jnp.float32) for k in params}
    device_map = {k: ("cpu" if i < n // 2 else "disk") for i, k in enumerate(sorted(params))}

    tensor_bytes = int(np.prod(shape)) * 4
    resident_bytes = (n // 2) * tensor_bytes // 2  # bf16 halves the cpu-placed portion

    tracemalloc.start()
    try:
        restored = load_checkpoint_in_model(
            abstract, tmp_path, device_map=device_map,
            offload_folder=tmp_path / "off", dtype=jnp.bfloat16,
        )
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()

    assert peak <= resident_bytes + 3 * tensor_bytes, (
        f"loader residency blew the streaming bound: peak {peak / 2**20:.1f} MiB vs "
        f"resident {resident_bytes / 2**20:.1f} + 3 tensors {3 * tensor_bytes / 2**20:.1f} MiB"
    )
    # And the load is still correct: cpu leaves converted in RAM, disk leaves offloaded.
    assert str(restored["w00"].dtype) == "bfloat16"
    from accelerate_tpu.utils.offload import OffloadedWeight

    assert isinstance(restored["w15"], OffloadedWeight)
    np.testing.assert_allclose(
        np.asarray(restored["w00"], dtype=np.float32),
        params["w00"].astype(ml_bf16()).astype(np.float32),
    )


def ml_bf16():
    import ml_dtypes

    return np.dtype(ml_dtypes.bfloat16)


def test_iter_safetensors_bf16_views(tmp_path):
    """The raw per-tensor reader replaces the old whole-file safetensors.flax fallback
    for bf16: values must come back as zero-copy ml_dtypes views, equal to what was
    saved, without any jax materialization in the read path."""
    from accelerate_tpu.utils.modeling import iter_safetensors

    rng = np.random.default_rng(1)
    src = {
        "a": rng.standard_normal((64, 32), dtype=np.float32).astype(ml_bf16()),
        "b": rng.standard_normal((8,), dtype=np.float32),
        "c": np.float32(3.5),  # scalar: shape [] round-trips through reshape(())
    }
    save_sharded_checkpoint(src, tmp_path)
    got = dict(iter_safetensors(tmp_path / "model.safetensors"))
    assert set(got) == set(src)
    assert got["a"].dtype == ml_bf16() and not got["a"].flags.owndata  # view, not copy
    np.testing.assert_array_equal(
        got["a"].view(np.uint16), np.asarray(src["a"]).view(np.uint16)
    )
    np.testing.assert_array_equal(got["b"], src["b"])
    assert got["c"].shape == () and float(got["c"]) == 3.5


def test_load_checkpoint_shape_mismatch_raises(tmp_path):
    params = tiny_params()
    save_sharded_checkpoint(params, tmp_path)
    bad_cfg = dataclasses.replace(TINY, d_model=64)
    abstract = init_empty_weights(llama.init_params, bad_cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="Shape mismatch"):
        load_checkpoint_in_model(abstract, tmp_path, device_map={"": 0})


def test_cpu_offload_with_hook_chain():
    """The manual-control offload variant (reference big_modeling.py:216 /
    hooks.py:726): fetch() moves a model's params on-device WHOLE and caches them;
    offload() frees the HBM copy immediately (buffer delete — previously fetched trees
    are invalidated); fetching a hook with a prev_module_hook evicts the previous
    stage first, chaining a multi-model pipeline through one device's memory."""
    from accelerate_tpu import cpu_offload_with_hook

    p1 = {"w": jnp.ones((8, 8), jnp.float32)}
    p2 = {"w": jnp.full((8, 8), 2.0, jnp.float32)}

    fetch1, hook1 = cpu_offload_with_hook(p1)
    fetch2, hook2 = cpu_offload_with_hook(p2, prev_module_hook=hook1)

    d1 = fetch1()
    assert float(jnp.sum(d1["w"] @ d1["w"])) == 8 * 8 * 8
    assert fetch1() is d1  # cached while resident — repeated invocations don't re-transfer

    d2 = fetch2()  # evicts stage 1
    assert hook1._on_device is None
    with pytest.raises(RuntimeError):
        _ = np.asarray(d1["w"])  # stage-1 buffers were deleted, not GC'd
    assert float(d2["w"][0, 0]) == 2.0

    d1b = fetch1()  # re-fetch after eviction works (fresh transfer from the host copy)
    assert float(d1b["w"][0, 0]) == 1.0

    hook2.offload()
    hook1.offload()
    assert hook1._on_device is None and hook2._on_device is None
    hook1.offload()  # idempotent
