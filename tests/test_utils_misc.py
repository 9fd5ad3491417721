"""Tests for utils.other, serialization, tqdm, LocalSGD, and the profiler context."""

import os
import pickle

import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu import Accelerator, LocalSGD
from accelerate_tpu.utils.other import (
    check_os_kernel,
    convert_bytes,
    extract_model_from_parallel,
    get_pretty_name,
    recursive_getattr,
    save,
)
from accelerate_tpu.utils.operations import ConvertOutputsToFp32
from accelerate_tpu.utils.serialization import (
    flatten_pytree,
    load_pytree_safetensors,
    save_pytree_safetensors,
    unflatten_to_nested_dict,
)


class TestOther:
    def test_extract_model_unwraps_fp32_closure(self):
        fn = lambda x: x  # noqa: E731
        wrapped = ConvertOutputsToFp32(fn)
        assert extract_model_from_parallel(wrapped, keep_fp32_wrapper=False) is fn
        assert extract_model_from_parallel(wrapped, keep_fp32_wrapper=True) is wrapped

    def test_save_pytree_safetensors_roundtrip(self, tmp_path):
        tree = {"layer": {"w": jnp.ones((2, 3)), "b": jnp.zeros((3,))}}
        save(tree, tmp_path / "model.safetensors")
        loaded = load_pytree_safetensors(tmp_path / "model.safetensors")
        np.testing.assert_allclose(np.asarray(loaded["layer"]["w"]), np.ones((2, 3)))

    def test_save_pickle_fallback(self, tmp_path):
        obj = {"a": 1, "b": "two"}
        save(obj, tmp_path / "obj.bin", safe_serialization=False)
        with open(tmp_path / "obj.bin", "rb") as f:
            assert pickle.load(f) == obj

    def test_bf16_roundtrip(self, tmp_path):
        tree = {"w": jnp.ones((4,), dtype=jnp.bfloat16)}
        save_pytree_safetensors(tree, tmp_path / "m.safetensors")
        loaded = load_pytree_safetensors(tmp_path / "m.safetensors")
        assert loaded["w"].dtype == jnp.bfloat16 or loaded["w"].dtype == np.float32

    def test_flatten_unflatten(self):
        tree = {"a": {"b": 1, "c": {"d": 2}}, "e": 3}
        flat = {k: v for k, v in flatten_pytree(tree).items()}
        assert set(flat) == {"a/b", "a/c/d", "e"}
        assert unflatten_to_nested_dict(flat) == tree

    def test_recursive_getattr(self):
        class A:
            pass

        a = A()
        a.b = A()
        a.b.c = 7
        assert recursive_getattr(a, "b.c") == 7

    def test_get_pretty_name(self):
        assert get_pretty_name(TestOther) == "TestOther"
        assert "int" in get_pretty_name(3)

    def test_convert_bytes(self):
        assert convert_bytes(1024) == "1.0 KB"
        assert convert_bytes(5) == "5 B"
        assert convert_bytes(3 * 1024**3) == "3.0 GB"

    def test_check_os_kernel_no_crash(self):
        check_os_kernel()


class TestTqdm:
    def test_main_process_only(self):
        from accelerate_tpu.utils.tqdm import tqdm

        bar = tqdm(range(3))
        assert bar.disable in (False, None)
        bar.close()

    def test_positional_bool_rejected(self):
        from accelerate_tpu.utils.tqdm import tqdm

        with pytest.raises(ValueError):
            tqdm(True, range(3))


class TestLocalSGD:
    def test_noop_single_process(self):
        acc = Accelerator(cpu=True)
        params = {"w": jnp.ones((2,))}
        with LocalSGD(accelerator=acc, local_sgd_steps=2) as lsgd:
            out = lsgd.step(params)
        assert out is params  # disabled on 1 process → passthrough


class TestProfile:
    def test_profile_writes_trace(self, tmp_path):
        from accelerate_tpu.utils.dataclasses import ProfileKwargs

        acc = Accelerator(cpu=True)
        seen = {}
        handler = ProfileKwargs(
            output_trace_dir=str(tmp_path / "trace"),
            on_trace_ready=lambda d: seen.setdefault("dir", d),
        )
        with acc.profile(handler):
            x = jnp.ones((128, 128)) @ jnp.ones((128, 128))
            x.block_until_ready()
        assert seen["dir"] == str(tmp_path / "trace")
        # jax.profiler.trace writes a plugins/profile/<ts>/ tree
        assert any(os.scandir(tmp_path / "trace"))


def test_get_tpu_info_probes():
    from accelerate_tpu.utils.environment import get_tpu_info

    info = get_tpu_info()
    assert info["backend"] == "cpu"
    assert info["device_count"] == 8
    assert "device_kind" in info
    # GCE metadata is absent in this sandbox — bounded probe must not raise or hang.
    assert "gce_accelerator" not in info or isinstance(info["gce_accelerator"], str)


def test_place_compile_cache_env_wins_else_fixed_checkout_path(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set → the helper touches no config (jax reads the
    variable itself); unset → the fixed ``<checkout>/.jax_cache``, equal across calls
    (the directory is part of the cache key: a moving path never hits)."""
    import jax

    from accelerate_tpu.utils.environment import place_compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert place_compile_cache() is None and updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert place_compile_cache() == place_compile_cache() == os.path.join(repo, ".jax_cache")
    assert [u for u in updates if u[0] == "jax_compilation_cache_dir"] == [
        ("jax_compilation_cache_dir", os.path.join(repo, ".jax_cache"))] * 2
    assert place_compile_cache("/elsewhere") == "/elsewhere"


def test_parity_helper_apis(tmp_path):
    """Reference-parity helpers: find_device, merge_dicts, is_port_in_use, version probes,
    write_basic_config (reference utils/__init__ surface)."""
    import jax.numpy as jnp

    from accelerate_tpu.commands.config import load_config_from_file, write_basic_config
    from accelerate_tpu.utils import (
        compare_versions,
        find_device,
        is_bf16_available,
        is_fp8_available,
        is_jax_version,
        is_port_in_use,
        merge_dicts,
    )

    assert is_bf16_available() and is_fp8_available()
    assert compare_versions("numpy", ">=", "1.0")
    assert is_jax_version(">=", "0.4")
    with pytest.raises(ValueError):
        compare_versions("numpy", "~=", "1.0")

    assert find_device({"a": [None, 3], "b": jnp.ones(2)}) is not None
    assert find_device({"a": [1, "x"]}) is None

    dest = {"a": {"b": 1}, "k": 0}
    assert merge_dicts({"a": {"c": 2}, "k": 9}, dest) == {"a": {"b": 1, "c": 2}, "k": 9}

    assert isinstance(is_port_in_use(1), bool)

    loc = tmp_path / "basic.yaml"
    assert write_basic_config("bf16", str(loc))
    cfg = load_config_from_file(str(loc))
    assert cfg.mixed_precision == "bf16"
    assert write_basic_config("bf16", str(loc)) is False  # existing config never overridden
    with pytest.raises(ValueError):
        write_basic_config("int3", str(tmp_path / "other.yaml"))


def test_parity_enums_and_ddp_kwargs():
    """LoggerType / ComputeEnvironment enums + DistributedDataParallelKwargs (reference
    utils/dataclasses.py:128,565,584): the one DDP knob with a TPU meaning (comm_hook)
    maps to gradient-compression reduce_dtype; CUDA-only knobs raise loudly."""
    import jax.numpy as jnp

    from accelerate_tpu.utils import (
        ComputeEnvironment,
        DistributedDataParallelKwargs,
        LoggerType,
        PrefixedDataset,
        is_peft_available,
    )

    assert "wandb" in LoggerType and LoggerType("tensorboard") is LoggerType.TENSORBOARD
    assert ComputeEnvironment("LOCAL_MACHINE") is ComputeEnvironment.LOCAL_MACHINE
    assert isinstance(is_peft_available(), bool)

    assert DistributedDataParallelKwargs().reduce_dtype is None
    assert DistributedDataParallelKwargs(comm_hook="bf16").reduce_dtype == jnp.bfloat16
    for bad in (
        dict(comm_hook="powersgd"),
        dict(static_graph=True),
        dict(find_unused_parameters=True),
        dict(bucket_cap_mb=50),
    ):
        with pytest.raises(ValueError):
            DistributedDataParallelKwargs(**bad)

    ds = PrefixedDataset([{"a": 1, "b": 2}, {"a": 3}], "x_")
    assert len(ds) == 2 and ds[0] == {"x_a": 1, "x_b": 2}


def test_ddp_comm_hook_applies_to_policy():
    """Passing DistributedDataParallelKwargs(comm_hook=...) through kwargs_handlers must
    land on the state's MixedPrecisionPolicy.reduce_dtype (the DDP-hook analog) — and a
    hook dtype that the train step would silently never apply (it compresses only when
    reduce_dtype == compute_dtype) must RAISE, per the handler's accepted-but-ignored-
    is-worse-than-an-error policy (advisor r2)."""
    import jax.numpy as jnp
    import pytest as _pytest

    from accelerate_tpu import Accelerator
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.utils import DistributedDataParallelKwargs

    def _reset():
        AcceleratorState._reset_state()
        GradientState._reset_state()
        PartialState._reset_state()

    _reset()
    acc = Accelerator(
        mixed_precision="bf16",
        kwargs_handlers=[DistributedDataParallelKwargs(comm_hook="bf16")],
    )
    assert acc.mixed_precision_policy.reduce_dtype == jnp.bfloat16
    _reset()
    with _pytest.raises(ValueError, match="never applied"):
        Accelerator(kwargs_handlers=[DistributedDataParallelKwargs(comm_hook="bf16")])
    _reset()
