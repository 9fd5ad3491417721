"""Launcher env serialization — the ``ACCELERATE_*`` wire protocol (L9 ↔ L0 glue).

TPU-native analog of reference ``utils/launch.py`` (/root/reference/src/accelerate/utils/
launch.py): ``prepare_simple_launcher_cmd_env`` (:97), ``prepare_multi_gpu_env`` (:194),
``prepare_tpu`` (:465), ``PrepareForLaunch`` (:654). The launcher serializes CLI flags + YAML
config into env vars; ``PartialState``/``AcceleratorState``/``Accelerator`` deserialize them
(SURVEY.md §1: the env-var namespace is the load-bearing wire protocol).

Key divergence: there is no torchrun. Multi-process rendezvous is the JAX distributed service —
the launcher picks a coordinator address and assigns ``ACCELERATE_PROCESS_ID`` per child;
``jax.distributed.initialize`` (called from ``PartialState``) does the handshake. On a TPU pod
each *host* runs exactly one process that drives all its local chips, so ``--num-processes``
means hosts, not chips — chip parallelism lives in the mesh env (``ACCELERATE_MESH_*``).
"""

from __future__ import annotations

import os
import sys
from typing import Any, Optional

from .constants import ENV_PREFIX

__all__ = [
    "prepare_simple_launcher_cmd_env",
    "prepare_multi_process_env",
    "mesh_env_from_args",
    "PrepareForLaunch",
]

_MESH_AXES = ("dp", "fsdp", "tp", "sp", "pp", "ep", "dcn_dp")


def _str_flag(value: bool) -> str:
    return "true" if value else "false"


def mesh_env_from_args(args: Any) -> dict[str, str]:
    """``--dp/--fsdp/--tp/--sp/--pp/--ep`` flags → ``ACCELERATE_MESH_*`` env."""
    env: dict[str, str] = {}
    for axis in _MESH_AXES:
        value = getattr(args, axis, None)
        if value is not None:
            env[f"{ENV_PREFIX}MESH_{axis.upper()}"] = str(value)
    return env


def _common_env(args: Any) -> dict[str, str]:
    env: dict[str, str] = {}
    # Dev-checkout robustness: children are plain `python script.py` subprocesses whose
    # sys.path[0] is the script's own directory — when accelerate_tpu is imported from a
    # source tree (not pip-installed), the package root must ride PYTHONPATH or every
    # launched script dies on `import accelerate_tpu` (entries already on PYTHONPATH
    # are preserved).
    import accelerate_tpu

    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(accelerate_tpu.__file__)))
    existing = os.environ.get("PYTHONPATH", "")
    if pkg_root not in existing.split(os.pathsep):
        env["PYTHONPATH"] = f"{pkg_root}{os.pathsep}{existing}" if existing else pkg_root
    if getattr(args, "mixed_precision", None):
        env[f"{ENV_PREFIX}MIXED_PRECISION"] = str(args.mixed_precision).lower()
    if getattr(args, "cpu", False) or getattr(args, "use_cpu", False):
        env[f"{ENV_PREFIX}USE_CPU"] = "true"
    if getattr(args, "debug", False):
        env[f"{ENV_PREFIX}DEBUG_MODE"] = "true"
    if getattr(args, "gradient_accumulation_steps", None):
        env[f"{ENV_PREFIX}GRADIENT_ACCUMULATION_STEPS"] = str(args.gradient_accumulation_steps)
    if getattr(args, "use_fsdp", False):
        env[f"{ENV_PREFIX}USE_FSDP"] = "true"
    if getattr(args, "fsdp_zero_stage", None):
        env[f"{ENV_PREFIX}FSDP_ZERO_STAGE"] = str(args.fsdp_zero_stage)
        env.setdefault(f"{ENV_PREFIX}USE_FSDP", "true")
    if getattr(args, "fsdp_cpu_offload", False):
        env[f"{ENV_PREFIX}FSDP_CPU_OFFLOAD"] = "true"
    if getattr(args, "fsdp_state_dict_type", None):
        env[f"{ENV_PREFIX}FSDP_STATE_DICT_TYPE"] = str(args.fsdp_state_dict_type)
    if getattr(args, "fsdp_min_weight_size", None):
        env[f"{ENV_PREFIX}FSDP_MIN_WEIGHT_SIZE"] = str(args.fsdp_min_weight_size)
    if getattr(args, "sp_mode", None):
        env[f"{ENV_PREFIX}SP_MODE"] = str(args.sp_mode)
    if getattr(args, "fp8_format", None):
        env[f"{ENV_PREFIX}FP8_FORMAT"] = str(args.fp8_format)
    if getattr(args, "fp8_margin", None) is not None:
        env[f"{ENV_PREFIX}FP8_MARGIN"] = str(args.fp8_margin)
    if getattr(args, "fp8_amax_history_len", None):
        env[f"{ENV_PREFIX}FP8_AMAX_HISTORY_LEN"] = str(args.fp8_amax_history_len)
    if getattr(args, "fp8_use_delayed_scaling", None):
        env[f"{ENV_PREFIX}FP8_DELAYED_SCALING"] = "true"
    if getattr(args, "fp8_opt_level", None) and args.fp8_opt_level != "O1":
        env[f"{ENV_PREFIX}FP8_OPT_LEVEL"] = str(args.fp8_opt_level)
    if getattr(args, "pp_num_microbatches", None):
        env[f"{ENV_PREFIX}PP_MICROBATCHES"] = str(args.pp_num_microbatches)
    if getattr(args, "pp_schedule", None):
        env[f"{ENV_PREFIX}PP_SCHEDULE"] = str(args.pp_schedule)
    if getattr(args, "pp_virtual_stages", None):
        env[f"{ENV_PREFIX}PP_VIRTUAL_STAGES"] = str(args.pp_virtual_stages)
    if getattr(args, "dispatch_batches", None) is not None:
        env[f"{ENV_PREFIX}DISPATCH_BATCHES"] = _str_flag(args.dispatch_batches)
    if getattr(args, "even_batches", None) is not None:
        env[f"{ENV_PREFIX}EVEN_BATCHES"] = _str_flag(args.even_batches)
    if getattr(args, "use_seedable_sampler", None) is not None:
        env[f"{ENV_PREFIX}USE_SEEDABLE_SAMPLER"] = _str_flag(args.use_seedable_sampler)
    if getattr(args, "project_dir", None):
        env[f"{ENV_PREFIX}PROJECT_DIR"] = str(args.project_dir)
    if getattr(args, "checkpoint_total_limit", None):
        env[f"{ENV_PREFIX}CHECKPOINT_TOTAL_LIMIT"] = str(args.checkpoint_total_limit)
    if getattr(args, "log_with", None):
        env[f"{ENV_PREFIX}LOG_WITH"] = str(args.log_with)
    env.update(mesh_env_from_args(args))
    # Virtual-device CPU simulation (--num-virtual-devices): the test backbone.
    nvd = getattr(args, "num_virtual_devices", None)
    if nvd:
        # Replace any inherited device-count flag — the explicit CLI value must win.
        prev = [
            f for f in os.environ.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        env["XLA_FLAGS"] = " ".join([*prev, f"--xla_force_host_platform_device_count={nvd}"])
        env[f"{ENV_PREFIX}USE_CPU"] = "true"
        env["JAX_PLATFORMS"] = "cpu"
    return env


def _script_cmd(args: Any) -> list[str]:
    cmd = []
    if not getattr(args, "no_python", False):
        cmd.append(sys.executable)
        if getattr(args, "module", False):
            cmd.append("-m")
    cmd.append(args.training_script)
    cmd.extend(getattr(args, "training_script_args", []) or [])
    return cmd


def prepare_simple_launcher_cmd_env(args: Any) -> tuple[list[str], dict[str, str]]:
    """Single-process launch: user script + serialized env (reference ``launch.py:97``)."""
    env = {**os.environ, **_common_env(args)}
    return _script_cmd(args), env


def prepare_multi_process_env(
    args: Any,
    process_id: int,
    num_processes: Optional[int] = None,
    coordinator_address: Optional[str] = None,
) -> dict[str, str]:
    """Env for one child of a multi-process (multi-host-style) launch.

    Reference analog: ``prepare_multi_gpu_env`` (``launch.py:194``) building torchrun's
    RANK/MASTER_ADDR — here the JAX coordinator triple.
    """
    num_processes = num_processes or int(getattr(args, "num_processes", 1) or 1)
    if coordinator_address is None:
        ip = getattr(args, "main_process_ip", None) or "127.0.0.1"
        port = getattr(args, "main_process_port", None) or 29500
        coordinator_address = f"{ip}:{port}"
    env = {**os.environ, **_common_env(args)}
    env[f"{ENV_PREFIX}COORDINATOR_ADDRESS"] = coordinator_address
    env[f"{ENV_PREFIX}NUM_PROCESSES"] = str(num_processes)
    env[f"{ENV_PREFIX}PROCESS_ID"] = str(process_id)
    return env


class PrepareForLaunch:
    """Picklable target for ``multiprocessing.spawn`` children (reference ``launch.py:654``).

    Sets the per-process ``ACCELERATE_*`` rendezvous env *inside* the child before calling the
    user function, so ``PartialState`` initializes the JAX distributed client correctly.
    """

    def __init__(
        self,
        launcher,
        num_processes: int,
        coordinator_address: str,
        use_cpu: bool = True,
        debug: bool = False,
        devices_per_process: int | None = None,
    ):
        self.launcher = launcher
        self.num_processes = num_processes
        self.coordinator_address = coordinator_address
        self.use_cpu = use_cpu
        self.debug = debug
        self.devices_per_process = devices_per_process

    def __call__(self, index: int, *args):
        os.environ[f"{ENV_PREFIX}COORDINATOR_ADDRESS"] = self.coordinator_address
        os.environ[f"{ENV_PREFIX}NUM_PROCESSES"] = str(self.num_processes)
        os.environ[f"{ENV_PREFIX}PROCESS_ID"] = str(index)
        os.environ["FORK_LAUNCHED"] = "true"
        if self.devices_per_process:
            import re

            # Override (not skip) any inherited count — e.g. the pytest parent's 8-device
            # conftest flag — so an explicit per-child topology always wins.
            flags = os.environ.get("XLA_FLAGS", "")
            flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags).strip()
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{self.devices_per_process}"
            ).strip()
            os.environ["ACCELERATE_DEVICES_PER_PROCESS"] = str(self.devices_per_process)
        if self.use_cpu:
            os.environ[f"{ENV_PREFIX}USE_CPU"] = "true"
            os.environ["JAX_PLATFORMS"] = "cpu"
            # jax may have been imported before this env took effect; the config
            # update works as long as no backend has initialized yet.
            try:
                import jax

                jax.config.update("jax_platforms", "cpu")
            except ImportError:  # pragma: no cover
                pass
        if self.debug:
            os.environ[f"{ENV_PREFIX}DEBUG_MODE"] = "true"
        self.launcher(*args)
