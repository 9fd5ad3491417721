"""``accelerate-tpu config`` — questionnaire → YAML default config.

TPU-native analog of reference ``commands/config/`` (cluster.py's prompt tree, config_args.py's
dataclass config objects with yaml/json IO, default path at
``~/.cache/huggingface/accelerate/default_config.yaml`` — reference ``config_args.py:30-40``).

The config file feeds ``accelerate-tpu launch`` defaults, which serializes it into the
``ACCELERATE_*`` env wire protocol (``utils/launch.py``). Interactive mode asks a compact
question tree (machines, processes, mesh axes, precision); ``config default`` writes sane
defaults non-interactively; ``config update`` rewrites an old file with current fields.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

__all__ = [
    "ClusterConfig",
    "default_config_file",
    "load_config_from_file",
    "save_config",
    "write_basic_config",
    "config_command",
    "config_command_parser",
]

cache_dir = os.environ.get(
    "ACCELERATE_TPU_CACHE", os.path.join(os.path.expanduser("~"), ".cache", "accelerate_tpu")
)
default_yaml_config_file = os.path.join(cache_dir, "default_config.yaml")
default_json_config_file = os.path.join(cache_dir, "default_config.json")


def default_config_file() -> str:
    return default_yaml_config_file if not os.path.isfile(default_json_config_file) else default_json_config_file


@dataclass
class ClusterConfig:
    """The whole launch-relevant configuration (reference ``config_args.py`` ClusterConfig).

    ``num_processes`` counts host processes (one per TPU VM host); per-chip parallelism is the
    mesh axes. ``-1`` on a mesh axis means fill-remaining (``MeshConfig`` semantics).
    """

    compute_environment: str = "LOCAL_MACHINE"  # or TPU_POD
    distributed_type: str = "NO"  # NO | MULTI_DEVICE | MULTI_HOST
    num_machines: int = 1
    num_processes: int = 1
    machine_rank: int = 0
    main_process_ip: Optional[str] = None
    main_process_port: Optional[int] = None
    mixed_precision: str = "no"  # no | bf16 | fp16 | fp8
    use_cpu: bool = False
    debug: bool = False
    # Mesh axes (chip parallelism).
    dp: int = -1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    pp: int = 1
    ep: int = 1
    # FSDP/ZeRO.
    fsdp_zero_stage: int = 0
    fsdp_cpu_offload: bool = False
    fsdp_min_weight_size: int = 1024
    fsdp_state_dict_type: str = "SHARDED_STATE_DICT"
    # Sequence parallelism flavor (ring attention / Ulysses all-to-all / allgather).
    sp_mode: str = "ring"
    # Pipeline microbatching / schedule / interleaved virtual stages.
    pp_num_microbatches: Optional[int] = None
    pp_schedule: Optional[str] = None       # None = gpipe; "1f1b" for the custom-VJP schedule
    pp_virtual_stages: Optional[int] = None  # >1 = interleaved (requires 1f1b)
    # fp8 recipe (when mixed_precision == fp8).
    fp8_format: str = "HYBRID"
    fp8_opt_level: str = "O1"
    fp8_margin: int = 0
    fp8_amax_history_len: int = 16
    fp8_use_delayed_scaling: bool = False
    # Gradient accumulation.
    gradient_accumulation_steps: int = 1
    # Dataloader behavior.
    dispatch_batches: Optional[bool] = None
    even_batches: bool = True
    use_seedable_sampler: bool = True
    # Checkpointing / tracking defaults.
    project_dir: Optional[str] = None
    checkpoint_total_limit: Optional[int] = None
    log_with: Optional[str] = None
    # CPU simulator.
    num_virtual_devices: Optional[int] = None
    # Pod fan-out (tpu-config / multi-host launch).
    tpu_name: Optional[str] = None
    tpu_zone: Optional[str] = None

    def to_dict(self) -> dict:
        out = dataclasses.asdict(self)
        return {k: v for k, v in out.items() if v is not None}

    def save(self, path: Optional[str] = None) -> str:
        return save_config(self, path)


def save_config(config: ClusterConfig, path: Optional[str] = None) -> str:
    path = path or default_yaml_config_file
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    data = config.to_dict()
    if str(path).endswith(".json"):
        Path(path).write_text(json.dumps(data, indent=2) + "\n")
    else:
        import yaml

        Path(path).write_text(yaml.safe_dump(data, sort_keys=False))
    return str(path)


def write_basic_config(mixed_precision: str = "no", save_location: Optional[str] = None):
    """Create and save a basic config non-interactively (reference
    ``commands/config/default.py:36``, exported as ``accelerate.utils.write_basic_config``).

    Probes the local backend for the device count and writes a single-machine config that
    fills the ``dp`` mesh axis. Returns the path written, or ``False`` if a config already
    exists there (reference semantics: never override silently).
    """
    save_location = save_location or default_yaml_config_file
    path = Path(save_location)
    if path.exists():
        print(
            f"Configuration already exists at {save_location}, will not override. "
            "Run `accelerate-tpu config` manually or pass a different `save_location`."
        )
        return False
    mixed_precision = mixed_precision.lower()
    if mixed_precision not in ("no", "fp16", "bf16", "fp8"):
        raise ValueError(
            f"`mixed_precision` should be one of 'no', 'fp16', 'bf16', or 'fp8'; got {mixed_precision}"
        )
    try:
        import jax

        num_devices = jax.local_device_count()
        use_cpu = jax.default_backend() == "cpu"
    except RuntimeError:  # no usable backend (chip held elsewhere) — still write a sane default
        num_devices, use_cpu = 1, True
    config = ClusterConfig(
        distributed_type="MULTI_DEVICE" if num_devices > 1 else "NO",
        mixed_precision=mixed_precision,
        use_cpu=use_cpu,
    )
    return save_config(config, str(path))


def load_config_from_file(path: Optional[str] = None) -> ClusterConfig:
    path = path or default_config_file()
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"No config file at {path}. Run `accelerate-tpu config` first or pass flags explicitly."
        )
    text = Path(path).read_text()
    if str(path).endswith(".json"):
        data = json.loads(text)
    else:
        import yaml

        data = yaml.safe_load(text)
    known = {f.name for f in dataclasses.fields(ClusterConfig)}
    return ClusterConfig(**{k: v for k, v in (data or {}).items() if k in known})


def _interactive_config() -> ClusterConfig:
    """Per-mode prompt tree (reference ``commands/config/cluster.py``'s 856-line
    questionnaire + ``commands/menu/`` TUI, compressed to the knobs this runtime has).

    Every multi-choice question is a cursor menu on a TTY (numbered prompt on pipes);
    numeric/boolean questions are free-form with defaults. Sub-trees only open when the
    parent answer makes them relevant — the reference's questionnaire structure.
    """
    from .menu import ask, ask_bool, ask_int, select

    cfg = ClusterConfig()

    # ---- compute environment -------------------------------------------------
    cfg.compute_environment = select(
        "In which environment are you running?",
        ["LOCAL_MACHINE", "TPU_POD", "CPU_SIMULATOR"],
    )
    if cfg.compute_environment == "CPU_SIMULATOR":
        cfg.use_cpu = True
        cfg.num_virtual_devices = ask_int("How many virtual devices?", 8)
    if cfg.compute_environment == "TPU_POD":
        cfg.tpu_name = ask("TPU pod name (gcloud)", None) or None
        cfg.tpu_zone = ask("TPU zone", None) or None
        cfg.num_machines = ask_int("How many hosts (TPU VMs) in the pod?", 1)
    else:
        cfg.num_machines = ask_int("How many machines (TPU hosts)?", 1)
    if cfg.num_machines > 1:
        cfg.machine_rank = ask_int("Rank of this machine", 0)
        cfg.main_process_ip = ask("Coordinator (rank-0 internal) IP", "127.0.0.1")
        cfg.main_process_port = ask_int("Coordinator port", 29500)
        cfg.distributed_type = "MULTI_HOST"
    cfg.num_processes = ask_int("Total host processes (one per host)", cfg.num_machines)

    # ---- precision -----------------------------------------------------------
    cfg.mixed_precision = select(
        "Mixed precision?", ["bf16", "no", "fp16", "fp8"], default=0
    )
    if cfg.mixed_precision == "fp8":
        cfg.fp8_format = select("fp8 format?", ["HYBRID", "E4M3"])
        cfg.fp8_margin = ask_int("fp8 scale margin (powers of 2 backed off)", 0)
        cfg.fp8_use_delayed_scaling = ask_bool("Use delayed (history-based) scaling?", False)
        if cfg.fp8_use_delayed_scaling:
            cfg.fp8_amax_history_len = ask_int("fp8 amax history length", 16)
        cfg.fp8_opt_level = select(
            "MS-AMP opt level? (O2 = scaled-fp8 AdamW moments, needs fused_adamw)",
            ["O1", "O2"],
        )

    # ---- ZeRO / FSDP ----------------------------------------------------------
    stage = select(
        "ZeRO/FSDP sharding stage?",
        [
            "0 — replicated params (plain data parallel)",
            "1 — shard optimizer state",
            "2 — + reduce-scatter gradients",
            "3 — + shard parameters (FSDP FULL_SHARD)",
        ],
    )
    cfg.fsdp_zero_stage = int(stage.split(" ")[0])
    if cfg.fsdp_zero_stage > 0:
        cfg.fsdp = ask_int("fsdp axis size (-1 = all remaining devices)", -1)
        cfg.dp = 1
        cfg.fsdp_cpu_offload = ask_bool(
            "Offload optimizer state to host RAM (ZeRO-Offload)?", False
        )
        cfg.fsdp_min_weight_size = ask_int(
            "Min parameter size to shard (smaller stay replicated)", 1024
        )
        cfg.fsdp_state_dict_type = select(
            "Checkpoint layout?", ["SHARDED_STATE_DICT", "FULL_STATE_DICT"]
        )

    # ---- model parallelism ----------------------------------------------------
    cfg.tp = ask_int("Tensor-parallel degree", 1)
    cfg.sp = ask_int("Sequence/context-parallel degree (long-context)", 1)
    if cfg.sp > 1:
        cfg.sp_mode = select(
            "Sequence-parallel mode?",
            ["ring", "ulysses", "allgather"],
        )
    cfg.pp = ask_int("Pipeline-parallel degree", 1)
    if cfg.pp > 1:
        mb = ask_int("Pipeline microbatches (0 = one per stage)", 0)
        cfg.pp_num_microbatches = mb or None
        sched = select("Pipeline schedule?", ["gpipe", "1f1b"])
        cfg.pp_schedule = sched if sched != "gpipe" else None
        if sched == "1f1b":
            v = ask_int("Interleaved virtual stages per device (1 = off)", 1)
            cfg.pp_virtual_stages = v if v > 1 else None
    cfg.ep = ask_int("Expert-parallel degree (MoE)", 1)

    # ---- training loop --------------------------------------------------------
    cfg.gradient_accumulation_steps = ask_int("Gradient accumulation steps", 1)
    if ask_bool("Configure dataloader behavior?", False):
        cfg.dispatch_batches = ask_bool(
            "Dispatch batches from the main process (IterableDataset mode)?", False
        )
        cfg.even_batches = ask_bool("Pad uneven final batches (even_batches)?", True)
        cfg.use_seedable_sampler = ask_bool("Use the seedable sampler?", True)
    if ask_bool("Configure checkpointing/tracking defaults?", False):
        cfg.project_dir = ask("Project directory (checkpoints/logs)", None) or None
        limit = ask_int("Max checkpoints to keep (0 = unlimited)", 0)
        cfg.checkpoint_total_limit = limit or None
        tracker = select(
            "Experiment tracker?",
            ["none", "tensorboard", "wandb", "mlflow", "jsonl"],
        )
        cfg.log_with = None if tracker == "none" else tracker
    cfg.debug = ask_bool("Enable collective debug (shape verification)?", False)
    return cfg


def config_command_parser(subparsers=None) -> argparse.ArgumentParser:
    description = "Create the default config file for accelerate-tpu launch."
    if subparsers is not None:
        parser = subparsers.add_parser("config", description=description)
    else:
        parser = argparse.ArgumentParser("accelerate-tpu config", description=description)
    parser.add_argument("subcommand", nargs="?", choices=[None, "default", "update"], default=None)
    parser.add_argument("--config_file", default=None, help="Where to write the YAML/JSON config.")
    if subparsers is not None:
        parser.set_defaults(func=config_command)
    return parser


def config_command(args) -> str:
    if args.subcommand == "default":
        cfg = ClusterConfig(mixed_precision="bf16")
    elif args.subcommand == "update":
        cfg = load_config_from_file(args.config_file)
    else:
        cfg = _interactive_config()
    path = save_config(cfg, args.config_file)
    print(f"accelerate-tpu configuration saved at {path}")
    return path
