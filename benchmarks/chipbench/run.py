"""One cell, once, in a fresh process:

    python3 -m benchmarks.chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration in ``configs/``, the model's
family in ``families/`` (by the configuration's ``model_type``), its traffic in
``traffic/`` (whose ``window`` names the module that drives it) and each per-layer
metric's reader in ``metrics/``. Places the compile cache, builds weights on the device
from the seed, warms the cell's shapes (set-up), measures, frees the program, runs the
plain reference, prints each number compared beside its limit, and as the last line of
standard output one JSON object. ``setup_s`` runs from the moment the TPU runtime is up to
the window's start; the runtime's own start-up is printed under ``readings``. No TPU, or
fewer chips than the cell asks for: exit 3 and no result. ``--cpu-dry-run`` walks the same
path at toy widths and marks its line ``"dry_run": true`` — never a measurement.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"   # once per new program


class Tracer:
    """The traced slice. The window calls ``begin`` as it opens and ``poll`` at every step
    boundary: ``after`` seconds into the window ``poll`` starts the profiler and marks
    the slice's start, ``seconds`` later it marks the end and stops the profiler from a
    helper thread, so the window is not held up while the trace is written."""

    def __init__(self, on: bool, after: float, seconds: float, out_dir: str):
        self.on, self.after, self.seconds, self.dir = on, after, seconds, out_dir
        self.thread, self.host = None, [None, None]      # the slice on the host's clock

    def mark(self, name: str):
        import jax

        with jax.profiler.TraceAnnotation("cb." + name):
            pass

    def begin(self):
        self.opened = time.perf_counter()
        self.poll()

    def poll(self):
        if not self.on or self.thread is not None:
            return
        import jax

        now = time.perf_counter()
        if self.host[0] is None:
            if now - self.opened >= self.after:
                shutil.rmtree(self.dir, ignore_errors=True)
                jax.profiler.start_trace(self.dir)
                self.mark("slice_begin")
                self.host[0] = time.perf_counter()
        elif now - self.host[0] >= self.seconds:
            self.host[1] = now
            self.mark("slice_end")
            self.thread = threading.Thread(target=jax.profiler.stop_trace)
            self.thread.start()

    def end(self):
        if self.on:
            self.after = self.seconds = 0.0     # a window shorter than the slice: close it now
            self.poll()
            self.poll()
            self.thread.join()


def load_cell(workload: str, dry: bool, root: str = ROOT):
    """(benchmark, cell, config, traffic spec, the benchmark's directory) for one
    ``workloads`` entry, by name. The directory is the one that holds ``configs/``."""
    from . import traffic

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    where = os.path.dirname(os.path.dirname(os.path.join(root, entry["file"])))
    if dry:                     # toy sizes for the CPU: dry_run/<config>.json, never a cell's
        config.update(traffic.load("dry_run", entry["name"], root=where))
    return bench, cell, config, traffic.load("traffic", cell["traffic"], dry, where), where


def load_by_path(name: str, path: str):
    """The module in the file ``path``, under ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_family(model_type: str, where: str = HERE):
    """``families/<model_type>.py`` beside ``configs/``: everything the windows and the
    readers need of the model (the README has the contract)."""
    path = os.path.join(where, "families", f"{model_type}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no family for model_type {model_type!r}: looked for {path}")
    return load_by_path(f"cb_family_{model_type}", path)


def load_window(name: str, family):
    """The module that drives the traffic; what it calls on the family has to be there."""
    module = importlib.import_module(f"{__package__}.{name}")
    missing = [n for n in module.NEEDS if not hasattr(family, n)]
    if missing:
        raise NotImplementedError(
            f"{family.__file__} has no {', '.join(missing)}: {name} calls them, so this "
            f"family cannot drive a cell whose traffic names {name}")
    return module


def applies(metric: dict, cell: dict) -> bool:
    return "workloads" not in metric or cell["name"] in metric["workloads"]


def read_metric(name: str, run, where: str = HERE):
    """``metrics/<name>.json`` (or the name up to its first dot) names a reader — a
    function of trace_reduce.py — and its arguments; ``metrics/<name>.py`` is a reader of
    its own with ``read(run)``."""
    from . import trace_reduce

    for stem in (name, name.split(".")[0]):
        path = os.path.join(where, "metrics", stem)
        if os.path.exists(path + ".json"):
            with open(path + ".json") as f:
                spec = json.load(f)
            return getattr(trace_reduce, spec["reader"])(run, **spec.get("args", {}))
        if os.path.exists(path + ".py"):
            return load_by_path(f"cb_metric_{stem}", path + ".py").read(run)
    raise FileNotFoundError(f"no reader for per-layer metric {name!r} under metrics/")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--cpu-dry-run", action="store_true")
    ap.add_argument("--control", type=int, default=0,
                    help="1: also read the lower-precision control and the faults "
                         "(never set by the driver; see README)")
    ap.add_argument("--root", default=ROOT, help="where BENCHMARK.json lies (tests)")
    args = ap.parse_args(argv)
    t_start = T_START if argv is None else time.perf_counter()
    bench, cell, config, spec, where = load_cell(args.workload, args.cpu_dry_run, args.root)
    family = load_family(config["model_type"], where)
    window_module = load_window(spec["window"], family)

    import jax

    if args.cpu_dry_run:
        jax.config.update("jax_platforms", "cpu")
    elif not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if not args.cpu_dry_run and (devices[0].platform != "tpu" or len(devices) < cell["chips"]):
        print(f"chipbench: {cell['name']} needs {cell['chips']} TPU chip(s); found "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 3
    devices = devices[:cell["chips"]]

    from . import trace_reduce, work

    tracing = bool(args.trace)
    tracer = Tracer(tracing, spec.get("trace_after_s", 0), spec.get("trace_seconds", 5),
                    os.path.join(ROOT, ".cb_trace"))
    # Set-up's clock starts once the TPU runtime is up: its start-up read 9.4 to 14.4 s in
    # identical runs on one machine (PERF.md §2), no code of the repo moves it, and all of
    # the program's own set-up (weights, programs, warm-in) comes after it.
    t_ready = time.perf_counter()
    marks = {"runtime_start": t_ready - t_start}     # where the seconds before the window go
    ctx = types.SimpleNamespace(
        config=config, family=family, traffic=spec, seed=args.seed, seconds=args.seconds,
        devices=devices, dry=args.cpu_dry_run,
        mark=lambda name: marks.__setitem__(name, time.perf_counter() - t_ready),
        span=(lambda n: jax.profiler.TraceAnnotation("cb." + n)) if tracing
        else (lambda n: contextlib.nullcontext()))
    window = window_module.Window(ctx)
    window.warm()
    lowered = []                # programs lowered from here on: none may be (no compile)
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _s, **_kw: lowered.append(event) if event == LOWERING else None)
    obs = window.measure(args.seconds, tracer)
    compiled_in_window = len(lowered)
    setup_s = obs["t0"] - t_ready
    stats = [d.memory_stats() or {} for d in devices]
    memory_peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    t_check = time.perf_counter()
    numbers, readings = window.check(obs, bool(args.control))
    check_s = time.perf_counter() - t_check        # the reference's seconds, after the window

    # The configuration's ``limits`` name the numbers compared; what the check read besides
    # (numbers with no upper reading, PERF.md §2) goes under ``readings``, judged by nobody.
    limits = config["limits"]
    compared = {k: {"value": numbers.get(k, float("inf")), "limit": lim}
                for k, lim in limits.items()}
    readings = {**{k: v for k, v in numbers.items() if k not in limits}, **readings,
                "compiled_in_window": compiled_in_window, "setup_marks_s": marks,
                "window": obs.get("notes", {}), "check_s": check_s}
    correct = obs["failed"] == 0 and all(
        c["limit"] is not None and c["value"] <= c["limit"] for c in compared.values())
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    line = {"correct": bool(correct), "attempted": obs["attempted"], "failed": obs["failed"]}
    if args.cpu_dry_run:
        line["dry_run"] = True
    if tracing:
        trace = trace_reduce.Trace(tracer.dir)
        device.update(busy_s=trace.busy_s, window_s=trace.window_s)
        run = types.SimpleNamespace(
            obs=obs, trace=trace, config=config, family=family,
            memory_peak_bytes=memory_peak,
            slice_host=tracer.host,
            peak=None if args.cpu_dry_run else work.peaks(devices[0].device_kind))
        line["metrics"] = {}
        for m in bench["per_layer"]:
            if applies(m, cell) and not (args.cpu_dry_run and m["source"] == "device_trace"):
                v = read_metric(m["name"], run, where)
                if v is not None:
                    line["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        line["breakdown"] = trace.breakdown()
        readings["module_seconds"] = trace.module_seconds()
    else:
        e2e = {**obs["end_to_end"], "setup_s": setup_s}
        line["metrics"] = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                           for m in bench["end_to_end"] if applies(m, cell)}
    line["device"] = device
    line["readings"] = readings
    line["compared"] = compared
    for k, c in compared.items():
        print(f"compared {k} = {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
