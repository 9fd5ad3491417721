"""``.xplane.pb`` → busy/idle, per-op time, idle gaps by host span; and the readers that
per-layer metrics name in ``metrics/<metric>.json``.

The traced slice runs from the ``cb.slice_begin`` marker to the ``cb.slice_end`` marker,
both written by the benchmark with ``jax.profiler.TraceAnnotation`` — as are its host
spans (``cb.dispatch``, ``cb.fence``, ``cb.loss_read``, ``cb.submit``, ``cb.engine.step``),
which puts them on the profiler's clock beside the device's operations. Kernels carry no
names of their own yet, so an op is found by a pattern over ``<module>/<op>``.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

from . import work

SPAN = "cb."
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")   # their bodies' ops are listed


def op_name(text: str):
    """An "XLA Ops" event's HLO text → ``<op>_<dtype>_<dims>`` (+ ``__mosaic_`` for a Pallas
    kernel), or None for a control-flow op that only contains other ops."""
    op = text.split(" = ")[0].lstrip("%")
    if CONTAINER.match(op):
        return None
    shape = re.search(r"= \(?(\w+)\[([\d,]*)\]", text)
    if shape:
        op += f"_{shape.group(1)}_{shape.group(2).replace(',', '_')}"
    return op + ("__mosaic_" if "tpu_custom_call" in text else "")


def _stat(ev, key):
    return next((v for k, v in ev.stats if k == key), None)


class Trace:
    """Device operations and host spans of the traced slice, in ns on one clock."""

    def __init__(self, path: str):
        import jax

        if os.path.isdir(path):
            path = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))[-1]
        data = jax.profiler.ProfileData.from_file(path)
        self.devices, self.modules, self.spans = {}, {}, []
        fallback = []
        for plane in data.planes:
            for line in plane.lines:
                for ev in line.events:
                    t0, t1 = ev.start_ns, ev.start_ns + ev.duration_ns
                    if plane.name.startswith("/device:TPU:"):
                        if line.name == OPS_LINE and (name := op_name(ev.name)):
                            self.devices.setdefault(plane.name, []).append((t0, t1, name))
                        elif line.name == MODULES_LINE:
                            self.modules.setdefault(plane.name, []).append(
                                (t0, t1, re.sub(r"\(\d+\)$", "", ev.name)))
                    elif ev.name.startswith(SPAN):
                        self.spans.append((t0, t1, ev.name[len(SPAN):]))
                    elif (mod := _stat(ev, "hlo_module")) is not None:
                        fallback.append((t0, t1, f"{mod}/{ev.name}"))   # a CPU recording
        if not self.devices and fallback:
            self.devices["/host:CPU"] = fallback
        marks = {name: (t0, t1) for t0, t1, name in self.spans}
        everything = [iv for ops in self.devices.values() for iv in ops]
        self.begin = marks["slice_begin"][1] if "slice_begin" in marks else min(
            (t0 for t0, _, _ in everything), default=0)
        self.end = marks["slice_end"][0] if "slice_end" in marks else max(
            (t1 for _, t1, _ in everything), default=0)
        self.spans = sorted(s for s in self.spans if not s[2].startswith("slice_"))
        for dev, ops in self.devices.items():
            mods = self.modules[dev] = sorted(self.modules.get(dev, []))
            starts = np.array([m[0] for m in mods])
            named = []
            for t0, t1, name in sorted(ops):
                if t1 <= self.begin or t0 >= self.end:
                    continue
                if mods:
                    i = int(np.searchsorted(starts, t0, side="right")) - 1
                    if i >= 0 and t0 < mods[i][1]:
                        name = f"{mods[i][2]}/{name}"
                named.append((max(t0, self.begin), min(t1, self.end), name))
            self.devices[dev] = named

    @property
    def window_s(self) -> float:
        return (self.end - self.begin) / 1e9

    def _merged(self, ops):
        out = []
        for t0, t1, _ in ops:
            if out and t0 <= out[-1][1]:
                out[-1][1] = max(out[-1][1], t1)
            else:
                out.append([t0, t1])
        return out

    @property
    def busy_s(self) -> float:
        """Union of the intervals in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return float(np.mean([sum(b - a for a, b in self._merged(ops))
                              for ops in self.devices.values()])) / 1e9

    def op_seconds(self, pattern: str = ".") -> dict:
        """Summed device seconds by ``<module>/<op>``, averaged over the devices."""
        rx, out = re.compile(pattern), {}
        for ops in self.devices.values():
            for t0, t1, name in ops:
                if rx.search(name):
                    out[name] = out.get(name, 0.0) + (t1 - t0) / 1e9 / len(self.devices)
        return out

    def module_seconds(self) -> dict:
        """Device seconds of each jitted program's executions inside the slice (first device)."""
        out = {}
        for t0, t1, name in self.modules.get(sorted(self.devices)[0], []) if self.devices else []:
            if t1 > self.begin and t0 < self.end:
                out[name] = out.get(name, 0.0) + (min(t1, self.end) - max(t0, self.begin)) / 1e9
        return out

    def idle_gaps(self, floor_ns: int = 10_000) -> dict:
        """Idle seconds of the first device keyed ``<host span open when the gap
        began>_before_<module that ended it>``; a gap between two ops of one execution
        of a module (no host call can fill it) reads ``_inside_`` for ``_before_``."""
        if not self.devices:
            return {}
        dev = sorted(self.devices)[0]
        ops, mods = self.devices[dev], self.modules.get(dev, [])
        merged = self._merged(ops)
        starts = [t0 for t0, _, _ in ops]
        edges = [(self.begin, merged[0][0])] if merged else [(self.begin, self.end)]
        edges += [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
        if merged:
            edges.append((merged[-1][1], self.end))
        out = {}
        for g0, g1 in edges:
            if g1 <= g0:
                continue
            if g1 - g0 < floor_ns:
                key = "_gaps_under_10_us_"
            else:
                open_ = [s for s in self.spans if s[0] <= g0 < s[1]]
                span = max(open_)[2] if open_ else "_no_span_"
                i = int(np.searchsorted(starts, g1 - 1, side="left"))
                nxt = ops[i][2].split("/")[0] if i < len(ops) else "_end_of_slice_"
                inside = any(a <= g0 and g1 <= b for a, b, _ in mods)
                key = f"{span}_{'inside' if inside else 'before'}_{nxt}"
            out[key] = out.get(key, 0.0) + (g1 - g0) / 1e9
        return out

    def breakdown(self) -> dict:
        def top(d):
            return [[k[:64], v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
        return {"device_ops": top(self.op_seconds()), "idle_gaps": top(self.idle_gaps())}


# ----------------------------------------------------------------------------- readers
# reader(run, **args) → a number, or None when there is nothing to read. ``run`` has
# ``obs`` (the window's observations), ``trace`` (a Trace or None), ``config``, ``family``
# (the configuration's family module: the model's counts), ``peak`` (this chip's row of
# peaks.json) and ``memory_peak_bytes``.
def percentile(run, sample: str, q: float):
    xs = run.obs["samples"].get(sample)
    return float(np.percentile(xs, q)) if xs else None


def mean(run, sample: str):
    xs = run.obs["samples"].get(sample)
    return float(np.mean(xs)) if xs else None


def peak(run, sample: str):
    xs = run.obs["samples"].get(sample)
    return float(np.max(xs)) if xs else None


def value(run, key: str):
    return run.obs["values"].get(key)


def device_idle_share(run):
    t = run.trace
    return None if t is None or not t.window_s else 100.0 * (1.0 - t.busy_s / t.window_s)


def hbm_peak_gb(run):
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None


def mfu(run, rate: str, flops: str, **args):
    """The whole step's share of the chip's peak: a rate the window counted (tokens/s
    per chip) × the FLOPs one token needs (a count of the family's) ÷ peak."""
    r = run.obs["values"].get(rate)
    if r is None or run.peak is None:
        return None
    per = getattr(run.family, flops)(
        run.config, **{k: run.obs["values"][v] for k, v in args.items()})
    return 100.0 * r * per / run.peak["bf16_flops"]


def train_kernel_roofline(run, pattern: str, work_fn: str):
    """A kernel of the train step: the least seconds its work needs per step ÷ its device
    seconds per step, over the WHOLE executions of the step's module in the slice (the
    module that was busiest), on each chip, averaged."""
    t = run.trace
    if t is None or not t.modules:
        return None
    v, rx, shares = run.obs["values"], re.compile(pattern), []
    fl, by = getattr(run.family, work_fn)(run.config, v["batch"], v["seq"])
    for dev, ops in t.devices.items():
        inside = [m for m in t.modules.get(dev, []) if m[0] >= t.begin and m[1] <= t.end]
        if not inside:
            continue
        busiest = max({m[2] for m in inside},
                      key=lambda n: sum(b - a for a, b, k in inside if k == n))
        whole = [m for m in inside if m[2] == busiest]
        # the execution under way when the trace stops is recorded cut short, by a little
        # or by most of it: not a whole one (whole steps last alike to a part in 10 000)
        full = 0.98 * float(np.median([b - a for a, b, _ in whole]))
        whole = [m for m in whole if m[1] - m[0] >= full]
        secs = sum(t1 - t0 for t0, t1, name in ops if rx.search(name)
                   and any(a <= t0 < b for a, b, _ in whole)) / 1e9
        if secs:
            least = work.least_seconds(fl / len(t.devices), by / len(t.devices), run.peak)
            shares.append(100.0 * len(whole) * least / secs)
    return float(np.mean(shares)) if shares else None


def paged_attn_roofline(run, pattern: str):
    """Bytes of the pages each lane's ACTUAL length needs (+ q, o) in the decode
    dispatches of the slice ÷ bandwidth ÷ the kernel's device time there."""
    t = run.trace
    if t is None:
        return None
    secs = sum(t.op_seconds(pattern).values())
    sv, least = run.config["serve"], 0.0
    for s in run.obs.get("decode_steps", ()):
        if run.slice_host[0] <= s["t0"] and s["t1"] <= run.slice_host[1]:
            for j in range(sv["decode_steps"]):
                lens = [max(1, n - j) for n in s["lens"]]
                least += work.least_seconds(*run.family.paged_attn_work(
                    run.config, lens, sv["page_size"]), run.peak)
    return 100.0 * least / secs if secs and least else None

