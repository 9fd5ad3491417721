"""Request-scoped tracing: where did THIS request's latency go?

The serving stack's observability used to stop at aggregates — one terminal
``gateway.request/v1`` row per request, per-step pool counters — so "where did
this request's 400 ms go: queue, prefill padding, decode stalls behind another
lane's verify round, a COW re-materialization, or a preemption retry?" had no
answer. This module is the per-request layer: a :class:`Tracer` rides the
gateway + engine and emits one ``accelerate_tpu.telemetry.trace.span/v1`` record
per lifecycle phase, all carrying the same ``trace_id``:

===========  =================================================================
span kind    meaning / extra attributes
===========  =================================================================
``queue``    submit → admission (or → terminal, for requests that never ran)
``admit``    the admission decision: lane, ``kv_defer_retries`` (paged pool
             pressure re-tries before pages freed)
``prefill``  the admission prefill: ``mode`` (bucket/chunk/prefix), padded
             ``width`` vs actual ``prompt_len``, prefix ``hit``/``cow``/
             ``adopted_pages``
``decode``   one per decode round the request participated in: engine ``step``
             index (the causal link to ``serving.kv/v1``/``serving.spec/v1``
             records of the same step), batch ``occupancy``, ``tokens``
             emitted, spec ``proposed``/``accepted``
``handoff``  one cross-engine KV page handoff (disaggregated serving:
             src/dst replica, pages, bytes — splits the trace into its
             prefill-replica and decode-replica phases)
``first_token``  zero-duration: the client-visible first token (TTFT anchor)
``preempt``  the request lost its lane to a higher-priority one
``retry``    its retry was requeued (stream reset; attempt index)
``shed``     removed from the queue by overload shedding
``terminal`` final state: status, reason, ``ttft_s``/``tpot_s``/``n_tokens``
===========  =================================================================

Reconstruction: ``accelerate-tpu trace-report`` (``commands/trace_report.py``)
groups spans by ``trace_id`` into per-request timelines and a critical-path
breakdown (queue vs prefill vs decode vs decode-stall vs retry). TTFT is
recoverable from spans alone (``first_token.t1 - queue.t0``), and the stall
component is what spans uniquely expose: time spent RUNNING but not advancing,
i.e. admitted lanes waiting while other requests' prefills hold the host loop.

Overhead contract (same as :class:`~.core.Telemetry`): **disabled tracing costs
two attribute reads per engine step** — no clock calls, no dict lookups, no
records (asserted by ``tests/test_tracing.py``). A ``Tracer`` is enabled iff its
``Telemetry`` is (or an explicit ``sink`` is given); spans flow through the same
``Telemetry.emit`` pipeline (JSONL + trackers) as every other record.

**Sampling** (the flight-recorder tier, docs/telemetry.md): full per-request
tracing is unaffordable at fleet scale, so :meth:`start` can make a
deterministic HEAD decision per trace — every-Kth (``sample_every``) or seeded
probability (``sample_prob``), both clock-free and reproducible under a fixed
seed. An unsampled trace still produces every span record, but they are routed
to the :class:`~.recorder.FlightRecorder` ring only (``recorder.buffer``) —
no JSONL, no sinks, no per-trace side table. TAIL promotion
(:meth:`promote`, called by the gateway when a request ends badly: failed /
expired / shed / quarantined / deadline-breached) replays the buffered spans
verbatim through ``Telemetry.emit``, so slow-and-broken requests are always
fully traced while the happy path pays ring entries alone — and a promoted
trace reconstructs TTFT to the digit, because the span records ARE the ones
full tracing would have written.

**Program phases: one span, two sinks.** :func:`phase` names a piece of the
program's own work ``atpu.<name>`` (:func:`step_phase` is the same for a train
step). Every phase feeds the process-wide :data:`PHASES` ledger — always on, in
bounded memory, on ``clocks.PHASE_CLOCK_NS`` — and is a
``jax.profiler.TraceAnnotation`` besides, which reaches a TRACE only while a
``jax.profiler`` session is live (``start_trace`` is that switch — there is no
other) and lands there beside the device's operations, nested by time on the
calling thread's line. :class:`EnginePhase` is the helper the serving engine's
boundaries go through where they also emit :class:`Tracer` records: the phase,
plus — only while the request-scoped :class:`Tracer` is enabled — the
tracer-clock reads its span records are stamped with (docs/telemetry.md lists
the spans).
"""

from __future__ import annotations

import collections
import itertools
import random
import threading
from typing import Callable, Dict, NamedTuple, Optional

import jax

from .clocks import PHASE_CLOCK_NS, resolve_clock
from .schemas import TRACE_SPAN_SCHEMA

__all__ = ["Tracer", "TraceHandle", "TRACE_SPAN_SCHEMA", "phase", "step_phase",
           "Phase", "EnginePhase", "PhaseRecord", "PhaseLedger", "PHASES"]

#: Every program span's name starts with this, so a trace reader finds them all.
PHASE_PREFIX = "atpu."


class PhaseRecord(NamedTuple):
    """One closed phase. Times are ``clocks.PHASE_CLOCK_NS`` readings; ``self_ns``
    is the duration less the phases opened inside it on the same thread;
    ``depth`` counts the phases open around it (0: none); ``attrs`` is the dict
    the profiler's span got, ``set_metadata`` additions included."""

    name: str
    t0_ns: int
    t1_ns: int
    self_ns: int
    depth: int
    attrs: dict
    thread: int


class _OpenPhases(threading.local):
    """A thread's stack of open phases (built on the thread's first phase)."""

    def __init__(self):
        self.stack: list = []
        self.thread = threading.get_ident()


class PhaseLedger:
    """What the program remembers of its own phases, profiler or no profiler: a
    ring of the last :attr:`RING` closed phases (``dropped`` counts those that
    fell out of it) and, per name, totals that never fall out. One per process
    (:data:`PHASES`); every thread has its own stack of open phases, so nesting
    and self time are a thread's own."""

    RING = 65536

    def __init__(self):
        # Plain tuples in PhaseRecord's order (records() wraps them): cheaper to
        # build, and the collector can stop tracking a tuple of numbers, a string
        # and a dict of such, where it would walk 65 536 instances of a subclass.
        self._ring: collections.deque = collections.deque(maxlen=self.RING)
        # name -> [count, total_ns, self_ns, max_ns, max_t0_ns, max_attrs, sums]
        self._totals: Dict[str, list] = {}
        self._open = _OpenPhases()
        self._lock = threading.Lock()
        self.dropped = 0

    def _close(self, ph: "Phase", t1: int) -> None:
        """Pop ``ph`` off its thread's stack and book it: one record, its name's
        totals, and its duration against the phase around it."""
        open_ = self._open
        stack = open_.stack
        stack.pop()
        t0, attrs = ph._t0, ph.attrs
        dur = t1 - t0
        self_ns = dur - ph._inner_ns
        if stack:
            stack[-1]._inner_ns += dur
        with self._lock:
            ring = self._ring
            if len(ring) == self.RING:
                self.dropped += 1
            ring.append((ph.name, t0, t1, self_ns, len(stack), attrs, open_.thread))
            tot = self._totals.get(ph.name)
            if tot is None:
                tot = self._totals[ph.name] = [0, 0, 0, -1, 0, attrs, {}]
            tot[0] += 1
            tot[1] += dur
            tot[2] += self_ns
            if dur > tot[3]:
                tot[3], tot[4], tot[5] = dur, t0, attrs
            if attrs:
                sums = tot[6]
                for k, v in attrs.items():
                    if isinstance(v, (int, float)):
                        sums[k] = sums.get(k, 0) + v

    def records(self, since_ns: Optional[int] = None,
                until_ns: Optional[int] = None) -> list:
        """The ring's :class:`PhaseRecord` s that overlap ``[since_ns, until_ns]``
        (None: open on that side), in the order they closed — a phase after the
        phases inside it. A record that straddles an edge is kept whole: the
        caller clips."""
        with self._lock:
            out = list(self._ring)
        return [PhaseRecord._make(r) for r in out
                if (since_ns is None or r[2] >= since_ns)
                and (until_ns is None or r[1] <= until_ns)]

    def totals(self, prefix: str = "") -> Dict[str, dict]:
        """Per phase name (those that start with ``prefix``): ``count``,
        ``total_ns``, ``self_ns``, ``max_ns`` with the ``max_t0_ns`` and
        ``max_attrs`` of that longest phase, and ``sums`` — the running sum of
        every numeric attribute, i.e. the engine's per-dispatch counts as
        counters. A COPY as of the call (``sums`` too), a few microseconds for
        the engine's dozen names; ``max_attrs`` is the record's own dict."""
        with self._lock:
            return {
                name: {"count": t[0], "total_ns": t[1], "self_ns": t[2], "max_ns": t[3],
                       "max_t0_ns": t[4], "max_attrs": t[5], "sums": dict(t[6])}
                for name, t in self._totals.items() if name.startswith(prefix)}


#: The process's phase ledger. Always on: there is no switch.
PHASES = PhaseLedger()


class Phase:
    """Context manager behind :func:`phase`: the profiler's span and the
    :data:`PHASES` record, opened and closed by the same two statements, so the
    two have the same edges up to a constant offset between their clocks. It
    reads the ledger's clock exactly twice; an exception inside it still closes
    both."""

    __slots__ = ("name", "attrs", "_annotation", "_t0", "_inner_ns")

    def __init__(self, name: str, attrs: dict, annotation=None):
        self.name, self.attrs = name, attrs
        self._annotation = annotation if annotation is not None else (
            jax.profiler.TraceAnnotation(PHASE_PREFIX + name, **attrs))
        self._inner_ns = 0

    def __enter__(self) -> "Phase":
        self._annotation.__enter__()
        self._t0 = PHASE_CLOCK_NS()
        PHASES._open.stack.append(self)
        return self

    def __exit__(self, *exc) -> None:
        t1 = PHASE_CLOCK_NS()
        self._annotation.__exit__(*exc)
        PHASES._close(self, t1)

    def set_metadata(self, **attrs) -> None:
        """Attributes known only at the span's end: onto the profiler's span and
        into the record (and its name's sums)."""
        self._annotation.set_metadata(**attrs)
        self.attrs.update(attrs)


def phase(name: str, **attrs) -> Phase:
    """Context manager: the phase ``atpu.<name>`` with ``attrs``, into the
    :data:`PHASES` ledger always and into a ``jax.profiler`` session's trace
    while one is live. ``set_metadata(**attrs)`` on the entered object adds
    values known only at the phase's end."""
    return Phase(name, attrs)


def step_phase(name: str, step_num: int) -> Phase:
    """:func:`phase` for one step of a training loop: profiler tools group the
    device's work by the ``step_num`` of the step span that dispatched it."""
    return Phase(name, {"step_num": step_num}, jax.profiler.StepTraceAnnotation(
        PHASE_PREFIX + name, step_num=step_num))


class EnginePhase(Phase):
    """:func:`phase` for an engine boundary that also emits :class:`Tracer`
    records: ``t0`` on the tracer's clock, read only while that tracer is
    enabled. ``tracer`` is None otherwise, so a boundary with tracing off reads
    two attributes and no tracer clock (the overhead contract above); callers
    emit their span records through :meth:`span`."""

    __slots__ = ("tracer", "t0")

    def __init__(self, tracer: Optional["Tracer"], name: str, **attrs):
        super().__init__(name, attrs)
        self.tracer = tracer if tracer is not None and tracer.enabled else None
        self.t0 = 0.0

    def __enter__(self) -> "EnginePhase":
        super().__enter__()
        if self.tracer is not None:
            self.t0 = self.tracer._clock()
        return self

    def now(self) -> float:
        """The tracer's clock (only while ``tracer`` is not None)."""
        return self.tracer._clock()

    def span(self, engine_uid: int, kind: str, t1: float, **attrs) -> None:
        """One ``[t0, t1]`` span record on the trace bound to ``engine_uid``."""
        tracer = self.tracer
        tracer.span(tracer.handle_for(engine_uid), kind, self.t0, t1, **attrs)

#: Process-wide trace sequence: uid + submit time alone would collide when
#: several gateways run on injectable VIRTUAL clocks against one telemetry sink
#: (e.g. serve-bench replaying one trace per policy — every policy's request 0
#: would share "0:0.000000000" and trace-report would merge them).
_TRACE_SEQ = itertools.count()


class TraceHandle:
    """One live request's trace state (identity + the counters spans stamp).

    ``trace_id`` is gateway uid + submit time + a process-wide sequence number —
    unique within a process even across gateways/virtual clocks, and stable
    across the request's whole lifecycle, including preemption retries (a retry
    is a new attempt inside the SAME trace)."""

    __slots__ = ("trace_id", "uid", "tenant", "t_start", "kv_defers", "attempt",
                 "sampled")

    def __init__(self, uid: int, tenant: str, t_start: float,
                 sampled: bool = True):
        self.trace_id = f"{uid}:{t_start:.9f}:{next(_TRACE_SEQ):x}"
        self.uid = uid
        self.tenant = tenant
        self.t_start = t_start
        self.kv_defers = 0   # paged-pool admission defers observed for this request
        self.attempt = 0     # preemption retries re-admit under attempt n+1
        self.sampled = sampled  # head decision; tail promotion flips it True


class Tracer:
    """Span emitter threaded through gateway + engine.

    The gateway opens a trace per submit (:meth:`start`), binds it to the engine
    request uid after ``engine.submit`` (:meth:`bind_engine`) so the engine's
    prefill/decode instrumentation can attribute device work to the right trace,
    and closes it at the terminal state (:meth:`finish`). ``clock`` is injectable
    (tests and trace replay use a manual virtual clock — spans then share the
    gateway's deadline clock, so timelines and deadlines agree)."""

    def __init__(self, telemetry=None, clock: Optional[Callable[[], float]] = None,
                 sink: Optional[Callable[[dict], None]] = None,
                 sample_every: Optional[int] = None,
                 sample_prob: Optional[float] = None,
                 sample_seed: Optional[int] = None,
                 recorder=None):
        cfg = getattr(telemetry, "config", None)
        self.telemetry = telemetry
        self._sink = sink
        #: The ONE flag the hot path reads; spans are dropped wholesale when off.
        self.enabled = bool(sink) or (
            telemetry is not None and getattr(telemetry, "enabled", False)
        )
        #: Where unsampled spans buffer (tail-promotion source); defaults to
        #: the telemetry-owned FlightRecorder when one is configured.
        self.recorder = (getattr(telemetry, "recorder", None)
                         if recorder is None else recorder)
        # Inherit the bound recorder's time domain when no clock is injected:
        # buffered spans replay through the recorder's ring and cooldowns, so
        # a tracer stamping wall seconds against a virtual-clock recorder
        # would split one trace across two domains.
        self._clock = resolve_clock(
            clock, getattr(self.recorder, "_clock", None)
        )
        # Head sampling: every-Kth (deterministic counter) or seeded
        # probability — both resolvable from TelemetryConfig so production
        # wiring needs no extra plumbing. Explicit kwargs win over config.
        self.sample_every = int(
            getattr(cfg, "trace_sample_every", 1) if sample_every is None
            else sample_every
        )
        self.sample_prob = (
            getattr(cfg, "trace_sample_prob", None) if sample_prob is None
            else sample_prob
        )
        seed = (getattr(cfg, "trace_sample_seed", 0) if sample_seed is None
                else sample_seed)
        self._rng = (random.Random(seed) if self.sample_prob is not None
                     else None)
        self.spans_emitted = 0
        self.spans_buffered = 0
        self.traces_started = 0
        self.traces_sampled = 0
        self.traces_promoted = 0
        self._traces: Dict[int, TraceHandle] = {}      # gateway uid → handle
        self._by_engine: Dict[int, TraceHandle] = {}   # engine uid → handle

    # ------------------------------------------------------------------ lifecycle
    def _sample(self) -> bool:
        """The clock-free head-sampling decision for the next trace."""
        if self.sample_every > 1:
            return self.traces_started % self.sample_every == 0
        if self._rng is not None:
            return self._rng.random() < self.sample_prob
        return True

    def start(self, uid: int, tenant: str = "default",
              t: Optional[float] = None) -> Optional[TraceHandle]:
        """Open a trace for request ``uid``; returns None while disabled (callers
        store the handle wherever they track the request — a None handle makes
        every later emit a no-op)."""
        if not self.enabled:
            return None
        sampled = self._sample()
        self.traces_started += 1
        if sampled:
            self.traces_sampled += 1
        handle = TraceHandle(uid, tenant, self._clock() if t is None else t,
                             sampled=sampled)
        self._traces[uid] = handle
        return handle

    def bind_engine(self, handle: Optional[TraceHandle], engine_uid: int) -> None:
        """Associate an engine request uid with ``handle`` so engine-side spans
        (prefill, decode rounds, pool defers) land in the right trace."""
        if handle is not None:
            self._by_engine[engine_uid] = handle

    def handle_for(self, engine_uid: int) -> Optional[TraceHandle]:
        """The handle bound to ``engine_uid`` (None when unbound — engine-direct
        submissions trace nothing)."""
        return self._by_engine.get(engine_uid)

    def finish(self, handle: Optional[TraceHandle]) -> None:
        """Drop a terminal trace's state (its spans are already emitted)."""
        if handle is None:
            return
        self._traces.pop(handle.uid, None)
        stale = [k for k, v in self._by_engine.items() if v is handle]
        for k in stale:
            self._by_engine.pop(k, None)

    # ------------------------------------------------------------------ emission
    def span(self, handle: Optional[TraceHandle], kind: str, t0: float, t1: float,
             step: Optional[int] = None, **attrs) -> None:
        """Emit one span record on ``handle``'s trace. ``step`` is the engine
        decode-step index — the causal key joining this span to the
        ``serving/v1``/``serving.kv/v1``/``serving.spec/v1`` record of the same
        step. No-op on a None handle or while disabled."""
        if handle is None or not self.enabled:
            return
        record = {
            "schema": TRACE_SPAN_SCHEMA,
            "trace_id": handle.trace_id,
            "uid": handle.uid,
            "tenant": handle.tenant,
            "span": kind,
            "t0": round(t0, 9),
            "t1": round(t1, 9),
            "dur_s": round(t1 - t0, 9),
        }
        if step is not None:
            record["step"] = step
        if attrs:
            record.update(attrs)
        if not handle.sampled:
            # Unsampled trace: the span exists ONLY as a flight-ring entry
            # (no JSONL, no sinks) until tail promotion replays it. With no
            # recorder armed the span is dropped — head sampling alone.
            self.spans_buffered += 1
            if self.recorder is not None:
                self.recorder.buffer(record)
            return
        self.spans_emitted += 1
        if self.telemetry is not None:
            self.telemetry.emit(record)
        if self._sink is not None:
            self._sink(record)

    def event(self, handle: Optional[TraceHandle], kind: str,
              t: Optional[float] = None, step: Optional[int] = None,
              **attrs) -> None:
        """A zero-duration span (``first_token``, ``preempt``, ``shed``...).
        ``t`` lets the caller reuse a timestamp it already took — the gateway's
        first-token event shares the exact clock read its ``ttft_s`` uses, so
        trace-reconstructed TTFT equals the gateway's to the digit."""
        if handle is None or not self.enabled:
            return
        if t is None:
            t = self._clock()
        self.span(handle, kind, t, t, step=step, **attrs)

    def promote(self, handle: Optional[TraceHandle]) -> int:
        """Tail-promote an unsampled trace: flip its head decision so every
        LATER span emits in full, and replay the spans already buffered in the
        flight ring through ``Telemetry.emit`` (the gateway calls this before
        emitting the terminal event of a request that ended badly, so the
        promoted stream is chronological). No-op on sampled/None handles.
        Returns the number of ring spans replayed."""
        if handle is None or not self.enabled or handle.sampled:
            return 0
        handle.sampled = True
        self.traces_promoted += 1
        if self.recorder is None:
            return 0
        return self.recorder.promote(handle.trace_id)

    def count_defer(self, engine_uid: int) -> None:
        """One paged-pool admission defer observed for this engine request; the
        count lands on the eventual ``admit`` span as ``kv_defer_retries``."""
        handle = self._by_engine.get(engine_uid)
        if handle is not None:
            handle.kv_defers += 1

    def __repr__(self) -> str:
        return (
            f"Tracer(enabled={self.enabled}, live={len(self._traces)}, "
            f"spans_emitted={self.spans_emitted}, "
            f"spans_buffered={self.spans_buffered}, "
            f"promoted={self.traces_promoted})"
        )
