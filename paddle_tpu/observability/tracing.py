"""Request tracing: one span timeline per request, one chrome dump.

Reference counterpart: platform/profiler.cc RecordEvent +
tools/timeline.py:131 (the reference's host-span capture and its
chrome://tracing serializer). The reference stops at host annotations;
a serving runtime needs the REQUEST axis — "where did THIS slow
request spend its 300 ms" — so this module adds:

* ``Trace`` — one request's timeline. Created at ``Router.submit``
  (or a standalone server's ``submit``) when
  ``FLAGS_observability=trace``; carried on the request object across
  the router thread -> batcher thread -> completion callback, so the
  spans of one request land in one tree no matter which thread
  recorded them. Spans are (name, t0, t1, attrs) in ``time.monotonic``
  seconds; the parent relation is recovered at dump time by smallest
  enclosing interval, which keeps recording lock-cheap and
  thread-order-free.
* **Ambient context** — the batcher dispatches ONE batch for many
  requests, and the runner below it (serving.ProgramRunner) has a
  fixed ``run_batch(feed)`` signature; ``ambient()`` parks the batch's
  traces in a thread-local so execute/readback spans recorded deep in
  the runner attach to every co-batched request without threading
  trace handles through the runner protocol.
* **Global (non-request) events** — compile events from the Executor
  (core/executor.py _resolve_block/_resolve_scan, both called from
  the one lookup, Executor._bound_step), annotated with
  ``Program.fingerprint()``, the cache tier that satisfied the
  resolution (``disk`` rehydration vs ``cold`` compile; a memory hit
  never produces a compile event — the steady-state-serving tests
  assert their absence), and ``compiled.memory_analysis()`` sizes
  when the backend exposes them.
* ``dump_trace(path)`` — ONE chrome-trace/Perfetto JSON merging host
  RecordEvent spans (profiler.py — absorbed, not duplicated), request
  span trees, and global compile events (tools/timeline.py:273
  parity, extended with the request axis).

* **The device trace's clock** — ``span`` also enters a
  ``jax.profiler.TraceAnnotation`` named ``paddle_tpu:<name>`` with
  its attributes as metadata. The profiler is that sink's own gate:
  while a JAX profile is being taken, at any flag level, every program
  span lands in the ``.xplane.pb`` on the host thread that ran it, on
  the clock of the device's operations (benchmark/chip/program_spans.py
  reads them back).

* **The cycle record** — a third sink of ``span``, kept at every
  flag level: inside ``with cycle(name, ring)`` every span that closes
  on the thread adds its length under its name to the open record,
  which closes with the counts its owner sets and the collector's
  runs (four times a second, and at every slow one, also with the
  thread's and the process's processor time), and goes to the
  ring (``CycleRing``: the last 512 records, a histogram a phase,
  the slow ones to the flight recorder) and, while a profile runs,
  into it as a short ``paddle_tpu:<name>`` marker whose metadata is
  the record. The serving scheduler opens one a cycle
  (``slotpool.cycle``); a training loop wraps ``exe.run`` the same
  way.

Everything here is always compiled in. ``FLAGS_observability``
decides the request sinks: at ``off``/``metrics`` no request trace
is opened, no span is kept in the process beyond the open cycle's
sums and ``dump_trace`` writes an empty trace.
"""
from __future__ import annotations

import collections
import gc
import itertools
import json
import os
import statistics
import threading
import time
import weakref
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from .metrics import Histogram, metrics_on, trace_on

__all__ = ["Span", "Trace", "Tracer", "TRACER", "trace_on",
           "metrics_on", "start_request", "current_request_trace",
           "request_context", "ambient", "ambient_traces", "span",
           "record_global_event", "dump_trace", "reset", "SPAN_PREFIX",
           "CycleRing", "cycle", "current_cycle", "EXE_PHASES"]

# what every program span is called in a profiler trace
SPAN_PREFIX = "paddle_tpu:"
# True while a JAX profile is being taken: the gate of that sink
_profiling = TraceAnnotation.is_enabled


class Span:
    """One named host-side interval inside a request's timeline
    (reference platform/profiler.h:81 — RecordEvent's begin/end pair
    is the same shape, minus the request attribution)."""

    __slots__ = ("name", "t0", "t1", "attrs")

    def __init__(self, name: str, t0: float, t1: float,
                 attrs: Optional[dict] = None):
        self.name = name
        self.t0 = t0
        self.t1 = t1
        self.attrs = attrs or {}


class Trace:
    """One request's timeline: request id + span list + outcome.
    ``add_span`` may be called from any thread (router, batcher,
    completion callback); ``finish`` seals the trace, records the root
    ``request`` span, and hands it to the tracer sink + flight
    recorder (observability/flight.py). No direct reference
    counterpart: the reference profiler aggregates by event NAME
    (platform/profiler.cc); per-request trees are this runtime's
    addition."""

    __slots__ = ("request_id", "seq", "attrs", "t_start", "t_end",
                 "status", "slo_violated", "spans", "owner", "_lock",
                 "_done")

    def __init__(self, request_id: str, seq: int, owner: str = "router",
                 **attrs):
        self.request_id = request_id
        self.seq = seq
        self.attrs = attrs
        self.t_start = time.monotonic()
        self.t_end = None
        self.status = None
        self.slo_violated = False
        self.spans: List[Span] = []
        self.owner = owner
        self._lock = threading.Lock()
        self._done = False

    def add_span(self, name: str, t0: float, t1: float, **attrs):
        with self._lock:
            if not self._done:
                self.spans.append(Span(name, t0, t1, attrs))

    def finish(self, status: str = "ok", slo_violated: bool = False,
               **attrs):
        with self._lock:
            if self._done:
                return
            self._done = True
            self.t_end = time.monotonic()
            self.status = status
            self.slo_violated = bool(slo_violated)
            self.attrs.update(attrs)
            # the root span must ENCLOSE every child (parent recovery
            # is by smallest enclosing interval): child t0s can
            # precede this Trace's construction by microseconds (e.g.
            # the router stamps t_submit before opening the trace),
            # so widen the root to the span hull
            t0 = min([self.t_start] + [s.t0 for s in self.spans])
            t1 = max([self.t_end] + [s.t1 for s in self.spans])
            self.t_start, self.t_end = t0, t1
            self.spans.append(Span("request", t0, t1,
                                   {"status": status}))
        TRACER._completed(self)
        from . import flight  # deferred: flight imports metrics too

        flight.RECORDER.record(self.timeline(),
                               incident=(status != "ok"
                                         or self.slo_violated))

    def timeline(self) -> dict:
        """JSON-able summary: the flight-recorder entry shape."""
        lat = None
        if self.t_end is not None:
            lat = round((self.t_end - self.t_start) * 1e3, 3)
        return {
            "request_id": self.request_id,
            "status": self.status,
            "slo_violated": self.slo_violated,
            "latency_ms": lat,
            **{k: v for k, v in self.attrs.items()},
            "spans": [
                {"name": s.name,
                 "t0_ms": round((s.t0 - self.t_start) * 1e3, 3),
                 "dur_ms": round((s.t1 - s.t0) * 1e3, 3),
                 **({"attrs": s.attrs} if s.attrs else {})}
                for s in sorted(self.spans, key=lambda s: s.t0)],
        }


class Tracer:
    """Process-global trace sink: completed request traces plus
    global (non-request) events, both bounded rings (the in-process
    analogue of the reference's DeviceTracer event store,
    platform/profiler.cc, that tools/timeline.py:131 renders)."""

    def __init__(self, max_traces: int = 1024, max_events: int = 4096):
        self._lock = threading.Lock()
        self._seq = itertools.count(1)
        self.completed = collections.deque(maxlen=max_traces)
        self.global_events = collections.deque(maxlen=max_events)

    def start_request(self, owner: str = "router", **attrs) \
            -> Optional[Trace]:
        """A new Trace when FLAGS_observability=trace, else None (the
        per-request gate every caller shares)."""
        if not trace_on():
            return None
        seq = next(self._seq)
        return Trace(f"req-{seq:08d}", seq, owner=owner, **attrs)

    def next_request_id(self) -> str:
        """Request id without span capture (metrics level: the flight
        recorder still names requests in incident reports)."""
        return f"req-{next(self._seq):08d}"

    def _completed(self, trace: Trace):
        with self._lock:
            self.completed.append(trace)

    def record_global_event(self, name: str, t0: float, t1: float,
                            **attrs):
        if not trace_on():
            return
        with self._lock:
            self.global_events.append(Span(name, t0, t1, attrs))

    def reset(self):
        with self._lock:
            self.completed.clear()
            self.global_events.clear()


TRACER = Tracer()
start_request = TRACER.start_request
record_global_event = TRACER.record_global_event


# --- ambient context (cross-layer span attachment) ---------------------
class _ThreadState(threading.local):
    """What is parked on a thread, with nothing parked as the class's
    defaults: a read of an unset `threading.local` attribute raises
    inside `getattr`, ten times the cost of a plain lookup, and every
    span reads two of these."""
    request_trace = None
    batch_traces = None
    cycle = None


_tls = _ThreadState()


class request_context:
    """Parks ONE request trace in a thread-local for the duration of a
    downstream synchronous call (Router._try_forward wraps
    ``handle.submit`` in this so the server attaches to the router's
    trace instead of opening its own)."""

    def __init__(self, trace: Optional[Trace]):
        self._trace = trace

    def __enter__(self):
        self._prev = _tls.request_trace
        _tls.request_trace = self._trace
        return self._trace

    def __exit__(self, *exc):
        _tls.request_trace = self._prev
        return False


def current_request_trace() -> Optional[Trace]:
    return _tls.request_trace


class ambient:
    """Parks a BATCH's traces in a thread-local so spans recorded
    below a fixed-signature boundary (runner.run_batch) attach to
    every co-batched request."""

    def __init__(self, traces):
        self._traces = [t for t in (traces or []) if t is not None]

    def __enter__(self):
        self._prev = _tls.batch_traces
        _tls.batch_traces = self._traces
        return self._traces

    def __exit__(self, *exc):
        _tls.batch_traces = self._prev
        return False


def ambient_traces() -> List[Trace]:
    return _tls.batch_traces or []


def cache_tier(exe, compiles_before, disk_loads_before) -> str:
    """Which tier satisfied the executable resolutions inside a
    dispatch window, from the executor's counter deltas: any fresh
    XLA compile = ``cold``, else any warm-start disk rehydration =
    ``disk``, else ``memory``. Annotates the dispatch/execute spans
    so a retained incident timeline says "this slow request was
    compiling" without cross-referencing the global compile events."""
    if exe.compile_count > compiles_before:
        return "cold"
    if exe.disk_load_count > disk_loads_before:
        return "disk"
    return "memory"


class span:
    """Context manager recording one (name, t0, t1) span into every
    ambient trace and, while a JAX profile is being taken, into the
    profiler's trace as ``paddle_tpu:<name>`` (reference
    platform/profiler.h:81 RecordEvent, which feeds the reference's
    device tracer the same way). With neither sink it costs one
    thread-local lookup and one call into the profiler's gate. Inside
    an open cycle record (``cycle``) it also adds its length to the
    record under its name: two clock reads and one dictionary update.

    Attributes that cost anything to compute are set late, and only
    for a sink: ``if sp.recording: sp.attrs[...] = ...`` inside the
    block."""

    __slots__ = ("name", "attrs", "_traces", "_cycle", "_t0", "_ann")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs

    @property
    def recording(self) -> bool:
        """True inside the block when some sink takes the span's
        attributes (a cycle record takes its length only)."""
        return bool(self._traces) or self._ann is not None

    def __enter__(self):
        traces = self._traces = _tls.batch_traces
        open_cycle = self._cycle = _tls.cycle
        if traces or open_cycle is not None:
            self._t0 = time.monotonic()
        if _profiling():
            self._ann = TraceAnnotation(SPAN_PREFIX + self.name)
            self._ann.__enter__()
        else:
            self._ann = None
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            if self.attrs:
                self._ann.set_metadata(**self.attrs)
            self._ann.__exit__(*exc)
        open_cycle = self._cycle
        if open_cycle is not None:
            t1 = time.monotonic()
            phases = open_cycle.phases
            phases[self.name] = phases.get(self.name, 0.0) \
                + (t1 - self._t0)
        if self._traces:
            t1 = time.monotonic()
            for tr in self._traces:
                tr.add_span(self.name, self._t0, t1, **self.attrs)
        return False


class execute_span(span):
    """``span("execute")`` whose ``cache`` attr is derived from the
    executor's compile/disk-load counter deltas across the block —
    the ONE copy of the dispatch-attribution convention shared by
    serving.ProgramRunner.run_batch and
    predictor.AnalysisPredictor._run_feed. Open it BEFORE the
    prepared-cache lookup: a lookup miss is itself the compile the
    tier must attribute."""

    __slots__ = ("_exe", "_c0", "_d0")

    def __init__(self, exe, **attrs):
        super().__init__("execute", **attrs)
        self._exe = exe

    def __enter__(self):
        self._c0 = self._exe.compile_count
        self._d0 = self._exe.disk_load_count
        return super().__enter__()

    def __exit__(self, *exc):
        if self.recording:
            self.attrs["cache"] = cache_tier(self._exe, self._c0,
                                             self._d0)
        return super().__exit__(*exc)


# --- the cycle record -------------------------------------------------
# A ring keeps this many records. A cycle is slow when it takes over
# SLOW_CYCLE_FACTOR times the median of the ring's cycles with its key,
# once the ring holds SLOW_CYCLE_MIN of those; every REFRESH_EVERY
# records the ring works the medians out again. The processor time is
# read when a cycle closes CPU_READ_S or more after the last reading,
# and when a slow one closes, for the cycles since the reading before:
# under the chip machine's kernel (gVisor) one reading of
# `time.thread_time` or `time.process_time` costs 6-10 microseconds
# and steps by 10 ms, so a reading a cycle would be most of a short
# cycle's record and say little. Constants, not flags.
CYCLE_RING_SIZE = 512
SLOW_CYCLE_FACTOR = 2.0
SLOW_CYCLE_MIN = 32
REFRESH_EVERY = 32
CPU_READ_S = 0.25
# the executor's spans of one dispatch: the phases a ring keeps a
# histogram of unless it is given its own
EXE_PHASES = ("exe.feed", "exe.lookup", "exe.state", "exe.call",
              "exe.store", "exe.fetch")

# the collector's runs, process-wide (a collection holds every thread
# of the interpreter): [seconds, runs, start of the run in progress]
_gc_seen = [0.0, 0, 0.0]
_gc_rings = [0]
# the run in progress in a profile, while one is being taken
_gc_marker = []


def _on_gc(phase, info):
    """`gc.callbacks` hook, installed while any ring exists: the
    collector's time for the open records and, while a profile runs,
    each run as `paddle_tpu:gc`."""
    if phase == "start":
        _gc_seen[2] = time.monotonic()
        if _profiling():
            ann = TraceAnnotation(SPAN_PREFIX + "gc",
                                  generation=info["generation"])
            ann.__enter__()
            _gc_marker.append(ann)
    else:
        _gc_seen[0] += time.monotonic() - _gc_seen[2]
        _gc_seen[1] += 1
        if _gc_marker:
            _gc_marker.pop().__exit__(None, None, None)


def _gc_unwatch():
    _gc_rings[0] -= 1
    if not _gc_rings[0] and _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


def _ms(seconds: float) -> float:
    return round(seconds * 1e3, 4)


class CycleRing:
    """What a loop keeps of its cycles at every flag level: the last
    CYCLE_RING_SIZE records (`records()`), one fixed-bucket histogram
    a phase (`phases`: {label: span name}; `wall` and `gc` beside
    them; `summary()` gives p50/p95/max, `metric_samples` the series),
    the count of slow cycles, and each slow one as a `slow_cycle`
    incident in the flight recorder, which is gated on
    FLAGS_observability."""

    def __init__(self, owner: str = "", phases=None):
        self.owner = owner
        labels = dict(phases) if phases is not None \
            else {name: name for name in EXE_PHASES}
        self._hist = {label: Histogram() for label in
                      (*labels, "gc", "wall")}
        self._observed = [(name, self._hist[label])
                          for label, name in labels.items()]
        self._lock = threading.Lock()
        self._records = collections.deque(maxlen=CYCLE_RING_SIZE)
        self._medians = {}
        self._pushed = 0
        self.slow_cycles = 0
        # the last processor-time reading: (thread, thread's seconds,
        # process's seconds, cycles pushed by then, when)
        self._cpu = None
        if not _gc_rings[0]:
            gc.callbacks.append(_on_gc)
        _gc_rings[0] += 1
        weakref.finalize(self, _gc_unwatch)

    def _push(self, rec):
        phases = rec.phases
        with self._lock:
            for name, hist in self._observed:
                seconds = phases.get(name)
                if seconds is not None:
                    hist.observe(seconds * 1e3)
            if rec.gc_runs:
                self._hist["gc"].observe(rec.gc * 1e3)
            self._hist["wall"].observe(rec.wall * 1e3)
            median = self._medians.get(rec.key)
            self._records.append(rec)
            self._pushed += 1
            if not self._pushed % REFRESH_EVERY:
                walls = collections.defaultdict(list)
                for r in self._records:
                    walls[r.key].append(r.wall)
                self._medians = {
                    key: statistics.median(v) for key, v in walls.items()
                    if len(v) >= SLOW_CYCLE_MIN}
            slow = median is not None \
                and rec.wall > SLOW_CYCLE_FACTOR * median
            closed = rec.t0 + rec.wall
            if slow or self._cpu is None \
                    or closed - self._cpu[4] >= CPU_READ_S:
                self._read_cpu(rec, closed)
            if not slow:
                return
            self.slow_cycles += 1
        if metrics_on():
            from . import flight  # deferred, as in Trace.finish

            flight.RECORDER.record(
                {"kind": "slow_cycle", "server": self.owner,
                 "median_ms": _ms(median), **rec.as_dict()},
                incident=True)

    def _read_cpu(self, rec, closed):
        """Give `rec` the processor time of its thread and of the
        process since the reading before, and the cycles that covers
        (`cpu_cycles`); nothing where the reading before was another
        thread's (a restarted scheduler) or there was none."""
        now = (threading.get_ident(), time.thread_time(),
               time.process_time(), self._pushed, closed)
        last, self._cpu = self._cpu, now
        if last is not None and last[0] == now[0]:
            rec.thread_cpu = now[1] - last[1]
            rec.process_cpu = now[2] - last[2]
            rec.cpu_cycles = now[3] - last[3]

    def records(self) -> List[dict]:
        """The ring's records, oldest first."""
        with self._lock:
            kept = list(self._records)
        return [rec.as_dict() for rec in kept]

    def summary(self) -> dict:
        """{label: {"p50", "p95", "max"}} in milliseconds; None where
        the window saw no such phase."""
        def r(v):
            return None if v is None else round(v, 3)
        return {label: {"p50": r(h.percentile(0.50)),
                        "p95": r(h.percentile(0.95)), "max": r(h.max)}
                for label, h in self._hist.items()}

    def metric_samples(self, name: str, labels: dict):
        return [(name, {**labels, "phase": label}, h)
                for label, h in self._hist.items()]

    def clear(self):
        """A new window (`stats(reset=True)`)."""
        with self._lock:
            self._records.clear()
            self._medians = {}
            self._pushed = 0
            self._cpu = None
            self.slow_cycles = 0
            for h in self._hist.values():
                h.reset()


def current_cycle() -> Optional["cycle"]:
    """The record open on this thread, for its owner's counts."""
    return _tls.cycle


class cycle:
    """Context manager that is one cycle's record: while it is open on
    the thread every `span` that closes there adds its length to
    `phases` ({span name: seconds}; nested spans each under their own
    name). The owner sets `key` (a cycle is compared with the ring's
    cycles of the same key) and its counts in `attrs` before the block
    ends; `drop()` keeps a cycle that did nothing out of the ring. On
    exit the record takes its wall time and the collector's runs and
    goes to `ring`, which gives a record that closes CPU_READ_S after
    its last reading, and every slow one, the thread's and the
    process's processor time since then (over `cpu_cycles` cycles). While a profile runs the
    cycle also ends in a `paddle_tpu:<name>` marker whose metadata is
    the record: it starts where the cycle ends, covers the ring's work
    and says in `wall_us` where the cycle began; a span around the
    whole cycle would make every idle moment an attributed one."""

    __slots__ = ("name", "ring", "attrs", "key", "phases", "t0", "wall",
                 "thread_cpu", "process_cpu", "cpu_cycles", "gc",
                 "gc_runs", "_prev", "_dropped")

    def __init__(self, name: str, ring: CycleRing, **attrs):
        self.name = name
        self.ring = ring
        self.attrs = attrs
        self.key = None
        self.phases: Dict[str, float] = {}
        self.cpu_cycles = 0
        self._dropped = False

    def drop(self):
        self._dropped = True

    def __enter__(self):
        self._prev = _tls.cycle
        _tls.cycle = self
        self.gc, self.gc_runs = _gc_seen[0], _gc_seen[1]
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.wall = time.monotonic() - self.t0
        self.gc = _gc_seen[0] - self.gc
        self.gc_runs = _gc_seen[1] - self.gc_runs
        _tls.cycle = self._prev
        if self._dropped:
            return False
        if not _profiling():
            self.ring._push(self)
            return False
        # the marker's start is the cycle's end: entered before the
        # ring's work, its metadata set once the ring has given the
        # record its processor time
        with TraceAnnotation(SPAN_PREFIX + self.name) as mark:
            self.ring._push(self)
            meta = {k: v if isinstance(v, (int, float)) else str(v)
                    for k, v in self.attrs.items()}
            if self.cpu_cycles:
                meta.update(
                    cpu_cycles=self.cpu_cycles,
                    thread_cpu_us=round(self.thread_cpu * 1e6),
                    process_cpu_us=round(self.process_cpu * 1e6))
            mark.set_metadata(
                wall_us=round(self.wall * 1e6), key=str(self.key),
                gc_us=round(self.gc * 1e6), **meta)
        return False

    def as_dict(self) -> dict:
        """JSON-able: the ring's and the flight recorder's entry."""
        cpu = {"cpu_cycles": self.cpu_cycles,
               "thread_cpu_ms": _ms(self.thread_cpu),
               "process_cpu_ms": _ms(self.process_cpu)} \
            if self.cpu_cycles else {}
        return {"name": self.name, "key": self.key,
                "wall_ms": _ms(self.wall),
                "phases": {n: _ms(v) for n, v in self.phases.items()},
                **self.attrs, **cpu,
                "gc_ms": _ms(self.gc), "gc_runs": self.gc_runs}


# --- chrome trace dump -------------------------------------------------
def _assign_parents(spans: List[Span]) -> List[int]:
    """parent index per span (-1 = root): smallest strictly-enclosing
    interval. O(n^2) over a request's handful of spans."""
    parents = []
    for i, s in enumerate(spans):
        best, best_len = -1, None
        for j, o in enumerate(spans):
            if j == i:
                continue
            if o.t0 <= s.t0 and s.t1 <= o.t1 \
                    and (o.t1 - o.t0) > (s.t1 - s.t0):
                if best_len is None or (o.t1 - o.t0) < best_len:
                    best, best_len = j, o.t1 - o.t0
        parents.append(best)
    return parents


def dump_trace(path: str) -> dict:
    """Write ONE chrome://tracing / Perfetto-loadable JSON merging

    * host RecordEvent spans (profiler.py, pid 0),
    * per-request span trees (pid 1, one tid per request), and
    * global compile/cache events (pid 2),

    and return the trace dict (tests read it without re-parsing).
    ``path`` gets ``.json`` appended unless already present. Reference
    counterpart: tools/timeline.py:273 _build_trace — extended with
    the request axis the reference never had."""
    events = []

    def meta(pid, name):
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"name": name}})

    meta(0, "host (RecordEvent)")
    meta(1, "requests")
    meta(2, "compile/cache")

    from .. import profiler

    for name, t0_ns, t1_ns, tid in profiler._snapshot_events():
        events.append({
            "name": name, "ph": "X", "pid": 0, "tid": tid,
            "ts": t0_ns / 1e3, "dur": (t1_ns - t0_ns) / 1e3,
            "cat": "host"})

    with TRACER._lock:
        traces = list(TRACER.completed)
        gevents = list(TRACER.global_events)

    for tr in traces:
        spans = sorted(tr.spans, key=lambda s: (s.t0, -(s.t1 - s.t0)))
        parents = _assign_parents(spans)
        for i, s in enumerate(spans):
            args = {"request_id": tr.request_id,
                    "span": f"{tr.request_id}/{i}",
                    "parent": (f"{tr.request_id}/{parents[i]}"
                               if parents[i] >= 0 else None)}
            args.update(tr.attrs)
            args.update(s.attrs)
            events.append({
                "name": s.name, "ph": "X", "pid": 1, "tid": tr.seq,
                "ts": s.t0 * 1e6, "dur": (s.t1 - s.t0) * 1e6,
                "cat": "request", "args": args})

    for s in gevents:
        events.append({
            "name": s.name, "ph": "X", "pid": 2, "tid": 0,
            "ts": s.t0 * 1e6, "dur": (s.t1 - s.t0) * 1e6,
            "cat": "compile", "args": dict(s.attrs)})

    trace = {"traceEvents": events}
    if not path.endswith(".json"):
        path = path + ".json"
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(trace, f)
    return trace


def reset():
    """Clear the trace sinks (tests; window starts)."""
    TRACER.reset()
