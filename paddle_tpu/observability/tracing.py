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

Everything here is always compiled in. ``FLAGS_observability``
decides the in-process sinks: at ``off``/``metrics`` no request trace
is opened, no span is kept in the process and ``dump_trace`` writes an
empty trace.
"""
from __future__ import annotations

import collections
import itertools
import json
import os
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from .metrics import metrics_on, trace_on

__all__ = ["Span", "Trace", "Tracer", "TRACER", "trace_on",
           "metrics_on", "start_request", "current_request_trace",
           "request_context", "ambient", "ambient_traces", "span",
           "record_global_event", "dump_trace", "reset", "SPAN_PREFIX"]

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
_tls = threading.local()


class request_context:
    """Parks ONE request trace in a thread-local for the duration of a
    downstream synchronous call (Router._try_forward wraps
    ``handle.submit`` in this so the server attaches to the router's
    trace instead of opening its own)."""

    def __init__(self, trace: Optional[Trace]):
        self._trace = trace

    def __enter__(self):
        self._prev = getattr(_tls, "request_trace", None)
        _tls.request_trace = self._trace
        return self._trace

    def __exit__(self, *exc):
        _tls.request_trace = self._prev
        return False


def current_request_trace() -> Optional[Trace]:
    return getattr(_tls, "request_trace", None)


class ambient:
    """Parks a BATCH's traces in a thread-local so spans recorded
    below a fixed-signature boundary (runner.run_batch) attach to
    every co-batched request."""

    def __init__(self, traces):
        self._traces = [t for t in (traces or []) if t is not None]

    def __enter__(self):
        self._prev = getattr(_tls, "batch_traces", None)
        _tls.batch_traces = self._traces
        return self._traces

    def __exit__(self, *exc):
        _tls.batch_traces = self._prev
        return False


def ambient_traces() -> List[Trace]:
    return getattr(_tls, "batch_traces", None) or []


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
    thread-local lookup and one call into the profiler's gate.

    Attributes that cost anything to compute are set late, and only
    for a sink: ``if sp.recording: sp.attrs[...] = ...`` inside the
    block."""

    __slots__ = ("name", "attrs", "_traces", "_t0", "_ann")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs

    @property
    def recording(self) -> bool:
        """True inside the block when some sink takes the span."""
        return bool(self._traces) or self._ann is not None

    def __enter__(self):
        traces = self._traces = getattr(_tls, "batch_traces", None)
        if traces:
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
