"""Profiler (reference python/paddle/fluid/profiler.py +
platform/profiler.cc RecordEvent + tools/timeline.py).

Host-side RecordEvent scopes + jax.profiler device traces. The chrome://
tracing dump capability is preserved: jax.profiler writes Perfetto/XPlane
under the hood and we also emit a chrome-trace JSON of host events,
mirroring tools/timeline.py:131.

This module is ABSORBED by the unified observability layer
(paddle_tpu/observability): ``observability.dump_trace(path)`` merges
these host spans with per-request span trees and compile events into
ONE chrome trace. RecordEvent therefore captures when EITHER the
profiler window is open (start/stop_profiler) or
``FLAGS_observability=trace`` — the legacy API keeps working and the
new layer sees the same events.

Capture rule (the r12 consistency fix): a span is recorded iff capture
was enabled when the span STARTED. The pre-r12 rule sampled the flag
at span END, which (a) HALF-recorded events straddling
``start_profiler`` — their t0 predated the window, skewing totals —
and (b) silently DROPPED events that began inside the window but ended
after ``stop_profiler``. Entry-sampling makes the window edge
deterministic: pre-window starts are excluded whole, in-window starts
are kept whole (they land in ``_events`` when they close, visible to
the next dump). State flips and event appends share one lock.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import defaultdict

__all__ = ["profiler", "start_profiler", "stop_profiler", "reset_profiler",
           "cuda_profiler", "RecordEvent", "record_event"]

import collections

from jax.profiler import TraceAnnotation

# Bounded like every other observability sink (TRACER rings,
# FlightRecorder): with FLAGS_observability=trace capture runs outside
# any start/stop_profiler window, so an unbounded list would grow with
# traffic for the life of the process. Oldest spans age out of dumps.
_MAX_EVENTS = 65536
_events = collections.deque(maxlen=_MAX_EVENTS)
_enabled = False
_lock = threading.Lock()


_trace_on = None  # bound on first use (import cycle: observability
#                   imports this module's _snapshot_events)


def _capture_on() -> bool:
    """Capture gate sampled at span START (see module docstring)."""
    global _trace_on
    if _enabled:
        return True
    if _trace_on is None:
        from .observability import trace_on as _t

        _trace_on = _t
    return _trace_on()


class RecordEvent:
    """RAII host annotation (reference platform/profiler.h:81). While
    a JAX profile is being taken the event also lands in the
    profiler's trace under its own name, beside the program's
    `paddle_tpu:` spans (observability/tracing.py). Stamps
    `time.monotonic_ns`, the clock of the request spans."""

    def __init__(self, name):
        self.name = name
        self._t0 = None
        self._record = False
        self._ann = None

    def __enter__(self):
        self._record = _capture_on()
        if TraceAnnotation.is_enabled():
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
        self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, *a):
        if self._record:
            t1 = time.monotonic_ns()
            with _lock:
                _events.append((self.name, self._t0, t1,
                                threading.get_ident()))
        if self._ann is not None:
            self._ann.__exit__(*a)
            self._ann = None
        return False


@contextlib.contextmanager
def record_event(name):
    with RecordEvent(name):
        yield


def start_profiler(state="All", trace_dir=None):
    global _enabled
    with _lock:
        _enabled = True
    if trace_dir:
        import jax

        jax.profiler.start_trace(trace_dir)
        start_profiler._trace_dir = trace_dir
    else:
        start_profiler._trace_dir = None


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    global _enabled
    with _lock:
        _enabled = False
    if getattr(start_profiler, "_trace_dir", None):
        import jax

        jax.profiler.stop_trace()
    _dump_chrome_trace(profile_path)
    _print_summary(sorted_key)


def reset_profiler():
    with _lock:
        _events.clear()


def _snapshot_events():
    """Atomic copy of the recorded host spans — the observability
    layer's merge source (observability/tracing.py dump_trace)."""
    with _lock:
        return list(_events)


def _dump_chrome_trace(path):
    """chrome://tracing JSON (tools/timeline.py:273 parity)."""
    trace = {"traceEvents": []}
    for name, t0, t1, tid in _snapshot_events():
        trace["traceEvents"].append({
            "name": name, "ph": "X", "pid": 0, "tid": tid,
            "ts": t0 / 1000.0, "dur": (t1 - t0) / 1000.0,
            "cat": "host"})
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path + ".chrome_trace.json", "w") as f:
            json.dump(trace, f)
    except OSError:
        pass


def _print_summary(sorted_key):
    agg = defaultdict(lambda: [0, 0.0])
    for name, t0, t1, _ in _snapshot_events():
        agg[name][0] += 1
        agg[name][1] += (t1 - t0) / 1e6
    rows = sorted(agg.items(), key=lambda kv: -kv[1][1])
    if not rows:
        return
    print(f"{'Event':<40} {'Calls':>8} {'Total(ms)':>12} {'Avg(ms)':>10}")
    for name, (calls, total) in rows:
        print(f"{name:<40} {calls:>8} {total:>12.3f} "
              f"{total / calls:>10.3f}")


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path="/tmp/profile",
             tracer_option=None):
    start_profiler(state)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def cuda_profiler(output_file=None, output_mode=None, config=None):
    """Device-trace context; on TPU this wraps jax.profiler traces."""
    import jax

    trace_dir = (output_file or "/tmp/tpu_trace").rstrip(".nvprof")
    jax.profiler.start_trace(trace_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
