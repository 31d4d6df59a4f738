"""Unified observability layer: tracing + metrics + flight recorder.

SURVEY §1 puts the reference's profiler (platform/profiler.h:81,
tools/timeline.py) on the platform layer, peer to devices and memory;
this package is the TPU-native reproduction of that layer, grown to
serving scale. Before it, telemetry was fragmented — profiler.py host
spans, Executor compile/hit counters, ExecutableCache.stats(), disk
compile-cache counters, two servers' stats windows, and
RuntimeStats.stats_json() each invented a surface, and none could
answer "where did THIS slow request spend its 300 ms".

Three sub-modules, one gate:

* ``metrics`` — central registry of counters/gauges/fixed-bucket
  histograms; the scattered counters re-register as pull providers;
  ``metrics.expose()`` is the Prometheus text exposition and the
  existing ``stats_json()`` shapes are kept byte-compatible on top of
  the same instruments.
* ``tracing`` — ``Trace``/``Span`` per request, propagated
  Router.submit -> tenant queue -> batcher -> Executor dispatch ->
  execute -> readback; every ``span`` also lands in a running JAX
  profile as ``paddle_tpu:<name>``, on the device trace's clock, at
  any flag level; compile events annotated with
  ``Program.fingerprint()``, cache tier, ``memory_analysis()`` sizes;
  ``dump_trace(path)`` merges host RecordEvent spans (profiler.py,
  absorbed) and request trees into ONE chrome-trace JSON.
* ``flight`` — bounded ring of completed request timelines; SLO
  violations and errors are retained with their full span tree;
  ``incident_report()`` dumps them.
* ``devtel`` — device-resident decode telemetry: the declarative
  registry of [1] int64 RMW counters the decode engine compiles into
  every serve/step/burst program (burst exit reason, ticks, occupancy
  integral, admission tiers), deltaed per dispatch into the stats and
  metric surfaces — the INTERIOR of the one ``execute`` span a fused
  admission+burst dispatch used to be.
* ``costmodel`` — static per-executable ``cost_analysis()`` /
  ``memory_analysis()`` snapshots keyed on ``Program.fingerprint()``
  plus a median achieved-rate calibration: the expected time of a
  tick, from which ``expected_service_ms()`` makes the estimate the
  router sheds by.

Gate: ``FLAGS_observability = off | metrics | trace`` (flags.py),
read per call so ``set_flags`` flips the level mid-process. The layer
is always compiled in; at ``metrics`` it must cost <3% rps on
``bench.py multitenant`` (measured — PERF.md "Observability
overhead").
"""
from __future__ import annotations

from . import metrics
from .flight import RECORDER, incident_report
from .metrics import metrics_on, trace_on
from .tracing import TRACER, dump_trace, start_request

__all__ = ["metrics", "tracing", "flight", "devtel", "costmodel",
           "dump_trace", "incident_report", "start_request",
           "metrics_on", "trace_on", "reset", "TRACER", "RECORDER"]

from . import costmodel, devtel, flight, tracing  # noqa: E402


def reset():
    """Clear trace sinks + flight recorder (tests / window starts).
    Metric instruments are NOT dropped — counters are cumulative by
    contract (delta them across snapshots)."""
    tracing.reset()
    RECORDER.reset()
