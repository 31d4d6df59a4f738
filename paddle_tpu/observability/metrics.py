"""Central metrics registry: counters, gauges, fixed-bucket histograms.

Reference counterpart: the reference's telemetry is per-subsystem
(platform/profiler.cc event totals, inference/api/analysis_predictor.cc:832
per-predictor profiling); there is no process-wide registry. Serving a
model zoo from ONE process (inference/runtime) needs the cross-cutting
surface the reference never built, so this module follows the
OpenMetrics/Prometheus shape instead: named metric families with
labels, exported as a text exposition (``expose()``), while the
existing ``stats_json()`` dict surfaces stay byte-compatible on top of
the same instruments.

Three design rules keep the hot path honest on this 2-core host
(PERF.md "Multi-tenant serving"):

* **Histograms are fixed-bucket** (geometric ladder, ~1.19x per step,
  O(1) memory). They replace the servers' per-request latency deques:
  a million-request run holds ~120 ints per series instead of raw
  samples, and ``percentile()`` answers from bucket counts with error
  bounded by one bucket width (pinned in tests/test_observability.py).
* **Exposition is pull-based.** Long-lived objects (executors, caches,
  servers, the router) register as *providers* via weakref; their
  existing counters stay the single source of truth and are only read
  at ``expose()`` time — per-request cost of the metrics level is a
  handful of histogram observes that the stats surfaces needed anyway.
* **Always compiled in, gated by ``FLAGS_observability``**: ``off``
  empties the exposition; ``metrics`` enables it; ``trace`` adds span
  capture (tracing.py). Gates are read per call so ``set_flags`` works
  mid-process (the bench's interleaved A/B legs rely on that).
"""
from __future__ import annotations

import bisect
import math
import threading
import weakref
from typing import Dict, Iterable, List, Optional, Tuple

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "REGISTRY", "expose", "counter", "gauge", "histogram",
           "register_provider", "default_ms_buckets", "metrics_on",
           "trace_on"]


from ..flags import FLAGS as _FLAGS

# The gates below run per request on the serving hot path (several
# times each), so they read the raw flag store through ONE bound
# global: a per-call ``from ..flags import FLAGS`` costs ~3 us on
# this host (import machinery + __getattr__) — measured to eat >2%
# of multitenant rps by itself — while the dict read keeps the
# read-per-call semantics (set_flags and direct _values pokes both
# take effect immediately) at ~100 ns.
_OBS_VALUES = _FLAGS._values


def metrics_on() -> bool:
    """True at FLAGS_observability in {metrics, trace}."""
    return _OBS_VALUES["observability"] != "off"


def trace_on() -> bool:
    """True at FLAGS_observability=trace."""
    return _OBS_VALUES["observability"] == "trace"


def default_ms_buckets() -> Tuple[float, ...]:
    """Geometric latency ladder in milliseconds: 1e-3 ms .. ~10 min,
    ratio 2**0.25 (~19% per step, ~118 buckets). Fine enough that a
    bucketed p99 stays within one step of the exact sample p99 (the
    tests pin this), coarse enough to stay O(100) ints per series."""
    ratio = 2.0 ** 0.25
    edges = []
    v = 1e-3
    while v < 6e5:
        edges.append(v)
        v *= ratio
    return tuple(edges)


_DEFAULT_MS_BUCKETS = default_ms_buckets()


class Counter:
    """Monotonic counter. ``inc`` is lock-protected (providers read it
    from the expose thread while request threads bump it). No direct
    reference counterpart — the reference's closest metric surface is
    the profiler's per-event summary tables (platform/profiler.cc);
    Prometheus-style primitives are this runtime's serving-scale
    addition."""

    __slots__ = ("name", "help", "labels", "_value", "_lock")

    def __init__(self, name: str, help: str = "",
                 labels: Optional[Dict[str, str]] = None):
        self.name = name
        self.help = help
        self.labels = dict(labels or {})
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, n: float = 1.0):
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        return self._value


class Gauge:
    """Point-in-time value (set wins, no aggregation). Reference
    counterpart: none direct — see Counter."""

    __slots__ = ("name", "help", "labels", "_value", "_lock")

    def __init__(self, name: str, help: str = "",
                 labels: Optional[Dict[str, str]] = None):
        self.name = name
        self.help = help
        self.labels = dict(labels or {})
        self._value = 0.0
        self._lock = threading.Lock()

    def set(self, v: float):
        with self._lock:
            self._value = float(v)

    @property
    def value(self) -> float:
        return self._value


class Histogram:
    """Fixed-bucket histogram with percentile estimation.

    Buckets are upper edges (ascending); one implicit overflow bucket
    catches everything past the last edge. ``observe`` is one bisect +
    one increment under a lock — O(1) memory regardless of sample
    count, which is what lets the serving stats surfaces report
    p50/p99 for a million-request run without holding raw samples
    (the deques this replaces, inference/serving.py pre-r12).

    ``percentile(p)`` is nearest-rank over the bucket counts with
    linear interpolation inside the winning bucket: the estimate is
    guaranteed inside the bucket containing the exact nearest-rank
    sample, i.e. off by at most one bucket width
    (tests/test_observability.py pins this against the exact sorted-
    sample percentile). The overflow bucket reports the tracked max.

    Reference counterpart: none direct (see Counter); the bucket-edge
    shape follows the Prometheus client convention.
    """

    __slots__ = ("name", "help", "labels", "buckets", "_counts",
                 "_count", "_sum", "_max", "_lock")

    def __init__(self, name: str = "", help: str = "",
                 labels: Optional[Dict[str, str]] = None,
                 buckets: Optional[Iterable[float]] = None):
        self.name = name
        self.help = help
        self.labels = dict(labels or {})
        self.buckets = tuple(buckets) if buckets is not None \
            else _DEFAULT_MS_BUCKETS
        if list(self.buckets) != sorted(self.buckets):
            raise ValueError("histogram buckets must be ascending")
        self._counts = [0] * (len(self.buckets) + 1)
        self._count = 0
        self._sum = 0.0
        self._max = None
        self._lock = threading.Lock()

    def observe(self, v: float):
        idx = bisect.bisect_left(self.buckets, v)
        with self._lock:
            self._counts[idx] += 1
            self._count += 1
            self._sum += v
            if self._max is None or v > self._max:
                self._max = v

    def __len__(self):
        return self._count

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    @property
    def max(self) -> Optional[float]:
        """The largest value observed, exact; None when empty."""
        return self._max

    def reset(self):
        """Window reset (the servers' ``stats(reset=True)`` contract)."""
        with self._lock:
            for i in range(len(self._counts)):
                self._counts[i] = 0
            self._count = 0
            self._sum = 0.0
            self._max = None

    def clear(self):  # deque-API compatibility for the stats surfaces
        self.reset()

    def percentile(self, p: float) -> Optional[float]:
        """Nearest-rank percentile estimate, None when empty."""
        with self._lock:
            n = self._count
            if n == 0:
                return None
            rank = max(1, math.ceil(p * n))
            seen = 0
            for i, c in enumerate(self._counts):
                if c == 0:
                    continue
                seen += c
                if seen >= rank:
                    if i >= len(self.buckets):
                        return self._max  # overflow: exact max tracked
                    hi = self.buckets[i]
                    lo = self.buckets[i - 1] if i > 0 else 0.0
                    # linear interpolation by rank position within the
                    # bucket; stays inside [lo, hi] so the estimate is
                    # within one bucket width of the exact sample
                    frac = (rank - (seen - c)) / c
                    est = lo + (hi - lo) * frac
                    if self._max is not None and est > self._max:
                        est = self._max
                    return est
            return self._max

    def percentile_dict(self) -> dict:
        p50 = self.percentile(0.50)
        p99 = self.percentile(0.99)
        return {"p50": round(p50, 3) if p50 is not None else None,
                "p99": round(p99, 3) if p99 is not None else None}

    def cumulative_counts(self) -> List[Tuple[float, int]]:
        """[(upper_edge, cumulative_count)] including +inf — the
        Prometheus histogram exposition shape."""
        with self._lock:
            out, cum = [], 0
            for edge, c in zip(self.buckets, self._counts):
                cum += c
                out.append((edge, cum))
            cum += self._counts[-1]
            out.append((math.inf, cum))
            return out


def _escape_label_value(v) -> str:
    """Prometheus text-exposition label-value escaping (\\, \", and
    newline) — tenant/model names are arbitrary caller strings and
    one bad value must not make the whole scrape unparseable."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _fmt_labels(labels: Dict[str, str]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{_escape_label_value(v)}"'
                     for k, v in sorted(labels.items()))
    return "{" + inner + "}"


class MetricsRegistry:
    """Process-global registry: directly-owned instruments plus weakly
    registered *providers* (objects with ``_metrics_samples()``
    yielding ``(name, labels, value-or-Histogram)``). Providers keep
    their counters where they always lived (Executor.compile_count,
    ExecutableCache.stats(), the servers' windows) — the registry
    reads them only when ``expose()`` is called, so steady-state
    serving pays nothing for the exposition. Reference counterpart:
    none direct — the reference scatters counters across VLOG and the
    profiler summary (platform/profiler.cc); one pull-based registry
    is this runtime's addition."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: Dict[Tuple[str, Tuple], object] = {}
        self._providers: List[weakref.ref] = []

    # --- owned instruments -------------------------------------------
    def _get_or_make(self, cls, name, help, labels):
        key = (name, tuple(sorted((labels or {}).items())))
        with self._lock:
            m = self._metrics.get(key)
            if m is None:
                m = cls(name, help, labels)
                self._metrics[key] = m
            return m

    def counter(self, name: str, help: str = "",
                labels: Optional[Dict[str, str]] = None) -> Counter:
        return self._get_or_make(Counter, name, help, labels)

    def gauge(self, name: str, help: str = "",
              labels: Optional[Dict[str, str]] = None) -> Gauge:
        return self._get_or_make(Gauge, name, help, labels)

    def histogram(self, name: str, help: str = "",
                  labels: Optional[Dict[str, str]] = None,
                  buckets=None) -> Histogram:
        key = (name, tuple(sorted((labels or {}).items())))
        with self._lock:
            m = self._metrics.get(key)
            if m is None:
                m = Histogram(name, help, labels, buckets=buckets)
                self._metrics[key] = m
            return m

    # --- providers ----------------------------------------------------
    def register_provider(self, obj):
        """Weakly register ``obj`` (must expose _metrics_samples()).
        Dead refs are pruned HERE as well as at collect time: at the
        default FLAGS_observability=off nothing ever calls collect(),
        so an executor/server-churning process would otherwise grow
        the list by one weakref per dead object forever. Registration
        is per-object-construction (never per request), so the O(live)
        sweep is cheap where it runs."""
        with self._lock:
            self._providers = [r for r in self._providers
                               if r() is not None]
            self._providers.append(weakref.ref(obj))

    def _live_providers(self):
        with self._lock:
            live, refs = [], []
            for r in self._providers:
                o = r()
                if o is not None:
                    live.append(o)
                    refs.append(r)
            self._providers = refs
            return live

    # --- collection ---------------------------------------------------
    def collect(self) -> List[Tuple[str, Dict[str, str], object]]:
        """All samples: (name, labels, float-or-Histogram)."""
        out = []
        with self._lock:
            owned = list(self._metrics.values())
        for m in owned:
            out.append((m.name, m.labels,
                        m if isinstance(m, Histogram) else m.value))
        for p in self._live_providers():
            try:
                samples = list(p._metrics_samples())
            except Exception:
                continue  # a broken provider must never break expose
            for name, labels, value in samples:
                out.append((name, dict(labels or {}), value))
        return out

    def expose(self) -> str:
        """Prometheus/OpenMetrics text exposition. Histograms are
        rendered as summaries (quantile gauges + _count/_sum) to keep
        the payload proportional to series, not buckets. Empty (bar a
        comment) when FLAGS_observability=off."""
        if not metrics_on():
            return ("# observability disabled "
                    "(FLAGS_observability=off)\n")
        lines = []
        for name, labels, value in sorted(
                self.collect(), key=lambda s: (s[0], sorted(s[1].items()))):
            if isinstance(value, Histogram):
                for q in (0.5, 0.99):
                    est = value.percentile(q)
                    if est is None:
                        continue
                    ql = dict(labels)
                    ql["quantile"] = f"{q:g}"
                    lines.append(f"{name}{_fmt_labels(ql)} {est:g}")
                lines.append(
                    f"{name}_count{_fmt_labels(labels)} {value.count}")
                lines.append(
                    f"{name}_sum{_fmt_labels(labels)} {value.sum:g}")
            else:
                lines.append(f"{name}{_fmt_labels(labels)} {value:g}")
        return "\n".join(lines) + "\n"

    def reset(self):
        """Drop owned instruments + provider registrations (tests)."""
        with self._lock:
            self._metrics.clear()
            self._providers = []


REGISTRY = MetricsRegistry()

# module-level conveniences (the documented call surface:
# ``observability.metrics.expose()``)
counter = REGISTRY.counter
gauge = REGISTRY.gauge
histogram = REGISTRY.histogram
register_provider = REGISTRY.register_provider
expose = REGISTRY.expose
