"""Executable cost models: static flops/bytes per compiled program.

Reference counterpart: the reference profiler reports MEASURED per-op
times only (platform/profiler.cc summary tables); it has no static
cost side, so "is this op slow or is the host throttled" is
unanswerable there. This host is 2-core and CPU-share throttled —
identical dispatches swing ~3x wall time (PERF.md) — so a wall-clock
number alone cannot distinguish "the model got more expensive" from
"the throttle window moved". This module supplies the machine-readable
static side:

* **Snapshots** — one ``cost_analysis()`` (+ ``memory_analysis()``
  when the executable exposes it) per compiled executable, keyed on
  ``(Program.fingerprint(), feed specs, kind)``. Captured by the
  Executor's compile hook (core/executor.py ``_resolve_block`` /
  ``_resolve_scan``, the miss side of the one lookup every entry
  point dispatches through) — compiles are rare by design, so
  snapshot cost rides the compile budget, never a request. The
  analysis surface is feature-detected across jaxlib spellings:

  - an AOT ``Compiled`` (disk-cache paths) answers
    ``cost_analysis()``/``memory_analysis()`` directly;
  - a live ``jax.jit`` callable (the default serving path — AOT
    dispatch is ~25 us/call slower, PERF.md "Warm start") exposes
    neither, so the hook stashes an **aval probe** (shape structs
    only, never arrays) and the FIRST ``lookup()`` resolves it with
    ``fn.lower(*avals).cost_analysis()`` — one extra trace, no XLA
    compile (``Lowered.cost_analysis`` computes from the unoptimized
    HLO), cached forever after;
  - a backend without either records ``{}`` once and stays silent.

  XLA's HLO cost analysis counts a While body ONCE (trip counts are
  dynamic), so a decode-burst serve program's ``flops`` is its
  per-TICK cost plus the admission prologue — exactly the unit the
  per-tick service-time estimate needs.

* **Calibration** — ``observe(flops, seconds)`` feeds achieved-rate
  samples (the serving layer reports ``snapshot-flops x ticks`` per
  burst dispatch); ``flops_per_s()`` is the MEDIAN of a bounded
  window, which the 3x throttle swings cannot drag around the way a
  mean would. ``expected_ms(flops)`` divides by it: the expected
  time of one tick, which the servers' ``expected_service_ms()``
  multiplies by a request's ticks for the router's deadline
  shedding.

Everything here is per-call gated by the callers on
``FLAGS_observability`` (lookups at ``off`` return the cached dict or
None and never resolve a probe), so the off-mode request budget stays
at a dict read.
"""
from __future__ import annotations

import collections
import statistics
import threading
import weakref
from typing import Dict, List, Optional, Tuple

from .metrics import REGISTRY, metrics_on

__all__ = ["ExecutableCostModel", "MODEL", "note_executable",
           "lookup", "observe", "flops_per_s", "expected_ms",
           "snapshot_fields", "feed_specs_of"]

# cost_analysis keys kept in a snapshot (jax spells them with spaces)
_COST_FIELDS = (("flops", "flops"),
                ("bytes accessed", "bytes_accessed"),
                ("transcendentals", "transcendentals"))
# memory_analysis attrs kept when the executable exposes them
_MEM_FIELDS = ("temp_size_in_bytes", "argument_size_in_bytes",
               "output_size_in_bytes", "generated_code_size_in_bytes")


def snapshot_fields() -> Tuple[str, ...]:
    """The keys a resolved snapshot may carry (golden-keyset tests).
    Reference counterpart: none — the reference profiler's event
    fields are measured times only (profiler.proto)."""
    return tuple(dst for _, dst in _COST_FIELDS) + _MEM_FIELDS + (
        "kind", "fingerprint")


def feed_specs_of(program, feed) -> Optional[tuple]:
    """The (name, shape, dtype) spec tuple the Executor derives from
    this feed — the snapshot key's second component — replicating the
    `_coerce_feed` dtype rule (declared-dtype cast within the same
    float/int family) WITHOUT materializing anything: this runs per
    traced request, so shapes/dtypes are read off the arrays in
    place, never copied. None when anything defies spec-ing;
    best-effort by design."""
    import numpy as np

    try:
        from ..core.executor import _var_np_dtype

        block = program.global_block
        specs = []
        for name, val in feed.items():
            if isinstance(val, tuple) and len(val) == 2:
                val = val[0]   # (data, lod) legacy feed
            shape = getattr(val, "shape", None)
            dtype = getattr(val, "dtype", None)
            castable = isinstance(val, np.ndarray)
            if shape is None or dtype is None:
                arr = np.asarray(val)   # list feeds: rare, must copy
                shape, dtype = arr.shape, arr.dtype
                castable = True
            dtype = np.dtype(dtype)
            decl = _var_np_dtype(block, name)
            # _coerce_feed casts numpy (same float/int family) but
            # returns device-resident jax arrays untouched
            if castable and decl is not None and dtype != decl \
                    and np.issubdtype(dtype, np.floating) \
                    == np.issubdtype(decl, np.floating):
                dtype = np.dtype(decl)
            specs.append((name, tuple(shape), str(dtype)))
        return tuple(sorted(specs))
    except Exception:
        return None


def _normalize_cost(ca) -> Optional[dict]:
    """jax cost_analysis payload -> plain dict (it is a dict in this
    jaxlib; older spellings returned [dict] — accept both)."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    return ca


def _analyze(obj, kind: str, fingerprint: str) -> Optional[dict]:
    """Snapshot from anything answering cost_analysis (an AOT
    Compiled or a Lowered); None when the object has no analysis
    surface at all (plain jit callable)."""
    ca_fn = getattr(obj, "cost_analysis", None)
    if ca_fn is None:
        return None
    snap = {"kind": kind, "fingerprint": fingerprint[:16]}
    try:
        ca = _normalize_cost(ca_fn())
        if ca is not None:
            for src, dst in _COST_FIELDS:
                v = ca.get(src)
                if v is not None:
                    snap[dst] = float(v)
    except Exception:
        pass  # analysis is best-effort; an empty snapshot is honest
    ma_fn = getattr(obj, "memory_analysis", None)
    if ma_fn is not None:
        try:
            m = ma_fn()
            for field in _MEM_FIELDS:
                v = getattr(m, field, None)
                if v is not None:
                    snap[field] = int(v)
        except Exception:
            pass
    return snap


class ExecutableCostModel:
    """Process-global snapshot store + achieved-rate calibration
    (module docstring). Thread-safe: compile hooks and serving
    threads touch it concurrently. Reference counterpart: none — the
    reference has measured-only telemetry (platform/profiler.cc);
    static executable cost models are this runtime's addition."""

    def __init__(self, rate_window: int = 64):
        self._lock = threading.Lock()
        self._snapshots: Dict[tuple, dict] = {}
        self._latest: Dict[str, dict] = {}      # fingerprint -> snap
        self._probes: Dict[tuple, tuple] = {}   # key -> (fn, avals)
        self._rates = collections.deque(maxlen=rate_window)
        self.probe_resolutions = 0   # lazy lowerings actually run
        self.probe_failures = 0
        REGISTRY.register_provider(self)

    @staticmethod
    def _key(fingerprint: str, feed_specs, kind: str) -> tuple:
        return (fingerprint, tuple(sorted(feed_specs or ())), kind)

    # --- capture (the Executor compile hook) -------------------------
    def note_executable(self, program, fn, kind: str, feed_specs=(),
                        avals=None):
        """Record one resolved executable. Direct analysis when `fn`
        answers it (AOT paths); else stash the aval probe for a lazy
        first-lookup lowering; else (no probe) record {} so lookup
        never re-asks. Never raises — telemetry must not break a
        compile."""
        try:
            fp = program.fingerprint()
            key = self._key(fp, feed_specs, kind)
            with self._lock:
                if key in self._snapshots:
                    return
                probe = self._probes.get(key)
                if probe is not None and probe[0]() is not None:
                    return   # live pending probe for this key
            snap = _analyze(fn, kind, fp)
            with self._lock:
                if snap is not None:
                    self._snapshots[key] = snap
                    self._latest[fp] = snap
                elif avals is not None:
                    # WEAK ref only: at `off` no lookup ever resolves
                    # a probe, and a strong ref would pin the jit
                    # callable (and the XLA executable it closes
                    # over) for the process lifetime — exactly the
                    # GC-ability the executor's uid-guarded in-memory
                    # cache preserves
                    try:
                        ref = weakref.ref(fn)
                    except TypeError:   # non-weakrefable callable:
                        #   skip the probe rather than pin it
                        ref = None
                    if ref is not None:
                        self._probes[key] = (ref, avals)
                    else:
                        empty = {"kind": kind,
                                 "fingerprint": fp[:16]}
                        self._snapshots[key] = empty
                        self._latest.setdefault(fp, empty)
                else:
                    empty = {"kind": kind, "fingerprint": fp[:16]}
                    self._snapshots[key] = empty
                    self._latest.setdefault(fp, empty)
        except Exception:
            pass

    # --- query --------------------------------------------------------
    def lookup(self, program, feed_arrays=None) -> Optional[dict]:
        """Snapshot for the program's fingerprint, resolving a
        pending lazy probe on first call (ONE extra trace, no XLA
        compile; failures — including a probe whose weakly-held fn
        already died — cache an empty snapshot). With ``feed_arrays``
        (a feed dict) the spec-EXACT snapshot is preferred, so a
        program compiled at several feed shapes (bucketed servers)
        annotates each dispatch with its own specialization's cost
        rather than whichever compiled last; without it, the latest
        snapshot for the fingerprint. Callers gate on
        FLAGS_observability — at `off` a pending probe stays pending
        and None is returned."""
        try:
            fp = program.fingerprint()
        except Exception:
            return None
        specs = feed_specs_of(program, feed_arrays) \
            if feed_arrays else None
        with self._lock:
            if specs is not None:
                for kind in ("block", "scan"):
                    exact = self._snapshots.get((fp, specs, kind))
                    if exact is not None:
                        return exact
                pending = [(k, v) for k, v in self._probes.items()
                           if k[0] == fp and k[1] == specs]
            else:
                pending = []
            fallback = self._latest.get(fp)
            if not pending:
                if fallback is not None:
                    return fallback
                pending = [(k, v) for k, v in self._probes.items()
                           if k[0] == fp]
        if not pending:
            return None
        if not metrics_on():
            return fallback
        snap = fallback
        for key, (ref, avals) in pending:
            snap = self._resolve_probe(key, ref(), avals)
        return snap

    def _resolve_probe(self, key, fn, avals) -> dict:
        fp, _specs, kind = key
        lower = getattr(fn, "lower", None)   # fn is None when the
        #   weakly-held callable died before the first metrics-on
        #   lookup: nothing left to analyze, cache the empty snapshot
        snap = None
        if lower is not None:
            try:
                snap = _analyze(lower(*avals), kind, fp)
                self.probe_resolutions += 1
            except Exception:
                snap = None
        if snap is None:
            self.probe_failures += 1
            snap = {"kind": kind, "fingerprint": fp[:16]}
        with self._lock:
            self._probes.pop(key, None)
            self._snapshots[key] = snap
            self._latest[fp] = snap
        return snap

    # --- calibration --------------------------------------------------
    def observe(self, flops: float, seconds: float):
        """One achieved-rate sample (flops actually moved / wall
        seconds of the dispatch window that moved them)."""
        if flops and seconds and seconds > 0:
            with self._lock:
                self._rates.append(flops / seconds)

    def flops_per_s(self) -> Optional[float]:
        """Median achieved rate over the bounded sample window (the
        3x throttle swings shift a mean; they straddle a median)."""
        with self._lock:
            if not self._rates:
                return None
            return statistics.median(self._rates)

    def expected_ms(self, flops: Optional[float]) -> Optional[float]:
        """Calibrated expectation for moving `flops` once (for a
        serve program: one TICK — its While body is costed once)."""
        rate = self.flops_per_s()
        if not flops or not rate:
            return None
        return flops / rate * 1e3

    # --- observability of the observer -------------------------------
    def _metrics_samples(self):
        with self._lock:
            n_snap = len(self._snapshots)
            n_pending = len(self._probes)
            rate = (statistics.median(self._rates)
                    if self._rates else 0.0)
        return [
            ("paddle_tpu_costmodel_snapshots", {}, n_snap),
            ("paddle_tpu_costmodel_pending_probes", {}, n_pending),
            ("paddle_tpu_costmodel_probe_resolutions_total", {},
             self.probe_resolutions),
            ("paddle_tpu_costmodel_flops_per_s", {}, rate),
        ]

    def reset(self):
        """Tests: drop snapshots, probes and calibration."""
        with self._lock:
            self._snapshots.clear()
            self._latest.clear()
            self._probes.clear()
            self._rates.clear()
            self.probe_resolutions = 0
            self.probe_failures = 0


MODEL = ExecutableCostModel()

# module-level conveniences (the documented call surface, mirroring
# observability.metrics)
note_executable = MODEL.note_executable
lookup = MODEL.lookup
observe = MODEL.observe
flops_per_s = MODEL.flops_per_s
expected_ms = MODEL.expected_ms
