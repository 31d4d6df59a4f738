"""Unified observability layer (paddle_tpu/observability) — r12.

Covers the tentpole and its satellites:

* **Histograms** — the fixed-bucket percentile estimator that replaced
  the servers'/router's raw-sample deques: bucketed p50/p99 must land
  within one bucket width of the EXACT sorted-sample percentile
  (serving._pct is kept as the oracle), memory must stay O(buckets)
  regardless of sample count, and the window-reset contract must hold.
* **Profiler window** — the r12 capture-rule fix: a RecordEvent is
  recorded iff capture was on when the span STARTED (pre-window starts
  excluded whole, in-window starts kept whole past stop_profiler), plus
  the previously-uncovered reset_profiler, plus capture under
  FLAGS_observability=trace with no profiler window open.
* **Trace propagation** — requests submitted through ServingRuntime at
  FLAGS_observability=trace produce a CONNECTED span tree per request
  id in the dumped chrome trace (router.queue -> server.queue ->
  server.dispatch -> execute -> readback under the request root), with
  compile events only during warmup (zero steady-state compile spans)
  carrying fingerprint/tier annotations — and ``off`` emits nothing.
* **Flight recorder** — SLO violations and errors retain full
  timelines; ``incident_report()`` dumps them; metrics level records
  coarse timelines with O(1) cost.
* **Schema stability** — golden key-sets for ``stats_json()`` and the
  metric families in ``expose()`` so dashboards don't silently break.
* **The device trace's clock** (PR 25) — one JAX profile on the CPU
  backend around a tiny paged server and a few ``Executor.run`` steps
  at ``FLAGS_observability=off``: every program span is in the
  ``.xplane.pb`` under the ``paddle_tpu:`` prefix, nested as the code
  nests; with no profiler and the flag off a span reaches no sink;
  the queue-wait counter exists at every level.
"""
import bisect
import gc
import json
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observability as obs
from paddle_tpu import profiler, unique_name
from paddle_tpu.core.scope import Scope
from paddle_tpu.flags import FLAGS
from paddle_tpu.inference.runtime import ServingRuntime, zoo
from paddle_tpu.inference.serving import _pct, _pct_dict
from paddle_tpu.observability.metrics import (Histogram, MetricsRegistry,
                                              default_ms_buckets)


@pytest.fixture(autouse=True)
def _obs_hermetic():
    """Restore FLAGS_observability and clear the trace/flight sinks
    around every test in this module (the registry's weakref providers
    self-prune, so it is left alone)."""
    saved = FLAGS._values["observability"]
    profiler.reset_profiler()
    obs.reset()
    yield
    FLAGS._values["observability"] = saved
    profiler.reset_profiler()
    obs.reset()


def _set_level(level):
    FLAGS._values["observability"] = level


# --------------------------------------------------------------------
# fixed-bucket histograms (the satellite replacing raw-sample deques)
# --------------------------------------------------------------------
class TestHistogram:
    def test_p99_within_one_bucket_of_exact(self):
        """The pinned accuracy contract: the bucketed estimate must
        land inside the bucket that contains the exact nearest-rank
        sample, for a spread of realistic latency distributions."""
        rng = np.random.RandomState(7)
        edges = default_ms_buckets()
        for dist in (rng.lognormal(3.0, 1.0, 5000),     # ~20ms median
                     rng.exponential(120.0, 5000),       # heavy tail
                     rng.uniform(0.5, 400.0, 5000)):
            h = Histogram("t")
            for v in dist:
                h.observe(float(v))
            samples = sorted(float(v) for v in dist)
            for p in (0.50, 0.99):
                exact = _pct(samples, p)
                est = h.percentile(p)
                idx = bisect.bisect_left(edges, exact)
                lo = edges[idx - 1] if idx > 0 else 0.0
                hi = edges[idx] if idx < len(edges) else samples[-1]
                assert lo <= est <= hi, (
                    f"p{int(p * 100)}: estimate {est} outside the "
                    f"exact sample's bucket [{lo}, {hi}] "
                    f"(exact {exact})")

    def test_memory_is_o1_in_sample_count(self):
        """A million-request run must hold bucket counts, not raw
        samples: the storage footprint is fixed at construction."""
        h = Histogram("t")
        n_cells = len(h._counts)
        for v in np.random.RandomState(0).exponential(50.0, 20000):
            h.observe(float(v))
        assert len(h._counts) == n_cells          # no growth
        assert h.count == 20000
        assert not hasattr(h, "maxlen")           # not a deque

    def test_overflow_bucket_reports_tracked_max(self):
        h = Histogram("t", buckets=[1.0, 10.0])
        for v in (0.5, 5.0, 1e9):
            h.observe(v)
        assert h.percentile(0.99) == 1e9

    def test_reset_window(self):
        h = Histogram("t")
        h.observe(5.0)
        assert h.count == 1
        h.reset()
        assert h.count == 0 and h.percentile(0.5) is None
        h.observe(2.0)
        assert h.count == 1

    def test_pct_dict_handles_both_shapes(self):
        """_pct_dict serves the Histogram path (serving/router) and
        the legacy raw-sample path with one surface."""
        h = Histogram("t")
        for v in (1.0, 2.0, 3.0):
            h.observe(v)
        d = _pct_dict(h)
        assert set(d) == {"p50", "p99"} and d["p50"] is not None
        d2 = _pct_dict([1.0, 2.0, 3.0])
        assert set(d2) == {"p50", "p99"} and d2["p50"] == 2.0

    def test_empty_histogram(self):
        h = Histogram("t")
        assert h.percentile(0.5) is None
        assert _pct_dict(h) == {"p50": None, "p99": None}


# --------------------------------------------------------------------
# profiler window consistency (the r12 capture-rule fix)
# --------------------------------------------------------------------
class TestProfilerWindow:
    def test_pre_window_start_excluded_whole(self, tmp_path, capsys):
        """An event that STARTED before start_profiler must not be
        recorded at all, even though it ends inside the window (the
        old end-sampled rule half-recorded it with a pre-window t0)."""
        ev = profiler.RecordEvent("pre_window")
        ev.__enter__()
        profiler.start_profiler()
        ev.__exit__(None, None, None)
        profiler.stop_profiler(
            profile_path=str(tmp_path / "profile"))
        names = [e[0] for e in profiler._snapshot_events()]
        assert "pre_window" not in names

    def test_in_window_start_kept_past_stop(self, tmp_path, capsys):
        """An event that started inside the window is kept WHOLE even
        when it ends after stop_profiler (the old rule silently
        dropped it)."""
        profiler.start_profiler()
        ev = profiler.RecordEvent("straddles_stop")
        ev.__enter__()
        profiler.stop_profiler(
            profile_path=str(tmp_path / "profile"))
        ev.__exit__(None, None, None)
        names = [e[0] for e in profiler._snapshot_events()]
        assert "straddles_stop" in names

    def test_reset_profiler_clears_events(self, tmp_path, capsys):
        profiler.start_profiler()
        with profiler.record_event("to_reset"):
            pass
        profiler.stop_profiler(
            profile_path=str(tmp_path / "profile"))
        assert profiler._snapshot_events()
        profiler.reset_profiler()
        assert profiler._snapshot_events() == []

    def test_trace_flag_captures_without_profiler_window(self):
        """FLAGS_observability=trace opens capture for the absorbed
        RecordEvent API with no start_profiler call — the host spans
        land in the same _events the unified dump merges."""
        _set_level("trace")
        with profiler.record_event("obs_trace_host_span"):
            pass
        names = [e[0] for e in profiler._snapshot_events()]
        assert "obs_trace_host_span" in names

    def test_event_ring_is_bounded(self):
        """Under FLAGS_observability=trace capture runs outside any
        start/stop window, so the host-span sink must be a bounded
        ring (oldest age out), not an unbounded list that grows with
        traffic for the life of a serving process."""
        _set_level("trace")
        assert profiler._events.maxlen == profiler._MAX_EVENTS

    def test_off_records_nothing(self):
        _set_level("off")
        with profiler.record_event("dropped"):
            pass
        assert profiler._snapshot_events() == []


# --------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------
class TestMetricsRegistry:
    def test_off_exposition_is_empty(self):
        _set_level("off")
        text = obs.metrics.expose()
        assert text.startswith("# observability disabled")
        assert "paddle_tpu" not in text

    def test_instruments_dedupe_by_name_and_labels(self):
        reg = MetricsRegistry()
        c1 = reg.counter("c", labels={"a": "1"})
        c2 = reg.counter("c", labels={"a": "1"})
        c3 = reg.counter("c", labels={"a": "2"})
        assert c1 is c2 and c1 is not c3
        c1.inc(2)
        assert c2.value == 2.0 and c3.value == 0.0

    def test_provider_weakref_pruned(self):
        _set_level("metrics")
        reg = MetricsRegistry()

        class P:
            def _metrics_samples(self):
                return [("ephemeral_metric", {}, 1.0)]

        p = P()
        reg.register_provider(p)
        assert any(n == "ephemeral_metric"
                   for n, _, _ in reg.collect())
        del p
        assert not any(n == "ephemeral_metric"
                       for n, _, _ in reg.collect())

    def test_broken_provider_never_breaks_expose(self):
        _set_level("metrics")
        reg = MetricsRegistry()

        class Broken:
            def _metrics_samples(self):
                raise RuntimeError("boom")

        b = Broken()
        reg.register_provider(b)
        reg.counter("survives").inc()
        assert "survives 1" in reg.expose()

    def test_histogram_exposition_shape(self):
        _set_level("metrics")
        reg = MetricsRegistry()
        h = reg.histogram("lat_ms", labels={"server": "s1"})
        for v in (1.0, 5.0, 9.0):
            h.observe(v)
        text = reg.expose()
        assert 'lat_ms{quantile="0.5",server="s1"}' in text
        assert 'lat_ms_count{server="s1"} 3' in text
        assert 'lat_ms_sum{server="s1"} 15' in text

    def test_no_duplicate_series_across_instances(self):
        """Every provider labels its samples with a unique instance
        id: two co-resident registries/routers (same tenant names)
        must not emit duplicate (name, labels) series — duplicates
        make a scraper reject the WHOLE exposition."""
        _set_level("metrics")
        from paddle_tpu.inference.runtime.registry import ModelRegistry
        from paddle_tpu.inference.runtime.router import Router
        regs = [ModelRegistry() for _ in range(2)]
        routers = [Router(r, start=False) for r in regs]
        for r in routers:
            r.add_tenant("same-name", weight=1.0)
        try:
            samples = obs.metrics.REGISTRY.collect()
            keys = [(n, tuple(sorted(l.items())))
                    for n, l, _ in samples]
            dupes = sorted({k for k in keys if keys.count(k) > 1})
            assert not dupes, dupes
        finally:
            for r in routers:
                r.close()

    def test_label_values_are_escaped(self):
        """Tenant/model names are arbitrary caller strings; one
        quote/backslash/newline must not make the whole Prometheus
        scrape unparseable (label-value escaping is required by the
        text exposition format)."""
        _set_level("metrics")
        reg = MetricsRegistry()
        reg.counter("hits", labels={"tenant": 'team"a\\b\nc'}).inc()
        text = reg.expose()
        assert 'hits{tenant="team\\"a\\\\b\\nc"} 1' in text


# --------------------------------------------------------------------
# runtime-driven tracing / flight recorder / schema
# --------------------------------------------------------------------
def _small_runtime(max_batch_size=4):
    """One tiny fc model + one tenant ServingRuntime (module-local
    prefix so scopes never collide with the zoo tests)."""
    rt = ServingRuntime()
    server, scope = zoo.make_fc_server(
        "obsm", 16, 32, 8, executor=rt.executor(),
        max_batch_size=max_batch_size, max_wait_ms=1.0)
    rt.load_model("obsm", server)
    rt.add_tenant("acme", weight=1.0, max_queue=4096)
    return rt, scope


def _submit_n(rt, n, rows=1, rng=None):
    rng = rng or np.random.RandomState(0)
    reps = [rt.submit("acme", "obsm",
                      {"obsm_x": rng.randn(rows, 16).astype(np.float32)})
            for _ in range(n)]
    return [r.result(120.0) for r in reps]


_CHAIN = {"request", "router.queue", "server.queue",
          "server.dispatch", "execute", "readback"}


class TestTracePropagation:
    def test_span_tree_connected_per_request(self, tmp_path):
        """The acceptance criterion: every traced request's chrome
        events form ONE connected tree rooted at its `request` span,
        containing the router->queue->dispatch->execute->readback
        chain, with cache-tier annotations on the dispatch/execute
        spans; compile events appear during warmup ONLY, annotated
        with fingerprint + tier."""
        _set_level("trace")
        rt, _ = _small_runtime()
        try:
            # warmup happened inside load_model: compile events with
            # fingerprint/tier annotations must be in the sink
            with obs.TRACER._lock:
                compiles = [dict(s.attrs)
                            for s in obs.TRACER.global_events]
            assert compiles, "warmup produced no compile events"
            for a in compiles:
                assert a["tier"] in ("cold", "disk")
                assert len(a["fingerprint"]) == 16
            obs.reset()  # end of warmup: steady-state window begins

            _submit_n(rt, 12)
            trace = rt.dump_trace(str(tmp_path / "trace"))
        finally:
            rt.close()

        reqs = {}
        for e in trace["traceEvents"]:
            if e.get("cat") == "request":
                reqs.setdefault(e["args"]["request_id"], []).append(e)
            assert e.get("cat") != "compile", (
                f"steady-state compile span leaked: {e}")
        assert len(reqs) == 12
        for rid, events in reqs.items():
            names = {e["name"] for e in events}
            assert _CHAIN <= names, (
                f"{rid}: incomplete chain {sorted(names)}")
            # connectivity: exactly one root (the request span), and
            # every other span's parent is another span of the SAME
            # request
            ids = {e["args"]["span"] for e in events}
            roots = [e for e in events if e["args"]["parent"] is None]
            assert len(roots) == 1 and roots[0]["name"] == "request"
            for e in events:
                parent = e["args"]["parent"]
                assert parent is None or parent in ids
            # cache-tier annotations ride on the dispatch/execute spans
            by_name = {e["name"]: e for e in events}
            assert by_name["execute"]["args"]["cache"] == "memory"
            assert by_name["server.dispatch"]["args"]["cache"] \
                == "memory"
            assert by_name["request"]["args"]["tenant"] == "acme"

    def test_off_emits_nothing(self, tmp_path):
        _set_level("off")
        rt, _ = _small_runtime()
        try:
            _submit_n(rt, 4)
            trace = rt.dump_trace(str(tmp_path / "trace_off"))
        finally:
            rt.close()
        payload = [e for e in trace["traceEvents"]
                   if e.get("ph") != "M"]
        assert payload == []
        assert obs.RECORDER.recorded_total == 0
        assert obs.start_request() is None
        assert rt.metrics_expose().startswith(
            "# observability disabled")

    def test_host_spans_merge_into_one_dump(self, tmp_path):
        """profiler.py is absorbed: RecordEvent host spans land in the
        same chrome dump (pid 0) as request trees (pid 1)."""
        _set_level("trace")
        with profiler.record_event("host_side_work"):
            time.sleep(0.001)
        trace = obs.dump_trace(str(tmp_path / "merged"))
        host = [e for e in trace["traceEvents"]
                if e.get("cat") == "host"]
        assert any(e["name"] == "host_side_work" for e in host)
        assert all(e["pid"] == 0 for e in host)

    def test_standalone_server_owns_its_traces(self, tmp_path):
        """A server used WITHOUT the router still traces: it opens
        server-owned traces at submit and finishes them at demux."""
        _set_level("trace")
        exe = fluid.Executor(fluid.TPUPlace(0))
        server, _scope = zoo.make_fc_server(
            "obss", 16, 32, 8, executor=exe, max_batch_size=4,
            max_wait_ms=1.0)
        rng = np.random.RandomState(0)
        with server:
            reps = [server.submit(
                {"obss_x": rng.randn(1, 16).astype(np.float32)})
                for _ in range(3)]
            for r in reps:
                r.result(120.0)
        with obs.TRACER._lock:
            traces = list(obs.TRACER.completed)
        assert len(traces) == 3
        for tr in traces:
            assert tr.owner == "server"
            names = {s.name for s in tr.spans}
            assert {"request", "server.queue", "server.dispatch",
                    "execute", "readback"} <= names

    def test_cache_tier_cold_then_memory(self):
        """The dispatch/execute spans derive their cache annotation
        from executor counter deltas around the call (including the
        prepared-lookup compile on a miss): an UNWARMED server's
        first request must say cold, the repeat must say memory —
        'this slow request was compiling' must be readable off the
        incident timeline itself."""
        _set_level("trace")
        exe = fluid.Executor(fluid.TPUPlace(0))
        server, _scope = zoo.make_fc_server(
            "obst", 16, 32, 8, executor=exe, max_batch_size=4,
            max_wait_ms=1.0)
        rng = np.random.RandomState(0)
        feed = {"obst_x": rng.randn(1, 16).astype(np.float32)}
        with server:
            server.submit(dict(feed)).result(120.0)
            server.submit(dict(feed)).result(120.0)
        with obs.TRACER._lock:
            cold_t, warm_t = list(obs.TRACER.completed)[-2:]

        def tiers(tr):
            return {s.name: s.attrs.get("cache") for s in tr.spans
                    if s.name in ("execute", "server.dispatch")}

        assert set(tiers(cold_t).values()) == {"cold"}, tiers(cold_t)
        assert set(tiers(warm_t).values()) == {"memory"}, tiers(warm_t)

    def test_error_path_keeps_server_queue_span(self):
        """Dispatch failure: the server must record its spans BEFORE
        fulfilling the future — set_exception fires the router's
        done-callback synchronously, which seals router-owned traces,
        and a span added after that is dropped. Errored requests are
        exactly the incidents whose timelines must stay complete."""
        _set_level("trace")
        rt = ServingRuntime()
        server, _ = zoo.make_fc_server(
            "obse", 16, 32, 8, executor=rt.executor(),
            max_batch_size=4, max_wait_ms=1.0)
        rt.load_model("obse", server)
        rt.add_tenant("acme", weight=1.0, max_queue=64)

        def boom(feed):
            raise RuntimeError("injected dispatch failure")

        server._runner.run_batch = boom
        try:
            obs.reset()
            with pytest.raises(RuntimeError, match="injected"):
                rt.infer("acme", "obse",
                         {"obse_x": np.zeros((1, 16), np.float32)},
                         timeout=30.0)
        finally:
            rt.close()
        report = obs.incident_report()
        assert report["incidents"], "errored request not retained"
        inc = report["incidents"][-1]
        assert inc["status"] == "error"
        names = {s["name"] for s in inc["spans"]}
        assert "server.queue" in names, sorted(names)


class TestFlightRecorder:
    def test_slo_violation_retained_with_span_tree(self):
        """An SLO-violating request's FULL span tree survives in the
        incident ring and is dumpable via incident_report()."""
        _set_level("trace")
        rt = ServingRuntime()
        server, _ = zoo.make_fc_server(
            "obsm", 16, 32, 8, executor=rt.executor(),
            max_batch_size=4, max_wait_ms=1.0)
        rt.load_model("obsm", server)
        # any real request blows a 1 us target
        rt.add_tenant("acme", weight=1.0, max_queue=4096,
                      target_p99_ms=0.001)
        try:
            obs.reset()
            _submit_n(rt, 3)
            report = rt.incident_report()
        finally:
            rt.close()
        assert report["incidents_total"] == 3
        assert report["incidents"], "no incident retained"
        inc = report["incidents"][-1]
        assert inc["slo_violated"] is True
        assert inc["status"] == "ok"
        assert inc["tenant"] == "acme"
        names = {s["name"] for s in inc["spans"]}
        assert _CHAIN <= names
        json.dumps(report)  # must be JSON-able end to end

    def test_error_is_an_incident(self):
        _set_level("trace")
        rt, _ = _small_runtime()
        try:
            obs.reset()
            rep = rt.submit("acme", "obsm",
                            {"obsm_x": np.zeros((1, 7), np.float32)})
            with pytest.raises(Exception):
                rep.result(120.0)
            report = rt.incident_report()
        finally:
            rt.close()
        assert report["incidents_total"] >= 1
        inc = report["incidents"][-1]
        assert inc["status"] == "error" and "error" in inc

    def test_metrics_level_records_coarse_timelines(self):
        """At metrics level the recorder still names requests and
        keeps coarse timelines (no span capture)."""
        _set_level("metrics")
        rt, _ = _small_runtime()
        try:
            obs.reset()
            _submit_n(rt, 5)
        finally:
            rt.close()
        assert obs.RECORDER.recorded_total == 5
        entry = obs.RECORDER.recent[-1]
        assert entry["request_id"].startswith("req-")
        assert entry["latency_ms"] is not None
        assert "spans" not in entry
        assert len(obs.TRACER.completed) == 0  # no span capture

    def test_ring_bounds(self):
        _set_level("metrics")
        rec = obs.flight.FlightRecorder(max_recent=4, max_incidents=2)
        for i in range(10):
            rec.record({"request_id": f"r{i}"}, incident=(i % 2 == 0))
        assert len(rec.recent) == 4
        assert len(rec.incidents) == 2
        assert rec.recorded_total == 10 and rec.incidents_total == 5

    def test_private_rings_are_not_providers(self):
        """Only the global RECORDER exports paddle_tpu_flight_*
        series: a private ring (tests, bench microbench spins) must
        not emit a duplicate — ambiguous — series into expose()."""
        _set_level("metrics")
        scratch = obs.flight.FlightRecorder(max_recent=4)
        for i in range(7):
            scratch.record({"request_id": f"s{i}"})
        lines = [l for l in obs.metrics.expose().splitlines()
                 if l.startswith("paddle_tpu_flight_recorded_total")]
        assert len(lines) == 1, lines
        assert lines[0].endswith(f" {obs.RECORDER.recorded_total}")


class TestSchemaStability:
    """Golden key-sets: a dashboard scraping stats_json()/expose()
    must not silently break. Extend these sets deliberately when a
    surface grows; never shrink them casually."""

    STATS_TOP = {"uptime_s", "tenants", "models", "registry", "cache"}
    TENANT_KEYS = {"weight", "rate", "target_p99_ms", "queue_depth",
                   "admitted", "rejected", "completed", "failed",
                   "slo_violations", "queue_ms", "latency_ms",
                   "ttft_ms"}
    MODEL_KEYS = {"fingerprint", "kind", "max_inflight", "inflight",
                  "requests", "completed", "batches", "rows",
                  "padded_rows", "batch_occupancy", "queue_depth",
                  "uptime_s", "window_s", "compile_count",
                  "cache_hit_count", "disk_load_count",
                  "cache_evict_count", "warmed_compiles",
                  "latency_ms", "ttft_ms", "per_token_ms", "tokens",
                  "retired_per_s"}
    CACHE_KEYS = {"executable", "compile_count", "cache_hit_count",
                  "disk_load_count", "disk"}
    EXPOSE_FAMILIES = {
        "paddle_tpu_executor_compiles_total",
        "paddle_tpu_executor_cache_hits_total",
        "paddle_tpu_executor_disk_loads_total",
        "paddle_tpu_executor_cache_evictions_total",
        "paddle_tpu_executor_dispatches_total",
        "paddle_tpu_executor_placed_arrays_total",
        "paddle_tpu_executor_placements_total",
        "paddle_tpu_executor_fetched_arrays_total",
        "paddle_tpu_executable_cache_size",
        "paddle_tpu_executable_cache_capacity",
        "paddle_tpu_executable_cache_inserts_total",
        "paddle_tpu_executable_cache_evictions_total",
        "paddle_tpu_registry_models_loaded",
        "paddle_tpu_registry_swaps_total",
        "paddle_tpu_registry_retired_total",
        "paddle_tpu_server_requests_total",
        "paddle_tpu_server_completed_total",
        "paddle_tpu_server_batches_total",
        "paddle_tpu_server_queue_depth",
        "paddle_tpu_server_batch_occupancy",
        "paddle_tpu_server_tokens_total",
        "paddle_tpu_request_latency_ms",
        "paddle_tpu_request_ttft_ms",
        "paddle_tpu_per_token_ms",
        "paddle_tpu_tenant_admitted_total",
        "paddle_tpu_tenant_rejected_total",
        "paddle_tpu_tenant_completed_total",
        "paddle_tpu_tenant_failed_total",
        "paddle_tpu_tenant_slo_violations_total",
        "paddle_tpu_tenant_queue_depth",
        "paddle_tpu_tenant_latency_ms",
        "paddle_tpu_tenant_queue_ms",
        "paddle_tpu_tenant_ttft_ms",
        "paddle_tpu_flight_recorded_total",
        "paddle_tpu_flight_incidents_total",
    }

    @staticmethod
    def _family(line):
        """Metric family name from one exposition line, folding the
        histogram sub-series back onto their family."""
        name = line.split("{")[0].split(" ")[0]
        for suffix in ("_count", "_sum"):
            if name.endswith(suffix):
                name = name[: -len(suffix)]
        return name

    def test_stats_json_golden_keyset(self):
        _set_level("metrics")
        rt, _ = _small_runtime()
        try:
            _submit_n(rt, 4)
            stats = json.loads(rt.stats_json())
        finally:
            rt.close()
        assert set(stats) == self.STATS_TOP
        assert set(stats["tenants"]["acme"]) == self.TENANT_KEYS
        assert set(stats["models"]["obsm"]) == self.MODEL_KEYS
        assert set(stats["cache"]) == self.CACHE_KEYS
        for hist_key in ("latency_ms", "ttft_ms", "queue_ms"):
            assert set(stats["tenants"]["acme"][hist_key]) \
                == {"p50", "p99"}

    def test_expose_golden_families(self):
        _set_level("metrics")
        rt, _ = _small_runtime()
        try:
            _submit_n(rt, 4)
            text = rt.metrics_expose()
        finally:
            rt.close()
        families = {self._family(ln) for ln in text.splitlines()
                    if ln and not ln.startswith("#")}
        missing = self.EXPOSE_FAMILIES - families
        assert not missing, f"expose() lost families: {sorted(missing)}"


# --------------------------------------------------------------------
# a decoder-only server whose lanes carry state of their own (PR 34)
# --------------------------------------------------------------------
BLOCKPOOL_STATE_FAMILIES = {
    "paddle_tpu_blockpool_state_lanes",
    "paddle_tpu_blockpool_state_bytes",
    "paddle_tpu_blockpool_state_resets_total",
    "paddle_tpu_blockpool_prefix_reuse_skipped_total",
}
BLOCKPOOL_STATE_STATS = {"state_lanes", "state_bytes", "state_resets",
                         "prefix_reuse_skipped"}


def test_lane_state_golden_families_and_pool_stats_keys():
    """The pull provider of a decoder-only server with per-lane state
    exposes the state's series beside the block pool's, and
    pool_stats() holds their counters."""
    from benchmark.chip import controls
    from benchmark.chip.drivers import nemotron_serve

    _set_level("metrics")
    c = controls._sizes("nemotron-3-super-serve-ep4", True)
    srv, _exe, _scope = nemotron_serve.build_server(c, 5)
    try:
        text = obs.metrics.expose()
        stats = srv.pool_stats()
    finally:
        srv.close()
    families = {TestSchemaStability._family(ln) for ln in text.splitlines()
                if ln and not ln.startswith("#")}
    missing = BLOCKPOOL_STATE_FAMILIES - families
    assert not missing, f"expose() lost families: {sorted(missing)}"
    assert "paddle_tpu_blockpool_blocks_in_use" in families
    assert BLOCKPOOL_STATE_STATS <= set(stats)
    assert stats["state_lanes"] == c["n_slots"]


# --------------------------------------------------------------------
# the program's spans in a JAX profile (PR 25)
# --------------------------------------------------------------------
EXE_SPANS = {"exe.feed", "exe.lookup", "exe.compile", "exe.state",
             "exe.call", "exe.store", "exe.fetch"}
SLOTPOOL_SPANS = {"slotpool.wait", "slotpool.plan", "slotpool.admit",
                  "slotpool.feed", "slotpool.dispatch",
                  "slotpool.retire", "slotpool.deliver",
                  "slotpool.submit"}
# PR 36: the cycle's marker, the collector, and the sub-spans of the
# two largest host pieces
CYCLE_SPANS = {"slotpool.cycle", "gc", "slotpool.retire.tree",
               "exe.state.gather", "exe.state.put"}
CONTINUOUS_STATS_KEYS = {
    "requests", "completed", "queue_depth", "slots", "slot_occupancy",
    "ticks", "steps_per_tick", "uptime_s", "window_s", "compile_count",
    "cache_hit_count", "disk_load_count", "cache_evict_count",
    "warmed_compiles", "latency_ms", "ttft_ms", "queue_wait_ms",
    "per_token_ms", "cycle_ms", "slow_cycles", "tokens",
    "retired_per_s", "cancelled",
    "deadline_expired", "self_attention_routes",
    "cross_attention_routes", "device_telemetry"}


@pytest.fixture(scope="module")
def tiny():
    """(dense bundle, paged bundle, executor, scope, prompts): the
    2017 transformer at toy widths with initialized, untrained
    weights and an end token the argmax cannot emit, so every request
    decodes to the end of its buffer."""
    from paddle_tpu.models import transformer as T
    from paddle_tpu.models.decode_engine import CacheConfig

    v, s_len = 16, 8
    scope = Scope()
    model = dict(seq_len=s_len, d_model=32, n_heads=2, n_layers=1,
                 d_inner=64, vocab=v)
    with unique_name.guard():
        _, startup, _ = T.build_program(
            with_optimizer=False, dropout_rate=0.0, **model)
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    kw = dict(n_slots=2, admit_buckets=[1, 2], max_out_len=8,
              start_id=2, end_id=v + 7, **model)
    with unique_name.guard():
        dense = T.build_decode_step_program(
            state_prefix="@obsd/", **kw)
    with unique_name.guard():
        paged = T.build_decode_step_program(
            state_prefix="@obsp/", cache=CacheConfig(
                layout="paged", block_size=4, n_blocks=8,
                n_prompt_entries=4), **kw)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(3, v, (1, s_len)).astype(np.int64)
               for _ in range(3)]
    return dense, paged, exe, scope, prompts


@pytest.fixture(scope="module")
def profiled(tiny):
    """{"spans": [[name, thread, start_ns, duration_ns, metadata]],
    "user_events": names outside the prefix} of ONE JAX profile (CPU
    backend) taken at FLAGS_observability=off around: a fresh
    program's first Executor.run (a compile) and two more steps, a
    user's `profiler.record_event`, and a paged server that starts,
    serves three streamed requests and closes. The caller of the
    plain steps writes a parameter into their scope as a host array
    first, which the next step places (`exe.state.put`): a server's
    cycle places nothing, its tables ride the call as feeds."""
    import glob
    import tempfile

    import jax
    from jax.profiler import ProfileData

    from benchmark.chip import program_spans
    from paddle_tpu.inference.serving import \
        PagedContinuousGenerationServer

    saved = FLAGS._values["observability"]
    FLAGS._values["observability"] = "off"
    _dense, paged, exe, scope, prompts = tiny
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.fc(x, 8)
    step_scope = Scope()
    exe.run(startup, scope=step_scope)
    # bound (and its programs compiled) before the profile starts
    srv = PagedContinuousGenerationServer(
        paged, executor=exe, scope=scope, start=False,
        steps_per_tick=2, drain_steps=2)
    srv.start()
    srv.submit(prompts[0]).result(120.0)
    srv.close()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # the host's TraceMe events only
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=options)
        try:
            weight = main.all_parameters()[0].name
            step_scope._set(weight, np.asarray(step_scope._get(weight)))
            for _ in range(3):
                exe.run(main, feed={"x": np.ones((2, 4), "float32")},
                        fetch_list=[y], scope=step_scope)
            with profiler.record_event("user_scope"):
                pass
            gc.collect()        # a server's ring exists: marked
            srv.start()
            time.sleep(0.05)    # the scheduler finds nothing: it waits
            replies = [srv.submit(p, stream=True) for p in prompts]
            streamed = [[tok for _seq, tok in r] for r in replies]
            srv.close()
        finally:
            jax.profiler.stop_trace()
            FLAGS._values["observability"] = saved
        path, = glob.glob(d + "/plugins/profile/*/*.xplane.pb")
        loaded = program_spans.load(path)
        user = {ev.name for plane in ProfileData.from_file(path).planes
                for line in plane.lines for ev in line.events
                if ev.name == "user_scope"}
    assert all(len(toks) == 7 for toks in streamed), streamed
    return {"spans": loaded["spans"], "user_events": user}


def _inside(inner, outer):
    return inner[1] == outer[1] and outer[2] <= inner[2] \
        and inner[2] + inner[3] <= outer[2] + outer[3]


class TestProfilerClock:
    @pytest.mark.parametrize("name", sorted(EXE_SPANS | SLOTPOOL_SPANS
                                            | CYCLE_SPANS))
    def test_every_span_is_in_the_profile(self, profiled, name):
        assert any(ev[0] == name for ev in profiled["spans"]), sorted(
            {ev[0] for ev in profiled["spans"]})

    def test_record_event_lands_unprefixed(self, profiled):
        assert profiled["user_events"] == {"user_scope"}

    def test_executor_spans_nest_inside_the_dispatch(self, profiled):
        spans = profiled["spans"]
        dispatches = [ev for ev in spans if ev[0] == "slotpool.dispatch"]
        assert dispatches
        assert len({ev[1] for ev in dispatches}) == 1   # one thread
        for d in dispatches:
            inner = {ev[0] for ev in spans
                     if ev[0].startswith("exe.") and _inside(ev, d)}
            assert EXE_SPANS - {"exe.compile"} <= inner, (d, inner)
        # and in the order the code runs them
        first = dispatches[0]
        order = [ev[0] for ev in sorted(
            (ev for ev in spans if ev[0].startswith("exe.")
             and _inside(ev, first)), key=lambda ev: ev[2])]
        # no `exe.state.put`: the scheduler's tables are feeds of the
        # call, and what `init_slot_state` left in the scope as host
        # arrays went up with the request served before the profile
        assert order == ["exe.lookup", "exe.feed", "exe.state",
                         "exe.state.gather",
                         "exe.call", "exe.store", "exe.fetch"]
        puts = [ev for ev in spans if ev[0] == "exe.state.put"]
        assert puts and not any(_inside(ev, d) for ev in puts
                                for d in dispatches)

    def test_compile_is_inside_the_first_lookup_only(self, profiled):
        spans = profiled["spans"]
        compiles = [ev for ev in spans if ev[0] == "exe.compile"]
        assert len(compiles) == 1       # the fresh program's first run
        assert compiles[0][4]["tier"] == "cold"
        assert len(compiles[0][4]["fingerprint"]) == 16
        lookups = [ev for ev in spans if ev[0] == "exe.lookup"
                   and _inside(compiles[0], ev)]
        assert len(lookups) == 1

    def test_admissions_carry_their_wait_and_tier(self, profiled):
        spans = profiled["spans"]
        admits = [ev for ev in spans if ev[0] == "slotpool.admit"]
        assert len(admits) == 3
        plans = [ev for ev in spans if ev[0] == "slotpool.plan"]
        for a in admits:
            assert a[4]["wait_us"] >= 0
            assert a[4]["tier"] in ("miss", "hit", "radix")
            assert a[4]["slot"] in (0, 1)
            assert any(_inside(a, p) for p in plans)
        assert {"admits", "queue_depth", "tier"} <= set(plans[0][4])
        # two lanes, three requests: the third waited for a lane
        assert sorted(a[4]["wait_us"] for a in admits)[-1] > 0

    def test_a_cycle_is_marked_at_its_end_with_its_record(self, profiled):
        """One short `slotpool.cycle` event a dispatch, on the
        scheduler's thread, whose metadata is the record; `wall_us`
        before it the cycle began, so the interval holds the cycle's
        one dispatch whole. No span encloses a cycle: idle time under
        "any span" stays what the phases cover."""
        spans = profiled["spans"]
        dispatches = [ev for ev in spans if ev[0] == "slotpool.dispatch"]
        marks = sorted((ev for ev in spans if ev[0] == "slotpool.cycle"),
                       key=lambda ev: ev[2])
        assert len(marks) == len(dispatches) > 0
        assert {ev[1] for ev in marks} == {dispatches[0][1]}
        counts = {"wall_us", "gc_us",
                  "key", "admits", "queue_depth", "tier", "n_steps",
                  "retired", "delivered", "submitted", "placed_arrays",
                  "fetched_arrays"}
        for m in marks:
            meta = m[4]
            assert counts <= set(meta), sorted(meta)
            assert m[3] < meta["wall_us"] * 1e3      # a mark, no cover
            held = [d for d in dispatches
                    if m[2] - meta["wall_us"] * 1e3 <= d[2]
                    and d[2] + d[3] <= m[2]]
            assert len(held) == 1, (m, held)
            assert held[0][4]["admits"] == meta["admits"]
            # one array each way: the packed row down, nothing placed
            assert meta["fetched_arrays"] == 1
            assert meta["placed_arrays"] == 0
        # a marker with a processor-time reading carries all of it
        # (tests/test_cycle_record.py holds when one is taken)
        for meta in (m[4] for m in marks if "cpu_cycles" in m[4]):
            assert {"thread_cpu_us", "process_cpu_us"} <= set(meta)
        assert sum(m[4]["admits"] for m in marks) == 3
        assert sum(m[4]["retired"] for m in marks) == 3
        assert sum(m[4]["delivered"] for m in marks) > 0

    def test_the_sub_spans_nest_under_their_phase(self, profiled):
        spans = profiled["spans"]
        for inner, outer in (("exe.state.gather", "exe.state"),
                             ("exe.state.put", "exe.state"),
                             ("slotpool.retire.tree", "slotpool.retire")):
            outers = [ev for ev in spans if ev[0] == outer]
            for ev in (ev for ev in spans if ev[0] == inner):
                assert any(_inside(ev, o) for o in outers), (inner, ev)
        collections = [ev for ev in spans if ev[0] == "gc"]
        assert any(ev[4].get("generation") == 2 for ev in collections)

    def test_submit_runs_on_the_callers_thread(self, profiled):
        spans = profiled["spans"]
        sched = {ev[1] for ev in spans if ev[0] == "slotpool.dispatch"}
        submits = {ev[1] for ev in spans if ev[0] == "slotpool.submit"}
        assert submits and not submits & sched


class TestNoSinkNoRecord:
    def test_off_and_no_profiler_reaches_no_sink(self):
        """The flag off and no profile running: a span is entered and
        left and nothing anywhere keeps it."""
        _set_level("off")
        from paddle_tpu.observability import tracing

        with tracing.span("slotpool.plan", admits=1) as sp:
            assert not sp.recording
            with tracing.execute_span(fluid.Executor(
                    fluid.TPUPlace(0))) as ex:
                assert not ex.recording
        with profiler.record_event("user_scope"):
            pass
        assert "cache" not in ex.attrs   # nobody to compute it for
        assert not obs.TRACER.completed and not obs.TRACER.global_events
        assert not profiler._snapshot_events()
        assert obs.RECORDER.recorded_total == 0

    @pytest.mark.parametrize("level", ["off", "metrics", "trace"])
    @pytest.mark.parametrize("kind", ["dense", "paged"])
    def test_queue_wait_counter_at_every_level(self, tiny, level,
                                               kind):
        from paddle_tpu.inference.serving import (
            ContinuousGenerationServer, PagedContinuousGenerationServer)

        _set_level(level)
        dense, paged, exe, scope, prompts = tiny
        cls, bundle = {
            "dense": (ContinuousGenerationServer, dense),
            "paged": (PagedContinuousGenerationServer, paged)}[kind]
        with cls(bundle, executor=exe, scope=scope) as srv:
            for r in [srv.submit(p) for p in prompts]:
                r.result(120.0)
            st = srv.stats()
            assert set(st) - {"block_pool"} == CONTINUOUS_STATS_KEYS
            assert ("block_pool" in st) == (kind == "paged")
            assert set(st["queue_wait_ms"]) == {"p50", "p99"}
            assert st["queue_wait_ms"]["p50"] is not None
            assert srv._queue_wait.count == 3
            families = {name for name, _lab, _v
                        in srv._metrics_samples()}
            assert "paddle_tpu_request_queue_wait_ms" in families
            assert srv.stats(reset=True)["queue_wait_ms"]["p50"] \
                is not None
            assert srv.stats()["queue_wait_ms"]["p50"] is None
