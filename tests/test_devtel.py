"""Device-side flight data (observability/devtel.py + costmodel.py +
the decode-engine/serving integration).

What must hold:

* **counter units** — ticks count device While iterations (not
  scheduler cycles), the occupancy integral sums live lanes per tick,
  admission counters count REAL lanes per tier, and the burst exit
  reason is one-hot per burst — all deterministic with no-EOS prompts
  (end_id outside the vocab: argmax can never emit it, so every lane
  runs to buffer exhaustion);
* **window semantics** — ``stats()['device_telemetry']`` re-bases on
  ``reset=True`` exactly like the r14 speculative counters;
* **golden keysets** — the ``paddle_tpu_devtel_*`` metric names and
  the stats keyset are a published contract;
* **zero steady-state compiles / executable bound with telemetry
  enabled** — the counters ride state_in/state_out of the SAME serve
  executables, so enabling observability must not change the
  compile story;
* **flight-recorder interior** — a forced slow burst (lone request
  outgrowing a tiny paged pool) retains an incident whose span tree
  carries exit reason, tick count and occupancy integral, and whose
  bursts calibrated the cost model (observability/costmodel.py);
* **cost model units** — snapshot capture, lazy probe gating on
  FLAGS_observability, and the median-rate calibration arithmetic.

Determinism: the scheduler tests drive the server SINGLE-THREADED
(start=False + manual cycles — the test_paged_decode discipline) so
burst boundaries and admission order are exact, not race-lucky.
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import unique_name
from paddle_tpu.core.scope import Scope
from paddle_tpu.flags import FLAGS, set_flags
from paddle_tpu.inference.serving import (
    ContinuousGenerationServer, PagedContinuousGenerationServer)
from paddle_tpu.models.decode_engine import (BlockPoolExhausted,
                                             CacheConfig)
from paddle_tpu.observability import costmodel as obs_costmodel
from paddle_tpu.observability import devtel
from paddle_tpu.observability import metrics as obs_metrics

V, D, L, S, MAXT = 16, 32, 1, 8, 16
NO_EOS = V + 7   # argmax over [0, V) can never emit it: every lane
#                  decodes to buffer exhaustion, deterministically

DENSE_STATS_KEYS = {"ticks", "occupancy_integral", "exit_n_steps",
                    "exit_all_idle", "exit_min_active",
                    "admitted_miss", "mean_live_lanes"}
PAGED_STATS_KEYS = DENSE_STATS_KEYS | {
    "admitted_hit", "admitted_radix", "cow_blocks", "blocks_hwm",
    "prompt_entries_hwm", "pause_events", "preemptions"}
DENSE_METRICS = {
    "paddle_tpu_devtel_ticks_total",
    "paddle_tpu_devtel_occupancy_integral_total",
    "paddle_tpu_devtel_exit_n_steps_total",
    "paddle_tpu_devtel_exit_all_idle_total",
    "paddle_tpu_devtel_exit_min_active_total",
    "paddle_tpu_devtel_admit_miss_total",
}
PAGED_METRICS = DENSE_METRICS | {
    "paddle_tpu_devtel_admit_hit_total",
    "paddle_tpu_devtel_admit_radix_total",
    "paddle_tpu_devtel_cow_blocks_total",
    "paddle_tpu_devtel_blocks_hwm",
    "paddle_tpu_devtel_prompt_entries_hwm",
    "paddle_tpu_devtel_pause_events_total",
    "paddle_tpu_devtel_preemptions_total",
}
# chunked-prefill bundles (CacheConfig(chunk_tokens=C)) carry two more
# counters; plain paged bundles keep EXACTLY the set above
CHUNKED_STATS_KEYS = PAGED_STATS_KEYS | {
    "prefill_chunks", "prefill_occupancy_integral"}
CHUNKED_METRICS = PAGED_METRICS | {
    "paddle_tpu_devtel_prefill_chunks_total",
    "paddle_tpu_devtel_prefill_occupancy_integral_total",
}


@pytest.fixture(scope="module")
def ctx():
    """Initialized (NOT trained) weights + a dense bundle: devtel
    counts structure, not token quality, and no-EOS prompts make
    every lane's lifetime exactly maxT-1 ticks regardless of what
    garbage the untrained argmax emits."""
    from paddle_tpu.models import transformer as T

    scope = Scope()
    with unique_name.guard():
        main, startup, _ = T.build_program(
            seq_len=S, d_model=D, n_heads=2, n_layers=L, d_inner=64,
            vocab=V, with_optimizer=False, dropout_rate=0.0)
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    kw = dict(seq_len=S, max_out_len=MAXT, d_model=D, n_heads=2,
              n_layers=L, d_inner=64, vocab=V, start_id=2,
              end_id=NO_EOS)
    with unique_name.guard():
        bundle = T.build_decode_step_program(n_slots=2,
                                             admit_buckets=[1], **kw)
    return {"exe": exe, "scope": scope, "bundle": bundle, "kw": kw}


@pytest.fixture
def obs(request):
    """Set an observability level for one test; restore + clear the
    process-global sinks afterwards so trace/flight/cost state never
    leaks across tests."""
    import paddle_tpu.observability as observability

    prev = FLAGS.observability

    def setter(level):
        set_flags({"FLAGS_observability": level})

    yield setter
    set_flags({"FLAGS_observability": prev})
    observability.reset()
    obs_costmodel.MODEL.reset()


def _prompts(n, rng=None):
    rng = rng or np.random.RandomState(0)
    return [rng.randint(3, V, (1, S)).astype(np.int64)
            for _ in range(n)]


def _drive(srv, max_cycles=200, until=None):
    """Single-threaded scheduler drive (the _loop body, minus the
    thread): deterministic burst boundaries."""
    for _ in range(max_cycles):
        if until is not None and until():
            return
        failures = []
        with srv._cv:
            if not srv._queue and all(l is None for l in srv._lanes):
                return
            admits = srv._plan_admissions_locked(failures)
            drain = not srv._queue
            n, m, run = srv._plan_burst_locked(admits, drain,
                                               failures)
        srv._fail_requests(failures)
        if run:
            srv._cycle(admits, n, m)
    raise AssertionError("scheduler did not converge")


def _dense(ctx, **kw):
    kw.setdefault("executor", ctx["exe"])
    kw.setdefault("scope", ctx["scope"])
    kw.setdefault("start", False)
    return ContinuousGenerationServer(ctx["bundle"], **kw)


def _paged_bundle(ctx, prefix, n_blocks=3, n_entries=2,
                  admit_buckets=(1, 2)):
    from paddle_tpu.models import transformer as T

    with unique_name.guard():
        return T.build_decode_step_program(
            n_slots=2, admit_buckets=list(admit_buckets),
            state_prefix=prefix,
            cache=CacheConfig(layout="paged", block_size=4,
                              n_blocks=n_blocks,
                              n_prompt_entries=n_entries),
            **ctx["kw"])


class TestCounterUnits:
    def test_single_request_ticks_and_occupancy_exact(self, ctx):
        srv = _dense(ctx)
        r = srv.submit(_prompts(1)[0])
        _drive(srv)
        dt = srv.stats()["device_telemetry"]
        toks = r.result(0)
        assert toks is not None
        # a no-EOS lane lives exactly maxT-1 ticks (room exhaustion),
        # alone in the pool -> occupancy integral == ticks
        assert dt["ticks"] == MAXT - 1
        assert dt["occupancy_integral"] == MAXT - 1
        assert dt["mean_live_lanes"] == 1.0
        assert dt["admitted_miss"] == 1
        # one drain burst, exited because the pool went idle
        assert dt["exit_all_idle"] == 1
        assert dt["exit_n_steps"] == 0
        srv.close()

    def test_exit_reason_mix_under_queue_pressure(self, ctx):
        # n_slots=2, admit_buckets=[1]: one admission per cycle keeps
        # the queue non-empty, so bursts cap at steps_per_tick and
        # exit n_steps until lanes start dying
        srv = _dense(ctx, steps_per_tick=4)
        for p in _prompts(3):
            srv.submit(p)
        _drive(srv)
        dt = srv.stats()["device_telemetry"]
        assert dt["admitted_miss"] == 3
        assert dt["exit_n_steps"] >= 1
        assert dt["exit_all_idle"] >= 1
        # every burst classified exactly once
        bursts = (dt["exit_n_steps"] + dt["exit_all_idle"]
                  + dt["exit_min_active"])
        assert dt["ticks"] >= bursts  # >= 1 tick per classified burst
        # total device work: 3 no-EOS lanes x (maxT-1) lane-ticks
        assert dt["occupancy_integral"] == 3 * (MAXT - 1)
        srv.close()

    def test_min_active_exit_fires_on_retirement(self, ctx):
        # exit_on_retire hands control back the moment a lane dies
        # while others live: staggered admissions (one per cycle)
        # guarantee lanes die on different ticks
        srv = _dense(ctx, steps_per_tick=4, exit_on_retire=True)
        for p in _prompts(3):
            srv.submit(p)
        _drive(srv)
        dt = srv.stats()["device_telemetry"]
        assert dt["exit_min_active"] >= 1
        srv.close()

    def test_reset_rebases_window(self, ctx):
        srv = _dense(ctx)
        srv.submit(_prompts(1)[0])
        _drive(srv)
        before = srv.stats(reset=True)["device_telemetry"]
        assert before["ticks"] == MAXT - 1
        after = srv.stats()["device_telemetry"]
        assert after["ticks"] == 0
        assert after["occupancy_integral"] == 0
        assert after["admitted_miss"] == 0
        assert after["mean_live_lanes"] is None
        # the metric samples stay CUMULATIVE (Prometheus convention)
        samples = dict(((name, labels.get("server")), v)
                       for name, labels, v
                       in srv._metrics_samples()
                       if name.startswith("paddle_tpu_devtel"))
        assert samples[("paddle_tpu_devtel_ticks_total",
                        srv._obs_id)] == MAXT - 1
        srv.close()

    def test_whole_loop_decode_steps_probe(self, ctx):
        """The unified tick-counter convention's whole-loop half: the
        fixed-name @decode_steps var (declared through
        devtel.declare_decode_steps) is fetchable and reports the
        early-exit iteration count."""
        from paddle_tpu.models import transformer as T
        from paddle_tpu.models.decode_engine import DECODE_STEPS_VAR

        assert DECODE_STEPS_VAR == devtel.DECODE_STEPS_VAR
        with unique_name.guard():
            m, _, _, buf = T.build_incremental_decode_program(
                **ctx["kw"])
        src = np.concatenate(_prompts(2), axis=0)
        toks, steps = ctx["exe"].run(
            m, feed={"src_ids": src},
            fetch_list=[buf, DECODE_STEPS_VAR], scope=ctx["scope"])
        assert int(np.asarray(steps).reshape(-1)[0]) == MAXT - 1


class TestPagedTelemetry:
    def test_hit_admissions_count_separately(self, ctx, obs):
        bundle = _paged_bundle(ctx, "@dtlp/", n_blocks=6)
        # radix_reuse=False: this test pins the HIT tier's counter —
        # under the default, an identical repeat prompt admits through
        # the radix tier instead (tel_admit_radix; ISSUE 17
        # cross-request reuse) and never reaches the hit program
        srv = PagedContinuousGenerationServer(
            bundle, executor=ctx["exe"], scope=ctx["scope"],
            start=False, radix_reuse=False)
        p = _prompts(1)[0]
        srv.submit(p)
        _drive(srv)
        srv.submit(p.copy())   # identical prompt: prefix HIT
        _drive(srv)
        dt = srv.stats()["device_telemetry"]
        assert dt["admitted_miss"] == 1
        assert dt["admitted_hit"] == 1
        assert dt["blocks_hwm"] >= 1
        assert dt["prompt_entries_hwm"] >= 1
        srv.close()

    def test_pause_and_preempt_surface_in_window(self, ctx):
        # two STAGGERED no-EOS lanes (one admission per cycle) over 4
        # blocks: the younger lane hits a block boundary the older
        # one already drained the free list for (one PAUSE), then
        # both block and the youngest is recompute-PREEMPTED — the
        # r13 dynamics, now visible in the telemetry window
        bundle = _paged_bundle(ctx, "@dtlq/", n_blocks=4,
                               admit_buckets=(1,))
        srv = PagedContinuousGenerationServer(
            bundle, executor=ctx["exe"], scope=ctx["scope"],
            start=False, steps_per_tick=4)
        rs = [srv.submit(p) for p in _prompts(2)]
        _drive(srv, max_cycles=400)
        for r in rs:
            assert r.result(0).shape == (MAXT,)
        dt = srv.stats()["device_telemetry"]
        assert dt["pause_events"] >= 1
        assert dt["preemptions"] >= 1
        assert 2 <= dt["blocks_hwm"] <= 4
        # window reset re-bases the host supplement too (hwm drops to
        # the CURRENT residency, not zero-forever)
        srv.stats(reset=True)
        dt2 = srv.stats()["device_telemetry"]
        assert dt2["pause_events"] == 0
        assert dt2["preemptions"] == 0
        srv.close()


class TestGoldenKeysets:
    def test_dense_stats_keyset(self, ctx):
        srv = _dense(ctx)
        srv.submit(_prompts(1)[0])
        _drive(srv)
        assert set(srv.stats()["device_telemetry"]) == DENSE_STATS_KEYS
        srv.close()

    def test_paged_stats_keyset(self, ctx):
        bundle = _paged_bundle(ctx, "@dtlk/", n_blocks=6)
        srv = PagedContinuousGenerationServer(
            bundle, executor=ctx["exe"], scope=ctx["scope"],
            start=False)
        srv.submit(_prompts(1)[0])
        _drive(srv)
        assert set(srv.stats()["device_telemetry"]) == PAGED_STATS_KEYS
        srv.close()

    def test_metric_names_exposed(self, ctx, obs):
        obs("metrics")
        bundle = _paged_bundle(ctx, "@dtlm/", n_blocks=6)
        srv = PagedContinuousGenerationServer(
            bundle, executor=ctx["exe"], scope=ctx["scope"],
            start=False)
        srv.submit(_prompts(1)[0])
        _drive(srv)
        names = {line.split("{")[0]
                 for line in obs_metrics.expose().splitlines()
                 if line.startswith("paddle_tpu_devtel")}
        assert PAGED_METRICS <= names
        srv.close()

    def test_registry_is_the_single_naming_source(self):
        # every metric name/stat key asserted above comes from the
        # declarative registry — the golden sets and the registry
        # must agree or the contract forked
        dense_logical = {c.stat for c in devtel.bundle_counters(False)}
        assert dense_logical | {"mean_live_lanes"} == DENSE_STATS_KEYS
        paged = {c.stat
                 for c in devtel.bundle_counters(True, chunked=False)} \
            | {c.stat for c in devtel.HOST_COUNTERS}
        assert paged | {"mean_live_lanes"} == PAGED_STATS_KEYS
        chunked = {c.stat for c in devtel.bundle_counters(True)} \
            | {c.stat for c in devtel.HOST_COUNTERS}
        assert chunked | {"mean_live_lanes"} == CHUNKED_STATS_KEYS
        assert {c.metric for c in devtel.BUNDLE_COUNTERS} \
            | {c.metric for c in devtel.HOST_COUNTERS} \
            == CHUNKED_METRICS


class TestChurnWithTelemetry:
    def test_zero_steady_state_compiles_and_executable_bound(
            self, ctx, obs):
        """The acceptance bound: telemetry enabled changes NOTHING
        about the compile story — the counters ride state_in/out of
        the same executables."""
        obs("metrics")
        exe = ctx["exe"]
        srv = _dense(ctx, steps_per_tick=4)
        warmed = srv._warmed_compiles
        assert warmed <= len(ctx["bundle"].serves)
        after_warm = exe.compile_count
        rng = np.random.RandomState(3)
        rs = [srv.submit(p) for p in _prompts(30, rng)]
        _drive(srv, max_cycles=600)
        for r in rs:
            assert r.result(0).shape == (MAXT,)
        assert exe.compile_count == after_warm, \
            "telemetry-on churn compiled something"
        dt = srv.stats()["device_telemetry"]
        assert dt["admitted_miss"] == 30
        assert dt["occupancy_integral"] == 30 * (MAXT - 1)
        srv.close()


class TestFlightRecorderInterior:
    def test_exhaustion_incident_carries_burst_interior(self, ctx,
                                                        obs):
        """The forced slow burst: a lone no-EOS request outgrows a
        2-block pool — pause-free growth, then hard exhaustion. The
        retained incident's span tree must explain the burst
        interior: exit reason, tick count, occupancy integral."""
        import paddle_tpu.observability as observability

        obs("trace")
        observability.reset()
        bundle = _paged_bundle(ctx, "@dtlx/", n_blocks=2)
        srv = PagedContinuousGenerationServer(
            bundle, executor=ctx["exe"], scope=ctx["scope"],
            start=False, steps_per_tick=2, drain_steps=2)
        r = srv.submit(_prompts(1)[0])
        _drive(srv, max_cycles=50,
               until=lambda: r.done())
        with pytest.raises(BlockPoolExhausted):
            r.result(0)
        report = observability.incident_report()
        assert report["incidents_retained"] >= 1
        inc = report["incidents"][-1]
        assert inc["status"] == "error"
        bursts = [s for s in inc["spans"]
                  if s["name"] == "slotpool.dispatch"
                  and "attrs" in s and "ticks" in s["attrs"]]
        assert bursts, inc["spans"]
        # 2-block coverage = 8 positions, 2-tick bursts: the doomed
        # request decodes st 0->8 in 4 bursts before exhaustion
        assert len(bursts) == 4
        for b in bursts:
            a = b["attrs"]
            assert a["ticks"] == 2
            assert a["occupancy_integral"] == 2  # lone lane
            assert a["exit_reason"] == "n_steps"
        # every burst fed the cost model's rate calibration (burst 1
        # admits; its sample is prologue-corrected via the key
        # snapshot): what expected_service_ms() sheds by
        assert obs_costmodel.flops_per_s() > 0
        assert srv.expected_service_ms() > 0
        # the queue span carries the prefix tier (r13) so the whole
        # slow-admission story reads from one timeline
        queue = [s for s in inc["spans"]
                 if s["name"] == "slotpool.queue"]
        assert queue and queue[0]["attrs"]["prefix"] == "miss"
        srv.close()


class TestCostModel:
    def test_snapshot_fields_contract(self):
        fields = obs_costmodel.snapshot_fields()
        assert "flops" in fields and "bytes_accessed" in fields \
            and "kind" in fields and "fingerprint" in fields

    def test_lazy_probe_gated_on_flag(self, ctx, obs):
        """At off, a pending probe stays pending (lookup None); the
        first metrics-on lookup resolves it with ONE lowering."""
        obs("off")
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            from paddle_tpu import layers

            x = layers.data("x", shape=[4], dtype="float32")
            y = layers.fc(x, 8)
        exe = fluid.Executor(fluid.TPUPlace(0))
        scope = Scope()
        exe.run(startup, scope=scope)
        exe.run(main, feed={"x": np.ones((2, 4), "float32")},
                fetch_list=[y], scope=scope)
        obs_costmodel.MODEL.probe_resolutions = 0
        assert obs_costmodel.lookup(main) is None
        obs("metrics")
        snap = obs_costmodel.lookup(main)
        assert snap is not None and snap["flops"] > 0
        assert snap["kind"] == "block"
        assert obs_costmodel.MODEL.probe_resolutions == 1
        # second lookup is a dict read, not a second lowering
        assert obs_costmodel.lookup(main) is snap
        assert obs_costmodel.MODEL.probe_resolutions == 1

    def test_calibration_median_and_expected(self, obs):
        m = obs_costmodel.ExecutableCostModel()
        # 3x throttle swings straddle the median
        m.observe(1e6, 1.0)    # 1 Mflop/s
        m.observe(1e6, 3.0)    # throttled leg
        m.observe(3e6, 1.0)    # lucky leg
        assert m.flops_per_s() == pytest.approx(1e6)
        assert m.expected_ms(2e6) == pytest.approx(2000.0)
        assert m.expected_ms(None) is None
        assert obs_costmodel.ExecutableCostModel().expected_ms(1e6) \
            is None  # no calibration yet
