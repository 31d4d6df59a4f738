"""One record a scheduler cycle (PR 36): the third sink of
`tracing.span`, kept at every flag level.

* a served request leaves ring records whose top-level phases add up to
  the cycle and whose counts add up to `stats()`;
* a cycle stalled in a caller's callback becomes a `slow_cycle`
  incident with its phase split at `metrics`, and none at `off`, where
  `stats()["cycle_ms"]` and `stats()["slow_cycles"]` are still filled;
* the ring and its histograms are bounded, and `stats(reset=True)`
  clears them;
* `with tracing.cycle(...)` around `Executor.run` gives a training loop
  the executor's six phases;
* with every sink off a span inside an open record reaches no other
  sink and leaves nothing behind but its sum in the record.
"""
import gc
import statistics
import sys
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observability as obs
from paddle_tpu import profiler, unique_name
from paddle_tpu.core.scope import Scope
from paddle_tpu.flags import FLAGS
from paddle_tpu.observability import tracing

TOP = ("slotpool.plan", "slotpool.feed", "slotpool.dispatch",
       "slotpool.retire", "slotpool.deliver")
CYCLE_MS_KEYS = {"plan", "feed", "dispatch", "retire", "deliver",
                 "exe.feed", "exe.state", "exe.call", "exe.store",
                 "exe.fetch", "gc", "wall"}


@pytest.fixture(autouse=True)
def _obs_hermetic():
    saved = FLAGS._values["observability"]
    profiler.reset_profiler()
    obs.reset()
    yield
    FLAGS._values["observability"] = saved
    profiler.reset_profiler()
    obs.reset()


@pytest.fixture(scope="module")
def tiny():
    """(paged bundle, executor, scope, prompts): the 2017 transformer at
    toy widths, untrained, with an end token the argmax cannot emit, so
    every request decodes its 7 tokens in bursts of 2 ticks."""
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
    with unique_name.guard():
        paged = T.build_decode_step_program(
            state_prefix="@cyc/", n_slots=2, admit_buckets=[1, 2],
            max_out_len=8, start_id=2, end_id=v + 7,
            cache=CacheConfig(layout="paged", block_size=4, n_blocks=8,
                              n_prompt_entries=4), **model)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(3, v, (1, s_len)).astype(np.int64)
               for _ in range(3)]
    return paged, exe, scope, prompts


def _server(tiny, **kw):
    from paddle_tpu.inference.serving import \
        PagedContinuousGenerationServer

    paged, exe, scope, _prompts = tiny
    return PagedContinuousGenerationServer(
        paged, executor=exe, scope=scope, steps_per_tick=2,
        drain_steps=2, **kw)


def _settle(srv):
    """Wait for the last cycle's record: a reply resolves inside the
    cycle's delivery, a moment before the record closes."""
    deadline = time.monotonic() + 30
    while len(srv._cycles.records()) != srv.stats()["ticks"]:
        assert time.monotonic() < deadline
        time.sleep(0.005)


@pytest.mark.parametrize("level", ["off", "metrics"])
def test_records_add_up_to_the_cycle_and_to_stats(tiny, level):
    FLAGS._values["observability"] = level
    prompts = tiny[3]
    with _server(tiny) as srv:
        chunks = []
        replies = [srv.submit(p, stream_cb=lambda toks, seq, fin:
                              chunks.append(len(toks)))
                   for p in prompts]
        for r in replies:
            r.result(120.0)
        _settle(srv)
        st = srv.stats()
        records = srv._cycles.records()
    assert len(records) == st["ticks"] > 0      # one a dispatch
    cover = []
    for rec in records:
        top = sum(rec["phases"].get(name, 0.0) for name in TOP)
        assert top <= rec["wall_ms"] + 1e-3, rec
        cover.append(top / rec["wall_ms"])
        # the executor's phases lie inside the dispatch
        inner = sum(v for k, v in rec["phases"].items()
                    if k.startswith("exe.") and k.count(".") == 1)
        assert inner <= rec["phases"]["slotpool.dispatch"] + 1e-3
        assert rec["gc_ms"] >= 0 and rec["name"] == "slotpool.cycle"
        assert ("cpu_cycles" in rec) == ("thread_cpu_ms" in rec)
    assert statistics.median(cover) >= 0.8, cover
    assert sum(r["admits"] for r in records) == st["requests"] == 3
    assert sum(r["retired"] for r in records) == st["completed"] == 3
    # every burst's chunk and every reply's finish marker was delivered
    assert sum(r["delivered"] for r in records) \
        == sum(1 for n in chunks if n)
    assert sum(r["submitted"] for r in records) == 0   # no resubmits
    assert all(r["n_steps"] == 2 for r in records)
    # the serve key: 0 for a pure burst, (tier, bucket) for an admission
    assert all((r["key"] == 0) == (r["admits"] == 0) for r in records)
    assert {r["key"] for r in records if r["admits"]} \
        <= {(t, a) for t in ("miss", "hit", "radix") for a in (1, 2)}
    assert {r["tier"] for r in records} <= {"miss", "hit", "radix",
                                            "none"}
    # what the executor moved: the packed row down in every cycle,
    # and nothing placed once the first has taken up what
    # `init_slot_state` left in the scope (the tables of
    # `_pre_dispatch` ride the call as feeds)
    assert all(r["fetched_arrays"] == 1 for r in records)
    assert all(r["placed_arrays"] == 0 for r in records[1:])
    assert set(st["cycle_ms"]) == CYCLE_MS_KEYS
    for phase in CYCLE_MS_KEYS - {"gc"}:
        got = st["cycle_ms"][phase]
        assert set(got) == {"p50", "p95", "max"}
        assert 0 <= got["p50"] <= got["p95"] <= got["max"] + 1e-9, phase
    assert st["cycle_ms"]["wall"]["max"] == pytest.approx(
        max(r["wall_ms"] for r in records), abs=1e-3)
    assert st["slow_cycles"] == srv._cycles.slow_cycles


def _stall_one_cycle(srv, prompts):
    """Serve until the ring can judge a decode cycle, then one request
    whose callback sleeps 50 median cycles at its second burst (a
    decode cycle: serve key 0). Returns the sleep in ms."""
    decode = lambda: [r for r in srv._cycles.records() if r["key"] == 0]
    for i in range(400):
        srv.submit(prompts[i % len(prompts)]).result(120.0)
        if len(decode()) >= 2 * tracing.SLOW_CYCLE_MIN \
                and 0 in srv._cycles._medians:
            break
    else:
        raise AssertionError("the ring never held its 32 decode cycles")
    median_ms = statistics.median(r["wall_ms"] for r in decode())
    nap_ms = max(50 * median_ms, 50.0)
    calls = []

    def slow_cb(toks, first_seq, finish):
        calls.append(first_seq)
        if len(calls) == 2:
            time.sleep(nap_ms / 1e3)
    # a prompt the server has not seen: an admission by prefill, then
    # decode bursts of two tokens, the second of them stalled
    fresh = np.full_like(prompts[0], 3)
    srv.submit(fresh, stream_cb=slow_cb).result(120.0)
    assert len(calls) >= 4
    _settle(srv)
    # processor time: read when a quarter of a second has passed, for
    # the cycles since the reading before, and at a slow cycle
    timed = [r for r in srv._cycles.records() if "cpu_cycles" in r]
    assert timed
    for rec in timed:
        assert rec["cpu_cycles"] >= 1
        assert rec["thread_cpu_ms"] >= 0 and rec["process_cpu_ms"] >= 0
    return nap_ms


def test_a_stalled_cycle_is_an_incident_at_metrics(tiny):
    FLAGS._values["observability"] = "metrics"
    with _server(tiny) as srv:
        nap_ms = _stall_one_cycle(srv, tiny[3])
        slow = srv.stats()["slow_cycles"]
        owner = srv._obs_id
        records = srv._cycles.records()
    report = obs.flight.incident_report()
    mine = [inc for inc in report["incidents"]
            if inc.get("kind") == "slow_cycle"
            and inc["phases"].get("slotpool.deliver", 0) >= 0.9 * nap_ms]
    assert len(mine) == 1, report["incidents"]
    inc = mine[0]
    assert inc["server"] == owner and inc["key"] == 0
    assert max(inc["phases"], key=inc["phases"].get) \
        == "slotpool.deliver"
    assert inc["wall_ms"] >= nap_ms > 2 * inc["median_ms"] > 0
    # a slow cycle always reads the processor time (for the cycles
    # since the reading before, a quarter of a second's at most, and
    # the faster a cycle the more of them): the thread slept through
    # the stall, it did not compute through it
    assert inc["cpu_cycles"] >= 1
    at = max(range(len(records)),
             key=lambda i: records[i]["phases"].get("slotpool.deliver", 0))
    covered = records[max(0, at + 1 - inc["cpu_cycles"]):at + 1]
    assert inc["thread_cpu_ms"] \
        < sum(r["wall_ms"] for r in covered) - 0.5 * nap_ms
    assert {"admits", "retired", "delivered", "submitted", "n_steps",
            "queue_depth", "tier", "placed_arrays", "fetched_arrays",
            "process_cpu_ms", "gc_ms", "gc_runs"} <= set(inc)
    assert slow >= 1


def test_a_stalled_cycle_is_counted_and_not_recorded_at_off(tiny):
    FLAGS._values["observability"] = "off"
    with _server(tiny) as srv:
        _stall_one_cycle(srv, tiny[3])
        st = srv.stats()
    assert st["slow_cycles"] >= 1
    assert st["cycle_ms"]["deliver"]["max"] >= 50.0
    assert obs.RECORDER.recorded_total == 0
    assert not obs.flight.incident_report()["incidents"]


def test_ring_and_histograms_are_bounded_and_cleared_by_reset(tiny):
    ring = tracing.CycleRing(owner="t")
    for i in range(tracing.CYCLE_RING_SIZE + 90):
        with tracing.cycle("step", ring) as rec:
            with tracing.span("exe.call"):
                pass
            rec.attrs["i"] = i
    kept = ring.records()
    assert len(kept) == tracing.CYCLE_RING_SIZE
    assert kept[0]["i"] == 90 and kept[-1]["i"] == i
    hist = ring._hist["exe.call"]
    assert hist.count == tracing.CYCLE_RING_SIZE + 90
    assert len(hist._counts) == len(hist.buckets) + 1   # O(buckets)
    ring.clear()
    assert ring.records() == [] and hist.count == 0
    assert ring.summary()["wall"] == {"p50": None, "p95": None,
                                      "max": None}
    # and the server's, through stats(reset=True)
    with _server(tiny) as srv:
        srv.submit(tiny[3][0]).result(120.0)
        _settle(srv)
        assert srv.stats(reset=True)["cycle_ms"]["wall"]["p50"] > 0
        st = srv.stats()
        assert st["cycle_ms"]["wall"]["p50"] is None
        assert st["slow_cycles"] == 0
        assert srv._cycles.records() == []
        families = {(name, lab.get("phase"))
                    for name, lab, _v in srv._metrics_samples()}
        assert {("paddle_tpu_server_cycle_ms", p)
                for p in CYCLE_MS_KEYS} <= families


def test_a_training_loop_gets_the_executors_phases():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.fc(x, 8)
    exe, scope = fluid.Executor(fluid.TPUPlace(0)), Scope()
    exe.run(startup, scope=scope)
    ring = tracing.CycleRing(owner="trainer")
    for step in range(3):
        with tracing.cycle("train.step", ring, step=step):
            exe.run(main, feed={"x": np.ones((2, 4), "float32")},
                    fetch_list=[y], scope=scope)
    records = ring.records()
    assert [r["step"] for r in records] == [0, 1, 2]
    for rec in records:
        assert set(tracing.EXE_PHASES) <= set(rec["phases"])
        assert sum(rec["phases"][p] for p in tracing.EXE_PHASES) \
            <= rec["wall_ms"] + 1e-3
    assert "exe.compile" in records[0]["phases"]     # the first step's
    assert "exe.compile" not in records[2]["phases"]
    assert set(ring.summary()) == set(tracing.EXE_PHASES) | {"gc",
                                                             "wall"}
    assert ring.summary()["exe.call"]["p50"] > 0
    assert tracing.current_cycle() is None


def test_with_every_sink_off_a_span_feeds_the_record_alone(monkeypatch):
    FLAGS._values["observability"] = "off"

    def no_sink(*args, **kwargs):
        raise AssertionError("a sink was opened with every sink off")
    monkeypatch.setattr(tracing, "TraceAnnotation", no_sink)
    monkeypatch.setattr(tracing.Trace, "__init__", no_sink)
    ring = tracing.CycleRing(owner="t")
    with tracing.cycle("step", ring, who="test") as rec:
        assert tracing.current_cycle() is rec
        for _ in range(3):
            with tracing.span("exe.call", arrays=2) as sp:
                assert not sp.recording         # no one takes attributes
                assert not sp._traces and sp._ann is None
        with tracing.span("exe.fetch"):
            pass
    assert tracing.current_cycle() is None
    assert tracing.start_request(owner="server") is None
    # three spans of one name are one sum, nothing more
    assert set(rec.phases) == {"exe.call", "exe.fetch"}
    assert all(type(v) is float and v >= 0 for v in rec.phases.values())
    assert rec.as_dict()["who"] == "test"
    assert not obs.TRACER.completed and not obs.TRACER.global_events
    assert obs.RECORDER.recorded_total == 0
    # the record is the ring's alone (beside this frame's name and the
    # call's argument): no span, thread or sink kept hold of it
    del sp
    assert sys.getrefcount(rec) == 3
    ring.clear()
    assert sys.getrefcount(rec) == 2


def test_the_collectors_runs_are_counted_while_a_ring_exists():
    ring = tracing.CycleRing(owner="t")
    assert tracing._on_gc in gc.callbacks
    with tracing.cycle("step", ring):
        gc.collect()
        gc.collect()
    rec = ring.records()[0]
    assert rec["gc_runs"] == 2 and rec["gc_ms"] > 0
    assert ring.summary()["gc"]["max"] == pytest.approx(rec["gc_ms"],
                                                        abs=1e-3)
    with tracing.cycle("step", ring):
        pass
    assert ring.records()[1]["gc_runs"] == 0


def test_a_dropped_cycle_leaves_no_record():
    ring = tracing.CycleRing(owner="t")
    with tracing.cycle("step", ring) as rec:
        rec.drop()
    assert ring.records() == [] and ring._hist["wall"].count == 0


def test_slow_is_judged_against_the_same_key_once_32_are_held():
    """Hand-made records: walls of 1 ms under key "a", 10 ms under key
    "b"; a 5 ms cycle is slow under "a" and not under "b"; before the
    ring holds 32 of a key nothing is slow."""
    ring = tracing.CycleRing(owner="t")

    def push(key, wall):
        rec = tracing.cycle("step", ring)
        rec.key, rec.wall, rec.gc, rec.gc_runs = key, wall, 0.0, 0
        rec.t0 = time.monotonic()
        before = ring.slow_cycles
        ring._push(rec)
        return ring.slow_cycles - before
    for i in range(31):
        assert push("a", 1e-3) == 0
    assert push("a", 5e-3) == 0         # 32nd: no median yet
    assert ring._medians == {"a": pytest.approx(1e-3)}
    for i in range(32):
        push("b", 10e-3)
    assert push("a", 5e-3) == 1
    assert push("b", 5e-3) == 0 and push("b", 25e-3) == 1
    assert push("c", 1.0) == 0          # a key of its own: not judged


def test_the_marker_of_a_profile_carries_the_record(monkeypatch):
    """While a profile runs a cycle ends in one `paddle_tpu:<name>`
    event whose metadata is the record, with the processor time since
    the reading before wherever one was taken (here at every cycle). (The profiler's own file
    is read back in tests/test_observability.py.)"""
    events = []

    class Annotation:
        def __init__(self, name, **meta):
            self.name, self.meta = name, dict(meta)

        def __enter__(self):
            events.append(self)
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **meta):
            self.meta.update(meta)
    monkeypatch.setattr(tracing, "TraceAnnotation", Annotation)
    monkeypatch.setattr(tracing, "_profiling", lambda: True)
    monkeypatch.setattr(tracing, "CPU_READ_S", 0.0)
    ring = tracing.CycleRing(owner="t")
    for i in range(2 * tracing.REFRESH_EVERY):
        with tracing.cycle("slotpool.cycle", ring, admits=i) as rec:
            rec.key = ("miss", 1)
            with tracing.span("exe.call"):
                pass
    marks = [ev for ev in events if ev.name == "paddle_tpu:slotpool.cycle"]
    assert len(marks) == 2 * tracing.REFRESH_EVERY
    assert [m.meta["admits"] for m in marks] == list(range(len(marks)))
    assert all(m.meta["key"] == "('miss', 1)" and m.meta["wall_us"] >= 0
               and "gc_us" in m.meta for m in marks)
    # the first cycle's reading has none before it to measure from
    assert "cpu_cycles" not in marks[0].meta
    for m in marks[1:]:
        assert m.meta["cpu_cycles"] == 1
        assert m.meta["thread_cpu_us"] >= 0
        assert m.meta["process_cpu_us"] >= 0
    spans = [ev for ev in events if ev.name == "paddle_tpu:exe.call"]
    assert len(spans) == len(marks)
