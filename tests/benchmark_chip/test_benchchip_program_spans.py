"""The program's spans against the device's idle time: on hand-made
intervals, where every answer can be worked out; on a small trace
recorded on the chip (recorded_program_spans.json.gz: three dispatches
of serve_big_repeat, as `program_spans.dump` wrote them); and the
readers' silence where a run took no trace or the program records no
span."""
import json
import os

import pytest

from benchmark.chip import harness, program_spans

from benchchip_util import RUN, cell_args, python, result_line

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000
NEW_METRICS = sorted(
    [f"idle_ms.exe.{k}" for k in ("feed", "lookup", "state", "call",
                                  "store", "fetch")]
    + [f"idle_ms.slotpool.{k}" for k in ("plan", "feed", "dispatch",
                                         "retire", "deliver")]
    + ["queue_wait_ms_p50", "idle_attributed_share.train",
       "idle_attributed_share.serve"])


def _loaded():
    """Busy [0, 10) [20, 30) [34, 40) ms: the window is 40 ms, idle
    [10, 20) and [30, 34). Spans, on the scheduler's thread unless
    said:
      plan      [8, 14)     half of it over the first idle interval
      dispatch  [14, 32)    6 ms of the first and 2 of the second
      exe.call  [15, 21)    nested in the dispatch: 5 ms idle
      plan      [33, 36)    a second event of the name: 1 ms idle
      admit     [9, 9.001)  inside plan, busy under it; wait 1500 us
      admit     [33.5, +1us) idle under it; wait 500 us
      submit    [12, 13)    on a caller's thread, over idle time that
                            plan covers too
      wait      [41, 50)    after the window: left out
      retire    [38, 45)    runs past the window's end: clipped
    """
    sched, caller = "/host:CPU#0", "/host:CPU#1"
    return {
        "busy": [[0, 10 * MS], [20 * MS, 30 * MS], [34 * MS, 40 * MS]],
        "spans": [
            ["slotpool.plan", sched, 8 * MS, 6 * MS, {"admits": 1}],
            ["slotpool.dispatch", sched, 14 * MS, 18 * MS, {}],
            ["exe.call", sched, 15 * MS, 6 * MS, {}],
            ["slotpool.plan", sched, 33 * MS, 3 * MS, {"admits": 1}],
            ["slotpool.admit", sched, 9 * MS, 1000,
             {"wait_us": 1500, "tier": "miss", "slot": 0}],
            ["slotpool.admit", sched, 33 * MS + MS // 2, 1000,
             {"wait_us": 500, "tier": "hit", "slot": 1}],
            ["slotpool.submit", caller, 12 * MS, 1 * MS, {}],
            ["slotpool.wait", sched, 41 * MS, 9 * MS, {}],
            ["slotpool.retire", sched, 38 * MS, 7 * MS, {}],
        ]}


def test_idle_under_each_span_by_hand():
    t = program_spans.table(_loaded())
    assert t["window_ms"] == pytest.approx(40)
    assert t["idle_ms"] == pytest.approx(14)
    rows = t["spans"]
    assert rows["slotpool.plan"] == {
        "events": 2, "ms": pytest.approx(9),
        "idle_ms": pytest.approx(4 + 1)}
    assert rows["slotpool.dispatch"]["idle_ms"] == pytest.approx(6 + 2)
    assert rows["exe.call"]["idle_ms"] == pytest.approx(5)
    assert rows["slotpool.admit"]["events"] == 2
    assert rows["slotpool.admit"]["idle_ms"] == pytest.approx(0.001)
    assert rows["slotpool.submit"]["idle_ms"] == pytest.approx(1)
    assert "slotpool.wait" not in rows              # outside
    assert rows["slotpool.retire"]["ms"] == pytest.approx(2)   # clipped
    assert rows["slotpool.retire"]["idle_ms"] == 0
    # plan [10, 14) + dispatch [14, 20) [30, 32) + plan [33, 34): the
    # nested and the second-thread spans add nothing of their own
    assert t["attributed_idle_ms"] == pytest.approx(13)
    assert t["queue_wait_us"] == [1500, 500]
    top = ("slotpool.plan", "slotpool.dispatch", "slotpool.retire")
    assert sum(rows[k]["idle_ms"] for k in top) \
        == pytest.approx(t["attributed_idle_ms"])


def test_one_name_on_two_threads_counts_an_idle_moment_once():
    loaded = _loaded()
    loaded["spans"].append(
        ["slotpool.submit", "/host:CPU#2", 12 * MS + MS // 2, MS, {}])
    row = program_spans.table(loaded)["spans"]["slotpool.submit"]
    assert row["events"] == 2 and row["ms"] == pytest.approx(2)
    assert row["idle_ms"] == pytest.approx(1.5)


def test_no_operation_gives_no_table_and_no_span_an_empty_one():
    assert program_spans.table({"busy": [], "spans": []}) is None
    bare = program_spans.table({"busy": _loaded()["busy"], "spans": []})
    assert bare["spans"] == {} and bare["attributed_idle_ms"] == 0
    assert bare["idle_ms"] == pytest.approx(14)


def test_dump_and_load_round_trip(tmp_path):
    path = str(tmp_path / "spans.json.gz")
    program_spans.dump(_loaded(), path)
    assert program_spans.load(path) == _loaded()


@pytest.fixture
def traced_obs(monkeypatch):
    """An `obs` as run.py hands the readers after a traced run whose
    profile is the hand-made one."""
    made = program_spans.table(_loaded())
    monkeypatch.setattr(program_spans, "window_table", lambda obs: made)
    return {"trace": {"busy_s": 0.026, "window_s": 0.040},
            "counters": {"traced_steps": 2, "dispatches": 2}}


def test_readers_read_the_table(traced_obs):
    read = {n: harness.load_reader(n).read(traced_obs)
            for n in NEW_METRICS}
    assert read["idle_ms.slotpool.plan"] == pytest.approx(2.5)
    assert read["idle_ms.slotpool.dispatch"] == pytest.approx(4)
    assert read["idle_ms.slotpool.retire"] == 0
    assert read["idle_ms.exe.call"] == pytest.approx(2.5)
    assert read["queue_wait_ms_p50"] == pytest.approx(1.0)
    assert read["idle_attributed_share.serve"] \
        == read["idle_attributed_share.train"] \
        == pytest.approx(100 * 13 / 14)
    # a span the program did not record in this window reads nothing
    assert read["idle_ms.exe.fetch"] is None
    assert read["idle_ms.slotpool.deliver"] is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_readers_read_nothing_without_a_trace_or_without_spans(
        name, monkeypatch):
    reader = harness.load_reader(name)
    untraced = {"trace": None, "counters": {"traced_steps": 3,
                                            "dispatches": 3}}
    assert reader.read(untraced) is None
    # a traced run of a program that records no span (the parent of
    # PR 25): an empty table
    bare = program_spans.table({"busy": _loaded()["busy"], "spans": []})
    monkeypatch.setattr(program_spans, "window_table", lambda obs: bare)
    assert reader.read({**untraced, "trace": {"busy_s": 1.0}}) is None


def test_recorded_dispatches_of_serve_big_repeat(monkeypatch):
    """Three dispatches cut from a traced run of serve_big_repeat on
    the chip (my chip run, PR 25, seed 2147486121): an admission of
    each of two tiers, 8 ticks a burst, every span on the scheduler's
    thread. The first cycle's planning and feeds lie before the
    window's first operation and are left out."""
    loaded = program_spans.load(os.path.join(
        HERE, "recorded_program_spans.json.gz"))
    assert len({ev[1] for ev in loaded["spans"]}) == 1
    made = program_spans.table(loaded)
    assert made["window_ms"] == pytest.approx(657.579588)
    assert made["idle_ms"] == pytest.approx(25.731123)
    rows = made["spans"]
    assert rows["slotpool.dispatch"]["events"] == 3
    assert rows["slotpool.plan"]["events"] == 2
    for row in rows.values():
        assert row["idle_ms"] <= row["ms"] + 1e-9
    # the spans of a cycle do not nest in each other: their idle
    # times add up to the attributed idle time, inside all of it
    cycle = ("slotpool.plan", "slotpool.feed", "slotpool.dispatch",
             "slotpool.retire", "slotpool.deliver")
    assert sum(rows[k]["idle_ms"] for k in cycle) \
        == pytest.approx(made["attributed_idle_ms"]) \
        == pytest.approx(25.622663)
    assert made["attributed_idle_ms"] <= made["idle_ms"]
    # the executor's spans nest in the dispatch, most of whose idle
    # time is the readback of the fetches one array at a time
    inner = sum(rows[k]["idle_ms"] for k in rows if k.startswith("exe."))
    assert inner <= rows["slotpool.dispatch"]["idle_ms"]
    assert rows["exe.fetch"]["idle_ms"] == pytest.approx(13.565745)
    assert rows["exe.store"]["idle_ms"] < 0.001
    monkeypatch.setattr(program_spans, "window_table", lambda obs: made)
    obs = {"trace": {"busy_s": 0.63}, "counters": {"dispatches": 3}}
    read = {n: harness.load_reader(n).read(obs) for n in NEW_METRICS}
    assert read["idle_attributed_share.serve"] \
        == pytest.approx(99.5785, abs=1e-3)
    assert read["idle_ms.slotpool.dispatch"] \
        == pytest.approx(21.562154 / 3)
    assert read["queue_wait_ms_p50"] == pytest.approx(0.0585)
    assert read["idle_ms.exe.feed"] is None     # no `traced_steps`


def test_window_table_takes_the_newest_profile_and_writes_it_out(
        tmp_path, monkeypatch):
    monkeypatch.setattr(program_spans, "ROOT", str(tmp_path))
    monkeypatch.setattr(program_spans, "load",
                        lambda path: {**_loaded(), "path": path})
    program_spans._table_of.cache_clear()
    assert program_spans.window_table({"trace": {}}) is None  # none yet
    for cell, age in (("cell_a", 100), ("cell_b", 50)):
        d = tmp_path / ".benchchip_trace" / cell / "plugins" / \
            "profile" / "2026_01_01"
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(b"")
        os.utime(d / "host.xplane.pb", (1e9 - age, 1e9 - age))
    made = program_spans.window_table({"trace": {}})
    assert made["idle_ms"] == pytest.approx(14)
    out = tmp_path / "chiprun_out" / "benchchip"
    assert [p.name for p in out.iterdir()] == ["cell_b.spans.json"]
    assert json.loads((out / "cell_b.spans.json").read_text()) == made
    assert program_spans.window_table({"trace": None}) is None
    program_spans._table_of.cache_clear()


def test_a_traced_rehearsal_still_passes_and_reads_none_of_them():
    done = python([RUN] + cell_args("train_base_s256", 1)
                  + ["--rehearse"])
    assert done.returncode == 0, done.stderr[-2000:]
    res = result_line(done.stdout)
    assert res is not None and res["correct"] is True
    assert not set(res["metrics"]) & set(NEW_METRICS)
