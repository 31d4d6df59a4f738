"""The scheduler's cycles against the device's idle time (PR 36): on a
hand-made profile, where every answer can be worked out; on a small one
recorded on the chip (recorded_cycle_spans.json.gz: eight cycles of
serve_big_repeat, as `program_spans.dump` wrote them); the manifest's
nine entries and their readers; and the readers' silence where the
trace holds no `slotpool.cycle` marker (the parent of PR 36), or a run
took no trace."""
import json
import os

import pytest

from benchmark.chip import cycle_spans, harness, program_spans

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000
SERVE = ["serve_big_repeat", "serve_big_cold", "serve_glm52_docs_32k",
         "serve_nemotron3_chat_128"]
CYCLE_METRICS = {
    "cycle_idle_ms_p95": ("ms", "tpot_ms_p95", SERVE),
    "slow_cycle_idle_share": ("%", "tpot_ms_p95", SERVE),
    "idle_ms.cycle.admitting": ("ms", "serve_tokens_per_s", SERVE),
    # a decoder-only cell's window may hold no cycle without a chunk
    "idle_ms.cycle.decoding": ("ms", "serve_tokens_per_s", SERVE[:2]),
    "cycle_thread_cpu_ms": ("ms", "serve_tokens_per_s", SERVE),
    "placed_arrays_per_dispatch": ("count", "serve_tokens_per_s", SERVE),
    "fetched_arrays_per_dispatch": ("count", "serve_tokens_per_s",
                                    SERVE),
}
SPAN_METRICS = {
    "idle_ms.slotpool.retire.probe": (
        "ms", "serve_tokens_per_s",
        ["serve_glm52_docs_32k", "serve_nemotron3_chat_128"]),
    "idle_ms.slotpool.retire.tree": (
        "ms", "serve_tokens_per_s",
        ["serve_big_repeat", "serve_glm52_docs_32k"]),
}
NEW_METRICS = {**CYCLE_METRICS, **SPAN_METRICS}


def _mark(end_ms, wall_ms, thread="/host:CPU#0", **record):
    return ["slotpool.cycle", thread, int(end_ms * MS), 2000,
            {"wall_us": int(wall_ms * 1000), "key": "0", **record}]


def _loaded():
    """Busy [0, 10) [20, 30) [40, 50) [81, 88) [95, 100) ms: the window
    is 100 ms, idle [10, 20) [30, 40) [50, 81) [88, 95), 58 ms. Cycles
    on the scheduler's thread, by their markers (end, wall):
      c0  [-3, 5)    begins before the window: cut
      c1  [8, 25)    10 ms idle; one admission
      c2  [26, 45)   10 ms idle; a pure burst; 8 ms of cpu in 3 cycles
      c3  [46, 85)   31 ms idle; two prefill chunks
      c4  [86, 97)    7 ms idle; a pure burst; 4 ms of cpu in 2 cycles
      c5  [98, 104)  ends after the window: cut
    The records carry the counts: `placed_arrays` 3 but 5 in c3,
    `fetched_arrays` 12 but 14 in c4, and none in c2, which failed
    before its last phase."""
    sched = "/host:CPU#0"
    spans = [
        _mark(5, 8, admits=1, thread_cpu_us=90000, cpu_cycles=8,
              placed_arrays=3, fetched_arrays=12),
        _mark(25, 17, admits=1, prefill_chunks=0, placed_arrays=3,
              fetched_arrays=12),
        _mark(45, 19, admits=0, thread_cpu_us=8000, cpu_cycles=3),
        _mark(85, 39, admits=0, prefill_chunks=2, placed_arrays=5,
              fetched_arrays=12),
        _mark(97, 11, admits=0, prefill_chunks=0, thread_cpu_us=4000,
              cpu_cycles=2, placed_arrays=3, fetched_arrays=14),
        _mark(104, 6, admits=2, thread_cpu_us=9000, cpu_cycles=1,
              placed_arrays=3, fetched_arrays=12),
        ["slotpool.plan", sched, 8 * MS, 2 * MS, {"admits": 1}],
    ]
    return {"busy": [[0, 10 * MS], [20 * MS, 30 * MS], [40 * MS, 50 * MS],
                     [81 * MS, 88 * MS], [95 * MS, 100 * MS]],
            "spans": spans}


def test_cycles_cut_by_the_windows_ends_are_dropped():
    made = cycle_spans.table(_loaded())
    assert made["window_ms"] == pytest.approx(100)
    assert made["idle_ms"] == pytest.approx(58)
    assert made["cut"] == 2
    # c0 and c5 lie over busy time only where they are in the window
    assert made["cut_idle_ms"] == 0
    rows = made["cycles"]
    assert [r["start_ms"] for r in rows] == pytest.approx([8, 26, 46, 86])
    assert [r["wall_ms"] for r in rows] == pytest.approx([17, 19, 39, 11])
    assert [r["idle_ms"] for r in rows] == pytest.approx([10, 10, 31, 7])
    assert [r["admitting"] for r in rows] == [True, False, True, False]
    assert [r["record"].get("placed_arrays") for r in rows] \
        == [3, None, 5, 3]
    assert rows[2]["record"]["prefill_chunks"] == 2
    # the cycles' idle times are the window's, but for what lies
    # between two cycles and in the cut ones (none here)
    assert sum(r["idle_ms"] for r in rows) == pytest.approx(58)


def test_nearest_rank_percentile():
    assert cycle_spans.percentile([7, 10, 10, 31], 0.95) == 31
    assert cycle_spans.percentile([7, 10, 10, 31], 0.5) == 10
    assert cycle_spans.percentile(list(range(1, 101)), 0.95) == 95
    assert cycle_spans.percentile([4.0], 0.95) == 4.0


@pytest.fixture
def traced_obs(monkeypatch):
    """An `obs` as run.py hands the readers after a traced run whose
    profile is the hand-made one."""
    loaded = _loaded()
    made = cycle_spans.table(loaded)
    monkeypatch.setattr(cycle_spans, "window_cycles",
                        lambda obs: made["cycles"])
    return {"trace": {"busy_s": 0.042, "window_s": 0.1},
            "counters": {"dispatches": 4}}


def test_readers_on_the_hand_made_case(traced_obs):
    read = {n: harness.load_reader(n).read(traced_obs)
            for n in CYCLE_METRICS}
    assert read["cycle_idle_ms_p95"] == pytest.approx(31)
    # the median cycle idles 10 ms; only c3 is over twice that
    assert read["slow_cycle_idle_share"] == pytest.approx(100 * 31 / 58)
    assert read["idle_ms.cycle.admitting"] == pytest.approx(20.5)
    assert read["idle_ms.cycle.decoding"] == pytest.approx(8.5)
    # two readings in the window: 8 + 4 ms over 3 + 2 cycles
    assert read["cycle_thread_cpu_ms"] == pytest.approx(12 / 5)
    # c2's record has no counts: it counts as a cycle that moved none
    assert read["placed_arrays_per_dispatch"] == pytest.approx(11 / 4)
    assert read["fetched_arrays_per_dispatch"] == pytest.approx(38 / 4)


def test_a_window_of_one_kind_of_cycle_reads_nothing_for_the_other(
        monkeypatch):
    rows = [r for r in cycle_spans.table(_loaded())["cycles"]
            if r["admitting"]]
    monkeypatch.setattr(cycle_spans, "window_cycles", lambda obs: rows)
    obs = {"trace": {}, "counters": {}}
    assert harness.load_reader("idle_ms.cycle.decoding").read(obs) is None
    assert harness.load_reader("idle_ms.cycle.admitting").read(obs) \
        == pytest.approx(20.5)


def test_the_retire_sub_spans_read_the_spans_table(monkeypatch):
    loaded = _loaded()
    loaded["spans"] += [
        ["slotpool.retire", "/host:CPU#0", 30 * MS, 8 * MS, {}],
        ["slotpool.retire.probe", "/host:CPU#0", 31 * MS, 3 * MS, {}],
        ["slotpool.retire.tree", "/host:CPU#0", 34 * MS, 2 * MS, {}],
        ["slotpool.retire.tree", "/host:CPU#0", 48 * MS, 4 * MS, {}]]
    made = program_spans.table(loaded)
    monkeypatch.setattr(program_spans, "window_table", lambda obs: made)
    obs = {"trace": {}, "counters": {"dispatches": 4}}
    assert harness.load_reader("idle_ms.slotpool.retire.probe").read(obs) \
        == pytest.approx(3 / 4)
    # [34, 36) idle whole, of [48, 52) the part after 50
    assert harness.load_reader("idle_ms.slotpool.retire.tree").read(obs) \
        == pytest.approx((2 + 2) / 4)


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_every_new_metric_is_in_the_manifest_with_a_reader(name):
    manifest = harness.load_manifest()
    entry, = [m for m in manifest["per_layer"] if m["name"] == name]
    unit, moves, workloads = NEW_METRICS[name]
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": "program_span",
                     "layer": "serving scheduler", "moves": moves,
                     "workloads": workloads}
    assert callable(harness.load_reader(name).read)
    # added at the end: nothing the benchmark had moved
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.index(name) >= len(names) - len(NEW_METRICS)
    # every listed cell reports the end-to-end metric it moves
    for cell in workloads:
        reported = {m["name"] for m in harness.cell_metrics(
            manifest, harness.find_cell(manifest, cell), "end_to_end")}
        assert moves in reported


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_readers_read_nothing_without_a_trace_or_without_a_marker(
        name, monkeypatch):
    reader = harness.load_reader(name)
    untraced = {"trace": None, "counters": {"dispatches": 3}}
    assert reader.read(untraced) is None
    # a traced run of the parent of PR 36: its spans, no marker, no
    # sub-span of `slotpool.retire`
    parent = _loaded()
    parent["spans"] = [ev for ev in parent["spans"]
                       if ev[0] != "slotpool.cycle"]
    assert cycle_spans.table(parent) is None
    monkeypatch.setattr(cycle_spans, "_table_of",
                        lambda path: cycle_spans.table(parent))
    monkeypatch.setattr(cycle_spans.glob, "glob", lambda pat: ["x.pb"])
    monkeypatch.setattr(cycle_spans.os.path, "getmtime", lambda p: 0)
    monkeypatch.setattr(program_spans, "window_table",
                        lambda obs: program_spans.table(parent))
    assert reader.read({**untraced, "trace": {"busy_s": 1.0}}) is None


def test_no_operation_or_no_counted_cycle_gives_nothing(monkeypatch):
    assert cycle_spans.table({"busy": [], "spans": _loaded()["spans"]}) \
        is None
    only_cut = _loaded()
    only_cut["spans"] = [ev for ev in only_cut["spans"]
                         if ev[0] != "slotpool.cycle"
                         or ev[2] in (5 * MS, 104 * MS)]
    made = cycle_spans.table(only_cut)
    assert made["cut"] == 2 and made["cycles"] == []
    monkeypatch.setattr(cycle_spans, "_table_of", lambda path: made)
    monkeypatch.setattr(cycle_spans.glob, "glob", lambda pat: ["x.pb"])
    monkeypatch.setattr(cycle_spans.os.path, "getmtime", lambda p: 0)
    assert cycle_spans.window_cycles({"trace": {}}) is None


def test_recorded_cycles_of_serve_big_repeat(monkeypatch):
    """Eight cycles cut from a traced run of serve_big_repeat on the
    chip (my chip run, PR 36, call 2, seed 3600000021; the processor
    time was read every eighth cycle and at slow ones then): the
    window's ends cut three, the five counted hold an admission of
    the hit and of the radix tier and two pure bursts, three tables
    placed and thirteen arrays fetched a dispatch."""
    loaded = program_spans.load(os.path.join(
        HERE, "recorded_cycle_spans.json.gz"))
    made = cycle_spans.table(loaded)
    spans = program_spans.table(loaded)
    assert made["window_ms"] == pytest.approx(spans["window_ms"]) \
        == pytest.approx(224.363007)
    assert made["idle_ms"] == pytest.approx(spans["idle_ms"]) \
        == pytest.approx(131.670112)
    rows = made["cycles"]
    assert made["cut"] == 3 and len(rows) == 5
    assert [r["admitting"] for r in rows] == [False, True, True, True,
                                              False]
    assert [r["record"]["tier"] for r in rows] \
        == ["none", "hit", "radix", "hit", "none"]
    assert [str(r["record"]["key"]) for r in rows] \
        == ["0", "('hit', 2)", "('radix', 4)", "('hit', 1)", "0"]
    assert [r["idle_ms"] for r in rows] == pytest.approx(
        [28.564353, 24.733642, 27.166033, 15.606403, 16.384647])
    assert all(r["record"]["placed_arrays"] == 3
               and r["record"]["fetched_arrays"] == 13 for r in rows)
    for r in rows:
        assert 0 < r["idle_ms"] < r["wall_ms"]
        assert r["wall_ms"] == pytest.approx(
            float(r["record"]["wall_us"]) / 1e3)
    # one cycle follows another: what the cycles hold, with the cut
    # ones' part, is the window's idle time but for the few
    # microseconds between two cycles and what no span covers
    held = sum(r["idle_ms"] for r in rows) + made["cut_idle_ms"]
    assert spans["attributed_idle_ms"] - 0.2 <= held <= spans["idle_ms"]
    # a cycle holds one dispatch whole
    dispatches = [ev for ev in loaded["spans"]
                  if ev[0] == "slotpool.dispatch"]
    for r in rows:
        lo = r["start_ms"] * MS
        assert sum(lo <= ev[2] - loaded["busy"][0][0]
                   and ev[2] + ev[3] - loaded["busy"][0][0]
                   <= lo + r["wall_ms"] * MS for ev in dispatches) == 1
    monkeypatch.setattr(cycle_spans, "window_cycles", lambda obs: rows)
    monkeypatch.setattr(program_spans, "window_table", lambda obs: spans)
    obs = {"trace": {"busy_s": 0.09}, "counters": {"dispatches": 5}}
    read = {n: harness.load_reader(n).read(obs) for n in NEW_METRICS}
    assert read["cycle_idle_ms_p95"] == pytest.approx(28.564353)
    assert read["slow_cycle_idle_share"] == 0       # none over 2 medians
    assert read["idle_ms.cycle.admitting"] == pytest.approx(
        (24.733642 + 27.166033 + 15.606403) / 3)
    assert read["idle_ms.cycle.decoding"] == pytest.approx(
        (28.564353 + 16.384647) / 2)
    # three readings: 90 + 30 + 40 ms over 5 + 1 + 2 cycles
    assert read["cycle_thread_cpu_ms"] == pytest.approx(160 / 8)
    assert read["placed_arrays_per_dispatch"] == 3
    assert read["fetched_arrays_per_dispatch"] == 13
    assert read["idle_ms.slotpool.retire.tree"] == pytest.approx(
        spans["spans"]["slotpool.retire.tree"]["idle_ms"] / 5)
    assert read["idle_ms.slotpool.retire.probe"] is None   # no probes


def test_window_cycles_takes_the_newest_profile_and_writes_it_out(
        tmp_path, monkeypatch):
    monkeypatch.setattr(cycle_spans, "ROOT", str(tmp_path))
    monkeypatch.setattr(program_spans, "load", lambda path: _loaded())
    cycle_spans._table_of.cache_clear()
    assert cycle_spans.window_cycles({"trace": {}}) is None   # none yet
    for cell, age in (("cell_a", 100), ("cell_b", 50)):
        d = tmp_path / ".benchchip_trace" / cell / "plugins" / \
            "profile" / "2026_01_01"
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(b"")
        os.utime(d / "host.xplane.pb", (1e9 - age, 1e9 - age))
    rows = cycle_spans.window_cycles({"trace": {}})
    assert len(rows) == 4
    out = tmp_path / "chiprun_out" / "benchchip"
    assert [p.name for p in out.iterdir()] == ["cell_b.cycles.json"]
    kept = json.loads((out / "cell_b.cycles.json").read_text())
    assert kept["cut"] == 2 and len(kept["cycles"]) == 4
    assert cycle_spans.window_cycles({"trace": None}) is None
    cycle_spans._table_of.cache_clear()
