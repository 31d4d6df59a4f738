"""The scheduler's cycles in the profiler's trace, one by one.

Since PR 36 the serving scheduler closes every cycle with a short host
event `paddle_tpu:slotpool.cycle` whose metadata is the program's record
of that cycle (`paddle_tpu/observability/tracing.py`, `cycle`):
`wall_us` before the marker's start the cycle began (the start of its
`slotpool.plan`), and the counts say what it did (`admits`,
`prefill_chunks`, `retired`, `delivered`, `placed_arrays`,
`fetched_arrays`, `thread_cpu_us`, ...). `table`
cuts the device's idle time by those intervals, so that the readers
under `metrics/` can take a distribution over cycles where
`program_spans.table` gives one sum a span name: `cycle_idle_ms_p95`,
`slow_cycle_idle_share`, `idle_ms.cycle.admitting`, `.decoding`,
`cycle_thread_cpu_ms`, `placed_arrays_per_dispatch`,
`fetched_arrays_per_dispatch`. A cycle counts if it lies wholly inside
the traced window (first operation's start to the last one's end, as
`program_spans.table` has it). A trace without the marker (the parent
of PR 36, a training cell, the CPU) gives None and every reader reads
nothing. The profile is read with `program_spans.load`.
"""
import functools
import glob
import json
import math
import os
import statistics

from . import program_spans
from .program_spans import ROOT, _overlap

CYCLE_SPAN = "slotpool.cycle"
# a cycle is slow when its idle time is over this many medians
SLOW_FACTOR = 2.0


def table(loaded):
    """None where no operation ran or the trace holds no cycle marker.
    Otherwise
      window_ms, idle_ms   as `program_spans.table`
      cut, cut_idle_ms     cycles left out because the window's start
                           or end cuts them, and the idle time of
                           their part inside the window
      cycles               one row a counted cycle, in order: `start_ms`
                           from the window's start, `wall_ms`,
                           `idle_ms` (its interval intersected with the
                           device's idle intervals), `admitting` (an
                           admission or a prefill chunk), and the
                           marker's metadata under `record`
    """
    busy = loaded["busy"]
    marks = sorted((ev for ev in loaded["spans"] if ev[0] == CYCLE_SPAN
                    and "wall_us" in ev[4]), key=lambda ev: ev[2])
    if not busy or not marks:
        return None
    w0, w1 = busy[0][0], busy[-1][1]
    idle = [[a[1], b[0]] for a, b in zip(busy, busy[1:])]
    rows, cut, cut_idle = [], 0, 0
    for _name, _thread, end, _dur, meta in marks:
        start = end - int(float(meta["wall_us"]) * 1000)
        if start < w0 or end > w1:
            cut += 1
            cut_idle += _overlap([[start, end]], idle)
            continue
        rows.append({
            "start_ms": (start - w0) / 1e6,
            "wall_ms": (end - start) / 1e6,
            "idle_ms": _overlap([[start, end]], idle) / 1e6,
            "admitting": float(meta.get("admits", 0)) > 0
            or float(meta.get("prefill_chunks", 0)) > 0,
            "record": meta})
    return {"window_ms": (w1 - w0) / 1e6,
            "idle_ms": sum(e - s for s, e in idle) / 1e6,
            "cut": cut, "cut_idle_ms": cut_idle / 1e6, "cycles": rows}


@functools.lru_cache(maxsize=1)
def _table_of(xplane_path):
    """`table` of one profile, read once a process, and left beside the
    run's other files as chiprun_out/benchchip/<trace directory's
    name>.cycles.json (the rows: which cycles were the slow ones)."""
    made = table(program_spans.load(xplane_path))
    trace_dir = xplane_path
    for _ in range(4):      # <dir>/plugins/profile/<time>/<file>
        trace_dir = os.path.dirname(trace_dir)
    out_dir = os.path.join(ROOT, "chiprun_out", "benchchip")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(
            out_dir, os.path.basename(trace_dir) + ".cycles.json"),
            "w") as f:
        json.dump(made, f)
    return made


def window_cycles(obs):
    """The counted cycles of this run's traced window (rows of
    `table`), or None where the run took no trace, the trace holds no
    marker, or no cycle lies wholly inside the window. The newest
    profile under .benchchip_trace/ is this run's, as for
    `program_spans.window_table`."""
    if obs.get("trace") is None:
        return None
    found = glob.glob(os.path.join(
        ROOT, ".benchchip_trace", "*", "plugins", "profile", "*",
        "*.xplane.pb"))
    if not found:
        return None
    made = _table_of(max(found, key=os.path.getmtime))
    return (made or {}).get("cycles") or None


def percentile(values, p):
    """Nearest rank: the smallest value with at least `p` of the
    values at or under it."""
    ranked = sorted(values)
    return ranked[max(1, math.ceil(p * len(ranked))) - 1]


def slow_idle_share(obs):
    """Percent of the cycles' idle time that lies in cycles whose idle
    time is over SLOW_FACTOR times the median cycle's."""
    rows = window_cycles(obs)
    if not rows:
        return None
    idle = [r["idle_ms"] for r in rows]
    if not sum(idle):
        return None
    limit = SLOW_FACTOR * statistics.median(idle)
    return 100.0 * sum(v for v in idle if v > limit) / sum(idle)


def mean_of(obs, value, admitting=None):
    """Mean over the counted cycles of `value(row)`; with `admitting`
    True or False over the cycles of that kind only. None where there
    is none."""
    rows = window_cycles(obs)
    if rows and admitting is not None:
        rows = [r for r in rows if r["admitting"] == admitting]
    if not rows:
        return None
    return statistics.fmean(value(r) for r in rows)


def mean_count(obs, count):
    """Mean over the counted cycles of the record's `count` (a cycle
    that failed before its last phase has none and counts 0)."""
    return mean_of(obs, lambda r: float(r["record"].get(count, 0)))


def thread_cpu_ms(obs):
    """Processor time of the scheduler's thread a cycle: the program
    reads it four times a second and at every slow cycle, for the
    `cpu_cycles` cycles since the reading before, so the mean is the
    counted cycles' readings over the cycles they cover."""
    rows = [r["record"] for r in window_cycles(obs) or ()
            if float(r["record"].get("cpu_cycles", 0)) > 0]
    if not rows:
        return None
    return sum(float(r["thread_cpu_us"]) for r in rows) / 1e3 \
        / sum(float(r["cpu_cycles"]) for r in rows)
