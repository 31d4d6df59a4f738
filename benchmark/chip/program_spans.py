"""The program's own spans in the profiler's trace, by what the device
was doing under them.

`paddle_tpu/observability/tracing.py` puts every program span into a
JAX profile as a host event named "paddle_tpu:<name>", on the clock of
the device's operations. `load` reads those events and the first
device's busy intervals out of the `.xplane.pb` (with nothing but
`jax.profiler.ProfileData`, like `trace.load`); `table` says, for every
span name, how much of the device's idle time passed under it. The
readers under `metrics/` (`idle_ms.*`, `queue_wait_ms_p50`,
`idle_attributed_share.*`) take their numbers from `window_table`. A
program without such spans, as before PR 25, gives an empty table and
every reader reads nothing.
"""
import functools
import glob
import gzip
import json
import os

from .trace import OPS_LINE, _union

SPAN_PREFIX = "paddle_tpu:"
ADMIT_SPAN = "slotpool.admit"
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _overlap(a, b):
    """Length of the intersection of two lists of sorted, disjoint
    intervals."""
    total = i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def load(path):
    """{"busy": merged [start_ns, end_ns] intervals in which an
    operation ran on the first device (its "XLA Ops" line, as
    `trace.reduce` takes busy time), "spans": [[name without the
    prefix, thread, start_ns, duration_ns, metadata], ...] of every
    host event named "paddle_tpu:..."}. A `.json.gz` written by `dump`
    loads the same way."""
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            return json.load(f)
    from jax.profiler import ProfileData

    busy, spans = None, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            if busy is None:
                busy = _union(
                    [int(ev.start_ns), int(ev.start_ns + ev.duration_ns)]
                    for line in plane.lines if line.name == OPS_LINE
                    for ev in line.events if ev.duration_ns > 0)
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{plane.name}#{i}"
            for ev in line.events:
                name = ev.name
                if name.startswith(SPAN_PREFIX):
                    spans.append([
                        name[len(SPAN_PREFIX):], thread,
                        int(ev.start_ns), int(ev.duration_ns),
                        dict(ev.stats)])
    return {"busy": busy or [], "spans": spans}


def dump(loaded, path):
    with gzip.open(path, "wt") as f:
        json.dump(loaded, f)


def table(loaded):
    """Numbers of the traced window, which runs from the first
    operation's start to the last one's end as `trace.reduce`'s does.
    None where no operation ran. Otherwise
      window_ms, idle_ms     the window, and the part of it in which
                             no operation ran
      attributed_idle_ms     idle time under any program span
      spans                  {name: {"events", "ms": summed length
                             inside the window, "idle_ms": idle time
                             under that name's events}}; events of one
                             name are merged first, so a name that
                             runs on two threads at once counts an
                             idle moment once
      queue_wait_us          `wait_us` of the window's admissions
    """
    busy = loaded["busy"]
    if not busy:
        return None
    w0, w1 = busy[0][0], busy[-1][1]
    idle = [[a[1], b[0]] for a, b in zip(busy, busy[1:])]
    by_name, waits = {}, []
    for name, _thread, start, dur, meta in loaded["spans"]:
        s, e = max(start, w0), min(start + dur, w1)
        if e < s:
            continue
        by_name.setdefault(name, []).append([s, e])
        if name == ADMIT_SPAN and "wait_us" in meta:
            waits.append(float(meta["wait_us"]))
    rows, everything = {}, []
    for name, intervals in by_name.items():
        merged = _union(intervals)
        everything += merged
        rows[name] = {
            "events": len(intervals),
            "ms": sum(e - s for s, e in intervals) / 1e6,
            "idle_ms": _overlap(merged, idle) / 1e6}
    return {"window_ms": (w1 - w0) / 1e6,
            "idle_ms": sum(e - s for s, e in idle) / 1e6,
            "attributed_idle_ms": _overlap(_union(everything),
                                           idle) / 1e6,
            "spans": rows, "queue_wait_us": waits}


@functools.lru_cache(maxsize=1)
def _table_of(xplane_path):
    """`table` of one profile, read once a process; the whole table is
    left beside the run's other files as
    chiprun_out/benchchip/<trace directory's name>.spans.json."""
    made = table(load(xplane_path))
    trace_dir = xplane_path
    for _ in range(4):      # <dir>/plugins/profile/<time>/<file>
        trace_dir = os.path.dirname(trace_dir)
    out_dir = os.path.join(ROOT, "chiprun_out", "benchchip")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(
            out_dir, os.path.basename(trace_dir) + ".spans.json"),
            "w") as f:
        json.dump(made, f)
    return made


def window_table(obs):
    """`table` of this run's traced window, or None where the run took
    no trace (a rehearsal, the CPU). `obs` carries no path: the
    harness's `Tracer` empties its directory at every start, so the
    newest profile under .benchchip_trace/ is this run's."""
    if obs.get("trace") is None:
        return None
    found = glob.glob(os.path.join(
        ROOT, ".benchchip_trace", "*", "plugins", "profile", "*",
        "*.xplane.pb"))
    if not found:
        return None
    return _table_of(max(found, key=os.path.getmtime))


def idle_ms_per(obs, span, counter):
    """Idle milliseconds under `span` for each of the window's
    `obs["counters"][counter]` (steps, dispatches); None where the
    trace has no such span."""
    made, n = window_table(obs), obs["counters"].get(counter)
    if not made or not n or span not in made["spans"]:
        return None
    return made["spans"][span]["idle_ms"] / n


def idle_attributed_share(obs):
    """Percent of the window's idle time that passed under any program
    span; None where the program records none."""
    made = window_table(obs)
    if not made or not made["spans"] or not made["idle_ms"]:
        return None
    return 100.0 * made["attributed_idle_ms"] / made["idle_ms"]
