"""The profiler's trace, taken and reduced to numbers.

`Tracer` starts and stops JAX's profiler around a window and marks
host spans in it (`jax.profiler.TraceAnnotation`, names prefixed
"bench:"), so that the device's operations and what the host was doing
lie on one clock. `load` reads the `.xplane.pb` with nothing but JAX
(`jax.profiler.ProfileData`) into plain lists, and `reduce` turns those
into busy time, kernel times and the idle gaps by what the host was
doing. `reduce` never sees JAX, so a test feeds it a
small recorded trace.
"""
import contextlib
import glob
import gzip
import json
import os
import re
import shutil

SPAN_PREFIX = "bench:"
OPS_LINE = "XLA Ops"


class Tracer:
    """Profiles one window into `directory` (emptied first)."""

    def __init__(self, directory):
        self.directory = directory
        self.on = False

    def start(self):
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        jax.profiler.start_trace(self.directory)
        self.on = True

    def stop(self):
        import jax

        if self.on:
            jax.profiler.stop_trace()
            self.on = False

    def span(self, name):
        """A host span in the trace while tracing, nothing otherwise."""
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)

    def xplane_path(self):
        found = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(
                f"the profiler left no .xplane.pb under {self.directory}")
        return found[-1]


def load(path):
    """[{"name": plane, "lines": [{"name": line, "events":
    [[name, start_ns, duration_ns], ...]}]}] of the device planes'
    operation lines (events under their stable names; the plane's
    "signatures" keep one whole HLO text a name) and of the host's
    "bench:" spans. A `.json.gz` written by `dump` loads the same
    way."""
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            return json.load(f)
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        lines, signatures, short = [], {}, {}
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            events = []
            for ev in line.events:
                name = ev.name
                if device:
                    # the device names an operation by its whole HLO
                    # text: keep the stable name, and the text once
                    key = short.get(name)
                    if key is None:
                        key = short[name] = stable_name(name)
                        signatures.setdefault(key, name)
                    name = key
                elif not name.startswith(SPAN_PREFIX):
                    continue
                events.append([name, int(ev.start_ns),
                               int(ev.duration_ns)])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines,
                           **({"signatures": signatures}
                              if device else {})})
    return planes


def dump(planes, path):
    with gzip.open(path, "wt") as f:
        json.dump(planes, f)


_SHAPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")
# operations that only hold others (their children are events too)
CONTAINERS = ("while", "conditional", "call")


def stable_name(op_name):
    """A short name that survives a recompile. The device line names
    an operation by its whole HLO text, "%fusion.12 = bf16[8,64]{..}
    fusion(..)": the result keeps the name without the counter XLA
    appends and the first result's type and shape,
    "fusion_bf16_8_64". A bare name ("%fusion.123", a kernel's own
    name) just loses the counter."""
    head, _, rest = op_name.lstrip("%").partition(" = ")
    base = re.sub(r"(\.\d+)+$", "", head) or head
    m = _SHAPE.search(rest)
    if m:
        dims = m.group(2).replace(",", "_")
        base = f"{base}_{m.group(1)}" + (f"_{dims}" if dims else "")
    return base[:64]


def is_container(stable):
    return stable.split("_")[0] in CONTAINERS


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def reduce(planes, window_ns=None, top=10, unattributed="no_span"):
    """Numbers of one traced window.

    Returns a dict with
      window_s      the traced window: `window_ns` (start, end) if
                    given, else first operation start to last end
      busy_s        union of the operations' intervals, averaged over
                    the device planes that ran any
      devices       how many device planes ran an operation
      op_s          {stable name: summed seconds}, averaged likewise;
                    operations that only hold others (a while loop)
                    are left out, their children are counted
      op_count      {stable name: events}, summed over devices
      device_ops    top operations [[name, seconds], ...]
      idle_gaps     longest gaps on the first device by the host span
                    that covers most of each (`unattributed` where none
                    covers half), [[name, seconds], ...]
      host_span_s   {span name: summed seconds}
      signatures    {stable name: one whole HLO text}, as loaded
    """
    dev = [p for p in planes if p["name"].startswith("/device:")]
    host_spans = [ev for p in planes if not p["name"].startswith(
        "/device:") for ln in p["lines"] for ev in ln["events"]
        if ev[0].startswith(SPAN_PREFIX)]
    per_dev = []
    for p in dev:
        events = [ev for ln in p["lines"] for ev in ln["events"]
                  if ev[2] > 0]
        if window_ns is not None:
            events = [ev for ev in events
                      if ev[1] + ev[2] > window_ns[0]
                      and ev[1] < window_ns[1]]
        if events:
            per_dev.append(events)
    if not per_dev:
        return {"devices": 0, "busy_s": 0.0, "window_s": 0.0}
    n = len(per_dev)
    if window_ns is None:
        window_ns = (min(ev[1] for evs in per_dev for ev in evs),
                     max(ev[1] + ev[2] for evs in per_dev for ev in evs))
    busy = 0
    op_ns, op_count = {}, {}
    for events in per_dev:
        merged = _union([ev[1], ev[1] + ev[2]] for ev in events)
        busy += sum(b - a for a, b in merged)
        for name, _start, dur in events:
            key = stable_name(name)
            if is_container(key):
                continue    # its time is its children's, counted below
            op_ns[key] = op_ns.get(key, 0) + dur
            op_count[key] = op_count.get(key, 0) + 1
    first = _union([ev[1], ev[1] + ev[2]] for ev in per_dev[0])
    gaps = []
    edges = [window_ns[0]] + [t for ab in first for t in ab] \
        + [window_ns[1]]
    for i in range(0, len(edges), 2):
        s, e = edges[i], edges[i + 1]
        if e > s:
            gaps.append((e - s, s, e))
    gaps.sort(reverse=True)
    spans_sorted = sorted(host_spans, key=lambda ev: ev[1])
    idle = []
    for length, s, e in gaps[:top]:
        best, cover = unattributed, 0
        for name, start, dur in spans_sorted:
            if start >= e:
                break
            c = min(e, start + dur) - max(s, start)
            # a span names a gap only if it covers half of it; the
            # innermost span wins a tie: it starts later
            if 2 * c >= length and c >= cover:
                best, cover = name[len(SPAN_PREFIX):], c
        idle.append([best, length / 1e9])
    span_s = {}
    for name, _start, dur in host_spans:
        key = name[len(SPAN_PREFIX):]
        span_s[key] = span_s.get(key, 0.0) + dur / 1e9
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])
    signatures = {}
    for p in dev:
        for key, text in p.get("signatures", {}).items():
            signatures.setdefault(key, text)
    return {
        "devices": n,
        "signatures": signatures,
        "window_s": (window_ns[1] - window_ns[0]) / 1e9,
        "busy_s": busy / n / 1e9,
        "op_s": {k: v / n / 1e9 for k, v in op_ns.items()},
        "op_count": op_count,
        "device_ops": [[k, v / n / 1e9] for k, v in ops[:top]],
        "idle_gaps": idle,
        "host_span_s": span_s,
    }
