"""Device time by the `jax.named_scope` an operation was traced under.

A device profile names an operation by its HLO text, which does not say
under which scope it was traced; the compiled module does, in each
instruction's `op_name` ("jit(step)/lfm2.moe.experts/dot_general").
The driver of a cell whose program names scopes writes the instruction
names of its step with their scopes beside the run's other files
(`write_scopes`, from `Executor.compiled_text`), and `share_of_busy`
joins them to the events of the profile's "XLA Ops" line, which it
reads with nothing but `jax.profiler.ProfileData`, as
`program_spans.load` reads the host's spans. Without that file, as on a
program that names no scope, every reader reads nothing.
"""
import functools
import glob
import json
import os
import re

from .trace import OPS_LINE, is_container, stable_name

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "benchchip")

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name="([^"]*)"', re.M)


def scopes_of(hlo_text, prefix):
    """{instruction name: scope} of every instruction of a compiled
    module whose `op_name` path holds a scope that starts with
    `prefix`; the outermost such scope. In a backward pass the path
    wraps it: "jit(step)/transpose(jvp(lfm2.moe.experts))/gather"."""
    scope = re.compile(r"(?:^|[/(])(%s[\w.\-]*)" % re.escape(prefix))
    out = {}
    for name, path in _INSTRUCTION.findall(hlo_text):
        found = scope.search(path)
        if found:
            out[name] = found.group(1)
    return out


def write_scopes(workload, hlo_text, prefix):
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, f"{workload}.scopes.json"),
              "w") as f:
        json.dump(scopes_of(hlo_text, prefix), f)


@functools.lru_cache(maxsize=1)
def scope_seconds(xplane_path):
    """{scope: seconds} of the first device's operations in a profile,
    by the scopes file of the workload whose trace directory holds it;
    operations that only hold others are left out, as in
    `trace.reduce`. Empty where the driver wrote no scopes."""
    trace_dir = xplane_path
    for _ in range(4):      # <dir>/plugins/profile/<time>/<file>
        trace_dir = os.path.dirname(trace_dir)
    path = os.path.join(OUT_DIR,
                        os.path.basename(trace_dir) + ".scopes.json")
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        scope_of = json.load(f)
    from jax.profiler import ProfileData

    seconds = {}
    for plane in ProfileData.from_file(xplane_path).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                if ev.duration_ns <= 0 \
                        or is_container(stable_name(ev.name)):
                    continue
                name = ev.name.lstrip("%").partition(" = ")[0]
                scope = scope_of.get(name)
                if scope:
                    seconds[scope] = seconds.get(scope, 0.0) \
                        + ev.duration_ns / 1e9
        if seconds:
            break
    return seconds


def share_of_busy(obs, prefix):
    """Percent of the traced window's busy time spent in operations
    under a scope that starts with `prefix`; None without a trace or
    where no operation carries such a scope."""
    if not obs.get("trace"):
        return None
    found = glob.glob(os.path.join(
        ROOT, ".benchchip_trace", "*", "plugins", "profile", "*",
        "*.xplane.pb"))
    if not found:
        return None
    under = sum(s for scope, s in scope_seconds(
        max(found, key=os.path.getmtime)).items()
        if scope.startswith(prefix))
    busy = obs["trace"]["busy_s"]
    return 100.0 * under / busy if under and busy else None
