"""Device time by `jax.named_scope` for a cell that runs several
compiled programs (the GLM serve cell: a tick-only program and one a
prefill chunk size). device_scopes.py joins a profile's events to one
module's instruction names; two modules number their instructions
alike, so here an instruction is known by its name AND its result's
type and shape (trace.stable_name of its line), over all the modules
the driver wrote. An instruction that two modules put under different
scopes is left out and counted in "ambiguous". Without the file, as on
a program that names no such scope, every reader reads nothing."""
import functools
import glob
import json
import os
import re

from .device_scopes import OUT_DIR, ROOT
from .trace import OPS_LINE, is_container, stable_name

PREFIX = "glm."

_LINE = re.compile(r'^\s*(?:ROOT\s+)?(%?[\w.\-]+ = .*?)op_name="([^"]*)"',
                   re.M)
_SCOPE = re.compile(r"(?:^|[/(])(%s[\w.\-]*)" % re.escape(PREFIX))


def _key(text):
    name = text.lstrip("%").partition(" = ")[0]
    return f"{name}|{stable_name(text)}"


def scopes_of(hlo_texts):
    """{instruction key: outermost `glm.` scope} over the modules."""
    out, clash = {}, set()
    for text in hlo_texts:
        for line, path in _LINE.findall(text):
            found = _SCOPE.search(path)
            key = _key(line)
            scope = found.group(1) if found else ""
            if out.setdefault(key, scope) != scope:
                clash.add(key)
    return {k: ("?" if k in clash else v) for k, v in out.items()
            if v or k in clash}


def write_scopes(workload, hlo_texts):
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, f"{workload}.glm_scopes.json"),
              "w") as f:
        json.dump(scopes_of(hlo_texts), f)


@functools.lru_cache(maxsize=1)
def _seconds(xplane_path):
    trace_dir = xplane_path
    for _ in range(4):      # <dir>/plugins/profile/<time>/<file>
        trace_dir = os.path.dirname(trace_dir)
    path = os.path.join(OUT_DIR, os.path.basename(trace_dir)
                        + ".glm_scopes.json")
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
                scope = scope_of.get(_key(ev.name))
                if scope:
                    seconds[scope] = seconds.get(scope, 0.0) \
                        + ev.duration_ns / 1e9
        if seconds:
            break
    return seconds


def scope_seconds(obs):
    """{scope: seconds} of the traced window's operations on the first
    device ("?" holds what two modules name alike under different
    scopes); empty without a trace or a scopes file."""
    if not obs.get("trace"):
        return {}
    found = glob.glob(os.path.join(
        ROOT, ".benchchip_trace", "*", "plugins", "profile", "*",
        "*.xplane.pb"))
    if not found:
        return {}
    return _seconds(max(found, key=os.path.getmtime))


def under(obs, *prefixes):
    """Seconds under the scopes that start with any of `prefixes`."""
    return sum(s for scope, s in scope_seconds(obs).items()
               if scope.startswith(prefixes))


def tick_seconds(obs):
    """The traced window's busy time less what ran under a prefill
    chunk: the decode ticks' (and the admissions' few operations)."""
    tr = obs.get("trace")
    if not tr or not tr.get("busy_s"):
        return None
    return tr["busy_s"] - under(obs, "glm.prefill_chunk")
