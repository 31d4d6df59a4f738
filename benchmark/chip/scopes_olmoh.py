"""Device time by `jax.named_scope` for the Olmo-Hybrid serve cell,
whose scopes nest (a prefill chunk's linear-attention mixers run under
`olmoh.prefill_chunk/olmoh.gdn_chunk`): as scopes_nemotron.py keeps
every `nemotronh.` scope on an instruction's path, this keeps every
`olmoh.` scope, so that an operation counts under each once. The file
it writes and the way instructions are known (name and result type,
over all the modules the driver wrote) are scopes_glm.py's. Without
the file, as on a parent whose program names no such scope, every
reader reads nothing."""
import functools
import glob
import json
import os
import re

from .device_scopes import OUT_DIR, ROOT
from .scopes_glm import _LINE, _key
from .trace import OPS_LINE, is_container, stable_name

PREFIX = "olmoh."
CHUNK = "olmoh.prefill_chunk"
SUFFIX = ".olmoh_scopes.json"

_SCOPES = re.compile(r"(?:^|[/(])(%s[\w.\-]*)" % re.escape(PREFIX))


def scopes_of(hlo_texts):
    """{instruction key: its `olmoh.` scopes, outermost first,
    joined by a space}; "?" where two modules disagree."""
    out, clash = {}, set()
    for text in hlo_texts:
        for line, path in _LINE.findall(text):
            key = _key(line)
            scopes = " ".join(dict.fromkeys(_SCOPES.findall(path)))
            if out.setdefault(key, scopes) != scopes:
                clash.add(key)
    return {k: ("?" if k in clash else v) for k, v in out.items()
            if v or k in clash}


def write_scopes(workload, hlo_texts):
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, workload + SUFFIX), "w") as f:
        json.dump(scopes_of(hlo_texts), f)


@functools.lru_cache(maxsize=1)
def _seconds(xplane_path):
    trace_dir = xplane_path
    for _ in range(4):      # <dir>/plugins/profile/<time>/<file>
        trace_dir = os.path.dirname(trace_dir)
    path = os.path.join(OUT_DIR, os.path.basename(trace_dir) + SUFFIX)
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
                scopes = scope_of.get(_key(ev.name))
                if scopes:
                    seconds[scopes] = seconds.get(scopes, 0.0) \
                        + ev.duration_ns / 1e9
        if seconds:
            break
    return seconds


def scope_seconds(obs):
    """{scopes of an operation's path: seconds} of the traced window's
    operations on the first device; empty without a trace or a scopes
    file."""
    if not obs.get("trace"):
        return {}
    found = glob.glob(os.path.join(
        ROOT, ".benchchip_trace", "*", "plugins", "profile", "*",
        "*.xplane.pb"))
    if not found:
        return {}
    return _seconds(max(found, key=os.path.getmtime))


def under(obs, *names):
    """Seconds of the operations whose path holds one of the scopes
    `names` or a scope below one (`<name>.<more>`), each operation
    once."""
    below = tuple(n + "." for n in names)
    return sum(s for scopes, s in scope_seconds(obs).items()
               if any(one in names or one.startswith(below)
                      for one in scopes.split()))


def tick_seconds(obs):
    """The traced window's busy time less what ran under a prefill
    chunk: the decode ticks' (and the admissions' few operations)."""
    tr = obs.get("trace")
    if not tr or not tr.get("busy_s") or not scope_seconds(obs):
        return None
    return tr["busy_s"] - under(obs, CHUNK)
