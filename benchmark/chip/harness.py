"""What every cell's run shares: finding the cell's files by the names
BENCHMARK.json gives, the look for the chip, the clock, the readers of
the per-layer metrics, and the result line."""
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def process_start_age_s():
    """Seconds since this process was started, from /proc (Linux): the
    set-up time counts the interpreter's start and the imports too."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


def load_manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(manifest, workload):
    for cell in manifest["workloads"]:
        if cell["name"] == workload:
            return cell
    raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; it "
                     f"has {[c['name'] for c in manifest['workloads']]}")


def load_config(manifest, name):
    for cfg in manifest["configs"]:
        if cfg["name"] == name:
            with open(os.path.join(ROOT, cfg["file"])) as f:
                return json.load(f)
    raise SystemExit(f"no configuration {name!r} in BENCHMARK.json")


def cell_metrics(manifest, cell, group):
    """The metrics of `group` ("end_to_end" or "per_layer") that this
    cell reports. A metric with a `workloads` key belongs to the cells
    it lists; an end-to-end metric without one to every cell; a
    per-layer metric without one to every cell that reports the metric
    it moves."""
    e2e = [m for m in manifest["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if group == "end_to_end":
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def load_reader(name):
    """The reader of one per-layer metric: metrics/<name>.py, found by
    the metric's name."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "benchchip_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"per-layer metric {name!r} has no "
                                f"reader at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def require_devices(chips, rehearse):
    """The devices this cell runs on. Without --rehearse anything but
    `chips` TPU chips ends the run with no result."""
    import jax

    devs = jax.devices()
    if rehearse:
        if len(devs) < chips:
            raise SystemExit(f"rehearsal of a {chips}-chip cell needs "
                             f"{chips} devices, JAX found {len(devs)}")
        return devs[:chips]
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"benchmark: JAX found platform {devs[0].platform!r}, not "
            f"'tpu' (JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})"
            f"; only --rehearse may run elsewhere")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, "
                         f"JAX found {len(devs)}")
    return devs[:chips]


def memory_peak_bytes(devices):
    """Peak bytes on the fullest of `devices`, read once the window has
    closed and before anything is freed. The runtime counts buffers
    (weights, state, feeds: `bytes_in_use`, `peak_bytes_in_use`) apart
    from the scratch it reserves for a running program
    (`peak_bytes_reserved`), which is most of a training step's
    footprint; a step holds both, so the peak is the larger of the
    buffers' own peak and the buffers now in use plus the largest
    scratch. None where the backend reports no memory, as the CPU's
    does not."""
    peaks = []
    for d in devices:
        s = d.memory_stats() or {}
        if "peak_bytes_in_use" not in s:
            return None
        peaks.append(max(int(s["peak_bytes_in_use"]),
                         int(s["bytes_in_use"])
                         + int(s.get("peak_bytes_reserved", 0))))
    return max(peaks)


class Clock:
    """The host clock of a run; `setup_s()` is the time from the
    process's start to now."""

    def __init__(self, t_import):
        """`t_import`: `time.monotonic()` as early as the entry point
        could read it."""
        self.t_import = t_import
        age = process_start_age_s()
        self.before_import_s = 0.0 if age is None else max(
            0.0, age - (time.monotonic() - t_import))

    def setup_s(self):
        return self.before_import_s + (time.monotonic() - self.t_import)


def emit(result, compared):
    """The numbers compared as the last lines of standard error, and
    the result as the last line of standard output, `compared` last in
    it."""
    for line in compared.lines():
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps({**result, "compared": compared.rows}), flush=True)
