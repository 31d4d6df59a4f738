#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, in this process.

    python3 benchmark/chip/run.py --workload <name> --seed <n>
                                  --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, driver, reference and per-layer
readers are found by the names BENCHMARK.json gives (PERF.md, "How a
later PR adds a cell"). The last line of standard output is the result;
without a TPU (or with fewer chips than the cell asks for) the run ends
with another code than 0 and prints none. `--rehearse` runs the same
code at the configuration's rehearsal sizes on whatever backend JAX
finds, for the tests: its line carries counts only.
"""
import os
import sys
import time

_T_IMPORT = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402

from benchmark.chip import harness, trace, traffic  # noqa: E402
from benchmark.chip.meter import CompileMeter  # noqa: E402
from benchmark.chip.peaks import peak  # noqa: E402

TRACE_SECONDS = 6.0


class Context:
    """What a driver is handed and what it leaves for the readers."""

    def __init__(self, args, cell, config, spec, clock, meter, devices):
        self.clock, self.meter, self.devices = clock, meter, devices
        self.workload, self.seed = args.workload, args.seed
        self.seconds, self.trace = args.seconds, bool(args.trace)
        self.rehearse = args.rehearse
        # a rehearsal reads counts only: it does not start the profiler
        self.profile = self.trace and not args.rehearse
        self.trace_seconds = min(TRACE_SECONDS, args.seconds)
        self.cell, self.traffic = cell, spec
        self.sizes = dict(config["sizes"])
        if args.rehearse:
            self.sizes.update(config["rehearsal"])
            self.traffic = {**spec, **spec.get("rehearsal", {})}
        self.counters = {}
        self.notes = {}
        self.memory_peak = None
        self.out_dir = os.path.join(harness.ROOT, "chiprun_out",
                                    "benchchip")
        self.tracer = trace.Tracer(os.path.join(
            harness.ROOT, ".benchchip_trace", args.workload))

    def note(self, **facts):
        self.notes.update(facts)

    def write_times(self, record):
        os.makedirs(self.out_dir, exist_ok=True)
        path = os.path.join(
            self.out_dir, f"{self.workload}.seed{self.seed}."
            f"trace{int(self.trace)}.times.json")
        with open(path, "w") as f:
            json.dump(record, f)

    def write_sample(self, **arrays):
        """What the reference checked, for controls.py to read."""
        import numpy as np

        os.makedirs(self.out_dir, exist_ok=True)
        np.savez(os.path.join(
            self.out_dir, f"{self.workload}.seed{self.seed}."
            f"trace{int(self.trace)}.sample.npz"), **arrays)

    def read_memory_peak(self):
        return harness.memory_peak_bytes(self.devices)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="rehearsal sizes, any backend, counts only")
    args = ap.parse_args(argv)

    clock = harness.Clock(_T_IMPORT)
    manifest = harness.load_manifest()
    cell = harness.find_cell(manifest, args.workload)
    config = harness.load_config(manifest, cell["config"])
    spec = traffic.load(cell["traffic"])

    from paddle_tpu.core.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    meter = CompileMeter()
    devices = harness.require_devices(cell["chips"], args.rehearse)
    ctx = Context(args, cell, config, spec, clock, meter, devices)
    dev = devices[0]
    on_chip = dev.platform == "tpu"

    driver = importlib.import_module(
        f"benchmark.chip.drivers.{config['driver']}")
    out = driver.run(ctx)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(ctx.devices),
              "memory_peak_bytes": ctx.memory_peak}
    obs = {"sizes": ctx.sizes, "counters": ctx.counters,
           "end_to_end": out["end_to_end"], "observed": out["observed"],
           "on_chip": on_chip, "trace": None,
           "peaks": peak(dev.device_kind) if on_chip else None}
    result = {"correct": out["compared"].correct,
              "attempted": out["attempted"], "failed": out["failed"]}
    group = "per_layer" if ctx.trace else "end_to_end"
    wanted = harness.cell_metrics(manifest, cell, group)
    metrics = {}
    if ctx.trace:
        if on_chip:
            reduced = trace.reduce(
                trace.load(ctx.tracer.xplane_path()),
                unattributed=out.get("unattributed", "no_span"))
            if not reduced["devices"]:
                raise SystemExit("benchmark: the traced window holds "
                                 "no operation on a device")
            obs["trace"] = reduced
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {
                "device_ops": reduced["device_ops"],
                "idle_gaps": reduced["idle_gaps"]}
        for m in wanted:
            value = harness.load_reader(m["name"]).read(obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in wanted:
            # a rehearsal's clock readings are not device numbers
            if on_chip:
                metrics[m["name"]] = {
                    "value": out["end_to_end"][m["name"]],
                    "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    print(json.dumps({"notes": ctx.notes, "counters": ctx.counters}),
          flush=True)
    harness.emit(result, out["compared"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
