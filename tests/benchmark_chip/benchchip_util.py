"""Shared by the tests that drive benchmark/chip/run.py: every run is a
process of its own, as the driver's are (a run switches the Pallas
kernels to interpret mode and JAX's persistent cache on, which must not
leak into the worker's other tests)."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(REPO, "benchmark", "chip", "run.py")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def python(args, cwd=REPO, devices=8, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True,
                          timeout=timeout)


def cell_args(workload, trace, seed=2 ** 31 + 17, seconds=1):
    return ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]


def result_line(stdout):
    """The contract's last line, or None when no result was printed."""
    lines = stdout.strip().splitlines()
    if not lines:
        return None
    try:
        res = json.loads(lines[-1])
    except ValueError:
        return None
    return res if RESULT_KEYS <= set(res) else None


def planted(plant, workload, trace=0):
    """Run a rehearsal with `plant` (python source that may use `run`,
    `train`, `serve`) executed before it: the timed path broken
    underneath, the rest of the run as it is."""
    code = ("import sys; sys.path.insert(0, %r)\n"
            "from benchmark.chip import run\n"
            "from benchmark.chip.drivers import train, serve\n"
            "%s\n"
            "sys.exit(run.main(%r))"
            % (REPO, plant, cell_args(workload, trace) + ["--rehearse"]))
    return python(["-c", code])
