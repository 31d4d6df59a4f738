#!/usr/bin/env python3
"""Readings of the control and of the planted faults of the LFM2-MoE
training cell, at the cell's own size, for setting and checking the
limits of configs/lfm2-24b-a2b-train-ep8.json (PERF.md, section 2,
lists the readings each limit was set from). The benchmark's own runs
never call this; controls.py is its pattern.

    python3 benchmark/chip/controls_lfm2.py <config> <seed> [<seed> ..]
                                            [--rehearse]

For every seed the reference follows the first three steps at
"highest", and is then held to itself
  control_fp8             with every product's operands rounded to fp8,
                          the precision below the configuration's
                          bfloat16,
  fault_experts_left_out  with the held experts' contribution left out,
  fault_softmax_router    with softmax scores and no bias in the router,
  fault_conv_looks_ahead  with the convolution's window one position
                          ahead,
each through the comparison a run makes, printed with the `correct` it
comes to. One JSON line a seed.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.chip import traffic  # noqa: E402
from benchmark.chip.controls import _sizes, held  # noqa: E402
from benchmark.chip.drivers import lfm2_train as D  # noqa: E402

VARIANTS = {"control_fp8": {"precision": "fp8"},
            "fault_experts_left_out": {"fault": "experts_left_out"},
            "fault_softmax_router": {"fault": "softmax_router"},
            "fault_conv_looks_ahead": {"fault": "conv_looks_ahead"}}


def train_controls(c, seed, spec, variants=VARIANTS):
    """{what: {number: value, "correct": bool}} of one seed."""
    feeds = traffic.train_batches(
        seed, {**spec, "pool_batches": D.FOLLOWED_STEPS}, c, D.START_ID)
    want = D.reference_readings(c, seed, feeds)
    return {what: held(D.compare_readings(
        D.reference_readings(c, seed, feeds, **how), want, c["limits"]))
        for what, how in variants.items()}


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    rehearse = "--rehearse" in argv
    if rehearse:
        argv.remove("--rehearse")
    if len(argv) < 2:
        raise SystemExit(__doc__)
    c = _sizes(argv[0], rehearse)
    spec = traffic.load("fresh_batches")
    for seed in map(int, argv[1:]):
        print(json.dumps({"seed": seed, **train_controls(c, seed, spec)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
