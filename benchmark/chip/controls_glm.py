#!/usr/bin/env python3
"""Readings of the control and of the planted faults of the GLM-5.2
serving cell, at the cell's own size, for setting and checking the
limits of configs/glm-5.2-serve-ep16.json (PERF.md, section 2, lists
the readings each limit was set from). The benchmark's own runs never
call this; controls.py is its pattern.

    python3 benchmark/chip/controls_glm.py <config> <sample.npz> ..
                                           [--rehearse] [--first N]

For every sample a run saved (prompts, the rows it served, what its
probes held) the run's own comparison of what was served ("program"),
and of what the reference answers at the same positions
  control_fp8                   with every product's operands rounded
                                to 4 exponent and 3 mantissa bits, the
                                precision below the configuration's
                                bfloat16,
  fault_selection_left_out      attending every position (no indexer),
  fault_shared_selects_itself   a "shared" layer selecting for itself,
  fault_shared_expert_left_out  without the shared expert,
each through the comparison a run makes, printed with the `correct` it
comes to. One JSON line a sample. `--first N` holds only the first N
requests of each sample (a pass over 33k positions takes a minute).
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.chip.controls import _sizes, held  # noqa: E402
from benchmark.chip.drivers import glm_serve as D  # noqa: E402

VARIANTS = {"control_fp8": {"control": "fp8"},
            "fault_selection_left_out": {"fault": "selection_left_out"},
            "fault_shared_selects_itself":
                {"fault": "shared_selects_itself"},
            "fault_shared_expert_left_out":
                {"fault": "shared_expert_left_out"}}


def serve_controls(c, seed, sample, variants=VARIANTS):
    """{what: {number: value, "correct": bool}} of one sample."""
    refs = D.reference_of(c, seed, sample)
    out = {"program": held(D.hold_sample(
        c, D.check_sample(c, seed, sample, refs=refs)))}
    for what, how in variants.items():
        out[what] = held(D.hold_sample(
            c, D.check_sample(c, seed, sample, refs=refs, **how)))
    return out


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    rehearse = "--rehearse" in argv
    if rehearse:
        argv.remove("--rehearse")
    first = None
    if "--first" in argv:
        at = argv.index("--first")
        first = int(argv[at + 1])
        del argv[at:at + 2]
    if len(argv) < 2:
        raise SystemExit(__doc__)
    c = _sizes(argv[0], rehearse)
    for path in argv[1:]:
        seed, sample = D.load_sample(path)
        print(json.dumps({"sample": os.path.basename(path), "seed": seed,
                          **serve_controls(c, seed, sample[:first])}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
