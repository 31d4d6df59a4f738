#!/usr/bin/env python3
"""Readings of the controls and of the planted faults of the
Nemotron-3-Super serving cell, at the cell's own size, for setting and
checking the limits of configs/nemotron-3-super-serve-ep4.json
(PERF.md, section 2, lists the readings each limit was set from). The
benchmark's own runs never call this; controls_glm.py is its pattern.

    python3 benchmark/chip/controls_nemotron.py <config> <sample.npz> ..
                                                [--rehearse] [--first N]

For every sample a run saved (prompts, the rows it served, what its
probes held) the run's own comparison of what was served ("program"),
and of what the reference answers at the same positions
  control_low                   one precision down on both counts:
                                every product's operands rounded to 4
                                exponent and 3 mantissa bits (below the
                                configuration's bfloat16) and the scan
                                state rounded to bfloat16 after every
                                position (below its float32),
  control_fp8                   the operands alone,
  control_state_bf16            the state alone (beside bfloat16
                                weights the logits hide it and the
                                state the probes' lanes kept shows it:
                                PERF.md section 2),
  fault_state_not_reset         every state-space layer starting from
                                what the same sequence left behind,
  fault_shared_expert_left_out  without the shared expert,
  fault_latent_up_left_out      the routed part not projected up,
  fault_padded_advance          the padding of every prefill chunk
                                advancing the state and entering the
                                convolution tail,
each through the comparison a run makes, printed with the `correct` it
comes to and with `state_gap` (the scan state's gap to the reference's
in the first state-space layer and in the worst, which no limit holds).
One JSON line a sample. `--first N` holds only the first N
requests of each sample.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.chip.controls import _sizes, held  # noqa: E402
from benchmark.chip.drivers import nemotron_serve as D  # noqa: E402

VARIANTS = {"control_low": {"control": "fp8", "fault": "state_bf16"},
            "control_fp8": {"control": "fp8"},
            "control_state_bf16": {"fault": "state_bf16"},
            "fault_state_not_reset": {"fault": "state_not_reset"},
            "fault_shared_expert_left_out":
                {"fault": "shared_expert_left_out"},
            "fault_latent_up_left_out": {"fault": "latent_up_left_out"},
            "fault_padded_advance": {"fault": D.PADDED_ADVANCE}}


def serve_controls(c, seed, sample, variants=VARIANTS):
    """{what: {number: value, "correct": bool}} of one sample."""
    refs = D.reference_of(c, seed, sample)
    out = {}
    for what, how in {"program": {}, **variants}.items():
        read = D.check_sample(c, seed, sample, refs=refs, **how)
        out[what] = {**held(D.hold_sample(c, read)),
                     "state_gap": read["state_gap"]}
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
