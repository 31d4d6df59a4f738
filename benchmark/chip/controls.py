#!/usr/bin/env python3
"""Readings of the controls and of the planted faults, at a cell's own
size, for setting and checking the limits of configs/*.json (PERF.md,
section 2, lists the readings each limit was set from). The benchmark's
own runs never call this.

    python3 benchmark/chip/controls.py train <config> <seed> [<seed> ..]
    python3 benchmark/chip/controls.py serve <config> <sample.npz> ..

train: for every seed the reference follows the first three steps at
"highest", and is then held to: itself in the precision below the
configuration's ("fp8": the control), and itself with part of every
batch left out, the mean taken over the rest (a half: the planted
fault).
serve: for every sample a run saved (prompts and the rows it served),
the run's own comparison of the served tokens, and of the tokens the
lower precisions ("bf16": the control; "fp8") put first at the same
positions. Every reading goes through the comparison a run makes and
is printed with the `correct` it comes to. One JSON line a seed or
sample.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402

from benchmark.chip import harness, traffic  # noqa: E402
from benchmark.chip.drivers import serve, train  # noqa: E402


def _sizes(config_name, rehearse):
    with open(os.path.join(harness.HERE, "configs",
                           f"{config_name}.json")) as f:
        config = json.load(f)
    sizes = dict(config["sizes"])
    if rehearse:
        sizes.update(config["rehearsal"])
    return sizes


def held(compared):
    """{number: value} and `correct` of a comparison, for one line."""
    return {**{r["name"]: r["value"] for r in compared.rows},
            "correct": compared.correct}


def train_controls(c, seed, spec):
    """{what: {number: value, "correct": bool}} of one seed."""
    feeds = traffic.train_batches(
        seed, {**spec, "pool_batches": train.FOLLOWED_STEPS}, c,
        train.START_ID)
    want = train.reference_readings(c, seed, feeds)

    def against(got):
        return held(train.compare_readings(got, want, c["limits"]))
    return {
        "control_fp8": against(
            train.reference_readings(c, seed, feeds, "fp8")),
        "fault_half_batch": against(train.reference_readings(
            c, seed, feeds, rows=c["batch"] // 2))}


def serve_controls(c, seed, sample):
    """{what: {number: value, "correct": bool}}: the sample as it was
    served, and as each lower precision would have answered at the same
    positions, through the run's own comparison."""
    out = {"program": held(serve.hold_sample(
        c, serve.check_sample(c, seed, sample)))}
    for precision in ("bf16", "fp8"):
        out[f"control_{precision}"] = held(serve.hold_sample(
            c, serve.check_sample(c, seed, sample, control=precision)))
    return out


def load_sample(path):
    with np.load(path) as z:
        return int(z["seed"]), [
            (p, r, list(serve.served_rows(r)[1]))
            for p, r in zip(z["prompts"], z["rows"])]


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    rehearse = "--rehearse" in argv
    if rehearse:
        argv.remove("--rehearse")
    if len(argv) < 2:
        raise SystemExit(__doc__)
    kind, config_name, rest = argv[0], argv[1], argv[2:]
    c = _sizes(config_name, rehearse)
    if kind == "train":
        spec = traffic.load("fresh_batches")
        for seed in map(int, rest):
            print(json.dumps({"seed": seed,
                              **train_controls(c, seed, spec)}),
                  flush=True)
    elif kind == "serve":
        for path in rest:
            seed, sample = load_sample(path)
            print(json.dumps({
                "sample": os.path.basename(path), "seed": seed,
                **serve_controls(c, seed, sample)}), flush=True)
    else:
        raise SystemExit(__doc__)
    return 0


if __name__ == "__main__":
    sys.exit(main())
