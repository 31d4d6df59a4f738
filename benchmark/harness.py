"""Shared bench scaffolding for bench.py's measured configs.

The seven serving/training configs (serving, coldstart, generation,
paged, speculative, multitenant, and the `_time_loop` training suite)
accreted one copy each of the same two disciplines, both grown from
measured incidents on a 2-core CPU-share-throttled host (PERF.md):

* **interleaved best-of-N** — single-pass walls swing ~3x with the
  host's multi-second throttle windows, so competing legs must
  ALTERNATE (adjacent legs share a window) and ratios must be the
  best PAIRED ones, never a ratio of global bests (one leg's lucky
  window vs another's throttled one reports 2x-off);
* **telemetry snapshots** — every BENCH_SELF_*.json carries the r12
  `telemetry` key (metrics exposition + runtime stats + flight
  summary) so future rounds read counter context next to the
  headline number.

This module is that scaffolding ONCE. It changes no measured
semantics: call orders, leg interleavings, and best-of selections are
the ones the configs already used — `write_bench_self` additionally
asserts the emitted record keeps the SAME top-level schema as the
committed BENCH_SELF file it replaces, so a refactor that silently
drops a recorded field fails loudly.

Reference counterpart: reference benchmark/fluid/fluid_benchmark.py
is the per-model harness; a cross-config measurement-discipline layer
has no reference analogue (single-tenant, dedicated-host era).
"""
from __future__ import annotations

import json
import os
from typing import Callable, Dict, List, Sequence, Tuple

__all__ = ["telemetry_snapshot", "write_bench_self",
           "best_of", "interleave_rounds", "best_leg",
           "paired_ratio_max", "paired_median_ab", "BENCH_DIR"]

# BENCH_SELF records live next to bench.py at the repo root
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def telemetry_snapshot(stats_json_dict=None) -> dict:
    """The `telemetry` key every BENCH_SELF_*.json carries from r12
    on: the central metrics exposition (observability/metrics.py) +
    the runtime's stats_json() dict, so future perf rounds read the
    counter context (compiles, cache tiers, occupancy) next to the
    headline number instead of re-deriving it.

    The flag is flipped to `metrics` just for the expose() call: the
    counters behind the exposition (executor compiles/hits, cache
    residency, server histograms) are live pull providers that count
    at EVERY level, so benches that ran at `off` still snapshot real
    values — only the exposition rendering itself is gated.

    Reference counterpart: the reference had no cross-config telemetry
    record (per-model prints only, reference benchmark/fluid/
    fluid_benchmark.py:296-300); the r12 BENCH_SELF contract is ours.
    """
    from paddle_tpu import observability as obs
    from paddle_tpu.flags import FLAGS, set_flags

    prev = FLAGS.observability
    set_flags({"FLAGS_observability": "metrics"})
    try:
        exposition = obs.metrics.expose()
    finally:
        set_flags({"FLAGS_observability": prev})
    return {
        "metrics_expose": exposition,
        "stats_json": stats_json_dict,
        "flight": {
            "recorded_total": obs.RECORDER.recorded_total,
            "incidents_total": obs.RECORDER.incidents_total,
        },
    }


def write_bench_self(filename: str, result: dict,
                     stats_json_dict=None,
                     allow_schema_change: bool = False) -> dict:
    """Write a BENCH_SELF_*.json next to bench.py, injecting the r12
    `telemetry` key (telemetry_snapshot). When the file already exists
    (the committed record of the last measured round), the new
    result's TOP-LEVEL key set must match it — the BENCH_SELF schema
    is a contract later rounds diff against, and a refactor dropping
    or renaming a recorded field must fail the run, not silently thin
    the record. Intentional schema evolution passes
    ``allow_schema_change=True`` (and reviews the diff). Returns the
    result dict (with telemetry attached).

    Reference counterpart: reference benchmark/fluid/fluid_benchmark.py
    prints per-pass speed lines; a committed machine-readable record
    with a schema contract has no reference analogue.
    """
    result["telemetry"] = telemetry_snapshot(stats_json_dict)
    out_path = os.path.join(BENCH_DIR, filename)
    if os.path.exists(out_path) and not allow_schema_change:
        try:
            with open(out_path) as f:
                old_keys = set(json.load(f))
        except (OSError, ValueError):
            old_keys = None  # unreadable/corrupt: nothing to hold to
        if old_keys is not None and set(result) != old_keys:
            missing = sorted(old_keys - set(result))
            added = sorted(set(result) - old_keys)
            raise AssertionError(
                f"{filename} schema drifted: missing keys {missing}, "
                f"new keys {added}; pass allow_schema_change=True if "
                f"this is an intentional record evolution")
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    # perf-trend sentinel hookup (benchmark/trend.py): a freshly
    # measured record that regresses the committed trajectory must
    # never land silently — the warning prints at write time, the
    # committed bench_trend.json still gates in CI (`bench.py trend`)
    # until refreshed intentionally with --write-trend. A record the
    # sentinel cannot extract fails the run: it would fail CI next.
    from .trend import (_cross_round_warnings, build_records,
                        extract_record)

    extract_record(out_path)
    for w in _cross_round_warnings(build_records()):
        print(f"# trend WARNING: {w}")
    return result


def best_of(fn: Callable[[], float], n: int = 3,
            better=max) -> float:
    """Sequential best-of-N for a SCALAR leg (naive rps floors, child
    process timing loops): this host's single-pass swings are ~3x, so
    anything recorded in a BENCH file is a best-of-N (CLAUDE.md r9).
    For RATIOS between competing legs use interleave_rounds — a
    sequential best-of-N compares throttle-window luck.

    Reference counterpart: reference benchmark/fluid/fluid_benchmark.py
    :296 averages one pass; best-of-N is the throttled-shared-host
    discipline (PERF.md), no reference analogue.
    """
    return better(fn() for _ in range(n))


def interleave_rounds(legs: Sequence[Tuple[str, Callable[[], dict]]],
                      rounds: int = 3) -> List[Dict[str, dict]]:
    """Run the named legs IN ORDER, `rounds` times: adjacent legs of a
    round share this host's multi-second CPU-throttle windows, so
    cross-leg ratios taken WITHIN a round compare modes, not windows
    (the r10 discipline; sequential per-leg best-of-3 measured
    2x-off ratios). Returns one {name: result} dict per round.

    Reference counterpart: none — single-tenant dedicated-host era;
    grown from this repo's r10 measured 2x-off sequential ratios.
    """
    out: List[Dict[str, dict]] = []
    for _ in range(rounds):
        out.append({name: fn() for name, fn in legs})
    return out


def best_leg(rounds: List[Dict[str, dict]], name: str,
             key=lambda r: r["wall_s"]):
    """Best result of ONE leg across rounds (headline numbers).

    Reference counterpart: none (see interleave_rounds).
    """
    return min((r[name] for r in rounds), key=key)


def paired_ratio_max(rounds: List[Dict[str, dict]], num: str,
                     den: str,
                     value=lambda r: r["tok_s"]) -> float:
    """Best PAIRED ratio num/den: each ratio uses the two legs of ONE
    round (shared throttle window). This is the only ratio form the
    configs assert on — best(num)/best(den) across different rounds
    pits one leg's lucky window against another's throttled one.

    Reference counterpart: none (see interleave_rounds); the r10
    guard-test method.
    """
    return max(value(r[num]) / value(r[den]) for r in rounds)


def paired_median_ab(run_leg: Callable[[], tuple],
                     set_mode: Callable[[str], None],
                     mode_a: str, mode_b: str, reps: int):
    """Median of PAIRED adjacent-leg ratios mode_a/mode_b for A/B'ing
    a process-global mode (the r12 observability gate). Three
    defenses against the throttle: the two modes run back-to-back
    (shared throttle state); the order alternates per rep (the second
    leg of a pair trends measurably warmer); and the median over reps
    rejects window-boundary outliers. `run_leg` returns (scalar,
    extra); returns (median_ratio, ratios, legs_by_mode).

    Reference counterpart: none — the r12 observability acceptance
    protocol (PERF.md 'Observability overhead').
    """
    ratios: List[float] = []
    legs: Dict[str, list] = {mode_a: [], mode_b: []}
    for rep in range(reps):
        order = ((mode_a, mode_b) if rep % 2 == 0
                 else (mode_b, mode_a))
        res = {}
        for mode in order:
            set_mode(mode)
            res[mode] = run_leg()
        for m in (mode_a, mode_b):
            legs[m].append(res[m])
        ratios.append(res[mode_a][0] / res[mode_b][0])
    srt = sorted(ratios)
    mid = len(srt) // 2
    med = (srt[mid] if len(srt) % 2
           else 0.5 * (srt[mid - 1] + srt[mid]))
    return med, ratios, legs
