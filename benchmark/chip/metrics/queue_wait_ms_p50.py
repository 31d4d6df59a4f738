"""Median wait in the server's queue, arrival to admission, of the
requests admitted inside the traced window: the `wait_us` of the
program's `slotpool.admit` spans (a 6 s window holds a few dozen
admissions, so no tail is read). Layer: serving scheduler; moves
ttft_ms_p95."""
import statistics

from benchmark.chip import program_spans


def read(obs):
    made = program_spans.window_table(obs)
    if not made or not made["queue_wait_us"]:
        return None
    return statistics.median(made["queue_wait_us"]) / 1e3
