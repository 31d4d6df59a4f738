"""Percent of the cycles' device idle time that lies in cycles whose idle
time is over twice the median cycle's: how much of the host's cost is a
few slow cycles and not every cycle's. Layer: serving scheduler; moves
tpot_ms_p95.

How to read it in the two `transformer-big-serve` cells (PERF.md
section 6, PR 36): for as long as the profile is taken with the
Python tracer it detects the tracer's slow stretch, which most traced
windows there hold and no untraced run does: under 5% (0.7% was read)
the window has none and compares with another such window; 26-47%
says the stretch fell inside the window, and the window's other host
metrics are stretched with it. In the two decoder-only cells, whose
windows show no stretch, it reads the scheduler's own slow cycles (0%
in PR 36's windows)."""
from benchmark.chip import cycle_spans


def read(obs):
    return cycle_spans.slow_idle_share(obs)
