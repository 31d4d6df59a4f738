"""95th percentile, over the scheduler cycles that lie wholly inside the
traced window, of the device's idle time inside one cycle (the interval
the program's `slotpool.cycle` marker gives, intersected with the
device's idle intervals): the slow cycles that set a request's tail,
which the mean a span (`idle_ms.slotpool.*`) cannot show. Layer: serving
scheduler (inference/serving.py _loop and _cycle); moves tpot_ms_p95.

How to read it in the two `transformer-big-serve` cells (PERF.md
section 6, PR 36): for as long as the profile is taken with the
Python tracer, most traced windows there hold one slow stretch that is
the tracer's (the same cycles at twice the processor time from some
moment on; no untraced run has it, and `tpot_ms_p95` is read
untraced), and this metric reads 10 ms without the stretch and 22-32
with it. There it says whether the stretch fell in the window, not
what the scheduler did; `slow_cycle_idle_share` under 5% marks a
window without it, and only two such windows compare. In the two
decoder-only cells, whose windows show no stretch, it reads the
scheduler's own slow cycles."""
from benchmark.chip import cycle_spans


def read(obs):
    rows = cycle_spans.window_cycles(obs)
    if not rows:
        return None
    return cycle_spans.percentile([r["idle_ms"] for r in rows], 0.95)
