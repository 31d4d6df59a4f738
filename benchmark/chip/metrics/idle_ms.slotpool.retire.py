"""Device idle time a dispatch under the program's `slotpool.retire` span
(absorbing the fetched state and the sweep over the lanes). Layer:
serving scheduler (inference/serving.py _loop and _cycle); moves
serve_tokens_per_s."""
from benchmark.chip import program_spans


def read(obs):
    return program_spans.idle_ms_per(obs, "slotpool.retire",
                                     "dispatches")
