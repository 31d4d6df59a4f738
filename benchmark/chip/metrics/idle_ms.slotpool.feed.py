"""Device idle time a dispatch under the program's `slotpool.feed` span
(building the admission feeds and publishing the block tables). Layer:
serving scheduler (inference/serving.py _loop and _cycle); moves
serve_tokens_per_s."""
from benchmark.chip import program_spans


def read(obs):
    return program_spans.idle_ms_per(obs, "slotpool.feed",
                                     "dispatches")
