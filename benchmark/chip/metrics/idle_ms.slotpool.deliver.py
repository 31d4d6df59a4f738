"""Device idle time a dispatch under the program's `slotpool.deliver` span
(stream chunks, finish markers and results: the callers' callbacks run
here). Layer: serving scheduler (inference/serving.py _loop and _cycle);
moves serve_tokens_per_s."""
from benchmark.chip import program_spans


def read(obs):
    return program_spans.idle_ms_per(obs, "slotpool.deliver",
                                     "dispatches")
