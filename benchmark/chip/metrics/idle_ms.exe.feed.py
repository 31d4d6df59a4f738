"""Device idle time a step under the program's `exe.feed` span (fetch-name
and feed-shape checks, coercion and device_put of the feeds). Layer:
executor (Executor.run); moves train_tokens_per_s."""
from benchmark.chip import program_spans


def read(obs):
    return program_spans.idle_ms_per(obs, "exe.feed", "traced_steps")
