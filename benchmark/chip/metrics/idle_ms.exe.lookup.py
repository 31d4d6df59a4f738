"""Device idle time a step under the program's `exe.lookup` span (the cache
key and the cache lookup; a miss compiles under it). Layer: executor
(Executor.run); moves train_tokens_per_s."""
from benchmark.chip import program_spans


def read(obs):
    return program_spans.idle_ms_per(obs, "exe.lookup", "traced_steps")
