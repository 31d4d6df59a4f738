"""Device idle time a step under the program's `exe.store` span (writing
the new state and the advanced key back to the scope). Layer: executor
(Executor.run); moves train_tokens_per_s."""
from benchmark.chip import program_spans


def read(obs):
    return program_spans.idle_ms_per(obs, "exe.store", "traced_steps")
