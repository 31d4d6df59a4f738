"""Device idle time a step under the program's `exe.call` span (the jitted
call, which dispatches the step). Layer: executor (Executor.run); moves
train_tokens_per_s."""
from benchmark.chip import program_spans


def read(obs):
    return program_spans.idle_ms_per(obs, "exe.call", "traced_steps")
