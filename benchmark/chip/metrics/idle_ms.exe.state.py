"""Device idle time a step under the program's `exe.state` span (gathering
every parameter and optimizer moment from the scope). Layer: executor
(Executor.run); moves train_tokens_per_s."""
from benchmark.chip import program_spans


def read(obs):
    return program_spans.idle_ms_per(obs, "exe.state", "traced_steps")
