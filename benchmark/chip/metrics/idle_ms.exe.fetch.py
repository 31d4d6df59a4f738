"""Device idle time a step under the program's `exe.fetch` span (np.asarray
of the fetches: the host blocked on the device). Layer: executor
(Executor.run); moves train_tokens_per_s."""
from benchmark.chip import program_spans


def read(obs):
    return program_spans.idle_ms_per(obs, "exe.fetch", "traced_steps")
