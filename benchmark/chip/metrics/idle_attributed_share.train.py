"""Share of the traced window's device idle time that passed under any of
the program's `paddle_tpu:` spans: what is left is host code no span
covers. Layer: executor; moves train_tokens_per_s."""
from benchmark.chip import program_spans


def read(obs):
    return program_spans.idle_attributed_share(obs)
