"""Mean device idle time of the scheduler cycles that admitted nothing and
prefilled no chunk: a pure decode burst's host cost. Layer: serving
scheduler; moves serve_tokens_per_s."""
from benchmark.chip import cycle_spans


def read(obs):
    return cycle_spans.mean_of(obs, lambda r: r["idle_ms"],
                               admitting=False)
