"""Mean device idle time of the scheduler cycles whose record holds an
admission or a prefill chunk (`admits` > 0 or `prefill_chunks` > 0 on
the program's `slotpool.cycle` marker). Layer: serving scheduler; moves
serve_tokens_per_s."""
from benchmark.chip import cycle_spans


def read(obs):
    return cycle_spans.mean_of(obs, lambda r: r["idle_ms"],
                               admitting=True)
