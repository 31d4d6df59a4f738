"""Arrays read back a dispatch: `fetched_arrays` of the program's record
of a cycle (the `slotpool.cycle` marker's metadata: each is one copy to
the host behind the burst), over the counted cycles. Layer: serving
scheduler (the fetch list is its serve program's); moves
serve_tokens_per_s."""
from benchmark.chip import cycle_spans


def read(obs):
    return cycle_spans.mean_count(obs, "fetched_arrays")
