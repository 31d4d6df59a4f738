"""Arrays placed on the device a dispatch: `placed_arrays` of the
program's record of a cycle (the `slotpool.cycle` marker's metadata:
what `_Transfers.put` sent up between the cycle's feed and its last
delivery: the data-parallel feeds, a scope's host-written tables), over
the counted cycles. Layer: serving scheduler (the tables are its
`_pre_dispatch`'s); moves serve_tokens_per_s."""
from benchmark.chip import cycle_spans


def read(obs):
    return cycle_spans.mean_count(obs, "placed_arrays")
