"""Device idle time a dispatch under the program's `slotpool.retire.tree`
span, inside `slotpool.retire`: what a lane's end costs in the radix
tree and the pools (the harvest's inserts, the releases of its shared
prefix and its blocks). Layer: serving scheduler; moves
serve_tokens_per_s."""
from benchmark.chip import program_spans


def read(obs):
    return program_spans.idle_ms_per(obs, "slotpool.retire.tree",
                                     "dispatches")
