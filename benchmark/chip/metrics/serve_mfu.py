"""The server's share of the chip's peak: decoder operations per output
token (and the encoder's on a miss) from shapes, times
serve_tokens_per_s, over peak. Replayed tokens count as output and cost
no operations, so this reads what the tokens served would have cost.
Layer: whole step; moves serve_tokens_per_s."""
from benchmark.chip import shapes


def read(obs):
    if not obs["on_chip"]:
        return None
    c, n = obs["sizes"], obs["counters"]
    adm = n.get("prefix_hits", 0) + n.get("prefix_misses", 0)
    miss = n.get("prefix_misses", 0) / adm if adm else 1.0
    flops = shapes.serve_flops_per_output_token(
        c, c["seq_len"], c["max_out_len"], miss)
    return 100.0 * flops * obs["end_to_end"]["serve_tokens_per_s"] \
        / obs["peaks"]["flops_per_s"]
