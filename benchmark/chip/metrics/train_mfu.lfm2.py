"""The LFM2-MoE training step's share of the chip's peak: forward and
backward operations a token from shapes and from the counted pairs
routed to the experts held (benchmark/chip/shapes_lfm2.py), times
train_tokens_per_s, over chips times peak. Layer: whole step; moves
train_tokens_per_s."""
from benchmark.chip import shapes_lfm2


def read(obs):
    pairs = obs["counters"].get("moe_pairs_per_step")
    if not obs["on_chip"] or pairs is None:
        return None
    c = obs["sizes"]
    rate = obs["end_to_end"]["train_tokens_per_s"]
    flops = shapes_lfm2.train_flops_per_token(c, c["seq_len"], pairs)
    return 100.0 * flops * rate / (obs["observed"]["chips"]
                                   * obs["peaks"]["flops_per_s"])
