"""The whole training step's share of the chips' peak: forward and
backward operations per target token from shapes, times
train_tokens_per_s, over chips times peak. Layer: whole step; moves
train_tokens_per_s."""
from benchmark.chip import shapes


def read(obs):
    if not obs["on_chip"]:
        return None
    c = obs["sizes"]
    rate = obs["end_to_end"]["train_tokens_per_s"]
    flops = shapes.train_flops_per_target_token(c, c["seq_len"])
    return 100.0 * flops * rate / (obs["observed"]["chips"]
                                   * obs["peaks"]["flops_per_s"])
