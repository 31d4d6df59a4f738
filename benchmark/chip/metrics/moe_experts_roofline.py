"""The grouped expert products' share of their roofline: the least time
for the products of the counted pairs (operations over peak, or the
held experts' weight bytes over the memory bandwidth, whichever is
larger; benchmark/chip/shapes_lfm2.py) over the device time of the
grouped-product kernels' events (megablox gmm and tgmm, forward and
backward). Layer: expert layer; moves train_tokens_per_s."""
from benchmark.chip import shapes, shapes_lfm2


def read(obs):
    tr, steps = obs["trace"], obs["counters"].get("traced_steps")
    pairs = obs["counters"].get("moe_pairs_per_step")
    if not tr or not steps or pairs is None:
        return None
    spent = sum(s for name, s in tr["op_s"].items() if "gmm" in name)
    if not spent:
        return None
    cost = shapes_lfm2.moe_experts_cost(obs["sizes"], pairs)
    return 100.0 * steps * shapes.roofline_seconds(cost, obs["peaks"]) \
        / spent
