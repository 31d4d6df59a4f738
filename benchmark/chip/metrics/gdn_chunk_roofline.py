"""The linear-attention mixers of the prefill chunks (projections, the
convolution, the chunked delta rule from the lane's state, the gated
norm, the post-norm): their least time (operations of the real
positions over peak, or the weights and the lane's state once a chunk
and the rows in and out over the memory bandwidth, whichever is larger:
shapes_olmoh.py) over the device time of the operations traced under
`olmoh.gdn_chunk`. The positions a chunk is padded by are work the
device did and the count leaves out. Layer: decode tick kernels
(ops/delta_ops.py gated_delta_chunk); moves serve_tokens_per_s."""
from benchmark.chip import scopes_olmoh, shapes, shapes_olmoh


def read(obs):
    n = obs["counters"]
    spent = scopes_olmoh.under(obs, "olmoh.gdn_chunk")
    if not spent or not n.get("prefill_tokens") \
            or not n.get("prefill_chunks"):
        return None
    cost = shapes_olmoh.gdn_chunk_cost(
        obs["sizes"], n["prefill_tokens"], n["prefill_chunks"])
    return 100.0 * shapes.roofline_seconds(cost, obs["peaks"]) / spent
