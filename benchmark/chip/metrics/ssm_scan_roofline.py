"""The state-space mixers of the prefill chunks (projections, the
convolution, the chunked scan from the lane's state, the gated norm):
their least time (operations of the real positions over peak, or the
weights and the lane's state once a chunk and the rows in and out over
the memory bandwidth, whichever is larger: shapes_nemotron.py) over the
device time of the operations traced under `nemotronh.ssm_scan`. The
positions a chunk is padded by are work the device did and the count
leaves out. Layer: decode tick kernels (ops/ssm_ops.py
mamba2_chunk_scan); moves serve_tokens_per_s."""
from benchmark.chip import scopes_nemotron, shapes, shapes_nemotron


def read(obs):
    n = obs["counters"]
    spent = scopes_nemotron.under(obs, "nemotronh.ssm_scan")
    if not spent or not n.get("prefill_tokens") \
            or not n.get("prefill_chunks"):
        return None
    cost = shapes_nemotron.ssm_chunk_cost(
        obs["sizes"], n["prefill_tokens"], n["prefill_chunks"])
    return 100.0 * shapes.roofline_seconds(cost, obs["peaks"]) / spent
