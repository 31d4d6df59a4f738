"""The linear-attention mixers of the decode ticks (projections, the
convolution with its tail, one delta-rule step for every lane, the
gated norm, the post-norm): their least time (the six layers' weights
once and the live lanes' state and tail read and written, or their
operations over peak: shapes_olmoh.py) over the device time of the
operations traced under `olmoh.gdn`. Bound by bytes. Layer: decode
tick kernels (ops/delta_ops.py gated_delta_step, causal_conv_tail);
moves tpot_ms_p95."""
from benchmark.chip import scopes_olmoh, shapes, shapes_olmoh


def read(obs):
    n = obs["counters"]
    ticks = n.get("traced_ticks")
    spent = scopes_olmoh.under(obs, "olmoh.gdn")
    if not ticks or not spent or not n.get("mean_live_lanes"):
        return None
    cost = shapes_olmoh.gdn_tick_cost(obs["sizes"], n["mean_live_lanes"])
    return 100.0 * ticks * shapes.roofline_seconds(
        cost, obs["peaks"]) / spent
