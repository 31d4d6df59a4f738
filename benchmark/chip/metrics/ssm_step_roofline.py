"""The state-space mixers of the decode ticks (projections, the
convolution with its tail, one step of the recurrence for every lane,
the gated norm): their least time (the five layers' weights once and
the live lanes' state read and written, or their operations over peak:
shapes_nemotron.py) over the device time of the operations traced
under `nemotronh.ssm`. Bound by bytes. Layer: decode tick kernels
(ops/ssm_ops.py mamba2_step, causal_conv_tail); moves tpot_ms_p95."""
from benchmark.chip import scopes_nemotron, shapes, shapes_nemotron


def read(obs):
    n = obs["counters"]
    ticks = n.get("traced_ticks")
    spent = scopes_nemotron.under(obs, "nemotronh.ssm")
    if not ticks or not spent or not n.get("mean_live_lanes"):
        return None
    cost = shapes_nemotron.ssm_tick_cost(obs["sizes"],
                                         n["mean_live_lanes"])
    return 100.0 * ticks * shapes.roofline_seconds(
        cost, obs["peaks"]) / spent
