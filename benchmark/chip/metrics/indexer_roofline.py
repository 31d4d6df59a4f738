"""The sparse-attention indexer in the decode ticks, projections,
scores and selection: its least time (every live lane's cached indexer
keys and the indexer's weights once a layer that owns one, or its
operations over peak: shapes_glm.py) over the device time of the
operations traced under `glm.indexer` and `glm.select` outside a
prefill chunk. Bound by bytes. Layer: decode tick kernels
(ops/paged_ops.py dsa_indexer_scores, dsa_select); moves
tpot_ms_p95."""
from benchmark.chip import scopes_glm, shapes, shapes_glm


def read(obs):
    n = obs["counters"]
    ticks = n.get("traced_ticks")
    spent = scopes_glm.under(obs, "glm.indexer", "glm.select")
    if not ticks or not spent or not n.get("mean_context"):
        return None
    cost = shapes_glm.indexer_tick_cost(
        obs["sizes"], n["mean_live_lanes"], n["mean_context"])
    return 100.0 * ticks * shapes.roofline_seconds(
        cost, obs["peaks"]) / spent
