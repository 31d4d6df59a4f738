"""Attention over the selected latent rows in the decode ticks: its
least time (the selected rows' bytes of every live lane and layer, or
its operations over peak, whichever is larger: shapes_glm.py) over the
device time of the operations traced under `glm.sparse_attn` outside a
prefill chunk, whatever route computed them. Bound by bytes. Layer:
decode tick kernels (ops/paged_ops.py sparse_latent_attention); moves
tpot_ms_p95."""
from benchmark.chip import scopes_glm, shapes, shapes_glm


def read(obs):
    n = obs["counters"]
    ticks = n.get("traced_ticks")
    spent = scopes_glm.under(obs, "glm.sparse_attn")
    if not ticks or not spent or not n.get("selected_keys_per_query"):
        return None
    cost = shapes_glm.sparse_attention_tick_cost(
        obs["sizes"], n["mean_live_lanes"], n["selected_keys_per_query"])
    return 100.0 * ticks * shapes.roofline_seconds(
        cost, obs["peaks"]) / spent
