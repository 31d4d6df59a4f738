"""The least bytes a GLM-5.2 decode tick must move (the weights it
touches, with the held experts the program counted as hit; the live
lanes' indexer keys at their mean context; their selected latent rows;
the rows it writes: shapes_glm.py) over the chip's memory bandwidth,
against the device time of a tick (the traced busy time less what ran
under `glm.prefill_chunk`, over the ticks the device counted). Bound by
bytes. Layer: decode tick kernels; moves tpot_ms_p95."""
from benchmark.chip import scopes_glm, shapes_glm


def read(obs):
    n = obs["counters"]
    ticks, spent = n.get("traced_ticks"), scopes_glm.tick_seconds(obs)
    if not ticks or not spent or not n.get("mean_context"):
        return None
    need = shapes_glm.decode_tick_min_bytes(
        obs["sizes"], n["mean_live_lanes"], n["mean_context"],
        n["selected_keys_per_query"], n["held_experts_hit_per_tick"])
    return 100.0 * need / obs["peaks"]["hbm_bytes_per_s"] \
        / (spent / ticks)
