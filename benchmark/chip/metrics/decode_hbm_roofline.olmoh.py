"""The least bytes an Olmo-Hybrid decode tick must move (the weights;
the live lanes' delta-rule state and convolution tail read and written;
their cached keys and values at the mean context: shapes_olmoh.py)
over the chip's memory bandwidth, against the device time of a tick
(the traced busy time less what ran under `olmoh.prefill_chunk`, over
the ticks the device counted). Bound by bytes. Layer: decode tick
kernels; moves tpot_ms_p95."""
from benchmark.chip import scopes_olmoh, shapes_olmoh


def read(obs):
    n = obs["counters"]
    ticks, spent = n.get("traced_ticks"), scopes_olmoh.tick_seconds(obs)
    if not ticks or not spent or not n.get("mean_context"):
        return None
    need = shapes_olmoh.decode_tick_min_bytes(
        obs["sizes"], n["mean_live_lanes"], n["mean_context"])
    return 100.0 * need / obs["peaks"]["hbm_bytes_per_s"] \
        / (spent / ticks)
