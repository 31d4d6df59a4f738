"""The least bytes a decode tick must read (decoder weights once, the
live lanes' self and cross keys and values at their mean length) over
the chip's memory bandwidth, against the device time of a tick.
Bound by bytes. Layer: decode tick kernels; moves tpot_ms_p95."""

from benchmark.chip import shapes


def read(obs):
    tr, ticks = obs["trace"], obs["counters"].get("traced_ticks")
    if not tr or not ticks or not tr["busy_s"]:
        return None
    c = obs["sizes"]
    lanes = obs["counters"].get("mean_live_lanes") or c["n_slots"]
    need = shapes.decode_tick_min_bytes(
        c, lanes, c["max_out_len"] / 2, c["seq_len"])
    least_s = need / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (tr["busy_s"] / ticks)
