"""Device time of the serve programs per decode tick: the traced
window's busy time over the ticks the device counted in it. Layer:
decode engine (models/decode_engine.py serve programs); moves
tpot_ms_p95."""


def read(obs):
    tr, ticks = obs["trace"], obs["counters"].get("traced_ticks")
    if not tr or not ticks or not tr["busy_s"]:
        return None
    return tr["busy_s"] / ticks * 1e3
