"""Median, over the window's streamed bursts after a request's first,
of the gap to the burst before over the tokens the burst carried.
Layer: decode engine; moves tpot_ms_p95."""


def read(obs):
    if not obs["on_chip"]:
        return None
    return obs["observed"].get("token_gap_ms_p50")
