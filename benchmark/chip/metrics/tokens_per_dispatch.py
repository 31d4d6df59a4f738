"""Output tokens the server produced per fused dispatch in the window.
Layer: serving scheduler (inference/serving.py _cycle); moves
serve_tokens_per_s."""


def read(obs):
    c = obs["counters"]
    if not c.get("dispatches"):
        return None
    return c["window_tokens_all"] / c["dispatches"]
