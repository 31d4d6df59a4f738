"""The Olmo-Hybrid server's share of the chip's peak: operations of the
tokens it served (each at the mean context) and of the prompt tokens it
prefilled in the window (by the chunked delta rule, no head), from
shapes (shapes_olmoh.py), over the window and the peak. Layer: whole
step; moves serve_tokens_per_s."""
from benchmark.chip import shapes_olmoh as S


def read(obs):
    if not obs["on_chip"]:
        return None
    c, n = obs["sizes"], obs["counters"]
    if not n.get("lane_ticks") or not n.get("mean_context"):
        return None
    ctx = n["mean_context"]
    served = obs["end_to_end"]["serve_tokens_per_s"] \
        * S.token_flops(c, ctx)
    prefilled = n.get("prefill_tokens", 0) / obs["observed"]["window_s"] \
        * S.token_flops(c, ctx, chunked=True, head=False)
    return 100.0 * (served + prefilled) / obs["peaks"]["flops_per_s"]
