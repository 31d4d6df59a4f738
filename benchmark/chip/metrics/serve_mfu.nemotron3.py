"""The Nemotron-3-Super server's share of the chip's peak: operations of
the tokens it served (each at the mean context, with the pairs the
program counted on held experts) and of the prompt tokens it prefilled
in the window (by the chunked scan, no head, routed as any token is),
from shapes (shapes_nemotron.py), over the window and the peak. Layer:
whole step; moves serve_tokens_per_s."""
from benchmark.chip import shapes_nemotron as S


def read(obs):
    if not obs["on_chip"]:
        return None
    c, n = obs["sizes"], obs["counters"]
    if not n.get("lane_ticks") or not n.get("mean_context"):
        return None
    pairs = n["moe_pairs"] / n["lane_ticks"] / S.count(c, "E")
    ctx = n["mean_context"]
    served = obs["end_to_end"]["serve_tokens_per_s"] \
        * S.token_flops(c, ctx, pairs)
    expected = c["top_k"] * c["experts_held"] / c["n_experts"]
    prefilled = n.get("prefill_tokens", 0) / obs["observed"]["window_s"] \
        * S.token_flops(c, ctx, expected, chunked=True, head=False)
    return 100.0 * (served + prefilled) / obs["peaks"]["flops_per_s"]
