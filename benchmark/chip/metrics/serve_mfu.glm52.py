"""The GLM-5.2 server's share of the chip's peak: operations of the
tokens it served (each at the mean context, over the mean selection,
with the pairs the program counted on held experts) and of the prompt
tokens it prefilled in the window, from shapes (shapes_glm.py), over
the window and the peak. Layer: whole step; moves
serve_tokens_per_s."""
from benchmark.chip import shapes_glm


def read(obs):
    if not obs["on_chip"]:
        return None
    c, n = obs["sizes"], obs["counters"]
    if not n.get("lane_ticks") or not n.get("mean_context"):
        return None
    n_moe = c["n_layers"] - c["n_dense_layers"]
    pairs = n["moe_pairs"] / n["lane_ticks"] / n_moe
    ctx, sel = n["mean_context"], n["selected_keys_per_query"]
    served = obs["end_to_end"]["serve_tokens_per_s"] \
        * shapes_glm.token_flops(c, ctx, sel, pairs)
    # a prefilled token is routed like any other: top_k of n_experts,
    # experts_held of them here
    expected = c["top_k"] * c["experts_held"] / c["n_experts"]
    prefilled = n.get("prefill_tokens", 0) / obs["observed"]["window_s"] \
        * shapes_glm.token_flops(c, ctx, min(ctx, c["index_topk"]),
                                 expected)
    return 100.0 * (served + prefilled) / obs["peaks"]["flops_per_s"]
