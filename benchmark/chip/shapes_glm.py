"""Operations and bytes of the GLM-5.2 serve programs reckoned from
shapes and from what the program counted (live lanes, contexts,
selected keys, held experts hit), the same whatever implements a layer.
A multiply-add counts two operations. Every function takes the
configuration's sizes as a dict (configs/glm-5.2-serve-ep16.json
`sizes`) and returns plain numbers. (`shapes.roofline_seconds` turns a cost into the chip's least
time.)"""

BYTES = 2       # bfloat16 weights and pools


def _full_layers(c):
    return sum(k == "full" for k in c["indexer_types"])


def _moe_layers(c):
    return c["n_layers"] - c["n_dense_layers"]


def attention_weights(c):
    """Parameters of one layer's latent attention (q_a, q_b, kv_a, kv_b,
    o)."""
    d, h = c["d_model"], c["n_heads"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    return d * rq + rq * h * (dn + dr) + d * (rkv + dr) \
        + rkv * h * (dn + dv) + h * dv * d


def indexer_weights(c):
    return c["q_lora_rank"] * c["index_n_heads"] * c["index_head_dim"] \
        + c["d_model"] * (c["index_head_dim"] + c["index_n_heads"])


def gated_weights(d, f):
    return 3 * d * f


def latent_row_bytes(c):
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * BYTES


def cell_bytes(c):
    """Cache bytes of one position: a latent row a layer, an indexer
    key in the layers that own an indexer."""
    return c["n_layers"] * latent_row_bytes(c) \
        + _full_layers(c) * c["index_head_dim"] * BYTES


def attention_flops(c, selected):
    """One query of one layer over `selected` rows, absorbed: the score
    and the weighted sum a head (the projections are the weights')."""
    rkv, dr = c["kv_lora_rank"], c["qk_rope_head_dim"]
    return 2 * c["n_heads"] * selected * (rkv + dr + rkv)


def absorb_flops(c):
    """Absorbing the key up-projection into a query and applying the
    value up-projection after the sum, one token one layer."""
    return 2 * c["n_heads"] * c["kv_lora_rank"] \
        * (c["qk_nope_head_dim"] + c["v_head_dim"])


def indexer_score_flops(c, context):
    return 2 * c["index_n_heads"] * c["index_head_dim"] * context


def token_flops(c, context, selected, pairs_per_layer):
    """One token through the cut stack at `context` cached positions,
    `selected` of them attended, `pairs_per_layer` (token, held expert)
    pairs a routed layer: every product of the weights (the kv_b
    product is the absorbed one), the indexer over the context, the
    attention over the selection, the head."""
    d = c["d_model"]
    per_layer = attention_weights(c) - c["kv_lora_rank"] * c["n_heads"] \
        * (c["qk_nope_head_dim"] + c["v_head_dim"])
    total = 2 * d * c["vocab"]
    total += c["n_layers"] * (2 * per_layer + absorb_flops(c)
                              + attention_flops(c, selected))
    total += _full_layers(c) * (2 * indexer_weights(c)
                                + indexer_score_flops(c, context))
    total += c["n_dense_layers"] * 2 * gated_weights(d, c["d_dense"])
    total += _moe_layers(c) * (
        2 * d * c["n_experts"]
        + 2 * gated_weights(d, c["d_expert"] * c["n_shared_experts"])
        + pairs_per_layer * 2 * gated_weights(d, c["d_expert"]))
    return total


def tick_weight_bytes(c, experts_hit_per_layer):
    """Weights one decode tick has to read whatever its lanes: the
    attention of every layer, the indexers, the dense feed-forward, the
    routers and shared experts, the head, and the held experts that got
    a pair (`experts_hit_per_layer`, counted by the program)."""
    d = c["d_model"]
    n = c["n_layers"] * attention_weights(c) \
        + _full_layers(c) * indexer_weights(c) \
        + c["n_dense_layers"] * gated_weights(d, c["d_dense"]) \
        + _moe_layers(c) * (
            d * c["n_experts"]
            + gated_weights(d, c["d_expert"] * c["n_shared_experts"])
            + experts_hit_per_layer * gated_weights(d, c["d_expert"])) \
        + d * c["vocab"]
    return n * BYTES


def indexer_tick_cost(c, lanes, context):
    """The indexers of one tick: every live lane's cached keys and the
    indexer's own weights, once a layer that owns one."""
    n = _full_layers(c)
    return {"bytes": n * (lanes * context * c["index_head_dim"] * BYTES
                          + indexer_weights(c) * BYTES),
            "flops": n * lanes * (2 * indexer_weights(c)
                                  + indexer_score_flops(c, context))}


def sparse_attention_tick_cost(c, lanes, selected):
    """Attention over the selected rows of one tick, every layer: a
    lane reads `selected` latent rows a layer."""
    return {"bytes": c["n_layers"] * lanes * selected
            * latent_row_bytes(c),
            "flops": c["n_layers"] * lanes * attention_flops(c, selected)}


def decode_tick_min_bytes(c, lanes, context, selected,
                          experts_hit_per_layer):
    """Least bytes of one decode tick: the weights touched, the indexer
    keys of the live context, the selected latent rows, this tick's
    rows written."""
    return tick_weight_bytes(c, experts_hit_per_layer) \
        + indexer_tick_cost(c, lanes, context)["bytes"] \
        - _full_layers(c) * indexer_weights(c) * BYTES \
        + sparse_attention_tick_cost(c, lanes, selected)["bytes"] \
        + lanes * cell_bytes(c)
