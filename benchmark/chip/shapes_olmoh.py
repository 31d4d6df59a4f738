"""Operations and bytes of the Olmo-Hybrid serve programs reckoned from
shapes and from what the program counted (live lanes, contexts), the
same whatever implements a layer. A multiply-add counts two operations.
Every function takes the configuration's sizes as a dict
(configs/olmo-hybrid-7b-serve-pp4.json `sizes`) and returns plain
numbers. (`shapes.roofline_seconds` turns a cost into the chip's least
time.)"""

BYTES = 2           # bfloat16 weights, pools and convolution tail
STATE_BYTES = 4     # float32 delta-rule state


def count(c, kind):
    return c["layers"].count(kind)


def key_width(c):
    return c["lin_heads"] * c["lin_key_dim"]


def value_width(c):
    return c["lin_heads"] * c["lin_value_dim"]


def conv_width(c):
    """Numbers a position the convolution sees: q, k and v."""
    return 2 * key_width(c) + value_width(c)


def gdn_weights(c):
    """Parameters of one linear-attention mixer that a product reads:
    the input projection (q, k, v, the gate, beta, a), the output
    projection and the convolution."""
    d = c["d_model"]
    return d * (conv_width(c) + value_width(c) + 2 * c["lin_heads"]) \
        + value_width(c) * d + conv_width(c) * c["conv_kernel"]


def attention_weights(c):
    d, dh = c["d_model"], c["head_dim"]
    return 2 * d * c["n_heads"] * dh + 2 * d * c["n_kv_heads"] * dh


def mlp_weights(c):
    return 3 * c["d_model"] * c["d_ff"]


def layer_params(c, kind):
    """Every parameter of one layer of `kind` ("L" or "F") with its MLP
    and its two post-norms."""
    d = c["d_model"]
    norms = 2 * d
    if kind == "L":
        mixer = gdn_weights(c) + c["lin_value_dim"] + 2 * c["lin_heads"]
    else:
        mixer = attention_weights(c) \
            + (c["n_heads"] + c["n_kv_heads"]) * c["head_dim"]
    return mixer + mlp_weights(c) + norms


def top_params(c):
    """Embedding, final norm and head."""
    return 2 * c["vocab"] * c["d_model"] + c["d_model"]


def state_elements(c):
    return c["lin_heads"] * c["lin_key_dim"] * c["lin_value_dim"]


def lane_state_bytes(c):
    """One lane's state in one linear layer: the float32 delta-rule
    state and the convolution tail."""
    return state_elements(c) * STATE_BYTES \
        + (c["conv_kernel"] - 1) * conv_width(c) * BYTES


def kv_position_bytes(c):
    """Keys and values of one position in one full-attention layer."""
    return 2 * c["n_kv_heads"] * c["head_dim"] * BYTES


def delta_flops(c, chunked):
    """The delta rule for one token of one layer. A step: the read
    S^T k, the write of k (v - read) and the read S^T q, a multiply-add
    a state element each (the decay and beta fold into them). The
    chunked form at block L, a head: K K^T and Q K^T inside the block
    (L Dk each), the triangular solve for U and W (L (Dk + Dv) / 2),
    the causal product with the new values (L Dv), and against the
    state W S, Q S and the state's update (Dk Dv each)."""
    h, dk, dv = c["lin_heads"], c["lin_key_dim"], c["lin_value_dim"]
    if not chunked:
        return 6 * h * dk * dv
    big = c["delta_block"]
    return 2 * h * (2 * big * dk + big * (dk + dv) // 2 + big * dv
                    + 3 * dk * dv)


def attention_flops(c, context):
    """One query of one full layer over `context` positions: the score
    and the weighted sum a head."""
    return 4 * c["n_heads"] * c["head_dim"] * context


def token_flops(c, context, chunked=False, head=True):
    """One token through the cut stack at `context` cached positions:
    every product of the weights, the delta rule, the attention, and
    the head where the token is served (a prefilled token's logits are
    never made)."""
    total = 2 * c["d_model"] * c["vocab"] if head else 0
    total += count(c, "L") * (2 * gdn_weights(c)
                              + delta_flops(c, chunked))
    total += count(c, "F") * (2 * attention_weights(c)
                              + attention_flops(c, context))
    return total + len(c["layers"]) * 2 * mlp_weights(c)


def tick_weight_bytes(c):
    """Weights one decode tick has to read: every layer's products and
    the head."""
    n = count(c, "L") * gdn_weights(c) + count(c, "F") \
        * attention_weights(c) + len(c["layers"]) * mlp_weights(c) \
        + c["d_model"] * c["vocab"]
    return n * BYTES


def gdn_tick_cost(c, lanes):
    """The linear-attention mixers of one tick: their weights once,
    every live lane's state and tail read and written."""
    n = count(c, "L")
    return {"bytes": n * (gdn_weights(c) * BYTES
                          + 2 * lanes * lane_state_bytes(c)),
            "flops": n * lanes * (2 * gdn_weights(c)
                                  + delta_flops(c, False))}


def gdn_chunk_cost(c, tokens, chunks):
    """The linear-attention mixers of `chunks` prefill chunks that
    hold `tokens` real positions: weights once a chunk, the lane's
    state and tail read and written once a chunk, the rows in and out
    (the mixer's input, the projection, the convolution's output, the
    gated norm's output, the mixer's output)."""
    n = count(c, "L")
    rows = 2 * c["d_model"] + conv_width(c) + value_width(c) \
        + 2 * c["lin_heads"] + conv_width(c) + value_width(c)
    return {"bytes": n * (chunks * (gdn_weights(c) * BYTES
                                    + 2 * lane_state_bytes(c))
                          + tokens * rows * BYTES),
            "flops": n * tokens * (2 * gdn_weights(c)
                                   + delta_flops(c, True))}


def decode_tick_min_bytes(c, lanes, context):
    """Least bytes of one decode tick: the weights, the live lanes'
    state and tail read and written, their cached keys and values at
    the mean context with this tick's row written."""
    return tick_weight_bytes(c) \
        + count(c, "L") * 2 * lanes * lane_state_bytes(c) \
        + count(c, "F") * lanes * (context + 1) * kv_position_bytes(c)
