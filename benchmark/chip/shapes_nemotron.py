"""Operations and bytes of the Nemotron-3-Super serve programs reckoned
from shapes and from what the program counted (live lanes, contexts,
held experts hit), the same whatever implements a layer. A
multiply-add counts two operations. Every function takes the
configuration's sizes as a dict (configs/nemotron-3-super-serve-ep4.json
`sizes`) and returns plain numbers. (`shapes.roofline_seconds` turns a
cost into the chip's least time.)"""

BYTES = 2           # bfloat16 weights, pools and convolution tail
STATE_BYTES = 4     # float32 scan state


def count(c, kind):
    return c["layers"].count(kind)


def d_inner(c):
    return c["ssm_heads"] * c["ssm_head_dim"]


def conv_width(c):
    return d_inner(c) + 2 * c["ssm_groups"] * c["ssm_state"]


def ssm_weights(c):
    """Parameters of one state-space layer that a product reads: the
    input and output projections and the convolution."""
    d = c["d_model"]
    return d * (d_inner(c) + conv_width(c) + c["ssm_heads"]) \
        + d_inner(c) * d + conv_width(c) * (c["conv_kernel"] + 1)


def attention_weights(c):
    d, dh = c["d_model"], c["head_dim"]
    return 2 * d * c["n_heads"] * dh + 2 * d * c["n_kv_heads"] * dh


def expert_layer_fixed_weights(c):
    """What an expert layer reads whatever the routing: router, latent
    projections down and up, shared expert."""
    d = c["d_model"]
    return d * c["n_experts"] + 2 * d * c["d_latent"] \
        + 2 * d * c["d_shared"]


def expert_weights(c):
    """One routed expert: up and down in the latent width."""
    return 2 * c["d_latent"] * c["d_expert"]


def scan_state_elements(c):
    return c["ssm_heads"] * c["ssm_head_dim"] * c["ssm_state"]


def lane_state_bytes(c):
    """One lane's state in one state-space layer: the float32 scan
    state and the convolution tail."""
    return scan_state_elements(c) * STATE_BYTES \
        + (c["conv_kernel"] - 1) * conv_width(c) * BYTES


def kv_position_bytes(c):
    """Keys and values of one position in one attention layer."""
    return 2 * c["n_kv_heads"] * c["head_dim"] * BYTES


def scan_flops(c, chunked):
    """The recurrence for one token of one layer. A step: the decay
    times the state, the outer product added, the read through C: three
    multiply-adds a state element. The chunked form at block L: C.B
    inside the block (G L N), the masked product (H L P), the state's
    part and the state's update (H P N each)."""
    if not chunked:
        return 6 * scan_state_elements(c)
    big = c["scan_block"]
    return 2 * c["ssm_groups"] * big * c["ssm_state"] \
        + 2 * c["ssm_heads"] * big * c["ssm_head_dim"] \
        + 4 * scan_state_elements(c)


def attention_flops(c, context):
    """One query of one attention layer over `context` positions: the
    score and the weighted sum a head."""
    return 4 * c["n_heads"] * c["head_dim"] * context


def token_flops(c, context, pairs_per_layer, chunked=False, head=True):
    """One token through the cut stack at `context` cached positions
    with `pairs_per_layer` (token, held expert) pairs an expert layer:
    every product of the weights, the recurrence, the attention, and
    the head where the token is served (a prefilled token's logits are
    never made)."""
    total = 2 * c["d_model"] * c["vocab"] if head else 0
    total += count(c, "M") * (2 * ssm_weights(c) + scan_flops(c, chunked))
    total += count(c, "*") * (2 * attention_weights(c)
                              + attention_flops(c, context))
    total += count(c, "E") * (2 * expert_layer_fixed_weights(c)
                              + pairs_per_layer * 2 * expert_weights(c))
    return total


def tick_weight_bytes(c, experts_hit_per_layer):
    """Weights one decode tick has to read whatever its lanes, with the
    held experts that got a pair (counted by the program)."""
    n = count(c, "M") * ssm_weights(c) \
        + count(c, "*") * attention_weights(c) \
        + count(c, "E") * (expert_layer_fixed_weights(c)
                           + experts_hit_per_layer * expert_weights(c)) \
        + c["d_model"] * c["vocab"]
    return n * BYTES


def ssm_tick_cost(c, lanes):
    """The state-space mixers of one tick: their weights once, every
    live lane's state read and written."""
    n = count(c, "M")
    return {"bytes": n * (ssm_weights(c) * BYTES
                          + 2 * lanes * lane_state_bytes(c)),
            "flops": n * lanes * (2 * ssm_weights(c)
                                  + scan_flops(c, False))}


def ssm_chunk_cost(c, tokens, chunks):
    """The state-space mixers of `chunks` prefill chunks that hold
    `tokens` real positions: weights once a chunk, the lane's state
    read and written once a chunk, the rows in and out of the two
    projections."""
    n = count(c, "M")
    rows = c["d_model"] * 2 + d_inner(c) * 2 + conv_width(c) \
        + c["ssm_heads"]
    return {"bytes": n * (chunks * (ssm_weights(c) * BYTES
                                    + 2 * lane_state_bytes(c))
                          + tokens * rows * BYTES),
            "flops": n * tokens * (2 * ssm_weights(c)
                                   + scan_flops(c, True))}


def decode_tick_min_bytes(c, lanes, context, experts_hit_per_layer):
    """Least bytes of one decode tick: the weights touched, the live
    lanes' state read and written, their cached keys and values at the
    mean context, this tick's rows written."""
    return tick_weight_bytes(c, experts_hit_per_layer) \
        + count(c, "M") * 2 * lanes * lane_state_bytes(c) \
        + count(c, "*") * lanes * (context + 1) * kv_position_bytes(c)
