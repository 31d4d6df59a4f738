"""Operations and bytes of the LFM2-MoE training step reckoned from
shapes and from the counted (token, held expert) pairs, the same
whatever implements the step. A multiply-add counts two operations.
Every function takes the configuration's sizes as a dict
(configs/lfm2-24b-a2b-train-ep8.json `sizes`) and returns plain
numbers."""


def conv_mixer_flops(c):
    """One token through a gated short convolution: the input
    projection to 3D, the taps, the two gates, the output projection."""
    d = c["d_model"]
    return 2 * d * 3 * d + 2 * d * c["conv_taps"] + 2 * d + 2 * d * d


def attention_mixer_flops(c, seq_len):
    """One token through grouped-query attention: q, k, v and output
    projections, and scores and weighted sum at the mean causal
    length (T + 1) / 2."""
    d = c["d_model"]
    head = d // c["n_heads"]
    proj = 2 * d * (c["n_heads"] + 2 * c["n_kv_heads"]) * head + 2 * d * d
    return proj + 2 * 2 * (seq_len + 1) / 2 * d


def gated_ff_flops(d, f):
    return 3 * 2 * d * f


def expert_flops_per_pair(c):
    """One (token, expert) pair through W1, W3 and W2."""
    return gated_ff_flops(c["d_model"], c["d_expert"])


def forward_flops_per_token(c, seq_len, pairs_per_token_layer):
    """Forward operations of one token through the cut stack:
    `n_dense_layers` conv layers with the dense feed-forward, then
    periods of attention, conv, conv, conv with the router over all
    experts and `pairs_per_token_layer` pairs computed by the experts
    held here, then the head over the vocabulary slice."""
    d = c["d_model"]
    total = 2 * d * c["vocab"]
    for i in range(c["n_layers"]):
        dense = i < c["n_dense_layers"]
        attn = not dense and (i - c["n_dense_layers"]) % 4 == 0
        total += attention_mixer_flops(c, seq_len) if attn \
            else conv_mixer_flops(c)
        total += gated_ff_flops(d, c["d_dense"]) if dense else (
            2 * d * c["n_experts"]
            + pairs_per_token_layer * expert_flops_per_pair(c))
    return total


def train_flops_per_token(c, seq_len, pairs_per_step):
    """Forward and backward (3x the forward) a token; `pairs_per_step`
    is the counted (token, held expert) pairs of a step, all expert
    layers together."""
    n_moe = c["n_layers"] - c["n_dense_layers"]
    tokens = c["batch"] * seq_len
    return 3 * forward_flops_per_token(
        c, seq_len, pairs_per_step / (n_moe * tokens))


def flash_attention_train_flops(c, seq_len):
    """Causal attention of one step, forward and backward: two products
    forward and five backward (FlashAttention-2's count; what a kernel
    recomputes is not counted), each 2 * T(T+1)/2 * head operations a
    query head."""
    head = c["d_model"] // c["n_heads"]
    one = 2 * seq_len * (seq_len + 1) / 2 * head
    n_attn = sum((i - c["n_dense_layers"]) % 4 == 0
                 for i in range(c["n_dense_layers"], c["n_layers"]))
    return 7 * one * c["n_heads"] * c["batch"] * n_attn


def moe_experts_cost(c, pairs_per_step, bytes_per=2):
    """The grouped products of one step, all expert layers: operations
    of the counted pairs forward and backward, and the bytes of the
    held experts' weights, read forward, read again for the input's
    gradient and written once as the weights' gradient."""
    n_moe = c["n_layers"] - c["n_dense_layers"]
    weights = c["experts_held"] * 3 * c["d_model"] * c["d_expert"]
    return {"flops": 3 * pairs_per_step * expert_flops_per_pair(c),
            "bytes": 3 * n_moe * weights * bytes_per}
