"""Operations and bytes reckoned from shapes, the same whatever
implements the step. A multiply-add counts two operations. Every
function takes the configuration's sizes as a dict (`d_model`,
`d_inner`, `n_heads`, `n_layers`, `vocab`) and returns plain numbers."""
import re


def _attn_proj_flops(d):
    return 2 * 4 * d * d            # q, k, v and the output projection


def _ffn_flops(d, f):
    return 2 * 2 * d * f


def encoder_flops_per_token(c, src_len):
    """Forward operations of the encoder for one source token among
    `src_len` (full self-attention)."""
    d = c["d_model"]
    attn = 2 * 2 * src_len * d      # scores and the weighted sum
    return c["n_layers"] * (_attn_proj_flops(d) + attn
                            + _ffn_flops(d, c["d_inner"]))


def decoder_flops_per_token(c, src_len, self_len, with_cross_kv):
    """Forward operations of the decoder for one target token that
    attends to `self_len` target positions and `src_len` source
    positions. `with_cross_kv` counts the projection of the source
    into this token's share of the cross keys and values (training
    projects them every step; a server does so once a prompt)."""
    d = c["d_model"]
    self_attn = _attn_proj_flops(d) + 2 * 2 * self_len * d
    cross = 2 * 2 * d * d + 2 * 2 * src_len * d   # q and out, attend
    if with_cross_kv:
        cross += 2 * 2 * d * d
    layers = c["n_layers"] * (self_attn + cross
                              + _ffn_flops(d, c["d_inner"]))
    return layers + 2 * d * c["vocab"]


def train_flops_per_target_token(c, seq_len):
    """Forward and backward (3x the forward) of one sentence pair of
    `seq_len` source and `seq_len` target positions, per target token.
    Causal self-attention is counted at its mean length, (T + 1) / 2."""
    fwd = encoder_flops_per_token(c, seq_len) \
        + decoder_flops_per_token(c, seq_len, (seq_len + 1) / 2, True)
    return 3 * fwd


def serve_flops_per_output_token(c, src_len, out_len, miss_share):
    """Decoder operations of one output token at the mean cache length
    of a request of `out_len` positions, plus the share of an encoder
    pass (and of the cross key and value projection) that a prompt
    miss costs, spread over the request's out_len - 1 output tokens."""
    dec = decoder_flops_per_token(c, src_len, out_len / 2, False)
    d = c["d_model"]
    prefill = src_len * (encoder_flops_per_token(c, src_len)
                         + c["n_layers"] * 2 * 2 * d * d)
    return dec + miss_share * prefill / (out_len - 1)


def decoder_weight_bytes(c, bytes_per=4):
    d, f = c["d_model"], c["d_inner"]
    per_layer = 8 * d * d + 2 * d * f + f + d + 6 * d
    return bytes_per * (c["n_layers"] * per_layer + d * c["vocab"])


def decode_tick_min_bytes(c, live_lanes, self_len, src_len,
                          bytes_per=4):
    """Least bytes one decode tick must read: the decoder's weights
    and output table once, and for each live lane its self keys and
    values up to `self_len` and its cross keys and values."""
    d = c["d_model"]
    kv = 2 * c["n_layers"] * d * bytes_per
    return decoder_weight_bytes(c, bytes_per) \
        + live_lanes * kv * (self_len + src_len)


_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "f32": 4,
                "s32": 4, "u32": 4, "f64": 8, "s64": 8, "u64": 8}


def hlo_call_bytes(text):
    """Bytes a kernel call must move to and from HBM, from the shapes
    in its HLO text "%k = <results> custom-call(<operands>),
    attributes...": every result written once and every operand read
    once. A buffer whose layout names another memory space ("S(1)":
    the compiler keeps it on the chip) moves nothing through HBM and is
    left out, as are the attributes (which may repeat the operands'
    shapes)."""
    _, _, rest = text.partition(" = ")
    m = re.search(r"\s[a-z][a-z\-]*\(", rest)
    if not m:
        raise ValueError(f"no operation in HLO text {text[:80]!r}")
    depth, end = 0, None
    for i in range(m.end() - 1, len(rest)):
        depth += rest[i] == "("
        depth -= rest[i] == ")"
        if depth == 0:
            end = i
            break
    total = 0
    for dtype, dims, layout in re.findall(
            r"([a-z]+[0-9a-z]*)\[([0-9,]*)\](\{[^{}]*\})?", rest[:end]):
        if "S(" in layout:
            continue
        if dtype not in _DTYPE_BYTES:
            raise ValueError(f"unknown element type {dtype!r} in "
                             f"{text[:80]!r}")
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def kernel_roofline_share(trace, prefix, peaks):
    """Share of its roofline, in percent, that the kernels whose stable
    names start with `prefix` reached in a reduced trace: the least
    time the chip could take for the bytes their calls must move (both
    kernels here are bound by bytes, not by operations) over the device
    time of their events. None where the trace holds no such event."""
    least = spent = 0.0
    for name, seconds in trace["op_s"].items():
        if not name.startswith(prefix):
            continue
        calls = trace["op_count"][name] / trace["devices"]
        cost = {"flops": 0,
                "bytes": calls * hlo_call_bytes(trace["signatures"][name])}
        least += roofline_seconds(cost, peaks)
        spent += seconds
    return 100.0 * least / spent if spent else None


def roofline_seconds(cost, peaks):
    """The least time the chip could take: the larger of operations
    over peak operations a second and bytes over peak bytes a second."""
    return max(cost["flops"] / peaks["flops_per_s"],
               cost["bytes"] / peaks["hbm_bytes_per_s"])
