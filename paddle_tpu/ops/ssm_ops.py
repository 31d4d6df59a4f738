"""State-space (Mamba-2, Dao & Gu '24) token mixing on the serve
engine: a lane carries a scan state `S [H, P, N]` float32 and the last
`K - 1` inputs of a causal depthwise convolution, both indexed by LANE
(not by block table: their size does not grow with the context).

    xBC <- silu(conv1d_causal_depthwise(xBC, K) + b)   (`causal_conv_tail`)
    dt  <- softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        (`mamba2_step`, one
    y_t = S_t C_t + D x_t                               token of every lane;
                                                        `mamba2_chunk_scan`,
                                                        a chunk of one lane)
    y   <- group_rms_norm(y * silu(z)) * w              (`gated_group_rms_norm`)

Head h of H reads group h // (H / G) of B and C. A chunk computes the
recurrence in the chunked (state-space dual) form: inside a block of
`block` positions a masked product, across blocks the carried state.
A row whose position is 0 starts from zero state and an empty tail
whatever the lane held (a lane is reused by the next request); padded
rows of a chunk take dt = 0, which leaves S as it is, and do not enter
the tail; a lane whose gate is 0 keeps both bit for bit.

No reference counterpart (Fluid 1.x has no state-space layer). State,
dt, A, the decay and the state update are float32; x, B, C and the
tail keep the activations' dtype, products accumulate in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register_op


def _f32(x):
    return x.astype(jnp.float32)


def _conv_silu(seq, w, b, n):
    """seq [..., K-1+n, W] float32 -> silu(conv + b) [..., n, W]: tap
    K-1 of Filter [W, K] is the present; b None: no bias."""
    taps = w.shape[1]
    wf = _f32(w)
    conv = sum(seq[..., j:j + n, :] * wf[:, j] for j in range(taps))
    return jax.nn.silu(conv if b is None else conv + _f32(b))


@register_op("causal_conv_tail", differentiable=False,
             stop_gradient_slots=("X", "Tail", "Filter", "Bias", "Lane",
                                  "Len", "Pos", "Gate"))
def causal_conv_tail(ctx):
    """silu(causal depthwise convolution + bias) with the K-1 inputs
    before the first row carried in Tail [R, K-1, W] (also the op's
    output TailOut, in place). X [N, W]; Filter [W, K]; Bias [W] or
    none.

    A chunk (Lane [1] given): the N rows are consecutive positions of
    lane Lane from position Pos [1]; the first Len [1] are real. The
    lane's tail becomes its last K-1 real inputs (older ones stay
    where the chunk is shorter than K-1).
    A tick (Gate [R] given, N = R): row r is lane r's next input at
    position Pos [R]; lanes with gate 0 keep their tail.
    Either way a row at position 0 has nothing before it."""
    x, tail = ctx.input("X"), ctx.input("Tail")
    w, b = ctx.input("Filter"), ctx.input("Bias")
    keep = w.shape[1] - 1
    pos = ctx.input("Pos").reshape(-1)
    with jax.named_scope("ssm.conv"):
        if ctx.input("Lane") is not None:
            lane = ctx.input("Lane").reshape(()).astype(jnp.int32)
            n = ctx.input("Len").reshape(()).astype(jnp.int32)
            before = jnp.where(pos[0] == 0, 0, tail[lane]).astype(x.dtype)
            seq = jnp.concatenate([before, x], 0)       # [K-1+N, W]
            out = _conv_silu(_f32(seq), w, b, x.shape[0])
            new = jax.lax.dynamic_slice_in_dim(seq, n, keep, 0)
            tail = jax.lax.dynamic_update_index_in_dim(
                tail, new.astype(tail.dtype), lane, 0)
        else:
            on = ctx.input("Gate").reshape(-1, 1, 1) > 0
            before = jnp.where(pos.reshape(-1, 1, 1) == 0, 0,
                               tail).astype(x.dtype)
            seq = jnp.concatenate([before, x[:, None]], 1)  # [R, K, W]
            out = _conv_silu(_f32(seq), w, b, 1)[:, 0]
            tail = jnp.where(on, seq[:, 1:].astype(tail.dtype), tail)
    return {"Out": out.astype(x.dtype), "TailOut": tail}


def _split(xbc, dt, dt_bias, a_log, heads, head_dim, groups, n):
    """(x [.., G, H/G, P], B [.., G, N], C [.., G, N], dt [.., G, H/G]
    float32 after softplus, A [G, H/G] float32)."""
    lead = xbc.shape[:-1]
    d_inner = heads * head_dim
    per = heads // groups
    x = xbc[..., :d_inner].reshape(*lead, groups, per, head_dim)
    bm = xbc[..., d_inner:d_inner + groups * n].reshape(*lead, groups, n)
    cm = xbc[..., d_inner + groups * n:].reshape(*lead, groups, n)
    dt = jax.nn.softplus(_f32(dt) + _f32(dt_bias)).reshape(
        *lead, groups, per)
    return x, bm, cm, dt, -jnp.exp(_f32(a_log)).reshape(groups, per)


def chunk_scan(x, bm, cm, dt, a, s0, block):
    """The recurrence over T positions from state s0, a `block` of
    positions at a time. x [T, G, r, P]; bm, cm [T, G, N]; dt [T, G, r]
    float32 (0 where a position is padding); a [G, r]; s0 [G, r, P, N]
    float32. Returns (y [T, G, r, P] float32 without the D term, the
    state after position T-1)."""
    t = x.shape[0]
    size = block if t % block == 0 else t
    nb = t // size

    def cut(v):
        return v.reshape(nb, size, *v.shape[1:])

    causal = jnp.tril(jnp.ones((size, size), bool))

    def body(s, blk):
        xk, bk, ck, dtk = blk
        acs = jnp.cumsum(dtk * a, 0)                    # [L, G, r] <= 0
        # inside the block: position l reads source s <= l through
        # C_l.B_s, the decay between them and the source's dt
        cb = jnp.einsum("lgn,sgn->gls", ck, bk,
                        preferred_element_type=jnp.float32)
        seg = acs[:, None] - acs[None, :]               # [l, s, G, r]
        decay = jnp.exp(jnp.where(causal[..., None, None], seg,
                                  -jnp.inf))
        wgt = jnp.transpose(decay * dtk[None], (2, 3, 0, 1)) \
            * cb[:, None]                               # [G, r, l, s]
        y = jnp.einsum("grls,sgrp->lgrp", wgt, _f32(xk))
        # what the state before the block adds, decayed to position l
        y = y + jnp.einsum("lgn,grpn->lgrp", _f32(ck), s) \
            * jnp.exp(acs)[..., None]
        # the state after the block
        left = jnp.exp(acs[-1][None] - acs) * dtk       # [L, G, r]
        s = jnp.exp(acs[-1])[..., None, None] * s + jnp.einsum(
            "sgrp,sgn->grpn", _f32(xk) * left[..., None], _f32(bk))
        return s, y

    s, y = jax.lax.scan(body, s0, (cut(x), cut(bm), cut(cm), cut(dt)))
    return y.reshape(t, *y.shape[2:]), s


@register_op("mamba2_chunk_scan", differentiable=False,
             stop_gradient_slots=("XBC", "Dt", "DtBias", "ALog", "D",
                                  "State", "Lane", "Len", "Pos"))
def mamba2_chunk_scan(ctx):
    """A prefill chunk of ONE lane through the recurrence, from the
    lane's stored state. XBC [T, H*P + 2*G*N] (after the convolution);
    Dt [T, H] (before bias and softplus); DtBias, ALog, D [H]; State
    [R, H, P, N] float32 (also the output StateOut, in place); Lane
    [1]; Len [1], the chunk's real rows; Pos [1], the position of row
    0 (0: the lane starts from zero state). attrs n_groups, block (H, P
    and N are State's). Y [T, H*P] float32; the lane's state becomes
    the state after its last real row."""
    xbc, state = ctx.input("XBC"), ctx.input("State")
    heads, p, n = state.shape[1], state.shape[2], state.shape[3]
    groups = int(ctx.attr("n_groups"))
    lane = ctx.input("Lane").reshape(()).astype(jnp.int32)
    length = ctx.input("Len").reshape(()).astype(jnp.int32)
    t = xbc.shape[0]
    with jax.named_scope("ssm.scan"):
        x, bm, cm, dt, a = _split(xbc, ctx.input("Dt"),
                                  ctx.input("DtBias"), ctx.input("ALog"),
                                  heads, p, groups, n)
        dt = jnp.where((jnp.arange(t) < length)[:, None, None], dt, 0.0)
        s0 = jnp.where(ctx.input("Pos").reshape(()) == 0, 0.0,
                       _f32(state[lane])).reshape(groups, -1, p, n)
        y, s = chunk_scan(x, bm, cm, dt, a, s0, int(ctx.attr("block")))
        y = y + _f32(ctx.input("D")).reshape(groups, -1, 1) * _f32(x)
        state = jax.lax.dynamic_update_index_in_dim(
            state, s.reshape(heads, p, n).astype(state.dtype), lane, 0)
    return {"Y": y.reshape(t, heads * p), "StateOut": state}


@register_op("mamba2_step", differentiable=False,
             stop_gradient_slots=("XBC", "Dt", "DtBias", "ALog", "D",
                                  "State", "Gate", "Pos"))
def mamba2_step(ctx):
    """One step of the recurrence for EVERY lane: row r of XBC [R, H*P
    + 2*G*N] and Dt [R, H] is lane r's token at position Pos [R]. State
    [R, H, P, N] float32 (also StateOut, in place); Gate [R] 0/1: a
    lane with gate 0 keeps its state bit for bit (its row of Y is
    junk). Y [R, H*P] float32."""
    xbc, state = ctx.input("XBC"), ctx.input("State")
    r, heads, p, n = state.shape
    groups = int(ctx.attr("n_groups"))
    with jax.named_scope("ssm.step"):
        x, bm, cm, dt, a = _split(xbc, ctx.input("Dt"),
                                  ctx.input("DtBias"), ctx.input("ALog"),
                                  heads, p, groups, n)
        s = state.reshape(r, groups, -1, p, n)
        first = ctx.input("Pos").reshape(r, 1, 1, 1, 1) == 0
        grown = jnp.exp(dt * a)[..., None, None] \
            * jnp.where(first, 0.0, _f32(s)) \
            + (dt[..., None] * _f32(x))[..., None] \
            * _f32(bm)[:, :, None, None, :]
        y = jnp.sum(grown * _f32(cm)[:, :, None, None, :], -1) \
            + _f32(ctx.input("D")).reshape(groups, -1, 1) * _f32(x)
        on = ctx.input("Gate").reshape(r, 1, 1, 1, 1) > 0
        state = jnp.where(on, grown.astype(state.dtype),
                          s).reshape(state.shape)
    return {"Y": y.reshape(r, heads * p), "StateOut": state}


@register_op("gated_group_rms_norm", differentiable=False)
def gated_group_rms_norm(ctx):
    """rms_norm in `groups` equal groups of the last axis of X * silu(Z),
    times Scale [D] (Mamba-2's gated norm, the gate before the norm).
    X [N, D] float32; Z [N, D]. Out in Z's dtype; statistics float32.
    attr `norm_before_gate` (Gated DeltaNet's order): rms_norm of each
    group of X times Scale [D / groups], one weight every group shares,
    then times silu(Z)."""
    x, z = _f32(ctx.input("X")), ctx.input("Z")
    groups = int(ctx.attr("groups"))
    eps = float(ctx.attr("epsilon", 1e-5))
    if ctx.attr("norm_before_gate", False):
        v = x.reshape(x.shape[0], groups, -1)
        v = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps) \
            * _f32(ctx.input("Scale"))
        return {"Out": (v.reshape(x.shape) * jax.nn.silu(_f32(z)))
                .astype(z.dtype)}
    v = (x * jax.nn.silu(_f32(z))).reshape(x.shape[0], groups, -1)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps)
    return {"Out": (v.reshape(x.shape) * _f32(ctx.input("Scale")))
            .astype(z.dtype)}
