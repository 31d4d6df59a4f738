"""Gated delta-rule token mixing (Gated DeltaNet; Yang, Kautz &
Hatamizadeh '24) on the serve engine: a lane carries a matrix state
`S [H, Dk, Dv]` float32, indexed by LANE (not by block table), beside
the convolution tail of `causal_conv_tail` (ops/ssm_ops.py).

    q <- l2norm(q) * Dk^-1/2;  k <- l2norm(k)            (per head)
    beta = sigmoid(b) (times 2 where negative eigenvalues are allowed)
    g = -exp(A_log) * softplus(a + dt_bias);  alpha = exp(g)
    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

`gated_delta_step` is one token of every lane; `gated_delta_chunk` a
prefill chunk of one lane in the chunked (WY) form: inside a block of
`block` positions the UT transform `T = (I + tril(diag(beta) K K^T *
Gamma, -1))^-1` by a triangular solve, `U = T diag(beta) V` and `W = T
diag(beta gamma) K`, for every block at once; across blocks the state,
one block after another: `u = U - W S`, `o = (Q gamma) S + (Q K^T *
Gamma, causal) u`, `S <- gamma_L S + (K gamma_L / gamma)^T u`.

A row whose position is 0 starts from zero state whatever the lane held
(a lane is reused by the next request); padded rows of a chunk take
beta = 0 and g = 0 (alpha = 1), which leaves S as it is; a lane whose
gate is 0 keeps its state bit for bit. Everything that touches the
state is float32: products that contract it run at precision HIGHEST,
the tick's by elementwise sums.

No reference counterpart (Fluid 1.x has no linear-attention layer).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register_op

HIGHEST = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6


def _f32(x):
    return x.astype(jnp.float32)


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _inputs(ctx, rows, heads, dk, dv):
    """(q, k [rows, H, Dk], v [rows, H, Dv], beta, g [rows, H]) float32."""
    q = l2norm(_f32(ctx.input("Q")).reshape(rows, heads, dk)) * dk ** -0.5
    k = l2norm(_f32(ctx.input("K")).reshape(rows, heads, dk))
    v = _f32(ctx.input("V")).reshape(rows, heads, dv)
    beta = jax.nn.sigmoid(_f32(ctx.input("Beta")))
    if ctx.attr("allow_neg_eigval", False):
        beta = 2.0 * beta
    g = -jnp.exp(_f32(ctx.input("ALog"))) * jax.nn.softplus(
        _f32(ctx.input("A")) + _f32(ctx.input("DtBias")))
    return q, k, v, beta, g


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def chunk_delta(q, k, v, beta, g, s0, block):
    """The recurrence over T positions from state s0, a `block` of
    positions at a time. q, k [T, H, Dk]; v [T, H, Dv]; beta, g [T, H]
    float32 (0 where a position is padding); s0 [H, Dk, Dv] float32.
    Returns (o [T, H, Dv] float32, the state after position T-1)."""
    t, heads, dk = k.shape
    size = block if t % block == 0 else t
    nb = t // size

    def cut(x):         # [T, H, ...] -> [nb, H, L, ...]
        x = x.reshape(nb, size, heads, *x.shape[2:])
        return jnp.moveaxis(x, 1, 2)

    qb, kb, vb, bb, gb = (cut(x) for x in (q, k, v, beta, g))
    gc = jnp.cumsum(gb, -1)                          # [nb, H, L] <= 0
    rows = jnp.arange(size)
    causal = rows[:, None] >= rows[None, :]
    # Gamma[l, r] = gamma_l / gamma_r for r <= l, else 0
    gamma = jnp.exp(jnp.where(causal, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))             # [nb, H, L, L]
    a = jnp.where(rows[:, None] > rows[None, :],
                  _mm("bhlk,bhrk->bhlr", kb, kb) * gamma
                  * bb[..., :, None], 0.0)
    eye = jnp.eye(size, dtype=jnp.float32)
    rhs = jnp.concatenate([vb * bb[..., None],
                           kb * (bb * jnp.exp(gc))[..., None]], -1)
    uw = jax.lax.linalg.triangular_solve(
        eye + a, rhs, left_side=True, lower=True, unit_diagonal=True)
    u_all, w_all = uw[..., :vb.shape[-1]], uw[..., vb.shape[-1]:]
    qk = _mm("bhlk,bhrk->bhlr", qb, kb) * gamma      # causal, diag in
    q_in = qb * jnp.exp(gc)[..., None]
    k_out = kb * jnp.exp(gc[..., -1:] - gc)[..., None]

    def body(s, blk):
        u, w, qkb, qi, ko, last = blk
        new = u - _mm("hlk,hkv->hlv", w, s)
        o = _mm("hlk,hkv->hlv", qi, s) + _mm("hlr,hrv->hlv", qkb, new)
        s = jnp.exp(last)[:, None, None] * s \
            + _mm("hlk,hlv->hkv", ko, new)
        return s, o

    s, o = jax.lax.scan(body, s0, (u_all, w_all, qk, q_in, k_out,
                                   gc[..., -1]))
    return jnp.moveaxis(o, 2, 1).reshape(t, heads, -1), s


@register_op("gated_delta_chunk", differentiable=False,
             stop_gradient_slots=("Q", "K", "V", "Beta", "A", "DtBias",
                                  "ALog", "State", "Lane", "Len", "Pos"))
def gated_delta_chunk(ctx):
    """A prefill chunk of ONE lane through the recurrence, from the
    lane's stored state. Q, K [T, H*Dk]; V [T, H*Dv] (after the
    convolution); Beta, A [T, H] (before sigmoid and softplus); DtBias,
    ALog [H]; State [R, H, Dk, Dv] float32 (also StateOut, in place);
    Lane [1]; Len [1], the chunk's real rows; Pos [1], the position of
    row 0 (0: the lane starts from zero state). attrs block,
    allow_neg_eigval. Y [T, H*Dv] float32; the lane's state becomes the
    state after its last real row."""
    state = ctx.input("State")
    heads, dk, dv = state.shape[1:]
    lane = ctx.input("Lane").reshape(()).astype(jnp.int32)
    length = ctx.input("Len").reshape(()).astype(jnp.int32)
    t = ctx.input("Q").shape[0]
    with jax.named_scope("gdn.chunk"):
        q, k, v, beta, g = _inputs(ctx, t, heads, dk, dv)
        real = (jnp.arange(t) < length)[:, None]
        beta, g = jnp.where(real, beta, 0.0), jnp.where(real, g, 0.0)
        s0 = jnp.where(ctx.input("Pos").reshape(()) == 0, 0.0,
                       _f32(state[lane]))
        o, s = chunk_delta(q, k, v, beta, g, s0, int(ctx.attr("block")))
        state = jax.lax.dynamic_update_index_in_dim(
            state, s.astype(state.dtype), lane, 0)
    return {"Y": o.reshape(t, heads * dv), "StateOut": state}


@register_op("gated_delta_step", differentiable=False,
             stop_gradient_slots=("Q", "K", "V", "Beta", "A", "DtBias",
                                  "ALog", "State", "Gate", "Pos"))
def gated_delta_step(ctx):
    """One step of the recurrence for EVERY lane: row r of Q, K, V,
    Beta and A (as gated_delta_chunk has them) is lane r's token at
    position Pos [R]. State [R, H, Dk, Dv] float32 (also StateOut, in
    place); Gate [R] 0/1: a lane with gate 0 keeps its state bit for
    bit (its row of Y is junk). Y [R, H*Dv] float32."""
    state = ctx.input("State")
    r, heads, dk, dv = state.shape
    with jax.named_scope("gdn.step"):
        q, k, v, beta, g = _inputs(ctx, r, heads, dk, dv)
        first = ctx.input("Pos").reshape(r, 1, 1, 1) == 0
        s = jnp.exp(g)[..., None, None] \
            * jnp.where(first, 0.0, _f32(state))
        read = jnp.sum(k[..., None] * s, -2)              # S^T k
        s = s + k[..., None] * (beta[..., None] * (v - read))[:, :, None]
        o = jnp.sum(q[..., None] * s, -2)                 # S^T q
        on = ctx.input("Gate").reshape(r, 1, 1, 1) > 0
        state = jnp.where(on, s.astype(state.dtype), state)
    return {"Y": o.reshape(r, heads * dv), "StateOut": state}

