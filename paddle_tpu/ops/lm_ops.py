"""Ops of today's decoder-only language models that the reference's
op set (2019) has no counterpart for: RMS norm, rotary positions, the
gate of a gated feed-forward, the gated short convolution as a token
mixer, and the dropless routed expert layer (the routing math is in
parallel/moe.py beside the capacity routing). Grouped-query attention
needs no op of its own: the `attention` op takes fewer key-value heads
than query heads (ops/nn_ops.py).

Each kernel computes in float32 where rounding matters (statistics,
angles, the router) and returns its input's dtype; gradients come from
the generic vjp maker.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register_op


@register_op("rms_norm")
def rms_norm(ctx):
    """y = x / sqrt(mean(x^2, last axis) + eps) * scale (Zhang &
    Sennrich '19). X: [..., D]; Scale: [D]. On amp's BLACK list: the
    statistics are float32."""
    x = ctx.input("X")
    scale = ctx.input("Scale")
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                        + ctx.attr("epsilon", 1e-5))
    return {"Y": (xf * inv * scale.astype(jnp.float32)).astype(x.dtype)}


def rotary_tables(length, dim, theta):
    """cos, sin [length, dim] of the default rotary embedding (Su et
    al. '21 as Hugging Face lays it out): frequency i of dim/2 is
    theta^(-2i/dim), and the table repeats the dim/2 angles twice."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                                / dim))
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq
    angle = jnp.concatenate([angle, angle], axis=-1)
    return jnp.cos(angle), jnp.sin(angle)


@register_op("rotary_embedding")
def rotary_embedding(ctx):
    """Rotary positions on X [B, T, H, D], positions 0..T-1: x * cos +
    rotate_half(x) * sin with rotate_half([a, b]) = [-b, a]."""
    x = ctx.input("X")
    cos, sin = rotary_tables(x.shape[1], x.shape[-1],
                             float(ctx.attr("theta", 10000.0)))
    xf = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-xf[..., half:], xf[..., :half]], axis=-1)
    out = xf * cos[None, :, None, :] + rot * sin[None, :, None, :]
    return {"Out": out.astype(x.dtype)}


@register_op("swiglu")
def swiglu(ctx):
    """silu(a) * b for X = [a, b] side by side on the last axis
    (Shazeer '20): the gate of a feed-forward whose two input
    projections are one matrix product."""
    x = ctx.input("X")
    f = x.shape[-1] // 2
    a = x[..., :f].astype(jnp.float32)
    b = x[..., f:].astype(jnp.float32)
    return {"Out": (jax.nn.silu(a) * b).astype(x.dtype)}


@register_op("relu2")
def relu2(ctx):
    """relu(x)^2: the activation of a feed-forward that is not gated
    (one up matrix; Nemotron-H's `mlp_hidden_act`)."""
    x = ctx.input("X")
    return {"Out": jnp.square(jax.nn.relu(x.astype(jnp.float32)))
            .astype(x.dtype)}


@register_op("short_conv")
def short_conv(ctx):
    """The inside of a gated short convolution (Liquid's LFM2 mixer):
    X [B, T, 3D] is [b, c, z] side by side; v = b * z; a depthwise
    causal convolution of v over time with Filter [D, K] (tap K-1 is
    the present, zeros before the sequence starts); Out = c * conv(v),
    [B, T, D]. Shifts and products only: XLA fuses them into one
    pass."""
    x = ctx.input("X")
    w = ctx.input("Filter")
    d, taps = w.shape
    xf = x.astype(jnp.float32)
    b, c, z = xf[..., :d], xf[..., d:2 * d], xf[..., 2 * d:]
    v = b * z
    t = v.shape[1]
    padded = jnp.pad(v, ((0, 0), (taps - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    conv = sum(padded[:, j:j + t] * wf[:, j] for j in range(taps))
    return {"Out": (c * conv).astype(x.dtype)}


@register_op("moe_dropless", stop_gradient_slots=("ExpertBias",))
def moe_dropless(ctx):
    """One rank's share of an expert layer whose routing drops nothing
    (parallel/moe.py `moe_dropless`). X [..., D]; GateW [D, E];
    ExpertBias [E] (enters the choice only); W13 [n_held, D, 2F]; W2
    [n_held, F, D]. On amp's KEEP list: the router sees X as it comes
    (float32 from an RMS norm) and the experts run in bfloat16 under
    AMP. Chosen, Load and PairsHere are free when unfetched. attr
    activation "relu2": W13 [n_held, D, F], no gate. ExpertX [..., De]
    (optional): the experts' input where it is not the router's; W13,
    W2 and Out are then De wide."""
    from .. import amp
    from ..parallel import moe as moe_mod

    x = ctx.input("X")
    shape = x.shape
    xt = x.reshape(-1, shape[-1])
    more = {}
    if ctx.input("ExpertX") is not None:
        ex = ctx.input("ExpertX")
        shape = ex.shape
        more["expert_x"] = ex.reshape(-1, shape[-1])
    if ctx.attr("activation", "swiglu") != "swiglu":
        more["activation"] = ctx.attr("activation")
    out, idx, load, pairs = moe_mod.moe_dropless(
        xt, ctx.input("GateW"), ctx.input("ExpertBias"),
        ctx.input("W13"), ctx.input("W2"),
        first_held=int(ctx.attr("first_held", 0)),
        top_k=int(ctx.attr("top_k", 1)),
        norm_topk=bool(ctx.attr("norm_topk", True)),
        scaling=float(ctx.attr("scaling", 1.0)),
        compute_dtype=jnp.bfloat16 if amp.enabled() else None,
        scope=ctx.attr("scope", "moe"), **more)
    return {"Out": out.reshape(shape), "Chosen": idx, "Load": load,
            "PairsHere": pairs}


# ---------------------------------------------------------------------
# Latent attention (MLA) and the sparse-attention indexer (DSA) of the
# glm_moe_dsa family (models/glm_moe_dsa.py). The pool side (scores over
# a lane's cached indexer keys, the selection, attention over the
# selected rows of the latent pool) is in ops/paged_ops.py.
# ---------------------------------------------------------------------
def rope_interleaved(x, pos, theta):
    """Rotary positions on the pairs (2i, 2i+1) of x's last axis
    (`rope_interleave`): pair i of row n turns by pos[n] *
    theta^(-2i/dim). x [N, ..., dim]; pos [N]. float32 in, float32
    out."""
    dim = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = pos.astype(jnp.float32)[:, None] * inv
    angle = angle.reshape(angle.shape[:1] + (1,) * (x.ndim - 2)
                          + angle.shape[1:])
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


def _rms(x, g, eps):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                              + eps) * g.astype(jnp.float32)


def _dot(a, b):
    """a [..., k] x b [k, n] in the operands' dtype with float32
    accumulation, float32 out."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


@register_op("mla_project", differentiable=False)
def mla_project(ctx):
    """The down- and up-projections of latent attention for N rows.

    X [N, D] (after the layer's RMS norm); Pos [N] int (cache position
    of each row); QA [D, rq], QANorm [rq], QB [rq, H*(dn+dr)]; KVA [D,
    rkv+dr], KVANorm [rkv], KVB [rkv, H*(dn+dv)]. Outputs: CQ [N, rq]
    (the query latent, which the indexer reads too); Latent [N, rkv+dr]
    = [RMSNorm(c_KV) | rotated k_r], the row the cache holds; QLat [N,
    H, rkv+dr] = [W_UK,h^T q_nope,h | rotated q_rope,h], the query with
    the key up-projection absorbed, so that its product with a cache
    row is the head's score (decode and prefill alike: no key is ever
    expanded a head). attr row_width (default rkv + dr): Latent and
    QLat are that wide, zeros past rkv + dr. Norms and angles float32;
    products in X's dtype with float32 accumulation."""
    x, pos = ctx.input("X"), ctx.input("Pos").reshape(-1)
    h = int(ctx.attr("n_heads"))
    dn, dr = int(ctx.attr("qk_nope_head_dim")), \
        int(ctx.attr("qk_rope_head_dim"))
    eps, theta = float(ctx.attr("epsilon", 1e-5)), \
        float(ctx.attr("theta", 10000.0))
    dt = x.dtype
    kvb = ctx.input("KVB")
    rkv = kvb.shape[0]
    with jax.named_scope("glm.mla_proj"):
        cq = _rms(_dot(x, ctx.input("QA")), ctx.input("QANorm"),
                  eps).astype(dt)
        q = _dot(cq, ctx.input("QB")).reshape(-1, h, dn + dr)
        q_rope = rope_interleaved(q[..., dn:], pos, theta)
        ckv = _dot(x, ctx.input("KVA"))
        c = _rms(ckv[:, :rkv], ctx.input("KVANorm"), eps)
        kr = rope_interleaved(ckv[:, rkv:], pos, theta)
        w_uk = kvb.reshape(rkv, h, -1)[:, :, :dn]
        q_lat = jnp.einsum("nhd,rhd->nhr", q[..., :dn].astype(dt), w_uk,
                           preferred_element_type=jnp.float32)
    # a row of the pool is `row_width` numbers wide (the latent row
    # rounded up to whole lane tiles); what lies past rkv + dr is zero
    # in rows and queries alike and adds nothing to a score
    pad = max(0, int(ctx.attr("row_width", 0)) - (rkv + dr))
    return {"CQ": cq,
            "Latent": jnp.concatenate(
                [c, kr, jnp.zeros((x.shape[0], pad), jnp.float32)],
                -1).astype(dt),
            "QLat": jnp.concatenate(
                [q_lat, q_rope,
                 jnp.zeros((x.shape[0], h, pad), jnp.float32)],
                -1).astype(dt)}


@register_op("mla_output", differentiable=False)
def mla_output(ctx):
    """The value up-projection after attention over latent rows: Ctx
    [N, H, rkv] (softmax-weighted sums of cache rows' latent part) x
    W_UV,h (the value columns of KVB [rkv, H*(dn+dv)]) -> [N, H*dv],
    what the output projection takes."""
    c, kvb = ctx.input("Ctx"), ctx.input("KVB")
    h = c.shape[1]
    dn = int(ctx.attr("qk_nope_head_dim"))
    with jax.named_scope("glm.mla_proj"):
        w_uv = kvb.reshape(kvb.shape[0], h, -1)[:, :, dn:]
        out = jnp.einsum("nhr,rhv->nhv", c.astype(kvb.dtype), w_uv,
                         preferred_element_type=jnp.float32)
    return {"Out": out.reshape(c.shape[0], -1).astype(kvb.dtype)}


@register_op("dsa_indexer_project", differentiable=False)
def dsa_indexer_project(ctx):
    """The indexer's projections for N rows. X [N, D] (after the
    layer's RMS norm); CQ [N, rq]; Pos [N]; IQ [rq, hi*di]; IK [D, di];
    IKNormW, IKNormB [di] (LayerNorm); IW [D, hi]. Outputs QI [N, hi,
    di] and KI [N, di] (the row the indexer's cache holds), both with
    rotary positions on their first `rope_dim` numbers, and W [N, hi]
    float32, scaled by hi^-0.5 * di^-0.5."""
    x, cq = ctx.input("X"), ctx.input("CQ")
    pos = ctx.input("Pos").reshape(-1)
    hi, ri = int(ctx.attr("n_heads")), int(ctx.attr("rope_dim"))
    theta = float(ctx.attr("theta", 10000.0))
    with jax.named_scope("glm.indexer"):
        qi = _dot(cq, ctx.input("IQ")).reshape(cq.shape[0], hi, -1)
        di = qi.shape[-1]
        qi = jnp.concatenate(
            [rope_interleaved(qi[..., :ri], pos, theta), qi[..., ri:]],
            -1)
        k = _dot(x, ctx.input("IK"))
        mean = k.mean(-1, keepdims=True)
        var = jnp.mean(jnp.square(k - mean), -1, keepdims=True)
        k = (k - mean) * jax.lax.rsqrt(var + 1e-6) \
            * ctx.input("IKNormW").astype(jnp.float32) \
            + ctx.input("IKNormB").astype(jnp.float32)
        k = jnp.concatenate(
            [rope_interleaved(k[..., :ri], pos, theta), k[..., ri:]], -1)
        w = _dot(x, ctx.input("IW")) * (hi ** -0.5 * di ** -0.5)
    return {"QI": qi.astype(x.dtype), "KI": k.astype(x.dtype), "W": w}


@register_op("lm_head", differentiable=False)
def lm_head(ctx):
    """Logits in float32: X [N, D] x W [D, V], operands in their own
    dtype, float32 accumulation and result."""
    return {"Out": _dot(ctx.input("X"), ctx.input("W"))}


@register_op("moe_tick_stats", differentiable=False)
def moe_tick_stats(ctx):
    """What a decode tick's live lanes sent to the experts held here:
    Chosen [N, k] int32 (a row's chosen experts), Active [N] 0/1.
    Outputs Pairs [1] int64 (pairs on held experts), Hit [1] int64
    (held experts with at least one pair), Load [n_held] int64."""
    chosen = ctx.input("Chosen")
    act = ctx.input("Active").reshape(-1, 1) > 0
    first, n = int(ctx.attr("first_held")), int(ctx.attr("n_held"))
    local = chosen - first
    held = (local >= 0) & (local < n) & act
    load = jnp.sum(
        (local[..., None] == jnp.arange(n)) & held[..., None],
        axis=(0, 1)).astype(jnp.int32)
    return {"Pairs": load.sum().reshape(1),
            "Hit": jnp.sum(load > 0).astype(jnp.int32).reshape(1),
            "Load": load}
