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
    AMP. Chosen, Load and PairsHere are free when unfetched."""
    from .. import amp
    from ..parallel import moe as moe_mod

    x = ctx.input("X")
    shape = x.shape
    xt = x.reshape(-1, shape[-1])
    out, idx, load, pairs = moe_mod.moe_dropless(
        xt, ctx.input("GateW"), ctx.input("ExpertBias"),
        ctx.input("W13"), ctx.input("W2"),
        first_held=int(ctx.attr("first_held", 0)),
        top_k=int(ctx.attr("top_k", 1)),
        norm_topk=bool(ctx.attr("norm_topk", True)),
        scaling=float(ctx.attr("scaling", 1.0)),
        compute_dtype=jnp.bfloat16 if amp.enabled() else None,
        scope=ctx.attr("scope", "moe"))
    return {"Out": out.reshape(shape), "Chosen": idx, "Load": load,
            "PairsHere": pairs}
