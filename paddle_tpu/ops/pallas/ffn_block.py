"""Whole-layer fused FFN block — the second half of PERF.md's
"whole-layer pallas fusion (attention+MLP epilogues)" lever.

One kernel computes the position-wise MLP

    out = relu(x @ W1 + b1) @ W2 + b2

so the [T, d_inner] hidden activation (the largest tensor in the
sub-layer: d_inner = 4*d_model) never touches HBM in forward, and the
backward kernel recomputes it from x (matmul-bound — cheaper than the
HBM round-trip at bench shapes) while accumulating dW1/dW2/db per
program.

Layout contract matches models/transformer._ffn with dropout=0:
x [B,T,D], W1 [D,F], b1 [F], W2 [F,D], b2 [D]; residual and the
following layer_norm stay outside (XLA fuses them into neighbors).

Gating mirrors attention_block: routed from the model by
PADDLE_TPU_FUSE_ATTN_BLOCK=1 (one knob = the whole fused layer),
disabled kernel-side by PADDLE_TPU_DISABLE_PALLAS_FFN_BLOCK=1.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from . import on_tpu
from .attention import _interp

__all__ = ["ffn_block", "ffn_block_reference", "usable"]

_GROUP_FWD = 2
_GROUP_BWD = 1


def usable(x, w1) -> bool:
    if os.environ.get("PADDLE_TPU_DISABLE_PALLAS_FFN_BLOCK") == "1":
        return False
    if not (on_tpu() or _interp()):
        return False
    if x.ndim != 3 or w1.ndim != 2:
        return False
    b, t, d = x.shape
    f = w1.shape[1]
    if w1.shape[0] != d:
        return False
    if not (t % 8 == 0 and d % 8 == 0 and f % 8 == 0
            and b % _GROUP_FWD == 0 and b % _GROUP_BWD == 0):
        return False
    # explicit VMEM estimate (f32 words) for BOTH kernels — the
    # backward additionally holds dw1/dw2 accumulators, doubling the
    # weight footprint, and is the binding case for weights-dominated
    # shapes
    fwd = (d * f * 2                        # W1 + W2 (f32 in-kernel)
           + _GROUP_FWD * (2 * t * d + t * f))
    bwd = (d * f * 4                        # W1+W2 + dw1+dw2 accums
           + _GROUP_BWD * (3 * t * d + 3 * t * f))
    return max(fwd, bwd) * 4 <= 12 * 1024 * 1024


def ffn_block_reference(x, w1, b1, w2, b2):
    """jnp oracle/fallback — same math, one op at a time."""
    xf = x.astype(jnp.float32)
    h = jax.nn.relu(xf @ w1.astype(jnp.float32)
                    + b1.astype(jnp.float32))
    out = h @ w2.astype(jnp.float32) + b2.astype(jnp.float32)
    return out.astype(x.dtype)


@jax.custom_vjp
def ffn_block(x, w1, b1, w2, b2):
    """x [B,T,D], w1 [D,F], b1 [F], w2 [F,D], b2 [D] -> [B,T,D]."""
    return _fwd_impl(x, w1, b1, w2, b2)


def _fwd(x, w1, b1, w2, b2):
    return _fwd_impl(x, w1, b1, w2, b2), (x, w1, b1, w2, b2)


def _bwd(res, g):
    return _bwd_impl(*res, g)


ffn_block.defvjp(_fwd, _bwd)


def _fwd_impl(x, w1, b1, w2, b2):
    from jax.experimental import pallas as pl

    b, t, d = x.shape
    f = w1.shape[1]
    grp = _GROUP_FWD

    def kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref):
        w1f = w1_ref[...].astype(jnp.float32)
        w2f = w2_ref[...].astype(jnp.float32)
        b1f = b1_ref[...].astype(jnp.float32)
        b2f = b2_ref[...].astype(jnp.float32)
        for g_i in range(grp):
            xf = x_ref[g_i].astype(jnp.float32)       # [T,D]
            h = jnp.maximum(xf @ w1f + b1f[None], 0.0)  # [T,F] in VMEM
            o_ref[g_i] = (h @ w2f + b2f[None]).astype(o_ref.dtype)

    x_spec = pl.BlockSpec((grp, t, d), lambda i: (i, 0, 0))
    out, = pl.pallas_call(
        kernel,
        grid=(b // grp,),
        in_specs=[x_spec,
                  pl.BlockSpec((d, f), lambda i: (0, 0)),
                  pl.BlockSpec((f,), lambda i: (0,)),
                  pl.BlockSpec((f, d), lambda i: (0, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=[x_spec],
        out_shape=[jax.ShapeDtypeStruct((b, t, d), x.dtype)],
        interpret=_interp(),
        name="ffn_block_fwd",
    )(x, w1, b1, w2, b2)
    return out


def _bwd_impl(x, w1, b1, w2, b2, g):
    from jax.experimental import pallas as pl

    b, t, d = x.shape
    f = w1.shape[1]
    grp = _GROUP_BWD
    n_prog = b // grp

    def kernel(x_ref, w1_ref, w2_ref, b1_ref, g_ref,
               dx_ref, dw1_ref, db1_ref, dw2_ref, db2_ref):
        w1f = w1_ref[...].astype(jnp.float32)
        w2f = w2_ref[...].astype(jnp.float32)
        b1f = b1_ref[...].astype(jnp.float32)
        dw1 = jnp.zeros((d, f), jnp.float32)
        db1 = jnp.zeros((f,), jnp.float32)
        dw2 = jnp.zeros((f, d), jnp.float32)
        db2 = jnp.zeros((d,), jnp.float32)
        for g_i in range(grp):
            xf = x_ref[g_i].astype(jnp.float32)
            gg = g_ref[g_i].astype(jnp.float32)
            pre = xf @ w1f + b1f[None]                # recompute [T,F]
            h = jnp.maximum(pre, 0.0)
            dw2 = dw2 + h.T @ gg
            db2 = db2 + jnp.sum(gg, axis=0)
            dh = jnp.where(pre > 0.0, gg @ w2f.T, 0.0)  # relu vjp
            dw1 = dw1 + xf.T @ dh
            db1 = db1 + jnp.sum(dh, axis=0)
            dx_ref[g_i] = (dh @ w1f.T).astype(dx_ref.dtype)
        dw1_ref[0] = dw1
        db1_ref[0] = db1
        dw2_ref[0] = dw2
        db2_ref[0] = db2

    x_spec = pl.BlockSpec((grp, t, d), lambda i: (i, 0, 0))
    dx, dw1p, db1p, dw2p, db2p = pl.pallas_call(
        kernel,
        grid=(n_prog,),
        in_specs=[x_spec,
                  pl.BlockSpec((d, f), lambda i: (0, 0)),
                  pl.BlockSpec((f, d), lambda i: (0, 0)),
                  pl.BlockSpec((f,), lambda i: (0,)),
                  x_spec],
        out_specs=[x_spec,
                   pl.BlockSpec((1, d, f), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, f), lambda i: (i, 0)),
                   pl.BlockSpec((1, f, d), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, d), lambda i: (i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, d), x.dtype),
            jax.ShapeDtypeStruct((n_prog, d, f), jnp.float32),
            jax.ShapeDtypeStruct((n_prog, f), jnp.float32),
            jax.ShapeDtypeStruct((n_prog, f, d), jnp.float32),
            jax.ShapeDtypeStruct((n_prog, d), jnp.float32),
        ],
        interpret=_interp(),
        name="ffn_block_bwd",
    )(x, w1, w2, b1, g)
    return (dx,
            jnp.sum(dw1p, axis=0).astype(w1.dtype),
            jnp.sum(db1p, axis=0).astype(b1.dtype),
            jnp.sum(dw2p, axis=0).astype(w2.dtype),
            jnp.sum(db2p, axis=0).astype(b2.dtype))
