"""Fused layer_norm Pallas TPU kernel (reference layer_norm_op.cu's
fused-kernel role). One VMEM pass per row-block: mean/var/normalize/
affine without materializing intermediates in HBM. Forward-only -- the
layer_norm op wraps it in custom_vjp with the jnp backward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


from . import on_tpu
from .attention import _interp

# row-block candidates, all multiples of the fp32 (8, 128) tile
_ROW_BLOCKS = (256, 128, 64, 32, 16, 8)


def usable(n: int, d: int) -> bool:
    """Rows must tile by 8 (the fp32 sublane): a serve program's
    `n_slots + 1` rows (9 at eight lanes) would need a (1, d) block,
    so such shapes take `_ln_ref`, which XLA fuses into its
    neighbours."""
    return (on_tpu() or _interp()) and d % 128 == 0 and n % 8 == 0 \
        and n >= 8


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def layer_norm(x, scale, bias, eps=1e-5):
    """x: [N,D]; scale/bias: [D]."""
    return _ln_impl(x, scale, bias, eps)


def _ln_ref(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mean = x32.mean(axis=1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)[None]
            + bias.astype(jnp.float32)[None]).astype(x.dtype)


def _ln_fwd(x, scale, bias, eps):
    return _ln_impl(x, scale, bias, eps), (x, scale, bias)


def _ln_bwd(eps, res, g):
    x, scale, bias = res
    _, vjp = jax.vjp(lambda x_, s_, b_: _ln_ref(x_, s_, b_, eps),
                     x, scale, bias)
    return vjp(g)


layer_norm.defvjp(_ln_fwd, _ln_bwd)


def _ln_impl(x, scale, bias, eps):
    from jax.experimental import pallas as pl

    n, d = x.shape
    block_n = next(b for b in _ROW_BLOCKS if n % b == 0)

    def kernel(x_ref, s_ref, b_ref, o_ref):
        xb = x_ref[...].astype(jnp.float32)
        mean = xb.mean(axis=1, keepdims=True)
        var = jnp.mean(jnp.square(xb - mean), axis=1, keepdims=True)
        y = (xb - mean) * jax.lax.rsqrt(var + eps)
        y = y * s_ref[...].astype(jnp.float32) \
            + b_ref[...].astype(jnp.float32)
        o_ref[...] = y.astype(o_ref.dtype)

    # scale/bias ride as [1, D]: a block whose sublane dim equals the
    # array's is always a legal tile, a rank-1 (d,) block is not
    return pl.pallas_call(
        kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        interpret=_interp(),
        name="layer_norm",
    )(x, scale.reshape(1, d), bias.reshape(1, d))
