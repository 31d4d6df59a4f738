"""Fused softmax-cross-entropy Pallas kernels.

Parity: reference softmax_with_cross_entropy_op.cu (the fused CUDA
kernel pair). TPU motivation (profiled on v5e, transformer-base
128x256x32000): the jnp composition upcasts logits to fp32 for the
stable logsumexp, and XLA materializes that f32 [N,V] buffer (4 GB)
in HBM because forward loss, picked-logit gather and backward all
consume it. These kernels stream bf16 logits through VMEM row-blocks
and keep every fp32 intermediate on-chip:

  forward:  loss = (1-eps)*(lse - picked) + eps*(lse - mean)   [+ lse out]
  backward: dlogits = (softmax - (1-eps)*onehot - eps/V) * g
            with lse recomputed in-kernel -- ONE bf16 read of the
            logits, one bf16 write of the grad, no residuals.

Hard labels only (soft-label programs take the jnp path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import note_route, on_tpu
from .attention import _interp

_ROW_BLOCK = 32  # bn x V fp32 temps stay ~4 MB in VMEM at V=32k


def usable(logits2d, label1d) -> bool:
    import os

    if os.environ.get("PADDLE_TPU_DISABLE_PALLAS_XENT") == "1":
        return False
    if not (on_tpu() or _interp()):
        return False
    n, v = logits2d.shape
    return (n % _ROW_BLOCK == 0 and v % 128 == 0
            and label1d.shape == (n,))


# ---------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------
def _fwd_kernel(x_ref, lab_ref, loss_ref, lse_ref, *, eps, v, ignore):
    x = x_ref[...].astype(jnp.float32)          # [bn, V]
    bn = x.shape[0]
    m = jnp.max(x, axis=1)
    ex = jnp.exp(x - m[:, None])
    lse = m + jnp.log(jnp.sum(ex, axis=1))
    lab = lab_ref[..., 0]                       # [bn] int32
    cols = jax.lax.broadcasted_iota(jnp.int32, (bn, v), 1)
    picked = jnp.sum(jnp.where(cols == lab[:, None], x, 0.0), axis=1)
    loss = lse - picked
    if eps:
        uniform = lse - jnp.mean(x, axis=1)
        loss = (1.0 - eps) * loss + eps * uniform
    # ignore_index rows contribute 0 loss (reference
    # softmax_with_cross_entropy_op.h hard-label semantics)
    loss_ref[..., 0] = jnp.where(lab == ignore, 0.0, loss)
    lse_ref[..., 0] = lse


def xent_forward(logits2d, label1d, eps=0.0, ignore_index=-100):
    """bf16/f32 [N,V] + int32 [N] -> (loss f32 [N], lse f32 [N])."""
    from jax.experimental import pallas as pl

    n, v = logits2d.shape
    bn = _ROW_BLOCK
    kernel = functools.partial(_fwd_kernel, eps=float(eps), v=v,
                               ignore=int(ignore_index))
    # per-row vectors ride as [N,1]: rank-1 blocks of bn<128 rows are
    # rejected by the TPU lowering (lane dim must be full or 128-mult)
    loss, lse = pl.pallas_call(
        kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, v), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=_interp(),
        name="xent_forward",
    )(logits2d, label1d.astype(jnp.int32)[:, None])
    return loss[:, 0], lse[:, 0]


# ---------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------
def _bwd_kernel(x_ref, lab_ref, g_ref, dx_ref, *, eps, v, ignore):
    x = x_ref[...].astype(jnp.float32)
    bn = x.shape[0]
    m = jnp.max(x, axis=1)
    ex = jnp.exp(x - m[:, None])
    denom = jnp.sum(ex, axis=1)
    sm = ex / denom[:, None]
    lab = lab_ref[..., 0]
    cols = jax.lax.broadcasted_iota(jnp.int32, (bn, v), 1)
    onehot = (cols == lab[:, None]).astype(jnp.float32)
    tgt = (1.0 - eps) * onehot + (eps / v if eps else 0.0)
    g = g_ref[..., 0].astype(jnp.float32)
    g = jnp.where(lab == ignore, 0.0, g)  # ignored rows: zero grad
    dx_ref[...] = ((sm - tgt) * g[:, None]).astype(dx_ref.dtype)


def xent_backward(logits2d, label1d, dloss1d, eps=0.0,
                  ignore_index=-100):
    """dlogits in the logits' storage dtype; lse recomputed on-chip."""
    from jax.experimental import pallas as pl

    n, v = logits2d.shape
    bn = _ROW_BLOCK
    kernel = functools.partial(_bwd_kernel, eps=float(eps), v=v,
                               ignore=int(ignore_index))
    return pl.pallas_call(
        kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, v), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, v), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, v), logits2d.dtype),
        interpret=_interp(),
        name="xent_backward",
    )(logits2d, label1d.astype(jnp.int32)[:, None],
      dloss1d.astype(jnp.float32)[:, None])


def maybe_route(logits, label):
    """Shared gate + label normalization for the swce forward AND grad
    kernels (they must route identically): returns
    (logits2d, label1d) when the pallas kernels apply, else None."""
    lab = label.astype(jnp.int32)
    if lab.ndim == logits.ndim:
        lab = lab[..., 0]
    l2 = logits.reshape(-1, logits.shape[-1])
    lab1 = lab.reshape(-1)
    if note_route("xent", l2.shape, usable(l2, lab1)):
        return l2, lab1
    return None
