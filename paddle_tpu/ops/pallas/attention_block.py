"""Whole-layer fused attention block — the PERF.md MFU lever
("whole-layer pallas fusion", named since round 2; the on-chip A/B is
ROADMAP S5).

One kernel computes the ENTIRE self-attention sub-layer

    out = ((split_heads(x @ Wqkv) -> softmax(scale*QK^T [causal]) @ V)
           merged) @ Wo

so the QKV/context intermediates and the [T,T] score matrices never
touch HBM (the unfused path round-trips all of them between the four
XLA fusions), and the normalized probabilities are saved ONCE in bf16:
the backward kernel does ZERO exps (PERF.md "the exp floor": v5e VPU
exp throughput is the attention bound; re-exping in backward doubles
it) and recomputes only matmul-bound quantities (QKV, context).

Layout contract matches models/transformer.multi_head_attention's
self-attention branch: x [B,T,D], Wqkv [D,3D] (q|k|v concatenated,
then head-split [T,H,Dh]), Wo [D,D], no projection biases, no
residual (the caller's add+LN stays outside — XLA fuses it anyway).

Gating: `usable()`; A/B knobs:
    PADDLE_TPU_FUSE_ATTN_BLOCK=1   route multi_head_attention here
    PADDLE_TPU_DISABLE_PALLAS_ATTN_BLOCK=1  jnp fallback inside the op
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from . import on_tpu
from .attention import _interp

__all__ = ["attention_block", "attention_block_reference", "usable"]

# batch rows per program. VMEM at the routed ceiling (T=512, D=1024):
# fwd per row keeps qkv [T,3D] f32 (6 MB) + one [T,T] f32 score temp
# (1 MB) + weights (Wqkv f32 12 MB shared) -- G=2 stays inside the
# ~16 MB budget the sdpa_short kernel validated on v5e; at the bench
# shape (T=256, D=512) the same G leaves headroom to raise later.
_GROUP_FWD = 2
_GROUP_BWD = 1


def usable(x, w_qkv, n_heads) -> bool:
    if os.environ.get("PADDLE_TPU_DISABLE_PALLAS_ATTN_BLOCK") == "1":
        return False
    if not (on_tpu() or _interp()):
        return False
    if x.ndim != 3 or w_qkv.ndim != 2:
        return False
    b, t, d = x.shape
    if w_qkv.shape != (d, 3 * d) or d % n_heads:
        return False
    dh = d // n_heads
    if not (8 <= t <= 512 and t % 8 == 0 and dh % 8 == 0
            and b % _GROUP_FWD == 0 and b % _GROUP_BWD == 0):
        return False
    # explicit VMEM estimate (f32 words) — a too-big shape must fall
    # back to jnp rather than fail in Mosaic. Forward per program:
    # Wqkv + Wo f32 copies + per-row qkv/ctx + one [T,T] score +
    # x/out rows.
    vmem = (d * 3 * d + d * d            # weights (f32 in-kernel)
            + _GROUP_FWD * (2 * t * 3 * d + 2 * t * d + t * t))
    return vmem * 4 <= 12 * 1024 * 1024


def _causal_iota(t):
    r = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    return r >= c


def attention_block_reference(x, w_qkv, w_o, n_heads, scale, causal):
    """jnp oracle/fallback — same math, one op at a time."""
    b, t, d = x.shape
    dh = d // n_heads
    xf = x.astype(jnp.float32)
    qkv = xf @ w_qkv.astype(jnp.float32)            # [B,T,3D]
    q, k, v = jnp.split(qkv, 3, axis=2)

    def heads(z):                                    # [B,T,H,Dh]
        return z.reshape(b, t, n_heads, dh)

    q, k, v = heads(q), heads(k), heads(v)
    s = jnp.einsum("bthd,bshd->bhts", q, k) * scale
    if causal:
        s = jnp.where(_causal_iota(t), s, jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhts,bshd->bthd", p, v).reshape(b, t, d)
    return (ctx @ w_o.astype(jnp.float32)).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def attention_block(x, w_qkv, w_o, n_heads, scale, causal):
    """x [B,T,D], w_qkv [D,3D], w_o [D,D] -> [B,T,D]."""
    out, _ = _fwd_impl(x, w_qkv, w_o, n_heads, scale, causal,
                       save_p=False)
    return out


def _fwd(x, w_qkv, w_o, n_heads, scale, causal):
    out, p = _fwd_impl(x, w_qkv, w_o, n_heads, scale, causal,
                       save_p=True)
    return out, (x, w_qkv, w_o, p)


def _bwd(n_heads, scale, causal, res, g):
    x, w_qkv, w_o, p = res
    return _bwd_impl(x, w_qkv, w_o, p, g, n_heads, scale, causal)


attention_block.defvjp(_fwd, _bwd)


def _fwd_impl(x, w_qkv, w_o, n_heads, scale, causal, save_p):
    from jax.experimental import pallas as pl

    b, t, d = x.shape
    dh = d // n_heads
    grp = _GROUP_FWD

    def kernel(x_ref, wqkv_ref, wo_ref, o_ref, p_ref=None):
        wqkv = wqkv_ref[...].astype(jnp.float32)
        wo = wo_ref[...].astype(jnp.float32)
        for g_i in range(grp):          # static unroll: 2-D MXU dots
            xf = x_ref[g_i].astype(jnp.float32)      # [T,D]
            qkv = xf @ wqkv                          # [T,3D]
            ctx_heads = []
            for h_i in range(n_heads):
                qh = qkv[:, h_i * dh:(h_i + 1) * dh] * scale
                kh = qkv[:, d + h_i * dh:d + (h_i + 1) * dh]
                vh = qkv[:, 2 * d + h_i * dh:2 * d + (h_i + 1) * dh]
                s = qh @ kh.T                        # [T,T]
                if causal:
                    s = jnp.where(_causal_iota(t), s, -jnp.inf)
                m = jnp.max(s, axis=1)
                p = jnp.exp(s - m[:, None])
                l = jnp.sum(p, axis=1)
                pn = p / l[:, None]
                if p_ref is not None:
                    # bf16 saved-P: backward reads it back instead of
                    # re-exping (the whole point of the fusion)
                    p_ref[g_i, h_i] = pn.astype(p_ref.dtype)
                ctx_heads.append(pn @ vh)            # [T,Dh]
            ctx = jnp.concatenate(ctx_heads, axis=1)  # [T,D]
            o_ref[g_i] = (ctx @ wo).astype(o_ref.dtype)

    x_spec = pl.BlockSpec((grp, t, d), lambda i: (i, 0, 0))
    w_qkv_spec = pl.BlockSpec((d, 3 * d), lambda i: (0, 0))
    w_o_spec = pl.BlockSpec((d, d), lambda i: (0, 0))
    out_specs = [x_spec]
    out_shape = [jax.ShapeDtypeStruct((b, t, d), x.dtype)]
    if save_p:
        out_specs.append(
            pl.BlockSpec((grp, n_heads, t, t), lambda i: (i, 0, 0, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((b, n_heads, t, t), jnp.bfloat16))
    res = pl.pallas_call(
        kernel,
        grid=(b // grp,),
        in_specs=[x_spec, w_qkv_spec, w_o_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=_interp(),
        name="attention_block_fwd",
    )(x, w_qkv, w_o)
    if save_p:
        return res[0], res[1]
    return res[0], None


def _bwd_impl(x, w_qkv, w_o, p, g, n_heads, scale, causal):
    from jax.experimental import pallas as pl

    b, t, d = x.shape
    dh = d // n_heads
    grp = _GROUP_BWD
    n_prog = b // grp

    def kernel(x_ref, wqkv_ref, wo_ref, p_ref, g_ref,
               dx_ref, dwqkv_ref, dwo_ref):
        wqkv = wqkv_ref[...].astype(jnp.float32)
        wo = wo_ref[...].astype(jnp.float32)
        dwqkv = jnp.zeros((d, 3 * d), jnp.float32)
        dwo = jnp.zeros((d, d), jnp.float32)
        for g_i in range(grp):
            xf = x_ref[g_i].astype(jnp.float32)          # [T,D]
            gg = g_ref[g_i].astype(jnp.float32)          # [T,D]
            qkv = xf @ wqkv                              # recompute
            # context recompute (matmul-bound, zero exps)
            ctx_heads = []
            for h_i in range(n_heads):
                vh = qkv[:, 2 * d + h_i * dh:2 * d + (h_i + 1) * dh]
                pn = p_ref[g_i, h_i].astype(jnp.float32)
                ctx_heads.append(pn @ vh)
            ctx = jnp.concatenate(ctx_heads, axis=1)     # [T,D]
            dwo = dwo + ctx.T @ gg
            dctx = gg @ wo.T                             # [T,D]
            dqkv_cols = []
            dk_cols = []
            dv_cols = []
            for h_i in range(n_heads):
                qh = qkv[:, h_i * dh:(h_i + 1) * dh]
                kh = qkv[:, d + h_i * dh:d + (h_i + 1) * dh]
                vh = qkv[:, 2 * d + h_i * dh:2 * d + (h_i + 1) * dh]
                pn = p_ref[g_i, h_i].astype(jnp.float32)
                dctx_h = dctx[:, h_i * dh:(h_i + 1) * dh]
                dv_cols.append(pn.T @ dctx_h)
                dpn = dctx_h @ vh.T                      # [T,T]
                row = jnp.sum(dpn * pn, axis=1)
                ds = pn * (dpn - row[:, None])           # no exp
                dqkv_cols.append((ds @ kh) * scale)
                dk_cols.append((ds.T @ qh) * scale)
            dqkv = jnp.concatenate(
                dqkv_cols + dk_cols + dv_cols, axis=1)   # [T,3D]
            dwqkv = dwqkv + xf.T @ dqkv
            dx_ref[g_i] = (dqkv @ wqkv.T).astype(dx_ref.dtype)
        dwqkv_ref[0] = dwqkv
        dwo_ref[0] = dwo

    x_spec = pl.BlockSpec((grp, t, d), lambda i: (i, 0, 0))
    p_spec = pl.BlockSpec((grp, n_heads, t, t),
                          lambda i: (i, 0, 0, 0))
    dx, dwqkv_part, dwo_part = pl.pallas_call(
        kernel,
        grid=(n_prog,),
        in_specs=[x_spec,
                  pl.BlockSpec((d, 3 * d), lambda i: (0, 0)),
                  pl.BlockSpec((d, d), lambda i: (0, 0)),
                  p_spec, x_spec],
        out_specs=[x_spec,
                   pl.BlockSpec((1, d, 3 * d), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, d, d), lambda i: (i, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, d), x.dtype),
            jax.ShapeDtypeStruct((n_prog, d, 3 * d), jnp.float32),
            jax.ShapeDtypeStruct((n_prog, d, d), jnp.float32),
        ],
        interpret=_interp(),
        name="attention_block_bwd",
    )(x, w_qkv, w_o, p, g)
    # partial-per-program weight grads summed by XLA (one reduce over
    # a [B/G, D, 3D] buffer -- negligible next to the matmuls)
    return (dx,
            jnp.sum(dwqkv_part, axis=0).astype(w_qkv.dtype),
            jnp.sum(dwo_part, axis=0).astype(w_o.dtype))
