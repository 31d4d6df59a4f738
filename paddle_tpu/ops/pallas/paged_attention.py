"""Paged decode-attention Pallas kernel (single-query, block tables).

The device-side half of the paged KV layout (models/decode_engine.py):
every decode tick, each lane attends its generated prefix whose K/V
live scattered across a SHARED block pool behind the lane's block
table. The serving path today lowers this as gather-to-dense + masked
softmax through ordinary ops (decode_engine._PagedLaneCache) — correct
everywhere, but it materializes a [R, H, maxT, Dh] K/V view per tick.
This kernel streams pool blocks through VMEM page by page with online
softmax instead (the vLLM PagedAttention shape, expressed per the
Pallas conventions of ops/pallas/attention.py), so the dense view
never exists.

STATUS: validated against the jnp reference in interpret mode
(tests/test_paged_decode.py), NOT routed into the decode programs: the
repo convention (CLAUDE.md) requires an A/B on the chip before routing
(ROADMAP S7). `usable()` gates exactly like the flash kernels; the jnp
composition in decode_engine stays the serving path either way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .attention import _interp


def usable(q, pool_k, block_tab) -> bool:
    """Gate: real TPU (or forced interpret mode), pool/table shapes
    consistent, lane-friendly head dims."""
    import os

    from . import on_tpu

    if os.environ.get("PADDLE_TPU_DISABLE_PAGED_ATTN") == "1":
        return False
    if not (on_tpu() or _interp()):
        return False
    r, h, d = q.shape
    nb, bs, hp, dp = pool_k.shape
    return (hp == h and dp == d and d % 8 == 0 and bs % 8 == 0
            and block_tab.shape[0] == r)


def paged_decode_attention_reference(q, pool_k, pool_v, block_tab,
                                     step, scale=1.0):
    """jnp oracle (the math decode_engine's gather path lowers to):
    q [R,H,Dh]; pool_k/pool_v [NB,BS,H,Dh]; block_tab [R,NP] int32;
    step [R] int32 — positions > step are masked. Returns [R,H,Dh]."""
    r, h, d = q.shape
    nb, bs, _, _ = pool_k.shape
    np_pages = block_tab.shape[1]
    t = np_pages * bs
    kv_k = pool_k[block_tab].reshape(r, t, h, d)
    kv_v = pool_v[block_tab].reshape(r, t, h, d)
    s = jnp.einsum("rhd,rthd->rht", q.astype(jnp.float32),
                   kv_k.astype(jnp.float32)) * scale
    pos = jnp.arange(t, dtype=jnp.int32)
    s = jnp.where(pos[None, None, :] <= step[:, None, None], s, -1e9)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("rht,rthd->rhd", p,
                      kv_v.astype(jnp.float32)).astype(q.dtype)


def paged_decode_attention(q, pool_k, pool_v, block_tab, step,
                           scale=1.0):
    """Pallas lowering: grid over lanes; per lane, stream NP pool
    blocks (dynamically addressed through the lane's table row)
    through VMEM with the online-softmax carry — no [R,H,maxT,Dh]
    gather ever materializes."""
    from jax.experimental import pallas as pl

    r, h, d = q.shape
    nb, bs, _, _ = pool_k.shape
    np_pages = block_tab.shape[1]
    kernel = functools.partial(_paged_kernel, scale=scale, bs=bs,
                               np_pages=np_pages)
    out = pl.pallas_call(
        kernel,
        grid=(r,),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda i: (i, 0, 0)),
            # the WHOLE pool is visible to every program: blocks are
            # dynamically addressed via the table, which BlockSpec
            # index maps cannot express (they see only grid indices)
            pl.BlockSpec((nb, bs, h, d), lambda i: (0, 0, 0, 0)),
            pl.BlockSpec((nb, bs, h, d), lambda i: (0, 0, 0, 0)),
            pl.BlockSpec((1, np_pages), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((r, h, d), q.dtype),
        interpret=_interp(),
        name="paged_decode_attention",
    )(q, pool_k, pool_v,
      block_tab.astype(jnp.int32),
      step.reshape(r, 1).astype(jnp.int32))
    return out


def _paged_kernel(q_ref, kpool_ref, vpool_ref, tab_ref, step_ref,
                  o_ref, *, scale, bs, np_pages):
    q = q_ref[0].astype(jnp.float32) * scale          # [H, Dh]
    h, d = q.shape
    st = step_ref[0, 0]
    m = jnp.full((h,), -jnp.inf, dtype=jnp.float32)
    l = jnp.zeros((h,), dtype=jnp.float32)
    acc = jnp.zeros((h, d), dtype=jnp.float32)

    def body(p, carry):
        m, l, acc = carry
        b = tab_ref[0, p]
        k_blk = kpool_ref[b]                          # [BS, H, Dh]
        v_blk = vpool_ref[b]
        # s[h, pos]: one dot per head over the block's BS positions
        s = jnp.einsum("hd,shd->hs", q,
                       k_blk.astype(jnp.float32))
        pos = p * bs + jax.lax.broadcasted_iota(jnp.int32, (h, bs), 1)
        s = jnp.where(pos <= st, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        pr = jnp.where(jnp.isfinite(s),
                       jnp.exp(s - m_safe[:, None]), 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + pr.sum(axis=1)
        acc_new = acc * corr[:, None] + jnp.einsum(
            "hs,shd->hd", pr, v_blk.astype(jnp.float32))
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, np_pages, body, (m, l, acc))
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / safe_l[:, None]).astype(o_ref.dtype)
