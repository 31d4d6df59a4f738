"""Flash attention Pallas TPU kernels (forward AND backward).

The hot path of the Transformer benchmark (BASELINE.md config 3). Online-
softmax tiling keeps the full [Tq,Tk] logits matrix out of HBM: per
(batch*head, q-block) grid cell we stream k/v blocks through VMEM,
carrying running max/denominator -- the standard flash pattern expressed
in Pallas (see /opt/skills/guides/pallas_guide.md).

Backward: the forward additionally writes the per-row logsumexp; the
backward recomputes attention probabilities blockwise from (q, k, lse)
and accumulates dq in one kernel (grid over q-blocks) and dk/dv in a
second (grid over k-blocks) -- the FlashAttention-2 recipe. Residuals
are q, k, v, out, lse: O(T) extra memory instead of the O(T^2)
probability matrix, and no jnp fallback on the grad path.

Both directions use BOTTOM-RIGHT causal alignment (query i sees keys
<= i + tk - tq), the same convention as the jnp fallback in
ops/nn_ops.py, so kernel/fallback numerics agree for tq != tk.

Block sizes adapt to the sequence length (min(t, 256) when divisible),
so the kernels engage for seq-128 benchmark shapes, not just multiples
of 256. `force_interpret(True)` runs every pallas_call in interpreter
mode so CPU tests can exercise the real kernel code paths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_MAX_BLOCK = 256

_INTERPRET = [False]


def force_interpret(on: bool = True) -> None:
    """Run kernels in pallas interpreter mode (CPU testing)."""
    _INTERPRET[0] = bool(on)


def _interp() -> bool:
    return _INTERPRET[0]


def _pick_block(t: int) -> int:
    for b in (_MAX_BLOCK, 128, 64, 32, 16, 8):
        if t % b == 0:
            return b
    return 0


def usable(q, k, v) -> bool:
    import os

    from . import on_tpu

    if os.environ.get("PADDLE_TPU_DISABLE_FLASH_ATTN") == "1":
        return False  # perf-debug escape hatch: XLA attention path
    if not (on_tpu() or _interp()):
        return False
    b, h, tq, d = q.shape
    hkv, tk = k.shape[1], k.shape[2]
    return (_pick_block(tq) >= 8 and _pick_block(tk) >= 8
            and h % hkv == 0 and k.shape == v.shape
            and d in (64, 128, 256) and q.dtype == k.dtype == v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, scale=1.0, causal=False):
    """q: [B,H,T,D]; k, v: [B,Hkv,T,D] with H a multiple of Hkv (each
    key-value head shared by H/Hkv query heads, read in place) ->
    [B,H,T,D]."""
    out, _ = _flash_fwd_impl(q, k, v, scale, causal)
    return out


def _flash_fwd(q, k, v, scale, causal):
    out, lse = _flash_fwd_impl(q, k, v, scale, causal)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, res, g):
    q, k, v, out, lse = res
    return _flash_bwd_impl(q, k, v, out, lse, g, scale, causal)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def _dot_nt(a, b):
    """a [m, d] x b [n, d]^T -> [m, n], operands as stored (bfloat16
    runs on the MXU at its own rate), float32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    """a [m, n]^T x b [m, d] -> [n, d]."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _causal_keep(q0, k0, block_q, block_k, offset):
    """Bottom-right alignment: query row i sees keys <= i + offset."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return q_pos + offset >= k_pos


def _causal_key_blocks(qi, block_q, block_k, offset, n_blocks):
    """Key blocks any row of query block qi sees: those after them lie
    wholly above the diagonal and are never visited."""
    return jnp.clip(((qi + 1) * block_q + offset + block_k - 1) // block_k,
                    0, n_blocks)


def _flash_fwd_impl(q, k, v, scale, causal):
    from jax.experimental import pallas as pl

    b, h, tq, d = q.shape
    hkv, tk = k.shape[1], k.shape[2]
    group = h // hkv
    block_q = _pick_block(tq)
    block_k = _pick_block(tk)
    bh = b * h
    q3 = q.reshape(bh, tq, d)
    k3 = k.reshape(b * hkv, tk, d)
    v3 = v.reshape(b * hkv, tk, d)

    grid = (bh, tq // block_q)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               tq=tq, tk=tk, block_k=block_k)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            # query head i reads key-value head i // group
            pl.BlockSpec((1, tk, d), lambda i, j: (i // group, 0, 0)),
            pl.BlockSpec((1, tk, d), lambda i, j: (i // group, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            # lse rides as (bh, 1, tq): sublane dim 1 == array dim, lane
            # dim block_q is 128-divisible (TPU BlockSpec constraint)
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, tq), jnp.float32),
        ],
        interpret=_interp(),
        name="flash_attention_fwd",
    )(q3, k3, v3)
    return out.reshape(b, h, tq, d), lse.reshape(b, h, tq)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                tq, tk, block_k):
    from jax.experimental import pallas as pl

    q = q_ref[0]                              # [BQ, D]
    block_q = q.shape[0]
    qi = pl.program_id(1)
    m = jnp.full((block_q,), -jnp.inf, dtype=jnp.float32)
    l = jnp.zeros((block_q,), dtype=jnp.float32)
    acc = jnp.zeros(q.shape, dtype=jnp.float32)
    # bottom-right causal alignment: query row i attends keys
    # <= i + (tk - tq), matching the jnp fallback's tril offset.
    offset = tk - tq

    def body(kb, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.dslice(kb * block_k, block_k)]
        v_blk = v_ref[0, pl.dslice(kb * block_k, block_k)]
        s = _dot_nt(q, k_blk) * scale         # [BQ, BK] float32
        if causal:
            s = jnp.where(_causal_keep(qi * block_q, kb * block_k,
                                       block_q, block_k, offset),
                          s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=1))
        # rows with no valid key yet keep m=-inf; guard the exp
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe[:, None]), 0.0)
        correction = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * correction + p.sum(axis=1)
        acc_new = acc * correction[:, None] \
            + _dot(p.astype(v_blk.dtype), v_blk)
        return m_new, l_new, acc_new

    n_blocks = tk // block_k
    if causal:
        n_blocks = _causal_key_blocks(qi, block_q, block_k, offset,
                                      n_blocks)
    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m, l, acc))
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / safe_l[:, None]).astype(o_ref.dtype)
    # lse = m + log(l); -inf for fully-masked rows (p will be 0 in bwd)
    lse_ref[0, 0] = jnp.where(l > 0.0, m + jnp.log(safe_l), -jnp.inf)


# ---------------------------------------------------------------------------
# backward (FlashAttention-2): dq over q-blocks, dk/dv over k-blocks
# ---------------------------------------------------------------------------
def _flash_bwd_impl(q, k, v, out, lse, g, scale, causal):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, tq, d = q.shape
    hkv, tk = k.shape[1], k.shape[2]
    group = h // hkv
    block_q = _pick_block(tq)
    block_k = _pick_block(tk)
    bh = b * h
    q3 = q.reshape(bh, tq, d)
    k3 = k.reshape(b * hkv, tk, d)
    v3 = v.reshape(b * hkv, tk, d)
    g3 = g.reshape(bh, tq, d)
    lse3 = lse.reshape(bh, 1, tq)
    # delta_i = rowsum(dO_i * O_i); tiny elementwise+reduce, XLA fuses
    delta = jnp.sum(g3.astype(jnp.float32)
                    * out.reshape(bh, tq, d).astype(jnp.float32),
                    axis=-1).reshape(bh, 1, tq)

    dq_kernel = functools.partial(_bwd_dq_kernel, scale=scale,
                                  causal=causal, tq=tq, tk=tk,
                                  block_k=block_k)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, tq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, tk, d), lambda i, j: (i // group, 0, 0)),
            pl.BlockSpec((1, tk, d), lambda i, j: (i // group, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        interpret=_interp(),
        name="flash_attention_dq",
    )(q3, k3, v3, g3, lse3, delta)

    # one program a (key-value head, key block, query head of its
    # group): the group's query heads follow one another on the last
    # grid axis and add into one accumulator, written after the last
    dkv_kernel = functools.partial(_bwd_dkv_kernel, scale=scale,
                                   causal=causal, tq=tq, tk=tk,
                                   block_q=block_q, group=group)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b * hkv, tk // block_k, group),
        in_specs=[
            pl.BlockSpec((1, tq, d), lambda i, j, r: (i * group + r, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, r: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, r: (i, j, 0)),
            pl.BlockSpec((1, tq, d), lambda i, j, r: (i * group + r, 0, 0)),
            pl.BlockSpec((1, 1, tq), lambda i, j, r: (i * group + r, 0, 0)),
            pl.BlockSpec((1, 1, tq), lambda i, j, r: (i * group + r, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, j, r: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, r: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, tk, d), k.dtype),
            jax.ShapeDtypeStruct((b * hkv, tk, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=_interp(),
        name="flash_attention_dkv",
    )(q3, k3, v3, g3, lse3, delta)

    return (dq.reshape(b, h, tq, d), dk.reshape(b, hkv, tk, d),
            dv.reshape(b, hkv, tk, d))


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, *, scale, causal, tq, tk, block_k):
    from jax.experimental import pallas as pl

    q = q_ref[0]                              # [BQ, D]
    do = do_ref[0]                            # [BQ, D]
    lse = lse_ref[0, 0]                       # [BQ]
    delta = delta_ref[0, 0]                   # [BQ]
    block_q = q.shape[0]
    qi = pl.program_id(1)
    offset = tk - tq
    lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)[:, None]
    dq = jnp.zeros(q.shape, dtype=jnp.float32)

    def body(kb, dq):
        k_blk = k_ref[0, pl.dslice(kb * block_k, block_k)]
        v_blk = v_ref[0, pl.dslice(kb * block_k, block_k)]
        s = _dot_nt(q, k_blk) * scale
        p = jnp.exp(s - lse_safe)
        if causal:
            p = jnp.where(_causal_keep(qi * block_q, kb * block_k,
                                       block_q, block_k, offset), p, 0.0)
        dp = _dot_nt(do, v_blk)               # [BQ, BK]
        ds = p * (dp - delta[:, None]) * scale
        return dq + _dot(ds.astype(k_blk.dtype), k_blk)

    n_blocks = tk // block_k
    if causal:
        n_blocks = _causal_key_blocks(qi, block_q, block_k, offset,
                                      n_blocks)
    dq = jax.lax.fori_loop(0, n_blocks, body, dq)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal, tq,
                    tk, block_q, group):
    from jax.experimental import pallas as pl

    k = k_ref[0]                              # [BK, D]
    v = v_ref[0]                              # [BK, D]
    block_k = k.shape[0]
    ki = pl.program_id(1)
    r = pl.program_id(2)
    offset = tk - tq

    @pl.when(r == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(qb, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.dslice(qb * block_q, block_q)]
        do_blk = do_ref[0, pl.dslice(qb * block_q, block_q)]
        lse_blk = lse_ref[0, 0, pl.dslice(qb * block_q, block_q)]
        delta_blk = delta_ref[0, 0, pl.dslice(qb * block_q, block_q)]
        lse_safe = jnp.where(jnp.isfinite(lse_blk), lse_blk, 0.0)[:, None]
        s = _dot_nt(q_blk, k) * scale         # [BQ, BK]
        p = jnp.exp(s - lse_safe)
        if causal:
            p = jnp.where(_causal_keep(qb * block_q, ki * block_k,
                                       block_q, block_k, offset), p, 0.0)
        dv = dv + _dot_tn(p.astype(do_blk.dtype), do_blk)
        dp = _dot_nt(do_blk, v)               # [BQ, BK]
        ds = p * (dp - delta_blk[:, None]) * scale
        dk = dk + _dot_tn(ds.astype(q_blk.dtype), q_blk)
        return dk, dv

    n_q = tq // block_q
    first = 0
    if causal:
        # query blocks wholly before this key block never see it
        first = jnp.clip((ki * block_k - offset) // block_q, 0, n_q)
    dk, dv = jax.lax.fori_loop(
        first, n_q, body, (jnp.zeros(k.shape, jnp.float32),
                           jnp.zeros(v.shape, jnp.float32)))
    dk_acc[...] += dk
    dv_acc[...] += dv

    @pl.when(r == group - 1)
    def _():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# short-sequence fused SDPA: full [T,T] scores live in VMEM, several
# (b,h) rows batched per program.
#
# Why not the flash kernel: at T<=~512 the flash grid degenerates to
# b*h tiny programs (1024 on transformer-base) whose per-program
# launch/DMA overhead dominates (~5 ms/call measured on v5e, slower
# than the jnp composition). Here one program handles _SDPA_GROUP
# heads with the entire score matrix on-chip -- no online-softmax
# rescaling, no HBM [B,H,T,T] buffer (the jnp path's cost), and the
# whole backward (dq, dk, dv) in ONE pass with softmax recomputed
# from the saved lse.
# ---------------------------------------------------------------------------
# VMEM sizing at the routed window's top (T=512, the worst case
# sdpa_usable admits): G*T*T f32 score temps = 8*512*512*4 = 8 MB fwd
# (verified compiling + faster than the jnp path on v5e); the backward
# additionally holds the saved-P block, hence the smaller group.
_SDPA_GROUP_FWD = 8
_SDPA_GROUP_BWD = 4


def sdpa_usable(q, k, v) -> bool:
    import os

    from . import on_tpu

    if os.environ.get("PADDLE_TPU_DISABLE_PALLAS_SDPA") == "1":
        return False
    if not (on_tpu() or _interp()):
        return False
    b, h, tq, d = q.shape
    tk = k.shape[2]
    # measured window on v5e (see module comment): at T<=256 the jnp
    # composition wins in-model (XLA fuses softmax into neighbors and
    # overlaps better); at T>512 the [grp,T,T] f32 scores overflow
    # VMEM (1024^2*4*grp) -- that range belongs to the flash kernel
    if tq != tk or not (256 < tq <= 512) or tq % 8 != 0:
        return False
    if d not in (64, 128) or q.dtype != k.dtype or k.dtype != v.dtype:
        return False
    bh = b * h
    return bh % _SDPA_GROUP_FWD == 0 and bh % _SDPA_GROUP_BWD == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def sdpa_short(q, k, v, scale=1.0, causal=False):
    """q,k,v: [B,H,T,D] (same T) -> [B,H,T,D]."""
    # primal (inference) path: p is only a backward residual; skip
    # materializing the [B*H,T,T] tensor entirely
    out, _ = _sdpa_short_fwd_impl(q, k, v, scale, causal,
                                  save_p=False)
    return out


def _sdpa_short_fwd(q, k, v, scale, causal):
    out, p = _sdpa_short_fwd_impl(q, k, v, scale, causal, save_p=True)
    return out, (q, k, v, p)


def _sdpa_short_bwd(scale, causal, res, g):
    q, k, v, p = res
    return _sdpa_short_bwd_impl(q, k, v, p, g, scale, causal)


sdpa_short.defvjp(_sdpa_short_fwd, _sdpa_short_bwd)


def _causal_mask(t):
    r = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    return r >= c


def _sdpa_short_fwd_impl(q, k, v, scale, causal, save_p):
    from jax.experimental import pallas as pl

    b, h, t, d = q.shape
    bh = b * h
    grp = _SDPA_GROUP_FWD
    q3 = q.reshape(bh, t, d)
    k3 = k.reshape(bh, t, d)
    v3 = v.reshape(bh, t, d)

    def kernel(q_ref, k_ref, v_ref, o_ref, p_ref=None):
        for g_i in range(grp):  # static unroll: 2-D matmuls on the MXU
            qg = q_ref[g_i].astype(jnp.float32) * scale  # [T,D]
            kg = k_ref[g_i].astype(jnp.float32)
            vg = v_ref[g_i].astype(jnp.float32)
            s = qg @ kg.T                                # [T,T]
            if causal:
                s = jnp.where(_causal_mask(t), s, -jnp.inf)
            m = jnp.max(s, axis=1)
            p = jnp.exp(s - m[:, None])
            l = jnp.sum(p, axis=1)
            pn = p / l[:, None]
            o_ref[g_i] = (pn @ vg).astype(o_ref.dtype)
            if p_ref is not None:
                # normalized probabilities saved bf16 for the
                # backward: the VPU's exp throughput (~25G/s on v5e)
                # is the floor of this whole kernel, so the backward
                # must NOT re-exp -- rereading 2*T*T bf16 from HBM is
                # ~7x cheaper than the recompute
                p_ref[g_i] = pn.astype(p_ref.dtype)

    blk_td = pl.BlockSpec((grp, t, d), lambda i: (i, 0, 0))
    out_specs = [blk_td]
    out_shape = [jax.ShapeDtypeStruct((bh, t, d), q.dtype)]
    if save_p:
        out_specs.append(pl.BlockSpec((grp, t, t), lambda i: (i, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bh, t, t), jnp.bfloat16))
    res = pl.pallas_call(
        kernel,
        grid=(bh // grp,),
        in_specs=[blk_td] * 3,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=_interp(),
        name="sdpa_short_fwd",
    )(q3, k3, v3)
    if save_p:
        out, p = res
    else:
        out, p = res[0], None
    return out.reshape(b, h, t, d), p


def _sdpa_short_bwd_impl(q, k, v, p, g, scale, causal):
    from jax.experimental import pallas as pl

    b, h, t, d = q.shape
    bh = b * h
    grp = _SDPA_GROUP_BWD
    q3 = q.reshape(bh, t, d)
    k3 = k.reshape(bh, t, d)
    v3 = v.reshape(bh, t, d)
    g3 = g.reshape(bh, t, d)

    def kernel(q_ref, k_ref, v_ref, g_ref, p_ref,
               dq_ref, dk_ref, dv_ref):
        for g_i in range(grp):
            qg = q_ref[g_i].astype(jnp.float32)
            kg = k_ref[g_i].astype(jnp.float32)
            vg = v_ref[g_i].astype(jnp.float32)
            gg = g_ref[g_i].astype(jnp.float32)
            pg = p_ref[g_i].astype(jnp.float32)          # [T,T] saved
            dv_ref[g_i] = (pg.T @ gg).astype(dv_ref.dtype)
            dp = gg @ vg.T                               # [T,T]
            # softmax vjp: ds = p * (dp - rowsum(dp * p)); no exp here
            row = jnp.sum(dp * pg, axis=1)
            ds = pg * (dp - row[:, None])
            dq_ref[g_i] = ((ds @ kg) * scale).astype(dq_ref.dtype)
            dk_ref[g_i] = ((ds.T @ qg) * scale).astype(dk_ref.dtype)

    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(bh // grp,),
        in_specs=[pl.BlockSpec((grp, t, d), lambda i: (i, 0, 0))] * 4
        + [pl.BlockSpec((grp, t, t), lambda i: (i, 0, 0))],
        out_specs=[pl.BlockSpec((grp, t, d), lambda i: (i, 0, 0))] * 3,
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t, d), v.dtype),
        ],
        interpret=_interp(),
        name="sdpa_short_bwd",
    )(q3, k3, v3, g3, p)
    return (dq.reshape(b, h, t, d), dk.reshape(b, h, t, d),
            dv.reshape(b, h, t, d))
