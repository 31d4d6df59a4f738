"""Paged decode attention: a lane's keys and values are read where the
pool stores them.

The device-side half of the paged KV layout (models/decode_engine.py):
every decode tick each lane attends the positions it has written, whose
keys and values lie in blocks of a SHARED ``[NB * BS, H * Dh]`` pool
behind the lane's row of the block table. Both routes here read the
pool's rows as they are stored, ``H * Dh`` on the lanes, and never
build a ``[R, H, maxT, Dh]`` view of it:

* ``paged_decode_attention`` (the TPU route for one query a lane): a
  Pallas kernel with the table and the positions as scalar prefetch and
  the pools left in HBM. One program a lane copies that lane's live
  blocks (64 KiB each at ``16 x 1024`` float32, contiguous and
  tile-aligned) into one of two VMEM buffers while the lane before it
  is computed, and skips the blocks past the lane's position.
* ``paged_attention_reference`` (the CPU, programs a mesh places, and
  the speculative verify step's several queries a lane): a jnp
  composition that gathers whole blocks with the in-bounds promise.

Neither splits the 1,024 lanes into ``16 x 64``: a head's sum over its
64 lanes is a product with the 0/1 matrix ``head_indicator`` and a
head's weight is spread back over its lanes by the transpose, at full
precision (a 0/1 matrix is exact in bfloat16, so three bfloat16 passes
over the float32 operand's three bfloat16 parts give the float32 sum).

The in-bounds promise is the ownership prover's (analysis/absint.py,
PTA190: the op's table input chains to the host's block table with its
bound); see ops/paged_ops.py ``paged_decode_attention``. Reference
counterpart: none (vLLM's PagedAttention, SOSP'23, PAPERS.md; JAX's
``pallas/ops/tpu/paged_attention`` shows the copy pattern for
head-major pools).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .attention import _interp

# positions a lane may not see score this much; exp() of it less any
# real score is 0 in float32 (the dense step's bias is -1e9 the same way)
_MASKED = -1e30
_LANES = 128


def head_indicator(n_heads: int, head_dim: int, width: int,
                   dtype=jnp.float32):
    """[H*Dh, width] 0/1: column h is 1 on head h's lanes (columns
    past H stay 0, so ``width`` may pad H up to a lane tile)."""
    hd = n_heads * head_dim
    lane_head = jnp.arange(hd, dtype=jnp.int32) // head_dim
    return (lane_head[:, None]
            == jnp.arange(width, dtype=jnp.int32)[None, :]).astype(dtype)


def usable(q, pool_k, block_tab, block_size: int) -> bool:
    """Shapes the kernel takes, on a single TPU (or in interpret
    mode): one query a lane, ``H*Dh`` a multiple of the 128 lanes,
    blocks of whole sublane tiles."""
    from . import on_tpu

    if not (on_tpu() or _interp()):
        return False
    r, nq, hd = q.shape
    return (nq == 1 and hd % _LANES == 0 and block_size % 8 == 0
            and pool_k.shape[1] == hd
            and pool_k.shape[0] % block_size == 0
            and block_tab.shape[0] == r
            and q.dtype == pool_k.dtype == jnp.float32)


def paged_attention_reference(q, pool_k, pool_v, block_tab, pos, *,
                              block_size, n_heads, scale):
    """q [R, Q, H*Dh]; pools [NB*BS, H*Dh]; block_tab [R, NP] int;
    pos [R] int, the cache position of a lane's first query (query j
    sees positions <= pos + j). Returns [R, Q, H*Dh].

    Whole blocks are gathered (``[NB, BS, H*Dh]`` is the stored pool
    with its leading axis split, which moves nothing) under the
    in-bounds promise, so no pass fills rows for an index out of
    range. On one device every contraction keeps ``H*Dh`` on the
    lanes (products with ``head_indicator``). Under a mesh the pools
    are sharded by whole heads on that axis and a contraction over it
    would be a psum a layer a tick, so there the heads are split off
    the axis instead, which GSPMD keeps on the shard."""
    from . import mesh_placed

    r, nq, hd = q.shape
    head_dim = hd // n_heads
    t = block_tab.shape[1] * block_size
    hi = jax.lax.Precision.HIGHEST
    tab = block_tab.astype(jnp.int32)

    def rows_of(pool):
        blocks = pool.reshape(-1, block_size, hd)
        return blocks.at[tab].get(mode="promise_in_bounds"
                                  ).reshape(r, t, hd)

    k, v, q = rows_of(pool_k), rows_of(pool_v), q * scale
    split_heads = mesh_placed()
    if split_heads:
        heads = (n_heads, head_dim)
        s = jnp.einsum("rqhd,rthd->rqth", q.reshape(r, nq, *heads),
                       k.reshape(r, t, *heads), precision=hi)
    else:
        ind = head_indicator(n_heads, head_dim, n_heads, q.dtype)
        # [R,Q,T,HD] products summed over each head's lanes
        s = jnp.einsum("rqtc,ch->rqth", k[:, None] * q[:, :, None],
                       ind, precision=hi)
    seen = (jnp.arange(t, dtype=jnp.int32)[None, None, :]
            <= (pos.astype(jnp.int32)[:, None]
                + jnp.arange(nq, dtype=jnp.int32)[None, :])[:, :, None])
    p = jax.nn.softmax(jnp.where(seen[..., None], s, _MASKED), axis=2)
    if split_heads:
        out = jnp.einsum("rqth,rthd->rqhd", p, v.reshape(r, t, *heads),
                         precision=hi).reshape(r, nq, hd)
    else:
        spread = jnp.einsum("rqth,ch->rqtc", p, ind, precision=hi)
        out = jnp.sum(spread * v[:, None], axis=2)
    return out.astype(pool_v.dtype)


def _parts(x):
    """A float32 array as three bfloat16 arrays that sum to it."""
    out = []
    for _ in range(2):
        hi = x.astype(jnp.bfloat16)
        out.append(hi)
        x = x - hi.astype(jnp.float32)
    return out + [x.astype(jnp.bfloat16)]


def _dot_indicator(x, ind):
    """x @ ind for a 0/1 bfloat16 ``ind``, exact to float32's
    accumulation: three single-pass products (the MXU's six-pass
    float32 product took 1.47 ms where this takes 1.20, and one pass
    is wrong by 5e-3: PERF.md, PR 31)."""
    return sum(jnp.dot(part, ind, preferred_element_type=jnp.float32)
               for part in _parts(x))


def _kernel(tab_ref, pos_ref, q_ref, ind_ref, indt_ref, k_hbm, v_hbm,
            o_ref, k_buf, v_buf, s_buf, acc, sems, *, block_size,
            n_pages, chunk_pages):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lane, n_lanes = pl.program_id(0), pl.num_programs(0)
    t = n_pages * block_size
    chunk = chunk_pages * block_size
    n_chunks = n_pages // chunk_pages

    def last_pos(ln):
        return jnp.clip(pos_ref[ln], 0, t - 1)

    def copies(ln, slot, act):
        # a lane's live chunks, block by block through its table row
        live = last_pos(ln) // chunk + 1
        for c in range(n_chunks):
            @pl.when(c < live)
            def _():
                for page in range(c * chunk_pages,
                                  (c + 1) * chunk_pages):
                    row = pl.multiple_of(
                        tab_ref[ln * n_pages + page] * block_size,
                        block_size)
                    for which, (hbm, buf) in enumerate(
                            ((k_hbm, k_buf), (v_hbm, v_buf))):
                        act(pltpu.make_async_copy(
                            hbm.at[pl.ds(row, block_size), :],
                            buf.at[slot, pl.ds(page * block_size,
                                               block_size), :],
                            sems.at[which, slot]))

    slot = lane % 2

    @pl.when(lane == 0)
    def _():
        copies(lane, slot, lambda cp: cp.start())

    @pl.when(lane + 1 < n_lanes)
    def _():
        copies(lane + 1, 1 - slot, lambda cp: cp.start())

    copies(lane, slot, lambda cp: cp.wait())

    last = last_pos(lane)
    q = q_ref[0]                                        # [1, HD]
    acc[...] = jnp.zeros_like(acc)
    for c in range(n_chunks):
        @pl.when(c * chunk <= last)
        def _():
            k = k_buf[slot, c * chunk:(c + 1) * chunk, :]
            s_buf[c * chunk:(c + 1) * chunk, :] = _dot_indicator(
                k * q, ind_ref[...])
    # [T, 128]: positions on the sublanes, heads on the first H lanes;
    # a chunk that was not computed holds whatever VMEM held, which
    # the mask replaces before anything reads it
    seen = jax.lax.broadcasted_iota(jnp.int32, s_buf.shape, 0) <= last
    s = jnp.where(seen, s_buf[...], _MASKED)
    m = jnp.max(s, axis=0, keepdims=True)
    p = jnp.where(seen, jnp.exp(s - m), 0.0)
    s_buf[...] = p / jnp.sum(p, axis=0, keepdims=True)
    for c in range(n_chunks):
        @pl.when(c * chunk <= last)
        def _():
            v = v_buf[slot, c * chunk:(c + 1) * chunk, :]
            w = _dot_indicator(s_buf[c * chunk:(c + 1) * chunk, :],
                               indt_ref[...])
            acc[...] += (w * v).reshape(chunk // 8, 8, -1).sum(axis=0)
    o_ref[0] = jnp.sum(acc[...], axis=0, keepdims=True
                       ).astype(o_ref.dtype)


def paged_decode_attention(q, pool_k, pool_v, block_tab, pos, *,
                           block_size, n_heads, scale):
    """The kernel: same arguments and result as
    ``paged_attention_reference`` with Q = 1. The layers of a program
    call ONE jitted function, so a program lowers the kernel once
    (0.2 s) and not once a layer (1.4 s for six): a serve cell binds
    up to 19 programs at set-up."""
    return _call(q, pool_k, pool_v, block_tab, pos,
                 block_size=block_size, n_heads=n_heads,
                 scale=float(scale), interpret=_interp())


@functools.partial(jax.jit, static_argnames=(
    "block_size", "n_heads", "scale", "interpret"))
def _call(q, pool_k, pool_v, block_tab, pos, *, block_size, n_heads,
          scale, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, _, hd = q.shape
    n_pages = block_tab.shape[1]
    t = n_pages * block_size
    # rows a product streams through the MXU for one load of the
    # indicator: as many blocks as make 64 rows, where they divide
    # the table (128 and 256 rows measured the same within 4%)
    chunk_pages = max(1, 64 // block_size)
    while n_pages % chunk_pages:
        chunk_pages -= 1
    ind = head_indicator(n_heads, hd // n_heads, _LANES, jnp.bfloat16)
    kernel = functools.partial(_kernel, block_size=block_size,
                               n_pages=n_pages, chunk_pages=chunk_pages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(r,),
        in_specs=[
            pl.BlockSpec((1, 1, hd), lambda i, tab, pos: (i, 0, 0)),
            pl.BlockSpec((hd, _LANES), lambda i, tab, pos: (0, 0)),
            pl.BlockSpec((_LANES, hd), lambda i, tab, pos: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), lambda i, tab, pos: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, t, hd), pool_k.dtype),
            pltpu.VMEM((2, t, hd), pool_v.dtype),
            pltpu.VMEM((t, _LANES), jnp.float32),
            pltpu.VMEM((8, hd), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, 1, hd), q.dtype),
        # the next lane's blocks are in flight when a program ends, so
        # the lanes run in order on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(block_tab.astype(jnp.int32).reshape(-1), pos.astype(jnp.int32),
      q * scale, ind, ind.T, pool_k, pool_v)
