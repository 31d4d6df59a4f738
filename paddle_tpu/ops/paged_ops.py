"""Paged-KV pool ops (models/decode_engine.py paged layout).

Reference counterpart: none — the reference framework's decode caches
are per-request dense tensors (reference
tests/unittests/dist_transformer.py:1498 fast_decode caches). The
shared block pool follows vLLM's PagedAttention block tables
(SOSP'23, PAPERS.md), re-designed for XLA static shapes: the pool is
one persistable tensor, lanes address it through host-allocated
int32 tables, ALL writes funnel through ``masked_pool_write`` so the
lane-exclusivity contract is one auditable surface (analysis checker
PTA110), and a decode tick's self-attention reads the pool where it
is stored through ``paged_decode_attention`` (the cross-attention
prompt table and the COW copy still read by plain `gather`).
"""
from __future__ import annotations

import jax.numpy as jnp

from ..core.registry import register_op


@register_op("masked_pool_write", differentiable=False,
             stop_gradient_slots=("Pool", "New", "Index", "Gate"))
def masked_pool_write(ctx):
    """Disjoint one-hot masked scatter into a SHARED KV pool.

    inputs: Pool [N0(, N1), ...tail] (the pool var — also the op's
    output, an in-place read-modify-write so the var rides the
    executor's state_in path); New [R, ...tail]; Index [R] int
    (flattened leading index of each row's target cell); Gate [R]
    optional 0/1 (rows with gate 0 — idle/dustbin/paused lanes —
    write nothing). attrs: leading_dims (how many leading Pool axes
    the Index addresses, flattened), exclusive_via (the builder's
    declaration of WHY row indices cannot alias: "block_table" =
    per-lane blocks from a host free-list, "host_indices" =
    host-deduplicated admission targets, "cow_dst" = freshly
    allocated exclusive blocks a COW copy diverges a lane into —
    checker PTA110 requires it).

    Out-of-range and gated-off rows write nothing (their index
    becomes n, past the last cell, and the scatter drops it), and
    cells hit by a gated row take EXACTLY the new value. Only the
    ``leading_dims`` axes merge: the tail axes, which the TPU tiles,
    keep their stored shape, so a write moves R rows and nothing the
    size of the pool. The lowering is an indexed row
    scatter — O(R x cell) instead of the O(n_cells x R x cell)
    one-hot matmul, which MEASURED as ~3x the cost of the attention
    itself per decode tick at small head dims; the semantics are the
    disjoint-one-hot-mask semantics PTA110 assumes (under the
    exclusivity contract the two lowerings are identical — aliased
    gated rows are the corruption class the host allocator + PTA110
    exclude, not something either lowering can repair).

    Since the ownership prover landed, ``exclusive_via`` is more
    than a declaration: the abstract interpreter (analysis/absint.py
    ownership domain) chains the Index input's provenance back to a
    marked host-owned source and PTA191 PROVES lane-exclusivity
    under that source's named allocator assumption — a via that
    disagrees with the proven chain, an index of unknown provenance
    (PTA190), or an index reaching a REFCOUNTED shared entry
    (PTA192 write-while-shared, the COW contract) are build-time
    errors. Dropping covers out-of-range WRITES; reads have no such
    net, which is why PTA190 also proves gather bounds.
    """
    pool = ctx.input("Pool")
    new = ctx.input("New")
    idx = ctx.input("Index")
    gate = ctx.input("Gate")
    lead = int(ctx.attr("leading_dims", 1))
    n = 1
    for d in pool.shape[:lead]:
        n *= int(d)
    tail = pool.shape[lead:]
    rows = new.shape[0]
    idx = idx.reshape(rows).astype(jnp.int32)
    keep = (idx >= 0) & (idx < n)
    if gate is not None:
        keep = keep & (gate.reshape(rows) > 0)
    # a negative index would wrap before mode="drop" looks at it
    safe = jnp.where(keep, idx, n)
    out = pool.reshape((n,) + tail).at[safe].set(
        new.reshape((rows,) + tail).astype(pool.dtype), mode="drop")
    return out.reshape(pool.shape)


@register_op("paged_decode_attention", differentiable=False,
             stop_gradient_slots=("Q", "PoolK", "PoolV", "Table",
                                  "Pos"))
def paged_decode_attention(ctx):
    """Self-attention of the decode tick's queries over a lane's own
    cache positions, read from the SHARED pools where they are stored.

    inputs: Q [R, q, H*Dh] (this tick's query rows); PoolK, PoolV
    [NB*BS, H*Dh] (after this tick's ``masked_pool_write``); Table
    [R, NP] int (the lane's block table: cache position p of lane r is
    pool row ``Table[r, p // BS] * BS + p % BS``); Pos [R] int (the
    cache position of a lane's first query: query j attends positions
    <= Pos + j, so stale cells past a lane's position are masked as
    the dense step's -1e9 bias masks them). attrs: block_size,
    n_heads, scale. Out [R, q, H*Dh], the context rows. Idle and
    dustbin lanes read whatever blocks their table rows name (block 0
    when cleared) and their rows are ignored downstream.

    Nothing of shape ``[R, H, maxT, Dh]`` is built: the routes in
    ops/pallas/paged_attention.py (a Pallas kernel for q = 1 on one
    TPU, a jnp composition elsewhere) keep ``H*Dh`` on the lanes, and
    ``note_route`` records which was taken. Reads are NOT clamped or
    filled: Table must be proven in bounds, which the ownership prover
    does at build time (analysis/absint.py records the read with
    Table as its index, PTA190 wants provenance from a marked host
    table AND a bound that fits NB, and rejects the op otherwise).
    """
    from .pallas import note_route
    from .pallas import paged_attention as PA

    q = ctx.input("Q")
    pool_k, pool_v = ctx.input("PoolK"), ctx.input("PoolV")
    tab, pos = ctx.input("Table"), ctx.input("Pos")
    kw = dict(block_size=int(ctx.attr("block_size")),
              n_heads=int(ctx.attr("n_heads")),
              scale=float(ctx.attr("scale", 1.0)))
    pos = pos.reshape(q.shape[0])
    if note_route("paged_decode_attention", q.shape,
                  PA.usable(q, pool_k, tab, kw["block_size"])):
        return PA.paged_decode_attention(q, pool_k, pool_v, tab, pos,
                                         **kw)
    return PA.paged_attention_reference(q, pool_k, pool_v, tab, pos,
                                        **kw)
