"""Paged-KV pool ops (models/decode_engine.py paged layout).

Reference counterpart: none — the reference framework's decode caches
are per-request dense tensors (reference
tests/unittests/dist_transformer.py:1498 fast_decode caches). The
shared block pool follows vLLM's PagedAttention block tables
(SOSP'23, PAPERS.md), re-designed for XLA static shapes: the pool is
one persistable tensor, lanes address it through host-allocated
int32 tables, ALL writes funnel through ``masked_pool_write`` so the
lane-exclusivity contract is one auditable surface (analysis checker
PTA110), and a decode tick's attention reads the pools where they are
stored through ``paged_decode_attention``: the self pools behind the
block table, the cross-attention prompt table behind ``prompt_ref``
(the COW copy and the chunked prefill's staging rows still read by
plain `gather`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from ..core.types import to_jnp_dtype


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


# what the routing record calls the op's decision, by what it reads
ROUTE_LABELS = {"cells": "paged_decode_attention",
                "prompt_table": "paged_decode_attention.prompt_table"}
# past this, a tick does not gather every lane's whole table at once (the
# reference and grouped routes do): 33 lanes of 9,216 positions of 3,840
# bfloat16 numbers are 2.2 GB a pool, which the chip cannot hold beside
# pools that fill it. There, on one TPU, a tick of one query a
# lane over bfloat16 pools with a key-value head a query head streams
# each lane's live blocks through VMEM once (the Pallas kernel
# `paged_decode_attention_streamed`); off the TPU, under a mesh,
# for grouped heads and for several queries a lane it reads one lane at
# a time (`paged_attention_by_lane`). Every other cell's tick gathers
# 0.2 GB or less, or takes the float32 kernel
WHOLE_TABLES_BYTES = 1 << 30


@register_op("paged_decode_attention", differentiable=False,
             stop_gradient_slots=("Q", "PoolK", "PoolV", "Table",
                                  "Pos"))
def paged_decode_attention(ctx):
    """Attention of the decode tick's queries over a lane's own
    positions, read from the SHARED pools where they are stored: the
    self-attention over its cache cells and (``reads`` =
    "prompt_table") the cross-attention over its prompt entry, a table
    of one block of ``seq_len`` rows a lane with every lane at the
    last position.

    inputs: Q [R, q, H*Dh] (this tick's query rows); PoolK, PoolV
    [NB*BS, H*Dh] (after this tick's ``masked_pool_write``); Table
    [R, NP] int (the lane's block table: cache position p of lane r is
    pool row ``Table[r, p // BS] * BS + p % BS``); Pos [R] int (the
    cache position of a lane's first query: query j attends positions
    <= Pos + j, so stale cells past a lane's position are masked as
    the dense step's -1e9 bias masks them). attrs: block_size,
    n_heads, scale, n_kv_heads (default n_heads; fewer: grouped
    queries, the pools [NB*BS, Hkv*Dh], query head h reads key-value
    head h // (H / Hkv); the jnp route), reads ("cells" by default,
    or "prompt_table": which read of a tick this is, for the routing
    record alone: no route depends on it). Out [R, q, H*Dh], the
    context rows. Idle and dustbin lanes read whatever blocks their
    table rows name (block 0 when cleared, the dustbin entry of the
    prompt table) and their rows are ignored downstream.

    Nothing of shape ``[R, H, maxT, Dh]`` is built: the routes in
    ops/pallas/paged_attention.py (Pallas kernels for q = 1 on one TPU,
    one over float32 pools and one that streams wide bfloat16 pools
    past ``WHOLE_TABLES_BYTES``; a jnp composition elsewhere) and the
    jnp routes below keep ``H*Dh`` on the lanes, and ``note_route``
    records whether a kernel was taken. Reads are NOT clamped or
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
    n_kv = int(ctx.attr("n_kv_heads", 0)) or kw["n_heads"]
    label = ROUTE_LABELS[ctx.attr("reads", "cells")]
    if q.shape[0] * tab.shape[1] * kw["block_size"] * pool_k.shape[1] \
            * pool_k.dtype.itemsize > WHOLE_TABLES_BYTES:
        if note_route(label, q.shape, PA.streams(
                q, pool_k, tab, kw["block_size"], kw["n_heads"], n_kv)):
            return PA.paged_decode_attention_streamed(
                q, pool_k, pool_v, tab, pos, **kw)
        return paged_attention_by_lane(q, pool_k, pool_v, tab, pos,
                                       n_kv_heads=n_kv, **kw)
    if n_kv != kw["n_heads"]:
        note_route(label, q.shape, False)
        return grouped_paged_attention(q, pool_k, pool_v, tab, pos,
                                       n_kv_heads=n_kv, **kw)
    if note_route(label, q.shape,
                  PA.usable(q, pool_k, tab, kw["block_size"])):
        return PA.paged_decode_attention(q, pool_k, pool_v, tab, pos,
                                         **kw)
    return PA.paged_attention_reference(q, pool_k, pool_v, tab, pos,
                                        **kw)


# ---------------------------------------------------------------------
# The paged side of latent attention with a learned selection
# (models/glm_moe_dsa.py): one block table addresses a latent pool
# [NB*BS, rkv+dr] a layer and, in the layers that own an indexer, a
# pool of indexer keys [NB*BS, di]. Rows come in G groups of n, each
# group one lane with its row of the table: a decode tick has G lanes
# of one query, a prefill chunk one lane of n queries.
# ---------------------------------------------------------------------
QUERY_BLOCK = 128   # queries of a group worked on at once
DENSE_QUERY_BLOCK = 16  # ... where a block makes [b, H, context] scores


def _by_query_blocks(fn, args, size=None):
    """`fn` over arrays [G, n, ...] a block of `size` (QUERY_BLOCK) of
    the n queries at a time (one after another, so that what `fn` makes
    in between is a block's and not a chunk's), the results put back
    together on the same axis; all at once where n is a block or
    less."""
    size = size or QUERY_BLOCK
    n = args[0].shape[1]
    if n <= size or n % size:
        return fn(args)
    cut = [jnp.moveaxis(a.reshape(a.shape[0], n // size, size,
                                  *a.shape[2:]), 1, 0)
           for a in args]
    out = jax.lax.map(fn, tuple(cut))       # [blocks, G, b, ...]
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape(out.shape[0], n, *out.shape[3:])


def _over_live_pages(run, n_pages, block_size, live):
    """`run(pages)` for the fewest pages of a table that hold `live`
    positions (a traced scalar), out of a half, three quarters and all
    of them: many queries of one lane cost what that lane's context is
    long, not what the longest context could be. `run` returns the same
    shape whatever `pages` is."""
    cuts = sorted({max(1, -(-n_pages * f // 4)) for f in (2, 3, 4)})
    if live is None or len(cuts) == 1:
        return run(n_pages)
    which = sum((live > c * block_size).astype(jnp.int32)
                for c in cuts[:-1])
    return jax.lax.switch(which, [functools.partial(run, c)
                                  for c in cuts])


def _group_cells(tab, pos, block_size):
    """Pool rows of positions `pos` [G, n] under `tab` [G, NP]; a
    position past the table reads its last page."""
    page = jnp.clip(pos // block_size, 0, tab.shape[1] - 1)
    return jnp.take_along_axis(tab.astype(jnp.int32), page, axis=1) \
        * block_size + pos % block_size


def grouped_paged_attention(q, pool_k, pool_v, tab, pos, *, block_size,
                            n_heads, n_kv_heads, scale, live=None):
    """Grouped-query attention over paged keys and values: q [G, n,
    H*Dh]; pools [NB*BS, Hkv*Dh]; tab [G, NP]; pos [G] (query j of
    group g sees the group's positions <= pos[g] + j) or [G, n] (each
    query's own position). `live`: how many positions the longest row
    reaches, where the caller knows it (many queries of one lane read
    only the pages that hold them). Scores, softmax and accumulation
    float32, operands as stored. Out [G, n, H*Dh] in q's dtype."""
    g, n, hd = q.shape
    dh = hd // n_heads
    rep = n_heads // n_kv_heads
    n_pages = tab.shape[1]
    pos = pos.astype(jnp.int32)
    if pos.ndim == 1:
        pos = pos[:, None] + jnp.arange(n, dtype=jnp.int32)[None]
    tab = tab.astype(jnp.int32)
    # the pools stay [rows, Hkv*Dh] with the heads side by side on the
    # lanes, and a key-value head is a slice of whole lane tiles: a
    # [.., Hkv, Dh] view of a pool is another tiling, which the compiler
    # makes by copying the pool whole, every tick (PERF.md, PR 34)
    kb = pool_k.reshape(-1, block_size, pool_k.shape[-1])
    vb = pool_v.reshape(-1, block_size, pool_v.shape[-1])
    args = (q.reshape(g, n, n_kv_heads, rep, dh), pos)

    def run(pages):
        t = pages * block_size
        k = kb.at[tab[:, :pages]].get(mode="promise_in_bounds").reshape(
            g, t, -1)
        v = vb.at[tab[:, :pages]].get(mode="promise_in_bounds").reshape(
            g, t, -1)
        at = jnp.arange(t, dtype=jnp.int32)

        def block(args):
            qb, pb = args       # [G, b, Hkv, rep, Dh], [G, b]
            seen = (at[None, None] <= pb[..., None])[:, :, None]
            heads = []
            for kv in range(n_kv_heads):
                mine = slice(kv * dh, (kv + 1) * dh)
                s = jnp.einsum("gbrd,gtd->gbrt", qb[:, :, kv],
                               k[..., mine],
                               preferred_element_type=jnp.float32) * scale
                s = jnp.where(seen, s, -jnp.inf)
                m = jnp.maximum(s.max(-1, keepdims=True), -1e30)
                p = jnp.exp(s - m)
                p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
                heads.append(jnp.einsum(
                    "gbrt,gtd->gbrd", p.astype(v.dtype), v[..., mine],
                    preferred_element_type=jnp.float32))
            out = jnp.stack(heads, 2)           # [G, b, Hkv, rep, Dh]
            return out.reshape(g, qb.shape[1], hd).astype(q.dtype)

        return _by_query_blocks(block, args)

    return _over_live_pages(run, n_pages, block_size, live)


def paged_attention_by_lane(q, pool_k, pool_v, tab, pos, *, block_size,
                            n_heads, n_kv_heads, scale, cuts=8):
    """`grouped_paged_attention` of q [R, n, H*Dh] (query j of lane r
    at position pos[r] + j) one lane after another (`lax.map`), each
    over the fewest of `cuts` even shares of its table that hold its
    positions (`lax.switch`): a tick reads what its lanes hold, and
    holds one lane's keys and values at a time, where gathering every
    lane's whole table ([R, NP*BS, Hkv*Dh]) would not fit beside wide
    pools. Scores, softmax and accumulation float32, operands as
    stored. Out [R, n, H*Dh] in q's dtype."""
    r, n, hd = q.shape
    dh = hd // n_heads
    rep = n_heads // n_kv_heads
    n_pages = tab.shape[1]
    kb = pool_k.reshape(-1, block_size, pool_k.shape[-1])
    vb = pool_v.reshape(-1, block_size, pool_v.shape[-1])
    sizes = sorted({max(1, -(-n_pages * f // cuts))
                    for f in range(1, cuts + 1)})

    def lane(args):
        qr, row, first = args           # [n, H*Dh], [NP], []

        def run(pages):
            t = pages * block_size
            k, v = (b.at[row[:pages]].get(mode="promise_in_bounds")
                    .reshape(t, n_kv_heads, dh) for b in (kb, vb))
            s = jnp.einsum("qgrd,tgd->grqt",
                           qr.reshape(n, n_kv_heads, rep, dh), k,
                           preferred_element_type=jnp.float32) * scale
            seen = jnp.arange(t, dtype=jnp.int32)[None] \
                <= first + jnp.arange(n, dtype=jnp.int32)[:, None]
            s = jnp.where(seen, s, -jnp.inf)
            p = jnp.exp(s - s.max(-1, keepdims=True))
            p = p / p.sum(-1, keepdims=True)
            out = jnp.einsum("grqt,tgd->qgrd", p.astype(v.dtype), v,
                             preferred_element_type=jnp.float32)
            return out.reshape(n, hd).astype(q.dtype)

        live = first + n
        which = sum((live > c * block_size).astype(jnp.int32)
                    for c in sizes[:-1])
        return jax.lax.switch(which, [functools.partial(run, c)
                                      for c in sizes])

    return jax.lax.map(lane, (q, tab.astype(jnp.int32),
                              pos.astype(jnp.int32)))


@register_op("paged_prefill_attention", differentiable=False,
             stop_gradient_slots=("Q", "PoolK", "PoolV", "Table", "Pos"))
def paged_prefill_attention(ctx):
    """Causal attention of a prefill chunk's queries over the lane's
    paged prefix and the chunk itself. Q [N, H*Dh], N = G * n rows in
    G groups (a chunk: one lane, G = 1); PoolK, PoolV [NB*BS, Hkv*Dh]
    (after the chunk's own write); Table [G, NP]; Pos [N], each row's
    cache position (it sees the positions <= its own). Only the pages
    that hold the rows' positions are read (`_over_live_pages`). attrs
    block_size, n_heads, n_kv_heads, scale. Out [N, H*Dh]."""
    q, tab = ctx.input("Q"), ctx.input("Table")
    g = tab.shape[0]
    pos = ctx.input("Pos").reshape(g, -1).astype(jnp.int32)
    n_heads = int(ctx.attr("n_heads"))
    out = grouped_paged_attention(
        q.reshape(g, -1, q.shape[-1]), ctx.input("PoolK"),
        ctx.input("PoolV"), tab, pos,
        block_size=int(ctx.attr("block_size")), n_heads=n_heads,
        n_kv_heads=int(ctx.attr("n_kv_heads", 0)) or n_heads,
        scale=float(ctx.attr("scale", 1.0)), live=pos.max() + 1)
    return {"Out": out.reshape(q.shape)}


@register_op("paged_cell_index", differentiable=False,
             stop_gradient_slots=("Table", "Pos"))
def paged_cell_index(ctx):
    """Table [G, NP] int, Pos [G*n] int -> Out [G*n] int32: the pool
    row of each position through its group's row of the table (row
    Table[g, p // BS] * BS + p % BS)."""
    tab = ctx.input("Table")
    pos = ctx.input("Pos").reshape(tab.shape[0], -1).astype(jnp.int32)
    return {"Out": _group_cells(tab, pos, int(ctx.attr("block_size")))
            .reshape(-1)}


def indexer_scores(qi, w, pool, tab, pos, block_size):
    """qi [N, hi, di]; w [N, hi]; pool [NB*BS, di]; tab [G, NP]; pos
    [N] -> [N, NP*BS] float32 (what `dsa_indexer_scores` computes).
    Many queries of one lane read only as many pages as hold their
    positions (`_over_live_pages`); what lies past is -inf either
    way."""
    bs = block_size
    g, n_pages = tab.shape
    n = qi.shape[0] // g
    pos = pos.reshape(g, n).astype(jnp.int32)
    blocks = pool.reshape(-1, bs, pool.shape[-1])
    args = (qi.reshape(g, n, *qi.shape[1:]),
            w.reshape(g, n, -1).astype(jnp.float32), pos)

    def run(pages):
        keys = blocks[tab[:, :pages].astype(jnp.int32)]  # [G,P,BS,di]
        at = jnp.arange(pages * bs)

        def block(args):
            q, wt, p = args     # [G, b, hi, di], [G, b, hi], [G, b]
            sc = jnp.einsum("gnhd,gpbd->gnhpb", q, keys,
                            preferred_element_type=jnp.float32)
            out = jnp.einsum("gnhpb,gnh->gnpb", jax.nn.relu(sc), wt)
            out = out.reshape(g, q.shape[1], pages * bs)
            return jnp.where(at[None, None] <= p[..., None], out,
                             -jnp.inf)

        score = _by_query_blocks(block, args)
        return jnp.pad(score, ((0, 0), (0, 0),
                               (0, (n_pages - pages) * bs)),
                       constant_values=-jnp.inf)

    # one query a lane (a tick): the lanes' contexts differ, all pages
    live = pos.max() + 1 if n > 1 else None
    return _over_live_pages(run, n_pages, bs, live).reshape(g * n, -1)


@register_op("dsa_indexer_scores", differentiable=False,
             stop_gradient_slots=("QI", "W", "Pool", "Table", "Pos"))
def dsa_indexer_scores(ctx):
    """The indexer's score of every cached position of a row's lane:
    I[t, s] = sum_j W[t, j] * relu(QI[t, j] . k^I[s]) for s <= Pos[t],
    and -inf past it. QI [N, hi, di]; W [N, hi] float32; Pool [NB*BS,
    di] (after this step's write); Table [G, NP]; Pos [N]. Out [N,
    NP*BS] float32. The keys are read a block at a time through the
    table (a lane's whole context, live or not; what lies past Pos is
    masked); scores and their sum are float32."""
    with jax.named_scope("glm.indexer"):
        return {"Out": indexer_scores(
            ctx.input("QI"), ctx.input("W"), ctx.input("Pool"),
            ctx.input("Table"), ctx.input("Pos").reshape(-1),
            int(ctx.attr("block_size")))}


def kth_largest(s, k):
    """The k-th largest of each row of s [N, T] float32, exactly, with
    no sort: 32 halvings of the range of the values' bit patterns
    (mapped so that they order as the values do), each a count of the
    row's values at or above the middle. -inf where a row has fewer
    than k finite values above it."""
    u = jax.lax.bitcast_convert_type(s, jnp.uint32)
    u = jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))

    def halve(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2 + (hi - lo) % 2
        enough = jnp.sum(u >= mid[:, None], axis=1) >= k
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid - 1)

    lo = jnp.zeros((s.shape[0],), jnp.uint32)
    lo, _ = jax.lax.fori_loop(
        0, 32, halve, (lo, jnp.full_like(lo, 0xFFFFFFFF)))
    back = jnp.where(lo >> 31 == 1, lo & jnp.uint32(0x7FFFFFFF), ~lo)
    return jax.lax.bitcast_convert_type(back, jnp.float32)


@register_op("dsa_select", differentiable=False,
             stop_gradient_slots=("Scores",))
def dsa_select(ctx):
    """A row's selection: its `k` largest scores, all of its live
    positions while it has at most k. Scores [N, T] float32, -inf where
    not live. mode "indices": Out [N, min(k, T)] int32, the positions,
    -1 where a row has fewer live ones, in no particular order (a
    softmax does not ask for one); by `lax.top_k`. mode "threshold":
    Out [N] float32, the k-th largest score (-inf where a row has fewer
    live positions): the selection is the live positions whose score
    is at or above it, found without a sort (`kth_largest`), which is
    what many queries of one lane can afford."""
    s = ctx.input("Scores")
    k = min(int(ctx.attr("k")), s.shape[-1])
    with jax.named_scope("glm.select"):
        if ctx.attr("mode", "indices") == "threshold":
            return {"Out": kth_largest(s, k)}
        val, idx = jax.lax.top_k(s, k)
        return {"Out": jnp.where(val > -jnp.inf, idx, -1)
                .astype(jnp.int32)}


def dense_masked_latent_attention(q, pool, tab, scores, thr, k,
                                  block_size, latent_dim, scale):
    """The same attention for many queries of one lane (a prefill
    chunk): q [N, H, rkv+dr]; scores [N, T] the indexer's (-inf where
    not live), thr [N] each query's k-th largest (`k` of them are
    attended: where scores tie at the threshold, the earliest
    positions). The lane's context, as many pages as the scores are
    live in (`_over_live_pages`), is read once, a block at a time
    through the table, and every query attends the positions whose
    score is at or above its threshold: K rows a query by gather would
    move more than the context does. [N, H, latent_dim] float32."""
    g, n_pages = tab.shape
    n = q.shape[0] // g
    blocks = pool.reshape(-1, block_size, pool.shape[-1])
    scores = scores.reshape(g, n, -1)
    args = (q.reshape(g, n, *q.shape[1:]), thr.reshape(g, n))

    def run(pages):
        t = pages * block_size
        rows = blocks[tab[:, :pages].astype(jnp.int32)].reshape(
            g, t, pool.shape[-1])

        def block(args):
            qb, tb, sb = args   # [G, b, H, W], [G, b], [G, b, T]
            s = jnp.einsum("gnhd,gtd->gnht", qb, rows,
                           preferred_element_type=jnp.float32) * scale
            # the k largest, the earliest first among equal scores (as
            # a sort by score keeps them): every score above the
            # threshold, and of those that equal it as many as are
            # still missing
            live = sb > -jnp.inf
            above = sb > tb[..., None]
            ties = (sb == tb[..., None]) & live
            room = k - jnp.sum(above, -1, keepdims=True)
            chosen = (above | (ties & (jnp.cumsum(ties, -1) <= room))) \
                & live
            s = jnp.where(chosen[:, :, None, :], s, -jnp.inf)
            m = jnp.maximum(s.max(-1, keepdims=True), -1e30)
            p = jnp.exp(s - m)
            p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
            return jnp.einsum("gnht,gtd->gnhd", p.astype(rows.dtype),
                              rows[..., :latent_dim],
                              preferred_element_type=jnp.float32)

        return _by_query_blocks(block, args + (scores[..., :t],),
                                DENSE_QUERY_BLOCK)

    # the scores say how far the queries' positions reach
    live = jnp.sum(jnp.any(scores > -jnp.inf, axis=(0, 1)))
    out = _over_live_pages(run, n_pages, block_size, live)
    return out.reshape(g * n, *out.shape[2:])


def sparse_latent_attention_reference(q, pool, tab, sel, block_size,
                                      latent_dim, scale, cells=None):
    """q [N, H, W]; pool [NB*BS, W]; tab [G, NP]; sel [N, K] positions
    (-1: none); cells [N*K] their pool rows where the caller has them
    already (layers that share a selection share them). The selected
    rows are gathered; scores, softmax and the weights float32; the
    weighted sum is over the first `latent_dim` numbers of a row. [N,
    H, latent_dim] float32."""
    g = tab.shape[0]
    n = q.shape[0] // g

    def block(args):
        qb, sb, cb = args       # [G, b, H, W], [G, b, K], [G, b, K]
        valid = sb >= 0
        rows = pool[cb]                             # [G, b, K, W]
        s = jnp.einsum("gnhd,gnkd->gnhk", qb, rows,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid[:, :, None, :], s, -jnp.inf)
        # a row with nothing selected (padding) gives zeros, not NaN
        m = jnp.maximum(s.max(-1, keepdims=True), -1e30)
        p = jnp.exp(s - m)
        p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
        return jnp.einsum("gnhk,gnkd->gnhd", p.astype(rows.dtype),
                          rows[..., :latent_dim],
                          preferred_element_type=jnp.float32)

    if cells is None:
        cells = _group_cells(tab, jnp.maximum(sel, 0).reshape(g, -1),
                             block_size)
    out = _by_query_blocks(
        block, (q.reshape(g, n, *q.shape[1:]), sel.reshape(g, n, -1),
                cells.reshape(g, n, -1)))
    return out.reshape(g * n, *out.shape[2:])


@register_op("sparse_latent_attention", differentiable=False,
             stop_gradient_slots=("Q", "Pool", "Table", "Sel", "Cells",
                                  "Scores", "Thr"))
def sparse_latent_attention(ctx):
    """Attention of N absorbed queries over the rows of the latent
    pool that each one's selection names. Q [N, H, rkv+dr]; Pool
    [NB*BS, rkv+dr] (after this step's write); Table [G, NP]; the
    selection either as Sel [N, K] int32 (positions of the row's lane,
    -1 for none, with Cells [N*K] their pool rows if the caller made
    them: the selected rows alone are read, K a query whatever the
    lane's context; a decode tick's route) or as Scores [N, T] and
    Thr [N] (`dsa_select` mode "threshold": the lane's context is read
    once for all its queries; a prefill chunk's route; attr k, the
    selection's size). attrs: block_size, latent_dim (rkv: the part of a row that is summed),
    scale. Out [N, H, rkv] float32."""
    q, pool, tab = ctx.input("Q"), ctx.input("Pool"), ctx.input("Table")
    kw = (int(ctx.attr("block_size")), int(ctx.attr("latent_dim")),
          float(ctx.attr("scale", 1.0)))
    with jax.named_scope("glm.sparse_attn"):
        if ctx.input("Sel") is not None:
            return {"Out": sparse_latent_attention_reference(
                q, pool, tab, ctx.input("Sel"), *kw,
                cells=ctx.input("Cells"))}
        return {"Out": dense_masked_latent_attention(
            q, pool, tab, ctx.input("Scores"), ctx.input("Thr"),
            int(ctx.attr("k")), *kw)}


@register_op("lane_probe_write", differentiable=False,
             stop_gradient_slots=("Hist", "New", "Step", "Gate"))
def lane_probe_write(ctx):
    """Keep what a tick's live lanes computed where the host can read
    it back: Hist [R, K] or [R, T, K] (also the op's output, in place);
    New [R, K]; Gate [R] 0/1; Step [R] int (given for the 3-D form:
    the row of a lane's history that this tick fills). Lanes with gate
    0 keep what they had."""
    hist, new = ctx.input("Hist"), ctx.input("New").astype(
        ctx.input("Hist").dtype)
    on = ctx.input("Gate").reshape(-1) > 0
    step = ctx.input("Step")
    if step is None:
        return jnp.where(on[:, None], new, hist)
    here = (jnp.arange(hist.shape[1])[None] == step.reshape(-1, 1)) \
        & on[:, None]
    return jnp.where(here[..., None], new[:, None], hist)


@register_op("pack_row", differentiable=False, stop_gradient_slots=("X",))
def pack_row(ctx):
    """X (a list of integer arrays of any shapes) laid end to end, each
    flattened, as one flat row of the output's dtype: what a serve
    program hands back to its scheduler in one array
    (models/decode_engine.ServeRow cuts it back by the same shapes)."""
    dtype = jax.dtypes.canonicalize_dtype(
        to_jnp_dtype(ctx.attr("dtype", "int64")))
    return jnp.concatenate(
        [x.reshape(-1).astype(dtype) for x in ctx.inputs("X")])

