"""Grouped matrix product over ragged groups of rows: rows of `lhs`
sorted by group, one weight matrix a group, only the rows that belong
to a group computed.

No reference counterpart (Fluid has no routed experts). The TPU kernel
is JAX's own megablox `gmm` / `tgmm` pair
(jax/experimental/pallas/ops/tpu/megablox, custom vjp included): it
walks row tiles, looks the tile's group up in scalar-prefetched
metadata and skips the tiles of groups whose weights `rhs` does not
hold, so a buffer sized for the worst case costs what the rows in it
cost: a layer that holds n experts puts their pairs first and every
other pair into one last group. Off the TPU
(and under a mesh, where a Mosaic call cannot be partitioned) the
jnp reference runs: a loop over the held groups with a row mask.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import note_route, on_tpu
from .attention import _interp

# rows, contraction, columns of one tile. 512 rows a tile keeps the
# tiles a group's ragged edge wastes small against 4,096 rows a layer.
TILING = (512, 1024, 1024)
# rows of a tile where no group can have that many: a tile is computed
# once for every group that has a row in it, so among groups of a
# handful of rows each a tile of 512 is mostly other groups' rows
FEW_ROWS = 128


def row_tile(m, groups) -> int:
    """Rows of a tile for m rows over `groups` held groups, from what
    is known when the product is traced: TILING's, FEW_ROWS where the
    rows would not fill a tile of FEW_ROWS a group even if every row
    were a held group's, and all m rows where they fit one tile."""
    tm = TILING[0]
    if m <= tm:
        return m
    return FEW_ROWS if m < FEW_ROWS * groups else tm


def usable(lhs, rhs) -> bool:
    if not (on_tpu() or _interp()):
        return False
    m, k = lhs.shape
    n = rhs.shape[-1]
    tm, tk, tn = _tiling(m, k, n, rhs.shape[0])
    return (lhs.dtype == rhs.dtype and m % tm == 0
            and k % 128 == 0 and n % 128 == 0)


def _tiling(m, k, n, groups):
    _, tk, tn = TILING
    return row_tile(m, groups), min(tk, k), min(tn, n)


def grouped_matmul_reference(lhs, rhs, group_sizes):
    """lhs [m, k] rows sorted by group; `group_sizes` [G] adds up to m;
    rhs [g, k, n] holds the first g <= G groups' weights. Rows of the
    other groups come out zero."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    row = jnp.arange(lhs.shape[0])[:, None]
    out = jnp.zeros((lhs.shape[0], rhs.shape[-1]), jnp.float32)
    for g in range(rhs.shape[0]):
        mine = (row >= starts[g]) & (row < ends[g])
        out = out + jnp.where(mine, jnp.dot(
            lhs, rhs[g], preferred_element_type=jnp.float32), 0.0)
    return out.astype(lhs.dtype)


def grouped_matmul(lhs, rhs, group_sizes):
    """The routed product: megablox on the TPU, the reference
    elsewhere. Differentiable in lhs and rhs either way."""
    if note_route("grouped_matmul", lhs.shape, usable(lhs, rhs)):
        from jax.experimental.pallas.ops.tpu.megablox import ops

        m, k = lhs.shape
        return ops.gmm(lhs, rhs, group_sizes.astype(jnp.int32), lhs.dtype,
                       _tiling(m, k, rhs.shape[-1], rhs.shape[0]),
                       jnp.asarray(0, jnp.int32), None, False, _interp())
    return grouped_matmul_reference(lhs, rhs, group_sizes)
