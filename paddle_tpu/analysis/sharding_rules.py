"""Sharding-propagation rules for the core op families.

GSPMD-style spec propagation (Xu et al., "GSPMD: General and Scalable
Parallelization for ML Computation Graphs") over the Program IR: each
rule states how one op family carries a {tensor dim -> mesh axis}
placement from inputs to outputs, and which collectives its XLA
lowering IMPLIES under those placements (a matmul contracting a
sharded dim is a partial-sum + psum; a reduce over a sharded dim is a
psum; a reshape that breaks a sharded dim forces a GSPMD reshard).
The abstract interpreter (analysis/absint.py) runs these rules to the
same fixpoint as the divergence domain; the PTA160/161 provers and
the PTA170 per-device memory planner read the resulting facts.

Rules register through ``core.registry.register_sharding_rule`` —
alongside the kernels they describe — so adding an op that touches
sharded state means adding its propagation fact in the same place
(CLAUDE.md conventions). Ops WITHOUT a rule degrade to the explicit
⊤ spec (warn-once in absint) the moment a sharded value reaches
them: imprecision is visible, never silently wrong.

Rule contract::

    rule(op, spec_of, shape_of, mesh) -> (out_specs, events)

* ``spec_of(name) -> ShardSpec``, ``shape_of(name) -> tuple | None``
* ``out_specs``: {output var name -> ShardSpec}
* ``events``: [CollectiveEvent] the lowering implies at this site

Rules are PURE metadata functions: no jax, no tracing — the whole
zoo propagates in milliseconds.

Reference counterpart: none — the reference sharded at runtime via
transpilers (reference transpiler/distribute_transpiler.py); the
compile-time layout algebra is the Megatron-LM / GSPMD capability.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

from ..core.registry import EMPTY_VAR, register_sharding_rule
from .absint import (REPLICATED_SPEC, TOP_SPEC, CollectiveEvent,
                     ShardSpec, spec_join)

__all__ = ["RULE_FAMILIES"]

# family name -> op types it covers (documentation + the property
# tests' enumeration; the actual registry is core.registry's)
RULE_FAMILIES: Dict[str, Tuple[str, ...]] = {}


def _family(name, op_types):
    RULE_FAMILIES[name] = tuple(op_types)

    def deco(fn):
        register_sharding_rule(op_types, fn)
        return fn

    return deco


def _outs(op):
    return [n for n in op.output_arg_names if n != EMPTY_VAR]


def _in(op, slot, idx=0):
    names = op.inputs.get(slot) or []
    return names[idx] if len(names) > idx else None


def _shift(spec: ShardSpec, delta: int, start: int = 0) -> ShardSpec:
    """Shift placement dims >= start by delta (unsqueeze/reduce)."""
    if spec.placements is None:
        return spec
    return ShardSpec.of([(d + delta if d >= start else d, a)
                         for d, a in spec.placements])


def _all_outs(op, spec, events=()):
    return {n: spec for n in _outs(op)}, list(events)


# ---------------------------------------------------------------------------
# elementwise / identity family: layout passes straight through
# ---------------------------------------------------------------------------
@_family("identity", (
    "assign", "cast", "scale", "relu", "sigmoid", "tanh", "exp",
    "log", "sqrt", "square", "abs", "clip", "dropout", "increment",
    "brelu", "elu", "leaky_relu", "relu6", "softsign", "softplus",
    "gelu", "fill_zeros_like", "fill_any_like", "sign", "floor",
    "ceil", "round", "reciprocal", "logical_not", "optimization_barrier",
))
def rule_identity(op, spec_of, shape_of, mesh):
    src = _in(op, "X") or (op.input_arg_names[:1] or [None])[0]
    spec = spec_of(src) if src and src != EMPTY_VAR else REPLICATED_SPEC
    return _all_outs(op, spec)


@_family("elementwise", (
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_min", "elementwise_max",
    "elementwise_pow", "elementwise_mod", "elementwise_floordiv",
    "equal", "not_equal", "greater_than", "greater_equal",
    "less_than", "less_equal", "logical_and", "logical_or",
))
def rule_elementwise(op, spec_of, shape_of, mesh):
    """Binary elementwise with the fluid `axis` broadcast: Y's dims
    align into X at offset `axis` (default: trailing). Two full-rank
    operands demanding different placements is a sharding
    CONTRADICTION — GSPMD must reshard one side at this site."""
    x, y = _in(op, "X"), _in(op, "Y")
    sx = spec_of(x) if x else REPLICATED_SPEC
    sy = spec_of(y) if y else REPLICATED_SPEC
    if sx.is_top or sy.is_top:
        return _all_outs(op, TOP_SPEC)
    shx, shy = shape_of(x) if x else None, shape_of(y) if y else None
    if shx is not None and shy is not None and len(shy) < len(shx):
        axis = op.attrs.get("axis", -1)
        off = len(shx) - len(shy) if axis in (-1, None) else int(axis)
        sy = _shift(sy, off)
    if sy.is_replicated or sx == sy:
        return _all_outs(op, sx)
    if sx.is_replicated:
        return _all_outs(op, sy)
    ev = CollectiveEvent(
        "conflict", tuple(sx.axes()) + tuple(sy.axes()),
        _outs(op)[0] if _outs(op) else None,
        f"elementwise operands demand incompatible specs "
        f"{sx.describe()} vs {sy.describe()}: GSPMD reshards one "
        f"side at this site")
    return _all_outs(op, TOP_SPEC, [ev])


@_family("sum", ("sum",))
def rule_sum(op, spec_of, shape_of, mesh):
    specs = [spec_of(n) for n in op.inputs.get("X", [])
             if n != EMPTY_VAR]
    if not specs:
        return _all_outs(op, REPLICATED_SPEC)
    out = specs[0]
    for s in specs[1:]:
        if s != out and not s.is_replicated and not out.is_replicated:
            ev = CollectiveEvent(
                "conflict", tuple(out.axes()) + tuple(s.axes()),
                _outs(op)[0] if _outs(op) else None,
                f"sum operands demand incompatible specs "
                f"{out.describe()} vs {s.describe()}")
            return _all_outs(op, TOP_SPEC, [ev])
        out = s if out.is_replicated else out
    return _all_outs(op, out)


# ---------------------------------------------------------------------------
# contraction family: mul (the fc matmul) and matmul
# ---------------------------------------------------------------------------
def _contraction(out_var, keep_a, keep_b, contracted, why):
    """Shared tail: psum event iff any contracted placement exists."""
    events = []
    if contracted:
        events.append(CollectiveEvent(
            "psum", tuple(sorted({a for a in contracted})), out_var,
            why))
    return events


@_family("mul", ("mul",))
def rule_mul(op, spec_of, shape_of, mesh):
    """The fc matmul: X flattens to [prod(:p), prod(p:)], Y to
    [prod(:q), prod(q:)] (p = x_num_col_dims, q = y_num_col_dims);
    out rank = p + (rank_y - q). Sharded contraction dims (X dims
    >= p, Y dims < q) are Megatron row-parallel: each device holds a
    partial product and the lowering implies a psum over the
    contraction axes."""
    x, y = _in(op, "X"), _in(op, "Y")
    sx, sy = spec_of(x), spec_of(y)
    if sx.is_top or sy.is_top:
        return _all_outs(op, TOP_SPEC)
    p = int(op.attrs.get("x_num_col_dims", 1))
    q = int(op.attrs.get("y_num_col_dims", 1))
    shy = shape_of(y)
    rank_y = len(shy) if shy is not None else 2
    out_places = []
    contracted = []
    for d, a in (sx.placements or ()):
        if d < p:
            out_places.append((d, a))
        else:
            contracted.append(a)
    for d, a in (sy.placements or ()):
        if d < q:
            contracted.append(a)
        else:
            out_places.append((p + d - q, a))
    out = _outs(op)
    events = _contraction(
        out[0] if out else None, None, None, contracted,
        "matmul contracts a sharded dim: each device holds a partial "
        "product; the lowering implies a psum over the contraction "
        "axes (Megatron row-parallel)")
    return {n: ShardSpec.of(out_places) for n in out}, events


@_family("matmul", ("matmul",))
def rule_matmul(op, spec_of, shape_of, mesh):
    """Batched matmul [..., m, k] x [..., k, n] (transpose_x/y
    attrs): batch placements carry from X, m from X, n from Y;
    a sharded k implies a psum."""
    x, y = _in(op, "X"), _in(op, "Y")
    sx, sy = spec_of(x), spec_of(y)
    if sx.is_top or sy.is_top:
        return _all_outs(op, TOP_SPEC)
    shx, shy = shape_of(x), shape_of(y)
    if shx is None or shy is None:
        if sx.is_replicated and sy.is_replicated:
            return _all_outs(op, REPLICATED_SPEC)
        return _all_outs(op, TOP_SPEC)
    rx, ry = len(shx), len(shy)
    tx = bool(op.attrs.get("transpose_x", False))
    ty = bool(op.attrs.get("transpose_y", False))
    xm, xk = (rx - 1, rx - 2) if tx else (rx - 2, rx - 1)
    yk, yn = (ry - 1, ry - 2) if ty else (ry - 2, ry - 1)
    out_rank = max(rx, ry)
    out_places = []
    contracted = []
    for d, a in (sx.placements or ()):
        if d == xk:
            contracted.append(a)
        elif d == xm:
            out_places.append((out_rank - 2, a))
        elif d < rx - 2:
            out_places.append((d + (out_rank - rx), a))
    for d, a in (sy.placements or ()):
        if d == yk:
            contracted.append(a)
        elif d == yn:
            out_places.append((out_rank - 1, a))
        elif d < ry - 2:
            dd = d + (out_rank - ry)
            if all(od != dd for od, _ in out_places):
                out_places.append((dd, a))
    out = _outs(op)
    events = _contraction(
        out[0] if out else None, None, None, contracted,
        "matmul contracts a sharded dim: each device holds a partial "
        "product; the lowering implies a psum over the contraction "
        "axes")
    # two batch placements landing on one out dim would have
    # collided above (first-wins); a genuine disagreement surfaces
    # as an elementwise conflict downstream
    return {n: ShardSpec.of(out_places) for n in out}, events


# ---------------------------------------------------------------------------
# layout movers: transpose / reshape / squeeze / unsqueeze / expand
# ---------------------------------------------------------------------------
@_family("transpose", ("transpose", "transpose2"))
def rule_transpose(op, spec_of, shape_of, mesh):
    x = _in(op, "X")
    sx = spec_of(x)
    if sx.is_top or sx.is_replicated:
        return _all_outs(op, sx)
    perm = op.attrs.get("perm") or op.attrs.get("axis")
    if not perm:
        return _all_outs(op, TOP_SPEC)
    perm = [int(p) for p in perm]
    out_places = []
    for d, a in sx.placements:
        if d in perm:
            out_places.append((perm.index(d), a))
    return _all_outs(op, ShardSpec.of(out_places))


def _reshape_groups(in_shape, out_shape):
    """Greedy factorization of a reshape into (in_dims, out_dims)
    groups with equal products; None when the shapes do not factor
    cleanly (dynamic dims, -1, non-matching products)."""
    if any(d is None or d < 0 for d in in_shape) or \
            any(d is None or d < 0 for d in out_shape):
        return None
    groups = []
    i = j = 0
    while i < len(in_shape) or j < len(out_shape):
        gi, gj = [i], [j]
        pi = in_shape[i] if i < len(in_shape) else 1
        pj = out_shape[j] if j < len(out_shape) else 1
        while pi != pj:
            if pi < pj and gi[-1] + 1 < len(in_shape):
                gi.append(gi[-1] + 1)
                pi *= in_shape[gi[-1]]
            elif pj < pi and gj[-1] + 1 < len(out_shape):
                gj.append(gj[-1] + 1)
                pj *= out_shape[gj[-1]]
            else:
                return None
        # absorb trailing 1s so indices advance
        groups.append((gi, gj))
        i, j = gi[-1] + 1, gj[-1] + 1
    return groups


@_family("reshape", ("reshape", "reshape2"))
def rule_reshape(op, spec_of, shape_of, mesh):
    """A placement survives a reshape when its dim maps 1:1, or when
    it rides the MAJOR dim of a clean split/merge group whose size
    the mesh axis still divides (GSPMD's divisibility condition).
    Anything else is a forced reshard — the r5 'dp on the
    pre-reshape dim' family."""
    x = _in(op, "X")
    sx = spec_of(x)
    if sx.is_top or sx.is_replicated:
        return _all_outs(op, sx)
    in_shape = shape_of(x)
    out_names = _outs(op)
    out_shape = shape_of(out_names[0]) if out_names else None
    if in_shape is None or out_shape is None:
        return _all_outs(op, TOP_SPEC)
    groups = _reshape_groups(in_shape, out_shape)
    if groups is None:
        ev = CollectiveEvent(
            "reshard", sx.axes(), out_names[0] if out_names else None,
            f"reshape {in_shape}->{out_shape} does not factor; the "
            f"sharded layout {sx.describe()} cannot carry through")
        return _all_outs(op, TOP_SPEC, [ev])
    out_places = []
    events = []
    for d, a in sx.placements:
        grp = next((g for g in groups if d in g[0]), None)
        if grp is None:
            continue
        gi, gj = grp
        major_in, major_out = gi[0], gj[0]
        size = mesh.size(a) if mesh is not None else None
        carries = (d == major_in) and (
            size is None or out_shape[major_out] % size == 0)
        if len(gi) == 1 and len(gj) == 1:
            out_places.append((gj[0], a))
        elif carries:
            out_places.append((major_out, a))
        else:
            events.append(CollectiveEvent(
                "reshard", (a,),
                out_names[0] if out_names else None,
                f"reshape {in_shape}->{out_shape} splits/merges the "
                f"{a}-sharded dim {d} off the major position: GSPMD "
                f"must reshard (the r5 pre-reshape-dim trap)"))
    return _all_outs(op, ShardSpec.of(out_places), events)


@_family("unsqueeze", ("unsqueeze", "unsqueeze2"))
def rule_unsqueeze(op, spec_of, shape_of, mesh):
    x = _in(op, "X")
    sx = spec_of(x)
    if sx.is_top or sx.is_replicated:
        return _all_outs(op, sx)
    axes = sorted(int(a) for a in (op.attrs.get("axes") or []))
    for pos in axes:
        sx = _shift(sx, 1, start=pos)
    return _all_outs(op, sx)


@_family("squeeze", ("squeeze", "squeeze2"))
def rule_squeeze(op, spec_of, shape_of, mesh):
    x = _in(op, "X")
    sx = spec_of(x)
    if sx.is_top or sx.is_replicated:
        return _all_outs(op, sx)
    axes = sorted((int(a) for a in (op.attrs.get("axes") or [])),
                  reverse=True)
    for pos in axes:
        if sx.axis_of(pos) is not None:
            return _all_outs(op, TOP_SPEC)  # squeezing a sharded dim
        # the squeezed position itself is unsharded (checked above),
        # so shifting higher dims down is the whole story — a
        # placement landing ON pos after the shift is dim pos+1's,
        # legitimately renumbered
        sx = _shift(sx, -1, start=pos + 1)
    return _all_outs(op, sx)


@_family("expand", ("expand",))
def rule_expand(op, spec_of, shape_of, mesh):
    x = _in(op, "X")
    sx = spec_of(x)
    if sx.is_top or sx.is_replicated:
        return _all_outs(op, sx)
    times = [int(t) for t in (op.attrs.get("expand_times") or [])]
    out_places = []
    events = []
    for d, a in sx.placements:
        if d < len(times) and times[d] != 1:
            events.append(CollectiveEvent(
                "reshard", (a,), _outs(op)[0] if _outs(op) else None,
                f"expand tiles the {a}-sharded dim {d}: the tiled "
                f"layout needs an allgather/reshard"))
        else:
            out_places.append((d, a))
    return _all_outs(op, ShardSpec.of(out_places), events)


# ---------------------------------------------------------------------------
# reductions & normalizations
# ---------------------------------------------------------------------------
def _reduce_places(spec, dims, rank, keep_dim):
    dropped_axes = []
    out_places = []
    dimset = {d % rank for d in dims}
    for d, a in (spec.placements or ()):
        if d in dimset:
            dropped_axes.append(a)
        elif keep_dim:
            out_places.append((d, a))
        else:
            out_places.append((d - sum(1 for r in dimset if r < d), a))
    return out_places, dropped_axes


@_family("reduce", ("reduce_sum", "reduce_mean", "reduce_max",
                    "reduce_min", "reduce_prod", "frobenius_norm"))
def rule_reduce(op, spec_of, shape_of, mesh):
    x = _in(op, "X")
    sx = spec_of(x)
    if sx.is_top:
        return _all_outs(op, TOP_SPEC)
    if sx.is_replicated:
        return _all_outs(op, REPLICATED_SPEC)
    shape = shape_of(x)
    rank = len(shape) if shape is not None else None
    dims = op.attrs.get("dim")
    if op.attrs.get("reduce_all") or dims is None:
        dims = list(range(rank)) if rank is not None else None
    elif isinstance(dims, int):
        dims = [dims]
    if rank is None or dims is None:
        return _all_outs(op, TOP_SPEC)
    keep = bool(op.attrs.get("keep_dim", False))
    out_places, dropped = _reduce_places(sx, dims, rank, keep)
    events = []
    if dropped:
        events.append(CollectiveEvent(
            "psum", tuple(sorted(set(dropped))),
            _outs(op)[0] if _outs(op) else None,
            f"{op.type} reduces over dim(s) sharded on "
            f"{sorted(set(dropped))}: the lowering implies a psum "
            f"over those mesh axes"))
    return _all_outs(op, ShardSpec.of(out_places), events)


@_family("argminmax", ("arg_max", "arg_min", "argmax", "argmin"))
def rule_argminmax(op, spec_of, shape_of, mesh):
    """Arg-reduce over a sharded dim (the vocab-parallel argmax of a
    tp-sharded logits row): each device knows only its shard's
    winner; the lowering implies a cross-shard select (allgather/
    psum-of-max in Megatron's vocab-parallel head)."""
    x = _in(op, "X")
    sx = spec_of(x)
    if sx.is_top:
        return _all_outs(op, TOP_SPEC)
    if sx.is_replicated:
        return _all_outs(op, REPLICATED_SPEC)
    shape = shape_of(x)
    rank = len(shape) if shape is not None else None
    axis = op.attrs.get("axis", -1)
    if rank is None:
        return _all_outs(op, TOP_SPEC)
    axis = int(axis) % rank
    events = []
    a = sx.axis_of(axis)
    if a is not None:
        events.append(CollectiveEvent(
            "allgather", (a,), _outs(op)[0] if _outs(op) else None,
            f"arg-reduce over the {a}-sharded dim {axis}: each "
            f"device holds only its shard's winner — the lowering "
            f"implies a cross-shard select over {a!r}"))
    out_places, _ = _reduce_places(sx, [axis], rank, False)
    return _all_outs(op, ShardSpec.of(out_places), events)


@_family("mean", ("mean",))
def rule_mean(op, spec_of, shape_of, mesh):
    x = _in(op, "X")
    sx = spec_of(x)
    events = []
    if not sx.is_replicated and not sx.is_top:
        events.append(CollectiveEvent(
            "psum", tuple(sorted(set(sx.axes()))),
            _outs(op)[0] if _outs(op) else None,
            "global mean of a sharded value implies a psum"))
    return _all_outs(op, REPLICATED_SPEC, events)


@_family("softmax", ("softmax", "filtered_softmax"))
def rule_softmax(op, spec_of, shape_of, mesh):
    x = _in(op, "X")
    sx = spec_of(x)
    if sx.is_top:
        return _all_outs(op, TOP_SPEC)
    axis = int(op.attrs.get("axis", -1))
    shape = shape_of(x)
    rank = len(shape) if shape is not None else None
    events = []
    if rank is not None:
        a = sx.axis_of(axis % rank)
        if a is not None:
            events.append(CollectiveEvent(
                "psum", (a,), _outs(op)[0] if _outs(op) else None,
                f"softmax normalizes over the {a}-sharded dim: the "
                f"max/sum reductions imply psums over {a!r}"))
    return _all_outs(op, sx, events)


@_family("layer_norm", ("layer_norm",))
def rule_layer_norm(op, spec_of, shape_of, mesh):
    x = _in(op, "X")
    sx = spec_of(x)
    if sx.is_top:
        return _all_outs(op, TOP_SPEC)
    begin = int(op.attrs.get("begin_norm_axis", 1))
    events = []
    norm_axes = sorted({a for d, a in (sx.placements or ())
                        if d >= begin})
    if norm_axes:
        events.append(CollectiveEvent(
            "psum", tuple(norm_axes),
            _outs(op)[0] if _outs(op) else None,
            f"layer_norm's mean/variance reduce over dims sharded on "
            f"{norm_axes}: the lowering implies psums"))
    # Y keeps the input layout; Mean/Variance side outputs are
    # reductions — rank-agnostic REPLICATED is the safe spec for them
    outs = {}
    for slot, names in op.outputs.items():
        for n in names:
            if n == EMPTY_VAR:
                continue
            outs[n] = sx if slot == "Y" else REPLICATED_SPEC
    return outs, events


# ---------------------------------------------------------------------------
# concat / split / gather / scatter / one-hot families
# ---------------------------------------------------------------------------
@_family("concat", ("concat",))
def rule_concat(op, spec_of, shape_of, mesh):
    names = [n for n in op.inputs.get("X", []) if n != EMPTY_VAR]
    specs = [spec_of(n) for n in names]
    if any(s.is_top for s in specs):
        return _all_outs(op, TOP_SPEC)
    axis = int(op.attrs.get("axis", 0))
    events = []
    out = REPLICATED_SPEC
    for n, s in zip(names, specs):
        if s.axis_of(axis) is not None:
            events.append(CollectiveEvent(
                "reshard", (s.axis_of(axis),),
                _outs(op)[0] if _outs(op) else None,
                f"concat along the {s.axis_of(axis)}-sharded dim "
                f"{axis} of {n!r} forces a reshard"))
            s = ShardSpec.of([(d, a) for d, a in s.placements
                              if d != axis])
        out = s if out.is_replicated else out
        if not s.is_replicated and s != out:
            return _all_outs(op, TOP_SPEC, events + [CollectiveEvent(
                "conflict", tuple(out.axes()) + tuple(s.axes()),
                _outs(op)[0] if _outs(op) else None,
                f"concat operands demand incompatible specs "
                f"{out.describe()} vs {s.describe()}")])
    return _all_outs(op, out, events)


@_family("split", ("split",))
def rule_split(op, spec_of, shape_of, mesh):
    x = _in(op, "X")
    sx = spec_of(x)
    if sx.is_top or sx.is_replicated:
        return _all_outs(op, sx)
    axis = int(op.attrs.get("dim", op.attrs.get("axis", 0)))
    events = []
    a = sx.axis_of(axis)
    if a is not None:
        events.append(CollectiveEvent(
            "reshard", (a,), _outs(op)[0] if _outs(op) else None,
            f"split along the {a}-sharded dim {axis} forces a "
            f"reshard"))
        sx = ShardSpec.of([(d, ax) for d, ax in sx.placements
                           if d != axis])
    return _all_outs(op, sx, events)


@_family("gather", ("gather", "lookup_table"))
def rule_gather(op, spec_of, shape_of, mesh):
    """Row gather (and the embedding lookup): a table sharded on the
    gathered dim 0 means every device holds only a vocab/row shard —
    the lowering one-hots + psums (or allgathers) across that axis.
    Trailing table dims carry their placements into the output's
    trailing dims; index placements carry into the leading dims."""
    table = _in(op, "W") or _in(op, "X")
    ids = _in(op, "Ids") or _in(op, "Index")
    st = spec_of(table) if table else REPLICATED_SPEC
    si = spec_of(ids) if ids else REPLICATED_SPEC
    if st.is_top or si.is_top:
        return _all_outs(op, TOP_SPEC)
    out_names = _outs(op)
    out_shape = shape_of(out_names[0]) if out_names else None
    tshape = shape_of(table) if table else None
    if out_shape is None or tshape is None:
        if st.is_replicated and si.is_replicated:
            return _all_outs(op, REPLICATED_SPEC)
        return _all_outs(op, TOP_SPEC)
    out_rank, trank = len(out_shape), len(tshape)
    lead = out_rank - (trank - 1)   # dims coming from the index
    events = []
    out_places = []
    if st.axis_of(0) is not None:
        events.append(CollectiveEvent(
            "allgather", (st.axis_of(0),),
            out_names[0] if out_names else None,
            f"gather from a table sharded on the gathered dim "
            f"(axis {st.axis_of(0)!r}): the lowering one-hots and "
            f"psums/allgathers across that axis"))
    for d, a in st.placements:
        if d >= 1 and lead + d - 1 >= 0:
            out_places.append((lead + d - 1, a))
    for d, a in si.placements:
        if d < lead:
            out_places.append((d, a))
    return _all_outs(op, ShardSpec.of(out_places), events)


@_family("paged_read", ("paged_decode_attention",))
def rule_paged_read(op, spec_of, shape_of, mesh):
    """The paged self-attention read: every contraction stays inside
    one head, so pools sharded on their ``H*Dh`` axis (whole heads a
    shard, the tp serving layout) give context rows sharded the same
    way on their last axis with no collective. The lane axis follows
    Q; a table or positions that are not replicated would have to be
    gathered first, and a query laid out against the pools' heads
    resharded — both surfaced as events."""
    q, out = _in(op, "Q"), _outs(op)
    sk = spec_of(_in(op, "PoolK"))
    sv = spec_of(_in(op, "PoolV"))
    sq = spec_of(q) if q else REPLICATED_SPEC
    if sk.is_top or sv.is_top or sq.is_top:
        return _all_outs(op, TOP_SPEC)
    events = []
    head_axis = sk.axis_of(1)
    if sv.axis_of(1) != head_axis or sk.axis_of(0) is not None \
            or sv.axis_of(0) is not None:
        events.append(CollectiveEvent(
            "reshard", tuple(sorted(set(sk.axes()) | set(sv.axes()))),
            out[0] if out else None,
            f"paged read wants both pools sharded alike on H*Dh "
            f"alone, got {sk.describe()} and {sv.describe()}"))
    if sq.axis_of(2) not in (None, head_axis):
        events.append(CollectiveEvent(
            "reshard", (sq.axis_of(2),), out[0] if out else None,
            f"query rows are laid out {sq.describe()} against pools "
            f"sharded {sk.describe()}"))
    for slot in ("Table", "Pos"):
        name = _in(op, slot)
        st = spec_of(name) if name else REPLICATED_SPEC
        if not st.is_top and not st.is_replicated:
            events.append(CollectiveEvent(
                "allgather", tuple(st.axes()),
                out[0] if out else None,
                f"paged read's {slot} {name!r} is sharded "
                f"{st.describe()}: every shard needs every lane's "
                f"row"))
    places = [(d, a) for d, a in sq.placements if d < 2]
    if head_axis is not None:
        places.append((2, head_axis))
    return _all_outs(op, ShardSpec.of(places), events)


@_family("one_hot", ("one_hot",))
def rule_one_hot(op, spec_of, shape_of, mesh):
    x = _in(op, "X")
    sx = spec_of(x)
    if sx.is_top:
        return _all_outs(op, TOP_SPEC)
    return _all_outs(op, sx)  # new trailing depth dim: replicated


@_family("pool_scatter", ("masked_pool_write", "span_scatter"))
def rule_pool_scatter(op, spec_of, shape_of, mesh):
    """One-hot-scatter state writers: the written buffer keeps ITS
    layout (the write is elementwise in the pool's own space); a New
    value laid out differently from the pool's trailing dims would
    need a reshard on the way in — surfaced as an event, the pool
    spec stays authoritative."""
    pool = _in(op, "Pool") or _in(op, "X")
    sp = spec_of(pool) if pool else REPLICATED_SPEC
    events = []
    new = _in(op, "New") or _in(op, "Vals")
    if new is not None and pool is not None:
        sn = spec_of(new)
        pshape, nshape = shape_of(pool), shape_of(new)
        if not sn.is_top and not sp.is_top and \
                pshape is not None and nshape is not None:
            off = len(pshape) - len(nshape)
            want = ShardSpec.of([(d - off, a)
                                 for d, a in sp.placements
                                 if d - off >= 0])
            if sn != want and not sn.is_replicated:
                events.append(CollectiveEvent(
                    "reshard", tuple(sn.axes()),
                    pool,
                    f"scatter source {new!r} is laid out "
                    f"{sn.describe()} but the pool's trailing dims "
                    f"demand {want.describe()}"))
    return _all_outs(op, sp, events)


@_family("sample", ("sample_categorical",))
def rule_sample_categorical(op, spec_of, shape_of, mesh):
    """Categorical draw over the last (vocab) axis: the sampled token
    ids are REPLICATED (one logical draw per lane), and a
    vocab-sharded probability row implies the lowering gathers (or
    psum-reduces the cumulative mass of) the full distribution — the
    Megatron vocab-parallel sampling collective. Seed/Pos carry no
    layout."""
    probs = _in(op, "Probs")
    sp = spec_of(probs) if probs else REPLICATED_SPEC
    if sp.is_top:
        return _all_outs(op, TOP_SPEC)
    events = []
    shape = shape_of(probs) if probs else None
    if shape is not None:
        a = sp.axis_of(len(shape) - 1)
        if a is not None:
            events.append(CollectiveEvent(
                "allgather", (a,),
                _outs(op)[0] if _outs(op) else None,
                f"categorical draw over the {a}-sharded vocab dim: "
                f"the lowering materializes the full distribution "
                f"(or psums its cumulative mass) across {a!r}"))
    return _all_outs(op, REPLICATED_SPEC, events)


@_family("spec_accept", ("spec_accept",))
def rule_spec_accept(op, spec_of, shape_of, mesh):
    """Draft-and-verify acceptance (ops/spec_ops.py): per-lane
    scalars/short rows out — REPLICATED — computed from per-token
    probability lookups; a vocab-sharded draft/target distribution
    implies a cross-shard gather of the looked-up p/q columns (and of
    the residual distribution for the correction draw)."""
    events = []
    axes = set()
    for slot in ("DraftProbs", "TargetProbs"):
        name = _in(op, slot)
        if name is None:
            continue
        s = spec_of(name)
        if s.is_top:
            return _all_outs(op, TOP_SPEC)
        shape = shape_of(name)
        if shape is not None:
            a = s.axis_of(len(shape) - 1)
            if a is not None:
                axes.add(a)
    if axes:
        events.append(CollectiveEvent(
            "allgather", tuple(sorted(axes)),
            _outs(op)[0] if _outs(op) else None,
            f"speculative acceptance over vocab dims sharded on "
            f"{sorted(axes)}: the p/q token lookups and the residual "
            f"correction distribution materialize across those axes"))
    return _all_outs(op, REPLICATED_SPEC, events)


# ---------------------------------------------------------------------------
# shape-like producers: mint fresh replicated values even when their
# reference input is sharded (they only read its metadata)
# ---------------------------------------------------------------------------
@_family("shape_like", ("fill_constant_batch_size_like", "shape",
                        "range", "fill_constant", "uniform_random",
                        "gaussian_random"))
def rule_shape_like(op, spec_of, shape_of, mesh):
    return _all_outs(op, REPLICATED_SPEC)


# ---------------------------------------------------------------------------
# literal collectives: the result is replicated over the collective
# axis by construction (the order proof for these sites is PTA130's)
# ---------------------------------------------------------------------------
@_family("collective", ("allreduce",))
def rule_collective(op, spec_of, shape_of, mesh):
    return _all_outs(op, REPLICATED_SPEC)
