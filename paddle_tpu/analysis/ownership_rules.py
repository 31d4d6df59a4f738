"""Pool-index provenance rules for the core op families.

The ownership domain (analysis/absint.py) proves where every index
reaching a ``@POOL`` read/write COMES FROM: a host-owned table mark
(``mark_pool_index_source``), a trace-time constant, or a composition
of those through the affine / one-hot-selection idioms the paged
lowerings actually use (models/decode_engine.py: block-table cell
addressing is ``tab[lane, p//BS]*BS + p%BS`` built from cast/scale/
expand/add; the current write cell is a one-hot page/offset selection
``reduce_sum(tab * onehot)``). Each rule states how one op family
carries a ProvFact (source tags, constness, 0/1 indicators,
one-hotness, value bounds) from inputs to outputs.

Rules register through ``core.registry.register_index_rule`` —
beside the sharding rules — so an op that joins an index-composition
path registers its provenance fact where it registers its kernel
(CLAUDE.md conventions). Ops WITHOUT a rule propagate NOTHING: an
index flowing through one reaches the pool access with UNKNOWN
provenance and PTA190 rejects it loudly — imprecision can only cause
false alarms at annotated pool accesses, never a silent pass.

Bound semantics: ``bound`` is an EXCLUSIVE upper bound on integer
values; the sub/mul/scale bound arithmetic is only sound over
non-negative operands, so signs are TRACKED (``ProvFact.nonneg``):
negative constants mint no fact at all, subtraction drops the bound
unless the subtrahend is provably >= 0 and marks its own result
possibly-negative, and products/selections require non-negative
operands before certifying a bound. (Negative indices at a WRITE are
dropped by the masked_pool_write kernel,
ops/paged_ops.py; reads have no such net — which is why the read
bound proof must not lie.)
One-hot semantics: ``onehot`` promises at most one nonzero in each
ROW's trailing block — the mint is ``equal(distinct 1-D constant,
broadcast scalar-per-row)`` with the broadcast SHAPE checked, reshape
preserves it (the row axis stays leading), transpose DROPS it (the
row axis moves), and a reduce_sum over non-leading axes of a per-row
one-hot stays 0/1-valued — which is what lets a selector product
(``selection``) keep the selected operand's tags and bound through
the contraction, and only then.

Rule contract::

    rule(op, prov_of, shape_of) -> {output var name: ProvFact}

``prov_of(name) -> Optional[ProvFact]`` (None = no provenance known),
``shape_of(name) -> tuple | None``. Rules are PURE metadata functions:
no jax, no tracing.

Reference counterpart: none — the reference checks allocator state at
runtime (reference framework/scope.cc, memory/allocation); the
compile-time provenance algebra is the shared-pool serving capability
this framework adds (vLLM SOSP'23 block tables, machine-checked).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import numpy as np

from ..core.registry import EMPTY_VAR, register_index_rule
from .absint import ProvFact, prov_join

__all__ = ["INDEX_RULE_FAMILIES"]

# family name -> op types it covers (documentation + the tests'
# enumeration; the actual registry is core.registry's)
INDEX_RULE_FAMILIES: Dict[str, Tuple[str, ...]] = {}


def _family(name, op_types):
    INDEX_RULE_FAMILIES[name] = tuple(op_types)

    def deco(fn):
        register_index_rule(op_types, fn)
        return fn

    return deco


def _outs(op):
    return [n for n in op.output_arg_names if n != EMPTY_VAR]


def _in(op, slot, idx=0):
    names = op.inputs.get(slot) or []
    return names[idx] if len(names) > idx else None


def _all_outs(op, fact):
    if fact is None:
        return {}
    return {n: fact for n in _outs(op)}


def _step(fact, op):
    return fact.with_step(op.type) if fact is not None else None


def _chain(base, op_type):
    """Extend a provenance chain under the same 8-entry cap
    ProvFact.with_step enforces (rules that construct ProvFact
    directly must not bypass it — an unbounded chain bloats the
    cached facts and the printed diagnostics alike)."""
    return base if len(base) >= 8 else base + (op_type,)


# --- constant mints ---------------------------------------------------------
# Negative-valued constants mint NO fact at all: the non-negative
# index domain is what makes the sub/mul/scale bound arithmetic
# sound, and a negative constant reaching an index slot should fail
# the provenance proof loudly rather than carry a lying bound.
@_family("const-fill", ("fill_constant", "fill_zeros_like"))
def _fill_constant(op, prov_of, shape_of):
    v = op.attrs.get("value", 0.0)
    try:
        v = float(v)
    except (TypeError, ValueError):
        return {}
    if op.type == "fill_zeros_like":
        v = 0.0
    if v < 0:
        return {}
    bound = int(v) + 1 if float(v).is_integer() else None
    return _all_outs(op, ProvFact(
        const=True, bound=bound, indicator=v in (0.0, 1.0),
        chain=(f"{op.type}({v})",)))


@_family("const-values", ("assign_value",))
def _assign_value(op, prov_of, shape_of):
    vals = op.attrs.get("values")
    try:
        arr = np.asarray(vals, dtype="float64").ravel()
    except (TypeError, ValueError):
        return {}
    if not arr.size or float(arr.min()) < 0:
        return {}
    bound = int(math.floor(float(arr.max()))) + 1
    fact = ProvFact(
        const=True, bound=bound,
        indicator=bool(np.isin(arr, (0.0, 1.0)).all()),
        distinct=bool(np.unique(arr).size == arr.size),
        chain=("assign_value",))
    return _all_outs(op, fact)


@_family("const-range", ("range",))
def _range(op, prov_of, shape_of):
    start = op.attrs.get("start")
    end = op.attrs.get("end")
    step = op.attrs.get("step")
    if not all(isinstance(v, (int, float))
               for v in (start, end, step)):
        return {}   # Variable bounds: host values unknown at lint
    if step <= 0 or start < 0:
        return {}   # descending/negative ranges leave the domain
    bound = max(1, int(math.ceil(end)))
    return _all_outs(op, ProvFact(
        const=True, distinct=True, bound=bound,
        chain=(f"range({start},{end},{step})",)))


# --- value-preserving views / copies ----------------------------------------
@_family("identity", (
        "cast", "assign", "unsqueeze", "unsqueeze2", "squeeze",
        "squeeze2", "stop_gradient"))
def _identity(op, prov_of, shape_of):
    src = _in(op, "X")
    return _all_outs(op, _step(prov_of(src) if src else None, op))


def _prod(dims):
    n = 1
    for d in dims:
        if d is None or d < 0:
            return None
        n *= int(d)
    return n


@_family("reshape", ("reshape", "reshape2"))
def _reshape(op, prov_of, shape_of):
    # per-element properties always survive; the per-row one-hot/
    # selection block survives ONLY a reshape that keeps the leading
    # (row) dims intact and re-factors the trailing block — a
    # reshape folding rows INTO the block ([A, R] -> [A*R]) piles
    # A nonzeros into one block and the <=1 claim breaks
    src = _in(op, "X")
    f = prov_of(src) if src else None
    if f is None:
        return {}
    if not (f.onehot or f.selection):
        return _all_outs(op, f.with_step(op.type))
    si = shape_of(src)
    outs = _outs(op)
    so = shape_of(outs[0]) if outs else None
    keep = False
    tail = 0
    if si is not None and so is not None and \
            0 < f.oh_tail <= len(si):
        lead = tuple(si[:len(si) - f.oh_tail])
        if tuple(so[:len(lead)]) == lead and len(so) > len(lead):
            tail = len(so) - len(lead)
            ip = _prod(si[len(lead):])
            op_ = _prod(so[len(lead):])
            keep = ip is not None and ip == op_
    return _all_outs(op, f.with_step(
        op.type, onehot=f.onehot and keep,
        selection=f.selection and keep,
        oh_tail=tail if keep else 0))


@_family("transpose", ("transpose", "transpose2"))
def _transpose(op, prov_of, shape_of):
    # per-ELEMENT properties survive a permutation; the per-row
    # one-hot/selection structure does not (moving the row axis off
    # the front lets later trailing-axis reduces sum ACROSS rows —
    # the admission ohT [A,rows]->[rows,A] case, where the dustbin
    # row holds many nonzeros)
    f = prov_of(_in(op, "X") or "")
    if f is None:
        return {}
    return _all_outs(op, f.with_step(op.type, onehot=False,
                                     selection=False, oh_tail=0))


@_family("expand", ("expand",))
def _expand(op, prov_of, shape_of):
    f = prov_of(_in(op, "X") or "")
    if f is None:
        return {}
    # tiling repeats entries: per-VALUE properties survive, pairwise
    # distinctness does not, and neither does the one-hot block
    # (tiling along the block duplicates its nonzero)
    return _all_outs(op, f.with_step(op.type, distinct=False,
                                     onehot=False, selection=False,
                                     oh_tail=0))


@_family("gather", ("gather", "gather_nd"))
def _gather(op, prov_of, shape_of):
    # output VALUES come from X (a subset, possibly repeated): tags/
    # bound/constness survive, distinctness does not. Plain gather
    # selects whole axis-0 rows, so a per-row one-hot block rides
    # along; gather_nd may index INTO the block (its last-axis
    # components address several leading axes), so the structural
    # claims drop there. The gather's own Index is judged at the
    # pool-access record when X is a pool view
    # (absint._record_pool_access), not here.
    f = prov_of(_in(op, "X") or "")
    if f is None:
        return {}
    if op.type == "gather_nd":
        return _all_outs(op, f.with_step(op.type, distinct=False,
                                         onehot=False,
                                         selection=False,
                                         oh_tail=0))
    return _all_outs(op, f.with_step(op.type, distinct=False))


@_family("paged_read", ("paged_decode_attention",))
def _paged_read(op, prov_of, shape_of):
    # the output is attention context, not an index: no provenance
    # leaves the op. Its Table input is judged where gather's Index
    # is, at the pool-access record (absint._record_pool_access),
    # with the bound compared against the pool's BLOCK count.
    return {}


@_family("split", ("split",))
def _split(op, prov_of, shape_of):
    f = prov_of(_in(op, "X") or "")
    if f is None:
        return {}
    # splitting can cut THROUGH the one-hot block: drop the
    # structural claims, keep the per-element ones
    return _all_outs(op, f.with_step(op.type, distinct=False,
                                     onehot=False, selection=False,
                                     oh_tail=0))


@_family("concat", ("concat",))
def _concat(op, prov_of, shape_of):
    facts = [prov_of(n) for n in op.input_arg_names
             if n != EMPTY_VAR]
    if not facts or any(f is None for f in facts):
        return {}
    out = facts[0]
    for f in facts[1:]:
        out = prov_join(out, f)
    # prov_join's both-sides-keep-it semantics is for ALTERNATIVE
    # writers; concatenated values COEXIST — two per-row one-hots
    # glued along the block hold two nonzeros per row, so the
    # structural claims never survive a concat
    return _all_outs(op, out.with_step(op.type, distinct=False,
                                       onehot=False,
                                       selection=False, oh_tail=0))


# --- affine arithmetic ------------------------------------------------------
@_family("scale", ("scale",))
def _scale(op, prov_of, shape_of):
    f = prov_of(_in(op, "X") or "")
    if f is None:
        return {}
    s = float(op.attrs.get("scale", 1.0))
    b = float(op.attrs.get("bias", 0.0))
    if s < 0:
        return {}
    bound = None
    if f.bound is not None and b >= 0:
        # v <= bound-1 and s >= 0 make (bound-1)*s + b an upper
        # bound regardless of v's sign; b < 0 could go negative, so
        # the bound AND the nonneg claim are dropped together below
        bound = int(math.floor((f.bound - 1) * s + b)) + 1
    plain = s == 1.0 and b == 0.0
    return _all_outs(op, f.with_step(
        f"scale(x{s}+{b})", bound=bound,
        indicator=f.indicator and plain,
        onehot=f.onehot and plain,
        distinct=f.distinct and s > 0,
        nonneg=f.nonneg and b >= 0,
        const=f.const))


def _ew_facts(op, prov_of):
    fx = prov_of(_in(op, "X") or "")
    fy = prov_of(_in(op, "Y") or "")
    return fx, fy


@_family("elementwise-add", ("elementwise_add",))
def _ew_add(op, prov_of, shape_of):
    fx, fy = _ew_facts(op, prov_of)
    if fx is None or fy is None:
        return {}
    bound = None
    if fx.bound is not None and fy.bound is not None:
        bound = fx.bound + fy.bound - 1
    return _all_outs(op, ProvFact(
        tuple(sorted(set(fx.tags) | set(fy.tags))),
        fx.const and fy.const, bound=bound,
        nonneg=fx.nonneg and fy.nonneg,
        chain=_chain(fx.chain or fy.chain, op.type)))


@_family("elementwise-sub", ("elementwise_sub",))
def _ew_sub(op, prov_of, shape_of):
    fx, fy = _ew_facts(op, prov_of)
    if fx is None or fy is None:
        return {}
    # v1 - v2 <= v1 < bound(v1) ONLY when v2 is provably >= 0 — a
    # possibly-negative subtrahend inflates the value past any
    # certified bound, so the bound is dropped with it. The result
    # itself can go negative (nonneg=False), except the
    # (const 1) - indicator mask idiom, which stays a 0/1 indicator
    # — but a COMPLEMENT carries NO source tags: 1-active is the
    # idle mask, not the active mask, and letting it keep the
    # lane_active tag would pass an INVERTED gate through PTA190's
    # gate proof (idle lanes writing, active lanes frozen — the
    # exact corruption the gate exists to stop).
    ind = fx.const and fx.bound == 2 and fy.indicator
    return _all_outs(op, ProvFact(
        () if ind else tuple(sorted(set(fx.tags) | set(fy.tags))),
        fx.const and fy.const, indicator=ind,
        bound=fx.bound if fy.nonneg else None,
        nonneg=ind,
        chain=_chain(fx.chain or fy.chain, op.type)))


@_family("elementwise-mul", ("elementwise_mul",))
def _ew_mul(op, prov_of, shape_of):
    fx, fy = _ew_facts(op, prov_of)
    if fx is None or fy is None:
        return {}
    tags = tuple(sorted(set(fx.tags) | set(fy.tags)))
    chain = _chain(fx.chain or fy.chain, op.type)
    for a, b in ((fx, fy), (fy, fx)):
        if a.indicator and not b.indicator:
            # gating/selection: values are b's entries or 0 —
            # b's bound and tags survive; a ONE-HOT selector makes
            # the product summable without losing the bound (the
            # selector's block extent rides along for the reduce's
            # containment check). 0 is only inside b's bound on the
            # non-negative domain.
            sel = a.onehot and b.nonneg
            return _all_outs(op, ProvFact(
                tags, a.const and b.const,
                bound=b.bound if b.nonneg else None,
                selection=sel, nonneg=b.nonneg,
                oh_tail=a.oh_tail if sel else 0, chain=chain))
    if fx.indicator and fy.indicator:
        # nonzeros of the product are a subset of EACH operand's, so
        # any one-hot claim survives — keep the stronger (larger)
        # block
        tail = max(fx.oh_tail if fx.onehot else 0,
                   fy.oh_tail if fy.onehot else 0)
        return _all_outs(op, ProvFact(
            tags, fx.const and fy.const, indicator=True,
            onehot=tail > 0, bound=2, oh_tail=tail, chain=chain))
    bound = None
    if fx.bound is not None and fy.bound is not None \
            and fx.nonneg and fy.nonneg:
        # (b1-1)*(b2-1)+1 needs both operands >= 0 (two negatives
        # multiply to an arbitrarily large positive)
        bound = (fx.bound - 1) * (fy.bound - 1) + 1
    return _all_outs(op, ProvFact(
        tags, fx.const and fy.const, bound=bound,
        nonneg=fx.nonneg and fy.nonneg, chain=chain))


@_family("elementwise-minmax", ("elementwise_min",
                                "elementwise_max"))
def _ew_minmax(op, prov_of, shape_of):
    fx, fy = _ew_facts(op, prov_of)
    if fx is None or fy is None:
        return {}
    bounds = [b for b in (fx.bound, fy.bound) if b is not None]
    if op.type == "elementwise_min":
        bound = min(bounds) if bounds else None
        nonneg = fx.nonneg and fy.nonneg
    else:
        bound = max(bounds) if len(bounds) == 2 else None
        nonneg = fx.nonneg or fy.nonneg
    return _all_outs(op, ProvFact(
        tuple(sorted(set(fx.tags) | set(fy.tags))),
        fx.const and fy.const,
        indicator=fx.indicator and fy.indicator, bound=bound,
        nonneg=nonneg,
        chain=_chain(fx.chain or fy.chain, op.type)))


# --- indicator mints --------------------------------------------------------
@_family("compare", (
        "equal", "not_equal", "greater_than", "greater_equal",
        "less_than", "less_equal", "logical_and", "logical_or",
        "logical_xor", "logical_not"))
def _compare(op, prov_of, shape_of):
    fx, fy = _ew_facts(op, prov_of)
    onehot = False
    if op.type == "equal":
        # equal(distinct-constant 1-D axis, BROADCAST value) matches
        # at most one entry along the constant's axis — the one-hot
        # mint every paged page/offset selection is built from. The
        # broadcast shape is part of the proof: the other operand
        # must be scalar-per-row (trailing dim 1 / scalar), or a
        # same-length vector (equal(range(N), ids[N]) can match
        # EVERY position) would be falsely certified one-hot.
        for a_slot, b_slot, fa in (("X", "Y", fx), ("Y", "X", fy)):
            if fa is None or not (fa.const and fa.distinct):
                continue
            sa = shape_of(_in(op, a_slot) or "")
            sb = shape_of(_in(op, b_slot) or "")
            if sa is not None and len(sa) == 1 \
                    and sb is not None \
                    and (len(sb) == 0 or sb[-1] == 1):
                onehot = True
                break
    return _all_outs(op, ProvFact(
        const=all(f is not None and f.const for f in (fx, fy)),
        indicator=True, onehot=onehot, bound=2,
        oh_tail=1 if onehot else 0,
        chain=(op.type,)))


@_family("one-hot", ("one_hot",))
def _one_hot(op, prov_of, shape_of):
    return _all_outs(op, ProvFact(indicator=True, onehot=True,
                                  bound=2, oh_tail=1,
                                  chain=("one_hot",)))


# --- contractions -----------------------------------------------------------
def _tail_reduced(op, shape_of, oh_tail):
    """(contained, n) — whether the reduce's dims all lie INSIDE the
    one-hot fact's trailing block (the last ``oh_tail`` axes), and
    how many of them do. The <=1-nonzero claim only survives a
    reduce that stays inside the block: reducing a leading (row)
    axis sums one-hots from DIFFERENT rows (the admission mask
    `reduce_sum(oh, dim=0)` counts up to A) and the claim breaks."""
    dims = op.attrs.get("dim")
    if dims is None:
        return False, 0              # full reduce: rows included
    if isinstance(dims, int):
        dims = [dims]
    try:
        dims = [int(d) for d in dims]
    except (TypeError, ValueError):
        return False, 0
    shape = shape_of(_in(op, "X") or "")
    if shape is None:
        return False, 0              # rank unknown: unprovable
    rank = len(shape)
    norm = [d + rank if d < 0 else d for d in dims]
    ok = all(rank - oh_tail <= d < rank for d in norm) \
        and 0 < oh_tail <= rank
    return ok, len(set(norm))


@_family("reduce", ("reduce_sum", "reduce_max", "reduce_min",
                    "reduce_mean"))
def _reduce(op, prov_of, shape_of):
    f = prov_of(_in(op, "X") or "")
    if f is None:
        return {}
    if op.type in ("reduce_max", "reduce_min"):
        # per-ELEMENT properties (bound, indicator, tags) survive a
        # max/min regardless of axes; the per-row ONE-HOT block
        # survives only a reduce INSIDE it (a dim=0 reduce_max of
        # an [A, rows] one-hot is an any-mask with up to A nonzeros)
        keep, n = (False, 0) if not f.onehot else \
            _tail_reduced(op, shape_of, f.oh_tail)
        return _all_outs(op, f.with_step(
            op.type, selection=False, distinct=False,
            onehot=f.onehot and keep,
            oh_tail=f.oh_tail - n if (f.onehot and keep) else 0))
    if op.type == "reduce_mean":
        return _all_outs(op, f.with_step(op.type, selection=False,
                                         distinct=False,
                                         onehot=False, oh_tail=0,
                                         indicator=False))
    if f.selection:
        keep, _n = _tail_reduced(op, shape_of, f.oh_tail)
        if keep:
            # sum over a bounded x one-hot product, inside the
            # selector's trailing block: picks at most one entry —
            # the selected operand's tags and bound survive
            return _all_outs(op, f.with_step(
                "reduce_sum[selection]", selection=False,
                onehot=False, oh_tail=0, indicator=False,
                distinct=False))
    if f.onehot:
        keep, n = _tail_reduced(op, shape_of, f.oh_tail)
        if keep:
            # summing groups WITHIN a per-row one-hot block stays
            # 0/1-valued; a fully-reduced block degrades to a plain
            # per-row indicator
            tail = f.oh_tail - n
            return _all_outs(op, f.with_step(
                "reduce_sum[one-hot]", distinct=False,
                onehot=tail > 0, oh_tail=tail))
    if f.const:
        return _all_outs(op, ProvFact(const=True,
                                      chain=_chain(f.chain, op.type)))
    return {}


@_family("matmul", ("matmul", "mul"))
def _matmul(op, prov_of, shape_of):
    fx, fy = _ew_facts(op, prov_of)
    # a one-hot X operand makes the contraction a pure selection of
    # Y's rows (reduce_sum(onehot * vals) in matmul clothing): X's
    # per-row one-hot block must span EXACTLY the contracted (last)
    # axis — oh_tail == 1. Y-side one-hots do NOT qualify (Y's
    # per-row one-hot is along the NON-contracted axis, so one
    # column of Y can hold many nonzeros), nor does a transposed X.
    if fx is not None and fx.onehot and fx.oh_tail == 1 \
            and fy is not None \
            and not op.attrs.get("transpose_X") \
            and not op.attrs.get("transpose_x"):
        return _all_outs(op, fy.with_step(
            f"{op.type}[one-hot-select]",
            bound=fy.bound if fy.nonneg else None,
            selection=False, onehot=False, oh_tail=0,
            indicator=False, distinct=False))
    if fx is not None and fy is not None and fx.const and fy.const:
        return _all_outs(op, ProvFact(
            const=True, chain=_chain(fx.chain or fy.chain, op.type)))
    return {}
