"""Op registry: kernels, shape inference, grad-op makers.

TPU-native analogue of the reference's OpInfoMap / REGISTER_OPERATOR
machinery (reference: paddle/fluid/framework/op_registry.h:197-270,
op_info.h, grad_op_desc_maker.h). Differences driven by XLA:

* A "kernel" is a pure JAX-traceable function over the op's inputs; the
  Executor traces a whole Block of them into ONE XLA computation, so there
  is no per-device kernel dispatch key -- XLA picks the device code.
* Gradients: the reference hand-writes a grad op per op plus a
  GradOpDescMaker. Here every differentiable op gets its grad op derived
  automatically through jax.vjp of the forward kernel (rematerialized in
  the backward pass -- a win on TPU where FLOPs are cheaper than HBM).
  Ops whose fluid grad semantics differ (dropout's saved mask, sparse
  embedding grads) register custom grad makers/kernels.
* Shape inference (reference shape_inference.h / each op's InferShape) is
  generic: we jax.eval_shape the kernel at two different fake batch sizes;
  output dims that vary are batch-dims (-1). Ops can override.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .program import (DEVICE_SCOPE_ATTR, GRAD_SUFFIX, Block, Operator,
                      grad_var_name)
from .types import to_jnp_dtype


class OpInfo:
    def __init__(self, type: str, kernel: Callable,
                 infer_shape: Optional[Callable] = None,
                 grad_maker=None, differentiable: bool = True,
                 inplace: Optional[Dict[str, str]] = None,
                 stop_gradient_slots=(), needs_rng: bool = False,
                 host_effect: bool = False):
        self.type = type
        self.kernel = kernel
        self.infer_shape = infer_shape
        self.grad_maker = grad_maker
        self.differentiable = differentiable
        # output slot -> input slot it aliases (buffer donation hint,
        # analogue of the reference's inplace_op_inference.h)
        self.inplace = inplace or {}
        # input slots that never receive gradient (e.g. integer indices)
        self.stop_gradient_slots = tuple(stop_gradient_slots)
        self.needs_rng = needs_rng
        # True for kernels that bridge to the host (io_callback /
        # pure_callback / trace-time host state): they run per-step but
        # cannot be lowered into a lax.scan over steps — the multi-step
        # executor (Executor.run_steps) falls back to the per-step path
        # when a block contains one (with the op named in the reason)
        self.host_effect = host_effect


_REGISTRY: Dict[str, OpInfo] = {}

# op type -> sharding propagation rule for the analysis layer's
# sharding domain (analysis/absint.py). A rule is a PURE function
#     rule(op, spec_of, shape_of, mesh) -> (out_specs, events)
# over Program metadata: `spec_of(name)`/`shape_of(name)` resolve an
# input var's abstract ShardSpec / static shape, `out_specs` maps
# output var names to their propagated ShardSpec, and `events` lists
# the CollectiveEvents (psum/allgather/reshard/conflict) the op's
# GSPMD lowering implies under those specs. Rules live alongside the
# kernels they describe (analysis/sharding_rules.py registers the
# core families) so a new op that touches sharded state registers its
# propagation fact the same way it registers its kernel — an op
# WITHOUT a rule degrades its outputs to the explicit ⊤ spec
# (warn-once) the moment a sharded value reaches it, so imprecision
# is visible, never silently wrong.
_SHARDING_RULES: Dict[str, Callable] = {}


def register_sharding_rule(op_types, fn: Optional[Callable] = None):
    """Register a sharding-propagation rule for one op type or a
    family of op types (mirrors register_op; usable as a decorator).

    Reference counterpart: none — the reference shards at runtime via
    transpilers (reference transpiler/distribute_transpiler.py), so a
    compile-time per-op sharding algebra had nothing to attach to.
    """
    if isinstance(op_types, str):
        op_types = (op_types,)

    def deco(f):
        for t in op_types:
            _SHARDING_RULES[t] = f
        return f

    return deco(fn) if fn is not None else deco


def get_sharding_rule(op_type: str) -> Optional[Callable]:
    return _SHARDING_RULES.get(op_type)


def has_sharding_rule(op_type: str) -> bool:
    return op_type in _SHARDING_RULES


def sharding_rule_types() -> List[str]:
    return sorted(_SHARDING_RULES)


# op type -> pool-index PROVENANCE rule for the analysis layer's
# ownership domain (analysis/absint.py). A rule is a PURE function
#     rule(op, prov_of, shape_of) -> {out_name: ProvFact}
# over Program metadata: it states how the op carries symbolic
# provenance of pool indices (host-owned table tags, trace-time
# constants, 0/1 indicators, value bounds) from inputs to outputs.
# Families live in analysis/ownership_rules.py, beside the sharding
# families; an op WITHOUT a rule propagates NO provenance, so an
# index that flows through it reaches a @POOL access with UNKNOWN
# provenance and PTA190 rejects the access — imprecision is a loud
# error at the one place it matters, never a silent pass.
_INDEX_RULES: Dict[str, Callable] = {}


def register_index_rule(op_types, fn: Optional[Callable] = None):
    """Register a pool-index provenance rule for one op type or a
    family (mirrors register_sharding_rule; usable as a decorator).

    Reference counterpart: none — the reference checks allocator
    state at RUNTIME (reference framework/scope.cc Var lookups); a
    compile-time index-provenance algebra is the shared-pool-era
    capability the whole-block-jit serving path needs instead.
    """
    if isinstance(op_types, str):
        op_types = (op_types,)

    def deco(f):
        for t in op_types:
            _INDEX_RULES[t] = f
        return f

    return deco(fn) if fn is not None else deco


def get_index_rule(op_type: str) -> Optional[Callable]:
    return _INDEX_RULES.get(op_type)


def has_index_rule(op_type: str) -> bool:
    return op_type in _INDEX_RULES


def index_rule_types() -> List[str]:
    return sorted(_INDEX_RULES)


# ownership tag -> acquire/release CONTRACT for the analysis layer's
# liveness domain (analysis/liveness.py). Where the index rules above
# prove WHERE a pool index came from, a contract declares the
# obligation that acquiring through that tag creates — which host
# call mints the hold, which call discharges it, and the exhaustive
# set of protocol exit paths on which the discharge must be proven to
# run (normal retirement, preemption, abort, invalidate, session
# close, server close, future cancel). PTA201 walks these: a tag a
# program actually exercises with NO contract, or a declared exit
# path with NO registered release site, is an unproven obligation —
# an error, never a silent pass. Contracts register via
# absint.register_acquire_release (which validates the tag against
# the ownership-source table); release SITES register from the code
# that implements them (inference/serving.py) so the ledger names
# real methods, not prose.
_ACQUIRE_CONTRACTS: Dict[str, object] = {}

# (tag, exit_path) -> list of "module.method" site strings proving
# the release runs on that path.
_RELEASE_SITES: Dict[Tuple[str, str], List[str]] = {}


def register_acquire_contract(tag: str, contract: object) -> None:
    """Register the acquire/release contract for an ownership tag.
    Idempotent on identical re-registration; raises on a DIFFERING
    redefinition (two subsystems disagreeing about an obligation is
    a bug, not a merge).

    Reference counterpart: none — the reference frees at runtime via
    GC passes (reference framework/executor_gc.md); a static
    obligation registry is the proof-tier analogue.
    """
    prev = _ACQUIRE_CONTRACTS.get(tag)
    if prev is not None:
        if prev == contract:
            return
        raise ValueError(
            f"acquire contract for {tag!r} already registered with "
            f"different terms: {prev} vs {contract}")
    _ACQUIRE_CONTRACTS[tag] = contract


def get_acquire_contract(tag: str):
    return _ACQUIRE_CONTRACTS.get(tag)


def acquire_contracts() -> Dict[str, object]:
    return dict(_ACQUIRE_CONTRACTS)


def register_release_site(tag: str, exit_path: str,
                          site: str) -> None:
    """Record that `site` (a "Class.method" string in the serving
    layer) discharges `tag`'s obligation on `exit_path`. Append-only
    and idempotent per site. Validation that the tag has a contract
    and declares the exit lives in absint.register_release_site (the
    public wrapper) — this is the bare store.

    Reference counterpart: none (see register_acquire_contract).
    """
    sites = _RELEASE_SITES.setdefault((tag, exit_path), [])
    if site not in sites:
        sites.append(site)


def release_sites() -> Dict[Tuple[str, str], List[str]]:
    return {k: list(v) for k, v in _RELEASE_SITES.items()}


def kernel_bridges_host(fn: Callable) -> bool:
    """True when `fn`'s code references jax's io_callback/pure_callback
    host bridges — directly, in nested functions, or through helper
    functions defined in the SAME module (a kernel that factors its
    callback into a shared module helper must still trip the
    host_effect assert). Works off code objects (co_names covers both
    module-level imports and function-local `from jax.experimental
    import io_callback`), so it costs microseconds at registration —
    no source parsing. Cross-module helpers are not followed; a
    kernel delegating its host bridge to another module must carry
    host_effect=True explicitly."""
    import types

    targets = ("io_callback", "pure_callback")
    seen = set()

    def scan_fn(f):
        code = getattr(f, "__code__", None)
        if code is None or id(code) in seen:
            return False  # seen: also breaks mutual-recursion cycles
        if scan_code(code):
            return True
        # follow same-module helper functions referenced by name
        module = getattr(f, "__module__", None)
        globs = getattr(f, "__globals__", {})
        for name in code.co_names:
            g = globs.get(name)
            if isinstance(g, types.FunctionType) and \
                    g.__module__ == module and scan_fn(g):
                return True
        return False

    def scan_code(code):
        if id(code) in seen:
            return False
        seen.add(id(code))
        if any(n in code.co_names for n in targets):
            return True
        return any(isinstance(c, types.CodeType) and scan_code(c)
                   for c in code.co_consts)

    return scan_fn(fn)

# placeholder input name meaning "no value" (e.g. an output grad that is
# never reached by backprop); run_op resolves it to None and the vjp grad
# kernel substitutes zeros (reference uses fill_zeros_like ops instead).
EMPTY_VAR = "@EMPTY@"


def get_op_info(type: str) -> OpInfo:
    if type not in _REGISTRY:
        raise KeyError(f"Operator {type!r} is not registered "
                       f"({len(_REGISTRY)} ops registered)")
    return _REGISTRY[type]


def is_registered(type: str) -> bool:
    return type in _REGISTRY


def registered_ops() -> List[str]:
    return sorted(_REGISTRY)


class OpContext:
    """What a kernel sees: resolved input values + attrs + a PRNG tap."""

    __slots__ = ("op", "attrs", "_inputs", "_rng_cell", "_rng_salt",
                 "_rng_calls")

    def __init__(self, op: Operator, inputs: Dict[str, List],
                 rng_cell=None, rng_salt: int = 0):
        self.op = op
        self.attrs = op.attrs
        self._inputs = inputs
        self._rng_cell = rng_cell  # single-element list holding step key
        self._rng_salt = rng_salt
        self._rng_calls = 0

    def input(self, slot, idx=0):
        vals = self._inputs.get(slot)
        if not vals:
            return None
        return vals[idx]

    def inputs(self, slot) -> List:
        return list(self._inputs.get(slot, []))

    def has_input(self, slot):
        return bool(self._inputs.get(slot))

    def attr(self, name, default=None):
        return self.attrs.get(name, default)

    def rng(self):
        """Derive this op's PRNG key from the per-step key.

        Purely functional: key = fold_in(step_key, op uid) -- never
        advances shared state, so the vjp grad kernel can reproduce the
        exact forward noise by re-deriving with the same salt. The
        executor advances the step key once per step instead."""
        if self._rng_cell is None:
            # shape-inference / eval_shape path: abstract key is fine
            return jax.random.PRNGKey(0)
        key = jax.random.fold_in(self._rng_cell[0], self._rng_salt)
        if self._rng_calls:
            key = jax.random.fold_in(key, self._rng_calls)
        self._rng_calls += 1
        return key


def register_op(type: str, *, infer_shape=None, grad_maker=None,
                differentiable=True, inplace=None, stop_gradient_slots=(),
                needs_rng=False, host_effect=False, sharding_rule=None):
    """Decorator: register `fn(ctx) -> {out_slot: value|[values]}`.
    `sharding_rule` optionally registers the op's sharding-propagation
    rule in the same breath (see register_sharding_rule)."""

    def deco(fn):
        if sharding_rule is not None:
            register_sharding_rule(type, sharding_rule)
        if not host_effect and kernel_bridges_host(fn):
            # the r6 'REMEMBER the flag' learning, mechanized: a
            # host-bridging kernel registered without the flag would be
            # silently lowered into Executor.run_steps' device-resident
            # lax.scan, breaking its once-per-step host semantics
            raise RuntimeError(
                f"op {type!r}: kernel references io_callback/"
                f"pure_callback but is registered with "
                f"host_effect=False — register with host_effect=True "
                f"so Executor.run_steps falls back to the per-step "
                f"path (analysis checker PTA070)")
        _REGISTRY[type] = OpInfo(
            type, fn, infer_shape=infer_shape, grad_maker=grad_maker,
            differentiable=differentiable, inplace=inplace,
            stop_gradient_slots=stop_gradient_slots, needs_rng=needs_rng,
            host_effect=host_effect)
        return fn

    return deco


def _normalize_outputs(op: Operator, raw) -> Dict[str, List]:
    out: Dict[str, List] = {}
    if raw is None:
        return out
    if not isinstance(raw, dict):
        # single-output convenience: bind to the op's single output slot
        slots = [s for s in op.outputs if op.outputs[s]]
        if len(slots) != 1:
            raise ValueError(
                f"op {op.type} returned a bare value but has output slots "
                f"{list(op.outputs)}")
        raw = {slots[0]: raw}
    for slot, vals in raw.items():
        if vals is None:
            continue
        if not isinstance(vals, (list, tuple)):
            vals = [vals]
        out[slot] = list(vals)
    return out


def run_op(op: Operator, env: Dict, rng_cell=None, rng_salt=0) -> None:
    """Execute one op against an env of name->traced value; write outputs."""
    info = get_op_info(op.type)
    inputs: Dict[str, List] = {}
    for slot, names in op.inputs.items():
        vals = []
        for n in names:
            if n == EMPTY_VAR:
                vals.append(None)
            elif n not in env:
                raise KeyError(
                    f"op {op.type}: input var {n!r} (slot {slot}) not "
                    f"materialized; known={sorted(list(env))[:20]}...")
            else:
                vals.append(env[n])
        inputs[slot] = vals
    from .. import amp

    if amp.enabled():
        inputs = amp.cast_op_inputs(op.type, inputs)
    ctx = OpContext(op, inputs, rng_cell=rng_cell, rng_salt=rng_salt)
    scope = op.attrs.get(DEVICE_SCOPE_ATTR)
    if scope:
        with jax.named_scope(scope):
            raw = info.kernel(ctx)
    else:
        raw = info.kernel(ctx)
    outs = _normalize_outputs(op, raw)
    for slot, names in op.outputs.items():
        vals = outs.get(slot)
        if vals is None:
            continue
        if len(vals) != len(names):
            raise ValueError(
                f"op {op.type}: slot {slot} produced {len(vals)} values for "
                f"{len(names)} output vars")
        for n, v in zip(names, vals):
            env[n] = v


# ---------------------------------------------------------------------------
# Generic shape inference: eval_shape at two fake batch sizes; dims that
# move with the fake size are dynamic (-1).
# ---------------------------------------------------------------------------
_PROBE_A, _PROBE_B = 7, 11
_INFER_WARNED: set = set()


def _probe_spec(var, probe):
    shape = tuple(probe if d == -1 else d for d in (var.shape or ()))
    dtype = to_jnp_dtype(var.dtype or "float32")
    return jax.ShapeDtypeStruct(shape, dtype)


def _snapshot_output_decls(op: Operator, block: Block):
    """Pre-inference (var, shape, dtype) of the op's existing output
    vars — the evidence base for the PTA140 declared-shape-clobber
    checker (analysis/checkers.py). Output names with NO var yet are
    recorded as (name, None): the var the inference pass creates for
    them is inference-shaped from birth, never a declaration."""
    snap = []
    missing = []
    for n in op.output_arg_names:
        v = block._find_var_recursive(n)
        if v is not None:
            snap.append((v, v.shape, v.dtype))
        else:
            missing.append(n)
    return snap, missing


def _record_decl_clobbers(snap) -> None:
    """Build-time shape inference OVERWRITES a var's declared
    shape/dtype with the producer's inferred one, in place (the r10
    incident: assign of a [-1,4] value onto a concretely-declared
    persistable rewrites it to [-1,4], silently breaking the var's
    feed/carry contract). The declaration is unrecoverable after the
    fact, so this hook stashes it on FIRST clobber: a shape/dtype that
    was present before any inference pass changed it is the builder's
    declaration (`_declared_shape`/`_declared_dtype`); shapes a prior
    inference pass itself wrote (`_shape_inferred`) are producer
    facts, not declarations — multi-writer temps never false-positive.
    The PTA140 checker reads the stash."""
    for v, shape0, dtype0 in snap:
        if v.shape != shape0:
            if shape0 is not None and \
                    not getattr(v, "_shape_inferred", False) and \
                    not hasattr(v, "_declared_shape"):
                v._declared_shape = tuple(shape0)
            v._shape_inferred = True
        if v.dtype != dtype0:
            if dtype0 is not None and \
                    not getattr(v, "_dtype_inferred", False) and \
                    not hasattr(v, "_declared_dtype"):
                v._declared_dtype = dtype0
            v._dtype_inferred = True


def infer_shape_for_op(op: Operator, block: Block) -> None:
    info = _REGISTRY.get(op.type)
    if info is None:
        return  # unregistered (e.g. feed/fetch placeholders) -- skip
    snap, missing = _snapshot_output_decls(op, block)
    try:
        _infer_shape_for_op(op, block, info)
    finally:
        _record_decl_clobbers(snap)
        for n in missing:
            v = block._find_var_recursive(n)
            if v is not None:
                # created by this inference pass: its metadata is a
                # producer fact from birth, never a declaration
                v._shape_inferred = True
                v._dtype_inferred = True


def _infer_shape_for_op(op: Operator, block: Block, info) -> None:
    if info.infer_shape is not None:
        info.infer_shape(op, block)
        return
    try:
        results = []
        for probe in (_PROBE_A, _PROBE_B):
            ins = {}
            ok = True
            for slot, names in op.inputs.items():
                vals = []
                for n in names:
                    v = block._find_var_recursive(n)
                    if v is None or v.shape is None or v.dtype is None:
                        ok = False
                        break
                    vals.append(_probe_spec(v, probe))
                if not ok:
                    break
                ins[slot] = vals
            if not ok:
                return

            def f(ins):
                ctx = OpContext(op, ins)
                return _normalize_outputs(op, info.kernel(ctx))

            results.append(jax.eval_shape(f, ins))
    except Exception as e:
        # Reference InferShape raises at build time (framework/
        # shape_inference.h). Here kernels double as shape functions via
        # eval_shape, and some legitimately cannot trace with -1 probe
        # dims -- so default is warn-and-defer, with FLAGS_strict_infer_
        # shape=1 restoring raise-at-append_op semantics.
        from ..flags import FLAGS

        if FLAGS.strict_infer_shape:
            raise RuntimeError(
                f"shape inference failed for op {op.type!r}: {e}") from e
        if op.type not in _INFER_WARNED:
            _INFER_WARNED.add(op.type)
            import warnings

            warnings.warn(
                f"shape inference for op {op.type!r} failed at build "
                f"time ({type(e).__name__}: {e}); output shapes left "
                f"unset -- errors may surface later at trace time. Set "
                f"FLAGS_strict_infer_shape=1 to raise here instead.")
        return
    ra, rb = results
    for slot, names in op.outputs.items():
        if slot not in ra:
            continue
        for n, sa, sb in zip(names, ra[slot], rb[slot]):
            var = block._find_var_recursive(n)
            if var is None:
                var = block.create_var(name=n)
            shape = tuple(
                da if da == db else -1
                for da, db in zip(sa.shape, sb.shape))
            var.shape = shape
            from .types import as_datatype

            var.dtype = as_datatype(sa.dtype.name)


# ---------------------------------------------------------------------------
# Generic grad machinery: <type>_grad op derived via jax.vjp of the forward.
# ---------------------------------------------------------------------------
def _is_float_dtype(x) -> bool:
    return jnp.issubdtype(jnp.result_type(x), jnp.floating)


def make_vjp_grad_kernel(fwd_type: str):
    """Build the kernel for `<fwd_type>_grad`.

    The grad op's inputs are the forward inputs plus `<slot>@GRAD` entries
    for each forward output slot; outputs are `<slot>@GRAD` for each
    differentiable forward input slot. The forward is recomputed inside the
    vjp (rematerialization) -- on TPU this trades cheap MXU FLOPs for HBM.
    """
    def kernel(ctx: OpContext):
        info = get_op_info(fwd_type)
        fwd_op = ctx.attr("__fwd_op__")
        # partition ctx inputs into forward inputs vs output cotangents
        fwd_inputs = {s: ctx.inputs(s) for s in fwd_op.inputs}
        # flatten differentiable leaves
        diff_paths, diff_leaves, const = [], [], {}
        for slot, vals in fwd_inputs.items():
            keep = (slot not in info.stop_gradient_slots)
            for i, v in enumerate(vals):
                if keep and _is_float_dtype(v):
                    diff_paths.append((slot, i))
                    diff_leaves.append(v)
                else:
                    const[(slot, i)] = v

        def f(leaves):
            ins = {s: [None] * len(v) for s, v in fwd_inputs.items()}
            for (s, i), v in const.items():
                ins[s][i] = v
            for (s, i), v in zip(diff_paths, leaves):
                ins[s][i] = v
            # same step key + the FORWARD op's salt: the recomputed
            # forward draws the identical noise the real forward drew
            inner = OpContext(fwd_op, ins, rng_cell=ctx._rng_cell,
                              rng_salt=fwd_op._uid)
            return _normalize_outputs(fwd_op, info.kernel(inner))

        outs, vjp_fn = jax.vjp(f, diff_leaves)
        # assemble cotangents in the same structure as outs
        cots = {}
        for slot, vals in outs.items():
            gs = ctx.inputs(slot + GRAD_SUFFIX)
            slot_cots = []
            for i, v in enumerate(vals):
                if gs and i < len(gs) and gs[i] is not None:
                    g = gs[i]
                    if g.dtype != v.dtype:
                        g = g.astype(v.dtype)
                    slot_cots.append(g)
                elif _is_float_dtype(v):
                    slot_cots.append(jnp.zeros_like(v))
                else:
                    # an integer output (chosen experts, counts) takes
                    # no cotangent: jax.vjp wants float0 there
                    slot_cots.append(np.zeros(v.shape, jax.dtypes.float0))
            cots[slot] = slot_cots
        (grads,) = vjp_fn(cots)
        result: Dict[str, List] = {}
        for (slot, i), g in zip(diff_paths, grads):
            names = fwd_op.inputs[slot]
            result.setdefault(slot + GRAD_SUFFIX,
                              [None] * len(names))[i] = g
        # drop slots whose grads were all skipped
        return {s: v for s, v in result.items()
                if any(x is not None for x in v)}

    return kernel


def default_grad_maker(op: Operator, no_grad_set=frozenset()):
    """Create the grad OpDesc for `op` (reference grad_op_desc_maker.h).

    Returns a list of Operator descs (not yet appended to any block).
    """
    info = get_op_info(op.type)
    if not info.differentiable:
        return []
    grad_type = op.type + "_grad"
    if not is_registered(grad_type):
        register_op(grad_type, differentiable=False)(
            make_vjp_grad_kernel(op.type))
    inputs = {s: list(v) for s, v in op.inputs.items()}
    for slot, names in op.outputs.items():
        inputs[slot + GRAD_SUFFIX] = [grad_var_name(n) for n in names]
    outputs = {}
    for slot, names in op.inputs.items():
        if slot in info.stop_gradient_slots:
            continue
        grads = [grad_var_name(n) for n in names]
        if all(g in no_grad_set or n in no_grad_set
               for g, n in zip(grads, names)):
            continue
        outputs[slot + GRAD_SUFFIX] = grads
    if not outputs:
        return []
    attrs = dict(op.attrs)
    attrs["__fwd_op__"] = op
    return [Operator(op.block, grad_type, inputs, outputs, attrs)]


def make_grad_ops(op: Operator, no_grad_set=frozenset()):
    info = get_op_info(op.type)
    if info.grad_maker is not None:
        return info.grad_maker(op, no_grad_set)
    return default_grad_maker(op, no_grad_set)
