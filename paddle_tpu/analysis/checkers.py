"""Checker suite: static verification passes over the Program IR.

Reference counterparts: paddle/fluid/framework/op_desc.cc (attr/shape
checks at OpDesc build), operator.cc RunImpl enforcement, and the
transpiler-era program validators. The whole-block-jit Executor has no
per-op hook, so invalid programs here historically failed DEEP inside
a jax trace — or deadlocked a real TPU (CLAUDE.md session learnings).
Every checker below is grounded in one of those incidents and carries
a stable diagnostic code so tests/docs can reference the class:

  PTA001  uninitialized read            (go/_launch_go_ops bug class)
  PTA002  multiple writers              (ambiguous recompute/go capture)
  PTA003  dead op                       (build waste; XLA would DCE)
  PTA004  go-capture hazard             (late writer / host producer)
  PTA010  collective in divergent branch (r5 pp deadlock trap)
  PTA011  maybe-collective in branch    (scope-dependent lowering)
  PTA020  while-carry dtype promotion   (increment int->float trap)
  PTA030  duplicate uid on sampling ops (fwd/bwd noise divergence)
  PTA031  clone dropped/mutated uid     (Program.clone contract)
  PTA040  recompute clone not barrier-rooted (XLA CSE undoes remat)
  PTA050  auto-generated param names    (cross-build sharing fragility)
  PTA051  cross-program shared-name conflict
  PTA060  @SEQ_LEN companion mismatch   (static-batch probe trap)
  PTA070  host_effect flag missing      (run_steps scan correctness)
  PTA080  unregistered op type
  PTA090  write-only persistable not carry-declarable (r6 scan-carry
          trap: run_steps/prepare(steps=K) seed it with zeros)
  PTA100  cross-model param-name collision (co-resident serving
          runtime models aliasing/clobbering one scope's weights)
  PTA110  shared-pool write not provably lane-exclusive (paged KV
          block pools: aliased scatter = silent cross-request KV
          corruption)
  PTA120  speculative advance bound unprovable (spec_accept shape/
          attr disagreement: the counter-advance <= k+1 clamp and
          the accepted-prefix scatter's room clip are only sound
          when the declared k/max_len match the wired tensors)
  PTA130  collective under divergent control flow, PROVEN (absint
          guard contexts: subsumes PTA010/011, which remain as its
          fast-path corroboration — every diagnostic carries the
          per-guard divergence classification and source chain)
  PTA131  replicated value differentiated / sharded value consumed
          inside a divergent context (the r5 trap family: the grad
          transpose of an implicit replicated->varying cast is a
          psum, and an auto-axis sharding annotation reaching a
          divergent site invites a GSPMD-inserted collective)
  PTA140  declared shape/dtype clobbered by producer inference (the
          r10 'shape inference CLOBBERS a declared persistable'
          class; generalizes PTA020's int->float promotion beyond
          the `increment` special case)
  PTA150  decode-bundle contract (check_bundle: all serve/admission/
          step specializations of one DecodeStepBundle must agree on
          cache geometry, seed derivation, and counter presence)
  PTA160  sharding contradiction / implicit reshard (the sharding
          domain: consumers demanding incompatible ShardSpecs, or a
          GSPMD-forced reshard landing inside a serve-While body —
          the r5 'dp on the pre-reshape dim' trap, proven from the
          propagated specs instead of pattern-matched)
  PTA161  collective-order agreement (symbolic enumeration of the
          collective sequence each mesh coordinate observes through
          divergent guards, over BOTH literal collective ops and the
          sharding-implied psum/allgather/reshard events; ERROR when
          two coordinates can disagree — the 1F1B x tp vocab-psum
          rejection becomes a corollary of this proof)
  PTA170  per-device memory budget (the static planner
          analysis/memplan.py: persistable/feed/temp bytes under the
          propagated specs vs an opt-in per-program budget)
  PTA180  device-telemetry counter contract (@TEL-marked counters —
          observability/devtel.py — must be int64, concretely
          declared, persistable, and read-modify-write wherever
          written: the PTA020/PTA090 lessons applied to the decode
          flight-data subsystem; a drifted counter poisons every
          stats window with no downstream error)
  PTA190  pool-access provenance + in-bounds (the ownership domain,
          absint ProvFact: every index reaching a @POOL read/write
          must chain to a registered host-owned source or a
          trace-time constant, block-table writes must be gated by
          the lane-active mask, and the index bound must fit the
          indexed axis — unknown provenance is ERROR with the chain
          printed)
  PTA191  lane-exclusive write PROVEN (given the host allocator's
          disjoint-allocation invariant as a NAMED assumption, the
          provenance proof shows distinct lanes' writes hit disjoint
          rows — subsumes PTA110's syntactic declaration the way
          PTA130 subsumed PTA010: twin-dedupe at prover-covered
          sites, the exclusive_via declaration survives as the
          assumption's name and must AGREE with the proven chain)
  PTA192  read-only-while-shared (writes are only legal in the
          exclusive typestate of the free→exclusive→shared→freed
          block lifetime lattice: an index whose provenance chains
          to a REFCOUNTED source — prompt_entry_ref — certifies
          reads only; a write through it is the COW violation the
          radix/beam prefix-sharing work must never ship)
  PTA200  admission-capacity feasibility (the liveness domain,
          analysis/liveness.py: worst-case steady-state resource
          demand per serving configuration vs the static pools —
          lane block chains vs HostBlockPool, pinned session
          prompts vs PromptPrefixCache entries; an infeasible
          config gets a concrete deadlock witness, validated
          against the exhaustive protomodel explorer)
  PTA201  release-on-every-exit-path (every acquire obligation an
          ownership tag creates — absint.register_acquire_release —
          must have a registered release SITE on every declared
          protocol exit path: retirement, preemption, abort,
          invalidate, session/server close, handoff; an
          undischarged path is a leak nobody is maintaining)
  PTA202  serve-While progress (every While must carry a SOUND
          variant: an increment-driven counter in its condition's
          backward slice bounded by a loop-invariant feed/const;
          serve/burst Whiles additionally rest on the NAMED
          monotone-lane_active_mask assumption for their burst-exit
          disjunct)

Severities: "error" = the program is wrong (strict mode raises),
"warning" = almost certainly a bug but a legal feed/scope could save
it, "info" = hygiene finding. `run_checks(program)` runs everything;
per-site suppressions ride the ``_pta_suppress=("PTA0xx", "reason")``
op attr (counted, surfaced in the CLI's --json and the CI baseline).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional

from ..core.program import Block, Operator, Program
from ..core.registry import (EMPTY_VAR, get_op_info, is_registered,
                             kernel_bridges_host)
from .dataflow import (BlockDataflow, OpSite, analyze_block,
                       block_entry_names, iter_blocks, iter_ops,
                       iter_sub_blocks)

__all__ = ["Diagnostic", "Checker", "register_checker", "run_checks",
           "check_registry", "check_shared_params", "check_clone_uids",
           "check_cross_model_collision", "check_bundle",
           "registered_checkers", "format_diagnostics",
           "ERROR", "WARNING", "INFO", "SUPPRESS_ATTR"]

ERROR, WARNING, INFO = "error", "warning", "info"

# per-site diagnostic suppression: an op carrying
# _pta_suppress=("PTA0xx", "reason") — or a list/tuple of such pairs —
# silences diagnostics of that code ANCHORED AT that op. Suppressions
# are counted and surfaced (CLI --json `suppressed`, CI baseline), so
# they are reviewable debt, not disappearances.
SUPPRESS_ATTR = "_pta_suppress"

# ops the Executor skips at trace time (core/executor.py _SKIP_OP_TYPES
# plus the feed/fetch placeholders that are never registered)
_PLUMBING = ("feed", "fetch")

# cross-process / cross-device collective ops (ops/dist_ops.py): their
# host-bridge (ordered io_callback) or psum sequencing must be
# IDENTICAL on every participant — a divergent lax.cond/while means
# participants disagree on whether the collective runs at all
DIST_OP_TYPES = frozenset({
    "send", "recv", "send_barrier", "fetch_barrier", "prefetch",
    "prefetch_grad", "checkpoint_notify", "allreduce",
    "listen_and_serv", "gen_nccl_id",
})

# ops whose kernels lower through shard_map / with_sharding_constraint
# when a parallel scope (context/expert parallel) is active — inside a
# divergent branch GSPMD may then plant a collective in the branch
# body (the r6 1F1B x tp generalized trap)
SCOPE_COLLECTIVE_OP_TYPES = frozenset({
    "attention", "attention_block", "switch_moe",
})

# container op type -> whether its sub-blocks trace as DIVERGENT
# control flow (lax.cond / lax.while_loop): different devices can take
# different paths, so a collective inside deadlocks
DIVERGENT_CONTAINERS = frozenset({
    "conditional_block", "run_block_if", "ifelse", "while",
})

_AUTO_PARAM_RE = re.compile(r"_\d+\.[wb]_\d+$")

RECOMP_MARK = "@RECOMP"
BARRIER_MARK = "@BAR"


@dataclass(frozen=True)
class Diagnostic:
    """One structured finding (reference: the EnforceNotMet message the
    C++ validators would have raised, made machine-readable)."""
    code: str
    severity: str
    message: str
    block_idx: int = 0
    op_idx: Optional[int] = None
    op_type: Optional[str] = None
    var: Optional[str] = None
    hint: Optional[str] = None

    def format(self) -> str:
        where = f"block {self.block_idx}"
        if self.op_idx is not None:
            where += f" op {self.op_idx}"
        if self.op_type:
            where += f" ({self.op_type})"
        out = f"{self.code} [{self.severity}] {where}: {self.message}"
        if self.hint:
            out += f"\n    hint: {self.hint}"
        return out


def _diag_at(code, severity, site: OpSite, message, var=None,
             hint=None) -> Diagnostic:
    return Diagnostic(code, severity, message, block_idx=site.block_idx,
                      op_idx=site.op_idx, op_type=site.op.type, var=var,
                      hint=hint)


@dataclass
class Checker:
    code: str
    name: str
    fn: Callable[[Program], Iterable[Diagnostic]]
    doc: str = ""


_CHECKERS: Dict[str, Checker] = {}


def register_checker(code: str, name: str, doc: str = ""):
    """Decorator registering `fn(program) -> iterable of Diagnostic`
    under a stable PTA code (mirrors core/registry.register_op)."""

    def deco(fn):
        _CHECKERS[code] = Checker(code, name, fn, doc or fn.__doc__ or "")
        return fn

    return deco


def registered_checkers() -> List[Checker]:
    return [_CHECKERS[c] for c in sorted(_CHECKERS)]


def _normalize_suppressions(raw):
    """Accept ("PTA0xx", "reason") or a list/tuple of such pairs;
    return [(code, reason)] or None for a malformed attr."""
    if isinstance(raw, (list, tuple)) and len(raw) == 2 and \
            all(isinstance(x, str) for x in raw):
        raw = [raw]
    if not isinstance(raw, (list, tuple)):
        return None
    out = []
    for entry in raw:
        if not (isinstance(entry, (list, tuple)) and len(entry) == 2
                and all(isinstance(x, str) for x in entry)
                and re.fullmatch(r"PTA\d{3}", entry[0])):
            return None
        out.append((entry[0], entry[1]))
    return out


def _collect_suppressions(program: Program):
    """(block_idx, op_idx, code) -> reason, plus malformed-attr
    diagnostics (a suppression that silently failed to parse would be
    a suppression that silently does nothing)."""
    sup: Dict[tuple, str] = {}
    malformed: List[Diagnostic] = []
    for site in iter_ops(program):
        raw = site.op.attrs.get(SUPPRESS_ATTR)
        if raw is None:
            continue
        entries = _normalize_suppressions(raw)
        if entries is None:
            malformed.append(_diag_at(
                "PTA199", WARNING, site,
                f"malformed {SUPPRESS_ATTR} attr {raw!r}; expected "
                f"(\"PTA0xx\", \"reason\") or a list of such pairs "
                f"— the suppression is IGNORED",
                hint="fix the attr; nothing is suppressed until it "
                     "parses"))
            continue
        for code, reason in entries:
            sup[(site.block_idx, site.op_idx, code)] = reason
    return sup, malformed


def run_checks(program: Program,
               only: Optional[Iterable[str]] = None,
               collect_suppressed: Optional[list] = None,
               collect_timings: Optional[Dict[str, float]] = None
               ) -> List[Diagnostic]:
    """Run every registered checker (or the `only` subset of codes)
    over `program`; returns diagnostics sorted error-first, stable
    within severity. Diagnostics anchored at an op carrying a matching
    ``_pta_suppress`` attr are dropped from the return value and — when
    `collect_suppressed` is a list — appended to it as
    (diagnostic, reason) pairs so callers (CLI --json, the CI
    baseline) can count and surface them. `collect_timings`
    accumulates per-checker wall seconds (code -> s) across calls —
    the CLI's --json surfaces the totals so a slow checker is
    attributable instead of a mystery in the gate's wall-time pin."""
    import time as _time

    codes = set(only) if only is not None else None
    out: List[Diagnostic] = []
    for checker in registered_checkers():
        if codes is not None and checker.code not in codes:
            continue
        t0 = _time.perf_counter() if collect_timings is not None \
            else 0.0
        out.extend(checker.fn(program))
        if collect_timings is not None:
            collect_timings[checker.code] = collect_timings.get(
                checker.code, 0.0) + (_time.perf_counter() - t0)
    sup, malformed = _collect_suppressions(program)
    if malformed and (codes is None or "PTA199" in codes):
        out.extend(malformed)
    if sup:
        kept = []
        for d in out:
            reason = None
            if d.op_idx is not None:
                reason = sup.get((d.block_idx, d.op_idx, d.code))
            if reason is None:
                kept.append(d)
            elif collect_suppressed is not None:
                collect_suppressed.append((d, reason))
        out = kept
    rank = {ERROR: 0, WARNING: 1, INFO: 2}
    out.sort(key=lambda d: (rank.get(d.severity, 3), d.code,
                            d.block_idx, d.op_idx or 0))
    return out


def format_diagnostics(diags: Iterable[Diagnostic]) -> str:
    return "\n".join(d.format() for d in diags)


# ---------------------------------------------------------------------------
# Dataflow checks: PTA001 uninitialized read, PTA002 multi-writer,
# PTA003 dead op, PTA004 go-capture hazards.
# ---------------------------------------------------------------------------
def _seed_names(blk: Block, container: Optional[Operator]) -> set:
    """Names defined before any op of `blk` runs: persistables (from
    the scope after the startup program), declared data vars (feeds),
    and — for sub-blocks — the containing op's declared environment
    (control-flow kernels build a FRESH env; see block_entry_names)."""
    seeded = set()
    b: Optional[Block] = blk
    while b is not None:
        for v in b.vars.values():
            if v.persistable or v.is_data:
                seeded.add(v.name)
        b = b.parent_block
    if container is not None:
        seeded |= block_entry_names(container)
    return seeded


@register_checker("PTA001", "uninitialized-read")
def check_uninitialized_reads(program: Program):
    """A var read before any write that is neither persistable (scope
    state), a declared data var (feed), nor part of a sub-block's
    declared environment. At run time this is the Executor's
    'used before initialization' error or a trace-time KeyError —
    warning severity because an undeclared name CAN still be fed."""
    for blk, container in iter_blocks(program):
        seeded = _seed_names(blk, container)
        written = set()
        for i, op in enumerate(blk.ops):
            if op.type in _PLUMBING:
                continue
            for n in op.input_arg_names:
                if n == EMPTY_VAR or n in written or n in seeded:
                    continue
                site = OpSite(blk.idx, i, op, container)
                yield _diag_at(
                    "PTA001", WARNING, site,
                    f"var {n!r} is read before any write in the block "
                    f"and is neither persistable nor a declared data "
                    f"var", var=n,
                    hint="feed it, declare it with layers.data(...), "
                         "or produce it before this op")
                seeded.add(n)  # one diagnostic per name per block
            written.update(op.output_arg_names)


@register_checker("PTA002", "multi-writer")
def check_multi_writers(program: Program):
    """A non-persistable var written by more than one op in a block.
    Legal (last-writer-wins under the trace), but it makes the value
    observed by threads (go), recompute clones, and human readers
    order-dependent — the exact ambiguity _launch_go_ops refuses at
    run time. Info severity; the go-specific EP is PTA004."""
    for blk, container in iter_blocks(program):
        df = analyze_block(blk)
        for name, idxs in df.multi_writers().items():
            var = blk._find_var_recursive(name)
            if var is not None and var.persistable:
                continue  # in-place state updates are the normal idiom
            op = blk.ops[idxs[1]]
            site = OpSite(blk.idx, idxs[1], op, container)
            yield _diag_at(
                "PTA002", INFO, site,
                f"var {name!r} has {len(idxs)} writers in this block "
                f"(ops {idxs})", var=name,
                hint="rename intermediate results or route the value "
                     "through a persistable var if threads/clones "
                     "must observe a specific write")


@register_checker("PTA003", "dead-op")
def check_dead_ops(program: Program):
    """An op none of whose outputs is ever read anywhere in the
    program, written to a persistable (scope state), or side-effecting.
    XLA dead-codes it, but it still costs build/trace time and usually
    marks builder bugs. Info severity: fetch targets are unknown
    statically, so the last producer of a to-be-fetched var looks
    dead here."""
    read_anywhere = set()
    for blk, _ in iter_blocks(program):
        for op in blk.ops:
            read_anywhere.update(op.input_arg_names)
            for v in op.attrs.values():
                if isinstance(v, (list, tuple)) and v and all(
                        isinstance(x, str) for x in v):
                    read_anywhere.update(v)
    for site in iter_ops(program):
        op = site.op
        if op.type in _PLUMBING or not op.output_arg_names:
            continue
        if is_registered(op.type) and get_op_info(op.type).host_effect:
            continue
        if any(isinstance(v, Block) for v in op.attrs.values()):
            continue
        live = False
        for n in op.output_arg_names:
            var = site.op.block._find_var_recursive(n) \
                if site.op.block is not None else None
            if n in read_anywhere or (var is not None and
                                      var.persistable):
                live = True
                break
        if not live:
            yield _diag_at(
                "PTA003", INFO, site,
                f"no output of this op ({op.output_arg_names}) is read "
                f"anywhere, persistable, or side-effecting",
                hint="drop the op, or fetch/persist its result")


@register_checker("PTA004", "go-capture-hazard")
def check_go_captures(program: Program):
    """Static form of the _launch_go_ops run-time refusals: a `go` op
    capture that is (a) first written AFTER the go op, (b) written by
    multiple ops before it (ambiguous recompute), or (c) produced by a
    host-effecting op (recomputing doubles its side effects). All
    three raise at run time today — this surfaces them at build."""
    for blk, container in iter_blocks(program):
        df = analyze_block(blk)
        for go_idx, op in enumerate(blk.ops):
            if op.type != "go":
                continue
            site = OpSite(blk.idx, go_idx, op, container)
            for n in op.inputs.get("X", []):
                var = blk._find_var_recursive(n)
                if var is not None and (var.persistable or var.is_data):
                    continue
                writes = df.writers.get(n, [])
                before = [i for i in writes if i < go_idx]
                if not before:
                    if writes:
                        yield _diag_at(
                            "PTA004", ERROR, site,
                            f"go captures {n!r}, first written by op "
                            f"{writes[0]} AFTER the go op — the "
                            f"reference's eager executor would not "
                            f"observe it at the go point", var=n)
                    else:
                        yield _diag_at(
                            "PTA004", ERROR, site,
                            f"go captures {n!r} which is neither fed, "
                            f"persistable, nor produced by the block",
                            var=n)
                    continue
                if len(before) > 1:
                    yield _diag_at(
                        "PTA004", ERROR, site,
                        f"go captures {n!r} which has multiple writers "
                        f"before the go op (ops {before}); recomputing "
                        f"it in the go thread is ambiguous", var=n,
                        hint="route the value through a persistable "
                             "var")
                    continue
                producer = blk.ops[before[0]]
                if is_registered(producer.type) and \
                        get_op_info(producer.type).host_effect:
                    yield _diag_at(
                        "PTA004", ERROR, site,
                        f"go captures {n!r} produced by host-effecting "
                        f"op {producer.type!r}; recomputing it in the "
                        f"go thread would double its side effects",
                        var=n,
                        hint="route the value through a persistable "
                             "var")


# ---------------------------------------------------------------------------
# PTA010/PTA011: collectives inside divergent control flow.
# ---------------------------------------------------------------------------
def _is_collective(op: Operator) -> bool:
    if op.type in DIST_OP_TYPES:
        return True
    # an explicit shard_map axis on any op (sync_batch_norm and
    # friends) makes its kernel emit lax.psum over that axis
    return bool(op.attrs.get("axis_name"))


def _walk_block_ops(blk: Block, seen=None):
    """All ops in blk and (recursively) its sub-block attrs."""
    if seen is None:
        seen = set()
    for i, op in enumerate(blk.ops):
        yield i, op
        for _, sub in iter_sub_blocks(op):
            if id(sub) in seen:
                continue
            seen.add(id(sub))
            yield from _walk_block_ops(sub, seen)


def _prover_coverage(program: Program):
    """Op ids the PTA130 prover covers (every site it walked under a
    traced guard), or None when the prover is unavailable for this
    program (fixpoint failed to converge / raised) — the legacy
    PTA010/011 pattern matchers only emit at sites the prover does
    NOT cover, so each incident surfaces exactly once, with the
    proof-carrying diagnostic when one exists (the twin-diagnostic
    dedupe; the gate test pins the superset relation)."""
    from . import absint

    try:
        facts = absint.analyze(program)
    except Exception:
        return None
    if not facts.converged:
        return None
    return {id(site.op) for site, _g in facts.guarded_sites()}


@register_checker("PTA010", "collective-in-divergent-branch")
def check_collective_in_branch(program: Program):
    """NO collective may live inside divergent control flow: devices
    (or processes, for the io_callback pserver ops) that take
    different branches disagree on whether — or in which order — the
    collective executes, and the program deadlocks. This is the r5
    shard_map + lax.cond trap (CLAUDE.md) as a build-time error; the
    reference had no equivalent because its executor ran branches on
    the host.

    Sites the absint prover covers are left to PTA130, which carries
    the same ERROR stance plus the divergence proof — this pattern
    matcher is the FALLBACK for programs the fixpoint engine cannot
    analyze, so the two never double-report one incident."""
    covered = _prover_coverage(program)
    for blk, container in iter_blocks(program):
        for i, op in enumerate(blk.ops):
            if op.type not in DIVERGENT_CONTAINERS:
                continue
            for attr_name, sub in iter_sub_blocks(op):
                for j, inner in _walk_block_ops(sub):
                    if _is_collective(inner):
                        if covered is not None and \
                                id(inner) in covered:
                            continue  # PTA130 proves this site
                        site = OpSite(blk.idx, i, op, container)
                        yield _diag_at(
                            "PTA010", ERROR, site,
                            f"collective op {inner.type!r} (sub-block "
                            f"{attr_name} op {j}) lives inside "
                            f"divergent control flow ({op.type}); "
                            f"participants taking different paths "
                            f"will deadlock",
                            var=(inner.output_arg_names or [None])[0],
                            hint="hoist the collective out of the "
                                 "branch and mask its input instead "
                                 "(psum of a zeroed contribution is "
                                 "the identity)")


@register_checker("PTA011", "scope-collective-in-branch")
def check_scope_collective_in_branch(program: Program):
    """Ops that lower to shard_map collectives only when a parallel
    scope (context/expert parallel) is active, found inside divergent
    control flow. Warning: single-device lowering is fine, but the
    same program under scope_context_parallel/expert_parallel plants
    a collective in the branch — the r6 generalized GSPMD trap.
    Like PTA010, sites the absint prover covers are left to PTA130
    (which also upgrades them to ERROR under a proven-divergent
    guard); this matcher is the non-convergence fallback."""
    covered = _prover_coverage(program)
    for blk, container in iter_blocks(program):
        for i, op in enumerate(blk.ops):
            if op.type not in DIVERGENT_CONTAINERS:
                continue
            found: Dict[str, int] = {}
            for attr_name, sub in iter_sub_blocks(op):
                for _, inner in _walk_block_ops(sub):
                    if inner.type in SCOPE_COLLECTIVE_OP_TYPES:
                        if covered is not None and \
                                id(inner) in covered:
                            continue
                        found[inner.type] = found.get(inner.type, 0) + 1
            for inner_type, count in sorted(found.items()):
                site = OpSite(blk.idx, i, op, container)
                yield _diag_at(
                    "PTA011", WARNING, site,
                    f"{count} {inner_type!r} op(s) inside this "
                    f"{op.type}'s sub-blocks lower to shard_map "
                    f"collectives under context/expert-parallel "
                    f"scopes; there they become branch-internal "
                    f"collectives and deadlock",
                    hint=f"keep parallel-scope models' {inner_type} "
                         "ops out of divergent branches, or run this "
                         "program only outside those scopes")


# ---------------------------------------------------------------------------
# PTA020: while-carry dtype stability.
# ---------------------------------------------------------------------------
def _is_int_dtype_str(s: Optional[str]) -> bool:
    return bool(s) and s.startswith(("int", "uint", "bool"))


def _writer_dtype_map(program: Program) -> Dict[str, str]:
    """name -> dtype attr of its FIRST writer op carrying an explicit
    dtype (fill_constant / cast / ...), in program walk order. One
    pass, shared by every increment check in the run — writer attrs
    beat the Variable's dtype field, because build-time shape
    inference OVERWRITES an in-place op's shared var dtype with the
    (possibly already promoted) inferred result."""
    out: Dict[str, str] = {}
    for site in iter_ops(program):
        dt = site.op.attrs.get("dtype") or \
            site.op.attrs.get("out_dtype")
        if not isinstance(dt, str):
            continue
        for n in site.op.output_arg_names:
            out.setdefault(n, dt)
    return out


@register_checker("PTA020", "while-carry-dtype")
def check_while_carry_dtypes(program: Program):
    """`increment(x, 1.0)` on an integer var promotes the value to
    float under JAX weak typing; if the var is a lax.while_loop carry
    the loop raises a carry-structure TypeError deep inside the trace
    (CLAUDE.md: 'pass int steps'). Error inside while bodies, warning
    elsewhere (the counter silently changes dtype)."""
    in_while = set()
    for blk, _ in iter_blocks(program):
        for op in blk.ops:
            if op.type == "while":
                for _, sub in iter_sub_blocks(op):
                    for _, inner in _walk_block_ops(sub):
                        in_while.add(id(inner))
    writer_dtypes = None  # built lazily: most programs have 0 hits
    for site in iter_ops(program):
        op = site.op
        if op.type != "increment":
            continue
        step = op.attrs.get("step", 1.0)
        if not isinstance(step, float):
            continue
        names = op.inputs.get("X", [])
        if not names:
            continue
        if writer_dtypes is None:
            writer_dtypes = _writer_dtype_map(program)
        var = site.op.block._find_var_recursive(names[0])
        dtype = writer_dtypes.get(names[0]) or (
            var.dtype.value if var is not None and var.dtype is not None
            else None)
        if not _is_int_dtype_str(dtype):
            continue
        severity = ERROR if id(op) in in_while else WARNING
        yield _diag_at(
            "PTA020", severity, site,
            f"increment of integer var {names[0]!r} "
            f"(dtype {dtype}) with float step {step!r} "
            f"promotes the value to float"
            + (" and breaks the lax.while_loop carry dtype"
               if severity == ERROR else ""),
            var=names[0],
            hint="pass an int step: layers.increment(x, 1)")


# ---------------------------------------------------------------------------
# PTA030/PTA031: structural uid preservation for sampling ops.
# ---------------------------------------------------------------------------
def _needs_rng(op_type: str) -> bool:
    return is_registered(op_type) and get_op_info(op_type).needs_rng


def _is_recompute_clone(op: Operator) -> bool:
    return any(RECOMP_MARK in n for n in op.output_arg_names) or (
        op.attrs.get("op_role") == "backward")


@register_checker("PTA030", "sampling-uid-collision")
def check_sampling_uids(program: Program):
    """Sampling ops derive their PRNG salt from `op._uid`
    (fold_in(step_key, uid), core/registry.py OpContext.rng). Two
    DIFFERENT sampling ops sharing a uid draw byte-identical noise —
    silently correlated dropout masks. The one legal duplicate is a
    recompute clone (backward.py _emit_recompute), which shares its
    forward op's uid ON PURPOSE so the re-tossed noise matches."""
    groups: Dict[int, List[OpSite]] = {}
    for site in iter_ops(program):
        if _needs_rng(site.op.type):
            groups.setdefault(site.op._uid, []).append(site)
    for uid, sites in groups.items():
        if len(sites) < 2:
            continue
        types = {s.op.type for s in sites}
        originals = [s for s in sites
                     if not _is_recompute_clone(s.op)]
        if len(types) == 1 and len(originals) <= 1:
            continue  # forward op + its recompute clones: intended
        site = sites[1]
        yield _diag_at(
            "PTA030", ERROR, site,
            f"{len(sites)} sampling ops share uid {uid} "
            f"(types {sorted(types)}, anchors "
            f"{[s.anchor() for s in sites]}); their PRNG salts "
            f"collide and they draw identical noise",
            hint="ops cloned outside Program.clone/recompute must "
                 "re-derive or preserve _uid correctly (see "
                 "Operator.__init__)")


def check_clone_uids(src: Program, cloned: Program) -> List[Diagnostic]:
    """PTA031: verify a Program.clone (or any structural copy)
    preserved `_uid` on sampling ops — a clone that re-derives uids
    breaks fwd/bwd noise parity for programs sharing a scope with the
    source (CLAUDE.md architecture invariant). Ops are matched by
    (type, output names) signature since for_test clones prune ops."""
    out: List[Diagnostic] = []
    src_uids: Dict[tuple, int] = {}
    for site in iter_ops(src):
        if _needs_rng(site.op.type):
            sig = (site.op.type, tuple(site.op.output_arg_names))
            src_uids.setdefault(sig, site.op._uid)
    for site in iter_ops(cloned):
        if not _needs_rng(site.op.type):
            continue
        sig = (site.op.type, tuple(site.op.output_arg_names))
        want = src_uids.get(sig)
        if want is not None and site.op._uid != want:
            out.append(_diag_at(
                "PTA031", ERROR, site,
                f"cloned sampling op {site.op.type!r} has uid "
                f"{site.op._uid} but the source op (same outputs "
                f"{list(site.op.output_arg_names)}) has uid {want}: "
                f"the clone draws DIFFERENT noise",
                hint="clones must copy op._uid (Program.clone does; "
                     "custom passes must too)"))
    return out


# ---------------------------------------------------------------------------
# PTA040: recompute clones rooted in optimization_barrier.
# ---------------------------------------------------------------------------
@register_checker("PTA040", "recompute-barrier-rooting")
def check_recompute_barriers(program: Program):
    """Recompute clones (@RECOMP outputs) must read ONLY barriered
    (@BAR) or recomputed (@RECOMP) inputs: a clone reading the
    original forward activation is byte-identical HLO and XLA CSE
    merges it back, silently undoing the memory saving (backward.py
    _emit_recompute). Also verifies every @BAR name is actually
    produced by an optimization_barrier op."""
    barrier_outs = set()
    for site in iter_ops(program):
        if site.op.type == "optimization_barrier":
            barrier_outs.update(site.op.output_arg_names)
    for site in iter_ops(program):
        op = site.op
        if not any(RECOMP_MARK in n for n in op.output_arg_names):
            continue
        for n in op.input_arg_names:
            if n == EMPTY_VAR or RECOMP_MARK in n:
                continue
            if BARRIER_MARK in n:
                if n not in barrier_outs:
                    yield _diag_at(
                        "PTA040", ERROR, site,
                        f"recompute clone reads {n!r} which no "
                        f"optimization_barrier op produces", var=n)
                continue
            yield _diag_at(
                "PTA040", ERROR, site,
                f"recompute clone reads forward var {n!r} directly; "
                f"without an optimization_barrier root XLA CSE merges "
                f"the clone back into the forward op and the "
                f"rematerialization silently vanishes", var=n,
                hint="route out-of-region reads through "
                     "optimization_barrier (backward.py _emit_"
                     "recompute._bar)")


# ---------------------------------------------------------------------------
# PTA050/PTA051: parameter naming across builds.
# ---------------------------------------------------------------------------
@register_checker("PTA050", "auto-param-names")
def check_auto_param_names(program: Program):
    """Auto-generated parameter names (fc_N.w_M ...) come from ONE
    global helper counter: two programs built in different op orders
    assign the SAME name to DIFFERENT parameters, so sharing weights
    by name across separate train/decode builds breaks (CLAUDE.md
    late-r2 learning). Info severity per program — it only bites when
    a second build shares the scope; PTA051 (check_shared_params)
    upgrades it when two programs are actually paired."""
    auto = sorted(n for n in program._parameters
                  if _AUTO_PARAM_RE.search(n))
    if auto:
        sample = ", ".join(auto[:4]) + ("..." if len(auto) > 4 else "")
        yield Diagnostic(
            "PTA050", INFO,
            f"{len(auto)} parameter(s) carry auto-generated names "
            f"({sample}); cross-program weight sharing by these names "
            f"depends on identical build order",
            hint="name parameters explicitly (ParamAttr(name=...)) "
                 "for any model with a separate decode/inference "
                 "build — see models/transformer.py enc{i}_*/dec{i}_*")


def check_cross_model_collision(a: Program,
                                b: Program) -> List[Diagnostic]:
    """PTA100: lint two UNRELATED programs that will be co-resident
    in one process/scope (the multi-tenant serving runtime's model
    zoo, inference/runtime). Unlike PTA051 — where sharing is the
    INTENT and only broken sharing is flagged — here ANY persistable
    name overlap is an ERROR: same name + different shape means one
    model's init/swap clobbers the other (a shape error at best),
    same name + same shape means silent weight aliasing — model B
    quietly serves model A's parameters and every answer is wrong
    with no error anywhere. The aliasing case is the WORSE defect
    (no error ever surfaces), so it must not rank below the loud
    one: both are errors and both fail the --strict gate. Diagnosed
    from the runtime scheduling work (ModelRegistry.load refuses
    colliding co-loads with this check); the fix is per-model name
    prefixes (the runtime zoo's ``{prefix}_fc1.w`` scheme) or
    per-model Scopes.

    Covers ALL persistable vars, not just parameters: batch_norm's
    moving mean/variance are persistables created via
    create_global_variable (never registered in ``_parameters``), and
    two models saved from fresh processes both carry e.g.
    ``batch_norm_0...`` names — a parameters-only intersection stays
    silent on exactly the running-statistics aliasing this check
    exists to catch."""
    out: List[Diagnostic] = []

    def persistables(p: Program):
        vars_by_name = {}
        for v in p.list_vars():
            if getattr(v, "persistable", False):
                vars_by_name.setdefault(v.name, v)
        return vars_by_name

    pa, pb = persistables(a), persistables(b)
    for name in sorted(set(pa) & set(pb)):
        sa = pa[name].shape
        sb = pb[name].shape
        if sa is not None and sb is not None \
                and tuple(sa) != tuple(sb):
            out.append(Diagnostic(
                "PTA100", ERROR,
                f"co-resident models both declare persistable {name!r} "
                f"with DIFFERENT shapes {tuple(sa)} vs {tuple(sb)}: "
                f"loading both into one scope clobbers one of them",
                var=name,
                hint="give each model its own Scope, or prefix its "
                     "parameter names (ParamAttr(name='<model>_...'))"))
        elif sa is None or sb is None:
            out.append(Diagnostic(
                "PTA100", ERROR,
                f"co-resident models both declare persistable {name!r} "
                f"(shape unknown on at least one side): one scope "
                f"would alias or clobber their weights", var=name,
                hint="give each model its own Scope, or prefix its "
                     "parameter names (ParamAttr(name='<model>_...'))"))
        else:
            out.append(Diagnostic(
                "PTA100", ERROR,
                f"co-resident models both declare persistable {name!r} "
                f"at the same shape: one scope would silently ALIAS "
                f"their weights (model B serves model A's "
                f"parameters, no error anywhere)", var=name,
                hint="give each model its own Scope, or prefix its "
                     "parameter names (ParamAttr(name='<model>_...'))"))
    return out


def check_shared_params(a: Program, b: Program) -> List[Diagnostic]:
    """PTA051: lint a (train, inference) program pair that shares
    weights by name through one scope. Shared names with DIFFERENT
    shapes are errors (the share is already broken); shared
    auto-generated names are warnings (one added layer reorders the
    global counter and silently shuffles every weight)."""
    out: List[Diagnostic] = []
    shared = sorted(set(a._parameters) & set(b._parameters))
    for name in shared:
        sa = a._parameters[name].shape
        sb = b._parameters[name].shape
        if sa is not None and sb is not None and tuple(sa) != tuple(sb):
            out.append(Diagnostic(
                "PTA051", ERROR,
                f"programs share parameter {name!r} with mismatched "
                f"shapes {tuple(sa)} vs {tuple(sb)}: scope sharing by "
                f"this name is broken", var=name))
        elif _AUTO_PARAM_RE.search(name):
            out.append(Diagnostic(
                "PTA051", WARNING,
                f"programs share AUTO-generated parameter name "
                f"{name!r}; any build-order divergence re-assigns it "
                f"to a different weight", var=name,
                hint="use explicit ParamAttr names in both builds"))
    return out


# ---------------------------------------------------------------------------
# PTA060: @SEQ_LEN companion declaration/batch consistency.
# ---------------------------------------------------------------------------
SEQ_LEN_SUFFIX = "@SEQ_LEN"


@register_checker("PTA060", "seq-len-companion")
def check_seq_len_companions(program: Program):
    """Padded sequences ride with an int32 [batch] `name@SEQ_LEN`
    companion (layers/sequence.py). Build-time shape probes replace -1
    dims with a probe value, so a program whose data var has a
    CONCRETE batch must declare the companion at the SAME concrete
    batch — a (-1,) companion probes at a different batch and the
    kernel trace fails with an opaque broadcast error (CLAUDE.md
    late-r2 learning). Companions read by ops but declared nowhere
    get a warning (the feed path would KeyError)."""
    written = set()
    declared = set()
    for blk, _ in iter_blocks(program):
        declared.update(blk.vars)
        for op in blk.ops:
            written.update(op.output_arg_names)
    # companions READ by some op but declared in no block: the program
    # expects a feed it never announces (DataFeeder/_check_feed_shape
    # cannot validate it; the trace KeyErrors)
    flagged = set()
    for site in iter_ops(program):
        for n in site.op.input_arg_names:
            if not n.endswith(SEQ_LEN_SUFFIX) or n in declared \
                    or n in written or n in flagged:
                continue
            flagged.add(n)
            yield _diag_at(
                "PTA060", WARNING, site,
                f"op reads sequence-length companion {n!r} which no "
                f"block declares; the feed path cannot validate it "
                f"and the trace will KeyError", var=n,
                hint="declare it (layers.sequence.seq_len_of / "
                     "bind_seq_len) or create the data var explicitly")
    for blk, container in iter_blocks(program):
        for name, var in blk.vars.items():
            if not name.endswith(SEQ_LEN_SUFFIX):
                continue
            if name in written and not var.is_data:
                # produced in-graph (bind_seq_len assign): shape
                # inference rewrites its shape from the producer, so
                # the declared placeholder shape is not a feed contract
                continue
            base = blk._find_var_recursive(name[:-len(SEQ_LEN_SUFFIX)])
            if base is None or base.shape is None:
                continue
            batch = base.shape[0] if len(base.shape) else None
            if batch is None or batch == -1:
                continue
            cshape = var.shape
            if cshape is None or tuple(cshape) != (batch,):
                yield Diagnostic(
                    "PTA060", ERROR,
                    f"companion {name!r} is declared with shape "
                    f"{tuple(cshape) if cshape else None} but its base "
                    f"var has CONCRETE batch {batch}; build-time shape "
                    f"probes will disagree", block_idx=blk.idx,
                    var=name,
                    hint=f"declare the companion at shape ({batch},) "
                         f"(models/machine_translation.py "
                         f"build_decode_program does)")


# ---------------------------------------------------------------------------
# PTA070: host_effect flag completeness (registry-level).
# ---------------------------------------------------------------------------
def check_registry(op_types: Optional[Iterable[str]] = None
                   ) -> List[Diagnostic]:
    """PTA070: every registered kernel whose code references
    io_callback/pure_callback must be flagged host_effect=True —
    otherwise Executor.run_steps lowers it into a device-resident
    lax.scan and its once-per-step host semantics silently break
    (CLAUDE.md r6 'REMEMBER the flag', mechanized). register_op now
    asserts this at registration; this sweep is the belt-and-braces
    for kernels registered before the assert or monkeypatched in."""
    from ..core.registry import registered_ops

    out: List[Diagnostic] = []
    types = list(op_types) if op_types is not None else registered_ops()
    for t in types:
        if not is_registered(t):
            continue
        info = get_op_info(t)
        if info.host_effect:
            continue
        if kernel_bridges_host(info.kernel):
            out.append(Diagnostic(
                "PTA070", ERROR,
                f"op {t!r} kernel references io_callback/pure_callback "
                f"but is registered with host_effect=False; "
                f"Executor.run_steps would scan it on device and break "
                f"its per-step host semantics", op_type=t,
                hint="register with host_effect=True"))
    return out


@register_checker("PTA070", "host-effect-flag")
def check_program_host_effects(program: Program):
    """Registry sweep restricted to the op types this program uses."""
    used = {site.op.type for site in iter_ops(program)}
    for d in check_registry(sorted(used)):
        yield d


# ---------------------------------------------------------------------------
# PTA090: write-only persistables must be carry-declarable.
# ---------------------------------------------------------------------------
@register_checker("PTA090", "write-only-carry")
def check_write_only_carry(program: Program):
    """A persistable var a step program WRITES but never READS (KV
    slots / counters / stats written for the next consumer) does not
    flow through the executor's state-in path: Executor.run_steps and
    PreparedProgram(steps=K) must seed it into the lax.scan carry with
    zeros or the carry structure changes between iterations — the r6
    write-only-carry trap. That zeros slot is declared from the var's
    metadata, so the var must be CARRY-DECLARABLE: a known dtype and a
    concrete shape (no -1 / missing dims). A write-only persistable
    that is not breaks the K-step scan (and its disk-cached
    rehydration) with an opaque tree-structure error deep in jax;
    error severity because the program is one run_steps call away
    from it.

    Reads anywhere count — including inside While/cond sub-blocks,
    whose parent-visible reads surface as the container op's input
    slots — so ordinary read-modify-write state (params, optimizer
    moments, counters) never trips this."""
    read = set()
    for site in iter_ops(program):
        read.update(site.op.input_arg_names)
    blk = program.global_block
    df = analyze_block(blk)
    flagged = set()
    for name in df.writers:
        if name in read or name in flagged or name == EMPTY_VAR:
            continue
        var = blk._find_var_recursive(name)
        if var is None or not var.persistable:
            continue
        problems = []
        if var.dtype is None:
            problems.append("no dtype")
        if var.shape is None:
            problems.append("no declared shape")
        elif any(d is None or d < 0 for d in var.shape):
            problems.append(f"non-concrete shape {tuple(var.shape)}")
        if not problems:
            continue
        flagged.add(name)
        first = df.first_write[name]
        op = blk.ops[first]
        yield Diagnostic(
            "PTA090", ERROR,
            f"persistable {name!r} is write-only in this program but "
            f"not carry-declarable ({'; '.join(problems)}): "
            f"Executor.run_steps / prepare(steps=K) must seed its "
            f"scan-carry slot with zeros of the declared shape/dtype",
            block_idx=blk.idx, op_idx=first, op_type=op.type, var=name,
            hint="declare it with a concrete shape and dtype "
                 "(models/decode_engine._declare_slot_state does), or "
                 "read-modify-write it so it rides state_in")


# ---------------------------------------------------------------------------
# PTA110: shared-pool writes must be provably lane-exclusive.
# ---------------------------------------------------------------------------
# the pool name mark is OWNED by the ownership domain (absint) —
# importing it keeps this sweep and the prover matching the same
# vars (the PTA180/TEL_MARK drifted-literal lesson);
# models/decode_engine.py re-declares the literal only because
# analysis never imports models
from .absint import POOL_MARK  # noqa: E402

# the builder-declared reasons row indices of a shared-pool write
# cannot alias (layers/extras.py masked_pool_write documents all
# three; "cow_dst" is the COW copy's fresh-exclusive destination
# window — the radix/beam branching path)
_POOL_EXCLUSIVE_VIA = ("block_table", "host_indices", "cow_dst")


def _ownership_coverage(program: Program):
    """Op ids of the @POOL write sites the ownership prover covers
    (every pool access absint's converged fixpoint recorded), or None
    when the prover is unavailable for this program — the PTA110
    declaration checker only emits at sites the prover does NOT
    cover, so each incident surfaces exactly once, with the
    proof-carrying PTA191/190/192 diagnostic when one exists (the
    PTA010/PTA130 twin-dedupe pattern applied to ownership)."""
    from . import absint

    try:
        facts = absint.analyze(program)
    except Exception:
        return None
    if not facts.converged:
        return None
    return {id(acc.site.op) for acc in facts.pool_accesses
            if acc.kind == "write"}


@register_checker("PTA110", "shared-pool-write-exclusive")
def check_shared_pool_writes(program: Program):
    """Writes into a SHARED decode KV block pool (persistable vars
    carrying the @POOL name mark — models/decode_engine.py paged
    layout) must be provably lane-exclusive: unlike the per-lane
    dense buffers, a pool cell is not owned by a row index, so an
    aliased or unmasked scatter silently corrupts ANOTHER request's
    KV — generations stay plausible and no error ever surfaces,
    which makes this the nastiest paged-serving failure class.

    Provably exclusive means: the ONE blessed writer op
    (``masked_pool_write``: disjoint one-hot masks, clamped keep
    mask), reading the pool it writes (read-modify-write, so the
    pool rides the executor's state_in path instead of tripping the
    PTA090 write-only-carry trap), carrying the builder's
    ``exclusive_via`` declaration ('block_table' = per-lane blocks
    from the host free-list, 'host_indices' = host-deduplicated
    admission targets), and — for block-table writes — an active-lane
    ``Gate`` so idle/dustbin/paused lanes write nothing.

    Sites the ownership prover covers are left to PTA190/191/192,
    which carry the same ERROR stance plus the provenance PROOF —
    this declaration checker is the fallback for programs the
    fixpoint engine cannot analyze, so the two never double-report
    one incident (the PTA010/PTA130 dedupe pattern)."""
    covered = _ownership_coverage(program)
    for site in iter_ops(program):
        op = site.op
        hit = [n for n in op.output_arg_names if POOL_MARK in n]
        if not hit:
            continue
        if any(isinstance(v, Block) for v in op.attrs.values()):
            # container ops (while/cond) surface their sub-blocks'
            # writes as their own output slots; the actual writer
            # inside the sub-block is what this sweep judges
            continue
        var = op.block._find_var_recursive(hit[0]) \
            if op.block is not None else None
        if var is not None and not var.persistable:
            continue
        if covered is not None and id(op) in covered:
            continue  # the ownership prover judges this site
        name = hit[0]
        if op.type != "masked_pool_write":
            yield _diag_at(
                "PTA110", ERROR, site,
                f"op {op.type!r} writes shared block pool {name!r} "
                f"directly; only masked_pool_write's disjoint one-hot "
                f"scatter is provably lane-exclusive — anything else "
                f"is the silent cross-request KV corruption class",
                var=name,
                hint="route the write through layers.masked_pool_"
                     "write(pool, new, index, gate, exclusive_via=...)")
            continue
        if name not in op.input_arg_names:
            yield _diag_at(
                "PTA110", ERROR, site,
                f"masked_pool_write writes {name!r} without reading "
                f"it: the keep-mask read-modify-write is what "
                f"preserves other lanes' cells (and keeps the pool "
                f"on the state_in path — see PTA090)", var=name)
            continue
        via = op.attrs.get("exclusive_via")
        if via not in _POOL_EXCLUSIVE_VIA:
            yield _diag_at(
                "PTA110", ERROR, site,
                f"masked_pool_write into {name!r} carries "
                f"exclusive_via={via!r}; the builder must declare why "
                f"row indices cannot alias "
                f"({'/'.join(_POOL_EXCLUSIVE_VIA)})", var=name)
            continue
        if via == "block_table" and not op.inputs.get("Gate"):
            yield _diag_at(
                "PTA110", ERROR, site,
                f"block-table write into {name!r} has no Gate input: "
                f"idle/dustbin/paused lanes (active=0) would scatter "
                f"through stale table rows into blocks other lanes "
                f"own", var=name,
                hint="pass gate=cast(active, 'float32')")


# ---------------------------------------------------------------------------
# PTA120: speculative counter-advance bound.
# ---------------------------------------------------------------------------
@register_checker("PTA120", "spec-advance-bounded")
def check_spec_advance(program: Program):
    """The speculative decode step advances per-lane counters by
    ``spec_accept``'s Advance output, whose <= k+1 clamp (and the
    EOS/room clips) the kernel computes FROM the op's ``k`` and
    ``max_len`` attrs (ops/spec_ops.py). That bound is only provable
    when the attrs agree with the wired tensors: Proposals [R, k],
    DraftProbs [R, k, V], TargetProbs [R, k+1, V] — a builder that
    lies about k mis-slices the acceptance scan and the advance can
    exceed the verified positions. Likewise the accepted-prefix
    ``span_scatter`` consuming the Tokens output must write a
    [R, max_len] buffer, or the room clip bounds writes against the
    WRONG buffer width (per-lane counter corruption / out-of-buffer
    token writes — the silent class the accepted-prefix scatter can
    hide). Grown from the r14 draft-and-verify work."""
    # one walk up front: the Tokens-consumer sweep below would
    # otherwise re-walk the whole program per spec_accept site, and
    # the spec serve programs are the zoo's biggest builds
    spec_sites, scatter_sites = [], []
    for site in iter_ops(program):
        if site.op.type == "spec_accept":
            spec_sites.append(site)
        elif site.op.type == "span_scatter":
            scatter_sites.append(site)
    for site in spec_sites:
        op = site.op
        blk = op.block
        k = op.attrs.get("k")
        max_len = op.attrs.get("max_len")
        if not isinstance(k, int) or k < 0:
            yield _diag_at(
                "PTA120", ERROR, site,
                f"spec_accept carries k={k!r}; the advance bound "
                f"needs a static k >= 0")
            continue
        if not isinstance(max_len, int) or max_len < 1:
            yield _diag_at(
                "PTA120", ERROR, site,
                f"spec_accept carries max_len={max_len!r}; the room "
                f"clip needs the real decode-buffer width")
            continue

        def _shape(slot):
            names = op.inputs.get(slot) or []
            if not names or blk is None:
                return None
            v = blk._find_var_recursive(names[0])
            return tuple(v.shape) if v is not None and v.shape \
                else None

        for slot, axis, want in (("Proposals", 1, k),
                                 ("DraftProbs", 1, k),
                                 ("TargetProbs", 1, k + 1)):
            shape = _shape(slot)
            if shape is None or len(shape) <= axis:
                continue
            if shape[axis] != want:
                yield _diag_at(
                    "PTA120", ERROR, site,
                    f"spec_accept attr k={k} disagrees with its "
                    f"{slot} input (shape {shape}, axis {axis} "
                    f"expected {want}): the counter-advance <= k+1 "
                    f"bound is unprovable",
                    var=(op.inputs.get(slot) or [None])[0])
        # the accepted-prefix scatter: every span_scatter fed by this
        # op's Tokens must write a buffer of width max_len
        tok_names = set(op.outputs.get("Tokens") or [])
        if not tok_names:
            continue
        for other in scatter_sites:
            o = other.op
            if not tok_names & set(o.inputs.get("Vals") or []):
                continue
            buf_names = o.inputs.get("X") or []
            v = o.block._find_var_recursive(buf_names[0]) \
                if buf_names and o.block is not None else None
            shape = tuple(v.shape) if v is not None and v.shape \
                else None
            if shape is not None and len(shape) == 2 \
                    and shape[1] != max_len:
                yield _diag_at(
                    "PTA120", ERROR, other,
                    f"accepted-prefix span_scatter writes buffer "
                    f"{buf_names[0]!r} of width {shape[1]} but the "
                    f"producing spec_accept clips room against "
                    f"max_len={max_len}: the advance bound guards "
                    f"the wrong buffer", var=buf_names[0])


# ---------------------------------------------------------------------------
# PTA180: device-telemetry counter contract.
# ---------------------------------------------------------------------------
# the devtel registry owns the mark (single source of truth: a local
# copy drifting from the registry would make PTA180 silently match
# zero vars and unenforce the whole contract)
from ..observability.devtel import TEL_MARK  # noqa: E402


def _rmw_chain_reads(block, site_idx: int, name: str,
                     depth: int = 8) -> bool:
    """Does the value written to ``name`` at ``block.ops[site_idx]``
    derive from a read of ``name``? Direct read on the writing op
    counts (container ops carry the var through their inputs), else a
    bounded backward walk over same-block producers — the RMW idiom
    ``assign(elementwise_add(var, delta), output=var)`` reads the var
    one producer behind the write."""
    ops = block.ops
    op = ops[site_idx]
    if name in op.input_arg_names:
        return True
    producers = {}
    for i, o in enumerate(ops[:site_idx]):
        for out in o.output_arg_names:
            producers[out] = i   # last producer before the write wins
    frontier = [n for n in op.input_arg_names if n != name]
    seen = set(frontier)
    for _ in range(depth):
        nxt = []
        for n in frontier:
            pi = producers.get(n)
            if pi is None:
                continue
            po = ops[pi]
            if name in po.input_arg_names:
                return True
            for m in po.input_arg_names:
                if m not in seen:
                    seen.add(m)
                    nxt.append(m)
        if not nxt:
            return False
        frontier = nxt
    return False


@register_checker("PTA180", "telemetry-counter-contract")
def check_telemetry_counters(program: Program):
    """Device-telemetry counters (persistables carrying the ``@TEL``
    name mark — observability/devtel.py) are the flight recorder's
    only view into a fused admission+burst dispatch, and they ride
    the executor's state paths, so each one must honor the contract
    the measured traps behind PTA020 and PTA090 taught:

    * **declared int64** — an accidentally-float counter silently
      breaks the lax.while_loop / scan carry dtypes under JAX weak
      typing (the PTA020 `increment` promotion class, applied to the
      new subsystem);
    * **concrete declared shape + persistable** — the counter must be
      carry-declarable so `Executor.run_steps` / `prepare(steps=K)`
      can seed its scan slot (the PTA090 class);
    * **read-modify-write at EVERY writing site** — a write whose
      value does not derive from a read of the counter (checked per
      site via the producer chain, not a program-global read set: a
      legitimate RMW bump elsewhere must not whitewash a clobbering
      ``assign(fill_constant, output=var)``) overwrites the
      cumulative total, so the serving layer's per-dispatch deltas go
      negative and every window silently lies. Reads inside While
      bodies surface through the container op's carried inputs, so
      the serve programs' in-loop increments count.

    ERROR severity: a drifted counter poisons the telemetry surface
    with no error anywhere downstream — the defect class this whole
    checker family exists for."""
    written: Dict[str, OpSite] = {}
    clobbered: Dict[str, OpSite] = {}
    for blk, container in iter_blocks(program):
        for i, op in enumerate(blk.ops):
            for n in op.output_arg_names:
                if TEL_MARK not in n:
                    continue
                site = OpSite(blk.idx, i, op, container)
                written.setdefault(n, site)
                if n not in clobbered \
                        and not _rmw_chain_reads(blk, i, n):
                    clobbered[n] = site
    seen = set()
    for blk, _container in iter_blocks(program):
        for name, var in blk.vars.items():
            if TEL_MARK not in name or name in seen:
                continue
            seen.add(name)
            dtype = getattr(var, "_declared_dtype", None) or var.dtype
            dtype_name = np_dtype_name(dtype) if dtype is not None \
                else None
            shape = getattr(var, "_declared_shape", None)
            if shape is None:
                shape = tuple(var.shape) if var.shape is not None \
                    else None
            site = written.get(name)
            problems = []
            if not var.persistable:
                problems.append(
                    "not persistable (it would not ride "
                    "state_in/state_out across dispatches)")
            if dtype_name != "int64":
                problems.append(
                    f"declared dtype {dtype_name or 'unknown'} "
                    f"(must be int64: float counters break while/"
                    f"scan carry dtypes under weak typing)")
            if shape is None or any(d is None or d < 0
                                    for d in shape):
                problems.append(
                    f"non-concrete declared shape "
                    f"{tuple(shape) if shape else None} (must be "
                    f"carry-declarable for the K-step scan)")
            clobber = clobbered.get(name)
            if clobber is not None:
                site = clobber   # anchor the diagnostic at the bad
                #                  write, not just the first one
                problems.append(
                    "written without reading it (the update must be "
                    "read-modify-write — var = var + delta — at "
                    "every site, or per-dispatch deltas go negative)")
            if not problems:
                continue
            msg = (f"telemetry counter {name!r} violates the devtel "
                   f"contract: {'; '.join(problems)}")
            hint = ("declare it through observability/devtel."
                    "counter_specs ([1] int64 persistable) and "
                    "update it with layers.assign(elementwise_add("
                    "var, delta), output=var)")
            if site is not None:
                yield _diag_at("PTA180", ERROR, site, msg, var=name,
                               hint=hint)
            else:
                yield Diagnostic("PTA180", ERROR, msg,
                                 block_idx=blk.idx, var=name,
                                 hint=hint)


# ---------------------------------------------------------------------------
# PTA080: unregistered op types.
# ---------------------------------------------------------------------------
@register_checker("PTA080", "unregistered-op")
def check_registered(program: Program):
    """Every non-plumbing op must have a registered kernel, or the
    Executor raises at compile ('op has no registered kernel') —
    catch it before the jax trace starts."""
    for site in iter_ops(program):
        if site.op.type in _PLUMBING:
            continue
        if not is_registered(site.op.type):
            yield _diag_at(
                "PTA080", ERROR, site,
                f"op type {site.op.type!r} has no registered kernel "
                f"(core/registry.py)",
                hint="register the op or remove it from the program")


# ---------------------------------------------------------------------------
# PTA130/PTA131: the divergence & sharding prover (analysis/absint.py
# abstract interpretation — whole-program fixpoint over divergence
# contexts and the replication lattice).
# ---------------------------------------------------------------------------
def _guard_proof(facts, guards) -> str:
    lines = [g.describe() for g in guards]
    return "; ".join(lines)


@register_checker("PTA130", "divergence-proof-collective")
def check_collective_divergence_proof(program: Program):
    """The PROOF form of PTA010/011: for every collective site, the
    abstract interpreter computes the full guard context (every
    while/cond predicate the site executes under, transitively) and
    classifies each predicate on the replication lattice. A collective
    under ANY traced guard is an ERROR — same stance as PTA010, so
    PTA130's findings are a superset by construction — but the
    diagnostic now carries the proof: a guard PROVEN divergent names
    its divergence source and mint site (the r5 deadlock explained,
    not pattern-matched); an unprovable guard says what is missing;
    a value-uniform guard says which replication assumptions the
    safety would rest on. Scope-dependent collectives (attention/
    switch_moe under cp/ep scopes) mirror PTA011 at WARNING, upgraded
    to ERROR when a guard is proven divergent — under a per-lane/
    per-stage predicate the scoped lowering WILL deadlock."""
    from . import absint

    facts = absint.analyze(program)
    scope_hits: Dict[tuple, list] = {}
    for site, guards in facts.guarded_sites():
        op = site.op
        if _is_collective(op):
            proven = facts.divergent(guards)
            yield _diag_at(
                "PTA130", ERROR, site,
                f"collective op {op.type!r} executes under "
                f"{len(guards)} traced guard(s) "
                f"[{_guard_proof(facts, guards)}] — "
                + ("participants PROVABLY disagree on whether/in "
                   "which order it runs: deadlock" if proven else
                   "collective order under traced control flow "
                   "cannot be verified: hoist it"),
                var=(op.output_arg_names or [None])[0],
                hint="hoist the collective out of the branch and mask "
                     "its input instead (psum of a zeroed "
                     "contribution is the identity)")
        elif op.type in SCOPE_COLLECTIVE_OP_TYPES:
            key = (guards[-1].container_anchor, op.type)
            scope_hits.setdefault(key, []).append((site, guards))
    for (anchor, op_type), entries in sorted(scope_hits.items()):
        site, guards = entries[0]
        proven = facts.divergent(guards)
        sev = ERROR if proven else WARNING
        yield _diag_at(
            "PTA130", sev, site,
            f"{len(entries)} {op_type!r} op(s) under traced guard(s) "
            f"of {anchor} [{_guard_proof(facts, guards)}] lower to "
            f"shard_map collectives under context/expert-parallel "
            f"scopes"
            + (" — and the guard is PROVEN divergent, so the scoped "
               "lowering deadlocks" if proven else
               "; there they become branch-internal collectives "
               "and deadlock"),
            hint=f"keep parallel-scope models' {op_type} ops out of "
                 f"divergent branches, or run this program only "
                 f"outside those scopes")


@register_checker("PTA131", "replicated-in-divergent-context")
def check_replicated_in_divergent_context(program: Program):
    """The r5 trap family, proven from the replication lattice:

    (a) a grad op inside a divergent context producing a gradient for
        a REPLICATED forward input — the transpose of the implicit
        replicated->varying broadcast is a psum, and it lands INSIDE
        the branch: participants on other paths never post it, so the
        program deadlocks. The fix is the r5 `_vary` discipline: cast
        the input varying BEFORE the divergent region
        (absint.mark_divergence_source(v, "vary")) and mask-psum
        after.
    (b) a value carrying an auto-axis sharding annotation
        (absint.mark_sharded / a `sharding_axes` attr) consumed inside
        a divergent context — GSPMD is free to materialize the
        resharding collective at the consumption site, i.e. inside
        the branch (the r6 generalized trap: 1F1B x tp's
        vocab-sharded logits psum).

    ERROR when a guard is PROVEN divergent; WARNING when divergence is
    unprovable; silent when every guard is value-uniform (every mesh
    program instance takes the same path, so implied collectives
    match up — this is exactly what the uniformity proof buys)."""
    from . import absint

    facts = absint.analyze(program)
    for site, guards in facts.guarded_sites():
        if not facts.unproven(guards):
            continue  # all guards proven value-uniform
        sev = ERROR if facts.divergent(guards) else WARNING
        op = site.op
        is_grad = op.type.endswith("_grad") or \
            op.attrs.get("op_role") == "backward"
        if is_grad:
            flagged = set()
            for g in op.output_arg_names:
                if not g.endswith(GRAD_MARK) or g in flagged:
                    continue
                x = g[:-len(GRAD_MARK)]
                if facts.value(x).repl != "replicated":
                    continue  # varying input: the r5 fix was applied
                flagged.add(g)
                yield _diag_at(
                    "PTA131", sev, site,
                    f"grad op {op.type!r} differentiates "
                    f"REPLICATED input {x!r} inside divergent "
                    f"control flow [{_guard_proof(facts, guards)}]: "
                    f"the transpose of the implicit replicated->"
                    f"varying cast is a psum INSIDE the branch — "
                    f"participants on other paths never post it",
                    var=x,
                    hint="make the input varying BEFORE the branch "
                         "(absint.mark_divergence_source(v, 'vary')) "
                         "and mask-psum after — the r5 1F1B fix")
        for n in op.input_arg_names:
            if n == EMPTY_VAR:
                continue
            vf = facts.value(n)
            if vf.sharded is None:
                continue
            yield _diag_at(
                "PTA131", sev, site,
                f"op {op.type!r} consumes {n!r}, which carries the "
                f"auto-axis sharding annotation {vf.sharded} "
                f"(minted at {vf.minted_at}), inside divergent "
                f"control flow [{_guard_proof(facts, guards)}]: "
                f"GSPMD may materialize the resharding collective "
                f"at this site — inside the branch",
                var=n,
                hint="apply the sharding constraint OUTSIDE the "
                     "divergent region (CLAUDE.md r5: ONE "
                     "with_sharding_constraint on the pre-branch "
                     "value)")


GRAD_MARK = "@GRAD"


# ---------------------------------------------------------------------------
# PTA160/PTA161/PTA170: the sharding & resource provers (the sharding
# domain of analysis/absint.py — propagated ShardSpecs, implied
# collectives, and the static per-device memory planner).
# ---------------------------------------------------------------------------
_LOOP_CONTAINERS = ("while", "run_block_if")


def _in_loop(guards) -> bool:
    return any(g.container_type in _LOOP_CONTAINERS for g in guards)


def _event_where(es) -> str:
    out = f"{es.event.kind} over mesh axes {sorted(set(es.event.axes))}"
    if es.event.var:
        out += f" (var {es.event.var!r})"
    return out


@register_checker("PTA160", "sharding-contradiction")
def check_sharding_contradiction(program: Program):
    """Sharding-contradiction / implicit-reshard prover. Two failure
    classes, both read off the propagated spec facts:

    * **conflict** — consumers demand incompatible ShardSpecs for one
      value (an elementwise/concat joining a dim0-dp operand with a
      dim0-tp operand): GSPMD silently reshards one side. WARNING in
      straight-line code (a one-off reshard is a perf bug), ERROR
      under a serve-While / divergent guard (a reshard per tick, or a
      branch-internal collective — the deadlock class).
    * **reshard** — a single value whose layout GSPMD must change at
      this site (a reshape splitting a sharded dim off its major
      position, a producer disagreeing with a pinned annotation —
      the r5 'dp on the pre-reshape dim' trap). Silent in
      straight-line code (the facts record it; the planner prices
      it), ERROR inside a While body or divergent context.
    """
    from . import absint

    facts = absint.analyze(program)
    for es in facts.collective_events:
        if es.event.kind not in ("conflict", "reshard"):
            continue
        hot = _in_loop(es.guards) or facts.divergent(es.guards)
        if es.event.kind == "reshard" and not hot:
            continue  # a recorded fact, not a finding
        sev = ERROR if hot else WARNING
        where = ("inside a serve-While/divergent context "
                 f"[{_guard_proof(facts, es.guards)}]" if es.guards
                 else "in straight-line code")
        yield _diag_at(
            "PTA160", sev, es.site,
            f"sharding {es.event.kind}: {es.event.why} — {where}"
            + ("; GSPMD materializes the reshard collective INSIDE "
               "the loop/branch body, every iteration" if hot
               else ""),
            var=es.event.var,
            hint="apply ONE with_sharding_constraint on the value the "
                 "consumers actually share, OUTSIDE the divergent "
                 "region (CLAUDE.md r5: the post-reshape mb dim, not "
                 "the pre-reshape full-batch dim)")


@register_checker("PTA161", "collective-order-proof")
def check_collective_order(program: Program):
    """Collective-order agreement, proven symbolically: enumerate the
    sequence of collectives — literal collective ops AND the psum/
    allgather/reshard events the sharding domain proves the lowering
    implies — that each mesh coordinate observes, composing with the
    divergence lattice: a collective under a PROVEN-divergent guard
    is observed by the coordinates taking that path and NOT by the
    others, so the two coordinate classes disagree on the collective
    sequence and the program deadlocks (XLA collectives must be
    issued in identical order on every participant). ERROR with the
    divergence source named; WARNING when a guard's divergence is
    unprovable (order agreement cannot be verified).

    The 1F1B x tp rejection (pipeline_1f1b.py's named ValueError) is
    a COROLLARY here: a vocab/row-sharded matmul inside the per-stage
    F/B cond implies a psum over 'tp' under a 'pp_stage_id'-divergent
    guard — exactly the shape this prover rejects, for any future
    lowering, without naming schedules. Literal collective sites
    under guards are already PTA130 errors; this checker reports the
    sharding-IMPLIED events PTA130 cannot see, and carries the full
    observed-sequence enumeration in the diagnostic so the
    disagreement is readable, not asserted."""
    from . import absint

    facts = absint.analyze(program)
    implied = [es for es in facts.collective_events
               if es.event.kind in ("psum", "allgather")]
    if not implied:
        return
    # the symbolic sequence: every collective-like event in walk
    # order, tagged with whether ALL coordinates observe it
    literal = {id(site.op): site for site in facts.sites
               if _is_collective(site.op)}
    seq = []
    for site in facts.sites:
        if id(site.op) in literal:
            g = facts.guards(site.op)
            seq.append((f"{site.op.type}@{site.anchor()}",
                        facts.divergent(g) or facts.unproven(g)))
    for es in implied:
        seq.append((f"implied-{es.event.kind}"
                    f"[{','.join(sorted(set(es.event.axes)))}]"
                    f"@{es.site.anchor()}",
                    facts.divergent(es.guards)
                    or facts.unproven(es.guards)))
    for es in implied:
        if not es.guards or not facts.unproven(es.guards):
            continue  # unguarded / value-uniform: every coord agrees
        divergent = facts.divergent(es.guards)
        sev = ERROR if divergent else WARNING
        srcs = sorted({g.source for g in es.guards
                       if g.fact == absint.VARYING and g.source})
        all_seq = ", ".join(s for s, _ in seq)
        other_seq = ", ".join(s for s, guarded in seq
                              if not guarded) or "(empty)"
        yield _diag_at(
            "PTA161", sev, es.site,
            f"collective-order disagreement: the sharded lowering "
            f"implies a {_event_where(es)} under "
            f"{len(es.guards)} traced guard(s) "
            f"[{_guard_proof(facts, es.guards)}]. "
            + (f"Coordinates where the guard holds observe the "
               f"sequence [{all_seq}]; coordinates differing in "
               f"{srcs} observe [{other_seq}] — participants "
               f"disagree on whether this collective runs: deadlock"
               if divergent else
               "divergence of the guard is unprovable, so order "
               "agreement across mesh coordinates cannot be "
               "verified"),
            var=es.event.var,
            hint="hoist the sharded computation (and its implied "
                 "collective) out of the divergent region and mask "
                 "its input instead — or keep tp-sharded params out "
                 "of per-stage/per-lane branches (the 1F1B x tp "
                 "rejection, derived)")


@register_checker("PTA170", "device-memory-budget")
def check_device_memory_budget(program: Program):
    """Static per-device memory budget: when a program opts in via
    ``absint.set_device_memory_budget(program, bytes)``, the PTA170
    planner (analysis/memplan.py — persistable + feed + temp bytes
    under the propagated ShardSpecs, validated against the XLA
    compiler's own ``compiled.memory_analysis()`` accounting in
    tests/test_memory_plan.py) prices the program per device and an
    over-budget plan becomes an ERROR here instead of a device OOM
    after minutes of compile."""
    from . import absint

    budget = absint.device_memory_budget(program)
    if budget is None:
        return
    facts = absint.analyze(program)
    plan = facts.device_memory_plan()
    total = plan.total_device_bytes
    if total <= budget:
        return
    top = sorted(plan.state + plan.feeds,
                 key=lambda v: -v.device_bytes)[:3]
    biggest = ", ".join(f"{v.name}={v.device_bytes}B" for v in top)
    yield Diagnostic(
        "PTA170", ERROR,
        f"per-device memory plan {total} bytes exceeds the declared "
        f"budget {budget} bytes (state {plan.state_device_bytes} + "
        f"feeds {plan.feed_device_bytes} + temps "
        f"{plan.temp_device_bytes}; largest: {biggest})"
        + (f" on mesh {plan.mesh.describe()}" if plan.mesh else ""),
        hint="shard the largest state over a mesh axis "
             "(absint.mark_sharded with a {dim: axis} placement), "
             "shrink the geometry, or raise the budget")


# ---------------------------------------------------------------------------
# PTA190/PTA191/PTA192: the pool ownership & lifetime prover (the
# ownership domain of analysis/absint.py — symbolic index provenance,
# per-block typestates, and the host allocator's named assumptions).
# ---------------------------------------------------------------------------
def _chain_of(fact) -> str:
    if fact is None or not fact.chain:
        return "(no provenance chain: the value never passed a "\
            "registered index rule or marked source)"
    return " ← ".join(reversed(fact.chain))


def _exclusive_tags(fact):
    from . import absint

    srcs = absint.pool_index_sources()
    return [t for t in (fact.tags if fact else ())
            if t in srcs
            and srcs[t].typestate == absint.TS_EXCLUSIVE]


def _shared_tags(fact):
    from . import absint

    srcs = absint.pool_index_sources()
    return [t for t in (fact.tags if fact else ())
            if t in srcs and srcs[t].typestate == absint.TS_SHARED]


def _gate_ok(fact) -> bool:
    from . import absint

    srcs = absint.pool_index_sources()
    return fact is not None and any(
        t in srcs and srcs[t].typestate == absint.TS_GATE
        for t in fact.tags)


@register_checker("PTA190", "pool-access-provenance")
def check_pool_access_provenance(program: Program):
    """Provenance + in-bounds prover for every ``@POOL`` access the
    ownership domain recorded (reads AND writes):

    * **provenance** — the index must chain to a registered
      host-owned source (``mark_pool_index_source``: block-table
      feeds, host-deduplicated admission targets, refcounted prompt
      refs) or be a trace-time constant (the dustbin row). An index
      of UNKNOWN provenance is an ERROR with the chain printed: a
      device-computed index nobody vouches for is exactly how a lane
      scribbles over another request's KV with no error anywhere.
    * **gate** — a write declared ``exclusive_via='block_table'``
      must be gated by the lane-active mask (a gate whose provenance
      chains to a ``lane_active``-marked source): stale table rows of
      idle/dustbin/paused lanes address blocks other lanes now own.
    * **in-bounds** — when the indexed axis extent is static, the
      index fact's bound must fit it (ERROR when the bound provably
      exceeds the axis; WARNING when no bound is derivable for a
      READ — the write kernel drops out-of-range rows, reads have
      no such net; ERROR for an UNCHECKED read,
      ``paged_decode_attention``, whose kernel copies the blocks its
      table names with no clamp and no fill: this proof is the only
      thing between a stale table entry and a read outside the
      pool)."""
    from . import absint

    facts = absint.analyze(program)
    if not facts.converged:
        return  # PTA110's declaration fallback owns this program
    for acc in facts.pool_accesses:
        if acc.kind == "write" and acc.index_var is None:
            continue  # direct (non-masked_pool_write) writer: PTA191
        fact = acc.index_fact
        if fact is None or (not fact.tags and not fact.const):
            yield _diag_at(
                "PTA190", ERROR, acc.site,
                f"{acc.kind} of shared pool {acc.pool!r} through "
                f"index {acc.index_var!r} of UNKNOWN provenance "
                f"[{_chain_of(fact)}]: no host-owned source vouches "
                f"for these cells", var=acc.pool,
                hint="chain the index to a marked host table "
                     "(absint.mark_pool_index_source) through "
                     "registered index rules "
                     "(analysis/ownership_rules.py), or feed "
                     "host-deduplicated indices")
            continue
        if acc.kind == "write" and acc.gate_var is not None and \
                acc.site.op.attrs.get("exclusive_via") \
                == "block_table" and not _gate_ok(acc.gate_fact):
            # a write with NO Gate input at all is PTA191's finding
            # (one incident, one diagnostic); this judges only the
            # provenance of a gate that exists
            yield _diag_at(
                "PTA190", ERROR, acc.site,
                f"block-table write into {acc.pool!r} is not gated "
                f"by the lane-active mask (gate {acc.gate_var!r}: "
                f"{_chain_of(acc.gate_fact)}): idle/dustbin/paused "
                f"lanes would scatter through stale table rows into "
                f"blocks other lanes own", var=acc.pool,
                hint="gate with the active mask "
                     "(absint.mark_pool_index_source(active, "
                     "'lane_active'); gate=cast(active,'float32'))")
        if acc.unchecked and not fact.const and (
                fact.bound is None or acc.axis_size is None):
            yield _diag_at(
                "PTA190", ERROR, acc.site,
                f"unchecked read of pool {acc.pool!r}: in-bounds is "
                f"unprovable (index {acc.index_var!r} "
                f"[{_chain_of(fact)}]: bound {fact.bound}, pool extent "
                f"{acc.axis_size} blocks) and the kernel neither "
                f"clamps nor fills",
                var=acc.pool,
                hint="declare the host invariant's bound at the mint "
                     "site (mark_pool_index_source(var, tag, "
                     "bound=N)) and give the pool a static shape")
            continue
        if acc.axis_size is not None:
            if fact.bound is not None and fact.bound > acc.axis_size:
                yield _diag_at(
                    "PTA190", ERROR, acc.site,
                    f"{acc.kind} of pool {acc.pool!r}: index bound "
                    f"{fact.bound} exceeds the indexed axis extent "
                    f"{acc.axis_size} [{_chain_of(fact)}]",
                    var=acc.pool,
                    hint="fix the mint-site bound "
                         "(mark_pool_index_source(..., bound=N)) or "
                         "the addressing arithmetic")
            elif fact.bound is None and acc.kind == "read" \
                    and not fact.const:
                yield _diag_at(
                    "PTA190", WARNING, acc.site,
                    f"read of pool {acc.pool!r}: in-bounds is "
                    f"unprovable (no bound derivable for index "
                    f"{acc.index_var!r} [{_chain_of(fact)}]); a "
                    f"gather past the pool end returns clamped "
                    f"garbage silently", var=acc.pool,
                    hint="declare the host invariant's bound at the "
                         "mint site: mark_pool_index_source(var, "
                         "tag, bound=N)")


@register_checker("PTA191", "pool-write-exclusive-proven")
def check_pool_write_exclusive_proven(program: Program):
    """The PROOF form of PTA110: for every shared-pool write the
    ownership domain recorded, prove distinct lanes' writes hit
    disjoint rows — GIVEN the host allocator's disjoint-allocation
    invariant as a NAMED assumption (the ownership seed table entry
    backing the index's provenance tag; property-tested in
    tests/test_block_pool_model.py). The structural PTA110 contract
    (one blessed writer op, read-modify-write, a declared
    ``exclusive_via``, a Gate on block-table writes) is re-enforced
    here so the twin-dedupe loses nothing, and the declaration is
    UPGRADED: ``exclusive_via`` must AGREE with the provenance the
    prover actually derived — a builder declaring 'block_table'
    while wiring host-admission indices (or vice versa) claims an
    invariant nobody is maintaining. Indices mixing two exclusive
    source families are rejected: each family's disjointness is
    per-family; their union proves nothing."""
    from . import absint

    facts = absint.analyze(program)
    if not facts.converged:
        return  # PTA110's declaration fallback owns this program
    srcs = absint.pool_index_sources()
    for acc in facts.pool_accesses:
        if acc.kind != "write":
            continue
        op = acc.site.op
        name = acc.pool
        if op.type != "masked_pool_write":
            yield _diag_at(
                "PTA191", ERROR, acc.site,
                f"op {op.type!r} writes shared block pool {name!r} "
                f"directly; only masked_pool_write's disjoint "
                f"one-hot scatter is provably lane-exclusive — "
                f"anything else is the silent cross-request KV "
                f"corruption class", var=name,
                hint="route the write through layers.masked_pool_"
                     "write(pool, new, index, gate, "
                     "exclusive_via=...)")
            continue
        if name not in op.input_arg_names:
            yield _diag_at(
                "PTA191", ERROR, acc.site,
                f"masked_pool_write writes {name!r} without reading "
                f"it: the keep-mask read-modify-write is what "
                f"preserves other lanes' cells (and keeps the pool "
                f"on the state_in path — see PTA090)", var=name)
            continue
        via = op.attrs.get("exclusive_via")
        if via not in _POOL_EXCLUSIVE_VIA:
            yield _diag_at(
                "PTA191", ERROR, acc.site,
                f"masked_pool_write into {name!r} carries "
                f"exclusive_via={via!r}; the builder must name the "
                f"exclusivity assumption "
                f"({'/'.join(_POOL_EXCLUSIVE_VIA)})", var=name)
            continue
        if via == "block_table" and not op.inputs.get("Gate"):
            yield _diag_at(
                "PTA191", ERROR, acc.site,
                f"block-table write into {name!r} has no Gate input: "
                f"idle/dustbin/paused lanes (active=0) would scatter "
                f"through stale table rows into blocks other lanes "
                f"own", var=name,
                hint="pass gate=cast(active, 'float32')")
            continue
        fact = acc.index_fact
        if fact is None or (not fact.tags and not fact.const):
            continue  # unknown provenance: PTA190's finding
        excl = sorted(set(_exclusive_tags(fact)))
        if len(excl) > 1:
            yield _diag_at(
                "PTA191", ERROR, acc.site,
                f"write into {name!r} mixes exclusive index "
                f"families {excl} [{_chain_of(fact)}]: each "
                f"family's disjointness assumption "
                f"({', '.join(srcs[t].assumption or t for t in excl)}) "
                f"is per-family — their union proves nothing",
                var=name,
                hint="derive the write index from ONE host-owned "
                     "source family")
            continue
        if excl and excl[0] != via:
            src = srcs[excl[0]]
            yield _diag_at(
                "PTA191", ERROR, acc.site,
                f"write into {name!r} declares exclusive_via="
                f"{via!r} but its index provenance chains to "
                f"{excl[0]!r} (assumption "
                f"{src.assumption or 'none'}) "
                f"[{_chain_of(fact)}]: the declaration names an "
                f"invariant nobody is maintaining for these "
                f"indices", var=name,
                hint="fix the declaration or the index wiring; the "
                     "declared via must name the assumption the "
                     "proof actually rests on")


@register_checker("PTA192", "pool-write-while-shared")
def check_pool_write_while_shared(program: Program):
    """Read-only-while-shared: the per-block lifetime lattice is
    ``free → exclusive(lane) → shared(refcount>1) → freed``, and
    WRITES are only legal in the exclusive typestate — exactly the
    copy-on-write contract the radix-tree/beam prefix-sharing work
    needs (ROADMAP), landed BEFORE the feature so COW lowerings
    build on a proven base. An index whose provenance chains to a
    REFCOUNTED source (``prompt_entry_ref``: entries shared across
    lanes with identical prompts) certifies reads only; a write
    through it would mutate KV that OTHER live lanes are attending
    to — generations stay plausible and no error ever surfaces.
    The host half of the bargain (refcount monotonicity, no
    free-while-shared, fresh entries exclusive at refcount==1) is
    the property-tested allocator state machine
    (models/decode_engine.HostBlockPool / PromptPrefixCache,
    tests/test_block_pool_model.py)."""
    from . import absint

    facts = absint.analyze(program)
    if not facts.converged:
        return  # PTA110's declaration fallback owns this program
    srcs = absint.pool_index_sources()
    for acc in facts.pool_accesses:
        if acc.kind != "write":
            continue
        shared = sorted(set(_shared_tags(acc.index_fact)))
        if not shared:
            continue
        descs = "; ".join(
            f"{t}: {srcs[t].description}" for t in shared)
        yield _diag_at(
            "PTA192", ERROR, acc.site,
            f"write into shared pool {acc.pool!r} through index "
            f"{acc.index_var!r} whose provenance chains to "
            f"REFCOUNTED (shared-typestate) source(s) {shared} "
            f"[{_chain_of(acc.index_fact)}]: writes are only legal "
            f"in the exclusive typestate (refcount==1) — this is "
            f"the write-while-shared COW violation ({descs})",
            var=acc.pool,
            hint="copy-on-write first: acquire a FRESH entry "
                 "(PromptPrefixCache.acquire_fresh, refcount==1), "
                 "write through its host-fed index "
                 "(exclusive_via='host_indices'), and repoint the "
                 "lane's ref after the copy")


# ---------------------------------------------------------------------------
# PTA140: declared shape/dtype clobbered by producer inference.
# ---------------------------------------------------------------------------
@register_checker("PTA140", "declared-shape-clobber")
def check_declared_clobbers(program: Program):
    """Build-time shape inference overwrites a var's DECLARED shape/
    dtype with the producer's inferred one, in place (the r10
    incident: assign of a [-1,4] value onto a concretely-declared
    persistable rewrites it to [-1,4] — and every contract hanging
    off the declaration, scan-carry seeding, feed validation, PTA090
    concreteness, silently moves with it). core/registry.py stashes
    the pre-clobber declaration; this checker surfaces the
    disagreements:

    * a persistable/data var declared with a CONCRETE shape whose
      producer re-inferred it differently — ERROR (the declaration
      was a contract; the producer broke it);
    * an integer-declared CONTRACT var (persistable, data, or a
      while/run_block_if carry) whose producer promoted it to float —
      the PTA020 int->float promotion generalized beyond `increment`:
      ERROR when the var is a while carry (the lax.while_loop carry
      dtype breaks), WARNING elsewhere. Arithmetic temps are exempt:
      an int scaled by a float step legitimately becomes float — only
      dtypes some contract hangs off are findings."""
    from . import absint

    clobbers = absint.declared_clobbers(program)
    if not clobbers:
        return
    carried = absint.while_carried_names(program)
    for c in clobbers:
        if c.declared_shape is not None and \
                (c.persistable or c.is_data) and \
                all(d is not None and d >= 0
                    for d in c.declared_shape):
            yield Diagnostic(
                "PTA140", ERROR,
                f"{'persistable' if c.persistable else 'data'} var "
                f"{c.name!r} was DECLARED with concrete shape "
                f"{c.declared_shape} but build-time shape inference "
                f"clobbered it to {c.final_shape} from its producer "
                f"— the declared feed/carry contract silently moved",
                block_idx=c.block_idx, var=c.name,
                hint="make the producer emit the declared shape (a "
                     "static-batch producer pins it — the PTA090 "
                     "test discipline), or declare the var with the "
                     "producer's real shape")
        if c.declared_dtype is not None and \
                _is_int_dtype_str(c.declared_dtype) and \
                c.final_dtype is not None and \
                c.final_dtype.startswith("float") and \
                (c.persistable or c.is_data or c.name in carried):
            sev = ERROR if c.name in carried else WARNING
            yield Diagnostic(
                "PTA140", sev,
                f"var {c.name!r} was DECLARED {c.declared_dtype} but "
                f"its producer promoted it to {c.final_dtype}"
                + (" and it is a while-loop carry: the "
                   "lax.while_loop carry dtype breaks (PTA020 "
                   "generalized)" if sev == ERROR else
                   " (PTA020's int->float promotion, generalized "
                   "beyond `increment`)"),
                block_idx=c.block_idx, var=c.name,
                hint="keep integer state integer: int steps, int "
                     "fill_constants, explicit casts at the float "
                     "boundary")


# ---------------------------------------------------------------------------
# PTA201/PTA202: the liveness domain's program-level provers
# (analysis/liveness.py; PTA200's capacity model is bundle-level and
# lives in check_bundle below).
# ---------------------------------------------------------------------------
@register_checker("PTA200", "admission-capacity-feasibility")
def check_admission_capacity(program: Program):
    """Admission-capacity feasibility: the serving configuration's
    worst-case steady-state resource demand must fit its static
    pools, or admission can wedge forever with no error anywhere.
    Two pools are modeled (analysis/liveness.py): ``HostBlockPool``
    (demand = n_slots lanes x pages(max_out_len) blocks, assuming no
    radix sharing) and ``PromptPrefixCache`` (demand = the declared
    workload's distinct SESSION prompts, which pin one entry each for
    the session lifetime, plus one churn entry when cold traffic
    shares the cache). The deadlock witness is validated against the
    exhaustive bounded explorer in analysis/protomodel.py
    (session_protocol), so "INFEASIBLE" comes with a replayable
    minimal trace, and the serving layer raises the same verdict as
    ``AdmissionInfeasible`` at submit time.

    This checker is BUNDLE-level: the capacity model reads the
    bundle's static shape (n_slots/max_out_len/cache) and declared
    ``workload``, not any one program's IR, so the check runs in
    ``check_bundle`` and this program-level registration exists for
    the catalog/--explain surface.

    Example::

        bundle.workload = {"distinct_session_prompts": 5}
        # cache.n_prompt_entries == 3, sessions never close:
        # every admitted session pins an entry forever; after 3
        # admissions all entries are pinned and unevictable, the
        # 4th distinct prompt waits forever -> PTA200 error

    Suppress with a bundle-level attr
    ``bundle._pta_suppress = (("PTA200", "reason"),)`` — counted in
    the CI baseline's suppressed section, never silent."""
    return ()


@register_checker("PTA201", "release-on-every-exit-path")
def check_release_obligations(program: Program):
    """Every acquire obligation this program exercises must be
    discharged on EVERY declared protocol exit path. An ownership tag
    reaching a ``@POOL`` access names a resource hold (HostBlockPool
    block, PromptPrefixCache entry, radix incref); its
    ``AcquireContract`` (absint.register_acquire_release) declares
    the exit paths — retirement, preemption, abort, invalidate,
    session close, server close, handoff — and the serving layer
    registers the release SITE proving each one
    (absint.register_release_site at the method that implements it).
    A tag with no contract, or a declared exit with no site, is an
    ERROR: an undischarged hold on a rare exit path is exactly how a
    pool drains one leaked block per preemption until admission
    wedges with no error anywhere.

    Example::

        # a builder minting a NEW resource-holding index source
        absint.register_pool_index_source("my_tab", "...",
                                          absint.TS_EXCLUSIVE)
        absint.mark_pool_index_source(tab, "my_tab", bound=N)
        # ...without ALSO registering its liveness contract:
        #   absint.register_acquire_release("my_tab",
        #       acquire="MyPool.alloc", release="MyPool.decref",
        #       exits=("retire", "preempt"), resource="MyPool")
        # and a release site per exit (from the code implementing
        # it):
        #   absint.register_release_site("my_tab", "retire",
        #       "MyServer._free_lane_locked")
        # -> PTA201 error at the first @POOL access the tag reaches

    Suppress with ``_pta_suppress=("PTA201", "why this hold is
    deliberately leaked")`` on the mint-site/access op — counted in
    the CI baseline, never silent."""
    from . import absint, liveness

    facts = absint.analyze(program)
    if not facts.converged:
        return
    ledger = liveness.obligation_ledger(facts)
    if not ledger["unproven"]:
        return
    # anchor each tag's findings at its first pool access so the
    # counted _pta_suppress convention (op-anchored) applies
    anchor_of: Dict[str, OpSite] = {}
    for acc in facts.pool_accesses:
        fact = acc.index_fact
        for t in (fact.tags if fact is not None else ()):
            anchor_of.setdefault(t, acc.site)
    for item in ledger["unproven"]:
        tag = item.split(":", 1)[0]
        site = anchor_of.get(tag)
        msg = (f"unproven release obligation — {item}: a hold with "
               f"an unproven discharge path leaks pool capacity on "
               f"that path until admission wedges")
        hint = ("register the contract/site: absint."
                "register_acquire_release(tag, acquire, release, "
                "exits, resource) beside the mint site, absint."
                "register_release_site(tag, exit, 'Class.method') "
                "from the code that releases")
        if site is not None:
            yield _diag_at("PTA201", ERROR, site, msg, hint=hint)
        else:
            yield Diagnostic("PTA201", ERROR, msg, hint=hint)


@register_checker("PTA202", "while-variant-progress")
def check_while_progress(program: Program):
    """Every While loop must carry a SOUND termination variant
    instead of being trusted by construction: the condition's
    backward slice through the body must contain a positive-step
    ``increment`` counter AND a loop-invariant bound terminal (a data
    feed, a ``fill_constant``, or a parent-block value the body
    cannot write). Serve/burst Whiles (condition producer marked
    ``lane_active_mask``) are held to ERROR — their burst-exit
    disjunct additionally rides the NAMED monotone-mask assumption
    (active lanes only retire within a burst), so the counter term
    alone must bound the loop; other unproven Whiles are WARNING (a
    legal data-dependent loop could still terminate, but nothing
    here proves it).

    Example::

        cond = layers.less_than(counter, limit, cond=cond)  # in body
        # ...with NO layers.increment(counter, 1) in the body:
        # the slice has a bound but no counter -> PTA202 (and a
        # serve While whose body never recomputes its condition at
        # all can only spin -> PTA202 error)

    Suppress with ``_pta_suppress=("PTA202", "reason")`` on the
    while op — counted, never silent."""
    from . import liveness

    for v in liveness.while_variants(program):
        if v.proven:
            continue
        sev = ERROR if v.kind == "serve" else WARNING
        msg = (f"While has no provable termination variant "
               f"({v.detail}); "
               + ("this is a serve/burst loop — an unbounded burst "
                  "holds the dispatch hostage and never returns "
                  "lane results" if v.kind == "serve" else
                  "nothing proves this loop makes progress"))
        hint = ("drive the condition from an increment-stepped "
                "counter compared against a fed/const limit, "
                "recomputed in the body (the decode_engine "
                "_serve_cond pattern)")
        if v.site is not None:
            yield _diag_at("PTA202", sev, v.site, msg, hint=hint)
        else:
            yield Diagnostic("PTA202", sev, msg, hint=hint)


# ---------------------------------------------------------------------------
# PTA150: whole-bundle contracts (DecodeStepBundle as ONE lint unit).
# ---------------------------------------------------------------------------
def _bundle_programs(bundle):
    """(label, program) for every program a DecodeStepBundle ships.
    Duck-typed: analysis stays IR-level and never imports
    models/decode_engine."""
    out = []
    for a, p in sorted(getattr(bundle, "prefills", {}).items()):
        out.append((f"prefill{a}", p))
    for a, p in sorted(getattr(bundle, "hit_prefills", {}).items()):
        out.append((f"hit_prefill{a}", p))
    step = getattr(bundle, "step", None)
    if step is not None:
        out.append(("step", step))
    for key, p in sorted(getattr(bundle, "serves", {}).items(),
                         key=lambda kv: str(kv[0])):
        out.append((f"serve{key}", p))
    return out


def _persistable_decls(program):
    """name -> (shape, dtype) as the BUILDER declared it: the stashed
    pre-clobber declaration (core/registry.py) beats the final
    inferred metadata — e.g. with x64 disabled, inference
    canonicalizes a declared int64 persistable to int32 on every
    program identically, which is not a bundle disagreement."""
    decls = {}
    for blk, _ in iter_blocks(program):
        for name, var in blk.vars.items():
            if not var.persistable or name in decls:
                continue
            shape = getattr(var, "_declared_shape", None)
            if shape is None:
                shape = tuple(var.shape) if var.shape is not None \
                    else None
            dtype = getattr(var, "_declared_dtype", None) or var.dtype
            decls[name] = (shape,
                           dtype.value if dtype is not None else None)
    return decls


def check_bundle(bundle,
                 collect_suppressed: Optional[list] = None
                 ) -> List[Diagnostic]:
    """PTA150 + PTA200: lint a whole DecodeStepBundle as ONE unit.
    The bundle's
    programs are SPECIALIZATIONS over shared scope state — one
    admission flavor per bucket, a standalone step, the fused serves —
    and the serving layer dispatches them interchangeably against the
    same scope, so they must agree on:

    * **cache geometry** — every slot-state var (`_state_specs`) and
      every shared persistable must be declared with IDENTICAL
      shape/dtype in every program that touches it: a serve
      specialization disagreeing with the step program corrupts the
      scope the other programs read (today only pairwise
      `pair_check`s existed; this is the n-way sweep);
    * **counter presence** — the bundle's `state` vars (token buffer,
      step/finished/active masks, spec counters) must be declared in
      the step program and every serve: a specialization missing one
      silently decodes against stale state;
    * **seed derivation** — every sampling/acceptance op that carries
      a `base_seed` attr must carry the SAME value across all
      specializations: the r14 replay contract keys noise purely on
      (base_seed, request seed, position), so a serve specialization
      with a drifted base_seed emits different tokens for the same
      request depending on which program the scheduler happened to
      dispatch;
    * **admission-capacity feasibility** (PTA200, the liveness
      domain): the bundle's static shape must admit a live steady
      state — lane block chains must fit ``HostBlockPool`` and the
      declared session workload's pinned prompts must fit
      ``PromptPrefixCache`` (analysis/liveness.py; the protomodel
      explorer is the oracle). Bundle-level diagnostics have no op
      anchor, so a deliberate witness target suppresses via a
      ``_pta_suppress`` attr ON THE BUNDLE object — counted through
      `collect_suppressed` exactly like op-anchored ones.

    Example (PTA200)::

        bundle.workload = {"distinct_session_prompts": 5}
        # with cache.n_prompt_entries == 3 and sessions that never
        # close: 5 pinned entries can never fit 3 slots -> PTA200
        # error with the session-pinning deadlock witness

    Reference counterpart: op_desc.cc validates ONE program; the
    bundle gate is the capability the whole-block-jit serving path
    needs instead."""
    out: List[Diagnostic] = []
    progs = _bundle_programs(bundle)
    if not progs:
        return out
    specs = dict(getattr(bundle, "_state_specs", {}) or {})
    state = dict(getattr(bundle, "state", {}) or {})

    decls_by_prog = {label: _persistable_decls(p)
                     for label, p in progs}

    # cache geometry: spec agreement + n-way cross-program agreement
    for name, (shape, dt) in sorted(specs.items()):
        want = (tuple(shape), str(np_dtype_name(dt)))
        for label, decls in decls_by_prog.items():
            got = decls.get(name)
            if got is None:
                continue
            got_n = (got[0], np_dtype_name(got[1])
                     if got[1] is not None else None)
            if got_n != want:
                out.append(Diagnostic(
                    "PTA150", ERROR,
                    f"bundle program {label!r} declares slot-state "
                    f"var {name!r} as {got_n} but the bundle's state "
                    f"spec says {want}: the specializations share "
                    f"ONE scope — a geometry disagreement corrupts "
                    f"it", var=name,
                    hint="every specialization must declare slot "
                         "state from the same _slot_state_specs "
                         "table"))
    seen: Dict[str, tuple] = {}
    for label, decls in sorted(decls_by_prog.items()):
        for name, got in sorted(decls.items()):
            if name in specs:
                continue  # already checked against the spec table
            prev = seen.get(name)
            if prev is None:
                seen[name] = (label, got)
            elif prev[1] != got and None not in (prev[1][0], got[0]):
                out.append(Diagnostic(
                    "PTA150", ERROR,
                    f"bundle programs {prev[0]!r} and {label!r} "
                    f"declare shared persistable {name!r} with "
                    f"different shape/dtype ({prev[1]} vs {got}): "
                    f"one scope serves both", var=name))

    # counter presence
    must_have = [(label, p) for label, p in progs
                 if label == "step" or label.startswith("serve")]
    for logical, name in sorted(state.items()):
        for label, _p in must_have:
            if name not in decls_by_prog[label]:
                out.append(Diagnostic(
                    "PTA150", ERROR,
                    f"bundle program {label!r} does not declare the "
                    f"bundle state var {name!r} (logical "
                    f"{logical!r}): it would decode against stale "
                    f"or missing scope state", var=name))

    # seed derivation
    base_seeds: Dict[str, Dict[object, str]] = {}
    for label, p in progs:
        for site in iter_ops(p):
            bs = site.op.attrs.get("base_seed")
            if bs is None:
                continue
            base_seeds.setdefault(site.op.type, {}).setdefault(
                bs, label)
    for op_type, values in sorted(base_seeds.items()):
        if len(values) > 1:
            detail = ", ".join(
                f"{v!r} (first in {label!r})"
                for v, label in sorted(values.items(),
                                       key=lambda kv: str(kv[0])))
            out.append(Diagnostic(
                "PTA150", ERROR,
                f"bundle specializations disagree on {op_type!r} "
                f"base_seed: {detail} — the same logical draw must "
                f"be byte-identical in every specialization (the "
                f"r14 replay contract), so one bundle has ONE "
                f"base_seed",
                hint="derive every specialization's sampling ops "
                     "from the bundle's single SamplingConfig/"
                     "DraftConfig base_seed"))

    # PTA200: admission-capacity feasibility (bundle-level — the
    # capacity model is a property of the bundle's static shape +
    # declared workload, not of any one program)
    from . import liveness as _liveness

    suppress: Dict[str, str] = {}
    raw = getattr(bundle, SUPPRESS_ATTR, None)
    if raw is not None:
        entries = _normalize_suppressions(raw)
        if entries is None:
            out.append(Diagnostic(
                "PTA199", WARNING,
                f"malformed bundle-level {SUPPRESS_ATTR} attr "
                f"{raw!r}; expected (\"PTA0xx\", \"reason\") or a "
                f"list of such pairs — the suppression is IGNORED"))
        else:
            suppress = dict(entries)
    for chk in _liveness.bundle_capacity_checks(bundle):
        if chk.feasible:
            continue
        d = Diagnostic(
            "PTA200", ERROR,
            f"admission-capacity INFEASIBLE for {chk.resource}: "
            f"{chk.witness}", var=chk.resource,
            hint="grow the pool (n_blocks/n_prompt_entries), shrink "
                 "the workload's distinct session prompts, or let "
                 "sessions close (close_session releases the pin); "
                 "serving preflights raise AdmissionInfeasible on "
                 "this config before any request wedges")
        reason = suppress.get("PTA200")
        if reason is not None:
            if collect_suppressed is not None:
                collect_suppressed.append((d, reason))
            continue
        out.append(d)
    return out


def np_dtype_name(dt) -> str:
    """Canonical dtype string for bundle-spec comparison ('int64',
    'float32', ...): accepts numpy dtypes/strings/DataType values.
    Reference counterpart: framework/data_type.h ToDataType's
    proto-enum canonicalization, reduced to numpy names."""
    import numpy as np

    try:
        return np.dtype(dt).name
    except TypeError:
        return str(getattr(dt, "value", dt))
