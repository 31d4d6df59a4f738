"""Abstract interpretation over the Program IR: the divergence &
sharding prover.

Reference counterpart: the reference validates every program in C++
before execution (reference paddle/fluid/framework/op_desc.cc
CheckAttrs/InferShape, operator.cc:975 RunImpl enforcement) but runs
control flow on the HOST, so "is this collective inside a divergent
branch" is not a question its validators can even ask. Here a whole
Block jits into ONE XLA computation and control flow traces into
lax.cond/lax.while_loop — a collective under a predicate that differs
across mesh coordinates deadlocks the chip (the r5 shard_map trap,
re-hit as 1F1B x tp; CLAUDE.md session learnings). The pattern
matcher (checkers.py PTA010/011) catches the lexical shape of that
bug; this module upgrades it to a PROOF: whole-program fixpoint
propagation over three abstract domains, so "this site executes
uniformly" and "this value is replicated across the mesh" become
checkable facts that PR 12's sharded serving lowerings can lean on.

Domains
-------
1. **Divergence contexts** — for every OpSite, the stack of guard
   predicates (while / conditional_block / run_block_if / ifelse
   conditions) the site executes under, each classified by the
   replication fact of its condition value.
2. **Replication lattice** — ``replicated ⊑ varying ⊑ unknown`` per
   value. Seeds: persistables, data vars and constants are
   `replicated` (the single-logical-device build); ops annotated with
   a registered *divergence source* (``divergence_source`` attr —
   lane active masks, pp stage ids, explicit `_vary` casts) or an
   auto-axis sharding annotation (``sharding_axes`` attr) mint
   `varying` values; joins propagate through assign/arith chains and
   through sub-blocks to a fixpoint.
3. **Symbolic shape/dtype** — build-time shape inference clobbers
   declared shapes in place (core/registry.py stashes the original as
   ``_declared_shape``/``_declared_dtype``); `declared_clobbers`
   surfaces declared-vs-producer disagreements (the r10 class) and
   int->float promotions (PTA020 generalized beyond `increment`).
4. **Ownership / index provenance** — symbolic provenance for every
   index reaching a ``@POOL`` read/write (the shared paged-KV pools,
   models/decode_engine.py): a ProvFact tracks which HOST-OWNED index
   sources (block-table feeds, host-deduplicated admission targets,
   refcounted prompt-entry refs — the registered ownership-source
   seed table, each tag carrying a TYPESTATE ``exclusive``/``shared``
   and the named host-allocator assumption that backs it), trace-time
   constants, 0/1 indicators and value BOUNDS a value derives from,
   through the gather/reshape/one-hot-matmul/affine compositions the
   paged lowerings actually use (rules in analysis/ownership_rules.py
   via core.registry.register_index_rule). Checkers PTA190/191/192
   read the recorded PoolAccess facts: provenance+bounds, PROVEN
   lane-exclusive writes (subsuming PTA110's syntactic declaration —
   the ``exclusive_via`` attr survives as the assumption's name), and
   the read-only-while-shared COW contract.

Annotation surface (the seed table)
-----------------------------------
Builders that MINT a predicate that can differ across mesh
coordinates must mark the minting op::

    from paddle_tpu.analysis import absint
    cond = layers.greater_than(live, min_active)
    absint.mark_divergence_source(cond, "lane_active_mask")

New divergence sources (PR 12's sharded lowerings: dp lane shards,
tp/vocab shards) must register a tag first via
``register_divergence_source`` — `mark_divergence_source` refuses
unknown tags so the seed table stays the single source of truth.

Checkers PTA130/131 (checkers.py) read the facts computed here; the
engine itself is pure Python over Program metadata (no jax, no
tracing) and analyzes a whole model program in milliseconds.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

from ..core.program import Block, Operator, Program
from ..core.registry import EMPTY_VAR
from .dataflow import OpSite, iter_blocks, iter_sub_blocks

__all__ = [
    "REPLICATED", "VARYING", "UNKNOWN", "join",
    "DIVERGENCE_ATTR", "SHARDING_ATTR", "SHARDING_DIMS_ATTR",
    "register_divergence_source", "divergence_sources",
    "mark_divergence_source", "mark_sharded",
    "ValueFact", "GuardFact", "ProgramFacts", "analyze",
    "declared_clobbers",
    "ShardSpec", "TOP_SPEC", "REPLICATED_SPEC", "spec_join",
    "MeshConfig", "set_mesh", "mesh_of",
    "CollectiveEvent", "EventSite",
    "set_device_memory_budget", "device_memory_budget",
    # --- the ownership domain ---
    "POOL_MARK", "OWNERSHIP_ATTR", "OWNERSHIP_BOUND_ATTR",
    "TS_EXCLUSIVE", "TS_SHARED", "TS_GATE",
    "OwnershipSource", "register_pool_index_source",
    "pool_index_sources", "mark_pool_index_source",
    "ProvFact", "prov_join", "PoolAccess",
    # --- the liveness domain ---
    "AcquireContract", "register_acquire_release",
    "register_release_site", "acquire_contracts", "release_sites",
]

# --- the replication lattice ------------------------------------------------
REPLICATED, VARYING, UNKNOWN = "replicated", "varying", "unknown"
_ORDER = {REPLICATED: 0, VARYING: 1, UNKNOWN: 2}


def join(a: str, b: str) -> str:
    """Least upper bound: replicated ⊑ varying ⊑ unknown.

    Reference counterpart: none — standard dataflow lattice join.
    """
    return a if _ORDER[a] >= _ORDER[b] else b


# --- the sharding domain ----------------------------------------------------
# A ShardSpec is the abstract placement of ONE value on the mesh: a
# sparse {tensor dim -> mesh axis} mapping (GSPMD/NamedSharding's
# PartitionSpec, made order-free), with two distinguished points:
# REPLICATED_SPEC (empty mapping — every device holds the full value)
# and TOP_SPEC (placements=None — layout UNKNOWN, the explicit ⊤ an
# op without a registered sharding rule degrades to). The sparse
# form is rank-agnostic, so replicated values never need shape
# bookkeeping and the fixpoint join stays O(1).
@dataclass(frozen=True)
class ShardSpec:
    """Abstract mesh placement of one value (see module docstring).

    Reference counterpart: none — the reference shards at runtime via
    transpilers (transpiler/distribute_transpiler.py); a compile-time
    placement lattice is the GSPMD-era capability this module adds.
    """
    placements: Optional[Tuple[Tuple[int, str], ...]] = ()

    @property
    def is_top(self) -> bool:
        return self.placements is None

    @property
    def is_replicated(self) -> bool:
        return self.placements == ()

    def axis_of(self, dim: int) -> Optional[str]:
        if self.placements is None:
            return None
        for d, a in self.placements:
            if d == dim:
                return a
        return None

    def axes(self):
        return () if self.placements is None else tuple(
            a for _, a in self.placements)

    def describe(self) -> str:
        if self.placements is None:
            return "⊤"
        if not self.placements:
            return "replicated"
        return ",".join(f"dim{d}:{a}" for d, a in self.placements)

    @staticmethod
    def of(placements) -> "ShardSpec":
        """Normalize a {dim: axis} dict / iterable of (dim, axis)
        pairs into a canonical (sorted, deduped) ShardSpec."""
        if placements is None:
            return TOP_SPEC
        if isinstance(placements, dict):
            placements = placements.items()
        return ShardSpec(tuple(sorted(
            (int(d), str(a)) for d, a in placements)))


TOP_SPEC = ShardSpec(None)
REPLICATED_SPEC = ShardSpec(())


def spec_join(a: ShardSpec, b: ShardSpec) -> ShardSpec:
    """Lattice join: equal specs meet at themselves, anything else
    goes to ⊤ — a value written with two different placements has no
    single static layout, and pretending otherwise would let the
    memory planner and the order prover reason from a lie."""
    return a if a == b else TOP_SPEC


@dataclass(frozen=True)
class MeshConfig:
    """Named device mesh a program is built against (SNIPPETS.md
    [1]/[3]'s ``Mesh(devices, ("batch", "model"))`` pattern, as
    static metadata): ordered (axis name, size) pairs. Attached to a
    Program via ``set_mesh`` so the planner can turn propagated
    ShardSpecs into per-DEVICE bytes and the provers can name the
    axes a collective spans.

    Reference counterpart: none — reference device placement was
    per-op attrs (framework/op_desc.cc), not a named mesh.
    """
    axes: Tuple[Tuple[str, int], ...]

    @staticmethod
    def make(**axes) -> "MeshConfig":
        return MeshConfig(tuple((str(k), int(v))
                                for k, v in axes.items()))

    def size(self, name: str, default: int = 1) -> int:
        for n, s in self.axes:
            if n == name:
                return s
        return default

    def n_devices(self) -> int:
        n = 1
        for _, s in self.axes:
            n *= s
        return n

    def describe(self) -> str:
        return "x".join(f"{n}={s}" for n, s in self.axes)


def set_mesh(program, mesh: Optional[MeshConfig]) -> None:
    """Attach (or clear) the MeshConfig a program's sharding
    annotations refer to; bumps the version so cached facts refresh."""
    program._mesh_config = mesh
    program._version = getattr(program, "_version", 0) + 1


def mesh_of(program) -> Optional[MeshConfig]:
    return getattr(program, "_mesh_config", None)


def set_device_memory_budget(program, n_bytes: Optional[int]) -> None:
    """Per-program per-DEVICE memory budget in bytes: when set, the
    PTA170 checker turns an over-budget ``device_memory_plan()`` into
    an error diagnostic (the static OOM gate)."""
    program._device_memory_budget = n_bytes
    program._version = getattr(program, "_version", 0) + 1


def device_memory_budget(program) -> Optional[int]:
    return getattr(program, "_device_memory_budget", None)


@dataclass(frozen=True)
class CollectiveEvent:
    """One collective a lowering IMPLIES under the propagated specs
    (not a literal collective op — those are checkers._is_collective):
    kind "psum" (contraction/reduce over a sharded dim), "allgather"
    (gather/consume of a dim-sharded value that must materialize
    fully), "reshard" (GSPMD layout change forced at this site), or
    "conflict" (two consumers/producers demand incompatible specs)."""
    kind: str
    axes: Tuple[str, ...]
    var: Optional[str]
    why: str


@dataclass(frozen=True)
class EventSite:
    """A CollectiveEvent anchored at its op site with the guard stack
    it executes under — the record PTA160/PTA161 read."""
    site: "OpSite"
    guards: tuple
    event: CollectiveEvent


# --- annotation attrs & the divergence-source seed table --------------------
DIVERGENCE_ATTR = "divergence_source"
# optional companion attr: the mesh axes a marked predicate actually
# varies ACROSS (mark_divergence_source(axes=...)). With a MeshConfig
# attached, a mark whose axes are all absent from the mesh is inert —
# the predicate provably cannot differ on a mesh that lacks its axis
# (the tp-sharded serve While: lanes replicated over 'tp', the
# burst-exit predicate varies only across a lane-sharding axis).
# Without a mesh (or without axes) the mark stays unconditionally
# varying — the historical conservative stance.
DIVERGENCE_AXES_ATTR = "divergence_axes"
SHARDING_ATTR = "sharding_axes"
SHARDING_DIMS_ATTR = "sharding_dims"

# tag -> human explanation of WHY values minted under it differ across
# mesh coordinates. This is the seed table the ISSUE/ROADMAP name: a
# new sharded lowering that mints a new predicate family registers its
# tag here (CLAUDE.md conventions) so the prover knows about it.
_DIVERGENCE_SOURCES: Dict[str, str] = {
    "lane_active_mask": (
        "per-lane active/finished masks: once decode lanes shard "
        "across a data-parallel mesh axis, each device sees only its "
        "own lanes' masks — burst-exit predicates derived from them "
        "differ per device"),
    "pp_stage_id": (
        "pipeline-stage coordinate: per-stage predicates (the 1F1B "
        "F/B selector) differ across pp mesh coordinates BY "
        "construction — the r5 deadlock family"),
    "mesh_coord": (
        "a mesh axis index (lax.axis_index analogue): differs across "
        "that axis by definition"),
    "vary": (
        "explicit replicated->varying cast done OUTSIDE divergent "
        "control flow (the r5 `_vary` fix): the value is per-device "
        "from here on, and its grad transpose psum lands at this op, "
        "not inside a branch"),
}


def register_divergence_source(tag: str, description: str) -> None:
    """Add a divergence-source tag to the seed table (idempotent for
    an identical description; refuses silent redefinition).

    Reference counterpart: none — the reference ran control flow on
    the host (reference operators/controlflow/while_op.cc), so a
    cross-device predicate-divergence registry had nothing to gate.
    """
    old = _DIVERGENCE_SOURCES.get(tag)
    if old is not None and old != description:
        raise ValueError(
            f"divergence source {tag!r} already registered with a "
            f"different description; pick a new tag")
    _DIVERGENCE_SOURCES[tag] = description


def divergence_sources() -> Dict[str, str]:
    """The registered seed table, copied. Reference counterpart:
    none (see register_divergence_source)."""
    return dict(_DIVERGENCE_SOURCES)


# --- the ownership domain: pool-index provenance & typestates ---------------
# name mark on SHARED block-pool persistables (models/decode_engine.py
# defines the same literal; analysis stays IR-level and never imports
# models, so the mark is re-declared here as the domain's anchor)
POOL_MARK = "@POOL"

# op attr carrying a mint-site ownership tag (mark_pool_index_source);
# the bound attr carries the host-invariant exclusive upper bound on
# the minted index values (e.g. a block-table entry < n_blocks)
OWNERSHIP_ATTR = "pool_index_source"
OWNERSHIP_BOUND_ATTR = "pool_index_bound"

# typestates of the per-block lifetime lattice
#   free -> exclusive(lane) -> shared(refcount>1) -> freed
# as seen FROM the device program: an index source's typestate says
# what the host allocator guarantees about the blocks/entries it
# addresses at the moment the program runs. TS_GATE is the odd one
# out: not an index source but the active-lane mask a block-table
# write must be gated by (PTA190's gate obligation).
TS_EXCLUSIVE, TS_SHARED, TS_GATE = "exclusive", "shared", "gate"


@dataclass(frozen=True)
class OwnershipSource:
    """One registered pool-index source family: the tag builders mark
    mint sites with, the host typestate it certifies, and the NAMED
    host-allocator assumption the exclusivity proof rests on (the
    property-tested invariant — tests/test_block_pool_model.py).

    Reference counterpart: none — the reference's allocator checks
    are runtime Scope/memory asserts (reference framework/scope.cc);
    a compile-time ownership contract has no analogue there.
    """
    tag: str
    description: str
    typestate: str                  # TS_EXCLUSIVE | TS_SHARED | TS_GATE
    assumption: Optional[str] = None  # named host invariant
    indicator: bool = False         # values provably 0/1 (masks)


# The seed table. The two EXCLUSIVE tags deliberately spell exactly
# like PTA110's ``exclusive_via`` declarations: the prover checks the
# declared via AGREES with the proven provenance, so the old
# declaration survives as the assumption's name (the PTA130/PTA010
# subsumption pattern applied to ownership).
_OWNERSHIP_SOURCES: Dict[str, OwnershipSource] = {}


def register_pool_index_source(tag: str, description: str,
                               typestate: str,
                               assumption: Optional[str] = None,
                               indicator: bool = False) -> None:
    """Add an ownership-source tag to the seed table (idempotent for
    an identical entry; refuses silent redefinition — the
    register_divergence_source contract).

    Reference counterpart: none (see OwnershipSource) — the
    reference's allocator checks are runtime-only."""
    if typestate not in (TS_EXCLUSIVE, TS_SHARED, TS_GATE):
        raise ValueError(
            f"register_pool_index_source: typestate must be one of "
            f"{TS_EXCLUSIVE!r}/{TS_SHARED!r}/{TS_GATE!r}, got "
            f"{typestate!r}")
    entry = OwnershipSource(tag, description, typestate, assumption,
                            indicator)
    old = _OWNERSHIP_SOURCES.get(tag)
    if old is not None and old != entry:
        raise ValueError(
            f"ownership source {tag!r} already registered "
            f"differently; pick a new tag")
    _OWNERSHIP_SOURCES[tag] = entry


def pool_index_sources() -> Dict[str, OwnershipSource]:
    """The registered ownership seed table, copied. Reference
    counterpart: none (see register_pool_index_source)."""
    return dict(_OWNERSHIP_SOURCES)


# the canonical sources every paged lowering uses (models/
# decode_engine.py marks its mint sites with these; the assumption
# names point at the host state machines whose invariants
# tests/test_block_pool_model.py property-tests)
register_pool_index_source(
    "block_table",
    "per-lane block rows the HOST allocator wrote into the fed/"
    "persistable block table: every block in a lane's WRITE-REACHABLE "
    "suffix (table positions >= the lane's resume step page) is "
    "exclusive to it (HostBlockPool refcount==1 between alloc and "
    "free/decref), while radix-shared blocks (refcount>1) appear "
    "only in the read-only prefix BELOW the resume step — so the "
    "step body's act-gated current-position write always lands in "
    "an exclusive block, and distinct lanes' writable rows are "
    "disjoint",
    TS_EXCLUSIVE, assumption="HostBlockPool.alloc-disjoint")
register_pool_index_source(
    "host_indices",
    "host-deduplicated admission targets (prompt-entry slots fed per "
    "admission): the scheduler feeds pairwise-distinct FRESH entries "
    "(PromptPrefixCache.acquire_fresh, refcount==1 at write time) "
    "with padded rows aimed at the dustbin entry",
    TS_EXCLUSIVE, assumption="PromptPrefixCache.fresh-exclusive")
register_pool_index_source(
    "prompt_entry_ref",
    "per-lane prompt-entry refs: entries are REFCOUNTED across lanes "
    "with identical prompts (refcount may exceed 1), so these "
    "indices certify reads only — a write through them is the "
    "write-while-shared COW violation PTA192 rejects",
    TS_SHARED)
register_pool_index_source(
    "lane_active",
    "per-lane active mask (0/1 by the slot-state contract): the gate "
    "a block-table pool write must carry so idle/dustbin/paused "
    "lanes write nothing",
    TS_GATE, indicator=True)
register_pool_index_source(
    "cow_src",
    "COW copy sources: blocks of a radix-SHARED chain "
    "(HostBlockPool refcount>=1, typically >1) the host feeds to "
    "the bundle's cow program — read-legal (the gather side of the "
    "copy), write-ILLEGAL: an index with this tag reaching a pool "
    "write is exactly the write-while-shared violation PTA192 "
    "rejects",
    TS_SHARED)
register_pool_index_source(
    "cow_dst",
    "COW copy destinations: blocks freshly popped from "
    "HostBlockPool.alloc (refcount==1, exclusive) for this copy "
    "dispatch, pairwise-distinct and disjoint from every live "
    "chain; padded rows aim at -1 (out of range: dropped) under gate 0 — "
    "the exclusive write window a lane diverges into when it "
    "branches off a shared prefix",
    TS_EXCLUSIVE, assumption="HostBlockPool.cow-fresh-exclusive")
register_pool_index_source(
    "chunk_cursor",
    "chunked-prefill position cursor (the `chunk_pos` feed): the "
    "host walks it 0, C, 2C, ... < seq_len across ONE prompt whose "
    "entry stays fresh-exclusive (refcount==1, unpublished) for the "
    "whole multi-phase prefill — it selects POSITIONS inside that "
    "exclusive entry's staging/cross rows, never a pool row, so "
    "every write it parameterizes stays inside the host_indices "
    "exclusivity window",
    TS_EXCLUSIVE, assumption="PromptPrefixCache.fresh-exclusive")


# --- the liveness domain: acquire/release obligation contracts ---------------
# Where an OwnershipSource certifies what a minted index MEANS, an
# AcquireContract declares the OBLIGATION minting through that tag
# creates: the host call that takes the hold, the host call that
# discharges it, and the exhaustive set of protocol exit paths the
# discharge must be proven on. The contract store lives in
# core/registry.py beside the sharding/index rule stores; this module
# owns validation (tags must exist in the ownership seed table and
# must not be gates — a 0/1 mask is not a resource) so a typo'd tag
# fails at import, not as a silently-empty ledger.
@dataclass(frozen=True)
class AcquireContract:
    """One acquire/release obligation family for a resource tag.

    ``acquire``/``release`` name the host calls ("Class.method") that
    mint and discharge the hold; ``exits`` is the exhaustive tuple of
    protocol exit paths on which PTA201 requires a registered release
    site; ``resource`` names the allocator machine the hold draws
    from (the protomodel/PTA200 capacity pool it counts against).

    Reference counterpart: none — the reference discharges at runtime
    via scoped GC (reference framework/executor.cc Scope teardown); a
    static per-exit-path obligation has no analogue there.
    """
    tag: str
    acquire: str
    release: str
    exits: Tuple[str, ...]
    resource: str


def register_acquire_release(tag: str, acquire: str, release: str,
                             exits: Iterable[str],
                             resource: str) -> AcquireContract:
    """Register the liveness contract for ownership tag ``tag``
    (idempotent-identical, raise-on-redefinition — the standing
    registry contract). The tag must already be a registered
    NON-GATE ownership source: contracts attach obligations to real
    resource holds, and registering first forces the mint-site mark
    to exist before anyone claims its release story.

    Reference counterpart: none (see AcquireContract)."""
    from ..core import registry as _registry

    src = _OWNERSHIP_SOURCES.get(tag)
    if src is None:
        raise ValueError(
            f"register_acquire_release: {tag!r} is not a registered "
            f"ownership source (register_pool_index_source first)")
    if src.typestate == TS_GATE:
        raise ValueError(
            f"register_acquire_release: {tag!r} is a gate (0/1 "
            f"mask), not a resource hold — gates carry no obligation")
    exits = tuple(exits)
    if not exits:
        raise ValueError(
            f"register_acquire_release: {tag!r} declares no exit "
            f"paths — an obligation with no discharge path is a "
            f"declared leak, suppress it at the checker instead")
    contract = AcquireContract(tag, acquire, release, exits, resource)
    _registry.register_acquire_contract(tag, contract)
    return contract


def register_release_site(tag: str, exit_path: str,
                          site: str) -> None:
    """Record that ``site`` discharges ``tag``'s obligation on
    ``exit_path``. The contract must exist and must declare the exit
    — a release on an undeclared path means the contract's exit set
    is stale, which is exactly the drift PTA201 exists to catch, so
    it raises here rather than widening silently.

    Reference counterpart: none (see AcquireContract)."""
    from ..core import registry as _registry

    contract = _registry.get_acquire_contract(tag)
    if contract is None:
        raise ValueError(
            f"register_release_site: no acquire contract for "
            f"{tag!r} (register_acquire_release first)")
    if exit_path not in contract.exits:
        raise ValueError(
            f"register_release_site: {tag!r} does not declare exit "
            f"path {exit_path!r} (declared: {contract.exits}); "
            f"extend the contract, don't widen it from a call site")
    _registry.register_release_site(tag, exit_path, site)


def acquire_contracts() -> Dict[str, AcquireContract]:
    """The registered contract table, copied. Reference counterpart:
    none (see AcquireContract)."""
    from ..core import registry as _registry

    return _registry.acquire_contracts()


def release_sites() -> Dict[Tuple[str, str], List[str]]:
    """The registered release-site table, copied. Reference
    counterpart: none (see AcquireContract)."""
    from ..core import registry as _registry

    return _registry.release_sites()


# The canonical contracts for the serving-era tags above. Exit-path
# vocabulary (shared with inference/serving.py's site registrations):
#   retire        normal lane retirement (_free_lane_locked)
#   preempt       recompute-preemption of a live lane
#   abort         abandoned chunked-prefill job teardown
#   invalidate    admission backout / entry invalidation
#   session_close close_session releasing a pinned entry
#   server_close  close() draining lanes, jobs, and handoff refs
#   handoff       disagg prefill->decode ownership transfer
#   cancel        client cancellation / deadline expiry (the r20
#                 front door): a queued, chunking, or LIVE request is
#                 torn down mid-hold at the next burst boundary.
#                 Deadline expiry RIDES this exit (a deadline miss is
#                 a server-initiated cancel — same release path, a
#                 different recorded reason), so one exit covers both
#                 and a tag with no cancel site leaks once per
#                 abandoned request until admission wedges.
register_acquire_release(
    "block_table", acquire="HostBlockPool.alloc",
    release="HostBlockPool.decref",
    exits=("retire", "preempt", "cancel", "server_close"),
    resource="HostBlockPool")
register_acquire_release(
    "host_indices", acquire="PromptPrefixCache.acquire_fresh",
    release="PromptPrefixCache.release",
    exits=("retire", "abort", "invalidate", "cancel",
           "server_close"),
    resource="PromptPrefixCache")
register_acquire_release(
    "prompt_entry_ref", acquire="PromptPrefixCache.acquire_hit",
    release="PromptPrefixCache.release",
    exits=("retire", "session_close", "cancel", "server_close"),
    resource="PromptPrefixCache")
register_acquire_release(
    "cow_src", acquire="RadixBlockTree.acquire",
    release="RadixBlockTree.release",
    exits=("retire", "preempt", "evict", "cancel", "server_close"),
    resource="HostBlockPool")
register_acquire_release(
    "cow_dst", acquire="HostBlockPool.alloc",
    release="HostBlockPool.decref",
    exits=("retire", "preempt", "cancel", "server_close"),
    resource="HostBlockPool")
register_acquire_release(
    "chunk_cursor", acquire="PromptPrefixCache.acquire_fresh",
    release="PromptPrefixCache.release",
    exits=("handoff", "abort", "cancel", "server_close"),
    resource="PromptPrefixCache")


@dataclass(frozen=True)
class ProvFact:
    """Symbolic provenance of one value, as an index candidate.

    ``tags``: ownership-source tags the value derives from (sorted).
    ``const``: every contribution is a trace-time constant.
    ``indicator``: values provably in {0, 1} (comparison mints, the
    active mask, products of indicators).
    ``onehot``: an indicator with AT MOST ONE nonzero in each
    leading-index row's trailing block — ``oh_tail`` records HOW MANY
    trailing axes that block spans (1 at the `equal`-against-a-
    distinct-`range` mint; a last-axis-splitting reshape widens it).
    The extent is load-bearing: a reshape that folds leading axes
    into the block, a concat along it, or a reduce outside it breaks
    the property, and the rules must drop the flag there rather than
    certify a lying bound downstream.
    ``selection``: product of a bounded value with a one-hot — a
    reduce over the one-hot's trailing block picks at most one
    entry, so tags/bound survive the sum (``oh_tail`` carries the
    selector's block extent through to the reduce).
    ``distinct``: constant with pairwise-distinct entries (range /
    arange mints) — the operand that makes an `equal` one-hot.
    ``bound``: exclusive upper bound on the (integer) values when
    provable; None = unbounded/unknown.
    ``nonneg``: values provably >= 0. Mints of negative constants
    produce NO fact at all; this flag exists because subtraction can
    turn a non-negative fact negative, and the sub/mul/scale bound
    arithmetic is only sound over non-negative operands — a rule
    must consult it before reusing a bound (ownership_rules.py).
    ``chain``: mint-site + transform anchors (capped) — the
    provenance chain PTA190 prints on a failed proof.

    Reference counterpart: none — the reference's allocator safety
    was runtime Scope/memory asserts (reference framework/scope.cc);
    a static provenance fact has nothing to mirror there.
    """
    tags: Tuple[str, ...] = ()
    const: bool = False
    indicator: bool = False
    onehot: bool = False
    selection: bool = False
    distinct: bool = False
    bound: Optional[int] = None
    nonneg: bool = True
    oh_tail: int = 0
    chain: Tuple[str, ...] = ()

    def with_step(self, anchor: str, **changes) -> "ProvFact":
        chain = self.chain if len(self.chain) >= 8 \
            else self.chain + (anchor,)
        return ProvFact(**{**self.__dict__, **changes,
                           "chain": chain})

    def typestates(self) -> Tuple[str, ...]:
        return tuple(sorted({
            _OWNERSHIP_SOURCES[t].typestate for t in self.tags
            if t in _OWNERSHIP_SOURCES}))

    def describe(self) -> str:
        bits = []
        if self.tags:
            bits.append("tags=" + ",".join(self.tags))
        if self.const:
            bits.append("const")
        if self.onehot:
            bits.append("one-hot")
        elif self.indicator:
            bits.append("indicator")
        if self.bound is not None:
            bits.append(f"bound<{self.bound}")
        return "{" + " ".join(bits or ["unknown"]) + "}"


def prov_join(a: ProvFact, b: ProvFact) -> ProvFact:
    """Join of two writers of one name: union the tags, keep a
    property only when BOTH sides have it, weaken the bound to the
    larger one (None wins — unbounded).

    Reference counterpart: none — standard dataflow lattice join
    (see ProvFact)."""
    bound = None
    if a.bound is not None and b.bound is not None:
        bound = max(a.bound, b.bound)
    lead = a if a.chain else b
    both_oh = a.onehot and b.onehot
    both_sel = a.selection and b.selection
    # the larger trailing block is the STRONGER claim; the join
    # keeps the weaker (smaller) one
    tail = min(a.oh_tail, b.oh_tail) if (both_oh or both_sel) else 0
    return ProvFact(tuple(sorted(set(a.tags) | set(b.tags))),
                    a.const and b.const,
                    a.indicator and b.indicator,
                    both_oh, both_sel,
                    a.distinct and b.distinct,
                    bound, a.nonneg and b.nonneg, tail, lead.chain)


@dataclass(frozen=True)
class PoolAccess:
    """One read/write of a ``@POOL`` persistable, with the resolved
    index/gate provenance — the record PTA190/191/192 judge.
    ``axis_size`` is the extent of the indexed leading axis (the
    flattened cell count for a write, the gathered view's first dim
    for a read) when statically known — the in-bounds half of
    PTA190's proof compares the index fact's bound against it.

    Reference counterpart: none — the closest thing in the
    reference is the runtime bounds assert inside each kernel
    (reference operators/gather_op.h); a build-time access record
    has no analogue."""
    site: "OpSite"
    guards: tuple
    kind: str                       # "read" | "write"
    pool: str                       # the @POOL var name
    index_var: Optional[str]
    index_fact: Optional[ProvFact]
    gate_var: Optional[str] = None
    gate_fact: Optional[ProvFact] = None
    axis_size: Optional[int] = None
    # the reader neither clamps nor fills (paged_decode_attention):
    # an index PTA190 cannot bound is an error there, not a warning
    unchecked: bool = False


def _producer_op(var) -> Optional[Operator]:
    """Most recent op writing `var` (searched from the var's program,
    current block first — the helper is called right after the layer
    call appends the producer)."""
    name = getattr(var, "name", var)
    blk = getattr(var, "block", None)
    program = blk.program if blk is not None else None
    if program is None:
        return None
    blocks = [program.current_block()] + list(program.blocks)
    seen = set()
    for b in blocks:
        if id(b) in seen:
            continue
        seen.add(id(b))
        for op in reversed(b.ops):
            if name in op.output_arg_names:
                return op
    return None


def mark_divergence_source(var, tag: str, axes=None) -> None:
    """Build-time annotation: mark the producer op of `var` as minting
    a mesh-varying value (tag must be in the registered seed table).
    The abstract interpreter seeds the replication lattice from these
    marks; collectives/grads guarded by values derived from them get
    PROVEN-divergent diagnostics (PTA130/131) instead of pattern
    guesses.

    ``axes`` (optional) names the mesh axes the predicate varies
    ACROSS. When given AND the program carries a MeshConfig
    (``set_mesh``) that has none of those axes at size > 1, the mark
    is inert — the predicate provably cannot differ on a mesh lacking
    its axis, so the guard classifies from its actual inputs instead
    (the tp-sharded serve While's burst-exit predicate: lanes are
    replicated over 'tp'; the predicate varies only across a
    lane-sharding axis, which the tp mesh does not have). Without
    axes, or without a mesh, the mark stays unconditionally varying —
    the conservative historical stance.

    Reference counterpart: none (see register_divergence_source);
    compile-time capability of the whole-block-jit executor.
    """
    if tag not in _DIVERGENCE_SOURCES:
        raise ValueError(
            f"unknown divergence source {tag!r}; register it first "
            f"(absint.register_divergence_source) — known: "
            f"{sorted(_DIVERGENCE_SOURCES)}")
    op = _producer_op(var)
    if op is None:
        raise ValueError(
            f"mark_divergence_source: no producer op found for "
            f"{getattr(var, 'name', var)!r}")
    op.attrs[DIVERGENCE_ATTR] = tag
    if axes is not None:
        op.attrs[DIVERGENCE_AXES_ATTR] = tuple(
            str(a) for a in (axes if isinstance(axes, (list, tuple))
                             else (axes,)))
    blk = getattr(var, "block", None)
    if blk is not None and blk.program is not None:
        blk.program._version += 1  # invalidate cached fingerprints/facts


def _parse_sharding(var, axes):
    """(axis_names, dim_placements|None) from the two accepted forms:

    * ``{dim: axis}`` dict (or (dim, axis) pairs) — the full per-dim
      placement the sharding DOMAIN propagates (negative dims resolve
      against the var's rank when known);
    * a bare axis name or sequence of names — the legacy
      which-axes-touch-this-value form (dims unknown: the replication
      lattice still marks the value varying, the spec domain pins ⊤).
    """
    if isinstance(axes, dict) or (
            isinstance(axes, (list, tuple)) and axes and all(
                isinstance(e, (list, tuple)) and len(e) == 2
                for e in axes)):
        items = axes.items() if isinstance(axes, dict) else axes
        rank = None
        shape = getattr(var, "shape", None)
        if shape is not None:
            rank = len(shape)
        placements = []
        for d, a in items:
            d = int(d)
            if d < 0:
                if rank is None:
                    raise ValueError(
                        f"mark_sharded: negative dim {d} needs a var "
                        f"with a known shape")
                d += rank
            if rank is not None and not (0 <= d < rank):
                raise ValueError(
                    f"mark_sharded: dim {d} out of range for shape "
                    f"{tuple(shape)}")
            placements.append((d, str(a)))
        spec = ShardSpec.of(placements)
        return tuple(a for _, a in spec.placements), spec.placements
    names = tuple(axes) if isinstance(axes, (list, tuple)) else (axes,)
    return tuple(str(a) for a in names), None


def mark_sharded(var, axes) -> None:
    """Mark `var` as carrying an auto-axis sharding annotation (the
    with_sharding_constraint analogue PR 12+'s lowerings emit): GSPMD
    may insert collectives wherever the value is consumed, so the
    prover treats it as varying and PTA131 rejects reads of it inside
    divergent contexts. The dict form ``{dim: axis}`` additionally
    pins the value's ShardSpec for the sharding domain (PTA160/161
    propagation, the PTA170 per-device planner).

    The annotation rides BOTH the producer op (when one exists) and
    the Variable itself: data/feed vars and parameters have no
    producer in an inference/step program, yet sharded INPUTS are
    precisely the sharded-serving entry point — the var-level seed is
    what lets a builder annotate them at all.

    Reference counterpart: the reference annotated placement per op
    (reference framework/op_desc.cc device attrs); GSPMD auto-axis
    annotations whose collectives MOVE have no analogue there.
    """
    names, placements = _parse_sharding(var, axes)
    op = _producer_op(var)
    if op is not None and any(True for _ in iter_sub_blocks(op)):
        # a CONTAINER op (while/cond) lists every carried name as an
        # output — pinning the op would smear this var's placement
        # onto every co-carried output (annotating a while-carried KV
        # buffer must not shard the loop counter). The body's real
        # writer ops are walked anyway; the var-level seed below is
        # what holds the annotation.
        op = None
    if op is None and getattr(var, "block", None) is None:
        raise ValueError(
            f"mark_sharded: {getattr(var, 'name', var)!r} has neither "
            f"a producer op nor a Variable to seed — pass the "
            f"Variable object (layers.data / block.create_var result)")
    if op is not None:
        op.attrs[SHARDING_ATTR] = names
        if placements is not None:
            op.attrs[SHARDING_DIMS_ATTR] = placements
    if getattr(var, "block", None) is not None:
        # var-level seed: producer-less vars (feeds, parameters) AND
        # read-before-write state see the annotation from iteration 1
        var._sharding_axes = names
        var._sharding_dims = placements
    blk = getattr(var, "block", None)
    if blk is not None and blk.program is not None:
        blk.program._version += 1


def mark_pool_index_source(var, tag: str,
                           bound: Optional[int] = None) -> None:
    """Build-time annotation: mark `var` as a HOST-OWNED pool-index
    source of family `tag` (must be in the registered ownership seed
    table). The ownership domain seeds its provenance facts from
    these marks; an index reaching a ``@POOL`` access whose
    provenance does not chain to a marked source (or a trace-time
    constant) is a PTA190 error with the chain printed.

    `bound` is the host invariant's exclusive upper bound on the
    minted values (a block-table entry < n_blocks, a prompt ref <=
    the dustbin entry): it feeds the in-bounds half of PTA190 through
    the affine composition rules.

    Like ``mark_sharded``, the annotation rides the producer op when
    one exists AND the Variable itself — fed tables and persistable
    scope state have no producer in a step program, yet host-written
    tables are precisely the ownership entry point.

    Reference counterpart: none (see OwnershipSource) — the
    reference's allocator checks are runtime-only.
    """
    if tag not in _OWNERSHIP_SOURCES:
        raise ValueError(
            f"unknown ownership source {tag!r}; register it first "
            f"(absint.register_pool_index_source) — known: "
            f"{sorted(_OWNERSHIP_SOURCES)}")
    op = _producer_op(var)
    if op is None and getattr(var, "block", None) is None:
        raise ValueError(
            f"mark_pool_index_source: {getattr(var, 'name', var)!r} "
            f"has neither a producer op nor a Variable to seed — "
            f"pass the Variable object")
    if op is not None:
        op.attrs[OWNERSHIP_ATTR] = tag
        if bound is not None:
            op.attrs[OWNERSHIP_BOUND_ATTR] = int(bound)
    if getattr(var, "block", None) is not None:
        var._ownership_tag = tag
        var._ownership_bound = int(bound) if bound is not None \
            else None
    blk = getattr(var, "block", None)
    if blk is not None and blk.program is not None:
        blk.program._version += 1


# --- facts ------------------------------------------------------------------
@dataclass(frozen=True)
class ValueFact:
    """Abstract value of one var name."""
    repl: str = REPLICATED          # REPLICATED | VARYING | UNKNOWN
    source: Optional[str] = None    # divergence tag when VARYING
    minted_at: Optional[str] = None  # anchor of the minting op
    sharded: Optional[tuple] = None  # sharding axes annotation, if any
    # True when ANY varying ancestry came from a MANUAL divergence
    # source (the registered seed table: pp_stage_id, mesh_coord,
    # lane_active_mask, vary) as opposed to GSPMD auto-axis sharding
    # annotations. STICKY across joins: a predicate mixing sharded
    # values with a stage id is manually divergent no matter which
    # operand's source string survives the join — the GSPMD-uniform
    # guard reclassification must never fire for it.
    manual: bool = False

    def joined(self, other: "ValueFact") -> "ValueFact":
        repl = join(self.repl, other.repl)
        # keep the explanation of whichever side made us varying;
        # between two varying sides, prefer the MANUAL one — its tag
        # names the real divergence source in diagnostics
        lead = self if _ORDER[self.repl] >= _ORDER[other.repl] else other
        if self.repl == VARYING and other.repl == VARYING \
                and lead.source and str(lead.source).startswith(
                    "sharding:"):
            alt = other if lead is self else self
            if alt.manual:
                lead = alt
        return ValueFact(repl, lead.source, lead.minted_at,
                         self.sharded or other.sharded,
                         self.manual or other.manual)


@dataclass(frozen=True)
class GuardFact:
    """One divergent-control-flow predicate a site executes under."""
    container_type: str             # while / conditional_block / ...
    container_anchor: str           # OpSite.anchor() of the container
    cond_var: Optional[str]         # predicate var name
    fact: str                       # replication class of the predicate
    source: Optional[str] = None    # divergence tag when proven varying
    minted_at: Optional[str] = None

    def describe(self) -> str:
        what = f"{self.container_type} guard {self.cond_var!r}"
        if self.fact == VARYING:
            src = _DIVERGENCE_SOURCES.get(self.source or "", "")
            out = (f"{what}: PROVEN divergent across mesh coordinates "
                   f"(source {self.source!r}")
            if self.minted_at:
                out += f", minted at {self.minted_at}"
            out += ")"
            if src:
                out += f" — {src}"
            return out
        if self.fact == UNKNOWN:
            return (f"{what}: divergence UNPROVABLE (predicate derives "
                    f"from values outside the replication facts)")
        if self.source and str(self.source).startswith("sharding:"):
            return (f"{what}: value-uniform — its only varying "
                    f"ancestry is GSPMD auto-axis sharding "
                    f"({self.source}); the partitioner computes "
                    f"predicates consistently on every device, so "
                    f"control flow stays uniform (no manual "
                    f"divergence source in its chain)")
        return (f"{what}: value-uniform under current replication "
                f"facts (facts assume unsharded feeds)")


@dataclass
class ProgramFacts:
    """Result of one fixpoint run over a Program."""
    program: Program
    values: Dict[str, ValueFact] = field(default_factory=dict)
    # id(op) -> guard stack (outermost first); only guarded ops appear
    _guards: Dict[int, Tuple[GuardFact, ...]] = field(
        default_factory=dict)
    # every site, recorded in walk order (guarded or not)
    sites: List[OpSite] = field(default_factory=list)
    iterations: int = 0
    converged: bool = True
    # --- the sharding domain ---
    specs: Dict[str, ShardSpec] = field(default_factory=dict)
    pinned: Dict[str, ShardSpec] = field(default_factory=dict)
    # sharding-implied collectives/reshards, in walk order
    collective_events: List[EventSite] = field(default_factory=list)
    mesh: Optional[MeshConfig] = None
    # --- the ownership domain ---
    prov: Dict[str, ProvFact] = field(default_factory=dict)
    pool_accesses: List[PoolAccess] = field(default_factory=list)

    def value(self, name: str) -> ValueFact:
        return self.values.get(name, ValueFact(REPLICATED))

    def spec(self, name: str) -> ShardSpec:
        got = self.pinned.get(name)
        if got is not None:
            return got
        return self.specs.get(name, REPLICATED_SPEC)

    def nontrivial_specs(self) -> Dict[str, str]:
        """{var: spec description} for every var whose propagated (or
        pinned) spec is not plain-replicated — the snapshot the CI
        baseline's ``sharding_facts`` section drift-gates."""
        out = {}
        for name in set(self.specs) | set(self.pinned):
            s = self.spec(name)
            if not s.is_replicated:
                out[name] = s.describe()
        return out

    def stable_sharding_facts(self) -> Dict[str, str]:
        """``nontrivial_specs`` restricted to STABLY-named vars —
        pinned annotations plus persistable/data vars: auto-generated
        temp names (tmp_N) shift with process-global build order, so
        only the stable surface feeds the CI baseline's
        ``sharding_facts`` drift gate (analysis/baseline.py)."""
        stable = {}
        named = set(self.pinned)
        for blk, _ in iter_blocks(self.program):
            for name, var in blk.vars.items():
                if var.persistable or var.is_data:
                    named.add(name)
        for name, desc in self.nontrivial_specs().items():
            if name in named:
                stable[name] = desc
        if self.mesh is not None and stable:
            stable["@mesh"] = self.mesh.describe()
        return stable

    def device_memory_plan(self, batch: int = 1):
        """Static per-device memory plan for the program under the
        propagated specs (analysis/memplan.py): bytes per persistable
        / feed / temp, totals and per-device totals. `batch`
        substitutes dynamic (-1) dims."""
        from . import memplan

        return memplan.build_plan(self, batch=batch)

    def guards(self, op: Operator) -> Tuple[GuardFact, ...]:
        return self._guards.get(id(op), ())

    def guarded_sites(self) -> Iterable[Tuple[OpSite,
                                              Tuple[GuardFact, ...]]]:
        for site in self.sites:
            g = self._guards.get(id(site.op))
            if g:
                yield site, g

    def divergent(self, guards: Tuple[GuardFact, ...]) -> bool:
        return any(g.fact == VARYING for g in guards)

    def unproven(self, guards: Tuple[GuardFact, ...]) -> bool:
        return any(g.fact in (VARYING, UNKNOWN) for g in guards)

    # --- the ownership surface -----------------------------------------
    def prov_of(self, name: str) -> Optional[ProvFact]:
        return self.prov.get(name)

    def ownership_ledger(self) -> dict:
        """The assumptions/obligations ledger of this program's pool
        accesses: which NAMED host-allocator invariants the proofs
        rest on (with site counts), how many accesses the domain
        proved, and which remain unproven — the CLI's --json
        ownership surface and the CI baseline's raw material."""
        assumptions: Dict[str, int] = {}
        obligations: Dict[str, int] = {}
        proven_w = proven_r = unproven = 0
        for acc in self.pool_accesses:
            fact = acc.index_fact
            tags = fact.tags if fact is not None else ()
            ok = fact is not None and (
                fact.const or (tags and all(
                    t in _OWNERSHIP_SOURCES for t in tags)))
            if not ok:
                unproven += 1
                continue
            if acc.kind == "write":
                proven_w += 1
            else:
                proven_r += 1
            for t in tags:
                src = _OWNERSHIP_SOURCES[t]
                if src.assumption:
                    assumptions[src.assumption] = \
                        assumptions.get(src.assumption, 0) + 1
            if acc.kind == "write" and acc.gate_fact is not None \
                    and any(_OWNERSHIP_SOURCES.get(t) is not None
                            and _OWNERSHIP_SOURCES[t].typestate
                            == TS_GATE
                            for t in acc.gate_fact.tags):
                obligations["gate=lane_active"] = \
                    obligations.get("gate=lane_active", 0) + 1
        return {"assumptions": assumptions,
                "obligations": obligations,
                "proven_writes": proven_w, "proven_reads": proven_r,
                "unproven": unproven}

    def stable_ownership_facts(self) -> Dict[str, str]:
        """Per-pool access summary over STABLE names (the pools are
        persistables), for the CI baseline's drift-gated
        ``ownership_facts`` section: a provenance-rule or annotation
        change that silently re-derives a pool access shows up as a
        value diff, exactly like ``sharding_facts``."""
        per_pool: Dict[str, Dict[str, set]] = {}
        for acc in self.pool_accesses:
            slot = per_pool.setdefault(acc.pool,
                                       {"read": set(), "write": set()})
            fact = acc.index_fact
            if fact is None:
                desc = "unknown"
            elif fact.tags:
                parts = []
                for t in fact.tags:
                    src = _OWNERSHIP_SOURCES.get(t)
                    if src is not None and src.assumption:
                        parts.append(f"{t}⊢{src.assumption}")
                    else:
                        parts.append(t)
                desc = ",".join(parts)
            elif fact.const:
                desc = "const"
            else:
                desc = "unknown"
            if acc.kind == "write" and acc.gate_fact is not None \
                    and acc.gate_fact.tags:
                desc += f" gate={','.join(acc.gate_fact.tags)}"
            slot[acc.kind].add(desc)
        out = {}
        for pool, kinds in per_pool.items():
            bits = []
            for kind in ("write", "read"):
                if kinds[kind]:
                    bits.append(
                        f"{kind}s[{';'.join(sorted(kinds[kind]))}]")
            out[pool] = " ".join(bits)
        ledger = self.ownership_ledger()
        if out and ledger["assumptions"]:
            out["@assumptions"] = ",".join(
                sorted(ledger["assumptions"]))
        return out


# container op type -> input slot holding the branch predicate
# (mirrors checkers.DIVERGENT_CONTAINERS; the kernels are in
# ops/control_flow_ops.py and ops/lod_ops.py)
_COND_SLOTS = {
    "while": "Condition",
    "run_block_if": "Condition",
    "conditional_block": "Condition",
    "ifelse": "Cond",
}

_MAX_ITERS = 16


class _Interp:
    """One fixpoint run. Values live in ONE name->fact map: var names
    are program-unique in practice (sub-block kernels resolve parent
    names by identity), and the join makes any accidental collision
    err toward varying/unknown — conservative, never silently
    uniform. The sharding domain runs in the SAME walk: per-op
    propagation rules (core/registry.py register_sharding_rule) carry
    ShardSpecs forward, annotation pins hold them fixed, and the
    collectives a lowering implies are recorded per site with the
    guard stack they would execute under."""

    def __init__(self, program: Program):
        self.program = program
        self.values: Dict[str, ValueFact] = {}
        self.guards: Dict[int, Tuple[GuardFact, ...]] = {}
        self.sites: List[OpSite] = []
        self.changed = False
        self.mesh = mesh_of(program)
        self.specs: Dict[str, ShardSpec] = {}
        self.events: List[EventSite] = []
        self._top_warned: set = set()
        # --- the ownership domain ---
        self.prov: Dict[str, ProvFact] = {}
        self.pool_accesses: List[PoolAccess] = []
        # pool VIEWS: names that alias a @POOL var through pure
        # view ops (reshape/transpose/...) — a gather off one is a
        # pool READ whose index PTA190 must judge
        self.pool_views: Dict[str, str] = {}
        # var-level ownership pins (mark_pool_index_source on fed/
        # persistable tables): the annotation HOLDS — in-program
        # writers (the active mask's RMW update) never weaken it
        self.prov_pins: Dict[str, ProvFact] = {}
        # spec pins: var-level annotations (mark_sharded on feeds /
        # parameters / state) plus op-level dim annotations — the
        # with_sharding_constraint analogue: the annotated name HOLDS
        # its spec; a producer that disagrees is an implicit reshard
        # fact, not a join to ⊤
        self.pins: Dict[str, ShardSpec] = {}
        for blk, _ in iter_blocks(program):
            for name, var in blk.vars.items():
                tag = getattr(var, "_ownership_tag", None)
                if tag is not None and tag in _OWNERSHIP_SOURCES:
                    src = _OWNERSHIP_SOURCES[tag]
                    self.prov_pins[name] = ProvFact(
                        tags=(tag,), indicator=src.indicator,
                        bound=getattr(var, "_ownership_bound", None),
                        chain=(f"{tag} mark on {name!r}",))
                dims = getattr(var, "_sharding_dims", None)
                axes = getattr(var, "_sharding_axes", None)
                if dims is not None:
                    self.pins[name] = ShardSpec.of(dims)
                elif axes is not None:
                    self.pins.setdefault(name, TOP_SPEC)
                if axes is not None:
                    # var-level annotations (producer-less feeds/
                    # params/state) mint VARYING from iteration 1 —
                    # sharded values invite GSPMD collectives at
                    # their consumers (PTA131's premise)
                    self.values[name] = ValueFact(
                        VARYING, f"sharding:{tuple(axes)}", None,
                        sharded=tuple(axes))
            for op in blk.ops:
                dims = op.attrs.get(SHARDING_DIMS_ATTR)
                if dims is not None:
                    for n in op.output_arg_names:
                        if n != EMPTY_VAR:
                            self.pins.setdefault(n, ShardSpec.of(dims))

    def run(self) -> ProgramFacts:
        # rule families register at first use (import side effect),
        # mirroring how kernels register at ops/ import
        from . import ownership_rules  # noqa: F401
        from . import sharding_rules  # noqa: F401

        iters = 0
        converged = False
        for iters in range(1, _MAX_ITERS + 1):
            self.changed = False
            self.guards.clear()
            self.sites = []
            self.events = []
            self.pool_accesses = []
            self.pool_views = {}
            for blk, container in self._top_blocks():
                self._walk(blk, container, ())
            if not self.changed:
                converged = True
                break
        prov = dict(self.prov)
        prov.update(self.prov_pins)   # pins win (the annotation HOLDS)
        facts = ProgramFacts(self.program, dict(self.values),
                             dict(self.guards), list(self.sites),
                             iterations=iters, converged=converged,
                             specs=dict(self.specs),
                             pinned=dict(self.pins),
                             collective_events=list(self.events),
                             mesh=self.mesh,
                             prov=prov,
                             pool_accesses=list(self.pool_accesses))
        return facts

    def _top_blocks(self):
        """Blocks NOT owned by a container op (the global block plus
        strays); container-owned blocks are walked from their op so
        guard stacks nest correctly."""
        owned = set()
        for blk, _ in iter_blocks(self.program):
            for op in blk.ops:
                for _, sub in iter_sub_blocks(op):
                    owned.add(id(sub))
        for blk, container in iter_blocks(self.program):
            if id(blk) not in owned:
                yield blk, container

    def _value_of(self, name: str, blk: Block) -> ValueFact:
        got = self.values.get(name)
        if got is not None:
            return got
        # unwritten names — persistables, data vars, and undeclared
        # feeds/companions alike — seed REPLICATED: the single-
        # logical-device runtime materializes one value for everyone,
        # and divergence must be proven positively through a marked
        # source (PTA001 flags genuinely missing names)
        return ValueFact(REPLICATED)

    def _set(self, name: str, fact: ValueFact):
        old = self.values.get(name)
        new = fact if old is None else old.joined(fact)
        if old != new:
            self.values[name] = new
            self.changed = True

    def _mark_active(self, op: Operator) -> bool:
        """Whether a divergence-source mark on `op` fires under this
        program's mesh: axes-qualified marks are inert when the
        attached MeshConfig has none of the named axes at size > 1
        (the predicate cannot vary across a mesh that lacks its
        axis); unqualified marks, or no mesh, stay active."""
        axes = op.attrs.get(DIVERGENCE_AXES_ATTR)
        if not axes or self.mesh is None:
            return True
        return any(self.mesh.size(str(a)) > 1 for a in axes)

    def _transfer(self, op: Operator, blk: Block,
                  site: OpSite) -> ValueFact:
        tag = op.attrs.get(DIVERGENCE_ATTR)
        if isinstance(tag, str) and tag and self._mark_active(op):
            return ValueFact(VARYING, tag, site.anchor(), manual=True)
        axes = op.attrs.get(SHARDING_ATTR)
        if axes:
            return ValueFact(VARYING, f"sharding:{tuple(axes)}",
                             site.anchor(), sharded=tuple(axes))
        if any(True for _ in iter_sub_blocks(op)):
            # container op: the body's writes land in the shared name
            # map during the sub-block walk, so joining every DATA
            # input here would smear e.g. a sharded loop input onto
            # the carried guard var and misclassify a genuinely
            # uniform loop as divergent. Only the guard's own
            # divergence flows onto the carried outputs (a value
            # whose definition depends on a divergent predicate is
            # divergent even if each branch writes uniformly).
            fact = ValueFact(REPLICATED)
            cond_slot = _COND_SLOTS.get(op.type)
            if cond_slot is not None:
                for n in op.inputs.get(cond_slot) or []:
                    if n != EMPTY_VAR:
                        fact = fact.joined(self._value_of(n, blk))
            return fact
        fact = ValueFact(REPLICATED)
        for n in op.input_arg_names:
            if n == EMPTY_VAR:
                continue
            fact = fact.joined(self._value_of(n, blk))
        return fact

    # --- the sharding-spec transfer ------------------------------------
    def _spec_of(self, name: str, blk: Block) -> ShardSpec:
        got = self.pins.get(name)
        if got is not None and not got.is_top:
            return got
        got = self.specs.get(name)
        if got is not None:
            return got
        if name in self.pins:           # legacy axes-only annotation
            return TOP_SPEC
        return REPLICATED_SPEC

    def _set_spec(self, name: str, spec: ShardSpec, site: OpSite,
                  guards) -> None:
        pin = self.pins.get(name)
        if pin is not None and not pin.is_top:
            # the annotation HOLDS (with_sharding_constraint): a
            # producer computing a different layout implies GSPMD
            # reshards at the write — record the fact, keep the pin
            if spec != pin and not spec.is_top:
                self.events.append(EventSite(site, guards, CollectiveEvent(
                    "reshard", spec.axes() + pin.axes(), name,
                    f"producer computes {spec.describe()} but "
                    f"{name!r} is pinned {pin.describe()}")))
            return
        old = self.specs.get(name)
        new = spec if old is None else spec_join(old, spec)
        if old != new:
            self.specs[name] = new
            self.changed = True

    def _transfer_specs(self, op: Operator, blk: Block, site: OpSite,
                        guards) -> None:
        from ..core.registry import get_sharding_rule

        if any(True for _ in iter_sub_blocks(op)):
            # container op: carried outputs are written BY the body
            # (walked into the same spec map), so there is nothing to
            # transfer here — and degrading them to ⊤ would clobber
            # the body-propagated layouts and emit a misleading
            # "register a rule for 'while'" warning
            return
        dims = op.attrs.get(SHARDING_DIMS_ATTR)
        if dims is not None:
            spec = ShardSpec.of(dims)
            for n in op.output_arg_names:
                if n != EMPTY_VAR:
                    self._set_spec(n, spec, site, guards)
            return

        def spec_of(name):
            return self._spec_of(name, blk)

        def shape_of(name):
            var = blk._find_var_recursive(name) \
                if blk is not None else None
            if var is None or var.shape is None:
                return None
            return tuple(var.shape)

        rule = get_sharding_rule(op.type)
        if rule is not None:
            out_specs, events = rule(op, spec_of, shape_of, self.mesh)
            for n, s in out_specs.items():
                self._set_spec(n, s, site, guards)
            for ev in events:
                self.events.append(EventSite(site, guards, ev))
            return
        # no rule: replicated-in -> replicated-out is sound (an
        # unannotated op cannot mint sharding); any sharded input
        # degrades every output to the explicit ⊤ spec, warn-once
        touched = [n for n in op.input_arg_names
                   if n != EMPTY_VAR
                   and not self._spec_of(n, blk).is_replicated]
        out = TOP_SPEC if touched else REPLICATED_SPEC
        if touched and op.type not in self._top_warned:
            self._top_warned.add(op.type)
            import warnings

            warnings.warn(
                f"sharding domain: op type {op.type!r} has no "
                f"registered sharding rule but consumes sharded "
                f"value(s) {touched[:3]}; its outputs degrade to the "
                f"⊤ spec. Register a rule via core.registry."
                f"register_sharding_rule (analysis/sharding_rules.py "
                f"has the families) or explicitly declare replication.")
        for n in op.output_arg_names:
            if n != EMPTY_VAR:
                self._set_spec(n, out, site, guards)

    # --- the ownership (index-provenance) transfer ----------------------
    # ops whose output still EXPOSES the pool's cells to a downstream
    # gather (value-preserving views and per-element copies): a miss
    # here would let a pool read escape PTA190 silently, so the set
    # over-approximates — slice/split narrow but still alias pool
    # rows, cast copies values 1:1
    _VIEW_OPS = frozenset({
        "reshape", "reshape2", "transpose", "transpose2",
        "unsqueeze", "unsqueeze2", "squeeze", "squeeze2",
        "slice", "split", "cast",
    })

    def _prov_of(self, name: str) -> Optional[ProvFact]:
        got = self.prov_pins.get(name)
        if got is not None:
            return got
        return self.prov.get(name)

    def _set_prov(self, name: str, fact: Optional[ProvFact]) -> None:
        if fact is None or name in self.prov_pins:
            return
        old = self.prov.get(name)
        new = fact if old is None else prov_join(old, fact)
        if old is not None and new.bound is not None and \
                (old.bound is None or new.bound > old.bound):
            # WIDENING: the bound lattice has infinite ascending
            # chains (a const-seeded RMW counter — assign(add(cnt,
            # 1), output=cnt) in a While — grows its bound by 1
            # every fixpoint iteration, to non-convergence at
            # _MAX_ITERS and a silently-disabled prover). A join
            # that GROWS an existing bound jumps straight to
            # unbounded; single-writer straight-line chains never
            # re-join and keep their exact bounds.
            new = ProvFact(new.tags, new.const, new.indicator,
                           new.onehot, new.selection, new.distinct,
                           None, new.nonneg, new.oh_tail, new.chain)
        if old != new:
            self.prov[name] = new
            self.changed = True

    def _is_pool(self, name: str, blk: Block) -> bool:
        if POOL_MARK not in name:
            return False
        var = blk._find_var_recursive(name)
        return var is None or bool(var.persistable)

    def _transfer_prov(self, op: Operator, blk: Block, site: OpSite,
                       guards) -> None:
        from ..core.registry import get_index_rule

        # mint site: a mark_pool_index_source'd producer
        tag = op.attrs.get(OWNERSHIP_ATTR)
        if isinstance(tag, str) and tag in _OWNERSHIP_SOURCES:
            src = _OWNERSHIP_SOURCES[tag]
            fact = ProvFact(
                tags=(tag,), indicator=src.indicator,
                bound=op.attrs.get(OWNERSHIP_BOUND_ATTR),
                chain=(f"{tag} mint at {site.anchor()}",))
            for n in op.output_arg_names:
                if n != EMPTY_VAR:
                    self._set_prov(n, fact)
            return
        rule = get_index_rule(op.type)
        if rule is not None:
            def shape_of(name):
                var = blk._find_var_recursive(name) \
                    if blk is not None else None
                if var is None or var.shape is None:
                    return None
                return tuple(var.shape)

            out = rule(op, self._prov_of, shape_of)
            for n, f in out.items():
                self._set_prov(n, f)
        # an op without a rule propagates NO provenance: its outputs
        # reach a @POOL access as unknown and PTA190 rejects there

    def _record_pool_access(self, op: Operator, blk: Block,
                            site: OpSite, guards) -> None:
        def _first(slot):
            names = op.inputs.get(slot) or []
            return names[0] if names and names[0] != EMPTY_VAR \
                else None

        if op.type == "masked_pool_write":
            pools = [n for n in op.output_arg_names
                     if self._is_pool(n, blk)]
            idx = _first("Index")
            gate = _first("Gate")
            for pool in pools:
                cells = None
                var = blk._find_var_recursive(pool)
                lead = op.attrs.get("leading_dims", 1)
                if var is not None and var.shape is not None and \
                        isinstance(lead, int) and \
                        0 < lead <= len(var.shape) and all(
                            d is not None and d >= 0
                            for d in var.shape[:lead]):
                    cells = 1
                    for d in var.shape[:lead]:
                        cells *= int(d)
                self.pool_accesses.append(PoolAccess(
                    site, guards, "write", pool, idx,
                    self._prov_of(idx) if idx else None, gate,
                    self._prov_of(gate) if gate else None,
                    axis_size=cells))
            return
        # any OTHER writer of a pool var (container ops surface their
        # sub-blocks' writes and are judged at the inner site)
        for n in op.output_arg_names:
            if self._is_pool(n, blk):
                self.pool_accesses.append(PoolAccess(
                    site, guards, "write", n, None, None))
        # view tracking + gather reads
        if op.type in self._VIEW_OPS:
            roots = [self.pool_views.get(n) or
                     (n if self._is_pool(n, blk) else None)
                     for n in op.input_arg_names if n != EMPTY_VAR]
            root = next((r for r in roots if r is not None), None)
            if root is not None:
                for n in op.output_arg_names:
                    if n != EMPTY_VAR:
                        self.pool_views[n] = root
            return
        if op.type == "paged_decode_attention":
            # the table addresses whole blocks of BOTH pools; whatever
            # var is wired to a pool slot is read unchecked, marked
            # @POOL or not, so the proof is never skipped
            tab = _first("Table")
            bs = op.attrs.get("block_size")
            for slot in ("PoolK", "PoolV"):
                x = _first(slot)
                if x is None:
                    continue
                xvar = blk._find_var_recursive(x)
                blocks = None
                if xvar is not None and xvar.shape and \
                        isinstance(bs, int) and bs > 0 and \
                        xvar.shape[0] is not None and xvar.shape[0] >= 0:
                    blocks = int(xvar.shape[0]) // bs
                self.pool_accesses.append(PoolAccess(
                    site, guards, "read", self.pool_views.get(x) or x,
                    tab, self._prov_of(tab) if tab else None,
                    axis_size=blocks, unchecked=True))
            return
        if op.type in ("gather", "gather_nd"):
            x = _first("X")
            root = self.pool_views.get(x) if x else None
            if root is None and x and self._is_pool(x, blk):
                root = x
            if root is not None:
                idx = _first("Index")
                axis = None
                # gather_nd's last-axis index COMPONENTS address
                # multiple leading axes of X — a single scalar bound
                # cannot be compared against shape[0] (falsely
                # flags correct programs AND falsely passes a
                # too-big trailing component), so its axis stays
                # unknown and only provenance is judged
                xvar = blk._find_var_recursive(x) \
                    if x is not None else None
                if op.type == "gather" and xvar is not None and \
                        xvar.shape and xvar.shape[0] is not None \
                        and xvar.shape[0] >= 0:
                    axis = int(xvar.shape[0])
                self.pool_accesses.append(PoolAccess(
                    site, guards, "read", root, idx,
                    self._prov_of(idx) if idx else None,
                    axis_size=axis))

    def _walk(self, blk: Block, container: Optional[Operator],
              guard_stack: Tuple[GuardFact, ...]):
        for i, op in enumerate(blk.ops):
            site = OpSite(blk.idx, i, op, container)
            self.sites.append(site)
            if guard_stack:
                self.guards[id(op)] = guard_stack
            out_fact = self._transfer(op, blk, site)
            for n in op.output_arg_names:
                if n != EMPTY_VAR:
                    self._set(n, out_fact)
            subs = list(iter_sub_blocks(op))
            if op.type not in ("feed", "fetch"):
                self._transfer_specs(op, blk, site, guard_stack)
                if not subs:
                    self._transfer_prov(op, blk, site, guard_stack)
                    self._record_pool_access(op, blk, site,
                                             guard_stack)
            if not subs:
                continue
            inner = guard_stack
            cond_slot = _COND_SLOTS.get(op.type)
            if cond_slot is not None:
                cond_names = op.inputs.get(cond_slot) or []
                cond = cond_names[0] if cond_names else None
                cf = self._value_of(cond, blk) if cond else \
                    ValueFact(UNKNOWN)
                repl = cf.repl
                if repl == VARYING and not cf.manual \
                        and isinstance(cf.source, str) \
                        and cf.source.startswith("sharding:"):
                    # GSPMD-uniform guard: the predicate's only
                    # varying ancestry is auto-axis sharding
                    # annotations — under GSPMD SPMD semantics the
                    # partitioner computes predicates CONSISTENTLY
                    # on every device (it inserts whatever
                    # collectives the replicated cond needs, outside
                    # any manual divergence), so control flow stays
                    # uniform. Manual sources (pp_stage_id,
                    # mesh_coord, lane_active_mask under a lane-
                    # sharding mesh) never take this path: the
                    # STICKY ValueFact.manual bit survives joins, so
                    # a predicate MIXING sharded values with a
                    # manual source stays proven-divergent even when
                    # the surviving source string is "sharding:*".
                    repl = REPLICATED
                inner = guard_stack + (GuardFact(
                    op.type, site.anchor(), cond, repl,
                    cf.source, cf.minted_at),)
            for _, sub in subs:
                self._walk(sub, op, inner)


def analyze(program: Program) -> ProgramFacts:
    """Run (or fetch the cached) fixpoint analysis for `program`.
    The cache rides ON the program object (`_absint_cache`, keyed by
    `_version` — the `fingerprint()` caching pattern), so PTA130 and
    PTA131 share one run, Pass.apply's version bump invalidates it,
    and a dead Program frees its facts with itself: a global
    facts-by-uid map would pin every analyzed program's whole IR
    (blocks/vars/ops via the recorded OpSites) for the life of a
    serving process under model churn.

    Reference counterpart: reference framework/op_desc.cc CheckAttrs
    validates ONE op; a whole-program fixpoint over divergence/
    replication facts is the jit-era gate with no reference analogue.
    """
    version = getattr(program, "_version", 0)
    cached = getattr(program, "_absint_cache", None)
    if cached is not None and cached[0] == version:
        return cached[1]
    facts = _Interp(program).run()
    try:
        program._absint_cache = (version, facts)
    except AttributeError:
        pass  # exotic program-likes without attribute space
    return facts


# --- symbolic shape/dtype: declared-vs-producer disagreements ---------------
@dataclass(frozen=True)
class DeclClobber:
    """One var whose builder declaration was overwritten in place by
    build-time shape inference (core/registry.py stashes the
    original)."""
    block_idx: int
    name: str
    declared_shape: Optional[tuple]
    final_shape: Optional[tuple]
    declared_dtype: Optional[str]
    final_dtype: Optional[str]
    persistable: bool
    is_data: bool


def declared_clobbers(program: Program) -> List[DeclClobber]:
    """Every var carrying a stashed declaration that differs from its
    final (producer-inferred) shape/dtype, in block order.

    Reference counterpart: reference InferShape (framework/
    shape_inference.h) RAISES on declared-vs-inferred disagreement;
    the in-place Python IR overwrites instead, so the stash+sweep
    recovers the check the reference got for free.
    """
    out: List[DeclClobber] = []
    for blk, _ in iter_blocks(program):
        for name, var in blk.vars.items():
            ds = getattr(var, "_declared_shape", None)
            dd = getattr(var, "_declared_dtype", None)
            if ds is None and dd is None:
                continue
            final_shape = tuple(var.shape) if var.shape is not None \
                else None
            if ds is not None and final_shape == tuple(ds):
                ds = None  # converged back: not a clobber
            dtype_s = var.dtype.value if var.dtype is not None else None
            decl_dtype_s = dd.value if dd is not None else None
            if decl_dtype_s is not None and decl_dtype_s == dtype_s:
                decl_dtype_s = None
            if ds is None and decl_dtype_s is None:
                continue
            out.append(DeclClobber(
                blk.idx, name,
                tuple(ds) if ds is not None else None, final_shape,
                decl_dtype_s, dtype_s,
                bool(var.persistable), bool(var.is_data)))
    return out


def while_carried_names(program: Program) -> set:
    """Names carried through while/run_block_if loops anywhere in the
    program — the set whose dtype stability the lax.while_loop carry
    contract depends on (PTA020/PTA140).

    Reference counterpart: reference operators/controlflow/
    while_op_helper.cc skip-eager-deletion var lists — the carried
    set whose dtype/shape stability the loop depends on.
    """
    carried = set()
    for blk, _ in iter_blocks(program):
        for op in blk.ops:
            if op.type in ("while", "run_block_if"):
                names = op.attrs.get("carried")
                if isinstance(names, (list, tuple)):
                    carried.update(n for n in names
                                   if isinstance(n, str))
    return carried
