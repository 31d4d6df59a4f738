"""Program verifier: static analysis over the Program IR.

The reference validates programs in C++ BEFORE execution
(reference paddle/fluid/framework/op_desc.cc CheckAttrs + each op's
InferShape, operator.cc:975 RunImpl enforcement); the whole-block-jit
Executor here compiles the entire block in one shot and had no
equivalent gate — malformed programs surfaced as multi-hour trace
debugging or a hung compile. This package is that gate, in the
shape of TVM's Relay well-formedness passes / TensorFlow's GraphDef
validators (PAPERS.md): a millisecond-scale diagnostics engine over
the program-as-data IR.

Pieces:

* analysis.dataflow — def-use chains per block + recursive sub-block
  walking (the `_scan_fallback_reason` walk, generalized), with an
  explicit per-op-type registry of sub-block entry-name attrs.
* analysis.absint — the divergence & sharding prover: whole-program
  fixpoint abstract interpretation (divergence contexts, the
  replicated/varying/unknown lattice, declared-vs-producer
  shape/dtype facts, and the SHARDING DOMAIN — per-op ShardSpec
  propagation over the rules registered in core/registry.py, seeded
  from mark_sharded/MeshConfig annotations) feeding
  PTA130/131/140/160/161/170, plus the divergence-source seed table
  sharded lowerings register with.
* analysis.sharding_rules — the per-op-family propagation rules
  (matmul/mul contraction psums, reshape major-dim carry, reduce
  psums, gather allgathers, elementwise conflicts, ...); unknown ops
  degrade to an explicit ⊤ spec with a warn-once.
* analysis.ownership_rules — the pool-index PROVENANCE rules behind
  the ownership domain (absint ProvFact: host-owned source tags with
  typestates, constants, one-hot indicators, value bounds, through
  the affine/selection idioms the paged lowerings use), feeding
  PTA190 (provenance + in-bounds), PTA191 (lane-exclusive write
  PROVEN under the named host-allocator assumption — subsumes
  PTA110's declaration) and PTA192 (read-only-while-shared, the COW
  contract); ops without a rule propagate nothing, so an unproven
  index fails loudly at the pool access.
* analysis.liveness — the protocol LIVENESS domain: admission-
  capacity feasibility (PTA200 — a declarative resource model over
  the host allocators; session-pinned prompt entries against
  never-closing sessions is the canonical infeasible witness),
  release-on-every-exit-path obligation ledgers (PTA201 — every
  acquire contract registered via absint.register_acquire_release
  must name a release site for each declared exit path), and While
  progress variants (PTA202 — a bounded increment-driven counter in
  the condition's backward slice; serve loops additionally carry the
  named monotone-lane_active_mask assumption).
* analysis.protomodel — the exhaustive bounded model checker over
  the HOST allocator typestate machines (HostBlockPool,
  PromptPrefixCache, RadixBlockTree, session pin/unpin): BFS over
  small-bound state spaces with refcount-conservation invariants,
  drain-to-free leak checks, deadlock detection and minimal
  counterexample traces — the oracle PTA200's feasibility predicate
  is validated against (tests/test_protomodel.py grid).
* analysis.memplan — the static per-device memory planner behind
  ``analyze(p).device_memory_plan()`` / CLI ``--memory-plan`` /
  checker PTA170: persistable/feed/temp bytes under the propagated
  specs, validated against ``compiled.memory_analysis()``.
* analysis.checkers — the Checker registry: stable `PTA0xx` codes,
  severity error/warn/info, op/var anchors, fix hints. Every checker
  encodes a REAL incident from CLAUDE.md's session learnings
  (collective-in-divergent-cond deadlocks, int->float while-carry
  promotion, _uid loss, global-counter param names, ...). Bundle-
  level contracts ride `check_bundle` (PTA150); per-site
  suppressions ride the ``_pta_suppress`` op attr (counted,
  surfaced).
* Executor gate — ``FLAGS_static_check={off,warn,strict}`` runs the
  suite before every compile (strict raises EnforceNotMet with the
  diagnostic list).
* CLI — ``python -m paddle_tpu.analysis`` builds and lints every
  program in models/ and benchmark/ (``--strict`` for CI;
  ``--baseline`` diffs the zoo's diagnostic set against the
  committed analysis_baseline.json and fails on any NEW
  error-or-warning — analysis.baseline has the machinery).

Usage::

    from paddle_tpu import analysis
    diags = analysis.run_checks(program)         # all checkers
    errs = [d for d in diags if d.severity == analysis.ERROR]
    analysis.check_shared_params(train_prog, decode_prog)
    analysis.check_clone_uids(prog, prog.clone())
"""
from __future__ import annotations

from typing import List

from . import absint, liveness, protomodel
from .checkers import (Checker, Diagnostic, ERROR, INFO, WARNING,
                       SUPPRESS_ATTR, check_bundle, check_clone_uids,
                       check_cross_model_collision,
                       check_registry, check_shared_params,
                       format_diagnostics, register_checker,
                       registered_checkers, run_checks)
from .dataflow import (BlockDataflow, OpSite, analyze_block,
                       iter_blocks, iter_ops, iter_sub_blocks,
                       register_block_entry_attrs)

__all__ = [
    "Diagnostic", "Checker", "ERROR", "WARNING", "INFO",
    "run_checks", "register_checker", "registered_checkers",
    "check_registry", "check_shared_params", "check_clone_uids",
    "check_cross_model_collision", "check_bundle", "SUPPRESS_ATTR",
    "format_diagnostics", "maybe_check_program", "absint",
    "liveness", "protomodel",
    "BlockDataflow", "OpSite", "analyze_block", "iter_blocks",
    "iter_ops", "iter_sub_blocks", "register_block_entry_attrs",
]

# one gate evaluation per (program uid, version): the Executor calls
# maybe_check_program on every compile, and one program compiles many
# specializations (feed-shape buckets, AMP tokens) — the diagnostics
# only change when the PROGRAM does (Pass.apply bumps _version)
_checked_cache: dict = {}


def maybe_check_program(program) -> List[Diagnostic]:
    """The Executor's pre-compile gate (core/executor.py
    _build_step_fn): honors FLAGS_static_check. off -> no-op;
    warn -> warnings.warn with the error/warning diagnostics;
    strict -> raise EnforceNotMet when any ERROR diagnostic fires."""
    from ..flags import FLAGS

    mode = FLAGS.static_check
    if mode == "off":
        return []
    key = (getattr(program, "_uid", id(program)),
           getattr(program, "_version", 0), mode)
    cached = _checked_cache.get(key)
    if cached is None:
        cached = run_checks(program)
        if len(_checked_cache) > 512:
            _checked_cache.clear()
        _checked_cache[key] = cached
    errors = [d for d in cached if d.severity == ERROR]
    warns = [d for d in cached if d.severity == WARNING]
    if errors and mode == "strict":
        from ..enforce import EnforceNotMet

        raise EnforceNotMet(
            f"FLAGS_static_check=strict: program verifier found "
            f"{len(errors)} error(s):\n"
            + format_diagnostics(errors))
    if errors or warns:
        import warnings

        warnings.warn(
            f"static_check: {len(errors)} error(s), {len(warns)} "
            f"warning(s) in program:\n"
            + format_diagnostics(errors + warns))
    return cached
