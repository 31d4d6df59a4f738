"""Generic dataflow engine over the Program IR.

The reference validates programs in C++ before execution
(reference paddle/fluid/framework/op_desc.cc CheckAttrs/InferShape,
operator.cc:975 RunImpl enforcement); the TPU-native Executor compiles
a whole Block in one shot, so there is no per-op hook to catch a
malformed program — it surfaces as a jax trace error, a wrong number,
or a hung compile. This module computes the structural facts the
checker suite (analysis/checkers.py) reads: def-use chains per block,
recursive sub-block walking (the same Block-attr walk
core/executor.py's _scan_fallback_reason does), and writer/reader
indices with stable op anchors.

Everything here is pure Python over Program/Block/Operator metadata —
no jax, no tracing: a whole model program analyzes in milliseconds.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Tuple

from ..core.program import Block, Operator, Program
from ..core.registry import EMPTY_VAR

__all__ = ["BlockDataflow", "analyze_block", "iter_sub_blocks",
           "iter_blocks", "iter_ops", "OpSite", "block_entry_names",
           "register_block_entry_attrs", "BLOCK_ENTRY_ATTRS"]


@dataclass(frozen=True)
class OpSite:
    """Stable anchor for one op occurrence: (block idx, op position).

    `container` is the op whose Block-typed attr holds this op's block
    (None for ops sitting in a block reached straight from the program
    block list walk), letting checkers distinguish "inside a while
    body" from "top level".
    """
    block_idx: int
    op_idx: int
    op: Operator
    container: Optional[Operator] = None

    def anchor(self) -> str:
        where = f"block {self.block_idx} op {self.op_idx}"
        if self.container is not None:
            where += f" (inside {self.container.type!r})"
        return f"{self.op.type} @ {where}"


@dataclass
class BlockDataflow:
    """Def-use facts for ONE block (sub-blocks are separate analyses).

    writers/readers map var name -> op positions in block order;
    `first_write`/`first_read` are the minimum positions. Names on the
    op's input slots count as reads, output slots as writes; EMPTY_VAR
    placeholders are ignored.
    """
    block: Block
    writers: Dict[str, List[int]] = field(default_factory=dict)
    readers: Dict[str, List[int]] = field(default_factory=dict)

    @property
    def first_write(self) -> Dict[str, int]:
        return {n: idxs[0] for n, idxs in self.writers.items()}

    @property
    def first_read(self) -> Dict[str, int]:
        return {n: idxs[0] for n, idxs in self.readers.items()}

    def multi_writers(self) -> Dict[str, List[int]]:
        return {n: idxs for n, idxs in self.writers.items()
                if len(idxs) > 1}


def analyze_block(block: Block) -> BlockDataflow:
    df = BlockDataflow(block)
    for i, op in enumerate(block.ops):
        for n in op.input_arg_names:
            if n == EMPTY_VAR:
                continue
            df.readers.setdefault(n, []).append(i)
        for n in op.output_arg_names:
            if n == EMPTY_VAR:
                continue
            df.writers.setdefault(n, []).append(i)
    return df


def iter_sub_blocks(op: Operator) -> Iterator[Tuple[str, Block]]:
    """Block-typed attrs of one op (sub_block / true_block / ...)."""
    for k, v in op.attrs.items():
        if isinstance(v, Block):
            yield k, v


def iter_blocks(program: Program) -> Iterator[Tuple[Block,
                                                    Optional[Operator]]]:
    """Every block reachable from the program, with the op that
    contains it (None for blocks no control-flow op references — the
    global block, and orphaned builds). Blocks live both in
    program.blocks and behind op attrs; the attr walk establishes the
    container relation, the list walk catches strays. Each block is
    yielded once."""
    containers: Dict[int, Operator] = {}
    seen = set()
    stack: List[Block] = [program.global_block]
    while stack:
        blk = stack.pop()
        if id(blk) in seen:
            continue
        seen.add(id(blk))
        yield blk, containers.get(id(blk))
        for op in blk.ops:
            for _, sub in iter_sub_blocks(op):
                containers.setdefault(id(sub), op)
                stack.append(sub)
    for blk in program.blocks:
        if id(blk) not in seen:
            seen.add(id(blk))
            yield blk, containers.get(id(blk))


def iter_ops(program: Program) -> Iterator[OpSite]:
    """Every op in every reachable block, as anchored OpSites."""
    for blk, container in iter_blocks(program):
        for i, op in enumerate(blk.ops):
            yield OpSite(blk.idx, i, op, container)


# op type -> the attr names whose string lists genuinely SEED the
# sub-block environment (read straight from the kernels:
# ops/control_flow_ops.py builds while/run_block_if envs from
# externals+carried and conditional_block's from its X inputs only;
# ops/lod_ops.py builds ifelse branch envs from externals and
# recurrent step envs from externals + per-step x_names + pre_names).
# Output-name lists (true_out/false_out, out_names, mem_names) are
# PRODUCED inside the block — treating them as entries (the old
# any-all-str-list heuristic) over-seeded PTA001 and masked true
# uninitialized reads.
BLOCK_ENTRY_ATTRS: Dict[str, Tuple[str, ...]] = {
    "while": ("carried", "externals"),
    "run_block_if": ("carried", "externals"),
    "conditional_block": (),
    "ifelse": ("externals",),
    "recurrent": ("externals", "x_names", "pre_names"),
    "go": (),
}

_ENTRY_FALLBACK_WARNED: set = set()


def register_block_entry_attrs(op_type: str,
                               attr_names: Tuple[str, ...]) -> None:
    """Register which of a NEW container op's list attrs seed its
    sub-block environment (mirrors core/registry.register_op: a
    sub-block-carrying op added without an entry registration falls
    back to the permissive heuristic with a warn-once, so the gap is
    visible instead of silent)."""
    BLOCK_ENTRY_ATTRS[op_type] = tuple(attr_names)


def block_entry_names(op: Operator) -> set:
    """Names a control-flow op's sub-block environment starts with.

    The sub-block kernels build a FRESH env: parent-block vars are NOT
    visible unless declared through the op's inputs or the registered
    entry-name attrs (BLOCK_ENTRY_ATTRS). This is the seed set an
    uninitialized-read analysis of the sub-block must start from.

    Unregistered container op types fall back to the old permissive
    heuristic — every all-str list attr counts — with a warn-once:
    over-seeding can MASK true uninitialized reads (PTA001), so the
    fallback is a visible stopgap, not the contract."""
    names = set(op.input_arg_names)
    registered = BLOCK_ENTRY_ATTRS.get(op.type)
    if registered is not None:
        for attr in registered:
            v = op.attrs.get(attr)
            if isinstance(v, (list, tuple)):
                names.update(x for x in v if isinstance(x, str))
        return names
    if op.type not in _ENTRY_FALLBACK_WARNED:
        _ENTRY_FALLBACK_WARNED.add(op.type)
        import warnings

        warnings.warn(
            f"block_entry_names: container op type {op.type!r} has no "
            f"registered entry-name attrs; falling back to the "
            f"permissive any-all-str-list heuristic, which can mask "
            f"uninitialized-read findings (PTA001). Register it via "
            f"analysis.dataflow.register_block_entry_attrs.")
    for v in op.attrs.values():
        if isinstance(v, (list, tuple)) and v and all(
                isinstance(x, str) for x in v):
            names.update(v)
    return names
