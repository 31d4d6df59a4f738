"""tp-sharded decoder-step fixture — a thin wrapper over the REAL
sharded lowering.

Until PR 15 this module hand-annotated a stock dense bundle with a
prospective Megatron layout so the sharding prover and the per-device
memory planner could be built ahead of the feature. The sharded
serving lowering has now landed in the engine itself
(``models/decode_engine.ShardingConfig`` →
``build_decode_step_program(sharding=...)``), so this fixture simply
builds a tp-sharded bundle through the SHIPPED code path and exposes
its step program — the zoo target (analysis/targets.py
``sharded_decoder``) and the memory-plan tests lint/price the code
that actually serves, not a hand-built twin.

What the shipped layout pins (ShardingConfig docstring has the full
rationale):

* self/cross KV state sharded along heads (dim 1 of the dense
  ``[rows, H, maxT, Dh]`` lane buffers; the paged pools shard
  ``[n_blocks * block_size, (H/tp) * Dh]``) — per-device KV bytes exactly
  1/tp (tests/test_memory_plan.py);
* row-parallel attention out-projections + column/row-parallel ffn
  (their psums are the PTA161-proof obligations), column-parallel
  cross-attention query, vocab-sharded logits head;
* the fused self-attention qkv and the fused cross-KV projections
  REPLICATED (their fused-axis split crosses tp shard boundaries —
  sharding them would force a per-tick reshard, which PTA160 rejects
  inside the serve While).

Reference counterpart: none — the reference sharded at runtime via
transpilers (reference transpiler/distribute_transpiler.py); a
statically-annotated, statically-proven tensor-parallel decode step
is the GSPMD-era capability this repo builds toward.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List

from .. import unique_name
from ..analysis import absint

__all__ = ["ShardedDecoderFixture", "build_tp_sharded_decoder_step",
           "TP_AXIS", "DP_AXIS"]

DP_AXIS = "dp"
TP_AXIS = "tp"


@dataclass
class ShardedDecoderFixture:
    """The sharded step program plus everything tests need to assert
    the sharding story: the bundle it came from, the mesh, and the
    annotated name -> placement map."""
    program: object                 # the tp-annotated step program
    startup: object
    bundle: object                  # the tp-sharded DecodeStepBundle
    mesh: absint.MeshConfig
    placements: Dict[str, dict] = field(default_factory=dict)
    kv_names: List[str] = field(default_factory=list)

    def kv_state_bytes(self) -> int:
        """Unsharded KV bytes of the bundle's self+cross cache state
        (the denominator of the ~1/tp per-device claim)."""
        return self.bundle.kv_state_bytes()


def build_tp_sharded_decoder_step(tp: int = 2,
                                  seq_len: int = 8,
                                  max_out_len: int = 8,
                                  d_model: int = 32, n_heads: int = 4,
                                  n_layers: int = 2,
                                  d_inner: int = 64, vocab: int = 64,
                                  n_slots: int = 4,
                                  state_prefix: str = "@tpfx/"
                                  ) -> ShardedDecoderFixture:
    """Build a dense decode-step bundle through the REAL sharded
    lowering (``ShardingConfig(tp=tp)``) and expose its step program
    as the prover/planner fixture."""
    from . import transformer as T
    from .decode_engine import ShardingConfig

    with unique_name.guard():
        bundle = T.build_decode_step_program(
            seq_len=seq_len, max_out_len=max_out_len, d_model=d_model,
            n_heads=n_heads, n_layers=n_layers, d_inner=d_inner,
            vocab=vocab, n_slots=n_slots, state_prefix=state_prefix,
            sharding=ShardingConfig(tp=tp, axis=TP_AXIS))
    step = bundle.step
    placements = dict(bundle.sharding_plan.placements)
    kv_names = [
        name for name in bundle._state_specs
        if name.split("/")[-1].startswith(("self_k", "self_v",
                                           "cross_k", "cross_v"))]
    return ShardedDecoderFixture(step, bundle.startup, bundle,
                                 absint.mesh_of(step), placements,
                                 kv_names)
