"""Decode engine: ONE home for every decode capability.

Reference counterpart: tests/unittests/dist_transformer.py:1498
fast_decode is the decode loop all of this re-designs TPU-first; the
slot-pool/paged serving discipline follows Orca (OSDI'22), vLLM
(SOSP'23 — PagedAttention block tables) and SGLang (RadixAttention
prefix sharing), PAPERS.md.

models/transformer.py used to carry three decode builders (whole-loop,
incremental, DecodeStepBundle) with ~600 lines of overlapping loop/
cache/emission logic, so every new decode capability (paged KV,
speculative, sampling, sharding) had to be implemented three times.
This module factors the decode machinery into composable pieces the
builders share — transformer.py's builder entry points keep their
public signatures and delegate here:

* **Cache layout** — ``CacheConfig`` selects ``dense`` (per-lane
  ``[rows, H, maxT, Dh]`` KV buffers, the r10 design) or ``paged``
  (a SHARED block pool ``[n_blocks * block_size, H * Dh]`` per layer,
  one row a cache cell, + per-lane int32 block-table rows;
  cross-attention K/V lives in a refcounted prompt-entry pool
  ``[n_prompt_entries + 1, seq_len, H * Dh]`` so identical prompts
  prefill ONCE). Pools are stored in the shape they are addressed in,
  ``H * Dh`` on the minor axis: a tick's attention reads a lane's
  cells, and its prompt entry as one block of ``seq_len`` rows, where
  they lie (``paged_decode_attention``), so no tick copies or
  relayouts a pool or the table; writes
  go through the ``masked_pool_write`` registry op whose disjoint
  one-hot masks are the lane-exclusivity contract checker PTA110
  enforces (shared-pool aliasing is the silent cross-request KV
  corruption class).
* **Step body** — ``cached_decoder_step`` runs the KV-cached decoder
  stack over per-layer cache-access objects (``_DenseLaneCache`` /
  ``_PagedLaneCache``), so the whole-loop, single-step and paged
  programs trace IDENTICAL math — token-for-token parity across
  layouts is structural, not coincidental.
* **Loop/burst/exit policy** — the serve-program While (n_steps +
  min_active early exits) and the scalar-counter whole-loop tail.
* **Emission** — the greedy emit/EOS-freeze/one-hot-write tail, in
  scalar-loop and per-lane-vectorized forms.

Host-side allocation policy (``HostBlockPool``, ``PromptPrefixCache``)
also lives here: the device only ever sees fed/persistable tables, so
blocks/refcounts/prefix hashing stay plain testable Python in the
serving scheduler (inference/serving.py).

The host boundary of a serve dispatch carries one array each way
(``build_serve_program``): the scheduler's tables (block table, prompt
references, the lane mask: a bundle's ``fed_tables``) ride the call as
feeds beside ``n_steps`` and the admission feeds, and what the
scheduler reads back (token rows, lane counters, telemetry) comes home
as one packed row (``ServeRow``).
"""
from __future__ import annotations

import dataclasses
import heapq
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import layers
from ..analysis import absint
from ..core.program import device_scope
from ..observability import devtel
from ..observability.devtel import DECODE_STEPS_VAR  # noqa: F401
from ..param_attr import ParamAttr

# name mark on SHARED block-pool persistables: checker PTA110 requires
# every write to a var carrying this mark to be a provably
# lane-exclusive masked_pool_write (analysis/checkers.py)
POOL_MARK = "@POOL"

# the mesh-axis name that WOULD shard decode lanes across devices.
# No shipped lowering shards lanes (tensor parallelism shards heads;
# data parallelism is replica servers on disjoint device slices), so
# a tp-only mesh proves the serve While's burst-exit predicate
# uniform — but the burst-exit mark names this axis so that any
# future lane-sharding mesh flips the prover back to
# proven-divergent automatically (absint.mark_divergence_source
# axes= semantics).
LANE_AXIS = "lanes"


def fed_name(table: str) -> str:
    """The feed that carries the scheduler's table `table` (a logical
    state name: 'block_tab', 'prompt_ref', 'active') into a serve
    dispatch."""
    return f"fed_{table}"


class ServeRow:
    """What a serve dispatch hands back to its scheduler, as ONE flat
    array: the state variables `names` (token rows, lane counters, the
    speculative and telemetry counters, a bundle's extras), each
    flattened, end to end in that order in the variable `name`
    (build_serve_program packs it on the device after the burst; a few
    tens of KB). `cut(row)` gives the list a fetch of `names` would
    have given, as views of the one host buffer. One row holds one
    dtype, and everything a scheduler reads back today is int64; a
    bundle that ever fetches another dtype gets a second row."""

    __slots__ = ("name", "names", "shapes", "dtype", "size", "_cuts")

    def __init__(self, name, names, specs, dtype="int64"):
        self.name = name
        self.names = tuple(names)
        self.dtype = dtype
        self.shapes, self._cuts, at = [], [], 0
        for n in self.names:
            shape, dt = specs[n]
            if dt != dtype:
                raise ValueError(
                    f"serve row {name!r} holds {dtype}; {n!r} is {dt} "
                    f"-- give it a row of its own")
            size = int(np.prod(shape))
            self.shapes.append(tuple(shape))
            self._cuts.append((at, at + size))
            at += size
        self.size = at

    def cut(self, row) -> list:
        row = np.asarray(row)
        return [row[a:b].reshape(shape)
                for (a, b), shape in zip(self._cuts, self.shapes)]


class _ServeBoundary:
    """What a slot-pool bundle says of a serve dispatch's host
    boundary (both bundle kinds): `fed_tables`, the logical names of
    the scheduler-owned tables every serve program takes as feeds
    (`fed_name`), and `serve_row`, the packed row every serve program
    ends in."""

    fed_tables: tuple = ()
    serve_row: Optional[ServeRow] = None

    def table_feed_spec(self) -> List[tuple]:
        """(name, shape, dtype) of the fed tables: the tail of every
        key's `serve_feed_spec`."""
        return [(fed_name(t), *self._state_specs[self.state[t]])
                for t in self.fed_tables]

    def idle_table_feed(self) -> Dict[str, np.ndarray]:
        """The fed tables of a dispatch no lane decodes in (what
        `init_slot_state` leaves in a scope): no block, the dustbin
        prompt entry, every lane down."""
        feed = {}
        for t in self.fed_tables:
            shape, dt = self._state_specs[self.state[t]]
            fill = self.cache.n_prompt_entries if t == "prompt_ref" \
                else 0
            feed[fed_name(t)] = np.full(shape, fill, dt)
        return feed


# the speculative counters a scheduler reads back every dispatch, in
# the row's order: the five [1] totals, then the two per-lane ones
SPEC_COUNTERS = ("spec_proposed", "spec_accepted", "spec_emitted",
                 "spec_draft_steps", "spec_target_steps")
SPEC_LANE_COUNTERS = ("spec_lane_accepted", "spec_lane_ticks")


def serve_row_of(state_prefix, state, specs, cache, extra=()) -> ServeRow:
    """The packed row of a bundle whose state map is `state`: the token
    rows and the three lane vectors, the speculative counters where the
    bundle speculates, the telemetry counters (DeviceTelemetry's own
    list and order), then the bundle's `extra` logical names (a
    decoder-only bundle's expert counters). The ONE place the order of
    a serve dispatch's `outs` is decided; the servers index it."""
    from types import SimpleNamespace

    names = [state[k] for k in ("tok_buf", "step", "active", "finished")]
    names += [state[c] for c in SPEC_COUNTERS + SPEC_LANE_COUNTERS
              if c in state]
    names += devtel.DeviceTelemetry(
        SimpleNamespace(cache=cache, state=state)).fetch_names
    names += [state[k] for k in extra]
    return ServeRow(f"{state_prefix}serve_row", names, specs)


@dataclass(frozen=True)
class ShardingConfig:
    """Tensor-parallel execution layout of a decode bundle — the
    Megatron-LM composition (Shoeybi et al.; SNIPPETS.md [1]/[3]'s
    ``Mesh + NamedSharding`` pattern) re-designed for the decode
    engine's serving regime:

    * self/cross KV state sharded along HEADS — dense per-lane
      buffers ``[R, H/tp, T, Dh]``, the paged pools
      ``[n_blocks * block_size, (H/tp) * Dh]`` — so per-device KV bytes
      drop ~1/tp. Block tables / prompt refs stay host-owned and
      REPLICATED: ``HostBlockPool`` and the PTA190/191 ownership
      proofs are untouched.
    * column/row-parallel ffn (fc1 out-dim, fc2 in-dim), row-parallel
      attention out-projections, column-parallel cross-attention
      query, vocab-sharded logits head. The implied psums/allgathers
      sit inside the decode-burst While — legal under GSPMD exactly
      because the burst-exit predicate is PROVEN value-uniform on a
      tp-only mesh (PTA130/131/160/161; the r5 contract).
    * the CONTIGUOUS fused self-attention qkv projection and the fused
      cross-KV projection stay REPLICATED deliberately: their
      ``split`` on the fused output axis crosses tp shard boundaries,
      so column-sharding them would force a reshard collective EVERY
      tick — PTA160 rejects that shape inside the While.
      ``qkv_interleaved=True`` switches the decode-step builders to
      the HEAD-INTERLEAVED fused layout (``dec{li}_self_qkvh.w``,
      columns ordered ``[H, 3, Dh]``-major): the q/k/v decomposition
      becomes reshape ``[.., H, 3, Dh]`` → split on the local 3-axis →
      squeeze → transpose, every step of which carries a head-sharded
      placement locally (analysis/sharding_rules.py reshape
      major-carry + split/squeeze/transpose rules), so the qkv weight
      column-shards with ZERO per-tick reshard — the Megatron
      column-parallel attention block, completed. Convert trained
      contiguous weights with ``interleave_qkv_params``.

    ``dp`` replica lanes are NOT part of this config: data
    parallelism is separate server instances on disjoint device
    slices (inference/runtime/placement.py), each carrying its own
    bound copy of this plan.

    Reference counterpart: reference
    transpiler/distribute_transpiler.py:69 VarBlock sliced params by
    REWRITING programs at runtime; a declarative layout config the
    compiler partitions from is the GSPMD-era shape.
    """

    tp: int = 1
    axis: str = "tp"
    # head-interleaved fused-qkv weight layout (dec{li}_self_qkvh.w)
    # — lets the fused qkv projection column-shard under tp; False
    # keeps the contiguous (replicated-qkv) layout byte-compatible
    # with pre-r19 checkpoints
    qkv_interleaved: bool = False

    @property
    def enabled(self) -> bool:
        return self.tp > 1

    def validate(self, n_heads: int, vocab: int, d_model: int,
                 d_inner: int):
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1, got {self.tp}")
        if not self.enabled:
            return
        for what, dim in (("n_heads", n_heads), ("vocab", vocab),
                          ("d_model", d_model), ("d_inner", d_inner)):
            if dim % self.tp:
                raise ValueError(
                    f"ShardingConfig(tp={self.tp}) needs {what} "
                    f"divisible by tp, got {what}={dim}")
        if self.axis == LANE_AXIS:
            raise ValueError(
                f"mesh axis {LANE_AXIS!r} is reserved for (future) "
                f"lane sharding — the serve While's divergence mark "
                f"names it; pick another tp axis name")

    def token(self) -> tuple:
        return ("tp", int(self.tp), self.axis,
                int(self.qkv_interleaved))


@dataclass(frozen=True)
class CacheConfig:
    """KV cache layout of a slot-pool decode bundle.

    ``dense``: per-lane KV buffers — every admitted request reserves
    the full ``[maxT, ...]`` self-KV and ``[seq_len, ...]`` cross-KV
    regardless of its actual generation/prompt reuse (the r10 layout).

    ``paged``: self-attention KV lives in ONE shared pool of
    ``n_blocks`` blocks of ``block_size`` positions per layer
    (``[n_blocks, block_size, n_heads, head_dim]``), addressed through
    per-lane int32 block-table rows the HOST allocates
    (``HostBlockPool``); cross-attention K/V lives in a pool of
    ``n_prompt_entries`` whole-prompt entries (+1 dustbin), shared
    refcounted across lanes with identical prompts
    (``PromptPrefixCache``) so a repeated system prompt prefills once
    and later admissions skip the encoder entirely.
    """

    layout: str = "dense"          # "dense" | "paged"
    block_size: int = 8            # positions per self-KV block
    n_blocks: int = 0              # shared self-KV pool blocks
    n_prompt_entries: int = 0      # shared cross-KV prompt entries
    chunk_tokens: int = 0          # >0: build ("chunked", p) prefill
    #                                phase programs processing this
    #                                many prompt tokens per tick

    def validate(self, max_out_len: int):
        if self.layout not in ("dense", "paged"):
            raise ValueError(f"unknown KV layout {self.layout!r}")
        if self.layout == "paged":
            if self.block_size < 1 or self.n_blocks < 1 \
                    or self.n_prompt_entries < 1:
                raise ValueError(
                    f"paged layout needs block_size/n_blocks/"
                    f"n_prompt_entries >= 1, got {self}")
            if max_out_len % self.block_size != 0:
                raise ValueError(
                    f"block_size={self.block_size} must divide "
                    f"max_out_len={max_out_len} (token-exact parity "
                    f"needs the paged cache view to cover exactly the "
                    f"dense [maxT] positions)")
        if self.chunk_tokens < 0:
            raise ValueError(
                f"chunk_tokens must be >= 0, got {self.chunk_tokens}")
        if self.chunk_tokens and self.layout != "paged":
            raise ValueError(
                "chunked prefill needs the paged layout (chunks land "
                "in the shared prompt-entry pool)")
        if self.chunk_tokens == 1:
            raise ValueError(
                "chunk_tokens == 1 is rejected: a single-query "
                "attention chunk lowers to a different XLA "
                "contraction whose accumulation order drifts ~1e-7 "
                "from the monolithic encoder, breaking the bit-exact "
                "chunked==monolithic parity contract (any C >= 2 is "
                "exact — the ragged last chunk keeps width C by "
                "zero-padding, so no dispatch ever sees a "
                "single-query shape)")

    @property
    def chunked(self) -> bool:
        return self.layout == "paged" and self.chunk_tokens > 0

    def n_chunks(self, seq_len: int) -> int:
        """Ticks needed to stream one seq_len prompt through at
        chunk_tokens per tick (ceil division; the last chunk may be
        ragged — phase bodies mask past-the-end positions)."""
        c = self.chunk_tokens
        return (seq_len + c - 1) // c if c else 0

    def pages(self, max_out_len: int) -> int:
        return max_out_len // self.block_size

    def token(self) -> tuple:
        """Content identity of the layout — part of
        ``server_fingerprint`` and therefore of hot-swap/dedupe
        decisions: two servers differing only in KV layout must not
        dedupe as 'same fingerprint' (inference/runtime/registry.py)."""
        if self.layout == "dense":
            return ("dense",)
        tok = ("paged", self.block_size, self.n_blocks,
               self.n_prompt_entries)
        # append-only so historical paged tokens stay byte-identical:
        # a chunked and an unchunked build of one geometry carry
        # different program sets and must never dedupe
        if self.chunk_tokens:
            tok = tok + ("chunk", self.chunk_tokens)
        return tok

    @staticmethod
    def suggest_chunk_tokens(bundle, tick_budget_ms: float,
                             prefill_ms: float = 150.0) -> int:
        """Largest power-of-two chunk size whose per-tick prefill
        slice fits ``tick_budget_ms`` — the PERF.md "Chunk-size
        arithmetic" made callable (the PR 17 leftover ROADMAP named:
        tuning C per shape was manual).

        One chunk tick runs ONE phase over C prompt tokens; a
        monolithic prefill runs all ``2L+2`` phases over all
        ``seq_len`` tokens in ``prefill_ms`` (default: the measured
        ~150 ms for the 2k-token encoder on the throttled CPU host —
        pass a fresh measurement for other shapes/backends). So
        ``tick(C) ~= prefill_ms * C / (seq_len * n_phases)``, and the
        two-tier schedule bounds every decode tick's wait by one such
        slice. L is read off the bundle's state specs (one cross_k
        entry per layer); the floor is C=2 because ``validate``
        rejects C=1 (accumulation-order drift breaks byte-exact
        parity). Worked example (PERF.md): seq_len=2048, L=1 (4
        phases), 5.0 ms budget -> C=256 (tick 4.69 ms; C=512 would
        be 9.38 ms).

        Reference counterpart: none — the reference has no chunked
        prefill; DistServe-style chunk sizing is serving-era
        arithmetic."""
        if tick_budget_ms <= 0:
            raise ValueError(
                f"tick_budget_ms must be > 0, got {tick_budget_ms}")
        seq_len = int(bundle.seq_len)
        n_layers = sum(1 for name in bundle._state_specs
                       if "cross_k" in name)
        n_phases = 2 * max(n_layers, 1) + 2

        def tick(c):
            return prefill_ms * c / (seq_len * n_phases)

        c = 2
        while c * 2 <= seq_len and tick(c * 2) <= tick_budget_ms:
            c *= 2
        return c


# ---------------------------------------------------------------------------
# Emission helpers (shared by every decode front).
# ---------------------------------------------------------------------------
def step_logits(dec, positions, counter, vocab):
    """Select step t's hidden row BEFORE the vocab projection: a
    [rows,D]x[D,V] matmul instead of [rows,maxT,D]x[D,V] — identical
    logits, maxT-fold cheaper (shared by all decode builders)."""
    t_mask = layers.cast(layers.equal(positions, counter), "float32")
    step_hidden = layers.reduce_sum(
        layers.elementwise_mul(dec, layers.unsqueeze(t_mask, [1]),
                               axis=1), dim=1)
    return layers.fc(step_hidden, vocab, bias_attr=False,
                     param_attr="logits.w")


def init_token_buffer(src, positions, max_out_len, start_id):
    """[B, maxT] int64 zeros with the start token at position 0 — the
    loop-carried decode buffer the whole-loop builders share."""
    buf = layers.fill_constant_batch_size_like(
        src, [-1, max_out_len], "int64", 0.0)
    if start_id:
        start_col = layers.cast(
            layers.equal(positions,
                         layers.fill_constant([1], "int64", 0.0)),
            "int64")
        buf = layers.elementwise_add(
            buf, layers.cast(
                layers.scale(start_col, scale=float(start_id)),
                "int64"))
    return layers.assign(buf)


def emit_token_step(src, step_logits_v, positions, tgt_buf, finished,
                    counter, limit, cond, max_out_len, end_id):
    """Shared whole-loop decode tail: greedy argmax, EOS freeze
    (finished rows keep emitting end_id), one-hot write at position
    t+1, counter bump, loop-condition refresh. Mutates tgt_buf/
    finished/counter/cond in place — keep BOTH whole-loop builders on
    this helper so their token-for-token equivalence can't silently
    diverge.

    The refreshed condition carries an all-rows-finished early-exit
    term: once every row has emitted end_id the loop stops instead of
    spinning to max_out_len emitting frozen end_id rows. Positions
    past the exit step keep their zero init — callers that need the
    variable-length result go through apply_eos_sentinel
    (inference/serving.py), which normalizes everything after the
    first end_id to the -1 sentinel either way. Expressed with
    reduce_sum/elementwise_min/greater_than only, all inside the
    native xla_train kernel slice."""
    tok = layers.cast(layers.argmax(step_logits_v, axis=-1), "int64")
    not_fin = layers.elementwise_sub(
        layers.fill_constant_batch_size_like(
            src, [-1], "int64", 1.0), finished)
    tok = layers.elementwise_add(
        layers.elementwise_mul(tok, not_fin),
        layers.cast(layers.scale(finished, scale=float(end_id)),
                    "int64"))
    layers.assign(
        layers.elementwise_max(
            finished,
            layers.cast(layers.equal(
                tok, layers.fill_constant([1], "int64",
                                          float(end_id))), "int64")),
        output=finished)
    next_mask = layers.cast(
        layers.equal(positions,
                     layers.increment(counter, 1, in_place=False)),
        "int64")
    keep = layers.elementwise_sub(
        layers.fill_constant([max_out_len], "int64", 1.0), next_mask)
    layers.assign(
        layers.elementwise_add(
            layers.elementwise_mul(tgt_buf, keep),
            layers.elementwise_mul(layers.unsqueeze(tok, [1]),
                                   next_mask)),
        output=tgt_buf)
    layers.increment(counter, 1)
    # continue while BOTH hold: steps remain (limit - counter > 0) AND
    # at least one row is unfinished (sum(1 - finished) > 0); min(a, b)
    # > 0 encodes the conjunction without logical ops
    unfinished = layers.reduce_sum(
        layers.elementwise_sub(
            layers.fill_constant_batch_size_like(
                src, [-1], "int64", 1.0), finished),
        keep_dim=True)
    layers.greater_than(
        layers.elementwise_min(
            layers.elementwise_sub(limit, counter), unfinished),
        layers.fill_constant([1], "int64", 0.0), cond=cond)


def heads_of(x, t, n_heads, head_dim):
    """[R,t,H*D] -> [R,H,t,D] (the cached-attention head layout every
    KV-cached decode builder shares)."""
    return layers.transpose(
        layers.reshape(x, [0, t, n_heads, head_dim]),
        perm=[0, 2, 1, 3])


def cells_of_blocks(blocks, block_size, offs):
    """[..., n] float32 block ids -> flat int32 ids of their cache
    cells, block-major: block b owns the pool rows b*BS..b*BS+BS-1
    (``offs`` is the float32 ``arange(BS)``). The paged step bodies
    (a lane's block table -> its maxT cells) and the COW program share
    it, so 'which rows are a block' is written once."""
    nd = len(blocks.shape)
    base = layers.expand(
        layers.unsqueeze(layers.scale(blocks, scale=float(block_size)),
                         [nd]), [1] * nd + [block_size])
    return layers.cast(
        layers.reshape(layers.elementwise_add(base, offs, axis=nd),
                       [-1]), "int32")


# ---------------------------------------------------------------------------
# Cache-access objects: the ONE place layout differences live.
# ---------------------------------------------------------------------------
class _DenseViewAttention:
    """Self-attention of the dense layouts: ``update`` writes this
    tick's keys and values and hands back the ``[R, H, maxT, Dh]``
    vars, over which the attention is the shared
    matmul / bias / softmax / matmul."""

    def attend(self, qh, kh, vh, att_bias, scale):
        """qh, kh, vh [R,H,q,Dh] -> context rows [R,q,H*Dh]."""
        kc, vc = self.update(kh, vh)
        scores = layers.scale(
            layers.matmul(qh, kc, transpose_y=True),
            scale=scale)  # [R,H,q,maxT]
        scores = layers.elementwise_add(scores, att_bias)
        probs = layers.softmax(scores, axis=-1)
        ctx = layers.matmul(probs, vc)
        return layers.reshape(
            layers.transpose(ctx, perm=[0, 2, 1, 3]),
            [0, qh.shape[2], qh.shape[1] * qh.shape[3]])  # [R,q,HD]


class _DenseLaneCache(_DenseViewAttention):
    """Per-layer dense self-KV access: in-place one-hot masked write
    into per-lane ``[R, H, maxT, Dh]`` vars, attention reads the vars
    directly (the r10 layout; write masks broadcast for either a
    shared scalar counter [maxT,1] or per-lane counters
    [R,1,maxT,1])."""

    def __init__(self, kc, vc, write_mask, keep_mask):
        self.kc, self.vc = kc, vc
        self.write_mask, self.keep_mask = write_mask, keep_mask

    def update(self, kh, vh):
        new_kc = layers.elementwise_add(
            layers.elementwise_mul(self.kc, self.keep_mask),
            layers.elementwise_mul(kh, self.write_mask))
        new_vc = layers.elementwise_add(
            layers.elementwise_mul(self.vc, self.keep_mask),
            layers.elementwise_mul(vh, self.write_mask))
        layers.assign(new_kc, output=self.kc)
        layers.assign(new_vc, output=self.vc)
        return self.kc, self.vc


class _PagedLaneCache:
    """Per-layer paged self-KV access. Writes go through the
    ``masked_pool_write`` registry op (disjoint one-hot scatter into
    the SHARED ``[NB * BS, H * Dh]`` pool at each lane's block-table
    cell, gated by the active mask so idle/dustbin lanes never
    touch the pool — the PTA110 exclusivity contract). The attention
    is the cache's own: ``paged_decode_attention`` reads each lane's
    cells from the pools where they are stored, through the lane's
    row of the block table, and no dense ``[R, H, maxT, Dh]`` view of
    a pool exists. Positions a lane has not written yet hold stale
    pool bytes; the op masks positions past ``pos`` (+ j for query j)
    exactly like the dense layouts' -1e9 bias masks their zeros, so
    the softmax sees identical values — token-exact parity with dense.

    ``q`` > 1 is the multi-position verify write: the q positions of
    every lane flatten to R*q masked_pool_write rows (distinct cells —
    positions within a lane are distinct, lanes own disjoint blocks
    via the host table: the PTA110 exclusivity story is unchanged),
    with ``write_idx``/``gate`` [R*q] and the gate extended by
    per-position validity so positions past the buffer end never
    touch the pool."""

    def __init__(self, pool_k, pool_v, write_idx, gate, block_tab, pos,
                 rows, block_size, q=1):
        self.pool_k, self.pool_v = pool_k, pool_v
        self.write_idx, self.gate = write_idx, gate
        self.block_tab = block_tab        # [rows, NP] int32, host-owned
        self.pos = pos                    # [rows] position of query 0
        self.rows, self.block_size, self.q = rows, block_size, q

    def attend(self, qh, kh, vh, att_bias, scale):
        """qh, kh, vh [R,H,q,Dh] -> context rows [R,q,H*Dh]; the
        mask comes from ``pos``, so ``att_bias`` is not read."""
        n_heads, head_dim = qh.shape[1], qh.shape[3]

        def rows_of(x, lead):     # [R,H,q,Dh] -> lead + [H*Dh]
            return layers.reshape(
                layers.transpose(x, perm=[0, 2, 1, 3]),
                lead + [n_heads * head_dim])

        for pool, new in ((self.pool_k, kh), (self.pool_v, vh)):
            layers.masked_pool_write(
                pool, rows_of(new, [self.rows * self.q]),
                self.write_idx, gate=self.gate, leading_dims=1,
                exclusive_via="block_table")
        return layers.paged_decode_attention(
            rows_of(qh, [self.rows, self.q]), self.pool_k, self.pool_v,
            self.block_tab, self.pos, self.block_size, n_heads,
            scale=scale)


class _DenseSpanCache(_DenseViewAttention):
    """Per-layer dense self-KV access for a MULTI-position write (the
    speculative verify step: q=k+1 query rows per lane land at cache
    positions t..t+k in one update). ``pos_oh`` is the [R,q,maxT]
    one-hot of each query's cache position (all-zero rows for
    positions past the buffer write nothing); the scatter is the
    one-hot matmul the admission bodies already use, and the read
    view is the raw var exactly like _DenseLaneCache."""

    def __init__(self, kc, vc, pos_oh, keep_mask):
        self.kc, self.vc = kc, vc
        # [R,1,maxT,q] scatter operand (matmul against [R,H,q,Dh])
        self.scat = layers.unsqueeze(
            layers.transpose(pos_oh, perm=[0, 2, 1]), [1])
        self.keep_mask = keep_mask  # [R,1,maxT,1]

    def update(self, kh, vh):
        for var, new in ((self.kc, kh), (self.vc, vh)):
            scat = layers.matmul(self.scat, new)  # [R,H,maxT,Dh]
            layers.assign(layers.elementwise_add(
                layers.elementwise_mul(var, self.keep_mask), scat),
                output=var)
        return self.kc, self.vc


class _DenseCross:
    """Per-layer cross-attention over per-lane ``[R, H, S, Dh]`` vars
    (the dense layouts, the whole-loop front's projections and the
    speculative draft's ``draft_cross_*`` state): the shared
    matmul / softmax / matmul over the whole prompt."""

    def __init__(self, ck, cv):
        self.ck, self.cv = ck, cv

    def attend(self, q2, n_heads, scale):
        """q2 [R,q,H*Dh] query rows -> context rows [R,q,H*Dh]."""
        q, d_model = q2.shape[1], q2.shape[2]
        q2h = heads_of(q2, q, n_heads, d_model // n_heads)
        s2 = layers.scale(
            layers.matmul(q2h, self.ck, transpose_y=True),
            scale=scale)  # [R,H,q,S]
        p2 = layers.softmax(s2, axis=-1)
        return layers.reshape(
            layers.transpose(layers.matmul(p2, self.cv),
                             perm=[0, 2, 1, 3]),
            [0, q, d_model])


class _PagedPromptCross:
    """Per-layer cross-attention of the paged layout: a lane's prompt
    entry is read where the ``[E+1, S, H*Dh]`` table stores it, through
    the read the self pools take. The table is ``(E+1) * S`` rows of
    ``H*Dh`` in blocks of ``S``, ``prompt_ref`` a block table of one
    block a lane, and every lane stands at position ``S - 1`` (a
    prompt is exactly ``S`` positions, so nothing is masked, for any
    number of queries). No ``[R, H, S, Dh]`` copy of the lanes'
    entries exists; idle lanes read the dustbin entry ``E``."""

    def __init__(self, pool_k, pool_v, table, last_pos):
        self.pool_k, self.pool_v = pool_k, pool_v
        self.table = table                # [rows, 1]: prompt_ref
        self.last_pos = last_pos          # [rows], all S - 1

    def attend(self, q2, n_heads, scale):
        """q2 [R,q,H*Dh] query rows -> context rows [R,q,H*Dh]."""
        seq_len, width = self.pool_k.shape[1], self.pool_k.shape[2]
        return layers.paged_decode_attention(
            q2, layers.reshape(self.pool_k, [-1, width]),
            layers.reshape(self.pool_v, [-1, width]), self.table,
            self.last_pos, seq_len, n_heads, scale=scale,
            reads="prompt_table")


def _paged_prompt_cross(sv, state_prefix, n_layers, rows, seq_len):
    """The paged tick bodies' per-layer cross-access objects over the
    bundle's prompt table."""
    table = layers.reshape(sv[f"{state_prefix}prompt_ref"], [rows, 1])
    last_pos = layers.fill_constant([rows], "int32",
                                    float(seq_len - 1))
    return [_PagedPromptCross(
        sv[f"{state_prefix}cross_k{li}{POOL_MARK}"],
        sv[f"{state_prefix}cross_v{li}{POOL_MARK}"], table, last_pos)
        for li in range(n_layers)]


@dataclass(frozen=True)
class SamplingConfig:
    """Emission-lane sampling policy (temperature/top-k/top-p) for a
    decode bundle. temperature == 0 degenerates to greedy argmax;
    ``base_seed`` is the bundle's noise root — per-request seeds fold
    into it, so two servers over the same weights with different
    base seeds sample independently. Noise derivation (and why the
    executor step key deliberately stays out of it):
    ops/spec_ops.py module docstring."""

    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    base_seed: int = 0

    def validate(self):
        if self.temperature < 0:
            raise ValueError(f"temperature must be >= 0, got "
                             f"{self.temperature}")
        if self.top_p <= 0 or self.top_p > 1.0:
            raise ValueError(f"top_p must be in (0, 1], got "
                             f"{self.top_p}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0

    def token(self) -> tuple:
        return ("sample", float(self.temperature), int(self.top_k),
                float(self.top_p), int(self.base_seed))


@dataclass(frozen=True)
class DraftConfig:
    """Draft model of a speculative (draft-and-verify) decode bundle
    (Leviathan et al.; the vLLM spec-decode worker family, PAPERS.md).
    The draft is a SMALLER enc-dec transformer co-resident with the
    target in ONE scope, so every parameter it creates is prefixed
    (``prefix``, default ``draft_``) — explicit names per the PTA050
    cross-build rule, and the builder pair-lints draft-vs-target
    persistable names with the PTA100 collision check at bundle
    build. ``k`` proposals per lane per step; k=0 degenerates to the
    plain one-token step (the r10 path).

    r19 adaptive-speculation knobs:

    * ``kind="ngram"`` replaces the draft MODEL with a model-free
      prompt-copy proposer: each tick proposes the continuation of
      the longest (up to ``ngram``-token) prompt/history suffix match
      ("prompt lookup decoding"; PAPERS.md). Proposals enter the SAME
      spec_accept verify path as deterministic one-hot
      "distributions" — exact under greedy AND sampled emission,
      because a one-hot draft distribution makes the Leviathan accept
      test exact (accept w.p. p(x); residual is p with x zeroed). No
      draft params, no draft KV, no draft model steps — the whole
      proposer is index arithmetic over per-lane prompt/history
      state.
    * ``k_options`` is the pre-built adaptive-k ladder: for every
      ``kv`` in it besides the default ``k``, the bundle builds a
      parallel serve-program set keyed ``("k", kv, base_key)`` over
      the SAME slot state, so the host controller
      (inference/spec_controller.py) re-buckets lanes across draft
      lengths by pure program selection — zero steady-state compiles
      by construction. ``k`` must itself be a rung of a non-empty
      ladder.
    * ``sharded`` opts the draft model INTO the bundle's tp plan
      (draft params + draft KV head-sharded). Default False: r17
      measured a sharded draft as all-overhead (a draft small enough
      to be cheap is small enough that its psums dominate), so the
      shipped placement shards only the TARGET.
    """

    d_model: int = 32
    n_heads: int = 2
    n_layers: int = 1
    d_inner: int = 64
    k: int = 3
    prefix: str = "draft_"
    kind: str = "model"       # "model" | "ngram"
    ngram: int = 2            # suffix-match length for kind="ngram"
    k_options: tuple = ()     # adaptive ladder; () = fixed-k bundle
    sharded: bool = False     # shard draft params/KV under tp

    def validate(self, max_out_len: int):
        if self.k < 0:
            raise ValueError(f"draft k must be >= 0, got {self.k}")
        if self.k + 1 > max_out_len:
            raise ValueError(
                f"draft k={self.k} proposes past the decode buffer "
                f"(max_out_len={max_out_len})")
        if self.kind not in ("model", "ngram"):
            raise ValueError(
                f"draft kind must be 'model' or 'ngram', got "
                f"{self.kind!r}")
        if self.kind == "ngram":
            if self.ngram < 1:
                raise ValueError(
                    f"ngram suffix length must be >= 1, got "
                    f"{self.ngram}")
            if self.sharded:
                raise ValueError(
                    "DraftConfig(kind='ngram') has no draft params "
                    "to shard — sharded=True is meaningless")
        elif self.d_model % self.n_heads:
            raise ValueError(
                f"draft d_model={self.d_model} not divisible by "
                f"n_heads={self.n_heads}")
        if self.k_options:
            opts = tuple(int(v) for v in self.k_options)
            if list(opts) != sorted(set(opts)):
                raise ValueError(
                    f"k_options must be sorted unique ints, got "
                    f"{self.k_options!r}")
            for kv in opts:
                if kv < 0 or kv + 1 > max_out_len:
                    raise ValueError(
                        f"k_options entry {kv} out of range for "
                        f"max_out_len={max_out_len}")
            if self.k not in opts:
                raise ValueError(
                    f"default k={self.k} must be a rung of "
                    f"k_options={self.k_options!r} (the serve keys "
                    f"the controller starts from)")
            if self.k == 0:
                raise ValueError(
                    "adaptive bundles need a speculative DEFAULT "
                    "(k > 0): the k=0 rung is the degradation "
                    "target, not the build anchor — every draft.k>0 "
                    "gate (state specs, admissions) keys off the "
                    "default")

    def token(self) -> tuple:
        return ("spec", int(self.k), int(self.d_model),
                int(self.n_heads), int(self.n_layers),
                int(self.d_inner), self.prefix, self.kind,
                int(self.ngram),
                tuple(int(v) for v in self.k_options),
                int(self.sharded))


def cached_decoder_step(x, caches, cross, att_bias, d_model,
                        n_heads, d_inner, prefix="", q=1,
                        qkv_interleaved=False):
    """One KV-cached decoder-stack step over a [R,q,D] row batch
    (reference tests/unittests/dist_transformer.py:1498 fast_decode's
    cached decoder, factored so the whole-loop incremental program and
    the slot-pool single-step programs — dense AND paged — trace the
    IDENTICAL math; their token-for-token parity is structural, not
    coincidental).

    ``caches``: per-layer cache-access objects (_DenseLaneCache for
    q=1, _DenseSpanCache for the speculative q=k+1 verify step,
    _PagedLaneCache for either) owning the self-attention: the KV
    write and ``attend``, the context rows over the lane's cache (the
    dense objects share matmul/softmax/matmul over their vars, the
    paged object reads its pools in place).
    ``cross``: per-layer cross-access objects owning the attention
    over the prompt's encoder projections (_DenseCross over per-lane
    [R,H,S,Dh] vars; _PagedPromptCross reads the lanes' entries of the
    paged prompt table in place). ``att_bias`` is the
    0/-1e9 validity bias the dense objects add to their [R,H,q,maxT]
    attention scores — for q>1 it must be per-query-position causal
    ([R,1,q,maxT]: query j masks cache positions > t+j); the paged
    object masks the same positions from its lanes' counters and
    takes None. Param names are the
    explicit {prefix}dec{li}_* scheme shared with the training build
    (``prefix`` is how a speculative DRAFT model co-resides with the
    target in one scope without aliasing — the PTA100 contract).

    ``qkv_interleaved=True`` uses the head-interleaved fused weight
    ``{prefix}dec{li}_self_qkvh.w`` (columns ``[H, 3, Dh]``-major;
    see ShardingConfig and ``interleave_qkv_params``): the q/k/v
    decomposition becomes reshape → local split → squeeze →
    transpose, so the fused projection column-shards under tp with
    zero per-tick reshard. Identical math to the contiguous layout —
    only the weight column ORDER differs.
    Returns the [R,q,D] hidden rows after all layers.
    """
    from . import transformer as T

    head_dim = d_model // n_heads
    scale = head_dim ** -0.5
    for li, cache in enumerate(caches):
        # --- cached causal self-attention (fused qkv) ---
        if qkv_interleaved:
            qkv = layers.fc(
                x, 3 * d_model, num_flatten_dims=2, bias_attr=False,
                param_attr=T._attn_proj_attr(f"{prefix}dec{li}_self",
                                             "qkvh", d_model))
            # [R,q,3D] -> [R,q,H,3,Dh]: H rides the MAJOR position
            # of the split group, so a column shard on dim 2 of the
            # fc output carries to the H axis (sharding_rules
            # rule_reshape major-carry); the 3-way split is then on
            # the UNSHARDED interleave axis — entirely local
            z = layers.reshape(qkv, [0, q, n_heads, 3, head_dim])
            zq, zk, zv = layers.split(z, 3, dim=3)
            qh, kh, vh = (
                layers.transpose(layers.squeeze(t, axes=[3]),
                                 perm=[0, 2, 1, 3])
                for t in (zq, zk, zv))  # [R,H,q,Dh]
        else:
            qkv = layers.fc(
                x, 3 * d_model, num_flatten_dims=2, bias_attr=False,
                param_attr=T._attn_proj_attr(f"{prefix}dec{li}_self",
                                             "qkv", d_model))
            qv, k, v = layers.split(qkv, 3, dim=2)
            qh = heads_of(qv, q, n_heads, head_dim)
            kh = heads_of(k, q, n_heads, head_dim)
            vh = heads_of(v, q, n_heads, head_dim)
        ctx = cache.attend(qh, kh, vh, att_bias, scale)  # [R,q,HD]
        attn_out = layers.fc(ctx, d_model, num_flatten_dims=2,
                             bias_attr=False,
                             param_attr=f"{prefix}dec{li}_self_out.w")
        x = T._add_norm(attn_out, x, 0.0, True,
                        name=f"{prefix}dec{li}_a")
        # --- cross attention against precomputed enc K/V ---
        q2 = layers.fc(
            x, d_model, num_flatten_dims=2, bias_attr=False,
            param_attr=T._attn_proj_attr(f"{prefix}dec{li}_cross",
                                         "q", d_model))
        ctx2 = cross[li].attend(q2, n_heads, scale)  # [R,q,HD]
        cross_out = layers.fc(
            ctx2, d_model, num_flatten_dims=2,
            bias_attr=False,
            param_attr=f"{prefix}dec{li}_cross_out.w")
        x = T._add_norm(cross_out, x, 0.0, True,
                        name=f"{prefix}dec{li}_b")
        # --- ffn ---
        ffn = T._ffn(x, d_model, d_inner, 0.0, True,
                     name=f"{prefix}dec{li}")
        x = T._add_norm(ffn, x, 0.0, True, name=f"{prefix}dec{li}_c")
    return x


# ---------------------------------------------------------------------------
# Whole-loop fronts (scalar step counter; per-request programs).
# ---------------------------------------------------------------------------
def build_greedy_decode_program(seq_len=16, max_out_len=16,
                                d_model=64, n_heads=4, n_layers=2,
                                d_inner=128, vocab=1000, start_id=0,
                                end_id=1, sharding=None):
    """Autoregressive greedy generation (reference
    tests/unittests/dist_transformer.py:1498 fast_decode — its
    while-op beam loop, at beam 1 — rebuilt as a lax.while_loop over
    the full decoder at static shapes: each step re-runs the
    causally-masked decoder on the [B, max_out_len] token buffer and
    writes position t+1 by a one-hot mask; positions past t are
    ignored by the causal mask, so no KV cache is needed for
    correctness — incremental caching is a perf upgrade, not a
    semantics change). Rows that emit end_id are frozen: every later
    position holds end_id, like the reference's early-finish
    handling.

    Weight sharing with a training program is by EXPLICIT param name
    (enc{i}_*/dec{i}_*/logits.w/…_word_emb) — build order and
    unique_name state are irrelevant.
    Returns (program, startup, feeds, out_ids_var).
    """
    import paddle_tpu as fluid

    from . import transformer as T

    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        src = layers.data("src_ids", shape=[seq_len], dtype="int64")
        enc = T._embed(src, vocab, d_model, max(seq_len, max_out_len),
                       0.0, True, "src_word_emb")
        for li in range(n_layers):
            enc = T.encoder_layer(enc, d_model, n_heads, d_inner, 0.0,
                                  is_test=True, name=f"enc{li}")

        positions = layers.cast(layers.range(0, max_out_len, 1),
                                "int64")
        tgt_buf = init_token_buffer(src, positions, max_out_len,
                                    start_id)
        # fixed-name counter so tests/benches can fetch the number of
        # loop iterations actually taken (the early-exit probe)
        counter = devtel.declare_decode_steps(main.global_block)
        limit = layers.fill_constant([1], "int64",
                                     float(max_out_len - 1))
        finished = layers.assign(layers.fill_constant_batch_size_like(
            src, [-1], "int64", 0.0))  # [B]: 1 once EOS emitted
        cond = layers.less_than(counter, limit)
        w = layers.While(cond)
        with w.block():
            dec = T._embed(tgt_buf, vocab, d_model,
                           max(seq_len, max_out_len), 0.0, True,
                           "tgt_word_emb")
            for li in range(n_layers):
                dec = T.decoder_layer(dec, enc, d_model, n_heads,
                                      d_inner, 0.0, is_test=True,
                                      name=f"dec{li}")
            logits_v = step_logits(dec, positions, counter,
                                   vocab)  # [B, V]
            emit_token_step(src, logits_v, positions, tgt_buf,
                            finished, counter, limit, cond,
                            max_out_len, end_id)
    if sharding is not None and sharding.enabled:
        sharding.validate(n_heads, vocab, d_model, d_inner)
        # params-only tp layout, mirroring the incremental front: the
        # full-recompute loop holds no persistable KV at all, so the
        # fused attention ops pick up head sharding purely from the
        # GSPMD-propagated param placements — which is exactly what
        # makes this front the sharded parity oracle for the paged
        # bundle (same placements, no cache layout to disagree on)
        annotate_sharded_program(
            main, tp_param_placements(n_layers, sharding),
            ((sharding.axis, sharding.tp),))
    return main, startup, ["src_ids"], tgt_buf


def build_incremental_decode_program(seq_len=16, max_out_len=16,
                                     d_model=64, n_heads=4,
                                     n_layers=2, d_inner=128,
                                     vocab=1000, start_id=0,
                                     end_id=1, sharding=None):
    """KV-cached autoregressive greedy generation — the incremental
    variant of build_greedy_decode_program (reference
    tests/unittests/dist_transformer.py:1498 fast_decode caches
    per-layer K/V the same way). Each step embeds ONE token, runs the
    decoder stack on that single row against cached self-attention
    K/V (written in place at position t) and precomputed
    cross-attention K/V, so per-step cost is O(maxT) instead of
    O(maxT^2) — token-for-token identical to the full-recompute
    program (asserted in tests).

    Weight sharing: the same explicit param names the training build
    and build_greedy_decode_program use — order-independent.

    Returns (program, startup, feeds, out_ids_var).
    """
    import paddle_tpu as fluid

    from . import transformer as T

    head_dim = d_model // n_heads
    maxT = max_out_len

    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        src = layers.data("src_ids", shape=[seq_len], dtype="int64")
        enc = T._embed(src, vocab, d_model, max(seq_len, maxT), 0.0,
                       True, "src_word_emb")
        for li in range(n_layers):
            enc = T.encoder_layer(enc, d_model, n_heads, d_inner, 0.0,
                                  is_test=True, name=f"enc{li}")

        # cross-attention K/V once per layer (explicitly named
        # dec{li}_cross_kv.w, shared with the training build)
        cross_kv = []
        for li in range(n_layers):
            kv = layers.fc(enc, 2 * d_model, num_flatten_dims=2,
                           bias_attr=False,
                           param_attr=T._attn_proj_attr(
                               f"dec{li}_cross", "kv", d_model))
            k, v = layers.split(kv, 2, dim=2)
            cross_kv.append(_DenseCross(
                heads_of(k, seq_len, n_heads, head_dim),
                heads_of(v, seq_len, n_heads, head_dim)))

        positions = layers.cast(layers.range(0, maxT, 1), "int64")
        posf = layers.cast(positions, "float32")
        pos_table = layers.assign(
            T._position_encoding(max(seq_len, maxT), d_model)[:maxT])

        tgt_buf = init_token_buffer(src, positions, maxT, start_id)
        # per-layer self-attn caches [B,H,maxT,D]
        caches = []
        for li in range(n_layers):
            kc = layers.assign(layers.fill_constant_batch_size_like(
                src, [-1, n_heads, maxT, head_dim], "float32", 0.0))
            vc = layers.assign(layers.fill_constant_batch_size_like(
                src, [-1, n_heads, maxT, head_dim], "float32", 0.0))
            caches.append((kc, vc))
        counter = devtel.declare_decode_steps(main.global_block)
        limit = layers.fill_constant([1], "int64", float(maxT - 1))
        finished = layers.assign(layers.fill_constant_batch_size_like(
            src, [-1], "int64", 0.0))
        cond = layers.less_than(counter, limit)
        w = layers.While(cond)
        with w.block():
            # embed ONLY the current token
            t_mask = layers.cast(layers.equal(positions, counter),
                                 "float32")  # [maxT]
            cur_tok = layers.reduce_sum(
                layers.elementwise_mul(tgt_buf,
                                       layers.cast(t_mask, "int64")),
                dim=1, keep_dim=True)  # [B,1]
            x = layers.embedding(cur_tok, size=[vocab, d_model],
                                 param_attr=ParamAttr(
                                     name="tgt_word_emb"))
            # lookup_table squeezes the trailing 1 of [B,1] ids:
            # restore the time axis for the [B,1,D] step row
            x = layers.unsqueeze(x, [1])
            x = layers.scale(x, scale=d_model ** 0.5)
            pos_t = layers.reduce_sum(
                layers.elementwise_mul(
                    pos_table, layers.unsqueeze(t_mask, [1]), axis=0),
                dim=0)  # [D]
            x = layers.elementwise_add(x, pos_t)  # [B,1,D]

            # attention validity: cached positions <= t
            att_mask = layers.scale(
                layers.cast(layers.greater_than(
                    posf, layers.cast(counter, "float32")),
                    "float32"), scale=-1e9)  # [maxT] 0 keep / -1e9 drop

            # one-hot write column at cache position t (axis 2 of the
            # [B,H,maxT,Dh] caches) and its complement
            m2 = layers.unsqueeze(t_mask, [1])  # [maxT,1]
            keepc = layers.unsqueeze(
                layers.elementwise_sub(
                    layers.fill_constant([maxT], "float32", 1.0),
                    t_mask), [1])
            cache_objs = [_DenseLaneCache(kc, vc, m2, keepc)
                          for kc, vc in caches]
            x = cached_decoder_step(x, cache_objs, cross_kv, att_mask,
                                    d_model, n_heads, d_inner)

            logits_v = layers.fc(
                layers.reshape(x, [0, d_model]), vocab,
                bias_attr=False, param_attr="logits.w")  # [B,V]
            emit_token_step(src, logits_v, positions, tgt_buf,
                            finished, counter, limit, cond, maxT,
                            end_id)
    if sharding is not None and sharding.enabled:
        sharding.validate(n_heads, vocab, d_model, d_inner)
        # params-only tp layout (the per-request KV caches here are
        # loop-local temporaries — the paged POOL is where per-device
        # KV bytes matter); the emit While's guard derives purely
        # from GSPMD-sharded values, which the prover classifies
        # value-uniform (absint GSPMD-uniform guards)
        annotate_sharded_program(
            main, tp_param_placements(n_layers, sharding),
            ((sharding.axis, sharding.tp),))
    return main, startup, ["src_ids"], tgt_buf


# ---------------------------------------------------------------------------
# Slot-pool front: bucketed admission + single-step/burst programs.
# ---------------------------------------------------------------------------
class DecodeStepBundle(_ServeBoundary):
    """Program set for slot-pool continuous batching (reference
    tests/unittests/dist_transformer.py:1498 fast_decode is the decode
    loop; the slot-pool scheduling follows the iteration-level /
    paged-slot serving discipline of Orca (OSDI'22) and vLLM
    (SOSP'23), PAPERS.md).

    All per-slot decode state is PERSISTABLE scope state shared by the
    programs (KV cache, token buffers, per-slot step counters,
    finished/active lane masks — written by one-hot scatter, the
    repo's loop-carried-history convention). The pool holds
    ``n_slots`` schedulable lanes plus ONE extra dustbin row (index
    ``n_slots``) that absorbs the padded rows of a bucketed admission
    batch — it decodes garbage harmlessly (every op is row-wise, and
    under the paged layout its pool writes are gated off) and is
    never scheduled.

    KV layout is selected by ``cache`` (CacheConfig): ``dense``
    per-lane buffers, or ``paged`` shared block pools + per-lane
    block-table/prompt-entry indirection (module docstring). Under
    the paged layout the block table and prompt-entry references are
    HOST-owned and read-only: the serving scheduler allocates
    blocks/entries (HostBlockPool/PromptPrefixCache) and FEEDS the
    tables to every serve dispatch, with the lane mask it decides
    (``fed_tables``; the unfused programs below still read all three
    as scope state, which whoever drives them writes) — the device
    programs never mutate the two tables.

    * ``prefills[A]`` — one admission program per bucket size A
      (power-of-two ladder up to n_slots): feeds ``src_ids`` [A,
      seq_len] + ``slots`` [A] (dustbin index for padded rows); runs
      the encoder over the WHOLE admission batch, installs each row's
      cross-attention K/V (dense: one-hot matmul scatter into the
      lane rows; paged: masked_pool_write into the fed
      ``prompt_slots`` entries), resets the slots' decode state, and
      raises their active flags. ``prefill`` aliases the smallest
      bucket. Paged bundles also carry ``hit_prefills[A]`` —
      encoder-free admissions for prompts whose entry is already
      cached (the prefix-reuse fast path: lane reset only).
    * ``step`` — no feeds; advances EVERY lane one token in one
      dispatch via the shared ``cached_decoder_step`` body.
    * ``serves[key]`` — the fused scheduler-cycle programs: the
      admission body (absent at key 0) followed by a While that runs
      the step body until ``n_steps`` ticks ran or the live-lane
      count drops to ``min_active`` (both fed as [1] int64). Keys are
      admission buckets (ints) for dense bundles and ``("hit"|"miss",
      A)`` tuples (plus 0) for paged ones; ``serve_feed_spec(key)``
      names each program's feed signature (the paged bundles' ends in
      the fed tables), and every one ends by packing ``serve_row``,
      the one array a scheduler fetches. Chunked-prefill bundles
      (``cache.chunk_tokens > 0``) additionally carry ``("chunked",
      p)`` programs — phase p of the incremental encoder over ONE
      prompt chunk, fused with the same decode While so live lanes
      keep ticking while the chunk computes (the two-tier schedule).

    ``state`` maps logical names ('tok_buf', 'step', 'finished',
    'active', and for paged 'block_tab'/'prompt_ref') to the scope
    var names; ``init_slot_state(scope)`` seeds the pool. The
    returned ``startup`` holds param initializers only — serving runs
    against an already-trained scope and must NOT run it.

    Weight sharing: the explicit enc{i}_*/dec{i}_*/logits.w/…_word_emb
    names — order-independent with the train and whole-loop builds.
    """

    def __init__(self, prefills, step, serves, startup, state,
                 n_slots, seq_len, max_out_len, start_id, end_id,
                 cache=None, hit_prefills=None, sampling=None,
                 draft=None, cow=None, probe=None, fed_tables=(),
                 serve_row=None):
        self.fed_tables = tuple(fed_tables)
        self.serve_row = serve_row
        self.prefills = dict(prefills)   # bucket size A -> Program
        self.prefill = self.prefills[min(self.prefills)]
        self.hit_prefills = dict(hit_prefills or {})
        self.step = step
        self.serves = dict(serves)       # key -> Program (see docstring)
        self.startup = startup
        self.state = dict(state)
        self.n_slots = n_slots
        self.dustbin = n_slots           # the padded-admission row
        self.seq_len = seq_len
        self.max_out_len = max_out_len
        self.start_id = start_id
        self.end_id = end_id
        self.cache = cache or CacheConfig()
        self.sampling = sampling         # SamplingConfig | None
        self.draft = draft               # DraftConfig | None
        self.cow = cow                   # COW block-copy Program
        self.probe = probe               # probe-step Program
        self.sharding = None             # ShardingConfig | None
        self.sharding_plan = None        # core.sharding_plan plan
        self._state_specs = {}

    def programs(self):
        """Every program of the bundle, in a stable order (prefills,
        hit prefills, step, serves) — the sweep surface for sharding
        annotation/placement and zoo registration."""
        out = [p for _a, p in sorted(self.prefills.items())]
        out += [p for _a, p in sorted(self.hit_prefills.items())]
        out.append(self.step)
        if self.cow is not None:
            out.append(self.cow)
        if self.probe is not None:
            out.append(self.probe)
        out += [p for _k, p in sorted(self.serves.items(),
                                      key=lambda kv: str(kv[0]))]
        return out

    @property
    def spec_k(self) -> int:
        """Draft proposals per lane per step (0 = plain decode)."""
        return self.draft.k if self.draft is not None else 0

    @property
    def spec_k_options(self) -> tuple:
        """The pre-built adaptive-k ladder (empty on fixed-k and
        plain bundles). Non-empty means serves carries a ("k", kv,
        base_key) variant set per non-default rung and the host
        controller may re-bucket across them compile-free."""
        if self.draft is None:
            return ()
        return tuple(int(v) for v in self.draft.k_options)

    @property
    def chunk_phase_keys(self):
        """The ("chunked", p) serve keys in phase order (empty on
        non-chunked bundles). The host drives ONE prompt through
        them phase-major: run phase p at EVERY chunk cursor before
        advancing to phase p+1 — attention phases read the full
        staged K/V of their layer, so a later phase may not start
        until the earlier one covered the whole prompt (the
        scheduler's chunk-job state machine walks exactly this
        order; total ticks = n_chunks * len(chunk_phase_keys))."""
        return sorted((k for k in self.serves
                       if isinstance(k, tuple) and k[0] == "chunked"),
                      key=lambda kv: kv[1])

    @property
    def tokens_per_tick(self) -> int:
        """Max tokens ONE device tick can emit per lane — the paged
        scheduler sizes block coverage by this (k accepted proposals
        + the correction/bonus token). Adaptive bundles size by the
        ladder's TOP rung: the controller may select it any
        dispatch."""
        return max((self.spec_k,) + self.spec_k_options) + 1

    @property
    def needs_seeds(self) -> bool:
        """True when admissions must feed per-request noise seeds
        (sampled emission lanes, or any speculative bundle — the
        acceptance draws are keyed on them)."""
        return self.sampling is not None or self.draft is not None

    def cache_token(self) -> tuple:
        """Content identity for server_fingerprint/compile-cache
        keys: KV layout (CacheConfig.token) PLUS the speculative and
        sampling configs — a spec bundle and a plain bundle over the
        same weights (or two spec bundles differing only in k or
        temperature) serve different token streams and must never
        dedupe or hot-swap as 'same model'."""
        tok = self.cache.token()
        if self.draft is not None:
            tok = tok + self.draft.token()
        if self.sampling is not None:
            tok = tok + self.sampling.token()
        if self.sharding is not None and self.sharding.enabled:
            # mesh shape + axis: a tp-sharded and a dense build over
            # the same weights serve different executables on
            # different device footprints — they must never dedupe
            # or hot-swap as "same model" (the plan token additionally
            # separates DEVICE slices at the compile-cache layer)
            tok = tok + self.sharding.token()
        return tok

    def serve_feed_spec(self, key) -> List[tuple]:
        """Feed signature (name, shape, dtype) of ``serves[key]`` —
        the serving layer binds prepared handles from this: the
        key's admission feeds, the burst's two, then the scheduler's
        tables (``fed_tables``), which every key takes."""
        feed = [("n_steps", (1,), "int64"),
                ("min_active", (1,), "int64")] + self.table_feed_spec()
        if isinstance(key, tuple) and key and key[0] == "k":
            # adaptive-k variant: same admission body, same slot
            # state, same feeds — only the burst's draft length
            # differs (the whole point: re-bucketing is pure program
            # selection)
            return self.serve_feed_spec(key[2])
        if key == 0:
            return feed
        tier, A = key if isinstance(key, tuple) else ("miss", key)
        if tier == "radix":
            pre = [("hist_toks", (A, self.max_out_len), "int64"),
                   ("resume_steps", (A,), "int64"),
                   ("prefill_until", (A,), "int64"),
                   ("slots", (A,), "int64")]
            if self.needs_seeds:
                pre.append(("seeds", (A,), "int64"))
            return pre + feed
        if tier == "chunked":
            # A is the PHASE index p here (0 = embed, 1+2l = layer
            # l's kv projection, 2+2l = layer l's attn+ffn, 2L+1 =
            # final cross-projection install)
            pre = [("chunk_entry", (1,), "int64"),
                   ("chunk_pos", (1,), "int64")]
            if A == 0:
                pre.append(("chunk_toks",
                            (1, self.cache.chunk_tokens), "int64"))
            return pre + feed
        pre = []
        if tier == "miss" or self.spec_k > 0:
            # spec bundles feed src_ids on HIT admissions too: the
            # (tiny) draft encoder always runs so its per-lane
            # cross-KV exists — only the TARGET encoder is skipped
            pre.append(("src_ids", (A, self.seq_len), "int64"))
        pre.append(("slots", (A,), "int64"))
        if tier == "miss" and self.cache.layout == "paged":
            pre.append(("prompt_slots", (A,), "int64"))
        if self.needs_seeds:
            pre.append(("seeds", (A,), "int64"))
        return pre + feed

    def cow_feed_spec(self) -> List[tuple]:
        """Feed signature of the COW block-copy program (``cow``):
        per-row (src shared block, dst fresh exclusive block, gate).
        Padded rows feed gate 0 and dst -1 (out of range: dropped)."""
        rows = self.n_slots + 1
        return [("cow_src", (rows,), "int64"),
                ("cow_dst", (rows,), "int64"),
                ("cow_gate", (rows,), "float32")]

    def kv_state_bytes(self) -> int:
        """Total persistable KV bytes of the bundle (self + cross KV
        incl. table/indirection state; token/flag buffers excluded —
        identical across layouts). The capacity denominator for the
        requests-per-KV-byte bench metric."""
        total = 0
        for name, (shape, dt) in self._state_specs.items():
            short = name.split("/")[-1]
            if short.startswith(("self_", "cross_", "block_tab",
                                 "prompt_ref", "draft_self_",
                                 "draft_cross_")):
                total += int(np.prod(shape)) * np.dtype(dt).itemsize
        return total

    def init_slot_state(self, scope):
        """Seed the pool state in `scope` (idle slots: finished=1,
        active=0 — they step harmlessly until admitted; paged
        prompt_ref points every lane at the dustbin entry)."""
        for name, (shape, dt) in self._state_specs.items():
            if name == self.state["finished"]:
                scope._set(name, np.ones(shape, dt))
            elif name == self.state.get("prompt_ref"):
                scope._set(name, np.full(shape,
                                         self.cache.n_prompt_entries,
                                         dt))
            else:
                scope._set(name, np.zeros(shape, dt))


class DecoderOnlyStepBundle(_ServeBoundary):
    """Program set of a DECODER-ONLY model on the slot pool (the
    builder in models/glm_moe_dsa.py): no encoder, no cross-attention
    prompt table. A request's prompt goes into its lane's OWN paged
    cache in chunks; the radix tree over prompt tokens is the only
    prefix cache. What the servers read of a DecodeStepBundle is here
    under the same names (`serves`, `state`, `n_slots`, `dustbin`,
    `max_out_len`, `end_id`, `cache`, `init_slot_state`,
    `serve_feed_spec`, `fed_tables`: the block table and the lane
    mask, `serve_row`: whose tail is the experts' counters);
    `decoder_only` tells PagedContinuousGenerationServer to plan by
    prompt length.

    * ``serves[0]`` — the tick-only program: a While of decode ticks,
      each advancing every live lane one token.
    * ``serves[PREFILL]`` — prompt chunks, one after another, largest
      size first: for each of ``chunk_sizes`` up to ``max_chunks``
      chunks of at most that many tokens (each a lane, a start position
      and a length: the chunk's latent and indexer-key rows land in the
      lane's blocks, its queries see the cached prefix and the chunk
      under the causal mask); then the admission of up to
      ``max_chunks`` lanes whose prompt is now cached but for its last
      token (token buffer, base position, limit, active flag); then
      the same While of ticks.

    Position 0 of a lane's row of ``tok_buf`` holds the prompt's last
    token, at cache position ``base``; tick s reads position s of the
    row, writes cache position base + s, and emits position s + 1. A
    lane ends after ``limit`` tokens (its request's max_new_tokens) or
    at the end token. ``max_out_len`` is the row's length: the most
    new tokens a request may ask for, plus one. ``context`` is the
    most positions (prompt and reply) a lane's table can address."""

    decoder_only = True
    fed_tables = ("block_tab", "active")
    PREFILL = ("prefill", 0)    # the serve key of the prefill program
    seq_len = None              # prompts come in their own length
    spec_k = 0
    spec_k_options = ()
    tokens_per_tick = 1
    needs_seeds = False
    sharding = None
    sharding_plan = None

    def __init__(self, serves, startup, state, state_specs, n_slots,
                 max_out_len, context, end_id, cache, chunk_sizes,
                 max_chunks, serve_row, probes=None, selection_size=0,
                 lane_state=()):
        self.serve_row = serve_row
        self.moe_keys = self.moe_keys_of(state)
        self.serves = dict(serves)
        self.startup = startup
        self.state = dict(state)
        self._state_specs = dict(state_specs)
        self.n_slots = n_slots
        self.dustbin = n_slots
        self.max_out_len = max_out_len
        self.context = context
        self.end_id = end_id
        self.start_id = None
        self.cache = cache
        self.chunk_sizes = tuple(sorted(chunk_sizes))
        self.max_chunks = max_chunks
        # {"selected": {layer: var}, "chosen": {layer: var}}: what the
        # last tick of a lane selected and how its ticks were routed
        self.probes = probes or {}
        # positions a query attends at most (0: all it has cached)
        self.selection_size = selection_size
        # state of the second kind: fixed-size, indexed by lane and not
        # by block table (a state-space layer's scan state and
        # convolution tail). None where the bundle has none; a server
        # then may map cached prefix blocks into a lane, which it may
        # not where the state at the prefix's end exists nowhere
        self.lane_state = self._lane_state_of(lane_state)

    @staticmethod
    def moe_keys_of(state) -> tuple:
        """The experts' counters of the state map `state` (logical
        names), as they end the serve row: they ride every dispatch,
        because a scope read from another thread would find the state
        given to a running step."""
        return ("moe_pairs", "moe_hit") + tuple(sorted(
            k for k in state if k.startswith("moe_load")))

    def _lane_state_of(self, names):
        """{"names", "shapes" (a lane's), "bytes_per_lane"} of the
        state vars `names` ([rows, ...] each), or None."""
        import jax.numpy as jnp

        if not names:
            return None
        shapes = {n: tuple(self._state_specs[n][0][1:]) for n in names}
        per_lane = sum(
            int(np.prod(shapes[n]))
            * jnp.dtype(self._state_specs[n][1]).itemsize for n in names)
        return {"names": tuple(names), "shapes": shapes,
                "bytes_per_lane": per_lane}

    def programs(self):
        return [p for _k, p in sorted(self.serves.items(),
                                      key=lambda kv: str(kv[0]))]

    def cache_token(self) -> tuple:
        return self.cache.token() + ("decoder_only", self.context,
                                     self.chunk_sizes, self.max_chunks)

    def serve_feed_spec(self, key) -> List[tuple]:
        feed = [("n_steps", (1,), "int64"),
                ("min_active", (1,), "int64")] + self.table_feed_spec()
        if key == 0:
            return feed
        a = self.max_chunks
        chunks = [spec for c in self.chunk_sizes for spec in (
            (f"chunk_toks_{c}", (a, c), "int64"),
            (f"chunk_lane_{c}", (a,), "int64"),
            (f"chunk_pos_{c}", (a,), "int64"),
            (f"chunk_len_{c}", (a,), "int64"),
            (f"n_chunks_{c}", (1,), "int64"))]
        return chunks + [("admit_slots", (a,), "int64"),
                         ("admit_tok", (a,), "int64"),
                         ("admit_base", (a,), "int64"),
                         ("admit_limit", (a,), "int64")] + feed

    def kv_state_bytes(self) -> int:
        import jax.numpy as jnp

        return sum(int(np.prod(shape)) * jnp.dtype(dt).itemsize
                   for name, (shape, dt) in self._state_specs.items()
                   if name.endswith(POOL_MARK)
                   or name == self.state["block_tab"])

    def init_slot_state(self, scope):
        """Seed the pool state in `scope`: idle lanes finished and not
        active, everything else zero. The pools and the lanes' state
        are made on the device (the latent pool of a deployment is
        gigabytes, and so is the scan state of its lanes)."""
        import jax.numpy as jnp

        on_device = set(self.lane_state["names"]) if self.lane_state \
            else ()
        for name, (shape, dt) in self._state_specs.items():
            if name.endswith(POOL_MARK) or name in on_device:
                scope._set(name, jnp.zeros(shape, dt))
            elif name == self.state["finished"]:
                scope._set(name, np.ones(shape, dt))
            else:
                scope._set(name, np.zeros(shape, dt))


def _slot_state_specs(prefix, rows, maxT, seq_len, n_heads,
                      head_dim, n_layers, cache, sampling=None,
                      draft=None, vocab=None):
    specs = {
        f"{prefix}tok_buf": ((rows, maxT), "int64"),
        f"{prefix}step": ((rows,), "int64"),
        f"{prefix}finished": ((rows,), "int64"),
        f"{prefix}active": ((rows,), "int64"),
    }
    if sampling is not None or draft is not None:
        # per-lane noise seed, written at admission from the fed
        # per-request seeds — the (request, position) key channel
        specs[f"{prefix}seed"] = ((rows,), "int64")
    if draft is not None and draft.k > 0:
        if draft.kind == "model":
            dh = draft.d_model // draft.n_heads
            # the draft's self-KV stays DENSE per-lane in BOTH target
            # layouts (the draft is small — that is the point; paging
            # it would buy bytes nobody is short of), its cross-KV is
            # per-lane too (the draft encoder re-runs even on
            # prefix-HIT admissions, so no pooled entries to refcount)
            for li in range(draft.n_layers):
                specs[f"{prefix}draft_self_k{li}"] = (
                    (rows, draft.n_heads, maxT, dh), "float32")
                specs[f"{prefix}draft_self_v{li}"] = (
                    (rows, draft.n_heads, maxT, dh), "float32")
                specs[f"{prefix}draft_cross_k{li}"] = (
                    (rows, draft.n_heads, seq_len, dh), "float32")
                specs[f"{prefix}draft_cross_v{li}"] = (
                    (rows, draft.n_heads, seq_len, dh), "float32")
        else:
            # ngram proposer: no model, no KV — just the per-lane
            # prompt copy the suffix matcher scans (tok_buf already
            # holds the generated history)
            specs[f"{prefix}prompt_toks"] = ((rows, seq_len),
                                             "int64")
        # device-side speculative accounting ([1] int64 RMW counters;
        # the serving layer deltas them per dispatch): proposals
        # offered / accepted / tokens emitted / draft vs target model
        # steps — the observability satellite's raw series
        for c in ("spec_proposed", "spec_accepted", "spec_emitted",
                  "spec_draft_steps", "spec_target_steps"):
            specs[f"{prefix}{c}"] = ((1,), "int64")
        # PER-LANE acceptance accounting (the adaptive-k controller's
        # signal): accepted proposals and spec ticks per lane,
        # cumulative since init — the controller deltas them per
        # dispatch to estimate each lane's acceptance rate
        specs[f"{prefix}spec_lane_accepted"] = ((rows,), "int64")
        specs[f"{prefix}spec_lane_ticks"] = ((rows,), "int64")
        if draft.k_options:
            # per-rung tick counters for the pre-built k ladder
            # (@TEL: PTA180 contract, devtel fetch/stats for free)
            specs.update(devtel.spec_k_counter_specs(
                prefix, draft.k_options))
    # device-side flight data (observability/devtel.py): [1] int64
    # RMW counters every program of the bundle declares — ticks,
    # occupancy integral, burst exit reasons, admission-tier counts.
    # The @TEL name mark puts them under checker PTA180's contract.
    specs.update(devtel.counter_specs(prefix,
                                      cache.layout == "paged",
                                      chunked=cache.chunked))
    if cache.layout == "dense":
        for li in range(n_layers):
            specs[f"{prefix}self_k{li}"] = (
                (rows, n_heads, maxT, head_dim), "float32")
            specs[f"{prefix}self_v{li}"] = (
                (rows, n_heads, maxT, head_dim), "float32")
            specs[f"{prefix}cross_k{li}"] = (
                (rows, n_heads, seq_len, head_dim), "float32")
            specs[f"{prefix}cross_v{li}"] = (
                (rows, n_heads, seq_len, head_dim), "float32")
        return specs
    NP = cache.pages(maxT)
    E = cache.n_prompt_entries
    specs[f"{prefix}block_tab"] = ((rows, NP), "int32")
    specs[f"{prefix}prompt_ref"] = ((rows,), "int32")
    # teacher-forcing horizon per lane: while step+1 < prefill_until
    # the lane re-plays its (admission-written) token-buffer history —
    # KV is written, logits are computed, but the emitted token never
    # lands and EOS never latches. 0 (the idle/cold default) makes
    # every tick a real decode tick, so non-radix admissions are
    # untouched by construction. This is what lets a radix admission
    # chunk-prefill ONLY the divergent tail of a resumed chat turn.
    specs[f"{prefix}prefill_until"] = ((rows,), "int64")
    if cache.chunked:
        # chunked-prefill staging: per-PROMPT-ENTRY activation rows
        # the phase programs hand forward between ticks. The encoder
        # is bidirectional (layer l+1 needs ALL of layer l), so a
        # resumable prefill must stage whole-prompt activations —
        # indexed by prompt-entry id like the cross pools (+1
        # dustbin), NOT by lane: the entry is host-exclusive for the
        # whole prefill, and the staging row is dead once the final
        # phase installs the cross-KV. a/b ping-pong across layers;
        # kv holds the concat(K,V) self-attn projection of the layer
        # being chunked (attention needs K/V at ALL positions before
        # any query chunk can run — that is the phase split).
        d_model = n_heads * head_dim
        specs[f"{prefix}chunk_stage_a{POOL_MARK}"] = (
            (E + 1, seq_len, d_model), "float32")
        specs[f"{prefix}chunk_stage_b{POOL_MARK}"] = (
            (E + 1, seq_len, d_model), "float32")
        specs[f"{prefix}chunk_stage_kv{POOL_MARK}"] = (
            (E + 1, seq_len, 2 * d_model), "float32")
    if vocab is not None and (draft is None or draft.k == 0):
        # the beam/probe front's full next-token distribution, one
        # softmax row per lane, refreshed by the probe step program —
        # host-side beam branching reads it instead of re-running the
        # decoder outside the bundle
        specs[f"{prefix}probe_probs"] = ((rows, vocab), "float32")
    # one row a cache cell, heads x head_dim flat on the minor axis: the
    # shape the tick gathers and scatters in, and one the TPU neither
    # pads (a minor axis of Dh=64 fills half of its 128 lanes) nor
    # relayouts on the way into and out of a dispatch
    cells = (cache.n_blocks * cache.block_size, n_heads * head_dim)
    for li in range(n_layers):
        specs[f"{prefix}self_k{li}{POOL_MARK}"] = (cells, "float32")
        specs[f"{prefix}self_v{li}{POOL_MARK}"] = (cells, "float32")
        # +1: the dustbin entry padded admission rows scatter into
        # (entry-major rows of the same width: the tick reads a lane's
        # entry as one block of seq_len rows, as it reads the cells)
        specs[f"{prefix}cross_k{li}{POOL_MARK}"] = (
            (E + 1, seq_len, n_heads * head_dim), "float32")
        specs[f"{prefix}cross_v{li}{POOL_MARK}"] = (
            (E + 1, seq_len, n_heads * head_dim), "float32")
    return specs


def _declare_slot_state(block, specs):
    """Declare the persistable slot-pool vars in a program's global
    block (all programs bind the SAME scope values by name). Concrete
    shapes + dtypes keep them carry-declarable (checker PTA090)."""
    return {name: block.create_var(name=name, shape=shape, dtype=dt,
                                   persistable=True,
                                   stop_gradient=True)
            for name, (shape, dt) in specs.items()}


def tp_param_placements(n_layers: int, sharding: "ShardingConfig",
                        prefix: str = "") -> Dict[str, dict]:
    """{param name -> {dim: axis}} of the Megatron column/row-parallel
    decoder layout for the explicit ``{prefix}dec{li}_*`` name scheme
    (ShardingConfig docstring: the CONTIGUOUS fused qkv / fused
    cross-kv stay replicated — their fused-axis split crosses tp
    shard boundaries; biases stay replicated — GSPMD slices them
    locally for free). With ``sharding.qkv_interleaved`` the
    head-interleaved fused weight ``dec{li}_self_qkvh.w``
    column-shards: its ``[H, 3, Dh]``-major column order puts heads
    on the MAJOR axis of the decomposition reshape, so the shard
    carries through reshape/split/squeeze/transpose with zero
    reshard (the r17 leftover, closed)."""
    ax = sharding.axis
    out: Dict[str, dict] = {f"{prefix}logits.w": {1: ax}}
    for li in range(n_layers):
        if sharding.qkv_interleaved:
            out[f"{prefix}dec{li}_self_qkvh.w"] = {1: ax}
        out[f"{prefix}dec{li}_self_out.w"] = {0: ax}
        out[f"{prefix}dec{li}_cross_q.w"] = {1: ax}
        out[f"{prefix}dec{li}_cross_out.w"] = {0: ax}
        out[f"{prefix}dec{li}_fc1.w"] = {1: ax}
        out[f"{prefix}dec{li}_fc2.w"] = {0: ax}
    return out


def interleave_qkv_params(scope, n_layers: int, n_heads: int,
                          d_model: int, prefix: str = ""):
    """Convert trained CONTIGUOUS fused-qkv weights
    (``{prefix}dec{li}_self_qkv.w``, columns ``[3, H, Dh]``-major) to
    the HEAD-INTERLEAVED layout (``{prefix}dec{li}_self_qkvh.w``,
    columns ``[H, 3, Dh]``-major) a ``qkv_interleaved`` decode build
    reads — a pure column permutation, so the decode math is
    bit-identical to the contiguous layout (asserted by the bundle
    parity tests). Writes the converted weights into ``scope`` and
    returns the new param names. Reference counterpart:
    transpiler/distribute_transpiler.py:69 VarBlock param slicing —
    there a runtime program rewrite, here an offline weight re-layout
    feeding a declaratively sharded build."""
    head_dim = d_model // n_heads
    out = []
    for li in range(n_layers):
        src = f"{prefix}dec{li}_self_qkv.w"
        dst = f"{prefix}dec{li}_self_qkvh.w"
        w = np.asarray(scope._get(src))
        d_in = w.shape[0]
        scope._set(dst, np.ascontiguousarray(
            w.reshape(d_in, 3, n_heads, head_dim)
             .transpose(0, 2, 1, 3)
             .reshape(d_in, 3 * d_model)))
        out.append(dst)
    return out


def _tp_state_placements(state_prefix, n_layers, cache, sharding
                         ) -> Dict[str, dict]:
    """{slot-state name -> {dim: axis}}: KV sharded along heads (dim
    1 of the dense ``[R, H, T, Dh]`` lane buffers and of the paged
    ``[NB*BS, H*Dh]`` self pool, dim 2 of the ``[E+1, S, H*Dh]``
    prompt table: heads are the major part of an ``H*Dh`` axis).
    Tables/masks/counters/draft state stay replicated —
    block tables in particular remain host-owned replicated int32, so
    the ownership story (PTA190/191) is untouched."""
    ax = sharding.axis
    out: Dict[str, dict] = {}
    for li in range(n_layers):
        if cache.layout == "dense":
            out[f"{state_prefix}self_k{li}"] = {1: ax}
            out[f"{state_prefix}self_v{li}"] = {1: ax}
            out[f"{state_prefix}cross_k{li}"] = {1: ax}
            out[f"{state_prefix}cross_v{li}"] = {1: ax}
        else:
            out[f"{state_prefix}self_k{li}{POOL_MARK}"] = {1: ax}
            out[f"{state_prefix}self_v{li}{POOL_MARK}"] = {1: ax}
            out[f"{state_prefix}cross_k{li}{POOL_MARK}"] = {2: ax}
            out[f"{state_prefix}cross_v{li}{POOL_MARK}"] = {2: ax}
    return out


def annotate_sharded_program(program, placements: Dict[str, dict],
                             mesh_axes, plan=None):
    """Wire ONE program into both halves of the sharded story from
    one placement table: the PROVER half (``absint.set_mesh`` + a
    ``mark_sharded`` pin per var present in the program, so
    PTA130/131/160/161 judge the real lowering) and the EXECUTION
    half (a shared ``core.sharding_plan.ShardingPlan`` attached for
    the Executor's jit in/out_shardings and cache-key tokens).
    Returns the plan (created when not passed) so a program family —
    every specialization of one bundle — shares one bind site."""
    from ..core import sharding_plan as sp

    absint.set_mesh(program,
                    absint.MeshConfig.make(**dict(mesh_axes)))
    blk = program.global_block
    for name, dims in placements.items():
        var = blk.vars.get(name) or blk._find_var_recursive(name)
        if var is None:
            continue  # this specialization never touches the var
        absint.mark_sharded(var, dims)
    if plan is None:
        plan = sp.ShardingPlan(tuple(mesh_axes), placements)
    sp.attach_plan(program, plan)
    return plan


def _apply_tp_sharding(bundle: "DecodeStepBundle",
                       sharding: "ShardingConfig", n_layers: int):
    """Annotate every program of a bundle with the tp layout and
    attach ONE shared execution plan (ShardingConfig docstring).
    The DRAFT model of a speculative bundle joins the plan only when
    ``draft.sharded`` opted it in (DraftConfig: r17 measured a
    sharded draft as all-overhead, so target-only is the default
    placement the controller hands out)."""
    placements = dict(tp_param_placements(n_layers, sharding))
    prefix = _state_prefix_of(bundle)
    placements.update(_tp_state_placements(
        prefix, n_layers, bundle.cache, sharding))
    draft = bundle.draft
    if draft is not None and draft.sharded and draft.k > 0:
        if draft.n_heads % sharding.tp or \
                draft.d_model % sharding.tp or \
                draft.d_inner % sharding.tp:
            raise ValueError(
                f"DraftConfig(sharded=True) needs draft "
                f"n_heads/d_model/d_inner divisible by tp="
                f"{sharding.tp}, got {draft.n_heads}/"
                f"{draft.d_model}/{draft.d_inner}")
        # the draft's fused qkv is never interleaved (it is not worth
        # a second weight layout for a model this small), so its
        # placements come from the contiguous view of the config
        dcfg = dataclasses.replace(sharding, qkv_interleaved=False)
        placements.update(tp_param_placements(
            draft.n_layers, dcfg, prefix=draft.prefix))
        # draft KV is dense per-lane [R, dH, T, dh] in both target
        # layouts — heads on dim 1
        for li in range(draft.n_layers):
            for nm in (f"draft_self_k{li}", f"draft_self_v{li}",
                       f"draft_cross_k{li}", f"draft_cross_v{li}"):
                placements[f"{prefix}{nm}"] = {1: sharding.axis}
    mesh_axes = ((sharding.axis, sharding.tp),)
    plan = None
    for prog in bundle.programs():
        plan = annotate_sharded_program(prog, placements, mesh_axes,
                                        plan=plan)
    bundle.sharding = sharding
    bundle.sharding_plan = plan
    return plan


def _state_prefix_of(bundle) -> str:
    """Recover the state prefix from any state entry ('@cb/' style:
    everything up to and including the last '/')."""
    name = bundle.state["tok_buf"]
    return name[:len(name) - len("tok_buf")]


def enc_param_placements(n_layers: int, sharding: "ShardingConfig",
                         prefix: str = "") -> Dict[str, dict]:
    """{param name -> {dim: axis}} for the ENCODER-side (prefill
    phase) stack: column/row-parallel ffn and row-parallel attention
    out-projections per encoder layer — prefill is MXU-bound, so the
    tp win is in the projection matmuls, where decode's plan
    (tp_param_placements) spends its placements on the KV bytes
    instead. The fused ``enc{l}_self_qkv.w`` and the cross-KV install
    ``dec{li}_cross_kv.w`` stay replicated for the same
    fused-axis-crosses-shards reason as the decoder's (ShardingConfig
    docstring)."""
    ax = sharding.axis
    out: Dict[str, dict] = {}
    for li in range(n_layers):
        out[f"{prefix}enc{li}_self_out.w"] = {0: ax}
        out[f"{prefix}enc{li}_fc1.w"] = {1: ax}
        out[f"{prefix}enc{li}_fc2.w"] = {0: ax}
    return out


def _prefill_state_placements(state_prefix, n_layers, cache, sharding
                              ) -> Dict[str, dict]:
    """Prefill-phase slot-state placements: the cross pools it WRITES
    sharded along heads (dim 2 of ``[E+1, S, H*Dh]``, heads major —
    the same tensor layout the decode plan reads, so the handoff is a
    device_put, not a re-layout) plus the chunk staging pools along
    d_model (the same heads-concat axis)."""
    ax = sharding.axis
    out: Dict[str, dict] = {}
    for li in range(n_layers):
        out[f"{state_prefix}cross_k{li}{POOL_MARK}"] = {2: ax}
        out[f"{state_prefix}cross_v{li}{POOL_MARK}"] = {2: ax}
    if cache.chunked:
        out[f"{state_prefix}chunk_stage_a{POOL_MARK}"] = {2: ax}
        out[f"{state_prefix}chunk_stage_b{POOL_MARK}"] = {2: ax}
        out[f"{state_prefix}chunk_stage_kv{POOL_MARK}"] = {2: ax}
    return out


def apply_phase_sharding(bundle: "DecodeStepBundle",
                         prefill_sharding: "ShardingConfig",
                         decode_sharding: "ShardingConfig",
                         n_layers: int):
    """Disaggregated prefill/decode sharding (DistServe, Zhong et al.
    OSDI'24 — PAPERS.md): the bundle's ``("chunked", p)`` phase
    programs get the PREFILL plan (MXU-bound: tp over the encoder
    projections, ``enc_param_placements``) while every other program
    gets the DECODE plan (bandwidth-bound: tp over KV bytes,
    ``tp_param_placements``) — two ``ShardingPlan``s whose tokens
    differ by placements AND, once bound to disjoint slices, by
    device ids, so no executable, disk-cache entry, or
    server_fingerprint can ever dedup across phases.

    Returns ``(prefill_plan, decode_plan)``. The decode plan is also
    attached as ``bundle.sharding_plan`` (what the serving layer's
    placement step binds); the prefill plan rides as
    ``bundle.prefill_plan`` and binds at
    ``runtime.placement.place_disaggregated_bundle``."""
    if not bundle.cache.chunked:
        raise ValueError(
            "apply_phase_sharding needs a chunked-prefill bundle "
            "(CacheConfig(chunk_tokens=C)) — without ('chunked', p) "
            "programs there is no prefill phase to carve out")
    prefix = _state_prefix_of(bundle)
    dec_placements = dict(tp_param_placements(n_layers,
                                              decode_sharding))
    dec_placements.update(_tp_state_placements(
        prefix, n_layers, bundle.cache, decode_sharding))
    pre_placements = dict(enc_param_placements(n_layers,
                                               prefill_sharding))
    pre_placements.update(_prefill_state_placements(
        prefix, n_layers, bundle.cache, prefill_sharding))
    dec_axes = ((decode_sharding.axis, decode_sharding.tp),)
    pre_axes = ((prefill_sharding.axis, prefill_sharding.tp),)
    dec_plan = None
    pre_plan = None
    chunk_progs = {id(p) for k, p in bundle.serves.items()
                   if isinstance(k, tuple) and k[0] == "chunked"}
    for prog in bundle.programs():
        if id(prog) in chunk_progs:
            pre_plan = annotate_sharded_program(
                prog, pre_placements, pre_axes, plan=pre_plan)
        else:
            dec_plan = annotate_sharded_program(
                prog, dec_placements, dec_axes, plan=dec_plan)
    pre_plan.label = "prefill"
    dec_plan.label = "decode"
    bundle.sharding = decode_sharding
    bundle.sharding_plan = dec_plan
    bundle.prefill_plan = pre_plan
    return pre_plan, dec_plan


def place_sharded_bundle(bundle: "DecodeStepBundle", scope,
                         devices=None) -> int:
    """The one-time serving placement step for a sharded bundle: bind
    the plan to a device slice (default: the first tp devices) and
    device_put EVERY persistable the bundle's programs read — sharded
    per the placement table, replicated otherwise — so steady-state
    dispatches never re-transfer params and per-device KV actually
    shrinks. Returns the number of arrays placed. Call AFTER params
    are trained/loaded and ``init_slot_state`` ran."""
    from ..core import sharding_plan as sp

    plan = getattr(bundle, "sharding_plan", None)
    if plan is None:
        raise ValueError("bundle has no sharding plan — build it "
                         "with ShardingConfig(tp>1)")
    ids_before = plan._device_ids
    plan.bind(devices)
    rebound = plan._device_ids != ids_before
    names = set(bundle._state_specs)
    for prog in bundle.programs():
        blk = prog.global_block
        for name, var in blk.vars.items():
            if var.persistable:
                names.add(name)
        # version-bump ONLY on a real (re)bind: prepared handles
        # bound against the old device slice must re-resolve, but a
        # second server over the SAME placement (fresh scope, same
        # slice) must hit the warmed executables — an unconditional
        # bump recompiled every serve program per server
        # construction (caught by bench.py sharded's zero-steady-
        # state-compiles assertion)
        if rebound or sp.plan_of(prog) is not plan:
            sp.attach_plan(prog, plan)
    return plan.place_state(scope, sorted(names))


def place_sharded_program(program, scope, devices=None) -> int:
    """``place_sharded_bundle`` for a single whole-loop program
    (build_incremental_decode_program(sharding=...)): bind the plan
    and device_put the program's persistables (params; the loop's KV
    caches are trace-local temporaries)."""
    from ..core import sharding_plan as sp

    plan = sp.plan_of(program)
    if plan is None:
        raise ValueError("program has no sharding plan — build it "
                         "with sharding=ShardingConfig(tp>1)")
    ids_before = plan._device_ids
    plan.bind(devices)
    names = sorted(v.name for v in program.list_vars()
                   if getattr(v, "persistable", False))
    if plan._device_ids != ids_before:
        sp.attach_plan(program, plan)  # re-bound: re-resolve handles
    return plan.place_state(scope, names)


def _param_probe(prefix, seq_len, max_out_len, d_model, n_heads,
                 n_layers, d_inner, vocab):
    """Tiny program whose only job is to CREATE every parameter the
    (prefix-named) enc-dec decode stack owns, through the REAL
    param-creating code paths (T.encoder_layer / cached_decoder_step /
    the embeddings and the logits fc) so the name set cannot drift
    from the actual builders — the draft-vs-target PTA100 pair lint
    (_pair_lint_draft_target) reads its persistables."""
    import paddle_tpu as fluid

    from . import transformer as T

    head_dim = d_model // n_heads
    maxT = max_out_len
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        src = layers.data("src_ids", shape=[1, seq_len],
                          dtype="int64", append_batch_size=False)
        enc = T._embed(src, vocab, d_model, max(seq_len, maxT), 0.0,
                       True, f"{prefix}src_word_emb")
        for li in range(n_layers):
            enc = T.encoder_layer(enc, d_model, n_heads, d_inner,
                                  0.0, is_test=True,
                                  name=f"{prefix}enc{li}")
        cross = []
        for li in range(n_layers):
            kvp = layers.fc(enc, 2 * d_model, num_flatten_dims=2,
                            bias_attr=False,
                            param_attr=T._attn_proj_attr(
                                f"{prefix}dec{li}_cross", "kv",
                                d_model))
            k, v = layers.split(kvp, 2, dim=2)
            cross.append(_DenseCross(
                heads_of(k, seq_len, n_heads, head_dim),
                heads_of(v, seq_len, n_heads, head_dim)))
        ids = layers.assign(np.zeros((1, 1), "int64"))
        x = layers.unsqueeze(
            layers.embedding(ids, size=[vocab, d_model],
                             param_attr=ParamAttr(
                                 name=f"{prefix}tgt_word_emb")), [1])
        wm = layers.assign(np.zeros((1, 1, maxT, 1), "float32"))
        km = layers.assign(np.ones((1, 1, maxT, 1), "float32"))
        caches = [
            _DenseLaneCache(
                layers.assign(np.zeros((1, n_heads, maxT, head_dim),
                                       "float32")),
                layers.assign(np.zeros((1, n_heads, maxT, head_dim),
                                       "float32")), wm, km)
            for _ in range(n_layers)]
        bias = layers.assign(np.zeros((maxT,), "float32"))
        x = cached_decoder_step(x, caches, cross, bias, d_model,
                                n_heads, d_inner, prefix=prefix)
        layers.fc(layers.reshape(x, [0, d_model]), vocab,
                  bias_attr=False, param_attr=f"{prefix}logits.w")
    return main


def _pair_lint_draft_target(draft, *, seq_len, max_out_len, d_model,
                            n_heads, n_layers, d_inner, vocab):
    """ModelRegistry-style PTA100 pair lint at bundle build: the
    speculative draft co-resides with the target in ONE scope, so ANY
    persistable name overlap between them is the aliasing/clobbering
    defect check_cross_model_collision exists for (same shape =
    silent weight aliasing — the draft would serve target weights and
    acceptance statistics would be garbage with no error anywhere).
    Raises with the formatted diagnostics on collision; a distinct
    ``draft.prefix`` keeps it silent."""
    from ..analysis.checkers import (ERROR,
                                     check_cross_model_collision,
                                     format_diagnostics)

    target = _param_probe("", seq_len, max_out_len, d_model, n_heads,
                          n_layers, d_inner, vocab)
    probe = _param_probe(draft.prefix, seq_len, max_out_len,
                         draft.d_model, draft.n_heads,
                         draft.n_layers, draft.d_inner, vocab)
    diags = [d for d in check_cross_model_collision(target, probe)
             if d.severity == ERROR]
    if diags:
        raise ValueError(
            f"speculative draft (prefix {draft.prefix!r}) collides "
            f"with the target model's persistables — co-residence in "
            f"one scope would alias/clobber weights (PTA100):\n"
            + format_diagnostics(diags))


def tel_add(sv, state_prefix, logical, delta):
    """Device-telemetry increment: var = var + delta on a bundle
    counter (observability/devtel.py registry); silently skipped for
    counters the bundle does not carry (tel_admit_hit on dense
    bundles). Shared by every slot-pool builder."""
    var = sv.get(f"{state_prefix}{logical}{devtel.TEL_MARK}")
    if var is None:
        return
    layers.assign(layers.elementwise_add(var, delta), output=var)


def lane_onehots(slots, A, rows):
    """One-hot masks over the fed slot ids of an admission of A rows:
    (oh [A, rows] float32, any_f, any_i, keep_f, keep_i [rows]). Padded
    rows all point at the dustbin, whose scatter-sum is garbage by
    design; min() clamps its multiplicity in the masks."""
    lane_range = layers.cast(layers.range(0, rows, 1), "int64")
    oh = layers.cast(
        layers.equal(lane_range,
                     layers.reshape(slots, [A, 1])),
        "float32")
    any_f = layers.elementwise_min(
        layers.reduce_sum(oh, dim=0),
        layers.fill_constant([rows], "float32", 1.0))
    any_i = layers.cast(any_f, "int64")
    keep_f = layers.elementwise_sub(
        layers.fill_constant([rows], "float32", 1.0), any_f)
    keep_i = layers.elementwise_sub(
        layers.fill_constant([rows], "int64", 1.0), any_i)
    return oh, any_f, any_i, keep_f, keep_i


def emit_lane_tokens(tok, tok_buf, stepv, fin, act, rows, maxT, end_id,
                     emit_flag=None, room_limit=None):
    """The per-lane emit tail of a slot-pool tick (emit_token_step
    vectorised over lane counters; the same freeze and write): `tok`
    [rows] int64 is what each lane's step chose. Finished lanes keep
    emitting end_id; the token lands at position step + 1 of the
    lane's row of `tok_buf` (not where `emit_flag` is 0: a lane that
    replays its history); the EOS latch counts only lanes that
    advanced (`act`); a lane deactivates on EOS or when its step
    reaches `room_limit` ([1] or [rows] int64; default maxT - 1, the
    end of the buffer). Mutates tok_buf / stepv / fin / act in place;
    every slot-pool builder's step body ends here, so their emission
    cannot diverge."""
    ones_n = layers.fill_constant([rows], "int64", 1.0)
    if emit_flag is None:
        emit_flag = ones_n
    if room_limit is None:
        room_limit = layers.fill_constant([1], "int64", float(maxT - 1))
    positions = layers.cast(layers.range(0, maxT, 1), "int64")
    not_fin = layers.elementwise_sub(ones_n, fin)
    tok = layers.elementwise_add(
        layers.elementwise_mul(tok, not_fin),
        layers.cast(layers.scale(fin, scale=float(end_id)),
                    "int64"))
    # the EOS latch only counts lanes that actually ADVANCED this
    # tick (act gate): a host-paused paged lane (no KV block for
    # its next write) decodes a garbage token — its tok_buf write
    # is re-done correctly on resume, but an un-gated fin latch
    # would freeze the lane on garbage-EOS permanently
    new_fin = layers.elementwise_max(
        fin, layers.elementwise_mul(
            layers.elementwise_mul(act, emit_flag),
            layers.cast(layers.equal(
                tok, layers.fill_constant(
                    [1], "int64", float(end_id))), "int64")))
    next2 = layers.reshape(
        layers.elementwise_add(stepv, ones_n), [rows, 1])
    next_mask = layers.cast(layers.equal(positions, next2),
                            "int64")                   # [R,maxT]
    next_mask = layers.elementwise_mul(
        next_mask, layers.reshape(emit_flag, [rows, 1]))
    keep_tok = layers.elementwise_sub(
        layers.fill_constant([rows, maxT], "int64", 1.0),
        next_mask)
    new_step = layers.elementwise_add(stepv, act)  # gate by lane
    layers.assign(layers.elementwise_add(
        layers.elementwise_mul(tok_buf, keep_tok),
        layers.elementwise_mul(next_mask,
                               layers.reshape(tok, [rows, 1]))),
        output=tok_buf)
    layers.assign(new_step, output=stepv)
    # lanes auto-deactivate on EOS or buffer exhaustion — the
    # host retires a lane the moment its active flag drops
    room = layers.cast(layers.less_than(new_step, room_limit),
                       "int64")                        # [N]
    new_act = layers.elementwise_mul(
        layers.elementwise_mul(
            act, layers.elementwise_sub(ones_n, new_fin)),
        room)
    layers.assign(new_act, output=act)
    layers.assign(new_fin, output=fin)


def build_serve_program(specs, state_prefix, pre_body, step_body, row,
                        mark=None, fed=()):
    """One fused scheduler-cycle program of a slot-pool bundle: the
    slot state declared, `pre_body(sv)` (an admission, a prefill chunk,
    or nothing), then a While that runs `step_body(sv)` until `n_steps`
    ticks ran or the live-lane count drops to `min_active` (both fed as
    [1] int64), then the burst's exit reason counted once, then the
    state a scheduler reads back packed into the one variable
    `row.name` (a ServeRow: the program's only fetch). Every serve
    program of every bundle has this shape, so the servers drive them
    alike, and a dispatch crosses the host boundary with one array each
    way beside its admission feeds.

    `fed` names the scheduler-owned tables of `specs` (logical names:
    'block_tab', 'prompt_ref', 'active') that ride the call as the
    feeds `fed_name(table)`: the program copies each into its state
    variable before `pre_body`, so the bodies and the While read (and,
    the lane mask, write: an admission raises a lane, a finished lane
    drops) the state variables they always did, the scope holds after
    a dispatch what the scheduler fed it, and no program reads a table
    from the scope that a scheduler would have to place there.
    `mark(sv)` annotates the declared state (ownership sources); of a
    fed table it annotates the feed, the host-owned source now, and the
    state variable's provenance is derived through the copy. The lane
    mask alone keeps its mark on the state variable too: the program
    rewrites it, and the pin is what holds through that."""
    import paddle_tpu as fluid

    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        sv = _declare_slot_state(prog.global_block, specs)
        fed_vars = {}
        for table in fed:
            name = f"{state_prefix}{table}"
            shape, dt = specs[name]
            fed_vars[name] = layers.data(
                fed_name(table), shape=list(shape), dtype=dt,
                append_batch_size=False)
        if mark is not None:
            mark({**sv, **fed_vars})
        for name, var in fed_vars.items():
            layers.assign(var, output=sv[name])
        mask = f"{state_prefix}active"
        if mask in fed_vars:
            absint.mark_pool_index_source(sv[mask], "lane_active")
        pre_body(sv)
        n_steps = layers.data("n_steps", shape=[1], dtype="int64",
                              append_batch_size=False)
        min_active = layers.data("min_active", shape=[1],
                                 dtype="int64",
                                 append_batch_size=False)
        act = sv[f"{state_prefix}active"]
        k = layers.fill_constant([1], "int64", 0)

        def _serve_cond(cond=None):
            # ticks remain AND live lanes exceed the exit
            # threshold: min(a, b) > 0
            out = layers.greater_than(
                layers.elementwise_min(
                    layers.elementwise_sub(n_steps, k),
                    layers.elementwise_sub(
                        layers.reduce_sum(act, keep_dim=True),
                        min_active)),
                layers.fill_constant([1], "int64", 0.0),
                cond=cond)
            # divergence-source annotation (analysis/absint.py
            # seed table): this predicate derives from the
            # per-lane active mask — the moment a lowering shards
            # LANES across a mesh axis it differs per device,
            # and the burst While becomes divergent control
            # flow. The prover (PTA130/131) uses the mark to
            # REJECT collectives/sharded values inside the burst
            # with a proof instead of a pattern guess. axes=
            # names the lane-sharding axis: on a tp-only mesh
            # (heads sharded, lanes replicated) the mark is
            # provably inert and the guard classifies from its
            # actual inputs — which is what lets the tp-sharded
            # serve programs carry their vocab-psum INSIDE the
            # burst legally (GSPMD-uniform control flow), while
            # any future lanes-sharding mesh flips this back to
            # proven-divergent automatically.
            absint.mark_divergence_source(out, "lane_active_mask",
                                          axes=(LANE_AXIS,))
            return out

        cond = _serve_cond()
        w = layers.While(cond)
        with w.block():
            step_body(sv)
            layers.increment(k, 1)
            _serve_cond(cond=cond)
        # devtel: classify THIS burst's exit exactly once, after
        # the While (k and act read their final loop values).
        # Precedence: ran all n_steps ticks > every lane idle >
        # live dropped to min_active — int arithmetic only, no
        # logical ops (the emit_token_step conjunction idiom)
        ran_out = layers.cast(layers.equal(k, n_steps), "int64")
        live = layers.reduce_sum(act, keep_dim=True)
        idle = layers.cast(
            layers.equal(live,
                         layers.fill_constant([1], "int64", 0.0)),
            "int64")
        one = layers.fill_constant([1], "int64", 1.0)
        not_ran = layers.elementwise_sub(one, ran_out)
        tel_add(sv, state_prefix, "tel_exit_n_steps", ran_out)
        tel_add(sv, state_prefix, "tel_exit_all_idle",
                layers.elementwise_mul(not_ran, idle))
        tel_add(sv, state_prefix, "tel_exit_min_active",
                layers.elementwise_mul(
                    not_ran,
                    layers.elementwise_sub(one, idle)))
        layers.pack_row([sv[n] for n in row.names], row.name, row.dtype)
    return prog


def build_decoder_only_bundle(stack, layer_specs, *, state_prefix, vocab,
                              d_model, dtype, norm_eps, top_names,
                              moe_layers, first_held, experts_held, top_k,
                              n_slots, block_size, n_blocks, context,
                              max_new_tokens, chunk_sizes, max_chunks,
                              end_id, probe_logits, chunk_scope,
                              selected_probes=None, selection_size=0,
                              lane_state=(), probe_top_logit=False):
    """The serve programs of a decoder-only stack on the slot pool, as
    a DecoderOnlyStepBundle: what every such builder shares
    (models/glm_moe_dsa.py, models/nemotron_h.py). The builder brings
    the layers; this makes the slot state, the tick (a lane's current
    token embedded, the layers, the final norm, the head, argmax, the
    probes, the experts' counters, the emit tail), the prefill program
    (a While of fed chunks a chunk size, largest first, then the
    admission of the lanes whose prompt is cached) and the tick-only
    program, both ending in the While of ticks (build_serve_program).

    `stack(sv, x, pos, cell, gate, tab, chunk)`: the layers on rows x
    [N, D] at cache positions pos [N], whose pool rows are cell [N]
    (written where gate [N] is 1) under the block-table rows tab [G,
    NP]. `chunk` is None in a tick (row r is lane r; gate is the lanes'
    active flag) and {"lane", "len", "pos"} (each [1]: the lane, the
    real rows, the position of row 0) in a prefill chunk, whose rows
    are one lane's. Returns (x, {layer: selection [N, K]} for the
    layers in `selected_probes`, {expert layer: chosen [N, top_k]});
    a chunk's probes are dropped.
    `layer_specs`: a dict of state specs a layer (its pools, its lane
    state, what it probes), full names. `top_names`: the parameters
    (embedding, final norm, head). `moe_layers`: the layers that route,
    whose counters ride the state. `lane_state`: names of the state
    that is indexed by lane and made on the device (per-lane recurrent
    state; the bundle's `lane_state` says what a lane of it costs).
    `probe_top_logit` keeps the logit of every token a lane emitted
    (rows x tokens floats; the probe "top_logit"): what a comparison
    with a reference can hold to a number, where tokens only agree or
    do not."""
    import paddle_tpu as fluid

    chunk_sizes = tuple(sorted(set(int(c) for c in chunk_sizes)))
    if context % block_size:
        raise ValueError(f"block_size={block_size} must divide "
                         f"context={context}")
    cache = CacheConfig(layout="paged", block_size=block_size,
                        n_blocks=n_blocks, n_prompt_entries=1)
    rows, maxT = n_slots + 1, max_new_tokens + 1
    p = state_prefix
    emb_name, norm_name, head_name = top_names
    selected_probes = dict(selected_probes or {})
    specs = {
        f"{p}tok_buf": ((rows, maxT), "int64"),
        f"{p}step": ((rows,), "int64"),
        f"{p}finished": ((rows,), "int64"),
        f"{p}active": ((rows,), "int64"),
        # cache position of position 0 of a lane's token row, and how
        # many tokens the lane's request asked for
        f"{p}base": ((rows,), "int64"),
        f"{p}limit": ((rows,), "int64"),
        f"{p}block_tab": ((rows, context // block_size), "int32"),
        # what the live lanes of the ticks sent to the experts held
        # here: pairs, held experts with a pair, pairs an expert
        f"{p}moe_pairs": ((1,), "int64"),
        f"{p}moe_hit": ((1,), "int64"),
    }
    if probe_logits:
        specs[f"{p}logits_hist"] = ((rows, maxT, vocab), "float32")
    if probe_top_logit:
        specs[f"{p}top_logit_hist"] = ((rows, maxT, 1), "float32")
    specs.update(devtel.counter_specs(p, True, chunked=True))
    for li, layer in enumerate(layer_specs):
        specs.update(layer)
        if li in moe_layers:
            specs[f"{p}moe_load{li}"] = ((experts_held,), "int64")
            specs[f"{p}chosen_hist{li}"] = ((rows, maxT, top_k), "int32")

    def mark(sv):
        absint.mark_pool_index_source(sv[f"{p}block_tab"], "block_table",
                                      bound=n_blocks)
        absint.mark_pool_index_source(sv[f"{p}active"], "lane_active")
        return sv

    def embed(toks):
        return layers.embedding(toks, size=[vocab, d_model], dtype=dtype,
                                param_attr=ParamAttr(name=emb_name))

    def add_to(var, delta):
        layers.assign(layers.elementwise_add(var, delta), output=var)

    def tick_body(sv):
        tok_buf, stepv = sv[f"{p}tok_buf"], sv[f"{p}step"]
        fin, act = sv[f"{p}finished"], sv[f"{p}active"]
        tel_add(sv, p, "tel_ticks",
                layers.fill_constant([1], "int64", 1.0))
        tel_add(sv, p, "tel_occupancy",
                layers.reduce_sum(act, keep_dim=True))
        positions = layers.cast(layers.range(0, maxT, 1), "int64")
        t_mask = layers.cast(
            layers.equal(positions, layers.reshape(stepv, [rows, 1])),
            "int64")
        cur_tok = layers.reduce_sum(
            layers.elementwise_mul(tok_buf, t_mask), dim=1,
            keep_dim=True)                                  # [R,1]
        pos = layers.elementwise_add(sv[f"{p}base"], stepv)
        tab = sv[f"{p}block_tab"]
        cell = layers.paged_cell_index(tab, pos, block_size)
        # idle, dustbin and prefilling lanes (act = 0) write nothing
        gate = layers.cast(act, "float32")
        x, selected, chosen = stack(sv, embed(cur_tok), pos, cell, gate,
                                    tab, None)
        logits = layers.lm_head(
            layers.rms_norm(x, norm_eps, param_attr=norm_name),
            vocab, head_name)
        tok = layers.cast(layers.argmax(logits, axis=-1), "int64")
        if probe_logits:
            layers.lane_probe_write(sv[f"{p}logits_hist"], logits, act,
                                    step=stepv)
        if probe_top_logit:
            layers.lane_probe_write(
                sv[f"{p}top_logit_hist"],
                layers.reduce_max(logits, dim=-1, keep_dim=True), act,
                step=stepv)
        for li, sel in selected.items():
            layers.lane_probe_write(sv[selected_probes[li]], sel, act)
        for li, idx in chosen.items():
            layers.lane_probe_write(sv[f"{p}chosen_hist{li}"], idx, act,
                                    step=stepv)
            pairs, hit, load = layers.moe_tick_stats(
                idx, act, first_held, experts_held)
            add_to(sv[f"{p}moe_pairs"], pairs)
            add_to(sv[f"{p}moe_hit"], hit)
            add_to(sv[f"{p}moe_load{li}"], load)
        emit_lane_tokens(tok, tok_buf, stepv, fin, act, rows, maxT,
                         end_id, room_limit=sv[f"{p}limit"])

    def chunk_loop(sv, C, chunk_toks, chunk_lane, chunk_pos, chunk_len,
                   n_chunks):
        """The fed chunks of at most C tokens, one after another."""
        j = layers.fill_constant([1], "int64", 0)
        offs = layers.cast(layers.range(0, C, 1), "int64")
        cond = layers.less_than(j, n_chunks)
        loop = layers.While(cond)
        with loop.block(), device_scope(chunk_scope):
            toks = layers.reshape(layers.gather(chunk_toks, j), [C, 1])
            n = layers.gather(chunk_len, j)
            start = layers.gather(chunk_pos, j)
            pos = layers.elementwise_add(offs, start)
            # rows past the chunk's length are padding: they write
            # nothing, and what they compute is dropped
            gate = layers.cast(layers.less_than(offs, n), "float32")
            lane = layers.gather(chunk_lane, j)
            tab = layers.gather(sv[f"{p}block_tab"], lane)  # [1,NP]
            cell = layers.paged_cell_index(tab, pos, block_size)
            stack(sv, embed(toks), pos, cell, gate, tab,
                  {"lane": lane, "len": n, "pos": start})
            tel_add(sv, p, "tel_chunks",
                    layers.fill_constant([1], "int64", 1.0))
            layers.increment(j, 1)
            layers.less_than(j, n_chunks, cond=cond)

    def prefill_body(sv):
        A = max_chunks

        def fed(name, shape):
            return layers.data(name, shape=shape, dtype="int64",
                               append_batch_size=False)

        # the largest chunks first: a lane's prompt is cut into whole
        # chunks of the largest size and one smaller rest, which has
        # to find them cached
        for C in sorted(chunk_sizes, reverse=True):
            chunk_loop(sv, C, fed(f"chunk_toks_{C}", [A, C]),
                       fed(f"chunk_lane_{C}", [A]),
                       fed(f"chunk_pos_{C}", [A]),
                       fed(f"chunk_len_{C}", [A]),
                       fed(f"n_chunks_{C}", [1]))
        slots, a_tok = fed("admit_slots", [A]), fed("admit_tok", [A])
        a_base, a_limit = fed("admit_base", [A]), fed("admit_limit", [A])
        # admission: the lanes whose prompt is cached now but for its
        # last token, which is position 0 of their token row
        oh, _, any_i, _, keep_i = lane_onehots(slots, A, rows)
        oh_i = layers.cast(oh, "int64")

        def scattered(v):       # [A] -> [rows]; the dustbin's is junk
            return layers.reduce_sum(layers.elementwise_mul(
                oh_i, layers.reshape(v, [A, 1])), dim=0)

        start_col = layers.assign(
            (np.arange(maxT) == 0).astype("int64"))
        keep_col = layers.reshape(keep_i, [rows, 1])
        tok_buf = sv[f"{p}tok_buf"]
        layers.assign(layers.elementwise_add(
            layers.elementwise_mul(tok_buf, keep_col),
            layers.elementwise_mul(
                layers.reshape(scattered(a_tok), [rows, 1]), start_col)),
            output=tok_buf)
        for name, new in (("step", None), ("finished", None),
                          ("base", a_base), ("limit", a_limit)):
            var = sv[f"{p}{name}"]
            kept = layers.elementwise_mul(var, keep_i)
            layers.assign(kept if new is None else
                          layers.elementwise_add(kept, scattered(new)),
                          output=var)
        valid = layers.assign(
            (np.arange(rows) < n_slots).astype("int64"))
        admitted = layers.elementwise_mul(any_i, valid)
        act = sv[f"{p}active"]
        layers.assign(layers.elementwise_add(
            layers.elementwise_mul(act, keep_i), admitted), output=act)
        tel_add(sv, p, "tel_admit_miss",
                layers.reduce_sum(admitted, keep_dim=True))

    state = {k: f"{p}{k}" for k in
             ("tok_buf", "step", "finished", "active", "base", "limit",
              "block_tab", "moe_pairs", "moe_hit")}
    state.update(devtel.state_entries(p, True, chunked=True))
    state.update({f"moe_load{li}": f"{p}moe_load{li}"
                  for li in moe_layers})
    row = serve_row_of(p, state, specs, cache,
                       extra=DecoderOnlyStepBundle.moe_keys_of(state))
    fed = DecoderOnlyStepBundle.fed_tables
    serves = {0: build_serve_program(specs, p, lambda sv: None, tick_body,
                                     row, mark=mark, fed=fed)}
    serves[DecoderOnlyStepBundle.PREFILL] = build_serve_program(
        specs, p, prefill_body, tick_body, row, mark=mark, fed=fed)
    probes = {"selected": selected_probes,
              "chosen": {li: f"{p}chosen_hist{li}" for li in moe_layers}}
    if probe_logits:
        probes["logits"] = f"{p}logits_hist"
    if probe_top_logit:
        probes["top_logit"] = f"{p}top_logit_hist"
    return DecoderOnlyStepBundle(
        serves, fluid.Program(), state, specs, n_slots, maxT, context,
        end_id, cache, chunk_sizes, max_chunks, row, probes=probes,
        selection_size=selection_size, lane_state=lane_state)


def build_decode_step_program(seq_len=16, max_out_len=16, d_model=64,
                              n_heads=4, n_layers=2, d_inner=128,
                              vocab=1000, start_id=0, end_id=1,
                              n_slots=8, admit_buckets=None,
                              state_prefix="@cb/", cache=None,
                              sampling=None, draft=None,
                              sharding=None):
    """Build the slot-pool continuous-batching bundle (bucketed
    admission prefills + single-step decode over ``n_slots``
    device-resident lanes) — see DecodeStepBundle. The step program's
    per-layer math IS build_incremental_decode_program's While body
    (``cached_decoder_step``), with the scalar loop counter replaced
    by a per-lane counter vector, so a lane decodes token-for-token
    exactly what the whole-loop program would — the continuous
    server's parity invariant, across BOTH KV layouts.

    ``admit_buckets`` bounds the admission specializations (default:
    power-of-two ladder 1,2,4,... capped at n_slots); padded rows of
    a bucket land on the dustbin lane. ``cache`` (CacheConfig)
    selects the KV layout; None = dense.

    ``sampling`` (SamplingConfig) replaces the greedy argmax emission
    with temperature/top-k/top-p sampled lanes keyed on per-request
    seeds (admissions then feed ``seeds``); ``draft`` (DraftConfig)
    turns the step into SPECULATIVE draft-and-verify: k unrolled
    cached draft-model steps propose tokens per lane, ONE batched
    k+1-query target step verifies them, and per-lane counters
    advance by the accepted prefix (+ the correction/bonus token).
    Greedy spec (sampling None or temperature 0) is token-exact vs
    the whole-loop decode; sampled spec uses the rejection rule so
    the emitted stream matches the target model's (filtered)
    distribution. draft.k == 0 degenerates to the plain one-token
    step. The draft's params are prefix-named and pair-linted
    against the target's with the PTA100 collision check at build.

    Returns a DecodeStepBundle.
    """
    import paddle_tpu as fluid

    from . import transformer as T

    cache = cache or CacheConfig()
    cache.validate(max_out_len)
    if sharding is not None:
        sharding.validate(n_heads, vocab, d_model, d_inner)
    if sampling is not None:
        sampling.validate()
    if draft is not None:
        draft.validate(max_out_len)
        if draft.kind == "model":
            _pair_lint_draft_target(
                draft, seq_len=seq_len, max_out_len=max_out_len,
                d_model=d_model, n_heads=n_heads, n_layers=n_layers,
                d_inner=d_inner, vocab=vocab)
    spec = draft is not None and draft.k > 0
    ngram = spec and draft.kind == "ngram"
    qkv_il = sharding is not None and sharding.qkv_interleaved
    greedy = sampling is None or sampling.greedy
    samp = sampling or SamplingConfig(temperature=0.0)
    paged = cache.layout == "paged"
    needs_seeds = sampling is not None or draft is not None
    head_dim = d_model // n_heads
    maxT = max_out_len
    rows = n_slots + 1  # + the dustbin lane for padded admissions
    if admit_buckets is None:
        admit_buckets, b = [], 1
        while b < n_slots:
            admit_buckets.append(b)
            b *= 2
        admit_buckets.append(n_slots)
    admit_buckets = sorted(set(int(a) for a in admit_buckets))
    if admit_buckets[0] < 1 or admit_buckets[-1] > n_slots:
        raise ValueError(
            f"admit_buckets {admit_buckets} must lie in "
            f"[1, n_slots={n_slots}]")
    specs = _slot_state_specs(state_prefix, rows, maxT, seq_len,
                              n_heads, head_dim, n_layers, cache,
                              sampling=sampling, draft=draft,
                              vocab=vocab if paged else None)
    if paged:
        NP, BS, NB = cache.pages(maxT), cache.block_size, cache.n_blocks
        E = cache.n_prompt_entries

    # --- device-telemetry increment: var = var + delta on a bundle
    # counter (observability/devtel.py registry; silently skipped for
    # counters this layout does not carry, e.g. tel_admit_hit on
    # dense bundles) ------------------------------------------------
    def _tel_add(sv, logical, delta):
        tel_add(sv, state_prefix, logical, delta)

    # --- ownership mint-site annotations (analysis/absint.py seed
    # table): every paged program declares the SAME host-owned index
    # sources, so the ownership prover (PTA190/191/192) can chain
    # each @POOL access back to the allocator invariant that makes it
    # lane-exclusive. block_tab rows are disjoint per lane
    # (HostBlockPool.alloc-disjoint, entries < NB), prompt_ref is the
    # REFCOUNTED read path (entries <= the dustbin at E), and the
    # active mask is the gate block-table writes must carry. ---------
    def _mark_ownership(sv):
        if not paged:
            return sv
        absint.mark_pool_index_source(
            sv[f"{state_prefix}block_tab"], "block_table", bound=NB)
        absint.mark_pool_index_source(
            sv[f"{state_prefix}prompt_ref"], "prompt_entry_ref",
            bound=E + 1)
        absint.mark_pool_index_source(
            sv[f"{state_prefix}active"], "lane_active")
        return sv

    # --- lane-reset tail shared by every admission flavor: one-hot
    # masks over the fed slot ids, then token-buffer/counter/flag
    # resets for exactly the admitted lanes --------------------------
    def _lane_onehots(slots, A):
        return lane_onehots(slots, A, rows)

    def _reset_lane_state(sv, any_i, keep_i, oh=None, seeds=None,
                          tier="miss"):
        # token buffer rows: start_id at position 0, zeros
        # elsewhere (identical init row for every admission)
        positions = layers.cast(layers.range(0, maxT, 1), "int64")
        start_col = layers.cast(
            layers.equal(positions,
                         layers.fill_constant([1], "int64", 0.0)),
            "int64")
        row_init = layers.cast(
            layers.scale(start_col, scale=float(start_id)),
            "int64")
        any_col = layers.reshape(any_i, [rows, 1])
        keep_col = layers.reshape(keep_i, [rows, 1])
        tok_buf = sv[f"{state_prefix}tok_buf"]
        layers.assign(layers.elementwise_add(
            layers.elementwise_mul(tok_buf, keep_col),
            layers.elementwise_mul(any_col, row_init)),
            output=tok_buf)
        stepv = sv[f"{state_prefix}step"]
        layers.assign(layers.elementwise_mul(stepv, keep_i),
                      output=stepv)
        fin = sv[f"{state_prefix}finished"]
        layers.assign(layers.elementwise_mul(fin, keep_i),
                      output=fin)
        pfu = sv.get(f"{state_prefix}prefill_until")
        if pfu is not None:
            # admitted lanes start un-forced (a radix admission
            # re-scatters its horizon AFTER this shared reset)
            layers.assign(layers.elementwise_mul(pfu, keep_i),
                          output=pfu)
        if seeds is not None:
            # per-request noise seeds scatter to their lanes in PURE
            # int arithmetic (a float32 one-hot matmul would truncate
            # 32-bit seeds past 2^24); dustbin duplicates sum to
            # garbage harmlessly
            oh_i = layers.cast(oh, "int64")  # [A, rows]
            scat = layers.reduce_sum(
                layers.elementwise_mul(
                    oh_i, layers.reshape(seeds, [-1, 1])), dim=0)
            seedv = sv[f"{state_prefix}seed"]
            layers.assign(layers.elementwise_add(
                layers.elementwise_mul(seedv, keep_i), scat),
                output=seedv)
        act = sv[f"{state_prefix}active"]
        # the dustbin lane never activates: it must not hold the
        # serve While open nor count against min_active
        valid = layers.assign(
            (np.arange(rows) < n_slots).astype("int64"))
        layers.assign(layers.elementwise_add(
            layers.elementwise_mul(act, keep_i),
            layers.elementwise_mul(any_i, valid)), output=act)
        # devtel: count the REAL lanes this admission touched (padded
        # rows collapse onto the dustbin lane, masked out by `valid`)
        _tel_add(sv, f"tel_admit_{tier}",
                 layers.reduce_sum(
                     layers.elementwise_mul(any_i, valid),
                     keep_dim=True))

    def _seeds_data(A):
        if not needs_seeds:
            return None
        return layers.data("seeds", shape=[A], dtype="int64",
                           append_batch_size=False)

    def _draft_admit(sv, src, A, oh, keep_f):
        """Speculative admission tail: run the (tiny) DRAFT encoder
        over the admission prompts and install per-lane draft
        cross-KV + zeroed draft self-KV. Runs on EVERY admission
        flavor — including paged prefix-HITs, which skip only the
        TARGET encoder (the draft's cross-KV is per-lane, not
        pooled; re-encoding with the draft costs ~nothing, and
        pooling it would couple the prompt-entry refcounts to the
        draft's lifetime for no capacity win)."""
        dd = draft.d_model
        dh = dd // draft.n_heads
        denc = T._embed(src, vocab, dd, max(seq_len, maxT), 0.0,
                        True, f"{draft.prefix}src_word_emb")
        for li in range(draft.n_layers):
            denc = T.encoder_layer(denc, dd, draft.n_heads,
                                   draft.d_inner, 0.0, is_test=True,
                                   name=f"{draft.prefix}enc{li}")
        keep4 = layers.reshape(keep_f, [rows, 1, 1, 1])
        ohT = layers.transpose(oh, perm=[1, 0])  # [rows, A]
        flat = draft.n_heads * seq_len * dh
        for li in range(draft.n_layers):
            kvp = layers.fc(denc, 2 * dd, num_flatten_dims=2,
                            bias_attr=False,
                            param_attr=T._attn_proj_attr(
                                f"{draft.prefix}dec{li}_cross", "kv",
                                dd))
            k, v = layers.split(kvp, 2, dim=2)
            kh = heads_of(k, seq_len, draft.n_heads, dh)
            vh = heads_of(v, seq_len, draft.n_heads, dh)
            for var, new in (
                    (sv[f"{state_prefix}draft_cross_k{li}"], kh),
                    (sv[f"{state_prefix}draft_cross_v{li}"], vh)):
                scat = layers.reshape(
                    layers.matmul(ohT,
                                  layers.reshape(new, [A, flat])),
                    [rows, draft.n_heads, seq_len, dh])
                layers.assign(layers.elementwise_add(
                    layers.elementwise_mul(var, keep4), scat),
                    output=var)
            for var in (sv[f"{state_prefix}draft_self_k{li}"],
                        sv[f"{state_prefix}draft_self_v{li}"]):
                layers.assign(layers.elementwise_mul(var, keep4),
                              output=var)

    def _ngram_admit(sv, src, A, oh, keep_f):
        """Model-free draft admission: scatter the admission prompts
        into the admitted lanes' ``prompt_toks`` copies — the text
        the suffix matcher scans at every spec tick. The one-hot
        scatter is an INTEGER matmul (the radix hist_toks idiom): a
        float32 one at the TPU's default precision rounds its
        operands to bf16 and corrupts every token id above 256."""
        ohT = layers.cast(layers.transpose(oh, perm=[1, 0]),
                          "int64")                         # [rows, A]
        scat = layers.matmul(ohT, src)                     # [R,S]
        keep_i = layers.cast(keep_f, "int64")
        keep_col = layers.reshape(keep_i, [rows, 1])
        var = sv[f"{state_prefix}prompt_toks"]
        layers.assign(layers.elementwise_add(
            layers.elementwise_mul(var, keep_col), scat),
            output=var)

    def _spec_admit(sv, src, A, oh, keep_f):
        """Speculative admission tail dispatch: draft-MODEL bundles
        install per-lane draft cross-KV (_draft_admit); ngram bundles
        install the per-lane prompt copy (_ngram_admit)."""
        if ngram:
            _ngram_admit(sv, src, A, oh, keep_f)
        else:
            _draft_admit(sv, src, A, oh, keep_f)

    def _encode_prompts(A):
        src = layers.data("src_ids", shape=[A, seq_len],
                          dtype="int64", append_batch_size=False)
        enc = T._embed(src, vocab, d_model, max(seq_len, maxT), 0.0,
                       True, "src_word_emb")
        for li in range(n_layers):
            enc = T.encoder_layer(enc, d_model, n_heads, d_inner,
                                  0.0, is_test=True,
                                  name=f"enc{li}")
        return src, enc

    def _cross_proj(enc, li):
        """Layer li's cross-attention keys and values of the encoded
        prompts, as rows: two [A, S, H*Dh]."""
        kvp = layers.fc(enc, 2 * d_model, num_flatten_dims=2,
                        bias_attr=False,
                        param_attr=T._attn_proj_attr(
                            f"dec{li}_cross", "kv", d_model))
        return layers.split(kvp, 2, dim=2)

    # --- admission bodies: admit up to A prompts in ONE dispatch ----
    def _admit_body_dense(sv, A):
        src, enc = _encode_prompts(A)
        slots = layers.data("slots", shape=[A], dtype="int64",
                            append_batch_size=False)
        seeds = _seeds_data(A)
        oh, any_f, any_i, keep_f, keep_i = _lane_onehots(slots, A)
        keep4 = layers.reshape(keep_f, [rows, 1, 1, 1])
        ohT = layers.transpose(oh, perm=[1, 0])  # [rows, A]
        flat = n_heads * seq_len * head_dim
        for li in range(n_layers):
            kh, vh = (heads_of(kv, seq_len, n_heads, head_dim)
                      for kv in _cross_proj(enc, li))
            for var, new in (
                    (sv[f"{state_prefix}cross_k{li}"], kh),
                    (sv[f"{state_prefix}cross_v{li}"], vh)):
                # one-hot matmul scatter: row a of `new` lands on
                # lane slots[a]; untouched lanes read 0 and keep
                # their old value through keep4
                scat = layers.reshape(
                    layers.matmul(ohT,
                                  layers.reshape(new, [A, flat])),
                    [rows, n_heads, seq_len, head_dim])
                layers.assign(layers.elementwise_add(
                    layers.elementwise_mul(var, keep4), scat),
                    output=var)
            for var in (sv[f"{state_prefix}self_k{li}"],
                        sv[f"{state_prefix}self_v{li}"]):
                layers.assign(layers.elementwise_mul(var, keep4),
                              output=var)
        if spec:
            _spec_admit(sv, src, A, oh, keep_f)
        _reset_lane_state(sv, any_i, keep_i, oh=oh, seeds=seeds)

    def _admit_body_paged_miss(sv, A):
        """Cold-prompt admission: encode, publish cross-KV into the
        fed prompt-pool entries (host-distinct indices — padded rows
        target the dustbin entry), reset the lanes. The lanes' block
        tables / prompt refs are HOST-written scope state."""
        src, enc = _encode_prompts(A)
        slots = layers.data("slots", shape=[A], dtype="int64",
                            append_batch_size=False)
        pslots = layers.data("prompt_slots", shape=[A], dtype="int64",
                             append_batch_size=False)
        # the scheduler feeds pairwise-distinct FRESH entries
        # (refcount==1 at write time; padded rows aim at the dustbin
        # E) — the host invariant PTA191 names in its proof
        absint.mark_pool_index_source(pslots, "host_indices",
                                      bound=E + 1)
        seeds = _seeds_data(A)
        for li in range(n_layers):
            k, v = _cross_proj(enc, li)
            for var, new in (
                    (sv[f"{state_prefix}cross_k{li}{POOL_MARK}"], k),
                    (sv[f"{state_prefix}cross_v{li}{POOL_MARK}"], v)):
                # the projection's rows are the entry as it is stored
                layers.masked_pool_write(
                    var, new, pslots, leading_dims=1,
                    exclusive_via="host_indices")
        oh, _, any_i, keep_f, keep_i = _lane_onehots(slots, A)
        if spec:
            _spec_admit(sv, src, A, oh, keep_f)
        _reset_lane_state(sv, any_i, keep_i, oh=oh, seeds=seeds)
        # fresh lanes need no self-pool zeroing: every cache position
        # <= t is rewritten by the lane before it is ever attended to,
        # and positions > t are masked by the validity bias exactly
        # like the dense layout's zeros

    def _admit_body_paged_hit(sv, A):
        """Prefix-HIT admission: the prompt's cross-KV entry is
        already in the pool (refcount bumped host-side), so admission
        is a lane reset only — no TARGET encoder, no pool write. This
        is the prefix-reuse fast path a shared system prompt rides.
        Speculative bundles still feed src_ids here and run the
        (tiny) DRAFT encoder: its cross-KV is per-lane state (see
        _draft_admit)."""
        if spec:
            src = layers.data("src_ids", shape=[A, seq_len],
                              dtype="int64", append_batch_size=False)
        slots = layers.data("slots", shape=[A], dtype="int64",
                            append_batch_size=False)
        seeds = _seeds_data(A)
        oh, _, any_i, keep_f, keep_i = _lane_onehots(slots, A)
        if spec:
            _spec_admit(sv, src, A, oh, keep_f)
        _reset_lane_state(sv, any_i, keep_i, oh=oh, seeds=seeds,
                          tier="hit")

    def _admit_body_paged_radix(sv, A):
        """Radix-resume admission (multi-turn sessions / shared-chain
        fan-out): the prompt's cross-KV entry is pooled (prefix HIT —
        the session pin guarantees it) and the longest shared BLOCK
        prefix of the lane's token history is host-mapped read-only
        into its block table, so the device neither encodes nor
        replays those positions. Admission scatters the full token
        HISTORY into tok_buf, sets step = resume_steps (the first
        position NOT covered by shared blocks — every device write
        lands in a freshly allocated exclusive block, which is how
        PTA192's read-only-while-shared holds by construction) and
        prefill_until = the history length, so the divergent tail
        chunk-prefills via teacher forcing before real decoding
        starts."""
        hist = layers.data("hist_toks", shape=[A, maxT],
                           dtype="int64", append_batch_size=False)
        resume = layers.data("resume_steps", shape=[A], dtype="int64",
                             append_batch_size=False)
        until = layers.data("prefill_until", shape=[A], dtype="int64",
                            append_batch_size=False)
        slots = layers.data("slots", shape=[A], dtype="int64",
                            append_batch_size=False)
        seeds = _seeds_data(A)
        oh, _, any_i, keep_f, keep_i = _lane_onehots(slots, A)
        _reset_lane_state(sv, any_i, keep_i, oh=oh, seeds=seeds,
                          tier="radix")
        # overwrite the shared reset's cold-start row/counters with
        # the session history, in INTEGER arithmetic throughout: a
        # float32 one-hot matmul is exact on the CPU backend only --
        # at the TPU's default matmul precision its operands round to
        # bf16, and the first chip run (PR 21) replayed token 6532 as
        # 6528 on every radix admission
        ohT = layers.cast(layers.transpose(oh, perm=[1, 0]),
                          "int64")                         # [rows, A]
        hist_scat = layers.matmul(ohT, hist)               # [R,maxT]
        any_col = layers.reshape(any_i, [rows, 1])
        keep_col = layers.reshape(keep_i, [rows, 1])
        tok_buf = sv[f"{state_prefix}tok_buf"]
        layers.assign(layers.elementwise_add(
            layers.elementwise_mul(tok_buf, keep_col),
            layers.elementwise_mul(hist_scat, any_col)),
            output=tok_buf)
        oh_i = layers.cast(oh, "int64")
        for feed_v, state_name in ((resume, "step"),
                                   (until, "prefill_until")):
            var = sv[f"{state_prefix}{state_name}"]
            scat = layers.reduce_sum(
                layers.elementwise_mul(
                    oh_i, layers.reshape(feed_v, [-1, 1])), dim=0)
            layers.assign(layers.elementwise_add(
                layers.elementwise_mul(var, keep_i), scat),
                output=var)

    admit_bodies = {"miss": _admit_body_dense if not paged
                    else _admit_body_paged_miss}
    if paged:
        admit_bodies["hit"] = _admit_body_paged_hit
        if not spec:
            # the radix tier rides the plain paged step: speculative
            # decode advances counters by variable accepted lengths,
            # which the block-aligned resume arithmetic does not
            # model (and the draft's dense per-lane KV has no shared
            # prefix to reuse anyway)
            admit_bodies["radix"] = _admit_body_paged_radix

    prefills = {}
    hit_prefills = {}
    startup = None
    for A in admit_buckets:
        prog = fluid.Program()
        st = fluid.Program()
        with fluid.program_guard(prog, st):
            admit_bodies["miss"](
                _mark_ownership(
                    _declare_slot_state(prog.global_block, specs)), A)
        prefills[A] = prog
        startup = startup or st
        if paged:
            hprog = fluid.Program()
            with fluid.program_guard(hprog, fluid.Program()):
                admit_bodies["hit"](
                    _mark_ownership(_declare_slot_state(
                        hprog.global_block, specs)), A)
            hit_prefills[A] = hprog

    # --- the one-token step body over all lanes (shared by the
    # standalone step program and the fused serve programs' While) ---
    def _step_body(sv, probe=False):
        tok_buf = sv[f"{state_prefix}tok_buf"]
        stepv = sv[f"{state_prefix}step"]
        fin = sv[f"{state_prefix}finished"]
        act = sv[f"{state_prefix}active"]
        # devtel: one tick ran; occupancy integral reads act BEFORE
        # this tick's retirements mutate it (live lanes AT tick start)
        _tel_add(sv, "tel_ticks",
                 layers.fill_constant([1], "int64", 1.0))
        _tel_add(sv, "tel_occupancy",
                 layers.reduce_sum(act, keep_dim=True))
        positions = layers.cast(layers.range(0, maxT, 1), "int64")
        # the dense layouts' bias alone reads it
        posf = None if paged else layers.cast(positions, "float32")
        pos_table = layers.assign(
            T._position_encoding(max(seq_len, maxT), d_model)[:maxT])
        step2 = layers.reshape(stepv, [rows, 1])           # [R,1]
        t_mask = layers.cast(layers.equal(positions, step2),
                             "float32")                    # [R,maxT]
        cur_tok = layers.reduce_sum(
            layers.elementwise_mul(tok_buf,
                                   layers.cast(t_mask, "int64")),
            dim=1, keep_dim=True)                          # [R,1]
        x = layers.embedding(cur_tok, size=[vocab, d_model],
                             param_attr=ParamAttr(
                                 name="tgt_word_emb"))     # [R,D]
        x = layers.unsqueeze(x, [1])                       # [R,1,D]
        x = layers.scale(x, scale=d_model ** 0.5)
        pos_t = layers.matmul(t_mask, pos_table)           # [R,D]
        x = layers.elementwise_add(x, layers.unsqueeze(pos_t, [1]))
        att_bias = None
        if not paged:
            # per-lane attention validity (the paged read masks the
            # same positions from stepv: block_size divides maxT, so
            # both layouts attend exactly the maxT cache positions)
            att_bias = layers.reshape(
                layers.scale(layers.cast(layers.greater_than(
                    posf, layers.cast(step2, "float32")), "float32"),
                    scale=-1e9),
                [rows, 1, 1, maxT])
            write_mask = layers.reshape(t_mask, [rows, 1, maxT, 1])
            keep_mask = layers.reshape(
                layers.elementwise_sub(
                    layers.fill_constant([rows, maxT], "float32",
                                         1.0),
                    t_mask),
                [rows, 1, maxT, 1])
            caches = [_DenseLaneCache(sv[f"{state_prefix}self_k{li}"],
                                      sv[f"{state_prefix}self_v{li}"],
                                      write_mask, keep_mask)
                      for li in range(n_layers)]
            cross_kv = [_DenseCross(sv[f"{state_prefix}cross_k{li}"],
                                    sv[f"{state_prefix}cross_v{li}"])
                        for li in range(n_layers)]
        else:
            # cell addresses through the HOST-owned block table:
            # flat cache cell of position p = tab[lane, p//BS]*BS
            # + p%BS. The read takes the table itself; only the
            # current write position's cell is materialized, from
            # its page/offset one-hots out of t_mask
            block_tab = sv[f"{state_prefix}block_tab"]     # [R,NP]
            tabf = layers.cast(block_tab, "float32")
            offs = layers.assign(np.arange(BS, dtype="float32"))
            t_pages = layers.reshape(t_mask, [rows, NP, BS])
            page_oh = layers.reduce_sum(t_pages, dim=2)    # [R,NP]
            off_oh = layers.reduce_sum(t_pages, dim=1)     # [R,BS]
            cur_block = layers.reduce_sum(
                layers.elementwise_mul(tabf, page_oh), dim=1)
            cur_off = layers.reduce_sum(
                layers.elementwise_mul(off_oh, offs), dim=1)
            write_idx = layers.cast(
                layers.elementwise_add(
                    layers.scale(cur_block, scale=float(BS)),
                    cur_off), "int32")                     # [R]
            # idle/dustbin/paused lanes (act=0) must NOT write the
            # SHARED pool — the gate is the lane-exclusivity half
            # PTA110 checks alongside the block-table indices
            gate = layers.cast(act, "float32")
            caches = [_PagedLaneCache(
                sv[f"{state_prefix}self_k{li}{POOL_MARK}"],
                sv[f"{state_prefix}self_v{li}{POOL_MARK}"],
                write_idx, gate, block_tab, stepv, rows, BS)
                for li in range(n_layers)]
            cross_kv = _paged_prompt_cross(sv, state_prefix, n_layers,
                                           rows, seq_len)
        x = cached_decoder_step(x, caches, cross_kv, att_bias,
                                d_model, n_heads, d_inner,
                                qkv_interleaved=qkv_il)
        logits_v = layers.fc(
            layers.reshape(x, [0, d_model]), vocab,
            bias_attr=False, param_attr="logits.w")        # [R,V]
        if probe:
            # beam/probe front: publish every lane's full next-token
            # distribution for the HOST to branch on (the paged beam
            # decoder's expansion oracle — host selection, device KV)
            layers.assign(layers.softmax(logits_v),
                          output=sv[f"{state_prefix}probe_probs"])
        # --- per-lane emit (the emit_token_step tail, vectorized over
        # lane counters; same freeze/write semantics). Sampled lanes
        # draw from the filtered distribution keyed on (per-request
        # seed, position) — invariant to admission order / burst
        # boundaries / which serve specialization runs the tick
        # (ops/spec_ops.py noise discipline) ---
        ones_n = layers.fill_constant([rows], "int64", 1.0)
        if sampling is not None and not sampling.greedy:
            probs_v = layers.filtered_softmax(
                logits_v, temperature=samp.temperature,
                top_k=samp.top_k, top_p=samp.top_p)
            tok = layers.sample_categorical(
                probs_v, sv[f"{state_prefix}seed"],
                layers.elementwise_add(stepv, ones_n),
                noise_tag=0, base_seed=samp.base_seed)     # [R]
        else:
            tok = layers.cast(layers.argmax(logits_v, axis=-1),
                              "int64")                     # [R]
        # teacher forcing (radix tail prefill / beam probe): while
        # step+1 < prefill_until the lane is REPLAYING its history —
        # the decoder ran and its KV write landed (that is the whole
        # point), but the emitted token must not clobber the history
        # token already sitting at step+1, and a coincidental end_id
        # must not latch fin. prefill_until defaults to 0 everywhere,
        # so non-radix lanes take emit_flag == act identically to the
        # pre-forcing lowering.
        emit_flag = None
        if paged:
            forcing = layers.elementwise_mul(
                act, layers.cast(layers.less_than(
                    layers.elementwise_add(stepv, ones_n),
                    sv[f"{state_prefix}prefill_until"]), "int64"))
            emit_flag = layers.elementwise_sub(ones_n, forcing)
        emit_lane_tokens(tok, tok_buf, stepv, fin, act, rows, maxT,
                         end_id, emit_flag=emit_flag)

    # --- the speculative (draft-and-verify) step body: k unrolled
    # cached DRAFT steps propose tokens per lane, ONE batched
    # (k+1)-query TARGET step verifies them, and spec_accept advances
    # each lane by its accepted prefix + the correction/bonus token.
    # Greedy is token-exact vs the whole-loop decode (the acceptance
    # rule degenerates exactly — ops/spec_ops.py); KV cells past the
    # accepted prefix hold rejected-token garbage, which is masked by
    # the per-query validity bias and rewritten when the lane reaches
    # those positions (the same staleness discipline the paged
    # layout already relies on). ------------------------------------
    def _spec_step_body(sv, k_run=None):
        # k_run: the draft length THIS serve variant runs (adaptive-k
        # ladder rungs share the body builder; None = the default k)
        k = draft.k if k_run is None else int(k_run)
        Q = k + 1
        tok_buf = sv[f"{state_prefix}tok_buf"]
        stepv = sv[f"{state_prefix}step"]
        fin = sv[f"{state_prefix}finished"]
        act = sv[f"{state_prefix}active"]
        seedv = sv[f"{state_prefix}seed"]
        # devtel: same tick/occupancy discipline as _step_body (act
        # read before the post-verify state assigns)
        _tel_add(sv, "tel_ticks",
                 layers.fill_constant([1], "int64", 1.0))
        _tel_add(sv, "tel_occupancy",
                 layers.reduce_sum(act, keep_dim=True))
        # adaptive ladder: which rung ticked (absent on fixed-k
        # bundles — _tel_add skips missing counters)
        _tel_add(sv, devtel.spec_k_logical(k),
                 layers.fill_constant([1], "int64", 1.0))
        positions = layers.cast(layers.range(0, maxT, 1), "int64")
        posf = layers.cast(positions, "float32")
        pos_table = layers.assign(
            T._position_encoding(max(seq_len, maxT), d_model)[:maxT])
        ones_n = layers.fill_constant([rows], "int64", 1.0)
        step2 = layers.reshape(stepv, [rows, 1])           # [R,1]
        t_mask0 = layers.cast(layers.equal(positions, step2),
                              "float32")                   # [R,maxT]
        cur_tok = layers.reduce_sum(
            layers.elementwise_mul(tok_buf,
                                   layers.cast(t_mask0, "int64")),
            dim=1, keep_dim=True)                          # [R,1]

        if ngram:
            # ---- model-free propose (prompt-lookup decoding): find
            # the RIGHTMOST non-trivial occurrence of the lane's
            # last-n-token suffix in prompt+history and propose its
            # continuation. The proposals are deterministic, and
            # their one-hot "distributions" make the Leviathan
            # accept test exact under greedy AND sampled emission
            # (accept w.p. p(x); residual = p with x zeroed), so the
            # whole proposer is FREE of model steps — index
            # arithmetic only.
            n = draft.ngram
            S_ = seq_len
            CTX = S_ + maxT
            ctx_i = layers.concat(
                [sv[f"{state_prefix}prompt_toks"], tok_buf],
                axis=1)                                    # [R,CTX]
            ctx_f = layers.cast(ctx_i, "float32")
            ctx_posf = layers.assign(
                np.arange(CTX, dtype="float32"))           # [CTX]
            step2f = layers.cast(step2, "float32")         # [R,1]
            # candidate match-END validity: j >= n-1 (a full suffix
            # sits to its left) AND j < S + step (strictly left of
            # the live suffix end — excludes the trivial self-match
            # and, because validity is prefix-closed, every
            # uncommitted tok_buf position the window could touch)
            j_ok = layers.cast(layers.greater_than(
                ctx_posf, layers.fill_constant(
                    [1], "float32", float(n - 2))),
                "float32")                                 # [CTX]
            end_ok = layers.cast(layers.less_than(
                ctx_posf, layers.scale(step2f, bias=float(S_))),
                "float32")                                 # [R,CTX]
            score = layers.elementwise_mul(end_ok, j_ok, axis=1)
            for i in range(n):
                # suffix token i back from the live end: ctx[S+step-i]
                # — reading the CONCATENATED prompt+history means the
                # suffix crosses the prompt boundary correctly during
                # the first n generated tokens. A spurious match
                # against pad/zero tokens merely proposes tokens the
                # verify step then rejects (acceptance cost, never a
                # correctness cost).
                m_i = layers.cast(layers.equal(
                    ctx_posf, layers.scale(
                        step2f, bias=float(S_ - i))), "float32")
                s_i = layers.reduce_sum(
                    layers.elementwise_mul(ctx_f, m_i), dim=1,
                    keep_dim=True)                         # [R,1]
                # ctx shifted right by i (matmul with the off-
                # diagonal identity): shifted[r, j] = ctx[r, j-i]
                shift = layers.assign(
                    np.eye(CTX, dtype="float32", k=i))
                shifted = layers.matmul(ctx_f, shift)      # [R,CTX]
                score = layers.elementwise_mul(
                    score, layers.cast(layers.equal(shifted, s_i),
                                       "float32"))
            # rightmost match end: argmax of score*(j+1); 0 = none
            best = layers.reduce_max(
                layers.elementwise_mul(
                    score, layers.scale(ctx_posf, bias=1.0),
                    axis=1),
                dim=1, keep_dim=True)                      # [R,1]
            has = layers.cast(layers.greater_than(
                best, layers.fill_constant([1], "float32", 0.0)),
                "float32")                                 # [R,1]
            idx = layers.scale(best, bias=-1.0)            # [R,1]
            cur_f = layers.cast(cur_tok, "float32")        # [R,1]
            proposals, dprob_rows = [], []
            for m in range(k):
                pm = layers.scale(idx, bias=float(1 + m))  # [R,1]
                # committed-continuation gate: the proposed position
                # must itself be prompt/history (pm <= S+step)
                ok_m = layers.elementwise_mul(
                    has, layers.cast(layers.less_than(
                        pm, layers.scale(step2f,
                                         bias=float(S_ + 1))),
                        "float32"))                        # [R,1]
                om = layers.cast(layers.equal(ctx_posf, pm),
                                 "float32")                # [R,CTX]
                got = layers.reduce_sum(
                    layers.elementwise_mul(ctx_f, om), dim=1,
                    keep_dim=True)                         # [R,1]
                # fallback: repeat the current token (any proposal
                # is CORRECT — the verify step rejects bad ones; the
                # fallback only matters for acceptance rate)
                tok_m = layers.cast(layers.reshape(
                    layers.elementwise_add(
                        layers.elementwise_mul(got, ok_m),
                        layers.elementwise_mul(
                            cur_f, layers.scale(ok_m, scale=-1.0,
                                                bias=1.0))),
                    [rows]), "int64")                      # [R]
                proposals.append(tok_m)
                dprob_rows.append(layers.unsqueeze(
                    layers.one_hot(tok_m, vocab), [1]))    # [R,1,V]
        else:
            dd, dH = draft.d_model, draft.n_heads
            dpos_table = layers.assign(
                T._position_encoding(max(seq_len, maxT), dd)[:maxT])
            # ---- draft propose: k+1 unrolled cached draft-model
            # steps over positions step..step+k. Steps 0..k-1 yield
            # the k proposals; step k exists ONLY to write the
            # draft's KV at position step+k — after a full-acceptance
            # tick the counter advances to step+k+1, and without that
            # write the draft cache keeps a PERMANENT hole at step+k
            # (never reprocessed: later ticks start past it),
            # silently poisoning every subsequent proposal for the
            # lane's lifetime (measured: acceptance collapsed to ~0
            # after the first burst). The same discipline is why the
            # adaptive k=0 rung keeps a one-step draft keepalive
            # (_draft_keepalive) in front of the plain body. ----
            proposals, dprob_rows = [], []
            prev = cur_tok
            for j in range(k + 1):
                stepj = stepv if j == 0 else layers.elementwise_add(
                    stepv, layers.fill_constant([1], "int64",
                                                float(j)))
                stepj2 = layers.reshape(stepj, [rows, 1])
                t_mask_j = layers.cast(
                    layers.equal(positions, stepj2),
                    "float32")                             # [R,maxT]
                x = layers.embedding(prev, size=[vocab, dd],
                                     param_attr=ParamAttr(
                                         name=f"{draft.prefix}"
                                              f"tgt_word_emb"))
                x = layers.unsqueeze(x, [1])               # [R,1,dd]
                x = layers.scale(x, scale=dd ** 0.5)
                pos_e = layers.matmul(t_mask_j, dpos_table)
                x = layers.elementwise_add(
                    x, layers.unsqueeze(pos_e, [1]))
                dbias = layers.reshape(
                    layers.scale(layers.cast(layers.greater_than(
                        posf, layers.cast(stepj2, "float32")),
                        "float32"), scale=-1e9),
                    [rows, 1, 1, maxT])
                wm = layers.reshape(t_mask_j, [rows, 1, maxT, 1])
                km = layers.reshape(
                    layers.elementwise_sub(
                        layers.fill_constant([rows, maxT], "float32",
                                             1.0), t_mask_j),
                    [rows, 1, maxT, 1])
                dcaches = [
                    _DenseLaneCache(
                        sv[f"{state_prefix}draft_self_k{li}"],
                        sv[f"{state_prefix}draft_self_v{li}"],
                        wm, km)
                    for li in range(draft.n_layers)]
                dcross = [_DenseCross(
                    sv[f"{state_prefix}draft_cross_k{li}"],
                    sv[f"{state_prefix}draft_cross_v{li}"])
                    for li in range(draft.n_layers)]
                x = cached_decoder_step(x, dcaches, dcross, dbias,
                                        dd, dH, draft.d_inner,
                                        prefix=draft.prefix)
                if j == k:
                    # the cache-fill-only step: position step+k's KV
                    # is written (the full-acceptance hole), no
                    # proposal
                    break
                dlogits = layers.fc(
                    layers.reshape(x, [0, dd]), vocab,
                    bias_attr=False,
                    param_attr=f"{draft.prefix}logits.w")  # [R,V]
                dprobs = layers.filtered_softmax(
                    dlogits, temperature=samp.temperature,
                    top_k=samp.top_k, top_p=samp.top_p)
                if greedy:
                    tok_j = layers.cast(
                        layers.argmax(dprobs, axis=-1), "int64")
                else:
                    tok_j = layers.sample_categorical(
                        dprobs, seedv,
                        layers.elementwise_add(
                            stepj, layers.fill_constant(
                                [1], "int64", 1.0)),
                        noise_tag=1, base_seed=samp.base_seed)
                proposals.append(tok_j)
                dprob_rows.append(layers.unsqueeze(dprobs, [1]))
                prev = layers.reshape(tok_j, [rows, 1])

        # ---- target verify: ONE batched Q-query cached step over
        # [current token, k proposals] ----
        toks_q = layers.concat(
            [cur_tok] + [layers.reshape(t, [rows, 1])
                         for t in proposals], axis=1)      # [R,Q]
        x = layers.embedding(toks_q, size=[vocab, d_model],
                             param_attr=ParamAttr(
                                 name="tgt_word_emb"))     # [R,Q,D]
        x = layers.scale(x, scale=d_model ** 0.5)
        posq = layers.elementwise_add(
            step2, layers.assign(np.arange(Q).astype("int64")))
        posq3 = layers.reshape(posq, [rows, Q, 1])
        t_mask_q = layers.cast(layers.equal(positions, posq3),
                               "float32")                  # [R,Q,maxT]
        x = layers.elementwise_add(
            x, layers.matmul(t_mask_q, pos_table))         # [R,Q,D]
        # per-query causal validity: query j attends positions
        # <= step+j (positions past the buffer get all-zero one-hots
        # and never write — see the span caches)
        bias = None
        if not paged:
            bias = layers.reshape(
                layers.scale(layers.cast(layers.greater_than(
                    posf, layers.cast(posq3, "float32")), "float32"),
                    scale=-1e9),
                [rows, 1, Q, maxT])
            keep = layers.reshape(
                layers.elementwise_sub(
                    layers.fill_constant([rows, maxT], "float32",
                                         1.0),
                    layers.reduce_sum(t_mask_q, dim=1)),
                [rows, 1, maxT, 1])
            caches = [_DenseSpanCache(
                sv[f"{state_prefix}self_k{li}"],
                sv[f"{state_prefix}self_v{li}"], t_mask_q, keep)
                for li in range(n_layers)]
            cross_kv = [_DenseCross(sv[f"{state_prefix}cross_k{li}"],
                                    sv[f"{state_prefix}cross_v{li}"])
                        for li in range(n_layers)]
        else:
            block_tab = sv[f"{state_prefix}block_tab"]     # [R,NP]
            tabf = layers.cast(block_tab, "float32")
            offs = layers.assign(np.arange(BS, dtype="float32"))
            t_pages_q = layers.reshape(t_mask_q, [rows, Q, NP, BS])
            page_oh = layers.reduce_sum(t_pages_q, dim=3)  # [R,Q,NP]
            off_oh = layers.reduce_sum(t_pages_q, dim=2)   # [R,Q,BS]
            cur_block = layers.reduce_sum(
                layers.elementwise_mul(layers.unsqueeze(tabf, [1]),
                                       page_oh), dim=2)    # [R,Q]
            cur_off = layers.reduce_sum(
                layers.elementwise_mul(off_oh, offs), dim=2)
            write_idx = layers.cast(
                layers.reshape(
                    layers.elementwise_add(
                        layers.scale(cur_block, scale=float(BS)),
                        cur_off), [rows * Q]), "int32")
            # gate = active AND position-in-buffer: an out-of-range
            # query's one-hot is all-zero, which would otherwise
            # alias cell 0 of block 0 — another lane's KV
            validq = layers.reduce_sum(t_mask_q, dim=2)    # [R,Q]
            gate = layers.reshape(
                layers.elementwise_mul(
                    layers.reshape(layers.cast(act, "float32"),
                                   [rows, 1]), validq), [rows * Q])
            caches = [_PagedLaneCache(
                sv[f"{state_prefix}self_k{li}{POOL_MARK}"],
                sv[f"{state_prefix}self_v{li}{POOL_MARK}"],
                write_idx, gate, block_tab, stepv, rows, BS, q=Q)
                for li in range(n_layers)]
            cross_kv = _paged_prompt_cross(sv, state_prefix, n_layers,
                                           rows, seq_len)
        x = cached_decoder_step(x, caches, cross_kv, bias, d_model,
                                n_heads, d_inner, q=Q,
                                qkv_interleaved=qkv_il)    # [R,Q,D]
        logits_q = layers.fc(x, vocab, num_flatten_dims=2,
                             bias_attr=False,
                             param_attr="logits.w")        # [R,Q,V]
        tprobs = layers.filtered_softmax(
            logits_q, temperature=samp.temperature,
            top_k=samp.top_k, top_p=samp.top_p)
        dprobs_s = layers.concat(dprob_rows, axis=1)       # [R,k,V]
        props = layers.concat(
            [layers.reshape(t, [rows, 1]) for t in proposals],
            axis=1)                                        # [R,k]
        adv, toks, accepted, fin_new = layers.spec_accept(
            props, dprobs_s, tprobs, seedv, stepv, k=k,
            end_id=end_id, max_len=maxT, greedy=greedy,
            base_seed=samp.base_seed, noise_tag=8)
        adv_g = layers.elementwise_mul(adv, act)           # [R]
        layers.span_scatter(tok_buf, toks,
                            layers.elementwise_add(stepv, ones_n),
                            adv_g)
        new_fin = layers.elementwise_max(
            fin, layers.elementwise_mul(fin_new, act))
        new_step = layers.elementwise_add(stepv, adv_g)
        room = layers.cast(layers.less_than(
            new_step, layers.fill_constant([1], "int64",
                                           float(maxT - 1))),
            "int64")
        new_act = layers.elementwise_mul(
            layers.elementwise_mul(
                act, layers.elementwise_sub(ones_n, new_fin)), room)
        # ---- device-side speculative accounting (the serving layer
        # deltas these per dispatch). Computed BEFORE the state
        # assigns: the in-place act update below would otherwise feed
        # the POST-tick mask into this tick's live/accepted sums ----
        live = layers.reduce_sum(act, keep_dim=True)       # [1]
        k_const = layers.fill_constant([1], "int64", float(k))
        one_c = layers.fill_constant([1], "int64", 1.0)
        acc_live = layers.elementwise_mul(accepted, act)   # [R]
        bumps = [
            ("spec_proposed",
             layers.elementwise_mul(live, k_const)),
            ("spec_accepted",
             layers.reduce_sum(acc_live, keep_dim=True)),
            ("spec_emitted",
             layers.reduce_sum(adv_g, keep_dim=True)),
            ("spec_target_steps", one_c)]
        if not ngram:
            # the n-gram lane runs ZERO draft-model steps — keeping
            # this counter honest is what makes the devtel
            # draft/target step ratio meaningful per flavor
            bumps.append(("spec_draft_steps", k_const))
        # per-lane acceptance telemetry: the host controller
        # (inference/spec_controller.py) deltas these each dispatch
        # to re-bucket lanes across the pre-built k ladder
        bumps.append(("spec_lane_accepted", acc_live))
        bumps.append(("spec_lane_ticks", act))
        for name, delta in bumps:
            var = sv[f"{state_prefix}{name}"]
            layers.assign(layers.elementwise_add(var, delta),
                          output=var)
        layers.assign(new_step, output=stepv)
        layers.assign(new_act, output=act)
        layers.assign(new_fin, output=fin)

    def _draft_keepalive(sv):
        # adaptive k=0 rung, model drafts only: run ONE cached draft
        # step at the current position (output dead-coded by XLA)
        # purely to keep the draft KV cache hole-free. Without it a
        # lane parked at k=0 advances its counter past positions the
        # draft never processed, and every later re-promotion to
        # k>0 proposes from a holey cache — the same permanent-hole
        # failure mode as skipping the j==k cache-fill step.
        dd, dH = draft.d_model, draft.n_heads
        stepv = sv[f"{state_prefix}step"]
        tok_buf = sv[f"{state_prefix}tok_buf"]
        positions = layers.cast(layers.range(0, maxT, 1), "int64")
        posf = layers.cast(positions, "float32")
        dpos_table = layers.assign(
            T._position_encoding(max(seq_len, maxT), dd)[:maxT])
        step2 = layers.reshape(stepv, [rows, 1])
        t_mask = layers.cast(layers.equal(positions, step2),
                             "float32")                    # [R,maxT]
        cur_tok = layers.reduce_sum(
            layers.elementwise_mul(tok_buf,
                                   layers.cast(t_mask, "int64")),
            dim=1, keep_dim=True)                          # [R,1]
        x = layers.embedding(cur_tok, size=[vocab, dd],
                             param_attr=ParamAttr(
                                 name=f"{draft.prefix}tgt_word_emb"))
        x = layers.unsqueeze(x, [1])
        x = layers.scale(x, scale=dd ** 0.5)
        pos_e = layers.matmul(t_mask, dpos_table)
        x = layers.elementwise_add(x, layers.unsqueeze(pos_e, [1]))
        dbias = layers.reshape(
            layers.scale(layers.cast(layers.greater_than(
                posf, layers.cast(step2, "float32")), "float32"),
                scale=-1e9),
            [rows, 1, 1, maxT])
        wm = layers.reshape(t_mask, [rows, 1, maxT, 1])
        km = layers.reshape(
            layers.elementwise_sub(
                layers.fill_constant([rows, maxT], "float32", 1.0),
                t_mask),
            [rows, 1, maxT, 1])
        dcaches = [
            _DenseLaneCache(sv[f"{state_prefix}draft_self_k{li}"],
                            sv[f"{state_prefix}draft_self_v{li}"],
                            wm, km)
            for li in range(draft.n_layers)]
        dcross = [_DenseCross(sv[f"{state_prefix}draft_cross_k{li}"],
                              sv[f"{state_prefix}draft_cross_v{li}"])
                  for li in range(draft.n_layers)]
        cached_decoder_step(x, dcaches, dcross, dbias, dd, dH,
                            draft.d_inner, prefix=draft.prefix)

    def _k0_body(sv):
        # graceful k->0 degradation: the plain (non-speculative) step
        # body — one target step, one token — plus the draft-cache
        # keepalive for model drafts. Spec scalar/lane counters are
        # deliberately NOT bumped (nothing proposed, nothing
        # verified); only the per-k tick counter records residency.
        if draft.kind == "model":
            _draft_keepalive(sv)
        _step_body(sv)
        _tel_add(sv, devtel.spec_k_logical(0),
                 layers.fill_constant([1], "int64", 1.0))

    body = _spec_step_body if spec else _step_body

    # --- standalone single-step program (one tick = one dispatch;
    # also the Executor.prepare(steps=K) scan target) ----------------
    step_prog = fluid.Program()
    with fluid.program_guard(step_prog, fluid.Program()):
        body(_mark_ownership(
            _declare_slot_state(step_prog.global_block, specs)))

    # --- the logical -> scope-name map of the slot state, and from it
    # what every serve program hands back (the packed row) and takes
    # in as feeds (the scheduler's tables) ---------------------------
    state = {"tok_buf": f"{state_prefix}tok_buf",
             "step": f"{state_prefix}step",
             "finished": f"{state_prefix}finished",
             "active": f"{state_prefix}active"}
    if paged:
        state["block_tab"] = f"{state_prefix}block_tab"
        state["prompt_ref"] = f"{state_prefix}prompt_ref"
        state["prefill_until"] = f"{state_prefix}prefill_until"
        if not spec:
            state["probe_probs"] = f"{state_prefix}probe_probs"
    if needs_seeds:
        state["seed"] = f"{state_prefix}seed"
    if spec:
        for c in SPEC_COUNTERS + SPEC_LANE_COUNTERS:
            state[c] = f"{state_prefix}{c}"
        if draft.k_options:
            state.update(devtel.spec_k_state_entries(
                state_prefix, draft.k_options))
    # devtel counters join the state map (and therefore the PTA150
    # counter-presence sweep) under their logical names
    state.update(devtel.state_entries(state_prefix, paged))
    fed_tables = ("block_tab", "prompt_ref", "active") if paged else ()
    row = serve_row_of(state_prefix, state, specs, cache)

    # --- fused serve programs: [admission +] a decode-burst While —
    # a WHOLE scheduler cycle (admit + burst) is ONE dispatch, so the
    # host overhead amortizes over A admissions and a burst of tokens
    # per lane. The loop exits when EITHER n_steps ticks ran OR the
    # live-lane count drops to min_active (both fed): with a
    # non-empty host queue the server sets min_active = live - 1, so
    # control returns the MOMENT a lane retires and its slot refills
    # — iteration-level scheduling with zero zombie ticks — while an
    # empty queue sets min_active = 0 and the burst drains the pool.
    # One specialization per admission flavor x bucket (0: no
    # admission). ---------------------------------------------------
    def _build_serve(tier, A, step_body=None):
        def pre(sv):
            if A > 0:
                admit_bodies[tier](sv, A)
        return _serve_program(pre, step_body)

    def _serve_program(pre_body, step_body=None):
        # step_body overrides the bundle's default tick body — the
        # adaptive-k serve variants swap in _spec_step_body(k=kv) or
        # _k0_body while sharing the SAME slot-state specs, so
        # controller re-bucketing is pure program selection (all
        # executables built up front, zero steady-state compiles)
        return build_serve_program(
            specs, state_prefix, pre_body,
            body if step_body is None else step_body, row,
            mark=_mark_ownership, fed=fed_tables)

    # --- chunked-prefill phase bodies (cache.chunk_tokens > 0): the
    # miss admission's encoder, re-cut into resumable C-token ticks.
    # The encoder is BIDIRECTIONAL — layer l+1 needs layer l at ALL
    # prompt positions — so "C tokens per tick" must be phase-major:
    # phase p runs over every chunk cursor before phase p+1 starts.
    # Phases: 0 = embed+positional into stage_a; 1+2l = layer l's
    # fused qkv projection of a chunk into stage_kv (per-position —
    # chunkable); 2+2l = layer l's attention (C queries over the FULL
    # staged K/V) + add_norm + ffn + add_norm into the other stage;
    # 2L+1 = the per-layer cross-KV projection of a chunk, installed
    # into the prompt entry's cross pools. Every op is per-position
    # outside `layers.attention`, and the attention phase reads
    # complete staged K/V, so the chunked pipeline is BIT-EXACT vs
    # the monolithic _admit_body_paged_miss encoder (asserted in
    # tests) — which is what lets a chunk-prefilled entry finish as
    # an ordinary prefix HIT. Ragged last chunks need no extra
    # masking: an out-of-range cursor row of the chunk-selection
    # one-hot is all-zero, so its scatter contributes nothing and
    # `keep` preserves the row.
    def _chunk_phase_body(sv, p):
        C, S, L = cache.chunk_tokens, seq_len, n_layers
        entry = layers.data("chunk_entry", shape=[1], dtype="int64",
                            append_batch_size=False)
        pos0 = layers.data("chunk_pos", shape=[1], dtype="int64",
                           append_batch_size=False)
        # mint-site ownership marks: the entry is a host-FRESH
        # prompt-pool index (refcount==1 for the whole prefill — the
        # same allocator invariant the monolithic miss admission's
        # prompt_slots ride), and the chunk cursor is a host-bounded
        # position index (< seq_len) — marking it keeps the
        # PTA190 provenance chain closed over the staging writes
        # instead of silently downgrading the prover
        absint.mark_pool_index_source(entry, "host_indices",
                                      bound=E + 1)
        absint.mark_pool_index_source(pos0, "chunk_cursor", bound=S)
        # devtel: one chunk ticked; how many decode lanes were live
        # while it did (the prefill-vs-decode occupancy split)
        _tel_add(sv, "tel_chunks",
                 layers.fill_constant([1], "int64", 1.0))
        _tel_add(sv, "tel_prefill_occupancy",
                 layers.reduce_sum(sv[f"{state_prefix}active"],
                                   keep_dim=True))
        # [C, S] chunk-position one-hot: row c selects prompt
        # position pos0+c (all-zero past seq_len — ragged tail)
        sr = layers.cast(layers.range(0, S, 1), "int64")
        cr = layers.cast(layers.range(0, C, 1), "int64")
        csel = layers.cast(
            layers.equal(sr, layers.elementwise_add(
                layers.reshape(cr, [C, 1]), pos0)), "float32")
        cselT = layers.transpose(csel, perm=[1, 0])       # [S, C]
        keep = layers.reshape(
            layers.elementwise_sub(
                layers.fill_constant([S], "float32", 1.0),
                layers.reduce_sum(csel, dim=0)), [S, 1])
        stage = [sv[f"{state_prefix}chunk_stage_a{POOL_MARK}"],
                 sv[f"{state_prefix}chunk_stage_b{POOL_MARK}"]]
        stage_kv = sv[f"{state_prefix}chunk_stage_kv{POOL_MARK}"]

        def _stage_row(pool, width):                      # [S, width]
            return layers.reshape(layers.gather(pool, entry),
                                  [S, width])

        def _chunk_of(row, width):                     # [1, C, width]
            return layers.reshape(layers.matmul(csel, row),
                                  [1, C, width])

        def _stage_merge(pool, row, chunk2d):
            # RMW the entry row: this tick's C positions replaced,
            # every other position kept — the one-hot matmul scatter
            # is exact (single nonzero per column)
            merged = layers.elementwise_add(
                layers.elementwise_mul(row, keep),
                layers.matmul(cselT, chunk2d))
            layers.masked_pool_write(
                pool, layers.unsqueeze(merged, [0]), entry,
                leading_dims=1, exclusive_via="host_indices")

        if p == 0:
            # embed the chunk's tokens + positional encoding (the
            # _embed math at chunk offsets; garbage pad tokens of a
            # ragged tail embed then scatter to nothing)
            toks = layers.data("chunk_toks", shape=[1, C],
                               dtype="int64",
                               append_batch_size=False)
            emb = layers.embedding(toks, size=[vocab, d_model],
                                   param_attr=ParamAttr(
                                       name="src_word_emb"))
            # C == 1 hits lookup_table's trailing-1 id-axis squeeze
            # ([1,1] ids give [1,D]) — restore the [1,C,D] rank
            emb = layers.reshape(emb, [1, C, d_model])
            emb = layers.scale(emb, scale=d_model ** 0.5)
            pos_tab = layers.assign(
                T._position_encoding(max(S, maxT), d_model)[:S])
            x = layers.elementwise_add(
                emb, layers.matmul(csel, pos_tab), axis=1)
            _stage_merge(stage[0], _stage_row(stage[0], d_model),
                         layers.reshape(x, [C, d_model]))
            return
        if p <= 2 * L:
            l = (p - 1) // 2
            xrow = _stage_row(stage[l % 2], d_model)
            x = _chunk_of(xrow, d_model)
            # same fused-qkv param as encoder_layer's self-attention
            qkv = layers.fc(x, 3 * d_model, num_flatten_dims=2,
                            bias_attr=False,
                            param_attr=T._attn_proj_attr(
                                f"enc{l}_self", "qkv", d_model))
            q, k, v = layers.split(qkv, 3, dim=2)
            if (p - 1) % 2 == 0:
                # kv phase: stage this chunk's K/V columns (fc is
                # per-position — chunkable; q recomputes next phase)
                _stage_merge(
                    stage_kv, _stage_row(stage_kv, 2 * d_model),
                    layers.reshape(layers.concat([k, v], axis=2),
                                   [C, 2 * d_model]))
                return
            # attention phase: C queries over the layer's FULL
            # staged K/V, then the per-position encoder tail
            kvrow = _stage_row(stage_kv, 2 * d_model)     # [S, 2D]
            kf, vf = layers.split(kvrow, 2, dim=1)
            q4 = layers.reshape(q, [0, 0, n_heads, head_dim])
            k4 = layers.reshape(kf, [1, S, n_heads, head_dim])
            v4 = layers.reshape(vf, [1, S, n_heads, head_dim])
            ctx = layers.attention(q4, k4, v4, causal=False,
                                   scale=head_dim ** -0.5,
                                   dropout_rate=0.0, layout="bthd")
            ctx = layers.reshape(ctx, [0, 0, d_model])
            attn = layers.fc(ctx, d_model, num_flatten_dims=2,
                             bias_attr=False,
                             param_attr=f"enc{l}_self_out.w")
            x1 = T._add_norm(attn, x, 0.0, True, name=f"enc{l}_a")
            ffn = T._ffn(x1, d_model, d_inner, 0.0, True,
                         name=f"enc{l}")
            x2 = T._add_norm(ffn, x1, 0.0, True, name=f"enc{l}_b")
            out_pool = stage[(l + 1) % 2]
            _stage_merge(out_pool, _stage_row(out_pool, d_model),
                         layers.reshape(x2, [C, d_model]))
            return
        # final phase: project the chunk's cross-attention K/V for
        # every decoder layer and merge it into the prompt entry's
        # rows of the cross pools, which are stored [S, H*Dh] an entry
        # as the staging pools are
        xrow = _stage_row(stage[L % 2], d_model)
        x = _chunk_of(xrow, d_model)
        for li in range(n_layers):
            kvp = layers.fc(x, 2 * d_model, num_flatten_dims=2,
                            bias_attr=False,
                            param_attr=T._attn_proj_attr(
                                f"dec{li}_cross", "kv", d_model))
            k, v = layers.split(kvp, 2, dim=2)
            for tag, val in (("k", k), ("v", v)):
                pool = sv[f"{state_prefix}cross_{tag}{li}"
                          f"{POOL_MARK}"]
                _stage_merge(pool, _stage_row(pool, d_model),
                             layers.reshape(val, [C, d_model]))

    serves = {0: _build_serve("miss", 0)}
    for A in admit_buckets:
        if paged:
            serves[("miss", A)] = _build_serve("miss", A)
            serves[("hit", A)] = _build_serve("hit", A)
            if "radix" in admit_bodies:
                serves[("radix", A)] = _build_serve("radix", A)
        else:
            serves[A] = _build_serve("miss", A)
    if paged and cache.chunked:
        # one serve program per phase, each fused with the SAME
        # decode While as key 0 — a chunk dispatch IS a decode burst
        # with a chunk bolted on the front, so live lanes keep
        # ticking while the chunk computes (the two-tier schedule);
        # executable count grows by exactly 2*n_layers+2 programs
        for p in range(2 * n_layers + 2):
            serves[("chunked", p)] = _serve_program(
                lambda sv, _p=p: _chunk_phase_body(sv, _p))
    if spec and draft.k_options:
        # --- adaptive-k serve variants: for every non-default rung
        # of the ladder, rebuild each (admission x bucket) flavor
        # with the tick body pinned at that k. Keyed ("k", kv,
        # base_key); serve_feed_spec recurses to the base key, and
        # every variant declares the SAME slot-state specs, so the
        # host controller re-buckets lanes by pure program selection
        # — the executable count is bounded at build time
        # (|ladder|-1 extra copies of the non-chunked serve set) and
        # steady state compiles NOTHING. k decisions stay host
        # policy: no new device predicate is minted here (the burst
        # cond is the same lane_active_mask-marked one).
        base_keys = [bk for bk in serves
                     if not (isinstance(bk, tuple)
                             and bk[0] == "chunked")]
        for kv in draft.k_options:
            if kv == draft.k:
                continue
            kv_body = (_k0_body if kv == 0
                       else (lambda sv, _k=kv:
                             _spec_step_body(sv, _k)))
            for bk in base_keys:
                tier, A = (bk, 0) if bk == 0 else (
                    ("miss", bk) if isinstance(bk, int)
                    else bk)
                serves[("k", kv, bk)] = _build_serve(
                    tier, A, step_body=kv_body)

    # --- COW block copy (paged only): gather the SHARED source rows
    # and masked-write them into freshly allocated EXCLUSIVE blocks —
    # the one lowering through which a lane may diverge from a shared
    # chain (beam branching, partial-page session resume). A block is
    # the BS consecutive cell rows b*BS..b*BS+BS-1 of the [NB*BS, H*Dh]
    # pool, so the copy addresses rows along dim 0 only and stays
    # layout-oblivious under tp (the sharded minor axis is never
    # reshaped or reduced). Padded rows feed gate 0 AND dst -1 (every
    # cell of it negative: dropped), so one fixed-shape program serves
    # any copy count. --
    cow_prog = None
    if paged:
        cow_prog = fluid.Program()
        with fluid.program_guard(cow_prog, fluid.Program()):
            sv = _mark_ownership(
                _declare_slot_state(cow_prog.global_block, specs))
            csrc = layers.data("cow_src", shape=[rows], dtype="int64",
                               append_batch_size=False)
            cdst = layers.data("cow_dst", shape=[rows], dtype="int64",
                               append_batch_size=False)
            cgate = layers.data("cow_gate", shape=[rows],
                                dtype="float32",
                                append_batch_size=False)
            # mint-site ownership marks (analysis/absint.py seed
            # table): sources are refcount>=1 SHARED chain blocks
            # (read-legal, write-ILLEGAL — PTA192 proves no write
            # chains from them), destinations are host-fresh
            # exclusive allocations (the COW window)
            absint.mark_pool_index_source(csrc, "cow_src", bound=NB)
            absint.mark_pool_index_source(cdst, "cow_dst", bound=NB)
            offs = layers.assign(np.arange(BS, dtype="float32"))
            src_cells, dst_cells = (
                cells_of_blocks(layers.cast(blocks, "float32"), BS,
                                offs) for blocks in (csrc, cdst))
            cell_gate = layers.reshape(
                layers.expand(layers.unsqueeze(cgate, [1]), [1, BS]),
                [rows * BS])
            for li in range(n_layers):
                for tag in ("k", "v"):
                    pool = sv[f"{state_prefix}self_{tag}{li}"
                              f"{POOL_MARK}"]
                    layers.masked_pool_write(
                        pool, layers.gather(pool, src_cells),
                        dst_cells, cell_gate, leading_dims=1,
                        exclusive_via="cow_dst")
            _tel_add(sv, "tel_cow_blocks",
                     layers.reduce_sum(layers.cast(cgate, "int64"),
                                       keep_dim=True))

    # --- probe step (paged, non-spec): one decode tick that ALSO
    # publishes every lane's full softmax row to probe_probs — the
    # paged beam decoder's expansion oracle (host selects tokens,
    # device owns KV; under permanent teacher forcing the tick never
    # writes tok_buf or latches fin) ---------------------------------
    probe_prog = None
    if paged and not spec:
        probe_prog = fluid.Program()
        with fluid.program_guard(probe_prog, fluid.Program()):
            _step_body(_mark_ownership(_declare_slot_state(
                probe_prog.global_block, specs)), probe=True)

    bundle = DecodeStepBundle(prefills, step_prog, serves, startup,
                              state, n_slots, seq_len, maxT, start_id,
                              end_id, cache=cache,
                              hit_prefills=hit_prefills,
                              sampling=sampling, draft=draft,
                              cow=cow_prog, probe=probe_prog,
                              fed_tables=fed_tables, serve_row=row)
    bundle._state_specs = {
        n: (shape, dt) for n, (shape, dt) in specs.items()}
    if sharding is not None and sharding.enabled:
        _apply_tp_sharding(bundle, sharding, n_layers)
    return bundle


# ---------------------------------------------------------------------------
# Beam front (the last decode loop folded in from transformer.py —
# every decode capability now lives in this module).
# ---------------------------------------------------------------------------
def build_beam_decode_program(seq_len=16, max_out_len=16, d_model=64,
                              n_heads=4, n_layers=2, d_inner=128,
                              vocab=1000, start_id=0, end_id=1,
                              beam_size=4, batch_size=1):
    """Batched beam-search generation (reference
    tests/unittests/dist_transformer.py:1523 beam_search inside
    fast_decode). Beams ride the batch axis at static
    [batch*beam, maxT] shapes (batch-major blocks of beam rows, the
    beam_search op's row layout): every step runs the causally-masked
    decoder over all rows, expands per-source with the beam_search op
    (accumulated log-probs, EOS freezing), reorders each hypothesis'
    token history by absolute parent_idx, and backtracks with
    beam_search_decode.

    Weight sharing: the explicit enc{i}_*/dec{i}_*/logits.w names.
    Returns (program, startup, feeds, (sentence_ids
    [T, batch*beam], sentence_scores [batch*beam])).
    """
    import paddle_tpu as fluid

    from . import transformer as T

    maxT = max_out_len
    rows = batch_size * beam_size
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        # static-batch program so build-time probes agree with the
        # concrete [rows, ...] vars downstream
        src = layers.data("src_ids", shape=[batch_size, seq_len],
                          dtype="int64", append_batch_size=False)
        enc1 = T._embed(src, vocab, d_model, max(seq_len, maxT), 0.0,
                        True, "src_word_emb")
        for li in range(n_layers):
            enc1 = T.encoder_layer(enc1, d_model, n_heads, d_inner,
                                   0.0, is_test=True, name=f"enc{li}")
        # repeat each source's encoding beam_size times consecutively
        # ([B,S,D] -> [B,beam,S,D] -> [B*beam,S,D], batch-major rows)
        enc = layers.reshape(
            layers.expand(layers.unsqueeze(enc1, [1]),
                          [1, beam_size, 1, 1]),
            [rows, seq_len, d_model])

        positions = layers.cast(layers.range(0, maxT, 1), "int64")
        # per-hypothesis token history [rows, maxT], GO at position 0
        tgt_buf = layers.assign(layers.fill_constant(
            [rows, maxT], "int64", 0.0))
        if start_id:
            start_col = layers.cast(
                layers.equal(positions,
                             layers.fill_constant([1], "int64", 0.0)),
                "int64")
            tgt_buf = layers.assign(layers.elementwise_add(
                tgt_buf, layers.cast(
                    layers.scale(start_col, scale=float(start_id)),
                    "int64")))
        pre_ids = layers.assign(layers.fill_constant(
            [rows, 1], "int64", float(start_id)))
        # ONE live beam per source at step 0 (the reference's LoD
        # single-seed): identical rows with equal scores would make
        # per-block top-k pick beam_size copies of the same argmax and
        # the beams would never diverge (degenerate greedy)
        pre_scores = layers.assign(np.where(
            np.arange(rows) % beam_size == 0, 0.0,
            -1e9).astype("float32").reshape(rows, 1))
        # step buffers for the backtrack [maxT, rows, 1]
        ids_buf = layers.assign(layers.fill_constant(
            [maxT, rows, 1], "int64", float(end_id)))
        scores_buf = layers.assign(layers.fill_constant(
            [maxT, rows, 1], "float32", 0.0))
        parents_buf = layers.assign(layers.fill_constant(
            [maxT, rows, 1], "int64", 0.0))
        zero = layers.fill_constant([1], "int64", 0)
        ids_buf = layers.assign(layers.scatter(
            ids_buf, zero, layers.reshape(pre_ids, [1, rows, 1])))

        counter = layers.fill_constant([1], "int64", 0)
        limit = layers.fill_constant([1], "int64", float(maxT - 1))
        cond = layers.less_than(counter, limit)
        w = layers.While(cond)
        with w.block():
            dec = T._embed(tgt_buf, vocab, d_model,
                           max(seq_len, maxT), 0.0, True,
                           "tgt_word_emb")
            for li in range(n_layers):
                dec = T.decoder_layer(dec, enc, d_model, n_heads,
                                      d_inner, 0.0, is_test=True,
                                      name=f"dec{li}")
            logits_v = step_logits(dec, positions, counter,
                                   vocab)  # [rows, V]
            probs = layers.softmax(logits_v)  # [rows, V]
            topk_scores, topk_ids = layers.topk(
                probs, min(2 * beam_size, vocab))
            acc = layers.elementwise_add(layers.log(topk_scores),
                                         pre_scores)
            sel_ids, sel_scores, parent = layers.beam_search(
                pre_ids, pre_scores, topk_ids, acc,
                beam_size=beam_size, end_id=end_id,
                return_parent_idx=True)
            parent_flat = layers.reshape(parent, shape=[rows])
            # each surviving hypothesis inherits its parent's history
            layers.assign(layers.gather(tgt_buf, parent_flat),
                          output=tgt_buf)
            layers.increment(counter, 1)
            next_mask = layers.cast(layers.equal(positions, counter),
                                    "int64")
            keep = layers.elementwise_sub(
                layers.fill_constant([maxT], "int64", 1.0), next_mask)
            layers.assign(layers.elementwise_add(
                layers.elementwise_mul(tgt_buf, keep),
                layers.elementwise_mul(
                    layers.reshape(sel_ids, [rows, 1]),
                    next_mask)), output=tgt_buf)
            layers.assign(layers.scatter(
                ids_buf, counter,
                layers.reshape(sel_ids, [1, rows, 1])),
                output=ids_buf)
            layers.assign(layers.scatter(
                scores_buf, counter,
                layers.reshape(sel_scores, [1, rows, 1])),
                output=scores_buf)
            layers.assign(layers.scatter(
                parents_buf, counter,
                layers.reshape(parent, [1, rows, 1])),
                output=parents_buf)
            layers.assign(layers.reshape(sel_ids, [rows, 1]),
                          output=pre_ids)
            layers.assign(layers.reshape(sel_scores, [rows, 1]),
                          output=pre_scores)
            layers.less_than(counter, limit, cond=cond)
        out_ids, out_scores = layers.beam_search_decode(
            ids_buf, scores_buf, beam_size=beam_size, end_id=end_id,
            parents=parents_buf)
    return main, startup, ["src_ids"], (out_ids, out_scores)


# ---------------------------------------------------------------------------
# Host-side allocation policy (plain Python; the device only sees the
# tables the scheduler writes into the scope).
# ---------------------------------------------------------------------------
class ServingUnavailable(RuntimeError):
    """Base of the serving-layer rejection taxonomy: every named
    condition under which the front door cannot take (or keep) a
    request derives from this ONE class, carrying the machine-readable
    retry contract — ``retryable`` (may the caller resubmit the same
    request and expect a different outcome?) and ``retry_after_ms``
    (earliest point a retry is worth attempting, ``None`` = no
    estimate). Retry logic anywhere above (runtime Router, clients)
    dispatches on ``isinstance`` + these attributes ONLY — never on
    message text (the r20 taxonomy contract; message-substring
    matching is what this base exists to delete).

    Subclasses: ``BlockPoolExhausted``/``ServerQuiesced``/
    ``ServerClosed`` (transient, retryable), ``AdmissionInfeasible``
    (config can never admit — not retryable), the Router's
    ``AdmissionError`` family including the deadline-shed rejection
    (retryability depends on the reason). Reference counterpart: none
    — the reference's serving errors are bare PADDLE_ENFORCE strings
    (inference/api/analysis_predictor.cc); a typed retry contract is
    the multi-tenant front-door tier this layer adds."""

    retryable = False
    retry_after_ms = None


class BlockPoolExhausted(ServingUnavailable):
    """The shared KV block pool (or the prompt-entry pool) cannot
    satisfy an allocation AND nothing in flight can ever free one —
    a NAMED, RETRYABLE error (``retryable=True``): the caller may
    resubmit once other requests retire, or against a server with a
    larger pool. Raised instead of hanging the scheduler (the r13
    acceptance contract); transient pressure is handled by queueing/
    pausing, never by this error."""

    retryable = True
    retry_after_ms = 50.0


class AdmissionInfeasible(ServingUnavailable):
    """The serving CONFIGURATION (not transient load) can never admit
    this request: the liveness capacity model
    (analysis/liveness.py ``session_feasibility``, validated against
    the exhaustive protomodel explorer) proves steady-state demand
    exceeds a static pool — e.g. more distinct session prompts than
    ``n_prompt_entries``, each pinning an entry for its session
    lifetime. NAMED and NOT retryable (``retryable=False``): unlike
    ``BlockPoolExhausted``, waiting cannot help — pinned entries are
    unevictable until a session closes, so the preflight raises up
    front instead of letting admissions wedge silently at runtime.

    Reference counterpart: none — the reference admits until OOM
    (runtime PADDLE_ENFORCE); a provably-infeasible-config error is
    the capacity-model tier this layer adds."""

    retryable = False


class BlockLifetimeError(ValueError):
    """A host-allocator call violated the per-block lifetime lattice
    ``free → exclusive(lane) → shared(refcount>1) → freed``: freeing
    an unallocated or already-freed block, or releasing a zero-ref
    prompt entry. NAMED (and a ValueError subclass for callers that
    caught the old bare error) so the scheduler fails loudly at the
    bad transition instead of silently corrupting the free list —
    the next alloc would hand one block to TWO lanes and break the
    very disjointness invariant the ownership prover (PTA191)
    assumes. The full automaton is property-tested in
    tests/test_block_pool_model.py."""


class HostBlockPool:
    """Free-list over the ``n_blocks`` shared self-KV blocks, run as
    an explicit TYPESTATE machine riding per-block refcounts:
    ``free -> exclusive (refcount==1, owned by one lane) -> shared
    (refcount>1, read-only radix prefix) -> free``. This is the host
    half of the lane-exclusivity story the ownership prover leans on
    — its alloc-disjoint invariant is the NAMED assumption
    (``HostBlockPool.alloc-disjoint``, analysis/absint.py ownership
    seed table) under which PTA191 proves distinct lanes' pool
    writes hit disjoint rows: every block a lane can WRITE (the
    write-reachable suffix of its table) is exclusive to it; shared
    blocks may appear in many tables but only in the read-only
    prefix below ``resume_step`` (PTA192's read-only-while-shared is
    the device half, the host half is ``writable()`` here). Invalid
    transitions raise ``BlockLifetimeError`` instead of corrupting
    the free list (a double-freed block would be handed to two
    lanes)."""

    def __init__(self, n_blocks: int):
        self.n_blocks = int(n_blocks)
        self._free = list(range(self.n_blocks))
        self._state = ["free"] * self.n_blocks
        self._refs = [0] * self.n_blocks

    def alloc(self) -> Optional[int]:
        if not self._free:
            return None
        b = self._free.pop()
        self._state[b] = "exclusive"
        self._refs[b] = 1
        return b

    def free(self, blocks):
        """Strict single-owner free: legal ONLY from the exclusive
        (refcount==1) typestate — the legacy lane-release path.
        Radix-aware callers holding one ref among several use
        ``decref`` instead; routing a possibly-shared block through
        here raises rather than yanking KV other lanes attend to."""
        blocks = list(blocks)
        seen = set()
        for b in blocks:
            if not 0 <= b < self.n_blocks:
                raise BlockLifetimeError(
                    f"free of block {b} outside the pool "
                    f"[0, {self.n_blocks})")
            if self._state[b] != "exclusive" or b in seen:
                raise BlockLifetimeError(
                    f"free of block {b} in typestate "
                    f"{'freed-in-this-call' if b in seen else self._state[b]!r} "
                    f"(legal only from 'exclusive'): double-free/"
                    f"unallocated/shared free would hand one block "
                    f"to two lanes")
            seen.add(b)
        for b in blocks:
            self._state[b] = "free"
            self._refs[b] = 0
            self._free.append(b)

    # --- refcount surface (the radix tree + COW path) ----------------
    def incref(self, block: int) -> int:
        """A new reader adopts the block (radix-tree node, extra lane
        mapping it read-only, COW source pin). refcount 1 -> 2 is the
        exclusive -> shared transition."""
        if not 0 <= block < self.n_blocks:
            raise BlockLifetimeError(
                f"incref of block {block} outside the pool "
                f"[0, {self.n_blocks})")
        if self._refs[block] <= 0:
            raise BlockLifetimeError(
                f"incref of block {block} in typestate "
                f"{self._state[block]!r} (refcount 0): a freed block "
                f"may be re-handed to another lane at any alloc")
        self._refs[block] += 1
        self._state[block] = "shared"
        return self._refs[block]

    def decref(self, block: int) -> int:
        """Drop one reference; at refcount 0 the block returns to the
        free list (the shared -> exclusive -> free unwinding; a
        decref from refcount 1 IS the radix-aware free)."""
        if not 0 <= block < self.n_blocks:
            raise BlockLifetimeError(
                f"decref of block {block} outside the pool "
                f"[0, {self.n_blocks})")
        if self._refs[block] <= 0:
            raise BlockLifetimeError(
                f"decref of block {block} at refcount "
                f"{self._refs[block]}: refcounts never go negative — "
                f"a double decref would free KV another reader still "
                f"attends to")
        self._refs[block] -= 1
        if self._refs[block] == 0:
            self._state[block] = "free"
            self._free.append(block)
        elif self._refs[block] == 1:
            self._state[block] = "exclusive"
        return self._refs[block]

    def refcount(self, block: int) -> int:
        return self._refs[block]

    def writable(self, block: int) -> bool:
        """True while a device write into the block is legal:
        refcount == 1 (single owner). A lane's first write into a
        SHARED block must COW — copy into a fresh exclusive block,
        then decref the shared source — never write through the
        shared path (checker PTA192's host half)."""
        return self._refs[block] == 1

    def typestate(self, block: int) -> str:
        return self._state[block]

    def live_blocks(self) -> set:
        return {b for b, s in enumerate(self._state)
                if s != "free"}

    def shared_blocks(self) -> set:
        return {b for b, s in enumerate(self._state)
                if s == "shared"}

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.n_blocks - len(self._free)


class PromptPrefixCache:
    """Refcounted exact-prompt cache over the cross-KV entry pool,
    with block-hash-chain partial detection (the SGLang/RadixAttention
    shape at whole-prompt granularity: this framework's encoder is
    BIDIRECTIONAL, so a cross-KV column depends on the WHOLE prompt
    and only a full-content match may reuse an entry; a leading-chunk
    match is reported as the ``partial`` tier — re-prefilled like a
    miss, and counted as a copy-on-write materialization — which a
    causal-encoder model could upgrade to true radix reuse).

    Entries are pinned while any lane references them (``refs > 0``);
    unpinned entries stay cached LRU and are evicted only when a miss
    needs a slot. Counters feed the block-pool observability gauges
    (prefix_hits/misses/partials=cow_copies, evictions)."""

    def __init__(self, n_entries: int, chunk_tokens: int):
        self.n_entries = int(n_entries)
        self.chunk = max(1, int(chunk_tokens))
        self._free = list(range(self.n_entries))
        self._by_prompt: Dict[tuple, int] = {}   # prompt -> entry
        self._entry_prompt: Dict[int, tuple] = {}
        self._refs: Dict[int, int] = {}
        self._lru: "Dict[tuple, None]" = {}      # insertion-ordered
        self._heads: Dict[tuple, int] = {}       # first chunk -> count
        self.hits = 0
        self.misses = 0
        self.partials = 0       # exposed as cow_copies
        self.evictions = 0

    def _head(self, prompt: tuple) -> tuple:
        return prompt[:self.chunk]

    def lookup(self, prompt: tuple) -> Tuple[str, Optional[int]]:
        """('hit', entry) on a full-content match, ('partial', None)
        when only a leading chunk matches a cached prompt, else
        ('miss', None). Pure lookup — no counters, no refcounts (the
        scheduler may probe the queue head every cycle)."""
        entry = self._by_prompt.get(prompt)
        if entry is not None:
            return "hit", entry
        if self._heads.get(self._head(prompt)):
            return "partial", None
        return "miss", None

    def acquire_hit(self, prompt: tuple) -> int:
        entry = self._by_prompt[prompt]
        self._refs[entry] = self._refs.get(entry, 0) + 1
        self._lru.pop(prompt, None)
        self._lru[prompt] = None
        self.hits += 1
        return entry

    def acquire_fresh(self, prompt: tuple,
                      partial: bool = False) -> Optional[int]:
        """Entry for a cold prompt: a free slot, else the LRU
        UNPINNED entry (evicted). None when every entry is pinned —
        the caller backpressures (or, with nothing in flight, raises
        BlockPoolExhausted)."""
        if self._free:
            entry = self._free.pop()
        else:
            victim = next((p for p in self._lru
                           if self._refs.get(self._by_prompt[p],
                                             0) == 0), None)
            if victim is None:
                return None
            entry = self._by_prompt.pop(victim)
            self._lru.pop(victim, None)
            self._entry_prompt.pop(entry, None)
            head = self._head(victim)
            self._heads[head] -= 1
            if not self._heads[head]:
                del self._heads[head]
            self.evictions += 1
        self._by_prompt[prompt] = entry
        self._entry_prompt[entry] = prompt
        self._refs[entry] = 1
        self._lru[prompt] = None
        self._heads[self._head(prompt)] = \
            self._heads.get(self._head(prompt), 0) + 1
        if partial:
            self.partials += 1
        else:
            self.misses += 1
        return entry

    def release(self, entry: int):
        refs = self._refs.get(entry, 0)
        if refs <= 0:
            raise BlockLifetimeError(
                f"release of prompt entry {entry} at refcount "
                f"{refs}: refcounts are monotone within a lifetime "
                f"(acquire+/release-) and never go negative — a "
                f"double release would unpin an entry another lane "
                f"still attends to")
        self._refs[entry] = refs - 1

    def invalidate(self, entry: int):
        """Forget an UNPINNED entry's prompt mapping and return the
        slot to the free list — for an ABANDONED part-written prefill
        (a chunked-prefill job whose dispatch failed mid-fill): the
        prompt must never again be looked up as a hit against stale
        cross-KV. Raises while any lane still references the entry
        (typestate: only a free entry may be forgotten)."""
        if self._refs.get(entry, 0) > 0:
            raise BlockLifetimeError(
                f"invalidate of prompt entry {entry} at refcount "
                f"{self._refs[entry]}: a referenced entry is still "
                f"attended to — release every ref first")
        prompt = self._entry_prompt.pop(entry, None)
        if prompt is None:
            return
        del self._by_prompt[prompt]
        self._lru.pop(prompt, None)
        head = self._head(prompt)
        self._heads[head] -= 1
        if not self._heads[head]:
            del self._heads[head]
        self._refs.pop(entry, None)
        self._free.append(entry)

    # --- the refcount typestate surface (the COW contract PTA192
    # checks the device half of): free -> exclusive (refcount==1) ->
    # shared (refcount>1) -> back; writes to an entry's KV are only
    # legal while it is EXCLUSIVE — acquire_fresh's refcount==1
    # window is when admission prefill writes happen, and the
    # ``PromptPrefixCache.fresh-exclusive`` assumption PTA191 names
    # is exactly that window's guarantee. ----------------------------
    def refcount(self, entry: int) -> int:
        return self._refs.get(entry, 0)

    def is_shared(self, entry: int) -> bool:
        return self.refcount(entry) > 1

    def writable(self, entry: int) -> bool:
        """True while a write to the entry's pooled KV is legal:
        refcount <= 1 (nobody else attends to it). A COW lowering
        must check this (or copy to a fresh entry) before mutating."""
        return self.refcount(entry) <= 1

    def typestate(self, entry: int) -> str:
        refs = self.refcount(entry)
        if refs == 0:
            return "free"
        return "exclusive" if refs == 1 else "shared"

    @property
    def in_use(self) -> int:
        return sum(1 for r in self._refs.values() if r > 0)


class BlockKeys(list):
    """A token sequence already cut into the radix tree's keys, one
    hashable a whole block (`bytes` of the block's ids, say): what a
    caller with prompts of tens of thousands of tokens hands
    `RadixBlockTree` in place of the tokens, so that no call makes a
    tuple of every id."""


class _RadixNode:
    __slots__ = ("chunk", "block", "children", "parent", "order",
                 "queued")

    def __init__(self, chunk, block, parent, seq):
        self.chunk = chunk        # the BS-token tuple this edge spells
        #                           (a root sentinel: its prompt key)
        self.block = block        # pool block holding its self-KV
        self.children = {}        # chunk tuple -> _RadixNode
        self.parent = parent
        # eviction rank (RadixBlockTree._leaves): a root carries its
        # insertion number, a node its parent's rank plus its own
        # insertion number NEGATED, so the rank is one longer than the
        # node is deep — among equal depths the smallest rank is the
        # oldest root's leaf that a depth-first descent through each
        # node's newest child meets first
        self.order = (seq,) if parent is None \
            else parent.order + (-seq,)
        self.queued = False       # one entry of _leaves names it


class RadixBlockTree:
    """Host-side radix tree over decoded-token -> self-KV block
    chains (the SGLang/RadixAttention longest-shared-prefix shape,
    PAPERS.md, on vLLM-style block tables — reference counterpart:
    none; the reference framework's fast_decode
    (tests/unittests/dist_transformer.py:1498) holds per-request
    dense caches with nothing shareable).

    Granularity is one FULL block (``block_size`` tokens): a node is
    a block whose KV is fully determined by the root prompt plus the
    token chunks spelling the path to it. Roots are keyed by the
    PROMPT CONTENT tuple — this framework's encoder is bidirectional,
    so every self-KV row also attends cross-attention values derived
    from the whole prompt, and chains are shareable only between
    requests with the SAME prompt (the cross-KV entry the
    PromptPrefixCache already dedupes).

    Refcount protocol (HostBlockPool): the tree holds ONE ref per
    adopted node (``incref`` at insert); every lane mapping a chain
    read-only holds one ref per block (``acquire``/``release``). A
    node whose block is at refcount 1 is tree-only and evictable —
    ``evict`` drops such LEAF nodes (never an interior node: its
    children's KV transitively depends on it), which is exactly the
    "eviction only unpins refcount-0 subtrees" invariant
    tests/test_block_pool_model.py property-checks.

    No call but ``tree_blocks`` (the tests' oracle) visits every
    node: ``_leaves`` is a heap of the leaves, deepest first, kept up
    to date as ``insert`` and ``evict`` add and drop nodes. Lanes
    drop their refs through ``HostBlockPool.decref``, so the tree is
    never told when a block returns to refcount 1: the heap holds
    every leaf, pinned or not, and ``evict`` reads the refcount of
    each candidate it takes."""

    def __init__(self, pool: "HostBlockPool", block_size: int):
        self.pool = pool
        self.block_size = max(1, int(block_size))
        self._roots: Dict[tuple, _RadixNode] = {}
        # (-len(order), order, node), at most one entry a node (its
        # ``queued``); an entry whose node has grown a child since is
        # stale and dropped when it surfaces
        self._leaves: List[tuple] = []
        self._seq = 0             # insertion numbers, roots and nodes
        self.n_nodes = 0
        self.inserts = 0
        self.adoptions = 0
        self.hit_blocks = 0
        self.evicted_blocks = 0
        self.evict_calls = 0
        self.evict_candidates = 0  # leaves examined, pinned included

    def _new_node(self, chunk, block, parent):
        self._seq += 1
        return _RadixNode(chunk, block, parent, self._seq)

    def _queue_leaf(self, node):
        if not node.queued:
            node.queued = True
            heapq.heappush(self._leaves,
                           (-len(node.order), node.order, node))

    def _chunks(self, tokens):
        if isinstance(tokens, BlockKeys):
            return list(tokens)
        toks = tuple(int(t) for t in tokens)
        bs = self.block_size
        return [toks[i:i + bs] for i in
                range(0, len(toks) - len(toks) % bs, bs)]

    def _walk(self, prompt, tokens):
        """Longest-prefix walk: (matched nodes, first divergent chunk
        index)."""
        node = self._roots.get(tuple(int(t) for t in prompt))
        path = []
        if node is None:
            return path
        for chunk in self._chunks(tokens):
            nxt = node.children.get(chunk)
            if nxt is None:
                break
            path.append(nxt)
            node = nxt
        return path

    def match(self, prompt, tokens) -> int:
        """Longest shared block-prefix depth (in BLOCKS) for this
        (prompt, decoded-token) pair. Pure probe — no refcounts."""
        return len(self._walk(prompt, tokens))

    def acquire(self, prompt, tokens, max_blocks=None):
        """Map the longest shared prefix read-only into a lane: one
        ``incref`` per matched block (the lane's refs — released
        with ``release``). Returns the block-id list, shallowest
        first."""
        path = self._walk(prompt, tokens)
        if max_blocks is not None:
            path = path[:max_blocks]
        blocks = [n.block for n in path]
        for b in blocks:
            self.pool.incref(b)
        self.hit_blocks += len(blocks)
        return blocks

    def release(self, blocks):
        """Drop a lane's refs on a shared chain (reverse order so a
        block freed at refcount 0 never outlives a deeper block that
        depends on it)."""
        for b in reversed(list(blocks)):
            self.pool.decref(b)

    def insert(self, prompt, tokens, blocks) -> int:
        """Adopt a finished lane's FULL-block chain: walk the chunks;
        where a node already exists the existing block wins (the
        lane's duplicate stays lane-owned — the caller releases it
        normally); where it doesn't, the tree adopts the lane's block
        with its OWN incref (the lane still releases its ref).
        Returns the number of newly adopted blocks."""
        key = tuple(int(t) for t in prompt)
        chunks = self._chunks(tokens)
        if not chunks:
            return 0
        blocks = list(blocks)
        if len(blocks) < len(chunks):
            raise BlockLifetimeError(
                f"radix insert of {len(chunks)} full chunks backed "
                f"by only {len(blocks)} blocks: a node without its "
                f"KV block would serve garbage to every later hit")
        root = self._roots.get(key)
        if root is None:
            root = self._roots[key] = self._new_node(key, None, None)
        node, tip, adopted = root, None, 0
        try:
            for chunk, block in zip(chunks, blocks):
                nxt = node.children.get(chunk)
                if nxt is None:
                    self.pool.incref(block)
                    nxt = tip = self._new_node(chunk, block, node)
                    node.children[chunk] = nxt
                    adopted += 1
                node = nxt
        finally:
            # of the nodes this call made only the last is a leaf; the
            # node it hangs from stopped being one (its entry is stale)
            if tip is not None:
                self._queue_leaf(tip)
            elif not root.children:
                del self._roots[key]
            self.n_nodes += adopted
            self.adoptions += adopted
        self.inserts += 1
        return adopted

    def evict(self, need: int) -> int:
        """Free >= ``need`` blocks by unpinning tree-only (refcount
        1) LEAF nodes, deepest first; among equal depths the oldest
        root's, and inside a root the one a depth-first descent
        through each node's newest child meets first
        (``_RadixNode.order``). Returns how many were freed; pinned
        subtrees (any lane ref anywhere below) are never touched: a
        pinned leaf is examined, left out for this call and put back."""
        self.evict_calls += 1
        leaves, pool = self._leaves, self.pool
        freed, pinned = 0, []
        while freed < need and leaves:
            entry = heapq.heappop(leaves)
            node = entry[2]
            if node.children:
                node.queued = False
                continue
            self.evict_candidates += 1
            if pool.refcount(node.block) != 1:
                pinned.append(entry)
                continue
            parent = node.parent
            del parent.children[node.chunk]
            pool.decref(node.block)
            freed += 1
            if not parent.children:
                if parent.parent is None:
                    del self._roots[parent.chunk]
                else:
                    self._queue_leaf(parent)
        for entry in pinned:
            heapq.heappush(leaves, entry)
        self.n_nodes -= freed
        self.evicted_blocks += freed
        return freed

    def tree_blocks(self) -> set:
        """Every block currently adopted by a node (the tree's own
        refs) — the property tests' overlap oracle."""
        out = set()
        for root in self._roots.values():
            stack = list(root.children.values())
            while stack:
                n = stack.pop()
                out.add(n.block)
                stack.extend(n.children.values())
        return out


__all__ = ["CacheConfig", "SamplingConfig", "DraftConfig",
           "ShardingConfig", "DecodeStepBundle",
           "DecoderOnlyStepBundle", "build_decoder_only_bundle",
           "DECODE_STEPS_VAR",
           "POOL_MARK", "LANE_AXIS",
           "tp_param_placements", "annotate_sharded_program",
           "place_sharded_bundle", "place_sharded_program",
           "ServingUnavailable", "BlockPoolExhausted",
           "BlockLifetimeError", "AdmissionInfeasible",
           "HostBlockPool", "RadixBlockTree", "BlockKeys",
           "PromptPrefixCache", "build_greedy_decode_program",
           "build_incremental_decode_program",
           "build_decode_step_program", "build_beam_decode_program",
           "cached_decoder_step",
           "step_logits", "init_token_buffer", "emit_token_step",
           "emit_lane_tokens", "lane_onehots", "tel_add",
           "build_serve_program", "heads_of", "ServeRow", "fed_name",
           "serve_row_of"]
