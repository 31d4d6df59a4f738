"""Device-side flight data: declarative decode-telemetry counters.

Reference counterpart: platform/profiler.h:81,166 — the reference's
profiler records per-op host/device events through host callbacks.
This framework fuses a whole scheduler cycle (admission + a
decode-burst While) into ONE dispatch (r10), so exactly the requests
the flight recorder retains — slow bursts, stalls, preemption storms —
have no host-visible interior: the host sees one opaque ``execute``
span per dispatch and nothing about what the device did inside it.

This module is the registry of **device-resident counters** the decode
engine (models/decode_engine.py) compiles into every serve/step/burst
program, following the r14 speculative-counter pattern:

* every counter is a ``[1]`` int64 PERSISTABLE that is
  read-modify-written in the program (``var = var + delta`` through
  ``layers.assign(..., output=var)``), so it rides the executor's
  ``state_in``/``state_out`` path and the K-step scan carry without
  tripping the PTA090 write-only-carry trap; int64 keeps it clear of
  the PTA020 weak-typing promotion trap. Checker PTA180
  (analysis/checkers.py) enforces both properties on every var
  carrying the ``@TEL`` name mark.
* counters are CUMULATIVE since ``init_slot_state``; the serving layer
  fetches them once per dispatch (they ride the packed row the
  dispatch already reads back) and DELTAS them into per-window stats and
  uniquely-labeled pull-provider metric samples
  (``paddle_tpu_devtel_*``). The device-side cost is a handful of
  scalar int64 adds per tick — measured unresolvable next to the
  decoder matmuls (PERF.md "Device-side telemetry") — and the
  host-side cost at ``FLAGS_observability=off`` is the delta
  arithmetic on a dict of ints.

The registry is DECLARATIVE: ``BUNDLE_COUNTERS`` is the single source
of truth for counter names, metric names and stats keys, shared by the
decode-engine builders (spec tables + state maps), the serving layer
(fetch/absorb/expose) and checker PTA180 — a new serve program
registers its counters by building its slot-state table through
``counter_specs()`` and never invents a parallel name scheme
(CLAUDE.md convention).

``HOST_COUNTERS`` is the paged scheduler's host-side supplement (block
/prompt-entry high-water marks, pause/preempt events): those are HOST
allocation decisions the device cannot observe, but they explain the
same slow bursts, so they share the ``device_telemetry`` stats surface
and the ``paddle_tpu_devtel_*`` metric namespace.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Tuple

__all__ = ["TEL_MARK", "DECODE_STEPS_VAR", "CounterSpec",
           "BUNDLE_COUNTERS", "HOST_COUNTERS", "counter_specs",
           "state_entries", "declare_decode_steps",
           "spec_k_counter_specs", "spec_k_state_entries",
           "spec_k_logical", "DeviceTelemetry", "EXIT_REASONS"]

# name mark on device-telemetry counter persistables: checker PTA180
# requires every var carrying it to be an int64, concretely-shaped,
# read-modify-write persistable (analysis/checkers.py)
TEL_MARK = "@TEL"

# fixed-name [1] int64 var holding the number of While iterations a
# WHOLE-LOOP decode program actually ran (the early-exit probe; the
# slot-pool bundles carry the same fact as their per-bundle
# ``tel_ticks`` counter — one tick-counter convention for every
# decode front). Kept at its historical name: tests and benches fetch
# it by name.
DECODE_STEPS_VAR = "@decode_steps"

# burst exit reasons, in reporting order (the serve programs bump
# exactly one per burst; see decode_engine._build_serve)
EXIT_REASONS = ("n_steps", "all_idle", "min_active")


@dataclass(frozen=True)
class CounterSpec:
    """One device-telemetry counter: its logical name (the key in
    ``bundle.state`` and ``stats()['device_telemetry']``), its metric
    sample name, and where it applies. Reference counterpart: the
    profiler event-name table (platform/profiler.h:166) — there
    host-recorded, here compiled into the program."""

    logical: str            # e.g. "tel_ticks"
    metric: str             # e.g. "paddle_tpu_devtel_ticks_total"
    stat: str               # key inside stats()["device_telemetry"]
    doc: str
    paged_only: bool = False
    chunked_only: bool = False  # only bundles built with chunked
    #                             prefill programs carry it


# the counters every DecodeStepBundle program set carries (device
# side). Order is the fetch/absorb order — append-only.
BUNDLE_COUNTERS: Tuple[CounterSpec, ...] = (
    CounterSpec(
        "tel_ticks", "paddle_tpu_devtel_ticks_total", "ticks",
        "device While iterations actually run (every step-body "
        "invocation: standalone step, serve bursts, scan steps)"),
    CounterSpec(
        "tel_occupancy", "paddle_tpu_devtel_occupancy_integral_total",
        "occupancy_integral",
        "sum over ticks of the live-lane count at tick start — the "
        "per-tick occupancy integral; divide by ticks for mean live "
        "lanes"),
    CounterSpec(
        "tel_exit_n_steps", "paddle_tpu_devtel_exit_n_steps_total",
        "exit_n_steps",
        "bursts that exited because n_steps ticks ran"),
    CounterSpec(
        "tel_exit_all_idle", "paddle_tpu_devtel_exit_all_idle_total",
        "exit_all_idle",
        "bursts that exited because every lane went idle"),
    CounterSpec(
        "tel_exit_min_active",
        "paddle_tpu_devtel_exit_min_active_total", "exit_min_active",
        "bursts that exited because live lanes dropped to min_active "
        "(retirement-granularity exit)"),
    CounterSpec(
        "tel_admit_miss", "paddle_tpu_devtel_admit_miss_total",
        "admitted_miss",
        "real (non-dustbin) lanes admitted through an encoder "
        "(miss/cold) admission body"),
    CounterSpec(
        "tel_admit_hit", "paddle_tpu_devtel_admit_hit_total",
        "admitted_hit",
        "real lanes admitted through the encoder-free prefix-HIT "
        "body", paged_only=True),
    CounterSpec(
        "tel_admit_radix", "paddle_tpu_devtel_admit_radix_total",
        "admitted_radix",
        "real lanes admitted through the radix-resume body (shared "
        "block prefix mapped read-only, divergent tail teacher-"
        "force prefilled)", paged_only=True),
    CounterSpec(
        "tel_cow_blocks", "paddle_tpu_devtel_cow_blocks_total",
        "cow_blocks",
        "KV blocks copied by the COW program (lane diverging off a "
        "shared radix/beam chain into a fresh exclusive block)",
        paged_only=True),
    CounterSpec(
        "tel_chunks", "paddle_tpu_devtel_prefill_chunks_total",
        "prefill_chunks",
        "prompt chunks ticked through the chunked-prefill phase "
        "programs (one bump per chunk body run)",
        paged_only=True, chunked_only=True),
    CounterSpec(
        "tel_prefill_occupancy",
        "paddle_tpu_devtel_prefill_occupancy_integral_total",
        "prefill_occupancy_integral",
        "sum over chunk dispatches of the live decode-lane count at "
        "dispatch — with tel_occupancy this is the prefill-vs-decode "
        "occupancy split (how many decode lanes kept ticking while a "
        "prompt chunked in)",
        paged_only=True, chunked_only=True),
)

# host-side supplement the PAGED scheduler reports through the same
# device_telemetry surface (allocation decisions the device cannot
# see). `stat` keys double as the PagedContinuousGenerationServer
# attribute/pool-stat they are read from.
HOST_COUNTERS: Tuple[CounterSpec, ...] = (
    CounterSpec("host_blocks_hwm", "paddle_tpu_devtel_blocks_hwm",
                "blocks_hwm",
                "high-water mark of KV blocks in use (window-scoped: "
                "stats(reset=True) re-bases it to the current "
                "residency)", paged_only=True),
    CounterSpec("host_prompt_entries_hwm",
                "paddle_tpu_devtel_prompt_entries_hwm",
                "prompt_entries_hwm",
                "high-water mark of prompt-pool entries in use",
                paged_only=True),
    CounterSpec("host_pause_events",
                "paddle_tpu_devtel_pause_events_total",
                "pause_events",
                "lanes parked for >= 1 cycle by pool pressure",
                paged_only=True),
    CounterSpec("host_preemptions",
                "paddle_tpu_devtel_preemptions_total", "preemptions",
                "recompute-preempted lanes (vLLM-style requeue)",
                paged_only=True),
)


def bundle_counters(paged: bool,
                    chunked: bool = True) -> Tuple[CounterSpec, ...]:
    """The device counters a bundle of the given layout carries.
    ``chunked`` defaults True on the ABSORB side (DeviceTelemetry
    filters by actual state presence) and is passed False by builders
    of non-chunked bundles so their spec tables stay exactly as
    before. Reference counterpart: none — the reference profiler has
    no per-layout event selection (platform/profiler.h:166)."""
    return tuple(c for c in BUNDLE_COUNTERS
                 if (paged or not c.paged_only)
                 and (chunked or not c.chunked_only))


def counter_specs(prefix: str, paged: bool,
                  chunked: bool = False) -> Dict[str, tuple]:
    """Slot-state spec entries (name -> ((1,), 'int64')) for the
    devtel counters of one bundle — merged into
    decode_engine._slot_state_specs so declaration, scope seeding and
    the PTA150 bundle sweep all see them like any other slot state.
    Names carry the @TEL mark so PTA180 can find them without a
    side-channel registry. Reference counterpart: none — reference
    counters are host-side aggregates (platform/profiler.cc)."""
    return {f"{prefix}{c.logical}{TEL_MARK}": ((1,), "int64")
            for c in bundle_counters(paged, chunked)}


def state_entries(prefix: str, paged: bool,
                  chunked: bool = False) -> Dict[str, str]:
    """logical -> var name map entries for ``DecodeStepBundle.state``
    (the serving layer resolves fetch names through this).
    Reference counterpart: none (see counter_specs)."""
    return {c.logical: f"{prefix}{c.logical}{TEL_MARK}"
            for c in bundle_counters(paged, chunked)}


_SPEC_K_STEM = "tel_spec_ticks_k"


def spec_k_logical(k: int) -> str:
    """Logical name of the per-k speculative tick counter: bumped once
    per step-body invocation of the serve variant built at draft
    length k, so windows over these counters show which rungs of the
    adaptive-k ladder actually ran on-device (the controller's
    decisions, observed from the device side). Reference counterpart:
    the profiler event-name table (platform/profiler.h:166)."""
    return f"{_SPEC_K_STEM}{int(k)}"


def spec_k_counter_specs(prefix: str,
                         k_options: Iterable[int]) -> Dict[str, tuple]:
    """Slot-state spec entries for the adaptive-speculation per-k tick
    counters, one per rung of the bundle's k ladder — same @TEL-marked
    [1] int64 RMW contract as counter_specs (checker PTA180 covers
    them identically). Reference counterpart: none — the reference
    fast-decode path has no draft-length ladder
    (operators/math/sequence2batch.h:47)."""
    return {f"{prefix}{spec_k_logical(k)}{TEL_MARK}": ((1,), "int64")
            for k in k_options}


def spec_k_state_entries(prefix: str,
                         k_options: Iterable[int]) -> Dict[str, str]:
    """logical -> var name entries for ``DecodeStepBundle.state``
    covering the per-k tick counters (see spec_k_counter_specs)."""
    return {spec_k_logical(k): f"{prefix}{spec_k_logical(k)}{TEL_MARK}"
            for k in k_options}


def declare_decode_steps(block):
    """Create the fixed-name whole-loop tick counter (the ONE copy of
    the create_var + fill_constant plumbing both whole-loop builders
    used to duplicate): a [1] int64 var named ``@decode_steps``,
    initialized to 0, fetchable by name. Returns the counter var —
    the builder increments it per While iteration, so fetching it
    after the loop reports how many iterations the early exit
    allowed. Reference counterpart: the step counter inside
    operators/controlflow/while_op.cc's execution loop (there an
    execution detail, here a fetchable observable)."""
    from .. import layers  # deferred: devtel is importable standalone

    return layers.fill_constant(
        [1], "int64", 0,
        out=block.create_var(name=DECODE_STEPS_VAR, shape=(1,),
                             dtype="int64", stop_gradient=True))


class DeviceTelemetry:
    """Host-side absorb/window/expose helper for one bundle's devtel
    counters (the serving layer's half of the contract). Mirrors the
    r14 speculative-counter discipline: the device counters are
    cumulative since ``init_slot_state``; ``absorb(values)`` returns
    the DELTAS of one dispatch; ``window()`` is the totals since the
    last ``rebase()`` — the ``stats(reset=True)`` window semantics.

    NOT thread-safe by itself: callers mutate it under their own
    scheduler lock (the servers' ``_cv``), exactly like the spec
    counters. Reference counterpart: none — the reference profiler
    has no device-resident counters to delta (platform/profiler.cc
    aggregates host events)."""

    def __init__(self, bundle):
        paged = getattr(getattr(bundle, "cache", None), "layout",
                        "dense") == "paged"
        state = getattr(bundle, "state", {}) or {}
        # ordered (logical, var-name) pairs present on this bundle —
        # duck-typed so hand-built test bundles without devtel state
        # degrade to an empty (inactive) telemetry view
        self._counters = [(c.logical, state[c.logical])
                          for c in bundle_counters(paged)
                          if c.logical in state]
        self._metric_by_logical = {
            c.logical: c.metric for c in BUNDLE_COUNTERS}
        # adaptive-speculation per-k tick counters are parametrized by
        # the bundle's k ladder (spec_k_counter_specs), so they join
        # dynamically: sorted by k for a stable fetch order
        spec_k = sorted(
            (logical for logical in state
             if logical.startswith(_SPEC_K_STEM)),
            key=lambda s: int(s[len(_SPEC_K_STEM):]))
        for logical in spec_k:
            self._counters.append((logical, state[logical]))
            self._metric_by_logical[logical] = \
                f"paddle_tpu_devtel_spec_ticks_k" \
                f"{logical[len(_SPEC_K_STEM):]}_total"
        self.totals: Dict[str, int] = {
            logical: 0 for logical, _ in self._counters}
        self._base: Dict[str, int] = dict(self.totals)

    @property
    def active(self) -> bool:
        return bool(self._counters)

    @property
    def fetch_names(self) -> List[str]:
        """Var names a dispatch hands back, in the order ``absorb``
        expects them: their place in the bundle's packed serve row
        (models/decode_engine.serve_row_of)."""
        return [name for _, name in self._counters]

    def absorb(self, values: Iterable) -> Dict[str, int]:
        """Update totals from one dispatch's fetched counter values
        (same order as ``fetch_names``); returns this dispatch's
        deltas keyed by logical name."""
        import numpy as np

        deltas = {}
        for (logical, _name), v in zip(self._counters, values):
            val = int(np.asarray(v).reshape(-1)[0])
            deltas[logical] = val - self.totals[logical]
            self.totals[logical] = val
        return deltas

    def window(self) -> Dict[str, int]:
        """Totals since the last rebase() (the stats() window)."""
        return {logical: self.totals[logical] - self._base[logical]
                for logical, _ in self._counters}

    def rebase(self):
        """stats(reset=True): subsequent window() calls cover only
        dispatches after this point."""
        self._base = dict(self.totals)

    @staticmethod
    def exit_reason(deltas: Dict[str, int]) -> Optional[str]:
        """Which exit fired in a dispatch's deltas ('n_steps' /
        'all_idle' / 'min_active'), None when no burst ran."""
        for reason in EXIT_REASONS:
            if deltas.get(f"tel_exit_{reason}", 0) > 0:
                return reason
        return None

    def stats_dict(self, window: Dict[str, int]) -> dict:
        """The ``stats()['device_telemetry']`` device half from a
        window() snapshot: raw counters under their stat keys plus
        the derived mean live-lane occupancy."""
        by_logical = {c.logical: c.stat for c in BUNDLE_COUNTERS}
        out = {by_logical.get(logical, logical[len("tel_"):]):
               window[logical] for logical, _ in self._counters}
        ticks = window.get("tel_ticks", 0)
        occ = window.get("tel_occupancy", 0)
        out["mean_live_lanes"] = (round(occ / ticks, 4)
                                  if ticks else None)
        return out

    def metric_samples(self, labels: Dict[str, str]) -> List[tuple]:
        """Cumulative-totals pull-provider samples (Prometheus
        convention: _total series never reset; windows are the
        scraper's delta)."""
        return [(self._metric_by_logical[logical], labels,
                 self.totals[logical])
                for logical, _ in self._counters]
