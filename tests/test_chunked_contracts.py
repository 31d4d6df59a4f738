"""Chunked-prefill + radix-preemption CONTRACTS (ISSUE 17), fast
lane: everything here is host-side logic, static analysis, or a
white-box scheduler probe over one small L=1 bundle — the end-to-end
serve waves (token parity, latency, disaggregation) live in
tests/test_chunked_prefill.py and tests/test_disagg_serving.py (slow
lane).

* ``CacheConfig`` chunk validation: ``chunk_tokens == 1`` is rejected
  (single-query attention drifts ~1e-7 off the monolithic encoder —
  the bit-exact parity contract), chunking needs the paged layout,
  and the cache token carries ``("chunk", C)`` so a chunked and an
  unchunked build of one geometry never dedupe;
* ``PromptPrefixCache.invalidate`` typestate (the abandoned
  part-written-prefill path): pinned entries refuse, invalidated
  prompts stop matching (even as partials) and the slot is reusable;
* radix-aware preemption (white-box): under hard pool exhaustion the
  scheduler bulk-evicts refcount-1 radix leaves BEFORE preempting,
  and when it must preempt it picks the lane with the DEEPEST shared
  prefix (least exclusive work lost), youngest t_admit tiebreak;
* analysis contracts: the ``chunk_cursor`` ownership source is
  registered and the chunk phase programs discharge PTA180 (telemetry
  contract) and PTA190/191/192 (pool ownership) with zero errors.
"""
import concurrent.futures
import types

import pytest

import paddle_tpu as fluid
from paddle_tpu import unique_name
from paddle_tpu.analysis import ERROR, absint, run_checks
from paddle_tpu.core.scope import Scope
from paddle_tpu.inference.serving import PagedContinuousGenerationServer
from paddle_tpu.models import transformer as T
from paddle_tpu.models.decode_engine import (BlockLifetimeError,
                                             BlockPoolExhausted,
                                             CacheConfig,
                                             PromptPrefixCache)

V, D, H, L, S, MAXT = 16, 16, 2, 1, 8, 16
BS, NB, E, C = 4, 10, 2, 4
N_SLOTS = 4
NPH = 2 * L + 2


@pytest.fixture(scope="module")
def built():
    """One SMALL untrained chunked bundle: the contracts below probe
    scheduler/prover structure, never token quality, so the cheapest
    geometry that has a radix tier and chunk phases wins."""
    fluid.seed(0)
    scope = Scope()
    with unique_name.guard():
        _, t_st, _ = T.build_program(
            seq_len=S, d_model=D, n_heads=H, n_layers=L, d_inner=32,
            vocab=V, with_optimizer=False, dropout_rate=0.0)
    with unique_name.guard():
        bundle = T.build_decode_step_program(
            n_slots=N_SLOTS, admit_buckets=[1], state_prefix="@cc/",
            seq_len=S, max_out_len=MAXT, d_model=D, n_heads=H,
            n_layers=L, d_inner=32, vocab=V, start_id=2, end_id=1,
            cache=CacheConfig(layout="paged", block_size=BS,
                              n_blocks=NB, n_prompt_entries=E,
                              chunk_tokens=C))
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(t_st, scope=scope)
    return {"scope": scope, "exe": exe, "bundle": bundle}


class TestCacheConfigChunking:
    def _cfg(self, **kw):
        kw.setdefault("layout", "paged")
        kw.setdefault("block_size", BS)
        kw.setdefault("n_blocks", NB)
        kw.setdefault("n_prompt_entries", E)
        return CacheConfig(**kw)

    def test_single_token_chunks_rejected(self):
        # C == 1 lowers attention to a single-query contraction whose
        # accumulation order drifts off the monolithic encoder — the
        # bit-exact parity contract rejects it at validation
        with pytest.raises(ValueError, match="chunk_tokens == 1"):
            self._cfg(chunk_tokens=1).validate(MAXT)

    def test_negative_chunks_rejected(self):
        with pytest.raises(ValueError, match=">= 0"):
            self._cfg(chunk_tokens=-2).validate(MAXT)

    def test_chunking_needs_paged_layout(self):
        with pytest.raises(ValueError, match="paged layout"):
            CacheConfig(layout="dense", chunk_tokens=4).validate(MAXT)

    def test_token_carries_chunk_suffix(self):
        plain = self._cfg().token()
        chunked = self._cfg(chunk_tokens=C).token()
        # append-only: historical unchunked tokens stay byte-identical
        assert chunked[:len(plain)] == plain
        assert chunked[len(plain):] == ("chunk", C)

    def test_n_chunks_ceil(self):
        assert self._cfg(chunk_tokens=4).n_chunks(10) == 3
        assert self._cfg(chunk_tokens=5).n_chunks(10) == 2
        assert self._cfg(chunk_tokens=4).n_chunks(12) == 3
        assert self._cfg().n_chunks(10) == 0


class TestPromptEntryInvalidate:
    def test_invalidate_pinned_entry_raises(self):
        pc = PromptPrefixCache(2, C)
        e = pc.acquire_fresh((1, 2, 3, 4))
        with pytest.raises(BlockLifetimeError, match="invalidate"):
            pc.invalidate(e)

    def test_invalidate_forgets_prompt_and_recycles_slot(self):
        pc = PromptPrefixCache(2, C)
        prompt = (1, 2, 3, 4, 5)
        e = pc.acquire_fresh(prompt)
        pc.release(e)
        assert pc.lookup(prompt) == ("hit", e)
        pc.invalidate(e)
        # the abandoned part-written entry must never be looked up
        # again — not even as a partial (its head count is gone too)
        assert pc.lookup(prompt) == ("miss", None)
        assert pc.lookup(prompt[:C] + (9,)) == ("miss", None)
        assert pc.acquire_fresh((7, 7, 7, 7)) == e
        # idempotent on an already-forgotten entry
        pc.release(e)
        pc.invalidate(e)
        pc.invalidate(e)


class TestRadixAwarePreemption:
    """White-box: drive _plan_burst_locked directly on an idle
    (start=False) server with hand-built lane state and a drained
    block pool — the only way to pin the VICTIM CHOICE without
    racing a live scheduler into a specific exhaustion interleaving."""

    def _req(self, t_admit):
        return types.SimpleNamespace(
            t_admit=t_admit, t_first=None,
            reply=concurrent.futures.Future(), trace=None)

    def _idle(self, built):
        return PagedContinuousGenerationServer(
            built["bundle"], executor=built["exe"],
            scope=built["scope"], steps_per_tick=4, start=False)

    def _drain_pool(self, srv):
        held = []
        while True:
            b = srv._blocks.alloc()
            if b is None:
                return held
            held.append(b)

    def test_deepest_shared_lane_preempted_first(self, built):
        srv = self._idle(built)
        try:
            held = self._drain_pool(srv)
            freed = []
            srv._free_lane_locked = lambda slot: freed.append(slot)
            old_plain = self._req(t_admit=5.0)   # older, depth 0
            young_shared = self._req(t_admit=9.0)
            srv._lanes[0] = old_plain
            srv._lanes[1] = young_shared
            # lane 1 resumes over a 2-block shared radix prefix: its
            # re-admission replays from 2*BS, so preempting it loses
            # the LEAST exclusive work despite the younger t_admit
            srv._lane_shared[1] = held[:2]
            srv._lane_step[0] = 0
            srv._lane_step[1] = 2 * BS
            failures = []
            with srv._cv:
                n, m, run = srv._plan_burst_locked([], False, failures)
            assert run and n >= 0
            # rung 2 fires on the shared-prefix lane first ...
            assert freed[0] == 1
            assert srv._preemptions == 1
            assert srv._lanes[1] is None
            assert list(srv._queue) == [young_shared]
            assert young_shared.t_admit is None   # requeued cold
            # ... and the lone survivor, still unable to grow, gets
            # the NAMED retryable failure instead of a preempt loop
            assert freed == [1, 0]
            assert [r for r, _ in failures] == [old_plain]
            assert isinstance(failures[0][1], BlockPoolExhausted)
        finally:
            srv.close(1.0)

    def test_admit_age_breaks_equal_depth_ties(self, built):
        srv = self._idle(built)
        try:
            self._drain_pool(srv)
            freed = []
            srv._free_lane_locked = lambda slot: freed.append(slot)
            older = self._req(t_admit=1.0)
            younger = self._req(t_admit=2.0)
            srv._lanes[0] = younger
            srv._lanes[1] = older
            failures = []
            with srv._cv:
                srv._plan_burst_locked([], False, failures)
            # equal (zero) shared depth: the r13 discipline — the
            # YOUNGEST admission loses the least work
            assert freed[0] == 0
            assert list(srv._queue) == [younger]
        finally:
            srv.close(1.0)

    def test_bulk_leaf_evict_preferred_over_preemption(self, built):
        srv = self._idle(built)
        try:
            held = self._drain_pool(srv)
            spare = [held.pop(), held.pop()]
            evict_calls = []

            def fake_evict(n):
                # per-alloc growth asks for 1 leaf (none evictable);
                # rung 1's BULK ask finds the two reclaimable leaves
                evict_calls.append(n)
                if n < 2 or not spare:
                    return 0
                srv._blocks.free([spare.pop(), spare.pop()])
                return 2

            srv._radix.evict = fake_evict
            freed = []
            srv._free_lane_locked = lambda slot: freed.append(slot)
            srv._lanes[0] = self._req(1.0)
            srv._lanes[1] = self._req(2.0)
            srv._lane_blocks[0] = [held.pop()]
            srv._lane_blocks[1] = [held.pop()]
            srv._lane_step[0] = BS     # both at a block boundary
            srv._lane_step[1] = BS
            failures = []
            with srv._cv:
                n, m, run = srv._plan_burst_locked([], False, failures)
            # cache before work: both lanes grow into the evicted
            # blocks, nobody is preempted, the burst proceeds
            assert evict_calls == [1, 1, 2]
            assert freed == [] and not failures
            assert srv._preemptions == 0
            assert run and n == 4
            assert srv._lanes[0] is not None
            assert srv._lanes[1] is not None
        finally:
            srv.close(1.0)


class TestPromptEntryWriters:
    """Every writer of the prompt table leaves an entry as the
    projection's rows, ``[S, H*Dh]`` as stored: the monolithic miss
    admission, the chunked prefill's final install, and the
    disaggregated handoff that copies an entry from the prefill
    worker's scope. The projection is the dense layout's admission of
    the same prompt (its per-lane ``[H, S, Dh]``, heads folded back)."""

    @pytest.fixture(scope="class")
    def projection(self, built):
        import numpy as np

        scope, exe = built["scope"], built["exe"]
        with unique_name.guard():
            dense = T.build_decode_step_program(
                n_slots=N_SLOTS, admit_buckets=[1],
                state_prefix="@ccd/", seq_len=S, max_out_len=MAXT,
                d_model=D, n_heads=H, n_layers=L, d_inner=32, vocab=V,
                start_id=2, end_id=1)
        dense.init_slot_state(scope)
        src = np.random.RandomState(5).randint(
            3, V, (1, S)).astype(np.int64)
        exe.run(dense.serves[1],
                feed={"src_ids": src, "slots": np.array([0], np.int64),
                      "n_steps": np.array([0], np.int64),
                      "min_active": np.array([0], np.int64)},
                fetch_list=[dense.state["active"]], scope=scope)
        rows = {f"cross_{kind}{li}": np.asarray(scope._get(
            f"@ccd/cross_{kind}{li}"))[0].transpose(1, 0, 2).reshape(
                S, D) for kind in "kv" for li in range(L)}
        return src, rows

    def _entry(self, scope, entry):
        import numpy as np

        from paddle_tpu.models.decode_engine import POOL_MARK

        return {f"cross_{kind}{li}": np.asarray(scope._get(
            f"@cc/cross_{kind}{li}{POOL_MARK}"))[entry]
            for kind in "kv" for li in range(L)}

    @pytest.mark.parametrize("writer", ["miss_admission",
                                        "chunked_install", "handoff"])
    def test_entry_reads_back_equal_to_the_projection(
            self, built, projection, writer):
        import numpy as np

        from paddle_tpu.inference.serving import \
            DisaggregatedPrefillWorker

        b, exe, scope = built["bundle"], built["exe"], built["scope"]
        src, want = projection
        idle = {"n_steps": np.array([0], np.int64),
                "min_active": np.array([0], np.int64),
                **b.idle_table_feed()}
        b.init_slot_state(scope)
        if writer == "miss_admission":
            exe.run(b.serves[("miss", 1)],
                    feed={"src_ids": src,
                          "slots": np.array([0], np.int64),
                          "prompt_slots": np.array([1], np.int64),
                          **idle},
                    fetch_list=[b.state["active"]], scope=scope)
            got = self._entry(scope, 1)
        elif writer == "chunked_install":
            for key in b.chunk_phase_keys:
                for pos in range(0, S, C):
                    feed = {"chunk_entry": np.array([0], np.int64),
                            "chunk_pos": np.array([pos], np.int64),
                            **idle}
                    if key[1] == 0:
                        feed["chunk_toks"] = src[:, pos:pos + C]
                    exe.run(b.serves[key], feed=feed,
                            fetch_list=[b.state["active"]],
                            scope=scope)
            got = self._entry(scope, 0)
        else:
            worker = DisaggregatedPrefillWorker(
                b, executor=exe, scope=Scope(), params_from=scope)
            try:
                with PagedContinuousGenerationServer(
                        b, executor=exe, scope=scope,
                        steps_per_tick=4,
                        prefill_worker=worker) as srv:
                    srv.submit(src[0]).result(timeout=120.0)
                    assert srv.pool_stats()["disagg_handoffs"] == 1
                    got = self._entry(
                        scope, srv._prefix._by_prompt[tuple(src[0])])
            finally:
                worker.close()
        for name, rows in want.items():
            assert got[name].shape == (S, D)
            np.testing.assert_allclose(got[name], rows, rtol=1e-5,
                                       atol=1e-6, err_msg=name)
        b.init_slot_state(scope)


class TestAnalysisContracts:
    def test_chunk_cursor_source_registered(self):
        srcs = absint.pool_index_sources()
        assert "chunk_cursor" in srcs
        assert srcs["chunk_cursor"].typestate == absint.TS_EXCLUSIVE
        assert srcs["chunk_cursor"].assumption == \
            "PromptPrefixCache.fresh-exclusive"

    @pytest.mark.parametrize("pick", [0, 1, 2, NPH - 1],
                             ids=["embed", "kv", "attn", "cross"])
    def test_chunk_phase_programs_discharge_provers(self, built,
                                                    pick):
        """The phase programs' staging/cross pool writes must chain
        to marked sources (chunk_cursor/host_indices) and keep the
        telemetry contract — zero error diagnostics from the
        ownership prover (PTA190/191/192) and PTA180."""
        prog = built["bundle"].serves[("chunked", pick)]
        bad = [d for d in run_checks(prog)
               if d.code in ("PTA180", "PTA190", "PTA191", "PTA192")
               and d.severity == ERROR]
        assert not bad, [(d.code, d.message) for d in bad]
