"""Chunked prefill (ISSUE 17 tentpole, Sarathi-Serve-style): the
END-TO-END serve waves on the CPU backend — slow lane; the cheap
contracts (CacheConfig validation, invalidate typestate, preemption
white-box, analysis provers) live in tests/test_chunked_contracts.py
(fast lane):

* the DEVICE parity contract: walking one prompt through the
  ``("chunked", p)`` phase programs (phase-major, every chunk cursor
  per phase, ragged tail zero-padded) writes cross-KV rows
  BIT-IDENTICAL to the monolithic miss admission's encoder — which is
  what lets a chunk-prefilled entry finish as an ordinary prefix HIT;
* the SERVE parity contract: a chunked server and a monolithic server
  produce token-identical results over a mixed miss/hit wave, with
  the chunk-tick arithmetic exact (jobs x n_chunks x phases) and the
  devtel ``tel_chunks`` counter agreeing with the host count;
* the LATENCY contract the chunking exists for: short requests
  admitted while a long cold prompt chunks in complete BEFORE it —
  decode ticks are never blocked behind a whole-prompt prefill;
* zero steady-state compiles: a second traffic wave (including a
  fresh cold prompt -> new chunk job) compiles nothing;
* cross-request radix reuse WITHOUT a session (satellite): an
  identical sessionless resubmit admits through the plain-radix tier
  and re-decodes token-identically;
* disaggregated prefill (unsharded half; the sharded phase-plan half
  lives in test_disagg_serving.py): a DisaggregatedPrefillWorker on
  its OWN scope feeds the decode server through the handoff inbox
  token-identically, and the constructor contracts hold.
"""
import time
import types

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import unique_name
from paddle_tpu.core.scope import Scope
from paddle_tpu.inference.serving import (DisaggregatedPrefillWorker,
                                          PagedContinuousGenerationServer)
from paddle_tpu.models import transformer as T
from paddle_tpu.models.decode_engine import (POOL_MARK, CacheConfig,
                                             fed_name)

V, D, H, L, S, MAXT = 16, 32, 2, 2, 10, 32
BS, NB, E, C = 8, 24, 3, 4
N_SLOTS = 4
NC = (S + C - 1) // C      # chunk cursors per phase (ragged tail)
NPH = 2 * L + 2            # phases: embed, (kv + attn) per layer, cross
PREFIX = "@cp/"


@pytest.fixture(scope="module")
def built():
    """One untrained transformer + chunked paged bundle for every
    serve test (greedy decode is deterministic either way; training
    buys nothing for parity/scheduling contracts)."""
    fluid.seed(0)
    scope = Scope()
    with unique_name.guard():
        _, t_st, _ = T.build_program(
            seq_len=S, d_model=D, n_heads=H, n_layers=L, d_inner=64,
            vocab=V, with_optimizer=False, dropout_rate=0.0)
    with unique_name.guard():
        bundle = T.build_decode_step_program(
            n_slots=N_SLOTS, admit_buckets=[1, 4], state_prefix=PREFIX,
            seq_len=S, max_out_len=MAXT, d_model=D, n_heads=H,
            n_layers=L, d_inner=64, vocab=V, start_id=2, end_id=1,
            cache=CacheConfig(layout="paged", block_size=BS,
                              n_blocks=NB, n_prompt_entries=E,
                              chunk_tokens=C))
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(t_st, scope=scope)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(3, V, (1, S)).astype(np.int64)
               for _ in range(4)]
    return {"scope": scope, "exe": exe, "bundle": bundle,
            "prompts": prompts, "order": [0, 1, 0, 2, 1, 3, 2, 0]}


def _server(built, **kw):
    kw.setdefault("steps_per_tick", 4)
    return PagedContinuousGenerationServer(
        built["bundle"], executor=built["exe"], scope=built["scope"],
        **kw)


def _wave(srv, built):
    futs = [srv.submit(built["prompts"][i]) for i in built["order"]]
    return [np.asarray(f.result(120.0)) for f in futs]


@pytest.fixture(scope="module")
def mono_ref(built):
    """Monolithic-prefill reference tokens over the standard wave."""
    with _server(built, chunked_prefill=False) as srv:
        toks = _wave(srv, built)
        stats = srv.pool_stats()
    assert stats["chunk_jobs"] == 0 and stats["chunk_ticks"] == 0
    return toks


class TestDeviceChunkParity:
    def test_phase_keys_in_order(self, built):
        b = built["bundle"]
        assert b.chunk_phase_keys == [("chunked", p)
                                      for p in range(NPH)]
        assert b.cache.n_chunks(S) == NC

    def test_phase_walk_bit_exact_vs_monolithic_encoder(self, built):
        """Entry 0: monolithic miss admission. Entry 1: the same
        prompt streamed through every ('chunked', p) phase at every
        chunk cursor (phase-major, ragged last chunk zero-padded).
        The cross-KV rows must match BIT-EXACTLY — that is what lets
        a chunk-prefilled entry later admit as an ordinary HIT."""
        b, exe, scope = built["bundle"], built["exe"], built["scope"]
        b.init_slot_state(scope)
        src = np.random.RandomState(3).randint(
            3, V, (1, S)).astype(np.int64)
        tab = np.zeros((N_SLOTS + 1, MAXT // BS), np.int32)
        tab[0] = np.arange(MAXT // BS)
        pref = np.full((N_SLOTS + 1,), E, np.int32)
        pref[0] = 0
        # the scheduler's tables ride every serve dispatch as feeds
        idle = {"n_steps": np.array([0], np.int64),
                "min_active": np.array([0], np.int64),
                **b.idle_table_feed(),
                fed_name("block_tab"): tab, fed_name("prompt_ref"): pref}
        exe.run(b.serves[("miss", 1)],
                feed={"src_ids": src,
                      "slots": np.array([0], np.int64),
                      "prompt_slots": np.array([0], np.int64), **idle},
                fetch_list=[b.state["active"]], scope=scope)
        names = [f"{PREFIX}cross_{kind}{li}{POOL_MARK}"
                 for kind in ("k", "v") for li in range(L)]
        want = {n: np.asarray(scope._get(n))[0].copy() for n in names}
        for key in b.chunk_phase_keys:
            for ci in range(NC):
                feed = {"chunk_entry": np.array([1], np.int64),
                        "chunk_pos": np.array([ci * C], np.int64),
                        **idle}
                if key[1] == 0:
                    pad = np.zeros((1, C), np.int64)
                    seg = src[0, ci * C: ci * C + C]
                    pad[0, :len(seg)] = seg
                    feed["chunk_toks"] = pad
                exe.run(b.serves[key], feed=feed,
                        fetch_list=[b.state["active"]], scope=scope)
        for n in names:
            got = np.asarray(scope._get(n))[1]
            np.testing.assert_array_equal(got, want[n], err_msg=n)


class TestServeParity:
    def test_chunked_wave_token_identical(self, built, mono_ref):
        with _server(built) as srv:
            toks = _wave(srv, built)
            stats = srv.pool_stats()
            tel = srv.stats().get("device_telemetry") or {}
        for got, want in zip(toks, mono_ref):
            assert np.array_equal(got, want)
        # 4 distinct prompts with E=3 entries: >= 4 chunk jobs (a
        # repeat of an LRU-evicted prompt re-chunks, timing-
        # dependent); each job walks every phase over every chunk
        # cursor exactly once
        assert stats["chunked_prefill"] is True
        assert stats["chunk_jobs"] >= 4
        assert stats["chunk_ticks"] == stats["chunk_jobs"] * NC * NPH
        # device counter agrees with the host count (PTA180 contract:
        # the counters live in slot state and ride the dispatch RMW)
        if "prefill_chunks" in tel:
            assert tel["prefill_chunks"] == stats["chunk_ticks"]

    def test_shorts_complete_while_long_prompt_chunks_in(self, built):
        """The latency contract chunking buys: a cold prompt's
        NC x NPH chunk dispatches interleave 1:1 with decode bursts,
        so warm (prefix-hit) requests admitted alongside it finish
        first instead of waiting out the whole prefill."""
        done = {}
        with _server(built) as srv:
            warm = built["prompts"][0]
            srv.submit(warm).result(120.0)      # entry now cached
            f_cold = srv.submit(built["prompts"][3])
            f_hits = [srv.submit(warm) for _ in range(2)]
            f_cold.add_done_callback(
                lambda f: done.setdefault("cold", time.monotonic()))
            for i, f in enumerate(f_hits):
                f.add_done_callback(
                    lambda f, i=i: done.setdefault(i, time.monotonic()))
            f_cold.result(120.0)
            for f in f_hits:
                f.result(120.0)
            stats = srv.pool_stats()
        assert stats["chunk_jobs"] == 2        # warm once, cold once
        assert max(done[i] for i in range(2)) < done["cold"]

    def test_second_wave_compiles_nothing(self, built):
        exe = built["exe"]
        with _server(built) as srv:
            first = _wave(srv, built)
            warmed = exe.compile_count
            second = _wave(srv, built)
            assert exe.compile_count == warmed
        # the repeat wave re-admits through hit/radix tiers — same
        # deterministic tokens
        for got, want in zip(second, first):
            assert np.array_equal(got, want)


class TestPlainRadixReuse:
    def test_sessionless_resubmit_rides_radix_tier(self, built):
        p = np.random.RandomState(11).randint(
            3, V, (1, S)).astype(np.int64)
        with _server(built) as srv:
            t1 = np.asarray(srv.submit(p).result(120.0))
            s1 = srv.pool_stats()
            t2 = np.asarray(srv.submit(p).result(120.0))
            s2 = srv.pool_stats()
        assert s1["plain_radix_admissions"] == 0
        assert s2["plain_radix_admissions"] >= 1
        assert s2["radix_hit_blocks"] > s1["radix_hit_blocks"]
        assert np.array_equal(t1, t2)


class TestDisaggUnsharded:
    """The scope-split half of disaggregation without mesh plans:
    worker prefills on its OWN scope, handoff rows land in the decode
    scope token-exactly. The sharded phase-plan half (different
    ShardingPlans, disjoint device slices) is test_disagg_serving.py
    (slow lane)."""

    def test_worker_fed_server_token_identical(self, built, mono_ref):
        pre_scope = Scope()
        worker = DisaggregatedPrefillWorker(
            built["bundle"], executor=built["exe"], scope=pre_scope,
            params_from=built["scope"])
        try:
            with _server(built, prefill_worker=worker) as srv:
                toks = _wave(srv, built)
                stats = srv.pool_stats()
        finally:
            worker.close()
        for got, want in zip(toks, mono_ref):
            assert np.array_equal(got, want)
        assert stats["disaggregated"] is True
        assert stats["chunk_jobs"] >= 4
        assert stats["disagg_handoffs"] == stats["chunk_jobs"]
        assert stats["disagg_outstanding"] == 0
        ws = worker.stats()
        assert ws["jobs_done"] == stats["chunk_jobs"]
        assert ws["jobs_failed"] == 0
        assert ws["chunk_ticks"] == ws["jobs_done"] * NC * NPH

    def test_worker_contradicts_unchunked_scheduling(self, built):
        fake = types.SimpleNamespace(bundle=built["bundle"])
        with pytest.raises(ValueError, match="implies chunked"):
            _server(built, prefill_worker=fake,
                    chunked_prefill=False)

    def test_worker_must_serve_same_bundle(self, built):
        fake = types.SimpleNamespace(bundle=object())
        with pytest.raises(ValueError, match="SAME bundle"):
            _server(built, prefill_worker=fake)

    def test_worker_needs_chunked_bundle(self, built):
        with unique_name.guard():
            plain = T.build_decode_step_program(
                n_slots=2, admit_buckets=[1], state_prefix="@cpu/",
                seq_len=S, max_out_len=MAXT, d_model=D, n_heads=H,
                n_layers=1, d_inner=64, vocab=V, start_id=2, end_id=1,
                cache=CacheConfig(layout="paged", block_size=BS,
                                  n_blocks=8, n_prompt_entries=2))
        with pytest.raises(ValueError, match="chunk"):
            DisaggregatedPrefillWorker(plain, executor=built["exe"],
                                       scope=Scope(), start=False)
