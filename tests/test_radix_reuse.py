"""Radix block-prefix reuse (ISSUE 16): shared decoded-token self-KV
chains, COW branching, multi-turn chat sessions.

The invariants this module pins:

* a session's first turn is byte-identical to the cold decode, and a
  RESUBMIT admits through the radix tier — shared blocks mapped
  read-only (``radix_hit_blocks`` counts them), replayed prefix
  byte-identical, ``extend_tokens`` echoed in place — so resumed
  decoding is token-exact vs the history it resumes from;
* ``radix_reuse=False`` keeps the session API but re-prefills full
  history into fresh blocks: SAME tokens (the baseline bench.py
  multiturn measures against), ZERO radix hits;
* best-of-n fan-out shares the prompt entry; greedy branches are
  identical rows;
* the pool never leaks: after close_session the only retained blocks
  are the radix tree's, and evicting the tree drains the pool to
  fully free;
* PagedBeamDecoder — beam branching as COW block branching — is
  token-exact AND score-exact vs the whole-loop
  ``build_beam_decode_program`` oracle, including decodes that cross
  multiple block boundaries, and returns every block to the pool;
* the radix tier composes with tp=2 sharded bundles token-exactly
  (block tables are host-owned and replicated — the tree is oblivious
  to the KV layout a ShardingConfig picks).
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.inference import (PagedBeamDecoder,
                                  PagedContinuousGenerationServer,
                                  apply_eos_sentinel)
from paddle_tpu.models.decode_engine import CacheConfig, ShardingConfig

V, D, H, L, S, MAXT = 16, 32, 2, 1, 10, 32
BS, NB, E = 8, 24, 3
END_ID = 1
N_SLOTS = 4
EXT = [5, 6, 7]


def _mixed_len_prompts(rng, n):
    src = rng.randint(3, V, (n, S)).astype(np.int64)
    for r in range(n):
        p = rng.randint(1, S + 1)
        if p < S:
            src[r, p:] = END_ID
    return src


@pytest.fixture(scope="module")
def trained():
    """Train the tiny terminator-copy transformer once; build the
    paged serving bundle and pick a session prompt BY DECODE (the
    test_paged_decode discipline): its generation must cross a block
    boundary yet leave buffer room for two extension turns."""
    from paddle_tpu import unique_name
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.models import transformer as T

    fluid.seed(0)
    scope = Scope()
    with unique_name.guard():
        main, startup, loss = T.build_program(
            seq_len=S, d_model=D, n_heads=H, n_layers=L, d_inner=64,
            vocab=V, with_optimizer=False, dropout_rate=0.0)
        with fluid.program_guard(main, startup):
            fluid.optimizer.Adam(learning_rate=0.02).minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(7)
    for _ in range(200):
        src = _mixed_len_prompts(rng, 8)
        tgt_in = np.concatenate(
            [np.full((8, 1), 2, np.int64), src[:, :-1]], 1)
        exe.run(main, feed={"src_ids": src, "tgt_ids": tgt_in,
                            "label": src}, fetch_list=[loss],
                scope=scope)
    kwargs = dict(seq_len=S, max_out_len=MAXT, d_model=D, n_heads=H,
                  n_layers=L, d_inner=64, vocab=V, start_id=2,
                  end_id=END_ID)
    with unique_name.guard():
        paged = T.build_decode_step_program(
            n_slots=N_SLOTS, state_prefix="@rx/",
            cache=CacheConfig(layout="paged", block_size=BS,
                              n_blocks=NB, n_prompt_entries=E),
            **kwargs)
    # candidates shaped like the training prompts (content, then the
    # terminator to the end): the model was taught to copy up to the
    # first terminator and stop, so only the longest, 8 or 9 tokens,
    # can decode the 10 tokens and more that cross a block boundary
    # (a full-length prompt, which training never ended, stops only
    # by chance)
    cands = rng.randint(3, V, (24, S)).astype(np.int64)
    cands[0::2, S - 1:] = END_ID
    cands[1::2, S - 2:] = END_ID
    p1 = cold = None
    with PagedContinuousGenerationServer(paged, executor=exe,
                                         scope=scope) as srv:
        for c in cands:
            out = srv.submit(c).result(timeout=120)
            n = int((out != -1).sum())
            if BS + 2 <= n <= MAXT - 2 * (len(EXT) + 1) \
                    and out[n - 1] == END_ID:
                p1, cold = c, np.asarray(out)
                break
    assert p1 is not None, (
        f"none of {len(cands)} candidates generated 10..24 tokens")
    return {"exe": exe, "scope": scope, "paged": paged,
            "kwargs": kwargs, "T": T, "unique_name": unique_name,
            "p1": p1, "cold": cold, "rng": rng}


def _server(tr, **kw):
    return PagedContinuousGenerationServer(
        tr["paged"], executor=tr["exe"], scope=tr["scope"], **kw)


def _two_turns(tr, srv):
    """Turn 1 (fresh session) + turn 2 (extend_tokens) on the picked
    prompt; returns (r1, history-after-turn-1, r2)."""
    r1 = np.asarray(srv.submit(tr["p1"],
                               session_id="chat").result(120.0))
    h1 = list(srv.session_history("chat"))
    r2 = np.asarray(srv.submit(tr["p1"], session_id="chat",
                               extend_tokens=EXT).result(120.0))
    return r1, h1, r2


class TestSessions:
    def test_turn1_byte_identical_to_cold_decode(self, trained):
        with _server(trained) as srv:
            r1 = srv.submit(trained["p1"],
                            session_id="chat").result(120.0)
        assert np.array_equal(r1, trained["cold"])

    def test_turn2_resumes_via_radix_tier(self, trained):
        with _server(trained) as srv:
            r1, h1, r2 = _two_turns(trained, srv)
            st = srv.pool_stats()
        # the harvested history holds >= 1 full block, so turn 2 MUST
        # come back through the radix tier with real block reuse
        assert st["radix_admissions"] >= 1, st
        assert st["radix_hit_blocks"] >= 1, st
        assert st["radix_inserts"] >= 1, st
        # resumed decode replays the retained history byte-exactly,
        # then echoes the user turn in place
        assert np.array_equal(r2[:len(h1)], r1[:len(h1)])
        assert list(r2[len(h1):len(h1) + len(EXT)]) == EXT
        # ... and keeps decoding PAST the first turn's terminator
        assert int((r2 != -1).sum()) > len(h1)

    def test_radix_reuse_false_baseline_same_tokens_zero_hits(
            self, trained):
        with _server(trained) as radix_srv:
            _, _, want = _two_turns(trained, radix_srv)
        with _server(trained, radix_reuse=False) as replay_srv:
            _, _, got = _two_turns(trained, replay_srv)
            st = replay_srv.pool_stats()
        # the re-prefill baseline serves the SAME tokens (it is the
        # cold full-history decode) without touching the tree
        assert np.array_equal(got, want)
        assert st["radix_hit_blocks"] == 0, st
        assert st["radix_inserts"] == 0, st

    def test_close_session_releases_and_evict_drains_pool(
            self, trained):
        with _server(trained) as srv:
            _two_turns(trained, srv)
            srv.close_session("chat")
            assert srv.session_history("chat") is None
            held = len(srv._radix.tree_blocks())
            assert held >= 1
            # only the tree retains blocks once the session is gone
            assert srv._blocks.free_count == NB - held, (
                NB, held, srv._blocks.free_count)
            assert srv._radix.evict(held) == held
            assert srv._blocks.free_count == NB

    def test_server_evicts_by_its_leaf_index_and_counts_it(
            self, trained):
        """Plain generations fill the pool with retired chains until
        the server's own allocations evict (``_alloc_block_locked``,
        the admission watermark): the eviction counters reach
        ``pool_stats()`` and the pull provider, and an evicted block
        cost about one examined leaf."""
        from paddle_tpu import observability as obs
        from paddle_tpu.flags import FLAGS, set_flags

        rng = np.random.RandomState(23)
        prompts = rng.randint(3, V, (2 * NB, S)).astype(np.int64)
        prompts[:, S - 1:] = END_ID   # 9 tokens: one full block each
        prev = FLAGS.observability
        set_flags({"FLAGS_observability": "metrics"})
        try:
            with _server(trained) as srv:
                for p in prompts:
                    srv.submit(p).result(120.0)
                st = srv.pool_stats()
                label = srv._obs_id
                expo = obs.metrics.expose()
                assert st["radix_nodes"] == len(
                    srv._radix.tree_blocks())
                held = st["radix_nodes"]
                assert srv._radix.evict(NB) == held
                assert srv._blocks.free_count == NB
        finally:
            set_flags({"FLAGS_observability": prev})
        assert st["radix_evicted_blocks"] >= 1, st
        assert 1 <= st["radix_evict_calls"], st
        assert st["radix_evicted_blocks"] \
            <= st["radix_evict_candidates"] \
            < 3 * st["radix_evicted_blocks"], st
        for name, key in (("evicted_blocks", "radix_evicted_blocks"),
                          ("evict_calls", "radix_evict_calls"),
                          ("evict_candidates",
                           "radix_evict_candidates")):
            line = (f'paddle_tpu_blockpool_radix_{name}_total'
                    f'{{server="{label}"}} {st[key]}')
            assert line in expo, line

    def test_best_of_n_shares_prompt_entry_greedy_identical(
            self, trained):
        p2 = _mixed_len_prompts(trained["rng"], 1)[0]
        with _server(trained) as srv:
            hits0 = srv.pool_stats()["prefix_hits"]
            rs = [np.asarray(r.result(120.0))
                  for r in srv.submit(p2, n_best=3)]
            st = srv.pool_stats()
        for r in rs[1:]:
            assert np.array_equal(r, rs[0])
        # branches 2..n admit through the prompt-entry HIT tier (the
        # fan-out shares one refcounted encoder entry)
        assert st["prefix_hits"] - hits0 >= 2, st


class TestBeamCOW:
    """PagedBeamDecoder vs the whole-loop beam oracle. Slow-marked:
    the While-loop beam reference is a multi-minute compile (the
    test_control_flow_decode class of program)."""

    @pytest.fixture(scope="class")
    def beam(self, trained):
        T, unique_name = trained["T"], trained["unique_name"]
        with unique_name.guard():
            beam_m, _, _, (b_ids, b_scores) = \
                T.build_beam_decode_program(
                    beam_size=3, batch_size=1, **trained["kwargs"])
        # params are already trained in the shared scope (explicit
        # enc/dec names) — running the beam startup would re-init them
        with unique_name.guard():
            paged2 = T.build_decode_step_program(
                n_slots=N_SLOTS, state_prefix="@rxb/",
                cache=CacheConfig(layout="paged", block_size=BS,
                                  n_blocks=NB, n_prompt_entries=E),
                **trained["kwargs"])
        dec = PagedBeamDecoder(paged2, beam_size=3,
                               executor=trained["exe"],
                               scope=trained["scope"])
        return {"m": beam_m, "ids": b_ids, "scores": b_scores,
                "dec": dec}

    def _check_parity(self, tr, beam, prompt):
        ref_ids, ref_scores = tr["exe"].run(
            beam["m"], feed={"src_ids": prompt[None]},
            fetch_list=[beam["ids"], beam["scores"]],
            scope=tr["scope"])
        ref_rows = apply_eos_sentinel(np.asarray(ref_ids).T, END_ID)
        ref_sc = sorted(float(s) for s in np.asarray(ref_scores))
        hyps = beam["dec"].decode(prompt, return_all=True)
        got_sc = sorted(sc for _, sc in hyps)
        for g, r in zip(got_sc, ref_sc):
            assert abs(g - r) < 1e-4, (got_sc, ref_sc)
        assert {tuple(t) for t, _ in hyps} \
            == {tuple(r) for r in ref_rows}
        # every block came back (sharing/COW balanced its refcounts)
        assert beam["dec"]._pool.free_count == NB

    @pytest.mark.slow
    def test_short_decode_token_and_score_exact(self, trained, beam):
        p = _mixed_len_prompts(np.random.RandomState(11), 1)[0]
        self._check_parity(trained, beam, p)

    @pytest.mark.slow
    def test_long_decode_crosses_block_boundaries(self, trained,
                                                  beam):
        # the fixture prompt decodes > BS tokens greedily: beam
        # hypotheses cross >= 1 boundary, exercising full-block
        # sharing, sole-heir inheritance and partial-block COW
        self._check_parity(trained, beam, trained["p1"])
        assert beam["dec"].cow_blocks >= 1


class TestTpComposition:
    @pytest.mark.slow
    def test_radix_session_token_exact_on_tp2_bundle(self, trained):
        """The tree keys on token content and block INDICES — both
        host-side and replicated — so a tp=2 placement must not move
        a single token of the resumed decode."""
        import jax

        from paddle_tpu.core.scope import Scope

        T, unique_name = trained["T"], trained["unique_name"]
        with _server(trained) as srv:
            r1, h1, r2 = _two_turns(trained, srv)
        with unique_name.guard():
            tp_bundle = T.build_decode_step_program(
                n_slots=N_SLOTS, state_prefix="@rxtp/",
                sharding=ShardingConfig(tp=2),
                cache=CacheConfig(layout="paged", block_size=BS,
                                  n_blocks=NB, n_prompt_entries=E),
                **trained["kwargs"])
        assert tp_bundle.sharding_plan is not None
        # fork the trained scope to host numpy: the sharded server
        # places ITS OWN copy on its mesh slice
        fork = Scope()
        for name in list(trained["scope"]._vars):
            val = trained["scope"]._get(name)
            if isinstance(val, jax.Array):
                val = np.asarray(val)
            fork._set(name, np.copy(val)
                      if isinstance(val, np.ndarray) else val)
        with PagedContinuousGenerationServer(
                tp_bundle, executor=trained["exe"],
                scope=fork) as tp_srv:
            t1, th1, t2 = _two_turns(trained, tp_srv)
            st = tp_srv.pool_stats()
        assert st["radix_hit_blocks"] >= 1, st
        assert np.array_equal(t1, r1)
        assert th1 == h1
        assert np.array_equal(t2, r2)
