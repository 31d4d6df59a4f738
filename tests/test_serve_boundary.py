"""The host boundary of a serve dispatch (models/decode_engine.py
`build_serve_program`, `ServeRow`; inference/serving.py `_cycle`): one
array each way. The scheduler's tables ride the call as feeds, and what
the scheduler reads back comes home as one packed row.

* over cycles with admissions, retirements and (where the bundle kind
  has them) paused or filling lanes, the list cut from the packed row
  equals, bit for bit and in order, what a second handle prepared with
  the unpacked fetch list returns on the same scope and the same feed,
  the scope after the two is the same, and the served tokens are the
  whole-loop decode's (the reference's, for the decoder-only bundles);
* once the first dispatch has taken up what `init_slot_state` left, the
  scope holds no host array under any name a serve program reads, and
  a serve program's lowered module returns one array besides its state.

The bundle kinds: paged encoder-decoder (a pool too small for its
lanes), dense slot-pool, speculative with the k ladder, chunked
prefill, the GLM-5.2 and Nemotron-3 rehearsal bundles, a tp=2 paged
bundle."""
import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import unique_name
from paddle_tpu.core.executor import RNG_VAR
from paddle_tpu.core.scope import Scope
from paddle_tpu.inference import (ContinuousGenerationServer,
                                  PagedContinuousGenerationServer,
                                  apply_eos_sentinel)
from paddle_tpu.models import transformer as T
from paddle_tpu.models.decode_engine import (CacheConfig, DraftConfig,
                                             ShardingConfig, fed_name)

V, D, H, L, S, MAXT = 16, 32, 2, 1, 10, 32
END_ID = 1
KINDS = ("paged", "dense", "spec_ladder", "chunked", "glm", "nemotron",
         "tp2")


class _BothWays:
    """Stands in for one of a server's serve handles: runs the dispatch
    as the server prepared it (fetched for the packed row), puts the
    scope back as it was, runs the same program prepared with the
    unpacked fetch list on the same feed, and holds the two to each
    other: the lists, and the scope they leave."""

    def __init__(self, srv, key, seen):
        self.srv, self.key, self.seen = srv, key, seen
        self.handle = srv._serves[key]
        self.plain = None

    def _state(self):
        scope = self.srv.scope
        out = {}
        for n in list(self.srv.bundle._state_specs) + [RNG_VAR]:
            v = scope._get(n)
            out[n] = None if v is None else np.array(v)
        return out

    def run(self, feed, return_numpy=True):
        srv, row = self.srv, self.srv.bundle.serve_row
        if self.plain is None:
            self.plain = srv.executor.prepare(
                srv.bundle.serves[self.key],
                feed=srv.bundle.serve_feed_spec(self.key),
                fetch_list=list(row.names), scope=srv.scope)
        before = self._state()
        got = self.handle.run(feed, return_numpy=return_numpy)
        after = self._state()
        for n, v in before.items():
            srv.scope._set(n, v)
        want = self.plain.run(feed, return_numpy=True)
        assert len(got) == len(want) == len(row.names)
        for name, g, w in zip(row.names, got, want):
            w = np.asarray(w)
            assert g.dtype == w.dtype and g.shape == w.shape, name
            np.testing.assert_array_equal(g, w, err_msg=name)
        # views of one host buffer
        assert all(g.base is not None for g in got)
        now = self._state()
        for n, v in after.items():
            np.testing.assert_array_equal(v, now[n], err_msg=n)
        self.seen.append((self.key, feed))
        return got

    def __getattr__(self, name):
        return getattr(self.handle, name)


def _both_ways(srv):
    """Every serve handle of `srv` held to its unpacked twin; returns
    the list that collects (key, feed) of each dispatch."""
    seen = []
    for key in list(srv._serves):
        srv._serves[key] = _BothWays(srv, key, seen)
    return seen


# --- the 2017 transformer at toy widths: weights as initialized ----------
@pytest.fixture(scope="module")
def model():
    """(executor, scope, whole-loop oracle): target and draft weights
    as the startup programs leave them; greedy argmax over them is
    deterministic, which is all parity needs."""
    fluid.seed(0)
    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    with unique_name.guard():
        _, t_st, _ = T.build_program(
            seq_len=S, d_model=D, n_heads=H, n_layers=L, d_inner=64,
            vocab=V, with_optimizer=False, dropout_rate=0.0)
        _, d_st, _ = T.build_program(
            seq_len=S, d_model=16, n_heads=H, n_layers=L, d_inner=32,
            vocab=V, with_optimizer=False, dropout_rate=0.0,
            name_prefix="draft_")
    exe.run(t_st, scope=scope)
    exe.run(d_st, scope=scope)
    kwargs = dict(seq_len=S, max_out_len=MAXT, d_model=D, n_heads=H,
                  n_layers=L, d_inner=64, vocab=V, start_id=2,
                  end_id=END_ID)
    with unique_name.guard():
        inc_m, _, _, inc_buf = T.build_incremental_decode_program(
            **kwargs)

    def oracle(srcs):
        ref, = exe.run(inc_m, feed={"src_ids": srcs},
                       fetch_list=[inc_buf], scope=scope)
        return apply_eos_sentinel(np.asarray(ref), end_id=END_ID)

    return {"exe": exe, "scope": scope, "kwargs": kwargs,
            "oracle": oracle}


class _Rungs:
    """A controller that walks the ladder, one rung a dispatch."""

    def __init__(self, ladder):
        self.ladder, self.i = ladder, 0

    def choose(self):
        self.i += 1
        return self.ladder[self.i % len(self.ladder)]

    def observe(self, accepted_delta, ticks_delta, k):
        pass

    def reset_lane(self, lane):
        pass

    def stats(self):
        return {}


def _transformer_server(kind, model):
    """(server, prompts, expected rows, what the run must have seen)."""
    kw, exe, scope = model["kwargs"], model["exe"], model["scope"]
    rng = np.random.RandomState(11)
    srcs = rng.randint(3, V, (10, S)).astype(np.int64)
    srcs[3:6] = srcs[0:3]                   # repeats: the hit tier
    want = model["oracle"](srcs)
    if kind == "tp2":
        # the mesh takes the weights over: a scope of its own
        shared, scope = scope, Scope()
        for name in list(shared._vars):
            scope._set(name, np.array(shared._get(name)))
    paged = dict(layout="paged", block_size=8, n_prompt_entries=3)
    with unique_name.guard():
        if kind == "paged":
            # 4 lanes of 4 pages each over 7 blocks: lanes pause
            bundle = T.build_decode_step_program(
                n_slots=4, state_prefix="@sbp/",
                cache=CacheConfig(n_blocks=7, **paged), **kw)
        elif kind == "dense":
            bundle = T.build_decode_step_program(
                n_slots=4, state_prefix="@sbd/", **kw)
        elif kind == "spec_ladder":
            bundle = T.build_decode_step_program(
                n_slots=4, state_prefix="@sbs/", admit_buckets=[4],
                draft=DraftConfig(d_model=16, n_heads=H, n_layers=L,
                                  d_inner=32, k=2, k_options=(0, 2, 4)),
                **kw)
        elif kind == "chunked":
            bundle = T.build_decode_step_program(
                n_slots=4, admit_buckets=[1, 4], state_prefix="@sbc/",
                cache=CacheConfig(n_blocks=24, chunk_tokens=4, **paged),
                **kw)
        else:
            assert kind == "tp2"
            bundle = T.build_decode_step_program(
                n_slots=2, admit_buckets=[2], state_prefix="@sbt/",
                sharding=ShardingConfig(tp=2),
                cache=CacheConfig(n_blocks=8, **paged), **kw)
    common = dict(executor=exe, scope=scope, steps_per_tick=3,
                  start=False)
    if kind == "dense":
        srv = ContinuousGenerationServer(bundle, **common)
    elif kind == "spec_ladder":
        srv = ContinuousGenerationServer(
            bundle, spec_controller=_Rungs((0, 2, 4)), **common)
    else:
        srv = PagedContinuousGenerationServer(bundle, **common)
    return srv, list(srcs), want


def _decoder_only_server(kind):
    """The rehearsal bundle of a decoder-only configuration, and a
    check of a served row against the reference's forward pass."""
    if kind == "glm":
        import test_glm_moe_dsa as M
        lengths, news = (45, 5, 17, 33, 3), (6, 10, 16, 4, 8)
    else:
        import test_nemotron_h as M
        lengths, news = (45, 5, 17, 33, 1), (6, 10, 16, 4, 8)
    c = M.sizes()
    srv, _scope = M.build(c)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(3, c["vocab"], n) for n in lengths]

    def check(prompt, row):
        toks = M.served(row)
        want = np.arange(len(prompt) - 1, len(prompt) - 1 + len(toks))
        ref = M.R.forward(c, M.SEED, np.concatenate([prompt, toks]),
                          want)
        assert (ref["logits"].argmax(-1) == toks).all()

    return srv, prompts, news, check


@pytest.mark.parametrize("kind", KINDS)
def test_packed_row_is_the_unpacked_fetch_list(kind, model):
    if kind in ("glm", "nemotron"):
        srv, prompts, news, check = _decoder_only_server(kind)
        seen = _both_ways(srv)
        try:
            replies = [srv.submit(p, max_new_tokens=m)
                       for p, m in zip(prompts, news)]
            rows = [np.asarray(r.result(timeout=600)) for r in replies]
            stats = srv.pool_stats()
        finally:
            srv.close()
        for p, m, row in zip(prompts, news, rows):
            assert len(row[1:][row[1:] >= 0]) == m
            check(p, row)
        # lanes filled over several cycles while others decoded
        assert stats["prefill_chunks"] >= len(prompts) - 1
        assert sum(key == srv.bundle.PREFILL for key, _ in seen) > 1
        tables = ("block_tab", "active")
    else:
        srv, prompts, want = _transformer_server(kind, model)
        seen = _both_ways(srv)
        srv.start()
        try:
            replies = [srv.submit(p) for p in prompts]
            got = np.stack([r.result(timeout=300) for r in replies])
            stats = {} if kind in ("dense", "spec_ladder") \
                else srv.pool_stats()
        finally:
            srv.close()
        np.testing.assert_array_equal(got, want)
        tables = () if kind in ("dense", "spec_ladder") \
            else ("block_tab", "prompt_ref", "active")
        if kind == "paged":
            assert stats["pause_events"] + stats["preemptions"] > 0
        if kind == "chunked":
            assert stats["chunk_ticks"] > 0
        if kind == "spec_ladder":
            assert {k[1] for k, _ in seen
                    if isinstance(k, tuple) and k[0] == "k"} == {0, 4}
    # admissions and pure bursts (a chunk's phase rides every burst
    # of the chunked run) crossed the boundary, each with every table
    # the bundle says it is fed and no other
    keys = {key for key, _ in seen}
    assert len(keys) > 1 and (0 in keys or kind == "chunked")
    assert srv.bundle.fed_tables == tables
    for _key, feed in seen:
        assert {n for n in feed if n.startswith("fed_")} \
            == {fed_name(t) for t in tables}
    assert srv.stats()["completed"] == len(prompts)


@pytest.mark.parametrize("kind", ["paged", "dense", "glm"])
def test_steady_state_leaves_nothing_to_place(kind, model):
    """After a cycle the scope holds a device array under every name a
    serve program reads from it (a host array there is what the next
    dispatch would have to place), none of the fed tables is among
    them for a scheduler to write, and the lowered module of a serve
    program returns one array besides its state and its key."""
    if kind == "glm":
        srv, prompts, news, _check = _decoder_only_server(kind)
        submit = [lambda p=p, m=m: srv.submit(p, max_new_tokens=m)
                  for p, m in zip(prompts[:2], news)]
    else:
        srv, prompts, _want = _transformer_server(kind, model)
        srv.start()
        submit = [lambda p=p: srv.submit(p) for p in prompts[:3]]
    bundle, scope = srv.bundle, srv.scope
    try:
        for r in [s() for s in submit]:
            r.result(timeout=600)
        assert srv.drain(timeout=60)
        counts = srv.executor._transfers
        placed = counts.placed_arrays
        for r in [s() for s in submit]:
            r.result(timeout=600)
        assert counts.placed_arrays == placed
        reads = set()
        for key, handle in srv._serves.items():
            comp = handle.step.compiled
            assert comp.fetch_names == [bundle.serve_row.name]
            reads |= set(comp.state_in) | set(comp.const_in)
            # the fed tables are written by the program before it reads
            # them: no serve program takes one from the scope
            assert not reads & {bundle.state[t]
                                for t in bundle.fed_tables}
            assert {bundle.state[t] for t in bundle.fed_tables} \
                <= set(comp.state_out)
        host = [n for n in sorted(reads)
                if not isinstance(scope._get(n), jax.Array)]
        assert not host, host
        # and what the scheduler fed is what the scope holds of them
        for t in bundle.fed_tables:
            assert isinstance(scope._get(bundle.state[t]), jax.Array)
        handle = srv._serves[0]
        feed = {name: jax.ShapeDtypeStruct(shape, np.dtype(dt))
                for name, shape, dt in bundle.serve_feed_spec(0)}
        state_out, fetches, _key = handle.step.lower(
            scope, feed).out_info
        assert len(fetches) == 1
        assert fetches[0].shape == (bundle.serve_row.size,)
        assert set(state_out) == set(handle.step.compiled.state_out)
    finally:
        srv.close()


def test_a_row_holds_one_dtype():
    from paddle_tpu.models.decode_engine import ServeRow

    specs = {"a": ((2, 3), "int64"), "b": ((1,), "int64"),
             "c": ((4,), "float32")}
    row = ServeRow("row", ["a", "b"], specs)
    assert row.size == 7 and row.shapes == [(2, 3), (1,)]
    a, b = row.cut(np.arange(7))
    assert a.tolist() == [[0, 1, 2], [3, 4, 5]] and b.tolist() == [6]
    with pytest.raises(ValueError, match="row of its own"):
        ServeRow("row", ["a", "c"], specs)
