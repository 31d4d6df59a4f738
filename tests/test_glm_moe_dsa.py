"""GLM-5.2 (glm_moe_dsa) on the serve engine at rehearsal sizes on the
CPU, float32 weights from the seed: the decoder-only bundle served by
PagedContinuousGenerationServer against the plain reference
(benchmark/chip/reference/glm_moe_dsa.py) through prefill in chunks,
the paged latent cache and decoding; the shared selection; the expert
ranks' shares; the absorbed attention against the expanded; block
sharing through the radix tree; the planner's edges."""
import json
import os

import numpy as np
import pytest

from benchmark.chip.reference import glm_moe_dsa as R

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 7


def sizes(**over):
    with open(os.path.join(HERE, "..", "benchmark", "chip", "configs",
                           "glm-5.2-serve-ep16.json")) as f:
        config = json.load(f)
    return {**config["sizes"], **config["rehearsal"],
            "weight_dtype": "float32", **over}


def build(c, seed=SEED, **over):
    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.inference import PagedContinuousGenerationServer
    from paddle_tpu.models import glm_moe_dsa as G

    from benchmark.chip.drivers.glm_serve import NOT_THE_BUILDERS

    model = {k: v for k, v in R.model_cfg(c).items()
             if k not in NOT_THE_BUILDERS}
    geometry = dict(n_slots=4, block_size=8, n_blocks=64, context=128,
                    max_new_tokens=16, chunk_sizes=(8, 32), max_chunks=4)
    geometry.update(over)
    server = {k: geometry.pop(k) for k in ("steps_per_tick", "drain_steps")
              if k in geometry}
    with unique_name.guard():
        bundle = G.build_glm_serve_bundle(
            dtype=c["weight_dtype"], probe_logits=True, **model,
            **geometry)
    scope, exe = Scope(), fluid.Executor(fluid.TPUPlace(0))
    for name, value in R.make_top(seed, c).items():
        scope._set(name, value)
    for i in range(c["n_layers"]):
        for name, value in R.make_layer(seed, c, i).items():
            scope._set(name, value)
    srv = PagedContinuousGenerationServer(
        bundle, executor=exe, scope=scope, record_probes=True,
        **{"steps_per_tick": 4, "drain_steps": 4, **server})
    return srv, scope


def served(row):
    return row[1:1 + int((row[1:] >= 0).sum())]


@pytest.fixture(scope="module")
def session():
    """One server, a document cached by a first request, then three
    requests at once (two on the document, one short and new), with
    the block tables of every dispatch kept."""
    c = sizes()
    srv, scope = build(c)
    tables = []
    pre = srv._pre_dispatch

    def keep_tables():
        tables.append((srv._tab.copy(),
                       [r is not None for r in srv._lanes]))
        return pre()
    srv._pre_dispatch = keep_tables
    rng = np.random.default_rng(0)
    doc = rng.integers(3, c["vocab"], 40)
    prompts = [np.concatenate([doc, rng.integers(3, c["vocab"], n)])
               for n in (5, 17, 9)] + [rng.integers(3, c["vocab"], 3)]
    news = (6, 10, 16, 4)
    first = srv.submit(prompts[0], max_new_tokens=news[0],
                       cache_tokens=len(doc))
    first.result(timeout=600)
    pool = srv.bundle.state["block_tab"].replace("block_tab", "lat0@POOL")
    doc_blocks = [int(b) for b in srv._radix.acquire(
        (), __import__("paddle_tpu").inference.decoder_only._chunks(
            doc, 8))]
    srv._radix.release(doc_blocks)
    before = np.asarray(scope._get(pool)).reshape(64, 8, -1)[doc_blocks]
    rest = [srv.submit(p, max_new_tokens=m, cache_tokens=len(doc))
            for p, m in zip(prompts[1:], news[1:])]
    for r in rest:
        r.result(timeout=600)
    after = np.asarray(scope._get(pool)).reshape(64, 8, -1)[doc_blocks]
    stats = srv.pool_stats()
    out = {"c": c, "prompts": prompts, "news": news,
           "replies": [first] + rest, "tables": tables, "doc": doc,
           "doc_blocks": doc_blocks, "before": before, "after": after,
           "stats": stats, "srv": srv}
    yield out
    srv.close()


@pytest.fixture(scope="module")
def passes(session):
    """The reference's pass over every request: prompt and what was
    served, read at the decode positions."""
    c, out = session["c"], []
    for p, reply in zip(session["prompts"], session["replies"]):
        toks = served(reply.result())
        want = np.arange(len(p) - 1, len(p) - 1 + len(toks))
        out.append(R.forward(c, SEED, np.concatenate([p, toks]), want,
                             block=16))
    return out


@pytest.mark.parametrize("i", range(4))
def test_served_tokens_and_logits_follow_the_reference(session, passes, i):
    reply, ref = session["replies"][i], passes[i]
    toks = served(reply.result())
    assert len(toks) == session["news"][i]
    assert (ref["logits"].argmax(-1) == toks).all()
    assert np.abs(reply.probe["logits"] - ref["logits"]).max() < 5e-5


@pytest.mark.parametrize("i", range(4))
def test_selection_and_routing_equal_the_references(session, passes, i):
    probe, ref = session["replies"][i].probe, passes[i]
    p = session["prompts"][i]
    assert probe["position"] == len(p) - 2 + session["news"][i]
    kinds = session["c"]["indexer_types"]
    assert sorted(probe["selected"]) == list(range(len(kinds)))
    for li in sorted(probe["selected"]):
        mine = sorted(int(x) for x in probe["selected"][li] if x >= 0)
        theirs = [int(x) for x in ref["selected"][li][-1] if x >= 0]
        if kinds[li] == "shared":   # the layer below's, to the letter
            assert (probe["selected"][li]
                    == probe["selected"][li - 1]).all()
        assert mine == theirs
        # contexts short of index_topk select everything, past it
        # exactly index_topk
        assert len(mine) == min(probe["position"] + 1,
                                session["c"]["index_topk"])
    for j, li in enumerate(sorted(probe["chosen"])):
        assert (np.sort(probe["chosen"][li], -1)
                == ref["chosen"][j]).all()


def test_contexts_short_of_and_past_index_topk_are_both_served(session):
    topk = session["c"]["index_topk"]
    lengths = [len(p) for p in session["prompts"]]
    assert min(lengths) < topk < max(lengths)


def test_a_shared_layer_attends_its_full_layers_selection(session):
    """In every serve program a layer's attention reads the selection
    of the nearest layer below it that owns an indexer."""
    kinds = session["c"]["indexer_types"]
    for prog in session["srv"].bundle.programs():
        for block in prog.blocks:
            ops = [op for op in block.ops
                   if op.type in ("dsa_select",
                                  "sparse_latent_attention")]
            if not ops:
                continue
            last, seen = None, []
            for op in ops:
                if op.type == "dsa_select":
                    last = op.outputs["Out"][0]
                else:   # positions in a tick, a threshold in a chunk
                    mine = op.inputs.get("Sel") or op.inputs["Thr"]
                    seen.append(mine[0] == last)
            assert len(seen) == len(kinds) and all(seen)
            assert sum(op.type == "dsa_select" for op in ops) \
                == kinds.count("full")


def test_two_lanes_on_one_document_map_the_same_blocks(session):
    blocks = session["doc_blocks"]
    assert len(blocks) == len(session["doc"]) // 8
    shared = [tab for tab, live in session["tables"]
              if sum((tab[s, :len(blocks)] == blocks).all()
                     for s in range(4) if live[s]) >= 2]
    assert shared, "no dispatch saw two lanes on the document's blocks"


def test_a_shared_block_is_never_written(session):
    """What the document's blocks hold is what its first request wrote:
    the lanes that mapped them later wrote their own blocks only."""
    assert (session["before"] == session["after"]).all()
    assert np.abs(session["before"]).sum() > 0


def test_cached_prompt_tokens_are_counted_and_not_prefilled(session):
    st, doc = session["stats"], session["doc"]
    assert st["cached_prompt_tokens"] == 2 * len(doc)
    assert st["prompt_tokens"] == sum(len(p)
                                      for p in session["prompts"])
    assert st["prefill_tokens"] == st["prompt_tokens"] \
        - st["cached_prompt_tokens"] - len(session["prompts"])
    assert st["radix_admissions"] == 2
    assert st["lane_ticks"] == sum(session["news"])


def test_lanes_give_their_blocks_back(session):
    st = session["stats"]
    assert st["blocks_in_use"] == st["radix_nodes"] \
        == len(session["doc"]) // 8
    assert st["filling_lanes"] == 0


def test_experts_counters_count_live_lanes_only(session):
    st, c = session["stats"], session["c"]
    n_moe = c["n_layers"] - c["n_dense_layers"]
    assert st["moe_pairs"] == sum(sum(v) for v in st["moe_load"].values())
    assert 0 < st["moe_pairs"] <= st["lane_ticks"] * n_moe * c["top_k"]
    assert st["moe_hit"] <= st["moe_pairs"]


def test_cycle_records_count_the_chunks_and_split_the_retirement(session):
    """The scheduler's record of every cycle (PR 36): its prefill
    chunks and positions add up to `pool_stats()`, and a cycle that
    retired a lane holds the two pieces of `slotpool.retire` that are
    not the sweep: the probes' readback and the radix tree's share."""
    records = session["srv"]._cycles.records()
    st = session["stats"]
    assert sum(r.get("prefill_chunks", 0) for r in records) \
        == st["prefill_chunks"] > 0
    assert sum(r.get("prefill_positions", 0) for r in records) \
        == st["prefill_tokens"]
    assert sum(r["retired"] for r in records) == len(session["prompts"])
    for rec in records:
        ph = rec["phases"]
        if rec["retired"]:
            assert ph["slotpool.retire.probe"] \
                + ph["slotpool.retire.tree"] \
                <= ph["slotpool.retire"] + 1e-3
        else:
            assert "slotpool.retire.probe" not in ph
        # a cycle with a chunk is an admitting one to the benchmark
        assert ("prefill_chunks" in rec) == (rec["key"] != 0)


# ---------------------------------------------------------------------
# the ranks' shares, tied to the model
# ---------------------------------------------------------------------
def test_the_ranks_shares_add_up_to_the_whole_layer():
    """Over all ranks' experts_held, the routed parts plus the shared
    expert counted once are the uncut layer; and the program's layer,
    told a rank's experts, computes that rank's part."""
    import jax.numpy as jnp
    from paddle_tpu.parallel import moe

    c = sizes()
    x = np.random.default_rng(1).normal(size=(24, c["d_model"]))
    whole, shared, chosen = R.moe_layer_parts(
        c, SEED, x, 1, experts=(0, c["n_experts"]))
    held = c["experts_held"]
    total = 0
    for first in range(0, c["n_experts"], held):
        part, same, idx = R.moe_layer_parts(c, SEED, x, 1,
                                            experts=(first, held))
        assert (np.asarray(idx) == np.asarray(chosen)).all()
        assert np.allclose(same, shared)
        p = R.make_layer(SEED, {**R.model_cfg(c), "first_held": first}, 1)
        u = R.rms_norm(jnp.asarray(x, jnp.float32), p["g1_norm2.w"],
                       c["norm_eps"])
        out, _, load, pairs = moe.moe_dropless(
            u, p["g1_moe_gate.w"], p["g1_moe_bias"], p["g1_moe_w13"],
            p["g1_moe_w2"], first_held=first, top_k=c["top_k"],
            norm_topk=True, scaling=c["routed_scaling"])
        assert np.abs(np.asarray(out) - np.asarray(part)).max() < 2e-5
        assert int(pairs[0]) == int(
            ((np.asarray(idx) >= first)
             & (np.asarray(idx) < first + held)).sum())
        total = total + part
    assert np.abs(np.asarray(total) - np.asarray(whole)).max() < 2e-5
    assert np.abs(np.asarray(whole)).max() > 0.1


# ---------------------------------------------------------------------
# the absorbed attention against the expanded
# ---------------------------------------------------------------------
def _attention_program(c, n, pages, bs):
    import paddle_tpu as fluid
    from paddle_tpu import layers, unique_name

    prog = fluid.Program()
    with unique_name.guard(), fluid.program_guard(prog, fluid.Program()):
        x = layers.data("x", shape=[n, c["d_model"]],
                        append_batch_size=False)
        pos = layers.data("pos", shape=[n], dtype="int64",
                          append_batch_size=False)
        tab = layers.data("tab", shape=[1, pages], dtype="int32",
                          append_batch_size=False)
        sel = layers.data("sel", shape=[n, n], dtype="int32",
                          append_batch_size=False)
        pool = prog.global_block.create_var(
            name="pool@POOL", shape=(pages * bs, c["kv_lora_rank"]
                                     + c["qk_rope_head_dim"]),
            dtype="float32", persistable=True, stop_gradient=True)
        q_lat, _, latent, kv_b = layers.mla_project(
            x, pos, c["n_heads"], c["q_lora_rank"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"], rope_theta=c["rope_theta"],
            epsilon=c["norm_eps"], name="g0")
        cell = layers.paged_cell_index(tab, pos, bs)
        layers.masked_pool_write(
            pool, latent, cell,
            gate=layers.fill_constant([n], "float32", 1.0),
            leading_dims=1, exclusive_via="block_table")
        ctx = layers.sparse_latent_attention(
            q_lat, pool, tab, sel, bs, c["kv_lora_rank"],
            scale=(c["qk_nope_head_dim"]
                   + c["qk_rope_head_dim"]) ** -0.5)
        out = layers.mla_output(ctx, kv_b, c["qk_nope_head_dim"])
    return prog, out


@pytest.mark.parametrize("query_block", [128, 4])
def test_absorbed_attention_equals_the_expanded(monkeypatch, query_block):
    """mla_project -> the paged latent pool -> sparse_latent_attention
    -> mla_output (keys and values never expanded) against keys and
    values expanded a head, as the reference computes them; with the
    queries worked on all at once and a block at a time."""
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.ops import paged_ops

    monkeypatch.setattr(paged_ops, "QUERY_BLOCK", query_block)
    c, n, bs = sizes(), 16, 8
    pages = n // bs
    prog, out = _attention_program(c, n, pages, bs)
    p = R.make_layer(SEED, R.model_cfg(c), 0)
    scope = Scope()
    for name, value in p.items():
        scope._set(name, value)
    scope._set("pool@POOL", np.zeros(
        (pages * bs, c["kv_lora_rank"] + c["qk_rope_head_dim"]),
        np.float32))
    rng = np.random.default_rng(2)
    x = rng.normal(size=(n, c["d_model"])).astype(np.float32)
    causal = np.where(np.arange(n)[None] <= np.arange(n)[:, None],
                      np.arange(n)[None], -1).astype(np.int32)
    got, = fluid.Executor(fluid.TPUPlace(0)).run(
        prog, feed={"x": x, "pos": np.arange(n), "sel": causal,
                    "tab": np.array([[1, 0]], np.int32)},
        fetch_list=[out], scope=scope)
    # expanded, in the reference's own words
    h, dn, dr, dv = (c["n_heads"], c["qk_nope_head_dim"],
                     c["qk_rope_head_dim"], c["v_head_dim"])
    rkv, pos = c["kv_lora_rank"], jnp.arange(n)
    xf = jnp.asarray(x)
    cq = R.rms_norm(xf @ p["g0_q_a.w"], p["g0_q_a_norm.w"],
                    c["norm_eps"])
    q = (cq @ p["g0_q_b.w"]).reshape(n, h, dn + dr)
    q = jnp.concatenate([q[..., :dn],
                         R.rope(q[..., dn:], pos, c["rope_theta"])], -1)
    ckv = xf @ p["g0_kv_a.w"]
    lat = R.rms_norm(ckv[:, :rkv], p["g0_kv_a_norm.w"], c["norm_eps"])
    kr = R.rope(ckv[:, rkv:], pos, c["rope_theta"])
    kv = (lat @ p["g0_kv_b.w"]).reshape(n, h, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        kr[:, None], (n, h, dr))], -1)
    s = jnp.einsum("thd,shd->hts", q, k) * (dn + dr) ** -0.5
    s = jnp.where(jnp.asarray(causal >= 0)[None], s, -1e30)
    want = jnp.einsum("hts,shd->thd", __import__("jax").nn.softmax(
        s, -1), kv[..., dn:]).reshape(n, h * dv)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    assert np.abs(np.asarray(want)).max() > 0.05


# ---------------------------------------------------------------------
# the planner's edges
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def small():
    srv, _ = build(sizes(), n_blocks=12, n_slots=2, context=64,
                   max_new_tokens=8)
    yield srv
    srv.close()


def test_submit_says_what_does_not_fit(small):
    with pytest.raises(ValueError, match="max_new_tokens"):
        small.submit(np.arange(3, 9), max_new_tokens=9)
    with pytest.raises(ValueError, match="context"):
        small.submit(np.arange(3, 63), max_new_tokens=8)
    with pytest.raises(ValueError, match="encoder-decoder"):
        small.submit(np.arange(3, 9), session_id="s")
    with pytest.raises(ValueError, match="encoder-decoder"):
        small.submit(np.arange(3, 9), n_best=2)


def test_a_lone_request_larger_than_the_pool_fails_by_name():
    from paddle_tpu.inference import BlockPoolExhausted

    srv, _ = build(sizes(), n_blocks=4, n_slots=2, context=64,
                   max_new_tokens=8)
    try:
        with pytest.raises(BlockPoolExhausted):
            srv.submit(np.arange(3, 43), max_new_tokens=8).result(60)
        assert srv.pool_stats()["blocks_in_use"] == 0
    finally:
        srv.close()


def test_requests_wait_for_blocks_and_all_are_served(small):
    """Six requests of five blocks each on a pool of twelve: two run at
    a time, the rest wait in the queue; nothing is kept in the tree, so
    every block comes back."""
    rng = np.random.default_rng(3)
    replies = [small.submit(rng.integers(3, 200, 30), max_new_tokens=6,
                            cache_tokens=0) for _ in range(6)]
    rows = [r.result(timeout=600) for r in replies]
    assert all(len(served(row)) == 6 for row in rows)
    st = small.pool_stats()
    assert st["blocks_in_use"] == 0 and st["radix_nodes"] == 0


def test_the_tree_gives_way_when_blocks_run_short(small):
    """Cached prompts are evicted for a request that needs their
    blocks, and a repeated prompt is found again afterwards."""
    rng = np.random.default_rng(4)
    a, b = rng.integers(3, 200, 40), rng.integers(3, 200, 50)
    row_a = small.submit(a, max_new_tokens=4).result(timeout=600)
    assert small.pool_stats()["radix_nodes"] == 5
    small.submit(b, max_new_tokens=8).result(timeout=600)
    st = small.pool_stats()
    assert st["radix_evicted_blocks"] > 0
    again = small.submit(a, max_new_tokens=4).result(timeout=600)
    assert (again == row_a).all()


def test_streamed_tokens_equal_the_row(small):
    rng = np.random.default_rng(5)
    got = []
    reply = small.submit(rng.integers(3, 200, 21), max_new_tokens=7,
                         cache_tokens=0, stream=True,
                         stream_cb=lambda chunk, seq, fin:
                             got.extend(chunk.tolist()))
    streamed = [tok for _seq, tok in reply]
    row = reply.result(timeout=60)
    assert streamed == list(served(row)) == got
    assert reply.finish_reason == "length"


def test_a_cancelled_request_gives_its_blocks_back(small):
    rng = np.random.default_rng(6)
    reply = small.submit(rng.integers(3, 200, 40), max_new_tokens=8,
                         cache_tokens=0, stream=True)
    reply.cancel()
    with pytest.raises(Exception):
        reply.result(timeout=60)
    assert small.drain(timeout=60)
    st = small.pool_stats()
    assert st["blocks_in_use"] == st["radix_nodes"]
    assert st["filling_lanes"] == 0


def test_the_encoder_decoder_server_keeps_its_contract():
    """PagedContinuousGenerationServer on an encoder-decoder bundle is
    the class itself, with its exact-length submit."""
    from paddle_tpu.inference import PagedContinuousGenerationServer
    from paddle_tpu.inference.decoder_only import DecoderOnlyPagedServer
    from paddle_tpu.models.decode_engine import DecodeStepBundle

    assert not getattr(DecodeStepBundle, "decoder_only", False)
    assert issubclass(DecoderOnlyPagedServer,
                      PagedContinuousGenerationServer)
    assert object.__new__(PagedContinuousGenerationServer).__class__ \
        is PagedContinuousGenerationServer


@pytest.mark.parametrize("first", [3, 70, 100])
def test_a_chunks_routes_equal_a_ticks_whatever_the_context(first):
    """Many queries of one lane (the threshold and the context read
    once, as many pages as are live: a half, three quarters, all)
    against one query a lane (the positions and their rows gathered),
    on the same pools, scores with ties among them."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_ops as P

    rng = np.random.default_rng(first)
    bs, pages, n, k, heads = 8, 16, 24, 8, 3
    pool = jnp.asarray(rng.normal(size=(32 * bs, 40)), jnp.float32)
    ipool = jnp.asarray(rng.normal(size=(32 * bs, 6)), jnp.float32)
    tab = rng.permutation(32)[:pages].astype(np.int32)
    pos = np.arange(first, first + n)
    qi = jnp.asarray(rng.normal(size=(n, 2, 6)), jnp.float32)
    w = jnp.asarray(np.abs(rng.normal(size=(n, 2))), jnp.float32)
    q = jnp.asarray(rng.normal(size=(n, heads, 40)), jnp.float32)
    # a chunk: one group of n queries
    s_chunk = P.indexer_scores(qi, w, ipool, jnp.asarray(tab[None]),
                               jnp.asarray(pos), bs)
    thr = P.kth_largest(s_chunk, k)
    chunk = P.dense_masked_latent_attention(
        q, pool, jnp.asarray(tab[None]), s_chunk, thr, k, bs, 32, 0.3)
    # a tick: n groups of one query, each with the same table
    tabs = jnp.asarray(np.repeat(tab[None], n, 0))
    s_tick = P.indexer_scores(qi, w, ipool, tabs, jnp.asarray(pos), bs)
    assert (np.asarray(s_tick) == np.asarray(s_chunk)).all()
    assert (np.asarray(s_chunk) == 0).sum() > n      # relu's ties
    val, idx = jax.lax.top_k(s_tick, k)
    sel = jnp.where(val > -jnp.inf, idx, -1).astype(jnp.int32)
    tick = P.sparse_latent_attention_reference(q, pool, tabs, sel, bs,
                                               32, 0.3)
    assert np.abs(np.asarray(chunk) - np.asarray(tick)).max() < 1e-5
