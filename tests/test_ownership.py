"""Pool ownership & lifetime prover tests (the ownership domain of
paddle_tpu/analysis/absint.py + checkers PTA190/191/192).

Crafted fixtures pin the acceptance classes from ISSUE 14:

* the PROOF positive: the real block-table cell-addressing chain
  (``tab[lane, p//BS]*BS + p%BS`` through cast/scale/expand/add and
  the one-hot page/offset selection) resolves to a single exclusive
  source with the right bound, the named host assumption lands in the
  ledger, and PTA190/191/192 stay silent;
* ALIASED-WRITE fixtures: an index of unknown provenance (PTA190,
  chain printed), a direct non-masked_pool_write writer, a declared
  ``exclusive_via`` that disagrees with the proven provenance, and an
  index mixing two exclusive families (all PTA191, assumption named);
* the WRITE-WHILE-SHARED fixture: an index chaining to the refcounted
  ``prompt_entry_ref`` source is a PTA192 error — the COW contract;
* in-bounds: a mint-site bound exceeding the indexed axis is a PTA190
  error; an unbounded read is a warning;
* the PTA110 twin-dedupe and its non-convergence fallback.
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.analysis import ERROR, WARNING, absint, checkers, run_checks
from paddle_tpu.analysis.baseline import baseline_payload, collect_reports


def _guarded():
    main, startup = fluid.Program(), fluid.Program()
    return main, startup, fluid.program_guard(main, startup)


def _diags(program, code):
    return [d for d in run_checks(program) if d.code == code]


def _mk_pool(block, name="@own/self_k0@POOL", shape=(8, 4, 2, 8)):
    return block.create_var(name=name, shape=shape, dtype="float32",
                            persistable=True, stop_gradient=True)


def _mk_state(block, name, shape, dtype="int32"):
    return block.create_var(name=name, shape=shape, dtype=dtype,
                            persistable=True, stop_gradient=True)


def _block_table_chain(tab, act, rows=3, NP=2, BS=4, maxT=8):
    """The REAL paged addressing arithmetic (decode_engine._step_body
    condensed): write cell = tab[lane, page(t)]*BS + offset(t) via
    one-hot page/offset selection; gate = cast(active)."""
    stepv = _mk_state(tab.block, "@own/step", (rows,), "int64")
    tabf = layers.cast(tab, "float32")
    positions = layers.cast(layers.range(0, maxT, 1), "int64")
    step2 = layers.reshape(stepv, [rows, 1])
    t_mask = layers.cast(layers.equal(positions, step2), "float32")
    t_pages = layers.reshape(t_mask, [rows, NP, BS])
    page_oh = layers.reduce_sum(t_pages, dim=2)
    off_oh = layers.reduce_sum(t_pages, dim=1)
    offs = layers.assign(np.arange(BS, dtype="float32"))
    cur_block = layers.reduce_sum(
        layers.elementwise_mul(tabf, page_oh), dim=1)
    cur_off = layers.reduce_sum(
        layers.elementwise_mul(off_oh, offs), dim=1)
    write_idx = layers.cast(
        layers.elementwise_add(
            layers.scale(cur_block, scale=float(BS)), cur_off),
        "int32")
    gate = layers.cast(act, "float32")
    return write_idx, gate


class TestProvenanceEngine:
    def test_block_table_chain_proven_with_bound(self):
        main, startup, g = _guarded()
        with g:
            blk = main.global_block
            tab = _mk_state(blk, "@own/block_tab", (3, 2))
            act = _mk_state(blk, "@own/active", (3,), "int64")
            absint.mark_pool_index_source(tab, "block_table", bound=8)
            absint.mark_pool_index_source(act, "lane_active")
            write_idx, gate = _block_table_chain(tab, act)
        facts = absint.analyze(main)
        f = facts.prov_of(write_idx.name)
        assert f is not None and f.tags == ("block_table",)
        assert f.bound == 32        # NB*BS = 8*4: exactly the cells
        assert any("block_table mint" in c or "mark" in c
                   for c in f.chain)
        gf = facts.prov_of(gate.name)
        assert gf is not None and gf.tags == ("lane_active",)
        assert gf.indicator

    def test_mark_requires_registered_tag(self):
        main, startup, g = _guarded()
        with g:
            v = _mk_state(main.global_block, "@own/t", (3,))
            with pytest.raises(ValueError, match="unknown ownership"):
                absint.mark_pool_index_source(v, "no_such_source")

    def test_register_refuses_silent_redefinition(self):
        with pytest.raises(ValueError, match="already registered"):
            absint.register_pool_index_source(
                "block_table", "something else entirely",
                absint.TS_EXCLUSIVE)
        # idempotent re-registration of the identical entry is fine
        src = absint.pool_index_sources()["block_table"]
        absint.register_pool_index_source(
            src.tag, src.description, src.typestate, src.assumption,
            src.indicator)

    def test_typestate_seed_table_shape(self):
        srcs = absint.pool_index_sources()
        assert srcs["block_table"].typestate == absint.TS_EXCLUSIVE
        assert srcs["block_table"].assumption == \
            "HostBlockPool.alloc-disjoint"
        assert srcs["host_indices"].typestate == absint.TS_EXCLUSIVE
        assert srcs["prompt_entry_ref"].typestate == absint.TS_SHARED
        assert srcs["lane_active"].typestate == absint.TS_GATE


def _write_fixture(mark_idx=None, via="block_table", gate_mark=True,
                   idx_bound=32):
    """Pool + masked_pool_write through a FED index var, optionally
    marked; returns the program."""
    main, startup, g = _guarded()
    with g:
        blk = main.global_block
        pool = _mk_pool(blk)
        new = layers.data("new", shape=[3, 2, 8], dtype="float32",
                          append_batch_size=False)
        idx = layers.data("idx", shape=[3], dtype="int32",
                          append_batch_size=False)
        gate = layers.data("gate", shape=[3], dtype="float32",
                           append_batch_size=False)
        if mark_idx:
            absint.mark_pool_index_source(idx, mark_idx,
                                          bound=idx_bound)
        if gate_mark:
            absint.mark_pool_index_source(gate, "lane_active")
        layers.masked_pool_write(pool, new, idx, gate=gate,
                                 leading_dims=2, exclusive_via=via)
    return main


class TestPTA190:
    def test_unknown_provenance_write_is_error_with_chain(self):
        main = _write_fixture(mark_idx=None)
        ds = _diags(main, "PTA190")
        assert ds and ds[0].severity == ERROR
        assert "UNKNOWN provenance" in ds[0].message
        assert "chain" in ds[0].message  # the chain is printed

    def test_unmarked_gate_on_block_table_write_is_error(self):
        main = _write_fixture(mark_idx="block_table",
                              gate_mark=False)
        ds = [d for d in _diags(main, "PTA190")
              if "lane-active" in d.message]
        assert ds and ds[0].severity == ERROR

    def test_read_with_unknown_index_is_error(self):
        main, startup, g = _guarded()
        with g:
            blk = main.global_block
            pool = _mk_pool(blk)
            idx = layers.data("ridx", shape=[6], dtype="int32",
                              append_batch_size=False)
            flat = layers.reshape(pool, [32, 16])
            layers.gather(flat, idx)
        ds = _diags(main, "PTA190")
        assert ds and ds[0].severity == ERROR
        assert "read" in ds[0].message

    def test_bound_exceeding_axis_is_error(self):
        main, startup, g = _guarded()
        with g:
            blk = main.global_block
            pool = _mk_pool(blk)           # 8*4 = 32 cells
            idx = layers.data("ridx", shape=[6], dtype="int32",
                              append_batch_size=False)
            # the host invariant claims entries < 64: provably too
            # big for the 32-cell flattened view
            absint.mark_pool_index_source(idx, "block_table",
                                          bound=64)
            flat = layers.reshape(pool, [32, 16])
            layers.gather(flat, idx)
        ds = [d for d in _diags(main, "PTA190")
              if "exceeds" in d.message]
        assert ds and ds[0].severity == ERROR

    def test_unbounded_read_warns(self):
        main, startup, g = _guarded()
        with g:
            blk = main.global_block
            pool = _mk_pool(blk)
            idx = layers.data("ridx", shape=[6], dtype="int32",
                              append_batch_size=False)
            absint.mark_pool_index_source(idx, "block_table")
            flat = layers.reshape(pool, [32, 16])
            layers.gather(flat, idx)
        ds = [d for d in _diags(main, "PTA190")
              if "unprovable" in d.message]
        assert ds and ds[0].severity == WARNING

    def test_proven_chain_is_clean(self):
        main, startup, g = _guarded()
        with g:
            blk = main.global_block
            pool = _mk_pool(blk)
            tab = _mk_state(blk, "@own/block_tab", (3, 2))
            act = _mk_state(blk, "@own/active", (3,), "int64")
            absint.mark_pool_index_source(tab, "block_table", bound=8)
            absint.mark_pool_index_source(act, "lane_active")
            write_idx, gate = _block_table_chain(tab, act)
            new = layers.data("new", shape=[3, 2, 8],
                              dtype="float32",
                              append_batch_size=False)
            layers.masked_pool_write(pool, new, write_idx, gate=gate,
                                     leading_dims=2,
                                     exclusive_via="block_table")
        for code in ("PTA190", "PTA191", "PTA192", "PTA110"):
            assert not _diags(main, code), code


def _paged_read_fixture(mark, bound):
    """``paged_decode_attention`` over two @POOL vars of 8 blocks of 4
    cells, its table marked (or not) the way a builder would."""
    main, startup, g = _guarded()
    with g:
        blk = main.global_block
        pk = _mk_pool(blk, "@own/self_k0@POOL", (32, 16))
        pv = _mk_pool(blk, "@own/self_v0@POOL", (32, 16))
        tab = _mk_state(blk, "@own/block_tab", (3, 2))
        pos = _mk_state(blk, "@own/step", (3,), "int64")
        if mark:
            absint.mark_pool_index_source(tab, mark, bound=bound)
        q = layers.data("q", shape=[3, 1, 16], dtype="float32",
                        append_batch_size=False)
        layers.paged_decode_attention(q, pk, pv, tab, pos,
                                      block_size=4, n_heads=2)
    return main


class TestPTA190UncheckedRead:
    """The paged attention read copies the blocks its table names with
    no clamp and no fill: PTA190's proof is what licenses that, so
    every way of not having the proof is an error at build time."""

    @pytest.mark.parametrize("mark,bound,needle", [
        (None, None, "UNKNOWN provenance"),
        ("block_table", None, "unprovable"),
        ("block_table", 9, "exceeds"),
    ], ids=["unknown_provenance", "no_bound", "bound_past_the_pool"])
    def test_unproven_table_is_error(self, mark, bound, needle):
        ds = [d for d in _diags(_paged_read_fixture(mark, bound),
                                "PTA190") if needle in d.message]
        # one finding a pool: the table addresses both
        assert len(ds) == 2 and {d.severity for d in ds} == {ERROR}
        assert {d.var for d in ds} == {"@own/self_k0@POOL",
                                       "@own/self_v0@POOL"}

    def test_proven_table_is_clean_and_counted(self):
        main = _paged_read_fixture("block_table", 8)
        for code in ("PTA190", "PTA191", "PTA192"):
            assert not _diags(main, code), code
        reads = [a for a in absint.analyze(main).pool_accesses
                 if a.kind == "read"]
        assert [(a.index_var, a.axis_size, a.unchecked)
                for a in reads] == [("@own/block_tab", 8, True)] * 2
        ledger = absint.analyze(main).ownership_ledger()
        assert ledger["proven_reads"] == 2 and ledger["unproven"] == 0

    def test_pool_slot_is_judged_whatever_the_var_is_called(self):
        """A var without the @POOL mark wired to a pool slot is still
        read unchecked, so the proof is still owed."""
        main, startup, g = _guarded()
        with g:
            blk = main.global_block
            pk = _mk_state(blk, "plain_k", (32, 16), "float32")
            pv = _mk_state(blk, "plain_v", (32, 16), "float32")
            tab = layers.data("tab", shape=[3, 2], dtype="int32",
                              append_batch_size=False)
            pos = layers.data("pos", shape=[3], dtype="int64",
                              append_batch_size=False)
            q = layers.data("q", shape=[3, 1, 16], dtype="float32",
                            append_batch_size=False)
            layers.paged_decode_attention(q, pk, pv, tab, pos,
                                          block_size=4, n_heads=2)
        ds = _diags(main, "PTA190")
        assert len(ds) == 2 and all(d.severity == ERROR for d in ds)


def _prompt_read_fixture(bound, entries=4):
    """The paged tick's cross-attention read as the engine wires it
    (``decode_engine._PagedPromptCross``): a prompt table of
    ``entries`` entries of 4 rows, the dustbin among them, behind a
    ``prompt_ref`` marked with ``bound``."""
    from paddle_tpu.models.decode_engine import _PagedPromptCross

    main, startup, g = _guarded()
    with g:
        blk = main.global_block
        tk = _mk_pool(blk, "@own/cross_k0@POOL", (entries, 4, 16))
        tv = _mk_pool(blk, "@own/cross_v0@POOL", (entries, 4, 16))
        pref = _mk_state(blk, "@own/prompt_ref", (3,))
        absint.mark_pool_index_source(pref, "prompt_entry_ref",
                                      bound=bound)
        q = layers.data("q", shape=[3, 1, 16], dtype="float32",
                        append_batch_size=False)
        last = layers.fill_constant([3], "int32", 3.0)
        _PagedPromptCross(tk, tv, layers.reshape(pref, [3, 1]),
                          last).attend(q, 2, 0.5)
    return main


class TestPTA190PromptTableRead:
    """The cross-attention read of a paged tick is the same unchecked
    read: PTA190 proves it from ``prompt_ref``'s mark
    (``prompt_entry_ref``, bound E + 1) against the table's E + 1
    blocks of ``seq_len`` rows, through the reshapes on the way."""

    def test_proven_from_prompt_entry_ref(self):
        main = _prompt_read_fixture(bound=4)
        for code in ("PTA190", "PTA191", "PTA192"):
            assert not _diags(main, code), code
        facts = absint.analyze(main)
        reads = [a for a in facts.pool_accesses if a.kind == "read"]
        assert [(a.pool, a.axis_size, a.unchecked) for a in reads] == [
            ("@own/cross_k0@POOL", 4, True),
            ("@own/cross_v0@POOL", 4, True)]
        for a in reads:
            assert a.index_fact.tags == ("prompt_entry_ref",)
            assert a.index_fact.bound == 4
        ledger = facts.ownership_ledger()
        assert ledger["proven_reads"] == 2 and ledger["unproven"] == 0

    @pytest.mark.parametrize("bound,entries,needle", [
        (5, 4, "exceeds"), (None, 4, "unprovable"), (4, 3, "exceeds"),
    ], ids=["bound_past_the_table", "no_bound", "table_one_short"])
    def test_bound_past_the_table_is_error(self, bound, entries,
                                           needle):
        ds = [d for d in _diags(_prompt_read_fixture(bound, entries),
                                "PTA190") if needle in d.message]
        assert len(ds) == 2 and {d.severity for d in ds} == {ERROR}
        assert {d.var for d in ds} == {"@own/cross_k0@POOL",
                                       "@own/cross_v0@POOL"}


class TestProvenanceSoundness:
    """Regression pins for the review-found holes in the bound/
    one-hot algebra: each was a way to certify a LYING bound (a
    silent in-bounds pass — the exact failure class the prover
    exists to kill)."""

    def test_negative_constant_mints_no_fact(self):
        main, startup, g = _guarded()
        with g:
            neg = layers.fill_constant([3], "float32", -4.0)
            offs = layers.assign(np.array([-1.0, 2.0], "float32"))
        facts = absint.analyze(main)
        assert facts.prov_of(neg.name) is None
        assert facts.prov_of(offs.name) is None

    def test_sub_with_unsigned_subtrahend_drops_bound(self):
        # idx = tab - (a - b): (a - b) can be negative, so idx can
        # EXCEED tab's bound — the fact must not keep it
        main, startup, g = _guarded()
        with g:
            blk = main.global_block
            tab = _mk_state(blk, "@own/block_tab", (3,))
            absint.mark_pool_index_source(tab, "block_table", bound=8)
            a = layers.fill_constant([3], "float32", 2.0)
            b = layers.fill_constant([3], "float32", 5.0)
            maybe_neg = layers.elementwise_sub(a, b)
            idx = layers.elementwise_sub(layers.cast(tab, "float32"),
                                         maybe_neg)
        facts = absint.analyze(main)
        mn = facts.prov_of(maybe_neg.name)
        assert mn is not None and not mn.nonneg
        f = facts.prov_of(idx.name)
        assert f is not None and f.bound is None
        # the plain tab - const case keeps the bound (const >= 0)
        with fluid.program_guard(main):
            ok = layers.elementwise_sub(layers.cast(tab, "float32"),
                                        layers.fill_constant(
                                            [3], "float32", 1.0))
        f2 = absint.analyze(main).prov_of(ok.name)
        assert f2 is not None and f2.bound == 8

    def test_equal_same_shape_vector_is_not_onehot(self):
        # equal(range(N), ids[N]) can match EVERY position — only a
        # broadcast scalar-per-row comparison mints a one-hot
        main, startup, g = _guarded()
        with g:
            ids = layers.data("ids", shape=[8], dtype="int64",
                              append_batch_size=False)
            rng = layers.cast(layers.range(0, 8, 1), "int64")
            multi = layers.equal(rng, ids)
            scalar = layers.equal(rng, layers.reshape(
                layers.data("s", shape=[1], dtype="int64",
                            append_batch_size=False), [1, 1]))
        facts = absint.analyze(main)
        assert not facts.prov_of(multi.name).onehot
        assert facts.prov_of(scalar.name).onehot

    def test_row_reduce_drops_onehot(self):
        # the admission-mask shape: reduce_sum over axis 0 of an
        # [A, rows] one-hot COUNTS (up to A), it does not select
        main, startup, g = _guarded()
        with g:
            slots = layers.data("slots", shape=[4], dtype="int64",
                                append_batch_size=False)
            lane_range = layers.cast(layers.range(0, 6, 1), "int64")
            oh = layers.cast(layers.equal(
                lane_range, layers.reshape(slots, [4, 1])),
                "float32")
            counts = layers.reduce_sum(oh, dim=0)      # across rows
            per_row = layers.reduce_sum(
                layers.reshape(oh, [4, 2, 3]), dim=2)  # trailing
        facts = absint.analyze(main)
        assert facts.prov_of(oh.name).onehot
        cf = facts.prov_of(counts.name)
        assert cf is None or not (cf.onehot or cf.indicator)
        assert facts.prov_of(per_row.name).onehot

    def test_inverted_gate_is_rejected(self):
        # gate = 1 - active (a keep/write-mask mixup): the complement
        # is the IDLE mask — it must not inherit the lane_active tag,
        # or idle lanes write while active lanes freeze, proven-green
        main, startup, g = _guarded()
        with g:
            blk = main.global_block
            pool = _mk_pool(blk)
            idx = layers.data("idx", shape=[3], dtype="int32",
                              append_batch_size=False)
            absint.mark_pool_index_source(idx, "block_table",
                                          bound=32)
            act = _mk_state(blk, "@own/active", (3,), "int64")
            absint.mark_pool_index_source(act, "lane_active")
            inv = layers.elementwise_sub(
                layers.fill_constant([3], "float32", 1.0),
                layers.cast(act, "float32"))
            new = layers.data("new", shape=[3, 2, 8],
                              dtype="float32",
                              append_batch_size=False)
            layers.masked_pool_write(pool, new, idx, gate=inv,
                                     leading_dims=2,
                                     exclusive_via="block_table")
        ds = [d for d in _diags(main, "PTA190")
              if "lane-active" in d.message]
        assert ds and ds[0].severity == ERROR

    def test_row_merging_reshape_drops_onehot(self):
        # reshape folding the row axis INTO the block piles A
        # nonzeros into one block; only last-axis refactors keep it
        main, startup, g = _guarded()
        with g:
            slots = layers.data("slots", shape=[4], dtype="int64",
                                append_batch_size=False)
            lane_range = layers.cast(layers.range(0, 6, 1), "int64")
            oh = layers.cast(layers.equal(
                lane_range, layers.reshape(slots, [4, 1])),
                "float32")                         # [4, 6] one-hot
            merged = layers.reshape(oh, [24])      # rows folded in
            split = layers.reshape(oh, [4, 2, 3])  # block refactor
        facts = absint.analyze(main)
        assert not facts.prov_of(merged.name).onehot
        sf = facts.prov_of(split.name)
        assert sf.onehot and sf.oh_tail == 2

    def test_concat_of_onehots_is_not_onehot(self):
        main, startup, g = _guarded()
        with g:
            slots = layers.data("slots", shape=[4], dtype="int64",
                                append_batch_size=False)
            lane_range = layers.cast(layers.range(0, 6, 1), "int64")
            oh = layers.cast(layers.equal(
                lane_range, layers.reshape(slots, [4, 1])),
                "float32")
            both = layers.concat([oh, oh], axis=1)  # 2 nonzeros/row
        facts = absint.analyze(main)
        f = facts.prov_of(both.name)
        assert f is not None and not f.onehot and f.indicator

    def test_row_reduce_max_drops_onehot(self):
        # reduce_max over the row axis of a per-row one-hot is an
        # ANY-mask (up to A nonzeros), not a one-hot
        main, startup, g = _guarded()
        with g:
            slots = layers.data("slots", shape=[4], dtype="int64",
                                append_batch_size=False)
            lane_range = layers.cast(layers.range(0, 6, 1), "int64")
            oh = layers.cast(layers.equal(
                lane_range, layers.reshape(slots, [4, 1])),
                "float32")
            anymask = layers.reduce_max(oh, dim=0)
        facts = absint.analyze(main)
        f = facts.prov_of(anymask.name)
        assert f is not None and not f.onehot and f.indicator

    def test_transpose_drops_onehot(self):
        main, startup, g = _guarded()
        with g:
            slots = layers.data("slots", shape=[4], dtype="int64",
                                append_batch_size=False)
            lane_range = layers.cast(layers.range(0, 6, 1), "int64")
            oh = layers.cast(layers.equal(
                lane_range, layers.reshape(slots, [4, 1])),
                "float32")
            ohT = layers.transpose(oh, perm=[1, 0])
        facts = absint.analyze(main)
        f = facts.prov_of(ohT.name)
        assert f is not None and not f.onehot and f.indicator

    def test_rmw_counter_converges_via_widening(self):
        # a const-seeded counter RMW-bumped in a While used to grow
        # its bound by 1 per fixpoint iteration (an infinite
        # ascending chain): non-convergence silently disabled the
        # whole prover. The widening step jumps a re-grown bound to
        # unbounded, so the fixpoint terminates and the pool proofs
        # elsewhere in the program survive.
        main, startup, g = _guarded()
        with g:
            blk = main.global_block
            pool = _mk_pool(blk)
            tab = _mk_state(blk, "@own/block_tab", (3,))
            act = _mk_state(blk, "@own/active", (3,), "int64")
            absint.mark_pool_index_source(tab, "block_table", bound=8)
            absint.mark_pool_index_source(act, "lane_active")
            cnt = layers.fill_constant([1], "int64", 0)
            cond = layers.less_than(
                cnt, layers.fill_constant([1], "int64", 4.0))
            w = layers.While(cond)
            with w.block():
                one = layers.fill_constant([1], "int64", 1.0)
                layers.assign(layers.elementwise_add(cnt, one),
                              output=cnt)
                new = layers.fill_constant([3, 2, 8], "float32",
                                           0.0)
                idx = layers.cast(tab, "int32")
                gate = layers.cast(act, "float32")
                layers.masked_pool_write(
                    pool, new, idx, gate=gate, leading_dims=2,
                    exclusive_via="block_table")
                layers.less_than(
                    cnt, layers.fill_constant([1], "int64", 4.0),
                    cond=cond)
        facts = absint.analyze(main)
        assert facts.converged, facts.iterations
        cf = facts.prov_of(cnt.name)
        assert cf is not None and cf.bound is None  # widened
        # the in-loop pool write still PROVES
        writes = [a for a in facts.pool_accesses
                  if a.kind == "write"]
        assert writes and writes[0].index_fact.tags == \
            ("block_table",)
        for code in ("PTA190", "PTA191", "PTA192"):
            assert not _diags(main, code), code

    def test_ungated_write_is_one_incident_one_diagnostic(self):
        # no Gate input at all: PTA191 owns it; PTA190's gate check
        # only judges a gate that EXISTS (no double report)
        main, startup, g = _guarded()
        with g:
            blk = main.global_block
            pool = _mk_pool(blk)
            idx = layers.data("idx", shape=[3], dtype="int32",
                              append_batch_size=False)
            absint.mark_pool_index_source(idx, "block_table",
                                          bound=32)
            new = layers.data("new", shape=[3, 2, 8],
                              dtype="float32",
                              append_batch_size=False)
            blk.append_op(
                "masked_pool_write",
                {"Pool": [pool.name], "New": [new.name],
                 "Index": [idx.name]},
                {"Out": [pool.name]},
                {"leading_dims": 2, "exclusive_via": "block_table"})
        p190 = [d for d in _diags(main, "PTA190")
                if "gated" in d.message]
        p191 = [d for d in _diags(main, "PTA191")
                if "Gate" in d.message]
        assert len(p191) == 1 and len(p190) == 0

    def test_slice_of_pool_is_still_a_judged_read(self):
        # a pool read routed through slice must NOT escape PTA190
        main, startup, g = _guarded()
        with g:
            blk = main.global_block
            pool = _mk_pool(blk)
            idx = layers.data("ridx", shape=[4], dtype="int32",
                              append_batch_size=False)
            flat = layers.reshape(pool, [32, 16])
            part = layers.slice(flat, axes=[0], starts=[0],
                                ends=[16])
            layers.gather(part, idx)
        ds = _diags(main, "PTA190")
        assert ds and ds[0].severity == ERROR


class TestPTA191:
    def test_direct_write_is_error(self):
        main, startup, g = _guarded()
        with g:
            pool = _mk_pool(main.global_block)
            zeros = layers.fill_constant([8, 4, 2, 8], "float32",
                                         0.0)
            layers.assign(zeros, output=pool)
        ds = _diags(main, "PTA191")
        assert ds and ds[0].severity == ERROR
        assert "directly" in ds[0].message

    def test_via_mismatch_names_the_assumption(self):
        # the builder DECLARES per-lane block-table exclusivity but
        # wires host-admission indices: the declaration names an
        # invariant nobody maintains for these indices
        main = _write_fixture(mark_idx="host_indices",
                              via="block_table", idx_bound=32)
        ds = [d for d in _diags(main, "PTA191")
              if "declares exclusive_via" in d.message]
        assert ds and ds[0].severity == ERROR
        assert "PromptPrefixCache.fresh-exclusive" in ds[0].message

    def test_mixed_exclusive_families_is_error(self):
        main, startup, g = _guarded()
        with g:
            blk = main.global_block
            pool = _mk_pool(blk)
            a = layers.data("ia", shape=[3], dtype="int32",
                            append_batch_size=False)
            b = layers.data("ib", shape=[3], dtype="int32",
                            append_batch_size=False)
            gate = layers.data("gate", shape=[3], dtype="float32",
                               append_batch_size=False)
            absint.mark_pool_index_source(a, "block_table", bound=8)
            absint.mark_pool_index_source(b, "host_indices",
                                          bound=4)
            absint.mark_pool_index_source(gate, "lane_active")
            mixed = layers.elementwise_add(a, b)
            new = layers.data("new", shape=[3, 2, 8],
                              dtype="float32",
                              append_batch_size=False)
            layers.masked_pool_write(pool, new, mixed, gate=gate,
                                     leading_dims=2,
                                     exclusive_via="block_table")
        ds = [d for d in _diags(main, "PTA191")
              if "mixes exclusive" in d.message]
        assert ds and ds[0].severity == ERROR

    def test_pta110_twin_dedupe_and_fallback(self, monkeypatch):
        main, startup, g = _guarded()
        with g:
            pool = _mk_pool(main.global_block)
            zeros = layers.fill_constant([8, 4, 2, 8], "float32",
                                         0.0)
            layers.assign(zeros, output=pool)
        # covered site: the defect surfaces as PTA191, PTA110 silent
        assert _diags(main, "PTA191")
        assert not _diags(main, "PTA110")
        # prover unavailable (non-convergence/crash): the PTA110
        # declaration checker is the fallback and still fires
        monkeypatch.setattr(checkers, "_ownership_coverage",
                            lambda program: None)
        ds = list(checkers.check_shared_pool_writes(main))
        assert ds and ds[0].code == "PTA110" and \
            ds[0].severity == ERROR


class TestPTA192:
    def test_write_while_shared_is_error(self):
        # a write through the REFCOUNTED prompt-entry refs: the
        # exact COW violation the radix/beam prefix work must not
        # ship — writes are only legal in the exclusive typestate
        main = _write_fixture(mark_idx="prompt_entry_ref",
                              via="host_indices", gate_mark=False,
                              idx_bound=32)
        ds = _diags(main, "PTA192")
        assert ds and ds[0].severity == ERROR
        assert "exclusive typestate" in ds[0].message
        assert "prompt_entry_ref" in ds[0].message

    def test_fresh_entry_write_is_clean(self):
        # the COW-correct path: host-fed FRESH entries (refcount==1)
        main = _write_fixture(mark_idx="host_indices",
                              via="host_indices", gate_mark=False,
                              idx_bound=32)
        assert not _diags(main, "PTA192")
        assert not _diags(main, "PTA191")

    def test_shared_read_is_legal(self):
        main, startup, g = _guarded()
        with g:
            blk = main.global_block
            pool = _mk_pool(blk, name="@own/cross_k0@POOL",
                            shape=(4, 2, 8, 8))
            pref = _mk_state(blk, "@own/prompt_ref", (3,))
            absint.mark_pool_index_source(pref, "prompt_entry_ref",
                                          bound=4)
            flat = layers.reshape(pool, [4, 2 * 8 * 8])
            layers.gather(flat, pref)
        assert not _diags(main, "PTA192")
        assert not _diags(main, "PTA190")


def _unmark(var):
    """Take `var`'s ownership mark away (the variable's and its
    producer's), as a builder that forgot it would have left it."""
    del var._ownership_tag, var._ownership_bound
    for blk in var.block.program.blocks:
        for op in blk.ops:
            if var.name in op.output_arg_names:
                op.attrs.pop(absint.OWNERSHIP_ATTR, None)
                op.attrs.pop(absint.OWNERSHIP_BOUND_ATTR, None)
    var.block.program._version += 1


class TestMarksOnTheFedTables:
    """A serve program takes the scheduler's tables as feeds and copies
    them into its state (decode_engine.build_serve_program): the marks
    sit on the feeds, the read-only tables' state is proven THROUGH the
    copy, and every rule the marks feed still fires when one is
    missing or wrong."""

    NB, E = 4, 2

    def _serve(self, key=0):
        from paddle_tpu import unique_name
        from paddle_tpu.models import transformer as T
        from paddle_tpu.models.decode_engine import CacheConfig

        with unique_name.guard():
            bundle = T.build_decode_step_program(
                seq_len=8, max_out_len=8, d_model=32, n_heads=2,
                n_layers=1, d_inner=64, vocab=50, n_slots=2,
                state_prefix="@ownfed/",
                cache=CacheConfig(layout="paged", block_size=4,
                                  n_blocks=self.NB,
                                  n_prompt_entries=self.E))
        return bundle, bundle.serves[key]

    def _errors(self, prog):
        return [d for d in run_checks(prog)
                if d.code in ("PTA190", "PTA191", "PTA192")
                and d.severity == ERROR]

    def test_the_feeds_carry_the_marks_and_every_access_is_proven(self):
        from paddle_tpu.models.decode_engine import fed_name

        bundle, prog = self._serve(("miss", 2))
        blk = prog.global_block
        for table, tag, bound in (
                ("block_tab", "block_table", self.NB),
                ("prompt_ref", "prompt_entry_ref", self.E + 1),
                ("active", "lane_active", None)):
            fed = blk.var(fed_name(table))
            assert not fed.persistable
            assert (fed._ownership_tag, fed._ownership_bound) \
                == (tag, bound)
        # the two read-only tables' state is no pinned source: its
        # provenance is derived through the copy from the feed
        facts = absint.analyze(prog)
        for table, tag in (("block_tab", "block_table"),
                           ("prompt_ref", "prompt_entry_ref")):
            var = blk.var(bundle.state[table])
            assert var.persistable
            assert getattr(var, "_ownership_tag", None) is None
            assert facts.prov_of(var.name).tags == (tag,)
        # the lane mask keeps its pin: the program rewrites it
        assert blk.var(bundle.state["active"])._ownership_tag \
            == "lane_active"
        assert facts.ownership_ledger()["unproven"] == 0
        assert not self._errors(prog)

    @pytest.mark.parametrize("table", ["block_tab", "prompt_ref"])
    def test_an_unmarked_fed_table_is_unknown_provenance(self, table):
        from paddle_tpu.models.decode_engine import fed_name

        _bundle, prog = self._serve()
        _unmark(prog.global_block.var(fed_name(table)))
        ds = [d for d in self._errors(prog) if d.code == "PTA190"]
        assert ds and any("UNKNOWN provenance" in d.message for d in ds)

    def test_the_fed_prompt_refs_bound_is_held_to_the_pool(self):
        from paddle_tpu.models.decode_engine import fed_name

        _bundle, prog = self._serve()
        fed = prog.global_block.var(fed_name("prompt_ref"))
        absint.mark_pool_index_source(fed, "prompt_entry_ref",
                                      bound=self.E + 2)
        assert [d for d in self._errors(prog) if d.code == "PTA190"]

    def test_an_unmarked_lane_mask_gates_nothing(self):
        from paddle_tpu.models.decode_engine import fed_name

        bundle, prog = self._serve()
        blk = prog.global_block
        _unmark(blk.var(fed_name("active")))
        _unmark(blk.var(bundle.state["active"]))
        ds = [d for d in self._errors(prog) if "lane-active" in d.message]
        assert ds and ds[0].code == "PTA190"

    def test_a_write_through_the_fed_prompt_refs_is_write_while_shared(
            self):
        """The refcounted tag survives the copy into the state
        variable: wired as a write index it is the COW violation."""
        main, startup, g = _guarded()
        with g:
            blk = main.global_block
            pool = _mk_pool(blk)
            new = layers.data("new", shape=[3, 2, 8], dtype="float32",
                              append_batch_size=False)
            fed = layers.data("fed_prompt_ref", shape=[3], dtype="int32",
                              append_batch_size=False)
            absint.mark_pool_index_source(fed, "prompt_entry_ref",
                                          bound=8)
            pref = _mk_state(blk, "@own/prompt_ref", (3,))
            layers.assign(fed, output=pref)
            layers.masked_pool_write(pool, new, pref, leading_dims=2,
                                     exclusive_via="host_indices")
        ds = _diags(main, "PTA192")
        assert ds and ds[0].severity == ERROR
        assert "prompt_entry_ref" in ds[0].message

    def test_the_burst_exit_is_still_the_divergence_source(self):
        _bundle, prog = self._serve()
        marked = [op for blk in prog.blocks for op in blk.ops
                  if op.attrs.get(absint.DIVERGENCE_ATTR)
                  == "lane_active_mask"]
        assert len(marked) == 2         # the cond before and inside


class TestLedgerAndBaseline:
    def _paged_bundle(self):
        from paddle_tpu.models import transformer as T
        from paddle_tpu.models.decode_engine import CacheConfig

        return T.build_decode_step_program(
            seq_len=8, max_out_len=8, d_model=32, n_heads=2,
            n_layers=1, d_inner=64, vocab=50, n_slots=2,
            state_prefix="@ownled/",
            cache=CacheConfig(layout="paged", block_size=4,
                              n_blocks=4, n_prompt_entries=2))

    def test_ledger_names_assumptions_on_shipped_programs(self):
        bundle = self._paged_bundle()
        facts = absint.analyze(bundle.step)
        led = facts.ownership_ledger()
        assert led["unproven"] == 0
        assert led["proven_writes"] >= 2      # self k/v pools
        assert "HostBlockPool.alloc-disjoint" in led["assumptions"]
        assert led["obligations"].get("gate=lane_active", 0) >= 2
        miss = bundle.serves[("miss", 2)]
        led2 = absint.analyze(miss).ownership_ledger()
        assert "PromptPrefixCache.fresh-exclusive" in \
            led2["assumptions"]

    def test_stable_ownership_facts_and_baseline_drift(self):
        bundle = self._paged_bundle()
        facts = absint.analyze(bundle.step)
        stable = facts.stable_ownership_facts()
        pools = [k for k in stable if "@POOL" in k]
        assert pools and "@assumptions" in stable
        assert any("⊢HostBlockPool.alloc-disjoint" in v
                   for v in stable.values())
        # baseline payload carries the section; a drifted fact fails
        # the gate until a reviewed refresh
        from paddle_tpu.analysis.baseline import (
            TargetReport, diff_against_baseline)

        rep = TargetReport("own:step")
        rep.ownership = dict(stable)
        payload = baseline_payload([rep])
        assert payload["version"] == 4  # liveness_facts joined in PR 18
        key = f"own:step|{pools[0]}"
        assert key in payload["ownership_facts"]
        base = {"ownership_facts":
                {**payload["ownership_facts"],
                 key: "writes[somewhere-else]"}}
        new, _res = diff_against_baseline([rep], base)
        assert any("ownership drift" in n for n in new)

    def test_version_bump_invalidates_cached_facts(self):
        main, startup, g = _guarded()
        with g:
            blk = main.global_block
            tab = _mk_state(blk, "@own/block_tab", (3, 2))
            idx = layers.cast(tab, "int32")
        facts0 = absint.analyze(main)
        assert facts0.prov_of(idx.name) is None
        absint.mark_pool_index_source(tab, "block_table", bound=8)
        facts1 = absint.analyze(main)
        f = facts1.prov_of(idx.name)
        assert f is not None and f.tags == ("block_table",)
