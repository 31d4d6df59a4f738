"""Sharding domain tests (absint ShardSpec propagation + the PTA160/
PTA161 provers + the tp-sharded decoder fixture).

The property suite pins each registered rule family against WHAT XLA
ACTUALLY DOES: the same computation runs under jax.jit on the virtual
8-device mesh with NamedSharding inputs, and the rule's propagated
output spec must equal the sharding GSPMD chose for the real output
(conftest.py provides the 4x2 dp/tp mesh). That keeps the static
algebra honest — a rule drifting from GSPMD's behavior fails here,
not in a wrong memory plan or a missed deadlock.
"""
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.analysis import ERROR, WARNING, absint, run_checks
from paddle_tpu.analysis.absint import (MeshConfig, REPLICATED_SPEC,
                                        ShardSpec, TOP_SPEC)


def _diags(program, code):
    return [d for d in run_checks(program) if d.code == code]


def _guarded():
    main, startup = fluid.Program(), fluid.Program()
    return main, startup, fluid.program_guard(main, startup)


MESH = MeshConfig.make(dp=4, tp=2)


def _data(name, shape, placements=None, dtype="float32"):
    v = layers.data(name, shape=list(shape), dtype=dtype,
                    append_batch_size=False)
    if placements:
        absint.mark_sharded(v, placements)
    return v


def _spec_to_pspec(spec, rank):
    """ShardSpec -> jax PartitionSpec-equivalent tuple of axis names."""
    return tuple(spec.axis_of(d) for d in range(rank))


def _jax_out_pspec(fn, in_arrays, in_pspecs, out_rank):
    """What GSPMD actually picks for fn's output under these input
    shardings, padded to out_rank."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2),
                ("dp", "tp"))
    put = [jax.device_put(a, NamedSharding(mesh, PartitionSpec(*p)))
           for a, p in zip(in_arrays, in_pspecs)]
    out = jax.jit(fn)(*put)
    got = tuple(out.sharding.spec)
    return got + (None,) * (out_rank - len(got))


# ---------------------------------------------------------------------------
# spec / mesh primitives
# ---------------------------------------------------------------------------
class TestSpecPrimitives:
    def test_spec_normalization_and_describe(self):
        s = ShardSpec.of({1: "tp", 0: "dp"})
        assert s.placements == ((0, "dp"), (1, "tp"))
        assert s.describe() == "dim0:dp,dim1:tp"
        assert REPLICATED_SPEC.is_replicated
        assert TOP_SPEC.is_top and TOP_SPEC.describe() == "⊤"

    def test_spec_join(self):
        a = ShardSpec.of({0: "dp"})
        assert absint.spec_join(a, a) == a
        assert absint.spec_join(a, REPLICATED_SPEC).is_top
        assert absint.spec_join(a, TOP_SPEC).is_top

    def test_mesh_config(self):
        assert MESH.size("tp") == 2
        assert MESH.size("nope") == 1
        assert MESH.n_devices() == 8
        assert MESH.describe() == "dp=4xtp=2"

    def test_set_mesh_bumps_version(self):
        p = fluid.Program()
        v0 = p._version
        absint.set_mesh(p, MESH)
        assert p._version > v0
        assert absint.mesh_of(p) == MESH

    def test_clone_carries_mesh_and_budget(self):
        # Program.clone keeps the analysis-layer program attrs, like
        # it keeps var annotations and op _uids: an eval/serving
        # clone must not silently lose its mesh (per-device plans)
        # or its OOM-gate budget
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 16), {1: "tp"})
            layers.fc(x, size=4)
        absint.set_mesh(main, MESH)
        absint.set_device_memory_budget(main, 12345)
        clone = main.clone(for_test=True)
        assert absint.mesh_of(clone) == MESH
        assert absint.device_memory_budget(clone) == 12345


# ---------------------------------------------------------------------------
# mark_sharded: dict placements, legacy axes, producer-less vars
# ---------------------------------------------------------------------------
class TestMarkSharded:
    def test_producerless_data_var_seeds_spec(self):
        # the sharded-serving ENTRY POINT: feeds have no producer op,
        # and the annotation must still seed both domains
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 16), {0: "dp"})
            h = layers.scale(x, 2.0)
        facts = absint.analyze(main)
        assert facts.spec(x.name) == ShardSpec.of({0: "dp"})
        assert facts.value(x.name).repl == absint.VARYING
        # and it propagates
        assert facts.spec(h.name) == ShardSpec.of({0: "dp"})

    def test_producerless_parameter_seeds_spec(self):
        main, startup, g = _guarded()
        with g:
            w = main.global_block.create_parameter(
                name="tt_w", shape=[16, 8], dtype="float32")
            absint.mark_sharded(w, {1: "tp"})
            x = _data("x", (4, 16))
            main.global_block.append_op(
                "mul", {"X": [x.name], "Y": [w.name]},
                {"Out": ["o"]}, {"x_num_col_dims": 1,
                                 "y_num_col_dims": 1})
        facts = absint.analyze(main)
        assert facts.spec("o") == ShardSpec.of({1: "tp"})

    def test_legacy_axes_form_still_marks_varying(self):
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8,))
            h = layers.scale(x, 1.0)
            absint.mark_sharded(h, ("model",))
        facts = absint.analyze(main)
        assert facts.value(h.name).sharded == ("model",)
        # dims unknown: the spec domain pins the explicit ⊤
        assert facts.spec(h.name).is_top

    def test_negative_dim_resolves_against_rank(self):
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 16), {-1: "tp"})
        facts = absint.analyze(main)
        assert facts.spec(x.name) == ShardSpec.of({1: "tp"})

    def test_out_of_range_dim_refused(self):
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 16))
            with pytest.raises(ValueError, match="out of range"):
                absint.mark_sharded(x, {5: "tp"})

    def test_nameless_string_refused(self):
        with pytest.raises(ValueError, match="neither"):
            absint.mark_sharded("just_a_name", {0: "dp"})


# ---------------------------------------------------------------------------
# property tests: rule output == GSPMD's actual choice, per family
# ---------------------------------------------------------------------------
class TestRulesMatchGSPMD:
    """Each case builds the op through the REAL layer path, seeds
    input placements, and compares the propagated spec with the
    sharding jax.jit+GSPMD picks for the identical computation on the
    identical mesh."""

    def _propagated(self, main, out_var):
        absint.set_mesh(main, MESH)
        facts = absint.analyze(main)
        assert facts.converged
        return facts.spec(out_var.name)

    def test_elementwise_add(self):
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 16), {0: "dp"})
            y = _data("y", (8, 16))
            out = layers.elementwise_add(x, y)
        spec = self._propagated(main, out)
        want = _jax_out_pspec(
            lambda a, b: a + b,
            [np.zeros((8, 16), np.float32)] * 2,
            [("dp", None), (None, None)], 2)
        assert _spec_to_pspec(spec, 2) == want == ("dp", None)

    def test_transpose(self):
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 16), {0: "dp"})
            out = layers.transpose(x, perm=[1, 0])
        spec = self._propagated(main, out)
        want = _jax_out_pspec(
            lambda a: a.T, [np.zeros((8, 16), np.float32)],
            [("dp", None)], 2)
        assert _spec_to_pspec(spec, 2) == want == (None, "dp")

    def test_reduce_unsharded_dim_keeps_placement(self):
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 16), {0: "dp"})
            out = layers.reduce_sum(x, dim=1)
        spec = self._propagated(main, out)
        want = _jax_out_pspec(
            lambda a: a.sum(1), [np.zeros((8, 16), np.float32)],
            [("dp", None)], 1)
        assert _spec_to_pspec(spec, 1) == want == ("dp",)

    def test_reduce_sharded_dim_replicates_and_implies_psum(self):
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 16), {0: "dp"})
            out = layers.reduce_sum(x, dim=0)
        absint.set_mesh(main, MESH)
        facts = absint.analyze(main)
        spec = facts.spec(out.name)
        want = _jax_out_pspec(
            lambda a: a.sum(0), [np.zeros((8, 16), np.float32)],
            [("dp", None)], 1)
        assert _spec_to_pspec(spec, 1) == want == (None,)
        psums = [es for es in facts.collective_events
                 if es.event.kind == "psum"]
        assert psums and psums[0].event.axes == ("dp",)

    def test_matmul_batch_row_sharded(self):
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 16), {0: "dp"})
            w = _data("w", (16, 4))
            out = layers.matmul(x, w)
        spec = self._propagated(main, out)
        want = _jax_out_pspec(
            lambda a, b: a @ b,
            [np.zeros((8, 16), np.float32),
             np.zeros((16, 4), np.float32)],
            [("dp", None), (None, None)], 2)
        assert _spec_to_pspec(spec, 2) == want == ("dp", None)

    def test_matmul_contraction_sharded_row_parallel(self):
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 16), {1: "tp"})
            w = _data("w", (16, 4), {0: "tp"})
            out = layers.matmul(x, w)
        absint.set_mesh(main, MESH)
        facts = absint.analyze(main)
        spec = facts.spec(out.name)
        want = _jax_out_pspec(
            lambda a, b: a @ b,
            [np.zeros((8, 16), np.float32),
             np.zeros((16, 4), np.float32)],
            [(None, "tp"), ("tp", None)], 2)
        assert _spec_to_pspec(spec, 2) == want == (None, None)
        psums = [es for es in facts.collective_events
                 if es.event.kind == "psum"]
        assert psums and psums[0].event.axes == ("tp",)

    def test_matmul_column_parallel(self):
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 16))
            w = _data("w", (16, 4), {1: "tp"})
            out = layers.matmul(x, w)
        spec = self._propagated(main, out)
        want = _jax_out_pspec(
            lambda a, b: a @ b,
            [np.zeros((8, 16), np.float32),
             np.zeros((16, 4), np.float32)],
            [(None, None), (None, "tp")], 2)
        assert _spec_to_pspec(spec, 2) == want == (None, "tp")

    def test_reshape_split_carries_major_dim(self):
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 16), {1: "tp"})
            out = layers.reshape(x, [8, 4, 4])
        spec = self._propagated(main, out)
        want = _jax_out_pspec(
            lambda a: a.reshape(8, 4, 4),
            [np.zeros((8, 16), np.float32)], [(None, "tp")], 3)
        assert _spec_to_pspec(spec, 3) == want == (None, "tp", None)

    def test_reshape_merge_carries_major_dim(self):
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 4, 4), {1: "tp"})
            out = layers.reshape(x, [8, 16])
        spec = self._propagated(main, out)
        want = _jax_out_pspec(
            lambda a: a.reshape(8, 16),
            [np.zeros((8, 4, 4), np.float32)], [(None, "tp", None)],
            2)
        assert _spec_to_pspec(spec, 2) == want == (None, "tp")

    def test_softmax_keeps_layout(self):
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 16), {1: "tp"})
            out = layers.softmax(x, axis=-1)
        spec = self._propagated(main, out)
        import jax

        want = _jax_out_pspec(
            lambda a: jax.nn.softmax(a, -1),
            [np.zeros((8, 16), np.float32)], [(None, "tp")], 2)
        assert _spec_to_pspec(spec, 2) == want == (None, "tp")

    def test_argmax_over_sharded_dim_replicates(self):
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 16), {1: "tp"})
            out = layers.argmax(x, axis=-1)
        absint.set_mesh(main, MESH)
        facts = absint.analyze(main)
        import jax.numpy as jnp

        want = _jax_out_pspec(
            lambda a: jnp.argmax(a, -1),
            [np.zeros((8, 16), np.float32)], [(None, "tp")], 1)
        assert _spec_to_pspec(facts.spec(out.name), 1) == want \
            == (None,)
        gathers = [es for es in facts.collective_events
                   if es.event.kind == "allgather"]
        assert gathers and gathers[0].event.axes == ("tp",)

    def test_squeeze_shifts_placement_down(self):
        # the [B,1,D] {2:tp} -> squeeze axes=[1] case: the placement
        # legitimately lands ON the squeezed position after the
        # shift and must survive (regression: an over-eager filter
        # dropped it to replicated)
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 1, 16), {2: "tp"})
            out = layers.squeeze(x, axes=[1])
        absint.set_mesh(main, MESH)
        facts = absint.analyze(main)
        import jax.numpy as jnp

        want = _jax_out_pspec(
            lambda a: jnp.squeeze(a, 1),
            [np.zeros((8, 1, 16), np.float32)],
            [(None, None, "tp")], 2)
        assert _spec_to_pspec(facts.spec(out.name), 2) == want \
            == (None, "tp")

    def test_squeeze_of_sharded_dim_degrades_to_top(self):
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 1, 16), {1: "tp"})
            out = layers.squeeze(x, axes=[1])
        absint.set_mesh(main, MESH)
        assert absint.analyze(main).spec(out.name).is_top

    def test_unknown_op_degrades_to_top_and_warns_once(self):
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 16), {0: "dp"})
            main.global_block.append_op(
                "_no_rule_op_xyz", {"X": [x.name]}, {"Out": ["o"]}, {})
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            facts = absint.analyze(main)
        assert facts.spec("o").is_top
        msgs = [w for w in caught
                if "no registered sharding rule" in str(w.message)]
        assert msgs and "_no_rule_op_xyz" in str(msgs[0].message)

    def test_unknown_op_with_replicated_inputs_stays_replicated(self):
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 16))
            main.global_block.append_op(
                "_no_rule_op_xyz2", {"X": [x.name]}, {"Out": ["o"]},
                {})
        facts = absint.analyze(main)
        assert facts.spec("o").is_replicated


# ---------------------------------------------------------------------------
# head-interleaved fused qkv (r19 satellite): the exact decomposition
# chain cached_decoder_step builds, pinned against GSPMD
# ---------------------------------------------------------------------------
class TestInterleavedQKV:
    """The r17 leftover closed by ``qkv_interleaved``: with the fused
    qkv columns ``[H, 3, Dh]``-major, a dim-1 column shard on the
    weight must carry through matmul → reshape (major-carry onto the
    HEAD axis) → split on the local 3-axis → squeeze → transpose and
    land head-sharded, with zero reshard events.  The contiguous
    ``[3, H, Dh]``-major layout fails at the very first split (it
    slices ACROSS tp shard boundaries) — which is why it deliberately
    stays replicated (ShardingConfig docstring)."""

    R, D, H, DH = 8, 16, 4, 4  # 3D = 48, tp=2 divides H

    def _chain(self, interleaved):
        """Build cached_decoder_step's qkv decomposition through the
        real layer path; returns (main, out_var, facts)."""
        main, startup, g = _guarded()
        R, D, H, DH = self.R, self.D, self.H, self.DH
        with g:
            x = _data("x", (R, 1, D))
            w = _data("w", (D, 3 * D), {1: "tp"})
            qkv = layers.matmul(x, w)  # [R,1,3D]
            if interleaved:
                z = layers.reshape(qkv, [R, 1, H, 3, DH])
                zq = layers.split(z, 3, dim=3)[0]
                out = layers.transpose(layers.squeeze(zq, axes=[3]),
                                       perm=[0, 2, 1, 3])
            else:
                qv = layers.split(qkv, 3, dim=2)[0]  # [R,1,D]
                z = layers.reshape(qv, [R, 1, H, DH])
                out = layers.transpose(z, perm=[0, 2, 1, 3])
        absint.set_mesh(main, MESH)
        facts = absint.analyze(main)
        assert facts.converged
        return main, out, facts

    def test_interleaved_carries_head_shard_matches_gspmd(self):
        import jax.numpy as jnp

        R, D, H, DH = self.R, self.D, self.H, self.DH
        _, out, facts = self._chain(interleaved=True)
        spec = facts.spec(out.name)

        def fn(a, b):
            z = (a @ b).reshape(R, 1, H, 3, DH)
            zq = jnp.split(z, 3, axis=3)[0]
            return jnp.transpose(jnp.squeeze(zq, 3), (0, 2, 1, 3))

        want = _jax_out_pspec(
            fn,
            [np.zeros((R, 1, D), np.float32),
             np.zeros((D, 3 * D), np.float32)],
            [(None, None, None), (None, "tp")], 4)
        assert _spec_to_pspec(spec, 4) == want == \
            (None, "tp", None, None)
        # the whole decomposition is LOCAL under the column shard
        assert not [es for es in facts.collective_events
                    if es.event.kind == "reshard"]

    def test_contiguous_split_forces_reshard(self):
        _, out, facts = self._chain(interleaved=False)
        # the fused-axis split crosses tp shard boundaries: the rule
        # records the forced reshard and drops the placement — the
        # reason the contiguous layout ships replicated
        reshards = [es for es in facts.collective_events
                    if es.event.kind == "reshard"]
        assert reshards and reshards[0].event.axes == ("tp",)
        assert facts.spec(out.name).axes() == ()


# ---------------------------------------------------------------------------
# PTA160: sharding contradiction / implicit reshard
# ---------------------------------------------------------------------------
class TestPTA160:
    def test_conflicting_operands_warn_at_top_level(self):
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 16), {0: "dp"})
            y = _data("y", (8, 16), {0: "tp"})
            layers.elementwise_add(x, y)
        ds = _diags(main, "PTA160")
        assert ds and ds[0].severity == WARNING
        assert "incompatible specs" in ds[0].message

    def test_conflict_inside_while_is_error(self):
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 16), {0: "dp"})
            y = _data("y", (8, 16), {0: "tp"})
            i = layers.fill_constant([1], "int64", 0)
            limit = layers.fill_constant([1], "int64", 4)
            cond = layers.less_than(i, limit)
            w = layers.While(cond)
            with w.block():
                layers.elementwise_add(x, y)
                layers.increment(i, 1)
                layers.less_than(i, limit, cond=cond)
        ds = _diags(main, "PTA160")
        assert ds and ds[0].severity == ERROR
        assert "INSIDE the loop" in ds[0].message

    def test_pin_disagreement_in_while_is_error(self):
        # the r5 family: state pinned to a placement, a loop body
        # writing it replicated — GSPMD reshards every iteration
        main, startup, g = _guarded()
        with g:
            acc = main.global_block.create_var(
                name="@acc160", shape=(8, 16), dtype="float32",
                persistable=True, stop_gradient=True)
            absint.mark_sharded(acc, {0: "dp"})
            x = _data("x", (8, 16))
            i = layers.fill_constant([1], "int64", 0)
            limit = layers.fill_constant([1], "int64", 4)
            cond = layers.less_than(i, limit)
            w = layers.While(cond)
            with w.block():
                layers.assign(layers.scale(x, 2.0), output=acc)
                layers.increment(i, 1)
                layers.less_than(i, limit, cond=cond)
        ds = _diags(main, "PTA160")
        assert ds and ds[0].severity == ERROR
        assert "pinned" in ds[0].message

    def test_top_level_reshard_is_silent_but_recorded(self):
        # a one-off layout change in straight-line code is a fact
        # for the planner, not a diagnostic
        main, startup, g = _guarded()
        with g:
            acc = main.global_block.create_var(
                name="@acc160b", shape=(8, 16), dtype="float32",
                persistable=True, stop_gradient=True)
            absint.mark_sharded(acc, {0: "dp"})
            x = _data("x", (8, 16))
            layers.assign(layers.scale(x, 2.0), output=acc)
        assert not _diags(main, "PTA160")
        facts = absint.analyze(main)
        assert any(es.event.kind == "reshard"
                   for es in facts.collective_events)

    def test_consistent_sharding_is_clean(self):
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 16), {0: "dp"})
            y = _data("y", (8, 16), {0: "dp"})
            layers.elementwise_add(x, y)
        assert not _diags(main, "PTA160")


# ---------------------------------------------------------------------------
# PTA161: collective-order agreement (the 1F1B x tp corollary)
# ---------------------------------------------------------------------------
def _vocab_psum_under_stage_cond():
    """THE r5 shape, rebuilt from sharding facts alone: a per-STAGE
    predicate (pp_stage_id divergence source) gating a branch whose
    body contracts a tp-sharded dim — the Megatron vocab head's psum,
    landing inside divergent control flow. No collective op appears
    anywhere; the psum exists only as a consequence of the layout,
    which is exactly what the pattern matchers could never see."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[8], dtype="float32")
        w = main.global_block.create_parameter(
            name="vocab_head.w", shape=[8, 16], dtype="float32")
        absint.mark_sharded(w, {0: "tp"})
        absint.set_mesh(main, MeshConfig.make(pp=2, tp=2))
        stage = layers.fill_constant([1], "float32", 0.0)
        absint.mark_divergence_source(stage, "pp_stage_id")
        pred = layers.less_than_value(stage, 1.0)
        sub = main.create_block()
        sub.append_op("mul", {"X": [x.name], "Y": [w.name]},
                      {"Out": ["logits"]},
                      {"x_num_col_dims": 1, "y_num_col_dims": 1})
        main.rollback()
        fsub = main.create_block()
        fsub.append_op("scale", {"X": [x.name]}, {"Out": ["noop"]},
                       {"scale": 1.0})
        main.rollback()
        main.global_block.append_op(
            "conditional_block",
            {"Condition": [pred.name], "X": [x.name, w.name]},
            {"Out": ["b_out"]},
            {"true_block": sub, "false_block": fsub,
             "true_out": "logits", "false_out": "noop"})
    return main


class TestPTA161:
    def test_1f1b_x_tp_rejection_rederived(self):
        """The acceptance pin: the 1F1B x tp vocab-psum rejection
        (pipeline_1f1b.py's named ValueError) falls out of the
        collective-order PROOF — divergence source named, mesh axis
        named, observed sequences enumerated — with no schedule-
        specific special case anywhere."""
        main = _vocab_psum_under_stage_cond()
        ds = _diags(main, "PTA161")
        assert ds and ds[0].severity == ERROR
        msg = ds[0].message
        assert "pp_stage_id" in msg          # the divergence source
        assert "'tp'" in msg or "tp" in msg  # the collective's axis
        assert "disagree" in msg and "deadlock" in msg
        assert "observe" in msg              # the sequence proof

    def test_same_psum_at_top_level_is_silent(self):
        main, startup, g = _guarded()
        with g:
            x = layers.data("x", shape=[8], dtype="float32")
            w = main.global_block.create_parameter(
                name="vh2.w", shape=[8, 16], dtype="float32")
            absint.mark_sharded(w, {0: "tp"})
            main.global_block.append_op(
                "mul", {"X": [x.name], "Y": [w.name]},
                {"Out": ["logits"]},
                {"x_num_col_dims": 1, "y_num_col_dims": 1})
        assert not _diags(main, "PTA161")

    def test_unprovable_guard_is_warning(self):
        # a guard whose predicate the replication facts cannot
        # classify: order agreement is unverifiable, not disproven
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 16), {1: "tp"})
            sub = main.create_block()
            sub.append_op("reduce_sum", {"X": [x.name]},
                          {"Out": ["s"]}, {"dim": [1]})
            main.rollback()
            # a while with NO Condition slot: the guard classifies
            # UNKNOWN (nothing to prove uniform)
            main.global_block.append_op(
                "while", {"X": [], "Init": []}, {"Out": []},
                {"sub_block": sub, "carried": [], "externals": []})
        ds = _diags(main, "PTA161")
        assert ds and ds[0].severity == WARNING
        assert "cannot be verified" in ds[0].message

    def test_uniform_guard_is_silent(self):
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 16), {1: "tp"})
            i = layers.fill_constant([1], "int64", 0)
            limit = layers.fill_constant([1], "int64", 4)
            cond = layers.less_than(i, limit)
            w = layers.While(cond)
            with w.block():
                layers.reduce_sum(x, dim=1)
                layers.increment(i, 1)
                layers.less_than(i, limit, cond=cond)
        assert not _diags(main, "PTA161")

    def test_mixed_manual_and_sharded_guard_stays_divergent(self):
        """The GSPMD-uniform reclassification must NOT fire for a
        predicate that mixes sharded values with a MANUAL divergence
        source: the sticky ValueFact.manual bit survives joins even
        when the sharded operand comes FIRST and its 'sharding:*'
        source string wins the join — a psum under such a guard is
        still a proven deadlock."""
        main, startup, g = _guarded()
        with g:
            x = _data("x", (8, 16), {1: "tp"})
            stage = layers.fill_constant([1], "float32", 0.0)
            absint.mark_divergence_source(stage, "pp_stage_id")
            # sharded ancestry FIRST, manual second: the joined
            # fact's source string is the sharding one
            sx = layers.reduce_sum(x, dim=1)          # varying: tp
            mixed = layers.elementwise_add(
                layers.reduce_sum(sx, dim=0, keep_dim=True), stage)
            one = layers.fill_constant([1], "float32", 1.0)
            cond = layers.less_than(mixed, one)
            w = layers.While(cond)
            with w.block():
                layers.reduce_sum(x, dim=1)  # implied psum in body
                layers.less_than(mixed, one, cond=cond)
        ds = _diags(main, "PTA161")
        assert ds and ds[0].severity == ERROR
        assert "pp_stage_id" in ds[0].message


# ---------------------------------------------------------------------------
# paged/spec op families vs GSPMD's ACTUAL choice (the r17 satellite:
# an unregistered op blinds PTA160/161 and inflates the PTA170 plan on
# exactly the sharded serve programs — these pin each family's rule
# against what XLA does on the 8-dev mesh)
# ---------------------------------------------------------------------------
class TestPagedSpecOpRules:
    def _facts(self, main):
        return absint.analyze(main)

    def test_masked_pool_write_keeps_pool_layout(self):
        NB, BS, H, Dh, R = 8, 4, 4, 4, 5
        main, startup, g = _guarded()
        with g:
            pool = main.global_block.create_var(
                name="@rulepool", shape=(NB, BS, H, Dh),
                dtype="float32", persistable=True,
                stop_gradient=True)
            absint.mark_sharded(pool, {2: "tp"})
            new = _data("new", (R, H, Dh))
            idx = _data("idx", (R,), dtype="int64")
            gate = _data("gate", (R,))
            layers.masked_pool_write(pool, new, idx, gate=gate,
                                     leading_dims=2,
                                     exclusive_via="block_table")
        facts = self._facts(main)
        assert facts.spec("@rulepool") == ShardSpec.of({2: "tp"})
        # replicated New into a sharded pool is a local slice — the
        # rule must NOT claim a reshard (free under GSPMD)
        assert not [es for es in facts.collective_events
                    if es.event.kind == "reshard"]

        import jax.numpy as jnp

        def fn(pool, new, idx, gate):
            n = NB * BS
            pf = pool.reshape(n, -1)
            nf = new.reshape(R, -1).astype(pf.dtype)
            ii = idx.reshape(R).astype(jnp.int32)
            keep = (ii >= 0) & (ii < n) & (gate.reshape(R) > 0)
            safe = jnp.where(keep, ii, n)
            padded = jnp.concatenate(
                [pf, jnp.zeros((1,) + pf.shape[1:], pf.dtype)], 0)
            return padded.at[safe].set(nf)[:n].reshape(pool.shape)

        got = _jax_out_pspec(
            fn,
            [np.zeros((NB, BS, H, Dh), np.float32),
             np.ones((R, H, Dh), np.float32),
             np.arange(R, dtype=np.int32), np.ones(R, np.float32)],
            [(None, None, "tp", None), (), (), ()], 4)
        assert got == _spec_to_pspec(facts.spec("@rulepool"), 4)

    def test_paged_decode_attention_keeps_head_shard_no_collective(
            self):
        """Pools sharded on H*Dh under tp: the context rows come out
        sharded the same way and the tick pays no reshard, no gather
        and no psum (every contraction is inside one head); GSPMD
        picks the same layout for the reference route."""
        NB, BS, H, Dh, R, NP = 8, 4, 4, 8, 5, 2
        main, startup, g = _guarded()
        with g:
            pools = []
            for tag in "kv":
                pool = main.global_block.create_var(
                    name=f"@rule/self_{tag}0@POOL",
                    shape=(NB * BS, H * Dh), dtype="float32",
                    persistable=True, stop_gradient=True)
                absint.mark_sharded(pool, {1: "tp"})
                pools.append(pool)
            q = _data("q", (R, 1, H * Dh), {2: "tp"})
            tab = _data("tab", (R, NP), dtype="int32")
            absint.mark_pool_index_source(tab, "block_table", bound=NB)
            pos = _data("pos", (R,), dtype="int64")
            out = layers.paged_decode_attention(
                q, pools[0], pools[1], tab, pos, block_size=BS,
                n_heads=H, scale=0.5)
        absint.set_mesh(main, MESH)
        facts = self._facts(main)
        assert facts.converged
        assert facts.spec(out.name) == ShardSpec.of({2: "tp"})
        assert not [es for es in facts.collective_events
                    if es.site.op.type == "paged_decode_attention"]
        assert not _diags(main, "PTA190")

        from paddle_tpu.ops.pallas.paged_attention import \
            paged_attention_reference

        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        from paddle_tpu.ops import pallas

        def fn(q, pk, pv, tab, pos):
            # as the executor traces a program a mesh places
            with pallas.auto_partitioned():
                return paged_attention_reference(
                    q, pk, pv, tab, pos, block_size=BS, n_heads=H,
                    scale=0.5)

        arrays = [np.zeros((R, 1, H * Dh), np.float32),
                  np.zeros((NB * BS, H * Dh), np.float32),
                  np.zeros((NB * BS, H * Dh), np.float32),
                  np.zeros((R, NP), np.int32), np.zeros((R,), np.int32)]
        pspecs = [(None, None, "tp"), (None, "tp"), (None, "tp"), (),
                  ()]
        got = _jax_out_pspec(fn, arrays, pspecs, 3)
        assert got == _spec_to_pspec(facts.spec(out.name), 3)
        # and GSPMD's program for it holds no collective at all
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2),
                    ("dp", "tp"))
        hlo = jax.jit(fn).lower(*[
            jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=NamedSharding(
                mesh, PartitionSpec(*p)))
            for a, p in zip(arrays, pspecs)]).compile().as_text()
        for collective in ("all-reduce", "all-gather", "all-to-all",
                           "collective-permute"):
            assert collective not in hlo, collective

    def test_prompt_table_read_keeps_head_shard_no_collective(self):
        """The cross-attention read of a paged tick as the engine
        wires it: the ``[E+1, S, H*Dh]`` prompt table sharded on its
        ``H*Dh`` axis under tp. The reshapes to rows and to a table of
        one block a lane carry the placement, the context rows come
        out sharded the same way, and neither the rules nor GSPMD's
        program for the reference route hold a collective."""
        from paddle_tpu.models.decode_engine import _PagedPromptCross

        E1, S, H, Dh, R = 4, 8, 4, 8, 5
        main, startup, g = _guarded()
        with g:
            tables = []
            for tag in "kv":
                t = main.global_block.create_var(
                    name=f"@rule3/cross_{tag}0@POOL",
                    shape=(E1, S, H * Dh), dtype="float32",
                    persistable=True, stop_gradient=True)
                absint.mark_sharded(t, {2: "tp"})
                tables.append(t)
            q = _data("q", (R, 1, H * Dh), {2: "tp"})
            pref = _data("pref", (R,), dtype="int32")
            absint.mark_pool_index_source(pref, "prompt_entry_ref",
                                          bound=E1)
            last = layers.fill_constant([R], "int32", float(S - 1))
            out = _PagedPromptCross(
                tables[0], tables[1], layers.reshape(pref, [R, 1]),
                last).attend(q, H, 0.5)
        absint.set_mesh(main, MESH)
        facts = self._facts(main)
        assert facts.converged
        assert facts.spec(out.name) == ShardSpec.of({2: "tp"})
        assert not facts.collective_events
        assert not _diags(main, "PTA190")

        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        from paddle_tpu.ops import pallas
        from paddle_tpu.ops.pallas.paged_attention import \
            paged_attention_reference

        def fn(q, tk, tv, ref):
            with pallas.auto_partitioned():
                return paged_attention_reference(
                    q, tk.reshape(-1, H * Dh), tv.reshape(-1, H * Dh),
                    ref.reshape(R, 1),
                    np.full((R,), S - 1, np.int32), block_size=S,
                    n_heads=H, scale=0.5)

        arrays = [np.zeros((R, 1, H * Dh), np.float32),
                  np.zeros((E1, S, H * Dh), np.float32),
                  np.zeros((E1, S, H * Dh), np.float32),
                  np.zeros((R,), np.int32)]
        pspecs = [(None, None, "tp"), (None, None, "tp"),
                  (None, None, "tp"), ()]
        got = _jax_out_pspec(fn, arrays, pspecs, 3)
        assert got == _spec_to_pspec(facts.spec(out.name), 3)
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2),
                    ("dp", "tp"))
        hlo = jax.jit(fn).lower(*[
            jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=NamedSharding(
                mesh, PartitionSpec(*p)))
            for a, p in zip(arrays, pspecs)]).compile().as_text()
        for collective in ("all-reduce", "all-gather", "all-to-all",
                           "collective-permute"):
            assert collective not in hlo, collective

    def test_paged_decode_attention_sharded_table_is_an_event(self):
        NB, BS, H, Dh, R, NP = 8, 4, 4, 8, 8, 2
        main, startup, g = _guarded()
        with g:
            pools = [main.global_block.create_var(
                name=f"@rule2/self_{tag}0@POOL",
                shape=(NB * BS, H * Dh), dtype="float32",
                persistable=True, stop_gradient=True)
                for tag in "kv"]
            q = _data("q", (R, 1, H * Dh))
            tab = _data("tab", (R, NP), {0: "dp"}, dtype="int32")
            pos = _data("pos", (R,), dtype="int64")
            layers.paged_decode_attention(
                q, pools[0], pools[1], tab, pos, block_size=BS,
                n_heads=H)
        absint.set_mesh(main, MESH)
        kinds = [es.event.kind for es in
                 self._facts(main).collective_events
                 if es.site.op.type == "paged_decode_attention"]
        assert kinds == ["allgather"]

    def test_span_scatter_keeps_buffer_layout(self):
        R, T, W = 8, 16, 4
        main, startup, g = _guarded()
        with g:
            buf = main.global_block.create_var(
                name="@rulebuf", shape=(R, T), dtype="int64",
                persistable=True, stop_gradient=True)
            absint.mark_sharded(buf, {0: "dp"})
            vals = _data("vals", (R, W), dtype="int64")
            start = _data("start", (R,), dtype="int64")
            count = _data("count", (R,), dtype="int64")
            layers.span_scatter(buf, vals, start, count)
        facts = self._facts(main)
        assert facts.spec("@rulebuf") == ShardSpec.of({0: "dp"})

        import jax.numpy as jnp

        def fn(buf, vals, start, count):
            pos = jnp.arange(T)[None, :]
            rel = pos - start[:, None]
            sel = (rel >= 0) & (rel < count[:, None]) & (rel < W)
            relc = jnp.clip(rel, 0, W - 1)
            va = jnp.take_along_axis(vals, relc, axis=1)
            return jnp.where(sel, va.astype(buf.dtype), buf)

        got = _jax_out_pspec(
            fn,
            [np.zeros((R, T), np.int64), np.ones((R, W), np.int64),
             np.zeros(R, np.int64), np.full(R, 2, np.int64)],
            [("dp", None), (), (), ()], 2)
        assert got == _spec_to_pspec(facts.spec("@rulebuf"), 2)

    def test_filtered_softmax_keeps_vocab_shard_and_implies_psum(self):
        R, V = 8, 64
        main, startup, g = _guarded()
        with g:
            z = _data("z", (R, V), {1: "tp"})
            p = layers.filtered_softmax(z, temperature=0.8, top_k=8,
                                        top_p=0.95)
        facts = self._facts(main)
        assert facts.spec(p.name) == ShardSpec.of({1: "tp"})
        psums = [es for es in facts.collective_events
                 if es.event.kind == "psum"]
        assert psums and all("tp" in es.event.axes for es in psums)

        import jax
        import jax.numpy as jnp

        def fn(z):
            zz = (z / 0.8).astype(jnp.float32)
            kth = jax.lax.top_k(zz, 8)[0][..., -1:]
            zz = jnp.where(zz >= kth, zz, -jnp.inf)
            pr = jax.nn.softmax(zz, axis=-1)
            ps = jnp.sort(pr, axis=-1)[..., ::-1]
            cs = jnp.cumsum(ps, axis=-1)
            keep = (cs - ps) < 0.95
            cut = jnp.min(jnp.where(keep, ps, jnp.inf), axis=-1,
                          keepdims=True)
            pr = jnp.where(pr >= cut, pr, 0.0)
            return pr / jnp.sum(pr, axis=-1, keepdims=True)

        got = _jax_out_pspec(fn, [np.random.rand(R, V).astype(
            np.float32)], [(None, "tp")], 2)
        assert got == _spec_to_pspec(facts.spec(p.name), 2)

    def test_sample_categorical_replicates_and_implies_gather(self):
        R, V = 8, 64
        main, startup, g = _guarded()
        with g:
            probs = _data("probs", (R, V), {1: "tp"})
            seed = _data("seed", (R,), dtype="int64")
            pos = _data("pos", (R,), dtype="int64")
            tok = layers.sample_categorical(probs, seed, pos)
        facts = self._facts(main)
        assert facts.spec(tok.name).is_replicated
        ag = [es for es in facts.collective_events
              if es.event.kind == "allgather"]
        assert ag and "tp" in ag[0].event.axes

    def test_spec_accept_replicates_and_implies_gather(self):
        R, V, k = 8, 64, 2
        main, startup, g = _guarded()
        with g:
            props = _data("props", (R, k), dtype="int64")
            dprobs = _data("dprobs", (R, k, V))
            tprobs = _data("tprobs", (R, k + 1, V), {2: "tp"})
            seed = _data("seed", (R,), dtype="int64")
            pos = _data("pos", (R,), dtype="int64")
            adv, toks, acc, fin = layers.spec_accept(
                props, dprobs, tprobs, seed, pos, k=k, end_id=1,
                max_len=16, greedy=True)
        facts = self._facts(main)
        for v in (adv, toks, acc, fin):
            assert facts.spec(v.name).is_replicated, v.name
        ag = [es for es in facts.collective_events
              if es.event.kind == "allgather"]
        assert ag and "tp" in ag[0].event.axes

        import jax.numpy as jnp

        def fn(props, dprobs, tprobs):
            px = jnp.take_along_axis(tprobs[:, :k], props[..., None],
                                     axis=-1)[..., 0]
            qx = jnp.take_along_axis(dprobs, props[..., None],
                                     axis=-1)[..., 0]
            a = jnp.cumprod((qx < px).astype(jnp.int64),
                            axis=1).sum(axis=1)
            return a

        got = _jax_out_pspec(
            fn,
            [np.zeros((R, k), np.int64),
             np.random.rand(R, k, V).astype(np.float32),
             np.random.rand(R, k + 1, V).astype(np.float32)],
            [(), (), (None, None, "tp")], 1)
        assert got == _spec_to_pspec(facts.spec(adv.name), 1)


# ---------------------------------------------------------------------------
# the tp-sharded decoder fixture (analysis/targets.py zoo target)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tp_fixture():
    from paddle_tpu.models import sharded_decoder

    return sharded_decoder.build_tp_sharded_decoder_step()


class TestShardedDecoderFixture:
    def test_strict_green(self, tp_fixture):
        ds = run_checks(tp_fixture.program)
        assert not [d for d in ds
                    if d.severity in (ERROR, WARNING)], \
            [d.format() for d in ds][:5]

    def test_head_sharded_attention_flow(self, tp_fixture):
        # the propagated layout is the Megatron one: KV pinned on
        # heads, row-parallel projections implying the psums
        facts = absint.analyze(tp_fixture.program)
        assert facts.converged
        for name in tp_fixture.kv_names:
            assert facts.spec(name) == ShardSpec.of({1: "tp"}), name
        psums = [es for es in facts.collective_events
                 if es.event.kind == "psum"]
        # row-parallel self_out/cross_out/fc2 per layer
        assert len(psums) >= 3 * 2
        assert all(es.event.axes == ("tp",) for es in psums)

    def test_sharding_facts_are_stable_surface_only(self, tp_fixture):
        facts = absint.analyze(tp_fixture.program)
        stable = facts.stable_sharding_facts()
        # the REAL lowering's mesh: tp only (dp replica lanes are
        # separate server instances on disjoint device slices, not a
        # mesh axis of one program)
        assert stable["@mesh"] == "tp=2"
        assert stable["logits.w"] == "dim1:tp"
        # tmp_N propagation intermediates stay OUT of the baseline
        assert not any(k.startswith("tmp") or ".tmp" in k
                       for k in stable)


# ---------------------------------------------------------------------------
# baseline drift gate for sharding_facts
# ---------------------------------------------------------------------------
class TestShardingFactsBaseline:
    def _report(self, target, sharding):
        from paddle_tpu.analysis.baseline import TargetReport

        rep = TargetReport(target)
        rep.sharding = dict(sharding)
        return rep

    def test_changed_fact_fails_until_refresh(self):
        from paddle_tpu.analysis.baseline import (baseline_payload,
                                                  diff_against_baseline)

        base = baseline_payload(
            [self._report("t:step", {"w": "dim1:tp"})])
        drifted = [self._report("t:step", {"w": "dim0:tp"})]
        new, _res = diff_against_baseline(drifted, base)
        assert new == ["t:step|w=dim0:tp (was dim1:tp: sharding "
                       "drift)"]
        refreshed = baseline_payload(drifted)
        assert diff_against_baseline(drifted, refreshed) == ([], [])

    def test_new_and_gone_facts(self):
        from paddle_tpu.analysis.baseline import (baseline_payload,
                                                  diff_against_baseline)

        base = baseline_payload(
            [self._report("t:step", {"w": "dim1:tp"})])
        now = [self._report("t:step", {"v": "dim0:dp"})]
        new, resolved = diff_against_baseline(now, base)
        assert new == ["t:step|v=dim0:dp (new sharding fact)"]
        assert resolved == ["t:step|w (sharding fact gone)"]
