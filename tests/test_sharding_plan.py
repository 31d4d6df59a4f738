"""Fast-lane sharded-serving smoke + plan/placement units.

The trained-model tp/dp serving suite (parity on every decode front,
per-device KV, runtime churn, subprocess warm start) lives in
tests/test_sharded_serving.py (slow lane). This module keeps tier-1
coverage of the sharded machinery cheap: a tiny UNTRAINED
token-parity smoke (argmax over random-initialized weights is
deterministic, so sharded-vs-single byte equality needs no
training), the ShardingPlan/ShardingConfig identity+validation
contracts, the mesh carve, the ReplicaSet fingerprint, and the
compile-cache mesh-mismatch named discard.
"""
import os
import pickle

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import unique_name
from paddle_tpu.core.scope import Scope
from paddle_tpu.inference import (ContinuousGenerationServer,
                                  apply_eos_sentinel)
from paddle_tpu.models import transformer as T
from paddle_tpu.models.decode_engine import (CacheConfig,
                                             ShardingConfig,
                                             place_sharded_program)

DIMS = dict(seq_len=6, max_out_len=8, d_model=16, n_heads=2,
            n_layers=1, d_inner=32, vocab=16, start_id=1, end_id=2)


def _init_scope(exe):
    """Random-initialized (untrained) weights: greedy argmax over
    them is deterministic, which is all byte-parity needs."""
    fluid.seed(3)
    scope = Scope()
    with unique_name.guard():
        _m, st, _loss = T.build_program(
            seq_len=DIMS["seq_len"], d_model=DIMS["d_model"],
            n_heads=DIMS["n_heads"], n_layers=DIMS["n_layers"],
            d_inner=DIMS["d_inner"], vocab=DIMS["vocab"],
            with_optimizer=False, dropout_rate=0.0)
    exe.run(st, scope=scope)
    return scope


class TestSmokeParity:
    def test_whole_loop_and_burst_sharded_vs_single(self):
        exe = fluid.Executor(fluid.TPUPlace(0))
        scope = _init_scope(exe)
        srcs = np.random.RandomState(5).randint(
            3, DIMS["vocab"], (4, DIMS["seq_len"])).astype(np.int64)
        with unique_name.guard():
            inc_m, _, _, inc_buf = T.build_incremental_decode_program(
                **DIMS)
        want, = exe.run(inc_m, feed={"src_ids": srcs},
                        fetch_list=[inc_buf], scope=scope)
        want = apply_eos_sentinel(np.asarray(want), DIMS["end_id"])
        # sharded whole-loop front
        with unique_name.guard():
            sh_m, _, _, sh_buf = T.build_incremental_decode_program(
                sharding=ShardingConfig(tp=2), **DIMS)
        assert place_sharded_program(sh_m, scope) > 0
        got, = exe.run(sh_m, feed={"src_ids": srcs},
                       fetch_list=[sh_buf], scope=scope)
        np.testing.assert_array_equal(
            apply_eos_sentinel(np.asarray(got), DIMS["end_id"]), want)
        # sharded slot-pool burst front
        with unique_name.guard():
            b = T.build_decode_step_program(
                n_slots=2, admit_buckets=[2], state_prefix="@fsm/",
                sharding=ShardingConfig(tp=2), **DIMS)
        with ContinuousGenerationServer(b, executor=exe,
                                        scope=scope) as srv:
            outs = [srv.submit(s) for s in srcs]
            got = np.stack([o.result(120.0) for o in outs])
        np.testing.assert_array_equal(got, want)


    def test_paged_tick_under_tp_keeps_the_prompt_table_sharded(self):
        """A paged bundle under tp=2: the prompt table is placed on
        its ``H*Dh`` axis like the self pools, the tick's reads of it
        imply no collective (the prover's events on the program the
        executor runs), and the served tokens are the single-device
        whole loop's."""
        from paddle_tpu.analysis import absint
        from paddle_tpu.inference import PagedContinuousGenerationServer
        from paddle_tpu.models.decode_engine import POOL_MARK

        exe = fluid.Executor(fluid.TPUPlace(0))
        scope = _init_scope(exe)
        srcs = np.random.RandomState(5).randint(
            3, DIMS["vocab"], (4, DIMS["seq_len"])).astype(np.int64)
        with unique_name.guard():
            inc_m, _, _, inc_buf = T.build_incremental_decode_program(
                **DIMS)
        want, = exe.run(inc_m, feed={"src_ids": srcs},
                        fetch_list=[inc_buf], scope=scope)
        want = apply_eos_sentinel(np.asarray(want), DIMS["end_id"])
        with unique_name.guard():
            b = T.build_decode_step_program(
                n_slots=2, admit_buckets=[2], state_prefix="@fsp/",
                sharding=ShardingConfig(tp=2),
                cache=CacheConfig(layout="paged", block_size=4,
                                  n_blocks=8, n_prompt_entries=4),
                **DIMS)
        places = b.sharding_plan.placements
        for li in range(DIMS["n_layers"]):
            for tag in "kv":
                assert places[f"@fsp/cross_{tag}{li}{POOL_MARK}"] == {
                    2: "tp"}
                assert places[f"@fsp/self_{tag}{li}{POOL_MARK}"] == {
                    1: "tp"}
        facts = absint.analyze(b.serves[0])
        reads = [es.site.op for es in facts.collective_events
                 if es.site.op.type == "paged_decode_attention"]
        assert not reads
        n_reads = sum(op.type == "paged_decode_attention"
                      for blk in b.serves[0].blocks for op in blk.ops)
        assert n_reads == 2 * DIMS["n_layers"]
        with PagedContinuousGenerationServer(b, executor=exe,
                                             scope=scope) as srv:
            outs = [srv.submit(s) for s in srcs]
            got = np.stack([o.result(120.0) for o in outs])
            st = srv.stats()
        np.testing.assert_array_equal(got, want)
        # a program a mesh places takes the reference route
        for stat in ("self_attention_routes", "cross_attention_routes"):
            assert {tuple(v) for v in st[stat].values() if v} == {
                ("reference",)}


class TestIdentity:
    def _bundle(self, prefix, sharding=None):
        with unique_name.guard():
            return T.build_decode_step_program(
                n_slots=2, admit_buckets=[2], state_prefix=prefix,
                sharding=sharding, **DIMS)

    def test_sharded_and_dense_fingerprints_differ(self):
        from paddle_tpu.inference.runtime import server_fingerprint

        b_dense = self._bundle("@fid/")
        b_tp = self._bundle("@fid/", sharding=ShardingConfig(tp=2))
        assert b_dense.cache_token() != b_tp.cache_token()

        class _Srv:
            def __init__(self, bundle):
                self.bundle = bundle

        assert server_fingerprint(_Srv(b_dense)) != \
            server_fingerprint(_Srv(b_tp))

    def test_plan_token_separates_device_slices(self):
        import jax

        b = self._bundle("@ftk/", sharding=ShardingConfig(tp=2))
        plan = b.sharding_plan
        t0 = plan.token()
        plan.bind(jax.devices()[:2])
        t1 = plan.token()
        assert t1 != t0
        plan.bind(jax.devices()[2:4])
        assert plan.token() != t1

    def test_sharding_config_validation(self):
        with pytest.raises(ValueError, match="n_heads"):
            ShardingConfig(tp=3).validate(4, 64, 32, 64)
        with pytest.raises(ValueError, match="reserved"):
            ShardingConfig(tp=2, axis="lanes").validate(4, 64, 32, 64)
        with pytest.raises(ValueError, match="mesh_devices"):
            ContinuousGenerationServer(
                _BundleStub(), mesh_devices=[1, 2])


class _BundleStub:
    """Minimal dense bundle stand-in for the mesh_devices refusal."""
    cache = CacheConfig()
    n_slots = 1
    end_id = 1
    max_out_len = 8
    state = {}
    serves = {}
    sharding_plan = None

    def init_slot_state(self, scope):
        raise AssertionError("must refuse before state init")


class TestPlacementUnits:
    def test_plan_mesh_carve_and_bounds(self):
        import jax

        from paddle_tpu.inference.runtime import plan_mesh

        mp = plan_mesh(n_tp_models=2, tp=2, n_dp_lanes=4)
        devs = jax.devices()
        assert [d.id for d in mp.tp_slices[0]] == [devs[0].id,
                                                   devs[1].id]
        assert [d.id for d in mp.tp_slices[1]] == [devs[2].id,
                                                   devs[3].id]
        assert [d.id for d in mp.dp_devices] == [d.id
                                                 for d in devs[4:8]]
        with pytest.raises(ValueError):
            plan_mesh(n_tp_models=4, tp=2, n_dp_lanes=4)

    def test_replica_set_fingerprint_depends_on_lanes(self):
        from paddle_tpu.core.executor import Executor, TPUPlace
        from paddle_tpu.inference.runtime import (ReplicaSet,
                                                  server_fingerprint,
                                                  zoo)

        exe = Executor(TPUPlace(0))
        servers = []
        for j in range(2):
            srv, _sc = zoo.make_fc_server(f"frs{j}", 8, 16, 4,
                                          executor=exe, start=False)
            servers.append(srv)
        f2 = server_fingerprint(ReplicaSet(servers))
        f1 = server_fingerprint(ReplicaSet(servers[:1]))
        assert f2 != f1
        for s in servers:
            s.close()


class TestMeshMismatchDiscard:
    def test_mesh_mismatched_entry_is_named_discard(self, tmp_path):
        """An entry whose recorded mesh devices do not exist locally
        must be discarded with a NAMED reason before deserialization
        is even attempted — never a jaxlib crash."""
        from paddle_tpu.core import compile_cache as CC
        from paddle_tpu.flags import set_flags

        set_flags({"FLAGS_compile_cache": "rw",
                   "FLAGS_compile_cache_dir": str(tmp_path / "cc")})
        try:
            cache = CC.active_cache()
            digest = "ab" + "0" * 62
            path = cache._path(digest)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            entry = {"magic": CC._MAGIC, "format": "aot",
                     "payload": b"\x00junk-not-an-executable",
                     "in_tree": None, "out_tree": None,
                     "device_ids": [98, 99],
                     "meta": {"mesh": {"ndev": 2,
                                       "axes": [["tp", 2]],
                                       "device_ids": [98, 99]}}}
            with open(path, "wb") as f:
                pickle.dump(entry, f)
            assert cache.load_executable(digest) is None
            assert "mesh mismatch" in cache.last_discard_reason
            assert "98" in cache.last_discard_reason
        finally:
            set_flags({"FLAGS_compile_cache": "off"})
