"""Sharded serving: tensor-parallel decode + data-parallel lanes on
the virtual 8-device mesh (models/decode_engine.ShardingConfig +
core/sharding_plan.py + inference/runtime/placement.py).

The invariants this module pins (the r17 acceptance criteria):

* token-exact greedy parity sharded-vs-single across every decode
  front — whole-loop incremental, plain dense burst, paged,
  speculative — and BIT-exact sampled streams (the noise keying is
  (seed, position), so a tp mesh must not move a single draw);
* per-device self-KV bytes ~1/tp at tp=2: exactly 1/tp per pool in
  the PTA170 static plan, and <= 0.55x end-to-end argument bytes via
  the compiled executable's ``memory_analysis()``;
* zero steady-state compiles under 100-request churn with tp models
  AND dp replica lanes serving concurrently through the runtime
  registry/router;
* warm start survives sharded programs: a fresh process rehydrates a
  sharded serve executable from the disk compile cache with ZERO
  compiles, and a mesh-mismatched entry is a NAMED discard, never a
  crash;
* fingerprints/cache keys separate sharded from dense builds (they
  must never dedupe or hot-swap as the same model).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import unique_name
from paddle_tpu.core.scope import Scope
from paddle_tpu.inference import (ContinuousGenerationServer,
                                  PagedContinuousGenerationServer,
                                  apply_eos_sentinel)
from paddle_tpu.models import transformer as T
from paddle_tpu.models.decode_engine import (CacheConfig, DraftConfig,
                                             SamplingConfig,
                                             ShardingConfig,
                                             place_sharded_program)

V, D, DD, H, L, S, MAXT = 16, 32, 16, 4, 1, 12, 16
END_ID = 2
N_SLOTS = 4
TP = 2
BS, NB, E = 4, 64, 6

# fixed prompt pool (the r14 discipline): planted EOS at varied
# positions gives MODEL-DRIVEN mixed-length generations, and the
# repeated prompts give the speculative draft real agreement
_POOL_RNG = np.random.RandomState(5)
PROMPT_POOL = []
for _p in (1, 2, 3, 4, 6, 8, 10, 10):
    _src = _POOL_RNG.randint(3, V, (S,)).astype(np.int64)
    if _p < S:
        _src[_p:] = END_ID
    PROMPT_POOL.append(_src)
PROMPT_POOL = np.stack(PROMPT_POOL)


def _mixed_len_prompts(rng, n):
    return PROMPT_POOL[rng.randint(0, len(PROMPT_POOL), n)]


def _fork_scope(scope):
    """Copy every scope value to host numpy in a FRESH scope: each
    sharded server places ITS OWN copy on its mesh slice, and the
    trained oracle scope stays plain host arrays (placement must
    never leak into the single-device reference leg)."""
    import jax

    fork = Scope()
    for name in list(scope._vars):
        val = scope._get(name)
        if isinstance(val, jax.Array):
            val = np.asarray(val)
        fork._set(name, np.copy(val) if isinstance(val, np.ndarray)
                  else val)
    return fork


@pytest.fixture(scope="module")
def trained():
    """Train target (d32/L1) + draft (d16/L1) terminator-copy models
    into one scope; build the unsharded whole-loop oracle and the
    sharded bundle flavors."""
    fluid.seed(0)
    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    with unique_name.guard():
        t_main, t_st, t_loss = T.build_program(
            seq_len=S, d_model=D, n_heads=H, n_layers=L, d_inner=64,
            vocab=V, with_optimizer=False, dropout_rate=0.0)
        with fluid.program_guard(t_main, t_st):
            fluid.optimizer.Adam(learning_rate=0.02).minimize(t_loss)
        d_main, d_st, d_loss = T.build_program(
            seq_len=S, d_model=DD, n_heads=H, n_layers=L, d_inner=32,
            vocab=V, with_optimizer=False, dropout_rate=0.0,
            name_prefix="draft_")
        with fluid.program_guard(d_main, d_st):
            fluid.optimizer.Adam(learning_rate=0.02).minimize(d_loss)
    exe.run(t_st, scope=scope)
    exe.run(d_st, scope=scope)
    rng = np.random.RandomState(7)
    for _ in range(150):
        src = _mixed_len_prompts(rng, 8)
        tgt_in = np.concatenate(
            [np.full((8, 1), 1, np.int64), src[:, :-1]], 1)
        feed = {"src_ids": src, "tgt_ids": tgt_in, "label": src}
        exe.run(t_main, feed=feed, fetch_list=[t_loss], scope=scope)
        exe.run(d_main, feed=feed, fetch_list=[d_loss], scope=scope)

    kwargs = dict(seq_len=S, max_out_len=MAXT, d_model=D, n_heads=H,
                  n_layers=L, d_inner=64, vocab=V, start_id=1,
                  end_id=END_ID)
    with unique_name.guard():
        inc_m, _, _, inc_buf = T.build_incremental_decode_program(
            **kwargs)
    return {"exe": exe, "scope": scope, "inc_m": inc_m,
            "inc_buf": inc_buf, "kwargs": kwargs}


def _oracle(tr, srcs):
    ref, = tr["exe"].run(tr["inc_m"], feed={"src_ids": srcs},
                         fetch_list=[tr["inc_buf"]],
                         scope=tr["scope"])
    return apply_eos_sentinel(np.asarray(ref), end_id=END_ID)


def _build(tr, prefix, **kw):
    args = dict(tr["kwargs"])
    args.update(kw)
    with unique_name.guard():
        return T.build_decode_step_program(
            n_slots=N_SLOTS, admit_buckets=[N_SLOTS],
            state_prefix=prefix, **args)


def _serve(tr, bundle, srcs, seeds=None, **srv_kw):
    cls = (PagedContinuousGenerationServer
           if bundle.cache.layout == "paged"
           else ContinuousGenerationServer)
    fork = _fork_scope(tr["scope"])
    with cls(bundle, executor=tr["exe"], scope=fork,
             **srv_kw) as srv:
        replies = []
        for i, s in enumerate(srcs):
            kw = {"seed": int(seeds[i])} if seeds is not None else {}
            replies.append(srv.submit(s, **kw))
        got = np.stack([r.result(timeout=300.0) for r in replies])
        st = srv.stats()
    return got, st


# ---------------------------------------------------------------------------
# token-exact parity sharded-vs-single, every decode front
# ---------------------------------------------------------------------------
class TestParity:
    def test_whole_loop_sharded_vs_single(self, trained):
        srcs = _mixed_len_prompts(np.random.RandomState(11), 8)
        want = _oracle(trained, srcs)
        assert len(set(int((w != -1).sum()) for w in want)) > 1, \
            "workload must have mixed output lengths"
        with unique_name.guard():
            sh_m, _, _, sh_buf = T.build_incremental_decode_program(
                sharding=ShardingConfig(tp=TP), **trained["kwargs"])
        fork = _fork_scope(trained["scope"])
        placed = place_sharded_program(sh_m, fork)
        assert placed > 0
        got, = trained["exe"].run(sh_m, feed={"src_ids": srcs},
                                  fetch_list=[sh_buf], scope=fork)
        got = apply_eos_sentinel(np.asarray(got), END_ID)
        np.testing.assert_array_equal(got, want)

    def test_greedy_full_recompute_sharded_vs_single(self, trained):
        """The greedy FULL-RECOMPUTE whole-loop front takes
        ``sharding=`` too (params-only tp layout — it holds no
        persistable KV, so the fused attention ops take head
        sharding purely from GSPMD param propagation): token parity
        against the single-device incremental oracle."""
        srcs = _mixed_len_prompts(np.random.RandomState(29), 8)
        want = _oracle(trained, srcs)
        with unique_name.guard():
            g_m, _, _, g_buf = T.build_greedy_decode_program(
                sharding=ShardingConfig(tp=TP), **trained["kwargs"])
        fork = _fork_scope(trained["scope"])
        placed = place_sharded_program(g_m, fork)
        assert placed > 0
        got, = trained["exe"].run(g_m, feed={"src_ids": srcs},
                                  fetch_list=[g_buf], scope=fork)
        got = apply_eos_sentinel(np.asarray(got), END_ID)
        np.testing.assert_array_equal(got, want)

    def test_dense_burst_sharded_vs_single(self, trained):
        srcs = _mixed_len_prompts(np.random.RandomState(13), 12)
        want = _oracle(trained, srcs)
        b = _build(trained, "@shd/", sharding=ShardingConfig(tp=TP))
        got, _ = _serve(trained, b, srcs)
        np.testing.assert_array_equal(got, want)

    def test_paged_sharded_vs_single_with_prefix_hits(self, trained):
        srcs = _mixed_len_prompts(np.random.RandomState(17), 16)
        want = _oracle(trained, srcs)
        b = _build(trained, "@shp/", sharding=ShardingConfig(tp=TP),
                   cache=CacheConfig(layout="paged", block_size=BS,
                                     n_blocks=NB,
                                     n_prompt_entries=E))
        got, st = _serve(trained, b, srcs)
        np.testing.assert_array_equal(got, want)
        # the pooled prompts repeat: the prefix-reuse fast path must
        # have served some admissions encoder-free on the tp mesh too
        assert st["block_pool"]["prefix_hits"] > 0

    def test_speculative_sharded_vs_single(self, trained):
        srcs = _mixed_len_prompts(np.random.RandomState(19), 12)
        want = _oracle(trained, srcs)
        b = _build(trained, "@shs/", sharding=ShardingConfig(tp=TP),
                   draft=DraftConfig(d_model=DD, n_heads=H,
                                     n_layers=L, d_inner=32, k=2))
        got, st = _serve(trained, b, srcs)
        np.testing.assert_array_equal(got, want)
        # the trained draft must actually accept on the tp mesh (the
        # sharded verify step's acceptance math is unchanged)
        assert st["speculative"]["acceptance_rate"] > 0.5

    def test_sampled_bit_repro_sharded_vs_single(self, trained):
        """Sampled emission is keyed purely on (seed, position): the
        tp mesh must not move a single draw — byte equality against
        the UNSHARDED sampled bundle, same seeds."""
        rng = np.random.RandomState(23)
        srcs = _mixed_len_prompts(rng, 12)
        seeds = rng.randint(0, 2 ** 31, 12)
        samp = SamplingConfig(temperature=1.0, top_k=8)
        b1 = _build(trained, "@sm1/", sampling=samp)
        b2 = _build(trained, "@sm2/", sampling=samp,
                    sharding=ShardingConfig(tp=TP))
        single, _ = _serve(trained, b1, srcs, seeds=seeds)
        sharded, _ = _serve(trained, b2, srcs, seeds=seeds)
        np.testing.assert_array_equal(sharded, single)


# ---------------------------------------------------------------------------
# per-device KV bytes: PTA170 static plan + compiled memory_analysis
# ---------------------------------------------------------------------------
class TestPerDeviceKV:
    def test_pta170_plan_prices_pools_at_one_over_tp(self, trained):
        from paddle_tpu.analysis import absint

        b = _build(trained, "@kvp/", sharding=ShardingConfig(tp=TP),
                   cache=CacheConfig(layout="paged", block_size=BS,
                                     n_blocks=NB,
                                     n_prompt_entries=E))
        facts = absint.analyze(b.step)
        plan = facts.device_memory_plan(batch=1)
        pools = [n for n in b._state_specs if "@POOL" in n]
        assert pools
        for name in pools:
            entry = plan.entry(name)
            assert entry is not None, name
            assert entry.device_bytes * TP == entry.bytes, name

    def test_memory_analysis_argument_bytes_shrink(self, trained,
                                                   tmp_path):
        """End-to-end corroboration: the compiled serve executable's
        per-device argument bytes at tp=2 are <= 0.55x the
        single-device build (the pool geometry dominates the
        argument set by construction)."""
        from paddle_tpu.flags import set_flags

        # the disk cache turns on the AOT compile path, whose
        # Compiled exposes memory_analysis() (conftest forces off)
        set_flags({"FLAGS_compile_cache": "rw",
                   "FLAGS_compile_cache_dir": str(tmp_path / "cc")})
        try:
            # serving-scale pool (the capacity regime the claim is
            # about): self-KV dominates the argument set, so the
            # END-TO-END ratio lands at ~0.5 + the replicated
            # remainder (tables, embeddings, fused projections)
            geo = dict(cache=CacheConfig(layout="paged",
                                         block_size=BS, n_blocks=160,
                                         n_prompt_entries=E))
            sizes = {}
            for tag, sh in (("single", None),
                            ("tp", ShardingConfig(tp=TP))):
                b = _build(trained, f"@ma{tag}/", sharding=sh, **geo)
                fork = _fork_scope(trained["scope"])
                with PagedContinuousGenerationServer(
                        b, executor=trained["exe"],
                        scope=fork) as srv:
                    fn = srv._serves[0].step.compiled.fn
                    ma = getattr(fn, "memory_analysis", None)
                    assert ma is not None, \
                        "AOT path did not engage (no memory_analysis)"
                    sizes[tag] = int(ma().argument_size_in_bytes)
            ratio = sizes["tp"] / sizes["single"]
            assert ratio <= 0.55, sizes
        finally:
            set_flags({"FLAGS_compile_cache": "off"})


# ---------------------------------------------------------------------------
# tp + dp through the runtime: placement, churn, zero compiles
# ---------------------------------------------------------------------------
class TestRuntimeMesh:
    def test_churn_zero_steady_state_compiles_tp_and_dp(self, trained):
        """2 tp-2 decode models on devices [0,1]/[2,3] + 4 dp fc
        lanes on devices 4..7, loaded through the registry and routed
        100 requests each way: ZERO compiles in the traffic window,
        and every piece lands on its assigned slice."""
        import jax

        from paddle_tpu.inference.runtime import (ModelRegistry,
                                                  ReplicaSet,
                                                  plan_mesh,
                                                  place_scope_on_device,
                                                  zoo)

        mp = plan_mesh(n_tp_models=2, tp=TP, n_dp_lanes=4)
        registry = ModelRegistry()
        exe = registry.executor()
        # --- 2 tensor-parallel decode models on their slices ---
        decode = []
        for i, devices in enumerate(mp.tp_slices):
            b = _build(trained, f"@mesh{i}/",
                       sharding=ShardingConfig(tp=TP))
            fork = _fork_scope(trained["scope"])
            srv = ContinuousGenerationServer(
                b, executor=exe, scope=fork, mesh_devices=devices)
            registry.load(f"decode-{i}", srv, warm=False)
            decode.append((b, fork, srv, devices))
            # the bundle's state really lives on this slice
            pool = fork._get(b.state["tok_buf"])
            assert {d.id for d in pool.sharding.mesh.devices.flat} \
                == {d.id for d in devices}
        # --- 4 dp fc replica lanes behind one alias ---
        lanes, lane_scopes = [], []
        for j, dev in enumerate(mp.dp_devices):
            srv, sc = zoo.make_fc_server(f"lane{j}", 16, 32, 4,
                                         executor=exe,
                                         max_wait_ms=0.5)
            place_scope_on_device(sc, dev)
            assert list(sc._get(f"lane{j}_fc1.w").devices())[0].id \
                == dev.id
            lanes.append(srv)
            lane_scopes.append(sc)
        # warm=True: ReplicaSet.aot_warmup fans out and seeds every
        # lane's whole bucket ladder (churn batches land on arbitrary
        # buckets; an unwarmed bucket would be a steady-state compile)
        registry.load("fc", ReplicaSet(lanes, mp.dp_devices),
                      warm=True)

        # decode warm: one admission per tp model (the serve set was
        # already prepared — compiled — at server construction)
        rng = np.random.RandomState(29)
        for _b, _f, srv, _d in decode:
            srv.submit(_mixed_len_prompts(rng, 1)[0]).result(120)

        warm = exe.compile_count
        fc = registry.get("fc")
        replies, fc_replies = [], []
        for i in range(100):
            srv = decode[i % 2][2]
            replies.append(srv.submit(_mixed_len_prompts(rng, 1)[0]))
            j = i % 4
            fc_replies.append(fc.submit(
                {f"lane{j}_x": rng.rand(1, 16).astype(np.float32)}))
        for r in replies:
            r.result(timeout=300.0)
        for r in fc_replies:
            r.result(timeout=300.0)
        assert exe.compile_count == warm, \
            "steady-state traffic compiled under tp+dp"
        registry.close()

    def test_server_reconstruction_hits_warm_executables(self,
                                                         trained):
        """A SECOND server over the same bundle + same device slice
        (fresh scope) must serve entirely from the warmed
        executables: placement is idempotent — an unconditional
        plan re-attach used to version-bump every program and
        recompile the whole serve set per server construction
        (caught by bench.py sharded)."""
        srcs = _mixed_len_prompts(np.random.RandomState(31), 4)
        b = _build(trained, "@warm2/", sharding=ShardingConfig(tp=TP))
        _serve(trained, b, srcs)
        c0 = trained["exe"].compile_count
        got, _ = _serve(trained, b, srcs)
        assert trained["exe"].compile_count == c0, \
            "server re-construction recompiled the serve set"
        np.testing.assert_array_equal(got, _oracle(trained, srcs))


# (fingerprint/validation/carve/mesh-discard units live in the
# fast-lane tests/test_sharding_plan.py)
# ---------------------------------------------------------------------------
# warm start: disk rehydration of a sharded serve program
# ---------------------------------------------------------------------------
_SUBPROCESS_SCRIPT = r"""
import json
import numpy as np
import paddle_tpu as fluid
from paddle_tpu.core.scope import Scope
from paddle_tpu.inference import ContinuousGenerationServer
from paddle_tpu.models import transformer as T
from paddle_tpu.models.decode_engine import ShardingConfig

fluid.seed(11)
scope = Scope()
exe = fluid.Executor(fluid.TPUPlace(0))
from paddle_tpu import unique_name
with unique_name.guard():
    # serving runs against a trained scope: the train build's startup
    # initializes EVERY decoder param (deterministic under seed 11)
    _m, t_st, _loss = T.build_program(
        seq_len=6, d_model=16, n_heads=2, n_layers=1, d_inner=32,
        vocab=16, with_optimizer=False, dropout_rate=0.0)
exe.run(t_st, scope=scope)
with unique_name.guard():
    bundle = T.build_decode_step_program(
        seq_len=6, max_out_len=8, d_model=16, n_heads=2, n_layers=1,
        d_inner=32, vocab=16, start_id=1, end_id=2, n_slots=2,
        admit_buckets=[2], state_prefix="@sub/",
        sharding=ShardingConfig(tp=2))
src = np.arange(3, 9, dtype=np.int64)[None].repeat(2, 0)[0]
with ContinuousGenerationServer(bundle, executor=exe,
                                scope=scope) as srv:
    toks = [srv.submit(src).result(120).tolist() for _ in range(2)]
print(json.dumps({"compiles": exe.compile_count,
                  "disk_loads": exe.disk_load_count,
                  "toks": toks}))
"""


class TestShardedWarmStart:
    def test_subprocess_rehydrates_sharded_serves(self, tmp_path):
        env = dict(os.environ,
                   JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count"
                             "=8",
                   FLAGS_compile_cache="rw",
                   FLAGS_compile_cache_dir=str(tmp_path / "cc"))

        def run_once(tag):
            proc = subprocess.run(
                [sys.executable, "-c", _SUBPROCESS_SCRIPT],
                capture_output=True, text=True, env=env, timeout=600)
            assert proc.returncode == 0, \
                f"{tag} failed:\n{proc.stderr[-2000:]}"
            return json.loads(proc.stdout.strip().splitlines()[-1])

        a = run_once("process A (cold)")
        assert a["compiles"] > 0
        b = run_once("process B (disk-warmed)")
        assert b["compiles"] == 0, b
        assert b["disk_loads"] > 0
        assert b["toks"] == a["toks"]

    # (the mesh-mismatch named-discard unit lives in the fast-lane
    # tests/test_sharding_plan.py)
