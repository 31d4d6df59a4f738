#!/usr/bin/env python3
"""chip_smoke.py -- the quickest proof that the system still starts on
the chip.

    python chip_smoke.py            the default pass: full width, needs
                                    platform "tpu", exits 0 only if
                                    every check of both phases held
    python chip_smoke.py --rehearse the same two phases at a tiny size
                                    on whatever backend JAX finds, with
                                    the Pallas kernels in interpret mode
                                    (how tier-1 covers this file)
    python chip_smoke.py --sweep    builder-run: every Pallas kernel
                                    entry point, forward and backward,
                                    against its own jnp reference
    python chip_smoke.py --four-chips
                                    builder-run: trainer over dp=4, the
                                    paged server at tp=2, and four
                                    one-chip replicas; asserts where
                                    the arrays live

Two phases in ONE process (a chip belongs to one process), through the
entry points a user calls:

* trainer -- transformer-base exactly as bench.py builds it (seq 256,
  batch 128, vocab 32,000, d512, 8 heads, 6+6 layers, d_inner 2048,
  bf16 AMP): startup, a few `Executor.run` steps, one
  `Executor.prepare(steps=K)` scan.
* server -- the same width as a paged decode bundle behind
  `PagedContinuousGenerationServer`: three identical waves of
  `submit()`s (one repeated prompt, one streamed), compared with the
  whole-loop incremental decode on the same device.

Nothing here catches a phase's exception: a raise or a failed check is
a traceback and a non-zero exit, and the last line of stdout is the
result JSON only when everything held.
"""
from __future__ import annotations

import argparse
import collections
import importlib.metadata
import json
import os
import re
import sys
import time

import numpy as np

FULL = {
    "trainer": dict(seq=256, batch=128, vocab=32000, d_model=512,
                    n_heads=8, n_layers=6, d_inner=2048,
                    run_steps=4, scan_steps=8),
    "server": dict(seq_len=32, max_out_len=48, vocab=32000, d_model=512,
                   n_heads=8, n_layers=6, d_inner=2048, n_slots=8,
                   block_size=16, n_blocks=24, n_prompt_entries=8,
                   n_prompts=5),
}
# the rehearsal keeps every routing rule in play: d % 128 == 0 and
# rows % 8 == 0 for layer_norm, rows % 32 == 0 and V % 128 == 0 for
# the cross-entropy kernel
TINY = {
    "trainer": dict(seq=16, batch=4, vocab=256, d_model=128, n_heads=2,
                    n_layers=1, d_inner=256, run_steps=4, scan_steps=4),
    "server": dict(seq_len=8, max_out_len=16, vocab=256, d_model=128,
                   n_heads=2, n_layers=1, d_inner=256, n_slots=4,
                   block_size=8, n_blocks=12, n_prompt_entries=4,
                   n_prompts=3),
}
START_ID, END_ID = 2, 1
SEED = 21


def say(tag, **fields):
    """One line per fact: `[tag] {json}`."""
    print(f"[{tag}] {json.dumps(fields, default=str)}", flush=True)


def check(ok, what):
    """A failed check is an exception like any other: it ends the run."""
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {what}")


# ---------------------------------------------------------------------
# what JAX compiled, and what the persistent cache gave back
# ---------------------------------------------------------------------
class CompileMeter:
    """Counts JAX's own compile events: how many backend compiles ran,
    how long tracing + lowering + backend compile took, and how many
    persistent-cache hits and misses there were."""

    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
    _BACKEND = _DURATIONS[2]
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring

        self.count = collections.Counter()
        self.secs = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_):
        self.count[event] += 1

    def _on_duration(self, event, secs, **_):
        if event in self._DURATIONS:
            self.secs += secs
            self.count[event] += 1

    def mark(self):
        return (self.count[self._BACKEND], self.secs,
                self.count[self._HIT], self.count[self._MISS])

    def since(self, mark):
        now = self.mark()
        return {"backend_compiles": now[0] - mark[0],
                "compile_s": round(now[1] - mark[1], 2),
                "cache_hits": now[2] - mark[2],
                "cache_misses": now[3] - mark[3]}


# ---------------------------------------------------------------------
# phase 1: the trainer
# ---------------------------------------------------------------------
def expected_train_kernels(c):
    """The Pallas kernels the routing rules select for this train
    step's shapes (ops/pallas/*.usable): layer-norm over the
    [batch*seq, d_model] rows and the fused cross-entropy over
    [batch*seq, vocab]. Attention at T <= 256 is the jnp composition
    by design (attention.sdpa_usable)."""
    rows = c["batch"] * c["seq"]
    return {("layer_norm", (rows, c["d_model"])),
            ("xent", (rows, c["vocab"]))}


def losses_ok(losses):
    """Finite at every step, lower at the end than at the start."""
    return bool(np.all(np.isfinite(losses))) and losses[-1] < losses[0]


def build_trainer(c, n_layers=None):
    """(main, startup, cost) exactly as bench.py's bench_transformer
    builds it; `n_layers` cuts depth only."""
    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    from paddle_tpu.models import transformer as T

    fluid.seed(SEED)
    with unique_name.guard():
        return T.build_program(
            seq_len=c["seq"], d_model=c["d_model"], n_heads=c["n_heads"],
            n_layers=n_layers or c["n_layers"], d_inner=c["d_inner"],
            vocab=c["vocab"], dropout_rate=0.0, with_optimizer=True,
            learning_rate=2.0, warmup_steps=8000)


def train_feed(c):
    r = np.random.RandomState(0)
    return {k: r.randint(0, c["vocab"], (c["batch"], c["seq"])).astype(
        np.int64) for k in ("src_ids", "tgt_ids", "label")}


def trainer_phase(c, meter, on_chip):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import amp
    from paddle_tpu.ops import pallas

    main, startup, cost = build_trainer(c)
    exe = fluid.Executor(fluid.TPUPlace(0))
    feed = train_feed(c)

    losses, step_s = [], []
    with amp.amp_guard(True):
        m0, t0 = meter.mark(), time.perf_counter()
        exe.run(startup)
        say("trainer", step="startup",
            wall_s=round(time.perf_counter() - t0, 2), **meter.since(m0))

        m0 = meter.mark()
        with pallas.record_routes() as routes:
            for i in range(c["run_steps"]):
                t0 = time.perf_counter()
                loss, = exe.run(main, feed=feed, fetch_list=[cost])
                step_s.append(time.perf_counter() - t0)
                losses.append(float(np.asarray(loss).reshape(-1)[0]))
        comp = meter.since(m0)
        say("trainer", step="run", first_call_s=round(step_s[0], 2),
            steady_step_s=[round(s, 4) for s in step_s[1:]], **comp)

        # which Pallas kernels the compiled step carries: the routing
        # decisions recorded while it traced and, on the chip, the
        # Mosaic custom calls of the lowered module itself
        if exe.disk_load_count:
            say("trainer", kernel_check="skipped: FLAGS_compile_cache "
                "rehydrated the executable, so nothing was traced")
        else:
            routed = {(k, shape) for k, shape, took in routes if took}
            say("trainer", routing=sorted(set(routes)))
            missing = expected_train_kernels(c) - routed
            check(not missing,
                  f"train step lacks routed kernels {missing}")
            single = exe.prepare(main, feed=feed, fetch_list=[cost])
            mosaic = collections.Counter(re.findall(
                r'kernel_name = "([^"]+)"', single.lowered_text()))
            say("trainer", mosaic_custom_calls=dict(mosaic))
            if on_chip:
                check(mosaic["layer_norm"] > 0
                      and mosaic["xent_forward"] > 0
                      and mosaic["xent_backward"] > 0,
                      f"lowered train step has Mosaic calls "
                      f"{dict(mosaic)}")

        m0, t0 = meter.mark(), time.perf_counter()
        prepared = exe.prepare(main, feed=feed, fetch_list=[cost],
                               steps=c["scan_steps"])
        out, = prepared.run(feed)
        first_s = time.perf_counter() - t0
        losses += [float(v) for v in np.asarray(out).reshape(-1)]
        t0 = time.perf_counter()
        out, = prepared.run(feed)
        scan_s = time.perf_counter() - t0
        losses += [float(v) for v in np.asarray(out).reshape(-1)]
        say("trainer", step="scan", steps=c["scan_steps"],
            first_call_s=round(first_s, 2), steady_call_s=round(scan_s, 4),
            fallback_reason=prepared.fallback_reason, **meter.since(m0))

    say("trainer", losses=[round(v, 5) for v in losses],
        aot_failures=exe.aot_failures)
    check(prepared.fallback_reason is None,
          f"scan fell back: {prepared.fallback_reason}")
    check(losses_ok(losses), f"losses not finite and decreasing: {losses}")
    check(not exe.aot_failures, f"AOT failures: {exe.aot_failures}")
    param = fluid.global_scope()._get("logits.w")
    check(param.devices() == {jax.devices()[0]},
          f"logits.w lives on {param.devices()}")
    return {"loss_first": losses[0], "loss_last": losses[-1]}


# ---------------------------------------------------------------------
# phase 2: the paged server
# ---------------------------------------------------------------------
def model_kwargs(c):
    return dict(seq_len=c["seq_len"], max_out_len=c["max_out_len"],
                d_model=c["d_model"], n_heads=c["n_heads"],
                n_layers=c["n_layers"], d_inner=c["d_inner"],
                vocab=c["vocab"], start_id=START_ID, end_id=END_ID)


def init_server_weights(c, exe, scope):
    """Random weights from SEED: the training build's startup program
    names every parameter the decode builds share."""
    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    from paddle_tpu.models import transformer as T

    fluid.seed(SEED)
    with unique_name.guard():
        _, startup, _ = T.build_program(
            seq_len=c["seq_len"], d_model=c["d_model"],
            n_heads=c["n_heads"], n_layers=c["n_layers"],
            d_inner=c["d_inner"], vocab=c["vocab"],
            with_optimizer=False, dropout_rate=0.0)
    exe.run(startup, scope=scope)


def build_server_programs(c, exe, scope, state_prefix, sharding=None):
    """Weights into `scope`, then the whole-loop incremental decode
    (the oracle) and the paged decode bundle at the same width.
    Returns (oracle program, its token buffer var, bundle)."""
    from paddle_tpu import unique_name
    from paddle_tpu.models import transformer as T
    from paddle_tpu.models.decode_engine import CacheConfig

    init_server_weights(c, exe, scope)
    kwargs = model_kwargs(c)
    with unique_name.guard():
        inc_main, _, _, inc_buf = T.build_incremental_decode_program(
            **kwargs)
    with unique_name.guard():
        bundle = T.build_decode_step_program(
            n_slots=c["n_slots"], state_prefix=state_prefix,
            cache=CacheConfig(layout="paged", block_size=c["block_size"],
                              n_blocks=c["n_blocks"],
                              n_prompt_entries=c["n_prompt_entries"]),
            sharding=sharding, **kwargs)
    return inc_main, inc_buf, bundle


def run_oracle(exe, scope, inc_main, inc_buf, prompts):
    from paddle_tpu.inference import apply_eos_sentinel

    rows, = exe.run(inc_main, feed={"src_ids": np.stack(prompts)},
                    fetch_list=[inc_buf], scope=scope)
    return apply_eos_sentinel(np.asarray(rows), end_id=END_ID)


def first_divergence(a, b):
    """Index of the first differing token, or None."""
    diff = np.nonzero(np.asarray(a) != np.asarray(b))[0]
    return int(diff[0]) if diff.size else None


def logit_gap(c, exe, scope, prompt, row, pos, tok_a, tok_b):
    """logit[tok_a] - logit[tok_b] at buffer position `pos`, from a
    third program: the training graph's teacher-forced forward in
    fp32 at matmul precision "highest" (what FLAGS_cpu_deterministic
    pins), fed the common prefix row[:pos]."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import layers, unique_name
    from paddle_tpu.models import transformer as T

    S, maxT = c["seq_len"], c["max_out_len"]
    prog, start = fluid.Program(), fluid.Program()
    with unique_name.guard(), fluid.program_guard(prog, start):
        src = layers.data("src_ids", shape=[S], dtype="int64")
        tgt = layers.data("tgt_ids", shape=[maxT], dtype="int64")
        label = layers.data("label", shape=[maxT], dtype="int64")
        _, logits = T.transformer(
            src, tgt, label, src_vocab=c["vocab"], tgt_vocab=c["vocab"],
            max_len=max(S, maxT, 256), d_model=c["d_model"],
            n_heads=c["n_heads"], n_layers=c["n_layers"],
            d_inner=c["d_inner"], dropout_rate=0.0, is_test=True)
    tgt_in = np.zeros((1, maxT), np.int64)
    tgt_in[0, :pos] = np.asarray(row)[:pos]
    with jax.default_matmul_precision("highest"):
        lg, = exe.run(prog, feed={"src_ids": np.asarray(prompt)[None],
                                  "tgt_ids": tgt_in,
                                  "label": np.zeros_like(tgt_in)},
                      fetch_list=[logits], scope=scope)
    lg = np.asarray(lg)[0, pos - 1].astype(np.float64)
    return float(lg[tok_a] - lg[tok_b]), float(np.abs(lg).max())


def compare(c, exe, scope, prompts, got_rows, want_rows, what):
    """Token rows against reference rows. Returns "exact", "ties" or
    "BROKEN" and prints every divergence it judged.

    Exact equality is the expectation. Where a row differs, everything
    before its first diverging position still agrees by construction
    of the search, and at that position the two candidate tokens must
    be a numerical tie: their fp32 logit gap (logit_gap) no larger
    than the rounding of a bf16-operand dot, 2**-8 of the largest
    logit -- what a default-precision TPU matmul in two differently
    structured programs may legitimately break either way. After a
    tie the two rows are different sequences and are not compared
    further. Anything larger is a bug."""
    verdict = "exact"
    for i, (p, got, want) in enumerate(zip(prompts, got_rows, want_rows)):
        pos = first_divergence(got, want)
        if pos is None:
            continue
        gap, top = logit_gap(c, exe, scope, p, want, pos,
                             int(want[pos]), int(got[pos]))
        tie = abs(gap) <= 2.0 ** -8 * max(top, 1.0)
        say("server", divergence=dict(
            between=what, request=i, position=pos,
            last_position=len(want) - 1, reference_token=int(want[pos]),
            token=int(got[pos]), logit_gap=gap, max_abs_logit=top,
            numerical_tie=tie))
        if not tie:
            verdict = "BROKEN"
        elif verdict == "exact":
            verdict = "ties"
    return verdict


def pool_drained(srv, n_blocks):
    """After close(): the only blocks still held are the radix tree's
    memo of finished generations, and evicting it empties the pool."""
    held = srv._blocks.in_use
    return (srv._radix.evict(n_blocks) == held
            and srv._blocks.free_count == n_blocks
            and srv._prefix.in_use == 0)


def server_phase(c, meter, on_chip):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.inference import PagedContinuousGenerationServer
    from paddle_tpu.ops import pallas
    from paddle_tpu.ops.paged_ops import ROUTE_LABELS

    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    inc_main, inc_buf, bundle = build_server_programs(
        c, exe, scope, "@smoke/")

    r = np.random.RandomState(SEED)
    distinct = r.randint(3, c["vocab"], (c["n_prompts"], c["seq_len"])
                         ).astype(np.int64)
    # one wave: every distinct prompt, then the first one again (the
    # prefix-cache hit); the second submission streams
    wave = list(distinct) + [distinct[0]]

    m0, t0 = meter.mark(), time.perf_counter()
    oracle = run_oracle(exe, scope, inc_main, inc_buf, wave)
    say("server", step="oracle", wall_s=round(time.perf_counter() - t0, 2),
        **meter.since(m0))

    m0, t0 = meter.mark(), time.perf_counter()
    with pallas.record_routes() as routes:
        srv = PagedContinuousGenerationServer(bundle, executor=exe,
                                              scope=scope)
    say("server", step="bind", programs=len(bundle.serves),
        wall_s=round(time.perf_counter() - t0, 2), **meter.since(m0))

    def run_wave():
        replies = [srv.submit(p, stream=(i == 1))
                   for i, p in enumerate(wave)]
        streamed = [tok for _seq, tok in replies[1]]
        rows = [np.asarray(rep.result(timeout=600)) for rep in replies]
        return rows, np.asarray(streamed, np.int64)

    waves = []
    for w in range(3):
        cc, m0, t0 = exe.compile_count, meter.mark(), time.perf_counter()
        with pallas.record_routes() as wave_routes:
            rows, streamed = run_wave()
        routes += wave_routes
        stats = meter.since(m0)
        say("server", wave=w, wall_s=round(time.perf_counter() - t0, 2),
            executor_compiles=exe.compile_count - cc, **stats)
        waves.append((rows, streamed, exe.compile_count - cc, stats))
    pool = srv.pool_stats()
    srv.close()
    say("server", routing=sorted(set(routes)), pool={
        k: pool[k] for k in ("prefix_hits", "prefix_misses",
                             "radix_admissions",
                             "plain_radix_admissions", "preemptions")},
        aot_failures=exe.aot_failures)
    # on the chip and in the rehearsal (interpret mode) the paged
    # self-attention read and the cross-attention read of the prompt
    # table are the kernel's, in every program that ran
    for label in ROUTE_LABELS.values():
        paged_read = (label, (c["n_slots"] + 1, 1, c["d_model"]))
        check({r for r in routes if r[:2] == paged_read}
              == {paged_read + (True,)},
              f"{label} did not take its kernel: "
              f"{sorted(set(routes))}")

    rows0, streamed0 = waves[0][0], waves[0][1]
    # every comparison is printed before any of them can end the run
    verdicts = {
        "wave0_vs_whole_loop_decode": compare(
            c, exe, scope, wave, rows0, oracle, "wave 0 / oracle"),
        "wave1_vs_wave0": compare(
            c, exe, scope, wave, waves[1][0], rows0, "wave 1 / wave 0"),
        "wave2_vs_wave1": compare(
            c, exe, scope, wave, waves[2][0], waves[1][0],
            "wave 2 / wave 1"),
        "repeated_prompt_within_wave0":
            "exact" if np.array_equal(rows0[0], rows0[-1]) else "BROKEN",
    }
    say("server", parity=verdicts)

    check(all(row.shape == (c["max_out_len"],) for row in rows0),
          "every request answers one row of max_out_len tokens")
    n = int(np.sum(rows0[1][1:] >= 0))
    check(np.array_equal(streamed0, rows0[1][1:1 + n]),
          "streamed tokens equal the whole-response row")
    check(pool["prefix_hits"] >= 1, f"no prefix hit: {pool}")
    for w in (1, 2):
        check(waves[w][2] == 0,
              f"wave {w} built {waves[w][2]} executables")
    check(waves[2][3]["backend_compiles"] == 0,
          f"steady-state wave still compiled: {waves[2][3]}")
    # what must hold on any backend: the same prompt through the same
    # programs decodes the same tokens, and no comparison differs by
    # more than a numerical tie
    check(verdicts["repeated_prompt_within_wave0"] == "exact",
          "the repeated prompt did not decode the same tokens")
    check("BROKEN" not in verdicts.values(),
          f"served tokens differ beyond a numerical tie: {verdicts}")
    if not on_chip:
        # fp32 on the CPU backend has no reduced-precision matmul:
        # there every comparison is byte-exact
        check(set(verdicts.values()) == {"exact"},
              f"CPU parity is not exact: {verdicts}")
    check(pool_drained(srv, c["n_blocks"]), "block pool did not drain")
    check(not exe.aot_failures, f"AOT failures: {exe.aot_failures}")
    state = scope._get(bundle.state["tok_buf"])
    check(state.devices() == {jax.devices()[0]},
          f"slot state lives on {state.devices()}")
    return verdicts


# ---------------------------------------------------------------------
# builder-run modes live below the default pass
# ---------------------------------------------------------------------
def _err(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-6))


def sweep_cases(tiny):
    """(name, shape note, usable()?, routed by default?, stages, tol)
    with stages = [(direction, thunk -> [(what, got, want)])], so a
    kernel whose forward compiles and whose backward is refused shows
    both. Shapes are the ones each kernel's routing admits at
    transformer-base (ISSUE 21 item 6); `tiny` shrinks them for the
    interpret-mode rehearsal of this harness."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas
    from paddle_tpu.ops.pallas import (attention, attention_block,
                                       ffn_block, grouped_matmul,
                                       layer_norm, paged_attention, xent)

    key = jax.random.PRNGKey(SEED)

    def rnd(i, shape, dtype=jnp.float32, scale=1.0):
        return (jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * scale).astype(dtype)

    def fwd_bwd(fn, ref, args, wrt):
        """Stages comparing fn with ref: the value, then the grads of
        sum(out * w) with respect to args[wrt]."""
        def scalar(f, w):
            return lambda *a: jnp.sum(f(*a).astype(jnp.float32) * w)

        def bwd():
            w = rnd(99, jax.eval_shape(ref, *args).shape)
            got = jax.jit(jax.grad(scalar(fn, w), wrt))(*args)
            want = jax.grad(scalar(ref, w), wrt)(*args)
            return list(zip([f"d_arg{i}" for i in wrt], got, want))
        return [("fwd", lambda: [("out", jax.jit(fn)(*args), ref(*args))]),
                ("bwd", bwd)]

    cases = []

    def ln_case(n, d):
        x, s, b = rnd(1, (n, d)), rnd(2, (d,)), rnd(3, (d,))
        routed = layer_norm.usable(n, d)

        # through the routing rule, as the layer_norm op does
        fn = (lambda *a: layer_norm.layer_norm(*a, 1e-5)) if routed \
            else (lambda *a: layer_norm._ln_ref(*a, 1e-5))
        cases.append(("layer_norm", f"({n},{d}) f32", routed, True,
                      fwd_bwd(fn, lambda *a: layer_norm._ln_ref(*a, 1e-5),
                              (x, s, b), (0, 1, 2)), 1e-4))

    ln_case(256 if tiny else 32768, 128 if tiny else 512)
    ln_case(9, 128 if tiny else 512)

    n, v = (64, 256) if tiny else (32768, 32000)
    logits = rnd(4, (n, v), jnp.bfloat16, 2.0)
    label = jax.random.randint(jax.random.fold_in(key, 5), (n,), 0, v)
    dloss = rnd(6, (n,))

    # the reference upcasts to fp32 ([rows, V] x 4 bytes), so it scores
    # the first and last row-blocks, not all 32,768 rows
    rows = np.r_[0:min(n, 256), max(0, n - 256):n]
    l_r, y_r, g_r = logits[rows], label[rows], dloss[rows]

    def ref_loss(l, y):
        lf = l.astype(jnp.float32)
        lse_r = jax.scipy.special.logsumexp(lf, axis=-1)
        picked = jnp.take_along_axis(lf, y[:, None], 1)[:, 0]
        return (0.9 * (lse_r - picked)
                + 0.1 * (lse_r - lf.mean(-1))), lse_r

    def xent_fwd():
        loss, lse = jax.jit(lambda l, y: xent.xent_forward(l, y, 0.1))(
            logits, label)
        want_loss, want_lse = ref_loss(l_r, y_r)
        return [("loss", loss[rows], want_loss),
                ("lse", lse[rows], want_lse)]

    def xent_bwd():
        dx = jax.jit(lambda l, y, g: xent.xent_backward(l, y, g, 0.1))(
            logits, label, dloss)
        want = jax.grad(lambda l: jnp.sum(ref_loss(l, y_r)[0] * g_r))(
            l_r.astype(jnp.float32))
        return [("dlogits", dx[rows], want)]
    cases.append(("xent", f"({n},{v}) bf16", xent.usable(logits, label),
                  True, [("fwd", xent_fwd), ("bwd", xent_bwd)], 2e-2))

    def attn_case(name, fn, usable, b, h, t, d, causal):
        q, k, vv = (rnd(i, (b, h, t, d), jnp.bfloat16) for i in (7, 8, 9))
        scale = d ** -0.5

        cases.append((
            name, f"B{b} H{h} T{t} Dh{d} bf16 causal={causal}",
            usable(q, k, vv), True,
            fwd_bwd(lambda *a: fn(*a, scale, causal),
                    lambda *a: pallas.reference_attention(
                        *a, scale, causal),
                    (q, k, vv), (0, 1, 2)), 3e-2))

    t_short, t_long = (264, 1024) if tiny else (512, 1024)
    for causal in (False, True):
        attn_case("sdpa_short", attention.sdpa_short,
                  attention.sdpa_usable, 1 if tiny else 16, 8, t_short,
                  64, causal)
        attn_case("flash_attention", attention.flash_attention,
                  attention.usable, 1 if tiny else 4, 2 if tiny else 8,
                  t_long, 64, causal)

    # grouped-query attention at the long sequence the LFM2 cell
    # trains on: a key-value head under four query heads, read in place
    tq, hq, hkv = (1024, 4, 2) if tiny else (8192, 4, 1)
    q, k, vv = (rnd(i, (1, h, tq, 64), jnp.bfloat16)
                for i, h in ((27, hq), (28, hkv), (29, hkv)))

    def gqa_reference(q, k, v):
        return pallas.reference_attention(
            q, jnp.repeat(k, hq // hkv, 1), jnp.repeat(v, hq // hkv, 1),
            0.125, True)
    cases.append((
        "flash_attention", f"B1 H{hq} Hkv{hkv} T{tq} Dh64 bf16 causal",
        attention.usable(q, k, vv), True,
        fwd_bwd(lambda *a: attention.flash_attention(*a, 0.125, True),
                gqa_reference, (q, k, vv), (0, 1, 2)), 3e-2))

    # the dropless expert layer's grouped products: rows sorted by
    # expert, the last group's rows belong to experts held elsewhere
    rows_, dk, dn, g = (256, 128, 256, 4) if tiny else (8192, 2048, 3072, 8)
    lhs = rnd(30, (rows_, dk), jnp.bfloat16)
    rhs = rnd(31, (g, dk, dn), jnp.bfloat16, dk ** -0.5)
    cut = np.sort(np.random.RandomState(SEED).randint(0, rows_ // 2, g))
    sizes = jnp.asarray(np.diff(np.r_[0, cut, rows_]), jnp.int32)
    cases.append((
        "grouped_matmul", f"({rows_},{dk}) x ({g},{dk},{dn}) bf16, "
        f"{int(cut[-1])} rows in {g} groups",
        grouped_matmul.usable(lhs, rhs), True,
        fwd_bwd(lambda a, b_: grouped_matmul.grouped_matmul(a, b_, sizes),
                lambda a, b_: grouped_matmul.grouped_matmul_reference(
                    a, b_, sizes), (lhs, rhs), (0, 1)), 3e-2))

    b, t, d, f, heads = (2, 16, 128, 256, 2) if tiny \
        else (16, 256, 512, 2048, 8)
    x = rnd(10, (b, t, d), jnp.bfloat16)
    wqkv = rnd(11, (d, 3 * d), jnp.bfloat16, d ** -0.5)
    wo = rnd(12, (d, d), jnp.bfloat16, d ** -0.5)

    sc = (d // heads) ** -0.5
    cases.append((
        "attention_block", f"x({b},{t},{d}) H{heads} bf16",
        attention_block.usable(x, wqkv, heads), False,
        fwd_bwd(lambda *a: attention_block.attention_block(
                    *a, heads, sc, True),
                lambda *a: attention_block.attention_block_reference(
                    *a, heads, sc, True),
                (x, wqkv, wo), (0, 1, 2)), 3e-2))

    w1 = rnd(13, (d, f), jnp.bfloat16, d ** -0.5)
    b1, b2 = rnd(14, (f,)), rnd(15, (d,))
    w2 = rnd(16, (f, d), jnp.bfloat16, f ** -0.5)

    cases.append(("ffn_block", f"x({b},{t},{d}) F{f} bf16",
                  ffn_block.usable(x, w1), False,
                  fwd_bwd(ffn_block.ffn_block,
                          ffn_block.ffn_block_reference,
                          (x, w1, b1, w2, b2), (0, 1, 2, 3, 4)), 3e-2))

    # the serve cell's size (BENCHMARK.json, transformer-big-serve):
    # 32 lanes and the dustbin, 16 heads of 64, 1,280 blocks of 16
    rws, hh, dh, nb, bs, pages = (5, 2, 64, 24, 8, 4) if tiny \
        else (33, 16, 64, 1280, 16, 16)
    q = rnd(17, (rws, 1, hh * dh))
    pk, pv = (rnd(i, (nb * bs, hh * dh)) for i in (18, 19))
    rs = np.random.RandomState(SEED)
    # lanes own disjoint blocks; the dustbin's cleared row names block 0
    tab = np.zeros((rws, pages), np.int32)
    tab[:-1] = 1 + rs.permutation(nb - 1)[:(rws - 1) * pages].reshape(
        rws - 1, pages)
    tab = jnp.asarray(tab)
    step = jnp.asarray(np.append(
        rs.randint(0, pages * bs, (rws - 1,)), 0).astype(np.int32))
    kw = dict(block_size=bs, n_heads=hh, scale=dh ** -0.5)

    def paged_fwd(args, kw):
        def fwd():
            got = jax.jit(
                lambda *a: paged_attention.paged_decode_attention(
                    *a, **kw))(*args)
            return [("out", got,
                     paged_attention.paged_attention_reference(
                         *args, **kw))]
        return [("fwd", fwd)]  # inference-only kernel
    cases.append(("paged_attention",
                  f"q({rws},1,{hh * dh}) pool({nb * bs},{hh * dh}) "
                  f"table({rws},{pages}) f32",
                  paged_attention.usable(q, pk, tab, bs), True,
                  paged_fwd((q, pk, pv, tab, step), kw), 1e-3))

    # the same cell's cross-attention read: every lane on one of the
    # 129 prompt entries (the dustbin last), an entry one block of 256
    # rows, every lane at the last position
    ents, seq = (5, 16) if tiny else (129, 256)
    tk, tv = (rnd(i, (ents, seq, hh * dh)).reshape(-1, hh * dh)
              for i in (20, 21))
    ref = jnp.asarray(np.append(
        rs.randint(0, ents - 1, (rws - 1,)), ents - 1
    ).astype(np.int32))[:, None]
    last = jnp.full((rws,), seq - 1, jnp.int32)
    cases.append(("paged_attention_prompt_table",
                  f"q({rws},1,{hh * dh}) table({ents},{seq},{hh * dh}) "
                  f"ref({rws},1) f32",
                  paged_attention.usable(q, tk, ref, seq), True,
                  paged_fwd((q, tk, tv, ref, last),
                            dict(kw, block_size=seq)), 1e-3))

    # the Olmo-Hybrid serve cell's full attention: 30 heads of 128 over
    # bfloat16 pools of 3,584 blocks of 64, tables of 144 pages, the
    # lanes streamed; its reference is the route it replaces
    from paddle_tpu.ops import paged_ops

    rws, hh, dh, nb, bs, pages = (5, 4, 32, 24, 16, 4) if tiny \
        else (33, 30, 128, 3584, 64, 144)
    q = rnd(22, (rws, 1, hh * dh), jnp.bfloat16)
    pk, pv = (rnd(i, (nb * bs, hh * dh), jnp.bfloat16) for i in (23, 24))
    tab = jnp.asarray(rs.randint(0, nb, (rws, pages)).astype(np.int32))
    step = jnp.asarray(rs.randint(0, pages * bs, (rws,)).astype(np.int32))
    kw = dict(block_size=bs, n_heads=hh, scale=dh ** -0.5)

    def streamed_fwd():
        args = (q, pk, pv, tab, step)
        got = jax.jit(
            lambda *a: paged_attention.paged_decode_attention_streamed(
                *a, **kw))(*args)
        return [("out", got, paged_ops.paged_attention_by_lane(
            *args, n_kv_heads=hh, **kw))]
    cases.append(("paged_attention_streamed",
                  f"q({rws},1,{hh * dh}) pool({nb * bs},{hh * dh}) "
                  f"table({rws},{pages}) bf16",
                  paged_attention.streams(q, pk, tab, bs, hh, hh), True,
                  [("fwd", streamed_fwd)], 2e-2))
    return cases


def kernel_sweep(tiny):
    """Compile and run every kernel entry point against its reference.
    A refusal is a row of the table, not a crash: this mode exists to
    collect the compiler's verdict on each kernel. Returns False when
    a ROUTED kernel was refused or disagreed with its reference."""
    table, ok = [], True
    for name, shape, usable, routed, stages, tol in sweep_cases(tiny):
        for direction, thunk in stages:
            row = {"kernel": name, "direction": direction,
                   "shape": shape, "usable": bool(usable),
                   "routed_by_default": routed}
            t0 = time.perf_counter()
            try:
                errs = {what: _err(got, want)
                        for what, got, want in thunk()}
                row.update(status="ok" if max(errs.values()) <= tol
                           else "mismatch", rel_err=errs, tol=tol)
            except Exception as e:  # the compiler's message IS the row
                row.update(status="refused", message=(
                    f"{type(e).__name__}: {e}")[:1500])
            row["wall_s"] = round(time.perf_counter() - t0, 2)
            say("sweep", **row)
            table.append(row)
            if routed and row["status"] != "ok":
                ok = False
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kernel_sweep.json", "w") as f:
        json.dump(table, f, indent=1)
    return ok


def four_chips(cfg, meter):
    """Trainer over dp=4, paged server at tp=2, four one-chip
    replicas; every leg asserts where its arrays live. Like the sweep,
    this mode collects a verdict per leg (a four-chip call is the
    expensive one) and fails at the end if any leg failed."""
    import traceback

    import jax

    import paddle_tpu as fluid
    from paddle_tpu import amp
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.inference import PagedContinuousGenerationServer
    from paddle_tpu.models.decode_engine import POOL_MARK, ShardingConfig
    from paddle_tpu.ops import pallas

    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 devices, found {len(devs)}")
    c, s = cfg["trainer"], cfg["server"]
    feed = train_feed(c)

    def ids(arr):
        return sorted(d.id for d in arr.devices())

    def dp4():
        """CompiledProgram.with_data_parallel over four devices."""
        main, startup, cost = build_trainer(c)
        scope, exe = Scope(), fluid.Executor(fluid.TPUPlace(0))
        dp = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=cost.name,
            places=[fluid.TPUPlace(i) for i in range(4)])
        losses, step_s = [], []
        with amp.amp_guard(True), pallas.record_routes() as routes:
            exe.run(startup, scope=scope)
            for _ in range(6):
                t0 = time.perf_counter()
                loss, = exe.run(dp, feed=feed, fetch_list=[cost],
                                scope=scope)
                step_s.append(round(time.perf_counter() - t0, 4))
                losses.append(float(np.asarray(loss).reshape(-1)[0]))
        w = scope._get("logits.w")
        say("four_chips", leg="dp4", losses=[round(v, 5) for v in losses],
            step_s=step_s, logits_w_devices=ids(w),
            routing=sorted(set(routes)))
        check(losses_ok(losses), f"dp4 losses {losses}")
        check(w.devices() == set(devs[:4]),
              f"dp4 params live on {w.devices()}")

    def tp2():
        """The paged server tensor-parallel on devices [0, 1]."""
        scope, exe = Scope(), fluid.Executor(fluid.TPUPlace(0))
        inc_main, inc_buf, bundle = build_server_programs(
            s, exe, scope, "@tp/", sharding=ShardingConfig(tp=2))
        rr = np.random.RandomState(SEED)
        prompts = rr.randint(3, s["vocab"], (s["n_prompts"], s["seq_len"])
                             ).astype(np.int64)
        oracle = run_oracle(exe, scope, inc_main, inc_buf, prompts)
        with pallas.record_routes() as routes:
            srv = PagedContinuousGenerationServer(
                bundle, executor=exe, scope=scope, mesh_devices=devs[:2])
            rows = [np.asarray(rep.result(timeout=600))
                    for rep in [srv.submit(p) for p in prompts]]
            again = [np.asarray(rep.result(timeout=600))
                     for rep in [srv.submit(p) for p in prompts]]
            srv.close()
        pool_name = "@tp/self_k0" + POOL_MARK  # layer 0's shared K pool
        pool = scope._get(pool_name)
        verdicts = {
            "tp2_vs_whole_loop_decode": compare(
                s, exe, scope, prompts, rows, oracle, "tp2 / oracle"),
            "tp2_second_pass_vs_first": compare(
                s, exe, scope, prompts, again, rows,
                "tp2 pass 2 / pass 1")}
        say("four_chips", leg="tp2", pool_var=pool_name,
            pool_devices=ids(pool), pool_shard_shape=list(
                pool.addressable_shards[0].data.shape),
            pool_shape=list(pool.shape), parity=verdicts,
            layer_norm_routing=sorted({r for r in routes
                                       if r[0] == "layer_norm"}))
        check(pool.devices() == set(devs[:2]),
              f"tp2 pool lives on {pool.devices()}")
        check("BROKEN" not in verdicts.values(),
              f"tp2 tokens differ beyond a numerical tie: {verdicts}")
        check(pool_drained(srv, s["n_blocks"]),
              "tp2 block pool did not drain")

    def replicas():
        """Four one-chip replicas, Executor(TPUPlace(i)), depth 1."""
        main, startup, cost = build_trainer(c, n_layers=1)
        for i in range(4):
            scope, exe = Scope(), fluid.Executor(fluid.TPUPlace(i))
            losses = []
            with amp.amp_guard(True):
                exe.run(startup, scope=scope)
                for _ in range(3):
                    loss, = exe.run(main, feed=feed, fetch_list=[cost],
                                    scope=scope, return_numpy=False)
                    losses.append(loss)
            w = scope._get("logits.w")
            say("four_chips", leg=f"replica{i}",
                loss_devices=ids(losses[-1]), logits_w_devices=ids(w))
            check(w.devices() == {devs[i]}
                  and losses[-1].devices() == {devs[i]},
                  f"replica {i} arrays live on {w.devices()}")
            check(losses_ok([float(np.asarray(v).reshape(-1)[0])
                             for v in losses]), f"replica {i} losses")

    failed = []
    for leg in (dp4, tp2, replicas):
        m0, t0 = meter.mark(), time.perf_counter()
        try:
            leg()
            status = "ok"
        except Exception:  # the leg's traceback is its verdict
            traceback.print_exc()
            status = "FAILED"
            failed.append(leg.__name__)
        say("four_chips", leg=leg.__name__, status=status,
            wall_s=round(time.perf_counter() - t0, 2), **meter.since(m0))
    check(not failed, f"four-chip legs failed: {failed}")


# ---------------------------------------------------------------------
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size, any backend, Pallas kernels in "
                         "interpret mode")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--sweep", action="store_true",
                      help="kernel sweep instead of the two phases")
    mode.add_argument("--four-chips", action="store_true",
                      help="multi-chip placement run instead of the "
                           "two phases")
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    from paddle_tpu import native
    from paddle_tpu.core.compile_cache import (enable_persistent_cache,
                                               exe_cache_root)
    from paddle_tpu.flags import FLAGS
    from paddle_tpu.ops.pallas import attention

    cache_dir = enable_persistent_cache()
    meter = CompileMeter()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    say("smoke", **device, jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu, compile_cache_dir=cache_dir,
        cache_dir_from_env=bool(os.environ.get(
            "JAX_COMPILATION_CACHE_DIR")),
        exe_cache=f"{FLAGS.compile_cache} at {exe_cache_root()}",
        mode="rehearsal" if args.rehearse else "full")
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.rehearse:
        sys.exit(f"chip_smoke: JAX found platform {dev.platform!r}, not "
                 f"'tpu' (JAX_PLATFORMS="
                 f"{os.environ.get('JAX_PLATFORMS')!r}); only --rehearse "
                 f"may run elsewhere")
    say("smoke", native_available=native.available(),
        native_build_error=native.build_error())
    if args.rehearse:
        attention.force_interpret(True)
    cfg = TINY if args.rehearse else FULL

    t0 = time.perf_counter()
    if args.sweep:
        check(kernel_sweep(args.rehearse),
              "a routed kernel was refused or disagrees with its "
              "reference (see the [sweep] rows)")
    elif args.four_chips:
        four_chips(cfg, meter)
    else:
        say("smoke", trainer=trainer_phase(cfg["trainer"], meter, on_chip))
        say("smoke", server=server_phase(cfg["server"], meter, on_chip))
    say("smoke", total_wall_s=round(time.perf_counter() - t0, 1),
        **meter.since((0, 0.0, 0, 0)))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
