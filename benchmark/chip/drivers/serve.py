"""Driver of the serving cells: a closed loop of callers against
`PagedContinuousGenerationServer.submit(stream=True, stream_cb=...)`.

Every caller submits its next prompt when its reply ends. The load is
driven from the server's own stream callbacks (the scheduler thread
calls them between two dispatches), so no caller thread competes with
the scheduler for the interpreter, and a caller's next request is in the
queue before the next admission is planned. Times are read in those
callbacks: one clock read a burst.

Set-up makes the weights from the seed, builds the paged bundle and the
server, sends traffic of every admission tier and bucket the cell can
use (the serve programs compile at their first dispatch), fills the
prompt table where the mix says so, starts the callers staggered over
`ramp_s`, and lets the loop settle. After the window a sample of the
finished requests, drawn from the seed, is held to the reference.
"""
import functools
import gc
import threading
import time

import numpy as np

from .. import compare, program_map, traffic
from ..reference import transformer2017 as R

START_ID, END_ID = 2, 1


def _model_cfg(c):
    return {k: c[k] for k in ("d_model", "d_inner", "n_heads",
                              "n_layers", "vocab", "init_gain",
                              "silent_ids") if k in c}


def build_server(c, seed):
    """(server, executor, scope): the weights from the seed in a scope
    of their own, the paged decode bundle, the server bound to both."""
    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.inference import PagedContinuousGenerationServer
    from paddle_tpu.models import transformer as T
    from paddle_tpu.models.decode_engine import CacheConfig

    scope, exe = Scope(), fluid.Executor(fluid.TPUPlace(0))
    model = dict(seq_len=c["seq_len"], d_model=c["d_model"],
                 n_heads=c["n_heads"], n_layers=c["n_layers"],
                 d_inner=c["d_inner"], vocab=c["vocab"])
    with unique_name.guard():
        # the training build's startup program names every parameter
        # the decode builds share (chip_smoke.py init_server_weights)
        _, startup, _ = T.build_program(
            with_optimizer=False, dropout_rate=0.0, **model)
    exe.run(startup, scope=scope)
    weights = program_map.to_program(
        R.make_params(seed, _model_cfg(c)), c["n_layers"])
    for name, value in weights.items():
        scope._set(name, value)
    del weights
    with unique_name.guard():
        bundle = T.build_decode_step_program(
            n_slots=c["n_slots"], state_prefix="@bench/",
            cache=CacheConfig(layout="paged",
                              block_size=c["block_size"],
                              n_blocks=c["n_blocks"],
                              n_prompt_entries=c["n_prompt_entries"]),
            max_out_len=c["max_out_len"], start_id=START_ID,
            end_id=END_ID, **model)
    srv = PagedContinuousGenerationServer(
        bundle, executor=exe, scope=scope,
        steps_per_tick=c["steps_per_tick"],
        drain_steps=c["drain_steps"])
    return srv, exe, scope


class Request:
    __slots__ = ("prompt", "caller", "t_submit", "t_first", "t_last",
                 "t_done", "finish", "tokens", "reply", "on_burst",
                 "on_done")

    def __init__(self, prompt, caller=None):
        self.prompt, self.caller = prompt, caller
        self.t_first = self.t_last = self.t_done = self.finish = None
        self.tokens = []
        self.on_burst = self.on_done = None


class Load:
    """Submits requests and keeps their times. Every callback of the
    server lands in `_cb`, on the scheduler thread."""

    def __init__(self, srv, tracer):
        self.srv, self.tracer = srv, tracer
        self.errors = []
        self.gaps = []          # (time, seconds a token) of later bursts
        self.inflight = set()

    def submit(self, req):
        req.t_submit = time.perf_counter()
        self.inflight.add(req)
        with self.tracer.span("submit"):
            req.reply = self.srv.submit(
                req.prompt, stream=True,
                stream_cb=lambda chunk, seq, fin, r=req:
                    self._cb(r, chunk, fin))
        return req

    def _cb(self, req, chunk, fin):
        try:
            now = time.perf_counter()
            if fin is None:
                if req.t_first is None:
                    req.t_first = now
                else:
                    self.gaps.append(
                        (now, (now - req.t_last) / len(chunk)))
                req.t_last = now
                req.tokens.extend(chunk.tolist())
                if req.on_burst is not None:
                    req.on_burst(req)
            else:
                req.t_done, req.finish = now, fin
                self.inflight.discard(req)
                if req.on_done is not None:
                    req.on_done(req)
        except BaseException as e:  # the server swallows what a
            self.errors.append(repr(e))  # callback raises; keep it

    def cancel_all(self, timeout=120.0):
        for req in list(self.inflight):
            req.reply.cancel()
        end = time.monotonic() + timeout
        while self.inflight and time.monotonic() < end:
            time.sleep(0.01)
        if self.inflight:
            raise RuntimeError(f"{len(self.inflight)} requests did not "
                               f"end after cancel")


class Warmup:
    """Sends one group of `k` same-tier requests for every admission
    bucket, so that every serve program the window can use has run,
    then admits `fill` (the prompts a warm table holds).

    Every group but the first is submitted from a stream callback,
    hence between two dispatches, while every lane it needs is free:
    the next admission takes the whole group together, and `k` decides
    the bucket. Miss groups are new prompts, cancelled once each has
    streamed a burst (their prompt entries stay in the table). Hit
    groups are copies of a prompt whose first request is still
    decoding (the host). Radix groups are copies of the host's prompt
    after it has retired and its generation is memoised; they end by
    themselves after replaying a short tail."""

    def __init__(self, load, tiers, n_slots, new_prompt, fill=()):
        self.load, self.new_prompt = load, new_prompt
        buckets = [b for b in (2 ** i for i in range(16))
                   if b <= n_slots]
        self.plan = [("miss", k) for k in buckets]
        if "hit" in tiers or "radix" in tiers:
            self.plan.append(("host", 1))
        if "hit" in tiers:
            self.plan += [("hit", min(k, n_slots - 1)) for k in buckets]
        if "radix" in tiers:
            self.plan.append(("retire", 0))
            self.plan += [("radix", k) for k in buckets]
        elif "hit" in tiers:
            self.plan.append(("drop_host", 0))
        fill = list(fill)
        while fill:
            self.plan.append(("fill", fill[-n_slots:]))
            del fill[-n_slots:]
        self.group, self.kind = [], None
        self.host = None
        self.done = threading.Event()

    def start(self):
        """From the main thread, with the server idle: the first group
        is a single request, so no race decides its bucket."""
        self._step()

    def _send(self, prompts):
        out = []
        for p in prompts:
            req = Request(p)
            req.on_burst = req.on_done = self._event
            out.append(self.load.submit(req))
        return out

    def _event(self, _req):
        if not self.done.is_set():
            self._step()

    def _step(self):
        live = [r for r in self.group if r.finish is None]
        if live:
            if self.kind != "radix" and all(
                    r.t_first is not None for r in live):
                for r in live:
                    r.reply.cancel()
            return
        while self.plan:
            kind, arg = self.plan[0]
            if kind == "retire":
                if self.host.finish is None:
                    return      # its bursts bring us back here
                if self.host.finish not in ("length", "eos"):
                    raise RuntimeError(
                        f"the warm-up's host ended {self.host.finish}")
            self.plan.pop(0)
            if kind == "retire":
                continue
            if kind == "drop_host":
                self.host.reply.cancel()
                continue
            if kind == "host":
                self.host, = self._send([self.new_prompt()])
                self.group, self.kind = [], kind
                return          # its first burst brings us back
            if kind == "miss":
                prompts = [self.new_prompt() for _ in range(arg)]
            elif kind == "fill":
                prompts = arg
            else:
                prompts = [self.host.prompt] * arg
            self.kind = kind
            self.group = self._send(prompts)
            return
        self.done.set()


def _percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


def run(ctx):
    c, spec = ctx.sizes, ctx.traffic
    if ctx.rehearse:
        from paddle_tpu.ops.pallas import attention

        attention.force_interpret(True)
    t_phase = time.perf_counter()
    srv, exe, scope = build_server(c, ctx.seed)
    ctx.note(build_s=time.perf_counter() - t_phase)
    mix = traffic.ClosedLoop(ctx.seed, spec, c)
    load = Load(srv, ctx.tracer)
    rng = np.random.default_rng([int(ctx.seed), 3])

    def junk_prompt():
        return rng.integers(spec["id_low"], c["vocab"], c["seq_len"],
                            dtype=np.int64)

    # most popular first in the list: groups are cut from its end, so
    # the most popular prompts are admitted last and are the freshest
    fill = mix.by_popularity(c["n_prompt_entries"]) \
        if spec.get("fill_table") else ()
    warm = Warmup(load, spec["warm_tiers"], c["n_slots"], junk_prompt,
                  fill)
    t_phase = time.perf_counter()
    warm.start()
    if not warm.done.wait(spec["warm_timeout_s"]):
        raise RuntimeError(f"warm-up did not finish; left {warm.plan}, "
                           f"errors {load.errors}")
    load.cancel_all()
    ctx.note(warm_s=time.perf_counter() - t_phase)

    # the closed loop
    # a traced run measures the traced window only: stopping the
    # profiler takes seconds that belong to no request
    seconds = ctx.trace_seconds if ctx.profile else ctx.seconds
    state = {"t0": None, "t_end": None, "armed": False,
             "stopping": False}
    finished = []
    window_done = threading.Event()

    def on_done(req):
        # the window runs from one request's end to another's: from
        # the first after the loop is armed to the first after
        # `seconds` more
        finished.append(req)
        now = req.t_done
        if state["t0"] is None:
            if state["armed"]:
                state["t0"] = now
        elif not state["stopping"] and now - state["t0"] >= seconds:
            state["t_end"] = now
            state["stopping"] = True
            window_done.set()
        if not state["stopping"]:
            start_caller(req.caller)

    def start_caller(i):
        req = Request(mix.next_prompt(), caller=i)
        req.on_done = on_done
        load.submit(req)

    t_ramp = time.monotonic()
    for i in range(spec["callers"]):
        due = t_ramp + i * spec["ramp_s"] / spec["callers"]
        time.sleep(max(0.0, due - time.monotonic()))
        start_caller(i)
    time.sleep(spec["settle_s"])
    at_setup = ctx.meter.mark()
    ctx.counters["cache_hits_at_setup"] = at_setup["cache_hits"]
    ctx.counters["backend_compiles_at_setup"] = \
        at_setup["backend_compiles"]
    gc.collect()
    gc.freeze()     # as in the training driver
    stats0 = srv.stats()
    if ctx.profile:
        ctx.tracer.start()
    setup_s = ctx.clock.setup_s()
    state["armed"] = True
    if not window_done.wait(seconds + spec["window_timeout_s"]):
        raise RuntimeError(f"no request ended the window; errors "
                           f"{load.errors}")
    stats1 = srv.stats()
    ctx.tracer.stop()
    in_window = ctx.meter.since(at_setup)
    load.cancel_all()
    ctx.memory_peak = ctx.read_memory_peak()
    t0, t_end = state["t0"], state["t_end"]
    window_s = t_end - t0

    def delta(a, b, *path):
        for k in path:
            a, b = a[k], b[k]
        return b - a

    counters = ctx.counters
    counters["compiles_in_window"] = \
        in_window["backend_compiles"] + in_window["cache_hits"]
    counters["dispatches"] = delta(stats0, stats1, "ticks")
    for k in ("prefix_hits", "prefix_misses", "radix_admissions",
              "preemptions", "pause_events", "evictions",
              "radix_evicted_blocks"):
        counters[k] = delta(stats0, stats1, "block_pool", k)
    tel0, tel1 = (s.get("device_telemetry", {}) for s in (stats0, stats1))
    if "ticks" in tel1:
        counters["device_ticks"] = tel1["ticks"] - tel0.get("ticks", 0)
        occ = tel1["occupancy_integral"] - tel0.get(
            "occupancy_integral", 0)
        if counters["device_ticks"]:
            counters["mean_live_lanes"] = occ / counters["device_ticks"]
        if ctx.profile:
            counters["traced_ticks"] = counters["device_ticks"]

    done = [r for r in finished if t0 < r.t_done <= t_end]
    if not done:
        raise RuntimeError("no request ended inside the window")
    good = [r for r in done if r.finish in ("length", "eos")
            and r.tokens]
    failed = len(done) - len(good) + len(load.errors)
    out_tokens = sum(len(r.tokens) for r in good)
    counters["window_tokens_all"] = out_tokens
    ttft = [(r.t_first - r.t_submit) * 1e3 for r in good]
    tpot = [(r.t_last - r.t_first) / (len(r.tokens) - 1) * 1e3
            for r in good if len(r.tokens) > 1]
    gaps = [g for t, g in load.gaps if t0 < t <= t_end]
    durs = [(r.t_done - r.t_submit) * 1e3 for r in good]
    ctx.note(requests=len(done), failed=failed, window_s=window_s,
             request_ms_least=min(durs), request_ms_greatest=max(durs),
             request_ms_median=float(np.median(durs)),
             ttft_ms_median=float(np.median(ttft)),
             tpot_ms_median=float(np.median(tpot)),
             sent=mix.sent)
    ctx.write_times({
        "done_s": [r.t_done - t0 for r in good], "ttft_ms": ttft,
        "tpot_ms": tpot, "tokens": [len(r.tokens) for r in good]})

    # the sample the reference checks, the longest request in it
    n_check = min(c["check_requests"], len(good))
    pick = list(rng.choice(len(good), size=n_check, replace=False))
    longest = int(np.argmax([len(r.tokens) for r in good]))
    if longest not in pick:
        pick[0] = longest
    sample = []
    for i in pick:
        r = good[i]
        sample.append((np.asarray(r.prompt), np.asarray(
            r.reply.result(timeout=60.0)), list(r.tokens)))
    n_done, errors = len(done), list(load.errors)
    ctx.write_sample(prompts=np.stack([p for p, _, _ in sample]),
                     rows=np.stack([r for _, r, _ in sample]),
                     seed=np.int64(ctx.seed))
    srv.close()
    for name in list(scope.local_var_names()):
        scope.erase(name)
    del srv, exe, scope, load, warm, finished, done, good
    gc.collect()

    compared = hold_sample(c, check_sample(c, ctx.seed, sample))
    compared.require("no_request_failed", failed == 0,
                     "; ".join(errors)[:300] or None)
    return {
        "attempted": n_done, "failed": failed,
        "compared": compared,
        # between its callbacks the scheduler thread is the program's
        "unattributed": "server_cycle_unattributed",
        "end_to_end": {
            "serve_tokens_per_s": out_tokens / window_s,
            "ttft_ms_p95": _percentile(ttft, 95),
            "tpot_ms_p95": _percentile(tpot, 95),
            "setup_s": setup_s},
        "observed": {
            "window_s": window_s, "requests": n_done,
            "token_gap_ms_p50": float(np.median(gaps)) * 1e3
            if gaps else None},
    }


def served_rows(row):
    """(decoder input ids, served tokens) of one reply row: position 0
    is the start token, -1 marks what follows the end token."""
    row = np.asarray(row)
    n = int(np.sum(row[1:] >= 0))
    tgt = np.where(row >= 0, row, 0)[:-1]
    return tgt, row[1:1 + n]


@functools.lru_cache(maxsize=None)
def _gap_fn(n_heads, n_layers, control):
    """Jitted (params, prompt, decoder inputs, held tokens) -> how far,
    at every position, the held token's logit lies below the
    reference's best. With `control` the token that this lower
    precision puts first at that position is held instead."""
    import jax
    import jax.numpy as jnp

    cfg = {"n_heads": n_heads, "n_layers": n_layers}

    def gaps(params, prompt, tgt, held):
        logits = R.forward_logits(params, prompt[None], tgt[None], cfg,
                                  "highest")[0]
        if control is not None:
            held = jnp.argmax(R.forward_logits(
                params, prompt[None], tgt[None], cfg, control)[0], -1)
        got = jnp.take_along_axis(logits, held[:, None], -1)[:, 0]
        return logits.max(-1) - got
    return jax.jit(gaps)


def check_sample(c, seed, sample, control=None):
    """The sample held to the reference, one pass a request: the gap by
    which every served token's logit lies below the reference's best,
    and whether each stream equalled its row. `control` names a lower
    precision of the reference (controls.py): the tokens it puts first
    at the same positions stand in for the served ones."""
    import jax.numpy as jnp

    params = R.make_params(seed, _model_cfg(c))
    fn = _gap_fn(c["n_heads"], c["n_layers"], control)
    gaps, stream_ok = [], True
    for prompt, row, streamed in sample:
        tgt, served = served_rows(row)
        stream_ok &= list(served) == list(streamed)
        held = np.zeros(len(tgt), np.int32)
        held[:len(served)] = served
        gaps.append(np.asarray(fn(
            params, jnp.asarray(prompt), jnp.asarray(tgt),
            jnp.asarray(held)))[:len(served)])
    return {"gaps": np.concatenate(gaps), "requests": len(sample),
            "stream_ok": bool(stream_ok)}


def hold_sample(c, read):
    """The numbers of `check_sample` beside their limits. The widest
    gap catches one wrong token; the share of tokens whose gap is wider
    than `wide_gap` catches a precision that loses many near-ties by a
    little (PERF.md section 2)."""
    out = compare.Compared()
    gaps, limits = read["gaps"], c["limits"]
    note = f"{read['requests']} requests, {len(gaps)} tokens"
    out.add("served_logit_gap", float(gaps.max()),
            limits["served_logit_gap"], note)
    wide = int((gaps > c["wide_gap"]).sum())
    out.add("served_wide_gap_share", wide / len(gaps),
            limits["served_wide_gap_share"],
            f"{wide} of {len(gaps)} tokens over {c['wide_gap']:g}")
    out.require("stream_equals_row", read["stream_ok"])
    return out
