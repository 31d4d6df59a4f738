"""Driver of the Olmo-Hybrid serving cell: the closed loop of
drivers/serve.py (callers driven from the server's own stream
callbacks, times read there) against
`PagedContinuousGenerationServer.submit(prompt, max_new_tokens=,
stream=True, stream_cb=...)` over a decoder-only bundle whose lanes
carry gated delta-rule state (models/olmo_hybrid.py), under multi-turn
sessions: each caller's next prompt is its last prompt, the reply the
server streamed for it and a new message, so that most of a prompt
repeats what the lane was fed one turn before (and a bundle with lane
state prefills all of it again).

Set-up makes the weights on the device from the seed (a jitted call a
tensor, bfloat16; the recurrence's own leaves float32), builds the
bundle and the server, sends one short prompt so that both serve
programs have run, starts the callers staggered over `ramp_s`, each at
turn `1 + caller mod turns` with a history drawn from the seed, and lets
the loop settle until the first replies have ended. After the window a
sample of the finished requests, drawn from the seed (one of each turn,
and the longest prompt among them), is run teacher-forced through the
reference, prompt and served tokens; with them the reset probes of
drivers/nemotron_serve.py, a few prompts of a few tokens on lanes that
other requests have just left, whose lanes keep the delta-rule state
they ended with for the driver to read.
"""
import gc
import sys
import threading
import time

import numpy as np

from .. import compare, scopes_olmoh
from ..reference import olmo_hybrid as R
from . import glm_traffic
from .glm_serve import DocLoad, DocRequest, served_of
from .nemotron_serve import (PADDED_ADVANCE, flat_sample, ghosted,
                             heads_of, reset_probes, taken)
from .nemotron_serve import load_sample  # noqa: F401 (controls_olmoh's)
from .serve import _percentile

END_ID = 1
SERVER_KEYS = ("n_slots", "block_size", "n_blocks", "context",
               "max_new_tokens", "chunk_sizes", "max_chunks",
               "delta_block", "state_dtype")
NOT_THE_BUILDERS = ("weight_dtype", "silent_ids", "emb_scale", "layers",
                    "time_step_min", "time_step_max", "time_step_floor")


def _say(what, t0):
    """A phase's end on standard error: where a run that is cut had
    got to."""
    print(f"[olmoh_serve] {what} at {time.perf_counter() - t0:.1f} s",
          file=sys.stderr, flush=True)


class Turn(DocRequest):
    __slots__ = ("turn",)

    def __init__(self, prompt, max_new, turn, caller=None):
        super().__init__(prompt, max_new, 0, caller=caller)
        self.turn = turn


class Sessions:
    """The requests of the `sessions_grow_9k` closed loop. A caller
    holds sessions of `session_turns` turns: turn 1 sends a prompt of
    `first_prompt_tokens`, a later turn the last prompt, the reply the
    server streamed for it and a message of `message_tokens`. The
    lengths and the new tokens a request asks for are drawn in blocks
    of `stratum`, each holding every choice in its expected number,
    from the file's own `order_seed`: the same for every `--seed`,
    because the lengths decide the work. Caller c takes every
    `callers`-th entry of each table from the c-th, so that what a
    caller sends does not hang on the order in which the callers'
    replies end (drawn in that order, one swap sent two runs down
    different histories, 7% apart in tokens a second: PR 38). The
    seed decides what the prompts say."""

    def __init__(self, seed, spec, sizes):
        self.turns = spec["session_turns"]
        n = spec["max_requests"]
        self.lo, self.vocab = spec["id_low"], sizes["vocab"]

        def drawn(key, stream):
            values, shares = zip(*spec[key])
            return np.asarray(values)[glm_traffic.stratified(
                np.random.default_rng([spec["order_seed"], stream]),
                shares, spec["stratum"], n)]

        self.first_len = drawn("first_prompt_tokens", 6)
        self.msg_len = drawn("message_tokens", 9)
        self.max_new = drawn("max_new_tokens", 7)
        self.callers = spec["callers"]
        self.at = {}                    # (table, caller): entries taken
        self.ids = np.random.default_rng([int(seed), 3])
        self.sent = 0
        self.repeated = 0               # prompt tokens of the last turn
        self.prompt_tokens = 0

    def _next(self, what, table, caller):
        k = self.at.get((what, caller), 0)
        i = caller + self.callers * k
        if i >= len(table):
            raise RuntimeError(f"traffic exhausted after {self.sent} "
                               f"requests; raise max_requests in the "
                               f"traffic file")
        self.at[what, caller] = k + 1
        return int(table[i])

    def _words(self, n):
        return self.ids.integers(self.lo, self.vocab, n, dtype=np.int64)

    def _request(self, prompt, turn, caller, repeated):
        self.sent += 1
        self.repeated += repeated
        self.prompt_tokens += len(prompt)
        return Turn(prompt, self._next("new", self.max_new, caller), turn,
                    caller)

    def opening(self, caller):
        """Caller `caller`'s first request: turn `1 + caller mod
        turns`, behind a history of the earlier turns drawn from the
        seed (their replies as random words of their drawn lengths)."""
        turn = 1 + caller % self.turns
        prompt = self._words(self._next("first", self.first_len, caller))
        for _ in range(turn - 1):
            reply = self._words(self._next("new", self.max_new, caller))
            prompt = np.concatenate([prompt, reply, self._words(
                self._next("msg", self.msg_len, caller))])
        return self._request(prompt, turn, caller, 0)

    def following(self, req):
        """What req's caller sends when req ends: the next turn of the
        session, or turn 1 of a new one."""
        if req.turn == self.turns:
            return self._request(self._words(self._next(
                "first", self.first_len, req.caller)), 1, req.caller, 0)
        prompt = np.concatenate([
            req.prompt, np.asarray(req.tokens, np.int64),
            self._words(self._next("msg", self.msg_len, req.caller))])
        return self._request(prompt, req.turn + 1, req.caller,
                             len(req.prompt) + len(req.tokens))


def build_server(c, seed):
    """(server, executor, scope): the weights from the seed in a scope
    of their own, the decoder-only bundle, the server bound to both."""
    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.inference import PagedContinuousGenerationServer
    from paddle_tpu.models import olmo_hybrid as O

    scope, exe = Scope(), fluid.Executor(fluid.TPUPlace(0))
    model = {k: v for k, v in R.model_cfg(c).items()
             if k not in NOT_THE_BUILDERS}
    with unique_name.guard():
        bundle = O.build_olmo_hybrid_serve_bundle(
            layers_pattern=c["layers"], dtype=c["weight_dtype"],
            end_id=END_ID, **model, **{k: c[k] for k in SERVER_KEYS})
    for name, value in R.make_top(seed, c).items():
        scope._set(name, value)
    for i in range(len(c["layers"])):
        for name, value in R.make_layer(seed, c, i).items():
            scope._set(name, value)
    srv = PagedContinuousGenerationServer(
        bundle, executor=exe, scope=scope, record_probes=True,
        steps_per_tick=c["steps_per_tick"],
        drain_steps=c["drain_steps"])
    return srv, exe, scope


def warm_up(load, c, rng, vocab, timeout, t0):
    """Both serve programs once: the prefill program holds every chunk
    size; a reply long enough that cycles follow which carry no chunk
    runs the tick-only one."""
    done = threading.Event()
    n = max(2, min(c["chunk_sizes"][0],
                   c["context"] - c["max_new_tokens"]) - 1)
    req = DocRequest(rng.integers(3, vocab, n, dtype=np.int64),
                     min(3 * c["steps_per_tick"], c["max_new_tokens"]), 0)
    req.on_done = lambda _req: done.set()
    load.submit(req)
    if not done.wait(timeout) or load.errors \
            or req.finish not in ("length", "eos"):
        try:
            req.reply.result(timeout=1.0)
        except BaseException as e:      # what the dispatch raised
            raise RuntimeError(f"warm-up ended {req.finish!r}: "
                               f"{e!r}"[:4000]) from e
        raise RuntimeError(f"warm-up ended {req.finish!r}; errors "
                           f"{load.errors}")
    _say("both serve programs warm", t0)


def run(ctx):
    c, spec = ctx.sizes, ctx.traffic
    if ctx.rehearse:
        from paddle_tpu.ops.pallas import attention

        attention.force_interpret(True)
    t_run = t_phase = time.perf_counter()
    srv, exe, scope = build_server(c, ctx.seed)
    ctx.note(build_s=time.perf_counter() - t_phase)
    _say("server built", t_run)
    mix = Sessions(ctx.seed, spec, c)
    load = DocLoad(srv, ctx.tracer)
    rng = np.random.default_rng([int(ctx.seed), 8])
    t_phase = time.perf_counter()
    warm_up(load, c, rng, mix.vocab, spec["warm_timeout_s"], t_run)
    ctx.note(warm_s=time.perf_counter() - t_phase)

    # the closed loop, as drivers/serve.py runs it
    seconds = ctx.trace_seconds if ctx.profile else ctx.seconds
    state = {"t0": None, "t_end": None, "armed": False,
             "stopping": False}
    finished = []
    window_done = threading.Event()

    def on_done(req):
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
            send(mix.following(req) if req.finish == "length"
                 else mix.opening(req.caller))

    def send(req):
        req.on_done = on_done
        load.submit(req)

    t_ramp = time.monotonic()
    for i in range(spec["callers"]):
        due = t_ramp + i * spec["ramp_s"] / spec["callers"]
        time.sleep(max(0.0, due - time.monotonic()))
        send(mix.opening(i))
    time.sleep(spec["settle_s"])
    at_setup = ctx.meter.mark()
    ctx.counters["cache_hits_at_setup"] = at_setup["cache_hits"]
    ctx.counters["backend_compiles_at_setup"] = \
        at_setup["backend_compiles"]
    gc.collect()
    gc.freeze()
    stats0 = srv.stats()
    sent0 = (mix.repeated, mix.prompt_tokens)
    if ctx.profile:
        ctx.tracer.start()
    setup_s = ctx.clock.setup_s()
    state["armed"] = True
    if not window_done.wait(seconds + spec["window_timeout_s"]):
        raise RuntimeError(f"no request ended the window; errors "
                           f"{load.errors}")
    stats1 = srv.stats()
    sent1 = (mix.repeated, mix.prompt_tokens)
    _say("window closed", t_run)
    ctx.tracer.stop()
    in_window = ctx.meter.since(at_setup)
    load.cancel_all()
    ctx.memory_peak = ctx.read_memory_peak()
    probes = reset_probes(load, mix, c, rng, spec["window_timeout_s"])
    states = lane_states(srv, scope,
                         [r.reply.probe["lane"] for r in probes])
    t0, t_end = state["t0"], state["t_end"]
    window_s = t_end - t0

    counters = ctx.counters
    counters["compiles_in_window"] = \
        in_window["backend_compiles"] + in_window["cache_hits"]
    counters["dispatches"] = stats1["ticks"] - stats0["ticks"]
    pool0, pool1 = stats0["block_pool"], stats1["block_pool"]

    def delta(key):
        return pool1[key] - pool0[key]

    for k in ("prompt_tokens", "cached_prompt_tokens", "prefill_tokens",
              "prefill_chunks", "lane_ticks", "state_resets",
              "prefix_reuse_skipped", "radix_admissions"):
        counters[k] = delta(k)
    counters["state_lanes"] = pool1["state_lanes"]
    counters["state_bytes"] = pool1["state_bytes"]
    if sent1[1] > sent0[1]:
        # of the prompt tokens sent in the window, those that repeat
        # what the caller's lane was fed one turn before
        counters["repeated_prompt_share"] = \
            (sent1[0] - sent0[0]) / (sent1[1] - sent0[1])
    tel0, tel1 = (s.get("device_telemetry", {}) for s in (stats0, stats1))
    ticks = tel1.get("ticks", 0) - tel0.get("ticks", 0)
    counters["device_ticks"] = ticks
    if ticks:
        counters["mean_live_lanes"] = (
            tel1["occupancy_integral"]
            - tel0.get("occupancy_integral", 0)) / ticks
        if ctx.profile:
            counters["traced_ticks"] = ticks
    if delta("lane_ticks"):
        counters["mean_context"] = \
            delta("context_sum") / delta("lane_ticks")

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
             ttft_ms_p95=_percentile(ttft, 95),
             tpot_ms_median=float(np.median(tpot)), sent=mix.sent,
             by_turn=[sum(r.turn == k for r in good)
                      for k in range(1, mix.turns + 1)],
             longest_prompt=max(len(r.prompt) for r in good),
             blocks_in_use=pool1["blocks_in_use"])
    ctx.write_times({
        "done_s": [r.t_done - t0 for r in good], "ttft_ms": ttft,
        "tpot_ms": tpot, "tokens": [len(r.tokens) for r in good],
        "prompt": [len(r.prompt) for r in good],
        "turn": [r.turn for r in good]})

    sample = pick_sample(good, mix, rng) + [
        {**taken(r), "state": s} for r, s in zip(probes, states)]
    n_done, errors = len(done), list(load.errors)
    if ctx.profile and not ctx.rehearse:
        write_scopes(ctx, srv, exe, scope)
    ctx.write_sample(
        seed=np.int64(ctx.seed),
        **{f"{k}{i}": v for i, s in enumerate(sample)
           for k, v in flat_sample(s).items()})
    srv.close()
    for name in list(scope.local_var_names()):
        scope.erase(name)
    del srv, exe, scope, load, finished, done, good
    gc.collect()

    compared = hold_sample(c, check_sample(c, ctx.seed, sample))
    _say("sample held to the reference", t_run)
    compared.require("no_request_failed", failed == 0,
                     "; ".join(errors)[:300] or None)
    compared.require("no_prefix_hit_with_lane_state",
                     counters["cached_prompt_tokens"] == 0
                     and counters["radix_admissions"] == 0)
    return {
        "attempted": n_done, "failed": failed,
        "compared": compared,
        "unattributed": "server_cycle_unattributed",
        "end_to_end": {
            "serve_tokens_per_s": out_tokens / window_s,
            "tpot_ms_p95": _percentile(tpot, 95),
            "setup_s": setup_s},
        "observed": {
            "window_s": window_s, "requests": n_done,
            "token_gap_ms_p50": float(np.median(gaps)) * 1e3
            if gaps else None},
    }


def write_scopes(ctx, srv, exe, scope):
    """The instruction names of every serve program that ran, with
    their `olmoh.` scopes, for the readers of the device trace."""
    texts = []
    for key, prog in srv.bundle.serves.items():
        feed = {name: np.zeros(shape, dtype) for name, shape, dtype
                in srv.bundle.serve_feed_spec(key)}
        try:
            texts.append(exe.compiled_text(prog, feed, srv._fetches,
                                           scope=scope))
        except RuntimeError:
            pass            # a program the run never dispatched
    scopes_olmoh.write_scopes(ctx.workload, texts)


def lane_states(srv, scope, lanes):
    """[linear layers, H, Dk, Dv] a lane: the delta-rule state it holds
    in every layer, as the slot state stores it. A lane that serves
    nothing keeps its state bit for bit, so where nothing followed a
    request on its lane (`reply.probe["lane"]`) this is the state
    behind the last token the lane was fed."""
    names = [n for n in srv.bundle.lane_state["names"] if "gdn_state" in n]
    return [np.stack([np.asarray(scope._get(n)[lane]) for n in names])
            for lane in lanes]


def pick_sample(good, mix, rng):
    """A finished request of each turn drawn from the seed, and the one
    with the longest prompt where none of those is it."""
    out = []
    for k in range(1, mix.turns + 1):
        pool = [r for r in good if r.turn == k]
        if pool:
            out.append(pool[int(rng.integers(len(pool)))])
    longest = max(good, key=lambda r: len(r.prompt))
    if all(r is not longest for r in out):
        out.append(longest)
    return [taken(r) for r in out]


def _passes(c, seed, s, precision="highest", fault=None):
    """The reference over what the lane was fed: the prompt and every
    served token but the last, whose logits nobody reads."""
    served = served_of(s["row"])
    if fault == PADDED_ADVANCE:
        toks, ghost, want = ghosted(c, s["prompt"], served)
        return R.forward(c, seed, toks, want, ghost=ghost)
    first = len(s["prompt"]) - 1
    return R.forward(c, seed, np.concatenate([s["prompt"], served[:-1]]),
                     np.arange(first, first + len(served)),
                     precision=precision, fault=fault)


def reference_of(c, seed, sample):
    """The reference's pass over each request of the sample: prompt and
    served tokens, read at the decode positions."""
    return [_passes(c, seed, s) for s in sample]


def check_sample(c, seed, sample, control=None, fault=None, refs=None):
    """The sample held to the reference, one pass a request over prompt
    and served tokens: the gap by which every served token's logit lies
    below the reference's best; how far the logit the program gave
    each token it served lies from the reference's logit of that token
    (a request's median); whether each stream equalled its row; of the
    delta-rule states that the sample holds (the reset probes'), the
    share of the numbers that bfloat16 holds exactly, and how far the
    state lies from the reference's behind the same tokens (a layer's
    gap as a share of the reference's norm, in the first linear layer
    and in the worst: a note). `control` (a lower precision) or `fault`
    of the reference stands in for the program: the tokens it puts
    first and its state."""
    gaps, errors, stream_ok = [], [], True
    state_exact = state_all = 0
    state_gap = np.zeros(2)                 # first layer's, the worst
    for s, ref in zip(sample, refs or reference_of(c, seed, sample)):
        held = served_of(s["row"])
        stream_ok &= list(held) == list(s["streamed"])
        top = np.asarray(s["probe"]["top_logit"]).reshape(-1)
        low = None
        if control is not None or fault is not None:
            low = _passes(c, seed, s, control or "highest", fault)
            held, top = low["logits"].argmax(-1), low["logits"].max(-1)
        if "state" in s:
            n = s["state"].shape[1]
            state = s["state"] if low is None else heads_of(low["states"], n)
            # a float32 number that bfloat16 holds: its low 16 bits zero
            state_exact += int((np.ascontiguousarray(state, np.float32)
                                .view(np.uint32) & 0xFFFF == 0).sum())
            state_all += state.size
            layers = [float(np.linalg.norm(a - b) / np.linalg.norm(b))
                      for a, b in zip(state, heads_of(ref["states"], n))]
            state_gap = np.maximum(state_gap, [layers[0], max(layers)])
        logits = ref["logits"]
        mine = logits[np.arange(len(held)), held]
        gaps.append(logits.max(-1) - mine)
        errors.append(float(np.median(np.abs(top - mine))))
    return {"gaps": np.concatenate(gaps), "errors": errors,
            "requests": len(sample), "stream_ok": bool(stream_ok),
            "state_bf16_share": state_exact / max(state_all, 1),
            "state_numbers": state_all, "state_gap": state_gap.tolist()}


def hold_sample(c, read):
    """The numbers of `check_sample` beside their limits
    (configs/olmo-hybrid-7b-serve-pp4.json `limits`; PERF.md section 2
    gives the readings each was set from)."""
    out = compare.Compared()
    gaps, limits = read["gaps"], c["limits"]
    note = f"{read['requests']} requests, {len(gaps)} tokens"
    out.add("served_logit_gap", float(gaps.max()),
            limits["served_logit_gap"], note)
    wide = int((gaps > c["wide_gap"]).sum())
    out.add("served_wide_gap_share", wide / len(gaps),
            limits["served_wide_gap_share"],
            f"{wide} of {len(gaps)} tokens over {c['wide_gap']:g}")
    # a request's median (a precision moves every token's logit), and
    # the largest over the requests (a lane that was not reset shows in
    # the short request alone)
    errors = read["errors"]
    out.add("served_logit_error", max(errors),
            limits["served_logit_error"],
            f"a request's median; smallest {min(errors):.3g}")
    out.require("stream_equals_row", read["stream_ok"])
    # the configuration states a float32 state: of float32 numbers one
    # in 65,536 is a bfloat16 number, of a state kept or updated in
    # bfloat16 every one
    if read["state_numbers"]:
        out.add("state_bf16_share", read["state_bf16_share"],
                limits["state_bf16_share"],
                "of {} numbers of the probes' delta-rule states; gap to "
                "the reference's state {:.3g} in the first linear layer, "
                "{:.3g} in the worst".format(
                    read["state_numbers"], *read["state_gap"]))
    return out
