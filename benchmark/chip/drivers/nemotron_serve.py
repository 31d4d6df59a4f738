"""Driver of the Nemotron-3-Super serving cell: the closed loop of
drivers/serve.py (callers driven from the server's own stream
callbacks, times read there) against
`PagedContinuousGenerationServer.submit(prompt, max_new_tokens=,
stream=True, stream_cb=...)` over a decoder-only bundle whose lanes
carry state-space state (models/nemotron_h.py).

Set-up makes the weights on the device from the seed (a jitted call a
tensor, bfloat16; the recurrence's own leaves float32), builds the
bundle and the server, sends one short prompt so that both serve
programs have run, starts the callers staggered over `ramp_s`, and lets
the loop settle until the first replies have ended. Every prompt is
new: nothing is ever found cached, and a bundle with lane state takes
no prefix hit anyway. After the window a sample of the finished
requests, drawn from the seed (one of each prompt length, the longest
among them), is run teacher-forced through the reference, prompt and
served tokens; with them a few prompts of a few tokens that the driver
sends once the window has closed, on lanes that other requests have
just left: what the first positions of a sequence read shows whether a
lane's state was reset and whether a chunk's padding moved it, which no
reply behind a prompt of 128 tokens can (the recurrence has forgotten
its start by then). Nothing follows those prompts, so their lanes keep
the scan state they ended with, and the driver reads it: the precision
the state is held in shows in the state's own numbers and nowhere
behind a bfloat16 residual stream.
"""
import gc
import sys
import threading
import time

import numpy as np

from .. import compare, scopes_nemotron
from ..reference import nemotron_h as R
from . import glm_traffic
from .glm_serve import DocLoad, DocRequest, served_of
from .serve import _percentile

END_ID = 1
SERVER_KEYS = ("n_slots", "block_size", "n_blocks", "context",
               "max_new_tokens", "chunk_sizes", "max_chunks",
               "scan_block", "state_dtype")
NOT_THE_BUILDERS = ("weight_dtype", "init_gain", "silent_ids",
                    "emb_scale", "router_gain", "bias_scale", "layers",
                    "time_step_min", "time_step_max", "time_step_floor")
# what the driver itself plants behind the reference's own faults
PADDED_ADVANCE = "padded_advance"
# heads a layer of a probe's scan state that the sample's file keeps
# (all of it is 21 MB a lane at the cell's size)
STATE_HEADS_KEPT = 8


def _say(what, t0):
    """A phase's end on standard error: where a run that is cut had
    got to."""
    print(f"[nemotron_serve] {what} at {time.perf_counter() - t0:.1f} s",
          file=sys.stderr, flush=True)


class ChatMix:
    """The requests of the `chat_cold_mix` closed loop, in the order
    they are sent. How long a prompt is and how many new tokens it asks
    for are drawn in blocks of `stratum` requests, each holding every
    choice in its expected number, from the file's own `order_seed`:
    the sequence is the same for every `--seed`, because the lengths
    decide the work. The seed decides what the prompts say."""

    def __init__(self, seed, spec, sizes):
        if spec["prompt_pool"]:
            raise ValueError("chat_cold_mix sends every prompt once: "
                             "prompt_pool has to be 0")
        self.callers = spec["callers"]
        self.max_requests = n = spec["max_requests"]
        self.lo, self.vocab = spec["id_low"], sizes["vocab"]

        def drawn(key, stream):
            values, shares = zip(*spec[key])
            return np.asarray(values)[glm_traffic.stratified(
                np.random.default_rng([spec["order_seed"], stream]),
                shares, spec["stratum"], n)]

        self.lengths = sorted(v for v, _ in spec["prompt_tokens"])
        self.p_len = drawn("prompt_tokens", 6)
        self.max_new = drawn("max_new_tokens", 7)
        self.ids = np.random.default_rng([int(seed), 3])
        self.sent = 0

    def next_request(self):
        """(prompt, max_new_tokens)."""
        if self.sent >= self.max_requests:
            raise RuntimeError(
                f"traffic exhausted after {self.sent} requests; raise "
                f"max_requests in the traffic file")
        i, self.sent = self.sent, self.sent + 1
        return (self.ids.integers(self.lo, self.vocab, int(self.p_len[i]),
                                  dtype=np.int64), int(self.max_new[i]))


def build_server(c, seed):
    """(server, executor, scope): the weights from the seed in a scope
    of their own, the decoder-only bundle, the server bound to both."""
    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.inference import PagedContinuousGenerationServer
    from paddle_tpu.models import nemotron_h as N

    scope, exe = Scope(), fluid.Executor(fluid.TPUPlace(0))
    model = {k: v for k, v in R.model_cfg(c).items()
             if k not in NOT_THE_BUILDERS}
    with unique_name.guard():
        bundle = N.build_nemotron_h_serve_bundle(
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


def warm_up(load, mix, c, rng, timeout, t0):
    """Both serve programs once: the prefill program holds every chunk
    size; a reply long enough that cycles follow which carry no chunk
    runs the tick-only one."""
    done = threading.Event()
    n = max(2, min(c["chunk_sizes"][0],
                   c["context"] - c["max_new_tokens"]) - 1)
    req = DocRequest(rng.integers(mix.lo, mix.vocab, n, dtype=np.int64),
                     min(3 * c["steps_per_tick"], c["max_new_tokens"]), 0)
    req.on_done = lambda _req: done.set()
    load.submit(req)
    if not done.wait(timeout) or load.errors \
            or req.finish not in ("length", "eos"):
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
    mix = ChatMix(ctx.seed, spec, c)
    load = DocLoad(srv, ctx.tracer)
    rng = np.random.default_rng([int(ctx.seed), 8])
    t_phase = time.perf_counter()
    warm_up(load, mix, c, rng, spec["warm_timeout_s"], t_run)
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
            start_caller(req.caller)

    def start_caller(i):
        prompt, max_new = mix.next_request()
        req = DocRequest(prompt, max_new, 0, caller=i)
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
    gc.freeze()
    stats0 = srv.stats()
    if ctx.profile:
        ctx.tracer.start()
    setup_s = ctx.clock.setup_s()
    state["armed"] = True
    if not window_done.wait(seconds + spec["window_timeout_s"]):
        raise RuntimeError(f"no request ended the window; errors "
                           f"{load.errors}")
    stats1 = srv.stats()
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
              "prefill_chunks", "lane_ticks", "moe_pairs", "moe_hit",
              "state_resets", "prefix_reuse_skipped", "radix_admissions"):
        counters[k] = delta(k)
    counters["state_lanes"] = pool1["state_lanes"]
    counters["state_bytes"] = pool1["state_bytes"]
    tel0, tel1 = (s.get("device_telemetry", {}) for s in (stats0, stats1))
    ticks = tel1.get("ticks", 0) - tel0.get("ticks", 0)
    counters["device_ticks"] = ticks
    n_moe = c["layers"].count("E")
    if ticks:
        counters["mean_live_lanes"] = (
            tel1["occupancy_integral"]
            - tel0.get("occupancy_integral", 0)) / ticks
        counters["moe_pairs_per_tick"] = delta("moe_pairs") / ticks
        counters["held_experts_hit_per_tick"] = \
            delta("moe_hit") / ticks / n_moe
        if ctx.profile:
            counters["traced_ticks"] = ticks
    if delta("lane_ticks"):
        counters["mean_context"] = \
            delta("context_sum") / delta("lane_ticks")
    loads = [np.asarray(pool1["moe_load"][k]) - np.asarray(
        pool0["moe_load"][k]) for k in sorted(pool1["moe_load"])]
    if all(l.sum() for l in loads):
        counters["moe_load_imbalance"] = float(np.mean(
            [l.max() / l.mean() for l in loads]))

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
             distinct_tokens=len({t for r in good for t in r.tokens}),
             blocks_in_use=pool1["blocks_in_use"])
    ctx.write_times({
        "done_s": [r.t_done - t0 for r in good], "ttft_ms": ttft,
        "tpot_ms": tpot, "tokens": [len(r.tokens) for r in good],
        "prompt": [len(r.prompt) for r in good]})

    sample = pick_sample(good, mix, c, rng) + [
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
    their `nemotronh.` scopes, for the readers of the device trace."""
    texts = []
    for key, prog in srv.bundle.serves.items():
        feed = {name: np.zeros(shape, dtype) for name, shape, dtype
                in srv.bundle.serve_feed_spec(key)}
        try:
            texts.append(exe.compiled_text(prog, feed, srv._fetches,
                                           scope=scope))
        except RuntimeError:
            pass            # a program the run never dispatched
    scopes_nemotron.write_scopes(ctx.workload, texts)


def reset_probes(load, mix, c, rng, timeout):
    """Prompts of `reset_probe_prompts` tokens, sent together once the
    window's requests are gone, each on a lane that holds what its last
    request left: the finished requests."""
    done = threading.Event()
    reqs = [DocRequest(rng.integers(mix.lo, mix.vocab, n, dtype=np.int64),
                       c["reset_probe_new"], 0)
            for n in c["reset_probe_prompts"]]
    left = [len(reqs)]

    def ended(_req):
        left[0] -= 1
        if not left[0]:
            done.set()

    for r in reqs:
        r.on_done = ended
        load.submit(r)
    if not done.wait(timeout) or any(
            r.finish not in ("length", "eos") for r in reqs):
        raise RuntimeError(f"reset probes ended "
                           f"{[r.finish for r in reqs]}; errors "
                           f"{load.errors}")
    return reqs


def lane_states(srv, scope, lanes):
    """[state-space layers, H, P, N] a lane: the scan state it holds
    in every layer, as the slot state stores it. A lane that serves
    nothing keeps its state bit for bit, so where nothing followed a
    request on its lane (`reply.probe["lane"]`) this is the state
    behind the last token the lane was fed: the prompt and every
    served token but the last."""
    names = [n for n in srv.bundle.lane_state["names"] if "ssm_state" in n]
    return [np.stack([np.asarray(scope._get(n)[lane]) for n in names])
            for lane in lanes]


def taken(r):
    """What the comparison reads of a finished request."""
    return {"prompt": np.asarray(r.prompt),
            "row": np.asarray(r.reply.result(timeout=60.0)),
            "streamed": list(r.tokens), "probe": r.reply.probe}


def pick_sample(good, mix, c, rng):
    """`sample_per_length` finished requests of each prompt length,
    drawn from the seed."""
    out = []
    for n in mix.lengths:
        pool = [r for r in good if len(r.prompt) == n]
        out += [taken(pool[i]) for i in
                rng.permutation(len(pool))[:c["sample_per_length"]]]
    return out


def flat_sample(s):
    """A request of the sample as the arrays of a file; of a state at
    most STATE_HEADS_KEPT heads a layer, evenly spaced."""
    out = {"prompt": s["prompt"], "row": s["row"],
           "top_logit": s["probe"]["top_logit"]}
    if "state" in s:
        out["state"] = heads_of(s["state"], min(STATE_HEADS_KEPT,
                                                s["state"].shape[1]))
    for li, v in s["probe"]["chosen"].items():
        out[f"chosen_l{li}_"] = v
    return out


def ghosted(c, prompt, served):
    """(tokens, ghost, want): the sequence with the positions a prefill
    chunk is padded by put in behind the chunk (id 0, as the program
    feeds them), as the planner cuts a prompt's rest: whole chunks of
    the largest size and one smaller one, padded to the smallest size
    that holds it."""
    sizes = sorted(c["chunk_sizes"])
    toks, ghost, at, end = [], [], 0, len(prompt) - 1
    while at < end:
        n = min(end - at, sizes[-1])
        pad = next(s for s in sizes if s >= n) - n
        toks += list(prompt[at:at + n]) + [0] * pad
        ghost += [False] * n + [True] * pad
        at += n
    first = len(toks)
    toks += [prompt[-1]] + list(served[:-1])
    ghost += [False] * len(served)
    return (np.asarray(toks, np.int64), np.asarray(ghost),
            np.arange(first, first + len(served)))


def heads_of(states, n):
    """`n` heads of [layers, H, P, N], evenly spaced."""
    return states[:, ::states.shape[1] // n]


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
    (a request's median);
    whether each stream equalled its row;
    over every decode position and expert layer, the share of the
    chosen experts that the reference did not choose (of top_k a token
    a layer: with 22 of 512 chosen, the last few lie within a rounding
    of the first few left out, and a share of whole sets that differ
    would read near one for any precision);
    of the scan states that the sample holds (the reset probes'), the
    share of the numbers that bfloat16 holds exactly, and how far the
    state lies from the reference's behind the same tokens (a layer's
    gap as a share of the reference's norm, in the first state-space
    layer and in the worst: a note, for the bfloat16 stream moves the
    first layer's more than a bfloat16 state would, and a routing
    near-tie upstream moves the later layers' ten times more).
    `control` (a lower precision) or
    `fault` of the reference stands in for the program: the tokens it
    puts first, its routing and its state."""
    gaps, errors, stream_ok = [], [], True
    route_diff = route_all = 0
    state_exact = state_all = 0
    state_gap = np.zeros(2)                 # first layer's, the worst
    for s, ref in zip(sample, refs or reference_of(c, seed, sample)):
        held = served_of(s["row"])
        stream_ok &= list(held) == list(s["streamed"])
        chosen = s["probe"]["chosen"]
        top = np.asarray(s["probe"]["top_logit"]).reshape(-1)
        low = None
        if control is not None or fault is not None:
            low = _passes(c, seed, s, control or "highest", fault)
            held, top = low["logits"].argmax(-1), low["logits"].max(-1)
            chosen = dict(enumerate(low["chosen"]))
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
        for j, li in enumerate(sorted(chosen)):
            mine = np.asarray(chosen[li])
            theirs = np.asarray(ref["chosen"][j])
            route_diff += int((~(mine[:, :, None] == theirs[:, None, :])
                               .any(-1)).sum())
            route_all += mine.size
    return {"gaps": np.concatenate(gaps), "errors": errors,
            "requests": len(sample),
            "stream_ok": bool(stream_ok),
            "routing_flip_share": route_diff / max(route_all, 1),
            "routed": route_all,
            "state_bf16_share": state_exact / max(state_all, 1),
            "state_numbers": state_all, "state_gap": state_gap.tolist()}


def load_sample(path):
    """(seed, sample) of a file `run` wrote (`ctx.write_sample`)."""
    with np.load(path) as z:
        out, i = [], 0
        while f"prompt{i}" in z:
            chosen = {}
            for key in z.files:
                kind, _, rest = key.partition("_l")
                if kind == "chosen" and rest.endswith(f"_{i}"):
                    chosen[int(rest[:-len(f"_{i}")])] = z[key]
            row = z[f"row{i}"]
            out.append({"prompt": z[f"prompt{i}"], "row": row,
                        "streamed": list(served_of(row)),
                        "probe": {"chosen": chosen,
                                  "top_logit": z[f"top_logit{i}"]}})
            if f"state{i}" in z:
                out[-1]["state"] = z[f"state{i}"]
            i += 1
        return int(z["seed"]), out


def hold_sample(c, read):
    """The numbers of `check_sample` beside their limits
    (configs/nemotron-3-super-serve-ep4.json `limits`; PERF.md section
    2 gives the readings each was set from)."""
    out = compare.Compared()
    gaps, limits = read["gaps"], c["limits"]
    note = f"{read['requests']} requests, {len(gaps)} tokens"
    out.add("served_logit_gap", float(gaps.max()),
            limits["served_logit_gap"], note)
    wide = int((gaps > c["wide_gap"]).sum())
    out.add("served_wide_gap_share", wide / len(gaps),
            limits["served_wide_gap_share"],
            f"{wide} of {len(gaps)} tokens over {c['wide_gap']:g}")
    # a request's median (a precision moves every token's logit, one
    # routing near-tie moves one token's by much more), and the largest
    # over the requests (a lane that was not reset shows in the short
    # request alone)
    errors = read["errors"]
    out.add("served_logit_error", max(errors),
            limits["served_logit_error"],
            f"a request's median; smallest {min(errors):.3g}")
    out.require("stream_equals_row", read["stream_ok"])
    out.add("routing_flip_share", read["routing_flip_share"],
            limits["routing_flip_share"],
            f"of {read['routed']} chosen experts")
    # the configuration states a float32 scan state: of float32
    # numbers one in 65,536 is a bfloat16 number, of a state kept or
    # updated in bfloat16 every one
    if read["state_numbers"]:
        out.add("state_bf16_share", read["state_bf16_share"],
                limits["state_bf16_share"],
                "of {} numbers of the probes' scan states; gap to the "
                "reference's state {:.3g} in the first state-space "
                "layer, {:.3g} in the worst".format(
                    read["state_numbers"], *read["state_gap"]))
    return out
