"""Driver of the GLM-5.2 serving cell: the closed loop of drivers/serve.py
(callers driven from the server's own stream callbacks, times read
there) against `PagedContinuousGenerationServer.submit(prompt,
max_new_tokens=, cache_tokens=, stream=True, stream_cb=...)` over a
decoder-only bundle (models/glm_moe_dsa.py).

Set-up makes the weights on the device from the seed (a jitted call a
tensor, bfloat16), builds the bundle and the server, sends one short
prompt so that both serve programs have run, then
every document of the pool once (its prefill is set-up: all documents
are resident in the radix tree when the window opens), starts the
callers staggered over `ramp_s`, and lets the loop settle. After the
window a sample of the finished requests, drawn from the seed (one of
each document length, a 1,024-token question among them where one
finished), is run teacher-forced through the reference, document,
question and served tokens.
"""
import gc
import sys
import threading
import time

import numpy as np

from .. import compare, scopes_glm
from ..reference import glm_moe_dsa as R
from . import glm_traffic
from .serve import Load, Request, _percentile

END_ID = 1


def _say(what, t0):
    """A phase's end on standard error: where a run that is cut had
    got to."""
    print(f"[glm_serve] {what} at {time.perf_counter() - t0:.1f} s",
          file=sys.stderr, flush=True)
SERVER_KEYS = ("n_slots", "block_size", "n_blocks", "context",
               "max_new_tokens", "chunk_sizes", "max_chunks")
NOT_THE_BUILDERS = ("weight_dtype", "init_gain", "silent_ids",
                    "emb_scale", "router_gain", "bias_scale")


def build_server(c, seed):
    """(server, executor, scope): the weights from the seed in a scope
    of their own, the decoder-only bundle, the server bound to both."""
    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.inference import PagedContinuousGenerationServer
    from paddle_tpu.models import glm_moe_dsa as G

    scope, exe = Scope(), fluid.Executor(fluid.TPUPlace(0))
    model = {k: v for k, v in R.model_cfg(c).items()
             if k not in NOT_THE_BUILDERS}
    with unique_name.guard():
        bundle = G.build_glm_serve_bundle(
            dtype=c["weight_dtype"], end_id=END_ID, **model,
            **{k: c[k] for k in SERVER_KEYS})
    for name, value in R.make_top(seed, c).items():
        scope._set(name, value)
    for i in range(c["n_layers"]):
        for name, value in R.make_layer(seed, c, i).items():
            scope._set(name, value)
    srv = PagedContinuousGenerationServer(
        bundle, executor=exe, scope=scope, record_probes=True,
        steps_per_tick=c["steps_per_tick"],
        drain_steps=c["drain_steps"])
    return srv, exe, scope


class DocRequest(Request):
    __slots__ = ("max_new", "cache_tokens", "doc")

    def __init__(self, prompt, max_new, cache_tokens, doc=None,
                 caller=None):
        super().__init__(prompt, caller)
        self.max_new, self.cache_tokens = max_new, cache_tokens
        self.doc = doc


class DocLoad(Load):
    def submit(self, req):
        req.t_submit = time.perf_counter()
        self.inflight.add(req)
        with self.tracer.span("submit"):
            req.reply = self.srv.submit(
                req.prompt, max_new_tokens=req.max_new,
                cache_tokens=req.cache_tokens, stream=True,
                stream_cb=lambda chunk, seq, fin, r=req:
                    self._cb(r, chunk, fin))
        return req


def warm_up(load, mix, c, rng, timeout, t0):
    """Both serve programs once (a short prompt, nothing of it kept in
    the tree), then every document of the pool, each prefilled once and
    left resident."""
    done = threading.Event()
    left = [0]

    def ended(_req):
        left[0] -= 1
        if left[0] == 0:
            done.set()

    def send(reqs):
        done.clear()
        left[0] = len(reqs)
        for r in reqs:
            r.on_done = ended
            load.submit(r)
        if not done.wait(timeout):
            raise RuntimeError(f"warm-up did not finish; errors "
                               f"{load.errors}")
        bad = [r.finish for r in reqs if r.finish not in ("length", "eos")]
        if bad or load.errors:
            raise RuntimeError(f"warm-up requests ended {bad}; errors "
                               f"{load.errors}")

    # the prefill program holds every chunk size; a reply long enough
    # that cycles follow which carry no chunk runs the tick-only one
    n = max(2, min(c["chunk_sizes"][0],
                   c["context"] - c["max_new_tokens"]) - 1)
    send([DocRequest(rng.integers(mix.lo, mix.vocab, n, dtype=np.int64),
                     min(3 * c["steps_per_tick"], c["max_new_tokens"]),
                     0)])
    _say("both serve programs warm", t0)
    send([DocRequest(doc, 1, len(doc)) for doc in mix.docs])
    _say(f"{len(mix.docs)} documents resident", t0)


def run(ctx):
    c, spec = ctx.sizes, ctx.traffic
    if ctx.rehearse:
        from paddle_tpu.ops.pallas import attention

        attention.force_interpret(True)
    t_run = t_phase = time.perf_counter()
    srv, exe, scope = build_server(c, ctx.seed)
    ctx.note(build_s=time.perf_counter() - t_phase)
    _say("server built", t_run)
    mix = glm_traffic.SharedDocs(ctx.seed, spec, c)
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
        prompt, max_new, cache_tokens, doc = mix.next_request()
        req = DocRequest(prompt, max_new, cache_tokens, doc, caller=i)
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
    t0, t_end = state["t0"], state["t_end"]
    window_s = t_end - t0

    counters = ctx.counters
    counters["compiles_in_window"] = \
        in_window["backend_compiles"] + in_window["cache_hits"]
    counters["dispatches"] = stats1["ticks"] - stats0["ticks"]
    pool0, pool1 = stats0["block_pool"], stats1["block_pool"]

    def delta(key):
        return pool1[key] - pool0[key]

    for k in ("radix_admissions", "radix_evicted_blocks",
              "prompt_tokens", "cached_prompt_tokens", "prefill_tokens",
              "prefill_chunks", "lane_ticks", "moe_pairs", "moe_hit"):
        counters[k] = delta(k)
    tel0, tel1 = (s.get("device_telemetry", {}) for s in (stats0, stats1))
    ticks = tel1.get("ticks", 0) - tel0.get("ticks", 0)
    counters["device_ticks"] = ticks
    n_moe = c["n_layers"] - c["n_dense_layers"]
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
        counters["selected_keys_per_query"] = \
            delta("selected_keys_sum") / delta("lane_ticks")
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
             radix_nodes=pool1["radix_nodes"],
             blocks_in_use=pool1["blocks_in_use"])
    ctx.write_times({
        "done_s": [r.t_done - t0 for r in good], "ttft_ms": ttft,
        "tpot_ms": tpot, "tokens": [len(r.tokens) for r in good],
        "prompt": [len(r.prompt) for r in good]})

    sample = pick_sample(good, mix, c, rng)
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
    return {
        "attempted": n_done, "failed": failed,
        "compared": compared,
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


def write_scopes(ctx, srv, exe, scope):
    """The instruction names of every serve program that ran, with
    their `glm.` scopes, for the readers of the device trace."""
    texts = []
    for key, prog in srv.bundle.serves.items():
        feed = {name: np.zeros(shape, dtype) for name, shape, dtype
                in srv.bundle.serve_feed_spec(key)}
        try:
            texts.append(exe.compiled_text(prog, feed, srv._fetches,
                                           scope=scope))
        except RuntimeError:
            pass            # a program the run never dispatched
    scopes_glm.write_scopes(ctx.workload, texts)


def pick_sample(good, mix, c, rng):
    """`sample_per_length` finished requests of each document length,
    drawn from the seed; one with the longest question that finished is
    among them."""
    lengths = sorted({len(d) for d in mix.docs})
    q_longest = max(len(r.prompt) - len(mix.docs[r.doc]) for r in good)
    picked, have_long = [], False
    for n in lengths:
        pool = [r for r in good if len(mix.docs[r.doc]) == n]
        take = list(rng.permutation(len(pool))[:c["sample_per_length"]])
        long_q = [i for i, r in enumerate(pool)
                  if len(r.prompt) - n == q_longest]
        if take and long_q and not have_long:
            have_long = True
            if not set(take) & set(long_q):
                take[0] = long_q[int(rng.integers(len(long_q)))]
        picked += [pool[i] for i in take]
    out = []
    for r in picked:
        row = np.asarray(r.reply.result(timeout=60.0))
        out.append({"prompt": np.asarray(r.prompt), "row": row,
                    "streamed": list(r.tokens),
                    "probe": r.reply.probe})
    return out


def flat_sample(s):
    out = {"prompt": s["prompt"], "row": s["row"],
           "position": np.int64(s["probe"]["position"])}
    for kind in ("selected", "chosen"):
        for li, v in s["probe"][kind].items():
            out[f"{kind}_l{li}_"] = v
    return out


def served_of(row):
    """The served tokens of a reply row: position 0 is the prompt's
    last token, -1 marks what follows the reply's end."""
    row = np.asarray(row)
    return row[1:1 + int(np.sum(row[1:] >= 0))]


def _passes(c, seed, s, **how):
    served = served_of(s["row"])
    first = len(s["prompt"]) - 1
    return R.forward(c, seed, np.concatenate([s["prompt"], served]),
                     np.arange(first, first + len(served)),
                     block=c["reference_block"], **how)


def reference_of(c, seed, sample):
    """The reference's pass over each request of the sample: document,
    question and served tokens, read at the decode positions."""
    return [_passes(c, seed, s) for s in sample]


def check_sample(c, seed, sample, control=None, fault=None, refs=None):
    """The sample held to the reference, one pass a request over
    document, question and served tokens: the gap by which every served
    token's logit lies below the reference's best; whether each stream
    equalled its row; at the last decode position, how much of what
    each layer attended the reference's layer did not; over every decode
    position, the share of (token, expert layer) pairs routed to
    another set of experts. `control` (a lower precision) or `fault` of
    the reference stands in for the program: the tokens it puts first,
    its selections and its routing."""
    gaps, stream_ok = [], True
    sel_diff = sel_all = route_diff = route_all = 0
    for s, ref in zip(sample, refs or reference_of(c, seed, sample)):
        held = served_of(s["row"])
        stream_ok &= list(held) == list(s["streamed"])
        probe = s["probe"]
        if control is not None or fault is not None:
            low = _passes(c, seed, s, precision=control or "highest",
                          fault=fault)
            held = low["logits"].argmax(-1)
            probe = {"selected": dict(enumerate(low["selected"][:, -1])),
                     "chosen": dict(enumerate(low["chosen"]))}
        logits = ref["logits"]
        gaps.append(logits.max(-1)
                    - logits[np.arange(len(held)), held])
        for li in sorted(probe["selected"]):
            mine = {int(x) for x in probe["selected"][li] if x >= 0}
            theirs = {int(x) for x in ref["selected"][li][-1] if x >= 0}
            sel_diff += len(mine - theirs)
            sel_all += len(mine)
        for j, li in enumerate(sorted(probe["chosen"])):
            mine = np.sort(np.asarray(probe["chosen"][li]), -1)
            route_diff += int((mine != ref["chosen"][j]).any(-1).sum())
            route_all += len(mine)
    return {"gaps": np.concatenate(gaps), "requests": len(sample),
            "stream_ok": bool(stream_ok),
            "selection_flip_share": sel_diff / max(sel_all, 1),
            "routing_flip_share": route_diff / max(route_all, 1),
            "selected": sel_all, "routed": route_all}


def load_sample(path):
    """(seed, sample) of a file `run` wrote (`ctx.write_sample`)."""
    with np.load(path) as z:
        out, i = [], 0
        while f"prompt{i}" in z:
            probe = {"position": int(z[f"position{i}"]),
                     "selected": {}, "chosen": {}}
            for key in z.files:
                kind, _, rest = key.partition("_l")
                if kind in probe and rest.endswith(f"_{i}"):
                    probe[kind][int(rest[:-len(f"_{i}")])] = z[key]
            row = z[f"row{i}"]
            out.append({"prompt": z[f"prompt{i}"], "row": row,
                        "streamed": list(served_of(row)),
                        "probe": probe})
            i += 1
        return int(z["seed"]), out


def hold_sample(c, read):
    """The numbers of `check_sample` beside their limits
    (configs/glm-5.2-serve-ep16.json `limits`; PERF.md section 2 gives
    the readings each was set from)."""
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
    out.add("selection_flip_share", read["selection_flip_share"],
            limits["selection_flip_share"],
            f"of {read['selected']} selected positions")
    out.add("routing_flip_share", read["routing_flip_share"],
            limits["routing_flip_share"],
            f"of {read['routed']} (token, expert layer) pairs")
    return out
