"""Driver of the LFM2-MoE training cell: `Executor.run(main, feed,
fetch_list=[loss])` on `models/lfm2_moe.py`'s training program, a new
seeded batch every step, the loss read back each step.

The same shape as drivers/train.py. Set-up builds one object (program,
executor, scope, with the weights the reference's generator makes from
the seed written over the startup program's, a layer at a time), follows
its first three steps with the window's own call, warms it further and
hands the same object to the window: one executable is timed and held
to the reference. Before the followed steps one call of the step
program with the expert layers' counters fetched beside the loss (the
experts each token chose, the tokens each held expert received: a
second executable, because the executor compiles a program once a set
of fetches) reads the routing on the seed's state, which is then put
back. After the window the trainer's state is freed, because the
float32 reference does not fit beside it, and the reference follows
the same three steps. `compare_readings` holds the program's losses,
its first gradient (Adam's first moment after one step), the change of
its parameters and the experts it chose to the reference's.
"""
import gc
import math
import time

import numpy as np

from .. import compare, device_scopes, traffic
from ..reference import lfm2_moe as R

START_ID = 2
FOLLOWED_STEPS = 3
MODEL_KEYS = ("d_model", "n_heads", "n_kv_heads", "n_layers",
              "n_dense_layers", "d_dense", "d_expert", "n_experts",
              "top_k", "conv_taps", "rope_theta", "norm_eps",
              "norm_topk")


def model_cfg(c):
    """The reference's configuration from the cell's sizes."""
    keys = MODEL_KEYS + ("experts_held", "first_held", "vocab",
                         "routed_scaling", "learning_rate", "adam_beta1",
                         "adam_beta2", "adam_eps", "router_gain",
                         "bias_scale")
    return {k: c[k] for k in keys}


def moe_layers(c):
    return list(range(c["n_dense_layers"], c["n_layers"]))


def program_leaves(c):
    """program variable name -> the reference's names, side by side on
    the last axis in that order (the program fuses q, k, v and each
    gated feed-forward's two input projections into one matrix)."""
    out = {"tok_emb": ["emb"], "out_norm.w": ["out_norm.g"]}
    kinds = R.layer_types(model_cfg(c))
    for i, kind in enumerate(kinds):
        p, r = f"l{i}", f"l{i}"
        out[f"{p}_norm1.w"] = [f"{r}.norm1.g"]
        out[f"{p}_norm2.w"] = [f"{r}.norm2.g"]
        if kind == "conv":
            out[f"{p}_conv_in.w"] = [f"{r}.conv.w_in"]
            out[f"{p}_conv.k"] = [f"{r}.conv.k"]
            out[f"{p}_conv_out.w"] = [f"{r}.conv.w_out"]
        else:
            out[f"{p}_attn_qkv.w"] = [f"{r}.attn.w{x}" for x in "qkv"]
            out[f"{p}_attn_out.w"] = [f"{r}.attn.wo"]
            out[f"{p}_attn_qnorm.w"] = [f"{r}.attn.qnorm.g"]
            out[f"{p}_attn_knorm.w"] = [f"{r}.attn.knorm.g"]
        if i < c["n_dense_layers"]:
            out[f"{p}_ff_w13.w"] = [f"{r}.ff.w1", f"{r}.ff.w3"]
            out[f"{p}_ff_w2.w"] = [f"{r}.ff.w2"]
        else:
            m = f"layer{i}_moe"
            out[f"{m}_gate.w"] = [f"{r}.moe.wg"]
            out[f"{m}_bias"] = [f"{r}.moe.b"]
            out[f"{m}_w13"] = [f"{r}.moe.w1", f"{r}.moe.w3"]
            out[f"{m}_w2"] = [f"{r}.moe.w2"]
    return out


def trained_leaves(c):
    """The leaves Adam updates: all but the routers' biases."""
    fixed = R.buffers(model_cfg(c))
    return {name: parts for name, parts in program_leaves(c).items()
            if not set(parts) & fixed}


def to_program(ref_params, leaves):
    """The program's arrays for `leaves` from the reference's."""
    import jax.numpy as jnp

    return {name: ref_params[parts[0]] if len(parts) == 1
            else jnp.concatenate([ref_params[p] for p in parts], axis=-1)
            for name, parts in leaves.items()}


def group_norms(ref_norms, leaves):
    return {name: math.sqrt(sum(ref_norms[p] ** 2 for p in parts))
            for name, parts in leaves.items()}


def build(c):
    """(program to run, startup, loss variable)."""
    from paddle_tpu import unique_name
    from paddle_tpu.models import lfm2_moe as M

    with unique_name.guard():
        return M.build_program(
            seq_len=c["seq_len"], vocab=c["vocab"],
            learning_rate=c["learning_rate"], beta1=c["adam_beta1"],
            beta2=c["adam_beta2"], epsilon=c["adam_eps"],
            experts_held=(c["first_held"], c["experts_held"]),
            routed_scaling=c["routed_scaling"],
            **{k: c[k] for k in MODEL_KEYS})


def feed_of(batch):
    """The generator's decoder input and its labels are a language
    model's ids and next tokens."""
    return {"ids": batch["tgt_ids"], "label": batch["label"]}


def _norm(a):
    import jax.numpy as jnp

    return float(jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))))


class Trainer:
    """The compiled step with its state: what set-up builds and the
    window drives."""

    def __init__(self, c, seed):
        import paddle_tpu as fluid
        from paddle_tpu import amp
        from paddle_tpu.core.scope import Scope

        self.c, self.seed = c, seed
        self.amp_guard = amp.amp_guard
        self.program, self.startup, self.cost = build(c)
        self.exe = fluid.Executor(fluid.TPUPlace(0))
        self.scope = Scope()
        self.leaves = program_leaves(c)
        self.trained = sorted(trained_leaves(c))
        self.seed_state()

    def seed_state(self):
        """The state before the first step: the startup program's, with
        the seed's weights written over its."""
        with self.amp_guard(self.c["amp"]):
            self.exe.run(self.startup, scope=self.scope)
        for name, value in self._seed_weights():
            self.scope._set(name, value)

    def _seed_weights(self):
        """(program name, array) of every leaf, the reference's weights
        made again a layer at a time so that no second copy of the
        model is ever held."""
        cfg = model_cfg(self.c)
        lo, hi = R.seed_words(self.seed)
        for part in range(-1, cfg["n_layers"]):
            made = R.make_part(lo, hi, cfg, part)
            mine = {n: parts for n, parts in self.leaves.items()
                    if parts[0] in made}
            yield from to_program(made, mine).items()

    def step(self, feed, fetch=()):
        """One step as a Fluid trainer's loop makes it; the loss comes
        back to the host (and `fetch`, in the call that reads the
        routing)."""
        with self.amp_guard(self.c["amp"]):
            out = self.exe.run(self.program, feed=feed,
                               fetch_list=[self.cost, *fetch],
                               scope=self.scope)
        loss = float(np.asarray(out[0]).reshape(-1)[0])
        return (loss, out[1:]) if fetch else loss

    def compiled_text(self, feed):
        """HLO of the window's step as the chip's compiler left it."""
        with self.amp_guard(self.c["amp"]):
            return self.exe.compiled_text(self.program, feed,
                                          [self.cost], self.scope)

    def routing_on_seed_state(self, feed):
        """One call of the step program with the expert layers'
        counters fetched, on the seed's state, which it puts back:
        the experts the first step chooses by layer, the tokens each
        held expert receives [layer, held expert] and their sum by
        layer, and the loss, which is the first followed step's."""
        layers = moe_layers(self.c)
        loss, extra = self.step(feed, [
            f"layer{i}_moe_{tag}" for i in layers
            for tag in ("chosen", "load", "pairs_here")])
        self.seed_state()
        got = [np.asarray(a) for a in extra]
        return {"loss": loss, "chosen": dict(zip(layers, got[0::3])),
                "load": np.stack(got[1::3]),
                "pairs_here": np.concatenate(got[2::3])}

    def first_steps(self, feeds):
        """Drive the first steps with the window's own call; returns
        their losses, the first gradient's norm by leaf and the norm of
        the parameters' change by leaf."""
        losses, grad = [], None
        for n, feed in enumerate(feeds):
            losses.append(self.step(feed))
            if n == 0:
                grad = {name: _norm(self.scope._get(f"{name}_moment1_0"))
                        / (1.0 - self.c["adam_beta1"])
                        for name in self.trained}
        change = {name: _norm(self.scope._get(name) - p0)
                  for name, p0 in self._seed_weights()
                  if name in grad}
        return {"losses": losses, "grad_norms": grad,
                "change_norms": change}

    def free(self):
        for name in list(self.scope.local_var_names()):
            self.scope.erase(name)
        self.exe = self.scope = self.program = self.startup = None
        gc.collect()


def reference_readings(c, seed, feeds, precision="highest", fault=None):
    """What the reference reads over the same first steps."""
    import jax.numpy as jnp

    cfg = model_cfg(c)
    batches = [{k: jnp.asarray(v) for k, v in feed_of(f).items()}
               for f in feeds]
    losses, g_n, chosen, p = R.train_steps(
        R.make_params(seed, cfg), batches, cfg, precision, fault)
    leaves = trained_leaves(c)
    lo, hi = R.seed_words(seed)
    d_n = {}
    for part in range(-1, cfg["n_layers"]):
        for name, p0 in R.make_part(lo, hi, cfg, part).items():
            d_n[name] = _norm(p[name] - p0)
    return {"losses": losses, "grad_norms": group_norms(g_n, leaves),
            "change_norms": group_norms(d_n, leaves),
            "chosen": {i: np.asarray(a) for i, a in chosen.items()}}


def routing_flip_share(got, want):
    """Share of (token, expert layer) pairs whose set of chosen experts
    differs."""
    flips = total = 0
    for i, mine in got.items():
        a = np.sort(mine.reshape(-1, mine.shape[-1]), axis=-1)
        b = np.sort(want[i].reshape(a.shape), axis=-1)
        flips += int((a != b).any(-1).sum())
        total += a.shape[0]
    return flips / max(total, 1)


def compare_readings(got, want, limits, out=None):
    """Hold the program's readings to the reference's."""
    out = out or compare.Compared()
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"]), 1):
        out.add(f"loss_gap_step{i}", compare.relative_gap(a, b),
                limits["loss_gap"])
    gap, leaf = compare.worst_leaf_gap(got["grad_norms"],
                                       want["grad_norms"])
    out.add("grad_norm_gap", gap, limits["grad_norm_gap"], leaf)
    still = compare.still_leaves(want["grad_norms"])
    gap, leaf = compare.worst_leaf_gap(got["change_norms"],
                                       want["change_norms"], skip=still)
    out.add("update_norm_gap", gap, limits["update_norm_gap"], leaf)
    out.add("routing_flip_share",
            routing_flip_share(got["chosen"], want["chosen"]),
            limits["routing_flip_share"])
    return out


class HostLedger:
    """What the host did in each step of a window, kept with the
    profiler off as with it on, so that a step that stalls can be
    laid at some door: the executor's own `exe.*` spans (an ambient
    trace is their sink), the process's processor time and the
    collector's runs. A step costs it seven small objects, some 20
    microseconds. (The chip's machine keeps no count of a thread's
    switches off the processor: `getrusage` reads 0 there.)"""

    def __init__(self):
        from paddle_tpu.observability import tracing

        self.trace = tracing.Trace("window", 0, owner="benchmark")
        self.ambient = tracing.ambient([self.trace])
        self.rows, self.gc_runs, self._gc_t0 = [], [], None

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.monotonic()
        else:
            self.gc_runs.append((self._gc_t0, time.monotonic()))

    def __enter__(self):
        gc.callbacks.append(self._gc)
        self.ambient.__enter__()
        self.mark()
        return self

    def __exit__(self, *exc):
        self.ambient.__exit__(*exc)
        gc.callbacks.remove(self._gc)

    def mark(self):
        """The end of a step (the first call: the window's start)."""
        self.rows.append((time.monotonic(), time.process_time()))

    def steps(self):
        """One record a step: wall ms, the process's processor ms (all
        its threads), collector ms, and the ms under each `exe.*`
        span."""
        spans = sorted(self.trace.spans, key=lambda sp: sp.t0)
        out, at = [], 0
        for a, b in zip(self.rows, self.rows[1:]):
            row = {"ms": (b[0] - a[0]) * 1e3,
                   "process_cpu_ms": (b[1] - a[1]) * 1e3,
                   "gc_ms": sum(t1 - t0 for t0, t1 in self.gc_runs
                                if a[0] <= t0 < b[0]) * 1e3}
            while at < len(spans) and spans[at].t0 < b[0]:
                sp = spans[at]
                row[sp.name] = row.get(sp.name, 0.0) \
                    + (sp.t1 - sp.t0) * 1e3
                at += 1
            out.append(row)
        return out


def long_steps(steps):
    """The steps that took over 1.25 times the median step, with their
    place in the window."""
    median = float(np.median([r["ms"] for r in steps]))
    return [{"step": i, **{k: round(v, 3) for k, v in r.items()}}
            for i, r in enumerate(steps) if r["ms"] > 1.25 * median]


def run(ctx):
    c = ctx.sizes
    spec = ctx.traffic
    if ctx.rehearse:
        from paddle_tpu.ops.pallas import attention

        attention.force_interpret(True)
    feeds = traffic.train_batches(ctx.seed, spec, c, START_ID)
    trainer = Trainer(c, ctx.seed)
    followed = [feed_of(f) for f in feeds[:FOLLOWED_STEPS]]
    routing = trainer.routing_on_seed_state(followed[0])
    load = routing["load"]                      # [layer, held expert]
    ctx.counters["moe_pairs_per_step"] = float(routing["pairs_here"].sum())
    ctx.counters["moe_load_imbalance"] = float(
        (load.max(-1) / np.maximum(load.mean(-1), 1e-9)).mean())
    first = trainer.first_steps(followed)
    first["chosen"] = routing["chosen"]
    used = FOLLOWED_STEPS
    for _ in range(spec["warm_steps"]):
        trainer.step(feed_of(feeds[used % len(feeds)]))
        used += 1
    if ctx.profile:
        # which instruction of the step was traced under which scope
        device_scopes.write_scopes(
            ctx.workload, trainer.compiled_text(feed_of(feeds[0])),
            "lfm2.")
    at_setup = ctx.meter.mark()
    ctx.counters["cache_hits_at_setup"] = at_setup["cache_hits"]
    ctx.counters["backend_compiles_at_setup"] = \
        at_setup["backend_compiles"]

    tokens_per_step = c["batch"] * c["seq_len"]
    # what set-up made is not garbage: keep the collector off it
    gc.collect()
    gc.freeze()
    # a traced run measures the traced window only: stopping the
    # profiler takes seconds, which are no part of any step
    seconds = ctx.trace_seconds if ctx.profile else ctx.seconds
    if ctx.profile:
        ctx.tracer.start()
    setup_s = ctx.clock.setup_s()
    step_ends, losses = [], []
    with HostLedger() as ledger:
        t0 = time.perf_counter()
        while True:
            feed = feed_of(feeds[used % len(feeds)])
            used += 1
            if ctx.tracer.on:
                with ctx.tracer.span("executor_run"):
                    loss = trainer.step(feed)
            else:
                loss = trainer.step(feed)
            now = time.perf_counter()
            ledger.mark()
            step_ends.append(now)
            losses.append(loss)
            if now - t0 >= seconds:
                break
    ctx.tracer.stop()
    window_s = step_ends[-1] - t0
    in_window = ctx.meter.since(at_setup)
    ctx.counters["compiles_in_window"] = \
        in_window["backend_compiles"] + in_window["cache_hits"]
    steps = len(step_ends)
    if ctx.profile:
        ctx.counters["traced_steps"] = steps
    durs = np.diff([t0] + step_ends)
    ctx.note(steps=steps, window_s=window_s,
             step_ms_least=float(durs.min() * 1e3),
             step_ms_median=float(np.median(durs) * 1e3),
             step_ms_greatest=float(durs.max() * 1e3))
    host = ledger.steps()
    ctx.note(long_steps=long_steps(host))
    ctx.write_times({"step_end_s": [t - t0 for t in step_ends],
                     "loss": losses, "host": host})
    ctx.memory_peak = ctx.read_memory_peak()
    trainer.free()

    want = reference_readings(c, ctx.seed, feeds[:FOLLOWED_STEPS])
    compared = compare_readings(first, want, c["limits"])
    # the counters' executable took the timed one's first step
    compared.add("routing_call_loss_gap", compare.relative_gap(
        routing["loss"], first["losses"][0]), c["limits"]["loss_gap"])
    compared.require("window_losses_finite",
                     bool(np.all(np.isfinite(losses))))
    return {
        "attempted": steps, "failed": 0, "compared": compared,
        "end_to_end": {
            "train_tokens_per_s": steps * tokens_per_step / window_s,
            "setup_s": setup_s},
        "observed": {"steps": steps, "window_s": window_s,
                     "tokens_per_step": tokens_per_step,
                     "chips": len(ctx.devices)},
    }
