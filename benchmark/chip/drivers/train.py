"""Driver of the training cells: `Executor.run(main, feed,
fetch_list=[loss])` on the transformer's training program, a new seeded
batch every step, the loss read back each step.

Set-up builds one object (the program, its executor and scope, with the
weights the reference's generator makes from the seed), drives it
through its first three steps with the window's own call and feed, and
hands the same object to the window. After the window the reference
follows those three steps and `compare` holds the program's losses, its
first gradient (from Adam's first moment after one step) and the
change of its parameters to it.
"""
import gc
import time

import numpy as np

from .. import compare, program_map, traffic
from ..reference import transformer2017 as R

START_ID = 2
FOLLOWED_STEPS = 3


def _model_cfg(c):
    return {k: c[k] for k in (
        "d_model", "d_inner", "n_heads", "n_layers", "vocab",
        "label_smooth_eps", "warmup_steps", "adam_beta1", "adam_beta2",
        "adam_eps")}


def build(c):
    """(program to run, startup, loss variable) as chip_smoke.py's
    build_trainer does."""
    from paddle_tpu import unique_name
    from paddle_tpu.models import transformer as T

    with unique_name.guard():
        main, startup, cost = T.build_program(
            seq_len=c["seq_len"], d_model=c["d_model"],
            n_heads=c["n_heads"], n_layers=c["n_layers"],
            d_inner=c["d_inner"], vocab=c["vocab"],
            dropout_rate=c["dropout"], with_optimizer=True,
            warmup_steps=c["warmup_steps"])
    return main, startup, cost


def _leaf_norms():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(arrays):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32)))) for a in arrays])

    @jax.jit
    def change_norms(now, before):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b))) for a, b in zip(now, before)])
    return norms, change_norms


class Trainer:
    """The compiled step with its state: what set-up builds and the
    window drives."""

    def __init__(self, c, seed):
        import paddle_tpu as fluid
        from paddle_tpu import amp
        from paddle_tpu.core.scope import Scope

        self.c, self.seed = c, seed
        self.amp_guard = amp.amp_guard
        self.program, startup, self.cost = build(c)
        self.exe = fluid.Executor(fluid.TPUPlace(0))
        self.scope = Scope()
        self.leaves = sorted(program_map.program_leaves(c["n_layers"]))
        with amp.amp_guard(c["amp"]):
            self.exe.run(startup, scope=self.scope)
        for name, value in self._seed_weights().items():
            self.scope._set(name, value)

    def _seed_weights(self):
        return program_map.to_program(
            R.make_params(self.seed, _model_cfg(self.c)),
            self.c["n_layers"])

    def step(self, feed):
        """One step as a Fluid trainer's loop makes it; the loss comes
        back to the host."""
        with self.amp_guard(self.c["amp"]):
            loss, = self.exe.run(self.program, feed=feed,
                                 fetch_list=[self.cost],
                                 scope=self.scope)
        return float(np.asarray(loss).reshape(-1)[0])

    def first_steps(self, feeds):
        """Drive the first steps; returns their losses, the first
        gradient's norm by leaf and the norm of the parameters' change
        by leaf."""
        norms, change_norms = _leaf_norms()
        losses, grad = [], None
        for i, feed in enumerate(feeds):
            losses.append(self.step(feed))
            if i == 0:
                m1 = norms([self.scope._get(f"{n}_moment1_0")
                            for n in self.leaves])
                grad = np.asarray(m1) / (1.0 - self.c["adam_beta1"])
        p0 = self._seed_weights()
        change = np.asarray(change_norms(
            [self.scope._get(n) for n in self.leaves],
            [p0[n] for n in self.leaves]))
        return {"losses": losses,
                "grad_norms": dict(zip(self.leaves, grad.tolist())),
                "change_norms": dict(zip(self.leaves, change.tolist()))}

    def free(self):
        for name in list(self.scope.local_var_names()):
            self.scope.erase(name)
        self.exe = self.scope = self.program = None
        gc.collect()


def reference_readings(c, seed, feeds, precision="highest", rows=None):
    """What the reference reads over the same first steps. `rows`
    keeps only the first that many rows of each batch (the planted
    fault "part of the batch left out")."""
    import jax.numpy as jnp

    cfg = _model_cfg(c)
    p0 = R.make_params(seed, cfg)
    batches = [{k: jnp.asarray(v[:rows] if rows else v)
                for k, v in f.items()} for f in feeds]
    losses, g, p = R.train_steps(p0, batches, cfg, precision,
                                 c["reference_block_rows"])
    g_n = {k: float(jnp.linalg.norm(v)) for k, v in g.items()}
    d_n = {k: float(jnp.linalg.norm(p[k] - p0[k])) for k in p}
    return {"losses": losses,
            "grad_norms": program_map.group_norms(g_n, c["n_layers"]),
            "change_norms": program_map.group_norms(d_n, c["n_layers"])}


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
    return out


def run(ctx):
    c = ctx.sizes
    spec = ctx.traffic
    if ctx.rehearse:
        from paddle_tpu.ops.pallas import attention

        attention.force_interpret(True)
    feeds = traffic.train_batches(ctx.seed, spec, c, START_ID)
    trainer = Trainer(c, ctx.seed)
    first = trainer.first_steps(feeds[:FOLLOWED_STEPS])
    used = FOLLOWED_STEPS
    for _ in range(spec["warm_steps"]):
        trainer.step(feeds[used % len(feeds)])
        used += 1
    at_setup = ctx.meter.mark()
    ctx.counters["cache_hits_at_setup"] = at_setup["cache_hits"]
    ctx.counters["backend_compiles_at_setup"] = \
        at_setup["backend_compiles"]

    tokens_per_step = c["batch"] * c["seq_len"]
    # what set-up made is not garbage: keep the collector off it, so
    # that a full collection inside the window has little to walk
    gc.collect()
    gc.freeze()
    # a traced run measures the traced window only: stopping the
    # profiler takes seconds, which are no part of any step
    seconds = ctx.trace_seconds if ctx.profile else ctx.seconds
    if ctx.profile:
        ctx.tracer.start()
    setup_s = ctx.clock.setup_s()
    step_ends, losses = [], []
    t0 = time.perf_counter()
    while True:
        feed = feeds[used % len(feeds)]
        used += 1
        if ctx.tracer.on:
            with ctx.tracer.span("executor_run"):
                loss = trainer.step(feed)
        else:
            loss = trainer.step(feed)
        now = time.perf_counter()
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
    ctx.write_times({"step_end_s": [t - t0 for t in step_ends],
                     "loss": losses})
    ctx.memory_peak = ctx.read_memory_peak()
    trainer.free()

    want = reference_readings(c, ctx.seed, feeds[:FOLLOWED_STEPS])
    compared = compare_readings(first, want, c["limits"])
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
