"""StableHLO serving export.

SURVEY.md §5 checkpoint/resume: "keep save_inference_model-style
export (StableHLO) as the serving artifact". Reference counterpart:
python/paddle/fluid/io.py:865 save_inference_model writes a frozen
ProgramDesc (`__model__`) that inference/io.cc + NaiveExecutor
(framework/naive_executor.h) re-interpret per request; the TPU-native
serving artifact is the COMPILED program itself: the whole inference
block traced to one XLA computation with the parameters baked in as
constants, serialized with jax.export (StableHLO + calling
convention), loadable and runnable with no paddle_tpu op registry, no
Program interpretation -- any jax-capable server can run it.

    export_stablehlo(model_dir, example_feeds, out_path)
    served = load_stablehlo(out_path)
    fetches = served(feed_dict)          # list of np arrays

The artifact directory holds `model.stablehlo` (serialized Exported)
plus `meta.json` (feed order/shapes/dtypes + fetch names).
"""
from __future__ import annotations

import json
import os
from typing import Dict, List

import numpy as np


def export_stablehlo(model_dir, example_feeds: Dict[str, np.ndarray],
                     out_path, ir_optim: bool = True,
                     platforms=None) -> str:
    """Freeze the inference model at `model_dir` for the shapes of
    `example_feeds` and serialize it as StableHLO.

    Params are baked as constants (self-contained artifact). Returns
    out_path. `platforms` optionally pins lowering platforms (e.g.
    ["tpu", "cpu"]); default is the current backend."""
    import jax
    from jax import export as jexport

    from .config import AnalysisConfig
    from .predictor import AnalysisPredictor

    cfg = AnalysisConfig(str(model_dir))
    cfg.switch_ir_optim(bool(ir_optim))
    pred = AnalysisPredictor(cfg)
    feed_names = pred.get_input_names()
    missing = [n for n in feed_names if n not in example_feeds]
    if missing:
        raise ValueError(f"example_feeds missing inputs: {missing}")

    from ..core.executor import _analyze_block, _build_step_fn

    block = pred._program.global_block
    fetch_names = pred._fetch_names
    mutated, const, state_out = _analyze_block(
        block, tuple(sorted(feed_names)), list(fetch_names))
    step = _build_step_fn(block, tuple(sorted(feed_names)), mutated,
                          const, state_out, list(fetch_names))
    scope = pred._scope
    state_m = {n: np.asarray(scope._get(n)) for n in mutated}
    state_c = {n: np.asarray(scope._get(n)) for n in const}
    rng = jax.random.PRNGKey(0)

    def serve(feeds):
        # params closed over (lowered to constants); inference programs
        # have no state writes worth keeping, fetches are the contract
        _, fetches, _ = step(state_m, state_c, feeds, rng)
        return fetches

    from ..core.executor import _coerce_feed, _var_np_dtype

    # coerce exactly like the live Executor path (executor.py:345):
    # the trace and the advertised meta dtypes must both be the
    # model's declared dtypes, not the caller's raw arrays (float64
    # examples would otherwise record a dtype the computation was
    # never traced with)
    example = {n: np.asarray(_coerce_feed(example_feeds[n],
                                          _var_np_dtype(block, n)))
               for n in feed_names}
    kwargs = {}
    if platforms is not None:
        kwargs["platforms"] = tuple(platforms)
    exported = jexport.export(jax.jit(serve), **kwargs)(example)
    blob = exported.serialize()

    out_path = str(out_path)
    os.makedirs(out_path, exist_ok=True)
    with open(os.path.join(out_path, "model.stablehlo"), "wb") as f:
        f.write(blob)
    meta = {
        "kind": "inference",
        "feed_names": list(feed_names),
        "fetch_names": list(fetch_names),
        "feeds": {n: {"shape": list(example[n].shape),
                      "dtype": str(example[n].dtype)}
                  for n in feed_names},
    }
    with open(os.path.join(out_path, "meta.json"), "w") as f:
        json.dump(meta, f, indent=1)
    return out_path


class StableHLOServer:
    """Loaded serving artifact: a plain callable over feed dicts
    (the NaiveExecutor-serving role, framework/naive_executor.h,
    without any program interpretation)."""

    def __init__(self, dirname):
        from jax import export as jexport

        dirname = str(dirname)
        self._dirname = dirname
        with open(os.path.join(dirname, "model.stablehlo"), "rb") as f:
            self._exported = jexport.deserialize(f.read())
        with open(os.path.join(dirname, "meta.json")) as f:
            self._meta = json.load(f)
        self._check_kind()

    @property
    def feed_names(self) -> List[str]:
        return list(self._meta["feed_names"])

    @property
    def fetch_names(self) -> List[str]:
        return list(self._meta["fetch_names"])

    _KIND = "inference"

    def _check_kind(self):
        kind = self._meta.get("kind", "inference")
        if kind != self._KIND:
            raise ValueError(
                f"artifact at {self._dirname!r} is a {kind!r} export; "
                f"load it with "
                f"{'load_train_stablehlo' if kind == 'train_step' else 'load_stablehlo'}")

    def _coerce_feeds(self, feeds):
        spec = self._meta["feeds"]
        arrs = {}
        for n in self.feed_names:
            if n not in feeds:
                raise ValueError(f"missing feed {n!r}")
            a = np.asarray(feeds[n])
            want = tuple(spec[n]["shape"])
            if tuple(a.shape) != want:
                raise ValueError(
                    f"feed {n!r}: shape {a.shape} != exported {want} "
                    f"(StableHLO artifacts are shape-specialized)")
            arrs[n] = a.astype(spec[n]["dtype"], copy=False)
        return arrs

    def __call__(self, feeds: Dict[str, np.ndarray]) -> List[np.ndarray]:
        outs = self._exported.call(self._coerce_feeds(feeds))
        return [np.asarray(o) for o in outs]


def load_stablehlo(dirname) -> StableHLOServer:
    """Counterpart of reference io.py:1020 load_inference_model for
    the StableHLO artifact."""
    return StableHLOServer(dirname)


def export_train_stablehlo(main_program, scope, example_feeds,
                           fetch_names, out_path, platforms=None) -> str:
    """Freeze a TRAINING step as a StableHLO artifact.

    Counterpart of the reference's C++ train-from-saved-program demo
    (inference/train/demo/, train/test_train_recognize_digits.cc:
    train a `__model__` + startup artifact with no Python). Here the
    artifact is the whole compiled train step with explicit state
    threading:

        served = load_stablehlo(out)
        state = served.initial_state()           # from export time
        state, fetches = served.train_step(state, feeds)

    so any jax-capable runtime can drive the training loop. Optimizer
    state/params ride as inputs+outputs (NOT constants -- they must
    update); feeds are shape-specialized like the inference export."""
    import jax
    from jax import export as jexport

    from ..core.executor import (_analyze_block, _build_step_fn,
                                 _coerce_feed, _var_np_dtype)

    block = main_program.global_block
    feed_names = sorted(example_feeds)
    mutated, const, state_out = _analyze_block(
        block, tuple(feed_names), list(fetch_names))
    step = _build_step_fn(block, tuple(feed_names), mutated, const,
                          state_out, list(fetch_names))
    state0 = {n: np.asarray(scope._get(n)) for n in mutated}
    const0 = {n: np.asarray(scope._get(n)) for n in const}
    from ..core.executor import RNG_VAR, _global_seed

    # exactly Executor.run's key source: the scope's current step key
    # (already advanced by e.g. the startup run) when present, else
    # program seed, else global seed -- so the artifact continues the
    # live session's trajectory bit-for-bit
    rng0 = scope._get(RNG_VAR)
    if rng0 is None:
        seed = getattr(main_program, "_seed", None)
        if seed is None:
            seed = _global_seed[0]
        rng0 = jax.random.PRNGKey(int(seed))
    rng0 = np.asarray(rng0)

    def train_step(state, rng, feeds):
        new_state, fetches, rng_out = step(state, const0, feeds, rng)
        # next step re-reads only `mutated` (executor.py semantics);
        # returning the full state_out set would make the returned
        # pytree an invalid input to the traced signature
        return ({n: new_state[n] for n in mutated}, rng_out, fetches)

    example = {n: np.asarray(_coerce_feed(example_feeds[n],
                                          _var_np_dtype(block, n)))
               for n in feed_names}
    kwargs = {}
    if platforms is not None:
        kwargs["platforms"] = tuple(platforms)
    exported = jexport.export(jax.jit(train_step), **kwargs)(
        state0, rng0, example)

    out_path = str(out_path)
    os.makedirs(out_path, exist_ok=True)
    with open(os.path.join(out_path, "model.stablehlo"), "wb") as f:
        f.write(exported.serialize())
    np.savez(os.path.join(out_path, "state0.npz"), **state0)
    np.save(os.path.join(out_path, "rng0.npy"), rng0)
    meta = {
        "kind": "train_step",
        "feed_names": feed_names,
        "fetch_names": list(fetch_names),
        "state_names": sorted(state0),
        "feeds": {n: {"shape": list(example[n].shape),
                      "dtype": str(example[n].dtype)}
                  for n in feed_names},
    }
    with open(os.path.join(out_path, "meta.json"), "w") as f:
        json.dump(meta, f, indent=1)
    return out_path


def export_train_hlo(main_program, scope, example_feeds, fetch_names,
                     out_path) -> str:
    """Freeze a TRAINING step as an HLO artifact runnable from C++
    with NO Python in the process — the reference's C++ train demo
    (reference paddle/fluid/train/demo/demo_trainer.cc) done the
    XLA-native way. The artifact holds:

      * train_step.hlo.pb — the serialized HloModuleProto of the WHOLE
        train step (forward + backward + optimizer ops, exactly what
        the Executor compiles), flat-parameter calling convention;
      * manifest.json — flat input order (name/dtype/shape/kind/file),
        flat output order, and which output threads back into which
        input between steps;
      * data/*.bin — raw little-endian initial state, rng key, and
        example feeds.

    Drive it with `paddle_tpu.native.run_train_demo(out_path, steps)`
    (compiles native/train_demo/train_demo.cc against the bundled XLA
    runtime) or any XLA-capable host."""
    import jax

    from ..core.executor import (RNG_VAR, _analyze_block,
                                 _build_step_fn, _coerce_feed,
                                 _global_seed, _var_np_dtype)

    block = main_program.global_block
    feed_names = sorted(example_feeds)
    mutated, const, state_out = _analyze_block(
        block, tuple(feed_names), list(fetch_names))
    step = _build_step_fn(block, tuple(feed_names), mutated, const,
                          state_out, list(fetch_names))
    state0 = {n: np.asarray(scope._get(n)) for n in mutated}
    const0 = {n: np.asarray(scope._get(n)) for n in const}
    rng0 = scope._get(RNG_VAR)
    if rng0 is None:
        seed = getattr(main_program, "_seed", None)
        if seed is None:
            seed = _global_seed[0]
        rng0 = jax.random.PRNGKey(int(seed))
    rng0 = np.asarray(rng0)

    def train_step(state, rng, feeds):
        new_state, fetches, rng_out = step(state, const0, feeds, rng)
        return ({n: new_state[n] for n in mutated}, rng_out, fetches)

    example = {n: np.asarray(_coerce_feed(example_feeds[n],
                                          _var_np_dtype(block, n)))
               for n in feed_names}
    args = (state0, rng0, example)
    lowered = jax.jit(train_step).lower(*args)
    hlo_bytes = lowered.compiler_ir(
        "hlo").as_serialized_hlo_module_proto()

    out_path = str(out_path)
    os.makedirs(os.path.join(out_path, "data"), exist_ok=True)
    with open(os.path.join(out_path, "train_step.hlo.pb"), "wb") as f:
        f.write(hlo_bytes)

    # flat input order == jax's pytree flatten order of the traced args
    from jax.tree_util import tree_flatten_with_path

    def _entry_name(path):
        idx = path[0].idx
        if idx == 1:
            return "__rng__", "rng"
        key = path[1].key
        return key, ("state" if idx == 0 else "feed")

    flat_in, _ = tree_flatten_with_path(args)
    inputs = []
    in_index = {}
    for i, (path, leaf) in enumerate(flat_in):
        name, kind = _entry_name(path)
        arr = np.ascontiguousarray(np.asarray(leaf))
        # the traced computation sees jax-canonicalized dtypes (int64
        # demotes to int32 under the default x64-disabled config); the
        # artifact must carry what parameter i actually wants
        arr = arr.astype(jax.dtypes.canonicalize_dtype(arr.dtype))
        fname = f"data/{i:03d}.bin"
        arr.tofile(os.path.join(out_path, fname))
        inputs.append({"name": name, "kind": kind,
                       "dtype": str(arr.dtype),
                       "shape": list(arr.shape), "file": fname})
        in_index[(kind, name)] = i

    out_shape = jax.eval_shape(train_step, *args)
    flat_out, _ = tree_flatten_with_path(out_shape)
    outputs = []
    for path, leaf in flat_out:
        idx = path[0].idx
        if idx == 0:
            name = path[1].key
            dst = in_index.get(("state", name), -1)
            outputs.append({"name": name, "kind": "state",
                            "feeds_input": dst})
        elif idx == 1:
            outputs.append({"name": "__rng__", "kind": "rng",
                            "feeds_input": in_index[("rng", "__rng__")]})
        else:
            fi = path[1].idx
            outputs.append({"name": fetch_names[fi], "kind": "fetch",
                            "feeds_input": -1})
    manifest = {"hlo": "train_step.hlo.pb", "inputs": inputs,
                "outputs": outputs,
                "fetch_names": list(fetch_names)}
    with open(os.path.join(out_path, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return out_path


class StableHLOTrainer(StableHLOServer):
    """Loaded train-step artifact: initial_state() + train_step().
    The PRNG key rides in the state dict under "__rng__" so sampling
    ops (dropout) advance exactly like the live Executor."""

    _KIND = "train_step"
    _RNG = "__rng__"

    def initial_state(self):
        path = os.path.join(self._dirname, "state0.npz")
        with np.load(path) as z:
            state = {k: z[k] for k in z.files}
        state[self._RNG] = np.load(
            os.path.join(self._dirname, "rng0.npy"))
        return state

    def train_step(self, state, feeds):
        state = dict(state)
        rng = state.pop(self._RNG)
        new_state, rng_out, fetches = self._exported.call(
            state, rng, self._coerce_feeds(feeds))
        new_state = dict(new_state)
        new_state[self._RNG] = np.asarray(rng_out)
        return new_state, [np.asarray(f) for f in fetches]

    def __call__(self, feeds):
        raise TypeError("this is a train_step artifact: use "
                        "train_step(state, feeds), starting from "
                        "initial_state()")


def load_train_stablehlo(dirname) -> StableHLOTrainer:
    return StableHLOTrainer(dirname)


def export_train_program(main_program, scope, example_feeds,
                         fetch_names, out_path) -> str:
    """Export a training block for the NATIVE XLA builder
    (native/xla_train/xla_train.cc): unlike `export_train_hlo`, which
    ships an HLO traced by the Python Executor, this artifact ships the
    PROGRAM ITSELF (Program.to_dict JSON) — the C++ driver builds the
    XLA computation from the ops with its own registry kernels, the
    way the reference's C++ core owns kernel dispatch (reference
    framework/op_registry.h:197-270). The Python Executor stays the
    numerical oracle: tests assert per-step loss parity to 1e-5.

    Artifact: program.json + manifest.json (flat input/output order,
    threading links) + data/*.bin. Drive with
    `paddle_tpu.native.run_xla_train(out_path, steps)`."""
    from ..core.executor import _analyze_block, _coerce_feed, \
        _var_np_dtype

    block = main_program.global_block
    feed_names = sorted(example_feeds)
    mutated, const, state_out = _analyze_block(
        block, tuple(feed_names), list(fetch_names))
    out_path = str(out_path)
    os.makedirs(os.path.join(out_path, "data"), exist_ok=True)

    with open(os.path.join(out_path, "program.json"), "w") as f:
        json.dump(main_program.to_dict(), f)

    inputs = []
    in_index = {}

    def add_input(name, kind, arr):
        i = len(inputs)
        import jax as _jax

        arr = np.asarray(arr)
        # canonicalize like the jax runtime (int64->int32 etc. under
        # the default x64-disabled config): the manifest dtypes define
        # the computation's PARAMETER types
        arr = np.ascontiguousarray(
            arr.astype(_jax.dtypes.canonicalize_dtype(arr.dtype)))
        fname = f"data/{i:03d}.bin"
        arr.tofile(os.path.join(out_path, fname))
        inputs.append({"name": name, "kind": kind,
                       "dtype": str(arr.dtype),
                       "shape": list(arr.shape), "file": fname})
        in_index[name] = i

    for n in sorted(mutated) + sorted(const):
        v = scope._get(n)
        if v is None:
            raise RuntimeError(
                f"state var {n!r} missing from scope -- run the "
                f"startup program first")
        add_input(n, "state", v)
    for n in feed_names:
        add_input(n, "feed",
                  _coerce_feed(example_feeds[n],
                               _var_np_dtype(block, n)))

    outputs = [{"name": n, "kind": "state", "feeds_input": in_index[n]}
               for n in sorted(mutated)]
    outputs += [{"name": n, "kind": "fetch", "feeds_input": -1}
                for n in fetch_names]
    manifest = {"program": "program.json", "inputs": inputs,
                "outputs": outputs,
                "fetch_names": list(fetch_names)}
    with open(os.path.join(out_path, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return out_path
