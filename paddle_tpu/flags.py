"""Global FLAGS_* configuration system.

TPU-native analogue of fluid's gflags environment bridge (reference
python/paddle/fluid/__init__.py:129-180 builds an env allowlist and
feeds it to ``core.init_gflags(--tryfromenv=...)``; reference
paddle/fluid/platform/enforce.h + framework/operator.cc:975 implement
the FLAGS_check_nan_inf guard at op granularity).

Design differences, by construction:

* The reference reads flags into C++ gflags consumed by allocators,
  RPC threads, cuDNN heuristics... Most of those subsystems are
  compiler-owned here (XLA picks memory layout, fusion, scheduling),
  so their flags are ACCEPTED as documented no-ops instead of raising
  -- a fluid user's launch script with ``FLAGS_fraction_of_gpu_memory_
  to_use=0.9`` keeps working.
* ``check_nan_inf`` cannot hook each kernel (the whole block is ONE
  XLA program), so the Executor checks every fetched value and every
  mutated state buffer in-graph after the step -- one fused
  all-finite reduction, one scalar transfer -- and raises naming the
  first offending variable (see core/executor.py).
* ``cpu_deterministic``/``cudnn_deterministic`` map to the one real
  nondeterminism knob XLA exposes: matmul precision. Enabling pins
  ``jax_default_matmul_precision="highest"``.

Flags are read from the environment ONCE at import; programmatic
updates go through ``set_flags`` / ``get_flags`` (paddle's public
API shape).
"""
from __future__ import annotations

import os
import warnings

__all__ = ["FLAGS", "set_flags", "get_flags"]


def _as_static_check(s):
    """FLAGS_static_check mode: off | warn | strict (bool spellings
    map 0->off, 1->warn for launch-script convenience)."""
    v = str(s).strip().lower()
    if v in ("off", "warn", "strict"):
        return v
    if v in ("0", "false", "no", ""):
        return "off"
    if v in ("1", "true", "yes", "on"):
        return "warn"
    raise ValueError(f"{s!r} is not one of off/warn/strict")


def _as_cache_mode(s):
    """FLAGS_compile_cache mode: off | ro | rw (bool spellings map
    0->off, 1->rw for launch-script convenience)."""
    v = str(s).strip().lower()
    if v in ("off", "ro", "rw"):
        return v
    if v in ("0", "false", "no", ""):
        return "off"
    if v in ("1", "true", "yes", "on"):
        return "rw"
    raise ValueError(f"{s!r} is not one of off/ro/rw")


def _as_obs_mode(s):
    """FLAGS_observability level: off | metrics | trace (bool
    spellings map 0->off, 1->metrics for launch-script convenience)."""
    v = str(s).strip().lower()
    if v in ("off", "metrics", "trace"):
        return v
    if v in ("0", "false", "no", ""):
        return "off"
    if v in ("1", "true", "yes", "on"):
        return "metrics"
    raise ValueError(f"{s!r} is not one of off/metrics/trace")


def _as_bool(s):
    if isinstance(s, bool):
        return s
    v = str(s).strip().lower()
    if v in ("1", "true", "yes", "on"):
        return True
    if v in ("0", "false", "no", "off", ""):
        return False
    # a typo'd value must not silently disable a guard flag
    raise ValueError(f"{s!r} is not a boolean")


# name -> (type-coercer, default, consumed_here)
# consumed_here=False marks accepted no-ops kept for launch-script
# compatibility (the subsystem they tuned is XLA-owned on TPU).
_DEFS = {
    # guards / determinism (consumed)
    "check_nan_inf": (_as_bool, False, True),
    "cpu_deterministic": (_as_bool, False, True),
    "cudnn_deterministic": (_as_bool, False, True),
    "strict_infer_shape": (_as_bool, False, True),
    # program verifier (paddle_tpu/analysis): run the static checker
    # suite before every Executor compile. off = skip, warn =
    # warnings.warn the diagnostics, strict = raise EnforceNotMet on
    # any error-severity diagnostic (PTA0xx codes)
    "static_check": (_as_static_check, "off", True),
    # unified observability layer (paddle_tpu/observability): off =
    # dormant (no span capture, empty exposition), metrics = central
    # metrics registry exposition + coarse flight-recorder timelines,
    # trace = + per-request span capture and chrome-trace dumps.
    # Always compiled in; read per call so set_flags flips it live.
    "observability": (_as_obs_mode, "off", True),
    # warm-start layer (core/compile_cache.py): persist serialized
    # executables on disk so a fresh process serves every shape with
    # zero in-process compiles. off = current behavior, ro = load
    # existing entries but never write, rw = load + populate.
    "compile_cache": (_as_cache_mode, "off", True),
    # resolved against compile_cache.cache_root() ($JAX_COMPILATION_
    # CACHE_DIR, else <checkout>/.jax_cache), never the current
    # directory; an absolute path stands as given
    "compile_cache_dir": (str, "paddle_tpu_exe", True),
    # disk compile-cache GC (multi-model churn grows the cache dir
    # unboundedly otherwise): prune LRU-by-mtime on write down to
    # these bounds. <= 0 = unbounded. Loads touch mtime so entries
    # a serving process still warm-starts from stay resident.
    "compile_cache_max_entries": (int, 0, True),
    "compile_cache_max_bytes": (int, 0, True),
    # bound on the Executor's in-memory executable cache (LRU;
    # Pass.apply version bumps permanently strand the old entry, so
    # long-lived serving processes leak one executable per program
    # mutation without a cap). <= 0 = unbounded.
    "executor_cache_capacity": (int, 64, True),
    "use_bf16": (_as_bool, False, True),
    "benchmark": (_as_bool, False, True),
    # cross-check the native (C++) block analyzer/GC-planner against the
    # Python oracle on every compile; raise on divergence instead of
    # silently preferring either side
    "native_verify": (_as_bool, False, True),
    # memory / allocator family (XLA buffer assignment owns this)
    "eager_delete_scope": (_as_bool, True, False),
    "eager_delete_tensor_gb": (float, -1.0, False),
    "fast_eager_deletion_mode": (_as_bool, False, False),
    "memory_fraction_of_eager_deletion": (float, 1.0, False),
    "allocator_strategy": (str, "legacy", False),
    "initial_cpu_memory_in_mb": (int, 500, False),
    "init_allocated_mem": (_as_bool, False, False),
    "free_idle_memory": (_as_bool, False, False),
    "use_pinned_memory": (_as_bool, True, False),
    "fraction_of_gpu_memory_to_use": (float, 0.92, False),
    "initial_gpu_memory_in_mb": (int, 0, False),
    "reallocate_gpu_memory_in_mb": (int, 0, False),
    "limit_of_tmp_allocation": (int, -1, False),
    "times_excess_than_required_tmp_allocation": (int, 2, False),
    # threading / rpc family (io_callback + jax.distributed own this)
    "paddle_num_threads": (int, 1, False),
    "dist_threadpool_size": (int, 0, False),
    "inner_op_parallelism": (int, 0, False),
    "rpc_deadline": (int, 180000, False),
    "rpc_send_thread_num": (int, 12, False),
    "rpc_get_thread_num": (int, 12, False),
    "rpc_prefetch_thread_num": (int, 12, False),
    "rpc_disable_reuse_port": (_as_bool, False, False),
    "sync_nccl_allreduce": (_as_bool, False, False),
    # graph/pass family (XLA fusion owns this)
    "enable_parallel_graph": (_as_bool, False, False),
    "fuse_parameter_groups_size": (int, 3, False),
    "fuse_parameter_memory_size": (int, -1, False),
    "enable_subgraph_optimize": (_as_bool, False, False),
    "memory_optimize_debug": (str, "", False),
    "enable_inplace_whitelist": (_as_bool, False, False),
    # cudnn heuristics family (MXU path has no workspace knobs)
    "conv_workspace_size_limit": (int, 4096, False),
    "cudnn_exhaustive_search": (_as_bool, False, False),
    "cudnn_batchnorm_spatial_persistent": (_as_bool, False, False),
    "enable_cublas_tensor_op_math": (_as_bool, False, False),
    # misc accepted no-ops
    "reader_queue_speed_test_mode": (_as_bool, False, False),
    "print_sub_graph_dir": (str, "", False),
    "pe_profile_fname": (str, "", False),
    "warpctc_dir": (str, "", False),
    "multiple_of_cupti_buffer_size": (int, 1, False),
    "tracer_profile_fname": (str, "", False),
    "selected_gpus": (str, "", False),
}


class _Flags:
    """Attribute-style access: ``flags.FLAGS.check_nan_inf``."""

    def __init__(self):
        object.__setattr__(self, "_values", {})
        for name, (coerce, default, _) in _DEFS.items():
            val = default
            env = os.environ.get("FLAGS_" + name)
            if env is not None:
                try:
                    val = coerce(env)
                except (TypeError, ValueError):
                    warnings.warn(
                        f"FLAGS_{name}={env!r} is not a valid "
                        f"{coerce.__name__}; using default {default!r}")
            self._values[name] = val

    def __getattr__(self, name):
        try:
            return object.__getattribute__(self, "_values")[name]
        except KeyError:
            raise AttributeError(f"unknown flag {name!r}") from None

    def __setattr__(self, name, value):
        set_flags({name: value})

    def _set(self, name, value):
        if name.startswith("FLAGS_"):
            name = name[len("FLAGS_"):]
        if name not in _DEFS:
            raise ValueError(
                f"unknown flag {name!r}; known flags: "
                f"{sorted(_DEFS)}")
        coerce, _, consumed = _DEFS[name]
        self._values[name] = coerce(value)
        if not consumed:
            warnings.warn(
                f"FLAGS_{name} is accepted for fluid compatibility but "
                f"has no effect on TPU (the subsystem it tunes is "
                f"XLA-owned)", stacklevel=3)
        self._apply_side_effects(name)

    def _apply_side_effects(self, name):
        if name in ("cpu_deterministic", "cudnn_deterministic"):
            _apply_deterministic(self._values["cpu_deterministic"] or
                                 self._values["cudnn_deterministic"])
        elif name == "use_bf16":
            from . import amp

            amp.enable(self._values["use_bf16"])


def _apply_deterministic(on: bool):
    """Deterministic mode: the one compiler-level nondeterminism knob on
    TPU is matmul precision promotion; pin it to 'highest' so repeated
    runs bit-match (reference: FLAGS_cudnn_deterministic pins cuDNN
    algo selection, operator.cc)."""
    import jax

    jax.config.update("jax_default_matmul_precision",
                      "highest" if on else None)


def set_flags(flags: dict):
    """paddle-API-shaped programmatic update: set_flags({'FLAGS_check_
    nan_inf': 1})."""
    for k, v in flags.items():
        FLAGS._set(k, v)


def get_flags(names):
    if isinstance(names, str):
        names = [names]
    out = {}
    for n in names:
        key = n[len("FLAGS_"):] if n.startswith("FLAGS_") else n
        out["FLAGS_" + key] = getattr(FLAGS, key)
    return out


FLAGS = _Flags()

# env-driven side effects applied once at import, through the same
# path set_flags uses so the two can't drift
for _name in ("cpu_deterministic", "cudnn_deterministic", "use_bf16"):
    if FLAGS._values[_name]:
        FLAGS._apply_side_effects(_name)
