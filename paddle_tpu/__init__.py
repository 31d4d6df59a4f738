"""paddle_tpu: a TPU-native deep-learning framework with the capability
surface of PaddlePaddle Fluid (reference mounted at /root/reference).

Architecture (vs the reference's interpret-the-graph design):
  Python builds a Program (program-as-data, like fluid) ->
  Executor lowers whole blocks through JAX to ONE XLA computation ->
  XLA schedules fusion/memory/collectives on TPU (MXU for matmuls,
  ICI collectives via sharding annotations instead of NCCL op handles).

Top-level API mirrors `paddle.fluid`: layers, Program, Executor,
optimizer, backward, io, initializer, ParamAttr, CompiledProgram...
"""
from . import ops as _ops  # registers all kernels
from .core.program import (Program, Block, Variable, Operator,
                           default_main_program, default_startup_program,
                           program_guard, switch_main_program,
                           switch_startup_program, device_scope)
from .core.executor import (Executor, TPUPlace, CPUPlace, CUDAPlace,
                            CUDAPinnedPlace,
                            seed)
from .core.scope import Scope, global_scope, _reset_global_scope
from .core import registry as _registry
from .core.registry import registered_ops
from .backward import append_backward, gradients
from .param_attr import ParamAttr, WeightNormParamAttr
from . import layers
from . import initializer
from . import optimizer
from . import regularizer
from . import clip
from . import unique_name
from . import nets
from . import metrics
from . import profiler
from . import observability
from .io import (save_vars, save_params, save_persistables, load_vars,
                 load_params, load_persistables, save_inference_model,
                 load_inference_model, save_sharded_persistables,
                 load_sharded_persistables)
from .core.compiler import CompiledProgram, BuildStrategy, \
    ExecutionStrategy, ParallelExecutor
from .data_feeder import DataFeeder
from .reader import PyReader
from . import dygraph
from . import readers
from .readers import batch
from . import dataset
from . import ir
from . import inference
from . import transpiler
from .transpiler import DistributeTranspiler, DistributeTranspilerConfig, \
    memory_optimize, release_memory, InferenceTranspiler
from . import distributed
from . import distribute_lookup_table
from . import amp
from . import flags
from .flags import set_flags, get_flags
from . import enforce
from .enforce import EnforceNotMet
from . import train_checkpoint
from .train_checkpoint import TrainCheckpoint
from . import contrib
from . import lod_tensor
from .lod_tensor import create_lod_tensor, create_random_int_lodtensor
from . import average
from .average import WeightedAverage  # noqa: F401
from . import recordio_writer  # noqa: F401
from .lod_tensor import LoDTensor  # noqa: F401
# reference fluid exposes Tensor as an alias of LoDTensor
# (python/paddle/fluid/__init__.py Tensor = LoDTensor)
Tensor = LoDTensor
LoDTensorArray = list
from .layers import learning_rate_scheduler as learning_rate_decay  # noqa: F401,E402
from . import debugger
from . import net_drawer
from . import evaluator
from . import install_check
from .async_executor import AsyncExecutor
from .data_feed import DataFeedDesc

# fluid-compat: many scripts do `import paddle.fluid as fluid`; we expose
# the same names so `import paddle_tpu as fluid` works.
name_scope = program_guard


def scope_guard(scope):
    import contextlib

    @contextlib.contextmanager
    def _guard():
        from .core import scope as scope_mod

        old = scope_mod._global_scope
        scope_mod._global_scope = scope
        try:
            yield
        finally:
            scope_mod._global_scope = old

    return _guard()


def cuda_places(device_ids=None):
    import jax

    n = len(jax.devices())
    ids = device_ids if device_ids is not None else range(n)
    return [TPUPlace(i) for i in ids]


def cpu_places(device_count=None):
    return [CPUPlace()]


def cuda_pinned_places(device_count=None):
    """reference framework.py:153 cuda_pinned_places: page-locked
    staging buffers. XLA manages host staging itself; returns
    CUDAPinnedPlace objects (CPU-backed) for isinstance parity."""
    return [CUDAPinnedPlace() for _ in range(device_count or 1)]


def device_count():
    import jax

    return len(jax.devices())


def is_compiled_with_cuda():
    return False


def is_compiled_with_tpu():
    return True


__version__ = "0.1.0"
