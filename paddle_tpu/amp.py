"""Automatic mixed precision (bf16) -- TPU-native AMP.

The reference frameworks of this era run fp32 everywhere; on TPU the
idiomatic default is bf16 compute with fp32 master weights: the MXU's
native input format is bf16 and HBM bandwidth halves. This module is the
policy layer; `core.registry.run_op` consults it on every op:

* WHITE ops (matmul/conv/attention/embedding -- the MXU ops): float32
  inputs are cast to bfloat16, so the matmul runs native-bf16 and its
  activations flow onward in bf16.
* BLACK ops (softmax/losses/norm statistics/reductions/optimizer
  updates): bfloat16 inputs are cast up to float32; parameters are
  never stored in bf16, so optimizer ops always update fp32 masters.
* Everything else is elementwise-ish glue: when enabled, mixed
  bf16/fp32 float inputs are harmonized DOWN to bf16 (a bias or
  residual read in bf16 is cheaper than promoting the activation up),
  except for a small KEEP set whose output dtype is user-contracted.

Because the grad ops re-run the forward kernel under jax.vjp
(core/registry.py make_vjp_grad_kernel), casting an op's inputs before
the kernel automatically gives the backward pass the same precision:
cotangents w.r.t. fp32 leaves come back fp32 (the cast's transpose),
i.e. bf16 compute with fp32 gradient hand-off to the optimizer.

There is no GradScaler: bf16 has fp32's exponent range, so loss scaling
(needed for fp16 CUDA AMP) is unnecessary -- a real TPU-vs-GPU design
divergence, not an omission.

Enable per-process via `paddle_tpu.amp.enable()` / the `amp_guard`
context, or the FLAGS_use_bf16 env var.
"""
from __future__ import annotations

import contextlib
import os
from typing import Iterable, Optional

import jax.numpy as jnp

# MXU-bound ops: run in bf16.
WHITE_LIST = {
    "mul", "matmul", "fc", "conv2d", "depthwise_conv2d",
    "conv2d_transpose", "conv3d", "conv3d_transpose", "attention",
    "attention_block", "ffn_block",
    "lookup_table", "sequence_conv", "bilinear_tensor_product",
}

# Numerically sensitive ops: run in fp32.
BLACK_LIST = {
    "softmax", "log_softmax",
    "cross_entropy", "sigmoid_cross_entropy_with_logits",
    "layer_norm", "rms_norm", "batch_norm", "group_norm", "instance_norm",
    "data_norm", "l2_normalize", "norm", "lrn",
    "mean", "reduce_mean", "reduce_sum", "reduce_prod", "sum",
    "exp", "log", "pow", "square", "rsqrt", "sqrt",
    "softmax_with_cross_entropy_smooth",
    # optimizer ops always touch fp32 master params
    "sgd", "momentum", "lars_momentum", "adam", "adamax", "adagrad",
    "decayed_adagrad", "adadelta", "rmsprop", "ftrl", "dgc_momentum",
    "clip_by_norm", "squared_l2_norm",
    # accumulation / metric ops
    "accuracy", "auc", "increment",
}

# Ops whose output dtype is part of their user contract: no harmonize.
KEEP_LIST = {"cast", "fill_constant", "assign", "one_hot", "range",
             "uniform_random", "gaussian_random", "eye",
             "fill_zeros_like", "fill_constant_batch_size_like",
             "share_data", "print", "is_empty", "shape",
             # manages its own precision: bf16 [N,V] logits stay put,
             # reductions accumulate fp32 in-register (nn_ops.py swce)
             "softmax_with_cross_entropy",
             # the router reads its input as it comes (float32 from an
             # RMS norm: a choice made from rounded scores wanders) and
             # the experts run in bfloat16 (ops/lm_ops.py moe_dropless)
             "moe_dropless",
             # float32 inside, its input's dtype out; the filter is not
             # rounded on the way in
             "short_conv"}

_enabled = [os.environ.get("FLAGS_use_bf16", "") in
            ("1", "true", "True")]


def enabled() -> bool:
    return _enabled[0]


def enable(on: bool = True) -> None:
    _enabled[0] = bool(on)


def state_token() -> bool:
    """Part of the Executor's compile-cache key: a program compiled with
    AMP on is a different XLA program than one compiled with it off."""
    return _enabled[0]


@contextlib.contextmanager
def amp_guard(enable_flag: bool = True,
              custom_white_list: Optional[Iterable[str]] = None,
              custom_black_list: Optional[Iterable[str]] = None):
    """Context manager enabling bf16 AMP for programs compiled inside."""
    added_w = set(custom_white_list or ()) - WHITE_LIST
    added_b = set(custom_black_list or ()) - BLACK_LIST
    prev = _enabled[0]
    WHITE_LIST.update(added_w)
    BLACK_LIST.update(added_b)
    _enabled[0] = bool(enable_flag)
    try:
        yield
    finally:
        _enabled[0] = prev
        WHITE_LIST.difference_update(added_w)
        BLACK_LIST.difference_update(added_b)


def _is_f32(x) -> bool:
    return getattr(x, "dtype", None) == jnp.float32


def _is_bf16(x) -> bool:
    return getattr(x, "dtype", None) == jnp.bfloat16


def cast_op_inputs(op_type: str, inputs: dict) -> dict:
    """Apply the AMP policy to a resolved {slot: [values]} input dict.

    Called by run_op for every op when AMP is enabled. Grad ops follow
    their forward op's color (mul_grad is white like mul), so the
    recomputed forward inside the vjp sees identical dtypes.
    """
    base = op_type[:-5] if op_type.endswith("_grad") else op_type
    if base in WHITE_LIST:
        want, pred = jnp.bfloat16, _is_f32
    elif base in BLACK_LIST:
        want, pred = jnp.float32, _is_bf16
    elif base in KEEP_LIST:
        return inputs
    else:
        # harmonize: if any float input is bf16, bring fp32 ones down
        if not any(_is_bf16(v) for vals in inputs.values()
                   for v in vals if v is not None):
            return inputs
        want, pred = jnp.bfloat16, _is_f32
    out = {}
    for slot, vals in inputs.items():
        out[slot] = [v.astype(want) if v is not None and pred(v) else v
                     for v in vals]
    return out
