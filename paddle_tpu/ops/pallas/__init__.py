"""Pallas TPU kernels for the genuinely hot paths (SURVEY.md §7 step 7):
flash attention, layer_norm. Each module exposes usable() gating so ops
fall back to jnp compositions off-TPU or on unsupported shapes.

Shared helpers live here so backend detection and the attention oracle
exist exactly once (kernel modules and the nn_ops fallback all import
them).
"""
import contextlib
import threading

import jax
import jax.numpy as jnp


def on_tpu() -> bool:
    """True when a Mosaic kernel can run where this is being traced:
    the default backend is a TPU, and the program is not one that
    GSPMD partitions over a mesh (auto_partitioned). A backend that
    fails to initialize raises here instead of routing every kernel to
    its jnp reference."""
    return jax.default_backend() == "tpu" and not mesh_placed()


def mesh_placed() -> bool:
    """True while the program being traced is one GSPMD partitions
    over a mesh (inside ``auto_partitioned``)."""
    return getattr(_TRACE, "auto_partitioned", False)


# Thread-local: a serving thread traces its programs lazily while
# another thread may be tracing a single-device one.
_TRACE = threading.local()


@contextlib.contextmanager
def auto_partitioned(on: bool = True):
    """Entered by the step function of a program whose arrays a mesh
    places (core/executor._build_step_fn). XLA cannot split a Mosaic
    custom call ("Mosaic kernels cannot be automatically partitioned.
    Please wrap the call in a shard_map", the first four-chip run of
    PR 21), so inside it every on_tpu()-gated kernel routes to its
    jnp reference, which GSPMD partitions like any other op; the
    routing record shows the decision. Wrapping the kernels in
    shard_map is the faster answer and is not built yet."""
    prev = getattr(_TRACE, "auto_partitioned", False)
    _TRACE.auto_partitioned = bool(on)
    try:
        yield
    finally:
        _TRACE.auto_partitioned = prev


# Trace-time record of kernel routing. `usable()` returning False is
# otherwise indistinguishable from the kernel running, so callers that
# must know (chip_smoke.py, the executor's per-program record) open a
# list with record_routes() and every routing site reports its decision
# through note_route(). Records nest: a decision goes to every list
# that is open, so a caller's list still sees what a program traced
# inside it recorded for itself.
_ROUTE_LOGS = []


@contextlib.contextmanager
def record_routes():
    """Collect (kernel, shape, routed) for every routing decision
    traced inside the block."""
    log = []
    _ROUTE_LOGS.append(log)
    try:
        yield log
    finally:
        # by identity: two logs that hold the same entries are equal
        _ROUTE_LOGS[:] = [l for l in _ROUTE_LOGS if l is not log]


def note_route(kernel: str, shape, routed: bool) -> bool:
    """Report one routing decision; returns `routed` so call sites can
    wrap their usable() test."""
    for log in tuple(_ROUTE_LOGS):  # another thread may open or close one
        log.append((kernel, tuple(int(d) for d in shape), bool(routed)))
    return routed


def reference_attention(q, k, v, scale, causal):
    """Masked-softmax attention oracle: q,k,v [B,H,T,D] -> [B,H,T,D].

    Used as the custom_vjp backward composition for the flash kernel and
    as the off-TPU forward fallback. Masking uses finfo.min (not -inf) so
    fully-masked rows yield a uniform distribution instead of NaN.
    """
    logits = jnp.einsum("bhqd,bhkd->bhqk",
                        q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), dtype=bool), tk - tq)
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    weights = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v.astype(
        jnp.float32)).astype(q.dtype)
