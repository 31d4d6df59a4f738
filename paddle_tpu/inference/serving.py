"""InferenceServer: batched, bucketed serving over a loaded predictor.

Reference counterpart: inference/api/analysis_predictor.cc:192 Run is a
one-request API — the reference leaves batching to the caller (its C++
deploy apps loop requests through one predictor). Serving heavy traffic
on TPU inverts the economics: every `AnalysisPredictor.run` costs one
Python dispatch plus one host readback, and every DISTINCT feed shape
costs a fresh XLA compile (the executable cache is keyed on feed
specs). This module applies the PERF.md "Host dispatch & the multi-step
scan" arithmetic to inference — amortize dispatch/readback over a
micro-batch — plus the Clipper/ORT-style dynamic-batching discipline
(PAPERS.md):

* **DynamicBatcher** — a thread-safe request queue; a single batcher
  thread forms micro-batches up to ``max_batch_size`` rows or
  ``max_wait_ms`` after the oldest queued request, runs ONE compiled
  executable, and demultiplexes output rows back to each caller.
* **Shape bucketing** — the batch dim is padded UP to a fixed ladder
  (1, 2, 4, ... max_batch_size) by replicating the last real row, and
  declared ``-1`` sequence dims are padded up to ``seq_buckets`` (with
  ``name@SEQ_LEN`` companions left at the REAL lengths), so the number
  of executables is bounded by #batch-buckets x #seq-buckets instead
  of growing with traffic shape diversity.
* **aot_warmup()** — pre-compiles every bucket before traffic by
  pushing one synthetic batch per bucket through the normal path; this
  SEEDS the Executor cache (keyed on feed specs), it is not a second
  compiler path.
* **GenerationServer** — routes multi-token requests through the
  KV-cached While-loop decode program
  (models/decode_engine.py build_incremental_decode_program), so a
  T-token generation is ONE dispatch + ONE readback instead of T.
* **ContinuousGenerationServer** — iteration-level scheduling over a
  fixed slot pool (Orca OSDI'22 / vLLM SOSP'23, PAPERS.md): a
  single-step decode program advances every occupied slot one token
  per dispatch, queued prompts are admitted into free slots by a
  prefill dispatch, and EOS'd lanes retire IMMEDIATELY — no
  head-of-line blocking on the longest request in a batch, which is
  the whole-loop server's structural cost under mixed output lengths.
* **PagedContinuousGenerationServer** — the same scheduler over the
  PAGED KV layout (models/decode_engine.py): host-allocated block
  tables over a shared self-KV pool, prefix-cache admission
  (hit/partial/miss tiers; a repeated system prompt prefills once),
  block-pool backpressure with the named retryable
  ``BlockPoolExhausted``, and block-pool gauges.

Observability: `stats()` returns queue depth, batch occupancy, compile
and cache-hit counts (Executor.compile_count / cache_hit_count),
p50/p99 request latency, time-to-first-token and per-generated-token
latency; the generation servers add slot occupancy and retired
requests/s — serving perf work is unverifiable without them.
"""
from __future__ import annotations

import collections
import itertools
import math
import re
import threading
import time
from concurrent import futures
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..core.executor import Executor, PreparedCache, TPUPlace
from ..core.scope import Scope, global_scope
from ..core.types import to_np_dtype
from ..analysis import absint as _absint
from ..models.decode_engine import POOL_MARK as dec_POOL_MARK
from ..models.decode_engine import (AdmissionInfeasible,
                                    BlockLifetimeError,
                                    BlockPoolExhausted, HostBlockPool,
                                    PromptPrefixCache, RadixBlockTree,
                                    SPEC_COUNTERS, SPEC_LANE_COUNTERS,
                                    ServingUnavailable, fed_name)
from ..observability import costmodel as obs_costmodel
from ..observability import devtel as obs_devtel
from ..observability import metrics as obs_metrics
from ..observability import tracing as obs_tracing
from ..observability.metrics import Histogram
from ..observability.tracing import cache_tier as _cache_tier
from ..ops.paged_ops import ROUTE_LABELS

SEQ_SUFFIX = "@SEQ_LEN"


def default_batch_buckets(max_batch_size: int) -> List[int]:
    """Power-of-two ladder 1,2,4,... capped at (and always including)
    max_batch_size (the shape-specialization analogue of the
    reference's TRT max-batch knob, inference/api/
    paddle_analysis_config.h EnableTensorRtEngine max_batch_size —
    there one engine serves [1, max]; XLA specializes per shape, so
    the ladder bounds the specialization count instead)."""
    if max_batch_size < 1:
        raise ValueError(f"max_batch_size must be >= 1, got "
                         f"{max_batch_size}")
    ladder = []
    b = 1
    while b < max_batch_size:
        ladder.append(b)
        b *= 2
    ladder.append(max_batch_size)
    return ladder


def _bucket_for(size: int, ladder: Sequence[int], what: str) -> int:
    for b in ladder:
        if size <= b:
            return b
    raise ValueError(
        f"{what} {size} exceeds the largest bucket {max(ladder)}; "
        f"raise the bucket ladder or split the request")


def _pad_rows(arr: np.ndarray, rows: int) -> np.ndarray:
    """Pad the batch axis up to `rows` by replicating the last real
    row: replication (vs zeros) keeps padded rows numerically benign
    for any op (no fresh NaN/inf paths), and padded rows are sliced
    away before demux anyway."""
    have = arr.shape[0]
    if have == rows:
        return arr
    reps = np.repeat(arr[-1:], rows - have, axis=0)
    return np.concatenate([arr, reps], axis=0)


def _pad_axis(arr: np.ndarray, axis: int, size: int) -> np.ndarray:
    """Zero-pad `axis` up to `size` (sequence bucketing; real lengths
    ride the @SEQ_LEN companion untouched)."""
    have = arr.shape[axis]
    if have == size:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, size - have)
    return np.pad(arr, widths)


# per-request future the batcher thread fulfils after demux; the
# stdlib Future already provides done()/result(timeout)/set_result/
# set_exception with the right rethrow semantics
_Reply = futures.Future


class ServerQuiesced(ServingUnavailable):
    """submit() hit a server that stopped ACCEPTING but is still
    draining its queue (ModelRegistry hot swap: quiesce -> drain ->
    close). Distinct from ServerClosed so routing layers can
    re-resolve the model alias and retry instead of failing the
    request; ``retryable=True`` with a short ``retry_after_ms`` (the
    swap flip is milliseconds away). No direct reference counterpart:
    the reference swaps models by restarting predictor processes, so
    it never needs an accepting/draining distinction."""

    retryable = True
    retry_after_ms = 2.0


class ServerClosed(ServingUnavailable):
    """submit() hit a server whose close() already ran. Typed (not a
    bare RuntimeError) so the Router's swap-transparency retry can
    catch it by TYPE — matching on message substrings would silently
    retry unrelated errors; retryable because under the registry's
    warm-then-flip discipline a closed server means the alias already
    points at its replacement. No direct reference counterpart (see
    ServerQuiesced)."""

    retryable = True
    retry_after_ms = 2.0


class RequestCancelled(ServingUnavailable):
    """The terminal outcome of ``reply.cancel()``: the request was
    torn down (dequeued, or its lane retired at the next burst
    boundary with every block / prompt-entry / radix hold released —
    the PTA201 ``cancel`` exit) before producing a full response.
    NOT retryable: the caller asked for exactly this. Reference
    counterpart: none — the reference's synchronous predictors
    (inference/api/analysis_predictor.cc Run) cannot abandon a
    request mid-flight."""

    retryable = False


class DeadlineExceeded(ServingUnavailable):
    """A request's ``deadline_ms`` budget expired before completion:
    queued past its deadline (shed before occupying a slot) or still
    decoding at a burst boundary past it (server-initiated cancel —
    rides the same PTA201 ``cancel`` release path as
    ``RequestCancelled``). NOT retryable as-is: the same request
    under the same deadline sheds again; callers must relax the SLO
    or retry against spare capacity. Reference counterpart: none
    (see RequestCancelled)."""

    retryable = False


class GenerationReply(futures.Future):
    """Whole-response future for one generation request, with a
    cancel() that actually frees device state: the stdlib
    ``Future.cancel`` only flips a client-side flag, but an abandoned
    generation keeps burning a lane, KV blocks, and radix holds until
    it finishes — so this subclass routes cancel() through the owning
    server, which retires the lane at the next burst boundary and
    releases every hold through the PTA201 ``cancel`` release sites.
    The reply then fails with ``RequestCancelled``. Returns True when
    the cancellation was accepted (the request was still queued or
    live under the scheduler lock), False when the outcome was
    already decided. Reference counterpart: none — the reference's
    predictors are synchronous (inference/api/analysis_predictor.cc
    Run); request teardown is the async front door's addition."""

    _gen_server = None
    _gen_req = None

    def cancel(self):
        srv, req = self._gen_server, self._gen_req
        if srv is not None and req is not None:
            return srv._cancel_request(req, "cancelled")
        return super().cancel()


class StreamingReply:
    """Per-token delivery handle returned by ``submit(stream=True)``
    (the front door's Orca-style iteration-level surface; SURVEY §7's
    AsyncExecutor/RPC-server capability, reference
    inference/api/api_impl.cc:71 NativePaddlePredictor::Run — there
    one blocking call per whole response).

    Iterating yields ``(seq, token)`` pairs as bursts land: ``seq``
    is a monotone 0-based sequence number, ``token`` a python int.
    Tokens are delivered from the per-burst host readback the
    scheduler already performs — streaming adds NO fetches and NO
    programs (zero steady-state compiles is unchanged). Iteration
    ends after the final token; ``finish_reason`` then reads "eos" |
    "length" | "cancelled" | "deadline" | "error".

    Byte-parity contract (pinned in tests and per bench leg): the
    concatenation of the streamed tokens equals the generated region
    ``row[1:1+n]`` of the sentinel-normalized row the whole-response
    path returns for the same submit (``n`` =
    ``count_generated_tokens``; position 0 is the GO token, the tail
    past the terminator is the -1 sentinel — neither is streamed),
    and ``result(timeout)`` returns that same full row.

    ``cancel()`` tears the request down exactly like
    ``GenerationReply.cancel`` (iteration then ends with
    finish_reason "cancelled" and ``result`` raises
    ``RequestCancelled``). ``ttft_s`` is the client-observed
    first-token wall-clock instant minus submit time (the bench's
    streamed-TTFT measure). Thread-safe: one scheduler produces,
    any number of consumer threads may iterate (each event is
    delivered once)."""

    def __init__(self, server):
        self._cond = threading.Condition()
        self._events = collections.deque()  # (seq, int token)
        self._fin = None        # finish_reason once decided
        self._exc = None
        self._server = server
        self._req = None        # backref set by submit()
        self._future = None     # the underlying GenerationReply
        self.t_submit = time.monotonic()
        self.t_first = None     # wall instant the first token landed

    # --- consumer side -----------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        with self._cond:
            while not self._events and self._fin is None:
                self._cond.wait()
            if self._events:
                return self._events.popleft()
            raise StopIteration

    def result(self, timeout: Optional[float] = None):
        """The whole sentinel-normalized row (identical to the
        non-streaming future's result; raises RequestCancelled /
        DeadlineExceeded / the dispatch error on teardown)."""
        return self._future.result(timeout)

    def done(self) -> bool:
        return self._future.done()

    def cancel(self) -> bool:
        return self._server._cancel_request(self._req, "cancelled")

    @property
    def finish_reason(self) -> Optional[str]:
        with self._cond:
            return self._fin

    @property
    def ttft_s(self) -> Optional[float]:
        with self._cond:
            if self.t_first is None:
                return None
            return self.t_first - self.t_submit

    # --- producer side (scheduler thread, OUTSIDE the server lock) ---
    def _push(self, first_seq: int, toks) -> None:
        now = time.monotonic()
        with self._cond:
            if self.t_first is None:
                self.t_first = now
            for i, t in enumerate(toks):
                self._events.append((first_seq + i, int(t)))
            self._cond.notify_all()

    def _finish(self, reason: str, exc=None) -> None:
        with self._cond:
            if self._fin is None:
                self._fin = reason
                self._exc = exc
            self._cond.notify_all()


def _call_scheduling_hook(server, hook, arg, hook_name, fallback):
    """Run a pluggable queue-selection hook; on ANY exception warn
    ONCE per server (the `_hook_warned` latch) and return (False,
    None) so the caller falls back to its default policy. A sane
    call that returns an invalid pick is the CALLER's check — a hook
    may legitimately decline — and falls back silently."""
    try:
        return True, hook(arg)
    except Exception as e:
        if not server._hook_warned:
            server._hook_warned = True
            import warnings

            warnings.warn(
                f"{hook_name} hook failed ({type(e).__name__}: {e}); "
                f"falling back to {fallback} for this server")
        return False, None


def _pct(sorted_vals, p):
    """Nearest-rank percentile over an ascending list (ceil(p*N)-1:
    int(p*N) overshoots — p50 of 2 samples must be the 1st, not the
    2nd). None on empty. Kept as the EXACT oracle the observability
    histograms are pinned against (tests/test_observability.py)."""
    if not sorted_vals:
        return None
    idx = max(0, math.ceil(p * len(sorted_vals)) - 1)
    return round(sorted_vals[min(len(sorted_vals) - 1, idx)], 3)


def _pct_dict(vals):
    """p50/p99 dict from a fixed-bucket Histogram (the O(1)-memory
    serving path — a million-request run holds bucket counts, not raw
    samples) or, for compatibility, any iterable of raw samples."""
    if isinstance(vals, Histogram):
        return vals.percentile_dict()
    lat = sorted(vals)
    return {"p50": _pct(lat, 0.50), "p99": _pct(lat, 0.99)}


_obs_server_seq = itertools.count(1)


# the phases of a scheduler cycle that get a histogram
# (`paddle_tpu_server_cycle_ms{phase=...}`, `stats()["cycle_ms"]`), by
# the span that times each; `gc` and `wall` are the ring's own
_CYCLE_PHASES = {
    **{name: "slotpool." + name for name in
       ("plan", "feed", "dispatch", "retire", "deliver")},
    **{name: name for name in ("exe.feed", "exe.state", "exe.call",
                               "exe.store", "exe.fetch")}}


def _obs_server_id(server) -> str:
    """Stable per-instance metrics label, e.g. InferenceServer-3
    (itertools.count: thread-safe like Executor._obs_seq — servers
    are constructed concurrently by registry loads)."""
    return f"{type(server).__name__}-{next(_obs_server_seq)}"


class _Request:
    __slots__ = ("feed", "rows", "reply", "t_arrival", "trace")

    def __init__(self, feed, rows, reply, trace=None):
        self.feed = feed
        self.rows = rows
        self.reply = reply
        self.t_arrival = time.monotonic()
        # observability: the request's Trace (observability/tracing),
        # None unless FLAGS_observability=trace. Router-owned traces
        # are finished by the router's completion path; server-owned
        # ones (standalone servers) are finished at demux.
        self.trace = trace


class _PredictorRunner:
    """Adapts an AnalysisPredictor to the server's runner protocol."""

    def __init__(self, predictor):
        self._predictor = predictor
        self.feed_names = list(predictor.get_input_names())
        self.fetch_names = list(predictor.get_output_names())
        self.program = predictor.program()
        self.executor = predictor._exe

    def run_batch(self, feed):
        return self._predictor._run_feed(feed)


class ProgramRunner:
    """Runs a raw Program (the generation path) through an Executor
    against a trained scope (the serving reading of reference
    python/paddle/fluid/executor.py:451 run); one batched
    device->host pull per batch (see AnalysisPredictor._run_feed for
    the per-fetch pitfall)."""

    def __init__(self, program, feed_names, fetch_names, executor=None,
                 scope=None):
        self.program = program
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        self.executor = executor or Executor(TPUPlace(0))
        self.scope = scope or global_scope()
        # batcher hot loop: one PreparedProgram per bucket shape
        # (core/executor.py PreparedCache; PERF.md "Host dispatch")
        self._prepared = PreparedCache(self.executor, program,
                                       self.fetch_names, self.scope)

    def run_batch(self, feed):
        import jax

        # execute/readback spans attach to every co-batched request
        # via the ambient batch context the server set (near-free when
        # tracing is off: one thread-local lookup per span); the
        # execute_span helper stamps the cache-tier attr from counter
        # deltas, covering a prepared-lookup-miss compile
        with obs_tracing.execute_span(self.executor):
            # None = program not preparable (go ops / CompiledProgram
            # / native build): per-call Executor.run path
            prepared = self._prepared.lookup(feed)
            if prepared is not None:
                outs = prepared.run(feed, return_numpy=False)
            else:
                outs = self.executor.run(self.program, feed=feed,
                                         fetch_list=self.fetch_names,
                                         scope=self.scope,
                                         return_numpy=False)
        with obs_tracing.span("readback"):
            return [np.asarray(o) for o in jax.device_get(outs)]


class InferenceServer:
    """Dynamic-batching, shape-bucketing server over a predictor.

    Reference counterpart: AnalysisPredictor::Run
    (inference/api/analysis_predictor.cc:192) is the one-request API
    this batches over; the reference has no traffic layer (its C++
    deploy apps loop requests), so the batcher follows the
    Clipper/ORT dynamic-batching discipline instead (PAPERS.md).

    Requests are feed dicts whose arrays carry a leading batch axis
    (batch-of-1 arrivals are the common case); fetched outputs must be
    batch-major the same way (true for every program this framework
    builds: fixed-size padded outputs with batch at axis 0).

    ``submit`` enqueues and returns a future-like reply; ``infer``
    blocks for one request. A single batcher thread groups compatible
    requests (same post-bucketing shape signature), pads the batch dim
    up the bucket ladder, runs ONE executable, and slices each
    caller's rows back out.
    """

    def __init__(self, predictor_or_runner,
                 max_batch_size: Optional[int] = None,
                 max_wait_ms: Optional[float] = None,
                 batch_buckets: Optional[Sequence[int]] = None,
                 seq_buckets: Optional[Sequence[int]] = None,
                 select_group=None,
                 start: bool = True):
        # precedence: explicit constructor args > the predictor
        # config's enable_dynamic_batching knobs > built-in defaults
        # (a call site tightening max_batch_size must win over the
        # config it did not write)
        knobs = None
        if hasattr(predictor_or_runner, "run_batch"):
            self._runner = predictor_or_runner
        else:
            self._runner = _PredictorRunner(predictor_or_runner)
            cfg = getattr(predictor_or_runner, "_config", None)
            knobs = getattr(cfg, "serving_options", lambda: None)()
        if knobs:
            if max_batch_size is None:
                max_batch_size = knobs.get("max_batch_size")
            if max_wait_ms is None:
                max_wait_ms = knobs.get("max_wait_ms")
            if batch_buckets is None:
                batch_buckets = knobs.get("batch_buckets")
            if seq_buckets is None and knobs.get("seq_buckets"):
                seq_buckets = knobs["seq_buckets"]
        self.max_batch_size = int(
            max_batch_size if max_batch_size is not None else 8)
        self.max_wait_ms = float(
            max_wait_ms if max_wait_ms is not None else 2.0)
        seq_buckets = seq_buckets if seq_buckets is not None else ()
        self.batch_buckets = sorted(
            set(batch_buckets or default_batch_buckets(
                self.max_batch_size)))
        if self.batch_buckets[-1] < self.max_batch_size:
            raise ValueError(
                f"batch_buckets {self.batch_buckets} do not cover "
                f"max_batch_size={self.max_batch_size}")
        self.seq_buckets = sorted(set(int(s) for s in seq_buckets))
        self._feed_names = list(self._runner.feed_names)
        self._fetch_names = list(self._runner.fetch_names)
        self._block = self._runner.program.global_block

        self._cv = threading.Condition()
        # group key -> FIFO of pending requests (insertion order is
        # arrival order; dict preserves group creation order)
        self._groups: Dict[tuple, collections.deque] = {}
        self._running = False
        self._closed = False     # close() called: reject everything
        self._accepting = True   # quiesce() flips; drain/close path
        self._inflight = 0       # batches handed to the runner
        self._thread: Optional[threading.Thread] = None
        # pluggable queue selection: callable(groups) -> group key,
        # where `groups` maps key -> tuple of queued requests (each
        # with .rows and .t_arrival). Called under the server lock —
        # it must be fast and must NOT call back into the server.
        # None / a bad return / an exception fall back to the default
        # oldest-request-first policy.
        self._select_group_hook = select_group
        self._hook_warned = False

        # observability counters (under _cv)
        self._n_requests = 0
        self._n_batches = 0
        self._n_rows = 0
        self._n_padded_rows = 0
        self._n_done = 0
        self._n_tokens = 0
        # fixed-bucket histograms (observability/metrics): O(1) memory
        # for a million-request run; p50/p99 read from bucket counts
        # (within one bucket width of exact — pinned in tests)
        self._latencies = Histogram("paddle_tpu_request_latency_ms")
        # time-to-first-token: for one-shot inference (and the
        # whole-loop generation server) the first token and the last
        # arrive in the same readback, so TTFT == request latency —
        # recorded separately anyway so the continuous server's
        # stats() shape is identical and legs are comparable
        self._ttft = Histogram("paddle_tpu_request_ttft_ms")
        self._per_token = Histogram("paddle_tpu_per_token_ms")
        self._t_first_arrival = None
        self._t_last_done = None
        self._warmed_compiles = 0
        self._t_start = time.monotonic()   # monotonic uptime anchor
        self._t_window = self._t_start     # stats(reset=True) window
        # observability: pull-provider registration (weakref — the
        # registry reads these counters only at expose() time)
        self._obs_id = _obs_server_id(self)
        obs_metrics.register_provider(self)

        if start:
            self.start()

    # --- lifecycle ----------------------------------------------------
    def start(self):
        with self._cv:
            if self._running:
                return
            self._running = True
            # an explicit restart after close() re-opens the server
            # (pre-lifecycle behavior: submit gated on _running only)
            self._closed = False
            self._accepting = True
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def quiesce(self):
        """Stop ACCEPTING new requests (submit raises ServerQuiesced)
        while the batcher keeps draining queued + in-flight work — the
        hot-swap half of close(). Idempotent."""
        with self._cv:
            self._accepting = False

    def drain(self, timeout: Optional[float] = 60.0) -> bool:
        """Block until every queued request has been dispatched AND
        every in-flight batch has completed (their futures fulfilled).
        True on fully drained, False on timeout. Usually preceded by
        quiesce() so the queue cannot refill behind the wait."""
        deadline = None if timeout is None \
            else time.monotonic() + timeout
        with self._cv:
            while self._running and (
                    any(self._groups.values()) or self._inflight):
                if deadline is None:
                    self._cv.wait()
                    continue
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(left)
            return not (any(self._groups.values()) or self._inflight)

    def close(self, timeout: float = 5.0):
        """Stop the batcher; pending requests are failed, not dropped
        silently."""
        with self._cv:
            self._running = False
            self._closed = True
            self._accepting = False
            pending = [r for grp in self._groups.values() for r in grp]
            self._groups.clear()
            self._cv.notify_all()
        for r in pending:
            r.reply.set_exception(
                ServerClosed("InferenceServer closed"))
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # --- request path -------------------------------------------------
    def submit(self, feed: Dict[str, np.ndarray]) -> _Reply:
        feed = {k: np.asarray(v) for k, v in feed.items()}
        missing = [n for n in self._feed_names if n not in feed]
        if missing:
            raise ValueError(f"missing inputs: {missing}")
        rows = int(feed[self._feed_names[0]].shape[0])
        if rows < 1:
            raise ValueError("empty request: feeds need >= 1 row")
        for n in self._feed_names:
            if feed[n].shape[0] != rows:
                raise ValueError(
                    f"feed {n!r} has {feed[n].shape[0]} rows but "
                    f"{self._feed_names[0]!r} has {rows}; all inputs "
                    f"share the batch axis")
        if rows > self.max_batch_size:
            raise ValueError(
                f"request of {rows} rows exceeds max_batch_size="
                f"{self.max_batch_size}; split it client-side")
        feed, key = self._bucket_seq(feed)
        reply = _Reply()
        # request tracing: adopt the router's trace when one is parked
        # in the ambient request context, else (standalone server at
        # FLAGS_observability=trace) open a server-owned one
        trace = obs_tracing.current_request_trace()
        if trace is None:
            trace = obs_tracing.start_request(owner="server",
                                              server=self._obs_id)
        req = _Request(feed, rows, reply, trace=trace)
        with self._cv:
            # not-yet-started servers QUEUE (start() drains them);
            # only closed/quiesced ones reject
            if self._closed:
                raise ServerClosed("InferenceServer is closed")
            if not self._accepting:
                raise ServerQuiesced(
                    "InferenceServer is quiesced (draining for "
                    "retire/hot swap); re-resolve the model and "
                    "retry")
            self._groups.setdefault(key, collections.deque()).append(
                req)
            self._n_requests += 1
            if self._t_first_arrival is None:
                self._t_first_arrival = req.t_arrival
            self._cv.notify_all()
        return reply

    def infer(self, feed: Dict[str, np.ndarray],
              timeout: Optional[float] = 60.0) -> List[np.ndarray]:
        return self.submit(feed).result(timeout)

    # --- bucketing ----------------------------------------------------
    def _declared_shape(self, name):
        v = self._block._find_var_recursive(name)
        return tuple(v.shape) if v is not None and v.shape else None

    def _bucket_seq(self, feed):
        """Pad declared -1 non-batch dims up to the seq-bucket ladder;
        returns (padded feed, group key). @SEQ_LEN companions keep the
        REAL lengths — padded tail positions are masked by sequence
        ops exactly like ordinary pad (the framework's no-LoD
        contract)."""
        out = {}
        key = []
        for name in sorted(feed):
            arr = feed[name]
            want = self._declared_shape(name)
            if want is not None and not name.endswith(SEQ_SUFFIX) \
                    and len(want) == arr.ndim:
                for ax in range(1, arr.ndim):
                    if want[ax] == -1 and self.seq_buckets:
                        arr = _pad_axis(
                            arr, ax,
                            _bucket_for(arr.shape[ax],
                                        self.seq_buckets,
                                        f"sequence dim of {name!r}"))
            out[name] = arr
            key.append((name, arr.shape[1:], str(arr.dtype)))
        return out, tuple(key)

    # --- batcher thread -----------------------------------------------
    def _oldest_group(self):
        best = None
        for key, grp in self._groups.items():
            if grp and (best is None
                        or grp[0].t_arrival
                        < self._groups[best][0].t_arrival):
                best = key
        return best

    def _pick_group(self):
        """Next group to dispatch: the pluggable hook when set (and
        sane), else oldest-request-first. Called under _cv."""
        hook = self._select_group_hook
        if hook is not None and any(self._groups.values()):
            ok, key = _call_scheduling_hook(
                self, hook,
                {k: tuple(g) for k, g in self._groups.items() if g},
                "select_group", "oldest-first")
            if ok and key in self._groups and self._groups[key]:
                return key
        return self._oldest_group()

    def _loop(self):
        while True:
            with self._cv:
                while self._running and self._oldest_group() is None:
                    self._cv.wait()
                if not self._running:
                    return
                key = self._pick_group()
                grp = self._groups[key]
                deadline = grp[0].t_arrival + self.max_wait_ms / 1e3
                while self._running:
                    rows = sum(r.rows for r in grp)
                    now = time.monotonic()
                    if rows >= self.max_batch_size or now >= deadline:
                        break
                    self._cv.wait(timeout=deadline - now)
                    grp = self._groups.get(key)
                    if grp is None or not grp:
                        break  # close() drained us
                if not self._running:
                    return
                grp = self._groups.get(key)
                if grp is None or not grp:
                    continue
                batch, taken = [], 0
                while grp and taken + grp[0].rows <= self.max_batch_size:
                    r = grp.popleft()
                    batch.append(r)
                    taken += r.rows
                if not grp:
                    del self._groups[key]
                if batch:
                    self._inflight += 1  # drain() waits on this
            if batch:
                try:
                    self._dispatch(batch, taken)
                finally:
                    with self._cv:
                        self._inflight -= 1
                        self._cv.notify_all()

    def _dispatch(self, batch: List[_Request], rows: int):
        bucket = _bucket_for(rows, self.batch_buckets, "batch rows")
        traces = [r.trace for r in batch if r.trace is not None]
        exe = self._runner.executor
        c0, d0 = exe.compile_count, exe.disk_load_count
        t_d0 = time.monotonic()
        try:
            feed = {
                name: _pad_rows(
                    np.concatenate([r.feed[name] for r in batch],
                                   axis=0)
                    if len(batch) > 1 else batch[0].feed[name],
                    bucket)
                for name in batch[0].feed}
            with obs_tracing.ambient(traces):
                outs = self._runner.run_batch(feed)
        except BaseException as e:
            for r in batch:
                # spans BEFORE set_exception: fulfilling the future
                # fires the router's done-callback synchronously in
                # this thread, which finishes router-owned traces —
                # a span added after that is dropped by the sealed-
                # trace guard, and errored requests are exactly the
                # incidents whose timelines must stay complete
                if r.trace is not None:
                    r.trace.add_span("server.queue", r.t_arrival, t_d0)
                r.reply.set_exception(e)
                if r.trace is not None and r.trace.owner == "server":
                    r.trace.finish(status="error", error=repr(e))
            return
        done_t = time.monotonic()
        for r in batch:
            if r.trace is not None:
                # queue: arrival -> batch formation; dispatch: the
                # whole padded-batch runner call (its execute/readback
                # children were recorded inside run_batch)
                r.trace.add_span("server.queue", r.t_arrival, t_d0)
                r.trace.add_span("server.dispatch", t_d0, done_t,
                                 rows=rows, bucket=bucket,
                                 cache=_cache_tier(exe, c0, d0))
        # counters BEFORE fulfilling the futures: a caller unblocked
        # by set_result may read stats() immediately and must see the
        # batch that just completed
        with self._cv:
            self._n_batches += 1
            self._n_rows += rows
            self._n_padded_rows += bucket
            off = 0
            for r in batch:
                lat = (done_t - r.t_arrival) * 1e3
                self._latencies.observe(lat)
                self._ttft.observe(lat)
                ntok = self._tokens_in_rows(
                    np.asarray(outs[0])[off:off + r.rows])
                if ntok:
                    self._n_tokens += ntok
                    self._per_token.observe(lat / ntok)
                self._n_done += 1
                off += r.rows
            self._t_last_done = done_t
        off = 0
        for r in batch:
            r.reply.set_result([np.asarray(o)[off:off + r.rows]
                                for o in outs])
            off += r.rows
            if r.trace is not None and r.trace.owner == "server":
                r.trace.finish()

    def _tokens_in_rows(self, rows) -> Optional[int]:
        """Generated-token count for the primary output rows of one
        request, or None when the served program is not generative
        (plain inference: per-token latency is meaningless).
        GenerationServer overrides with the EOS-aware count."""
        return None

    # --- AOT warmup ---------------------------------------------------
    def _warmup_feed_specs(self):
        """Synthetic feed shapes for every bucket combination, derived
        from the program's declared var shapes: batch -1 -> each batch
        bucket, other -1 dims -> each seq bucket (all seq-bucketed
        inputs move together per combination — mixed-per-input seq
        buckets would square the executable count for no caller)."""
        shapes = {}
        needs_seq = False
        for name in self._feed_names:
            want = self._declared_shape(name)
            if want is None:
                raise ValueError(
                    f"aot_warmup: feed {name!r} has no declared shape "
                    f"in the program; warm manually via infer()")
            if any(d == -1 for d in want[1:]):
                needs_seq = True
            shapes[name] = want
        if needs_seq and not self.seq_buckets:
            raise ValueError(
                "aot_warmup: the program declares -1 sequence dims; "
                "pass seq_buckets=(...) so warmup knows the ladder")
        seq_ladder = self.seq_buckets if needs_seq else [None]
        for seq in seq_ladder:
            for b in self.batch_buckets:
                feed = {}
                for name, want in shapes.items():
                    shp = [b] + [seq if d == -1 else d
                                 for d in want[1:]]
                    v = self._block._find_var_recursive(name)
                    dt = to_np_dtype(v.dtype) if v is not None and \
                        v.dtype is not None else np.float32
                    if name.endswith(SEQ_SUFFIX):
                        base = name[:-len(SEQ_SUFFIX)]
                        bw = shapes.get(base)
                        full = seq if (bw is not None
                                       and any(d == -1
                                               for d in bw[1:])) \
                            else (bw[1] if bw and len(bw) > 1
                                  else 1)
                        feed[name] = np.full((b,), full, dtype=dt)
                    else:
                        feed[name] = np.zeros(shp, dtype=dt)
                yield feed

    def aot_warmup(self) -> int:
        """Pre-compile every bucket before traffic: one synthetic
        batch per (seq bucket x batch bucket) combination runs
        directly through the runner at EXACTLY the padded shape the
        batcher will dispatch, so this seeds the Executor's executable
        cache under exactly the keys real traffic will hit (cache
        seeding, not a second compiler path). Probes bypass the
        request queue: queued probes of one ladder would coalesce
        into a single micro-batch and only warm the largest bucket.
        Returns the number of fresh compiles it caused."""
        exe = self._runner.executor
        before = exe.compile_count
        evict_before = exe.cache_evict_count
        for feed in self._warmup_feed_specs():
            self._runner.run_batch(feed)
        if exe.cache_evict_count > evict_before:
            import warnings

            warnings.warn(
                f"aot_warmup: the bucket ladder overflowed the "
                f"executor's bounded executable cache "
                f"({exe.cache_evict_count - evict_before} "
                f"eviction(s)) — early buckets will recompile "
                f"INSIDE the traffic window, the exact cost warmup "
                f"exists to avoid. Raise "
                f"FLAGS_executor_cache_capacity above the ladder "
                f"size.")
        self._warmed_compiles = exe.compile_count - before
        return self._warmed_compiles

    # --- observability ------------------------------------------------
    def stats(self, reset: bool = False) -> dict:
        """Atomic snapshot of the serving counters. With reset=True
        the WINDOW counters (requests/batches/latency histograms/...)
        are zeroed under the same lock the batcher thread updates
        them with, so an aggregator polling stats(reset=True)
        computes per-window rates without racing in-flight updates.
        `uptime_s` is monotonic since server start (never reset);
        `window_s` is the span the returned counters cover. Executor
        counters (compile/cache) are cumulative by design — delta
        them across snapshots. NOTE (r12 semantics change): p50/p99
        come from fixed-bucket histograms that accumulate SINCE THE
        LAST RESET, not from a recent-N-samples ring — a monitor that
        wants the current regime (not lifetime) must poll with
        reset=True windows; in exchange percentile memory is O(1) for
        a million-request run."""
        exe = self._runner.executor
        with self._cv:
            now = time.monotonic()
            depth = sum(len(g) for g in self._groups.values())
            occ = (self._n_rows / self._n_padded_rows
                   if self._n_padded_rows else None)
            done_span = (
                self._t_last_done - self._t_first_arrival
                if self._t_last_done is not None
                and self._t_first_arrival is not None else None)
            snap = {
                "requests": self._n_requests,
                "completed": self._n_done,
                "batches": self._n_batches,
                "rows": self._n_rows,
                "padded_rows": self._n_padded_rows,
                "batch_occupancy": round(occ, 4) if occ else None,
                "queue_depth": depth,
                "uptime_s": round(now - self._t_start, 3),
                "window_s": round(now - self._t_window, 3),
                "compile_count": exe.compile_count,
                "cache_hit_count": exe.cache_hit_count,
                # warm-start observability: executables rehydrated
                # from the on-disk compile cache (zero in-process
                # compiles) and in-memory LRU evictions
                "disk_load_count": exe.disk_load_count,
                "cache_evict_count": exe.cache_evict_count,
                "warmed_compiles": self._warmed_compiles,
                "latency_ms": _pct_dict(self._latencies),
                "ttft_ms": _pct_dict(self._ttft),
                "per_token_ms": _pct_dict(self._per_token),
                "tokens": self._n_tokens,
                "retired_per_s": (
                    round(self._n_done / done_span, 1)
                    if done_span else None),
            }
            if reset:
                self._n_requests = self._n_batches = 0
                self._n_rows = self._n_padded_rows = 0
                self._n_done = self._n_tokens = 0
                self._latencies.clear()
                self._ttft.clear()
                self._per_token.clear()
                self._t_first_arrival = None
                self._t_last_done = None
                self._t_window = now
            return snap

    def _metrics_samples(self):
        """Pull-provider for observability.metrics.expose(): the same
        counters stats() reports, as Prometheus samples."""
        lab = {"server": self._obs_id}
        with self._cv:
            occ = (self._n_rows / self._n_padded_rows
                   if self._n_padded_rows else 0.0)
            return [
                ("paddle_tpu_server_requests_total", lab,
                 self._n_requests),
                ("paddle_tpu_server_completed_total", lab,
                 self._n_done),
                ("paddle_tpu_server_batches_total", lab,
                 self._n_batches),
                ("paddle_tpu_server_queue_depth", lab,
                 sum(len(g) for g in self._groups.values())),
                ("paddle_tpu_server_batch_occupancy", lab, occ),
                ("paddle_tpu_server_tokens_total", lab,
                 self._n_tokens),
                ("paddle_tpu_request_latency_ms", lab,
                 self._latencies),
                ("paddle_tpu_request_ttft_ms", lab, self._ttft),
                ("paddle_tpu_per_token_ms", lab, self._per_token),
            ]


class GenerationServer(InferenceServer):
    """Dynamic-batching server for autoregressive generation
    (reference tests/unittests/dist_transformer.py:1498 fast_decode
    is the decode loop being served).

    Wraps the KV-cached incremental decode program
    (models/decode_engine.py, re-exported by models/transformer.py):
    the whole T-token greedy loop is ONE
    While-loop executable, so a served generation costs one dispatch +
    one readback regardless of output length, and concurrent requests
    share it through the same bucket ladder as plain inference.

    ``generate(src_ids)`` accepts one source row ([T] or [1, T]) or a
    [B, T] block, and returns the decode buffer rows for the REAL
    rows only. With ``end_id`` set, positions strictly after the first
    emitted end_id are rewritten to the fixed-size -1 sentinel (the
    detection-op padded-output convention), so callers can split
    variable-length results out of the static [maxT] buffer.

    PASS end_id whenever the program has one: the decode loop's
    all-rows-finished early exit stops writing once every CO-BATCHED
    row has finished, so without sentinel normalization the raw tail
    past a row's EOS (frozen end_id up to the batch-wide exit step,
    zero init after) depends on which requests the batcher happened
    to coalesce — end_id=None returns that raw, co-tenant-dependent
    tail verbatim.
    """

    def __init__(self, program, out_var, feed_name: str = "src_ids",
                 executor: Optional[Executor] = None, scope=None,
                 end_id: Optional[int] = None, **kwargs):
        out_name = getattr(out_var, "name", out_var)
        runner = ProgramRunner(program, [feed_name], [out_name],
                                executor=executor, scope=scope)
        self._end_id = end_id
        super().__init__(runner, **kwargs)

    def generate(self, src_ids, timeout: Optional[float] = 120.0):
        arr = np.asarray(src_ids)
        one_row = arr.ndim == 1
        if one_row:
            arr = arr[None]
        toks = self.infer({self._feed_names[0]: arr},
                          timeout=timeout)[0]
        toks = apply_eos_sentinel(toks, self._end_id)
        return toks[0] if one_row else toks

    def _tokens_in_rows(self, rows) -> Optional[int]:
        """Generated tokens per request: positions up to and including
        the first end_id (the GO token at position 0 excluded), full
        buffer length when no EOS fired."""
        return int(count_generated_tokens(rows, self._end_id).sum())

    def stats(self, reset: bool = False) -> dict:
        st = super().stats(reset=reset)
        # the whole-loop server's "slots" are its padded batch rows
        st["slots"] = self.max_batch_size
        st["slot_occupancy"] = st["batch_occupancy"]
        return st


class _GenRequest:
    __slots__ = ("src", "reply", "t_arrival", "t_first", "t_admit",
                 "trace", "seed", "session", "harvest", "radix",
                 "stream", "stream_cb", "deadline", "cancel_reason",
                 "finalized", "emitted", "n_streamed")

    def __init__(self, src, reply, trace=None, seed=0, session=None,
                 harvest=True, stream=None, stream_cb=None,
                 deadline=None):
        self.src = src
        self.reply = reply
        self.t_arrival = time.monotonic()
        self.t_first = None  # set when its first token lands
        self.t_admit = None  # set when a slot admits it
        self.trace = trace   # observability (see _Request.trace)
        # per-request noise seed (sampled/speculative bundles): folded
        # with each POSITION into the emission keys, so a request
        # samples the same tokens whatever lane/order/burst served it
        self.seed = seed
        # chat-session id (paged radix reuse); fan-out branches of a
        # best-of-n submit carry harvest=False — probe generations
        # never extend the session's retained history
        self.session = session
        self.harvest = harvest
        # admission-time radix plan (hist tokens, resume step, history
        # length), written by the paged scheduler under its lock
        self.radix = None
        # r20 front door: per-token delivery + teardown. `stream` is
        # the StreamingReply handle (None = whole-response only),
        # `stream_cb` the callback form; `emitted` is the highest
        # tok_buf POSITION already delivered (0 = only the GO token
        # exists — never streamed) and survives preemption, so the
        # byte-exact re-decode resumes delivery without duplicates;
        # `n_streamed` is the monotone sequence-number base handed to
        # stream_cb. `deadline` is an absolute time.monotonic()
        # instant; `cancel_reason` ("cancelled" | "deadline") is the
        # one-way teardown mark, and `finalized` is the scheduler's
        # under-lock commit that the reply's outcome is decided (the
        # cancel/retire race arbiter).
        self.stream = stream
        self.stream_cb = stream_cb
        self.deadline = deadline
        self.cancel_reason = None
        self.finalized = False
        self.emitted = 0
        self.n_streamed = 0


class _PackedServe:
    """A serve program's prepared handle, fetched for its bundle's
    packed row alone (decode_engine.ServeRow): `run` hands the row back
    cut into the list a fetch of the row's names would have given
    (token rows first), as views of the one host buffer; everything
    else is the prepared handle's."""

    def __init__(self, prepared, row):
        self._prepared, self._row = prepared, row

    def run(self, feed, return_numpy: bool = True):
        return self._row.cut(
            self._prepared.run(feed, return_numpy=return_numpy)[0])

    def __getattr__(self, name):
        return getattr(self._prepared, name)


class ContinuousGenerationServer:
    """Continuous-batching generation over a fixed slot pool
    (iteration-level scheduling: Orca, Yu et al. OSDI'22; slot-based
    KV management: vLLM, Kwon et al. SOSP'23 — PAPERS.md. Reference
    decode loop: tests/unittests/dist_transformer.py:1498
    fast_decode).

    Wraps a models/transformer.build_decode_step_program bundle: the
    KV cache slots, token buffers, per-slot step counters, and
    active-lane masks live as persistable scope state ON DEVICE; the
    host loops over fused scheduler cycles, each ONE prepared
    dispatch of a ``bundle.serves[A]`` program:

      admit   — FIFO: up to A oldest queued prompts fill free slots
                (batched encoder + cross-K/V one-hot matmul scatter,
                lane reset; padded rows land on the dustbin lane), A
                drawn from the power-of-two admission-bucket ladder;
      step    — the same dispatch then advances every live lane up to
                ``steps_per_tick`` tokens in a device-side While with
                an all-lanes-idle early exit, so the ~0.5-1 ms host
                dispatch + readback amortizes over A admissions and a
                whole burst of tokens;
      retire  — lanes whose active flag dropped (EOS emitted, or
                buffer exhausted) are read back, sentinel-normalized
                (apply_eos_sentinel) and their futures fulfilled;
                the slot frees for the next arrival IMMEDIATELY.

    Short requests therefore never wait on long ones (the whole-loop
    GenerationServer's head-of-line cost), and arrivals never wait for
    a draining batch. Executable count is fixed: ONE serve
    specialization per admission bucket of the (slot_count, seq
    bucket) config, resolved through Executor.prepare (the serving
    fast path) and disk-cacheable via Program.fingerprint();
    steady-state traffic compiles NOTHING (asserted in tests).

    Greedy parity: a lane's token row equals the whole-loop decode of
    the same prompt after apply_eos_sentinel, independent of admission
    order or slot assignment — the step program's math IS the
    whole-loop body (models/decode_engine.cached_decoder_step) and
    every op is row-wise, so co-resident lanes cannot interact.
    """

    def __init__(self, bundle, executor=None, scope=None,
                 steps_per_tick: Optional[int] = None,
                 drain_steps: Optional[int] = None,
                 exit_on_retire: bool = False,
                 admit_select=None,
                 start: bool = True,
                 mesh_devices=None,
                 spec_controller=None):
        bundle_cache = getattr(bundle, "cache", None)
        if (type(self) is ContinuousGenerationServer
                and bundle_cache is not None
                and bundle_cache.layout != "dense"):
            # the mirror of the paged subclass's dense-bundle check:
            # this scheduler never publishes block tables / active
            # masks, so serving a paged bundle here would fail every
            # admission with an opaque KeyError at best
            raise ValueError(
                f"ContinuousGenerationServer serves DENSE bundles; "
                f"this bundle's KV layout is "
                f"{bundle_cache.layout!r} — use "
                f"PagedContinuousGenerationServer")
        self.bundle = bundle
        self.executor = executor or Executor(TPUPlace(0))
        self.scope = scope or global_scope()
        # burst caps. steps_per_tick bounds the queue-pressure burst:
        # a retired lane's slot refills only at the next cycle, so the
        # cap trades slot-refill latency (up to K-1 idle steps for one
        # slot) against per-dispatch overhead amortization — K ~ 8 is
        # right when host dispatch costs a few device iterations (this
        # CPU host); on hardware where an iteration dwarfs dispatch,
        # pass exit_on_retire=True to hand control back the moment a
        # lane dies (the serve programs' min_active feed) instead.
        # drain_steps bounds the empty-queue drain burst (the While
        # exits by itself when the pool goes idle); a request arriving
        # mid-drain waits at most one drain dispatch.
        self.steps_per_tick = int(steps_per_tick) \
            if steps_per_tick is not None else 8
        self.drain_steps = int(drain_steps) if drain_steps is not None \
            else bundle.max_out_len
        self.exit_on_retire = bool(exit_on_retire)
        self.n_slots = bundle.n_slots
        self._end_id = bundle.end_id
        if mesh_devices is not None \
                and getattr(bundle, "sharding_plan", None) is None:
            raise ValueError(
                "mesh_devices given but the bundle carries no "
                "sharding plan — build it with ShardingConfig(tp>1)")
        bundle.init_slot_state(self.scope)
        # tensor-parallel bundles: bind the sharding plan to its
        # device slice (``mesh_devices``; default the first tp
        # devices) and place every persistable BEFORE the prepared
        # handles bind below — params land replicated-on-mesh once,
        # KV pools land head-sharded (per-device bytes ~1/tp), and
        # the serve executables compile directly at the placed
        # layout (models/decode_engine.place_sharded_bundle)
        if getattr(bundle, "sharding_plan", None) is not None:
            if getattr(bundle, "prefill_plan", None) is not None:
                # disaggregated bundle (apply_phase_sharding): TWO
                # plans over two scopes — bound by
                # runtime.placement.place_disaggregated_bundle BEFORE
                # server construction; re-placing here would fold the
                # chunk programs back under the decode plan
                if mesh_devices is not None:
                    raise ValueError(
                        "mesh_devices does not apply to a "
                        "disaggregated bundle — bind both slices "
                        "via place_disaggregated_bundle")
                if bundle.sharding_plan._mesh is None:
                    raise ValueError(
                        "disaggregated bundle is unplaced — run "
                        "runtime.placement.place_disaggregated_"
                        "bundle(bundle, decode_scope, prefill_scope) "
                        "before constructing the server")
            else:
                from ..models.decode_engine import \
                    place_sharded_bundle

                place_sharded_bundle(bundle, self.scope,
                                     devices=mesh_devices)

        # sampled/speculative bundle knobs (absent on pre-r14 plain
        # bundles): per-request seeds in the admission feeds, tokens
        # per device tick (> 1 under draft-and-verify — the paged
        # scheduler sizes block coverage by it), and the device-side
        # spec counters the stats surface deltas per dispatch
        self._needs_seeds = bool(getattr(bundle, "needs_seeds",
                                         False))
        self._spec_k = int(getattr(bundle, "spec_k", 0))
        self._toks_per_tick = int(getattr(bundle, "tokens_per_tick",
                                          1))
        self._spec_names = [bundle.state[c] for c in SPEC_COUNTERS] \
            if self._spec_k > 0 else []
        self._spec_tot = dict.fromkeys(
            ("proposed", "accepted", "emitted", "draft_steps",
             "target_steps"), 0)
        # adaptive speculation (r19): per-lane acceptance counters
        # join the fetch list, and a host-side controller re-buckets
        # the pool across the bundle's pre-built k-ladder serve
        # variants — pure program selection, zero steady-state
        # compiles (inference/spec_controller.py)
        self._lane_names = [
            bundle.state[c] for c in SPEC_LANE_COUNTERS
            if c in getattr(bundle, "state", {})] \
            if self._spec_k > 0 else []
        self._lane_tot = [None] * len(self._lane_names)
        self._spec_k_options = tuple(
            getattr(bundle, "spec_k_options", ()) or ())
        if spec_controller is None and self._spec_k_options:
            from .spec_controller import SpecController

            draft = getattr(bundle, "draft", None)
            spec_controller = SpecController(
                self._spec_k_options, default_k=self._spec_k,
                draft_cost_ratio=(
                    0.0 if draft is not None
                    and getattr(draft, "kind", "model") == "ngram"
                    else 0.25))
        self._spec_ctl = spec_controller or None
        if self._spec_ctl is not None and not self._spec_k_options:
            raise ValueError(
                "spec_controller given but the bundle has no k "
                "ladder — build it with DraftConfig(k_options=...)")
        # per-k-bucket windows (controller observability): each fused
        # dispatch runs the WHOLE pool at one rung, so its spec-
        # counter deltas attribute cleanly to that rung
        self._per_k_tot: Dict[int, dict] = {
            k: dict.fromkeys(
                ("dispatches", "proposed", "accepted", "emitted"), 0)
            for k in (self._spec_k_options or ())}
        self._per_k_base = {k: dict(v)
                            for k, v in self._per_k_tot.items()}
        self._acc_hist_k = {
            k: Histogram(
                f"paddle_tpu_spec_acceptance_rate_k{k}",
                buckets=tuple(round(0.1 * i, 1)
                              for i in range(1, 11)))
            for k in self._spec_k_options if k > 0}
        # stats(reset=True) window baseline: the DEVICE counters are
        # cumulative since init_slot_state, so the window view is
        # tot - base — keeping every number in the "speculative" dict
        # on the same window the histograms cover
        self._spec_base = dict(self._spec_tot)
        # acceptance-rate histogram: fraction of offered draft tokens
        # accepted per dispatch (fixed 0.1-wide buckets)
        self._acc_hist = Histogram(
            "paddle_tpu_spec_acceptance_rate",
            buckets=tuple(round(0.1 * i, 1) for i in range(1, 11)))
        # device-side flight data (observability/devtel.py): the
        # bundle's telemetry counters join the dispatch fetch list and
        # are deltaed per burst — ticks, occupancy integral, exit
        # reason, admission tiers. Inactive (empty) for hand-built
        # bundles without devtel state.
        self._devtel = obs_devtel.DeviceTelemetry(bundle)
        # per-serve-key cost-model snapshots (lazy: the first
        # metrics-on dispatch of a key resolves them, cached forever)
        self._cost_snaps: Dict[object, dict] = {}

        # bind the prepared handles up front (= AOT warmup: all
        # compiles happen HERE, none in the traffic window): one fused
        # serve program per admission flavor x bucket (0 = tick-only).
        # Every one is fetched for the bundle's packed row alone
        # (decode_engine.ServeRow) and hands it back cut into `outs`
        # (_PackedServe): token rows, step, active, finished, the
        # speculative counters, the telemetry counters, then what the
        # bundle adds (a decoder-only bundle's expert counters)
        before = self.executor.compile_count
        self._row = bundle.serve_row
        st = bundle.state
        read = [st["tok_buf"], st["step"], st["active"],
                st["finished"]] + self._spec_names \
            + self._lane_names + self._devtel.fetch_names
        if list(self._row.names[:len(read)]) != read:
            raise ValueError(
                f"the bundle's serve row {self._row.names} does not "
                f"start with what this scheduler reads: {read}")
        self._fetches = [self._row.name]
        self._serves = {}
        for key, prog in sorted(bundle.serves.items(),
                                key=lambda kv: str(kv[0])):
            if self._skip_serve_key(key):
                continue
            self._serves[key] = _PackedServe(self.executor.prepare(
                prog, feed=bundle.serve_feed_spec(key),
                fetch_list=self._fetches, scope=self.scope), self._row)
        self._admit_buckets = sorted(
            {k for k in self._serves if isinstance(k, int) and k > 0}
            | {k[1] for k in self._serves if isinstance(k, tuple)
               and k[0] not in ("chunked", "k")})
        # radix capability: paged non-speculative bundles build
        # ("radix", A) serve programs (teacher-forced resume over a
        # shared block prefix) — the gate for session_id / n_best
        self._radix_ok = any(isinstance(k, tuple) and k[0] == "radix"
                             for k in self._serves)
        self._warmed_compiles = self.executor.compile_count - before
        # lanes the scheduler parked because the shared KV pool could
        # not cover their next burst (paged layout only; always empty
        # on the dense server) — the retire sweep must skip them
        self._paused: set = set()

        self._cv = threading.Condition()
        self._queue: "collections.deque[_GenRequest]" = \
            collections.deque()
        self._lanes: List[Optional[_GenRequest]] = \
            [None] * self.n_slots
        self._running = False
        self._closed = False    # close() called: reject everything
        self._accepting = True  # quiesce() flips
        self._busy = False      # a fused cycle is mid-dispatch
        self._thread: Optional[threading.Thread] = None
        # pluggable admission selection: callable(queue) -> index of
        # the request to admit next, where `queue` is a tuple of
        # pending _GenRequest (each with .t_arrival/.src). Called
        # under the server lock; bad values / exceptions fall back to
        # FIFO (index 0).
        self._admit_select = admit_select
        self._hook_warned = False

        # observability (under _cv)
        self._n_requests = 0
        self._n_done = 0
        self._n_tokens = 0
        self._n_ticks = 0
        self._occ_sum = 0.0
        # r20 front-door teardown counters: client cancels vs
        # deadline expiries (queued sheds + live-lane teardowns both)
        self._n_cancelled = 0
        self._n_deadline = 0
        # fixed-bucket histograms — same O(1)-memory contract as
        # InferenceServer (observability/metrics)
        self._latencies = Histogram("paddle_tpu_request_latency_ms")
        self._ttft = Histogram("paddle_tpu_request_ttft_ms")
        self._per_token = Histogram("paddle_tpu_per_token_ms")
        # arrival to admission, observed at every admission
        self._queue_wait = Histogram("paddle_tpu_request_queue_wait_ms")
        self._t_first_arrival = None
        self._t_last_done = None
        self._t_start = time.monotonic()
        self._t_window = self._t_start
        self._obs_id = _obs_server_id(self)
        # one record a scheduler cycle, at every flag level
        self._cycles = obs_tracing.CycleRing(
            owner=self._obs_id, phases=_CYCLE_PHASES)
        obs_metrics.register_provider(self)

        if start:
            self.start()

    # --- lifecycle ----------------------------------------------------
    def start(self):
        with self._cv:
            if self._running:
                return
            self._running = True
            # an explicit restart after close() re-opens the server
            # (pre-lifecycle behavior: submit gated on _running only)
            self._closed = False
            self._accepting = True
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def quiesce(self):
        """Stop ACCEPTING (submit raises ServerQuiesced); the
        scheduler keeps running queued prompts and live lanes to
        completion — the hot-swap half of close(). Idempotent."""
        with self._cv:
            self._accepting = False

    def drain(self, timeout: Optional[float] = 60.0) -> bool:
        """Block until the queue is empty, every lane has retired, and
        no fused cycle is mid-dispatch. True on drained, False on
        timeout. Pair with quiesce() so arrivals cannot refill the
        pool behind the wait."""
        deadline = None if timeout is None \
            else time.monotonic() + timeout
        with self._cv:
            def dirty():
                return (self._queue or self._busy
                        or any(l is not None for l in self._lanes)
                        or self._has_background_work_locked()
                        or self._has_pending_external_locked())

            while self._running and dirty():
                if deadline is None:
                    self._cv.wait()
                    continue
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(left)
            return not dirty()

    def close(self, timeout: float = 5.0):
        with self._cv:
            self._running = False
            self._closed = True
            self._accepting = False
            pending = list(self._queue)
            self._queue.clear()
            pending += [r for r in self._lanes if r is not None]
            self._lanes = [None] * self.n_slots
            bg = self._background_abort_locked()
            if bg is not None:
                pending.append(bg)
            for r in pending:
                r.finalized = True
            self._flush_requests_locked(pending)
            self._cv.notify_all()
        for r in pending:
            exc = ServerClosed("ContinuousGenerationServer closed")
            self._finish_stream(r, "error", exc)
            try:
                r.reply.set_exception(exc)
            except futures.InvalidStateError:
                pass
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # --- request path -------------------------------------------------
    def submit(self, src_ids, seed=None, session_id=None,
               extend_tokens=None, n_best=1, stream=False,
               stream_cb=None, deadline_ms=None):
        """Enqueue one prompt row. ``seed`` keys the request's
        emission noise on sampled/speculative bundles (ignored by
        plain greedy ones); None derives it from the prompt CONTENT
        (crc32), so identical prompts sample identical streams and
        the served tokens are invariant to admission order — the
        bit-repro contract tests pin.

        The r20 front door adds:

        * ``stream=True`` — returns a ``StreamingReply`` instead of a
          future: tokens are delivered per BURST from the host
          readback the scheduler already performs (monotone sequence
          numbers, EOS/finish markers, byte-parity with the
          whole-response row — see StreamingReply). TTFT becomes
          first-burst latency. On speculative bundles each burst
          delivers the accepted runs of its ticks.
        * ``stream_cb`` — callback form: ``cb(tokens, first_seq,
          finish_reason)`` is invoked from the scheduler thread
          (outside the scheduler lock) with a fresh int64 chunk and
          the sequence number of its first token; the final call
          carries an empty chunk and the finish reason. The normal
          whole-response future is still returned.
        * ``deadline_ms`` — a completion SLO relative to now: if the
          request is still queued or still decoding once it expires,
          it is torn down at the next planning/burst boundary (every
          block/prompt-entry/radix hold released through the PTA201
          ``cancel`` exit) and the reply fails with the typed,
          non-retryable ``DeadlineExceeded``.

        Paged bundles additionally unlock (raising elsewhere):

        * ``session_id`` — a multi-turn CHAT session: the first turn
          decodes normally; when it retires, the full-block prefix of
          its decoded tokens is adopted into the server's radix tree
          and the history retained. A RESUBMIT with the same
          session_id (same prompt — the bidirectional encoder pins
          cross-KV to the whole prompt) admits through the
          encoder-free radix tier: the longest shared block prefix is
          mapped read-only, only the divergent tail is teacher-force
          re-prefilled, and decode resumes where the history ends —
          never a re-prefill, never a recompute of shared KV.
        * ``extend_tokens`` — appended to the session's retained
          history before the turn runs (the "user turn" injected into
          the decoder stream); requires a session with at least one
          retired turn. Sessions are sequential: submit the next turn
          after the previous one resolved.
        * ``n_best`` — fan-out: n requests sharing the prompt entry
          (and, for a session, the radix block chain) with seeds
          ``seed..seed+n-1``; returns a LIST of replies. Branches
          never extend the session history. Distinct generations need
          a sampled bundle — greedy branches are identical.
        """
        with self._submit_span():
            return self._enqueue(src_ids, seed, session_id,
                                 extend_tokens, n_best, stream,
                                 stream_cb, deadline_ms)

    def _submit_span(self):
        """The `slotpool.submit` span of one `submit`, on the caller's
        thread. A callback that submits from the scheduler's thread
        does so inside the open cycle, whose record counts it."""
        rec = obs_tracing.current_cycle()
        if rec is not None:
            rec.attrs["submitted"] = rec.attrs.get("submitted", 0) + 1
        return obs_tracing.span("slotpool.submit")

    def _enqueue(self, src_ids, seed, session_id, extend_tokens, n_best,
                 stream, stream_cb, deadline_ms):
        """submit()'s body, on the caller's thread: validation, the
        request objects, the push under the scheduler lock."""
        arr = np.asarray(src_ids)
        if arr.ndim == 1:
            arr = arr[None]
        if arr.shape != (1, self.bundle.seq_len):
            raise ValueError(
                f"continuous generation takes one prompt row of "
                f"exactly seq_len={self.bundle.seq_len} tokens; got "
                f"shape {tuple(np.asarray(src_ids).shape)}")
        arr = arr.astype(np.int64)
        n_best = int(n_best)
        if n_best < 1:
            raise ValueError(f"n_best must be >= 1, got {n_best}")
        if (session_id is not None or n_best > 1) \
                and not self._radix_ok:
            raise ValueError(
                "session_id/n_best need the radix serve tier — a "
                "PAGED, non-speculative bundle served by "
                "PagedContinuousGenerationServer")
        if extend_tokens is not None and session_id is None:
            raise ValueError(
                "extend_tokens extends an existing chat session; "
                "pass session_id")
        if (stream or stream_cb is not None) and n_best > 1:
            raise ValueError(
                "streaming delivers ONE ordered token sequence; "
                "n_best fan-out returns whole-response futures — "
                "submit the branches separately to stream them")
        if deadline_ms is not None and deadline_ms <= 0:
            raise ValueError(
                f"deadline_ms must be > 0, got {deadline_ms}")
        deadline = (time.monotonic() + float(deadline_ms) / 1e3
                    if deadline_ms is not None else None)
        if seed is None:
            import zlib

            seed = zlib.crc32(arr.tobytes())
        reqs = []
        for i in range(n_best):
            trace = obs_tracing.current_request_trace() \
                if i == 0 else None
            if trace is None:
                trace = obs_tracing.start_request(owner="server",
                                                  server=self._obs_id)
            reply = GenerationReply()
            sreply = StreamingReply(self) if stream else None
            req = _GenRequest(arr, reply, trace=trace,
                              seed=int(seed) + i,
                              session=session_id,
                              harvest=(n_best == 1),
                              stream=sreply, stream_cb=stream_cb,
                              deadline=deadline)
            reply._gen_server = self
            reply._gen_req = req
            if sreply is not None:
                sreply._req = req
                sreply._future = reply
            reqs.append(req)
        with self._cv:
            if self._closed:
                raise ServerClosed(
                    "ContinuousGenerationServer is closed")
            if not self._accepting:
                raise ServerQuiesced(
                    "ContinuousGenerationServer is quiesced "
                    "(draining for retire/hot swap); re-resolve the "
                    "model and retry")
            if session_id is not None:
                self._session_submit_locked(session_id, arr,
                                            extend_tokens)
            for req in reqs:
                self._queue.append(req)
            self._n_requests += len(reqs)
            if self._t_first_arrival is None:
                self._t_first_arrival = reqs[0].t_arrival
            self._cv.notify_all()
        if stream:
            return reqs[0].stream
        return reqs[0].reply if n_best == 1 \
            else [r.reply for r in reqs]

    def _session_submit_locked(self, session_id, arr, extend_tokens):
        raise ValueError(  # unreachable behind the _radix_ok gate
            "chat sessions need PagedContinuousGenerationServer")

    def generate(self, src_ids, timeout: Optional[float] = 120.0,
                 seed=None):
        """One prompt row in, one sentinel-normalized [max_out_len]
        token row out (same contract as GenerationServer.generate for
        a single row)."""
        return self.submit(src_ids, seed=seed).result(timeout)

    def expected_service_ms(self, n_tokens=None) -> Optional[float]:
        """Costmodel-backed completion-latency estimate for ONE
        request decoding ``n_tokens`` (default: the bundle's
        max_out_len): the expected wall of one TICK of the key-0
        serve While (observability/costmodel.py throughput fit over
        this server's own dispatches — expected_ms costs the While
        BODY once, and the achieved-rate samples it divides by are
        tick-flops x ticks over the burst's wall, so per-burst host
        overhead is already amortized INTO the per-tick figure) times
        the ticks the request needs. Do not divide by steps_per_tick
        on top: that re-counts the burst grouping the calibration
        already folded in and runs the estimate steps_per_tick-x low
        — low enough that a Router deadline stated as a multiple of
        this estimate never sheds (bench.py frontdoor caught it).
        None until the costmodel is calibrated (an uncalibrated
        estimator must not shed anyone). Lanes decode in lockstep, so
        co-residency does not stretch a request's own burst count —
        queue wait is the CALLER's (Router's) term. Reference
        counterpart: none — the reference has no service-time model
        (its deploy apps time requests after the fact)."""
        snap = obs_costmodel.lookup(self.bundle.serves[0]) or {}
        per_tick = obs_costmodel.expected_ms(snap.get("flops"))
        if per_tick is None:
            return None
        toks = self.bundle.max_out_len if n_tokens is None \
            else max(1, int(n_tokens))
        ticks = math.ceil(toks / max(1, self._toks_per_tick))
        return per_tick * ticks

    # --- cancellation / deadline teardown (r20 front door) ------------
    def _cancel_request(self, req, reason: str) -> bool:
        """Client-thread half of cancel()/deadline teardown: mark the
        request under the scheduler lock and wake the loop. All state
        release happens ON the scheduler thread — queued requests are
        shed at the next planning pass (_shed_cancelled_locked), live
        lanes at the next burst boundary (_cancel_lane_locked) — so
        every pool mutation keeps the existing single-writer
        discipline. False = the outcome was already decided."""
        with self._cv:
            if req is None or req.finalized:
                return False
            if req.cancel_reason is None:
                req.cancel_reason = reason
            self._cv.notify_all()
        return True

    def _expired_locked(self, req, now: float) -> Optional[str]:
        """The request's teardown reason, minting "deadline" on
        expiry. Called under _cv."""
        reason = req.cancel_reason
        if reason is None and req.deadline is not None \
                and now > req.deadline:
            reason = req.cancel_reason = "deadline"
        return reason

    def _count_cancel_locked(self, reason: str):
        if reason == "deadline":
            self._n_deadline += 1
        else:
            self._n_cancelled += 1

    def _drop_queued_locked(self, req):
        """Hook: a QUEUED request is being shed (cancel/deadline) —
        drop per-request bookkeeping it may hold without a lane
        (paged: a disagg handoff entry ref). Called under _cv."""

    def _shed_cancelled_locked(self, now: float):
        """Remove cancelled / deadline-expired requests from the
        queue before admission planning — they must never occupy a
        slot. The PTA201 ``cancel`` release site for queue-held refs
        (via the _drop_queued_locked hook; the paged override extends
        this to the in-flight chunked-prefill job). Returns the
        (req, reason) list the caller finalizes OUTSIDE the lock."""
        out = []
        if not self._queue:
            return out
        kept = collections.deque()
        for req in self._queue:
            reason = self._expired_locked(req, now)
            if reason is None:
                kept.append(req)
            else:
                req.finalized = True
                self._drop_queued_locked(req)
                self._count_cancel_locked(reason)
                out.append((req, reason))
        self._queue = kept
        return out

    def _cancel_lane_locked(self, slot, req, reason: str):
        """Burst-boundary teardown of one LIVE lane whose request
        was cancelled or ran past its deadline: the PTA201 ``cancel``
        release site for every lane-held tag — routes through
        _release_lane, so the paged _free_lane_locked decrefs KV
        blocks (block_table / cow_dst), radix holds (cow_src) and
        the lane's prompt-entry ref exactly as retirement does.
        Harvest is skipped: a torn-down turn must not extend session
        history. Called under _cv."""
        req.harvest = False
        req.finalized = True
        self._release_lane(slot, req)
        self._lanes[slot] = None
        self._paused.discard(slot)
        self._count_cancel_locked(reason)

    def _deliver_stream(self, req, first_seq: int, chunk):
        """Push one burst's fresh tokens to the request's streaming
        surfaces. Scheduler thread, OUTSIDE the lock (stream_cb is
        user code and StreamingReply waiters run done-callbacks)."""
        if req.stream is not None:
            req.stream._push(first_seq, chunk)
        if req.stream_cb is not None:
            try:
                req.stream_cb(chunk, first_seq, None)
            except Exception as e:
                if not self._hook_warned:
                    self._hook_warned = True
                    import warnings

                    warnings.warn(
                        f"stream_cb raised ({type(e).__name__}: {e});"
                        f" further failures are silent")

    def _finish_stream(self, req, reason: str, exc=None):
        """Terminal stream event (scheduler thread, outside the
        lock): ends StreamingReply iteration and makes the final
        stream_cb call (empty chunk + finish reason). getattr, not
        attribute access: scheduler white-box tests (and any
        admit_select-style hook consumer) drive this path with
        minimal request fakes that predate the streaming fields."""
        stream = getattr(req, "stream", None)
        if stream is not None:
            stream._finish(reason, exc)
        stream_cb = getattr(req, "stream_cb", None)
        if stream_cb is not None:
            try:
                stream_cb(np.empty(0, np.int64),
                          getattr(req, "n_streamed", 0), reason)
            except Exception:
                pass

    def _finalize_cancelled(self, cancels):
        """Fail torn-down requests with the typed taxonomy error and
        seal their observability record (OUTSIDE the lock): the span
        tree carries the cancel/shed reason and the request is
        retained as a flight-recorder incident — exactly the
        requests an operator will ask about."""
        for req, reason in cancels:
            if reason == "deadline":
                exc = DeadlineExceeded(
                    "deadline_ms expired before completion; request "
                    "torn down at the burst boundary")
            else:
                exc = RequestCancelled("request cancelled by client")
            self._finish_stream(req, reason, exc)
            try:
                req.reply.set_exception(exc)
            except futures.InvalidStateError:
                pass
            if req.trace is not None \
                    and req.trace.owner == "server":
                req.trace.finish(status="cancelled", reason=reason,
                                 error=repr(exc))
            elif obs_metrics.metrics_on():
                from ..observability import flight as obs_flight

                obs_flight.RECORDER.record(
                    {"request_id":
                         obs_tracing.TRACER.next_request_id(),
                     "status": "cancelled", "reason": reason,
                     "server": self._obs_id,
                     "error": repr(exc)}, incident=True)

    # --- scheduler ----------------------------------------------------
    def _pop_next(self):
        """Next queued request to admit: FIFO, or the pluggable
        admit_select hook's pick (index into the queue snapshot).
        Called under _cv with a non-empty queue."""
        hook = self._admit_select
        idx = 0
        if hook is not None and len(self._queue) > 1:
            # int() failure counts as a hook failure (warned), an
            # out-of-range index as a silent decline
            ok, raw = _call_scheduling_hook(
                self, lambda q: int(hook(q)), tuple(self._queue),
                "admit_select", "FIFO admission")
            if ok and 0 <= raw < len(self._queue):
                idx = raw
        if idx == 0:
            return self._queue.popleft()
        self._queue.rotate(-idx)
        req = self._queue.popleft()
        self._queue.rotate(idx)
        return req

    def _plan_admissions_locked(self, failures):
        """FIFO admission into free slots (arrival order is the
        fairness contract, admit_select the pluggable override; slots
        assigned lowest-index-first; at most the largest admission
        bucket per cycle — a custom admit_buckets ladder may cover
        less than n_slots, and the overflow simply waits one cycle).
        Called under _cv; `failures` collects (req, exc) pairs the
        caller fails OUTSIDE the lock (paged exhaustion path)."""
        admits = []
        t_admit = time.monotonic()
        for slot in range(self.n_slots):
            if not self._queue \
                    or len(admits) >= self._admit_buckets[-1]:
                break
            if self._lanes[slot] is None:
                req = self._pop_next()
                self._lanes[slot] = req
                self._note_admit_locked(req, slot, t_admit,
                                        self._admit_tier)
                if req.trace is not None:
                    req.trace.add_span("slotpool.queue",
                                       req.t_arrival, t_admit,
                                       slot=slot)
                admits.append((slot, req))
        return admits

    # the admission flavor of the cycle being planned: the dense
    # server has one (every admission prefills), the paged server
    # decides one per cycle in its _plan_admissions_locked
    _admit_tier = "miss"

    def _note_admit_locked(self, req, slot, t_admit, tier):
        """One admission: stamp the request, count its wait in the
        queue, and mark it in a profile as a short `slotpool.admit`
        span inside `slotpool.plan` (how a per-request number reaches
        the device trace). Called under _cv."""
        req.t_admit = t_admit
        wait_s = t_admit - req.t_arrival
        self._queue_wait.observe(wait_s * 1e3)
        with obs_tracing.span("slotpool.admit") as sp:
            if sp.recording:
                sp.attrs.update(wait_us=round(wait_s * 1e6),
                                tier=tier, slot=slot)

    def _plan_burst_locked(self, admits, drain, failures):
        """Burst policy for the coming cycle: (n_steps, min_active,
        run). Paged scheduling overrides this to cap the burst at the
        allocated block coverage. Called under _cv."""
        occupied = sum(l is not None for l in self._lanes)
        if not occupied:
            return 0, 0, False
        n = self.drain_steps if drain else self.steps_per_tick
        m = occupied - 1 if (self.exit_on_retire and not drain) else 0
        return n, max(0, m), True

    def _admission_feed(self, admits):
        """(serve key, admission feeds) for this cycle's admits;
        padded rows replicate the last prompt and scatter to the
        dustbin lane."""
        A = _bucket_for(len(admits), self._admit_buckets,
                        "admission batch")
        feed = {
            "src_ids": np.concatenate(
                [req.src for _, req in admits]
                + [admits[-1][1].src] * (A - len(admits)), axis=0),
            "slots": np.array(
                [slot for slot, _ in admits]
                + [self.bundle.dustbin] * (A - len(admits)),
                np.int64)}
        if self._needs_seeds:
            # padded rows' seeds scatter to the dustbin lane: garbage
            # there is harmless (it never activates)
            feed["seeds"] = np.array(
                [req.seed for _, req in admits]
                + [0] * (A - len(admits)), np.int64)
        return A, feed

    def _pre_dispatch(self) -> Dict[str, np.ndarray]:
        """Hook: the host-owned tables this dispatch is fed (the paged
        schedulers' block table, prompt references and lane mask: the
        bundle's `fed_tables`), read just before the fused dispatch."""
        return {}

    def _post_dispatch(self, outs):
        """Hook: absorb fetched state (paged per-lane step counters)
        right after a successful dispatch."""

    # --- background work (chunked prefill) ---------------------------
    # A cycle with no admissions may still carry background device
    # work fused with the decode burst (paged chunked prefill: one
    # prompt-chunk phase program per dispatch). The hooks keep the
    # base loop generic: the wait predicate stays awake while a job
    # is in flight, the cycle swaps the serve key, and a failed
    # dispatch aborts the job alongside the lanes.
    def _has_background_work_locked(self) -> bool:
        """Hook: True while a background job needs dispatches even
        with an empty queue and no live lanes. Called under _cv."""
        return False

    def _has_pending_external_locked(self) -> bool:
        """Hook: True while requests are in flight OUTSIDE this
        scheduler (a disaggregated prefill worker) — drain() must
        wait on them, but the cycle loop must NOT wake for them
        (their completion callback notifies _cv itself; waking early
        would busy-spin for the whole external job). Called under
        _cv."""
        return False

    def _skip_serve_key(self, key) -> bool:
        """Hook: True to leave a serve program unprepared (the paged
        server skips ('chunked', p) keys when an external prefill
        worker owns their dispatches on its own scope)."""
        return False

    def _background_feed(self):
        """Hook: (serve key, extra feeds) for this cycle's background
        work, or None. Only consulted when the cycle admits nothing
        (admissions and background work are distinct serve keys)."""
        return None

    def _background_abort_locked(self):
        """Hook: a dispatch raised (or the server is closing) — drop
        the in-flight background job and return its request (failed
        by the caller) or None. Called under _cv."""
        return None

    def _flush_requests_locked(self, pending):
        """Hook: the listed requests are being failed wholesale
        (close()) — drop any per-request bookkeeping (paged handoff
        entry refs). Called under _cv."""

    def _release_lane(self, slot, req):
        """Hook: a lane stopped serving `req` (retired, errored, or
        failed) — paged scheduling frees its blocks/prompt entry."""

    def _fail_requests(self, failures):
        for req, exc in failures:
            self._finish_stream(req, "error", exc)
            try:
                req.reply.set_exception(exc)
            except futures.InvalidStateError:
                pass
            if req.trace is not None and req.trace.owner == "server":
                req.trace.finish(status="error", error=repr(exc))

    def _idle_locked(self) -> bool:
        """Nothing queued, no live lane, no background job: the
        scheduler may sleep. Called under _cv."""
        return self._running and not self._queue \
            and all(l is None for l in self._lanes) \
            and not self._has_background_work_locked()

    def _loop(self):
        while True:
            failures = []
            # the cycle's record: opened after the wait, where the
            # planning starts and under the lock the wait held, and
            # closed after the last delivery
            rec = obs_tracing.cycle("slotpool.cycle", self._cycles)
            try:
                with self._cv:
                    if self._idle_locked():
                        with obs_tracing.span("slotpool.wait"):
                            while self._idle_locked():
                                self._cv.wait()
                    if not self._running:
                        return
                    rec.__enter__()
                    with obs_tracing.span("slotpool.plan") as sp:
                        cancels = self._shed_cancelled_locked(
                            time.monotonic())
                        admits = self._plan_admissions_locked(failures)
                        drain = not self._queue
                        # empty queue: let the burst run — the device
                        # loop exits by itself once the pool drains
                        n_steps, min_active, run = \
                            self._plan_burst_locked(admits, drain,
                                                    failures)
                        planned = dict(admits=len(admits),
                                       queue_depth=len(self._queue),
                                       tier=self._admit_tier or "none")
                        # `submitted`: what the callers' callbacks
                        # send back in on this thread during the
                        # cycle (`_submit_span`)
                        rec.attrs.update(planned, submitted=0)
                        if sp.recording:
                            sp.attrs.update(planned)
                    if run:
                        self._busy = True  # drain() waits on this
                # failing futures fires their done-callbacks
                # synchronously — never under the scheduler lock
                if cancels or failures:
                    with obs_tracing.span("slotpool.deliver"):
                        self._finalize_cancelled(cancels)
                        self._fail_requests(failures)
                if run:
                    try:
                        self._cycle(admits, n_steps, min_active)
                    finally:
                        with self._cv:
                            self._busy = False
                            self._cv.notify_all()
                else:
                    # a pass that dispatched nothing is no cycle
                    rec.drop()
            finally:
                if obs_tracing.current_cycle() is rec:
                    rec.__exit__(None, None, None)

    def _cycle(self, admits, n_steps, min_active):
        """ONE fused dispatch per scheduler cycle: admit up to A
        queued prompts and run decode ticks over every live lane
        until n_steps ran or the live-lane count drops to min_active
        — admission cost scales with buckets, not requests, and the
        dispatch overhead amortizes over the whole burst."""
        with obs_tracing.span("slotpool.feed"):
            # the open record of this cycle (none under a test's own
            # drive): its counts are set inside the phases they
            # belong to, so that no moment of a cycle lies between
            # two spans for the record's sake
            rec = obs_tracing.current_cycle()
            transfers = self.executor._transfers
            placed0 = transfers.placed_arrays
            fetched0 = transfers.fetched_arrays
            feed = {"n_steps": np.array([n_steps], np.int64),
                    "min_active": np.array([max(0, min_active)],
                                           np.int64)}
            key = 0
            background = False
            if admits:
                key, extra = self._admission_feed(admits)
                feed.update(extra)
            else:
                bg = self._background_feed()
                if bg is not None:
                    key, extra = bg
                    feed.update(extra)
                    background = True
            k_used = self._spec_k
            if self._spec_ctl is not None and not background:
                # adaptive-k: the controller picks the rung the whole
                # pool runs this dispatch; non-default rungs route
                # through the pre-built ("k", kv, base) serve variant.
                # Background (chunked-prefill) dispatches keep the
                # default body — their phase programs have no k ladder.
                for slot, _req in admits:
                    self._spec_ctl.reset_lane(slot)
                kv = int(self._spec_ctl.choose())
                if kv != self._spec_k \
                        and ("k", kv, key) in self._serves:
                    key = ("k", kv, key)
                    k_used = kv
            feed.update(self._pre_dispatch())
            if rec is not None:
                rec.key = key
                rec.attrs["n_steps"] = n_steps
        try:
            c0 = self.executor.compile_count
            d0 = self.executor.disk_load_count
            with obs_tracing.ambient(
                    [r.trace for r in self._lanes
                     if r is not None and r.trace is not None]):
                with obs_tracing.span("slotpool.dispatch",
                                      admits=len(admits),
                                      n_steps=n_steps) as sp:
                    t_run0 = time.monotonic()
                    outs = self._serves[key].run(feed,
                                                 return_numpy=True)
                    wall_s = time.monotonic() - t_run0
                    if sp.recording:
                        sp.attrs["cache"] = _cache_tier(
                            self.executor, c0, d0)
                    if self._devtel.active:
                        # device-side burst interior: delta the
                        # telemetry counters and annotate the span
                        # the flight recorder retains (exit reason,
                        # ticks, occupancy)
                        ticks = self._absorb_devtel(key, outs, wall_s,
                                                    sp)
                        if rec is not None:
                            rec.attrs["ticks"] = ticks
                    if self._spec_names:
                        # delta the device-side spec counters for
                        # this dispatch: the acceptance-rate sample
                        # and the burst annotation the flight
                        # recorder uses to explain slow bursts
                        # (low mean accepted length = the draft
                        # stopped agreeing with the target)
                        d = self._absorb_spec_counters(outs)
                        self._absorb_lane_counters(outs, d, k_used)
                        if d["proposed"] > 0:
                            self._acc_hist.observe(
                                d["accepted"] / d["proposed"])
                            if sp.recording:
                                # per lane-tick (see stats()): a LOW
                                # value explains a slow burst — the
                                # draft stopped agreeing with the
                                # target
                                sp.attrs["mean_accepted_len"] = round(
                                    d["emitted"] * k_used
                                    / d["proposed"], 3)
                        if self._spec_ctl is not None:
                            sp.attrs["spec_k"] = k_used
        except BaseException as e:
            with self._cv:
                lanes = [(slot, r)
                         for slot, r in enumerate(self._lanes)
                         if r is not None]
                for slot, r in lanes:
                    r.finalized = True
                    self._release_lane(slot, r)
                self._lanes = [None] * self.n_slots
                bg_req = self._background_abort_locked()
            if bg_req is not None:
                bg_req.finalized = True
                lanes = lanes + [(None, bg_req)]
            for _slot, r in lanes:
                self._finish_stream(r, "error", e)
                try:
                    r.reply.set_exception(e)
                except futures.InvalidStateError:
                    pass
                if r.trace is not None and r.trace.owner == "server":
                    r.trace.finish(status="error", error=repr(e))
            return
        with obs_tracing.span("slotpool.retire"):
            self._post_dispatch(outs)
            retired, cancels, stream_out = self._retire_lanes(outs)
        # ordered delivery, OUTSIDE the lock: every streamed token of
        # a burst lands before its finish marker, which lands before
        # the whole-response future resolves
        with obs_tracing.span("slotpool.deliver"):
            for req, first_seq, chunk in stream_out:
                self._deliver_stream(req, first_seq, chunk)
            for req, toks, fin in retired:
                self._finish_stream(req, fin)
                try:
                    req.reply.set_result(toks)
                except futures.InvalidStateError:
                    pass
                if req.trace is not None \
                        and req.trace.owner == "server":
                    req.trace.finish()
            self._finalize_cancelled(cancels)
            if rec is not None:
                rec.attrs.update(
                    retired=len(retired), delivered=len(stream_out),
                    placed_arrays=transfers.placed_arrays - placed0,
                    fetched_arrays=transfers.fetched_arrays - fetched0)

    def _retire_lanes(self, outs):
        """The sweep over the lanes after a dispatch, under the lock:
        retire what finished, tear down what was cancelled or ran
        past its deadline, slice every stream's fresh tokens. Returns
        (retired [(req, row, finish reason)], cancels [(req, reason)],
        stream chunks [(req, first seq, tokens)]) for the caller to
        deliver outside the lock."""
        tok_buf, step, active, _fin = outs[:4]  # [4:] = spec counters
        done_t = time.monotonic()
        retired = []
        cancels = []
        stream_out = []
        with self._cv:
            occupied = 0
            for slot in range(self.n_slots):
                req = self._lanes[slot]
                if req is None:
                    continue
                occupied += 1
                if req.t_first is None:
                    req.t_first = done_t  # first token just landed
                retiring = active[slot] == 0 \
                    and slot not in self._paused
                reason = self._expired_locked(req, done_t)
                if reason is not None and not retiring:
                    # burst-boundary teardown: a finished result
                    # always wins over a same-tick cancel, a doomed
                    # live lane never decodes another burst
                    self._cancel_lane_locked(slot, req, reason)
                    cancels.append((req, reason))
                    continue
                if retiring:
                    # EOS emitted (or buffer full): retire NOW, free
                    # the slot for the next arrival
                    toks = apply_eos_sentinel(
                        tok_buf[slot:slot + 1], self._end_id)[0]
                    ntok = int(count_generated_tokens(
                        toks[None], self._end_id)[0])
                    lat = (done_t - req.t_arrival) * 1e3
                    self._latencies.observe(lat)
                    self._ttft.observe(
                        (req.t_first - req.t_arrival) * 1e3)
                    if ntok:
                        self._per_token.observe(lat / ntok)
                        self._n_tokens += ntok
                    self._n_done += 1
                    self._t_last_done = done_t
                    req.finalized = True
                    self._release_lane(slot, req)
                    self._lanes[slot] = None
                    if req.trace is not None:
                        req.trace.add_span(
                            "slotpool.decode",
                            req.t_admit if req.t_admit is not None
                            else req.t_arrival,
                            done_t, slot=slot, tokens=ntok)
                    fin = "eos" if (ntok < toks.shape[0]
                                    and toks[ntok] == self._end_id) \
                        else "length"
                    retired.append((req, toks, fin))
                    # stream through the terminator: positions
                    # emitted+1..ntok (row is already sentinel-
                    # normalized, so nothing past ntok is real)
                    hi, row = ntok, toks
                else:
                    # live lane: step[slot] is the NEXT write
                    # position, so step-1 is the newest valid token.
                    # Position 0 is the GO token — never streamed.
                    # Preempted-and-readmitted lanes re-decode the
                    # same prefix byte-exactly (greedy + per-position
                    # seed folding), so the monotone `emitted` mark
                    # suppresses duplicates for free.
                    hi, row = int(step[slot]) - 1, tok_buf[slot]
                if (req.stream is not None
                        or req.stream_cb is not None) \
                        and hi > req.emitted:
                    chunk = np.asarray(
                        row[req.emitted + 1:hi + 1]).astype(np.int64)
                    stream_out.append((req, req.n_streamed, chunk))
                    req.n_streamed += len(chunk)
                    req.emitted = hi
            self._n_ticks += 1
            self._occ_sum += occupied / self.n_slots
        return retired, cancels, stream_out

    def _absorb_spec_counters(self, outs) -> dict:
        """Read the fetched device-side speculative counters
        (cumulative since init_slot_state) and return this dispatch's
        DELTAS; updates the running totals under the scheduler
        lock."""
        vals = {key: int(np.asarray(outs[4 + i]).reshape(-1)[0])
                for i, key in enumerate(
                    ("proposed", "accepted", "emitted",
                     "draft_steps", "target_steps"))}
        with self._cv:
            deltas = {k: vals[k] - self._spec_tot[k] for k in vals}
            self._spec_tot = vals
        return deltas

    def _absorb_lane_counters(self, outs, spec_deltas, k_used):
        """Delta the per-lane acceptance counters, feed the adaptive
        controller, and attribute this dispatch's spec deltas to the
        rung it ran (the per-k stats windows)."""
        if not self._lane_names:
            return
        off = 4 + len(self._spec_names)
        lane_deltas = []
        with self._cv:
            for i in range(len(self._lane_names)):
                cur = np.asarray(outs[off + i]).reshape(-1).astype(
                    np.int64)
                prev = self._lane_tot[i]
                lane_deltas.append(
                    cur if prev is None else cur - prev)
                self._lane_tot[i] = cur
            per_k = self._per_k_tot.get(int(k_used))
            if per_k is not None:
                per_k["dispatches"] += 1
                for src, dst in (("proposed", "proposed"),
                                 ("accepted", "accepted"),
                                 ("emitted", "emitted")):
                    per_k[dst] += spec_deltas[src]
            hist = self._acc_hist_k.get(int(k_used))
            if hist is not None and spec_deltas["proposed"] > 0:
                hist.observe(spec_deltas["accepted"]
                             / spec_deltas["proposed"])
        if self._spec_ctl is not None and len(lane_deltas) == 2:
            self._spec_ctl.observe(lane_deltas[0], lane_deltas[1],
                                   k=int(k_used))

    def _cost_snapshot(self, key) -> Optional[dict]:
        """Executable cost-model snapshot for serves[key]
        (observability/costmodel.py), resolved lazily on the first
        metrics-on dispatch of the key (one extra trace, no XLA
        compile) and cached on the server forever after — never a
        steady-state cost."""
        snap = self._cost_snaps.get(key)
        if snap is None and obs_metrics.metrics_on():
            snap = obs_costmodel.lookup(self.bundle.serves[key])
            if snap is not None:
                self._cost_snaps[key] = snap
        return snap

    def _absorb_devtel(self, key, outs, wall_s, sp):
        """Delta the fetched device-telemetry counters for this
        dispatch, annotate the burst span with the interior the
        flight recorder retains (ticks actually run, the exit reason,
        the occupancy integral) and, at metrics level, feed the
        burst's work and wall time to the cost model's rate
        calibration (`expected_service_ms`)."""
        off = 4 + len(self._spec_names) + len(self._lane_names)
        with self._cv:
            deltas = self._devtel.absorb(
                outs[off:off + len(self._devtel.fetch_names)])
        ticks = deltas.get("tel_ticks", 0)
        if not ticks:
            return 0
        if sp.recording:
            sp.attrs["ticks"] = ticks
            sp.attrs["occupancy_integral"] = deltas.get(
                "tel_occupancy", 0)
            reason = obs_devtel.DeviceTelemetry.exit_reason(deltas)
            if reason is not None:
                sp.attrs["exit_reason"] = reason
        if not obs_metrics.metrics_on():
            return ticks
        # per-tick cost comes from the KEY-0 serve snapshot — the
        # pure-burst program (no admission body), so its one-While-
        # body cost IS one tick. A per-key snapshot would fold the
        # admission prologue (A full encoder prefills on a miss key)
        # into every tick of the burst, inflating the calibrated rate
        # by ticks x prologue.
        flops = (self._cost_snapshot(0) or {}).get("flops")
        if flops:
            # the While body is costed once, so tick-flops x ticks is
            # the burst's work — but an admission dispatch's wall
            # ALSO covers the encoder prologue the key-0 flops
            # excludes, and feeding that wall uncorrected would
            # depress the calibrated rate (blurring the very
            # model-cost-vs-host-weather split this exists for).
            # Add the prologue's own flops from the key's snapshot
            # (key flops = admission body + one tick body); when the
            # prologue cost is unknown, skip the sample rather than
            # poison the median. Low-concurrency traffic admits on
            # EVERY dispatch, so admission dispatches must calibrate
            # or the rate never warms.
            work = flops * ticks
            if sp.attrs.get("admits", 0) and key != 0:
                kflops = (self._cost_snapshot(key) or {}).get("flops")
                work = None if kflops is None \
                    else work + max(0.0, kflops - flops)
            if work:
                obs_costmodel.observe(work, wall_s)
        return ticks

    def _host_tel_locked(self, reset: bool) -> dict:
        """Host-side supplement to stats()['device_telemetry']
        (window-scoped; re-based on reset). The paged scheduler
        overrides with its allocation counters; the dense server has
        none. Called under _cv."""
        return {}

    def _speculative_stats_locked(self) -> Optional[dict]:
        if self._spec_k <= 0:
            return None
        # window-scoped like every other stats() counter: reset=True
        # re-bases, so acceptance_rate and the acceptance-rate
        # histogram always describe the SAME window (a lifetime-
        # average rate next to a window histogram masked exactly the
        # acceptance collapses the surface exists to show)
        t = {key: self._spec_tot[key] - self._spec_base[key]
             for key in self._spec_tot}
        out = {
            "k": self._spec_k,
            "proposed": t["proposed"],
            "accepted": t["accepted"],
            "emitted": t["emitted"],
            "draft_steps": t["draft_steps"],
            "target_steps": t["target_steps"],
            "acceptance_rate": (
                round(t["accepted"] / t["proposed"], 4)
                if t["proposed"] else None),
            # per LANE-tick (proposed/k = live lane-ticks): tokens a
            # lane advances per verify, in [1, k+1] — NOT per program
            # tick, which sums all live lanes and scales with
            # occupancy (the bench reports that separately as
            # tokens_per_target_step)
            "mean_accepted_len": (
                round(t["emitted"] * self._spec_k / t["proposed"], 3)
                if t["proposed"] else None),
            "acceptance_rate_hist": self._acc_hist.percentile_dict(),
        }
        if self._spec_k_options:
            # adaptive-k controller observability: the same window
            # (reset=True re-bases — the r14 semantics) split per
            # rung, so a degradation to k=0 is visible as residency,
            # not just as a blended acceptance number
            per_k = {}
            for kv in self._spec_k_options:
                w = {c: self._per_k_tot[kv][c]
                     - self._per_k_base[kv][c]
                     for c in self._per_k_tot[kv]}
                w["acceptance_rate"] = (
                    round(w["accepted"] / w["proposed"], 4)
                    if w["proposed"] else None)
                hist = self._acc_hist_k.get(kv)
                if hist is not None:
                    w["acceptance_rate_hist"] = \
                        hist.percentile_dict()
                per_k[kv] = w
            out["per_k"] = per_k
            out["k_options"] = list(self._spec_k_options)
            if self._spec_ctl is not None:
                out["controller"] = self._spec_ctl.stats()
        return out

    # --- observability ------------------------------------------------
    def stats(self, reset: bool = False) -> dict:
        """Atomic snapshot; reset/uptime semantics identical to
        InferenceServer.stats (window counters zeroed under the
        scheduler lock, uptime_s monotonic since start)."""
        exe = self.executor
        with self._cv:
            now = time.monotonic()
            done_span = (
                self._t_last_done - self._t_first_arrival
                if self._t_last_done is not None
                and self._t_first_arrival is not None else None)
            occ = (self._occ_sum / self._n_ticks
                   if self._n_ticks else None)
            snap = {
                "requests": self._n_requests,
                "completed": self._n_done,
                "queue_depth": len(self._queue),
                "slots": self.n_slots,
                "slot_occupancy": round(occ, 4) if occ else None,
                "ticks": self._n_ticks,
                "steps_per_tick": self.steps_per_tick,
                "uptime_s": round(now - self._t_start, 3),
                "window_s": round(now - self._t_window, 3),
                "compile_count": exe.compile_count,
                "cache_hit_count": exe.cache_hit_count,
                "disk_load_count": exe.disk_load_count,
                "cache_evict_count": exe.cache_evict_count,
                "warmed_compiles": self._warmed_compiles,
                "latency_ms": _pct_dict(self._latencies),
                "ttft_ms": _pct_dict(self._ttft),
                "queue_wait_ms": _pct_dict(self._queue_wait),
                "per_token_ms": _pct_dict(self._per_token),
                # the scheduler's cycles by phase (p50/p95/max), and
                # how many took over twice their serve key's median
                "cycle_ms": self._cycles.summary(),
                "slow_cycles": self._cycles.slow_cycles,
                "tokens": self._n_tokens,
                "retired_per_s": (
                    round(self._n_done / done_span, 1)
                    if done_span else None),
                # r20 teardowns (lifetime, like requests/completed):
                # every count released its holds through the PTA201
                # `cancel` exit — leak checks gauge-assert against
                # the pool stats, these explain WHY lanes vanished
                "cancelled": self._n_cancelled,
                "deadline_expired": self._n_deadline,
                # which route each bound serve program's paged
                # self-attention, and its read of the prompt table for
                # cross-attention, took when it was traced ("kernel",
                # "reference"; a program not traced yet, or a dense
                # one, lists none): trace-time record, no tick reads it
                **{stat: {
                    str(key): sorted({
                        "kernel" if routed else "reference"
                        for kernel, _, routed in h.kernel_routes()
                        if kernel == label})
                    for key, h in self._serves.items()}
                   for stat, label in (
                       ("self_attention_routes",
                        ROUTE_LABELS["cells"]),
                       ("cross_attention_routes",
                        ROUTE_LABELS["prompt_table"]))},
            }
            spec = self._speculative_stats_locked()
            if spec is not None:
                snap["speculative"] = spec
            if self._devtel.active:
                # the device-side burst interior, window-scoped like
                # every other stats() counter (reset=True re-bases —
                # the r14 spec-counter window semantics)
                dt = self._devtel.stats_dict(self._devtel.window())
                dt.update(self._host_tel_locked(reset))
                snap["device_telemetry"] = dt
            if reset:
                self._n_requests = self._n_done = 0
                self._n_tokens = self._n_ticks = 0
                self._occ_sum = 0.0
                self._latencies.clear()
                self._ttft.clear()
                self._queue_wait.clear()
                self._per_token.clear()
                self._cycles.clear()
                self._acc_hist.clear()
                self._spec_base = dict(self._spec_tot)
                self._per_k_base = {k: dict(v) for k, v in
                                    self._per_k_tot.items()}
                for hist in self._acc_hist_k.values():
                    hist.clear()
                self._devtel.rebase()
                self._t_first_arrival = None
                self._t_last_done = None
                self._t_window = now
            return snap

    def _metrics_samples(self):
        """Pull-provider for observability.metrics.expose()."""
        lab = {"server": self._obs_id}
        with self._cv:
            occ = (self._occ_sum / self._n_ticks
                   if self._n_ticks else 0.0)
            samples = [
                ("paddle_tpu_server_requests_total", lab,
                 self._n_requests),
                ("paddle_tpu_server_completed_total", lab,
                 self._n_done),
                ("paddle_tpu_server_queue_depth", lab,
                 len(self._queue)),
                ("paddle_tpu_server_slot_occupancy", lab, occ),
                ("paddle_tpu_server_ticks_total", lab, self._n_ticks),
                ("paddle_tpu_server_tokens_total", lab,
                 self._n_tokens),
                ("paddle_tpu_server_cancelled_total", lab,
                 self._n_cancelled),
                ("paddle_tpu_server_deadline_expired_total", lab,
                 self._n_deadline),
                ("paddle_tpu_request_latency_ms", lab,
                 self._latencies),
                ("paddle_tpu_request_ttft_ms", lab, self._ttft),
                ("paddle_tpu_request_queue_wait_ms", lab,
                 self._queue_wait),
                ("paddle_tpu_per_token_ms", lab, self._per_token),
            ] + self._cycles.metric_samples(
                "paddle_tpu_server_cycle_ms", lab)
            if self._spec_k > 0:
                t = self._spec_tot
                samples += [
                    ("paddle_tpu_spec_proposed_total", lab,
                     t["proposed"]),
                    ("paddle_tpu_spec_accepted_total", lab,
                     t["accepted"]),
                    ("paddle_tpu_spec_emitted_total", lab,
                     t["emitted"]),
                    ("paddle_tpu_spec_draft_steps_total", lab,
                     t["draft_steps"]),
                    ("paddle_tpu_spec_target_steps_total", lab,
                     t["target_steps"]),
                    ("paddle_tpu_spec_acceptance_rate", lab,
                     self._acc_hist),
                ]
                for kv in self._spec_k_options:
                    klab = dict(lab, k=str(kv))
                    samples.append(
                        ("paddle_tpu_spec_k_dispatches_total", klab,
                         self._per_k_tot[kv]["dispatches"]))
                    hist = self._acc_hist_k.get(kv)
                    if hist is not None:
                        samples.append(
                            ("paddle_tpu_spec_acceptance_rate_k",
                             klab, hist))
            samples += self._devtel.metric_samples(lab)
            return samples


class PagedContinuousGenerationServer(ContinuousGenerationServer):
    """Continuous batching over the PAGED KV layout (vLLM-style block
    tables + prefix reuse; models/decode_engine.py module docstring
    has the layout).

    Everything the base scheduler does (fused admit+burst dispatches,
    immediate retirement, zero steady-state compiles) carries over;
    this subclass adds the HOST side of paging:

    * **Block allocation** — per-lane self-KV blocks come from a
      ``HostBlockPool`` free-list; a lane starts with one block and
      grows lazily as its generation crosses block boundaries
      (``_plan_burst_locked`` caps each burst at the coverage it
      could allocate). Short requests therefore consume 1 block where
      the dense layout reserved the full maxT — the capacity lever.
    * **Prefix-cache admission** — prompts are classified hit/partial/
      miss against the refcounted ``PromptPrefixCache``; hits admit
      through the encoder-free ``("hit", A)`` serve programs (the
      shared-system-prompt fast path), misses/partials prefill ONCE
      into a pool entry later hits reuse. One admission flavor per
      fused cycle; duplicate cold prompts in one batch defer one
      cycle and come back as hits.
    * **Backpressure, pausing, preemption, exhaustion** — transient
      pool pressure queues (admission) or pauses lanes for a cycle
      (mid-generation: the lane's active flag is host-masked so it
      cannot write the shared pool); when EVERY live lane blocks at a
      boundary (lockstep long generations), the youngest is
      recompute-PREEMPTED — blocks freed, request re-queued at the
      front; greedy decode is deterministic so the re-decoded tokens
      are byte-identical. Only a LONE request that outgrows the whole
      pool fails, with the NAMED retryable ``BlockPoolExhausted`` —
      never a hang, and never a lost request that could have run.

    FIFO admission only: ``admit_select`` hooks are rejected (tier
    grouping owns the admission order).
    """

    def __new__(cls, bundle=None, *args, **kwargs):
        # a decoder-only bundle (no encoder, prompts of their own
        # length) is planned by inference/decoder_only.py; the cycle,
        # the pools and the radix tree are this class's
        if cls is PagedContinuousGenerationServer \
                and getattr(bundle, "decoder_only", False):
            from .decoder_only import DecoderOnlyPagedServer

            cls = DecoderOnlyPagedServer
        return super().__new__(cls)

    def __init__(self, bundle, radix_reuse=True, chunked_prefill=None,
                 prefill_worker=None, **kwargs):
        cache = getattr(bundle, "cache", None)
        if cache is None or cache.layout != "paged":
            raise ValueError(
                "PagedContinuousGenerationServer needs a bundle built "
                "with CacheConfig(layout='paged') — for dense bundles "
                "use ContinuousGenerationServer")
        # radix_reuse=False keeps the session API but replays every
        # turn's FULL history into fresh blocks (resume step 0, no
        # shared chains) — the re-prefill baseline bench.py multiturn
        # measures the radix win against
        self._radix_reuse = bool(radix_reuse)
        if kwargs.get("admit_select") is not None:
            raise ValueError(
                "paged serving owns admission order (prefix-tier "
                "grouping); admit_select hooks are not supported")
        self.cache = cache
        # PTA200 preflight: a bundle DECLARING its session workload
        # (bundle.workload = {"distinct_session_prompts": K, ...})
        # gets the capacity model's verdict at construction — a
        # provably-infeasible config raises the named, non-retryable
        # AdmissionInfeasible here instead of wedging admissions at
        # runtime (the same predicate the zoo gate's PTA200 checker
        # and the per-submit session preflight evaluate; the
        # protomodel explorer is its oracle)
        workload = getattr(bundle, "workload", None)
        if isinstance(workload, dict) \
                and "distinct_session_prompts" in workload:
            from ..analysis.liveness import session_feasibility

            chk = session_feasibility(
                cache.n_prompt_entries,
                int(workload["distinct_session_prompts"]),
                sessions_close=bool(workload.get("sessions_close",
                                                 False)),
                cold_traffic=bool(workload.get("cold_traffic",
                                               False)))
            if not chk.feasible:
                raise AdmissionInfeasible(chk.witness)
        self._bs = cache.block_size
        self._blocks = HostBlockPool(cache.n_blocks)
        self._prefix = PromptPrefixCache(cache.n_prompt_entries,
                                         cache.block_size)
        rows = bundle.n_slots + 1
        self._tab = np.zeros((rows, cache.pages(bundle.max_out_len)),
                             np.int32)
        self._pref = np.full((rows,), cache.n_prompt_entries,
                             np.int32)
        self._lane_blocks = [[] for _ in range(bundle.n_slots)]
        self._lane_entry: List[Optional[int]] = [None] * bundle.n_slots
        self._lane_step = np.zeros((rows,), np.int64)
        self._admit_tier = None
        # radix block-prefix reuse (multi-turn chat sessions): the
        # tree shares decoded-token self-KV chains across turns and
        # fan-out branches; per-lane the READ-ONLY shared prefix
        # (_lane_shared, one pool ref per block) is kept apart from
        # the lane-exclusive writable tail (_lane_blocks) — the
        # host half of the PTA192 read-only-while-shared contract
        self._radix = RadixBlockTree(self._blocks, self._bs)
        self._lane_shared = [[] for _ in range(bundle.n_slots)]
        self._lane_sess: List[Optional[object]] = \
            [None] * bundle.n_slots
        self._sessions: Dict[object, dict] = {}
        # session harvest source: the last dispatch's token buffer
        # (valid only between a successful _post_dispatch and the
        # next _pre_dispatch — a failed dispatch must never graft a
        # stale buffer into the tree)
        self._last_tok = None
        self._harvest_ok = False
        self._radix_admits = 0
        # prefix hit-DEPTH histogram (in blocks): how deep radix
        # admissions actually share — the reuse-efficiency signal
        # the flat hit counter cannot show
        self._hit_depth = Histogram(
            "paddle_tpu_blockpool_prefix_hit_depth",
            buckets=(0.0, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0))
        self._pause_events = 0  # lanes parked for >= 1 cycle by pool
        #                         pressure (observability)
        self._preemptions = 0   # recompute-preempted lanes (vLLM-
        #                         style requeue; tokens stay exact)
        # devtel host supplement (observability/devtel.HOST_COUNTERS):
        # window-scoped high-water marks + pause/preempt bases for
        # stats()['device_telemetry'] (the device cannot see host
        # allocation decisions, but they explain the same slow bursts)
        self._blocks_hwm = 0
        self._entries_hwm = 0
        self._pause_base = 0
        self._preempt_base = 0
        # chunked-prefill job state (set BEFORE super().__init__ —
        # the scheduler thread may consult the hooks the moment the
        # loop starts): ONE prompt prefills at a time, one phase
        # program per fused dispatch, decode ticks riding in the same
        # While either way
        self._chunk_keys = sorted(
            (k for k in bundle.serves
             if isinstance(k, tuple) and k[0] == "chunked"),
            key=lambda kv: kv[1])
        self._prefill_job = None     # {req, prompt, entry, phase, ci}
        self._chunk_turn = False     # alternation vs admission cycles
        self._bg_ticked = False      # this dispatch carried a chunk
        self._handoff: Dict[int, int] = {}  # id(req) -> entry ref
        self._chunk_jobs = 0
        self._chunk_ticks_host = 0
        self._n_chunks = cache.n_chunks(bundle.seq_len) \
            if cache.chunked else 0
        # cross-request radix reuse on PLAIN submits: retired greedy
        # generations memoized prompt -> history so an identical
        # sessionless prompt re-admits through the encoder-free radix
        # tier (teacher-forced replay of its own deterministic output)
        self._plain_hist: "collections.OrderedDict[tuple, list]" = \
            collections.OrderedDict()
        self._plain_hist_cap = 32
        self._plain_radix_admits = 0
        # disaggregated prefill (DistServe): cold prompts route to an
        # external DisaggregatedPrefillWorker (own scope, own device
        # slice, own thread); finished cross-KV rows come back
        # through _disagg_inbox, drained on THIS scheduler thread
        self._prefill_worker = prefill_worker
        self._disagg_inbox: "collections.deque" = collections.deque()
        self._disagg_prompts: set = set()
        self._disagg_out = 0
        self._disagg_handoffs = 0
        self._prefill_blocked = False
        super().__init__(bundle, **kwargs)
        if prefill_worker is not None:
            if chunked_prefill is False:
                raise ValueError(
                    "prefill_worker implies chunked scheduling; "
                    "chunked_prefill=False contradicts it")
            if prefill_worker.bundle is not bundle:
                raise ValueError(
                    "prefill_worker must serve the SAME bundle (the "
                    "handoff copies cross-KV rows between scopes by "
                    "the bundle's state names)")
            chunked_prefill = True
        if chunked_prefill is None:
            chunked_prefill = bool(self._chunk_keys) \
                and self._spec_k == 0
        if chunked_prefill and not self._chunk_keys:
            raise ValueError(
                "chunked_prefill=True needs a bundle built with "
                "CacheConfig(chunk_tokens=C) — this bundle carries no "
                "('chunked', phase) serve programs")
        if chunked_prefill and self._spec_k > 0:
            raise ValueError(
                "chunked prefill does not compose with speculative "
                "bundles yet (the draft encoder runs whole-prompt at "
                "admission); build without spec_k or pass "
                "chunked_prefill=False")
        self._chunked = bool(chunked_prefill)

    # how deep past the queue head the tier-grouped admission scan may
    # look for batch-compatible requests (bounds the O(scan) planning
    # cost per cycle; the head itself is ALWAYS first, so no request
    # can be starved by later same-tier traffic)
    _ADMIT_SCAN_DEPTH = 64

    # --- chat sessions (radix block-prefix reuse) --------------------
    def _session_submit_locked(self, session_id, arr, extend_tokens):
        prompt = tuple(int(x) for x in arr.reshape(-1))
        sess = self._sessions.get(session_id)
        if sess is None:
            if extend_tokens is not None:
                raise ValueError(
                    f"session {session_id!r} has no retired turn to "
                    f"extend; submit its first turn plain")
            # PTA200 dynamic preflight: every open session pins one
            # PromptPrefixCache entry per DISTINCT prompt for its
            # lifetime; admitting a session that pushes the distinct
            # count past the entry pool can NEVER be satisfied until
            # some session closes (pinned entries are unevictable),
            # so raise the named verdict now instead of deadlocking
            # admissions later (== is feasible; close_session frees
            # capacity)
            open_prompts = {s["prompt"]
                            for s in self._sessions.values()}
            open_prompts.add(prompt)
            from ..analysis.liveness import session_feasibility

            chk = session_feasibility(self.cache.n_prompt_entries,
                                      len(open_prompts))
            if not chk.feasible:
                raise AdmissionInfeasible(
                    f"opening session {session_id!r} would pin "
                    f"{len(open_prompts)} distinct prompts against "
                    f"n_prompt_entries="
                    f"{self.cache.n_prompt_entries}; close a "
                    f"session (close_session) or grow the entry "
                    f"pool. {chk.witness}")
            self._sessions[session_id] = {
                "prompt": prompt, "hist": None, "entry": None,
                "turns": 0}
            return
        if sess["prompt"] != prompt:
            raise ValueError(
                f"session {session_id!r} was opened with a different "
                f"prompt: sessions are keyed by PROMPT content (the "
                f"bidirectional encoder pins every KV chain to the "
                f"whole prompt); open a new session for a new prompt")
        if extend_tokens is not None:
            if sess["hist"] is None:
                raise ValueError(
                    f"session {session_id!r}'s first turn has not "
                    f"retired yet; extend after its reply resolves")
            ext = [int(t) for t in np.asarray(extend_tokens)
                   .reshape(-1)]
            maxT = self.bundle.max_out_len
            if len(sess["hist"]) + len(ext) > maxT - 1:
                raise ValueError(
                    f"session {session_id!r} history "
                    f"({len(sess['hist'])} + {len(ext)} tokens) "
                    f"exceeds the decode buffer (max_out_len-1 = "
                    f"{maxT - 1}); close_session and restart")
            sess["hist"] = sess["hist"] + ext

    def close_session(self, session_id):
        """Drop a chat session: releases its cross-KV entry pin and
        forgets the retained history. The session's radix tree nodes
        persist as shared CACHE until evicted under pool pressure.
        Idempotent; in-flight turns of the session finish normally
        (their harvest is skipped)."""
        with self._cv:
            sess = self._sessions.pop(session_id, None)
            if sess is not None and sess["entry"] is not None:
                self._prefix.release(sess["entry"])

    def session_history(self, session_id):
        """The session's retained decoded-token history (list of
        ints, GO token first, terminator excluded), or None before
        its first turn retired / for an unknown session."""
        with self._cv:
            sess = self._sessions.get(session_id)
            if sess is None or sess["hist"] is None:
                return None
            return list(sess["hist"])

    def _alloc_block_locked(self):
        """Pool alloc with the radix tree as reclaimable capacity:
        a miss first evicts the deepest tree-only (refcount-1) leaf
        — cached prefixes are exactly the blocks it is safe to drop
        under pressure."""
        b = self._blocks.alloc()
        if b is None and self._radix.evict(1):
            b = self._blocks.alloc()
        return b

    def _has_background_work_locked(self):
        return self._prefill_job is not None \
            or bool(self._disagg_inbox)

    def _has_pending_external_locked(self):
        return self._disagg_out > 0

    def _skip_serve_key(self, key):
        return (self._prefill_worker is not None
                and isinstance(key, tuple) and key[0] == "chunked")

    def _prefill_inflight_locked(self, prompt) -> bool:
        """True while `prompt`'s cross-KV entry is registered but
        still FILLING (local chunk job or disaggregated worker):
        lookup says hit, but admitting against it would read garbage
        — defer until the handoff re-queues the owning request."""
        if self._prefill_worker is not None:
            return prompt in self._disagg_prompts
        return self._prefill_job is not None \
            and prompt == self._prefill_job["prompt"]

    def _maybe_start_prefill_locked(self, failures):
        """Pop the first plain cold prompt in the scan window into
        the (single) chunked-prefill job: its cross-KV entry is
        acquired fresh-exclusive NOW, then filled one C-token phase
        program per fused dispatch while decode ticks keep running —
        the request itself re-queues as an encoder-free HIT once the
        final phase lands. With a disaggregated worker the job runs
        on the WORKER's scope/slice instead (_route_prefills_locked);
        this scheduler only ever sees the finished handoff."""
        if self._prefill_worker is not None:
            self._route_prefills_locked(failures)
            return
        if self._prefill_job is not None or not self._queue:
            return
        for pos, req in enumerate(self._queue):
            if pos >= self._ADMIT_SCAN_DEPTH:
                return
            if req.session is not None:
                continue  # session turns keep the monolithic path
            prompt = tuple(int(x) for x in req.src.reshape(-1))
            tier, _entry = self._prefix.lookup(prompt)
            if tier == "hit":
                continue
            entry = self._prefix.acquire_fresh(
                prompt, partial=(tier == "partial"))
            if entry is None:
                # every entry pinned: backpressure this cycle (the
                # flag feeds the idle-pool exhaustion check — with
                # nothing in flight to unpin one, waiting is a hang)
                self._prefill_blocked = True
                return
            del self._queue[pos]
            self._prefill_job = {"req": req, "prompt": prompt,
                                 "entry": entry, "phase": 0, "ci": 0}
            self._chunk_jobs += 1
            return

    # --- disaggregated prefill: routing + handoff --------------------
    def _route_prefills_locked(self, failures):
        """Ship every plain cold prompt in the scan window to the
        prefill worker: the cross-KV entry is acquired
        fresh-exclusive HERE (this server owns the prompt-entry
        cache), filled on the worker's scope/slice, and handed back
        through _disagg_inbox. Unlike the local single-job mode the
        worker pipelines jobs — admission order among handoffs is
        preserved by the inbox drain."""
        pos = 0
        scanned = 0
        while pos < len(self._queue) \
                and scanned < self._ADMIT_SCAN_DEPTH:
            req = self._queue[pos]
            scanned += 1
            if req.session is not None:
                pos += 1
                continue
            prompt = tuple(int(x) for x in req.src.reshape(-1))
            if prompt in self._disagg_prompts:
                pos += 1
                continue
            tier, _entry = self._prefix.lookup(prompt)
            if tier == "hit":
                pos += 1
                continue
            entry = self._prefix.acquire_fresh(
                prompt, partial=(tier == "partial"))
            if entry is None:
                self._prefill_blocked = True
                return
            try:
                self._prefill_worker.submit_job(
                    req, prompt, entry, self._disagg_done,
                    self._disagg_fail)
            except BaseException as e:
                self._prefix.release(entry)
                self._prefix.invalidate(entry)
                del self._queue[pos]
                failures.append((req, e))
                return
            del self._queue[pos]
            self._disagg_prompts.add(prompt)
            self._disagg_out += 1
            self._chunk_jobs += 1
            # pos unchanged: the deque shifted left over the del

    def _disagg_done(self, req, prompt, entry, rows):
        """Worker thread: a prefill job finished — queue the handoff
        for the scheduler thread (never touch decode scope state from
        here; the scheduler owns it between dispatches)."""
        fail = None
        with self._cv:
            self._disagg_prompts.discard(prompt)
            self._disagg_out -= 1
            if self._closed:
                self._prefix.release(entry)
                fail = ServerClosed(
                    "server closed while its prompt prefilled")
            else:
                self._disagg_inbox.append((req, entry, rows))
            self._cv.notify_all()
        if fail is not None:
            req.reply.set_exception(fail)
            if req.trace is not None and req.trace.owner == "server":
                req.trace.finish(status="error", error=repr(fail))

    def _disagg_fail(self, req, prompt, entry, exc):
        """Worker thread: a prefill job died — the entry is
        part-written; unmap it so the prompt can never hit stale
        cross-KV, and fail the request."""
        with self._cv:
            self._disagg_prompts.discard(prompt)
            self._disagg_out -= 1
            self._prefix.release(entry)
            self._prefix.invalidate(entry)
            self._cv.notify_all()
        req.reply.set_exception(exc)
        if req.trace is not None and req.trace.owner == "server":
            req.trace.finish(status="error", error=repr(exc))

    def _drain_disagg_inbox_locked(self):
        """Scheduler thread: land finished prefills. The worker
        filled the entry's cross-KV under ITS plan on ITS scope; copy
        the rows into THIS scope's pools (numpy round-trip — the next
        dispatch's in_shardings re-places them under the decode plan)
        and re-queue each request at the front with its entry ref
        held (the handoff) until the hit admission pins its own."""
        if not self._disagg_inbox:
            return
        drained = []
        while self._disagg_inbox:
            drained.append(self._disagg_inbox.popleft())
        for _req, entry, rows in drained:
            for name, row in rows.items():
                val = np.array(np.asarray(self.scope._get(name)))
                val[entry] = row
                self.scope._set(name, val)
            self._disagg_handoffs += 1
        for req, entry, _rows in reversed(drained):
            self._handoff[id(req)] = entry
            self._queue.appendleft(req)

    def _plan_admissions_locked(self, failures):
        admits = []
        self._admit_tier = None
        self._prefill_blocked = False
        if self._prefill_worker is not None:
            self._drain_disagg_inbox_locked()
        if self._chunked:
            self._maybe_start_prefill_locked(failures)
        if self._prefill_job is not None and self._chunk_turn:
            # the chunk's cycle: admit nothing so _background_feed
            # picks the phase program (live lanes' decode burst rides
            # in the same dispatch either way)
            self._chunk_turn = False
            return admits
        if not self._queue:
            return admits
        t_admit = time.monotonic()
        free_slots = [s for s in range(self.n_slots)
                      if self._lanes[s] is None]
        max_A = self._admit_buckets[-1]
        seen_cold = set()
        blocked_reason = None
        taken = []
        # ONE admission flavor per fused cycle (hit admissions are
        # encoder-free programs), decided by the QUEUE HEAD so its
        # request always ships first; the rest of the batch is filled
        # with same-tier requests scanned from deeper in the queue —
        # strictly consecutive admission would shrink batches to the
        # head's same-tier run length (~1/miss-rate) and make the
        # mixed hit/miss workload admission-bound (measured 0.35x of
        # the dense server before this scan)
        for pos, req in enumerate(self._queue):
            if pos >= self._ADMIT_SCAN_DEPTH or not free_slots \
                    or len(admits) >= max_A:
                break
            prompt = tuple(int(x) for x in req.src.reshape(-1))
            if self._prefill_inflight_locked(prompt):
                # the in-flight prefill REGISTERED this prompt
                # (acquire_fresh), so lookup says hit — but the entry
                # is still filling; defer until the handoff
                continue
            tier, _entry = self._prefix.lookup(prompt)
            sess = self._sessions.get(req.session) \
                if req.session is not None else None
            if (sess is not None and sess["hist"] is not None
                    and sess["entry"] is not None):
                # a retired-turn session: admit through the
                # encoder-free radix tier — shared block prefix
                # mapped read-only, divergent tail teacher-forced
                flavor = "radix"
            else:
                flavor = "hit" if tier == "hit" else "miss"
                if (flavor == "miss" and self._chunked
                        and req.session is None):
                    # cold plain prompts go through the chunk-job
                    # lane, never the stall-everyone monolithic
                    # prefill; shorts behind them admit this cycle
                    continue
                if (flavor == "hit" and req.session is None
                        and self._radix_ok and self._radix_reuse
                        and self._spec_k == 0
                        and not self._needs_seeds
                        and prompt in self._plain_hist):
                    # cross-request reuse without a session: an
                    # identical plain prompt replays its memoized
                    # deterministic generation teacher-forced over
                    # whatever chain the radix tree still holds
                    flavor = "radix"
            if self._admit_tier is None:
                self._admit_tier = flavor
            if flavor != self._admit_tier:
                continue  # next cycle's flavor
            if flavor == "miss" and prompt in seen_cold:
                # a duplicate cold prompt in one batch would alias
                # the pool entry write; it comes back a HIT next cycle
                continue
            # admission watermark (the vLLM can_allocate discipline):
            # after this admission, one spare block must remain per
            # ALREADY-live lane, or growth pressure turns into
            # preempt/re-admit thrash — preempted lockstep longs used
            # to steal their own freed blocks back at the next
            # admission and re-decode forever. Radix-cached
            # (tree-only) blocks are reclaimable capacity: evict
            # before declaring pressure.
            live_now = self.n_slots - len(free_slots)
            if self._blocks.free_count - 1 < live_now:
                self._radix.evict(
                    live_now + 1 - self._blocks.free_count)
            if self._blocks.free_count - 1 < live_now:
                blocked_reason = ("free KV blocks below the live-lane "
                                  "watermark")
                break
            if flavor == "radix":
                if sess is not None:
                    hist = list(sess["hist"])
                else:
                    # plain reuse: memoized retired generation (LRU
                    # touch); tier == "hit" was checked at the flavor
                    # upgrade, so acquire_hit below cannot miss
                    hist = list(self._plain_hist[prompt])
                    self._plain_hist.move_to_end(prompt)
                    self._plain_radix_admits += 1
                P = len(hist)
                # cap the shared prefix at (P-1)//BS full blocks:
                # resume = h*BS must leave >= 1 tick of history to
                # replay, and the FIRST device write then lands in
                # the fresh exclusive tail block — never in a shared
                # block (PTA192 green by construction)
                shared = self._radix.acquire(
                    prompt, hist,
                    max_blocks=(P - 1) // self._bs) \
                    if self._radix_reuse else []
                blk = self._alloc_block_locked()
                if blk is None:
                    self._radix.release(shared)
                    blocked_reason = "no free KV block"
                    break
                # the session's entry pin keeps the prompt resident,
                # so this is always a hit (encoder-free admission)
                entry = self._prefix.acquire_hit(prompt)
                h = len(shared)
                slot = free_slots.pop(0)
                taken.append(req)
                self._lane_shared[slot] = shared
                self._lane_blocks[slot] = [blk]
                self._lane_entry[slot] = entry
                self._lane_sess[slot] = req.session
                self._lane_step[slot] = h * self._bs
                self._tab[slot, :] = 0
                for j, b in enumerate(shared):
                    self._tab[slot, j] = b
                self._tab[slot, h] = blk
                self._pref[slot] = entry
                self._lanes[slot] = req
                self._note_admit_locked(req, slot, t_admit, flavor)
                req.radix = (hist, h * self._bs, P)
                self._radix_admits += 1
                self._hit_depth.observe(float(h))
                if req.trace is not None:
                    # blocks_reused is the radix win (KV pages NOT
                    # recomputed); none is ever copied on this path —
                    # serving admissions never write a shared block
                    # (COW lives in PagedBeamDecoder)
                    req.trace.add_span(
                        "slotpool.queue", req.t_arrival, t_admit,
                        slot=slot, prefix="radix", blocks_reused=h)
                admits.append((slot, req))
                continue
            blk = self._alloc_block_locked()
            if blk is None:
                blocked_reason = "no free KV block"
                break
            if flavor == "hit":
                entry = self._prefix.acquire_hit(prompt)
            else:
                entry = self._prefix.acquire_fresh(
                    prompt, partial=(tier == "partial"))
                if entry is None:
                    self._blocks.free([blk])
                    blocked_reason = "every prompt entry is pinned"
                    break
                seen_cold.add(prompt)
            slot = free_slots.pop(0)
            taken.append(req)
            self._lane_shared[slot] = []
            self._lane_blocks[slot] = [blk]
            self._lane_entry[slot] = entry
            self._lane_sess[slot] = req.session
            self._lane_step[slot] = 0
            self._tab[slot, :] = 0
            self._tab[slot, 0] = blk
            self._pref[slot] = entry
            self._lanes[slot] = req
            self._note_admit_locked(req, slot, t_admit, flavor)
            if req.trace is not None:
                # the prefix tier is what explains slow (miss: full
                # encoder prefill) vs fast (hit: lane reset only)
                # admissions in the flight recorder
                req.trace.add_span("slotpool.queue", req.t_arrival,
                                   t_admit, slot=slot, prefix=tier)
            admits.append((slot, req))
        if taken:
            taken_ids = {id(r) for r in taken}
            self._queue = collections.deque(
                r for r in self._queue if id(r) not in taken_ids)
            for r in taken:
                e = self._handoff.pop(id(r), None)
                if e is not None:
                    # the chunk job held the filled entry resident
                    # until this admission took its own ref
                    self._prefix.release(e)
        if admits and self._prefill_job is not None:
            self._chunk_turn = True  # next cycle belongs to the chunk
        if blocked_reason is None and self._prefill_blocked:
            # the chunk/worker path could not even START a prefill
            # (every entry pinned); same exhaustion discipline below
            blocked_reason = "every prompt entry is pinned"
        if blocked_reason and not admits \
                and self._prefill_job is None \
                and self._disagg_out == 0 \
                and not self._disagg_inbox \
                and all(l is None for l in self._lanes):
            # nothing in flight can ever free a block/entry: fail the
            # head with the NAMED retryable error instead of hanging
            req = self._queue.popleft()
            e = self._handoff.pop(id(req), None)
            if e is not None:
                self._prefix.release(e)
            failures.append((req, BlockPoolExhausted(
                f"cannot admit prompt: {blocked_reason} with the pool "
                f"otherwise idle (n_blocks={self._blocks.n_blocks}, "
                f"n_prompt_entries={self._prefix.n_entries}); "
                f"retryable against a larger pool")))
        return admits

    def _admission_feed(self, admits):
        tier = self._admit_tier
        A = _bucket_for(len(admits), self._admit_buckets,
                        "admission batch")
        feed = {"slots": np.array(
            [slot for slot, _ in admits]
            + [self.bundle.dustbin] * (A - len(admits)), np.int64)}
        if tier == "radix":
            # teacher-forced resume: the lane replays its retained
            # history from resume = h*BS (the first position past the
            # shared prefix) and flips to real decode at step P-1 —
            # padded rows (dustbin) feed zero rows harmlessly
            maxT = self.bundle.max_out_len
            hist = np.zeros((A, maxT), np.int64)
            resume = np.zeros((A,), np.int64)
            until = np.zeros((A,), np.int64)
            for i, (_slot, req) in enumerate(admits):
                htoks, r, n = req.radix
                hist[i, :n] = htoks
                resume[i] = r
                until[i] = n
            feed["hist_toks"] = hist
            feed["resume_steps"] = resume
            feed["prefill_until"] = until
            if self._needs_seeds:
                feed["seeds"] = np.array(
                    [req.seed for _, req in admits]
                    + [0] * (A - len(admits)), np.int64)
            return (tier, A), feed
        if tier == "miss" or self._spec_k > 0:
            # spec bundles feed src_ids on HITs too: the hit program
            # skips only the TARGET encoder — the (tiny) draft
            # encoder re-runs per lane (decode_engine._draft_admit)
            feed["src_ids"] = np.concatenate(
                [req.src for _, req in admits]
                + [admits[-1][1].src] * (A - len(admits)), axis=0)
        if tier == "miss":
            # padded rows scatter into the dustbin ENTRY (index E):
            # duplicates there sum to garbage harmlessly, real
            # entries stay host-distinct (PTA110 "host_indices")
            feed["prompt_slots"] = np.array(
                [self._lane_entry[slot] for slot, _ in admits]
                + [self.cache.n_prompt_entries] * (A - len(admits)),
                np.int64)
        if self._needs_seeds:
            feed["seeds"] = np.array(
                [req.seed for _, req in admits]
                + [0] * (A - len(admits)), np.int64)
        return (tier, A), feed

    # --- chunked prefill: the background job -------------------------
    def _background_feed(self):
        job = self._prefill_job
        if job is None:
            return None
        C = self.cache.chunk_tokens
        key = self._chunk_keys[job["phase"]]
        feed = {"chunk_entry": np.array([job["entry"]], np.int64),
                "chunk_pos": np.array([job["ci"] * C], np.int64)}
        if key[1] == 0:
            # the embed phase is the only one that sees tokens; the
            # ragged last chunk zero-pads (its one-hot rows select
            # nothing past seq_len, so the pad never lands)
            toks = np.zeros((1, C), np.int64)
            seg = np.asarray(job["req"].src).reshape(-1)[
                job["ci"] * C: job["ci"] * C + C]
            toks[0, :len(seg)] = seg
            feed["chunk_toks"] = toks
        self._bg_ticked = True
        return key, feed

    def _advance_prefill(self):
        """One chunk phase dispatched successfully: walk the cursor
        phase-major (every chunk of phase p before phase p+1 — the
        bidirectional encoder's layer l+1 reads ALL of layer l). On
        the final phase the entry holds the complete cross-KV: the
        request re-queues at the FRONT and re-admits encoder-free as
        a prefix HIT, with the job's entry ref held (the handoff)
        until that admission pins its own."""
        with self._cv:
            self._chunk_ticks_host += 1
            job = self._prefill_job
            job["ci"] += 1
            if job["ci"] < self._n_chunks:
                return
            job["ci"] = 0
            job["phase"] += 1
            if job["phase"] < len(self._chunk_keys):
                return
            req = job["req"]
            self._handoff[id(req)] = job["entry"]
            self._prefill_job = None
            self._chunk_turn = False
            self._queue.appendleft(req)
            self._cv.notify_all()

    def _background_abort_locked(self):
        job = self._prefill_job
        if job is None:
            return None
        self._prefill_job = None
        self._chunk_turn = False
        # the entry is PART-written: unmap it so the prompt can never
        # again be looked up as a hit against stale cross-KV
        self._prefix.release(job["entry"])
        self._prefix.invalidate(job["entry"])
        return job["req"]

    def _flush_requests_locked(self, pending):
        while self._disagg_inbox:
            # finished handoffs the scheduler never landed: the
            # entry content is complete but the server is closing —
            # drop the job's ref and fail the request with the rest
            req, entry, _rows = self._disagg_inbox.popleft()
            self._prefix.release(entry)
            pending.append(req)
        for r in pending:
            e = self._handoff.pop(id(r), None)
            if e is not None:
                self._prefix.release(e)

    def _drop_queued_locked(self, req):
        """PTA201 ``cancel`` release site (queue-held refs): a shed
        request that came back through a disaggregated handoff still
        holds the filled entry resident — drop that ref."""
        e = self._handoff.pop(id(req), None)
        if e is not None:
            self._prefix.release(e)

    def _shed_cancelled_locked(self, now: float):
        out = super()._shed_cancelled_locked(now)
        job = self._prefill_job
        if job is not None:
            reason = self._expired_locked(job["req"], now)
            if reason is not None:
                # a part-written chunk job: abort releases AND
                # invalidates the entry (same as a mid-chunk error),
                # so the prompt can never hit stale cross-KV
                req = self._background_abort_locked()
                req.finalized = True
                self._count_cancel_locked(reason)
                out.append((req, reason))
        return out

    # --- burst planning: coverage, pausing, hard exhaustion ----------
    def _grow_blocks_locked(self, slot, upto_pos):
        need = upto_pos // self._bs + 1
        # the lane's table = read-only shared radix prefix (never
        # grown, never written) + the exclusive writable tail
        base = len(self._lane_shared[slot])
        blocks = self._lane_blocks[slot]
        while base + len(blocks) < need:
            b = self._alloc_block_locked()
            if b is None:
                return
            self._tab[slot, base + len(blocks)] = b
            blocks.append(b)

    def _free_lane_locked(self, slot):
        if self._lane_shared[slot]:
            # the lane's refs on the shared radix prefix (the tree
            # keeps its own ref per node — blocks stay cached)
            self._radix.release(self._lane_shared[slot])
            self._lane_shared[slot] = []
        if self._lane_blocks[slot]:
            # radix-aware free: decref from refcount 1 IS the strict
            # free; a block the tree adopted at session harvest
            # (refcount 2) survives tree-owned. Reverse order so a
            # freed block never outlives a deeper one that depends
            # on it.
            for b in reversed(self._lane_blocks[slot]):
                self._blocks.decref(b)
            self._lane_blocks[slot] = []
        if self._lane_entry[slot] is not None:
            self._prefix.release(self._lane_entry[slot])
            self._lane_entry[slot] = None
        self._lane_sess[slot] = None
        self._paused.discard(slot)

    def _plan_burst_locked(self, admits, drain, failures):
        n_steps, min_active, run = super()._plan_burst_locked(
            admits, drain, failures)
        if not run:
            if self._prefill_job is not None:
                # chunk-only dispatch: the phase body runs in the
                # pre-While prologue; the decode While exits at once
                # (no live lanes)
                return 0, 0, True
            return n_steps, min_active, run
        maxT = self.bundle.max_out_len
        tpt = self._toks_per_tick
        while True:
            live = [s for s in range(self.n_slots)
                    if self._lanes[s] is not None]
            if not live:
                self._paused = set()
                break
            k = n_steps
            blocked = []
            for s in live:
                st = int(self._lane_step[s])
                # a K-tick burst writes KV at positions st..st+K*tpt-1
                # (under draft-and-verify every tick VERIFIES tpt =
                # k+1 positions even when fewer are accepted, so
                # coverage must be sized by the worst case or a
                # rejected-run verify would scatter through
                # unallocated table rows into other lanes' blocks)
                self._grow_blocks_locked(
                    s, min(st + n_steps * tpt - 1, maxT - 1))
                covered = (len(self._lane_shared[s])
                           + len(self._lane_blocks[s])) * self._bs
                if covered >= maxT:
                    # whole buffer covered: writes can never leave
                    # the lane's blocks (the verify gate masks
                    # positions past maxT-1), so coverage does not
                    # bound this lane's ticks at all — without this,
                    # a lane with < tpt positions LEFT counted as
                    # blocked and a lone nearly-done request died
                    # BlockPoolExhausted owning every block it needs
                    coverable = n_steps
                else:
                    coverable = (covered - st) // tpt
                if coverable <= 0:
                    blocked.append(s)
                else:
                    k = min(k, coverable)
            if blocked and len(blocked) == len(live):
                # hard exhaustion: every live lane sits at a block
                # boundary with an empty free list (lockstep long
                # generations do this the moment admission packs
                # them). Radix-aware preemption, two rungs:
                #
                # 1. CACHE before WORK — bulk-evict refcount-1 radix
                #    leaves and re-plan. Per-alloc growth already
                #    evicts one leaf per miss, so this usually finds
                #    nothing on the first pass; it fires on LATER
                #    passes, when a preempted lane's released shared
                #    refs just turned tree nodes back to refcount 1
                #    (cheaper to drop that cache than preempt again).
                if self._radix.evict(len(blocked)):
                    continue
                # 2. Preempt the lane that loses the LEAST work:
                #    deepest shared radix prefix first (its
                #    re-admission replays from resume = h*BS, so only
                #    the exclusive tail is recomputed), youngest
                #    t_admit as the tiebreak (the r13 discipline —
                #    and the exact old behavior for plain lanes,
                #    where every shared depth is 0). PREEMPT by
                #    recompute: free its blocks so the older lanes
                #    advance, re-queue the request at the FRONT —
                #    greedy decode is deterministic, so the
                #    re-decoded tokens are byte-identical and only
                #    work is lost, never a request. Each preemption
                #    hands >= 1 block to a surviving lane, so total
                #    outstanding work decreases and the loop
                #    terminates.
                victim = max(blocked,
                             key=lambda s: (len(self._lane_shared[s]),
                                            self._lanes[s].t_admit
                                            or 0))
                req = self._lanes[victim]
                if len(live) == 1:
                    # a LONE lane owns every in-use block and still
                    # cannot advance: re-running it can never do
                    # better — the named retryable error, not a
                    # preempt-forever loop
                    self._free_lane_locked(victim)
                    self._lanes[victim] = None
                    failures.append((req, BlockPoolExhausted(
                        f"KV block pool exhausted mid-generation "
                        f"(n_blocks={self._blocks.n_blocks}, the "
                        f"request alone outgrows the pool); request "
                        f"evicted — retryable against a larger "
                        f"pool")))
                    continue
                self._free_lane_locked(victim)
                self._lanes[victim] = None
                self._preemptions += 1
                req.t_admit = None
                req.t_first = None
                self._queue.appendleft(req)
                continue
            self._pause_events += len(set(blocked) - self._paused)
            self._paused = set(blocked)
            n_steps = k
            break
        if self.exit_on_retire and not drain:
            live_unpaused = sum(
                1 for s in range(self.n_slots)
                if self._lanes[s] is not None
                and s not in self._paused)
            min_active = max(0, live_unpaused - 1)
        # devtel: pool high-water marks AFTER this cycle's admissions
        # and block growth (under _cv like every planning mutation)
        self._blocks_hwm = max(self._blocks_hwm, self._blocks.in_use)
        self._entries_hwm = max(self._entries_hwm, self._prefix.in_use)
        return n_steps, min_active, True

    def _pre_dispatch(self):
        """The host-owned indirection + the pause/victim mask, as feeds
        of the fused dispatch: the whole host->device channel beside
        the admission feeds (the executable takes host feeds up itself
        inside the call, so nothing is set into the scope and nothing
        is placed from Python; the program copies the mask into its
        `active` state, which it goes on to write)."""
        act = np.zeros((self.n_slots + 1,), np.int64)
        for s in range(self.n_slots):
            if self._lanes[s] is not None and s not in self._paused:
                act[s] = 1
        # paused lanes MUST read 0 (an act-gated pool write is the
        # exclusivity contract); retired/victim/idle lanes likewise;
        # freshly admitted lanes are raised by the admission body
        # inside the same dispatch either way
        self._harvest_ok = False  # until this dispatch's outs land
        return {fed_name("block_tab"): self._tab.copy(),
                fed_name("prompt_ref"): self._pref.copy(),
                fed_name("active"): act}

    def _post_dispatch(self, outs):
        self._lane_step = np.asarray(outs[1]).astype(np.int64).copy()
        # session harvest source: the retire sweep adopts the full
        # blocks behind each finished session turn into the radix
        # tree and retains its history for the next turn
        self._last_tok = np.asarray(outs[0])
        self._harvest_ok = True
        if self._bg_ticked:
            self._bg_ticked = False
            self._advance_prefill()

    def _release_lane(self, slot, req):
        # `slotpool.retire.tree`: what a lane's end costs in the radix
        # tree and the pools (the harvest's inserts, the releases)
        with obs_tracing.span("slotpool.retire.tree"):
            sid = self._lane_sess[slot]
            if sid is not None and req.harvest and self._harvest_ok:
                self._harvest_session_locked(slot, sid)
            elif (sid is None and req.harvest and self._harvest_ok
                    and self._radix_ok and self._radix_reuse
                    and self._spec_k == 0 and not self._needs_seeds):
                self._harvest_plain_locked(slot, req)
            self._free_lane_locked(slot)

    def _harvest_session_locked(self, slot, sid):
        """Adopt a retiring session turn into the radix tree: the
        FULL blocks behind its decoded tokens become tree nodes (one
        tree ref each — 'existing node wins' makes replayed chunks
        idempotent), and the history (terminator excluded, so the
        next turn can extend past it) is retained for the session's
        next radix admission."""
        sess = self._sessions.get(sid)
        if sess is None:
            return  # closed mid-flight: nothing to extend
        row = np.asarray(self._last_tok[slot]).reshape(-1)
        if self._end_id is None:
            e = row.shape[0] - 1
        else:
            hit = row[1:] == self._end_id
            e = int(hit.argmax()) + 1 if hit.any() \
                else row.shape[0] - 1
        hist = [int(t) for t in row[:e]]
        # KV positions 0..e-1 are valid => e // BS FULL blocks; the
        # lane's chain (shared prefix + exclusive tail) covers them
        f = e // self._bs
        if f and self._radix_reuse:
            chain = (list(self._lane_shared[slot])
                     + list(self._lane_blocks[slot]))
            self._radix.insert(sess["prompt"], hist, chain[:f])
        sess["hist"] = hist
        sess["turns"] += 1
        if sess["entry"] is None:
            # pin the cross-KV entry for the session's lifetime by
            # TRANSFERRING the lane's ref (the lane free below must
            # not release it) — later turns admit as guaranteed hits
            sess["entry"] = self._lane_entry[slot]
            self._lane_entry[slot] = None

    def _harvest_plain_locked(self, slot, req):
        """Sessionless analogue of the session harvest: a retired
        plain GREEDY generation's full blocks join the radix tree
        keyed by its prompt, and the history is memoized (bounded
        LRU) so an identical later submit re-admits through the
        encoder-free radix tier — teacher-forced replay of its own
        deterministic output, byte-identical by construction. The
        entry ref is NOT transferred (no session pins it); the entry
        stays cached LRU in the prefix cache like any retired miss."""
        row = np.asarray(self._last_tok[slot]).reshape(-1)
        if self._end_id is None:
            e = row.shape[0] - 1
        else:
            hit = row[1:] == self._end_id
            e = int(hit.argmax()) + 1 if hit.any() \
                else row.shape[0] - 1
        hist = [int(t) for t in row[:e]]
        prompt = tuple(int(x) for x in req.src.reshape(-1))
        f = e // self._bs
        if f:
            chain = (list(self._lane_shared[slot])
                     + list(self._lane_blocks[slot]))
            self._radix.insert(prompt, hist, chain[:f])
        self._plain_hist.pop(prompt, None)
        self._plain_hist[prompt] = hist
        while len(self._plain_hist) > self._plain_hist_cap:
            self._plain_hist.popitem(last=False)

    # --- observability ------------------------------------------------
    def pool_stats(self) -> dict:
        """Block-pool + prefix-cache counters (also exposed as the
        paddle_tpu_blockpool_* pull-provider gauges)."""
        with self._cv:
            return self._pool_stats_locked()

    def _pool_stats_locked(self) -> dict:
        return {
            "layout": "paged",
            "block_size": self._bs,
            "n_blocks": self._blocks.n_blocks,
            "blocks_in_use": self._blocks.in_use,
            "blocks_free": self._blocks.free_count,
            "prompt_entries": self._prefix.n_entries,
            "prompt_entries_in_use": self._prefix.in_use,
            "prefix_hits": self._prefix.hits,
            "prefix_misses": self._prefix.misses,
            # partial-tier admissions re-prefill (bidirectional
            # encoder: only a FULL prompt match may share) — each is
            # a copy-on-write materialization of a shared prefix
            "cow_copies": self._prefix.partials,
            "evictions": self._prefix.evictions,
            "paused_lanes": len(self._paused),
            "pause_events": self._pause_events,
            "preemptions": self._preemptions,
            # radix block-prefix reuse (decoded-token self-KV chains)
            "shared_blocks": len(self._blocks.shared_blocks()),
            "radix_nodes": self._radix.n_nodes,
            "radix_hit_blocks": self._radix.hit_blocks,
            "radix_inserts": self._radix.inserts,
            "radix_adoptions": self._radix.adoptions,
            "radix_evicted_blocks": self._radix.evicted_blocks,
            # leaves examined over blocks evicted is what an eviction
            # costs: about 1, plus the leaves live lanes pin
            "radix_evict_calls": self._radix.evict_calls,
            "radix_evict_candidates": self._radix.evict_candidates,
            "radix_admissions": self._radix_admits,
            "plain_radix_admissions": self._plain_radix_admits,
            "sessions_open": len(self._sessions),
            # chunked prefill (host view; device tel_chunks agrees)
            "chunked_prefill": self._chunked,
            "chunk_jobs": self._chunk_jobs,
            "chunk_ticks": self._chunk_ticks_host,
            # disaggregated prefill (DistServe-style phase split)
            "disaggregated": self._prefill_worker is not None,
            "disagg_outstanding": self._disagg_out,
            "disagg_handoffs": self._disagg_handoffs,
        }

    def _host_tel_locked(self, reset: bool) -> dict:
        """Paged host supplement: window-scoped pool high-water marks
        and pause/preempt counts (pool_stats() keeps the LIFETIME
        views of the latter). Called under _cv from stats()."""
        out = {
            "blocks_hwm": self._blocks_hwm,
            "prompt_entries_hwm": self._entries_hwm,
            "pause_events": self._pause_events - self._pause_base,
            "preemptions": self._preemptions - self._preempt_base,
        }
        if reset:
            # hwm re-bases to CURRENT residency (not zero): the next
            # window's mark must not under-report lanes already live
            self._blocks_hwm = self._blocks.in_use
            self._entries_hwm = self._prefix.in_use
            self._pause_base = self._pause_events
            self._preempt_base = self._preemptions
        return out

    def stats(self, reset: bool = False) -> dict:
        st = super().stats(reset=reset)
        st["block_pool"] = self.pool_stats()
        return st

    def _metrics_samples(self):
        samples = super()._metrics_samples()
        lab = {"server": self._obs_id}  # unique per instance: two
        # co-resident paged servers must not collide series
        host_tel = {
            "blocks_hwm": self._blocks_hwm,
            "prompt_entries_hwm": self._entries_hwm,
            "pause_events": self._pause_events,
            "preemptions": self._preemptions,
        }
        samples += [(c.metric, lab, host_tel[c.stat])
                    for c in obs_devtel.HOST_COUNTERS]
        b, p = self._blocks, self._prefix
        samples += [
            ("paddle_tpu_blockpool_blocks_in_use", lab, b.in_use),
            ("paddle_tpu_blockpool_blocks_free", lab, b.free_count),
            ("paddle_tpu_blockpool_prompt_entries_in_use", lab,
             p.in_use),
            ("paddle_tpu_blockpool_prefix_hits_total", lab, p.hits),
            ("paddle_tpu_blockpool_prefix_misses_total", lab,
             p.misses),
            ("paddle_tpu_blockpool_cow_copies_total", lab,
             p.partials),
            ("paddle_tpu_blockpool_evictions_total", lab,
             p.evictions),
            # radix reuse gauges: shared (refcount>1) residency, tree
            # size, and the hit-depth histogram — together they say
            # how much KV the pool holds ONCE for many readers
            ("paddle_tpu_blockpool_shared_blocks", lab,
             len(b.shared_blocks())),
            ("paddle_tpu_blockpool_radix_nodes", lab,
             self._radix.n_nodes),
            ("paddle_tpu_blockpool_radix_hit_blocks_total", lab,
             self._radix.hit_blocks),
            ("paddle_tpu_blockpool_radix_evicted_blocks_total", lab,
             self._radix.evicted_blocks),
            ("paddle_tpu_blockpool_radix_evict_calls_total", lab,
             self._radix.evict_calls),
            ("paddle_tpu_blockpool_radix_evict_candidates_total", lab,
             self._radix.evict_candidates),
            ("paddle_tpu_blockpool_radix_admissions_total", lab,
             self._radix_admits),
            ("paddle_tpu_blockpool_sessions_open", lab,
             len(self._sessions)),
            ("paddle_tpu_blockpool_prefix_hit_depth", lab,
             self._hit_depth),
        ]
        return samples


class DisaggregatedPrefillWorker:
    """The PREFILL half of disaggregated serving (DistServe, Zhong
    et al. OSDI'24 — PAPERS.md): a dedicated dispatcher for the
    bundle's ``("chunked", p)`` phase programs on its OWN scope —
    and, via ``models.decode_engine.apply_phase_sharding`` +
    ``runtime.placement.place_disaggregated_bundle``, its own device
    slice under its own ShardingPlan (MXU-bound: tp over the encoder
    projections) while the decode server's plan shards KV bytes.

    The decode server owns the host allocators (HostBlockPool /
    PromptPrefixCache): it acquires the cross-KV entry and routes
    cold prompts here (``prefill_worker=``); this worker runs every
    chunk phase back-to-back with ``n_steps=0`` (each phase program
    embeds the decode While, which exits immediately — the slot
    state in this scope is dead weight XLA never reads), then reads
    the finished entry's cross-KV rows off its scope and hands them
    to the completion callback. The decode scheduler lands the rows
    in ITS scope and re-admits the request encoder-free.

    Construction order: build the bundle chunked; for the sharded
    mode run ``apply_phase_sharding``, train/load params +
    ``init_slot_state`` into the decode scope, then
    ``place_disaggregated_bundle(bundle, decode_scope,
    prefill_scope)`` (binds both plans, syncs params across), THEN
    this worker, THEN the server with ``prefill_worker=``. The
    unsharded two-scope mode skips the plans and passes
    ``params_from=decode_scope`` here instead.

    Reference counterpart: reference
    inference/api/analysis_predictor.cc:832 — a second predictor
    process specialized to one phase of the request; here it is a
    thread over a second scope with phase-specialized programs."""

    def __init__(self, bundle, executor=None, scope=None,
                 params_from=None, start: bool = True):
        from ..models.decode_engine import _state_prefix_of

        cache = getattr(bundle, "cache", None)
        if cache is None or cache.layout != "paged" \
                or not cache.chunked:
            raise ValueError(
                "DisaggregatedPrefillWorker needs a paged bundle "
                "built with CacheConfig(chunk_tokens=C) — the phase "
                "split IS the chunk-program set")
        self.bundle = bundle
        self.executor = executor or Executor(TPUPlace(0))
        self.scope = scope or Scope()
        if params_from is not None:
            for name in list(params_from._vars):
                if self.scope._get(name) is None:
                    val = params_from._get(name)
                    if val is not None:
                        self.scope._set(name,
                                        np.array(np.asarray(val)))
        bundle.init_slot_state(self.scope)
        self._chunk_keys = sorted(
            (k for k in bundle.serves
             if isinstance(k, tuple) and k[0] == "chunked"),
            key=lambda kv: kv[1])
        self._n_chunks = cache.n_chunks(bundle.seq_len)
        prefix = _state_prefix_of(bundle)
        pat = re.compile(
            re.escape(prefix) + r"cross_[kv]\d+"
            + re.escape(dec_POOL_MARK))
        self._cross_names = sorted(
            n for n in bundle._state_specs if pat.fullmatch(n))
        before = self.executor.compile_count
        fetches = [bundle.state["step"]]
        self._serves = {
            k: self.executor.prepare(
                bundle.serves[k], feed=bundle.serve_feed_spec(k),
                fetch_list=fetches, scope=self.scope)
            for k in self._chunk_keys}
        self._warmed_compiles = self.executor.compile_count - before
        self._cv = threading.Condition()
        self._jobs: "collections.deque" = collections.deque()
        self._running = False
        self._closed = False
        self._busy = False
        self._jobs_done = 0
        self._jobs_failed = 0
        self._ticks = 0
        self._thread: Optional[threading.Thread] = None
        if start:
            self.start()

    # --- lifecycle ---------------------------------------------------
    def start(self):
        with self._cv:
            if self._running:
                return
            if self._closed:
                raise ServerClosed(
                    "DisaggregatedPrefillWorker closed")
            self._running = True
            self._thread = threading.Thread(
                target=self._loop, name="paddle-tpu-prefill-worker",
                daemon=True)
            self._thread.start()

    def drain(self, timeout: Optional[float] = 60.0) -> bool:
        deadline = None if timeout is None \
            else time.monotonic() + timeout
        with self._cv:
            while self._running and (self._jobs or self._busy):
                if deadline is None:
                    self._cv.wait()
                    continue
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(left)
            return not (self._jobs or self._busy)

    def close(self, timeout: float = 5.0):
        with self._cv:
            self._running = False
            self._closed = True
            dropped = list(self._jobs)
            self._jobs.clear()
            self._cv.notify_all()
        for req, prompt, entry, _done, fail in dropped:
            fail(req, prompt, entry, ServerClosed(
                "DisaggregatedPrefillWorker closed"))
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # --- the job surface the decode server routes to -----------------
    def submit_job(self, req, prompt, entry, on_done, on_fail):
        """Queue one prefill job. ``on_done(req, prompt, entry,
        rows)`` / ``on_fail(req, prompt, entry, exc)`` fire on the
        WORKER thread (never under this worker's lock) — ``rows``
        maps each cross-pool state name to the entry's finished
        [S, H*Dh] rows, copied off this scope."""
        with self._cv:
            if not self._running or self._closed:
                raise ServerClosed(
                    "DisaggregatedPrefillWorker closed")
            self._jobs.append((req, prompt, entry, on_done, on_fail))
            self._cv.notify_all()

    def _loop(self):
        while True:
            with self._cv:
                while self._running and not self._jobs:
                    self._cv.wait()
                if not self._running:
                    return
                job = self._jobs.popleft()
                self._busy = True
            req, prompt, entry, on_done, on_fail = job
            try:
                rows = self._run_job(req, entry)
            except BaseException as e:
                with self._cv:
                    self._busy = False
                    self._jobs_failed += 1
                    self._cv.notify_all()
                on_fail(req, prompt, entry, e)
            else:
                with self._cv:
                    self._busy = False
                    self._jobs_done += 1
                    self._cv.notify_all()
                on_done(req, prompt, entry, rows)

    def _run_job(self, req, entry):
        """Phase-major chunk walk (every chunk of phase p before
        phase p+1 — the bidirectional encoder's layer l+1 reads ALL
        of layer l), one dispatch per (phase, chunk); identical
        cursor order to the decode server's local chunk-job mode, so
        the entry content is bit-identical to it."""
        C = self.bundle.cache.chunk_tokens
        src = np.asarray(req.src).reshape(-1)
        # no lane decodes on this scope: the tables every serve
        # program is fed say so
        idle = self.bundle.idle_table_feed()
        for key in self._chunk_keys:
            for ci in range(self._n_chunks):
                feed = {"n_steps": np.array([0], np.int64),
                        "min_active": np.array([0], np.int64),
                        "chunk_entry": np.array([entry], np.int64),
                        "chunk_pos": np.array([ci * C], np.int64),
                        **idle}
                if key[1] == 0:
                    toks = np.zeros((1, C), np.int64)
                    seg = src[ci * C: ci * C + C]
                    toks[0, :len(seg)] = seg
                    feed["chunk_toks"] = toks
                self._serves[key].run(feed, return_numpy=False)
                with self._cv:
                    self._ticks += 1
        return {name:
                np.array(np.asarray(self.scope._get(name))[entry])
                for name in self._cross_names}

    def stats(self, reset: bool = False) -> dict:
        with self._cv:
            return {
                "jobs_done": self._jobs_done,
                "jobs_failed": self._jobs_failed,
                "jobs_queued": len(self._jobs),
                "chunk_ticks": self._ticks,
                "warmed_compiles": self._warmed_compiles,
            }


class PagedBeamDecoder:
    """Beam search where beam branching IS copy-on-write block
    branching (reference counterpart: the whole-loop
    models/decode_engine.build_beam_decode_program, itself mirroring
    reference tests/unittests/dist_transformer.py:1523 beam_search —
    which holds ``beam_size`` FULL dense histories and re-decodes
    them every step; here each shared hypothesis prefix is stored
    ONCE in the paged pool).

    Drives the bundle's PROBE program — one device tick that runs
    the cached decoder over every lane and publishes the full
    next-token distribution (``probe_probs``), with teacher forcing
    pinned to ``prefill_until = max_out_len`` so the device never
    emits a token or latches a lane: the HOST owns tokens, scores,
    block tables, and the refcount typestate. Per expansion step:

    * a child hypothesis shares its parent's FULL blocks read-only
      (``incref`` — exclusive→shared is the branch point);
    * the parent's PARTIAL tail block is copied through the bundle's
      COW program into a fresh exclusive block per diverging child —
      the ONLY write path into branched state (checker PTA192's
      copy-before-write contract, held here by host construction);
    * a parent with a single heir hands its tail over exclusively —
      zero copies on a non-branching step (beam_size=1 degenerates
      to greedy with no COW at all).

    Expansion math mirrors ops/decode_ops.beam_search exactly
    (2*beam candidates, accumulated log-probs, EOS freezing,
    per-batch top-k with lower-index tie preference), so decoded
    tokens are token-exact against the whole-loop reference on a
    trained model.

    Owns the bundle's scope state between calls — do not serve the
    same bundle/scope from a ContinuousGenerationServer concurrently.
    """

    def __init__(self, bundle, beam_size, executor=None, scope=None):
        cache = getattr(bundle, "cache", None)
        if cache is None or cache.layout != "paged" \
                or getattr(bundle, "probe", None) is None:
            raise ValueError(
                "PagedBeamDecoder needs a paged, non-speculative "
                "bundle (its probe + cow programs); build with "
                "CacheConfig(layout='paged') and no DraftConfig")
        if not 1 <= int(beam_size) <= bundle.n_slots:
            raise ValueError(
                f"beam_size {beam_size} must fit the bundle's "
                f"{bundle.n_slots} lanes")
        self.bundle = bundle
        self.beam = int(beam_size)
        self.executor = executor or Executor(TPUPlace(0))
        self.scope = scope or global_scope()
        self.cache = cache
        self._bs = cache.block_size
        self._pool = HostBlockPool(cache.n_blocks)
        bundle.init_slot_state(self.scope)
        st = bundle.state
        self._st = st
        self._rows = bundle.n_slots + 1
        self._probe = self.executor.prepare(
            bundle.probe, feed=[],
            fetch_list=[st["probe_probs"], st["step"]],
            scope=self.scope)
        self._cow = self.executor.prepare(
            bundle.cow, feed=bundle.cow_feed_spec(),
            fetch_list=[st["step"]], scope=self.scope)
        # prompt admission reuses the fused serve programs at
        # n_steps=0 (prefill + lane reset, zero decode ticks): beam 0
        # prefills the cross-KV entry (miss), beams 1.. reset as hits
        buckets = sorted({k[1] for k in bundle.serves
                          if isinstance(k, tuple)})
        mk = ("miss", _bucket_for(1, buckets, "beam admission"))
        self._miss = self.executor.prepare(
            bundle.serves[mk], feed=bundle.serve_feed_spec(mk),
            fetch_list=[st["step"]], scope=self.scope)
        self._miss_A = mk[1]
        self._hit = None
        if self.beam > 1:
            hk = ("hit", _bucket_for(self.beam - 1, buckets,
                                     "beam fan-out"))
            self._hit = self.executor.prepare(
                bundle.serves[hk], feed=bundle.serve_feed_spec(hk),
                fetch_list=[st["step"]], scope=self.scope)
            self._hit_A = hk[1]
        # observability (pool_stats-shaped; blocks_cowed is the
        # satellite the admission spans of the radix server pin at 0)
        self.cow_blocks = 0
        self.shared_block_peak = 0

    def _alloc(self):
        b = self._pool.alloc()
        if b is None:
            raise BlockPoolExhausted(
                f"beam branching exhausted the KV block pool "
                f"(n_blocks={self._pool.n_blocks}, beam="
                f"{self.beam}); retryable against a larger pool")
        return b

    def _admit(self, arr, tab, pref):
        st, scope = self._st, self.scope
        # the probe and COW programs read the tables as scope state;
        # the serve programs that admit are fed them (no tick runs
        # here, so the mask they are fed is all down)
        scope._set(st["block_tab"], tab.copy())
        scope._set(st["prompt_ref"], pref.copy())
        zero = np.array([0], np.int64)
        tables = {**self.bundle.idle_table_feed(),
                  fed_name("block_tab"): tab.copy(),
                  fed_name("prompt_ref"): pref.copy()}
        A = self._miss_A
        feed = {"src_ids": np.repeat(arr, A, axis=0),
                "slots": np.full((A,), self.bundle.dustbin, np.int64),
                "prompt_slots": np.full(
                    (A,), self.cache.n_prompt_entries, np.int64),
                "n_steps": zero, "min_active": zero, **tables}
        feed["slots"][0] = 0
        feed["prompt_slots"][0] = 0
        if getattr(self.bundle, "needs_seeds", False):
            feed["seeds"] = np.zeros((A,), np.int64)
        self._miss.run(feed, return_numpy=True)
        if self._hit is not None:
            A = self._hit_A
            slots = np.full((A,), self.bundle.dustbin, np.int64)
            slots[:self.beam - 1] = np.arange(1, self.beam)
            feed = {"slots": slots, "n_steps": zero,
                    "min_active": zero, **tables}
            if getattr(self.bundle, "needs_seeds", False):
                feed["seeds"] = np.zeros((A,), np.int64)
            self._hit.run(feed, return_numpy=True)

    def decode(self, src_ids, return_all=False):
        """One prompt row in; the best hypothesis out as
        ``(tokens [max_out_len] sentinel-normalized, score)`` —
        or every hypothesis best-first with ``return_all=True``."""
        W, maxT, bs = self.beam, self.bundle.max_out_len, self._bs
        end_id = self.bundle.end_id
        arr = np.asarray(src_ids)
        if arr.ndim == 1:
            arr = arr[None]
        if arr.shape != (1, self.bundle.seq_len):
            raise ValueError(
                f"beam decode takes one prompt row of exactly "
                f"seq_len={self.bundle.seq_len} tokens; got "
                f"{tuple(np.asarray(src_ids).shape)}")
        arr = arr.astype(np.int64)
        st, scope, rows = self._st, self.scope, self._rows
        tab = np.zeros((rows, self.cache.pages(maxT)), np.int32)
        pref = np.full((rows,), self.cache.n_prompt_entries,
                       np.int32)
        pref[:W] = 0
        tables = []
        for b in range(W):
            blk = self._alloc()
            tables.append([blk])
            tab[b, 0] = blk
        self._admit(arr, tab, pref)
        # probe mode: the device computes KV + distributions but
        # never emits — set AFTER admission (the lane reset clears
        # prefill_until)
        until = np.zeros((rows,), np.int64)
        until[:W] = maxT
        scope._set(st["prefill_until"], until)
        buf = np.zeros((rows, maxT), np.int64)
        buf[:W, 0] = self.bundle.start_id
        scores = np.full((W,), -1e9, np.float32)
        scores[0] = 0.0  # single live seed (the reference's LoD seed)
        act = np.zeros((rows,), np.int64)
        act[:W] = 1
        neg = np.finfo(np.float32).min
        for s in range(maxT - 1):
            scope._set(st["tok_buf"], buf.copy())
            scope._set(st["active"], act.copy())
            scope._set(st["block_tab"], tab.copy())
            outs = self._probe.run({}, return_numpy=True)
            probs = np.asarray(outs[0])[:W]
            k2 = min(2 * W, probs.shape[1])
            finished = buf[:W, s] == end_id
            cand_ids = np.empty((W, k2), np.int64)
            cand_tot = np.empty((W, k2), np.float32)
            for b in range(W):
                if finished[b]:
                    # frozen beam: only candidate is end_id at an
                    # unchanged score (decode_ops.beam_search rule)
                    cand_ids[b] = end_id
                    cand_tot[b] = neg
                    cand_tot[b, 0] = scores[b]
                else:
                    order = np.argsort(-probs[b],
                                       kind="stable")[:k2]
                    cand_ids[b] = order
                    with np.errstate(divide="ignore"):
                        cand_tot[b] = (np.log(probs[b, order])
                                       + scores[b])
            flat = cand_tot.reshape(-1)
            top = np.argsort(-flat, kind="stable")[:W]
            parents = top // k2
            toks = cand_ids.reshape(-1)[top]
            scores = flat[top].astype(np.float32)
            # --- reassignment: sharing, inheritance, COW ----------
            boundary = (s + 1) % bs == 0
            c = s // bs  # block holding position s (just written)
            heirs = collections.Counter(int(p) for p in parents)
            new_tables, cow_src, cow_dst = [], [], []
            for b in range(W):
                pt = tables[int(parents[b])]
                if boundary:
                    # block c is FULL: shareable read-only; the next
                    # write opens a fresh block either way
                    share, tail = pt[:c + 1], None
                elif heirs[int(parents[b])] == 1:
                    # sole heir inherits the partial tail exclusively
                    share, tail = pt, []
                else:
                    # diverging children each COW the partial block
                    share = pt[:c]
                    tail = [self._alloc()]
                    cow_src.append(pt[c])
                    cow_dst.append(tail[0])
                for blk in share:
                    self._pool.incref(blk)
                if tail is None:
                    tail = [self._alloc()]
                new_tables.append(share + tail)
            if cow_src:
                # device-side block copy BEFORE the old refs drop
                # (the sources must stay pinned while read)
                csrc = np.zeros((rows,), np.int64)
                cdst = np.full((rows,), -1, np.int64)
                cgate = np.zeros((rows,), np.float32)
                csrc[:len(cow_src)] = cow_src
                cdst[:len(cow_dst)] = cow_dst
                cgate[:len(cow_src)] = 1.0
                self._cow.run({"cow_src": csrc, "cow_dst": cdst,
                               "cow_gate": cgate},
                              return_numpy=True)
                self.cow_blocks += len(cow_src)
            for pt in tables:
                for blk in reversed(pt):
                    self._pool.decref(blk)
            tables = new_tables
            tab[:W, :] = 0
            for b in range(W):
                for j, blk in enumerate(tables[b]):
                    tab[b, j] = blk
            self.shared_block_peak = max(
                self.shared_block_peak,
                len(self._pool.shared_blocks()))
            newbuf = buf.copy()
            for b in range(W):
                newbuf[b] = buf[int(parents[b])]
                newbuf[b, s + 1] = toks[b]
            buf = newbuf
            if np.all(toks == end_id):
                break  # every hypothesis frozen: later steps no-op
        order = np.argsort(-scores, kind="stable")
        hyps = [(apply_eos_sentinel(buf[b:b + 1], end_id)[0],
                 float(scores[b])) for b in order]
        for pt in tables:
            for blk in reversed(pt):
                self._pool.decref(blk)
        return hyps if return_all else hyps[0]


def count_generated_tokens(tokens: np.ndarray,
                           end_id: Optional[int]) -> np.ndarray:
    """Per-row generated-token count of a [B, maxT] decode buffer:
    positions 1..first-end_id inclusive (the GO token never counts),
    maxT-1 when the row never emitted end_id (the length the
    reference's fast_decode early-finish handling implies, reference
    tests/unittests/dist_transformer.py:1498; the serving layer's
    tokens/s and per-token-latency unit)."""
    toks = np.asarray(tokens)
    if end_id is None:
        return np.full((toks.shape[0],), toks.shape[1] - 1,
                       dtype=np.int64)
    hit = toks[:, 1:] == end_id
    return np.where(hit.any(axis=1), hit.argmax(axis=1) + 1,
                    toks.shape[1] - 1).astype(np.int64)


def apply_eos_sentinel(tokens: np.ndarray,
                       end_id: Optional[int]) -> np.ndarray:
    """Rewrite positions strictly AFTER each row's first `end_id` to
    -1 (the first end_id itself is kept as the terminator). The decode
    programs freeze finished rows at end_id (reference
    tests/unittests/dist_transformer.py:1498 fast_decode early-finish
    handling); the -1 tail is this repo's fixed-size padded-output
    sentinel convention (detection/NMS ops). Position 0 (the GO
    token) never counts as a terminator."""
    if end_id is None:
        return tokens
    toks = np.array(tokens, copy=True)
    hit = toks[:, 1:] == end_id
    first = np.where(hit.any(axis=1), hit.argmax(axis=1) + 1,
                     toks.shape[1])
    pos = np.arange(toks.shape[1])[None, :]
    toks[pos > first[:, None]] = -1
    return toks


# --- PTA201 release-site registrations (the liveness domain) ---------------
# Every acquire contract absint declares gets its release SITES
# registered HERE, from the module that implements them, so the
# obligation ledger names real methods. The exit-path vocabulary is
# the contract's (absint.py); adding a protocol exit (the front-door
# "cancel") means extending the contract AND registering its site —
# PTA201 flags every tag until both halves land.
_P = "PagedContinuousGenerationServer"
for _tag in ("block_table", "cow_dst"):
    # lane-exclusive block chains: reversed decref in retirement,
    # the same unwinding on preemption/close
    _absint.register_release_site(_tag, "retire",
                                  f"{_P}._free_lane_locked")
    _absint.register_release_site(_tag, "preempt",
                                  f"{_P}._plan_burst_locked")
    _absint.register_release_site(_tag, "server_close",
                                  f"{_P}._flush_requests_locked")
    # r20 cancel/deadline teardown of a live lane: routes through
    # _release_lane -> _free_lane_locked, the same reversed decref
    _absint.register_release_site(_tag, "cancel",
                                  f"{_P}._cancel_lane_locked")
# radix-shared chains: tree-aware release on every lane exit, plus
# the watermark/pressure eviction rungs dropping the tree's own refs
_absint.register_release_site("cow_src", "retire",
                              f"{_P}._free_lane_locked")
_absint.register_release_site("cow_src", "preempt",
                              f"{_P}._plan_burst_locked")
_absint.register_release_site("cow_src", "evict",
                              f"{_P}._alloc_block_locked")
_absint.register_release_site("cow_src", "server_close",
                              f"{_P}._flush_requests_locked")
_absint.register_release_site("cow_src", "cancel",
                              f"{_P}._cancel_lane_locked")
# fresh prompt entries: released on retirement, on admission backout
# (invalidate), on abandoned-prefill abort, and at close
_absint.register_release_site("host_indices", "retire",
                              f"{_P}._free_lane_locked")
_absint.register_release_site("host_indices", "abort",
                              f"{_P}._background_abort_locked")
_absint.register_release_site("host_indices", "invalidate",
                              f"{_P}._plan_admissions_locked")
_absint.register_release_site("host_indices", "server_close",
                              f"{_P}._flush_requests_locked")
_absint.register_release_site("host_indices", "cancel",
                              f"{_P}._cancel_lane_locked")
# refcounted hit refs: lane ref drops at retirement; the session PIN
# (ref transferred by _harvest_session_locked) drops at close_session
_absint.register_release_site("prompt_entry_ref", "retire",
                              f"{_P}._free_lane_locked")
_absint.register_release_site("prompt_entry_ref", "session_close",
                              f"{_P}.close_session")
_absint.register_release_site("prompt_entry_ref", "server_close",
                              f"{_P}._flush_requests_locked")
# lane ref on cancel rides _cancel_lane_locked; a handoff ref on a
# shed queued request drops in _drop_queued_locked
_absint.register_release_site("prompt_entry_ref", "cancel",
                              f"{_P}._cancel_lane_locked")
_absint.register_release_site("prompt_entry_ref", "cancel",
                              f"{_P}._drop_queued_locked")
# chunked-prefill cursor entries: ownership hands off to the decode
# lane (or the disagg inbox) on completion, releases on abort/close
_absint.register_release_site("chunk_cursor", "handoff",
                              f"{_P}._advance_prefill")
_absint.register_release_site("chunk_cursor", "handoff",
                              f"{_P}._disagg_done")
_absint.register_release_site("chunk_cursor", "abort",
                              f"{_P}._background_abort_locked")
_absint.register_release_site("chunk_cursor", "abort",
                              f"{_P}._disagg_fail")
_absint.register_release_site("chunk_cursor", "server_close",
                              f"{_P}._flush_requests_locked")
# cancel/deadline on the in-flight chunk job: the shed pass aborts
# it (release + invalidate, same as a mid-chunk error)
_absint.register_release_site("chunk_cursor", "cancel",
                              f"{_P}._shed_cancelled_locked")
del _P, _tag


__all__ = ["InferenceServer", "GenerationServer",
           "ContinuousGenerationServer",
           "PagedContinuousGenerationServer", "PagedBeamDecoder",
           "ServingUnavailable", "BlockPoolExhausted",
           "AdmissionInfeasible", "RequestCancelled",
           "DeadlineExceeded", "StreamingReply", "GenerationReply",
           "ProgramRunner", "ServerQuiesced", "ServerClosed",
           "apply_eos_sentinel", "count_generated_tokens",
           "default_batch_buckets"]
