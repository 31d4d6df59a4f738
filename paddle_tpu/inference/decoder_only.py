"""The paged continuous-batching scheduler for a DECODER-ONLY bundle
(models/decode_engine.DecoderOnlyStepBundle): what
`PagedContinuousGenerationServer(bundle)` is when the bundle has no
encoder. The cycle is the base server's (plan, feed, dispatch, retire,
deliver; one prepared dispatch a cycle); what differs is the planning:

* a request is a prompt of its own length and a `max_new_tokens` of its
  own; the planner admits by blocks free, not by a fixed `seq_len`;
* the radix tree over prompt tokens is the only prefix cache: the whole
  blocks of the prompt that the tree holds are mapped into the lane
  read-only (shared, reference-counted), and prefill starts at the
  first position that is not cached;
* the rest of the prompt but its last token goes into the lane's own
  blocks in chunks (the bundle's prefill program: several chunks of
  several sizes a dispatch, one after another on the device, fused
  with the decode ticks of the lanes that are live); the last token is position
  0 of the lane's token row and the lane's first tick computes the
  first new token from it;
* when a request ends, the whole blocks of its prompt (of the first
  `cache_tokens` tokens, where the caller said how much of the prompt
  others will send again) are adopted by the tree. A lane writes only
  blocks it alone holds: a shared block is never written through;
* a bundle whose lanes carry state of their own beside the paged cache
  (`bundle.lane_state`: a state-space layer's scan state and
  convolution tail) takes no hit from the tree and leaves nothing to
  it: the cached blocks of a prefix could be mapped, but the lane state
  at the prefix's end exists nowhere, and blocks that nobody can reuse
  only cost evictions. Every admission is counted
  (`prefix_reuse_skipped`); its prefill starts at position 0, where the
  programs start the lane's state from zero (`state_resets`).
"""
from __future__ import annotations

import collections
import time

import jax
import numpy as np

from ..models.decode_engine import (BlockKeys, BlockPoolExhausted,
                                    fed_name)
from ..observability import tracing as obs_tracing
from .serving import (GenerationReply, PagedContinuousGenerationServer,
                      ServerClosed, ServerQuiesced, StreamingReply,
                      _GenRequest)

_ROOT = ()      # one tree for every prompt: no encoder ties a chain of
#                 blocks to a whole prompt


class _DecoderOnlyRequest(_GenRequest):
    __slots__ = ("prompt", "max_new", "cache_tokens", "cached", "probe")

    def __init__(self, prompt, max_new, cache_tokens, *args, **kwargs):
        super().__init__(prompt[None], *args, **kwargs)
        self.prompt = prompt            # [P] int64
        self.max_new = max_new
        self.cache_tokens = cache_tokens
        self.cached = 0                 # prompt tokens found cached
        self.probe = None               # what record_probes keeps


class DecoderOnlyPagedServer(PagedContinuousGenerationServer):
    """See the module's docstring. `record_probes` keeps, on each
    finished request (`reply.probe`), what the bundle's probes hold of
    its lane: the selection its last tick attended in every layer, and
    the experts chosen for every token it emitted."""

    def __init__(self, bundle, record_probes=False, **kwargs):
        self._ctx_pages = bundle.context // bundle.cache.block_size
        # chunk tokens (as padded) a dispatch carries before its ticks:
        # two chunks of the largest size (a sweep on the chip, PERF.md
        # PR 32: half of it leaves a large chunk waiting, twice it
        # holds the ticks up)
        self._prefill_budget = 2 * bundle.chunk_sizes[-1]
        self._record_probes = bool(record_probes)
        # lanes whose prompt is still going into the cache, in the
        # order they were given a slot: slot -> next position to fill
        self._filling: "collections.OrderedDict[int, int]" = \
            collections.OrderedDict()
        self._plan = None               # this cycle's chunks, admits
        self._lane_base = np.zeros((bundle.n_slots + 1,), np.int64)
        self._dec = dict.fromkeys(
            ("prompt_tokens", "cached_prompt_tokens", "prefill_tokens",
             "prefill_chunks", "lane_ticks", "context_sum",
             "selected_keys_sum", "state_resets", "prefix_reuse_skipped"),
            0)
        # per-lane state beside the paged cache: no prefix reuse
        self._lane_state = bundle.lane_state
        # the experts' counters, as they end every dispatch's row
        self._moe_keys = list(bundle.moe_keys)
        self._moe_read = {k: 0 for k in self._moe_keys}
        kwargs.pop("radix_reuse", None)
        kwargs.pop("chunked_prefill", None)
        super().__init__(bundle, radix_reuse=True, chunked_prefill=False,
                         **kwargs)
        with self._cv:
            self._tab = np.zeros((bundle.n_slots + 1, self._ctx_pages),
                                 np.int32)
        self._topk = bundle.selection_size or bundle.context

    # --- request path -------------------------------------------------
    def submit(self, src_ids, max_new_tokens=None, cache_tokens=None,
               stream=False, stream_cb=None, deadline_ms=None, **kwargs):
        """Enqueue one prompt of any length from 1 to the context less
        `max_new_tokens` (default: the most the bundle's token rows
        hold). `cache_tokens`: how much of the prompt, from its start,
        later prompts will repeat (a document before a question); its
        whole blocks stay in the radix tree when the request ends
        (default: the whole prompt). `stream`, `stream_cb` and
        `deadline_ms` as the base server has them; sessions, n_best and
        seeds belong to the encoder-decoder bundles."""
        extra = {k: v for k, v in kwargs.items() if v is not None
                 and not (k == "n_best" and v == 1)}
        if extra:
            raise ValueError(
                f"a decoder-only bundle takes a prompt, max_new_tokens "
                f"and cache_tokens; {sorted(extra)} belong to the "
                f"encoder-decoder bundles")
        with self._submit_span():
            return self._enqueue_prompt(src_ids, max_new_tokens,
                                        cache_tokens, stream, stream_cb,
                                        deadline_ms)

    def _enqueue_prompt(self, src_ids, max_new, cache_tokens, stream,
                        stream_cb, deadline_ms):
        prompt = np.asarray(src_ids, np.int64).reshape(-1)
        room = self.bundle.max_out_len - 1
        max_new = room if max_new is None else int(max_new)
        if not 1 <= max_new <= room:
            raise ValueError(f"max_new_tokens must lie in [1, {room}], "
                             f"got {max_new}")
        if not 1 <= len(prompt) <= self.bundle.context - max_new:
            raise ValueError(
                f"a prompt of {len(prompt)} tokens and {max_new} new "
                f"ones do not fit the context of {self.bundle.context}")
        if deadline_ms is not None and deadline_ms <= 0:
            raise ValueError(f"deadline_ms must be > 0, got {deadline_ms}")
        deadline = (time.monotonic() + float(deadline_ms) / 1e3
                    if deadline_ms is not None else None)
        cache_tokens = len(prompt) if cache_tokens is None \
            else max(0, min(int(cache_tokens), len(prompt)))
        trace = obs_tracing.current_request_trace() \
            or obs_tracing.start_request(owner="server",
                                         server=self._obs_id)
        reply = GenerationReply()
        sreply = StreamingReply(self) if stream else None
        req = _DecoderOnlyRequest(
            prompt, max_new, cache_tokens, reply, trace=trace,
            stream=sreply, stream_cb=stream_cb, deadline=deadline)
        reply._gen_server, reply._gen_req = self, req
        if sreply is not None:
            sreply._req, sreply._future = req, reply
        with self._cv:
            if self._closed:
                raise ServerClosed("ContinuousGenerationServer is closed")
            if not self._accepting:
                raise ServerQuiesced(
                    "ContinuousGenerationServer is quiesced (draining "
                    "for retire/hot swap); re-resolve the model and "
                    "retry")
            self._queue.append(req)
            self._n_requests += 1
            if self._t_first_arrival is None:
                self._t_first_arrival = req.t_arrival
            self._cv.notify_all()
        return sreply if stream else reply

    # --- planning -------------------------------------------------------
    def _pages(self, req):
        """Blocks that hold the request's prompt and all it may
        emit."""
        return -(-(len(req.prompt) + req.max_new) // self._bs)

    def _plan_admissions_locked(self, failures):
        """Give queued requests free lanes while their blocks fit, then
        cut this cycle's prefill chunks. Returns the lanes that start
        to decode in this dispatch."""
        t_admit = time.monotonic()
        bs = self._bs
        for slot in range(self.n_slots):
            if not self._queue:
                break
            if self._lanes[slot] is not None:
                continue
            req = self._queue[0]
            n = len(req.prompt)
            # at least the last token is computed here, and the block
            # that holds the first computed position is the lane's own
            cap = (n - 1) // bs
            pages = self._pages(req)
            # the cached chain first: it pins its blocks, so that
            # making room below cannot take them. None for a bundle with
            # lane state: the state at a hit's boundary exists nowhere
            shared = [] if self._lane_state else self._radix.acquire(
                _ROOT, _chunks(req.prompt[:cap * bs], bs))
            short = pages - len(shared) - self._blocks.free_count
            if short > 0 and self._radix.evict(short) < short:
                self._radix.release(shared)
                if not any(l is not None for l in self._lanes):
                    # nothing will ever free a block: it cannot run
                    self._queue.popleft()
                    req.finalized = True
                    failures.append((req, BlockPoolExhausted(
                        f"a request of {pages} blocks does not fit the "
                        f"pool of {self._blocks.n_blocks}")))
                    continue
                break           # blocks come back as lanes retire
            self._queue.popleft()
            own = [self._blocks.alloc()
                   for _ in range(pages - len(shared))]
            self._lane_shared[slot], self._lane_blocks[slot] = shared, own
            self._tab[slot, :pages] = shared + own
            self._lanes[slot] = req
            req.cached = len(shared) * bs
            self._filling[slot] = req.cached
            self._dec["prompt_tokens"] += n
            self._dec["cached_prompt_tokens"] += req.cached
            if self._lane_state:
                self._dec["prefix_reuse_skipped"] += 1
                self._dec["state_resets"] += 1
            if shared:
                self._radix_admits += 1
                self._hit_depth.observe(float(len(shared)))
            self._admit_tier = "radix" if shared else "miss"
            self._note_admit_locked(req, slot, t_admit, self._admit_tier)
            if req.trace is not None:
                req.trace.add_span("slotpool.queue", req.t_arrival,
                                   t_admit, slot=slot,
                                   blocks_reused=len(shared))
        self._blocks_hwm = max(self._blocks_hwm, self._blocks.in_use)
        return self._cut_chunks_locked()

    def _cut_chunks_locked(self):
        """This cycle's chunks (lane, first position, length) by chunk
        size, oldest lane first, and the lanes they finish: `_plan` for
        the feed, the finished lanes as the admits. A lane's rest is
        cut into whole chunks of the largest size and one smaller one;
        the program runs the sizes largest first, so each finds the
        ones before it cached. A dispatch carries at most
        two of the largest chunks (as padded), so that the
        lanes that decode are not held up for long, and always one."""
        sizes, room = self.bundle.chunk_sizes, self.bundle.max_chunks
        chunks = {c: [] for c in sizes}
        admits, spent = [], 0
        for slot, at in self._filling.items():
            end = len(self._lanes[slot].prompt) - 1
            while at < end:
                n = min(end - at, sizes[-1])
                fit = next(c for c in sizes if c >= n)
                if len(chunks[fit]) == room or (
                        spent and spent + fit > self._prefill_budget):
                    break
                chunks[fit].append((slot, at, n))
                spent += fit
                at += n
            if at < end or len(admits) == room:
                break           # chunks keep the lanes' order
            admits.append(slot)
        self._plan = (chunks, admits) if spent or admits else None
        return [(slot, self._lanes[slot]) for slot in admits]

    def _plan_burst_locked(self, admits, drain, failures):
        """Every lane holds its blocks from the start, so a burst is
        never cut short for coverage: a short one while prompts wait
        for their next chunks, the drain burst otherwise."""
        if self._plan is None \
                and all(l is None for l in self._lanes):
            return 0, 0, False
        waiting = len(self._filling) > len(admits) or self._queue
        return (self.steps_per_tick if waiting else self.drain_steps), \
            0, True

    def _has_background_work_locked(self):
        return bool(self._filling)

    def _build_feed(self):
        chunks, admits = self._plan
        a = self.bundle.max_chunks
        feed = {}
        n_chunks = positions = 0
        for size, cut in chunks.items():
            toks = np.zeros((a, size), np.int64)
            lane = np.full((a,), self.bundle.dustbin, np.int64)
            pos, length = np.zeros((a,), np.int64), \
                np.zeros((a,), np.int64)
            for i, (slot, at, n) in enumerate(cut):
                toks[i, :n] = self._lanes[slot].prompt[at:at + n]
                lane[i], pos[i], length[i] = slot, at, n
                positions += n
            n_chunks += len(cut)
            feed.update({
                f"chunk_toks_{size}": toks, f"chunk_lane_{size}": lane,
                f"chunk_pos_{size}": pos, f"chunk_len_{size}": length,
                f"n_chunks_{size}": np.array([len(cut)], np.int64)})
        slots = np.full((a,), self.bundle.dustbin, np.int64)
        tok, base, limit = (np.zeros((a,), np.int64) for _ in range(3))
        for i, slot in enumerate(admits):
            req = self._lanes[slot]
            slots[i], tok[i] = slot, req.prompt[-1]
            base[i], limit[i] = len(req.prompt) - 1, req.max_new
            self._lane_base[slot] = base[i]
        feed.update({"admit_slots": slots, "admit_tok": tok,
                     "admit_base": base, "admit_limit": limit})
        self._dec["prefill_chunks"] += n_chunks
        self._dec["prefill_tokens"] += positions
        rec = obs_tracing.current_cycle()
        if rec is not None:
            rec.attrs.update(prefill_chunks=n_chunks,
                             prefill_positions=positions)
        return self.bundle.PREFILL, feed

    def _admission_feed(self, admits):
        return self._build_feed()

    def _background_feed(self):
        return None if self._plan is None else self._build_feed()

    def _background_abort_locked(self):
        self._filling.clear()
        self._plan = None
        return None

    def _pre_dispatch(self):
        """The block table and the lane mask, as feeds of the dispatch
        (the bundle's `fed_tables`)."""
        act = np.zeros((self.n_slots + 1,), np.int64)
        for s in range(self.n_slots):
            if self._lanes[s] is not None and s not in self._filling:
                act[s] = 1
        # lanes whose prompt is still filling read 0 here; the
        # admission body raises the ones this dispatch finishes
        self._harvest_ok = False
        return {fed_name("block_tab"): self._tab.copy(),
                fed_name("active"): act}

    def _post_dispatch(self, outs):
        step = np.asarray(outs[1]).astype(np.int64)
        with self._cv:
            if self._plan is not None:
                for cut in self._plan[0].values():
                    for slot, at, n in cut:
                        self._filling[slot] = max(self._filling[slot],
                                                  at + n)
                for slot in self._plan[1]:
                    del self._filling[slot]
                    self._lane_step[slot] = 0
                self._plan = None
            for s in range(self.n_slots):
                if self._lanes[s] is None or s in self._filling:
                    continue
                ran = int(step[s] - self._lane_step[s])
                if ran > 0:
                    # a tick at position p reads p + 1 cache positions
                    # and selects at most index_topk of them
                    ctx = self._lane_base[s] + self._lane_step[s] + 1 \
                        + np.arange(ran)
                    self._dec["lane_ticks"] += ran
                    self._dec["context_sum"] += int(ctx.sum())
                    self._dec["selected_keys_sum"] += int(
                        np.minimum(ctx, self._topk).sum())
            self._lane_step = step.copy()
            self._moe_read = dict(zip(
                self._moe_keys,
                (np.asarray(v) for v in outs[-len(self._moe_keys):])))
        self._harvest_ok = True

    # --- retirement -----------------------------------------------------
    def _retire_lanes(self, outs):
        """The base sweep for a lane that ends after its own number of
        tokens: a finished row is cut at the lane's step (what follows
        is -1), a filling lane has no tokens yet and only its deadline
        is looked at."""
        tok_buf, step, active = outs[:3]
        done_t = time.monotonic()
        retired, cancels, stream_out = [], [], []
        with self._cv:
            occupied = 0
            for slot in range(self.n_slots):
                req = self._lanes[slot]
                if req is None:
                    continue
                occupied += 1
                reason = self._expired_locked(req, done_t)
                filling = slot in self._filling
                retiring = not filling and active[slot] == 0
                if reason is not None and not retiring:
                    self._filling.pop(slot, None)
                    self._cancel_lane_locked(slot, req, reason)
                    cancels.append((req, reason))
                    continue
                if filling:
                    continue
                if req.t_first is None:
                    req.t_first = done_t
                n = int(step[slot])
                if retiring:
                    toks = np.array(tok_buf[slot], np.int64)
                    toks[n + 1:] = -1
                    lat = (done_t - req.t_arrival) * 1e3
                    self._latencies.observe(lat)
                    self._ttft.observe(
                        (req.t_first - req.t_arrival) * 1e3)
                    if n:
                        self._per_token.observe(lat / n)
                        self._n_tokens += n
                    self._n_done += 1
                    self._t_last_done = done_t
                    req.finalized = True
                    self._release_lane(slot, req)
                    self._lanes[slot] = None
                    if req.trace is not None:
                        req.trace.add_span(
                            "slotpool.decode", req.t_admit or
                            req.t_arrival, done_t, slot=slot, tokens=n)
                    retired.append((req, toks, "eos" if n and toks[n]
                                    == self._end_id else "length"))
                if (req.stream is not None
                        or req.stream_cb is not None) \
                        and n > req.emitted:
                    chunk = np.asarray(
                        tok_buf[slot][req.emitted + 1:n + 1]).astype(
                            np.int64)
                    stream_out.append((req, req.n_streamed, chunk))
                    req.n_streamed += len(chunk)
                    req.emitted = n
            self._n_ticks += 1
            self._occ_sum += occupied / self.n_slots
        return retired, cancels, stream_out

    def _release_lane(self, slot, req):
        """The lane stops serving `req`. A request that ended by itself
        leaves the whole blocks of its cacheable prompt to the tree
        (the tree takes its own reference; blocks it already has stay
        the lane's and are freed) and, with `record_probes`, keeps what
        the probes hold of its lane."""
        self._filling.pop(slot, None)
        harvest = req.harvest and self._harvest_ok
        if harvest and self._record_probes:
            with obs_tracing.span("slotpool.retire.probe"):
                self._keep_probe(slot, req)
        with obs_tracing.span("slotpool.retire.tree"):
            keep = 0 if self._lane_state or not harvest else \
                min(req.cache_tokens, len(req.prompt)) // self._bs
            if keep:
                self._radix.insert(
                    _ROOT, _chunks(req.prompt[:keep * self._bs],
                                   self._bs),
                    [int(b) for b in self._tab[slot, :keep]])
            self._free_lane_locked(slot)
        self._tab[slot, :] = 0

    def _keep_probe(self, slot, req):
        n = int(self._lane_step[slot])
        probes = self.bundle.probes
        # the lane's rows of every probe in one compiled call and one
        # transfer; the scheduler thread is between two dispatches, so
        # the state is the scope's own
        names = [name for kind in ("selected", "chosen")
                 for name in probes[kind].values()]
        per_tick = [kind for kind in ("logits", "top_logit")
                    if kind in probes]
        names += [probes[kind] for kind in per_tick]
        rows = dict(zip(names, jax.device_get(_take_rows(
            tuple(self.scope._get(name) for name in names),
            np.int32(slot)))))
        probe = req.reply.probe = req.probe = {
            "lane": int(slot),
            "position": int(self._lane_base[slot]) + n - 1,
            "selected": {li: rows[name]
                         for li, name in probes["selected"].items()},
            "chosen": {li: rows[name][:n]
                       for li, name in probes["chosen"].items()}}
        for kind in per_tick:
            probe[kind] = rows[probes[kind]][:n]
        if req.stream is not None:
            req.stream.probe = probe

    # --- what it counted --------------------------------------------------
    def _pool_stats_locked(self):
        st = super()._pool_stats_locked()
        d = self._dec
        st.update(d)
        st["filling_lanes"] = len(self._filling)
        st["state_lanes"], st["state_bytes"] = self._state_size()
        st["mean_context"] = d["context_sum"] / d["lane_ticks"] \
            if d["lane_ticks"] else None
        st["selected_keys_per_query"] = \
            d["selected_keys_sum"] / d["lane_ticks"] \
            if d["lane_ticks"] else None
        for key in ("moe_pairs", "moe_hit"):
            st[key] = int(np.asarray(self._moe_read[key]).reshape(-1)[0])
        st["moe_load"] = {
            k: np.asarray(v).tolist() for k, v in self._moe_read.items()
            if k.startswith("moe_load")}
        return st


    def _metrics_samples(self):
        """The base server's series and, under the same
        `paddle_tpu_blockpool_*` prefix, the lanes' state: how many
        lanes carry it, its bytes, the admissions that started it from
        zero and those that took no prefix hit because of it."""
        lab = {"server": self._obs_id}
        lanes, size = self._state_size()
        return super()._metrics_samples() + [
            ("paddle_tpu_blockpool_state_lanes", lab, lanes),
            ("paddle_tpu_blockpool_state_bytes", lab, size),
            ("paddle_tpu_blockpool_state_resets_total", lab,
             self._dec["state_resets"]),
            ("paddle_tpu_blockpool_prefix_reuse_skipped_total", lab,
             self._dec["prefix_reuse_skipped"])]

    def _state_size(self):
        """(lanes that carry state of their own, its bytes over all
        rows); zeros for a bundle without."""
        if not self._lane_state:
            return 0, 0
        return self.n_slots, \
            (self.n_slots + 1) * self._lane_state["bytes_per_lane"]


@jax.jit
def _take_rows(arrays, i):
    return tuple(a[i] for a in arrays)


def _chunks(tokens, block_size):
    """`tokens` (a whole number of blocks) as the radix tree's keys:
    the bytes of a block's ids."""
    toks = np.ascontiguousarray(tokens, np.int64)
    return BlockKeys(toks[i:i + block_size].tobytes()
                     for i in range(0, len(toks), block_size))
