"""Request lifecycle + FCFS continuous-batching scheduler.

Lifecycle: QUEUED -> PREFILL -> DECODING -> FINISHED, with
DECODING -> PREEMPTED when the page pool exhausts (the victim waits at
the queue front in PREEMPTED state until re-admission re-prefills it).

Policies (vLLM-style, kept deliberately simple and deterministic):

- Admission is strict FCFS with no head-of-line bypass: the queue head
  is admitted only when a slot is free AND the pool has pages for its
  whole (resume) prompt; nothing behind it jumps ahead. Deterministic
  order is what lets tests pin bit-identical outputs.
- Preemption victim = the most recently admitted OTHER running request
  (last-in, first-preempted). The victim's pages are freed, and it is
  requeued at the FRONT of the queue by recompute: its resume prompt is
  ``prompt + generated so far``, so greedy decoding continues
  bit-identically after re-prefill.
- A finished/preempted slot is immediately reusable (slot reuse on
  EOS) — the next admission claims the lowest free slot index.

Serving tier 2 (both default-off, latched at Engine construction):
with FLAGS_serving_prefix_cache the admission check charges only the
UNCACHED SUFFIX of the resume prompt (matched prefix pages are adopted
shared/refcounted from the radix tree, and an LRU reclaim of cold
cached pages runs before admission gives up); release inserts the
slot's full pages into the tree before decref'ing, so preempt-by-
recompute resumes mostly from cache. With
FLAGS_serving_chunked_prefill, PREFILL is a RESUMABLE state — the
request holds its slot across steps while ``prefill_pos`` walks its
prompt in chunks through the mixed step — and mid-prefill rows are
preemption candidates like decode rows.
"""
from __future__ import annotations

import itertools
from collections import deque
from enum import Enum

from ..monitor import trace as _trace
from .metrics import RequestMetrics, now


class RequestState(Enum):
    QUEUED = "queued"
    PREFILL = "prefill"
    DECODING = "decoding"
    PREEMPTED = "preempted"
    FINISHED = "finished"
    # terminal degraded outcomes (resilience layer): the request ended
    # WITHOUT full service, each with a machine-readable status_reason
    EXPIRED = "expired"      # queue-TTL deadline passed while waiting
    SHED = "shed"            # load-shed (queue bound / preemption cap)
    FAILED = "failed"        # poison: its own step raised; engine lives


class Request:
    _ids = itertools.count()

    def __init__(self, prompt, max_new_tokens, eos_token_id=None,
                 deadline_s=None):
        self.id = next(Request._ids)
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.state = RequestState.QUEUED
        self.generated = []
        self.slot = None
        self.admit_seq = None      # monotone admission stamp (victim pick)
        self.metrics = RequestMetrics(now())
        self.metrics.prompt_tokens = len(self.prompt)
        # queue-TTL deadline (monotonic absolute): a request still
        # WAITING (queued or preempted-requeued) past it is shed with
        # the EXPIRED terminal status; once decoding it runs to finish
        self.deadline_t = (None if deadline_s is None
                           else self.metrics.arrival_t + float(deadline_s))
        self.status_reason = None  # terminal detail for EXPIRED/SHED/FAILED
        self.error = None          # the exception of a FAILED request
        # span journal (monitor/trace.py, FLAGS_monitor_trace): the
        # request's trace id, assigned at admission to the engine; None
        # while the journal is off, and every trace_* helper below
        # no-ops on None — a mid-run flag flip never half-traces
        self.trace_id = None
        self._span_root = None
        self._span_phase = None
        # prefix-cache / chunked-prefill state (FLAGS_serving_*; both 0
        # and unused on the default paths):
        # cached_tokens — tokens of THIS admission's resume prompt that
        # came out of the radix cache (the prefill starts there);
        # prefill_pos — resumable chunked-prefill cursor: tokens of
        # resume_tokens already run through the mixed step. Both reset
        # at every (re-)admission.
        self.cached_tokens = 0
        self.prefill_pos = 0
        # tokens of programs dispatched and not read back yet: the
        # engine runs one step ahead of the host, so a row's next step
        # is dispatched while its last token is still on the device
        self.in_flight = 0

    @property
    def resume_tokens(self):
        """Context to (re-)prefill: prompt plus everything generated —
        recompute-on-resume keeps greedy output bit-identical."""
        return self.prompt + self.generated

    @property
    def remaining(self):
        return self.max_new_tokens - len(self.generated)

    def finish(self):
        self.state = RequestState.FINISHED
        self.metrics.on_finish(now(), len(self.generated))

    def close(self, state, reason, error=None):
        """Terminal close for the degraded outcomes (EXPIRED / SHED /
        FAILED): stamps the finish time for wall accounting WITHOUT
        observing the latency histograms — a shed request's lifetime is
        not a service latency, and mixing them would poison the p99s
        the SLO reads."""
        self.state = state
        self.status_reason = reason
        self.error = error
        self.metrics.finish_t = now()
        self.metrics.output_tokens = len(self.generated)
        self.trace_finish(state.value, reason=reason)

    @property
    def terminal(self):
        return self.state in (RequestState.FINISHED, RequestState.EXPIRED,
                              RequestState.SHED, RequestState.FAILED)

    # -- span timeline (monitor/trace.py) ---------------------------------
    #
    # One root "request" span per request; lifecycle phases (queue ->
    # prefill -> decode -> preempted -> prefill(resume) -> ...) are
    # CONTIGUOUS child phase spans — each transition ends the previous
    # phase and starts the next at ONE timestamp, so the phase
    # durations sum to the request's e2e latency (the acceptance
    # contract tests/test_trace.py pins at +-5%).

    def trace_begin(self, trace_ctx=None):
        """``trace_ctx=(trace_id, parent_span_id)`` adopts a context
        minted by another process (the fleet router's traceparent): the
        engine's phase spans land under the SAME fleet-wide trace id,
        the root span naming the sender's dispatch span as its remote
        parent."""
        if not _trace.is_enabled():
            return
        remote_parent = None
        if trace_ctx is not None and trace_ctx[0] is not None:
            self.trace_id = _trace.adopt_trace(
                trace_ctx[0], "request", request_id=self.id,
                prompt_tokens=len(self.prompt),
                max_new_tokens=self.max_new_tokens)
            remote_parent = trace_ctx[1]
        else:
            self.trace_id = _trace.new_trace(
                "request", request_id=self.id,
                prompt_tokens=len(self.prompt),
                max_new_tokens=self.max_new_tokens)
        self._span_root = _trace.start_span(
            "request", self.trace_id, kind="request", request_id=self.id,
            remote_parent=remote_parent)
        self.metrics.trace_id = self.trace_id

    def trace_phase(self, phase, **attrs):
        if self.trace_id is None:
            return
        t = _trace.now()
        if self._span_phase is not None:
            _trace.end_span(self._span_phase, t=t)
        self._span_phase = _trace.start_span(
            phase, self.trace_id, parent_id=self._span_root,
            kind="phase", t=t, **attrs)

    def trace_event(self, name, **attrs):
        if self.trace_id is None:
            return
        _trace.add_event(self._span_phase
                         if self._span_phase is not None
                         else self._span_root, name, **attrs)

    def trace_finish(self, status="finished", **attrs):
        if self.trace_id is None:
            return
        t = _trace.now()
        if self._span_phase is not None:
            _trace.end_span(self._span_phase, t=t)
            self._span_phase = None
        _trace.end_span(self._span_root, t=t, status=status,
                        output_tokens=len(self.generated),
                        preemptions=self.metrics.preemptions, **attrs)


class Scheduler:
    def __init__(self, max_slots, cache, prefix_cache=None):
        self.max_slots = max_slots
        self.cache = cache
        # radix prefix cache (FLAGS_serving_prefix_cache; None = the
        # pre-cache admission path, bit-identical)
        self.prefix_cache = prefix_cache
        self.queue = deque()
        self.slots = [None] * max_slots    # slot -> Request or None
        self._admit_counter = itertools.count()

    # -- queue ------------------------------------------------------------

    def add(self, req):
        self.queue.append(req)

    def requeue_front(self, req):
        self.queue.appendleft(req)

    def expire_waiting(self, t=None):
        """Remove waiting requests (QUEUED or PREEMPTED — both hold no
        slot) whose queue-TTL deadline passed; returns them, oldest
        first, for the engine to close as EXPIRED. Decoding requests
        are never expired: their pages are live and finishing is
        strictly cheaper than recomputing a replacement."""
        t = now() if t is None else t
        expired = [r for r in self.queue
                   if r.deadline_t is not None and t >= r.deadline_t]
        if expired:
            dead = set(id(r) for r in expired)
            self.queue = deque(r for r in self.queue
                               if id(r) not in dead)
        return expired

    def has_work(self):
        return bool(self.queue) or any(
            r is not None for r in self.slots)

    def active(self):
        """(slot, req) for slots currently decoding, slot order."""
        return [(i, r) for i, r in enumerate(self.slots)
                if r is not None and r.state is RequestState.DECODING]

    def occupied(self):
        """(slot, req) for every slot holding live work — DECODING rows
        plus mid-prefill chunk rows (chunked prefill keeps PREFILL
        state across steps); the mixed ragged step batches them all."""
        return [(i, r) for i, r in enumerate(self.slots)
                if r is not None and r.state in (RequestState.PREFILL,
                                                 RequestState.DECODING)]

    def slots_active(self):
        """Occupied slot count (any state) — the batch-slot occupancy
        the trace events stamp."""
        return sum(1 for r in self.slots if r is not None)

    # -- admission --------------------------------------------------------

    def admit_next(self):
        """Admit the queue head if a slot is free and the pool can hold
        its resume prompt's UNCACHED SUFFIX (with the prefix cache off,
        that is the whole prompt — the pre-cache check, bit-identical).
        Returns (slot, req) or None. Strict FCFS: a blocked head blocks
        everything behind it. With the prefix cache on, the head's
        prefix is matched against the radix tree first: matched pages
        are adopted (shared, refcounted) instead of allocated, and when
        even the suffix doesn't fit, an LRU reclaim of unreferenced
        cached pages runs BEFORE giving up."""
        if not self.queue:
            return None
        free = [i for i, r in enumerate(self.slots) if r is None]
        if not free:
            return None
        req = self.queue[0]
        slot = free[0]
        tokens = req.resume_tokens
        matched_pages, matched = [], 0
        if self.prefix_cache is not None:
            matched_pages, matched = self.prefix_cache.match(
                tokens, limit=len(tokens) - 1)
        need = self.cache.pages_needed(len(tokens)) - len(matched_pages)
        if matched % self.cache.block_size:
            # a partially-matched page will be copy-on-write cloned at
            # first write — charge the clone page now so the prefill
            # can never fail mid-admission (all-or-nothing stays true)
            need += 1
        # adopt BEFORE any reclaim: the slot's reference (refcount 2)
        # protects the just-matched pages from the LRU walk — otherwise
        # an eviction pass triggered by THIS admission could free the
        # very prefix it matched
        if matched_pages:
            self.cache.adopt_prefix(slot, matched_pages, matched)
        if need > self.cache.allocator.free_blocks:
            if self.prefix_cache is not None:
                self.prefix_cache.reclaim(
                    need - self.cache.allocator.free_blocks)
            if need > self.cache.allocator.free_blocks:
                if matched_pages:   # undo: all-or-nothing admission
                    self.cache.release_slot(slot)
                return None
        self.queue.popleft()
        if not self.cache.ensure_capacity(slot, len(tokens)):
            raise AssertionError("admission raced the allocator")
        req.cached_tokens = matched
        req.prefill_pos = matched
        if self.prefix_cache is not None:
            self.prefix_cache.note_lookup(len(tokens), matched)
            req.metrics.on_prefix_lookup(len(tokens), matched)
        self.slots[slot] = req
        req.slot = slot
        req.state = RequestState.PREFILL
        req.admit_seq = next(self._admit_counter)
        req.metrics.on_admit(now())
        if req.trace_id is not None:    # attrs cost nothing when off
            req.trace_event(
                "scheduled", slot=slot, kv_pages=need,
                kv_cached_tokens=matched,
                kv_free_blocks=self.cache.allocator.free_blocks,
                slots_active=self.slots_active(),
                resume=req.metrics.preemptions > 0)
        return slot, req

    # -- slot release / preemption ---------------------------------------

    def release(self, req):
        """Release the request's slot + page references (finish or
        preempt). With the prefix cache on, the slot's FULL pages are
        inserted into the radix tree FIRST — release then decrefs, so
        the computed prefix (prompt and generated history both) stays
        warm: a preempted victim's resume re-matches its own pages and
        recomputes almost nothing, and the next request sharing the
        prompt head skips it entirely."""
        slot = req.slot
        if self.prefix_cache is not None:
            # a step in flight has written positions whose tokens the
            # host does not hold yet: only the known ones are keys
            tokens = req.resume_tokens
            self.prefix_cache.insert(
                tokens, self.cache.slot_pages(slot),
                min(int(self.cache.seq_lens[slot]), len(tokens)))
        self.cache.release_slot(slot)
        self.slots[slot] = None
        req.slot = None

    def preempt_victim(self, exclude_slot, max_preemptions=None,
                       include_prefill=False):
        """Pick and preempt the most recently admitted running request
        other than ``exclude_slot``; requeues it at the front. Returns
        the victim or None when there is no ELIGIBLE other running
        request. With ``max_preemptions`` set, a request that already
        paid the cap is no longer a candidate — it runs to completion,
        which is what breaks the preempt-recompute livelock (two
        requests evicting each other forever make no progress; a capped
        request cannot be evicted, so it finishes and frees pages).
        ``include_prefill`` widens the candidate set to mid-prefill
        chunk rows (chunked prefill holds PREFILL slots across steps;
        on the default path a request decodes from its prefill's
        dispatch on, and the wider set is identical to active())."""
        pool = self.occupied() if include_prefill else self.active()
        candidates = [r for i, r in pool if i != exclude_slot
                      and (max_preemptions is None
                           or r.metrics.preemptions < max_preemptions)]
        if not candidates:
            return None
        victim = max(candidates, key=lambda r: r.admit_seq)
        seq_len = (int(self.cache.seq_lens[victim.slot])
                   if victim.trace_id is not None else 0)
        self.release(victim)
        victim.state = RequestState.PREEMPTED
        victim.metrics.preemptions += 1
        self.requeue_front(victim)
        if victim.trace_id is not None:
            victim.trace_phase(
                "preempted", seq_len=seq_len,
                kv_pages_freed=self.cache.pages_needed(seq_len),
                kv_free_blocks=self.cache.allocator.free_blocks,
                slots_active=self.slots_active())
        return victim
