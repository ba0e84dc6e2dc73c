"""Continuous-batching serving engine: ONE compiled decode step.

Shape discipline (the whole point, and what the reference
AnalysisPredictor stack cannot do): the decode step is a single jitted
function over a FIXED ``max_slots`` batch —

    decode(state, pools, tokens[S], block_tables[S, MB], seq_lens[S])
        -> (next_tokens[S], pools)

Requests arriving, finishing, and getting preempted never change a
shape, so XLA compiles the decode EXACTLY ONCE per (model, engine
config); ``Engine.stats()["decode_compiles"]`` is asserted in-test.
Prefill is jitted per power-of-two length bucket (right-padded; pad
rows are causally invisible to real rows and their K/V lands in the
trash page), so a serving lifetime compiles O(log max_len) prefills.

Serving tier 2 (default-off flags, latched at construction):
``FLAGS_serving_prefix_cache`` adopts shared refcounted pages for
cached prompt prefixes and prefills only the uncached suffix (the
per-bucket prefill becomes the hist-parameterized suffix prefill);
``FLAGS_serving_chunked_prefill`` replaces the split decode/prefill
pair with ONE mixed ragged step over [max_slots, prefill_chunk] rows —
decode rows are q_len==1 chunks — so long prompts stream through the
decode batch one chunk per step instead of stalling it, and the
compile-once contract holds as ``decode_compiles == 1`` for the mixed
step. Both off: every compiled function, shape and output below is
bit-identical to the tier-1 engine (test-pinned).

The engine OWNS the cache: a model's ``paged_cache_spec()`` lists, one
entry a layer, what a slot holds there (serving/kv_cache.py: K/V pages,
latent pages, fixed slot-indexed state, or nothing), and its layers take a per-layer hook
object (``update_and_attend`` for pages, ``read``/``write`` for state)
— the model never allocates or stores cache state.

Greedy decoding (argmax, matching GenerationMixin.generate's
``do_sample=False`` semantics token-for-token) — the parity contract
tests/test_serving.py pins. Driving loop is host-side, and ONE STEP
AHEAD of the device: the sampled tokens stay on the device as the next
step's input (a prefill writes its first token there itself), so
``Engine.step()`` dispatches decode step N before it reads step N-1's
tokens back, and the device always has the next program queued while
the host accepts, schedules and admits. ``seq_lens`` advance when a row
is dispatched; a request that finishes by length is known then and
gives its slot back at once; one that finishes by EOS is known a step
late, and its row in the step already in flight is discarded. Device
order keeps slot and page reuse safe: a program dispatched later writes
them later. While the poison quarantine holds requests the engine steps
synchronously (each program read back right after its dispatch), and
the chunked-prefill mixed step always does.
"""
from __future__ import annotations

import time
import weakref
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import monitor as _monitor
from ..distributed import mesh as _mesh
from ..resilience import faultinject as _fi
from .kv_cache import (KVBlockPool, LatentPool, PagedKVCache,
                       PagedMixedView)
from . import replay as _replay
from .metrics import EngineMetrics, now, span
from .scheduler import Request, RequestState, Scheduler


class AdmissionError(RuntimeError):
    """Request rejected AT admission (load shed) — never enqueued, no
    id assigned; the caller retries elsewhere or backs off."""

    reason = "admission"


class QueueFullError(AdmissionError):
    """Bounded admission queue is full (``max_queue``)."""

    reason = "queue_full"


class DrainingError(AdmissionError):
    """Engine is draining (``Engine.drain()``): in-flight work
    completes, new admissions are rejected — the fleet layer's
    drain-and-reschedule building block."""

    reason = "draining"


class _Program(NamedTuple):
    """A compiled step dispatched and not read back yet."""

    out: object         # its tokens (and expert counters), on the device
    rows: tuple         # ((slot, request), ...) whose tokens it computes
    # a prefill's (prompt tokens, bucket, tokens fed, dispatch stamp);
    # None for a decode step
    prefill: tuple = None
    live_tokens: int = 0    # a decode step's cached tokens (latent cache)
    step: int = None        # a decode step's number, as its span has it


# watchdog heartbeat (monitor/watchdog.py): every engine iteration runs
# inside a busy bracket, so a scheduler deadlock or a hung decode
# dispatch is a detectable stall; an engine with no queued work is idle,
# never stalled
_HB_SERVE = _monitor.heartbeat("serving_engine")

# weight-only quantized decode (FLAGS_serving_quant_weights) eligibility:
# 2-D projection weights of the attention/MLP stacks — the memory-bound
# decode matmuls. Embeddings, lm_head, norms and biases stay fp32 (the
# embedding gather and the final projection dominate accuracy, and
# 1-D params have no reduction axis to block-scale over).
_QUANT_PROJ_SEGMENTS = frozenset((
    "q_proj", "k_proj", "v_proj", "o_proj", "qkv_proj",       # llama attn
    "gate_proj", "up_proj", "down_proj", "gate_up_proj",      # llama mlp
    "qkv", "proj", "fc1", "fc2",                              # gpt
))


def _quantizable_weight(name, val):
    parts = name.split(".")
    return (getattr(val, "ndim", 0) == 2 and parts[-1] == "weight"
            and any(p in _QUANT_PROJ_SEGMENTS for p in parts[:-1]))


class Engine:
    def __init__(self, model, max_slots=4, num_blocks=64, block_size=16,
                 max_model_len=None, max_queue=None,
                 default_deadline_s=None, max_preemptions=None,
                 prefill_chunk=16):
        """Resilience knobs (all default-off — the engine behaves
        exactly as before unless asked):

        max_queue           bounded admission queue: add_request raises
                            QueueFullError (and counts a queue_full
                            shed) once this many requests wait
        default_deadline_s  queue-TTL for requests that don't pass
                            their own deadline_s: still WAITING past it
                            -> terminal EXPIRED status (never kills a
                            decoding request)
        max_preemptions     a request preempted this many times becomes
                            non-preemptible (runs to completion) — the
                            preempt-recompute livelock breaker; when NO
                            eligible victim remains, the grower is shed
                            (reason preempt_cap) instead of deadlocking

        Serving tier-2 flags, LATCHED HERE at construction (a mid-life
        flag flip never changes a live engine's compiled step set):

        FLAGS_serving_prefix_cache   radix prefix cache over the page
                            pool (serving/prefix_cache.py): shared
                            prompt heads map to shared refcounted
                            pages, admission charges only the uncached
                            suffix, release keeps prefixes warm, LRU
                            reclaim runs before any preemption
        FLAGS_serving_chunked_prefill  prompts prefill in
                            ``prefill_chunk``-token chunks interleaved
                            into the ONE compiled mixed step as ragged
                            rows next to the decode rows — a long
                            prefill no longer stalls the decode batch,
                            and ``decode_compiles`` stays exactly 1
        FLAGS_serving_quant_kv  the paged K/V pools are int8 planes
                            with per-(page, position, head) fp32 scale
                            planes riding alongside in KVBlockPool —
                            quantized at page-write time, dequantized
                            inside the attention gather; ~4x page
                            capacity at the same byte budget
        FLAGS_serving_quant_weights  projection weights quantized int8
                            block-scaled ONCE here at bind; the decode/
                            mixed steps bind the dequantize-fused
                            weights (memory-bound rows), the split
                            prefill steps keep fp32
        """
        from ..core import flags as _flags

        self.model = model
        spec = model.paged_cache_spec()
        limit = model.max_decode_len()
        if max_model_len is None:
            max_model_len = limit
        if max_model_len is None:
            raise ValueError("max_model_len required for an unbounded "
                             "model")
        if limit is not None:
            max_model_len = min(max_model_len, limit)
        self.max_slots = max_slots
        self.block_size = block_size
        self.max_model_len = max_model_len
        mb = -(-max_model_len // block_size)
        self.quant_kv = bool(_flags.flag("FLAGS_serving_quant_kv"))
        self.quant_weights = bool(
            _flags.flag("FLAGS_serving_quant_weights"))
        self.cache = PagedKVCache(
            spec, num_blocks=num_blocks, block_size=block_size,
            max_slots=max_slots, max_blocks_per_slot=mb,
            quantized=self.quant_kv)
        # a slot_state layer's row cannot be adopted from a shared
        # prefix, fed a chunk at a time or quantized yet, and the prefix
        # cache, the mixed step and the int8 planes are written for
        # (k, v) pools, not latent rows: refuse, rather than serve such
        # a model wrongly
        for has, kind, why in (
                (self.cache.has_slot_state, "slot_state",
                 "a slot's recurrent state is rebuilt by a whole prefill"),
                (self.cache.has_latent, "latent_pages",
                 "a latent page is written by a whole prefill and a "
                 "decode step"),
                (self.cache.has_window, "window_ring",
                 "a ring is filled by a whole prefill and a decode "
                 "step")):
            for flag in ("FLAGS_serving_prefix_cache",
                         "FLAGS_serving_chunked_prefill",
                         "FLAGS_serving_quant_kv"):
                if has and _flags.flag(flag):
                    raise ValueError(
                        "%s with a %s cache layer (%s): %s, and cannot "
                        "be adopted from a cached prefix, chunked or "
                        "quantized yet"
                        % (flag, kind, type(model).__name__, why))
        # int8 bytes one page's k+v planes hold over every layer — the
        # dequant-bytes accounting unit for
        # serving_quant_dequant_bytes_total
        self._quant_page_bytes = sum(
            2 * block_size * layer.num_kv_heads * layer.head_dim
            for layer in spec if layer.kind == "kv_pages")
        # expert layers whose step counters the model hands back with
        # the tokens (models that declare them only)
        self._moe_layers = int(getattr(model, "moe_layers", 0))
        self.prefix_cache = None
        if _flags.flag("FLAGS_serving_prefix_cache"):
            from .prefix_cache import RadixPrefixCache

            self.prefix_cache = RadixPrefixCache(self.cache)
        self.chunked_prefill = bool(
            _flags.flag("FLAGS_serving_chunked_prefill"))
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        self.scheduler = Scheduler(max_slots, self.cache,
                                   self.prefix_cache)
        self.metrics = EngineMetrics(max_slots)
        self.metrics.moe_experts_held = int(
            getattr(model, "moe_experts_held", 0))
        # a model that runs part of a prefill on fewer rows (YOCO) says
        # how many a bucket; [prefills, prompt rows, rows run past the
        # self-decoder] for stats()["yoco"]
        self._yoco = ([0, 0, 0] if getattr(model, "yoco_rows", None)
                      is not None else None)
        # memory plane (monitor/memory.py, FLAGS_monitor_memory),
        # LATCHED HERE like the tier-2 flags: the step hot path only
        # ever checks the handle. None = flags-off, bit-identical.
        self._mem = None
        # fleet identity beacon (monitor/fleet.py): under
        # FLAGS_monitor_fleet the scraped serving series resolve to
        # this rank/host/job; one flag branch when off
        _monitor.fleet.note_identity("serving")
        self.requests = {}
        self.max_queue = max_queue
        self.default_deadline_s = default_deadline_s
        self.max_preemptions = max_preemptions
        self._draining = False
        # poison quarantine: request ids that were active in a FAILED
        # batched decode — re-admitted ONE AT A TIME so the next decode
        # failure is attributable to a single request (bisect-by-
        # serialization); empties as its members reach terminal states
        self._quarantine = set()
        self._names, values = model.functional_state()
        self._state_vals = list(values)
        # everything lives where the weights live: one device
        device = min(values[0].devices(), key=lambda d: d.id)
        self._device = device
        self._mesh = _mesh.Mesh(np.array([device]), ("dp",))
        # weight-only quantized decode (FLAGS_serving_quant_weights):
        # projection weights quantized ONCE here; _decode_vals is the
        # state the decode/mixed steps bind — each quantized leaf is an
        # (int8 q, f32 scales) pair the step dequantizes in-trace so
        # XLA fuses the broadcast-multiply into the consuming matmul's
        # operand read. Prefill steps keep binding _state_vals (fp32):
        # compute-bound rows gain nothing from a smaller weight read.
        # Flag off: _decode_vals IS _state_vals — same leaves, same
        # jaxpr, bit-identical (test-pinned).
        self._qw_dtypes = {}            # leaf index -> original dtype
        self._decode_vals = self._state_vals
        if self.quant_weights:
            from ..kernels.quant import quantize_int8_weight

            self._decode_vals = list(self._state_vals)
            for i, (name, val) in enumerate(zip(self._names, values)):
                if _quantizable_weight(name, val):
                    self._qw_dtypes[i] = val.dtype
                    self._decode_vals[i] = quantize_int8_weight(val)
        # slot_tokens[s]: last generated token, not yet written to KV —
        # the next decode step's input for that slot. It lives on the
        # device: a decode step's output is the next one's input, and a
        # prefill writes its first token into it
        self._slot_tokens = self._zero_tokens()
        # programs dispatched and not read back yet, in dispatch order
        self._inflight = []
        # donate_argnums=(1,): the KV pools are CARRIED state — every
        # step consumes the previous pools and returns the next, and
        # the caller rebinds self.cache.pools immediately — so the
        # buffers must alias in-place (input_output_aliases) instead of
        # doubling the pool's HBM footprint every step. The pthlo
        # donation audit (paddle_tpu/analysis/graph) pins this: an
        # un-donated pool in the hot step is a finding. Weights
        # (state_vals, arg 0) are deliberately NOT donated — the same
        # buffers feed every subsequent call.
        if self.chunked_prefill:
            # ONE mixed ragged step serves decode rows AND prefill
            # chunks (a decode row is the q_len==1 case); the split
            # decode/prefill functions are never traced
            self._mixed = jax.jit(self._mixed_fn, donate_argnums=(1,))
        else:
            self._decode = jax.jit(self._decode_fn, donate_argnums=(1,))
            # a prefill also takes the slot tokens and writes its first
            # token there. They are not donated: they are the output of
            # the decode step before it, which the host has yet to read
            # back (a copy of max_slots int32 costs nothing)
            if self.prefix_cache is not None:
                # cache-aware prefill: runs only the uncached suffix
                # over the adopted pool history (hist == 0 on a miss),
                # jitted per suffix-length bucket like _prefill was
                self._suffix_prefill = jax.jit(
                    self._writing_first_token(self._suffix_prefill_fn),
                    donate_argnums=(1,))
            else:
                self._prefill = jax.jit(
                    self._writing_first_token(self._prefill_fn),
                    donate_argnums=(1,))
        self._mem = _monitor.memory.tracker(
            "serving", self._mem_components(),
            context_fn=self._mem_context)
        # ptprof step hook (monitor/profile.py, FLAGS_monitor_profile),
        # LATCHED HERE like the tier-2 flags and the memory tracker:
        # per-iteration dispatch/gap timers, prefill/decode phase
        # timers, and the device-capture-window lifecycle. None =
        # flags-off; the step hot path only ever checks the handle.
        self._prof = _monitor.profile.step_hook("serving")
        # weight-swap generation (ROADMAP item 6): stamped into every
        # replay journal entry + benchmark requests_detail row so a
        # post-hot-swap divergence is attributable to the generation
        # that served it; the swap path will bump it
        self.weights_generation = 0
        # record/replay recorder (serving/replay.py,
        # FLAGS_serving_replay), LATCHED HERE like the tier-2 flags
        # and the monitor handles: None = flags-off — every capture
        # site below is one handle-is-None branch, zero journal
        # allocations, wire/result payloads bit-identical
        self._replay = _replay.recorder(self)

    def _mem_components(self):
        """Ledger providers (monitor/memory.py): the paged KV pools
        (every layer's k/v planes, with prefix-cache/COW page detail)
        and the resident model weights. Providers read live engine
        state at sample time, so pool resets and COW churn are always
        current — and hold the engine WEAKLY, so the global ledger
        never pins a discarded engine's pools/weights alive (a dead
        engine's components just report empty)."""
        wself = weakref.ref(self)

        def kv_pool():
            s = wself()
            if s is None:
                return ()
            cache = s.cache
            entries = []
            for i, pool in enumerate(cache.pools):
                if pool is None:        # a layer that keeps nothing
                    continue
                if isinstance(pool, LatentPool):
                    entries.append(("latent_pool/layer%d" % i, pool.rows))
                    continue
                if not isinstance(pool, KVBlockPool):
                    entries.extend(("state_pool/layer%d/%s" % (i, name), a)
                                   for name, a in pool.items())
                    continue
                entries.append(("kv_pool/layer%d/k" % i, pool.k))
                entries.append(("kv_pool/layer%d/v" % i, pool.v))
                if pool.k_scale is not None:
                    entries.append(("kv_pool/layer%d/k_scale" % i,
                                    pool.k_scale))
                    entries.append(("kv_pool/layer%d/v_scale" % i,
                                    pool.v_scale))
            alloc = cache.allocator
            detail = {
                "pages_used": alloc.usable_blocks - alloc.free_blocks,
                "pages_usable": alloc.usable_blocks,
                "cow_clones": cache.cow_clones,
            }
            if s.prefix_cache is not None:
                detail["prefix_cached_pages"] = \
                    s.prefix_cache.stats()["cached_pages"]
            return {"entries": entries, "detail": detail}

        def model_params():
            s = wself()
            if s is None:
                return ()
            entries = list(zip(s._names, s._state_vals))
            # quantized decode copies (FLAGS_serving_quant_weights) are
            # resident alongside the fp32 originals (prefill binds
            # fp32) — the ledger must see both
            for i in s._qw_dtypes:
                q, scales = s._decode_vals[i]
                entries.append(("int8/" + s._names[i], q))
                entries.append(("int8/" + s._names[i] + ".scales",
                                scales))
            return entries

        return {"kv_pool": kv_pool, "model_params": model_params}

    def _mem_context(self):
        """OOM-postmortem context: the pool/batch state at the moment
        of death — occupancy, slot fill, prefix-cache residency."""
        alloc = self.cache.allocator
        used = alloc.usable_blocks - alloc.free_blocks
        ctx = {
            "kv_page_occupancy": used / max(alloc.usable_blocks, 1),
            "kv_pages_used": used,
            "kv_pages_usable": alloc.usable_blocks,
            "slots_active": self.scheduler.slots_active(),
            "queue_depth": len(self.scheduler.queue),
            "cow_clones": self.cache.cow_clones,
        }
        if self.prefix_cache is not None:
            ctx["prefix_cached_pages"] = \
                self.prefix_cache.stats()["cached_pages"]
        return ctx

    # -- public API -------------------------------------------------------

    def add_request(self, prompt, max_new_tokens=32, eos_token_id=None,
                    deadline_s=None, trace_ctx=None):
        """Queue a request; returns its id. Validates that the request
        can EVER run alone (admission control proper is per-step).
        Raises DrainingError / QueueFullError when load-shedding (the
        request is never enqueued and gets no id). ``trace_ctx=(trace_id,
        parent_span_id)`` adopts a caller-minted trace context (the
        fleet router's traceparent) instead of minting a fresh id."""
        if self._draining:
            self.metrics.on_request_shed("draining")
            raise DrainingError(
                "engine is draining: new admissions rejected")
        if self.max_queue is not None \
                and len(self.scheduler.queue) >= self.max_queue:
            self.metrics.on_request_shed("queue_full")
            raise QueueFullError(
                "admission queue full (%d waiting, max_queue=%d)"
                % (len(self.scheduler.queue), self.max_queue))
        prompt = list(map(int, prompt))
        if not prompt:
            raise ValueError("empty prompt")
        total = len(prompt) + max_new_tokens
        if total > self.max_model_len:
            raise ValueError(
                "prompt (%d) + max_new_tokens (%d) exceeds max_model_len"
                " (%d)" % (len(prompt), max_new_tokens,
                           self.max_model_len))
        pages_needed = self.cache.pages_needed(total)
        if pages_needed > self.cache.allocator.usable_blocks:
            raise ValueError(
                "request needs %d pages but the pool only has %d usable "
                "blocks — it could never be scheduled"
                % (pages_needed, self.cache.allocator.usable_blocks))
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        req = Request(prompt, max_new_tokens, eos_token_id,
                      deadline_s=deadline_s)
        self.requests[req.id] = req
        # span journal (FLAGS_monitor_trace): trace id assigned here —
        # the admission point — so the queue phase covers every second
        # the engine owned the request
        req.trace_begin(trace_ctx)
        # replay journal admission capture (FLAGS_serving_replay):
        # AFTER trace_begin so the entry cross-links the adopted
        # fleet-wide trace id, not a pre-adoption placeholder
        rec = self._replay
        if rec is not None:
            rec.admit(req, deadline_s=deadline_s)
        self.metrics.on_request_in()
        if max_new_tokens == 0:     # zero-length generation: trivially done
            req.finish()
            self.metrics.on_request_finished()
            req.trace_finish("finished")
            if rec is not None:
                rec.terminal(req)
            return req.id
        if req.trace_id is not None:
            req.trace_phase("queue")
            req.trace_event("admitted", kv_pages_needed=pages_needed)
        self.scheduler.add(req)
        return req.id

    def has_work(self):
        return self.scheduler.has_work() or bool(self._inflight)

    def step(self):
        """One engine iteration, in this order: (a) grow pages
        (preempting on exhaustion) and dispatch decode step N; (b) read
        back step N-1's tokens and those of the prefills the previous
        call dispatched, which ran before N on the device; (c) accept
        them; (d) expire, admit and dispatch prefills into the slots now
        free. Under chunked prefill: admit, grow, one mixed step, read
        back. Returns has_work()."""
        with _HB_SERVE.busy("serving.step"):
            try:
                # engine-level injection site: a fault here models a
                # transient failure BETWEEN requests (scheduler glitch,
                # control-plane hiccup) — nothing owned it, no request
                # is harmed, the iteration is simply retried
                if _fi.is_enabled():
                    _fi.fire("serving.step")
            except _fi.InjectedFault:
                return self.has_work()
            prof = self._prof
            if prof is not None:
                # ptprof: open any queued capture window BEFORE the
                # iteration dispatches, so the Xprof trace covers it
                prof.step_begin()
                _pt0 = time.perf_counter()
            try:
                # OOM forensics (monitor/memory.py, latched at
                # construction): mem.oom is the deterministic
                # RESOURCE_EXHAUSTED stand-in; any OOM-shaped failure
                # writes oom_postmortem_rank{r}.json and RE-RAISES —
                # allocator state after a real OOM is unknowable, so
                # unlike the poison paths there is no recovery here
                if self._mem is not None and _fi.is_enabled():
                    _fi.fire("mem.oom")
                # the engine's own account (serving/metrics.py): one
                # now() at each phase boundary, a span between them
                self.metrics.on_step_begin()
                if self.chunked_prefill:
                    self._scheduling(self._expire_waiting)
                    self._timed_phase(prof, "prefill",
                                      self._admit_and_prefill)
                    self._scheduling(self._grow_or_preempt)
                    self._note_kv_occupancy()
                    rows = self.scheduler.occupied()
                    if rows:
                        self._timed_phase(prof, "decode",
                                          self._mixed_once, rows)
                else:
                    self._scheduling(self._grow_or_preempt)
                    self._note_kv_occupancy()
                    active = self.scheduler.active()
                    went = bool(active) and self._timed_phase(
                        prof, "decode", self._dispatch_decode, active)
                    # the programs before the step just dispatched ran
                    # before it on the device; stepping synchronously,
                    # it is read back too
                    ahead = went and not self._quarantine
                    self._collect(len(self._inflight) - ahead)
                    self._scheduling(self._expire_waiting)
                    self._timed_phase(prof, "prefill",
                                      self._admit_and_prefill)
                self.metrics.on_step_end()
                if self.prefix_cache is not None:
                    self.metrics.on_prefix_stats(
                        self.prefix_cache.stats(),
                        self.cache.cow_clones)
            except Exception as e:
                if self._mem is not None \
                        and _monitor.memory.looks_like_oom(e):
                    self._mem.write_postmortem(e)
                if prof is not None:
                    # a raising step must not leak the open capture
                    # window (or its live device trace); the partial
                    # artifact lands marked aborted
                    prof.step_abort()
                raise
            if prof is not None:
                # no block arg: the iteration synced the previous step's
                # outputs to host numpy — the iteration wall IS the
                # host-exposed time; gap covers the scheduler idle
                # between iterations
                prof.step_end(_pt0, time.perf_counter())
        return self.has_work()

    def _note_kv_occupancy(self):
        """Perf attribution (FLAGS_perf_attribution): KV-page
        occupancy + goodput per engine iteration, sampled at the step's
        high-water point (pages grown, nothing released yet) — pure host
        arithmetic, but still flag-gated so the default serving hot path
        does no new work."""
        if _monitor.is_enabled() and _monitor.perf.attribution_enabled():
            alloc = self.cache.allocator
            self.metrics.on_kv_occupancy(
                1.0 - alloc.free_blocks / max(alloc.usable_blocks, 1))

    def _scheduling(self, fn):
        """``fn()`` as scheduler and KV-allocator time: under the span
        ``serving.schedule`` and in the open step's ``schedule``
        seconds."""
        t0 = now()
        with span("serving.schedule"):
            out = fn()
        self.metrics.phase_s["schedule"] += now() - t0
        return out

    def _timed_phase(self, prof, phase, fn, *args):
        """Run one step phase, feeding its host wall into the ptprof
        per-phase timers when the handle is latched (one call site per
        phase instead of three copies of the stamp dance). -> what
        ``fn`` returns."""
        if prof is None:
            return fn(*args)
        t = time.perf_counter()
        out = fn(*args)
        prof.note_phase(phase, time.perf_counter() - t)
        return out

    def run(self):
        """Drain all queued work; returns {request_id: generated tokens}."""
        with _HB_SERVE.busy("serving.run"):
            while self.step():
                pass
        return {rid: list(r.generated) for rid, r in self.requests.items()}

    @property
    def draining(self):
        return self._draining

    def drain(self):
        """Stop admitting, finish everything already accepted (active
        slots AND the queue), return the outputs. The fleet layer's
        drain-and-reschedule primitive: after drain() returns, the
        engine holds no work and every accepted request reached a
        terminal state; new add_request calls keep raising
        DrainingError. Waiting requests still honor their deadlines —
        a drain under overload sheds what it cannot serve in time."""
        self._draining = True
        return self.run()

    def output(self, rid):
        return list(self.requests[rid].generated)

    def request_metrics(self, rid):
        return self.requests[rid].metrics.to_dict()

    def request_trace(self, rid):
        """(trace_id, {phase: seconds}) of a request's span timeline —
        (None, None) while the journal (FLAGS_monitor_trace) is off OR
        when the bounded journal already evicted this request's trace
        (callers never have to distinguish the two absences)."""
        tid = self.requests[rid].trace_id
        if tid is None:
            return None, None
        phases = _monitor.trace.phase_breakdown(tid)
        if phases is None:      # evicted from the bounded journal
            return None, None
        return tid, phases

    def stats(self):
        out = self.metrics.to_dict()
        out["state"] = (self.cache.state_stats()
                        if self.cache.has_slot_state else None)
        out["latent"] = None
        if self.cache.has_latent:
            out["latent"] = dict(
                self.cache.latent_stats(),
                cached_tokens=self.metrics.live_tokens_mean())
        out["window"] = (self.cache.window_stats()
                         if self.cache.has_window else None)
        out["yoco"] = None
        if self._yoco is not None and self._yoco[0]:
            n, prompt_rows, cross_rows = self._yoco
            out["yoco"] = {"prefills": n,
                           "prompt_rows_per_prefill": prompt_rows / n,
                           "cross_rows_per_prefill": cross_rows / n}
        out["ssm"] = None
        ssm_layers = getattr(self.model, "ssm_layers", 0)
        if ssm_layers:
            out["ssm"] = {
                "layers": int(ssm_layers),
                "state_bytes_slot": out["state"]["slot_bytes"],
                "active_slots": self.metrics.active_slots_mean()}
        return out

    def request_status(self, rid):
        """Terminal-status view of one request: state + machine-readable
        reason (finished | expired | shed | failed | a live state)."""
        r = self.requests[rid]
        return {
            "id": rid,
            "state": r.state.value,
            "reason": r.status_reason,
            "output_tokens": len(r.generated),
            "preemptions": r.metrics.preemptions,
            "error": repr(r.error) if r.error is not None else None,
        }

    # -- lifecycle --------------------------------------------------------

    def _expire_waiting(self):
        """Queue-TTL pass: waiting requests past their deadline get the
        EXPIRED terminal status (shed reason ``expired``) before any
        admission work is spent on them."""
        for req in self.scheduler.expire_waiting():
            req.close(RequestState.EXPIRED, "deadline")
            self._quarantine.discard(req.id)
            self.metrics.on_request_shed("expired")
            if self._replay is not None:
                self._replay.terminal(req)

    def _admit_and_prefill(self):
        while True:
            admitted = self._scheduling(self._admit_one)
            if admitted is None:
                return
            slot, req = admitted
            if self.chunked_prefill:
                # no synchronous prefill: the request sits in PREFILL
                # state and its prompt streams through the mixed step
                # in prefill_chunk-token rows next to everyone else's
                # decode rows (resumable: prefill_pos is the cursor).
                # The per-request serving.prefill injection site fires
                # HERE — admission is the last moment a prefill fault
                # is attributable to this one request
                try:
                    if _fi.is_enabled():
                        _fi.fire("serving.prefill", request=req.id,
                                 slot=slot)
                except Exception as e:
                    self._fail_request(req, e)
                    continue
                self.metrics.on_prefill_run()
                req.trace_phase(
                    "prefill", slot=slot, tokens=len(req.resume_tokens),
                    cached=req.cached_tokens, chunked=True,
                    resume=req.metrics.preemptions > 0)
                continue
            try:
                self._prefill_request(slot, req)
            except Exception as e:  # poison quarantine: the request's
                self._fail_request(req, e)  # OWN step failed, not the engine
                continue
            if self._quarantine:
                # stepping synchronously: the token on the host now
                self._collect(len(self._inflight))

    def _admit_one(self):
        """The admission decision: (slot, request) of the next waiting
        request that fits, or None."""
        if self._quarantine and self.scheduler.slots_active() > 0:
            # poison bisect in progress: serialize admissions so a
            # failing decode names a single request
            return None
        admitted = self.scheduler.admit_next()
        if admitted is not None:
            slot, req = admitted
            self.metrics.on_admission()
            if self._mem is not None:
                self._mem.note_decision(
                    "admit", request=req.id, slot=slot,
                    kv_pages_free=self.cache.allocator.free_blocks)
        return admitted

    def _fail_request(self, req, exc, lost=False):
        """Poison quarantine: one request's step raised — fail IT with
        a terminal status and keep serving everyone else. ``lost``: the
        step failed on the device, so the pools it handed on are gone
        whatever their buffers say."""
        if req.slot is not None and self.scheduler.slots[req.slot] is req:
            self.scheduler.release(req)
        req.slot = None
        req.close(RequestState.FAILED, "poison", error=exc)
        self._quarantine.discard(req.id)
        self.metrics.on_request_shed("poison")
        if self._replay is not None:
            self._replay.terminal(req)
        self._recover_consumed_pools(force=lost)

    def _recover_consumed_pools(self, force=False):
        """The donated-pools failure path: the compiled steps donate
        their input pools (``donate_argnums=(1,)``), so a step that
        raises AFTER execution started leaves ``cache.pools`` pointing at
        DELETED buffers — every slot's KV, not just the failing
        request's, is gone. ``force``: a step failed on the device, and
        every program queued behind it read its garbage, the slot tokens
        too. (A pre-dispatch failure — fault injection, a trace-time
        error — never consumes anything and this is one cheap liveness
        check.) Recovery is preempt-by-recompute for every
        occupied slot, and for every request whose last token was in a
        program now dropped, over a fresh zeroed pool plane: recompute
        re-prefills from host-side tokens deterministically, so outputs
        stay bit-identical and a one-step transient cannot become
        permanent engine death. The prefix cache is REBUILT, not kept:
        its pages map into the dead pools, and the keep-warm release
        path must not re-serve garbage KV — which is also why the
        requeue below bypasses scheduler.release (its insert would
        cache those pages)."""
        if not force and self.cache.pools_alive():
            return
        from ..monitor.registry import warn_once

        warn_once(
            "serving.pools_consumed",
            "paddle_tpu.serving: a compiled step failed on the device or "
            "after its donated KV pools were consumed; resetting the "
            "pool plane and requeueing every occupied slot "
            "(preempt-by-recompute)")
        self._drop_inflight()
        # reversed + requeue_front, the _on_decode_failure idiom:
        # appendleft in reverse slot order keeps the survivors'
        # re-admission strictly FCFS
        for slot, req in reversed(list(self.scheduler.occupied())):
            if self.scheduler.slots[slot] is not req:
                continue
            self.cache.release_slot(slot)
            self.scheduler.slots[slot] = None
            self._requeue(req)
        if self.prefix_cache is not None:
            from .prefix_cache import RadixPrefixCache

            self.prefix_cache = RadixPrefixCache(self.cache)
            self.scheduler.prefix_cache = self.prefix_cache
        self.cache.reset_pools()
        self._slot_tokens = self._zero_tokens()

    def _requeue(self, req):
        """Back to the queue's front, holding no slot, to resume by
        recompute from the tokens on the host."""
        req.slot = None
        req.state = RequestState.PREEMPTED
        req.metrics.preemptions += 1
        self.scheduler.requeue_front(req)
        self.metrics.on_preemption()

    def _drop_inflight(self):
        """Forget every program in flight: its tokens are never read.
        A request whose last token was among them has given its slot
        back already, so it is requeued here; the requests in slots are
        the caller's."""
        dropped, self._inflight = self._inflight, []
        for program in dropped:
            for slot, req in program.rows:
                req.in_flight = 0
                if req.slot == slot and not req.terminal \
                        and self.scheduler.slots[slot] is not req:
                    self._requeue(req)

    def _zero_tokens(self):
        """A fresh slot-token array on the engine's device."""
        return jax.device_put(np.zeros((self.max_slots,), np.int32),
                              self._device)

    def _prefill_request(self, slot, req):
        """Dispatch the request's prefill into ``slot``. The program
        writes the first token into the slot tokens on the device, so
        the next decode step can take the row before the host has read
        it; ``_collect`` reads it back later."""
        # per-request injection site: the poison-request model — an
        # error here is attributable to THIS request and fails only it
        if _fi.is_enabled():
            _fi.fire("serving.prefill", request=req.id, slot=slot)
        tokens = req.resume_tokens
        L = len(tokens)
        if self.prefix_cache is not None:
            # cache-aware path: only the uncached suffix runs through
            # the model (hist == 0 on a miss — same function, so a miss
            # and a hit share the per-bucket compile). A partially-
            # matched page is split copy-on-write first; admission
            # charged the clone page, so this cannot fail here.
            hist = req.cached_tokens
            if not self.cache.make_writable(slot, hist, L):
                raise AssertionError("COW clone raced the allocator")
            feed = tokens[hist:]
            P = self._bucket(len(feed))
            req.trace_phase("prefill", slot=slot, tokens=L, bucket=P,
                            cached=hist,
                            resume=req.metrics.preemptions > 0)
        else:
            hist = None
            feed = tokens
            P = self._bucket(L)
            req.trace_phase("prefill", slot=slot, tokens=L, bucket=P,
                            resume=req.metrics.preemptions > 0)
        t0 = now()
        with span("serving.prefill", request=req.id, tokens=L, bucket=P):
            ids = np.zeros((1, P), np.int32)
            ids[0, :len(feed)] = feed
            with span("serving.upload"):
                # a copy of the table row: the host grows it before the
                # program has run
                args = [jnp.asarray(ids),
                        jnp.asarray(self.cache.block_tables[slot].copy())]
                if hist is None:
                    fn = self._prefill
                else:
                    fn = self._suffix_prefill
                    args.append(jnp.asarray(hist, jnp.int32))
                args.append(jnp.asarray(len(feed), jnp.int32))
            with span("serving.dispatch"):
                out, self._slot_tokens, self.cache.pools = self._run_eval(
                    fn, self._state_vals, self.cache.pools,
                    self._slot_tokens, jnp.asarray(slot, jnp.int32), *args)
        self.cache.seq_lens[slot] = L
        req.state = RequestState.DECODING
        req.in_flight += 1
        if self.prefix_cache is not None:
            # publish the prompt pages now — the next queued request
            # sharing this prompt head admits against them, and its
            # prefill runs after this one on the device
            self.prefix_cache.insert(tokens, self.cache.slot_pages(slot),
                                     L)
        self._inflight.append(
            _Program(out, ((slot, req),), (L, P, len(feed), t0)))
        if req.remaining <= req.in_flight:
            self._detach(req)

    def _detach(self, req):
        """``req``'s last token is in flight: its slot and pages go back
        now, so an admission of this same iteration can take them (a
        program dispatched later writes them later on the device), and
        no step runs a row for it again. ``req.slot`` keeps the row its
        token comes from until that token is on the host."""
        slot = req.slot
        self.scheduler.release(req)
        req.slot = slot

    def _grow_or_preempt(self):
        """Every live row writes K/V this step — decode rows one
        position at seq_len, prefill-chunk rows their next chunk — make
        sure the pages exist AND are exclusively owned (copy-on-write
        splits a partially-shared prefix page before the first write).
        On pool exhaustion the ESCALATION ORDER is: (1) LRU-reclaim
        pages held only by the prefix cache — dropping cold cached
        state costs nothing already-computed in flight; (2) read back
        the programs in flight, so a victim resumes from every token it
        has and what finished gives its pages back; (3) preempt the
        most recent other request (recompute-requeue) — now the LAST
        resort, not the first; (4) shed the grower when every victim is
        preemption-capped."""
        rows = (self.scheduler.occupied() if self.chunked_prefill
                else self.scheduler.active())
        for slot, req in list(rows):
            if self.scheduler.slots[slot] is not req:
                continue            # became a victim earlier in the loop
            while True:
                start = int(self.cache.seq_lens[slot])
                if req.state is RequestState.PREFILL:
                    end = start + min(
                        self.prefill_chunk,
                        len(req.resume_tokens) - req.prefill_pos)
                else:
                    end = start + 1
                ok = self.cache.ensure_capacity(slot, end)
                if ok and self.prefix_cache is not None:
                    ok = self.cache.make_writable(slot, start, end)
                if ok:
                    break
                if self.prefix_cache is not None:
                    # reclaim the WHOLE shortfall in one heap walk
                    # (+1 covers a possible COW clone page); calling
                    # reclaim(1) per loop turn would pay a full tree
                    # walk per page under sustained pressure
                    shortfall = max(
                        self.cache.pages_needed(end)
                        - self.cache.slot_page_count(slot) + 1
                        - self.cache.allocator.free_blocks, 1)
                    if self.prefix_cache.reclaim(shortfall):
                        continue
                if self._inflight:
                    self._collect(len(self._inflight))
                    if self.scheduler.slots[slot] is not req:
                        break       # finished, or requeued by a failure
                    continue
                victim = self.scheduler.preempt_victim(
                    slot, self.max_preemptions,
                    include_prefill=self.chunked_prefill)
                if victim is None:
                    others = [r for i, r in self.scheduler.occupied()
                              if i != slot]
                    if others:
                        # every other running request is at the
                        # preemption cap (non-preemptible by design):
                        # shed THIS grower rather than livelock or
                        # deadlock the pool
                        self.scheduler.release(req)
                        req.close(RequestState.SHED, "preempt_cap")
                        self._quarantine.discard(req.id)
                        self.metrics.on_request_shed("preempt_cap")
                        if self._replay is not None:
                            self._replay.terminal(req)
                        if self._mem is not None:
                            self._mem.note_decision(
                                "shed", request=req.id,
                                reason="preempt_cap")
                        break
                    raise RuntimeError(
                        "KV pool exhausted by a single request — "
                        "add_request validation should have caught this")
                self.metrics.on_preemption()
                if self._mem is not None:
                    self._mem.note_decision(
                        "preempt", victim=victim.id, grower=req.id,
                        kv_pages_free=self.cache.allocator.free_blocks)

    def _dispatch_decode(self, rows):
        """Dispatch one decode step over ``rows`` from the tables the
        host holds, under the span ``serving.decode_step``: its tokens
        stay on the device as the next step's input, and ``_collect``
        reads them back later. Each row's ``seq_len`` advances now.
        -> whether it went (False when it raised and
        ``_on_decode_failure`` has dealt with it)."""
        ahead = any(p.prefill is None for p in self._inflight)
        step = self.metrics.decode_dispatches
        with span("serving.decode_step", step=step, rows=len(rows)):
            try:
                # batched injection site: a decode failure is NOT
                # attributable to one request — the quarantine
                # serializes the batch until it is
                if _fi.is_enabled():
                    _fi.fire("serving.decode", batch=len(rows))
                t0 = now()
                with span("serving.upload"):
                    # copies: the host advances seq_lens and grows the
                    # tables before the step has run
                    tables = jnp.asarray(self.cache.block_tables.copy())
                    lens = jnp.asarray(self.cache.seq_lens.copy())
                t1 = now()
                with span("serving.dispatch"):
                    out, pools = self._run_eval(
                        self._decode, self._decode_vals, self.cache.pools,
                        self._slot_tokens, tables, lens)
                    # behind a model's tokens ride its expert counters
                    tokens = (out[:self.max_slots] if self._moe_layers
                              else out)
            except Exception as e:  # poison quarantine
                # the rows requeued there resume from every token they
                # have: the programs before this one are read first
                self._collect(len(self._inflight))
                self._on_decode_failure(rows, e)
                return False
            t2 = now()
        self.cache.pools = pools
        self._slot_tokens = tokens
        phase_s = self.metrics.phase_s
        phase_s["upload"] += t1 - t0
        phase_s["dispatch"] += t2 - t1
        live = int(self.cache.seq_lens.sum()) if self.cache.has_latent \
            else 0
        self._note_quant_step()
        for slot, req in rows:
            # the input token's K/V row lands at position seq_len
            self.cache.seq_lens[slot] += 1
            req.in_flight += 1
        self._inflight.append(_Program(out, tuple(rows), None, live, step))
        self.metrics.on_decode_dispatch(ahead)
        for slot, req in rows:
            if req.remaining <= req.in_flight:
                self._detach(req)
        return True

    def _collect(self, n):
        """Read back and accept the first ``n`` programs in flight, in
        the order they were dispatched (the device's): each program's
        tokens are stamped when they reach the host."""
        phase_s = self.metrics.phase_s
        for _ in range(n):
            if not self._inflight:
                return
            program = self._inflight.pop(0)
            names = ({"step": program.step} if program.prefill is None
                     else {"request": program.rows[0][1].id})
            t0 = now()
            try:
                with span("serving.readback", **names):
                    host = self._readback(program.out)
            except Exception as e:
                self._on_readback_failure(program, e)
                return
            t = now()
            phase_s["readback"] += t - t0
            with span("serving.accept"):
                if program.prefill is None:
                    self._accept_decode(program, host, t)
                else:
                    self._accept_prefill(program, host, t)
            phase_s["accept"] += now() - t

    @staticmethod
    def _readback(out):
        """A program's tokens on the host: waits for the device."""
        return np.asarray(out)

    def _accept_decode(self, program, out, t):
        rows = program.rows
        self.metrics.on_decode_step(len(rows), program.live_tokens)
        if self._moe_layers:
            self.metrics.on_moe_call(out[self.max_slots:], len(rows),
                                     self.max_slots, decode=True)
        for slot, req in rows:
            req.in_flight -= 1
            if req.terminal:
                # finished by an EOS read after this step went out
                self.metrics.on_discarded_row()
                continue
            self._accept_token(req, int(out[slot]), t)

    def _accept_prefill(self, program, out, t):
        (slot, req), = program.rows
        L, P, fed, t0 = program.prefill
        req.in_flight -= 1
        tok = out
        if self._moe_layers:
            self.metrics.on_moe_call(out[1:], fed, P, decode=False)
            tok = out[0]
        self.metrics.on_prefill_run()
        # from building the ids to the token on the host
        self.metrics.on_prefill_done(t - t0, L, P)
        if self._yoco is not None:
            self._yoco[0] += 1
            self._yoco[1] += L
            self._yoco[2] += self.model.yoco_rows.get(P, P)
        req.metrics.on_first_token(t)
        # decode phase opens BEFORE the first token is accepted: a
        # max_new_tokens=1 request finishes inside _accept_token and
        # its trace_finish must close the decode span, not prefill
        req.trace_phase("decode", slot=slot)
        self._accept_token(req, int(tok), t)

    def _on_readback_failure(self, program, exc):
        """A program failed on the device. Every program dispatched
        after it read its pools and slot tokens, so none of their
        results stand: the failed program's rows take the path of a
        failed dispatch, and everyone else recomputes over a fresh pool
        plane."""
        self._inflight.insert(0, program)
        if program.prefill is None:
            self._on_decode_failure(program.rows, exc, lost=True)
        else:
            self._fail_request(program.rows[0][1], exc, lost=True)

    def _batched_step(self, name, rows, fn, *host_args):
        """One synchronous batched step through ``fn`` (the mixed step)
        as the host lives it, under the span ``name``: upload
        ``host_args``, dispatch, wait for the tokens. -> (the tokens on
        the host, the stamp taken when they got there), or None when the
        step raised and ``_on_decode_failure`` has dealt with it."""
        with span(name, step=self.metrics.decode_steps, rows=len(rows)):
            try:
                # batched injection site: a decode failure is NOT
                # attributable to one request — the quarantine
                # serializes the batch until it is
                if _fi.is_enabled():
                    _fi.fire("serving.decode", batch=len(rows))
                t0 = now()
                with span("serving.upload"):
                    args = [jnp.asarray(a) for a in host_args]
                t1 = now()
                with span("serving.dispatch"):
                    next_toks, new_pools = self._run_eval(
                        fn, self._decode_vals, self.cache.pools, *args)
            except Exception as e:  # poison quarantine
                self._on_decode_failure(rows, e)
                return None
            t2 = now()
            self.cache.pools = new_pools
            with span("serving.readback"):
                out = self._readback(next_toks)
            t3 = now()
        phase_s = self.metrics.phase_s
        phase_s["upload"] += t1 - t0
        phase_s["dispatch"] += t2 - t1
        phase_s["readback"] += t3 - t2
        return out, t3

    def _mixed_once(self, rows):
        """ONE mixed ragged step (chunked prefill): decode rows feed
        their pending token (q_len 1), PREFILL rows feed their next
        prompt chunk (q_len up to prefill_chunk) — all through the ONE
        compiled step, so a long prefill costs the decode batch one
        chunk of latency per step instead of a full-prompt stall."""
        C = self.prefill_chunk
        tokens = np.zeros((self.max_slots, C), np.int32)
        q_lens = np.zeros((self.max_slots,), np.int32)
        chunk_rows = 0
        for slot, req in rows:
            if req.state is RequestState.PREFILL:
                toks = req.resume_tokens
                n = min(C, len(toks) - req.prefill_pos)
                tokens[slot, :n] = toks[req.prefill_pos:
                                        req.prefill_pos + n]
                q_lens[slot] = n
                chunk_rows += 1
            else:
                tokens[slot, 0] = req.generated[-1]
                q_lens[slot] = 1
        done = self._batched_step(
            "serving.mixed_step", rows, self._mixed, tokens,
            self.cache.block_tables, self.cache.seq_lens, q_lens)
        if done is None:
            return
        out, t = done
        with span("serving.accept"):
            self.metrics.on_decode_step(len(rows))
            self._note_quant_step()
            for _ in range(chunk_rows):
                self.metrics.on_prefill_chunk()
            for slot, req in rows:
                n = int(q_lens[slot])
                self.cache.seq_lens[slot] += n
                if req.state is RequestState.PREFILL:
                    req.prefill_pos += n
                    if req.prefill_pos < len(req.resume_tokens):
                        continue    # mid-prompt: sampled token discarded
                    # final chunk: its last position's logits are the
                    # first generated token — the request becomes a
                    # decode row
                    if self.prefix_cache is not None:
                        self.prefix_cache.insert(
                            req.resume_tokens,
                            self.cache.slot_pages(slot),
                            int(self.cache.seq_lens[slot]))
                    req.state = RequestState.DECODING
                    req.metrics.on_first_token(t)
                    req.trace_phase("decode", slot=slot)
                self._accept_token(req, int(out[slot]), t)
        self.metrics.phase_s["accept"] += now() - t

    def _note_quant_step(self):
        """Per-step quant-KV accounting (FLAGS_serving_quant_kv; one
        attribute check when off): live int8 page count, plus the int8
        bytes this step's attention gathers dequantized — every live
        slot's full history pages, k and v planes, every layer."""
        if not self.quant_kv:
            return
        alloc = self.cache.allocator
        read_pages = sum(-(-int(n) // self.block_size)
                         for n in self.cache.seq_lens if n)
        self.metrics.on_quant_step(
            alloc.usable_blocks - alloc.free_blocks,
            read_pages * self._quant_page_bytes)

    def _on_decode_failure(self, active, exc, lost=False):
        """A batched decode raised. With ONE active request the poison
        is named — fail it, keep the engine. With several, requeue them
        all (preempt-by-recompute keeps their output bit-identical) and
        enter serial quarantine: one request per batch until the set
        clears, so the next failure IS attributable. The engine never
        dies for one request's exception. ``lost``: the step failed on
        the device (``_recover_consumed_pools``).

        Cost, by design: every quarantined request runs solo to
        completion, so one transient batched failure serializes its
        batch's remaining decode. Early exoneration (drop from
        quarantine after one clean solo step, then re-batch) was
        considered and rejected: a re-batched exonerated request
        decoding next to a still-quarantined poison makes the next
        failure unattributable again — with a deterministic poison that
        ping-pongs forever. Strict FCFS also means nothing behind the
        quarantined head could use the freed batch slots anyway."""
        # an OOM-shaped decode failure gets its forensics BEFORE the
        # recovery below mutates the pool state the postmortem must
        # describe (the quarantine path still runs — a transient OOM
        # in a batched decode is recoverable the same way any decode
        # failure is)
        if self._mem is not None and _monitor.memory.looks_like_oom(exc):
            self._mem.write_postmortem(exc)
        # rows whose request finished (or was requeued) since the step
        # went out are no longer the step's to answer for
        active = [(slot, req) for slot, req in active
                  if req.slot == slot and not req.terminal]
        if len(active) == 1:
            _, req = active[0]
            self._fail_request(req, exc, lost)
            return
        for slot, req in reversed(active):
            seq_len = int(self.cache.seq_lens[slot])
            if self.scheduler.slots[slot] is req:
                self.scheduler.release(req)
            self._requeue(req)
            self._quarantine.add(req.id)
            if req.trace_id is not None:
                req.trace_phase(
                    "preempted", seq_len=seq_len, quarantine=True,
                    slots_active=self.scheduler.slots_active())
        self._recover_consumed_pools(force=lost)

    def _accept_token(self, req, tok, t):
        """``t``: when the token reached the host, the stamp taken right
        after its program's readback."""
        req.generated.append(tok)
        self.metrics.on_output_token(req.metrics.on_token(t))
        done = (req.remaining <= 0
                or (req.eos_token_id is not None
                    and tok == req.eos_token_id))
        if req.trace_id is not None:
            # token MILESTONES, not every token (bounded journal): the
            # first, every 8th, and the last, each stamped with the KV
            # and batch-slot occupancy the step saw
            n = len(req.generated)
            if n == 1 or done or n % 8 == 0:
                alloc = self.cache.allocator
                req.trace_event(
                    "token", n=n,
                    kv_pages_used=(alloc.usable_blocks
                                   - alloc.free_blocks),
                    slots_active=self.scheduler.slots_active())
        if done:
            # an EOS finish still holds its slot; a finish by length
            # gave it back when its last step went out
            if self.scheduler.slots[req.slot] is req:
                self.scheduler.release(req)
            req.slot = None
            req.finish()
            self._quarantine.discard(req.id)   # survived serial decode
            self.metrics.on_request_finished(len(req.generated))
            req.trace_finish("finished")
            if self._replay is not None:
                self._replay.terminal(req)

    # -- graph analysis ---------------------------------------------------

    def _hot_step(self):
        """(name, jitted fn, raw fn, args) of THE hot step — the mixed
        step under chunked prefill, else decode — at the engine's own
        fixed shapes (that fixedness IS the compile-once contract)."""
        S = self.max_slots
        bt = jnp.asarray(self.cache.block_tables)
        lens = jnp.asarray(self.cache.seq_lens)
        if self.chunked_prefill:
            toks = jnp.zeros((S, self.prefill_chunk), jnp.int32)
            ql = jnp.zeros((S,), jnp.int32)
            return ("mixed", self._mixed, self._mixed_fn,
                    (self._decode_vals, self.cache.pools, toks, bt, lens,
                     ql))
        toks = jnp.zeros((S,), jnp.int32)
        return ("decode", self._decode, self._decode_fn,
                (self._decode_vals, self.cache.pools, toks, bt, lens))

    def hot_step_hlo(self):
        """Compiled-HLO text of the hot step (AOT lower + compile, never
        executed) — what chip_smoke.py reads to assert that the Mosaic
        paged kernel is in the step the chip runs. Lowering traces, and
        a trace counts into ``decode_compiles``: read the stats first."""
        _, jit_fn, _, args = self._hot_step()
        return self._run_eval(jit_fn.lower, *args).compile().as_text()

    def graph_report(self):
        """AOT-lower (never execute) every compiled step this engine
        configuration would run — the ONE mixed step under chunked
        prefill, else decode + the live prefill variant — and return
        the raw graph-analysis artifact for the offline analyzer
        (paddle_tpu/analysis/graph, tools/pthlo.py): jaxpr + StableHLO
        + compiled-HLO text per step, the donated-pool leaf census,
        and the weight census. Representative shapes are the engine's
        own fixed shapes (that fixedness IS the compile-once
        contract). Tracing counts into the compile metrics like any
        trace; call this on fixture engines, not mid-serve."""
        import jax.tree_util as jtu

        from ..analysis.graph.artifact import arg_leaf_census, \
            param_census
        from ..monitor import perf as _perf

        pools = self.cache.pools

        def artifact(jit_fn, raw_fn, args):
            lowered = jit_fn.lower(*args)
            compiled = lowered.compile()
            # weights feed every call (never donated); pools are
            # carried state and MUST alias; the rest is per-call input
            spans = [("weights", len(jtu.tree_leaves(args[0]))),
                     ("state", len(jtu.tree_leaves(args[1]))),
                     ("input", len(jtu.tree_leaves(args[2:])))]
            return {
                "hlo": compiled.as_text(),
                "stablehlo": lowered.as_text(),
                "jaxpr": str(jax.make_jaxpr(raw_fn)(*args)),
                "arg_leaves": arg_leaf_census(
                    jtu.tree_leaves(lowered.args_info), spans),
                "cost": _perf.executable_analysis(compiled, steps=1),
            }

        steps = {}
        name, jit_fn, raw_fn, args = self._hot_step()
        steps[name] = artifact(jit_fn, raw_fn, args)
        if not self.chunked_prefill:
            P = self._bucket(8)
            head = (self._state_vals, pools,
                    jnp.zeros((self.max_slots,), jnp.int32),
                    jnp.asarray(0, jnp.int32),
                    jnp.zeros((1, P), jnp.int32),
                    jnp.asarray(self.cache.block_tables[0]))
            if self.prefix_cache is not None:
                steps["suffix_prefill"] = artifact(
                    self._suffix_prefill,
                    self._writing_first_token(self._suffix_prefill_fn),
                    head + (jnp.asarray(0, jnp.int32),
                            jnp.asarray(P, jnp.int32)))
            else:
                steps["prefill"] = artifact(
                    self._prefill,
                    self._writing_first_token(self._prefill_fn),
                    head + (jnp.asarray(P, jnp.int32),))
        return {
            "kind": "serving",
            "params": param_census(zip(self._names, self._state_vals)),
            "steps": steps,
            "mesh_axes": None,
            "qsync_buckets": None,
            "flags": {"prefix_cache": self.prefix_cache is not None,
                      "chunked_prefill": self.chunked_prefill,
                      "quant_kv": self.quant_kv,
                      "quant_weights": self.quant_weights},
        }

    # -- compiled steps ---------------------------------------------------

    def _bucket(self, n):
        """Prefill length bucket: next power of two (>= 8), capped at
        max_model_len rounded up to a multiple of 8 AND at the block
        table's position capacity — a pad length past ``MB * bs`` would
        make the prefill scatter's clamped gather write pad K/V over
        the request's last real page."""
        p = 8
        while p < n:
            p *= 2
        cap = min(-(-self.max_model_len // 8) * 8,
                  self.cache.max_blocks_per_slot * self.block_size)
        return min(p, max(cap, n))

    def _dequant_state(self, state_vals):
        """Rebuild the fp32 weight list from the mixed quantized state
        (traced — runs INSIDE the decode/mixed steps, so the per-leaf
        dequant is a broadcast-multiply XLA fuses into the consuming
        matmul's operand read; the int8 planes are what crosses HBM).
        No quantized leaves (flag off): the list passes through
        untouched and the trace is unchanged."""
        if not self._qw_dtypes:
            return list(state_vals)
        from ..kernels.quant import dequantize_int8_weight

        out = list(state_vals)
        for i, dt in self._qw_dtypes.items():
            q, scales = out[i]
            out[i] = dequantize_int8_weight(q, scales, dt)
        return out

    def _run_eval(self, fn, *args):
        """Call (and on first use trace) a compiled step: model in eval
        mode, and under the engine's own one-device mesh — the engine
        is single-device by construction, whatever mesh the process
        built for training (distributed/mesh.py scoped_mesh)."""
        was_training = self.model.training
        self.model.eval()
        try:
            with _mesh.scoped_mesh(self._mesh):
                return fn(*args)
        finally:
            if was_training:
                self.model.train()

    @staticmethod
    def _writing_first_token(fn):
        """``fn``, a prefill ``(state_vals, pools, *args) -> (out,
        pools)``, as the program the engine runs: it also takes the slot
        tokens and the slot, and writes the prompt's first token there
        on the device -> (out, slot tokens, pools)."""
        def prefill(state_vals, pools, slot_tokens, slot, *args):
            out, pools = fn(state_vals, pools, *args)
            with jax.named_scope("lm_head"):
                slot_tokens = slot_tokens.at[slot].set(
                    jnp.reshape(out, (-1,))[0])
            return out, slot_tokens, pools

        return prefill

    def _prefill_fn(self, state_vals, pools, ids, table_row, true_len):
        from ..core.dispatch import no_grad
        from ..core.tensor import Tensor

        self.metrics.on_prefill_compile()       # trace-time counter
        with self.model.bind_state(self._names, list(state_vals)):
            with no_grad():
                views = self.cache.prefill_views(pools, table_row,
                                                 true_len)
                logits, views = self.model.generate_step(
                    Tensor(ids), views, 0, self._last_row(true_len))
        tok = self._greedy(logits)[0]
        return self._with_moe_counters(tok), [v.pool for v in views]

    @staticmethod
    def _last_row(q_lens):
        """``logits_at`` of a prefill or mixed step: the one row a
        sequence whose token the engine takes (its last valid position;
        an idle row of the mixed step clamps to 0 and is ignored on the
        host), int32 [B]. The model then norms and projects that row
        alone instead of the whole padded bucket."""
        return jnp.maximum(
            jnp.reshape(q_lens, (-1,)).astype(jnp.int32) - 1, 0)

    @staticmethod
    def _greedy(logits):
        """int32 [B]: argmax in float32 of the one row a sequence that
        ``logits_at`` left in ``logits`` [B, 1, vocab]."""
        from ..core.tensor import Tensor

        lv = logits._value if isinstance(logits, Tensor) else logits
        with jax.named_scope("lm_head"):
            return jnp.argmax(lv[:, 0, :].astype(jnp.float32),
                              axis=-1).astype(jnp.int32)

    def _with_moe_counters(self, tokens):
        """The step's tokens, and behind them for a model that declares
        expert layers the counters of the step being traced, flat int32
        [4 * layers] (pairs routed here, experts that received a row,
        the largest expert load, rows handed to the grouped matmuls; a
        layer after a layer): they ride back
        to the host in the step's one readback. A model that declares
        none gets its tokens as they are, and its compiled steps are
        what they were."""
        if not self._moe_layers:
            return tokens
        with jax.named_scope("lm_head"):
            return jnp.concatenate([
                tokens.reshape(-1),
                self.model.moe_step_stats().reshape(-1).astype(jnp.int32)])

    def _decode_fn(self, state_vals, pools, tokens, block_tables,
                   seq_lens):
        from ..core.dispatch import no_grad
        from ..core.tensor import Tensor

        self.metrics.on_decode_compile()        # trace-time counter
        with self.model.bind_state(self._names,
                                   self._dequant_state(state_vals)):
            with no_grad():
                views = self.cache.decode_views(pools, block_tables,
                                                seq_lens)
                logits, views = self.model.generate_step(
                    Tensor(tokens[:, None]), views, seq_lens)
        lv = logits._value if isinstance(logits, Tensor) else logits
        with jax.named_scope("lm_head"):
            nxt = jnp.argmax(lv[:, -1, :].astype(jnp.float32),
                             axis=-1).astype(jnp.int32)
        return self._with_moe_counters(nxt), [v.pool for v in views]

    def _suffix_prefill_fn(self, state_vals, pools, ids, table_row,
                           hist, true_len):
        """Cache-aware prefill: ids [1, P] (right-padded uncached
        suffix) runs at absolute positions hist..hist+true_len-1 over
        the slot's adopted pool history — the mixed ragged view with
        S == 1. ``hist`` and ``true_len`` are traced, so a hit and a
        miss (hist == 0) share the per-bucket compile."""
        from ..core.dispatch import no_grad
        from ..core.tensor import Tensor

        self.metrics.on_prefill_compile()       # trace-time counter
        hist_v = jnp.reshape(hist, (1,)).astype(jnp.int32)
        qlen_v = jnp.reshape(true_len, (1,)).astype(jnp.int32)
        with self.model.bind_state(self._names, list(state_vals)):
            with no_grad():
                views = [PagedMixedView(p, table_row[None, :], hist_v,
                                        qlen_v, self.block_size)
                         for p in pools]
                logits, views = self.model.generate_step(
                    Tensor(ids), views, hist_v, self._last_row(qlen_v))
        return self._greedy(logits)[0], [v.pool for v in views]

    def _mixed_fn(self, state_vals, pools, tokens, block_tables,
                  seq_lens, q_lens):
        """THE compiled step under chunked prefill: [S, C] ragged rows
        (decode rows q_len 1, prefill chunks up to C, idle rows 0) over
        fixed shapes — requests arriving, chunking, finishing and
        preempting never change a shape, so this traces EXACTLY once
        (it counts into decode_compiles; the compile-once contract
        holds with the flag on)."""
        from ..core.dispatch import no_grad
        from ..core.tensor import Tensor

        self.metrics.on_decode_compile()        # trace-time counter
        with self.model.bind_state(self._names,
                                   self._dequant_state(state_vals)):
            with no_grad():
                views = [PagedMixedView(p, block_tables, seq_lens,
                                        q_lens, self.block_size)
                         for p in pools]
                logits, views = self.model.generate_step(
                    Tensor(tokens), views, seq_lens,
                    self._last_row(q_lens))
        return self._greedy(logits), [v.pool for v in views]
