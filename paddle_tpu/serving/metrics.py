"""Serving metrics: per-request latency breakdown + engine counters.

Schema (all plain dicts, json-ready): this is what ``Engine.stats()``
returns, and what ``benchmark/layer_metrics/`` reads.

per-request (``RequestMetrics.to_dict()``):
  queue_time_s     arrival -> first admission
  ttft_s           arrival -> first token out of prefill
  tpot_s           mean inter-token time after the first token
  e2e_s            arrival -> finished
  prompt_tokens / output_tokens / preemptions

engine (``EngineMetrics.to_dict()``):
  requests_in / requests_finished / preemptions
  prefill_runs / decode_steps / output_tokens
  decode_compiles / prefill_compiles   (jit trace counts — the
      compile-once contract tests assert decode_compiles == 1)
  throughput_tok_s                     output tokens / wall time since
      FIRST ADMISSION (not engine construction — an engine created
      before traffic arrives must not understate throughput)
  slot_occupancy                       mean active-slots / max_slots
      over decode steps (the 占用 utilization counter)

Every sample also flows through the framework-wide registry
(paddle_tpu.monitor): counters/gauges under ``serving_*`` plus
TTFT/TPOT/queue/e2e histograms, so serving shows up on the same
/metrics endpoint and JSON snapshots as training telemetry.

The engine's own account of a step (always on; ``Engine.step()`` takes
one ``now()`` at each phase boundary and keeps the rows in bounded rings
here, ``to_dict()`` reduces them when asked):
  host_ms        {schedule, upload, dispatch, readback, accept}: median
      milliseconds of each host phase over the recent steps that read
      back a decode step and no prefill; ``readback`` is the host
      waiting for the device, and while the engine runs ahead (below)
      the device works under the other four as well
  recent_steps   how many steps those medians are over
  prefill_ms     median milliseconds of one prefill, from building its
      ids to its token on the host, over the recent prefills: with the
      engine ahead, that includes the rest of the decode step queued on
      the device before it, since its token is read back in the next
      ``Engine.step()``
  itl_ms         {p50, p95} of the time between consecutive output
      tokens of one request, each stamped when its program's tokens
      reached the host (a prefill's token and the request's first decode
      token get their own two stamps), over the tokens of the recent
      steps: a step leaves one row of (gap, tokens that had it), so the
      ring covers the same stretch of time whatever the number of slots
  ahead          {steps, ahead, discarded_rows, share}: ``steps`` decode
      steps dispatched, of which ``ahead`` went while the tokens of the
      step before were still on the device, both over the recent steps,
      ``share`` = 100 x ahead / steps; ``discarded_rows`` counts, since
      construction, rows a step computed for a request that had already
      finished by EOS when they were read back. ``None`` before the
      first decode step
  moe            for a model that declares expert layers
      (``model.moe_layers``): the counters every compiled step hands
      back behind its tokens, a layer at a time (pairs routed to the
      experts held here, held experts that received a row, the largest
      load of one expert, rows of the sorted pair list handed to the
      grouped matmuls). ``pairs``, ``experts_touched``, ``load_max``
      and ``load_max_over_mean`` (largest load over pairs / experts
      held) are means over the recent decode steps and their layers;
      ``prefill_rows_over_pairs`` is the mean over the recent prefills
      and their layers of the rows handed to the grouped matmuls over
      the pairs routed here (1 is a layer that lays out what it
      computes; ``None`` while the ring holds no prefill);
      ``calls`` lists the recent programs, prefills included, newest
      last, as [real token rows, rows the program ran (a prefill's
      bucket, every slot of a decode step), 1 for a decode step, pairs
      a layer, experts touched a layer] for the kernel's roofline
      reader
An empty ring gives ``None`` for its entry, never 0. (``state`` and
``latent``, the slot_state and latent_pages sides of the cache, are
added by ``Engine.stats()``; ``latent.cached_tokens`` is the mean over
the recent decode steps of the tokens the cache held for the decoding
slots, from the same ring of step rows. ``ssm``, for a model that
declares state-space layers (``model.ssm_layers``): ``layers``,
``state_bytes_slot`` (one slot's state and convolution tail over all of
them) and ``active_slots``, the mean over the same recent decode steps
of the slots whose state the step read and wrote.)

Spans: ``span(name, **meta)`` IS ``jax.profiler.TraceAnnotation`` — a
span lands in the profiler's trace, on the device trace's clock, and
nowhere else; with no profiler session it costs under a microsecond.
The engine opens eight names at the same boundaries as the stamps:
``serving.schedule``, ``serving.prefill`` and ``serving.decode_step``
(each ends after its dispatch; its tokens are read back under
``serving.readback``, named by the same ``request=`` or ``step=``, in
the next ``Engine.step()``), ``serving.mixed_step`` (ends after its
readback), their children ``serving.upload``, ``serving.dispatch`` (and
the mixed step's ``serving.readback``), and ``serving.accept``.
"""
from __future__ import annotations

import collections
import itertools
import math
import statistics
import time

import jax
import numpy as np

from ..monitor import counter as _mcounter
from ..monitor import gauge as _mgauge
from ..monitor import histogram as _mhistogram
from ..monitor import trace as _mtrace

# shared-registry series (idempotent: re-imports / engine re-creation
# reuse the registered metric). Counters and histograms are cumulative
# across every engine in the process; instantaneous gauges
# (active slots, throughput) are labeled per engine instance —
# per-engine windows come from EngineMetrics.to_dict().
_REQUESTS = _mcounter(
    "serving_requests_total", "request lifecycle events",
    labelnames=("event",))
# graceful-degradation accounting (resilience layer): every request
# that terminates WITHOUT full service, by reason — queue_full /
# draining (load shed at admission), expired (queue-TTL deadline),
# preempt_cap (no eligible victim under the preemption cap), poison
# (its own step raised). The SLO reads shed rate next to goodput.
_SHED = _mcounter(
    "serving_requests_shed_total",
    "requests terminated without full service, by reason",
    labelnames=("reason",))
_PREFILLS = _mcounter("serving_prefill_runs_total",
                      "prefill executions (admissions + resumes)")
# radix prefix cache (FLAGS_serving_prefix_cache) + chunked prefill
# (FLAGS_serving_chunked_prefill) accounting: hit/lookup token counters
# give the cache hit RATE, eviction/insert/COW counters describe pool
# churn, chunk counter sizes the mixed step's prefill interleave. All
# zero (and series-free until first touch) with the flags off.
_PREFIX_HIT = _mcounter("serving_prefix_cache_hit_tokens_total",
                        "prompt tokens served from the radix prefix "
                        "cache instead of prefill compute")
_PREFIX_LOOKUP = _mcounter("serving_prefix_cache_lookup_tokens_total",
                           "prompt tokens looked up in the prefix cache "
                           "at admission")
_PREFIX_EVICT = _mcounter("serving_prefix_cache_evictions_total",
                          "cached pages reclaimed by the LRU walk")
_PREFIX_INSERT = _mcounter("serving_prefix_cache_insert_pages_total",
                           "full pages registered in the radix tree")
_COW_CLONES = _mcounter("serving_kv_cow_clones_total",
                        "copy-on-write page splits (shared prefix page "
                        "cloned before a divergent write)")
_PREFIX_PAGES = _mgauge("serving_prefix_cache_pages",
                        "pages currently held by the radix tree",
                        labelnames=("engine",))
_CHUNKS = _mcounter("serving_prefill_chunks_total",
                    "prefill chunks interleaved into the mixed step")
_DECODE_STEPS = _mcounter("serving_decode_steps_total",
                          "batched decode steps")
_TOKENS = _mcounter("serving_output_tokens_total", "tokens generated")
_COMPILES = _mcounter("serving_compiles_total",
                      "XLA traces of serving step functions",
                      labelnames=("fn",))
_ACTIVE = _mgauge("serving_active_slots",
                  "decoding slots in the current step",
                  labelnames=("engine",))
_THROUGHPUT = _mgauge("serving_throughput_tok_s",
                      "engine-lifetime output tokens/s",
                      labelnames=("engine",))
# perf attribution (monitor/perf.py, FLAGS_perf_attribution): goodput
# counts only FINISHED requests' tokens — work discarded by
# preempt-by-recompute is throughput but not goodput, so the gap
# between these two gauges IS the preemption tax
_GOODPUT = _mgauge("serving_goodput_tokens_per_s",
                   "finished-request output tokens/s (recomputed/"
                   "discarded work excluded)", labelnames=("engine",))
_KV_OCC = _mgauge("serving_kv_page_occupancy",
                  "fraction of usable KV pages held by live requests",
                  labelnames=("engine",))
# KV quantization (FLAGS_serving_quant_kv): gauge bound lazily on the
# first quant sample — with the flag off no series exists at all, and
# the counter is registered-but-untouched (series-free), the PR-2/5/6
# flags-off discipline
_KV_QUANT_PAGES = _mgauge("serving_kv_quant_pages",
                          "KV pages held as int8 block-scaled planes",
                          labelnames=("engine",))
_QUANT_DEQ_BYTES = _mcounter(
    "serving_quant_dequant_bytes_total",
    "int8 KV bytes dequantized inside paged-attention gathers")
_ENGINE_IDS = itertools.count()
# engine-labeled gauge series are pruned to this many newest engines —
# a process that constructs engines repeatedly (test suites, rolling
# reloads) must not grow the registry without bound
_MAX_ENGINE_SERIES = 32


def _prune_engine_series():
    for g in (_ACTIVE, _THROUGHPUT, _GOODPUT, _KV_OCC, _PREFIX_PAGES):
        keys = sorted(g._children, key=lambda k: int(k[0]))
        for k in keys[:-_MAX_ENGINE_SERIES]:
            g.remove(*k)
_LAT_BUCKETS = (.0025, .005, .01, .025, .05, .1, .25, .5, 1.0, 2.5,
                5.0, 10.0, 30.0)
_TTFT = _mhistogram("serving_ttft_seconds", "arrival -> first token",
                    buckets=_LAT_BUCKETS)
_TPOT = _mhistogram("serving_tpot_seconds",
                    "mean inter-token time per request",
                    buckets=_LAT_BUCKETS)
_QUEUE = _mhistogram("serving_queue_time_seconds",
                     "arrival -> first admission", buckets=_LAT_BUCKETS)
_E2E = _mhistogram("serving_e2e_seconds", "arrival -> finished",
                   buckets=_LAT_BUCKETS)


def now():
    return time.monotonic()


span = jax.profiler.TraceAnnotation

# the host phases of one Engine.step(), in the order a step runs them
HOST_PHASES = ("schedule", "upload", "dispatch", "readback", "accept")
# stats() is read at the end of a run and must not hold the warm-up's
# compiles, so the account is over recent rows, not since construction
STEP_RING = 512
PREFILL_RING = 512
# steps, not tokens: a ring of tokens is 256 steps at 64 slots and 64 at
# 256, and whether a long prefill fell inside it decided the percentile
GAP_RING = 2048
MOE_RING = 2048
# counters an expert layer's step returns (parallel/moe.py moe_forward)
MOE_COUNTERS = 4


def _weighted_percentile(ordered, q):
    """The value at rank ceil(q n) of a sample given as sorted
    (value, count) pairs, n the sum of the counts."""
    rank = max(math.ceil(q * sum(n for _, n in ordered)), 1)
    for value, n in ordered:
        rank -= n
        if rank <= 0:
            return value
    return ordered[-1][0]


def counter(name, value):
    """Named counter sample on the native trace timeline (no-op
    without the lib, and skipped entirely when the monitor is disabled
    — the disabled fast path must not touch native code)."""
    from ..monitor.registry import is_enabled

    if not is_enabled():
        return
    try:
        from ..core import native

        native.get_lib().pt_trace_counter(name.encode(), int(value))
    except Exception as e:
        from ..monitor.registry import warn_once

        warn_once(
            "serving.native_counter",
            "paddle_tpu.serving.metrics: native trace counter "
            "unavailable (registry metrics unaffected): %r" % (e,))


class RequestMetrics:
    def __init__(self, arrival_t):
        self.arrival_t = arrival_t
        self.first_admit_t = None
        self.first_token_t = None
        self.last_token_t = None
        self.finish_t = None
        self.prompt_tokens = 0
        self.output_tokens = 0
        self.preemptions = 0
        # span-journal trace id (monitor/trace.py): set by the engine
        # at admission when FLAGS_monitor_trace is on; observations
        # below then record bucket EXEMPLARS so a p99 outlier in any
        # latency histogram resolves back to this request's timeline.
        # None while the journal is off — the observes below pay one
        # attribute check and nothing else (test-pinned).
        self.trace_id = None
        # prefix-cache accounting (FLAGS_serving_prefix_cache): tokens
        # of this request's prompt looked up / served from the radix
        # cache, summed across admissions (a preempted request's resume
        # looks up again — and usually re-hits its own inserted pages)
        self.prefix_lookup_tokens = 0
        self.prefix_cached_tokens = 0
        # cached tokens at the FIRST admission only: the hit/miss
        # CLASSIFICATION bit. The cumulative count above also absorbs
        # resume re-matches (a preempted miss re-hits its own inserted
        # pages), which must not reclassify a miss-TTFT as a hit.
        self.prefix_cached_tokens_first = None

    def on_prefix_lookup(self, lookup_tokens, hit_tokens):
        if self.prefix_cached_tokens_first is None:
            self.prefix_cached_tokens_first = int(hit_tokens)
        self.prefix_lookup_tokens += int(lookup_tokens)
        self.prefix_cached_tokens += int(hit_tokens)
        _PREFIX_LOOKUP.inc(int(lookup_tokens))
        if hit_tokens:
            _PREFIX_HIT.inc(int(hit_tokens))

    def on_admit(self, t):
        if self.first_admit_t is None:
            self.first_admit_t = t
            with _mtrace.exemplar_context(self.trace_id):
                _QUEUE.observe(t - self.arrival_t)

    def on_first_token(self, t):
        if self.first_token_t is None:
            self.first_token_t = t
            with _mtrace.exemplar_context(self.trace_id):
                _TTFT.observe(t - self.arrival_t)

    def on_token(self, t):
        """One output token reached the host at ``t``; -> seconds since
        this request's previous one (None for its first). A preempted
        request keeps its stamp: the gap over its recompute is one its
        reader waited through."""
        last, self.last_token_t = self.last_token_t, t
        return None if last is None else t - last

    def on_finish(self, t, output_tokens):
        self.finish_t = t
        self.output_tokens = output_tokens
        with _mtrace.exemplar_context(self.trace_id):
            _E2E.observe(t - self.arrival_t)
            if self.first_token_t is not None and output_tokens > 1:
                _TPOT.observe((t - self.first_token_t)
                              / (output_tokens - 1))

    def to_dict(self):
        ttft = (None if self.first_token_t is None
                else self.first_token_t - self.arrival_t)
        tpot = None
        if (self.finish_t is not None and self.first_token_t is not None
                and self.output_tokens > 1):
            tpot = ((self.finish_t - self.first_token_t)
                    / (self.output_tokens - 1))
        return {
            "queue_time_s": (None if self.first_admit_t is None
                             else self.first_admit_t - self.arrival_t),
            "ttft_s": ttft,
            "tpot_s": tpot,
            "e2e_s": (None if self.finish_t is None
                      else self.finish_t - self.arrival_t),
            "prompt_tokens": self.prompt_tokens,
            "output_tokens": self.output_tokens,
            "preemptions": self.preemptions,
            "prefix_cached_tokens": self.prefix_cached_tokens,
            "prefix_cached_tokens_first": (
                self.prefix_cached_tokens_first or 0),
        }


class EngineMetrics:
    def __init__(self, max_slots):
        self.max_slots = max_slots
        # instantaneous gauges are per engine instance: two engines in
        # one process must not overwrite each other's last-write-wins
        # series (bind the children once — no per-step dict lookups)
        eid = str(next(_ENGINE_IDS))
        self._active_gauge = _ACTIVE.labels(engine=eid)
        self._throughput_gauge = _THROUGHPUT.labels(engine=eid)
        self._goodput_gauge = _GOODPUT.labels(engine=eid)
        self._kv_occ_gauge = _KV_OCC.labels(engine=eid)
        # bound lazily on the first prefix-cache sample: with the flags
        # off no serving_prefix_cache_pages series exists at all
        self._eid = eid
        self._prefix_pages_gauge = None
        self._quant_pages_gauge = None
        _prune_engine_series()
        # wall clock starts at FIRST ADMISSION, not construction: an
        # engine built ahead of traffic must not understate throughput
        self.start_t = None
        self.requests_in = 0
        self.requests_finished = 0
        self.requests_shed = 0
        self.shed_by_reason = {}
        self.preemptions = 0
        self.prefill_runs = 0
        self.decode_steps = 0
        self.output_tokens = 0
        self.finished_output_tokens = 0
        self.decode_compiles = 0
        self.prefill_compiles = 0
        self._occupancy_sum = 0
        self._kv_occupancy = 0.0
        # prefix cache / chunked prefill (FLAGS_serving_*; all stay 0
        # with the flags off)
        self.prefix_hit_tokens = 0
        self.prefix_lookup_tokens = 0
        self.prefix_evictions = 0
        self.prefix_insert_pages = 0
        self.prefix_cached_pages = 0
        self.cow_clones = 0
        self.prefill_chunks = 0
        # KV quantization (FLAGS_serving_quant_kv; 0 with the flag off)
        self.kv_quant_pages = 0
        self.quant_dequant_bytes = 0
        # the engine's account of its own time (module docstring):
        # phase_s is the open step's seconds by host phase, which the
        # engine adds to at each boundary; a finished step's row is
        # (*phase seconds, prefills run, rows decoded)
        self.steps = collections.deque(maxlen=STEP_RING)
        self.prefills = collections.deque(maxlen=PREFILL_RING)
        # one row a step: ((gap seconds, tokens that had it), ...); the
        # open step's gaps are merged in _gaps until it ends
        self.token_gaps = collections.deque(maxlen=GAP_RING)
        self._gaps = {}
        self.on_step_begin()
        # one row a compiled step of a model with expert layers:
        # (real rows, rows run, int32 [layers, 4] counters, decode step?)
        self.moe_calls = collections.deque(maxlen=MOE_RING)
        self.moe_experts_held = 0
        # one bool a dispatched decode step: was the step before it
        # still unread?
        self.ahead = collections.deque(maxlen=STEP_RING)
        self.decode_dispatches = 0
        self.discarded_rows = 0

    # -- engine hooks (mirror every sample into the shared registry) ---

    def on_request_in(self):
        self.requests_in += 1
        _REQUESTS.labels(event="in").inc()

    def on_request_finished(self, output_tokens=0):
        self.requests_finished += 1
        self.finished_output_tokens += int(output_tokens)
        _REQUESTS.labels(event="finished").inc()
        if self.start_t is not None:
            self._note_perf_job()

    def on_request_shed(self, reason):
        """One request terminated without full service (expired /
        queue_full / draining / preempt_cap / poison)."""
        self.requests_shed += 1
        self.shed_by_reason[reason] = \
            self.shed_by_reason.get(reason, 0) + 1
        _SHED.labels(reason=reason).inc()
        _REQUESTS.labels(event="shed").inc()

    def on_preemption(self):
        self.preemptions += 1
        _REQUESTS.labels(event="preempted").inc()

    def on_admission(self):
        if self.start_t is None:
            self.start_t = now()

    def on_prefill_run(self):
        self.prefill_runs += 1
        _PREFILLS.inc()

    def on_prefill_chunk(self):
        self.prefill_chunks += 1
        _CHUNKS.inc()

    def on_prefix_stats(self, pc_stats, cow_clones):
        """Engine-pushed snapshot of the radix cache counters (called
        once per engine step with the cache on; the registry series get
        the DELTAS so counters stay monotone across engines)."""
        if self._prefix_pages_gauge is None:
            self._prefix_pages_gauge = _PREFIX_PAGES.labels(
                engine=self._eid)
        # hit/lookup token counters are incremented per-request in
        # on_prefix_lookup — here only the engine-dict mirrors update
        d = pc_stats["evicted_pages"] - self.prefix_evictions
        if d:
            _PREFIX_EVICT.inc(d)
        d = pc_stats["inserted_pages"] - self.prefix_insert_pages
        if d:
            _PREFIX_INSERT.inc(d)
        d = cow_clones - self.cow_clones
        if d:
            _COW_CLONES.inc(d)
        self.prefix_hit_tokens = pc_stats["hit_tokens"]
        self.prefix_lookup_tokens = pc_stats["lookup_tokens"]
        self.prefix_evictions = pc_stats["evicted_pages"]
        self.prefix_insert_pages = pc_stats["inserted_pages"]
        self.prefix_cached_pages = pc_stats["cached_pages"]
        self.cow_clones = cow_clones
        self._prefix_pages_gauge.set(pc_stats["cached_pages"])

    def on_quant_step(self, pages_used, dequant_bytes):
        """Engine-pushed quant-KV sample, once per decode/mixed step
        with FLAGS_serving_quant_kv on: the live int8 page count and
        the int8 bytes the step's attention gathers dequantized."""
        if self._quant_pages_gauge is None:
            self._quant_pages_gauge = _KV_QUANT_PAGES.labels(
                engine=self._eid)
        self.kv_quant_pages = pages_used
        self._quant_pages_gauge.set(pages_used)
        if dequant_bytes:
            self.quant_dequant_bytes += int(dequant_bytes)
            _QUANT_DEQ_BYTES.inc(int(dequant_bytes))

    def on_output_token(self, gap_s=None):
        """``gap_s``: seconds since the same request's previous token
        (``RequestMetrics.on_token``)."""
        self.output_tokens += 1
        _TOKENS.inc()
        if gap_s is not None:
            self._gaps[gap_s] = self._gaps.get(gap_s, 0) + 1

    def _close_gaps(self):
        if self._gaps:
            self.token_gaps.append(tuple(self._gaps.items()))
            self._gaps = {}

    def gap_rows(self):
        """[(gap seconds, tokens that had it)] of the recent steps."""
        # list() copies a deque in one C call, so a reader on another
        # thread never sees it mid-append
        return [pair for row in list(self.token_gaps) for pair in row]

    def on_step_begin(self):
        self._close_gaps()      # of a step that failed before its end
        self.phase_s = dict.fromkeys(HOST_PHASES, 0.0)
        self._prefills_before = self.prefill_runs
        self._rows = 0
        self._live_tokens = 0

    def on_step_end(self):
        """Close the open step's row. A step that found nothing to do
        leaves none, so polling an idle engine does not push the
        working steps out of the ring."""
        self._close_gaps()
        prefills = self.prefill_runs - self._prefills_before
        if self._rows or prefills:
            self.steps.append(
                (*self.phase_s.values(), self._live_tokens, prefills,
                 self._rows))

    def live_tokens_mean(self):
        """Mean, over the recent steps in which a decode ran, of the
        tokens the cache held for the decoding slots when it ran (the
        engine counts them for a latent cache only); None while empty."""
        live = [r[-3] for r in list(self.steps) if r[-1]]
        return statistics.fmean(live) if live else None

    def active_slots_mean(self):
        """Mean, over the recent steps in which a decode ran, of the
        slots it decoded: the rows whose recurrent state a state-space
        layer's step read and wrote; None while empty."""
        rows = [r[-1] for r in list(self.steps) if r[-1]]
        return statistics.fmean(rows) if rows else None

    def on_prefill_done(self, seconds, tokens, bucket):
        self.prefills.append((seconds, tokens, bucket))

    def on_moe_call(self, counters, rows, rows_run, decode):
        """``counters``: the flat int32 [4 * layers] a step returned
        behind its tokens; ``rows``: its real token rows; ``rows_run``:
        the rows the program ran (a prefill's bucket, every slot of a
        decode step)."""
        self.moe_calls.append(
            (int(rows), int(rows_run),
             np.asarray(counters).reshape(-1, MOE_COUNTERS), bool(decode)))

    def _moe_dict(self):
        calls = list(self.moe_calls)
        steps = [c for _, _, c, decode in calls if decode]
        if not steps:
            return None
        held = max(self.moe_experts_held, 1)
        per = np.stack(steps).astype(np.float64)        # [n, layers, 4]
        pairs, touched, largest = per[..., 0], per[..., 1], per[..., 2]
        prefills = [c for _, _, c, decode in calls if not decode]
        laid_out = None
        if prefills:
            laid = np.stack(prefills).astype(np.float64)
            laid_out = float(
                (laid[..., 3] / np.maximum(laid[..., 0], 1.0)).mean())
        return {
            "layers": int(per.shape[1]),
            "experts_held": self.moe_experts_held,
            "pairs": float(pairs.mean()),
            "experts_touched": float(touched.mean()),
            "load_max": float(largest.mean()),
            "load_max_over_mean": float(
                (largest / np.maximum(pairs / held, 1e-9)).mean()),
            "recent_steps": len(steps),
            "prefill_rows_over_pairs": laid_out,
            "calls": [[rows, rows_run, int(decode), c[:, 0].tolist(),
                       c[:, 1].tolist()]
                      for rows, rows_run, c, decode in calls],
        }

    def on_decode_dispatch(self, ahead):
        """A decode step went to the device; ``ahead``: the tokens of
        the step before it were not on the host yet."""
        self.decode_dispatches += 1
        self.ahead.append(bool(ahead))

    def on_discarded_row(self):
        """A step's row was read back for a request that had already
        finished by EOS: computed, and thrown away."""
        self.discarded_rows += 1

    def _ahead_dict(self):
        ring = list(self.ahead)
        if not ring:
            return None
        ahead = sum(ring)
        return {"steps": len(ring), "ahead": ahead,
                "discarded_rows": self.discarded_rows,
                "share": 100.0 * ahead / len(ring)}

    def on_decode_compile(self):
        self.decode_compiles += 1
        _COMPILES.labels(fn="decode").inc()

    def on_prefill_compile(self):
        self.prefill_compiles += 1
        _COMPILES.labels(fn="prefill").inc()

    def on_decode_step(self, active_slots, live_tokens=0):
        self.decode_steps += 1
        self._rows = active_slots
        self._live_tokens = live_tokens
        self._occupancy_sum += active_slots
        _DECODE_STEPS.inc()
        self._active_gauge.set(active_slots)
        # the throughput gauge updates on the WRITE path (here, once per
        # step) so /metrics scrapes are live — not only when something
        # happens to call to_dict()
        if self.start_t is not None:
            self._throughput_gauge.set(self.output_tokens
                                       / max(now() - self.start_t, 1e-9))
        counter("serving.active_slots", active_slots)

    def on_kv_occupancy(self, occupancy):
        """Engine-reported KV-page occupancy (used pages / usable) —
        updated per step under FLAGS_perf_attribution, and mirrored
        into the /debugz/perf payload with the goodput numbers."""
        self._kv_occupancy = occupancy
        self._kv_occ_gauge.set(occupancy)
        self._note_perf_job()

    def _note_perf_job(self):
        """Goodput gauge + /debugz/perf mirror, uniformly flag-gated:
        with attribution off this is an early return — no gauge series
        appears, the payload stays empty (test-pinned), and a scraper
        can read the flag state from the series' presence."""
        try:
            from ..monitor import perf as _perf

            if not _perf.attribution_enabled():
                return
            wall = (max(now() - self.start_t, 1e-9)
                    if self.start_t is not None else 0.0)
            if wall:
                self._goodput_gauge.set(
                    self.finished_output_tokens / wall)
            _perf.note_job(
                "serving",
                goodput_tokens_per_s=(self.finished_output_tokens / wall
                                      if wall else 0.0),
                throughput_tokens_per_s=(self.output_tokens / wall
                                         if wall else 0.0),
                kv_page_occupancy=self._kv_occupancy,
                output_tokens=self.output_tokens,
                finished_output_tokens=self.finished_output_tokens,
                preemptions=self.preemptions,
                decode_steps=self.decode_steps,
                prefix_hit_tokens=self.prefix_hit_tokens,
                prefix_cached_pages=self.prefix_cached_pages,
                prefill_chunks=self.prefill_chunks)
        except Exception as e:
            from ..monitor.registry import warn_once

            warn_once(
                "serving.note_perf_job",
                "paddle_tpu.serving.metrics: perf-job attribution "
                "failed (serving unaffected, goodput series stop): "
                "%r" % (e,))

    def to_dict(self):
        wall = (max(now() - self.start_t, 1e-9)
                if self.start_t is not None else 0.0)
        occ = (self._occupancy_sum / (self.decode_steps * self.max_slots)
               if self.decode_steps else 0.0)
        throughput = self.output_tokens / wall if wall else 0.0
        # list()/sorted() copy a deque in one C call, so a reader on
        # another thread (fleet/replica.py) never sees one mid-append
        decode_only = [r for r in list(self.steps) if r[-1] and not r[-2]]
        prefills = list(self.prefills)
        gaps = sorted(self.gap_rows())
        return {
            "requests_in": self.requests_in,
            "requests_finished": self.requests_finished,
            "requests_shed": self.requests_shed,
            "shed_by_reason": dict(self.shed_by_reason),
            "preemptions": self.preemptions,
            "prefill_runs": self.prefill_runs,
            "decode_steps": self.decode_steps,
            "output_tokens": self.output_tokens,
            "finished_output_tokens": self.finished_output_tokens,
            "decode_compiles": self.decode_compiles,
            "prefill_compiles": self.prefill_compiles,
            "wall_s": wall,
            "throughput_tok_s": throughput,
            "goodput_tok_s": (self.finished_output_tokens / wall
                              if wall else 0.0),
            "slot_occupancy": occ,
            "kv_page_occupancy": self._kv_occupancy,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prefix_lookup_tokens": self.prefix_lookup_tokens,
            "prefix_evictions": self.prefix_evictions,
            "prefix_insert_pages": self.prefix_insert_pages,
            "prefix_cached_pages": self.prefix_cached_pages,
            "cow_clones": self.cow_clones,
            "prefill_chunks": self.prefill_chunks,
            "kv_quant_pages": self.kv_quant_pages,
            "quant_dequant_bytes": self.quant_dequant_bytes,
            "host_ms": ({
                phase: 1e3 * statistics.median(r[i] for r in decode_only)
                for i, phase in enumerate(HOST_PHASES)}
                if decode_only else None),
            "recent_steps": len(decode_only),
            "prefill_ms": (1e3 * statistics.median(r[0] for r in prefills)
                           if prefills else None),
            "itl_ms": ({"p50": 1e3 * _weighted_percentile(gaps, 0.50),
                        "p95": 1e3 * _weighted_percentile(gaps, 0.95)}
                       if gaps else None),
            "moe": self._moe_dict(),
            "ahead": self._ahead_dict(),
        }
