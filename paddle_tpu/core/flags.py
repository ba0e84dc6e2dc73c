"""Runtime flag system.

Analog of the reference's exported gflags
(/root/reference/paddle/phi/core/flags.cc, python paddle.set_flags at
python/paddle/fluid/framework.py:7630). Flags are plain process-global values,
bootstrapped from FLAGS_* environment variables at import, settable from
Python. TPU-relevant flags map onto XLA/JAX controls where one exists.
"""
from __future__ import annotations

import os

_DEFAULTS = {
    # numerics / debugging
    "FLAGS_check_nan_inf": False,
    "FLAGS_check_nan_inf_level": 0,
    "FLAGS_benchmark": False,
    # eager engine
    "FLAGS_retain_grad_for_all_tensor": False,
    # compile / cache behavior (XLA analogs of allocator & executor flags)
    "FLAGS_jit_cache_size": 4096,
    "FLAGS_use_bf16_matmul": True,  # prefer bfloat16 MXU matmuls under amp
    # minimum head_dim routed to the Pallas flash-attention kernel.
    # The kernel is numerically exact down to 64 (interpret-mode parity
    # tests) and Mosaic-compiles there (tests/test_tpu_lowering.py), but
    # it has only run on the chip at 128; 64 (e.g. ERNIE's 12x64 heads)
    # becomes the default when a ledger row says so (ROADMAP D2).
    "FLAGS_flash_min_head_dim": 128,
    # route the decoder loss tail through the streaming Pallas
    # lm_head+CE kernel (kernels/fused_ce.py) on compiled training
    # steps. Interpret-mode exact, and it compiles and runs inside the
    # real step on the chip (chip_smoke.py train_fused_ce); default off
    # until a ledger row decides it (ROADMAP D2).
    # COMPILED-STEP ONLY: the eager tape structurally cannot fuse (it
    # cannot differentiate through the kernel's custom_vjp) and takes
    # the unfused materialized-logits path with a loud one-time warning
    # — an eager-vs-compiled A/B under this flag compares different
    # loss tails and must not be read as a kernel speedup/slowdown.
    "FLAGS_fused_lm_head_ce": False,
    # dropout mask PRNG implementation: 'threefry' (default, the global
    # splittable PRNG) or 'rbg' (the TPU hardware RNG instruction —
    # much cheaper per bit for the big per-layer masks; statistical
    # quality is ample for dropout, and the mask stream stays
    # deterministic per key). Opt-in because it changes the mask
    # sequence for a given seed.
    "FLAGS_dropout_rng_impl": "threefry",
    "FLAGS_eager_delete_tensor_gb": 0.0,  # accepted, no-op under XLA GC
    "FLAGS_allocator_strategy": "xla",  # buffer assignment is XLA's
    "FLAGS_fraction_of_gpu_memory_to_use": 1.0,  # accepted for compat
    # executor (reference new_executor flags family)
    "FLAGS_use_native_interpreter": True,
    # distributed
    "FLAGS_distributed_barrier_timeout_s": 600,
    # quantized gradient communication (distributed/compress.py,
    # EQuARX-style block-scaled int8). Off = both collective paths are
    # bit-identical to the uncompressed build (test-pinned): the
    # compiled train step keeps its implicit fp32 psum/reduce-scatter
    # and the eager store wire format is unchanged. On = the compiled
    # step reduces grads via a bucketed two-phase quantized all-reduce
    # (error-feedback residuals carried in the step state) and float
    # eager all_reduce/reduce_scatter/all_gather payloads >= 1024
    # elements ship as int8+block-scales (~4x fewer wire bytes).
    "FLAGS_quantized_grad_sync": False,
    # stochastic rounding for the quantized sync (unbiased, stateless
    # alternative to error feedback; higher variance per step)
    "FLAGS_quantized_grad_sync_stochastic": False,
    # fused-communication bucket size threshold, MiB of fp32 grad
    # payload: small params coalesce until a bucket crosses this, so
    # the compiled step issues few large reductions XLA can overlap
    # with backward compute instead of many tiny ones
    "FLAGS_grad_sync_bucket_mb": 4.0,
    # metric time-series ring (monitor/timeseries.py): every registry
    # Counter/Gauge sample also appends (ts, value) to a bounded
    # per-series ring — the substrate for /debugz/timeseries, watchdog
    # bundle tails, and the perf sentinels. Off = the registry hot path
    # is unchanged (the hook slot stays None; test-pinned).
    "FLAGS_monitor_timeseries": False,
    # MFU/goodput attribution (monitor/perf.py): compiled train steps
    # publish mfu / model_flops / hbm_peak_bytes / per-step phase split
    # (compute vs comm vs host), the serving engine publishes per-token
    # goodput + KV-page occupancy. Costs one extra AOT lower+compile of
    # the step (for XLA cost/memory analysis) and one loss-scalar host
    # readback per step — opt-in for measurement runs, off on the
    # training hot path by default.
    "FLAGS_perf_attribution": False,
    # span journal (monitor/trace.py): per-request serving timelines
    # (queue/prefill/decode/preempted phase spans + token-milestone
    # events), per-step train spans with flight-recorder-linked comm
    # child spans, and TTFT/TPOT histogram bucket exemplars resolving
    # to trace ids. Off = emitters early-return and the registry
    # exemplar hook slot stays None (zero journal allocations, zero
    # threads, zero native calls on the hot path — test-pinned).
    # Served at /debugz/trace + /debugz/trace/{id}; merged into the
    # chrome timeline by tools/trace_merge.py --requests.
    "FLAGS_monitor_trace": False,
    # regression sentinels (monitor/perf.py) over the time-series ring:
    # NaN/inf loss, loss spike vs EWMA, throughput regression vs a
    # rolling baseline, grad-norm explosion. Each firing increments
    # perf_anomalies_total{kind}, drops a structured event into the
    # flight-recorder ring, and flips the /healthz degraded flag.
    # Enabling sentinels enables the time-series ring (they read it).
    "FLAGS_perf_sentinels": False,
    # fleet telemetry plane (monitor/fleet.py): each rank announces its
    # metrics endpoint in the TCPStore and a collector (rank
    # PT_FLEET_COLLECTOR_RANK, default 0, or a standalone process)
    # scrapes /metrics.json + /debugz/perf + /healthz from every rank,
    # fuses them into rank-labeled fleet series (counter sums, gauge
    # min/max/p50 spreads) served at /debugz/fleet* + /metrics/fleet,
    # flags stragglers (persistently slower than the fleet-median step
    # time -> fleet_straggler_total{rank}) BEFORE anything times out,
    # and pulls a fleet-wide capture (bundles + journal tails from all
    # ranks) when any rank's sentinel fires. Off = announce/identity
    # hooks are one flag branch: no server, no collector thread, no
    # store traffic (test-pinned, the PR-2/5/6 discipline).
    "FLAGS_monitor_fleet": False,
    # memory plane (monitor/memory.py): per-component device-memory
    # ledger (mem_device_bytes{component,job} reconciled against
    # allocator stats, mem_hbm_headroom_bytes{job} = capacity − static
    # ledger − compiled transient peak), OOM forensics on the hot
    # paths (oom_postmortem_rank{r}.json written before the failure
    # re-raises; deterministic mem.oom injection site), and a leak
    # sentinel firing perf_anomalies_total{kind="mem_leak"} on
    # steady-state growth. Engines latch the tracker ONCE at
    # construction; off = one attribute load + branch on the hot
    # paths — no threads, no native calls, no registry series, no jax
    # import (test-pinned, the PR-2/5/6 discipline).
    "FLAGS_monitor_memory": False,
    # continuous profiling plane (monitor/profile.py): an always-on
    # stdlib host sampling profiler (sys._current_frames() at
    # PT_PROFILE_HZ, folded stacks with component attribution, served
    # at /debugz/profile[-/folded]), anomaly-triggered one-shot device
    # capture windows (jax.profiler start/stop_trace around the next N
    # hot steps, armed by throughput-cliff/mem_leak sentinels, watchdog
    # stalls and fleet stragglers; cooldown + PT_PROFILE_MAX_CAPTURES,
    # defer-not-drop), and measured dispatch/blocked/gap step timers
    # (profile_*_seconds{job}) that make the analytic
    # perf_phase_seconds split falsifiable. Off = engines latch
    # step_hook()=None at construction and the hot paths pay one
    # attribute load + branch: no daemon threads, no native calls, no
    # profile_* series, both routes report disabled (test-pinned, the
    # PR-2/5/6 discipline).
    "FLAGS_monitor_profile": False,
    # SLO/error-budget plane + unified incident manager (monitor/slo.py
    # + monitor/incidents.py): declarative objectives (serving
    # TTFT/TPOT/e2e latency attainment + availability, training
    # step-time/goodput floors) judged over the PR-5 timeseries ring —
    # no new sampling path, the evaluator is a ring listener —
    # publishing slo_attainment_ratio / slo_error_budget_remaining_
    # ratio / slo_burn_rate with multi-window multi-burn-rate alerting
    # (fast+slow pairs on the monotonic clock; page vs ticket severity
    # from the pair). Every detector (perf sentinels, mem-leak,
    # watchdog stalls, fleet stragglers, OOM postmortems, router
    # evictions, burn-rate alerts) reports into ONE bounded incident
    # table (episode-keyed dedup, open->resolve lifecycle, evidence
    # links to the artifacts each already writes); /healthz "degraded"
    # derives from the open set. Off = open/resolve and the ring
    # listener hook are one flag branch: no threads, no native calls,
    # no slo_*/incident_* series, /debugz/slo + /debugz/incidents
    # report disabled, and /healthz is bit-identical to the
    # pre-incident build (test-pinned, the PR-2/5/6 discipline).
    "FLAGS_monitor_slo": False,
    # radix prefix cache over the serving engine's paged KV pool
    # (serving/prefix_cache.py): requests sharing a prompt prefix
    # (system prompts, few-shot headers) map their block-table head to
    # SHARED pages via a radix tree keyed on block_size token chunks;
    # admission charges only the uncached suffix, release decrefs
    # instead of freeing (finished/preempted prefixes stay warm), and
    # an LRU walk reclaims unreferenced cached pages under pressure
    # BEFORE any running request is preempted. Off = the allocator
    # behaves exactly as before (exclusive pages, release frees) and
    # engine outputs are bit-identical to the pre-cache build
    # (test-pinned). Latched at Engine construction.
    "FLAGS_serving_prefix_cache": False,
    # chunked prefill (serving/engine.py): long prompts prefill in
    # fixed-size chunks interleaved into the ONE compiled mixed step as
    # extra ragged rows next to the decode rows, so a long prefill no
    # longer stalls the whole decode batch's TPOT and the engine
    # compiles exactly one step function (decode_compiles == 1,
    # test-pinned; the trash-page scatter discipline makes padded rows
    # safe). Off = the split decode/prefill paths are unchanged.
    # Latched at Engine construction; chunk size is the Engine's
    # prefill_chunk argument.
    "FLAGS_serving_chunked_prefill": False,
    # int8 block-scaled KV-cache pages (serving/kv_cache.py): the paged
    # k/v pools are stored as int8 planes with per-(page, position,
    # head) fp32 scales living alongside them in KVBlockPool, quantized
    # at page-write time (the views' scatter) and dequantized inside the
    # paged-attention gather (kernels/quant.py discipline: amax/127,
    # zero-vector floor, non-finite poison) — ~3.8x pool capacity at
    # the same HBM byte budget for head_dim 64. COW clones and prefix
    # adoption carry the scale planes, so refcounted sharing works
    # unchanged on quantized pages. Off = pools stay fp32, no scale
    # planes exist, engine outputs are bit-identical to the pre-quant
    # build (test-pinned). Latched at Engine construction.
    "FLAGS_serving_quant_kv": False,
    # weight-only int8 block-scaled decode (serving/engine.py):
    # attention/MLP projection weights are quantized ONCE at engine
    # bind (block-scaled along the input axis) and dequantize-fused
    # into the memory-bound decode-row matmuls; the split prefill step
    # keeps fp32 weights (compute-bound rows gain nothing). Under
    # chunked prefill the ONE mixed step binds the quantized weights
    # for all rows — a prefill chunk rides as a decode-batch row.
    # Off = every step binds the fp32 state, outputs bit-identical
    # (test-pinned). Latched at Engine construction.
    "FLAGS_serving_quant_weights": False,
    # serving fleet plane (serving/fleet/): N data-parallel engine
    # replicas announce themselves in the TCPStore under
    # __sfleet/replica/{r} (endpoint + generation + capability
    # snapshot), renew a liveness lease on the elastic TTL machinery,
    # and a router (serving/fleet/router.py, tools/serving_router.py)
    # dispatches admitted requests over HTTP: prefix-affinity first
    # (router-side radix index over block_size token chunks), least
    # loaded as tie-break, nonce-idempotent bounded retry-with-reroute,
    # healthz-driven drain-and-reschedule, dead-lease evict +
    # affinity invalidation. Off = Replica/Router refuse to construct:
    # no lease/serve/router threads, no __sfleet store traffic, no
    # router_* series, and the /debugz/router routes report disabled
    # (test-pinned, the PR-2/5/6 discipline). Latched at Replica/
    # Router construction.
    "FLAGS_serving_fleet": False,
    # deterministic request record/replay journal (serving/replay.py,
    # tools/ptreplay.py): every admission captures what re-execution
    # needs — prompt token ids, sampling params, the engine's latched
    # flag snapshot (prefix x chunked x quant axes), weights
    # generation, capability snapshot — and every terminal stamps the
    # outcome digest (output ids + rolling token hash, phase timings,
    # preempt count, shed/expired reason) into a bounded journal
    # (PT_REPLAY_CAPACITY, finished-evicted-first). write_journal()
    # emits the versioned JSONL artifact tools/ptreplay.py re-drives a
    # REAL engine from and diffs token-for-token (--matrix bisects
    # which flag axis introduced a divergence; --against diffs two
    # recordings). Off = the engine's recorder handle stays None: zero
    # journal allocations, zero threads (this plane NEVER has
    # threads), zero replay_* series, wire/result payloads
    # bit-identical (test-pinned, the PR-2/5/6 discipline). Latched at
    # Engine construction.
    "FLAGS_serving_replay": False,
    # deterministic fault injection (paddle_tpu/resilience/faultinject).
    # Off = every injection site (store ops, eager collectives, serving
    # engine step, compiled train step) is one attribute load + branch:
    # no RNG, no locks, no threads, no native calls (test-pinned, the
    # PR-2/5/6 discipline). On = the seeded schedule in
    # PT_FAULT_SCHEDULE (site:kind[=arg][@when]; PT_FAULT_SEED) fires
    # reproducible faults so every detect->recover->resume path runs in
    # CI; firings count into faults_injected_total{site,kind}.
    "FLAGS_fault_inject": False,
    # logging
    "FLAGS_v": 0,
    # structured errors (reference FLAGS_call_stack_level, enforce.h):
    # 0 = message only, 1 = + structured context, 2 = + chained cause
    "FLAGS_call_stack_level": 1,
}

_flags = {}


def _coerce(default, raw):
    if isinstance(default, bool):
        return raw.lower() in ("1", "true", "yes", "on")
    if isinstance(default, int):
        return int(raw)
    if isinstance(default, float):
        return float(raw)
    return raw


def _bootstrap():
    for k, v in _DEFAULTS.items():
        raw = os.environ.get(k)
        _flags[k] = _coerce(v, raw) if raw is not None else v


_bootstrap()


def get_flags(name=None):
    if name is None:
        return dict(_flags)
    if isinstance(name, (list, tuple)):
        return {n: _flags[n] for n in name}
    return {name: _flags[name]}


def set_flags(d):
    for k, v in d.items():
        if k not in _flags:
            _flags[k] = v
        else:
            _flags[k] = _coerce(_DEFAULTS.get(k, v), str(v)) if isinstance(
                _DEFAULTS.get(k), (bool, int, float)
            ) and isinstance(v, str) else v


def flag(name, default=None):
    return _flags.get(name, default)
