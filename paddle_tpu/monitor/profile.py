"""ptprof — continuous profiling plane: always-on host sampler, anomaly
capture windows, measured phase reconciliation.

Every timing attribution the monitor stack owned before this module was
analytic or bracket-derived: ``perf_phase_seconds`` comes from XLA
cost_analysis plus flight-recorder watermarks, and the only MEASURED
profiles were manual ``paddle_tpu/profiler`` Xprof sessions someone had
to start by hand — so the profile you got was never the profile of the
*bad* steps. Three capabilities close that gap (the seventh pillar of
the division of labor: **profile = where the time measurably went**):

1. **Always-on host sampling profiler** — a stdlib-only daemon thread
   samples ``sys._current_frames()`` at ``PT_PROFILE_HZ`` (default 19,
   deliberately off the round numbers so the sampler never phase-locks
   to a 10/20 Hz periodic workload) on the MONOTONIC clock, folds each
   thread's stack into a bounded aggregation table (cap
   ``PT_PROFILE_MAX_STACKS``; past it samples collapse into
   per-component overflow buckets — attribution survives saturation,
   growth never goes unbounded), and attributes every sample to a
   component
   (``scheduler`` / ``store-io`` / ``device-wait`` / ``tokenize`` /
   ``other``) by leaf-most frame-to-module matching. Exported as
   collapsed-stack text (``/debugz/profile/folded`` — flamegraph.pl
   input) and a top-K summary (``/debugz/profile``). The sampler
   measures its OWN time per tick; the overhead bound (self-time < 1%
   of wall at the default hz) is test-pinned.

2. **Anomaly-triggered device capture windows** —
   ``capture_window(steps=N)`` / ``arm_capture()`` arms a ONE-SHOT
   ``jax.profiler.start_trace``/``stop_trace`` window around the next N
   hot-step invocations (``CompiledTrainStep.__call__``/``run_steps``,
   serving ``Engine.step``), through the ``paddle_tpu/profiler`` Xprof
   session guard so ptprof and a manual ``Profiler(with_xprof=True)``
   can never double-``start_trace``. Armed automatically by perf
   sentinels (throughput-cliff, mem_leak), watchdog stall escalation,
   and fresh fleet stragglers — so the Xprof artifact is of the
   ANOMALOUS steps, not whatever someone profiled by hand later.
   Cooldown + ``PT_PROFILE_MAX_CAPTURES`` cap, defer-not-drop (the
   PR-8 fleet-capture discipline): a trigger landing inside the
   cooldown queues and fires on the next eligible step. Each finished
   window writes ``profile_capture_<ts>/`` (manifest + per-window
   folded host stacks + the Xprof trace dir when the backend
   cooperates; host-only capture is still a capture).

3. **Measured phase reconciliation** — hot steps gain a dispatch/block
   timer: ``profile_dispatch_seconds{job}`` (call issue → handles
   returned), ``profile_host_blocked_seconds{job}`` (explicit
   ``block_until_ready`` on the step result), and
   ``profile_host_gap_seconds{job}`` (host time between consecutive
   steps). Mirrored into the /debugz/perf job rows (``perf.note_job``)
   so ``tools/perf_report.py`` can diff MEASURED against PR-5's
   analytic ``perf_phase_seconds`` — the analytic model becomes
   falsifiable, and the exposed-comm residual (measured step − analytic
   compute) is the scoreboard ROADMAP item 4 starts from. The serving
   engine additionally feeds per-phase host timers
   (``note_phase("prefill"|"decode", dt)``), shown as ``phases`` in
   the job's ``/debugz/profile`` row.

Discipline (the PR-2/5/6/12 contract, test-pinned): default OFF via
``FLAGS_monitor_profile``. Engines latch ``step_hook(job)`` ONCE at
construction (the ptlint hot-path-latch convention) — while off the hot
paths pay one attribute load + branch: no daemon threads, no native
calls, no ``profile_*`` registry series, both debugz routes answer
``enabled: false``. Module import stays stdlib-only; jax is only ever
imported lazily behind the enabled paths (``block_until_ready``, the
Xprof window), so bare workers scraping the route never drag an
accelerator backend in.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

from . import perf as _perf
from . import registry as _registry
from .timeseries import _flag

_THREAD_NAME = "pt-profiler"

# -- metrics (shared registry; series appear only while enabled) -------------

_DISPATCH = _registry.gauge(
    "profile_dispatch_seconds",
    "measured host wall of the last hot-step call (issue -> handles "
    "returned, incl. any implicit blocking inside the call)",
    labelnames=("job",))
_BLOCKED = _registry.gauge(
    "profile_host_blocked_seconds",
    "measured host wall spent in block_until_ready on the last step's "
    "result AFTER the call returned (device time exposed to the host)",
    labelnames=("job",))
_GAP = _registry.gauge(
    "profile_host_gap_seconds",
    "measured host wall between the previous step's completion and "
    "this step's dispatch (input pipeline / scheduler / host tax)",
    labelnames=("job",))
_SAMPLES = _registry.counter(
    "profile_samples_total",
    "host sampling-profiler samples taken (one per thread-sweep tick)")
_CAPTURES = _registry.counter(
    "profile_captures_total",
    "device capture windows completed, by arming reason",
    labelnames=("reason",))

# sentinel kinds that arm a capture window automatically (monitor/perf.py
# calls on_anomaly on every firing; only these kinds are profile-shaped
# — a NaN loss needs no timeline, a cliff or a leak does)
CAPTURE_KINDS = ("throughput_regression", "mem_leak")

# component attribution: leaf-most frame whose "filename:funcname" key
# contains one of the patterns wins; order = per-frame priority. The
# division: scheduler = batching/admission host logic, store-io = KV
# store + HTTP plumbing, device-wait = the jax dispatch/block surface,
# tokenize = text preprocessing, other = everything else.
COMPONENT_PATTERNS = (
    ("device-wait", ("/jax/", "jax/_src", "jaxlib",
                     "block_until_ready")),
    ("scheduler", ("serving/scheduler.py", "serving/engine.py",
                   "parallel/engine.py", "parallel/pipeline")),
    ("store-io", ("distributed/store.py", "fleet/utils/http_server",
                  "monitor/fleet.py", "monitor/exporter.py",
                  "socketserver", "http/server", "http/client",
                  "socket.py")),
    # anchored to tokenizer modules/functions — a bare "tokenize"
    # substring would claim CPython's stdlib tokenize.py (linecache/
    # inspect render paths) for text preprocessing it never did
    ("tokenize", ("text/tokenizer.py", "tokenizer", ":tokenize",
                  "_tokenizer_")),
)

_STACK_DEPTH = 48


class _ProfState:
    __slots__ = ("lock", "thread", "stop_event", "hz", "samples",
                 "self_time_s", "started_mono", "stacks", "overflow",
                 "max_stacks", "jobs", "captures", "pending", "window",
                 "last_capture_end", "cooldown_s", "max_captures")

    def __init__(self):
        self.lock = threading.Lock()
        self.thread = None
        self.stop_event = None
        self.hz = _env_float("PT_PROFILE_HZ", 19.0)
        self.samples = 0
        self.self_time_s = 0.0
        self.started_mono = None
        self.stacks = {}        # folded key -> {count, component}
        self.overflow = 0       # samples collapsed past max_stacks
        self.max_stacks = _env_int("PT_PROFILE_MAX_STACKS", 512)
        self.jobs = {}          # job -> cumulative measured totals
        self.captures = []      # finished capture records
        self.pending = []       # queued triggers (defer-not-drop)
        self.window = None      # the ONE in-flight capture window
        self.last_capture_end = None    # monotonic
        self.cooldown_s = _env_float("PT_PROFILE_CAPTURE_COOLDOWN_S",
                                     60.0)
        self.max_captures = _env_int("PT_PROFILE_MAX_CAPTURES", 4)


def _env_float(name, default):
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_int(name, default):
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


_state = _ProfState()


def is_enabled():
    return _flag("FLAGS_monitor_profile")


def _rank():
    try:
        from ..distributed import process_group as _pg

        pg = _pg.get_world_group()
        if pg is not None:
            return int(pg.rank)
    except Exception as e:
        _registry.warn_once(
            "profile.rank",
            "paddle_tpu.monitor.profile: world-group rank lookup "
            "failed (artifacts file as rank from env/0): %r" % (e,))
    try:
        return int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    except ValueError:
        return 0


# -- the host sampling profiler ----------------------------------------------

def _component_of(key):
    """Component of one frame key ("filename:funcname"), or None."""
    for comp, pats in COMPONENT_PATTERNS:
        for p in pats:
            if p in key:
                return comp
    return None


def _modname(filename):
    base = os.path.basename(filename)
    return base[:-3] if base.endswith(".py") else base


def _fold_thread(frame):
    """(folded_stack, component) of one thread's current frame chain.
    Manual f_back walk — no linecache/IO on the sampling tick."""
    parts = []
    comp = None
    f = frame
    depth = 0
    while f is not None and depth < _STACK_DEPTH:
        code = f.f_code
        if comp is None:
            c = _component_of("%s:%s" % (code.co_filename, code.co_name))
            if c is not None:
                comp = c
        parts.append("%s.%s" % (_modname(code.co_filename),
                                code.co_name))
        f = f.f_back
        depth += 1
    parts.reverse()     # collapsed-stack convention: root first
    return ";".join(parts), comp or "other"


def _sample_once():
    """One sweep over every thread but the sampler's own. Self-time is
    measured on the monotonic clock around the sweep — the overhead
    bound the tests pin reads these two counters."""
    t0 = time.monotonic()
    me = threading.get_ident()
    names = {t.ident: t.name for t in threading.enumerate()}
    folded = []
    for tid, frame in sys._current_frames().items():
        if tid == me:
            continue
        stack, comp = _fold_thread(frame)
        name = names.get(tid, "?")
        folded.append(("%s;%s" % (name, stack), comp))
    with _state.lock:
        for key, comp in folded:
            rec = _state.stacks.get(key)
            if rec is not None:
                rec["count"] += 1
            elif len(_state.stacks) < _state.max_stacks:
                _state.stacks[key] = {"count": 1, "component": comp}
            else:
                # saturated table: the sample still counts, collapsed
                # into ONE per-component overflow bucket (bounded by
                # the component set) — component attribution survives
                # saturation even when the exact stack is lost, so a
                # capture window opened after a long churny compile
                # still names where the time went
                _state.overflow += 1
                okey = "(overflow);%s" % comp
                orec = _state.stacks.get(okey)
                if orec is None:
                    orec = _state.stacks[okey] = {"count": 0,
                                                  "component": comp}
                orec["count"] += 1
        _state.samples += 1
        _state.self_time_s += time.monotonic() - t0
    _SAMPLES.inc()


def _sampler_run(stop_event, interval_s):
    while not stop_event.wait(interval_s):
        try:
            _sample_once()
        except Exception as e:
            # the profiler eating its own tick failures is the exact
            # blind spot this repo lints against: say it once, keep
            # sampling
            _registry.warn_once(
                "profile.sample_tick",
                "paddle_tpu.monitor.profile: sampler tick failed "
                "(sampler keeps running): %r" % (e,))


def start_sampler(hz=None):
    """Start (or return) the process-wide sampling daemon thread.
    Refuses while ``FLAGS_monitor_profile`` is off — the disabled path
    must stay thread-free even against an explicit call."""
    if not is_enabled():
        return None
    with _state.lock:
        if _state.thread is not None and _state.thread.is_alive():
            return _state.thread
        if hz is not None:
            _state.hz = float(hz)
        _state.hz = max(_state.hz, 0.1)
        # a (re)start opens a FRESH sampling window: counters, self-time
        # and the folded table reset together so overhead_share and the
        # "each count ≈ 1/hz s over window_s" time-weighting stay
        # internally consistent — snapshot before stopping if the old
        # window matters
        _state.samples = 0
        _state.self_time_s = 0.0
        _state.stacks = {}
        _state.overflow = 0
        _state.started_mono = time.monotonic()
        _state.stop_event = threading.Event()
        _state.thread = threading.Thread(
            target=_sampler_run,
            args=(_state.stop_event, 1.0 / _state.hz),
            name=_THREAD_NAME, daemon=True)
        _state.thread.start()
        return _state.thread


def stop_sampler():
    with _state.lock:
        ev, t = _state.stop_event, _state.thread
        _state.stop_event = None
        _state.thread = None
    if ev is not None:
        ev.set()
    if t is not None and t.is_alive():
        t.join(timeout=5)


def sampler_running():
    t = _state.thread
    return t is not None and t.is_alive()


def folded_snapshot():
    """{folded_stack: {count, component}} — cumulative since sampler
    start. Each count is one sample ≈ 1/hz seconds of that stack being
    live (the time-weighted view the watchdog bundle embeds)."""
    with _state.lock:
        return {k: dict(v) for k, v in _state.stacks.items()}


def component_totals(stacks=None):
    """Sample counts and shares by component."""
    if stacks is None:
        stacks = folded_snapshot()
    counts = {}
    for rec in stacks.values():
        counts[rec["component"]] = \
            counts.get(rec["component"], 0) + rec["count"]
    total = sum(counts.values())
    return {comp: {"samples": n,
                   "share": (n / total) if total else 0.0}
            for comp, n in sorted(counts.items())}


def folded_text(stacks=None, k=None):
    """Collapsed-stack text ("stack count" lines, count-descending) —
    flamegraph.pl / speedscope input."""
    if stacks is None:
        stacks = folded_snapshot()
    rows = sorted(stacks.items(), key=lambda kv: -kv[1]["count"])
    if k is not None:
        rows = rows[:int(k)]
    return "".join("%s %d\n" % (key, rec["count"]) for key, rec in rows)


# -- anomaly-triggered device capture windows --------------------------------

def arm_capture(steps=None, reason="manual", detail=None):
    """Queue a one-shot device-capture window around the next ``steps``
    hot-step invocations. Defer-not-drop: a trigger landing while a
    window is in flight or inside the cooldown stays queued and fires
    at the next eligible step (its watermark already advanced and will
    not re-fire on its own — the PR-8 discipline). Returns True when
    the trigger was queued (False while the plane is off)."""
    if not is_enabled():
        return False
    rec = {"reason": str(reason),
           "steps": max(int(steps if steps is not None
                            else _env_int("PT_PROFILE_CAPTURE_STEPS", 4)),
                        1),
           "detail": dict(detail) if detail else {},
           "armed_at": time.time()}
    with _state.lock:
        _state.pending.append(rec)
    return True


def capture_window(steps=4, reason="manual", detail=None):
    """The manual-arming spelling from the ISSUE: identical to
    ``arm_capture`` with an explicit step count."""
    return arm_capture(steps=steps, reason=reason, detail=detail)


def on_anomaly(kind):
    """perf-sentinel hook (monitor/perf.py calls this on every firing):
    profile-shaped kinds (CAPTURE_KINDS) arm a capture window so the
    Xprof trace covers the steps right after the anomaly."""
    if str(kind) in CAPTURE_KINDS:
        return arm_capture(reason="sentinel:%s" % kind)
    return False


def on_stall(stalls=None):
    """Watchdog escalation hook: a fresh stall episode arms a capture
    window — if the wedge clears (or recovery restarts the loop), the
    first steps after it get a measured profile."""
    detail = None
    if stalls:
        detail = {"stalls": [
            {"heartbeat": s.get("heartbeat"), "phase": s.get("phase"),
             "age_s": s.get("age_s")} for s in stalls]}
    return arm_capture(reason="watchdog_stall", detail=detail)


def on_straggler(ranks):
    """Fleet-collector hook: freshly flagged stragglers arm a local
    capture window (the collector rank's own steps — the cross-rank
    folded stacks ride the fleet capture's /debugz/profile pulls)."""
    return arm_capture(reason="straggler",
                       detail={"ranks": list(ranks)})


def _xprof_begin(trace_dir):
    """Start the device trace through the paddle_tpu/profiler session
    guard (ptprof and a manual Profiler can never double-start_trace).
    Returns (started, why_not). Lazy import: the profiler package pulls
    core.native, which a bare monitor worker must not pay for."""
    try:
        from .. import profiler as _profiler

        if not _profiler.xprof_session_begin("ptprof", trace_dir):
            return False, "xprof session held by %r" % (
                _profiler.xprof_session_owner(),)
        return True, None
    except Exception as e:
        return False, repr(e)


def _xprof_end():
    try:
        from .. import profiler as _profiler

        _profiler.xprof_session_end("ptprof")
    except Exception as e:
        _registry.warn_once(
            "profile.xprof_end",
            "paddle_tpu.monitor.profile: Xprof stop failed (host-side "
            "capture artifacts were still written): %r" % (e,))


def _capture_root():
    return os.environ.get("PT_MONITOR_DUMP_DIR") or "."


def _window_step_begin():
    """Hot-step entry (StepProfiler.step_begin): open a queued capture
    window when eligible. Cooldown math is monotonic — an NTP step must
    neither extend nor collapse it."""
    with _state.lock:
        if _state.window is not None or not _state.pending:
            return
        now = time.monotonic()
        if _state.last_capture_end is not None and \
                now - _state.last_capture_end < _state.cooldown_s:
            return
        if len(_state.captures) >= _state.max_captures:
            _state.pending = []
            return
        pending, _state.pending = _state.pending, []
        first = dict(pending[0])
        if len(pending) > 1:
            # later triggers fold into the window's manifest rather
            # than burning extra windows — distinct incidents keep
            # their reason attribution
            first["also"] = [{"reason": p["reason"],
                              "detail": p["detail"]}
                             for p in pending[1:]]
        ts = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
        d = os.path.join(_capture_root(), "profile_capture_%s" % ts)
        n = 1
        while os.path.exists(d):
            d = os.path.join(_capture_root(),
                             "profile_capture_%s_%d" % (ts, n))
            n += 1
        _state.window = {
            "reason": first["reason"],
            "detail": first.get("detail") or {},
            "also": first.get("also") or [],
            "steps": first["steps"],
            "steps_left": first["steps"],
            "dir": d,
            "jobs": [],
            "started_mono": now,
            "samples_mark": _state.samples,
            "folded_mark": {k: v["count"]
                            for k, v in _state.stacks.items()},
            "xprof": False,
            "xprof_error": None,
            # setup handshake: the device trace starts OUTSIDE the
            # lock below, so a concurrent step_end/abort from another
            # engine must not finalize until setup completed — it
            # requests the close and the setup path performs it
            "ready": False,
            "close_requested": False,
            "aborted": None,
        }
        w = _state.window
    # filesystem + device-trace work OUTSIDE the lock (the sampler and
    # other hot steps must not serialize behind an Xprof start)
    try:
        os.makedirs(d, exist_ok=True)
        started, why = _xprof_begin(os.path.join(d, "xprof"))
        if not started and why:
            _registry.warn_once(
                "profile.xprof_begin",
                "paddle_tpu.monitor.profile: device trace unavailable "
                "for capture %s (host-only capture proceeds): %s"
                % (d, why))
    except Exception as e:
        started, why = False, repr(e)
        _registry.warn_once(
            "profile.capture_begin",
            "paddle_tpu.monitor.profile: capture-window setup failed "
            "(window continues host-only): %r" % (e,))
    closed = None
    with _state.lock:
        w["xprof"] = started
        w["xprof_error"] = why
        w["ready"] = True
        if w["close_requested"] and _state.window is w:
            closed = _close_window_locked(w)
    if closed is not None:
        _xprof_end()
        _finalize_capture(w, *closed)


def _close_window_locked(w):
    """Under _state.lock: detach the window and compute its folded
    delta. Returns (delta, window_samples, window_s) for the caller to
    finalize OUTSIDE the lock."""
    _state.window = None
    _state.last_capture_end = time.monotonic()
    mark = w["folded_mark"]
    delta = {}
    for key, rec in _state.stacks.items():
        d = rec["count"] - mark.get(key, 0)
        if d > 0:
            delta[key] = {"count": d, "component": rec["component"]}
    return (delta, _state.samples - w["samples_mark"],
            time.monotonic() - w["started_mono"])


def _window_step_end(job):
    """Hot-step exit: count the step against the open window and
    finalize (stop trace, write manifest + folded delta) when the
    window is exhausted. A window still mid-setup (another engine's
    Xprof start in flight) is close-REQUESTED and finalized by the
    setup path — never finalized under its feet."""
    with _state.lock:
        w = _state.window
        if w is None:
            return
        if job not in w["jobs"]:
            w["jobs"].append(job)
        w["steps_left"] -= 1
        if w["steps_left"] > 0:
            return
        if not w["ready"]:
            w["close_requested"] = True
            return
        closed = _close_window_locked(w)
    # owner-checked stop: a no-op when ptprof never got the session
    _xprof_end()
    _finalize_capture(w, *closed)


def abort_window(reason="hot step raised mid-window"):
    """Finalize the open capture window EARLY — the hot-step exception
    path calls this so a step raising mid-window can never leak a live
    device trace or wedge the one-window-at-a-time state. The partial
    artifact still lands (a failing step is exactly the evidence the
    arming anomaly wanted), marked ``aborted`` in the manifest."""
    with _state.lock:
        w = _state.window
        if w is None:
            return
        w["aborted"] = str(reason)
        if not w["ready"]:
            w["close_requested"] = True
            return
        closed = _close_window_locked(w)
    _xprof_end()
    _finalize_capture(w, *closed)


def _finalize_capture(w, delta, window_samples, window_s):
    """Write the capture artifacts; never raises (a full disk must not
    take down the step that happened to close the window)."""
    rank = _rank()
    try:
        os.makedirs(w["dir"], exist_ok=True)
        fpath = os.path.join(w["dir"], "folded_rank%d.txt" % rank)
        tmp = fpath + ".tmp"
        with open(tmp, "w") as f:
            f.write(folded_text(delta))
        os.replace(tmp, fpath)
        manifest = {
            "kind": "profile_capture",
            "version": 1,
            "reason": w["reason"],
            "detail": w["detail"],
            "also": w["also"],
            "rank": rank,
            "pid": os.getpid(),
            "created_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                        time.gmtime()),
            "unix_time": time.time(),
            "steps": w["steps"],
            "jobs": w["jobs"],
            "window_s": window_s,
            "window_samples": window_samples,
            "sampler_hz": _state.hz,
            "components": component_totals(delta),
            "aborted": w.get("aborted"),
            "xprof": w["xprof"],
            "xprof_error": w["xprof_error"],
            "xprof_dir": (os.path.join(w["dir"], "xprof")
                          if w["xprof"] else None),
        }
        mpath = os.path.join(w["dir"], "manifest.json")
        tmp = mpath + ".tmp"
        with open(tmp, "w") as f:
            json.dump(manifest, f, indent=1, default=str)
            f.write("\n")
        os.replace(tmp, mpath)
    except Exception as e:
        _registry.warn_once(
            "profile.capture_write",
            "paddle_tpu.monitor.profile: capture artifact write "
            "failed (%s): %r" % (w["dir"], e))
        return
    rec = {"dir": w["dir"], "reason": w["reason"],
           "detail": w["detail"], "jobs": w["jobs"],
           "steps": w["steps"], "window_s": window_s,
           "aborted": w.get("aborted"), "xprof": w["xprof"],
           "unix_time": manifest["unix_time"]}
    with _state.lock:
        _state.captures.append(rec)
    _CAPTURES.labels(reason=w["reason"]).inc()


# -- measured phase reconciliation (the engine-facing latch) -----------------

class StepProfiler:
    """One engine's latched handle (the ``memory.tracker`` convention):
    the hot path only ever checks the handle, never the flag. Wraps
    each hot step with the dispatch/block/gap timers, mirrors the
    measured numbers into the /debugz/perf job row, and drives the
    capture-window lifecycle."""

    __slots__ = ("job", "_last_end")

    def __init__(self, job):
        self.job = job
        self._last_end = None

    def step_begin(self):
        """Before dispatch: open a queued capture window (if any)."""
        _window_step_begin()

    def step_end(self, t0, t1, block=None):
        """After the call returned at ``t1`` (perf_counter stamps from
        the caller): optionally block on the step's result to split
        dispatch from device-exposed time, publish the measured gauges,
        and count the step against any open capture window. Returns
        the measured dict."""
        t2 = t1
        if block is not None:
            try:
                import jax

                jax.block_until_ready(block)
                t2 = time.perf_counter()
            except Exception as e:
                _registry.warn_once(
                    "profile.block_until_ready",
                    "paddle_tpu.monitor.profile: block_until_ready "
                    "failed (blocked-time reads 0 this step): %r"
                    % (e,))
        dispatch = max(t1 - t0, 0.0)
        blocked = max(t2 - t1, 0.0)
        gap = (max(t0 - self._last_end, 0.0)
               if self._last_end is not None else 0.0)
        self._last_end = t2
        job = self.job
        _DISPATCH.labels(job=job).set(dispatch)
        _BLOCKED.labels(job=job).set(blocked)
        _GAP.labels(job=job).set(gap)
        with _state.lock:
            tot = _state.jobs.setdefault(job, {
                "steps": 0, "dispatch_s": 0.0, "blocked_s": 0.0,
                "gap_s": 0.0, "phases": {}})
            tot["steps"] += 1
            tot["dispatch_s"] += dispatch
            tot["blocked_s"] += blocked
            tot["gap_s"] += gap
        _perf.note_job(job,
                       profile_dispatch_seconds=dispatch,
                       profile_host_blocked_seconds=blocked,
                       profile_host_gap_seconds=gap)
        _window_step_end(job)
        return {"dispatch_s": dispatch, "blocked_s": blocked,
                "gap_s": gap}

    def step_abort(self):
        """Hot-step exception path: close any open capture window so a
        raising step can never leak a live device trace (the partial
        artifact still lands, marked aborted)."""
        abort_window("hot step raised (job=%s)" % self.job)

    def note_phase(self, phase, seconds):
        """Accumulate one sub-phase's measured host seconds (the
        serving engine feeds prefill/decode; ``/debugz/profile`` and
        tools/profile_snapshot.py report the totals)."""
        with _state.lock:
            tot = _state.jobs.setdefault(self.job, {
                "steps": 0, "dispatch_s": 0.0, "blocked_s": 0.0,
                "gap_s": 0.0, "phases": {}})
            tot["phases"][str(phase)] = \
                tot["phases"].get(str(phase), 0.0) + float(seconds)


def step_hook(job):
    """THE construction-latch entry point: when ``FLAGS_monitor_profile``
    is on, make sure the sampler runs and return a ``StepProfiler``;
    when off, return None — one flag read at construction, and the hot
    path only ever checks the handle (the memory.tracker contract)."""
    if not is_enabled():
        return None
    start_sampler()
    return StepProfiler(job)


# -- payloads / routes -------------------------------------------------------

def job_totals():
    with _state.lock:
        return {j: {k: (dict(v) if isinstance(v, dict) else v)
                    for k, v in tot.items()}
                for j, tot in _state.jobs.items()}


def profile_payload(top_k=20):
    """The /debugz/profile JSON body. Off = pinned
    ``{"enabled": false}`` shape (the route answers 200 either way —
    "off" is a payload, not an error)."""
    enabled = is_enabled()
    out = {"enabled": enabled, "time": time.time(),
           "sampler": None, "components": {}, "top": [],
           "jobs": {}, "captures": [], "pending_captures": 0,
           "window": None}
    if not enabled:
        return out
    stacks = folded_snapshot()
    with _state.lock:
        samples = _state.samples
        self_time = _state.self_time_s
        started = _state.started_mono
        overflow = _state.overflow
        hz = _state.hz
        captures = list(_state.captures)
        pending = len(_state.pending)
        w = _state.window
        window = None if w is None else {
            "reason": w["reason"], "steps_left": w["steps_left"],
            "dir": w["dir"], "xprof": w["xprof"]}
    elapsed = (time.monotonic() - started) if started is not None \
        else None
    out["sampler"] = {
        "running": sampler_running(),
        "hz": hz,
        "samples": samples,
        "distinct_stacks": len(stacks),
        "overflow_samples": overflow,
        "self_time_s": self_time,
        "window_s": elapsed,
        "overhead_share": (self_time / elapsed
                           if elapsed and elapsed > 0 else None),
    }
    out["components"] = component_totals(stacks)
    rows = sorted(stacks.items(), key=lambda kv: -kv[1]["count"])
    out["top"] = [{"stack": key, "count": rec["count"],
                   "component": rec["component"]}
                  for key, rec in rows[:int(top_k)]]
    out["jobs"] = job_totals()
    out["captures"] = captures
    out["pending_captures"] = pending
    out["window"] = window
    return out


def folded_route_text():
    """The /debugz/profile/folded body (text/plain). Disabled = a
    comment header instead of an empty 200 body, so a probe can tell
    "off" from "on but idle"."""
    if not is_enabled():
        return "# ptprof disabled (FLAGS_monitor_profile off)\n"
    return folded_text()


def bundle_payload(top_k=64):
    """The watchdog-bundle embedding: the sampler's TIME-WEIGHTED view
    (each count ≈ 1/hz s) next to the bundle's point-in-time stacks —
    a stall postmortem shows where the time went, not just where
    threads sat at one instant. None while the plane is off (the
    bundle key stays null, never fabricated)."""
    if not is_enabled():
        return None
    stacks = folded_snapshot()
    rows = sorted(stacks.items(), key=lambda kv: -kv[1]["count"])
    with _state.lock:
        samples = _state.samples
        started = _state.started_mono
        hz = _state.hz
    return {
        "samples": samples,
        "hz": hz,
        "window_s": (time.monotonic() - started)
        if started is not None else None,
        "components": component_totals(stacks),
        "folded": {key: rec["count"] for key, rec in rows[:int(top_k)]},
    }


def reset():
    """Test hook: stop the sampler, forget stacks/jobs/captures/window
    state, restore the env-derived tunables (tests mutate hz /
    max_stacks / cooldown_s / max_captures and must not leak them into
    later suites), and drop the published ``profile_*`` series
    (flags-off after reset is pinned series-free)."""
    stop_sampler()
    with _state.lock:
        _state.samples = 0
        _state.self_time_s = 0.0
        _state.started_mono = None
        _state.stacks = {}
        _state.overflow = 0
        _state.jobs = {}
        _state.captures = []
        _state.pending = []
        w, _state.window = _state.window, None
        _state.last_capture_end = None
        _state.hz = _env_float("PT_PROFILE_HZ", 19.0)
        _state.max_stacks = _env_int("PT_PROFILE_MAX_STACKS", 512)
        _state.cooldown_s = _env_float("PT_PROFILE_CAPTURE_COOLDOWN_S",
                                       60.0)
        _state.max_captures = _env_int("PT_PROFILE_MAX_CAPTURES", 4)
    if w is not None:
        # an open window's device trace must not outlive the reset
        # (owner-checked: a no-op when ptprof never held the session)
        _xprof_end()
    for m in (_DISPATCH, _BLOCKED, _GAP, _CAPTURES):
        for key in list(m._children):
            m.remove(*key)
    for key in list(_SAMPLES._children):
        _SAMPLES.remove(*key)
    _SAMPLES._values.pop((), None)


# env/FLAGS bootstrap (the timeseries/perf/memory discipline): a process
# started with FLAGS_monitor_profile=1 samples from its first moments
# without any code change.
if _flag("FLAGS_monitor_profile"):
    start_sampler()
