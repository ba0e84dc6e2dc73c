"""paddle_tpu.profiler — unified host + device profiling.

Parity: reference python/paddle/profiler/profiler.py:344 (`Profiler` with
scheduler windows ProfilerState cycle at :79), RecordEvent annotations
threaded through executors/ops, chrome-trace export
(platform/profiler/chrometracing_logger.cc) and summary statistics
(profiler_statistic.py). TPU-native split: host events go through the C++
recorder (csrc/trace.cc, the host_event_recorder.h analog); device-side
tracing is delegated to jax.profiler (Xprof) which captures XLA/TPU
activity — the CUPTI analog is the TPU runtime's own tracer, reached via
jax.profiler.start_trace.

A device capture is read by model scope: the models open
``jax.named_scope("layer_<i>")`` and ``attn`` / ``mla`` / ``mlp`` /
``moe`` / ``gdn`` / ``ssm`` inside it, ``embed``, ``lm_head`` and
``optimizer`` beside the layers, and XProf groups device time by that
``op_name`` (there is no summary-table switch here for it).
"""
from __future__ import annotations

import enum
import os
import threading
import time

from ..core import native
from ..monitor.registry import warn_once as _warn_once

__all__ = [
    "Profiler", "RecordEvent", "ProfilerState", "ProfilerTarget",
    "make_scheduler", "export_chrome_tracing", "load_profiler_result",
    "xprof_session_begin", "xprof_session_end", "xprof_session_owner",
]

# -- Xprof session guard -----------------------------------------------------
# jax.profiler allows exactly ONE live trace per process; a second
# start_trace raises and the first window's artifact is at the mercy of
# whoever calls stop_trace first. Every device-trace user in this repo
# (the manual Profiler below, ptprof's anomaly capture windows in
# monitor/profile.py) goes through this guard so two owners can never
# double-start or steal each other's stop.
_xprof_lock = threading.Lock()
_xprof_owner = None


def xprof_session_owner():
    """Name of the owner currently holding the live Xprof session, or
    None."""
    return _xprof_owner


def xprof_session_begin(owner, trace_dir):
    """Claim the process-wide Xprof session and start the device trace
    into ``trace_dir``. Returns True when THIS call started the trace;
    False when another owner already holds the session (the caller
    degrades to host-only — never an exception on the busy path). A
    ``start_trace`` failure releases the claim and re-raises so the
    caller can report the real cause."""
    global _xprof_owner
    with _xprof_lock:
        if _xprof_owner is not None:
            return False
        _xprof_owner = str(owner)
    try:
        import jax

        jax.profiler.start_trace(trace_dir)
    except BaseException:
        with _xprof_lock:
            _xprof_owner = None
        raise
    return True


def xprof_session_end(owner):
    """Stop the device trace IF ``owner`` holds the session (a no-op
    returning False otherwise — an owner can never stop a window it
    did not start). The historical broad silent-except here is narrowed
    to the types jax.profiler.stop_trace actually raises (RuntimeError
    "No profile started" when the backend already closed the window,
    ValueError from a torn-down profiler state) and routed through
    warn_once — the PR-10 discipline applied to the one module that
    predates it."""
    global _xprof_owner
    with _xprof_lock:
        if _xprof_owner != str(owner):
            return False
    # ownership is held UNTIL stop_trace returns: releasing first would
    # let a concurrent begin claim the session and start_trace into the
    # still-live old trace — the double-start this guard exists to stop
    try:
        import jax

        jax.profiler.stop_trace()
        ok = True
    except (RuntimeError, ValueError) as e:
        _warn_once(
            "profiler.stop_trace",
            "paddle_tpu.profiler: jax.profiler.stop_trace failed — the "
            "backend already closed the window; whatever landed in the "
            "trace dir is kept: %r" % (e,))
        ok = False
    finally:
        with _xprof_lock:
            if _xprof_owner == str(owner):
                _xprof_owner = None
    return ok


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(enum.Enum):
    CPU = 0
    TPU = 1  # reference: GPU


def make_scheduler(*, closed, ready, record, repeat=0, skip_first=0):
    """Step-window scheduler (reference profiler.py:170 make_scheduler)."""

    def sched(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        period = closed + ready + record
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return sched


class RecordEvent:
    """Scoped host annotation (reference platform/profiler/event_tracing.h
    RecordEvent; python API python/paddle/profiler/utils.py RecordEvent)."""

    def __init__(self, name, event_type=None, level=1):
        self.name = name
        self.level = level
        self._lib = None
        self._xprof = None

    def begin(self):
        self._lib = native.get_lib()
        self._lib.pt_trace_push(self.name.encode(), self.level)
        # bridge into the device timeline: the same span shows up in the
        # Xprof trace (reference merges host RecordEvents with CUPTI
        # events into one EventNode tree, chrometracing_logger.cc)
        try:
            import jax

            self._xprof = jax.profiler.TraceAnnotation(self.name)
            self._xprof.__enter__()
        except Exception:
            self._xprof = None

    def end(self):
        if self._xprof is not None:
            try:
                self._xprof.__exit__(None, None, None)
            # ptlint: silent-except-ok — profiler teardown is
            # best-effort; the trace dir keeps whatever landed
            except Exception:
                pass
            self._xprof = None
        if self._lib is not None:
            self._lib.pt_trace_pop()
            self._lib = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()


def _counter(name, value):
    native.get_lib().pt_trace_counter(name.encode(), int(value))


class Profiler:
    """Collect host (+ optional Xprof device) traces over scheduled steps.

    Usage matches the reference (profiler.py:344):
        with Profiler(scheduler=(2, 5), on_trace_ready=...) as p:
            for batch in loader:
                train_step(batch)
                p.step()
    """

    def __init__(self, *, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, with_xprof=False, trace_dir=None):
        if scheduler is None:
            self._sched = lambda step: ProfilerState.RECORD
        elif isinstance(scheduler, tuple):
            start, end = scheduler
            self._sched = make_scheduler(
                closed=max(start, 0), ready=0, record=end - start, repeat=1)
        else:
            self._sched = scheduler
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.with_xprof = with_xprof and not timer_only
        self.trace_dir = trace_dir or os.path.join(".", "profiler_log")
        self._step = 0
        self._state = ProfilerState.CLOSED
        self._xprof_on = False
        self._step_times = []
        self._t0 = None

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        self._apply_state(self._sched(self._step))
        self._t0 = time.perf_counter()
        return self

    def stop(self):
        if self._state in (ProfilerState.RECORD,
                           ProfilerState.RECORD_AND_RETURN):
            self._finish_window()
        self._apply_state(ProfilerState.CLOSED)

    def step(self):
        now = time.perf_counter()
        if self._t0 is not None:
            self._step_times.append(now - self._t0)
        self._t0 = now
        prev = self._state
        self._step += 1
        new = self._sched(self._step)
        if prev in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN) \
                and new in (ProfilerState.CLOSED, ProfilerState.READY):
            self._finish_window()
        self._apply_state(new)

    def _apply_state(self, state):
        if self.timer_only:
            self._state = state
            return
        lib = native.get_lib()
        recording = state in (ProfilerState.RECORD,
                              ProfilerState.RECORD_AND_RETURN)
        was = self._state in (ProfilerState.RECORD,
                              ProfilerState.RECORD_AND_RETURN)
        if recording and not was:
            lib.pt_trace_enable(2)
            if self.with_xprof and not self._xprof_on:
                # through the session guard: a ptprof capture window
                # (monitor/profile.py) holding the session degrades
                # this window to host-only instead of raising — and
                # vice versa
                try:
                    self._xprof_on = xprof_session_begin(
                        "profiler", self.trace_dir)
                except Exception as e:
                    self._xprof_on = False
                    _warn_once(
                        "profiler.start_trace",
                        "paddle_tpu.profiler: device trace unavailable "
                        "(host trace still records): %r" % (e,))
        elif not recording and was:
            lib.pt_trace_disable()
        self._state = state

    def _finish_window(self):
        if self._xprof_on:
            # the guard narrows the except to stop_trace's real raise
            # types and warns once instead of swallowing
            xprof_session_end("profiler")
            self._xprof_on = False
        if self.on_trace_ready is not None:
            self.on_trace_ready(self)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # -- results -----------------------------------------------------------
    def export_chrome_tracing(self, path):
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        rc = native.get_lib().pt_trace_dump(path.encode())
        if rc != 0:
            raise IOError("trace dump to %s failed" % path)
        return path

    def export_merged_chrome_tracing(self, path):
        """ONE chrome trace containing both timelines: the native host
        tracer's events (csrc/trace.cc) and the device/XLA events from
        the Xprof capture (jax writes tensorboard-plugin
        *.trace.json.gz files in trace_dir) — the unified EventNode view
        the reference builds in chrometracing_logger.cc from host +
        CUPTI streams."""
        import glob
        import gzip
        import json

        host_path = path + ".host.json"
        self.export_chrome_tracing(host_path)
        with open(host_path) as f:
            merged = json.load(f)
        events = merged.get("traceEvents", merged if isinstance(
            merged, list) else [])
        if isinstance(merged, list):
            merged = {"traceEvents": events}
        device_files = sorted(glob.glob(os.path.join(
            self.trace_dir, "plugins", "profile", "*", "*.trace.json.gz")))
        for i, df in enumerate(device_files):
            # ALL capture files merge in (a scheduler with repeat>1
            # produces one Xprof capture per record window); each file
            # gets its own pid namespace so windows don't overdraw each
            # other on one track
            tag = "xla%d" % i if len(device_files) > 1 else "xla"
            with gzip.open(df, "rt") as f:
                dev = json.load(f)
            for ev in dev.get("traceEvents", []):
                # keep device pids distinct from host pids
                if isinstance(ev, dict) and "pid" in ev:
                    ev = dict(ev)
                    ev["pid"] = "%s/%s" % (tag, ev["pid"])
                events.append(ev)
        merged["traceEvents"] = events
        with open(path, "w") as f:
            json.dump(merged, f)
        os.remove(host_path)
        return path

    def summary(self):
        """Step-time stats (reference profiler_statistic.py summary)."""
        ts = self._step_times
        if not ts:
            return {"steps": 0}
        ts_sorted = sorted(ts)
        n = len(ts_sorted)
        return {
            "steps": n,
            "avg_s": sum(ts) / n,
            "min_s": ts_sorted[0],
            "p50_s": ts_sorted[n // 2],
            "p99_s": ts_sorted[min(n - 1, int(n * 0.99))],
            "max_s": ts_sorted[-1],
        }


def export_chrome_tracing(dir_name, worker_name=None):
    """on_trace_ready factory (reference profiler.py export_chrome_tracing)."""

    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or "worker"
        path = os.path.join(dir_name, "%s_%d.json" % (name, prof._step))
        prof.export_chrome_tracing(path)

    return handler


def load_profiler_result(path):
    import json

    with open(path) as f:
        return json.load(f)
