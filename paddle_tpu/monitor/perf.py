"""MFU / goodput attribution + regression sentinels.

The monitor stack so far can say *that* a step ran (registry), *that* a
rank hung (watchdog), and *what* went over the wire (flight recorder) —
but not whether the step was any good. This module closes that gap with
the PaLM-style MFU recipe: analytic/measured FLOPs over measured wall
clock, phase-attributed, watched continuously.

1. **Attribution** (``TrainStepPerf``): the compiled train step's
   executable is asked what it actually is — ``cost_analysis()`` FLOPs
   and ``memory_analysis()`` peak bytes (the llama7b_plan fallback when
   the jaxlib build lacks the buffer-assignment peak) — and combined
   with the measured step wall time into:

     ``mfu{job}``               model-FLOPs utilization vs the machine
                                peak (cost_model.py MachineSpec; env
                                PT_PERF_PEAK_FLOPS overrides)
     ``model_flops{job}``       FLOPs of one optimizer step
     ``model_flops_per_s{job}`` achieved FLOP rate over the step window
     ``hbm_peak_bytes{job}``    executable HBM high-water mark
     ``perf_phase_seconds{job,phase}``  compute / comm / host split:
         host = inter-step gap on the driving thread, comm = measured
         eager-collective bracket time (flight-recorder entries by seq,
         wire bytes attached) or the analytic grad-sync estimate
         (bytes / ICI bw) when the collectives are compiled-implicit,
         compute = the step-call remainder.

   The serving engine publishes the serving analogs (per-token goodput
   — finished-request tokens only, preempted-and-recomputed work
   excluded — and KV-page occupancy) through serving/metrics.py, and
   mirrors them here via ``note_job("serving", ...)``.

2. **Sentinels**: pluggable detectors subscribed to the time-series
   ring (monitor/timeseries.py): NaN/inf loss, loss spike vs EWMA,
   throughput regression vs a rolling baseline, grad-norm explosion.
   A firing increments ``perf_anomalies_total{kind}``, drops a
   structured event into the flight-recorder ring, and flips the
   ``degraded`` flag that /healthz reports — the "loss went NaN two
   hours ago and nobody noticed" failure mode becomes a scrape-able,
   probe-able signal. Detectors are armed only after their warmup
   window; a clean warmup can never fire.

Gating (FLAGS precedent, all default-off): ``FLAGS_perf_attribution``
for (1) — it costs one AOT lower+compile of the step and one
loss-scalar host readback per step; ``FLAGS_perf_sentinels`` for (2) —
it implies the ``FLAGS_monitor_timeseries`` ring. Disabled = zero
native calls, zero extra threads, registry hot path unchanged
(test-pinned). Module import stays stdlib-only; jax objects only ever
arrive as arguments.
"""
from __future__ import annotations

import math
import os
import threading
import time

from . import registry as _registry
from . import timeseries as _timeseries
from .flight_recorder import get_flight_recorder
from .timeseries import _flag

# -- metrics (shared registry; every mutator no-ops when disabled) ----------

_MFU = _registry.gauge(
    "mfu", "model-FLOPs utilization of the last step window vs the "
    "machine peak (monitor/perf.py attribution)", labelnames=("job",))
_MODEL_FLOPS = _registry.gauge(
    "model_flops", "FLOPs of one optimizer step (XLA cost_analysis of "
    "the compiled executable)", labelnames=("job",))
_FLOPS_RATE = _registry.gauge(
    "model_flops_per_s", "achieved model FLOP/s over the last step "
    "window", labelnames=("job",))
_HBM_PEAK = _registry.gauge(
    "hbm_peak_bytes", "compiled-executable HBM high-water mark "
    "(memory_analysis; upper-bound estimate on jaxlib builds without "
    "the buffer-assignment peak)", labelnames=("job",))
_PHASE = _registry.gauge(
    "perf_phase_seconds", "last-window phase attribution: compute | "
    "comm | host", labelnames=("job", "phase"))
_TRAIN_LOSS = _registry.gauge(
    "train_loss", "last train-step loss (host readback under "
    "FLAGS_perf_attribution; the NaN/spike sentinels watch this "
    "series)", labelnames=("job",))
_ANOMALIES = _registry.counter(
    "perf_anomalies_total", "sentinel firings by kind",
    labelnames=("kind",))

_EVENTS_CAP = 64


class _PerfState:
    __slots__ = ("lock", "jobs", "events", "degraded_since",
                 "anomaly_counts", "sentinels", "listener_installed")

    def __init__(self):
        self.lock = threading.Lock()
        self.jobs = {}              # job -> last attribution report
        self.events = []            # recent anomaly events (bounded)
        self.degraded_since = None
        self.anomaly_counts = {}    # kind -> count (payload mirror)
        self.sentinels = []
        self.listener_installed = False


_state = _PerfState()


def attribution_enabled():
    return _flag("FLAGS_perf_attribution")


def sentinels_enabled():
    return _state.listener_installed


_PEAK_ENV = (("peak_flops", "PT_PERF_PEAK_FLOPS"),
             ("hbm_bw", "PT_PERF_HBM_BW"),
             ("ici_bw", "PT_PERF_ICI_BW"))


class UnknownDeviceKindError(RuntimeError):
    """The local device has no row in DEVICE_PEAKS and no PT_PERF_*
    override: there is no honest denominator for an MFU here."""


def _local_peaks():
    """(device_kind, peaks) for the device this process runs on: the
    DEVICE_PEAKS row for ``jax.devices()[0].device_kind`` (empty for an
    unknown kind) with PT_PERF_{PEAK_FLOPS,HBM_BW,ICI_BW} laid over it.
    A malformed override raises — a typo must not silently select the
    table value."""
    import jax

    from ..distributed.auto_parallel.cost_model import DEVICE_PEAKS

    kind = jax.devices()[0].device_kind
    spec = dict(DEVICE_PEAKS.get(kind, ()))
    for key, env in _PEAK_ENV:
        raw = os.environ.get(env)
        if raw:
            spec[key] = float(raw)
    return kind, spec


def machine_spec():
    """Per-chip peak numbers of the LOCAL device — the denominator of
    every MFU in this module. Raises UnknownDeviceKindError when the
    device kind is not in the table and the PT_PERF_* overrides do not
    cover it (a CPU test that needs a denominator passes one)."""
    kind, spec = _local_peaks()
    missing = [env for key, env in _PEAK_ENV if key not in spec]
    if missing:
        raise UnknownDeviceKindError(
            "no peak numbers for device kind %r: add a sourced row to "
            "DEVICE_PEAKS (distributed/auto_parallel/cost_model.py) or "
            "set %s" % (kind, ", ".join(missing)))
    return spec


# -- executable analysis -----------------------------------------------------

def executable_analysis(compiled, steps=1, memory_only=False):
    """FLOPs + HBM accounting of one compiled executable (a jax AOT
    ``Compiled`` — passed in, never imported). ``steps`` divides the
    totals for multi-step modules. ``memory_only`` skips the
    cost_analysis FLOPs walk for callers (monitor/memory.py
    ``compiled_peak``) that only need the peak — the peak RULE still
    lives here and nowhere else. Never raises: perf attribution must
    not take down a training run."""
    out = {"source": "xla_cost_analysis", "steps_per_call": int(steps)}
    steps = max(int(steps), 1)
    if not memory_only:
        try:
            ca = compiled.cost_analysis()
            d = ca[0] if isinstance(ca, (list, tuple)) and ca else ca
            if d:
                flops = float(d.get("flops", 0.0))
                if flops > 0:
                    out["flops_per_step"] = flops / steps
                ba = float(d.get("bytes accessed", 0.0))
                if ba > 0:
                    out["bytes_accessed_per_step"] = ba / steps
        # ptlint: silent-except-ok — cost_analysis is a
        # backend-optional introspection API; absent fields are the
        # documented contract
        except Exception:
            pass
    try:
        ma = compiled.memory_analysis()
        arg = int(ma.argument_size_in_bytes)
        tmp = int(ma.temp_size_in_bytes)
        outb = int(ma.output_size_in_bytes)
        alias = int(ma.alias_size_in_bytes)
        out["argument_bytes"] = arg
        out["temp_bytes"] = tmp
        out["output_bytes"] = outb
        peak = getattr(ma, "peak_memory_in_bytes", None)
        if not peak:
            # llama7b_plan fallback: args + temps + outputs net of
            # donation aliasing — an over-estimate (liveness overlap is
            # ignored), flagged so readers don't mistake it for the
            # scheduler's real high-water mark
            peak = arg + tmp + outb - alias
            out["hbm_peak_is_estimate"] = True
        out["hbm_peak_bytes"] = int(peak)
    # ptlint: silent-except-ok — memory_analysis is a backend-optional
    # introspection API; absent fields are the documented contract
    except Exception:
        pass
    return out


def bench_fields(analysis, tokens_per_s=None, tokens_per_step=None,
                 peak_flops=None):
    """Bench-row JSON fields from an ``executable_analysis`` dict:
    ``mfu`` / ``model_flops_per_step`` / ``hbm_peak_bytes`` — the
    hardware-normalized form of a raw tokens/s number
    (tools/perf_report.py's smoke row carries these)."""
    out = {}
    if not analysis:
        return out
    flops = analysis.get("flops_per_step")
    if flops:
        out["model_flops_per_step"] = round(flops)
    if "hbm_peak_bytes" in analysis:
        out["hbm_peak_bytes"] = analysis["hbm_peak_bytes"]
        if analysis.get("hbm_peak_is_estimate"):
            out["hbm_peak_is_estimate"] = True
    if flops and tokens_per_s and tokens_per_step:
        peak = peak_flops or machine_spec()["peak_flops"]
        steps_per_s = tokens_per_s / float(tokens_per_step)
        out["model_flops_per_s"] = round(flops * steps_per_s)
        # 3 significant digits, never rounded to a flat 0
        out["mfu"] = float("%.3g" % (flops * steps_per_s / peak))
        out["mfu_peak_flops"] = peak
    return out


# -- train-step attribution --------------------------------------------------

class TrainStepPerf:
    """Per-train-step attribution for one engine instance. The engine
    calls ``on_step`` once per compiled call; the first call resolves
    ``analysis_fn`` (the engine's AOT lower+compile of its own step —
    one extra compile, under the opt-in flag)."""

    def __init__(self, job, analysis_fn=None, machine=None):
        self.job = job
        self._analysis_fn = analysis_fn
        self.analysis = None
        self._analysis_tried = False
        self.machine = machine or machine_spec()
        self._last_end = None       # perf_counter of the previous call end
        self._fr_seq = None         # flight-recorder seq watermark

    def _resolve_analysis(self):
        if self._analysis_tried:
            return
        self._analysis_tried = True
        fn, self._analysis_fn = self._analysis_fn, None
        if fn is None:
            return
        try:
            self.analysis = fn() or None
        except Exception:
            self.analysis = None
        # fn (and with it the closure-captured device batch) is
        # dropped either way: a one-shot analysis must not pin
        # batch-sized arrays in HBM for the run's lifetime

    def _comm_since_last(self):
        """(seconds, wire_bytes, source) of eager collectives since the
        previous step, by flight-recorder sequence watermark (timestamps
        live in a different clock domain than the engine's perf_counter
        — seq comparison is domain-free). Falls back to the analytic
        grad-sync estimate when the collectives are compiled-implicit
        (no eager entries): bytes published by distributed/compress.py
        over the ICI bandwidth."""
        fr = get_flight_recorder()
        mark = self._fr_seq
        self._fr_seq = fr._seq
        comm_s, wire = 0.0, 0
        if mark is not None and fr._seq > mark:
            for e in fr.entries():
                seq = e.get("seq")
                if seq is None or seq < mark:
                    continue
                t0, t1 = e.get("t_start"), e.get("t_end")
                if t0 is not None and t1 is not None:
                    comm_s += max(t1 - t0, 0.0)
                wire += int(e.get("wire_bytes", 0) or 0)
            if comm_s > 0 or wire > 0:
                return comm_s, wire, "flight_recorder"
        # analytic fallback: the compiled-path grad sync is invisible to
        # the eager recorder; use its published per-step wire bytes
        try:
            g = _registry.get_registry().get("grad_sync_bytes_per_step")
            if g is not None:
                vals = [v for _, v in g.collect()]
                nbytes = max(vals) if vals else 0
                if nbytes > 0:
                    return (nbytes / self.machine["ici_bw"], int(nbytes),
                            "analytic")
        # ptlint: silent-except-ok — absent/odd comm metric degrades
        # the overlap attribution to "none", which is the fallback row
        except Exception:
            pass
        return 0.0, 0, "none"

    def on_step(self, dt, steps=1, tokens=0, loss=None, t_start=None,
                t_end=None):
        """Publish attribution for one engine call covering ``steps``
        optimizer steps and ``tokens`` batch tokens, measured at ``dt``
        seconds of host wall (dispatch + blocking)."""
        if t_end is None:
            t_end = time.perf_counter()
        host_s = 0.0
        if self._last_end is not None and t_start is not None:
            host_s = max(t_start - self._last_end, 0.0)
        self._last_end = t_end
        self._resolve_analysis()
        comm_s, wire, comm_source = self._comm_since_last()
        comm_s = min(comm_s, dt + host_s)
        compute_s = max(dt - comm_s, 0.0)
        window = max(dt + host_s, 1e-12)
        # shares normalize over the SUM of attributed seconds, not the
        # window: comm measured in the inter-step gap (a background
        # sync thread) can exceed dt, and the split must still read as
        # fractions of a whole (== the window whenever comm fits
        # inside the step call)
        attributed = max(compute_s + comm_s + host_s, 1e-12)
        job = self.job
        report = {
            "steps": steps,
            "tokens": tokens,
            "step_seconds": dt,
            "window_seconds": window,
            "tokens_per_s": tokens / window if tokens else 0.0,
            "phase_seconds": {"compute": compute_s, "comm": comm_s,
                              "host": host_s},
            "phase_share": {
                "compute": compute_s / attributed,
                "comm": comm_s / attributed,
                "host": host_s / attributed,
            },
            "comm_source": comm_source,
            "comm_wire_bytes": wire,
            "peak_flops": self.machine["peak_flops"],
        }
        a = self.analysis
        if a:
            flops = a.get("flops_per_step")
            if flops:
                rate = flops * steps / window
                report["model_flops_per_step"] = flops
                report["model_flops_per_s"] = rate
                report["mfu"] = rate / self.machine["peak_flops"]
                _MODEL_FLOPS.labels(job=job).set(flops)
                _FLOPS_RATE.labels(job=job).set(rate)
                _MFU.labels(job=job).set(report["mfu"])
            if "hbm_peak_bytes" in a:
                report["hbm_peak_bytes"] = a["hbm_peak_bytes"]
                if a.get("hbm_peak_is_estimate"):
                    report["hbm_peak_is_estimate"] = True
                _HBM_PEAK.labels(job=job).set(a["hbm_peak_bytes"])
        for phase, v in report["phase_seconds"].items():
            _PHASE.labels(job=job, phase=phase).set(v)
        if loss is not None:
            try:
                lv = float(loss)
            except Exception:
                lv = None
            if lv is not None:
                report["loss"] = lv
                # nan/inf flow through on purpose: this gauge IS the
                # sentinel's input series
                _TRAIN_LOSS.labels(job=job).set(lv)
        note_job(job, **report)
        return report


def note_job(job, **fields):
    """Merge the latest attribution numbers for ``job`` into the
    /debugz/perf payload (serving/metrics.py mirrors goodput/occupancy
    here; train steps publish their whole report)."""
    fields["updated_at"] = time.time()
    with _state.lock:
        cur = _state.jobs.setdefault(job, {})
        cur.update(fields)


# -- sentinels ---------------------------------------------------------------

class Sentinel:
    """One detector over one ring series (matched by exact name or by
    ``name{...labels}`` prefix). Subclasses implement ``check(state,
    value)`` returning a detail dict to fire, None to stay quiet; the
    base class handles warmup (never fire before ``warmup`` samples)
    and a refire cooldown so a persistent condition counts episodes,
    not samples."""

    kind = "anomaly"

    def __init__(self, series, warmup=0, cooldown=None):
        self.series = series
        self.warmup = int(warmup)
        self.cooldown = int(cooldown if cooldown is not None
                            else max(warmup, 1))
        self._per_series = {}

    def matches(self, name):
        return name == self.series or name.startswith(self.series + "{")

    def _new_state(self):
        return {"n": 0, "cool": 0}

    def observe(self, name, ts, value):
        st = self._per_series.get(name)
        if st is None:
            st = self._per_series[name] = self._new_state()
        fired = None
        if st["n"] >= self.warmup and st["cool"] <= 0:
            fired = self.check(st, value)
            if fired is not None:
                st["cool"] = self.cooldown
        elif st["cool"] > 0:
            st["cool"] -= 1
        self.update(st, value)
        st["n"] += 1
        return fired

    def check(self, st, value):
        return None

    def update(self, st, value):
        pass

    def recovered(self, name):
        """Consume the recovery edge for ``name``: True exactly once
        after the sentinel's episode latch clears (subclasses set
        ``st["recovered"]`` when their condition ends). The incident
        table resolves on this edge — detection stays in the
        sentinel, aggregation in monitor/incidents.py."""
        st = self._per_series.get(name)
        return bool(st) and bool(st.pop("recovered", False))


class NaNLossSentinel(Sentinel):
    """Non-finite loss. Latched: one firing per contiguous non-finite
    run (a 10k-step NaN tail is one incident, not 10k)."""

    kind = "nan_loss"

    def __init__(self, series="train_loss", warmup=0):
        super().__init__(series, warmup=warmup, cooldown=0)

    def check(self, st, value):
        bad = not math.isfinite(value)
        if bad and not st.get("latched"):
            st["latched"] = True
            return {"value": repr(value)}
        if not bad and st.get("latched"):
            st["latched"] = False
            st["recovered"] = True
        return None


class LossSpikeSentinel(Sentinel):
    """Finite loss far above its EWMA. Non-finite samples are the NaN
    sentinel's domain — skipped entirely here (no fire, no stat
    update)."""

    kind = "loss_spike"

    def __init__(self, series="train_loss", warmup=8, alpha=0.3,
                 factor=3.0):
        super().__init__(series, warmup=warmup)
        self.alpha = alpha
        self.factor = factor

    def check(self, st, value):
        if not math.isfinite(value):
            return None
        mean, dev = st.get("mean"), st.get("dev", 0.0)
        if mean is None:
            return None
        thr = mean + self.factor * max(dev, 0.1 * abs(mean), 1e-9)
        if value > thr:
            st["spiking"] = True
            return {"value": value, "ewma": mean, "threshold": thr}
        if st.get("spiking"):
            st["spiking"] = False
            st["recovered"] = True
        return None

    def update(self, st, value):
        if not math.isfinite(value):
            return
        mean = st.get("mean")
        if mean is None:
            st["mean"], st["dev"] = value, 0.0
            return
        a = self.alpha
        st["dev"] = (1 - a) * st.get("dev", 0.0) + a * abs(value - mean)
        st["mean"] = (1 - a) * mean + a * value


class ThroughputRegressionSentinel(Sentinel):
    """Throughput below a fraction of its rolling-window baseline — the
    "the run quietly got 2x slower" detector over tokens/s."""

    kind = "throughput_regression"

    def __init__(self, series="train_tokens_per_s", warmup=8,
                 window=None, drop=0.5):
        super().__init__(series, warmup=warmup)
        self.window = int(window or max(warmup, 4))
        self.drop = drop

    def check(self, st, value):
        if not math.isfinite(value):
            return None
        win = st.get("win") or []
        if len(win) < self.window:
            return None
        baseline = sorted(win)[len(win) // 2]    # median
        thr = baseline * (1.0 - self.drop)
        if baseline > 0 and value < thr:
            st["cliff"] = True
            return {"value": value, "baseline": baseline,
                    "threshold": thr}
        if st.get("cliff"):
            st["cliff"] = False
            st["recovered"] = True
        return None

    def update(self, st, value):
        if not math.isfinite(value):
            return
        win = st.setdefault("win", [])
        win.append(value)
        if len(win) > self.window:
            del win[:len(win) - self.window]


class GradNormSentinel(Sentinel):
    """Gradient-norm explosion: norm a multiplicative factor above its
    EWMA. Watches ``train_grad_norm`` — published by whoever computes
    norms (a clipping optimizer, user code); inert when nobody does."""

    kind = "grad_norm_explosion"

    def __init__(self, series="train_grad_norm", warmup=8, alpha=0.3,
                 factor=10.0):
        super().__init__(series, warmup=warmup)
        self.alpha = alpha
        self.factor = factor

    def check(self, st, value):
        if not math.isfinite(value):
            return None
        mean = st.get("mean")
        if mean is None or mean <= 0:
            return None
        if value > self.factor * mean:
            st["exploding"] = True
            return {"value": value, "ewma": mean,
                    "threshold": self.factor * mean}
        if st.get("exploding"):
            st["exploding"] = False
            st["recovered"] = True
        return None

    def update(self, st, value):
        if not math.isfinite(value):
            return
        mean = st.get("mean")
        st["mean"] = value if mean is None \
            else (1 - self.alpha) * mean + self.alpha * value


def default_sentinels():
    return [NaNLossSentinel(), LossSpikeSentinel(),
            ThroughputRegressionSentinel(), GradNormSentinel()]


def _fire(sentinel, name, ts, value, detail):
    kind = sentinel.kind
    event = {
        "kind": kind,
        "series": name,
        "ts": ts,
        "detail": detail,
    }
    with _state.lock:
        _state.anomaly_counts[kind] = \
            _state.anomaly_counts.get(kind, 0) + 1
        if _state.degraded_since is None:
            _state.degraded_since = ts
        _state.events.append(event)
        if len(_state.events) > _EVENTS_CAP:
            del _state.events[:len(_state.events) - _EVENTS_CAP]
    try:
        _ANOMALIES.labels(kind=kind).inc()
    except Exception as e:
        _registry.warn_once(
            "perf.anomaly_counter",
            "paddle_tpu.monitor.perf: anomaly counter increment "
            "failed (event ring still recorded it): %r" % (e,))
    try:
        get_flight_recorder().note_event(
            "perf_anomaly", anomaly_kind=kind, series=name,
            value=repr(value), detail=detail)
    except Exception as e:
        _registry.warn_once(
            "perf.anomaly_flight_note",
            "paddle_tpu.monitor.perf: flight-recorder anomaly note "
            "failed: %r" % (e,))
    # ptprof (monitor/profile.py): profile-shaped anomalies
    # (throughput cliff, mem leak) arm a one-shot device-capture
    # window around the next hot steps, so the Xprof artifact is of
    # the ANOMALOUS steps. Lazy import, no-op while the plane is off.
    try:
        from . import profile as _profile

        _profile.on_anomaly(kind)
    except Exception as e:
        _registry.warn_once(
            "perf.profile_arm",
            "paddle_tpu.monitor.perf: profile capture arming failed "
            "(anomaly was still recorded above): %r" % (e,))
    # ptslo (monitor/incidents.py): every firing is also an incident —
    # episode-keyed on (kind, series) so a persistent condition is ONE
    # open incident that re-fires extend. Lazy import, one flag branch
    # while the plane is off.
    try:
        from . import incidents as _incidents

        _incidents.open(
            "perf/%s/%s" % (kind, name),
            severity=("page" if kind in ("nan_loss",
                                         "grad_norm_explosion")
                      else "ticket"),
            kind=kind, source="perf",
            summary="%s on %s" % (kind, name),
            evidence={"series": name, "detail": detail})
    except Exception as e:
        _registry.warn_once(
            "perf.incident_open",
            "paddle_tpu.monitor.perf: incident open failed (anomaly "
            "was still recorded above): %r" % (e,))


def _recover(sentinel, name):
    """The episode's recovery edge: resolve the matching incident.
    Detection (and the latch) stays in the sentinel — this only
    reports the edge to the table."""
    try:
        from . import incidents as _incidents

        _incidents.resolve("perf/%s/%s" % (sentinel.kind, name),
                           reason="sentinel recovered")
    except Exception as e:
        _registry.warn_once(
            "perf.incident_resolve",
            "paddle_tpu.monitor.perf: incident resolve failed "
            "(sentinel state already recovered): %r" % (e,))


def _dispatch(name, ts, value):
    """The timeseries listener: route each ring append through every
    matching sentinel. Must never raise (it runs inline on the metric
    hot path while sentinels are enabled)."""
    for s in list(_state.sentinels):
        try:
            if s.matches(name):
                detail = s.observe(name, ts, value)
                if detail is not None:
                    _fire(s, name, ts, value, detail)
                elif s.recovered(name):
                    _recover(s, name)
        except Exception as e:
            # must never raise (inline on the metric hot path), but a
            # sentinel dying forever deserves one line
            _registry.warn_once(
                "perf.sentinel.%s" % type(s).__name__,
                "paddle_tpu.monitor.perf: sentinel %s raised while "
                "observing %r (sentinel stays enabled): %r"
                % (type(s).__name__, name, e))


def enable_sentinels(sentinels=None):
    """Install the detector set (default: NaN loss, loss spike,
    throughput regression, grad-norm explosion) over the time-series
    ring — enabling the ring if it is off (detectors read it)."""
    _state.sentinels = list(sentinels if sentinels is not None
                            else default_sentinels())
    if not _timeseries.is_enabled():
        _timeseries.enable()
    _timeseries.add_listener(_dispatch)
    _state.listener_installed = True


def add_sentinel(sentinel):
    """Plug one more detector into the enabled set."""
    if not _state.listener_installed:
        enable_sentinels([])
    _state.sentinels.append(sentinel)
    return sentinel


def disable_sentinels():
    _timeseries.remove_listener(_dispatch)
    _state.listener_installed = False
    _state.sentinels = []


def is_degraded():
    return _state.degraded_since is not None


def clear_anomalies():
    """Acknowledge the incident: the degraded flag and recent-event
    list reset (the ``perf_anomalies_total`` counter is monotone and
    keeps its history)."""
    with _state.lock:
        _state.degraded_since = None
        _state.events = []
        _state.anomaly_counts = {}
    # the incident table is the healthz source of truth while the SLO
    # plane is on — acknowledging here must clear it there too, or the
    # flag would change what clear_anomalies means (pinned equivalent).
    try:
        from . import incidents as _incidents

        _incidents.resolve_source("perf", reason="anomalies cleared")
    except Exception as e:
        _registry.warn_once(
            "perf.incident_clear",
            "paddle_tpu.monitor.perf: incident clear failed (local "
            "anomaly state was still reset): %r" % (e,))


def anomaly_summary():
    with _state.lock:
        return {
            "degraded": _state.degraded_since is not None,
            "degraded_since": _state.degraded_since,
            "counts": dict(_state.anomaly_counts),
            "recent": list(_state.events[-8:]),
        }


# -- payload / routes --------------------------------------------------------

def perf_payload():
    """The /debugz/perf JSON body: per-job attribution + anomaly state
    + the machine model the MFUs were computed against."""
    with _state.lock:
        jobs = {j: dict(r) for j, r in _state.jobs.items()}
    kind, peaks = _local_peaks()
    return {
        "enabled": {
            "attribution": attribution_enabled(),
            "timeseries": _timeseries.is_enabled(),
            "sentinels": sentinels_enabled(),
        },
        "device_kind": kind,
        # whatever is known about this kind; an unknown kind shows {}
        # and every job above carries no mfu (machine_spec raised)
        "machine": peaks,
        "jobs": jobs,
        "anomalies": anomaly_summary(),
        "time": time.time(),
    }


def reset():
    """Test hook: forget job reports and anomaly state."""
    clear_anomalies()
    with _state.lock:
        _state.jobs = {}


# env/FLAGS bootstrap, mirroring timeseries: sentinels armed from the
# first sample in a process started with FLAGS_perf_sentinels=1
if _flag("FLAGS_perf_sentinels"):
    enable_sentinels()
