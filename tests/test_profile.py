"""paddle_tpu.monitor.profile — the ISSUE-13 continuous profiling plane.

Covers the acceptance surface:
- hard disabled-path pinning (PR-2/5/6/12 style): `FLAGS_monitor_profile`
  off ⇒ engines latch `step_hook()` = None, zero daemon threads, zero
  native calls from the plane's entry points, zero `profile_*` registry
  series, both debugz routes report disabled (route matrix in
  tests/test_debugz_routes.py);
- sampler overhead bound: at the default `PT_PROFILE_HZ` the sampler's
  self-time stays under 1% of wall on a busy process;
- folded-stack component attribution on a synthetic workload: a hot
  function whose name matches the `tokenize` component dominates the
  folded profile and the component shares;
- anomaly-triggered capture: a forced throughput-cliff sentinel run
  arms a one-shot window, the next hot steps produce a
  `profile_capture_<ts>/` artifact (manifest + folded host stacks whose
  component attribution names the synthetic hot component), and the
  cooldown defers — never drops — a second trigger;
- measured phase reconciliation: `profile_dispatch_seconds` /
  `profile_host_blocked_seconds` / `profile_host_gap_seconds` publish
  per hot step, mirror into /debugz/perf job rows, and
  tools/perf_report.py renders the measured-vs-analytic diff without
  fabricating an absent side;
- the profiler Xprof session guard: ptprof and a manual Profiler can
  never double-start_trace, and an owner cannot stop a window it did
  not start;
- watchdog bundles embed the sampler's time-weighted `profile_folded`;
- tools/profile_snapshot.py: --once CLI smoke; a failed measurement is
  rc=3 and writes nothing.
"""
from __future__ import annotations

import importlib.util
import io
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor, serving
from paddle_tpu.monitor import perf
from paddle_tpu.monitor import profile as pprof
from paddle_tpu.monitor import registry as mreg
from paddle_tpu.monitor import timeseries as ts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROFILE_SERIES = ("profile_dispatch_seconds",
                  "profile_host_blocked_seconds",
                  "profile_host_gap_seconds",
                  "profile_samples_total",
                  "profile_captures_total")


@pytest.fixture(autouse=True)
def _prof_clean():
    """Every test starts and ends with the profiling plane at its
    default (off), no sampler thread, no capture state — later suites
    must see a pristine monitor."""
    _reset()
    yield
    _reset()


def _reset():
    from paddle_tpu.monitor import memory as ptmem
    from paddle_tpu.resilience import faultinject as fi

    fi.disable()
    fi._state.rules = []
    # drop fault-counter samples this suite's injections created (the
    # resilience suite pins the counter sample-free on its disabled
    # path, and counters are process-global — the test_memory hygiene)
    m = mreg.get_registry().get("faults_injected_total")
    if m is not None:
        for key in list(m._children):
            m.remove(*key)
    paddle.set_flags({"FLAGS_monitor_profile": False,
                      "FLAGS_monitor_memory": False,
                      "FLAGS_perf_attribution": False,
                      "FLAGS_perf_sentinels": False,
                      "FLAGS_monitor_timeseries": False})
    ptmem.reset()
    pprof.reset()
    perf.disable_sentinels()
    perf.reset()
    ts.disable()
    ts.clear()
    mreg.enable(trace_bridge=False)
    import paddle_tpu.profiler as ptprofiler

    with ptprofiler._xprof_lock:
        ptprofiler._xprof_owner = None


def _tiny_step():
    import paddle_tpu.nn.functional as F
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.parallel.engine import CompiledTrainStep

    paddle.seed(0)
    cfg = LlamaConfig.tiny(use_parallel=False)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())

    def loss_fn(logits, labels):
        return F.cross_entropy(
            logits.reshape([-1, cfg.vocab_size]),
            labels.reshape([-1]))

    step = CompiledTrainStep(model, loss_fn, opt)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(
        0, cfg.vocab_size, (8, 16)).astype(np.int32))
    labels = paddle.to_tensor(rng.randint(
        0, cfg.vocab_size, (8, 16)).astype(np.int32))
    return step, ids, labels


def _tiny_engine(**kw):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=4,
                      max_position_embeddings=64, use_parallel=False)
    model = LlamaForCausalLM(cfg)
    return serving.Engine(model, **kw)


def _tokenizer_synthetic_hot(stop):
    """The synthetic hot component: the function NAME matches the
    `tokenize` attribution pattern, so samples landing here must be
    attributed to that component. The loop yields the GIL regularly —
    a pure spin can starve the sampler thread for seconds (CPython
    convoy effect) and flake the timing-based assertions; a sample
    taken mid-sleep still attributes here (time.sleep is C — this
    frame stays the Python leaf)."""
    x = 0
    while not stop.is_set():
        for _ in range(512):
            x = (x * 31 + 7) % 1000003
        time.sleep(0.0005)
    return x


def _run_hot_thread():
    stop = threading.Event()
    t = threading.Thread(target=_tokenizer_synthetic_hot, args=(stop,),
                         name="t-prof-hot", daemon=True)
    t.start()
    return stop, t


# ---------------------------------------------------------------------------
# disabled-path pinning (PR-2/5/6/12 style)
# ---------------------------------------------------------------------------

class TestDisabledPathPinning:
    def test_flag_default_off(self):
        assert not paddle.get_flags(
            ["FLAGS_monitor_profile"])["FLAGS_monitor_profile"]
        assert not pprof.is_enabled()

    def test_off_zero_native_zero_threads_zero_series(self, monkeypatch):
        from paddle_tpu.core import native

        with monkeypatch.context() as m:
            m.setattr(native, "get_lib", lambda: pytest.fail(
                "disabled profile plane touched native lib"))
            assert pprof.step_hook("t_off") is None
            assert pprof.start_sampler() is None
            assert pprof.arm_capture(reason="t_off") is False
            assert pprof.capture_window(steps=2) is False
            p = pprof.profile_payload()
            assert p["enabled"] is False and p["sampler"] is None
            assert "ptprof disabled" in pprof.folded_route_text()
            assert pprof.bundle_payload() is None
        threads_before = set(threading.enumerate())
        step, ids, labels = _tiny_step()
        assert step._prof is None
        step(ids, labels)
        eng = _tiny_engine(max_slots=2, num_blocks=32, block_size=4)
        assert eng._prof is None
        r = eng.add_request([1, 2, 3], max_new_tokens=2)
        eng.run()
        assert eng.request_status(r)["state"] == "finished"
        for name in PROFILE_SERIES:
            metric = mreg.get_registry().get(name)
            assert metric is None or list(metric.collect()) == [], name
        assert set(threading.enumerate()) == threads_before
        assert not pprof.sampler_running()
        assert pprof._state.pending == [] and pprof._state.window is None

    def test_on_anomaly_noop_while_off(self):
        assert pprof.on_anomaly("throughput_regression") is False
        assert pprof.on_stall() is False
        assert pprof.on_straggler([1]) is False
        assert pprof._state.pending == []


# ---------------------------------------------------------------------------
# sampler: overhead bound + component attribution
# ---------------------------------------------------------------------------

class TestSampler:
    def test_overhead_bound_at_default_hz(self):
        """THE overhead pin: at the default PT_PROFILE_HZ the sampler's
        own work stays under 1% of wall on a busy process."""
        paddle.set_flags({"FLAGS_monitor_profile": True})
        assert pprof._state.hz == pytest.approx(19.0)
        pprof.start_sampler()
        stop, t = _run_hot_thread()
        try:
            t0 = time.monotonic()
            with pprof._state.lock:
                base_self = pprof._state.self_time_s
                base_n = pprof._state.samples
            while time.monotonic() - t0 < 1.2:
                time.sleep(0.02)
            elapsed = time.monotonic() - t0
        finally:
            stop.set()
            t.join(timeout=5)
        with pprof._state.lock:
            self_dt = pprof._state.self_time_s - base_self
            n = pprof._state.samples - base_n
        assert n >= 5, n            # the sampler actually ran
        assert self_dt < 0.01 * elapsed, (self_dt, elapsed)
        payload = pprof.profile_payload()
        assert payload["sampler"]["overhead_share"] < 0.01

    def test_component_attribution_synthetic_workload(self):
        """A hot function whose name matches the tokenize pattern
        dominates the folded profile; the folded text carries the
        function name; counts land under the right component."""
        paddle.set_flags({"FLAGS_monitor_profile": True})
        pprof.start_sampler(hz=200)
        stop, t = _run_hot_thread()
        try:
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                comps = pprof.component_totals()
                if comps.get("tokenize", {}).get("samples", 0) >= 10:
                    break
                time.sleep(0.02)
        finally:
            stop.set()
            t.join(timeout=5)
        comps = pprof.component_totals()
        assert comps.get("tokenize", {}).get("samples", 0) >= 10, comps
        folded = pprof.folded_text()
        assert "_tokenizer_synthetic_hot" in folded
        # the hot thread's folded key leads with the thread name
        hot = [line for line in folded.splitlines()
               if line.startswith("t-prof-hot;")]
        assert hot, folded
        top = pprof.profile_payload()["top"]
        hot_rows = [r for r in top if r["component"] == "tokenize"]
        assert hot_rows and hot_rows[0]["count"] >= 10

    def test_stack_table_bounded(self):
        """Distinct-stack growth is capped: past PT_PROFILE_MAX_STACKS
        new stacks collapse into the overflow counter instead of
        growing without bound."""
        paddle.set_flags({"FLAGS_monitor_profile": True})
        with pprof._state.lock:
            pprof._state.max_stacks = 4
        pprof.start_sampler(hz=500)
        # churn distinct stacks by running distinct code objects
        fns = []
        ns = {}
        for i in range(8):
            exec("def _burn_%d(stop):\n"
                 "    x = 0\n"
                 "    while not stop.is_set():\n"
                 "        x = (x + %d) %% 99991\n" % (i, i + 1), ns)
            fns.append(ns["_burn_%d" % i])
        stop = threading.Event()
        threads = [threading.Thread(target=f, args=(stop,), daemon=True)
                   for f in fns]
        for t in threads:
            t.start()
        try:
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                with pprof._state.lock:
                    if pprof._state.overflow > 0:
                        break
                time.sleep(0.02)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=5)
        with pprof._state.lock:
            # cap + the bounded per-component overflow buckets
            real = [k for k in pprof._state.stacks
                    if not k.startswith("(overflow);")]
            assert len(real) <= 4
            assert len(pprof._state.stacks) <= \
                4 + len(pprof.COMPONENT_PATTERNS) + 1
            assert pprof._state.overflow > 0
            # saturated samples kept their component attribution
            assert any(k.startswith("(overflow);")
                       for k in pprof._state.stacks)


# ---------------------------------------------------------------------------
# measured phase reconciliation
# ---------------------------------------------------------------------------

class TestMeasuredPhases:
    def test_step_profiler_gauges_and_note_job_mirror(self):
        paddle.set_flags({"FLAGS_monitor_profile": True})
        sp = pprof.step_hook("t_job")
        assert sp is not None
        t0 = 100.0
        sp.step_begin()
        out = sp.step_end(t0, t0 + 0.5)
        assert out["dispatch_s"] == pytest.approx(0.5)
        assert out["gap_s"] == 0.0
        sp.step_begin()
        out = sp.step_end(t0 + 0.7, t0 + 0.8)
        assert out["gap_s"] == pytest.approx(0.2)   # 0.7 - prev end 0.5
        g = mreg.get_registry().get("profile_dispatch_seconds")
        assert dict(g.collect())[("t_job",)] == pytest.approx(0.1)
        g = mreg.get_registry().get("profile_host_gap_seconds")
        assert dict(g.collect())[("t_job",)] == pytest.approx(0.2)
        # mirrored into the /debugz/perf job row for perf_report
        row = perf.perf_payload()["jobs"]["t_job"]
        assert row["profile_dispatch_seconds"] == pytest.approx(0.1)
        assert row["profile_host_gap_seconds"] == pytest.approx(0.2)
        sp.note_phase("prefill", 0.05)
        sp.note_phase("prefill", 0.05)
        tot = pprof.job_totals()["t_job"]
        assert tot["steps"] == 2
        assert tot["phases"]["prefill"] == pytest.approx(0.1)

    def test_train_step_publishes_measured_split(self):
        paddle.set_flags({"FLAGS_monitor_profile": True})
        step, ids, labels = _tiny_step()
        assert step._prof is not None
        step(ids, labels)
        step(ids, labels)
        tot = pprof.job_totals()["train"]
        assert tot["steps"] == 2
        assert tot["dispatch_s"] > 0
        row = perf.perf_payload()["jobs"]["train"]
        for k in ("profile_dispatch_seconds",
                  "profile_host_blocked_seconds",
                  "profile_host_gap_seconds"):
            assert isinstance(row[k], float), k

    def test_serving_step_publishes_phases(self):
        paddle.set_flags({"FLAGS_monitor_profile": True})
        eng = _tiny_engine(max_slots=2, num_blocks=64, block_size=4)
        assert eng._prof is not None
        eng.add_request([1, 2, 3, 4], max_new_tokens=4)
        eng.run()
        tot = pprof.job_totals()["serving"]
        assert tot["steps"] >= 1
        assert tot["phases"].get("prefill", 0) > 0
        assert tot["phases"].get("decode", 0) > 0

    def test_perf_report_measured_vs_analytic_no_fabrication(self):
        spec = importlib.util.spec_from_file_location(
            "t_perf_report", os.path.join(REPO, "tools",
                                          "perf_report.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        both = {"jobs": {"train": {
            "phase_seconds": {"compute": 0.8, "comm": 0.1,
                              "host": 0.05},
            "comm_source": "analytic",
            "profile_dispatch_seconds": 0.7,
            "profile_host_blocked_seconds": 0.25,
            "profile_host_gap_seconds": 0.06,
        }}}
        buf = io.StringIO()
        mod.render_measured(both, buf)
        text = buf.getvalue()
        assert "exposed-comm residual" in text
        assert "delta" in text
        # measured only: the analytic side is ABSENT, not zero
        meas_only = {"jobs": {"train": {
            "profile_dispatch_seconds": 0.7,
            "profile_host_blocked_seconds": 0.25,
            "profile_host_gap_seconds": 0.06}}}
        buf = io.StringIO()
        mod.render_measured(meas_only, buf)
        assert "no diff fabricated" in buf.getvalue()
        assert "residual" not in buf.getvalue()
        # analytic only: the measured side is ABSENT, not zero
        analytic_only = {"jobs": {"train": {
            "phase_seconds": {"compute": 0.8, "comm": 0.1,
                              "host": 0.05}}}}
        buf = io.StringIO()
        mod.render_measured(analytic_only, buf)
        assert "no diff fabricated" in buf.getvalue()


# ---------------------------------------------------------------------------
# anomaly-triggered capture windows
# ---------------------------------------------------------------------------

class TestCaptureWindows:
    def test_throughput_cliff_arms_and_captures(self, monkeypatch,
                                                tmp_path):
        """THE acceptance path: a forced throughput-cliff sentinel run
        arms a capture window; the next hot steps finalize it into a
        profile_capture_<ts>/ artifact whose folded host stacks name
        the synthetic hot component; a second trigger inside the
        cooldown is deferred, never dropped."""
        monkeypatch.setenv("PT_MONITOR_DUMP_DIR", str(tmp_path))
        monkeypatch.setenv("PT_PROFILE_CAPTURE_STEPS", "2")
        paddle.set_flags({"FLAGS_monitor_profile": True})
        pprof.start_sampler(hz=200)
        pprof._state.cooldown_s = 3600.0
        perf.enable_sentinels()
        # compile OUTSIDE the window: the capture must be of the
        # anomalous steady-state steps, not a trace-time churn blob
        step, ids, labels = _tiny_step()
        step(ids, labels)
        # drop the compile-churn stacks so the (bounded) table has
        # room for the synthetic hot component's exact stack
        with pprof._state.lock:
            pprof._state.stacks = {}
            pprof._state.overflow = 0
        stop, t = _run_hot_thread()
        time.sleep(0.15)    # the hot thread's stack registers
        try:
            # synthetic throughput trace: healthy warmup, then the cliff
            for _ in range(12):
                ts.record("train_tokens_per_s", 100.0)
            ts.record("train_tokens_per_s", 1.0)
            counts = perf.anomaly_summary()["counts"]
            assert counts.get("throughput_regression", 0) >= 1
            assert len(pprof._state.pending) == 1
            assert pprof._state.pending[0]["reason"] == \
                "sentinel:throughput_regression"

            step(ids, labels)           # window opens on this step
            assert pprof._state.window is not None
            time.sleep(0.4)             # sampler sees the hot thread
            step(ids, labels)           # window closes (2 steps)
        finally:
            stop.set()
            t.join(timeout=5)
        assert pprof._state.window is None
        caps = pprof.profile_payload()["captures"]
        assert len(caps) == 1
        d = caps[0]["dir"]
        assert caps[0]["reason"] == "sentinel:throughput_regression"
        assert os.path.isdir(d) and d.startswith(str(tmp_path))
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        assert manifest["kind"] == "profile_capture"
        assert manifest["steps"] == 2
        assert "train" in manifest["jobs"]
        # contents pinned: the component attribution of the window's
        # folded stacks names the synthetic hot component
        assert manifest["components"].get(
            "tokenize", {}).get("samples", 0) > 0, manifest["components"]
        with open(os.path.join(d, "folded_rank0.txt")) as f:
            folded = f.read()
        assert "_tokenizer_synthetic_hot" in folded
        c = mreg.get_registry().get("profile_captures_total")
        assert dict(c.collect())[
            ("sentinel:throughput_regression",)] == 1

        # cooldown pinned: a fresh trigger queues (defer-not-drop) and
        # does NOT open a window while the cooldown holds...
        assert pprof.arm_capture(reason="second")
        step(ids, labels)
        assert pprof._state.window is None
        assert len(pprof._state.pending) == 1
        # ...and fires as soon as the cooldown expires (host-only: the
        # ONE real Xprof window above already proved the device path)
        monkeypatch.setattr(pprof, "_xprof_begin",
                            lambda d: (False, "patched out"))
        pprof._state.last_capture_end = time.monotonic() - 7200.0
        step(ids, labels)
        assert pprof._state.window is not None \
            or len(pprof.profile_payload()["captures"]) == 2

    def test_anomaly_kind_filter(self):
        """Only profile-shaped sentinel kinds arm a window: a NaN loss
        has no timeline to capture, a cliff and a leak do."""
        paddle.set_flags({"FLAGS_monitor_profile": True})
        assert pprof.on_anomaly("nan_loss") is False
        assert pprof._state.pending == []
        assert pprof.on_anomaly("throughput_regression") is True
        assert pprof.on_anomaly("mem_leak") is True
        assert len(pprof._state.pending) == 2

    def test_max_captures_cap(self, monkeypatch, tmp_path):
        monkeypatch.setenv("PT_MONITOR_DUMP_DIR", str(tmp_path))
        paddle.set_flags({"FLAGS_monitor_profile": True})
        monkeypatch.setattr(pprof, "_xprof_begin",
                            lambda d: (False, "patched out"))
        pprof._state.cooldown_s = 0.0
        pprof._state.max_captures = 1
        sp = pprof.step_hook("t_job")
        for i in range(2):
            pprof.arm_capture(steps=1, reason="cap%d" % i)
            sp.step_begin()
            sp.step_end(float(i), float(i) + 0.01)
        assert len(pprof.profile_payload()["captures"]) == 1
        # past the cap the queue is drained, not grown forever
        assert pprof._state.pending == []

    def test_exception_mid_window_aborts_not_leaks(self, monkeypatch,
                                                   tmp_path):
        """A hot step raising mid-window (the reviewer's OOM scenario:
        the postmortem path re-raises) must CLOSE the window — partial
        artifact lands marked aborted, the one-window state clears, and
        the Xprof session owner is released, never leaked."""
        from paddle_tpu.resilience import faultinject as fi

        monkeypatch.setenv("PT_MONITOR_DUMP_DIR", str(tmp_path))
        paddle.set_flags({"FLAGS_monitor_profile": True,
                          "FLAGS_monitor_memory": True})
        monkeypatch.setattr(pprof, "_xprof_begin",
                            lambda d: (False, "patched out"))
        pprof._state.cooldown_s = 0.0
        eng = _tiny_engine(max_slots=2, num_blocks=64, block_size=4)
        eng.add_request([1, 2, 3], max_new_tokens=4)
        assert eng.step()                   # healthy step first
        pprof.arm_capture(steps=8, reason="pre_crash")
        fi.enable("mem.oom:error@1", seed=0)
        with pytest.raises(fi.InjectedFault):
            eng.step()
        assert pprof._state.window is None
        assert pprof._state.pending == []
        caps = pprof.profile_payload()["captures"]
        assert len(caps) == 1 and caps[0]["aborted"]
        with open(os.path.join(caps[0]["dir"], "manifest.json")) as f:
            manifest = json.load(f)
        assert manifest["aborted"] and "serving" in manifest["aborted"]
        import paddle_tpu.profiler as ptprofiler
        assert ptprofiler.xprof_session_owner() is None

    def test_stall_and_straggler_hooks_arm(self):
        paddle.set_flags({"FLAGS_monitor_profile": True})
        assert pprof.on_stall([{"heartbeat": "train_step",
                                "phase": "train.step",
                                "age_s": 61.0}]) is True
        assert pprof.on_straggler([2]) is True
        reasons = [p["reason"] for p in pprof._state.pending]
        assert reasons == ["watchdog_stall", "straggler"]
        assert pprof._state.pending[0]["detail"]["stalls"][0][
            "heartbeat"] == "train_step"


# ---------------------------------------------------------------------------
# Xprof session guard (the satellite on paddle_tpu/profiler)
# ---------------------------------------------------------------------------

class TestXprofSessionGuard:
    def test_busy_path_never_double_starts(self):
        import paddle_tpu.profiler as ptprofiler

        # claim the session by hand: a second owner's begin answers
        # False on the BUSY path without ever importing/starting jax
        with ptprofiler._xprof_lock:
            ptprofiler._xprof_owner = "manual"
        try:
            assert ptprofiler.xprof_session_begin(
                "ptprof", "/nonexistent") is False
            assert ptprofiler.xprof_session_owner() == "manual"
            # an owner cannot stop a window it did not start
            assert ptprofiler.xprof_session_end("ptprof") is False
            assert ptprofiler.xprof_session_owner() == "manual"
            # the holder can
            # (stop_trace itself may warn-once — that is the narrowed,
            # routed failure path, not a swallow)
            ptprofiler.xprof_session_end("manual")
            assert ptprofiler.xprof_session_owner() is None
        finally:
            with ptprofiler._xprof_lock:
                ptprofiler._xprof_owner = None

    def test_capture_degrades_host_only_when_session_busy(
            self, monkeypatch, tmp_path):
        """A manual profiler holding the Xprof session degrades a
        ptprof window to host-only — a capture still lands."""
        import paddle_tpu.profiler as ptprofiler

        monkeypatch.setenv("PT_MONITOR_DUMP_DIR", str(tmp_path))
        paddle.set_flags({"FLAGS_monitor_profile": True})
        pprof._state.cooldown_s = 0.0
        with ptprofiler._xprof_lock:
            ptprofiler._xprof_owner = "manual"
        try:
            mreg._warned.discard("profile.xprof_begin")
            sp = pprof.step_hook("t_job")
            pprof.arm_capture(steps=1, reason="busy_test")
            sp.step_begin()
            sp.step_end(0.0, 0.01)
        finally:
            with ptprofiler._xprof_lock:
                ptprofiler._xprof_owner = None
        caps = pprof.profile_payload()["captures"]
        assert len(caps) == 1 and caps[0]["xprof"] is False
        with open(os.path.join(caps[0]["dir"], "manifest.json")) as f:
            manifest = json.load(f)
        assert manifest["xprof"] is False
        assert "session held" in (manifest["xprof_error"] or "")


# ---------------------------------------------------------------------------
# surfacing: watchdog bundle + perf payload
# ---------------------------------------------------------------------------

class TestSurfacing:
    def test_watchdog_bundle_embeds_profile_folded(self):
        paddle.set_flags({"FLAGS_monitor_profile": True})
        pprof.start_sampler(hz=200)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            with pprof._state.lock:
                if pprof._state.samples >= 3:
                    break
            time.sleep(0.02)
        bundle = monitor.build_bundle(reason="test")
        prof = bundle["profile_folded"]
        assert prof is not None
        assert prof["samples"] >= 3
        assert prof["folded"]
        assert "components" in prof

    def test_watchdog_bundle_profile_none_when_off(self):
        bundle = monitor.build_bundle(reason="test")
        assert bundle["profile_folded"] is None


# ---------------------------------------------------------------------------
# tools/profile_snapshot.py (battery row artifact)
# ---------------------------------------------------------------------------

def _load_snapshot_mod():
    spec = importlib.util.spec_from_file_location(
        "t_profile_snapshot", os.path.join(REPO, "tools",
                                           "profile_snapshot.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestProfileSnapshotTool:
    def test_failed_measurement_leaves_previous_artifact_alone(
            self, tmp_path, monkeypatch):
        mod = _load_snapshot_mod()
        out = str(tmp_path / "profile_snapshot.json")
        fresh = {"kind": "profile_snapshot", "version": 1, "ok": True,
                 "written_at": "2026-08-03T00:00:00Z",
                 "profile": {"enabled": True}}
        mod.write_artifact(out, fresh)
        with open(out, "rb") as f:
            before = f.read()

        def boom(window):
            raise RuntimeError("child died")

        monkeypatch.setattr(mod, "measure_once", boom)
        try:
            assert mod.main(["--once", "--out", out]) == 3
        finally:
            signal.alarm(0)         # main() arms a watchdog alarm
        with open(out, "rb") as f:
            assert f.read() == before
        assert json.loads(before) == fresh

    def test_failed_measurement_writes_no_file(self, tmp_path,
                                               monkeypatch):
        mod = _load_snapshot_mod()
        out = str(tmp_path / "profile_snapshot.json")
        monkeypatch.setattr(
            mod, "measure_once",
            lambda window: (_ for _ in ()).throw(RuntimeError("boom")))
        try:
            assert mod.main(["--once", "--out", out]) == 3
        finally:
            signal.alarm(0)
        assert not os.path.exists(out)

    def test_cli_once_commits(self, tmp_path):
        """The --once spelling end-to-end: a fresh ok artifact with a
        live sampler summary, no train smoke paid."""
        out = str(tmp_path / "profile_snapshot.json")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=REPO + os.pathsep +
                   os.environ.get("PYTHONPATH", ""))
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "profile_snapshot.py"),
             "--once", "--window", "0.5", "--out", out],
            capture_output=True, text=True, env=env, timeout=540)
        assert r.returncode == 0, r.stdout + r.stderr
        with open(out) as f:
            snap = json.load(f)
        assert snap["ok"] is True
        assert snap["mode"] == "once"
        prof = snap["profile"]
        assert prof["enabled"] is True
        assert prof["sampler"]["samples"] >= 1
        assert prof["sampler"]["overhead_share"] < 0.01
