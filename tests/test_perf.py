"""paddle_tpu.monitor.perf + timeseries: MFU/goodput attribution, the
metric time-series ring, and the regression sentinels.

Covers the ISSUE-5 acceptance surface:
- time-series ring semantics (bounded, labeled series, histogram raw
  observations) and the hard disabled-path pinning: flags off means the
  registry hook slot stays None, zero native calls, zero extra threads;
- sentinels: synthetic NaN-loss, loss-spike, throughput-cliff and
  grad-norm traces each fire exactly their own detector and nothing
  else; a clean warmup window never fires; firings land in
  perf_anomalies_total{kind}, the flight-recorder ring, and the
  /healthz degraded flag (and are invisible to the desync diagnoser);
- compiled-train-step attribution: mfu / model_flops / hbm_peak_bytes /
  compute-comm-host phase split published to the registry, served live
  at /debugz/perf + /debugz/timeseries + Prometheus;
- a forced NaN-loss training run increments
  perf_anomalies_total{kind="nan_loss"} and marks /healthz degraded;
- serving goodput + KV-page occupancy under the flag;
- watchdog bundles embed the last-K time-series tail;
- the tools/perf_report.py CPU smoke prints MFU, phase split, and HBM
  peak (the CLI acceptance row).
"""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import threading
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.monitor import flight_recorder as frmod
from paddle_tpu.monitor import perf
from paddle_tpu.monitor import registry as mreg
from paddle_tpu.monitor import timeseries as ts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _perf_clean(cpu_peaks):
    """Every test starts and ends with perf/timeseries at their
    defaults (off) and no anomaly state — later suites (serving,
    watchdog) must see a pristine monitor. ``cpu_peaks`` (conftest)
    supplies the MFU denominator the 'cpu' device kind does not have."""
    mreg.enable(trace_bridge=False)
    yield
    paddle.set_flags({"FLAGS_perf_attribution": False,
                      "FLAGS_perf_sentinels": False,
                      "FLAGS_monitor_timeseries": False})
    perf.disable_sentinels()
    perf.reset()
    ts.disable()
    ts.clear()
    mreg.enable(trace_bridge=False)


def _counts():
    return perf.anomaly_summary()["counts"]


# ---------------------------------------------------------------------------
# time-series ring
# ---------------------------------------------------------------------------

class TestTimeSeriesRing:
    def test_gauge_and_counter_recorded_with_labels(self):
        ts.enable()
        g = monitor.gauge("t_ts_gauge")
        g.set(1.5)
        g.set(2.5)
        c = monitor.counter("t_ts_counter_total", labelnames=("k",))
        c.labels(k="a").inc(2)
        c.labels(k="a").inc(3)
        assert ts.get_ring("t_ts_gauge").values() == [1.5, 2.5]
        # counters ring their CUMULATIVE value, labeled series form
        assert ts.get_ring('t_ts_counter_total{k="a"}').values() == [2, 5]

    def test_ring_bounded(self):
        ts.enable(capacity=4)
        g = monitor.gauge("t_ts_bounded")
        for i in range(10):
            g.set(float(i))
        ring = ts.get_ring("t_ts_bounded")
        assert len(ring) == 4
        assert ring.values() == [6.0, 7.0, 8.0, 9.0]
        ts.enable(capacity=ts.DEFAULT_CAPACITY)

    def test_histogram_rings_raw_observation(self):
        ts.enable()
        h = monitor.histogram("t_ts_hist_seconds", buckets=(1, 10))
        h.observe(0.25)
        h.observe(4.0)
        assert ts.get_ring("t_ts_hist_seconds").values() == [0.25, 4.0]

    def test_snapshot_and_tail_filtering(self):
        ts.enable()
        monitor.gauge("t_ts_snap_a").set(1)
        monitor.gauge("t_ts_snap_b").set(2)
        snap = ts.snapshot(match="t_ts_snap_a")
        assert list(snap) == ["t_ts_snap_a"]
        assert snap["t_ts_snap_a"]["points"][0][1] == 1
        tail = ts.tail(prefixes=("t_ts_snap_",), k=1)
        assert set(tail) == {"t_ts_snap_a", "t_ts_snap_b"}

    def test_timestamps_monotone_nondecreasing(self):
        ts.enable()
        g = monitor.gauge("t_ts_stamps")
        g.set(1)
        g.set(2)
        stamps = [p[0] for p in ts.get_ring("t_ts_stamps").tail()]
        assert stamps == sorted(stamps)

    def test_disabled_records_nothing(self):
        g = monitor.gauge("t_ts_off")
        g.set(7)
        assert ts.get_ring("t_ts_off") is None
        assert mreg._state.ts_hook is None

    def test_nonfinite_gauge_survives_prometheus_export(self):
        """A NaN loss gauge (the sentinel's input) must not crash the
        /metrics scrape mid-incident — exposition-format spellings."""
        g = monitor.gauge("t_ts_nonfinite")
        g.set(float("nan"))
        txt = monitor.get_registry().prometheus_text()
        assert "t_ts_nonfinite NaN" in txt
        g.set(float("inf"))
        assert "t_ts_nonfinite +Inf" in \
            monitor.get_registry().prometheus_text()
        g.set(float("-inf"))
        assert "t_ts_nonfinite -Inf" in \
            monitor.get_registry().prometheus_text()


# ---------------------------------------------------------------------------
# disabled-path pinning (the CI satellite)
# ---------------------------------------------------------------------------

class TestDisabledPathPinning:
    def test_flags_default_off(self):
        flags = paddle.get_flags(["FLAGS_monitor_timeseries",
                                  "FLAGS_perf_attribution",
                                  "FLAGS_perf_sentinels"])
        assert not any(flags.values())
        assert mreg._state.ts_hook is None
        assert not ts.is_enabled()
        assert not perf.sentinels_enabled()
        assert not perf.attribution_enabled()

    def test_zero_native_calls_zero_threads_hot_path_unchanged(
            self, monkeypatch):
        """The PR 2/PR 3 pinning style: with the monitor disabled and
        perf/timeseries at their defaults, the instrumented hot paths —
        registry mutators, the serving metric hooks — make zero native
        calls, start zero threads, leave the ring hook slot None, and
        record nothing into the perf payload."""
        from paddle_tpu.core import native
        from paddle_tpu.serving.metrics import EngineMetrics

        monkeypatch.setattr(
            native, "get_lib",
            lambda: pytest.fail("disabled perf touched the native lib"))
        threads_before = set(threading.enumerate())
        perf.reset()
        mreg.disable()
        # trace bridge armed: would call native if any gate leaked
        mreg._state.trace_bridge = True
        mreg._state._trace_fn = None
        c = monitor.counter("t_pin_total", labelnames=("k",))
        g = monitor.gauge("t_pin_gauge")
        h = monitor.histogram("t_pin_seconds")
        for i in range(50):
            c.labels(k="a").inc()
            g.set(i)
            h.observe(0.01)
        em = EngineMetrics(max_slots=4)
        em.on_request_in()
        em.on_decode_step(2)
        em.on_output_token()
        em.on_request_finished(1)
        assert mreg._state.ts_hook is None
        assert ts.get_ring("t_pin_gauge") is None
        assert perf.perf_payload()["jobs"] == {}
        assert set(threading.enumerate()) == threads_before

    def test_monitor_on_flags_off_adds_no_ring_no_payload(self):
        """Monitor ENABLED but perf flags off (the common production
        default): registry mutators run their pre-perf hot path — hook
        slot None, nothing ringed, perf payload empty — and the serving
        finish hook never reaches note_job."""
        from paddle_tpu.serving.metrics import EngineMetrics

        perf.reset()
        g = monitor.gauge("t_pin_on_gauge")
        for i in range(20):
            g.set(i)
        em = EngineMetrics(max_slots=2)
        em.on_admission()
        em.on_output_token()
        em.on_request_finished(1)
        em.on_kv_occupancy(0.5)
        assert mreg._state.ts_hook is None
        assert ts.get_ring("t_pin_on_gauge") is None
        assert perf.perf_payload()["jobs"] == {}

    def test_disable_restores_boot_fast_path(self):
        ts.enable()
        assert mreg._state.ts_hook is not None
        ts.disable()
        assert mreg._state.ts_hook is None


# ---------------------------------------------------------------------------
# sentinels over synthetic traces
# ---------------------------------------------------------------------------

class TestSentinels:
    def _arm(self):
        perf.reset()
        ts.clear()
        perf.enable_sentinels()     # fresh detector instances

    def test_clean_warmup_window_never_fires(self):
        self._arm()
        for i in range(8):
            ts.record("train_loss", 1.0 + 0.01 * i)
            ts.record("train_tokens_per_s", 1000.0 + i)
            ts.record("train_grad_norm", 1.0)
        assert _counts() == {}
        assert not perf.is_degraded()

    def test_nan_loss_fires_exactly_its_detector(self):
        self._arm()
        for _ in range(10):
            ts.record("train_loss", 1.0)
        ts.record("train_loss", float("nan"))
        assert _counts() == {"nan_loss": 1}
        # latched: a contiguous NaN tail is ONE incident...
        ts.record("train_loss", float("inf"))
        assert _counts() == {"nan_loss": 1}
        # ...and recovery + relapse is a second one
        ts.record("train_loss", 1.0)
        ts.record("train_loss", float("nan"))
        assert _counts() == {"nan_loss": 2}

    def test_loss_spike_fires_exactly_its_detector(self):
        self._arm()
        for i in range(12):
            ts.record("train_loss", 1.0 + 0.02 * (i % 3))
        ts.record("train_loss", 10.0)
        assert _counts() == {"loss_spike": 1}

    def test_throughput_cliff_fires_exactly_its_detector(self):
        self._arm()
        for i in range(12):
            ts.record("train_tokens_per_s", 1000.0 + i)
        ts.record("train_tokens_per_s", 300.0)
        assert _counts() == {"throughput_regression": 1}

    def test_grad_norm_explosion_fires_exactly_its_detector(self):
        self._arm()
        for _ in range(12):
            ts.record("train_grad_norm", 1.0)
        ts.record("train_grad_norm", 50.0)
        assert _counts() == {"grad_norm_explosion": 1}

    def test_firing_reaches_counter_flight_ring_and_healthz(self):
        from paddle_tpu.monitor import watchdog as wd

        self._arm()
        frmod.get_flight_recorder().clear()
        for _ in range(10):
            ts.record("train_loss", 1.0)
        ts.record("train_loss", float("nan"))
        # 1. the labeled counter
        ctr = monitor.get_registry().get("perf_anomalies_total")
        assert ctr.labels(kind="nan_loss").value >= 1
        # 2. a structured flight-recorder event
        evs = [e for e in frmod.get_flight_recorder().entries()
               if e.get("event") == "perf_anomaly"]
        assert evs and evs[-1]["data"]["anomaly_kind"] == "nan_loss"
        # 3. /healthz flips degraded (200, not 503 — degraded is alive)
        payload = wd.healthz_payload()
        assert payload["degraded"] is True
        assert payload["status"] == "degraded"
        code, _, _ = wd.http_healthz()
        assert code == 200
        # acknowledged incident resets the flag, not the counter
        perf.clear_anomalies()
        assert wd.healthz_payload()["degraded"] is False
        assert ctr.labels(kind="nan_loss").value >= 1

    def test_events_invisible_to_desync_diagnosis(self):
        """A perf anomaly on ONE rank must never read as a collective
        stream divergence."""
        self._arm()
        fr = frmod.FlightRecorder(capacity=16)
        with fr.record("all_reduce", shape=(4,), dtype="float32"):
            pass
        fr.note_event("perf_anomaly", anomaly_kind="nan_loss")
        with fr.record("all_reduce", shape=(4,), dtype="float32"):
            pass
        peer = frmod.FlightRecorder(capacity=16)
        with peer.record("all_reduce", shape=(4,), dtype="float32"):
            pass
        with peer.record("all_reduce", shape=(4,), dtype="float32"):
            pass
        rep = frmod.diagnose({0: fr.entries(), 1: peer.entries()},
                             world_size=2)
        assert rep["status"] == "consistent"

    def test_pluggable_sentinel(self):
        self._arm()

        class Always(perf.Sentinel):
            kind = "custom_kind"

            def check(self, st, value):
                return {"value": value} if value > 5 else None

        perf.add_sentinel(Always("t_custom_series", warmup=2))
        ts.record("t_custom_series", 9.0)   # warmup sample 0: no fire
        ts.record("t_custom_series", 9.0)   # warmup sample 1: no fire
        assert "custom_kind" not in _counts()
        ts.record("t_custom_series", 9.0)
        assert _counts()["custom_kind"] == 1


# ---------------------------------------------------------------------------
# compiled-train-step attribution (the acceptance core)
# ---------------------------------------------------------------------------

def _tiny_step(loss_fn=None):
    import paddle_tpu.nn.functional as F
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.parallel.engine import CompiledTrainStep

    paddle.seed(0)
    cfg = LlamaConfig.tiny(use_parallel=False)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    if loss_fn is None:
        def loss_fn(logits, labels):
            return F.cross_entropy(
                logits.reshape([-1, cfg.vocab_size]),
                labels.reshape([-1]))
    step = CompiledTrainStep(model, loss_fn, opt)
    rng = np.random.RandomState(0)
    # batch 8: divisible by the 8-way virtual-device dp mesh, so the
    # test composes with whatever mesh earlier suites left behind
    ids = paddle.to_tensor(rng.randint(
        0, cfg.vocab_size, (8, 16)).astype(np.int32))
    labels = paddle.to_tensor(rng.randint(
        0, cfg.vocab_size, (8, 16)).astype(np.int32))
    return step, ids, labels


class TestTrainAttribution:
    def test_mfu_phase_hbm_published_and_served(self):
        paddle.set_flags({"FLAGS_perf_attribution": True})
        ts.enable()
        perf.reset()
        step, ids, labels = _tiny_step()
        for _ in range(3):
            step(ids, labels)
        report = perf.perf_payload()["jobs"]["train"]
        # MFU + FLOPs + HBM from the executable analysis
        assert report["model_flops_per_step"] > 0
        assert 0 < report["mfu"] < 1
        assert report["hbm_peak_bytes"] > 0
        assert math.isfinite(report["loss"])
        # phase split covers the window
        ph = report["phase_seconds"]
        assert set(ph) == {"compute", "comm", "host"}
        assert all(v >= 0 for v in ph.values())
        share = report["phase_share"]
        assert sum(share.values()) == pytest.approx(1.0, abs=1e-6)
        # the same numbers on the registry / Prometheus surface
        txt = monitor.get_registry().prometheus_text()
        assert 'mfu{job="train"}' in txt
        assert 'model_flops{job="train"}' in txt
        assert 'hbm_peak_bytes{job="train"}' in txt
        assert 'perf_phase_seconds{job="train",phase="compute"}' in txt
        # the ring saw the per-step series
        assert len(ts.get_ring("train_step_seconds")) >= 3
        assert len(ts.get_ring('train_loss{job="train"}')) >= 3

    def test_debugz_perf_and_timeseries_routes(self):
        paddle.set_flags({"FLAGS_perf_attribution": True})
        ts.enable()
        perf.reset()
        step, ids, labels = _tiny_step()
        step(ids, labels)
        srv = monitor.MetricsServer(port=0).start()
        try:
            base = "http://127.0.0.1:%d" % srv.port
            live = json.loads(urllib.request.urlopen(
                base + "/debugz/perf").read().decode())
            train = live["jobs"]["train"]
            assert train["model_flops_per_step"] > 0
            assert train["mfu"] == \
                perf.perf_payload()["jobs"]["train"]["mfu"]
            assert set(train["phase_seconds"]) == \
                {"compute", "comm", "host"}
            series = json.loads(urllib.request.urlopen(
                base + "/debugz/timeseries").read().decode())
            assert series["enabled"] is True
            assert "train_step_seconds" in series["series"]
        finally:
            srv.stop()

    def test_run_steps_attribution(self):
        paddle.set_flags({"FLAGS_perf_attribution": True})
        perf.reset()
        step, ids, labels = _tiny_step()
        stacked_ids = paddle.to_tensor(
            np.stack([np.asarray(ids.numpy())] * 2))
        stacked_labels = paddle.to_tensor(
            np.stack([np.asarray(labels.numpy())] * 2))
        step.run_steps(stacked_ids, stacked_labels)
        report = perf.perf_payload()["jobs"]["train"]
        assert report["steps"] == 2
        assert report["model_flops_per_step"] > 0

    def test_flag_off_no_attribution_no_extra_compile(self):
        perf.reset()
        step, ids, labels = _tiny_step()
        step(ids, labels)
        assert step._perf_attr is None
        assert "train" not in perf.perf_payload()["jobs"]

    def test_phase_share_sums_to_one_even_with_gap_comm(self):
        """Comm measured in the inter-step gap (a background sync
        thread) can exceed the step call's dt — shares must still read
        as fractions of a whole."""
        tp = perf.TrainStepPerf("t_share_job", analysis_fn=None)
        tp._comm_since_last = lambda: (0.05, 1024, "flight_recorder")
        tp._last_end = 0.0
        r = tp.on_step(0.01, steps=1, tokens=10, t_start=0.02,
                       t_end=0.03)
        # comm clamps to the window (dt 0.01 + host 0.02); compute
        # floors at 0; shares still read as fractions of a whole
        assert r["phase_seconds"]["comm"] == pytest.approx(0.03)
        assert r["phase_seconds"]["compute"] == 0.0
        assert sum(r["phase_share"].values()) == pytest.approx(1.0)

    def test_debug_payloads_stay_parseable_with_nan_loss(self):
        """Strict-JSON consumers (jq, JSON.parse) must parse
        /debugz/perf mid-NaN-incident: bare NaN tokens are replaced
        with string spellings."""
        from paddle_tpu.monitor import watchdog as wd

        perf.reset()
        perf.note_job("t_nanjob", loss=float("nan"),
                      nested={"v": float("inf")})
        code, _, body = monitor.MetricsServer.__dict__["_perf"](
            type("S", (), {"_registry": None})())
        assert code == 200
        decoded = json.loads(body.decode(), parse_constant=lambda c:
                             pytest.fail("bare %s token" % c))
        assert decoded["jobs"]["t_nanjob"]["loss"] == "NaN"
        assert decoded["jobs"]["t_nanjob"]["nested"]["v"] == "Infinity"
        assert wd.json_safe(float("-inf")) == "-Infinity"

    def test_perf_analysis_shape(self):
        step, ids, labels = _tiny_step()
        a = step.perf_analysis(ids, labels)
        assert a["flops_per_step"] > 0
        assert a["hbm_peak_bytes"] > 0
        assert a["source"] == "xla_cost_analysis"
        fields = perf.bench_fields(a, tokens_per_s=1000.0,
                                   tokens_per_step=8 * 16)
        assert fields["mfu"] > 0
        assert fields["hbm_peak_bytes"] == a["hbm_peak_bytes"]


class TestForcedNaNLossRun:
    def test_nan_loss_run_increments_counter_and_degrades_healthz(self):
        """The acceptance row: a training run whose loss goes NaN."""
        from paddle_tpu.monitor import watchdog as wd

        paddle.set_flags({"FLAGS_perf_attribution": True})
        ts.enable()
        perf.enable_sentinels()
        perf.reset()
        ctr = monitor.get_registry().get("perf_anomalies_total")
        before = ctr.labels(kind="nan_loss").value

        def nan_loss(logits, labels):
            return (logits * 0.0).sum() + float("nan")

        step, ids, labels = _tiny_step(loss_fn=nan_loss)
        step(ids, labels)
        step(ids, labels)
        assert ctr.labels(kind="nan_loss").value > before
        payload = wd.healthz_payload()
        assert payload["degraded"] is True
        counts = payload["perf_anomalies"]["counts"]
        assert counts.get("nan_loss", 0) >= 1


# ---------------------------------------------------------------------------
# serving attribution
# ---------------------------------------------------------------------------

class TestServingAttribution:
    def test_goodput_and_kv_occupancy(self):
        from paddle_tpu import serving
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        paddle.set_flags({"FLAGS_perf_attribution": True})
        ts.enable()
        perf.reset()
        paddle.seed(0)
        cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=2,
                          num_attention_heads=4,
                          max_position_embeddings=64,
                          use_parallel=False)
        m = LlamaForCausalLM(cfg)
        eng = serving.Engine(m, max_slots=2, num_blocks=64, block_size=4)
        rng = np.random.RandomState(0)
        for n in (5, 9):
            eng.add_request(rng.randint(0, 64, (n,)).tolist(),
                            max_new_tokens=6)
        eng.run()
        stats = eng.stats()
        assert stats["goodput_tok_s"] > 0
        assert stats["finished_output_tokens"] == stats["output_tokens"]
        # the per-step occupancy gauge saw live pages mid-run
        ring = next((r for name, r in ts._state.rings.items()
                     if name.startswith("serving_kv_page_occupancy{")),
                    None)
        assert ring is not None and max(ring.values()) > 0
        job = perf.perf_payload()["jobs"]["serving"]
        assert job["goodput_tokens_per_s"] > 0
        assert "kv_page_occupancy" in job

    def test_goodput_excludes_unfinished_work(self):
        from paddle_tpu.serving.metrics import EngineMetrics

        paddle.set_flags({"FLAGS_perf_attribution": True})
        em = EngineMetrics(max_slots=2)
        em.on_admission()
        for _ in range(10):
            em.on_output_token()
        em.on_request_finished(4)   # only 4 of the 10 tokens finished
        d = em.to_dict()
        assert d["finished_output_tokens"] == 4
        assert d["goodput_tok_s"] < d["throughput_tok_s"]


# ---------------------------------------------------------------------------
# watchdog bundle tail (satellite)
# ---------------------------------------------------------------------------

class TestBundleTimeseriesTail:
    def test_bundle_embeds_last_k_tail(self):
        ts.enable()
        h = monitor.histogram(
            "train_step_seconds",
            buckets=(.001, .0025, .005, .01, .025, .05, .1, .25, .5,
                     1.0, 2.5, 5.0, 10.0, 30.0, 60.0))
        g = monitor.gauge("train_tokens_per_s")
        for i in range(40):
            h.observe(0.01 * (i + 1))
            g.set(1000.0 - i)
        bundle = monitor.build_bundle("test")
        tail = bundle["timeseries_tail"]
        assert "train_step_seconds" in tail
        assert "train_tokens_per_s" in tail
        # last-K bounded (PT_WATCHDOG_TS_TAIL default 32)
        assert len(tail["train_step_seconds"]) == 32
        # ...and it is the TAIL: the deceleration into a stall, not the
        # warmup
        assert tail["train_tokens_per_s"][-1][1] == 1000.0 - 39

    def test_bundle_tail_empty_when_ring_off(self):
        bundle = monitor.build_bundle("test")
        assert bundle["timeseries_tail"] == {}


# ---------------------------------------------------------------------------
# perf_report CLI (acceptance)
# ---------------------------------------------------------------------------

class TestPerfReportCLI:
    def test_cpu_smoke_prints_mfu_phase_hbm(self, tmp_path):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out_json = tmp_path / "perf.json"
        p = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "perf_report.py"),
             "--steps", "2", "--out", str(out_json)],
            capture_output=True, text=True, timeout=420, env=env,
            cwd=REPO)
        assert p.returncode == 0, p.stderr[-2000:]
        # the human report names all three acceptance numbers
        assert "mfu" in p.stdout
        assert "phase split" in p.stdout
        assert "hbm peak" in p.stdout
        assert "compute" in p.stdout and "comm" in p.stdout \
            and "host" in p.stdout
        payload = json.loads(out_json.read_text())
        train = payload["jobs"]["train"]
        assert train["model_flops_per_step"] > 0
        assert train["hbm_peak_bytes"] > 0
        assert 0 < train["mfu"] < 1
        assert payload["smoke"]["mfu"] > 0
