"""SLO/error-budget plane + unified incident manager (ISSUE 18).

Pins the contracts the rest of the stack routes on:

* disabled path (``FLAGS_monitor_slo`` off, the default): open/resolve
  are no-ops, payloads say ``enabled: false``, ZERO threads and ZERO
  ``slo_``/``incident_`` registry series materialize;
* the incident table: episode-keyed dedup (re-fire extends, never
  duplicates), ticket->page escalation (never the reverse), bounded
  resolved list, evidence merge, (rank, pid)-embedding ids;
* multi-window multi-burn-rate alerting on an INJECTED monotonic
  clock: warmup never fires, a fast-window burst without slow-window
  evidence never fires, a sustained violation opens page+ticket
  incidents exactly once per episode, recovery resolves them;
* detector round-trip: a perf sentinel firing opens an incident, its
  recovery edge resolves it, ``clear_anomalies`` acknowledges;
* /healthz single source of truth: flag off the payload is
  bit-identical to the pre-SLO shape (no ``incidents_open`` key);
  plane on, "degraded" derives from the open set;
* the fleet merge (``fleet_incidents_payload``): dedup by id across
  local + scraped tables, local wins, peer wall stamps shifted by the
  per-rank clock offset, capture manifests back-link capture dirs;
* tools/slo_report.py: --once artifact; a failed measurement is rc=3
  and writes nothing.
"""
from __future__ import annotations

import importlib.util
import json
import os
import signal
import threading
import time

import pytest

import paddle_tpu as paddle
from paddle_tpu.monitor import incidents as ptinc
from paddle_tpu.monitor import perf
from paddle_tpu.monitor import registry as mreg
from paddle_tpu.monitor import slo as ptslo
from paddle_tpu.monitor import timeseries as ts
from paddle_tpu.monitor import watchdog as wd

FLAGS = ("FLAGS_monitor_slo", "FLAGS_monitor_timeseries",
         "FLAGS_perf_sentinels")


def _reset():
    paddle.set_flags({f: False for f in FLAGS})
    ptslo.disable()
    ptslo.clear()
    ptslo.set_objectives([])
    ptinc.disable()
    ptinc.clear()
    perf.disable_sentinels()
    perf.reset()
    ts.disable()
    ts.clear()
    # drop slo_/incident_ series other tests in this session minted:
    # the disabled-path pin asserts the families stay series-free
    for m in mreg.get_registry().metrics():
        if m.name.startswith(("slo_", "incident_")):
            for store in ("_values", "_series"):
                for key in list(getattr(m, store, ()) or ()):
                    m.remove(*key)
    mreg.enable(trace_bridge=False)


@pytest.fixture(autouse=True)
def _clean():
    _reset()
    yield
    signal.alarm(0)     # a CLI test may have armed slo_report's alarm
    _reset()


class FakeClock:
    """Injected monotonic clock: window math in virtual seconds."""

    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


def _series(name):
    return (mreg.get_registry().snapshot().get(name) or {}) \
        .get("series") or []


# -- disabled path ------------------------------------------------------------

class TestDisabledPath:
    def test_flag_defaults_off(self):
        if os.environ.get("FLAGS_monitor_slo") is None:
            from paddle_tpu.core import flags as _flags_mod
            assert _flags_mod._DEFAULTS["FLAGS_monitor_slo"] is False

    def test_disabled_everything_is_inert(self):
        threads_before = set(threading.enumerate())
        assert ptinc.open("x/y", severity="page", summary="no") is None
        assert ptinc.resolve("x/y") is False
        assert ptinc.add_evidence("x/y", p="q") is False
        assert ptinc.resolve_source("perf") == 0
        assert ptinc.is_degraded() is False
        assert ptinc.payload() == {"enabled": False, "open": [],
                                   "resolved": []}
        assert ptslo.payload() == {"enabled": False, "objectives": []}
        assert ptslo.is_enabled() is False
        # a ring sample with the judge off never evaluates
        ptslo._observe("serving_ttft_seconds", time.time(), 99.0)
        assert set(threading.enumerate()) == threads_before
        for name in ("slo_attainment_ratio",
                     "slo_error_budget_remaining_ratio",
                     "slo_burn_rate", "slo_alerts_total",
                     "incident_opened_total", "incident_resolved_total",
                     "incident_open_count"):
            assert _series(name) == [], name


# -- the incident table -------------------------------------------------------

class TestIncidentTable:
    def test_open_dedup_extends_and_escalates(self):
        ptinc.enable(rank=0)
        i1 = ptinc.open("perf/x/s", severity="ticket", source="perf",
                        summary="first", evidence={"a": 1})
        i2 = ptinc.open("perf/x/s", severity="page", source="perf",
                        summary="second", evidence={"b": 2})
        assert i1 == i2
        inc = ptinc.get("perf/x/s")
        assert inc["count"] == 2
        assert inc["severity"] == "page"            # escalated
        assert inc["summary"] == "second"
        assert inc["evidence"] == {"a": 1, "b": 2}  # merged
        ptinc.open("perf/x/s", severity="ticket")
        assert ptinc.get("perf/x/s")["severity"] == "page"  # never down
        assert len(ptinc.open_incidents()) == 1
        assert _series("incident_opened_total")[0]["value"] == 1

    def test_lifecycle_resolve_moves_to_bounded_list(self, monkeypatch):
        monkeypatch.setenv("PT_INCIDENTS_CAP", "3")
        ptinc.enable()
        for i in range(5):
            ptinc.open("k/%d" % i, source="test")
            assert ptinc.is_degraded() is True
            assert ptinc.resolve("k/%d" % i, reason="done %d" % i)
        assert ptinc.is_degraded() is False
        assert ptinc.resolve("k/0") is False        # already closed
        p = ptinc.payload()
        assert p["open"] == []
        assert len(p["resolved"]) == 3              # bounded, newest kept
        assert [r["key"] for r in p["resolved"]] == \
            ["k/2", "k/3", "k/4"]
        assert p["resolved"][-1]["state"] == "resolved"
        assert p["resolved"][-1]["resolve_reason"] == "done 4"
        assert _series("incident_resolved_total")[0]["value"] == 5

    def test_resolve_source_and_evidence(self):
        ptinc.enable()
        ptinc.open("perf/a", source="perf")
        ptinc.open("perf/b", source="perf")
        ptinc.open("oom/train", source="memory")
        assert ptinc.add_evidence("perf/a", bundle="/tmp/b.json")
        assert ptinc.get("perf/a")["evidence"]["bundle"] == \
            "/tmp/b.json"
        assert ptinc.resolve_source("perf", reason="ack") == 2
        assert [i["key"] for i in ptinc.open_incidents()] == \
            ["oom/train"]

    def test_ids_embed_rank_and_pid(self):
        ptinc.enable(rank=3)
        iid = ptinc.open("a/b")
        assert iid.startswith("inc-r3-p%d-" % os.getpid())
        assert ptinc.get("a/b")["rank"] == 3


# -- burn-rate alerting on the injected clock ---------------------------------

def _objective(target=0.99):
    return ptslo.Objective("ttft", "ttft_s", kind="latency",
                           threshold=1.0, target=target, job="serving")


def _feed(clock, value, n, dt=1.0):
    for _ in range(n):
        clock.advance(dt)
        ts.record("ttft_s", value)


class TestBurnRateAlerting:
    def _enable(self, monkeypatch, min_samples=5):
        monkeypatch.setenv("PT_SLO_MIN_SAMPLES", str(min_samples))
        clock = FakeClock()
        paddle.set_flags({"FLAGS_monitor_slo": True})
        ptslo.enable(objectives=[_objective()], clock=clock)
        return clock

    def test_warmup_never_fires(self, monkeypatch):
        clock = self._enable(monkeypatch, min_samples=50)
        # 40 all-bad samples across 80 virtual seconds: elapsed passes
        # the fast window but samples < min_samples -> not warm
        _feed(clock, 5.0, 40, dt=2.0)
        assert ptinc.open_incidents() == []
        # and the mirror case: enough samples, not enough elapsed time
        ptslo.clear()
        ptinc.clear()
        monkeypatch.setenv("PT_SLO_MIN_SAMPLES", "5")
        ptslo.enable(objectives=[_objective()], clock=clock)
        _feed(clock, 5.0, 30, dt=0.5)   # 15s < the 60s fast window
        assert ptinc.open_incidents() == []

    def test_compliant_workload_never_alerts(self, monkeypatch):
        clock = self._enable(monkeypatch)
        _feed(clock, 0.1, 200, dt=4.0)  # 800 virtual s, all good
        assert ptinc.open_incidents() == []
        obj = ptslo.payload()["objectives"][0]
        assert obj["attainment"] == 1.0
        assert obj["budget_remaining_ratio"] == 1.0
        assert not any(obj["alerting"].values())
        assert _series("slo_alerts_total") == []

    def test_fast_burst_without_slow_evidence_never_pages(
            self, monkeypatch):
        clock = self._enable(monkeypatch)
        # 700 virtual s of good traffic fills the slow windows...
        _feed(clock, 0.1, 700, dt=1.0)
        # ...then a 20s all-bad burst: the page-fast window burns hot,
        # but page-slow (600s) attainment is 580/600 -> burn ~3.3 < 10
        _feed(clock, 5.0, 20, dt=1.0)
        burns = ptslo.payload()["objectives"][0]["burn_rate"]
        assert burns["page_fast"] > 10.0
        assert burns["page_slow"] < 10.0
        assert not any(i["key"].startswith("slo/ttft/page")
                       for i in ptinc.open_incidents())

    def test_sustained_violation_alerts_once_then_resolves(
            self, monkeypatch):
        clock = self._enable(monkeypatch)
        _feed(clock, 5.0, 120, dt=1.0)  # 120 virtual s, all bad
        keys = sorted(i["key"] for i in ptinc.open_incidents())
        assert keys == ["slo/ttft/page", "slo/ttft/ticket"]
        page = ptinc.get("slo/ttft/page")
        assert page["severity"] == "page"
        assert page["source"] == "slo"
        assert page["evidence"]["burn_threshold"] == 10.0
        ticket = ptinc.get("slo/ttft/ticket")
        assert ticket["severity"] == "ticket"
        # the alert counter counts TRANSITION EDGES, the incident
        # table counts every extension of the episode
        alerts = {s["labels"]["severity"]: s["value"]
                  for s in _series("slo_alerts_total")}
        assert alerts == {"page": 1, "ticket": 1}
        assert page["count"] > 1
        # recovery: a quiet gap then sustained good traffic empties
        # both fast windows -> both grades resolve
        clock.advance(400.0)
        _feed(clock, 0.1, 80, dt=1.0)
        assert ptinc.open_incidents() == []
        resolved = {i["key"]: i for i in ptinc.payload()["resolved"]}
        assert resolved["slo/ttft/page"]["resolve_reason"] == \
            "fast-window burn recovered"
        obj = ptslo.payload()["objectives"][0]
        assert not any(obj["alerting"].values())
        # alert counter unchanged by the resolve (monotone, edges only)
        alerts = {s["labels"]["severity"]: s["value"]
                  for s in _series("slo_alerts_total")}
        assert alerts == {"page": 1, "ticket": 1}

    def test_window_scale_env(self, monkeypatch):
        monkeypatch.setenv("PT_SLO_WINDOW_SCALE", "0.01")
        paddle.set_flags({"FLAGS_monitor_slo": True})
        ptslo.enable(objectives=[_objective()], clock=FakeClock())
        grades = {g["grade"]: g for g in ptslo.payload()["grades"]}
        assert grades["page"]["fast_s"] == pytest.approx(0.6)
        assert grades["page"]["slow_s"] == pytest.approx(6.0)
        assert grades["ticket"]["slow_s"] == pytest.approx(36.0)
        assert grades["page"]["burn"] == 10.0       # thresholds unscaled

    def test_availability_objective_seeds_baseline(self, monkeypatch):
        monkeypatch.setenv("PT_SLO_MIN_SAMPLES", "5")
        clock = FakeClock()
        obj = ptslo.Objective(
            "avail", 'req_total{event="finished"}',
            kind="availability", target=0.9, job="serving",
            bad_series=("req_shed_total",))
        paddle.set_flags({"FLAGS_monitor_slo": True})
        ptslo.enable(objectives=[obj], clock=clock)
        # first cumulative sample per series seeds the baseline only
        ts.record('req_total{event="finished"}', 100.0)
        ts.record("req_shed_total", 7.0)
        assert ptslo.payload()["objectives"][0]["samples"] == 0
        # deltas judge: +20 good, +5 bad -> attainment 0.8
        clock.advance(10.0)
        ts.record('req_total{event="finished"}', 120.0)
        ts.record("req_shed_total", 12.0)
        o = ptslo.payload()["objectives"][0]
        assert o["samples"] == 25
        assert o["attainment"] == pytest.approx(0.8)

    def test_slo_gauges_publish_without_reentrant_feedback(
            self, monkeypatch):
        clock = self._enable(monkeypatch)
        _feed(clock, 0.1, 30, dt=1.0)
        att = _series("slo_attainment_ratio")
        assert att and att[0]["labels"] == {"objective": "ttft",
                                            "job": "serving"}
        assert att[0]["value"] == 1.0
        windows = {s["labels"]["window"]
                   for s in _series("slo_burn_rate")}
        assert windows == {"page_fast", "page_slow",
                           "ticket_fast", "ticket_slow"}
        # the gauge publications rode the ring too; none was ingested
        # back as an objective sample (the reentrancy latch)
        assert ptslo.payload()["objectives"][0]["samples"] == 30


# -- detector round trip ------------------------------------------------------

class TestSentinelRoundTrip:
    def _arm(self):
        paddle.set_flags({"FLAGS_monitor_slo": True,
                          "FLAGS_perf_sentinels": True})
        ts.enable()
        perf.enable_sentinels()
        ptinc.enable()

    def test_nan_episode_opens_then_recovery_resolves(self):
        self._arm()
        ts.record("train_loss", 2.0)
        ts.record("train_loss", float("nan"))
        inc = ptinc.get("perf/nan_loss/train_loss")
        assert inc is not None and inc["severity"] == "page"
        assert inc["source"] == "perf"
        assert inc["evidence"]["series"] == "train_loss"
        # the NaN tail re-fires nothing (latched): one incident
        ts.record("train_loss", float("nan"))
        assert len(ptinc.open_incidents()) == 1
        # recovery edge resolves it
        ts.record("train_loss", 2.1)
        assert ptinc.get("perf/nan_loss/train_loss") is None
        resolved = ptinc.payload()["resolved"]
        assert resolved[-1]["key"] == "perf/nan_loss/train_loss"
        # a SECOND episode opens a fresh incident
        ts.record("train_loss", float("nan"))
        assert ptinc.get("perf/nan_loss/train_loss") is not None

    def test_clear_anomalies_acknowledges_perf_incidents(self):
        self._arm()
        ts.record("train_loss", float("nan"))
        assert ptinc.open_incidents()
        perf.clear_anomalies()
        assert not [i for i in ptinc.open_incidents()
                    if i["source"] == "perf"]


# -- healthz single source of truth -------------------------------------------

class TestHealthz:
    def test_flag_off_payload_is_pre_slo_shape(self):
        p = wd.healthz_payload()
        assert "incidents_open" not in p
        assert p["status"] in ("ok", "degraded")

    def test_plane_on_degraded_derives_from_open_set(self):
        ptinc.enable()
        p = wd.healthz_payload()
        assert p["status"] == "ok" and p["incidents_open"] == 0
        ptinc.open("watchdog/stall/x/y", severity="page",
                   source="watchdog")
        p = wd.healthz_payload()
        assert p["status"] == "degraded" and p["incidents_open"] == 1
        ptinc.resolve("watchdog/stall/x/y")
        p = wd.healthz_payload()
        assert p["status"] == "ok" and p["incidents_open"] == 0


# -- fleet merge --------------------------------------------------------------

class TestFleetMerge:
    def test_disabled_payload(self):
        from paddle_tpu.monitor import fleet
        assert fleet.fleet_incidents_payload() == \
            {"enabled": False, "incidents": []}

    def test_merge_dedups_aligns_and_backlinks(self, monkeypatch):
        from paddle_tpu.monitor import fleet

        ptinc.enable(rank=0)
        local_id = ptinc.open("fleet/straggler/rank1", source="fleet",
                              summary="local view")
        # a collector that scraped rank 1: one incident the local
        # table ALSO holds (dedup, local wins) + one only rank 1 has
        c = fleet.FleetCollector(endpoints={1: "http://127.0.0.1:1"})
        remote_only = {
            "id": "inc-r1-p999-1", "key": "oom/train",
            "kind": "oom", "source": "memory", "severity": "page",
            "summary": "rank 1 oom", "rank": 1, "state": "open",
            "opened_at": 1000.0, "last_seen": 1000.0, "count": 1,
            "evidence": {"postmortem": "/tmp/pm.json"},
        }
        dup = {
            "id": local_id, "key": "fleet/straggler/rank1",
            "kind": "fleet", "source": "fleet", "severity": "ticket",
            "summary": "scraped copy", "rank": 0, "state": "open",
            "opened_at": 999.0, "last_seen": 999.0, "count": 9,
            "evidence": {},
        }
        with c._lock:
            c._ranks[1] = {"rank": 1, "clock_offset_s": 5.0,
                           "scraped_at": time.monotonic(),
                           "_incidents": {"open": [remote_only, dup],
                                          "resolved": []}}
            c._captures.append({"dir": "/tmp/cap_1",
                                "incidents": ["inc-r1-p999-1"]})
        monkeypatch.setattr(fleet, "_collector", c)

        p = fleet.fleet_incidents_payload()
        assert p["enabled"] is True
        by_id = {i["id"]: i for i in p["incidents"]}
        assert len(by_id) == 2                      # deduped by id
        assert by_id[local_id]["origin"] == "local"
        assert by_id[local_id]["summary"] == "local view"
        r = by_id["inc-r1-p999-1"]
        assert r["origin"] == "rank1" and r["origin_rank"] == 1
        # peer wall stamps shifted onto the collector's clock
        assert r["opened_at"] == pytest.approx(995.0)
        # the capture manifest back-links the dir as evidence
        assert r["evidence"]["capture_dir"] == "/tmp/cap_1"
        assert r["evidence"]["postmortem"] == "/tmp/pm.json"
        assert p["counts"]["open"] == 2
        assert p["ranks_merged"] == [1]


# -- tools/slo_report.py ------------------------------------------------------

def _load_slo_report():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "slo_report.py")
    spec = importlib.util.spec_from_file_location("slo_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestSloReportCLI:
    def test_once_writes_artifact(self, tmp_path, capsys):
        mod = _load_slo_report()
        out = str(tmp_path / "slo_snapshot.json")
        assert mod.main(["--once", "--out", out]) == 0
        signal.alarm(0)
        with open(out) as f:
            snap = json.load(f)
        assert snap["kind"] == "slo_snapshot" and snap["ok"] is True
        assert snap["source"] == "once"
        assert "slo" in snap and "incidents" in snap

    def test_failed_scrape_leaves_previous_artifact_alone(self,
                                                           tmp_path):
        mod = _load_slo_report()
        out = str(tmp_path / "slo_snapshot.json")
        good = dict(mod._base("measure"), slo={"enabled": True},
                    incidents={"enabled": True})
        mod.write_artifact(out, good)
        with open(out, "rb") as f:
            before = f.read()
        # a dead endpoint fails the scrape: rc=3, artifact untouched
        rc = mod.main(["--endpoint", "http://127.0.0.1:1",
                       "--out", out])
        signal.alarm(0)
        assert rc == 3
        with open(out, "rb") as f:
            assert f.read() == before

    def test_failed_scrape_writes_no_file(self, tmp_path):
        mod = _load_slo_report()
        out = str(tmp_path / "slo_snapshot.json")
        rc = mod.main(["--endpoint", "http://127.0.0.1:1",
                       "--out", out])
        signal.alarm(0)
        assert rc == 3
        assert not os.path.exists(out)
