"""paddle_tpu.monitor.fleet: cross-rank aggregation, straggler/skew
detection, anomaly-triggered fleet capture, and the disabled path.

Covers the ISSUE-8 acceptance surface:
- fuse semantics: counters SUM across ranks, gauges keep per-rank
  values + min/max/p50, histograms sum bucket-wise;
- straggler detector: fires once per episode after `persist`
  consecutive slow scrapes, clears on recovery, re-fires on relapse;
- disabled path (FLAGS_monitor_fleet off): announce()/note_identity()
  are no-ops — zero store traffic, zero collector threads, zero
  native calls, routes answer 200 with enabled:false;
- capture: bundles + journal tails from every rank land in one
  fleet_capture_<ts>/ dir; tools/trace_merge.py --capture renders the
  merged chrome trace from it;
- fleet snapshot artifact: a dead scrape writes nothing and leaves a
  previous artifact alone;
- the 4-process acceptance run: one artificially slowed rank is named
  as straggler while the run still makes progress (no timeout), the
  fleet_straggler_total{rank} counter increments, and a forced NaN
  sentinel produces a capture containing every rank's artifacts.
"""
from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.monitor import fleet
from paddle_tpu.monitor import registry
from paddle_tpu.monitor import trace
from paddle_tpu.monitor import trace_merge as tm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tests"))
from dist_utils import free_port, rank_env, report, run_ranks  # noqa: E402


def _fleet_threads():
    return [t for t in threading.enumerate()
            if t.name == fleet._THREAD_NAME]


@pytest.fixture(autouse=True)
def _fleet_off():
    """Every test starts and ends flag-off with no collector."""
    paddle.set_flags({"FLAGS_monitor_fleet": False})
    fleet.stop_collector()
    yield
    paddle.set_flags({"FLAGS_monitor_fleet": False})
    fleet.stop_collector()


class _RecordingStore:
    """Store stub counting traffic — the disabled path must never
    touch it."""

    def __init__(self):
        self.sets = []
        self.gets = []
        self.kv = {}

    def set(self, key, value):
        self.sets.append(key)
        self.kv[key] = value

    def get(self, key, timeout_s=None):
        self.gets.append(key)
        return self.kv.get(key)


class TestFuseSemantics:
    def test_counter_sums_gauge_spread_histogram_bucketwise(self):
        snap = lambda c, g, h_sum, h_count: {  # noqa: E731
            "reqs": {"kind": "counter", "help": "",
                     "series": [{"labels": {"code": "200"}, "value": c}]},
            "occ": {"kind": "gauge", "help": "",
                    "series": [{"labels": {}, "value": g}]},
            "lat": {"kind": "histogram", "help": "",
                    "series": [{"labels": {}, "sum": h_sum,
                                "count": h_count,
                                "buckets": {"0.1": h_count}}]},
        }
        fused = fleet.fuse_snapshots({
            0: snap(10, 0.25, 1.0, 4),
            1: snap(5, 0.75, 2.0, 8),
            2: snap(1, 0.50, 3.0, 12),
        })
        c = fused["reqs"]["series"][0]
        assert c["labels"] == {"code": "200"}
        assert c["fleet"] == {"sum": 16}
        assert c["per_rank"] == {0: 10, 1: 5, 2: 1}
        g = fused["occ"]["series"][0]["fleet"]
        assert g["min"] == 0.25 and g["max"] == 0.75
        assert g["p50"] == 0.50
        h = fused["lat"]["series"][0]["fleet"]
        assert h["sum"] == 6.0 and h["count"] == 24
        assert h["buckets"] == {"0.1": 24}

    def test_missing_rank_is_absent_not_zero(self):
        fused = fleet.fuse_snapshots({
            0: {"m": {"kind": "gauge", "help": "",
                      "series": [{"labels": {}, "value": 7.0}]}},
            1: {},
        })
        se = fused["m"]["series"][0]
        assert se["per_rank"] == {0: 7.0}
        assert se["fleet"]["min"] == se["fleet"]["max"] == 7.0


class TestStragglerDetection:
    def _collector(self, **kw):
        kw.setdefault("straggler_factor", 2.0)
        kw.setdefault("straggler_persist", 2)
        return fleet.FleetCollector(endpoints={}, world_size=4, **kw)

    def _seed(self, c, times, steps=None):
        for r, t in times.items():
            c._ranks[r] = {"rank": r, "ok": True, "step_time_s": t,
                           "steps_total": (steps or {}).get(r, 10)}

    def test_persistently_slow_rank_flagged_once(self):
        c = self._collector()
        self._seed(c, {0: 0.1, 1: 0.1, 2: 0.5, 3: 0.1},
                   steps={0: 20, 1: 20, 2: 7, 3: 20})
        assert c._detect_stragglers() == set()      # hit 1 of 2
        assert c._detect_stragglers() == {2}        # hit 2 -> fires
        assert c._detect_stragglers() == set()      # episode persists
        assert 2 in c._stragglers
        info = c._stragglers[2]
        assert info["step_time_s"] == 0.5
        assert info["fleet_median_s"] == 0.1
        assert c._ranks[2]["steps_behind"] == 13
        assert c._ranks[0]["steps_behind"] == 0

    def test_recovery_clears_and_relapse_refires(self):
        c = self._collector()
        self._seed(c, {0: 0.1, 1: 0.1, 2: 0.5, 3: 0.1})
        c._detect_stragglers()
        assert c._detect_stragglers() == {2}
        c._ranks[2]["step_time_s"] = 0.1            # recovered
        assert c._detect_stragglers() == set()
        assert 2 not in c._stragglers
        assert c._ranks[2]["straggler"] is False
        c._ranks[2]["step_time_s"] = 0.6            # relapse
        c._detect_stragglers()
        assert c._detect_stragglers() == {2}

    def test_uniform_fleet_never_flags(self):
        c = self._collector()
        self._seed(c, {r: 0.1 for r in range(4)})
        for _ in range(5):
            assert c._detect_stragglers() == set()
        assert not c._stragglers

    def test_single_rank_never_flags(self):
        c = self._collector()
        self._seed(c, {0: 9.0})
        assert c._detect_stragglers() == set()


class TestDisabledPath:
    def test_announce_no_store_traffic_no_threads(self):
        assert not fleet.is_enabled()
        store = _RecordingStore()
        assert fleet.announce(store, rank=0, world_size=2) is None
        fleet.note_identity("train")
        assert store.sets == [] and store.gets == []
        assert _fleet_threads() == []
        from paddle_tpu.monitor import exporter
        assert exporter._server is None, \
            "disabled announce must not start the metrics server"

    def test_zero_native_calls(self, monkeypatch):
        from paddle_tpu.core import native

        def _boom():
            raise AssertionError("native lib touched on the disabled "
                                 "fleet path")

        monkeypatch.setattr(native, "get_lib", _boom)
        store = _RecordingStore()
        assert fleet.announce(store, rank=0, world_size=2) is None
        fleet.note_identity("serving")
        fleet.fleet_payload()
        fleet.ranks_payload()
        fleet.prometheus_fleet_text()

    def test_routes_answer_disabled(self):
        srv = monitor.MetricsServer(port=0).start()
        try:
            base = "http://127.0.0.1:%d" % srv.port
            with urllib.request.urlopen(base + "/debugz/fleet",
                                        timeout=10) as r:
                p = json.loads(r.read().decode())
            assert r.status == 200
            assert p["enabled"] is False and p["collector"] is None
            with urllib.request.urlopen(base + "/metrics/fleet",
                                        timeout=10) as r:
                assert "not running" in r.read().decode()
        finally:
            srv.stop()


class TestEndpointRegistry:
    def test_register_and_discover_roundtrip(self):
        store = _RecordingStore()
        fleet.register_endpoint(store, 0, "http://h0:1", job="train")
        fleet.register_endpoint(store, 2, "http://h2:3")
        eps = fleet.discover_endpoints(store, 4)
        assert set(eps) == {0, 2}
        assert eps[0]["url"] == "http://h0:1"
        assert eps[0]["job"] == "train"
        assert eps[2]["rank"] == 2 and eps[2]["pid"] == os.getpid()


@pytest.fixture()
def live_server():
    """A real MetricsServer over the live registry, with enough train
    telemetry flowing that the collector sees progress."""
    paddle.set_flags({"FLAGS_monitor_fleet": True})
    srv = monitor.start_metrics_server(0)
    url = "http://127.0.0.1:%d" % srv.port
    reg = monitor.get_registry()
    stop = threading.Event()

    def feed():
        while not stop.wait(0.05):
            reg.get("train_step_seconds").observe(0.05)
            reg.get("train_steps_total").inc()

    t = threading.Thread(target=feed, daemon=True)
    t.start()
    yield url
    stop.set()
    t.join(timeout=5)
    monitor.stop_metrics_server()


class TestCollectorLive:
    def test_scrape_fuse_and_federation(self, live_server):
        c = fleet.FleetCollector(
            endpoints={0: live_server, 1: live_server}, interval_s=0.2)
        c.scrape_once()
        time.sleep(0.3)
        fused = c.scrape_once()
        assert "train_steps_total" in fused
        se = fused["train_steps_total"]["series"][0]
        assert set(se["per_rank"]) == {0, 1}
        rows = c.ranks_table()
        assert [r["rank"] for r in rows] == [0, 1]
        assert all(r["ok"] for r in rows)
        assert all(isinstance(r["step_time_s"], float) for r in rows)
        assert all(isinstance(r["clock_offset_s"], float) for r in rows)
        text = c.prometheus_text()
        assert re.search(r'train_steps_total\{rank="0"\} \d+', text)
        assert "train_steps_total_fleet_sum" in text
        assert "train_step_seconds_fleet_bucket" in text
        summary = c.summary()
        assert summary["ranks_ok"] == [0, 1]
        assert summary["stragglers"] == {}

    def test_unreachable_rank_is_an_error_row_not_a_crash(
            self, live_server):
        c = fleet.FleetCollector(
            endpoints={0: live_server,
                       1: "http://127.0.0.1:9/"},  # nothing listens
            interval_s=0.2, http_timeout_s=0.5)
        c.scrape_once()
        rows = {r["rank"]: r for r in c.ranks_table()}
        assert rows[0]["ok"] is True
        assert rows[1]["ok"] is False
        assert rows[1]["error"]
        assert rows[1]["consecutive_errors"] == 1

    def test_flight_http_error_leaves_rank_healthy(self, monkeypatch):
        """A truncated /debugz/flight body (http.client.HTTPException,
        not OSError) must leave flight_seq None — not mark the whole
        rank as a scrape error when its other endpoints answered."""
        import http.client

        real = {"/metrics.json": {"metrics": {}, "unix_time": 1.0},
                "/debugz/perf": {}, "/healthz": {"ok": True}}

        def fake_http_json(url, timeout):
            for suffix, payload in real.items():
                if url.endswith(suffix):
                    return payload, 0.0, 0.001, 0.001
            raise http.client.IncompleteRead(b"")

        monkeypatch.setattr(fleet, "_http_json", fake_http_json)
        c = fleet.FleetCollector(endpoints={0: "http://fake:1"},
                                 interval_s=0.2, http_timeout_s=0.5)
        c.scrape_once()
        rows = {r["rank"]: r for r in c.ranks_table()}
        assert rows[0]["ok"] is True
        assert rows[0]["consecutive_errors"] == 0

    def test_capture_failure_warns_not_swallows(self, monkeypatch,
                                                capsys):
        """capture() raising (disk full, unwritable dir) must leave a
        warn-once trail, not silently eat the consumed trigger."""
        c = fleet.FleetCollector(endpoints={0: "http://fake:1"},
                                 interval_s=0.2, http_timeout_s=0.5)

        def boom(reason, detail=None):
            raise OSError("disk full")

        monkeypatch.setattr(c, "capture", boom)
        # warn_once dedups on a process-global key: an earlier test that
        # drove a failing capture would consume it — make this hermetic
        registry._warned.discard("fleet.capture")
        assert c._maybe_capture(reason="test_anomaly") is None
        err = capsys.readouterr().err
        assert "anomaly capture failed" in err
        assert "test_anomaly" in err

    def test_capture_and_trace_merge_capture(self, live_server,
                                             tmp_path):
        trace.enable()
        tid = trace.new_trace("train", job="t_fleet")
        sid = trace.start_span("step", tid, kind="step")
        trace.end_span(sid)
        try:
            c = fleet.FleetCollector(
                endpoints={0: live_server, 1: live_server},
                capture_dir=str(tmp_path))
            c.scrape_once()
            d = c.capture("manual", {"why": "test"})
            assert os.path.isdir(d)
            names = sorted(os.listdir(d))
            assert "manifest.json" in names
            for r in (0, 1):
                assert "bundle_rank%d.json" % r in names
                assert "journal_rank%d.json" % r in names
            with open(os.path.join(d, "manifest.json")) as f:
                manifest = json.load(f)
            assert manifest["kind"] == "fleet_capture"
            assert manifest["reason"] == "manual"
            assert manifest["ranks"] == [0, 1]
            # journals are real write_journal artifacts
            manifest2, journals = tm.load_fleet_capture(d)
            assert set(journals) == {0, 1}
            assert tid in journals[0]["traces"]
            # one command renders the merged fleet chrome trace
            out = str(tmp_path / "merged.json")
            rc = subprocess.run(
                [sys.executable,
                 os.path.join(REPO, "tools", "trace_merge.py"),
                 "--capture", d, "--out", out],
                capture_output=True, text=True, timeout=240)
            assert rc.returncode == 0, rc.stderr[-2000:]
            with open(out) as f:
                merged = json.load(f)
            pids = {e.get("pid") for e in merged["traceEvents"]}
            assert any(str(p).startswith("rank0/") for p in pids)
            assert any(str(p).startswith("rank1/") for p in pids)
        finally:
            trace.disable()
            trace.clear()


class TestSnapshotArtifact:
    def test_dead_scrape_leaves_previous_artifact_alone(self,
                                                        live_server,
                                                        tmp_path):
        path = str(tmp_path / "fleet_snapshot.json")
        c = fleet.FleetCollector(endpoints={0: live_server})
        c.scrape_once()
        time.sleep(0.2)
        c.scrape_once()
        snap = fleet.write_snapshot_artifact(path, collector=c)
        assert snap["ok"] is True
        assert snap["ranks"][0]["rank"] == 0
        with open(path, "rb") as f:
            before = f.read()
        # a dead scrape returns not-ok and rewrites nothing
        dead = fleet.FleetCollector(
            endpoints={0: "http://127.0.0.1:9/"}, http_timeout_s=0.5)
        dead.scrape_once()
        snap2 = fleet.write_snapshot_artifact(path, collector=dead)
        assert snap2["ok"] is False and snap2["error"]
        with open(path, "rb") as f:
            assert f.read() == before

    def test_dead_scrape_writes_no_file(self, tmp_path):
        path = str(tmp_path / "fleet_snapshot.json")
        dead = fleet.FleetCollector(
            endpoints={0: "http://127.0.0.1:9/"}, http_timeout_s=0.5)
        dead.scrape_once()
        snap = fleet.write_snapshot_artifact(path, collector=dead)
        assert snap["ok"] is False
        assert not os.path.exists(path)


class TestFleetTopCLI:
    def test_once_json(self, live_server, tmp_path):
        out = str(tmp_path / "snap.json")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        rc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "fleet_top.py"),
             "--endpoints", "0=%s,1=%s" % (live_server, live_server),
             "--once", "--json", "--window", "0.4", "--out", out],
            capture_output=True, text=True, timeout=240, env=env)
        assert rc.returncode == 0, rc.stderr[-2000:]
        snap = json.loads(rc.stdout)
        assert snap["kind"] == "fleet_snapshot"
        assert [r["rank"] for r in snap["ranks"]] == [0, 1]
        assert snap["ranks"][0]["steps_total"] is not None
        with open(out) as f:
            assert json.load(f)["ok"] is True


class TestRoutesWithCollector:
    def test_debugz_fleet_carries_collector_state(self, live_server):
        fleet.start_collector(endpoints={0: live_server},
                              interval_s=0.1)
        try:
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if fleet.get_collector()._scrapes >= 2:
                    break
                time.sleep(0.1)
            with urllib.request.urlopen(live_server + "/debugz/fleet",
                                        timeout=10) as r:
                p = json.loads(r.read().decode())
            assert p["enabled"] is True
            assert p["collector"]["running"] is True
            assert p["collector"]["scrapes"] >= 2
            assert "train_steps_total" in p["aggregates"]
            with urllib.request.urlopen(
                    live_server + "/debugz/fleet/ranks",
                    timeout=10) as r:
                p = json.loads(r.read().decode())
            assert p["ranks"][0]["rank"] == 0
            with urllib.request.urlopen(
                    live_server + "/metrics/fleet", timeout=10) as r:
                assert 'rank="0"' in r.read().decode()
        finally:
            fleet.stop_collector()
        assert _fleet_threads() == []


class TestFleetMultiProc:
    """ISSUE-8 acceptance: 4 processes, rank 2 artificially slowed,
    rank 1 forced into a NaN-loss sentinel firing. The collector (rank
    0) names the straggler while the run still progresses, increments
    fleet_straggler_total{rank}, and pulls a fleet capture with every
    rank's bundle + journal tail. Every rank exits 0."""

    WORLD = 4
    STRAGGLER_RANK = 2
    NAN_RANK = 1

    @pytest.fixture(scope="class")
    def fleet_run(self, tmp_path_factory):
        dump_dir = str(tmp_path_factory.mktemp("fleet_dumps"))
        port = free_port()
        worker = os.path.join(REPO, "tests", "fleet_worker.py")
        envs = [rank_env(
            rank, self.WORLD, port,
            PT_MONITOR_DUMP_DIR=dump_dir,
            FLAGS_monitor_fleet=1, FLAGS_perf_sentinels=1,
            FLAGS_monitor_timeseries=1, FLAGS_monitor_trace=1,
            FLAGS_monitor_memory=1, FLAGS_monitor_slo=1,
            PT_MEM_CAPACITY_BYTES=1 << 30,
            STRAGGLER_RANK=self.STRAGGLER_RANK, STRAGGLER_RECOVER_STEP=25,
            NAN_RANK=self.NAN_RANK, NAN_STEP=30, STEPS=45,
            FAST_S=0.08, SLOW_S=0.32)
            for rank in range(self.WORLD)]
        return dump_dir, run_ranks([sys.executable, worker], envs, 150)

    def test_all_ranks_exit_clean(self, fleet_run):
        _, outs = fleet_run
        for rank, rc, out, err in outs:
            assert rc == 0, report(outs)
            assert "FLEET_OK rank=%d" % rank in out, (rank, out)

    def test_straggler_named_while_run_progresses(self, fleet_run):
        _, outs = fleet_run
        out0 = outs[0][2]
        m = re.search(r"STRAGGLER_FLAGGED step=(\d+) ranks=\[(\d+)\] "
                      r"watermark=(\d+)", out0)
        assert m, out0
        assert int(m.group(2)) == self.STRAGGLER_RANK
        watermark = int(m.group(3))
        final = int(re.search(r"FINAL_STEPS (\d+)", out0).group(1))
        # the fleet kept stepping AFTER the straggler was named — the
        # verdict arrived mid-run, not from a postmortem
        assert final > watermark, (watermark, final)
        # the counter incremented for exactly the slow rank
        mt = re.search(r"STRAGGLER_TOTAL rank=%d value=(\d+)"
                       % self.STRAGGLER_RANK, out0)
        assert mt and int(mt.group(1)) >= 1, out0
        # the HTTP verdict names the rank and the policy
        verdict = json.loads(
            re.search(r"FLEET_VERDICT (.*)", out0).group(1))
        assert str(self.STRAGGLER_RANK) in verdict["stragglers"]
        info = verdict["stragglers"][str(self.STRAGGLER_RANK)]
        assert info["step_time_s"] > info["fleet_median_s"] * \
            verdict["straggler_policy"]["factor"]
        # federation text answered too
        assert "FEDERATION_OK" in out0

    def test_anomaly_capture_has_every_ranks_evidence(self, fleet_run):
        dump_dir, outs = fleet_run
        out0 = outs[0][2]
        captures = json.loads(
            re.search(r"CAPTURES (.*)", out0).group(1))
        reasons = {c["reason"] for c in captures}
        assert "anomaly" in reasons, captures
        # healthz "degraded" derives from the incident table (ISSUE
        # 18), so the straggler episode degrades rank 0 itself and MAY
        # claim the first anomaly capture; find the NaN rank's capture
        # by its manifest attribution (a cooldown-deferred trigger
        # folds into an earlier capture's detail under "also")
        cap = manifest = nan_detail = None
        for c in captures:
            with open(os.path.join(c["dir"], "manifest.json")) as f:
                man = json.load(f)
            details = [(man.get("reason"), man.get("detail") or {})]
            details += [(a.get("reason"), a.get("detail") or {})
                        for a in (man.get("detail") or {}).get(
                            "also") or ()]
            for why, det in details:
                if why == "anomaly" and \
                        self.NAN_RANK in (det.get("ranks") or ()):
                    cap, manifest, nan_detail = c, man, det
                    break
            if cap is not None:
                break
        assert cap is not None, captures
        assert nan_detail["ranks"] == [self.NAN_RANK]
        assert sorted(cap["ranks"]) == list(range(self.WORLD))
        d = cap["dir"]
        assert os.path.isdir(d)
        for r in range(self.WORLD):
            bpath = os.path.join(d, "bundle_rank%d.json" % r)
            with open(bpath) as f:
                bundle = json.load(f)
            assert bundle.get("kind") == "watchdog_bundle", bpath
            assert bundle["rank"] == r
            jpath = os.path.join(d, "journal_rank%d.json" % r)
            with open(jpath) as f:
                journal = json.load(f)
            assert journal.get("kind") == "trace_journal", jpath
            assert journal["traces"], "rank %d journal empty" % r
            # ISSUE 12: the capture embeds every rank's memory
            # breakdown, carrying that rank's OWN ledger bytes
            mpath = os.path.join(d, "memory_rank%d.json" % r)
            with open(mpath) as f:
                memory = json.load(f)
            assert memory.get("enabled") is True, mpath
            assert memory["components"]["train"]["synthetic"][
                "bytes"] == (64 + r) << 20, mpath
        # the straggler episode rode into the manifest (flagged before
        # the scripted recovery; this capture precedes the resolve)
        assert str(self.STRAGGLER_RANK) in manifest["stragglers"]
        # ISSUE 18: the manifest names the open incident ids it was
        # taken under — the merge back-links capture dirs from these
        assert manifest["incidents"], manifest

    def test_per_rank_memory_columns_in_fleet_table(self, fleet_run):
        """ISSUE-12 satellite: /debugz/fleet/ranks (and so
        tools/fleet_top.py's MEM/HEADROOM columns) carries per-rank
        memory — each rank's headroom reflects its OWN synthetic
        ledger (64+rank MiB) + noted transient peak (8 MiB) against
        PT_MEM_CAPACITY_BYTES (1 GiB)."""
        _, outs = fleet_run
        out0 = outs[0][2]
        rows = json.loads(re.search(r"MEM_COLUMNS (.*)", out0).group(1))
        assert sorted(r["rank"] for r in rows) == list(
            range(self.WORLD))
        for row in rows:
            r = row["rank"]
            assert isinstance(row["mem_live_bytes"], (int, float)), row
            want = (1 << 30) - ((64 + r) << 20) - (8 << 20)
            assert row["mem_headroom_bytes"] == want, row

    def test_capture_dirs_are_unique(self, fleet_run):
        dump_dir, _ = fleet_run
        dirs = glob.glob(os.path.join(dump_dir, "fleet_capture_*"))
        assert len(dirs) == len(set(dirs)) and dirs

    def test_incident_timeline_dedup_lifecycle_causality(
            self, fleet_run):
        """ISSUE-18 acceptance: the merged /debugz/fleet/incidents
        timeline (fetched over real HTTP) carries ONE deduped incident
        per episode — the straggler episode names the rank, links the
        fleet capture dir, and is RESOLVED after the scripted mid-run
        recovery; the NaN rank's sentinel incident merges in from that
        rank's scraped table and stays open (the loss never heals)."""
        _, outs = fleet_run
        out0 = outs[0][2]
        merged = json.loads(
            re.search(r"INCIDENTS (.*)", out0).group(1))
        assert merged["enabled"] is True
        incidents = merged["incidents"]
        # dedup by id: the collector's own table is ALSO scraped as
        # rank 0, and every rank is re-scraped every round — one
        # timeline entry per incident id regardless
        ids = [i["id"] for i in incidents]
        assert len(ids) == len(set(ids)), ids
        skey = "fleet/straggler/rank%d" % self.STRAGGLER_RANK
        straggler = [i for i in incidents if i["key"] == skey]
        assert len(straggler) == 1, incidents       # ONE per episode
        s = straggler[0]
        assert s["state"] == "resolved"
        assert s["resolve_reason"] == \
            "step time recovered to fleet pace"
        assert s["source"] == "fleet"
        assert s["evidence"]["rank"] == self.STRAGGLER_RANK
        # causality: the episode links the capture artifact dir
        assert s["evidence"]["capture_dir"].startswith(fleet_run[0])
        assert os.path.isdir(s["evidence"]["capture_dir"])
        # the NaN rank's local sentinel incident merged in from its
        # scraped table, origin-labeled, still open, page severity
        # the key embeds the fully-labeled ring series name
        nan = [i for i in incidents
               if i["key"].startswith("perf/nan_loss/train_loss")]
        assert len(nan) == 1, incidents
        n = nan[0]
        assert n["state"] == "open"
        assert n["severity"] == "page"
        assert n["origin"] == "rank%d" % self.NAN_RANK
        assert n["rank"] == self.NAN_RANK
        assert merged["counts"]["open"] >= 1
        assert self.NAN_RANK in merged["ranks_merged"]
