"""paddle_tpu.monitor.watchdog: heartbeats, stall detection, /healthz +
/debugz endpoints, diagnostic bundles, cross-rank postmortems.

Covers the ISSUE-3 acceptance surface:
- disabled watchdog == zero native calls AND zero daemon threads while
  the instrumented hot paths (train step, serving engine, collectives)
  run;
- a forced stall produces a bundle (all-thread stacks, flight ring,
  metric snapshot, heartbeat ages) and /healthz flips ok -> stalled
  (HTTP 503) and back;
- a progressing loop under an enabled watchdog raises zero false
  positives;
- a deadlocked serving-engine thread is named with its stack;
- the multi-process forced stall (one rank sleeps between steps while
  peers wait in a collective): every surviving rank's postmortem names
  the stalled rank, shows the peers' in-flight collective gseq, and
  carries the sleeper's stack;
- tools/debug_bundle.py merges on-disk bundles into the same diagnosis.
"""
from __future__ import annotations

import glob
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

import paddle_tpu  # noqa: F401  (forces the cpu test config first)
from paddle_tpu import monitor
from paddle_tpu.monitor import watchdog as wd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

sys.path.insert(0, os.path.join(REPO, "tests"))
from dist_utils import free_port, rank_env, report, run_ranks  # noqa: E402


@pytest.fixture(autouse=True)
def _watchdog_stopped():
    """Every test starts and ends with the watchdog off."""
    monitor.stop_watchdog()
    yield
    monitor.stop_watchdog()


def _wd_threads():
    return [t for t in threading.enumerate()
            if t.name == wd._THREAD_NAME]


class TestDisabledPath:
    def test_zero_daemon_threads_and_noop_beats(self):
        hb = monitor.heartbeat("t_wd_disabled")
        before = hb.beats
        hb.beat()
        with hb.busy("phase") as b:
            assert b is None          # the shared no-op context
        assert hb.beats == before
        assert not _wd_threads()
        assert not monitor.is_watchdog_running()

    def test_zero_native_calls_through_hot_paths(self, monkeypatch):
        """The tier-1 guard: with the watchdog off, the instrumented
        paths (heartbeat beats/brackets + a real collective through
        StoreProcessGroup's span) never touch the native trace lib —
        only the store wire itself (which predates the watchdog)."""
        from paddle_tpu.monitor import registry as mreg

        calls = []
        # arm the one native-touching path the monitor owns
        monkeypatch.setattr(mreg._state, "trace_bridge", True)
        monkeypatch.setattr(
            mreg._state, "_trace_fn",
            lambda name, v: calls.append((name, v)))
        mreg.disable()
        try:
            hb = monitor.heartbeat("t_wd_native")
            hb.beat()
            with hb.busy("phase", seq=1):
                pass
            # a real collective through the watchdog-bracketed span
            import numpy as np

            from paddle_tpu.distributed.process_group import \
                StoreProcessGroup
            from paddle_tpu.distributed.store import TCPStore

            with TCPStore("127.0.0.1", 0, is_master=True) as store:
                pg = StoreProcessGroup(store, 0, 1)
                pg.allreduce(np.ones((2,), np.float32))
            assert calls == []
            assert not _wd_threads()
        finally:
            mreg.enable(trace_bridge=False)

    def test_healthz_reports_disabled(self):
        p = wd.healthz_payload()
        assert p["status"] == "ok"
        assert p["watchdog"] == "disabled"


class TestStallDetection:
    def test_stall_fires_bundle_and_healthz_flips(self, tmp_path,
                                                  monkeypatch):
        monkeypatch.setenv("PT_MONITOR_DUMP_DIR", str(tmp_path))
        monitor.start_watchdog(stall_threshold_s=0.3,
                               poll_interval_s=0.05)
        assert len(_wd_threads()) == 1
        hb = monitor.heartbeat("t_wd_stall")
        with hb.busy("wedged.phase", step=7):
            deadline = time.time() + 5
            while time.time() < deadline and not list(
                    tmp_path.glob("watchdog_bundle_rank*.json")):
                time.sleep(0.05)
            p = wd.healthz_payload()
            assert p["status"] == "stalled"
            assert p["stalls"][0]["heartbeat"] == "t_wd_stall"
            assert p["stalls"][0]["phase"] == "wedged.phase"
            assert p["stalls"][0]["info"] == {"step": 7}
        # phase exited: healthz recovers
        assert wd.healthz_payload()["status"] == "ok"
        bundle_path = tmp_path / "watchdog_bundle_rank0.json"
        assert bundle_path.exists()
        b = json.loads(bundle_path.read_text())
        assert b["kind"] == "watchdog_bundle"
        assert b["verdict"] == "stalled"
        assert b["stalls"][0]["heartbeat"] == "t_wd_stall"
        # the bundle carries all four diagnostic surfaces
        assert any(s["name"] == "MainThread" for s in b["stacks"])
        assert "entries" in b["flight_recorder"]
        assert "watchdog_stalls_total" in b["metrics"]
        assert "t_wd_stall" in b["heartbeats"]

    def test_progressing_loop_no_false_positive(self, tmp_path,
                                                monkeypatch):
        monkeypatch.setenv("PT_MONITOR_DUMP_DIR", str(tmp_path))
        monitor.start_watchdog(stall_threshold_s=0.5,
                               poll_interval_s=0.05)
        hb = monitor.heartbeat("t_wd_progress")
        with hb.busy("long.window"):
            end = time.time() + 1.2       # > 2x the threshold
            while time.time() < end:
                hb.beat()                 # steady progress
                time.sleep(0.05)
        assert not list(tmp_path.glob("watchdog_bundle_rank*.json"))
        assert wd.healthz_payload()["status"] == "ok"

    def test_stall_refires_after_recovery(self, tmp_path, monkeypatch):
        """Episode dedupe must not permanently silence a heartbeat: a
        second distinct stall fires a second bundle."""
        monkeypatch.setenv("PT_MONITOR_DUMP_DIR", str(tmp_path))
        monitor.start_watchdog(stall_threshold_s=0.2,
                               poll_interval_s=0.05)
        hb = monitor.heartbeat("t_wd_refire")
        stalls = monitor.get_registry().get("watchdog_stalls_total")
        v0 = stalls.value
        start = v0
        for _ in range(2):
            with hb.busy("wedge"):
                deadline = time.time() + 5
                while time.time() < deadline \
                        and stalls.value == start:
                    time.sleep(0.05)
            start = stalls.value
        assert stalls.value >= v0 + 2

    def test_train_and_serving_paths_beat_under_watchdog(self):
        """The real instrumented paths progress cleanly (zero false
        positives) and advance their heartbeats."""
        import numpy as np

        import paddle_tpu as paddle
        from paddle_tpu import nn, optimizer
        from paddle_tpu.parallel.engine import CompiledTrainStep

        monitor.start_watchdog(stall_threshold_s=30,
                               poll_interval_s=0.5)
        hb = monitor.heartbeat("train_step")
        before = hb.beats
        net = nn.Sequential(nn.Linear(4, 4))
        opt = optimizer.SGD(learning_rate=0.1,
                            parameters=net.parameters())
        step = CompiledTrainStep(net, nn.MSELoss(), opt)
        x = paddle.to_tensor(np.zeros((8, 4), "float32"))
        step(x, x)
        assert hb.beats > before
        assert not hb.snapshot()["active_phases"]
        assert wd.healthz_payload()["status"] == "ok"


class TestHTTPEndpoints:
    def test_debugz_surface(self):
        srv = monitor.MetricsServer(port=0).start()
        try:
            base = "http://127.0.0.1:%d" % srv.port
            h = json.loads(urllib.request.urlopen(
                base + "/healthz").read())
            assert h["status"] == "ok"
            st = json.loads(urllib.request.urlopen(
                base + "/debugz/stacks").read())
            # this very test function is on the main thread's stack
            assert any("test_debugz_surface" in f["func"]
                       for s in st["stacks"] for f in s["frames"])
            fl = json.loads(urllib.request.urlopen(
                base + "/debugz/flight").read())
            assert "entries" in fl
            bu = json.loads(urllib.request.urlopen(
                base + "/debugz/bundle").read())
            assert bu["kind"] == "watchdog_bundle"
            assert bu["reason"] == "debugz"
        finally:
            srv.stop()

    def test_healthz_503_when_stalled(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PT_MONITOR_DUMP_DIR", str(tmp_path))
        monitor.start_watchdog(stall_threshold_s=0.2,
                               poll_interval_s=0.05)
        srv = monitor.MetricsServer(port=0).start()
        hb = monitor.heartbeat("t_wd_http_stall")
        try:
            base = "http://127.0.0.1:%d" % srv.port
            with hb.busy("wedge"):
                time.sleep(0.4)
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(base + "/healthz")
                assert ei.value.code == 503
                body = json.loads(ei.value.read())
                assert body["status"] == "stalled"
            h = json.loads(urllib.request.urlopen(
                base + "/healthz").read())
            assert h["status"] == "ok"
        finally:
            srv.stop()


class TestServingEngineDeadlock:
    def test_deadlocked_engine_thread_named_with_stack(self, tmp_path,
                                                       monkeypatch):
        """ISSUE-3 satellite: a serving engine thread wedged inside
        step() is a detectable stall whose bundle carries the blocked
        thread's stack."""
        import paddle_tpu as paddle
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.serving.engine import Engine

        monkeypatch.setenv("PT_MONITOR_DUMP_DIR", str(tmp_path))
        paddle.seed(0)
        cfg = LlamaConfig(vocab_size=32, hidden_size=16,
                          intermediate_size=32, num_hidden_layers=1,
                          num_attention_heads=2,
                          max_position_embeddings=32,
                          use_parallel=False)
        engine = Engine(LlamaForCausalLM(cfg), max_slots=1,
                        num_blocks=8, block_size=4)
        lock = threading.Lock()
        lock.acquire()

        def deadlocked_admit():
            with lock:                    # blocks until the test releases
                return None

        engine.scheduler.admit_next = deadlocked_admit
        monitor.start_watchdog(stall_threshold_s=0.3,
                               poll_interval_s=0.05)
        t = threading.Thread(target=engine.run, name="serving-loop")
        t.start()
        try:
            deadline = time.time() + 8
            bundle = None
            while time.time() < deadline and bundle is None:
                files = list(tmp_path.glob("watchdog_bundle_rank*.json"))
                if files:
                    bundle = json.loads(files[0].read_text())
                time.sleep(0.05)
            assert bundle is not None, "watchdog never fired"
            assert any(s["heartbeat"] == "serving_engine"
                       and s["phase"] == "serving.step"
                       for s in bundle["stalls"])
            # the deadlocked thread's stack is in the bundle, wedged in
            # the admit path
            loop_stacks = [s for s in bundle["stacks"]
                           if s["name"] == "serving-loop"]
            assert loop_stacks, bundle["stacks"]
            assert any("deadlocked_admit" in f["func"]
                       for f in loop_stacks[0]["frames"])
        finally:
            lock.release()
            t.join(timeout=30)
        assert not t.is_alive()


class TestDiagnoseBundles:
    def _bundle(self, rank, world=4, coll=None, stalls=(),
                hb_ages=None):
        hbs = {}
        if coll is not None:
            op, gseq, age = coll
            hbs["collectives"] = {
                "beats": 3, "last_beat": 0, "last_beat_age_s": age,
                "active_phases": [{
                    "phase": "collective.%s" % op,
                    "info": {"op": op, "gseq": gseq,
                             "group": "pg/default", "rank": rank,
                             "world_size": world},
                    "since": 100.0, "age_s": age}],
            }
        for name, age in (hb_ages or {}).items():
            hbs[name] = {"beats": 1, "last_beat": 0,
                         "last_beat_age_s": age, "active_phases": []}
        return {"kind": "watchdog_bundle", "rank": rank,
                "world_size": world, "verdict":
                "stalled" if stalls else "ok",
                "stalls": list(stalls), "heartbeats": hbs,
                "stacks": [], "flight_recorder": {}, "metrics": {}}

    def test_rank_between_steps_named(self):
        bundles = {r: self._bundle(r, coll=("all_reduce", 2, 10.0))
                   for r in (0, 1, 3)}
        bundles[2] = self._bundle(2, hb_ages={"collectives": 11.0})
        rep = monitor.diagnose_bundles(
            bundles, world_size=4,
            liveness={r: 0.1 for r in range(4)}, lease_s=5)
        assert rep["status"] == "stalled"
        assert rep["stalled_ranks"] == [2]
        assert rep["per_rank"][2]["state"] == "between-steps"
        assert rep["collective"]["gseq"] == 2
        assert rep["collective"]["op"] == "all_reduce"
        assert "rank 2" in rep["summary"]

    def test_rank_behind_in_collective_named(self):
        bundles = {r: self._bundle(r, coll=("all_reduce", 5, 8.0))
                   for r in range(3)}
        bundles[1] = self._bundle(1, coll=("all_reduce", 3, 8.0))
        rep = monitor.diagnose_bundles(
            bundles, world_size=3,
            liveness={r: 0.1 for r in range(3)}, lease_s=5)
        assert rep["status"] == "stalled"
        assert rep["stalled_ranks"] == [1]
        assert rep["per_rank"][1]["state"] == "in-collective"

    def test_dead_rank_by_lease_expiry(self):
        bundles = {r: self._bundle(r, world=3,
                                   coll=("all_reduce", 1, 9.0))
                   for r in (0, 1)}
        rep = monitor.diagnose_bundles(
            bundles, world_size=3,
            liveness={0: 0.2, 1: 0.3, 2: 60.0}, lease_s=5)
        assert rep["status"] == "stalled"
        assert rep["stalled_ranks"] == [2]
        assert rep["dead_ranks"] == [2]
        assert rep["per_rank"][2]["state"] == "dead"
        assert "DEAD" in rep["summary"]

    def test_all_waiting_same_seq_is_external(self):
        bundles = {r: self._bundle(r, world=2,
                                   coll=("all_gather", 4, 12.0))
                   for r in range(2)}
        rep = monitor.diagnose_bundles(
            bundles, world_size=2,
            liveness={0: 0.1, 1: 0.1}, lease_s=5)
        assert rep["status"] == "external-stall"
        assert rep["stalled_ranks"] == []

    def test_single_process_local_stall(self):
        bundles = {0: self._bundle(
            0, world=1,
            stalls=[{"heartbeat": "serving_engine",
                     "phase": "serving.step", "info": {},
                     "age_s": 9.0, "since": 1.0,
                     "threshold_s": 1.0}])}
        rep = monitor.diagnose_bundles(bundles, world_size=1,
                                       liveness={0: 0.1}, lease_s=5)
        assert rep["status"] == "stalled"
        assert rep["stalled_ranks"] == [0]


class TestForcedStallMultiProc:
    """ISSUE-3 acceptance: one rank sleeps between steps while peers
    wait in a collective; the watchdog postmortem names the stalled
    rank, shows the in-flight collective gseq of the waiters, and
    carries the sleeper's stack — and every rank exits 0 afterwards."""

    WORLD = 4
    STALL_RANK = 2

    @pytest.fixture(scope="class")
    def stall_run(self, tmp_path_factory):
        dump_dir = str(tmp_path_factory.mktemp("wd_dumps"))
        port = free_port()
        worker = os.path.join(REPO, "tests", "watchdog_stall_worker.py")
        envs = [rank_env(rank, self.WORLD, port,
                         PT_MONITOR_DUMP_DIR=dump_dir,
                         STALL_RANK=self.STALL_RANK, STALL_SLEEP_S=12,
                         WD_STALL_S=1.5, WD_GRACE_S=4)
                for rank in range(self.WORLD)]
        return dump_dir, run_ranks([sys.executable, worker], envs, 150)

    def test_all_ranks_recover_and_exit_clean(self, stall_run):
        _, outs = stall_run
        for rank, rc, out, err in outs:
            assert rc == 0, report(outs)
            assert "STALL_RUN_OK" in out, (rank, out)

    def test_postmortem_names_stalled_rank_with_stack(self, stall_run):
        dump_dir, _ = stall_run
        reports = sorted(glob.glob(os.path.join(
            dump_dir, "watchdog_postmortem_rank*.json")))
        assert reports, "no watchdog postmortem written"
        # a healthy detecting rank's report (rank 0 always is one here)
        path = os.path.join(dump_dir, "watchdog_postmortem_rank0.json")
        with open(path) as f:
            rep = json.load(f)
        assert rep["status"] == "stalled"
        assert rep["stalled_ranks"] == [self.STALL_RANK]
        assert rep["per_rank"][str(self.STALL_RANK)]["state"] \
            == "between-steps"
        # the waiters' in-flight collective: third allreduce = gseq 2
        assert rep["collective"]["op"] == "all_reduce"
        assert rep["collective"]["gseq"] == 2
        assert 0 in rep["collective"]["waiting_ranks"]
        # the sleeper's bundle rode along — with the guilty stack
        sleeper = rep["bundles"][str(self.STALL_RANK)]
        frames = json.dumps(sleeper["stacks"])
        assert "watchdog_stall_worker" in frames
        assert "time.sleep" in frames
        # and the detecting rank's own bundle shows it waiting at gseq 2
        detecting = rep["bundles"]["0"]
        colls = [p for s in detecting["heartbeats"].values()
                 for p in s["active_phases"]
                 if "gseq" in p.get("info", {})]
        assert any(p["info"]["gseq"] == 2 for p in colls)

    def test_debug_bundle_cli_merges_to_same_verdict(self, stall_run,
                                                     tmp_path):
        dump_dir, _ = stall_run
        assert glob.glob(os.path.join(dump_dir,
                                      "watchdog_bundle_rank*.json"))
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import debug_bundle as cli
        finally:
            sys.path.pop(0)
        out = tmp_path / "merged.json"
        rc = cli.main(["merge", "--dir", dump_dir, "--out", str(out),
                       "--world-size", str(self.WORLD)])
        assert rc == 1          # stalled verdict -> nonzero for scripting
        merged = json.loads(out.read_text())
        assert merged["kind"] == "watchdog_bundle_merged"
        assert merged["diagnosis"]["status"] == "stalled"
        assert merged["diagnosis"]["stalled_ranks"] == [self.STALL_RANK]
