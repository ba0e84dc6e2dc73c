"""paddle_tpu.monitor: registry, exporters, flight recorder, trace merge.

Covers the ISSUE-2 acceptance surface:
- Counter/Gauge/Histogram semantics + JSON/Prometheus exporters, and
  the /metrics endpoint riding the fleet KV HTTP server;
- the disabled-monitor fast path making ZERO native-lib calls (the
  tier-1 CI guard) and graceful no-native-lib degradation;
- make_scheduler window edges + RecordEvent nesting balance
  (profiler satellites);
- flight-recorder ring semantics, nested-op suppression, and the
  desync diagnoser — including the 8-process forced-desync acceptance
  test where one rank skips a collective and the postmortem report
  names the diverging rank and sequence number;
- multi-rank chrome-trace merge with clock offsets.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import subprocess
import sys
import urllib.request

import pytest

import paddle_tpu  # noqa: F401  (forces the cpu test config first)
from paddle_tpu import monitor
from paddle_tpu.monitor import flight_recorder as fr
from paddle_tpu.monitor import registry as mreg
from paddle_tpu.monitor import trace_merge as tm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

sys.path.insert(0, os.path.join(REPO, "tests"))
from dist_utils import free_port  # noqa: E402


@pytest.fixture(autouse=True)
def _monitor_enabled_and_clean():
    """Each test starts enabled with the trace bridge off; metrics
    created by tests are scoped by unique names, so no registry reset
    is needed (module-level serving/train metrics must survive)."""
    mreg.enable(trace_bridge=False)
    yield
    mreg.enable(trace_bridge=False)


class TestRegistry:
    def test_counter_labels_and_snapshot(self):
        c = monitor.counter("t_reg_requests_total", "reqs",
                            labelnames=("code",))
        c.labels(code="200").inc()
        c.labels(code="200").inc(2)
        c.labels(code="500").inc()
        snap = monitor.get_registry().snapshot()["t_reg_requests_total"]
        assert snap["kind"] == "counter"
        by_code = {s["labels"]["code"]: s["value"]
                   for s in snap["series"]}
        assert by_code == {"200": 3, "500": 1}

    def test_counter_monotone(self):
        c = monitor.counter("t_reg_mono_total")
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_set_inc_dec(self):
        g = monitor.gauge("t_reg_occupancy")
        g.set(4)
        g.inc(2)
        g.dec()
        assert g.value == 5

    def test_histogram_buckets_sum_count(self):
        h = monitor.histogram("t_reg_lat_seconds", buckets=(0.1, 1, 10))
        for v in (0.05, 0.5, 5.0, 50.0):
            h.observe(v)
        (_, data), = h.collect()
        assert data["count"] == 4
        assert data["sum"] == pytest.approx(55.55)
        assert data["buckets"] == {0.1: 1, 1: 2, 10: 3}

    def test_histogram_timer(self):
        h = monitor.histogram("t_reg_timer_seconds")
        with h.time():
            pass
        (_, data), = h.collect()
        assert data["count"] == 1 and data["sum"] >= 0

    def test_idempotent_recreate_and_kind_conflict(self):
        c1 = monitor.counter("t_reg_idem_total", labelnames=("a",))
        c2 = monitor.counter("t_reg_idem_total", labelnames=("a",))
        assert c1 is c2
        with pytest.raises(ValueError):
            monitor.gauge("t_reg_idem_total")
        with pytest.raises(ValueError):
            monitor.counter("t_reg_idem_total", labelnames=("b",))

    def test_direct_duplicate_construction_raises(self):
        """A matched duplicate via the class constructor would be an
        orphan (unregistered, samples dropped) — it must raise and
        point at the idempotent helpers."""
        monitor.counter("t_reg_orphan_total")
        with pytest.raises(ValueError, match="monitor.counter"):
            mreg.Counter("t_reg_orphan_total")

    def test_histogram_bucket_mismatch_raises(self):
        monitor.histogram("t_reg_bkt_seconds", buckets=(1, 2, 3))
        h = monitor.histogram("t_reg_bkt_seconds", buckets=(3, 2, 1))
        assert h.buckets == (1, 2, 3)   # order-insensitive match
        with pytest.raises(ValueError, match="buckets"):
            monitor.histogram("t_reg_bkt_seconds", buckets=(1, 2))

    def test_labels_kw_validation(self):
        c = monitor.counter("t_reg_kwval_total", labelnames=("event",))
        with pytest.raises(ValueError, match="unknown"):
            c.labels(event="in", shard="3")   # extra label: not silent
        with pytest.raises(ValueError, match="missing"):
            c.labels(evnt="in")               # typo: not a KeyError

    def test_trace_bridge_scales_fractional_values(self, monkeypatch):
        sent = []
        monkeypatch.setattr(mreg._state, "_trace_fn",
                            lambda name, v: sent.append((name, v)))
        monkeypatch.setattr(mreg._state, "trace_bridge", True)
        g = monitor.gauge("t_reg_frac")
        g.set(0.73)     # int64 native API: 0.73 must not flatline to 0
        g.set(2.0)      # whole-number FLOAT stays on the milli series
        g.set(5)        # int samples stay on the plain series
        assert sent == [(b"t_reg_frac_milli", 730),
                        (b"t_reg_frac_milli", 2000),
                        (b"t_reg_frac", 5)]

    def test_prometheus_text_format(self):
        c = monitor.counter("t_reg_prom_total", "help text",
                            labelnames=("x",))
        c.labels(x="1").inc(7)
        h = monitor.histogram("t_reg_prom_seconds", buckets=(1, 2))
        h.observe(1.5)
        txt = monitor.get_registry().prometheus_text()
        assert "# TYPE t_reg_prom_total counter" in txt
        assert 't_reg_prom_total{x="1"} 7' in txt
        assert 't_reg_prom_seconds_bucket{le="1"} 0' in txt
        assert 't_reg_prom_seconds_bucket{le="2"} 1' in txt
        assert 't_reg_prom_seconds_bucket{le="+Inf"} 1' in txt
        assert "t_reg_prom_seconds_count 1" in txt

    def test_remove_series(self):
        g = monitor.gauge("t_reg_rm", labelnames=("k",))
        g.labels(k="a").set(1)
        g.labels(k="b").set(2)
        g.remove(k="a")
        snap = monitor.get_registry().snapshot()["t_reg_rm"]
        assert [s["labels"]["k"] for s in snap["series"]] == ["b"]

    def test_engine_gauge_series_bounded(self):
        from paddle_tpu.serving import metrics as sm

        first = sm.EngineMetrics(max_slots=1)
        first.on_admission()
        first.on_decode_step(1)
        for _ in range(sm._MAX_ENGINE_SERIES + 8):
            em = sm.EngineMetrics(max_slots=1)
            em.on_admission()
            em.on_decode_step(1)
        assert len(sm._ACTIVE._children) <= sm._MAX_ENGINE_SERIES
        assert len(sm._THROUGHPUT._values) <= sm._MAX_ENGINE_SERIES
        # a pruned-but-live engine keeps stepping: its detached child
        # must NOT resurrect the series outside the pruning view
        first.on_decode_step(1)
        assert len(sm._ACTIVE._values) <= sm._MAX_ENGINE_SERIES
        assert len(sm._ACTIVE._children) <= sm._MAX_ENGINE_SERIES

    def test_disabled_mutators_are_noops(self):
        c = monitor.counter("t_reg_disabled_total")
        c.inc(5)
        mreg.disable()
        c.inc(100)
        mreg.enable()
        assert c.value == 5


class TestNativeIsolation:
    """The CI satellite: disabled monitor == zero native calls; and a
    build without the native lib degrades, never raises."""

    def test_disabled_fast_path_no_native_calls(self, monkeypatch):
        from paddle_tpu.core import native
        from paddle_tpu.serving.metrics import EngineMetrics, \
            RequestMetrics

        calls = []
        monkeypatch.setattr(
            native, "get_lib",
            lambda: calls.append("get_lib") or pytest.fail(
                "disabled monitor touched the native lib"))
        mreg.disable()
        # trace bridge armed: would call native if the gate leaked
        mreg._state.trace_bridge = True
        mreg._state._trace_fn = None
        c = monitor.counter("t_iso_total", labelnames=("k",))
        c.labels(k="a").inc()
        monitor.gauge("t_iso_gauge").set(3)
        monitor.histogram("t_iso_seconds").observe(0.1)
        em = EngineMetrics(max_slots=4)
        em.on_request_in()
        em.on_decode_step(2)       # the hot serving loop hook
        em.on_output_token()
        rm = RequestMetrics(0.0)
        rm.on_admit(1.0)
        rm.on_first_token(2.0)
        rm.on_finish(3.0, 4)
        assert calls == []

    def test_no_native_lib_degradation(self, monkeypatch):
        from paddle_tpu.core import native

        def boom():
            raise OSError("no native lib in this build")

        monkeypatch.setattr(native, "get_lib", boom)
        mreg.enable(trace_bridge=True)
        mreg._state._trace_fn = None
        c = monitor.counter("t_iso_degrade_total")
        c.inc()            # first inc probes the lib, fails, degrades
        c.inc()
        assert c.value == 2
        assert mreg._state.trace_bridge is False


class TestMetricsHTTP:
    def test_metrics_endpoint_and_kv_coexist(self):
        monitor.counter("t_http_hits_total").inc(3)
        srv = monitor.MetricsServer(port=0).start()
        try:
            base = "http://127.0.0.1:%d" % srv.port
            txt = urllib.request.urlopen(base + "/metrics").read().decode()
            assert "t_http_hits_total 3" in txt
            snap = json.loads(urllib.request.urlopen(
                base + "/metrics.json").read().decode())
            assert snap["metrics"]["t_http_hits_total"]["series"][0][
                "value"] == 3
            assert "written_at" in snap
            # the KV side of the server still works (PUT then GET)
            req = urllib.request.Request(base + "/scope/key", data=b"v",
                                         method="PUT")
            urllib.request.urlopen(req)
            got = urllib.request.urlopen(base + "/scope/key").read()
            assert got == b"v"
        finally:
            srv.stop()

    def test_write_snapshot_artifact(self, tmp_path):
        monitor.counter("t_http_snap_total").inc()
        path = tmp_path / "snap.json"
        monitor.write_snapshot(str(path), meta={"source": "test"})
        snap = json.loads(path.read_text())
        assert snap["meta"]["source"] == "test"
        assert "written_at" in snap and "pid" in snap
        assert "t_http_snap_total" in snap["metrics"]


class TestSchedulerWindows:
    """make_scheduler edge cases (profiler satellite)."""

    def test_skip_first_window(self):
        from paddle_tpu import profiler as prof

        sched = prof.make_scheduler(closed=1, ready=1, record=1,
                                    skip_first=3)
        states = [sched(s) for s in range(6)]
        assert states[:3] == [prof.ProfilerState.CLOSED] * 3
        assert states[3] == prof.ProfilerState.CLOSED
        assert states[4] == prof.ProfilerState.READY
        assert states[5] == prof.ProfilerState.RECORD_AND_RETURN

    def test_repeat_expiry(self):
        from paddle_tpu import profiler as prof

        sched = prof.make_scheduler(closed=1, ready=0, record=1, repeat=2)
        # two periods of (closed, record&return), then closed forever
        expect = [prof.ProfilerState.CLOSED,
                  prof.ProfilerState.RECORD_AND_RETURN] * 2
        assert [sched(s) for s in range(4)] == expect
        assert all(sched(s) is prof.ProfilerState.CLOSED
                   for s in range(4, 12))

    def test_record_and_return_exactly_at_period_end(self):
        from paddle_tpu import profiler as prof

        sched = prof.make_scheduler(closed=1, ready=1, record=3)
        period = 5
        for s in range(3 * period):
            st = sched(s)
            if s % period == period - 1:
                assert st is prof.ProfilerState.RECORD_AND_RETURN, s
            else:
                assert st is not prof.ProfilerState.RECORD_AND_RETURN, s

    def test_zero_closed_starts_ready(self):
        from paddle_tpu import profiler as prof

        sched = prof.make_scheduler(closed=0, ready=1, record=1)
        assert sched(0) is prof.ProfilerState.READY
        assert sched(1) is prof.ProfilerState.RECORD_AND_RETURN


class TestRecordEventNesting:
    def test_nested_spans_balance_in_dump(self, tmp_path):
        import paddle_tpu.profiler as prof

        path = str(tmp_path / "nest.json")
        with prof.Profiler() as p:
            with prof.RecordEvent("outer"):
                with prof.RecordEvent("mid"):
                    with prof.RecordEvent("inner"):
                        pass
                with prof.RecordEvent("mid2"):
                    pass
            p.export_chrome_tracing(path)
        events = prof.load_profiler_result(path)["traceEvents"]
        spans = {e["name"]: e for e in events
                 if isinstance(e, dict)
                 and e.get("name") in ("outer", "mid", "inner", "mid2")}
        assert set(spans) == {"outer", "mid", "inner", "mid2"}
        # balanced nesting: every span closed (complete events with a
        # duration) and children contained within their parent
        for e in spans.values():
            assert e.get("dur", -1) >= 0, e
        out, mid = spans["outer"], spans["mid"]
        inner = spans["inner"]
        assert out["ts"] <= mid["ts"]
        assert mid["ts"] + mid["dur"] <= out["ts"] + out["dur"] + 1
        assert inner["ts"] >= mid["ts"]
        assert inner["dur"] <= mid["dur"] + 1

    def test_unbalanced_pop_is_harmless(self):
        from paddle_tpu.core import native

        lib = native.get_lib()
        lib.pt_trace_enable(2)
        try:
            ev_count = lib.pt_trace_event_count()
            lib.pt_trace_pop()      # pop with empty stack: no crash
            assert lib.pt_trace_event_count() == ev_count
        finally:
            lib.pt_trace_disable()


class TestFlightRecorderUnit:
    def test_ring_capacity_and_seq(self):
        rec = fr.FlightRecorder(capacity=3)
        for i in range(5):
            with rec.record("all_reduce", shape=(i,)):
                pass
        entries = rec.entries()
        assert len(entries) == 3
        assert [e["seq"] for e in entries] == [2, 3, 4]
        assert all(e["t_end"] is not None for e in entries)

    def test_nested_records_collapse_to_outermost(self):
        rec = fr.FlightRecorder(capacity=16)
        with rec.record("all_reduce", reduce_op="sum"):
            with rec.record("all_gather"):
                pass
        entries = rec.entries()
        assert len(entries) == 1 and entries[0]["op"] == "all_reduce"

    def test_diagnose_divergent_op(self):
        def entry(seq, op, shape=(4,)):
            return {"seq": seq, "op": op, "reduce_op": "sum",
                    "shape": list(shape), "dtype": "float32",
                    "axis": None, "group": "pg/default",
                    "strict_shape": True}

        bufs = {r: [entry(0, "all_reduce"), entry(1, "all_reduce")]
                for r in range(4)}
        bufs[2][1] = entry(1, "broadcast")
        rep = fr.diagnose(bufs, world_size=4)
        assert rep["status"] == "desync"
        assert rep["first_divergence_seq"] == 1
        assert rep["diverging_ranks"] == [2]

    def test_diagnose_shorter_stream(self):
        def entry(seq):
            return {"seq": seq, "op": "all_reduce", "strict_shape": False}

        bufs = {0: [entry(0), entry(1)], 1: [entry(0), entry(1)],
                2: [entry(0)]}
        rep = fr.diagnose(bufs, world_size=3)
        assert rep["status"] == "desync"
        assert rep["diverging_ranks"] == [2]
        assert rep["first_divergence_seq"] == 1

    def test_diagnose_missing_rank(self):
        def entry(seq):
            return {"seq": seq, "op": "all_reduce", "strict_shape": False}

        bufs = {0: [entry(0)], 1: [entry(0)]}
        rep = fr.diagnose(bufs, world_size=3)
        assert rep["status"] == "desync"
        assert rep["diverging_ranks"] == [2]
        assert rep["missing_ranks"] == [2]

    def test_diagnose_aligns_by_seq_across_ring_wrap(self):
        """A rank whose ring wrapped earlier (shorter retained window)
        must not read as diverging: seqs evicted from its ring are
        unknown, not mismatches."""
        def entry(seq):
            return {"seq": seq, "op": "all_reduce",
                    "strict_shape": False}

        bufs = {0: [entry(s) for s in range(10)],
                1: [entry(s) for s in range(6, 10)]}  # wrapped: kept 6..9
        rep = fr.diagnose(bufs, world_size=2)
        assert rep["status"] == "consistent"
        bufs[1][-1] = dict(bufs[1][-1], op="broadcast")
        rep = fr.diagnose(bufs, world_size=2)
        assert rep["status"] == "desync"
        assert rep["first_divergence_seq"] == 9
        assert rep["diverging_ranks"] == [1]

    def test_group_scoped_diagnosis_ignores_subgroup_seq_shift(self):
        """Subgroup collectives advance the global seq only on member
        ranks; a world-group diagnosis scoped by group + per-group gseq
        must not blame the subgroup members for the shift."""
        def entry(seq, gseq, op, group):
            return {"seq": seq, "gseq": gseq, "op": op, "group": group,
                    "strict_shape": False}

        world, sub = "pg/default", "pg/g1/0_1"
        bufs = {
            # ranks 0/1 ran a subgroup op between world ops
            0: [entry(0, 0, "all_reduce", world),
                entry(1, 0, "all_reduce", sub),
                entry(2, 1, "all_reduce", world)],
            1: [entry(0, 0, "all_reduce", world),
                entry(1, 0, "all_reduce", sub),
                entry(2, 1, "all_reduce", world)],
            2: [entry(0, 0, "all_reduce", world),
                entry(1, 1, "all_reduce", world)],
            # rank 3 skipped the second WORLD op
            3: [entry(0, 0, "all_reduce", world)],
        }
        rep = fr.diagnose(bufs, world_size=4, group=world)
        assert rep["status"] == "desync"
        assert rep["diverging_ranks"] == [3]
        assert rep["first_divergence_seq"] == 1   # gseq within the group
        # global-seq alignment (no group hint) would have blamed 2 and 3
        rep_unscoped = fr.diagnose(bufs, world_size=4)
        assert set(rep_unscoped["diverging_ranks"]) != {3}

    def test_diagnose_consistent(self):
        def entry(seq):
            return {"seq": seq, "op": "barrier", "strict_shape": False}

        bufs = {r: [entry(0)] for r in range(2)}
        rep = fr.diagnose(bufs, world_size=2)
        assert rep["status"] == "consistent"
        assert rep["diverging_ranks"] == []

    def test_object_collectives_not_shape_strict(self):
        """Rank-varying payload sizes (object allgather) must not read
        as desync — shapes only participate for strict_shape ops."""
        bufs = {
            0: [{"seq": 0, "op": "all_gather", "shape": [10],
                 "strict_shape": False}],
            1: [{"seq": 0, "op": "all_gather", "shape": [999],
                 "strict_shape": False}],
        }
        rep = fr.diagnose(bufs, world_size=2)
        assert rep["status"] == "consistent"

    def test_stale_dumps_from_previous_incident_ignored(self):
        """Fixed per-rank keys survive on the store across incidents;
        a dump stamped long ago must not feed a NEW postmortem."""
        import time as _time

        from paddle_tpu.distributed.store import TCPStore

        with TCPStore("127.0.0.1", 0, is_master=True) as store:
            stale = {"entries": [{"seq": 0, "op": "all_reduce"}],
                     "dumped_at": _time.time() - 3600}
            store.set("__fr/rank1", json.dumps(stale).encode())
            rec = fr.FlightRecorder(capacity=8)
            with rec.record("all_reduce"):
                pass
            fr.dump_to_store(store, 0, 2, rec)
            bufs = fr.gather_from_store(store, 2, grace_s=0.6)
            assert 0 in bufs and 1 not in bufs

    def test_p2p_recv_timeout_skips_world_postmortem(self, tmp_path,
                                                     monkeypatch):
        """A stalled send is a pairwise problem: the recv timeout must
        not fabricate a world-wide 'desync' naming every idle rank."""
        from paddle_tpu.distributed.process_group import \
            StoreProcessGroup
        from paddle_tpu.distributed.store import TCPStore

        monkeypatch.setenv("PT_MONITOR_DUMP_DIR", str(tmp_path))
        with TCPStore("127.0.0.1", 0, is_master=True) as store:
            pg = StoreProcessGroup(store, 0, 2)
            with pytest.raises(TimeoutError) as ei:
                pg.recv(src=1, timeout_s=0.3)
            assert "desync" not in str(ei.value)
        assert not list(tmp_path.glob("flight_recorder_rank*.json"))

    def test_pg_collectives_recorded_single_process(self):
        """A world_size=1 StoreProcessGroup exercises the real record
        hooks end-to-end (allreduce lowers to allgather — exactly one
        outer entry per API call)."""
        import numpy as np

        from paddle_tpu.distributed.process_group import \
            StoreProcessGroup
        from paddle_tpu.distributed.store import TCPStore

        rec = fr.get_flight_recorder()
        rec.clear()
        with TCPStore("127.0.0.1", 0, is_master=True) as store:
            pg = StoreProcessGroup(store, 0, 1)
            pg.allreduce(np.ones((4,), np.float32))
            pg.broadcast(np.zeros((2,), np.float32), src=0)
            pg.barrier()
        ops = [e["op"] for e in rec.entries()]
        assert ops == ["all_reduce", "broadcast", "barrier"]
        ar = rec.entries()[0]
        assert ar["reduce_op"] == "sum" and ar["shape"] == [4]
        assert ar["dtype"] == "float32" and ar["strict_shape"]
        rec.clear()


class TestDesync8Ranks:
    """ISSUE-2 acceptance: a forced desync in an 8-process virtual-mesh
    run (one rank skips a collective) is detected, and the
    flight-recorder report names the diverging rank and sequence
    number."""

    WORLD = 8
    DESYNC_RANK = 3

    @pytest.fixture(scope="class")
    def desync_run(self, tmp_path_factory):
        dump_dir = str(tmp_path_factory.mktemp("fr_dumps"))
        port = free_port()
        worker = os.path.join(REPO, "tests", "monitor_desync_worker.py")
        procs = []
        for rank in range(self.WORLD):
            env = dict(os.environ)
            env.update({
                "PYTHONPATH": REPO + os.pathsep +
                env.get("PYTHONPATH", ""),
                "JAX_PLATFORMS": "cpu",
                "PADDLE_TRAINER_ID": str(rank),
                "PADDLE_TRAINERS_NUM": str(self.WORLD),
                "PADDLE_MASTER": "127.0.0.1:%d" % port,
                "PT_MONITOR_DUMP_DIR": dump_dir,
                "PT_FR_GRACE_S": "6",
                "DESYNC_RANK": str(self.DESYNC_RANK),
                "DESYNC_OP_TIMEOUT_S": "5",
            })
            procs.append(subprocess.Popen(
                [sys.executable, worker], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True))
        outs = []
        for rank, p in enumerate(procs):
            try:
                out, err = p.communicate(timeout=240)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                raise
            outs.append((rank, p.returncode, out, err))
        return dump_dir, outs

    def test_every_rank_detects_and_exits_clean(self, desync_run):
        _, outs = desync_run
        for rank, rc, out, err in outs:
            assert rc == 0, (
                "rank %d rc=%d\nstdout:\n%s\nstderr:\n%s"
                % (rank, rc, out[-2000:], err[-3000:]))
            assert "DESYNC_CAUGHT" in out, (rank, out)

    def test_report_names_diverging_rank_and_seq(self, desync_run):
        dump_dir, _ = desync_run
        reports = sorted(glob.glob(
            os.path.join(dump_dir, "flight_recorder_rank*.json")))
        assert reports, "no flight-recorder report written"
        # a healthy rank's report (rank 0 always is one here)
        with open(os.path.join(
                dump_dir, "flight_recorder_rank0.json")) as f:
            rep = json.load(f)
        assert rep["status"] == "desync"
        assert rep["diverging_ranks"] == [self.DESYNC_RANK]
        # seqs 0,1 were lockstep allreduces; the skipped collective is
        # call stream position 2 on every rank
        assert rep["first_divergence_seq"] == 2
        assert rep["expected"][0] == "all_reduce"
        assert rep["observed"][str(self.DESYNC_RANK)][0] == "barrier"
        assert rep["world_size"] == self.WORLD
        # postmortem carries the raw per-rank streams for offline digging
        assert set(rep["buffers"]) >= {"0", str(self.DESYNC_RANK)}


class TestTraceMerge:
    def test_rank_of_path(self):
        assert tm.rank_of_path("/a/trace_rank3.json") == 3
        assert tm.rank_of_path("worker_12.json.gz") == 12
        assert tm.rank_of_path("noint.json") is None

    def test_merge_shifts_and_prefixes(self):
        merged = tm.merge_rank_events(
            {0: [{"ts": 100, "pid": 7, "name": "a", "ph": "X",
                  "dur": 5}],
             1: [{"ts": 100, "pid": 7, "name": "b", "ph": "X",
                  "dur": 5},
                 {"ph": "M", "pid": 7, "name": "process_name",
                  "args": {"name": "w"}}]},
            offsets={1: 0.002})
        by_name = {e.get("name"): e for e in merged}
        assert by_name["a"]["pid"] == "rank0/7"
        assert by_name["a"]["ts"] == 100.0
        assert by_name["b"]["pid"] == "rank1/7"
        assert by_name["b"]["ts"] == pytest.approx(2100.0)
        # metadata events ride along, pid-prefixed, ts untouched
        assert by_name["process_name"]["pid"] == "rank1/7"

    def test_merge_trace_files_gz_and_clock(self, tmp_path):
        d = tmp_path
        t0 = {"traceEvents": [{"ts": 10, "pid": 0, "tid": 0,
                               "name": "r0", "ph": "X", "dur": 1}]}
        (d / "trace_rank0.json").write_text(json.dumps(t0))
        t1 = [{"ts": 10, "pid": 0, "tid": 0, "name": "r1", "ph": "X",
               "dur": 1}]
        with gzip.open(d / "trace_rank1.json.gz", "wt") as f:
            json.dump(t1, f)
        tm.write_clock_file(str(d), 0, 0.0)
        tm.write_clock_file(str(d), 1, -0.001)
        offs = tm.load_clock_offsets(str(d))
        assert offs == {0: 0.0, 1: -0.001}
        out = d / "merged.json"
        n = tm.merge_trace_files(
            {0: str(d / "trace_rank0.json"),
             1: str(d / "trace_rank1.json.gz")}, str(out), offs)
        assert n == 2
        merged = json.loads(out.read_text())
        evs = {e["name"]: e for e in merged["traceEvents"]}
        assert evs["r0"]["ts"] == 10.0
        assert evs["r1"]["ts"] == pytest.approx(10 - 1000.0)
        assert merged["metadata"]["merged_ranks"] == [0, 1]

    def test_cli_merges_directory(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import trace_merge as cli
        finally:
            sys.path.pop(0)
        d = tmp_path
        for r in range(2):
            (d / ("trace_rank%d.json" % r)).write_text(json.dumps(
                {"traceEvents": [{"ts": 1, "pid": 0, "name": "e%d" % r,
                                  "ph": "X", "dur": 1}]}))
        out = d / "merged.json"
        rc = cli.main(["--dir", str(d), "--out", str(out)])
        assert rc == 0
        merged = json.loads(out.read_text())
        assert len(merged["traceEvents"]) == 2

    def test_clock_offset_estimation_two_processes(self):
        """NTP-style exchange over a real TCPStore: the offset between
        two processes on one host is sub-100ms (loopback RTT)."""
        import threading

        from paddle_tpu.distributed.store import TCPStore

        with TCPStore("127.0.0.1", 0, is_master=True) as master:
            client = TCPStore("127.0.0.1", master.port)
            try:
                results = {}

                def side(store, rank):
                    results[rank] = tm.estimate_clock_offset(
                        store, rank, 2, pings=4, timeout_s=20)

                t = threading.Thread(target=side, args=(master, 0))
                t.start()
                side(client, 1)
                t.join(30)
                assert not t.is_alive()
                assert results[0] == 0.0
                assert abs(results[1]) < 0.1
                # a second sync round on the SAME store must not read
                # round 1's cached echoes (near-zero RTT, stale t1)
                t2 = threading.Thread(target=side, args=(master, 0))
                t2.start()
                side(client, 1)
                t2.join(30)
                assert not t2.is_alive()
                assert abs(results[1]) < 0.1
            finally:
                client.close()


class TestServingThroughRegistry:
    """Acceptance: serving + training metrics flow through ONE registry
    and export both JSON and Prometheus text."""

    def test_one_registry_both_formats(self):
        import numpy as np

        import paddle_tpu as paddle
        from paddle_tpu import nn, optimizer
        from paddle_tpu.parallel.engine import CompiledTrainStep
        from paddle_tpu.serving.metrics import EngineMetrics

        em = EngineMetrics(max_slots=2)
        em.on_request_in()
        em.on_admission()
        em.on_decode_step(2)
        em.on_output_token()
        em.on_request_finished()
        assert em.to_dict()["requests_finished"] == 1

        net = nn.Sequential(nn.Linear(4, 4))
        opt = optimizer.SGD(learning_rate=0.1,
                            parameters=net.parameters())
        step = CompiledTrainStep(net, nn.MSELoss(), opt)
        x = paddle.to_tensor(np.zeros((8, 4), "float32"))
        step(x, x)

        snap = monitor.get_registry().snapshot()
        for name in ("serving_requests_total", "serving_decode_steps_total",
                     "train_steps_total", "train_compiles_total",
                     "train_step_seconds"):
            assert name in snap, name
        txt = monitor.get_registry().prometheus_text()
        assert "serving_output_tokens_total" in txt
        assert "train_step_seconds_bucket" in txt

    def test_engine_wall_clock_starts_at_first_admission(self):
        """Satellite: throughput must not be understated by idle time
        between engine construction and first traffic."""
        import time as _time

        from paddle_tpu.serving.metrics import EngineMetrics

        em = EngineMetrics(max_slots=1)
        _time.sleep(0.05)          # idle pre-traffic time
        assert em.to_dict()["wall_s"] == 0.0
        em.on_admission()
        for _ in range(10):
            em.on_output_token()
        d = em.to_dict()
        assert d["wall_s"] < 0.04, "wall clock included pre-traffic idle"
        assert d["throughput_tok_s"] > 250


class TestFleetMetricsMirror:
    def test_acc_mirrors_to_gauge(self):
        from paddle_tpu.distributed.fleet import metrics as fm

        out = fm.acc(3.0, 4.0)
        assert out == pytest.approx(0.75)
        g = monitor.get_registry().get("fleet_metric")
        assert g.labels(name="acc").value == pytest.approx(0.75)
