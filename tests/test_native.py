"""Tests for the native C++ runtime core (csrc/): tracer, TCP store,
data feed, stats. Mirrors the reference's C++ unit-test coverage for
profiler/gen_comm_id/data_feed/monitor (SURVEY.md §4.5)."""
import json
import os
import pickle
import threading
import time

import numpy as np
import pytest

from paddle_tpu.core import native


@pytest.fixture(scope="module")
def lib():
    return native.get_lib()


class TestStats:
    def test_add_get_peak(self, lib):
        native.Stats.reset("test_counter")
        native.Stats.add("test_counter", 5)
        native.Stats.add("test_counter", 3)
        assert native.Stats.get("test_counter") == 8
        native.Stats.add("test_counter", -6)
        assert native.Stats.get("test_counter") == 2
        assert native.Stats.peak("test_counter") == 8

    def test_dump(self, lib):
        native.Stats.reset("dump_me")
        native.Stats.add("dump_me", 42)
        d = native.Stats.dump()
        assert d["dump_me"] == 42

    def test_threaded(self, lib):
        native.Stats.reset("mt")
        ts = [threading.Thread(
            target=lambda: [native.Stats.add("mt", 1) for _ in range(1000)])
            for _ in range(8)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert native.Stats.get("mt") == 8000


class TestTrace:
    def test_push_pop_dump(self, lib, tmp_path):
        lib.pt_trace_clear()
        lib.pt_trace_enable(2)
        lib.pt_trace_push(b"outer", 1)
        lib.pt_trace_push(b"inner", 2)
        lib.pt_trace_pop()
        lib.pt_trace_pop()
        lib.pt_trace_instant(b"marker", 1)
        lib.pt_trace_counter(b"mem", 12345)
        lib.pt_trace_disable()
        path = str(tmp_path / "trace.json")
        assert lib.pt_trace_dump(path.encode()) == 0
        with open(path) as f:
            data = json.load(f)
        names = [e["name"] for e in data["traceEvents"]]
        assert "outer" in names and "inner" in names
        assert "marker" in names and "mem" in names
        dur = {e["name"]: e for e in data["traceEvents"]}
        assert dur["outer"]["dur"] >= dur["inner"]["dur"]

    def test_disabled_records_nothing(self, lib):
        lib.pt_trace_clear()
        lib.pt_trace_disable()
        lib.pt_trace_push(b"ghost", 1)
        lib.pt_trace_pop()
        assert lib.pt_trace_event_count() == 0

    def test_level_filter(self, lib):
        lib.pt_trace_clear()
        lib.pt_trace_enable(1)
        lib.pt_trace_push(b"verbose", 9)  # above level -> dropped
        lib.pt_trace_pop()
        assert lib.pt_trace_event_count() == 0
        lib.pt_trace_disable()


class TestTCPStore:
    def test_set_get_roundtrip(self):
        from paddle_tpu.distributed.store import TCPStore

        with TCPStore(is_master=True) as master:
            master.set("hello", b"world")
            assert master.get("hello") == b"world"
            with TCPStore(port=master.port) as client:
                assert client.get("hello") == b"world"
                client.set("k2", "v2")
                assert master.get("k2") == b"v2"

    def test_blocking_get_waits(self):
        from paddle_tpu.distributed.store import TCPStore

        with TCPStore(is_master=True) as master:
            def later():
                import time
                time.sleep(0.2)
                with TCPStore(port=master.port) as c:
                    c.set("late_key", b"arrived")

            t = threading.Thread(target=later)
            t.start()
            v = master.get("late_key", timeout_s=5)
            t.join()
            assert v == b"arrived"

    def test_get_timeout_returns_none(self):
        from paddle_tpu.distributed.store import TCPStore

        with TCPStore(is_master=True) as master:
            assert master.get("never_set", timeout_s=0.2) is None

    def test_add_and_barrier(self):
        from paddle_tpu.distributed.store import TCPStore

        with TCPStore(is_master=True) as master:
            assert master.add("cnt", 2) == 2
            assert master.add("cnt", 3) == 5
            errs = []

            def rank(i):
                try:
                    with TCPStore(port=master.port) as c:
                        c.barrier("b0", 3, timeout_s=10)
                except Exception as e:  # pragma: no cover
                    errs.append(e)

            ts = [threading.Thread(target=rank, args=(i,)) for i in range(2)]
            [t.start() for t in ts]
            master.barrier("b0", 3, timeout_s=10)
            [t.join() for t in ts]
            assert not errs

    def test_barrier_master_waits_for_a_slow_peer(self):
        # the master closes its store right after the barrier; the peer
        # reads the go key only 0.5 s later. Without the peer's
        # confirmation it would find no server and time out.
        from paddle_tpu.distributed.store import TCPStore

        master = TCPStore(is_master=True)
        errs = []

        def peer():
            try:
                with TCPStore(port=master.port) as c:
                    get = c.get

                    def slow_get(key, timeout_s=None):
                        if "/go/" in key:
                            time.sleep(0.5)
                        return get(key, timeout_s)

                    c.get = slow_get
                    c.barrier("slow", 2, timeout_s=5)
            except Exception as e:
                errs.append(e)

        t = threading.Thread(target=peer)
        t.start()
        master.barrier("slow", 2, timeout_s=5)
        master.close()
        t.join(timeout=30)
        assert not t.is_alive() and not errs, errs

    def test_close_wakes_an_idle_peer_connection(self):
        from paddle_tpu.distributed.store import TCPStore

        master = TCPStore(is_master=True)
        with TCPStore(port=master.port) as c:
            c.set("k", b"v")
            t = threading.Thread(target=master.close)
            t.start()
            t.join(timeout=10)
            assert not t.is_alive()

    def test_large_value(self):
        from paddle_tpu.distributed.store import TCPStore

        with TCPStore(is_master=True) as master:
            big = os.urandom(1 << 20)
            master.set("big", big)
            assert master.get("big") == big


class TestDataFeed:
    def test_roundtrip(self, tmp_path):
        from paddle_tpu.io.datafeed import DataFeed, RecordWriter

        path = str(tmp_path / "data.ptrec")
        with RecordWriter(path) as w:
            for i in range(100):
                w.write_example({"x": np.full((4,), i, np.float32),
                                 "y": np.int64(i)})
        feed = DataFeed(path, num_threads=2, deserialize=True)
        seen = sorted(int(ex["y"]) for ex in feed)
        assert seen == list(range(100))
        feed.close()

    def test_shuffle_changes_order(self, tmp_path):
        from paddle_tpu.io.datafeed import DataFeed, RecordWriter

        path = str(tmp_path / "s.ptrec")
        with RecordWriter(path) as w:
            for i in range(200):
                w.write(pickle.dumps(i))
        order = [pickle.loads(r) if isinstance(r, bytes) else r
                 for r in DataFeed(path, num_threads=1, shuffle_buffer=64,
                                   seed=7, deserialize=False)]
        order = [pickle.loads(r) for r in
                 DataFeed(path, num_threads=1, shuffle_buffer=64, seed=7,
                          deserialize=False)]
        assert sorted(order) == list(range(200))
        assert order != list(range(200))

    def test_batched(self, tmp_path):
        from paddle_tpu.io.datafeed import DataFeed, RecordWriter

        path = str(tmp_path / "b.ptrec")
        with RecordWriter(path) as w:
            for i in range(10):
                w.write_example({"x": np.ones((3,), np.float32) * i})
        batches = list(DataFeed(path, num_threads=1).batched(4))
        assert len(batches) == 2  # drop_last
        assert batches[0]["x"].shape == (4, 3)

    def test_multi_file(self, tmp_path):
        from paddle_tpu.io.datafeed import DataFeed, RecordWriter

        paths = []
        for f in range(3):
            p = str(tmp_path / ("f%d.ptrec" % f))
            with RecordWriter(p) as w:
                for i in range(10):
                    w.write_example(np.int64(f * 10 + i))
            paths.append(p)
        vals = sorted(int(v) for v in DataFeed(paths, num_threads=3))
        assert vals == list(range(30))


class TestProfiler:
    def test_record_event_and_export(self, tmp_path):
        import paddle_tpu.profiler as profiler

        with profiler.Profiler() as p:
            with profiler.RecordEvent("step0"):
                x = sum(range(1000))
            p.step()
        path = str(tmp_path / "chrome.json")
        p.export_chrome_tracing(path)
        data = profiler.load_profiler_result(path)
        assert any(e["name"] == "step0" for e in data["traceEvents"])
        s = p.summary()
        assert s["steps"] >= 1 and s["avg_s"] >= 0

    def test_scheduler_windows(self):
        import paddle_tpu.profiler as profiler

        sched = profiler.make_scheduler(closed=1, ready=1, record=2, repeat=1)
        states = [sched(i) for i in range(5)]
        assert states[0] == profiler.ProfilerState.CLOSED
        assert states[1] == profiler.ProfilerState.READY
        assert states[2] == profiler.ProfilerState.RECORD
        assert states[3] == profiler.ProfilerState.RECORD_AND_RETURN
        assert states[4] == profiler.ProfilerState.CLOSED


class TestNativeInterpreter:
    def test_raw_dag_scheduling(self, lib):
        import ctypes

        # diamond: 0 -> {1, 2} -> 3
        h = lib.pt_interp_create(4)
        assert h >= 0
        for b, a in [(0, 1), (0, 2), (1, 3), (2, 3)]:
            assert lib.pt_interp_add_dep(h, b, a) == 0
        order = []

        def body(_ctx, i):
            order.append(i)
            return 0

        cb = lib._INSTR_FN(body)
        assert lib.pt_interp_run(h, cb, ctypes.c_void_p(0), 1) == 0
        assert lib.pt_interp_executed(h) == 4
        assert order[0] == 0 and order[-1] == 3
        assert set(order[1:3]) == {1, 2}
        # re-run resets state
        order.clear()
        assert lib.pt_interp_run(h, cb, ctypes.c_void_p(0), 2) == 0
        assert len(order) == 4
        lib.pt_interp_destroy(h)

    def test_cycle_detected(self, lib):
        import ctypes

        h = lib.pt_interp_create(2)
        lib.pt_interp_add_dep(h, 0, 1)
        lib.pt_interp_add_dep(h, 1, 0)
        cb = lib._INSTR_FN(lambda _c, _i: 0)
        assert lib.pt_interp_run(h, cb, ctypes.c_void_p(0), 1) == -2
        lib.pt_interp_destroy(h)

    def test_callback_error_propagates(self, lib):
        import ctypes

        h = lib.pt_interp_create(3)
        lib.pt_interp_add_dep(h, 0, 1)
        lib.pt_interp_add_dep(h, 1, 2)
        cb = lib._INSTR_FN(lambda _c, i: 1 if i == 1 else 0)
        assert lib.pt_interp_run(h, cb, ctypes.c_void_p(0), 1) == -3
        assert lib.pt_interp_last_error(h) == 1
        lib.pt_interp_destroy(h)

    def test_program_replay_via_native(self):
        import paddle_tpu as paddle
        from paddle_tpu import static
        from paddle_tpu.core.interpreter import NativeInterpreter

        paddle.seed(0)
        static.enable_static()
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", [2, 3], "float32")
            y = (x * 2.0 + 1.0).sum()
        interp = NativeInterpreter(prog)
        assert interp._handle >= 0
        xin = np.arange(6, dtype="float32").reshape(2, 3)
        prog.feed_vars["x"].set_value(xin)
        interp.run()
        assert interp.executed() == len(prog.tape)
        np.testing.assert_allclose(float(y), (xin * 2 + 1).sum(), rtol=1e-6)
        interp.close()
        static.disable_static()

    def test_executor_uses_native_interp(self):
        import paddle_tpu as paddle
        from paddle_tpu import static

        paddle.seed(0)
        static.enable_static()
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", [4], "float32")
            z = paddle.exp(x) / (1.0 + paddle.exp(x))
        exe = static.Executor()
        xin = np.array([-1.0, 0.0, 1.0, 2.0], np.float32)
        (out,) = exe.run(prog, feed={"x": xin}, fetch_list=[z],
                         use_program_cache=False)
        np.testing.assert_allclose(out, 1 / (1 + np.exp(-xin)), rtol=1e-5)
        # the native DAG must actually have been built (no silent fallback)
        interp = getattr(prog, "_native_interp", None)
        assert interp is not None and interp._version == prog.version
        static.disable_static()


class TestOpsCodegen:
    def test_c_ops_namespace(self):
        import paddle_tpu as paddle
        from paddle_tpu import _C_ops
        from paddle_tpu.core.dispatch import WRAPPERS

        assert _C_ops.matmul is WRAPPERS["matmul"]
        out = _C_ops.add(paddle.to_tensor(np.float32(1.0)),
                         paddle.to_tensor(np.float32(2.0)))
        assert float(out) == 3.0

    def test_ops_yaml_covers_registry(self):
        import paddle_tpu  # noqa: F401
        from paddle_tpu.core.dispatch import WRAPPERS

        path = os.path.join(os.path.dirname(__file__), "..",
                            "paddle_tpu", "ops", "ops.yaml")
        names = set()
        for line in open(path):
            if line.startswith("- op : "):
                names.add(line.split(":", 1)[1].strip())
        # custom_* ops register at .so-load time (utils/cpp_extension) —
        # runtime-loaded user ops are not part of the shipped yaml, same
        # as the reference's custom-operator path vs ops.yaml
        missing = {n for n in set(WRAPPERS) - names
                   if not n.startswith("custom_")}
        assert not missing, ("ops.yaml stale; re-run tools/gen_ops.py: %s"
                             % sorted(missing)[:10])
