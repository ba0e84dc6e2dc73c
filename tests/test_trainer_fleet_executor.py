"""Trainer/DeviceWorker drivers + fleet datasets + FleetExecutor actor
runtime (reference framework/trainer.h, device_worker.h,
distributed/fleet/dataset/, distributed/fleet_executor/).
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.static as static
from paddle_tpu.distributed.fleet_executor import FleetExecutor, TaskNode
from paddle_tpu.framework.dataset import (
    InMemoryDataset,
    QueueDataset,
    RecordWriter,
)
from paddle_tpu.framework.trainer import (
    DistMultiTrainer,
    MultiTrainer,
    TrainerFactory,
)


def _write_records(path, n=32, seed=0):
    rng = np.random.RandomState(seed)
    with RecordWriter(path) as w:
        for i in range(n):
            x = rng.randn(4).astype(np.float32)
            y = np.asarray([x.sum()], np.float32)
            w.write_example((x, y))
    return path


class TestFleetDatasets:
    def test_queue_dataset_batches(self, tmp_path):
        f = _write_records(str(tmp_path / "a.rec"), n=10)
        ds = QueueDataset()
        ds.init(batch_size=4, thread_num=1, use_var=["x", "y"])
        ds.set_filelist([f])
        batches = list(ds.batches())
        assert sum(b["x"].shape[0] for b in batches) == 10
        assert batches[0]["x"].shape[1] == 4

    def test_in_memory_dataset_shuffle(self, tmp_path):
        f = _write_records(str(tmp_path / "a.rec"), n=16)
        ds = InMemoryDataset()
        ds.init(batch_size=16, thread_num=1, use_var=["x", "y"])
        ds.set_filelist([f])
        ds.load_into_memory()
        assert ds.get_memory_data_size() == 16
        first = next(iter(ds.batches()))["x"].copy()
        ds.local_shuffle(seed=3)
        second = next(iter(ds.batches()))["x"]
        assert first.shape == second.shape
        assert not np.allclose(first, second)
        # same multiset of rows
        np.testing.assert_allclose(np.sort(first.sum(1)),
                                   np.sort(second.sum(1)), rtol=1e-6)


class TestTrainFromDataset:
    def teardown_method(self, m):
        static.disable_static()

    def test_train_from_dataset_drops_loss(self, tmp_path):
        f = _write_records(str(tmp_path / "t.rec"), n=64)
        paddle.seed(0)
        static.enable_static()
        main, startup = static.Program(), static.Program()
        with static.program_guard(main, startup):
            x = static.data("x", [-1, 4], "float32")
            y = static.data("y", [-1, 1], "float32")
            lin = nn.Linear(4, 1)
            loss = F.mse_loss(lin(x), y)
            opt = paddle.optimizer.SGD(learning_rate=0.05, parameters=None)
            opt.minimize(loss)
        ds = InMemoryDataset()
        ds.init(batch_size=8, thread_num=2, use_var=[x, y])
        ds.set_filelist([f])
        ds.load_into_memory()
        exe = static.Executor()
        exe.run(startup)
        losses = []
        for _ in range(4):
            tr = exe.train_from_dataset(main, ds, fetch_list=[loss])
            losses.append(float(np.mean(tr.losses)))
        assert losses[-1] < losses[0], losses

    def test_trainer_factory(self):
        t = TrainerFactory().create_trainer("DistMultiTrainer",
                                            num_workers=3)
        assert isinstance(t, DistMultiTrainer)
        assert t.num_workers == 3


class TestDownpourWorker:
    def test_ps_pull_push_around_step(self):
        from paddle_tpu.distributed.ps.runtime import TheOnePSRuntime

        rt = TheOnePSRuntime()
        rt.create_sparse_table("emb", 4, optimizer="sgd", lr=1.0,
                               init_std=0.0)
        pulls, pushes = [], []

        def run_fn(batch):
            return batch

        def push_grads(slot, ids, rows, batch, out):
            pushes.append(ids.copy())
            return np.ones((ids.size, 4), np.float32)

        tr = DistMultiTrainer(num_workers=1)
        tr.initialize(run_fn=run_fn)
        tr.set_ps(rt, {"ids": "emb"}, push_grads)
        batches = [{"ids": np.array([1, 2], np.int64)},
                   {"ids": np.array([2, 3], np.int64)}]
        tr.run(iter(batches))
        assert len(pushes) == 2
        # id 2 was pushed twice with grad 1 and lr 1 -> row == -2
        np.testing.assert_allclose(rt.pull_sparse("emb", [2]),
                                   np.full((1, 4), -2.0))


class TestFleetExecutor:
    def test_linear_pipeline_order_and_results(self):
        fe = FleetExecutor.from_stages(
            [lambda x: x + 1, lambda x: x * 10],
            num_micro_batches=4,
            source_fn=lambda i: i)
        out = fe.run(timeout=30)
        assert out == [(i + 1) * 10 for i in range(4)]

    def test_diamond_graph(self):
        # source -> (a, b) -> join -> sink
        src = TaskNode(node_type="Source", task_id=0, max_run_times=3,
                       payload=lambda i: i)
        a = TaskNode(node_type="Compute", task_id=1, max_run_times=3,
                     payload=lambda x: x + 100)
        b = TaskNode(node_type="Compute", task_id=2, max_run_times=3,
                     payload=lambda x: x * 2)
        join = TaskNode(node_type="Compute", task_id=3, max_run_times=3,
                        payload=lambda u, v: (u, v))
        sink = TaskNode(node_type="Sink", task_id=4, max_run_times=3)
        for up, down in [(src, a), (src, b), (a, join), (b, join),
                         (join, sink)]:
            up.add_downstream_task(down.task_id)
            down.add_upstream_task(up.task_id)
        out = FleetExecutor([src, a, b, join, sink]).run(timeout=30)
        assert out == [(i + 100, i * 2) for i in range(3)]

    def test_timeout_raises(self):
        # a compute node with a missing upstream never fires
        src = TaskNode(node_type="Source", task_id=0, max_run_times=1,
                       payload=lambda i: i)
        c = TaskNode(node_type="Compute", task_id=1, max_run_times=1)
        sink = TaskNode(node_type="Sink", task_id=2, max_run_times=1)
        src.add_downstream_task(1)
        c.add_upstream_task(0)
        c.add_upstream_task(99)  # never sends
        c.add_downstream_task(2)
        sink.add_upstream_task(1)
        with pytest.raises(TimeoutError):
            FleetExecutor([src, c, sink]).run(timeout=1)


class TestReviewRegressions:
    def test_worker_error_propagates_without_deadlock(self):
        tr = MultiTrainer(num_workers=1)

        def bad(batch):
            raise ValueError("worker-boom")

        tr.initialize(run_fn=bad)
        with pytest.raises(ValueError, match="worker-boom"):
            tr.run(iter([{"x": i} for i in range(50)]))

    def test_diamond_binds_args_in_declaration_order(self):
        # upstream a has the LARGER task_id but is declared first
        src = TaskNode(node_type="Source", task_id=0, max_run_times=2,
                       payload=lambda i: i)
        a = TaskNode(node_type="Compute", task_id=7, max_run_times=2,
                     payload=lambda x: "A%d" % x)
        b = TaskNode(node_type="Compute", task_id=2, max_run_times=2,
                     payload=lambda x: "B%d" % x)
        join = TaskNode(node_type="Compute", task_id=3, max_run_times=2,
                        payload=lambda u, v: (u, v))
        sink = TaskNode(node_type="Sink", task_id=4, max_run_times=2)
        for up, down in [(src, a), (src, b)]:
            up.add_downstream_task(down.task_id)
            down.add_upstream_task(up.task_id)
        a.add_downstream_task(3)
        b.add_downstream_task(3)
        join.add_upstream_task(7)   # declared first -> first arg
        join.add_upstream_task(2)
        join.add_downstream_task(4)
        sink.add_upstream_task(3)
        out = FleetExecutor([src, a, b, join, sink]).run(timeout=30)
        assert out == [("A0", "B0"), ("A1", "B1")]

    def test_source_credit_bound(self):
        import threading
        import time as _time

        seen = []
        gate = threading.Event()

        def slow_stage(x):
            seen.append(x)
            gate.wait(0.2)
            return x

        fe = FleetExecutor.from_stages([slow_stage], num_micro_batches=8)
        # stage buffer size 2 (default credit): while the first batch is
        # in flight, at most `credit` tokens may have been emitted
        t = threading.Thread(target=fe.run, kwargs={"timeout": 30},
                             daemon=True)
        t.start()
        _time.sleep(0.05)
        assert len(seen) <= 2
        gate.set()
        t.join(30)


class TestCrossRankMessageBus:
    def test_pipeline_spans_two_processes(self):
        """Reference fleet_executor brpc MessageBus role: a 4-node
        pipeline split across two OS processes; interceptor messages
        (ready/ack) cross ranks over the TCP-store bus and the sink's
        completion releases both carriers."""
        import os
        import subprocess
        import sys

        from dist_utils import free_port

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        port = free_port()
        procs = []
        for rank in range(2):
            env = dict(os.environ)
            env.update({"FEXEC_RANK": str(rank), "FEXEC_PORT": str(port),
                        "FEXEC_MICRO": "5", "JAX_PLATFORMS": "cpu"})
            procs.append(subprocess.Popen(
                [sys.executable,
                 os.path.join(repo, "tests", "fexec_worker.py")],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True))
        outs = []
        for p in procs:
            try:
                outs.append(p.communicate(timeout=120))
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                raise
        for p, (o, e) in zip(procs, outs):
            assert p.returncode == 0, e[-2000:]
        assert "RANK0_DONE" in outs[0][0]
        # source i*10 -> stageA +1 -> stageB *2, in microbatch order
        assert "RESULTS [2, 22, 42, 62, 82]" in outs[1][0], outs[1][0]
