"""Launch / elastic / rpc / spawn tests.

Parity model: reference TestDistBase forks real localhost worker processes
(test_dist_base.py:1190); launch tests check env wiring; elastic tests mock
the registry (test_fleet_elastic_manager.py). Subprocess workers here are
tiny scripts that never import jax, so they start fast.
"""
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, **kw):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=120, **kw)


class TestLaunchCLI:
    def test_single_node_two_procs(self, tmp_path):
        script = tmp_path / "worker.py"
        script.write_text(
            "import json, os, sys\n"
            "out = {k: os.environ.get(k) for k in\n"
            "       ('PADDLE_TRAINER_ID', 'PADDLE_TRAINERS_NUM',\n"
            "        'PADDLE_TRAINER_ENDPOINTS', 'PADDLE_JOB_ID')}\n"
            "open(sys.argv[1] + '/rank%s.json'\n"
            "     % os.environ['PADDLE_TRAINER_ID'], 'w').write(\n"
            "    json.dumps(out))\n")
        r = _run([sys.executable, "-m", "paddle_tpu.distributed.launch",
                  "--nproc_per_node", "2", "--log_dir",
                  str(tmp_path / "log"), "--job_id", "jtest",
                  str(script), str(tmp_path)])
        assert r.returncode == 0, r.stderr
        for rank in (0, 1):
            data = json.loads((tmp_path / ("rank%d.json" % rank)).read_text())
            assert data["PADDLE_TRAINER_ID"] == str(rank)
            assert data["PADDLE_TRAINERS_NUM"] == "2"
            assert len(data["PADDLE_TRAINER_ENDPOINTS"].split(",")) == 2
            assert data["PADDLE_JOB_ID"] == "jtest"
        # per-rank logs exist (reference workerlog.N naming)
        assert (tmp_path / "log" / "workerlog.0").exists()
        assert (tmp_path / "log" / "workerlog.1").exists()

    def test_failure_propagates(self, tmp_path):
        script = tmp_path / "bad.py"
        script.write_text("import sys; sys.exit(3)\n")
        r = _run([sys.executable, "-m", "paddle_tpu.distributed.launch",
                  "--nproc_per_node", "2", "--log_dir",
                  str(tmp_path / "log"), str(script)])
        assert r.returncode == 3

    def test_multi_node_rendezvous(self, tmp_path):
        import socket

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        script = tmp_path / "worker.py"
        script.write_text(
            "import json, os, sys\n"
            "open(sys.argv[1] + '/rank%s.json'\n"
            "     % os.environ['PADDLE_TRAINER_ID'], 'w').write(json.dumps(\n"
            "    {k: os.environ.get(k) for k in\n"
            "     ('PADDLE_TRAINER_ID', 'PADDLE_NODE_RANK',\n"
            "      'PADDLE_TRAINERS_NUM', 'PADDLE_MASTER')}))\n")
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        launchers = [subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nnodes", "2", "--node_rank", str(n),
             "--master", "127.0.0.1:%d" % port,
             "--log_dir", str(tmp_path / ("log%d" % n)),
             "--job_id", "mn", str(script), str(tmp_path)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True) for n in range(2)]
        outs = [p.communicate(timeout=120)[0] for p in launchers]
        assert all(p.returncode == 0 for p in launchers), outs
        for rank in (0, 1):
            data = json.loads((tmp_path / ("rank%d.json" % rank)).read_text())
            assert data["PADDLE_TRAINER_ID"] == str(rank)
            assert data["PADDLE_NODE_RANK"] == str(rank)
            assert data["PADDLE_TRAINERS_NUM"] == "2"

    def test_multi_node_requires_master(self, tmp_path):
        from paddle_tpu.distributed.launch import Controller, LaunchConfig

        ctl = Controller(LaunchConfig(nnodes=2, node_rank=0),
                         "nonexistent.py")
        with pytest.raises(ValueError, match="master"):
            ctl.build_pod()

    def test_elastic_restart(self, tmp_path):
        # worker exits 101 once (restart requested), then succeeds
        script = tmp_path / "elastic.py"
        script.write_text(
            "import os, sys\n"
            "if os.environ['PADDLE_RESTART_ROUND'] == '0':\n"
            "    sys.exit(101)\n"
            "sys.exit(0)\n")
        r = _run([sys.executable, "-m", "paddle_tpu.distributed.launch",
                  "--nproc_per_node", "1", "--max_restarts", "1",
                  "--log_dir", str(tmp_path / "log"), str(script)])
        assert r.returncode == 0, r.stderr


class TestElasticManager:
    def test_membership_watch(self):
        from paddle_tpu.distributed.elastic import (
            ElasticManager, ElasticStatus)
        from paddle_tpu.distributed.store import TCPStore

        store = TCPStore(is_master=True)
        try:
            os.environ["PADDLE_ELASTIC_FAULT_TOLERANC_LEVEL"] = "1"
            try:
                # generous margins: heartbeat threads on a loaded CI
                # host can miss tight 0.1s/0.5s windows (observed flake)
                m0 = ElasticManager(store=store, job_id="ej", rank=0, np=2,
                                    heartbeat_interval=0.2, ttl=3.0)
                m1 = ElasticManager(store=store, job_id="ej", rank=1, np=2,
                                    heartbeat_interval=0.2, ttl=3.0)
            finally:
                del os.environ["PADDLE_ELASTIC_FAULT_TOLERANC_LEVEL"]
            m0.register()
            m1.register()
            time.sleep(0.5)
            assert m0.alive_nodes() == [0, 1]
            assert m0.watch() == ElasticStatus.HOLD
            # node 1 dies -> heartbeat goes stale -> RESTART (ftl=1)
            m1.exit()
            deadline = time.time() + 10.0
            while time.time() < deadline and m0.alive_nodes() != [0]:
                time.sleep(0.2)
            assert m0.alive_nodes() == [0]
            assert m0.watch() == ElasticStatus.RESTART
            m0.exit()
        finally:
            store.close()


class TestSpawn:
    def test_spawn_two_procs(self, tmp_path):
        import paddle_tpu.distributed as dist

        out = str(tmp_path)
        dist.spawn(_spawn_target, args=(out,), nprocs=2)
        ranks = sorted(p.name for p in tmp_path.glob("rank*"))
        assert ranks == ["rank0", "rank1"]


def _spawn_target(out_dir):
    # runs in a spawned child: record the wired rank env
    rank = os.environ["PADDLE_TRAINER_ID"]
    assert os.environ["PADDLE_TRAINERS_NUM"] == "2"
    open(os.path.join(out_dir, "rank%s" % rank), "w").close()


class TestRPC:
    def test_rpc_two_workers(self, tmp_path):
        # pick a free port for the master store
        import socket

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        script = tmp_path / "rpc_worker.py"
        script.write_text(
            "import os, sys\n"
            "sys.path.insert(0, %r)\n"
            "from paddle_tpu.distributed import rpc\n"
            "rank = int(sys.argv[1])\n"
            "rpc.init_rpc('worker%%d' %% rank, rank=rank, world_size=2,\n"
            "             master_endpoint='127.0.0.1:%d')\n"
            "infos = rpc.get_all_worker_infos()\n"
            "assert [w.name for w in infos] == ['worker0', 'worker1'], infos\n"
            "if rank == 0:\n"
            "    out = rpc.rpc_sync('worker1', pow, args=(2, 10))\n"
            "    assert out == 1024, out\n"
            "    fut = rpc.rpc_async('worker1', divmod, args=(7, 3))\n"
            "    assert fut.result(timeout=30) == (2, 1)\n"
            "rpc.shutdown()\n" % (REPO, port))
        env = {**os.environ,
               "PYTHONPATH": REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""),
               "JAX_PLATFORMS": "cpu"}
        procs = [subprocess.Popen(
            [sys.executable, str(script), str(r)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
            for r in range(2)]
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=120)
            outs.append(out)
        assert all(p.returncode == 0 for p in procs), outs

    def test_rpc_errors_propagate(self):
        from paddle_tpu.distributed import rpc

        rpc.init_rpc("solo", rank=0, world_size=1,
                     master_endpoint="127.0.0.1:0")
        try:
            assert rpc.rpc_sync("solo", len, args=([1, 2, 3],)) == 3
            info = rpc.get_worker_info()
            assert info.name == "solo" and info.rank == 0
            with pytest.raises(TypeError):
                rpc.rpc_sync("solo", len, args=(1,))
        finally:
            rpc.shutdown()
