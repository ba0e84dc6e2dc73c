"""Launch controller: spawn, watch, and (elastically) restart worker procs.

Parity map (reference python/paddle/distributed/launch/):
- `CollectiveController.build_pod` (controllers/collective.py) -> `Controller`
- `Pod`/`Container` (job/pod.py, job/container.py)             -> `Pod`/`Proc`
- `HTTPMaster/ETCDMaster` rendezvous (controllers/master.py)   -> TCPStore keys
- per-rank log files `workerlog.N` (job/container.py)          -> same names
- elastic restart on membership change (exit 101)              -> `Controller.run`
"""
from __future__ import annotations

import os
import signal
import subprocess
import sys
import time

from ..elastic import ELASTIC_EXIT_RESTART
from ..store import TCPStore


class LaunchConfig:
    def __init__(self, nnodes=1, node_rank=0, nproc_per_node=1,
                 master=None, log_dir="log", job_id="default",
                 max_restarts=0, devices=None):
        self.nnodes = int(nnodes)
        self.node_rank = int(node_rank)
        self.nproc_per_node = int(nproc_per_node)
        self.master = master  # "host:port" or None for single node
        self.log_dir = log_dir
        self.job_id = job_id
        self.max_restarts = int(max_restarts)
        self.devices = devices


class Proc:
    """One worker process (reference job/container.py Container)."""

    def __init__(self, cmd, env, log_path):
        self.cmd, self.env, self.log_path = cmd, env, log_path
        self.proc = None
        self.log_file = None

    def start(self):
        os.makedirs(os.path.dirname(self.log_path) or ".", exist_ok=True)
        self.log_file = open(self.log_path, "ab")
        full_env = dict(os.environ)
        full_env.update(self.env)
        self.proc = subprocess.Popen(
            self.cmd, env=full_env, stdout=self.log_file,
            stderr=subprocess.STDOUT)

    def alive(self):
        return self.proc is not None and self.proc.poll() is None

    @property
    def returncode(self):
        return None if self.proc is None else self.proc.poll()

    def stop(self, timeout=10):
        if self.proc is None:
            return
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        if self.log_file:
            self.log_file.close()
            self.log_file = None


class Pod:
    """The set of worker procs on this node (reference job/pod.py)."""

    def __init__(self):
        self.procs = []

    def add(self, proc):
        self.procs.append(proc)

    def start(self):
        for p in self.procs:
            p.start()

    def stop(self):
        for p in self.procs:
            p.stop()

    def poll(self):
        """Return (done, failed_rc): done when all exited or any failed."""
        codes = [p.returncode for p in self.procs]
        for rc in codes:
            if rc is not None and rc != 0:
                return True, rc
        if all(rc == 0 for rc in codes):
            return True, 0
        return False, None

    def clear(self):
        self.procs = []


class Controller:
    """Builds the pod env, runs rendezvous, watches, restarts on elastic."""

    def __init__(self, config: LaunchConfig, training_script,
                 training_script_args=()):
        self.cfg = config
        self.script = training_script
        self.script_args = list(training_script_args)
        self.pod = Pod()
        self.store = None

    # -- rendezvous -----------------------------------------------------
    def _rendezvous(self, restart_round=0):
        """All nodes register with the master store and learn peers.

        Reference: launch/controllers/master.py sync_peers (:110 HTTP,
        :203 etcd). Store keys: <job>/<round>/node/<rank> -> "host",
        barrier on all-registered. Keys are namespaced by restart round so
        an elastic restart re-synchronizes instead of reading stale state.
        """
        cfg = self.cfg
        if cfg.nnodes <= 1:
            return ["127.0.0.1"]
        if not cfg.master:
            raise ValueError(
                "launch: --master host:port is required when nnodes > 1 "
                "(got nnodes=%d)" % cfg.nnodes)
        if self.store is None:  # one server lives across restart rounds
            host, _, port = cfg.master.partition(":")
            self.store = TCPStore(host, int(port),
                                  is_master=(cfg.node_rank == 0))
        ns = "%s/%d" % (cfg.job_id, restart_round)
        self.store.set("%s/node/%d" % (ns, cfg.node_rank),
                       os.environ.get("POD_IP", cfg.master.split(":")[0]))
        self.store.barrier("%s/rendezvous" % ns, cfg.nnodes)
        nodes = []
        for r in range(cfg.nnodes):
            nodes.append(self.store.get("%s/node/%d" % (ns, r)).decode())
        return nodes

    # -- pod construction ----------------------------------------------
    def build_pod(self, restart_round=0):
        cfg = self.cfg
        nproc = cfg.nproc_per_node
        if nproc > 1:
            # one process per chip: several workers on one TPU host
            # would each try to take every chip. (nproc == 1 never asks:
            # this parent must stay off the backend its child needs.)
            from .. import refuse_multiprocess_on_tpu

            refuse_multiprocess_on_tpu(
                "launch --nproc_per_node %d" % nproc)
        nodes = self._rendezvous(restart_round)
        world = cfg.nnodes * nproc
        base_port = 6170
        endpoints = ",".join(
            "%s:%d" % (nodes[n % len(nodes)], base_port + i)
            for n in range(cfg.nnodes) for i in range(nproc))
        for local_rank in range(nproc):
            rank = cfg.node_rank * nproc + local_rank
            env = {
                "PADDLE_TRAINER_ID": str(rank),
                "PADDLE_LOCAL_RANK": str(local_rank),
                "PADDLE_TRAINERS_NUM": str(world),
                "PADDLE_NNODES": str(cfg.nnodes),
                "PADDLE_NODE_RANK": str(cfg.node_rank),
                "PADDLE_TRAINER_ENDPOINTS": endpoints,
                "PADDLE_CURRENT_ENDPOINT":
                    endpoints.split(",")[rank] if endpoints else "",
                "PADDLE_JOB_ID": cfg.job_id,
                "PADDLE_RESTART_ROUND": str(restart_round),
            }
            if cfg.master:
                env["PADDLE_MASTER"] = cfg.master
            if cfg.devices:
                env["PADDLE_DEVICES"] = cfg.devices
            cmd = [sys.executable, "-u", self.script] + self.script_args
            log = os.path.join(cfg.log_dir, "workerlog.%d" % local_rank)
            self.pod.add(Proc(cmd, env, log))

    # -- run loop -------------------------------------------------------
    def run(self, poll_interval=0.2):
        restarts = 0
        while True:
            self.build_pod(restart_round=restarts)
            self.pod.start()
            rc = self._watch(poll_interval)
            self.pod.stop()
            if rc == ELASTIC_EXIT_RESTART and restarts < self.cfg.max_restarts:
                restarts += 1
                self.pod.clear()
                continue
            return rc

    def _watch(self, poll_interval):
        while True:
            done, rc = self.pod.poll()
            if done:
                return rc
            time.sleep(poll_interval)

    def stop(self):
        self.pod.stop()
        if self.store is not None:
            self.store.close()


def launch(args=None):
    """CLI entry (python -m paddle_tpu.distributed.launch)."""
    import argparse

    parser = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    parser.add_argument("--nnodes", type=int,
                        default=int(os.environ.get("PADDLE_NNODES", 1)))
    parser.add_argument("--node_rank", type=int,
                        default=int(os.environ.get("PADDLE_NODE_RANK", 0)))
    parser.add_argument("--nproc_per_node", type=int, default=1)
    parser.add_argument("--master",
                        default=os.environ.get("PADDLE_MASTER"))
    parser.add_argument("--log_dir", default="log")
    parser.add_argument("--job_id", default="default")
    parser.add_argument("--max_restarts", type=int, default=0)
    parser.add_argument("--devices", default=None)
    parser.add_argument("training_script")
    parser.add_argument("training_script_args", nargs=argparse.REMAINDER)
    ns = parser.parse_args(args)

    cfg = LaunchConfig(nnodes=ns.nnodes, node_rank=ns.node_rank,
                       nproc_per_node=ns.nproc_per_node, master=ns.master,
                       log_dir=ns.log_dir, job_id=ns.job_id,
                       max_restarts=ns.max_restarts, devices=ns.devices)
    ctl = Controller(cfg, ns.training_script, ns.training_script_args)
    try:
        rc = ctl.run()
    finally:
        ctl.stop()
    sys.exit(rc or 0)
