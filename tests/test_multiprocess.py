"""Forked multi-process distributed tests — the reference TestDistBase
analog (/root/reference/python/paddle/fluid/tests/unittests/
test_dist_base.py:899 _run_cluster / :1709 check_with_place): real worker
processes on localhost, rendezvous over the native TCP store, loss
sequences compared between the 1-process and N-process runs.
"""
from __future__ import annotations

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "dist_worker.py")


from dist_utils import free_port as _free_port  # shared harness


def _run_cluster(nranks, timeout=240):
    port = _free_port()
    procs = []
    for rank in range(nranks):
        env = dict(os.environ)
        env.update({
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
            "JAX_PLATFORMS": "cpu",
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(nranks),
            "PADDLE_MASTER": "127.0.0.1:%d" % port,
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        })
        procs.append(subprocess.Popen(
            [sys.executable, WORKER], env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))
    outs = []
    for rank, p in enumerate(procs):
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        assert p.returncode == 0, (
            "rank %d failed (rc=%d):\nstdout:\n%s\nstderr:\n%s"
            % (rank, p.returncode, out[-2000:], err[-3000:]))
        outs.append(out)
    return outs


class TestMultiProcess2Ranks:
    @pytest.fixture(scope="class")
    def cluster_out(self):
        return _run_cluster(2)

    def test_all_collectives_pass_in_workers(self, cluster_out):
        # workers assert every collective internally; reaching DIST_RESULT
        # means all of them passed on both ranks
        for out in cluster_out:
            assert "DIST_RESULT" in out

    def test_dp_losses_match_single_process(self, cluster_out):
        sys.path.insert(0, os.path.join(REPO, "tests"))
        from dist_worker import mlp_losses

        golden = mlp_losses(rank=None, steps=4)
        per_rank = {}
        for out in cluster_out:
            line = [l for l in out.splitlines()
                    if l.startswith("DIST_RESULT ")][0]
            rec = json.loads(line[len("DIST_RESULT "):])
            per_rank[rec["rank"]] = rec["losses"]
        assert set(per_rank) == {0, 1}
        # both ranks see the identical (averaged) loss sequence, and it
        # equals the full-batch single-process sequence
        np.testing.assert_allclose(per_rank[0], per_rank[1], rtol=1e-12)
        np.testing.assert_allclose(per_rank[0], golden, rtol=1e-10)
