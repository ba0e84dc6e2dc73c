"""Forked multi-process distributed tests — the reference TestDistBase
analog (/root/reference/python/paddle/fluid/tests/unittests/
test_dist_base.py:899 _run_cluster / :1709 check_with_place): real worker
processes on localhost, rendezvous over the native TCP store, loss
sequences compared between the 1-process and N-process runs.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "dist_worker.py")


from dist_utils import free_port, rank_env, report, run_ranks  # noqa: E402


def _run_cluster(nranks):
    port = free_port()
    res = run_ranks(
        [sys.executable, WORKER],
        [rank_env(rank, nranks, port,
                  XLA_FLAGS="--xla_force_host_platform_device_count=2")
         for rank in range(nranks)], 90)
    assert all(rc == 0 for _, rc, _, _ in res), report(res)
    return [out for _, _, out, _ in res]


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
