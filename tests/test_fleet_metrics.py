"""Distributed fleet metrics (reference fleet/metrics/metric.py over
framework/fleet/metrics.cc): per-trainer partials reduce to the global
metric. Single-process oracle tests + a 2-process run whose global AUC
must equal the single-process AUC over the union of the data."""
from __future__ import annotations

import os
import sys

import numpy as np

from dist_utils import free_port, rank_env, report, run_ranks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bins(scores, labels, n=64):
    pos = np.zeros(n)
    neg = np.zeros(n)
    idx = np.clip((scores * n).astype(int), 0, n - 1)
    for i, y in zip(idx, labels):
        (pos if y else neg)[i] += 1
    return pos, neg


def _auc_oracle(scores, labels):
    order = np.argsort(-scores)
    y = np.asarray(labels)[order]
    tp = np.cumsum(y)
    fp = np.cumsum(1 - y)
    P, N = tp[-1], fp[-1]
    if P == 0 or N == 0:
        return 0.5
    # trapezoid over the ROC steps
    tpr = np.concatenate([[0], tp / P])
    fpr = np.concatenate([[0], fp / N])
    return float(np.trapezoid(tpr, fpr))


class TestSingleProcess:
    def test_auc_matches_rank_oracle(self):
        from paddle_tpu.distributed.fleet import metrics

        rng = np.random.RandomState(0)
        labels = rng.randint(0, 2, 512)
        scores = np.clip(labels * 0.35 + rng.rand(512) * 0.65, 0, 0.999)
        pos, neg = _bins(scores, labels, n=512)
        got = metrics.auc(pos, neg)
        want = _auc_oracle(scores, labels)
        assert abs(got - want) < 2e-2, (got, want)

    def test_degenerate_auc(self):
        from paddle_tpu.distributed.fleet import metrics

        assert metrics.auc(np.zeros(8), np.ones(8)) == 0.5

    def test_scalar_metrics(self):
        from paddle_tpu.distributed.fleet import metrics

        np.testing.assert_allclose(metrics.sum(np.arange(4.0)),
                                   np.arange(4.0))
        assert metrics.mae(np.array([6.0]), np.array([3.0])) == 2.0
        assert metrics.mse(np.array([12.0]), np.array([3.0])) == 4.0
        assert metrics.rmse(np.array([12.0]), np.array([3.0])) == 2.0
        assert metrics.acc(np.array([3.0]), np.array([4.0])) == 0.75


WORKER = r"""
import os, sys
sys.path.insert(0, %r)
import numpy as np
import paddle_tpu.distributed as dist
from paddle_tpu.distributed.fleet import metrics

dist.init_parallel_env()
rank = dist.get_rank()
rng = np.random.RandomState(0)
labels = rng.randint(0, 2, 512)
scores = np.clip(labels * 0.35 + rng.rand(512) * 0.65, 0, 0.999)
half = slice(rank * 256, (rank + 1) * 256)          # disjoint shards
n = 512
pos = np.zeros(n); neg = np.zeros(n)
idx = np.clip((scores[half] * n).astype(int), 0, n - 1)
for i, y in zip(idx, labels[half]):
    (pos if y else neg)[i] += 1
print("AUC", metrics.auc(pos, neg))
print("ACC", metrics.acc(np.array([float((labels[half] == 1).sum())]),
                         np.array([256.0])))
""" % REPO


class TestTwoProcess:
    def test_global_auc_equals_union(self):
        from paddle_tpu.distributed.fleet import metrics

        port = free_port()
        res = run_ranks([sys.executable, "-c", WORKER],
                        [rank_env(rank, 2, port) for rank in range(2)], 60)
        assert all(rc == 0 for _, rc, _, _ in res), report(res)
        outs = [out for _, _, out, _ in res]
        aucs = [float(o.split("AUC ")[1].split()[0]) for o in outs]
        # both ranks see the same GLOBAL metric...
        assert abs(aucs[0] - aucs[1]) < 1e-9
        # ...equal to the single-process metric over the full data
        rng = np.random.RandomState(0)
        labels = rng.randint(0, 2, 512)
        scores = np.clip(labels * 0.35 + rng.rand(512) * 0.65, 0, 0.999)
        pos, neg = _bins(scores, labels, n=512)
        assert abs(aucs[0] - metrics.auc(pos, neg)) < 1e-9
        # global accuracy is the pooled fraction
        accs = [float(o.split("ACC ")[1].split()[0]) for o in outs]
        assert abs(accs[0] - (labels == 1).mean()) < 1e-9
