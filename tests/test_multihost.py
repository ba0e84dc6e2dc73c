"""True multi-host SPMD proof (VERDICT r2 #5): two OS processes, each
hosting 4 virtual CPU devices, form ONE global 8-device mesh through
`init_parallel_env` (jax.distributed.initialize + the native TCP store),
run a dp train step on the global mesh, and reproduce the single-process
8-device loss sequence.

Reference pattern: test_dist_base.py:899 — fork real worker processes
with fabricated PADDLE_* env, compare loss sequences between 1-proc and
N-proc runs (check_with_place:1709).
"""
from __future__ import annotations

import os
import re
import subprocess
import sys

import numpy as np

from dist_utils import (REPO, base_env, free_ports, rank_env, report,
                        run_ranks)

WORKER = os.path.join(REPO, "tests", "multihost_worker.py")


def _parse_losses(stdout):
    out = {}
    for m in re.finditer(r"LOSS (\d+) ([-\d.]+)", stdout):
        out[int(m.group(1))] = float(m.group(2))
    return [out[i] for i in sorted(out)]


def _golden_single_process(steps):
    """The same steps in one process over 8 devices, outside any
    enclosing job's launcher variables."""
    env = base_env(XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run([sys.executable, WORKER, str(steps)], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-3000:]
    losses = _parse_losses(proc.stdout)
    assert len(losses) == steps, proc.stdout
    return losses


def test_two_processes_one_global_mesh():
    # 4 steps of the copy task (multihost_worker trains labels==ids):
    # loss drops ~0.2 by step 3 on every build, so the progress
    # assertion at the bottom is deterministic — with the old random
    # labels it was a coin flip around ln(vocab) (the PR-7-noted flake)
    steps = 4
    golden = _golden_single_process(steps)

    # reserve the store port AND the +1 the JAX coordinator derives from
    # it, plus the +10/+11 endpoint slots announced to the store
    port = free_ports(12)
    outs = run_ranks(
        [sys.executable, WORKER, str(steps)],
        [rank_env(rank, 2, port, PADDLE_NNODES=2, PADDLE_NODE_RANK=rank,
                  PADDLE_CURRENT_ENDPOINT="127.0.0.1:%d"
                  % (port + 10 + rank),
                  XLA_FLAGS="--xla_force_host_platform_device_count=4")
         for rank in range(2)], 120, cwd=REPO)
    assert all(rc == 0 for _, rc, _, _ in outs), report(outs)
    losses = [_parse_losses(out) for _, _, out, _ in outs]
    assert len(losses[0]) == steps and len(losses[1]) == steps, \
        report(outs)
    # both processes observe the same (replicated) loss...
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-5)
    # ...and the 2-process global mesh reproduces the single-process run
    np.testing.assert_allclose(losses[0], golden, rtol=1e-4, atol=1e-5)
    # training actually progresses
    assert losses[0][-1] < losses[0][0]
