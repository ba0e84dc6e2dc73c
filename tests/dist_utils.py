"""Shared plumbing for forked multi-process distributed tests
(the reference TestDistBase harness analog).

Every test that starts ranks as processes starts them through
``run_ranks``: the group waits on ONE deadline, and past it every rank
is killed and reaped and the test fails with each rank's return code
and output tail. No group deadline exceeds ``MAX_GROUP_S`` (180 s), so
it always fires before the fixed 300 s per-test limit in
``conftest.py``, and a hung group is reported by the message here,
which names the ranks.
"""
from __future__ import annotations

import os
import signal
import socket
import subprocess
import tempfile
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAX_GROUP_S = 180


def free_ports(n=1, host="127.0.0.1"):
    """Reserve n CONSECUTIVE free ports and return the first. Needed when
    a service derives sibling ports by offset (init_parallel_env puts the
    JAX coordinator on store-port + 1) — reserving only the base port
    leaves the sibling open to bind collisions."""
    for _ in range(64):
        socks = []
        try:
            s0 = socket.socket()
            s0.bind((host, 0))
            base = s0.getsockname()[1]
            socks.append(s0)
            ok = True
            for i in range(1, n):
                s = socket.socket()
                try:
                    s.bind((host, base + i))
                    socks.append(s)
                except OSError:
                    s.close()
                    ok = False
                    break
            if ok:
                return base
        finally:
            for s in socks:
                s.close()
    raise RuntimeError("could not reserve %d consecutive ports" % n)


def free_port(host="127.0.0.1"):
    return free_ports(1, host)


def base_env(**extra):
    """A CPU rank's environment outside any enclosing job: the
    inherited one without its ``PADDLE_*`` launcher variables, with the
    repo on ``PYTHONPATH``, plus ``extra``."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PADDLE_")}
    env.update(PYTHONPATH=REPO + os.pathsep + env.get("PYTHONPATH", ""),
               JAX_PLATFORMS="cpu")
    env.update({k: str(v) for k, v in extra.items()})
    return env


def rank_env(rank, world, port, **extra):
    """``base_env`` of rank ``rank`` of a ``world``-rank job whose
    master store listens on 127.0.0.1:``port``, plus ``extra``."""
    return base_env(PADDLE_TRAINER_ID=rank, PADDLE_TRAINERS_NUM=world,
                    PADDLE_MASTER="127.0.0.1:%d" % port, **extra)


def report(results):
    """Every rank's return code and the tail of its stdout and stderr."""
    return "\n".join(
        "rank %d rc=%s\n--- stdout ---\n%s\n--- stderr ---\n%s"
        % (rank, rc, out[-2000:], err[-3000:])
        for rank, rc, out, err in results)


def _exited(p):
    """True once ``p`` has exited. It stays unreaped (WNOWAIT)."""
    return os.waitid(os.P_PID, p.pid,
                     os.WEXITED | os.WNOHANG | os.WNOWAIT) is not None


def run_ranks(argv, envs, deadline_s, cwd=None):
    """Run ``argv`` once per env in ``envs`` (rank = index; ``argv`` may
    be a function of the rank) and wait for the whole group on one
    deadline. Returns ``[(rank, rc, out, err)]`` in rank order. Past the
    deadline every rank is killed and the test fails with ``report`` of
    the group.

    Each rank leads its own process group, so a rank's children (a
    launcher's workers) die with it; whatever way this returns, no
    process it started is left running."""
    assert deadline_s <= MAX_GROUP_S, deadline_s
    procs, files = [], []
    expired = False
    try:
        for rank, env in enumerate(envs):
            # files, not pipes: a rank that fills a pipe nobody reads
            # yet blocks, and its peers wait on it
            out = tempfile.TemporaryFile("w+")
            err = tempfile.TemporaryFile("w+")
            files.append((out, err))
            procs.append(subprocess.Popen(
                argv(rank) if callable(argv) else argv, env=env,
                cwd=cwd, stdout=out, stderr=err,
                text=True, start_new_session=True))
        end = time.monotonic() + deadline_s
        while not all(_exited(p) for p in procs):
            if time.monotonic() > end:
                expired = True
                break
            time.sleep(0.05)
    finally:
        # every leader is still unreaped here, so no other process can
        # hold its pid, and so its group id, yet
        for p in procs:
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            p.wait()
    results = []
    for rank, (p, (out, err)) in enumerate(zip(procs, files)):
        out.seek(0)
        err.seek(0)
        results.append((rank, p.returncode, out.read(), err.read()))
        out.close()
        err.close()
    if expired:
        pytest.fail("rank group passed its %s s deadline; every rank "
                    "killed\n%s" % (deadline_s, report(results)),
                    pytrace=False)
    return results
