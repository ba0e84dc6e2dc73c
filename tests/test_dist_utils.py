"""The test harness itself: ``dist_utils.run_ranks`` (one deadline for
a group of rank processes, none left behind) and the per-test limit in
``conftest.py``."""
from __future__ import annotations

import os
import re
import subprocess
import sys
import textwrap
import time

import pytest

from dist_utils import REPO, rank_env, run_ranks

# rank 1 starts a child of its own and then hangs; rank 0 exits 0
_HUNG_RANK = textwrap.dedent("""
    import os, subprocess, sys, time
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    print("pid", os.getpid(), flush=True)
    if rank == 1:
        kid = subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(600)"])
        print("pid", kid.pid, flush=True)
        time.sleep(600)
    print("rank %d leaves" % rank, file=sys.stderr)
""")


def _running(pid, wait_s=2.0):
    """True if ``pid`` still runs after ``wait_s``: a SIGKILL lands
    asynchronously, and a zombie awaiting its reaper does not run."""
    end = time.monotonic() + wait_s
    while time.monotonic() < end:
        try:
            os.kill(pid, 0)
            with open("/proc/%d/stat" % pid) as f:
                state = f.read().rsplit(")", 1)[1].split()[0]
        except (ProcessLookupError, FileNotFoundError):
            return False
        if state == "Z":
            return False
        time.sleep(0.05)
    return True


def test_group_returns_outputs_in_rank_order():
    # the later rank finishes first: order follows the rank, not the exit
    script = ("import os, time; r = int(os.environ['PADDLE_TRAINER_ID']); "
              "time.sleep(0.3 * (2 - r)); print('rank', r)")
    res = run_ranks([sys.executable, "-c", script],
                    [rank_env(r, 3, 0) for r in range(3)], 30)
    assert [(rank, rc, out) for rank, rc, out, _ in res] == [
        (r, 0, "rank %d\n" % r) for r in range(3)]


class TestHungGroup:
    DEADLINE_S = 3

    @pytest.fixture(scope="class")
    def hung(self):
        t0 = time.monotonic()
        with pytest.raises(pytest.fail.Exception) as ei:
            run_ranks([sys.executable, "-c", _HUNG_RANK],
                      [rank_env(r, 2, 0) for r in range(2)],
                      self.DEADLINE_S)
        return time.monotonic() - t0, str(ei.value)

    def test_fails_at_the_group_deadline(self, hung):
        elapsed, _ = hung
        assert self.DEADLINE_S <= elapsed < self.DEADLINE_S + 5, elapsed

    def test_no_process_outlives_the_group(self, hung):
        _, msg = hung
        pids = [int(p) for p in re.findall(r"^pid (\d+)$", msg, re.M)]
        assert len(pids) == 3, msg      # both ranks and rank 1's child
        assert not [p for p in pids if _running(p)], msg

    def test_message_names_every_rank(self, hung):
        _, msg = hung
        assert "passed its 3 s deadline" in msg
        assert "rank 0 rc=0" in msg and "rank 0 leaves" in msg
        assert "rank 1 rc=-9" in msg
        assert "rank 1 leaves" not in msg


# in this process, and in an xdist worker as the suite's own command runs
@pytest.mark.parametrize("workers", [[], ["-n", "1"]])
def test_phase_past_the_limit_fails_with_its_stack(tmp_path, workers):
    (tmp_path / "test_sleeper.py").write_text(textwrap.dedent("""
        import time

        import conftest

        conftest.TEST_LIMIT_S = 2


        def test_sleeps():
            time.sleep(60)


        def test_runs_after():
            pass
    """))
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "tests")
               + os.pathsep + REPO)
    t0 = time.monotonic()
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "test_sleeper.py", "-p",
         "conftest", "-p", "no:cacheprovider", "-q"] + workers,
        cwd=tmp_path,
        env=env, capture_output=True, text=True, timeout=60)
    assert time.monotonic() - t0 < 15, r.stdout
    assert r.returncode == 1, r.stdout + r.stderr
    assert "1 failed, 1 passed" in r.stdout, r.stdout
    assert "ran past the 2 s limit" in r.stdout, r.stdout
    assert 'test_sleeper.py", line 10 in test_sleeps' in r.stdout, r.stdout
