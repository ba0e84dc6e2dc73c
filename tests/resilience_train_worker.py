"""Worker for the multi-process training chaos acceptance test.

Every rank trains the SAME deterministic model (replicated
data-parallel style: identical seeds, identical batches — the per-step
cross-rank loss all-reduce is therefore an identity, which is what
lets the test pin the trajectory) through a ResilientTrainLoop:
CompiledTrainStep.run_steps windows, periodic snapshots, an
ElasticManager heartbeat over the shared TCPStore, and a
StoreProcessGroup all-reduce after every window.

Rank ``DIE_RANK`` hard-kills itself (os._exit) in window
``DIE_AT_WINDOW``: building that window's batch never returns, so the
death lands in that window on every run, however fast the machine
finishes the windows after it. The survivors' next all-reduce times
out waiting for the dead rank's frame (flight-recorder postmortem and
all); the recovery funnel confirms the death through the elastic
verdict, rebuilds membership over the store under a new generation
(leader publishes members + the min common snapshot step;
generation-suffixed barrier), resumes from the snapshot, and finishes
all TOTAL_STEPS. Rank 0 then re-runs the whole schedule uninterrupted
on a fresh model and asserts the recovered trajectory is IDENTICAL —
prints TRAJECTORY_MATCH.

Runs under PT_WATCHDOG=1: the incident must leave diagnostics, not
stalls — survivors exit 0 with a clean (never-503) healthz.

Spawned by tests/test_resilience.py with PADDLE_TRAINER_ID /
PADDLE_TRAINERS_NUM / PADDLE_MASTER / SNAP_DIR / DIE_* set.
"""
from __future__ import annotations

import json
import os
import sys

K = 2               # steps per run_steps window
BATCH = 8           # divisible by any inherited virtual-device mesh
FEATS = 8
CLASSES = 4


def make_step():
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.optimizer.optimizers import Adam
    from paddle_tpu.parallel.engine import CompiledTrainStep

    paddle.seed(1234)
    model = nn.Sequential(nn.Linear(FEATS, 16), nn.ReLU(),
                          nn.Dropout(0.1), nn.Linear(16, CLASSES))
    opt = Adam(learning_rate=1e-2, parameters=model.parameters())
    return CompiledTrainStep(model, nn.CrossEntropyLoss(), opt)


def make_batch_fn(die_window=None):
    import numpy as np

    def batch_fn(step_i):
        window = (step_i - 1) // K
        if window == die_window:
            os._exit(17)
        rng = np.random.RandomState(5000 + window)
        x = rng.randn(K, BATCH, FEATS).astype(np.float32)
        y = rng.randint(0, CLASSES, (K, BATCH)).astype(np.int64)
        return x, y

    return batch_fn


def main():
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    world = int(os.environ["PADDLE_TRAINERS_NUM"])
    host, _, port = os.environ["PADDLE_MASTER"].partition(":")
    die_rank = int(os.environ.get("DIE_RANK", "-1"))
    die_window = int(os.environ.get("DIE_AT_WINDOW", "3"))
    total_steps = int(os.environ.get("TOTAL_STEPS", "12"))
    snap_dir = os.path.join(os.environ["SNAP_DIR"], "rank%d" % rank)

    import numpy as np

    from paddle_tpu.distributed.elastic import ElasticManager
    from paddle_tpu.distributed.process_group import (
        StoreProcessGroup,
        set_world_group,
    )
    from paddle_tpu.distributed.store import TCPStore
    from paddle_tpu.resilience.train import ResilientTrainLoop

    # short store timeout: a dead peer's missing all-reduce frame must
    # become a TimeoutError (the detect signal) in seconds, not minutes
    store = TCPStore(host or "127.0.0.1", int(port),
                     is_master=(rank == 0), timeout_s=8)
    store.barrier("boot", world, timeout_s=120)
    pg_holder = {"pg": StoreProcessGroup(store, rank, world)}
    set_world_group(pg_holder["pg"])

    elastic = ElasticManager(store=store, job_id="chaos", rank=rank,
                             np=world, heartbeat_interval=0.3, ttl=1.5)
    elastic.register()

    step = make_step()

    def post_step(step_i, loss):
        # the all-reduce IS the fast death-detection signal (a dead
        # peer's missing frame raises TimeoutError into the recovery
        # funnel) — but the RECORDED loss stays the local one: avg of
        # world identical fp32 values can round one ulp ((3a)/3 != a),
        # and the pinned-trajectory contract is bit-identity
        out = pg_holder["pg"].allreduce(
            np.asarray([loss], np.float32), op="avg")
        assert abs(float(out[0]) - loss) < 1e-5 * max(abs(loss), 1.0)
        return loss

    def on_generation(gen, members, info):
        # ranks renumber 0..n-1 inside the group; original ids persist
        # everywhere else (beat keys, snapshot dirs)
        new_rank = members.index(rank)
        pg_holder["pg"] = StoreProcessGroup(
            store, new_rank, len(members), prefix="pg/gen%d" % gen)
        set_world_group(pg_holder["pg"])
        print("REBUILT gen=%d members=%s new_rank=%d resume=%s"
              % (gen, members, new_rank, info.get("resume_step")),
              flush=True)

    loop = ResilientTrainLoop(
        step, make_batch_fn(die_window if rank == die_rank else None),
        snap_dir,
        elastic=elastic, snapshot_every=2 * K, keep=3,
        post_step=post_step, on_generation=on_generation,
        store_timeout_s=30, steps_per_call=K)
    losses = loop.run(total_steps)
    loop.close()
    elastic.exit()

    print("CHAOS_DONE rank=%d recoveries=%s losses=%s"
          % (rank, loop.recovery_log,
             json.dumps({str(k): round(v, 8)
                         for k, v in sorted(losses.items())})),
          flush=True)
    assert loop.recovery_log, "no recovery happened — test proved nothing"
    assert any(k == "rank_death" for k, _ in loop.recovery_log), \
        loop.recovery_log

    if rank == min(elastic.members):
        # pin the trajectory: a fresh uninterrupted run of the same
        # schedule (no elastic, no collectives — the all-reduce of
        # identical losses is an identity) must match bit-for-bit
        ref_step = make_step()
        ref_loop = ResilientTrainLoop(
            ref_step, make_batch_fn(), snap_dir + "_ref",
            steps_per_call=K)
        ref = ref_loop.run(total_steps)
        ref_loop.close()
        mismatch = {k: (losses.get(k), ref[k]) for k in ref
                    if abs(ref[k] - losses.get(k, float("nan"))) > 1e-12}
        assert not mismatch, "trajectory diverged: %s" % mismatch
        print("TRAJECTORY_MATCH rank=%d" % rank, flush=True)
    print("CHAOS_OK rank=%d" % rank, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
