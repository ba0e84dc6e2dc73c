"""Fleet telemetry check: the multi-proc train entry under the fleet
collector, emitting ``tools/fleet_snapshot.json``.

Drives the EXISTING 2-process multihost train entry
(tests/multihost_worker.py — the same worker test_multihost.py golden-
pins) with ``FLAGS_monitor_fleet=1`` so each rank announces its
metrics endpoint in the TCPStore, while THIS process runs the fleet
collector standalone (a store client, no rank) — the "collector on any
rank or standalone" deployment — and writes the per-rank table +
aggregates as the artifact. The two ranks are CPU processes (a chip
belongs to one process). If the multi-proc run fails or nothing was
scrapeable, nothing is written and the exit code is 3.

    python tools/fleet_battery.py [--steps 40] [--out tools/fleet_snapshot.json]
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

from paddle_tpu.monitor import fleet  # noqa: E402

# the consecutive-port reservation the multihost tests use (the store's
# +1 JAX-coordinator slot and the +10/+11 endpoint slots derive from
# the base) — ONE copy, in the dist test utils
from dist_utils import free_ports  # noqa: E402


def worker_env(rank, port):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH",
                        "PADDLE_MASTER", "PADDLE_TRAINERS_NUM",
                        "PADDLE_TRAINER_ID", "PADDLE_NNODES",
                        "PADDLE_NODE_RANK")}
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PADDLE_NNODES": "2",
        "PADDLE_NODE_RANK": str(rank),
        "PADDLE_TRAINERS_NUM": "2",
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_MASTER": "127.0.0.1:%d" % port,
        "PADDLE_CURRENT_ENDPOINT": "127.0.0.1:%d" % (port + 10 + rank),
        "FLAGS_monitor_fleet": "1",
        # the collector runs HERE (standalone store client), not on a
        # rank: -1 matches no trainer id
        "PT_FLEET_COLLECTOR_RANK": "-1",
    })
    return env


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="multi-proc train entry under the fleet collector")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--out", default=os.path.join(
        REPO, "tools", "fleet_snapshot.json"))
    ap.add_argument("--interval", type=float, default=0.5)
    ap.add_argument("--timeout", type=float, default=600.0)
    args = ap.parse_args(argv)

    port = free_ports(12)
    worker = os.path.join(REPO, "tests", "multihost_worker.py")
    procs = [subprocess.Popen(
        [sys.executable, worker, str(args.steps)], cwd=REPO,
        env=worker_env(rank, port), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True) for rank in range(2)]

    collector = None
    failure = None
    try:
        # dial the rank-0 worker's store once it is up (the workers are
        # busy importing jax for a while — keep retrying quietly)
        from paddle_tpu.distributed.store import TCPStore

        store = None
        deadline = time.monotonic() + min(args.timeout / 2, 240)
        while store is None and time.monotonic() < deadline:
            if procs[0].poll() is not None:
                break
            try:
                store = TCPStore("127.0.0.1", port, is_master=False,
                                 timeout_s=10)
            except RuntimeError:
                time.sleep(1.0)
        if store is None:
            failure = "store never came up (worker died early?)"
        else:
            collector = fleet.FleetCollector(
                store=store, world_size=2, interval_s=args.interval,
                http_timeout_s=5.0).start()
        rcs = []
        for p in procs:
            try:
                out, err = p.communicate(timeout=args.timeout)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                out, err = p.communicate()
            rcs.append(p.returncode)
            sys.stderr.write(err[-2000:] + "\n" if rcs[-1] else "")
        if any(rc != 0 for rc in rcs):
            failure = "multi-proc train entry failed (rcs=%s)" % rcs
    finally:
        if collector is not None:
            collector.stop()
    if failure is not None:
        sys.stderr.write("fleet_battery: %s; nothing written\n" % failure)
        return 3
    snap = fleet.write_snapshot_artifact(args.out, collector=collector)
    if not snap.get("ok"):
        sys.stderr.write("fleet_battery: %s; nothing written\n"
                         % (snap.get("error") or "nothing scraped"))
        return 3
    print("fleet_battery: ok -> %s (ranks=%s steps=%s)"
          % (args.out,
             [r.get("rank") for r in snap.get("ranks") or ()],
             [r.get("steps_total") for r in snap.get("ranks") or ()]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
