"""Eager-collective wire benchmark: fp32 vs block-scaled int8.

The measurement companion of ``paddle_tpu.distributed.compress``: forks
a small multi-process world (rendezvous over the native TCP store, the
same transport multi-host eager sync rides), sweeps payload sizes, and
times ``all_reduce`` with the uncompressed fp32 wire format against the
quantized int8+scales format — reporting seconds/op, actual wire bytes
per op (from the ``comm_bytes_total`` registry counters, the same
series the acceptance gate asserts on), compression ratio, and max
relative error of the compressed reduction. One JSON row per (size,
format).

Backend note: the store transport is host-side TCP — numbers are
transport numbers and mean the same thing whatever the JAX backend.

Usage:
  python tools/comm_benchmark.py                      # CPU smoke sweep
  python tools/comm_benchmark.py --sizes 65536 1048576 --iters 5 \
      --out tools/comm_bench.json
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def worker_main(args):
    import numpy as np

    import paddle_tpu.distributed as dist
    from paddle_tpu import monitor
    from paddle_tpu.distributed import compress

    dist.init_parallel_env()
    pg = dist.collective._get_default_group().pg
    rank, world = pg.rank, pg.world_size
    rng = np.random.RandomState(1234 + rank)
    rows = []
    for numel in args.sizes:
        # wide dynamic range (what block scaling exists for), f32 wire
        payload = (rng.randn(numel)
                   * np.exp(rng.randn(numel) * 2)).astype(np.float32)
        ref = None
        for compressed in (False, True):
            label = "true" if compressed else "false"
            child = compress.COMM_BYTES.labels(path="eager",
                                               compressed=label)
            pg.barrier("comm_bench/%d/%s" % (numel, label))
            # one untimed warmup settles store-key allocation paths
            pg.allreduce(payload, "sum", compressed=compressed)
            b0 = child.value
            t0 = time.perf_counter()
            for _ in range(args.iters):
                out = pg.allreduce(payload, "sum",
                                   compressed=compressed)
            dt = (time.perf_counter() - t0) / args.iters
            wire = (child.value - b0) / args.iters
            compress.GRAD_SYNC_SECONDS.labels(path="eager").observe(dt)
            if not compressed:
                ref = out
                err = 0.0
            else:
                scale = float(np.abs(ref).max()) or 1.0
                err = float(np.abs(out - ref).max()) / scale
            rows.append({
                "payload_numel": numel,
                "payload_bytes": numel * 4,
                "world_size": world,
                "compressed": compressed,
                "seconds_per_op": round(dt, 6),
                "wire_bytes_per_op": int(wire),
                "max_rel_error": round(err, 6),
            })
    # fold in per-size ratios on the compressed rows
    by_size = {}
    for r in rows:
        by_size.setdefault(r["payload_numel"], {})[r["compressed"]] = r
    for numel, pair in by_size.items():
        if True in pair and False in pair and \
                pair[True]["wire_bytes_per_op"]:
            pair[True]["compression_ratio"] = round(
                pair[False]["wire_bytes_per_op"]
                / pair[True]["wire_bytes_per_op"], 3)
            if pair[True]["seconds_per_op"]:
                pair[True]["speedup"] = round(
                    pair[False]["seconds_per_op"]
                    / pair[True]["seconds_per_op"], 3)
    if rank == 0:
        print("COMM_RESULT " + json.dumps(rows))
    sys.stdout.flush()


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nranks", type=int, default=2)
    ap.add_argument("--sizes", type=int, nargs="+",
                    default=[1 << 14, 1 << 16, 1 << 18])
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--out")
    ap.add_argument("--worker", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.worker:
        return worker_main(args)

    port = _free_port()
    procs = []
    for rank in range(args.nranks):
        env = dict(os.environ)
        env.update({
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
            "JAX_PLATFORMS": "cpu",
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(args.nranks),
            "PADDLE_MASTER": "127.0.0.1:%d" % port,
        })
        cmd = [sys.executable, os.path.abspath(__file__), "--worker",
               "--nranks", str(args.nranks),
               "--iters", str(args.iters),
               "--sizes"] + [str(s) for s in args.sizes]
        procs.append(subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))
    rows = None
    for rank, p in enumerate(procs):
        out, err = p.communicate(timeout=600)
        if p.returncode != 0:
            sys.stderr.write(
                "comm_benchmark rank %d failed (rc=%d):\n%s\n%s\n"
                % (rank, p.returncode, out[-2000:], err[-3000:]))
            return 1
        for line in out.splitlines():
            if line.startswith("COMM_RESULT "):
                rows = json.loads(line[len("COMM_RESULT "):])
    if rows is None:
        sys.stderr.write("comm_benchmark: no result row from rank 0\n")
        return 1
    result = {
        "benchmark": "eager_allreduce_wire",
        "nranks": args.nranks,
        "iters": args.iters,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                     time.gmtime()),
        "rows": rows,
    }
    for r in rows:
        print(json.dumps(r))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1, sort_keys=True)
        print("wrote", args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
