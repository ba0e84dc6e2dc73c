"""Serving-fleet launcher: host the router, or be one replica.

Two modes over paddle_tpu/serving/fleet (FLAGS_serving_fleet is set
here — launchers own flag setup, the library refuses without it):

Router mode (default): joins the fleet TCPStore, watches the replica
announcements (``__sfleet/replica/{r}``), and serves the client API on
its own MetricsServer —

    POST /sfleet/submit          {prompt, max_new_tokens, ...} -> {nonce}
    GET  /sfleet/status/{nonce}  request progress / tokens when finished
    GET  /debugz/router          replica + affinity + request counters
    GET  /debugz/router/replicas per-replica table

Replica mode (``--replica``): the worker process a multi-host launcher
runs one-per-host (one process per chip — two replicas cannot share a
TPU host yet). Builds the preset model
+ ``serving.Engine``, wraps it in ``fleet.Replica`` — which announces
the endpoint in the store, heartbeats the liveness lease, and serves
the enqueue/result/load protocol until SIGTERM (handled as a graceful
deregister) or SIGKILL (the crash the router's TTL eviction exists
for).

Usage:
  python tools/serving_router.py --store 127.0.0.1:6170 --world 2
  python tools/serving_router.py --replica --rank 0 \
      --store 127.0.0.1:6170 --preset tiny
  # storeless router over fixed endpoints (no fleet store):
  python tools/serving_router.py --endpoints http://h1:9100,http://h2:9100
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))

PRESETS = {
    # geometry only: the weights are random
    "tiny": dict(hidden_size=64, intermediate_size=128,
                 num_hidden_layers=2, num_attention_heads=4,
                 vocab_size=256, max_position_embeddings=256),
    "llama1b": dict(hidden_size=2048, intermediate_size=5504,
                    num_hidden_layers=22, num_attention_heads=16,
                    vocab_size=32000, max_position_embeddings=2048),
}


def _store_from(spec, timeout_s=10.0):
    from paddle_tpu.distributed.store import TCPStore

    host, _, port = spec.partition(":")
    return TCPStore(host or "127.0.0.1", int(port), is_master=False,
                    timeout_s=timeout_s)


def run_replica(args):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.core import compile_cache
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving.fleet import Replica

    # One process per chip: this replica takes every chip JAX shows it
    # and serves from device 0. A second replica on the same TPU host
    # cannot start until something pins each to its own chip (ROADMAP
    # D5/R2).
    compile_cache.configure()
    sys.stderr.write("replica %d: device 0 of %d %s device(s)\n"
                     % (args.rank, len(jax.devices()),
                        jax.devices()[0].platform))
    paddle.seed(args.seed)
    cfg = LlamaConfig(use_parallel=False, **PRESETS[args.preset])
    model = LlamaForCausalLM(cfg)
    eng = serving.Engine(model, max_slots=args.max_slots,
                         num_blocks=args.num_blocks,
                         block_size=args.block_size)
    eng.max_queue = args.max_queue
    store = _store_from(args.store) if args.store else None
    rep = Replica(eng, args.rank, store=store, port=args.port,
                  ttl_s=args.ttl_s,
                  heartbeat_interval_s=args.heartbeat_s,
                  meta={"preset": args.preset, "pid": os.getpid()})
    stop = {"sig": None}

    def _term(signum, frame):
        stop["sig"] = signum

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)
    rep.start()
    # announce on stdout for a forking parent: one JSON line, then
    # serve until a signal lands
    print(json.dumps({"rank": rep.rank, "url": rep.url,
                      "generation": rep.generation,
                      "pid": os.getpid()}), flush=True)
    while stop["sig"] is None:
        time.sleep(0.1)
    rep.stop(deregister=True)
    return 0


def run_router(args):
    from paddle_tpu.monitor.exporter import MetricsServer
    from paddle_tpu.serving.fleet import Router

    endpoints = None
    store = None
    if args.endpoints:
        endpoints = {}
        for i, spec in enumerate(
                args.endpoints.replace(",", " ").split()):
            if "=" in spec and not spec.startswith("http"):
                r, _, u = spec.partition("=")
                endpoints[int(r)] = u
            else:
                endpoints[i] = spec
    elif args.store:
        if not args.world:
            sys.exit("--store needs --world N")
        store = _store_from(args.store)
    else:
        sys.exit("need --store or --endpoints (see --help)")
    router = Router(store=store, world_size=args.world,
                    endpoints=endpoints, block_size=args.block_size,
                    ttl_s=args.ttl_s, http_timeout_s=args.http_timeout)
    srv = MetricsServer(args.port)
    router.install_routes(srv)
    srv.start()
    router.start(interval_s=args.interval)
    stop = {"sig": None}

    def _term(signum, frame):
        stop["sig"] = signum

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)
    print(json.dumps({"router": "http://127.0.0.1:%d" % srv.port,
                      "pid": os.getpid()}), flush=True)
    while stop["sig"] is None:
        time.sleep(0.2)
    router.close()
    srv.stop()
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="serving-fleet router / replica launcher")
    ap.add_argument("--replica", action="store_true",
                    help="run ONE engine replica instead of the router")
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--store", help="fleet TCPStore HOST:PORT")
    ap.add_argument("--world", type=int, default=0,
                    help="router: expected replica count")
    ap.add_argument("--endpoints",
                    help="router: fixed replica URLs (storeless mode), "
                         "comma/space list, or R=URL pairs")
    ap.add_argument("--port", type=int, default=0,
                    help="HTTP port (0 = ephemeral, printed on stdout)")
    ap.add_argument("--preset", choices=sorted(PRESETS), default="tiny")
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--num-blocks", type=int, default=128)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--max-queue", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ttl-s", type=float, default=3.0)
    ap.add_argument("--heartbeat-s", type=float, default=0.5)
    ap.add_argument("--interval", type=float, default=0.05,
                    help="router pump interval")
    ap.add_argument("--http-timeout", type=float, default=2.0)
    args = ap.parse_args(argv)

    from paddle_tpu.core import flags as ptflags

    ptflags.set_flags({"FLAGS_serving_fleet": True})
    if args.replica:
        return run_replica(args)
    return run_router(args)


if __name__ == "__main__":
    sys.exit(main())
