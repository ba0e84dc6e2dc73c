"""Serving-engine benchmark: continuous batching under Poisson traffic.

Synthetic open-loop workload (the serving analog of bench.py's training
headline): requests arrive by a seeded Poisson process with random
prompt/output lengths and stream through ``serving.Engine`` —
continuous batching, paged KV blocks, preemption under pool pressure.
Reports engine throughput (tok/s), TTFT/TPOT p50/p99, queue time,
preemption count and the compile-once counters to a JSON artifact.

Backend note (same discipline as tools/model_benchmark.py): runs on
whatever backend jax resolves — the chip for recorded numbers, CPU for
plumbing checks — and every row names its platform, device_kind and
device count. CPU numbers are throughput of the jnp reference path and
are never recorded as device numbers. One engine uses ONE device;
``--fleet N`` forks N processes and is refused on a TPU backend (one
process per chip).

Usage:
  python tools/serving_benchmark.py                  # tiny CPU smoke
  python tools/serving_benchmark.py --preset llama1b # on-chip row
  python tools/serving_benchmark.py --requests 64 --rate 8 \
      --out tools/serving_bench.json
  # resilience row: injected faults + queue bounds + deadlines —
  # reports shed/expired/failed counts and goodput under chaos
  python tools/serving_benchmark.py --fault-rate 0.1 --max-queue 16 \
      --deadline-s 10
  # fleet row (ISSUE 16): N forked engine replicas + the in-process
  # prefix-affinity router; phase A is the no-kill baseline, phase B
  # SIGKILLs one replica mid-run — zero accepted requests may be
  # lost, kill-phase p99 TTFT must stay within 2x of baseline, and
  # every survivor must still report decode_compiles == 1. Fleet runs
  # also trace end-to-end (ISSUE 17): the router journals its
  # queue/placement/dispatch/reroute spans, each replica adopts the
  # dispatch traceparent, and the merged clock-aligned timeline lands
  # in --fleet-trace-out; requests_detail rows carry trace_id plus the
  # per-hop breakdown (router queue vs dispatch attempts vs replica
  # phases)
  python tools/serving_benchmark.py --fleet 3 --kill-replica-at 4 \
      --shared-prefix-tokens 32 --out tools/serving_fleet_snapshot.json
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

PRESETS = {
    # geometry-only: weights are random (throughput, not quality)
    "tiny": dict(hidden_size=64, intermediate_size=128,
                 num_hidden_layers=2, num_attention_heads=4,
                 vocab_size=256, max_position_embeddings=256),
    "llama1b": dict(hidden_size=2048, intermediate_size=5504,
                    num_hidden_layers=22, num_attention_heads=16,
                    vocab_size=32000, max_position_embeddings=2048),
}


def _watchdog(seconds):
    def fire(signum, frame):
        sys.stderr.write("serving_benchmark watchdog: %ds, aborting\n"
                         % seconds)
        os._exit(3)

    signal.signal(signal.SIGALRM, fire)
    signal.alarm(seconds)


def _pow2_bucket(n):
    """Engine._bucket without the engine: next power of two >= 8. The
    fleet parent pre-warms every bucket its workload can hit on every
    replica so phase TTFTs never pay an in-window prefill compile."""
    p = 8
    while p < n:
        p *= 2
    return p


def _pct(values, q):
    import numpy as np

    return float(np.percentile(np.asarray(values, dtype=float), q)) \
        if values else None


def _pcts(values):
    """Aggregate percentile row (p50/p90/p99) for the JSON artifact."""
    return {"p50": _pct(values, 50), "p90": _pct(values, 90),
            "p99": _pct(values, 99)}


def _write_artifact(path, report):
    """Atomic JSON write. A run that produced nothing writes nothing and
    exits non-zero; there is no previous artifact to re-emit."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(report, f, indent=1, default=str)
        f.write("\n")
    os.replace(tmp, path)
    return report


def _device_fields():
    """Where the numbers in a row came from, as JAX reports it."""
    import jax

    from paddle_tpu.monitor import perf

    return dict(perf.device_fields(), backend=jax.default_backend())


def run_fleet(args):
    """--fleet N: fork N replica processes (tools/serving_router.py
    --replica), drive them through the in-process store-backed router,
    and measure the fleet headline: baseline (phase A) vs kill-one-
    replica-mid-run (phase B) TTFT, rerouted/lost counts, per-replica
    affinity hit rate, survivor decode_compiles."""
    import subprocess
    import urllib.request

    import numpy as np

    from paddle_tpu.core import flags as ptflags
    from paddle_tpu.distributed import refuse_multiprocess_on_tpu
    from paddle_tpu.distributed.store import TCPStore
    from paddle_tpu.monitor import trace as mtrace
    from paddle_tpu.monitor import trace_merge as tm
    from paddle_tpu.serving.fleet import Router

    # one process per chip: every replica child initialises its own
    # backend and nothing assigns it a chip (ROADMAP D5/R2)
    refuse_multiprocess_on_tpu("serving_benchmark --fleet %d" % args.fleet)
    ptflags.set_flags({"FLAGS_serving_fleet": True})
    # fleet-wide tracing (on by default, the single-engine benchmark
    # discipline): the ROUTER journal records the dispatch half here;
    # each forked replica journals its engine half via the
    # FLAGS_monitor_trace env bootstrap, and the two merge into
    # tools/fleet_trace.json after the phases. Capacity covers both
    # phases plus warmups so early traces never get evicted.
    trace_cap = max(4 * args.requests + 128, 512)
    if not args.no_trace:
        mtrace.enable(capacity=trace_cap)

    def post_json(url, payload):
        req = urllib.request.Request(
            url, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
            method="POST")
        with urllib.request.urlopen(req, timeout=10) as r:
            return json.loads(r.read().decode())

    def get_json(url):
        with urllib.request.urlopen(url, timeout=10) as r:
            return json.loads(r.read().decode())

    def clock_offset(url, pings=5):
        """Replica wall clock minus local wall clock, NTP-style over
        /metrics.json (the monitor/fleet.py collector discipline:
        self-reported unix_time vs the local request midpoint, min-RTT
        sample wins) — the shift that clock-aligns the merged fleet
        timeline."""
        best_rtt, best_off = None, 0.0
        for _ in range(pings):
            t0 = time.time()    # ptlint: clock-ok — NTP offset probe
            m0 = time.monotonic()
            snap = get_json(url + "/metrics.json")
            t1 = time.time()    # ptlint: clock-ok — NTP offset probe
            rtt = time.monotonic() - m0
            if not isinstance(snap.get("unix_time"), (int, float)):
                return None
            if best_rtt is None or rtt < best_rtt:
                best_rtt = rtt
                best_off = float(snap["unix_time"]) - (t0 + t1) / 2.0
        return best_off

    launcher = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "serving_router.py")
    master = TCPStore(is_master=True)
    procs, announce, router = [], {}, None
    spt = args.shared_prefix_tokens
    vocab = PRESETS[args.preset]["vocab_size"]
    max_pos = PRESETS[args.preset]["max_position_embeddings"]
    rng = np.random.RandomState(args.seed)
    prefixes = [rng.randint(0, vocab, (spt,)).tolist()
                for _ in range(args.prefix_groups)] if spt else None

    def mk_workload():
        prompts = []
        for _ in range(args.requests):
            tail = rng.randint(
                0, vocab,
                (int(rng.randint(args.prompt_len[0],
                                 args.prompt_len[1] + 1)),)).tolist()
            head = prefixes[int(rng.randint(args.prefix_groups))] \
                if prefixes else []
            prompts.append(head + tail)
        new = [int(rng.randint(args.max_new[0], args.max_new[1] + 1))
               for _ in range(args.requests)]
        arrivals = np.cumsum(rng.exponential(1.0 / args.rate,
                                             args.requests))
        return prompts, new, arrivals

    def run_phase(name, kill_at=None):
        prompts, new, arrivals = mk_workload()
        nonces, killed = [], None
        start = time.perf_counter()
        nxt = 0
        while nxt < len(prompts) or (kill_at is not None
                                     and killed is None):
            now = time.perf_counter() - start
            if kill_at is not None and killed is None \
                    and now >= kill_at:
                # the victim is the live replica holding the most
                # unfinished work — the worst case for the
                # never-lose-a-request claim
                holding = {}
                for rq in router.requests():
                    if rq["state"] not in ("finished", "failed") \
                            and rq["rank"] is not None:
                        holding[rq["rank"]] = \
                            holding.get(rq["rank"], 0) + 1
                live = [r["rank"] for r in
                        router.replicas_debug_payload()
                        if r["state"] == "live"]
                killed = max(live,
                             key=lambda r: (holding.get(r, 0), -r)) \
                    if live else None
                if killed is not None:
                    procs[killed].kill()        # SIGKILL: no goodbye
            while nxt < len(prompts) and arrivals[nxt] <= now:
                nonces.append(router.submit(
                    prompts[nxt], max_new_tokens=new[nxt]))
                nxt += 1
            router.pump()
            time.sleep(0.002)
        settled = router.wait_all(timeout_s=args.fleet_wait_s)
        wall = time.perf_counter() - start
        reqs = [router.request(n) for n in nonces]
        ttft = [r["first_token_at"] - r["submitted_at"] for r in reqs
                if r["first_token_at"] is not None]
        lost = [r["nonce"] for r in reqs if r["state"] != "finished"]
        # per-request rows with the per-hop breakdown: router queue
        # (trace phase) vs dispatch attempts (every replica tried,
        # with outcome — a rerouted request reports BOTH attempts'
        # replicas) vs replica engine phases (from the result
        # payload's span summary). trace_id links each row to the
        # merged fleet timeline.
        detail = []
        for r in reqs:
            row = {
                "nonce": r["nonce"], "state": r["state"],
                "rank": r["rank"], "reroutes": r["reroutes"],
                "reroute_reasons": list(r["reroute_reasons"]),
                "attempt_ranks": list(r["attempt_ranks"]),
                "affinity": bool(r["affinity"]),
                "output_tokens": r["output_tokens"],
                "ttft_s": (round(r["first_token_at"]
                                 - r["submitted_at"], 6)
                           if r["first_token_at"] is not None
                           else None),
                "e2e_s": (round(r["finished_at"]
                                - r["submitted_at"], 6)
                          if r["finished_at"] is not None else None),
                "trace_id": r["trace_id"],
            }
            if r["trace_id"] is not None:
                pb = mtrace.phase_breakdown(r["trace_id"]) or {}
                row["hops"] = {
                    "router_queue_s": round(
                        pb.get("router_queue", 0.0), 6),
                    "dispatch_attempts": [dict(a)
                                          for a in r["attempts"]],
                    "replica_phases_s": (r["replica_trace"] or {}
                                         ).get("phases_s"),
                }
            detail.append(row)
        return {
            "phase": name, "requests": len(reqs),
            "settled": bool(settled), "wall_s": round(wall, 3),
            "ttft_s": _pcts(ttft),
            "finished": sum(r["state"] == "finished" for r in reqs),
            "lost": lost,
            "rerouted": sum(r["reroutes"] for r in reqs),
            "affinity_dispatches": sum(bool(r["affinity"])
                                       for r in reqs),
            "output_tokens": sum(r["output_tokens"] for r in reqs),
            "killed_rank": killed,
            "requests_detail": detail,
        }

    out = args.out
    try:
        for r in range(args.fleet):
            procs.append(subprocess.Popen(
                [sys.executable, launcher, "--replica",
                 "--rank", str(r),
                 "--store", "127.0.0.1:%d" % master.port,
                 "--preset", args.preset,
                 "--max-slots", str(args.max_slots),
                 "--num-blocks", str(args.num_blocks),
                 "--block-size", str(args.block_size),
                 "--seed", str(args.seed + r),
                 "--ttl-s", str(args.fleet_ttl_s),
                 "--heartbeat-s", "0.2"],
                stdout=subprocess.PIPE,
                # journal in the replica too (the trace.py env
                # bootstrap): its engine-half spans adopt the router's
                # traceparent and are pulled via /debugz/trace/journal
                # after the phases
                env=(dict(os.environ, FLAGS_monitor_trace="1",
                          PT_TRACE_CAPACITY=str(trace_cap))
                     if not args.no_trace else None)))
        for r, p in enumerate(procs):
            # one JSON line after Replica.start(): engine built, lease
            # registered, protocol served
            announce[r] = json.loads(p.stdout.readline().decode())
            print("replica %d up: %s" % (r, announce[r]["url"]),
                  flush=True)

        # per-replica compile warmup, straight to each replica's
        # enqueue endpoint (bypassing placement): every prefill bucket
        # the workload can hit + THE decode step, per replica, so
        # neither phase pays an in-window compile
        t0 = time.perf_counter()
        lo = args.prompt_len[0] + spt
        hi = args.prompt_len[1] + spt + args.max_new[1] - 1
        buckets = sorted({_pow2_bucket(n) for n in range(lo, hi + 1)})
        warm = []
        for r, info in announce.items():
            for i, b in enumerate(buckets):
                nonce = "warm-%d-%d" % (r, i)
                post_json(info["url"] + "/sfleet/enqueue",
                          {"nonce": nonce,
                           "prompt": [1] * min(b, max_pos - 4),
                           "max_new_tokens": 2})
                warm.append((info["url"], nonce))
        pending = list(warm)
        while pending:
            url, nonce = pending[0]
            st = get_json("%s/sfleet/result/%s" % (url, nonce))
            if st["state"] in ("finished", "failed", "shed",
                               "expired"):
                if st["state"] != "finished":
                    raise RuntimeError("warmup %s on %s: %r"
                                       % (nonce, url, st))
                pending.pop(0)
            else:
                time.sleep(0.05)
        warmup_s = time.perf_counter() - t0

        router = Router(store=TCPStore(port=master.port),
                        world_size=args.fleet,
                        block_size=args.block_size,
                        ttl_s=args.fleet_ttl_s)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            router.refresh_membership()
            router.scrape_loads()
            if router.debug_payload()["replicas"]["live"] \
                    == args.fleet:
                break
            time.sleep(0.05)
        else:
            raise RuntimeError(
                "only %r of %d replicas came live"
                % (router.debug_payload()["replicas"], args.fleet))

        baseline = run_phase("baseline")
        kill = run_phase("kill", kill_at=args.kill_replica_at) \
            if args.kill_replica_at is not None else None

        # merged fleet timeline: the router's journal (dispatch half)
        # + every SURVIVING replica's journal (engine half, pulled over
        # /debugz/trace/journal) + NTP-style clock offsets -> ONE
        # clock-aligned chrome trace with traceparent flow arrows. A
        # SIGKILLed victim's journal dies with it, but its attempt-1
        # evidence lives in the router's dispatch/reroute spans, so the
        # reroute causality chain survives the kill.
        trace_block = {"enabled": not args.no_trace}
        if not args.no_trace:
            replica_journals, offsets_s = {}, {}
            for r, info in announce.items():
                if procs[r].poll() is not None:
                    continue        # dead replica: journal lost
                try:
                    replica_journals[r] = get_json(
                        info["url"] + "/debugz/trace/journal")
                    off = clock_offset(info["url"])
                    if off is not None:
                        offsets_s[r] = off
                except (OSError, ValueError):
                    continue        # died mid-pull: same as dead
            doc = tm.write_fleet_timeline(
                args.fleet_trace_out, mtrace.dump(), replica_journals,
                offsets=offsets_s,
                meta={"tool": "serving_benchmark", "fleet": args.fleet,
                      "preset": args.preset,
                      "kill_replica_at_s": args.kill_replica_at,
                      "measured_at": time.strftime(
                          "%Y-%m-%dT%H:%M:%SZ", time.gmtime())})
            reqs_sum = doc.get("requests") or {}
            trace_block.update({
                "fleet_trace": args.fleet_trace_out,
                "router_traces": len(reqs_sum),
                "replica_journals": sorted(replica_journals),
                "clock_offsets_s": {r: round(o, 6)
                                    for r, o in offsets_s.items()},
                "rerouted_traces": sum(
                    1 for v in reqs_sum.values() if v["reroutes"]),
            })
            print("wrote", args.fleet_trace_out, flush=True)

        dbg = router.debug_payload()
        rows = router.replicas_debug_payload()
        killed_ranks = {p["killed_rank"] for p in (baseline, kill)
                        if p and p["killed_rank"] is not None}
        survivors = {
            r["rank"]: r["decode_compiles"] for r in rows
            if r["state"] != "evicted"
            and r["rank"] not in killed_ranks}
        lost = list(baseline["lost"]) + list(kill["lost"] if kill
                                             else [])
        ratio = None
        if kill and baseline["ttft_s"]["p99"] and \
                kill["ttft_s"]["p99"] is not None:
            ratio = round(kill["ttft_s"]["p99"]
                          / baseline["ttft_s"]["p99"], 3)
        report = {
            "kind": "serving_fleet_snapshot",
            "metric": "serving_fleet_kill_ttft_p99_ratio",
            "value": ratio,
            **_device_fields(),
            "preset": args.preset,
            "fleet": args.fleet,
            "workload": {
                "requests_per_phase": args.requests,
                "poisson_rate": args.rate,
                "prompt_len": list(args.prompt_len),
                "max_new": list(args.max_new), "seed": args.seed,
                "shared_prefix_tokens": spt,
                "prefix_groups": args.prefix_groups if spt else 0,
                "max_slots": args.max_slots,
                "num_blocks": args.num_blocks,
                "block_size": args.block_size,
                "kill_replica_at_s": args.kill_replica_at,
                "ttl_s": args.fleet_ttl_s,
            },
            "warmup_compile_s": round(warmup_s, 3),
            "baseline": baseline,
            "kill": kill,
            "lost_requests": lost,
            "ttft_p99_ratio_within_2x": (ratio is not None
                                         and ratio <= 2.0),
            "survivor_decode_compiles": survivors,
            "trace": trace_block,
            "router": dbg,
            "replicas": rows,
            "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime()),
        }
        print(json.dumps({k: v for k, v in report.items()
                          if k not in ("replicas",)}), flush=True)
        _write_artifact(out, report)
        print("wrote", out, flush=True)
        if lost:
            sys.stderr.write("FAIL: %d accepted request(s) lost: %r\n"
                             % (len(lost), lost))
            return 5
        bad = {r: c for r, c in survivors.items() if c != 1}
        if bad:
            sys.stderr.write("FAIL: survivor decode_compiles != 1: "
                             "%r\n" % (bad,))
            return 4
        return 0
    except (RuntimeError, OSError, ValueError,
            json.JSONDecodeError) as e:
        sys.stderr.write("serving_benchmark --fleet failed: %r; no "
                         "artifact written\n" % (e,))
        return 3
    finally:
        if router is not None:
            router.close()
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=15)
            except subprocess.TimeoutExpired:
                p.kill()
        master.close()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=sorted(PRESETS), default="tiny")
    ap.add_argument("--requests", type=int, default=20)
    ap.add_argument("--rate", type=float, default=10.0,
                    help="Poisson arrival rate, requests/second")
    ap.add_argument("--prompt-len", type=int, nargs=2, default=(4, 24),
                    metavar=("LO", "HI"))
    ap.add_argument("--max-new", type=int, nargs=2, default=(4, 16),
                    metavar=("LO", "HI"))
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--num-blocks", type=int, default=128)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--watchdog", type=int, default=1100)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "serving_bench.json"))
    ap.add_argument("--monitor-out", default=None,
                    help="also dump the monitor registry snapshot (with "
                         "written_at metadata) to this JSON path")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="resilience chaos knob: probability of an "
                         "injected per-request prefill error (the "
                         "poison-request path); 0 = injection off")
    ap.add_argument("--fault-schedule", default=None,
                    help="raw fault schedule (resilience/faultinject "
                         "grammar, overrides --fault-rate), e.g. "
                         "'serving.prefill:error@p0.1;"
                         "serving.decode:delay=0.01@%%8'")
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request queue-TTL: still waiting past "
                         "this -> terminal 'expired' status")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded admission queue: arrivals beyond it "
                         "are load-shed (counted, not enqueued)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="enable FLAGS_serving_prefix_cache (radix "
                         "prefix cache over the paged KV pool)")
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="enable FLAGS_serving_chunked_prefill (prompts "
                         "stream through the ONE mixed step in chunks)")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="chunk size for --chunked-prefill")
    ap.add_argument("--quant-kv", action="store_true",
                    help="enable FLAGS_serving_quant_kv (int8 block-"
                         "scaled KV pages + fp32 scale planes). "
                         "--num-blocks then names the FP32 pool the "
                         "byte budget could afford; the quantized run "
                         "gets the SAME bytes, which buy more pages — "
                         "the report's kv_capacity_headroom_vs_fp32")
    ap.add_argument("--quant-weights", action="store_true",
                    help="enable FLAGS_serving_quant_weights (weight-"
                         "only int8 block-scaled projection matmuls on "
                         "decode rows; prefill rows stay fp32)")
    ap.add_argument("--shared-prefix-tokens", type=int, default=0,
                    help="system-prompt traffic shape: every request's "
                         "prompt starts with one of --prefix-groups "
                         "shared prefixes of this many tokens (0 = "
                         "fully random prompts)")
    ap.add_argument("--prefix-groups", type=int, default=4,
                    help="number of distinct shared prefixes for "
                         "--shared-prefix-tokens")
    ap.add_argument("--slo", action="store_true",
                    help="judge the workload against the serving SLOs "
                    "(FLAGS_monitor_slo, latched before Engine "
                    "construction): per-objective attainment + budget "
                    "burn + burn-rate alerts land in the report")
    ap.add_argument("--profile", action="store_true",
                    help="FLAGS_monitor_profile: host sampling profiler "
                         "+ per-iteration dispatch/gap + prefill/decode "
                         "phase timers; arms a one-shot device-capture "
                         "window mid-run and reports host_blocked_s per "
                         "phase in the JSON")
    ap.add_argument("--record-out", default=None,
                    help="FLAGS_serving_replay: journal every measured "
                         "request (prompt ids, flag snapshot, weights "
                         "generation, output token hash) to this JSONL "
                         "path; tools/ptreplay.py run re-drives it and "
                         "diffs token-for-token")
    ap.add_argument("--replay", default=None,
                    help="replay a --record-out journal instead of "
                         "generating a workload: delegates to "
                         "tools/ptreplay.py (rebuilds the recorded "
                         "model + engine, re-drives every finished "
                         "request) and writes the divergence report to "
                         "--out; rc=2 on divergence")
    ap.add_argument("--no-trace", action="store_true",
                    help="skip the span journal (requests_detail rows "
                         "then carry no trace_id/phases_s breakdown)")
    ap.add_argument("--trace-out", default=None,
                    help="also write the span journal here "
                         "(tools/trace_merge.py --requests input)")
    ap.add_argument("--fleet", type=int, default=0,
                    help="serving-fleet mode: fork this many engine "
                         "replica processes (tools/serving_router.py "
                         "--replica) and drive them through the "
                         "in-process prefix-affinity router instead "
                         "of one local engine")
    ap.add_argument("--kill-replica-at", type=float, default=None,
                    help="fleet mode: SIGKILL one replica this many "
                         "seconds into the kill phase (phase B); the "
                         "router's TTL eviction + re-dispatch must "
                         "lose nothing")
    ap.add_argument("--fleet-ttl-s", type=float, default=2.0,
                    help="fleet mode: replica liveness lease TTL")
    ap.add_argument("--fleet-trace-out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "fleet_trace.json"),
        help="fleet mode: merged clock-aligned fleet timeline "
             "(router + surviving-replica journals stitched on "
             "traceparent; open in Perfetto)")
    ap.add_argument("--fleet-wait-s", type=float, default=300.0,
                    help="fleet mode: per-phase drain deadline")
    args = ap.parse_args()
    _watchdog(args.watchdog)
    if args.replay:
        # replay mode IS ptreplay: same entrypoint for record and
        # replay so CI rows and operators drive both through one tool
        import importlib.util

        p = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "ptreplay.py")
        spec = importlib.util.spec_from_file_location("ptreplay", p)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.run_replay(argparse.Namespace(
            journal=args.replay, out=args.out, full=False,
            matrix=False, against=None))
    if args.fleet > 0:
        return run_fleet(args)
    return _run_single(args)


def _run_single(args):
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.core import compile_cache
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.monitor import trace as mtrace

    compile_cache.configure()
    # one chip means one device: serving.Engine keeps everything on the
    # device that holds the weights (device 0), whatever the host has
    print("serving_benchmark: 1 of %d %s device(s)"
          % (len(jax.devices()), jax.devices()[0].platform), flush=True)
    # span journal on by default for the benchmark (a measurement
    # tool): per-request phase attribution makes the preemption tax
    # visible per-request, not only in the aggregate counters. Capacity
    # sized to the workload so early requests never get evicted.
    if not args.no_trace:
        mtrace.enable(capacity=max(2 * args.requests + 64, 256))

    paddle.seed(args.seed)
    cfg = LlamaConfig(use_parallel=False, **PRESETS[args.preset])
    model = LlamaForCausalLM(cfg)

    rng = np.random.RandomState(args.seed)
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, args.requests))
    # shared-prefix traffic shape (--shared-prefix-tokens): the
    # millions-of-users workload — every request opens with one of G
    # shared system-prompt/few-shot headers, then a random tail of the
    # configured prompt length. The PREFIX CACHE should collapse
    # hit-request TTFT to roughly the tail's prefill cost.
    if args.shared_prefix_tokens > 0:
        prefixes = [rng.randint(0, cfg.vocab_size,
                                (args.shared_prefix_tokens,)).tolist()
                    for _ in range(args.prefix_groups)]
        group_of = [int(rng.randint(args.prefix_groups))
                    for _ in range(args.requests)]
        prompts = [prefixes[group_of[i]]
                   + rng.randint(0, cfg.vocab_size,
                                 (int(rng.randint(args.prompt_len[0],
                                                  args.prompt_len[1] + 1)),)
                                 ).tolist()
                   for i in range(args.requests)]
    else:
        prompts = [rng.randint(0, cfg.vocab_size,
                               (int(rng.randint(args.prompt_len[0],
                                                args.prompt_len[1] + 1)),)
                               ).tolist()
                   for _ in range(args.requests)]
    max_new = [int(rng.randint(args.max_new[0], args.max_new[1] + 1))
               for _ in range(args.requests)]

    from paddle_tpu.core import flags as ptflags

    from paddle_tpu.serving import replay as sreplay

    if args.record_out:
        # journal capacity sized like the trace journal: the measured
        # workload must never evict its own head
        sreplay.enable(capacity=max(2 * args.requests + 64, 256))
    ptflags.set_flags({
        # the record journal latches at Engine construction like every
        # tier-2 serving flag
        "FLAGS_serving_replay": bool(args.record_out),
        "FLAGS_serving_prefix_cache": bool(args.prefix_cache),
        "FLAGS_serving_chunked_prefill": bool(args.chunked_prefill),
        # serving-quant flags latch at Engine construction too — set
        # BEFORE the engine is built (PR-9 discipline)
        "FLAGS_serving_quant_kv": bool(args.quant_kv),
        "FLAGS_serving_quant_weights": bool(args.quant_weights),
        # ptprof latches at Engine construction like the tier-2 flags
        # — set BEFORE the engine is built
        "FLAGS_monitor_profile": bool(args.profile),
        # ptslo same discipline: the judge's ring listener must be
        # installed before the engine publishes its first sample
        "FLAGS_monitor_slo": bool(args.slo)})
    if args.slo:
        from paddle_tpu.monitor import slo as ptslo

        ptslo.enable()

    # equal-byte-budget sizing (--quant-kv): --num-blocks names the
    # fp32 pool a fixed HBM budget could afford. The quantized run
    # keeps the SAME byte budget and converts it into MORE pages —
    # per-page k+v bytes: fp32 = 2*4*bs*Hkv*D, int8+scales =
    # 2*(bs*Hkv*D + 4*bs*Hkv). The capacity headroom is the serving
    # payoff: later preemption onset and lower shed rate at the same
    # memory, reported as kv_capacity_headroom_vs_fp32 (>= 1.8 for any
    # realistic head_dim; 4D/(D+4) ~ 3.76x at D=64).
    kv_heads = cfg.num_key_value_heads or cfg.num_attention_heads
    head_dim = cfg.hidden_size // cfg.num_attention_heads
    fp32_page_bytes = 8 * args.block_size * kv_heads * head_dim
    quant_page_bytes = 2 * args.block_size * kv_heads * (head_dim + 4)
    num_blocks = args.num_blocks
    if args.quant_kv:
        num_blocks = max(args.num_blocks,
                         args.num_blocks * fp32_page_bytes
                         // quant_page_bytes)
    kv_headroom = num_blocks / args.num_blocks

    # resilience knobs are applied AFTER warmup (below): the compile
    # warmup enqueues one request per prefill bucket, and a deadline or
    # queue bound there would expire/reject buckets — pushing their
    # compiles into the measured window
    eng = serving.Engine(model, max_slots=args.max_slots,
                         num_blocks=num_blocks,
                         block_size=args.block_size,
                         prefill_chunk=args.prefill_chunk)

    # warmup: compile THE decode step plus every prefill bucket the
    # workload can hit, outside the measured window (compile time is
    # reported separately); one warmup request per bucket. Buckets go up
    # to prompt_hi + max_new_hi - 1, not prompt_hi: a preempted request
    # resumes with prompt + generated-so-far, and its re-prefill must
    # not pay an in-window compile either. Chunked prefill has NO
    # per-bucket prefills — one warm request traces the one mixed step.
    # With the prefix cache on, suffix prefills can be SHORTER than any
    # full prompt, so the bucket sweep starts at length 1.
    t0 = time.perf_counter()
    prompt_hi = (args.prompt_len[1] + args.shared_prefix_tokens)
    resume_hi = prompt_hi + args.max_new[1] - 1
    if args.chunked_prefill:
        n_warm = 1
        eng.add_request([1] * min(resume_hi, eng.max_model_len - 2),
                        max_new_tokens=2)
    else:
        lo = 1 if args.prefix_cache else args.prompt_len[0]
        buckets = sorted({eng._bucket(n) for n in
                          range(lo, resume_hi + 1)})
        n_warm = len(buckets)
        for b in buckets:
            warm_len = min(b, resume_hi, eng.max_model_len - 2)
            eng.add_request([1] * warm_len, max_new_tokens=2)
            if eng.prefix_cache is not None:
                # each warm request must be a FULL MISS: letting warm
                # request N hit request N-1's cached pages would shrink
                # its suffix into a lower bucket and leave the top
                # buckets uncompiled — an in-window jit later
                eng.run()
                eng.prefix_cache.clear()
    eng.run()
    if eng.prefix_cache is not None:
        # warmup prompts must not seed the measured workload's cache;
        # push the post-clear counters into the engine mirror so the
        # warmup snapshot below absorbs the clear's evictions
        eng.prefix_cache.clear()
        eng.metrics.on_prefix_stats(eng.prefix_cache.stats(),
                                    eng.cache.cow_clones)
    warmup_s = time.perf_counter() - t0
    if args.record_out:
        # warmup requests are shape probes, not workload: drop their
        # journal entries (keeping the engine capability snapshot and
        # model meta) so replay re-drives the measured window only
        sreplay.drop_entries()
    if args.slo:
        # warmup requests must not count against the measured
        # window's objectives (the warmup-vs-workload split every
        # other counter gets via the `base` snapshot below)
        ptslo.clear()
    base = eng.stats()     # counters up to here are warmup, not workload
    prof_base = None
    if args.profile:
        # ptprof totals snapshot: the measured window's per-phase host
        # seconds must exclude the compile warmup above
        from paddle_tpu.monitor import profile as pprof

        _pt = pprof.job_totals().get("serving") or {}
        prof_base = {"steps": _pt.get("steps", 0),
                     "dispatch_s": _pt.get("dispatch_s", 0.0),
                     "blocked_s": _pt.get("blocked_s", 0.0),
                     "gap_s": _pt.get("gap_s", 0.0),
                     "phases": dict(_pt.get("phases", {}))}
    eng.max_queue = args.max_queue
    eng.default_deadline_s = args.deadline_s

    # chaos: arm the injection framework AFTER warmup so the compile
    # window stays clean and every injected fault lands in the
    # measured workload (resilience/faultinject — seeded, so the same
    # arguments replay the same faults)
    fault_schedule = args.fault_schedule
    if fault_schedule is None and args.fault_rate > 0:
        fault_schedule = ("serving.prefill:error@p%g" % args.fault_rate)
    if fault_schedule:
        from paddle_tpu.resilience import faultinject as fi

        fi.enable(fault_schedule, seed=args.fault_seed)

    ids = []
    rejected = {}          # admission-shed reason -> count (no id)
    # pool-pressure trajectory: peak page occupancy overall and the
    # occupancy right BEFORE the first preemption/shed event — with
    # --quant-kv the same byte budget holds more pages, so pressure
    # (and the preemption tax) arrives later or never
    peak_occ = 0.0
    occ_at_first_pressure = None
    pressure_base = (eng.metrics.preemptions, eng.metrics.requests_shed)
    start = time.perf_counter()
    nxt = 0
    profile_armed = False
    while nxt < args.requests or eng.has_work():
        now = time.perf_counter() - start
        if args.profile and not profile_armed \
                and nxt >= args.requests // 2:
            # mid-run capture window: the Xprof artifact covers
            # steady-state steps, not the warmup or the tail drain
            from paddle_tpu.monitor import profile as pprof

            pprof.arm_capture(steps=8, reason="serving_benchmark")
            profile_armed = True
        while nxt < args.requests and arrivals[nxt] <= now:
            try:
                ids.append(eng.add_request(
                    prompts[nxt], max_new_tokens=max_new[nxt]))
            except serving.AdmissionError as e:
                rejected[e.reason] = rejected.get(e.reason, 0) + 1
            nxt += 1
        if eng.has_work():
            alloc = eng.cache.allocator
            occ = (1.0 - alloc.free_blocks
                   / max(alloc.usable_blocks, 1))
            peak_occ = max(peak_occ, occ)
            eng.step()
            if occ_at_first_pressure is None and (
                    (eng.metrics.preemptions,
                     eng.metrics.requests_shed) != pressure_base):
                # occupancy going INTO the step that first preempted
                # or shed — the onset point of pool pressure
                occ_at_first_pressure = occ
        elif nxt < args.requests:
            time.sleep(min(arrivals[nxt] - now, 0.05))
    wall = time.perf_counter() - start
    if fault_schedule:
        from paddle_tpu.resilience import faultinject as fi

        fault_state = fi.state()
        fi.disable()
    else:
        fault_state = None

    stats = eng.stats()
    # engine counters aggregate over the whole lifetime — subtract the
    # warmup snapshot so the artifact reports the measured window only
    meas_steps = stats["decode_steps"] - base["decode_steps"]
    occ_sum = (stats["slot_occupancy"] * stats["decode_steps"]
               - base["slot_occupancy"] * base["decode_steps"])
    meas_occupancy = occ_sum / meas_steps if meas_steps else 0.0
    per_req = []
    for r in ids:
        row = dict(eng.request_metrics(r), request_id=r)
        status = eng.request_status(r)
        row["status"] = status["state"]
        if status["reason"] is not None:
            row["status_reason"] = status["reason"]
        # trace id + per-request phase breakdown (queue / prefill /
        # decode / preempted seconds): the preemption tax attributable
        # per-request — a preempted request shows the recompute in its
        # own prefill/preempted phases, not only in the aggregate
        tid, phases = eng.request_trace(r)
        if tid is not None:
            row["trace_id"] = tid
            row["phases_s"] = {k: round(v, 6)
                               for k, v in sorted(phases.items())}
        # the replay-audit columns ride along unconditionally (the
        # hash is a pure function of the output ids): two bench
        # artifacts can be diffed for token drift without either run
        # having recorded a journal
        row["output_token_hash"] = sreplay.token_hash(eng.output(r))
        row["weights_generation"] = eng.weights_generation
        per_req.append(row)
    ttft = [m["ttft_s"] for m in per_req if m["ttft_s"] is not None]
    tpot = [m["tpot_s"] for m in per_req if m["tpot_s"] is not None]
    queue = [m["queue_time_s"] for m in per_req
             if m["queue_time_s"] is not None]
    out_tokens = sum(m["output_tokens"] for m in per_req)
    # TTFT split by prefix-cache outcome at the FIRST admission (TTFT
    # is set by the first token, so only that admission's match can
    # explain it — a preempted miss that re-hits its own pages on
    # resume stays a miss). The acceptance headline is p50 hit-TTFT
    # collapsing vs miss-TTFT on the shared-prefix shape.
    ttft_hit = [m["ttft_s"] for m in per_req
                if m["ttft_s"] is not None
                and m["prefix_cached_tokens_first"] > 0]
    ttft_miss = [m["ttft_s"] for m in per_req
                 if m["ttft_s"] is not None
                 and m["prefix_cached_tokens_first"] == 0]

    report = {
        "kind": "serving_bench",
        "metric": "serving_throughput_tok_s",
        "value": round(out_tokens / max(wall, 1e-9), 1),
        "unit": "tok/s",
        **_device_fields(),
        # serving.Engine is single-device by construction: everything
        # lives on the device that holds the weights
        "devices_used": 1,
        "preset": args.preset,
        "workload": {
            "requests": args.requests, "poisson_rate": args.rate,
            "prompt_len": list(args.prompt_len),
            "max_new": list(args.max_new), "seed": args.seed,
            "max_slots": args.max_slots, "num_blocks": args.num_blocks,
            "block_size": args.block_size,
            "shared_prefix_tokens": args.shared_prefix_tokens,
            "prefix_groups": (args.prefix_groups
                              if args.shared_prefix_tokens else 0),
            "prefix_cache": bool(args.prefix_cache),
            "chunked_prefill": bool(args.chunked_prefill),
            "prefill_chunk": (args.prefill_chunk
                              if args.chunked_prefill else None),
            "quant_kv": bool(args.quant_kv),
            "quant_weights": bool(args.quant_weights),
        },
        "wall_s": round(wall, 3),
        "warmup_compile_s": round(warmup_s, 3),
        "output_tokens": out_tokens,
        "ttft_s": _pcts(ttft),
        "ttft_hit_s": _pcts(ttft_hit),
        "ttft_miss_s": _pcts(ttft_miss),
        "prefix_cache_hits": len(ttft_hit),
        "prefix_cache_hit_tokens_total": (stats["prefix_hit_tokens"]
                                          - base["prefix_hit_tokens"]),
        "prefix_cache_lookup_tokens_total": (
            stats["prefix_lookup_tokens"] - base["prefix_lookup_tokens"]),
        "prefix_cache_evictions": (stats["prefix_evictions"]
                                   - base["prefix_evictions"]),
        "cow_clones": stats["cow_clones"] - base["cow_clones"],
        "prefill_chunks": stats["prefill_chunks"] - base["prefill_chunks"],
        "tpot_s": _pcts(tpot),
        "queue_time_s": _pcts(queue),
        # serving-quant scoreboard: at the FIXED byte budget named by
        # --num-blocks, how many pages did the dtype buy, how late did
        # pool pressure arrive, and how much traffic was shed. The
        # acceptance headline is kv_capacity_headroom_vs_fp32 >= 1.8
        # with --quant-kv on.
        "quant": {
            "quant_kv": bool(args.quant_kv),
            "quant_weights": bool(args.quant_weights),
            "num_blocks_fp32_budget": args.num_blocks,
            "num_blocks_effective": num_blocks,
            "kv_page_bytes_fp32": fp32_page_bytes,
            "kv_page_bytes_quant": quant_page_bytes,
            "kv_capacity_headroom_vs_fp32": round(kv_headroom, 3),
            "peak_kv_page_occupancy": round(peak_occ, 4),
            "occupancy_before_first_pressure": (
                None if occ_at_first_pressure is None
                else round(occ_at_first_pressure, 4)),
            "shed_rate": round(
                stats["requests_shed"] / max(args.requests, 1), 4),
            "kv_quant_pages": stats.get("kv_quant_pages", 0),
            "quant_dequant_bytes": stats.get("quant_dequant_bytes", 0),
        },
        "preemptions": stats["preemptions"] - base["preemptions"],
        "decode_steps": meas_steps,
        "decode_compiles": stats["decode_compiles"],
        "prefill_compiles": stats["prefill_compiles"],
        "slot_occupancy": round(meas_occupancy, 4),
        "requests_finished": stats["requests_finished"] - n_warm,
        # resilience accounting: goodput (finished-request tokens only)
        # next to shed/expired/failed counts — under a fault schedule
        # the SLO question is "how much service survived the chaos"
        "goodput_tok_s": round(
            sum(m["output_tokens"] for m in per_req
                if m["status"] == "finished") / max(wall, 1e-9), 1),
        "requests_shed_total": stats["requests_shed"],
        "shed_by_reason": stats["shed_by_reason"],
        "rejected_at_admission": rejected,
        "fault_schedule": fault_schedule,
        "faults_injected": (
            None if fault_state is None else
            {r["rule"]: r["fired"] for r in fault_state["rules"]}),
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        # raw per-request rows ride along with the aggregates so
        # distribution questions don't need a re-run
        "requests_detail": per_req,
    }
    if args.slo:
        # ptslo verdicts next to the goodput-vs-throughput gap: the
        # same artifact answers "how fast" AND "did it meet the SLO"
        from paddle_tpu.monitor import incidents as ptincidents

        spay = ptslo.payload()
        report["slo"] = {
            "enabled": spay.get("enabled", False),
            "window_scale": spay.get("window_scale"),
            "objectives": [
                {"objective": o.get("objective"),
                 "job": o.get("job"),
                 "threshold": o.get("threshold"),
                 "target": o.get("target"),
                 "samples": o.get("samples"),
                 "attainment": o.get("attainment"),
                 "budget_remaining_ratio":
                     o.get("budget_remaining_ratio"),
                 "burn_rate": o.get("burn_rate"),
                 "alerting": o.get("alerting")}
                for o in spay.get("objectives") or ()],
            "alerts_open": sorted(
                i["key"] for i in ptincidents.open_incidents()
                if i.get("source") == "slo"),
            "incidents_open": len(ptincidents.open_incidents()),
        }
    if args.profile:
        # measured host attribution (monitor/profile.py): per-phase
        # host seconds over the measured window (warmup subtracted),
        # the sampler's component shares, and any capture artifacts
        from paddle_tpu.monitor import profile as pprof

        ppay = pprof.profile_payload()
        tot = (ppay.get("jobs") or {}).get("serving") or {}
        pb = prof_base or {}
        report["profile"] = {
            "host_blocked_s": {
                k: round(v - pb.get("phases", {}).get(k, 0.0), 6)
                for k, v in sorted((tot.get("phases") or {}).items())},
            "dispatch_s_total": round(
                tot.get("dispatch_s", 0.0)
                - pb.get("dispatch_s", 0.0), 6),
            "gap_s_total": round(
                tot.get("gap_s", 0.0) - pb.get("gap_s", 0.0), 6),
            "steps": tot.get("steps", 0) - pb.get("steps", 0),
            "sampler": ppay.get("sampler"),
            "components": ppay.get("components"),
            "captures": [c["dir"] for c in ppay.get("captures") or ()],
            "pending_captures": ppay.get("pending_captures"),
        }
    print(json.dumps({k: v for k, v in report.items()
                      if k != "requests_detail"}), flush=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    print("wrote", args.out, flush=True)
    if args.monitor_out:
        from paddle_tpu import monitor

        monitor.write_snapshot(args.monitor_out, meta={
            "tool": "serving_benchmark", "preset": args.preset,
            "backend": jax.default_backend(),
            "measured_at": report["measured_at"],
            "serving_throughput_tok_s": report["value"],
        })
        print("wrote", args.monitor_out, flush=True)
    if args.trace_out and not args.no_trace:
        mtrace.write_journal(args.trace_out)
        print("wrote", args.trace_out, flush=True)
    if args.record_out:
        # model meta makes the journal self-contained: ptreplay
        # rebuilds the exact weights from config kwargs + init seed
        # without ever importing this script
        sreplay.note_model({"preset": args.preset, "seed": args.seed,
                            "config": dict(PRESETS[args.preset])})
        head, jentries = sreplay.write_journal(args.record_out)
        print("wrote %s (%d journal entries, %d evictions)"
              % (args.record_out, len(jentries), head["evictions"]),
              flush=True)
    # contract check: the whole staggered workload must have reused ONE
    # compiled decode step (the engine's core shape-stability claim)
    if stats["decode_compiles"] != 1:
        sys.stderr.write("FAIL: decode compiled %d times (expected 1)\n"
                         % stats["decode_compiles"])
        return 4
    return 0


if __name__ == "__main__":
    sys.exit(main())
