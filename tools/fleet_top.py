"""htop-for-ranks: live per-rank fleet table from the collector view.

Scrapes every rank's metrics endpoint (monitor/fleet.py
FleetCollector, run in-process here — no server-side collector needed)
and renders the per-rank table: step, step time, tokens/s, MFU, HBM
peak, live memory + headroom (the /debugz/memory plane, round 14),
measured host-blocked share (the /debugz/profile plane, round 15),
comm share, serving-router replica count + affinity hit rate where a
rank hosts one (the /debugz/router plane, round 17), heartbeat age,
health verdict, straggler flag.

Endpoints come from one of:
  --endpoints URL[,URL...]   explicit list (rank = position, or R=URL)
  --store HOST:PORT --world N   discovery from the fleet TCPStore the
      ranks announced into (``__fleet/ep/rank{r}``, written by
      ``monitor.fleet.announce`` / ``init_parallel_env`` under
      ``FLAGS_monitor_fleet``)

Modes:
  (default)       live: redraw the table every --interval seconds
  --once          two scrapes --window apart (rates need a delta),
                  print the table, exit
  --json          print the machine-readable snapshot instead of the
                  table (scripts; implies --once unless live)
  --out PATH      write the fleet snapshot artifact. If NOTHING
                  answered the scrape, nothing is written and the exit
                  code is 3.

Usage:
  python tools/fleet_top.py --endpoints http://h1:9000,http://h2:9000
  python tools/fleet_top.py --store 127.0.0.1:6170 --world 4 --once --json
  python tools/fleet_top.py --store ... --world 4 --out tools/fleet_snapshot.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))

from paddle_tpu.monitor import fleet  # noqa: E402
from paddle_tpu.monitor.watchdog import json_safe  # noqa: E402


def _fmt(v, spec="%s", dash="-"):
    if v is None:
        return dash
    try:
        return spec % v
    except (TypeError, ValueError):
        return str(v)


def _fmt_bytes(v):
    if v is None:
        return "-"
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(v) < 1024 or unit == "TiB":
            return "%.1f%s" % (v, unit) if unit != "B" \
                else "%d%s" % (v, unit)
        v /= 1024.0
    return "-"


COLS = (
    ("RANK", 4, lambda r: _fmt(r.get("rank"), "%d")),
    ("STEP", 7, lambda r: _fmt(r.get("steps_total"), "%d")),
    ("BEHIND", 6, lambda r: _fmt(r.get("steps_behind"), "%d")),
    ("STEP_S", 8, lambda r: _fmt(r.get("step_time_s"), "%.3f")),
    ("TOK/S", 9, lambda r: _fmt(r.get("tokens_per_s"), "%.0f")),
    ("MFU", 6, lambda r: _fmt(r.get("mfu"), "%.3f")),
    ("HBM_PEAK", 9, lambda r: _fmt_bytes(r.get("hbm_peak_bytes"))),
    ("MEM", 9, lambda r: _fmt_bytes(r.get("mem_live_bytes"))),
    ("HEADROOM", 9, lambda r: _fmt_bytes(r.get("mem_headroom_bytes"))),
    ("HOSTBLK%", 8, lambda r: _fmt(
        r.get("profile_host_blocked_share") * 100 if isinstance(
            r.get("profile_host_blocked_share"), (int, float))
        else None, "%.1f")),
    ("COMM%", 6, lambda r: _fmt(
        r.get("comm_share") * 100 if isinstance(
            r.get("comm_share"), (int, float)) else None, "%.1f")),
    # serving-fleet router columns (blank unless the rank hosts a
    # Router — /debugz/router answers with a live hook there only)
    ("REPLICAS", 8, lambda r: _fmt(r.get("router_replicas"), "%d")),
    ("AFFIN%", 6, lambda r: _fmt(
        r.get("router_affinity_hit_rate") * 100 if isinstance(
            r.get("router_affinity_hit_rate"), (int, float))
        else None, "%.1f")),
    ("HB_AGE", 7, lambda r: _fmt(r.get("heartbeat_age_s"), "%.1f")),
    ("HEALTH", 9, lambda r: ("UNREACH" if not r.get("ok")
                             else (r.get("healthz") or "-"))),
    ("ANOM", 5, lambda r: _fmt(r.get("anomalies_total"), "%d")),
    ("STRAG", 5, lambda r: ("YES" if r.get("straggler") else "")),
    # SLO/incident columns (blank unless the rank runs
    # FLAGS_monitor_slo): worst objective's attainment %, worst
    # error-budget remaining %, open incident count
    ("SLO%", 6, lambda r: _fmt(
        r.get("slo_attainment_min") * 100 if isinstance(
            r.get("slo_attainment_min"), (int, float))
        else None, "%.1f")),
    ("BUDGET%", 7, lambda r: _fmt(
        r.get("slo_budget_min") * 100 if isinstance(
            r.get("slo_budget_min"), (int, float))
        else None, "%.1f")),
    ("INC", 4, lambda r: _fmt(r.get("incidents_open"), "%d")),
)


def render_table(rows, summary=None):
    lines = []
    hdr = "  ".join("%-*s" % (w, name) for name, w, _ in COLS)
    lines.append(hdr)
    lines.append("-" * len(hdr))
    for r in rows:
        lines.append("  ".join("%-*s" % (w, fn(r)[:w + 8])
                               for _, w, fn in COLS))
    if summary:
        strag = summary.get("stragglers") or {}
        caps = summary.get("captures") or ()
        lines.append("")
        lines.append(
            "scrapes=%s  ranks_ok=%s/%s  stragglers=%s  captures=%d"
            % (summary.get("collector", {}).get("scrapes"),
               len(summary.get("ranks_ok") or ()),
               summary.get("world_size"),
               ",".join(sorted(strag)) or "none", len(caps)))
        for c in caps[-2:]:
            lines.append("  capture[%s]: %s" % (c["reason"], c["dir"]))
    return "\n".join(lines)


def build_collector(args):
    endpoints = None
    store = None
    if args.endpoints:
        endpoints = {}
        for i, spec in enumerate(args.endpoints.replace(",", " ").split()):
            if "=" in spec and not spec.startswith("http"):
                r, _, u = spec.partition("=")
                endpoints[int(r)] = u
            else:
                endpoints[i] = spec
    elif args.store:
        from paddle_tpu.distributed.store import TCPStore

        host, _, port = args.store.partition(":")
        store = TCPStore(host or "127.0.0.1", int(port),
                         is_master=False, timeout_s=args.http_timeout + 5)
        if not args.world:
            sys.exit("--store needs --world N")
    else:
        sys.exit("need --endpoints or --store (see --help)")
    return fleet.FleetCollector(
        endpoints=endpoints, store=store, world_size=args.world,
        interval_s=args.interval, straggler_factor=args.factor,
        straggler_persist=args.persist, capture_dir=args.capture_dir,
        http_timeout_s=args.http_timeout)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="live per-rank fleet telemetry table")
    ap.add_argument("--endpoints", help="comma/space list of rank "
                                        "endpoint URLs (or R=URL)")
    ap.add_argument("--store", help="fleet TCPStore HOST:PORT to "
                                    "discover announced endpoints from")
    ap.add_argument("--world", type=int, default=0,
                    help="world size (required with --store)")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="live-mode refresh seconds (default 2)")
    ap.add_argument("--window", type=float, default=1.0,
                    help="--once: delta window between the two scrapes")
    ap.add_argument("--once", action="store_true",
                    help="two scrapes, one table, exit")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable snapshot instead of a table")
    ap.add_argument("--out", help="write the fleet snapshot artifact "
                                  "(stale re-emit on a dead scrape)")
    ap.add_argument("--duration", type=float, default=0.0,
                    help="live mode: exit after this many seconds "
                         "(0 = until interrupted)")
    ap.add_argument("--factor", type=float, default=None,
                    help="straggler factor vs fleet median step time")
    ap.add_argument("--persist", type=int, default=None,
                    help="consecutive slow scrapes before flagging")
    ap.add_argument("--capture-dir", default=None,
                    help="where anomaly captures land "
                         "(default PT_MONITOR_DUMP_DIR)")
    ap.add_argument("--http-timeout", type=float, default=3.0)
    args = ap.parse_args(argv)

    c = build_collector(args)
    once = args.once or args.json or bool(args.out)
    try:
        if once:
            c.scrape_once()
            time.sleep(args.window)
            c.scrape_once()
            snap = fleet.snapshot_dict(c)
            if args.out:
                snap = fleet.write_snapshot_artifact(args.out,
                                                     collector=c)
                print("fleet_top: %s %s (%d rank(s))"
                      % ("wrote" if snap.get("ok")
                         else "nothing scraped, did not write",
                         args.out, len(snap.get("ranks") or ())),
                      file=sys.stderr)
            if args.json:
                json.dump(json_safe(snap), sys.stdout,
                          indent=1, default=str)
                sys.stdout.write("\n")
            else:
                print(render_table(c.ranks_table(), c.summary()))
            return 0 if snap.get("ok") else 3
        deadline = (time.monotonic() + args.duration
                    if args.duration > 0 else None)
        while True:
            t0 = time.monotonic()
            c.scrape_once()
            sys.stdout.write("\x1b[2J\x1b[H")
            print("fleet_top  %s  interval=%.1fs"
                  % (time.strftime("%H:%M:%S"), args.interval))
            print(render_table(c.ranks_table(), c.summary()))
            sys.stdout.flush()
            if deadline is not None and time.monotonic() >= deadline:
                return 0
            time.sleep(max(args.interval - (time.monotonic() - t0), 0.05))
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
