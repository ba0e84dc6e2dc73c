"""Fleet telemetry plane: cross-rank aggregation, stragglers, capture.

Every monitor surface so far (registry, flight recorder, watchdog,
perf, trace) stops at one process: an N-rank run is N unrelated
``/metrics`` endpoints, and the only cross-rank story the stack can
tell is a postmortem AFTER something timed out. This module is the
live fleet view the ROADMAP item-2 router and item-3 overlap work both
need:

1. **Endpoint registration** (rank side, ``announce()``): each rank
   starts the process-wide metrics server (monitor/exporter.py) and
   registers its HTTP endpoint in the existing TCPStore under
   ``__fleet/ep/rank{r}`` — the same store the flight recorder and
   watchdog already rendezvous through. ``init_parallel_env`` wires
   this automatically under ``FLAGS_monitor_fleet``.

2. **Collector** (``FleetCollector``, runnable on any rank or as a
   standalone process holding a store client): scrapes every rank's
   ``/metrics.json`` + ``/debugz/perf`` + ``/healthz`` (plus
   best-effort ``/debugz/flight`` and ``/debugz/memory``) on an interval
   and fuses them into rank-labeled fleet series — counters SUM across
   ranks, gauges keep per-rank values plus min/max/p50 fleet
   aggregates, histograms sum bucket-wise. Each scrape also estimates
   the rank's wall-clock offset NTP-style (the PR-2 trace_merge
   discipline, here over the HTTP exchange itself: the rank's
   self-reported ``unix_time`` against the request's local midpoint,
   min-RTT sample wins), so per-rank freshness/progress stamps are
   compared on ONE clock. Served at ``/debugz/fleet`` (summary),
   ``/debugz/fleet/ranks`` (per-rank table), and Prometheus
   federation-style ``/metrics/fleet``.

3. **Straggler & skew detection**: per-scrape cross-rank deltas of
   ``train_step_seconds`` (windowed mean step time per rank) against
   the fleet median — a rank persistently slower than
   ``PT_FLEET_STRAGGLER_FACTOR`` (default 2.0) x median for
   ``PT_FLEET_STRAGGLER_PERSIST`` (default 2) consecutive scrapes is
   flagged: ``fleet_straggler_total{rank}`` increments and the rank is
   named in ``/debugz/fleet`` — while the run is still healthy,
   BEFORE any collective timeout (the flight recorder only names ranks
   post-timeout). ``train_steps_total`` watermark skew rides the same
   table (``steps_behind``).

4. **Anomaly-triggered fleet capture**: when any rank's perf sentinel
   fires (its ``perf_anomalies_total`` advances / healthz turns
   degraded) or a straggler is flagged, the collector pulls
   watchdog-style bundles (``/debugz/bundle``), span-journal tails
   (``/debugz/trace/journal``), the memory breakdown
   (``/debugz/memory``) and the profiling summary incl. folded host
   stacks (``/debugz/profile``) from ALL ranks into one
   ``fleet_capture_<ts>/`` directory (manifest + per-rank artifacts)
   — a loss spike on rank 3 automatically yields fleet-wide evidence.
   ``tools/trace_merge.py --capture`` renders the merged chrome trace
   from such a capture; ``tools/fleet_top.py`` renders the live table.

Discipline (the PR-2/5/6 contract, test-pinned): default OFF via
``FLAGS_monitor_fleet``. While off, ``announce()``/``note_identity()``
are one flag-load + branch — no metrics server, no collector thread,
no store traffic, no native calls. Stdlib-only imports so bare worker
processes can load it without an accelerator backend.
"""
from __future__ import annotations

import http.client
import json
import os
import threading
import time
import urllib.error
import urllib.request

from . import registry as _registry
from .timeseries import _flag

_EP_PREFIX = "__fleet/ep"
_THREAD_NAME = "pt-fleet-collector"

# -- collector telemetry (shared registry discipline: every mutator
# no-ops while the monitor is disabled) --------------------------------------

_SCRAPES = _registry.counter(
    "fleet_scrapes_total", "collector scrape rounds completed")
_SCRAPE_ERRS = _registry.counter(
    "fleet_scrape_errors_total",
    "per-rank scrape failures (unreachable/medium errors)",
    labelnames=("rank",))
_STRAGGLER_TOTAL = _registry.counter(
    "fleet_straggler_total",
    "straggler episodes flagged per rank (persistently slower than "
    "the fleet median step time)", labelnames=("rank",))
_CAPTURES_TOTAL = _registry.counter(
    "fleet_captures_total", "anomaly-triggered fleet captures",
    labelnames=("reason",))
_RANKS_OK = _registry.gauge(
    "fleet_ranks_reporting", "ranks answering the last scrape round")
_RANK_INFO = _registry.gauge(
    "fleet_rank_info",
    "per-rank identity beacon (value = pid); set by parallel/engine "
    "and serving under FLAGS_monitor_fleet so scraped series resolve "
    "to a rank/host/job", labelnames=("job", "rank", "host"))


def is_enabled():
    return _flag("FLAGS_monitor_fleet")


def _env_float(name, default):
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_int(name, default):
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _local_host():
    """The address peers should dial for this rank's endpoint: explicit
    override first, then the launch-provided routable endpoint, then
    loopback (single-host worlds)."""
    host = os.environ.get("PT_FLEET_HOST")
    if host:
        return host
    ep = os.environ.get("PADDLE_CURRENT_ENDPOINT", "")
    if ":" in ep:
        return ep.partition(":")[0]
    return "127.0.0.1"


# -- rank side: endpoint registration + identity -----------------------------

class _AnnounceState:
    __slots__ = ("lock", "url", "registered")

    def __init__(self):
        self.lock = threading.Lock()
        self.url = None
        self.registered = False


_announce = _AnnounceState()


def register_endpoint(store, rank, url, job=None, **meta):
    """Publish one rank's scrape endpoint in the fleet store."""
    rec = {"url": url, "rank": int(rank), "pid": os.getpid(),
           "host": _local_host(), "registered_at": time.time()}
    if job:
        rec["job"] = job
    rec.update(meta)
    store.set("%s/rank%d" % (_EP_PREFIX, rank),
              json.dumps(rec, default=str).encode())
    return rec


def discover_endpoints(store, world_size, timeout_s=0.05):
    """{rank: endpoint record} for every rank that has announced.
    Short per-key timeout: a rank that has not announced yet is simply
    absent this round and retried next scrape."""
    out = {}
    for r in range(int(world_size)):
        data = store.get("%s/rank%d" % (_EP_PREFIX, r),
                         timeout_s=timeout_s)
        if data is None:
            continue
        try:
            rec = json.loads(data.decode())
            if rec.get("url"):
                out[r] = rec
        except Exception:
            continue
    return out


def announce(store=None, rank=None, world_size=None, job=None, port=0):
    """Start (or reuse) this process's metrics server and register its
    endpoint under ``__fleet/ep/rank{r}``. Returns the endpoint url,
    or None while ``FLAGS_monitor_fleet`` is off (the disabled path is
    one flag-load + branch: no server, no store traffic, test-pinned).
    Idempotent: repeat calls re-register the same url (a restarted
    store server gets a fresh record) but never start a second
    server."""
    if not is_enabled():
        return None
    from . import exporter as _exporter

    with _announce.lock:
        srv = _exporter.start_metrics_server(port)
        url = "http://%s:%d" % (_local_host(), srv.port)
        _announce.url = url
    if store is None:
        from ..distributed import process_group as _pg

        pg = _pg.get_world_group()
        if pg is not None:
            store, rank, world_size = pg.store, pg.rank, pg.world_size
    if store is not None and rank is not None:
        register_endpoint(store, rank, url, job=job)
        _announce.registered = True
        try:
            _RANK_INFO.labels(job=job or "rank", rank=rank,
                              host=_local_host()).set(os.getpid())
        except Exception as e:
            _registry.warn_once(
                "fleet.rank_info",
                "paddle_tpu.monitor.fleet: rank-info gauge failed "
                "(identity labels missing from fleet view): %r" % (e,))
    return url


def announced_url():
    return _announce.url


def note_identity(job):
    """Per-rank identity label on the scraped series: the train/serving
    engines call this once at construction so the collector's fused
    view can say WHICH rank/host ran which job. One flag branch while
    fleet monitoring is off."""
    if not is_enabled():
        return
    try:
        from ..distributed import process_group as _pg

        pg = _pg.get_world_group()
        rank = pg.rank if pg is not None else 0
        _RANK_INFO.labels(job=job, rank=rank,
                          host=_local_host()).set(os.getpid())
    except Exception as e:
        _registry.warn_once(
            "fleet.note_identity",
            "paddle_tpu.monitor.fleet: identity labeling failed "
            "(fused view loses job attribution for this rank): "
            "%r" % (e,))


def maybe_announce_and_collect(pg):
    """The ``init_parallel_env`` hook: under ``FLAGS_monitor_fleet``,
    announce this rank's endpoint and — on the collector rank
    (``PT_FLEET_COLLECTOR_RANK``, default 0) — start the fleet
    collector thread. One flag branch when off."""
    if not is_enabled():
        return None
    url = announce(pg.store, pg.rank, pg.world_size)
    if pg.rank == _env_int("PT_FLEET_COLLECTOR_RANK", 0):
        start_collector(store=pg.store, world_size=pg.world_size,
                        rank=pg.rank)
    return url


# -- scraping ----------------------------------------------------------------

def _http_json(url, timeout_s):
    """(payload, t0, t1, rtt_s) — the WALL stamps around the exchange
    feed the NTP-style offset estimate (the one legitimate wall-clock
    use here: comparing the peer's self-reported unix_time against our
    own wall midpoint); the round-trip DURATION is measured on the
    monotonic clock, because an NTP step mid-exchange must not produce
    a negative or kilometric RTT. Raises on transport errors; HTTP
    error codes with a JSON body (healthz 503) still parse."""
    t0 = time.time()    # ptlint: clock-ok — NTP-style offset probe
    m0 = time.monotonic()
    try:
        with urllib.request.urlopen(url, timeout=timeout_s) as r:
            body = r.read()
    except urllib.error.HTTPError as e:
        body = e.read()
    t1 = time.time()    # ptlint: clock-ok — NTP-style offset probe
    rtt_s = max(time.monotonic() - m0, 0.0)
    return json.loads(body.decode()), t0, t1, rtt_s


def fuse_snapshots(metrics_by_rank):
    """Fuse per-rank registry snapshots into rank-labeled fleet series.

    Aggregation semantics (the /debugz/fleet contract): counters SUM
    across ranks (each rank counts its own events — the fleet total is
    their union); gauges are instantaneous per-rank states, so the
    fleet keeps every per-rank value plus min/max/p50 spread (a fleet
    "sum" of gauges like mfu would be meaningless); histograms sum
    bucket-wise (counts and sums are disjoint event sets).

    Returns {name: {kind, help, series: [{labels, per_rank, fleet}]}}.
    """
    fused = {}
    for rank, mets in sorted(metrics_by_rank.items()):
        for name, m in (mets or {}).items():
            ent = fused.setdefault(name, {
                "kind": m.get("kind", "untyped"),
                "help": m.get("help", ""), "_series": {}})
            for s in m.get("series", ()):
                labels = dict(s.get("labels") or {})
                key = tuple(sorted(labels.items()))
                se = ent["_series"].setdefault(
                    key, {"labels": labels, "per_rank": {}})
                if ent["kind"] == "histogram":
                    se["per_rank"][rank] = {
                        "sum": s.get("sum", 0.0),
                        "count": s.get("count", 0),
                        "buckets": dict(s.get("buckets") or {})}
                else:
                    se["per_rank"][rank] = s.get("value", 0)
    for name, ent in fused.items():
        series = []
        for key in sorted(ent["_series"]):
            se = ent["_series"][key]
            if ent["kind"] == "histogram":
                buckets = {}
                tot_sum, tot_count = 0.0, 0
                for h in se["per_rank"].values():
                    tot_sum += float(h["sum"] or 0.0)
                    tot_count += int(h["count"] or 0)
                    for b, c in h["buckets"].items():
                        buckets[b] = buckets.get(b, 0) + int(c)
                se["fleet"] = {"sum": tot_sum, "count": tot_count,
                               "buckets": buckets}
            else:
                vals = sorted(float(v) for v in se["per_rank"].values()
                              if isinstance(v, (int, float)))
                if not vals:
                    se["fleet"] = {}
                elif ent["kind"] == "counter":
                    se["fleet"] = {"sum": sum(vals)}
                else:
                    se["fleet"] = {
                        "min": vals[0], "max": vals[-1],
                        "p50": vals[len(vals) // 2],
                        "sum": sum(vals)}
            series.append(se)
        ent["series"] = series
        del ent["_series"]
    return fused


class FleetCollector:
    """Scrape-and-fuse loop over the fleet's rank endpoints.

    ``endpoints``: {rank: url} given explicitly, or discovered from
    ``store`` + ``world_size`` (ranks announce at their own pace — a
    missing rank is retried every round). Runs on any rank or in a
    standalone process; route payloads (``/debugz/fleet*``,
    ``/metrics/fleet``) read the installed collector via
    ``get_collector()``.
    """

    def __init__(self, endpoints=None, store=None, world_size=None,
                 interval_s=None, straggler_factor=None,
                 straggler_persist=None, capture_dir=None,
                 capture_cooldown_s=None, max_captures=None,
                 http_timeout_s=None, rank=None):
        self._lock = threading.Lock()
        self._endpoints = {int(r): (u if isinstance(u, str)
                                    else u.get("url"))
                           for r, u in (endpoints or {}).items()}
        self._store = store
        self.world_size = int(world_size) if world_size \
            else (max(self._endpoints) + 1 if self._endpoints else 0)
        self.rank = rank
        self.interval_s = float(interval_s if interval_s is not None
                                else _env_float("PT_FLEET_SCRAPE_S", 2.0))
        self.straggler_factor = float(
            straggler_factor if straggler_factor is not None
            else _env_float("PT_FLEET_STRAGGLER_FACTOR", 2.0))
        self.straggler_persist = int(
            straggler_persist if straggler_persist is not None
            else _env_int("PT_FLEET_STRAGGLER_PERSIST", 2))
        self.capture_cooldown_s = float(
            capture_cooldown_s if capture_cooldown_s is not None
            else _env_float("PT_FLEET_CAPTURE_COOLDOWN_S", 60.0))
        self.max_captures = int(
            max_captures if max_captures is not None
            else _env_int("PT_FLEET_MAX_CAPTURES", 4))
        self.http_timeout_s = float(
            http_timeout_s if http_timeout_s is not None
            else _env_float("PT_FLEET_HTTP_TIMEOUT_S", 3.0))
        self.capture_dir = capture_dir \
            or os.environ.get("PT_MONITOR_DUMP_DIR") or "."
        self._ranks = {}        # rank -> per-rank scrape/derived state
        self._fused = {}
        self._stragglers = {}   # rank -> episode info (active)
        self._captures = []     # [{dir, reason, created_at, ranks}]
        self._pending_captures = []     # [(reason, detail)] behind cooldown
        self._last_capture_at = None
        self._scrapes = 0
        self._started_at = None
        self._last_scrape_at = None
        self._thread = None
        self._stop = None
        self._pool = None       # scrape-fanout executor, lazy

    # -- lifecycle ---------------------------------------------------------

    def start(self):
        if self._thread is not None and self._thread.is_alive():
            return self
        self._started_at = time.time()
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name=_THREAD_NAME, daemon=True)
        self._thread.start()
        return self

    def stop(self, snapshot_out=None):
        if self._stop is not None:
            self._stop.set()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout=5)
        self._thread = None
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None
        out = snapshot_out or os.environ.get("PT_FLEET_SNAPSHOT_OUT")
        if out:
            try:
                write_snapshot_artifact(out, collector=self)
            except Exception as e:
                _registry.warn_once(
                    "fleet.snapshot_artifact",
                    "paddle_tpu.monitor.fleet: final snapshot "
                    "artifact write failed (%s): %r" % (out, e))

    def is_running(self):
        return self._thread is not None and self._thread.is_alive()

    def _run(self):
        while not self._stop.wait(self.interval_s):
            try:
                self.scrape_once()
            except Exception as e:
                # the collector eating its own scrape failures is the
                # exact watchdog-blind-spot this repo lints against:
                # say it once, keep the loop alive
                _registry.warn_once(
                    "fleet.scrape_loop",
                    "paddle_tpu.monitor.fleet: scrape round failed "
                    "(collector still running): %r" % (e,))

    # -- one scrape round --------------------------------------------------

    def _resolve_endpoints(self):
        if self._store is not None and self.world_size:
            # re-discover ranks that never announced AND ranks whose
            # endpoint has gone dark: a restarted rank (the PR-7
            # elastic flow) re-announces on a FRESH ephemeral port, and
            # a collector that kept dialing the dead URL forever would
            # permanently lose that rank's coverage
            stale = {r for r, st in self._rank_items()
                     if st.get("consecutive_errors", 0) >= 2}
            missing = [r for r in range(self.world_size)
                       if r not in self._endpoints or r in stale]
            if missing:
                found = discover_endpoints(self._store, self.world_size)
                for r, rec in found.items():
                    if r in stale or r not in self._endpoints:
                        self._endpoints[r] = rec["url"]
        return dict(self._endpoints)

    def _scrape_rank(self, rank, url):
        """One rank's scrape: /metrics.json + /debugz/perf + /healthz,
        with the HTTP exchange doubling as the NTP-style clock probe
        (rank-reported unix_time vs the local request midpoint; the
        min-RTT sample wins, the PR-2 trace_merge discipline).
        ``scraped_at`` is a MONOTONIC stamp: every consumer subtracts
        it (freshness ages, progress windows) and a wall step must not
        fake or mask staleness."""
        snap, t0, t1, rtt = _http_json(url + "/metrics.json",
                                       self.http_timeout_s)
        offset = None
        if isinstance(snap.get("unix_time"), (int, float)):
            offset = float(snap["unix_time"]) - (t0 + t1) / 2.0
        perf, _, _, _ = _http_json(url + "/debugz/perf",
                                   self.http_timeout_s)
        healthz, _, _, _ = _http_json(url + "/healthz",
                                      self.http_timeout_s)
        # flight-recorder seq watermark (best-effort): the second skew
        # signal next to train_steps_total — which COLLECTIVE stream is
        # behind, not just which optimizer loop. Narrow catch: an
        # unreachable or non-JSON /debugz/flight simply leaves the
        # watermark None this round.
        flight_seq = None
        try:
            flight, _, _, _ = _http_json(url + "/debugz/flight",
                                         self.http_timeout_s)
            if isinstance(flight.get("next_seq"), (int, float)):
                flight_seq = int(flight["next_seq"])
        except (OSError, ValueError, http.client.HTTPException):
            pass
        # memory plane (best-effort, same narrow-catch contract): a
        # rank without the route or with FLAGS_monitor_memory off just
        # has empty memory columns this round
        memory = None
        try:
            mem, _, _, _ = _http_json(url + "/debugz/memory",
                                      self.http_timeout_s)
            if isinstance(mem, dict):
                memory = mem
        except (OSError, ValueError, http.client.HTTPException):
            pass
        # profiling plane (best-effort, same contract): sampler summary
        # + measured dispatch/blocked/gap per job — feeds the HOSTBLK%
        # column; absent or flags-off ranks just have an empty column
        profile = None
        try:
            prof, _, _, _ = _http_json(url + "/debugz/profile",
                                       self.http_timeout_s)
            if isinstance(prof, dict):
                profile = prof
        except (OSError, ValueError, http.client.HTTPException):
            pass
        # serving-fleet router (best-effort, same contract): a rank
        # hosting a router reports replica/affinity columns so ONE pane
        # shows the training fleet and the serving fleet; every other
        # rank (or a pre-router build) just has empty columns
        router = None
        try:
            rt, _, _, _ = _http_json(url + "/debugz/router",
                                     self.http_timeout_s)
            if isinstance(rt, dict) and rt.get("router"):
                router = rt["router"]
        except (OSError, ValueError, http.client.HTTPException):
            pass
        # SLO + incident planes (best-effort, same contract): the
        # rank's objective verdicts feed the SLO/BUDGET columns and
        # its incident table feeds the merged /debugz/fleet/incidents
        # timeline; a flags-off or pre-ptslo rank just has empty
        # columns this round
        slo = None
        try:
            sl, _, _, _ = _http_json(url + "/debugz/slo",
                                     self.http_timeout_s)
            if isinstance(sl, dict) and sl.get("enabled"):
                slo = sl
        except (OSError, ValueError, http.client.HTTPException):
            pass
        incidents = None
        try:
            inc, _, _, _ = _http_json(url + "/debugz/incidents",
                                      self.http_timeout_s)
            if isinstance(inc, dict) and inc.get("enabled"):
                incidents = inc
        except (OSError, ValueError, http.client.HTTPException):
            pass
        return {"metrics": snap.get("metrics") or {},
                "snapshot_time": snap.get("unix_time"),
                "perf": perf, "healthz": healthz,
                "flight_seq": flight_seq, "memory": memory,
                "profile": profile, "router": router,
                "slo": slo, "incidents": incidents,
                "rtt_s": rtt, "clock_offset_s": offset,
                "scraped_at": time.monotonic()}

    @staticmethod
    def _metric_value(mets, name, kind="sum"):
        """Scalar view of one rank's metric: sum (counters) or max
        (gauges with per-engine labels) across its series."""
        m = mets.get(name)
        if not m:
            return None
        vals = [s.get("value") for s in m.get("series", ())
                if isinstance(s.get("value"), (int, float))]
        if not vals:
            return None
        return sum(vals) if kind == "sum" else max(vals)

    @staticmethod
    def _hist_totals(mets, name):
        """(sum, count) across one rank's histogram series."""
        m = mets.get(name)
        if not m:
            return None
        tot_s, tot_c = 0.0, 0
        for s in m.get("series", ()):
            tot_s += float(s.get("sum", 0.0) or 0.0)
            tot_c += int(s.get("count", 0) or 0)
        return tot_s, tot_c

    def _derive_rank_row(self, rank, st, scraped):
        """Update rank ``st`` with the derived table fields from a
        fresh ``scraped`` payload (step-time window estimate, mfu,
        comm share, heartbeat age, anomaly watermark)."""
        mets = scraped["metrics"]
        now = scraped["scraped_at"]
        prev_sum_count = st.get("_step_hist")
        hist = self._hist_totals(mets, "train_step_seconds")
        step_time = st.get("step_time_s")
        if hist is not None:
            st["_step_hist"] = hist
            if prev_sum_count is not None:
                d_sum = hist[0] - prev_sum_count[0]
                d_count = hist[1] - prev_sum_count[1]
                if d_count > 0:
                    step_time = d_sum / d_count
                    st["last_progress_at"] = now
                elif st.get("last_progress_at") is not None:
                    # no step completed this window: the rank is AT
                    # LEAST this slow — let the estimate grow so a
                    # fully wedged rank trends toward straggler/stall
                    # instead of freezing at its last healthy number
                    stuck = now - st["last_progress_at"]
                    step_time = max(step_time or 0.0, stuck)
            elif hist[1] > 0:
                step_time = hist[0] / hist[1]
                st["last_progress_at"] = now
        st["step_time_s"] = step_time
        st["steps_total"] = self._metric_value(
            mets, "train_steps_total")
        st["tokens_per_s"] = self._metric_value(
            mets, "train_tokens_per_s", kind="max")
        # perf payload: headline efficiency numbers per job
        jobs = (scraped["perf"] or {}).get("jobs") or {}
        mfu = [j.get("mfu") for j in jobs.values()
               if isinstance(j.get("mfu"), (int, float))]
        st["mfu"] = max(mfu) if mfu else None
        hbm = [j.get("hbm_peak_bytes") for j in jobs.values()
               if isinstance(j.get("hbm_peak_bytes"), (int, float))]
        st["hbm_peak_bytes"] = max(hbm) if hbm else None
        comm = [j.get("phase_share", {}).get("comm")
                for j in jobs.values()
                if isinstance(j.get("phase_share", {}).get("comm"),
                              (int, float))]
        st["comm_share"] = max(comm) if comm else None
        goodput = [j.get("serving_goodput_tokens_per_s")
                   for j in jobs.values()
                   if isinstance(j.get("serving_goodput_tokens_per_s"),
                                 (int, float))]
        if goodput:
            st["serving_goodput_tokens_per_s"] = max(goodput)
        # healthz: status + freshest heartbeat age
        hz = scraped["healthz"] or {}
        st["healthz"] = hz.get("status")
        st["degraded"] = bool(hz.get("degraded"))
        ages = [h.get("last_beat_age_s")
                for h in (hz.get("heartbeats") or {}).values()
                if isinstance(h.get("last_beat_age_s"), (int, float))]
        st["heartbeat_age_s"] = min(ages) if ages else None
        st["collective_seq"] = scraped.get("flight_seq")
        # memory columns (monitor/memory.py /debugz/memory): live
        # bytes prefer the allocator witness, fall back to the ledger
        # total (bare workers never import jax, so the witness may be
        # absent while the ledger reports); headroom is the tightest
        # job's
        mem = scraped.get("memory") or {}
        rec = mem.get("reconciliation") or {}
        live = rec.get("live_bytes")
        if not isinstance(live, (int, float)):
            live = rec.get("ledger_bytes")
        st["mem_live_bytes"] = live if isinstance(live, (int, float)) \
            else None
        heads = [j.get("headroom_bytes")
                 for j in (mem.get("jobs") or {}).values()
                 if isinstance(j.get("headroom_bytes"), (int, float))]
        st["mem_headroom_bytes"] = min(heads) if heads else None
        # profiling column (monitor/profile.py): host-blocked share of
        # the LAST measured step window — from the per-step gauges
        # mirrored into the perf job rows, not the lifetime totals (a
        # rank that blocked an hour ago but recovered must not wear a
        # red HOSTBLK% forever). Worst job wins, the memory columns'
        # convention.
        shares = []
        for j in jobs.values():
            d = j.get("profile_dispatch_seconds")
            b = j.get("profile_host_blocked_seconds")
            g = j.get("profile_host_gap_seconds")
            if all(isinstance(x, (int, float)) for x in (d, b, g)) \
                    and (d + b + g) > 0:
                shares.append(b / (d + b + g))
        st["profile_host_blocked_share"] = max(shares) if shares \
            else None
        # the /debugz/profile summary scrape: where the rank's host
        # time goes by the sampler's attribution (dominant component)
        prof = scraped.get("profile") or {}
        comps = prof.get("components") or {}
        st["profile_top_component"] = max(
            comps, key=lambda c: comps[c].get("share", 0)) if comps \
            else None
        # serving-fleet router columns (/debugz/router, best-effort):
        # live replica count + affinity hit rate for a rank hosting a
        # router — None everywhere else (the fleet_top REPLICAS /
        # AFFIN% columns)
        rt = scraped.get("router") or {}
        reps = rt.get("replicas") or {}
        st["router_replicas"] = reps.get("live") \
            if isinstance(reps.get("live"), int) else None
        aff = rt.get("affinity") or {}
        st["router_affinity_hit_rate"] = aff.get("hit_rate") \
            if isinstance(aff.get("hit_rate"), (int, float)) else None
        # SLO columns (/debugz/slo, best-effort): the rank's WORST
        # objective — min attainment and min budget remaining across
        # its judged objectives (the memory columns' worst-wins
        # convention); None for flags-off or pre-ptslo ranks
        slo = scraped.get("slo") or {}
        atts = [o.get("attainment")
                for o in (slo.get("objectives") or ())
                if isinstance(o.get("attainment"), (int, float))]
        st["slo_attainment_min"] = min(atts) if atts else None
        buds = [o.get("budget_remaining_ratio")
                for o in (slo.get("objectives") or ())
                if isinstance(o.get("budget_remaining_ratio"),
                              (int, float))]
        st["slo_budget_min"] = min(buds) if buds else None
        # incident columns + the raw table (the /debugz/fleet/incidents
        # merge reads the latest scraped table per rank)
        incidents = scraped.get("incidents")
        st["incidents_open"] = (
            len(incidents.get("open") or ())
            if isinstance(incidents, dict) else None)
        st["_incidents"] = incidents
        # anomaly watermark: total sentinel firings this rank reports
        anomalies = (scraped["perf"] or {}).get("anomalies") or {}
        st["anomalies_total"] = sum(
            (anomalies.get("counts") or {}).values())
        st["anomaly_kinds"] = sorted((anomalies.get("counts") or {}))

    def _fetch_all(self, endpoints):
        """HTTP-fetch every rank concurrently: a dead rank costs its
        own connect timeout, not a serial stall of the whole round (2
        unreachable ranks at a 3 s timeout must not turn a 2 s scrape
        interval into an 8 s one — detection latency is the product).
        Returns {rank: scraped dict | Exception}. State mutation stays
        on the caller (collector) thread."""
        if len(endpoints) <= 1:
            out = {}
            for rank, url in endpoints.items():
                try:
                    out[rank] = self._scrape_rank(rank, url)
                except Exception as e:
                    out[rank] = e
            return out
        if self._pool is None:
            import concurrent.futures

            self._pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=min(16, max(len(endpoints), 2)),
                thread_name_prefix="pt-fleet-scrape")
        futures = {r: self._pool.submit(self._scrape_rank, r, u)
                   for r, u in endpoints.items()}
        out = {}
        for rank, fut in futures.items():
            try:
                out[rank] = fut.result(timeout=4 * self.http_timeout_s)
            except Exception as e:
                out[rank] = e
        return out

    def scrape_once(self):
        """One collector round: scrape every known endpoint, fuse, run
        straggler detection, maybe trigger a fleet capture. Returns the
        fused metric dict. Never raises (per-rank errors are recorded
        on the rank's row)."""
        endpoints = self._resolve_endpoints()
        fetched = self._fetch_all(endpoints)
        scraped_by_rank = {}
        for rank, url in sorted(endpoints.items()):
            # row INSERTION under the lock: route handlers iterate
            # _ranks concurrently, and a mid-iteration resize would
            # 500 the fleet view exactly during fleet bring-up (field
            # updates on an existing row dict are fine unlocked)
            with self._lock:
                st = self._ranks.setdefault(rank, {"rank": rank})
            st["url"] = url
            scraped = fetched.get(rank)
            if isinstance(scraped, Exception) or scraped is None:
                st["ok"] = False
                st["error"] = repr(scraped)
                st["consecutive_errors"] = \
                    st.get("consecutive_errors", 0) + 1
                _SCRAPE_ERRS.labels(rank=rank).inc()
                continue
            st["ok"] = True
            st["error"] = None
            st["consecutive_errors"] = 0
            st["scraped_at"] = scraped["scraped_at"]
            # min-RTT clock sample wins (NTP discipline): a slow scrape
            # mid-GC must not wobble an already-good offset estimate
            if scraped["clock_offset_s"] is not None and (
                    st.get("rtt_s") is None
                    or scraped["rtt_s"] <= st["rtt_s"]):
                st["rtt_s"] = scraped["rtt_s"]
                st["clock_offset_s"] = scraped["clock_offset_s"]
            self._derive_rank_row(rank, st, scraped)
            scraped_by_rank[rank] = scraped
        fused = fuse_snapshots(
            {r: s["metrics"] for r, s in scraped_by_rank.items()})
        anomaly_ranks = self._advance_anomaly_watermarks(scraped_by_rank)
        with self._lock:
            if scraped_by_rank:
                self._fused = fused
            # else: keep the last good fused view — a transient
            # full-fleet outage is visible on the per-rank rows
            # (ok=False + consecutive_errors), not by silently
            # blanking every aggregate
            self._scrapes += 1
            self._last_scrape_at = time.time()
        _SCRAPES.inc()
        _RANKS_OK.set(len(scraped_by_rank))
        new_stragglers = self._detect_stragglers()
        if anomaly_ranks:
            self._maybe_capture(
                "anomaly", {"ranks": sorted(anomaly_ranks)})
        if new_stragglers:
            self._maybe_capture(
                "straggler", {"ranks": sorted(new_stragglers)})
            # ptprof (monitor/profile.py): a fresh straggler also arms
            # a local device-capture window — the per-rank folded
            # stacks ride the fleet capture's /debugz/profile pulls,
            # this adds the collector rank's own Xprof window. No-op
            # while FLAGS_monitor_profile is off.
            try:
                from . import profile as _profile

                _profile.on_straggler(sorted(new_stragglers))
            except Exception as e:
                _registry.warn_once(
                    "fleet.profile_arm",
                    "paddle_tpu.monitor.fleet: profile capture arming "
                    "failed (straggler was still flagged): %r" % (e,))
        # flush triggers the cooldown deferred: their watermarks have
        # already advanced and will not re-fire on their own
        self._maybe_capture()
        return fused

    def _advance_anomaly_watermarks(self, scraped_by_rank):
        """Ranks whose sentinel firing count advanced (or that turned
        degraded) since the previous round — the capture trigger."""
        fired = set()
        for rank, scraped in scraped_by_rank.items():
            st = self._ranks[rank]
            total = st.get("anomalies_total") or 0
            mark = st.get("_anomaly_mark")
            degraded = st.get("degraded", False)
            was_degraded = st.get("_was_degraded", False)
            if mark is not None and total > mark:
                fired.add(rank)
            elif degraded and not was_degraded:
                fired.add(rank)
            st["_anomaly_mark"] = total
            st["_was_degraded"] = degraded
        return fired

    # -- straggler detection -----------------------------------------------

    def _detect_stragglers(self):
        """Cross-rank step-time comparison: flag ranks persistently
        slower than ``straggler_factor`` x the fleet median. Returns
        the set of NEWLY flagged ranks (an episode fires once; a rank
        that recovers clears its episode and can re-fire)."""
        rows = {r: st for r, st in self._ranks.items()
                if st.get("ok") and isinstance(st.get("step_time_s"),
                                               (int, float))}
        newly = set()
        if len(rows) >= 2:
            times = sorted(st["step_time_s"] for st in rows.values())
            # LOWER median on even fleets: in a 2-rank world the upper
            # median IS the slow rank's own time (nothing could ever be
            # flagged); the lower median compares each rank against the
            # healthy half's pace
            median = times[(len(times) - 1) // 2]
            steps = [st.get("steps_total") for st in rows.values()
                     if isinstance(st.get("steps_total"), (int, float))]
            front = max(steps) if steps else None
            seqs = [st.get("collective_seq") for st in rows.values()
                    if isinstance(st.get("collective_seq"), int)]
            front_seq = max(seqs) if seqs else None
            for r, st in rows.items():
                if front is not None and \
                        isinstance(st.get("steps_total"), (int, float)):
                    st["steps_behind"] = max(
                        int(front - st["steps_total"]), 0)
                if front_seq is not None and \
                        isinstance(st.get("collective_seq"), int):
                    st["collective_seq_behind"] = \
                        front_seq - st["collective_seq"]
                slow = median > 0 and \
                    st["step_time_s"] > self.straggler_factor * median
                if slow:
                    st["slow_hits"] = st.get("slow_hits", 0) + 1
                else:
                    st["slow_hits"] = 0
                    if r in self._stragglers:
                        # recovered: close the episode so a relapse
                        # counts as a fresh straggler_total increment
                        # — and resolve its incident (the table lives
                        # on the collector, which detected it; no-op
                        # branch while the SLO plane is off)
                        self._stragglers.pop(r, None)
                        st["straggler"] = False
                        try:
                            from . import incidents as _incidents

                            _incidents.resolve(
                                "fleet/straggler/rank%d" % r,
                                reason="step time recovered to fleet "
                                "pace")
                        except Exception as e:
                            _registry.warn_once(
                                "fleet.incident_resolve",
                                "paddle_tpu.monitor.fleet: straggler "
                                "incident resolve failed (episode "
                                "still closed): %r" % (e,))
                if st.get("slow_hits", 0) >= self.straggler_persist \
                        and r not in self._stragglers:
                    info = {
                        "rank": r,
                        "step_time_s": st["step_time_s"],
                        "fleet_median_s": median,
                        "factor": self.straggler_factor,
                        "flagged_at": time.time(),
                        "steps_behind": st.get("steps_behind"),
                    }
                    with self._lock:
                        self._stragglers[r] = info
                    st["straggler"] = True
                    newly.add(r)
                    _STRAGGLER_TOTAL.labels(rank=r).inc()
                    # ptslo: ONE incident per straggler episode,
                    # naming the guilty rank; the recovery branch
                    # above resolves it, a relapse opens a fresh one
                    try:
                        from . import incidents as _incidents

                        _incidents.open(
                            "fleet/straggler/rank%d" % r,
                            severity="ticket", kind="straggler",
                            source="fleet", rank=r,
                            summary="rank %d straggling: step %.3fs "
                            "vs fleet median %.3fs" % (
                                r, st["step_time_s"], median),
                            evidence=dict(info))
                    except Exception as e:
                        _registry.warn_once(
                            "fleet.incident_open",
                            "paddle_tpu.monitor.fleet: straggler "
                            "incident open failed (episode still "
                            "flagged): %r" % (e,))
        return newly

    # -- anomaly-triggered fleet capture -------------------------------------

    def _maybe_capture(self, reason=None, detail=None):
        """Capture-with-cooldown. A trigger arriving inside the
        cooldown is QUEUED, never dropped (its watermark has already
        advanced and will not re-fire); the next eligible round fires
        one capture for the oldest pending trigger, with any later
        ones folded into its detail under ``also`` — distinct
        incidents keep their reason/detail attribution in the
        manifest. ``reason=None`` = flush-pending only. The cooldown
        interval is measured on the monotonic clock — an NTP step must
        neither extend nor collapse it."""
        now = time.monotonic()
        if reason is not None:
            self._pending_captures.append((reason, detail or {}))
        if not self._pending_captures:
            return None
        if self._last_capture_at is not None and \
                now - self._last_capture_at < self.capture_cooldown_s:
            return None
        if len(self._captures) >= self.max_captures:
            self._pending_captures = []
            return None
        pending, self._pending_captures = self._pending_captures, []
        reason, detail = pending[0]
        if len(pending) > 1:
            detail = dict(detail)
            detail["also"] = [{"reason": r, "detail": d}
                              for r, d in pending[1:]]
        self._last_capture_at = now
        try:
            return self.capture(reason, detail)
        except Exception as e:
            _registry.warn_once(
                "fleet.capture",
                "paddle_tpu.monitor.fleet: anomaly capture failed "
                "(trigger %r consumed, no capture dir written): %r"
                % (reason, e))
            return None

    def capture(self, reason="manual", detail=None):
        """Pull watchdog-style bundles + trace-journal tails from every
        reachable rank into one ``fleet_capture_<ts>/`` directory.
        Returns the capture dir path."""
        ts = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
        d = os.path.join(self.capture_dir, "fleet_capture_%s" % ts)
        n = 1
        while os.path.exists(d):
            d = os.path.join(self.capture_dir,
                             "fleet_capture_%s_%d" % (ts, n))
            n += 1
        os.makedirs(d, exist_ok=True)
        # resolve ONCE: discovery does blocking store reads for absent
        # ranks (the normal state mid-incident), and the pull loop and
        # manifest must agree on the endpoint set
        endpoints = self._resolve_endpoints()
        got_ranks = []
        for rank, url in sorted(endpoints.items()):
            ok = True
            for route, stem in (("debugz/bundle", "bundle"),
                                ("debugz/trace/journal", "journal"),
                                ("debugz/memory", "memory"),
                                ("debugz/profile", "profile")):
                try:
                    payload, _, _, _ = _http_json(
                        "%s/%s" % (url, route), self.http_timeout_s)
                except Exception as e:
                    payload = {"error": repr(e), "rank": rank,
                               "route": route}
                    ok = False
                path = os.path.join(d, "%s_rank%d.json" % (stem, rank))
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(payload, f, indent=1, default=str)
                    f.write("\n")
                os.replace(tmp, path)
            if ok:
                got_ranks.append(rank)
        # the serving-fleet router (when one runs in THIS process —
        # the tools/serving_router.py shape) journals the dispatch
        # half of every fleet trace: write its journal locally so the
        # capture carries router+replica fragments of one incident
        router_journal = None
        if _sfleet_enabled() and _router_hook is not None:
            from . import trace as _trace
            if _trace.is_enabled():
                path = os.path.join(d, "journal_router.json")
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(_trace.dump(), f, indent=1, default=str)
                    f.write("\n")
                os.replace(tmp, path)
                router_journal = "journal_router.json"
        manifest = {
            "kind": "fleet_capture",
            "version": 1,
            "router_journal": router_journal,
            "reason": reason,
            "detail": detail or {},
            "created_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                        time.gmtime()),
            "unix_time": time.time(),
            "world_size": self.world_size,
            "ranks": got_ranks,
            "endpoints": {str(r): u for r, u in
                          sorted(endpoints.items())},
            "clock_offsets_s": {
                str(r): st.get("clock_offset_s")
                for r, st in self._rank_items()
                if st.get("clock_offset_s") is not None},
            "stragglers": {str(r): i for r, i in
                           sorted(self._stragglers.items())},
            # causality: the open incidents known fleet-wide when the
            # capture fired — the triggering incident's id is in here
            # (its detector opened it before the watermark advanced).
            # Empty while FLAGS_monitor_slo is off everywhere.
            "incidents": self._known_open_incident_ids(),
        }
        tmp = os.path.join(d, "manifest.json.tmp")
        with open(tmp, "w") as f:
            json.dump(manifest, f, indent=1, default=str)
            f.write("\n")
        os.replace(tmp, os.path.join(d, "manifest.json"))
        rec = {"dir": d, "reason": reason, "detail": detail or {},
               "created_at": manifest["unix_time"],
               "ranks": got_ranks,
               "incidents": manifest["incidents"]}
        with self._lock:
            self._captures.append(rec)
        _CAPTURES_TOTAL.labels(reason=reason).inc()
        # back-link: the collector's OWN open incidents (stragglers,
        # local detectors) get the capture dir as evidence — remote
        # incidents get the link at merge time via the manifest ids
        try:
            from . import incidents as _incidents

            for inc in _incidents.open_incidents():
                _incidents.add_evidence(inc["key"], capture_dir=d)
        except Exception as e:
            _registry.warn_once(
                "fleet.capture_evidence",
                "paddle_tpu.monitor.fleet: capture evidence back-link "
                "failed (capture %s still written): %r" % (d, e))
        return d

    def _known_open_incident_ids(self):
        """Open incident ids across the collector's own table and the
        latest scraped table of every rank (deduped — the collector's
        process may also be a scraped rank)."""
        ids = []
        try:
            from . import incidents as _incidents

            for inc in _incidents.open_incidents():
                ids.append(inc["id"])
        except Exception as e:
            _registry.warn_once(
                "fleet.incident_ids",
                "paddle_tpu.monitor.fleet: local incident-id walk "
                "failed (scraped ids still recorded): %r" % (e,))
        for _, st in self._rank_items():
            pay = st.get("_incidents")
            if isinstance(pay, dict):
                for inc in pay.get("open") or ():
                    if inc.get("id"):
                        ids.append(inc["id"])
        return sorted(set(ids))

    # -- payloads ------------------------------------------------------------

    def _rank_items(self):
        """Sorted (rank, row) pairs, snapshotted under the lock (rows
        are inserted by the collector thread while route handlers
        read)."""
        with self._lock:
            return sorted(self._ranks.items())

    def ranks_table(self):
        """Per-rank table rows (the /debugz/fleet/ranks body and the
        fleet_top columns), sorted by rank. Freshness ages subtract
        monotonic stamps (``scraped_at`` is monotonic)."""
        now = time.monotonic()
        rows = []
        for r, st in self._rank_items():
            rows.append({k: st.get(k) for k in (
                "rank", "url", "ok", "error", "consecutive_errors",
                "steps_total", "steps_behind", "collective_seq",
                "collective_seq_behind", "step_time_s",
                "tokens_per_s", "mfu", "hbm_peak_bytes",
                "mem_live_bytes", "mem_headroom_bytes",
                "profile_host_blocked_share", "profile_top_component",
                "comm_share",
                "serving_goodput_tokens_per_s", "heartbeat_age_s",
                "healthz", "degraded", "anomalies_total",
                "anomaly_kinds", "straggler", "slow_hits",
                "router_replicas", "router_affinity_hit_rate",
                "slo_attainment_min", "slo_budget_min",
                "incidents_open",
                "clock_offset_s", "rtt_s")})
            rows[-1]["scrape_age_s"] = (
                round(now - st["scraped_at"], 3)
                if st.get("scraped_at") else None)
        return rows

    def summary(self):
        """The /debugz/fleet body: collector state, straggler verdict,
        fleet aggregates (per-rank series live at /debugz/fleet/ranks
        and /metrics/fleet)."""
        with self._lock:
            fused = self._fused
            stragglers = dict(self._stragglers)
            captures = list(self._captures)
            scrapes = self._scrapes
            last = self._last_scrape_at
            rank_rows = list(self._ranks.items())
        aggregates = {}
        for name, ent in fused.items():
            aggregates[name] = {
                "kind": ent["kind"],
                "series": [{"labels": s["labels"], "fleet": s["fleet"]}
                           for s in ent["series"]],
            }
        ok = [r for r, st in rank_rows if st.get("ok")]
        return {
            "enabled": True,
            "collector": {
                "running": self.is_running(),
                "rank": self.rank,
                "interval_s": self.interval_s,
                "scrapes": scrapes,
                "started_at": self._started_at,
                "last_scrape_at": last,
            },
            "world_size": self.world_size,
            "ranks_known": sorted(r for r, _ in rank_rows),
            "ranks_ok": sorted(ok),
            "straggler_policy": {
                "factor": self.straggler_factor,
                "persist": self.straggler_persist,
                "signal": "train_step_seconds windowed mean vs fleet "
                          "median",
            },
            "stragglers": {str(r): i for r, i in
                           sorted(stragglers.items())},
            "captures": captures,
            "aggregates": aggregates,
            "time": time.time(),
        }

    def fused(self):
        with self._lock:
            return dict(self._fused)

    def prometheus_text(self):
        """Federation-style exposition of the fused fleet view: every
        scraped counter/gauge series re-exposed with a ``rank`` label,
        plus fleet aggregates (``_fleet_sum`` for counters,
        ``_fleet_min``/``_fleet_max``/``_fleet_p50`` for gauges,
        bucket-wise-summed ``_fleet`` histograms)."""
        with self._lock:
            fused = dict(self._fused)
        lines = []
        for name in sorted(fused):
            ent = fused[name]
            kind = ent["kind"]
            if kind == "histogram":
                lines.append("# TYPE %s_fleet histogram" % name)
                for se in ent["series"]:
                    lbl = dict(se["labels"])
                    fl = se["fleet"]
                    for b in sorted(fl.get("buckets", {}),
                                    key=lambda x: float(x)):
                        lines.append("%s %d" % (_series(
                            "%s_fleet_bucket" % name,
                            dict(lbl, le=b)), fl["buckets"][b]))
                    lines.append("%s %d" % (_series(
                        "%s_fleet_bucket" % name,
                        dict(lbl, le="+Inf")), fl.get("count", 0)))
                    lines.append("%s %s" % (_series(
                        "%s_fleet_sum" % name, lbl),
                        _registry._fmt(fl.get("sum", 0.0))))
                    lines.append("%s %d" % (_series(
                        "%s_fleet_count" % name, lbl),
                        fl.get("count", 0)))
                continue
            lines.append("# TYPE %s %s" % (name, kind))
            for se in ent["series"]:
                # a scraped series that ALREADY carries a rank label
                # (fleet_straggler_total{rank}, fleet_rank_info) keeps
                # it — clobbering would misattribute it to the scraped
                # rank and collapse distinct series into duplicate
                # exposition lines; the scrape origin rides a separate
                # label instead
                origin = "scraped_rank" if "rank" in se["labels"] \
                    else "rank"
                for rank in sorted(se["per_rank"]):
                    lines.append("%s %s" % (_series(
                        name, dict(se["labels"], **{origin: rank})),
                        _registry._fmt(se["per_rank"][rank])))
            if kind == "counter":
                lines.append("# TYPE %s_fleet_sum counter" % name)
                for se in ent["series"]:
                    if "sum" in se["fleet"]:
                        lines.append("%s %s" % (_series(
                            "%s_fleet_sum" % name, se["labels"]),
                            _registry._fmt(se["fleet"]["sum"])))
            elif kind == "gauge":
                for stat in ("min", "max", "p50"):
                    lines.append("# TYPE %s_fleet_%s gauge"
                                 % (name, stat))
                    for se in ent["series"]:
                        if stat in se["fleet"]:
                            lines.append("%s %s" % (_series(
                                "%s_fleet_%s" % (name, stat),
                                se["labels"]),
                                _registry._fmt(se["fleet"][stat])))
        return "\n".join(lines) + "\n"


def _series(name, labels):
    if not labels:
        return name
    keys = sorted(labels)
    return _registry._series(name, keys, [labels[k] for k in keys])


# -- process-wide collector + route payloads ---------------------------------

_collector = None


def get_collector():
    return _collector


def start_collector(**kw):
    """Start (or return) the process-wide collector thread."""
    global _collector
    if _collector is None or not _collector.is_running():
        _collector = FleetCollector(**kw).start()
    return _collector


def stop_collector(snapshot_out=None):
    global _collector
    if _collector is not None:
        _collector.stop(snapshot_out=snapshot_out)
        _collector = None


def fleet_payload():
    """The /debugz/fleet body (route-pinned 200 whether or not a
    collector runs here: "off/elsewhere" is a payload, not an error)."""
    c = _collector
    if c is None:
        return {"enabled": is_enabled(), "collector": None,
                "announced_url": _announce.url, "time": time.time()}
    out = c.summary()
    out["enabled"] = is_enabled()
    out["announced_url"] = _announce.url
    return out


def ranks_payload():
    """The /debugz/fleet/ranks body."""
    c = _collector
    if c is None:
        return {"enabled": is_enabled(), "collector": None,
                "ranks": [], "time": time.time()}
    with c._lock:
        stragglers = sorted(c._stragglers)
        scrapes = c._scrapes
    return {"enabled": is_enabled(),
            "collector": {"running": c.is_running(),
                          "scrapes": scrapes},
            "world_size": c.world_size,
            "stragglers": stragglers,
            "ranks": c.ranks_table(),
            "time": time.time()}


def fleet_incidents_payload():
    """The /debugz/fleet/incidents body: one clock-offset-aligned
    fleet-wide incident timeline — the collector's own table merged
    with the latest scraped table of every rank, deduped by incident
    id (ids embed (rank, pid), so the collector re-seeing its own
    rank's table, or re-scraping a rank, never duplicates an
    episode). Peer wall stamps are shifted onto the collector's clock
    by the per-rank NTP-style offsets (the trace_merge discipline);
    capture manifests' incident ids back-link each merged incident to
    its capture dir."""
    from . import incidents as _incidents

    if not _incidents.is_enabled():
        return {"enabled": False, "incidents": []}
    merged = {}
    local = _incidents.payload()
    for inc in (local.get("open") or []) + \
            (local.get("resolved") or []):
        e = dict(inc)
        e["evidence"] = dict(e.get("evidence") or {})
        e["origin"] = "local"
        e["origin_rank"] = e.get("rank")
        merged[e["id"]] = e
    c = _collector
    ranks_merged = []
    if c is not None:
        for r, st in c._rank_items():
            pay = st.get("_incidents")
            if not isinstance(pay, dict):
                continue
            ranks_merged.append(r)
            offset = st.get("clock_offset_s") or 0.0
            for inc in (pay.get("open") or []) + \
                    (pay.get("resolved") or []):
                if not inc.get("id"):
                    continue
                prev = merged.get(inc["id"])
                if prev is not None and prev.get("origin") == "local":
                    continue    # our own table is fresher than a scrape
                e = dict(inc)
                e["evidence"] = dict(e.get("evidence") or {})
                e["origin"] = "rank%d" % r
                e["origin_rank"] = r
                # align the peer's wall stamps onto the collector's
                # clock (display metadata only — never subtracted)
                for k in ("opened_at", "last_seen", "resolved_at"):
                    if isinstance(e.get(k), (int, float)):
                        e[k] = e[k] - offset
                merged[e["id"]] = e
        with c._lock:
            captures = list(c._captures)
        for cap in captures:
            for iid in cap.get("incidents") or ():
                if iid in merged:
                    merged[iid]["evidence"].setdefault(
                        "capture_dir", cap["dir"])
    timeline = sorted(merged.values(),
                      key=lambda e: (e.get("opened_at") or 0,
                                     e["id"]))
    open_n = sum(1 for e in timeline if e.get("state") == "open")
    return {
        "enabled": True,
        "collector": c is not None,
        "ranks_merged": ranks_merged,
        "counts": {"total": len(timeline), "open": open_n,
                   "resolved": len(timeline) - open_n},
        "incidents": timeline,
        "time": time.time(),
    }


def prometheus_fleet_text():
    """The /metrics/fleet exposition body."""
    c = _collector
    if c is None:
        return ("# fleet collector not running on this rank "
                "(FLAGS_monitor_fleet=%s)\n" % ("on" if is_enabled()
                                                else "off"))
    return c.prometheus_text()


# -- serving-fleet router hook (the /debugz/router routes) -------------------
#
# serving/fleet/router.py sets this slot when a Router starts on this
# process; the monitor plane never imports the serving package (the
# hook is duck-typed: any object with debug_payload() /
# replicas_debug_payload()). With FLAGS_serving_fleet off the slot
# stays None and the routes report the pinned disabled body —
# no serving import, no store traffic (test-pinned).

_router_hook = None


def set_router_hook(router):
    global _router_hook
    _router_hook = router


def clear_router_hook():
    global _router_hook
    _router_hook = None


def _sfleet_enabled():
    return _flag("FLAGS_serving_fleet")


def router_payload():
    """The /debugz/router body."""
    if not _sfleet_enabled():
        return {"enabled": False, "router": None}
    r = _router_hook
    if r is None:
        return {"enabled": True, "router": None,
                "time": time.time()}
    return {"enabled": True, "router": r.debug_payload(),
            "time": time.time()}


def router_replicas_payload():
    """The /debugz/router/replicas body."""
    if not _sfleet_enabled():
        return {"enabled": False, "replicas": []}
    r = _router_hook
    if r is None:
        return {"enabled": True, "replicas": [],
                "time": time.time()}
    return {"enabled": True, "replicas": r.replicas_debug_payload(),
            "time": time.time()}


def router_trace_federation(trace_id):
    """The ``federation`` block a router process's ``/debugz/trace/
    {id}`` attaches: the replica-side fragments of one fleet trace,
    fetched on demand through the hook. ``{"enabled": False}`` — and
    ZERO cross-replica fetches — whenever FLAGS_serving_fleet is off
    or no router runs here (test-pinned)."""
    if not _sfleet_enabled() or _router_hook is None:
        return {"enabled": False}
    segments = getattr(_router_hook, "trace_segments", None)
    if segments is None:
        return {"enabled": True, "segments": {}}
    return dict(segments(trace_id), enabled=True)


# -- fleet snapshot artifact ---------------------------------------------------

def snapshot_dict(collector=None):
    """JSON-ready fleet snapshot: the per-rank table + aggregates that
    tools/fleet_battery.py writes as ``tools/fleet_snapshot.json``."""
    c = collector or _collector
    if c is None:
        return {"kind": "fleet_snapshot", "version": 1, "ok": False,
                "error": "no collector"}
    summary = c.summary()
    return {
        "kind": "fleet_snapshot",
        "version": 1,
        # ok = real fused data exists (a run that ENDED before the
        # final scrape still has its last good rounds; per-rank ok
        # flags on the rows carry the momentary reachability)
        "ok": bool(summary["aggregates"]),
        "written_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "unix_time": time.time(),
        "pid": os.getpid(),
        "world_size": summary["world_size"],
        "scrapes": summary["collector"]["scrapes"],
        "ranks_ok": summary["ranks_ok"],
        "ranks": c.ranks_table(),
        "stragglers": summary["stragglers"],
        "straggler_policy": summary["straggler_policy"],
        "captures": summary["captures"],
        "aggregates": summary["aggregates"],
    }


def write_snapshot_artifact(path, collector=None):
    """Write the fleet snapshot artifact and return the dict. A round
    that produced NOTHING scrapeable (``ok`` false) is returned but not
    written: a missing measurement leaves no file behind, and the
    caller's exit code says so."""
    snap = snapshot_dict(collector)
    if not snap.get("ok"):
        snap.setdefault("error", "no rank answered the scrape")
        return snap
    d = os.path.dirname(os.path.abspath(path))
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(snap, f, indent=1, default=str)
        f.write("\n")
    os.replace(tmp, path)
    return snap
