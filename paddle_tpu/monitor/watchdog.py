"""Progress watchdog: heartbeat registry, stall detection, diagnostic bundles.

The flight recorder (monitor/flight_recorder.py) is TIMEOUT-triggered:
it only speaks when a store-backed collective gives up. The dominant
production failure modes never get that far — a compiled step hung on
the device, a serving-scheduler deadlock, a rank that silently died —
so this module adds the PROGRESS-triggered half of the postmortem
surface:

1. **Heartbeats** — long-running loops report progress through a named
   ``Heartbeat``: the compiled train step (parallel/engine.py), the
   serving engine loop (serving/engine.py), and store-backed collectives
   (distributed/process_group.py, bracketing the flight-recorder entry
   so "in collective gseq=N for 40s" is distinguishable from "stuck
   between steps"). ``beat()`` marks progress; ``busy(phase)`` marks an
   in-flight region. Stalls are only armed INSIDE a busy bracket — a
   loop that exited cleanly and went idle is not a stall, which is what
   keeps a clean tier-1 run under an enabled watchdog free of false
   positives.

2. **Watchdog daemon thread** — started by ``start_watchdog()`` or the
   ``PT_WATCHDOG=1`` env flag; polls the heartbeats and, when an active
   phase stops advancing past ``PT_WATCHDOG_STALL_S`` (default 60),
   emits a **diagnostic bundle**: every Python thread's stack, the
   flight-recorder ring, a metric-registry snapshot, and per-heartbeat
   ages — written to ``PT_MONITOR_DUMP_DIR`` as
   ``watchdog_bundle_rank{r}.json``.

3. **Cross-rank gather** — in multi-rank runs (a world
   StoreProcessGroup exists) the firing rank publishes a bundle REQUEST
   through the TCPStore; every rank's watchdog answers with its own
   bundle (the stalled rank's daemon thread is alive even while its
   main thread sleeps — that is how the postmortem gets the guilty
   stack). Each watchdog also refreshes a liveness lease every tick, so
   a rank that died outright is named by lease expiry. The gathered
   bundles are diagnosed (``diagnose_bundles``) and persisted as
   ``watchdog_postmortem_rank{r}.json`` naming the stalled (or dead)
   rank — the same barrier-free gather discipline the flight recorder
   uses.

4. **Live endpoints** — monitor/exporter.py registers ``/healthz``
   (ok|stalled verdict + heartbeat ages; HTTP 503 when stalled),
   ``/debugz/stacks``, ``/debugz/flight`` and ``/debugz/bundle`` on the
   fleet KV HTTP server; tools/debug_bundle.py fetches and merges them
   across ranks.

Disabled by default with the registry's discipline: ``beat``/``busy``
early-return (no locks, no native calls), and no daemon thread exists —
both asserted by tests/test_watchdog.py.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
import traceback

from . import registry as _registry
from .flight_recorder import get_flight_recorder

_WD_PREFIX = "__wd"
_THREAD_NAME = "pt-watchdog"


def _env_truthy(name, default="0"):
    return os.environ.get(name, default).lower() in ("1", "true", "on")


class _WDState:
    __slots__ = ("enabled", "autostart", "thread", "stop_event",
                 "stall_threshold_s", "poll_interval_s", "grace_s",
                 "lease_s", "fired", "last_request_answered",
                 "healthz_out", "dump_dir", "action")

    def __init__(self):
        self.enabled = False
        self.autostart = _env_truthy("PT_WATCHDOG")
        self.thread = None
        self.stop_event = None
        self.stall_threshold_s = float(
            os.environ.get("PT_WATCHDOG_STALL_S", "60"))
        self.poll_interval_s = None
        self.grace_s = 5.0      # re-derived from the poll interval at start
        self.lease_s = None
        self.fired = {}
        self.last_request_answered = None   # nonce of the last answered req
        self.healthz_out = None
        self.dump_dir = None
        # escalation mode (PT_WATCHDOG_ACTION): "bundle" (default) =
        # diagnose only; "recover" = additionally invoke the registered
        # stall actions (resilience layer hooks) so a stalled bracket
        # can TRIGGER recovery instead of only writing a postmortem
        self.action = os.environ.get("PT_WATCHDOG_ACTION", "bundle")


_state = _WDState()
# stall-action hooks (escalation targets): called from the daemon
# thread on a FRESH stall episode when PT_WATCHDOG_ACTION=recover.
# The resilience layer registers here (ResilientTrainLoop requests a
# snapshot-resume, a serving wrapper can request drain); hooks must be
# quick + non-blocking (set a flag the owning loop consumes) and must
# never raise — a recovery hook that wedges the watchdog would be the
# failure it exists to fix.
_stall_actions = []
_hb_lock = threading.Lock()
# RLock: the restart path (explicit config while running) stops the old
# thread from inside start_watchdog. Guards against two threads racing
# the PT_WATCHDOG autostart and leaking an unstoppable duplicate daemon.
_lifecycle_lock = threading.RLock()
_heartbeats = {}


# -- heartbeats --------------------------------------------------------------

class _NoopBusy:
    """Shared disabled-path context manager: zero allocations per use."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP_BUSY = _NoopBusy()


class _Busy:
    __slots__ = ("_hb", "_phase", "_info", "_token")

    def __init__(self, hb, phase, info):
        self._hb = hb
        self._phase = phase
        self._info = info

    def __enter__(self):
        self._token = self._hb._enter_phase(self._phase, self._info)
        return self

    def __exit__(self, *exc):
        self._hb._exit_phase(self._token)
        return False


class Heartbeat:
    """One named progress source. ``beat()`` marks progress; ``busy()``
    marks an in-flight region — a stall is an active busy phase whose
    most recent progress (phase entry or any beat since) is older than
    the watchdog threshold."""

    def __init__(self, name):
        self.name = name
        self._lock = threading.Lock()
        self.beats = 0
        self.last_beat = None
        self._phases = {}       # token -> {"phase", "info", "since"}
        self._next_token = 0

    def beat(self, n=1):
        if not _state.enabled:
            return
        now = time.monotonic()
        tid = threading.get_ident()
        with self._lock:
            self.beats += n
            self.last_beat = now
            # progress is tracked PER PHASE, attributed by thread: a
            # beat from thread T only refreshes T's own in-flight
            # phases — another thread's completed work must not mask a
            # wedged one on the same (process-wide) heartbeat
            for p in self._phases.values():
                if p["tid"] == tid:
                    p["progress"] = now

    def busy(self, phase, **info):
        """Context manager marking an in-flight region (arms stall
        detection for its duration). ``info`` rides into healthz and
        bundles — the collective bracket passes op/seq/gseq/group."""
        if not _state.enabled:
            return _NOOP_BUSY
        return _Busy(self, phase, info)

    def _enter_phase(self, phase, info):
        now = time.monotonic()
        with self._lock:
            token = self._next_token
            self._next_token += 1
            self._phases[token] = {"phase": phase, "info": info,
                                   "since": now, "progress": now,
                                   "tid": threading.get_ident()}
            return token

    def _exit_phase(self, token):
        now = time.monotonic()
        tid = threading.get_ident()
        with self._lock:
            self._phases.pop(token, None)
            self.beats += 1
            self.last_beat = now
            # a nested phase completing IS progress for its enclosing
            # phases on the same thread (the serving run loop's steps)
            for p in self._phases.values():
                if p["tid"] == tid:
                    p["progress"] = now

    def snapshot(self, now=None):
        """Ages are computed on the MONOTONIC clock (``since`` /
        ``last_beat`` are monotonic stamps, compared only within this
        process): a wall-clock NTP step larger than the stall threshold
        must not fire a false stall storm or mask a real hang."""
        now = now or time.monotonic()
        with self._lock:
            phases = [{
                "phase": p["phase"],
                "info": dict(p["info"]),
                "since": p["since"],
                "age_s": round(now - p["progress"], 3),
            } for p in self._phases.values()]
        return {
            "name": self.name,
            "beats": self.beats,
            "last_beat": self.last_beat,
            "last_beat_age_s": (round(now - self.last_beat, 3)
                                if self.last_beat is not None else None),
            "active_phases": sorted(phases, key=lambda p: p["since"]),
        }


def heartbeat(name):
    """Get-or-create the process-wide heartbeat ``name``. First call
    auto-starts the watchdog when the ``PT_WATCHDOG`` env flag is set
    (the one-env-flag enable path)."""
    hb = _heartbeats.get(name)
    if hb is None:
        with _hb_lock:
            hb = _heartbeats.setdefault(name, Heartbeat(name))
    if _state.autostart and not _state.enabled:
        start_watchdog()
    return hb


def heartbeats_snapshot(now=None):
    now = now or time.monotonic()
    with _hb_lock:
        hbs = list(_heartbeats.values())
    return {hb.name: hb.snapshot(now) for hb in hbs}


def _find_stalls(now=None, threshold_s=None):
    """Active busy phases older than the stall threshold (monotonic)."""
    now = now or time.monotonic()
    if threshold_s is None:
        threshold_s = _state.stall_threshold_s
    stalls = []
    for name, snap in heartbeats_snapshot(now).items():
        for p in snap["active_phases"]:
            if p["age_s"] > threshold_s:
                stalls.append({
                    "heartbeat": name,
                    "phase": p["phase"],
                    "info": p["info"],
                    "age_s": p["age_s"],
                    "since": p["since"],
                    "threshold_s": threshold_s,
                })
    return stalls


# -- bundle assembly ---------------------------------------------------------

def thread_stacks():
    """Every Python thread's current stack (the py-spy-at-home core of
    the bundle — works from the daemon thread while the main thread is
    wedged)."""
    names = {t.ident: (t.name, t.daemon) for t in threading.enumerate()}
    stacks = []
    for ident, frame in sys._current_frames().items():
        name, daemon = names.get(ident, ("?", None))
        frames = [{"file": f.filename, "line": f.lineno, "func": f.name,
                   "code": (f.line or "").strip()}
                  for f in traceback.extract_stack(frame)]
        stacks.append({"thread_id": ident, "name": name,
                       "daemon": daemon, "frames": frames})
    return sorted(stacks, key=lambda s: str(s["name"]))


def _world():
    """(pg, rank, world_size) of the world group, or (None, 0, 1)."""
    from ..distributed import process_group as _pg

    pg = _pg.get_world_group()
    if pg is None:
        return None, 0, 1
    return pg, pg.rank, pg.world_size


def build_bundle(reason="debugz", stalls=None):
    """One rank's full diagnostic bundle (stdlib-only, JSON-ready)."""
    now = time.time()       # provenance stamps only; ages are monotonic
    pg, rank, world = _world()
    if stalls is None:
        stalls = _find_stalls() if _state.enabled else []
    try:
        metrics = _registry.get_registry().snapshot()
    except Exception:
        metrics = {}
    try:
        flight = get_flight_recorder().dump(rank, world)
    except Exception:
        flight = {}
    # time-series tail (monitor/timeseries.py, ring enabled): the
    # deceleration leading INTO the stall — step time, throughput, and
    # comm series — not just the frozen instant
    try:
        from . import timeseries as _timeseries

        ts_tail = _timeseries.tail(
            prefixes=("train_step_seconds", "train_tokens_per_s",
                      "train_loss", "comm_", "grad_sync_", "mem_",
                      "serving_throughput", "serving_goodput"),
            k=int(os.environ.get("PT_WATCHDOG_TS_TAIL", "32")))
    except Exception:
        ts_tail = {}
    try:
        from . import perf as _perf

        anomalies = _perf.anomaly_summary()
    except Exception:
        anomalies = {}
    # active (unfinished) spans (monitor/trace.py, journal enabled):
    # "rank 3 stalled while request r17 was mid-preemption-recompute
    # at gseq=N" — the journey context next to the frozen stacks
    try:
        from . import trace as _trace

        spans = _trace.active_spans()
    except Exception:
        spans = []
    # ptprof time-weighted profile (monitor/profile.py, sampler on):
    # WHERE the time went across the window leading into the stall —
    # the de-dup against the point-in-time "stacks" section above, so
    # a postmortem shows the time distribution, not just where threads
    # sat at one instant. None while FLAGS_monitor_profile is off.
    try:
        from . import profile as _profile

        prof = _profile.bundle_payload()
    except Exception:
        prof = None
    return {
        "kind": "watchdog_bundle",
        "version": 1,
        "reason": reason,
        "created_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                    time.gmtime(now)),
        "unix_time": now,
        "pid": os.getpid(),
        "rank": rank,
        "world_size": world,
        "watchdog": {
            "enabled": _state.enabled,
            "stall_threshold_s": _state.stall_threshold_s,
        },
        "verdict": "stalled" if stalls else "ok",
        "stalls": stalls,
        "heartbeats": heartbeats_snapshot(),
        "stacks": thread_stacks(),
        "flight_recorder": flight,
        "metrics": metrics,
        "timeseries_tail": ts_tail,
        "perf_anomalies": anomalies,
        "active_spans": spans,
        "profile_folded": prof,
    }


def _dump_dir():
    return (_state.dump_dir or os.environ.get("PT_MONITOR_DUMP_DIR")
            or ".")


def _atomic_write_json(path, obj):
    """tmp + rename: a kill mid-write (the very crash these artifacts
    diagnose) must never leave truncated JSON."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f, indent=1, default=str)
        f.write("\n")
    os.replace(tmp, path)
    return path


def write_bundle(bundle, dump_dir=None, name=None):
    d = dump_dir or _dump_dir()
    try:
        os.makedirs(d, exist_ok=True)
        return _atomic_write_json(
            os.path.join(d, name or ("watchdog_bundle_rank%d.json"
                                     % bundle["rank"])),
            bundle)
    except OSError:
        return None


# -- cross-rank exchange -----------------------------------------------------
#
# Clock discipline: rank clocks are never compared against each other
# (multi-host skew can exceed any lease window — the repo ships NTP-style
# offset estimation in trace_merge.py precisely because such offsets
# occur). Request nonces are matched by EQUALITY, and deadness is "the
# lease value stopped ADVANCING across the local gather window", both of
# which are skew-immune.

def _publish_bundle(store, rank, bundle, answering=None):
    bundle = dict(bundle)
    bundle["published_at"] = time.time()
    if answering is not None:
        bundle["answering"] = answering
    store.set("%s/bundle/rank%d" % (_WD_PREFIX, rank),
              json.dumps(bundle, default=str).encode())


def _publish_lease(store, rank):
    store.set("%s/alive/rank%d" % (_WD_PREFIX, rank),
              json.dumps({"t": time.time(), "pid": os.getpid()}).encode())


def _publish_request(store, rank, nonce):
    store.set("%s/req" % _WD_PREFIX,
              json.dumps({"t": nonce, "by_rank": rank}).encode())


def _read_request(store):
    data = store.get("%s/req" % _WD_PREFIX, timeout_s=0.05)
    if data is None:
        return None
    try:
        return json.loads(data.decode())
    except Exception:
        return None


def read_lease_stamps(store, world_size):
    """{rank: raw lease timestamp (None if never published)}. Stamps
    are only ever compared for EQUALITY against a later read of the
    same rank's key — never against another clock."""
    stamps = {}
    for r in range(world_size):
        data = store.get("%s/alive/rank%d" % (_WD_PREFIX, r),
                         timeout_s=0.05)
        t = None
        if data is not None:
            try:
                t = float(json.loads(data.decode())["t"])
            except (ValueError, KeyError, TypeError,
                    UnicodeDecodeError):
                pass    # malformed stamp reads as "no heartbeat"
        stamps[r] = t
    return stamps


def gather_bundles(store, world_size, grace_s=None, expect_nonce=None,
                   on_poll=None):
    """Collect per-rank bundles within the grace window. Barrier-free
    (the flight recorder's gather discipline): a dead rank never
    answers, and its absence IS the signal.

    A rank is locked in early only when its bundle carries
    ``answering == expect_nonce`` (it answered THIS incident's
    request); otherwise polling continues and the LATEST version seen
    before the deadline wins — a leftover bundle from a previous
    incident on the same store can be superseded but never blocks the
    fresh answer (wall-clock freshness checks are deliberately avoided:
    cross-host skew would break them).

    ``on_poll`` runs once per poll round — the caller's own liveness
    refresh: a FIRING rank spends its whole gather window inside this
    function instead of ticking, and without refreshing its lease here
    every concurrently-firing peer would read it as dead."""
    if grace_s is None:
        grace_s = _state.grace_s
    deadline = time.monotonic() + grace_s
    bundles = {}
    pending = set(range(world_size))
    while pending and time.monotonic() < deadline:
        if on_poll is not None:
            try:
                on_poll()
            except Exception as e:
                _registry.warn_once(
                    "watchdog.on_poll",
                    "paddle_tpu.monitor.watchdog: on_poll callback "
                    "raised during bundle gather: %r" % (e,))
        locked_in = False
        for r in sorted(pending):
            left = deadline - time.monotonic()
            data = store.get("%s/bundle/rank%d" % (_WD_PREFIX, r),
                             timeout_s=max(min(left, 0.25), 0.05))
            if data is None:
                continue
            try:
                b = json.loads(data.decode())
            except Exception:
                continue
            bundles[r] = b          # latest version wins
            if expect_nonce is None \
                    or b.get("answering") == expect_nonce:
                pending.discard(r)
                locked_in = True
        # pacing: an ABSENT bundle key blocks its get for the poll
        # window, but a stale leftover bundle (exists, wrong nonce)
        # returns instantly — without this sleep a round of only-stale
        # pending ranks busy-spins on the store for the entire grace
        # window (a ptcheck bundle-fixture finding: the gather loop's
        # schedule was unbounded whenever a previous incident left its
        # bundles behind)
        if pending and not locked_in:
            time.sleep(0.05)
    return bundles


# -- cross-rank diagnosis ----------------------------------------------------

def _collective_phase(bundle):
    """The innermost in-flight collective of a bundle's heartbeats, or
    None ('between steps'). Innermost = latest since: allreduce lowers
    to allgather, and the inner op is where the rank actually waits."""
    best = None
    for snap in (bundle.get("heartbeats") or {}).values():
        for p in snap.get("active_phases", ()):
            if "gseq" not in (p.get("info") or {}):
                continue
            if best is None or p["since"] > best["since"]:
                best = p
    return best


def diagnose_bundles(bundles, world_size=None, liveness=None,
                     lease_s=None):
    """Name the stalled (or dead) rank from gathered bundles.

    ``bundles``: {rank: bundle}; ``liveness``: {rank: lease age or
    None}. Mirrors the flight recorder's majority logic on the live
    in-collective positions: ranks blocked in a collective are the
    WAITERS — the suspect is a rank that is not in any collective
    ("between steps"), behind the furthest per-group sequence, dead
    (lease expired), or silent (no bundle, no lease)."""
    if lease_s is None:
        lease_s = _state.lease_s or _default_lease_s()
    bundles = {int(r): b for r, b in bundles.items()}
    liveness = {int(r): a for r, a in (liveness or {}).items()}
    ranks = range(world_size) if world_size else sorted(bundles)
    per_rank, dead, missing, in_coll = {}, [], [], {}
    for r in ranks:
        b = bundles.get(r)
        age = liveness.get(r)
        if b is None:
            # dead = a LEASE that expired (the rank was provably alive
            # and stopped renewing). No lease info at all (offline
            # merge, or a rank that never ran the watchdog) is merely
            # "no bundle" — still a suspect when peers wait on it, but
            # never reported as a confirmed death.
            if age is not None and age > lease_s:
                dead.append(r)
                per_rank[r] = {"state": "dead",
                               "lease_age_s": age}
            else:
                missing.append(r)
                per_rank[r] = {"state": "no-bundle",
                               "lease_age_s": age}
            continue
        coll = _collective_phase(b)
        stalls = b.get("stalls") or []
        if coll is not None:
            in_coll[r] = coll
            per_rank[r] = {"state": "in-collective",
                           "phase": coll["phase"],
                           "info": coll["info"],
                           "age_s": coll["age_s"]}
        elif stalls:
            per_rank[r] = {"state": "stalled",
                           "stalls": stalls}
        else:
            hb_ages = {n: s.get("last_beat_age_s")
                       for n, s in (b.get("heartbeats") or {}).items()}
            per_rank[r] = {"state": "between-steps",
                           "last_beat_ages_s": hb_ages}
    report = {
        "kind": "watchdog_postmortem",
        "world_size": world_size,
        "ranks_reporting": sorted(bundles),
        "dead_ranks": dead,
        "missing_ranks": missing,
        "per_rank": per_rank,
        "stalled_ranks": [],
        "collective": None,
        "status": "inconclusive",
    }
    if in_coll:
        # majority group, furthest gseq = where the pack is waiting
        groups = {}
        for r, p in in_coll.items():
            groups.setdefault(p["info"].get("group"), []).append(r)
        group = max(groups, key=lambda g: len(groups[g]))
        members = groups[group]
        front = max(int(in_coll[r]["info"].get("gseq", -1))
                    for r in members)
        behind = sorted(
            r for r in members
            if int(in_coll[r]["info"].get("gseq", -1)) < front)
        absent = sorted(r for r in ranks
                        if r not in in_coll and r not in dead)
        report["collective"] = {
            "group": group,
            "gseq": front,
            "waiting_ranks": sorted(r for r in members
                                    if r not in behind),
            "op": next((in_coll[r]["info"].get("op") for r in members
                        if int(in_coll[r]["info"].get("gseq", -1))
                        == front), None),
        }
        suspects = sorted(set(behind) | set(absent) | set(dead))
        if suspects:
            report["status"] = "stalled"
            report["stalled_ranks"] = suspects
        else:
            report["status"] = "external-stall"
    else:
        # no rank is inside a collective: suspects are the ranks that
        # reported a local stall, plus any dead ones
        suspects = sorted(set(dead)
                          | {r for r, p in per_rank.items()
                             if p["state"] == "stalled"})
        if suspects:
            report["status"] = "stalled"
            report["stalled_ranks"] = suspects
        elif bundles:
            report["status"] = "ok"
    report["summary"] = summarize_postmortem(report)
    return report


def summarize_postmortem(report):
    if report.get("status") == "stalled":
        bits = []
        for r in report["stalled_ranks"]:
            p = report["per_rank"].get(r, {})
            state = p.get("state", "?")
            if state == "in-collective":
                bits.append("rank %d behind in collective (%s)"
                            % (r, p.get("phase")))
            elif state == "dead":
                bits.append("rank %d DEAD (lease age %s)"
                            % (r, p.get("lease_age_s")))
            else:
                bits.append("rank %d %s" % (r, state))
        coll = report.get("collective")
        where = (" while peers wait in %s gseq=%s"
                 % (coll["op"], coll["gseq"])) if coll else ""
        return "watchdog stall: %s%s" % ("; ".join(bits), where)
    if report.get("status") == "external-stall":
        coll = report.get("collective") or {}
        return ("all ranks blocked in collective %s gseq=%s — "
                "store/network suspect, no rank diverges"
                % (coll.get("op"), coll.get("gseq")))
    return "watchdog: status %s" % report.get("status")


# -- the daemon thread -------------------------------------------------------

def _default_lease_s():
    return max(4 * (_state.poll_interval_s or 1.0), 10.0)


def _on_stall(stalls):
    """Local bundle + (multi-rank) request/gather/diagnose. Runs on the
    daemon thread; must never raise."""
    bundle = build_bundle("stall", stalls)
    path = write_bundle(bundle)
    lines = ["paddle_tpu.monitor.watchdog: STALL detected (bundle: %s)"
             % path]
    for s in stalls:
        lines.append("  %s/%s age %.1fs %s"
                     % (s["heartbeat"], s["phase"], s["age_s"],
                        s["info"] or ""))
    report = None
    pg, rank, world = _world()
    if pg is not None and world > 1:
        try:
            # the nonce identifies THIS incident's request; it is only
            # ever compared for equality, so peer clock skew is moot
            nonce = "%d.%f" % (rank, time.time())
            _state.last_request_answered = nonce   # don't answer self
            _publish_request(pg.store, rank, nonce)
            # a concurrently-firing peer may have a request up already;
            # tag our bundle as answering it so ITS gather locks us in
            peer_req = _read_request(pg.store)
            _publish_bundle(pg.store, rank, bundle,
                            answering=(peer_req or {}).get("t"))
            stamps0 = read_lease_stamps(pg.store, world)
            peers = gather_bundles(
                pg.store, world, expect_nonce=nonce,
                on_poll=lambda: _publish_lease(pg.store, rank))
            peers[rank] = bundle
            # deadness = the lease stopped ADVANCING across the gather
            # window (grace >= 2x the peers' poll interval, so a live
            # watchdog always ticks at least once inside it)
            stamps1 = read_lease_stamps(pg.store, world)
            liveness = {}
            dead_age = _state.grace_s + \
                (_state.lease_s or _default_lease_s()) + 1.0
            for r in range(world):
                if stamps1.get(r) is None:
                    liveness[r] = None          # never leased: unknown
                elif stamps1[r] == stamps0.get(r) and r != rank:
                    liveness[r] = dead_age
                else:
                    liveness[r] = 0.0           # advanced: alive
            # a DEAD rank's bundle that did not answer THIS incident is
            # a leftover from a previous one — drop it so the diagnosis
            # reaches the lease-expiry branch instead of reading stale
            # state as current
            for r in list(peers):
                if r != rank and liveness.get(r) == dead_age \
                        and peers[r].get("answering") != nonce:
                    del peers[r]
            report = diagnose_bundles(peers, world, liveness)
            report["detected_by_rank"] = rank
            report["bundles"] = peers
            d = _dump_dir()
            os.makedirs(d, exist_ok=True)
            ppath = _atomic_write_json(
                os.path.join(d, "watchdog_postmortem_rank%d.json"
                             % rank), report)
            report["report_path"] = ppath
            lines.append("  " + report["summary"])
            lines.append("  postmortem: %s" % ppath)
        except Exception as e:
            lines.append("  cross-rank gather failed: %r" % e)
    sys.stderr.write("\n".join(lines) + "\n")
    # ptslo (monitor/incidents.py): each stall episode is ONE open
    # page-severity incident keyed on (heartbeat, phase) — re-fires of
    # a persistent stall extend it, the _tick prune loop resolves it —
    # with the bundle (and multi-rank postmortem) as evidence. Lazy
    # import, one flag branch while the plane is off.
    try:
        from . import incidents as _incidents

        for s in stalls:
            evidence = {"bundle": path}
            if report is not None and report.get("report_path"):
                evidence["postmortem"] = report["report_path"]
                if report.get("stalled_ranks"):
                    evidence["stalled_ranks"] = \
                        report["stalled_ranks"]
            _incidents.open(
                "watchdog/stall/%s/%s" % (s["heartbeat"], s["phase"]),
                severity="page", kind="stall", source="watchdog",
                summary="stall: %s/%s blocked %.1fs"
                % (s["heartbeat"], s["phase"], s["age_s"]),
                evidence=evidence)
    except Exception as e:
        _registry.warn_once(
            "watchdog.incident_open",
            "paddle_tpu.monitor.watchdog: stall incident open failed "
            "(stall was still reported above): %r" % (e,))
    # ptprof escalation (monitor/profile.py): a fresh stall arms a
    # one-shot device-capture window, so the first steps after the
    # wedge clears (or recovery restarts the loop) get an Xprof trace
    # + folded host stacks. No-op while FLAGS_monitor_profile is off.
    try:
        from . import profile as _profile

        _profile.on_stall(stalls)
    except Exception as e:
        _registry.warn_once(
            "watchdog.profile_arm",
            "paddle_tpu.monitor.watchdog: profile capture arming "
            "failed (stall was still reported above): %r" % (e,))
    try:
        _STALLS_TOTAL.inc()
    except Exception as e:
        _registry.warn_once(
            "watchdog.stalls_counter",
            "paddle_tpu.monitor.watchdog: stall counter increment "
            "failed (stall was still reported above): %r" % (e,))
    if _state.action == "recover" and _stall_actions:
        for fn in list(_stall_actions):
            try:
                fn(stalls, report)
            except Exception as e:
                sys.stderr.write(
                    "paddle_tpu.monitor.watchdog: stall action %r "
                    "failed: %r\n" % (fn, e))
    return report


def register_stall_action(fn):
    """Register an escalation hook ``fn(stalls, report)`` invoked on a
    fresh stall episode when ``PT_WATCHDOG_ACTION=recover``. Returns
    ``fn`` (decorator-friendly)."""
    if fn not in _stall_actions:
        _stall_actions.append(fn)
    return fn


def unregister_stall_action(fn):
    try:
        _stall_actions.remove(fn)
    except ValueError:
        pass


def stall_action():
    """Current escalation mode ("bundle" | "recover") and hook count —
    surfaced at /debugz/resilience."""
    return {"mode": _state.action, "hooks": len(_stall_actions)}


def _write_healthz_artifact():
    path = _state.healthz_out
    if not path:
        return
    try:
        _atomic_write_json(path, healthz_payload())
    except OSError:
        pass


def _tick():
    now = time.monotonic()
    pg, rank, world = _world()
    if pg is not None and world > 1:
        try:
            _publish_lease(pg.store, rank)
            req = _read_request(pg.store)
            if req is not None \
                    and req.get("t") != _state.last_request_answered \
                    and req.get("by_rank") != rank:
                # a peer is gathering: answer with our bundle even if
                # we are healthy or idle — this is how the postmortem
                # gets the guilty rank's stack. Nonce equality (never
                # wall-clock age) decides whether we already answered.
                _state.last_request_answered = req.get("t")
                _publish_bundle(pg.store, rank,
                                build_bundle("request"),
                                answering=req.get("t"))
        except Exception as e:
            _registry.warn_once(
                "watchdog.respond",
                "paddle_tpu.monitor.watchdog: cross-rank bundle "
                "response failed (postmortem will miss this rank's "
                "stacks): %r" % (e,))
    _write_healthz_artifact()
    stalls = _find_stalls(now)
    live_keys = set()
    fresh = []
    for s in stalls:
        key = (s["heartbeat"], s["phase"], s["since"])
        live_keys.add(key)
        if key not in _state.fired:
            _state.fired[key] = now
            fresh.append(s)
    # prune episodes whose phase ended so a future stall re-fires —
    # the same edge resolves the episode's incident (monitor/
    # incidents.py; no-op branch while the SLO plane is off)
    for key in list(_state.fired):
        if key not in live_keys:
            del _state.fired[key]
            try:
                from . import incidents as _incidents

                _incidents.resolve(
                    "watchdog/stall/%s/%s" % (key[0], key[1]),
                    reason="stalled phase ended")
            except Exception as e:
                _registry.warn_once(
                    "watchdog.incident_resolve",
                    "paddle_tpu.monitor.watchdog: stall incident "
                    "resolve failed (episode latch still pruned): %r"
                    % (e,))
    if fresh:
        _on_stall(stalls)


def _run(stop_event, poll_s):
    while not stop_event.wait(poll_s):
        try:
            _tick()
        except Exception as e:
            # the watchdog eating its own tick failures is the exact
            # blind spot it exists to diagnose: say it once, keep
            # ticking
            _registry.warn_once(
                "watchdog.tick",
                "paddle_tpu.monitor.watchdog: tick failed (watchdog "
                "still polling): %r" % (e,))


def start_watchdog(stall_threshold_s=None, poll_interval_s=None,
                   grace_s=None, dump_dir=None):
    """Start (or return) the process-wide watchdog daemon thread and
    enable heartbeat recording. Idempotent without arguments; an
    explicit config on an already-running watchdog (e.g. started by the
    PT_WATCHDOG autostart) restarts the thread with the new settings
    rather than silently keeping the old ones."""
    with _lifecycle_lock:
        return _start_watchdog_locked(stall_threshold_s,
                                      poll_interval_s, grace_s,
                                      dump_dir)


def _start_watchdog_locked(stall_threshold_s, poll_interval_s, grace_s,
                           dump_dir):
    if _state.thread is not None and _state.thread.is_alive():
        if stall_threshold_s is None and poll_interval_s is None \
                and grace_s is None and dump_dir is None:
            return _state.thread
        autostart = _state.autostart
        stop_watchdog()
        _state.autostart = autostart
    if stall_threshold_s is not None:
        _state.stall_threshold_s = float(stall_threshold_s)
    if dump_dir is not None:
        _state.dump_dir = dump_dir
    if poll_interval_s is None:
        poll_interval_s = float(os.environ.get(
            "PT_WATCHDOG_POLL_S",
            str(max(min(_state.stall_threshold_s / 4.0, 5.0), 0.2))))
    _state.poll_interval_s = float(poll_interval_s)
    env_grace = os.environ.get("PT_WATCHDOG_GRACE_S")
    if grace_s is not None:
        _state.grace_s = float(grace_s)
    elif env_grace is not None:
        _state.grace_s = float(env_grace)
    else:
        # the gather window must outlast the PEERS' poll interval: a
        # healthy rank only answers a bundle request on its next tick,
        # so grace <= poll would falsely name slow-but-healthy ranks
        _state.grace_s = max(5.0, 2.0 * _state.poll_interval_s + 1.0)
    _state.lease_s = float(os.environ.get(
        "PT_WATCHDOG_LEASE_S", str(_default_lease_s())))
    _state.healthz_out = os.environ.get("PT_WATCHDOG_HEALTHZ_OUT")
    # like every PT_WATCHDOG_* sibling, the escalation mode re-reads
    # the env at start: setting PT_WATCHDOG_ACTION after import (the
    # common "configure then start" order) must take effect — and an
    # unset env resets to the default rather than keeping a stale mode.
    # Unknown values are called out loudly and degrade to diagnose-only:
    # a typo ('recovery') silently disabling the escalation the operator
    # armed would be discovered only after the outage.
    action = os.environ.get("PT_WATCHDOG_ACTION", "bundle")
    if action not in ("bundle", "recover"):
        sys.stderr.write(
            "paddle_tpu.monitor.watchdog: unknown PT_WATCHDOG_ACTION=%r "
            "(expected 'bundle' or 'recover'); using 'bundle'\n"
            % action)
        action = "bundle"
    _state.action = action
    _state.fired = {}
    _state.enabled = True
    _state.stop_event = threading.Event()
    _state.thread = threading.Thread(
        target=_run, args=(_state.stop_event, _state.poll_interval_s),
        name=_THREAD_NAME, daemon=True)
    _state.thread.start()
    return _state.thread


def stop_watchdog():
    """Stop the daemon thread and disable heartbeat recording."""
    with _lifecycle_lock:
        _state.enabled = False
        _state.autostart = False
        if _state.stop_event is not None:
            _state.stop_event.set()
        t = _state.thread
        if t is not None and t.is_alive():
            t.join(timeout=5)
        _state.thread = None
        _state.stop_event = None


def is_watchdog_running():
    return _state.thread is not None and _state.thread.is_alive()


# watchdog's own telemetry rides the shared registry (inc is a no-op
# while the monitor is disabled, like every other mutator)
_STALLS_TOTAL = _registry.counter(
    "watchdog_stalls_total", "stall episodes detected by the watchdog")


# -- live endpoints (registered on the fleet KV server by exporter.py) -------

def healthz_payload():
    now = time.time()       # reported wall stamp; ages are monotonic
    stalls = _find_stalls() if _state.enabled else []
    _, rank, world = _world()
    # perf-sentinel degradation (monitor/perf.py): a NaN loss or
    # throughput cliff marks the endpoint degraded — orthogonal to the
    # stalled verdict (a degraded run is alive and probe-200, but a
    # deploy gate can read the flag). With the SLO plane on, the
    # incident table is the single source of truth instead: degraded
    # = any open incident (the sentinels still report through it, so
    # the verdict is equivalent until something else opens one). Flag
    # off, the payload is bit-identical to the pre-incident build
    # (test-pinned).
    incidents_open = None
    try:
        from . import perf as _perf

        try:
            from . import incidents as _incidents
        except Exception:
            _incidents = None
        if _incidents is not None and _incidents.is_enabled():
            degraded = _incidents.is_degraded()
            incidents_open = len(_incidents.open_incidents())
        else:
            degraded = _perf.is_degraded()
        anomalies = _perf.anomaly_summary() if degraded else None
    except Exception:
        degraded, anomalies = False, None
    body = {
        "status": "stalled" if stalls
        else ("degraded" if degraded else "ok"),
        "degraded": degraded,
        "perf_anomalies": anomalies,
        "watchdog": "enabled" if _state.enabled else "disabled",
        "stall_threshold_s": _state.stall_threshold_s,
        "rank": rank,
        "world_size": world,
        "pid": os.getpid(),
        "time": now,
        "stalls": stalls,
        "heartbeats": {
            name: {
                "beats": s["beats"],
                "last_beat_age_s": s["last_beat_age_s"],
                "active_phases": s["active_phases"],
            } for name, s in heartbeats_snapshot().items()},
    }
    # key exists only while the incident plane is on — the flag-off
    # payload stays byte-for-byte what PR-17 served (test-pinned)
    if incidents_open is not None:
        body["incidents_open"] = incidents_open
    return body


def json_safe(obj):
    """Recursively replace non-finite floats with their string
    spellings. HTTP debug payloads carry NaN on purpose (a NaN loss IS
    the incident), but Python's json emits bare ``NaN`` tokens that
    strict parsers (jq, JSON.parse) reject — and an incident-response
    endpoint must stay parseable exactly mid-incident."""
    if isinstance(obj, float):
        if obj != obj:
            return "NaN"
        if obj == float("inf"):
            return "Infinity"
        if obj == float("-inf"):
            return "-Infinity"
        return obj
    if isinstance(obj, dict):
        return {k: json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [json_safe(v) for v in obj]
    return obj


def _json_route(payload, code=200):
    return code, "application/json", \
        json.dumps(json_safe(payload), default=str).encode()


def http_healthz():
    p = healthz_payload()
    return _json_route(p, 503 if p["status"] == "stalled" else 200)


def http_stacks():
    return _json_route({"pid": os.getpid(), "time": time.time(),
                        "stacks": thread_stacks()})


def http_flight():
    _, rank, world = _world()
    return _json_route(get_flight_recorder().dump(rank, world))


def http_bundle():
    return _json_route(build_bundle("debugz"))
