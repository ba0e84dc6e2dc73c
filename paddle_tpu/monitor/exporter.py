"""Registry exporters: JSON snapshot artifacts + HTTP /metrics endpoint.

The HTTP side rides the existing fleet KV server
(distributed/fleet/utils/http_server.py) rather than growing a second
server stack: ``KVHTTPServer`` gained a ``get_routes`` hook, and
``MetricsServer`` registers the telemetry routes on it —

    GET /metrics        Prometheus text exposition (scrape target)
    GET /metrics.json   JSON snapshot (tools, dashboards, bench artifacts)
    GET /healthz        ok|stalled verdict + heartbeat ages (503 when
                        stalled — load-balancer/probe friendly)
    GET /debugz/stacks  live all-thread Python stack dump
    GET /debugz/flight  this rank's collective flight-recorder ring
    GET /debugz/bundle  full on-demand diagnostic bundle (stacks +
                        flight ring + metrics + heartbeat ages)
    GET /debugz/perf    MFU/goodput attribution + anomaly state
                        (monitor/perf.py payload)
    GET /debugz/timeseries  the metric time-series rings
                        (monitor/timeseries.py payload)
    GET /debugz/trace   span-journal summary + histogram exemplars
                        (monitor/trace.py payload)
    GET /debugz/trace/journal  the full journal artifact (the
                        write_journal format — what a fleet capture
                        pulls so tools/trace_merge.py can merge it)
    GET /debugz/trace/{id}  one trace's full span timeline (404 for an
                        unknown or evicted trace id) + a
                        ``federation`` block: on a serving-fleet
                        router process the replica-side fragments of
                        the fleet trace, fetched on demand
                        (enabled:false otherwise, zero fetches)
    GET /debugz/memory  memory-plane breakdown: per-component ledger,
                        allocator reconciliation, headroom, recent
                        admission/preempt decisions, OOM postmortems
                        (monitor/memory.py payload)
    GET /debugz/profile continuous-profiling summary: sampler stats,
                        component attribution, top-K folded stacks,
                        measured dispatch/blocked/gap per job, capture
                        windows (monitor/profile.py payload)
    GET /debugz/profile/folded  collapsed-stack text of the host
                        sampling profiler (flamegraph.pl input)
    GET /debugz/fleet   fleet summary: collector state, straggler
                        verdict, fused cross-rank aggregates
                        (monitor/fleet.py payload)
    GET /debugz/fleet/ranks  the per-rank fleet table (step, tokens/s,
                        MFU, heartbeat age, straggler flag — what
                        tools/fleet_top.py renders)
    GET /metrics/fleet  Prometheus federation-style exposition of the
                        fused fleet series (rank-labeled + aggregates)
    GET /debugz/resilience  fault-injection state + recovery/shed
                        counters + watchdog escalation mode
                        (paddle_tpu/resilience payload)
    GET /debugz/router  serving-fleet router summary: replica states
                        (live/draining/evicted), request-outcome
                        counts, affinity-index stats (served via the
                        monitor/fleet.py router hook; reports disabled
                        when FLAGS_serving_fleet is off)
    GET /debugz/router/replicas  the router's per-replica table (url,
                        generation, state, load, queue depth, per-
                        replica dispatch/affinity counts)
    GET /debugz/slo     SLO/error-budget verdicts: per-objective
                        attainment, budget remaining, burn rates per
                        alerting window, active burn alerts
                        (monitor/slo.py payload; enabled:false while
                        FLAGS_monitor_slo is off)
    GET /debugz/incidents  the unified incident table: open + recently
                        resolved incidents with severity, episode
                        counts and evidence links
                        (monitor/incidents.py payload)
    GET /debugz/fleet/incidents  fleet-wide incident timeline merged
                        from every scraped rank's table + the
                        collector's own, clock-offset-aligned and
                        deduped by incident id (monitor/fleet.py)
    GET /debugz/replay  record/replay journal summary + per-request
                        outcome digests (prompt/output token counts,
                        rolling token hash, flag snapshot, trace_id
                        cross-links) + the router's dispatch-decision
                        ring (serving/replay.py payload; reports
                        disabled — without importing the serving
                        package — while FLAGS_serving_replay is off)

The /healthz and /debugz routes are served live from monitor/watchdog.py
whether or not the watchdog thread is running (the verdict just reads
"watchdog: disabled" when it is not).

Snapshot artifacts (``write_snapshot``) carry metadata —
``written_at``/``pid``/caller-supplied context — so an old artifact is
detectable from the artifact itself.
"""
from __future__ import annotations

import json
import os
import time

from . import fleet as _fleet
from . import incidents as _incidents
from . import memory as _memory
from . import perf as _perf
from . import profile as _profile
from . import slo as _slo
from . import timeseries as _timeseries
from . import trace as _trace
from . import watchdog as _watchdog
from .registry import get_registry


def snapshot(registry=None, meta=None):
    """Registry snapshot dict wrapped with provenance metadata."""
    reg = registry or get_registry()
    out = {
        "written_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "unix_time": time.time(),
        "pid": os.getpid(),
        "metrics": reg.snapshot(),
    }
    if meta:
        out["meta"] = dict(meta)
    return out


def write_snapshot(path, registry=None, meta=None):
    """Dump the snapshot JSON artifact; returns the snapshot dict."""
    snap = snapshot(registry, meta)
    d = os.path.dirname(os.path.abspath(path))
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(snap, f, indent=1, default=str)
        f.write("\n")
    return snap


class MetricsServer:
    """Serve the registry over HTTP via the fleet KV server.

    >>> srv = MetricsServer(port=0).start()
    >>> urllib.request.urlopen(
    ...     "http://127.0.0.1:%d/metrics" % srv.port).read()
    """

    def __init__(self, port=0, registry=None):
        from ..distributed.fleet.utils.http_server import KVServer

        self._registry = registry or get_registry()
        self._kv = KVServer(port)
        routes = self._kv.http_server.get_routes
        routes["metrics"] = self._prometheus
        routes["metrics.json"] = self._json
        routes["healthz"] = _watchdog.http_healthz
        routes["debugz/stacks"] = _watchdog.http_stacks
        routes["debugz/flight"] = _watchdog.http_flight
        routes["debugz/bundle"] = _watchdog.http_bundle
        routes["debugz/perf"] = self._perf
        routes["debugz/timeseries"] = self._timeseries
        routes["debugz/trace"] = self._trace
        # exact routes win over the debugz/trace prefix dispatch, so
        # "journal" can never be misread as a trace id
        routes["debugz/trace/journal"] = self._trace_journal
        routes["debugz/memory"] = self._memory
        routes["debugz/profile"] = self._profile
        routes["debugz/profile/folded"] = self._profile_folded
        routes["debugz/resilience"] = self._resilience
        routes["debugz/fleet"] = self._fleet
        routes["debugz/fleet/ranks"] = self._fleet_ranks
        routes["metrics/fleet"] = self._fleet_prometheus
        routes["debugz/router"] = self._router
        routes["debugz/router/replicas"] = self._router_replicas
        routes["debugz/slo"] = self._slo
        routes["debugz/incidents"] = self._incidents
        routes["debugz/fleet/incidents"] = self._fleet_incidents
        routes["debugz/replay"] = self._replay
        self._kv.http_server.get_prefix_routes["debugz/trace"] = \
            self._trace_by_id

    @property
    def port(self):
        return self._kv.port

    def start(self):
        self._kv.start()
        return self

    def stop(self):
        self._kv.stop()

    # -- route registration (serving/fleet rides the same server) ------

    def add_route(self, path, fn):
        """Register a GET route: ``fn() -> (code, ctype, body)``."""
        self._kv.http_server.get_routes[path.strip("/")] = fn

    def add_prefix_route(self, prefix, fn):
        """Register a parametric GET route: ``fn(rest) -> ...``."""
        self._kv.http_server.get_prefix_routes[prefix.strip("/")] = fn

    def add_post_route(self, path, fn):
        """Register a POST route: ``fn(body) -> (code, ctype, body)``."""
        self._kv.http_server.post_routes[path.strip("/")] = fn

    def _prometheus(self):
        body = self._registry.prometheus_text().encode()
        return 200, "text/plain; version=0.0.4; charset=utf-8", body

    def _json(self):
        # json_safe: a NaN gauge (the sentinel's input) must not turn
        # the scrape into an unparseable bare-NaN body mid-incident
        body = json.dumps(_watchdog.json_safe(snapshot(self._registry)),
                          default=str).encode()
        return 200, "application/json", body

    def _perf(self):
        body = json.dumps(_watchdog.json_safe(_perf.perf_payload()),
                          default=str).encode()
        return 200, "application/json", body

    def _timeseries(self):
        body = json.dumps(_watchdog.json_safe(_timeseries.payload()),
                          default=str).encode()
        return 200, "application/json", body

    def _trace(self):
        body = json.dumps(_watchdog.json_safe(_trace.payload()),
                          default=str).encode()
        return 200, "application/json", body

    def _trace_journal(self):
        body = json.dumps(_watchdog.json_safe(_trace.dump()),
                          default=str).encode()
        return 200, "application/json", body

    def _memory(self):
        body = json.dumps(_watchdog.json_safe(_memory.memory_payload()),
                          default=str).encode()
        return 200, "application/json", body

    def _profile(self):
        body = json.dumps(
            _watchdog.json_safe(_profile.profile_payload()),
            default=str).encode()
        return 200, "application/json", body

    def _profile_folded(self):
        return (200, "text/plain; charset=utf-8",
                _profile.folded_route_text().encode())

    def _fleet(self):
        body = json.dumps(_watchdog.json_safe(_fleet.fleet_payload()),
                          default=str).encode()
        return 200, "application/json", body

    def _fleet_ranks(self):
        body = json.dumps(_watchdog.json_safe(_fleet.ranks_payload()),
                          default=str).encode()
        return 200, "application/json", body

    def _fleet_prometheus(self):
        body = _fleet.prometheus_fleet_text().encode()
        return 200, "text/plain; version=0.0.4; charset=utf-8", body

    def _router(self):
        # serving-fleet router summary: served via monitor/fleet.py's
        # duck-typed hook slot so the monitor plane never imports the
        # serving package (flag off / no router = pinned disabled body)
        body = json.dumps(_watchdog.json_safe(_fleet.router_payload()),
                          default=str).encode()
        return 200, "application/json", body

    def _router_replicas(self):
        body = json.dumps(
            _watchdog.json_safe(_fleet.router_replicas_payload()),
            default=str).encode()
        return 200, "application/json", body

    def _slo(self):
        body = json.dumps(_watchdog.json_safe(_slo.payload()),
                          default=str).encode()
        return 200, "application/json", body

    def _incidents(self):
        body = json.dumps(_watchdog.json_safe(_incidents.payload()),
                          default=str).encode()
        return 200, "application/json", body

    def _fleet_incidents(self):
        body = json.dumps(
            _watchdog.json_safe(_fleet.fleet_incidents_payload()),
            default=str).encode()
        return 200, "application/json", body

    def _replay(self):
        # lazier than the /debugz/resilience route: the serving
        # package pulls in the accelerator backend, so the monitor
        # plane must not import it just to say "disabled" — serve the
        # module only if an engine (or tool) already imported it. The
        # literal below is pinned bit-identical to
        # serving/replay.payload()'s disabled body by
        # tests/test_debugz_routes.py.
        import sys

        mod = sys.modules.get("paddle_tpu.serving.replay")
        if mod is None:
            p = {"enabled": False, "requests": [], "dispatches": 0}
        else:
            p = mod.payload()
        body = json.dumps(_watchdog.json_safe(p), default=str).encode()
        return 200, "application/json", body

    def _resilience(self):
        # lazy: paddle_tpu.resilience imports back into monitor — the
        # route resolves at request time, never at module import
        from ..resilience import payload as _resilience_payload

        body = json.dumps(_watchdog.json_safe(_resilience_payload()),
                          default=str).encode()
        return 200, "application/json", body

    def _trace_by_id(self, rest):
        trace_id, _, query = rest.partition("?")
        p = _trace.trace_payload(trace_id)
        if p is None:
            return (404, "application/json",
                    json.dumps({"error": "unknown trace",
                                "trace_id": trace_id}).encode())
        # on a router process the trace is fleet-wide: federate the
        # replica-side fragments on demand (enabled:false — and zero
        # cross-replica fetches — without FLAGS_serving_fleet + a
        # running router; the 404-for-unknown contract is unchanged).
        # ``?local=1`` pins the LOCAL view: the router's own federation
        # fetches ask for it, so a fragment request can never recurse
        # into another fan-out (loop-proofs a misconfigured topology
        # where a router's endpoint resolves back to a router process)
        if "local=1" not in query.split("&"):
            p["federation"] = _fleet.router_trace_federation(trace_id)
        body = json.dumps(_watchdog.json_safe(p), default=str).encode()
        return 200, "application/json", body


_server = None


def start_metrics_server(port=0, registry=None):
    """Start (or return the running) process-wide metrics endpoint."""
    global _server
    if _server is None:
        _server = MetricsServer(port, registry).start()
    return _server


def stop_metrics_server():
    global _server
    if _server is not None:
        _server.stop()
        _server = None
