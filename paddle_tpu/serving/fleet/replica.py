"""Fleet replica: one engine behind the serving-fleet HTTP protocol.

Wraps an already-built ``serving.Engine`` (the engine is passed in —
this module never imports it, so the fleet package stays importable
without jax): announces itself in the store via ``membership``
(endpoint URL + generation + capability snapshot), renews the liveness
lease from a heartbeat thread, and serves the router-facing API on its
own ``MetricsServer`` (which also gives the replica ``/healthz`` and
the gauges the router scrapes for load):

    POST /sfleet/enqueue        {nonce, prompt, max_new_tokens,
                                 eos_token_id, deadline_s} -> {state}.
                                 An optional ``traceparent`` field
                                 (``pt1-<trace_id>-<span id>``) makes
                                 the engine adopt the router's
                                 fleet-wide trace context; absent
                                 (journal off) the payload — and the
                                 engine's local-mint tracing path —
                                 is bit-identical to pre-trace.
                                 Nonce-idempotent: a retried dispatch
                                 (router saw a dead connection after
                                 we DID accept) maps to the existing
                                 request — an accepted request is
                                 never double-admitted. 409 +
                                 {"error": reason} on load shed
                                 (draining / queue_full).
    GET  /sfleet/result/{nonce} request progress: state, output token
                                 count, and the generated tokens once
                                 terminal (plus the span summary —
                                 trace_id + per-phase seconds — when
                                 the journal is on, so the router
                                 settles e2e attribution). 404 for an
                                 unknown nonce
                                 (a restarted replica answers 404 for
                                 pre-restart nonces — the router
                                 re-routes them).
    GET  /sfleet/load            the router's load signals: kv-page
                                 occupancy, queue depth, active slots,
                                 draining bit, decode_compiles,
                                 requests_finished, capabilities.

Threading: the engine is touched ONLY by the serve thread
(``pt-sfleet-serve``) — HTTP handlers talk to it through a pending
queue and a status cache under a plain mutex, so an enqueue/result/
load request never blocks behind a multi-second ``step()`` (the first
step compiles; a handler waiting on it would time the router out and
get healthy replicas drained). Engine steps additionally serialize on
a process-wide lock (see ``_STEP_LOCK``): tracing through a shared
model object is not thread-safe across engines in one process. The
lease heartbeat runs on ``pt-sfleet-lease``. Both threads exist only while the replica is
started; ``FLAGS_serving_fleet`` off refuses construction (no
threads, no store traffic, no series).
"""
from __future__ import annotations

import json
import threading
import time

from ...monitor import trace as _trace
from ...monitor.exporter import MetricsServer
from ...monitor.registry import warn_once
from . import membership
from .router import _require_flag

_SERVE_THREAD = "pt-sfleet-serve"
_LEASE_THREAD = "pt-sfleet-lease"

_TERMINAL = ("finished", "expired", "shed", "failed")

# Engines in ONE process may share the model object, and
# ``Engine.step`` traces through ``model.bind_state`` — which swaps
# traced values into that shared model. Two serve threads tracing at
# once leak each other's tracers (UnexpectedTracerError poisons every
# in-flight request). Steps therefore serialize on a process-wide
# lock: uncontended in the deployment shape (one engine per process,
# tools/serving_router.py --replica), and correctness-over-overlap
# for in-process fleets (tests, single-host dev).
_STEP_LOCK = threading.Lock()


class Replica:
    """One data-parallel serving replica in the fleet."""

    def __init__(self, engine, rank, store=None, host="127.0.0.1",
                 port=0, ttl_s=3.0, heartbeat_interval_s=0.5,
                 capabilities=None, meta=None):
        _require_flag("Replica")
        self.engine = engine
        self.rank = int(rank)
        self._store = store
        self._host = host
        self._heartbeat_interval_s = float(heartbeat_interval_s)
        self._ttl_s = float(ttl_s)
        self.capabilities = dict(
            capabilities if capabilities is not None
            else membership.DEFAULT_CAPABILITIES)
        self._meta = dict(meta or {})
        self.generation = None
        # handler-side state: NEVER the engine itself. _pending feeds
        # the serve thread; _status is its published view back.
        self._mu = threading.Lock()
        self._pending = []              # [(nonce, payload), ...]
        self._status = {}               # nonce -> status dict
        self._stop = threading.Event()
        self._serve_thread = None
        self._lease_thread = None
        self._server = MetricsServer(port)
        self._server.add_post_route("sfleet/enqueue", self._enqueue)
        self._server.add_prefix_route("sfleet/result", self._result)
        self._server.add_route("sfleet/load", self._load)

    @property
    def port(self):
        return self._server.port

    @property
    def url(self):
        return "http://%s:%d" % (self._host, self._server.port)

    # -- lifecycle -------------------------------------------------------

    def start(self):
        self._server.start()
        if self._store is not None:
            self.generation = membership.register_replica(
                self._store, self.rank, self.url,
                capabilities=self.capabilities, meta=self._meta)
            self._lease_thread = threading.Thread(
                target=self._lease_loop, name=_LEASE_THREAD,
                daemon=True)
            self._lease_thread.start()
        self._serve_thread = threading.Thread(
            target=self._serve_loop, name=_SERVE_THREAD, daemon=True)
        self._serve_thread.start()
        return self

    def _lease_loop(self):
        while not self._stop.wait(self._heartbeat_interval_s):
            try:
                membership.renew_lease(self._store, self.rank)
            except (OSError, ValueError) as e:
                warn_once(
                    "sfleet.replica.lease.%d" % self.rank,
                    "paddle_tpu.serving.fleet: replica %d lease "
                    "renewal failed (%r) — watchers will age the "
                    "lease out after ttl=%.1fs" % (
                        self.rank, e, self._ttl_s))

    def _serve_loop(self):
        while not self._stop.is_set():
            self._admit_pending()
            worked = False
            if self.engine.has_work():
                with _STEP_LOCK:
                    worked = bool(self.engine.step())
            self._refresh_status()
            if not worked:
                time.sleep(0.005)

    def _admit_pending(self):
        with self._mu:
            pending, self._pending = self._pending, []
        for nonce, payload in pending:
            # cross-process trace context: the router's traceparent
            # field adopts its fleet-wide trace id here, so the
            # engine's phase spans land under it with the router's
            # dispatch span as remote parent. (None, None) — absent
            # or malformed — keeps the local-mint path.
            ctx = _trace.parse_traceparent(payload.get("traceparent"))
            try:
                rid = self.engine.add_request(
                    list(payload["prompt"]),
                    max_new_tokens=int(payload.get(
                        "max_new_tokens", 32)),
                    eos_token_id=payload.get("eos_token_id"),
                    deadline_s=payload.get("deadline_s"),
                    trace_ctx=ctx if ctx[0] is not None else None)
            except ValueError as e:
                upd = {"state": "failed", "reason": "invalid",
                       "error": repr(e), "tokens": []}
            except RuntimeError as e:
                # AdmissionError raced past the handler's lock-free
                # pre-check: surface it as a shed terminal — the
                # router re-routes sheds with an admission reason
                reason = getattr(e, "reason", None)
                if reason is None:
                    raise
                upd = {"state": "shed", "reason": reason,
                       "error": repr(e), "tokens": []}
            else:
                upd = {"rid": rid, "state": "queued"}
            with self._mu:
                self._status[nonce].update(upd)

    def _refresh_status(self):
        with self._mu:
            live = [(n, s["rid"]) for n, s in self._status.items()
                    if s["rid"] is not None
                    and s["state"] not in _TERMINAL]
        for nonce, rid in live:
            st = self.engine.request_status(rid)
            upd = {"state": st["state"], "reason": st["reason"],
                   "output_tokens": st["output_tokens"],
                   "error": st["error"]}
            if st["state"] in _TERMINAL:
                upd["tokens"] = self.engine.output(rid)
                # span summary for the router's e2e attribution —
                # computed here on the serve thread (handlers never
                # touch the engine); (None, None) while the journal
                # is off, and then the result payload carries no
                # trace keys at all
                tid, phases = self.engine.request_trace(rid)
                if tid is not None:
                    upd["trace_id"] = tid
                    upd["phases_s"] = {
                        k: round(v, 6)
                        for k, v in (phases or {}).items()}
            with self._mu:
                self._status[nonce].update(upd)

    def drain(self):
        """Stop admitting; the serve loop finishes accepted work.
        Published to the store so routers reschedule queued-but-
        unstarted requests instead of waiting on this replica."""
        self.engine._draining = True
        if self._store is not None:
            membership.mark_draining(self._store, self.rank)

    def stop(self, deregister=True):
        """Tear down threads + server; graceful exits delete the lease
        (immediate death for watchers, no TTL wait)."""
        self._stop.set()
        for t in (self._serve_thread, self._lease_thread):
            if t is not None:
                t.join(timeout=5)
        self._serve_thread = self._lease_thread = None
        if deregister and self._store is not None:
            try:
                membership.deregister_replica(self._store, self.rank)
            except (OSError, ValueError):
                pass
        self._server.stop()

    # -- router-facing HTTP API ------------------------------------------

    def _enqueue(self, body):
        try:
            payload = json.loads(body.decode())
            nonce = payload["nonce"]
            prompt = payload["prompt"]
            if not isinstance(prompt, list) or not prompt:
                raise ValueError("prompt must be a non-empty "
                                 "token-id list")
        except (ValueError, KeyError, UnicodeDecodeError) as e:
            return (400, "application/json",
                    json.dumps({"error": repr(e)}).encode())
        with self._mu:
            st = self._status.get(nonce)
            if st is not None:
                # the idempotent path: a retried dispatch after a lost
                # ack re-observes the existing acceptance, never a
                # second admission
                return (200, "application/json", json.dumps(
                    {"state": st["state"], "deduped": True}).encode())
        # admission pre-check: lock-free reads of engine scalars (the
        # GIL makes them atomic; the serve thread re-checks under
        # add_request, so a race sheds instead of corrupting)
        if self.engine.draining:
            return (409, "application/json",
                    json.dumps({"error": "draining"}).encode())
        mq = self.engine.max_queue
        with self._mu:
            if mq is not None and \
                    len(self.engine.scheduler.queue) \
                    + len(self._pending) >= mq:
                return (409, "application/json",
                        json.dumps({"error": "queue_full"}).encode())
            self._status[nonce] = {
                "rid": None, "state": "queued", "reason": None,
                "output_tokens": 0, "error": None, "tokens": None}
            self._pending.append((nonce, payload))
        return (200, "application/json", json.dumps(
            {"state": "queued", "deduped": False}).encode())

    def _result(self, nonce):
        with self._mu:
            st = self._status.get(nonce)
            if st is None:
                return (404, "application/json", json.dumps(
                    {"error": "unknown nonce",
                     "nonce": nonce}).encode())
            out = {k: st[k] for k in (
                "rid", "state", "reason", "output_tokens", "error",
                "tokens")}
            # replica span summary (present only when the journal was
            # on at finish — the journal-off payload is bit-identical)
            if "trace_id" in st:
                out["trace_id"] = st["trace_id"]
                out["phases_s"] = st["phases_s"]
        return 200, "application/json", json.dumps(out).encode()

    def _load(self):
        # scalar reads only — never blocks behind a running step
        alloc = self.engine.cache.allocator
        used = alloc.usable_blocks - alloc.free_blocks
        # the two counters themselves, not stats(): that reduces the
        # engine's timing rings, which is work for an end-of-run reader
        metrics = self.engine.metrics
        with self._mu:
            pending = len(self._pending)
        payload = {
            "rank": self.rank,
            "generation": self.generation,
            "draining": bool(self.engine.draining),
            "occupancy": used / max(alloc.usable_blocks, 1),
            "queue_depth": len(self.engine.scheduler.queue) + pending,
            "active_slots": self.engine.scheduler.slots_active(),
            "decode_compiles": metrics.decode_compiles,
            "requests_finished": metrics.requests_finished,
            "capabilities": self.capabilities,
        }
        return 200, "application/json", json.dumps(payload).encode()
