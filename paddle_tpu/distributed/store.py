"""TCPStore — distributed KV rendezvous over the native C++ store.

Parity: the reference bootstraps NCCL comm rings by TCP-broadcasting unique
ids (paddle/fluid/platform/gen_comm_id_helper.cc:396) and init_parallel_env
starts a master TCP store (python/paddle/distributed/parallel.py:108). On
TPU there are no comm ids to exchange — XLA owns the collectives — but the
multi-host launch/elastic subsystems still need rendezvous: rank
registration, coordinator discovery, barriers, heartbeats. The wire
implementation is csrc/store.cc (C++ threads + sockets), loaded via ctypes.

Hardening (resilience layer): every op retries transient fd-level
failures with exponential backoff + jitter and reconnects a dead
socket automatically (``store_reconnects_total`` counts successes) —
a bounced master or a dropped connection costs a retry, not the job.
Errors that survive the retries name op/key/peer/attempts. All ops are
fault-injection sites (``store.set``/``get``/``add``/``delete``,
resilience/faultinject.py) so the retry/reconnect paths are exercised
deterministically in CI.

Retried mutating ops are IDEMPOTENT: every ``add`` carries a client
nonce (a per-connection random 64-bit id + a per-op sequence number)
and the server replays the recorded result for a duplicate nonce
instead of re-applying the delta — a reply lost AFTER the server
applied used to double-count on retry, which leader election (first
``add`` to observe 1 wins) reads as a vanished claim. The injected
``lost_ack`` fault (applies the op, then forces the retry path)
exercises exactly that window; ptcheck's idempotence fixtures explore
it under every interleaving.
"""
from __future__ import annotations

import ctypes
import os
import random
import threading
import time

from ..core import native
from ..monitor import registry as _mreg
from ..resilience import faultinject as _fi

_RECONNECTS = _mreg.counter(
    "store_reconnects_total",
    "TCPStore client sockets re-established after a dead fd")
_OP_RETRIES = _mreg.counter(
    "store_op_retries_total",
    "TCPStore ops retried after a transient failure",
    labelnames=("op",))


class TCPStore:
    """KV store client; rank 0 also hosts the server (is_master=True)."""

    def __init__(self, host="127.0.0.1", port=0, is_master=False,
                 timeout_s=300, op_retries=None, backoff_s=None):
        self._lib = native.get_lib()
        # The wire protocol is strict request/response over ONE socket:
        # concurrent callers (e.g. elastic heartbeat threads sharing a
        # store with the watcher) interleave frames mid-request and the
        # peer thread blocks forever in recv on a response that never
        # comes. Serialize every op on this fd.
        self._mu = threading.Lock()
        self._server = None
        self._closed = False
        self.timeout_ms = int(timeout_s * 1000)
        # semantics: TOTAL attempts per op — clamped to >= 1 so a
        # "disable retries" value of 0 degrades to single-attempt
        # instead of zero-attempt (every op failing unconditionally)
        self._op_retries = max(1, int(
            op_retries if op_retries is not None
            else os.environ.get("PT_STORE_OP_RETRIES", "3")))
        self._backoff_s = float(backoff_s if backoff_s is not None
                                else os.environ.get("PT_STORE_BACKOFF_S",
                                                    "0.05"))
        # jitter decorrelates retry storms across ranks; per-instance
        # seeding keeps a single process's tests deterministic enough
        # while never synchronizing a whole fleet's backoff waves
        self._jitter = random.Random(os.getpid() * 1000003 + id(self) % 997)
        # idempotence nonce: a random connection id (urandom, NOT the
        # seeded jitter — uniqueness across every process in the fleet
        # is the whole point) + a per-op sequence. A retried add
        # resends the same (cid, seq) and the server replays the
        # recorded result instead of re-applying the delta.
        self._nonce_cid = int.from_bytes(os.urandom(8), "little")
        self._nonce_seq = 0
        if is_master:
            self._server = self._lib.pt_store_server_start(port)
            if self._server < 0:
                raise RuntimeError("TCPStore: failed to bind port %d" % port)
            port = self._lib.pt_store_server_port(self._server)
        self.host, self.port = host, port
        self._fd = self._connect_with_retry()

    def _peer(self):
        return "%s:%d" % (self.host, self.port)

    def _connect_with_retry(self):
        """Initial connect: the native layer already retries refused
        connections until its deadline; this adds backoff+jitter rounds
        on top for resolution failures and slow-starting masters."""
        attempts = max(1, int(
            os.environ.get("PT_STORE_CONNECT_RETRIES", "3")))
        per_try_ms = max(self.timeout_ms // attempts, 1000)
        for attempt in range(1, attempts + 1):
            fd = self._lib.pt_store_connect(
                self.host.encode(), self.port, per_try_ms)
            if fd >= 0:
                return fd
            if attempt < attempts:
                self._sleep_backoff(attempt)
        if self._server is not None:
            self._lib.pt_store_server_stop(self._server)
            self._server = None
        raise RuntimeError(
            "TCPStore: cannot connect to %s after %d attempts"
            % (self._peer(), attempts))

    def _sleep_backoff(self, attempt):
        delay = self._backoff_s * (2 ** (attempt - 1))
        time.sleep(delay * (0.5 + self._jitter.random()))

    def _break_fd_locked(self):
        """Injected broken-fd fault: close the live socket under the op
        lock so the NEXT native call fails at the fd level — the same
        observable state as a peer reset, exercising reconnect. The fd
        is invalidated here so the reconnect path never double-closes a
        number the OS may already have recycled to another socket."""
        if self._fd is not None and self._fd >= 0:
            self._lib.pt_store_close(self._fd)
            self._fd = -1

    def _reconnect(self, op, key, attempt):
        """Drop the dead fd and dial again (backoff + jitter first).
        Returns True when a fresh socket is up. Used by the blocking
        ``get`` poll loop, which must NOT hold the op lock across its
        waits (peers sharing the store would starve past their
        TTL)."""
        self._sleep_backoff(attempt)
        with self._mu:
            return self._reconnect_locked(op)

    def _reconnect_locked(self, op):
        if self._closed:
            return False
        if self._fd is not None and self._fd >= 0:
            self._lib.pt_store_close(self._fd)
            self._fd = -1
        self._fd = self._lib.pt_store_connect(
            self.host.encode(), self.port,
            min(self.timeout_ms, 5000))
        ok = self._fd >= 0
        _OP_RETRIES.labels(op=op).inc()
        if ok:
            _RECONNECTS.inc()
        return ok

    def _fd_alive_locked(self):
        """Cheap liveness probe on the current fd: a non-creating
        counter read of a reserved key answers -2 (healthy miss) from a
        live server and -1 from a dead socket."""
        out = ctypes.c_int64()
        rc = self._lib.pt_store_counter_get(
            self._fd, b"__store/ping", ctypes.byref(out))
        return rc != -1

    @property
    def is_master(self):
        return self._server is not None

    # cooperative fault kinds every store op can apply (faultinject):
    # callers off the hot path see one is_enabled() branch and build
    # no ctx allocations while injection is disabled. The retrying
    # request/reply ops additionally honor "lost_ack": the request is
    # SENT (and applied server-side) but the reply is discarded, so
    # the retry path resends it — the idempotence window.
    _FI_ACTS = ("drop", "broken_fd")
    _FI_ACTS_RETRY = ("drop", "broken_fd", "lost_ack")

    def set(self, key, value):
        if isinstance(value, str):
            value = value.encode()
        data = value
        # rides the shared _int_op retry/reconnect protocol; rc None =
        # injected drop (the write that never lands), else rc == 0
        self._int_op(
            "set", key,
            lambda: self._lib.pt_store_set(self._fd, key.encode(), data,
                                           len(data)))

    # waiting in get() is a short-poll loop, not one long server-side
    # wait: the fd lock must not be held for the full timeout or threads
    # sharing this store (elastic heartbeats during a barrier) starve
    # past their TTL
    _POLL_MS = 50

    def get(self, key, timeout_s=None):
        """Blocking get: waits until the key exists or timeout (then None)."""
        act = _fi.fire("store.get", _supports=self._FI_ACTS, key=key) \
            if _fi.is_enabled() else None
        if act == "drop":
            return None     # the value that never arrives
        to = self.timeout_ms if timeout_s is None else int(timeout_s * 1000)
        deadline = time.monotonic() + to / 1000.0
        cap = 1 << 16
        first = True
        attempt = 0
        while first or time.monotonic() < deadline:
            first = False
            left = max(int((deadline - time.monotonic()) * 1000), 0)
            wait_ms = min(self._POLL_MS, left)
            buf = ctypes.create_string_buffer(cap)
            t_call = time.monotonic()
            with self._mu:
                if act == "broken_fd":
                    self._break_fd_locked()
                    act = None
                n = self._lib.pt_store_get(self._fd, key.encode(), buf,
                                           cap, wait_ms)
            if n == -2:
                cap *= 16
                continue
            if n >= 0:
                return buf.raw[:n]
            # n == -1: server-side timeout OR dead fd. A real timeout
            # consumed its poll window server-side; an instant return
            # is a socket failure — probe, then reconnect. Reconnects
            # keep going until the caller's deadline (a blocking get is
            # deadline-bound by contract, and a server that comes back
            # mid-wait should be found again) but are PACED by the
            # capped backoff — never a hot spin on a dead fd.
            if (time.monotonic() - t_call) * 1000 < wait_ms / 2.0 \
                    and wait_ms >= 10:
                with self._mu:
                    alive = self._fd_alive_locked()
                if not alive:
                    attempt += 1
                    self._reconnect("get", key, min(attempt, 5))
                else:
                    time.sleep(wait_ms / 1000.0)
        return None

    def _int_op(self, name, key, call):
        """Shared retry/reconnect wrapper for the request/reply ops
        (set/add/counter_get/delete): injection site, broken-fd /
        lost-ack cooperation, backoff+reconnect between attempts, and
        the op/key/peer/attempts give-up error — ONE copy of the
        protocol. Returns None on an injected drop."""
        act = _fi.fire("store.%s" % name,
                       _supports=self._FI_ACTS_RETRY,
                       key=key) if _fi.is_enabled() else None
        if act == "drop":
            return None
        # the op lock is held across the WHOLE attempt loop, not per
        # attempt: a retried mutating op must resend its nonce before
        # any other op from this client can interleave — a hot peer
        # thread (elastic heartbeats at socket speed) would otherwise
        # push the pending nonce out of the server's bounded dedup
        # ring during the backoff and the retry would re-apply. Peers
        # block for the backoff+reconnect window, which costs them
        # nothing: the shared socket is dead for everyone until the
        # reconnect lands anyway.
        with self._mu:
            for attempt in range(1, self._op_retries + 1):
                if act == "broken_fd":
                    self._break_fd_locked()
                    act = None
                rc = call()
                if act == "lost_ack":
                    # the request LANDED (call() above ran) but the
                    # reply is "lost": force one pass through the
                    # retry path so the op is resent — the window
                    # where a non-idempotent add double-applies
                    # (nonce dedup keeps it exact)
                    act = None
                    rc = -1
                if rc != -1:
                    return rc
                if attempt < self._op_retries:
                    self._sleep_backoff(attempt)
                    self._reconnect_locked(name)
        raise RuntimeError(
            "TCPStore.%s(key=%r) to %s failed after %d attempts "
            "(socket-level failure; server down or unreachable)"
            % (name, key, self._peer(), self._op_retries))

    def add(self, key, delta=1):
        out = ctypes.c_int64()
        # ONE nonce per logical op, allocated before the retry loop:
        # every resend carries the same (cid, seq), so the server
        # applies the delta at most once no matter how many replies
        # are lost. Allocation takes the op lock — threads sharing
        # this store (elastic heartbeats) must never mint one seq
        # twice.
        with self._mu:
            self._nonce_seq += 1
            seq = self._nonce_seq
        rc = self._int_op(
            "add", key,
            lambda: self._lib.pt_store_add_nonced(
                self._fd, key.encode(), int(delta), self._nonce_cid,
                seq, ctypes.byref(out)))
        if rc is None:
            # injected drop: add has no silent no-op form (callers need
            # the counter value) — surface it as the op failure it is
            raise RuntimeError(
                "TCPStore.add(%r): request dropped (injected fault)"
                % key)
        if rc != 0:
            raise RuntimeError("TCPStore.add(%r) failed (rc=%r)"
                               % (key, rc))
        return int(out.value)

    def counter_get(self, key, default=None):
        """Non-creating counter read: value, or `default` if the counter
        was never created (distinguishes 'never registered' from 0)."""
        out = ctypes.c_int64()
        rc = self._int_op(
            "counter_get", key,
            lambda: self._lib.pt_store_counter_get(self._fd, key.encode(),
                                                   ctypes.byref(out)))
        if rc == -2 or rc is None:
            return default
        if rc != 0:
            raise RuntimeError("TCPStore.counter_get(%r) failed (rc=%r)"
                               % (key, rc))
        return int(out.value)

    def delete(self, key):
        self._int_op(
            "delete", key,
            lambda: self._lib.pt_store_delete(self._fd, key.encode()))

    def barrier(self, name, world_size, timeout_s=None):
        """All ranks arrive; releases when world_size ranks have added.

        REUSABLE by design: arrivals under one name are grouped into
        rounds of ``world_size`` and a release counter advances once
        per completed round — so the same name used again (restart
        generations, repeated ``pg.barrier("x")`` calls) waits for ITS
        round instead of over-counting into an instant or impossible
        release (the pre-resilience bug: ``count``+``go`` keys lived
        forever, so arrival world_size+1 could never reach the ==
        trigger while ``go`` was already set). State is one counter
        per (name, world_size) and fresh keys per round — nothing to
        clean up, no delete/arrive race. The namespace includes
        ``world_size`` because round arithmetic is only coherent
        within ONE world size: a
        SHRUNK restart generation reusing the name (3 ranks arrive,
        then 2 survivors re-barrier) would otherwise fold the old
        world's arrivals into the new world's rounds and strand the
        survivors waiting on rounds that can never fill (a ptcheck
        interleaving-explorer finding; regression-pinned there and in
        tests/test_resilience.py).
        """
        ns = "__barrier/%s/ws%d" % (name, world_size)
        n = self.add(ns + "/count", 1)
        round_i = (n - 1) // world_size
        # the go key is PER ROUND (a fresh KV key, not a mutated one):
        # waiters ride the server-side blocking get and are released
        # the instant the last arrival sets it
        go_key = "%s/go/%d" % (ns, round_i)
        if n == (round_i + 1) * world_size:
            self.set(go_key, b"1")
        got = self.get(go_key, timeout_s)
        if got is None:
            # diagnostic read only — a DEAD master must still surface
            # the contractual TimeoutError (callers match on it for the
            # flight-recorder postmortem), never a masked RuntimeError
            try:
                cur = self.counter_get(ns + "/count",
                                       default=0)
            except RuntimeError:
                cur = n
            raise TimeoutError(
                "barrier %r timed out (%d/%d arrived in round %d)"
                % (name, max(cur - round_i * world_size, 0),
                   world_size, round_i))
        # The master hosts the store and may close it once it returns;
        # a peer that has not read the go key yet (its next poll not
        # yet sent) would then wait out its whole timeout on a dead
        # server. So each peer confirms it got past under its arrival
        # slot, and the master returns only once every slot has.
        left = "%s/left/%d/" % (ns, round_i)
        slot = n - 1 - round_i * world_size
        if not self.is_master:
            self.set(left + str(slot), b"1")
            return
        end = time.monotonic() + (self.timeout_ms / 1000.0
                                  if timeout_s is None else timeout_s)
        for other in range(world_size):
            if other != slot and self.get(
                    left + str(other),
                    max(end - time.monotonic(), 0)) is None:
                raise TimeoutError(
                    "barrier %r released, but the peer in slot %d never "
                    "confirmed it got past (round %d)"
                    % (name, other, round_i))

    def close(self):
        with self._mu:
            self._closed = True
            if self._fd is not None and self._fd >= 0:
                self._lib.pt_store_close(self._fd)
                self._fd = -1
        if self._server is not None:
            self._lib.pt_store_server_stop(self._server)
            self._server = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        # ptlint: silent-except-ok — __del__ at store-GC time must
        # never raise (socket may already be torn down)
        except Exception:
            pass


def create_store_from_env(world_size=None):
    """Build the rendezvous store from PADDLE_MASTER / rank env vars."""
    master = os.environ.get("PADDLE_MASTER", "127.0.0.1:0")
    host, _, port = master.partition(":")
    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    return TCPStore(host or "127.0.0.1", int(port or 0), is_master=(rank == 0))
