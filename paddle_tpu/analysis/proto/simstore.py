"""SimStore: the TCPStore client API as schedulable in-process state.

``SimStore`` is the server (one kv/counter namespace shared by every
client); ``SimClient`` is one rank's connection, implementing the
exact client surface protocol code consumes — ``set``/``get`` (with
the server-side blocking-wait semantics)/``add``/``counter_get``/
``delete``/``barrier`` — so the round-based barrier, the election
protocol, ``ElasticManager`` and the watchdog bundle helpers run
**unmodified** (they already take a store object; ``barrier`` is
literally ``TCPStore.barrier`` invoked unbound on the sim client).

Every op starts at a scheduler boundary (the interleaving/crash/fault
point) and then applies atomically — the wire protocol's one-op-per-
request discipline. ``add`` models the shipped client's retry
protocol including the nonce-idempotence fix: a ``lost_ack``
transition applies the op, "loses" the reply, yields (the race
window), and resends the same client nonce. ``SimStore(
idempotent_add=False)`` reproduces the pre-fix server that re-applies
a retried delta — the known double-apply suspect, kept as a
regression fixture to prove the checker sees it.
"""
from __future__ import annotations


class SimStore:
    """Shared server state. Only ever touched by the single running
    task (the scheduler's invariant), so no locks — determinism comes
    from the scheduler, not from synchronization."""

    # matches the server's kNonceRing: the dedup window is a bounded
    # ring per client, not a single slot — other threads sharing a
    # client interleave adds between a lost ack and its retry
    NONCE_RING = 64

    def __init__(self, idempotent_add=True):
        self.kv = {}                # key -> bytes
        self.counters = {}          # key -> int
        self.nonces = {}            # client id -> [(seq, value), ...]
        self.idempotent_add = bool(idempotent_add)
        # [(key, cid, seq, value, applied)] — the verdicts' ledger:
        # double-applies and duplicate leader claims are visible here
        self.applies = []
        # [(key, cid, value)] — what each client's add() RETURNED
        self.observed = []

    def apply_add(self, key, delta, cid, seq):
        """Server-side add. With ``idempotent_add`` a duplicate
        (cid, seq) found in the client's nonce ring returns the
        recorded value without re-applying — the dedup the shipped
        server performs; without it every request applies (the
        historical behavior)."""
        ring = self.nonces.setdefault(cid, [])
        if self.idempotent_add:
            for s, v in ring:
                if s == seq:
                    self.applies.append((key, cid, seq, v, False))
                    return v
        value = self.counters.get(key, 0) + int(delta)
        self.counters[key] = value
        ring.append((seq, value))
        if len(ring) > self.NONCE_RING:
            ring.pop(0)
        self.applies.append((key, cid, seq, value, True))
        return value

    def observed_adds(self, key):
        """[(cid, value)] per client-OBSERVED add result on ``key``
        (what the protocol code's ``add()`` call returned — under a
        lost ack this is the retry's view, not the first apply's)."""
        return [(cid, value) for k, cid, value in self.observed
                if k == key]

    def fingerprint(self):
        return (tuple(sorted(self.kv.items())),
                tuple(sorted(self.counters.items())),
                tuple(sorted((cid, tuple(ring))
                             for cid, ring in self.nonces.items())))


class SimClient:
    """One rank's store connection. API-compatible with the TCPStore
    client surface the protocol plane consumes."""

    # the real client's default op deadline (timeout_s=300); virtual
    # seconds here, so a forgotten-timeout wait still unwinds
    DEFAULT_TIMEOUT_S = 300.0

    def __init__(self, store, sched, name, timeout_s=None,
                 is_master=False):
        self._store = store
        self._sched = sched
        self._cid = name
        # the client in the process that hosts the store: the barrier
        # holds it until every peer has confirmed leaving
        self.is_master = is_master
        self._seq = 0
        self._timeout_s = (self.DEFAULT_TIMEOUT_S if timeout_s is None
                           else float(timeout_s))
        sched.store = store

    # -- client ops (each: boundary -> atomic apply) ----------------------

    def set(self, key, value):
        if isinstance(value, str):
            value = value.encode()
        self._sched.op_boundary("set", key)
        self._store.kv[key] = bytes(value)
        self._sched.wake_key(key)
        self._sched.current_task().note("set", key, len(value))

    def get(self, key, timeout_s=None):
        """Blocking get: the server parks the request until the key
        exists or the deadline passes (then None) — modeled as a
        scheduler block woken by the setting op (push-release) or by
        a timeout transition."""
        self._sched.op_boundary("get", key)
        to = self._timeout_s if timeout_s is None else float(timeout_s)
        deadline = self._sched.clock.now + max(0.0, to)
        while True:
            val = self._store.kv.get(key)
            if val is not None:
                self._sched.current_task().note("get", key, val)
                return val
            reason = self._sched.block_on_key(key, deadline)
            if reason == "timeout":
                self._sched.current_task().note("get", key, None)
                return None

    def add(self, key, delta=1):
        """Atomic counter add, running the shipped client's retry
        protocol: one nonce (cid, seq) per logical op; a lost ack
        (scheduler transition ``a:<task>``) applies the op, yields the
        race window, then resends the SAME nonce — idempotent against
        the nonce-dedup server, double-applying against the legacy
        one."""
        self._seq += 1
        seq = self._seq
        mode = self._sched.op_boundary("add", key)
        value = self._store.apply_add(key, delta, self._cid, seq)
        if mode == "lost_ack":
            self._sched.current_task().note("add.lost", key, value)
            # the reply never arrived: the client cannot know whether
            # the delta landed; its retry resends the same op (same
            # nonce) after the backoff — a fresh boundary so peers can
            # interleave inside the race window
            self._sched.op_boundary("add.retry", key)
            value = self._store.apply_add(key, delta, self._cid, seq)
        self._store.observed.append((key, self._cid, value))
        self._sched.current_task().note("add", key, value)
        return value

    def counter_get(self, key, default=None):
        self._sched.op_boundary("counter_get", key)
        value = self._store.counters.get(key)
        out = default if value is None else int(value)
        self._sched.current_task().note("counter_get", key, out)
        return out

    def delete(self, key):
        self._sched.op_boundary("delete", key)
        self._store.kv.pop(key, None)
        self._store.counters.pop(key, None)
        self._sched.current_task().note("delete", key, None)

    def barrier(self, name, world_size, timeout_s=None):
        """THE real barrier: ``TCPStore.barrier`` executed unbound on
        this client — the protocol under test is the shipped code, not
        a model of it."""
        from ...distributed.store import TCPStore

        return TCPStore.barrier(self, name, world_size,
                                timeout_s=timeout_s)
