"""Retried-add idempotence: the lost-ack double-apply hole, closed.

The historical behavior (documented as a caveat since the resilience
round): the TCPStore client retries ops after socket-level failures,
and a reply lost AFTER the server applied an ``add`` re-applied the
delta on retry — double-counting barriers and, worse, leader claims
(the first rank to OBSERVE counter value 1 leads; a double-applied
retry observes 2 and nobody leads). The fix is a client op nonce: every
``add`` carries a per-connection random 64-bit id + per-op sequence,
resends carry the SAME nonce, and the server replays the recorded
result for a duplicate instead of re-applying (csrc/store.cc op 'N').

Layers pinned here:

- wire level: a duplicate (cid, seq) request re-applies nothing;
- client level: the injected ``lost_ack`` fault (applies the op, then
  forces the retry path) keeps counts exact and claims unique;
- multi-process: concurrent claimants with injected lost acks still
  elect exactly one leader and count exactly;
- ptcheck twin: ``add_legacy`` (the pre-fix semantics) stays findable,
  ``idempotence`` stays clean — tests/test_ptcheck.py.
"""
import ctypes
import json
import os
import sys
import textwrap

import pytest

from paddle_tpu.core import native
from paddle_tpu.distributed.store import TCPStore
from paddle_tpu.resilience import faultinject as fi

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "tests"))
from dist_utils import free_port, rank_env, report, run_ranks  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_fault_counters():
    """Drop the fault-counter samples this suite's injections create:
    the resilience suite's disabled-path guard pins
    ``faults_injected_total`` sample-free, and counters are
    process-global (the PR-12 memory-suite discipline)."""
    from paddle_tpu.monitor import registry as mreg

    yield
    m = mreg.get_registry().get("faults_injected_total")
    if m is not None:
        for key in list(m._children):
            m.remove(*key)


@pytest.fixture
def store_pair():
    master = TCPStore(is_master=True)
    client = TCPStore(port=master.port)
    yield master, client
    client.close()
    master.close()


class TestWireLevel:
    def test_duplicate_nonce_replies_without_reapplying(self,
                                                        store_pair):
        """The server contract, driven raw: resending the SAME
        (cid, seq) returns the recorded value and leaves the counter
        untouched; a FRESH seq applies."""
        master, client = store_pair
        lib = native.get_lib()
        out = ctypes.c_int64()
        fd = client._fd
        assert lib.pt_store_add_nonced(fd, b"wire", 5, 77, 1,
                                       ctypes.byref(out)) == 0
        assert out.value == 5
        # duplicate: reply replayed, no second application
        assert lib.pt_store_add_nonced(fd, b"wire", 5, 77, 1,
                                       ctypes.byref(out)) == 0
        assert out.value == 5
        assert client.counter_get("wire") == 5
        # fresh seq applies
        assert lib.pt_store_add_nonced(fd, b"wire", 5, 77, 2,
                                       ctypes.byref(out)) == 0
        assert out.value == 10
        assert client.counter_get("wire") == 10

    def test_legacy_add_still_works(self, store_pair):
        """The un-nonced 'A' op keeps its semantics (old clients)."""
        master, client = store_pair
        lib = native.get_lib()
        out = ctypes.c_int64()
        assert lib.pt_store_add(client._fd, b"legacy", 3,
                                ctypes.byref(out)) == 0
        assert out.value == 3

    def test_interleaved_adds_do_not_evict_pending_nonce(
            self, store_pair):
        """The dedup window is a RING, not a last-op slot: one
        TCPStore is routinely shared across threads (elastic
        heartbeats next to a leader claim), so other adds from the
        same cid land between a lost ack and its retry — a
        last-op-only ledger would evict the pending nonce and
        re-apply the claim."""
        master, client = store_pair
        lib = native.get_lib()
        out = ctypes.c_int64()
        fd = client._fd
        assert lib.pt_store_add_nonced(fd, b"claim", 1, 9, 1,
                                       ctypes.byref(out)) == 0
        assert out.value == 1       # applied; pretend the ack is lost
        for seq in range(2, 50):    # 48 interleaved heartbeat adds
            lib.pt_store_add_nonced(fd, b"beat", 1, 9, seq,
                                    ctypes.byref(out))
        # the retry of seq 1 must STILL find its nonce
        assert lib.pt_store_add_nonced(fd, b"claim", 1, 9, 1,
                                       ctypes.byref(out)) == 0
        assert out.value == 1
        assert client.counter_get("claim") == 1
        assert client.counter_get("beat") == 48

    def test_nonce_ledger_is_bounded_under_client_churn(
            self, store_pair):
        """A long-lived master must not grow memory with every client
        generation: past 4096 registered cids the oldest are evicted
        FIFO. Eviction loses only that dead client's dedup window —
        recent cids keep theirs."""
        master, client = store_pair
        lib = native.get_lib()
        out = ctypes.c_int64()
        fd = client._fd
        lib.pt_store_add_nonced(fd, b"old", 1, 1, 1,
                                ctypes.byref(out))
        assert out.value == 1
        for cid in range(2, 4103):      # churn past kMaxNonceClients
            lib.pt_store_add_nonced(fd, b"churn", 1, cid, 1,
                                    ctypes.byref(out))
        # the ancient cid's dup re-applies (its ledger slot is gone)
        lib.pt_store_add_nonced(fd, b"old", 1, 1, 1,
                                ctypes.byref(out))
        assert out.value == 2
        # a recent cid still dedups
        lib.pt_store_add_nonced(fd, b"churn", 1, 4102, 1,
                                ctypes.byref(out))
        assert out.value == 4101


class TestClientRetry:
    def test_lost_ack_applies_exactly_once(self, store_pair):
        """The injected lost-ack (request applied, reply discarded,
        retry path resends) leaves the counter EXACT and returns the
        originally-applied value."""
        master, client = store_pair
        assert client.add("k") == 1
        fi.enable("store.add:lost_ack@1", seed=0)
        try:
            assert client.add("k") == 2
        finally:
            fi.disable()
        assert client.counter_get("k") == 2
        assert client.add("k") == 3

    def test_lost_ack_on_first_claim_still_observes_one(self,
                                                        store_pair):
        """The leader-election shape: the claim that loses its ack
        must still OBSERVE value 1 after the retry — a double-apply
        here is a vanished leadership."""
        master, client = store_pair
        fi.enable("store.add:lost_ack@1", seed=0)
        try:
            assert client.add("leader") == 1
        finally:
            fi.disable()
        assert client.counter_get("leader") == 1

    def test_shared_store_heartbeats_during_lost_ack_claim(
            self, store_pair):
        """The production shape that motivated the nonce ring: a
        heartbeat thread hammers the SAME client while the main
        thread's claim loses its ack. Whatever the interleaving (and
        whichever op the injected rule actually hits), both counters
        must end exact and the claim must observe 1."""
        import threading

        master, client = store_pair
        stop = threading.Event()
        beats = [0]

        def heartbeat():
            while not stop.is_set():
                client.add("hb", 1)
                beats[0] += 1

        t = threading.Thread(target=heartbeat, daemon=True)
        t.start()
        fi.enable("store.add:lost_ack@1", seed=0)
        try:
            claim = client.add("claim2", 1)
        finally:
            fi.disable()
            stop.set()
            t.join(timeout=10)
        assert claim == 1
        assert client.counter_get("claim2") == 1
        assert client.counter_get("hb") == beats[0]

    def test_lost_ack_counts_as_retry_metric(self, store_pair):
        from paddle_tpu.monitor import registry as mreg

        master, client = store_pair
        before = _retry_count(mreg, "add")
        fi.enable("store.add:lost_ack@1", seed=0)
        try:
            client.add("m")
        finally:
            fi.disable()
        assert _retry_count(mreg, "add") == before + 1


def _retry_count(mreg, op):
    snap = mreg._default_registry.snapshot()
    for series in snap.get("store_op_retries_total",
                           {}).get("series", []):
        if series.get("labels", {}).get("op") == op:
            return series.get("value", 0)
    return 0


_WORKER = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, %(root)r)
    from paddle_tpu.distributed.store import TCPStore
    from paddle_tpu.resilience import faultinject as fi

    rank = int(os.environ["PADDLE_TRAINER_ID"])
    host, _, port = os.environ["PADDLE_MASTER"].partition(":")
    store = TCPStore(host, int(port), is_master=False)
    # every rank loses the ack of its FIRST add: the claim itself
    fi.enable("store.add:lost_ack@1", seed=rank)
    claim = store.add("leader", 1)
    fi.disable()
    for _ in range(4):
        store.add("ctr", 1)
    store.set("done/%%d" %% rank,
              json.dumps({"rank": rank, "claim": claim}))
    out = {"rank": rank, "claim": claim}
    print(json.dumps(out))
    store.close()
""")


class TestMultiProcess:
    def test_concurrent_lost_ack_claims_elect_exactly_one(
            self, tmp_path):
        """3 processes, each losing the ack of its own leader claim:
        the counter must end EXACT (3 claims + 12 adds) and exactly
        one process must have observed claim == 1."""
        port = free_port()
        master = TCPStore(port=port, is_master=True)
        worker = tmp_path / "idem_worker.py"
        worker.write_text(_WORKER % {"root": REPO_ROOT})
        try:
            res = run_ranks(
                [sys.executable, str(worker)],
                [rank_env(rank, 3, master.port) for rank in range(3)],
                60)
            assert all(rc == 0 for _, rc, _, _ in res), report(res)
            claims = sorted(json.loads(out.strip().splitlines()[-1])
                            ["claim"] for _, _, out, _ in res)
            assert claims == [1, 2, 3], claims
            assert master.counter_get("leader") == 3
            assert master.counter_get("ctr") == 12
        finally:
            master.close()
