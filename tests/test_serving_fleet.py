"""Serving fleet: membership protocol, affinity index, router+replica
end-to-end (ISSUE 16).

Three layers, mirroring the subsystem:

- membership unit tests over a REAL in-process ``TCPStore`` (register
  claims exactly one generation, lease/evict/drain key semantics,
  ``ReplicaView`` liveness on an injected clock, ``pick_replica``
  pure-function behavior);
- ``AffinityIndex`` radix-over-chunks behavior (prefix_cache.py
  chunking: full ``block_size`` chunks over ``tokens[:-1]``);
- in-process fleets of tiny-llama engines behind real HTTP: the
  shared-prefix path lands on the affinity replica, a killed replica's
  in-flight requests re-route with ZERO accepted requests lost, and
  every survivor keeps ``decode_compiles == 1`` (reroutes reuse the
  compiled step — no recompile storm).

Flag-off pins (the PR-2/5/6 discipline): ``FLAGS_serving_fleet`` off
means Replica/Router refuse to construct — no ``pt-sfleet-*`` threads,
no ``__sfleet`` store traffic, no ``router_*`` series.
"""
from __future__ import annotations

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.distributed.store import TCPStore
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.monitor import fleet as mfleet
from paddle_tpu.serving.fleet import (
    AffinityIndex,
    Replica,
    ReplicaView,
    Router,
    membership,
    pick_replica,
)


@pytest.fixture(scope="module")
def llama():
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=4,
                      max_position_embeddings=64, use_parallel=False)
    return LlamaForCausalLM(cfg), cfg


@pytest.fixture()
def fleet_flag():
    paddle.set_flags({"FLAGS_serving_fleet": True})
    yield
    paddle.set_flags({"FLAGS_serving_fleet": False})
    mfleet.clear_router_hook()


@pytest.fixture()
def store_pair():
    master = TCPStore(is_master=True)
    yield master
    master.close()


def _client(master):
    return TCPStore(port=master.port)


# ---------------------------------------------------------------------------
# membership protocol (unit, real TCPStore)
# ---------------------------------------------------------------------------

class TestMembership:
    def test_register_claims_exactly_one_generation(self, store_pair):
        c = _client(store_pair)
        gen = membership.register_replica(c, 0, "http://h:1")
        assert gen == 1
        rec = membership.read_replica(c, 0)
        assert rec["rank"] == 0 and rec["url"] == "http://h:1"
        assert rec["generation"] == 1
        # the capability snapshot carries the disaggregation seam
        assert rec["capabilities"] == {"prefill": True, "decode": True,
                                       "disaggregation": False}
        # a NEW incarnation (restart) claims the next generation
        assert membership.register_replica(c, 0, "http://h:2") == 2

    def test_read_replica_absent_is_none(self, store_pair):
        c = _client(store_pair)
        assert membership.read_replica(c, 7, timeout_s=0.05) is None

    def test_lease_and_drain_keys(self, store_pair):
        c = _client(store_pair)
        membership.register_replica(c, 1, "http://h:1")
        assert c.counter_get(membership.beat_key(1)) == 1
        membership.renew_lease(c, 1)
        assert c.counter_get(membership.beat_key(1)) == 2
        assert not membership.is_draining(c, 1)
        membership.mark_draining(c, 1)
        assert membership.is_draining(c, 1)
        membership.clear_draining(c, 1)
        assert not membership.is_draining(c, 1)
        membership.deregister_replica(c, 1)
        assert c.counter_get(membership.beat_key(1)) is None

    def test_view_liveness_on_injected_clock(self, store_pair):
        c = _client(store_pair)
        now = [0.0]
        view = ReplicaView(c, world_size=2, ttl_s=2.0,
                           clock=lambda: now[0])
        # nobody registered: both dead
        assert view.alive() == [] and view.dead() == [0, 1]
        membership.register_replica(c, 0, "http://h:1")
        assert view.alive() == [0]
        # silence past ttl on the WATCHER's clock ages the lease out
        now[0] = 3.0
        assert 0 in view.dead()
        # a renewal revives it
        membership.renew_lease(c, 0)
        assert view.alive() == [0]
        # eviction (beat deleted) is immediate death, no ttl wait
        membership.evict_replica(c, 0)
        assert view.alive() == []

    def test_pick_replica_affinity_then_load(self):
        assert pick_replica([]) == (None, False)
        # no affinity: least-loaded wins, rank breaks exact ties
        assert pick_replica([0, 1], load={0: 0.9, 1: 0.1}) == (1, False)
        assert pick_replica([0, 1], load={0: 0.5, 1: 0.5}) == (0, False)
        # affinity trumps load ...
        assert pick_replica([0, 1], load={0: 0.9, 1: 0.1},
                            affinity={0: 3}) == (0, True)
        # ... and among equal-depth affinity matches, load decides
        assert pick_replica([0, 1], load={0: 0.9, 1: 0.1},
                            affinity={0: 2, 1: 2}) == (1, True)
        # an evicted candidate is simply not in the list
        assert pick_replica([1], affinity={0: 5}) == (1, False)


# ---------------------------------------------------------------------------
# affinity index
# ---------------------------------------------------------------------------

class TestAffinityIndex:
    def test_chunking_matches_prefix_cache_discipline(self):
        idx = AffinityIndex(block_size=4)
        # 9 tokens -> usable 8 -> 2 full chunks; the last token is
        # never part of a chunk (prefix_cache never stores it)
        idx.note(list(range(9)), rank=0)
        assert idx.match(list(range(9))) == {0: 2}
        # same first chunk, divergent second: depth-1 match only
        probe = [0, 1, 2, 3, 99, 98, 97, 96, 5]
        assert idx.match(probe) == {0: 1}
        # fewer than block_size+1 tokens can never match
        assert idx.match([0, 1, 2, 3]) == {}

    def test_deepest_rank_wins_and_invalidate_drops(self):
        idx = AffinityIndex(block_size=2)
        idx.note([1, 2, 3, 4, 5], 0)        # chunks (1,2),(3,4)
        idx.note([1, 2, 9, 9, 9], 1)        # chunks (1,2),(9,9)
        m = idx.match([1, 2, 3, 4, 5])
        assert m[0] == 2 and m[1] == 1
        idx.invalidate(0)
        assert idx.match([1, 2, 3, 4, 5]) == {1: 1}
        # pruned subtrees release their nodes
        assert idx.stats()["nodes"] == 2

    def test_depth_cap(self):
        idx = AffinityIndex(block_size=1, max_chunks=3)
        idx.note(list(range(10)), 0)
        assert idx.match(list(range(10))) == {0: 3}


# ---------------------------------------------------------------------------
# flag-off pins
# ---------------------------------------------------------------------------

class TestFlagOffPinned:
    def test_construction_refused(self, llama):
        model, _ = llama
        flags = paddle.get_flags(["FLAGS_serving_fleet"])
        assert not flags["FLAGS_serving_fleet"]
        with pytest.raises(RuntimeError, match="FLAGS_serving_fleet"):
            Router(endpoints={0: "http://h:1"})
        eng = serving.Engine(model, max_slots=1, num_blocks=8,
                             block_size=4)
        with pytest.raises(RuntimeError, match="FLAGS_serving_fleet"):
            Replica(eng, 0)
        # refusal happens BEFORE any thread or store traffic
        assert not [t for t in threading.enumerate()
                    if t.name.startswith("pt-sfleet")]
        assert mfleet._router_hook is None

    def test_no_sfleet_store_traffic(self, store_pair):
        c = _client(store_pair)
        with pytest.raises(RuntimeError):
            Router(store=c, world_size=2)
        for rank in range(2):
            assert c.counter_get(membership.gen_key(rank)) is None
            assert c.counter_get(membership.beat_key(rank)) is None


# ---------------------------------------------------------------------------
# fleet end-to-end (tiny llama engines, real HTTP, real store)
# ---------------------------------------------------------------------------

def _mk_fleet(model, master, n, ttl_s=2.0):
    replicas = []
    for r in range(n):
        eng = serving.Engine(model, max_slots=2, num_blocks=64,
                             block_size=4)
        replicas.append(Replica(
            eng, r, store=_client(master), ttl_s=ttl_s,
            heartbeat_interval_s=0.1).start())
    router = Router(store=_client(master), world_size=n,
                    block_size=4, ttl_s=ttl_s)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        router.refresh_membership()
        if router.debug_payload()["replicas"]["live"] == n:
            break
        time.sleep(0.05)
    return replicas, router


class TestFleetEndToEnd:
    def test_shared_prefix_lands_on_the_affinity_replica(self, llama,
                                                         fleet_flag,
                                                         store_pair):
        model, _ = llama
        replicas, router = _mk_fleet(model, store_pair, 2)
        try:
            rng = np.random.RandomState(0)
            shared = rng.randint(1, 64, size=9).tolist()
            nonces = [router.submit(
                shared + rng.randint(1, 64, size=3).tolist(),
                max_new_tokens=5) for _ in range(5)]
            assert router.wait_all(timeout_s=180)
            reqs = [router.request(n) for n in nonces]
            assert all(r["state"] == "finished" for r in reqs)
            assert all(r["output_tokens"] == len(r["tokens"])
                       for r in reqs)
            # every dispatch after the first shares the 2-chunk prefix:
            # affinity pins them to the first request's replica
            placed = {r["rank"] for r in reqs}
            assert len(placed) == 1
            dbg = router.debug_payload()
            assert dbg["affinity"]["hit_rate"] >= 0.5
            assert dbg["requests"]["finished"] == 5
        finally:
            for rep in replicas:
                rep.stop()
            router.close()

    def test_killed_replica_requests_reroute_none_lost(self, llama,
                                                       fleet_flag,
                                                       store_pair):
        """THE acceptance pin: kill a replica with accepted requests
        on it — every request finishes on a survivor, no dispatch ever
        lands on the evicted rank afterwards, and the survivor's
        decode path never recompiles."""
        model, _ = llama
        replicas, router = _mk_fleet(model, store_pair, 2)
        try:
            rng = np.random.RandomState(1)
            prompts = [rng.randint(1, 64, size=10).tolist()
                       for _ in range(6)]
            nonces = [router.submit(p, max_new_tokens=5)
                      for p in prompts]
            victim = next(
                r["rank"]
                for n in nonces
                for r in [router.request(n)]
                if r["rank"] is not None)
            # kill it NOW — its accepted-but-unfinished requests must
            # move. deregister deletes the lease: immediate death for
            # the router's view, no ttl wait
            replicas[victim].stop(deregister=True)
            assert router.wait_all(timeout_s=180)
            reqs = [router.request(n) for n in nonces]
            assert all(r["state"] == "finished" for r in reqs), [
                (r["nonce"], r["state"], r["reason"]) for r in reqs]
            # the victim is evicted, nothing still assigned to it
            dbg = router.debug_payload()
            assert dbg["replicas"]["evicted"] >= 1
            assert all(r["rank"] != victim for r in reqs)
            # no recompile storm: the survivor absorbed the reroutes
            # inside its one compiled decode step
            survivor = replicas[1 - victim]
            assert survivor.engine.stats()["decode_compiles"] == 1
        finally:
            for rep in replicas:
                rep.stop()
            router.close()

    def test_drain_and_reschedule_moves_unstarted_work(self, llama,
                                                       fleet_flag,
                                                       store_pair):
        model, _ = llama
        replicas, router = _mk_fleet(model, store_pair, 2)
        try:
            rng = np.random.RandomState(2)
            nonces = [router.submit(
                rng.randint(1, 64, size=8).tolist(), max_new_tokens=4)
                for _ in range(4)]
            drained = next(
                r["rank"]
                for n in nonces
                for r in [router.request(n)]
                if r["rank"] is not None)
            replicas[drained].drain()
            assert router.wait_all(timeout_s=180)
            reqs = [router.request(n) for n in nonces]
            assert all(r["state"] == "finished" for r in reqs)
            # the drain verdict was published to the store, and the
            # router observed it (draining or later recovered states
            # both prove the marker moved through the plane)
            assert membership.is_draining(
                _client(store_pair), drained)
        finally:
            for rep in replicas:
                rep.stop()
            router.close()

    def test_enqueue_is_nonce_idempotent_over_http(self, llama,
                                                   fleet_flag,
                                                   store_pair):
        model, _ = llama
        eng = serving.Engine(model, max_slots=2, num_blocks=64,
                             block_size=4)
        rep = Replica(eng, 0, store=_client(store_pair)).start()
        try:
            body = json.dumps({
                "nonce": "n-1", "prompt": [1, 2, 3],
                "max_new_tokens": 3}).encode()

            def post():
                req = urllib.request.Request(
                    rep.url + "/sfleet/enqueue", data=body,
                    headers={"Content-Type": "application/json"},
                    method="POST")
                with urllib.request.urlopen(req, timeout=10) as r:
                    return json.loads(r.read().decode())

            first = post()
            assert first["deduped"] is False
            # the retry (lost-ack replay) maps to the SAME admission
            second = post()
            assert second["deduped"] is True
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                with urllib.request.urlopen(
                        rep.url + "/sfleet/result/n-1",
                        timeout=10) as r:
                    st = json.loads(r.read().decode())
                if st["state"] == "finished":
                    break
                time.sleep(0.05)
            assert st["state"] == "finished"
            assert len(st["tokens"]) == 3
            # ONE admission total: dedup means dedup
            assert eng.stats()["requests_finished"] == 1
        finally:
            rep.stop()

    def test_unknown_post_route_is_404(self, llama, fleet_flag,
                                       store_pair):
        model, _ = llama
        eng = serving.Engine(model, max_slots=1, num_blocks=8,
                             block_size=4)
        rep = Replica(eng, 0).start()
        try:
            req = urllib.request.Request(
                rep.url + "/sfleet/nope", data=b"{}", method="POST")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=10)
            assert ei.value.code == 404
        finally:
            rep.stop()


# ---------------------------------------------------------------------------
# distributed tracing (ISSUE 17): cross-process context + reroute causality
# ---------------------------------------------------------------------------

from paddle_tpu.monitor import trace as mtrace  # noqa: E402
from paddle_tpu.monitor import trace_merge as tmerge  # noqa: E402


@pytest.fixture()
def trace_flag():
    paddle.set_flags({"FLAGS_monitor_trace": True})
    mtrace.enable()
    yield
    paddle.set_flags({"FLAGS_monitor_trace": False})
    mtrace.disable()
    mtrace.clear()


class TestFleetTracing:
    def test_request_journey_is_one_trace_across_router_and_engine(
            self, llama, fleet_flag, trace_flag, store_pair):
        """The tentpole contract: the router mints the trace, the
        enqueue traceparent carries it, and the replica engine's phase
        spans land under the SAME id with the dispatch span as remote
        parent; /sfleet/result hands the span summary back for the
        settle span's e2e attribution."""
        model, _ = llama
        replicas, router = _mk_fleet(model, store_pair, 1)
        try:
            rng = np.random.RandomState(3)
            nonce = router.submit(rng.randint(1, 64, size=8).tolist(),
                                  max_new_tokens=4)
            assert router.wait_all(timeout_s=180)
            req = router.request(nonce)
            assert req["state"] == "finished"
            tid = req["trace_id"]
            assert tid is not None
            tr = mtrace.get_trace(tid)
            names = {s["name"] for s in tr["spans"]}
            # router half AND engine half, one trace id
            assert {"route", "router_queue", "placement", "dispatch",
                    "settle"} <= names
            assert {"request", "prefill", "decode"} <= names
            dispatch = next(s for s in tr["spans"]
                            if s["kind"] == "dispatch")
            assert dispatch["attrs"]["outcome"] == "accepted"
            assert dispatch["attrs"]["nonce"] == nonce
            engine_root = next(s for s in tr["spans"]
                               if s["kind"] == "request"
                               and s["name"] == "request")
            assert engine_root["remote_parent"] == dispatch["span_id"]
            # the result payload's span summary settled e2e attribution
            assert req["replica_trace"]["trace_id"] == tid
            assert req["replica_trace"]["phases_s"]["decode"] > 0
            settle = next(s for s in tr["spans"]
                          if s["kind"] == "settle")
            assert settle["attrs"]["status"] == "finished"
            assert settle["attrs"]["replica_phases_s"]["prefill"] >= 0
            root = next(s for s in tr["spans"] if s["name"] == "route")
            assert root["attrs"]["status"] == "finished"
            assert root["attrs"]["e2e_s"] > 0
            # dispatch + e2e histograms carry trace-id exemplars
            assert any(e["trace_id"] == tid for e in
                       mtrace.exemplars("router_e2e_seconds").values())
            assert any(
                e["trace_id"] == tid for e in
                mtrace.exemplars("router_dispatch_seconds").values())
            # phase breakdown includes the router queue hop
            assert "router_queue" in mtrace.phase_breakdown(tid)
        finally:
            for rep in replicas:
                rep.stop()
            router.close()

    def test_killed_replica_trace_pins_reroute_causality(
            self, llama, fleet_flag, trace_flag, store_pair):
        """THE acceptance pin (ISSUE 17): a rerouted request's merged
        timeline shows attempt 1 on the victim, a reroute span naming
        the reason, and attempt 2 finishing on the survivor — all
        under ONE trace id."""
        model, _ = llama
        replicas, router = _mk_fleet(model, store_pair, 2)
        try:
            rng = np.random.RandomState(4)
            nonces = [router.submit(
                rng.randint(1, 64, size=10).tolist(), max_new_tokens=5)
                for _ in range(6)]
            victim = next(
                r["rank"]
                for n in nonces
                for r in [router.request(n)]
                if r["rank"] is not None)
            moved = [n for n in nonces
                     if router.request(n)["rank"] == victim]
            replicas[victim].stop(deregister=True)
            assert router.wait_all(timeout_s=180)
            req = router.request(moved[0])
            assert req["state"] == "finished"
            assert req["reroutes"] >= 1
            survivor = req["rank"]
            assert survivor != victim
            assert req["attempt_ranks"][0] == victim
            assert req["attempt_ranks"][-1] == survivor
            tid = req["trace_id"]
            tr = mtrace.get_trace(tid)
            dispatches = [s for s in tr["spans"]
                          if s["kind"] == "dispatch"]
            assert dispatches[0]["attrs"]["replica"] == victim
            assert dispatches[0]["attrs"]["outcome"] == "accepted"
            assert dispatches[-1]["attrs"]["replica"] == survivor
            assert dispatches[-1]["attrs"]["outcome"] == "accepted"
            reroutes = [s for s in tr["spans"]
                        if s["kind"] == "reroute"]
            assert reroutes, "reroute span missing from the timeline"
            assert reroutes[0]["attrs"]["reason"] in (
                "lease-evicted", "404", "shed", "drain")
            assert reroutes[0]["attrs"]["from_rank"] == victim
            assert req["reroute_reasons"][0] == \
                reroutes[0]["attrs"]["reason"]
            # causality reads left-to-right: attempt 1, reroute,
            # attempt 2
            assert dispatches[0]["t_start"] \
                <= reroutes[0]["t_start"] <= dispatches[-1]["t_start"]
            # ...and the merged-artifact summary table pins the same
            # chain from the router journal alone (a SIGKILLed
            # victim's own journal dies with it)
            row = tmerge.fleet_trace_summary(mtrace.dump())[tid]
            assert [d["replica"] for d in row["dispatches"]
                    if d["outcome"] == "accepted"] == \
                req["attempt_ranks"]
            assert row["reroutes"][0]["reason"] == \
                reroutes[0]["attrs"]["reason"]
            # no recompile storm on the survivor, even traced
            assert replicas[survivor].engine.stats()[
                "decode_compiles"] == 1
        finally:
            for rep in replicas:
                rep.stop()
            router.close()

    def test_trace_off_pins_wire_format_and_result_keys(
            self, llama, fleet_flag, store_pair, monkeypatch):
        """Flags-off bit-identical pin: journal off means NO
        traceparent field on the enqueue wire, NO trace keys in the
        result payload, no trace ids router-side, and an empty
        journal."""
        import paddle_tpu.serving.fleet.router as rmod

        assert not paddle.get_flags(
            ["FLAGS_monitor_trace"])["FLAGS_monitor_trace"]
        sent = []
        orig = rmod._http_post_json

        def spy(url, payload, timeout_s):
            sent.append(payload)
            return orig(url, payload, timeout_s)

        monkeypatch.setattr(rmod, "_http_post_json", spy)
        model, _ = llama
        replicas, router = _mk_fleet(model, store_pair, 1)
        try:
            rng = np.random.RandomState(5)
            nonce = router.submit(rng.randint(1, 64, size=8).tolist(),
                                  max_new_tokens=3)
            assert router.wait_all(timeout_s=180)
            req = router.request(nonce)
            assert req["state"] == "finished"
            assert req["trace_id"] is None
            assert req["replica_trace"] is None
            assert sent and all("traceparent" not in p for p in sent)
            with urllib.request.urlopen(
                    "%s/sfleet/result/%s" % (replicas[0].url, nonce),
                    timeout=10) as r:
                st = json.loads(r.read().decode())
            assert "trace_id" not in st and "phases_s" not in st
            assert mtrace._state.traces == {}
            assert mtrace._state.exemplars == {}
            # status payload still reports the (empty) walk accounting
            assert req["attempt_ranks"] == [0]
            assert req["reroute_reasons"] == []
        finally:
            for rep in replicas:
                rep.stop()
            router.close()


import urllib.error  # noqa: E402  (used by the 404 pin above)
