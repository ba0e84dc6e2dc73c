"""paddle_tpu.resilience: deterministic fault injection +
detect→recover→resume across store, training, and serving.

Covers the ISSUE-7 acceptance surface:
- fault injection is flag-gated default-off with a branch-only disabled
  path (no RNG, no threads, no site state) and a seeded, deterministic
  schedule when on;
- the hardened TCPStore reconnects through an injected broken fd,
  retries with backoff, and names op/key/peer/attempts when it gives
  up; barrier names are reusable (the restart-generation bug);
- ElasticManager names WHO died (TTL aging on the watcher's clock vs
  immediate removal on exit());
- a serving engine under an injected fault schedule (step exceptions +
  deadline expiries + queue overflow) fails poisoned requests
  individually, sheds with terminal statuses + metrics, keeps
  goodput > 0, and drain() completes in-flight work while rejecting
  admissions;
- ResilientTrainLoop snapshots async, restores bit-identically, and
  the multi-process chaos run (rank killed in its third window) recovers via
  ElasticManager to a pinned loss trajectory with rc=0 and a clean
  watchdog;
- PT_WATCHDOG_ACTION=recover escalates a stall into the registered
  recovery hook; /debugz/resilience serves the injection state.
"""
from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor, serving
from paddle_tpu.distributed.store import TCPStore
from paddle_tpu.resilience import faultinject as fi
from paddle_tpu.resilience.train import ResilientTrainLoop, list_snapshots

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tests"))
from dist_utils import free_port, rank_env, report, run_ranks  # noqa: E402

WORKER = os.path.join(REPO, "tests", "resilience_train_worker.py")


@pytest.fixture(autouse=True)
def _fi_disabled():
    """Every test starts and ends with injection off and no rules."""
    fi.disable()
    fi._state.rules = []
    fi._state.site_hits = {}
    yield
    fi.disable()
    fi._state.rules = []
    fi._state.site_hits = {}


# ---------------------------------------------------------------------------
# fault injection framework
# ---------------------------------------------------------------------------

class TestFaultInject:
    def test_disabled_path_is_branch_only(self):
        """The tier-1 guard: with the flag off, fire() returns None
        without touching RNG, rule state, site counters, or threads."""
        assert not fi.is_enabled()
        before_threads = set(t.name for t in threading.enumerate())
        assert fi.fire("store.set", key="k") is None
        assert fi._state.site_hits == {}
        assert fi._state.rng is None or True  # rng untouched either way
        assert set(t.name for t in threading.enumerate()) \
            == before_threads
        # and the counter metric has no samples
        m = monitor.get_registry().get("faults_injected_total")
        assert m is None or m.collect() == []

    def test_schedule_grammar(self):
        rules = fi.parse_schedule(
            "a.b:error@3;c.d:delay=0.25@p0.5;e.f:drop@2..;"
            "g.h:broken_fd@%4;i.j:error@2..5;k.l:error")
        specs = [str(r) for r in rules]
        assert specs == ["a.b:error@3", "c.d:delay=0.25@p0.5",
                         "e.f:drop@2..", "g.h:broken_fd@%4",
                         "i.j:error@2..5", "k.l:error"]
        with pytest.raises(ValueError, match="bad fault rule"):
            fi.parse_schedule("nonsense")
        with pytest.raises(ValueError, match="unknown fault kind"):
            fi.parse_schedule("a.b:frobnicate@1")

    def test_nth_hit_fires_once(self):
        fi.enable("s.x:error@3", seed=0)
        assert fi.fire("s.x") is None
        assert fi.fire("s.x") is None
        with pytest.raises(fi.InjectedFault):
            fi.fire("s.x")
        assert fi.fire("s.x") is None
        assert fi._state.rules[0].fired == 1

    def test_range_and_modulo(self):
        fi.enable("s.r:drop@2..3;s.m:drop@%3", seed=0)
        got = [fi.fire("s.r", _supports=("drop",)) for _ in range(5)]
        assert got == [None, "drop", "drop", None, None]
        got = [fi.fire("s.m", _supports=("drop",)) for _ in range(7)]
        assert got == [None, None, "drop", None, None, "drop", None]

    def test_probability_is_seeded_deterministic(self):
        fi.enable("s.p:drop@p0.4", seed=42)
        run1 = [fi.fire("s.p", _supports=("drop",)) for _ in range(32)]
        fi.enable("s.p:drop@p0.4", seed=42)
        run2 = [fi.fire("s.p", _supports=("drop",)) for _ in range(32)]
        assert run1 == run2
        assert "drop" in run1 and None in run1

    def test_unsupported_action_counts_mismatched_not_fired(self):
        """A cooperative kind at a site that cannot apply it (e.g.
        'drop' at a collective) must NOT count as injected — metrics
        claiming chaos that never happened would be a chaos test that
        tests nothing."""
        fi.enable("s.u:drop@1..", seed=0)
        assert fi.fire("s.u") is None        # site declares no support
        rule = fi.state()["rules"][0]
        assert rule["fired"] == 0 and rule["mismatched"] == 1
        m = monitor.get_registry().get("faults_injected_total")
        assert m is None or m.labels(site="s.u", kind="drop").value == 0

    def test_delay_and_metric(self):
        fi.enable("s.d:delay=0.05@1", seed=0)
        t0 = time.monotonic()
        assert fi.fire("s.d") is None
        assert time.monotonic() - t0 >= 0.045
        m = monitor.get_registry().get("faults_injected_total")
        assert m.labels(site="s.d", kind="delay").value >= 1

    def test_state_payload(self):
        fi.enable("s.q:error@1", seed=7)
        with pytest.raises(fi.InjectedFault):
            fi.fire("s.q")
        st = fi.state()
        assert st["enabled"] and st["seed"] == 7
        assert st["rules"][0]["fired"] == 1
        assert st["site_hits"]["s.q"] == 1


# ---------------------------------------------------------------------------
# hardened store
# ---------------------------------------------------------------------------

class TestStoreHardening:
    def test_broken_fd_reconnects_and_counts(self):
        reconnects = monitor.get_registry().get("store_reconnects_total")
        before = reconnects.value
        with TCPStore(is_master=True, backoff_s=0.01) as store:
            fi.enable("store.set:broken_fd@1;store.get:broken_fd@1",
                      seed=0)
            store.set("hk", "v1")            # fd broken mid-op -> retry
            assert store.get("hk", timeout_s=2) == b"v1"
            store.set("hk2", "v2")           # healthy again
            assert store.get("hk2", timeout_s=2) == b"v2"
        assert reconnects.value >= before + 1

    def test_op_error_names_op_key_peer_attempts(self):
        master = TCPStore(is_master=True)
        port = master.port
        client = TCPStore("127.0.0.1", port, timeout_s=0.5,
                          op_retries=2, backoff_s=0.01)
        master.close()                       # server gone for good
        with pytest.raises(RuntimeError) as ei:
            client.set("lost-key", "v")
        msg = str(ei.value)
        assert "set" in msg and "lost-key" in msg
        assert "127.0.0.1:%d" % port in msg
        assert "2 attempts" in msg
        client.close()

    def test_injected_drop_set_is_silent_get_times_out(self):
        with TCPStore(is_master=True) as store:
            fi.enable("store.set:drop@1", seed=0)
            store.set("dropped", "x")        # silently never lands
            assert store.get("dropped", timeout_s=0.3) is None
            store.set("dropped", "y")        # next one lands
            assert store.get("dropped", timeout_s=2) == b"y"


class TestBarrierReuse:
    def test_same_name_reused_across_rounds(self):
        """The restart-generation regression (ISSUE-7 satellite): the
        old count+go keys lived forever, so a reused name over-counted
        and/or released instantly. Rounds must each require a full
        world_size of arrivals."""
        master = TCPStore(is_master=True)
        client = TCPStore("127.0.0.1", master.port)
        try:
            for _ in range(3):               # three rounds, one name
                errs = []

                def arrive(st):
                    try:
                        st.barrier("reused", 2, timeout_s=10)
                    except Exception as e:   # pragma: no cover
                        errs.append(e)

                t = threading.Thread(target=arrive, args=(client,),
                                     daemon=True)
                t.start()
                master.barrier("reused", 2, timeout_s=10)
                t.join(timeout=15)
                assert not t.is_alive() and not errs
        finally:
            client.close()
            master.close()

    def test_partial_round_times_out_not_instant_release(self):
        """After a completed round, a LONE arrival on the same name
        must wait for a full new round — with the old keys the stale
        'go' released it instantly."""
        master = TCPStore(is_master=True)
        client = TCPStore("127.0.0.1", master.port)
        try:
            t = threading.Thread(
                target=lambda: client.barrier("partial", 2,
                                              timeout_s=10),
                daemon=True)
            t.start()
            master.barrier("partial", 2, timeout_s=10)
            t.join(timeout=15)
            assert not t.is_alive()
            with pytest.raises(TimeoutError, match="partial"):
                master.barrier("partial", 2, timeout_s=0.5)
        finally:
            client.close()
            master.close()

    def test_single_rank_reuse(self):
        with TCPStore(is_master=True) as store:
            for _ in range(4):
                store.barrier("solo", 1, timeout_s=5)

    def test_shrunk_world_reuses_name(self):
        """A SHRUNK restart generation reusing the name (3 ranks
        arrive, then 2 survivors re-barrier) — the ptcheck finding:
        with ONE shared counter the survivors' arrivals landed as
        counts 4 and 5 of a ws-2 round series that can never fill, a
        permanent hang. Counters are namespaced per (name,
        world_size) now, so the shrunk generation starts fresh."""
        master = TCPStore(is_master=True)
        clients = [TCPStore("127.0.0.1", master.port)
                   for _ in range(2)]
        try:
            errs = []

            def arrive(st, ws):
                try:
                    st.barrier("shrink", ws, timeout_s=10)
                except Exception as e:      # pragma: no cover
                    errs.append(e)

            # generation 1: world of 3 (master + both clients)
            threads = [threading.Thread(target=arrive,
                                        args=(c, 3), daemon=True)
                       for c in clients]
            for t in threads:
                t.start()
            master.barrier("shrink", 3, timeout_s=10)
            for t in threads:
                t.join(timeout=15)
            assert not errs
            # generation 2: rank 2 "died" — the 2 survivors reuse
            # the SAME name with the shrunk world
            t = threading.Thread(target=arrive,
                                 args=(clients[0], 2), daemon=True)
            t.start()
            master.barrier("shrink", 2, timeout_s=10)
            t.join(timeout=15)
            assert not t.is_alive() and not errs
        finally:
            for c in clients:
                c.close()
            master.close()


# ---------------------------------------------------------------------------
# elastic: who died
# ---------------------------------------------------------------------------

class TestElasticDeadNodes:
    def _managers(self, store, ttl=1.0):
        from paddle_tpu.distributed.elastic import ElasticManager

        os.environ["PADDLE_ELASTIC_FAULT_TOLERANC_LEVEL"] = "1"
        try:
            mk = lambda r: ElasticManager(  # noqa: E731
                store=store, job_id="tdead", rank=r, np=2,
                heartbeat_interval=0.2, ttl=ttl)
            return mk(0), mk(1)
        finally:
            del os.environ["PADDLE_ELASTIC_FAULT_TOLERANC_LEVEL"]

    def test_heartbeat_stop_ages_out_on_watcher_clock(self):
        """A rank whose heartbeat merely STOPS (process wedged, network
        gone — counter still in the store) ages out after ttl measured
        on the watcher's own clock."""
        from paddle_tpu.distributed.elastic import ElasticStatus

        with TCPStore(is_master=True) as store:
            m0, m1 = self._managers(store)
            m0.register()
            m1.register()
            deadline = time.time() + 5
            while time.time() < deadline and m0.alive_nodes() != [0, 1]:
                time.sleep(0.1)
            assert m0.alive_nodes() == [0, 1]
            # wedge rank 1: stop its beats but do NOT delete its counter
            m1._stop.set()
            m1._thread.join(timeout=3)
            deadline = time.time() + 10
            while time.time() < deadline and m0.dead_nodes() != [1]:
                time.sleep(0.1)
            assert m0.dead_nodes() == [1]
            assert m0.watch() == ElasticStatus.RESTART
            assert m0.last_dead == [1]
            m0.exit()

    def test_exit_removes_immediately(self):
        with TCPStore(is_master=True) as store:
            m0, m1 = self._managers(store, ttl=30.0)  # aging impossible
            m0.register()
            m1.register()
            deadline = time.time() + 5
            while time.time() < deadline and m0.alive_nodes() != [0, 1]:
                time.sleep(0.1)
            m1.exit()                        # deletes the counter
            deadline = time.time() + 5
            while time.time() < deadline and m0.dead_nodes() != [1]:
                time.sleep(0.1)
            # immediate: the 30s ttl never elapsed, the delete did it
            assert m0.dead_nodes() == [1]
            m0.exit()

    def test_set_members_shrinks_watch_set(self):
        from paddle_tpu.distributed.elastic import ElasticStatus

        with TCPStore(is_master=True) as store:
            m0, m1 = self._managers(store)
            m0.register()
            deadline = time.time() + 5
            while time.time() < deadline and m0.alive_nodes() != [0]:
                time.sleep(0.1)
            assert m0.watch() in (ElasticStatus.RESTART,)
            m0.set_members([0])              # survivor-only generation
            assert m0.watch() == ElasticStatus.HOLD
            assert m0.dead_nodes() == []
            m0.exit()


# ---------------------------------------------------------------------------
# serving chaos
# ---------------------------------------------------------------------------

def _tiny_engine(**kw):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=4,
                      max_position_embeddings=64, use_parallel=False)
    model = LlamaForCausalLM(cfg)
    return serving.Engine(model, **kw)


class TestServingChaos:
    def test_fault_schedule_degrades_gracefully(self):
        """The ISSUE-7 serving acceptance: step exceptions + forced
        deadline expiries + queue overflow — poisoned requests fail
        individually, shed/expired get terminal statuses with metrics,
        goodput stays > 0, the engine survives."""
        eng = _tiny_engine(max_slots=2, num_blocks=32, block_size=4,
                           max_queue=4)
        # transient engine fault on step 1, poison on the 2nd prefill
        fi.enable("serving.step:error@1;serving.prefill:error@2",
                  seed=0)
        ok1 = eng.add_request([1, 2, 3], max_new_tokens=4)
        poison = eng.add_request([4, 5, 6], max_new_tokens=4)
        ok2 = eng.add_request([7, 8], max_new_tokens=3)
        expired = eng.add_request([9, 10], max_new_tokens=3,
                                  deadline_s=0.0)   # dead on arrival
        with pytest.raises(serving.QueueFullError):
            for _ in range(8):
                eng.add_request([1], max_new_tokens=1)
        eng.run()
        assert eng.request_status(ok1)["state"] == "finished"
        assert eng.request_status(ok2)["state"] == "finished"
        st = eng.request_status(poison)
        assert st["state"] == "failed" and st["reason"] == "poison"
        assert "InjectedFault" in st["error"]
        st = eng.request_status(expired)
        assert st["state"] == "expired" and st["reason"] == "deadline"
        stats = eng.stats()
        assert stats["requests_finished"] >= 2          # goodput > 0
        assert stats["shed_by_reason"]["poison"] == 1
        assert stats["shed_by_reason"]["expired"] == 1
        assert stats["shed_by_reason"]["queue_full"] >= 1
        # registry mirrors the same accounting
        shed = monitor.get_registry().get(
            "serving_requests_shed_total")
        assert shed.labels(reason="poison").value >= 1

    @staticmethod
    def _bisect_a_decode_poison(fail_twice):
        """Two requests through two slots, the first two decode steps
        made to fail by ``fail_twice(engine)``. -> (engine, the finished
        request's id)"""
        eng = _tiny_engine(max_slots=2, num_blocks=32, block_size=4)
        fail_twice(eng)
        a = eng.add_request([1, 2, 3], max_new_tokens=4)
        b = eng.add_request([4, 5, 6], max_new_tokens=4)
        eng.run()
        sa, sb = eng.request_status(a), eng.request_status(b)
        states = sorted([sa["state"], sb["state"]])
        assert states == ["failed", "finished"], (sa, sb)
        failed = sa if sa["state"] == "failed" else sb
        assert failed["reason"] == "poison"
        assert eng.stats()["requests_finished"] == 1
        return eng, (b if failed is sa else a)

    def test_decode_poison_quarantine_bisects(self):
        """A batched decode failure is not attributable — the batch is
        requeued and re-served serially; the request whose SOLO decode
        fails is the named poison, everyone else finishes."""
        # hit 1: batched decode (2 active) fails -> quarantine both;
        # hit 2: first SOLO decode fails -> that request is the poison
        self._bisect_a_decode_poison(
            lambda eng: fi.enable("serving.decode:error@1..2", seed=0))

    def test_a_readback_that_raises_bisects_the_same_way(self):
        """A decode step that fails on the device raises at its
        readback, after the engine dispatched the step behind it. That
        step is discarded with the pools, the batch takes the path of a
        failed dispatch (quarantine, then the serial decode names the
        poison), and the survivor's tokens are a clean engine's."""
        dispatched, read, failures = [], [], []

        def fail_twice(eng):
            run_eval, readback = eng._run_eval, eng._readback
            on_failure = eng._on_decode_failure

            def spy_run_eval(fn, *args):
                out = run_eval(fn, *args)
                if fn is eng._decode:
                    dispatched.append(out[0])
                return out

            def failing_readback(out):
                if any(out is d for d in dispatched):
                    read.append(out)
                    if len(read) <= 2:
                        raise RuntimeError("the step failed on the device")
                return readback(out)

            def spy_on_failure(rows, exc, lost=False):
                failures.append((len(rows), lost))
                return on_failure(rows, exc, lost)

            eng._run_eval, eng._readback = spy_run_eval, failing_readback
            eng._on_decode_failure = spy_on_failure

        eng, survivor = self._bisect_a_decode_poison(fail_twice)
        assert failures == [(2, True), (1, True)]
        # the step dispatched behind the first failure was never read
        assert sum(not any(d is r for r in read) for d in dispatched) == 1
        assert eng.cache.pools_alive() and not eng.has_work()
        clean = _tiny_engine(max_slots=2, num_blocks=32, block_size=4)
        rid = clean.add_request(eng.requests[survivor].prompt,
                                max_new_tokens=4)
        assert clean.run()[rid] == eng.output(survivor)

    def test_output_parity_with_flags_off(self):
        """Degradation knobs unset + injection off = the engine's
        outputs are exactly the pre-resilience ones (greedy parity
        suite already pins vs generate(); here: knobs-off equals
        knobs-on-but-unused)."""
        eng1 = _tiny_engine(max_slots=2, num_blocks=32, block_size=4)
        r1 = eng1.add_request([1, 2, 3, 4], max_new_tokens=6)
        eng1.run()
        eng2 = _tiny_engine(max_slots=2, num_blocks=32, block_size=4,
                            max_queue=64, default_deadline_s=3600.0,
                            max_preemptions=100)
        r2 = eng2.add_request([1, 2, 3, 4], max_new_tokens=6)
        eng2.run()
        assert eng1.output(r1) == eng2.output(r2)

    def test_preemption_cap_sheds_instead_of_livelock(self):
        """With every other request at the preemption cap there is no
        eligible victim: the grower is shed (reason preempt_cap), the
        engine terminates instead of thrashing."""
        eng = _tiny_engine(max_slots=2, num_blocks=6, block_size=4,
                           max_model_len=20, max_preemptions=0)
        # two long requests over a tiny pool force a preemption request;
        # cap 0 = nothing is ever preemptible
        a = eng.add_request([1, 2, 3, 4, 5], max_new_tokens=8)
        b = eng.add_request([6, 7, 8, 9, 10], max_new_tokens=8)
        eng.run()
        states = sorted([eng.request_status(a)["state"],
                         eng.request_status(b)["state"]])
        assert "finished" in states
        if "shed" in states:
            shed = (eng.request_status(a)
                    if eng.request_status(a)["state"] == "shed"
                    else eng.request_status(b))
            assert shed["reason"] == "preempt_cap"
            assert eng.stats()["shed_by_reason"]["preempt_cap"] == 1

    def test_drain_finishes_inflight_rejects_new(self):
        eng = _tiny_engine(max_slots=2, num_blocks=32, block_size=4)
        a = eng.add_request([1, 2, 3], max_new_tokens=4)
        b = eng.add_request([4, 5], max_new_tokens=3)
        eng.step()                           # a admitted + decoding
        out = eng.drain()
        assert eng.request_status(a)["state"] == "finished"
        assert eng.request_status(b)["state"] == "finished"
        assert len(out[a]) == 4 and len(out[b]) == 3
        with pytest.raises(serving.DrainingError):
            eng.add_request([1], max_new_tokens=1)
        assert eng.stats()["shed_by_reason"]["draining"] == 1
        assert not eng.has_work()


# ---------------------------------------------------------------------------
# resilient train loop (single process)
# ---------------------------------------------------------------------------

def _make_step(seed=7):
    from paddle_tpu import nn
    from paddle_tpu.optimizer.optimizers import Adam
    from paddle_tpu.parallel.engine import CompiledTrainStep

    paddle.seed(seed)
    model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Dropout(0.1),
                          nn.Linear(16, 4))
    opt = Adam(learning_rate=1e-2, parameters=model.parameters())
    return CompiledTrainStep(model, nn.CrossEntropyLoss(), opt)


def _batch_fn(step_i):
    # batch 8: divisible by the 8-virtual-device dp mesh conftest forces
    rng = np.random.RandomState(100 + step_i)
    return (rng.randn(8, 8).astype(np.float32),
            rng.randint(0, 4, (8,)).astype(np.int64))


class TestResilientTrainLoop:
    def test_snapshots_are_async_atomic_and_pruned(self, tmp_path):
        loop = ResilientTrainLoop(_make_step(), _batch_fn,
                                  str(tmp_path), snapshot_every=2,
                                  keep=2)
        loop.run(8)
        loop.close()
        steps = list_snapshots(str(tmp_path))
        # cadence 2 over 8 steps; a busy writer may SKIP a tick (by
        # design — the loop never blocks on disk), but the final flush
        # always lands the newest snapshot and retention holds
        assert steps and steps[-1] == 8 and len(steps) <= 2, steps
        assert all(s % 2 == 0 for s in steps)
        assert not glob.glob(str(tmp_path / ".tmp-snap_*"))
        snaps = monitor.get_registry().get("snapshots_total")
        assert snaps.value >= 2

    def test_injected_step_faults_recover_bit_identical(self, tmp_path):
        ref_loop = ResilientTrainLoop(_make_step(), _batch_fn,
                                      str(tmp_path / "ref"),
                                      snapshot_every=3)
        ref = ref_loop.run(9)
        ref_loop.close()
        fi.enable("train.step:error@4;train.step:error@8", seed=0)
        loop = ResilientTrainLoop(_make_step(), _batch_fn,
                                  str(tmp_path / "chaos"),
                                  snapshot_every=3)
        got = loop.run(9)
        loop.close()
        fi.disable()
        assert [k for k, _ in loop.recovery_log] \
            == ["step_error", "step_error"]
        assert sorted(got) == sorted(ref)
        for k in ref:
            assert got[k] == ref[k], (k, got[k], ref[k])
        recov = monitor.get_registry().get("recoveries_total")
        assert recov.labels(kind="step_error").value >= 2

    def test_injected_snapshot_fault_never_fails_training(self,
                                                          tmp_path):
        fi.enable("snapshot.save:error@1..", seed=0)
        loop = ResilientTrainLoop(_make_step(), _batch_fn,
                                  str(tmp_path), snapshot_every=2)
        losses = loop.run(4)
        loop.close()
        assert len(losses) == 4
        assert list_snapshots(str(tmp_path)) == []
        assert loop.recovery_log == []

    def test_max_recoveries_caps_the_retry_storm(self, tmp_path):
        fi.enable("train.step:error@2..", seed=0)   # every step from 2
        loop = ResilientTrainLoop(_make_step(), _batch_fn,
                                  str(tmp_path), snapshot_every=1,
                                  max_recoveries=3)
        with pytest.raises(RuntimeError, match="max_recoveries"):
            loop.run(6)
        loop.close()

    def test_watchdog_escalation_recover_mode(self, tmp_path,
                                              monkeypatch):
        """PT_WATCHDOG_ACTION=recover: a stalled bracket invokes the
        registered recovery hook (flag set, consumed at the next step
        boundary) instead of only writing a postmortem."""
        from paddle_tpu.monitor import watchdog as wd

        monkeypatch.setenv("PT_MONITOR_DUMP_DIR", str(tmp_path))
        loop = ResilientTrainLoop(_make_step(), _batch_fn,
                                  str(tmp_path / "snap"))
        loop.run(1)
        loop.snapshot()
        loop.flush_snapshots()
        loop.enable_watchdog_escalation()
        # the documented enable path: env var read at watchdog start
        monkeypatch.setenv("PT_WATCHDOG_ACTION", "recover")
        monitor.start_watchdog(stall_threshold_s=0.3,
                               poll_interval_s=0.05)
        assert wd.stall_action()["mode"] == "recover"
        try:
            hb = monitor.heartbeat("t_res_escalation")
            with hb.busy("wedged"):
                deadline = time.time() + 8
                while time.time() < deadline \
                        and loop._recover_requested is None:
                    time.sleep(0.05)
            assert loop._recover_requested == "watchdog"
            more = loop.run(3)               # consumes the request
            assert loop.recovery_log \
                and loop.recovery_log[0][0] == "watchdog"
            assert len(more) >= 2
        finally:
            monitor.stop_watchdog()
            loop.close()

    def test_bundle_mode_does_not_escalate(self, tmp_path,
                                           monkeypatch):
        from paddle_tpu.monitor import watchdog as wd

        monkeypatch.setenv("PT_MONITOR_DUMP_DIR", str(tmp_path))
        monkeypatch.delenv("PT_WATCHDOG_ACTION", raising=False)
        fired = []
        wd.register_stall_action(lambda s, r: fired.append(s))
        monitor.start_watchdog(stall_threshold_s=0.2,
                               poll_interval_s=0.05)
        # start re-reads the env; unset -> the default diagnose-only mode
        assert wd.stall_action()["mode"] == "bundle"
        try:
            hb = monitor.heartbeat("t_res_bundle_mode")
            with hb.busy("wedged"):
                deadline = time.time() + 4
                while time.time() < deadline and not list(
                        glob.glob(os.path.join(
                            str(tmp_path),
                            "watchdog_bundle_rank*.json"))):
                    time.sleep(0.05)
            assert fired == []               # bundle mode: no hooks
        finally:
            monitor.stop_watchdog()
            wd._stall_actions.clear()


# ---------------------------------------------------------------------------
# /debugz/resilience
# ---------------------------------------------------------------------------

class TestDebugzResilience:
    def test_route_serves_injection_state(self):
        srv = monitor.MetricsServer(port=0).start()
        try:
            fi.enable("x.y:error@99", seed=3)
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d/debugz/resilience" % srv.port,
                    timeout=10) as r:
                assert r.status == 200
                payload = json.loads(r.read().decode())
            assert payload["fault_injection"]["enabled"] is True
            assert payload["fault_injection"]["seed"] == 3
            assert payload["fault_injection"]["rules"][0]["rule"] \
                == "x.y:error@99"
            assert payload["watchdog_action"]["mode"] in ("bundle",
                                                          "recover")
        finally:
            srv.stop()

    def test_route_with_everything_off(self):
        srv = monitor.MetricsServer(port=0).start()
        try:
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d/debugz/resilience" % srv.port,
                    timeout=10) as r:
                assert r.status == 200
                payload = json.loads(r.read().decode())
            assert payload["fault_injection"]["enabled"] is False
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# multi-process chaos: rank killed mid-run_steps
# ---------------------------------------------------------------------------

class TestTrainChaosMultiProc:
    """ISSUE-7 acceptance: 3 ranks train run_steps windows with
    snapshots + elastic heartbeats + a per-window store all-reduce;
    rank 2 hard-kills itself mid-window. The survivors detect the death
    (collective timeout + elastic verdict), rebuild membership under a
    new generation, resume from the last common snapshot, finish all
    steps with a trajectory IDENTICAL to an uninterrupted run, and exit
    0 under an enabled watchdog (no stall, no hang)."""

    WORLD = 3
    DIE_RANK = 2

    @pytest.fixture(scope="class")
    def chaos_run(self, tmp_path_factory):
        snap_dir = str(tmp_path_factory.mktemp("res_snaps"))
        dump_dir = str(tmp_path_factory.mktemp("res_dumps"))
        port = free_port()
        envs = [rank_env(
            rank, self.WORLD, port,
            PADDLE_ELASTIC_FAULT_TOLERANC_LEVEL=1,
            PT_MONITOR_DUMP_DIR=dump_dir, PT_FR_GRACE_S=2,
            SNAP_DIR=snap_dir, DIE_RANK=self.DIE_RANK, DIE_AT_WINDOW=3,
            TOTAL_STEPS=12,
            # clean-watchdog criterion: enabled, generous threshold
            PT_WATCHDOG=1, PT_WATCHDOG_STALL_S=90)
            for rank in range(self.WORLD)]
        return dump_dir, run_ranks([sys.executable, WORKER], envs, 120)

    def test_dying_rank_never_leaves_its_window(self):
        # the death is certain: window 3's batch (steps 7-8) never
        # returns, while the windows before it build their batches
        r = subprocess.run(
            [sys.executable, "-c",
             "import resilience_train_worker as w\n"
             "b = w.make_batch_fn(die_window=3)\n"
             "b(5); print('WINDOW2', flush=True); b(7); print('RETURNED')"],
            cwd=os.path.dirname(WORKER), capture_output=True, text=True,
            timeout=60)
        assert (r.returncode, r.stdout) == (17, "WINDOW2\n"), r

    def test_survivors_recover_and_exit_clean(self, chaos_run):
        _, outs = chaos_run
        for rank, rc, out, err in outs:
            assert rc == (17 if rank == self.DIE_RANK else 0), \
                report(outs)
            if rank == self.DIE_RANK:
                continue
            assert "CHAOS_OK" in out, (rank, out)
            assert "rank_death" in out, (rank, out)

    def test_membership_rebuilt_without_dead_rank(self, chaos_run):
        _, outs = chaos_run
        survivors = [o for r, _, o, _ in outs if r != self.DIE_RANK]
        for out in survivors:
            line = [ln for ln in out.splitlines()
                    if ln.startswith("REBUILT")][0]
            assert "members=[0, 1]" in line
            assert "gen=1" in line

    def test_trajectory_pinned_vs_uninterrupted(self, chaos_run):
        _, outs = chaos_run
        joined = "".join(o for _, _, o, _ in outs)
        assert "TRAJECTORY_MATCH" in joined

    def test_watchdog_stayed_clean(self, chaos_run):
        dump_dir, _ = chaos_run
        assert not glob.glob(os.path.join(
            dump_dir, "watchdog_postmortem_rank*.json"))
