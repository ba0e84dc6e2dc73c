"""paddle_tpu.monitor.trace: span journal, exemplars, serving request
timelines, train-step spans, chrome round-trip.

Covers the ISSUE-6 acceptance surface:
- journal semantics: parent/child links, typed events, bounded traces
  and per-trace span rings, context-manager nesting;
- the hard disabled-path pinning (PR-2/5 style): FLAGS_monitor_trace
  off means zero journal allocations on the serving hot path, zero
  threads, zero native calls, and the registry exemplar hook slot
  stays None;
- the acceptance row: a forced p99-outlier request in a starved
  serving run resolves from its TTFT histogram exemplar to a complete
  span timeline — including a preempt/resume cycle — whose phase
  durations sum (+-5%) to its e2e latency;
- train-step spans whose child comm spans replay the flight-recorder
  brackets by sequence watermark (seq/gseq-linked);
- watchdog bundles embed the active (unfinished) spans;
- journal -> chrome-trace round-trip via tools/trace_merge.py
  --requests (span count + parentage preserved).
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.monitor import flight_recorder as frmod
from paddle_tpu.monitor import registry as mreg
from paddle_tpu.monitor import trace
from paddle_tpu.monitor import trace_merge as tmerge

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _trace_clean():
    """Every test starts AND ends with the journal at its default
    (off, empty) — neither earlier suites' leftovers nor ours leak."""
    paddle.set_flags({"FLAGS_monitor_trace": False})
    trace.disable()
    trace.clear()
    mreg.enable(trace_bridge=False)
    yield
    paddle.set_flags({"FLAGS_monitor_trace": False})
    trace.disable()
    trace.clear()
    mreg.enable(trace_bridge=False)


@pytest.fixture(scope="module")
def llama():
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=64, use_parallel=False)
    return LlamaForCausalLM(cfg), cfg


# ---------------------------------------------------------------------------
# journal core
# ---------------------------------------------------------------------------

class TestJournalCore:
    def test_span_lifecycle_and_parentage(self):
        trace.enable()
        tid = trace.new_trace("request", request_id=7)
        root = trace.start_span("request", tid, kind="request")
        child = trace.start_span("prefill", tid, parent_id=root,
                                 kind="phase", slot=1)
        trace.add_event(child, "token", n=1, kv_pages_used=3)
        trace.end_span(child)
        trace.end_span(root, status="finished")
        tr = trace.get_trace(tid)
        assert tr["attrs"]["request_id"] == 7
        assert tr["open_spans"] == 0
        spans = {s["name"]: s for s in tr["spans"]}
        assert spans["prefill"]["parent_id"] == spans["request"]["span_id"]
        assert spans["prefill"]["attrs"]["slot"] == 1
        ev = spans["prefill"]["events"][0]
        assert ev["name"] == "token" and ev["attrs"]["kv_pages_used"] == 3
        assert spans["request"]["attrs"]["status"] == "finished"
        assert spans["request"]["t_end"] >= spans["request"]["t_start"]

    def test_span_context_manager_nests_parents(self):
        trace.enable()
        tid = trace.new_trace("job")
        with trace.exemplar_context(tid):
            with trace.span("outer") as outer:
                with trace.span("inner"):
                    pass
        tr = trace.get_trace(tid)
        inner = next(s for s in tr["spans"] if s["name"] == "inner")
        assert inner["parent_id"] == outer.span_id

    def test_trace_capacity_bounded_finished_evicted_first(self):
        trace.enable(capacity=4)
        open_tid = trace.new_trace("keepme")
        trace.start_span("open", open_tid)
        for i in range(10):
            t = trace.new_trace("r%d" % i)
            s = trace.start_span("a", t)
            trace.end_span(s)
        assert len(trace._state.traces) == 4
        # the trace with an open span survived the eviction sweep
        assert trace.get_trace(open_tid) is not None
        trace.enable(capacity=trace.DEFAULT_CAPACITY)

    def test_per_trace_span_ring_bounded(self):
        trace.enable(span_cap=8)
        tid = trace.new_trace("train")
        for i in range(30):
            s = trace.start_span("step", tid, step=i)
            trace.end_span(s)
        tr = trace.get_trace(tid)
        assert len(tr["spans"]) == 8
        # it is the TAIL that is kept
        assert tr["spans"][-1]["attrs"]["step"] == 29
        trace.enable(span_cap=trace.DEFAULT_SPANS_PER_TRACE)

    def test_phase_breakdown_sums_phase_spans(self):
        trace.enable()
        tid = trace.new_trace("request")
        t0 = trace.now()
        for name, dur in (("queue", 0.5), ("prefill", 0.25),
                          ("decode", 1.0), ("preempted", 0.125),
                          ("prefill", 0.25)):
            s = trace.start_span(name, tid, kind="phase", t=t0)
            trace.end_span(s, t=t0 + dur)
            t0 += dur
        ph = trace.phase_breakdown(tid)
        assert ph["queue"] == pytest.approx(0.5)
        assert ph["prefill"] == pytest.approx(0.5)      # both spans
        assert ph["decode"] == pytest.approx(1.0)
        assert ph["preempted"] == pytest.approx(0.125)
        assert trace.phase_breakdown("nope") is None


# ---------------------------------------------------------------------------
# disabled-path pinning (the acceptance gate)
# ---------------------------------------------------------------------------

class TestDisabledPathPinning:
    def test_flag_default_off_and_hook_slot_none(self):
        assert paddle.get_flags("FLAGS_monitor_trace") == \
            {"FLAGS_monitor_trace": False}
        assert not trace.is_enabled()
        assert mreg._state.ex_hook is None

    def test_disabled_emitters_are_noops(self):
        assert trace.new_trace("x") is None
        assert trace.start_span("s", "whatever") is None
        trace.end_span(None)
        trace.add_event(None, "e")
        assert trace.span("s") is trace._NOOP
        assert trace.exemplar_context("tid") is trace._NOOP
        assert trace.record_train_step("j", 1, 0.01) is None
        assert trace._state.traces == {}

    def test_serving_hot_path_zero_journal_zero_threads_zero_native(
            self, monkeypatch, llama):
        """Journal off: a full serving run allocates nothing into the
        journal, assigns no trace ids, starts no threads, and never
        touches the native lib from the trace path."""
        from paddle_tpu import serving
        from paddle_tpu.core import native

        # the engine's spans are the profiler's own TraceAnnotation and
        # touch no native code; the native trace-counter bridge
        # (serving/metrics.counter, active while the MONITOR is on) is
        # pre-existing — neutralize it so the pytest.fail below only
        # fires for NEW native touches
        monkeypatch.setattr("paddle_tpu.serving.metrics.counter",
                            lambda name, value: None)
        monkeypatch.setattr(
            native, "get_lib",
            lambda: pytest.fail("disabled trace touched the native lib"))
        mreg._state.trace_bridge = False
        threads_before = set(threading.enumerate())
        m, cfg = llama
        eng = serving.Engine(m, max_slots=2, num_blocks=32, block_size=4)
        rng = np.random.RandomState(0)
        rid = eng.add_request(rng.randint(0, 64, (5,)).tolist(),
                              max_new_tokens=4)
        eng.run()
        assert eng.requests[rid].trace_id is None
        assert eng.requests[rid].metrics.trace_id is None
        assert eng.request_trace(rid) == (None, None)
        assert trace._state.traces == {}
        assert trace._state.exemplars == {}
        assert mreg._state.ex_hook is None
        assert set(threading.enumerate()) == threads_before

    def test_disable_restores_boot_fast_path(self):
        trace.enable()
        assert mreg._state.ex_hook is not None
        trace.disable()
        assert mreg._state.ex_hook is None

    def test_flag_bootstrap_enables_in_subprocess(self):
        import subprocess

        p = subprocess.run(
            [sys.executable, "-c",
             "from paddle_tpu.monitor import trace, registry\n"
             "assert trace.is_enabled()\n"
             "assert registry._state.ex_hook is not None\n"
             "print('BOOT_OK')"],
            capture_output=True, text=True, timeout=120,
            env=dict(os.environ, FLAGS_monitor_trace="1",
                     JAX_PLATFORMS="cpu"), cwd=REPO)
        assert p.returncode == 0, p.stderr[-2000:]
        assert "BOOT_OK" in p.stdout


# ---------------------------------------------------------------------------
# exemplars
# ---------------------------------------------------------------------------

class TestExemplars:
    def test_histogram_observation_records_bucket_exemplar(self):
        trace.enable()
        h = monitor.histogram("t_trace_ex_seconds", buckets=(0.1, 1.0))
        tid = trace.new_trace("request")
        with trace.exemplar_context(tid):
            h.observe(0.5)
            h.observe(5.0)      # past the last bucket -> +Inf
        ex = trace.exemplars("t_trace_ex_seconds")
        assert ex["1.0"]["trace_id"] == tid
        assert ex["1.0"]["value"] == 0.5
        assert ex["+Inf"]["trace_id"] == tid
        # no context -> no exemplar recorded
        h.observe(0.05)
        assert "0.1" not in trace.exemplars("t_trace_ex_seconds")

    def test_labeled_series_exemplars_keyed_by_series_name(self):
        trace.enable()
        h = monitor.histogram("t_trace_ex_lbl_seconds",
                              labelnames=("k",), buckets=(1.0,))
        tid = trace.new_trace("request")
        with trace.exemplar_context(tid):
            h.labels(k="a").observe(0.5)
        ex = trace.exemplars('t_trace_ex_lbl_seconds{k="a"}')
        assert ex["1.0"]["trace_id"] == tid


# ---------------------------------------------------------------------------
# serving acceptance: exemplar -> timeline -> phase sum, with preemption
# ---------------------------------------------------------------------------

class TestServingTimelineAcceptance:
    def test_outlier_resolves_to_timeline_with_preempt_cycle(self, llama):
        """The acceptance row: a forced p99-outlier request's TTFT
        exemplar resolves to a complete span timeline — including a
        preempt/resume cycle — whose phase durations sum (+-5%) to its
        e2e latency."""
        from paddle_tpu import serving

        trace.enable()
        m, cfg = llama
        rng = np.random.RandomState(1)
        # starved pool geometry (the test_serving preempt idiom): B's
        # page growth exhausts the pool first and preempts A — so A,
        # the request we make the latency outlier, is also the one
        # that pays a preempt/recompute cycle
        eng = serving.Engine(m, max_slots=2, num_blocks=7, block_size=4)
        prompt_a = rng.randint(0, 64, (6,)).tolist()
        prompt_b = rng.randint(0, 64, (8,)).tolist()

        orig = eng._prefill_request
        slowed = []

        def slow_prefill(slot, req):
            # force the outlier: A's FIRST prefill (not the resume)
            # sleeps long enough to land its TTFT in a bucket of its
            # own among this test's observations
            if req.id == rid_a and not slowed:
                slowed.append(True)
                time.sleep(0.35)
            return orig(slot, req)

        # compile the prefill bucket and the decode step first: the
        # engine dispatches A's first decode in the step after its
        # prefill, after B arrived, and B's TTFT must not carry that
        # compile
        eng.add_request(prompt_b, max_new_tokens=2)
        eng.run()
        eng._prefill_request = slow_prefill
        rid_a = eng.add_request(prompt_a, max_new_tokens=16)
        eng.step()      # A admitted + slow prefill dispatched
        # B arrives AFTER A's slow prefill so only A's TTFT carries the
        # forced outlier — the two must land in different buckets
        rid_b = eng.add_request(prompt_b, max_new_tokens=10)
        eng.run()

        assert eng.stats()["preemptions"] >= 1
        assert eng.requests[rid_a].metrics.preemptions >= 1

        # 1. the TTFT exemplar for the outlier's bucket names A's trace
        ma = eng.request_metrics(rid_a)
        assert ma["ttft_s"] >= 0.35
        tid_a = eng.requests[rid_a].trace_id
        ex = trace.exemplars("serving_ttft_seconds")
        from paddle_tpu.serving.metrics import _TTFT

        label = trace._bucket_label(_TTFT.buckets, ma["ttft_s"])
        assert ex[label]["trace_id"] == tid_a

        # 2. ...which resolves to a complete timeline with the
        # preempt/resume cycle: two prefill spans bracket a preempted
        # span, and the root request span closed "finished"
        tr = trace.get_trace(tid_a)
        names = [s["name"] for s in tr["spans"] if s["kind"] == "phase"]
        assert names.count("prefill") == 2
        assert "preempted" in names
        assert "queue" in names and "decode" in names
        root = next(s for s in tr["spans"] if s["kind"] == "request")
        assert root["attrs"]["status"] == "finished"
        assert root["attrs"]["preemptions"] >= 1
        assert tr["open_spans"] == 0

        # 3. phase durations sum to the e2e latency within 5%
        phases = trace.phase_breakdown(tid_a)
        assert set(phases) == {"queue", "prefill", "decode", "preempted"}
        assert sum(phases.values()) == \
            pytest.approx(ma["e2e_s"], rel=0.05)
        # B's timeline is complete too, without a preemption
        tid_b, phases_b = eng.request_trace(rid_b)
        assert sum(phases_b.values()) == \
            pytest.approx(eng.request_metrics(rid_b)["e2e_s"], rel=0.05)
        assert "preempted" not in phases_b

        # 4. token milestone events carry KV/slot occupancy
        decode = next(s for s in tr["spans"] if s["name"] == "decode")
        tokens = [e for e in decode["events"] if e["name"] == "token"]
        assert tokens
        assert tokens[0]["attrs"]["kv_pages_used"] > 0
        assert tokens[0]["attrs"]["slots_active"] >= 1
        # the scheduled event recorded admission-time pool state
        queue = next(s for s in tr["spans"] if s["name"] == "queue")
        sched = [e for e in queue["events"] if e["name"] == "scheduled"]
        assert sched and "kv_pages" in sched[0]["attrs"]

    def test_zero_length_request_traces_cleanly(self, llama):
        from paddle_tpu import serving

        trace.enable()
        m, _ = llama
        eng = serving.Engine(m, max_slots=2, num_blocks=16, block_size=4)
        rid = eng.add_request([1, 2, 3], max_new_tokens=0)
        tid, phases = eng.request_trace(rid)
        assert tid is not None
        tr = trace.get_trace(tid)
        assert tr["open_spans"] == 0
        root = next(s for s in tr["spans"] if s["kind"] == "request")
        assert root["attrs"]["status"] == "finished"
        assert root["attrs"]["output_tokens"] == 0


# ---------------------------------------------------------------------------
# train-step spans + flight-recorder-linked comm children
# ---------------------------------------------------------------------------

class TestTrainStepSpans:
    def test_comm_children_replay_flight_recorder_by_seq_watermark(self):
        trace.enable()
        fr = frmod.get_flight_recorder()
        fr.clear()
        # step 1 establishes the watermark (no comm attributed yet)
        trace.record_train_step("t_job", 1, 0.01)
        with fr.record("all_reduce", reduce_op="sum", shape=(4,),
                       dtype="float32", group="world",
                       strict_shape=True) as entry:
            time.sleep(0.002)
        entry["wire_bytes"] = 64
        trace.record_train_step("t_job", 2, 0.02)
        tid = trace._state.jobs["t_job"]["trace_id"]
        tr = trace.get_trace(tid)
        steps = [s for s in tr["spans"] if s["kind"] == "step"]
        assert [s["attrs"]["step"] for s in steps] == [1, 2]
        comm = [s for s in tr["spans"] if s["kind"] == "comm"]
        assert len(comm) == 1
        c = comm[0]
        # seq/gseq-linked: the SAME numbers a desync postmortem names
        assert c["attrs"]["seq"] == entry["seq"]
        assert c["attrs"]["gseq"] == entry["gseq"]
        assert c["attrs"]["group"] == "world"
        assert c["attrs"]["wire_bytes"] == 64
        assert c["parent_id"] == steps[1]["span_id"]
        assert c["t_start"] == entry["t_start"]
        assert c["t_end"] == entry["t_end"]
        # a third step with no new collectives adds no comm spans
        trace.record_train_step("t_job", 3, 0.01)
        tr = trace.get_trace(tid)
        assert len([s for s in tr["spans"] if s["kind"] == "comm"]) == 1

    def test_first_call_replays_own_window_by_wall_clock(self):
        """A one-shot workload (single run_steps call) has no previous
        seq watermark — its comm children come from the step's own
        wall window instead of being silently dropped."""
        trace.enable()
        fr = frmod.get_flight_recorder()
        fr.clear()
        t0 = time.time()
        with fr.record("all_reduce", shape=(4,), dtype="float32",
                       group="world", strict_shape=True):
            time.sleep(0.002)
        trace.record_train_step("t_oneshot", 1,
                                time.time() - t0 + 0.001)
        tid = trace._state.jobs["t_oneshot"]["trace_id"]
        tr = trace.get_trace(tid)
        comm = [s for s in tr["spans"] if s["kind"] == "comm"]
        assert len(comm) == 1 and comm[0]["attrs"]["op"] == "all_reduce"

    def test_compiled_train_step_emits_step_spans(self):
        import paddle_tpu.nn.functional as F
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.parallel.engine import CompiledTrainStep

        trace.enable()
        paddle.seed(0)
        cfg = LlamaConfig.tiny(use_parallel=False)
        model = LlamaForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters())

        def loss_fn(logits, labels):
            return F.cross_entropy(
                logits.reshape([-1, cfg.vocab_size]),
                labels.reshape([-1]))

        step = CompiledTrainStep(model, loss_fn, opt)
        rng = np.random.RandomState(0)
        ids = paddle.to_tensor(rng.randint(
            0, cfg.vocab_size, (8, 16)).astype(np.int32))
        labels = paddle.to_tensor(rng.randint(
            0, cfg.vocab_size, (8, 16)).astype(np.int32))
        step(ids, labels)
        step(ids, labels)
        tid = trace._state.jobs["train"]["trace_id"]
        tr = trace.get_trace(tid)
        steps = [s for s in tr["spans"] if s["kind"] == "step"]
        assert len(steps) == 2
        assert steps[-1]["attrs"]["tokens"] == 8 * 16
        assert steps[-1]["t_end"] is not None


# ---------------------------------------------------------------------------
# watchdog bundle embedding (satellite)
# ---------------------------------------------------------------------------

class TestBundleActiveSpans:
    def test_bundle_embeds_active_spans(self):
        trace.enable()
        tid = trace.new_trace("request", request_id=17)
        sid = trace.start_span("preempted", tid, kind="phase", slot=1)
        bundle = monitor.build_bundle("test")
        spans = bundle["active_spans"]
        assert any(s["span_id"] == sid and s["name"] == "preempted"
                   and s["trace_id"] == tid for s in spans)
        trace.end_span(sid)
        bundle = monitor.build_bundle("test")
        assert not any(s["span_id"] == sid
                       for s in bundle["active_spans"])

    def test_bundle_spans_empty_when_journal_off(self):
        bundle = monitor.build_bundle("test")
        assert bundle["active_spans"] == []


# ---------------------------------------------------------------------------
# chrome round-trip (CI/tooling satellite)
# ---------------------------------------------------------------------------

class TestChromeRoundTrip:
    def _journal(self, tmp_path):
        trace.enable()
        tid = trace.new_trace("request", request_id=1)
        root = trace.start_span("request", tid, kind="request")
        for phase in ("queue", "prefill", "decode"):
            s = trace.start_span(phase, tid, parent_id=root,
                                 kind="phase")
            trace.add_event(s, "token", n=1)
            trace.end_span(s)
        trace.end_span(root)
        path = str(tmp_path / "journal.json")
        journal = trace.write_journal(path)
        return path, journal, tid

    def test_journal_to_chrome_preserves_spans_and_parentage(
            self, tmp_path):
        path, journal, tid = self._journal(tmp_path)
        loaded = tmerge.load_journal(path)
        assert loaded["traces"].keys() == journal["traces"].keys()
        evs = tmerge.journal_events(loaded, clock="wall")
        xs = [e for e in evs if e["ph"] == "X"]
        assert len(xs) == 4                     # root + 3 phases
        root = next(e for e in xs if e["name"] == "request")
        for name in ("queue", "prefill", "decode"):
            child = next(e for e in xs if e["name"] == name)
            assert child["args"]["parent_id"] == \
                root["args"]["span_id"]
            assert child["tid"] == tid
        assert any(e["ph"] == "i" and e["name"] == "token" for e in evs)
        # monotonic alignment shifts by the journal's own clock anchor
        mono = tmerge.journal_events(loaded, clock="monotonic")
        anchor = loaded["clock_anchor"]
        shift_us = (anchor["monotonic"] - anchor["wall"]) * 1e6
        mroot = next(e for e in mono
                     if e["ph"] == "X" and e["name"] == "request")
        assert mroot["ts"] == pytest.approx(root["ts"] + shift_us)

    def test_trace_merge_cli_requests_mode(self, tmp_path):
        path, journal, tid = self._journal(tmp_path)
        out = str(tmp_path / "merged.json")
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import trace_merge as cli
        finally:
            sys.path.pop(0)
        rc = cli.main(["--out", out, "--requests", path,
                       "--requests-clock", "wall"])
        assert rc == 0
        merged = json.load(open(out))
        xs = [e for e in merged["traceEvents"] if e["ph"] == "X"]
        assert len(xs) == 4
        assert merged["metadata"]["extra_events"] == len(
            tmerge.journal_events(journal, clock="wall"))
        # parentage survives the full CLI round trip
        by_name = {e["name"]: e for e in xs}
        assert by_name["decode"]["args"]["parent_id"] == \
            by_name["request"]["args"]["span_id"]

    def test_load_journal_rejects_non_journal(self, tmp_path):
        p = tmp_path / "not_a_journal.json"
        p.write_text(json.dumps({"traceEvents": []}))
        with pytest.raises(ValueError):
            tmerge.load_journal(str(p))


# ---------------------------------------------------------------------------
# cross-process context (ISSUE 17): cid ids, traceparent, adoption
# ---------------------------------------------------------------------------

class TestCrossProcessContext:
    def test_trace_ids_minted_off_random_cid_not_pid(self):
        """The collision fix: ids come from a per-process RANDOM 64-bit
        cid, not the pid — pid-minted ids collide across hosts and
        recycle within one, silently fusing unrelated requests in
        fleet-merged journals."""
        trace.enable()
        cid = trace._state.cid
        assert len(cid) == 16
        int(cid, 16)                       # parses as hex
        assert cid != "%x" % os.getpid()
        assert cid != "%016x" % os.getpid()
        tid = trace.new_trace("request")
        assert tid.startswith(cid + ".")
        # two states (= two processes) mint from distinct id spaces
        assert trace._TraceState().cid != trace._TraceState().cid

    def test_journal_round_trips_cid(self, tmp_path):
        trace.enable()
        trace.new_trace("request")
        path = str(tmp_path / "journal.json")
        journal = trace.write_journal(path)
        assert journal["cid"] == trace._state.cid
        assert tmerge.load_journal(path)["cid"] == trace._state.cid

    def test_traceparent_format_parse_round_trip(self):
        tp = trace.format_traceparent("deadbeef.5", 11)
        assert tp == "pt1-deadbeef.5-b"
        assert trace.parse_traceparent(tp) == ("deadbeef.5", 11)
        # span-less context: the sender journals but had no span open
        assert trace.parse_traceparent(
            trace.format_traceparent("deadbeef.5")) == ("deadbeef.5",
                                                        None)
        # journal-off sender emits NO context field
        assert trace.format_traceparent(None) is None
        assert trace.format_traceparent(None, 11) is None
        # malformed/foreign input degrades to no-linkage, never raises
        for bad in (None, "", 7, "pt1", "pt2-x-1", "pt1--1",
                    "pt1-x-zz", "pt1-x-1-2"):
            assert trace.parse_traceparent(bad) == (None, None)

    def test_adopt_trace_registers_foreign_id_and_remote_parent(self):
        trace.enable()
        tid = trace.adopt_trace("feedface.3", "request", request_id=1)
        assert tid == "feedface.3"
        tr = trace.get_trace(tid)
        assert tr["attrs"]["adopted"] is True
        assert tr["attrs"]["request_id"] == 1
        # re-adoption merges attrs instead of duplicating the trace
        assert trace.adopt_trace(tid, "request", extra=2) == tid
        assert trace.get_trace(tid)["attrs"]["extra"] == 2
        assert len([t for t in trace._state.traces if t == tid]) == 1
        sid = trace.start_span("request", tid, kind="request",
                               remote_parent=42)
        trace.end_span(sid)
        span = trace.get_trace(tid)["spans"][-1]
        assert span["remote_parent"] == 42
        assert span["parent_id"] is None    # separate id spaces
        # the chrome export carries the linkage for the fleet merge
        evs = trace.chrome_events_from_journal(trace.dump())
        x = next(e for e in evs if e.get("ph") == "X"
                 and e["name"] == "request")
        assert x["args"]["remote_parent"] == 42

    def test_adopt_trace_disabled_or_none_noops(self):
        assert trace.adopt_trace("feedface.3", "request") is None
        trace.enable()
        assert trace.adopt_trace(None, "request") is None
        assert trace._state.traces == {}


# ---------------------------------------------------------------------------
# fleet-journal merge (ISSUE 17): router + replica journals, ONE trace
# ---------------------------------------------------------------------------

_FLEET_TID = "aaaaaaaaaaaaaaaa.0"


def _mk_span(sid, name, kind, t0, t1, parent=None, remote_parent=None,
             **attrs):
    s = {"span_id": sid, "trace_id": _FLEET_TID, "parent_id": parent,
         "name": name, "kind": kind, "t_start": t0, "t_end": t1,
         "attrs": dict(attrs), "events": []}
    if remote_parent is not None:
        s["remote_parent"] = remote_parent
    return s


def _mk_journal(cid, traces):
    return {"kind": "trace_journal", "version": 1, "pid": 1,
            "cid": cid, "written_at": "t",
            "clock_anchor": {"wall": 100.0, "monotonic": 50.0},
            "exemplars": {}, "traces": traces}


class TestFleetJournalMerge:
    """Synthetic router + replica journals reproducing the acceptance
    shape: attempt 1 dispatched to replica 0 (then killed), a reroute
    span naming the reason, attempt 2 finishing on replica 1 — all
    under ONE trace id, stitched on (trace_id, remote_parent)."""

    def _journals(self):
        router = _mk_journal("bbbbbbbbbbbbbbbb", {_FLEET_TID: {
            "trace_id": _FLEET_TID, "name": "fleet_request",
            "attrs": {"nonce": "n-1"}, "t_start": 10.0, "open_spans": 0,
            "spans": [
                _mk_span(0, "route", "request", 10.0, 14.0),
                _mk_span(1, "router_queue", "phase", 10.0, 10.5,
                         parent=0),
                _mk_span(2, "dispatch", "dispatch", 10.5, 10.6,
                         parent=0, nonce="n-1", replica=0,
                         outcome="accepted", attempt=1),
                _mk_span(3, "reroute", "reroute", 12.0, 12.0, parent=0,
                         reason="lease-evicted", from_rank=0),
                _mk_span(4, "dispatch", "dispatch", 12.1, 12.2,
                         parent=0, nonce="n-1", replica=1,
                         outcome="accepted", attempt=2),
                _mk_span(5, "settle", "settle", 14.0, 14.0, parent=0,
                         replica=1, status="finished"),
            ]}})
        victim = _mk_journal("cccccccccccccccc", {_FLEET_TID: {
            "trace_id": _FLEET_TID, "name": "request",
            "attrs": {"adopted": True}, "t_start": 10.5,
            "open_spans": 1,
            "spans": [_mk_span(0, "request", "request", 10.5, None,
                               remote_parent=2)]}})
        survivor = _mk_journal("dddddddddddddddd", {_FLEET_TID: {
            "trace_id": _FLEET_TID, "name": "request",
            "attrs": {"adopted": True}, "t_start": 12.1,
            "open_spans": 0,
            "spans": [_mk_span(0, "request", "request", 12.1, 14.0,
                               remote_parent=4)]}})
        return router, {0: victim, 1: survivor}

    def test_merge_prefixes_pids_and_stitches_flows(self):
        router, replicas = self._journals()
        evs = tmerge.merge_fleet_journals(router, replicas)
        pids = {e["pid"] for e in evs}
        assert "router/fleet_request" in pids
        assert "replica0/request" in pids and "replica1/request" in pids
        # one flow arrow per adopted replica span, dispatch -> request
        starts = [e for e in evs if e.get("ph") == "s"]
        finishes = [e for e in evs if e.get("ph") == "f"]
        assert len(starts) == 2 and len(finishes) == 2
        ids = {e["id"] for e in starts}
        assert ids == {"%s/2/r0" % _FLEET_TID, "%s/4/r1" % _FLEET_TID}
        assert {e["id"] for e in finishes} == ids
        # the arrow leaves the router track and lands on the replica's
        f1 = next(e for e in finishes
                  if e["id"] == "%s/4/r1" % _FLEET_TID)
        assert f1["pid"] == "replica1/request"
        s1 = next(e for e in starts
                  if e["id"] == "%s/4/r1" % _FLEET_TID)
        assert s1["pid"] == "router/fleet_request"
        assert s1["ts"] == pytest.approx(12.1 * 1e6)

    def test_merge_applies_clock_offsets_to_replica_events(self):
        router, replicas = self._journals()
        evs = tmerge.merge_fleet_journals(router, replicas,
                                          offsets={1: 0.5})
        # replica 1's clock runs 0.5s AHEAD of the router's: its spans
        # shift LEFT by 0.5s onto the router timebase
        x1 = next(e for e in evs if e.get("ph") == "X"
                  and e["pid"] == "replica1/request")
        assert x1["ts"] == pytest.approx((12.1 - 0.5) * 1e6)
        f1 = next(e for e in evs if e.get("ph") == "f"
                  and e["id"] == "%s/4/r1" % _FLEET_TID)
        assert f1["ts"] == pytest.approx((12.1 - 0.5) * 1e6)
        # router events never shift (it IS the timebase)
        xr = next(e for e in evs if e.get("ph") == "X"
                  and e["name"] == "route")
        assert xr["ts"] == pytest.approx(10.0 * 1e6)

    def test_fleet_trace_summary_orders_reroute_causality(self):
        router, _ = self._journals()
        summary = tmerge.fleet_trace_summary(router)
        row = summary[_FLEET_TID]
        assert row["nonce"] == "n-1"
        assert [d["replica"] for d in row["dispatches"]] == [0, 1]
        assert [d["outcome"] for d in row["dispatches"]] == \
            ["accepted", "accepted"]
        assert [r["reason"] for r in row["reroutes"]] == \
            ["lease-evicted"]
        assert row["reroutes"][0]["from_rank"] == 0
        # attempt 1 precedes the reroute precedes attempt 2
        assert row["dispatches"][0]["t_start"] \
            < row["reroutes"][0]["t_start"] \
            < row["dispatches"][1]["t_start"]

    def test_write_fleet_timeline_artifact(self, tmp_path):
        router, replicas = self._journals()
        path = str(tmp_path / "fleet_trace.json")
        doc = tmerge.write_fleet_timeline(path, router, replicas,
                                          offsets={1: 0.5},
                                          meta={"tool": "test"})
        on_disk = json.load(open(path))
        assert on_disk["kind"] == "fleet_trace"
        assert on_disk["requests"][_FLEET_TID]["reroutes"][0]["reason"] \
            == "lease-evicted"
        md = on_disk["metadata"]
        assert md["tool"] == "test"
        assert md["router_cid"] == "bbbbbbbbbbbbbbbb"
        assert md["replica_ranks"] == [0, 1]
        assert md["clock_offsets_s"] == {"1": 0.5}
        assert len(doc["traceEvents"]) == len(on_disk["traceEvents"])

    def test_trace_merge_cli_fleet_mode(self, tmp_path):
        router, replicas = self._journals()
        rp = str(tmp_path / "router.json")
        json.dump(router, open(rp, "w"))
        reps = []
        for r, j in replicas.items():
            p = str(tmp_path / ("replica%d.json" % r))
            json.dump(j, open(p, "w"))
            reps += ["--fleet-replica", "%d=%s" % (r, p)]
        out = str(tmp_path / "merged.json")
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import trace_merge as cli
        finally:
            sys.path.pop(0)
        rc = cli.main(["--out", out, "--fleet-router", rp,
                       "--fleet-offset", "1=0.5"] + reps)
        assert rc == 0
        merged = json.load(open(out))
        pids = {e.get("pid") for e in merged["traceEvents"]}
        assert "router/fleet_request" in pids
        assert "replica1/request" in pids
        assert any(e.get("ph") == "s" for e in merged["traceEvents"])
        # --fleet-replica without --fleet-router is an argparse error
        with pytest.raises(SystemExit):
            cli.main(["--out", out] + reps)
