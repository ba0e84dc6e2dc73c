"""Record/replay journal + divergence audit (ISSUE 20: ptreplay).

Off-discipline pins (the PR-2/5/6 contract, latch-at-construction):
with ``FLAGS_serving_replay`` at its default the engine's recorder
handle is None, the journal payload stays the pinned disabled literal
bit-for-bit through live traffic, zero ``replay_`` registry series
materialize, no threads appear, and the generated tokens are
bit-identical to a recording run's — the journal observes decode, it
never participates in it.

On-discipline: admission + terminal capture (prompt ids, latched flag
snapshot, weights generation, output token hash, shed/expired
reasons), bounded finished-evicted-first eviction, versioned JSONL
round-trip, and the replay half (tools/ptreplay.py, loaded by file
path): a mixed workload —
prefix hits + chunked prefill + quant-kv + forced preempt/resume —
re-executes with ZERO divergences and ``decode_compiles == 1``, a
deliberately perturbed weight leaf is detected, and the flag matrix
bisects that divergence to the ``weights`` axis instead of blaming a
flag. Fleet seams: an engine entry carries the router's adopted
fleet-wide trace id (surviving ``adopt_trace`` re-adoption), and a
rerouted dispatch (same nonce enqueued twice) journals ONE entry.
"""
from __future__ import annotations

import importlib.util
import json
import os
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.core import flags as _flags
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.monitor import incidents as ptinc
from paddle_tpu.monitor import registry as mreg
from paddle_tpu.monitor import trace as mtrace
from paddle_tpu.serving import replay as sreplay

# one model recipe shared by the recording fixture and the replayer's
# rebuild path — the journal's model meta IS this dict
MODEL_META = {
    "preset": "test_replay", "seed": 0,
    "config": dict(vocab_size=64, hidden_size=32, intermediate_size=64,
                   num_hidden_layers=2, num_attention_heads=4,
                   max_position_embeddings=96),
}

_PTREPLAY = None


def _ptreplay():
    """tools/ptreplay.py by file path."""
    global _PTREPLAY
    if _PTREPLAY is None:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "ptreplay.py")
        spec = importlib.util.spec_from_file_location("ptreplay", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _PTREPLAY = mod
    return _PTREPLAY


ALL = ("FLAGS_serving_replay", "FLAGS_serving_prefix_cache",
       "FLAGS_serving_chunked_prefill", "FLAGS_serving_quant_kv",
       "FLAGS_serving_quant_weights", "FLAGS_serving_fleet",
       "FLAGS_monitor_trace", "FLAGS_monitor_slo")


def _reset():
    _flags.set_flags({f: False for f in ALL})
    sreplay.disable()
    sreplay.clear()
    mtrace.disable()
    mtrace.clear()
    ptinc.disable()
    ptinc.clear()
    # drop replay_ (and any incident_ rows our divergence tests mint)
    # series: other suites pin these families series-free while off
    for m in mreg.get_registry().metrics():
        if m.name.startswith(("replay_", "incident_", "slo_")):
            for store in ("_values", "_series"):
                for key in list(getattr(m, store, ()) or ()):
                    m.remove(*key)


@pytest.fixture(autouse=True)
def _clean():
    _reset()
    yield
    _reset()


@pytest.fixture(scope="module")
def llama():
    paddle.seed(MODEL_META["seed"])
    cfg = LlamaConfig(use_parallel=False, **MODEL_META["config"])
    return LlamaForCausalLM(cfg), cfg


def _series(name):
    return mreg.get_registry().snapshot().get(name, {}).get("series",
                                                            [])


def _workload(rng, n=6):
    return [(rng.randint(0, 64, (5 + i % 4,)).tolist(), 4 + i % 3)
            for i in range(n)]


DISABLED_PAYLOAD = {"enabled": False, "requests": [], "dispatches": 0}


# ---------------------------------------------------------------------------
# flags-off discipline
# ---------------------------------------------------------------------------

class TestFlagsOffDiscipline:
    def test_recorder_none_payload_pinned_no_series_no_threads(
            self, llama):
        m, _ = llama
        before_threads = set(threading.enumerate())
        before = json.dumps(sreplay.payload(), sort_keys=True)
        assert json.loads(before) == DISABLED_PAYLOAD

        eng = serving.Engine(m, max_slots=2, num_blocks=32,
                             block_size=8)
        assert eng._replay is None      # the latch: one handle, None
        rng = np.random.RandomState(0)
        for prompt, mn in _workload(rng, 4):
            eng.add_request(prompt, max_new_tokens=mn)
        eng.run()
        # fleet-side hooks are no-ops while disabled too
        sreplay.note_dispatch(trace_id="t", nonce="n", rank=0,
                              endpoint="e", attempt=1,
                              outcome="accepted")
        sreplay.note_model({"seed": 1})

        after = json.dumps(sreplay.payload(), sort_keys=True)
        assert after == before          # bit-identical through traffic
        for name in ("replay_requests_recorded_total",
                     "replay_journal_evictions_total",
                     "replay_divergences_total"):
            assert _series(name) == [], name
        assert set(threading.enumerate()) == before_threads

    def test_recording_never_perturbs_tokens(self, llama):
        """The observer contract: tokens with the journal on are
        bit-identical to tokens with it off."""
        m, _ = llama
        rng = np.random.RandomState(1)
        work = _workload(rng, 4)

        off = serving.Engine(m, max_slots=2, num_blocks=32,
                             block_size=8)
        oid = [off.add_request(p, max_new_tokens=n) for p, n in work]
        off.run()

        _flags.set_flags({"FLAGS_serving_replay": True})
        on = serving.Engine(m, max_slots=2, num_blocks=32,
                            block_size=8)
        assert on._replay is not None
        nid = [on.add_request(p, max_new_tokens=n) for p, n in work]
        on.run()

        for a, b in zip(oid, nid):
            assert off.output(a) == on.output(b)


# ---------------------------------------------------------------------------
# recorder capture + bounded journal
# ---------------------------------------------------------------------------

class TestRecorder:
    def test_admission_and_terminal_capture(self, llama):
        m, _ = llama
        _flags.set_flags({"FLAGS_serving_replay": True,
                          "FLAGS_serving_quant_kv": True})
        eng = serving.Engine(m, max_slots=2, num_blocks=32,
                             block_size=8)
        rng = np.random.RandomState(2)
        work = _workload(rng, 3)
        ids = [eng.add_request(p, max_new_tokens=n) for p, n in work]
        eng.run()

        p = sreplay.payload()
        assert p["enabled"] is True
        assert p["recorded_total"] == 3 and len(p["requests"]) == 3
        rows = {r["id"]: r for r in p["requests"]}
        for rid, (prompt, mn) in zip(ids, work):
            row = rows[rid]
            assert row["state"] == "finished"
            assert row["output_tokens"] == len(eng.output(rid))
            assert row["output_token_hash"] == sreplay.token_hash(
                eng.output(rid))
            assert row["weights_generation"] == 0
            # the flag snapshot names the ENGINE's latches
            assert row["flags"] == {"prefix": False, "chunked": False,
                                    "quant_kv": True,
                                    "quant_weights": False}
        # the recorded counter minted exactly one unlabeled series
        s = _series("replay_requests_recorded_total")
        assert len(s) == 1 and s[0]["value"] == 3

    def test_expired_request_terminal_reason(self, llama):
        m, _ = llama
        _flags.set_flags({"FLAGS_serving_replay": True})
        eng = serving.Engine(m, max_slots=1, num_blocks=32,
                             block_size=8)
        # slot-starved: the second request waits, and its zero-second
        # queue TTL expires it before any admission work
        keep = eng.add_request([1, 2, 3, 4], max_new_tokens=4)
        drop = eng.add_request([5, 6, 7, 8], max_new_tokens=4,
                               deadline_s=0.0)
        eng.run()
        rows = {r["id"]: r for r in sreplay.payload()["requests"]}
        assert rows[keep]["state"] == "finished"
        assert rows[drop]["state"] == "expired"
        assert rows[drop]["reason"] == "deadline"
        assert rows[drop]["output_token_hash"] == sreplay.token_hash(())

    def test_bounded_eviction_finished_first(self, llama):
        m, _ = llama
        _flags.set_flags({"FLAGS_serving_replay": True})
        sreplay.enable(capacity=2)
        eng = serving.Engine(m, max_slots=2, num_blocks=32,
                             block_size=8)
        rng = np.random.RandomState(3)
        ids = [eng.add_request(p, max_new_tokens=n)
               for p, n in _workload(rng, 4)]
        eng.run()
        p = sreplay.payload()
        assert p["recorded_total"] == 4
        assert len(p["requests"]) == 2
        assert p["evictions"] == 2
        # survivors are the newest entries (oldest terminal evicted
        # first), and the eviction counter minted one series
        assert [r["id"] for r in p["requests"]] == ids[2:]
        s = _series("replay_journal_evictions_total")
        assert len(s) == 1 and s[0]["value"] == 2

    def test_journal_roundtrip(self, llama, tmp_path):
        m, _ = llama
        _flags.set_flags({"FLAGS_serving_replay": True})
        eng = serving.Engine(m, max_slots=2, num_blocks=32,
                             block_size=8)
        rng = np.random.RandomState(4)
        for p, n in _workload(rng, 3):
            eng.add_request(p, max_new_tokens=n)
        eng.run()
        sreplay.note_model(MODEL_META)
        path = str(tmp_path / "journal.jsonl")
        sreplay.write_journal(path)

        head, entries = sreplay.load_journal(path)
        assert head["kind"] == "replay_journal" and head["version"] == 1
        assert set(head["clock_anchor"]) == {"wall", "monotonic"}
        assert head["model"]["config"] == MODEL_META["config"]
        snap = head["engines"][str(entries[0]["engine"])]
        assert snap["caps"]["max_slots"] == 2
        assert snap["caps"]["block_size"] == 8
        assert len(entries) == 3
        for e in entries:
            assert e["state"] == "finished"
            assert e["output_token_hash"] == sreplay.token_hash(
                e["output"])
        # a journal from a future schema fails loudly
        bad = str(tmp_path / "bad.jsonl")
        with open(path) as f:
            lines = f.read().splitlines()
        h = json.loads(lines[0])
        h["version"] = 999
        with open(bad, "w") as f:
            f.write("\n".join([json.dumps(h)] + lines[1:]))
        with pytest.raises(ValueError):
            sreplay.load_journal(bad)


# ---------------------------------------------------------------------------
# fleet seams: adopted trace ids + reroute nonce dedup
# ---------------------------------------------------------------------------

class TestFleetSeams:
    def test_adopted_trace_id_survives_readoption(self, llama):
        """A router-minted fleet trace id, adopted (and RE-adopted —
        adopt_trace is idempotent) by the engine, is the id the
        journal entry carries: fleet dispatch rows and replica entries
        stitch on it."""
        m, _ = llama
        _flags.set_flags({"FLAGS_serving_replay": True,
                          "FLAGS_monitor_trace": True})
        mtrace.enable()
        tid = mtrace.new_trace("fleet_request", nonce="fleet-0-000001")
        # the re-adoption: the id is already live in the journal when
        # the engine adopts it for its request root span
        assert mtrace.adopt_trace(tid, "fleet_request") == tid

        eng = serving.Engine(m, max_slots=2, num_blocks=32,
                             block_size=8)
        rid = eng.add_request([1, 2, 3, 4], max_new_tokens=3,
                              trace_ctx=(tid, None))
        eng.run()
        rows = {r["id"]: r for r in sreplay.payload()["requests"]}
        assert rows[rid]["trace_id"] == tid
        sreplay.note_dispatch(trace_id=tid, nonce="fleet-0-000001",
                              rank=0, endpoint="http://x", attempt=1,
                              outcome="accepted")
        p = sreplay.payload()
        assert p["dispatches"] == 1
        assert p["dispatches_recent"][0]["trace_id"] \
            == rows[rid]["trace_id"]

    def test_rerouted_dispatch_journals_once(self, llama):
        """The regression the reroute path demands: a router retry
        (same nonce enqueued twice after a lost ack) admits ONE engine
        request, so the replica journals ONE entry."""
        m, _ = llama
        _flags.set_flags({"FLAGS_serving_replay": True,
                          "FLAGS_serving_fleet": True})
        from paddle_tpu.serving.fleet.replica import Replica

        eng = serving.Engine(m, max_slots=2, num_blocks=32,
                             block_size=8)
        rep = Replica(eng, rank=0)
        try:
            body = json.dumps({"nonce": "fleet-0-000001",
                               "prompt": [1, 2, 3, 4],
                               "max_new_tokens": 3}).encode()
            code, _, out = rep._enqueue(body)
            assert code == 200
            assert json.loads(out.decode())["deduped"] is False
            code, _, out = rep._enqueue(body)     # the reroute retry
            assert code == 200
            assert json.loads(out.decode())["deduped"] is True
            rep._admit_pending()
            eng.run()
        finally:
            rep._server._kv.http_server.server_close()
        p = sreplay.payload()
        assert p["recorded_total"] == 1
        assert len(p["requests"]) == 1
        assert p["requests"][0]["state"] == "finished"


# ---------------------------------------------------------------------------
# replay: zero divergence on a mixed workload, perturbation detected,
# matrix bisects to the weights axis
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded_journal(tmp_path_factory):
    """Record the acceptance workload ONCE per module: prefix hits +
    chunked prefill + quant-kv + forced preempt/resume (page-starved
    pool), model meta attached, journal on disk."""
    mod = _ptreplay()
    _flags.set_flags({
        "FLAGS_serving_replay": True,
        "FLAGS_serving_prefix_cache": True,
        "FLAGS_serving_chunked_prefill": True,
        "FLAGS_serving_quant_kv": True})
    sreplay.clear()
    sreplay.enable()
    try:
        model = mod._build_model(MODEL_META)
        eng = serving.Engine(model, max_slots=4, num_blocks=10,
                             block_size=8, prefill_chunk=8)
        rng = np.random.RandomState(0)
        shared = rng.randint(0, 64, (16,)).tolist()
        for i in range(12):
            prompt = (shared
                      + rng.randint(0, 64, (4 + i % 5,)).tolist()
                      if i % 2 else
                      rng.randint(0, 64, (6 + i % 7,)).tolist())
            eng.add_request(prompt, max_new_tokens=6 + i % 6)
        eng.run()
        stats = eng.stats()
        sreplay.note_model(MODEL_META)
        path = str(tmp_path_factory.mktemp("replay") / "mixed.jsonl")
        sreplay.write_journal(path)
    finally:
        _flags.set_flags({f: False for f in ALL})
        sreplay.disable()
        sreplay.clear()
    return path, stats


class TestReplayEndToEnd:
    def test_mixed_workload_replays_with_zero_divergence(
            self, recorded_journal):
        path, stats = recorded_journal
        # the workload really was mixed: cache hits AND preemptions
        assert stats["prefix_hit_tokens"] > 0
        assert stats["preemptions"] > 0
        assert stats["decode_compiles"] == 1
        mod = _ptreplay()
        head, entries = sreplay.load_journal(path)
        res = mod.replay_entries(head, entries)
        assert res["replayed"] == 12
        assert res["divergence_count"] == 0, res["divergences"]
        assert res["compile_once_ok"] is True

    def test_perturbed_weights_detected_with_token_index(
            self, recorded_journal):
        path, _ = recorded_journal
        mod = _ptreplay()
        head, entries = sreplay.load_journal(path)
        res = mod.replay_entries(head, entries, perturb=True,
                                 full=True)
        assert res["divergence_count"] > 0
        row = res["divergences"][0]
        assert isinstance(row["first_divergence"], int)
        assert row["recorded_tokens"][:row["first_divergence"]] \
            == row["replayed_tokens"][:row["first_divergence"]]
        assert row["recorded_hash"] != row["replayed_hash"]

    def test_matrix_bisects_perturbation_to_weights_axis(
            self, recorded_journal):
        """A diverging baseline (recorded flags, perturbed weights)
        names the weights axis — never a flag — and skips the flag
        flips entirely."""
        path, _ = recorded_journal
        mod = _ptreplay()
        head, entries = sreplay.load_journal(path)
        matrix = mod.matrix_bisect(head, entries, perturb=True)
        assert matrix["bisected_axes"] == ["weights"]
        assert matrix["baseline_divergences"] > 0
        assert matrix["axes"] == {}

    def test_against_diffs_two_journals(self, recorded_journal,
                                        tmp_path):
        path, _ = recorded_journal
        mod = _ptreplay()
        head, entries = sreplay.load_journal(path)
        res = mod.diff_journals(head, entries, head, entries)
        assert res["pairs"] == 12 and res["divergence_count"] == 0
        # perturb one recorded hash: --against flags exactly that pair
        import copy
        entries_b = copy.deepcopy(entries)
        entries_b[3]["output"] = list(entries_b[3]["output"]) + [9]
        entries_b[3]["output_token_hash"] = sreplay.token_hash(
            entries_b[3]["output"])
        res = mod.diff_journals(head, entries, head, entries_b)
        assert res["divergence_count"] == 1
        assert res["divergences"][0]["index"] == 3


# ---------------------------------------------------------------------------
# divergence -> metric + incident plumbing
# ---------------------------------------------------------------------------

class TestDivergencePlumbing:
    def test_note_divergence_counts_and_opens_incident(self):
        _flags.set_flags({"FLAGS_monitor_slo": True})
        ptinc.enable(rank=0)
        sreplay.note_divergence("weights", 2,
                                report="/tmp/replay_report.json")
        s = _series("replay_divergences_total")
        assert [(x["labels"], x["value"]) for x in s] \
            == [({"axis": "weights"}, 2)]
        inc = {i["key"]: i for i in ptinc.open_incidents()}
        row = inc["replay/divergence/weights"]
        assert row["kind"] == "replay_divergence"
        assert row["source"] == "replay"
        assert row["evidence"] == {"report": "/tmp/replay_report.json"}

    def test_note_divergence_counts_without_incident_plane(self):
        # incidents off: the counter still counts, nothing opens
        sreplay.note_divergence("quant_kv")
        s = _series("replay_divergences_total")
        assert [(x["labels"], x["value"]) for x in s] \
            == [({"axis": "quant_kv"}, 1)]
        assert ptinc.open_incidents() == []
