"""paddle_tpu.monitor.memory — the ISSUE-12 memory plane.

Covers the acceptance surface:
- ledger semantics: providers registered at engine construction
  (`FLAGS_monitor_memory` latched, the ptlint hot-path convention)
  report live bytes from array `nbytes`; `sample()` publishes
  `mem_device_bytes{component,job}` and isolates a dying provider to
  its own component;
- reconciliation: on the CPU backend the summed component bytes land
  within the documented tolerance of the `jax.live_arrays()` witness
  DELTA across engine construction;
- static-vs-transient split: `mem_hbm_headroom_bytes` = capacity −
  (static ledger + compiled transient peak), and the transient peak is
  the SAME donation-aware `executable_analysis` number `graph_report()`
  publishes (identity-pinned — no second hand-rolled estimate);
- OOM forensics: a forced `mem.oom` injection during a serving run
  writes `oom_postmortem_rank{r}.json` whose largest component is the
  KV pool, with KV occupancy in the context and the re-raise
  preserved; both train hot paths (`__call__`/`run_steps`) produce the
  same artifact; non-OOM failures write nothing;
- leak sentinel: a synthetic monotone-growth trace fires
  `perf_anomalies_total{kind="mem_leak"}` and flips /healthz degraded;
  a clean warmup and a sawtooth never fire;
- hard disabled-path pinning (PR-2/5/6 style): flags off = tracker
  None, zero native calls, zero new threads, zero `mem_*` registry
  series, `/debugz/memory` reports enabled:false;
- watchdog bundles embed the `mem_*` ring tails;
- tools/mem_snapshot.py: fresh artifact; a failed measurement is rc=3
  and writes nothing.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import signal
import subprocess
import sys
import threading
import weakref

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor, serving
from paddle_tpu.monitor import memory as ptmem
from paddle_tpu.monitor import perf
from paddle_tpu.monitor import registry as mreg
from paddle_tpu.monitor import timeseries as ts
from paddle_tpu.resilience import faultinject as fi

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _mem_clean():
    """Every test starts and ends with the memory plane at its default
    (off) and no ledger/sentinel/anomaly state — later suites must see
    a pristine monitor."""
    _reset()
    yield
    _reset()


def _reset():
    fi.disable()
    fi._state.rules = []
    # drop the fault-counter samples this suite's injections created:
    # the resilience suite's disabled-path guard pins the counter
    # sample-free, and counters are process-global
    m = mreg.get_registry().get("faults_injected_total")
    if m is not None:
        for key in list(m._children):
            m.remove(*key)
    paddle.set_flags({"FLAGS_monitor_memory": False,
                      "FLAGS_perf_attribution": False,
                      "FLAGS_perf_sentinels": False,
                      "FLAGS_monitor_timeseries": False})
    ptmem.reset()
    perf.disable_sentinels()
    perf.reset()
    ts.disable()
    ts.clear()
    mreg.enable(trace_bridge=False)


def _tiny_engine(**kw):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=4,
                      max_position_embeddings=64, use_parallel=False)
    model = LlamaForCausalLM(cfg)
    return serving.Engine(model, **kw)


def _tiny_step():
    import paddle_tpu.nn.functional as F
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.parallel.engine import CompiledTrainStep

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
    return step, ids, labels


# ---------------------------------------------------------------------------
# ledger
# ---------------------------------------------------------------------------

class TestLedger:
    def test_entry_forms_and_gauges(self):
        paddle.set_flags({"FLAGS_monitor_memory": True})
        arr = np.zeros((4, 8), dtype=np.float32)
        tr = ptmem.tracker("t_job", {
            "arrays": lambda: [("a", arr), ("b", 1024)],
            "dicts": lambda: {"entries": [
                {"tag": "c", "bytes": 100, "shape": [10],
                 "dtype": "int8"}], "detail": {"note": 1}},
        })
        assert tr is not None
        out = ptmem.sample()
        comps = out["components"]["t_job"]
        assert comps["arrays"]["bytes"] == arr.nbytes + 1024
        assert comps["dicts"]["bytes"] == 100
        assert comps["dicts"]["detail"] == {"note": 1}
        g = mreg.get_registry().get("mem_device_bytes")
        vals = dict(g.collect())
        assert vals[("arrays", "t_job")] == arr.nbytes + 1024
        assert vals[("dicts", "t_job")] == 100
        # top arrays carry tag/shape/dtype and sort by bytes
        top = out["top_arrays"][0]
        assert top["tag"] == "b" and top["bytes"] == 1024

    def test_provider_error_isolated(self):
        paddle.set_flags({"FLAGS_monitor_memory": True})

        def dying():
            raise ValueError("provider died")

        ptmem.tracker("t_job", {"ok": lambda: [("x", 7)],
                                "bad": dying})
        out = ptmem.sample()
        comps = out["components"]["t_job"]
        assert comps["ok"]["bytes"] == 7
        assert comps["bad"]["bytes"] == 0
        assert "ValueError" in comps["bad"]["error"]

    def test_reregistration_replaces_not_accumulates(self):
        paddle.set_flags({"FLAGS_monitor_memory": True})
        ptmem.register_component("c", lambda: [("x", 1)], job="t_job")
        ptmem.register_component("c", lambda: [("x", 2)], job="t_job")
        out = ptmem.sample()
        assert out["components"]["t_job"]["c"]["bytes"] == 2
        ptmem.unregister_component("c", job="t_job")
        assert "t_job" not in ptmem.sample()["components"]


# ---------------------------------------------------------------------------
# reconciliation + headroom (the acceptance math)
# ---------------------------------------------------------------------------

class TestReconciliationAndHeadroom:
    # CPU-backend slack on top of RECONCILE_TOLERANCE: paddle.seed /
    # engine construction create a few small untracked arrays (RNG
    # keys, block tables) next to the tracked pools
    SLACK = 256 << 10

    def test_serving_ledger_within_tolerance_of_witness_delta(self):
        paddle.set_flags({"FLAGS_monitor_memory": True})
        live_before = ptmem.allocator_stats()["live_bytes"]
        assert live_before is not None   # CPU backend: live_arrays
        eng = _tiny_engine(max_slots=2, num_blocks=256, block_size=4)
        out = ptmem.sample()
        rec = out["reconciliation"]
        assert rec["source"] == "live_arrays"
        delta = rec["live_bytes"] - live_before
        ledger = rec["ledger_bytes"]
        assert ledger > 0
        assert abs(delta - ledger) <= \
            ptmem.RECONCILE_TOLERANCE * ledger + self.SLACK, (
                delta, ledger)
        # the KV pool dominates this config, and its detail rows exist
        comps = out["components"]["serving"]
        assert comps["kv_pool"]["bytes"] > comps["model_params"]["bytes"]
        assert "pages_usable" in comps["kv_pool"]["detail"]
        assert eng._mem is not None

    def test_headroom_identity_and_matches_graph_report(
            self, monkeypatch):
        """mem_hbm_headroom_bytes = capacity − (static ledger +
        compiled transient peak), and the peak is the SAME
        donation-aware number graph_report() publishes for the llama
        fixture — identity-pinned so the repo cannot grow a second
        hand-rolled estimate."""
        monkeypatch.setenv("PT_MEM_CAPACITY_BYTES", str(2 << 30))
        paddle.set_flags({"FLAGS_monitor_memory": True,
                          "FLAGS_perf_attribution": True})
        step, ids, labels = _tiny_step()
        step(ids, labels)
        analysis = step.perf_analysis(ids, labels)
        peak = analysis["hbm_peak_bytes"]
        assert peak > 0
        out = ptmem.sample()
        row = out["jobs"]["train"]
        assert row["transient_peak_bytes"] == peak
        assert row["capacity_bytes"] == 2 << 30
        assert row["headroom_bytes"] == \
            (2 << 30) - row["ledger_bytes"] - peak
        g = mreg.get_registry().get("mem_hbm_headroom_bytes")
        assert dict(g.collect())[("train",)] == row["headroom_bytes"]
        # graph_report()'s cost row carries the identical peak
        rep = step.graph_report(ids, labels)
        costs = [(srep.get("cost") or {}).get("hbm_peak_bytes")
                 for srep in rep["steps"].values()]
        assert peak in costs, (peak, costs)
        # and memory.compiled_peak is definitionally that number
        assert ptmem.transient_peak("train")["bytes"] == peak

    def test_headroom_subtracts_full_ledger_across_jobs(
            self, monkeypatch):
        """Two jobs share ONE device: each job's headroom subtracts
        the FULL static ledger, not just its own slice — otherwise
        both would claim the other's bytes as free."""
        monkeypatch.setenv("PT_MEM_CAPACITY_BYTES", str(1 << 30))
        paddle.set_flags({"FLAGS_monitor_memory": True})
        ptmem.tracker("t_a", {"c": lambda: [("x", 100 << 20)]})
        ptmem.tracker("t_b", {"c": lambda: [("x", 50 << 20)]})
        jobs = ptmem.sample()["jobs"]
        want = (1 << 30) - (150 << 20)
        assert jobs["t_a"]["headroom_bytes"] == want
        assert jobs["t_b"]["headroom_bytes"] == want

    def test_dropped_engine_not_pinned_by_ledger(self):
        """The global ledger holds engines WEAKLY: discarding an
        engine must actually free its pools/params (a memory
        observability plane that leaks device memory would be
        self-parody); its components then report empty."""
        paddle.set_flags({"FLAGS_monitor_memory": True})
        eng = _tiny_engine(max_slots=2, num_blocks=32, block_size=4)
        assert ptmem.sample()["components"]["serving"]["kv_pool"][
            "bytes"] > 0
        wr = weakref.ref(eng)
        del eng
        gc.collect()
        assert wr() is None
        comps = ptmem.sample()["components"]["serving"]
        assert comps["kv_pool"]["bytes"] == 0
        assert comps["model_params"]["bytes"] == 0

    def test_no_capacity_no_fabricated_headroom(self, monkeypatch):
        monkeypatch.delenv("PT_MEM_CAPACITY_BYTES", raising=False)
        paddle.set_flags({"FLAGS_monitor_memory": True})
        ptmem.tracker("t_job", {"c": lambda: [("x", 10)]})
        row = ptmem.sample()["jobs"]["t_job"]
        # CPU allocator reports no bytes_limit: headroom absent
        assert row["capacity_bytes"] is None
        assert row["headroom_bytes"] is None
        g = mreg.get_registry().get("mem_hbm_headroom_bytes")
        assert ("t_job",) not in dict(g.collect())


# ---------------------------------------------------------------------------
# disabled-path pinning (PR-2/5/6 style)
# ---------------------------------------------------------------------------

class TestDisabledPathPinning:
    def test_flag_default_off(self):
        assert not paddle.get_flags(
            ["FLAGS_monitor_memory"])["FLAGS_monitor_memory"]
        assert not ptmem.is_enabled()

    def test_off_zero_native_zero_threads_zero_series(
            self, monkeypatch, tmp_path):
        """Flags off: engines latch tracker=None, the hot paths run,
        and the plane leaves NO trace — no native calls from ITS entry
        points, no new threads, no mem_* registry series, no sentinel,
        no postmortem machinery armed."""
        from paddle_tpu.core import native

        # the memory plane's own off-path entry points are native-free
        # (the engines' pre-existing profiler spans may use native —
        # that is not this plane's footprint)
        with monkeypatch.context() as m:
            m.setattr(native, "get_lib", lambda: pytest.fail(
                "disabled memory touched native lib"))
            assert ptmem.tracker("t_off", {"c": lambda: [("x", 1)]}) \
                is None
            assert ptmem.memory_payload()["enabled"] is False
            assert not ptmem.is_enabled()
        monkeypatch.setenv("PT_MONITOR_DUMP_DIR", str(tmp_path))
        threads_before = set(threading.enumerate())
        eng = _tiny_engine(max_slots=2, num_blocks=32, block_size=4)
        assert eng._mem is None
        r = eng.add_request([1, 2, 3], max_new_tokens=2)
        eng.run()
        assert eng.request_status(r)["state"] == "finished"
        step, ids, labels = _tiny_step()
        assert step._mem is None
        step(ids, labels)
        for name in ("mem_device_bytes", "mem_hbm_headroom_bytes",
                     "mem_unattributed_bytes",
                     "mem_oom_postmortems_total"):
            m = mreg.get_registry().get(name)
            assert m is None or list(m.collect()) == [], name
        assert ptmem._state.components == {}
        assert ptmem._state.sentinel is None
        assert set(threading.enumerate()) == threads_before
        assert not os.listdir(str(tmp_path))
        payload = ptmem.memory_payload()
        assert payload["enabled"] is False
        assert payload["components"] == {} and payload["jobs"] == {}


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------

class TestOOMForensics:
    def test_looks_like_oom_classification(self):
        assert ptmem.looks_like_oom(
            RuntimeError("RESOURCE_EXHAUSTED: Out of memory while "
                         "trying to allocate 17179869184 bytes"))
        assert ptmem.looks_like_oom(ValueError("Allocation failure"))
        assert not ptmem.looks_like_oom(RuntimeError("shape mismatch"))
        fi.enable("mem.oom:error@1", seed=0)
        with pytest.raises(fi.InjectedFault) as ei:
            fi.fire("mem.oom")
        assert ptmem.looks_like_oom(ei.value)
        # a NON-mem injected fault is not OOM-shaped
        fi.disable()
        fi.enable("serving.step:error@1", seed=0)
        with pytest.raises(fi.InjectedFault) as ei:
            fi.fire("serving.step")
        assert not ptmem.looks_like_oom(ei.value)

    def test_serving_mem_oom_postmortem_names_kv_pool(
            self, monkeypatch, tmp_path):
        """THE acceptance path: a forced mem.oom during a serving run
        produces oom_postmortem_rank{r}.json whose largest-component
        attribution names the KV pool, with KV occupancy present and
        the re-raise preserved."""
        monkeypatch.setenv("PT_MONITOR_DUMP_DIR", str(tmp_path))
        paddle.set_flags({"FLAGS_monitor_memory": True})
        eng = _tiny_engine(max_slots=2, num_blocks=256, block_size=4)
        eng.add_request([1, 2, 3, 4, 5], max_new_tokens=8)
        # hit 1 passes (the request admits, prefills, decodes once —
        # pages live, occupancy > 0); hit 2 is the OOM
        fi.enable("mem.oom:error@2", seed=0)
        assert eng.step()
        with pytest.raises(fi.InjectedFault):   # re-raise preserved
            eng.step()
        path = os.path.join(str(tmp_path), "oom_postmortem_rank0.json")
        assert os.path.exists(path)
        with open(path) as f:
            post = json.load(f)
        assert post["kind"] == "oom_postmortem"
        assert post["injected"] is True
        comps = post["ledger"]["components"]["serving"]
        largest = max(comps, key=lambda n: comps[n]["bytes"])
        assert largest == "kv_pool", comps
        # top consumer named: a kv pool plane with shape/dtype
        top = post["ledger"]["top_arrays"][0]
        assert top["component"] == "kv_pool" and top["shape"]
        # KV occupancy present and live (the request held pages)
        assert post["context"]["kv_page_occupancy"] > 0
        assert post["context"]["kv_pages_used"] > 0
        # the admission decision ring made it into the artifact
        assert any(d["kind"] == "admit" for d in post["decisions"])
        c = mreg.get_registry().get("mem_oom_postmortems_total")
        assert dict(c.collect())[("serving",)] >= 1
        assert ptmem.memory_payload()["postmortems"]

    def test_train_step_and_run_steps_postmortem(
            self, monkeypatch, tmp_path):
        monkeypatch.setenv("PT_MONITOR_DUMP_DIR", str(tmp_path))
        paddle.set_flags({"FLAGS_monitor_memory": True})
        step, ids, labels = _tiny_step()
        step(ids, labels)
        fi.enable("mem.oom:error@1", seed=0)
        with pytest.raises(fi.InjectedFault):
            step(ids, labels)
        path = os.path.join(str(tmp_path), "oom_postmortem_rank0.json")
        assert os.path.exists(path)
        with open(path) as f:
            post = json.load(f)
        assert post["job"] == "train"
        comps = post["ledger"]["components"]["train"]
        assert set(comps) == {"model_params", "optimizer_slots",
                              "ef_residuals"}
        # adam: 2 fp32 slots per param — slots outweigh params
        assert comps["optimizer_slots"]["bytes"] > \
            comps["model_params"]["bytes"]
        assert post["context"]["step_count"] >= 1
        os.unlink(path)
        fi.disable()
        fi.enable("mem.oom:error@1", seed=0)
        stacked = (np.stack([ids.numpy()] * 2),
                   np.stack([labels.numpy()] * 2))
        with pytest.raises(fi.InjectedFault):
            step.run_steps(stacked)
        assert os.path.exists(path)

    def test_non_oom_failure_writes_no_postmortem(
            self, monkeypatch, tmp_path):
        monkeypatch.setenv("PT_MONITOR_DUMP_DIR", str(tmp_path))
        paddle.set_flags({"FLAGS_monitor_memory": True})
        eng = _tiny_engine(max_slots=2, num_blocks=32, block_size=4)
        r = eng.add_request([1, 2, 3], max_new_tokens=3)
        fi.enable("serving.prefill:error@1", seed=0)
        eng.run()   # poison path handles it; not OOM-shaped
        assert eng.request_status(r)["state"] == "failed"
        assert not os.listdir(str(tmp_path))


# ---------------------------------------------------------------------------
# leak sentinel
# ---------------------------------------------------------------------------

class TestLeakSentinel:
    def _grower(self):
        state = {"bytes": 0}

        def provider():
            return [("blob", state["bytes"])]

        return state, provider

    def test_monotone_growth_fires_and_degrades(self):
        paddle.set_flags({"FLAGS_monitor_memory": True})
        state, provider = self._grower()
        tr = ptmem.tracker("t_job", {"leaky": provider})
        assert tr is not None and ptmem._state.sentinel is not None
        for i in range(20):
            state["bytes"] = (i + 1) << 20   # +1 MiB per sample
            ptmem.sample()
        summ = perf.anomaly_summary()
        assert summ["counts"].get("mem_leak", 0) >= 1
        assert summ["degraded"] is True
        c = mreg.get_registry().get("perf_anomalies_total")
        assert dict(c.collect())[("mem_leak",)] >= 1
        ev = [e for e in summ["recent"] if e["kind"] == "mem_leak"]
        assert ev and ev[0]["detail"]["growth_bytes"] >= (1 << 20)

    def test_warmup_never_fires(self):
        """A clean warmup can never fire — even a monotone-growth
        warmup window (engine filling its pools at startup is growth,
        not a leak)."""
        paddle.set_flags({"FLAGS_monitor_memory": True})
        state, provider = self._grower()
        s = ptmem.MemLeakSentinel()
        ptmem.tracker("t_job", {"leaky": provider})
        for i in range(s.warmup):
            state["bytes"] = (i + 1) << 20
            ptmem.sample()
        assert perf.anomaly_summary()["counts"] == {}

    def test_sawtooth_never_fires(self):
        """Grow-release-grow (preemption reclaim, request churn) is
        load, not a leak: any single decreasing sample resets."""
        paddle.set_flags({"FLAGS_monitor_memory": True})
        state, provider = self._grower()
        ptmem.tracker("t_job", {"leaky": provider})
        for i in range(40):
            # rises 5 samples, drops on the 6th — window is 6
            state["bytes"] = ((i % 6) + 1) << 20
            ptmem.sample()
        assert perf.anomaly_summary()["counts"] == {}


# ---------------------------------------------------------------------------
# decision ring
# ---------------------------------------------------------------------------

class TestDecisionRing:
    def test_bounded_and_ordered(self):
        paddle.set_flags({"FLAGS_monitor_memory": True})
        for i in range(ptmem._DECISIONS_CAP + 20):
            ptmem.note_decision("serving", "admit", request=i)
        decs = ptmem._state.decisions
        assert len(decs) == ptmem._DECISIONS_CAP
        assert decs[-1]["request"] == ptmem._DECISIONS_CAP + 19
        stamps = [d["t_mono"] for d in decs]
        assert stamps == sorted(stamps)
        assert len(ptmem.recent_decisions(5)) == 5


# ---------------------------------------------------------------------------
# surfacing: watchdog bundle tails + payload
# ---------------------------------------------------------------------------

class TestSurfacing:
    def test_watchdog_bundle_embeds_mem_ring_tails(self):
        paddle.set_flags({"FLAGS_monitor_memory": True})
        ptmem.tracker("t_job", {"c": lambda: [("x", 123)]})
        ptmem.sample()
        bundle = monitor.build_bundle(reason="test")
        tail = bundle["timeseries_tail"]
        mem_series = [k for k in tail if k.startswith("mem_")]
        assert mem_series, list(tail)

    def test_payload_carries_sentinel_config_and_decisions(self):
        paddle.set_flags({"FLAGS_monitor_memory": True})
        ptmem.tracker("t_job", {"c": lambda: [("x", 5)]})
        ptmem.note_decision("t_job", "admit", request=1)
        p = ptmem.memory_payload()
        assert p["enabled"] is True
        assert p["leak_sentinel"]["series"] == "mem_device_bytes"
        assert p["decisions"][-1]["kind"] == "admit"
        assert "reconciliation" in p


# ---------------------------------------------------------------------------
# tools/mem_snapshot.py (battery row artifact)
# ---------------------------------------------------------------------------

def _load_mem_snapshot_mod():
    spec = importlib.util.spec_from_file_location(
        "t_mem_snapshot", os.path.join(REPO, "tools",
                                       "mem_snapshot.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestMemSnapshotTool:
    def test_failed_measurement_leaves_previous_artifact_alone(
            self, tmp_path, monkeypatch):
        """A failed round is rc 3 and touches nothing: the previous
        artifact is neither rewritten nor marked."""
        mod = _load_mem_snapshot_mod()
        out = str(tmp_path / "mem_snapshot.json")
        fresh = {"kind": "mem_snapshot", "version": 1, "ok": True,
                 "written_at": "2026-08-03T00:00:00Z",
                 "memory": {"enabled": True}}
        mod.write_artifact(out, fresh)
        with open(out, "rb") as f:
            before = f.read()

        def boom(steps):
            raise RuntimeError("child died")

        monkeypatch.setattr(mod, "measure", boom)
        try:
            assert mod.main(["--out", out]) == 3
        finally:
            signal.alarm(0)         # main() arms a watchdog alarm
        with open(out, "rb") as f:
            assert f.read() == before
        assert json.loads(before) == fresh

    def test_failed_measurement_writes_no_file(self, tmp_path,
                                               monkeypatch):
        mod = _load_mem_snapshot_mod()
        out = str(tmp_path / "mem_snapshot.json")
        monkeypatch.setattr(
            mod, "measure",
            lambda steps: (_ for _ in ()).throw(RuntimeError("boom")))
        try:
            assert mod.main(["--out", out]) == 3
        finally:
            signal.alarm(0)
        assert not os.path.exists(out)

    def test_cli_measures_and_commits(self, tmp_path):
        """End-to-end CPU smoke: the battery row's exact invocation
        writes a fresh ok artifact with a nonempty ledger and the
        compiled transient peak."""
        out = str(tmp_path / "mem_snapshot.json")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=REPO + os.pathsep +
                   os.environ.get("PYTHONPATH", ""))
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "mem_snapshot.py"),
             "--steps", "2", "--out", out],
            capture_output=True, text=True, env=env, timeout=540)
        assert r.returncode == 0, r.stdout + r.stderr
        with open(out) as f:
            snap = json.load(f)
        assert snap["ok"] is True
        assert snap["compiled_peak_bytes"] > 0
        mem = snap["memory"]
        assert mem["enabled"] is True
        comps = mem["components"]["train"]
        assert comps["model_params"]["bytes"] > 0
        assert comps["optimizer_slots"]["bytes"] > 0
        rec = mem["reconciliation"]
        assert rec["source"] == "live_arrays"
        assert rec["ledger_bytes"] > 0
