"""Perf report: MFU / phase split / HBM peak for a compiled train step.

Renders the monitor/perf.py attribution surface as a run report, from
one of three sources:

  # smoke: build a llama decoder, run a few compiled steps with perf
  # attribution + the time-series ring on, report (the default;
  # CPU-safe — a tiny config off-chip, a 134M one on the real backend)
  python tools/perf_report.py [--steps N] [--json] [--out FILE]

  # live: GET /debugz/perf from a running rank's fleet KV HTTP server
  python tools/perf_report.py --endpoint host:port

  # artifact: render a previously-written payload JSON
  python tools/perf_report.py --in perf_report.json
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _watchdog(seconds=900):
    def fire(signum, frame):
        sys.stderr.write("perf_report watchdog: %ds, aborting\n" % seconds)
        os._exit(3)

    signal.signal(signal.SIGALRM, fire)
    signal.alarm(seconds)


def smoke(steps=5):
    """Run the bench-family decoder under full perf instrumentation and
    return the /debugz/perf payload (+ a bench-style summary row)."""
    import numpy as np
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed import mesh as pmesh
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.monitor import perf, timeseries
    from paddle_tpu.monitor import profile as pprofile
    from paddle_tpu.parallel.engine import CompiledTrainStep

    # ptprof next to the analytic attribution: the same smoke run
    # carries BOTH sides of the measured-vs-analytic diff below
    paddle.set_flags({"FLAGS_perf_attribution": True,
                      "FLAGS_monitor_profile": True})
    timeseries.enable()
    perf.enable_sentinels()
    pprofile.start_sampler()
    on_tpu = jax.default_backend() != "cpu"
    pmesh.build_hybrid_mesh(dp=1, devices=jax.devices()[:1])
    paddle.seed(0)
    if on_tpu:
        # a 134M decoder: large enough that the flash kernels engage
        cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                          intermediate_size=2048, num_hidden_layers=12,
                          num_attention_heads=6,
                          max_position_embeddings=2048,
                          use_parallel=False, dtype="bfloat16")
        batch, seq = 8, 1024
    else:
        cfg = LlamaConfig.tiny(use_parallel=False)
        batch, seq = 2, 32
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model.to(dtype="bfloat16")
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())

    def loss_fn(logits, labels):
        return F.cross_entropy(
            logits.reshape([-1, cfg.vocab_size]), labels.reshape([-1]))

    step = CompiledTrainStep(model, loss_fn, opt)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(
        0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    labels = paddle.to_tensor(rng.randint(
        0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    loss = step(ids, labels)        # compile + first attribution
    float(loss)
    t0 = time.perf_counter()
    for _ in range(max(steps, 1)):
        loss = step(ids, labels)
    final = float(loss)
    dt = time.perf_counter() - t0
    assert np.isfinite(final), final
    tokens_per_s = batch * seq * max(steps, 1) / dt
    payload = perf.perf_payload()
    payload["smoke"] = {
        "backend": jax.default_backend(),
        "batch": batch, "seq": seq, "steps": max(steps, 1),
        "tokens_per_s": round(tokens_per_s, 1),
        "final_loss": final,
    }
    # hardware-normalized bench fields over the steady-state window
    # (the per-step gauges cover the LAST step; this is the mean)
    payload["smoke"].update(perf.bench_fields(
        step._perf_attr.analysis if step._perf_attr else None,
        tokens_per_s=tokens_per_s, tokens_per_step=batch * seq))
    # host-sampler summary (component shares, top stacks) rides along
    # so the artifact answers "where did the host time go" too
    payload["profile"] = pprofile.profile_payload()
    return payload


def fetch(endpoint, timeout_s=10.0):
    url = endpoint if "://" in endpoint else "http://" + endpoint
    with urllib.request.urlopen(url.rstrip("/") + "/debugz/perf",
                                timeout=timeout_s) as r:
        return json.loads(r.read().decode())


def _fmt_bytes(n):
    if n is None:
        return "?"
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return "%.1f %s" % (n, unit)
        n /= 1024.0


def render(payload, out=sys.stdout):
    w = out.write
    jobs = payload.get("jobs") or {}
    machine = payload.get("machine") or {}
    smoke_row = payload.get("smoke")
    if smoke_row:
        w("== smoke run ==\n")
        for k in ("backend", "batch", "seq", "steps", "tokens_per_s",
                  "final_loss", "mfu", "model_flops_per_step",
                  "hbm_peak_bytes"):
            if k in smoke_row:
                w("  %-22s %s\n" % (k, smoke_row[k]))
    for job, r in sorted(jobs.items()):
        w("== perf: %s ==\n" % job)
        if "mfu" in r:
            w("  %-22s %.5f   (peak %.1f TFLOP/s)\n"
              % ("mfu", r["mfu"],
                 (r.get("peak_flops") or machine.get("peak_flops", 0))
                 / 1e12))
        if "model_flops_per_step" in r:
            w("  %-22s %.3e\n" % ("model_flops/step",
                                  r["model_flops_per_step"]))
        if "model_flops_per_s" in r:
            w("  %-22s %.3f\n" % ("model TFLOP/s",
                                  r["model_flops_per_s"] / 1e12))
        if "step_seconds" in r:
            w("  %-22s %.3f ms\n" % ("step time",
                                     r["step_seconds"] * 1e3))
        if "tokens_per_s" in r:
            w("  %-22s %.1f\n" % ("tokens/s", r["tokens_per_s"]))
        if "goodput_tokens_per_s" in r:
            w("  %-22s %.1f (throughput %.1f)\n"
              % ("goodput tok/s", r["goodput_tokens_per_s"],
                 r.get("throughput_tokens_per_s", 0.0)))
        if "kv_page_occupancy" in r:
            w("  %-22s %.3f\n" % ("kv page occupancy",
                                  r["kv_page_occupancy"]))
        share = r.get("phase_share")
        if share:
            w("  %-22s compute %.1f%%  comm %.1f%%  host %.1f%%"
              "  (comm source: %s)\n"
              % ("phase split", 100 * share.get("compute", 0),
                 100 * share.get("comm", 0), 100 * share.get("host", 0),
                 r.get("comm_source", "none")))
        if "hbm_peak_bytes" in r:
            note = (" (executable upper-bound estimate)"
                    if r.get("hbm_peak_is_estimate") else "")
            w("  %-22s %s%s\n" % ("hbm peak",
                                  _fmt_bytes(r["hbm_peak_bytes"]), note))
        if "loss" in r:
            w("  %-22s %s\n" % ("last loss", r["loss"]))
    anomalies = payload.get("anomalies") or {}
    counts = anomalies.get("counts") or {}
    w("== anomalies ==\n")
    if counts:
        w("  DEGRADED since %s: %s\n"
          % (anomalies.get("degraded_since"),
             ", ".join("%s x%d" % kv for kv in sorted(counts.items()))))
    else:
        w("  none\n")
    render_measured(payload, out)


def render_measured(payload, out=sys.stdout):
    """Measured-vs-analytic phase reconciliation (ISSUE 13): diff the
    ptprof dispatch/blocked/gap timers against the analytic
    ``perf_phase_seconds`` split per job. The analytic model becomes
    falsifiable here — and the exposed-comm residual (measured step −
    analytic compute) is the number ROADMAP item 4's overlap work is
    scored on. NEVER fabricates a side: a job missing the measured
    timers (FLAGS_monitor_profile off) or the analytic split
    (FLAGS_perf_attribution off) says so instead of diffing zeros."""
    w = out.write
    jobs = payload.get("jobs") or {}
    w("== measured vs analytic (ptprof) ==\n")
    if not jobs:
        w("  no jobs report either side\n")
        return
    for job, r in sorted(jobs.items()):
        meas = all(isinstance(r.get(k), (int, float)) for k in (
            "profile_dispatch_seconds", "profile_host_blocked_seconds",
            "profile_host_gap_seconds"))
        phases = r.get("phase_seconds") or {}
        analytic = bool(phases)
        if meas and analytic:
            md = r["profile_dispatch_seconds"]
            mb = r["profile_host_blocked_seconds"]
            mg = r["profile_host_gap_seconds"]
            step_meas = md + mb
            comp = float(phases.get("compute", 0.0))
            comm = float(phases.get("comm", 0.0))
            host = float(phases.get("host", 0.0))
            w("  %s:\n" % job)
            w("    step      measured %.6fs (dispatch %.6f + blocked "
              "%.6f)  analytic %.6fs (compute %.6f + comm %.6f)  "
              "delta %+.6fs\n"
              % (step_meas, md, mb, comp + comm, comp, comm,
                 step_meas - (comp + comm)))
            w("    host gap  measured %.6fs  analytic host %.6fs  "
              "delta %+.6fs\n" % (mg, host, mg - host))
            w("    exposed-comm residual %.6fs (measured step - "
              "analytic compute; analytic comm says %.6fs, source %s)"
              "\n" % (step_meas - comp, comm,
                      r.get("comm_source", "?")))
        elif meas:
            w("  %s: measured only (analytic phase split absent — "
              "FLAGS_perf_attribution off?); no diff fabricated\n"
              % job)
        elif analytic:
            w("  %s: analytic only (measured timers absent — "
              "FLAGS_monitor_profile off?); no diff fabricated\n"
              % job)
        else:
            w("  %s: neither side present\n" % job)


def render_graph(graph_path, out=sys.stdout):
    """Collective-count + donation-audit columns from the pthlo
    artifact (tools/graph_report.json, the battery's pthlo row): one
    report answers "is the comm schedule still what we shipped". Reads
    the artifact only — never re-lowers anything — and renders it with
    the analysis package's OWN formatter so these columns can never
    drift from pthlo's output. paddle_tpu/__init__ imports jax but
    analysis/ is stdlib-only, so a bare worker gets the ptlint.py
    stub-package trick."""
    w = out.write
    try:
        with open(graph_path) as f:
            graph = json.load(f)
    except (OSError, ValueError) as e:
        w("== graph report %s unreadable: %s ==\n" % (graph_path, e))
        return
    if graph.get("kind") != "pthlo_report":
        w("== %s is not a pthlo report ==\n" % graph_path)
        return
    if "paddle_tpu" not in sys.modules:
        import types

        _pkg = types.ModuleType("paddle_tpu")
        _pkg.__path__ = [os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "paddle_tpu")]
        sys.modules["paddle_tpu"] = _pkg
    from paddle_tpu.analysis.graph.runner import render_graph_text

    w("== graph report (%s) ==\n" % os.path.basename(graph_path))
    w(render_graph_text(graph))
    w("\n")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    src = ap.add_mutually_exclusive_group()
    src.add_argument("--endpoint",
                     help="host:port of a live rank (GET /debugz/perf)")
    src.add_argument("--in", dest="infile",
                     help="previously-written payload JSON")
    ap.add_argument("--smoke", action="store_true",
                    help="force the smoke run (the default source)")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--json", action="store_true",
                    help="print the payload JSON instead of the report")
    ap.add_argument("--out", help="also write the payload JSON here")
    ap.add_argument("--graph", default=None,
                    help="pthlo artifact for the collective/donation "
                         "columns (default: tools/graph_report.json "
                         "when present; 'none' disables)")
    a = ap.parse_args(argv)
    _watchdog()

    if a.endpoint:
        payload = fetch(a.endpoint)
    elif a.infile:
        with open(a.infile) as f:
            payload = json.load(f)
    else:
        payload = smoke(a.steps)

    if a.out:
        with open(a.out, "w") as f:
            json.dump(payload, f, indent=1, default=str)
            f.write("\n")
    if a.json:
        print(json.dumps(payload, default=str))
    else:
        render(payload)
    graph_path = a.graph
    if graph_path is None:
        default = os.path.join(os.path.dirname(os.path.abspath(
            __file__)), "graph_report.json")
        if os.path.exists(default):
            graph_path = default
    if graph_path and graph_path != "none" and not a.json:
        render_graph(graph_path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
