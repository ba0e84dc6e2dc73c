"""SLO/incident snapshot artifact.

Runs the bench-family decoder for a few compiled steps with the SLO
plane ON (``FLAGS_monitor_slo`` — the timeseries ring, the objective
judge and the incident table) and commits the /debugz/slo verdicts +
/debugz/incidents table as ``tools/slo_snapshot.json``: per-objective
attainment, error-budget remaining, burn rates per alerting window,
open/resolved incidents. A compliant bench run judges clean (no
burn-rate alert, empty incident table) — the artifact proves the
judge ran, not that something burned.

Alternative sources:
  --endpoint URL   scrape a LIVE process's /debugz/slo +
                   /debugz/incidents instead of measuring (operator
                   mode, the fleet_snapshot shape)
  --once           emit the current in-process payload without
                   driving any workload (smoke mode)

On failure nothing is written and the exit code is 3: a measurement
that did not happen leaves no artifact behind.

Usage:
  python tools/slo_report.py [--steps N] [--out tools/slo_snapshot.json]
  python tools/slo_report.py --json            # print payload, no file
  python tools/slo_report.py --endpoint http://127.0.0.1:8123
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))

DEFAULT_OUT = os.path.join(HERE, "slo_snapshot.json")


def _watchdog(seconds=540):
    def fire(signum, frame):
        sys.stderr.write("slo_report watchdog: %ds, aborting\n"
                         % seconds)
        os._exit(3)

    signal.signal(signal.SIGALRM, fire)
    signal.alarm(seconds)


def _base(source):
    return {
        "kind": "slo_snapshot",
        "version": 1,
        "ok": True,
        "source": source,
        "written_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                    time.gmtime()),
        "unix_time": time.time(),
        "pid": os.getpid(),
    }


def scrape(endpoint, timeout_s=5.0):
    """Operator mode: pull the verdicts from a live process."""
    out = _base("endpoint:%s" % endpoint)
    for route, key in (("debugz/slo", "slo"),
                       ("debugz/incidents", "incidents")):
        with urllib.request.urlopen(
                "%s/%s" % (endpoint.rstrip("/"), route),
                timeout=timeout_s) as r:
            out[key] = json.loads(r.read().decode())
    return out


def snapshot_local(source="once"):
    """The current in-process judge + table state."""
    from paddle_tpu.monitor import incidents as ptincidents
    from paddle_tpu.monitor import slo as ptslo

    out = _base(source)
    out["slo"] = ptslo.payload()
    out["incidents"] = ptincidents.payload()
    return out


def measure(steps=5):
    """Bench-family decoder under the SLO plane; returns the snapshot
    dict (ok=True)."""
    import numpy as np
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed import mesh as pmesh
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.monitor import slo as ptslo
    from paddle_tpu.parallel.engine import CompiledTrainStep

    paddle.set_flags({"FLAGS_monitor_slo": True})
    ptslo.enable()      # latch windows/objectives before the workload
    on_tpu = jax.default_backend() != "cpu"
    pmesh.build_hybrid_mesh(dp=1, devices=jax.devices()[:1])
    paddle.seed(0)
    if on_tpu:
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
    for _ in range(max(int(steps), 1)):
        loss = step(ids, labels)
    final = float(loss)
    assert np.isfinite(final), final
    snap = snapshot_local("measure")
    snap["backend"] = jax.default_backend()
    snap["config"] = {"batch": batch, "seq": seq,
                      "steps": max(int(steps), 1),
                      "hidden": cfg.hidden_size,
                      "layers": cfg.num_hidden_layers}
    snap["final_loss"] = final
    return snap


def write_artifact(path, snap):
    """Atomic write of a real snapshot. A failed measurement writes
    nothing (main returns 3): there is no previous artifact to re-emit."""
    d = os.path.dirname(os.path.abspath(path))
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(snap, f, indent=1, default=str)
        f.write("\n")
    os.replace(tmp, path)
    return snap


def _print_summary(snap, out_path):
    slo = snap.get("slo") or {}
    inc = snap.get("incidents") or {}
    print("slo_report: wrote %s (source=%s, objectives=%d, "
          "open_incidents=%d)"
          % (out_path, snap.get("source"),
             len(slo.get("objectives") or ()),
             len(inc.get("open") or ())))
    for o in slo.get("objectives") or ():
        att = o.get("attainment")
        bud = o.get("budget_remaining_ratio")
        alerting = [g for g, v in (o.get("alerting") or {}).items()
                    if v]
        print("  %-22s att=%-8s budget=%-8s samples=%-6s %s"
              % (o.get("objective"),
                 "%.4f" % att if isinstance(att, (int, float))
                 else "-",
                 "%.3f" % bud if isinstance(bud, (int, float))
                 else "-",
                 o.get("samples"),
                 "ALERTING:%s" % ",".join(alerting) if alerting
                 else ""))
    for i in inc.get("open") or ():
        print("  OPEN %s [%s] %s" % (i.get("key"), i.get("severity"),
                                     i.get("summary")))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--once", action="store_true",
                    help="emit the current in-process payload without "
                    "driving a workload")
    ap.add_argument("--endpoint",
                    help="scrape a live process's /debugz/slo + "
                    "/debugz/incidents instead of measuring")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="artifact path (nothing is written on failure)")
    ap.add_argument("--json", action="store_true",
                    help="print the snapshot JSON to stdout")
    a = ap.parse_args(argv)
    _watchdog()

    try:
        if a.endpoint:
            snap = scrape(a.endpoint)
        elif a.once:
            snap = snapshot_local()
        else:
            snap = measure(a.steps)
    except Exception as e:
        sys.stderr.write("slo_report: measurement failed: %r\n" % (e,))
        return 3
    write_artifact(a.out, snap)
    if a.json:
        print(json.dumps(snap, default=str))
    else:
        _print_summary(snap, a.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
