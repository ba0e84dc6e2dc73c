"""Memory-plane snapshot artifact.

Runs the bench-family decoder for a few compiled steps with the memory
plane ON (``FLAGS_monitor_memory`` + ``FLAGS_perf_attribution`` so the
compiled transient peak feeds the headroom math) and commits the
/debugz/memory breakdown — per-component ledger, allocator
reconciliation, static-vs-transient split, headroom — as
``tools/mem_snapshot.json``.

On failure nothing is written and the exit code is 3: a measurement
that did not happen leaves no artifact behind.

Usage:
  python tools/mem_snapshot.py [--steps N] [--out tools/mem_snapshot.json]
  python tools/mem_snapshot.py --json          # print payload, no file
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))

DEFAULT_OUT = os.path.join(HERE, "mem_snapshot.json")


def _watchdog(seconds=540):
    def fire(signum, frame):
        sys.stderr.write("mem_snapshot watchdog: %ds, aborting\n"
                         % seconds)
        os._exit(3)

    signal.signal(signal.SIGALRM, fire)
    signal.alarm(seconds)


def measure(steps=5):
    """Bench-family decoder under the memory plane; returns the
    snapshot dict (ok=True)."""
    import numpy as np
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed import mesh as pmesh
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.monitor import memory as ptmem
    from paddle_tpu.parallel.engine import CompiledTrainStep

    paddle.set_flags({"FLAGS_monitor_memory": True,
                      "FLAGS_perf_attribution": True})
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
    # the compiled transient peak for the headroom split (the same
    # donation-aware number graph_report()/perf publish)
    analysis = step.perf_analysis(ids, labels)
    payload = ptmem.memory_payload()
    return {
        "kind": "mem_snapshot",
        "version": 1,
        "ok": True,
        "written_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                    time.gmtime()),
        "unix_time": time.time(),
        "pid": os.getpid(),
        "backend": jax.default_backend(),
        "config": {"batch": batch, "seq": seq,
                   "steps": max(int(steps), 1),
                   "hidden": cfg.hidden_size,
                   "layers": cfg.num_hidden_layers},
        "final_loss": final,
        "compiled_peak_bytes": analysis.get("hbm_peak_bytes"),
        "compiled_peak_is_estimate":
            bool(analysis.get("hbm_peak_is_estimate")),
        "memory": payload,
    }


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


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="artifact path (nothing is written on failure)")
    ap.add_argument("--json", action="store_true",
                    help="print the snapshot JSON to stdout")
    a = ap.parse_args(argv)
    _watchdog()

    try:
        snap = measure(a.steps)
    except Exception as e:
        sys.stderr.write("mem_snapshot: measurement failed: %r\n" % (e,))
        return 3
    write_artifact(a.out, snap)
    if a.json:
        print(json.dumps(snap, default=str))
    else:
        mem = snap["memory"]
        rec = mem.get("reconciliation") or {}
        print("mem_snapshot: wrote %s (backend=%s, ledger=%s bytes, "
              "witness=%s via %s)"
              % (a.out, snap["backend"], rec.get("ledger_bytes"),
                 rec.get("live_bytes"), rec.get("source")))
        for job, row in sorted((mem.get("jobs") or {}).items()):
            print("  job=%-8s ledger=%s  transient_peak=%s  headroom=%s"
                  % (job, row.get("ledger_bytes"),
                     row.get("transient_peak_bytes"),
                     row.get("headroom_bytes")))
    return 0


if __name__ == "__main__":
    sys.exit(main())
