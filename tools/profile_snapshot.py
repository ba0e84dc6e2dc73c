"""Continuous-profiling snapshot artifact.

Runs the bench-family decoder for a few compiled steps with ptprof ON
(``FLAGS_monitor_profile`` for the host sampler + measured dispatch/
blocked/gap timers, ``FLAGS_perf_attribution`` so the analytic
``perf_phase_seconds`` split exists to reconcile against) and commits
the /debugz/profile payload — sampler stats, component attribution,
top-K folded stacks, per-job measured phases — plus the measured-vs-
analytic diff inputs, as ``tools/profile_snapshot.json``.

``--once`` skips the train smoke and just samples THIS process for a
short window — the host-only spelling for probing a box without paying
a compile.

On failure nothing is written and the exit code is 3: a measurement
that did not happen leaves no artifact behind.

Usage:
  python tools/profile_snapshot.py [--steps N] [--out tools/profile_snapshot.json]
  python tools/profile_snapshot.py --once        # host-only sample window
  python tools/profile_snapshot.py --json        # print payload too
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

DEFAULT_OUT = os.path.join(HERE, "profile_snapshot.json")


def _watchdog(seconds=540):
    def fire(signum, frame):
        sys.stderr.write("profile_snapshot watchdog: %ds, aborting\n"
                         % seconds)
        os._exit(3)

    signal.signal(signal.SIGALRM, fire)
    signal.alarm(seconds)


def _base_snap(backend, mode):
    return {
        "kind": "profile_snapshot",
        "version": 1,
        "ok": True,
        "written_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                    time.gmtime()),
        "unix_time": time.time(),
        "pid": os.getpid(),
        "backend": backend,
        "mode": mode,
    }


def measure_once(window_s=0.8):
    """Host-only: start the sampler, keep this process busy for a short
    window, snapshot. No model, no compile — a bare-box probe."""
    import paddle_tpu as paddle
    from paddle_tpu.monitor import profile as pprof

    paddle.set_flags({"FLAGS_monitor_profile": True})
    pprof.start_sampler()
    t0 = time.monotonic()
    x = 0
    while time.monotonic() - t0 < float(window_s):
        x = (x + 1) % 1000003
    snap = _base_snap("host-only", "once")
    snap["profile"] = pprof.profile_payload()
    return snap


def measure(steps=5):
    """Bench-family decoder under ptprof + perf attribution; returns
    the snapshot dict (ok=True) carrying both sides of the
    measured-vs-analytic reconciliation."""
    import numpy as np
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed import mesh as pmesh
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.monitor import perf
    from paddle_tpu.monitor import profile as pprof
    from paddle_tpu.parallel.engine import CompiledTrainStep

    paddle.set_flags({"FLAGS_monitor_profile": True,
                      "FLAGS_perf_attribution": True})
    pprof.start_sampler()
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
    snap = _base_snap(jax.default_backend(), "smoke")
    snap["config"] = {"batch": batch, "seq": seq,
                      "steps": max(int(steps), 1),
                      "hidden": cfg.hidden_size,
                      "layers": cfg.num_hidden_layers}
    snap["final_loss"] = final
    snap["profile"] = pprof.profile_payload()
    # the analytic side of the reconciliation (perf.note_job rows carry
    # both the phase split and the mirrored profile_* measurements)
    snap["perf_jobs"] = (perf.perf_payload() or {}).get("jobs") or {}
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


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--once", action="store_true",
                    help="host-only sampler window, no train smoke")
    ap.add_argument("--window", type=float, default=0.8,
                    help="--once: sample window seconds")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="artifact path (nothing is written on failure)")
    ap.add_argument("--json", action="store_true",
                    help="print the snapshot JSON to stdout")
    a = ap.parse_args(argv)
    _watchdog()

    try:
        snap = measure_once(a.window) if a.once else measure(a.steps)
    except Exception as e:
        sys.stderr.write("profile_snapshot: measurement failed: %r\n"
                         % (e,))
        return 3
    write_artifact(a.out, snap)
    if a.json:
        print(json.dumps(snap, default=str))
    else:
        prof = snap["profile"]
        sampler = prof.get("sampler") or {}
        print("profile_snapshot: wrote %s (backend=%s, samples=%s, "
              "overhead=%.4f%%)"
              % (a.out, snap["backend"], sampler.get("samples"),
                 100 * (sampler.get("overhead_share") or 0.0)))
        for comp, row in sorted((prof.get("components") or {}).items()):
            print("  component %-12s %5.1f%%  (%d samples)"
                  % (comp, 100 * row["share"], row["samples"]))
        for job, tot in sorted((prof.get("jobs") or {}).items()):
            print("  job=%-8s steps=%d dispatch=%.4fs blocked=%.4fs "
                  "gap=%.4fs"
                  % (job, tot["steps"], tot["dispatch_s"],
                     tot["blocked_s"], tot["gap_s"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
