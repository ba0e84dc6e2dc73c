"""Train-step timing of the toy decoder, on the chip or not at all.

One process. It fails (exit 2, no row, no file) when JAX's default
platform is not a TPU; there is no CPU configuration, no probe child and
no last-good file to re-emit. On a TPU it prints ONE JSON line:
tokens/s of a 134M Llama-style decoder (hidden 768, 12 layers, batch
8 x 1024, bf16) through parallel.engine.CompiledTrainStep on exactly one
device, every timed region ended by ``block_until_ready``, with the
platform, ``device_kind`` and device count it ran on in the row.

This is the pre-ledger toy cell; ROADMAP S1 replaces it with the
``workloads`` benchmark. ``python chip_smoke.py`` is the check that the
program starts on the chip at all.

Variants, tagged in the row: BENCH_FUSE=1 (fused qkv / gate-up
projections), FLAGS_fused_lm_head_ce=1 (streaming lm_head+CE kernel),
BENCH_SINGLE_STEP=1 (skip the run_steps device loop).
"""
from __future__ import annotations

import json
import os
import sys
import time

STEPS_PER_CALL = 10     # run_steps window
WINDOWS = 2
SINGLE_STEPS = 20


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.stderr.write(
            "bench.py: JAX's default platform is %r, not 'tpu' — a "
            "timing taken here would not be a device number; nothing "
            "measured, nothing written\n" % dev.platform)
        return 2

    import numpy as np
    from jax.sharding import Mesh

    import paddle_tpu as paddle
    from paddle_tpu.core import compile_cache
    from paddle_tpu.core import flags as _flg
    from paddle_tpu.kernels.fused_ce import DEFAULT_BLOCK_T
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.monitor import perf as _perf
    from paddle_tpu.parallel.engine import CompiledTrainStep

    compile_cache.configure()
    paddle.seed(0)
    fuse = os.environ.get("BENCH_FUSE") == "1"
    single = os.environ.get("BENCH_SINGLE_STEP") == "1"
    # head_dim 128 (768/6) engages the Pallas flash kernel
    cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                      intermediate_size=2048, num_hidden_layers=12,
                      num_attention_heads=6, max_position_embeddings=2048,
                      use_parallel=False, dtype="bfloat16",
                      fuse_attention_qkv=fuse, fuse_mlp=fuse)
    batch, seq = 8, 1024
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    fused_ce = bool(_flg.flag("FLAGS_fused_lm_head_ce")
                    and (batch * seq) % DEFAULT_BLOCK_T == 0)
    # one chip means one device: get_mesh()'s default would span every
    # chip of the host and make this step data-parallel
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    step = CompiledTrainStep(model, None, opt, mesh=mesh,
                             labels_to_model=True)
    rng = np.random.RandomState(0)
    shape = (STEPS_PER_CALL, batch, seq)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, shape).astype(np.int32))
    labels = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, shape).astype(np.int32))

    def timed(fn, calls):
        jax.block_until_ready(fn()._value)      # warm-up / compile
        t0 = time.perf_counter()
        for _ in range(calls):
            loss = fn()
        jax.block_until_ready(loss._value)
        dt = time.perf_counter() - t0
        if not np.isfinite(float(loss)):
            raise AssertionError("non-finite loss %r" % float(loss))
        return dt

    dt = timed(lambda: step(ids[0], labels[0]), SINGLE_STEPS)
    single_tps = batch * seq * SINGLE_STEPS / dt
    loop_tps = None
    if not single:
        dt = timed(lambda: step.run_steps(ids, labels), WINDOWS)
        loop_tps = batch * seq * STEPS_PER_CALL * WINDOWS / dt
    headline = single_tps if single else loop_tps

    row = {
        "metric": "llama134m_train_tokens_per_sec_per_chip",
        "value": round(headline, 1),
        "unit": "tokens/s",
        "single_step_tokens_per_sec": round(single_tps, 1),
        "steps_per_call": 1 if single else STEPS_PER_CALL,
        **_perf.device_fields(),
        "devices_used": int(mesh.size),
        "fused_lm_head_ce": fused_ce,
        "fused_projections": fuse,
    }
    # mfu against the DEVICE_PEAKS row of this device_kind; an unknown
    # kind raises here rather than borrowing another chip's peak
    row.update(_perf.bench_fields(
        step.perf_analysis(ids[0], labels[0]), tokens_per_s=headline,
        tokens_per_step=batch * seq))
    print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
