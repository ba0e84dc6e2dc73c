"""Pre-training steps: ``CompiledTrainStep`` on a mesh of the cell's
chips, AdamW, a fresh seeded batch uploaded from the host every step,
each step ended by ``block_until_ready`` on its loss, as a loop that logs
the loss does.

Set-up: model, the reference's loss on one fixed batch (before the
optimizer state exists, while the memory is free), optimizer and step,
the kernels-present check, then ``warmup_updates`` updates on the fixed
batch, which compile the one step program and must give a finite,
falling loss. The window runs whole steps until ``seconds`` have passed
and closes with the last one; rates are over its real length.
"""
from __future__ import annotations

import math
import statistics
import time

# the rate so far is logged every MARK_S seconds of the window
MARK_S = 10.0
STEP_SPAN = "bench.train_step"
UPLOAD_SPAN = "bench.upload"


def draw_batch(rng, cfg, mix):
    """(ids, labels): uniform token ids, the labels the ids one on."""
    import numpy as np

    shape = (int(mix["batch"]), int(mix["seq_len"]) + 1)
    ids = rng.integers(0, cfg["vocab_size"], shape).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


def run_steps(family, cfg, mix, seed, seconds, log, devices, trace_dir=None,
              on_chip=True):
    """Drive one training cell on ``devices``. All sizes come in through
    ``cfg`` and ``mix`` (the tests call this on the CPU at a tiny size
    with ``on_chip=False``, which only drops the Mosaic-kernel check)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    import paddle_tpu as paddle
    import trace_reduce
    from paddle_tpu.parallel.engine import CompiledTrainStep

    batch, seq_len = int(mix["batch"]), int(mix["seq_len"])
    tokens_per_step = batch * seq_len
    checks = {}

    model = family.build_model(cfg, seed, training=True)
    fixed = draw_batch(np.random.default_rng(int(seed) + 1), cfg, mix)
    t0 = time.monotonic()
    ref_loss = family.reference_loss(family.weights_of(model), cfg, *fixed)
    log("reference: loss %.5f on the fixed batch in %.1fs"
        % (ref_loss, time.monotonic() - t0))

    opt = paddle.optimizer.AdamW(learning_rate=float(mix["learning_rate"]),
                                 parameters=model.parameters())
    mesh = Mesh(np.array(devices), ("dp",))
    step = CompiledTrainStep(model, None, opt, mesh=mesh,
                             labels_to_model=True)
    sharding = NamedSharding(mesh, step.batch_spec)
    stats = devices[0].memory_stats() or {}
    log("train step: %d x %d tokens, %.3f B parameters; device holds "
        "%.3f GB of %.3f GB with weights and moments"
        % (batch, seq_len, family.param_count(cfg) / 1e9,
           stats.get("bytes_in_use", 0) / 1e9,
           stats.get("bytes_limit", 0) / 1e9))

    kernels = {}
    if on_chip:
        kernels, checks["kernels"] = trace_reduce.check_kernels(
            step.lowered_hlo(*fixed), mix["kernels"], cfg, "train step")

    def one_step(ids, labels):
        with jax.profiler.TraceAnnotation(UPLOAD_SPAN):
            placed = (jax.device_put(ids, sharding),
                      jax.device_put(labels, sharding))
        with jax.profiler.TraceAnnotation(STEP_SPAN):
            loss = step(*placed)
            jax.block_until_ready(loss._value)
        return float(loss)

    warm = []
    for i in range(int(mix["warmup_updates"]) + 1):
        t0 = time.monotonic()
        warm.append(one_step(*fixed))
        log("warm-up: update %d on the fixed batch, loss before it %.4f "
            "(%.1fs)" % (i + 1, warm[-1], time.monotonic() - t0))
    rel = abs(warm[0] - ref_loss) / abs(ref_loss)
    checks["reference"] = (
        rel <= float(mix["loss_rtol"]),
        "step-0 loss %.5f vs the reference's %.5f: relative difference "
        "%.2e (tolerance %.0e)" % (warm[0], ref_loss, rel,
                                   mix["loss_rtol"]))
    checks["loss_falls"] = (
        all(math.isfinite(x) for x in warm) and warm[-1] < warm[0],
        "losses on the fixed batch %s" % ["%.4f" % x for x in warm])

    cache_size = step._compiled._cache_size()
    rng = np.random.default_rng(int(seed))
    step_s, losses, marks = [], [], []
    trace_steps = int(mix["trace_steps"]) if trace_dir else 0
    tracing = False
    traced_from = None
    starting_s = 0.0    # the profiler's start, taken out of the window

    t_open = time.monotonic()
    t_prev = t_open
    while True:
        if trace_steps and not tracing and step_s and (
                time.monotonic() - t_open
                >= seconds - trace_steps * statistics.median(step_s)):
            t0 = time.monotonic()
            trace_reduce.start(trace_dir)
            t_prev = time.monotonic()
            starting_s = t_prev - t0
            tracing = True
            traced_from = len(step_s)
        losses.append(one_step(*draw_batch(rng, cfg, mix)))
        t_now = time.monotonic()
        step_s.append(t_now - t_prev)
        t_prev = t_now
        if not tracing and t_now - t_open >= MARK_S * (len(marks) + 1):
            marks.append((t_now - t_open, len(step_s)))
        if t_now - t_open >= seconds:
            break
    window = t_prev - t_open - starting_s
    if tracing:
        trace_reduce.stop()

    grew = step._compiled._cache_size() - cache_size
    checks["no_compile_in_window"] = (
        grew == 0, "the step's executable cache held %d programs before "
        "the window and %d after" % (cache_size, cache_size + grew))
    bad = sum(not math.isfinite(x) for x in losses)
    checks["losses_finite"] = (bad == 0, "%d of %d window losses are not "
                               "finite" % (bad, len(losses)))
    log("window %.3f s: %d steps of %d tokens, median step %.2f ms, loss "
        "%.4f -> %.4f" % (window, len(step_s), tokens_per_step,
                          1e3 * statistics.median(step_s), losses[0],
                          losses[-1]))
    for at, n in marks:
        log("  had the window closed after %.3f s: %.2f tokens/s/chip"
            % (at, n * tokens_per_step / at / len(devices)))
    return {
        "window_open_t": t_open,
        "window_s": window,
        "end_to_end": {"train_tok_s_chip": len(step_s) * tokens_per_step
                       / window / len(devices)},
        "checks": checks,
        "attempted": len(step_s),
        "failed": bad,
        "observations": {
            "step_s": step_s,
            "traced_step_s": step_s[traced_from:] if tracing else [],
            "tokens_per_step": tokens_per_step,
            "batch": batch,
            "seq_len": seq_len,
        },
        "trace": ({"dir": trace_dir,
                   "window_spans": (STEP_SPAN, UPLOAD_SPAN),
                   "kernels": kernels} if tracing else None),
    }


def rehearse(family, cfg, mix, devices, placed):
    """[(program name, compiled)] of the step at the cell's real sizes,
    compiled for described devices; ``placed`` turns an array into a
    ShapeDtypeStruct on them. The model is built on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    import paddle_tpu as paddle
    from paddle_tpu.framework import random as _random
    from paddle_tpu.parallel.engine import CompiledTrainStep

    model = family.build_model(cfg, 0, training=True)
    opt = paddle.optimizer.AdamW(learning_rate=float(mix["learning_rate"]),
                                 parameters=model.parameters())
    step = CompiledTrainStep(
        model, None, opt, mesh=Mesh(np.array(jax.devices()[:1]), ("dp",)),
        labels_to_model=True)
    step._build()
    ids = jnp.zeros((int(mix["batch"]), int(mix["seq_len"])), jnp.int32)
    args = ([step._tensors[n]._value for n in step._names],
            step._opt_state, step._ef_state, jnp.asarray(0, jnp.int32),
            jnp.asarray(0.0, jnp.float32), _random._key(), (ids, ids))
    lowered = jax.jit(step._step_fn, donate_argnums=(0, 1, 2)).lower(
        *jax.tree_util.tree_map(placed, args))
    return [("train step", lowered.compile())]


def run(ctx):
    return run_steps(ctx.family, ctx.config, ctx.traffic, ctx.seed,
                     ctx.seconds, ctx.log, ctx.devices,
                     trace_dir=ctx.trace_dir)
