"""Closed-loop backlog serving: ``serving.Engine`` with a queue that is
never empty.

Set-up: model, engine, one request per prefill bucket the mix can draw
and the decode step (so nothing compiles in the window), the reference
check on two prompts, the kernels-present check, then the fill. The
window: ``Engine.step()`` over and over, the queue topped up after every
step; it closes at the end of the step in which ``seconds`` ran out, and
rates are over its real length. The benchmark stamps every token from
outside, after the step that produced it: the program has no per-token
stamp.
"""
from __future__ import annotations

import math
import statistics
import time

PARITY_FRAC = 2.0 ** -5
# The engine's greedy token against the reference's logits at the same
# position: the token must be the reference's argmax or within this
# fraction of the largest |logit| of it. Two attention implementations a
# dozen layers deep in bf16 differ by a few roundings of 2^-8; the gap
# between the top two of 32768 near-gaussian logits is about 5 % of the
# largest, a wrong path is about 100 % away, and an exact bf16 tie has
# been seen on the chip (PR 23), so token ids will not do.

# the rate so far is logged every MARK_S seconds of the window: how
# steady it is through the window, and what a shorter window would read
MARK_S = 10.0
STEP_SPAN = "bench.engine_step"
BETWEEN_SPAN = "bench.between_steps"


def percentile(values, q):
    """The value at rank ceil(q n) of the sorted sample."""
    ordered = sorted(values)
    return ordered[min(max(math.ceil(q * len(ordered)) - 1, 0),
                       len(ordered) - 1)]


def prefill_buckets(lo, hi):
    """The engine's power-of-two prefill buckets that prompts of
    ``lo``..``hi`` tokens land in."""
    out, p = [], 8
    while p < lo:
        p *= 2
    while True:
        out.append(p)
        if p >= hi:
            return out
        p *= 2


def check_reference(family, cfg, weights, prompt, generated, log):
    """(ok, detail): every generated token is the reference's argmax at
    its position or within PARITY_FRAC of the largest |logit| of it."""
    import numpy as np

    toks = list(generated)
    logits = np.asarray(
        family.reference_logits(weights, cfg, list(prompt) + toks))
    p = len(prompt)
    rows = logits[p - 1:p - 1 + len(toks)]
    tol = PARITY_FRAC * float(np.abs(rows).max())
    gaps = rows.max(-1) - rows[np.arange(len(toks)), toks]
    exact = int((rows.argmax(-1) == np.asarray(toks)).sum())
    detail = ("prompt %d: %d/%d tokens are the reference argmax, largest "
              "gap %.3e (tolerance %.3e = 2^-5 of max |logit|)"
              % (p, exact, len(toks), float(gaps.max()), tol))
    log("reference: " + detail)
    return bool(gaps.max() <= tol), detail


def run_backlog(family, cfg, mix, seed, seconds, log, trace_dir=None,
                on_chip=True):
    """Drive one backlog cell. All sizes come in through ``cfg`` and
    ``mix`` (the tests call this on the CPU at a tiny size with
    ``on_chip=False``, which only drops the Mosaic-kernel check)."""
    import jax
    import numpy as np

    import trace_reduce
    import traffic_gen
    from paddle_tpu import serving
    from paddle_tpu.serving.scheduler import RequestState

    e = mix["engine"]
    slots = int(e["max_slots"])
    device = jax.devices()[0]
    model = family.build_model(cfg, seed, training=False)
    eng = serving.Engine(model, max_slots=slots,
                         num_blocks=int(e["num_blocks"]),
                         block_size=int(e["block_size"]),
                         max_model_len=int(e["max_model_len"]))
    stats = device.memory_stats() or {}
    page_bytes = family.kv_page_bytes(cfg, eng.block_size)
    log("engine: %d slots, %d pages of %d tokens (%.2f GB of KV at %d B a "
        "page), max_model_len %d; device holds %.3f GB of %.3f GB"
        % (slots, e["num_blocks"], eng.block_size,
           e["num_blocks"] * page_bytes / 1e9, page_bytes,
           eng.max_model_len, stats.get("bytes_in_use", 0) / 1e9,
           stats.get("bytes_limit", 0) / 1e9))

    stream = traffic_gen.RequestStream(mix, cfg["vocab_size"], seed)
    prompts = [p for p, _ in stream.pool]
    log("mix: prompt tokens %s; output tokens %s"
        % (traffic_gen.summary(prompts),
           traffic_gen.summary([o for _, o in stream.pool])))

    def drain():
        while eng.has_work():
            eng.step()

    # -- the reference check, which also compiles two buckets and the
    #    decode step
    checks = {}
    weights = family.weights_of(model)
    ref_ok, ref_details = True, []
    for n in mix["reference_prompts"]:
        prompt = stream.tokens(n)
        t0 = time.monotonic()
        rid = eng.add_request(prompt, int(mix["reference_tokens"]))
        drain()
        log("warm-up: prompt %d + %d tokens in %.1fs"
            % (n, mix["reference_tokens"], time.monotonic() - t0))
        ok, detail = check_reference(family, cfg, weights, prompt,
                                     eng.output(rid), log)
        ref_ok = ref_ok and ok
        ref_details.append(detail)
    checks["reference"] = (ref_ok, "; ".join(ref_details))
    del weights

    # -- every other prefill bucket the mix can draw
    for bucket in prefill_buckets(min(prompts), max(prompts)):
        t0 = time.monotonic()
        before = eng.metrics.prefill_compiles
        eng.add_request(stream.tokens(bucket), 1)
        drain()
        log("warm-up: prefill bucket %d in %.1fs%s"
            % (bucket, time.monotonic() - t0,
               "" if eng.metrics.prefill_compiles > before
               else " (already traced)"))

    traced_once = eng.metrics.decode_compiles == 1
    kernels = {}
    if on_chip:
        # hot_step_hlo() traces once more: the counters are read below
        kernels, checks["kernels"] = trace_reduce.check_kernels(
            eng.hot_step_hlo(), mix["kernels"], cfg, "decode step")

    # -- the fill: one request a slot, at every age
    fractions = stream.fractions(slots)
    submitted = []
    for frac in fractions:
        prompt, out_len = stream.next()
        submitted.append(eng.add_request(
            prompt, max(2, int(round(frac * out_len)))))

    def top_up():
        while len(eng.scheduler.queue) < int(mix["queue_depth"]):
            prompt, out_len = stream.next()
            submitted.append(eng.add_request(prompt, out_len))

    top_up()
    eng.step()
    top_up()

    metrics = eng.metrics
    alloc = eng.cache.allocator
    compiles_before = (metrics.decode_compiles, metrics.prefill_compiles)
    # request id -> [tokens seen, stamp of the last one]; requests are
    # admitted in the order they were submitted
    seen = {req.id: [len(req.generated), None]
            for req in eng.scheduler.slots if req is not None}
    next_unseen = sum(eng.requests[rid].metrics.first_admit_t is not None
                      for rid in submitted)
    steps = []      # one row a step, see the dict below
    gaps = []
    marks = []      # (seconds, tokens out, gaps) at every MARK_S, logged
    tokens_out = 0
    queue_never_empty = True
    trace_after = (max(seconds - float(mix["trace_seconds"]), 0.0)
                   if trace_dir else None)
    tracing = False
    traced_from = None
    starting_s = 0.0    # the profiler's start, taken out of the window

    t_open = time.monotonic()
    while True:
        if trace_after is not None and not tracing \
                and time.monotonic() - t_open >= trace_after:
            t0 = time.monotonic()
            trace_reduce.start(trace_dir)
            starting_s = time.monotonic() - t0
            tracing = True
            traced_from = len(steps)
        with jax.profiler.TraceAnnotation(STEP_SPAN):
            prefills_before = metrics.prefill_runs
            t0 = time.monotonic()
            eng.step()
            t1 = time.monotonic()
        with jax.profiler.TraceAnnotation(BETWEEN_SPAN):
            while next_unseen < len(submitted) and eng.requests[
                    submitted[next_unseen]].metrics.first_admit_t \
                    is not None:
                seen[submitted[next_unseen]] = [0, None]
                next_unseen += 1
            for rid in list(seen):
                req = eng.requests[rid]
                n = len(req.generated)
                mark = seen[rid]
                for _ in range(n - mark[0]):
                    if mark[1] is not None:
                        gaps.append(t1 - mark[1])
                    mark[1] = t1
                tokens_out += n - mark[0]
                mark[0] = n
                if req.slot is None and req.state is not \
                        RequestState.PREEMPTED:
                    del seen[rid]
            steps.append({
                "wall_s": t1 - t0,
                "prefills": metrics.prefill_runs - prefills_before,
                "active_slots": eng.scheduler.slots_active(),
                "free_blocks": alloc.free_blocks,
                "context_tokens": int(eng.cache.seq_lens.sum()),
                "rows": int((eng.cache.seq_lens > 0).sum()),
            })
            if not eng.scheduler.queue:
                queue_never_empty = False
            top_up()
            if t1 - t_open >= MARK_S * (len(marks) + 1):
                marks.append((t1 - t_open, tokens_out, len(gaps)))
        if t1 - t_open >= seconds:
            break
    t_close = t1
    window = t_close - t_open - starting_s
    if tracing:
        trace_reduce.stop()

    compiles_after = (metrics.decode_compiles, metrics.prefill_compiles)
    checks["no_compile_in_window"] = (
        traced_once and compiles_after == compiles_before,
        "decode traced once by the engine's own calls: %s; (decode, "
        "prefill) traces %s before the window, %s after"
        % (traced_once, compiles_before, compiles_after))
    checks["queue_never_empty"] = (
        queue_never_empty, "%d waiting at the window's end"
        % len(eng.scheduler.queue))

    attempted = failed = 0
    bad = (RequestState.EXPIRED, RequestState.SHED, RequestState.FAILED)
    for rid in submitted:
        req = eng.requests[rid]
        admitted = req.metrics.first_admit_t
        if admitted is not None and t_open <= admitted <= t_close:
            attempted += 1
            failed += req.state in bad
    log("window %.3f s: %d steps, %d tokens out, %d token gaps (median "
        "%.2f ms), %d requests admitted, %d failed, %d preemptions"
        % (window, len(steps), tokens_out, len(gaps),
           1e3 * statistics.median(gaps) if gaps else float("nan"),
           attempted, failed, metrics.preemptions))

    for at, toks, n in marks:
        log("  had the window closed after %.3f s: %.2f tokens/s, token gap "
            "p95 %.2f ms" % (at, toks / at, 1e3 * percentile(gaps[:n], 0.95)))

    end_to_end = {"serve_out_tok_s": tokens_out / window}
    if gaps:
        end_to_end["itl_p95_ms"] = 1e3 * percentile(gaps, 0.95)
    return {
        "window_open_t": t_open,
        "window_s": window,
        "end_to_end": end_to_end,
        "checks": checks,
        "attempted": attempted,
        "failed": failed,
        "observations": {
            "steps": steps,
            "traced_steps": (steps[traced_from:] if tracing else []),
            "counters": eng.stats(),
            "max_slots": slots,
            "usable_blocks": alloc.usable_blocks,
        },
        "trace": ({"dir": trace_dir,
                   "window_spans": (STEP_SPAN, BETWEEN_SPAN),
                   "kernels": kernels} if tracing else None),
    }


def rehearse(family, cfg, mix, devices, placed):
    """[(program name, compiled)] of the decode step and the largest
    prefill at the cell's real sizes, compiled for described devices;
    ``placed`` turns an array into a ShapeDtypeStruct on them. The model
    and the pools are built on the host."""
    import jax
    import jax.numpy as jnp

    import traffic_gen
    from paddle_tpu import serving

    e = mix["engine"]
    model = family.build_model(cfg, 0, training=False)
    eng = serving.Engine(model, max_slots=int(e["max_slots"]),
                         num_blocks=int(e["num_blocks"]),
                         block_size=int(e["block_size"]),
                         max_model_len=int(e["max_model_len"]))
    _, _, decode_fn, decode_args = eng._hot_step()
    bucket = eng._bucket(max(p for p, _ in traffic_gen.length_pool(mix)))
    prefill_args = (eng._state_vals, eng.cache.pools,
                    jnp.zeros((1, bucket), jnp.int32),
                    jnp.asarray(eng.cache.block_tables[0]),
                    jnp.asarray(bucket, jnp.int32))
    out = []
    for name, fn, args in (("decode step", decode_fn, decode_args),
                           ("prefill %d" % bucket, eng._prefill_fn,
                            prefill_args)):
        lowered = eng._run_eval(
            jax.jit(fn, donate_argnums=(1,)).lower,
            *jax.tree_util.tree_map(placed, args))
        out.append((name, lowered.compile()))
    return out


def run(ctx):
    return run_backlog(ctx.family, ctx.config, ctx.traffic, ctx.seed,
                       ctx.seconds, ctx.log, trace_dir=ctx.trace_dir)
