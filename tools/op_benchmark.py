"""Per-op micro-benchmark runner + regression gate.

Parity: reference op-benchmark CI tooling —
/root/reference/paddle/fluid/operators/benchmark/op_tester.cc (config-driven
op timing), /root/reference/tools/ci_op_benchmark.sh +
check_op_benchmark_result.py (compare against a stored baseline, fail the
gate on regression).

TPU shape: each case times a jitted op body looped on-device via lax.scan
(amortizes dispatch; see tools/ perf notes in BASELINE.md), subtracting
measured empty-body overhead. Baselines are committed JSON; `check`
compares a fresh run and fails on >tolerance slowdowns.

Usage:
  python tools/op_benchmark.py run  [--out FILE]      # measure
  python tools/op_benchmark.py check --baseline FILE [--tolerance 0.15]
  python tools/op_benchmark.py update --baseline FILE # refresh baseline

Both `check` and `update` print a COVERAGE summary (how many of the
measured cases the baseline actually guards) and list every UNGUARDED
row — a case with no baseline entry passes the gate vacuously, which is
how the committed TPU baseline quietly guarded only 8 of 44 cases.
`--strict-coverage` turns any unguarded row into a nonzero exit, so a
partial refresh can never masquerade as a full one.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _cases():
    """Benchmark config (reference op_tester's config files): op name ->
    (build_args, body). ~40 rows, one per op family feeding the
    north-star configs (llama decoder, ResNet-50, ERNIE-base, the
    optimizer/infra paths) — the breadth the reference gate guards
    (/root/reference/tools/ci_op_benchmark.sh:1). Shapes sized for the
    v5e bench models on TPU; scaled down 8x on CPU so the CI-plumbing
    run stays fast (baselines are per-platform — cross-platform numbers
    never compare; the original 8 rows keep their pre-expansion CPU
    shrink rule so the committed TPU baseline's names stay stable)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    rng = np.random.RandomState(0)
    scale = 1 if jax.default_backend() == "tpu" else 8

    def t(*shape, dtype=jnp.bfloat16):
        shape = tuple(max(s // scale, 1) if s >= 1024 else s for s in shape)
        return jnp.asarray(rng.randn(*shape), dtype)

    def s(*shape, dtype=jnp.bfloat16):
        """Aggressive CPU shrink (any dim >= 64) for the heavy new rows."""
        shape = tuple(max(d // scale, 1) if d >= 64 else d for d in shape)
        return jnp.asarray(rng.randn(*shape), dtype)

    cases = {}

    def case(name, args, body):
        cases[name] = (args, body)

    def fwd_bwd(fn, argnums=(0,)):
        def run(*args):
            return jax.value_and_grad(
                lambda *a: jnp.sum(fn(*a).astype(jnp.float32)),
                argnums=argnums)(*args)
        return run

    # -- original 8 rows (names/shapes frozen for baseline continuity) --
    case("matmul_8192x768x768",
         (t(8192, 768), t(768, 768)),
         lambda a, b: (a @ b, None)[0])
    case("matmul_8192x768x32000",
         (t(8192, 768), t(768, 32000)),
         lambda a, b: a @ b)
    case("softmax_8192x32000",
         (t(8192, 32000, dtype=jnp.float32),),
         lambda x: jax.nn.softmax(x, axis=-1))
    case("layer_norm_8192x768",
         (t(8192, 768, dtype=jnp.float32),),
         lambda x: (x - x.mean(-1, keepdims=True))
         / jnp.sqrt(x.var(-1, keepdims=True) + 1e-5))
    case("gelu_8192x2048",
         (t(8192, 2048),),
         jax.nn.gelu)
    case("flash_attention_8x1024x6x128", None, None)  # built below
    case("reduce_sum_8192x32000",
         (t(8192, 32000, dtype=jnp.float32),),
         lambda x: x.sum(axis=-1))
    case("transpose_8192x768",
         (t(8192, 768),),
         lambda x: x.T.copy() if hasattr(x.T, "copy") else jnp.swapaxes(
             x, 0, 1))

    from paddle_tpu.kernels.flash_attention import flash_attention

    q = t(8, 1024, 6, 128)
    cases["flash_attention_8x1024x6x128"] = (
        (q, t(8, 1024, 6, 128), t(8, 1024, 6, 128)),
        lambda q, k, v: flash_attention(q, k, v, causal=True))

    # -- llama-7B matmul shapes (MXU saturation at K/N >= 4096) --
    case("matmul_4096x4096x4096",
         (s(4096, 4096), s(4096, 4096)),
         lambda a, b: a @ b)
    case("matmul_mlp7b_4096x4096x11008",
         (s(4096, 4096), s(4096, 11008)),
         lambda a, b: a @ b)
    case("int8_matmul_8192x768x768",
         (jnp.asarray(rng.randint(-127, 127, (8192 // scale, 768)),
                      jnp.int8),
          jnp.asarray(rng.randint(-127, 127, (768, 768)), jnp.int8)),
         lambda a, b: lax.dot_general(
             a, b, (((1,), (0,)), ((), ())),
             preferred_element_type=jnp.int32))

    # -- ResNet-50 conv path (NCHW as the framework's conv lowers it) --
    dn = ("NCHW", "OIHW", "NCHW")
    case("conv2d_stem_7x7s2_64x3x224",
         (s(64, 3, 224, 224), s(64, 3, 7, 7)),
         lambda x, w: lax.conv_general_dilated(
             x, w, (2, 2), [(3, 3), (3, 3)], dimension_numbers=dn))
    case("conv2d_3x3_64x128x28",
         (s(64, 128, 28, 28), s(128, 128, 3, 3)),
         lambda x, w: lax.conv_general_dilated(
             x, w, (1, 1), "SAME", dimension_numbers=dn))
    case("conv2d_1x1_64x256x56_to512",
         (s(64, 256, 56, 56), s(512, 256, 1, 1)),
         lambda x, w: lax.conv_general_dilated(
             x, w, (1, 1), "VALID", dimension_numbers=dn))
    case("conv2d_fwd_bwd_3x3_64x128x28",
         (s(64, 128, 28, 28), s(128, 128, 3, 3)),
         fwd_bwd(lambda x, w: lax.conv_general_dilated(
             x, w, (1, 1), "SAME", dimension_numbers=dn),
             argnums=(0, 1)))
    case("batch_norm_train_64x128x28",
         (s(64, 128, 28, 28, dtype=jnp.float32),
          s(128, dtype=jnp.float32), s(128, dtype=jnp.float32)),
         lambda x, g, b: (x - x.mean((0, 2, 3), keepdims=True))
         / jnp.sqrt(x.var((0, 2, 3), keepdims=True) + 1e-5)
         * g.reshape(1, -1, 1, 1) + b.reshape(1, -1, 1, 1))
    case("batch_norm_fwd_bwd_64x128x28",
         (s(64, 128, 28, 28, dtype=jnp.float32),
          s(128, dtype=jnp.float32), s(128, dtype=jnp.float32)),
         fwd_bwd(lambda x, g, b: (x - x.mean((0, 2, 3), keepdims=True))
                 / jnp.sqrt(x.var((0, 2, 3), keepdims=True) + 1e-5)
                 * g.reshape(1, -1, 1, 1) + b.reshape(1, -1, 1, 1),
                 argnums=(0, 1, 2)))
    case("maxpool_3x3s2_64x64x112",
         (s(64, 64, 112, 112),),
         lambda x: lax.reduce_window(
             x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
             [(0, 0), (0, 0), (1, 1), (1, 1)]))

    # -- norms / rotary / activations (llama + ERNIE hot paths) --
    case("layer_norm_fwd_bwd_8192x768",
         (s(8192, 768, dtype=jnp.float32),),
         fwd_bwd(lambda x: (x - x.mean(-1, keepdims=True))
                 / jnp.sqrt(x.var(-1, keepdims=True) + 1e-5)))
    case("rmsnorm_8x1024x4096",
         (s(8, 1024, 4096), s(4096)),
         lambda x, w: (x.astype(jnp.float32)
                       * jax.lax.rsqrt(jnp.mean(
                           jnp.square(x.astype(jnp.float32)), -1,
                           keepdims=True) + 1e-6)).astype(x.dtype) * w)
    case("rmsnorm_fwd_bwd_8x1024x4096",
         (s(8, 1024, 4096), s(4096)),
         fwd_bwd(lambda x, w: (x.astype(jnp.float32)
                               * jax.lax.rsqrt(jnp.mean(
                                   jnp.square(x.astype(jnp.float32)), -1,
                                   keepdims=True) + 1e-6)
                               ).astype(x.dtype) * w,
                 argnums=(0, 1)))
    case("rope_halfsplit_8x1024x6x128", None, None)  # built below
    case("gelu_fwd_bwd_8192x3072",
         (s(8192, 3072),),
         fwd_bwd(jax.nn.gelu))
    case("silu_mul_8x1024x11008",
         (s(8, 1024, 11008), s(8, 1024, 11008)),
         lambda a, b: jax.nn.silu(a) * b)

    from paddle_tpu.models.llama import rope_apply

    def _rope(q, k):
        out = rope_apply(q, k, 10000.0)
        return tuple(o._value if hasattr(o, "_value") else o for o in out)

    cases["rope_halfsplit_8x1024x6x128"] = (
        (s(8, 1024, 6, 128), s(8, 1024, 6, 128)),
        _rope)

    # -- softmax / cross-entropy (ERNIE scores + llama lm head) --
    case("softmax_scores_96x512x512",
         (s(96, 512, 512, dtype=jnp.float32),),
         lambda x: jax.nn.softmax(x, axis=-1))
    case("cross_entropy_fwd_bwd_8192x32000", None, None)  # built below

    # index bounds must shrink WITH the indexed dim on CPU, or the
    # shrunken table clamps/drops most accesses and the row times a
    # degenerate access pattern
    vocab_s = max(32000 // scale, 1) if scale > 1 else 32000
    labels = jnp.asarray(
        rng.randint(0, vocab_s, (max(8192 // scale, 1),)), jnp.int32)

    def _ce(logits):
        lg = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
        return jnp.mean(lse - picked)

    cases["cross_entropy_fwd_bwd_8192x32000"] = (
        (s(8192, 32000),),
        lambda lg: jax.value_and_grad(_ce)(lg))

    # -- embedding lookup + grad scatter --
    ids = jnp.asarray(
        rng.randint(0, vocab_s, (max(8192 // scale, 1),)), jnp.int32)
    case("embedding_lookup_8192_v32000x768",
         (s(32000, 768),),
         lambda w: jnp.take(w, ids, axis=0))
    case("embedding_grad_scatter_8192_v32000x768",
         (s(8192, 768), s(32000, 768)),
         lambda g, w: jnp.zeros_like(w).at[ids].add(g))

    # -- reduce family --
    case("reduce_max_8192x32000",
         (s(8192, 32000, dtype=jnp.float32),),
         lambda x: x.max(axis=-1))
    case("reduce_mean_axis0_8192x768",
         (s(8192, 768, dtype=jnp.float32),),
         lambda x: x.mean(axis=0))
    case("argmax_8192x32000",
         (s(8192, 32000, dtype=jnp.float32),),
         lambda x: jnp.argmax(x, axis=-1))
    case("cumsum_8192x768",
         (s(8192, 768, dtype=jnp.float32),),
         lambda x: jnp.cumsum(x, axis=-1))

    # -- elementwise / HBM-bound --
    n64m = max(64 * 1024 * 1024 // (scale * scale), 1)
    case("add_64M", (s(n64m), s(n64m)), jnp.add)
    case("mul_add_64M", (s(n64m), s(n64m), s(n64m)),
         lambda a, b, c: a * b + c)
    case("cast_bf16_fp32_64M", (s(n64m),),
         lambda x: x.astype(jnp.float32))
    case("where_64M", (s(n64m), s(n64m)),
         lambda a, b: jnp.where(a > 0, a, b))

    # -- optimizer updates (the per-step elementwise tax; BASELINE.md
    #    measured AdamW at 5.25 ms/step on the 134M config) --
    n25m = max(25 * 1000 * 1000 // (scale * scale), 1)
    p32 = s(n25m, dtype=jnp.float32)

    def adamw(p, g, m, v):
        b1, b2, eps, lr, wd = 0.9, 0.999, 1e-8, 1e-3, 0.01
        m2 = b1 * m + (1 - b1) * g
        v2 = b2 * v + (1 - b2) * g * g
        return p - lr * (m2 / (jnp.sqrt(v2) + eps) + wd * p), m2, v2

    case("adamw_update_25M",
         (p32, s(n25m, dtype=jnp.float32), s(n25m, dtype=jnp.float32),
          s(n25m, dtype=jnp.float32)),
         adamw)
    case("sgd_momentum_update_25M",
         (p32, s(n25m, dtype=jnp.float32), s(n25m, dtype=jnp.float32)),
         lambda p, g, mom: (p - 1e-3 * (0.9 * mom + g),
                            0.9 * mom + g))
    case("global_norm_clip_25M",
         (s(n25m, dtype=jnp.float32),),
         lambda g: g * (1.0 / jnp.maximum(
             1.0, jnp.sqrt(jnp.sum(g * g)) / 1.0)))

    # -- gradient compression (distributed/compress.py quantized sync:
    #    the per-step host-side tax of the ~4x wire saving; sized like
    #    the optimizer rows — one full grad pass) --
    from paddle_tpu.kernels.quant import (dequantize_int8_block,
                                          quantize_int8_block)

    qrows = max(n25m // 4096, 1)
    qx = jnp.asarray(rng.randn(qrows, 4096), jnp.float32)
    case("quantize_int8_block_25M", (qx,),
         lambda x: quantize_int8_block(x))
    qq, qs = quantize_int8_block(qx)
    case("dequantize_int8_block_25M", (qq, qs),
         lambda q, sc: dequantize_int8_block(q, sc))
    # KV-page shape (serving quant-kv, FLAGS_serving_quant_kv): per-
    # (position, head) vector scales over head_dim — the write-time
    # quantize and the fused-gather dequantize the paged-attention
    # views pay, at a serving-sized pool slab [pages*bs, Hkv, D]
    from paddle_tpu.kernels.quant import quantize_int8_page

    kvp = s(8192, 8, 128, dtype=jnp.float32)
    case("quantize_int8_page_kv8M", (kvp,),
         lambda x: quantize_int8_page(x))
    kq, ks = quantize_int8_page(kvp)
    case("dequantize_int8_page_kv8M", (kq, ks),
         lambda q, sc: dequantize_int8_block(q, sc))

    # -- manipulation family --
    case("transpose_0213_8x12x512x64",
         (s(8, 12, 512, 64),),
         lambda x: jnp.transpose(x, (0, 2, 1, 3)))
    case("concat_2x_8192x768",
         (s(8192, 768), s(8192, 768)),
         lambda a, b: jnp.concatenate([a, b], axis=-1))
    case("gather_rows_8192_from_65536x768",
         (s(65536, 768),),
         lambda w: jnp.take(w, ids, axis=0))
    case("stack_4x_2048x768",
         (s(2048, 768), s(2048, 768), s(2048, 768), s(2048, 768)),
         lambda *xs: jnp.stack(xs))

    # -- attention extra shapes --
    case("flash_attention_7b_1x2048x32x128",
         (s(1, 2048, 32, 128), s(1, 2048, 32, 128),
          s(1, 2048, 32, 128)),
         lambda q, k, v: flash_attention(q, k, v, causal=True))
    case("attention_xla_8x512x12x64",
         (s(8, 512, 12, 64), s(8, 512, 12, 64), s(8, 512, 12, 64)),
         lambda q, k, v: jax.nn.softmax(
             jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / 8.0, axis=-1
         ).astype(q.dtype) @ jnp.swapaxes(v, 1, 2))
    return cases


def _time_case(args, body, iters=None, reps=3):
    """ms/iteration via on-device scan loop minus empty-body overhead."""
    import jax
    import jax.numpy as jnp

    if iters is None:
        iters = 30 if jax.default_backend() == "tpu" else 5

    def perturb(x, c):
        # chain iterations through the scalar carry so XLA cannot hoist
        # the loop-invariant body out of the scan: additive zero for
        # floats, xor with the (zero-valued but data-dependent) carry
        # truncation for ints. The zero must be cast to x.dtype FIRST:
        # `x + 0*c` with an f32 carry silently promotes bf16 inputs to
        # f32 and the row times the wrong kernel (review-found).
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x + (0 * c).astype(x.dtype)
        if jnp.issubdtype(x.dtype, jnp.integer):
            return x ^ c.astype(x.dtype)
        return x

    def loop(fn):
        @jax.jit
        def run_loop(a):
            def step(c, _):
                out = fn(*[perturb(x, c) for x in a])
                first = jax.tree_util.tree_leaves(out)[0]
                return jnp.sum(first.astype(jnp.float32)) * 1e-30, None

            c, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32), None,
                                length=iters)
            return c

        return run_loop

    run_loop = loop(body)
    s = run_loop(args)
    float(s)  # compile + settle
    best = 1e30
    for _ in range(reps):
        t0 = time.perf_counter()
        s = run_loop(args)
        float(s)
        best = min(best, time.perf_counter() - t0)
    return best / iters * 1000.0


def run_bench(out_path=None):
    import jax

    results = {"platform": jax.default_backend(), "ops": {}}
    cases = _cases()
    # measured empty-loop overhead to subtract
    import jax.numpy as jnp

    overhead = _time_case((jnp.zeros((8, 128)),), lambda x: x + 1.0,
                          iters=50)
    results["overhead_ms"] = round(overhead, 4)
    for name, (args, body) in sorted(cases.items()):
        try:
            ms = _time_case(args, body)
        except Exception as e:
            # a crashed case must not kill the whole sweep — it shows
            # up as an UNGUARDED/MISSING row in the coverage report
            # instead of silently erasing every case after it
            results.setdefault("failed", {})[name] = repr(e)[:300]
            print("%-36s FAILED: %r" % (name, e))
            continue
        results["ops"][name] = round(max(ms - overhead, 1e-4), 4)
        print("%-36s %8.3f ms" % (name, results["ops"][name]))
    if out_path:
        with open(out_path, "w") as f:
            json.dump(results, f, indent=1, sort_keys=True)
        print("wrote", out_path)
    return results


def check_result(current, baseline, tolerance=0.15):
    """Gate logic (reference check_op_benchmark_result.py): fail when an
    op is >tolerance slower than baseline ON THE SAME PLATFORM; report
    speedups informationally. Returns (ok, report_lines)."""
    lines = []
    ok = True
    if current.get("platform") != baseline.get("platform"):
        lines.append("SKIP: platform mismatch (%s vs baseline %s) — "
                     "baselines are per-platform"
                     % (current.get("platform"), baseline.get("platform")))
        return True, lines
    for name, base_ms in sorted(baseline.get("ops", {}).items()):
        cur_ms = current.get("ops", {}).get(name)
        if cur_ms is None:
            ok = False
            lines.append("MISSING %s (in baseline, not measured)" % name)
            continue
        ratio = cur_ms / base_ms if base_ms else float("inf")
        if ratio > 1.0 + tolerance:
            ok = False
            lines.append("REGRESSION %-36s %.3f -> %.3f ms (%.0f%%)"
                         % (name, base_ms, cur_ms, (ratio - 1) * 100))
        elif ratio < 1.0 - tolerance:
            lines.append("improved   %-36s %.3f -> %.3f ms" %
                         (name, base_ms, cur_ms))
    for name in sorted(set(current.get("ops", {})) -
                       set(baseline.get("ops", {}))):
        lines.append("new        %-36s %.3f ms"
                     % (name, current["ops"][name]))
    return ok, lines


def coverage_report(current_names, baseline, strict=False):
    """The anti-vacuous-pass report: which measured cases the baseline
    actually guards. Platform-independent (it compares NAMES — a
    platform-mismatched check skips the timing gate but must still
    scream about rows nobody guards anywhere). Returns
    (ok, unguarded_names, report_lines); ok is False only under
    ``strict`` with a non-empty unguarded list."""
    current_names = set(current_names)
    base_names = set(baseline.get("ops", {}))
    guarded = sorted(current_names & base_names)
    unguarded = sorted(current_names - base_names)
    lines = ["COVERAGE baseline guards %d of %d measured cases"
             % (len(guarded), len(current_names))]
    for name in unguarded:
        lines.append("UNGUARDED  %-36s (no baseline entry — the gate "
                     "passes vacuously)" % name)
    if unguarded:
        lines.append("%d unguarded row(s)%s"
                     % (len(unguarded),
                        " — FAILING (--strict-coverage)" if strict
                        else "; run `update` in an on-chip window or "
                             "pass --strict-coverage to enforce"))
    ok = not (strict and unguarded)
    return ok, unguarded, lines


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cmd", choices=["run", "check", "update"])
    ap.add_argument("--out")
    ap.add_argument("--baseline",
                    default=os.path.join(os.path.dirname(__file__),
                                         "op_bench_baseline.json"))
    ap.add_argument("--tolerance", type=float, default=0.15)
    ap.add_argument("--strict-coverage", action="store_true",
                    help="exit nonzero when any measured case has no "
                         "baseline entry (unguarded rows pass the "
                         "regression gate vacuously)")
    a = ap.parse_args(argv)
    if a.cmd == "run":
        cur = run_bench(a.out)
        # the sweep survives a crashed case (partial artifact beats
        # none), but the exit code stays loud about it
        if cur.get("failed"):
            print("%d case(s) FAILED: %s"
                  % (len(cur["failed"]), sorted(cur["failed"])))
            return 1
        return 0
    if a.cmd == "update":
        # measure FIRST, gate, then write: a mid-sweep crash (strict or
        # not — pre-resilient-sweep behavior was crash-before-write)
        # must not replace the committed baseline with a narrowed one
        # that every later non-strict check would pass vacuously
        cur = run_bench(None)
        all_names = set(cur.get("ops", {})) | set(cur.get("failed", {}))
        cov_ok, _, cov_lines = coverage_report(
            all_names, cur, strict=a.strict_coverage)
        print("\n".join(cov_lines))
        if cur.get("failed") or not cov_ok:
            print("baseline NOT written (%s): %s"
                  % ("case(s) crashed" if cur.get("failed")
                     else "coverage gate failed", a.baseline))
            return 1
        with open(a.baseline, "w") as f:
            json.dump(cur, f, indent=1, sort_keys=True)
        print("wrote", a.baseline)
        return 0
    cur = run_bench(None)
    with open(a.baseline) as f:
        base = json.load(f)
    ok, lines = check_result(cur, base, a.tolerance)
    print("\n".join(lines) or "all ops within tolerance")
    cov_ok, _, cov_lines = coverage_report(
        set(cur.get("ops", {})) | set(cur.get("failed", {})), base,
        strict=a.strict_coverage)
    print("\n".join(cov_lines))
    return 0 if (ok and cov_ok) else 1


if __name__ == "__main__":
    sys.exit(main())
