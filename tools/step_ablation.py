"""Train-step time attribution for the flagship bench config.

Parity motivation: the reference records per-op numbers next to its model
numbers (/root/reference/tools/ci_op_benchmark.sh:1 + op_tester.cc); this
tool answers the model-level question those leave open — *where does the
non-MXU time in a train step go* — by timing the step's components in
isolation at the exact bench shapes (bench.py 134M config by default,
--config llama1b for the weight-dominated one).

Pre-ledger tool: each component is a jitted closure re-dispatched in
isolation and synced by a host scalar readback; ROADMAP S4 redoes the
attribution from the device trace of a training cell. Components
overlap deliberately (fwd is part of fwd+bwd); the table reports both raw
ms and the share of the full step, so the residual row ("other: XLA
fusion glue, layernorms, residual adds, weight update") is the step time
minus the big named pieces.

Usage: python tools/step_ablation.py [--config 134m|llama1b] [--out FILE]
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _watchdog(seconds=1500):
    def fire(signum, frame):
        sys.stderr.write("step_ablation watchdog: %ds, aborting\n" % seconds)
        os._exit(3)

    signal.signal(signal.SIGALRM, fire)
    signal.alarm(seconds)


def _time_ms(fn, sync, iters):
    """Median-free simple timing: warmup twice, time `iters` calls."""
    for _ in range(2):
        out = fn()
    sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    sync(out)
    return (time.perf_counter() - t0) * 1000.0 / iters


def _loop_time_ms(body, init, sync, inner, outer):
    """Per-iteration time of `body` amortized inside ONE jitted
    fori_loop call. Isolated per-call timing carries the host->device
    dispatch of every call, which swamps sub-ms components (the first
    134m ablation, on the set-up of 2026-07, measured
    attention at 4.47 ms/layer isolated vs ~0.75 ms in-step and went
    negative in the residual). The carry threads a data dependency so
    XLA cannot hoist the body out of the loop."""
    import jax

    looped = jax.jit(lambda c: jax.lax.fori_loop(0, inner, body, c))
    c = looped(init)
    sync(c)
    c = looped(init)
    sync(c)
    t0 = time.perf_counter()
    for _ in range(outer):
        c = looped(c)
    sync(c)
    return (time.perf_counter() - t0) * 1000.0 / (outer * inner)


class _Emitter:
    def __init__(self, out_path):
        self.rows = []
        self.out_path = out_path

    def __call__(self, name, ms, note=""):
        rec = {"component": name, "ms": round(ms, 2), "note": note}
        self.rows.append(rec)
        print(json.dumps(rec), flush=True)
        if self.out_path:
            # incremental write: a watchdog abort mid-run must not
            # erase the components already measured
            with open(self.out_path, "w") as f:
                json.dump({"rows": self.rows, "partial": True}, f, indent=1)


def _dispatch_floor(emit, iters):
    import jax
    import jax.numpy as jnp

    tiny = jnp.zeros((8, 128), jnp.float32)
    disp_jit = jax.jit(lambda x: x + 1.0)
    ms = _time_ms(lambda: disp_jit(tiny),
                  lambda o: float(o[0, 0]), max(iters, 20))
    emit("dispatch_floor_per_call", ms,
         "host->device dispatch overhead; included once in full_step")
    return ms


def _forward_only(emit, model, ids_val, inner, outer, note):
    import jax.numpy as jnp

    names, vals = model.functional_state()
    state = dict(zip(names, vals))

    def fwd_fn(idsv):
        from paddle_tpu.core.dispatch import no_grad
        from paddle_tpu.core.tensor import Tensor

        with model.bind_state(list(state), [state[n] for n in state]):
            with no_grad():
                out = model(Tensor(idsv))
        out = out[0] if isinstance(out, tuple) else out
        return out._value

    def fwd_body(i, idsv):
        out = fwd_fn(idsv)
        # impossible predicate threads a dependency on the FULL output
        # into the next iteration without changing the input
        bump = (jnp.sum(out.astype(jnp.float32))
                > jnp.float32(1e30)).astype(idsv.dtype)
        return idsv + bump

    ms = _loop_time_ms(
        fwd_body, ids_val,
        lambda c: float(jnp.sum(c.reshape(-1)[:2].astype(jnp.float32))),
        inner, outer)
    emit("forward_only", ms, note)
    return ms


def _opt_update_only(emit, step, opt, inner, outer,
                     name="adamw_update_only"):
    import jax.numpy as jnp

    tr = {n: step._tensors[n]._value for n in step._trainable_names}
    gr = {n: jnp.ones_like(v) * 1e-6 for n, v in tr.items()}
    ost = step._opt_state
    first = step._trainable_names[0]

    def opt_body(i, carry):
        trc, stc = carry
        newp, news = opt.functional_apply(trc, gr, stc, step=1)
        return newp, news

    ms = _loop_time_ms(
        opt_body, (tr, ost),
        lambda c: float(jnp.sum(
            c[0][first].reshape(-1)[:1].astype(jnp.float32))),
        inner, outer)
    emit(name, ms, "elementwise, HBM-bound")
    return ms


def run_llama(args):
    import numpy as np
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed import mesh as pmesh
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.parallel.engine import CompiledTrainStep

    on_tpu = jax.default_backend() != "cpu"
    iters = args.iters or (20 if on_tpu else 2)
    if not on_tpu:
        cfg = LlamaConfig.tiny(use_parallel=False)
        batch, seq = 2, 64
    elif args.config == "134m":
        cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                          intermediate_size=2048, num_hidden_layers=12,
                          num_attention_heads=6,
                          max_position_embeddings=2048,
                          use_parallel=False, dtype="bfloat16")
        batch, seq = 8, 1024
    else:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5632, num_hidden_layers=16,
                          num_attention_heads=16,
                          max_position_embeddings=2048,
                          use_parallel=False, dtype="bfloat16",
                          recompute=True)
        batch, seq = 8, 1024

    pmesh.build_hybrid_mesh(dp=1, devices=jax.devices()[:1])
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model.to(dtype="bfloat16")
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())

    def loss_fn(logits, labels):
        return F.cross_entropy(logits.reshape([-1, cfg.vocab_size]),
                               labels.reshape([-1]))

    step = CompiledTrainStep(model, loss_fn, opt)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    labels = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))

    emit = _Emitter(args.out)
    rows = emit.rows

    inner = 16 if on_tpu else 2
    outer = max(2, iters // 4)

    disp_ms = _dispatch_floor(emit, iters)

    # 1. full train step (fwd + bwd + AdamW update)
    full_ms = _time_ms(lambda: step(ids, labels), lambda o: float(o), iters)
    emit("full_step", full_ms, "fwd+bwd+opt, the bench.py number")

    fwd_ms = _forward_only(emit, model, ids._value, inner, outer,
                           "inference pass; bwd ~= full - fwd - opt")

    # 2. flash attention fwd+bwd at the model's exact attention shape
    heads = cfg.num_attention_heads
    hd = cfg.hidden_size // heads
    q = jnp.asarray(rng.randn(batch, seq, heads, hd), jnp.bfloat16)

    from paddle_tpu.kernels.flash_attention import flash_attention

    def attn_loss(q, k, v):
        o = flash_attention(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32))

    attn_grad = jax.grad(attn_loss, argnums=(0, 1, 2))

    def attn_body(i, qc):
        dq, dk, dv = attn_grad(qc, qc, qc)
        # thread ALL three grads into the carry or XLA dead-code-
        # eliminates the dk/dv kernel out of the measurement
        dsum = (dq + dk + dv).astype(qc.dtype)
        return qc + dsum * jnp.asarray(1e-30, qc.dtype)

    attn_ms = _loop_time_ms(attn_body, q,
                            lambda c: float(c[0, 0, 0, 0]), inner, outer)
    emit("attention_fwd_bwd_per_layer", attn_ms,
         "x%d layers = %.2f ms" % (cfg.num_hidden_layers,
                                   attn_ms * cfg.num_hidden_layers))

    # 3. CE loss + lm_head matmul fwd+bwd (the vocab-sized tail)
    h = jnp.asarray(rng.randn(batch, seq, cfg.hidden_size), jnp.bfloat16)
    w = jnp.asarray(rng.randn(cfg.hidden_size, cfg.vocab_size),
                    jnp.bfloat16)
    lbl = jnp.asarray(labels._value)

    def head_loss(h, w):
        logits = (h @ w).reshape(-1, cfg.vocab_size).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lbl.reshape(-1, 1),
                                   axis=-1)[:, 0]
        return jnp.mean(lse - gold)

    head_grad = jax.grad(head_loss, argnums=(0, 1))

    def head_body(i, hc):
        gh, gw = head_grad(hc, w)
        # gw (the [hidden, vocab] wgrad matmul) must feed the carry too,
        # or XLA removes the dominant backward matmul from the timing
        gw_tap = jnp.sum(gw.astype(jnp.float32)) * jnp.float32(1e-38)
        return (hc + gh.astype(hc.dtype) * jnp.asarray(1e-30, hc.dtype)
                + gw_tap.astype(hc.dtype))

    head_ms = _loop_time_ms(head_body, h,
                            lambda c: float(c[0, 0, 0]), inner, outer)
    emit("lm_head_plus_ce_fwd_bwd", head_ms, "vocab %d" % cfg.vocab_size)

    # 4. optimizer apply only (AdamW elementwise over all params)
    opt_ms = _opt_update_only(emit, step, opt, inner, outer)

    attn_total = attn_ms * cfg.num_hidden_layers
    resid = full_ms - disp_ms - attn_total - head_ms - opt_ms
    emit("residual_mlp_norms_rope_glue", resid,
         "full - dispatch - attention - head/CE - opt: MLP matmuls + "
         "RMSNorm + RoPE + residual adds + XLA glue; in-step fusion can "
         "make isolated component times differ from their in-step cost")
    summary = {"config": args.config, "backend": jax.default_backend(),
               "batch": batch, "seq": seq, "full_step_ms": round(full_ms, 2),
               "shares": {r["component"]: round(
                   (r["ms"] * (cfg.num_hidden_layers
                               if r["component"].endswith("per_layer")
                               else 1)) / full_ms, 3)
                   for r in rows if r["component"] != "full_step"}}
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f, indent=1)
    return 0


def run_resnet50(args):
    """ResNet-50 attribution (VERDICT r4 #1): where do the ~87% of the
    chip go at 2,124 img/s? Components: layout (NHWC vs NCHW end-to-end
    — the conv relayout tax), forward, momentum update, head; residual
    is conv backward + BN glue."""
    import numpy as np
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed import mesh as pmesh
    from paddle_tpu.parallel.engine import CompiledTrainStep
    from paddle_tpu.vision.models import resnet50

    on_tpu = jax.default_backend() != "cpu"
    iters = args.iters or (20 if on_tpu else 2)
    batch = 64 if on_tpu else 4
    size = 224 if on_tpu else 32
    pmesh.build_hybrid_mesh(dp=1, devices=jax.devices()[:1])
    emit = _Emitter(args.out)
    disp_ms = _dispatch_floor(emit, iters)

    rng = np.random.RandomState(0)
    y = paddle.to_tensor(rng.randint(0, 1000, (batch,)).astype(np.int32))

    def build(layout):
        paddle.seed(0)
        m = resnet50(num_classes=1000, data_format=layout)
        if on_tpu:
            m.to(dtype="bfloat16")
        o = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                      parameters=m.parameters())
        s = CompiledTrainStep(m, lambda lg, lb: F.cross_entropy(lg, lb), o)
        shape = ((batch, 3, size, size) if layout == "NCHW"
                 else (batch, size, size, 3))
        x = paddle.to_tensor(rng.rand(*shape).astype(np.float32) * 2 - 1)
        if on_tpu:
            x = x.astype("bfloat16")
        return m, o, s, x

    per_layout = {}
    for layout in ("NHWC", "NCHW"):
        m, o, s, x = build(layout)
        ms = _time_ms(lambda: s(x, y), lambda r: float(r), iters)
        per_layout[layout] = ms
        emit("full_step_%s" % layout.lower(), ms,
             "%.0f img/s" % (batch / ms * 1000.0))
    emit("layout_tax_nchw_minus_nhwc",
         per_layout["NCHW"] - per_layout["NHWC"],
         "relayout cost XLA inserts around NCHW convs")

    # components on the faster layout
    layout = min(per_layout, key=per_layout.get)
    model, opt, step, x = build(layout)
    full_ms = per_layout[layout]
    inner = 8 if on_tpu else 2
    outer = max(2, iters // 4)
    fwd_ms = _forward_only(emit, model, x._value, inner, outer,
                           "conv tower + head, inference pass")
    opt_ms = _opt_update_only(emit, step, opt, inner, outer,
                              "momentum_update_only")
    emit("residual_bwd_and_glue",
         full_ms - disp_ms - fwd_ms - opt_ms,
         "conv/BN backward + XLA glue (fwd is measured separately)")
    summary = {"config": "resnet50", "backend": jax.default_backend(),
               "batch": batch, "image_size": size, "layout": layout,
               "full_step_ms": round(full_ms, 2),
               "images_per_sec": round(batch / full_ms * 1000.0, 1),
               "per_layout_ms": {k: round(v, 2)
                                 for k, v in per_layout.items()}}
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"rows": emit.rows, "summary": summary}, f, indent=1)
    return 0


def run_ernie(args):
    """ERNIE-base attribution (VERDICT r4 #1): splits the 25%-MFU step
    into attention (12 heads x 64 head_dim, XLA path), the vocab-40000
    MLM head + CE, the dropout RNG tax (train-mode masks the llama
    config doesn't pay), embeddings, and the AdamW update."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed import mesh as pmesh
    from paddle_tpu.models.ernie import ErnieConfig, ErnieForPretraining
    from paddle_tpu.parallel.engine import CompiledTrainStep

    on_tpu = jax.default_backend() != "cpu"
    iters = args.iters or (20 if on_tpu else 2)
    if on_tpu:
        cfg = ErnieConfig.base(fuse_qkv=not args.no_fuse)
        batch, seq = 16, 512
    else:
        cfg = ErnieConfig.tiny()
        batch, seq = 2, 64
    pmesh.build_hybrid_mesh(dp=1, devices=jax.devices()[:1])
    emit = _Emitter(args.out)
    paddle.seed(0)
    model = ErnieForPretraining(cfg)
    if on_tpu:
        model.to(dtype="bfloat16")
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())

    def loss_fn(out, labels):
        mlm, _sop = out
        return F.cross_entropy(mlm.reshape([-1, cfg.vocab_size]),
                               labels.reshape([-1]))

    step = CompiledTrainStep(model, loss_fn, opt)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    labels = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))

    inner = 16 if on_tpu else 2
    outer = max(2, iters // 4)
    disp_ms = _dispatch_floor(emit, iters)
    full_ms = _time_ms(lambda: step(ids, labels), lambda o: float(o), iters)
    emit("full_step", full_ms,
         "%.0f tok/s, fuse_qkv=%s" % (batch * seq / full_ms * 1000.0,
                                      getattr(cfg, "fuse_qkv", False)))
    fwd_ms = _forward_only(emit, model, ids._value, inner, outer,
                           "train-mode forward incl. dropout masks")

    # attention fwd+bwd at the exact shape (12 x 64: XLA path, not the
    # 128-head-dim Pallas kernel)
    heads = cfg.num_attention_heads
    hd = cfg.hidden_size // heads
    q = jnp.asarray(rng.randn(batch, seq, heads, hd),
                    jnp.bfloat16 if on_tpu else jnp.float32)

    def attn_loss(q, k, v):
        o = F.scaled_dot_product_attention(
            paddle.Tensor(q), paddle.Tensor(k), paddle.Tensor(v),
            is_causal=False)
        o = o._value if hasattr(o, "_value") else o
        return jnp.sum(o.astype(jnp.float32))

    attn_grad = jax.grad(attn_loss, argnums=(0, 1, 2))

    def attn_body(i, qc):
        dq, dk, dv = attn_grad(qc, qc, qc)
        dsum = (dq + dk + dv).astype(qc.dtype)
        return qc + dsum * jnp.asarray(1e-30, qc.dtype)

    attn_ms = _loop_time_ms(attn_body, q,
                            lambda c: float(c[0, 0, 0, 0]), inner, outer)
    emit("attention_fwd_bwd_per_layer", attn_ms,
         "x%d layers = %.2f ms" % (cfg.num_hidden_layers,
                                   attn_ms * cfg.num_hidden_layers))

    # MLM head + CE (hidden -> vocab 40000)
    h = jnp.asarray(rng.randn(batch, seq, cfg.hidden_size),
                    jnp.bfloat16 if on_tpu else jnp.float32)
    w = jnp.asarray(rng.randn(cfg.hidden_size, cfg.vocab_size), h.dtype)
    lbl = jnp.asarray(labels._value)

    def head_loss(h, w):
        logits = (h @ w).reshape(-1, cfg.vocab_size).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lbl.reshape(-1, 1),
                                   axis=-1)[:, 0]
        return jnp.mean(lse - gold)

    head_grad = jax.grad(head_loss, argnums=(0, 1))

    def head_body(i, hc):
        gh, gw = head_grad(hc, w)
        gw_tap = jnp.sum(gw.astype(jnp.float32)) * jnp.float32(1e-38)
        return (hc + gh.astype(hc.dtype) * jnp.asarray(1e-30, hc.dtype)
                + gw_tap.astype(hc.dtype))

    head_ms = _loop_time_ms(head_body, h,
                            lambda c: float(c[0, 0, 0]), inner, outer)
    emit("mlm_head_plus_ce_fwd_bwd", head_ms, "vocab %d" % cfg.vocab_size)

    # dropout RNG tax: mask generation at the train-graph's shapes —
    # 2 masks/layer on [b, s, h] plus 1 on [b, s, ffn] worth of bits
    key0 = jax.random.PRNGKey(0)

    def drop_body(i, carry):
        key, acc = carry
        key, k1, k2 = jax.random.split(key, 3)
        m1 = jax.random.bernoulli(k1, 0.9, (batch, seq, cfg.hidden_size))
        m2 = jax.random.bernoulli(k2, 0.9, (batch, seq, cfg.hidden_size))
        acc = acc + jnp.sum(m1.astype(jnp.float32)) \
            + jnp.sum(m2.astype(jnp.float32))
        return key, acc

    drop_ms = _loop_time_ms(drop_body, (key0, jnp.float32(0)),
                            lambda c: float(c[1]), inner, outer)
    emit("dropout_masks_per_layer", drop_ms,
         "2 x [b,s,h] bernoulli; x%d layers = %.2f ms (llama pays 0)"
         % (cfg.num_hidden_layers, drop_ms * cfg.num_hidden_layers))

    opt_ms = _opt_update_only(emit, step, opt, inner, outer)
    attn_total = attn_ms * cfg.num_hidden_layers
    drop_total = drop_ms * cfg.num_hidden_layers
    emit("residual_ffn_ln_embed_glue",
         full_ms - disp_ms - attn_total - head_ms - drop_total - opt_ms,
         "ffn matmuls + layernorms + embeddings + XLA glue")
    summary = {"config": "ernie", "backend": jax.default_backend(),
               "batch": batch, "seq": seq,
               "fuse_qkv": bool(getattr(cfg, "fuse_qkv", False)),
               "full_step_ms": round(full_ms, 2),
               "tokens_per_sec": round(batch * seq / full_ms * 1000.0, 1)}
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"rows": emit.rows, "summary": summary}, f, indent=1)
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config",
                    choices=["134m", "llama1b", "resnet50", "ernie"],
                    default="134m")
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--no-fuse", action="store_true",
                    help="ernie: disable the fused qkv projection")
    args = ap.parse_args()
    _watchdog()
    if args.config == "resnet50":
        return run_resnet50(args)
    if args.config == "ernie":
        return run_ernie(args)
    return run_llama(args)


if __name__ == "__main__":
    sys.exit(main())
