"""Compile-level evidence for the Llama-7B hybrid-parallel north star.

BASELINE.json's "GPT/Llama-7B (TP+PP) tokens/sec/chip via Fleet" row
needs a v5p-64 pod; this environment has one v5e chip (or one host of
four). This tool produces the strongest artifact the environment
permits:

  1. AOT-compiles the REAL 7B training step — the same
     CompiledTrainStep / PipelinedTrainStep classes users run — over a
     virtual 64-device mesh (CPU backend, compile only, no execution)
     in two pod-shaped hybrid configs:
       A. tp8 x zero3-sharding8        (Megatron TP + full ZeRO-3)
       B. dp2 x sharding2 x tp8 x pp2  (TP+PP+DP hybrid, ZeRO-2 slots
          + reduce-scattered grads, per-layer remat, 1F1B ring)
  2. Records per-device memory from XLA's buffer assignment
     (compiled.memory_analysis(): argument/temp/peak bytes per device)
     and gates it against v5p per-chip HBM (95 GB).
  3. Counts the collectives XLA inserted (all-reduce for TP,
     reduce-scatter for ZeRO-2/3 grads, all-gather for ZeRO-3 params,
     collective-permute for the pp ring) as structural proof the
     sharding lowers to the intended communication pattern.
  4. Projects tokens/s/chip analytically from the measured sustained
     model-FLOPs throughput of this framework's largest on-chip run
     (953M at 99.3 TF/s, 50.4% MFU — a pre-ledger reading) — labeled
     a PROJECTION, not a measurement.

No real weights are materialized for the heavy configs: parameters are
built zero-initialized (jax.random patched for construction speed),
optimizer slots enter the lowering as ShapeDtypeStructs, and the eager
device placement is skipped — XLA sees exactly the avals + shardings it
would see on a real pod. CPU-backend caveat: buffer assignment (fusion,
temp sizes) differs from the TPU backend, so temp/peak rows are
indicative; the argument-bytes rows (params + optimizer state + batch
per device) are exact sharding math.

Usage:
  python tools/llama7b_plan.py           # full artifact -> llama7b_plan.json
  python tools/llama7b_plan.py --quick   # 4-layer smoke of the harness
  python tools/llama7b_plan.py --microbench  # on-chip 7B-shape layer bench
                                             # (needs the chip)
"""
from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
OUT = os.path.join(HERE, "llama7b_plan.json")
V5P_HBM_BYTES = 95e9
N_DEV = 64

_CHILD = "_LLAMA7B_PLAN_CHILD"


def reexec_cpu():
    """Child process with 64 virtual CPU devices."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=%d" % N_DEV
        ).strip()
    env[_CHILD] = "1"
    os.execvpe(sys.executable, [sys.executable] + sys.argv, env)


def _patch_fast_init():
    """Zero-init params: PRNG generation of 6.7B elements on one CPU
    core is minutes; numerics are irrelevant for compile analysis."""
    import jax
    import jax.numpy as jnp

    def zeros(key, shape=(), dtype=jnp.float32, **kw):
        return jnp.zeros(shape, dtype)

    jax.random.normal = zeros
    jax.random.uniform = zeros
    jax.random.truncated_normal = (
        lambda key, lower, upper, shape=(), dtype=jnp.float32: jnp.zeros(
            shape, dtype))


def _struct_of_tree(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda v: jax.ShapeDtypeStruct(jnp.shape(v), jnp.result_type(v)),
        tree)


def _collective_counts(hlo_text):
    out = {}
    for op in ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all"):
        # count op starts ("op-name" or "op-name-start"), not tuple refs
        out[op] = sum(hlo_text.count(" %s%s(" % (op, sfx))
                      + hlo_text.count(" = %s%s(" % (op, sfx))
                      for sfx in ("", "-start"))
        if out[op] == 0:
            out[op] = hlo_text.count("%s(" % op)
    return out


def _allreduce_feeds_dynamic_slice(text):
    """True when some dynamic-slice consumes (within two def-use hops
    through pass-through ops) the result of an all-reduce — the
    unfused reduce-scatter pattern."""
    import re

    producers = set()

    def consumes(args):
        # word-boundary match: %reshape.5 must not match %reshape.57
        return any(re.search(re.escape(p) + r"(?![\w.-])", args)
                   for p in producers)

    for m in re.finditer(
            r"(%[\w.-]+) = [^\n=]*\ball-reduce(?:-done)?\(", text):
        producers.add(m.group(1))
    for _ in range(2):  # follow pass-through ops a couple of hops
        grew = False
        for m in re.finditer(
                r"(%[\w.-]+) = [^\n=]*\b(?:get-tuple-element|reshape|"
                r"bitcast|copy|convert|transpose)\(([^)\n]*)\)", text):
            name, args = m.group(1), m.group(2)
            if name not in producers and consumes(args):
                producers.add(name)
                grew = True
        if not grew:
            break
    for m in re.finditer(r"dynamic-slice\(([^)\n]*)\)", text):
        if consumes(m.group(1)):
            return True
    # XLA fuses the slice: the consumer is then a `fusion(...)` whose
    # assigned name carries the fused op (e.g.
    # %dynamic-slice_transpose_fusion = fusion(%get-tuple-element...))
    for m in re.finditer(r"(%[\w.-]*slice[\w.-]*) = [^\n=]*\bfusion\("
                         r"([^)\n]*)\)", text):
        if consumes(m.group(2)):
            return True
    # Newer XLA CPU pipelines wrap partitioned bodies in call/fusion
    # ops (to_apply=/calls=%computation): a call consuming an
    # all-reduce result whose called computation TRANSITIVELY contains
    # a dynamic-slice is the same unfused reduce-scatter, one boundary
    # down.
    comps, cur, body = {}, None, []
    for line in text.splitlines():
        if cur is None:
            ms = re.match(r"\s*(?:ENTRY\s+)?(%[\w.-]+)\s*\([^\n]*\{\s*$",
                          line)
            if ms:
                cur, body = ms.group(1), []
        elif line.strip() == "}":
            comps[cur], cur = "\n".join(body), None
        else:
            body.append(line)
    refs = {n: set(re.findall(r"(?:to_apply|calls)=(%[\w.-]+)", b))
            for n, b in comps.items()}

    def has_ds(n, seen):
        if n in seen or n not in comps:
            return False
        seen.add(n)
        return ("dynamic-slice(" in comps[n]
                or any(has_ds(r, seen) for r in refs[n]))

    for m in re.finditer(r"= [^\n=]*\b(?:call|fusion)\(([^)\n]*)\)"
                         r"[^\n]*?(?:to_apply|calls)=(%[\w.-]+)", text):
        if consumes(m.group(1)) and has_ds(m.group(2), set()):
            return True
    return False


def _mem_row(compiled):
    from paddle_tpu.monitor import memory as ptmem

    ma = compiled.memory_analysis()
    row = {
        "argument_bytes_per_device": int(ma.argument_size_in_bytes),
        "output_bytes_per_device": int(ma.output_size_in_bytes),
        "temp_bytes_per_device": int(ma.temp_size_in_bytes),
        "alias_bytes_per_device": int(ma.alias_size_in_bytes),
    }
    # ONE peak number repo-wide (monitor/memory.py compiled_peak, the
    # same donation-aware executable_analysis the ledger/headroom math
    # and graph_report() cost rows consume): the real buffer-assignment
    # peak when jaxlib reports one, else args + temps + outputs net of
    # donation aliasing — an over-estimate (liveness overlap ignored),
    # flagged so hbm_fit readers don't mistake it for the scheduler's
    # real high-water mark.
    peak, is_estimate = ptmem.compiled_peak(compiled)
    if is_estimate:
        row["peak_is_upper_bound_estimate"] = True
    if peak is None:    # memory_analysis succeeded above, so this is
        peak = 0        # unreachable in practice — but never KeyError
    row["peak_bytes_per_device"] = int(peak)
    return row


def _model_and_sizes(cfg_kw, dtype="bfloat16"):
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(**cfg_kw)
    paddle.seed(0)
    t0 = time.monotonic()
    model = LlamaForCausalLM(cfg)
    model.to(dtype=dtype)
    n_params = sum(
        int(p.size) for _, p in model.named_parameters())
    print("model built: %.1fs, %d params (%.2fB)"
          % (time.monotonic() - t0, n_params, n_params / 1e9), flush=True)
    return cfg, model, n_params


def _abstract_opt(optimizer):
    """Route functional_init through ShapeDtypeStructs so slot zeros are
    never materialized (they only contribute avals to the lowering)."""
    import jax
    import jax.numpy as jnp

    def init(params_dict):
        return {
            name: [jax.ShapeDtypeStruct(jnp.shape(v), jnp.result_type(v))
                   for _ in optimizer._slots()]
            for name, v in params_dict.items()}

    optimizer.functional_init = init


def config_a(model, cfg, batch, seq):
    """tp8 x sharding8, ZeRO-3 via CompiledTrainStep."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed import mesh as pmesh
    from paddle_tpu.parallel.engine import CompiledTrainStep

    pmesh.build_hybrid_mesh(mp=8, sharding=8)

    class AOTStep(CompiledTrainStep):
        def _shard_params(self):
            pass  # 64-way eager placement on one host would replicate

    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    _abstract_opt(opt)

    def loss_fn(logits, labels):
        return F.cross_entropy(
            logits.reshape([-1, cfg.vocab_size]), labels.reshape([-1]))

    step = AOTStep(model, loss_fn, opt, zero_stage=3)
    step._build()
    state_structs = _struct_of_tree(
        [step._tensors[n]._value for n in step._names])
    batch_structs = (jax.ShapeDtypeStruct((batch, seq), jnp.int32),
                     jax.ShapeDtypeStruct((batch, seq), jnp.int32))
    t0 = time.monotonic()
    lowered = step._compiled.lower(
        state_structs, step._opt_state, step._ef_state,
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.float32), jax.random.key(0),
        batch_structs)
    print("A lowered: %.1fs" % (time.monotonic() - t0), flush=True)
    t0 = time.monotonic()
    compiled = lowered.compile()
    print("A compiled: %.1fs" % (time.monotonic() - t0), flush=True)
    return compiled


def config_b(model, cfg, batch, seq, n_micro):
    """dp2 x sharding2 x tp8 x pp2, ZeRO-2, remat, 1F1B ring."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed import mesh as pmesh
    from paddle_tpu.parallel import pipeline_parallel as pp_mod

    pmesh.build_hybrid_mesh(dp=2, mp=8, pp=2, sharding=2)

    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    _abstract_opt(opt)

    def loss_fn(logits, labels):
        return F.cross_entropy(
            logits.reshape([-1, cfg.vocab_size]), labels.reshape([-1]))

    # skip eager 64-way placement; jit in_shardings carry the layout
    real_put = jax.device_put
    jax.device_put = lambda x, *a, **k: x
    try:
        step = pp_mod.PipelinedTrainStep(
            model, loss_fn, opt, n_micro=n_micro, remat=True,
            zero_stage=2)
    finally:
        jax.device_put = real_put
    step._build()
    nb_structs = _struct_of_tree(
        [step.model.raw_state_tensors()[n]._value for n in step._nb_names])
    st_structs = _struct_of_tree(
        [step._stacked[s] for s in step.suffixes])
    batch_structs = (jax.ShapeDtypeStruct((batch, seq), jnp.int32),
                     jax.ShapeDtypeStruct((batch, seq), jnp.int32))
    t0 = time.monotonic()
    lowered = step._compiled.lower(
        nb_structs, st_structs, step._opt_state,
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.float32), jax.random.key(0),
        batch_structs)
    print("B lowered: %.1fs" % (time.monotonic() - t0), flush=True)
    t0 = time.monotonic()
    compiled = lowered.compile()
    print("B compiled: %.1fs" % (time.monotonic() - t0), flush=True)
    return compiled


def projection(n_params, seq, layers, hidden):
    """Tokens/s/chip projection from the measured sustained model-FLOPs
    throughput (NOT a measurement)."""
    # model FLOPs per token: 6N (fwd 2N + bwd 4N) + attention
    # 12*L*s*h per token (fwd+bwd of the s x s score/APV matmuls)
    attn = 12 * layers * seq * hidden
    flops_per_token = 6 * n_params + attn
    measured_tf = 99.3e12  # 953M run, pre-ledger, 50.4% MFU
    tok_chip = measured_tf / flops_per_token
    return {
        "method": "PROJECTION from measured 953M sustained throughput "
                  "(99.3 TF/s model FLOPs, 50.4% MFU on v5e; MFU rises "
                  "with model size so this is conservative for 7B)",
        "model_flops_per_token": flops_per_token,
        "assumed_sustained_model_tf_per_chip": 99.3,
        "projected_tokens_per_sec_per_chip": round(tok_chip, 1),
        "projected_tokens_per_sec_v5p64_pod": round(tok_chip * 64, 1),
        "is_measurement": False,
    }


def main():
    quick = "--quick" in sys.argv
    import jax

    assert jax.device_count() == N_DEV, jax.device_count()
    _patch_fast_init()

    layers = 4 if quick else 32
    seq = 512 if quick else 2048
    batch = 8 if quick else 16
    cfg_kw = dict(num_hidden_layers=layers,
                  max_position_embeddings=seq, use_parallel=True,
                  dtype="bfloat16", recompute=True,
                  fuse_attention_qkv=True, fuse_mlp=True)
    cfg, model, n_params = _model_and_sizes(cfg_kw)

    report = {
        "north_star": "BASELINE.json Llama-7B TP+PP hybrid tokens/s/chip",
        "generated_by": "tools/llama7b_plan.py",
        "quick": quick,
        "backend": "cpu (virtual %d-device mesh; compile-only)" % N_DEV,
        "caveat": "CPU-backend buffer assignment: argument bytes are "
                  "exact sharding math; temp/peak are indicative, the "
                  "TPU backend fuses differently",
        "model": {"hidden": cfg.hidden_size, "layers": layers,
                  "heads": cfg.num_attention_heads,
                  "ffn": cfg.intermediate_size,
                  "vocab": cfg.vocab_size, "seq": seq,
                  "batch_global": batch, "params": n_params,
                  "dtype": "bfloat16", "recompute": True},
        "configs": [],
    }

    # expected signatures: ZeRO-3's is the param all-gathers + TP
    # all-reduces (the grad combine's reduce-scatter-vs-AR choice is the
    # partitioner's on this backend); the pp hybrid must show the ring
    # collective-permutes and the ZeRO-2 AR->slice grad pattern
    for name, build, kw, expect in (
        ("tp8_zero3_sharding8", config_a, {},
         ["all-reduce", "all-gather"]),
        ("dp2_sharding2_tp8_pp2_zero2", config_b, {"n_micro": 4},
         ["all-reduce", "collective-permute", "reduce-scatter"]),
    ):
        t0 = time.monotonic()
        compiled = build(model, cfg, batch, seq, **kw)
        mem = _mem_row(compiled)
        text = compiled.as_text()
        colls = _collective_counts(text)

        def present(c):
            if colls.get(c, 0) > 0:
                return True
            # XLA's CPU SPMD pipeline lowers a reduce-scatter as
            # all-reduce + dynamic-slice when the combiner pass is off;
            # the TPU backend emits the fused op. Accept the pattern —
            # but only when a dynamic-slice actually CONSUMES an
            # all-reduce result (any dynamic-slice anywhere would make
            # the check vacuous: pp loops index with them constantly).
            if c == "reduce-scatter":
                return _allreduce_feeds_dynamic_slice(text)
            return False

        row = {
            "name": name,
            "memory": mem,
            "collectives": colls,
            "reduce_scatter_as_allreduce_plus_slice":
                colls.get("reduce-scatter", 0) == 0
                and _allreduce_feeds_dynamic_slice(text),
            "expected_collectives": expect,
            "expected_present": all(present(c) for c in expect),
            "hbm_fit": {
                "v5p_hbm_bytes": V5P_HBM_BYTES,
                "peak_fraction_of_v5p":
                    round(mem["peak_bytes_per_device"] / V5P_HBM_BYTES, 4),
                "fits": mem["peak_bytes_per_device"] < V5P_HBM_BYTES,
            },
            "wall_seconds": round(time.monotonic() - t0, 1),
        }
        report["configs"].append(row)
        print(json.dumps(row), flush=True)

    report["projection"] = projection(n_params, seq, layers,
                                      cfg.hidden_size)
    report["generated_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                           time.gmtime())
    out = OUT if not quick else OUT.replace(".json", "_quick.json")
    for a in sys.argv:  # --out=PATH: redirect (the live-gate test uses
        if a.startswith("--out="):  # a tmpdir, keeping the tree clean)
            out = a[len("--out="):]
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    print("wrote", out, flush=True)


def microbench():
    """On-chip microbench of 7B-shape components (one v5e chip):
    per-layer fwd+bwd at hidden 4096 / ffn 11008 and the lm_head+CE at
    vocab 32000. Refines the projection with measured 7B-shape
    numbers."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    assert jax.default_backend() != "cpu", "needs the TPU chip"
    sys.path.insert(0, REPO)
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    _patch_fast_init()
    # one decoder layer at exact 7B shapes (batch 1 x seq 2048 fits the
    # v5e 16GB easily; FLOPs/s at these K/N dims is what transfers)
    cfg = LlamaConfig(num_hidden_layers=1, max_position_embeddings=2048,
                      use_parallel=False, dtype="bfloat16",
                      fuse_attention_qkv=True, fuse_mlp=True)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    layer = model.llama.layers[0]
    sfx, vals = layer.functional_state()

    def layer_loss(vals_in, x):
        out = layer.functional_call(
            dict(zip(sfx, vals_in)), paddle.Tensor(x), state_names=sfx)
        return (out._value if hasattr(out, "_value") else out).astype(
            jnp.float32).sum()

    g = jax.jit(jax.grad(layer_loss, argnums=(0, 1)))
    x = jnp.zeros((1, 2048, 4096), jnp.bfloat16)
    r = g(list(vals), x)
    jax.tree_util.tree_map(
        lambda a: np.asarray(a[..., :1]) if hasattr(a, "shape") else a, r)
    iters = 20
    t0 = time.perf_counter()
    for _ in range(iters):
        r = g(list(vals), x)
    float(jnp.asarray(r[1]).astype(jnp.float32).sum())
    dt = (time.perf_counter() - t0) / iters
    n_layer_params = sum(int(np.prod(v.shape)) for v in vals)
    flops = 6 * n_layer_params * 2048 + 12 * 2048 * 4096 * 2048
    row = {"metric": "llama7b_layer_fwd_bwd_ms", "value": round(dt * 1e3, 2),
           "tokens": 2048, "layer_params": n_layer_params,
           "tf_per_s": round(flops / dt / 1e12, 1),
           "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                        time.gmtime())}
    print(json.dumps(row), flush=True)
    # fold into the committed plan if present
    try:
        with open(OUT) as f:
            rep = json.load(f)
        rep.setdefault("microbench", []).append(row)
        with open(OUT, "w") as f:
            json.dump(rep, f, indent=1)
            f.write("\n")
    except OSError:
        pass


if __name__ == "__main__":
    if "--microbench" in sys.argv:
        sys.path.insert(0, REPO)
        microbench()
    elif os.environ.get(_CHILD) != "1":
        reexec_cpu()
    else:
        sys.path.insert(0, REPO)
        main()
