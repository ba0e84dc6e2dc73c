#!/usr/bin/env python3
"""chip_smoke.py — does paddle_tpu still start on the chip?

Drives the two normal entry points once, on the TPU, at the full width
of the llama1b geometry (hidden 2048, 22 layers, 16 heads x 128, vocab
32000; random weights from a seed):

  device          what JAX sees; not a TPU -> exit 2, nothing else runs
  train           parallel.engine.CompiledTrainStep, bf16, 8 x 1024,
                  recompute, AdamW, one device: warm-up + 3 steps
  train_fused_ce  the same step under FLAGS_fused_lm_head_ce, 2 steps
  serve           serving.Engine, 8 greedy requests, split prefill/decode
  serve_mixed     serving.Engine under prefix cache + chunked prefill
  serve_mla       serving.Engine over a latent page cache: the tiny
                  DeepSeek-V2 preset through mla_decode
  serve_ssm       serving.Engine over slot state beside K/V pages: a small
                  Nemotron-H through ssm_decode, against the family's
                  plain reference
  train4          dp=2 x mp=2 on four chips (skipped below four)

Each phase checks what came out by the repo's own means — finite
falling loss, the Mosaic kernels present in the compiled step,
kernel-vs-reference agreement on the engine's live pools, greedy tokens
against the dense forward — and prints its name and outcome. Any failed
phase makes the exit code non-zero and withholds the result line. On
success the last stdout line is one JSON object:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

One process, no children, no network. The phase functions take the
geometry as an argument (tests/test_chip_smoke.py drives them on CPU at
a tiny size with ``on_chip=False``); only ``main()`` insists on the
chip, and there is no CPU configuration in this file.
"""
from __future__ import annotations

import collections
import faulthandler
import gc
import json
import sys
import time
import traceback

Geometry = collections.namedtuple("Geometry", [
    "hidden", "intermediate", "layers", "heads", "vocab", "max_pos",
    "dtype",
    "batch", "seq",                         # train
    "slots", "num_blocks", "block_size",    # serve
    "prompt_lens", "parity_request", "new_tokens",
    "shared_prefix", "suffix_lens",         # serve_mixed
])

# llama1b width (tools/serving_router.py PRESETS["llama1b"]).
# prompt_lens land in three prefill buckets — 64 (< 128: reference SDPA),
# 256 and 512 (flash) — and prompt_lens[parity_request] + 8 == 256, so
# the dense forward it is checked against runs the flash kernel too.
FULL = Geometry(
    hidden=2048, intermediate=5504, layers=22, heads=16, vocab=32000,
    max_pos=2048, dtype="bfloat16", batch=8, seq=1024,
    slots=8, num_blocks=512, block_size=16,
    prompt_lens=(48, 60, 200, 248, 250, 300, 380, 400), parity_request=3,
    new_tokens=32, shared_prefix=64, suffix_lens=(16, 40, 70, 100))

SEED = 0
LEARNING_RATE = 3e-4
PARITY_TOKENS = 8
# The whole run must end inside the driver's 1200 s; past this every
# thread's stack is dumped and the process exits non-zero.
DEADLINE_S = 1150

# Tolerances, all for bf16 (8 bits of mantissa: one rounding is 2^-8,
# about 4e-3 relative).
# Step-0 loss (~ln 32000 = 10.4), fused vs unfused tail and four chips vs
# one: the unfused tail rounds [tokens, vocab] logits to bf16 before the
# fp32 log-sum-exp, the fused kernel never leaves fp32, and four chips
# reduce in another order; the mean over 8192 tokens averages most of it.
LOSS_RTOL = 5e-3
# Paged kernel vs jnp reference on the same pool: the reference rounds
# the probabilities to bf16 before the PV product, the kernel keeps fp32
# statistics and rounds the output once.
ATTN_ATOL = 2e-2
ATTN_RTOL = 2e-2
# Engine greedy token vs the dense forward's logits at the same position:
# the token must be the dense argmax or within this fraction of the
# largest |logit| of it. Two attention implementations 22 layers deep in
# bf16 differ by a few roundings; the gap between the top two of 32000
# near-gaussian logits is about 5 % of the maximum, a wrong path is
# about 100 % away.
PARITY_FRAC = 2.0 ** -5


def log(msg):
    print("[smoke] " + msg, flush=True)


# -- shared builders ---------------------------------------------------------

def build_model(geom, use_parallel=False, recompute=False):
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(SEED)
    cfg = LlamaConfig(
        vocab_size=geom.vocab, hidden_size=geom.hidden,
        intermediate_size=geom.intermediate,
        num_hidden_layers=geom.layers, num_attention_heads=geom.heads,
        max_position_embeddings=geom.max_pos, use_parallel=use_parallel,
        dtype=geom.dtype, recompute=recompute)
    model = LlamaForCausalLM(cfg)
    model.to(dtype=geom.dtype)
    return model


def train_batch(geom):
    import numpy as np

    rng = np.random.RandomState(SEED)
    shape = (geom.batch, geom.seq)
    return (rng.randint(0, geom.vocab, shape).astype(np.int32),
            rng.randint(0, geom.vocab, shape).astype(np.int32))


def set_flags(values):
    """Set flags, return the previous values (for the finally)."""
    import paddle_tpu as paddle

    prev = paddle.get_flags(list(values))
    paddle.set_flags(values)
    return prev


def bytes_in_use(device):
    stats = device.memory_stats()
    return None if stats is None else stats.get("bytes_in_use")


def require_kernels(hlo_text, wanted, what):
    from paddle_tpu.analysis.graph.hlo import mosaic_kernels

    found = mosaic_kernels(hlo_text)
    log("  mosaic kernels in the compiled %s: %s"
        % (what, json.dumps(found, sort_keys=True)))
    missing = sorted(set(wanted) - set(found))
    if missing:
        raise AssertionError(
            "compiled %s lacks the Mosaic kernel(s) %s — a reference "
            "path ran in their place" % (what, missing))


def run_train_steps(step, batch, steps):
    """Warm-up + ``steps`` calls on one fixed batch, each ended with
    block_until_ready. losses[i] is the loss BEFORE update i+1, so
    losses[0] is the step-0 loss and losses[-1] has seen ``steps``
    updates."""
    import math

    import jax

    losses = []
    for i in range(steps + 1):
        t0 = time.time()
        loss = step(*batch)
        jax.block_until_ready(loss._value)
        losses.append(float(loss))
        log("  step %d: loss %.4f (%s%.1fs)"
            % (i, losses[-1], "warm-up, compile included, "
               if i == 0 else "", time.time() - t0))
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError("non-finite loss: %r" % (losses,))
    if not losses[-1] < losses[0]:
        raise AssertionError(
            "loss did not fall on a repeated batch: %r" % (losses,))
    return losses


def check_loss0(name, got, want):
    err = abs(got - want) / abs(want)
    log("  step-0 loss %.4f vs %s %.4f: rel diff %.2e (tol %.0e)"
        % (got, name, want, err, LOSS_RTOL))
    if err > LOSS_RTOL:
        raise AssertionError(
            "step-0 loss %.5f differs from %s %.5f by %.2e > %.0e"
            % (got, name, want, err, LOSS_RTOL))


# -- phases ------------------------------------------------------------------

def phase_device():
    """What JAX sees. Returns the device dict of the result line."""
    from importlib import metadata

    import jax
    import jaxlib

    from paddle_tpu.core import compile_cache, native

    cache_dir = compile_cache.configure()
    native.get_lib()
    devs = jax.devices()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    log("  platform %s, device_kind %r, device count %d"
        % (devs[0].platform, devs[0].device_kind, len(devs)))
    log("  jax %s, jaxlib %s, libtpu %s, python %s"
        % (jax.__version__, jaxlib.__version__, libtpu,
           sys.version.split()[0]))
    log("  compile cache %s (%s), %d entries before this run"
        % (cache_dir,
           "placed by %s" % compile_cache.ENV_VAR
           if cache_dir != compile_cache.DEFAULT_DIR
           else "in-checkout default",
           len(compile_cache.entries(cache_dir))))
    log("  libpaddle_tpu_core.so built in this run: %s"
        % native.built_in_this_process())
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "cache_dir": cache_dir,
            "cache_before": compile_cache.entries(cache_dir)}


def phase_train(geom, devices, fused_ce=False, steps=3, ref_loss0=None,
                keep_init=False, on_chip=True):
    """CompiledTrainStep on exactly one device. Returns (losses, the
    initial weights on the host when ``keep_init``)."""
    import numpy as np
    from jax.sharding import Mesh

    import paddle_tpu as paddle
    from paddle_tpu.parallel.engine import CompiledTrainStep

    prev = set_flags({"FLAGS_fused_lm_head_ce": bool(fused_ce)})
    try:
        model = build_model(geom, recompute=True)
        init = None
        if keep_init:
            names, values = model.functional_state()
            init = {n: np.asarray(v) for n, v in zip(names, values)}
        opt = paddle.optimizer.AdamW(learning_rate=LEARNING_RATE,
                                     parameters=model.parameters())
        # the mesh is built here, from one device: get_mesh()'s default
        # spans every chip of the host
        mesh = Mesh(np.array(devices[:1]), ("dp",))
        step = CompiledTrainStep(model, None, opt, mesh=mesh,
                                 labels_to_model=True)
        batch = train_batch(geom)
        if on_chip:
            wanted = ["flash_fwd", "flash_dq", "flash_dkv"]
            if fused_ce:
                wanted += ["fused_ce_fwd", "fused_ce_dh", "fused_ce_dw"]
            require_kernels(step.lowered_hlo(*batch), wanted,
                            "train step")
        losses = run_train_steps(step, batch, steps)
    finally:
        paddle.set_flags(prev)
    if ref_loss0 is not None:
        check_loss0("unfused", losses[0], ref_loss0)
    return losses, init


def phase_train4(geom, devices, ref_loss0, init, steps=3, on_chip=True):
    """dp=2 x mp=2 over four devices with the mpu layers on."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.distributed import mesh as _mesh
    from paddle_tpu.parallel.engine import CompiledTrainStep

    prev_mesh = _mesh._global_mesh
    mesh = _mesh.build_hybrid_mesh(dp=2, mp=2, devices=devices[:4])
    try:
        model = build_model(geom, use_parallel=True, recompute=True)
        # the mpu layers draw their own initial values; start from the
        # one-chip phase's weights so the step-0 losses are comparable
        tensors = model.raw_state_tensors()
        for name, value in init.items():
            tensors[name]._value = jnp.asarray(value)
        opt = paddle.optimizer.AdamW(learning_rate=LEARNING_RATE,
                                     parameters=model.parameters())
        step = CompiledTrainStep(model, None, opt, mesh=mesh,
                                 labels_to_model=True)
        batch = train_batch(geom)
        if on_chip:
            require_kernels(step.lowered_hlo(*batch),
                            ["flash_fwd", "flash_dq", "flash_dkv"],
                            "four-chip train step")
        losses = run_train_steps(step, batch, steps)
        w = model.llama.layers[0].self_attn.q_proj.weight._value
        shards = w.addressable_shards
        shard_devices = sorted({s.device.id for s in shards})
        log("  q_proj.weight %s sharding %s: shard shape %s on devices %s"
            % (tuple(w.shape), w.sharding.spec,
               tuple(shards[0].data.shape), shard_devices))
        if "mp" not in jax.tree_util.tree_leaves(tuple(w.sharding.spec)):
            raise AssertionError(
                "q_proj.weight is not sharded over 'mp': %s"
                % (w.sharding.spec,))
        if shards[0].data.shape[1] * 2 != w.shape[1]:
            raise AssertionError("q_proj.weight columns are not halved")
        if len(shard_devices) != 4:
            raise AssertionError(
                "q_proj.weight lives on %d devices, not four"
                % len(shard_devices))
        in_use = [bytes_in_use(d) for d in devices[:4]]
        log("  bytes_in_use per device: %s" % in_use)
        if on_chip and not all(in_use):
            raise AssertionError(
                "a device of the mesh holds no memory: %r" % (in_use,))
    finally:
        _mesh.set_mesh(prev_mesh)
    check_loss0("one-chip", losses[0], ref_loss0)
    return losses


def compare_paged_kernel(eng, geom, mixed):
    """Mosaic kernel (the interpreter off-chip) vs the jnp reference on
    layer 0's live pool, block tables and lengths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.serving.kernels.paged_attention import (
        mixed_paged_attention_kernel,
        mixed_paged_attention_reference,
        paged_attention_kernel,
        paged_attention_reference,
    )

    pool = eng.cache.pools[0]
    bt = jnp.asarray(eng.cache.block_tables)
    lens = np.array(eng.cache.seq_lens)
    s, h, d = eng.max_slots, geom.heads, geom.hidden // geom.heads
    key = jax.random.PRNGKey(SEED + 7)
    if mixed:
        c = eng.prefill_chunk
        # the last min(C, len) positions of each live row, as a chunk
        # whose K/V the engine already wrote
        q_lens = np.minimum(lens, c)
        args = (bt, jnp.asarray(lens - q_lens), jnp.asarray(q_lens))
        q = jax.random.normal(key, (s, c, h, d), pool.k.dtype)
        got = mixed_paged_attention_kernel(q, pool.k, pool.v, *args)
        want = mixed_paged_attention_reference(q, pool.k, pool.v, *args)
        valid = (np.arange(c)[None, :] < q_lens[:, None])[:, :, None, None]
    else:
        q = jax.random.normal(key, (s, h, d), pool.k.dtype)
        got = paged_attention_kernel(q, pool.k, pool.v, bt,
                                     jnp.asarray(lens))
        want = paged_attention_reference(q, pool.k, pool.v, bt,
                                         jnp.asarray(lens))
        valid = (lens > 0)[:, None, None]
    check_kernel_output("mixed" if mixed else "decode", got, want, valid,
                        lens)


def check_kernel_output(name, got, want, valid, lens):
    """A paged kernel's output against its jnp reference where ``valid``
    (broadcastable to both): finite, and within ATTN_ATOL + ATTN_RTOL."""
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    excess = np.where(valid, np.abs(got - want)
                      - (ATTN_ATOL + ATTN_RTOL * np.abs(want)), -1.0)
    log("  %s kernel vs reference on live layer-0 pool (lens %s): max "
        "|diff| %.3e, max |ref| %.3e (atol %.0e + rtol %.0e)"
        % (name, lens.tolist(),
           float(np.where(valid, np.abs(got - want), 0).max()),
           float(np.where(valid, np.abs(want), 0).max()),
           ATTN_ATOL, ATTN_RTOL))
    if not np.isfinite(got[np.broadcast_to(valid, got.shape)]).all():
        raise AssertionError("%s kernel produced non-finite values" % name)
    if excess.max() > 0:
        raise AssertionError(
            "%s kernel disagrees with the reference by %.3e beyond "
            "tolerance" % (name, float(excess.max())))


def check_greedy_parity(model, prompt, generated):
    """The engine's first tokens against the dense (cache-free) forward
    of the same model on prompt + those tokens: each must be the dense
    argmax or within PARITY_FRAC of it (see the constant)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from jax.sharding import Mesh

    from paddle_tpu.core.dispatch import no_grad
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.distributed import mesh as _mesh

    toks = list(generated[:PARITY_TOKENS])
    ids = jnp.asarray([list(prompt) + toks], jnp.int32)
    names, values = model.functional_state()

    def dense(vals, ids):
        with model.bind_state(names, list(vals)), no_grad():
            return model(Tensor(ids))._value

    # one device, like the engine it is compared with
    with _mesh.scoped_mesh(Mesh(np.array(jax.devices()[:1]), ("dp",))):
        logits = np.asarray(jax.jit(dense)(values, ids)[0], np.float32)
    check_tokens(logits, len(prompt), toks, "dense forward")


def check_tokens(logits, p, toks, what):
    """``toks`` against ``logits`` [T, vocab] of prompt (``p`` tokens) +
    toks: each must be the argmax of its row or within PARITY_FRAC of
    it."""
    import numpy as np

    rows = logits[p - 1:p - 1 + len(toks)]
    tol = PARITY_FRAC * float(np.abs(rows).max())
    exact = int((rows.argmax(-1) == np.asarray(toks)).sum())
    gaps = rows.max(-1) - rows[np.arange(len(toks)), toks]
    log("  greedy parity vs %s: %d/%d tokens are its argmax; largest gap "
        "to it %.3e (tol %.3e = 2^-5 of max |logit|)"
        % (what, exact, len(toks), float(gaps.max()), tol))
    if gaps.max() > tol:
        raise AssertionError(
            "engine tokens %s are not the %s's greedy choice: "
            "gaps %s > %.3e" % (toks, what, gaps.tolist(), tol))


def phase_serve(geom, mixed=False, on_chip=True):
    """serving.Engine on device 0. ``mixed``: prefix cache + chunked
    prefill, i.e. the one mixed ragged step."""
    import numpy as np

    from paddle_tpu import serving
    from paddle_tpu.serving.scheduler import RequestState

    model = build_model(geom)
    model.eval()
    prev = set_flags({"FLAGS_serving_prefix_cache": mixed,
                      "FLAGS_serving_chunked_prefill": mixed})
    try:    # both flags are latched at construction
        eng = serving.Engine(model, max_slots=geom.slots,
                             num_blocks=geom.num_blocks,
                             block_size=geom.block_size)
    finally:
        set_flags(prev)
    rng = np.random.RandomState(SEED + 1)

    def tokens(n):
        return rng.randint(0, geom.vocab, n).tolist()

    if mixed:
        prefix = tokens(geom.shared_prefix)
        prompts = [prefix + tokens(n) for n in geom.suffix_lens]
        rids = [eng.add_request(prompts[0], geom.new_tokens)]
        # the first request alone until its prompt sits in the prefix
        # cache, so the other three can adopt the shared pages
        while eng.requests[rids[0]].state is not RequestState.DECODING:
            eng.step()
        rids += [eng.add_request(p, geom.new_tokens) for p in prompts[1:]]
    else:
        prompts = [tokens(n) for n in geom.prompt_lens]
        rids = [eng.add_request(p, geom.new_tokens) for p in prompts]

    compared = False
    while eng.has_work():
        eng.step()
        if not compared and all(
                eng.requests[r].state is RequestState.DECODING
                for r in rids):
            compare_paged_kernel(eng, geom, mixed)
            compared = True
    if not compared:
        raise AssertionError("never saw every request decoding at once")

    stats = eng.stats()
    states = [eng.request_status(r) for r in rids]
    log("  %d requests: %s; prefill_compiles %d, decode_compiles %d, "
        "decode_steps %d, output_tokens %d"
        % (len(rids), sorted({s["state"] for s in states}),
           stats["prefill_compiles"], stats["decode_compiles"],
           stats["decode_steps"], stats["output_tokens"]))
    for st in states:
        if st["state"] != "finished" \
                or st["output_tokens"] != geom.new_tokens:
            raise AssertionError("request did not finish: %r" % (st,))
    if stats["decode_compiles"] != 1:
        raise AssertionError(
            "decode_compiles == %d, not 1" % stats["decode_compiles"])
    if mixed:
        log("  prefix cache: %d of %d looked-up prompt tokens hit"
            % (stats["prefix_hit_tokens"], stats["prefix_lookup_tokens"]))
        if stats["prefix_hit_tokens"] < geom.shared_prefix:
            raise AssertionError("the shared prefix was never adopted")
    else:
        i = geom.parity_request
        check_greedy_parity(model, prompts[i], eng.output(rids[i]))
    if on_chip:    # after the stats: lowering traces once more
        require_kernels(eng.hot_step_hlo(),
                        ["paged_mixed" if mixed else "paged_decode"],
                        "mixed step" if mixed else "decode step")


def phase_serve_mla(on_chip=True, dtype="bfloat16"):
    """serving.Engine on device 0 over a latent page cache: the tiny
    DeepSeek-V2 preset (``DeepseekV2Config.tiny``: a latent of 128 + 16
    values a token for 8 heads, tileable as it stands), prefill over
    expanded heads, decode through ``mla_decode``; the kernel (the
    interpreter off-chip) against the jnp reference on layer 0's live
    pool, and the engine's tokens against the model's dense forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                               DeepseekV2ForCausalLM)
    from paddle_tpu.serving.kernels.mla_attention import (
        mla_attention_kernel,
        mla_attention_reference,
    )
    from paddle_tpu.serving.scheduler import RequestState

    paddle.seed(SEED)
    cfg = DeepseekV2Config.tiny(dtype=dtype)
    model = DeepseekV2ForCausalLM(cfg)
    model.eval()
    eng = serving.Engine(model, max_slots=4, num_blocks=64, block_size=16,
                         max_model_len=256)
    rng = np.random.RandomState(SEED + 2)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist()
               for n in (5, 40, 100)]         # a slot stays idle
    rids = [eng.add_request(p, 12) for p in prompts]
    compared = False
    while eng.has_work():
        eng.step()
        if not compared and all(
                eng.requests[r].state is RequestState.DECODING
                for r in rids):
            plane = eng.cache.pools[0].rows
            lens = np.array(eng.cache.seq_lens)
            q = jax.random.normal(
                jax.random.PRNGKey(SEED + 7),
                (eng.max_slots, cfg.num_attention_heads, plane.shape[2]),
                plane.dtype)
            args = (q, plane, jnp.asarray(eng.cache.block_tables),
                    jnp.asarray(lens))
            kw = dict(scale=0.1, rank=cfg.kv_lora_rank)
            got = np.asarray(mla_attention_kernel(*args, **kw), np.float32)
            live = (lens > 0)[:, None, None]
            check_kernel_output("mla_decode", got,
                                mla_attention_reference(*args, **kw), live,
                                lens)
            if got[lens == 0].any():
                raise AssertionError("mla_decode: an idle slot's output "
                                     "is not zero")
            compared = True
    if not compared:
        raise AssertionError("never saw every request decoding at once")
    stats = eng.stats()
    log("  %d requests; decode_compiles %d, decode_steps %d; latent %s"
        % (len(rids), stats["decode_compiles"], stats["decode_steps"],
           stats["latent"]))
    if stats["decode_compiles"] != 1:
        raise AssertionError(
            "decode_compiles == %d, not 1" % stats["decode_compiles"])
    check_greedy_parity(model, prompts[2], eng.output(rids[2]))
    if on_chip:    # after the stats: lowering traces once more
        require_kernels(eng.hot_step_hlo(), ["mla_decode", "moe_gmm"],
                        "latent decode step")


# a small Nemotron-H whose shapes the kernels tile as they stand: a
# Mamba-2 state of 8 groups x 64 x (2 heads x 64), attention heads of 128
SSM_CFG = dict(
    family="nemotron_h", vocab_size=512, hidden_size=256,
    hybrid_override_pattern="MEM*E", num_attention_heads=8,
    num_key_value_heads=2, head_dim=128, mamba_num_heads=16,
    mamba_head_dim=64, ssm_state_size=64, n_groups=8, conv_kernel=4,
    chunk_size=128, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=1e-4, n_routed_experts=4, n_routed_experts_published=8,
    num_experts_per_tok=3, moe_intermediate_size=256,
    moe_shared_expert_intermediate_size=512, routed_scaling_factor=2.5,
    norm_topk_prob=True, layer_norm_epsilon=1e-5,
    max_position_embeddings=512, tie_word_embeddings=False)


def phase_serve_ssm(on_chip=True, dtype="bfloat16"):
    """serving.Engine on device 0 over slot state, K/V pages and layers
    that keep nothing: a small Nemotron-H (``SSM_CFG``), prefill by the
    chunked Mamba-2 form, decode through ``ssm_decode``; the kernel (the
    interpreter off-chip) against its jnp twin on layer 0's live state,
    and the engine's tokens against the family's plain float32 reference
    (benchmark/families/nemotron_h.py)."""
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu import serving
    from paddle_tpu.serving.kernels.ssm import (ssm_decode_kernel,
                                                ssm_decode_reference)
    from paddle_tpu.serving.scheduler import RequestState

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "benchmark"))
    import run as bench

    family = bench.load_module("families", "nemotron_h")
    cfg = dict(SSM_CFG, torch_dtype=dtype)
    model = family.build_model(cfg, SEED, training=False)
    eng = serving.Engine(model, max_slots=4, num_blocks=64, block_size=16,
                         max_model_len=256)
    rng = np.random.RandomState(SEED + 3)
    prompts = [rng.randint(0, cfg["vocab_size"], n).tolist()
               for n in (5, 40, 100)]         # a slot stays idle
    rids = [eng.add_request(p, 12) for p in prompts]
    compared = False
    while eng.has_work():
        eng.step()
        if not compared and all(
                eng.requests[r].state is RequestState.DECODING
                for r in rids):
            state = eng.cache.pools[0]["state"] + 0     # a copy: the
            lens = np.array(eng.cache.seq_lens)         # pool is carried
            h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
            g, n = cfg["n_groups"], cfg["ssm_state_size"]
            keys = jax.random.split(jax.random.PRNGKey(SEED + 8), 4)
            slots = eng.max_slots
            args = (jax.random.normal(keys[0], (slots, h, p), state.dtype),
                    jax.nn.softplus(jax.random.normal(keys[1], (slots, h))),
                    -jnp.linspace(1.0, 16.0, h), jnp.ones((h,)),
                    jax.random.normal(keys[2], (slots, g, n)),
                    jax.random.normal(keys[3], (slots, g, n)),
                    jnp.asarray(lens > 0))
            want_y, want_state = ssm_decode_reference(*args, state)
            got_y, got_state = ssm_decode_kernel(*args, state)
            check_kernel_output("ssm_decode", got_y, want_y,
                                (lens > 0)[:, None, None], lens)
            check_kernel_output("ssm_decode (state)", got_state,
                                want_state, True, lens)
            if not np.array_equal(np.asarray(got_state)[lens == 0],
                                  np.asarray(state)[lens == 0]):
                raise AssertionError("ssm_decode: an idle slot's state "
                                     "changed")
            compared = True
    if not compared:
        raise AssertionError("never saw every request decoding at once")
    stats = eng.stats()
    log("  %d requests; decode_compiles %d, decode_steps %d; ssm %s"
        % (len(rids), stats["decode_compiles"], stats["decode_steps"],
           stats["ssm"]))
    if stats["decode_compiles"] != 1:
        raise AssertionError(
            "decode_compiles == %d, not 1" % stats["decode_compiles"])
    toks = eng.output(rids[2])[:PARITY_TOKENS]
    logits = np.asarray(family.reference_logits(
        family.weights_of(model), cfg, prompts[2] + toks), np.float32)
    check_tokens(logits, len(prompts[2]), toks, "family reference")
    if on_chip:    # after the stats: lowering traces once more
        require_kernels(eng.hot_step_hlo(),
                        ["ssm_decode", "paged_decode", "moe_gmm"],
                        "state-space decode step")


# -- driver ------------------------------------------------------------------

class Phases:
    """Runs phases in order, says each one's outcome, remembers failures.
    A failed phase does not stop the later ones (a chip call is too dear
    to learn one failure at a time) but it does decide the exit code."""

    def __init__(self):
        self.failed = []

    def run(self, name, fn, *args, **kwargs):
        import jax

        gc.collect()
        in_use = bytes_in_use(jax.devices()[0])
        log("%s: start%s" % (name, "" if in_use is None else
                             " (device 0 holds %.2f GB)" % (in_use / 1e9)))
        t0 = time.time()
        try:
            out = fn(*args, **kwargs)
        except Exception:
            traceback.print_exc()
            sys.stderr.flush()
            self.failed.append(name)
            log("%s: FAILED after %.0fs" % (name, time.time() - t0))
            return None
        log("%s: ok (%.0fs)" % (name, time.time() - t0))
        return out

    def blocked(self, name, why):
        self.failed.append(name)
        log("%s: FAILED (%s)" % (name, why))


def main():
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    t_start = time.time()
    log("device: start")
    device = phase_device()
    if device["platform"] != "tpu":
        log("device: FAILED — JAX's default platform is %r, not 'tpu'; "
            "this script only runs on the chip" % device["platform"])
        return 2
    log("device: ok")

    import jax

    from paddle_tpu.core import compile_cache

    devices = jax.devices()
    four = len(devices) >= 4
    phases = Phases()
    geom = FULL

    out = phases.run("train", phase_train, geom, devices, keep_init=four)
    losses, init = out if out else (None, None)
    if losses:
        phases.run("train_fused_ce", phase_train, geom, devices,
                   fused_ce=True, steps=2, ref_loss0=losses[0])
    else:
        phases.blocked("train_fused_ce", "needs the train phase's loss")
    phases.run("serve", phase_serve, geom)
    phases.run("serve_mixed", phase_serve, geom, mixed=True)
    phases.run("serve_mla", phase_serve_mla)
    phases.run("serve_ssm", phase_serve_ssm)
    if not four:
        log("train4: skipped (device_count=%d)" % len(devices))
    elif losses:
        phases.run("train4", phase_train4, geom, devices, losses[0], init)
    else:
        phases.blocked("train4", "needs the train phase's loss and weights")

    after = compile_cache.entries(device["cache_dir"])
    added = sorted(set(after) - set(device["cache_before"]))
    # entry names are "<program>-<key>-cache"
    log("compile cache %s: %d entries after this run, %d added%s"
        % (device["cache_dir"], len(after), len(added),
           ": " + ", ".join(sorted({n.rsplit("-", 2)[0] for n in added}))
           if added else ""))
    log("total %.0fs" % (time.time() - t_start))
    if phases.failed:
        log("FAILED phases: %s" % ", ".join(phases.failed))
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
