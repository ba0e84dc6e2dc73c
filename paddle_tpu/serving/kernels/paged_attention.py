"""Ragged paged-attention decode kernel (Pallas, TPU).

Serving decode is one query token per slot attending over that slot's
whole history, which lives scattered across fixed-size pool pages
(serving/kv_cache.py). The dense alternative — gather every slot's
pages into a contiguous [slots, max_len, heads, head_dim] context —
moves the entire KV history through HBM every step; at serving batch
sizes that gather IS the decode step. This kernel instead walks the
block table: grid (slot, page), the page id for (slot, j) read from the
scalar-prefetched block table by the BlockSpec index map, so each K/V
page is DMA'd from the pool exactly once and the running online-softmax
statistics stay in VMEM (same recurrence as kernels/flash_attention.py).

Layout contract (shared with serving/kv_cache.py):
  q            [S, H, D]        one query token per slot
  k/v pools    [NB, bs, Hkv, D] page pools (page 0 is the trash page)
  block_tables [S, MB] int32    page ids per slot, trash-padded
  seq_lens     [S]     int32    valid history length per slot (0 = idle)

GQA (H > Hkv) is folded inside the kernel: q reshapes to
[Hkv, H/Hkv, D] and both dots batch over the kv-head axis, so the pool
never stores repeated heads.

MIXED MODE (serving tier 2, FLAGS_serving_chunked_prefill /
FLAGS_serving_prefix_cache): ``mixed_paged_attention`` generalizes the
decode kernel to ragged [S, C] rows — row s holds q_lens[s] new tokens
at absolute positions hist_lens[s]..hist_lens[s]+q_lens[s]-1, and the
causal rule becomes ``key position <= hist + chunk index``. A decode
row is the q_len == 1 case, a prefill chunk is 1 < q_len <= C, and the
prefix-cache suffix prefill is S == 1 with hist = cached tokens; the
compiled mixed step batches all of them in one call, which is exactly
the mixed prefill/decode batch the Ragged Paged Attention paper's
kernel is built for.

Exact in interpret mode against masked_decode_attention
(tests/test_serving.py::TestPagedAttentionKernel); Mosaic-compiled for
the TPU in tests/test_tpu_lowering.py and compared against the jnp
reference on live pools by chip_smoke.py (serve / serve_mixed phases).
The jnp reference below is the CPU engine path and is bit-compatible
with the dense decode path generation.py uses.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...kernels.flash_attention import resolve_interpret
from ...kernels.quant import dequantize_int8_block

NEG_INF = -1e30
_STAT_LANES = 128


def _pa_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
               block_size, rep, scale, quantized=False):
    """One (slot, page) program. q [1, H, D]; k/v [1, bs, Hkv, D]
    (the page the index map picked via the block table); scratch
    m/l [H, 128], acc [H, D] — persisted across the page axis.
    ``quantized`` (FLAGS_serving_quant_kv): k/v blocks arrive int8 and
    two extra scale refs [1, bs, Hkv] ride the same block-table index
    map; dequant happens here, inside the gather, per the fused-dequant
    discipline (kernels/quant.py)."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
        ks_ref = vs_ref = None
    s_i = pl.program_id(0)
    j = pl.program_id(1)
    num_j = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[s_i]

    # pages at or past the slot's length hold no valid tokens: skip the
    # DMA'd block entirely (ragged early-out; idle slots skip all pages)
    @pl.when(j * block_size < length)
    def _compute():
        q = q_ref[0]                                  # [H, D]
        k = k_ref[0]                                  # [bs, Hkv, D]
        v = v_ref[0]
        if quantized:
            k = dequantize_int8_block(k, ks_ref[0], out_dtype=jnp.float32)
            v = dequantize_int8_block(v, vs_ref[0], out_dtype=jnp.float32)
        h, d = q.shape
        hkv = k.shape[1]
        qg = q.reshape(hkv, rep, d).astype(jnp.float32)
        kg = jnp.swapaxes(k, 0, 1).astype(jnp.float32)     # [Hkv, bs, D]
        s_blk = jax.lax.dot_general(
            qg, kg, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale    # [Hkv, rep, bs]
        s_blk = s_blk.reshape(h, block_size)
        pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (h, block_size), 1)
        s_blk = jnp.where(pos < length, s_blk, NEG_INF)
        m_prev = m_scr[...][:, :1]
        l_prev = l_scr[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=1, keepdims=True))
        p = jnp.exp(s_blk - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        vg = jnp.swapaxes(v, 0, 1).astype(jnp.float32)     # [Hkv, bs, D]
        upd = jax.lax.dot_general(
            p.reshape(hkv, rep, block_size), vg,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)            # [Hkv, rep, D]
        acc_scr[...] = alpha * acc_scr[...] + upd.reshape(h, d)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == num_j - 1)
    def _emit():
        l = l_scr[...][:, :1]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(
            o_ref.dtype)


def paged_attention_kernel(q, k_pool, v_pool, block_tables, seq_lens,
                           scale=None, interpret=None, k_scale=None,
                           v_scale=None):
    """Pallas path. q [S, H, D] -> [S, H, D]; idle slots (len 0) emit 0.
    ``k_scale``/``v_scale`` [NB, bs, Hkv]: int8 pools, fused dequant."""
    s, h, d = q.shape
    nb, block_size, hkv, _ = k_pool.shape
    mb = block_tables.shape[1]
    quantized = k_scale is not None
    if h % hkv:
        raise ValueError("paged_attention: %d heads not a multiple of "
                         "%d kv heads" % (h, hkv))
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    interpret = resolve_interpret(interpret)
    page_spec = pl.BlockSpec((1, block_size, hkv, d),
                             lambda si, j, bt, ln: (bt[si, j], 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, h, d), lambda si, j, bt, ln: (si, 0, 0)),
        page_spec, page_spec,
    ]
    operands = [q, k_pool, v_pool]
    if quantized:
        # scale planes ride the SAME block-table index map as the pages
        scale_spec = pl.BlockSpec((1, block_size, hkv),
                                  lambda si, j, bt, ln: (bt[si, j], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, d), lambda si, j, bt, ln: (si, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, _STAT_LANES), jnp.float32),
            pltpu.VMEM((h, _STAT_LANES), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_pa_kernel, block_size=block_size,
                          rep=h // hkv, scale=scale, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode",
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(seq_lens, jnp.int32), *operands)


def paged_attention_reference(q, k_pool, v_pool, block_tables, seq_lens,
                              scale=None, k_scale=None, v_scale=None):
    """jnp fallback: gather pages into a dense context, then the same
    fp32-statistics attention as nn.functional's _sdpa_reference — kept
    operation-for-operation compatible with the dense decode path so the
    serving engine's greedy tokens match GenerationMixin.generate.
    With scale planes the dequant sits right after the gather — XLA
    fuses the broadcast-multiply into the gather's consumer, so int8
    pages decompress 'for free' on the way into the einsum."""
    s, h, d = q.shape
    nb, block_size, hkv, _ = k_pool.shape
    mb = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    bt = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(seq_lens, jnp.int32)
    k = k_pool[bt].reshape(s, mb * block_size, hkv, d)
    v = v_pool[bt].reshape(s, mb * block_size, hkv, d)
    if k_scale is not None:
        k = dequantize_int8_block(
            k, k_scale[bt].reshape(s, mb * block_size, hkv),
            out_dtype=jnp.float32)
        v = dequantize_int8_block(
            v, v_scale[bt].reshape(s, mb * block_size, hkv),
            out_dtype=jnp.float32)
    if h != hkv:
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("shd,smhd->shm", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    valid = (jnp.arange(mb * block_size)[None, None, :]
             < lens[:, None, None])
    logits = jnp.where(valid, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    # idle slots (len 0) have an all-masked row -> uniform softmax over
    # trash; their output is ignored host-side but must stay finite
    out = jnp.einsum("shm,smhd->shd", probs.astype(v.dtype), v)
    return out


def _mixed_kernel(bt_ref, hist_ref, qlen_ref, q_ref, k_ref, v_ref, *rest,
                  block_size, rep, chunk, scale, quantized=False):
    """One (slot, page) program of the MIXED ragged step. q [1, C, H, D]
    (row s's chunk: q_len valid new tokens at absolute positions
    hist..hist+q_len-1); k/v [1, bs, Hkv, D] (the page the index map
    picked via the block table). The ragged causal rule is
    ``key position <= hist + ci`` per chunk row ci — a decode row is the
    C == q_len == 1 degenerate case. Stats flatten the (H, C) query rows
    to H*C online-softmax rows; scratch m/l [H*C, 128], acc [H*C, D].
    ``quantized``: int8 k/v blocks + scale refs [1, bs, Hkv] on the same
    index map, dequantized here inside the gather (_pa_kernel note)."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
        ks_ref = vs_ref = None
    s_i = pl.program_id(0)
    j = pl.program_id(1)
    num_j = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    hist = hist_ref[s_i]
    q_len = qlen_ref[s_i]

    # pages at or past hist + q_len hold nothing this row can see: skip
    # the DMA'd block (ragged early-out; idle rows q_len=0 skip every
    # page and emit exact zeros, same as the decode kernel)
    @pl.when(j * block_size < hist + q_len)
    def _compute():
        q = q_ref[0]                                  # [C, H, D]
        k = k_ref[0]                                  # [bs, Hkv, D]
        v = v_ref[0]
        if quantized:
            k = dequantize_int8_block(k, ks_ref[0], out_dtype=jnp.float32)
            v = dequantize_int8_block(v, vs_ref[0], out_dtype=jnp.float32)
        c, h, d = q.shape
        hkv = k.shape[1]
        # group for GQA: [C, H, D] -> [H, C, D] -> [Hkv, rep*C, D]
        qg = jnp.swapaxes(q, 0, 1).reshape(
            hkv, rep * c, d).astype(jnp.float32)
        kg = jnp.swapaxes(k, 0, 1).astype(jnp.float32)     # [Hkv, bs, D]
        s_blk = jax.lax.dot_general(
            qg, kg, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale    # [Hkv, rep*C, bs]
        s_blk = s_blk.reshape(h, c, block_size)
        kpos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (h, c, block_size), 2)
        qpos = hist + jax.lax.broadcasted_iota(
            jnp.int32, (h, c, block_size), 1)
        s_blk = jnp.where(kpos <= qpos, s_blk, NEG_INF)
        s_blk = s_blk.reshape(h * c, block_size)
        m_prev = m_scr[...][:, :1]
        l_prev = l_scr[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=1, keepdims=True))
        p = jnp.exp(s_blk - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        vg = jnp.swapaxes(v, 0, 1).astype(jnp.float32)     # [Hkv, bs, D]
        upd = jax.lax.dot_general(
            p.reshape(hkv, rep * c, block_size), vg,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)            # [Hkv, rep*C, D]
        acc_scr[...] = alpha * acc_scr[...] + upd.reshape(h * c, d)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == num_j - 1)
    def _emit():
        l = l_scr[...][:, :1]
        h = o_ref.shape[2]
        o = (acc_scr[...] / jnp.maximum(l, 1e-30)).reshape(
            h, chunk, o_ref.shape[3])
        o_ref[0] = jnp.swapaxes(o, 0, 1).astype(o_ref.dtype)


def mixed_paged_attention_kernel(q, k_pool, v_pool, block_tables,
                                 hist_lens, q_lens, scale=None,
                                 interpret=None, k_scale=None,
                                 v_scale=None):
    """Pallas path for the mixed step. q [S, C, H, D] -> [S, C, H, D];
    rows past q_len and idle rows emit unspecified-but-finite values the
    host ignores. ``k_scale``/``v_scale`` [NB, bs, Hkv]: int8 pools,
    fused dequant inside the gather."""
    s, c, h, d = q.shape
    nb, block_size, hkv, _ = k_pool.shape
    mb = block_tables.shape[1]
    quantized = k_scale is not None
    if h % hkv:
        raise ValueError("mixed_paged_attention: %d heads not a multiple"
                         " of %d kv heads" % (h, hkv))
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    interpret = resolve_interpret(interpret)
    page_spec = pl.BlockSpec((1, block_size, hkv, d),
                             lambda si, j, bt, hl, ql: (bt[si, j], 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, c, h, d),
                     lambda si, j, bt, hl, ql: (si, 0, 0, 0)),
        page_spec, page_spec,
    ]
    operands = [q, k_pool, v_pool]
    if quantized:
        scale_spec = pl.BlockSpec(
            (1, block_size, hkv),
            lambda si, j, bt, hl, ql: (bt[si, j], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, c, h, d), lambda si, j, bt, hl, ql: (si, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h * c, _STAT_LANES), jnp.float32),
            pltpu.VMEM((h * c, _STAT_LANES), jnp.float32),
            pltpu.VMEM((h * c, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mixed_kernel, block_size=block_size,
                          rep=h // hkv, chunk=c, scale=scale,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, c, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_mixed",
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(hist_lens, jnp.int32),
      jnp.asarray(q_lens, jnp.int32), *operands)


def mixed_paged_attention_reference(q, k_pool, v_pool, block_tables,
                                    hist_lens, q_lens, scale=None,
                                    k_scale=None, v_scale=None):
    """jnp fallback for the mixed ragged step (chunked prefill + prefix-
    cache suffix prefill + decode rows in ONE call): gather each row's
    pages into a dense context — which already contains the chunk's own
    freshly-scattered K/V — and apply the ragged causal mask
    ``key position <= hist + ci``. Same fp32-statistics discipline as
    paged_attention_reference (einsum -> NEG_INF mask -> softmax), so
    greedy outputs stay consistent with the dense paths."""
    s, c, h, d = q.shape
    nb, block_size, hkv, _ = k_pool.shape
    mb = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    bt = jnp.asarray(block_tables, jnp.int32)
    hist = jnp.asarray(hist_lens, jnp.int32)
    k = k_pool[bt].reshape(s, mb * block_size, hkv, d)
    v = v_pool[bt].reshape(s, mb * block_size, hkv, d)
    if k_scale is not None:
        k = dequantize_int8_block(
            k, k_scale[bt].reshape(s, mb * block_size, hkv),
            out_dtype=jnp.float32)
        v = dequantize_int8_block(
            v, v_scale[bt].reshape(s, mb * block_size, hkv),
            out_dtype=jnp.float32)
    if h != hkv:
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("schd,smhd->shcm", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qpos = hist[:, None] + jnp.arange(c)[None, :]          # [S, C]
    valid = (jnp.arange(mb * block_size)[None, None, :]
             <= qpos[:, :, None])                          # [S, C, M]
    logits = jnp.where(valid[:, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    # pad/idle rows see at least key position 0 (trash) -> finite
    out = jnp.einsum("shcm,smhd->schd", probs.astype(v.dtype), v)
    return out


def _reference_on_tpu(name, q, k_pool, k_scale):
    """Said once, by name: on a TPU the gather reference is a slow path
    (it materialises every row's history), never a silent default."""
    from ...monitor.registry import warn_once

    warn_once(
        "serving.%s.reference_on_tpu" % name,
        "paddle_tpu.serving: %s takes the jnp gather reference on the "
        "TPU (q %s, pool %s %s%s is not Mosaic-tileable); the Pallas "
        "kernel is NOT in this step"
        % (name, tuple(q.shape), tuple(k_pool.shape), k_pool.dtype,
           "" if k_scale is None else ", int8 scale planes"))


def mixed_paged_attention(q, k_pool, v_pool, block_tables, hist_lens,
                          q_lens, scale=None, interpret=None,
                          k_scale=None, v_scale=None):
    """Dispatch for the mixed ragged step: the Pallas kernel on TPU when
    the geometry is Mosaic-tileable, the jnp gather reference otherwise
    (CPU engine path and the parity-test oracle form; on a TPU it warns
    once). Quantized pools additionally need the scale block's lane dim
    (Hkv) tileable, so small-Hkv models take the reference (XLA still
    fuses the dequant into the gather) — ROADMAP S3."""
    s, c, h, d = q.shape
    block_size = k_pool.shape[1]
    hkv = k_pool.shape[2]
    tileable = (d % 128 == 0 and block_size % 8 == 0
                and (h * c) % 8 == 0
                and (k_scale is None or hkv % 128 == 0))
    if jax.default_backend() == "tpu":
        if tileable:
            return mixed_paged_attention_kernel(
                q, k_pool, v_pool, block_tables, hist_lens, q_lens,
                scale=scale, interpret=interpret, k_scale=k_scale,
                v_scale=v_scale)
        _reference_on_tpu("mixed_paged_attention", q, k_pool, k_scale)
    return mixed_paged_attention_reference(
        q, k_pool, v_pool, block_tables, hist_lens, q_lens, scale=scale,
        k_scale=k_scale, v_scale=v_scale)


def paged_attention(q, k_pool, v_pool, block_tables, seq_lens,
                    scale=None, interpret=None, k_scale=None,
                    v_scale=None):
    """Dispatch: the Pallas kernel on TPU when the page geometry is
    Mosaic-tileable, the jnp gather reference otherwise (CPU engine
    path, and the form the parity test pins against
    masked_decode_attention; on a TPU it warns once).
    Quantized-pool tileability note: see mixed_paged_attention."""
    s, h, d = q.shape
    block_size = k_pool.shape[1]
    hkv = k_pool.shape[2]
    tileable = (d % 128 == 0 and block_size % 8 == 0 and h % 8 == 0
                and (k_scale is None or hkv % 128 == 0))
    if jax.default_backend() == "tpu":
        if tileable:
            return paged_attention_kernel(
                q, k_pool, v_pool, block_tables, seq_lens, scale=scale,
                interpret=interpret, k_scale=k_scale, v_scale=v_scale)
        _reference_on_tpu("paged_attention", q, k_pool, k_scale)
    return paged_attention_reference(q, k_pool, v_pool, block_tables,
                                     seq_lens, scale=scale,
                                     k_scale=k_scale, v_scale=v_scale)
