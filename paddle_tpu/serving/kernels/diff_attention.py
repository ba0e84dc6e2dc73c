"""Differential attention for one decode row a slot (Pallas, TPU):
``diff_decode``.

Differential attention (Ye et al., "Differential Transformer",
arXiv:2410.05258; the SambaY decoder of Phi-4-mini-flash, arXiv:2507.06607)
pairs query heads (2h, 2h+1) and KV heads (2g, 2g+1); query pair h reads
KV pair g = h // (pairs / KV pairs)::

    A1 = softmax(q_2h k_2g^T * scale)      A2 = softmax(q_2h+1 k_2g+1^T * scale)
    o_h = A1 [v_2g | v_2g+1] - lam A2 [v_2g | v_2g+1]        (2 D wide)

The layer norms each o_h and projects; that is the model's. This kernel
is the two maps and their difference for ONE new row a slot, over the
rows a slot's block table names: a shared-pages layer's history or a
window layer's ring (serving/kv_cache.py), which are the same thing to it.

Layout: a page is ``[block_size, Hkv * D]``, every KV head of a token side
by side on the lanes, so KV pair g is the 2 D lanes at ``g * 2 D`` and its
V is exactly the value the pair's maps multiply. One program takes the
slots in order and walks each slot's pages a group at a time through a
VMEM double buffer (the structure of ``paged_decode``,
serving/kernels/paged_attention.py, whose page-copy helper it shares):
each K and V row of a slot is read from HBM ONCE for all four query heads
of its pair and both maps. The queries go to the MXU as one block
``[2 P, Hkv * D]``: row h of the first half is q_2h on the K lanes of its
pair and zero elsewhere, row P + h is q_2h+1 likewise, so one product
gives every score of both maps, and one product of the probabilities with
the V rows gives every row's values (each row keeps its own pair's lanes
at the end). The zeros cost MXU passes, not bytes: the decode step is
bound by the rows it reads.

Layout contract:
  q            [S, H, D]            one query row a slot, H = 2 P
  k/v pools    [NB, bs, Hkv * D]    pages (page 0 the trash page)
  block_tables [S, MB] int32        a slot's pages in order
  lens         [S] int32            rows of the slot to read (0 = idle)
  lam          float32 scalar
  ->           [S, P, 2 D] float32; an idle slot gives zeros

Exact in interpret mode against ``diff_decode_reference``
(tests/test_phi4flash.py); Mosaic-compiled at the published shapes in
tests/test_tpu_lowering.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...kernels.flash_attention import _dot, resolve_interpret
from .paged_attention import NEG_INF, _KV_VMEM_BUDGET, _for_group_pages

_F32 = jnp.float32


def diff_attention(q, k, v, mask, lam, scale):
    """The equations above in ``jax.numpy``, float32 statistics: q
    [B, R, H, D], k and v [B, T, Hkv, D], mask [B, R, T] (True where a
    row sees a key; every row sees at least one). -> [B, R, H / 2, 2 D]
    float32. The prefill's path and every reference of the kernel."""
    b, r, h, d = q.shape
    t, hkv = k.shape[1:3]
    kv_pairs = hkv // 2
    rep = h // hkv
    qf = q.astype(_F32).reshape(b, r, kv_pairs, rep, 2, d)
    kf = k.astype(_F32).reshape(b, t, kv_pairs, 2, d)
    vf = v.astype(_F32).reshape(b, t, kv_pairs, 2 * d)
    scores = jnp.einsum("brgied,btged->bgiert", qf, kf) * scale
    scores = jnp.where(mask[:, None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bgiert,btgv->brgiev", probs, vf)
    out = o[..., 0, :] - jnp.asarray(lam, _F32) * o[..., 1, :]
    return out.reshape(b, r, h // 2, 2 * d)


def diff_decode_reference(q, k_pool, v_pool, block_tables, lens, lam,
                          scale=None):
    """The kernel's contract in ``jax.numpy``: gather a slot's pages,
    mask the rows past ``lens``; the CPU engine path."""
    s, h, d = q.shape
    _, bs, width = k_pool.shape
    mb = block_tables.shape[1]
    bt = jnp.asarray(block_tables, jnp.int32)
    k = k_pool[bt].reshape(s, mb * bs, width // d, d)
    v = v_pool[bt].reshape(s, mb * bs, width // d, d)
    mask = (jnp.arange(mb * bs)[None, :]
            < jnp.asarray(lens, jnp.int32)[:, None])[:, None, :]
    out = diff_attention(q[:, None], k, v, mask, lam,
                         1.0 / math.sqrt(d) if scale is None else scale)
    return jnp.where((jnp.asarray(lens) > 0)[:, None, None], out[:, 0], 0.0)


def _pages_per_group(block_size, page_bytes, mb):
    """Pages a loop trip fetches: the most whose two K and two V halves
    fit ``_KV_VMEM_BUDGET``, in whole 128-row tiles of scores where that
    leaves any, capped at a slot's ``mb`` pages."""
    group = max(1, min(mb, _KV_VMEM_BUDGET // (4 * page_bytes)))
    tile = max(1, 128 // block_size)
    if group >= tile:
        group -= group % tile
    return int(group)


def _diff_kernel(bt_ref, len_ref, q_ref, lam_ref, k_hbm, v_hbm, o_ref,
                 k_buf, v_buf, sems, *, scale, rep):
    """The whole call, one program; q [S, Pp, 2D] float32 (query pairs
    padded to a whole sublane tile), o [S, Pp, 2D]; ``*_buf``
    [2, G, bs, W]."""
    slots, pp, wd = q_ref.shape
    _, group, block_size, width = k_buf.shape
    kv_pairs = width // wd
    gt = group * block_size
    planes = [(k_hbm, k_buf), (v_hbm, v_buf)]
    dtype = k_buf.dtype
    lam = lam_ref[0:1, :]                               # [1, 2D]

    def copies(si, g, half, fn):
        _for_group_pages(planes, sems, bt_ref, si, g, half,
                         pl.cdiv(len_ref[si], block_size), fn)

    def queries(si):
        """[2 Pp, W]: the first map's queries on their pair's K lanes,
        then the second's."""
        q = q_ref[si].astype(_F32)                      # [Pp, 2D]
        row = jax.lax.broadcasted_iota(jnp.int32, q.shape, 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, q.shape, 1)
        first = jnp.where(lane < wd // 2, q, 0.0)
        second = jnp.where(lane >= wd // 2, q, 0.0)
        blocks = []
        for g in range(kv_pairs):
            own = row // rep == g
            blocks.append(jnp.concatenate(
                [jnp.where(own, first, 0.0), jnp.where(own, second, 0.0)],
                axis=0))
        return jnp.concatenate(blocks, axis=1).astype(dtype)

    def attend(qbig, half, rem, carry):
        m_prev, l_prev, acc = carry
        kb = k_buf[half].reshape(gt, width)
        vb = v_buf[half].reshape(gt, width)
        s = _dot(qbig, kb, ((1,), (1,))) * scale          # [2Pp, T]
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < rem, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        # a row past the length reads as zeros: 0 x garbage is not 0
        tok = jax.lax.broadcasted_iota(jnp.int32, vb.shape, 0)
        vb = jnp.where(tok < rem, vb, jnp.zeros_like(vb))
        return (m_new, l_new,
                alpha * acc + _dot(p.astype(dtype), vb, ((1,), (0,))))

    def slot(si, half):
        length = len_ref[si]
        n_groups = pl.cdiv(length, gt)
        prefetched = jnp.logical_and(
            si > 0, len_ref[jnp.maximum(si - 1, 0)] > 0)

        @pl.when(jnp.logical_and(n_groups > 0,
                                 jnp.logical_not(prefetched)))
        def _first():
            copies(si, 0, half, lambda c: c.start())

        qbig = queries(si)

        def trip(g, carry):
            m, l, acc, half = carry
            more = g + 1 < n_groups
            next_si = jnp.where(more, si, jnp.minimum(si + 1, slots - 1))
            next_g = jnp.where(more, g + 1, 0)

            @pl.when(jnp.logical_or(more, si + 1 < slots))
            def _next():
                copies(next_si, next_g, 1 - half, lambda c: c.start())

            copies(si, g, half, lambda c: c.wait())
            m, l, acc = attend(qbig, half, length - g * gt, (m, l, acc))
            return m, l, acc, 1 - half

        m, l, acc, half = jax.lax.fori_loop(
            0, n_groups, trip,
            (jnp.full((2 * pp, 1), NEG_INF, _F32),
             jnp.zeros((2 * pp, 1), _F32),
             jnp.zeros((2 * pp, width), _F32), half))
        # each row keeps the values of its own pair
        row = jax.lax.broadcasted_iota(jnp.int32, (2 * pp, wd), 0)
        own = (row % pp) // rep
        vals = jnp.zeros((2 * pp, wd), _F32)
        for g in range(kv_pairs):
            vals = jnp.where(own == g, acc[:, g * wd:(g + 1) * wd], vals)
        vals = vals / jnp.maximum(l, 1e-30)
        o_ref[si] = vals[:pp] - lam * vals[pp:]
        return half

    jax.lax.fori_loop(0, slots, slot, jnp.int32(0))


# jitted so that a model's layers share ONE trace and one lowering of the
# kernel body, as _paged_decode's does
@functools.partial(jax.jit, static_argnames=("scale", "interpret",
                                             "group"))
def _diff_decode(q, k_pool, v_pool, block_tables, lens, lam, *, scale,
                 interpret, group):
    s, h, d = q.shape
    pairs = h // 2
    pp = -(-pairs // 8) * 8
    rep = pairs // (k_pool.shape[2] // (2 * d))
    qp = q.reshape(s, pairs, 2 * d)
    if pp != pairs:
        qp = jnp.pad(qp, ((0, 0), (0, pp - pairs), (0, 0)))
    lam_tile = jnp.full((8, 2 * d), lam, _F32)
    whole = pl.BlockSpec((s, pp, 2 * d), lambda i, bt, ln: (0, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[whole, pl.BlockSpec((8, 2 * d), lambda i, bt, ln: (0, 0)),
                  in_hbm, in_hbm],
        out_specs=whole,
        scratch_shapes=[
            pltpu.VMEM((2, group) + k_pool.shape[1:], k_pool.dtype),
            pltpu.VMEM((2, group) + v_pool.shape[1:], v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2))],
    )
    out = pl.pallas_call(
        functools.partial(_diff_kernel, scale=scale, rep=rep),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, pp, 2 * d), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="diff_decode",
    )(block_tables, lens, qp.astype(_F32), lam_tile, k_pool, v_pool)
    return out[:, :pairs]


def diff_decode_kernel(q, k_pool, v_pool, block_tables, lens, lam,
                       scale=None, interpret=None):
    """Pallas path. -> [S, H / 2, 2 D] float32."""
    d = q.shape[2]
    _, bs, width = k_pool.shape
    return _diff_decode(
        q, k_pool, v_pool, jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(lens, jnp.int32), jnp.asarray(lam, _F32),
        scale=1.0 / math.sqrt(d) if scale is None else float(scale),
        interpret=resolve_interpret(interpret),
        group=_pages_per_group(bs, bs * width * k_pool.dtype.itemsize,
                               block_tables.shape[1]))


def diff_decode(q, k_pool, v_pool, block_tables, lens, lam, scale=None,
                interpret=None):
    """Dispatch: the Pallas kernel on a TPU when a pair's values fill
    whole 128-lane tiles, the jnp twin otherwise (the CPU engine path;
    on a TPU it warns once)."""
    d = q.shape[2]
    if jax.default_backend() == "tpu":
        if (2 * d) % 128 == 0:
            return diff_decode_kernel(q, k_pool, v_pool, block_tables,
                                      lens, lam, scale, interpret)
        from ...monitor.registry import warn_once

        warn_once(
            "serving.diff_decode.reference_on_tpu",
            "paddle_tpu.serving: diff_decode takes the jnp reference on "
            "the TPU (a pair of %d-wide heads is not a whole lane tile); "
            "the Pallas kernel is NOT in this step" % d)
    return diff_decode_reference(q, k_pool, v_pool, block_tables, lens,
                                 lam, scale)


def diff_prefill(q, k, v, positions, lam, scale, window=None):
    """Rows of a prompt: q [B, R, H, D] at ``positions`` ([R] or
    [B, R]) over the prompt's fresh k, v [B, P, Hkv, D], each row seeing
    the keys at or before it (and, with a ``window``, the last
    ``window`` of them, its own counted). -> [B, R, H / 2, 2 D] float32.
    Every row of a prompt on a TPU: the two maps are two banded
    ``flash_attention`` calls over a 2 D-wide value (query pair h's map
    reads KV pair h // rep, repeated to the pairs' count); otherwise the
    ``jax.numpy`` form over an explicit mask."""
    b, r, h, d = q.shape
    p, hkv = k.shape[1:3]
    if jax.default_backend() == "tpu" and r == p:
        from ...kernels.flash_attention import flash_attention

        rep = h // hkv
        pairs = jnp.repeat(v.reshape(b, p, hkv // 2, 2 * d), rep, axis=2)
        a1, a2 = (flash_attention(
            q[:, :, e::2], jnp.repeat(k[:, :, e::2], rep, axis=2), pairs,
            causal=True, scale=scale, window=window).astype(_F32)
                  for e in (0, 1))
        return a1 - jnp.asarray(lam, _F32) * a2
    key = jnp.arange(p)
    pos = jnp.asarray(positions, jnp.int32)[..., None]
    mask = key <= pos
    if window is not None:
        mask = jnp.logical_and(mask, pos - key < window)
    return diff_attention(q, k, v, jnp.broadcast_to(mask, (b, r, p)), lam,
                          scale)
