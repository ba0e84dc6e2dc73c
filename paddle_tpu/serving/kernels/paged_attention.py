"""Ragged paged-attention decode kernel (Pallas, TPU).

Serving decode is one query token per slot attending over that slot's
whole history, which lives scattered across fixed-size pool pages
(serving/kv_cache.py). The dense alternative — gather every slot's
pages into a contiguous [slots, max_len, heads, head_dim] context —
moves the entire KV history through HBM every step; at serving batch
sizes that gather IS the decode step. This kernel instead walks the
block table itself. The pools stay whole in HBM; one program takes the
slots in order and, per slot, loops ``cdiv(len, G * bs)`` times: a trip
reads G page ids from the scalar-prefetched block table, starts one
async copy per live page of K and of V into one half of a VMEM double
buffer (the next group — or the next slot's first — is in flight while
this one is multiplied), and folds the group into running online-softmax
statistics (same recurrence as kernels/flash_attention.py). Nothing past
a slot's length costs a grid step, a copy or a compare; an idle slot
runs no trip. G comes from the shapes alone: the most pages whose four
buffer halves fit ``_KV_VMEM_BUDGET`` (32 pages = 512 tokens for
16 x 8 x 128 bf16), capped at a slot's pages. Each K/V page is DMA'd
from the pool exactly once, 32 KB contiguous at a time.

Layout contract (shared with serving/kv_cache.py):
  q            [S, H, D]        one query token per slot
  k/v pools    [NB, bs, Hkv, D] page pools (page 0 is the trash page)
  block_tables [S, MB] int32    page ids per slot, trash-padded
  seq_lens     [S]     int32    valid history length per slot (0 = idle)

GQA (H > Hkv) is folded inside the kernel without moving a byte: a
fetched group [G*bs, Hkv, D] is read strided over its head axis, one kv
head (one PAIR of kv heads for a 16-bit pool, whose 32-bit words hold
two heads) at a time, and multiplied by that head's H/Hkv query rows,
so the pool never stores repeated heads and no page is transposed. The
dots take q, k and v as stored (bf16 on the MXU at native precision,
float32 under the framework's matmul precision); m, l and the
accumulator are float32.

MIXED MODE (serving tier 2, FLAGS_serving_chunked_prefill /
FLAGS_serving_prefix_cache): ``mixed_paged_attention`` generalizes the
decode kernel to ragged [S, C] rows — row s holds q_lens[s] new tokens
at absolute positions hist_lens[s]..hist_lens[s]+q_lens[s]-1, and the
causal rule becomes ``key position <= hist + chunk index``. A decode
row is the q_len == 1 case, a prefill chunk is 1 < q_len <= C, and the
prefix-cache suffix prefill is S == 1 with hist = cached tokens; the
compiled mixed step batches all of them in one call, which is exactly
the mixed prefill/decode batch the Ragged Paged Attention paper's
kernel is built for. It still walks grid (slot, page), one page a
program (see ``_mixed_kernel``).

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

from ...kernels.flash_attention import _dot, resolve_interpret
from ...kernels.quant import dequantize_int8_block

NEG_INF = -1e30
_STAT_LANES = 128
# VMEM the decode kernel gives its K and V double buffers (two halves
# each); the pages a loop trip handles follow from it (_pages_per_group)
_KV_VMEM_BUDGET = 4 * 1024 * 1024


def _pages_per_group(block_size, hkv, d, itemsize, mb):
    """G, the pages one loop trip fetches and multiplies: the largest
    group whose two K and two V halves of the double buffer fit
    ``_KV_VMEM_BUDGET``, capped at a slot's ``mb`` pages. From the
    page's bytes alone: 32 pages (512 tokens) for 16 x 8 x 128 bf16."""
    page_bytes = block_size * hkv * d * itemsize
    return int(max(1, min(mb, _KV_VMEM_BUDGET // (4 * page_bytes))))


def _heads_per_word(dtype, hkv):
    """Kv heads that share one 32-bit word of a page's tile: 2 for a
    16-bit pool (Mosaic packs two rows of the second-minor axis, here
    the head axis, into each sublane word), else 1."""
    return 2 if jnp.dtype(dtype).itemsize == 2 and hkv % 2 == 0 else 1


def _for_group_pages(planes, sems, bt_ref, si, g, half, n_pages, fn):
    """``fn(copy)`` for the copy of every live page of group ``g`` of
    slot ``si`` into half ``half`` of each plane's double buffer.
    ``planes`` is [(pool in HBM [NB, ...], buffer [2, G, ...])]: K, V
    and, for int8 pools, their scale planes, which ride the same page
    ids. ``n_pages`` is the slot's live page count: nothing past it is
    looked up in the block table, let alone fetched. Written against
    the block table only, so the mixed kernel can adopt it with its own
    ``n_pages``."""
    group = planes[0][1].shape[1]
    first = g * group

    def page(i, carry):
        page_id = bt_ref[si, first + i]
        for pi, (pool, buf) in enumerate(planes):
            fn(pltpu.make_async_copy(pool.at[page_id], buf.at[half, i],
                                     sems.at[pi, half]))
        return carry

    jax.lax.fori_loop(0, jnp.minimum(n_pages - first, group), page, 0)


def _unit_rows(buf, scale_buf, u, pack, valid=None):
    """Rows of kv heads ``u*pack .. u*pack+pack-1`` over the T = G*bs
    tokens of one fetched group, as [T*pack, D] with row ``t*pack + e``
    = token t of head ``u*pack + e``. ``buf`` [G, bs, Hkv, D] is one
    half of the double buffer, read as it was fetched: flattened to
    [T*Hkv, D] and strided over the head axis, never transposed. A
    16-bit pool is read through its 32-bit words, each holding a head
    PAIR, and the pair goes to the MXU still interleaved (the caller
    masks the other head's columns), so no head is ever unpacked on the
    VPU. ``scale_buf`` [G, bs, Hkv]: int8 pages, dequantized here.
    ``valid``: tokens at or past it read as exact zeros (a V row past
    the length would otherwise put 0 * garbage into the sum)."""
    g, bs, hkv, d = buf.shape
    t = g * bs
    flat = buf.reshape(t * hkv, d)
    if pack > 1:
        flat = flat.bitcast(jnp.uint32)                 # [T*Hkv/pack, D]
    x = flat[pl.ds(u, t, stride=hkv // pack), :]        # [T, D]
    if scale_buf is not None:
        x = dequantize_int8_block(
            x, scale_buf.reshape(t, hkv)[:, u], out_dtype=jnp.float32)
    if valid is not None:
        row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        x = jnp.where(row < valid, x, jnp.zeros_like(x))
    if pack > 1:
        x = pltpu.bitcast(x, buf.dtype)                 # [T*pack, D]
    return x


def _pa_kernel(bt_ref, len_ref, q_ref, *rest, scale, pack,
               quantized=False):
    """The whole call, one program. q/o [S, H, D] in VMEM; the pools
    (and int8 scale planes) whole in HBM; ``*_buf`` [2, G, bs, Hkv(, D)]
    double buffers; ``sems`` one DMA semaphore per (plane, half).

    Slots in order; a slot runs ``cdiv(len, G*bs)`` trips (an idle slot
    none, and emits exact zeros). A trip first starts the copies of the
    NEXT group -- this slot's, or after its last the next slot's first,
    so the pipe does not drain between slots -- then waits for its own
    and multiplies. m, l, acc are float32 loop carries; the dots take q,
    k, v in the pool's dtype (kernels/flash_attention._dot)."""
    if quantized:
        (k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref,
         k_buf, v_buf, ks_buf, vs_buf, sems) = rest
        planes = [(k_hbm, k_buf), (v_hbm, v_buf),
                  (ks_hbm, ks_buf), (vs_hbm, vs_buf)]
    else:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sems = rest
        ks_buf = vs_buf = None
        planes = [(k_hbm, k_buf), (v_hbm, v_buf)]
    slots, h, d = q_ref.shape
    _, group, block_size, hkv, _ = k_buf.shape
    gt = group * block_size                 # tokens a trip
    rep = h // hkv
    units = hkv // pack
    rows = pack * rep                       # q rows one unit serves
    p_dtype = jnp.float32 if quantized else v_buf.dtype

    def copies(si, g, half, fn):
        _for_group_pages(planes, sems, bt_ref, si, g, half,
                         pl.cdiv(len_ref[si], block_size), fn)

    def attend(q_units, half, rem, carry):
        """One group into the running softmax; its first ``rem`` tokens
        are live (all G*bs but in a slot's last group)."""
        m_prev, l_prev, acc = carry
        kb, vb, ksb, vsb = (None if b is None else b.at[half]
                            for b in (k_buf, v_buf, ks_buf, vs_buf))
        s = jnp.concatenate(
            [_dot(q_units[u], _unit_rows(kb, ksb, u, pack), ((1,), (1,)))
             for u in range(units)], axis=0) * scale    # [H, T*pack]
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = col < rem * pack
        if pack > 1:
            # column t*pack + e is head u*pack + e: a row keeps its own
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            keep = jnp.logical_and(keep,
                                   col % pack == (row // rep) % pack)
        s = jnp.where(keep, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        p = p.astype(p_dtype)
        upd = jnp.concatenate(
            [_dot(p[u * rows:(u + 1) * rows],
                  _unit_rows(vb, vsb, u, pack, rem), ((1,), (0,)))
             for u in range(units)], axis=0)            # [H, D]
        return m_new, l_new, alpha * acc + upd

    def slot(si, half):
        """``half`` holds (or will hold) this slot's first group;
        returns the half the next slot starts on."""
        length = len_ref[si]
        n_groups = pl.cdiv(length, gt)
        # the slot before started our first group unless it ran no trip
        prefetched = jnp.logical_and(
            si > 0, len_ref[jnp.maximum(si - 1, 0)] > 0)

        @pl.when(jnp.logical_and(n_groups > 0,
                                 jnp.logical_not(prefetched)))
        def _first():
            copies(si, 0, half, lambda c: c.start())

        # a unit's q rows, cut from the float32 copy: a 16-bit [H, D]
        # cannot be sliced at offsets inside its packed tile
        q = q_ref[si].astype(jnp.float32)
        q_units = [q[u * rows:(u + 1) * rows].astype(q_ref.dtype)
                   for u in range(units)]

        def trip(g, carry):
            m, l, acc, half = carry
            more = g + 1 < n_groups
            next_si = jnp.where(more, si, jnp.minimum(si + 1, slots - 1))
            next_g = jnp.where(more, g + 1, 0)

            @pl.when(jnp.logical_or(more, si + 1 < slots))
            def _next():
                copies(next_si, next_g, 1 - half, lambda c: c.start())

            copies(si, g, half, lambda c: c.wait())
            m, l, acc = attend(q_units, half, length - g * gt,
                               (m, l, acc))
            return m, l, acc, 1 - half

        m, l, acc, half = jax.lax.fori_loop(
            0, n_groups, trip,
            (jnp.full((h, 1), NEG_INF, jnp.float32),
             jnp.zeros((h, 1), jnp.float32),
             jnp.zeros((h, d), jnp.float32), half))
        o_ref[si] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        return half

    jax.lax.fori_loop(0, slots, slot, jnp.int32(0))


def paged_attention_kernel(q, k_pool, v_pool, block_tables, seq_lens,
                           scale=None, interpret=None, k_scale=None,
                           v_scale=None):
    """Pallas path. q [S, H, D] -> [S, H, D]; idle slots (len 0) emit 0.
    ``k_scale``/``v_scale`` [NB, bs, Hkv]: int8 pools, fused dequant."""
    h, d = q.shape[1:]
    block_size, hkv = k_pool.shape[1:3]
    if h % hkv:
        raise ValueError("paged_attention: %d heads not a multiple of "
                         "%d kv heads" % (h, hkv))
    return _paged_decode(
        q, k_pool, v_pool, jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(seq_lens, jnp.int32), k_scale, v_scale,
        scale=1.0 / math.sqrt(d) if scale is None else float(scale),
        interpret=resolve_interpret(interpret),
        group=_pages_per_group(block_size, hkv, d, k_pool.dtype.itemsize,
                               block_tables.shape[1]))


# jitted so that a model's layers share ONE trace and one lowering of
# the kernel body: traced per layer inside the engine's decode step it
# cost every start of a 12-layer engine tens of seconds
@functools.partial(jax.jit, static_argnames=("scale", "interpret", "group"))
def _paged_decode(q, k_pool, v_pool, block_tables, seq_lens, k_scale,
                  v_scale, *, scale, interpret, group):
    s, h, d = q.shape
    quantized = k_scale is not None
    pack = 1 if quantized else _heads_per_word(k_pool.dtype,
                                               k_pool.shape[2])
    whole = pl.BlockSpec((s, h, d), lambda i, bt, ln: (0, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    operands = [q, k_pool, v_pool]
    page_bufs = [pltpu.VMEM((2, group) + k_pool.shape[1:], k_pool.dtype),
                 pltpu.VMEM((2, group) + v_pool.shape[1:], v_pool.dtype)]
    if quantized:
        operands += [k_scale, v_scale]
        page_bufs += [
            pltpu.VMEM((2, group) + k_scale.shape[1:], k_scale.dtype),
            pltpu.VMEM((2, group) + v_scale.shape[1:], v_scale.dtype)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[whole] + [in_hbm] * (len(operands) - 1),
        out_specs=whole,
        scratch_shapes=page_bufs + [
            pltpu.SemaphoreType.DMA((len(page_bufs), 2))],
    )
    return pl.pallas_call(
        functools.partial(_pa_kernel, scale=scale, pack=pack,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode",
    )(block_tables, seq_lens, *operands)


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
    picked via the block table). This kernel still walks grid
    (slot, page), one page a program, pages past the row's length
    included: the decode kernel's page groups (``_for_group_pages``)
    are written for it to adopt, but it runs only under the chunked-
    prefill / prefix-cache flags, which no benchmark cell turns on, so
    nothing could show its before and after (PERF.md section 7). The
    ragged causal rule is
    ``key position <= hist + ci`` per chunk row ci — a decode row is the
    C == q_len == 1 degenerate case. Stats flatten the (H, C) query rows
    to H*C online-softmax rows; scratch m/l [H*C, 128], acc [H*C, D].
    ``quantized``: int8 k/v blocks + scale refs [1, bs, Hkv] on the same
    index map, dequantized here inside the gather, per the fused-
    dequant discipline (kernels/quant.py)."""
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
    masked_decode_attention; on a TPU it warns once). A page's head
    axis has to fill whole tiles for a page to be cut out of the pool
    and strided over: 1, 2 or 4 heads or a multiple of 8, and an even
    count in a 16-bit pool (two heads share a word).
    Quantized-pool tileability note: see mixed_paged_attention."""
    s, h, d = q.shape
    block_size = k_pool.shape[1]
    hkv = k_pool.shape[2]
    tileable = (d % 128 == 0 and block_size % 8 == 0 and h % 8 == 0
                and (hkv in (1, 2, 4) or hkv % 8 == 0)
                and (k_pool.dtype.itemsize != 2 or hkv % 2 == 0)
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
