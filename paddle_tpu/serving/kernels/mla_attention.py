"""Absorbed latent-attention decode kernel (Pallas, TPU): ``mla_decode``.

Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434) caches ONE
row a token a layer, shared by every head: the normed compressed
key/value latent ``c_kv`` [rank] followed by the roped shared key
``k_pe`` [rope]. Decode never expands it into per-head keys and values.
The up-projections are absorbed into the query and the output instead:

    q_lat_h = [q_nope_h W_uk,h^T (rank) | q_pe_h (rope)]
    s_h     = scale * q_lat_h . row          (one dot over the whole row)
    o_lat_h = sum_t p_h,t row_t[:rank]       (the row's head IS the value)

so every head of a slot multiplies the same [tokens, rank + rope] block,
once for the scores and, its first ``rank`` columns, once for the
values. This kernel walks a slot's pages much as ``paged_decode`` does
(serving/kernels/paged_attention.py): the pool stays whole in HBM; the
grid takes the slots in order (q and the output a slot at a time, the
block table and the lengths scalar-prefetched); a slot loops
``cdiv(len, G * bs)`` times, a trip waits for its own group of G pages,
starts the async copies of the NEXT group (this slot's, or after its
last the next slot's first, so the pipe does not drain between slots)
into the other half of a VMEM double buffer, and folds its own into
float32 online-softmax statistics. Each latent page is DMA'd once and
used for both dots.

A trip is straight-line code: it starts ALL G copies, never a counted
few, and waits for them with one wait. Pages past a slot's length come
out of the trash-padded block table as page 0 and their rows are masked;
an idle slot runs one trip over nothing and emits exact zeros. With a
loop over the live pages and a branch a page, starting the copies (a
scalar-core job, ~35 ns a page of 20 KB) and multiplying ran one after
the other, 1.6 + 1.7 ms a call at the benchmark's sizes; without them
the compiler overlaps the two (measured on the chip, PR 34: 3.19 -> 2.15
ms a call).

At 128 heads a fetched token costs 128 x (576 + 512) x 2 FLOPs for
1,152 bytes, 242 FLOP/B against the v5e's 240: the kernel sits on the
ridge, not under the bandwidth roof as ``paged_decode`` does.

Layout contract (shared with serving/kv_cache.py, kind ``latent_pages``):
  q_lat        [S, H, W]       one absorbed query a slot
  pool         [NB, bs, W]     latent pages (page 0 is the trash page)
  block_tables [S, MB] int32   page ids per slot, trash-padded
  seq_lens     [S]     int32   valid history length per slot (0 = idle)
  ->           [S, H, rank]

W is rank + rope rounded up to whole 128-lane tiles (576 -> 640), the
columns past rank + rope zero in pool and query alike: the chip's memory
tiles a row that way whatever its declared width, and a page can only be
cut out of the pool along whole tiles, so the cache declares the padding
(serving/kv_cache.py) instead of leaving it to the layout. The padding
costs a ninth more bytes a token than the algorithm needs; the roofline
share counts the algorithm's.

Exact in interpret mode against ``mla_attention_reference``
(tests/test_serving.py), Mosaic-compiled at the published shapes in
tests/test_tpu_lowering.py, compared with the reference on live pools on
the chip by chip_smoke.py (serve_mla phase). The reference below is the
CPU engine path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...kernels.flash_attention import _dot, resolve_interpret
from .paged_attention import NEG_INF, _reference_on_tpu

# VMEM the kernel gives the two halves of its page buffer; the pages a
# loop trip handles follow from it (64 pages = 1024 tokens at 16 x 640
# bf16)
_PAGE_VMEM_BUDGET = 2560 * 1024


def _pages_per_group(block_size, width, itemsize, mb):
    """G, the pages one loop trip fetches and multiplies: the largest
    group whose two buffer halves fit ``_PAGE_VMEM_BUDGET``, capped at a
    slot's ``mb`` pages."""
    page_bytes = block_size * width * itemsize
    return int(max(1, min(mb, _PAGE_VMEM_BUDGET // (2 * page_bytes))))


def _mla_kernel(bt_ref, len_ref, q_ref, pool_hbm, o_ref, buf, sems,
                half_ref, m_scr, l_scr, acc_scr, *, scale):
    """One slot (grid step ``si``). q [1, H, W] and o [1, H, rank] in
    VMEM, the pool whole in HBM; ``buf`` [2, G, bs, W] the double
    buffer, ``sems`` one DMA semaphore a half, ``half_ref`` (SMEM) the
    half this slot's first group is in flight to: buffer, semaphores
    and it live across grid steps, which is what lets a slot's last trip
    start the next slot's first copies."""
    si = pl.program_id(0)
    slots = pl.num_programs(0)
    _, group, block_size, width = buf.shape
    rank = o_ref.shape[2]
    mb = bt_ref.shape[1]
    gt = group * block_size                 # tokens a trip

    def start_group(slot, g, half):
        # all G copies, no loop and no branch (module docstring); a
        # column past the table is clamped to its last, whose rows are
        # past the length like any other
        for i in range(group):
            page_id = bt_ref[slot, jnp.minimum(g * group + i, mb - 1)]
            pltpu.make_async_copy(pool_hbm.at[page_id], buf.at[half, i],
                                  sems.at[half]).start()

    def wait_group(half):
        # one wait for the G copies' bytes together
        pltpu.make_async_copy(pool_hbm.at[pl.ds(0, group)], buf.at[half],
                              sems.at[half]).wait()

    @pl.when(si == 0)
    def _first():
        half_ref[0] = 0
        start_group(0, 0, 0)

    length = len_ref[si]
    n_groups = jnp.maximum(pl.cdiv(length, gt), 1)
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    q = q_ref[0]                                        # [H, W]

    def trip(g, half):
        more = g + 1 < n_groups
        wait_group(half)
        # after this slot's last group the next slot's first (the last
        # slot fetches its own first again; _drain waits for it)
        start_group(jnp.where(more, si, jnp.minimum(si + 1, slots - 1)),
                    jnp.where(more, g + 1, 0), 1 - half)
        rem = length - g * gt               # live tokens of this group
        rows = buf[half].reshape(gt, width)             # [T, W]
        s = _dot(q, rows, ((1,), (1,))) * scale         # [H, T]
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < rem, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        # a row past the length may hold anything a page once held:
        # exact zeros, or 0 * garbage goes into the sum
        values = rows[:, :rank]
        row = jax.lax.broadcasted_iota(jnp.int32, values.shape, 0)
        values = jnp.where(row < rem, values, jnp.zeros_like(values))
        acc_scr[...] = alpha * acc_scr[...] + _dot(
            p.astype(values.dtype), values, ((1,), (0,)))
        return 1 - half

    half = jax.lax.fori_loop(0, n_groups, trip, half_ref[0])
    half_ref[0] = half
    # an idle slot's one trip saw no live column: p was 1 everywhere
    # over zeroed values, so acc is already exact zeros
    o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(
        o_ref.dtype)

    @pl.when(si == slots - 1)
    def _drain():
        wait_group(half)


def mla_attention_kernel(q_lat, pool, block_tables, seq_lens, *, scale,
                         rank, interpret=None):
    """Pallas path. q_lat [S, H, W] -> [S, H, rank]; idle slots (len 0)
    emit 0."""
    block_size, width = pool.shape[1:]
    return _mla_decode(
        q_lat, pool, jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(seq_lens, jnp.int32), scale=float(scale),
        rank=int(rank), interpret=resolve_interpret(interpret),
        group=_pages_per_group(block_size, width, pool.dtype.itemsize,
                               block_tables.shape[1]))


# jitted so that a model's layers share ONE trace and one lowering of
# the kernel body, as _paged_decode's do
@functools.partial(jax.jit,
                   static_argnames=("scale", "rank", "interpret", "group"))
def _mla_decode(q_lat, pool, block_tables, seq_lens, *, scale, rank,
                interpret, group):
    s, h, w = q_lat.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s,),
        in_specs=[pl.BlockSpec((1, h, w), lambda i, bt, ln: (i, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, h, rank), lambda i, bt, ln: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, group) + pool.shape[1:], pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, rank), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, h, rank), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_decode",
    )(block_tables, seq_lens, q_lat, pool)


def mla_attention_reference(q_lat, pool, block_tables, seq_lens, *, scale,
                            rank):
    """jnp fallback: gather every slot's pages into a dense
    [S, MB * bs, W] context, float32 scores over the whole row, masked
    softmax, values from the row's first ``rank`` columns. Idle slots
    (len 0) have an all-masked row -> a uniform softmax over trash;
    their output is ignored on the host but stays finite."""
    s = q_lat.shape[0]
    _, block_size, width = pool.shape
    mb = block_tables.shape[1]
    bt = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(seq_lens, jnp.int32)
    ctx = pool[bt].reshape(s, mb * block_size, width)
    logits = jnp.einsum("shw,smw->shm", q_lat.astype(jnp.float32),
                        ctx.astype(jnp.float32)) * scale
    valid = (jnp.arange(mb * block_size)[None, None, :]
             < lens[:, None, None])
    probs = jax.nn.softmax(jnp.where(valid, logits, NEG_INF), axis=-1)
    return jnp.einsum("shm,smr->shr", probs.astype(ctx.dtype),
                      ctx[..., :rank])


def mla_attention(q_lat, pool, block_tables, seq_lens, *, scale, rank,
                  interpret=None):
    """Dispatch: the Pallas kernel on a TPU when the geometry is
    Mosaic-tileable (a page's tokens fill whole sublane tiles, its rows
    and the value columns whole lane tiles), the jnp gather reference
    otherwise (the CPU engine path; on a TPU it warns once)."""
    h = q_lat.shape[1]
    block_size, width = pool.shape[1:]
    sublanes = 8 * (4 // pool.dtype.itemsize)
    tileable = (rank % 128 == 0 and width % 128 == 0
                and block_size % sublanes == 0 and h % 8 == 0
                and q_lat.dtype == pool.dtype)
    if jax.default_backend() == "tpu":
        if tileable:
            return mla_attention_kernel(
                q_lat, pool, block_tables, seq_lens, scale=scale,
                rank=rank, interpret=interpret)
        _reference_on_tpu("mla_attention", q_lat, pool, None)
    return mla_attention_reference(q_lat, pool, block_tables, seq_lens,
                                   scale=scale, rank=rank)
