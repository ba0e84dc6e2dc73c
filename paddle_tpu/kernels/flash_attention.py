"""Flash attention (Pallas, TPU).

Replaces the reference's fused attention CUDA ops
(/root/reference/paddle/fluid/operators/fused/fused_attention_op.cu and the
fmha wrappers): blocked online-softmax attention that never materializes the
[N, N] score matrix in HBM. The forward is a Pallas kernel with a
(batch*head, q_block, kv_block) grid: a grid step keeps one block of K/V
resident (the whole of it where VMEM allows, so a head's K/V are fetched
once) and walks it a score tile at a time with the running
max/denominator/accumulator held in VMEM scratch, so context length is
bounded by HBM, not VMEM. A causal call computes the tiles below the
diagonal without a mask, masks the ones it crosses, and neither computes
nor fetches the ones above. The forward picks its own tile from its shapes
(_fwd_tiles); the backward kernels keep 512 / 512. v may have a
head dim of its own (q and k [.., D], v and the output [.., Dv]: latent
attention's expanded heads are 192 wide in q/k and 128 in v); nothing is
padded. The backward is
also Pallas (FlashAttention-2-style): the forward saves the softmax
log-sum-exp, and two blocked kernels produce dq (q-major grid) and dk/dv
(kv-major grid) with fp32 VMEM accumulators — O(N) memory end to end; the
[N, N] score matrix never exists in either direction. Layout follows the
framework convention [B, N, H, D].

Causal semantics are start-aligned (query i attends to keys j <= i) in both
the kernel and the XLA fallback/VJP; causal cross-attention with
kv_len != q_len uses the same convention everywhere. A causal call may
name a ``window``: query i then sees keys i - window + 1 .. i (the token
itself counted), a banded mask; the forward neither computes nor fetches a
tile wholly left of the band, and a call without one compiles to what it
did before the option existed.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The backward kernels' tile, and the forward's when a caller names one:
# min()-clamped to the sequence length at call time.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30
_LOG2E = math.log2(math.e)
# The forward's own tile (_fwd_tiles), measured on a v5e in PR 35 at the
# shapes the benchmark's cells run (causal bf16, the kernel alone, ms a
# call at 512 x 512 a grid step -> as chosen, and its share of the
# chip's bf16 peak): 32 heads x 8192 rows, q/k 192, v 128 (a DeepSeek-V2
# prefill) 14.1 -> 6.6, 53 %; 64 x 4096, D 128 (a Mistral training step)
# 6.20 -> 2.70, 52 %; 16 x 8192, D 256 (Qwen3-Next) 6.54 -> 3.75, 74 %.
# The per-row statistics (m, l, the rescale of acc) cost a [bq, 128] pass
# each whatever the tile's width, so a 1024-wide kv tile halves them a
# score (512 x 1024: 8.1, 1024 x 1024: 7.2 at the first shape); 2048 wide
# loses more to the diagonal tile's masked half than it saves (8.0), and
# 256 wide runs at half the speed of 512 (18.5).
_FWD_BLOCK = 1024
# VMEM the forward lets its own estimate (_fwd_vmem_bytes) reach, and what
# it asks Mosaic for when the estimate passes half the compiler's default
# of 16 MiB (Mosaic's own temporaries are not in it); a v5e core has 128 MiB
_FWD_VMEM_BUDGET = 40 * 1024 * 1024
_FWD_VMEM_LIMIT = 64 * 1024 * 1024
_MOSAIC_VMEM_DEFAULT = 16 * 1024 * 1024


def _fwd_vmem_bytes(block_q, block_k, block_kv, d, dv, itemsize):
    """VMEM one forward program holds: q, the resident K/V block and both
    outputs double-buffered, the m / l / acc scratch (a [bq, 1] plane is
    tiled as 128 lanes), and a score tile with its exponentials in
    float32 and their cast for the second dot."""
    io = 2 * ((block_q * d + block_kv * (d + dv) + block_q * dv) * itemsize
              + block_q * 4)
    scratch = block_q * (2 * 128 + dv) * 4
    return io + scratch + block_q * block_k * (4 + 4 + itemsize)


def _fwd_tiles(n, kv_len, d, dv, itemsize, segmented=False, window=None):
    """(block_q, block_k, block_kv) of a forward call that names no tile,
    from its shapes alone. block_q x block_k is the score tile: 1024 a
    side where the sequence divides by it, else the backward's 512 (or
    the whole sequence), halved while the estimate passes
    ``_FWD_VMEM_BUDGET`` (float32 operands, wide heads). block_kv is the
    K/V block a grid step keeps resident and walks block_k rows a trip:
    the whole of K/V where the budget allows, so that a head's K/V are
    fetched once and not once a q block. A segmented call keeps
    block_kv = block_k (its kv segment ids are cut by the BlockSpec); so
    does a windowed one, at the backward's 512, so that a K/V block
    wholly left of a q block's band is never fetched."""
    def side(length, default):
        return (_FWD_BLOCK if length % _FWD_BLOCK == 0 and window is None
                else min(default, length))

    bq, bk = side(n, DEFAULT_BLOCK_Q), side(kv_len, DEFAULT_BLOCK_K)

    def fits(bq, bk, bkv):
        return _fwd_vmem_bytes(bq, bk, bkv, d, dv,
                               itemsize) <= _FWD_VMEM_BUDGET

    while not fits(bq, bk, bk) and bk % 256 == 0:
        bk //= 2
    while not fits(bq, bk, bk) and bq % 256 == 0:
        bq //= 2
    bkv = bk
    if not segmented and window is None:
        trips = kv_len // bk
        bkv = next((bk * t for t in range(trips, 1, -1)
                    if trips % t == 0 and fits(bq, bk, bk * t)), bk)
    return bq, bk, bkv


def resolve_interpret(interpret):
    """The one place a Pallas kernel decides between Mosaic and the
    interpreter: ``None`` means Mosaic on a TPU backend and the
    interpreter elsewhere (CPU tests). An explicit ``True`` on a TPU
    backend is refused — an interpreted kernel there would pass every
    check while hiding that the real kernel never ran."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError(
            "interpret=True on a TPU backend: the Pallas interpreter "
            "would stand in for the Mosaic kernel")
    return bool(interpret)


def _dot(a, b, dims, batch=((), ())):
    """fp32-accumulating dot. bf16 operands go to the MXU at native
    precision (DEFAULT — exact for bf16 inputs, 2x the fp32-upcast
    throughput); fp32 operands inherit the framework's global matmul
    precision (FLAGS_matmul_precision, default 'highest'), preserving the
    documented fp32 guarantee for fp32 callers."""
    # Both-bf16 pairs pin DEFAULT (native MXU bf16). A MIXED bf16/fp32
    # pair under the global 'highest' precision would hit Mosaic's "Bad
    # lhs type" on the bf16 side, so upcast the bf16 operand to fp32 —
    # never downcast the fp32 one, preserving its documented precision.
    if a.dtype == jnp.bfloat16 and b.dtype == jnp.bfloat16:
        prec = jax.lax.Precision.DEFAULT
    else:
        if a.dtype == jnp.bfloat16:
            a = a.astype(jnp.float32)
        if b.dtype == jnp.bfloat16:
            b = b.astype(jnp.float32)
        prec = None
    return jax.lax.dot_general(a, b, (dims, batch),
                               preferred_element_type=jnp.float32,
                               precision=prec)


def _fa_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, block_k,
               segmented, window=None):
    """One (bh, q_block, kv_block) program. Refs: q [1, bq, d];
    k [1, block_kv, d]; v [1, block_kv, dv]: the K/V block this grid step
    keeps resident, walked ``block_k`` rows a loop trip; optional
    segment-id refs sq [1, 1, bq], sk [1, 1, block_kv] (ragged/packed
    sequences: tokens attend only within their segment — the serving
    varlen path; block_kv == block_k there); o [1, bq, dv]; lse [1, bq]
    (softmax log-sum-exp, saved for the Pallas backward); scratch m/l
    [bq, 1], acc [bq, dv].

    A causal call runs two tile programs: the tiles wholly below the
    diagonal take no mask, the ones it crosses do; a tile above it is
    neither computed nor (the index maps of _flash_fwd_bhnd) fetched.
    The running max is kept over the raw products and ``scale`` folded
    with log2(e) into the one multiply before a base-2 exponential.

    With a ``window`` every tile a row can see is masked (the band's
    lower edge crosses tiles the diagonal does not), and the trips
    wholly left of the band are skipped like those above the diagonal.
    A row whose tile holds none of its keys adds exp(0) terms only while
    its running max is still the initial -1e30; its own key, which every
    row sees in a later trip, rescales them by exactly 0."""
    if segmented:
        sq_ref, sk_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    _, bq, d = q_ref.shape
    block_kv = k_ref.shape[1]
    trips = block_kv // block_k
    q_idx = pl.program_id(1)
    kv_i = pl.program_id(2)
    num_kv = pl.num_programs(2)
    # a scale that is not positive does not commute with the max: such a
    # call multiplies its products first, as the kernel always used to
    fold = scale > 0
    c = scale * _LOG2E if fold else _LOG2E

    @pl.when(kv_i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile(j, masked, r0=0, rows=bq, cols=block_k):
        """Rows r0 .. r0+rows of the q block against the first ``cols``
        rows of trip j of the resident block."""
        # bf16 operands straight into the MXU (fp32 accumulate): an fp32
        # upcast before the dot halves MXU throughput for statistics we
        # keep in fp32 anyway.
        rs = slice(r0, r0 + rows)
        ks = pl.ds(0 if trips == 1 else pl.multiple_of(j * block_k, block_k),
                   cols)
        v = v_ref[0, ks, :]
        s = _dot(q_ref[0, rs, :], k_ref[0, ks, :], ((1,), (1,)))
        if not fold:
            s = s * scale
        if masked:
            # start-aligned: query row sees key col iff row >= col
            row = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
            first = kv_i * block_kv + j * block_k - q_idx * bq - r0
            keep = row - col >= first
            if window is not None:
                keep = jnp.logical_and(keep, row - col < first + window)
            s = jnp.where(keep, s, NEG_INF)
        if segmented:
            s = jnp.where(
                sq_ref[0, 0, rs][:, None] == sk_ref[0, 0, ks][None, :],
                s, NEG_INF)
        m_prev = m_scr[rs]                              # [rows, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp2((s - m_new) * c)
        alpha = jnp.exp2((m_prev - m_new) * c)
        l_scr[rs] = alpha * l_scr[rs] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[rs] = alpha * acc_scr[rs] + _dot(p.astype(v.dtype), v,
                                                 ((1,), (0,)))
        m_scr[rs] = m_new

    def walk(lo, hi, body):
        jax.lax.fori_loop(lo, hi, lambda j, carry: (body(j), carry)[1], 0)

    if causal:
        # of this block's trips, the first `below` lie wholly below the
        # diagonal and the first `seen` hold a key some row can see
        q_lo = q_idx * bq - kv_i * block_kv
        below = jnp.minimum(jnp.maximum(q_lo + 1, 0) // block_k, trips)
        seen = jnp.minimum(
            jnp.maximum(q_lo + bq - 1 + block_k, 0) // block_k, trips)
        if window is not None:
            # the first trip that holds a key of the band of some row
            left = jnp.clip((q_lo - window + 1) // block_k, 0, trips)
            walk(left, seen, lambda j: tile(j, True))
    if causal and window is None:
        walk(0, below, lambda j: tile(j, False))

        def on_diagonal(j):
            if bq == block_k and bq % 256 == 0:
                # one tile on the diagonal, corner to corner: its upper
                # rows see only its left half
                tile(j, True, 0, bq // 2, bq // 2)
                tile(j, True, bq // 2, bq // 2)
            else:
                tile(j, True)

        walk(below, seen, on_diagonal)
    elif not causal:
        walk(0, trips, lambda j: tile(j, False))

    @pl.when(kv_i == num_kv - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        m = m_scr[...] * scale if fold else m_scr[...]
        lse_ref[0, 0] = (m + jnp.log(l))[:, 0]


# jitted so that a model's layers share ONE trace and one lowering of the
# kernel body: traced once a layer, its tile programs cost every start of
# a 12-layer engine 2.3 s a prefill bucket (measured on the chip, PR 35)
@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "block_q", "block_k", "interpret", "block_kv",
    "window"))
def _flash_fwd_bhnd(q, k, v, scale, causal, block_q, block_k, interpret,
                    segs=None, block_kv=None, window=None):
    """q,k: [BH, N, D], v: [BH, N, Dv] (heads folded into batch); segs:
    optional [BH, N] int32 segment ids (ragged/packed attention);
    block_kv: the K/V rows a grid step keeps resident (a multiple of
    block_k, block_k itself when not given); window: causal only, the
    keys a query sees counting itself.
    -> (out [BH, N, Dv], lse [BH, 1, N])."""
    bh, n, d = q.shape
    kv_len = k.shape[1]
    dv = v.shape[2]
    block_kv = block_kv or block_k
    grid = (bh, n // block_q, kv_len // block_kv)
    segmented = segs is not None
    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, block_k=block_k,
        segmented=segmented,
        **({} if window is None else {"window": window}))
    if causal and window is not None:
        # the blocks left of a q block's band are not fetched either:
        # they name the first block it can see, fetched once
        def kv_block(i, j):
            lo = jnp.maximum(i * block_q - window + 1, 0) // block_kv
            hi = (i * block_q + block_q - 1) // block_kv
            return jnp.clip(j, lo, hi)
    elif causal:
        # a block above the diagonal is not computed (the kernel's `seen`
        # is 0 there); naming the last block the q block can see in its
        # place keeps the pipeline from fetching it, since a block whose
        # index repeats is not fetched again
        def kv_block(i, j):
            return jnp.minimum(j, (i * block_q + block_q - 1) // block_kv)
    else:
        def kv_block(i, j):
            return j
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_kv, d),
                     lambda b, i, j: (b, kv_block(i, j), 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_kv, dv),
                     lambda b, i, j: (b, kv_block(i, j), 0),
                     memory_space=pltpu.VMEM),
    ]
    args = [q, k, v]
    if segmented:
        in_specs += [
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_kv),
                         lambda b, i, j: (b, 0, kv_block(i, j)),
                         memory_space=pltpu.VMEM),
        ]
        # [BH, 1, N]: same singleton-axis plane as lse (tiling rule)
        args += [segs[:, None, :]] * 2
    vmem = _fwd_vmem_bytes(block_q, block_k, block_kv, d, dv,
                           q.dtype.itemsize)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, n, dv), q.dtype),
            # lse as [bh, 1, n]: the singleton axis keeps the (1, block_q)
            # tail of the block equal-to-array-dim / lane-aligned (Mosaic
            # tiling rule)
            jax.ShapeDtypeStruct((bh, 1, n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=(_FWD_VMEM_LIMIT
                              if vmem > _MOSAIC_VMEM_DEFAULT // 2 else None)),
        interpret=interpret,
        name="flash_fwd",
    )(*args)


def _sees(q_pos, k_pos, window):
    """The causal mask, banded to ``window`` keys when one is named."""
    keep = q_pos >= k_pos
    if window is not None:
        keep = jnp.logical_and(keep, q_pos - k_pos < window)
    return keep


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, *rest,
               scale, causal, block_k, segmented, window=None):
    """dq pass: grid (bh, q_block, kv_block); dq accumulated in VMEM
    (v and do are [.., dv], q, k and dq [.., d]).
    ds = p * (dout.v^T - delta); dq = scale * ds @ k (FlashAttention-2
    backward, arXiv:2307.08691 alg. 4 — public algorithm, fresh code)."""
    if segmented:
        sq_ref, sk_ref, dq_ref, dq_scr = rest
    else:
        dq_ref, dq_scr = rest
    _, bq, d = q_ref.shape
    q_idx = pl.program_id(1)
    kv_i = pl.program_id(2)
    num_kv = pl.num_programs(2)

    @pl.when(kv_i == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0][:, None]                    # [bq, 1]
        delta = dl_ref[0, 0][:, None]
        s = _dot(q, k, ((1,), (1,))) * scale  # [bq, bk]
        if causal:
            q_pos = q_idx * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            k_pos = kv_i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(_sees(q_pos, k_pos, window), s, NEG_INF)
        if segmented:
            s = jnp.where(
                sq_ref[0, 0][:, None] == sk_ref[0, 0][None, :], s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = _dot(do, v, ((1,), (1,)))          # [bq, bk]
        ds = (p * (dp - delta)).astype(k.dtype)
        dq_scr[...] += scale * _dot(ds, k, ((1,), (0,)))

    if causal:
        @pl.when(kv_i * block_k <= q_idx * bq + bq - 1)
        def _run():
            compute()
    else:
        compute()

    @pl.when(kv_i == num_kv - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, *rest,
                scale, causal, block_q, segmented, window=None):
    """dk/dv pass: grid (bh, kv_block, q_block); dk [.., d] and dv
    [.., dv] accumulated in VMEM.
    dv = p^T @ dout; dk = scale * ds^T @ q."""
    if segmented:
        sq_ref, sk_ref, dk_ref, dv_ref, dk_scr, dv_scr = rest
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = rest
    _, bk, d = k_ref.shape
    kv_i = pl.program_id(1)
    q_idx = pl.program_id(2)
    num_q = pl.num_programs(2)

    @pl.when(q_idx == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        bq = q.shape[0]
        lse = lse_ref[0, 0][:, None]
        delta = dl_ref[0, 0][:, None]
        s = _dot(q, k, ((1,), (1,))) * scale  # [bq, bk]
        if causal:
            q_pos = q_idx * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_pos = kv_i * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(_sees(q_pos, k_pos, window), s, NEG_INF)
        if segmented:
            s = jnp.where(
                sq_ref[0, 0][:, None] == sk_ref[0, 0][None, :], s, NEG_INF)
        p = jnp.exp(s - lse)                             # [bq, bk]
        dv_scr[...] += _dot(p.astype(do.dtype), do, ((0,), (0,)))          # [bk, d]
        dp = _dot(do, v, ((1,), (1,)))          # [bq, bk]
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_scr[...] += scale * _dot(ds, q, ((0,), (0,)))          # [bk, d]

    if causal:
        # skip q blocks entirely above the diagonal for this kv block
        @pl.when(q_idx * q_ref.shape[1] + q_ref.shape[1] - 1
                 >= kv_i * bk)
        def _run():
            compute()
    else:
        compute()

    @pl.when(q_idx == num_q - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_bhnd(q, k, v, out, lse, g, scale, causal, block_q, block_k,
                    interpret, segs=None, window=None):
    """Pallas backward: returns (dq, dk [BH, N, D], dv [BH, N, Dv]);
    ``out`` and ``g`` are [BH, N, Dv]."""
    bh, n, d = q.shape
    kv_len = k.shape[1]
    dv_dim = v.shape[2]
    segmented = segs is not None
    # delta[b, i] = sum_d dout * out — one fused XLA reduction
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]                  # [bh, 1, n]
    dq_in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, dv_dim), lambda b, i, j: (b, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, dv_dim), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i),
                     memory_space=pltpu.VMEM),
    ]
    dq_args = [q, k, v, g, lse, delta]
    if segmented:
        dq_in_specs += [
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b, 0, j),
                         memory_space=pltpu.VMEM),
        ]
        # [BH, 1, N]: same singleton-axis plane as lse (tiling rule)
        dq_args += [segs[:, None, :]] * 2
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k, segmented=segmented,
                          window=window),
        grid=(bh, n // block_q, kv_len // block_k),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((bh, n, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_dq",
    )(*dq_args)
    dkv_in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, dv_dim), lambda b, j, i: (b, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, dv_dim), lambda b, j, i: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i),
                     memory_space=pltpu.VMEM),
    ]
    dkv_args = [q, k, v, g, lse, delta]
    if segmented:
        dkv_in_specs += [
            pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k), lambda b, j, i: (b, 0, j),
                         memory_space=pltpu.VMEM),
        ]
        # [BH, 1, N]: same singleton-axis plane as lse (tiling rule)
        dkv_args += [segs[:, None, :]] * 2
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, segmented=segmented,
                          window=window),
        grid=(bh, kv_len // block_k, n // block_q),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dv_dim), lambda b, j, i: (b, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, kv_len, d), k.dtype),
            jax.ShapeDtypeStruct((bh, kv_len, dv_dim), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv_dim), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_dkv",
    )(*dkv_args)
    return dq, dk, dv


def _reference_attention(q, k, v, scale, causal, segs=None, window=None):
    """[BH, N, D] (v and the result [BH, N, Dv]) fp32-statistics
    attention — the VJP recompute form.

    Uses the same start-aligned causal mask (and segment mask) as the
    Pallas kernel so forward and backward agree for any kv_len.
    """
    # bf16 operands + fp32 accumulation: the MXU-native contraction (same
    # dtype-gated policy as the kernel's _dot — fp32 callers keep the
    # global matmul-precision guarantee).
    logits = _dot(q, k, ((2,), (2,)), batch=((0,), (0,))) * scale
    if causal:
        n, m = logits.shape[-2], logits.shape[-1]
        q_pos = jax.lax.broadcasted_iota(jnp.int32, (n, m), 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (n, m), 1)
        logits = jnp.where(_sees(q_pos, k_pos, window), logits, NEG_INF)
    if segs is not None:
        logits = jnp.where(segs[:, :, None] == segs[:, None, :], logits,
                           NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bnm,bmd->bnd", p.astype(v.dtype), v)


# checkpoint names of the forward kernel's two outputs: what a recompute
# policy keeps (models/llama.py _remat_layer) so that a checkpointed layer
# does not run the kernel again in its backward pass
FLASH_SAVED_NAMES = ("flash_out", "flash_lse")


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _flash_carry(q, k, v, segs, out, lse, scale, causal, block_q, block_k,
                 interpret, window):
    """`out`, with the blocked backward as its VJP in q, k, v. The forward
    kernel has already run (_flash_core); this only carries its outputs to
    the backward kernels as residuals."""
    return out


def _flash_carry_fwd(q, k, v, segs, out, lse, scale, causal, block_q,
                     block_k, interpret, window):
    return out, (q, k, v, segs, out, lse)


def _flash_carry_bwd(scale, causal, block_q, block_k, interpret, window,
                     res, g):
    q, k, v, segs, out, lse = res
    # Pallas blocked backward: O(N) memory, never materializes [N, N]
    dq, dk, dv = _flash_bwd_bhnd(q, k, v, out, lse, g, scale, causal,
                                 block_q, block_k, interpret, segs=segs,
                                 window=window)
    dsegs = (None if segs is None
             else jnp.zeros(segs.shape, jax.dtypes.float0))
    # out and lse came from stop_gradient-ed operands: no cotangent (None)
    # for them, all of the gradient flows through dq, dk, dv
    return dq, dk, dv, dsegs, None, None


_flash_carry.defvjp(_flash_carry_fwd, _flash_carry_bwd)


def _flash_core(q, k, v, segs, scale, causal, block_q, block_k,
                interpret, fwd_tiles=None, window=None):
    """[BH, N, D] attention: one forward-kernel call, then _flash_carry.
    ``block_q`` / ``block_k`` are the backward kernels' tile and, unless
    ``fwd_tiles`` (block_q, block_k, block_kv) names another, the
    forward's.

    The forward kernel sits outside the custom_vjp on purpose: values born
    inside a custom_vjp's forward rule are invisible to a `jax.checkpoint`
    policy, and out here `out` and `lse` carry names (FLASH_SAVED_NAMES)
    that `save_only_these_names` can keep. With no enclosing checkpoint
    the names are identities and the residuals are q, k, v, segs, out,
    lse; with no gradient the whole function is the one `flash_fwd` call."""
    fwd_q, fwd_k, fwd_kv = fwd_tiles or (block_q, block_k, block_k)
    out, lse = _flash_fwd_bhnd(
        jax.lax.stop_gradient(q), jax.lax.stop_gradient(k),
        jax.lax.stop_gradient(v), scale, causal, fwd_q, fwd_k,
        interpret, segs=segs, block_kv=fwd_kv,
        **({} if window is None else {"window": window}))
    out = checkpoint_name(out, FLASH_SAVED_NAMES[0])
    lse = checkpoint_name(lse, FLASH_SAVED_NAMES[1])
    return _flash_carry(q, k, v, segs, out, lse, scale, causal, block_q,
                        block_k, interpret, window)


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=None, segment_ids=None,
                    window=None):
    """q,k: [B, N, H, D], v: [B, N, H, Dv] jax arrays (Dv is D unless v
    has a head dim of its own). Returns [B, N, H, Dv].

    block_q, block_k: the tile of all three kernels when either is
    named (tests that pin small tiles). A call that names none runs the
    backward kernels at 512 / 512 and the forward at the tile
    ``_fwd_tiles`` picks for its shapes.

    segment_ids: optional [B, N] int32 — ragged/packed attention
    (serving varlen batching): tokens attend only within their segment,
    composable with `causal` (packed causal LM).

    window: causal only; query i sees keys i - window + 1 .. i."""
    b, n, h, d = q.shape
    kv_n = k.shape[1]
    dv = v.shape[3]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if window is not None and not causal:
        raise ValueError("flash_attention: a window is a causal band")
    interpret = resolve_interpret(interpret)
    chosen = block_q is None and block_k is None
    block_q = min(block_q or DEFAULT_BLOCK_Q, n)
    block_k = min(block_k or DEFAULT_BLOCK_K, kv_n)
    # Kernel path requires Mosaic-tileable blocks: q blocks on the sublane
    # axis (multiple of 8) and kv blocks on the lane axis of the score tile
    # (multiple of 128); block_q additionally lands on the LANE axis of the
    # saved lse tile (1, 1, block_q), so it must be a multiple of 128 or
    # the whole sequence. Anything else takes the XLA fallback, which
    # shares the kernel's mask semantics. (The forward's own tile is a
    # multiple of this one, so the same test covers it.)
    tileable = (n % block_q == 0 and kv_n % block_k == 0
                and block_q % 8 == 0 and block_k % 128 == 0
                and (block_q % 128 == 0 or block_q == n))
    segs = None
    if segment_ids is not None:
        if n != kv_n:
            raise ValueError(
                "segment_ids requires q_len == kv_len (packed batches)")
        segs = jnp.broadcast_to(
            jnp.asarray(segment_ids, jnp.int32)[:, None, :],
            (b, h, kv_n)).reshape(b * h, kv_n)
    if not tileable:
        return jnp.swapaxes(
            _reference_attention(
                jnp.swapaxes(q, 1, 2).reshape(b * h, n, d),
                jnp.swapaxes(k, 1, 2).reshape(b * h, kv_n, d),
                jnp.swapaxes(v, 1, 2).reshape(b * h, kv_n, dv),
                scale, causal, segs=segs, window=window).reshape(
                    b, h, n, dv), 1, 2)

    def fold(x):
        return jnp.swapaxes(x, 1, 2).reshape(b * h, x.shape[1], x.shape[3])

    fwd_tiles = _fwd_tiles(n, kv_n, d, dv, q.dtype.itemsize,
                           segmented=segs is not None,
                           window=window) if chosen else None
    out = _flash_core(fold(q), fold(k), fold(v), segs, scale, causal,
                      block_q, block_k, interpret, fwd_tiles, window=window)
    return jnp.swapaxes(out.reshape(b, h, n, dv), 1, 2)
