"""Grouped matmul for a dropless expert layer (Pallas, TPU): ``moe_gmm``.

``lhs`` [M, K] holds rows sorted by group, ``group_sizes`` [G] says how
many consecutive rows each group owns, ``rhs`` [G, K, N] one matrix a
group; row ``i`` of the result is ``lhs[i] @ rhs[group of i]``. Rows
past ``sum(group_sizes)`` belong to no group and come back
UNINITIALISED (the caller masks them): nothing is spent on them.

The design is megablox's (``jax.experimental.pallas.ops.tpu.megablox``,
Gale et al., "MegaBlocks", arXiv:2211.15841), cut to what an expert
layer needs. The grid walks a list of VISITS, one per (group, row tile
the group touches), made by XLA from ``group_sizes`` and handed to the
kernel as scalar-prefetch arrays that the block index maps read: an
empty group has no visit, so its matrix is never fetched, and
consecutive visits of one group fetch it once. A row tile shared by
several groups is visited by each in turn and each writes only its own
rows. K is never tiled: a [K, tn] block of one matrix is about 2 MB, so
a decode step (a few rows a group) streams the matrices at one DMA a
visit. The visit list has a static length (row tiles + groups - 1 is
its bound); the tail past the real count repeats the last visit, which
costs a grid step and no DMA.

A width N that is no multiple of 128 (1856) cannot be cut into column
tiles, and the chip does not even keep such a matrix that way: it lays
[G, K, N] out with K, the multiple of 128, on the lanes. Handing the
kernel [K, N] blocks then costs a copy of every matrix a call. So the
kernel takes such a ``rhs`` as it lies, transposed ([G, N, K], which is
no copy: the same bytes), a group's whole matrix a block, and
contracts both operands' last dimension.

Off the TPU the layer calls ``jax.lax.ragged_dot`` in this kernel's
place (``grouped_matmul``), and the backward is always ``ragged_dot``'s.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _dot, resolve_interpret

# bytes of one [K, tn] block of a group's matrix; two are in flight
_RHS_BLOCK_BYTES = 2 * 1024 * 1024


# rows a group from which a visit multiplies 128 rows (``row_tile``)
ROWS_A_GROUP = 64


def row_tile(m, groups):
    """Rows a visit multiplies: 128 where a group owns about a tile or
    more (prefill), 32 where it owns a few rows (decode), so the MXU is
    not fed 125 rows of padding for every 3 real ones."""
    return 128 if m >= ROWS_A_GROUP * groups else 32


def _col_tile(k, n, itemsize):
    """The widest multiple of 128 that divides ``n`` and keeps a
    [k, tn] block within ``_RHS_BLOCK_BYTES``; ``n`` itself when it is
    not a multiple of 128 (small test shapes)."""
    if n % 128:
        return n
    best = 128
    for tn in range(128, n + 1, 128):
        if n % tn == 0 and k * tn * itemsize <= _RHS_BLOCK_BYTES:
            best = tn
    return best


def _vmem_limit(tm, k, tn, itemsize):
    """None, the compiler's own limit, while a [k, tn] block keeps to
    ``_RHS_BLOCK_BYTES``. Where a group's whole matrix is the block
    (``tn`` = N, a width that is no multiple of 128: 2688 x 1856 in
    bfloat16 is 10 MB), the limit is what two of them, the row and
    output tiles and the accumulator take, and half as much again."""
    block = k * tn * itemsize
    if block <= _RHS_BLOCK_BYTES:
        return None
    return int(1.5 * (2 * block + 2 * tm * (k + tn) * itemsize
                      + 4 * tm * tn))


def visit_list(group_sizes, m, tm):
    """(offsets [G+1], group of visit [V], row tile of visit [V],
    visits [1]) with V = m // tm + G - 1, the most visits there can be:
    every row tile once, and once more for each further group that
    starts inside a tile."""
    g = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_end = jnp.cumsum(tiles)
    visits = visit_end[-1]
    i = jnp.minimum(jnp.arange(m // tm + g - 1, dtype=jnp.int32),
                    jnp.maximum(visits - 1, 0))
    gid = jnp.minimum(jnp.searchsorted(visit_end, i, side="right"),
                      g - 1).astype(jnp.int32)
    tile = jnp.clip(first[gid] + i - (visit_end[gid] - tiles[gid]),
                    0, m // tm - 1).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, gid, tile, visits.reshape(1)


def _gmm_kernel(off_ref, gid_ref, tile_ref, visits_ref, lhs_ref, rhs_ref,
                out_ref, *, tm, rhs_t):
    v = pl.program_id(1)

    @pl.when(v < visits_ref[0])
    def _visit():
        g = gid_ref[v]
        acc = _dot(lhs_ref[...], rhs_ref[...],
                   ((1,), (1 if rhs_t else 0,)))
        row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc.shape, 0)
        mine = jnp.logical_and(row >= off_ref[g], row < off_ref[g + 1])
        out_ref[...] = jnp.where(
            mine, acc, out_ref[...].astype(jnp.float32)).astype(
                out_ref.dtype)


# jitted so that a model's layers share one trace and one lowering
@functools.partial(jax.jit, static_argnames=("tm", "rhs_t", "interpret"))
def _moe_gmm(lhs, rhs, offsets, gid, tile, visits, *, tm, rhs_t=False,
             interpret):
    m, k = lhs.shape
    if rhs_t:
        # [G, N, K] as the chip keeps it: a group's whole matrix a block
        tn = n = rhs.shape[1]
        rhs_spec = pl.BlockSpec((None, n, k), lambda j, v, off, gid, tile,
                                nv: (gid[v], 0, 0))
    else:
        n = rhs.shape[2]
        tn = _col_tile(k, n, rhs.dtype.itemsize)
        rhs_spec = pl.BlockSpec((None, k, tn), lambda j, v, off, gid, tile,
                                nv: (gid[v], 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n // tn, gid.shape[0]),
        in_specs=[
            pl.BlockSpec((tm, k), lambda j, v, off, gid, tile, nv:
                         (tile[v], 0)),
            rhs_spec,
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda j, v, off, gid, tile, nv:
                               (tile[v], j)),
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, rhs_t=rhs_t),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(tm, k, tn, rhs.dtype.itemsize)),
        interpret=interpret,
        name="moe_gmm",
    )(offsets, gid, tile, visits, lhs, rhs)


def moe_gmm(lhs, rhs, group_sizes, interpret=None):
    """The kernel. ``lhs`` [M, K], ``rhs`` [G, K, N], ``group_sizes``
    [G] int32 -> [M, N] in ``lhs``'s dtype. M is padded up to the row
    tile here. The two matmuls of one expert layer build the same visit
    list from the same sizes; inside one jit XLA keeps one."""
    m = lhs.shape[0]
    tm = row_tile(m, rhs.shape[0])
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    visits = visit_list(group_sizes.astype(jnp.int32), m + pad, tm)
    # a width the lanes cannot tile, under a K they can: the matrices
    # as the chip keeps them (module docstring)
    rhs_t = rhs.shape[2] % 128 != 0 and rhs.shape[1] % 128 == 0
    if rhs_t:
        rhs = jnp.swapaxes(rhs, 1, 2)
    return _moe_gmm(lhs, rhs, *visits, tm=tm, rhs_t=rhs_t,
                    interpret=resolve_interpret(interpret))[:m]


@jax.custom_vjp
def _gmm_with_ragged_vjp(lhs, rhs, group_sizes):
    return moe_gmm(lhs, rhs, group_sizes)


def _gmm_fwd(lhs, rhs, group_sizes):
    return moe_gmm(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _gmm_bwd(res, ct):
    lhs, rhs, group_sizes = res
    # rows of no group come back uninitialised from the kernel and the
    # caller masks them, so their cotangent is zero already
    _, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, group_sizes),
                     lhs, rhs)
    return (*vjp(ct), None)


_gmm_with_ragged_vjp.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs[rows of group g] @ rhs[g]`` for every group: ``moe_gmm`` on
    a TPU backend, ``jax.lax.ragged_dot`` elsewhere, picked by backend
    as the paged kernels are. Differentiable either way (the kernel's
    backward is ``ragged_dot``'s). Rows of no group are UNDEFINED."""
    sizes = group_sizes.astype(jnp.int32)
    if jax.default_backend() != "tpu":
        return jax.lax.ragged_dot(lhs, rhs, sizes)
    return _gmm_with_ragged_vjp(lhs, rhs, sizes)
