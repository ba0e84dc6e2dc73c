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


def row_tile(m, groups):
    """Rows a visit multiplies: 128 where a group owns about a tile or
    more (prefill), 32 where it owns a few rows (decode), so the MXU is
    not fed 125 rows of padding for every 3 real ones."""
    return 128 if m >= 64 * groups else 32


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
                out_ref, *, tm):
    v = pl.program_id(1)

    @pl.when(v < visits_ref[0])
    def _visit():
        g = gid_ref[v]
        acc = _dot(lhs_ref[...], rhs_ref[...], ((1,), (0,)))
        row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc.shape, 0)
        mine = jnp.logical_and(row >= off_ref[g], row < off_ref[g + 1])
        out_ref[...] = jnp.where(
            mine, acc, out_ref[...].astype(jnp.float32)).astype(
                out_ref.dtype)


# jitted so that a model's layers share one trace and one lowering
@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _moe_gmm(lhs, rhs, offsets, gid, tile, visits, *, tm, interpret):
    m, k = lhs.shape
    _, _, n = rhs.shape
    tn = _col_tile(k, n, rhs.dtype.itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n // tn, gid.shape[0]),
        in_specs=[
            pl.BlockSpec((tm, k), lambda j, v, off, gid, tile, nv:
                         (tile[v], 0)),
            pl.BlockSpec((None, k, tn), lambda j, v, off, gid, tile, nv:
                         (gid[v], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda j, v, off, gid, tile, nv:
                               (tile[v], j)),
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
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
    return _moe_gmm(lhs, rhs, *visits, tm=tm,
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
