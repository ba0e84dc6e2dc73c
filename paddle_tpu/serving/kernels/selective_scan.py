"""Mamba-1's selective scan over a prompt (Pallas, TPU): ``selective_scan``.

Mamba-1 (Gu & Dao, "Mamba", arXiv:2312.00752) decays its state per channel
AND per state element: for channel c and state element n::

    h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n h_t[n, c] C_t[n] + D[c] x_t[c]

so there is no chunked matmul form to fall back on as Mamba-2 has (its
decay is one scalar a head). The kernel runs the recurrence itself, a row
at a time, with the state on chip from the prompt's first row to its
last: grid (sequence, block of channels, block of rows), the row axis
sequential, a block's ``[N, channels]`` float32 state in VMEM scratch.
The state's layout is ``[N, C]``: the state elements on the sublanes, the
channels on the lanes, so dt, x, D and y are rows, B_t and C_t columns
(handed in already broadcast over 128 lanes, ``[T, N, 128]``, 8 MB at
2048 rows and N 16: the kernel then reads a row's B as one tile), and
the read-out's sum over n runs down the sublanes. Eight rows at a time
are loaded as one tile, stepped in registers and stored as one tile.

A padded row takes dt = 0: its decay is exactly 1 and its update exactly
0, so the state a prompt leaves is that of its real rows.

Layout contract (channels C, state N, rows T):
  x      [B, T, C]  the convolved, activated input, any float
  dt     [B, T, C]  float32, after the softplus
  a      [N, C]     float32, A = -exp(A_log) transposed
  b, c   [B, T, N]
  d      [C]
  ->     y [B, T, C] float32 (D x included), state [B, N, C] float32
         after the last row, from a zero state

Exact in interpret mode against ``selective_scan_reference``
(tests/test_phi4flash.py); Mosaic-compiled at the published shapes in
tests/test_tpu_lowering.py. The reference is the CPU engine path;
``selective_step`` is the decode step's one row, in ``jax.numpy``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...kernels.flash_attention import resolve_interpret

_F32 = jnp.float32
LANES = 128


def selective_step(x, dt, a, b, c, d, state):
    """One row of every sequence: x, dt [S, C], a [N, C], b, c [S, N],
    d [C], state [S, N, C] float32. -> (y [S, C] float32, new state). A
    row with dt = 0 leaves its state exactly as it was."""
    dt = dt.astype(_F32)
    xf = x.astype(_F32)
    new = (jnp.exp(dt[:, None, :] * a[None]) * state
           + (dt * xf)[:, None, :] * b.astype(_F32)[:, :, None])
    y = jnp.sum(new * c.astype(_F32)[:, :, None], axis=1)
    return y + d.astype(_F32) * xf, new


def selective_scan_reference(x, dt, a, b, c, d):
    """The contract as a ``lax.scan`` over the rows."""
    bsz, _, ch = x.shape
    n = a.shape[0]
    state = jnp.zeros((bsz, n, ch), _F32)

    def row(h, xs):
        y, h = selective_step(*xs[:2], a, *xs[2:], d, h)
        return h, y

    state, y = jax.lax.scan(
        row, state, tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1), state


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref, s_ref,
                 h_scr):
    """One (sequence, channel block, row block) program. x, dt, y
    [1, tb, cb]; b, c [1, tb, N, 128]; a [N, cb]; d [1, cb]; the state
    out [1, N, cb] and its scratch [N, cb], carried over the row
    blocks."""
    t_i = pl.program_id(2)
    _, tb, cb = x_ref.shape

    @pl.when(t_i == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    row8 = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 0)

    def chunk(ci, carry):
        t0 = pl.multiple_of(ci * 8, 8)
        for j in range(cb // LANES):
            ls = slice(j * LANES, (j + 1) * LANES)
            xs = x_ref[0, pl.ds(t0, 8), ls].astype(_F32)
            ds = dt_ref[0, pl.ds(t0, 8), ls]
            a = a_ref[:, ls]
            h = h_scr[:, ls]
            y8 = jnp.zeros((8, LANES), _F32)
            for r in range(8):
                dr = ds[r:r + 1]
                h = (jnp.exp(dr * a) * h
                     + (dr * xs[r:r + 1]) * b_ref[0, t0 + r])
                y8 = jnp.where(row8 == r, jnp.sum(
                    h * c_ref[0, t0 + r], axis=0, keepdims=True), y8)
            h_scr[:, ls] = h
            y_ref[0, pl.ds(t0, 8), ls] = y8 + d_ref[:, ls] * xs
        return carry

    jax.lax.fori_loop(0, tb // 8, chunk, 0)

    @pl.when(t_i == pl.num_programs(2) - 1)
    def _out():
        s_ref[0] = h_scr[...]


def _blocks(t, ch):
    """(row block, channel block): 128 rows (the whole prompt below
    that) and 512 channels where they divide."""
    return min(t, 128), (512 if ch % 512 == 0 else LANES)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _selective_scan(x, dt, a, b, c, d, *, interpret):
    bsz, t, ch = x.shape
    n = a.shape[0]
    tb, cb = _blocks(t, ch)
    wide = [jnp.broadcast_to(v.astype(_F32)[..., None], (bsz, t, n, LANES))
            for v in (b, c)]
    rows = pl.BlockSpec((1, tb, cb), lambda i, j, k: (i, k, j))
    cols = pl.BlockSpec((1, tb, n, LANES), lambda i, j, k: (i, k, 0, 0))
    y, state = pl.pallas_call(
        _scan_kernel,
        grid=(bsz, ch // cb, t // tb),
        in_specs=[rows, rows, cols, cols,
                  pl.BlockSpec((n, cb), lambda i, j, k: (0, j)),
                  pl.BlockSpec((1, cb), lambda i, j, k: (0, j))],
        out_specs=[rows,
                   pl.BlockSpec((1, n, cb), lambda i, j, k: (i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((bsz, t, ch), _F32),
                   jax.ShapeDtypeStruct((bsz, n, ch), _F32)],
        scratch_shapes=[pltpu.VMEM((n, cb), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="selective_scan",
    )(x, dt.astype(_F32), *wide, a.astype(_F32),
      d.astype(_F32).reshape(1, ch))
    return y, state


def selective_scan_kernel(x, dt, a, b, c, d, interpret=None):
    """Pallas path. -> (y [B, T, C] float32, state [B, N, C])."""
    return _selective_scan(x, dt, a, b, c, d,
                           interpret=resolve_interpret(interpret))


def selective_scan(x, dt, a, b, c, d, interpret=None):
    """Dispatch: the Pallas kernel on a TPU when the channels fill whole
    lane tiles and the rows whole sublane tiles (in blocks of 128 past
    128), the ``lax.scan`` reference otherwise (the CPU engine path; on a
    TPU it warns once)."""
    _, t, ch = x.shape
    if jax.default_backend() == "tpu":
        if ch % LANES == 0 and t % 8 == 0 and (t <= 128 or t % 128 == 0):
            return selective_scan_kernel(x, dt, a, b, c, d, interpret)
        from ...monitor.registry import warn_once

        warn_once(
            "serving.selective_scan.reference_on_tpu",
            "paddle_tpu.serving: selective_scan takes the lax.scan "
            "reference on the TPU (%d rows of %d channels do not tile); "
            "the Pallas kernel is NOT in this prefill" % (t, ch))
    return selective_scan_reference(x, dt, a, b, c, d)
