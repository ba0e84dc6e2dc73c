"""Mamba-2 decode step kernel (Pallas, TPU): ``ssm_decode``.

One token for every slot of a state-space layer (Dao & Gu, "Transformers
are SSMs", arXiv:2405.21060; the Nemotron-H family, arXiv:2504.03624).
Per head h of group g = h // (H / G), state S[h] of P x N in float32::

    S[h] <- exp(dt[h] A[h]) S[h] + (dt[h] x[h]) (x) B[g]
    y[h]  = S[h] C[g] + D[h] x[h]

There is no matmul worth the MXU in it: a slot's 2 MB of state is read
once, scaled, given a rank-one update, read out against C and written
once, so the bound is the memory's. What the kernel is for is exactly
that ONE read and ONE write, in place, inside a whole decode step: the
step's other form (the update, then the read-out, then a select over
the pool that keeps the idle slots' rows) reads the state three times
and writes it twice unless the compiler fuses all of it. Alone on the
chip, in this layout and with idle slots expressed as dt = 0, XLA does
fuse the twin below into one pass and the two measure the same, 1.66 and
1.68 ms a call at 256 slots (78 % of the memory's peak; PR 37): the
kernel makes that the step's shape whatever surrounds it, and gives the
trace a name to book it under.

Layout (shared with models/nemotron_h.py, which declares it in its cache
spec): a slot's state is ``[G, N, R]`` with ``R = (H / G) * P`` — group
g's heads and their P values side by side on the lanes, the state
dimension n on the sublanes. So everything indexed by (h, p) is a ROW
(dt x, the decay, D x, and y itself, which is written as it comes out),
B and C are columns, and the read-out's sum over n runs down the
sublanes: vector adds, not a reduction across the lanes of each of a
slot's 512 registers, which is what ``[H, P, N]`` with n on the lanes
costs.

The grid takes a slot a step; the state block is aliased to its output
(``input_output_aliases``), so the pool is updated in place. An idle
slot takes dt = 0: its decay is exactly 1 and its update exactly 0, and
its row is written back as it was (its y is whatever, and ignored).

Layout contract:
  x       [S, H, P]        the convolved, activated input, any float
  dt      [S, H]  float32  softplus(dt + dt_bias)
  a, d    [H]              A = -exp(A_log) and the skip weight D
  b, c    [S, G, N]
  active  [S] bool
  state   [S, G, N, R] float32
  ->      y [S, H, P] float32, the state after the step

Exact in interpret mode against ``ssm_decode_reference``
(tests/test_kernels.py), Mosaic-compiled at the published shapes in
tests/test_tpu_lowering.py. The reference below is the CPU engine path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...kernels.flash_attention import resolve_interpret

_F32 = jnp.float32
# two halves each of the state block in and out (2 MB at the published
# shapes), the rows and what a group's arithmetic holds
_VMEM_LIMIT = 32 * 1024 * 1024


def _rows(x, dt, a, d, active):
    """(decay, dt x, D x), each [S, H, P] in float32: what the step
    needs of everything but B, C and the state, with an idle slot's dt
    zeroed (decay exactly 1, update exactly 0)."""
    dt = jnp.where(active[:, None], dt.astype(_F32), 0.0)
    xs = x.astype(_F32)
    decay = jnp.broadcast_to(jnp.exp(dt * a.astype(_F32))[..., None],
                             xs.shape)
    return decay, dt[..., None] * xs, d.astype(_F32)[:, None] * xs


def _ssm_kernel(decay_ref, dtx_ref, skip_ref, b_ref, c_ref, s_ref, y_ref,
                o_ref):
    """One slot. Rows [1, G, R], b and c [1, G, N], state [1, G, N, R]."""
    bt = b_ref[0].astype(_F32).T                        # [N, G]
    ct = c_ref[0].astype(_F32).T
    for g in range(s_ref.shape[1]):
        new = (s_ref[0, g] * decay_ref[0, g:g + 1, :]
               + bt[:, g:g + 1] * dtx_ref[0, g:g + 1, :])   # [N, R]
        o_ref[0, g] = new
        y_ref[0, g:g + 1, :] = (
            jnp.sum(new * ct[:, g:g + 1], axis=0, keepdims=True)
            + skip_ref[0, g:g + 1, :])


# jitted so that a model's layers share ONE trace and one lowering of
# the kernel body, as _paged_decode's and _mla_decode's do
@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_decode(x, dt, a, d, b, c, active, state, *, interpret):
    s, h, p = x.shape
    _, g, n, r = state.shape
    rows = [v.reshape(s, g, r) for v in _rows(x, dt, a, d, active)]
    row_spec = pl.BlockSpec((1, g, r), lambda i: (i, 0, 0))
    col_spec = pl.BlockSpec((1, g, n), lambda i: (i, 0, 0))
    state_spec = pl.BlockSpec((1, g, n, r), lambda i: (i, 0, 0, 0))
    y, new_state = pl.pallas_call(
        _ssm_kernel,
        grid=(s,),
        in_specs=[row_spec] * 3 + [col_spec] * 2 + [state_spec],
        out_specs=[row_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((s, g, r), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="ssm_decode",
    )(*rows, b, c, state)
    return y.reshape(s, h, p), new_state


def ssm_decode_kernel(x, dt, a, d, b, c, active, state, interpret=None):
    """Pallas path. -> (y [S, H, P] float32, state after the step)."""
    return _ssm_decode(x, dt, a, d, b, c, active, state,
                       interpret=resolve_interpret(interpret))


def ssm_decode_reference(x, dt, a, d, b, c, active, state):
    """The same step in ``jax.numpy`` (the CPU engine path, and what the
    kernel is compared with): idle slots keep their rows here too."""
    s, h, p = x.shape
    _, g, n, r = state.shape
    decay, dtx, skip = (v.reshape(s, g, 1, r)
                        for v in _rows(x, dt, a, d, active))
    new = (state * decay
           + b.astype(_F32)[..., None] * dtx)              # [S, G, N, R]
    y = jnp.sum(new * c.astype(_F32)[..., None], axis=2) + skip[:, :, 0]
    return y.reshape(s, h, p), new


def ssm_decode(x, dt, a, d, b, c, active, state, interpret=None):
    """Dispatch: the Pallas kernel on a TPU when the state's tile fills
    whole registers (n in eights on the sublanes, a group's (h, p) row in
    128s on the lanes), the jnp twin otherwise (the CPU engine path; on
    a TPU it warns once)."""
    _, g, n, r = state.shape
    if jax.default_backend() == "tpu":
        if n % 8 == 0 and r % 128 == 0 and g % 8 == 0:
            return ssm_decode_kernel(x, dt, a, d, b, c, active, state,
                                     interpret=interpret)
        from ...monitor.registry import warn_once

        warn_once(
            "serving.ssm_decode.reference_on_tpu",
            "paddle_tpu.serving: ssm_decode takes the jnp reference on "
            "the TPU (state %s is not Mosaic-tileable); the Pallas "
            "kernel is NOT in this step" % (tuple(state.shape),))
    return ssm_decode_reference(x, dt, a, d, b, c, active, state)
