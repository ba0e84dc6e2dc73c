"""Chunked Gated DeltaNet prefill kernel (Pallas, TPU): ``gdn_chunked``.

The Gated DeltaNet recurrence (Yang et al., arXiv:2412.06464), per value
head, state S [Dk, Dv] in float32::

    S <- exp(g_t) S;  u = (v_t - S^T k_t) beta_t;  S <- S + k_t u^T
    o_t = S^T q_t

over a prompt of T tokens, C = 64 tokens a chunk in the WY form (the
same algebra as ``gated_delta_chunked`` below, the jnp twin). Inside a
chunk, with G the cumulative sum of g over the chunk's rows::

    decay = exp(G_i - G_j) for j <= i, else 0
    A     = strict_lower(beta_i (k_i . k_j) decay_ij)
    u     = (I + A)^-1 (beta v);   w = (I + A)^-1 (beta e^G k)
    v'    = u - w S;   o = (e^G q) S + (q k^T * decay) v'
    S    <- e^{G_C} S + (e^{G_C - G} k)^T v'

The twin writes every chunk's C x C arrays and its right-hand sides for
every value head to HBM, solves with XLA's batched triangular solve and
carries the state through a ``lax.scan`` of T / C trips. Here ONE
``pallas_call`` runs a prompt's whole recurrence: the grid is (sequence,
block of value heads, chunk), the chunk axis sequential, and each head's
state sits in VMEM scratch from the first chunk to the last (read from
``state`` at chunk 0, written out once after the last). A chunk's q, k,
v, g and beta are read from HBM once and its o is written once; nothing
C x C leaves the chip.

(I + A)^-1 is taken with matmuls alone and is exact for any strictly
lower A: within 8-row blocks as (I - A)(I + A^2)(I + A^4), then merged
8 -> 16 -> 32 -> 64 by block forward substitution (``_unit_lower_inverse``),
so no product of more than eight of A's rows is ever formed and the
series' cancellation stays bounded whatever the keys.

Value heads go two at a time, side by side on the lanes: one [C, 2C]
tile holds a pair's decay, A and inverse, and a product of the pair is
ONE dot of [x_a | x_b] with the block diagonal of its right factor,
which fills the MXU's 128 rows where a lone 64 x 64 product leaves three
quarters of it idle (on a v5e at T 8192 and 64 value heads, 11.8 ms a
call against 18.7 ms a head at a time and 28.2 for the twin). A key head
serves ``Hv / Hk`` value heads: a grid step's block of value heads takes
the key heads it needs through the index map, and q k^T and k k^T are
formed once for a pair that shares its key head. Numerics are the
twin's: the state
and every product in float32, every dot at ``precision=HIGHEST``; a row
with g = 0 and beta = 0 changes nothing (how a prompt's padding is
expressed).

Layout contract (the model's own, [B, T, heads, D] flattened to
[B, T, heads * D], which costs nothing):
  q, k   [B, T, Hk, Dk]  float32, q normalised and scaled, k normalised
  v      [B, T, Hv, Dv]  float32
  g, beta [B, T, Hv]     float32, the log decay and the write strength
  state  [B, Hv, Dk, Dv] float32
  ->     (o [B, T, Hv, Dv] float32, state after the last row)

``gdn_chunked`` takes the kernel on a TPU backend when Dk and Dv are
whole 128-lane tiles and Hv is even, the twin otherwise (the CPU path
and the reference). Its gradient is the twin's. Exact in interpret mode against
the twin and the token-by-token recurrence (tests/test_kernels.py),
Mosaic-compiled at both published shapes in tests/test_tpu_lowering.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import resolve_interpret

GDN_CHUNK = 64
_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
# rows of the diagonal blocks (I + A)^-1 is first taken in by its series
_SERIES_ROWS = 8
# VMEM a grid step's value heads may take (their q, k, v and o blocks
# double-buffered, their state in and out and in scratch: 0.5 MB a head
# at D 128), and the most heads one step unrolls
_HEAD_VMEM_BUDGET = 8 * 1024 * 1024
_MAX_HEADS_A_STEP = 8


# -- the jnp twin -------------------------------------------------------------

def gated_delta_chunked(q, k, v, g, beta, state, chunk=GDN_CHUNK):
    """The same recurrence over T tokens of B sequences, a chunk at a
    time (the WY representation: inside a chunk the token-by-token
    updates are one unit-lower-triangular solve, between chunks the
    state is carried). q, k [B, T, Hk, Dk], v [B, T, Hv, Dv], g and
    beta [B, T, Hv], state [B, Hv, Dk, Dv], float32. A row with g = 0
    and beta = 0 changes nothing, which is how padding is expressed
    (T is padded up to a whole chunk that way here).
    -> (o [B, T, Hv, Dv], state after the last row)."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    rep = hv // hk
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    n = (t + pad) // chunk

    def chunks(a):          # [B, T, H, ...] -> [n, B, H, C, ...]
        a = a.reshape((b, n, chunk) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 2, 3)

    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    gc = jnp.cumsum(chunks(g), axis=-1)                         # [n, B, Hv, C]
    bc = chunks(beta)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # exp(G_i - G_j) for j <= i; masked before the exp, not after
    decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))                        # [n,B,Hv,C,C]
    kk = jnp.repeat(jnp.einsum("nbhcd,nbhed->nbhce", kc, kc), rep, axis=2)
    qk = jnp.repeat(jnp.einsum("nbhcd,nbhed->nbhce", qc, kc), rep, axis=2)
    kv_heads = jnp.repeat(kc, rep, axis=2)                      # [n,B,Hv,C,Dk]
    qv_heads = jnp.repeat(qc, rep, axis=2)
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    a = jnp.where(strict, bc[..., None] * kk * decay, 0.0)
    rhs = jnp.concatenate(
        [vc * bc[..., None],
         kv_heads * (bc * jnp.exp(gc))[..., None]], axis=-1)
    # (I + A) sol = rhs; the solve takes the diagonal as 1 unread
    sol = jax.lax.linalg.triangular_solve(
        a, rhs, left_side=True, lower=True, unit_diagonal=True)
    u, w = sol[..., :dv], sol[..., dv:]
    local = qk * decay                                          # j <= i
    q_in = qv_heads * jnp.exp(gc)[..., None]
    last = gc[..., -1:]                                         # [n,B,Hv,1]
    k_out = kv_heads * jnp.exp(last - gc)[..., None]

    def body(s, xs):
        u_i, w_i, local_i, q_i, k_i, last_i = xs
        v_new = u_i - jnp.einsum("bhck,bhkv->bhcv", w_i, s)
        o_i = (jnp.einsum("bhck,bhkv->bhcv", q_i, s)
               + jnp.einsum("bhce,bhev->bhcv", local_i, v_new))
        s = (jnp.exp(last_i)[..., None] * s
             + jnp.einsum("bhck,bhcv->bhkv", k_i, v_new))
        return s, o_i

    state, o = jax.lax.scan(body, state, (u, w, local, q_in, k_out, last))
    o = jnp.moveaxis(jnp.moveaxis(o, 3, 2), 0, 1)               # [B,n,C,Hv,Dv]
    return o.reshape(b, t + pad, hv, dv)[:, :t], state


# -- the kernel ---------------------------------------------------------------

def _dot(a, b, contract=((1,), (0,))):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=_F32)


def _pair_layout(chunk):
    """Index arrays of a head pair's [C, 2C] tile, head a on the left
    lanes and head b on the right: (row, column within the head, left?,
    {s: row and column in the same s-row block} for s = 8, 16, .., C)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, 2 * chunk), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (chunk, 2 * chunk), 1)
    left = lanes < chunk
    cols = jnp.where(left, lanes, lanes - chunk)
    masks, s = {}, _SERIES_ROWS
    while s <= chunk:
        shift = s.bit_length() - 1
        masks[s] = (jax.lax.shift_right_logical(rows, shift)
                    == jax.lax.shift_right_logical(cols, shift))
        s *= 2
    return rows, cols, left, masks


def _block_diag(x_a, x_b):
    """[[x_a, 0], [0, x_b]] of two [R, W] blocks."""
    zero = jnp.zeros_like(x_a)
    return jnp.concatenate([jnp.concatenate([x_a, zero], axis=1),
                            jnp.concatenate([zero, x_b], axis=1)], axis=0)


def _unit_lower_inverse(a, eye, left, masks):
    """(I + a)^-1 of a head pair by matmuls, a = [a_a | a_b] [C, 2C],
    each half strictly lower; -> [t_a | t_b]. One dot takes a product of
    both heads: [x_a | x_b] [[y_a, 0], [0, y_b]] = [x_a y_a | x_b y_b],
    the block diagonal cut from the pair's tile by two selects. On the
    8-row diagonal blocks (I - a)(I + a^2)(I + a^4) is the whole series
    (a block's a^8 is 0); then each level s -> 2s is one step of block
    forward substitution: with D the level-s inverse and L the part of a
    below D's blocks inside a 2s block, (D^-1 + L)^-1 = D - D L D."""

    def prod(x, y):
        zero = jnp.zeros_like(y)
        return _dot(x, jnp.concatenate([jnp.where(left, y, zero),
                                        jnp.where(left, zero, y)], axis=0))

    chunk = a.shape[0]
    s = _SERIES_ROWS
    p = jnp.where(masks[s], a, 0.0)
    t = eye - p
    for _ in range(s.bit_length() - 2):
        p = prod(p, p)
        t = t + prod(t, p)
    while s < chunk:
        below = jnp.where(masks[2 * s] & jnp.logical_not(masks[s]), a, 0.0)
        t = t - prod(prod(t, below), t)
        s *= 2
    return t


def _gdn_kernel(q_ref, k_ref, v_ref, gcol_ref, grow_ref, bcol_ref,
                elast_ref, s_ref, o_ref, s_out_ref, s_scr, *, rep, dk, dv):
    """One (sequence, block of value heads, chunk) grid step, two value
    heads at a time side by side on the lanes. q, k [1, C, heads / rep *
    Dk] (this block's key heads), v and o [1, C, heads * Dv], the
    chunk's cumulative g as columns [1, 1, 1, C, heads] and as a row a
    head pair [1, 1, 1, heads / 2, 2C], beta as columns, e^{G_C} a row
    a head [1, 1, 1, heads, Dv] (Mosaic cannot broadcast one element of
    a column over both axes of the state); state in and out
    [1, heads, Dk, Dv]; scratch [heads, Dk, Dv], the carried state."""
    c = pl.program_id(2)
    heads = s_scr.shape[0]
    chunk = q_ref.shape[1]

    @pl.when(c == 0)
    def _load():
        s_scr[...] = s_ref[0]

    rows, cols, left, masks = _pair_layout(chunk)
    lower = cols <= rows
    strict = cols < rows
    eye = jnp.where(cols == rows, 1.0, 0.0).astype(_F32)
    gcol, grow, bcol = gcol_ref[0, 0, 0], grow_ref[0, 0, 0], bcol_ref[0, 0, 0]
    elast = elast_ref[0, 0, 0]

    def key_rows(ref, h):
        return ref[0, :, h // rep * dk:(h // rep + 1) * dk]

    for pair in range(heads // 2):
        ab = (2 * pair, 2 * pair + 1)
        q = [key_rows(q_ref, h) for h in ab]
        k = [key_rows(k_ref, h) for h in ab]
        # [k k^T | k k^T] over [q k^T | q k^T], a head's own key on its
        # side: one dot where the pair shares a key head
        both = jnp.concatenate(k, axis=0)
        kq = _dot(jnp.concatenate([k[0], q[0]], axis=0), both, ((1,), (1,)))
        if ab[0] // rep != ab[1] // rep:
            kq = jnp.where(
                jnp.concatenate([left, left], axis=0), kq,
                _dot(jnp.concatenate([k[1], q[1]], axis=0), both,
                     ((1,), (1,))))
        kk, qk = kq[:chunk], kq[chunk:]
        g = [gcol[:, h:h + 1] for h in ab]
        beta = [bcol[:, h:h + 1] for h in ab]
        decay = jnp.exp(jnp.where(
            lower, jnp.where(left, g[0], g[1]) - grow[pair:pair + 1, :],
            -jnp.inf))
        t = _unit_lower_inverse(
            jnp.where(strict, jnp.where(left, beta[0], beta[1]) * kk * decay,
                      0.0),
            eye, left, masks)
        states, q_s, rhs = [], [], []
        for i, h in enumerate(ab):
            e_g = jnp.exp(g[i])
            states.append(s_scr[h])
            # (e^G q) S and (beta e^G k) S, one dot for both
            qs_ks = _dot(jnp.concatenate([q[i] * e_g, k[i] * (beta[i] * e_g)],
                                         axis=0), states[i])
            q_s.append(qs_ks[:chunk])
            rhs.append(v_ref[0, :, h * dv:(h + 1) * dv] * beta[i]
                       - qs_ks[chunk:])
        # v' = (I + A)^-1 beta (v - e^G k S), [v'_a | v'_b]
        v_new = _dot(t, _block_diag(*rhs))
        v_new = [v_new[:, :dv], v_new[:, dv:]]
        o_ref[0, :, ab[0] * dv:(ab[1] + 1) * dv] = (
            jnp.concatenate(q_s, axis=1)
            + _dot(qk * decay, _block_diag(*v_new)))
        for i, h in enumerate(ab):
            last = g[i][chunk - 1:, :]                          # [1, 1]
            s_scr[h] = (elast[h:h + 1, :] * states[i]
                        + _dot(k[i] * jnp.exp(last - g[i]), v_new[i],
                               ((0,), (0,))))

    @pl.when(c == pl.num_programs(2) - 1)
    def _store():
        s_out_ref[0] = s_scr[...]


def _heads_a_step(hv, rep, dk, dv, chunk):
    """Value heads a grid step takes: the most that divide Hv, come in
    pairs, hold whole key heads, fit the VMEM budget and the unroll
    cap."""
    step = math.lcm(2, rep)
    a_head = 4 * (2 * 2 * chunk * (dv + dk // rep) + 5 * dk * dv)
    best = step
    for heads in range(step, hv + 1, step):
        if (hv % heads == 0 and heads <= _MAX_HEADS_A_STEP
                and heads * a_head <= _HEAD_VMEM_BUDGET):
            best = heads
    return best


# jitted so that a model's layers share ONE trace and one lowering of
# the kernel body, as _ssm_decode's and _mla_decode's do
@functools.partial(jax.jit, static_argnames=("interpret",))
def _gdn_forward(q, k, v, g, beta, state, *, interpret):
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    rep = hv // hk
    chunk = GDN_CHUNK
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    n = (t + pad) // chunk
    heads = _heads_a_step(hv, rep, dk, dv, chunk)
    blocks = hv // heads

    def per_block(a):       # [B, T, Hv] -> [B, blocks, n, C, heads]
        a = a.astype(_F32).reshape(b, n, chunk, blocks, heads)
        return jnp.transpose(a, (0, 3, 1, 2, 4))

    gcol = jnp.cumsum(per_block(g), axis=3)
    grow = jnp.swapaxes(gcol, 3, 4).reshape(b, blocks, n, heads // 2,
                                            2 * chunk)
    bcol = per_block(beta)
    elast = jnp.broadcast_to(jnp.exp(gcol[:, :, :, -1, :, None]),
                             (b, blocks, n, heads, dv))
    col_spec = pl.BlockSpec((1, 1, 1, chunk, heads),
                            lambda i, j, c: (i, j, c, 0, 0))
    row_spec = pl.BlockSpec((1, 1, 1, heads // 2, 2 * chunk),
                            lambda i, j, c: (i, j, c, 0, 0))
    last_spec = pl.BlockSpec((1, 1, 1, heads, dv),
                             lambda i, j, c: (i, j, c, 0, 0))
    key_spec = pl.BlockSpec((1, chunk, heads // rep * dk),
                            lambda i, j, c: (i, c, j))
    value_spec = pl.BlockSpec((1, chunk, heads * dv),
                              lambda i, j, c: (i, c, j))
    state_spec = pl.BlockSpec((1, heads, dk, dv),
                              lambda i, j, c: (i, j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_gdn_kernel, rep=rep, dk=dk, dv=dv),
        grid=(b, blocks, n),
        in_specs=[key_spec, key_spec, value_spec, col_spec, row_spec,
                  col_spec, last_spec, state_spec],
        out_specs=[value_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((b, t + pad, hv * dv), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=2 * _HEAD_VMEM_BUDGET),
        interpret=interpret,
        name="gdn_chunked",
    )(q.reshape(b, t + pad, hk * dk), k.reshape(b, t + pad, hk * dk),
      v.reshape(b, t + pad, hv * dv), gcol, grow, bcol, elast, state)
    return o.reshape(b, t + pad, hv, dv)[:, :t], state


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _gdn(q, k, v, g, beta, state, interpret):
    return _gdn_forward(q, k, v, g, beta, state, interpret=interpret)


def _gdn_fwd(q, k, v, g, beta, state, interpret):
    out = _gdn_forward(q, k, v, g, beta, state, interpret=interpret)
    return out, (q, k, v, g, beta, state)


def _gdn_bwd(interpret, residuals, cotangents):
    # the gradient is the twin's: no cell trains through this kernel
    return jax.vjp(gated_delta_chunked, *residuals)[1](cotangents)


_gdn.defvjp(_gdn_fwd, _gdn_bwd)


def gdn_chunked_kernel(q, k, v, g, beta, state, interpret=None):
    """Pallas path. -> (o [B, T, Hv, Dv] float32, state after the last
    row)."""
    return _gdn(q, k, v, g, beta, state, resolve_interpret(interpret))


def gdn_chunked(q, k, v, g, beta, state, interpret=None):
    """Dispatch: the Pallas kernel on a TPU when a head's key and value
    rows are whole 128-lane tiles and the value heads come in pairs, the
    jnp twin otherwise (the CPU path)."""
    if (jax.default_backend() == "tpu" and q.shape[-1] % 128 == 0
            and v.shape[-1] % 128 == 0 and v.shape[2] % 2 == 0):
        return gdn_chunked_kernel(q, k, v, g, beta, state,
                                  interpret=interpret)
    return gated_delta_chunked(q, k, v, g, beta, state)
