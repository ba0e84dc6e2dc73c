"""Ring attention — sequence/context parallelism over ICI.

The reference snapshot has NO sequence parallelism (SURVEY §5: verified
absent); this is the designed-in long-context capability. The sequence axis
is sharded over the 'sep' mesh axis; each device holds a query block and the
k/v blocks rotate around the ring via collective-permute while an online
softmax accumulates — compute on each hop overlaps the ICI transfer of the
next (Liu et al.'s Ring Attention, expressed in lax so XLA schedules the
overlap; runs identically on the CPU test mesh).

Use inside shard_map/pjit with the sequence dim sharded over `axis_name`:

    out = ring_attention(q, k, v, axis_name="sep", causal=True)
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _block_attention(q, k, v, scale, mask):
    """q [B,H,nq,D], k/v [B,H,nk,D]; returns (numerator, max, denom)."""
    s = jnp.einsum("bhnd,bhmd->bhnm", q, k) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    # fully-masked rows (m == NEG_INF) must contribute p = 0, not exp(0):
    # without this a block whose rows are all masked (e.g. a kv block
    # entirely in the causal future) would add garbage to the accumulator.
    p = jnp.where(m <= NEG_INF, 0.0, jnp.exp(s - m))
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhnm,bhmd->bhnd", p, v)
    return o, m, l


def ring_attention(q, k, v, axis_name="sep", causal=False, scale=None):
    """q,k,v: per-device blocks [B, N_local, H, D] inside shard_map.

    Global sequence = concat of blocks in axis order. Returns the local
    output block [B, N_local, H, D].
    """
    b, n_loc, h, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)

    qf = jnp.swapaxes(q, 1, 2).astype(jnp.float32)  # [B,H,n,D]
    kf = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vf = jnp.swapaxes(v, 1, 2).astype(jnp.float32)

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def causal_mask(kv_idx):
        if not causal:
            return None
        q_pos = my_idx * n_loc + jnp.arange(n_loc)[:, None]
        k_pos = kv_idx * n_loc + jnp.arange(n_loc)[None, :]
        return (q_pos >= k_pos)[None, None]

    def step(carry, _):
        kv_blk, vv_blk, kv_idx, m, l, acc = carry
        mask = causal_mask(kv_idx)
        o_i, m_i, l_i = _block_attention(qf, kv_blk, vv_blk, scale, mask)
        m_new = jnp.maximum(m, m_i)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(m_i - m_new)
        l_new = alpha * l + beta * l_i
        acc_new = alpha * acc + beta * o_i
        # rotate kv to the next device (ICI hop overlapped with compute)
        kv_next = jax.lax.ppermute(kv_blk, axis_name, perm)
        vv_next = jax.lax.ppermute(vv_blk, axis_name, perm)
        idx_next = jax.lax.ppermute(kv_idx, axis_name, perm)
        return (kv_next, vv_next, idx_next, m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, n_loc, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, n_loc, 1), jnp.float32)
    acc0 = jnp.zeros((b, h, n_loc, d), jnp.float32)
    carry = (kf, vf, my_idx, m0, l0, acc0)
    carry, _ = jax.lax.scan(step, carry, None, length=axis_size)
    _, _, _, m, l, acc = carry
    out = acc / jnp.maximum(l, 1e-30)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def sequence_parallel_attention(q, k, v, mesh=None, causal=False, scale=None,
                                axis_name="sep"):
    """Convenience wrapper: full arrays in, shard_map over the sequence
    axis, ring attention inside. The batch dim keeps its data-parallel
    sharding (dp and the ZeRO 'sharding' axis both split batch,
    reference topology.py), so sep composes with dp/ZeRO in one step."""
    from jax.sharding import PartitionSpec as P

    from ..distributed.collective import shard_map

    from ..distributed import mesh as _mesh

    mesh = mesh or _mesh.get_mesh()
    batch_axes = tuple(a for a in ("dp", "sharding")
                       if a in mesh.axis_names and mesh.shape[a] > 1)
    spec = P(batch_axes if batch_axes else None, axis_name, None, None)
    fn = shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, axis_name=axis_name,
                                          causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)
