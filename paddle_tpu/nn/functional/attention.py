"""Attention functionals.

The reference ships fused CUDA attention (operators/fused/fused_attention_op)
and sparse attention; here the TPU path is a Pallas flash-attention kernel
(paddle_tpu/kernels/flash_attention.py) with a pure-XLA fallback that still
fuses well. Long-context ring attention lives in paddle_tpu/parallel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.dispatch import primitive

_A = jnp.asarray


def _sdpa_reference(q, k, v, mask=None, dropout_p=0.0, causal=False, scale=None):
    # q,k,v: [B, N, H, D] (paddle convention: batch, seq, heads, head_dim)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    logits = jnp.einsum("bnhd,bmhd->bhnm", qf, kf) * scale
    if causal:
        # start-aligned (query i attends keys j <= i) — the ONE causal
        # convention across this fallback, the Pallas kernels, and ring
        # attention (kernels/flash_attention.py docstring). Cached decode
        # must pass an explicit end-aligned mask instead of is_causal
        # (models/llama.py does).
        n, m = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((n, m), bool))
        logits = jnp.where(cm, logits, -1e30)
    if mask is not None:
        mask = _A(mask)
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -1e30)
        else:
            logits = logits + mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhnm,bmhd->bnhd", probs.astype(v.dtype), v)
    return out


def _flash_on_mesh(q, k, v, causal, scale):
    """The flash kernel, per shard when the traced step spans devices.

    GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map"), so on a mesh of more than one device the kernel runs
    inside a shard_map over what attention is independent in: batch over
    the data-parallel axes ('dp', 'sharding') and heads over 'mp' — no
    collective is needed. The mesh is the one the step builder scoped
    for this trace (distributed/mesh.py scoped_mesh). Axes that an
    enclosing shard_map already made manual (the quantized grad-sync
    body) are left out; a dim the axes do not divide stays whole."""
    from ...kernels.flash_attention import flash_attention as _fa

    from ...distributed import mesh as _mesh

    mesh = _mesh.current_mesh()
    if mesh is None or mesh.size == 1 \
            or not isinstance(q, jax.core.Tracer):
        # no mesh built (plain single-device jit), one device, or an
        # eager call on concrete arrays
        return _fa(q, k, v, causal=causal, scale=scale)
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)

    def axes_for(names, dim):
        axes = tuple(a for a in names if a in mesh.axis_names
                     and a not in manual and mesh.shape[a] > 1)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        return axes if axes and dim % size == 0 else None

    batch = axes_for(("dp", "sharding"), q.shape[0])
    heads = axes_for(("mp",), q.shape[2])
    if batch is None and heads is None:
        return _fa(q, k, v, causal=causal, scale=scale)
    from jax.sharding import PartitionSpec as P

    spec = P(batch, None, heads, None)
    return jax.shard_map(
        lambda q_, k_, v_: _fa(q_, k_, v_, causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)(q, k, v)


@primitive
def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, scale=None,
                                 training=True, _warn_rect_causal=True):
    """Scaled dot-product attention over [B, N, H, D] inputs (reference
    nn/functional/flash_attention.py convention).

    Causal convention: ``is_causal=True`` applies a START-aligned mask —
    query i attends keys j <= i — uniformly across the XLA fallback, the
    Pallas flash kernels, and ring attention. This differs from the
    FA2/PyTorch bottom-right (end-aligned) convention when
    ``q_len != kv_len``: for cached decode, pass an explicit end-aligned
    ``attn_mask`` instead of ``is_causal`` (see models/llama.py).
    A warning is emitted for the ambiguous rectangular-causal case
    (``_warn_rect_causal=False`` silences it where start-aligned truly is
    intended, e.g. prefill against a preallocated decode cache).
    """
    q, k, v = _A(query), _A(key), _A(value)
    if (is_causal and attn_mask is None and _warn_rect_causal
            and q.shape[1] != k.shape[1]):
        import warnings

        warnings.warn(
            "scaled_dot_product_attention: is_causal=True with "
            "q_len != kv_len uses START-aligned masking (query i "
            "attends keys j <= i). For cached decode (bottom-right "
            "alignment), pass an explicit end-aligned attn_mask.",
            stacklevel=2)
    from ...core import flags as _flags

    min_d = _flags.get_flags("FLAGS_flash_min_head_dim")[
        "FLAGS_flash_min_head_dim"]
    use_flash = (
        jax.default_backend() == "tpu"
        and attn_mask is None
        and dropout_p == 0.0
        # validated head_dims only: 128-multiples (run on the chip),
        # exactly 64 (kernel-exact and Mosaic-compiled, flag-gated until
        # a ledger row decides it) and 192 (latent attention's expanded
        # q/k beside a 128-wide v, run on the chip) — NOT every
        # 64-multiple (320 is an untested lane layout)
        and (q.shape[-1] % 128 == 0 or q.shape[-1] in (64, 192))
        and q.shape[-1] >= min_d
        and q.shape[1] % 128 == 0
        and k.shape[1] % 128 == 0
    )
    if use_flash:
        # no catch: on a TPU a flash path that cannot be traced is an
        # error, not a reason to run the reference SDPA unannounced
        return _flash_on_mesh(q, k, v, is_causal, scale)
    return _sdpa_reference(q, k, v, mask=attn_mask, dropout_p=dropout_p,
                           causal=is_causal, scale=scale)


@primitive
def sequence_parallel_attention(query, key, value, is_causal=True,
                                scale=None, axis_name="sep"):
    """Ring attention over the 'sep' mesh axis (kernels/ring_attention.py
    — sequence/context parallelism, the capability the reference snapshot
    lacks, SURVEY §5). Falls back to regular attention when the mesh has
    no sep axis, so models can enable it unconditionally."""
    q, k, v = _A(query), _A(key), _A(value)
    from ...distributed import mesh as _mesh

    mesh = _mesh.get_mesh()
    if (axis_name not in mesh.axis_names
            or mesh.shape.get(axis_name, 1) <= 1):
        return scaled_dot_product_attention.raw_fn(
            q, k, v, is_causal=is_causal, scale=scale)
    from ...kernels.ring_attention import (
        sequence_parallel_attention as _ring,
    )

    return _ring(q, k, v, mesh=mesh, causal=is_causal, scale=scale,
                 axis_name=axis_name)


@primitive
def sparse_attention(query, key, value, sparse_csr_offset=None,
                     sparse_csr_columns=None, attn_mask=None):
    # Block-sparse attention degenerates to dense + mask on TPU; packed
    # variable-length serving goes through variable_length_attention
    # (segment-masked flash kernel).
    q, k, v = _A(query), _A(key), _A(value)
    return _sdpa_reference(q, k, v, mask=attn_mask)


@primitive
def variable_length_attention(query, key, value, seq_lens=None,
                              segment_ids=None, is_causal=True,
                              scale=None):
    """Ragged/packed attention (reference varlen fused attention,
    flash_attn_unpadded / variable_length_memory_efficient_attention):
    multiple sequences packed along one axis; tokens attend only within
    their own sequence. Provide per-batch `seq_lens` (list of lengths
    summing to N, converted to segment ids) or `segment_ids` [B, N]."""
    q, k, v = _A(query), _A(key), _A(value)
    if segment_ids is None:
        if seq_lens is None:
            raise ValueError("need seq_lens or segment_ids")
        import numpy as _np

        lens = _np.asarray(seq_lens)
        if lens.ndim == 1:
            lens = lens[None]
        total = q.shape[1]
        segs = _np.zeros((lens.shape[0], total), _np.int32)
        for bi in range(lens.shape[0]):
            off = 0
            for si, L in enumerate(lens[bi]):
                segs[bi, off:off + int(L)] = si
                off += int(L)
            # tail padding (if any) gets its own segment id
            segs[bi, off:] = lens.shape[1]
        segment_ids = jnp.asarray(segs)
    from ...kernels.flash_attention import flash_attention as _fa

    return _fa(q, k, v, causal=is_causal, scale=scale,
               segment_ids=_A(segment_ids))
