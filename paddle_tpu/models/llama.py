"""Llama-family decoder — the flagship distributed config (BASELINE.md:
GPT/Llama-7B TP+PP hybrid, tokens/sec/chip).

TPU-native design decisions:
- weights bf16, RMSNorm/softmax statistics fp32 (MXU-native mixed precision)
- attention via F.scaled_dot_product_attention → Pallas flash kernel on TPU
- TP via Column/RowParallelLinear sharding specs ('mp' axis): q/k/v/gate/up
  column-split, o/down row-split — the Megatron layout the reference builds
  from c_split/c_concat ops (fleet/layers/mpu/mp_layers.py)
- sequence axis carries a 'sep' sharding constraint for long-context
  (ring attention in paddle_tpu/kernels/ring_attention.py)
- the decode cache is functional (returned, not mutated) so the generation
  loop jits into one XLA while-loop
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import ops
from ..core.dispatch import primitive
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer
from ..nn.layers.common import Embedding
from ..nn.layers.container import LayerList
from ..nn.layers.norm import RMSNorm
from .generation import (
    DecodeCache,
    GenerationMixin,
    cache_update,
    decode_mask as _decode_mask,
    masked_decode_attention,
    rows_at,
)
from ..parallel.mp_layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
    mark_sharding,
)


class LlamaConfig:
    def __init__(self, vocab_size=32000, hidden_size=4096,
                 intermediate_size=11008, num_hidden_layers=32,
                 num_attention_heads=32, num_key_value_heads=None,
                 max_position_embeddings=4096, rms_norm_eps=1e-6,
                 rope_theta=10000.0, tie_word_embeddings=False,
                 use_parallel=True, dtype="float32",
                 fuse_attention_qkv=False, fuse_mlp=False,
                 sequence_parallel=False, recompute=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads or num_attention_heads
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.tie_word_embeddings = tie_word_embeddings
        self.use_parallel = use_parallel
        self.dtype = dtype
        # MXU shape optimization (reference incubate fused_attention /
        # fused_feedforward analog): one [h, (q+k+v)] and one [h, 2*ffn]
        # matmul instead of 3+2 narrow ones — at hidden sizes where K/N <
        # ~1024 the wider N keeps the systolic array fed (measured on v5e:
        # K=N=768 sustains ~34 TF/s, N=2304 nearly doubles that).
        self.fuse_attention_qkv = fuse_attention_qkv
        self.fuse_mlp = fuse_mlp
        # long-context: shard the sequence axis over 'sep' and run ring
        # attention (kernels/ring_attention.py) — capability the
        # reference snapshot lacks (SURVEY §5)
        self.sequence_parallel = sequence_parallel
        # activation recompute per decoder layer (reference fleet
        # recompute / --recompute flag): a layer keeps its input and its
        # attention output, two [B, S, H] tensors, and the backward pass
        # runs the rest of its forward again — ~1/3 extra FLOPs less the
        # attention kernel's, for O(layers * B*S*H) activation memory;
        # required to train ~1B+ params on one 16GB v5e chip
        self.recompute = recompute

    @classmethod
    def tiny(cls, **kw):
        d = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                 num_hidden_layers=2, num_attention_heads=4,
                 max_position_embeddings=128)
        d.update(kw)
        return cls(**d)

    @classmethod
    def llama_7b(cls, **kw):
        return cls(**kw)


@primitive
def rope_apply(q, k, theta, position_offset=0):
    """Rotary position embedding, fused on q and k. q,k: [B, S, H, D].

    Half-split ("rotate half") pairing: dim i rotates with dim i + D/2.
    On TPU this lowers to two contiguous lane slices + concat instead of
    the strided even/odd gather of the interleaved convention — measured
    3x faster fwd+bwd at the bench shape (8x1024x6x128) for identical
    positional geometry (the pairing of dims is a convention, not
    semantics; attention scores are invariant to which pairing is used
    as long as q and k share it).

    position_offset may be a scalar (one offset for the whole batch —
    training/generate) or a [B] int vector (per-row offsets — the
    serving engine's continuous-batching decode, where every slot sits
    at its own sequence position)."""
    q = jnp.asarray(q)
    k = jnp.asarray(k)
    d = q.shape[-1]
    seq = q.shape[1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    off = (position_offset if isinstance(position_offset, (int, float))
           else jnp.asarray(position_offset))
    if getattr(off, "ndim", 0):
        # per-row offsets: pos [B, S] -> freqs [B, S, D/2], cos/sin
        # [B, S, 1, D] (elementwise identical to the scalar path per row)
        pos = (off.astype(jnp.float32)[:, None]
               + jnp.arange(seq, dtype=jnp.float32)[None, :])
        freqs = pos[..., None] * inv_freq
        cos = jnp.concatenate([jnp.cos(freqs), jnp.cos(freqs)],
                              axis=-1)[:, :, None, :]
        sin = jnp.concatenate([jnp.sin(freqs), jnp.sin(freqs)],
                              axis=-1)[:, :, None, :]
        return _rope_rot(q, cos, sin), _rope_rot(k, cos, sin)
    pos = jnp.arange(seq, dtype=jnp.float32) + off
    freqs = jnp.outer(pos, inv_freq)  # [S, D/2]
    cos = jnp.concatenate([jnp.cos(freqs), jnp.cos(freqs)],
                          axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(freqs), jnp.sin(freqs)],
                          axis=-1)[None, :, None, :]

    return _rope_rot(q, cos, sin), _rope_rot(k, cos, sin)


def _rope_rot(x, cos, sin):
    d = x.shape[-1]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :d // 2], xf[..., d // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (xf * cos + rotated * sin).astype(x.dtype)


class LlamaAttention(Layer):
    def __init__(self, config):
        super().__init__()
        c = config
        self.num_heads = c.num_attention_heads
        self.num_kv_heads = c.num_key_value_heads
        self.head_dim = c.hidden_size // c.num_attention_heads
        self.rope_theta = c.rope_theta
        self.sequence_parallel = c.sequence_parallel
        self.fuse_qkv = c.fuse_attention_qkv and not c.use_parallel
        if self.fuse_qkv:
            from ..nn.layers.common import Linear

            q_dim = self.num_heads * self.head_dim
            kv_dim = self.num_kv_heads * self.head_dim
            self._qkv_splits = (q_dim, q_dim + kv_dim)
            self.qkv_proj = Linear(c.hidden_size, q_dim + 2 * kv_dim,
                                   bias_attr=False)
            self.o_proj = Linear(q_dim, c.hidden_size, bias_attr=False)
        elif c.use_parallel:
            self.q_proj = ColumnParallelLinear(
                c.hidden_size, self.num_heads * self.head_dim,
                has_bias=False, gather_output=False)
            self.k_proj = ColumnParallelLinear(
                c.hidden_size, self.num_kv_heads * self.head_dim,
                has_bias=False, gather_output=False)
            self.v_proj = ColumnParallelLinear(
                c.hidden_size, self.num_kv_heads * self.head_dim,
                has_bias=False, gather_output=False)
            self.o_proj = RowParallelLinear(
                self.num_heads * self.head_dim, c.hidden_size,
                has_bias=False, input_is_parallel=True)
        else:
            from ..nn.layers.common import Linear

            self.q_proj = Linear(c.hidden_size,
                                 self.num_heads * self.head_dim,
                                 bias_attr=False)
            self.k_proj = Linear(c.hidden_size,
                                 self.num_kv_heads * self.head_dim,
                                 bias_attr=False)
            self.v_proj = Linear(c.hidden_size,
                                 self.num_kv_heads * self.head_dim,
                                 bias_attr=False)
            self.o_proj = Linear(self.num_heads * self.head_dim,
                                 c.hidden_size, bias_attr=False)

    def forward(self, x, cache=None, position_offset=0):
        b, s, _ = x.shape
        if self.fuse_qkv:
            qkv = self.qkv_proj(x)
            s1, s2 = self._qkv_splits
            q = qkv[:, :, :s1].reshape([b, s, self.num_heads, self.head_dim])
            k = qkv[:, :, s1:s2].reshape(
                [b, s, self.num_kv_heads, self.head_dim])
            v = qkv[:, :, s2:].reshape(
                [b, s, self.num_kv_heads, self.head_dim])
        else:
            q = self.q_proj(x).reshape(
                [b, s, self.num_heads, self.head_dim])
            k = self.k_proj(x).reshape(
                [b, s, self.num_kv_heads, self.head_dim])
            v = self.v_proj(x).reshape(
                [b, s, self.num_kv_heads, self.head_dim])
        q, k = rope_apply(q, k, theta=self.rope_theta,
                          position_offset=position_offset)
        if cache is not None and hasattr(cache, "update_and_attend"):
            # external-cache hook (serving): the ENGINE owns a paged KV
            # cache; the per-layer view writes this step's K/V into its
            # pool pages and runs ragged paged attention (GQA repeat
            # happens inside the view/kernel — the pool never stores
            # repeated heads). serving/kv_cache.py.
            ctx, cache = cache.update_and_attend(q, k, v)
            out = ctx.reshape([b, s, self.num_heads * self.head_dim])
            return self.o_proj(out), cache
        mask = None
        if isinstance(cache, DecodeCache):
            # static-buffer decode path (generation.py): ONE compiled
            # shape for the whole generation, no concat-regrow recompiles
            cache, k, v = cache_update(cache, k, v, position_offset)
            mask = _decode_mask(position_offset, s, k.shape[1])
        elif cache is not None:
            pk, pv = cache
            k = ops.manipulation.concat([pk, k], axis=1)
            v = ops.manipulation.concat([pv, v], axis=1)
            cache = (k, v)
            # end-aligned: the s new queries sit at the END of the kv
            # window (one shared masking convention — generation.py)
            mask = _decode_mask(k.shape[1] - s, s, k.shape[1])
        if self.num_kv_heads != self.num_heads:
            rep = self.num_heads // self.num_kv_heads
            k = ops.manipulation.repeat_interleave(k, rep, axis=2)
            v = ops.manipulation.repeat_interleave(v, rep, axis=2)
        if self.sequence_parallel and cache is None:
            # ring attention over the 'sep' axis (falls back to flash
            # attention when the mesh has no sep axis)
            out = F.sequence_parallel_attention(q, k, v, is_causal=True)
        elif mask is not None:
            out = masked_decode_attention(q, k, v, mask)
        else:
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        out = out.reshape([b, s, self.num_heads * self.head_dim])
        out = self.o_proj(out)
        if cache is not None:
            return out, cache
        return out


class LlamaMLP(Layer):
    def __init__(self, config):
        super().__init__()
        c = config
        self.fuse_mlp = c.fuse_mlp and not c.use_parallel
        if self.fuse_mlp:
            from ..nn.layers.common import Linear

            self._inter = c.intermediate_size
            self.gate_up_proj = Linear(c.hidden_size,
                                       2 * c.intermediate_size,
                                       bias_attr=False)
            self.down_proj = Linear(c.intermediate_size, c.hidden_size,
                                    bias_attr=False)
        elif c.use_parallel:
            self.gate_proj = ColumnParallelLinear(
                c.hidden_size, c.intermediate_size, has_bias=False,
                gather_output=False)
            self.up_proj = ColumnParallelLinear(
                c.hidden_size, c.intermediate_size, has_bias=False,
                gather_output=False)
            self.down_proj = RowParallelLinear(
                c.intermediate_size, c.hidden_size, has_bias=False,
                input_is_parallel=True)
        else:
            from ..nn.layers.common import Linear

            self.gate_proj = Linear(c.hidden_size, c.intermediate_size,
                                    bias_attr=False)
            self.up_proj = Linear(c.hidden_size, c.intermediate_size,
                                  bias_attr=False)
            self.down_proj = Linear(c.intermediate_size, c.hidden_size,
                                    bias_attr=False)

    def forward(self, x):
        if self.fuse_mlp:
            gu = self.gate_up_proj(x)
            gate, up = gu[:, :, :self._inter], gu[:, :, self._inter:]
            return self.down_proj(F.silu(gate) * up)
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(Layer):
    def __init__(self, config):
        super().__init__()
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, x, cache=None, position_offset=0):
        # named scopes cost nothing but a compiled instruction's
        # op_name: a device trace can then tell this block's time apart
        with jax.named_scope("attn"):
            h = self.input_layernorm(x)
            if cache is not None:
                attn, cache = self.self_attn(h, cache, position_offset)
            else:
                attn = self.self_attn(h)
            x = x + attn
        with jax.named_scope("mlp"):
            x = x + self.mlp(self.post_attention_layernorm(x))
        if cache is not None:
            return x, cache
        return x


def _remat_layer(layer, x):
    """Per-layer activation recompute. Two engines (same split as
    static/__init__.py RecomputeContext vs fleet/recompute.py):
    - compiled path (CompiledTrainStep traces under no_grad + jax.grad):
      wrap the layer body in jax.checkpoint. A recomputed layer keeps its
      input and its attention output (the flash kernel's `out` and `lse`,
      kernels/flash_attention.py FLASH_SAVED_NAMES): the backward pass
      rebuilds norms, projections, rope and the MLP, but does not run the
      forward attention kernel a second time only to hand its output to
      the backward kernels. Where attention takes the XLA fallback there
      is nothing under those names and only the input is kept;
    - eager-tape path: route through the autograd engine's recompute(),
      which keeps the input alone.
    """
    from ..core.dispatch import tape_enabled

    if tape_enabled():
        from ..distributed.fleet.recompute import recompute

        return recompute(layer, x)
    import jax

    from ..core.tensor import Tensor
    from ..kernels.flash_attention import FLASH_SAVED_NAMES

    def body(xv, _l=layer):
        return _l(Tensor(xv))._value

    keep = jax.checkpoint_policies.save_only_these_names(*FLASH_SAVED_NAMES)
    return Tensor(jax.checkpoint(body, policy=keep)(x._value))


class LlamaModel(Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        Emb = VocabParallelEmbedding if config.use_parallel else Embedding
        self.embed_tokens = Emb(config.vocab_size, config.hidden_size)
        self.layers = LayerList(
            [LlamaDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)

    def _sep_spec(self):
        """(batch_axes, 'sep', None) when the mesh has a >1 'sep' axis."""
        if not self.config.sequence_parallel:
            return None
        from ..distributed import mesh as _mesh

        mesh = _mesh.get_mesh()
        if "sep" not in mesh.axis_names or mesh.shape["sep"] <= 1:
            return None
        batch = tuple(a for a in ("dp", "sharding")
                      if a in mesh.axis_names and mesh.shape[a] > 1)
        return (batch if batch else None, "sep", None)

    def forward(self, input_ids, caches=None, position_offset=0,
                logits_at=None):
        with jax.named_scope("embed"):
            x = self.embed_tokens(input_ids)
            # dp on batch, sep on sequence when those axes exist
            spec = self._sep_spec() if caches is None else None
            if spec is not None:
                x = mark_sharding(x, *spec)
        new_caches = []
        use_remat = self.config.recompute and caches is None
        for i, layer in enumerate(self.layers):
            with jax.named_scope("layer_%d" % i):
                if caches is not None:
                    x, c = layer(x, caches[i], position_offset)
                    new_caches.append(c)
                elif use_remat:
                    x = _remat_layer(layer, x)
                else:
                    x = layer(x)
        with jax.named_scope("lm_head"):
            x = self.norm(rows_at(x, logits_at))
        if caches is not None:
            return x, new_caches
        return x


class LlamaForCausalLM(GenerationMixin, Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if config.use_parallel:
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False)
        else:
            from ..nn.layers.common import Linear

            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  bias_attr=False)

    def _maybe_fused_ce(self, h, labels):
        """Scalar mean-CE loss via the streaming lm_head+CE kernel
        (kernels/fused_ce.py) when FLAGS_fused_lm_head_ce is on, the
        token count tiles, and we are on a TRACED (compiled-step) path
        — the custom_vjp carries grads through jax.grad but the eager
        tape cannot see through it. h must already be final-normed.
        Returns None when the fused path does not apply."""
        from ..core.tensor import Tensor
        from ..kernels.fused_ce import fused_ce_applies, fused_mean_ce

        hv = h._value if isinstance(h, Tensor) else h
        if not fused_ce_applies(hv, self.config.use_parallel):
            return None
        B, S, H = hv.shape
        lv = labels._value if isinstance(labels, Tensor) \
            else jnp.asarray(labels)
        return Tensor(fused_mean_ce(hv.reshape(B * S, H),
                                    self.lm_head.weight._value,
                                    lv.reshape(B * S)))

    def forward(self, input_ids, labels=None):
        h = self.llama(input_ids)
        with jax.named_scope("lm_head"):
            if labels is not None:
                fused = self._maybe_fused_ce(h, labels)
                if fused is not None:
                    return fused
            logits = self.lm_head(h)
            if labels is not None:
                if self.config.use_parallel:
                    # vocab stays mp-sharded through the loss (sharded-vocab
                    # c_softmax_with_cross_entropy, mp_layers.py) — no
                    # full-vocab gather under the partitioner
                    from ..parallel.mp_layers import (
                        parallel_softmax_cross_entropy,
                    )

                    flat = labels.reshape([-1])
                    per_tok = parallel_softmax_cross_entropy(
                        logits.reshape([-1, self.config.vocab_size]), flat)
                    # mean over VALID tokens (same contract as the
                    # F.cross_entropy branch: ignore_index rows excluded)
                    valid = (flat != -100).astype(per_tok.dtype)
                    return per_tok.sum() / valid.sum().clip(min=1.0)
                loss = F.cross_entropy(
                    logits.reshape([-1, self.config.vocab_size]),
                    labels.reshape([-1]))
                return loss
            return logits

    def generate_step(self, input_ids, caches, position_offset,
                      logits_at=None):
        """Single decode step with functional cache; ``logits_at``
        (generation.rows_at) names the one row a sequence to norm and
        project."""
        h, caches = self.llama(input_ids, caches, position_offset,
                               logits_at)
        with jax.named_scope("lm_head"):
            logits = self.lm_head(h)
        return logits, caches

    def max_decode_len(self):
        return self.config.max_position_embeddings

    def paged_cache_spec(self):
        """KV geometry for the serving engine's paged cache (the engine
        owns the cache — serving/engine.py)."""
        from ..serving.kv_cache import KVPages

        cfg = self.config
        return [KVPages(cfg.num_key_value_heads,
                        cfg.hidden_size // cfg.num_attention_heads,
                        cfg.dtype)] * cfg.num_hidden_layers

    def init_decode_caches(self, batch, total_len):
        cfg = self.config
        n_kv = cfg.num_key_value_heads
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        kv_dtype = jnp.dtype(cfg.dtype)
        return [DecodeCache(
            jnp.zeros((batch, total_len, n_kv, head_dim), kv_dtype),
            jnp.zeros((batch, total_len, n_kv, head_dim), kv_dtype))
            for _ in range(cfg.num_hidden_layers)]

    # -- pipeline-parallel protocol (parallel/pipeline_parallel.py) --------

    def pipeline_blocks(self):
        """The identical decoder blocks the ring pipeline stacks over 'pp'."""
        return list(self.llama.layers)

    def forward_embed(self, input_ids):
        with jax.named_scope("embed"):
            return self.llama.embed_tokens(input_ids)

    def forward_head(self, h):
        with jax.named_scope("lm_head"):
            return self.lm_head(self.llama.norm(h))

    def forward_head_loss(self, h, labels):
        """Fused pipeline loss tail (mean CE over non-ignored tokens —
        forward(labels=...)'s contract). Returns None so the caller
        falls back to forward_head + its loss_fn when the kernel path
        does not apply. Consulted only under PipelinedTrainStep's
        EXPLICIT fused_loss_tail=True opt-in: it replaces the step's
        loss_fn, which is only valid for the plain-CE objective."""
        with jax.named_scope("lm_head"):
            return self._maybe_fused_ce(self.llama.norm(h), labels)
