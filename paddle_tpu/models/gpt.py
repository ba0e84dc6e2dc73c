"""GPT-style decoder (ERNIE/GPT configs; reference ecosystem models built on
paddle.nn.TransformerDecoder). LayerNorm + learned positions + GELU MLP."""
from __future__ import annotations

import jax.numpy as jnp

from .. import ops
from ..nn import functional as F
from .generation import (
    DecodeCache,
    GenerationMixin,
    cache_update,
    decode_mask as _decode_mask,
    masked_decode_attention,
    rows_at,
)
from ..nn.layer import Layer
from ..nn.layers.common import Dropout, Embedding, Linear
from ..nn.layers.container import LayerList
from ..nn.layers.norm import LayerNorm
from ..parallel.mp_layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
)


class GPTBlock(Layer):
    def __init__(self, hidden, heads, ffn, dropout=0.0, use_parallel=False,
                 moe_experts=0, moe_top_k=2):
        super().__init__()
        self.ln1 = LayerNorm(hidden)
        self.ln2 = LayerNorm(hidden)
        self.heads = heads
        self.head_dim = hidden // heads
        self.is_moe = moe_experts > 0
        if use_parallel:
            self.qkv = ColumnParallelLinear(hidden, 3 * hidden,
                                            gather_output=False)
            self.proj = RowParallelLinear(hidden, hidden,
                                          input_is_parallel=True)
        else:
            self.qkv = Linear(hidden, 3 * hidden)
            self.proj = Linear(hidden, hidden)
        if self.is_moe:
            from ..parallel.moe import MoELayer

            self.moe = MoELayer(hidden, ffn, moe_experts, top_k=moe_top_k)
        elif use_parallel:
            self.fc1 = ColumnParallelLinear(hidden, ffn, gather_output=False)
            self.fc2 = RowParallelLinear(ffn, hidden, input_is_parallel=True)
        else:
            self.fc1 = Linear(hidden, ffn)
            self.fc2 = Linear(ffn, hidden)
        self.drop = Dropout(dropout)

    def forward(self, x, cache=None, position_offset=0):
        b, s, hdim = x.shape
        h = self.ln1(x)
        qkv = self.qkv(h).reshape([b, s, 3, self.heads, self.head_dim])
        q, k, v = ops.manipulation.unbind(qkv, axis=2)
        if cache is not None and hasattr(cache, "update_and_attend"):
            # external-cache hook: the serving engine's paged-KV view
            # writes K/V into its pool and runs ragged paged attention
            # (serving/kv_cache.py)
            attn, cache = cache.update_and_attend(q, k, v)
        elif isinstance(cache, DecodeCache):
            cache, k, v = cache_update(cache, k, v, position_offset)
            attn = masked_decode_attention(
                q, k, v, _decode_mask(position_offset, s, k.shape[1]))
        elif cache is not None:
            raise TypeError(
                "GPTBlock decode takes DecodeCache buffers "
                "(init_decode_caches); got %r" % type(cache).__name__)
        else:
            attn = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        attn = attn.reshape([b, s, hdim])
        x = x + self.drop(self.proj(attn))
        h = self.ln2(x)
        if self.is_moe:
            x = x + self.drop(self.moe(h))
        else:
            x = x + self.drop(self.fc2(F.gelu(self.fc1(h))))
        if cache is not None:
            return x, cache
        return x


class GPTModel(GenerationMixin, Layer):
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, ffn_size=None, max_seq_len=1024, dropout=0.0,
                 use_parallel=False, moe_experts=0, moe_every=2,
                 moe_top_k=2, moe_aux_coeff=0.01):
        """moe_experts > 0 turns every `moe_every`-th block into a
        dropless MoE block (parallel/moe.py; every expert held here)."""
        super().__init__()
        ffn_size = ffn_size or 4 * hidden_size
        Emb = VocabParallelEmbedding if use_parallel else Embedding
        self.wte = Emb(vocab_size, hidden_size)
        self.wpe = Embedding(max_seq_len, hidden_size)
        self.blocks = LayerList([
            GPTBlock(hidden_size, num_heads, ffn_size, dropout, use_parallel,
                     moe_experts=(moe_experts
                                  if moe_experts and i % moe_every == 1
                                  else 0),
                     moe_top_k=moe_top_k)
            for i in range(num_layers)])
        self.ln_f = LayerNorm(hidden_size)
        self.vocab_size = vocab_size
        self.moe_aux_coeff = moe_aux_coeff

    def moe_aux_loss(self):
        """Sum of load-balancing losses from the MoE blocks this forward."""
        total = None
        for blk in self.blocks:
            if getattr(blk, "is_moe", False) and blk.moe.aux_loss is not None:
                total = (blk.moe.aux_loss if total is None
                         else total + blk.moe.aux_loss)
        return total

    def forward(self, input_ids, labels=None, caches=None,
                position_offset=0, logits_at=None):
        import paddle_tpu as P

        b, s = input_ids.shape
        off = position_offset
        offv = off._value if hasattr(off, "_value") else off
        if getattr(offv, "ndim", 0):
            # per-row offsets (serving continuous batching): [B] -> [B, 1]
            # so the learned position lookup broadcasts to [B, S]
            off = P.Tensor(jnp.asarray(offv)[:, None].astype(jnp.int64))
        pos = P.arange(s, dtype="int64").unsqueeze(0) + off
        x = self.wte(input_ids) + self.wpe(pos)
        new_caches = []
        for i, blk in enumerate(self.blocks):
            if caches is not None:
                x, c = blk(x, caches[i], position_offset)
                new_caches.append(c)
            else:
                x = blk(x)
        x = self.ln_f(rows_at(x, logits_at))
        logits = P.matmul(x, self.wte.weight, transpose_y=True)
        if caches is not None:
            return logits, new_caches
        if labels is not None:
            loss = F.cross_entropy(
                logits.reshape([-1, self.vocab_size]), labels.reshape([-1]))
            aux = self.moe_aux_loss()
            if aux is not None:
                loss = loss + aux * self.moe_aux_coeff
            return loss
        return logits

    def generate_step(self, input_ids, caches, position_offset,
                      logits_at=None):
        """Single decode step with functional cache (GenerationMixin);
        ``logits_at`` (generation.rows_at) names the one row a sequence
        to norm and project."""
        return self.forward(input_ids, caches=caches,
                            position_offset=position_offset,
                            logits_at=logits_at)

    def max_decode_len(self):
        return self.wpe.num_embeddings

    def paged_cache_spec(self):
        """KV geometry for the serving engine's paged cache."""
        from ..serving.kv_cache import KVPages

        return [KVPages(self.blocks[0].heads, self.blocks[0].head_dim,
                        str(self.wte.weight._value.dtype))] * len(
                            self.blocks)

    def init_decode_caches(self, batch, total_len):
        head_dim = self.blocks[0].head_dim
        heads = self.blocks[0].heads
        dt = self.wte.weight._value.dtype  # cache in the model's dtype
        return [DecodeCache(
            jnp.zeros((batch, total_len, heads, head_dim), dt),
            jnp.zeros((batch, total_len, heads, head_dim), dt))
            for _ in range(len(self.blocks))]
