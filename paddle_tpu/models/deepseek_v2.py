"""DeepSeek-V2: multi-head latent attention (MLA) over a sparse mixture
of experts with group-limited routing (deepseek-ai/DeepSeek-V2
``config.json``; "DeepSeek-V2: A Strong, Economical, and Efficient
Mixture-of-Experts Language Model", arXiv:2405.04434).

What a layer keeps for a sequence is ONE row a token, shared by every
head: the normed compressed key/value latent ``c_kv`` [kv_lora_rank]
followed by the roped key ``k_pe`` [qk_rope_head_dim]
(``paged_cache_spec``: ``LatentPages`` a layer, serving/kv_cache.py).
The attention runs in two forms that give the same numbers, and the
cache hook says which it takes (``cache.absorbed``):

- expanded (a prompt, or no cache): ``[k_nope_h | v_h] = c_kv W_ukv,h``
  for every head, ``k_h = [k_nope_h | k_pe]``, 192-wide q/k and 128-wide
  v through the flash kernel;
- absorbed (a decode step): ``q_lat_h = [q_nope_h W_uk,h^T | q_pe_h]``
  against the cached rows themselves, ``o_h = (sum p row[:rank])
  W_uv,h`` (serving/kernels/mla_attention.py). ``W_uk,h`` and ``W_uv,h``
  are views of the one ``kv_b_proj``; no second copy is kept.

Rotary: pairs (2i, 2i+1) of the 64 rope dims, as the published code
pairs them; the rotated halves come out de-interleaved ([even | odd]),
in q and k alike, so every score is what rotating in place gives. YaRN
(factor 40 over 4096 original positions) blends each pair's frequency
with its 40th and multiplies the softmax scale by ``mscale^2``.

Layer ``i < first_k_dense_replace`` has a dense SwiGLU MLP; the others
route top-``num_experts_per_tok`` inside the ``topk_group`` best of
``n_group`` expert groups, weights not renormalised and times
``routed_scaling_factor`` (parallel/moe.py), beside
``n_shared_experts`` ungated shared experts fused into one SwiGLU. The
experts are told which of them live here (``experts_held``); the shared
experts and the router are whole, as every chip of an expert-parallel
deployment computes them alike. Inference code on raw arrays; the model
hands the engine its expert layers' step counters through
``moe_step_stats``.

Column order of the fused projections (a convention): ``gate_up`` and an
expert's ``w1`` are [gate | up]; ``q_b_proj`` is per head [nope | rope];
``kv_a_proj_with_mqa`` is [latent | rope key]; ``kv_b_proj`` is per head
[k_nope | v].
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..nn import initializer as I
from ..nn.layer import Layer
from ..nn.functional.norm import rms_norm as _rms_norm
from ..nn.layers.container import LayerList
from ..parallel.moe import MoELayer, moe_forward, swiglu_clamped
from .generation import rows_at

_F32 = jnp.float32
# the Llama family's RMSNorm on raw arrays: x / sqrt(mean(x^2) + eps) * w,
# statistics in float32
rms_norm = _rms_norm.raw_fn
YARN_V2 = {"type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
           "mscale": 0.707, "mscale_all_dim": 0.707,
           "original_max_position_embeddings": 4096}


class DeepseekV2Config:
    def __init__(self, vocab_size=102400, hidden_size=5120,
                 intermediate_size=12288, moe_intermediate_size=1536,
                 num_hidden_layers=60, num_attention_heads=128,
                 q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, n_routed_experts=160,
                 n_shared_experts=2, num_experts_per_tok=6, n_group=8,
                 topk_group=3, routed_scaling_factor=16.0,
                 norm_topk_prob=False, first_k_dense_replace=1,
                 rope_theta=10000.0, rope_scaling=None, rms_norm_eps=1e-6,
                 experts_held=None, max_position_embeddings=163840,
                 gated_attention=False, dtype="float32"):
        """``n_routed_experts`` is the router's published width;
        ``experts_held`` (a range, default all) the experts that live
        here. ``vocab_size`` is the number of vocabulary rows held here
        (ids, logits and argmax are over them). ``rope_scaling`` is the
        published YaRN dict (default: DeepSeek-V2's).
        ``gated_attention`` gives the latent attention a per-channel
        output gate (``DeepseekV2Attention``); DeepSeek-V2 has none."""
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.n_group = n_group
        self.topk_group = topk_group
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.first_k_dense_replace = first_k_dense_replace
        self.rope_theta = rope_theta
        self.rope_scaling = dict(YARN_V2 if rope_scaling is None
                                 else rope_scaling)
        self.rms_norm_eps = rms_norm_eps
        self.experts_held = (range(n_routed_experts) if experts_held is None
                             else experts_held)
        self.max_position_embeddings = max_position_embeddings
        self.gated_attention = gated_attention
        self.dtype = dtype

    @classmethod
    def tiny(cls, **kw):
        """Small enough for the CPU, Mosaic-tileable on the chip (the
        latent 128 wide, 8 heads): 1 dense layer and 2 sparse ones, 16
        experts in 4 groups, one group held here."""
        d = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
                 moe_intermediate_size=32, num_hidden_layers=3,
                 num_attention_heads=8, q_lora_rank=48, kv_lora_rank=128,
                 qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
                 n_routed_experts=16, n_shared_experts=2,
                 num_experts_per_tok=3, n_group=4, topk_group=2,
                 routed_scaling_factor=4.0, experts_held=range(4),
                 max_position_embeddings=512)
        d.update(kw)
        return cls(**d)


def _val(x):
    return x._value if isinstance(x, Tensor) else x


def swiglu(x, gate_up, down, limit=None):
    """down(silu(gate) * up) with gate and up side by side in one
    matrix; with ``limit`` both halves clamped first
    (parallel/moe.py ``swiglu_clamped``)."""
    f = down.shape[0]
    gu = jnp.matmul(x, gate_up)
    if limit is None:
        return jnp.matmul(jax.nn.silu(gu[..., :f]) * gu[..., f:], down)
    return jnp.matmul(swiglu_clamped(gu[..., :f], gu[..., f:], limit), down)


# -- YaRN rotary ---------------------------------------------------------------

def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim, theta, scaling):
    """float32 [dim / 2]: each pair's frequency, the plain ``theta^(-2i
    / dim)`` blended with its ``factor``-th by a linear ramp between the
    pairs that turn ``beta_fast`` and ``beta_slow`` times over the
    original context (NTK-by-parts)."""
    factor = scaling["factor"]
    original = scaling["original_max_position_embeddings"]
    plain = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(turns):
        return (dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (plain / factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def rope_pairs(x, position_offset, inv_freq, mscale=1.0):
    """Rotate the pairs (2i, 2i+1) of x [B, T, H, D] at positions
    ``offset + 0..T-1`` (``offset`` a scalar or int [B]); the result is
    de-interleaved: [rotated evens | rotated odds]."""
    b, t, _, d = x.shape
    off = jnp.asarray(position_offset, _F32)
    pos = off.reshape(-1, 1) + jnp.arange(t, dtype=_F32)[None, :]
    angle = pos[..., None] * jnp.asarray(inv_freq)          # [B|1, T, D/2]
    cos = (jnp.cos(angle) * mscale)[:, :, None, :]
    sin = (jnp.sin(angle) * mscale)[:, :, None, :]
    pairs = x.astype(_F32).reshape(x.shape[:-1] + (d // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.concatenate([even * cos - odd * sin,
                            odd * cos + even * sin], -1).astype(x.dtype)


# -- layers --------------------------------------------------------------------

# rows x heads whose expanded q, k and v a prefill holds at a time
# (8192 rows x 32 heads)
_EXPAND_HEAD_ROWS = 1 << 18


def _head_groups(heads, rows):
    """The fewest equal groups of heads with at most
    ``_EXPAND_HEAD_ROWS`` rows x heads in one."""
    for groups in range(1, heads + 1):
        if heads % groups == 0 and rows * heads <= groups * \
                _EXPAND_HEAD_ROWS:
            return groups
    return heads

class DeepseekV2Attention(Layer):
    """Multi-head latent attention; see the module docstring. With the
    config's ``gated_attention`` every head's value output is multiplied
    by ``sigmoid(x W_g)`` (``gate_proj`` [hidden, heads x v_head_dim],
    per channel) before ``o_proj``, in both forms: after the value
    up-projection in the absorbed one."""

    def __init__(self, config):
        super().__init__()
        c = config
        if c.q_lora_rank is None:
            raise ValueError("DeepseekV2Attention: q_lora_rank=None (a "
                             "full-rank query projection) is not built")
        self.heads = c.num_attention_heads
        self.rank = c.kv_lora_rank
        self.nope, self.rope = c.qk_nope_head_dim, c.qk_rope_head_dim
        self.v_dim = c.v_head_dim
        self.eps = c.rms_norm_eps
        rs = c.rope_scaling
        self.inv_freq = yarn_inv_freq(self.rope, c.rope_theta, rs)
        self.rope_mscale = (yarn_mscale(rs["factor"], rs["mscale"])
                            / yarn_mscale(rs["factor"],
                                          rs["mscale_all_dim"]))
        self.scale = ((self.nope + self.rope) ** -0.5
                      * yarn_mscale(rs["factor"],
                                    rs["mscale_all_dim"]) ** 2)
        dt, xavier, ones = c.dtype, I.XavierNormal(), I.Constant(1.0)
        self.q_a_proj = self.create_parameter(
            [c.hidden_size, c.q_lora_rank], dtype=dt,
            default_initializer=xavier)
        self.q_a_layernorm = self.create_parameter(
            [c.q_lora_rank], dtype=dt, default_initializer=ones)
        self.q_b_proj = self.create_parameter(
            [c.q_lora_rank, self.heads * (self.nope + self.rope)],
            dtype=dt, default_initializer=xavier)
        self.kv_a_proj_with_mqa = self.create_parameter(
            [c.hidden_size, self.rank + self.rope], dtype=dt,
            default_initializer=xavier)
        self.kv_a_layernorm = self.create_parameter(
            [self.rank], dtype=dt, default_initializer=ones)
        self.kv_b_proj = self.create_parameter(
            [self.rank, self.heads * (self.nope + self.v_dim)], dtype=dt,
            default_initializer=xavier)
        self.o_proj = self.create_parameter(
            [self.heads * self.v_dim, c.hidden_size], dtype=dt,
            default_initializer=xavier)
        self.gate_proj = None
        if c.gated_attention:
            self.gate_proj = self.create_parameter(
                [c.hidden_size, self.heads * self.v_dim], dtype=dt,
                default_initializer=xavier)

    def forward(self, x, cache=None, position_offset=0):
        b, t, _ = x.shape
        gate = None
        if self.gate_proj is not None:
            gate = jax.nn.sigmoid(jnp.matmul(
                x, self.gate_proj._value).astype(_F32))
        c_q = rms_norm(jnp.matmul(x, self.q_a_proj._value),
                       self.q_a_layernorm._value, self.eps)
        kv_a = jnp.matmul(x, self.kv_a_proj_with_mqa._value)
        c_kv = rms_norm(kv_a[..., :self.rank], self.kv_a_layernorm._value,
                        self.eps)
        k_pe = rope_pairs(kv_a[:, :, None, self.rank:], position_offset,
                          self.inv_freq, self.rope_mscale)
        if cache is not None:
            # what the cache keeps of a token: one row for every head
            cache = cache.update(
                jnp.concatenate([c_kv, k_pe[:, :, 0]], axis=-1))
        if cache is not None and cache.absorbed:
            return self._absorbed(c_q, cache, position_offset, gate), cache
        # heads a group at a time, each group's share of o_proj summed
        # as it comes: at 8192 rows a head's q, k and v are 8.4 MB, all
        # 128 heads' 1.07 GB, and the flash kernel folds a copy of each
        groups = _head_groups(self.heads, b * t)
        size = self.heads // groups

        def group(g, acc):
            return acc + self._expanded(c_q, c_kv, k_pe, g * size, size,
                                        cache, position_offset, gate)

        zero = jnp.zeros((b, t, self.o_proj.shape[1]), _F32)
        out = (group(0, zero) if groups == 1
               else jax.lax.fori_loop(0, groups, group, zero))
        return out.astype(x.dtype), cache

    def _queries(self, c_q, first, size, position_offset):
        """(q_nope [B, T, size, nope], roped q_pe [B, T, size, rope]) of
        heads ``first .. first + size - 1``."""
        b, t, _ = c_q.shape
        d = self.nope + self.rope
        w = jax.lax.dynamic_slice_in_dim(self.q_b_proj._value, first * d,
                                         size * d, axis=1)
        q = jnp.matmul(c_q, w).reshape(b, t, size, d)
        return q[..., :self.nope], rope_pairs(
            q[..., self.nope:], position_offset, self.inv_freq,
            self.rope_mscale)

    def _w_ukv(self, first, size):
        """W_ukv [rank, size, nope + v] of those heads: a view of
        ``kv_b_proj``, whose left columns are W_uk and right W_uv."""
        w = self.kv_b_proj._value.reshape(self.rank, self.heads,
                                          self.nope + self.v_dim)
        return jax.lax.dynamic_slice_in_dim(w, first, size, axis=1)

    def _absorbed(self, c_q, cache, position_offset, gate=None):
        """A decode step: the up-projections on the query's and the
        output's side, the cached rows as they are."""
        b, t, _ = c_q.shape
        q_nope, q_pe = self._queries(c_q, 0, self.heads, position_offset)
        w_ukv = self._w_ukv(0, self.heads)
        q_lat = jnp.concatenate(
            [jnp.einsum("bthn,rhn->bthr", q_nope, w_ukv[..., :self.nope]),
             q_pe], axis=-1)
        ctx = jnp.einsum("bthr,rhv->bthv",
                         _val(cache.attend(q_lat, self.scale, self.rank)),
                         w_ukv[..., self.nope:])
        return jnp.matmul(_gated(ctx.reshape(b, t, self.heads * self.v_dim),
                                 gate), self.o_proj._value)

    def _expanded(self, c_q, c_kv, k_pe, first, size, cache,
                  position_offset, gate=None):
        """float32 [B, T, hidden]: what heads ``first .. first + size -
        1`` add to the layer's output, keys and values expanded from the
        latent for the whole sequence."""
        from ..nn import functional as F

        b, t, _ = c_q.shape
        q_nope, q_pe = self._queries(c_q, first, size, position_offset)
        w_ukv = self._w_ukv(first, size)
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        k = jnp.concatenate(
            [jnp.einsum("btr,rhn->bthn", c_kv, w_ukv[..., :self.nope]),
             jnp.broadcast_to(k_pe, (b, t, size, self.rope))], axis=-1)
        v = jnp.einsum("btr,rhv->bthv", c_kv, w_ukv[..., self.nope:])
        if cache is not None:
            ctx = cache.attend(q, k, v, self.scale)
        else:
            ctx = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, scale=self.scale)
        w_o = jax.lax.dynamic_slice_in_dim(
            self.o_proj._value, first * self.v_dim, size * self.v_dim,
            axis=0)
        if gate is not None:
            gate = jax.lax.dynamic_slice_in_dim(
                gate, first * self.v_dim, size * self.v_dim, axis=2)
        return jnp.matmul(
            _gated(_val(ctx).reshape(b, t, size * self.v_dim), gate), w_o,
            preferred_element_type=_F32)


def _gated(ctx, gate):
    """``ctx`` [B, T, channels] times the float32 output gate of those
    channels, in ``ctx``'s dtype; ``ctx`` itself where there is none."""
    if gate is None:
        return ctx
    return (ctx.astype(_F32) * gate).astype(ctx.dtype)


class DeepseekV2MLP(Layer):
    """The dense SwiGLU of the leading layers."""

    def __init__(self, config):
        super().__init__()
        c = config
        xavier = I.XavierNormal()
        self.gate_up = self.create_parameter(
            [c.hidden_size, 2 * c.intermediate_size], dtype=c.dtype,
            default_initializer=xavier)
        self.down = self.create_parameter(
            [c.intermediate_size, c.hidden_size], dtype=c.dtype,
            default_initializer=xavier)

    def forward(self, x):
        return swiglu(x, self.gate_up._value, self.down._value)


class DeepseekV2MoE(Layer):
    """The routed experts held here (parallel/moe.py, group-limited
    routing) plus the shared experts, ungated, as one SwiGLU of
    ``n_shared_experts`` times the expert width."""

    def __init__(self, config):
        super().__init__()
        c = config
        self.experts = MoELayer(
            c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
            top_k=c.num_experts_per_tok, activation="silu", gated=True,
            bias=False, norm_topk_prob=c.norm_topk_prob,
            experts_held=c.experts_held, dtype=c.dtype)
        self.n_group, self.topk_group = c.n_group, c.topk_group
        self.routed_scaling_factor = float(c.routed_scaling_factor)
        width = c.n_shared_experts * c.moe_intermediate_size
        xavier = I.XavierNormal()
        self.shared_gate_up = self.create_parameter(
            [c.hidden_size, 2 * width], dtype=c.dtype,
            default_initializer=xavier)
        self.shared_down = self.create_parameter(
            [width, c.hidden_size], dtype=c.dtype,
            default_initializer=xavier)
        self.step_stats = None

    def routed(self, flat):
        """The share of the routed sum the experts held here give, on
        [rows, hidden]; the step's counters are kept for the engine."""
        e = self.experts
        out, _, self.step_stats = moe_forward(
            flat, e.gate_weight._value, e.w1._value, None, e.w2._value,
            None, top_k=e.top_k, lo=e.experts_held.start,
            activation="silu", gated=True,
            norm_topk_prob=e.norm_topk_prob, n_group=self.n_group,
            topk_group=self.topk_group,
            routed_scaling_factor=self.routed_scaling_factor)
        return out

    def shared(self, flat):
        """The shared experts: every chip that shares the layer computes
        them alike, so a sum over shares counts them once."""
        return swiglu(flat, self.shared_gate_up._value,
                      self.shared_down._value)

    def forward(self, x):
        flat = x.reshape(-1, x.shape[-1])
        return (self.routed(flat) + self.shared(flat)).reshape(x.shape)


class DeepseekV2DecoderLayer(Layer):
    def __init__(self, config, index):
        super().__init__()
        c = config
        self.sparse = index >= c.first_k_dense_replace
        self.eps = c.rms_norm_eps
        ones = I.Constant(1.0)
        self.input_layernorm = self.create_parameter(
            [c.hidden_size], dtype=c.dtype, default_initializer=ones)
        self.self_attn = DeepseekV2Attention(c)
        self.post_attention_layernorm = self.create_parameter(
            [c.hidden_size], dtype=c.dtype, default_initializer=ones)
        self.mlp = DeepseekV2MoE(c) if self.sparse else DeepseekV2MLP(c)

    def forward(self, x, cache, position_offset):
        with jax.named_scope("mla"):
            mixed, cache = self.self_attn(
                rms_norm(x, self.input_layernorm._value, self.eps), cache,
                position_offset)
            x = x + mixed
        with jax.named_scope("moe" if self.sparse else "mlp"):
            x = x + self.mlp(rms_norm(
                x, self.post_attention_layernorm._value, self.eps))
        return x, cache


class DeepseekV2Model(Layer):
    def __init__(self, config):
        super().__init__()
        c = config
        self.embed_tokens = self.create_parameter(
            [c.vocab_size, c.hidden_size], dtype=c.dtype,
            default_initializer=I.Normal(0.0, 1.0))
        self.layers = LayerList([DeepseekV2DecoderLayer(c, i)
                                 for i in range(c.num_hidden_layers)])
        self.norm = self.create_parameter(
            [c.hidden_size], dtype=c.dtype,
            default_initializer=I.Constant(1.0))


class DeepseekV2ForCausalLM(Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.model = DeepseekV2Model(config)
        self.lm_head = self.create_parameter(
            [config.hidden_size, config.vocab_size], dtype=config.dtype,
            default_initializer=I.XavierNormal())
        # what the serving engine reads: expert layers whose counters
        # ride back with the tokens, and how many experts each holds
        self.moe_layers = sum(layer.sparse for layer in self.model.layers)
        self.moe_experts_held = len(config.experts_held)

    def _run(self, input_ids, caches, position_offset, logits_at=None):
        c = self.config
        with jax.named_scope("embed"):
            x = jnp.take(self.model.embed_tokens._value, _val(input_ids),
                         axis=0)
        if caches is None:
            caches = [None] * c.num_hidden_layers
        new_caches = []
        for i, layer in enumerate(self.model.layers):
            with jax.named_scope("layer_%d" % i):
                x, cache = layer(x, caches[i], position_offset)
            new_caches.append(cache)
        with jax.named_scope("lm_head"):
            x = rms_norm(rows_at(x, logits_at), self.model.norm._value,
                         c.rms_norm_eps)
            logits = jnp.matmul(x, self.lm_head._value)
        return Tensor(logits), new_caches

    def forward(self, input_ids):
        """Logits [B, T, vocab] of whole sequences, nothing kept."""
        return self._run(input_ids, None, 0)[0]

    def generate_step(self, input_ids, caches, position_offset,
                      logits_at=None):
        """One compiled step of the serving engine: ``caches`` is one
        hook a layer (serving/kv_cache.py); ``logits_at``
        (generation.rows_at) names the one row a sequence to norm and
        project."""
        return self._run(input_ids, caches, position_offset, logits_at)

    def moe_step_stats(self):
        """int32 [expert layers, 4] of the step just traced: pairs
        routed to the experts held here, held experts that received a
        row, the largest load of one expert, rows handed to the
        grouped matmuls."""
        return jnp.stack([layer.mlp.step_stats
                          for layer in self.model.layers if layer.sparse])

    def max_decode_len(self):
        return self.config.max_position_embeddings

    def paged_cache_spec(self):
        """One entry a layer: latent pages, a row of ``kv_lora_rank +
        qk_rope_head_dim`` values a token."""
        from ..serving.kv_cache import LatentPages

        c = self.config
        return [LatentPages(c.kv_lora_rank + c.qk_rope_head_dim, c.dtype)
                for _ in self.model.layers]
