"""Qwen3-Next: a hybrid decoder — three Gated DeltaNet (linear
attention) layers, then one gated softmax-attention layer, every layer
followed by a sparse mixture of experts with one shared expert
(Qwen/Qwen3-Next-80B-A3B-Instruct ``config.json``; Yang et al., "Gated
Delta Networks", arXiv:2412.06464).

What a layer keeps for a sequence differs by kind, and the model only
SAYS so (``paged_cache_spec``): a full-attention layer keeps K/V pages,
a Gated DeltaNet layer a fixed recurrent state S [Hv, Dk, Dv] in
float32 and the last ``kernel - 1`` rows that went into its causal
convolution. The serving engine owns both (serving/kv_cache.py) and
hands each layer a hook object: ``update_and_attend`` for pages,
``read`` / ``write`` / ``valid_len`` for state.

The Gated DeltaNet recurrence, per value head, state S in float32::

    S <- exp(g_t) S;  u = (v_t - S^T k_t) beta_t;  S <- S + k_t u^T
    o_t = S^T q_t

runs in two forms that give the same numbers: the chunked WY form, 64
tokens a chunk, for a prompt (``kernels/gdn_chunked.py``: the Mosaic
kernel ``gdn_chunked`` on a TPU, its jnp twin ``gated_delta_chunked``
elsewhere) and ``gated_delta_step`` (one token for every slot, for
decode). A padded row takes g = 0 and beta = 0, which leaves S exactly
as it was.

The experts are ``parallel/moe.py``'s dropless layer, told which
experts live here (``experts_held``): it routes over the published
router width and computes its own experts' share. The shared expert is
computed whole (every chip of an expert-parallel deployment computes it
alike). The model is inference code on raw arrays; it hands the engine
its expert layers' step counters through ``moe_step_stats``.

Column order of the fused projections (a convention; the published
implementation interleaves them per key head): ``in_proj_qkvz`` is
[q | k | v | z], ``in_proj_ba`` is [b | a], ``q_proj`` is per head
[query | gate], an expert's ``w1`` is [gate | up].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..kernels.gdn_chunked import gdn_chunked
from ..nn import initializer as I
from ..nn.layer import Layer
from ..nn.layers.container import LayerList
from ..parallel.moe import MoELayer, moe_forward
from .generation import rows_at
from .llama import rope_apply

_F32 = jnp.float32


class Qwen3NextConfig:
    def __init__(self, vocab_size=151936, hidden_size=2048,
                 num_hidden_layers=48, num_attention_heads=16,
                 num_key_value_heads=2, head_dim=256,
                 partial_rotary_factor=0.25, rope_theta=1e7,
                 rms_norm_eps=1e-6, full_attention_interval=4,
                 linear_conv_kernel_dim=4, linear_key_head_dim=128,
                 linear_num_key_heads=16, linear_num_value_heads=32,
                 linear_value_head_dim=128, moe_intermediate_size=512,
                 shared_expert_intermediate_size=512, num_experts=512,
                 num_experts_per_tok=10, norm_topk_prob=True,
                 experts_held=None, max_position_embeddings=262144,
                 linear_sigmoid_gate_scale=None,
                 linear_attn_o_norm_eps=None, dtype="float32"):
        """``num_experts`` is the router's published width;
        ``experts_held`` (a range, default all) the experts that live
        here. ``vocab_size`` is the number of vocabulary rows held here
        (ids, logits and argmax are over them).
        ``linear_sigmoid_gate_scale`` None is Qwen3-Next's Gated
        DeltaNet output gate (a plain-weight head norm times silu(z));
        a number s makes it a zero-centred head norm times s sigmoid(z)
        (``Qwen3NextGatedDeltaNet``). ``linear_attn_o_norm_eps`` is that
        head norm's eps (None: ``rms_norm_eps``)."""
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.partial_rotary_factor = partial_rotary_factor
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.full_attention_interval = full_attention_interval
        self.linear_conv_kernel_dim = linear_conv_kernel_dim
        self.linear_key_head_dim = linear_key_head_dim
        self.linear_num_key_heads = linear_num_key_heads
        self.linear_num_value_heads = linear_num_value_heads
        self.linear_value_head_dim = linear_value_head_dim
        self.moe_intermediate_size = moe_intermediate_size
        self.shared_expert_intermediate_size = \
            shared_expert_intermediate_size
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = norm_topk_prob
        self.experts_held = (range(num_experts) if experts_held is None
                             else experts_held)
        self.max_position_embeddings = max_position_embeddings
        self.linear_sigmoid_gate_scale = linear_sigmoid_gate_scale
        self.linear_attn_o_norm_eps = (rms_norm_eps
                                       if linear_attn_o_norm_eps is None
                                       else linear_attn_o_norm_eps)
        self.dtype = dtype

    def is_full_attention(self, i):
        return (i + 1) % self.full_attention_interval == 0

    @classmethod
    def tiny(cls, **kw):
        d = dict(vocab_size=128, hidden_size=64, num_hidden_layers=4,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                 linear_key_head_dim=8, linear_num_key_heads=2,
                 linear_num_value_heads=4, linear_value_head_dim=8,
                 moe_intermediate_size=32,
                 shared_expert_intermediate_size=32, num_experts=16,
                 num_experts_per_tok=4, experts_held=range(8),
                 max_position_embeddings=512)
        d.update(kw)
        return cls(**d)


def _val(x):
    return x._value if isinstance(x, Tensor) else x


def rms_norm_zero_centred(x, weight, eps):
    """x / sqrt(mean(x^2) + eps) * (1 + w), statistics in float32."""
    xf = x.astype(_F32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * inv * (1.0 + weight.astype(_F32))).astype(x.dtype)


def l2_normalise(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


# -- the Gated DeltaNet recurrence, two forms --------------------------------

def gated_delta_step(q, k, v, g, beta, state):
    """One token for every row. q, k [B, Hk, Dk], v [B, Hv, Dv], g and
    beta [B, Hv], state [B, Hv, Dk, Dv], all float32 (q, k normalised,
    q scaled); key head j serves value heads j*r .. j*r + r - 1.
    -> (o [B, Hv, Dv], new state). The old state is read for S^T k and
    S^T q in one pass and rewritten in a second: with S' = e^g S,
    u = (v - e^g S^T k) beta and o = e^g S^T q + (k . q) u."""
    rep = v.shape[1] // k.shape[1]
    q = jnp.repeat(q, rep, axis=1)
    k = jnp.repeat(k, rep, axis=1)
    decay = jnp.exp(g)[..., None]                               # [B, Hv, 1]
    s_k = jnp.sum(state * k[..., None], axis=-2)                # [B, Hv, Dv]
    s_q = jnp.sum(state * q[..., None], axis=-2)
    u = (v - decay * s_k) * beta[..., None]
    new_state = decay[..., None] * state + k[..., None] * u[..., None, :]
    o = decay * s_q + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return o, new_state


class _NoCache:
    """The hook a Gated DeltaNet layer gets when nobody keeps its state
    (a plain forward over whole sequences): zeros in, nothing out."""

    def __init__(self, arrays, valid_len):
        self._arrays = arrays
        self.valid_len = valid_len

    def read(self):
        return self._arrays

    def write(self, arrays):
        return self


# -- layers ------------------------------------------------------------------

class Qwen3NextGatedDeltaNet(Layer):
    """The Gated DeltaNet mixer. Its output is per value head
    ``rmsnorm(o) * w * silu(z)`` (Qwen3-Next), or with the config's
    ``linear_sigmoid_gate_scale`` s ``rmsnorm(o) * (1 + w) * s
    sigmoid(z)``: a zero-centred norm gated by a scaled sigmoid
    (GigaChat3.5's ``gated_rmsnorm_sigmoid_zero_centered``); the head
    norm's eps is the config's ``linear_attn_o_norm_eps``."""

    def __init__(self, config):
        super().__init__()
        c = config
        self.hk, self.dk = c.linear_num_key_heads, c.linear_key_head_dim
        self.hv, self.dv = c.linear_num_value_heads, c.linear_value_head_dim
        self.kernel = c.linear_conv_kernel_dim
        self.eps = c.linear_attn_o_norm_eps
        self.gate_scale = c.linear_sigmoid_gate_scale
        self.key_dim = self.hk * self.dk
        self.value_dim = self.hv * self.dv
        self.conv_dim = 2 * self.key_dim + self.value_dim
        dt, xavier = c.dtype, I.XavierNormal()
        self.in_proj_qkvz = self.create_parameter(
            [c.hidden_size, self.conv_dim + self.value_dim], dtype=dt,
            default_initializer=xavier)
        self.in_proj_ba = self.create_parameter(
            [c.hidden_size, 2 * self.hv], dtype=dt,
            default_initializer=xavier)
        self.conv_weight = self.create_parameter(
            [self.conv_dim, self.kernel], dtype=dt,
            default_initializer=I.Uniform(-0.5, 0.5))
        # decays from about 1e-3 to 1 a token: A in [1, 16], softplus of
        # dt_bias in [1e-3, 1e-1] (the state-space family's usual draw)
        self.A_log = self.create_parameter(
            [self.hv], dtype=dt, default_initializer=I.Uniform(0.0, 2.77))
        self.dt_bias = self.create_parameter(
            [self.hv], dtype=dt, default_initializer=I.Uniform(-6.9, -2.25))
        self.norm_weight = self.create_parameter(
            [self.dv], dtype=dt, default_initializer=I.Constant(
                1.0 if self.gate_scale is None else 0.0))
        self.out_proj = self.create_parameter(
            [self.value_dim, c.hidden_size], dtype=dt,
            default_initializer=xavier)

    def state_spec(self, dtype):
        """((name, one slot's shape, dtype), ...) for the cache spec."""
        return (("state", (self.hv, self.dk, self.dv), "float32"),
                ("conv", (self.kernel - 1, self.conv_dim), dtype))

    def forward(self, x, cache):
        b, t, _ = x.shape
        qkvz = jnp.matmul(x, self.in_proj_qkvz._value)
        ba = jnp.matmul(x, self.in_proj_ba._value).astype(_F32)
        mixed = qkvz[..., :self.conv_dim]
        z = qkvz[..., self.conv_dim:].reshape(b, t, self.hv, self.dv)
        beta = jax.nn.sigmoid(ba[..., :self.hv])
        g = -jnp.exp(self.A_log._value.astype(_F32)) * jax.nn.softplus(
            ba[..., self.hv:] + self.dt_bias._value.astype(_F32))
        held = cache.read()
        # the rows the convolution sees: the tail kept from before, then
        # this call's rows
        window = jnp.concatenate(
            [held["conv"].astype(mixed.dtype), mixed], axis=1)
        w = self.conv_weight._value.astype(_F32)
        conv = sum(window[:, j:j + t].astype(_F32) * w[:, j]
                   for j in range(self.kernel))
        conv = jax.nn.silu(conv).astype(x.dtype).astype(_F32)
        q = conv[..., :self.key_dim].reshape(b, t, self.hk, self.dk)
        k = conv[..., self.key_dim:2 * self.key_dim].reshape(
            b, t, self.hk, self.dk)
        v = conv[..., 2 * self.key_dim:].reshape(b, t, self.hv, self.dv)
        q = l2_normalise(q) * self.dk ** -0.5
        k = l2_normalise(k)
        if cache.valid_len is None:
            # decode: one real token a row
            o, state = gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                        beta[:, 0], held["state"])
            o = o[:, None]
            tail = window[:, 1:]
        else:
            # a prompt, right-padded: the rows past valid_len change
            # neither the state nor the tail
            live = (jnp.arange(t) < cache.valid_len)[None, :, None]
            o, state = gdn_chunked(
                q, k, v, jnp.where(live, g, 0.0),
                jnp.where(live, beta, 0.0), held["state"])
            tail = jax.lax.dynamic_slice_in_dim(
                window, cache.valid_len, self.kernel - 1, axis=1)
        cache = cache.write({"state": state, "conv": tail})
        # per-head RMSNorm, then the gate
        inv = jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                            + self.eps)
        if self.gate_scale is None:
            o = (o * inv * self.norm_weight._value.astype(_F32)
                 * jax.nn.silu(z.astype(_F32))).astype(x.dtype)
        else:
            o = (o * inv * (1.0 + self.norm_weight._value.astype(_F32))
                 * (self.gate_scale * jax.nn.sigmoid(z.astype(_F32)))
                 ).astype(x.dtype)
        out = jnp.matmul(o.reshape(b, t, self.value_dim),
                         self.out_proj._value)
        return out, cache


class Qwen3NextAttention(Layer):
    """Gated softmax attention: per-head RMSNorm on q and k, rotary on
    the first ``partial_rotary_factor`` of the head's dims, the output
    multiplied by sigmoid of a per-head gate that ``q_proj`` also
    makes."""

    def __init__(self, config):
        super().__init__()
        c = config
        self.heads, self.kv_heads = (c.num_attention_heads,
                                     c.num_key_value_heads)
        self.d = c.head_dim
        self.rotary = int(c.head_dim * c.partial_rotary_factor)
        self.theta = c.rope_theta
        self.eps = c.rms_norm_eps
        dt, xavier = c.dtype, I.XavierNormal()
        self.q_proj = self.create_parameter(
            [c.hidden_size, self.heads * self.d * 2], dtype=dt,
            default_initializer=xavier)
        self.k_proj = self.create_parameter(
            [c.hidden_size, self.kv_heads * self.d], dtype=dt,
            default_initializer=xavier)
        self.v_proj = self.create_parameter(
            [c.hidden_size, self.kv_heads * self.d], dtype=dt,
            default_initializer=xavier)
        self.o_proj = self.create_parameter(
            [self.heads * self.d, c.hidden_size], dtype=dt,
            default_initializer=xavier)
        self.q_norm = self.create_parameter(
            [self.d], dtype=dt, default_initializer=I.Constant(0.0))
        self.k_norm = self.create_parameter(
            [self.d], dtype=dt, default_initializer=I.Constant(0.0))

    def forward(self, x, cache=None, position_offset=0):
        from ..nn import functional as F

        b, t, _ = x.shape
        qg = jnp.matmul(x, self.q_proj._value).reshape(
            b, t, self.heads, 2 * self.d)
        q, gate = qg[..., :self.d], qg[..., self.d:]
        k = jnp.matmul(x, self.k_proj._value).reshape(
            b, t, self.kv_heads, self.d)
        v = jnp.matmul(x, self.v_proj._value).reshape(
            b, t, self.kv_heads, self.d)
        q = rms_norm_zero_centred(q, self.q_norm._value, self.eps)
        k = rms_norm_zero_centred(k, self.k_norm._value, self.eps)
        r = self.rotary
        q_rot, k_rot = rope_apply.raw_fn(
            q[..., :r], k[..., :r], theta=self.theta,
            position_offset=position_offset)
        q = jnp.concatenate([q_rot, q[..., r:]], axis=-1)
        k = jnp.concatenate([k_rot, k[..., r:]], axis=-1)
        if cache is not None:
            ctx, cache = cache.update_and_attend(q, k, v)
        else:
            rep = self.heads // self.kv_heads
            ctx = F.scaled_dot_product_attention(
                q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
                is_causal=True)
        ctx = _val(ctx).reshape(b, t, self.heads, self.d)
        ctx = (ctx.astype(_F32)
               * jax.nn.sigmoid(gate.astype(_F32))).astype(x.dtype)
        out = jnp.matmul(ctx.reshape(b, t, self.heads * self.d),
                         self.o_proj._value)
        return out, cache


class Qwen3NextSparseMoe(Layer):
    """The routed experts held here (parallel/moe.py) plus the shared
    expert behind its sigmoid gate."""

    def __init__(self, config):
        super().__init__()
        c = config
        self.experts = MoELayer(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            top_k=c.num_experts_per_tok, activation="silu", gated=True,
            bias=False, norm_topk_prob=c.norm_topk_prob,
            experts_held=c.experts_held, dtype=c.dtype)
        self.shared_width = c.shared_expert_intermediate_size
        dt, xavier = c.dtype, I.XavierNormal()
        self.shared_gate_up = self.create_parameter(
            [c.hidden_size, 2 * self.shared_width], dtype=dt,
            default_initializer=xavier)
        self.shared_down = self.create_parameter(
            [self.shared_width, c.hidden_size], dtype=dt,
            default_initializer=xavier)
        self.shared_expert_gate = self.create_parameter(
            [c.hidden_size, 1], dtype=dt, default_initializer=xavier)
        self.step_stats = None

    def routed(self, flat):
        """The share of the routed sum the experts held here give, on
        [rows, hidden]; the step's counters are kept for the engine."""
        e = self.experts
        out, _, self.step_stats = moe_forward(
            flat, e.gate_weight._value, e.w1._value, None, e.w2._value,
            None, top_k=e.top_k, lo=e.experts_held.start,
            activation="silu", gated=True, norm_topk_prob=e.norm_topk_prob)
        return out

    def shared(self, flat):
        """The shared expert times its sigmoid gate: every chip that
        shares the layer computes it alike, so a sum over shares counts
        it once."""
        f = self.shared_width
        gu = jnp.matmul(flat, self.shared_gate_up._value)
        out = jnp.matmul(jax.nn.silu(gu[:, :f]) * gu[:, f:],
                         self.shared_down._value)
        gate = jax.nn.sigmoid(jnp.matmul(
            flat, self.shared_expert_gate._value).astype(_F32))
        return (out.astype(_F32) * gate).astype(flat.dtype)

    def forward(self, x):
        flat = x.reshape(-1, x.shape[-1])
        return (self.routed(flat) + self.shared(flat)).reshape(x.shape)


class Qwen3NextDecoderLayer(Layer):
    def __init__(self, config, index):
        super().__init__()
        c = config
        self.full_attention = c.is_full_attention(index)
        self.eps = c.rms_norm_eps
        zeros = I.Constant(0.0)
        self.input_layernorm = self.create_parameter(
            [c.hidden_size], dtype=c.dtype, default_initializer=zeros)
        if self.full_attention:
            self.self_attn = Qwen3NextAttention(c)
        else:
            self.linear_attn = Qwen3NextGatedDeltaNet(c)
        self.post_attention_layernorm = self.create_parameter(
            [c.hidden_size], dtype=c.dtype, default_initializer=zeros)
        self.mlp = Qwen3NextSparseMoe(c)

    def forward(self, x, cache, position_offset):
        # the norm before a mixer and the residual add after it are
        # under the mixer's scope: a device trace books time by it
        with jax.named_scope("attn" if self.full_attention else "gdn"):
            h = rms_norm_zero_centred(x, self.input_layernorm._value,
                                      self.eps)
            if self.full_attention:
                mixed, cache = self.self_attn(h, cache, position_offset)
            else:
                mixed, cache = self.linear_attn(h, cache)
            x = x + mixed
        with jax.named_scope("moe"):
            x = x + self.mlp(rms_norm_zero_centred(
                x, self.post_attention_layernorm._value, self.eps))
        return x, cache


class Qwen3NextModel(Layer):
    def __init__(self, config):
        super().__init__()
        c = config
        self.embed_tokens = self.create_parameter(
            [c.vocab_size, c.hidden_size], dtype=c.dtype,
            default_initializer=I.Normal(0.0, 1.0))
        self.layers = LayerList([Qwen3NextDecoderLayer(c, i)
                                 for i in range(c.num_hidden_layers)])
        self.norm = self.create_parameter(
            [c.hidden_size], dtype=c.dtype,
            default_initializer=I.Constant(0.0))


class Qwen3NextForCausalLM(Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.model = Qwen3NextModel(config)
        self.lm_head = self.create_parameter(
            [config.hidden_size, config.vocab_size], dtype=config.dtype,
            default_initializer=I.XavierNormal())
        # what the serving engine reads: expert layers whose counters
        # ride back with the tokens, and how many experts each holds
        self.moe_layers = config.num_hidden_layers
        self.moe_experts_held = len(config.experts_held)

    def _run(self, input_ids, caches, position_offset, logits_at=None):
        c = self.config
        ids = _val(input_ids)
        with jax.named_scope("embed"):
            x = jnp.take(self.model.embed_tokens._value, ids, axis=0)
        if caches is None:
            b, t = ids.shape
            caches = [
                None if layer.full_attention else _NoCache(
                    {name: jnp.zeros((b,) + shape, dtype) for
                     name, shape, dtype in
                     layer.linear_attn.state_spec(x.dtype)}, t)
                for layer in self.model.layers]
        new_caches = []
        for i, layer in enumerate(self.model.layers):
            with jax.named_scope("layer_%d" % i):
                x, cache = layer(x, caches[i], position_offset)
            new_caches.append(cache)
        with jax.named_scope("lm_head"):
            x = rms_norm_zero_centred(rows_at(x, logits_at),
                                      self.model.norm._value,
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
        """int32 [layers, 4] of the step just traced: pairs routed to
        the experts held here, held experts that received a row, the
        largest load of one expert, rows handed to the grouped
        matmuls."""
        return jnp.stack([layer.mlp.step_stats
                          for layer in self.model.layers])

    def max_decode_len(self):
        return self.config.max_position_embeddings

    def paged_cache_spec(self):
        """One entry a layer: K/V pages for a full-attention layer, the
        recurrent state and convolution tail for a Gated DeltaNet one."""
        from ..serving.kv_cache import KVPages, SlotState

        c = self.config
        return [KVPages(c.num_key_value_heads, c.head_dim, c.dtype)
                if layer.full_attention
                else SlotState(layer.linear_attn.state_spec(c.dtype))
                for layer in self.model.layers]
