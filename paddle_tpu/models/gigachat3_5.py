"""GigaChat3.5: a hybrid decoder — three Gated DeltaNet (linear
attention) layers, then one gated multi-head latent attention (MLA)
layer — with sandwich zero-centred norms, leading dense SwiGLU layers,
then sparse layers that route top-8 of 256 experts by sigmoid scores
beside one ungated shared expert, every SwiGLU clamped
(ai-sage/GigaChat3.5-432B-A28B ``config.json``, ``model_type``
``gigachat3_5``).

Layer ``i`` (its index in the published stack, ``layers_held``) mixes
with latent attention when ``i`` is in ``full_attention_layers`` and
with a Gated DeltaNet otherwise; its MLP is dense (``intermediate_size``)
when ``i < first_k_dense_replace`` and the expert layer otherwise::

    x <- x + Npost(mixer(N(x)));  x <- x + Npost'(mlp(N'(x)))
    N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)          zero-centred
    Npost(x) = N(x) * 2 sigmoid(gamma)                  per channel

The mixers are the repository's own, told what differs:
``models/deepseek_v2.py`` ``DeepseekV2Attention`` with
``gated_attention`` (the heads' value output times ``sigmoid(x W_g)``
before ``o_proj``, in the expanded prefill form and the absorbed decode
form alike; rotary pairs (2i, 2i+1) and YaRN as DeepSeek-V2's), and
``models/qwen3_next.py`` ``Qwen3NextGatedDeltaNet`` with
``linear_sigmoid_gate_scale`` 2 (its output ``N_head(o) * 2
sigmoid(z)``). So one slot of the serving cache holds two kinds of
state (``paged_cache_spec``): a Gated DeltaNet layer's recurrent state
and convolution tail (``SlotState``), and a latent attention layer's
one 576-value row a token (``LatentPages``).

Every SwiGLU, the dense MLP, the experts and the shared expert, is
``down(silu(min(g, limit)) * clip(u, -limit, limit))``
(``swiglu_limit``). The experts are ``parallel/moe.py``'s dropless
layer told which experts live here (``experts_held``); the router's
choice is the top-k of sigmoid score + ``e_score_correction_bias``, its
weights the scores, normalised, times ``routed_scaling_factor``; the
shared expert is whole. A prefill's padding rows send no pair to the
experts (``moe_forward``'s ``row_mask``): they all hold one token and
would send their pairs to the same few experts, and where those lie
here the layer lays out every pair of the bucket, which made the time
of an 8192-row prefill depend on its padding. ``balance_router_bias``
gives the bias what training gives it, as in ``models/nemotron_h.py``.
Inference code on raw arrays; the model hands the engine its expert
layers' step counters through ``moe_step_stats``. The
multi-token-prediction modules (``num_nextn_predict_layers``) are not
built.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn import initializer as I
from ..nn.layer import Layer
from ..nn.layers.container import LayerList
from ..parallel.moe import (MoELayer, balance_router_biases,
                            balance_select_bias, moe_forward)
from .deepseek_v2 import DeepseekV2Attention, swiglu
from .generation import rows_at
from .qwen3_next import (Qwen3NextGatedDeltaNet, _NoCache,
                         rms_norm_zero_centred)

_F32 = jnp.float32
PUBLISHED_FULL_ATTENTION = tuple(range(3, 40, 4))
YARN_GIGACHAT35 = {"type": "yarn", "factor": 8, "beta_fast": 32,
                   "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                   "original_max_position_embeddings": 32768}


class GigaChat35Config:
    def __init__(self, vocab_size=128256, hidden_size=7168,
                 intermediate_size=18432, moe_intermediate_size=2048,
                 num_hidden_layers=40, layers_held=None,
                 full_attention_layers=PUBLISHED_FULL_ATTENTION,
                 first_k_dense_replace=3, num_attention_heads=64,
                 q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128,
                 linear_num_key_heads=32, linear_num_value_heads=64,
                 linear_key_head_dim=128, linear_value_head_dim=128,
                 linear_conv_kernel_dim=4, linear_sigmoid_gate_scale=2.0,
                 linear_attn_o_norm_eps=1e-6, layernorm_gating_weight=2.0,
                 n_routed_experts=256, n_shared_experts=1,
                 num_experts_per_tok=8, routed_scaling_factor=2.5,
                 norm_topk_prob=True, swiglu_limit=10.0,
                 rope_theta=100000.0, rope_scaling=None, rms_norm_eps=1e-6,
                 experts_held=None, max_position_embeddings=262144,
                 dtype="float32"):
        """``layers_held`` (default ``range(num_hidden_layers)``) is the
        published index of each layer built here, in order: what decides
        its mixer and its MLP. ``n_routed_experts`` is the router's
        published width; ``experts_held`` (a range, default all) the
        experts that live here. ``vocab_size`` is the number of
        vocabulary rows held here (ids, logits and argmax are over
        them). ``rope_scaling`` is the published YaRN dict (default:
        GigaChat3.5's)."""
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.layers_held = tuple(range(num_hidden_layers)
                                 if layers_held is None else layers_held)
        if len(self.layers_held) != num_hidden_layers:
            raise ValueError("layers_held %r names %d layers, "
                             "num_hidden_layers is %d"
                             % (self.layers_held, len(self.layers_held),
                                num_hidden_layers))
        self.full_attention_layers = tuple(full_attention_layers)
        self.first_k_dense_replace = first_k_dense_replace
        self.num_attention_heads = num_attention_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.gated_attention = True
        self.linear_num_key_heads = linear_num_key_heads
        self.linear_num_value_heads = linear_num_value_heads
        self.linear_key_head_dim = linear_key_head_dim
        self.linear_value_head_dim = linear_value_head_dim
        self.linear_conv_kernel_dim = linear_conv_kernel_dim
        self.linear_sigmoid_gate_scale = linear_sigmoid_gate_scale
        self.linear_attn_o_norm_eps = linear_attn_o_norm_eps
        self.layernorm_gating_weight = layernorm_gating_weight
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.swiglu_limit = swiglu_limit
        self.rope_theta = rope_theta
        self.rope_scaling = dict(YARN_GIGACHAT35 if rope_scaling is None
                                 else rope_scaling)
        self.rms_norm_eps = rms_norm_eps
        self.experts_held = (range(n_routed_experts) if experts_held is None
                             else experts_held)
        self.max_position_embeddings = max_position_embeddings
        self.dtype = dtype

    def is_full_attention(self, j):
        """Whether the ``j``-th layer built here is a latent attention
        layer."""
        return self.layers_held[j] in self.full_attention_layers

    def is_sparse(self, j):
        return self.layers_held[j] >= self.first_k_dense_replace

    @classmethod
    def tiny(cls, **kw):
        """Small enough for the CPU, Mosaic-tileable on the chip (the
        latent 128 wide, 8 heads): the published layers 0 and 3-6 (one
        dense GDN layer, then MLA, GDN x 3 over experts), 16 experts
        top-4 with 8 held here."""
        d = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
                 moe_intermediate_size=32, num_hidden_layers=5,
                 layers_held=(0, 3, 4, 5, 6), num_attention_heads=8,
                 q_lora_rank=48, kv_lora_rank=128, qk_nope_head_dim=32,
                 qk_rope_head_dim=16, v_head_dim=32, linear_num_key_heads=2,
                 linear_num_value_heads=4, linear_key_head_dim=8,
                 linear_value_head_dim=8, n_routed_experts=16,
                 num_experts_per_tok=4, experts_held=range(8),
                 max_position_embeddings=512)
        d.update(kw)
        return cls(**d)


def _val(x):
    return x._value if isinstance(x, Tensor) else x


def post_norm(x, weight, gate, eps, scale):
    """The sandwich's second norm: the zero-centred RMSNorm times a
    per-channel gate ``scale * sigmoid(gate)``, statistics in
    float32."""
    xf = x.astype(_F32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * inv * (1.0 + weight.astype(_F32))
            * (scale * jax.nn.sigmoid(gate.astype(_F32)))).astype(x.dtype)


class GigaChat35MLP(Layer):
    """The dense clamped SwiGLU of the leading layers."""

    def __init__(self, config):
        super().__init__()
        c = config
        xavier = I.XavierNormal()
        self.limit = c.swiglu_limit
        self.gate_up = self.create_parameter(
            [c.hidden_size, 2 * c.intermediate_size], dtype=c.dtype,
            default_initializer=xavier)
        self.down = self.create_parameter(
            [c.intermediate_size, c.hidden_size], dtype=c.dtype,
            default_initializer=xavier)

    def forward(self, x):
        return swiglu(x, self.gate_up._value, self.down._value, self.limit)


class GigaChat35MoE(Layer):
    """The routed experts held here (parallel/moe.py: clamped SwiGLU,
    sigmoid scores with a selection bias) plus the shared experts,
    ungated, as one clamped SwiGLU of ``n_shared_experts`` times the
    expert width."""

    def __init__(self, config):
        super().__init__()
        c = config
        self.experts = MoELayer(
            c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
            top_k=c.num_experts_per_tok, activation="silu", gated=True,
            bias=False, norm_topk_prob=c.norm_topk_prob,
            experts_held=c.experts_held, dtype=c.dtype)
        self.routed_scaling_factor = float(c.routed_scaling_factor)
        self.limit = c.swiglu_limit
        dt, xavier = c.dtype, I.XavierNormal()
        # seeded non-zero, so that leaving it out of the choice, or
        # putting it into the weights, shows
        self.e_score_correction_bias = self.create_parameter(
            [c.n_routed_experts], dtype=dt,
            default_initializer=I.Uniform(-0.1, 0.1))
        width = c.n_shared_experts * c.moe_intermediate_size
        self.shared_gate_up = self.create_parameter(
            [c.hidden_size, 2 * width], dtype=dt, default_initializer=xavier)
        self.shared_down = self.create_parameter(
            [width, c.hidden_size], dtype=dt, default_initializer=xavier)
        self.step_stats = None

    def routed(self, flat, row_mask=None):
        """The share of the routed sum the experts held here give, on
        [rows, hidden], for the rows of ``row_mask`` (all by default);
        the step's counters are kept for the engine."""
        e = self.experts
        out, _, self.step_stats = moe_forward(
            flat, e.gate_weight._value, e.w1._value, None, e.w2._value,
            None, top_k=e.top_k, lo=e.experts_held.start,
            activation="silu", gated=True, norm_topk_prob=e.norm_topk_prob,
            routed_scaling_factor=self.routed_scaling_factor,
            select_bias=self.e_score_correction_bias._value,
            swiglu_limit=self.limit, row_mask=row_mask)
        return out

    def shared(self, flat):
        """The shared experts: every chip that shares the layer computes
        them alike, so a sum over shares counts them once."""
        return swiglu(flat, self.shared_gate_up._value,
                      self.shared_down._value, self.limit)

    def balance(self, flat, rounds, step):
        """``rounds`` updates of ``e_score_correction_bias`` on the rows
        ``flat`` [rows, hidden] (parallel/moe.py
        ``balance_select_bias``)."""
        bias = self.e_score_correction_bias
        bias._value = balance_select_bias(
            flat, self.experts.gate_weight._value, bias._value,
            self.experts.top_k, rounds, step)

    def forward(self, x, valid_len=None):
        """``valid_len``: a prefill's real rows (the rest of its bucket is
        padding, whose pairs are not computed: padded rows share one
        token and would send all their pairs to the same few experts,
        and a skew that lands here makes the layer lay out every pair);
        None computes every row's."""
        b, t, _ = x.shape
        flat = x.reshape(-1, x.shape[-1])
        mask = None
        if valid_len is not None:
            mask = jnp.broadcast_to(jnp.arange(t) < valid_len,
                                    (b, t)).reshape(-1)
        return (self.routed(flat, mask) + self.shared(flat)).reshape(x.shape)


class GigaChat35DecoderLayer(Layer):
    def __init__(self, config, j):
        super().__init__()
        c = config
        self.full_attention = c.is_full_attention(j)
        self.sparse = c.is_sparse(j)
        self.eps = c.rms_norm_eps
        self.gating = float(c.layernorm_gating_weight)

        def norm():
            return self.create_parameter(
                [c.hidden_size], dtype=c.dtype,
                default_initializer=I.Constant(0.0))

        self.input_layernorm = norm()
        if self.full_attention:
            self.self_attn = DeepseekV2Attention(c)
        else:
            self.linear_attn = Qwen3NextGatedDeltaNet(c)
        self.post_attention_layernorm = norm()
        self.post_attention_gate = norm()
        self.pre_feedforward_layernorm = norm()
        self.mlp = GigaChat35MoE(c) if self.sparse else GigaChat35MLP(c)
        self.post_feedforward_layernorm = norm()
        self.post_feedforward_gate = norm()

    def forward(self, x, cache, position_offset, valid_len=None):
        # both norms of a half and its residual add are under the half's
        # scope: a device trace books time by it
        with jax.named_scope("mla" if self.full_attention else "gdn"):
            h = rms_norm_zero_centred(x, self.input_layernorm._value,
                                      self.eps)
            if self.full_attention:
                mixed, cache = self.self_attn(h, cache, position_offset)
            else:
                mixed, cache = self.linear_attn(h, cache)
            x = x + post_norm(mixed, self.post_attention_layernorm._value,
                              self.post_attention_gate._value, self.eps,
                              self.gating)
        with jax.named_scope("moe" if self.sparse else "mlp"):
            h = rms_norm_zero_centred(
                x, self.pre_feedforward_layernorm._value, self.eps)
            h = self.mlp(h, valid_len) if self.sparse else self.mlp(h)
            x = x + post_norm(h, self.post_feedforward_layernorm._value,
                              self.post_feedforward_gate._value, self.eps,
                              self.gating)
        return x, cache


class GigaChat35Model(Layer):
    def __init__(self, config):
        super().__init__()
        c = config
        self.embed_tokens = self.create_parameter(
            [c.vocab_size, c.hidden_size], dtype=c.dtype,
            default_initializer=I.Normal(0.0, 1.0))
        self.layers = LayerList([GigaChat35DecoderLayer(c, j)
                                 for j in range(c.num_hidden_layers)])
        self.norm = self.create_parameter(
            [c.hidden_size], dtype=c.dtype,
            default_initializer=I.Constant(0.0))


class GigaChat35ForCausalLM(Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.model = GigaChat35Model(config)
        self.lm_head = self.create_parameter(
            [config.hidden_size, config.vocab_size], dtype=config.dtype,
            default_initializer=I.XavierNormal())
        # what the serving engine reads: expert layers whose counters
        # ride back with the tokens, and how many experts each holds
        self.moe_layers = sum(layer.sparse for layer in self.model.layers)
        self.moe_experts_held = len(config.experts_held)

    def _run(self, input_ids, caches, position_offset, logits_at=None):
        c = self.config
        ids = _val(input_ids)
        with jax.named_scope("embed"):
            x = jnp.take(self.model.embed_tokens._value, ids, axis=0)
        valid_len = None
        if caches is None:
            b, t = ids.shape
            caches = [
                None if layer.full_attention else _NoCache(
                    {name: jnp.zeros((b,) + shape, dtype) for
                     name, shape, dtype in
                     layer.linear_attn.state_spec(x.dtype)}, t)
                for layer in self.model.layers]
        else:
            # a prefill's hooks carry its real rows (a decode step's
            # carry None: every row is one real token)
            valid_len = next(cache.valid_len for cache, layer in
                             zip(caches, self.model.layers)
                             if not layer.full_attention)
        new_caches = []
        for i, layer in enumerate(self.model.layers):
            with jax.named_scope("layer_%d" % i):
                x, cache = layer(x, caches[i], position_offset, valid_len)
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

    def balance_router_bias(self, input_ids, rounds=200, step=0.02):
        """What training does to every expert layer's
        ``e_score_correction_bias``, done here on ``input_ids`` [B, T]
        (parallel/moe.py ``balance_router_biases``)."""
        balance_router_biases(
            self, [layer.mlp for layer in self.model.layers
                   if layer.sparse],
            lambda ids: self._run(ids, None, 0), _val(input_ids), rounds,
            step)

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
        """One entry a layer: latent pages (a row of ``kv_lora_rank +
        qk_rope_head_dim`` values a token) for a latent attention
        layer, the recurrent state and convolution tail for a Gated
        DeltaNet one."""
        from ..serving.kv_cache import LatentPages, SlotState

        c = self.config
        return [LatentPages(c.kv_lora_rank + c.qk_rope_head_dim, c.dtype)
                if layer.full_attention
                else SlotState(layer.linear_attn.state_spec(c.dtype))
                for layer in self.model.layers]
