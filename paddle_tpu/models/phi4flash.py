"""Phi-4-mini-flash: the SambaY decoder-hybrid-decoder
(microsoft/Phi-4-mini-flash-reasoning ``config.json``, ``model_type``
``phi4flash``; "Decoder-Hybrid-Decoder Architecture for Efficient
Reasoning with Long Generation", arXiv:2507.06607).

Every layer is pre-norm, LayerNorm with weight and bias::

    x <- x + mixer(LN1(x));   x <- x + MLP(LN2(x))
    MLP(u) = (silu(u W_g) * u W_u) W_d

and a final LayerNorm feeds a head tied to the embedding. With L layers
and ``mb_per_layer`` m, layer l is:

- l < L / 2: Mamba-1 when l % m == 0, else window attention (a query at
  position t sees keys t - window + 1 .. t);
- l = L / 2: Mamba-1, whose gated scan output before its out projection
  is the MEMORY M handed to the cross-decoder;
- l = L / 2 + 1: full causal attention, whose K/V are the model's only
  growing cache;
- l > L / 2 + 1: a gated memory unit ``(M * silu(u W_1)) W_2`` when
  l % m == 0, else cross attention: its own queries over layer
  L / 2 + 1's K/V.

Mamba-1 (N state elements, a depthwise causal convolution of
``mamba_d_conv`` taps with a bias, dt_rank ceil(hidden / 16)), per channel
c and state element n::

    [x, z] = u W_in;  x^ = silu(conv(x) + b);  [d, B, C] = x^ W_x
    dt = softplus(d W_dt + b_dt);  A = -exp(A_log)
    h_t[n, c] = exp(dt_t[c] A[c, n]) h_{t-1}[n, c] + dt_t[c] x^_t[c] B_t[n]
    y_t = h_t C_t + D x^_t;  out = (y * silu(z)) W_out

Attention is differential in all its layers (Ye et al.,
arXiv:2410.05258), with no positional rotation: query heads pair as
(2h, 2h+1), KV heads as (2g, 2g+1), query pair h reads KV pair h // 2;
``o_h = A1 [v_2g | v_2g+1] - lam A2 [v_2g | v_2g+1]`` with A1 the softmax
of q_2h against k_2g and A2 of q_2h+1 against k_2g+1
(serving/kernels/diff_attention.py), ``lam = exp(lq1 . lk1) -
exp(lq2 . lk2) + lam_init``, ``lam_init = 0.8 - 0.6 exp(-0.3 l)``; the
layer's output is ``concat_h(RMSNorm_2D(o_h) w (1 - lam_init)) W_o``.

What a layer keeps for a sequence (``paged_cache_spec``): Mamba-1 its
float32 state ``[N, channels]`` and convolution tail (``SlotState``);
window attention its last ``window`` K/V rows (``WindowRing``); the full
layer K/V pages, every head of a token side by side on the lanes
(``KVPages(flat=True)``); cross attention nothing of its own
(``SharedPages``: it reads the full layer's pages); a GMU nothing.

A prefill runs layers 0 .. L/2 over every row, writes the full layer's
K/V for every row, and runs that layer's attention, the cross-decoder and
the head on the rows ``logits_at`` names alone (YOCO, "you only cache
once"): no later position's output is ever read, so this is exact. The
prefill's Mamba-1 is the ``selective_scan`` kernel, its window layers
banded ``flash_attention``; a decode step's attention is ``diff_decode``
over the pages or a ring, its Mamba-1 one step in ``jax.numpy``.
The residual stream, the states and every product's accumulation and
result are float32; a product rounds its input to the weights' dtype
once. Inference code on raw arrays.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn import initializer as I
from ..nn.layer import Layer
from ..nn.layers.container import LayerList
from .generation import rows_at
from .nemotron_h import _InverseSoftplusLogUniform
from .qwen3_next import _NoCache

_F32 = jnp.float32


class Phi4FlashConfig:
    def __init__(self, vocab_size=200064, hidden_size=2560,
                 intermediate_size=10240, num_hidden_layers=32,
                 num_attention_heads=40, num_key_value_heads=20,
                 sliding_window=512, mb_per_layer=2, mamba_d_state=16,
                 mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=None,
                 layer_norm_eps=1e-5, max_position_embeddings=262144,
                 time_step_min=0.001, time_step_max=0.1,
                 time_step_floor=1e-4, lambda_std=0.1, dtype="float32"):
        if num_hidden_layers % 2 or num_attention_heads % 4 \
                or num_key_value_heads % 2:
            raise ValueError("phi4flash: an even depth, query heads in "
                             "pairs of pairs and KV heads in pairs")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = hidden_size // num_attention_heads
        self.sliding_window = sliding_window
        self.mb_per_layer = mb_per_layer
        self.mamba_d_state = mamba_d_state
        self.mamba_d_conv = mamba_d_conv
        self.mamba_inner = mamba_expand * hidden_size
        self.mamba_dt_rank = (math.ceil(hidden_size / 16)
                              if mamba_dt_rank is None else mamba_dt_rank)
        self.layer_norm_eps = layer_norm_eps
        self.max_position_embeddings = max_position_embeddings
        self.time_step_min = time_step_min
        self.time_step_max = time_step_max
        self.time_step_floor = time_step_floor
        self.lambda_std = lambda_std
        self.dtype = dtype

    @property
    def memory_layer(self):
        return self.num_hidden_layers // 2

    @property
    def full_layer(self):
        return self.num_hidden_layers // 2 + 1

    def layer_kind(self, i):
        """``mamba``, ``window``, ``full``, ``gmu`` or ``cross``."""
        if i <= self.memory_layer:
            return ("mamba" if i % self.mb_per_layer == 0
                    or i == self.memory_layer else "window")
        if i == self.full_layer:
            return "full"
        return "gmu" if i % self.mb_per_layer == 0 else "cross"

    @classmethod
    def tiny(cls, **kw):
        d = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
                 num_hidden_layers=8, num_attention_heads=8,
                 num_key_value_heads=4, sliding_window=8,
                 max_position_embeddings=512)
        d.update(kw)
        return cls(**d)


def _val(x):
    return x._value if isinstance(x, Tensor) else x


def _mm(a, w):
    """``a @ w`` with ``a`` rounded once to the weights' dtype and the
    product accumulated and returned in float32."""
    return jnp.matmul(a.astype(w.dtype), w, preferred_element_type=_F32)


def layer_norm(x, weight, bias, eps):
    """LayerNorm over the last axis in float32, in the weights' dtype."""
    xf = x.astype(_F32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(_F32)
            + bias.astype(_F32)).astype(weight.dtype)


class _SLinearA(I.Initializer):
    """A_log of Mamba-1: log(1 .. N) for every channel (S4D-real)."""

    def _generate(self, shape, dt):
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[-1] + 1, dtype=_F32)),
            shape).astype(dt)


class _FreshKV:
    """The attention hook when nobody keeps K/V (a plain forward over
    whole sequences): the rows of the call are all there is."""

    pool = None

    def __init__(self, window=None, fresh=None):
        self.window = window
        self.fresh = fresh

    def update(self, k, v):
        return _FreshKV(self.window, (_val(k), _val(v)))

    def attend_diff(self, q, lam, scale, positions):
        from ..serving.kernels.diff_attention import diff_prefill

        return diff_prefill(_val(q), *self.fresh, positions, lam, scale,
                            window=self.window)


# -- mixers ------------------------------------------------------------------

class Phi4FlashMamba(Layer):
    def __init__(self, config):
        super().__init__()
        c = config
        self.inner, self.n = c.mamba_inner, c.mamba_d_state
        self.kernel, self.rank = c.mamba_d_conv, c.mamba_dt_rank
        dt, xavier = c.dtype, I.XavierNormal()
        self.in_proj = self.create_parameter(
            [c.hidden_size, 2 * self.inner], dtype=dt,
            default_initializer=xavier)
        self.conv_weight = self.create_parameter(
            [self.inner, self.kernel], dtype=dt,
            default_initializer=I.Uniform(-0.5, 0.5))
        self.conv_bias = self.create_parameter(
            [self.inner], dtype=dt, default_initializer=I.Uniform(-0.5, 0.5))
        self.x_proj = self.create_parameter(
            [self.inner, self.rank + 2 * self.n], dtype=dt,
            default_initializer=xavier)
        self.dt_proj = self.create_parameter(
            [self.rank, self.inner], dtype=dt, default_initializer=xavier)
        self.dt_bias = self.create_parameter(
            [self.inner], dtype=dt,
            default_initializer=_InverseSoftplusLogUniform(
                c.time_step_min, c.time_step_max, c.time_step_floor))
        self.A_log = self.create_parameter(
            [self.inner, self.n], dtype=dt, default_initializer=_SLinearA())
        self.D = self.create_parameter(
            [self.inner], dtype=dt, default_initializer=I.Constant(1.0))
        self.out_proj = self.create_parameter(
            [self.inner, c.hidden_size], dtype=dt,
            default_initializer=xavier)

    def state_spec(self, dtype):
        """((name, one slot's shape, dtype), ...) for the cache spec:
        the state [N, channels] (state elements on the sublanes, the
        channels on the lanes: serving/kernels/selective_scan.py) and the
        convolution tail."""
        return (("state", (self.n, self.inner), "float32"),
                ("conv", (self.kernel - 1, self.inner), dtype))

    def forward(self, u, cache):
        """-> (output [B, T, hidden], hook, gated scan output [B, T,
        channels]: the memory when this is the memory layer)."""
        from ..serving.kernels.selective_scan import (selective_scan,
                                                      selective_step)

        t = u.shape[1]
        xz = _mm(u, self.in_proj._value)
        x, z = xz[..., :self.inner], xz[..., self.inner:]
        held = cache.read()
        # the rows the convolution sees: the tail kept from before, then
        # this call's rows
        window = jnp.concatenate([held["conv"].astype(_F32), x], axis=1)
        w = self.conv_weight._value.astype(_F32)
        conv = sum(window[:, j:j + t] * w[:, j] for j in range(self.kernel))
        conv = jax.nn.silu(conv + self.conv_bias._value.astype(_F32))
        dbc = _mm(conv, self.x_proj._value)
        b = dbc[..., self.rank:self.rank + self.n]
        c = dbc[..., self.rank + self.n:]
        dt = jax.nn.softplus(_mm(dbc[..., :self.rank], self.dt_proj._value)
                             + self.dt_bias._value.astype(_F32))
        a = -jnp.exp(self.A_log._value.astype(_F32)).T          # [N, C]
        d = self.D._value
        if cache.valid_len is None:
            # decode: an idle row takes dt = 0 and keeps its state as it
            # was, so the state is stored as it comes
            dt = jnp.where(cache.active[:, None], dt[:, 0], 0.0)
            y, state = selective_step(conv[:, 0], dt, a, b[:, 0], c[:, 0],
                                      d, held["state"])
            y = y[:, None]
            cache = cache.write({"state": state, "conv": window[:, 1:]},
                                kept=("state",))
        else:
            # a prompt, right-padded: the rows past valid_len change
            # neither the state nor the tail
            live = (jnp.arange(t) < cache.valid_len)[None, :, None]
            y, state = selective_scan(conv, jnp.where(live, dt, 0.0), a, b,
                                      c, d)
            tail = jax.lax.dynamic_slice_in_dim(
                window, cache.valid_len, self.kernel - 1, axis=1)
            cache = cache.write({"state": state, "conv": tail})
        gated = y * jax.nn.silu(z)
        return _mm(gated, self.out_proj._value), cache, gated


class Phi4FlashAttention(Layer):
    """Differential attention: self attention (window or full) with its
    own K/V, or, ``cross``, queries alone over another layer's K/V."""

    def __init__(self, config, layer_idx, cross=False):
        super().__init__()
        c = config
        self.heads, self.kv_heads = (c.num_attention_heads,
                                     c.num_key_value_heads)
        self.d = c.head_dim
        self.eps = c.layer_norm_eps
        self.lambda_init = 0.8 - 0.6 * math.exp(-0.3 * layer_idx)
        dt, xavier = c.dtype, I.XavierNormal()
        width = c.hidden_size
        self.q_proj = self.create_parameter(
            [width, self.heads * self.d], dtype=dt,
            default_initializer=xavier)
        if not cross:
            self.k_proj = self.create_parameter(
                [width, self.kv_heads * self.d], dtype=dt,
                default_initializer=xavier)
            self.v_proj = self.create_parameter(
                [width, self.kv_heads * self.d], dtype=dt,
                default_initializer=xavier)
        self.o_proj = self.create_parameter(
            [self.heads * self.d, width], dtype=dt,
            default_initializer=xavier)
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            setattr(self, name, self.create_parameter(
                [self.d], dtype="float32",
                default_initializer=I.Normal(0.0, c.lambda_std)))
        self.subln = self.create_parameter(
            [2 * self.d], dtype=dt, default_initializer=I.Constant(1.0))

    def lam(self):
        dot = [jnp.sum(getattr(self, "lambda_q%d" % i)._value.astype(_F32)
                       * getattr(self, "lambda_k%d" % i)._value.astype(_F32))
               for i in (1, 2)]
        return jnp.exp(dot[0]) - jnp.exp(dot[1]) + self.lambda_init

    def write(self, u, cache):
        """This layer's K/V of the rows ``u`` into its hook."""
        b, t, _ = u.shape
        k = jnp.matmul(u, self.k_proj._value).reshape(
            b, t, self.kv_heads, self.d)
        v = jnp.matmul(u, self.v_proj._value).reshape(
            b, t, self.kv_heads, self.d)
        return cache.update(k, v)

    def attend(self, u, cache, positions):
        """Queries of the rows ``u`` over what ``cache`` holds (after its
        write), to the layer's output."""
        b, r, _ = u.shape
        q = jnp.matmul(u, self.q_proj._value).reshape(
            b, r, self.heads, self.d)
        o = cache.attend_diff(q, self.lam(), 1.0 / math.sqrt(self.d),
                              positions)                 # [B, R, H/2, 2D]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + self.eps)
        o = o * self.subln._value.astype(_F32) * (1.0 - self.lambda_init)
        return _mm(o.reshape(b, r, self.heads * self.d), self.o_proj._value)


class Phi4FlashGMU(Layer):
    """Gated memory unit: ``(M * silu(u W_1)) W_2``."""

    def __init__(self, config):
        super().__init__()
        c = config
        xavier = I.XavierNormal()
        self.in_proj = self.create_parameter(
            [c.hidden_size, c.mamba_inner], dtype=c.dtype,
            default_initializer=xavier)
        self.out_proj = self.create_parameter(
            [c.mamba_inner, c.hidden_size], dtype=c.dtype,
            default_initializer=xavier)

    def forward(self, u, memory):
        return _mm(memory * jax.nn.silu(_mm(u, self.in_proj._value)),
                   self.out_proj._value)


class Phi4FlashMLP(Layer):
    def __init__(self, config):
        super().__init__()
        c = config
        xavier = I.XavierNormal()
        self.w_gate = self.create_parameter(
            [c.hidden_size, c.intermediate_size], dtype=c.dtype,
            default_initializer=xavier)
        self.w_up = self.create_parameter(
            [c.hidden_size, c.intermediate_size], dtype=c.dtype,
            default_initializer=xavier)
        self.w_down = self.create_parameter(
            [c.intermediate_size, c.hidden_size], dtype=c.dtype,
            default_initializer=xavier)

    def forward(self, u):
        return _mm(jax.nn.silu(_mm(u, self.w_gate._value))
                   * _mm(u, self.w_up._value), self.w_down._value)


_SCOPES = {"mamba": "ssm", "gmu": "ssm", "window": "attn", "full": "attn",
           "cross": "attn"}


class Phi4FlashBlock(Layer):
    def __init__(self, config, i):
        super().__init__()
        c = config
        self.kind = c.layer_kind(i)
        self.scope = _SCOPES[self.kind]
        self.eps = c.layer_norm_eps
        for name, init in (("ln1_weight", 1.0), ("ln1_bias", 0.0),
                           ("ln2_weight", 1.0), ("ln2_bias", 0.0)):
            setattr(self, name, self.create_parameter(
                [c.hidden_size], dtype=c.dtype,
                default_initializer=I.Constant(init)))
        if self.kind == "mamba":
            self.mixer = Phi4FlashMamba(c)
        elif self.kind == "gmu":
            self.mixer = Phi4FlashGMU(c)
        else:
            self.mixer = Phi4FlashAttention(c, i, cross=self.kind == "cross")
        self.mlp = Phi4FlashMLP(c)

    def ln1(self, x):
        return layer_norm(x, self.ln1_weight._value, self.ln1_bias._value,
                          self.eps)

    def ffn(self, x):
        with jax.named_scope("mlp"):
            return x + self.mlp(layer_norm(x, self.ln2_weight._value,
                                           self.ln2_bias._value, self.eps))


class Phi4FlashModel(Layer):
    def __init__(self, config):
        super().__init__()
        c = config
        self.embed_tokens = self.create_parameter(
            [c.vocab_size, c.hidden_size], dtype=c.dtype,
            default_initializer=I.Normal(0.0, 0.02))
        self.layers = LayerList([Phi4FlashBlock(c, i)
                                 for i in range(c.num_hidden_layers)])
        self.final_norm_weight = self.create_parameter(
            [c.hidden_size], dtype=c.dtype,
            default_initializer=I.Constant(1.0))
        self.final_norm_bias = self.create_parameter(
            [c.hidden_size], dtype=c.dtype,
            default_initializer=I.Constant(0.0))


class Phi4FlashForCausalLM(Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.model = Phi4FlashModel(config)
        kinds = [config.layer_kind(i)
                 for i in range(config.num_hidden_layers)]
        # what the serving engine reads: state-space layers for
        # stats()["ssm"]; the rows a prefill of each bucket ran past the
        # self-decoder (YOCO), recorded when the prefill is traced, for
        # stats()["yoco"]
        self.ssm_layers = kinds.count("mamba")
        self.yoco_rows = {}

    def _no_caches(self, b, t, dtype):
        c = self.config

        def hook(i, layer):
            if layer.kind == "mamba":
                return _NoCache({name: jnp.zeros((b,) + shape, dt) for
                                 name, shape, dt in
                                 layer.mixer.state_spec(dtype)}, t)
            if layer.kind == "window":
                return _FreshKV(c.sliding_window)
            if layer.kind == "full":
                return _FreshKV()
            return None

        return [hook(i, layer) for i, layer in enumerate(self.model.layers)]

    def _run(self, input_ids, caches, logits_at=None):
        c = self.config
        ids = _val(input_ids)
        b, t = ids.shape
        embed = self.model.embed_tokens._value
        with jax.named_scope("embed"):
            # the residual stream is kept in float32 through all the
            # layers, and so is every product: a matmul rounds its input
            # to the weights' dtype once and accumulates in float32
            x = jnp.take(embed, ids, axis=0).astype(_F32)
        if caches is None:
            caches = self._no_caches(b, t, embed.dtype)
        # a decode step's hooks hold one row a slot at its own length;
        # a prompt's rows are at 0 .. t-1
        decode = caches[0].valid_len is None
        positions = jnp.arange(t)
        memory = None
        new_caches = list(caches)
        for i, layer in enumerate(self.model.layers):
            kind = layer.kind
            with jax.named_scope("layer_%d" % i):
                with jax.named_scope(layer.scope):
                    u = layer.ln1(x)
                    if kind == "mamba":
                        out, new_caches[i], gated = layer.mixer(u, caches[i])
                        if i == c.memory_layer:
                            memory = gated
                    elif kind == "gmu":
                        out = layer.mixer(u, memory)
                    elif kind == "cross":
                        # the full layer's hook after its write
                        out = layer.mixer.attend(
                            u, new_caches[c.full_layer], positions)
                    else:
                        new_caches[i] = layer.mixer.write(u, caches[i])
                        if kind == "full" and logits_at is not None \
                                and not decode:
                            # YOCO: past the full layer's write only the
                            # rows whose logits are read go on
                            x, u, memory = (rows_at(a, logits_at)
                                            for a in (x, u, memory))
                            positions = jnp.reshape(logits_at, (b, -1))
                        out = layer.mixer.attend(u, new_caches[i],
                                                 positions)
                        if kind == "full" and not decode:
                            self.yoco_rows[t] = b * x.shape[1]
                    x = x + out
                x = layer.ffn(x)
        with jax.named_scope("lm_head"):
            x = layer_norm(x, self.model.final_norm_weight._value,
                           self.model.final_norm_bias._value,
                           c.layer_norm_eps)
            logits = _mm(x, embed.T)
        return Tensor(logits), new_caches

    def forward(self, input_ids):
        """Logits [B, T, vocab] of whole sequences, nothing kept."""
        return self._run(input_ids, None)[0]

    def generate_step(self, input_ids, caches, position_offset,
                      logits_at=None):
        """One compiled step of the serving engine: ``caches`` is one
        hook a layer (serving/kv_cache.py); ``position_offset`` is not
        used (no layer rotates by position; a hook knows its lengths);
        ``logits_at`` (generation.rows_at) names the one row a sequence
        whose logits are read, and in a prefill everything past the full
        layer's write runs on that row alone."""
        return self._run(input_ids, caches, logits_at)

    def max_decode_len(self):
        return self.config.max_position_embeddings

    def paged_cache_spec(self):
        from ..serving.kv_cache import (KVPages, NoCache, SharedPages,
                                        SlotState, WindowRing)

        c = self.config

        def entry(layer):
            if layer.kind == "mamba":
                return SlotState(layer.mixer.state_spec(c.dtype))
            if layer.kind == "window":
                return WindowRing(c.sliding_window, c.num_key_value_heads,
                                  c.head_dim, c.dtype)
            if layer.kind == "full":
                return KVPages(c.num_key_value_heads, c.head_dim, c.dtype,
                               flat=True)
            if layer.kind == "cross":
                return SharedPages(c.full_layer)
            return NoCache()

        return [entry(layer) for layer in self.model.layers]
