"""Nemotron-H: a hybrid decoder whose every layer is ONE mixer behind
one norm — a Mamba-2 state-space mixer (``M``), a sparse mixture of
experts (``E``) or softmax attention (``*``), in the order a pattern
string gives (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 ``config.json``,
``model_type`` ``nemotron_h``; "Nemotron-H", arXiv:2504.03624; Dao & Gu,
"Transformers are SSMs", arXiv:2405.21060).

    x <- x + mixer(RMSNorm(x))        plain-weight RMSNorm, no bias

What a layer keeps for a sequence differs by kind, and the model only
SAYS so (``paged_cache_spec``): ``M`` keeps a recurrent state in float32
and the last ``conv_kernel - 1`` rows that went into its causal
convolution (``SlotState``), ``*`` keeps K/V pages (``KVPages``), ``E``
keeps nothing (``NoCache``). The serving engine owns all three
(serving/kv_cache.py).

Mamba-2, per head h (H heads of P values, state N wide, B and C shared
by the H / G heads of a group)::

    z, xBC, dt = in_proj(u)                      [H P | H P + 2 G N | H]
    xBC_t = silu(bias + sum_j w[:, j] xBC_{t-K+1+j})     causal, depthwise
    dt_t  = softplus(dt_t + dt_bias);  A = -exp(A_log)
    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g]
    y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]
    out = out_proj(RMSNorm_groups(y * silu(z)))  gate first, then the norm

in two forms that give the same numbers: ``ssd_chunked`` for a prompt
(``chunk_size`` tokens a chunk: inside a chunk the decay-masked C B^T
product applied to dt x, between chunks the carried state) and the
``ssm_decode`` kernel for one token a slot (serving/kernels/ssm.py). A
padded row takes dt = 0, which leaves the state exactly as it was. A
slot's state is laid out ``[G, N, (H / G) P]`` (the kernel's docstring
says why), which is what the cache spec declares.

Attention has no positional rotation and no QK norm: position reaches
the model through the state-space layers. The experts are
``parallel/moe.py``'s dropless layer, not gated (``down(relu(up x)^2)``),
routed by sigmoid scores with a selection bias
(``e_score_correction_bias``), told which experts live here
(``experts_held``); the shared expert is whole. ``balance_router_bias``
gives that bias what training gives it, the family's balancing rule on
given tokens: a model built from random weights routes every token to
the same few experts without it. Inference code on raw arrays; the
model hands the engine its expert layers' step counters through
``moe_step_stats``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn import initializer as I
from ..nn.functional.norm import rms_norm as _rms_norm
from ..nn.layer import Layer
from ..nn.layers.container import LayerList
from ..parallel.moe import (MoELayer, balance_router_biases,
                            balance_select_bias, moe_forward)
from .generation import rows_at
# the hook a slot_state layer gets when nobody keeps its state
from .qwen3_next import _NoCache

_F32 = jnp.float32
rms_norm = _rms_norm.raw_fn
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


class NemotronHConfig:
    def __init__(self, vocab_size=131072, hidden_size=2688,
                 hybrid_override_pattern=PUBLISHED_PATTERN,
                 num_attention_heads=32, num_key_value_heads=2,
                 head_dim=128, mamba_num_heads=64, mamba_head_dim=64,
                 ssm_state_size=128, n_groups=8, conv_kernel=4,
                 chunk_size=128, time_step_min=0.001, time_step_max=0.1,
                 time_step_floor=1e-4, n_routed_experts=128,
                 num_experts_per_tok=6, moe_intermediate_size=1856,
                 moe_shared_expert_intermediate_size=3712,
                 routed_scaling_factor=2.5, norm_topk_prob=True,
                 layer_norm_epsilon=1e-5, experts_held=None,
                 max_position_embeddings=262144, dtype="float32"):
        """One character of ``hybrid_override_pattern`` a layer.
        ``n_routed_experts`` is the router's published width;
        ``experts_held`` (a range, default all) the experts that live
        here. ``vocab_size`` is the number of vocabulary rows held here.
        ``n_groups`` is Mamba's (B and C groups); the router has no
        groups."""
        if set(hybrid_override_pattern) - set("ME*"):
            raise ValueError("hybrid_override_pattern %r: a layer is M, E "
                             "or *" % (hybrid_override_pattern,))
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.hybrid_override_pattern = hybrid_override_pattern
        self.num_hidden_layers = len(hybrid_override_pattern)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.mamba_num_heads = mamba_num_heads
        self.mamba_head_dim = mamba_head_dim
        self.ssm_state_size = ssm_state_size
        self.n_groups = n_groups
        self.conv_kernel = conv_kernel
        self.chunk_size = chunk_size
        self.time_step_min = time_step_min
        self.time_step_max = time_step_max
        self.time_step_floor = time_step_floor
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.moe_shared_expert_intermediate_size = \
            moe_shared_expert_intermediate_size
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.layer_norm_epsilon = layer_norm_epsilon
        self.experts_held = (range(n_routed_experts)
                             if experts_held is None else experts_held)
        self.max_position_embeddings = max_position_embeddings
        self.dtype = dtype

    @classmethod
    def tiny(cls, **kw):
        d = dict(vocab_size=128, hidden_size=64,
                 hybrid_override_pattern="MEM*E", num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16, mamba_num_heads=4,
                 mamba_head_dim=8, ssm_state_size=16, n_groups=2,
                 chunk_size=8, n_routed_experts=8, num_experts_per_tok=3,
                 moe_intermediate_size=32,
                 moe_shared_expert_intermediate_size=48,
                 experts_held=range(4), max_position_embeddings=512)
        d.update(kw)
        return cls(**d)


def _val(x):
    return x._value if isinstance(x, Tensor) else x


class _LogUniform(I.Initializer):
    """log of a uniform draw: A_log, so that A = -exp(A_log) lies in
    -[low, high]."""

    def __init__(self, low, high):
        self.draw = I.Uniform(low, high)

    def _generate(self, shape, dt):
        return jnp.log(self.draw._generate(shape, _F32)).astype(dt)


class _InverseSoftplusLogUniform(I.Initializer):
    """dt_bias: the inverse softplus of a time step drawn log-uniform in
    [low, high] and floored, the family's init."""

    def __init__(self, low, high, floor):
        self.draw = I.Uniform(math.log(low), math.log(high))
        self.floor = floor

    def _generate(self, shape, dt):
        step = jnp.maximum(jnp.exp(self.draw._generate(shape, _F32)),
                           self.floor)
        return (step + jnp.log(-jnp.expm1(-step))).astype(dt)


# -- the state-space recurrence over a prompt --------------------------------

def ssd_chunked(x, dt, a, b, c, state, chunk):
    """The Mamba-2 recurrence over T tokens of B sequences, a chunk at a
    time, in float32. x [B, T, H, P], dt [B, T, H] (after the softplus;
    0 on a padded row, which then changes nothing), a [H] (negative),
    b, c [B, T, G, N], state [B, G, N, (H / G) P]. T is padded up to a
    whole chunk with such rows here.
    -> (y [B, T, H, P] without the D x term, state after the last row).

    Inside a chunk, with ``cum`` the running sum of dt A: y_t gets
    sum_{s <= t} (C_t . B_s) exp(cum_t - cum_s) dt_s x_s from its own
    chunk and exp(cum_t) C_t S_in from the state the chunk was entered
    with; the chunk leaves exp(cum_L) S_in + sum_s exp(cum_L - cum_s)
    dt_s x_s (x) B_s."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    hg = h // g
    pad = -t % chunk
    if pad:
        x, dt, b, c = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, b, c))
    m = (t + pad) // chunk
    x = x.astype(_F32).reshape(bsz, m, chunk, g, hg, p)
    dt = dt.astype(_F32).reshape(bsz, m, chunk, g, hg)
    b = b.astype(_F32).reshape(bsz, m, chunk, g, n)
    c = c.astype(_F32).reshape(bsz, m, chunk, g, n)
    # [B, m, G, hg, L]: the running log-decay inside each chunk
    cum = jnp.cumsum(jnp.moveaxis(dt, 2, -1)
                     * a.astype(_F32).reshape(g, hg, 1), axis=-1)
    dtx = dt[..., None] * x                               # [B,m,L,G,hg,P]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # exp(cum_t - cum_s) for s <= t; masked before the exp, not after
    decay = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                  # [B,m,G,hg,L,L]
    scores = jnp.einsum("bmtgk,bmsgk->bmgts", c, b)[:, :, :, None] * decay
    y = jnp.einsum("bmghts,bmsghp->bmtghp", scores, dtx)
    # what each chunk adds to the state, and what it keeps of the old
    keep = jnp.exp(cum[..., -1:] - cum)                   # [B,m,G,hg,L]
    added = jnp.einsum("bmsgk,bmsghp->bmgkhp", b,
                       jnp.moveaxis(keep, -1, 2)[..., None] * dtx)
    kept = jnp.exp(cum[..., -1])                          # [B,m,G,hg]

    def enter(s, xs):
        added_i, kept_i = xs
        return s * kept_i[:, :, None, :, None] + added_i, s

    state, entered = jax.lax.scan(
        enter, state.reshape(bsz, g, n, hg, p),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(kept, 1, 0)))
    carried = jnp.einsum("bmtgk,mbgkhp->bmtghp", c, entered)
    y = y + carried * jnp.moveaxis(jnp.exp(cum), -1, 2)[..., None]
    return (y.reshape(bsz, t + pad, h, p)[:, :t],
            state.reshape(bsz, g, n, hg * p))


# -- mixers ------------------------------------------------------------------

class NemotronHMamba2(Layer):
    def __init__(self, config):
        super().__init__()
        c = config
        self.h, self.p = c.mamba_num_heads, c.mamba_head_dim
        self.g, self.n = c.n_groups, c.ssm_state_size
        self.kernel, self.chunk = c.conv_kernel, c.chunk_size
        self.eps = c.layer_norm_epsilon
        self.inner = self.h * self.p
        self.conv_dim = self.inner + 2 * self.g * self.n
        dt, xavier = c.dtype, I.XavierNormal()
        self.in_proj = self.create_parameter(
            [c.hidden_size, self.inner + self.conv_dim + self.h], dtype=dt,
            default_initializer=xavier)
        self.conv_weight = self.create_parameter(
            [self.conv_dim, self.kernel], dtype=dt,
            default_initializer=I.Uniform(-0.5, 0.5))
        self.conv_bias = self.create_parameter(
            [self.conv_dim], dtype=dt,
            default_initializer=I.Uniform(-0.5, 0.5))
        self.A_log = self.create_parameter(
            [self.h], dtype=dt, default_initializer=_LogUniform(1.0, 16.0))
        self.dt_bias = self.create_parameter(
            [self.h], dtype=dt,
            default_initializer=_InverseSoftplusLogUniform(
                c.time_step_min, c.time_step_max, c.time_step_floor))
        self.D = self.create_parameter(
            [self.h], dtype=dt, default_initializer=I.Constant(1.0))
        self.norm_weight = self.create_parameter(
            [self.inner], dtype=dt, default_initializer=I.Constant(1.0))
        self.out_proj = self.create_parameter(
            [self.inner, c.hidden_size], dtype=dt,
            default_initializer=xavier)

    def state_spec(self, dtype):
        """((name, one slot's shape, dtype), ...) for the cache spec."""
        return (("state", (self.g, self.n, self.inner // self.g),
                 "float32"),
                ("conv", (self.kernel - 1, self.conv_dim), dtype))

    def forward(self, x, cache):
        from ..serving.kernels.ssm import ssm_decode

        bsz, t, _ = x.shape
        zxbcdt = jnp.matmul(x, self.in_proj._value)
        z = zxbcdt[..., :self.inner]
        mixed = zxbcdt[..., self.inner:self.inner + self.conv_dim]
        dt = jax.nn.softplus(
            zxbcdt[..., self.inner + self.conv_dim:].astype(_F32)
            + self.dt_bias._value.astype(_F32))
        held = cache.read()
        # the rows the convolution sees: the tail kept from before, then
        # this call's rows
        window = jnp.concatenate(
            [held["conv"].astype(mixed.dtype), mixed], axis=1)
        w = self.conv_weight._value.astype(_F32)
        conv = sum(window[:, j:j + t].astype(_F32) * w[:, j]
                   for j in range(self.kernel))
        conv = jax.nn.silu(
            conv + self.conv_bias._value.astype(_F32)).astype(x.dtype)
        gn = self.g * self.n
        xs = conv[..., :self.inner].reshape(bsz, t, self.h, self.p)
        b = conv[..., self.inner:self.inner + gn].reshape(
            bsz, t, self.g, self.n)
        c = conv[..., self.inner + gn:].reshape(bsz, t, self.g, self.n)
        a = -jnp.exp(self.A_log._value.astype(_F32))
        d = self.D._value.astype(_F32)
        if cache.valid_len is None:
            # decode: one real token a row, the state updated in place
            # for the active rows
            y, state = ssm_decode(xs[:, 0], dt[:, 0], a, d, b[:, 0],
                                  c[:, 0], cache.active, held["state"])
            y = y[:, None]
            cache = cache.write({"state": state, "conv": window[:, 1:]},
                                kept=("state",))
        else:
            # a prompt, right-padded: the rows past valid_len change
            # neither the state nor the tail
            live = (jnp.arange(t) < cache.valid_len)[None, :, None]
            y, state = ssd_chunked(xs, jnp.where(live, dt, 0.0), a, b, c,
                                   held["state"], self.chunk)
            y = y + d[:, None] * xs.astype(_F32)
            tail = jax.lax.dynamic_slice_in_dim(
                window, cache.valid_len, self.kernel - 1, axis=1)
            cache = cache.write({"state": state, "conv": tail})
        # gate first, then RMSNorm over each group's values
        y = y.reshape(bsz, t, self.inner) * jax.nn.silu(z.astype(_F32))
        grouped = y.reshape(bsz, t, self.g, self.inner // self.g)
        inv = jax.lax.rsqrt(
            jnp.mean(grouped * grouped, axis=-1, keepdims=True) + self.eps)
        y = ((grouped * inv).reshape(bsz, t, self.inner)
             * self.norm_weight._value.astype(_F32)).astype(x.dtype)
        return jnp.matmul(y, self.out_proj._value), cache


class NemotronHAttention(Layer):
    """Grouped-query softmax attention, no rotation, no QK norm."""

    def __init__(self, config):
        super().__init__()
        c = config
        self.heads, self.kv_heads = (c.num_attention_heads,
                                     c.num_key_value_heads)
        self.d = c.head_dim
        dt, xavier = c.dtype, I.XavierNormal()
        self.q_proj = self.create_parameter(
            [c.hidden_size, self.heads * self.d], dtype=dt,
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

    def forward(self, x, cache=None):
        from ..nn import functional as F

        b, t, _ = x.shape
        q = jnp.matmul(x, self.q_proj._value).reshape(
            b, t, self.heads, self.d)
        k = jnp.matmul(x, self.k_proj._value).reshape(
            b, t, self.kv_heads, self.d)
        v = jnp.matmul(x, self.v_proj._value).reshape(
            b, t, self.kv_heads, self.d)
        if cache is not None:
            ctx, cache = cache.update_and_attend(q, k, v)
        else:
            rep = self.heads // self.kv_heads
            ctx = F.scaled_dot_product_attention(
                q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
                is_causal=True)
        ctx = _val(ctx).reshape(b, t, self.heads * self.d).astype(x.dtype)
        return jnp.matmul(ctx, self.o_proj._value), cache


class NemotronHMoE(Layer):
    """The routed experts held here (parallel/moe.py: not gated, relu^2,
    sigmoid scores with a selection bias) plus the shared expert of the
    same form."""

    def __init__(self, config):
        super().__init__()
        c = config
        self.experts = MoELayer(
            c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
            top_k=c.num_experts_per_tok, activation="relu2", gated=False,
            bias=False, norm_topk_prob=c.norm_topk_prob,
            experts_held=c.experts_held, dtype=c.dtype)
        self.routed_scaling_factor = float(c.routed_scaling_factor)
        dt, xavier = c.dtype, I.XavierNormal()
        # seeded non-zero, so that leaving it out of the choice, or
        # putting it into the weights, shows
        self.e_score_correction_bias = self.create_parameter(
            [c.n_routed_experts], dtype=dt,
            default_initializer=I.Uniform(-0.1, 0.1))
        width = c.moe_shared_expert_intermediate_size
        self.shared_up = self.create_parameter(
            [c.hidden_size, width], dtype=dt, default_initializer=xavier)
        self.shared_down = self.create_parameter(
            [width, c.hidden_size], dtype=dt, default_initializer=xavier)
        self.step_stats = None

    def routed(self, flat):
        """The share of the routed sum the experts held here give, on
        [rows, hidden]; the step's counters are kept for the engine."""
        e = self.experts
        out, _, self.step_stats = moe_forward(
            flat, e.gate_weight._value, e.w1._value, None, e.w2._value,
            None, top_k=e.top_k, lo=e.experts_held.start,
            activation="relu2", gated=False,
            norm_topk_prob=e.norm_topk_prob,
            routed_scaling_factor=self.routed_scaling_factor,
            select_bias=self.e_score_correction_bias._value)
        return out

    def shared(self, flat):
        """The shared expert: every chip that shares the layer computes
        it alike, so a sum over shares counts it once."""
        up = jax.nn.relu(jnp.matmul(flat, self.shared_up._value))
        return jnp.matmul(up * up, self.shared_down._value)

    def balance(self, flat, rounds, step):
        """``rounds`` updates of ``e_score_correction_bias`` on the rows
        ``flat`` [rows, hidden] by the rule that keeps it in training
        (parallel/moe.py ``balance_select_bias``)."""
        bias = self.e_score_correction_bias
        bias._value = balance_select_bias(
            flat, self.experts.gate_weight._value, bias._value,
            self.experts.top_k, rounds, step)

    def forward(self, x):
        flat = x.reshape(-1, x.shape[-1])
        return (self.routed(flat) + self.shared(flat)).reshape(x.shape)


_MIXERS = {"M": ("ssm", NemotronHMamba2), "E": ("moe", NemotronHMoE),
           "*": ("attn", NemotronHAttention)}


class NemotronHBlock(Layer):
    def __init__(self, config, kind):
        super().__init__()
        self.kind = kind
        self.scope, mixer = _MIXERS[kind]
        self.eps = config.layer_norm_epsilon
        self.norm = self.create_parameter(
            [config.hidden_size], dtype=config.dtype,
            default_initializer=I.Constant(1.0))
        self.mixer = mixer(config)

    def forward(self, x, cache):
        with jax.named_scope(self.scope):
            h = rms_norm(x, self.norm._value, self.eps)
            if self.kind == "E":
                mixed = self.mixer(h)
            else:
                mixed, cache = self.mixer(h, cache)
            return x + mixed, cache


class NemotronHModel(Layer):
    def __init__(self, config):
        super().__init__()
        c = config
        self.embeddings = self.create_parameter(
            [c.vocab_size, c.hidden_size], dtype=c.dtype,
            default_initializer=I.Normal(0.0, 1.0))
        self.layers = LayerList([NemotronHBlock(c, kind)
                                 for kind in c.hybrid_override_pattern])
        self.norm_f = self.create_parameter(
            [c.hidden_size], dtype=c.dtype,
            default_initializer=I.Constant(1.0))


class NemotronHForCausalLM(Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.backbone = NemotronHModel(config)
        self.lm_head = self.create_parameter(
            [config.hidden_size, config.vocab_size], dtype=config.dtype,
            default_initializer=I.XavierNormal())
        # what the serving engine reads: expert layers whose counters
        # ride back with the tokens and how many experts each holds;
        # state-space layers, for stats()["ssm"]
        pattern = config.hybrid_override_pattern
        self.moe_layers = pattern.count("E")
        self.moe_experts_held = len(config.experts_held)
        self.ssm_layers = pattern.count("M")

    def _run(self, input_ids, caches, logits_at=None):
        c = self.config
        ids = _val(input_ids)
        with jax.named_scope("embed"):
            x = jnp.take(self.backbone.embeddings._value, ids, axis=0)
        if caches is None:
            b, t = ids.shape
            caches = [
                _NoCache({name: jnp.zeros((b,) + shape, dtype) for
                          name, shape, dtype in
                          layer.mixer.state_spec(x.dtype)}, t)
                if layer.kind == "M" else None
                for layer in self.backbone.layers]
        new_caches = []
        for i, layer in enumerate(self.backbone.layers):
            with jax.named_scope("layer_%d" % i):
                x, cache = layer(x, caches[i])
            new_caches.append(cache)
        with jax.named_scope("lm_head"):
            x = rms_norm(rows_at(x, logits_at), self.backbone.norm_f._value,
                         c.layer_norm_epsilon)
            logits = jnp.matmul(x, self.lm_head._value)
        return Tensor(logits), new_caches

    def forward(self, input_ids):
        """Logits [B, T, vocab] of whole sequences, nothing kept."""
        return self._run(input_ids, None)[0]

    def generate_step(self, input_ids, caches, position_offset,
                      logits_at=None):
        """One compiled step of the serving engine: ``caches`` is one
        hook a layer (serving/kv_cache.py); ``position_offset`` is not
        used (no layer rotates by position); ``logits_at``
        (generation.rows_at) names the one row a sequence to norm and
        project."""
        return self._run(input_ids, caches, logits_at)

    def balance_router_bias(self, input_ids, rounds=200, step=0.02):
        """What training does to every expert layer's
        ``e_score_correction_bias``, done here on ``input_ids`` [B, T]:
        one forward with each router's input caught on its way in, then
        ``NemotronHMoE.balance`` a layer. The published buffer exists to
        keep the experts' loads even; under seeded random weights the
        hidden states of all tokens share a large common part (a
        state-space layer averages its inputs), so without this every
        token picks much the same few experts and which ones is a draw
        of the seed."""
        balance_router_biases(
            self, [layer.mixer for layer in self.backbone.layers
                   if layer.kind == "E"],
            lambda ids: self._run(ids, None), _val(input_ids), rounds, step)

    def moe_step_stats(self):
        """int32 [expert layers, 4] of the step just traced: pairs
        routed to the experts held here, held experts that received a
        row, the largest load of one expert, rows handed to the
        grouped matmuls."""
        return jnp.stack([layer.mixer.step_stats
                          for layer in self.backbone.layers
                          if layer.kind == "E"])

    def max_decode_len(self):
        return self.config.max_position_embeddings

    def paged_cache_spec(self):
        """One entry a layer: the recurrent state and convolution tail
        for ``M``, K/V pages for ``*``, nothing for ``E``."""
        from ..serving.kv_cache import KVPages, NoCache, SlotState

        c = self.config

        def entry(layer):
            if layer.kind == "M":
                return SlotState(layer.mixer.state_spec(c.dtype))
            if layer.kind == "*":
                return KVPages(c.num_key_value_heads, c.head_dim, c.dtype)
            return NoCache()

        return [entry(layer) for layer in self.backbone.layers]
