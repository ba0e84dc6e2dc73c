"""The GigaChat3.5 family: a hybrid decoder in periods of four layers —
three Gated DeltaNet (linear attention) layers, then one gated
multi-head latent attention (MLA) layer — with sandwich zero-centred
norms, three leading dense SwiGLU layers, then sparse layers that route
top-8 of 256 experts by sigmoid scores beside one ungated shared expert,
every SwiGLU clamped (ai-sage/GigaChat3.5-432B-A28B ``config.json``,
``model_type`` ``gigachat3_5``; Yang et al., "Gated Delta Networks",
arXiv:2412.06464; DeepSeek-V2, arXiv:2405.04434, for latent attention).

Three things live here, as in ``families/qwen3_next.py``:

- ``build_model``: the system under test through the program's normal
  classes (``paddle_tpu.models.gigachat3_5``), nothing patched but the
  router biases balanced, as ``families/nemotron_h.py`` does;
- ``reference_*``: the architecture in plain ``jax.numpy`` float32 under
  ``jax.default_matmul_precision("highest")``, from the equations below,
  with no kernel, cache, absorption, chunking or batching and no import
  from ``paddle_tpu``: the Gated DeltaNet is the token-by-token
  recurrence (a ``lax.scan``), latent attention runs over expanded heads
  over the whole sequence, the router is written here, every held expert
  of a group runs on every token weighted by it (0 where not chosen). It
  reads the program's own weight arrays and upcasts one block at a time,
  the dense MLP a quarter of its width and the experts a group at a
  time, so that it fits beside a loaded engine;
- the arithmetic the layer metrics divide by.

The equations (``cfg`` keys in brackets). The ``j``-th layer built here
is published layer ``i = layers_held[j]``:

- block: ``x += Npost(mixer(N(x))); x += Npost'(mlp(N'(x)))`` with
  ``N(x) = x / sqrt(mean(x^2) + rms_norm_eps) * (1 + w)`` and ``Npost(x)
  = N(x) * layernorm_gating_weight * sigmoid(gamma)``, w and gamma per
  channel; the mixer is latent attention when ``i`` is in
  ``full_attention_layers``, else a Gated DeltaNet; the mlp is a SwiGLU
  of ``intermediate_size`` when ``i < first_k_dense_replace``, else the
  expert layer;
- Gated DeltaNet: ``in_proj_qkvz`` gives q, k (``linear_num_key_heads``
  x ``linear_key_head_dim``) and v, z (``linear_num_value_heads`` x
  ``linear_value_head_dim``), ``in_proj_ba`` gives b, a; causal
  depthwise convolution (``linear_conv_kernel_dim``) then SiLU over
  [q, k, v]; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
  dt_bias)``; q, k L2-normalised per head, q scaled by ``Dk^-1/2``, key
  head j serving value heads 2j, 2j + 1; per value head
  ``S <- exp(g_t) S; u = (v_t - S^T k_t) beta_t; S <- S + k_t u^T;
  o_t = S^T q_t``; ``out_proj(Nhead(o) * linear_sigmoid_gate_scale *
  sigmoid(z))``, ``Nhead`` zero-centred over a head with
  ``linear_attn_o_norm_eps``;
- latent attention: ``c_q = norm(x W_dq)`` [q_lora_rank]; per head
  ``[q_nope | q_pe] = c_q W_uq``; ``[c_kv | k_pe] = x W_dkv``, ``c_kv =
  norm(c_kv)`` (these two norms plain-weight, DeepSeek-V2's); rotary on
  ``q_pe`` and on the one ``k_pe`` all heads share, pairs (2i, 2i+1),
  YaRN [rope_scaling]; per head ``[k_nope | v] = c_kv W_ukv``; scores at
  ``(nope + rope)^-1/2 m(mscale_all_dim)^2``, causal softmax; ``o_h =
  sum p v``; ``o_proj(concat_h(o) * sigmoid(x W_g))``, ``W_g`` [hidden,
  heads x v_head_dim];
- SwiGLU (dense, experts, shared): ``W_down(silu(min(g, swiglu_limit)) *
  clip(u, -swiglu_limit, swiglu_limit))``, ``[g | u] = x W_gate_up``;
- experts: ``s = sigmoid(x W_r)`` over ``n_routed_experts_published``;
  the ``num_experts_per_tok`` chosen are the top of ``s +
  e_score_correction_bias``; their weights are ``s`` there over their
  sum + 1e-20, times ``routed_scaling_factor``; ``y = sum_{e chosen, e
  held here} w_e E_e(x) + S(x)``, ``S`` one SwiGLU of
  ``n_shared_experts`` times ``moe_intermediate_size``, ungated.

Departures of the reference from the published model, all forced by what
it is compared with: weights are the program's seeded random ones; the
fused projections' columns are in the program's order (q | k | v | z,
b | a, gate | up, per head nope | rope and k_nope | v); the rotary turns
each pair in place where the published code also moves the evens before
the odds (the same permutation of q and k: every score is the same); the
experts held elsewhere (``n_routed_experts`` of
``n_routed_experts_published`` are held here, from
``experts_held_from``) are left out of the sum and the vocabulary is the
slice held here, as in the program; the multi-token-prediction modules
are not built. The parametrisations the config does not pin are listed
under the configuration's ``assumed``. The reference follows three of
the config's switches where ``build_model`` refuses them
(``gated_attention`` false, a ``layernorm_type`` other than
``pre_post``, a ``linear_sigmoid_gate_scale`` of None: that part left
out), so that a test can show the comparison would catch a program that
left one out.
"""
from __future__ import annotations

import math

# functional_state() names of the program's decoder
# (models/gigachat3_5.py)
EMBED = "model.embed_tokens"
FINAL_NORM = "model.norm"
LM_HEAD = "lm_head"
LAYER = "model.layers.%d."
PRE_MIXER = "input_layernorm"
POST_MIXER = ("post_attention_layernorm", "post_attention_gate")
PRE_MLP = "pre_feedforward_layernorm"
POST_MLP = ("post_feedforward_layernorm", "post_feedforward_gate")
GDN_KEYS = (PRE_MIXER, "linear_attn.in_proj_qkvz", "linear_attn.in_proj_ba",
            "linear_attn.conv_weight", "linear_attn.A_log",
            "linear_attn.dt_bias", "linear_attn.norm_weight",
            "linear_attn.out_proj") + POST_MIXER
MLA_KEYS = (PRE_MIXER, "self_attn.q_a_proj", "self_attn.q_a_layernorm",
            "self_attn.q_b_proj", "self_attn.kv_a_proj_with_mqa",
            "self_attn.kv_a_layernorm", "self_attn.kv_b_proj",
            "self_attn.o_proj", "self_attn.gate_proj") + POST_MIXER
DENSE_KEYS = ("mlp.gate_up", "mlp.down")
MOE_KEYS = ("mlp.experts.gate_weight", "mlp.e_score_correction_bias",
            "mlp.shared_gate_up", "mlp.shared_down")
EXPERT_KEYS = ("mlp.experts.w1", "mlp.experts.w2")

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
LANES = 128
# experts upcast to float32 at a time in the reference: 4 experts of
# 7168 x 2048 x 3 are 0.70 GB
EXPERT_GROUP = 4
# the dense MLP upcast a quarter of its width at a time: 0.40 GB
DENSE_PARTS = 4
# seeded tokens the router biases are balanced on when a model is built
BALANCE_TOKENS = 1024
# the published switches the equations above are written for
BUILT = {"norm_type": "ZeroCenteredGatedNorm", "layernorm_type": "pre_post",
         "gated_attention": True, "rope_interleave": True,
         "use_mla_scaling_factor": True, "use_shared_expert_sigmoid": False,
         "linear_gating_type": "gated_rmsnorm_sigmoid_zero_centered",
         "n_group": 1, "num_nextn_predict_layers": 0}


def published_experts(cfg):
    return cfg.get("n_routed_experts_published", cfg["n_routed_experts"])


def held_from(cfg):
    return cfg.get("experts_held_from", 0)


def layers_held(cfg):
    return cfg.get("layers_held", list(range(cfg["num_hidden_layers"])))


def is_full_attention(cfg, j):
    return layers_held(cfg)[j] in cfg["full_attention_layers"]


def is_sparse(cfg, j):
    return layers_held(cfg)[j] >= cfg["first_k_dense_replace"]


def latent_width(cfg):
    """Values the cache keeps of a token in a latent attention layer."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


# -- the system under test ---------------------------------------------------

def model_config(cfg):
    """The program's ``GigaChat35Config`` of a configuration file."""
    from paddle_tpu.models.gigachat3_5 import GigaChat35Config

    for key, built in BUILT.items():
        if cfg.get(key, built) != built:
            raise ValueError("%s %r: the program builds %r only"
                             % (key, cfg[key], built))
    lo = held_from(cfg)
    return GigaChat35Config(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        layers_held=layers_held(cfg),
        full_attention_layers=cfg["full_attention_layers"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        num_attention_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        linear_num_key_heads=cfg["linear_num_key_heads"],
        linear_num_value_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
        linear_sigmoid_gate_scale=cfg["linear_sigmoid_gate_scale"],
        linear_attn_o_norm_eps=cfg["linear_attn_o_norm_eps"],
        layernorm_gating_weight=cfg["layernorm_gating_weight"],
        n_routed_experts=published_experts(cfg),
        n_shared_experts=cfg["n_shared_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"],
        swiglu_limit=cfg["swiglu_limit"],
        rope_theta=cfg["rope_theta"],
        rope_scaling=cfg["rope_scaling"],
        rms_norm_eps=cfg["rms_norm_eps"],
        experts_held=range(lo, lo + cfg["n_routed_experts"]),
        max_position_embeddings=cfg["max_position_embeddings"],
        dtype=cfg["torch_dtype"])


def build_model(cfg, seed, training):
    """``GigaChat35ForCausalLM`` at the configuration's sizes, as a user
    of the program builds it: every parameter is drawn on the default
    device from the seeded framework generator, in the served dtype;
    then every ``e_score_correction_bias`` gets what training gives it,
    the balancing rule on BALANCE_TOKENS seeded tokens
    (``model.balance_router_bias``): without it which few experts every
    token picks, and how many of them are held here, is a draw of the
    seed. Flags stay at the program's defaults."""
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models.gigachat3_5 import GigaChat35ForCausalLM

    if training:
        raise ValueError("the gigachat3_5 family is a serving family: "
                         "models/gigachat3_5.py is inference code")
    paddle.seed(int(seed) % (2 ** 31 - 1))
    model = GigaChat35ForCausalLM(model_config(cfg))
    model.eval()
    sparse = [layer.mlp for layer in model.model.layers if layer.sparse]
    # weights were drawn (rehearse.py's are 0)
    if sparse and bool(jnp.any(sparse[0].experts.gate_weight._value)):
        ids = np.random.default_rng(int(seed)).integers(
            0, cfg["vocab_size"],
            (1, min(BALANCE_TOKENS, cfg["max_position_embeddings"])))
        model.balance_router_bias(ids.astype(np.int32))
    return model


def weights_of(model):
    names, values = model.functional_state()
    return dict(zip(names, values))


# -- the plain reference -----------------------------------------------------

def _norm(x, weight, eps):
    """Zero-centred RMSNorm."""
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                        + eps) * (1.0 + weight)


def _plain_norm(x, weight, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                        + eps) * weight


def _post(y, weight, gamma, cfg):
    """The sandwich's second norm: zero-centred, times its per-channel
    gate (none where ``layernorm_type`` is not ``pre_post``)."""
    import jax

    if cfg.get("layernorm_type", "pre_post") != "pre_post":
        return y
    return (_norm(y, weight, cfg["rms_norm_eps"])
            * cfg["layernorm_gating_weight"] * jax.nn.sigmoid(gamma))


def _swiglu(h, gate_up, down, cfg):
    import jax
    import jax.numpy as jnp

    f = down.shape[0]
    limit = cfg["swiglu_limit"]
    gu = h @ gate_up
    return (jax.nn.silu(jnp.minimum(gu[..., :f], limit))
            * jnp.clip(gu[..., f:], -limit, limit)) @ down


def _gdn_block(x, w, cfg):
    """x + Npost(GatedDeltaNet(N(x))) on one sequence [T, hidden], one
    token at a time; ``w`` in GDN_KEYS order, any float type."""
    import jax
    import jax.numpy as jnp

    (pre_w, w_qkvz, w_ba, conv_w, a_log, dt_bias, out_norm, w_out, post_w,
     post_g) = (a.astype(jnp.float32) for a in w)
    hk, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    kernel = cfg["linear_conv_kernel_dim"]
    t = x.shape[0]
    key_dim, conv_dim = hk * dk, 2 * hk * dk + hv * dv
    h = _norm(x, pre_w, cfg["rms_norm_eps"])
    qkvz = h @ w_qkvz
    ba = h @ w_ba
    mixed, z = qkvz[:, :conv_dim], qkvz[:, conv_dim:].reshape(t, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(a_log) * jax.nn.softplus(ba[:, hv:] + dt_bias)
    # causal depthwise convolution: y[t] = sum_j w[:, j] x[t - (K-1) + j]
    padded = jnp.concatenate(
        [jnp.zeros((kernel - 1, conv_dim), jnp.float32), mixed])
    conv = jax.nn.silu(sum(padded[j:j + t] * conv_w[:, j]
                           for j in range(kernel)))
    q = conv[:, :key_dim].reshape(t, hk, dk)
    k = conv[:, key_dim:2 * key_dim].reshape(t, hk, dk)
    v = conv[:, 2 * key_dim:].reshape(t, hv, dv)

    def l2(a):
        return a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

    rep = hv // hk
    q = jnp.repeat(l2(q) / jnp.sqrt(jnp.float32(dk)), rep, axis=1)
    k = jnp.repeat(l2(k), rep, axis=1)

    def token(s, row):
        q_t, k_t, v_t, g_t, b_t = row           # [hv, d], [hv]
        s = jnp.exp(g_t)[:, None, None] * s
        u = (v_t - jnp.einsum("hkv,hk->hv", s, k_t)) * b_t[:, None]
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((hv, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    o = _norm(o, out_norm, cfg["linear_attn_o_norm_eps"])
    if cfg["linear_sigmoid_gate_scale"] is not None:
        o = o * cfg["linear_sigmoid_gate_scale"] * jax.nn.sigmoid(z)
    return x + _post(o.reshape(t, hv * dv) @ w_out, post_w, post_g, cfg)


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_angles(cfg, t):
    """(cos, sin) [T, rope / 2] at positions 0..T-1, cos and sin already
    times the YaRN magnitude: pair i's frequency ``theta^(-2i/d)``
    blended with its ``factor``-th by the linear ramp between the pairs
    that turn ``beta_fast`` and ``beta_slow`` times over
    ``original_max_position_embeddings``."""
    import jax.numpy as jnp

    d, theta, rs = cfg["qk_rope_head_dim"], cfg["rope_theta"], \
        cfg["rope_scaling"]
    factor, original = rs["factor"], rs["original_max_position_embeddings"]
    i = jnp.arange(d // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * i / d)

    def pair_turning(turns):
        return (d * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_turning(rs["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    freq = plain / factor * ramp + plain * (1.0 - ramp)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    m = _mscale(factor, rs["mscale"]) / _mscale(factor, rs["mscale_all_dim"])
    return jnp.cos(angle) * m, jnp.sin(angle) * m


def _rotate_pairs(x, cos, sin):
    """Turn the pairs (2i, 2i+1) of x [T, heads, D] in place."""
    import jax.numpy as jnp

    even, odd = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([even * c - odd * s, odd * c + even * s],
                     axis=-1).reshape(x.shape)


def _mla_block(x, w, cfg):
    """x + Npost(GatedMLA(N(x))) on one sequence [T, hidden], heads
    expanded; ``w`` in MLA_KEYS order."""
    import jax
    import jax.numpy as jnp

    (pre_w, w_dq, q_norm, w_uq, w_dkv, kv_norm, w_ukv, w_o, w_g, post_w,
     post_g) = (a.astype(jnp.float32) for a in w)
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    eps, rs = cfg["rms_norm_eps"], cfg["rope_scaling"]
    t = x.shape[0]
    h = _norm(x, pre_w, eps)
    q = (_plain_norm(h @ w_dq, q_norm, eps) @ w_uq).reshape(t, heads,
                                                            nope + rope)
    dkv = h @ w_dkv
    c_kv = _plain_norm(dkv[:, :rank], kv_norm, eps)
    cos, sin = _yarn_angles(cfg, t)
    q_pe = _rotate_pairs(q[..., nope:], cos, sin)
    k_pe = _rotate_pairs(dkv[:, None, rank:], cos, sin)         # [T, 1, rope]
    kv = (c_kv @ w_ukv).reshape(t, heads, nope + dv)
    scale = ((nope + rope) ** -0.5
             * _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2)
    scores = (jnp.einsum("thd,shd->hts", q[..., :nope], kv[..., :nope])
              + jnp.einsum("thd,sd->hts", q_pe, k_pe[:, 0])) * scale
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf),
                           axis=-1)
    ctx = jnp.einsum("hts,shd->thd", probs, kv[..., nope:])
    ctx = ctx.reshape(t, heads * dv)
    if cfg.get("gated_attention", True):
        ctx = ctx * jax.nn.sigmoid(h @ w_g)
    return x + _post(ctx @ w_o, post_w, post_g, cfg)


def _ffn_open(x, pre_w, cfg):
    """The normed input of a layer's MLP half, and a zero sum."""
    import jax.numpy as jnp

    h = _norm(x, pre_w.astype(jnp.float32), cfg["rms_norm_eps"])
    return h, jnp.zeros_like(h)


def _dense_part(acc, h, gate_up, down, part, cfg):
    """acc + what columns ``part`` of DENSE_PARTS of the dense SwiGLU
    give (a SwiGLU splits by its width)."""
    import jax
    import jax.numpy as jnp

    f = down.shape[0]
    n = f // DENSE_PARTS
    g = jax.lax.dynamic_slice_in_dim(gate_up, part * n, n, axis=1)
    u = jax.lax.dynamic_slice_in_dim(gate_up, f + part * n, n, axis=1)
    d = jax.lax.dynamic_slice_in_dim(down, part * n, n)
    gu = jnp.concatenate([g, u], axis=1).astype(jnp.float32)
    return acc + _swiglu(h, gu, d.astype(jnp.float32), cfg)


def _route(h, gate_w, bias, cfg):
    """(weights [T, E], chosen [T, k]): sigmoid scores; the top-k of
    score + bias; the scores of the chosen (no bias) over their sum,
    times the scaling factor, scattered over all published experts."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(h @ gate_w)
    _, chosen = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    rows = jnp.arange(h.shape[0])[:, None]
    top = scores[rows, chosen]
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * cfg["routed_scaling_factor"]
    return jnp.zeros_like(scores).at[rows, chosen].set(top), chosen


def _moe_open(h, w, cfg):
    """The expert layer's part outside the routed experts: router and
    shared expert. -> (router weights over all published experts,
    chosen experts, shared(h)); ``w`` in MOE_KEYS order."""
    import jax.numpy as jnp

    gate_w, bias, shared_gu, shared_down = (a.astype(jnp.float32) for a in w)
    weights, chosen = _route(h, gate_w, bias, cfg)
    return weights, chosen, _swiglu(h, shared_gu, shared_down, cfg)


def _expert_group(acc, h, weights, w1, w2, start, cfg):
    """acc + sum over the experts ``start .. start + EXPERT_GROUP - 1``
    of the held ones of weight * E_e(h): every expert of the group on
    every token, weighted by the router (0 where not chosen)."""
    import jax
    import jax.numpy as jnp

    size = min(EXPERT_GROUP, w1.shape[0])
    assert w1.shape[0] % size == 0, "whole groups of experts only"
    g1 = jax.lax.dynamic_slice_in_dim(w1, start, size).astype(jnp.float32)
    g2 = jax.lax.dynamic_slice_in_dim(w2, start, size).astype(jnp.float32)
    wt = jax.lax.dynamic_slice_in_dim(weights, held_from(cfg) + start,
                                      size, axis=1)
    f, limit = cfg["moe_intermediate_size"], cfg["swiglu_limit"]
    hid = jnp.einsum("td,edf->etf", h, g1)
    hid = (jax.nn.silu(jnp.minimum(hid[..., :f], limit))
           * jnp.clip(hid[..., f:], -limit, limit))
    return acc + jnp.einsum("te,etd->td", wt,
                            jnp.einsum("etf,efd->etd", hid, g2))


def _ffn_close(x, acc, post_w, post_g, cfg):
    import jax.numpy as jnp

    return x + _post(acc, post_w.astype(jnp.float32),
                     post_g.astype(jnp.float32), cfg)


def _head(x, w, cfg):
    import jax.numpy as jnp

    norm_w, lm_head = (a.astype(jnp.float32) for a in w)
    return _norm(x, norm_w, cfg["rms_norm_eps"]) @ lm_head


def reference_forward(weights, cfg, ids):
    """(logits [T, vocab] float32, [chosen experts [T, k] a sparse
    layer]) for ONE sequence of token ids. Each block is its own jitted
    program that upcasts its own weights (the dense MLP a part and the
    experts a group at a time), so the whole fits beside a loaded
    engine."""
    import jax
    import jax.numpy as jnp

    def block(fn):
        @jax.jit
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args, cfg)
        return run

    gdn, mla, head = block(_gdn_block), block(_mla_block), block(_head)
    ffn_open, dense_part, ffn_close = (block(_ffn_open), block(_dense_part),
                                       block(_ffn_close))
    moe_open, group = block(_moe_open), block(_expert_group)
    x = weights[EMBED][jnp.asarray(ids)].astype(jnp.float32)
    routing = []
    for j in range(cfg["num_hidden_layers"]):
        p = LAYER % j
        if is_full_attention(cfg, j):
            x = mla(x, [weights[p + k] for k in MLA_KEYS])
        else:
            x = gdn(x, [weights[p + k] for k in GDN_KEYS])
        h, acc = ffn_open(x, weights[p + PRE_MLP])
        if is_sparse(cfg, j):
            router, chosen, acc = moe_open(h, [weights[p + k]
                                               for k in MOE_KEYS])
            routing.append(chosen)
            w1, w2 = (weights[p + k] for k in EXPERT_KEYS)
            for start in range(0, cfg["n_routed_experts"], EXPERT_GROUP):
                acc = group(acc, h, router, w1, w2, start)
        else:
            gate_up, down = (weights[p + k] for k in DENSE_KEYS)
            for part in range(DENSE_PARTS):
                acc = dense_part(acc, h, gate_up, down, part)
        x = ffn_close(x, acc, *(weights[p + k] for k in POST_MLP))
    return head(x, [weights[FINAL_NORM], weights[LM_HEAD]]), routing


def reference_logits(weights, cfg, ids):
    """Logits [T, vocab] in float32 for ONE sequence of token ids."""
    return reference_forward(weights, cfg, ids)[0]


def cross_entropy(logits, labels):
    """Mean over tokens of -log softmax(logits)[label], float32."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, jnp.asarray(labels)[:, None], -1)
    return -jnp.mean(picked)


def reference_loss(weights, cfg, ids, labels):
    """Mean cross-entropy over a batch [B, T] of ids and labels, one
    sequence at a time. -> float."""
    import numpy as np

    per_seq = [float(cross_entropy(reference_logits(weights, cfg, row), lab))
               for row, lab in zip(np.asarray(ids), np.asarray(labels))]
    return float(np.mean(per_seq))


# -- arithmetic --------------------------------------------------------------

def layer_counts(cfg):
    """{"mla": latent attention layers, "gdn": Gated DeltaNet layers,
    "dense": dense MLPs, "moe": expert layers} of the layers held."""
    n = cfg["num_hidden_layers"]
    mla = sum(is_full_attention(cfg, j) for j in range(n))
    moe = sum(is_sparse(cfg, j) for j in range(n))
    return {"mla": mla, "gdn": n - mla, "dense": n - moe, "moe": moe}


def layer_params(cfg):
    """Parameters of each part of a layer, as held here ("norms": the
    four norm and two gate vectors of the sandwich)."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    q_rank, rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    hk, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    hv, lv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    conv_dim = 2 * hk * dk + hv * lv
    width = cfg["moe_intermediate_size"]
    experts = published_experts(cfg)
    return {
        "mla": (h * q_rank + q_rank * heads * (nope + rope)
                + h * (rank + rope) + rank * heads * (nope + dv)
                + 2 * heads * dv * h + q_rank + rank),
        "gdn": (h * (conv_dim + hv * lv) + h * 2 * hv
                + conv_dim * cfg["linear_conv_kernel_dim"] + 2 * hv + lv
                + hv * lv * h),
        "dense_mlp": 3 * h * cfg["intermediate_size"],
        "experts": cfg["n_routed_experts"] * 3 * h * width,
        "moe_other": (h * experts + experts
                      + 3 * h * cfg["n_shared_experts"] * width),
        "norms": 6 * h,
    }


def param_count(cfg):
    lp, n = layer_params(cfg), layer_counts(cfg)
    embeds = cfg["vocab_size"] * cfg["hidden_size"] * (
        1 if cfg["tie_word_embeddings"] else 2)
    return (n["mla"] * lp["mla"] + n["gdn"] * lp["gdn"]
            + n["dense"] * lp["dense_mlp"]
            + n["moe"] * (lp["experts"] + lp["moe_other"])
            + cfg["num_hidden_layers"] * lp["norms"] + embeds
            + cfg["hidden_size"])


def kv_page_bytes(cfg, block_size):
    """Bytes of one page over the latent attention layers' planes as
    the pool holds them: a token's row is ``latent_width`` rounded up to
    whole 128-lane tiles (576 -> 640)."""
    lanes = -(-latent_width(cfg) // LANES) * LANES
    return (layer_counts(cfg)["mla"] * block_size * lanes
            * DTYPE_BYTES[cfg["torch_dtype"]])


def state_slot_bytes(cfg):
    """Bytes one slot's recurrent state (float32) and convolution tail
    (the served dtype) take over the Gated DeltaNet layers."""
    hk, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    tail = ((cfg["linear_conv_kernel_dim"] - 1) * (2 * hk * dk + hv * dv)
            * DTYPE_BYTES[cfg["torch_dtype"]])
    return layer_counts(cfg)["gdn"] * (hv * dk * dv * 4 + tail)


def mla_decode_cost(cfg, context_tokens, rows):
    """(FLOPs, bytes) the algorithm needs for ONE call of the absorbed
    latent-attention decode kernel (one layer, one step): ``rows``
    queries of one token, every head against ``context_tokens`` cached
    rows in all. A cached row (``latent_width`` values, not the pool's
    lane padding) is read once for all heads and both dots; every head's
    absorbed query is read and its latent output written once."""
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    width = latent_width(cfg)
    size = DTYPE_BYTES[cfg["torch_dtype"]]
    flops = 2 * context_tokens * heads * (width + rank)
    moved = (context_tokens * width + rows * heads * (width + rank)) * size
    return flops, moved


def moe_gmm_cost(cfg, rows, pairs, experts_touched):
    """(FLOPs, bytes) any implementation must spend on the routed
    experts of ONE expert layer in one program (its two ``moe_gmm``
    calls together): ``pairs`` (token, expert) pairs landed on
    ``experts_touched`` of the experts held here, out of ``rows`` token
    rows. The weights of an expert that received a row are read once;
    the ``rows`` token rows are read once and the layer's output rows
    written once. 6 x hidden x width FLOPs a pair (gate, up, down)."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    size = DTYPE_BYTES[cfg["torch_dtype"]]
    flops = 6 * h * f * pairs
    moved = (experts_touched * 3 * h * f + 2 * rows * h) * size
    return flops, moved
