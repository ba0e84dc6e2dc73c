"""The Qwen3-Next family: a hybrid decoder in periods of
``full_attention_interval`` layers — Gated DeltaNet (linear attention)
layers, then one gated softmax-attention layer — every layer followed by
a sparse mixture of experts with one shared expert
(Qwen/Qwen3-Next-80B-A3B-Instruct ``config.json``; Yang et al., "Gated
Delta Networks", arXiv:2412.06464).

Three things live here, as in ``families/mistral.py``:

- ``build_model``: the system under test through the program's normal
  classes (``paddle_tpu.models.qwen3_next``), nothing patched;
- ``reference_*``: the architecture in plain ``jax.numpy`` float32 under
  ``jax.default_matmul_precision("highest")``, from the published
  equations, with no kernel, cache, chunking or batching and no import
  from ``paddle_tpu.models``: the Gated DeltaNet is the token-by-token
  recurrence (a ``lax.scan``), the experts a loop over groups of
  experts, every expert of a group run on every token and weighted by
  the router (0 where not chosen). It reads the program's own weight
  arrays, upcasts one block at a time and the experts a group at a time;
- the arithmetic the layer metrics divide by.

The equations (``cfg`` keys in brackets):

- block: ``x += mixer(norm(x)); x += moe(norm(x))`` with
  ``norm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)``; layer ``i`` is full
  attention when ``(i + 1) % full_attention_interval == 0``;
- gated attention: ``q_proj`` gives per head [query | gate]; q and k are
  RMS-normed over the head (zero-centred weights); rotate-half rotary on
  the first ``partial_rotary_factor * head_dim`` dims; causal softmax,
  scale ``head_dim^-1/2``; ``o_proj(attn * sigmoid(gate))``;
- Gated DeltaNet: ``in_proj_qkvz`` gives q, k (``linear_num_key_heads``
  x ``linear_key_head_dim``) and v, z (``linear_num_value_heads`` x
  ``linear_value_head_dim``), ``in_proj_ba`` gives b, a; causal
  depthwise convolution (``linear_conv_kernel_dim``) then SiLU over
  [q, k, v]; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
  dt_bias)``; q, k L2-normalised per head, q scaled by ``Dk^-1/2``, key
  head j serving value heads 2j, 2j + 1; per value head
  ``S <- exp(g_t) S; u = (v_t - S^T k_t) beta_t; S <- S + k_t u^T;
  o_t = S^T q_t``; ``out_proj(rmsnorm_w(o) * silu(z))`` per head;
- experts: ``p = softmax(x W_g)`` over ``num_experts_published``, top
  ``num_experts_per_tok``, renormalised; expert e is
  ``W_down(silu(W_gate x) * W_up x)``; the shared expert the same shape
  times ``sigmoid(x w_s)``; ``y = sum_{e in top, e held here} p_e
  E_e(x) + shared(x)``.

Departures of the reference from the published model, all forced by what
it is compared with: weights are the program's seeded random ones; the
fused projections' columns are in the program's order (q | k | v | z,
b | a, per head query | gate, gate | up: the published code interleaves
``in_proj_qkvz`` per key head, a permutation of columns); the L2
normalisation is ``x / sqrt(sum x^2 + 1e-6)`` as in the published
kernel; the experts held elsewhere (``num_experts`` of
``num_experts_published`` are held here, from ``experts_held_from``) are
left out of the sum and the vocabulary is the slice held here, as in the
program; the multi-token-prediction module the model card mentions is in
no key of the config and is not built.
"""
from __future__ import annotations

# functional_state() names of the program's decoder
# (models/qwen3_next.py)
EMBED = "model.embed_tokens"
FINAL_NORM = "model.norm"
LM_HEAD = "lm_head"
LAYER = "model.layers.%d."
GDN_KEYS = ("input_layernorm", "linear_attn.in_proj_qkvz",
            "linear_attn.in_proj_ba", "linear_attn.conv_weight",
            "linear_attn.A_log", "linear_attn.dt_bias",
            "linear_attn.norm_weight", "linear_attn.out_proj")
ATTN_KEYS = ("input_layernorm", "self_attn.q_proj", "self_attn.k_proj",
             "self_attn.v_proj", "self_attn.o_proj", "self_attn.q_norm",
             "self_attn.k_norm")
MOE_KEYS = ("post_attention_layernorm", "mlp.experts.gate_weight",
            "mlp.shared_gate_up", "mlp.shared_down",
            "mlp.shared_expert_gate")
EXPERT_KEYS = ("mlp.experts.w1", "mlp.experts.w2")

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
# experts upcast to float32 at a time in the reference: 32 experts of
# 2048 x 1536 are 0.4 GB
EXPERT_GROUP = 32


def published_experts(cfg):
    return cfg.get("num_experts_published", cfg["num_experts"])


def held_from(cfg):
    return cfg.get("experts_held_from", 0)


def is_full_attention(cfg, i):
    return (i + 1) % cfg["full_attention_interval"] == 0


# -- the system under test ---------------------------------------------------

def build_model(cfg, seed, training):
    """``Qwen3NextForCausalLM`` at the configuration's sizes, as a user
    of the program builds it: every parameter is drawn on the default
    device from the seeded framework generator, in the served dtype
    (3.68 B float32 leaves beside their casts would not load). Flags
    stay at the program's defaults."""
    import paddle_tpu as paddle
    from paddle_tpu.models.qwen3_next import (Qwen3NextConfig,
                                              Qwen3NextForCausalLM)

    if training:
        raise ValueError("the qwen3_next family is a serving family: "
                         "models/qwen3_next.py is inference code")
    paddle.seed(int(seed) % (2 ** 31 - 1))
    lo = held_from(cfg)
    model = Qwen3NextForCausalLM(Qwen3NextConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        rope_theta=cfg["rope_theta"],
        rms_norm_eps=cfg["rms_norm_eps"],
        full_attention_interval=cfg["full_attention_interval"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_num_key_heads=cfg["linear_num_key_heads"],
        linear_num_value_heads=cfg["linear_num_value_heads"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg[
            "shared_expert_intermediate_size"],
        num_experts=published_experts(cfg),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        experts_held=range(lo, lo + cfg["num_experts"]),
        max_position_embeddings=cfg["max_position_embeddings"],
        dtype=cfg["torch_dtype"]))
    model.eval()
    return model


def weights_of(model):
    names, values = model.functional_state()
    return dict(zip(names, values))


# -- the plain reference -----------------------------------------------------

def _norm(x, weight, eps):
    """Zero-centred RMSNorm."""
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + weight)


def _rotate_first(x, theta, rotary):
    """Rotate-half rotary on dims [0, rotary) of [T, heads, D],
    positions 0..T-1; the other dims pass."""
    import jax.numpy as jnp

    t = x.shape[0]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32)
                                / rotary))
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[:, None, :]
    r = x[..., :rotary]
    r1, r2 = r[..., :rotary // 2], r[..., rotary // 2:]
    r = r * cos + jnp.concatenate([-r2, r1], -1) * sin
    return jnp.concatenate([r, x[..., rotary:]], -1)


def _attention_block(x, w, cfg):
    """x + GatedAttention(norm(x)) on one sequence [T, hidden]; ``w`` in
    ATTN_KEYS order, any float type."""
    import jax
    import jax.numpy as jnp

    norm_w, wq, wk, wv, wo, q_norm, k_norm = (
        a.astype(jnp.float32) for a in w)
    heads, kv_heads, d = (cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    rotary = int(d * cfg["partial_rotary_factor"])
    t = x.shape[0]
    h = _norm(x, norm_w, eps)
    qg = (h @ wq).reshape(t, heads, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = (h @ wk).reshape(t, kv_heads, d)
    v = (h @ wv).reshape(t, kv_heads, d)
    q = _rotate_first(_norm(q, q_norm, eps), cfg["rope_theta"], rotary)
    k = _rotate_first(_norm(k, k_norm, eps), cfg["rope_theta"], rotary)
    group = heads // kv_heads
    q = q.reshape(t, kv_heads, group, d)
    scores = jnp.einsum("tkgd,skd->kgts", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("kgts,skd->tkgd", probs, v).reshape(t, heads, d)
    ctx = ctx * jax.nn.sigmoid(gate)
    return x + ctx.reshape(t, heads * d) @ wo


def _gdn_block(x, w, cfg):
    """x + GatedDeltaNet(norm(x)) on one sequence [T, hidden], one token
    at a time; ``w`` in GDN_KEYS order."""
    import jax
    import jax.numpy as jnp

    (norm_w, w_qkvz, w_ba, conv_w, a_log, dt_bias, out_norm,
     w_out) = (a.astype(jnp.float32) for a in w)
    hk, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    kernel = cfg["linear_conv_kernel_dim"]
    eps = cfg["rms_norm_eps"]
    t = x.shape[0]
    key_dim, conv_dim = hk * dk, 2 * hk * dk + hv * dv
    h = _norm(x, norm_w, eps)
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
    var = jnp.mean(o * o, axis=-1, keepdims=True)
    o = o / jnp.sqrt(var + eps) * out_norm * jax.nn.silu(z)
    return x + o.reshape(t, hv * dv) @ w_out


def _route(h, gate_w, cfg):
    """(weights [T, E], chosen [T, k]): the renormalised top-k
    probabilities scattered over all published experts, 0 elsewhere."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(h @ gate_w, axis=-1)
    top, chosen = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, chosen].set(top), chosen


def _moe_open(x, w, cfg):
    """The expert layer's part outside the routed experts: norm, router,
    shared expert. -> (normed input, router weights over all published
    experts, chosen experts, x + shared); ``w`` in MOE_KEYS order."""
    import jax
    import jax.numpy as jnp

    norm_w, gate_w, shared_gu, shared_down, shared_gate = (
        a.astype(jnp.float32) for a in w)
    h = _norm(x, norm_w, cfg["rms_norm_eps"])
    weights, chosen = _route(h, gate_w, cfg)
    f = cfg["shared_expert_intermediate_size"]
    gu = h @ shared_gu
    shared = (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ shared_down
    return h, weights, chosen, x + shared * jax.nn.sigmoid(h @ shared_gate)


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
    f = cfg["moe_intermediate_size"]
    hid = jnp.einsum("td,edf->etf", h, g1)
    hid = jax.nn.silu(hid[..., :f]) * hid[..., f:]
    return acc + jnp.einsum("te,etd->td", wt,
                            jnp.einsum("etf,efd->etd", hid, g2))


def _head(x, w, cfg):
    import jax.numpy as jnp

    norm_w, lm_head = (a.astype(jnp.float32) for a in w)
    return _norm(x, norm_w, cfg["rms_norm_eps"]) @ lm_head


def reference_forward(weights, cfg, ids):
    """(logits [T, vocab] float32, [chosen experts [T, k] a layer]) for
    ONE sequence of token ids. Each block is its own jitted program that
    upcasts its own weights, the experts a group at a time, so the whole
    fits beside a loaded engine."""
    import jax
    import jax.numpy as jnp

    def block(fn):
        @jax.jit
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args, cfg)
        return run

    attn, gdn, head = block(_attention_block), block(_gdn_block), \
        block(_head)
    moe_open, group = block(_moe_open), block(_expert_group)
    x = weights[EMBED][jnp.asarray(ids)].astype(jnp.float32)
    routing = []
    for i in range(cfg["num_hidden_layers"]):
        p = LAYER % i
        if is_full_attention(cfg, i):
            x = attn(x, [weights[p + k] for k in ATTN_KEYS])
        else:
            x = gdn(x, [weights[p + k] for k in GDN_KEYS])
        h, router, chosen, x = moe_open(x, [weights[p + k]
                                            for k in MOE_KEYS])
        routing.append(chosen)
        w1, w2 = (weights[p + k] for k in EXPERT_KEYS)
        for start in range(0, cfg["num_experts"], EXPERT_GROUP):
            x = group(x, h, router, w1, w2, start)
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
    """(full-attention layers, Gated DeltaNet layers)."""
    full = sum(is_full_attention(cfg, i)
               for i in range(cfg["num_hidden_layers"]))
    return full, cfg["num_hidden_layers"] - full


def layer_params(cfg):
    """Parameters of one layer by part, as held here."""
    h = cfg["hidden_size"]
    d = cfg["head_dim"]
    hk, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    conv_dim = 2 * hk * dk + hv * dv
    shared = cfg["shared_expert_intermediate_size"]
    return {
        "attention": (h * d * (3 * cfg["num_attention_heads"]
                               + 2 * cfg["num_key_value_heads"]) + 2 * d),
        "gdn": (h * (conv_dim + hv * dv) + h * 2 * hv
                + conv_dim * cfg["linear_conv_kernel_dim"] + 2 * hv + dv
                + hv * dv * h),
        "experts": cfg["num_experts"] * 3 * h * cfg["moe_intermediate_size"],
        "moe_other": h * published_experts(cfg) + 3 * h * shared + h,
        "norms": 2 * h,
    }


def param_count(cfg):
    lp = layer_params(cfg)
    full, linear = layer_counts(cfg)
    embeds = cfg["vocab_size"] * cfg["hidden_size"] * (
        1 if cfg["tie_word_embeddings"] else 2)
    return (full * lp["attention"] + linear * lp["gdn"]
            + cfg["num_hidden_layers"] * (lp["experts"] + lp["moe_other"]
                                          + lp["norms"])
            + embeds + cfg["hidden_size"])


def active_matmul_params(cfg):
    """Parameters a token is multiplied by here: the mixers, the router,
    the shared expert, the share of its ``num_experts_per_tok`` experts
    that is held here on average, and the output head."""
    lp = layer_params(cfg)
    full, linear = layer_counts(cfg)
    h = cfg["hidden_size"]
    routed = (cfg["num_experts_per_tok"] * cfg["num_experts"]
              / published_experts(cfg)) * 3 * h * cfg[
                  "moe_intermediate_size"]
    return (full * lp["attention"] + linear * lp["gdn"]
            + cfg["num_hidden_layers"] * (lp["moe_other"] + routed)
            + cfg["vocab_size"] * h)


def train_flops_per_token(cfg, seq_len):
    """Model FLOPs of forward + backward for one token of a sequence of
    ``seq_len`` on this chip's share: 6 per active matmul parameter,
    causal attention in the full layers, and the Gated DeltaNet state
    (read for S^T k and S^T q, written by the rank-one update: 3
    products of Dk x Dv a value head a token, 2 FLOPs each, three times
    for forward and backward)."""
    full, linear = layer_counts(cfg)
    attn_fwd = (2 * 2 * seq_len * cfg["num_attention_heads"]
                * cfg["head_dim"]) / 2
    state_fwd = (3 * 2 * cfg["linear_num_value_heads"]
                 * cfg["linear_key_head_dim"]
                 * cfg["linear_value_head_dim"])
    return (6 * active_matmul_params(cfg) + 3 * attn_fwd * full
            + 3 * state_fwd * linear)


def kv_page_bytes(cfg, block_size):
    """Bytes of one page across the K and V planes of the layers that
    keep pages: the full-attention layers only."""
    full, _ = layer_counts(cfg)
    return (2 * full * block_size * cfg["num_key_value_heads"]
            * cfg["head_dim"] * DTYPE_BYTES[cfg["torch_dtype"]])


def state_slot_bytes(cfg):
    """Bytes one slot's recurrent state and convolution tail take over
    the Gated DeltaNet layers (state in float32)."""
    _, linear = layer_counts(cfg)
    hk, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    tail = ((cfg["linear_conv_kernel_dim"] - 1) * (2 * hk * dk + hv * dv)
            * DTYPE_BYTES[cfg["torch_dtype"]])
    return linear * (hv * dk * dv * 4 + tail)


def paged_decode_cost(cfg, context_tokens, rows):
    """(FLOPs, bytes) the algorithm needs for ONE call of the paged
    decode kernel (one full-attention layer, one step): ``rows`` queries
    of one token attending to ``context_tokens`` cached tokens in all.
    Each cached token's K and V rows are read once per KV head; q is
    read and the output written once."""
    heads, kv_heads, d = (cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], cfg["head_dim"])
    size = DTYPE_BYTES[cfg["torch_dtype"]]
    flops = 2 * 2 * context_tokens * heads * d
    moved = (2 * context_tokens * kv_heads * d + 2 * rows * heads * d) * size
    return flops, moved


def moe_gmm_cost(cfg, rows, pairs, experts_touched):
    """(FLOPs, bytes) any implementation must spend on the routed
    experts of ONE expert layer in one program (its two ``moe_gmm``
    calls together): ``pairs`` (token, expert) pairs landed on
    ``experts_touched`` of the experts held here, out of ``rows`` token
    rows. The weights of an expert that received a row are read once;
    the ``rows`` token rows are read once and the layer's output rows
    written once (a pair's hidden activations need never leave the
    chip's fast memory). 6 x hidden x width FLOPs a pair."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    size = DTYPE_BYTES[cfg["torch_dtype"]]
    flops = 6 * h * f * pairs
    moved = (experts_touched * 3 * h * f + 2 * rows * h) * size
    return flops, moved
