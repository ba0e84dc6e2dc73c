"""The DeepSeek-V2 family: multi-head latent attention (MLA) in every
layer, a leading dense SwiGLU layer, then sparse layers that route
top-6 of 160 experts inside the 3 best of 8 expert groups beside two
ungated shared experts (deepseek-ai/DeepSeek-V2 ``config.json``;
"DeepSeek-V2: A Strong, Economical, and Efficient Mixture-of-Experts
Language Model", arXiv:2405.04434).

Three things live here, as in ``families/qwen3_next.py``:

- ``build_model``: the system under test through the program's normal
  classes (``paddle_tpu.models.deepseek_v2``), nothing patched;
- ``reference_*``: the architecture in plain ``jax.numpy`` float32 under
  ``jax.default_matmul_precision("highest")``, from the published
  equations, with no kernel, cache, absorption, chunking or batching and
  no import from ``paddle_tpu.models``: attention over expanded heads
  over the whole sequence, every held expert of a group on every token
  and weighted by the router (0 where not chosen). It reads the
  program's own weight arrays and upcasts one block at a time, the
  experts a group at a time;
- the arithmetic the layer metrics divide by.

The equations (``cfg`` keys in brackets), per layer
``h = x + MLA(norm(x)); y = h + FFN(norm(h))``,
``norm(x) = x / sqrt(mean(x^2) + rms_norm_eps) * w``:

- MLA: ``c_q = norm(x W_dq)`` [q_lora_rank]; per head
  ``[q_nope | q_pe] = c_q W_uq`` ([qk_nope_head_dim | qk_rope_head_dim]);
  ``[c_kv | k_pe] = x W_dkv`` ([kv_lora_rank | qk_rope_head_dim]);
  ``c_kv = norm(c_kv)``; rotary on ``q_pe`` and on the one ``k_pe`` all
  heads share, pairs (2i, 2i+1); per head ``[k_nope | v] = c_kv W_ukv``
  ([qk_nope_head_dim | v_head_dim]);
  ``s = scale (q_nope . k_nope + q_pe . k_pe)``, causal softmax,
  ``o = sum p v``, ``concat_h(o) W_o``;
- YaRN [rope_scaling]: pair i's frequency is ``theta^(-2i/d)`` blended
  with its ``factor``-th by the linear ramp between the pairs that turn
  ``beta_fast`` and ``beta_slow`` times over
  ``original_max_position_embeddings``; cos and sin times
  ``m(mscale) / m(mscale_all_dim)``, ``scale = (nope + rope)^-1/2
  m(mscale_all_dim)^2`` with ``m(a) = 0.1 a ln(factor) + 1``;
- FFN of layer ``i < first_k_dense_replace``: SwiGLU of
  ``intermediate_size``; of the others: ``p = softmax(x W_g)`` in
  float32 over ``n_routed_experts_published``; a group's score is the
  largest p of its experts (``n_group`` equal contiguous groups); the
  ``topk_group`` best groups are kept, the top ``num_experts_per_tok``
  of p taken inside them; weights are those p (``norm_topk_prob``
  false: not renormalised) times ``routed_scaling_factor``;
  ``y = sum_{e chosen, e held here} w_e E_e(x) + S(x)``, each ``E_e`` a
  SwiGLU of ``moe_intermediate_size``, ``S`` one SwiGLU of
  ``n_shared_experts`` times that width, added ungated.

Departures of the reference from the published model, all forced by what
it is compared with: weights are the program's seeded random ones; the
fused projections' columns are in the program's order (gate | up; per
head nope | rope and k_nope | v); the rotary turns each pair in place
where the published code also moves the evens before the odds (the same
permutation of q and k: every score is the same); the experts held
elsewhere (``n_routed_experts`` of ``n_routed_experts_published`` are
held here, from ``experts_held_from``) are left out of the sum and the
vocabulary is the slice held here, as in the program; the auxiliary
losses are training's and are not computed.
"""
from __future__ import annotations

import math

# functional_state() names of the program's decoder
# (models/deepseek_v2.py)
EMBED = "model.embed_tokens"
FINAL_NORM = "model.norm"
LM_HEAD = "lm_head"
LAYER = "model.layers.%d."
ATTN_KEYS = ("input_layernorm", "self_attn.q_a_proj",
             "self_attn.q_a_layernorm", "self_attn.q_b_proj",
             "self_attn.kv_a_proj_with_mqa", "self_attn.kv_a_layernorm",
             "self_attn.kv_b_proj", "self_attn.o_proj")
DENSE_KEYS = ("post_attention_layernorm", "mlp.gate_up", "mlp.down")
MOE_KEYS = ("post_attention_layernorm", "mlp.experts.gate_weight",
            "mlp.shared_gate_up", "mlp.shared_down")
EXPERT_KEYS = ("mlp.experts.w1", "mlp.experts.w2")

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
# experts upcast to float32 at a time in the reference: 5 experts of
# 5120 x 1536 x 3 are 0.47 GB
EXPERT_GROUP = 5
LANES = 128


def published_experts(cfg):
    return cfg.get("n_routed_experts_published", cfg["n_routed_experts"])


def held_from(cfg):
    return cfg.get("experts_held_from", 0)


def is_sparse(cfg, i):
    return i >= cfg["first_k_dense_replace"]


def latent_width(cfg):
    """Values the cache keeps of a token in a layer."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


# -- the system under test ---------------------------------------------------

def build_model(cfg, seed, training):
    """``DeepseekV2ForCausalLM`` at the configuration's sizes, as a user
    of the program builds it: every parameter is drawn on the default
    device from the seeded framework generator, in the served dtype.
    Flags stay at the program's defaults."""
    import paddle_tpu as paddle
    from paddle_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                               DeepseekV2ForCausalLM)

    if training:
        raise ValueError("the deepseek_v2 family is a serving family: "
                         "models/deepseek_v2.py is inference code")
    paddle.seed(int(seed) % (2 ** 31 - 1))
    lo = held_from(cfg)
    model = DeepseekV2ForCausalLM(DeepseekV2Config(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        n_routed_experts=published_experts(cfg),
        n_shared_experts=cfg["n_shared_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        rope_theta=cfg["rope_theta"],
        rope_scaling=cfg["rope_scaling"],
        rms_norm_eps=cfg["rms_norm_eps"],
        experts_held=range(lo, lo + cfg["n_routed_experts"]),
        max_position_embeddings=cfg["max_position_embeddings"],
        dtype=cfg["torch_dtype"]))
    model.eval()
    return model


def weights_of(model):
    names, values = model.functional_state()
    return dict(zip(names, values))


# -- the plain reference -----------------------------------------------------

def _norm(x, weight, eps):
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * weight


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_angles(cfg, t):
    """(cos, sin) [T, rope / 2] at positions 0..T-1, cos and sin already
    times the YaRN magnitude."""
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


def _attention_block(x, w, cfg):
    """x + MLA(norm(x)) on one sequence [T, hidden], heads expanded;
    ``w`` in ATTN_KEYS order, any float type."""
    import jax
    import jax.numpy as jnp

    norm_w, w_dq, q_norm, w_uq, w_dkv, kv_norm, w_ukv, w_o = (
        a.astype(jnp.float32) for a in w)
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    eps, rs = cfg["rms_norm_eps"], cfg["rope_scaling"]
    t = x.shape[0]
    h = _norm(x, norm_w, eps)
    q = (_norm(h @ w_dq, q_norm, eps) @ w_uq).reshape(t, heads,
                                                      nope + rope)
    dkv = h @ w_dkv
    c_kv = _norm(dkv[:, :rank], kv_norm, eps)
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
    return x + ctx.reshape(t, heads * dv) @ w_o


def _swiglu(h, gate_up, down):
    import jax

    f = down.shape[0]
    gu = h @ gate_up
    return (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ down


def _dense_block(x, w, cfg):
    """x + SwiGLU(norm(x)); ``w`` in DENSE_KEYS order."""
    import jax.numpy as jnp

    norm_w, gate_up, down = (a.astype(jnp.float32) for a in w)
    return x + _swiglu(_norm(x, norm_w, cfg["rms_norm_eps"]), gate_up, down)


def _route(h, gate_w, cfg):
    """(weights [T, E], chosen [T, k]): group-limited top-k over all
    published experts, the chosen probabilities (times the scaling
    factor, renormalised first if the config says so) scattered over
    them, 0 elsewhere."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(h @ gate_w, axis=-1)
    t, e = probs.shape
    groups = cfg["n_group"]
    best = jnp.max(probs.reshape(t, groups, e // groups), axis=-1)
    _, kept = jax.lax.top_k(best, cfg["topk_group"])
    in_kept = jnp.zeros((t, groups), bool).at[
        jnp.arange(t)[:, None], kept].set(True)
    allowed = jnp.repeat(in_kept, e // groups, axis=1)
    top, chosen = jax.lax.top_k(jnp.where(allowed, probs, 0.0),
                                cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * cfg["routed_scaling_factor"]
    rows = jnp.arange(t)[:, None]
    return jnp.zeros_like(probs).at[rows, chosen].set(top), chosen


def _moe_open(x, w, cfg):
    """The expert layer's part outside the routed experts: norm, router,
    shared experts. -> (normed input, router weights over all published
    experts, chosen experts, x + shared); ``w`` in MOE_KEYS order."""
    import jax.numpy as jnp

    norm_w, gate_w, shared_gu, shared_down = (
        a.astype(jnp.float32) for a in w)
    h = _norm(x, norm_w, cfg["rms_norm_eps"])
    weights, chosen = _route(h, gate_w, cfg)
    return h, weights, chosen, x + _swiglu(h, shared_gu, shared_down)


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
    """(logits [T, vocab] float32, [chosen experts [T, k] a sparse
    layer]) for ONE sequence of token ids. Each block is its own jitted
    program that upcasts its own weights, the experts a group at a time,
    so the whole fits beside a loaded engine."""
    import jax
    import jax.numpy as jnp

    def block(fn):
        @jax.jit
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args, cfg)
        return run

    attn, dense, head = block(_attention_block), block(_dense_block), \
        block(_head)
    moe_open, group = block(_moe_open), block(_expert_group)
    x = weights[EMBED][jnp.asarray(ids)].astype(jnp.float32)
    routing = []
    for i in range(cfg["num_hidden_layers"]):
        p = LAYER % i
        x = attn(x, [weights[p + k] for k in ATTN_KEYS])
        if not is_sparse(cfg, i):
            x = dense(x, [weights[p + k] for k in DENSE_KEYS])
            continue
        h, router, chosen, x = moe_open(x, [weights[p + k]
                                            for k in MOE_KEYS])
        routing.append(chosen)
        w1, w2 = (weights[p + k] for k in EXPERT_KEYS)
        for start in range(0, cfg["n_routed_experts"], EXPERT_GROUP):
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
    """(dense layers, sparse layers)."""
    sparse = sum(is_sparse(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return cfg["num_hidden_layers"] - sparse, sparse


def layer_params(cfg):
    """Parameters of one layer by part, as held here."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    q_rank, rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    width = cfg["moe_intermediate_size"]
    return {
        "attention": (h * q_rank + q_rank * heads * (nope + rope)
                      + h * (rank + rope) + rank * heads * (nope + dv)
                      + heads * dv * h + q_rank + rank),
        "dense_mlp": 3 * h * cfg["intermediate_size"],
        "experts": cfg["n_routed_experts"] * 3 * h * width,
        "moe_other": (h * published_experts(cfg)
                      + 3 * h * cfg["n_shared_experts"] * width),
        "norms": 2 * h,
    }


def param_count(cfg):
    lp = layer_params(cfg)
    dense, sparse = layer_counts(cfg)
    embeds = cfg["vocab_size"] * cfg["hidden_size"] * (
        1 if cfg["tie_word_embeddings"] else 2)
    return (cfg["num_hidden_layers"] * (lp["attention"] + lp["norms"])
            + dense * lp["dense_mlp"]
            + sparse * (lp["experts"] + lp["moe_other"])
            + embeds + cfg["hidden_size"])


def kv_page_bytes(cfg, block_size):
    """Bytes of one page over every layer's latent plane as the pool
    holds it: a token's row is ``latent_width`` rounded up to whole
    128-lane tiles (576 -> 640), which is how the chip's memory tiles a
    row of any declared width."""
    lanes = -(-latent_width(cfg) // LANES) * LANES
    return (cfg["num_hidden_layers"] * block_size * lanes
            * DTYPE_BYTES[cfg["torch_dtype"]])


def mla_decode_cost(cfg, context_tokens, rows):
    """(FLOPs, bytes) the algorithm needs for ONE call of the absorbed
    latent-attention decode kernel (one layer, one step): ``rows``
    queries of one token, every head against ``context_tokens`` cached
    rows in all. A cached row (``latent_width`` values, not the pool's
    lane padding) is read once for all heads and both dots: scores over
    its whole width, values from its first ``kv_lora_rank``; every
    head's absorbed query is read and its latent output written once."""
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
    written once. 6 x hidden x width FLOPs a pair."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    size = DTYPE_BYTES[cfg["torch_dtype"]]
    flops = 6 * h * f * pairs
    moved = (experts_touched * 3 * h * f + 2 * rows * h) * size
    return flops, moved
