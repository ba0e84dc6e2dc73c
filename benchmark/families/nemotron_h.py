"""The Nemotron-H family: a hybrid decoder whose every layer is ONE mixer
behind one norm — Mamba-2 (``M``), a sparse mixture of experts (``E``) or
softmax attention (``*``), in the order ``hybrid_override_pattern`` gives
(nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 ``config.json``,
``model_type`` ``nemotron_h``; "Nemotron-H", arXiv:2504.03624; Dao & Gu,
"Transformers are SSMs", arXiv:2405.21060).

Three things live here, as in ``families/qwen3_next.py``:

- ``build_model``: the system under test through the program's normal
  classes (``paddle_tpu.models.nemotron_h``), nothing patched;
- ``reference_*``: the architecture in plain ``jax.numpy`` float32 under
  ``jax.default_matmul_precision("highest")``, from the published
  equations, with no kernel, cache, chunking or batching and no import
  from ``paddle_tpu.models``: Mamba-2 is the token-by-token recurrence (a
  ``lax.scan`` over a state [H, P, N]), its convolution an explicit sum
  over the taps, attention ``softmax(Q K^T) V`` over the whole sequence
  (a block of query rows at a time, so that 8192 rows fit), the experts
  a loop over groups of experts, every expert of a group run on every
  token and weighted by a router written here (0 where not chosen);
- the arithmetic the layer metrics divide by. A family whose decode step
  holds the ``ssm_decode`` kernel provides ``ssm_decode_cost(cfg, rows)``
  for ``layer_metrics/ssm_decode_roofline.py``, and its mix's ``kernels``
  entry says how many calls a step holds (``"ssm_decode":
  "mamba_layers"``, a key of the configuration).

The equations (``cfg`` keys in brackets):

- layer: ``x += mixer(norm(x))``, ``norm(x) = x / sqrt(mean(x^2) +
  layer_norm_epsilon) * w``; one mixer a layer, no second sub-block;
- ``M``: ``in_proj`` gives z [H P], xBC [H P + 2 G N], dt [H] in that
  order (H ``mamba_num_heads``, P ``mamba_head_dim``, N
  ``ssm_state_size``, G ``n_groups``); ``xBC_t = silu(bias + sum_j
  w[:, j] xBC_{t - K + 1 + j})`` (K ``conv_kernel``, zeros before the
  sequence); x [H, P], B and C [G, N], head h reading group h // (H / G);
  ``dt = softplus(dt + dt_bias)`` (no clamp), ``A = -exp(A_log)``;
  ``S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g]``,
  ``y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]``; ``out_proj(RMSNorm(y *
  silu(z)))``, the gate first and the norm in G groups of H P / G;
- ``*``: q, k, v projections (``num_attention_heads``,
  ``num_key_value_heads``, ``head_dim``), causal softmax at scale
  ``head_dim^-1/2``, NO rotation and no QK norm, ``o_proj``;
- ``E``: ``s = sigmoid(x W_g)`` over ``n_routed_experts_published``; the
  ``num_experts_per_tok`` experts are the top of ``s +
  e_score_correction_bias``; their weights are ``s`` there (without the
  bias) over their sum + 1e-20, times ``routed_scaling_factor``; an
  expert is ``W_down relu(W_up x)^2``, not gated; one shared expert of
  the same form at ``moe_shared_expert_intermediate_size`` on every
  token; ``y = sum_{e chosen, e held here} w_e E_e(x) + shared(x)``.

Departures of the reference from the published model, all forced by what
it is compared with: weights are the program's seeded random ones; the
experts held elsewhere (``n_routed_experts`` of
``n_routed_experts_published`` are held here, from ``experts_held_from``)
are left out of the sum and the vocabulary is the slice held here, as in
the program; no multi-token-prediction module is built.
"""
from __future__ import annotations

# functional_state() names of the program's decoder
# (models/nemotron_h.py)
EMBED = "backbone.embeddings"
FINAL_NORM = "backbone.norm_f"
LM_HEAD = "lm_head"
LAYER = "backbone.layers.%d."
MAMBA_KEYS = ("norm", "mixer.in_proj", "mixer.conv_weight",
              "mixer.conv_bias", "mixer.A_log", "mixer.dt_bias", "mixer.D",
              "mixer.norm_weight", "mixer.out_proj")
ATTN_KEYS = ("norm", "mixer.q_proj", "mixer.k_proj", "mixer.v_proj",
             "mixer.o_proj")
MOE_KEYS = ("norm", "mixer.experts.gate_weight",
            "mixer.e_score_correction_bias", "mixer.shared_up",
            "mixer.shared_down")
EXPERT_KEYS = ("mixer.experts.w1", "mixer.experts.w2")

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
# experts upcast to float32 at a time in the reference: 16 experts of
# 2 x 2688 x 1856 are 0.64 GB
EXPERT_GROUP = 16
# seeded tokens the router biases are balanced on when a model is built
BALANCE_TOKENS = 1024
# query rows a block of the reference's attention: 32 heads x 1024 x
# 8192 scores are 1 GB
ATTN_ROWS = 1024


def published_experts(cfg):
    return cfg.get("n_routed_experts_published", cfg["n_routed_experts"])


def held_from(cfg):
    return cfg.get("experts_held_from", 0)


# -- the system under test ---------------------------------------------------

def model_config(cfg):
    """The program's ``NemotronHConfig`` of a configuration file."""
    from paddle_tpu.models.nemotron_h import NemotronHConfig

    lo = held_from(cfg)
    return NemotronHConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        hybrid_override_pattern=cfg["hybrid_override_pattern"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        mamba_num_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"],
        ssm_state_size=cfg["ssm_state_size"],
        n_groups=cfg["n_groups"],
        conv_kernel=cfg["conv_kernel"],
        chunk_size=cfg["chunk_size"],
        time_step_min=cfg["time_step_min"],
        time_step_max=cfg["time_step_max"],
        time_step_floor=cfg["time_step_floor"],
        n_routed_experts=published_experts(cfg),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=cfg[
            "moe_shared_expert_intermediate_size"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"],
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
        experts_held=range(lo, lo + cfg["n_routed_experts"]),
        max_position_embeddings=cfg["max_position_embeddings"],
        dtype=cfg["torch_dtype"])


def build_model(cfg, seed, training):
    """``NemotronHForCausalLM`` at the configuration's sizes, as a user
    of the program builds it: every parameter is drawn on the default
    device from the seeded framework generator, in the served dtype;
    then ``e_score_correction_bias`` gets what training gives it, the
    family's balancing rule, on BALANCE_TOKENS seeded tokens
    (``model.balance_router_bias``): the published buffer keeps the
    experts' loads even, and without it which few experts every token
    picks, and how many of them are held here, is a draw of the seed.
    Flags stay at the program's defaults."""
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models.nemotron_h import NemotronHForCausalLM

    if training:
        raise ValueError("the nemotron_h family is a serving family: "
                         "models/nemotron_h.py is inference code")
    paddle.seed(int(seed) % (2 ** 31 - 1))
    model = NemotronHForCausalLM(model_config(cfg))
    model.eval()
    gate = model.backbone.layers[cfg["hybrid_override_pattern"].index(
        "E")].mixer.experts.gate_weight._value
    if bool(jnp.any(gate)):     # weights were drawn (rehearse.py's are 0)
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
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                        + eps) * weight


def _mamba_block(x, w, cfg):
    """x + Mamba2(norm(x)) on one sequence [T, hidden], one token at a
    time; ``w`` in MAMBA_KEYS order, any float type."""
    import jax
    import jax.numpy as jnp

    (norm_w, w_in, conv_w, conv_b, a_log, dt_bias, skip, gate_norm,
     w_out) = (a.astype(jnp.float32) for a in w)
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    n, groups = cfg["ssm_state_size"], cfg["n_groups"]
    kernel, eps = cfg["conv_kernel"], cfg["layer_norm_epsilon"]
    inner = heads * p
    conv_dim = inner + 2 * groups * n
    t = x.shape[0]
    zxbcdt = _norm(x, norm_w, eps) @ w_in
    z = zxbcdt[:, :inner]
    xbc = zxbcdt[:, inner:inner + conv_dim]
    dt = jax.nn.softplus(zxbcdt[:, inner + conv_dim:] + dt_bias)
    # causal depthwise convolution: y[t] = sum_j w[:, j] x[t - (K-1) + j]
    padded = jnp.concatenate(
        [jnp.zeros((kernel - 1, conv_dim), jnp.float32), xbc])
    conv = conv_b
    for j in range(kernel):
        conv = conv + padded[j:j + t] * conv_w[:, j]
    conv = jax.nn.silu(conv)
    xs = conv[:, :inner].reshape(t, heads, p)
    rep = heads // groups               # head h reads group h // rep
    b = jnp.repeat(conv[:, inner:inner + groups * n].reshape(
        t, groups, n), rep, axis=1)
    c = jnp.repeat(conv[:, inner + groups * n:].reshape(
        t, groups, n), rep, axis=1)
    a = -jnp.exp(a_log)

    def token(s, row):
        x_t, b_t, c_t, dt_t = row           # [H, P], [H, N], [H, N], [H]
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, c_t) + skip[:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((heads, p, n), jnp.float32),
                        (xs, b, c, dt))
    y = y.reshape(t, inner) * jax.nn.silu(z)
    grouped = y.reshape(t, groups, inner // groups)
    grouped = grouped / jnp.sqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return x + (grouped.reshape(t, inner) * gate_norm) @ w_out


def _attention_block(x, w, cfg):
    """x + Attention(norm(x)) on one sequence [T, hidden], a block of
    ATTN_ROWS query rows at a time; ``w`` in ATTN_KEYS order."""
    import jax
    import jax.numpy as jnp

    norm_w, wq, wk, wv, wo = (a.astype(jnp.float32) for a in w)
    heads, kv_heads, d = (cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], cfg["head_dim"])
    t = x.shape[0]
    h = _norm(x, norm_w, cfg["layer_norm_epsilon"])
    q = (h @ wq).reshape(t, kv_heads, heads // kv_heads, d)
    k = (h @ wk).reshape(t, kv_heads, d)
    v = (h @ wv).reshape(t, kv_heads, d)
    out = []
    for start in range(0, t, ATTN_ROWS):
        rows = q[start:start + ATTN_ROWS]
        scores = jnp.einsum("tkgd,skd->kgts", rows, k) / jnp.sqrt(
            jnp.float32(d))
        causal = (jnp.arange(t)[None, :]
                  <= start + jnp.arange(rows.shape[0])[:, None])
        probs = jax.nn.softmax(
            jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("kgts,skd->tkgd", probs, v).reshape(
            rows.shape[0], heads * d))
    return x + jnp.concatenate(out) @ wo


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


def _relu2(x):
    import jax.numpy as jnp

    return jnp.square(jnp.maximum(x, 0.0))


def _moe_open(x, w, cfg):
    """The expert layer's part outside the routed experts: norm, router,
    shared expert. -> (normed input, router weights over all published
    experts, chosen experts, x + shared); ``w`` in MOE_KEYS order."""
    import jax.numpy as jnp

    norm_w, gate_w, bias, shared_up, shared_down = (
        a.astype(jnp.float32) for a in w)
    h = _norm(x, norm_w, cfg["layer_norm_epsilon"])
    weights, chosen = _route(h, gate_w, bias, cfg)
    return h, weights, chosen, x + _relu2(h @ shared_up) @ shared_down


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
    hid = _relu2(jnp.einsum("td,edf->etf", h, g1))
    return acc + jnp.einsum("te,etd->td", wt,
                            jnp.einsum("etf,efd->etd", hid, g2))


def _head(x, w, cfg):
    import jax.numpy as jnp

    norm_w, lm_head = (a.astype(jnp.float32) for a in w)
    return _norm(x, norm_w, cfg["layer_norm_epsilon"]) @ lm_head


def reference_forward(weights, cfg, ids):
    """(logits [T, vocab] float32, [chosen experts [T, k] an expert
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

    mamba, attn, head = (block(_mamba_block), block(_attention_block),
                         block(_head))
    moe_open, group = block(_moe_open), block(_expert_group)
    x = weights[EMBED][jnp.asarray(ids)].astype(jnp.float32)
    routing = []
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        p = LAYER % i
        if kind == "M":
            x = mamba(x, [weights[p + k] for k in MAMBA_KEYS])
        elif kind == "*":
            x = attn(x, [weights[p + k] for k in ATTN_KEYS])
        else:
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
    """{"M": Mamba-2 layers, "E": expert layers, "*": attention layers}."""
    pattern = cfg["hybrid_override_pattern"]
    return {kind: pattern.count(kind) for kind in "ME*"}


def layer_params(cfg):
    """Parameters of one layer of each kind (its norm included), as held
    here."""
    h = cfg["hidden_size"]
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv_dim = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    heads = cfg["mamba_num_heads"]
    d = cfg["head_dim"]
    return {
        "M": (h * (inner + conv_dim + heads)
              + conv_dim * (cfg["conv_kernel"] + 1)
              + 3 * heads + inner + inner * h + h),
        "*": (h * d * 2 * (cfg["num_attention_heads"]
                           + cfg["num_key_value_heads"]) + h),
        "E": (cfg["n_routed_experts"] * 2 * h * cfg["moe_intermediate_size"]
              + 2 * h * cfg["moe_shared_expert_intermediate_size"]
              + h * published_experts(cfg) + published_experts(cfg) + h),
    }


def param_count(cfg):
    lp, counts = layer_params(cfg), layer_counts(cfg)
    embeds = cfg["vocab_size"] * cfg["hidden_size"] * (
        1 if cfg["tie_word_embeddings"] else 2)
    return (sum(counts[kind] * lp[kind] for kind in "ME*") + embeds
            + cfg["hidden_size"])


def active_matmul_params(cfg):
    """Parameters a token is multiplied by here: the Mamba and attention
    projections, the router, the shared expert, the share of its
    ``num_experts_per_tok`` experts that is held here on average, and the
    output head."""
    h = cfg["hidden_size"]
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv_dim = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    counts = layer_counts(cfg)
    mamba = h * (inner + conv_dim + cfg["mamba_num_heads"]) + inner * h
    attention = h * cfg["head_dim"] * 2 * (cfg["num_attention_heads"]
                                           + cfg["num_key_value_heads"])
    routed = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
              / published_experts(cfg)) * 2 * h * cfg[
                  "moe_intermediate_size"]
    experts = (h * published_experts(cfg) + routed
               + 2 * h * cfg["moe_shared_expert_intermediate_size"])
    return (counts["M"] * mamba + counts["*"] * attention
            + counts["E"] * experts + cfg["vocab_size"] * h)


def train_flops_per_token(cfg, seq_len):
    """Model FLOPs of forward + backward for one token of a sequence of
    ``seq_len`` on this chip's share: 6 per active matmul parameter,
    causal attention in the attention layers, and the Mamba-2 state (the
    decay, the rank-one update and the read-out: 3 products of P x N a
    head a token, 2 FLOPs each, three times for forward and backward)."""
    counts = layer_counts(cfg)
    attn_fwd = (2 * 2 * seq_len * cfg["num_attention_heads"]
                * cfg["head_dim"]) / 2
    return (6 * active_matmul_params(cfg) + 3 * attn_fwd * counts["*"]
            + 3 * ssm_decode_cost(cfg, 1)[0] * counts["M"])


def kv_page_bytes(cfg, block_size):
    """Bytes of one page across the K and V planes of the layers that
    keep pages: the attention layers only."""
    return (2 * layer_counts(cfg)["*"] * block_size
            * cfg["num_key_value_heads"] * cfg["head_dim"]
            * DTYPE_BYTES[cfg["torch_dtype"]])


def state_slot_bytes(cfg):
    """Bytes one slot's recurrent state (float32) and convolution tail
    take over the Mamba-2 layers."""
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv_dim = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    tail = ((cfg["conv_kernel"] - 1) * conv_dim
            * DTYPE_BYTES[cfg["torch_dtype"]])
    return layer_counts(cfg)["M"] * (inner * cfg["ssm_state_size"] * 4
                                     + tail)


def paged_decode_cost(cfg, context_tokens, rows):
    """(FLOPs, bytes) the algorithm needs for ONE call of the paged
    decode kernel (one attention layer, one step): ``rows`` queries of
    one token attending to ``context_tokens`` cached tokens in all. Each
    cached token's K and V rows are read once per KV head; q is read and
    the output written once."""
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
    written once. 4 x hidden x width FLOPs a pair: an expert is two
    matrices, not three (no gate)."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    size = DTYPE_BYTES[cfg["torch_dtype"]]
    flops = 4 * h * f * pairs
    moved = (experts_touched * 2 * h * f + 2 * rows * h) * size
    return flops, moved


def ssm_decode_cost(cfg, rows):
    """(FLOPs, bytes) the algorithm needs for ONE call of the
    ``ssm_decode`` kernel (one Mamba-2 layer, one step) over ``rows``
    slots: a slot's float32 state is read once and written once; beside
    it x, B, C (the served dtype) and dt (float32) are read and y
    (float32) written. 6 FLOPs a state element: the decay, the rank-one
    update and the read-out, a multiply and an add each. The bound is
    the memory's by a factor of a hundred."""
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    n, groups = cfg["ssm_state_size"], cfg["n_groups"]
    size = DTYPE_BYTES[cfg["torch_dtype"]]
    state = heads * p * n
    moved = (2 * state * 4 + (heads * p + 2 * groups * n) * size
             + heads * 4 + heads * p * 4)
    return 6 * state * rows, moved * rows
