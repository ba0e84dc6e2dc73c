"""The Phi-4-mini-flash family: the SambaY decoder-hybrid-decoder
(microsoft/Phi-4-mini-flash-reasoning ``config.json``, ``model_type``
``phi4flash``; arXiv:2507.06607; Gu & Dao, "Mamba", arXiv:2312.00752; Ye
et al., "Differential Transformer", arXiv:2410.05258).

Three things live here, as in ``families/nemotron_h.py``:

- ``build_model``: the system under test through the program's normal
  classes (``paddle_tpu.models.phi4flash``), nothing patched;
- ``reference_*``: the architecture in plain ``jax.numpy`` float32 under
  ``jax.default_matmul_precision("highest")``, from the equations below,
  with no kernel, cache, ring or batching and no import from
  ``paddle_tpu``: Mamba-1 is the token-by-token recurrence (a
  ``lax.scan`` over a state [channels, N]), its convolution an explicit
  sum over the taps; both softmax maps of differential attention over
  explicit masks, a block of query rows at a time; the cross-decoder and
  the head over EVERY position (no YOCO skip), the head a block of the
  vocabulary at a time, so that it fits beside a loaded engine;
- the arithmetic the layer metrics divide by.

The equations (``cfg`` keys in brackets; L = ``num_hidden_layers``, m =
``mb_per_layer``):

- block: ``x += mixer(LN1(x)); x += MLP(LN2(x))``, LayerNorm with weight
  and bias at ``layer_norm_eps``; ``MLP(u) = (silu(u W_g) * u W_u) W_d``
  (``intermediate_size``, no bias); a final LayerNorm, then the head,
  the embedding transposed (``tie_word_embeddings``, no bias);
- the mixer of layer l: Mamba-1 for l < L/2 with l % m == 0 and for
  l = L/2 (the memory layer); window attention for the other l < L/2
  (``sliding_window`` keys, the token's own counted); full causal
  attention at l = L/2 + 1; for l > L/2 + 1 a GMU when l % m == 0, else
  cross attention over layer L/2 + 1's K/V;
- Mamba-1 (``mamba_d_state`` N, ``mamba_d_conv`` K, channels
  ``mamba_expand`` x hidden, ``mamba_dt_rank``): ``[x, z] = u W_in``;
  ``x^ = silu(b + sum_j w[:, j] x_{t-K+1+j})`` (zeros before the
  sequence); ``[d, B, C] = x^ W_x``; ``dt = softplus(d W_dt + b_dt)``;
  ``A = -exp(A_log)`` [channels, N]; ``h_t = exp(dt_t A) h_{t-1} +
  (dt_t x^_t) B_t^T``; ``y_t = h_t C_t + D x^_t``; ``M = y * silu(z)``,
  the output ``M W_out``; the memory is the memory layer's M;
- GMU: ``(M * silu(u W_1)) W_2``;
- differential attention (``num_attention_heads`` H, ``num_key_value_heads``
  Hkv, head size d = hidden / H, no rotation, no bias): query pair h =
  heads (2h, 2h+1) reads KV pair g = h // (H / Hkv), heads (2g, 2g+1);
  ``A1 = softmax(q_2h k_2g^T / sqrt(d))``, ``A2 = softmax(q_2h+1
  k_2g+1^T / sqrt(d))`` over the keys a row sees; ``o_h = A1 [v_2g |
  v_2g+1] - lam A2 [v_2g | v_2g+1]``; ``lam = exp(lq1 . lk1) - exp(lq2 .
  lk2) + lam_init``, ``lam_init = 0.8 - 0.6 exp(-0.3 l)``; ``out =
  concat_h(RMSNorm(o_h) w (1 - lam_init)) W_o``, the norm over 2 d at
  ``layer_norm_eps`` with weight w; cross attention has only W_q and
  W_o.

Keys a test may set to take a part out of the reference, and that
``build_model`` refuses: ``diff_lambda`` false (o_h = A1 [..] alone),
``diff_subnorm`` false (no RMSNorm of o_h), ``memory_layer`` (the memory
taken from another Mamba-1 layer), ``sliding_window`` null (window
layers attend to every earlier key).

Departures of the reference from the published model, all forced by what
it is compared with: the weights are the program's seeded random ones.
"""
from __future__ import annotations

import json
import math

import numpy as np

# functional_state() names of the program's decoder
# (models/phi4flash.py)
EMBED = "model.embed_tokens"
FINAL_NORM = ("model.final_norm_weight", "model.final_norm_bias")
LAYER = "model.layers.%d."
NORM_KEYS = ("ln1_weight", "ln1_bias")
MLP_KEYS = ("ln2_weight", "ln2_bias", "mlp.w_gate", "mlp.w_up",
            "mlp.w_down")
MAMBA_KEYS = NORM_KEYS + (
    "mixer.in_proj", "mixer.conv_weight", "mixer.conv_bias",
    "mixer.x_proj", "mixer.dt_proj", "mixer.dt_bias", "mixer.A_log",
    "mixer.D", "mixer.out_proj")
LAMBDA_KEYS = ("mixer.lambda_q1", "mixer.lambda_k1", "mixer.lambda_q2",
               "mixer.lambda_k2", "mixer.subln")
ATTN_KEYS = NORM_KEYS + ("mixer.q_proj", "mixer.k_proj", "mixer.v_proj",
                         "mixer.o_proj") + LAMBDA_KEYS
CROSS_KEYS = NORM_KEYS + ("mixer.q_proj", "mixer.o_proj") + LAMBDA_KEYS
GMU_KEYS = NORM_KEYS + ("mixer.in_proj", "mixer.out_proj")

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
# query rows a block of the reference's attention: 40 heads x 512 x 2048
# scores are 168 MB
ATTN_ROWS = 512
# vocabulary rows a block of the reference's head: 16384 x 2560 float32
# is 168 MB
HEAD_ROWS = 16384
ABLATIONS = {"diff_lambda": True, "diff_subnorm": True}


# -- the layer map -----------------------------------------------------------

def layer_kind(cfg, i):
    """``mamba``, ``window``, ``full``, ``gmu`` or ``cross``."""
    half, m = cfg["num_hidden_layers"] // 2, cfg["mb_per_layer"]
    if i <= half:
        return "mamba" if i % m == 0 or i == half else "window"
    if i == half + 1:
        return "full"
    return "gmu" if i % m == 0 else "cross"


def layer_counts(cfg):
    kinds = [layer_kind(cfg, i) for i in range(cfg["num_hidden_layers"])]
    return {k: kinds.count(k)
            for k in ("mamba", "window", "full", "gmu", "cross")}


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def mamba_inner(cfg):
    return cfg["mamba_expand"] * cfg["hidden_size"]


# -- the system under test ---------------------------------------------------

def model_config(cfg):
    """The program's ``Phi4FlashConfig`` of a configuration file."""
    from paddle_tpu.models.phi4flash import Phi4FlashConfig

    refused = [k for k, v in ABLATIONS.items() if cfg.get(k, v) != v]
    if cfg.get("memory_layer", cfg["num_hidden_layers"] // 2) \
            != cfg["num_hidden_layers"] // 2:
        refused.append("memory_layer")
    if cfg.get("sliding_window") is None:
        refused.append("sliding_window")
    if refused or not cfg["tie_word_embeddings"] or cfg["mlp_bias"] \
            or cfg["lm_head_bias"]:
        raise ValueError("the program builds the published model only, "
                         "not one with %s changed"
                         % (refused or "the head or the biases"))
    return Phi4FlashConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        sliding_window=cfg["sliding_window"],
        mb_per_layer=cfg["mb_per_layer"],
        mamba_d_state=cfg["mamba_d_state"],
        mamba_d_conv=cfg["mamba_d_conv"],
        mamba_expand=cfg["mamba_expand"],
        mamba_dt_rank=cfg["mamba_dt_rank"],
        layer_norm_eps=cfg["layer_norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"],
        time_step_min=cfg["time_step_min"],
        time_step_max=cfg["time_step_max"],
        time_step_floor=cfg["time_step_floor"],
        lambda_std=cfg["lambda_std"],
        dtype=cfg["torch_dtype"])


def build_model(cfg, seed, training):
    """``Phi4FlashForCausalLM`` at the configuration's sizes, as a user
    of the program builds it: every parameter drawn on the default device
    from the seeded framework generator, in the served dtype (the four
    lambda vectors of an attention layer in float32). Flags stay at the
    program's defaults."""
    import paddle_tpu as paddle
    from paddle_tpu.models.phi4flash import Phi4FlashForCausalLM

    if training:
        raise ValueError("the phi4flash family is a serving family: "
                         "models/phi4flash.py is inference code")
    paddle.seed(int(seed) % (2 ** 31 - 1))
    model = Phi4FlashForCausalLM(model_config(cfg))
    model.eval()
    return model


def weights_of(model):
    names, values = model.functional_state()
    return dict(zip(names, values))


# -- the plain reference -----------------------------------------------------

def _f32(ws):
    import jax.numpy as jnp

    return [jnp.asarray(w).astype(jnp.float32) for w in ws]


def _ln(x, weight, bias, eps):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * weight + bias


def _mlp_block(x, w, cfg):
    import jax

    ln_w, ln_b, w_gate, w_up, w_down = _f32(w)
    u = _ln(x, ln_w, ln_b, cfg["layer_norm_eps"])
    return x + (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def _mamba_block(x, w, cfg):
    """(x + Mamba1(LN1(x)), M) on one sequence [T, hidden], one token at
    a time."""
    import jax
    import jax.numpy as jnp

    (ln_w, ln_b, w_in, conv_w, conv_b, w_x, w_dt, dt_b, a_log, skip,
     w_out) = _f32(w)
    inner, n = mamba_inner(cfg), cfg["mamba_d_state"]
    kernel, rank = cfg["mamba_d_conv"], cfg["mamba_dt_rank"]
    t = x.shape[0]
    xz = _ln(x, ln_w, ln_b, cfg["layer_norm_eps"]) @ w_in
    xs, z = xz[:, :inner], xz[:, inner:]
    padded = jnp.concatenate([jnp.zeros((kernel - 1, inner), jnp.float32),
                              xs])
    conv = conv_b
    for j in range(kernel):
        conv = conv + padded[j:j + t] * conv_w[:, j]
    conv = jax.nn.silu(conv)
    dbc = conv @ w_x
    b, c = dbc[:, rank:rank + n], dbc[:, rank + n:]
    dt = jax.nn.softplus(dbc[:, :rank] @ w_dt + dt_b)
    a = -jnp.exp(a_log)                                 # [inner, N]

    def token(h, row):
        x_t, dt_t, b_t, c_t = row
        h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * x_t)[:, None] * b_t
        return h, h @ c_t + skip * x_t

    _, y = jax.lax.scan(token, jnp.zeros((inner, n), jnp.float32),
                        (conv, dt, b, c))
    gated = y * jax.nn.silu(z)
    return x + gated @ w_out, gated


def _gmu_block(x, memory, w, cfg):
    import jax

    ln_w, ln_b, w1, w2 = _f32(w)
    u = _ln(x, ln_w, ln_b, cfg["layer_norm_eps"])
    return x + (memory * jax.nn.silu(u @ w1)) @ w2


def _diff_attend(x, k, v, w, cfg, layer, window):
    """x + differential attention of LN1(x)'s queries over k, v
    [T, Hkv, d] (causal, banded to ``window`` when given), ATTN_ROWS
    query rows at a time. ``w``: the norm, W_q, W_o, then LAMBDA_KEYS."""
    import jax
    import jax.numpy as jnp

    ln_w, ln_b, wq, wo, lq1, lk1, lq2, lk2, subln = _f32(w)
    heads, kv_heads, d = (cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], head_dim(cfg))
    rep = heads // kv_heads
    t = x.shape[0]
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) \
        + lam_init
    if not cfg.get("diff_lambda", True):
        lam = 0.0
    q = (_ln(x, ln_w, ln_b, cfg["layer_norm_eps"]) @ wq).reshape(
        t, heads, d)
    values = v.reshape(t, kv_heads // 2, 2 * d)
    key = jnp.arange(t)
    out = []
    for start in range(0, t, ATTN_ROWS):
        rows = q[start:start + ATTN_ROWS]
        pos = start + jnp.arange(rows.shape[0])[:, None]
        seen = key[None, :] <= pos
        if window is not None:
            seen = seen & (pos - key[None, :] < window)
        maps = []
        for e in (0, 1):
            # query pair h's map e: head 2h + e against KV head
            # 2 (h // rep) + e
            qe = rows[:, e::2]                           # [R, H/2, d]
            ke = jnp.repeat(k[:, e::2], rep, axis=1)     # [T, H/2, d]
            s = jnp.einsum("rhd,thd->hrt", qe, ke) / math.sqrt(d)
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
            maps.append(jnp.einsum("hrt,thv->rhv", p,
                                   jnp.repeat(values, rep, axis=1)))
        o = maps[0] - lam * maps[1]                      # [R, H/2, 2d]
        if cfg.get("diff_subnorm", True):
            o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                             + cfg["layer_norm_eps"]) * subln
        out.append((o * (1.0 - lam_init)).reshape(rows.shape[0],
                                                  heads * d))
    return x + jnp.concatenate(out) @ wo


def _kv(x, w, cfg):
    """Layer's K and V [T, Hkv, d] of LN1(x): ``w`` is the norm, W_k,
    W_v."""
    ln_w, ln_b, wk, wv = _f32(w)
    u = _ln(x, ln_w, ln_b, cfg["layer_norm_eps"])
    shape = (x.shape[0], cfg["num_key_value_heads"], head_dim(cfg))
    return (u @ wk).reshape(shape), (u @ wv).reshape(shape)


def _head_part(x, weights, embed_rows, cfg):
    """Logits of the vocabulary rows ``embed_rows`` after the final
    norm."""
    norm_w, norm_b = _f32(weights)
    return _ln(x, norm_w, norm_b, cfg["layer_norm_eps"]) @ _f32(
        [embed_rows])[0].T


_BLOCKS = {}


def _block(cfg, fn, **static):
    """``fn`` of a block, jitted under the highest matmul precision with
    ``cfg`` and ``static`` bound; one program a configuration and block,
    kept across calls."""
    import jax

    key = (json.dumps(cfg, sort_keys=True), fn.__name__,
           tuple(sorted(static.items())))
    if key not in _BLOCKS:
        @jax.jit
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args, cfg, **static)
        _BLOCKS[key] = run
    return _BLOCKS[key]


def reference_logits(weights, cfg, ids):
    """Logits [T, vocab] (a host array, float32) for ONE sequence of
    token ids: every position through every layer. Each block is its own
    jitted program that upcasts its own weights, and the head a block of
    HEAD_ROWS vocabulary rows at a time."""
    import jax.numpy as jnp

    def block(fn, **static):
        return _block(cfg, fn, **static)

    mamba, mlp, gmu = block(_mamba_block), block(_mlp_block), \
        block(_gmu_block)
    kv, head = block(_kv), block(_head_part)
    x = jnp.asarray(weights[EMBED])[jnp.asarray(ids)].astype(jnp.float32)
    memory_layer = cfg.get("memory_layer", cfg["num_hidden_layers"] // 2)
    memory = full_kv = None
    for i in range(cfg["num_hidden_layers"]):
        p = LAYER % i
        kind = layer_kind(cfg, i)
        if kind == "mamba":
            x, gated = mamba(x, [weights[p + k] for k in MAMBA_KEYS])
            if i == memory_layer:
                memory = gated
        elif kind == "gmu":
            x = gmu(x, memory, [weights[p + k] for k in GMU_KEYS])
        else:
            if kind != "cross":
                k, v = kv(x, [weights[p + k] for k in NORM_KEYS
                              + ("mixer.k_proj", "mixer.v_proj")])
                if kind == "full":
                    full_kv = (k, v)
            else:
                k, v = full_kv
            window = cfg.get("sliding_window") if kind == "window" else None
            attn = block(_diff_attend, layer=i, window=window)
            x = attn(x, k, v, [weights[p + k] for k in NORM_KEYS
                               + ("mixer.q_proj", "mixer.o_proj")
                               + LAMBDA_KEYS])
        x = mlp(x, [weights[p + k] for k in MLP_KEYS])
    embed = weights[EMBED]
    norm = [weights[k] for k in FINAL_NORM]
    return np.concatenate(
        [np.asarray(head(x, norm, embed[lo:lo + HEAD_ROWS]))
         for lo in range(0, cfg["vocab_size"], HEAD_ROWS)], axis=1)


# -- arithmetic --------------------------------------------------------------

def layer_params(cfg):
    """Parameters of one layer of each kind, its two LayerNorms and its
    MLP included."""
    h, d = cfg["hidden_size"], head_dim(cfg)
    inner, n = mamba_inner(cfg), cfg["mamba_d_state"]
    rank, kernel = cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    common = 4 * h + 3 * h * cfg["intermediate_size"]
    lam = 4 * d + 2 * d
    mamba = (h * 2 * inner + inner * (kernel + 1) + inner * (rank + 2 * n)
             + rank * inner + inner + inner * n + inner + inner * h)
    attn = 2 * h * heads * d + 2 * h * kv_heads * d + lam
    return {"mamba": common + mamba, "window": common + attn,
            "full": common + attn,
            "cross": common + 2 * h * heads * d + lam,
            "gmu": common + 2 * h * inner}


def param_count(cfg):
    lp, counts = layer_params(cfg), layer_counts(cfg)
    return (sum(counts[k] * lp[k] for k in counts)
            + cfg["vocab_size"] * cfg["hidden_size"]
            + 2 * cfg["hidden_size"])


def kv_page_bytes(cfg, block_size):
    """Bytes of one page across the K and V planes of the one layer that
    keeps pages (the full-attention layer; the cross layers read it)."""
    return (2 * layer_counts(cfg)["full"] * block_size
            * cfg["num_key_value_heads"] * head_dim(cfg)
            * DTYPE_BYTES[cfg["torch_dtype"]])


def ring_slot_bytes(cfg):
    """Bytes one slot's rings take over the window layers."""
    return (layer_counts(cfg)["window"] * 2 * cfg["sliding_window"]
            * cfg["num_key_value_heads"] * head_dim(cfg)
            * DTYPE_BYTES[cfg["torch_dtype"]])


def state_slot_bytes(cfg):
    """Bytes one slot's Mamba-1 state (float32) and convolution tail
    take over the Mamba-1 layers."""
    inner = mamba_inner(cfg)
    return layer_counts(cfg)["mamba"] * (
        inner * cfg["mamba_d_state"] * 4
        + (cfg["mamba_d_conv"] - 1) * inner * DTYPE_BYTES[cfg["torch_dtype"]])


def diff_decode_step_cost(cfg, rows, context_tokens):
    """(FLOPs, bytes) the algorithm needs for the attention layers of ONE
    decode step over ``rows`` live slots holding ``context_tokens`` in
    all: every attention projection read once; per slot the full layer's
    rows read by it and by each cross layer, and min(length, window)
    rows of each window layer's ring (a slot's length taken as the mean,
    which counts no more than the rows when every slot holds at least a
    window, as the mix's prompts do); one K/V row written to the pages
    and to each ring; a query and an output row a layer. FLOPs: the
    projections and both maps' products, 4 d a key a query head."""
    counts = layer_counts(cfg)
    h, d = cfg["hidden_size"], head_dim(cfg)
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    size = DTYPE_BYTES[cfg["torch_dtype"]]
    row = 2 * kv_heads * d * size                   # a token's K and V
    self_layers = counts["window"] + counts["full"]
    readers = counts["full"] + counts["cross"]
    weights = (self_layers * (2 * h * heads * d + 2 * h * kv_heads * d)
               + counts["cross"] * 2 * h * heads * d)
    mean = context_tokens / max(rows, 1)
    ring = min(mean, cfg["sliding_window"])
    keys = rows * (readers * mean + counts["window"] * ring)
    moved = (weights * size + keys * row + rows * self_layers * row
             + rows * (self_layers + counts["cross"]) * 2 * h * size)
    flops = 2 * weights * rows + 4 * d * heads * keys
    return flops, moved


def selective_scan_prefill_cost(cfg, rows):
    """(FLOPs, bytes) the algorithm needs for the Mamba-1 layers of ONE
    prefill of ``rows`` real rows: each layer's weights read once; per
    row x and z in, dt, B and C in, y out (the served dtype, dt and y in
    float32); 2 FLOPs a matmul weight a row and 6 a state element a row
    (the decay, the rank-one update, the read-out)."""
    h, inner, n = cfg["hidden_size"], mamba_inner(cfg), cfg["mamba_d_state"]
    rank, kernel = cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    size = DTYPE_BYTES[cfg["torch_dtype"]]
    matmul = h * 2 * inner + inner * (rank + 2 * n) + rank * inner \
        + inner * h
    weights = matmul + inner * (kernel + 1) + 2 * inner + inner * n
    per_row = (2 * inner * size + inner * 4 + 2 * n * size + inner * 4)
    layers = layer_counts(cfg)["mamba"]
    flops = layers * rows * (2 * matmul + 6 * inner * n)
    moved = layers * (weights * size + rows * per_row)
    return flops, moved
