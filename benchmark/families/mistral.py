"""The Mistral-7B family: a dense pre-norm decoder with grouped-query
attention, rotary positions, SwiGLU and untied embeddings
(mistralai/Mistral-7B-v0.3 ``config.json``; Jiang et al., "Mistral 7B",
arXiv:2310.06825; v0.3 declares no sliding window).

Three things live here, and a family is this one file:

- ``build_model``: the system under test, built through the program's
  normal classes (``paddle_tpu.models.llama``), nothing patched;
- ``reference_*``: the architecture in plain ``jax.numpy`` float32 under
  ``jax.default_matmul_precision("highest")``, written from the published
  description, with no kernel, cache or batching and no import from
  ``paddle_tpu.models``. It reads the program's own weight arrays and
  upcasts one block at a time;
- the arithmetic the layer metrics divide by: parameters, model FLOPs a
  token, and FLOPs and bytes of one call of each Mosaic kernel.

Departures of the reference from the published model, all forced by what
it is compared with: weights are the program's seeded random ones, and
the rotary embedding pairs dimension i with i + D/2 (the "rotate half"
form of the published implementation).
"""
from __future__ import annotations

# functional_state() names of the program's decoder (models/llama.py)
EMBED = "llama.embed_tokens.weight"
FINAL_NORM = "llama.norm.weight"
LM_HEAD = "lm_head.weight"
LAYER = "llama.layers.%d."
ATTN_KEYS = ("input_layernorm.weight", "self_attn.q_proj.weight",
             "self_attn.k_proj.weight", "self_attn.v_proj.weight",
             "self_attn.o_proj.weight")
MLP_KEYS = ("post_attention_layernorm.weight", "mlp.gate_proj.weight",
            "mlp.up_proj.weight", "mlp.down_proj.weight")

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def head_dim(cfg):
    return cfg.get("head_dim") or (cfg["hidden_size"]
                                   // cfg["num_attention_heads"])


# -- the system under test ---------------------------------------------------

def build_model(cfg, seed, training):
    """``LlamaForCausalLM`` at the configuration's sizes, as a user of
    the program builds it: the constructor draws every parameter on the
    default device from the seeded framework generator (in float32, leaf
    by leaf: ``Layer.create_parameter`` knows no other way), then
    ``model.to(dtype=...)`` casts to the served type. Nothing touches the
    host. Flags stay at the program's defaults; ``recompute`` is the one
    switch a training configuration names, because the activations do
    not fit beside the optimizer state without it."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    if head_dim(cfg) * cfg["num_attention_heads"] != cfg["hidden_size"]:
        raise ValueError("models/llama.py derives head_dim from "
                         "hidden_size / num_attention_heads")
    paddle.seed(int(seed) % (2 ** 31 - 1))
    dtype = cfg["torch_dtype"]
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        use_parallel=False, dtype=dtype,
        recompute=bool(training and cfg.get("recompute", False))))
    model.to(dtype=dtype)
    if training:
        model.train()
    else:
        model.eval()
    return model


def weights_of(model):
    names, values = model.functional_state()
    return dict(zip(names, values))


# -- the plain reference -----------------------------------------------------

def _rms_norm(x, weight, eps):
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * weight


def _rotate(x, theta):
    """Rotary embedding on [T, heads, D], positions 0..T-1."""
    import jax.numpy as jnp

    t, _, d = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention_block(x, w, cfg):
    """x + Attention(RMSNorm(x)) on one sequence [T, hidden]; ``w`` is the
    block's weights in ATTN_KEYS order, any float type."""
    import jax
    import jax.numpy as jnp

    norm_w, wq, wk, wv, wo = (a.astype(jnp.float32) for a in w)
    heads, kv_heads, d = (cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], head_dim(cfg))
    t = x.shape[0]
    h = _rms_norm(x, norm_w, cfg["rms_norm_eps"])
    q = _rotate((h @ wq).reshape(t, heads, d), cfg["rope_theta"])
    k = _rotate((h @ wk).reshape(t, kv_heads, d), cfg["rope_theta"])
    v = (h @ wv).reshape(t, kv_heads, d)
    group = heads // kv_heads
    q = q.reshape(t, kv_heads, group, d)
    scores = jnp.einsum("tkgd,skd->kgts", q, k) / jnp.sqrt(
        jnp.float32(d))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("kgts,skd->tkgd", probs, v).reshape(t, heads * d)
    return x + ctx @ wo


def _mlp_block(x, w, cfg):
    """x + SwiGLU(RMSNorm(x)); ``w`` in MLP_KEYS order."""
    import jax
    import jax.numpy as jnp

    norm_w, gate, up, down = (a.astype(jnp.float32) for a in w)
    h = _rms_norm(x, norm_w, cfg["rms_norm_eps"])
    return x + (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _head(x, w, cfg):
    import jax.numpy as jnp

    norm_w, lm_head = (a.astype(jnp.float32) for a in w)
    return _rms_norm(x, norm_w, cfg["rms_norm_eps"]) @ lm_head


def reference_logits(weights, cfg, ids):
    """Logits [T, vocab] in float32 for ONE sequence of token ids. Each
    block is its own jitted program that upcasts its own weights, so only
    one block's float32 weights exist at a time and the whole fits beside
    a loaded engine. A pure function of ``weights``: the tests take
    gradients through it."""
    import jax
    import jax.numpy as jnp

    def block(fn):
        @jax.jit
        def run(x, w):
            with jax.default_matmul_precision("highest"):
                return fn(x, w, cfg)
        return run

    attn, mlp, head = block(_attention_block), block(_mlp_block), block(_head)
    x = weights[EMBED][jnp.asarray(ids)].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        p = LAYER % i
        x = attn(x, [weights[p + k] for k in ATTN_KEYS])
        x = mlp(x, [weights[p + k] for k in MLP_KEYS])
    return head(x, [weights[FINAL_NORM], weights[LM_HEAD]])


def cross_entropy(logits, labels):
    """Mean over tokens of -log softmax(logits)[label], float32."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, jnp.asarray(labels)[:, None], -1)
    return -jnp.mean(picked)


def reference_loss(weights, cfg, ids, labels):
    """Mean cross-entropy over a batch [B, T] of ids and labels (labels
    are given per position, as the program's forward takes them), one
    sequence at a time. -> float."""
    import numpy as np

    per_seq = [float(cross_entropy(reference_logits(weights, cfg, row), lab))
               for row, lab in zip(np.asarray(ids), np.asarray(labels))]
    return float(np.mean(per_seq))


# -- arithmetic --------------------------------------------------------------

def layer_params(cfg):
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    d = head_dim(cfg)
    attn = h * d * (2 * cfg["num_attention_heads"]
                    + 2 * cfg["num_key_value_heads"])
    return {"attention": attn, "mlp": 3 * h * inter, "norms": 2 * h}


def param_count(cfg):
    per_layer = sum(layer_params(cfg).values())
    embeds = cfg["vocab_size"] * cfg["hidden_size"] * (
        1 if cfg["tie_word_embeddings"] else 2)
    return (cfg["num_hidden_layers"] * per_layer + embeds
            + cfg["hidden_size"])


def matmul_params(cfg):
    """Parameters a token is multiplied by: every projection and the
    output head, not the embedding table (a lookup) or the norms."""
    lp = layer_params(cfg)
    return (cfg["num_hidden_layers"] * (lp["attention"] + lp["mlp"])
            + cfg["vocab_size"] * cfg["hidden_size"])


def train_flops_per_token(cfg, seq_len):
    """Model FLOPs of forward + backward for one token of a sequence of
    ``seq_len``: 6 per matmul parameter, plus causal attention (QK^T and
    PV, 2 FLOPs a multiply-add, half the square, three times for forward
    and the two backward products each). Recomputed operations do not
    count."""
    attn_fwd = (2 * 2 * seq_len * cfg["num_attention_heads"]
                * head_dim(cfg)) / 2
    return (6 * matmul_params(cfg)
            + 3 * attn_fwd * cfg["num_hidden_layers"])


def kv_page_bytes(cfg, block_size):
    """Bytes of one page across every layer's K and V planes."""
    return (2 * cfg["num_hidden_layers"] * block_size
            * cfg["num_key_value_heads"] * head_dim(cfg)
            * DTYPE_BYTES[cfg["torch_dtype"]])


def paged_decode_cost(cfg, context_tokens, rows):
    """(FLOPs, bytes) the algorithm needs for ONE call of the paged
    decode kernel (one layer, one step): ``rows`` queries of one token
    attending to ``context_tokens`` cached tokens in all. Each cached
    token's K and V rows are read once per KV head (the group's queries
    share them); q is read and the output written once."""
    heads, kv_heads, d = (cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], head_dim(cfg))
    size = DTYPE_BYTES[cfg["torch_dtype"]]
    flops = 2 * 2 * context_tokens * heads * d
    moved = (2 * context_tokens * kv_heads * d + 2 * rows * heads * d) * size
    return flops, moved


_FLASH_MATMULS = {
    # products of [S, D] x [D, S] size over the causal half:
    "flash_fwd": 2,     # QK^T, PV
    "flash_dq": 3,      # QK^T again, dO V^T, dS K
    "flash_dkv": 4,     # QK^T again, dO V^T, P^T dO, dS^T Q
}
_FLASH_TENSORS = {
    # [B, S, H, D] tensors read or written once:
    "flash_fwd": 4,     # q, k, v, out
    "flash_dq": 5,      # q, k, v, dO, dq
    "flash_dkv": 6,     # q, k, v, dO, dk, dv
}


def flash_cost(cfg, kernel, batch, seq_len):
    """(FLOPs, bytes) of ONE call of a flash attention kernel on
    [batch, seq_len] causal self-attention. The program repeats K and V
    to the query heads before the call, so every tensor has
    ``num_attention_heads`` heads."""
    heads, d = cfg["num_attention_heads"], head_dim(cfg)
    size = DTYPE_BYTES[cfg["torch_dtype"]]
    product = 2 * batch * heads * seq_len * seq_len * d / 2
    flops = _FLASH_MATMULS[kernel] * product
    moved = _FLASH_TENSORS[kernel] * batch * seq_len * heads * d * size
    return flops, moved
