"""The family file: its arithmetic against hand counts, and its plain
reference against the program's ``LlamaForCausalLM`` at a tiny size on
the CPU (logits, loss and gradients)."""
import numpy as np
import pytest

import run as bench

PUBLISHED = dict(hidden_size=4096, intermediate_size=14336,
                 num_attention_heads=32, num_key_value_heads=8,
                 head_dim=128, vocab_size=32768,
                 tie_word_embeddings=False, torch_dtype="bfloat16")
TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, vocab_size=256,
            num_hidden_layers=2, max_position_embeddings=128,
            rms_norm_eps=1e-5, rope_theta=1e6, tie_word_embeddings=False,
            torch_dtype="float32", recompute=True)


@pytest.fixture(scope="module")
def family():
    return bench.load_module("families", "mistral")


def test_parameter_counts_by_hand(family):
    cfg = dict(PUBLISHED, num_hidden_layers=12)
    # q and o are 4096 x 4096, k and v 4096 x 1024; three 4096 x 14336
    assert family.layer_params(cfg) == {
        "attention": 2 * 4096 * 4096 + 2 * 4096 * 1024,
        "mlp": 3 * 4096 * 14336, "norms": 2 * 4096}
    per_layer = 41943040 + 176160768 + 8192
    assert family.param_count(cfg) == (12 * per_layer + 2 * 32768 * 4096
                                       + 4096) == 2885783552
    assert family.param_count(dict(cfg, num_hidden_layers=4)) == 1140887552
    assert family.param_count(dict(cfg, num_hidden_layers=32)) \
        == 7248023552        # the published model's 7.25 B


def test_train_flops_by_hand(family):
    cfg = dict(PUBLISHED, num_hidden_layers=4)
    matmul = 4 * (41943040 + 176160768) + 32768 * 4096
    assert family.matmul_params(cfg) == matmul == 1006632960
    # causal attention: QK^T and PV are each 2 * S * H * D FLOPs a token,
    # halved by the mask, three times over for forward and backward
    attention = 4 * 3 * (2 * 2 * 4096 * 32 * 128) // 2
    assert family.train_flops_per_token(cfg, 4096) \
        == 6 * matmul + attention == 6442450944


def test_kernel_costs_by_hand(family):
    cfg = dict(PUBLISHED, num_hidden_layers=12)
    assert family.kv_page_bytes(cfg, 16) == 2 * 12 * 16 * 8 * 128 * 2 \
        == 786432
    flops, moved = family.paged_decode_cost(cfg, context_tokens=50000,
                                            rows=64)
    assert flops == 2 * 2 * 50000 * 32 * 128
    # K and V rows of 8 KV heads x 128 in bf16, q in and out for 64 rows
    assert moved == 2 * 50000 * 8 * 128 * 2 + 2 * 64 * 32 * 128 * 2
    product = 2 * 2 * 32 * 4096 * 4096 * 128 // 2
    tensor = 2 * 4096 * 32 * 128 * 2
    assert family.flash_cost(cfg, "flash_fwd", 2, 4096) \
        == (2 * product, 4 * tensor)
    assert family.flash_cost(cfg, "flash_dq", 2, 4096) \
        == (3 * product, 5 * tensor)
    assert family.flash_cost(cfg, "flash_dkv", 2, 4096) \
        == (4 * product, 6 * tensor)


@pytest.fixture(scope="module")
def tiny(family):
    model = family.build_model(TINY, seed=3000000019, training=True)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, TINY["vocab_size"], (2, 33)).astype(np.int32)
    return model, ids[:, :-1], ids[:, 1:]


def test_seed_makes_the_weights(family, tiny):
    model, _, _ = tiny
    again = family.build_model(TINY, seed=3000000019, training=True)
    other = family.build_model(TINY, seed=7, training=True)
    name = "llama.layers.1.mlp.up_proj.weight"
    w = np.asarray(family.weights_of(model)[name])
    assert np.array_equal(w, np.asarray(family.weights_of(again)[name]))
    assert not np.array_equal(w, np.asarray(family.weights_of(other)[name]))


def test_reference_logits_match_the_program(family, tiny):
    import paddle_tpu as paddle

    model, ids, _ = tiny
    model.eval()
    try:
        with paddle.no_grad():
            got = np.asarray(model(paddle.to_tensor(ids))._value)
    finally:
        model.train()
    weights = family.weights_of(model)
    for row, want in zip(ids, got):
        ref = np.asarray(family.reference_logits(weights, TINY, row))
        # float32 on both sides: what differs is the order of sums
        np.testing.assert_allclose(ref, want, rtol=2e-4, atol=2e-4)


def test_reference_loss_and_gradients_match_the_program(family, tiny):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.core.dispatch import no_grad
    from paddle_tpu.core.tensor import Tensor

    model, ids, labels = tiny
    names, values = model.functional_state()
    weights = dict(zip(names, values))

    def program_loss(vals):
        with model.bind_state(names, list(vals)), no_grad():
            return model(Tensor(jnp.asarray(ids)),
                         Tensor(jnp.asarray(labels)))._value

    def reference_loss(w):
        per_seq = [family.cross_entropy(
            family.reference_logits(w, TINY, row), lab)
            for row, lab in zip(ids, labels)]
        return jnp.mean(jnp.stack(per_seq))

    got_loss, got_grads = jax.value_and_grad(program_loss)(list(values))
    ref_loss, ref_grads = jax.value_and_grad(reference_loss)(weights)
    assert float(got_loss) == pytest.approx(float(ref_loss), rel=1e-5)
    assert family.reference_loss(weights, TINY, ids, labels) \
        == pytest.approx(float(ref_loss), rel=1e-5)
    for name, got in zip(names, got_grads):
        ref = np.asarray(ref_grads[name])
        scale = float(np.abs(ref).max())
        assert scale > 0, name
        np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-3,
                                   atol=1e-4 * scale, err_msg=name)
    assert paddle.get_default_dtype() == "float32"
