"""Qwen3-Next at a tiny size on the CPU (hidden 64, 4 layers = one
period, 16 experts top-4 with 8 held here), seeded, against the plain
float32 reference in ``benchmark/families/qwen3_next.py``: the model's
logits; prefill then decode through ``serving.Engine`` and its cache
(K/V pages beside slot state) against the reference's full forward; a
slot reused; a preempted request; the flags a slot_state model refuses;
the chunked Gated DeltaNet against the recurrence; and the expert
share."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.core import flags as _flags
from paddle_tpu.kernels.gdn_chunked import gated_delta_chunked
from paddle_tpu.models import qwen3_next as qn
from paddle_tpu.serving.kv_cache import KVBlockPool
from tools.serving_parity import logits_through_cache, program_routing

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
import run as bench                                         # noqa: E402

CFG = dict(
    family="qwen3_next", vocab_size=128, hidden_size=64,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, partial_rotary_factor=0.25, rope_theta=1e7,
    rms_norm_eps=1e-6, full_attention_interval=4,
    linear_conv_kernel_dim=4, linear_key_head_dim=8,
    linear_num_key_heads=2, linear_num_value_heads=4,
    linear_value_head_dim=8, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_experts=8,
    num_experts_published=16, num_experts_per_tok=4, norm_topk_prob=True,
    max_position_embeddings=512, tie_word_embeddings=False,
    torch_dtype="float32")


@pytest.fixture(scope="module")
def family():
    return bench.load_module("families", "qwen3_next")


def _build(family, cfg, seed=7):
    """The program's model with its zero-initialised norm weights moved
    off zero, so that ``1 + w`` is exercised."""
    model = family.build_model(cfg, seed, training=False)
    rng = np.random.RandomState(seed)
    for name, p in model.named_parameters():
        if "norm" in name:
            p._value = p._value + jnp.asarray(
                0.3 * rng.randn(*p.shape), p._value.dtype)
    return model


@pytest.fixture(scope="module")
def tiny(family):
    model = _build(family, CFG)
    return model, family.weights_of(model)


def _ids(n, seed=0):
    return np.random.RandomState(seed).randint(
        0, CFG["vocab_size"], (n,)).tolist()


# -- the model against the reference -----------------------------------------

@pytest.mark.parametrize("held_from", [0, 8])
def test_model_logits_match_reference(family, held_from):
    """Whole sequences, no cache: either half of the experts."""
    cfg = dict(CFG, experts_held_from=held_from)
    model = _build(family, cfg, seed=11 + held_from)
    ids = _ids(100, seed=held_from)
    got = np.asarray(model(paddle.to_tensor([ids]))._value)[0]
    want = np.asarray(family.reference_logits(
        family.weights_of(model), cfg, ids))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_param_count_matches_the_model(family, tiny):
    _, weights = tiny
    assert family.param_count(CFG) == sum(
        int(np.prod(v.shape)) for v in weights.values())
    assert family.layer_counts(CFG) == (1, 3)


# -- the two forms of the Gated DeltaNet recurrence --------------------------

def _gdn_inputs(b, t, hk=2, hv=4, d=8, seed=0):
    rng = np.random.RandomState(seed)
    q = qn.l2_normalise(jnp.asarray(rng.randn(b, t, hk, d),
                                    jnp.float32)) * d ** -0.5
    k = qn.l2_normalise(jnp.asarray(rng.randn(b, t, hk, d), jnp.float32))
    v = jnp.asarray(rng.randn(b, t, hv, d), jnp.float32)
    g = -jnp.asarray(rng.rand(b, t, hv), jnp.float32)
    beta = jnp.asarray(rng.rand(b, t, hv), jnp.float32)
    state = jnp.asarray(rng.randn(b, hv, d, d), jnp.float32)
    return q, k, v, g, beta, state


def _recurrence(q, k, v, g, beta, state):
    outs = []
    for t in range(q.shape[1]):
        o, state = qn.gated_delta_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                       beta[:, t], state)
        outs.append(o)
    return jnp.stack(outs, 1), state


@pytest.mark.parametrize("tokens", [1, 63, 64, 65, 150])
def test_chunked_form_matches_the_recurrence(tokens):
    """One chunk short, whole, one over, and several with a remainder,
    from a state that is not zero."""
    args = _gdn_inputs(2, tokens, seed=tokens)
    o_c, s_c = gated_delta_chunked(*args)
    o_r, s_r = _recurrence(*args)
    np.testing.assert_allclose(np.asarray(o_c), np.asarray(o_r),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_r),
                               rtol=1e-4, atol=1e-5)


def test_padded_rows_leave_the_state_as_it_was():
    """g = 0 and beta = 0 on the rows past the real ones: the state is
    what the real rows alone leave."""
    q, k, v, g, beta, state = _gdn_inputs(1, 128, seed=5)
    live = (jnp.arange(128) < 75)[None, :, None]
    _, padded = gated_delta_chunked(
        q, k, v, jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0), state)
    _, real = gated_delta_chunked(q[:, :75], k[:, :75], v[:, :75],
                                  g[:, :75], beta[:, :75], state)
    np.testing.assert_allclose(np.asarray(padded), np.asarray(real),
                               rtol=1e-5, atol=1e-6)


# -- through the serving engine ----------------------------------------------

def _engine(model, **kw):
    args = dict(max_slots=2, num_blocks=64, block_size=4, max_model_len=128)
    args.update(kw)
    return serving.Engine(model, **args)


@pytest.mark.parametrize("prompt_len", [32, 21])
def test_prefill_then_decode_match_the_full_forward(family, tiny,
                                                    prompt_len):
    """A prompt that fills its bucket and one that does not: the pad
    changes nothing, and the state and pages the prefill left carry six
    decode steps to the reference's full forward."""
    model, weights = tiny
    steps = 6
    seq = _ids(prompt_len + steps, seed=prompt_len)
    got, bucket = logits_through_cache(_engine(model), seq, steps)
    assert (bucket == prompt_len) == (prompt_len == 32)
    want = np.asarray(family.reference_logits(weights, CFG, seq))[
        prompt_len - 1:]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("prompt_len", [32, 21])
def test_prefill_token_is_the_argmax_of_the_full_logits_row(tiny,
                                                            prompt_len):
    """``Engine._prefill_fn`` asks the model for the one row it reads
    (``logits_at``); ``logits_through_cache`` asks for none and keeps
    that row of the bucket's full logits. A prompt that fills its
    bucket and one that does not."""
    model, _ = tiny
    prompt = _ids(prompt_len, seed=prompt_len + 1)
    rows, _ = logits_through_cache(_engine(model), prompt + [0], 1)
    eng = _engine(model)
    rid = eng.add_request(prompt, max_new_tokens=1)
    assert eng.run()[rid] == [int(np.argmax(rows[0]))]


def test_the_program_routes_as_the_reference_does(family, tiny):
    """tools/serving_parity.py's count of differing top-k selections:
    in float32 at this size there are none."""
    model, weights = tiny
    seq = _ids(40, seed=4)
    _, theirs = family.reference_forward(weights, CFG, seq)
    mine = program_routing(model, seq)
    assert len(mine) == len(theirs) == 4
    for a, b in zip(mine, theirs):
        assert np.array_equal(np.sort(a, -1), np.sort(np.asarray(b), -1))


def _reference_greedy_ok(family, weights, prompt, generated):
    logits = np.asarray(family.reference_logits(
        weights, CFG, list(prompt) + list(generated)))
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(generated)]
    gaps = rows.max(-1) - rows[np.arange(len(generated)), generated]
    return float(gaps.max()) <= 1e-4 * float(np.abs(rows).max())


def test_engine_tokens_are_the_reference_argmax(family, tiny):
    model, weights = tiny
    eng = _engine(model, max_slots=3)
    prompts = [_ids(n, seed=n) for n in (5, 16, 27)]
    rids = [eng.add_request(p, max_new_tokens=9) for p in prompts]
    outs = eng.run()
    for p, rid in zip(prompts, rids):
        assert len(outs[rid]) == 9
        assert _reference_greedy_ok(family, weights, p, outs[rid])
    assert eng.stats()["decode_compiles"] == 1


def test_two_requests_through_one_slot_in_turn(tiny):
    """The second request's prefill resets the slot's state: its tokens
    are those of an engine that never served the first."""
    model, _ = tiny
    first, second = _ids(19, seed=1), _ids(11, seed=2)
    eng = _engine(model, max_slots=1)
    a = eng.add_request(first, max_new_tokens=7)
    b = eng.add_request(second, max_new_tokens=7)
    outs = eng.run()
    fresh = _engine(model, max_slots=1)
    c = fresh.add_request(second, max_new_tokens=7)
    assert outs[b] == fresh.run()[c]
    assert len(outs[a]) == 7


def test_preempted_request_output_identical(tiny):
    """Page exhaustion preempts a request and requeues it by recompute:
    the re-prefill rebuilds its recurrent state, and its tokens are
    those of an uncontended run."""
    model, _ = tiny
    prompts = [_ids(n, seed=n) for n in (6, 8)]
    starved = _engine(model, num_blocks=7)
    sid = [starved.add_request(p, max_new_tokens=10) for p in prompts]
    souts = starved.run()
    assert starved.stats()["preemptions"] >= 1
    roomy = _engine(model)
    rid = [roomy.add_request(p, max_new_tokens=10) for p in prompts]
    routs = roomy.run()
    assert roomy.stats()["preemptions"] == 0
    for a, b in zip(sid, rid):
        assert souts[a] == routs[b]


@pytest.mark.parametrize("flag", ["FLAGS_serving_prefix_cache",
                                  "FLAGS_serving_chunked_prefill",
                                  "FLAGS_serving_quant_kv"])
def test_flags_that_cannot_hold_a_slot_state_are_refused(tiny, flag):
    model, _ = tiny
    _flags.set_flags({flag: True})
    try:
        with pytest.raises(ValueError, match="slot_state"):
            _engine(model)
    finally:
        _flags.set_flags({flag: False})


def test_cache_holds_both_kinds_under_one_allocator(tiny):
    model, _ = tiny
    eng = _engine(model, max_slots=3, num_blocks=20)
    kinds = [spec.kind for spec in eng.cache.layers]
    assert kinds == ["slot_state"] * 3 + ["kv_pages"]
    assert isinstance(eng.cache.pools[3], KVBlockPool)
    assert eng.cache.pools[3].k.shape == (20, 4, 2, 16)
    state = eng.cache.pools[0]
    assert state["state"].shape == (3, 4, 8, 8)
    assert state["state"].dtype == jnp.float32
    assert state["conv"].shape == (3, 3, 2 * 2 * 8 + 4 * 8)
    # a slot's own index rides in the last column of its table row
    assert eng.cache.block_tables[:, -1].tolist() == [0, 1, 2]
    free = eng.cache.allocator.free_blocks
    rid = eng.add_request(_ids(9), max_new_tokens=3)
    eng.step()
    assert eng.cache.allocator.free_blocks < free       # pages only
    eng.run()
    assert eng.cache.allocator.free_blocks == free
    assert eng.cache.block_tables[:, -1].tolist() == [0, 1, 2]
    assert len(eng.output(rid)) == 3


# -- the expert share ---------------------------------------------------------

def test_two_halves_and_the_shared_expert_once_are_the_uncut_layer(family):
    """Each half routes over all 16 experts and computes its own 8; the
    two routed parts plus the shared expert, counted once, are what the
    reference gives for the whole layer with every expert held."""
    uncut = dict(CFG, num_experts=16)
    whole = qn.Qwen3NextSparseMoe(qn.Qwen3NextConfig.tiny(
        experts_held=range(16)))
    x = jnp.asarray(np.random.RandomState(3).randn(40, 64), jnp.float32)
    # the reference's layer norms the residual stream itself: give it a
    # zero norm weight (1 + 0), the program's halves the normed rows
    normed = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True)
                          + uncut["rms_norm_eps"])
    total = whole.shared(normed)
    pairs = 0
    for lo in (0, 8):
        half = qn.Qwen3NextSparseMoe(qn.Qwen3NextConfig.tiny(
            experts_held=range(lo, lo + 8)))
        half.experts.gate_weight._value = whole.experts.gate_weight._value
        half.experts.w1._value = whole.experts.w1._value[lo:lo + 8]
        half.experts.w2._value = whole.experts.w2._value[lo:lo + 8]
        total = total + half.routed(normed)
        pairs += int(half.step_stats[0])
    assert pairs == 40 * 4
    with jax.default_matmul_precision("highest"):
        h, router, _, out = family._moe_open(
            x, [jnp.zeros((64,)), whole.experts.gate_weight._value,
                whole.shared_gate_up._value, whole.shared_down._value,
                whole.shared_expert_gate._value], uncut)
        out = family._expert_group(out, h, router, whole.experts.w1._value,
                                   whole.experts.w2._value, 0, uncut)
    np.testing.assert_allclose(np.asarray(total), np.asarray(out - x),
                               rtol=2e-4, atol=2e-5)
