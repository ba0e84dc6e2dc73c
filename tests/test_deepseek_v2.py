"""DeepSeek-V2 at a tiny size on the CPU (hidden 64, a dense layer and
two sparse ones, 16 experts in 4 groups top-3 inside the 2 best groups,
one group held here), seeded, against the plain float32 reference in
``benchmark/families/deepseek_v2.py``: the model's logits; prefill then
decode through ``serving.Engine`` and its latent page cache against the
reference's full forward (absorbed = expanded); a slot reused; a
preempted request; the flags a latent_pages model refuses; the YaRN
rotary; ``Engine.stats()["latent"]``; and the expert share."""
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.core import flags as _flags
from paddle_tpu.models import deepseek_v2 as ds
from paddle_tpu.serving.kv_cache import LatentPool
from tools.serving_parity import logits_through_cache, program_routing

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
import run as bench                                         # noqa: E402
from serving_checks import serve_an_eos_mid_flight  # noqa: E402

YARN = dict(ds.YARN_V2, original_max_position_embeddings=16)
CFG = dict(
    family="deepseek_v2", vocab_size=128, hidden_size=64,
    intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=3,
    num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=4, n_routed_experts_published=16, n_shared_experts=2,
    num_experts_per_tok=3, n_group=4, topk_group=2,
    routed_scaling_factor=4.0, norm_topk_prob=False,
    first_k_dense_replace=1, rope_theta=10000, rope_scaling=YARN,
    rms_norm_eps=1e-6, max_position_embeddings=512,
    tie_word_embeddings=False, torch_dtype="float32")


@pytest.fixture(scope="module")
def family():
    return bench.load_module("families", "deepseek_v2")


def _build(family, cfg, seed=7):
    """The program's model with its unit norm weights moved off one."""
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

@pytest.mark.parametrize("held_from", [0, 12])
def test_model_logits_match_reference(family, held_from):
    """Whole sequences, no cache, heads expanded: the first and the last
    of the four expert groups."""
    cfg = dict(CFG, experts_held_from=held_from)
    model = _build(family, cfg, seed=11 + held_from)
    ids = _ids(100, seed=held_from)
    got = np.asarray(model(paddle.to_tensor([ids]))._value)[0]
    want = np.asarray(family.reference_logits(
        family.weights_of(model), cfg, ids))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("head_rows,groups", [(200, 2), (100, 4)])
def test_heads_a_group_at_a_time_give_the_same_logits(
        family, tiny, monkeypatch, head_rows, groups):
    """A long prompt expands its heads a group at a time, each group's
    share of the output projection summed as it comes: the logits are
    those of all heads at once, with a cache and without."""
    model, _ = tiny
    ids = _ids(72, seed=5)
    whole = np.asarray(model(paddle.to_tensor([ids]))._value)[0]
    through, _ = logits_through_cache(_engine(model), ids + [0], 1)
    monkeypatch.setattr(ds, "_EXPAND_HEAD_ROWS", head_rows)
    assert ds._head_groups(4, 72) == groups
    got = np.asarray(model(paddle.to_tensor([ids]))._value)[0]
    np.testing.assert_allclose(got, whole, rtol=2e-4, atol=2e-5)
    grouped, _ = logits_through_cache(_engine(model), ids + [0], 1)
    np.testing.assert_allclose(grouped, through, rtol=2e-4, atol=2e-5)


def test_head_groups_at_the_published_shapes():
    # an 8192 prefill takes 128 heads 32 at a time, a 1024 one all at once
    assert ds._head_groups(128, 8192) == 4
    assert ds._head_groups(128, 4096) == 2
    assert ds._head_groups(128, 2048) == ds._head_groups(128, 128) == 1


def test_param_count_matches_the_model(family, tiny):
    _, weights = tiny
    assert family.param_count(CFG) == sum(
        int(np.prod(v.shape)) for v in weights.values())
    assert family.layer_counts(CFG) == (1, 2)


# -- the rotary ---------------------------------------------------------------

def test_yarn_frequencies_at_the_published_settings():
    """Pair 0 turns far more than 32 times in 4096 positions and keeps
    its frequency; the last pair turns less than once and takes its
    40th; between the correction pairs the blend is linear."""
    freq = ds.yarn_inv_freq(64, 10000.0, ds.YARN_V2)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64.0)
    # pairs that turn 32 times and once: 64 ln(4096 / (2 pi n)) / (2 ln 1e4)
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    np.testing.assert_allclose(freq[:low + 1], plain[:low + 1], rtol=1e-6)
    np.testing.assert_allclose(freq[high:], plain[high:] / 40, rtol=1e-6)
    mid = (low + high) // 2
    ramp = (mid - low) / (high - low)
    assert freq[mid] == pytest.approx(
        plain[mid] * (1 - ramp) + plain[mid] / 40 * ramp, rel=1e-6)
    assert ds.yarn_mscale(40, 0.707) == pytest.approx(1.2608, abs=1e-4)
    attn = ds.DeepseekV2Attention(ds.DeepseekV2Config.tiny(
        qk_nope_head_dim=128, qk_rope_head_dim=64))
    assert attn.scale == pytest.approx(192 ** -0.5 * 1.2608 ** 2, rel=1e-4)
    assert attn.rope_mscale == 1.0


@pytest.mark.parametrize("offset", [0, 37, [3, 250]])
def test_deinterleaved_rotation_keeps_every_score(offset):
    """``rope_pairs`` moves the rotated evens before the odds, in q and k
    alike: the scores are those of turning the pairs in place."""
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(2, 5, 3, 8), jnp.float32)
    k = jnp.asarray(rng.randn(2, 5, 1, 8), jnp.float32)
    freq = ds.yarn_inv_freq(8, 10000.0, YARN)
    got = jnp.einsum("bthd,bshd->bhts", ds.rope_pairs(q, offset, freq),
                     jnp.broadcast_to(ds.rope_pairs(k, offset, freq),
                                      q.shape))
    pos = (np.asarray(offset, np.float32).reshape(-1, 1)
           + np.arange(5, dtype=np.float32)[None, :])
    angle = pos[..., None] * freq                           # [B|1, T, 4]

    def in_place(x):
        x = np.asarray(x)
        even, odd = x[..., 0::2], x[..., 1::2]
        c, s = np.cos(angle)[:, :, None], np.sin(angle)[:, :, None]
        return np.stack([even * c - odd * s, odd * c + even * s],
                        -1).reshape(x.shape)

    want = np.einsum("bthd,bshd->bhts", in_place(q),
                     np.broadcast_to(in_place(k), q.shape))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)


# -- through the serving engine ----------------------------------------------

def _engine(model, **kw):
    args = dict(max_slots=2, num_blocks=64, block_size=4, max_model_len=128)
    args.update(kw)
    return serving.Engine(model, **args)


@pytest.mark.parametrize("prompt_len", [32, 21])
def test_prefill_then_decode_match_the_full_forward(family, tiny,
                                                    prompt_len):
    """A prompt that fills its bucket and one that does not: the pad
    changes nothing, and the latent rows the expanded-head prefill left
    carry eight absorbed decode steps to the reference's full forward
    (which expands every head over the whole sequence)."""
    model, weights = tiny
    steps = 8
    seq = _ids(prompt_len + steps, seed=prompt_len)
    got, bucket = logits_through_cache(_engine(model), seq, steps)
    assert (bucket == prompt_len) == (prompt_len == 32)
    want = np.asarray(family.reference_logits(weights, CFG, seq))[
        prompt_len - 1:]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("block,fits", [(32, True), (8, False)])
def test_a_prefill_that_lays_out_a_block_matches_the_reference(
        family, tiny, monkeypatch, block, fits):
    """The tiny model's prefill bucket has 32 x 3 pairs, a quarter of
    them on the four experts held here: a block of 32 rows holds them
    and is all the expert layers lay out, a block of 8 does not and
    they lay out every pair; the logits through the cache are the
    reference's either way, a decode step (2 slots x 3 pairs) lays out
    its pairs as ever, and the engine's ring says how much the prefill
    laid out."""
    from paddle_tpu.parallel import moe

    model, weights = tiny
    steps = 4
    seq = _ids(32 + steps, seed=3)
    monkeypatch.setattr(moe, "_pair_block", lambda *shapes: block)
    got, _ = logits_through_cache(_engine(model), seq, steps)
    want = np.asarray(family.reference_logits(weights, CFG, seq))[31:]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    eng = _engine(model)
    eng.add_request(seq[:32], max_new_tokens=steps)
    eng.run()
    stats = eng.stats()["moe"]
    (prefill,) = [c for c in stats["calls"] if not c[2]]
    pairs = prefill[3]
    assert all(p <= block for p in pairs) == fits
    assert stats["prefill_rows_over_pairs"] == pytest.approx(np.mean(
        [(block if p <= block else 96) / max(p, 1) for p in pairs]))
    assert all(len(c) == 5 for c in stats["calls"])


def test_absorbed_decode_is_the_models_own_expanded_forward(tiny):
    """The same weights both ways inside the program: a decode row
    through the cache against the plain forward's row."""
    model, _ = tiny
    seq = _ids(27, seed=9)
    got, _ = logits_through_cache(_engine(model), seq, 5)
    want = np.asarray(model(paddle.to_tensor([seq]))._value)[0, 21:]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_the_program_routes_as_the_reference_does(family, tiny):
    """tools/serving_parity.py's count of differing top-k selections:
    in float32 at this size there are none, group limit and all."""
    model, weights = tiny
    seq = _ids(40, seed=4)
    _, theirs = family.reference_forward(weights, CFG, seq)
    mine = program_routing(model, seq)
    assert len(mine) == len(theirs) == 2
    for a, b in zip(mine, theirs):
        assert a.shape == (40, 3)
        assert np.array_equal(np.sort(a, -1), np.sort(np.asarray(b), -1))
        # a token's experts lie in at most topk_group = 2 groups of 4
        assert max(len(set(row // 4)) for row in a) <= 2


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


def test_an_eos_mid_flight_is_discarded_and_its_slot_reused(family, tiny):
    """The engine runs a step ahead: a request that finishes by EOS has
    a row in the step already in flight, which is thrown away, and the
    next owner of its slot (latent pages) starts clean."""
    model, weights = tiny
    prompts = [_ids(n, seed=n) for n in (5, 7, 6)]
    for p, out in serve_an_eos_mid_flight(_engine(model), prompts, 8):
        assert _reference_greedy_ok(family, weights, p, out)


def test_two_requests_through_one_slot_in_turn(tiny):
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
    the re-prefill writes its latent rows into new pages, and its tokens
    are those of an uncontended run."""
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
def test_flags_that_cannot_hold_latent_pages_are_refused(tiny, flag):
    model, _ = tiny
    _flags.set_flags({flag: True})
    try:
        with pytest.raises(ValueError, match="latent_pages"):
            _engine(model)
    finally:
        _flags.set_flags({flag: False})


def test_cache_spec_and_latent_stats(tiny):
    """One latent plane a layer, rows padded to whole 128-lane tiles,
    pages from the one allocator; ``stats()["latent"]`` says what the
    pool is and how many tokens the decode steps found cached."""
    model, _ = tiny
    eng = _engine(model, max_slots=3, num_blocks=20)
    assert [spec.kind for spec in eng.cache.layers] == ["latent_pages"] * 3
    assert eng.cache.layers[0].width == 32 + 8
    assert all(isinstance(p, LatentPool) for p in eng.cache.pools)
    assert eng.cache.pools[0].rows.shape == (20, 4, 128)
    assert eng.stats()["latent"] == {
        "layers": 3, "row_bytes": 128 * 4, "pool_bytes": 3 * 20 * 4 * 128 * 4,
        "cached_tokens": None}
    assert eng.stats()["state"] is None
    free = eng.cache.allocator.free_blocks
    rid = eng.add_request(_ids(9), max_new_tokens=4)
    eng.step()
    assert eng.cache.allocator.free_blocks == free - 3      # 9 + 1 tokens
    eng.run()
    assert eng.cache.allocator.free_blocks == free
    assert len(eng.output(rid)) == 4
    # three decode steps found 9, 10 and 11 tokens cached
    assert eng.stats()["latent"]["cached_tokens"] == pytest.approx(10.0)
    moe = eng.stats()["moe"]
    assert moe["layers"] == 2 and moe["experts_held"] == 4


# -- the expert share ---------------------------------------------------------

def test_the_groups_and_the_shared_experts_once_are_the_uncut_layer(family):
    """Each of the four shares routes over all 16 experts (group-limited)
    and computes its own group of 4; the four routed parts plus the
    shared experts, counted once, are what the reference gives for the
    whole layer with every expert held."""
    uncut = dict(CFG, n_routed_experts=16)
    whole = ds.DeepseekV2MoE(ds.DeepseekV2Config.tiny(
        num_attention_heads=4, experts_held=range(16)))
    x = jnp.asarray(np.random.RandomState(3).randn(40, 64), jnp.float32)
    # the reference's layer norms the residual stream itself: give it a
    # unit norm weight, the program's shares the normed rows
    normed = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True)
                          + uncut["rms_norm_eps"])
    total = whole.shared(normed)
    pairs = 0
    for lo in (0, 4, 8, 12):
        share = ds.DeepseekV2MoE(ds.DeepseekV2Config.tiny(
            num_attention_heads=4, experts_held=range(lo, lo + 4)))
        share.experts.gate_weight._value = whole.experts.gate_weight._value
        share.experts.w1._value = whole.experts.w1._value[lo:lo + 4]
        share.experts.w2._value = whole.experts.w2._value[lo:lo + 4]
        total = total + share.routed(normed)
        pairs += int(share.step_stats[0])
    assert pairs == 40 * 3
    with jax.default_matmul_precision("highest"):
        h, router, _, out = family._moe_open(
            x, [jnp.ones((64,)), whole.experts.gate_weight._value,
                whole.shared_gate_up._value, whole.shared_down._value],
            uncut)
        # every expert on every token, four at a time (the reference
        # takes whole groups of at most EXPERT_GROUP = 5)
        for lo in (0, 4, 8, 12):
            out = family._expert_group(
                out, h, router, whole.experts.w1._value[lo:lo + 4],
                whole.experts.w2._value[lo:lo + 4], 0,
                dict(uncut, experts_held_from=lo))
    np.testing.assert_allclose(np.asarray(total), np.asarray(out - x),
                               rtol=2e-4, atol=2e-5)
