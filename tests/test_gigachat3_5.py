"""GigaChat3.5 at a tiny size on the CPU (hidden 64, the published layers
0 and 3-6: a Gated DeltaNet layer over a dense MLP, then latent
attention and three Gated DeltaNet layers over 16 experts top-4 with 8
held here), seeded, against the plain float32 reference in
``benchmark/families/gigachat3_5.py``: the model's logits; prefill then
decode through ``serving.Engine`` with a slot that holds Gated DeltaNet
state and latent pages at once; the slot released, reused and preempted;
what the comparison would catch; and the expert share."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.core import flags as _flags
from paddle_tpu.models import gigachat3_5 as gc
from paddle_tpu.serving.kv_cache import LatentPool
from tools.serving_parity import logits_through_cache, program_routing

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
import run as bench                                         # noqa: E402

CFG = dict(
    family="gigachat3_5", vocab_size=128, hidden_size=64,
    intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=5,
    layers_held=[0, 3, 4, 5, 6], full_attention_layers=[3, 7, 11],
    first_k_dense_replace=3, num_attention_heads=8, q_lora_rank=48,
    kv_lora_rank=128, qk_nope_head_dim=32, qk_rope_head_dim=16,
    v_head_dim=32, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=8,
    linear_conv_kernel_dim=4, linear_sigmoid_gate_scale=2,
    linear_attn_o_norm_eps=1e-6, layernorm_gating_weight=2,
    n_routed_experts=8, n_routed_experts_published=16, n_shared_experts=1,
    num_experts_per_tok=4, routed_scaling_factor=2.5, norm_topk_prob=True,
    swiglu_limit=10, rope_theta=100000,
    rope_scaling=dict(gc.YARN_GIGACHAT35), rms_norm_eps=1e-6,
    max_position_embeddings=512, tie_word_embeddings=False,
    torch_dtype="float32", linear_attention_layers=4,
    latent_attention_layers=1)
# float32 on both sides: what differs is the order of sums, a few units
# of 2^-24 a layer; a part of the block left out moves the logits by a
# tenth of their size or more (test_what_the_comparison_catches)
RTOL, ATOL = 2e-4, 2e-5


@pytest.fixture(scope="module")
def family():
    return bench.load_module("families", "gigachat3_5")


def _build(family, cfg, seed=7):
    """The program's model with its zero-initialised norm and gate
    weights moved off zero, so that ``1 + w`` and ``2 sigmoid(gamma)``
    are exercised."""
    model = family.build_model(cfg, seed, training=False)
    rng = np.random.RandomState(seed)
    for name, p in model.named_parameters():
        if "norm" in name or name.endswith("_gate"):
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


def _logits(model, ids):
    return np.asarray(model(paddle.to_tensor([ids]))._value)[0]


# -- the model against the reference -----------------------------------------

@pytest.mark.parametrize("held_from", [0, 8])
def test_model_logits_match_reference(family, held_from):
    """Whole sequences, no cache: either half of the experts."""
    cfg = dict(CFG, experts_held_from=held_from)
    model = _build(family, cfg, seed=11 + held_from)
    ids = _ids(100, seed=held_from)
    want = np.asarray(family.reference_logits(
        family.weights_of(model), cfg, ids))
    np.testing.assert_allclose(_logits(model, ids), want, rtol=RTOL,
                               atol=ATOL)


def test_param_count_matches_the_model(family, tiny):
    _, weights = tiny
    assert family.param_count(CFG) == sum(
        int(np.prod(v.shape)) for v in weights.values())
    assert family.layer_counts(CFG) == {"mla": 1, "gdn": 4, "dense": 1,
                                        "moe": 4}


@pytest.mark.parametrize("leave_out", [
    {"gated_attention": False},
    {"layernorm_type": "pre"},
    {"linear_sigmoid_gate_scale": None}])
def test_what_the_comparison_catches(family, tiny, leave_out):
    """The MLA output gate, the sandwich's post-norms and the Gated
    DeltaNet's ``2 sigmoid(z)`` gate each change the logits by far more
    than the comparison's tolerance: the reference with one left out is
    at least a hundred times ``ATOL + RTOL |want|`` from the model."""
    model, weights = tiny
    ids = _ids(60, seed=9)
    got = _logits(model, ids)
    want = np.asarray(family.reference_logits(weights, dict(CFG, **leave_out),
                                              ids))
    assert (np.abs(got - want) - RTOL * np.abs(want)).max() > 100 * ATOL
    assert np.abs(got - want).max() > 0.01 * np.abs(want).max()


# -- through the serving engine ----------------------------------------------

def _engine(model, **kw):
    args = dict(max_slots=2, num_blocks=64, block_size=4, max_model_len=128)
    args.update(kw)
    return serving.Engine(model, **args)


@pytest.mark.parametrize("prompt_len", [32, 21])
def test_prefill_then_decode_match_the_full_forward(family, tiny,
                                                    prompt_len):
    """A prompt that fills its bucket and one that does not: the pad
    changes nothing, and the state, tails and latent rows the prefill
    left carry eight decode steps (the absorbed latent attention) to the
    reference's full forward."""
    model, weights = tiny
    steps = 8
    seq = _ids(prompt_len + steps, seed=prompt_len)
    got, bucket = logits_through_cache(_engine(model), seq, steps)
    assert (bucket == prompt_len) == (prompt_len == 32)
    want = np.asarray(family.reference_logits(weights, CFG, seq))[
        prompt_len - 1:]
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


def test_the_program_routes_as_the_reference_does(family, tiny):
    """tools/serving_parity.py's count of differing top-k selections,
    the selection bias read: in float32 at this size there are none."""
    model, weights = tiny
    seq = _ids(40, seed=4)
    _, theirs = family.reference_forward(weights, CFG, seq)
    mine = program_routing(model, seq)
    assert len(mine) == len(theirs) == 4
    for a, b in zip(mine, theirs):
        assert np.array_equal(np.sort(a, -1), np.sort(np.asarray(b), -1))


def test_engine_tokens_are_the_reference_argmax(family, tiny):
    """Three requests decoding side by side; every token is the
    reference's argmax or within 1e-4 of the largest |logit| of it (the
    float32 rounding above, far below the gap of a wrong path)."""
    model, weights = tiny
    eng = _engine(model, max_slots=3)
    prompts = [_ids(n, seed=n) for n in (5, 16, 27)]
    rids = [eng.add_request(p, max_new_tokens=9) for p in prompts]
    outs = eng.run()
    for p, rid in zip(prompts, rids):
        logits = np.asarray(family.reference_logits(
            weights, CFG, p + outs[rid]))
        rows = logits[len(p) - 1:len(p) - 1 + 9]
        gaps = rows.max(-1) - rows[np.arange(9), outs[rid]]
        assert gaps.max() <= 1e-4 * np.abs(rows).max()
    stats = eng.stats()
    assert stats["decode_compiles"] == 1
    assert stats["moe"]["layers"] == 4 and stats["moe"]["experts_held"] == 8
    assert stats["state"]["layers"] == 4
    assert stats["state"]["slot_bytes"] == family.state_slot_bytes(CFG)
    assert stats["latent"]["layers"] == 1
    assert stats["latent"]["row_bytes"] == 256 * 4      # 144 values, 2 tiles
    assert stats["ssm"] is None


def test_one_slot_holds_state_and_latent_pages(family, tiny):
    """A slot's Gated DeltaNet state and convolution tail (four layers)
    and its latent pages (one layer) live side by side: pages come from
    the one allocator and go back on release, the slot's own index rides
    in the last column of its table row, and the state takes nothing
    from the allocator."""
    model, _ = tiny
    eng = _engine(model, max_slots=3, num_blocks=20)
    kinds = [spec.kind for spec in eng.cache.layers]
    assert kinds == ["slot_state", "latent_pages"] + ["slot_state"] * 3
    assert isinstance(eng.cache.pools[1], LatentPool)
    assert eng.cache.pools[1].rows.shape == (20, 4, 256)
    state = eng.cache.pools[0]
    assert state["state"].shape == (3, 4, 8, 8)
    assert state["state"].dtype == jnp.float32
    assert state["conv"].shape == (3, 3, 2 * 2 * 8 + 4 * 8)
    assert eng.cache.block_tables.shape == (3, 32 + 1)
    assert eng.cache.block_tables[:, -1].tolist() == [0, 1, 2]
    assert family.kv_page_bytes(CFG, 4) == 4 * 256 * 4
    free = eng.cache.allocator.free_blocks
    rid = eng.add_request(_ids(9), max_new_tokens=3)
    eng.step()
    assert eng.cache.allocator.free_blocks == free - 3      # 10 tokens
    eng.run()
    assert eng.cache.allocator.free_blocks == free
    assert eng.cache.block_tables[:, -1].tolist() == [0, 1, 2]
    assert len(eng.output(rid)) == 3


def test_a_slot_taken_after_another_left_it_starts_from_zero(tiny):
    """The second request's prefill resets the slot's state and tails
    and writes its own latent rows: its tokens are those of an engine
    that never served the first."""
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
    the re-prefill rebuilds its recurrent state and rewrites its latent
    rows, and its tokens are those of an uncontended run."""
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
def test_flags_that_cannot_hold_either_kind_are_refused(tiny, flag):
    model, _ = tiny
    _flags.set_flags({flag: True})
    try:
        with pytest.raises(ValueError, match="slot_state"):
            _engine(model)
    finally:
        _flags.set_flags({flag: False})


# -- the expert share ---------------------------------------------------------

def test_two_halves_and_the_shared_expert_once_are_the_uncut_layer(family):
    """Each half routes over all 16 experts (sigmoid scores, the
    selection bias) and computes its own 8; the two routed parts plus the
    shared expert, counted once, are what the reference gives for the
    whole layer with every expert held. The experts' and the shared
    expert's first matrices are scaled up so that the clamp of the
    SwiGLU bites, in program and reference alike."""
    uncut = dict(CFG, n_routed_experts=16)
    paddle.seed(3)
    whole = gc.GigaChat35MoE(gc.GigaChat35Config.tiny(experts_held=range(16)))
    rows = jnp.asarray(np.random.RandomState(3).randn(40, 64), jnp.float32)

    def hidden():
        return jnp.einsum("td,edf->etf", rows, whole.experts.w1._value)

    # gate and up at a standard deviation of 12: a third of them past 10
    scale = 12.0 / float(jnp.std(hidden()))
    whole.experts.w1._value = whole.experts.w1._value * scale
    whole.shared_gate_up._value = whole.shared_gate_up._value * scale
    assert float(jnp.mean(jnp.abs(hidden()) > 10.0)) > 0.3
    total = whole.shared(rows)
    pairs = 0
    for lo in (0, 8):
        half = gc.GigaChat35MoE(gc.GigaChat35Config.tiny(
            experts_held=range(lo, lo + 8)))
        half.experts.gate_weight._value = whole.experts.gate_weight._value
        half.e_score_correction_bias._value = \
            whole.e_score_correction_bias._value
        half.experts.w1._value = whole.experts.w1._value[lo:lo + 8]
        half.experts.w2._value = whole.experts.w2._value[lo:lo + 8]
        total = total + half.routed(rows)
        pairs += int(half.step_stats[0])
    assert pairs == 40 * 4
    with jax.default_matmul_precision("highest"):
        router, _, out = family._moe_open(
            rows, [whole.experts.gate_weight._value,
                   whole.e_score_correction_bias._value,
                   whole.shared_gate_up._value, whole.shared_down._value],
            uncut)
        for start in range(0, 16, family.EXPERT_GROUP):
            out = family._expert_group(
                out, rows, router, whole.experts.w1._value,
                whole.experts.w2._value, start, uncut)
    # float32 sums of 16 experts at scaled-up weights: a little more
    # rounding than the logits tests', relative to the largest output
    np.testing.assert_allclose(np.asarray(total), np.asarray(out),
                               rtol=2e-4, atol=2e-4 * float(
                                   jnp.abs(out).max()))


def test_balance_router_bias_moves_every_expert_layers_bias_only(tiny):
    """One forward, every expert layer's input caught: the four expert
    layers' biases move and nothing else does."""
    model, _ = tiny
    paddle.seed(1)
    fresh = gc.GigaChat35ForCausalLM(model.config)
    fresh.eval()
    names, before = fresh.functional_state()
    before = [np.asarray(v) for v in before]
    fresh.balance_router_bias(np.asarray([_ids(96, seed=3)], np.int32),
                              rounds=50)
    changed = [n for n, a, b in zip(names, before,
                                    fresh.functional_state()[1])
               if not np.array_equal(a, np.asarray(b))]
    assert changed == ["model.layers.%d.mlp.e_score_correction_bias" % j
                       for j in range(1, 5)]


# -- the shared classes keep their computation --------------------------------

def test_the_options_default_to_what_the_other_models_compute():
    """``Qwen3NextGatedDeltaNet``, ``DeepseekV2Attention`` and the expert
    layer take the new options only when told: without them no gate
    projection is built and the Gated DeltaNet's output norm keeps its
    plain weight of one."""
    from paddle_tpu.models import deepseek_v2, qwen3_next

    ds = deepseek_v2.DeepseekV2ForCausalLM(deepseek_v2.DeepseekV2Config.tiny())
    assert not any("gate_proj" in n for n in ds.functional_state()[0])
    qn = qwen3_next.Qwen3NextForCausalLM(qwen3_next.Qwen3NextConfig.tiny())
    gdn = qn.model.layers[0].linear_attn
    assert gdn.gate_scale is None
    assert np.all(np.asarray(gdn.norm_weight._value) == 1.0)
    mine = gc.GigaChat35ForCausalLM(gc.GigaChat35Config.tiny())
    assert mine.model.layers[0].linear_attn.gate_scale == 2.0
    assert np.all(np.asarray(
        mine.model.layers[0].linear_attn.norm_weight._value) == 0.0)


def test_a_prefills_padding_sends_no_pair_to_the_experts(tiny):
    """Rows past a prefill's real ones are routed but not computed: the
    real rows' routed share is what it is without the padding, a padded
    row's is 0, and the counters count the real rows' pairs only."""
    from paddle_tpu.parallel.moe import moe_forward

    model, _ = tiny
    moe = model.model.layers[1].mlp
    rows = jnp.asarray(np.random.RandomState(5).randn(32, 64), jnp.float32)
    e = moe.experts
    kw = dict(top_k=e.top_k, lo=0, activation="silu", gated=True,
              norm_topk_prob=True, routed_scaling_factor=2.5,
              select_bias=moe.e_score_correction_bias._value,
              swiglu_limit=10.0)
    args = (e.gate_weight._value, e.w1._value, None, e.w2._value, None)
    whole, _, every = moe_forward(rows, *args, **kw)
    part, _, real = moe_forward(rows, *args, row_mask=jnp.arange(32) < 21,
                                **kw)
    alone, _, first = moe_forward(rows[:21], *args, **kw)
    np.testing.assert_allclose(np.asarray(part[:21]), np.asarray(alone),
                               rtol=1e-6, atol=1e-6)
    assert not np.asarray(part[21:]).any() and np.asarray(whole[21:]).any()
    assert int(real[0]) == int(first[0]) < int(every[0])
    # through the engine: a 21-token prompt in a 32-row bucket
    eng = _engine(model)
    eng.add_request(_ids(21, seed=6), max_new_tokens=2)
    eng.run()
    prefill, decode = eng.stats()["moe"]["calls"]
    assert prefill[:3] == [21, 32, 0] and decode[2] == 1
    assert len(prefill[3]) == 4 and max(prefill[3]) <= 21 * 4
