"""Nemotron-H at a tiny size on the CPU (hidden 64, pattern ``MEM*E``, 4
Mamba heads x 8 with a state of 16 in 2 groups, 8 experts top-3 with 4
held here), seeded, against the plain float32 reference in
``benchmark/families/nemotron_h.py``: the model's logits; the chunked
Mamba-2 form against the token-by-token recurrence, with a padded
bucket; the decode step against one step of the recurrence; prefill
then decode through ``serving.Engine`` and its cache (slot state, K/V
pages and layers that keep nothing) against the reference's full
forward; a slot reused; and the expert share."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.core import flags as _flags
from paddle_tpu.models import nemotron_h as nh
from paddle_tpu.serving.kernels.ssm import ssm_decode_reference
from paddle_tpu.serving.kv_cache import KVBlockPool, StateDecodeView
from tools.serving_parity import logits_through_cache, program_routing

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
import run as bench                                         # noqa: E402
from serving_checks import serve_an_eos_mid_flight  # noqa: E402

CFG = dict(
    family="nemotron_h", vocab_size=128, hidden_size=64,
    hybrid_override_pattern="MEM*E", num_hidden_layers=5,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16, n_groups=2,
    conv_kernel=4, chunk_size=8, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=1e-4, n_routed_experts=4,
    n_routed_experts_published=8, num_experts_per_tok=3,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
    routed_scaling_factor=2.5, norm_topk_prob=True,
    layer_norm_epsilon=1e-5, max_position_embeddings=512,
    tie_word_embeddings=False, torch_dtype="float32")
H, P, G, N = 4, 8, 2, 16


@pytest.fixture(scope="module")
def family():
    return bench.load_module("families", "nemotron_h")


def _build(family, cfg, seed=7):
    """The program's model with its unit norm weights and D moved off
    one, so that each is exercised."""
    model = family.build_model(cfg, seed, training=False)
    rng = np.random.RandomState(seed)
    for name, p in model.named_parameters():
        if "norm" in name or name.endswith(".D"):
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

@pytest.mark.parametrize("held_from", [0, 4])
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
    assert family.layer_counts(CFG) == {"M": 2, "E": 2, "*": 1}


@pytest.mark.parametrize("leave_out", ["mixer.conv_bias",
                                       "mixer.e_score_correction_bias"])
def test_a_bias_left_out_fails_the_comparison(family, tiny, leave_out):
    """Both biases are seeded off zero so that a program without one
    would not pass: the reference with that bias zeroed is ten times
    the comparison's tolerance from the model, or more."""
    model, weights = tiny
    ids = _ids(60, seed=9)
    got = np.asarray(model(paddle.to_tensor([ids]))._value)[0]
    without = {k: jnp.zeros_like(v) if k.endswith(leave_out) else v
               for k, v in weights.items()}
    want = np.asarray(family.reference_logits(without, CFG, ids))
    assert np.abs(got - want).max() > 2e-3 * np.abs(want).max()


# -- the two forms of the Mamba-2 recurrence ---------------------------------

def _ssm_inputs(b, t, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(b, t, H, P), jnp.float32)
    dt = jnp.asarray(jax.nn.softplus(rng.randn(b, t, H)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, H), jnp.float32)
    bb = jnp.asarray(rng.randn(b, t, G, N), jnp.float32)
    cc = jnp.asarray(rng.randn(b, t, G, N), jnp.float32)
    state = jnp.asarray(rng.randn(b, G, N, H // G * P), jnp.float32)
    return x, dt, a, bb, cc, state


def _recurrence(x, dt, a, b, c, state):
    """Token by token, written out: S [B, H, P, N] a head, head h
    reading group h // (H / G)."""
    bsz, t = x.shape[:2]
    rep = H // G
    s = np.asarray(state, np.float64).reshape(bsz, G, N, rep, P)
    s = s.transpose(0, 1, 3, 4, 2).reshape(bsz, H, P, N)
    x, dt, a = (np.asarray(v, np.float64) for v in (x, dt, a))
    b = np.repeat(np.asarray(b, np.float64), rep, axis=2)
    c = np.repeat(np.asarray(c, np.float64), rep, axis=2)
    ys = []
    for i in range(t):
        decay = np.exp(dt[:, i] * a)[..., None, None]
        s = decay * s + ((dt[:, i, :, None] * x[:, i])[..., None]
                         * b[:, i, :, None, :])
        ys.append(np.einsum("bhpn,bhn->bhp", s, c[:, i]))
    s = s.reshape(bsz, G, rep, P, N).transpose(0, 1, 4, 2, 3)
    return np.stack(ys, 1), s.reshape(bsz, G, N, rep * P)


@pytest.mark.parametrize("tokens", [1, 7, 8, 9, 30])
def test_chunked_form_matches_the_recurrence(tokens):
    """One chunk short, whole, one over, and several with a remainder,
    from a state that is not zero."""
    args = _ssm_inputs(2, tokens, seed=tokens)
    y_c, s_c = nh.ssd_chunked(*args, chunk=8)
    y_r, s_r = _recurrence(*args)
    np.testing.assert_allclose(np.asarray(y_c), y_r, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_c), s_r, rtol=1e-4, atol=1e-4)


def test_padded_rows_leave_the_state_as_it_was():
    """dt = 0 on the rows past the real ones, the last real row in the
    middle of a chunk: the state is what the real rows alone leave."""
    x, dt, a, b, c, state = _ssm_inputs(1, 32, seed=5)
    live = (jnp.arange(32) < 21)[None, :, None]
    _, padded = nh.ssd_chunked(x, jnp.where(live, dt, 0.0), a, b, c, state,
                               chunk=8)
    _, real = nh.ssd_chunked(x[:, :21], dt[:, :21], a, b[:, :21],
                             c[:, :21], state, chunk=8)
    np.testing.assert_allclose(np.asarray(padded), np.asarray(real),
                               rtol=1e-5, atol=1e-6)


def _mixer_prefill(mixer, x, valid_len):
    """(output, the arrays the layer handed its hook) of the layer over
    ``x`` [B, T, hidden] from a zero state, ``valid_len`` rows real."""
    kept = {}

    class Hook(nh._NoCache):
        def write(self, arrays):
            kept.update(arrays)
            return self

    zeros = {name: jnp.zeros((x.shape[0],) + shape, dtype)
             for name, shape, dtype in mixer.state_spec("float32")}
    out, _ = mixer(x, Hook(zeros, valid_len))
    return np.asarray(out), kept


def test_padded_bucket_leaves_state_tail_and_rows(tiny):
    """The layer itself over a right-padded bucket, ``valid_len`` in the
    middle of a chunk: state, convolution tail and the real rows' output
    are those of the real rows alone."""
    model, _ = tiny
    mixer = model.backbone.layers[0].mixer
    x = jnp.asarray(np.random.RandomState(2).randn(1, 32, 64), jnp.float32)
    padded, kept_padded = _mixer_prefill(mixer, x, 21)
    real, kept_real = _mixer_prefill(mixer, x[:, :21], 21)
    np.testing.assert_allclose(padded[:, :21], real, rtol=1e-5, atol=1e-6)
    assert kept_real["conv"].shape == (1, 3, mixer.conv_dim)
    for name in ("state", "conv"):
        np.testing.assert_allclose(np.asarray(kept_padded[name]),
                                   np.asarray(kept_real[name]),
                                   rtol=1e-5, atol=1e-6)


def test_decode_step_is_one_step_of_the_recurrence_and_idle_rows_stay(tiny):
    """Through the layer and the cache's decode hook: eleven rows by the
    chunked form, then one token a slot; slot 1 is idle and its state
    and tail stay bit for bit."""
    model, _ = tiny
    mixer = model.backbone.layers[0].mixer
    x = jnp.asarray(np.random.RandomState(4).randn(3, 12, 64), jnp.float32)
    whole, after_12 = _mixer_prefill(mixer, x, 12)
    _, pool = _mixer_prefill(mixer, x[:, :11], 11)
    active = jnp.asarray([True, False, True])
    out, view = mixer(x[:, 11:12], StateDecodeView(pool, active))
    on = np.asarray(active)
    np.testing.assert_allclose(np.asarray(out)[on], whole[on, 11:12],
                               rtol=1e-4, atol=1e-5)
    for name in ("state", "conv"):
        np.testing.assert_allclose(
            np.asarray(view.pool[name])[on], np.asarray(after_12[name])[on],
            rtol=1e-4, atol=1e-5)
        assert np.array_equal(np.asarray(view.pool[name])[1],
                              np.asarray(pool[name])[1])


def test_ssm_decode_twin_is_the_recurrence():
    x, dt, a, b, c, state = _ssm_inputs(3, 1, seed=8)
    d = jnp.asarray([0.5, 1.0, 1.5, 2.0], jnp.float32)
    y, new = ssm_decode_reference(x[:, 0], dt[:, 0], a, d, b[:, 0],
                                  c[:, 0], jnp.ones((3,), bool), state)
    y_r, s_r = _recurrence(x, dt, a, b, c, state)
    np.testing.assert_allclose(
        np.asarray(y), y_r[:, 0] + np.asarray(d)[:, None] * np.asarray(
            x[:, 0]), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new), s_r, rtol=1e-5, atol=1e-5)


# -- through the serving engine ----------------------------------------------

def _engine(model, **kw):
    args = dict(max_slots=2, num_blocks=64, block_size=4, max_model_len=128)
    args.update(kw)
    return serving.Engine(model, **args)


@pytest.mark.parametrize("prompt_len", [32, 21])
def test_prefill_then_decode_match_the_full_forward(family, tiny,
                                                    prompt_len):
    """A prompt that fills its bucket and one that does not: the pad
    changes nothing, and the state, tail and pages the prefill left
    carry eight decode steps to the reference's full forward."""
    model, weights = tiny
    steps = 8
    seq = _ids(prompt_len + steps, seed=prompt_len)
    got, bucket = logits_through_cache(_engine(model), seq, steps)
    assert (bucket == prompt_len) == (prompt_len == 32)
    want = np.asarray(family.reference_logits(weights, CFG, seq))[
        prompt_len - 1:]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_the_program_routes_as_the_reference_does(family, tiny):
    """tools/serving_parity.py's count of differing top-k selections:
    in float32 at this size there are none."""
    model, weights = tiny
    seq = _ids(40, seed=4)
    _, theirs = family.reference_forward(weights, CFG, seq)
    mine = program_routing(model, seq)
    assert len(mine) == len(theirs) == 2
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
    stats = eng.stats()
    assert stats["decode_compiles"] == 1
    assert stats["ssm"]["layers"] == 2
    assert stats["ssm"]["state_bytes_slot"] == family.state_slot_bytes(CFG)
    assert 1.0 <= stats["ssm"]["active_slots"] <= 3.0
    assert stats["moe"]["layers"] == 2


def test_an_eos_mid_flight_is_discarded_and_its_slot_reused(family, tiny):
    """The engine runs a step ahead: a request that finishes by EOS has
    a row in the step already in flight, which is thrown away, and the
    next owner of its slot (slot state) starts clean."""
    model, weights = tiny
    prompts = [_ids(n, seed=n) for n in (5, 7, 6)]
    for p, out in serve_an_eos_mid_flight(_engine(model), prompts, 8):
        assert _reference_greedy_ok(family, weights, p, out)


def test_a_slot_taken_after_another_left_it_starts_from_zero_state(tiny):
    """The second request's prefill resets the slot's state and tail:
    its tokens are those of an engine that never served the first."""
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
    model, _ = tiny
    prompts = [_ids(n, seed=n) for n in (6, 8)]
    starved = _engine(model, num_blocks=7)
    sid = [starved.add_request(p, max_new_tokens=10) for p in prompts]
    souts = starved.run()
    assert starved.stats()["preemptions"] >= 1
    roomy = _engine(model)
    rid = [roomy.add_request(p, max_new_tokens=10) for p in prompts]
    routs = roomy.run()
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


def test_cache_holds_three_kinds_under_one_allocator(tiny):
    model, _ = tiny
    eng = _engine(model, max_slots=3, num_blocks=20)
    kinds = [spec.kind for spec in eng.cache.layers]
    assert kinds == ["slot_state", "nothing", "slot_state", "kv_pages",
                     "nothing"]
    assert eng.cache.pools[1] is None and eng.cache.pools[4] is None
    assert isinstance(eng.cache.pools[3], KVBlockPool)
    assert eng.cache.pools[3].k.shape == (20, 4, 2, 16)
    state = eng.cache.pools[0]
    assert state["state"].shape == (3, G, N, H // G * P)
    assert state["state"].dtype == jnp.float32
    assert state["conv"].shape == (3, 3, H * P + 2 * G * N)
    assert eng.stats()["state"]["layers"] == 2
    # page bytes a token count the one paged layer only
    assert eng._quant_page_bytes == 2 * 4 * 2 * 16
    free = eng.cache.allocator.free_blocks
    rid = eng.add_request(_ids(9), max_new_tokens=3)
    eng.step()
    assert eng.cache.allocator.free_blocks < free       # pages only
    eng.run()
    assert eng.cache.allocator.free_blocks == free
    assert len(eng.output(rid)) == 3


# -- the expert share ---------------------------------------------------------

def test_two_halves_and_the_shared_expert_once_are_the_uncut_layer(family):
    """Each half routes over all 8 experts and computes its own 4; the
    two routed parts plus the shared expert, counted once, are what the
    reference gives for the whole layer with every expert held."""
    uncut = dict(CFG, n_routed_experts=8)
    whole = nh.NemotronHMoE(nh.NemotronHConfig.tiny(experts_held=range(8)))
    x = jnp.asarray(np.random.RandomState(3).randn(40, 64), jnp.float32)
    # the reference's layer norms the residual stream itself: give it a
    # unit norm weight, the program's halves the normed rows
    normed = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True)
                          + uncut["layer_norm_epsilon"])
    total = whole.shared(normed)
    pairs = 0
    for lo in (0, 4):
        half = nh.NemotronHMoE(nh.NemotronHConfig.tiny(
            experts_held=range(lo, lo + 4)))
        half.experts.gate_weight._value = whole.experts.gate_weight._value
        half.e_score_correction_bias._value = \
            whole.e_score_correction_bias._value
        half.experts.w1._value = whole.experts.w1._value[lo:lo + 4]
        half.experts.w2._value = whole.experts.w2._value[lo:lo + 4]
        total = total + half.routed(normed)
        pairs += int(half.step_stats[0])
    assert pairs == 40 * 3
    with jax.default_matmul_precision("highest"):
        h, router, _, out = family._moe_open(
            x, [jnp.ones((64,)), whole.experts.gate_weight._value,
                whole.e_score_correction_bias._value,
                whole.shared_up._value, whole.shared_down._value], uncut)
        for start in range(0, 8, family.EXPERT_GROUP):
            out = family._expert_group(
                out, h, router, whole.experts.w1._value,
                whole.experts.w2._value, start, uncut)
    np.testing.assert_allclose(np.asarray(total), np.asarray(out - x),
                               rtol=2e-4, atol=2e-5)


# -- the router's selection bias, balanced ------------------------------------

def test_balance_evens_the_loads_of_rows_that_share_a_common_part():
    """Rows that are mostly one common vector send every token to the
    same few experts; ``NemotronHMoE.balance`` moves the selection bias
    by the family's rule until every expert gets about its share, and
    leaves the router's weights, and so the experts' weights in the
    result, alone."""
    paddle.seed(3)
    moe = nh.NemotronHMoE(nh.NemotronHConfig.tiny(
        n_routed_experts=32, num_experts_per_tok=4,
        experts_held=range(16)))
    rng = np.random.RandomState(0)
    rows = jnp.asarray(3.0 * rng.randn(1, 64) + rng.randn(512, 64),
                       jnp.float32)
    gate = np.asarray(moe.experts.gate_weight._value)
    drawn = np.asarray(moe.e_score_correction_bias._value)

    def loads():
        moe.routed(rows)
        scores = jax.nn.sigmoid(rows @ moe.experts.gate_weight._value)
        _, chosen = jax.lax.top_k(
            scores + moe.e_score_correction_bias._value, 4)
        return np.bincount(np.asarray(chosen).reshape(-1), minlength=32)

    before = loads()
    assert (before == 0).sum() >= 8 and before.max() > 4 * before.mean()
    moe.balance(rows, rounds=200, step=0.02)
    after = loads()
    assert (after > 0).all() and after.max() < 1.5 * after.mean()
    assert int(moe.step_stats[0]) == after[:16].sum()
    moved = np.asarray(moe.e_score_correction_bias._value) - drawn
    assert 0 < np.abs(moved).max() <= 200 * 0.02
    assert np.array_equal(np.asarray(moe.experts.gate_weight._value), gate)


def test_balance_router_bias_runs_every_expert_layer(tiny):
    """One forward, every expert layer's input caught: both layers'
    biases move, nothing else does, and the model still agrees with the
    reference (which reads the same buffer)."""
    model, _ = tiny
    paddle.seed(1)
    fresh = nh.NemotronHForCausalLM(model.config)
    fresh.eval()
    names, before = fresh.functional_state()
    before = [np.asarray(v) for v in before]
    fresh.balance_router_bias(np.asarray([_ids(96, seed=3)], np.int32),
                              rounds=50)
    changed = [n for n, a, b in zip(names, before,
                                    fresh.functional_state()[1])
               if not np.array_equal(a, np.asarray(b))]
    assert changed == ["backbone.layers.1.mixer.e_score_correction_bias",
                       "backbone.layers.4.mixer.e_score_correction_bias"]
