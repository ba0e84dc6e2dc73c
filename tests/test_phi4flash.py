"""Phi-4-mini-flash at a tiny size on the CPU (hidden 64, 8 layers: Mamba-1,
window, Mamba-1, window, the memory layer, full attention, a GMU, cross
attention; 8 query heads over 4 KV heads of 8, a window of 8), seeded,
against the plain float32 reference in ``benchmark/families/phi4flash.py``:
the model's logits; prefill then decode through ``serving.Engine`` with
prompts longer than the window and decoding past the ring's wrap; the
YOCO prefill's last row; the cache kinds (ring, shared pages); the
``diff_decode`` and ``selective_scan`` kernels and the banded
``flash_attention`` in interpret mode against their references; and
that the comparison catches each part of the model left out."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.core import flags as _flags
from paddle_tpu.core.dispatch import no_grad
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.kernels.flash_attention import (_reference_attention,
                                                flash_attention)
from paddle_tpu.serving.kernels.diff_attention import (
    diff_decode_kernel, diff_decode_reference)
from paddle_tpu.serving.kernels.selective_scan import (
    selective_scan_kernel, selective_scan_reference)
from paddle_tpu.serving.kv_cache import RingPool
from tools.serving_parity import logits_through_cache

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
import run as bench                                         # noqa: E402
from serving_checks import serve_an_eos_mid_flight  # noqa: E402

CFG = dict(
    family="phi4flash", vocab_size=128, hidden_size=64,
    intermediate_size=128, num_hidden_layers=8, num_attention_heads=8,
    num_key_value_heads=4, sliding_window=8, mb_per_layer=2,
    mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=4,
    layer_norm_eps=1e-5, max_position_embeddings=512, time_step_min=0.001,
    time_step_max=0.1, time_step_floor=1e-4, lambda_std=0.1,
    tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False,
    torch_dtype="float32")
WINDOW_LAYERS, FULL, CROSS = (1, 3), 5, 7


@pytest.fixture(scope="module")
def family():
    return bench.load_module("families", "phi4flash")


@pytest.fixture(scope="module")
def tiny(family):
    """The program's model with its norm weights and biases, the
    sub-norms and D moved off their init, so that each is exercised."""
    model = family.build_model(CFG, 7, training=False)
    rng = np.random.RandomState(7)
    for name, p in model.named_parameters():
        if "ln" in name or "norm" in name or "subln" in name \
                or name.endswith(".D"):
            p._value = p._value + jnp.asarray(
                0.3 * rng.randn(*p.shape), p._value.dtype)
    return model, family.weights_of(model)


def _ids(n, seed=0):
    return np.random.RandomState(seed).randint(
        0, CFG["vocab_size"], (n,)).tolist()


def _forward(model, seq):
    return np.asarray(model(paddle.to_tensor(
        np.asarray([seq], np.int32)))._value)[0]


def _engine(model, **kw):
    args = dict(max_slots=2, num_blocks=64, block_size=4, max_model_len=128)
    args.update(kw)
    return serving.Engine(model, **args)


# -- the model against the reference -----------------------------------------

def test_model_logits_match_reference(family, tiny):
    """Whole sequences, no cache: 30 positions, past the window."""
    model, weights = tiny
    seq = _ids(30, seed=1)
    np.testing.assert_allclose(_forward(model, seq),
                               family.reference_logits(weights, CFG, seq),
                               rtol=2e-4, atol=2e-5)


def test_param_count_matches_the_model(family, tiny):
    _, weights = tiny
    assert family.param_count(CFG) == sum(
        int(np.prod(w.shape)) for w in weights.values())


@pytest.mark.parametrize("prompt_len", [32, 21])
def test_prefill_then_decode_match_the_full_forward(family, tiny,
                                                    prompt_len):
    """A prompt that fills its bucket and one that does not, both longer
    than the window; twelve decode steps wrap every ring more than once.
    The state, tails, rings and pages the prefill left carry the decode
    to the reference's full forward."""
    model, weights = tiny
    steps = 12
    seq = _ids(prompt_len + steps, seed=prompt_len)
    got, bucket = logits_through_cache(_engine(model), seq, steps)
    assert (bucket == prompt_len) == (prompt_len == 32)
    want = np.asarray(family.reference_logits(weights, CFG, seq))[
        prompt_len - 1:]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_yoco_prefill_row_is_the_full_forwards(tiny):
    """``logits_at`` in a prefill: past the full layer's write one row
    runs on, and its logits are those of the forward over every row;
    the trace records one row past the self-decoder."""
    model, _ = tiny
    eng = _engine(model)
    seq = _ids(19, seed=3)
    bucket = eng._bucket(len(seq))
    eng.cache.ensure_capacity(0, len(seq))
    ids = np.zeros((1, bucket), np.int32)
    ids[0, :len(seq)] = seq

    def prefill(state_vals, pools, ids, table_row, true_len):
        with model.bind_state(eng._names, list(state_vals)), no_grad():
            views = eng.cache.prefill_views(pools, table_row, true_len)
            logits, _ = model.generate_step(
                Tensor(ids), views, 0, jnp.reshape(true_len - 1, (1,)))
        return logits._value

    logits = eng._run_eval(jax.jit(prefill), eng._state_vals,
                           eng.cache.pools, jnp.asarray(ids),
                           jnp.asarray(eng.cache.block_tables[0]),
                           jnp.asarray(len(seq), jnp.int32))
    assert logits.shape == (1, 1, CFG["vocab_size"])
    assert model.yoco_rows[bucket] == 1
    np.testing.assert_allclose(np.asarray(logits)[0, 0],
                               _forward(model, seq)[-1], rtol=2e-4,
                               atol=2e-5)


def _reference_greedy_ok(family, weights, prompt, generated):
    logits = family.reference_logits(weights, CFG,
                                     list(prompt) + list(generated))
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(generated)]
    gaps = rows.max(-1) - rows[np.arange(len(generated)), generated]
    return float(gaps.max()) <= 1e-4 * float(np.abs(rows).max())


def test_engine_tokens_are_the_reference_argmax(family, tiny):
    model, weights = tiny
    eng = _engine(model, max_slots=3)
    prompts = [_ids(n, seed=n) for n in (5, 16, 27)]
    rids = [eng.add_request(p, max_new_tokens=11) for p in prompts]
    eng.step()
    stats = eng.stats()
    ring_slot = stats["window"]["slot_bytes"]
    # two window layers, K and V, 8 rows of 4 heads x 8 float32 a slot
    assert ring_slot == 2 * 2 * 8 * 32 * 4
    assert stats["window"]["held_bytes"] == 3 * ring_slot
    outs = eng.run()
    for p, rid in zip(prompts, rids):
        assert len(outs[rid]) == 11
        assert _reference_greedy_ok(family, weights, p, outs[rid])
    stats = eng.stats()
    assert stats["decode_compiles"] == 1
    assert stats["window"]["held_bytes"] == 0
    assert stats["yoco"] == {"prefills": 3,
                             "prompt_rows_per_prefill": 16.0,
                             "cross_rows_per_prefill": 1.0}
    assert stats["ssm"]["layers"] == 3
    assert stats["ssm"]["state_bytes_slot"] == family.state_slot_bytes(CFG)


def test_an_eos_mid_flight_is_discarded_and_its_slot_reused(family, tiny):
    """The engine runs a step ahead: a request that finishes by EOS has
    a row in the step already in flight, which is thrown away, and the
    next owner of its slot (window rings and shared pages) starts clean."""
    model, weights = tiny
    prompts = [_ids(n, seed=n) for n in (5, 7, 6)]
    for p, out in serve_an_eos_mid_flight(_engine(model), prompts, 8):
        assert _reference_greedy_ok(family, weights, p, out)


# -- the cache kinds ---------------------------------------------------------

def test_cache_kinds_and_the_shared_reader_owns_no_pool(tiny):
    model, _ = tiny
    eng = _engine(model)
    assert [s.kind for s in eng.cache.layers] == [
        "slot_state", "window_ring", "slot_state", "window_ring",
        "slot_state", "kv_pages", "nothing", "shared_pages"]
    assert eng.cache.layers[CROSS].source == FULL
    assert eng.cache.pools[CROSS] is None
    # the full layer's pages: every head of a token on the lanes
    assert eng.cache.pools[FULL].k.shape == (64, 4, 4 * 8)
    for i in WINDOW_LAYERS:
        assert isinstance(eng.cache.pools[i], RingPool)
        assert eng.cache.pools[i].k.shape == (2 * 8 // 4, 4, 32)


def test_ring_holds_the_last_window_rows_at_position_mod_window(tiny):
    """After a prefill of 13 and 6 decode steps (19 positions) slot 1's
    ring in a window layer holds positions 11 .. 18, position p in row
    p mod 8: the K row the full forward computes there."""
    model, _ = tiny
    eng = _engine(model)
    seq = _ids(19, seed=5)
    logits_through_cache(eng, seq, 6)
    layer = model.model.layers[WINDOW_LAYERS[0]]
    hidden = _layer_input(model, seq, WINDOW_LAYERS[0])
    k = np.asarray(jnp.matmul(layer.ln1(hidden),
                              layer.mixer.k_proj._value))      # [19, 32]
    # slot 1's ring: pages 2 and 3 of the layer's ring pool
    ring = np.asarray(eng.cache.pools[WINDOW_LAYERS[0]].k)[2:4].reshape(
        8, 32)
    for p in range(11, 19):
        np.testing.assert_allclose(ring[p % 8], k[p], rtol=1e-4,
                                   atol=1e-5)


def _layer_input(model, seq, upto):
    """The residual stream entering layer ``upto`` of a plain forward."""
    x = jnp.take(model.model.embed_tokens._value,
                 jnp.asarray([seq]), axis=0)
    hooks = model._no_caches(1, len(seq), x.dtype)
    for i, layer in enumerate(model.model.layers[:upto]):
        u = layer.ln1(x)
        if layer.kind == "mamba":
            out = layer.mixer(u, hooks[i])[0]
        else:
            view = layer.mixer.write(u, hooks[i])
            out = layer.mixer.attend(u, view, jnp.arange(len(seq)))
        x = layer.ffn(x + out)
    return x[0]


def test_flags_that_cannot_hold_a_ring_are_refused(tiny):
    model, _ = tiny
    prev = _flags.flag("FLAGS_serving_chunked_prefill")
    _flags.set_flags({"FLAGS_serving_chunked_prefill": True})
    try:
        with pytest.raises(ValueError, match="slot_state|window_ring"):
            _engine(model)
    finally:
        _flags.set_flags({"FLAGS_serving_chunked_prefill": prev})


# -- the kernels, interpreted -------------------------------------------------

@pytest.mark.parametrize("lens", [(5, 0, 23), (1, 24, 9)])
def test_diff_decode_kernel_matches_its_reference(lens):
    """Pages named by a block table (an idle slot, a slot whose last page
    is partial, a whole table); 8 query heads over 4 KV heads of 64."""
    rng = np.random.RandomState(sum(lens))
    s, h, d, hkv, bs, nb, mb = 3, 8, 64, 4, 4, 40, 6
    q = jnp.asarray(rng.randn(s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(nb, bs, hkv * d), jnp.float32)
    v = jnp.asarray(rng.randn(nb, bs, hkv * d), jnp.float32)
    tables = jnp.asarray(rng.randint(1, nb, (s, mb)), jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    got = diff_decode_kernel(q, k, v, tables, lens, 0.37, interpret=True)
    want = diff_decode_reference(q, k, v, tables, lens, 0.37)
    assert got.shape == (s, h // 2, 2 * d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_selective_scan_kernel_matches_the_recurrence():
    """Two row blocks and two channel blocks; the last rows padded with
    dt = 0 leave the state as the real rows left it."""
    rng = np.random.RandomState(0)
    b, t, ch, n = 1, 256, 256, 16
    x = jnp.asarray(rng.randn(b, t, ch), jnp.float32)
    dt = jnp.asarray(0.1 * np.abs(rng.randn(b, t, ch)), jnp.float32)
    dt = dt.at[:, 200:].set(0.0)
    a = -jnp.asarray(np.tile(np.arange(1, n + 1)[:, None], (1, ch)),
                     jnp.float32)
    bm = jnp.asarray(rng.randn(b, t, n), jnp.float32)
    cm = jnp.asarray(rng.randn(b, t, n), jnp.float32)
    d = jnp.asarray(rng.randn(ch), jnp.float32)
    y, state = selective_scan_kernel(x, dt, a, bm, cm, d, interpret=True)
    y_ref, state_ref = selective_scan_reference(x, dt, a, bm, cm, d)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    _, state_200 = selective_scan_reference(
        x[:, :200], dt[:, :200], a, bm[:, :200], cm[:, :200], d)
    np.testing.assert_allclose(np.asarray(state), np.asarray(state_ref),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(state), np.asarray(state_200),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("window", [100, 128, 300])
def test_flash_window_matches_a_masked_reference(window):
    """Tiles of 128 over 512 rows: tiles wholly left of the band are
    skipped, those the band's edge crosses are masked; v wider than
    q and k; the gradient through the banded backward kernels too."""
    rng = np.random.RandomState(window)
    b, n, h, d, dv = 1, 512, 2, 64, 128
    q, k = (jnp.asarray(rng.randn(b, n, h, d), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.randn(b, n, h, dv), jnp.float32)

    def fold(x):
        return jnp.swapaxes(x, 1, 2).reshape(b * h, n, x.shape[3])

    def ref(q, k, v):
        out = _reference_attention(fold(q), fold(k), fold(v),
                                   1.0 / np.sqrt(d), True, window=window)
        return jnp.swapaxes(out.reshape(b, h, n, dv), 1, 2)

    def kern(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=128,
                               block_k=128, interpret=True, window=window)

    np.testing.assert_allclose(np.asarray(kern(q, k, v)),
                               np.asarray(ref(q, k, v)), rtol=1e-4,
                               atol=1e-5)
    # the band, spelled out: row 400 sees keys 400 - window + 1 .. 400
    row = np.asarray(ref(q, k, v))[0, 400, 0]
    qi, ks, vs = (np.asarray(a)[0, :, 0] for a in (q, k, v))
    lo = 400 - window + 1
    s = ks[lo:401] @ qi[400] / np.sqrt(d)
    p = np.exp(s - s.max())
    np.testing.assert_allclose(row, (p / p.sum()) @ vs[lo:401], rtol=1e-4,
                               atol=1e-5)
    grads = [jax.grad(lambda *a: f(*a).sum(), argnums=(0, 1, 2))(q, k, v)
             for f in (kern, ref)]
    for got, want in zip(*grads):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-3, atol=1e-4)


# -- the comparison catches what a program leaves out -------------------------

@pytest.mark.parametrize("ablation", [
    {"diff_lambda": False}, {"diff_subnorm": False},
    {"memory_layer": 2}, {"sliding_window": None}])
def test_a_part_left_out_fails_the_comparison(family, tiny, ablation):
    """A program without the lambda term, without the sub-norm, with its
    memory from another Mamba-1 layer, or with the window off computes
    what the reference computes with that part left out: the program's
    logits are far outside the tolerance of that reference, and the
    family's ``build_model`` refuses such a configuration."""
    model, weights = tiny
    seq = _ids(30, seed=1)
    cfg = dict(CFG, **ablation)
    left_out = family.reference_logits(weights, cfg, seq)
    gap = np.abs(_forward(model, seq) - left_out).max()
    assert gap > 100 * (2e-5 + 2e-4 * np.abs(left_out).max())
    with pytest.raises(ValueError, match="published model only"):
        family.build_model(cfg, 7, training=False)
