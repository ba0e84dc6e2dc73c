"""Serving quant tier (ISSUE 19): int8 block-scaled KV pages +
weight-only int8 decode.

Oracle discipline matches tests/test_serving_prefix.py: both flags are
pure memory/bandwidth optimizations layered on the SAME engine —
flags-off must stay bit-identical to the pre-quant engine (int8 never
enters the jaxpr), quant-kv must still reproduce
``GenerationMixin.generate``'s greedy tokens on the fixture workload
(head_dim-vector scales lose nothing the tiny softmax can see), and
quant-weights is pinned to greedy token-identity on short horizons plus
a reconstruction-error bound on every quantized leaf. Scheduling
invariants (COW divergence, preempt/resume, refcounts) are pinned
bit-identical ACROSS the quant axis: quantization changes what bytes a
page holds, never which pages a request owns.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.core import flags as _flags
from paddle_tpu.kernels.quant import (
    dequantize_int8_block,
    dequantize_int8_weight,
    quantize_int8_page,
    quantize_int8_weight,
    weight_block,
)
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving.kv_cache import (BlockAllocator, KVPages,
                                         PagedKVCache)

QUANT_COMBOS = [
    pytest.param((False, False), id="quant_off"),
    pytest.param((True, False), id="quant_kv"),
    pytest.param((False, True), id="quant_w"),
    pytest.param((True, True), id="quant_kv+w"),
]


@pytest.fixture(scope="module")
def llama():
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=64, use_parallel=False)
    return LlamaForCausalLM(cfg), cfg


def _set(prefix=False, chunked=False, quant_kv=False, quant_weights=False):
    _flags.set_flags({
        "FLAGS_serving_prefix_cache": prefix,
        "FLAGS_serving_chunked_prefill": chunked,
        "FLAGS_serving_quant_kv": quant_kv,
        "FLAGS_serving_quant_weights": quant_weights})


@pytest.fixture(autouse=True)
def _restore_flags():
    yield
    _set()


def _greedy_ref(model, prompt, max_new_tokens, eos_token_id=None):
    out = model.generate(
        paddle.to_tensor(np.asarray([prompt], np.int32)),
        max_new_tokens=max_new_tokens, eos_token_id=eos_token_id)
    toks = np.asarray(out._value)[0].tolist()
    if eos_token_id is not None and eos_token_id in toks:
        toks = toks[:toks.index(eos_token_id) + 1]
    return toks


def _assert_equal_up_to_a_near_tie(model, prompt, got, want, ctx):
    """Greedy ids are compared as the chip checks compare them: where
    two runs first part, the dense float32 logits of the two tokens
    must lie within what int8 pages can resolve (a block's values are
    rounded to 1/127 of its largest: 2^-7 of the largest |logit|).
    Past that point the runs decode different sequences."""
    assert len(got) == len(want), ctx
    if got == want:
        return
    i = next(k for k, (a, b) in enumerate(zip(got, want)) if a != b)
    ids = np.asarray([prompt + want[:i]], np.int32)
    logits = np.asarray(model(paddle.to_tensor(ids))._value,
                        np.float32)[0, -1]
    gap = abs(float(logits[got[i]] - logits[want[i]]))
    assert gap <= 2.0 ** -7 * float(np.abs(logits).max()), \
        (ctx, i, got, want, gap)


# ---------------------------------------------------------------------------
# quant primitives (no model): page and weight codecs
# ---------------------------------------------------------------------------

class TestPageCodec:
    def test_roundtrip_error_bounded(self):
        rng = np.random.RandomState(0)
        x = rng.randn(8, 4, 2, 16).astype(np.float32)
        q, s = quantize_int8_page(jnp.asarray(x))
        assert q.shape == x.shape and q.dtype == jnp.int8
        assert s.shape == x.shape[:-1] and s.dtype == jnp.float32
        deq = np.asarray(dequantize_int8_block(q, s))
        # symmetric int8: per-vector abs error <= scale/2 = amax/254
        bound = np.abs(x).max(-1, keepdims=True) / 254 + 1e-7
        assert (np.abs(deq - x) <= bound).all()

    def test_zero_vector_scale_floor_dequants_exact_zero(self):
        x = jnp.zeros((2, 4, 1, 8), jnp.float32)
        q, s = quantize_int8_page(x)
        np.testing.assert_array_equal(np.asarray(s), 1.0)
        np.testing.assert_array_equal(
            np.asarray(dequantize_int8_block(q, s)), 0.0)

    def test_nonfinite_vector_poisons_its_scale(self):
        x = np.ones((2, 2, 1, 4), np.float32)
        x[1, 0, 0, 2] = np.inf
        _, s = quantize_int8_page(jnp.asarray(x))
        s = np.asarray(s)
        assert np.isnan(s[1, 0, 0])
        assert np.isfinite(s[0]).all()        # poison stays local

    def test_axis_aware_dequant_out_dtype(self):
        rng = np.random.RandomState(1)
        q, s = quantize_int8_page(
            jnp.asarray(rng.randn(2, 4, 2, 8), jnp.float32))
        out = dequantize_int8_block(q, s, out_dtype=jnp.bfloat16)
        assert out.dtype == jnp.bfloat16 and out.shape == q.shape


class TestWeightCodec:
    def test_block_picker_pow2_divisor(self):
        assert weight_block(256) == 256
        assert weight_block(512) == 256     # capped at the default block
        assert weight_block(48) == 16       # largest pow2 <= 256 dividing
        # no power of two >= 8 divides -> one scale per column
        assert weight_block(12) == 12
        assert weight_block(7) == 7

    def test_roundtrip_error_bounded(self):
        rng = np.random.RandomState(2)
        w = rng.randn(32, 48).astype(np.float32)
        q, s = quantize_int8_weight(jnp.asarray(w))
        b = weight_block(32)
        assert q.shape == w.shape and q.dtype == jnp.int8
        assert s.shape == (32 // b, 48)
        deq = np.asarray(dequantize_int8_weight(q, s, jnp.float32))
        # per-(input-block, out-col) abs error <= amax/254
        amax = np.abs(w).reshape(32 // b, b, 48).max(1)
        bound = np.repeat(amax, b, axis=0) / 254 + 1e-7
        assert (np.abs(deq - w) <= bound).all()


# ---------------------------------------------------------------------------
# kernel parity on quantized pools (interpret mode, CPU): the fused
# dequant inside the Pallas gather == the jnp reference on valid rows;
# idle rows stay exact zero (trash-page discipline survives int8)
# ---------------------------------------------------------------------------

class TestQuantizedKernels:
    def _pools(self, rng, nb, bs, hkv, d, seqs):
        kp = np.zeros((nb, bs, hkv, d), np.float32)
        vp = np.zeros((nb, bs, hkv, d), np.float32)
        mb = max(-(-max(t for t in seqs) // bs), 1)
        bt = np.zeros((len(seqs), mb), np.int32)
        alloc = BlockAllocator(nb)
        for i, total in enumerate(seqs):
            pages = alloc.alloc(-(-total // bs)) if total else []
            bt[i, :len(pages)] = pages
            for pos in range(total):
                kp[pages[pos // bs], pos % bs] = rng.randn(hkv, d)
                vp[pages[pos // bs], pos % bs] = rng.randn(hkv, d)
        return kp, vp, bt

    def test_mixed_interpret_parity_quantized_gqa(self):
        from paddle_tpu.serving.kernels.paged_attention import (
            mixed_paged_attention_kernel,
            mixed_paged_attention_reference,
        )

        rng = np.random.RandomState(0)
        s, c, h, hkv, d, bs, nb = 4, 4, 8, 2, 16, 4, 32
        hist = [6, 0, 13, 3]
        qlen = [4, 0, 1, 2]
        kp, vp, bt = self._pools(
            rng, nb, bs, hkv, d, [a + b for a, b in zip(hist, qlen)])
        kq, ks = quantize_int8_page(jnp.asarray(kp))
        vq, vs = quantize_int8_page(jnp.asarray(vp))
        q = jnp.asarray(rng.randn(s, c, h, d), jnp.float32)
        hist = np.asarray(hist, np.int32)
        qlen = np.asarray(qlen, np.int32)
        got = np.asarray(mixed_paged_attention_kernel(
            q, kq, vq, bt, hist, qlen, k_scale=ks, v_scale=vs,
            interpret=True))
        ref = np.asarray(mixed_paged_attention_reference(
            q, kq, vq, bt, hist, qlen, k_scale=ks, v_scale=vs))
        fp32 = np.asarray(mixed_paged_attention_reference(
            q, jnp.asarray(kp), jnp.asarray(vp), bt, hist, qlen))
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got[1], 0.0)   # idle row: exact 0
        for i in range(s):
            for j in range(qlen[i]):
                np.testing.assert_allclose(
                    got[i, j], ref[i, j], atol=1e-5,
                    err_msg="row %d chunk %d" % (i, j))
                # and the dequant actually reconstructs the context:
                # attention over int8 pages tracks the fp32 answer
                np.testing.assert_allclose(
                    got[i, j], fp32[i, j], atol=0.05,
                    err_msg="row %d chunk %d vs fp32" % (i, j))

    def test_decode_interpret_parity_quantized(self):
        from paddle_tpu.serving.kernels.paged_attention import (
            paged_attention_kernel,
            paged_attention_reference,
        )

        rng = np.random.RandomState(1)
        s, h, hkv, d, bs, nb = 3, 4, 2, 16, 4, 16
        lens = [7, 0, 12]
        kp, vp, bt = self._pools(rng, nb, bs, hkv, d, lens)
        kq, ks = quantize_int8_page(jnp.asarray(kp))
        vq, vs = quantize_int8_page(jnp.asarray(vp))
        q = jnp.asarray(rng.randn(s, h, d), jnp.float32)
        lens = np.asarray(lens, np.int32)
        got = np.asarray(paged_attention_kernel(
            q, kq, vq, bt, lens, k_scale=ks, v_scale=vs, interpret=True))
        ref = np.asarray(paged_attention_reference(
            q, kq, vq, bt, lens, k_scale=ks, v_scale=vs))
        np.testing.assert_array_equal(got[1], 0.0)
        np.testing.assert_allclose(got[0], ref[0], atol=1e-5)
        np.testing.assert_allclose(got[2], ref[2], atol=1e-5)


# ---------------------------------------------------------------------------
# pool plumbing: scale planes live beside the pools and follow every
# page lifecycle transition (clone, reset)
# ---------------------------------------------------------------------------

class TestScalePlanes:
    def test_quantized_cache_geometry(self):
        c = PagedKVCache([KVPages(num_kv_heads=2, head_dim=8)] * 2,
                         num_blocks=8, block_size=4, max_slots=2,
                         max_blocks_per_slot=4, quantized=True)
        assert c.quantized
        for p in c.pools:
            assert p.k.dtype == jnp.int8 and p.v.dtype == jnp.int8
            assert p.k_scale.shape == (8, 4, 2)
            assert p.k_scale.dtype == jnp.float32
        c.reset_pools()
        assert c.pools[0].k_scale is not None

    def test_fp32_cache_has_no_scale_planes(self):
        c = PagedKVCache([KVPages(num_kv_heads=2, head_dim=8)],
                         num_blocks=8, block_size=4, max_slots=2,
                         max_blocks_per_slot=4)
        assert not c.quantized
        assert c.pools[0].k.dtype == jnp.float32
        assert c.pools[0].k_scale is None and c.pools[0].v_scale is None


# ---------------------------------------------------------------------------
# flags-off pin: the default engine is the pre-quant engine — fp32
# pools, no scale planes, no int8 anywhere in the compiled jaxpr, no
# new metric movement, same greedy tokens
# ---------------------------------------------------------------------------

class TestFlagsOffPinned:
    def test_flags_off_engine_is_pre_quant(self, llama):
        m, cfg = llama
        rng = np.random.RandomState(5)
        prompts = [rng.randint(0, cfg.vocab_size, (n,)).tolist()
                   for n in (5, 9, 12)]
        eng = serving.Engine(m, max_slots=2, num_blocks=64, block_size=4)
        assert not eng.quant_kv and not eng.quant_weights
        assert not eng.cache.quantized
        assert eng.cache.pools[0].k_scale is None
        assert eng._decode_vals is eng._state_vals   # no copied weights
        ids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        outs = eng.run()
        for p, rid in zip(prompts, ids):
            assert outs[rid] == _greedy_ref(m, p, 6)
        st = eng.stats()
        assert st["kv_quant_pages"] == 0
        assert st["quant_dequant_bytes"] == 0
        assert st["decode_compiles"] == 1

    def test_flags_off_jaxpr_has_no_int8(self, llama):
        """Structural bit-identity: with the flags off the compiled
        steps must not mention int8 at all — the scale planes are None
        pytree leaves, invisible to tracing."""
        m, _ = llama
        eng = serving.Engine(m, max_slots=2, num_blocks=16, block_size=4)
        art = eng.graph_report()
        for name, step in art["steps"].items():
            assert "i8[" not in step["jaxpr"], name

    def test_quant_kv_jaxpr_carries_int8_pools(self, llama):
        m, _ = llama
        _set(quant_kv=True)
        eng = serving.Engine(m, max_slots=2, num_blocks=16, block_size=4)
        art = eng.graph_report()
        assert "i8[" in art["steps"]["decode"]["jaxpr"]

    def test_latch_at_construction(self, llama):
        """PR-9 discipline: toggling the flags after construction must
        not touch a live engine."""
        m, _ = llama
        eng = serving.Engine(m, max_slots=2, num_blocks=16, block_size=4)
        _set(quant_kv=True, quant_weights=True)
        assert not eng.quant_kv and not eng.quant_weights
        assert eng.cache.pools[0].k.dtype == jnp.float32


# ---------------------------------------------------------------------------
# jaxpr-hash pins via the pthlo fixtures: quant flags change the quant
# fixtures' programs (int8 pools), never the fp32 fixtures', and the
# quant programs are deterministic across rebuilds
# ---------------------------------------------------------------------------

class TestJaxprPins:
    def _prints(self, name):
        from paddle_tpu.analysis.graph import build_fixture

        art = build_fixture(name)
        return {k: v["fingerprint"] for k, v in art["steps"].items()}

    def test_quant_fixture_fingerprints_stable(self):
        assert self._prints("serving_quant_kv") == \
            self._prints("serving_quant_kv")

    def test_quant_kv_differs_from_base_decode(self):
        base = self._prints("serving_base")
        quant = self._prints("serving_quant_kv")
        assert base["decode"] != quant["decode"]

    def test_base_fixture_unchanged_by_quant_flags_off(self):
        """The flags-off program is the SAME program whether the quant
        flags were never set or explicitly cleared."""
        a = self._prints("serving_base")
        _set(quant_kv=True, quant_weights=True)
        # build_fixture snapshots+restores flags and sets its own — the
        # polluted ambient state must not leak into the artifact
        b = self._prints("serving_base")
        assert a == b


# ---------------------------------------------------------------------------
# flag matrix: prefix x chunked x quant — outputs invariant to
# SCHEDULING at fixed quant setting, decode_compiles == 1 everywhere
# ---------------------------------------------------------------------------

class TestQuantFlagMatrix:
    @pytest.mark.parametrize("quant", QUANT_COMBOS)
    def test_outputs_scheduling_invariant_compile_once(self, llama, quant):
        m, cfg = llama
        qkv, qw = quant
        rng = np.random.RandomState(6)
        shared = rng.randint(0, cfg.vocab_size, (8,)).tolist()
        prompts = [shared + rng.randint(0, cfg.vocab_size, (n,)).tolist()
                   for n in (3, 5)] + \
                  [rng.randint(0, cfg.vocab_size, (7,)).tolist()]
        got = {}
        for prefix, chunked in [(False, False), (True, False),
                                (False, True), (True, True)]:
            _set(prefix, chunked, qkv, qw)
            eng = serving.Engine(m, max_slots=2, num_blocks=64,
                                 block_size=4, prefill_chunk=4)
            ids = [eng.add_request(p, max_new_tokens=5) for p in prompts]
            outs = eng.run()
            got[(prefix, chunked)] = [outs[r] for r in ids]
            st = eng.stats()
            assert st["decode_compiles"] == 1, (quant, prefix, chunked)
            if qkv:
                assert st["kv_quant_pages"] > 0
                assert st["quant_dequant_bytes"] > 0
        base = got[(False, False)]
        for combo, outs in got.items():
            if not qkv:
                assert outs == base, (quant, combo)
                continue
            # a prefill over adopted int8 history reads rounded K/V
            # where a whole prefill reads its own exact ones
            for prompt, out, want in zip(prompts, outs, base):
                _assert_equal_up_to_a_near_tie(m, prompt, out, want,
                                               (quant, combo))


# ---------------------------------------------------------------------------
# COW on quantized pages: divergence from a shared prefix is
# bit-identical to the solo quant runs, and the clone copies scales
# ---------------------------------------------------------------------------

class TestQuantCopyOnWrite:
    def test_shared_prefix_diverge_bit_identical(self, llama):
        m, cfg = llama
        rng = np.random.RandomState(3)
        base = rng.randint(0, cfg.vocab_size, (16,)).tolist()
        pb = base[:14] + rng.randint(0, cfg.vocab_size, (2,)).tolist()

        solo = {}
        _set(prefix=True, quant_kv=True)
        for key, prompt in (("a", base), ("b", pb)):
            eng = serving.Engine(m, max_slots=2, num_blocks=64,
                                 block_size=4)
            rid = eng.add_request(prompt, max_new_tokens=6)
            solo[key] = eng.run()[rid]

        shared = serving.Engine(m, max_slots=2, num_blocks=64,
                                block_size=4)
        ia = shared.add_request(base, max_new_tokens=6)
        shared.run()
        ib = shared.add_request(pb, max_new_tokens=6)
        outs = shared.run()
        assert shared.output(ia) == solo["a"]
        assert outs[ib] == solo["b"]
        st = shared.stats()
        assert shared.request_metrics(ib)["prefix_cached_tokens"] == 14
        assert st["cow_clones"] >= 1
        # the cloned page carries NON-ZERO scales: the COW copy moved
        # the scale planes with the int8 payload
        ks = np.asarray(shared.cache.pools[0].k_scale)
        assert (ks != 0).any()


# ---------------------------------------------------------------------------
# preempt/resume on quantized pages: pool exhaustion + recompute still
# lands the same tokens as a roomy quant engine
# ---------------------------------------------------------------------------

class TestQuantPreemptResume:
    @pytest.mark.parametrize("chunked", [False, True],
                             ids=["bucketed", "chunked"])
    def test_starved_equals_roomy(self, llama, chunked):
        m, cfg = llama
        rng = np.random.RandomState(10)
        prompts = [rng.randint(0, cfg.vocab_size, (n,)).tolist()
                   for n in (6, 8)]
        _set(chunked=chunked, quant_kv=True)
        starved = serving.Engine(m, max_slots=2, num_blocks=7,
                                 block_size=4, prefill_chunk=4)
        sid = [starved.add_request(p, max_new_tokens=10) for p in prompts]
        souts = starved.run()
        assert starved.stats()["preemptions"] >= 1
        roomy = serving.Engine(m, max_slots=2, num_blocks=64,
                               block_size=4, prefill_chunk=4)
        rid = [roomy.add_request(p, max_new_tokens=10) for p in prompts]
        routs = roomy.run()
        for a, b in zip(sid, rid):
            assert souts[a] == routs[b]


# ---------------------------------------------------------------------------
# refcount parity: quantization never changes page ownership — the
# allocator's refcounts, free count and COW counters match the fp32
# engine on the same shared-prefix workload
# ---------------------------------------------------------------------------

class TestScalePlaneRefcountParity:
    def test_allocator_state_matches_fp32_run(self, llama):
        m, cfg = llama
        rng = np.random.RandomState(7)
        base = rng.randint(0, cfg.vocab_size, (12,)).tolist()
        tails = [rng.randint(0, cfg.vocab_size, (n,)).tolist()
                 for n in (2, 4)]

        snap = {}
        for qkv in (False, True):
            _set(prefix=True, quant_kv=qkv)
            eng = serving.Engine(m, max_slots=2, num_blocks=64,
                                 block_size=4)
            eng.add_request(base, max_new_tokens=4)
            eng.run()
            for t in tails:
                eng.add_request(base + t, max_new_tokens=4)
            eng.run()
            st = eng.stats()
            snap[qkv] = dict(
                refs=dict(eng.cache.allocator._refs),
                free=eng.cache.allocator.free_blocks,
                cow=st["cow_clones"],
                hit=st["prefix_hit_tokens"])
        assert snap[True] == snap[False]


# ---------------------------------------------------------------------------
# accuracy pins vs the fp32 engine
# ---------------------------------------------------------------------------

class TestQuantAccuracy:
    def test_quant_kv_greedy_token_identical(self, llama):
        """head_dim-vector scales on the tiny fixture lose nothing the
        argmax can see: the quant-kv engine reproduces fp32 greedy
        tokens even on a batched multi-request workload."""
        m, cfg = llama
        rng = np.random.RandomState(11)
        prompts = [rng.randint(0, cfg.vocab_size, (n,)).tolist()
                   for n in (4, 7, 13)]
        _set(quant_kv=True)
        eng = serving.Engine(m, max_slots=3, num_blocks=64, block_size=4)
        ids = [eng.add_request(p, max_new_tokens=8) for p in prompts]
        outs = eng.run()
        for p, rid in zip(prompts, ids):
            assert outs[rid] == _greedy_ref(m, p, 8)

    def test_quant_weights_short_horizon_token_identical(self, llama):
        """Weight-only int8 decode: greedy token-identity on short
        horizons, single request at a time (the ISSUE's accuracy pin —
        long horizons may drift by design, the per-leaf reconstruction
        bound below is the standing guarantee)."""
        m, cfg = llama
        rng = np.random.RandomState(12)
        _set(quant_weights=True)
        for n in (1, 3, 6):
            prompt = rng.randint(0, cfg.vocab_size, (5 + n,)).tolist()
            eng = serving.Engine(m, max_slots=1, num_blocks=64,
                                 block_size=4)
            rid = eng.add_request(prompt, max_new_tokens=6)
            assert eng.run()[rid] == _greedy_ref(m, prompt, 6), n

    def test_quant_weights_reconstruction_rtol(self, llama):
        """Every engine-quantized projection leaf dequantizes back
        within the symmetric-int8 bound relative to its block amax."""
        m, _ = llama
        _set(quant_weights=True)
        eng = serving.Engine(m, max_slots=1, num_blocks=16, block_size=4)
        quantized = [(n, v) for n, v in
                     zip(eng._names, eng._decode_vals)
                     if isinstance(v, tuple)]
        assert len(quantized) == 14     # 7 projections x 2 layers
        by_name = dict(zip(eng._names, eng._state_vals))
        for name, (q, s) in quantized:
            w = np.asarray(by_name[name]._value
                           if hasattr(by_name[name], "_value")
                           else by_name[name])
            deq = np.asarray(dequantize_int8_weight(q, s, jnp.float32))
            err = np.abs(deq - w).max()
            assert err <= np.abs(w).max() / 126 + 1e-7, name
            # and the relative logit-scale error stays tiny
            denom = np.abs(w).max()
            assert err / denom < 2e-2, name
