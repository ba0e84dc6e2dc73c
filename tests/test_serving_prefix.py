"""Serving tier 2: radix prefix cache + chunked prefill.

Oracle discipline matches tests/test_serving.py: the engine under any
flag combination must reproduce ``GenerationMixin.generate``'s greedy
tokens per request; sharing/chunking are pure scheduling/memory
optimizations. The COW pin is stronger — a request admitted onto SHARED
prefix pages must emit tokens bit-identical to its own solo run — and
the eviction pin establishes the escalation order (reclaim cached pages
BEFORE preempting live work).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.core import flags as _flags
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving.kv_cache import (BlockAllocator, KVPages,
                                         PagedKVCache)
from paddle_tpu.serving.prefix_cache import RadixPrefixCache
from paddle_tpu.serving.scheduler import RequestState

FLAG_COMBOS = [
    pytest.param((False, False), id="flags_off"),
    pytest.param((True, False), id="prefix"),
    pytest.param((False, True), id="chunked"),
    pytest.param((True, True), id="prefix+chunked"),
]


@pytest.fixture(scope="module")
def llama():
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=64, use_parallel=False)
    return LlamaForCausalLM(cfg), cfg


@pytest.fixture
def serving_flags(request):
    """Set (prefix_cache, chunked_prefill) for the test, restore after."""
    prefix, chunked = getattr(request, "param", (False, False))
    _flags.set_flags({"FLAGS_serving_prefix_cache": prefix,
                      "FLAGS_serving_chunked_prefill": chunked})
    yield prefix, chunked
    _flags.set_flags({"FLAGS_serving_prefix_cache": False,
                      "FLAGS_serving_chunked_prefill": False})


def _set(prefix=False, chunked=False):
    _flags.set_flags({"FLAGS_serving_prefix_cache": prefix,
                      "FLAGS_serving_chunked_prefill": chunked})


def _greedy_ref(model, prompt, max_new_tokens, eos_token_id=None):
    out = model.generate(
        paddle.to_tensor(np.asarray([prompt], np.int32)),
        max_new_tokens=max_new_tokens, eos_token_id=eos_token_id)
    toks = np.asarray(out._value)[0].tolist()
    if eos_token_id is not None and eos_token_id in toks:
        toks = toks[:toks.index(eos_token_id) + 1]
    return toks


# ---------------------------------------------------------------------------
# allocator: refcounts + O(1) free (ISSUE satellite: the O(n) `i in
# self._free` membership scan made page-heavy teardown quadratic)
# ---------------------------------------------------------------------------

class TestBlockAllocator:
    def test_alloc_refcount_one(self):
        a = BlockAllocator(8)
        pages = a.alloc(3)
        assert pages == [1, 2, 3]
        assert all(a.refcount(p) == 1 for p in pages)
        assert a.refcount(5) == 0      # free page: no refcount

    def test_incref_decref_lifecycle(self):
        a = BlockAllocator(8)
        (p,) = a.alloc(1)
        a.incref(p)
        assert a.refcount(p) == 2
        assert a.decref(p) is False    # still referenced
        assert a.free_blocks == 6
        assert a.decref(p) is True     # last ref -> free list
        assert a.free_blocks == 7
        assert a.refcount(p) == 0

    def test_double_free_raises(self):
        a = BlockAllocator(8)
        (p,) = a.alloc(1)
        a.free([p])
        with pytest.raises(ValueError):
            a.free([p])
        with pytest.raises(ValueError):
            a.decref(5)                # never allocated
        with pytest.raises(ValueError):
            a.incref(5)
        with pytest.raises(ValueError):
            a.free([0])                # trash page is unmanaged
        with pytest.raises(ValueError):
            a.free([99])               # out of range

    def test_mass_release_10k_pages(self):
        """Behavioral pin for the set-backed free list: a 10k-page
        release round-trips exactly (no timing assertion — the O(1)
        membership check is structural, `_free_set`, not measured)."""
        n = 10_001
        a = BlockAllocator(n)
        pages = a.alloc(n - 1)
        assert a.free_blocks == 0
        a.free(pages)
        assert a.free_blocks == n - 1
        assert a._free_set == set(range(1, n))
        with pytest.raises(ValueError):
            a.free([pages[0]])         # double free still detected
        # LIFO recirculation preserved (cache-warm pages first)
        assert a.alloc(1) == [pages[-1]]

    def test_lifo_order_matches_pre_refcount_allocator(self):
        a = BlockAllocator(8)
        assert a.alloc(3) == [1, 2, 3]
        a.free([1, 2, 3])
        assert a.alloc(3) == [3, 2, 1]


# ---------------------------------------------------------------------------
# radix tree unit tests (no model)
# ---------------------------------------------------------------------------

def _mini_cache(num_blocks=32, block_size=4):
    return PagedKVCache([KVPages(num_kv_heads=1, head_dim=8)],
                        num_blocks=num_blocks, block_size=block_size,
                        max_slots=2, max_blocks_per_slot=8)


class TestRadixPrefixCache:
    def test_insert_then_match_full_pages(self):
        cache = _mini_cache()
        pc = RadixPrefixCache(cache)
        tokens = list(range(12))
        pages = cache.allocator.alloc(3)
        assert pc.insert(tokens, pages, 12) == 3
        got, matched = pc.match(tokens + [99], limit=12)
        assert got == pages and matched == 12
        # a diverging second chunk stops the walk after page one
        got, matched = pc.match(tokens[:4] + [50, 51, 52, 53], limit=8)
        assert got == pages[:1] and matched == 4

    def test_match_limit_leaves_a_suffix_token(self):
        """The engine always passes limit=len-1: a fully-cached prompt
        still prefills its last token (logits must come from a forward
        pass)."""
        cache = _mini_cache()
        pc = RadixPrefixCache(cache)
        tokens = list(range(8))
        pages = cache.allocator.alloc(2)
        pc.insert(tokens, pages, 8)
        got, matched = pc.match(tokens, limit=7)
        # 1 full page + a 3-token partial share of the second page
        assert matched == 7 and got == pages

    def test_partial_page_match_longest_head_wins(self):
        cache = _mini_cache()
        pc = RadixPrefixCache(cache)
        a = cache.allocator.alloc(1)
        b = cache.allocator.alloc(1)
        pc.insert([1, 2, 3, 4], a, 4)
        pc.insert([1, 2, 9, 9], b, 4)
        got, matched = pc.match([1, 2, 3, 7, 7], limit=4)
        assert got == a and matched == 3
        # tie on the head length: the first-inserted child wins
        # (deterministic dict order)
        got, matched = pc.match([1, 2, 8, 8, 8], limit=4)
        assert got == a and matched == 2

    def test_insert_dedup_keeps_existing_node(self):
        cache = _mini_cache()
        pc = RadixPrefixCache(cache)
        first = cache.allocator.alloc(1)
        dup = cache.allocator.alloc(1)
        assert pc.insert([5, 6, 7, 8], first, 4) == 1
        assert pc.insert([5, 6, 7, 8], dup, 4) == 0
        got, _ = pc.match([5, 6, 7, 8, 9], limit=4)
        assert got == first
        # the duplicate page stayed private: freeing it works normally
        assert cache.allocator.refcount(dup[0]) == 1
        cache.allocator.free(dup)

    def test_reclaim_lru_leaves_first_and_skips_shared(self):
        cache = _mini_cache()
        pc = RadixPrefixCache(cache)
        cold = cache.allocator.alloc(2)      # chain: cold[0] -> cold[1]
        hot = cache.allocator.alloc(1)
        pc.insert(list(range(8)), cold, 8)
        pc.insert([9, 9, 9, 9], hot, 4)
        cache.allocator.free(cold)           # tree now sole owner
        cache.allocator.free(hot)
        pc.match(list(range(8)), limit=8)    # touch cold
        pc.match([9, 9, 9, 9, 0], limit=4)   # hot touched later -> cold LRU
        free0 = cache.allocator.free_blocks
        assert pc.reclaim(1) == 1            # evicts the cold LEAF first
        assert cache.allocator.free_blocks == free0 + 1
        assert pc.match(list(range(8)), limit=8) == (cold[:1], 4)
        # a page a live slot still references is never evicted
        cache.allocator.incref(hot[0])       # simulate an adopting slot
        assert pc.reclaim(10) == 1           # only cold[0] is evictable
        assert pc.cached_pages == 1
        cache.allocator.decref(hot[0])

    def test_clear_drops_everything_unshared(self):
        cache = _mini_cache()
        pc = RadixPrefixCache(cache)
        pages = cache.allocator.alloc(3)
        pc.insert(list(range(12)), pages, 12)
        cache.allocator.free(pages)
        assert pc.clear() == 3
        assert pc.cached_pages == 0
        assert cache.allocator.free_blocks == cache.allocator.usable_blocks


# ---------------------------------------------------------------------------
# mixed ragged kernel: interpret-mode Pallas vs the jnp gather fallback
# (the CPU engine always dispatches to the reference, so this parity
# pin is the ONLY CI coverage the TPU kernel path gets — the same
# discipline as TestPagedAttentionKernel for the decode kernel)
# ---------------------------------------------------------------------------

class TestMixedPagedAttentionKernel:
    def test_interpret_parity_mixed_rows_gqa(self):
        import jax.numpy as jnp

        from paddle_tpu.serving.kernels.paged_attention import (
            mixed_paged_attention_kernel,
            mixed_paged_attention_reference,
        )

        rng = np.random.RandomState(0)
        s, c, h, hkv, d, bs, nb, mb = 4, 4, 8, 2, 16, 4, 32, 8
        # chunk row, idle row, decode row, mid-page-hist chunk row
        hist = [6, 0, 13, 3]
        qlen = [4, 0, 1, 2]
        kp = np.zeros((nb, bs, hkv, d), np.float32)
        vp = np.zeros((nb, bs, hkv, d), np.float32)
        bt = np.zeros((s, mb), np.int32)
        alloc = BlockAllocator(nb)
        for i in range(s):
            total = hist[i] + qlen[i]
            pages = alloc.alloc(-(-total // bs)) if total else []
            bt[i, :len(pages)] = pages
            for pos in range(total):
                kp[pages[pos // bs], pos % bs] = rng.randn(hkv, d)
                vp[pages[pos // bs], pos % bs] = rng.randn(hkv, d)
        q = jnp.asarray(rng.randn(s, c, h, d), jnp.float32)
        got = np.asarray(mixed_paged_attention_kernel(
            q, jnp.asarray(kp), jnp.asarray(vp), bt,
            np.asarray(hist, np.int32), np.asarray(qlen, np.int32),
            interpret=True))
        ref = np.asarray(mixed_paged_attention_reference(
            q, jnp.asarray(kp), jnp.asarray(vp), bt,
            np.asarray(hist, np.int32), np.asarray(qlen, np.int32)))
        assert np.isfinite(got).all()
        # idle rows emit exact zeros (decode-kernel discipline); pad
        # rows (j >= q_len) are unspecified — compare VALID rows only
        np.testing.assert_array_equal(got[1], 0.0)
        for i in range(s):
            for j in range(qlen[i]):
                np.testing.assert_allclose(
                    got[i, j], ref[i, j], atol=1e-5,
                    err_msg="row %d chunk %d" % (i, j))


# ---------------------------------------------------------------------------
# flags-off pin (PR-7 knobs-off style): the default engine is the
# pre-tier-2 engine — same outputs, no cache state, no new series
# ---------------------------------------------------------------------------

class TestFlagsOffPinned:
    def test_flags_off_engine_is_pre_tier2(self, llama, serving_flags):
        m, cfg = llama
        rng = np.random.RandomState(5)
        prompts = [rng.randint(0, cfg.vocab_size, (n,)).tolist()
                   for n in (5, 9, 12)]
        eng = serving.Engine(m, max_slots=2, num_blocks=64, block_size=4)
        assert eng.prefix_cache is None
        assert not eng.chunked_prefill
        ids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        outs = eng.run()
        for p, rid in zip(prompts, ids):
            assert outs[rid] == _greedy_ref(m, p, 6)
        st = eng.stats()
        for k in ("prefix_hit_tokens", "prefix_lookup_tokens",
                  "prefix_evictions", "prefix_insert_pages",
                  "prefix_cached_pages", "cow_clones", "prefill_chunks"):
            assert st[k] == 0, k
        assert st["decode_compiles"] == 1
        # the exclusive-ownership fast path: nothing is ever shared
        assert eng.cache.allocator._refs == {}
        assert all(m["prefix_cached_tokens"] == 0
                   for m in (eng.request_metrics(r) for r in ids))

    def test_flag_on_outputs_equal_flags_off(self, llama):
        """Cross-pin: every flag combination emits the SAME tokens for
        the same workload — tier 2 changes scheduling and memory, never
        sampling."""
        m, cfg = llama
        rng = np.random.RandomState(6)
        shared = rng.randint(0, cfg.vocab_size, (8,)).tolist()
        prompts = [shared + rng.randint(0, cfg.vocab_size, (n,)).tolist()
                   for n in (3, 5)] + \
                  [rng.randint(0, cfg.vocab_size, (7,)).tolist()]
        got = {}
        for prefix, chunked in [(False, False), (True, False),
                                (False, True), (True, True)]:
            _set(prefix, chunked)
            try:
                eng = serving.Engine(m, max_slots=2, num_blocks=64,
                                     block_size=4, prefill_chunk=4)
                ids = [eng.add_request(p, max_new_tokens=5)
                       for p in prompts]
                outs = eng.run()
                got[(prefix, chunked)] = [outs[r] for r in ids]
                assert eng.stats()["decode_compiles"] == 1
            finally:
                _set()
        base = got[(False, False)]
        for combo, outs in got.items():
            assert outs == base, combo


# ---------------------------------------------------------------------------
# COW correctness (ISSUE satellite): shared prefix, divergent tails —
# each request bit-identical to its solo run
# ---------------------------------------------------------------------------

class TestCopyOnWrite:
    @pytest.mark.parametrize("serving_flags",
                             [pytest.param((True, False), id="prefix"),
                              pytest.param((True, True),
                                           id="prefix+chunked")],
                             indirect=True)
    def test_shared_prefix_diverge_bit_identical(self, llama,
                                                 serving_flags):
        m, cfg = llama
        rng = np.random.RandomState(3)
        base = rng.randint(0, cfg.vocab_size, (16,)).tolist()
        # B shares 14 of A's 16 prompt tokens: 3 full pages + a 2-token
        # PARTIAL share of A's 4th page -> the suffix write hits a
        # shared page and must copy-on-write
        pb = base[:14] + rng.randint(0, cfg.vocab_size, (2,)).tolist()

        solo = {}
        for key, prompt in (("a", base), ("b", pb)):
            eng = serving.Engine(m, max_slots=2, num_blocks=64,
                                 block_size=4, prefill_chunk=4)
            rid = eng.add_request(prompt, max_new_tokens=6)
            solo[key] = eng.run()[rid]
            assert solo[key] == _greedy_ref(m, prompt, 6)

        shared = serving.Engine(m, max_slots=2, num_blocks=64,
                                block_size=4, prefill_chunk=4)
        ia = shared.add_request(base, max_new_tokens=6)
        shared.run()
        ib = shared.add_request(pb, max_new_tokens=6)
        outs = shared.run()
        assert shared.output(ia) == solo["a"]
        assert outs[ib] == solo["b"]
        st = shared.stats()
        assert shared.request_metrics(ib)["prefix_cached_tokens"] == 14
        assert st["cow_clones"] >= 1
        assert st["prefix_hit_tokens"] >= 14

    def test_resubmission_near_total_hit(self, llama):
        """Same prompt twice: the second admission prefills ONE token
        (match capped at len-1) and still matches greedy output."""
        m, cfg = llama
        rng = np.random.RandomState(4)
        prompt = rng.randint(0, cfg.vocab_size, (16,)).tolist()
        _set(prefix=True)
        try:
            eng = serving.Engine(m, max_slots=1, num_blocks=64,
                                 block_size=4)
            r1 = eng.add_request(prompt, max_new_tokens=5)
            eng.run()
            r2 = eng.add_request(prompt, max_new_tokens=5)
            outs = eng.run()
            assert outs[r2] == eng.output(r1) == _greedy_ref(m, prompt, 5)
            assert eng.request_metrics(r2)["prefix_cached_tokens"] == 15
        finally:
            _set()


# ---------------------------------------------------------------------------
# eviction under pressure (ISSUE satellite): cached-page reclaim is
# preferred over preempting a running request
# ---------------------------------------------------------------------------

class TestEvictionUnderPressure:
    def test_reclaim_before_preempt(self, llama):
        m, cfg = llama
        rng = np.random.RandomState(8)
        warm = rng.randint(0, cfg.vocab_size, (8,)).tolist()
        pb = rng.randint(0, cfg.vocab_size, (5,)).tolist()
        pc = rng.randint(0, cfg.vocab_size, (5,)).tolist()
        _set(prefix=True)
        try:
            # usable pages: 7. The warm request leaves 2 full cached
            # pages in the tree; B and C then grow the pool dry — the
            # engine must EVICT the cold cached pages, not preempt
            eng = serving.Engine(m, max_slots=2, num_blocks=8,
                                 block_size=4)
            rw = eng.add_request(warm, max_new_tokens=2)
            eng.run()
            assert eng.stats()["prefix_cached_pages"] >= 2
            ib = eng.add_request(pb, max_new_tokens=6)
            ic = eng.add_request(pc, max_new_tokens=6)
            outs = eng.run()
            st = eng.stats()
            assert outs[ib] == _greedy_ref(m, pb, 6)
            assert outs[ic] == _greedy_ref(m, pc, 6)
            assert st["prefix_evictions"] >= 1, st
            assert st["preemptions"] == 0, st
            assert eng.output(rw) == _greedy_ref(m, warm, 2)
        finally:
            _set()


# ---------------------------------------------------------------------------
# chunked prefill behavior
# ---------------------------------------------------------------------------

class TestChunkedPrefill:
    def test_long_prefill_does_not_stall_decode(self, llama):
        """The tentpole's TPOT claim, behaviorally: a short request
        admitted alongside a LONG prompt finishes while the long one is
        still mid-prefill — under the split-prefill engine the long
        prompt would have prefilled whole before the short one decoded
        a single token past it."""
        m, cfg = llama
        rng = np.random.RandomState(9)
        long_p = rng.randint(0, cfg.vocab_size, (24,)).tolist()
        short_p = rng.randint(0, cfg.vocab_size, (4,)).tolist()
        _set(chunked=True)
        try:
            eng = serving.Engine(m, max_slots=2, num_blocks=64,
                                 block_size=4, prefill_chunk=4)
            il = eng.add_request(long_p, max_new_tokens=4)
            is_ = eng.add_request(short_p, max_new_tokens=2)
            long_req = eng.requests[il]
            short_req = eng.requests[is_]
            saw_overlap = False
            while eng.step():
                if (short_req.state is RequestState.FINISHED
                        and long_req.state is RequestState.PREFILL):
                    saw_overlap = True
            assert saw_overlap, "short request should finish mid-prefill"
            assert eng.output(il) == _greedy_ref(m, long_p, 4)
            assert eng.output(is_) == _greedy_ref(m, short_p, 2)
            st = eng.stats()
            assert st["decode_compiles"] == 1
            assert st["prefill_compiles"] == 0
            assert st["prefill_chunks"] >= 6   # 24 tokens / 4 per chunk
        finally:
            _set()

    def test_chunked_preempt_resume_bit_identical(self, llama):
        """Pool exhaustion mid-run under chunked prefill: preemption +
        recompute still lands bit-identical tokens."""
        m, cfg = llama
        rng = np.random.RandomState(10)
        prompts = [rng.randint(0, cfg.vocab_size, (n,)).tolist()
                   for n in (6, 8)]
        _set(chunked=True)
        try:
            starved = serving.Engine(m, max_slots=2, num_blocks=7,
                                     block_size=4, prefill_chunk=4)
            sid = [starved.add_request(p, max_new_tokens=10)
                   for p in prompts]
            souts = starved.run()
            assert starved.stats()["preemptions"] >= 1
            for rid, p in zip(sid, prompts):
                assert souts[rid] == _greedy_ref(m, p, 10)
        finally:
            _set()


# ---------------------------------------------------------------------------
# flag-combination matrix over the serving edge-case suite (ISSUE
# satellite, tests/test_debugz_routes.py style): the new modes must
# inherit every existing serving invariant
# ---------------------------------------------------------------------------

class TestServingFlagMatrix:
    @pytest.mark.parametrize("serving_flags", FLAG_COMBOS, indirect=True)
    def test_preempt_requeue_bit_identical(self, llama, serving_flags):
        m, cfg = llama
        rng = np.random.RandomState(1)
        prompts = [rng.randint(0, cfg.vocab_size, (n,)).tolist()
                   for n in (6, 8)]
        starved = serving.Engine(m, max_slots=2, num_blocks=7,
                                 block_size=4, prefill_chunk=4)
        sid = [starved.add_request(p, max_new_tokens=10) for p in prompts]
        souts = starved.run()
        roomy = serving.Engine(m, max_slots=2, num_blocks=64,
                               block_size=4, prefill_chunk=4)
        rid = [roomy.add_request(p, max_new_tokens=10) for p in prompts]
        routs = roomy.run()
        assert roomy.stats()["preemptions"] == 0
        for a, b in zip(sid, rid):
            assert souts[a] == routs[b]
        if serving_flags == (False, False):
            # pool pressure MUST preempt without a cache to reclaim
            assert starved.stats()["preemptions"] >= 1

    @pytest.mark.parametrize("serving_flags", FLAG_COMBOS, indirect=True)
    def test_zero_length_generation(self, llama, serving_flags):
        m, _ = llama
        eng = serving.Engine(m, max_slots=2, num_blocks=16, block_size=4,
                             prefill_chunk=4)
        rid = eng.add_request([1, 2, 3], max_new_tokens=0)
        assert not eng.has_work()
        assert eng.run() == {rid: []}
        assert eng.stats()["decode_steps"] == 0
        assert eng.cache.allocator.free_blocks == 15

    @pytest.mark.parametrize("serving_flags", FLAG_COMBOS, indirect=True)
    def test_multi_page_prompt(self, llama, serving_flags):
        m, cfg = llama
        rng = np.random.RandomState(2)
        prompt = rng.randint(0, cfg.vocab_size, (11,)).tolist()
        eng = serving.Engine(m, max_slots=1, num_blocks=16, block_size=4,
                             prefill_chunk=4)
        rid = eng.add_request(prompt, max_new_tokens=5)
        assert eng.run()[rid] == _greedy_ref(m, prompt, 5)

    @pytest.mark.parametrize("serving_flags", FLAG_COMBOS, indirect=True)
    def test_compile_once_20_staggered_requests(self, llama,
                                                serving_flags):
        m, cfg = llama
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, cfg.vocab_size,
                               (int(rng.randint(2, 14)),)).tolist()
                   for _ in range(20)]
        eng = serving.Engine(m, max_slots=4, num_blocks=64, block_size=4,
                             prefill_chunk=4)
        it = iter(prompts)
        for p in [next(it) for _ in range(4)]:
            eng.add_request(p, max_new_tokens=int(rng.randint(2, 6)))
        pending = list(it)
        while eng.has_work() or pending:
            if pending:
                eng.add_request(pending.pop(0),
                                max_new_tokens=int(rng.randint(2, 6)))
            eng.step()
        stats = eng.stats()
        assert stats["requests_finished"] == 20
        assert stats["decode_compiles"] == 1, stats
        if serving_flags[1]:
            assert stats["prefill_compiles"] == 0, stats
        elif serving_flags == (False, False):
            buckets = {eng._bucket(len(p)) for p in prompts}
            assert stats["prefill_compiles"] == len(buckets), stats


# ---------------------------------------------------------------------------
# second architecture: the external-cache hook under both flags (GPT's
# learned positions exercise the per-row offset vector in the mixed view)
# ---------------------------------------------------------------------------

class TestGPTTier2:
    def test_gpt_both_flags_matches_generate(self):
        from paddle_tpu.models.gpt import GPTModel

        paddle.seed(11)
        m = GPTModel(vocab_size=64, hidden_size=32, num_layers=2,
                     num_heads=4, max_seq_len=64)
        rng = np.random.RandomState(4)
        shared = rng.randint(0, 64, (8,)).tolist()
        prompts = [shared + rng.randint(0, 64, (n,)).tolist()
                   for n in (3, 6)] + [rng.randint(0, 64, (10,)).tolist()]
        _set(prefix=True, chunked=True)
        try:
            eng = serving.Engine(m, max_slots=2, num_blocks=32,
                                 block_size=4, prefill_chunk=4)
            ids = [eng.add_request(p, max_new_tokens=5) for p in prompts]
            outs = eng.run()
            for p, rid in zip(prompts, ids):
                assert outs[rid] == _greedy_ref(m, p, 5)
            assert eng.stats()["decode_compiles"] == 1
        finally:
            _set()
