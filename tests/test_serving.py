"""Serving engine: continuous batching over a paged KV cache.

Oracle: ``GenerationMixin.generate`` greedy output for the same prompts
— the engine must reproduce it token-for-token under continuous
batching with slot reuse, mid-flight arrivals, and preemption.
Kernel oracle: ``masked_decode_attention`` (the dense decode path) —
the ragged paged-attention kernel gathers the same history through the
block table and must match to fp32 tolerance in interpret mode.
"""
import importlib
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.models.generation import decode_mask, masked_decode_attention
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving.kernels.paged_attention import (
    paged_attention_kernel,
    paged_attention_reference,
)
from paddle_tpu.serving.kernels.mla_attention import (
    mla_attention_kernel,
    mla_attention_reference,
)
from paddle_tpu.serving.kv_cache import (
    BlockAllocator,
    KVBlockPool,
    KVPages,
    LatentPages,
    LatentPool,
    PagedKVCache,
)

from serving_checks import serve_an_eos_mid_flight

# the package re-exports a function under the module's name
pa_module = importlib.import_module(
    "paddle_tpu.serving.kernels.paged_attention")


@pytest.fixture(scope="module")
def llama():
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=64, use_parallel=False)
    return LlamaForCausalLM(cfg), cfg


def _greedy_ref(model, prompt, max_new_tokens, eos_token_id=None):
    """generate()'s greedy tokens, truncated at the first eos inclusive
    (the engine stops emitting after eos; generate eos-pads instead)."""
    out = model.generate(
        paddle.to_tensor(np.asarray([prompt], np.int32)),
        max_new_tokens=max_new_tokens, eos_token_id=eos_token_id)
    toks = np.asarray(out._value)[0].tolist()
    if eos_token_id is not None and eos_token_id in toks:
        toks = toks[:toks.index(eos_token_id) + 1]
    return toks


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

class TestPagedAttentionKernel:
    def _random_paged(self, rng, s, h, hkv, d, bs, nb, mb, lens):
        """Scatter per-slot histories into pool pages; returns
        (q, k_pool, v_pool, block_tables, dense_k, dense_v)."""
        q = jnp.asarray(rng.randn(s, h, d), jnp.float32)
        kp = np.zeros((nb, bs, hkv, d), np.float32)
        vp = np.zeros((nb, bs, hkv, d), np.float32)
        bt = np.zeros((s, mb), np.int32)
        alloc = BlockAllocator(nb)
        max_len = mb * bs
        dk = np.zeros((s, max_len, hkv, d), np.float32)
        dv = np.zeros((s, max_len, hkv, d), np.float32)
        for i in range(s):
            L = lens[i]
            pages = alloc.alloc(-(-L // bs)) if L else []
            bt[i, :len(pages)] = pages
            hist_k = rng.randn(L, hkv, d).astype(np.float32)
            hist_v = rng.randn(L, hkv, d).astype(np.float32)
            dk[i, :L], dv[i, :L] = hist_k, hist_v
            for pos in range(L):
                kp[pages[pos // bs], pos % bs] = hist_k[pos]
                vp[pages[pos // bs], pos % bs] = hist_v[pos]
        return (q, jnp.asarray(kp), jnp.asarray(vp), bt,
                jnp.asarray(dk), jnp.asarray(dv))

    def test_parity_vs_masked_decode_attention(self):
        """Acceptance pin: interpret-mode Pallas kernel vs the dense
        decode path generation.py uses, <= 1e-5 fp32."""
        rng = np.random.RandomState(0)
        s, h, d, bs, nb, mb = 4, 4, 16, 4, 32, 8
        lens = [13, 32, 1, 7]
        q, kp, vp, bt, dk, dv = self._random_paged(
            rng, s, h, h, d, bs, nb, mb, lens)
        got = np.asarray(paged_attention_kernel(
            q, kp, vp, bt, np.asarray(lens, np.int32), interpret=True))
        for i in range(s):
            L = lens[i]
            # dense oracle: q is the token AT position L-1 over a cache
            # holding positions 0..L-1
            ref = masked_decode_attention(
                q[i][None, None], dk[i][None], dv[i][None],
                decode_mask(L - 1, 1, dk.shape[1]))
            ref = np.asarray(ref._value if hasattr(ref, "_value") else ref)
            np.testing.assert_allclose(got[i], ref[0, 0], atol=1e-5,
                                       err_msg="slot %d" % i)

    def test_kernel_matches_reference_gqa(self):
        """Pallas interpret vs the jnp gather fallback under GQA
        (pool stores 2 kv heads, q has 8)."""
        rng = np.random.RandomState(1)
        s, h, hkv, d, bs, nb, mb = 3, 8, 2, 16, 8, 16, 4
        lens = [9, 16, 3]
        q, kp, vp, bt, _, _ = self._random_paged(
            rng, s, h, hkv, d, bs, nb, mb, lens)
        a = np.asarray(paged_attention_kernel(
            q, kp, vp, bt, np.asarray(lens, np.int32), interpret=True))
        b = np.asarray(paged_attention_reference(
            q, kp, vp, bt, np.asarray(lens, np.int32)))
        np.testing.assert_allclose(a, b, atol=1e-5)

    def test_idle_slot_emits_finite_zero(self):
        """len-0 slots (idle) skip every page: output exactly 0 — and
        never NaN, which would poison the batched decode step."""
        rng = np.random.RandomState(2)
        q, kp, vp, bt, _, _ = self._random_paged(
            rng, 2, 4, 4, 16, 4, 8, 2, [5, 0])
        out = np.asarray(paged_attention_kernel(
            q, kp, vp, bt, np.asarray([5, 0], np.int32), interpret=True))
        assert np.isfinite(out).all()
        np.testing.assert_array_equal(out[1], 0.0)

    def test_trash_page_isolated(self):
        """Writes landing in page 0 (trash) must not change any live
        slot's attention output."""
        rng = np.random.RandomState(3)
        s, h, d, bs, nb, mb = 2, 4, 16, 4, 8, 2
        lens = [6, 4]
        q, kp, vp, bt, _, _ = self._random_paged(
            rng, s, h, h, d, bs, nb, mb, lens)
        base = np.asarray(paged_attention_kernel(
            q, kp, vp, bt, np.asarray(lens, np.int32), interpret=True))
        kp2 = kp.at[0].set(1e4)
        vp2 = vp.at[0].set(-1e4)
        noisy = np.asarray(paged_attention_kernel(
            q, kp2, vp2, bt, np.asarray(lens, np.int32), interpret=True))
        np.testing.assert_array_equal(base, noisy)


    # -- the page group ----------------------------------------------------
    # A loop trip handles G pages; G follows a VMEM budget, which the
    # tests shrink so that a few tiny pages make a group.

    BS, GROUP, D, NB = 4, 4, 16, 96          # G * bs = 16 tokens a trip

    @pytest.fixture
    def small_groups(self, monkeypatch):
        """Make ``GROUP`` float32 pages of 2 kv heads one group."""
        def pin(hkv, itemsize=4):
            monkeypatch.setattr(
                pa_module, "_KV_VMEM_BUDGET",
                4 * self.GROUP * self.BS * hkv * self.D * itemsize)
        return pin

    def _grouped(self, rng, h, hkv, mb, lens, order, dtype=jnp.float32):
        """Random pools with every page written (so anything read past a
        length shows), and block tables in the given page order."""
        bs, d, nb = self.BS, self.D, self.NB
        s = len(lens)
        q = jnp.asarray(rng.randn(s, h, d), dtype)
        kp = jnp.asarray(rng.randn(nb, bs, hkv, d), dtype)
        vp = jnp.asarray(rng.randn(nb, bs, hkv, d), dtype)
        need = [-(-n // bs) for n in lens]
        if order == "interleaved":       # slot i owns 1+i, 1+i+s, ...
            owned = [[1 + i + j * s for j in range(n)]
                     for i, n in enumerate(need)]
        else:
            owned, nxt = [], 1
            for n in need:
                owned.append(list(range(nxt, nxt + n)))
                nxt += n
            if order == "descending":
                owned = [pages[::-1] for pages in owned]
            elif order == "shuffled":
                perm = rng.permutation(np.arange(1, nb))
                owned = [[int(perm[p - 1]) for p in pages]
                         for pages in owned]
        bt = np.zeros((s, mb), np.int32)
        for i, pages in enumerate(owned):
            bt[i, :len(pages)] = pages
        return q, kp, vp, bt, np.asarray(lens, np.int32), owned

    # G*bs = 16 and mb*bs = 40: mb = 10 is not a multiple of G = 4
    @pytest.mark.parametrize("h,hkv,mb,lens,order", [
        (4, 4, 10, [0, 1, 0], "ascending"),
        (4, 4, 10, [15, 16, 17], "ascending"),
        (4, 4, 10, [40, 31, 32, 33], "ascending"),
        (8, 2, 10, [0, 1, 15, 16, 17, 40], "ascending"),
        (8, 2, 10, [15, 16, 17, 40], "descending"),
        (8, 2, 10, [17, 40, 1, 16], "interleaved"),
        (4, 4, 10, [33, 0, 40, 5], "shuffled"),
        (8, 2, 8, [32, 31, 17, 0], "shuffled"),       # mb a multiple of G
        (8, 2, 3, [12, 9, 1], "descending"),          # G capped at mb
        (6, 3, 10, [16, 40, 7], "interleaved"),       # odd kv heads
    ], ids=["len_0_1", "mha_around_one_group", "mha_last_partial_group",
            "gqa_every_boundary", "gqa_descending_pages",
            "gqa_interleaved_pages", "mha_shuffled_pages",
            "gqa_mb_multiple_of_group", "gqa_group_capped_at_mb",
            "gqa_three_kv_heads"])
    def test_groups_match_reference(self, small_groups, h, hkv, mb, lens,
                                    order):
        """Lengths on both sides of a group's edge, a last group that is
        partial, block tables in any order: the kernel equals the gather
        reference to fp32 tolerance; idle slots are exact zeros."""
        small_groups(hkv)
        assert pa_module._pages_per_group(
            self.BS, hkv, self.D, 4, mb) == min(self.GROUP, mb)
        rng = np.random.RandomState(len(lens) * 131 + h + mb)
        q, kp, vp, bt, ln, _ = self._grouped(rng, h, hkv, mb, lens, order)
        got = np.asarray(paged_attention_kernel(q, kp, vp, bt, ln,
                                                interpret=True))
        want = np.asarray(paged_attention_reference(q, kp, vp, bt, ln))
        live = ln > 0
        np.testing.assert_allclose(got[live], want[live], atol=1e-5)
        np.testing.assert_array_equal(got[~live], 0.0)

    def test_nothing_past_the_length_reaches_the_result(self,
                                                        small_groups):
        """NaN in every page a slot does not own, in its own last page
        past its length and in the trash page: that slot's output is
        finite and bit-equal to the clean run's, and an idle slot is
        still exactly zero."""
        hkv, mb = 2, 10
        small_groups(hkv)
        lens = [17, 16, 5, 0, 40]
        rng = np.random.RandomState(11)
        q, kp, vp, bt, ln, owned = self._grouped(
            rng, 8, hkv, mb, lens, "shuffled")
        clean = np.asarray(paged_attention_kernel(q, kp, vp, bt, ln,
                                                  interpret=True))
        assert np.isfinite(clean).all()
        for i, n in enumerate(lens):
            keep = np.zeros((self.NB, self.BS), bool)
            for j, page in enumerate(owned[i]):
                keep[page, :max(0, min(self.BS, n - j * self.BS))] = True
            assert keep.sum() == n and not keep[0].any()
            poison = jnp.asarray(~keep)[:, :, None, None]
            noisy = np.asarray(paged_attention_kernel(
                q, jnp.where(poison, jnp.nan, kp),
                jnp.where(poison, jnp.nan, vp), bt, ln, interpret=True))
            assert np.isfinite(noisy[i]).all(), "slot %d" % i
            np.testing.assert_array_equal(noisy[i], clean[i],
                                          err_msg="slot %d" % i)

    def test_bf16_pool_matches_reference(self, small_groups):
        """A bf16 pool goes to the dots as stored, two kv heads to a
        32-bit word: equal to the reference at chip_smoke.py's
        tolerance."""
        hkv, mb = 2, 10
        small_groups(hkv, itemsize=2)
        lens = [0, 1, 15, 16, 17, 40]
        rng = np.random.RandomState(12)
        q, kp, vp, bt, ln, _ = self._grouped(
            rng, 8, hkv, mb, lens, "shuffled", dtype=jnp.bfloat16)
        assert pa_module._heads_per_word(kp.dtype, hkv) == 2
        got = np.asarray(paged_attention_kernel(
            q, kp, vp, bt, ln, interpret=True), np.float32)
        want = np.asarray(paged_attention_reference(q, kp, vp, bt, ln),
                          np.float32)
        live = ln > 0
        np.testing.assert_allclose(got[live], want[live], atol=2e-2,
                                   rtol=2e-2)
        np.testing.assert_array_equal(got[~live], 0.0)

    @pytest.mark.parametrize("bs,hkv,d,itemsize,mb,want", [
        (16, 8, 128, 2, 160, 32),       # the chat-backlog cell: 512 tokens
        (16, 8, 128, 2, 12, 12),        # never more than a slot has
        (16, 16, 128, 4, 128, 8),       # fp32 MHA pages are 4x the bytes
        (16, 8, 128, 1, 160, 64),       # int8 pages half of bf16's
        (64, 64, 256, 4, 64, 1),        # a page over the budget: one
    ], ids=["cell", "capped_at_mb", "f32_mha", "int8", "huge_page"])
    def test_pages_per_group_follows_the_page_bytes(self, bs, hkv, d,
                                                    itemsize, mb, want):
        assert pa_module._pages_per_group(bs, hkv, d, itemsize,
                                          mb) == want


# ---------------------------------------------------------------------------
# engine vs generate parity
# ---------------------------------------------------------------------------

class TestLatentDecodeKernel:
    """``mla_decode`` in interpret mode against its jnp reference: one
    shared row a token, every head's absorbed query against it, values
    from its first ``rank`` columns."""

    @staticmethod
    def _case(seed, s, h, rank, rope, bs, nb, mb, lens, dtype):
        rng = np.random.RandomState(seed)
        width = -(-(rank + rope) // 128) * 128
        pool = np.zeros((nb, bs, width), np.float32)
        # page 0 is trash: anything at all, it is never a live page
        pool[0] = 1e3 * rng.randn(bs, width)
        q = np.zeros((s, h, width), np.float32)
        q[..., :rank + rope] = rng.randn(s, h, rank + rope)
        bt = np.zeros((s, mb), np.int32)
        alloc = BlockAllocator(nb)
        for i, n in enumerate(lens):
            pages = alloc.alloc(-(-n // bs)) if n else []
            bt[i, :len(pages)] = pages
            for page in pages:
                pool[page, :, :rank + rope] = rng.randn(bs, rank + rope)
        return (jnp.asarray(q, dtype), jnp.asarray(pool, dtype), bt,
                np.asarray(lens, np.int32))

    @pytest.mark.parametrize("lens,bs,mb,dtype,tol,pages_a_trip", [
        # ragged, idle slots, one token, exact page and group boundaries
        ([0, 1, 16, 17, 100, 128], 16, 8, jnp.float32, 2e-5, None),
        # several trips a slot (38 pages, 8 a trip; 37 pages: the last
        # trip short by three), idle slots between
        ([600, 0, 0, 333, 592], 16, 40, jnp.float32, 2e-5, 8),
        ([257, 0, 512, 0], 16, 40, jnp.float32, 2e-5, 4),
        ([5, 0, 77, 128], 16, 8, jnp.bfloat16, 2e-2, None),
        ([9, 3, 0, 24], 8, 4, jnp.float32, 2e-5, 2),
    ])
    def test_kernel_matches_reference(self, monkeypatch, lens, bs, mb,
                                      dtype, tol, pages_a_trip):
        if pages_a_trip:
            mla = importlib.import_module(
                "paddle_tpu.serving.kernels.mla_attention")
            monkeypatch.setattr(mla, "_PAGE_VMEM_BUDGET",
                                2 * pages_a_trip * bs * 256
                                * jnp.dtype(dtype).itemsize)
        q, pool, bt, ln = self._case(len(lens), len(lens), 8, 128, 64, bs,
                                     8 + sum(-(-n // bs) for n in lens),
                                     mb, lens, dtype)
        got = mla_attention_kernel(q, pool, bt, ln, scale=0.07, rank=128,
                                   interpret=True)
        want = mla_attention_reference(q, pool, bt, ln, scale=0.07,
                                       rank=128)
        assert got.shape == (len(lens), 8, 128)
        live = ln > 0
        np.testing.assert_allclose(
            np.asarray(got, np.float32)[live],
            np.asarray(want, np.float32)[live], atol=tol)
        # an idle slot ran no trip: exact zeros, whatever trash holds
        assert not np.asarray(got, np.float32)[~live].any()

    def test_reference_is_attention_over_the_expanded_heads(self):
        """The absorbed form against attention written the long way:
        k_h = [row[:rank] W_uk,h | row[rank:]], v_h = row[:rank] W_uv,h."""
        rng = np.random.RandomState(3)
        s, h, rank, rope, nope, dv, n = 1, 4, 128, 64, 16, 16, 21
        _, pool, bt, ln = self._case(3, s, h, rank, rope, 16, 8, 4, [n],
                                     jnp.float32)
        w_uk = rng.randn(rank, h, nope).astype(np.float32) * 0.1
        w_uv = rng.randn(rank, h, dv).astype(np.float32) * 0.1
        q_nope = rng.randn(h, nope).astype(np.float32)
        q_pe = rng.randn(h, rope).astype(np.float32)
        q_lat = np.zeros((1, h, 256), np.float32)
        q_lat[0, :, :rank] = np.einsum("hn,rhn->hr", q_nope, w_uk)
        q_lat[0, :, rank:rank + rope] = q_pe
        got = np.asarray(mla_attention_reference(
            jnp.asarray(q_lat), pool, bt, ln, scale=0.11, rank=rank))[0]
        got = np.einsum("hr,rhv->hv", got, w_uv)
        rows = np.asarray(pool)[bt[0, :2]].reshape(32, 256)[:n]
        k_nope = np.einsum("tr,rhn->thn", rows[:, :rank], w_uk)
        v = np.einsum("tr,rhv->thv", rows[:, :rank], w_uv)
        scores = 0.11 * (np.einsum("hn,thn->ht", q_nope, k_nope)
                         + q_pe @ rows[:, rank:rank + rope].T)
        p = np.exp(scores - scores.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(got, np.einsum("ht,thv->hv", p, v),
                                   rtol=1e-4, atol=1e-5)


class TestALayerThatKeepsNothing:
    """A spec entry of kind ``nothing`` (a layer that is one expert or
    MLP block) through ``PagedKVCache`` and both view builders: no pool,
    a hook nobody calls, and the other layers' bookkeeping as it was."""

    @staticmethod
    def _cache():
        from paddle_tpu.serving.kv_cache import NoCache, SlotState

        state = SlotState((("state", (2, 4, 8), "float32"),
                           ("conv", (3, 10), "float32")))
        return PagedKVCache(
            [state, NoCache(), KVPages(2, 8, "float32"), NoCache()],
            num_blocks=12, block_size=4, max_slots=3,
            max_blocks_per_slot=6)

    def test_pools_by_kind(self):
        cache = self._cache()
        assert [spec.kind for spec in cache.layers] == [
            "slot_state", "nothing", "kv_pages", "nothing"]
        assert cache.has_slot_state and not cache.has_latent
        assert cache.pools[1] is None and cache.pools[3] is None
        assert isinstance(cache.pools[2], KVBlockPool)
        assert cache.state_stats() == {
            "slots": 3, "layers": 1, "slot_bytes": (64 + 30) * 4,
            "pool_bytes": 3 * (64 + 30) * 4}
        # a layer without a pool is an empty node of the pools' tree:
        # nothing of it is donated, copied or reset
        leaves = jax.tree_util.tree_leaves(cache.pools)
        assert len(leaves) == 2 + 2
        assert cache.pools_alive()
        cache.reset_pools()
        assert cache.pools[1] is None

    def test_both_view_builders_and_the_engines_read_back(self):
        from paddle_tpu.serving.kv_cache import NoView

        cache = self._cache()
        assert cache.ensure_capacity(1, 7)
        row = jnp.asarray(cache.block_tables[1])
        views = cache.prefill_views(cache.pools, row, jnp.int32(6))
        assert [type(v).__name__ for v in views] == [
            "StatePrefillView", "NoView", "PagedPrefillView", "NoView"]
        assert isinstance(views[1], NoView) and views[1].pool is None
        lens = jnp.asarray([0, 6, 0], jnp.int32)
        dviews = cache.decode_views(cache.pools,
                                    jnp.asarray(cache.block_tables), lens)
        assert [type(v).__name__ for v in dviews] == [
            "StateDecodeView", "NoView", "PagedDecodeView", "NoView"]
        # what the engine's steps hand back: the pools, layer by layer
        back = [v.pool for v in dviews]
        assert back[1] is None and back[3] is None
        assert jax.tree_util.tree_structure(back) \
            == jax.tree_util.tree_structure(cache.pools)
        # release and copy-on-write walk past the empty entries
        assert cache.make_writable(1, 0, 7)
        cache.release_slot(1)
        assert cache.seq_lens[1] == 0

    def test_a_kernels_rows_are_stored_as_they_come(self):
        """``StateDecodeView.write(kept=...)``: the named array is not
        selected over again; the others are."""
        from paddle_tpu.serving.kv_cache import StateDecodeView

        pool = {"state": jnp.zeros((3, 2)), "conv": jnp.zeros((3, 2))}
        ones = {"state": jnp.ones((3, 2)), "conv": jnp.ones((3, 2))}
        active = jnp.asarray([True, False, True])
        plain = StateDecodeView(pool, active).write(ones).pool
        assert np.asarray(plain["state"])[:, 0].tolist() == [1, 0, 1]
        kept = StateDecodeView(pool, active).write(
            ones, kept=("state",)).pool
        assert kept["state"] is ones["state"]
        assert np.asarray(kept["conv"])[:, 0].tolist() == [1, 0, 1]


class TestLatentPagesBesideKVPages:
    """One ``PagedKVCache`` holding both paged kinds: a page id means
    the same page in every layer's pool, whatever the layer keeps
    there."""

    @staticmethod
    def _cache(num_blocks=12):
        return PagedKVCache(
            [KVPages(2, 8, "float32"), LatentPages(40, "float32"),
             KVPages(2, 8, "float32")], num_blocks=num_blocks,
            block_size=4, max_slots=3, max_blocks_per_slot=6)

    def test_pools_by_kind(self):
        cache = self._cache()
        assert cache.has_latent and not cache.has_slot_state
        assert isinstance(cache.pools[0], KVBlockPool)
        assert isinstance(cache.pools[1], LatentPool)
        # 40 values a token, a row of whole 128-lane tiles in the pool
        assert cache.pools[1].rows.shape == (12, 4, 128)
        assert cache.block_tables.shape == (3, 6)
        assert cache.latent_stats() == {
            "layers": 1, "row_bytes": 512, "pool_bytes": 12 * 4 * 512}

    def test_allocation_release_and_regrowth_share_one_allocator(self):
        cache = self._cache(num_blocks=8)
        free = cache.allocator.free_blocks
        assert cache.ensure_capacity(0, 9) and cache.ensure_capacity(1, 4)
        assert cache.allocator.free_blocks == free - 4
        pages = list(cache.slot_pages(0))
        assert cache.block_tables[0, :3].tolist() == pages
        # out of pages: nothing allocated, the caller preempts
        assert not cache.ensure_capacity(2, 4 * 6)
        assert cache.allocator.free_blocks == free - 4
        cache.release_slot(0)
        assert cache.allocator.free_blocks == free - 1
        assert not cache.block_tables[0].any() and cache.seq_lens[0] == 0
        # the preempted request's re-prefill takes pages anew
        assert cache.ensure_capacity(0, 9)
        assert sorted(cache.slot_pages(0)) == sorted(pages)

    def test_prefill_and_decode_views_write_both_kinds_through_one_table(
            self):
        cache = self._cache()
        assert cache.ensure_capacity(1, 7)
        row = jnp.asarray(cache.block_tables[1])
        rng = np.random.RandomState(0)
        rows = jnp.asarray(rng.randn(1, 8, 40), jnp.float32)
        views = cache.prefill_views(cache.pools, row, jnp.int32(6))
        assert [type(v).__name__ for v in views] == [
            "PagedPrefillView", "LatentPrefillView", "PagedPrefillView"]
        assert views[1].absorbed is False
        q = jnp.asarray(rng.randn(1, 8, 2, 12), jnp.float32)
        v = jnp.asarray(rng.randn(1, 8, 2, 8), jnp.float32)
        after = views[1].update(rows)
        ctx = after.attend(q, q, v, 0.3)
        assert np.asarray(getattr(ctx, "_value", ctx)).shape == (1, 8, 2, 8)
        pages = cache.slot_pages(1)
        plane = np.asarray(after.pool.rows)
        np.testing.assert_array_equal(plane[pages[0], :, :40],
                                      np.asarray(rows)[0, :4])
        np.testing.assert_array_equal(plane[pages[1], :, :40],
                                      np.asarray(rows)[0, 4:])
        assert not plane[:, :, 40:].any()
        # a decode step: slot 1 at length 6 rewrites position 6, slot 0
        # and 2 are idle and write trash
        pools = [cache.pools[0], after.pool, cache.pools[2]]
        lens = jnp.asarray([0, 6, 0], jnp.int32)
        dviews = cache.decode_views(pools, jnp.asarray(cache.block_tables),
                                    lens)
        assert dviews[1].absorbed is True
        new = jnp.asarray(rng.randn(3, 1, 40), jnp.float32)
        q_lat = jnp.asarray(rng.randn(3, 1, 2, 40), jnp.float32)
        dafter = dviews[1].update(new)
        ctx = dafter.attend(q_lat, 0.3, 32)
        assert np.asarray(ctx._value).shape == (3, 1, 2, 32)
        plane = np.asarray(dafter.pool.rows)
        np.testing.assert_array_equal(plane[pages[1], 2, :40],
                                      np.asarray(new)[1, 0])
        np.testing.assert_array_equal(plane[pages[1], 1, :40],
                                      np.asarray(rows)[0, 5])
        # the context is attention over positions 0..6 of slot 1's rows
        hist = np.concatenate([np.asarray(rows)[0, :6],
                               np.asarray(new)[1]], 0)
        scores = 0.3 * np.asarray(q_lat)[1, 0] @ hist.T
        p = np.exp(scores - scores.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(ctx._value)[1, 0],
                                   p @ hist[:, :32], rtol=1e-4, atol=1e-5)


class TestEngineParity:
    def test_mixed_arrival_matches_generate(self, llama):
        """The acceptance workload: staggered prompt lengths, an early
        EOS, and arrivals mid-flight, through 2 slots with slot reuse —
        per-request tokens must exactly match generate()'s greedy output."""
        m, cfg = llama
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, cfg.vocab_size, (n,)).tolist()
                   for n in (5, 9, 3, 12, 7)]
        eng = serving.Engine(m, max_slots=2, num_blocks=64, block_size=4)
        # pick an eos that actually fires early for prompt[1]
        probe = _greedy_ref(m, prompts[1], 8)
        eos = probe[2]

        ids, plan = {}, []
        ids[0] = eng.add_request(prompts[0], max_new_tokens=6)
        ids[1] = eng.add_request(prompts[1], max_new_tokens=8,
                                 eos_token_id=eos)
        plan.append((0, 6, None))
        plan.append((1, 8, eos))
        eng.step()
        eng.step()
        # arrivals mid-flight, while slots are decoding
        ids[2] = eng.add_request(prompts[2], max_new_tokens=5)
        ids[3] = eng.add_request(prompts[3], max_new_tokens=4)
        plan.append((2, 5, None))
        plan.append((3, 4, None))
        eng.step()
        ids[4] = eng.add_request(prompts[4], max_new_tokens=6)
        plan.append((4, 6, None))
        while eng.step():
            pass

        for pi, mnt, e in plan:
            ref = _greedy_ref(m, prompts[pi], mnt, e)
            assert eng.output(ids[pi]) == ref, "request %d" % pi
        stats = eng.stats()
        assert stats["requests_finished"] == 5
        assert stats["decode_compiles"] == 1

    def test_slot_reuse_on_eos(self, llama):
        """More requests than slots: finished slots must be reclaimed
        (all requests complete) without growing the batch shape."""
        m, cfg = llama
        rng = np.random.RandomState(7)
        prompts = [rng.randint(0, cfg.vocab_size, (4 + i,)).tolist()
                   for i in range(6)]
        eng = serving.Engine(m, max_slots=2, num_blocks=64, block_size=4)
        ids = [eng.add_request(p, max_new_tokens=4) for p in prompts]
        outs = eng.run()
        for p, rid in zip(prompts, ids):
            assert outs[rid] == _greedy_ref(m, p, 4)
        assert eng.stats()["decode_compiles"] == 1


# ---------------------------------------------------------------------------
# one step ahead of the device
# ---------------------------------------------------------------------------

class TestStepAhead:
    def test_step_n_plus_1_goes_out_before_step_n_is_read(self, llama):
        """A spy on the dispatches and the readbacks: each decode step
        is dispatched while the one before it is still on the device,
        except where the batch ran dry in between, and the engine's own
        account says so."""
        m, cfg = llama
        eng = serving.Engine(m, max_slots=3, num_blocks=64, block_size=4)
        run_eval, readback = eng._run_eval, eng._readback
        # id -> (step, its tokens): held, so that no id is reused
        steps, events = {}, []

        def spy_run_eval(fn, *args):
            out = run_eval(fn, *args)
            if fn is eng._decode:
                steps[id(out[0])] = (len(steps), out[0])
                events.append(("dispatch", len(steps) - 1))
            return out

        def spy_readback(out):
            if id(out) in steps:
                events.append(("read", steps[id(out)][0]))
            return readback(out)

        eng._run_eval, eng._readback = spy_run_eval, spy_readback
        rng = np.random.RandomState(5)
        work = [(rng.randint(0, cfg.vocab_size, (n,)).tolist(), new)
                for n, new in ((5, 12), (9, 15), (3, 10), (7, 14),
                               (6, 11), (4, 13))]
        rids = [eng.add_request(p, max_new_tokens=new) for p, new in work]
        outs = eng.run()
        for (p, new), rid in zip(work, rids):
            assert outs[rid] == _greedy_ref(m, p, new)
        at = {e: i for i, e in enumerate(events)}
        n = len(steps)
        assert sorted(at) == sorted([("dispatch", k) for k in range(n)]
                                    + [("read", k) for k in range(n)])
        ahead = sum(at[("dispatch", k + 1)] < at[("read", k)]
                    for k in range(n - 1))
        stats = eng.stats()
        assert stats["ahead"] == {"steps": n, "ahead": ahead,
                                  "discarded_rows": 0,
                                  "share": 100.0 * ahead / n}
        assert stats["ahead"]["share"] >= 90
        assert stats["decode_steps"] == n and stats["decode_compiles"] == 1

    def test_an_eos_mid_flight_is_discarded_and_its_slot_reused(self,
                                                                 llama):
        m, cfg = llama
        rng = np.random.RandomState(6)
        prompts = [rng.randint(0, cfg.vocab_size, (n,)).tolist()
                   for n in (5, 7, 6)]
        eng = serving.Engine(m, max_slots=2, num_blocks=64, block_size=4)
        eos = None
        for i, (p, out) in enumerate(
                serve_an_eos_mid_flight(eng, prompts, 8)):
            if i == 0:
                eos = out[-1]
            assert out == _greedy_ref(m, p, 8, eos if i == 0 else None)

    def test_no_stats_before_the_first_decode_step(self, llama):
        m, _ = llama
        eng = serving.Engine(m, max_slots=2, num_blocks=16, block_size=4)
        assert eng.stats()["ahead"] is None
        eng.add_request([1, 2, 3], max_new_tokens=1)
        eng.run()       # a prefill alone: no decode step
        assert eng.stats()["ahead"] is None


# ---------------------------------------------------------------------------
# edge cases (ISSUE satellite: exhaustion/preempt, zero-length, long
# prompt, compile-once under a staggered 20-request workload)
# ---------------------------------------------------------------------------

class TestServingEdgeCases:
    def test_preempt_requeue_bit_identical(self, llama):
        """Block-pool exhaustion preempts the youngest other request and
        requeues it by recompute — its final tokens must be bit-identical
        to an uncontended run."""
        m, cfg = llama
        rng = np.random.RandomState(1)
        prompts = [rng.randint(0, cfg.vocab_size, (n,)).tolist()
                   for n in (6, 8)]

        starved = serving.Engine(m, max_slots=2, num_blocks=7,
                                 block_size=4)
        sid = [starved.add_request(p, max_new_tokens=10) for p in prompts]
        souts = starved.run()
        assert starved.stats()["preemptions"] >= 1

        roomy = serving.Engine(m, max_slots=2, num_blocks=64, block_size=4)
        rid = [roomy.add_request(p, max_new_tokens=10) for p in prompts]
        routs = roomy.run()
        assert roomy.stats()["preemptions"] == 0

        for a, b in zip(sid, rid):
            assert souts[a] == routs[b]
        # the preempted request's metrics carry the count
        assert sum(starved.requests[i].metrics.preemptions
                   for i in sid) >= 1

    def test_zero_length_generation(self, llama):
        """max_new_tokens=0 finishes immediately: no slot, no pages, no
        decode step — but it still counts as finished."""
        m, _ = llama
        eng = serving.Engine(m, max_slots=2, num_blocks=16, block_size=4)
        rid = eng.add_request([1, 2, 3], max_new_tokens=0)
        assert not eng.has_work()
        assert eng.run() == {rid: []}
        assert eng.stats()["decode_steps"] == 0
        assert eng.stats()["requests_finished"] == 1
        assert eng.cache.allocator.free_blocks == 15  # nothing allocated

    def test_prefill_bucket_respects_block_table(self, llama):
        """Regression: with block_size < 8 and an unaligned
        max_model_len, the pow2 prefill bucket used to exceed
        ``MB * block_size`` — the pad scatter's clamped gather then
        overwrote the request's LAST REAL PAGE and decode silently
        diverged from generate()."""
        m, cfg = llama
        for seed in range(3):
            prompt = np.random.RandomState(seed).randint(
                0, cfg.vocab_size, (9,)).tolist()
            eng = serving.Engine(m, max_slots=1, num_blocks=16,
                                 block_size=4, max_model_len=11)
            assert (eng._bucket(9)
                    <= eng.cache.max_blocks_per_slot * eng.block_size)
            rid = eng.add_request(prompt, max_new_tokens=2)
            assert eng.run()[rid] == _greedy_ref(m, prompt, 2), seed

    def test_prompt_longer_than_block_size(self, llama):
        """A prompt spanning several pages prefills correctly (page
        boundaries inside the prompt)."""
        m, cfg = llama
        rng = np.random.RandomState(2)
        prompt = rng.randint(0, cfg.vocab_size, (11,)).tolist()  # 3 pages
        eng = serving.Engine(m, max_slots=1, num_blocks=16, block_size=4)
        rid = eng.add_request(prompt, max_new_tokens=5)
        assert eng.run()[rid] == _greedy_ref(m, prompt, 5)

    def test_oversized_request_rejected(self, llama):
        """A request that could never fit (pool or position table) is
        refused at add time, not deadlocked at schedule time."""
        m, _ = llama
        eng = serving.Engine(m, max_slots=1, num_blocks=4, block_size=4)
        with pytest.raises(ValueError):
            eng.add_request(list(range(10)), max_new_tokens=10)  # > pool
        eng2 = serving.Engine(m, max_slots=1, num_blocks=64, block_size=4)
        with pytest.raises(ValueError):
            eng2.add_request(list(range(40)), max_new_tokens=40)  # > 64 pos
        with pytest.raises(ValueError):
            eng2.add_request([], max_new_tokens=4)

    def test_compile_once_20_staggered_requests(self, llama):
        """jit-cache pin: a 20-request staggered workload (varying
        lengths, arrivals spread over the run) compiles the decode step
        EXACTLY once; prefill compiles once per length bucket."""
        m, cfg = llama
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, cfg.vocab_size,
                               (int(rng.randint(2, 14)),)).tolist()
                   for _ in range(20)]
        eng = serving.Engine(m, max_slots=4, num_blocks=64, block_size=4)
        it = iter(prompts)
        for p in [next(it) for _ in range(4)]:
            eng.add_request(p, max_new_tokens=int(rng.randint(2, 6)))
        pending = list(it)
        while eng.has_work() or pending:
            if pending:  # stagger: one arrival per engine step
                eng.add_request(pending.pop(0),
                                max_new_tokens=int(rng.randint(2, 6)))
            eng.step()
        stats = eng.stats()
        assert stats["requests_finished"] == 20
        assert stats["decode_compiles"] == 1, stats
        buckets = {eng._bucket(len(p)) for p in prompts}
        assert stats["prefill_compiles"] == len(buckets), stats

    def test_metrics_schema(self, llama):
        """Plain-dict metrics: per-request latency breakdown populated
        for a finished request; engine counters complete."""
        m, cfg = llama
        eng = serving.Engine(m, max_slots=1, num_blocks=16, block_size=4)
        rid = eng.add_request([3, 1, 4], max_new_tokens=4)
        eng.run()
        rm = eng.request_metrics(rid)
        assert set(rm) == {"queue_time_s", "ttft_s", "tpot_s", "e2e_s",
                           "prompt_tokens", "output_tokens", "preemptions",
                           "prefix_cached_tokens",
                           "prefix_cached_tokens_first"}
        assert rm["prompt_tokens"] == 3 and rm["output_tokens"] == 4
        for k in ("queue_time_s", "ttft_s", "tpot_s", "e2e_s"):
            assert rm[k] is not None and rm[k] >= 0
        es = eng.stats()
        for k in ("requests_in", "requests_finished", "preemptions",
                  "prefill_runs", "decode_steps", "output_tokens",
                  "decode_compiles", "prefill_compiles", "wall_s",
                  "throughput_tok_s", "slot_occupancy"):
            assert k in es
        assert es["requests_finished"] == 1
        assert 0 < es["slot_occupancy"] <= 1


# ---------------------------------------------------------------------------
# the external-cache hook on a second architecture (learned positions)
# ---------------------------------------------------------------------------

class TestGPTServing:
    def test_gpt_engine_matches_generate(self):
        from paddle_tpu.models.gpt import GPTModel

        paddle.seed(11)
        m = GPTModel(vocab_size=64, hidden_size=32, num_layers=2,
                     num_heads=4, max_seq_len=64)
        rng = np.random.RandomState(4)
        prompts = [rng.randint(0, 64, (n,)).tolist() for n in (4, 7, 10)]
        eng = serving.Engine(m, max_slots=2, num_blocks=32, block_size=4)
        ids = [eng.add_request(p, max_new_tokens=5) for p in prompts]
        outs = eng.run()
        for p, rid in zip(prompts, ids):
            assert outs[rid] == _greedy_ref(m, p, 5)
        assert eng.stats()["decode_compiles"] == 1


# ---------------------------------------------------------------------------
# donated-pools failure recovery
# ---------------------------------------------------------------------------

class TestDonatedPoolRecovery:
    """The compiled steps donate their input pools (donate_argnums) —
    a step that raises AFTER execution started leaves cache.pools
    pointing at DELETED buffers. The engine must detect that, reset
    the pool plane, and preempt-by-recompute every occupied slot:
    outputs stay bit-identical to a clean run and a one-step transient
    never becomes permanent engine death."""

    def _poison_after_dispatch(self, eng, attr):
        """Wrap a compiled step so its FIRST call runs the real jit
        (consuming the donated pools) and then raises — the
        post-dispatch failure mode fault injection (which fires before
        the call) cannot produce."""
        real = getattr(eng, attr)
        state = {"fired": False}

        def wrapper(*args):
            out = real(*args)
            if not state["fired"]:
                state["fired"] = True
                raise RuntimeError("post-dispatch transient")
            return out

        setattr(eng, attr, wrapper)
        return state

    def test_split_decode_recovers_bit_identical(self, llama):
        model, _cfg = llama
        rng = np.random.RandomState(9)
        prompts = [rng.randint(1, 64, (n,)).tolist() for n in (5, 9, 3)]
        clean = serving.Engine(model, max_slots=3, num_blocks=64,
                               block_size=4)
        ids = [clean.add_request(p, max_new_tokens=6) for p in prompts]
        want = clean.run()
        eng = serving.Engine(model, max_slots=3, num_blocks=64,
                             block_size=4)
        ids2 = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        state = self._poison_after_dispatch(eng, "_decode")
        got = eng.run()
        assert state["fired"]
        assert [got[i] for i in ids2] == [want[i] for i in ids]
        st = eng.stats()
        assert st["preemptions"] >= 3     # every occupied slot requeued
        assert st["requests_finished"] == 3
        assert eng.cache.pools_alive()

    def test_recovery_requeue_preserves_fcfs_order(self, llama):
        """The recovery requeue uses appendleft in REVERSE slot order
        (the _on_decode_failure idiom) so the survivors re-admit
        strictly FCFS — earliest-admitted request back at the queue
        head, not the tail-end slot."""
        model, _cfg = llama
        eng = serving.Engine(model, max_slots=3, num_blocks=64,
                             block_size=4)
        rng = np.random.RandomState(11)
        ids = [eng.add_request(rng.randint(1, 64, (n,)).tolist(),
                               max_new_tokens=4) for n in (5, 7, 3)]
        eng.step()                        # admit + prefill all three
        for p in eng.cache.pools:         # simulate a post-dispatch
            p.k.delete()                  # failure consuming the
            p.v.delete()                  # donated pools
        eng._recover_consumed_pools()
        assert [r.id for r in eng.scheduler.queue] == ids
        assert eng.cache.pools_alive()

    def test_mixed_step_with_prefix_cache_recovers(self, llama):
        model, _cfg = llama
        paddle.set_flags({"FLAGS_serving_prefix_cache": True,
                          "FLAGS_serving_chunked_prefill": True})
        try:
            rng = np.random.RandomState(10)
            shared = rng.randint(1, 64, (8,)).tolist()
            prompts = [shared + rng.randint(1, 64, (n,)).tolist()
                       for n in (4, 6, 2)]
            clean = serving.Engine(model, max_slots=3, num_blocks=64,
                                   block_size=4)
            ids = [clean.add_request(p, max_new_tokens=6)
                   for p in prompts]
            want = clean.run()
            eng = serving.Engine(model, max_slots=3, num_blocks=64,
                                 block_size=4)
            ids2 = [eng.add_request(p, max_new_tokens=6)
                    for p in prompts]
            state = self._poison_after_dispatch(eng, "_mixed")
            got = eng.run()
            assert state["fired"]
            assert [got[i] for i in ids2] == [want[i] for i in ids]
            # the rebuilt prefix cache serves the fresh pools, not the
            # dead ones: the tree must be consistent with a live pool
            assert eng.cache.pools_alive()
            assert eng.stats()["decode_compiles"] == 1
        finally:
            paddle.set_flags({"FLAGS_serving_prefix_cache": False,
                              "FLAGS_serving_chunked_prefill": False})


# ---------------------------------------------------------------------------
# generate_step(..., logits_at=): the head runs on the rows that are read
# ---------------------------------------------------------------------------

# sizes no two of which are equal, so a shape names what it holds
HEAD_VOCAB, HEAD_HIDDEN, HEAD_BUCKET = 80, 32, 16


def _head_model(kind):
    paddle.seed(3)
    if kind == "llama":
        return LlamaForCausalLM(LlamaConfig(
            vocab_size=HEAD_VOCAB, hidden_size=HEAD_HIDDEN,
            intermediate_size=48, num_hidden_layers=2,
            num_attention_heads=4, max_position_embeddings=64,
            use_parallel=False))
    if kind == "gpt":
        from paddle_tpu.models.gpt import GPTModel

        return GPTModel(vocab_size=HEAD_VOCAB, hidden_size=HEAD_HIDDEN,
                        num_layers=2, num_heads=4, max_seq_len=64)
    from paddle_tpu.models import qwen3_next as qn

    return qn.Qwen3NextForCausalLM(qn.Qwen3NextConfig.tiny(
        vocab_size=HEAD_VOCAB))


@pytest.fixture(scope="module", params=["llama", "gpt", "qwen3_next"])
def head_engine(request):
    return serving.Engine(_head_model(request.param), max_slots=3,
                          num_blocks=32, block_size=4, max_model_len=64)


class TestLogitsAt:
    """The protocol: ``logits_at`` [B] names one row a sequence and the
    model returns logits [B, 1, vocab] of those rows, the full logits'
    own to float32 rounding (a one-row matmul may accumulate in another
    order): within 1e-6 of the largest |logit|."""

    @staticmethod
    def _same(one, full_rows):
        np.testing.assert_allclose(
            one, full_rows, rtol=0,
            atol=1e-6 * float(np.abs(full_rows).max()))

    def _logits(self, eng, views_of, ids, logits_at):
        from paddle_tpu.core.dispatch import no_grad
        from paddle_tpu.core.tensor import Tensor

        model = eng.model

        def step(vals, pools, ids, logits_at):
            with model.bind_state(eng._names, list(vals)), no_grad():
                logits, _ = model.generate_step(
                    Tensor(ids), views_of(pools), 0, logits_at)
            return logits._value

        return np.asarray(eng._run_eval(
            jax.jit(step), eng._state_vals, eng.cache.pools,
            jnp.asarray(ids), logits_at))

    @pytest.mark.parametrize("true_len", [HEAD_BUCKET, 11],
                             ids=["fills_its_bucket", "short_of_it"])
    def test_a_prefill_reads_its_last_real_row(self, head_engine,
                                               true_len):
        eng = head_engine
        assert eng._bucket(true_len) == HEAD_BUCKET
        assert eng.cache.ensure_capacity(1, HEAD_BUCKET)
        ids = np.zeros((1, HEAD_BUCKET), np.int32)
        ids[0, :true_len] = np.random.RandomState(true_len).randint(
            1, HEAD_VOCAB, (true_len,))
        row = jnp.asarray(eng.cache.block_tables[1])
        n = jnp.asarray(true_len, jnp.int32)

        def views_of(pools):
            return eng.cache.prefill_views(pools, row, n)

        full = self._logits(eng, views_of, ids, None)
        one = self._logits(eng, views_of, ids,
                           jnp.asarray([true_len - 1], jnp.int32))
        assert full.shape == (1, HEAD_BUCKET, HEAD_VOCAB)
        assert one.shape == (1, 1, HEAD_VOCAB)
        self._same(one[:, 0], full[:, true_len - 1])

    def test_each_sequence_of_a_batch_reads_its_own_row(self,
                                                        head_engine):
        """The mixed step's case: B > 1, another row a sequence (over
        the model's own dense caches; a model that keeps none between
        steps takes ``None``)."""
        eng = head_engine
        rows = np.asarray([HEAD_BUCKET - 1, 4, 0], np.int32)
        ids = np.random.RandomState(5).randint(
            1, HEAD_VOCAB, (3, HEAD_BUCKET)).astype(np.int32)
        init = getattr(eng.model, "init_decode_caches", None)

        def views_of(_pools):
            return None if init is None else init(3, HEAD_BUCKET)

        full = self._logits(eng, views_of, ids, None)
        one = self._logits(eng, views_of, ids, jnp.asarray(rows))
        assert one.shape == (3, 1, HEAD_VOCAB)
        self._same(one[:, 0], full[np.arange(3), rows])


def _avals(jaxpr):
    """Every (primitive name, input shapes, output shapes) of a jaxpr,
    the bodies of its loops, branches and calls included."""
    for eqn in jaxpr.eqns:
        yield (eqn.primitive.name,
               [getattr(v.aval, "shape", ()) for v in eqn.invars],
               [getattr(v.aval, "shape", ()) for v in eqn.outvars])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


def _wide_rows(eqns, vocab, seqs):
    """Shapes [..., vocab] of more rows than the ``seqs`` sequences of
    the step: [P, vocab], [1, P, vocab], [S, C, vocab] alike."""
    return sorted({s for _, _, outs in eqns for s in outs
                   if len(s) >= 2 and s[-1] == vocab
                   and int(np.prod(s[:-1])) > seqs})


class TestHeadRunsOnTheRowsRead:
    """No compiled prefill program holds a [P, vocab] (or [S, C, vocab])
    array; the decode step, which reads every row it computes, is the
    program it was."""

    S, C = 3, 8

    def _traced(self, flags, pick):
        model = _head_model("llama")
        paddle.set_flags(dict.fromkeys(flags, True))
        try:
            eng = serving.Engine(model, max_slots=self.S, num_blocks=32,
                                 block_size=4, max_model_len=64,
                                 prefill_chunk=self.C)
            fn, args = pick(eng)
            return list(_avals(eng._run_eval(
                jax.make_jaxpr(fn), *args).jaxpr))
        finally:
            paddle.set_flags(dict.fromkeys(flags, False))

    @staticmethod
    def _prefill_args(eng, *more):
        return (eng._state_vals, eng.cache.pools,
                jnp.zeros((1, HEAD_BUCKET), jnp.int32),
                jnp.asarray(eng.cache.block_tables[0])) + more

    def test_prefill(self):
        n = jnp.asarray(HEAD_BUCKET - 3, jnp.int32)
        eqns = self._traced((), lambda eng: (
            eng._prefill_fn, self._prefill_args(eng, n)))
        assert _wide_rows(eqns, HEAD_VOCAB, 1) == []
        assert any((1, 1, HEAD_VOCAB) in outs for _, _, outs in eqns)

    def test_suffix_prefill(self):
        n = jnp.asarray(HEAD_BUCKET - 3, jnp.int32)
        eqns = self._traced(
            ("FLAGS_serving_prefix_cache",), lambda eng: (
                eng._suffix_prefill_fn,
                self._prefill_args(eng, jnp.asarray(4, jnp.int32), n)))
        assert _wide_rows(eqns, HEAD_VOCAB, 1) == []
        assert any((1, 1, HEAD_VOCAB) in outs for _, _, outs in eqns)

    def test_mixed_step(self):
        def pick(eng):
            name, _, fn, args = eng._hot_step()
            assert name == "mixed" and args[2].shape == (self.S, self.C)
            return fn, args

        eqns = self._traced(("FLAGS_serving_chunked_prefill",), pick)
        assert _wide_rows(eqns, HEAD_VOCAB, self.S) == []
        assert any((self.S, 1, HEAD_VOCAB) in outs
                   for _, _, outs in eqns)

    def test_decode_step_is_untouched(self):
        def pick(eng):
            name, _, fn, args = eng._hot_step()
            assert name == "decode"
            return fn, args

        eqns = self._traced((), pick)
        # its [S, 1, vocab] logits, every row of them read
        assert any((self.S, 1, HEAD_VOCAB) in outs
                   for _, _, outs in eqns)
        assert _wide_rows(eqns, HEAD_VOCAB, self.S) == []
        # and no row picked out of the hidden state: it passes no
        # logits_at
        assert not [ins for name, ins, _ in eqns if name == "gather"
                    and ins[0] == (self.S, 1, HEAD_HIDDEN)]
