"""The serving cache: what every layer keeps for a slot, by layer kind.

A model's ``paged_cache_spec()`` is a list with one entry a layer, and
an entry's kind says what a slot holds there, how it grows, and what
release and preemption do to it:

``KVPages`` (kind ``kv_pages``)
    K/V pages of ``block_size`` tokens at the layer's own
    ``num_kv_heads`` x ``head_dim``. A slot holds the pages its block
    table row names; it grows by a page every ``block_size`` tokens,
    from the ONE allocator every kv_pages layer shares (a page id means
    the same page in every such layer's pool); release returns the
    pages; a preempted request re-prefills into new ones.
``SlotState`` (kind ``slot_state``)
    fixed arrays indexed by slot (a recurrent state, a convolution
    tail). A slot holds row ``slot`` of each; it never grows and takes
    nothing from the allocator; release does nothing, because the
    prefill that next takes the slot overwrites its row (so does the
    re-prefill of a preempted request: the state is rebuilt, never
    saved). The prefix cache and the chunked mixed step cannot adopt or
    chunk such a state yet, and the engine refuses them for a model
    that has one.
``LatentPages`` (kind ``latent_pages``)
    pages of ``block_size`` tokens, ONE row of ``width`` values a token
    (latent attention's normed compressed key/value followed by the
    roped key every head shares): one plane, no V plane, no head axis.
    Page ids come from the same allocator and mean the same page as in
    every other paged layer, so such a layer grows, releases and is
    re-prefilled exactly as a kv_pages layer is. The plane's rows are
    ``width`` rounded up to whole 128-lane tiles, the rest zero: the
    chip's memory tiles a row that way whatever its declared width, and
    a kernel can only cut a page out of the pool along whole tiles. A
    prefill writes the rows and attends over the fresh expanded heads,
    never the pool; a decode step writes one row a slot and attends
    over the pool with every up-projection absorbed into the query
    (serving/kernels/mla_attention.py). Prefix adoption, the chunked
    mixed step and int8 pages have no latent form yet, and the engine
    refuses them for a model that has such a layer.
``NoCache`` (kind ``nothing``)
    a layer that keeps nothing for a sequence (an expert or MLP block
    that is a layer of its own, not the second half of one): no pool
    (``None``, an empty node of the pools' tree), nothing to grow,
    release or rebuild, and a hook the layer never calls.
``WindowRing`` (kind ``window_ring``)
    window attention's K/V: a slot keeps its last ``window`` rows in a
    ring, position p in row ``p mod window``, heads side by side on the
    lanes. The ring is ``window / block_size`` pages of its own pool,
    slot s's from page ``s * window / block_size`` on, so it never
    grows and takes nothing from the allocator; a prefill fills it from
    the prompt's last ``window`` rows, a decode step overwrites the
    oldest row, and release only stops counting it as held (the next
    prefill of the slot overwrites it whole). Attention reads a ring's
    valid rows in row order, not position order: with no positional
    rotation the order of the keys is nothing to a softmax.
``SharedPages`` (kind ``shared_pages``)
    a layer that reads another layer's pages and owns none (a cross-
    decoder's attention over the K/V one full-attention layer wrote): no
    pool, and the hook of a layer that keeps nothing; the model hands
    the layer the ``source`` layer's view after that layer's write.

The rest of this docstring is the kv_pages kind.

Block-paged KV cache: fixed page pools + per-request block tables.

Memory model (Ragged Paged Attention / vLLM, PAPERS.md arxiv
2604.15464): each layer owns a fixed pool of
``[num_blocks, block_size, kv_heads, head_dim]`` pages; a request holds
an ordered list of page ids (its block table row) covering positions
``0..seq_len-1`` via ``page = table[pos // block_size]``,
``offset = pos % block_size``. Pages are allocated on demand and
returned to the free list when the request finishes or is preempted —
KV memory scales with TOKENS IN FLIGHT, not with
``max_slots * max_model_len`` the way generation.py's dense
``DecodeCache`` does.

Page 0 is reserved as the TRASH page: block-table rows are 0-padded, so
writes for pad positions (right-padded prefill, idle decode slots) land
in trash instead of corrupting live pages, and every write stays a
single unconditional scatter — no masking inside the compiled step.

Ownership is REFCOUNTED (serving tier 2): pages leave ``alloc`` at
refcount 1; the radix prefix cache (serving/prefix_cache.py) increfs
pages shared between its tree and the requests mapping their
block-table head onto a cached prompt prefix; ``release_slot`` decrefs
instead of freeing, and a write into a still-shared page goes through
the ``make_writable`` copy-on-write guard. With
FLAGS_serving_prefix_cache off nothing ever increfs and the allocator
behaves exactly as the original exclusive-owner free list.

The ``PagedPrefillView`` / ``PagedDecodeView`` / ``PagedMixedView``
classes are the per-layer external-cache attention hook: model
attention layers that see a cache object with ``update_and_attend``
hand it (q, k, v) and get the attention context back (models/llama.py,
models/gpt.py). The ENGINE owns the pools, tables and lengths; the
model never holds cache state. Views are created inside the jitted
step from traced pool arrays and return updated views — functional,
like DecodeCache. ``PagedMixedView`` is the ragged superset the other
two are special cases of: [S, C] rows of q_len new tokens each at
positions hist..hist+q_len-1, serving chunked prefill, prefix-cache
suffix prefill, and decode rows through one code path.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

TRASH_BLOCK = 0


class KVPages(NamedTuple):
    """One layer's entry of a cache spec, kind ``kv_pages``. ``flat``:
    a page is [block_size, num_kv_heads * head_dim], every head of a
    token side by side on the lanes (what ``diff_decode`` reads), not
    [block_size, num_kv_heads, head_dim]."""

    num_kv_heads: int
    head_dim: int
    dtype: str = "float32"
    flat: bool = False
    kind = "kv_pages"


class SlotState(NamedTuple):
    """One layer's entry of a cache spec, kind ``slot_state``:
    ``arrays`` is ((name, shape of one slot's row, dtype), ...)."""

    arrays: tuple
    kind = "slot_state"


class LatentPages(NamedTuple):
    """One layer's entry of a cache spec, kind ``latent_pages``: a token
    is one row of ``width`` values."""

    width: int
    dtype: str = "float32"
    kind = "latent_pages"


class NoCache(NamedTuple):
    """One layer's entry of a cache spec, kind ``nothing``."""

    kind = "nothing"


class WindowRing(NamedTuple):
    """One layer's entry of a cache spec, kind ``window_ring``: a slot's
    last ``window`` K/V rows."""

    window: int
    num_kv_heads: int
    head_dim: int
    dtype: str = "float32"
    kind = "window_ring"


class SharedPages(NamedTuple):
    """One layer's entry of a cache spec, kind ``shared_pages``: it reads
    the pages of layer ``source``."""

    source: int
    kind = "shared_pages"


class RingPool(NamedTuple):
    """One window layer's rings: k/v [max_slots * window / block_size,
    block_size, num_kv_heads * head_dim]."""

    k: "object"
    v: "object"


class LatentPool(NamedTuple):
    """One latent layer's page pool: rows [num_blocks, block_size,
    width rounded up to 128]."""

    rows: "object"


class KVBlockPool(NamedTuple):
    """One layer's page pools: k/v [num_blocks, block_size, Hkv, D].

    Under FLAGS_serving_quant_kv the k/v planes are int8 and the
    per-(page, position, head) fp32 scale planes
    ``k_scale``/``v_scale`` [num_blocks, block_size, Hkv] live
    alongside them — same page ids, same scatter indices, donated and
    COW-cloned together. Flags-off they are None, which jax treats as
    an EMPTY pytree node: the flattened leaves (and therefore every
    compiled step's jaxpr) are bit-identical to the pre-quant build."""

    k: "object"
    v: "object"
    k_scale: "object" = None
    v_scale: "object" = None


class BlockAllocator:
    """Host-side free-list over page ids 1..num_blocks-1 (0 is trash).

    ``alloc`` returns None — the explicit out-of-blocks signal — instead
    of raising: the scheduler turns it into preempt-and-requeue.

    Ownership model: every allocated page carries a REFCOUNT. ``alloc``
    hands out pages at refcount 1 (the exclusive-owner fast path —
    without a prefix cache nothing ever increfs, and behavior is
    exactly the pre-refcount allocator). The prefix cache increfs pages
    it shares between a radix-tree node and the requests mapping their
    block-table head onto it; ``free``/``decref`` only return a page to
    the free list when the last reference drops. A page is free XOR
    refcounted — the double-free check is an O(1) set probe, not the
    O(n) list scan that made page-heavy teardown quadratic."""

    def __init__(self, num_blocks):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (page 0 is the trash page)")
        self.num_blocks = num_blocks
        # LIFO keeps recently-freed (cache-warm) pages in circulation
        self._free = list(range(num_blocks - 1, 0, -1))
        self._free_set = set(self._free)
        self._refs = {}                 # page id -> refcount (> 0)

    @property
    def free_blocks(self):
        return len(self._free)

    @property
    def usable_blocks(self):
        return self.num_blocks - 1

    def alloc(self, n=1):
        """n page ids at refcount 1, or None when fewer than n are free."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._free_set.discard(p)
            self._refs[p] = 1
        return pages

    def refcount(self, i):
        return self._refs.get(i, 0)

    def incref(self, i):
        """Add a reference to an allocated page (prefix-cache sharing)."""
        if i not in self._refs:
            raise ValueError("incref of unallocated page %r" % (i,))
        self._refs[i] += 1

    def decref(self, i):
        """Drop one reference; the page returns to the free list when
        the LAST reference drops. Returns True when the page was freed."""
        if (not 0 < i < self.num_blocks or i in self._free_set
                or i not in self._refs):
            raise ValueError("bad free of page %r" % (i,))
        self._refs[i] -= 1
        if self._refs[i] == 0:
            del self._refs[i]
            self._free.append(i)
            self._free_set.add(i)
            return True
        return False

    def free(self, ids):
        for i in ids:
            self.decref(i)


class PagedKVCache:
    """One pool a layer (``KVBlockPool`` for a kv_pages layer,
    ``LatentPool`` for a latent_pages layer, a dict of [max_slots, ...]
    arrays for a slot_state layer, ``None`` for a layer that keeps
    nothing) + the host-side table/length
    bookkeeping + the one allocator."""

    def __init__(self, layers, num_blocks, block_size, max_slots,
                 max_blocks_per_slot, quantized=False):
        self.layers = list(layers)
        self.block_size = block_size
        self.max_slots = max_slots
        self.max_blocks_per_slot = max_blocks_per_slot
        self.quantized = bool(quantized)
        self.has_slot_state = any(
            spec.kind == "slot_state" for spec in self.layers)
        self.has_latent = any(
            spec.kind == "latent_pages" for spec in self.layers)
        self.has_window = any(
            spec.kind == "window_ring" for spec in self.layers)
        self.num_blocks = num_blocks
        self.pools = [self._new_pool(spec) for spec in self.layers]
        self.allocator = BlockAllocator(num_blocks)
        # with a slot_state or window_ring layer a row's last column is
        # the slot's own index: a prefill is told its page-table row and
        # nothing else, and that is how it learns which slot's state or
        # ring to write
        self._slot_column = self.has_slot_state or self.has_window
        self.block_tables = np.zeros(
            (max_slots, max_blocks_per_slot + self._slot_column),
            np.int32)
        if self._slot_column:
            self.block_tables[:, -1] = np.arange(max_slots)
        self.seq_lens = np.zeros((max_slots,), np.int32)
        self._slot_pages = [[] for _ in range(max_slots)]
        self.cow_clones = 0             # copy-on-write page splits

    def _new_pool(self, spec):
        if spec.kind in ("nothing", "shared_pages"):
            return None
        if spec.kind == "window_ring":
            if spec.window % self.block_size:
                raise ValueError(
                    "a window of %d rows is not whole pages of %d"
                    % (spec.window, self.block_size))
            ring = (self.max_slots * spec.window // self.block_size,
                    self.block_size, spec.num_kv_heads * spec.head_dim)
            return RingPool(jnp.zeros(ring, jnp.dtype(spec.dtype)),
                            jnp.zeros(ring, jnp.dtype(spec.dtype)))
        if spec.kind == "slot_state":
            return {name: jnp.zeros((self.max_slots,) + tuple(shape),
                                    jnp.dtype(dtype))
                    for name, shape, dtype in spec.arrays}
        if spec.kind == "latent_pages":
            return LatentPool(jnp.zeros(
                (self.num_blocks, self.block_size,
                 -(-spec.width // 128) * 128), jnp.dtype(spec.dtype)))
        if spec.flat:
            if self.quantized:
                raise ValueError("flat K/V pages have no int8 form")
            page = (self.num_blocks, self.block_size,
                    spec.num_kv_heads * spec.head_dim)
            return KVBlockPool(jnp.zeros(page, jnp.dtype(spec.dtype)),
                               jnp.zeros(page, jnp.dtype(spec.dtype)))
        dt = jnp.dtype("int8") if self.quantized else jnp.dtype(spec.dtype)
        page = (self.num_blocks, self.block_size, spec.num_kv_heads,
                spec.head_dim)
        # zero scales x zero int8 pages dequantize to exact zeros, so
        # trash/idle reads match the fp32 zero-init pools bit-for-bit
        scale = page[:3]
        return KVBlockPool(
            jnp.zeros(page, dt), jnp.zeros(page, dt),
            jnp.zeros(scale, jnp.float32) if self.quantized else None,
            jnp.zeros(scale, jnp.float32) if self.quantized else None)

    def state_stats(self):
        """The slot_state side, for ``Engine.stats()["state"]``."""
        pool_bytes = sum(a.nbytes for p in self.pools
                         if isinstance(p, dict) for a in p.values())
        return {"slots": self.max_slots,
                "layers": sum(spec.kind == "slot_state"
                              for spec in self.layers),
                "slot_bytes": pool_bytes // self.max_slots,
                "pool_bytes": pool_bytes}

    def latent_stats(self):
        """The latent_pages side, for ``Engine.stats()["latent"]``:
        ``row_bytes`` is what a token takes in one layer's pool."""
        pools = [p.rows for p in self.pools if isinstance(p, LatentPool)]
        return {"layers": len(pools),
                "row_bytes": pools[0].shape[2] * pools[0].dtype.itemsize,
                "pool_bytes": sum(a.nbytes for a in pools)}

    def window_stats(self):
        """The window_ring side, for ``Engine.stats()["window"]``:
        ``held_bytes`` is what the rings of the slots that hold a
        sequence take."""
        rings = [p for p in self.pools if isinstance(p, RingPool)]
        pool_bytes = sum(p.k.nbytes + p.v.nbytes for p in rings)
        slot_bytes = pool_bytes // self.max_slots
        return {"layers": len(rings),
                "window": next(spec.window for spec in self.layers
                               if spec.kind == "window_ring"),
                "slot_bytes": slot_bytes, "pool_bytes": pool_bytes,
                "held_bytes": slot_bytes * int((self.seq_lens > 0).sum())}

    # -- the per-layer hooks of one compiled step (called in its trace)

    def prefill_views(self, pools, table_row, true_len):
        def view(spec, p):
            if spec.kind == "kv_pages":
                return PagedPrefillView(p, table_row, self.block_size)
            if spec.kind == "latent_pages":
                return LatentPrefillView(p, table_row, self.block_size)
            if spec.kind in ("nothing", "shared_pages"):
                return NoView()
            if spec.kind == "window_ring":
                return RingPrefillView(p, table_row[-1], true_len,
                                       spec.window, self.block_size)
            return StatePrefillView(p, table_row[-1], true_len)

        return [view(spec, p) for spec, p in zip(self.layers, pools)]

    def decode_views(self, pools, block_tables, seq_lens):
        if self._slot_column:
            # the page kernels take the page columns, not the slot's
            block_tables = block_tables[:, :self.max_blocks_per_slot]

        def view(spec, p):
            if spec.kind == "kv_pages":
                return PagedDecodeView(p, block_tables, seq_lens,
                                       self.block_size)
            if spec.kind == "latent_pages":
                return LatentDecodeView(p, block_tables, seq_lens,
                                        self.block_size)
            if spec.kind in ("nothing", "shared_pages"):
                return NoView()
            if spec.kind == "window_ring":
                return RingDecodeView(p, seq_lens, spec.window,
                                      self.block_size)
            return StateDecodeView(p, seq_lens > 0)

        return [view(spec, p) for spec, p in zip(self.layers, pools)]

    def pages_needed(self, num_tokens):
        return -(-num_tokens // self.block_size)  # ceil

    def slot_page_count(self, slot):
        return len(self._slot_pages[slot])

    def slot_pages(self, slot):
        """The slot's page ids in position order (prefix-cache insert
        reads them; treat as read-only)."""
        return self._slot_pages[slot]

    def ensure_capacity(self, slot, num_tokens):
        """Allocate pages so positions 0..num_tokens-1 are covered.
        Returns True, or False on pool exhaustion (nothing allocated —
        all-or-nothing, so a failed admission leaves no partial state)."""
        need = self.pages_needed(num_tokens) - len(self._slot_pages[slot])
        if need <= 0:
            return True
        if num_tokens > self.max_blocks_per_slot * self.block_size:
            raise ValueError(
                "%d tokens exceed the per-slot capacity %d"
                % (num_tokens, self.max_blocks_per_slot * self.block_size))
        pages = self.allocator.alloc(need)
        if pages is None:
            return False
        start = len(self._slot_pages[slot])
        self._slot_pages[slot].extend(pages)
        self.block_tables[slot, start:start + need] = pages
        return True

    def adopt_prefix(self, slot, pages, matched_tokens):
        """Map an (empty) slot's block-table head onto SHARED prefix
        pages from the radix cache: each page gains a reference for
        this slot, ``seq_lens`` starts at the matched token count, and
        the request only prefills the uncached suffix. The caller has
        already verified free-block capacity for that suffix."""
        assert not self._slot_pages[slot], "adopt into a non-empty slot"
        for p in pages:
            self.allocator.incref(p)
        self._slot_pages[slot] = list(pages)
        self.block_tables[slot, :len(pages)] = pages
        self.seq_lens[slot] = matched_tokens

    def make_writable(self, slot, start, end):
        """Copy-on-write guard: every page covering positions
        ``[start, end)`` the slot is about to WRITE must be exclusively
        owned. A shared page (a partially-matched prefix page, refcount
        > 1) is cloned — pool K/V copied for every layer, block table
        repointed, old reference dropped — so the write never corrupts
        the other holders' history. Returns False when the pool cannot
        supply a clone page (caller reclaims/preempts and retries) —
        already-cloned pages stay valid, so the retry is incremental."""
        if end <= start:
            return True
        ok = True
        src, dst = [], []
        for idx in range(start // self.block_size,
                         -(-end // self.block_size)):
            page = self._slot_pages[slot][idx]
            if self.allocator.refcount(page) <= 1:
                continue
            new = self.allocator.alloc(1)
            if new is None:
                ok = False          # partial progress kept (see above)
                break
            new = new[0]
            src.append(page)
            dst.append(new)
            self.allocator.decref(page)
            self._slot_pages[slot][idx] = new
            self.block_tables[slot, idx] = new
            self.cow_clones += 1
        if src:
            # ONE batched gather-scatter per pool for the whole call —
            # a functional .at[].set copies the entire pool buffer, so
            # per-page updates would pay that copy once per clone
            s = jnp.asarray(src, jnp.int32)
            d = jnp.asarray(dst, jnp.int32)
            # _replace keeps the scale planes; under quant they are
            # cloned with the same batched gather-scatter so a COW'd
            # page carries its scales (shared holders keep theirs)
            self.pools = [
                p._replace(
                    k=p.k.at[d].set(p.k[s]), v=p.v.at[d].set(p.v[s]),
                    **({} if p.k_scale is None else {
                        "k_scale": p.k_scale.at[d].set(p.k_scale[s]),
                        "v_scale": p.v_scale.at[d].set(p.v_scale[s])}))
                if isinstance(p, KVBlockPool) else p
                for p in self.pools]
        return ok

    def release_slot(self, slot):
        """Release the slot's page references (finish/preempt). A page
        the prefix cache still references survives — release DECREFS
        instead of freeing, so a finished request's prefix stays warm
        for the next request that shares it."""
        if self._slot_pages[slot]:
            self.allocator.free(self._slot_pages[slot])
        self._slot_pages[slot] = []
        self.block_tables[slot, :self.max_blocks_per_slot] = TRASH_BLOCK
        self.seq_lens[slot] = 0

    def pools_alive(self):
        """False once the pool buffers were CONSUMED by donation: the
        engine's compiled steps donate their input pools
        (``donate_argnums``), so a step that raises AFTER execution
        started leaves these arrays deleted — readable shape/dtype,
        unreadable data."""
        import jax

        try:
            return not any(a.is_deleted()
                           for a in jax.tree_util.tree_leaves(self.pools))
        except AttributeError:      # non-jax pools (unit fixtures)
            return True

    def reset_pools(self):
        """Fresh zeroed pool plane + allocator + per-slot bookkeeping —
        the donated-pools failure recovery. When a compiled step
        consumes its input pools (donation) and then fails, every KV
        byte is gone and every page mapping refers to garbage; the
        caller requeues the occupied slots first (preempt-by-recompute
        re-prefills from host-side tokens, so nothing durable lived
        only in the pools) and then rebuilds the plane here. Shapes
        and dtypes survive a deleted jax array, so the new pools match
        the compiled steps' signatures exactly — no retrace. A
        slot_state layer's rows are zeroed with the rest; the re-prefill
        of each requeued slot writes its row anew."""
        self.pools = [self._new_pool(spec) for spec in self.layers]
        self.allocator = BlockAllocator(self.num_blocks)
        self.block_tables[:, :self.max_blocks_per_slot] = TRASH_BLOCK
        self.seq_lens[:] = 0
        self._slot_pages = [[] for _ in range(self.max_slots)]


def _raw(x):
    return x._value if hasattr(x, "_value") else jnp.asarray(x)


def _write_pages(pool, pages, offs, kv, vv):
    """Scatter fresh K/V into the pool planes at ``(pages, offs)`` —
    the views' single unconditional write. With int8 pools (scale
    planes present) each (position, head) head_dim vector is quantized
    AT WRITE TIME and its scale lands in the scale plane at the same
    indices, so the trash-page discipline covers scales for free: a pad
    position's quantized garbage and its scale both land in page 0."""
    if pool.k_scale is None:
        return pool._replace(
            k=pool.k.at[pages, offs].set(kv.astype(pool.k.dtype)),
            v=pool.v.at[pages, offs].set(vv.astype(pool.v.dtype)))
    from ..kernels.quant import quantize_int8_page

    kq, ks = quantize_int8_page(kv)
    vq, vs = quantize_int8_page(vv)
    return pool._replace(
        k=pool.k.at[pages, offs].set(kq),
        v=pool.v.at[pages, offs].set(vq),
        k_scale=pool.k_scale.at[pages, offs].set(ks),
        v_scale=pool.v_scale.at[pages, offs].set(vs))


class PagedPrefillView:
    """One layer's hook for single-request prefill ([1, P] right-padded
    prompt): writes every position's K/V through the (trash-padded)
    block-table row in one scatter, then runs dense causal attention —
    rows past the true length attend only forward of real tokens, so
    real rows are exactly the unpadded computation."""

    def __init__(self, pool, table_row, block_size, fresh=None):
        self.pool = pool
        self.table_row = table_row            # [MB] int32, trash-padded
        self.block_size = block_size
        self.fresh = fresh                    # (k, v) of ``update``

    def update_and_attend(self, q, k, v):
        from ..nn import functional as F

        qv, kv, vv = _raw(q), _raw(k), _raw(v)
        p = kv.shape[1]
        pos = jnp.arange(p)
        pages = self.table_row[pos // self.block_size]
        offs = pos % self.block_size
        new_pool = _write_pages(self.pool, pages, offs, kv[0], vv[0])
        # prefill attends over the raw fp32 fresh K/V (dense causal),
        # never the pool — quantization error only enters on pool READS
        heads, kv_heads = qv.shape[2], kv.shape[2]
        if heads != kv_heads:
            rep = heads // kv_heads
            kv = jnp.repeat(kv, rep, axis=2)
            vv = jnp.repeat(vv, rep, axis=2)
        out = F.scaled_dot_product_attention(qv, kv, vv, is_causal=True,
                                             _warn_rect_causal=False)
        return out, PagedPrefillView(new_pool, self.table_row,
                                     self.block_size)

    # differential attention (models/phi4flash.py): ``update`` then
    # ``attend_diff``, the second by this layer and by every layer that
    # reads its pages

    def update(self, k, v):
        """k, v [1, P, Hkv, D]: every position's row into the pages. ->
        the view after the write, which keeps the fresh rows to attend
        over."""
        kv, vv = _raw(k), _raw(v)
        p = kv.shape[1]
        pos = jnp.arange(p)
        row = self.pool.k.shape[2:]
        return PagedPrefillView(
            _write_pages(self.pool, self.table_row[pos // self.block_size],
                         pos % self.block_size, kv[0].reshape((p,) + row),
                         vv[0].reshape((p,) + row)),
            self.table_row, self.block_size, (kv, vv))

    def attend_diff(self, q, lam, scale, positions):
        """q [1, R, H, D], the rows at ``positions`` [R] of the prompt,
        over the fresh rows at or before each. -> [1, R, H / 2, 2 D]
        float32."""
        from .kernels.diff_attention import diff_prefill

        return diff_prefill(_raw(q), *self.fresh, positions, lam, scale)


class PagedDecodeView:
    """One layer's hook for the batched decode step ([S, 1] tokens, one
    per slot): scatters each slot's new K/V into page
    ``table[slot, len // bs]`` at offset ``len % bs`` (idle slots write
    trash), then attends over the paged history including the new token
    (effective length ``len + 1``) via the ragged paged-attention
    kernel/fallback."""

    def __init__(self, pool, block_tables, seq_lens, block_size):
        self.pool = pool
        self.block_tables = block_tables      # [S, MB] int32
        self.seq_lens = seq_lens              # [S] int32
        self.block_size = block_size

    def update_and_attend(self, q, k, v):
        from ..core.tensor import Tensor
        from .kernels.paged_attention import paged_attention

        qv, kv, vv = _raw(q), _raw(k), _raw(v)
        s = qv.shape[0]
        lens = self.seq_lens
        pages = self.block_tables[jnp.arange(s), lens // self.block_size]
        offs = lens % self.block_size
        new_pool = _write_pages(self.pool, pages, offs, kv[:, 0], vv[:, 0])
        out = paged_attention(qv[:, 0], new_pool.k, new_pool.v,
                              self.block_tables, lens + 1,
                              k_scale=new_pool.k_scale,
                              v_scale=new_pool.v_scale)
        return Tensor(out[:, None]), PagedDecodeView(
            new_pool, self.block_tables, lens, self.block_size)

    def update(self, k, v):
        """k, v [S, 1, Hkv, D]: each slot's row at its length. -> the
        view after the write, its lengths counting the new row."""
        kv, vv = _raw(k), _raw(v)
        s = kv.shape[0]
        lens = self.seq_lens
        row = self.pool.k.shape[2:]
        return PagedDecodeView(
            _write_pages(self.pool,
                         self.block_tables[jnp.arange(s),
                                           lens // self.block_size],
                         lens % self.block_size,
                         kv[:, 0].reshape((s,) + row),
                         vv[:, 0].reshape((s,) + row)),
            self.block_tables, lens + 1, self.block_size)

    def attend_diff(self, q, lam, scale, positions=None):
        """q [S, 1, H, D] over each slot's pages (``diff_decode``). ->
        [S, 1, H / 2, 2 D] float32."""
        from .kernels.diff_attention import diff_decode

        return diff_decode(_raw(q)[:, 0], self.pool.k, self.pool.v,
                           self.block_tables, self.seq_lens, lam,
                           scale)[:, None]


def _lane_padded(x, width):
    """``x`` [..., w] with zero columns up to ``width``."""
    pad = width - x.shape[-1]
    if not pad:
        return x
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))


class LatentPrefillView:
    """A latent_pages layer's hook for single-request prefill ([1, P]
    right-padded prompt): writes every position's row through the
    (trash-padded) block-table row in one scatter, then runs dense
    causal attention over the fresh expanded heads the layer hands it,
    never the pool: ``update`` then ``attend``. ``absorbed`` tells the
    layer which form of its attention this hook takes."""

    absorbed = False

    def __init__(self, pool, table_row, block_size):
        self.pool = pool
        self.table_row = table_row            # [MB] int32, trash-padded
        self.block_size = block_size

    def update(self, rows):
        """rows [1, P, width]: what the cache keeps of each token. ->
        the view after the write."""
        rv = _raw(rows)
        pos = jnp.arange(rv.shape[1])
        pages = self.table_row[pos // self.block_size]
        plane = self.pool.rows
        return LatentPrefillView(
            LatentPool(plane.at[pages, pos % self.block_size].set(
                _lane_padded(rv[0], plane.shape[2]).astype(plane.dtype))),
            self.table_row, self.block_size)

    def attend(self, q, k, v, scale):
        """q, k [1, P, H, Dq] and v [1, P, H, Dv], the expanded heads
        (all of them or some: the layer may call once a group of heads).
        -> context [1, P, H, Dv]; rows past the true length attend only
        forward of real tokens, so real rows are exactly the unpadded
        computation."""
        from ..nn import functional as F

        return F.scaled_dot_product_attention(
            _raw(q), _raw(k), _raw(v), is_causal=True, scale=scale,
            _warn_rect_causal=False)


class LatentDecodeView:
    """A latent_pages layer's hook for the batched decode step ([S, 1]
    tokens, one a slot): scatters each slot's new row into page
    ``table[slot, len // bs]`` at offset ``len % bs`` (idle slots write
    trash), then attends over the paged rows including the new one
    (effective length ``len + 1``) with the absorbed query, through the
    ``mla_decode`` kernel or its fallback: ``update`` then ``attend``."""

    absorbed = True

    def __init__(self, pool, block_tables, seq_lens, block_size):
        self.pool = pool
        self.block_tables = block_tables      # [S, MB] int32
        self.seq_lens = seq_lens              # [S] int32
        self.block_size = block_size

    def update(self, rows):
        """rows [S, 1, width]. -> the view after the write, its lengths
        counting the new row."""
        rv = _raw(rows)
        lens = self.seq_lens
        plane = self.pool.rows
        pages = self.block_tables[jnp.arange(rv.shape[0]),
                                  lens // self.block_size]
        return LatentDecodeView(
            LatentPool(plane.at[pages, lens % self.block_size].set(
                _lane_padded(rv[:, 0], plane.shape[2]).astype(plane.dtype))),
            self.block_tables, lens + 1, self.block_size)

    def attend(self, q_lat, scale, rank):
        """q_lat [S, 1, H, width], every head's query against a row; the
        values are a row's first ``rank`` columns.
        -> context [S, 1, H, rank]."""
        from ..core.tensor import Tensor
        from .kernels.mla_attention import mla_attention

        plane = self.pool.rows
        out = mla_attention(
            _lane_padded(_raw(q_lat)[:, 0],
                         plane.shape[2]).astype(plane.dtype),
            plane, self.block_tables, self.seq_lens, scale=scale,
            rank=rank)
        return Tensor(out[:, None])


class PagedMixedView:
    """One layer's hook for the MIXED ragged step ([S, C] tokens): row
    ``s`` holds ``q_lens[s]`` valid new tokens at absolute positions
    ``hist_lens[s] .. hist_lens[s] + q_lens[s] - 1`` (0 = idle row). A
    decode row is the ``q_len == 1`` special case; a prefill chunk is
    ``1 < q_len <= C``; the prefix-cache suffix prefill is the ``S == 1``
    case with ``hist = cached tokens``. Every valid position's K/V
    scatters through the slot's block-table row; PAD positions
    (``j >= q_len``) route to the trash page — the same unconditional-
    scatter discipline as the prefill/decode views, so no masking is
    needed inside the compiled step. Attention runs over the POOL
    (history plus the chunk's own freshly-written K/V) with the ragged
    causal rule ``key position <= hist + j``."""

    def __init__(self, pool, block_tables, hist_lens, q_lens, block_size):
        self.pool = pool
        self.block_tables = block_tables      # [S, MB] int32
        self.hist_lens = hist_lens            # [S] int32 (pool history)
        self.q_lens = q_lens                  # [S] int32 (new tokens)
        self.block_size = block_size

    def update_and_attend(self, q, k, v):
        from ..core.tensor import Tensor
        from .kernels.paged_attention import mixed_paged_attention

        qv, kv, vv = _raw(q), _raw(k), _raw(v)
        s, c = qv.shape[0], qv.shape[1]
        mb = self.block_tables.shape[1]
        pos = self.hist_lens[:, None] + jnp.arange(c)[None, :]  # [S, C]
        valid = jnp.arange(c)[None, :] < self.q_lens[:, None]
        # pad positions may index past the table (clamped gather) but
        # their write is rerouted to the trash page anyway
        page_idx = jnp.clip(pos // self.block_size, 0, mb - 1)
        pages = jnp.where(
            valid, jnp.take_along_axis(self.block_tables, page_idx,
                                       axis=1), TRASH_BLOCK)
        offs = jnp.where(valid, pos % self.block_size, 0)
        new_pool = _write_pages(self.pool, pages, offs, kv, vv)
        out = mixed_paged_attention(qv, new_pool.k, new_pool.v,
                                    self.block_tables, self.hist_lens,
                                    self.q_lens,
                                    k_scale=new_pool.k_scale,
                                    v_scale=new_pool.v_scale)
        return Tensor(out), PagedMixedView(
            new_pool, self.block_tables, self.hist_lens, self.q_lens,
            self.block_size)


class NoView:
    """The hook of a layer that keeps nothing: there for the engine,
    which reads every layer's ``pool`` back after a step."""

    pool = None


class RingPrefillView:
    """A window_ring layer's hook for single-request prefill ([1, P]
    right-padded prompt of ``valid_len`` real rows): ``update`` fills
    slot ``slot``'s ring from the last ``window`` real rows and keeps
    the fresh rows; ``attend_diff`` is banded causal attention over
    them."""

    def __init__(self, pool, slot, valid_len, window, block_size,
                 fresh=None):
        self.pool = pool                      # RingPool
        self.slot = slot                      # traced int32 scalar
        self.valid_len = valid_len            # traced int32 scalar
        self.window = window
        self.block_size = block_size
        self.fresh = fresh                    # (k, v) of ``update``

    def update(self, k, v):
        """k, v [1, P, Hkv, D]. Ring row r takes the last real position
        p with p = r mod window (a row no position reached yet takes
        row 0's, and is never read)."""
        kv, vv = _raw(k), _raw(v)
        w, bs = self.window, self.block_size
        r = jnp.arange(w)
        last = self.valid_len - 1
        pos = jnp.maximum(last - jnp.mod(last - r, w), 0)
        pages = self.slot * (w // bs) + r // bs
        width = self.pool.k.shape[2]

        def put(plane, rows):
            return plane.at[pages, r % bs].set(
                rows[0, pos].reshape(w, width).astype(plane.dtype))

        return RingPrefillView(
            RingPool(put(self.pool.k, kv), put(self.pool.v, vv)),
            self.slot, self.valid_len, w, bs, (kv, vv))

    def attend_diff(self, q, lam, scale, positions):
        from .kernels.diff_attention import diff_prefill

        return diff_prefill(_raw(q), *self.fresh, positions, lam, scale,
                            window=self.window)


class RingDecodeView:
    """A window_ring layer's hook for the batched decode step: ``update``
    writes each slot's new row over its oldest (row ``len mod window``),
    ``attend_diff`` reads a slot's min(len + 1, window) valid rows through
    ``diff_decode``, the ring's pages standing for a block table. An
    idle slot writes its own ring, which its next prefill overwrites,
    and reads nothing."""

    def __init__(self, pool, seq_lens, window, block_size, active=None):
        self.pool = pool                      # RingPool
        self.seq_lens = seq_lens              # [S] int32
        self.window = window
        self.block_size = block_size
        self.active = seq_lens > 0 if active is None else active

    def update(self, k, v):
        kv, vv = _raw(k), _raw(v)
        s = kv.shape[0]
        w, bs = self.window, self.block_size
        row = self.seq_lens % w
        pages = jnp.arange(s) * (w // bs) + row // bs
        width = self.pool.k.shape[2]

        def put(plane, rows):
            return plane.at[pages, row % bs].set(
                rows[:, 0].reshape(s, width).astype(plane.dtype))

        return RingDecodeView(
            RingPool(put(self.pool.k, kv), put(self.pool.v, vv)),
            self.seq_lens + 1, w, bs, self.active)

    def attend_diff(self, q, lam, scale, positions=None):
        from .kernels.diff_attention import diff_decode

        s = self.seq_lens.shape[0]
        ppr = self.window // self.block_size
        tables = (jnp.arange(s, dtype=jnp.int32)[:, None] * ppr
                  + jnp.arange(ppr, dtype=jnp.int32)[None, :])
        lens = jnp.where(self.active,
                         jnp.minimum(self.seq_lens, self.window), 0)
        return diff_decode(_raw(q)[:, 0], self.pool.k, self.pool.v, tables,
                           lens, lam, scale)[:, None]


class StatePrefillView:
    """A slot_state layer's hook for single-request prefill. The layer
    starts from ``read()`` (a zero row: the prefill that takes a slot
    resets it), runs its ``valid_len`` real tokens (the rows past them
    are padding and must leave the state as it was), and hands the
    arrays it ends with to ``write``, which stores them in row ``slot``
    of the pool."""

    def __init__(self, pool, slot, valid_len):
        self.pool = pool                      # {name: [S, ...]}
        self.slot = slot                      # traced int32 scalar
        self.valid_len = valid_len            # traced int32 scalar

    def read(self):
        return {name: jnp.zeros((1,) + a.shape[1:], a.dtype)
                for name, a in self.pool.items()}

    def write(self, arrays):
        pool = {name: a.at[self.slot].set(
            arrays[name][0].astype(a.dtype))
            for name, a in self.pool.items()}
        return StatePrefillView(pool, self.slot, self.valid_len)


class StateDecodeView:
    """A slot_state layer's hook for the batched decode step: ``read()``
    is every slot's row, ``write`` stores the rows of the ``active``
    slots and leaves an idle slot's row as it was. ``valid_len`` is
    None: every row is one real token. A layer whose kernel updates an
    array in place (given ``active``, it leaves the idle rows itself)
    names it in ``kept``, and that array is stored as it comes: a select
    over it would read and write the whole pool once more."""

    valid_len = None

    def __init__(self, pool, active):
        self.pool = pool                      # {name: [S, ...]}
        self.active = active                  # [S] bool

    def read(self):
        return self.pool

    def write(self, arrays, kept=()):
        def keep(new, old):
            on = self.active.reshape((-1,) + (1,) * (old.ndim - 1))
            return jnp.where(on, new.astype(old.dtype), old)

        return StateDecodeView(
            {name: arrays[name] if name in kept else keep(arrays[name], a)
             for name, a in self.pool.items()},
            self.active)
