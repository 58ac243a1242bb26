"""Paged (block-table) KV cache for the serving engine.

The dense decode cache costs HBM slots x max_seq_len regardless of how
long requests actually are; the reference gets vLLM's paged attention
for free (/root/reference/llm/vllm/serve.yaml). This is the TPU-native
equivalent: a page POOL

    k/v: [n_layers, n_pages, kv_heads, page_size, head_dim]

plus a per-slot block table mapping logical token positions to pages.
HBM scales with tokens actually reserved, so at equal HBM the engine
holds more concurrent requests.

Allocation policy: a request reserves ceil((prompt + max_new)/P) pages
at ADMISSION — the worst case it can ever touch, knowable up front
because max_new_tokens is part of the request. Deterministic: no
mid-decode pool exhaustion, so no vLLM-style preemption/swapping is
needed; admission simply defers while the pool is full. The cost is
reserving tokens a request may finish early without using — still far
below the dense cache's max_seq_len per slot.

Device-side ops are shape-static for XLA:
  * insert: prompt KV scattered into the reserved pages (one compile per
    distinct page count — bounded by max_pages_per_slot);
  * gather: block table -> contiguous [slots, max_pages*P, H, d] view the
    unmodified model attends over (positions mask the tail);
  * append: one decoded token's KV scattered to (page[len//P], len%P)
    for every slot in one vectorized update.

Page 0 is a shared dummy: unreserved table entries point at it and are
never read unmasked (attention masks positions >= length).

int8 KV quantization (``kv_dtype='int8'``): the k/v pools store int8
with a PER-TOKEN, PER-HEAD f32 scale pool ``[L, n_pages, H, P]``
(scale = amax over head_dim / 127 — the JetStream/vLLM per-token
scheme: each written token row quantizes independently, so appends
never re-scale already-written entries). Scales add 4/head_dim to the
bytes per token (~3% at d=128), so pages-per-pool at equal HBM is
~1.9-3.8x the fp pool (infer/memory_plan.py does the exact
arithmetic). Dequantization folds into the attention matmuls: the
paged Pallas kernels read int8 pages + the scale block and multiply
the scores/weights by the scales (ops/paged_attention.py *_q), and
the XLA floor dequantizes at the gather (gather_view_layer_q).
Prefix-cache sharing is unchanged — quantized pages are what's
published and shared.
"""
import collections
import dataclasses
import hashlib
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# KV pool quantization modes ('auto' = store at the model's compute
# dtype, no quantization).
KV_DTYPES = ('auto', 'int8')


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x [..., d] float -> (int8 [..., d], f32 scale [...]) with a
    symmetric per-row (per-token, per-head) scale = amax/127. amax == 0
    rows get scale 1.0 so zero KV stays exactly zero."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def page_hashes(tokens: Sequence[int], page_size: int,
                salt: int = 0) -> List[bytes]:
    """Chained content hashes of a prompt's FULL pages — the prefix-cache
    key (vLLM's automatic prefix caching, which the reference gets via
    llm/vllm/serve.yaml). hash[i] covers tokens[0 : (i+1)*page_size], so
    two prompts share page i iff they agree on everything up to it.

    salt: the request's lora_id — K/V depend on the (adapter-modified)
    wk/wv projections, so pages must never be shared across adapters;
    salting the chain start keeps the ids in disjoint hash spaces."""
    h = hashlib.blake2b(digest_size=16)
    if salt:
        h.update(int(salt).to_bytes(8, 'little'))
    out: List[bytes] = []
    for i in range(len(tokens) // page_size):
        h.update(np.asarray(tokens[i * page_size:(i + 1) * page_size],
                            dtype=np.int64).tobytes())
        out.append(h.digest())
    return out


@dataclasses.dataclass
class PagedConfig:
    page_size: int = 64
    n_pages: int = 0              # total pool pages (incl. dummy page 0)
    max_pages_per_slot: int = 0   # ceil(max_seq_len / page_size)

    @staticmethod
    def for_engine(max_seq_len: int, num_slots: int, page_size: int,
                   pool_tokens: Optional[int] = None) -> 'PagedConfig':
        """pool_tokens: HBM budget in tokens; default = the dense
        equivalent (num_slots * max_seq_len), i.e. paging changes layout
        only — pass less to actually save HBM, or more slots at equal
        budget."""
        max_pages = -(-max_seq_len // page_size)
        tokens = pool_tokens if pool_tokens is not None \
            else num_slots * max_seq_len
        n_pages = -(-tokens // page_size) + 1   # +1: dummy page 0
        return PagedConfig(page_size=page_size, n_pages=n_pages,
                           max_pages_per_slot=max_pages)


class PagePool:
    """Host-side page accounting + the device pools and block table.

    Not thread-safe: owned by the engine loop thread, same as the slot
    table.
    """

    def __init__(self, cfg: PagedConfig, n_layers: int, kv_heads: int,
                 head_dim: int, num_slots: int, dtype,
                 sharding=None, kv_dtype: str = 'auto',
                 scale_sharding=None) -> None:
        """sharding / scale_sharding: where the k/v pools and the int8
        scale pools live (None: the default device). The pools are
        created in that layout — a pool sharded over a mesh is never
        whole on one device first."""
        self.cfg = cfg
        self.num_slots = num_slots
        if kv_dtype not in KV_DTYPES:
            raise ValueError(f'kv_dtype must be one of {KV_DTYPES}, '
                             f'got {kv_dtype!r}')
        self.kv_dtype = kv_dtype
        self.quantized = kv_dtype == 'int8'
        # Page-major pool: one page holds ALL kv heads ([H, P, d]
        # contiguous), so the Pallas paged-attention kernel
        # (ops/paged_attention.py) fetches a slot's whole page in ONE
        # block — grid (slots, pages), not (slots, heads, pages); per-
        # invocation and DMA-issue overhead dominate at decode sizes.
        shape = (n_layers, cfg.n_pages, kv_heads, cfg.page_size, head_dim)
        pool_dtype = jnp.int8 if self.quantized else dtype
        self.pools: Dict[str, jax.Array] = {
            'k': jnp.zeros(shape, pool_dtype, device=sharding),
            'v': jnp.zeros(shape, pool_dtype, device=sharding)}
        if self.quantized:
            # Per-token, per-head scales (see module docstring). Scale
            # of the never-written dummy page stays 0 -> dequantizes
            # to exact zeros, like the fp pool's zero init.
            sshape = shape[:-1]
            self.pools['k_scale'] = jnp.zeros(sshape, jnp.float32,
                                              device=scale_sharding)
            self.pools['v_scale'] = jnp.zeros(sshape, jnp.float32,
                                              device=scale_sharding)
        # Page 0 is the dummy; never allocated.
        self._free: List[int] = list(range(1, cfg.n_pages))
        self._owned: List[List[int]] = [[] for _ in range(num_slots)]
        # Host block table mirror; the device copy lives in the engine's
        # decode args and is updated on device at insert.
        self.tables = np.zeros((num_slots, cfg.max_pages_per_slot),
                               np.int32)
        # Prefix cache: content-hash -> page, plus per-page refcounts.
        # Pages with refcount 0 that still hold published content sit in
        # an LRU pool (_cached_free) and are reclaimed only when _free is
        # empty — so a released system prompt's KV stays warm as long as
        # HBM allows (vLLM's automatic prefix caching).
        self._refs = np.zeros((cfg.n_pages,), np.int64)
        self._registry: Dict[bytes, int] = {}
        self._page_hash: Dict[int, bytes] = {}
        self._cached_free: 'collections.OrderedDict[int, None]' = \
            collections.OrderedDict()
        self.prefix_stats = {'hit_pages': 0, 'miss_pages': 0,
                             'evictions': 0}
        # Spillover hook (infer/kv_tier.py): called as on_evict(page, h)
        # when _alloc_page reclaims a published page — the one moment a
        # page's KV is about to be lost. NOT called from flush_prefix:
        # version-invalidated pages must not outlive the swap in any
        # tier. The engine wraps its hook defensively; pool accounting
        # must not depend on it.
        self.on_evict = None

    # --------------------------------------------------- host accounting
    def pages_needed(self, total_tokens: int) -> int:
        return min(-(-total_tokens // self.cfg.page_size),
                   self.cfg.max_pages_per_slot)

    def free_pages(self) -> int:
        """Allocatable pages: never-published free pages plus published
        pages no live slot references (reclaimable via eviction)."""
        return len(self._free) + len(self._cached_free)

    def prefix_cached_pages(self) -> int:
        """Pages currently holding published (reusable) prefix KV —
        referenced or warm-LRU. The occupancy signal the LB's
        cache-affinity routing reads (ROADMAP item 2)."""
        return len(self._registry)

    def _alloc_page(self) -> Optional[int]:
        if self._free:
            return self._free.pop()
        if self._cached_free:
            # Evict the least-recently-released published page.
            page, _ = self._cached_free.popitem(last=False)
            h = self._page_hash.pop(page)
            del self._registry[h]
            self.prefix_stats['evictions'] += 1
            if self.on_evict is not None:
                self.on_evict(page, h)
            return page
        return None

    def _unref(self, page: int) -> None:
        self._refs[page] -= 1
        assert self._refs[page] >= 0, f'page {page} refcount underflow'
        if self._refs[page] == 0:
            if page in self._page_hash:
                self._cached_free[page] = None
                self._cached_free.move_to_end(page)
            else:
                self._free.append(page)

    def flush_prefix(self) -> int:
        """Unpublish every prefix-cache entry (weight swap: cached KV
        was computed under the OLD weights, so sharing it after the
        swap would silently mix versions — docs/robustness.md
        "Zero-downtime rollouts"). Warm unreferenced pages return to
        the plain free list; pages still referenced by live slots keep
        their reservations (their requests finish normally) but lose
        their registry entry, so they can never be shared again and
        free as plain pages on release. Returns entries flushed."""
        flushed = len(self._registry)
        self._registry.clear()
        self._page_hash.clear()
        for page in self._cached_free:
            self._free.append(page)
        self._cached_free.clear()
        return flushed

    def registered_page(self, h: bytes) -> Optional[int]:
        """Page currently published under hash `h`, or None — the KV
        export path (/kv/prefix) resolves hash runs through this."""
        return self._registry.get(h)

    def registered_hashes(self) -> List[bytes]:
        """Every published page hash in registration (publish) order —
        the /kv/index inventory the prewarm ownership map is computed
        over. Publish order approximates chain order for each prefix,
        so contiguous slices of this list mostly preserve leading
        runs. Engine-loop only (like every registry read)."""
        return list(self._registry.keys())

    def prefix_peek(self, lookup_hashes) -> int:
        """Length of the leading registered-page run for these hashes —
        a READ-ONLY probe of what try_reserve_prefix would share (no
        refs taken, nothing evicted). The engine's batched-admission
        path uses it to route prefix-hit prompts to the sequential
        suffix-prefill path without churning reservations."""
        n = 0
        for h in lookup_hashes:
            if self._registry.get(h) is None:
                break
            n += 1
        return n

    def install_prefix(self, hashes: Sequence[bytes]
                       ) -> Optional[List[int]]:
        """Allocate and register one page per hash at refcount 0 (warm
        LRU), for pages whose contents arrive from an outer tier (host
        promotion / fleet fetch) instead of a slot's prefill. The
        caller must write the page contents before any reservation can
        read them — same single-dispatch-chain ordering contract as
        publish(). Draws from the plain free list ONLY: promotion must
        never evict already-published pages (that would churn the warm
        set it is trying to grow). Returns the page ids, or None if
        the free list cannot cover the run or a hash is already
        registered (the caller re-peeks instead)."""
        new = [h for h in hashes if h not in self._registry]
        if len(new) != len(hashes) or len(new) > len(self._free):
            return None
        pages: List[int] = []
        for h in new:
            page = self._free.pop()
            self._registry[h] = page
            self._page_hash[page] = h
            self._cached_free[page] = None
            self._cached_free.move_to_end(page)
            pages.append(page)
        return pages

    def try_reserve(self, slot: int, total_tokens: int) -> Optional[np.ndarray]:
        """Reserve pages covering total_tokens for `slot`. Returns the
        slot's full table row (np [max_pages_per_slot]) or None if the
        pool cannot satisfy the reservation."""
        res = self.try_reserve_prefix(slot, total_tokens, ())
        return None if res is None else res[0]

    def try_reserve_prefix(
            self, slot: int, total_tokens: int,
            lookup_hashes: Sequence[bytes]
    ) -> Optional[Tuple[np.ndarray, int]]:
        """Reserve pages covering total_tokens for `slot`, sharing the
        longest registered run of `lookup_hashes` (page_hashes() of the
        prompt's full pages). Returns (table row, n shared pages) or
        None if the pool cannot satisfy the reservation."""
        n = self.pages_needed(total_tokens)
        assert not self._owned[slot], f'slot {slot} already holds pages'
        shared: List[int] = []
        for h in lookup_hashes[:n]:
            page = self._registry.get(h)
            if page is None:
                break
            shared.append(page)
        for page in shared:
            if self._refs[page] == 0:
                self._cached_free.pop(page, None)
            self._refs[page] += 1
        if n - len(shared) > len(self._free) + len(self._cached_free):
            # Cannot satisfy: bail BEFORE _alloc_page evicts anything —
            # a doomed oversized reservation must not wipe the warm
            # prefix cache on its way to being deferred.
            for p in shared:
                self._unref(p)
            return None
        private: List[int] = []
        for _ in range(n - len(shared)):
            page = self._alloc_page()
            assert page is not None   # guaranteed by the check above
            private.append(page)
        for page in private:
            self._refs[page] += 1
        self.prefix_stats['hit_pages'] += len(shared)
        self.prefix_stats['miss_pages'] += n - len(shared)
        pages = shared + private
        self._owned[slot] = pages
        row = np.zeros((self.cfg.max_pages_per_slot,), np.int32)
        row[:n] = pages
        self.tables[slot] = row
        return row, len(shared)

    def publish(self, slot: int, hashes: Sequence[bytes]) -> None:
        """Register hash -> page for the slot's leading pages (call once
        their contents are scheduled to be written — single dispatch
        chain, so later readers order after the write)."""
        pages = self._owned[slot]
        for i, h in enumerate(hashes):
            if i >= len(pages):
                break
            page = pages[i]
            if h in self._registry:
                continue      # an identical page is already published
            if page in self._page_hash:
                continue      # page already published under another key
            self._registry[h] = page
            self._page_hash[page] = h

    def release(self, slot: int) -> None:
        for page in self._owned[slot]:
            self._unref(page)
        self._owned[slot] = []
        self.tables[slot] = 0

    # ----------------------------------------------------- device kernels
    @staticmethod
    def insert_prompt(pool, prompt_kv, page_ids, src_off=0):
        """Scatter a prefill cache into reserved pages.

        pool:      [L, n_pages, H, P, d] (donated by the caller's jit)
        prompt_kv: [L, 1, S_bucket, H, d] from the prefill
        page_ids:  [n] int32 — the pages receiving prompt KV positions
                   [src_off, src_off + n*P) (n is static via the shape).
        src_off:   dynamic token offset — a prefix-cached admission only
                   writes the pages it computed, not the shared prefix.
        """
        n = page_ids.shape[0]
        l, _, _, h, d = prompt_kv.shape
        p = pool.shape[3]
        chunk = jax.lax.dynamic_slice(
            prompt_kv, (0, 0, src_off, 0, 0),
            (l, 1, n * p, h, d))[:, 0]             # [L, n*P, H, d]
        chunk = chunk.reshape(l, n, p, h, d).transpose(0, 1, 3, 2, 4)
        return pool.at[:, page_ids].set(chunk.astype(pool.dtype))

    @staticmethod
    def gather_view_layer(pool, tables):
        """One layer's per-slot contiguous KV view — the XLA decode
        path's gather (models/llama.py paged attention; on TPU the
        Pallas kernel reads pages directly instead).

        pool:   [n_pages, H, P, d]
        tables: [slots, max_pages] int32
        -> [slots, max_pages*P, H, d]
        """
        _, h, p, d = pool.shape
        slots, mp = tables.shape
        v = pool[tables]                       # [slots, mp, H, P, d]
        return v.transpose(0, 1, 3, 2, 4).reshape(slots, mp * p, h, d)

    @staticmethod
    def append_token_layer(pool, new_kv, tables, lengths):
        """Scatter one decoded token's KV for every slot, one layer —
        THE production scatter (models/llama.py paged attention).

        pool:    [n_pages, H, P, d]
        new_kv:  [slots, H, d] — the row each slot writes at
                 position lengths[slot].
        tables:  [slots, max_pages] int32
        lengths: [slots] int32 — the position the token is written at.
        """
        p = pool.shape[2]
        mp = tables.shape[1]
        page = jnp.take_along_axis(
            tables, jnp.clip(lengths // p, 0, mp - 1)[:, None],
            axis=1)[:, 0]                                    # [slots]
        off = lengths % p                                    # [slots]
        return PagePool._set_rows(pool, page, off,
                                  new_kv.astype(pool.dtype))

    @staticmethod
    def _set_rows(pool, page, off, rows):
        """pool[page[i], :, off[i]] = rows[i] for every i — the append
        scatter of all four append_* variants (rows [n, H, d] into a
        k/v pool, or [n, H] into a scale pool).

        The heads ride the scatter INDEX, so the scatter's window is
        the head-dim vector alone. With the [H, d] slab as the window
        (``pool.at[page, :, off]``), XLA:TPU lays the scattered
        page/offset dims major of H — a [pages, P, H, d] pool — and
        transposes the whole layer pool to that layout and back around
        the Pallas kernel, per layer, on every decode step, plus once
        at each end of the chunk. This form keeps the pool in the
        kernel's row-major layout throughout (tests_tpu reads the
        compiled decode step to check it)."""
        h = pool.shape[1]
        return pool.at[page[:, None], jnp.arange(h)[None, :],
                       off[:, None]].set(rows)

    @staticmethod
    def append_tokens_layer(pool, new_kv, tables, start):
        """Scatter a short run of decoded tokens per slot, one layer —
        the speculative-decode append (s = draft+1 tokens per step).

        pool:   [n_pages, H, P, d]
        new_kv: [slots, s, H, d] — token j of slot b is written at
                position start[b] + j.
        tables: [slots, max_pages] int32
        start:  [slots] int32
        """
        slots, s, h, d = new_kv.shape
        p = pool.shape[2]
        mp = tables.shape[1]
        pos = start[:, None] + jnp.arange(s)[None, :]       # [slots, s]
        page = jnp.take_along_axis(
            tables, jnp.clip(pos // p, 0, mp - 1), axis=1)  # [slots, s]
        off = pos % p
        return PagePool._set_rows(
            pool, page.reshape(-1), off.reshape(-1),
            new_kv.reshape(slots * s, h, d).astype(pool.dtype))

    # ------------------------------------------- int8-quantized kernels
    @staticmethod
    def insert_prompt_q(pool, scale_pool, prompt_kv, page_ids,
                        src_off=0):
        """Quantized insert_prompt: same contract, plus the per-token
        per-head scales scattered into scale_pool [L, n_pages, H, P].
        Returns (new_pool, new_scale_pool)."""
        n = page_ids.shape[0]
        l, _, _, h, d = prompt_kv.shape
        p = pool.shape[3]
        chunk = jax.lax.dynamic_slice(
            prompt_kv, (0, 0, src_off, 0, 0),
            (l, 1, n * p, h, d))[:, 0]             # [L, n*P, H, d]
        chunk = chunk.reshape(l, n, p, h, d).transpose(0, 1, 3, 2, 4)
        q, s = quantize_kv(chunk)                  # q [L,n,H,P,d] s [L,n,H,P]
        return (pool.at[:, page_ids].set(q),
                scale_pool.at[:, page_ids].set(s))

    @staticmethod
    def gather_view_layer_q(pool, scale_pool, tables, dtype):
        """Dequantizing gather — the XLA floor of the quantized decode
        path. pool [n_pages, H, P, d] int8 + scale_pool [n_pages, H, P]
        -> [slots, max_pages*P, H, d] at `dtype` (exactly the float
        gather_view_layer contract)."""
        _, h, p, d = pool.shape
        slots, mp = tables.shape
        v = pool[tables].astype(jnp.float32)   # [slots, mp, H, P, d]
        s = scale_pool[tables]                 # [slots, mp, H, P]
        v = (v * s[..., None]).astype(dtype)
        return v.transpose(0, 1, 3, 2, 4).reshape(slots, mp * p, h, d)

    @staticmethod
    def append_token_layer_q(pool, scale_pool, new_kv, tables, lengths):
        """Quantized append_token_layer: quantize the new row, scatter
        value + scale. Returns (new_pool, new_scale_pool)."""
        p = pool.shape[2]
        mp = tables.shape[1]
        page = jnp.take_along_axis(
            tables, jnp.clip(lengths // p, 0, mp - 1)[:, None],
            axis=1)[:, 0]                                    # [slots]
        off = lengths % p
        q, s = quantize_kv(new_kv)             # [slots, H, d], [slots, H]
        return (PagePool._set_rows(pool, page, off, q),
                PagePool._set_rows(scale_pool, page, off, s))

    @staticmethod
    def append_tokens_layer_q(pool, scale_pool, new_kv, tables, start):
        """Quantized append_tokens_layer (speculative-decode run of s
        tokens per slot). Returns (new_pool, new_scale_pool)."""
        slots, s_run, h, d = new_kv.shape
        p = pool.shape[2]
        mp = tables.shape[1]
        pos = start[:, None] + jnp.arange(s_run)[None, :]   # [slots, s]
        page = jnp.take_along_axis(
            tables, jnp.clip(pos // p, 0, mp - 1), axis=1)  # [slots, s]
        off = pos % p
        q, s = quantize_kv(new_kv.reshape(slots * s_run, h, d))
        page, off = page.reshape(-1), off.reshape(-1)
        return (PagePool._set_rows(pool, page, off, q),
                PagePool._set_rows(scale_pool, page, off, s))

    @staticmethod
    def gather_view_q(pool, scale_pool, tables, dtype):
        """All-layer dequantizing gather: [L, n_pages, H, P, d] int8 +
        [L, n_pages, H, P] scales -> [L, slots, mp*P, H, d] float."""
        return jax.vmap(
            lambda pl, sl: PagePool.gather_view_layer_q(
                pl, sl, tables, dtype))(pool, scale_pool)

    @staticmethod
    def gather_view(pool, tables):
        """All-layer convenience wrapper: [L, n_pages, H, P, d] ->
        [L, slots, mp*P, H, d]. Single-sourced on the layer kernel."""
        return jax.vmap(
            lambda pl: PagePool.gather_view_layer(pl, tables))(pool)

    @staticmethod
    def append_token(pool, new_kv, tables, lengths):
        """All-layer convenience wrapper over append_token_layer
        (pool [L, ...], new_kv [L, slots, H, d])."""
        return jax.vmap(
            lambda pl, kv: PagePool.append_token_layer(pl, kv, tables,
                                                       lengths)
        )(pool, new_kv)
