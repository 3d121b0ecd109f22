"""Paged device KVCache with block tables (the vLLM-style substrate that
Mooncake's disaggregated pool feeds — §3 step 1 loads pool blocks into
these pages, step 2 stores new pages back).

Layout (per attention layer stacked on a leading axis):

    k_pages, v_pages : ``PagedKVCache``: (L, n_pages, page_tokens) +
                       ``cfg.kv_widths``; ``DevicePagePool``: (L, n_pages,
                       page_tokens, width), lane-dense — width KV·Dh, or
                       latent attention's c_kv rank and k_pe rope width
    block_table      : (B, max_pages_per_seq) int32 — page id per slot
    seq_lens         : (B,) int32

Page allocation is host-side (a free list); attention over pages is the
``paged_attention`` kernel (Pallas) or its jnp oracle. ``page_tokens`` is
the on-device granularity and the pool's 512-token block is a multiple of
it, so a pool block maps to an integer page run.

Two tiers of API live here:

* the original functional ``PagedKVCache`` helpers (``assign_seq`` /
  ``grow_seq`` / ``write_kv`` / ``gather_kv``) — a self-contained paged
  cache whose block table and pages move together;
* ``DevicePagePool`` — the engine's substrate: ONE page store shared by
  every worker in the process (the stand-in for a node's HBM), with
  per-page refcounts, a block-hash → page-run registry so slots that hit
  the same prefix chain share physical pages, copy-on-write for shared
  partial tail pages, and LRU eviction of registry-only runs under
  allocation pressure. ``PrefillWorker`` stages fresh KV into pages and
  ``DecodeWorker.join`` adopts the run into its block table — the
  zero-copy prefill→decode handoff.
"""
from __future__ import annotations

import math
import threading
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.trace import BLOCK_TOKENS
from repro.models.layers import DTYPE


@dataclass
class PagedKVCache:
    k_pages: jax.Array          # (L, P, page, KV, Dh)
    v_pages: jax.Array
    block_table: jax.Array      # (B, max_pages) int32
    seq_lens: jax.Array         # (B,) int32
    page_tokens: int
    free: list = field(default_factory=list)   # host-side free page ids

    @property
    def n_layers(self) -> int:
        return self.k_pages.shape[0]

    @property
    def n_pages(self) -> int:
        return self.k_pages.shape[1]

    @property
    def max_pages_per_seq(self) -> int:
        return self.block_table.shape[1]


def init_paged_cache(cfg: ModelConfig, *, batch: int, n_pages: int,
                     page_tokens: int = 64,
                     max_seq: int = 32768) -> PagedKVCache:
    La = cfg.attention_layers
    wk, wv = cfg.kv_widths
    max_pages = (max_seq + page_tokens - 1) // page_tokens
    return PagedKVCache(
        k_pages=jnp.zeros((La, n_pages, page_tokens) + wk, DTYPE),
        v_pages=jnp.zeros((La, n_pages, page_tokens) + wv, DTYPE),
        block_table=jnp.zeros((batch, max_pages), jnp.int32),
        seq_lens=jnp.zeros((batch,), jnp.int32),
        page_tokens=page_tokens,
        free=list(range(n_pages - 1, 0, -1)),  # page 0 = null page
    )


# ---------------------------------------------------------------------------
# host-side allocation
# ---------------------------------------------------------------------------

def alloc_pages(cache: PagedKVCache, n: int) -> list[int]:
    if len(cache.free) < n:
        raise MemoryError(f"paged cache OOM: want {n}, free {len(cache.free)}")
    return [cache.free.pop() for _ in range(n)]


def free_seq(cache: PagedKVCache, slot: int) -> PagedKVCache:
    """Release all pages of a batch slot back to the free list."""
    table = np.asarray(cache.block_table)
    lens = np.asarray(cache.seq_lens)
    n_used = int(np.ceil(lens[slot] / cache.page_tokens))
    cache.free.extend(int(p) for p in table[slot, :n_used] if p != 0)
    table = table.copy()
    table[slot] = 0
    lens = lens.copy()
    lens[slot] = 0
    return PagedKVCache(cache.k_pages, cache.v_pages,
                        jnp.asarray(table), jnp.asarray(lens),
                        cache.page_tokens, cache.free)


def assign_seq(cache: PagedKVCache, slot: int, n_tokens: int) -> PagedKVCache:
    """Allocate pages for a new sequence of ``n_tokens`` in ``slot``."""
    n = (n_tokens + cache.page_tokens - 1) // cache.page_tokens
    pages = alloc_pages(cache, n)
    table = np.asarray(cache.block_table).copy()
    table[slot, :n] = pages
    table[slot, n:] = 0
    lens = np.asarray(cache.seq_lens).copy()
    lens[slot] = n_tokens
    return PagedKVCache(cache.k_pages, cache.v_pages,
                        jnp.asarray(table), jnp.asarray(lens),
                        cache.page_tokens, cache.free)


def grow_seq(cache: PagedKVCache, slot: int, extra: int = 1) -> PagedKVCache:
    """Extend a sequence; allocates a fresh page at a page boundary."""
    table = np.asarray(cache.block_table).copy()
    lens = np.asarray(cache.seq_lens).copy()
    old, new = int(lens[slot]), int(lens[slot]) + extra
    n_old = (old + cache.page_tokens - 1) // cache.page_tokens
    n_new = (new + cache.page_tokens - 1) // cache.page_tokens
    if n_new > n_old:
        pages = alloc_pages(cache, n_new - n_old)
        table[slot, n_old:n_new] = pages
    lens[slot] = new
    return PagedKVCache(cache.k_pages, cache.v_pages,
                        jnp.asarray(table), jnp.asarray(lens),
                        cache.page_tokens, cache.free)


# ---------------------------------------------------------------------------
# device-side reads / writes (jit-able; tables are traced inputs)
# ---------------------------------------------------------------------------

def write_kv(cache: PagedKVCache, slot: int, start: int,
             k_new: jax.Array, v_new: jax.Array) -> PagedKVCache:
    """Write (L, S, KV, Dh) new KV of one sequence into its pages,
    starting at token offset ``start``. Host loop over touched pages
    (S and the table are known host-side at engine level).

    A write that runs past the slot's assigned pages (page-table entry 0,
    the reserved null page) raises instead of silently corrupting page 0:
    callers must ``assign_seq``/``grow_seq`` first."""
    pt = cache.page_tokens
    table = np.asarray(cache.block_table)
    S = k_new.shape[1]
    k_pages, v_pages = cache.k_pages, cache.v_pages
    tok = start
    while tok < start + S:
        page_idx = tok // pt
        off = tok % pt
        n = min(pt - off, start + S - tok)   # stop at the page boundary
        if page_idx >= table.shape[1]:
            raise IndexError(
                f"write_kv overruns the block table: token {tok} needs page "
                f"index {page_idx} but the table holds {table.shape[1]} "
                f"pages per sequence (grow max_seq or shorten the write)")
        pid = int(table[slot, page_idx])
        if pid == 0:
            raise IndexError(
                f"write_kv into unassigned page: slot {slot} token {tok} "
                f"maps to table entry {page_idx} = 0 (the null page) — "
                f"assign_seq/grow_seq the sequence before writing")
        src = slice(tok - start, tok - start + n)
        k_pages = jax.lax.dynamic_update_slice(
            k_pages, k_new[:, src][:, None],
            (0, pid, off) + (0,) * (k_pages.ndim - 3))
        v_pages = jax.lax.dynamic_update_slice(
            v_pages, v_new[:, src][:, None],
            (0, pid, off) + (0,) * (v_pages.ndim - 3))
        tok += n
    return PagedKVCache(k_pages, v_pages, cache.block_table, cache.seq_lens,
                        pt, cache.free)


def gather_kv(cache: PagedKVCache, max_tokens: int):
    """Materialise per-sequence contiguous KV (L, B, max_tokens, KV, Dh)
    from pages via the block table — the pure-jnp paged read used by the
    engine on CPU (the Pallas kernel fuses this gather with attention).

    ``max_tokens`` that is not a multiple of ``page_tokens`` rounds UP to
    whole pages and the surplus tail tokens are sliced off — previously
    the partial page was silently dropped."""
    pt = cache.page_tokens
    n = (max_tokens + pt - 1) // pt
    tbl = cache.block_table[:, :n]                     # (B, n)
    k = cache.k_pages[:, tbl]                          # (L, B, n, pt, KV, Dh)
    v = cache.v_pages[:, tbl]
    L, B = k.shape[0], k.shape[1]
    k = k.reshape(L, B, n * pt, *k.shape[4:])[:, :, :max_tokens]
    v = v.reshape(L, B, n * pt, *v.shape[4:])[:, :, :max_tokens]
    return k, v


def page_write(page_tokens: int, *, per_page: bool, sharding=None):
    """The jitted page write behind ``DevicePagePool.write_run``:
    ``(k_pages, v_pages, idx, k, v) -> (k_pages, v_pages)`` with (L, T,
    ...) K and V (lane-dense rows as ``write_run`` passes them, or by
    token) written into the pages ``idx`` of the (L, P, page, width)
    slabs (a partial tail page zero-padded). Both slabs are donated, so
    the write happens in place; on a mesh, ``sharding`` is pinned as the
    slabs' output sharding.

    ``per_page``: one ``dynamic_update_slice`` per page, which touches
    only that page where pages are contiguous in the slab's layout.
    Otherwise one scatter: where the device layout puts the page axis
    minor-most, every page is strided over the whole slab, and one pass
    beats a pass per page. On a TPU v5e, into (L, P, page, KV, Dh)
    slabs: 1.0 against 17.6 ms per 8-page write into qwen2.5-3b's (pages
    major), 26 against 333 ms into phi3-mini's (pages minor; lane-dense,
    its pages are major). One flag for both slabs, or a (k, v) pair where
    their layouts differ (a latent pair's 64-wide ``k_pe`` slab puts its
    pages minor-most, its 512-wide ``c_kv`` slab does not)."""
    pt = page_tokens
    pk, pv = (per_page, per_page) if isinstance(per_page, bool) \
        else per_page

    def write(k_pages, v_pages, idx, k, v):
        n = idx.shape[0]

        def paged(x, slab):
            x = x.astype(slab.dtype)
            x = jnp.pad(x, ((0, 0), (0, n * pt - x.shape[1]))
                        + ((0, 0),) * (x.ndim - 2))
            return x.reshape((x.shape[0], n, pt) + slab.shape[3:])

        k, v = paged(k, k_pages), paged(v, v_pages)
        if not pk:
            k_pages = k_pages.at[:, idx].set(k)
        if not pv:
            v_pages = v_pages.at[:, idx].set(v)
        for i in range(n):
            at = (0, idx[i])
            if pk:
                k_pages = jax.lax.dynamic_update_slice(
                    k_pages, k[:, i:i + 1], at + (0,) * (k.ndim - 2))
            if pv:
                v_pages = jax.lax.dynamic_update_slice(
                    v_pages, v[:, i:i + 1], at + (0,) * (v.ndim - 2))
        return k_pages, v_pages

    out = None if sharding is None else (sharding, sharding)
    return jax.jit(write, donate_argnums=(0, 1), out_shardings=out)


# ---------------------------------------------------------------------------
# shared device page pool (the engine's paged decode substrate)
# ---------------------------------------------------------------------------

class DevicePagePool:
    """One process-wide paged KV store: the stand-in for a serving node's
    HBM that both ``PrefillWorker`` (writes fresh pages, §3 step 2) and
    ``DecodeWorker`` (attends them through block tables, §3 step 4) share.

    * ``k_pages``/``v_pages``: (L, P, page_tokens, width), lane-dense:
      each token's ``cfg.kv_widths`` flattened (KV·Dh, or a latent
      width), so a layer's rows are a contiguous run the decode step can
      write and gather in place. Every API here takes and returns K and V
      by token, (L, T) + ``cfg.kv_widths``; the flattening stays inside.
      Page 0 is the reserved null page (block tables pad with it, reads of
      it are always masked).
    * per-page REFCOUNTS (host side): a page is held by the hash-run
      registry and/or by block-table rows / staged prefill results.
      ``release`` at refcount 0 returns it to the free list; below 0
      raises (double-free guard).
    * REGISTRY: pool block hash → integer page run (``BLOCK_TOKENS`` is a
      multiple of ``page_tokens``). Slots whose chains share a prefix
      adopt the SAME physical pages — the device-side analogue of the
      DRAM pool's prefix reuse. Registry-only runs (refcount 1) are
      evicted LRU under allocation pressure; runs referenced by a live
      slot are pinned.
    * COPY-ON-WRITE: ``make_writable`` copies a shared page before a slot
      appends into it; full prefix pages are never written during decode,
      so in practice COW only triggers at a shared partial tail page
      (e.g. one ``PrefillResult`` joined into several slots).
    * MESH SHARDING (``mesh=(data, model)``): the page slabs become ONE
      global array laid out under ``P(None, 'data', None, 'model')``
      — the page axis splits into per-data-shard BANKS of ``n_pages``
      pages each (so capacity scales ×data) and the width stripes over
      the model axis, KV % model == 0, so each stripe is whole KV heads
      (so per-device slab bytes shrink ÷model). Every
      host-side structure here stays LOGICAL and global: page ids are
      global (``bank_of`` recovers the bank, local id = global %
      ``bank_pages``, and global ids ≡ 0 mod ``bank_pages`` are each
      bank's reserved null page), refcounts/generations are one global
      array, and the registry/free lists are per bank because a data
      shard's rows can only attend pages resident on that shard. With
      ``mesh=None`` everything degrades to the original single-bank pool
      (``self.free``/``self.runs``/``self._lru`` ARE bank 0's objects).
    """

    def __init__(self, cfg: ModelConfig, *, n_pages: int,
                 page_tokens: int = 64, mesh=None) -> None:
        if BLOCK_TOKENS % page_tokens:
            raise ValueError(
                f"page_tokens={page_tokens} must divide the pool block "
                f"({BLOCK_TOKENS} tokens) so a block maps to a page run")
        La, KV = cfg.attention_layers, cfg.n_kv_heads
        wk, wv = (math.prod(w) for w in cfg.kv_widths)
        self.cfg = cfg
        self.page_tokens = page_tokens
        self.mesh = mesh
        d = 1
        if mesh is not None:
            d = int(mesh.shape.get("data", 1))
            m = int(mesh.shape.get("model", 1))
            if cfg.mla is not None:
                raise ValueError("a latent page slab has no head axis to "
                                 "stripe over a mesh")
            if KV % m:
                raise ValueError(
                    f"{KV} kv heads do not stripe over model={m} shards")
        self.n_banks = d
        self.bank_pages = n_pages       # per-bank budget incl. its null page
        total = d * n_pages
        shape = (La, total, page_tokens, wk)
        if mesh is None:
            self._sharding = None
            self.k_pages = jnp.zeros(shape, DTYPE)
            self.v_pages = jnp.zeros((La, total, page_tokens, wv), DTYPE)
        else:
            from jax.sharding import NamedSharding, PartitionSpec
            self._sharding = NamedSharding(
                mesh, PartitionSpec(None, "data", None, "model"))
            self._src_sharding = NamedSharding(
                mesh, PartitionSpec(None, None, "model"))
            self.k_pages = jax.device_put(jnp.zeros(shape, DTYPE),
                                          self._sharding)
            self.v_pages = jax.device_put(jnp.zeros(shape, DTYPE),
                                          self._sharding)
        # each slab's device layout decides how a page is written into it
        per_page = tuple(x.format.layout.major_to_minor[-1] != 1
                         for x in (self.k_pages, self.v_pages))
        self._write = page_write(page_tokens, per_page=per_page,
                                 sharding=self._sharding)
        # reentrant: alloc -> eviction -> unregister -> release re-enters
        self._lock = threading.RLock()
        # one free list / registry / LRU per bank; bank 0's objects are
        # also exposed under the historical names so single-bank callers
        # (and every pre-mesh test) see the original pool unchanged
        self._bank_free: list[list[int]] = [           #: guarded_by self._lock
            list(range((b + 1) * n_pages - 1, b * n_pages, -1))
            for b in range(d)]
        self.free: list[int] = self._bank_free[0]  #: guarded_by self._lock
        self.refs = np.zeros(total, np.int32)  #: guarded_by self._lock
        self.gens = np.zeros(total, np.int64)  #: guarded_by self._lock
        self._bank_runs: list[dict[int, list[int]]] = [  #: guarded_by self._lock
            {} for _ in range(d)]
        self._bank_lru: list[list[int]] = [    #: guarded_by self._lock
            [] for _ in range(d)]
        self.runs: dict[int, list[int]] = self._bank_runs[0]  #: guarded_by self._lock
        self._lru: list[int] = self._bank_lru[0]   #: guarded_by self._lock
        #: guarded_by self._lock
        self.counters = dict(pages_written=0, shared_adoptions=0,
                             cow_copies=0, registry_evictions=0,
                             alloc_failures=0, pages_exported=0,
                             pages_imported=0)

    def _pin(self, x: jax.Array) -> jax.Array:
        """Keep a slab on its mesh sharding after an eager ``.at[]``
        update (eager updates preserve input shardings today; this guards
        the invariant rather than trusting it)."""
        if self._sharding is not None and x.sharding != self._sharding:
            x = jax.device_put(x, self._sharding)
        return x

    # ---- geometry ------------------------------------------------------
    @property
    def n_pages(self) -> int:
        """GLOBAL page count across every bank (``n_banks·bank_pages``
        — the historical meaning for an unmeshed single-bank pool)."""
        return self.k_pages.shape[1]

    def bank_of(self, page: int) -> int:
        """Data-shard bank a global page id lives on."""
        return page // self.bank_pages

    @property
    def pages_per_block(self) -> int:
        return BLOCK_TOKENS // self.page_tokens

    def pages_for(self, n_tokens: int) -> int:
        return (n_tokens + self.page_tokens - 1) // self.page_tokens

    @property
    def page_bytes(self) -> int:
        """Bytes of one page's k and v together, over all layers."""
        return (self.k_pages.nbytes + self.v_pages.nbytes) \
            // self.k_pages.shape[1]

    @property
    def pages_written(self) -> int:
        """Pages written by ``write_run`` over the pool's life."""
        with self._lock:
            return self.counters["pages_written"]

    @property
    def used_pages(self) -> int:
        with self._lock:
            return int((self.refs > 0).sum())

    @property
    def free_pages(self) -> int:
        """Mesh-wide LOGICAL free pages (sum over banks)."""
        with self._lock:
            return sum(len(f) for f in self._bank_free)

    @property
    def occupancy(self) -> float:
        """Fraction of usable pages (null pages excluded) currently held."""
        cap = self.n_pages - self.n_banks
        return self.used_pages / cap if cap else 1.0

    def pressure(self) -> dict:
        """Occupancy snapshot for admission backpressure — mesh-wide and
        LOGICAL: one page counted once no matter how its bytes stripe over
        the model axis, capacity summed over the data banks. ``pinned``
        pages (held by a live slot or staged result, not reclaimable) are
        the signal that matters: registry-only runs evict on demand, so
        high occupancy with low ``pinned_frac`` is a warm cache, not
        pressure."""
        with self._lock:
            cap = self.n_pages - self.n_banks
            evictable = sum(
                len(self._bank_runs[b][h])
                for b in range(self.n_banks)
                for h in self._evictable_locked(b))
            used = int((self.refs > 0).sum())
            pinned = used - evictable
            return dict(
                capacity=cap, free=sum(len(f) for f in self._bank_free),
                used=used, evictable=evictable, pinned=pinned,
                occupancy=used / cap if cap else 1.0,
                pinned_frac=pinned / cap if cap else 1.0)

    # ---- refcounted allocation ----------------------------------------
    def _evictable_locked(self, bank: int = 0) -> list[int]:
        """One bank's registered block hashes held ONLY by the registry,
        LRU first. Caller holds ``self._lock``."""
        return [h for h in self._bank_lru[bank]
                if all(self.refs[p] == 1 for p in self._bank_runs[bank][h])]

    def alloc(self, n: int, bank: int = 0) -> list[int]:
        """Take ``n`` fresh pages from one bank (refcount 1 each),
        evicting that bank's registry-only runs LRU when its free list
        runs short. Raises ``MemoryError`` (taking nothing) if pressure
        can't be relieved. Returned ids are GLOBAL."""
        with self._lock:
            free = self._bank_free[bank]
            if len(free) < n:
                for h in self._evictable_locked(bank):
                    self.unregister(h, bank=bank)
                    if len(free) >= n:
                        break
            if len(free) < n:
                self.counters["alloc_failures"] += 1
                raise MemoryError(
                    f"device page pool OOM: want {n} pages, "
                    f"free {len(free)} of {self.bank_pages - 1} "
                    f"in bank {bank}")
            pages = [free.pop() for _ in range(n)]
            for p in pages:
                self.refs[p] = 1
                self.gens[p] += 1
            return pages

    def best_stage_bank(self, hash_ids: list[int]) -> int:
        """Bank a fresh staging run should target: deepest registered
        prefix of this chain wins (maximises zero-copy adoption), free
        pages break ties (spreads load across the data shards)."""
        if self.n_banks == 1:
            return 0
        with self._lock:
            best, best_key = 0, None
            for b in range(self.n_banks):
                key = (self.lookup_chain(hash_ids, bank=b),
                       len(self._bank_free[b]), -b)
                if best_key is None or key > best_key:
                    best, best_key = b, key
            return best

    def gens_of(self, pages: list[int]) -> list[int]:
        """Allocation generations of a page run — a holder snapshots them
        and re-checks before taking late references (a freed-and-realloc'd
        page must read as STALE, never as someone else's KV)."""
        with self._lock:
            return [int(self.gens[p]) for p in pages]

    def retain(self, pages: list[int]) -> None:
        with self._lock:
            for p in pages:
                if self.refs[p] <= 0:
                    raise RuntimeError(f"retain of unowned page {p}")
                self.refs[p] += 1

    def release(self, pages: list[int]) -> None:
        with self._lock:
            for p in pages:
                if p == 0:
                    continue                # null-page padding in tables
                if self.refs[p] <= 0:
                    raise RuntimeError(f"double free of page {p}")
                self.refs[p] -= 1
                if self.refs[p] == 0:
                    self._bank_free[self.bank_of(p)].append(p)

    # ---- block-hash registry (cross-slot prefix sharing) ---------------
    def register_block(self, hash_id: int, pages: list[int]) -> None:
        """Publish a full block's page run for later chains to adopt.
        The registry holds one reference of its own. The run's bank is
        implied by its pages (a data shard's rows can only attend pages
        resident on that shard, so sharing never crosses banks — the same
        prefix may register independently per bank)."""
        assert len(pages) == self.pages_per_block
        bank = self.bank_of(pages[0])
        assert all(self.bank_of(p) == bank for p in pages), \
            f"page run straddles banks: {pages}"
        with self._lock:
            if hash_id in self._bank_runs[bank]:  # racing identical prefills
                return
            self.retain(pages)
            self._bank_runs[bank][hash_id] = list(pages)
            self._bank_lru[bank].append(hash_id)

    def unregister(self, hash_id: int, bank: Optional[int] = None) -> None:
        """Evict a registered run — from one bank, or (``bank=None``)
        from every bank holding an independent copy of it."""
        banks = range(self.n_banks) if bank is None else (bank,)
        with self._lock:
            for b in banks:
                pages = self._bank_runs[b].pop(hash_id, None)
                if pages is None:
                    continue
                self._bank_lru[b].remove(hash_id)
                self.release(pages)
                self.counters["registry_evictions"] += 1

    def lookup_chain(self, hash_ids: list[int], bank: int = 0) -> int:
        """Deepest consecutive registered prefix in one bank (no side
        effects)."""
        with self._lock:
            runs = self._bank_runs[bank]
            n = 0
            for h in hash_ids:
                if h not in runs:
                    break
                n += 1
            return n

    def adopt_chain(self, hash_ids: list[int],
                    bank: int = 0) -> tuple[int, list[int]]:
        """Retain + return the page runs of the chain's registered prefix
        in one bank: (n_blocks_adopted, flat page ids). The caller owns
        one reference per page; physical pages are SHARED with every
        other adopter of that bank."""
        with self._lock:
            n = self.lookup_chain(hash_ids, bank=bank)
            runs, lru = self._bank_runs[bank], self._bank_lru[bank]
            pages: list[int] = []
            for h in hash_ids[:n]:
                run = runs[h]
                self.retain(run)
                pages.extend(run)
                lru.remove(h)               # touch recency
                lru.append(h)
            if n:
                self.counters["shared_adoptions"] += n
            return n, pages

    # ---- device writes -------------------------------------------------
    def write_run(self, pages: list[int], k, v) -> None:
        """Scatter (L, T) + ``cfg.kv_widths`` K and V, or the same as
        lane-dense rows (L, T, width), into ``pages`` (T ≤
        len(pages)·page). ``k``/``v`` are host arrays or device arrays (a
        prefill's dense cache, sliced). One jitted write (``page_write``)
        of (L, T, width) rows that donates both slabs, so the pages are
        written in place; a partial tail page is zero-padded (fresh pages,
        nothing to preserve). Compiles once per (pages, T) shape, whatever
        the pages' ids or where the KV came from."""
        assert k.shape[1] <= len(pages) * self.page_tokens, \
            (k.shape, len(pages), self.page_tokens)
        # rows by token (free on the host; on the device a source that is
        # not yet lane-dense is laid out once here)
        k = k.reshape(k.shape[:2] + (-1,))
        v = v.reshape(v.shape[:2] + (-1,))
        idx = np.asarray(pages, np.int32)
        if self._sharding is not None:
            # a device source may sit on another device than the slabs:
            # lay it out as the slabs stripe their width (whole KV heads)
            k = jax.device_put(k, self._src_sharding)
            v = jax.device_put(v, self._src_sharding)
        with self._lock:
            self.k_pages, self.v_pages = self._write(
                self.k_pages, self.v_pages, idx, k, v)
            self.counters["pages_written"] += len(pages)

    def make_writable(self, page: int) -> int:
        """Copy-on-write: return a page id safe to append into. A page
        with a single owner is returned as-is; a shared page is copied to
        a fresh page IN THE SAME BANK (a slot's pages must stay on its
        data shard; the caller must drop its reference to the old id and
        point its table at the new one)."""
        with self._lock:
            if self.refs[page] == 1:
                return page
            (new,) = self.alloc(1, bank=self.bank_of(page))
            self.k_pages = self._pin(
                self.k_pages.at[:, new].set(self.k_pages[:, page]))
            self.v_pages = self._pin(
                self.v_pages.at[:, new].set(self.v_pages[:, page]))
            self.release([page])
            self.counters["cow_copies"] += 1
            return new

    # ---- device↔host tier transfers (preemption spill/restore) ---------
    def export_run(self, pages: list[int], n_tokens: int):
        """Demote a live page run to host memory: gather its contiguous
        (L, n_tokens, KV, Dh) KV into fresh host arrays, then RELEASE the
        caller's reference to ``pages`` — ownership of the run transfers
        into the returned ``(k, v)`` bytes (the device→host rung of the
        HBM↔DRAM↔SSD ladder; ``import_run``/``stage_run`` bring them
        back). The arrays are explicit copies: freed pages may be
        reallocated and rewritten at any time, so no view of device
        buffers may escape."""
        k, v = self.read_seq(pages, n_tokens)
        # read_seq's np.asarray can alias the device buffer on CPU jax —
        # materialise before the pages go back on the free list
        k, v = k.copy(), v.copy()
        with self._lock:
            self.release(pages)
            self.counters["pages_exported"] += len(pages)
        return k, v

    def import_run(self, k: np.ndarray, v: np.ndarray,
                   n_tokens: int, bank: int = 0) -> list[int]:
        """Promote host KV back into device pages: alloc a fresh run in
        one bank and scatter ``(L, n_tokens) + cfg.kv_widths`` into it. The
        caller owns one reference per returned page (the inverse of
        ``export_run``; the registry is NOT touched — use ``stage_run``
        to re-share full blocks). Raises ``MemoryError`` holding
        nothing."""
        pages = self.alloc(self.pages_for(n_tokens), bank=bank)
        try:
            self.write_run(pages, k[:, :n_tokens], v[:, :n_tokens])
        except BaseException:
            self.release(pages)
            raise
        with self._lock:
            self.counters["pages_imported"] += len(pages)
        return pages

    # ---- host-side reads (oracle/debug) --------------------------------
    def read_seq(self, pages: list[int], n_tokens: int):
        """Gather one sequence's contiguous (L, n_tokens) +
        ``cfg.kv_widths`` K and V."""
        idx = jnp.asarray(pages, jnp.int32)
        L = self.k_pages.shape[0]
        wk, wv = self.cfg.kv_widths
        k = self.k_pages[:, idx]            # (L, n, pt, width)
        v = self.v_pages[:, idx]
        k = k.reshape(L, -1, *wk)[:, :n_tokens]
        v = v.reshape(L, -1, *wv)[:, :n_tokens]
        return np.asarray(k), np.asarray(v)

    def stats(self) -> dict:
        """Unified snapshot (the cross-component ``stats()`` protocol:
        taken under the lock, plain dict, stable key names): lifetime
        counters + the ``pressure()`` occupancy gauges."""
        with self._lock:
            out = dict(self.counters)
            out.update(self.pressure())
            return out

    def check_leaks(self) -> None:
        """Invariant: every non-free page is referenced and vice versa,
        per bank; each bank's null page is never allocated (property
        tests call this after each op)."""
        with self._lock:
            n_free = 0
            free: set[int] = set()
            for b, bank_free in enumerate(self._bank_free):
                n_free += len(bank_free)
                free |= set(bank_free)
                assert all(self.bank_of(p) == b for p in bank_free), \
                    f"bank {b} free list holds foreign pages"
            for p in range(self.n_pages):
                if p % self.bank_pages == 0:    # a bank's null page
                    assert p not in free and self.refs[p] == 0, \
                        f"null page {p} entered circulation"
                elif p in free:
                    assert self.refs[p] == 0, \
                        f"freed page {p} still referenced"
                else:
                    assert self.refs[p] > 0, \
                        f"page {p} leaked (no ref, not free)"
            assert len(free) == n_free, "free list duplicates"
