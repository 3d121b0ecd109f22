"""Serving engines — the executable counterpart of the simulator.

This is a real (CPU-runnable, reduced-model) implementation of the §3
workflow: a host-DRAM KVCache pool holding 512-token blocks keyed by
prefix-chained hashes, a prefill worker that reuses pool blocks and runs
*chunked incremental prefill* (§3 step 2), layer-wise store-back of fresh
blocks (§5.2 semantics), and a continuous-batching decode worker whose
batch slots sit at independent depths (per-slot cache lengths).

The disaggregated pair (PrefillWorker feeding DecodeWorker through the
pool) is what examples/serve_cluster.py drives with a Conductor in front.
"""
from __future__ import annotations

import hashlib
import threading
import time
import warnings
from dataclasses import dataclass, field
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.cache import CachePool
from repro.core.trace import BLOCK_TOKENS
from repro.models.layers import DTYPE
from repro.models.transformer import (Caches, KVCache, decode_step,
                                      decode_step_paged,
                                      decode_step_paged_sharded, init_caches,
                                      paged_shard_reason, prefill)
from repro.serving.request import ServingRequest
from repro.serving.tracing import span
from repro.serving.transport import InProcPeer, PeerError, fallback_reason


def prefix_hash_ids(tokens: np.ndarray, block: int = BLOCK_TOKENS) -> list[int]:
    """Chained block hashes of a token sequence (Figure 3): block i's key
    commits to all tokens ≤ its end, so equal ids ⇔ equal prefixes."""
    out: list[int] = []
    h = hashlib.sha256()
    n_full = len(tokens) // block
    for i in range(n_full):
        h.update(np.ascontiguousarray(tokens[i * block:(i + 1) * block]).tobytes())
        out.append(int.from_bytes(h.copy().digest()[:8], "little"))
    return out


class PrefixHasher:
    """Incremental chained block hasher with a per-session memo.

    ``prefix_hash_ids`` recomputes the full SHA-256 chain per request —
    O(prompt) crypto hashing even when turn t+1 of a session merely
    extends turn t's prompt. The memo keeps, per session, the hasher
    STATE after the deepest previously-hashed block plus the exact tokens
    it commits to; a revisit verifies the prefix with one array compare
    (memcmp speed, ~an order of magnitude cheaper than SHA-256) and
    SHA-hashes only the suffix blocks. A diverging prefix falls back to
    the full chain and replaces the memo — ids are always identical to
    ``prefix_hash_ids``.
    """

    def __init__(self, block: int = BLOCK_TOKENS,
                 capacity_sessions: int = 256) -> None:
        from collections import OrderedDict
        self.block = block
        self.capacity = capacity_sessions
        # session -> (committed tokens, ids, sha256 state after deepest
        # block), LRU-bounded: each entry pins O(prompt) host tokens
        self._memo: "OrderedDict" = OrderedDict()
        self.blocks_hashed = 0
        self.memo_hits = 0

    def hash_ids(self, tokens: np.ndarray, session=None) -> list[int]:
        block = self.block
        n_full = len(tokens) // block
        out: list[int] = []
        h = hashlib.sha256()
        start = 0
        if session is not None:
            m = self._memo.get(session)
            if m is not None:
                mtok, mids, mh = m
                d = len(mids)
                if d <= n_full and np.array_equal(
                        np.asarray(tokens[:d * block]), mtok):
                    out = list(mids)
                    h = mh.copy()
                    start = d
                    self.memo_hits += 1
        for i in range(start, n_full):
            h.update(np.ascontiguousarray(
                tokens[i * block:(i + 1) * block]).tobytes())
            out.append(int.from_bytes(h.copy().digest()[:8], "little"))
        self.blocks_hashed += n_full - start
        if session is not None and n_full:
            self._memo[session] = (
                np.asarray(tokens[:n_full * block]).copy(), list(out), h)
            self._memo.move_to_end(session)
            while len(self._memo) > self.capacity:
                self._memo.popitem(last=False)
        return out


@dataclass
class FetchPlan:
    """Side-effect-free snapshot of a hash chain's residency: which prefix
    blocks are resident and in which tier. The engine plans the §5.2
    load-vs-compute split off this, then commits via ``finish_fetch``.

    With a ``GlobalBlockDirectory`` a miss can resolve to a PEER node
    (tier ``"peer"``); ``sources[i]`` then names the owning node. The
    directory is advisory — every peer block re-verifies at fetch time
    and a stale entry degrades to recompute, never to wrong bytes."""
    hash_ids: list[int]
    tiers: list[str]                # per resident block: dram | ssd | peer
    sources: Optional[list] = None  # per block: owner node id (peer only)

    @property
    def n_resident(self) -> int:
        return len(self.tiers)

    @property
    def has_ssd(self) -> bool:
        return "ssd" in self.tiers

    @property
    def has_remote(self) -> bool:
        return "peer" in self.tiers

    def source(self, i: int):
        return self.sources[i] if self.sources is not None else None

    def truncate(self, n: int) -> "FetchPlan":
        return FetchPlan(self.hash_ids, self.tiers[:n],
                         None if self.sources is None else self.sources[:n])


class PeerSource:
    """Read-side adapter over a peer transport — the Messenger's
    cross-node block channel, transport-agnostic.

    The peer object is either an ``InProcPeer`` (sibling ``HostKVPool``
    in this process) or a ``SocketPeer`` (wire protocol); both raise the
    SAME taxonomy (``PeerUnreachable``/``StaleDirectory``/``TornFrame``
    from ``repro.serving.transport``), so this adapter — and every
    ``fallback_reasons`` branch downstream — cannot tell the transports
    apart. ``read_layer`` maps a taxonomy error to a per-key reason
    (``peer_unreachable`` — the node died; ``stale_directory`` — the
    peer no longer holds the block; ``verify_failed`` — bytes present
    but integrity-rejected) and returns ``None``, exactly like a failed
    local store read, so the fetching pool can log WHY it fell back to
    recompute and self-heal the directory.
    """

    def __init__(self, node, peer) -> None:
        self.node = node
        self.peer = peer
        self.reasons: dict[int, str] = {}

    @property
    def n_layers(self) -> int:
        if self.peer is None:
            return 0
        try:
            return self.peer.n_layers
        except PeerError:
            return 0

    def note_empty(self, key: int) -> None:
        """Classify a fetch that never started: a dead peer vs an alive
        peer with nothing to serve (the directory entry was stale)."""
        if key in self.reasons:
            return
        if self.peer is None:
            self.reasons[key] = "peer_unreachable"
            return
        try:
            self.peer.n_layers
        except PeerError as e:
            self.reasons[key] = fallback_reason(e)
        else:
            self.reasons[key] = "stale_directory"

    def read_layer(self, key: int, layer: int):
        if self.peer is None:
            self.reasons[key] = "peer_unreachable"
            return None
        try:
            return self.peer.read_layer(key, layer)
        except PeerError as e:
            self.reasons[key] = fallback_reason(e)
            return None


class HostKVPool:
    """Two-tier CPU KVCache pool: prefix-hash → per-layer KV block bytes.

    Metadata/eviction delegated to ``CachePool``/``TieredCachePool``
    (``core/tiered.py`` — same demote-on-evict / promote-on-hit semantics
    the simulator prices). Models Figure 3's 'KVCache pool in CPU memory'
    plus the paper's SSD rung:

    * ``ssd_capacity_blocks`` alone keeps demoted bytes in host arrays
      (the pre-SSD-store behaviour — the tier split is the metadata/cost
      model's concern only);
    * with ``ssd_dir`` the SSD tier is REAL: demotions batch-write to a
      checksummed ``SSDBlockStore`` file, promotions read them back, and
      ``start_prefetch``/``finish_fetch`` expose the async layer-wise load
      path the ``PrefillWorker`` overlaps with head recompute (§5.2).
      A block whose on-disk bytes fail verification is discarded from the
      hierarchy and silently becomes a miss — never wrong bytes.

    With a shared ``GlobalBlockDirectory`` (+ ``node_id`` and peers wired
    via ``add_peer``/``connect_pools``) the pool joins the Figure-3
    cluster-wide pool: its tier moves publish to the directory, and
    ``plan_fetch`` resolves local misses to a peer's DRAM or SSD. Peer
    blocks stream through the same ``AsyncPrefetcher`` layer-major queue,
    verify before their metadata enters the local hierarchy, and on ANY
    failure (dead peer, stale directory entry, corrupt remote slot) the
    run truncates to recompute with the reason recorded in
    ``fallback_reasons`` — wrong bytes are impossible.
    """

    def __init__(self, capacity_blocks: Optional[int] = None,
                 policy: str = "lru", ssd_capacity_blocks: int = 0,
                 ssd_policy: str = "lru", writeback_batch: int = 8,
                 ssd_dir: Optional[str] = None,
                 ssd_read_bw: Optional[float] = None,
                 ssd_write_bw: Optional[float] = None,
                 spec=None, directory=None, node_id=None) -> None:
        from repro.configs.base import CacheTierSpec
        if spec is None:
            spec = CacheTierSpec(
                dram_blocks=capacity_blocks, ssd_blocks=ssd_capacity_blocks,
                dram_policy=policy, ssd_policy=ssd_policy,
                writeback_batch=writeback_batch, ssd_dir=ssd_dir)
        self.spec = spec
        self.meta: CachePool = spec.make_pool()
        self.data: dict[int, tuple[np.ndarray, np.ndarray]] = {}
        self.store = None
        self.prefetcher = None
        self.directory = directory
        self.node_id = node_id
        self.peers: dict = {}           # node id -> peer HostKVPool
        self.alive = True               # kill() = failure-injection switch
        self.peer_blocks_fetched = 0
        self.peer_fetch_failures = 0
        self.fallback_reasons: dict[str, int] = {}
        self._inflight: dict[int, tuple[np.ndarray, np.ndarray]] = {}
        # preemption spill slab: req_id -> (k, v, n_tokens) of a victim's
        # exported device run (the HBM→DRAM rung). Unlike the block pool
        # above this is keyed per REQUEST (live decode tails are private,
        # not prefix-shareable) and entries are explicitly popped on
        # restore/abandon. Written by the serving-loop thread, read by
        # stats() from any thread — hence its own lock.
        self._spill_lock = threading.Lock()
        self._spill: dict[int, tuple[np.ndarray, np.ndarray, int]] = {}  #: guarded_by self._spill_lock
        #: guarded_by self._spill_lock
        self._spill_counters = dict(spills=0, spill_restores=0,
                                    spill_drops=0)
        if spec.ssd_dir is not None and not spec.tiered:
            raise ValueError(
                "ssd_dir given but the SSD tier is disabled (ssd_blocks=0) "
                "— nothing would ever reach the file-backed store; set "
                "ssd_capacity_blocks/CacheTierSpec.ssd_blocks > 0")
        if spec.tiered and spec.ssd_dir is not None:
            from repro.core.cache import BlockMeta
            from repro.serving.ssd_store import AsyncPrefetcher, SSDBlockStore
            self.store = SSDBlockStore(
                spec.ssd_dir, writeback_batch=spec.writeback_batch,
                read_bw=ssd_read_bw, write_bw=ssd_write_bw)
            self.prefetcher = AsyncPrefetcher(self.store)
            self.meta.on_demote = self._on_demote
            self.meta.on_promote = self._on_promote
            self.meta.on_drop = self._on_drop
            # restart recovery: blocks a previous run flushed re-enter the
            # SSD tier's metadata (chain hashes are stable across runs, so
            # matching prefixes become hits again; depth is unknown → 0)
            for key in self.store.keys():
                ssd_evicted, placed = self.meta.ssd.insert_meta(
                    BlockMeta(key=key))
                for e in ssd_evicted:
                    self.store.delete(e)
                if not placed:
                    self.store.delete(key)
        # join the global pool AFTER recovery so bind() seeds recovered
        # blocks too; chaining preserves the byte-holder hooks above
        if directory is not None and hasattr(self.meta, "on_demote"):
            directory.bind(node_id, self.meta)

    # ---- global pool membership ----------------------------------------
    def add_peer(self, node_id, peer) -> None:
        """Make a remote node fetchable. Accepts either a peer transport
        (``InProcPeer``/``SocketPeer`` — anything with ``n_layers`` +
        ``read_layer`` raising the shared taxonomy) or, for backward
        compatibility, a raw ``HostKVPool``, which is wrapped in an
        ``InProcPeer`` so BOTH transports fail identically: a killed
        in-process pool and a kill -9'd remote process each surface as
        ``PeerUnreachable`` → ``fallback_reasons["peer_unreachable"]``."""
        if not hasattr(peer, "read_layer"):
            peer = InProcPeer(peer)
        self.peers[node_id] = peer

    def kill(self) -> None:
        """Failure injection: model this node dying — peers' reads against
        it raise ``PeerUnreachable`` from now on (the same error a dead
        socket raises). Local state is left intact so tests can assert
        nothing was served from a dead node."""
        self.alive = False

    def _note_fallback(self, reason: str) -> None:
        self.fallback_reasons[reason] = self.fallback_reasons.get(reason, 0) + 1

    # ---- tier-event hooks (file-backed mode only) ----------------------
    def _on_demote(self, key: int) -> None:
        kv = self.data.pop(key, None)
        if kv is not None:
            self.store.put(key, *kv)    # staged; flushed per writeback batch

    def _on_promote(self, key: int, count_read: bool) -> None:
        if count_read:
            kv = self._inflight.pop(key, None)
            if kv is None:              # promotion outside a verified fetch
                kv = self.store.read_block(key)
            if kv is not None:
                self.data[key] = kv
            # unreadable bytes leave no DRAM copy; the next verified fetch
            # sees the hole and discards the block's metadata
        self.store.delete(key)

    def _on_drop(self, key: int) -> None:
        self.data.pop(key, None)
        if self.store is not None:
            self.store.delete(key)

    # ---- fetch protocol ------------------------------------------------
    def plan_fetch(self, hash_ids: list[int]) -> FetchPlan:
        """Residency snapshot of the chain's prefix (no side effects).
        Local misses consult the global directory: a block a reachable
        peer claims extends the plan with tier ``"peer"``."""
        rt = getattr(self.meta, "resident_tier", None)
        tiers: list[str] = []
        sources: list = []
        for h in hash_ids:
            t = rt(h) if rt is not None \
                else ("dram" if h in self.meta else None)
            src = None
            if t is None and self.directory is not None and self.peers:
                owner = self.directory.pick_owner(
                    h, exclude=(self.node_id,), among=self.peers)
                if owner is not None:
                    t, src = "peer", owner[0]
            if t is None:
                break
            tiers.append(t)
            sources.append(src)
        return FetchPlan(list(hash_ids), tiers, sources)

    def start_prefetch(self, plan: FetchPlan, from_block: int = 0):
        """Enqueue async layer-wise loads of the plan's SSD and peer
        blocks at index ≥ ``from_block``; returns a PrefetchHandle (or
        None). Peer blocks stream through the same layer-major queue,
        read off the owning node via a ``PeerSource``."""
        if self.prefetcher is None:
            return None
        keys: list[int] = []
        sources: dict = {}
        peer_srcs: dict = {}
        for i in range(from_block, plan.n_resident):
            h, t = plan.hash_ids[i], plan.tiers[i]
            if t == "ssd":
                keys.append(h)
            elif t == "peer":
                node = plan.source(i)
                if node not in peer_srcs:
                    peer_srcs[node] = PeerSource(node, self.peers.get(node))
                keys.append(h)
                sources[h] = peer_srcs[node]
        if not keys:
            return None
        handle = self.prefetcher.fetch(keys, sources)
        handle.sources = sources        # finish_fetch reads failure reasons
        return handle

    def _remote_block(self, src: PeerSource, key: int):
        """Synchronous whole-block peer read (the blocking path)."""
        L = src.n_layers
        if L == 0:
            src.note_empty(key)
            return None
        ks, vs = [], []
        for layer in range(L):
            pair = src.read_layer(key, layer)
            if pair is None:
                return None
            ks.append(pair[0])
            vs.append(pair[1])
        return np.stack(ks), np.stack(vs)

    def _take_peer_block(self, i: int, h: int, kv, node) -> bool:
        """Install a VERIFIED peer block: bytes first, then metadata (the
        hierarchy never claims bytes it can't serve). Returns False when
        the local hierarchy has no room — treated as a fetch failure.
        Mirrors ``put``'s byte accounting: eviction victims free their
        bytes, and a pinned-full-DRAM insert that lands straight in the
        SSD tier writes the bytes through to the store."""
        self.data[h] = (np.asarray(kv[0]), np.asarray(kv[1]))
        evicted = self.meta.insert([h], start_pos=i)
        for e in evicted:
            self.data.pop(e, None)      # file-backed: on_drop already freed
        if h not in self.meta:
            self.data.pop(h, None)
            self._note_fallback("no_local_room")
            return False
        rt = getattr(self.meta, "resident_tier", None) \
            if self.store is not None else None
        if rt is not None and rt(h) == "ssd":
            blk = self.data.pop(h)
            if h not in self.store:     # landed straight in the SSD tier
                self.store.put(h, *blk)
        self.peer_blocks_fetched += 1
        return True

    def finish_fetch(self, plan: FetchPlan, handle=None,
                     from_block: int = 0) -> int:
        """Verify + install bytes for plan blocks [from_block:], promote
        their metadata, and return how many CONSECUTIVE blocks from
        ``from_block`` are usable. A block that fails verification is
        discarded from the hierarchy (peer blocks: the stale directory
        claim is withdrawn) and truncates the usable run — the caller
        recomputes from there, with the reason in ``fallback_reasons``
        (crash safety: stale/torn/remote-dead state degrades to
        recompute, never to wrong KV)."""
        if handle is not None:
            handle.wait()               # §5.2 wait-before-attend barrier
        h_sources = getattr(handle, "sources", None) or {}
        n_ok = 0
        local_seg: list[int] = []
        for i in range(from_block, plan.n_resident):
            h, tier = plan.hash_ids[i], plan.tiers[i]
            if tier == "dram":
                if h in self.data or self.store is None:
                    n_ok += 1
                    local_seg.append(h)
                    continue
                self.meta.discard(h)    # metadata claimed bytes we lost
                break
            if tier == "peer":
                node = plan.source(i)
                src = h_sources.get(h)
                kv = handle.result(h) if handle is not None else None
                if kv is None:
                    if src is None:
                        src = PeerSource(node, self.peers.get(node))
                    kv = self._remote_block(src, h)
                if kv is None:
                    reason = (src.reasons.get(h) if src is not None
                              else None) or "peer_fetch_failed"
                    self._note_fallback(reason)
                    self.peer_fetch_failures += 1
                    if self.directory is not None and reason in (
                            "stale_directory", "verify_failed"):
                        self.directory.unregister(h, node)  # self-heal
                    break
                if not self._take_peer_block(i, h, kv, node):
                    break
                n_ok += 1
                continue
            # tier == "ssd" — the local store path
            kv = handle.result(h) if handle is not None else None
            if kv is None and self.store is not None:
                kv = self.store.read_block(h)
            if kv is None:
                self.meta.discard(h)
                self._note_fallback("local_verify_failed")
                break
            self._inflight[h] = kv
            n_ok += 1
            local_seg.append(h)
        if local_seg:
            self.meta.touch_keys(local_seg)  # promotions consume _inflight
        self._inflight.clear()
        return n_ok

    def match_prefix(self, hash_ids: list[int]) -> int:
        if self.store is None and not self.peers:
            return self.meta.lookup(hash_ids)
        n = self.finish_fetch(self.plan_fetch(hash_ids))
        self.meta.misses += len(hash_ids) - n
        return n

    # ---- bytes ---------------------------------------------------------
    def get(self, hash_ids: list[int]):
        """Stack blocks → (L, n*512, ...) k and v (``cfg.kv_widths``)."""
        ks, vs = [], []
        for h in hash_ids:
            kv = self.data.get(h)
            if kv is None and self.store is not None:
                kv = self.store.read_block(h)
            if kv is None:
                raise KeyError(f"block {h} has no readable bytes")
            ks.append(kv[0])
            vs.append(kv[1])
        return np.concatenate(ks, axis=1), np.concatenate(vs, axis=1)

    def put(self, hash_ids: list[int], k: np.ndarray, v: np.ndarray,
            start_pos: int = 0) -> None:
        """k/v: (L, n*512, ...) covering ``hash_ids`` in order, each with
        its per-token width (``cfg.kv_widths``: (KV, Dh), or a latent
        pair's ``c_kv`` and ``k_pe``)."""
        evicted = self.meta.insert(hash_ids, start_pos=start_pos)
        for e in evicted:
            self.data.pop(e, None)      # file-backed: on_drop already freed
        rt = getattr(self.meta, "resident_tier", None) \
            if self.store is not None else None
        for i, h in enumerate(hash_ids):
            if h not in self.meta or h in self.data:
                continue
            sl = slice(i * BLOCK_TOKENS, (i + 1) * BLOCK_TOKENS)
            blk = (np.ascontiguousarray(k[:, sl]),
                   np.ascontiguousarray(v[:, sl]))
            if rt is not None and rt(h) == "ssd":
                if h not in self.store:  # inserted straight to the SSD tier
                    self.store.put(h, *blk)
            else:
                self.data[h] = blk

    # ---- preemption spill slab (device→host demotion of live runs) -----
    def spill_put(self, req_id: int, k: np.ndarray, v: np.ndarray,
                  n_tokens: int) -> None:
        """Park a preempted slot's exported KV run (from
        ``DevicePagePool.export_run``) until the victim restores. One
        entry per request; overwriting is a bug (the old bytes would be
        silently lost), so it raises."""
        with self._spill_lock:
            if req_id in self._spill:
                raise RuntimeError(
                    f"request {req_id} already has a spilled run — a victim "
                    f"must restore (spill_pop) before it can spill again")
            self._spill[req_id] = (k, v, n_tokens)
            self._spill_counters["spills"] += 1

    def spill_get(self, req_id: int):
        """Peek a spilled run: (k, v, n_tokens). KeyError if absent."""
        with self._spill_lock:
            return self._spill[req_id]

    def spill_pop(self, req_id: int, *, restored: bool = True) -> bool:
        """Drop a spilled run — after a successful restore (counted as
        such) or when the request is abandoned (``restored=False``).
        Returns whether an entry existed."""
        with self._spill_lock:
            had = self._spill.pop(req_id, None) is not None
            if had:
                key = "spill_restores" if restored else "spill_drops"
                self._spill_counters[key] += 1
            return had

    def spill_depth(self) -> int:
        """Number of preempted requests currently parked in the slab —
        the ``BackpressureSignal.spilled`` gauge (each is a restorable
        victim that will want device pages back)."""
        with self._spill_lock:
            return len(self._spill)

    def est_block_read_s(self) -> float:
        """Expected SSD read seconds per block (for the split search)."""
        return self.store.est_block_read_s() if self.store is not None \
            else 0.0

    @property
    def n_blocks(self) -> int:
        return len(self.data) + (len(self.store) if self.store else 0)

    def stats(self) -> dict:
        """Unified snapshot (cross-component ``stats()`` protocol: locked
        where state is shared, plain dict, stable key names): block
        residency, peer-fetch counters, fallback reasons (flattened as
        ``fallback_<reason>``), and the preemption spill slab."""
        out = dict(
            dram_blocks=len(self.data),
            store_blocks=len(self.store) if self.store else 0,
            total_blocks=self.n_blocks,
            peer_blocks_fetched=self.peer_blocks_fetched,
            peer_fetch_failures=self.peer_fetch_failures,
        )
        for reason, n in self.fallback_reasons.items():
            out[f"fallback_{reason}"] = n
        with self._spill_lock:
            out.update(self._spill_counters)
            out["spill_entries"] = len(self._spill)
            out["spill_bytes"] = sum(
                k.nbytes + v.nbytes for k, v, _ in self._spill.values())
        return out

    def close(self) -> None:
        with self._spill_lock:
            self._spill.clear()
        if self.prefetcher is not None:
            self.prefetcher.close()
        if self.store is not None:
            self.store.close()


def connect_pools(pools: list["HostKVPool"]) -> None:
    """Cross-register every pool as a peer of every other (the in-process
    stand-in for Messenger endpoints). Pools must carry distinct
    ``node_id``s and share one ``GlobalBlockDirectory``."""
    for a in pools:
        for b in pools:
            if a is not b:
                a.add_peer(b.node_id, b)


@dataclass
class PrefillResult:
    first_token: int
    # (L, S, KV, Dh) full-depth KV of the request on the host, or None
    # when the prefill staged it into device pages: ``host_kv`` reads
    # either
    kv_k: Optional[np.ndarray]
    kv_v: Optional[np.ndarray]
    prompt_len: int
    reused_blocks: int
    new_blocks: int
    ssd_blocks: int = 0         # prefix blocks loaded off the SSD store
    peer_blocks: int = 0        # prefix blocks fetched off a PEER's pool
    overlapped: bool = False    # head recompute ∥ tail SSD load was used
    skipped_blocks: int = 0     # DRAM blocks chunk-skipped mid-head-span
    hash_ids: Optional[list] = None   # the request's prefix chain
    pages: Optional[list] = None      # staged device page run (paged substrate)
    page_pool: Optional[object] = None  # the DevicePagePool holding ``pages``
    page_gens: Optional[list] = None  # allocation generations at staging time
    _pages_adopted: bool = False      # first join takes the staging reference

    def release_pages(self) -> None:
        """Drop the staging reference of a result that will never be
        joined (e.g. the request was cancelled after prefill). The first
        ``DecodeWorker.join`` normally consumes it; calling this after a
        join is a no-op."""
        if self.pages is not None and self.page_pool is not None \
                and not self._pages_adopted:
            self.page_pool.release(self.pages)
            self._pages_adopted = True

    def host_kv(self) -> tuple[np.ndarray, np.ndarray]:
        """The request's (L, S, KV, Dh) KV on the host: the bytes a
        prefill that could not stage read back, else a read of its staged
        page run, which must still hold what was staged."""
        if self.kv_k is not None:
            return self.kv_k, self.kv_v
        pp = self.page_pool
        if pp is None or self.pages is None or \
                pp.gens_of(self.pages) != self.page_gens:
            raise RuntimeError(
                "this PrefillResult's KV lived only in its staged page run, "
                "which was released and re-used; re-prefill instead")
        return pp.read_seq(self.pages, self.prompt_len)


def paged_supported(cfg: ModelConfig) -> bool:
    """The paged decode substrate covers uniform attention-only stacks;
    hybrid/SSM/encoder archs keep the dense arena. Drivers use this to
    decide whether to build a ``DevicePagePool`` at all (staging into a
    pool no decode worker will ever adopt from just leaks pages)."""
    return cfg.attention_layers == cfg.n_layers and not cfg.encoder_layers


@partial(jax.jit, static_argnums=2)
def _device_tokens(x, lo, n: int):
    """Tokens [lo, lo + n) of device KV, (L, S, ...) or a B=1 dense cache
    (L, 1, S, ...), told apart by its unit batch axis (a one-token run in
    the first layout reads as the second), as lane-dense rows (L, n,
    width): each token's ``cfg.kv_widths`` flattened, as a page slab
    holds it, so the copy that takes the slice also lays it out and the
    page write moves rows as they are. ``lo`` is traced: one program per
    (S, n) serves a block wherever it lies in the prompt."""
    if x.shape[1] == 1:
        x = x.reshape(x.shape[:1] + x.shape[2:])
    x = jax.lax.dynamic_slice_in_dim(x, lo, n, axis=1)
    return x.reshape(x.shape[:2] + (-1,))


def _kv_tokens(k, v, lo: int, hi: int):
    """Tokens [lo, hi) of KV on the host (numpy slices) or on the device
    (``_device_tokens``)."""
    if isinstance(k, jax.Array):
        return _device_tokens(k, lo, hi - lo), _device_tokens(v, lo, hi - lo)
    return k[:, lo:hi], v[:, lo:hi]


def stage_run(pool, hash_ids: list[int], k_full, v_full, S: int,
              bank: Optional[int] = None) -> Optional[list[int]]:
    """Stage a request's KV into a ``DevicePagePool`` page run (§3 step 2:
    fresh pages written layer-stacked; step 1: registered prefix runs
    ADOPTED — the physical pages are shared with every slot on the same
    hash chain, no bytes move). Full 512-token blocks register under
    their chain hash for later requests; the partial tail gets private
    pages. ``k_full``/``v_full`` hold the KV on the host (L, S, KV, Dh),
    or on the device: a prefill's dense cache, whose pages are then
    written device to device. On a banked (mesh-sharded) pool the whole
    run lives in ONE data-shard bank — ``bank=None`` picks the bank with
    the deepest registered prefix. The caller owns one reference per
    returned page. Returns None (nothing held) if the pool can't fit the
    run even after evicting registry-only runs."""
    if pool is None:
        return None
    if bank is None:
        bank = pool.best_stage_bank(hash_ids)
    B = BLOCK_TOKENS
    n_full = len(hash_ids)
    held: list[int] = []
    try:
        adopted, pages = pool.adopt_chain(hash_ids, bank=bank)
        held = list(pages)
        for i in range(adopted, n_full):
            run = pool.alloc(pool.pages_per_block, bank=bank)
            held += run
            pool.write_run(run, *_kv_tokens(k_full, v_full, i * B,
                                            (i + 1) * B))
            pool.register_block(hash_ids[i], run)
            pages += run
        tail = S - n_full * B
        if tail > 0:
            run = pool.alloc(pool.pages_for(tail), bank=bank)
            held += run
            pool.write_run(run, *_kv_tokens(k_full, v_full, n_full * B, S))
            pages += run
        return pages
    except MemoryError:
        pool.release(held)
        return None
    except BaseException:
        # a non-capacity failure (bad shapes, CRC mismatch upstream, an
        # interrupt) must not strand the partially-written run: release
        # everything held so far and let the error propagate
        pool.release(held)
        raise


@dataclass
class RestorePlan:
    """Priced decision for bringing a preempted victim back onto the
    device: reload its spilled bytes through ``stage_run`` vs recompute
    the whole sequence through chunked prefill."""
    mode: str                   # "reload" | "recompute"
    est_reload_s: float
    est_recompute_s: float


def plan_restore(n_tokens: int, *, reload_s_per_block: Optional[float],
                 recompute_s_per_block: Optional[float],
                 mode: str = "auto") -> RestorePlan:
    """Price the two restore arms for a spilled run of ``n_tokens`` (the
    'Why Not Both?' discipline applied to preemption recovery: transfer
    and compute are independent resources, pick the cheaper wall-clock).
    Per-block estimates are measured EMAs — ``None`` means unwarmed, and
    an unwarmed arm loses the comparison (reload wins overall ties: the
    bytes already exist and recompute would re-burn prefill FLOPs)."""
    if mode not in ("auto", "reload", "recompute"):
        raise ValueError(f"unknown restore mode {mode!r}")
    n_blocks = -(-n_tokens // BLOCK_TOKENS)
    tl = (reload_s_per_block or 0.0) * n_blocks
    tc = (recompute_s_per_block or 0.0) * n_blocks
    if mode != "auto":
        chosen = mode
    elif recompute_s_per_block is None:
        chosen = "reload"
    elif reload_s_per_block is None:
        chosen = "recompute"
    else:
        chosen = "reload" if tl <= tc else "recompute"
    return RestorePlan(mode=chosen, est_reload_s=tl, est_recompute_s=tc)


class ChunkedPrefill:
    """A prefill suspended between device chunks — the serving loop's
    interleave unit.

    ``advance()`` runs exactly one device chunk (the first call also does
    the hashing / fetch planning / pool reads that precede it) and
    returns True once the request is complete, with the finished
    ``PrefillResult`` in ``.result``. Draining the generator in one go is
    bit-exact with the old blocking path — it IS the blocking path, which
    is why ``PrefillWorker.__call__`` is now implemented on top of this.
    """

    def __init__(self, worker: "PrefillWorker", tokens: np.ndarray,
                 session=None) -> None:
        self.worker = worker
        self.tokens = np.asarray(tokens)
        self.prompt_len = len(self.tokens)
        self.chunks_done = 0
        self.result: Optional[PrefillResult] = None
        # arguments every profiler span of this prefill carries (the
        # serving loop sets ``req``)
        self.info: dict = {}
        self._gen = worker._chunks(self.tokens, session, self.info)

    @property
    def done(self) -> bool:
        return self.result is not None

    def advance(self) -> bool:
        """Run one device chunk; True once the prefill finished."""
        if self.result is not None:
            return True
        with span("prefill.chunk", **self.info):
            try:
                next(self._gen)
                self.chunks_done += 1
                return False
            except StopIteration as e:
                self.chunks_done += 1
                self.result = e.value
                return True

    def drain(self) -> PrefillResult:
        while not self.advance():
            pass
        return self.result


class PrefillWorker:
    """§3 steps 1–3: KVCache reuse → incremental (chunked) prefill →
    layer-wise store-back. One request at a time (B = 1).

    With a file-backed pool, ``ssd_mode`` picks how SSD-resident prefix
    blocks reach the accelerator: ``"blocking"`` loads them synchronously
    before any compute (the naive schedule); ``"overlap"`` — the
    executable ``why_not_both`` — splits the prefix per
    ``layerwise.overlap_split``, RECOMPUTING the head chunks while the
    tail streams from SSD layer-by-layer, and only then computes the
    uncached suffix. Verification failures shrink the loaded tail and the
    lost blocks are recomputed — wrong tokens are impossible.

    Every path is CHUNK-RESUMABLE: ``start()`` returns a
    ``ChunkedPrefill`` whose ``advance()`` runs one device chunk, so a
    serving loop can interleave prefill chunks between decode iterations
    (``__call__`` just drains it — the request-at-a-time oracle). Cold
    prefill runs as the same chunked incremental-extend loop from an
    empty cache, which is bit-identical to a monolithic prefill call.
    """

    def __init__(self, params, cfg: ModelConfig, pool: HostKVPool, *,
                 prefill_chunk: int = 1024, ssd_mode: str = "overlap",
                 page_pool=None) -> None:
        assert ssd_mode in ("blocking", "overlap"), ssd_mode
        self.params = params
        self.cfg = cfg
        self.pool = pool
        self.chunk = prefill_chunk
        self.ssd_mode = ssd_mode
        self.page_pool = page_pool      # shared DevicePagePool (paged handoff)
        self.hasher = PrefixHasher()
        # a latent-attention expert model also counts, on the device, the
        # token-expert pairs each chunk routes to the experts held here
        share = cfg.mla is not None
        self._extend = jax.jit(
            lambda p, t, c: decode_step(p, t, c, cfg, held_pairs=share))
        self.counters = dict(reused_blocks=0, computed_tokens=0, requests=0,
                             ssd_loaded_blocks=0, overlapped_requests=0,
                             fallback_blocks=0, peer_blocks=0,
                             skipped_blocks=0, chunks=0, h2d_bytes=0,
                             d2h_bytes=0, put_bytes=0, stage_bytes=0,
                             device_stages=0, host_stages=0)
        self._pairs_held = None
        if share:
            self.counters["moe_pairs"] = 0
            self._pairs_held = jnp.zeros((), jnp.int32)
        # serving-loop hook: called with the page count a stage would pin;
        # returning False skips staging (the join stages later) so staged
        # results can't eat the decode batch's reserved growth pages
        self.stage_guard = None
        self._t_block_ema: Optional[float] = None  # measured s / 512-tok blk

    def _note_compute(self, tokens: int, dt: float) -> None:
        if tokens <= 0 or dt <= 0:
            return
        per_block = dt * BLOCK_TOKENS / tokens
        self._t_block_ema = per_block if self._t_block_ema is None \
            else 0.7 * self._t_block_ema + 0.3 * per_block

    def est_chunk_s(self) -> float:
        """Measured seconds per device chunk (0.0 until warmed up) — the
        serving loop budgets interleaved chunks against the TBT slack
        with this."""
        if self._t_block_ema is None:
            return 0.0
        return self._t_block_ema * self.chunk / BLOCK_TOKENS

    def _chunk_extend(self, t, caches, lo: int, hi: int, info: dict):
        """One timed device chunk (the resumable unit)."""
        with span("prefill.compute", lo=lo, hi=hi, **info):
            t0 = time.monotonic()
            logits, caches, *held = self._extend(self.params, t[:, lo:hi],
                                                 caches)
            if held:
                moe = self.cfg.moe
                self._pairs_held = self._pairs_held + held[0]
                self.counters["moe_pairs"] += (hi - lo) * moe.top_k * (
                    self.cfg.n_layers - moe.first_dense)
            jax.block_until_ready(logits)
            self._note_compute(hi - lo, time.monotonic() - t0)
        self.counters["chunks"] += 1
        return logits, caches

    def _upload(self, kv: KVCache, lo: int, hi: int, hash_ids) -> KVCache:
        """Host-pool blocks ``hash_ids`` into the dense cache's tokens
        [lo, hi)."""
        k_np, v_np = self.pool.get(hash_ids)
        self.counters["h2d_bytes"] += k_np.nbytes + v_np.nbytes
        return KVCache(k=kv.k.at[:, 0, lo:hi].set(jnp.asarray(k_np)),
                       v=kv.v.at[:, 0, lo:hi].set(jnp.asarray(v_np)))

    def _read(self, kv: KVCache, spans) -> list:
        """Host copies of the dense cache's tokens [lo, hi) for each span
        (``lo`` on a block boundary), read in pieces of at most a block:
        one program per prompt length and piece size, the shapes staging
        uses, wherever a piece lies; every transfer in flight at once."""
        B = BLOCK_TOKENS
        cuts = [[(a, min(a + B, hi)) for a in range(lo, hi, B)]
                for lo, hi in spans]
        host = iter(jax.device_get([
            (_device_tokens(kv.k, a, b - a), _device_tokens(kv.v, a, b - a))
            for cut in cuts for a, b in cut]))
        wk, wv = self.cfg.kv_widths
        out = []
        for cut in cuts:
            ks, vs = zip(*(next(host) for _ in cut))
            k, v = np.concatenate(ks, axis=1), np.concatenate(vs, axis=1)
            out.append((k.reshape(k.shape[:2] + wk),
                        v.reshape(v.shape[:2] + wv)))
        return out

    def _put(self, hash_ids, k, v, i: int, j: int) -> None:
        """Blocks [i, j), held in ``k``/``v``, into the host pool."""
        self.pool.put(hash_ids[i:j], k, v, start_pos=i)
        self.counters["put_bytes"] += k.nbytes + v.nbytes

    def _stage(self, hash_ids, kv: KVCache, S) -> Optional[list[int]]:
        pp = self.page_pool
        if pp is None or (self.stage_guard is not None
                          and not self.stage_guard(pp.pages_for(S))):
            return None
        written = pp.pages_written
        pages = stage_run(pp, hash_ids, kv.k, kv.v, S)
        if pages is not None:
            self.counters["stage_bytes"] += \
                (pp.pages_written - written) * pp.page_bytes
        return pages

    def stats(self) -> dict:
        """Unified snapshot (cross-component ``stats()`` protocol):
        lifetime counters, the KV bytes of each tier transfer among them
        (``h2d_bytes`` host-pool hits uploaded, ``d2h_bytes`` read back
        from the dense cache, ``put_bytes`` stored into the host pool,
        ``stage_bytes`` written into device pages), and how each finished
        prefill handed its KV on (``device_stages`` into pages from the
        device, ``host_stages`` read back whole for a later join). An
        expert model's share adds ``moe_pairs``, the token-expert pairs
        its chunks routed, and ``moe_pairs_held``, those that fell to the
        experts held here (read from the device now)."""
        out = dict(self.counters)
        if self._pairs_held is not None:
            out["moe_pairs_held"] = int(self._pairs_held)
        return out

    def _finish(self, logits, kv: KVCache, hash_ids, runs, S: int,
                info: dict) -> dict:
        """The end of a prefill, on both paths: read back the first token
        and only the fresh full blocks ``runs`` ([(i, j)] block ranges)
        that the host pool stores, stage the pages straight from the dense
        device cache, and store the blocks. When the device cannot take
        the run (no page pool, the stage guard, page OOM), the whole cache
        comes back to the host and the join stages it later. Returns the
        ``PrefillResult`` fields it settles (with a generation snapshot of
        the staged run, so that late re-joins can detect recycled
        pages)."""
        B = BLOCK_TOKENS
        with span("prefill.readback", **info):
            first = int(jnp.argmax(logits[0, -1]))
            blocks = self._read(kv, [(i * B, j * B) for i, j in runs])
        with span("prefill.stage", **info):
            pages = self._stage(hash_ids, kv, S)
        k_full = v_full = None
        if pages is None:
            with span("prefill.readback", **info):
                k_full, v_full = self._read(kv, [(0, S)])[0]
            self.counters["d2h_bytes"] += k_full.nbytes + v_full.nbytes
        self.counters["d2h_bytes"] += sum(k.nbytes + v.nbytes
                                          for k, v in blocks)
        if runs:
            with span("prefill.store", **info):
                for (i, j), (k, v) in zip(runs, blocks):
                    self._put(hash_ids, k, v, i, j)
        self.counters["host_stages" if pages is None
                      else "device_stages"] += 1
        return dict(
            first_token=first, kv_k=k_full, kv_v=v_full, prompt_len=S,
            hash_ids=hash_ids, pages=pages, page_pool=self.page_pool,
            page_gens=None if pages is None
            else self.page_pool.gens_of(pages))

    def start(self, tokens: np.ndarray, session=None) -> ChunkedPrefill:
        """Begin a chunk-resumable prefill (nothing runs until the first
        ``advance()``)."""
        return ChunkedPrefill(self, tokens, session=session)

    def __call__(self, tokens: np.ndarray,
                 session=None) -> PrefillResult:
        return self.start(tokens, session=session).drain()

    def _chunks(self, tokens: np.ndarray, session=None, info=None):
        """Generator behind ``ChunkedPrefill``: yields between device
        chunks; its StopIteration value is the ``PrefillResult``.
        ``info``: arguments of every profiler span it opens."""
        cfg = self.cfg
        assert cfg.attention_layers == cfg.n_layers, \
            "PrefillWorker KV path supports uniform attention stacks"
        info = {} if info is None else info
        S = len(tokens)
        with span("prefill.lookup", **info):
            hash_ids = self.hasher.hash_ids(tokens, session=session)
            plan = None
            if self.ssd_mode == "overlap" and \
                    self.pool.prefetcher is not None:
                plan = self.pool.plan_fetch(hash_ids)
                n_res = plan.n_resident
                if n_res * BLOCK_TOKENS >= S:    # full hit: keep a tail to
                    n_res = max((S - 1) // BLOCK_TOKENS, 0)  # recompute
                plan = plan.truncate(n_res)      # the logits
                if not (plan.has_ssd or plan.has_remote):
                    plan = None
            if plan is None:
                # blocking path: flat pool, legacy tiered pool, or
                # synchronous file-backed/peer loads (ssd_mode="blocking")
                peer0 = self.pool.peer_blocks_fetched
                n_hit = self.pool.match_prefix(hash_ids)
        if plan is not None:
            result = yield from self._chunks_overlapped(
                tokens, hash_ids, plan, info)
            return result

        prefix_tokens = n_hit * BLOCK_TOKENS
        if prefix_tokens >= S:           # full hit: recompute last block's
            n_hit = max((S - 1) // BLOCK_TOKENS, 0)  # tail to get logits
            prefix_tokens = n_hit * BLOCK_TOKENS

        with span("prefill.load", blocks=n_hit, **info):
            t = jnp.asarray(tokens[None, :], jnp.int32)
            caches = init_caches(cfg, 1, S)
            caches = caches._replace(length=jnp.asarray(0, jnp.int32))
            if n_hit:
                kv = self._upload(caches.kv, 0, prefix_tokens,
                                  hash_ids[:n_hit])
                caches = caches._replace(
                    kv=kv, length=jnp.asarray(prefix_tokens, jnp.int32))
        # chunked incremental prefill over the uncached suffix (a cold
        # request is just the n_hit=0 case: extending an empty cache chunk
        # by chunk is bit-identical to a monolithic prefill)
        logits = None
        for lo in range(prefix_tokens, S, self.chunk):
            hi = min(lo + self.chunk, S)
            logits, caches = self._chunk_extend(t, caches, lo, hi, info)
            if hi < S:
                yield               # suspension point for the serving loop
        # store-back of every fresh full block (§5.2: on TPU the per-layer
        # store launches as soon as that layer's KV exists; here the
        # ordering contract is preserved by storing from the scanned
        # per-layer stack)
        n_total = len(hash_ids)
        runs = [(n_hit, n_total)] if n_total > n_hit else []
        done = self._finish(logits, caches.kv, hash_ids, runs, S, info)
        n_peer = self.pool.peer_blocks_fetched - peer0
        self.counters["reused_blocks"] += n_hit
        self.counters["computed_tokens"] += S - prefix_tokens
        self.counters["requests"] += 1
        self.counters["peer_blocks"] += n_peer
        return PrefillResult(reused_blocks=n_hit, new_blocks=n_total - n_hit,
                             peer_blocks=n_peer, **done)

    def _chunks_overlapped(self, tokens: np.ndarray, hash_ids: list[int],
                           plan: FetchPlan, info: dict):
        """Head recompute ∥ tail SSD load (§5.2 / Jin et al., executable),
        as a chunk-resumable generator.

        Timeline: pick split s via ``overlap_split``; blocks [0, d0) come
        from DRAM free; launch async layer-wise loads of blocks [s, n);
        recompute chunks over [d0·B, s·B) while they stream; barrier; set
        the loaded tail into the cache arena; compute the uncached suffix.
        """
        from repro.serving.layerwise import overlap_split
        B = BLOCK_TOKENS
        cfg = self.cfg
        S = len(tokens)
        n = plan.n_resident
        peer0 = self.pool.peer_blocks_fetched
        with span("prefill.lookup", **info):
            tl = self.pool.est_block_read_s()
            tc = self._t_block_ema if self._t_block_ema is not None else tl
            # peer blocks are loads for the split search (the local read
            # EMA is the available per-block load estimate; the network
            # hop of an in-process peer is free, so it errs mildly toward
            # recompute)
            ov = overlap_split(["dram" if t == "dram" else "ssd"
                                for t in plan.tiers], tc, tl)
            s, d0 = ov.split, ov.dram_head
            handle = self.pool.start_prefetch(plan, from_block=s)
            if d0:
                self.pool.meta.touch_keys(hash_ids[:d0])

        with span("prefill.load", blocks=d0, **info):
            t = jnp.asarray(tokens[None, :], jnp.int32)
            caches = init_caches(cfg, 1, S)
            caches = caches._replace(length=jnp.asarray(0, jnp.int32))
            if d0:
                kv = self._upload(caches.kv, 0, d0 * B, hash_ids[:d0])
                caches = caches._replace(
                    kv=kv, length=jnp.asarray(d0 * B, jnp.int32))

        # head assembly, overlapping the prefetch thread's layer loads:
        # DRAM blocks interleaved inside [d0, s) are chunk-SKIPPED — their
        # KV is set straight into the arena from the pool — and only the
        # non-resident runs between them recompute (incremental prefill
        # resumes after each assembled run, so attention still sees every
        # prior token). Every recompute chunk is a suspension point; the
        # suffix below is guaranteed non-empty, so yielding after each
        # head chunk never strands the result.
        i = d0
        while i < s:
            if plan.tiers[i] == "dram":
                j = i
                while j < s and plan.tiers[j] == "dram":
                    j += 1
                with span("prefill.load", blocks=j - i, **info):
                    kv = self._upload(caches.kv, i * B, j * B,
                                      hash_ids[i:j])
                    self.pool.meta.touch_keys(hash_ids[i:j])
                    caches = caches._replace(
                        kv=kv, length=jnp.asarray(j * B, jnp.int32))
            else:
                j = i
                while j < s and plan.tiers[j] != "dram":
                    j += 1
                for lo in range(i * B, j * B, self.chunk):
                    hi = min(lo + self.chunk, j * B)
                    _, caches = self._chunk_extend(t, caches, lo, hi, info)
                    yield
            i = j
        n_skip = ov.head_skipped

        # §5.2 barrier: verify + install the loaded tail
        with span("prefill.load", blocks=n - s, **info):
            n_tail = self.pool.finish_fetch(plan, handle, from_block=s)
            usable = s + n_tail
            if n_tail:
                kv = self._upload(caches.kv, s * B, usable * B,
                                  hash_ids[s:usable])
                caches = caches._replace(
                    kv=kv, length=jnp.asarray(usable * B, jnp.int32))

        # uncached suffix (+ any blocks lost to verification failures).
        # Always non-empty — the caller truncates full-hit plans so that
        # n_resident·B < S — which guarantees the logits below come from
        # position S-1 even when the head walk ended in a DRAM assembly.
        assert usable * B < S, (usable, S)
        logits = None
        for lo in range(usable * B, S, self.chunk):
            hi = min(lo + self.chunk, S)
            logits, caches = self._chunk_extend(t, caches, lo, hi, info)
            if hi < S:
                yield
        # store-back: the RECOMPUTED head runs (chunk-skipped DRAM blocks
        # are already pool-resident) and the fresh suffix blocks
        n_total = len(hash_ids)
        runs = []
        i = d0
        while i < s:
            if plan.tiers[i] == "dram":
                i += 1
                continue
            j = i
            while j < s and plan.tiers[j] != "dram":
                j += 1
            runs.append((i, j))
            i = j
        if n_total > usable:
            runs.append((usable, n_total))
        done = self._finish(logits, caches.kv, hash_ids, runs, S, info)

        reused = d0 + n_skip + n_tail
        n_peer = self.pool.peer_blocks_fetched - peer0
        self.counters["reused_blocks"] += reused
        self.counters["computed_tokens"] += S - reused * B
        self.counters["requests"] += 1
        self.counters["ssd_loaded_blocks"] += n_tail
        self.counters["overlapped_requests"] += 1
        self.counters["fallback_blocks"] += n - usable
        self.counters["peer_blocks"] += n_peer
        self.counters["skipped_blocks"] += n_skip
        return PrefillResult(reused_blocks=reused,
                             new_blocks=len(hash_ids) - reused,
                             ssd_blocks=n_tail, peer_blocks=n_peer,
                             overlapped=True, skipped_blocks=n_skip, **done)


@dataclass
class _Slot:
    """One occupied decode-batch slot. ``prompt_len`` is the KV depth the
    slot JOINED at (after a preemption restore that includes previously
    decoded tokens); ``final_len`` is the depth it will have grown to at
    completion — the growth-reservation bound, invariant across
    preempt/restore cycles."""
    request: ServingRequest
    prompt_len: int
    final_len: int
    emitted: list = field(default_factory=list)

    @property
    def req_id(self) -> int:
        return self.request.req_id

    @property
    def max_new(self) -> int:
        return self.request.max_new

    @property
    def done(self) -> bool:
        return len(self.emitted) >= self.request.max_new


@dataclass
class PreemptedRun:
    """A victim slot's full decode state after ``DecodeWorker.preempt``:
    the exported KV bytes (ownership transferred out of the device pool)
    plus everything ``join(..., resume_emitted=...)`` needs to resume the
    stream bit-exactly. ``n_tokens`` = prompt + all-but-the-last emitted
    token (the pending input's KV was never written)."""
    request: ServingRequest
    emitted: list
    n_tokens: int
    k: np.ndarray               # (L, n_tokens, KV, Dh) host copies
    v: np.ndarray


def _pow2_ceil(n: int) -> int:
    w = 1
    while w < n:
        w *= 2
    return w


def plan_width_buckets(needs: list[int], max_pages: int,
                       max_buckets: int = 1) -> list[int]:
    """Block-table widths (descending) for one decode step over slots
    needing ``needs`` pages each. Every width is a power of two capped at
    ``max_pages`` (so the jitted step sees at most log2(max_pages) table
    shapes per bucket count); with ``max_buckets=1`` the single width is
    exactly the historical global padding (deepest slot, pow2). More
    buckets keep the top distinct widths and merge shallower slots into
    the smallest kept — a shallow slot in a deep batch then attends a
    short table instead of padding to the deepest slot's width."""
    widths = sorted({min(_pow2_ceil(n), max_pages) for n in needs},
                    reverse=True)
    return widths[:max(max_buckets, 1)] or [1]


def bucket_width(need: int, plan: list[int], max_pages: int) -> int:
    """Smallest plan width covering ``need`` pages (plan from
    ``plan_width_buckets``; its head always covers the deepest slot)."""
    n2 = min(_pow2_ceil(need), max_pages)
    for w in reversed(plan):
        if w >= n2:
            return w
    return plan[0]


class DecodeWorker:
    """§3 step 4: continuous batching with per-slot cache depths.

    Two substrates share the slot/iteration machinery:

    * ``substrate="paged"`` (default): slots attend a block-table paged
      KV store (``DevicePagePool`` — shared with the prefill worker(s),
      the process stand-in for a node's HBM). ``join()`` ADOPTS the
      request's staged page run into the slot's block table — a host-side
      list splice, no full-depth device copy — and slots whose chains
      share a prefix share physical pages (refcounted; copy-on-write if a
      slot must append into a shared partial tail page). ``step()`` runs
      ``paged_decode_attention`` per layer over the live page span (the
      table is sliced to the deepest active slot, padded to a power of
      two to bound recompiles) instead of dense attention over
      ``max_len``.
    * ``substrate="dense"``: the original (L, B, max_len) arena — kept as
      the bit-exactness oracle and for archs the paged path doesn't cover
      (hybrid/SSM/encoder stacks).
    """

    def __init__(self, params, cfg: ModelConfig, *, max_batch: int,
                 max_len: int, substrate: str = "paged",
                 page_pool=None, page_tokens: int = 64,
                 use_pallas: bool = False, mesh=None,
                 width_buckets: int = 1) -> None:
        if substrate == "paged" and not paged_supported(cfg):
            substrate = "dense"     # non-uniform stacks keep the arena
        assert substrate in ("paged", "dense"), substrate
        self.params = params
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.substrate = substrate
        self.width_buckets = max(int(width_buckets), 1)
        self.slots: list[Optional[_Slot]] = [None] * max_batch
        self.tokens = jnp.zeros((max_batch, 1), jnp.int32)
        self.counters = dict(zero_copy_joins=0, staged_joins=0, steps=0,
                             preemptions=0, resumed_joins=0,
                             bucket_substeps=0)
        # a latent-attention expert model's paged step also returns how
        # many held experts its tokens touched: summed on the device
        self._touched = None
        if substrate == "paged" and cfg.mla is not None:
            self.counters["moe_held"] = 0
            self._touched = jnp.zeros((), jnp.int32)
        if substrate == "paged":
            from repro.serving.paged_cache import DevicePagePool
            if page_pool is not None:
                if mesh is not None and mesh is not page_pool.mesh:
                    raise ValueError(
                        "mesh= disagrees with page_pool.mesh — the pool's "
                        "banking fixes the decode mesh; pass one of them")
                mesh = page_pool.mesh
            d = 1 if mesh is None else int(mesh.shape.get("data", 1))
            if max_batch % d:
                raise ValueError(
                    f"max_batch={max_batch} must divide over the mesh's "
                    f"data axis ({d}) — slots partition into per-shard "
                    f"row groups")
            if mesh is not None:
                m = int(mesh.shape.get("model", 1))
                reason = paged_shard_reason(cfg, m, d)
                if reason:
                    raise ValueError(
                        f"cannot shard paged decode over {d}x{m}: {reason}")
            if page_pool is None:
                # standalone sizing (per bank): every slot of the bank at
                # full depth + one extra sequence of staging headroom
                # (registry runs are evictable on top, so this bound
                # holds under sharing too). Never sized to fill HBM.
                per_seq = (max_len + page_tokens - 1) // page_tokens
                page_pool = DevicePagePool(
                    cfg, n_pages=1 + (max_batch // d + 1) * per_seq,
                    page_tokens=page_tokens, mesh=mesh)
            self.page_pool = page_pool
            self.mesh = mesh
            self.slots_per_bank = max_batch // page_pool.n_banks
            if self.width_buckets > 1 and mesh is not None:
                raise ValueError(
                    "width_buckets>1 sub-batches the step, which breaks "
                    "the mesh's even data-axis row split — pick one")
            pt = page_pool.page_tokens
            self.max_pages = (max_len + pt - 1) // pt
            self.block_table = np.zeros((max_batch, self.max_pages), np.int32)
            self.seq_lens = np.zeros(max_batch, np.int32)
            self.n_pages_slot = np.zeros(max_batch, np.int32)
            self.caches = None
            # the step writes its rows into the donated slabs in place
            if mesh is None:
                self._step_paged = jax.jit(
                    lambda p, t, kp, vp, tbl, lens: decode_step_paged(
                        p, t, kp, vp, tbl, lens, cfg, use_pallas=use_pallas),
                    donate_argnums=(2, 3))
            else:
                self._step_paged = jax.jit(
                    lambda p, t, kp, vp, tbl, lens:
                    decode_step_paged_sharded(
                        p, t, kp, vp, tbl, lens, cfg, mesh,
                        use_pallas=use_pallas),
                    donate_argnums=(2, 3))
        else:
            self.page_pool = None
            self.mesh = None
            self.caches = init_caches(cfg, max_batch, max_len)
            self.caches = self.caches._replace(
                length=jnp.zeros((max_batch,), jnp.int32))
            self._step = jax.jit(lambda p, t, c: decode_step(p, t, c, cfg))

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def free_slots(self) -> int:
        return sum(s is None for s in self.slots)

    @property
    def has_free_slot(self) -> bool:
        return any(s is None for s in self.slots)

    def reserved_growth_pages(self) -> int:
        """Worst-case device pages the active slots may still allocate:
        growth to ``prompt_len + max_new`` plus one copy-on-write of a
        shared tail page each. Admission must keep this many pages
        obtainable (free + evictable) or a mid-decode ``alloc`` can OOM
        a step — pages pinned by not-yet-joined prefills don't release
        themselves."""
        if self.substrate != "paged":
            return 0
        pt = self.page_pool.page_tokens
        need = 0
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            held = int(self.n_pages_slot[i])
            need += max(-(-s.final_len // pt) - held, 0) + 1
        return need

    # ---- paged-substrate plumbing --------------------------------------
    def _slot_bank(self, slot: int) -> int:
        """Data-shard bank of a batch slot: slots partition into
        contiguous per-bank row groups so the mesh step's ``P('data')``
        row split lands each group on the shard holding its pages."""
        return slot // self.slots_per_bank

    def _pick_slot(self, pref_bank: Optional[int]) -> int:
        """Free slot for a join: the staged run's own bank when it has
        room (zero-copy adoption needs slot bank == page bank), else the
        bank with the most free slots (load-balances the data shards).
        Single-bank pools degrade to ``slots.index(None)``."""
        free_by_bank: dict[int, int] = {}
        for i, s in enumerate(self.slots):
            if s is None:
                free_by_bank.setdefault(self._slot_bank(i), i)
        if pref_bank is not None and pref_bank in free_by_bank:
            return free_by_bank[pref_bank]
        counts = {b: sum(1 for i, s in enumerate(self.slots)
                         if s is None and self._slot_bank(i) == b)
                  for b in free_by_bank}
        bank = max(counts, key=lambda b: (counts[b], -b))
        return free_by_bank[bank]

    def _adopt_pages(self, pres: PrefillResult, bank: int = 0) -> list[int]:
        """Take a reference on the request's page run: zero-copy when the
        prefill staged into OUR pool's target bank (first join consumes
        the staging reference; later joins of the same result share the
        run — n-best/beam fan-out), else stage a copy from the dense KV.
        A run staged into a DIFFERENT bank re-stages into ``bank`` (the
        slot's data shard can only attend its own bank) and this join
        consumes the staging reference — the copy is the handoff."""
        pp = self.page_pool
        same_bank = (pres.pages is not None and pres.page_pool is pp
                     and (not pres.pages
                          or pp.bank_of(pres.pages[0]) == bank))
        if same_bank:
            pages = list(pres.pages)
            if pres._pages_adopted:
                # late share (n-best): the staging reference is gone, so the
                # run is only alive through earlier joiners — verify no page
                # was freed + recycled in between (never retain someone
                # else's KV)
                if pp.gens_of(pages) != pres.page_gens:
                    raise RuntimeError(
                        "stale page run: this PrefillResult's pages were "
                        "released (its joined slots finished) and re-used; "
                        "re-prefill instead of re-joining")
                pp.retain(pages)
            else:
                pres._pages_adopted = True
            self.counters["zero_copy_joins"] += 1
            return pages
        hash_ids = pres.hash_ids if pres.hash_ids is not None else []
        pages = stage_run(pp, hash_ids, *pres.host_kv(), pres.prompt_len,
                          bank=bank)
        if pages is None:
            raise MemoryError("device page pool cannot hold the request")
        if pres.page_pool is pp:
            pres.release_pages()    # cross-bank copy consumes the staging ref
        self.counters["staged_joins"] += 1
        return pages

    def _free_slot_pages(self, slot: int) -> None:
        n = int(self.n_pages_slot[slot])
        self.page_pool.release([int(p) for p in self.block_table[slot, :n]])
        self.block_table[slot] = 0
        self.seq_lens[slot] = 0
        self.n_pages_slot[slot] = 0

    def join(self, request, pres: PrefillResult = None,
             max_new: Optional[int] = None, *,
             resume_emitted: Optional[list] = None) -> int:
        """Add a prefilled request to the continuous batch (§3: 'load the
        KVCache and add the request to the continuous batching process').
        Paged substrate: adoption of the staged page run — no dense
        full-depth copy.

        ``request`` is a ``ServingRequest`` (the legacy positional
        ``join(req_id, pres, max_new)`` still works behind a
        ``DeprecationWarning``). ``resume_emitted`` re-joins a preempted
        victim: ``pres`` then wraps the restored KV run (depth =
        ``PreemptedRun.n_tokens``), the stream continues from
        ``resume_emitted[-1]``, and the slot's completion bound stays
        exactly what it was before preemption."""
        if not isinstance(request, ServingRequest):
            warnings.warn(
                "DecodeWorker.join(req_id, pres, max_new) is deprecated; "
                "pass a ServingRequest", DeprecationWarning, stacklevel=2)
            request = ServingRequest(req_id=int(request), tokens=None,
                                     max_new=int(max_new))
        elif max_new is not None and max_new != request.max_new:
            raise ValueError(
                f"max_new={max_new} conflicts with request.max_new="
                f"{request.max_new}; drop the argument")
        if not self.has_free_slot:
            # NOT StopIteration (a bare next() here): inside a driver
            # generator that would be swallowed as silent termination
            raise RuntimeError(
                f"decode batch full: all {self.max_batch} slots occupied — "
                f"check has_free_slot before join")
        if self.substrate == "paged" and self.page_pool.n_banks > 1:
            pref = None
            if pres.pages and pres.page_pool is self.page_pool:
                pref = self.page_pool.bank_of(pres.pages[0])
            slot = self._pick_slot(pref)
        else:
            slot = self.slots.index(None)
        L = pres.prompt_len
        n_emit = 0
        if resume_emitted is not None:
            n_emit = len(resume_emitted)
            if not 1 <= n_emit < request.max_new:
                raise ValueError(
                    f"resume_emitted carries {n_emit} tokens; a resumable "
                    f"victim has emitted at least 1 and fewer than "
                    f"max_new={request.max_new}")
        # depth this slot reaches at completion; for a resume this equals
        # the ORIGINAL prompt_len + max_new (the victim's bound does not
        # drift across preempt/restore cycles)
        final_len = L + request.max_new - max(n_emit - 1, 0)
        # both substrates: an overlong request must fail loudly up front.
        # The dense arena's .at[].set past max_len is silently DROPPED on
        # CPU (jax out-of-bounds update semantics), which decodes wrong
        # tokens instead of erroring; the paged table would outgrow
        # max_pages mid-decode.
        if final_len > self.max_len:
            raise ValueError(
                f"prompt ({L}) + remaining new tokens exceeds max_len "
                f"({self.max_len}) — the slot would outgrow its KV capacity "
                f"mid-decode")
        if self.substrate == "paged":
            pages = self._adopt_pages(pres, bank=self._slot_bank(slot))
            assert len(pages) <= self.max_pages, \
                f"prompt needs {len(pages)} pages > max_len's {self.max_pages}"
            self.block_table[slot, :len(pages)] = pages
            self.block_table[slot, len(pages):] = 0
            self.n_pages_slot[slot] = len(pages)
            self.seq_lens[slot] = L
        else:
            if self.caches.kv is not None:
                kv = self.caches.kv
                k, v = pres.host_kv()
                kv = KVCache(k=kv.k.at[:, slot, :L].set(jnp.asarray(k[:, :L])),
                             v=kv.v.at[:, slot, :L].set(jnp.asarray(v[:, :L])))
                self.caches = self.caches._replace(kv=kv)
            self.caches = self.caches._replace(
                length=self.caches.length.at[slot].set(L))
        if resume_emitted is not None:
            # continue the stream from the victim's own last token (for a
            # recompute restore pres.first_token is the re-derived argmax —
            # identical when the prefill is bit-exact, but the victim's
            # emitted history is the ground truth either way)
            first = int(resume_emitted[-1])
            emitted = list(resume_emitted)
            self.counters["resumed_joins"] += 1
        else:
            first = pres.first_token
            emitted = [pres.first_token]
        self.tokens = self.tokens.at[slot, 0].set(first)
        self.slots[slot] = _Slot(request=request, prompt_len=L,
                                 final_len=final_len, emitted=emitted)
        return slot

    def preempt(self, slot: int) -> PreemptedRun:
        """Victim-evict an active slot (vLLM-style preemption, the
        device→host demotion rung): export its live page run to host
        bytes via ``export_run`` — ownership of the device pages
        transfers into the returned ``PreemptedRun`` — and free the
        slot. Registered prefix blocks the run adopted stay in the
        registry (the export releases only this slot's references), so a
        reload restore re-adopts them without moving bytes. Paged
        substrate only: the dense arena has no per-slot pages to
        reclaim."""
        if self.substrate != "paged":
            raise RuntimeError(
                "preempt() requires the paged substrate — the dense arena "
                "frees no reclaimable device pages")
        s = self.slots[slot]
        if s is None:
            raise ValueError(f"preempt of empty slot {slot}")
        n_tokens = int(self.seq_lens[slot])
        n = int(self.n_pages_slot[slot])
        pages = [int(p) for p in self.block_table[slot, :n]]
        k, v = self.page_pool.export_run(pages, n_tokens)
        self.block_table[slot] = 0
        self.seq_lens[slot] = 0
        self.n_pages_slot[slot] = 0
        self.slots[slot] = None
        self.counters["preemptions"] += 1
        return PreemptedRun(request=s.request, emitted=list(s.emitted),
                            n_tokens=n_tokens, k=k, v=v)

    def stats(self) -> dict:
        """Unified snapshot (cross-component ``stats()`` protocol):
        lifetime counters + live batch gauges."""
        out = dict(self.counters)
        out["active_slots"] = self.n_active
        out["reserved_growth_pages"] = self.reserved_growth_pages()
        if self._touched is not None:
            out["moe_touched"] = int(self._touched)
        return out

    def _note_touched(self, touched: list) -> None:
        """Sum a paged step's count of held experts touched (``moe_touched``,
        on the device) beside what it could have touched (``moe_held``:
        every expert layer's held experts)."""
        if touched:
            moe = self.cfg.moe
            self._touched = self._touched + touched[0]
            self.counters["moe_held"] += \
                (self.cfg.n_layers - moe.first_dense) * moe.held

    def _prepare_writes(self, active: list[int]) -> None:
        """Host-side bookkeeping before a step: give every active slot an
        exclusively-owned page at its write position — a fresh page at a
        page boundary, copy-on-write if the tail page is shared."""
        pp = self.page_pool
        pt = pp.page_tokens
        for i in active:
            pidx = int(self.seq_lens[i]) // pt
            if pidx >= self.max_pages:   # join() bounds L+max_new, so this
                raise RuntimeError(      # is a programming error, not load
                    f"slot {i} outgrew its block table (len "
                    f"{int(self.seq_lens[i])} of max_len {self.max_len})")
            if pidx == int(self.n_pages_slot[i]):
                (pg,) = pp.alloc(1, bank=self._slot_bank(i))
                self.block_table[i, pidx] = pg
                self.n_pages_slot[i] += 1
            else:
                pid = int(self.block_table[i, pidx])
                new = pp.make_writable(pid)
                if new != pid:
                    self.block_table[i, pidx] = new

    def _step_full(self, active: list[int]) -> jax.Array:
        """Single-width full-batch step (the historical path; also the
        only mesh path — the sharded step takes the whole batch so its
        ``P('data')`` row split stays even). Returns per-slot next
        tokens (B,) int32."""
        pp = self.page_pool
        pt = pp.page_tokens
        # live page span: deepest active slot, padded to a power of two
        # so the jitted step sees at most log2(max_pages) shapes
        need = max(int(self.seq_lens[i]) // pt + 1 for i in active)
        width = min(_pow2_ceil(need), self.max_pages)
        if self.mesh is None:
            # .copy(): jax CPU zero-copies 2-D numpy buffers, and the host
            # tables mutate (growth/COW/length bumps) while the async step
            # still reads them — hand jit a frozen snapshot
            tbl = jnp.asarray(self.block_table[:, :width].copy())
        else:
            # the sharded step wants BANK-LOCAL page ids: each data shard
            # indexes its own slab stripe (the % makes a fresh array, so
            # no host buffer aliases into the async step)
            tbl = jnp.asarray(self.block_table[:, :width] % pp.bank_pages)
        lens = jnp.asarray(self.seq_lens.copy())
        logits, kp, vp, *touched = self._step_paged(
            self.params, self.tokens, pp.k_pages, pp.v_pages, tbl, lens)
        pp.k_pages, pp.v_pages = kp, vp
        self._note_touched(touched)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)

    def _step_bucketed(self, active: list[int]) -> jax.Array:
        """Width-bucketed step: group active slots by the pow2 table
        width they need (``plan_width_buckets``) and run one jitted
        sub-batch per bucket, so a shallow slot in a deep batch attends a
        short table instead of padding to the deepest slot's width.
        Bit-exact with ``_step_full`` — every row's computation is
        row-local, and sub-batch rows pad to a power of two with null
        rows (len 0, table 0), which behave exactly like the full-batch
        path's inactive slots. Buckets run sequentially, threading the
        donated page slabs through the pool (their KV writes touch
        disjoint pages).
        Returns per-slot next tokens (B,) int32 (0 for inactive slots —
        same as don't-care argmax noise in the full path)."""
        pp = self.page_pool
        pt = pp.page_tokens
        needs = {i: int(self.seq_lens[i]) // pt + 1 for i in active}
        plan = plan_width_buckets(list(needs.values()), self.max_pages,
                                  self.width_buckets)
        toks_host = np.asarray(self.tokens)
        nxt = np.zeros(self.max_batch, np.int32)
        for w in plan:
            rows = [i for i in active
                    if bucket_width(needs[i], plan, self.max_pages) == w]
            if not rows:
                continue
            nr = _pow2_ceil(len(rows))
            # fancy-indexed gathers below are fresh arrays (never views of
            # the mutating host tables), safe to hand the async step
            toks = np.zeros((nr, 1), np.int32)
            toks[:len(rows)] = toks_host[rows]
            tbl = np.zeros((nr, w), np.int32)
            tbl[:len(rows)] = self.block_table[rows][:, :w]
            lens = np.zeros(nr, np.int32)
            lens[:len(rows)] = self.seq_lens[rows]
            logits, pp.k_pages, pp.v_pages, *touched = self._step_paged(
                self.params, jnp.asarray(toks), pp.k_pages, pp.v_pages,
                jnp.asarray(tbl), jnp.asarray(lens))
            self._note_touched(touched)
            sub = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
            for j, i in enumerate(rows):
                nxt[i] = int(sub[j])
            self.counters["bucket_substeps"] += 1
        return jnp.asarray(nxt)

    def step(self) -> list[tuple[int, int, bool]]:
        """One continuous-batching iteration.
        Returns [(req_id, token, finished)] for active slots."""
        n_active = self.n_active
        if n_active == 0:
            return []
        self.counters["steps"] += 1
        with span("decode.step", slots=n_active):
            if self.substrate == "paged":
                active = [i for i, s in enumerate(self.slots)
                          if s is not None]
                with span("decode.prepare"):
                    self._prepare_writes(active)
                with span("decode.launch"):
                    if self.width_buckets > 1:
                        nxt = self._step_bucketed(active)
                    else:
                        nxt = self._step_full(active)
                    for i in active:
                        self.seq_lens[i] += 1
                    self.tokens = nxt[:, None]
            else:
                with span("decode.launch"):
                    logits, self.caches = self._step(self.params,
                                                     self.tokens, self.caches)
                    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                    self.tokens = nxt[:, None]
            with span("decode.readout"):
                return self._readout(nxt)

    def _readout(self, nxt: jax.Array) -> list[tuple[int, int, bool]]:
        """Each active slot's next token; finished slots are freed."""
        out = []
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            tok = int(nxt[i])
            s.emitted.append(tok)
            if s.done:
                out.append((s.req_id, tok, True))
                self.slots[i] = None
                if self.substrate == "paged":
                    self._free_slot_pages(i)
                else:
                    self.caches = self.caches._replace(
                        length=self.caches.length.at[i].set(0))
            else:
                out.append((s.req_id, tok, False))
        return out


class StateCheckpointWorker:
    """Prefix caching for SSM architectures (DESIGN.md §Arch-applicability).

    Attention-free models have no append-only KVCache; Mooncake's
    prefix-reuse degenerates to *state checkpointing*: after every
    512-token block boundary we snapshot the (constant-size) recurrent
    state keyed by the same prefix-chained hash. A later request sharing
    a prefix restores the DEEPEST checkpoint on its chain and prefills
    only the suffix — transfer cost is O(state), independent of prefix
    length, which strengthens disaggregation for these archs.
    """

    def __init__(self, params, cfg: ModelConfig, *,
                 capacity_checkpoints: Optional[int] = None,
                 chunk: int = BLOCK_TOKENS) -> None:
        from repro.core.cache import StateCache
        assert cfg.kind == "ssm", "state checkpointing is the SSM path"
        self.params = params
        self.cfg = cfg
        self.chunk = chunk
        self.meta = StateCache(capacity_checkpoints)
        self.data: dict[int, tuple] = {}   # hash -> (ssm np, conv np)
        self._prefill = jax.jit(lambda p, t: prefill(p, t, cfg))
        self._extend = jax.jit(lambda p, t, c: decode_step(p, t, c, cfg))
        self.counters = dict(restored_tokens=0, computed_tokens=0)

    def _snapshot(self, hash_id: int, caches: Caches) -> None:
        evicted = self.meta.insert([hash_id])
        for e in evicted:
            self.data.pop(e, None)
        if hash_id in self.meta:
            self.data[hash_id] = (
                np.asarray(caches.ssm.ssm), np.asarray(caches.ssm.conv))

    def __call__(self, tokens: np.ndarray):
        """Prefill one request (B = 1) with state-checkpoint reuse.
        Returns (first_token, final Caches)."""
        cfg = self.cfg
        S = len(tokens)
        hash_ids = prefix_hash_ids(tokens, self.chunk)
        depth = self.meta.lookup(hash_ids)          # deepest checkpoint
        start = depth * self.chunk
        if start >= S:                              # full hit: redo last blk
            depth -= 1
            start = depth * self.chunk
        t = jnp.asarray(tokens[None, :], jnp.int32)

        if depth > 0:
            ssm_np, conv_np = self.data[hash_ids[depth - 1]]
            from repro.models.mamba import MambaState
            caches = Caches(
                kv=None, enc_kv=None,
                ssm=MambaState(ssm=jnp.asarray(ssm_np),
                               conv=jnp.asarray(conv_np)),
                length=jnp.asarray(start, jnp.int32))
            logits = None
        else:
            caches = None
            logits = None

        # chunked continuation, snapshotting at every block boundary
        lo = start
        while lo < S:
            hi = min(lo + self.chunk, S)
            if caches is None:
                logits, caches = self._prefill(self.params, t[:, :hi])
                logits = logits[:, None] if logits.ndim == 2 else logits
            else:
                logits, caches = self._extend(self.params, t[:, lo:hi],
                                              caches)
            if hi % self.chunk == 0:
                self._snapshot(hash_ids[hi // self.chunk - 1], caches)
            lo = hi
        self.counters["restored_tokens"] += start
        self.counters["computed_tokens"] += S - start
        first = int(jnp.argmax(logits[0, -1]))
        return first, caches

    def stats(self) -> dict:
        """Unified snapshot (cross-component ``stats()`` protocol)."""
        out = dict(self.counters)
        out["checkpoints"] = len(self.data)
        return out
