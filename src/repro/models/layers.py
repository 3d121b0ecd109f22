"""Transformer building blocks (pure JAX, bf16 compute / fp32 reductions).

Sharding philosophy: parameters are annotated by ``launch/shardings.py``
(FSDP over 'data', tensor-parallel over 'model'); inside the forward we only
place activation constraints at block boundaries and run the MoE hot-path
under ``shard_map`` (expert-parallel all_to_all or tensor-parallel experts),
because XLA's SPMD partitioner handles scatter-based token dispatch poorly.

GQA with head counts not divisible by the model axis (smollm 15H/5KV,
whisper 20H, qwen3-14b 40H): query heads are padded to a multiple of 16 and
K/V are expanded per padded query head with a static gather
(``qh2kv`` map). The gather adds HBM traffic but no FLOPs — the grouped
einsum for divisible archs is a recorded §Perf optimization.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig, MODEL_AXIS

DTYPE = jnp.bfloat16

# §Perf iteration 2 (EXPERIMENTS.md): grouped GQA attention — contract
# grouped queries against the raw (KV, Dh) cache instead of materialising
# a per-query-head expanded copy (jnp.take over heads). Requires
# padded_heads % n_kv_heads == 0; others keep the expansion path.
GROUPED_ATTN = __import__("os").environ.get("REPRO_GROUPED_ATTN", "1") == "1"
# §Perf iteration 3: Megatron-style sequence-sharded residual stream —
# block-boundary activations sharded over 'model' on the sequence dim so
# TP all-reduces become all-gather + reduce-scatter pairs (half traffic)
# and norms/residuals compute on S/16 shards.
SEQ_SHARDED_RESIDUAL = __import__("os").environ.get(
    "REPRO_SEQ_SHARDED", "1") == "1"

# Pallas hot path: route prefill-attention chunks through the
# flash_prefill kernel (kernels/flash_prefill). Off by default; "1" runs
# the compiled kernel (TPU), "interpret" the Pallas interpreter (CPU
# validation only — it is for correctness, not speed).
_PALLAS_ATTN = __import__("os").environ.get("REPRO_USE_PALLAS", "0")
USE_PALLAS_ATTN = _PALLAS_ATTN in ("1", "interpret")

# Token count at/below which MoE uses the global (pjit-propagated) dispatch;
# above it, the shard_map expert-parallel path (decode steps are tiny,
# train/prefill are huge).
MOE_GLOBAL_DISPATCH_MAX_TOKENS = 4096
# Query-chunk length for the scanned (flash-style) attention path.
ATTN_CHUNK_Q = 1024
# MoE dispatch group length inside shard_map (bounds the dispatch buffer).
MOE_GROUP_TOKENS = 8192


@dataclass(frozen=True)
class Dist:
    """Distribution context threaded through the model forward."""
    mesh: Optional[Mesh] = None
    batch_axes: Any = ("pod", "data")  # mesh axes carrying the batch dim
    model_axis: str = "model"

    @property
    def active(self) -> bool:
        return self.mesh is not None

    def axis_size(self, name) -> int:
        if not self.active:
            return 1
        if isinstance(name, tuple):
            import math
            return math.prod(self.mesh.shape[a] for a in name if a in self.mesh.shape)
        return self.mesh.shape.get(name, 1)

    def batch_spec(self, *rest) -> P:
        ax = tuple(a for a in self.batch_axes if self.axis_size(a) > 1) or None
        if isinstance(ax, tuple) and len(ax) == 1:
            ax = ax[0]
        return P(ax, *rest)

    def residual_spec(self, seq_len: int) -> P:
        """Block-boundary residual sharding: (batch, seq, d_model).
        §Perf iter 3 (SEQ_SHARDED_RESIDUAL): shard the sequence over
        'model' so TP all-reduces lower to all-gather + reduce-scatter
        (half the traffic) and norms/residuals compute on S/TP shards."""
        if SEQ_SHARDED_RESIDUAL and seq_len > 1 \
                and seq_len % max(self.axis_size(self.model_axis), 1) == 0:
            return self.batch_spec(self.model_axis, None)
        return self.batch_spec(None, None)

    def constrain(self, x, spec: P):
        if self.active:
            return jax.lax.with_sharding_constraint(
                x, jax.sharding.NamedSharding(self.mesh, spec))
        return x


NO_DIST = Dist()


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) or (S,)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (d/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,d/2)
    sin = jnp.sin(angles)[:, :, None, :]
    cos = jnp.cos(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def qh2kv_map(n_q: int, n_kv: int, padded_q: int) -> jnp.ndarray:
    """Static map padded-query-head -> kv head (llama grouping; padded extra
    heads reuse kv head 0 — their output projection rows are zero-init)."""
    group = max(n_q // max(n_kv, 1), 1)
    idx = [min(h // group, n_kv - 1) if h < n_q else 0 for h in range(padded_q)]
    return jnp.asarray(idx, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _attend(q, k, v, mask, scale):
    """q:(B,Sq,H,D); k,v:(B,Sk,H,D) *or* (B,Sk,KV,D) with H = KV·g
    (grouped GQA — §Perf iteration 2: contract grouped queries against the
    raw KV instead of materialising an H-wide expanded copy).
    mask:(B?,1,Sq,Sk) bool -> (B,Sq,H,D)."""
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    if KV != H:
        g = H // KV
        qg = q.reshape(B, Sq, KV, g, D)
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(mask[:, :, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)
        return o.reshape(B, Sq, H, D)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def causal_attention(q, k, v, q_offset, window: int = 0,
                     chunk_q: int = ATTN_CHUNK_Q):
    """Causal (optionally sliding-window) attention over a full K/V.

    q: (B, Sq, H, D) at absolute positions q_offset + [0, Sq)
    k, v: (B, Sk, H, D) at absolute positions [0, Sk)   (Sk >= q_offset+Sq)
    Scanned over query chunks so the (Sq, Sk) logits never materialize whole.
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    scale = 1.0 / (D ** 0.5)
    kpos = jnp.arange(Sk)

    def mask_for(qpos):  # qpos (C,) absolute
        m = qpos[:, None] >= kpos[None, :]
        if window:
            m &= (qpos[:, None] - kpos[None, :]) < window
        return m[None, None]  # (1,1,C,Sk)

    if USE_PALLAS_ATTN and Sq % 16 == 0 and Sk % 16 == 0 \
            and isinstance(q_offset, int) and D in (32, 64, 128, 256):
        from repro.kernels.flash_prefill.ops import flash_prefill_attention
        return flash_prefill_attention(
            q, k, v, q_offset=q_offset, window=window, use_pallas=True,
            interpret=_PALLAS_ATTN == "interpret")

    if Sq <= chunk_q:
        qpos = q_offset + jnp.arange(Sq)
        return _attend(q, k, v, mask_for(qpos), scale)

    n_chunks = Sq // chunk_q
    rem = Sq - n_chunks * chunk_q
    qs = q[:, :n_chunks * chunk_q].reshape(B, n_chunks, chunk_q, H, D)
    qs = jnp.moveaxis(qs, 1, 0)  # (n_chunks, B, C, H, D)

    def body(_, qc_i):
        qc, i = qc_i
        qpos = q_offset + i * chunk_q + jnp.arange(chunk_q)
        return None, _attend(qc, k, v, mask_for(qpos), scale)

    _, out = jax.lax.scan(body, None, (qs, jnp.arange(n_chunks)))
    out = jnp.moveaxis(out, 0, 1).reshape(B, n_chunks * chunk_q, H, D)
    if rem:
        qpos = q_offset + n_chunks * chunk_q + jnp.arange(rem)
        tail = _attend(q[:, -rem:], k, v, mask_for(qpos), scale)
        out = jnp.concatenate([out, tail], axis=1)
    return out


def decode_attention(q, k_cache, v_cache, cache_len, window: int = 0):
    """One-token attention against a (possibly ring-buffer) cache.

    q: (B, 1, H, D); k_cache/v_cache: (B, S_cache, H, D) with RoPE already
    applied at write time. ``cache_len`` counts tokens written *including*
    the current one — scalar or (B,) for continuous batching. For sliding
    windows the cache IS the window (ring buffer), so every slot
    < min(cache_len, S_cache) is valid.
    """
    B, S, KVH, D = k_cache.shape      # KVH = H (expanded) or KV (grouped)
    scale = 1.0 / (D ** 0.5)
    clen = jnp.asarray(cache_len)
    if clen.ndim == 1:
        clen = clen[:, None]          # (B, 1)
    valid = jnp.arange(S)[None, :] < jnp.minimum(clen, S)  # (1|B, S)
    if window and S > window:
        # linear (non-ring) cache of a windowed arch: mask slots older
        # than the window (ring callers size the cache AT the window).
        valid &= jnp.arange(S)[None, :] >= clen - window
    mask = valid[:, None, None, :]  # (B|1, 1, 1, S)
    return _attend(q, k_cache, v_cache, mask, scale)


def attention_block(x, p, cfg: ModelConfig, dist: Dist, *,
                    q_offset=0, cache=None, cache_len=None, ring: bool = False,
                    kv_out: bool = False, enc_kv=None, causal: bool = True,
                    window_override: Optional[int] = None):
    """Full attention sub-block: norm -> qkv -> rope -> attend -> out proj.

    Returns (y, new_cache_or_kv):
      * train/prefill (cache is None): new KV (k, v) if kv_out else None
      * decode (cache = (k_cache, v_cache)): updated cache
      * cross-attention (enc_kv given): attends encoder K/V, no cache.
    """
    B, S, _ = x.shape
    Hp, KV, Dh = cfg.padded_heads, cfg.n_kv_heads, cfg.head_dim
    window = cfg.sliding_window if window_override is None else window_override
    # grouped GQA (§Perf iter 2): skip the per-query-head KV expansion.
    # Only when heads are unpadded does the contiguous (KV, g) reshape
    # agree with the qh2kv mapping (padded archs — smollm/whisper/
    # qwen3-14b — keep the gather; group-contiguous head reordering for
    # padded archs is a recorded future iteration).
    grouped = GROUPED_ATTN and Hp == cfg.n_heads and Hp % KV == 0

    def expand(t):
        return t if grouped else jnp.take(t, qh2kv, axis=2)

    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q = (h @ p["wq"]).reshape(B, S, Hp, Dh)
    if cfg.attn_bias:
        q = q + p["bq"].reshape(1, 1, Hp, Dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)

    qh2kv = qh2kv_map(cfg.n_heads, KV, Hp)

    if enc_kv is not None:  # cross-attention: K/V precomputed from encoder
        k_full, v_full = enc_kv  # (B, S_enc, KV, Dh), rope-free
        k_exp = expand(k_full)
        v_exp = expand(v_full)
        Sk = k_exp.shape[1]
        mask = jnp.ones((1, 1, S, Sk), dtype=bool)
        o = _attend(q, k_exp, v_exp, mask, 1.0 / (Dh ** 0.5))
        y = o.reshape(B, S, Hp * Dh) @ p["wo"]
        return y, None

    k = (h @ p["wk"]).reshape(B, S, KV, Dh)
    v = (h @ p["wv"]).reshape(B, S, KV, Dh)
    if cfg.attn_bias:
        k = k + p["bk"].reshape(1, 1, KV, Dh)
        v = v + p["bv"].reshape(1, 1, KV, Dh)
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)

    if cache is None:
        positions = q_offset + jnp.arange(S)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        # §Perf iteration 5 (REFUTED, reverted — see EXPERIMENTS.md): the
        # partitioner all-gathers the H-headed Q here instead of the
        # KV-headed K/V (H/KV× more traffic than necessary). Explicitly
        # constraining K/V replicated (with or without pinning Q to the
        # sequence shards) back-propagated replication through the whole
        # layer: 11× redundant FLOPs/bytes. GSPMD's Q-gather stands.
        k_exp = expand(k)
        v_exp = expand(v)
        if causal:
            o = causal_attention(q, k_exp, v_exp, q_offset, window)
        else:
            Sk = k_exp.shape[1]
            mask = jnp.ones((1, 1, S, Sk), dtype=bool)
            o = _attend(q, k_exp, v_exp, mask, 1.0 / (Dh ** 0.5))
        y = o.reshape(B, S, Hp * Dh) @ p["wo"]
        return y, ((k, v) if kv_out else None)

    # ---- decode/extend: write S new tokens at absolute position cache_len --
    # ``cache_len`` is scalar (uniform batch: serve_step / chunked prefill)
    # or (B,) (continuous batching: every slot at a different depth).
    k_cache, v_cache = cache  # (B, S_cache, KV, Dh)
    S_cache = k_cache.shape[1]
    pos = jnp.asarray(cache_len)  # absolute position of the first new token
    per_seq = pos.ndim == 1
    positions = (pos[:, None] if per_seq else pos) \
        + jnp.arange(S, dtype=jnp.int32)[None, :]
    q = apply_rope(q, jnp.broadcast_to(positions, (B, S)), cfg.rope_theta)
    k = apply_rope(k, jnp.broadcast_to(positions, (B, S)), cfg.rope_theta)

    def upd(cache_b, new_b, at):
        return jax.lax.dynamic_update_slice_in_dim(cache_b, new_b, at, axis=0)

    if ring:
        # sliding-window ring buffer: the cache IS the window (S == 1 path,
        # used by serve_step for long-context decode of windowed archs).
        slot = pos % S_cache
        if per_seq:
            k_cache = jax.vmap(upd)(k_cache, k, slot)
            v_cache = jax.vmap(upd)(v_cache, v, slot)
        else:
            k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, slot, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, slot, axis=1)
        o = decode_attention(q, expand(k_cache), expand(v_cache),
                             pos + 1, window)
    else:
        if per_seq:
            k_cache = jax.vmap(upd)(k_cache, k, pos)
            v_cache = jax.vmap(upd)(v_cache, v, pos)
        else:
            k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, pos, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, pos, axis=1)
        if per_seq:
            o = decode_attention(q, expand(k_cache), expand(v_cache),
                                 pos + S, window)
        else:
            o = causal_attention(q, expand(k_cache), expand(v_cache),
                                 pos, window)
    y = o.reshape(B, S, Hp * Dh) @ p["wo"]
    return y, (k_cache, v_cache)


@dataclass(frozen=True)
class PagedShard:
    """shard_map context for the sharded paged decode step: the mesh axis
    that stripes KV heads and its size. ``n_model == 1`` degrades every
    sharded code path to the single-device one (no axis_index, no
    collective), so one implementation serves both."""
    model_axis: str = "model"
    n_model: int = 1


def paged_attention_block(x, p, cfg: ModelConfig, dist: Dist, *,
                          k_pages, v_pages, layer, block_table, seq_lens,
                          use_pallas: bool = False,
                          window_override: Optional[int] = None,
                          shard: Optional[PagedShard] = None):
    """Decode attention sub-block over one layer's PAGED KV store (§3
    step 4 on the block-table substrate): norm → qkv → rope at each
    slot's depth → scatter the new token's K/V into the slot's current
    tail page → attend through the block table (the dense-numerics
    oracle, or with ``use_pallas`` the Pallas kernel).

    x: (B, 1, D); k_pages/v_pages: the stacked lane-dense slabs (L, P,
    page, KV·Dh), threaded whole through the layers so that this layer,
    ``layer``, writes only its B new rows in place (one scatter at (layer,
    page, offset)) and reads its pages with one gather at (layer, block
    table); block_table: (B, max_pages) int32; seq_lens: (B,) tokens
    already written per slot (the new token lands at that position,
    exactly like the dense path's ``cache_len``). Returns (y, (k_pages,
    v_pages)).

    The engine guarantees host-side that every active slot's write-target
    page is exclusively owned (copy-on-write happens before the step), so
    the scatter never mutates a page another slot can read.

    ``shard`` (inside ``jax.shard_map`` only): KV heads are striped
    over ``shard.model_axis`` — this shard's page slabs hold KV/m heads
    (a contiguous KV/m·Dh stripe of the width).
    The projections compute the FULL head set (replicated math, so every
    per-head value is bitwise the single-device one), this shard's head
    slice is written/attended locally (attention is head-local: no
    collective in the inner loop), and the post-attention combine is one
    head-concatenating ``all_gather`` feeding the output projection —
    an exact recombination, never a partial-sum reduce.
    """
    from repro.kernels.paged_attention.ops import paged_decode_attention
    from repro.kernels.paged_attention.ref import (linearise,
                                                   paged_attention_rows)
    B, S, _ = x.shape
    assert S == 1, "paged decode is one token per slot per step"
    Hp, KV, Dh = cfg.padded_heads, cfg.n_kv_heads, cfg.head_dim
    window = cfg.sliding_window if window_override is None else window_override
    grouped = GROUPED_ATTN and Hp == cfg.n_heads and Hp % KV == 0
    qh2kv = None if grouped else qh2kv_map(cfg.n_heads, KV, Hp)
    n_model = shard.n_model if shard is not None else 1
    if n_model > 1:
        assert grouped and KV % n_model == 0, \
            "model-parallel KV heads require grouped GQA with KV % m == 0"

    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q = (h @ p["wq"]).reshape(B, S, Hp, Dh)
    if cfg.attn_bias:
        q = q + p["bq"].reshape(1, 1, Hp, Dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    k = (h @ p["wk"]).reshape(B, S, KV, Dh)
    v = (h @ p["wv"]).reshape(B, S, KV, Dh)
    if cfg.attn_bias:
        k = k + p["bk"].reshape(1, 1, KV, Dh)
        v = v + p["bv"].reshape(1, 1, KV, Dh)
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)

    pos = jnp.asarray(seq_lens)
    positions = jnp.broadcast_to(pos[:, None], (B, S))
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    if n_model > 1:
        # this shard's contiguous KV-head stripe (and the query group that
        # attends it — grouped GQA keeps query heads head-local too)
        kv_loc = KV // n_model
        g = Hp // KV
        mi = jax.lax.axis_index(shard.model_axis)
        q = jax.lax.dynamic_slice_in_dim(q, mi * kv_loc * g, kv_loc * g, 2)
        k = jax.lax.dynamic_slice_in_dim(k, mi * kv_loc, kv_loc, 2)
        v = jax.lax.dynamic_slice_in_dim(v, mi * kv_loc, kv_loc, 2)

    # scatter the new K/V row into each slot's tail page at this layer
    # (inactive slots target the null page 0 — always masked, never read)
    pt = k_pages.shape[2]
    pidx = jnp.clip(pos // pt, 0, block_table.shape[1] - 1)
    pids = block_table[jnp.arange(B), pidx]
    offs = pos % pt
    k_pages = k_pages.at[layer, pids, offs].set(k[:, 0].reshape(B, -1))
    v_pages = v_pages.at[layer, pids, offs].set(v[:, 0].reshape(B, -1))

    kv_loc = k.shape[2]
    if use_pallas:
        # the kernel takes one layer's (P, page, KV, Dh) pages
        def heads(slab):
            return slab[layer].reshape(slab.shape[1], pt, kv_loc, Dh)
        o = paged_decode_attention(q[:, 0], heads(k_pages), heads(v_pages),
                                   block_table, pos + 1, qh2kv=qh2kv,
                                   window=window, use_pallas=True)
    else:
        def rows(slab):
            return linearise(slab, block_table, layer).reshape(
                B, -1, kv_loc, Dh)
        o = paged_attention_rows(q[:, 0], rows(k_pages), rows(v_pages),
                                 pos + 1, qh2kv=qh2kv, window=window)
    if n_model > 1:
        # exact head-concatenating combine: each head's value comes from
        # exactly one shard, so the recombined o is bitwise the oracle's
        o = jax.lax.all_gather(o, shard.model_axis, axis=1, tiled=True)
    y = o.reshape(B, S, Hp * Dh) @ p["wo"]
    return y, (k_pages, v_pages)


# ---------------------------------------------------------------------------
# dense MLP
# ---------------------------------------------------------------------------

def mlp_block(x, p, cfg: ModelConfig):
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    gate = jax.nn.silu(h @ p["w1"])
    up = h @ p["w3"]
    return (gate * up) @ p["w2"]


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def _dispatch_indices(gates_idx, n_experts: int, capacity: int):
    """gates_idx: (T, k) expert ids -> flat slot ids (T*k,) into an
    (E*C [+1 overflow]) buffer; slot E*C means 'dropped'."""
    Tk = gates_idx.shape[0] * gates_idx.shape[1]
    flat_e = gates_idx.reshape(Tk)
    oh = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)  # (Tk, E)
    pos = jnp.cumsum(oh, axis=0) - oh
    pos_e = jnp.sum(pos * oh, axis=-1)  # (Tk,) position within expert
    slot = flat_e * capacity + pos_e
    return jnp.where(pos_e < capacity, slot, n_experts * capacity)


def _expert_ffn(buf, w1, w2, w3):
    """buf: (E, C, D); w*: (E, D, F)/(E, F, D) -> (E, C, D)."""
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w1))
    up = jnp.einsum("ecd,edf->ecf", buf, w3)
    return jnp.einsum("ecf,efd->ecd", gate * up, w2)


def _route(xf, router_w, top_k: int):
    logits = (xf @ router_w).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)  # (T, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # load-balance aux loss (Switch-style): E * mean(f_e * p_e)
    E = router_w.shape[-1]
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)
    return gates.astype(xf.dtype), idx, aux


def _moe_dispatch_compute(xf, router_w, w1, w2, w3, top_k, capacity):
    """Scatter-dispatch MoE on a flat token slab (T, D). Local/global agnostic."""
    T, D = xf.shape
    E = router_w.shape[-1]
    gates, idx, aux = _route(xf, router_w, top_k)
    slot = _dispatch_indices(idx, E, capacity)  # (T*k,)
    x_rep = jnp.repeat(xf, top_k, axis=0)  # (T*k, D)
    buf = jnp.zeros((E * capacity + 1, D), dtype=xf.dtype).at[slot].add(x_rep)
    out = _expert_ffn(buf[:-1].reshape(E, capacity, D), w1, w2, w3)
    out_flat = jnp.concatenate(
        [out.reshape(E * capacity, D), jnp.zeros((1, D), dtype=xf.dtype)])
    y = jnp.take(out_flat, slot, axis=0).reshape(T, top_k, D)
    y = jnp.sum(y * gates[:, :, None], axis=1)
    return y, aux


def _moe_ep_local(xf, router_w, w1l, w2l, w3l, *, top_k, capacity,
                  model_axis, ep, batch_axes):
    """Inside shard_map: xf (T_loc, D) local tokens; w*l (E_loc, D, F) local
    experts. all_to_all over the model axis redistributes capacity slabs."""
    T, D = xf.shape
    E_loc = w1l.shape[0]
    E = E_loc * ep
    gates, idx, aux = _route(xf, router_w, top_k)
    n_groups = max(T // MOE_GROUP_TOKENS, 1)
    G = T // n_groups

    def one_group(carry, args):
        xg, idxg, gatesg = args
        slot = _dispatch_indices(idxg, E, capacity)
        x_rep = jnp.repeat(xg, top_k, axis=0)
        buf = jnp.zeros((E * capacity + 1, D), dtype=xg.dtype).at[slot].add(x_rep)
        buf = buf[:-1].reshape(E, capacity, D)
        if ep > 1:
            # (E, C, D) -> peers: send expert-slab i*E_loc..(i+1)E_loc to peer i
            buf = jax.lax.all_to_all(buf, model_axis, split_axis=0,
                                     concat_axis=1, tiled=True)
            # now (E_loc, ep*C, D): all peers' tokens for my local experts
        out = _expert_ffn(buf, w1l, w2l, w3l)
        if ep > 1:
            out = jax.lax.all_to_all(out, model_axis, split_axis=1,
                                     concat_axis=0, tiled=True)
        out_flat = jnp.concatenate(
            [out.reshape(E * capacity, D), jnp.zeros((1, D), dtype=xg.dtype)])
        y = jnp.take(out_flat, slot, axis=0).reshape(G, top_k, D)
        return carry, jnp.sum(y * gatesg[:, :, None], axis=1)

    xg = xf.reshape(n_groups, G, D)
    idxg = idx.reshape(n_groups, G, top_k)
    gatesg = gates.reshape(n_groups, G, top_k)
    _, y = jax.lax.scan(one_group, None, (xg, idxg, gatesg))
    if batch_axes:
        aux = jax.lax.pmean(aux, batch_axes)
    return y.reshape(T, D), aux


def _moe_tp_local(xf, router_w, w1l, w2l, w3l, *, top_k, capacity, model_axis,
                  batch_axes):
    """Inside shard_map: all experts local, expert-FF hidden dim sharded over
    the model axis (row/column parallel) -> psum after the down projection."""
    T, D = xf.shape
    E = router_w.shape[-1]
    gates, idx, aux = _route(xf, router_w, top_k)
    n_groups = max(T // MOE_GROUP_TOKENS, 1)
    G = T // n_groups

    def one_group(carry, args):
        xg, idxg, gatesg = args
        slot = _dispatch_indices(idxg, E, capacity)
        x_rep = jnp.repeat(xg, top_k, axis=0)
        buf = jnp.zeros((E * capacity + 1, D), dtype=xg.dtype).at[slot].add(x_rep)
        out = _expert_ffn(buf[:-1].reshape(E, capacity, D), w1l, w2l, w3l)
        out = jax.lax.psum(out, model_axis)
        out_flat = jnp.concatenate(
            [out.reshape(E * capacity, D), jnp.zeros((1, D), dtype=xg.dtype)])
        y = jnp.take(out_flat, slot, axis=0).reshape(G, top_k, D)
        return carry, jnp.sum(y * gatesg[:, :, None], axis=1)

    xg = xf.reshape(n_groups, G, D)
    idxg = idx.reshape(n_groups, G, top_k)
    gatesg = gates.reshape(n_groups, G, top_k)
    _, y = jax.lax.scan(one_group, None, (xg, idxg, gatesg))
    if batch_axes:
        aux = jax.lax.pmean(aux, batch_axes)
    return y.reshape(T, D), aux


def moe_block(x, p, cfg: ModelConfig, dist: Dist):
    """x: (B, S, D) -> (y, aux_loss)."""
    moe = cfg.moe
    B, S, D = x.shape
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    T_total = B * S
    cap_of = lambda T: max(int(T * moe.top_k / moe.n_experts
                               * moe.capacity_factor + 0.999), moe.top_k)

    if not dist.active or T_total <= MOE_GLOBAL_DISPATCH_MAX_TOKENS:
        y, aux = _moe_dispatch_compute(
            h.reshape(T_total, D), p["router"], p["w1"], p["w2"], p["w3"],
            moe.top_k, cap_of(T_total))
        return y.reshape(B, S, D), aux

    mesh = dist.mesh
    ma = dist.model_axis
    ep = dist.axis_size(ma)
    batch_axes = tuple(a for a in dist.batch_axes if a in mesh.shape)
    dp = dist.axis_size(batch_axes)

    use_ep = moe.parallelism == "ep" and moe.n_experts % ep == 0 \
        and T_total % (max(dp, 1) * ep) == 0
    if use_ep:
        # expert parallelism: tokens are split over the MODEL axis too
        # (each device dispatches its own token slice; the all_to_all
        # exchanges capacity slabs). Without the model-axis split every
        # model-row device would redundantly dispatch the same tokens —
        # ep× wasted FLOPs (EXPERIMENTS.md §Perf iteration 1).
        tok_axes = batch_axes + (ma,)
        T_loc = max(T_total // max(dp * ep, 1), 1)
    else:
        # tensor-parallel experts: hidden dim sharded; tokens replicated
        # over model, partial FF psum'd — the work split is the hidden dim.
        tok_axes = batch_axes
        T_loc = max(T_total // max(dp, 1), 1)
    tok_spec = P(tok_axes if len(tok_axes) != 1 else tok_axes[0], None)
    cap = cap_of(max(T_loc // max(T_loc // MOE_GROUP_TOKENS, 1), 1))

    if use_ep:
        w_spec = P(ma, None, None)
        w2_spec = P(ma, None, None)
        local = partial(_moe_ep_local, top_k=moe.top_k, capacity=cap,
                        model_axis=ma, ep=ep, batch_axes=tok_axes)
    else:
        w_spec = P(None, None, ma)  # shard expert hidden dim
        w2_spec = P(None, ma, None)
        local = partial(_moe_tp_local, top_k=moe.top_k, capacity=cap,
                        model_axis=ma, batch_axes=batch_axes)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(tok_spec, P(None, None), w_spec, w2_spec, w_spec),
        out_specs=(tok_spec, P()),
        check_vma=False)
    y, aux = fn(h.reshape(T_total, D), p["router"], p["w1"], p["w2"], p["w3"])
    return y.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2/V3)
# ---------------------------------------------------------------------------

def _mla_project(h, p, cfg: ModelConfig, positions):
    """h (B, S, D) normed, positions (B, S) -> q_nope (B, S, H, nope),
    q_pe (B, S, H, rope) roped, c_kv (B, S, rank) normed, k_pe (B, S,
    rope) roped: the per-token latent pair the cache holds."""
    m = cfg.mla
    B, S, _ = h.shape
    q = (h @ p["wq"]).reshape(B, S, cfg.n_heads, m.qk_head_dim)
    q_nope, q_pe = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    kv = h @ p["wkv_a"]
    c_kv = rms_norm(kv[..., :m.kv_lora_rank], p["kv_ln"], cfg.norm_eps)
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta)
    k_pe = apply_rope(kv[..., None, m.kv_lora_rank:], positions,
                      cfg.rope_theta)[:, :, 0]
    return q_nope, q_pe, c_kv, k_pe


def mla_attention_block(x, p, cfg: ModelConfig, *, positions, cache, layer):
    """MLA in the expanded form over a dense latent arena (prefill, chunk
    extension, the dense decode oracle): norm → q and the latent pair of
    the new tokens → write the pair into layer ``layer`` of ``cache`` at
    ``positions`` → expand every cached ``c_kv`` through ``kv_b`` to
    per-head keys and values → causal attention (a query sees the cached
    positions up to its own) → ``o_proj``.

    x: (B, S, D); positions: (B, S) absolute; cache: (c_kv, k_pe) arenas
    (L, B, S_cache, rank) and (L, B, S_cache, rope), threaded through the
    layers. Returns (y, cache)."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q_nope, q_pe, c_new, pe_new = _mla_project(h, p, cfg, positions)
    c_all, pe_all = cache
    rows = jnp.arange(B)[:, None]
    c_all = c_all.at[layer, rows, positions].set(c_new.astype(c_all.dtype))
    pe_all = pe_all.at[layer, rows, positions].set(
        pe_new.astype(pe_all.dtype))
    c_kv, k_pe = c_all[layer], pe_all[layer]
    Sk = c_kv.shape[1]
    kv = (c_kv @ p["wkv_b"]).reshape(B, Sk, H,
                                     m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
    logits = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhr,bkr->bhqk", q_pe, k_pe,
                           preferred_element_type=jnp.float32))
    logits = logits * m.qk_head_dim ** -0.5
    mask = jnp.arange(Sk)[None, None, :] <= positions[:, :, None]
    logits = jnp.where(mask[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return o.reshape(B, S, H * m.v_head_dim) @ p["wo"], (c_all, pe_all)


def paged_mla_block(x, p, cfg: ModelConfig, *, c_pages, pe_pages, layer,
                    block_table, seq_lens):
    """MLA decode over the latent page slabs, absorbed: ``q_nope·W_UK``
    puts each head's query into the ``c_kv`` space, scores are taken
    against the cached ``c_kv`` and ``k_pe``, the context stays latent,
    then ``W_UV`` and ``o_proj``. ``kv_b`` is never applied to the cache.

    x: (B, 1, D); c_pages (L, P, page, rank), pe_pages (L, P, page, rope):
    the stacked slabs, threaded whole through the layers so that only the
    B new rows of layer ``layer`` are written; block_table (B, max_pages);
    seq_lens (B,) tokens already written per slot (the new token lands
    there; inactive slots point at the null page). Returns (y, (c_pages,
    pe_pages))."""
    from repro.kernels.paged_attention.ref import linearise
    m = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    pos = jnp.asarray(seq_lens)
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q_nope, q_pe, c_new, pe_new = _mla_project(h, p, cfg, pos[:, None])
    pt = c_pages.shape[2]
    n = block_table.shape[1]
    pids = block_table[jnp.arange(B), jnp.clip(pos // pt, 0, n - 1)]
    offs = pos % pt
    c_pages = c_pages.at[layer, pids, offs].set(
        c_new[:, 0].astype(c_pages.dtype))
    pe_pages = pe_pages.at[layer, pids, offs].set(
        pe_new[:, 0].astype(pe_pages.dtype))
    c_kv = linearise(c_pages, block_table, layer)      # (B, n·pt, rank)
    k_pe = linearise(pe_pages, block_table, layer)     # (B, n·pt, rope)
    w_b = p["wkv_b"].reshape(m.kv_lora_rank, H,
                             m.qk_nope_head_dim + m.v_head_dim)
    w_uk, w_uv = jnp.split(w_b, [m.qk_nope_head_dim], axis=-1)
    q_lat = jnp.einsum("bhn,chn->bhc", q_nope[:, 0], w_uk)
    logits = (jnp.einsum("bhc,bsc->bhs", q_lat, c_kv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhr,bsr->bhs", q_pe[:, 0], k_pe,
                           preferred_element_type=jnp.float32))
    logits = logits * m.qk_head_dim ** -0.5
    valid = jnp.arange(n * pt)[None, :] <= pos[:, None]
    logits = jnp.where(valid[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bhs,bsc->bhc", probs.astype(c_kv.dtype), c_kv)
    o = jnp.einsum("bhc,chv->bhv", ctx, w_uv).reshape(B, 1, -1)
    return o @ p["wo"], (c_pages, pe_pages)


# ---------------------------------------------------------------------------
# DeepSeek-V3 expert layer, as one chip's share of an expert-parallel group
# ---------------------------------------------------------------------------

def _swiglu(h, w1, w2, w3):
    return (jax.nn.silu(h @ w1) * (h @ w3)) @ w2


def expert_share_block(x, p, cfg: ModelConfig):
    """The MoE FFN that this chip holds a share of: the router scores all
    ``n_experts`` in float32 from the bf16 normed hidden state
    (``sigmoid``), picks ``top_k`` on score + the selection bias
    (``noaux_tc`` with one group), and gates by the picked scores
    renormalised × ``routed_scale``; of those picks only the held experts
    [``expert_first``, + ``held``) are computed, for the tokens routed to
    them, and the shared expert is added for every token. What the absent
    experts would add is left out; on one chip there is no exchange.

    Every held expert runs over every token with its combine weight (0
    where the token did not pick it), so no token is dropped and the
    shapes are static.

    x: (B, S, D); p: ``ln``, ``router`` (D, E), ``bias`` (E,) float32,
    ``w1``/``w3`` (held, D, F), ``w2`` (held, F, D) and ``shared``
    (``w1``, ``w2``, ``w3``). Returns (y, picked): picked (B·S, held)
    bool, whether each token routed to each held expert."""
    moe = cfg.moe
    B, S, D = x.shape
    h = rms_norm(x, p["ln"], cfg.norm_eps).reshape(B * S, D)
    logits = jnp.dot(h.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + p["bias"], moe.top_k)
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    gates = gates / gates.sum(-1, keepdims=True) * moe.routed_scale
    # (T, k, held): a pick of an expert held elsewhere matches no column
    hit = jax.nn.one_hot(idx - moe.expert_first, moe.held, dtype=jnp.float32)
    combine = jnp.einsum("tk,tke->te", gates, hit)
    up = jax.nn.silu(jnp.einsum("td,edf->etf", h, p["w1"])) \
        * jnp.einsum("td,edf->etf", h, p["w3"])
    out = jnp.einsum("etf,efd->etd", up, p["w2"])
    y = jnp.einsum("te,etd->td", combine, out.astype(jnp.float32))
    sh = p["shared"]
    y = y + _swiglu(h, sh["w1"], sh["w2"], sh["w3"]).astype(jnp.float32)
    return y.astype(x.dtype).reshape(B, S, D), hit.sum(1) > 0
