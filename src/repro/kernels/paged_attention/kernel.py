"""Paged decode attention — Pallas TPU kernel.

The continuous-batching hot spot (§3 step 4): each active sequence's
single query token attends its paged KV through a block table. The page
gather is fused into the attention: the BlockSpec index map reads the
block table (scalar-prefetched into SMEM) and pulls exactly the pages the
sequence owns from HBM into VMEM — no materialised contiguous copy.

Grid (B, n_pages): one grid step fetches one whole page of the
(P, page, KV, D) slab — every KV head at once — and loops over the KV
heads statically; each head's ``group`` query heads are processed
together (GQA packing keeps the MXU matmul at (group × D) · (D × page)).
The block spans the full (KV, D) trailing axes because Mosaic tiles the
last two block dimensions by (8, 128) unless they equal the array's:
a size-1 head block in front of D is refused at any real width.
Online softmax over the page loop; tokens past ``seq_lens[b]`` masked.
VMEM per step: one (page, KV, D) K tile + V tile + (KV, group, D)
accumulators — a few hundred KiB at page = 64, KV·D = 256.

Sharding: the kernel itself is mesh-oblivious. Under the (data, model)
shard_map entries (``ops.paged_decode_attention_sharded``, the engine's
``decode_step_paged_sharded``) each shard invokes this kernel unchanged
on its LOCAL slices — a KV/m head stripe of the page slab and a B/d row
slice of the batch. Attention is head-local and row-local, so the grid
simply shrinks along those axes; no cross-device traffic happens inside
the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(table_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, lead: tuple, page: int,
                  scale: float, n_pages: int, kv_heads: int):
    """One (sequence, page) grid step over every KV head. ``lead`` indexes
    the block's size-1 leading axes: (0,) per layer, (0, 0) for the
    layer-stacked entry whose grid carries the layer first."""
    b = pl.program_id(len(lead) - 1)
    ip = pl.program_id(len(lead))
    full = slice(None)

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # token validity within this page
    pos = ip * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    valid = pos < lens_ref[b]                         # (1, page)

    for h in range(kv_heads):
        q = q_ref[lead + (h,)].astype(jnp.float32)    # (group, D)
        k = k_ref[lead + (full, h, full)].astype(jnp.float32)  # (page, D)
        v = v_ref[lead + (full, h, full)].astype(jnp.float32)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, NEG_INF)              # (group, page)

        m_prev = m_ref[h]                             # (group, 1)
        m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(valid, jnp.exp(s - m_cur), 0.0)
        l_ref[h] = l_ref[h] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_cur

    @pl.when(ip == n_pages - 1)
    def _finalize():
        for h in range(kv_heads):
            den = jnp.maximum(l_ref[h], 1e-30)
            o_ref[lead + (h,)] = (acc_ref[h] / den).astype(o_ref.dtype)


def _paged_call(qg, k_pages, v_pages, block_table, seq_lens, *, lead: tuple,
                interpret: bool):
    """Shared pallas_call: qg (*lead_dims, KV, group, D) against
    (*layer, P, page, KV, D) slabs. The grid is the leading query axes
    (layer and/or batch) then the page loop."""
    *outer, KV, group, D = qg.shape
    page = k_pages.shape[-3]
    max_pages = block_table.shape[1]
    n_lead = len(lead)            # 1: (B,)   2: (L, B)
    kernel = functools.partial(_paged_kernel, lead=lead, page=page,
                               scale=1.0 / (D ** 0.5), n_pages=max_pages,
                               kv_heads=KV)
    if n_lead == 1:
        q_map = lambda b, ip, tbl, lens: (b, 0, 0, 0)
        kv_map = lambda b, ip, tbl, lens: (tbl[b, ip], 0, 0, 0)
    else:
        q_map = lambda l, b, ip, tbl, lens: (l, b, 0, 0, 0)
        kv_map = lambda l, b, ip, tbl, lens: (l, tbl[b, ip], 0, 0, 0)
    lead_ones = (1,) * n_lead
    q_spec = pl.BlockSpec(lead_ones + (KV, group, D), q_map)
    kv_spec = pl.BlockSpec(lead_ones + (page, KV, D), kv_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=tuple(outer) + (max_pages,),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((KV, group, D), jnp.float32),
            pltpu.VMEM((KV, group, 1), jnp.float32),
            pltpu.VMEM((KV, group, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, qg.dtype),
        interpret=interpret,
    )(block_table, seq_lens, qg, k_pages, v_pages)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, block_table, seq_lens, *,
                    interpret: bool = False):
    """q: (B, H, D); k/v_pages: (P, page, KV, D);
    block_table: (B, max_pages) int32; seq_lens: (B,) int32."""
    B, H, D = q.shape
    KV = k_pages.shape[2]
    qg = q.reshape(B, KV, H // KV, D)
    out = _paged_call(qg, k_pages, v_pages, block_table, seq_lens,
                      lead=(0,), interpret=interpret)
    return out.reshape(B, H, D)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_layers(qs, k_pages, v_pages, block_table, seq_lens, *,
                           interpret: bool = False):
    """Batched-over-layers entry: qs (L, B, H, D) against the stacked
    (L, P, page, KV, D) page store, one block table shared by all layers.
    Grid (L, B, n_pages) — each layer's page gather rides the same
    scalar-prefetched table, so L layers launch as ONE kernel instead of
    L dispatches (the microbench / layer-parallel entry; the scanned
    decode path calls the per-layer ``paged_attention`` inside its scan).
    """
    L, B, H, D = qs.shape
    KV = k_pages.shape[3]
    qg = qs.reshape(L, B, KV, H // KV, D)
    out = _paged_call(qg, k_pages, v_pages, block_table, seq_lens,
                      lead=(0, 0), interpret=interpret)
    return out.reshape(L, B, H, D)
