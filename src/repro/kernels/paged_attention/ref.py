"""Pure-jnp oracle for paged decode attention.

The numerics deliberately mirror ``models.layers._attend``'s decode path
(fp32 logits, -1e30 masking, fp32 softmax, probabilities cast to the
value dtype before the PV contraction) so the engine's paged substrate is
bit-comparable with the dense arena it replaces: the only difference
between the two is WHERE the KV bytes live, never how they are reduced.

Two head layouts:

* grouped GQA (``qh2kv is None``): requires H % KV == 0; query head h
  attends kv head h // (H // KV) — the layout the Pallas kernel packs.
* explicit map (``qh2kv`` = (H,) int32): arbitrary query-head → kv-head
  assignment, covering archs whose padded query heads are not divisible
  by KV (smollm 16→5); mirrors the dense path's ``qh2kv_map`` expansion.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import nn

NEG_INF = -1e30


def linearise(pages, block_table, layer=None):
    """Each slot's pages in table order, as one run of token rows: (P,
    page, ...) pages, or with ``layer`` the stacked (L, P, page, ...) slab
    read at that layer, + (B, max_pages) table -> (B, max_pages·page,
    ...). One gather either way: a layer is never sliced out first."""
    g = pages[block_table] if layer is None else pages[layer, block_table]
    B = g.shape[0]
    return g.reshape(B, g.shape[1] * g.shape[2], *g.shape[3:])


def _valid_mask(S, seq_lens, window):
    """Mirror ``decode_attention``'s mask: slots < len valid; a linear
    cache of a windowed arch masks slots older than the window."""
    clen = jnp.asarray(seq_lens)[:, None]            # (B, 1)
    valid = jnp.arange(S)[None, :] < jnp.minimum(clen, S)
    if window and S > window:
        valid &= jnp.arange(S)[None, :] >= clen - window
    return valid                                      # (B, S)


def paged_attention_ref(q, k_pages, v_pages, block_table, seq_lens, *,
                        qh2kv=None, window: int = 0):
    """One-token GQA attention over paged KV.

    q:          (B, H, D) — the current token's queries
    k_pages:    (P, page, KV, D) one layer's page store
    v_pages:    (P, page, KV, D)
    block_table:(B, max_pages) int32 page ids (0 = null page)
    seq_lens:   (B,) int32 valid tokens per sequence
    qh2kv:      optional (H,) query-head → kv-head map (padded GQA)
    window:     sliding-window size (0 = full attention)
    Returns (B, H, D) in q.dtype.
    """
    return paged_attention_rows(q, linearise(k_pages, block_table),
                                linearise(v_pages, block_table), seq_lens,
                                qh2kv=qh2kv, window=window)


def paged_attention_rows(q, k, v, seq_lens, *, qh2kv=None, window: int = 0):
    """``paged_attention_ref`` after the gather: q (B, H, D) against each
    slot's linearised rows k, v (B, S, KV, D). Returns (B, H, D)."""
    B, H, D = q.shape
    KV = k.shape[2]
    scale = 1.0 / (D ** 0.5)
    S = k.shape[1]
    valid = _valid_mask(S, seq_lens, window)

    if qh2kv is not None:                             # expanded-head path
        k = jnp.take(k, qh2kv, axis=2)                # (B, S, H, D)
        v = jnp.take(v, qh2kv, axis=2)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q[:, None], k,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
        probs = nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
        return out[:, 0]

    assert H % KV == 0, (
        f"H={H} not divisible by KV={KV}: pass qh2kv for padded GQA")
    g = H // KV
    qg = q.reshape(B, 1, KV, g, D)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(valid[:, None, None, None, :], logits, NEG_INF)
    probs = nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)
    return out.reshape(B, 1, H, D)[:, 0]


def paged_attention_split_ref(q, k_pages, v_pages, block_table, seq_lens,
                              *, n_model: int = 1, n_data: int = 1,
                              window: int = 0):
    """Mesh-free oracle of the SHARDED decomposition: split the KV heads
    into ``n_model`` contiguous stripes and the batch rows into ``n_data``
    banks, run ``paged_attention_ref`` on every (bank, stripe) piece
    independently, and recombine by concatenation — exactly what the
    shard_map entry does per device, minus the mesh. Bitwise equality
    with the plain oracle is the shard-invariance property the device
    suite re-checks on real virtual-device meshes; this version runs in
    the default single-device test lane. Grouped GQA only (the sharded
    path's boundary): H % KV == 0 and KV % n_model == 0."""
    B, H, D = q.shape
    KV = k_pages.shape[2]
    assert H % KV == 0 and KV % n_model == 0 and B % n_data == 0, \
        (H, KV, n_model, B, n_data)
    kv_loc, g = KV // n_model, H // KV
    rows = B // n_data
    outs = []
    for b in range(n_data):
        r = slice(b * rows, (b + 1) * rows)
        shards = []
        for mi in range(n_model):
            h = slice(mi * kv_loc, (mi + 1) * kv_loc)
            qh = slice(mi * kv_loc * g, (mi + 1) * kv_loc * g)
            shards.append(paged_attention_ref(
                q[r, qh], k_pages[:, :, h], v_pages[:, :, h],
                block_table[r], seq_lens[r], window=window))
        outs.append(jnp.concatenate(shards, axis=1))
    return jnp.concatenate(outs, axis=0)


def paged_attention_layers_ref(qs, k_pages, v_pages, block_table, seq_lens,
                               *, qh2kv=None, window: int = 0):
    """Batched-over-layers oracle: qs (L, B, H, D) against the stacked
    (L, P, page, KV, D) page store; one block table / seq_lens shared by
    every layer. Returns (L, B, H, D)."""
    import jax
    return jax.vmap(
        lambda q, kp, vp: paged_attention_ref(
            q, kp, vp, block_table, seq_lens, qh2kv=qh2kv, window=window)
    )(qs, k_pages, v_pages)
