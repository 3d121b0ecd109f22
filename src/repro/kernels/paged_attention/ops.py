"""Public op: paged decode attention (kernel or oracle dispatch).

``use_pallas`` is the caller's choice and is honoured or refused, never
quietly swapped: a case the kernel cannot take raises, and ``interpret``
(the Pallas interpreter, for CPU validation) is only ever what the caller
passes."""
from __future__ import annotations

import jax

from repro.kernels.paged_attention.kernel import (
    paged_attention as _kernel, paged_attention_layers as _kernel_layers)
from repro.kernels.paged_attention.ref import (
    paged_attention_layers_ref as _ref_layers, paged_attention_ref as _ref)


def _check_kernel_case(q_heads: int, kv_heads: int, qh2kv,
                       window: int) -> None:
    """The Pallas grid packs grouped GQA only: divisible heads, no padded
    query-head remap, full attention. Anything else is the oracle's, and
    asking the kernel for it is an error."""
    if qh2kv is not None or window or q_heads % kv_heads:
        raise ValueError(
            f"the Pallas paged kernel takes grouped GQA with full attention "
            f"only (H={q_heads}, KV={kv_heads}, padded head map="
            f"{qh2kv is not None}, window={window}); pass use_pallas=False")


def paged_decode_attention(q, k_pages, v_pages, block_table, seq_lens, *,
                           qh2kv=None, window: int = 0,
                           use_pallas: bool = False,
                           interpret: bool = False):
    """q: (B, H, D) over one layer's paged KV → (B, H, D)."""
    if not use_pallas:
        return _ref(q, k_pages, v_pages, block_table, seq_lens,
                    qh2kv=qh2kv, window=window)
    _check_kernel_case(q.shape[1], k_pages.shape[2], qh2kv, window)
    return _kernel(q, k_pages, v_pages, block_table, seq_lens,
                   interpret=interpret)


def paged_decode_attention_sharded(q, k_pages, v_pages, block_table,
                                   seq_lens, *, mesh, window: int = 0,
                                   use_pallas: bool = False,
                                   interpret: bool = False):
    """Mesh entry: one layer's paged decode attention shard_mapped over a
    (data, model) mesh — batch rows over 'data', KV-head stripes (and the
    grouped query heads that attend them) over 'model'. The inner loop is
    collective-free (attention is head-local); outputs reassemble to the
    global (B, H, D) by construction of the out_specs, so the result is
    bitwise ``paged_decode_attention`` on the unsharded arrays. Grouped
    GQA only. At this kernel-level entry the page store is replicated
    across the data axis (one shared bank, global page ids); the engine's
    ``DevicePagePool(mesh=…)`` additionally banks pages per data shard
    and hands the step bank-local tables."""
    from jax.sharding import PartitionSpec as P
    m = int(mesh.shape.get("model", 1))
    H, KV = q.shape[1], k_pages.shape[2]
    assert H % KV == 0 and KV % m == 0, \
        f"sharded paged attention is grouped-GQA only (H={H}, KV={KV}, m={m})"

    def local(q, kp, vp, tbl, lens):
        return paged_decode_attention(q, kp, vp, tbl, lens, window=window,
                                      use_pallas=use_pallas,
                                      interpret=interpret)

    f = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("data", "model", None), P(None, None, "model", None),
                  P(None, None, "model", None), P("data", None), P("data")),
        out_specs=P("data", "model", None), check_vma=False)
    return f(q, k_pages, v_pages, block_table, seq_lens)


def paged_decode_attention_layers(qs, k_pages, v_pages, block_table,
                                  seq_lens, *, qh2kv=None, window: int = 0,
                                  use_pallas: bool = False,
                                  interpret: bool = False):
    """Batched-over-layers variant: qs (L, B, H, D) over the stacked
    (L, P, page, KV, D) store → (L, B, H, D). One kernel launch covers
    every layer (microbench / layer-parallel callers)."""
    if not use_pallas:
        return _ref_layers(qs, k_pages, v_pages, block_table, seq_lens,
                           qh2kv=qh2kv, window=window)
    _check_kernel_case(qs.shape[2], k_pages.shape[3], qh2kv, window)
    return _kernel_layers(qs, k_pages, v_pages, block_table, seq_lens,
                          interpret=interpret)
