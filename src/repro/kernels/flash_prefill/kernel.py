"""Flash attention for chunked prefill — Pallas TPU kernel.

The serving hot spot of §3 step 2 (incremental prefill): a query chunk of
``Sq`` tokens starting at absolute offset ``q_offset`` attends a full
``Sk``-token K/V (cached prefix + itself). Online-softmax accumulation
over K blocks; GQA resolved in the kernel's head loop (each KV head's
K/V tile serves its ``group`` query heads — no materialised head
expansion).

Tiling: grid (B, nq, nk) with the K loop as the innermost sequential
dimension; VMEM scratch (acc, m, l) persists across the nk steps of one
(b, iq) tile. One grid step takes a (BQ, H, D) query tile and (BK, KV, D)
K/V tiles — every head at once — and loops over the heads statically, as
the paged decode kernel does: Mosaic tiles the last two block dimensions
by (8, 128) unless they equal the array's, so the blocks span the full
(heads, D) trailing axes and no layout change is needed around the call.
Block sizes default to the MXU-native 128×128; the working set per step at
H = 16, D = 128 is q(BQ·H·D) + k,v(2·BK·KV·D) + acc(H·BQ·D fp32) ≈ 2 MiB,
inside the ~16 MiB VMEM budget with room for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, q_offset: int, window: int,
                  bq: int, bk: int, nk: int, kv_heads: int, group: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    full = slice(None)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    qpos = q_offset + iq * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = qpos >= kpos
    if window:
        mask &= (qpos - kpos) < window

    for g in range(kv_heads):
        k = k_ref[0, full, g, full].astype(jnp.float32)      # (BK, D)
        v = v_ref[0, full, g, full].astype(jnp.float32)
        for h in range(g * group, (g + 1) * group):
            q = q_ref[0, full, h, full].astype(jnp.float32)  # (BQ, D)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_ref[h]                                # (BQ, 1)
            m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
            l_ref[h] = l_ref[h] * alpha + p.sum(axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_cur

    @pl.when(ik == nk - 1)
    def _finalize():
        d = acc_ref.shape[-1]
        for h in range(kv_heads * group):
            den = jnp.maximum(l_ref[h], 1e-30)
            o_ref[0, full, h * d:(h + 1) * d] = (
                acc_ref[h] / den).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("q_offset", "window", "bq",
                                             "bk", "interpret"))
def flash_prefill(q, k, v, *, q_offset: int = 0, window: int = 0,
                  bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                  interpret: bool = False):
    """q: (B, Sq, H, D); k, v: (B, Sk, KV, D). Sq % bq == Sk % bk == 0."""
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    group = H // KV
    nq, nk = Sq // bq, Sk // bk

    kernel = functools.partial(
        _flash_kernel, scale=1.0 / (D ** 0.5), q_offset=q_offset,
        window=window, bq=bq, bk=bk, nk=nk, kv_heads=KV, group=group)

    q_spec = pl.BlockSpec((1, bq, H, D), lambda b, iq, ik: (b, iq, 0, 0))
    kv_spec = pl.BlockSpec((1, bk, KV, D), lambda b, iq, ik: (b, ik, 0, 0))
    # the output is written head by head into lane slices of (Sq, H·D): a
    # free reshape of (Sq, H, D), where Mosaic cannot store one head's
    # (BQ, D) tile across the head axis at D < 128
    out = pl.pallas_call(
        kernel,
        grid=(B, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=pl.BlockSpec((1, bq, H * D),
                               lambda b, iq, ik: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Sq, H * D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((H, bq, D), jnp.float32),   # acc
            pltpu.VMEM((H, bq, 1), jnp.float32),   # running max m
            pltpu.VMEM((H, bq, 1), jnp.float32),   # running sum l
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out.reshape(q.shape)
