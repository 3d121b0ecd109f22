"""Chunked SSD (state-space duality) scan — Pallas TPU kernel.

Mamba2's mixer (arXiv:2405.21060) for the SSM / hybrid architectures.
The SSD form splits the sequence into chunks: within a chunk the output is
a masked "attention" (C Bᵀ ∘ L) — dense matmuls that feed the MXU — and
across chunks a tiny (P × N) recurrent state carries over, so the scan is
sequential only at chunk granularity.

Grid (batch, heads, chunks): chunks is the innermost, sequential
dimension; the running state h (P × N fp32) lives in VMEM scratch and
persists across the chunk steps of one (b, h) pair. Per-step working set:
x (l×P), B/C (l×N), the l×l decay mask, and h — ≈ 600 KiB at l = 256,
P = 64, N = 128; MXU-aligned contractions throughout.

B/C are single-group (shared across heads, the Mamba2 default), so their
BlockSpec ignores the head index — no per-head replication in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, A_ref, B_ref, C_ref, h0_ref,
                y_ref, hT_ref, h_scr, *, n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = h0_ref[0, 0]                    # (P, N) fp32

    x = x_ref[0, 0].astype(jnp.float32)              # (l, P)
    dt = dt_ref[0, 0].astype(jnp.float32)            # (1, l)  (see ops)
    A = A_ref[0]                                     # scalar decay rate
    Bm = B_ref[0].astype(jnp.float32)                # (l, N)
    Cm = C_ref[0].astype(jnp.float32)                # (l, N)
    l = x.shape[0]

    xdt = x * dt[0][:, None]                         # (l, P)
    dA = dt[0] * A                                   # (l,)
    cum = jnp.cumsum(dA)                             # (l,)

    # intra-chunk: Y_diag = ((C Bᵀ) ∘ L) (x·dt), L = exp(segsum(dA)) lower-tri
    seg = cum[:, None] - cum[None, :]                # (l, l)
    ii = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    Lmask = jnp.where(ii >= jj, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(scores * Lmask, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the incoming state, decayed per position
    h_prev = h_scr[...]                              # (P, N)
    state_decay = jnp.exp(cum)                       # (l,)
    y = y + jax.lax.dot_general(
        Cm * state_decay[:, None], h_prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: h = h_prev * exp(cum[-1]) + (x·dt)ᵀ (B · decay_to_end)
    decay_states = jnp.exp(cum[-1] - cum)            # (l,)
    chunk_state = jax.lax.dot_general(
        xdt, Bm * decay_states[:, None], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (P, N)
    h_scr[...] = h_prev * jnp.exp(cum[-1]) + chunk_state

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _emit_state():
        hT_ref[0, 0] = h_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, h0=None, *, chunk: int = 256,
             interpret: bool = False):
    """x: (b, s, h, p); dt: (b, s, h); A: (h,); B, C: (b, s, n) single-group.
    s % chunk == 0. Returns (y (b, s, h, p) fp32, state (b, h, p, n) fp32)."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0
    c = s // chunk
    if h0 is None:
        h0 = jnp.zeros((b, h, p, n), jnp.float32)

    # layouts the kernel wants: head-major sequence blocks
    x_t = x.transpose(0, 2, 1, 3)                    # (b, h, s, p)
    dt_t = dt.transpose(0, 2, 1)[:, :, None, :]      # (b, h, 1, s)

    kernel = functools.partial(_ssd_kernel, n_chunks=c)
    y, hT = pl.pallas_call(
        kernel,
        grid=(b, h, c),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda ib, ih, ic: (ib, ih, 0, ic)),
            pl.BlockSpec((1,), lambda ib, ih, ic: (ih,)),
            pl.BlockSpec((1, chunk, n), lambda ib, ih, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, n), lambda ib, ih, ic: (ib, ic, 0)),
            pl.BlockSpec((1, 1, p, n), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, p, n), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), jnp.float32),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_t, dt_t, A.astype(jnp.float32), B, C, h0)
    return y.transpose(0, 2, 1, 3), hT
