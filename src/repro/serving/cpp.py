"""Chunked Pipeline Parallelism (CPP) for long-context prefill (§5.1).

The paper's argument: extending TP across nodes costs two RDMA all-reduces
per layer; sequence parallelism (Ring Attention) still communicates every
layer. CPP instead groups X nodes into a *pipelined prefill group*: the
request's input is cut into ``prefill_chunk``-token chunks and chunk i can
run on stage s while chunk i+1 runs on stage s-1 — cross-node traffic only
at stage boundaries (one activation tensor per chunk), easily overlapped.

Why it works for prefill: by autoregressivity, chunk i only attends to
tokens of chunks ≤ i. Each pipeline stage owns a contiguous slice of
layers and accumulates its slice's KV for the chunks it has already
processed — so when chunk i arrives, all the KV it needs (for this
stage's layers) is already resident. KV also ends up *sharded by layer
across stages*, which is exactly the layout layer-wise streaming (§5.2)
wants for store-back.

TPU adaptation (DESIGN.md §3): stage handoff = ``jax.lax.ppermute`` over a
``stage`` mesh axis inside ``shard_map``; the ICI torus plays the role of
the RDMA fabric. The schedule is the classic (C + X − 1)-microstep GPipe
wavefront, expressed as ``lax.scan`` with masked bubbles so the lowered
HLO has one stage body.

Supports the uniform dense stack (the paper's dummy LLaMA2-70B is dense);
MoE/hybrid prefill uses the batch-sharded path instead.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import (DTYPE, NO_DIST, attention_block, mlp_block,
                                 rms_norm)
from repro.models.transformer import _embed, _logits_at


def _stage_body(x, p_stack, cfg: ModelConfig, kv_bufs, offset):
    """Run this stage's layer slice on one chunk.

    x: (B, C, D) chunk activations; p_stack: params with leading L_s;
    kv_bufs: (L_s, B, S, KV, Dh) ×2 this stage's accumulated KV;
    offset: scalar — absolute token position of the chunk start.
    Returns (y, updated kv_bufs).
    """
    k_buf, v_buf = kv_bufs

    def layer(carry, xs):
        h = carry
        p, kc, vc = xs
        y, (kc2, vc2) = attention_block(
            h, p["attn"], cfg, NO_DIST, cache=(kc, vc), cache_len=offset)
        h = h + y
        h = h + mlp_block(h, p["mlp"], cfg)
        return h, (kc2, vc2)

    h, (k2, v2) = jax.lax.scan(
        layer, x, ({"attn": p_stack["attn"], "mlp": p_stack["mlp"]},
                   k_buf, v_buf))
    return h, (k2, v2)


def cpp_prefill(params, tokens, cfg: ModelConfig, mesh: Mesh, *,
                stage_axis: str = "stage", prefill_chunk: int = 1024):
    """Pipelined prefill of ONE long request across ``X = mesh[stage_axis]``
    stages. tokens: (B, S) with S % prefill_chunk == 0.

    Returns last-position logits (B, V). Parameters must be stacked
    (n_layers, ...) with n_layers % X == 0; they are consumed sharded on
    the stage axis (each stage holds L/X layers).
    """
    X = mesh.shape[stage_axis]
    B, S = tokens.shape
    assert S % prefill_chunk == 0 and cfg.n_layers % X == 0
    C = S // prefill_chunk
    L_s = cfg.n_layers // X
    KV, Dh, D = cfg.n_kv_heads, cfg.head_dim, cfg.d_model

    x_emb = _embed(params, tokens, cfg)          # (B, S, D)
    chunks = x_emb.reshape(B, C, prefill_chunk, D).transpose(1, 0, 2, 3)

    stage_params = {"attn": params["attn"], "mlp": params["mlp"]}

    def pipeline(chunks_l, p_l):
        """Inside shard_map: one device = one stage. chunks_l is replicated
        (every stage sees the embedded input; only stage 0 consumes it).
        p_l: this stage's (L_s, ...) params."""
        sid = jax.lax.axis_index(stage_axis)
        k_buf = jnp.zeros((L_s, B, S, KV, Dh), DTYPE)
        v_buf = jnp.zeros((L_s, B, S, KV, Dh), DTYPE)
        zero = jnp.zeros((B, prefill_chunk, D), DTYPE)

        def microstep(carry, t):
            k_buf, v_buf, boundary = carry
            # stage 0 takes chunk t from the input; others take the
            # boundary activation handed over by the previous stage
            chunk_in = jnp.where(
                (t < C), jax.lax.dynamic_index_in_dim(
                    chunks_l, jnp.clip(t, 0, C - 1), keepdims=False), zero)
            x = jnp.where(sid == 0, chunk_in, boundary)
            my_chunk = t - sid                     # which chunk this stage sees
            valid = (my_chunk >= 0) & (my_chunk < C)
            offset = jnp.clip(my_chunk, 0, C - 1) * prefill_chunk

            y, (k2, v2) = _stage_body(
                x.astype(DTYPE), p_l, cfg, (k_buf, v_buf), offset)
            # only commit KV/output on valid microsteps (bubbles are masked)
            k_buf = jnp.where(valid, k2, k_buf)
            v_buf = jnp.where(valid, v2, v_buf)
            y = jnp.where(valid, y, zero)
            # hand the processed chunk to the next stage
            boundary = jax.lax.ppermute(
                y, stage_axis, [(i, (i + 1) % X) for i in range(X)])
            # emit the LAST stage's output chunk (post all layers)
            out = jnp.where(sid == X - 1, y, zero)
            return (k_buf, v_buf, boundary), out

        (k_buf, v_buf, _), outs = jax.lax.scan(
            microstep, (k_buf, v_buf, zero), jnp.arange(C + X - 1))
        # outs: (C+X-1, B, chunk, D); chunk c completed at microstep c+X-1.
        h_last = outs[-1]                          # final chunk's activations
        # broadcast the final hidden state from the last stage to all
        h_last = jax.lax.psum(
            jnp.where(sid == X - 1, h_last, jnp.zeros_like(h_last)),
            stage_axis)
        return h_last, (k_buf, v_buf)

    fn = jax.shard_map(
        pipeline, mesh=mesh,
        in_specs=(P(), P(stage_axis)),
        out_specs=(P(), P(stage_axis)),
        check_vma=False)
    h_last, kv = fn(chunks, stage_params)
    h_last = rms_norm(h_last, params["final_ln"], cfg.norm_eps)
    logits = _logits_at(params, h_last[:, -1:, :], cfg)[:, 0]
    return logits, kv


def cpp_reference(params, tokens, cfg: ModelConfig):
    """Single-device oracle: plain full prefill (same math, no pipeline)."""
    from repro.models.transformer import prefill
    logits, caches = prefill(params, tokens, cfg)
    return logits, (caches.kv.k, caches.kv.v)
