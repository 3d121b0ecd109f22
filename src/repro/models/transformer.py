"""Model assembly for all six architecture kinds.

Layers are SCANNED: per-layer parameters are stacked on a leading axis and
the decoder runs ``jax.lax.scan`` over it, so the lowered HLO contains one
layer body regardless of depth (94-layer models compile on this 1-core CPU
container at 512 placeholder devices).

Three forward modes share the same block code:
  * train    — no caches; returns hidden states for the chunked-CE loss.
  * prefill  — returns per-layer KV (stacked) / final SSM state; logits of
               the last position only (the "first generated token").
  * decode   — one (or a few, for chunked-prefill extension) tokens against
               preallocated caches updated in place (functionally).

Hybrid (jamba) runs a PERIOD scan: one attention layer + (attn_every-1)
Mamba layers per period, FFN alternating dense/MoE inside the period.
Whisper adds a (scanned) bidirectional encoder and per-layer cross-attention
whose K/V are computed once at prefill ("enc_kv" cache).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (DTYPE, Dist, NO_DIST, attention_block,
                                 expert_share_block, mla_attention_block,
                                 mlp_block, moe_block, rms_norm)
from repro.models.mamba import MambaState, mamba_block

# Sequence-chunk length for the chunked cross-entropy (bounds the logits
# buffer to (B, CE_CHUNK, V) instead of (B, S, V)).
CE_CHUNK = 512


class KVCache(NamedTuple):
    """Stacked attention KV: k, v are (L_attn, B, S, KV, Dh)."""
    k: jax.Array
    v: jax.Array


class Caches(NamedTuple):
    kv: Optional[KVCache]          # self-attention KV (None for pure SSM)
    ssm: Optional[MambaState]      # stacked (L_ssm, ...) (None if no SSM)
    enc_kv: Optional[KVCache]      # whisper cross-attn KV (L, B, S_enc, KV, Dh)
    length: jax.Array              # int32 scalar: tokens written so far


# ---------------------------------------------------------------------------
# parameter initialisation (stacked)
# ---------------------------------------------------------------------------

def _norm(key, shape, scale):
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(DTYPE)


def _init_attn(key, cfg: ModelConfig, L: int, cross: bool = False) -> dict:
    D, Hp, KV, Dh = cfg.d_model, cfg.padded_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    s_in = (2.0 / (D + Hp * Dh)) ** 0.5
    wq = _norm(ks[0], (L, D, Hp * Dh), s_in)
    # zero the padded query heads' projections so they contribute nothing
    if Hp != cfg.n_heads:
        m = jnp.repeat(jnp.arange(Hp) < cfg.n_heads, Dh)
        wq = wq * m[None, None, :].astype(DTYPE)
    p = dict(
        ln=jnp.ones((L, D), DTYPE),
        wq=wq,
        wk=_norm(ks[1], (L, D, KV * Dh), s_in),
        wv=_norm(ks[2], (L, D, KV * Dh), s_in),
        wo=_norm(ks[3], (L, Hp * Dh, D), s_in),
    )
    if cfg.attn_bias and not cross:
        p["bq"] = jnp.zeros((L, Hp * Dh), DTYPE)
        p["bk"] = jnp.zeros((L, KV * Dh), DTYPE)
        p["bv"] = jnp.zeros((L, KV * Dh), DTYPE)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((L, Dh), DTYPE)
        p["k_norm"] = jnp.ones((L, Dh), DTYPE)
    return p


def _init_mlp(key, cfg: ModelConfig, L: int) -> dict:
    D, F = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    s = (2.0 / (D + F)) ** 0.5
    return dict(
        ln=jnp.ones((L, D), DTYPE),
        w1=_norm(ks[0], (L, D, F), s),
        w2=_norm(ks[1], (L, F, D), s),
        w3=_norm(ks[2], (L, D, F), s),
    )


def _init_moe(key, cfg: ModelConfig, L: int) -> dict:
    D, E, F = cfg.d_model, cfg.moe.n_experts, cfg.moe.d_ff_expert
    ks = jax.random.split(key, 4)
    s = (2.0 / (D + F)) ** 0.5
    return dict(
        ln=jnp.ones((L, D), DTYPE),
        router=_norm(ks[0], (L, D, E), D ** -0.5),
        w1=_norm(ks[1], (L, E, D, F), s),
        w2=_norm(ks[2], (L, E, F, D), s),
        w3=_norm(ks[3], (L, E, D, F), s),
    )


def _init_mla(key, cfg: ModelConfig, L: int) -> dict:
    D, H, m = cfg.d_model, cfg.n_heads, cfg.mla
    ks = jax.random.split(key, 4)
    s_in = (2.0 / (D + H * m.qk_head_dim)) ** 0.5
    return dict(
        ln=jnp.ones((L, D), DTYPE),
        wq=_norm(ks[0], (L, D, H * m.qk_head_dim), s_in),
        wkv_a=_norm(ks[1], (L, D, m.kv_lora_rank + m.qk_rope_head_dim),
                    s_in),
        kv_ln=jnp.ones((L, m.kv_lora_rank), DTYPE),
        wkv_b=_norm(ks[2], (L, m.kv_lora_rank,
                            H * (m.qk_nope_head_dim + m.v_head_dim)),
                    m.kv_lora_rank ** -0.5),
        wo=_norm(ks[3], (L, H * m.v_head_dim, D), s_in),
    )


def _init_expert_share(key, cfg: ModelConfig, L: int) -> dict:
    """The held experts, the full-width router and its selection bias,
    and the shared expert (``expert_share_block``)."""
    D, moe = cfg.d_model, cfg.moe
    E, F, Fs = moe.held, moe.d_ff_expert, moe.d_ff_shared
    ks = jax.random.split(key, 7)
    s, ss = (2.0 / (D + F)) ** 0.5, (2.0 / (D + Fs)) ** 0.5
    return dict(
        ln=jnp.ones((L, D), DTYPE),
        router=_norm(ks[0], (L, D, moe.n_experts), D ** -0.5),
        bias=jnp.zeros((L, moe.n_experts), jnp.float32),
        w1=_norm(ks[1], (L, E, D, F), s),
        w2=_norm(ks[2], (L, E, F, D), s),
        w3=_norm(ks[3], (L, E, D, F), s),
        shared=dict(w1=_norm(ks[4], (L, D, Fs), ss),
                    w2=_norm(ks[5], (L, Fs, D), ss),
                    w3=_norm(ks[6], (L, D, Fs), ss)),
    )


def _init_mamba(key, cfg: ModelConfig, L: int) -> dict:
    s_cfg = cfg.ssm
    D = cfg.d_model
    di = s_cfg.d_inner(D)
    nh = s_cfg.n_heads(D)
    n = s_cfg.d_state
    conv_ch = di + 2 * s_cfg.n_groups * n
    ks = jax.random.split(key, 4)
    proj_out = 2 * di + 2 * s_cfg.n_groups * n + nh
    return dict(
        ln=jnp.ones((L, D), DTYPE),
        in_proj=_norm(ks[0], (L, D, proj_out), (2.0 / (D + proj_out)) ** 0.5),
        conv_w=_norm(ks[1], (L, s_cfg.d_conv, conv_ch), conv_ch ** -0.5),
        dt_bias=jnp.zeros((L, nh), jnp.float32),
        A_log=jnp.zeros((L, nh), jnp.float32),  # A = -exp(0) = -1
        D=jnp.ones((L, nh), jnp.float32),
        norm=jnp.ones((L, di), DTYPE),
        out_proj=_norm(ks[2], (L, di, D), (2.0 / (di + D)) ** 0.5),
    )


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    """Stacked parameter pytree for the full model."""
    keys = jax.random.split(key, 10)
    D, V = cfg.d_model, cfg.padded_vocab
    L = cfg.n_layers
    params: dict = {
        "embed": _norm(keys[0], (V, D), D ** -0.5),
        "final_ln": jnp.ones((D,), DTYPE),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _norm(keys[1], (D, V), D ** -0.5)

    if cfg.mla is not None:
        # leading dense layers, then a uniform stack of expert layers
        n0 = cfg.moe.first_dense
        params["dense"] = dict(attn=_init_mla(keys[5], cfg, n0),
                               mlp=_init_mlp(keys[6], cfg, n0))
        params["attn"] = _init_mla(keys[2], cfg, L - n0)
        params["moe"] = _init_expert_share(keys[4], cfg, L - n0)
    elif cfg.kind == "ssm":
        params["mamba"] = _init_mamba(keys[2], cfg, L)
    elif cfg.attn_every:  # hybrid (jamba): period scan stacks
        n_per = L // cfg.attn_every
        inner = cfg.attn_every - 1  # mamba layers per period
        params["attn"] = _init_attn(keys[2], cfg, n_per)
        params["mamba"] = jax.tree.map(
            lambda x: x.reshape((n_per, inner) + x.shape[1:]),
            _init_mamba(keys[3], cfg, n_per * inner))
        # FFN after every mixer: alternate dense (even pos) / MoE (odd pos)
        n_moe = cfg.attn_every // cfg.moe.every
        n_dense = cfg.attn_every - n_moe
        params["ffn_dense"] = jax.tree.map(
            lambda x: x.reshape((n_per, n_dense) + x.shape[1:]),
            _init_mlp(keys[4], cfg, n_per * n_dense))
        params["ffn_moe"] = jax.tree.map(
            lambda x: x.reshape((n_per, n_moe) + x.shape[1:]),
            _init_moe(keys[5], cfg, n_per * n_moe))
    else:
        params["attn"] = _init_attn(keys[2], cfg, L)
        if cfg.moe is not None and cfg.moe.every == 1:
            params["moe"] = _init_moe(keys[4], cfg, L)
        else:
            params["mlp"] = _init_mlp(keys[4], cfg, L)

    if cfg.encoder_layers:
        Le = cfg.encoder_layers
        params["enc_attn"] = _init_attn(keys[6], cfg, Le)
        params["enc_mlp"] = _init_mlp(keys[7], cfg, Le)
        params["enc_final_ln"] = jnp.ones((D,), DTYPE)
        params["cross_attn"] = _init_attn(keys[8], cfg, L, cross=True)
    return params


def param_count_exact(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# cache allocation
# ---------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, max_len: int, *,
                enc_len: int = 0, window: int = 0) -> Caches:
    """Preallocated decode caches. ``window`` > 0 bounds the attention cache
    to a ring buffer of that many slots (sliding-window serving)."""
    KV, Dh = cfg.n_kv_heads, cfg.head_dim
    eff_window = window or cfg.sliding_window
    S = min(max_len, eff_window) if eff_window else max_len
    kv = None
    if cfg.attention_layers:
        La = cfg.attention_layers
        wk, wv = cfg.kv_widths
        kv = KVCache(k=jnp.zeros((La, batch, S) + wk, DTYPE),
                     v=jnp.zeros((La, batch, S) + wv, DTYPE))
    ssm = None
    if cfg.ssm is not None and cfg.kind in ("ssm", "hybrid"):
        s_cfg = cfg.ssm
        L_ssm = cfg.n_layers - cfg.attention_layers if cfg.attn_every \
            else cfg.n_layers
        nh = s_cfg.n_heads(cfg.d_model)
        conv_ch = s_cfg.d_inner(cfg.d_model) + 2 * s_cfg.n_groups * s_cfg.d_state
        if cfg.attn_every:
            n_per = cfg.n_layers // cfg.attn_every
            inner = cfg.attn_every - 1
            shape_ssm = (n_per, inner, batch, nh, s_cfg.head_dim, s_cfg.d_state)
            shape_conv = (n_per, inner, batch, s_cfg.d_conv - 1, conv_ch)
        else:
            shape_ssm = (L_ssm, batch, nh, s_cfg.head_dim, s_cfg.d_state)
            shape_conv = (L_ssm, batch, s_cfg.d_conv - 1, conv_ch)
        ssm = MambaState(ssm=jnp.zeros(shape_ssm, jnp.float32),
                         conv=jnp.zeros(shape_conv, DTYPE))
    enc_kv = None
    if cfg.encoder_layers and enc_len:
        enc_kv = KVCache(
            k=jnp.zeros((cfg.n_layers, batch, enc_len, KV, Dh), DTYPE),
            v=jnp.zeros((cfg.n_layers, batch, enc_len, KV, Dh), DTYPE))
    return Caches(kv=kv, ssm=ssm, enc_kv=enc_kv,
                  length=jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# encoder (whisper) — bidirectional, scanned
# ---------------------------------------------------------------------------

def encode(params, frames, cfg: ModelConfig, dist: Dist):
    """frames: (B, F, D) stub embeddings -> (B, F, D) encoder output."""
    x = frames.astype(DTYPE)

    def block(x, p):
        pa, pm = p
        y, _ = attention_block(x, pa, cfg, dist, causal=False)
        x = x + y
        x = x + mlp_block(x, pm, cfg)
        x = dist.constrain(x, dist.residual_spec(x.shape[1]))
        return x, None

    fn = block
    if cfg.remat:
        fn = jax.checkpoint(block)
    x, _ = jax.lax.scan(fn, x, (params["enc_attn"], params["enc_mlp"]))
    return rms_norm(x, params["enc_final_ln"], cfg.norm_eps)


def build_enc_kv(params, enc_out, cfg: ModelConfig) -> KVCache:
    """Per-decoder-layer cross-attention K/V from the encoder output."""
    B, F, D = enc_out.shape
    KV, Dh = cfg.n_kv_heads, cfg.head_dim

    def one(p):
        k = (enc_out @ p["wk"]).reshape(B, F, KV, Dh)
        v = (enc_out @ p["wv"]).reshape(B, F, KV, Dh)
        return k, v

    k, v = jax.vmap(one)(params["cross_attn"])
    return KVCache(k=k, v=v)


# ---------------------------------------------------------------------------
# decoder stacks
# ---------------------------------------------------------------------------

def _ffn(x, p_mlp, p_moe, cfg, dist, use_moe: bool):
    if use_moe:
        y, aux = moe_block(x, p_moe, cfg, dist)
        return y, aux
    return mlp_block(x, p_mlp, cfg), 0.0


def _uniform_stack(params, x, cfg: ModelConfig, dist: Dist, *, mode: str,
                   caches: Optional[Caches], q_offset, ring: bool,
                   window_override, kv_out: bool):
    """dense / moe / vlm / audio-decoder / ssm stacks (one block per layer)."""
    use_moe = cfg.moe is not None and cfg.moe.every == 1 and cfg.kind != "ssm"
    is_ssm = cfg.kind == "ssm"
    cross = cfg.encoder_layers > 0

    if is_ssm:
        st_xs = caches.ssm if caches is not None else None
        want_state = mode != "train"

        def block_ssm(carry, xs_):
            x, aux = carry
            p_m, st = xs_ if st_xs is not None else (xs_, None)
            y, new_st = mamba_block(x, p_m, cfg, dist, state=st,
                                    return_state=want_state)
            x = x + y
            x = dist.constrain(x, dist.residual_spec(x.shape[1]))
            return (x, aux), (new_st if want_state else 0.0)

        xs = (params["mamba"], st_xs) if st_xs is not None else params["mamba"]
        fn = jax.checkpoint(block_ssm) if (cfg.remat and mode == "train") \
            else block_ssm
        (x, aux), new_states = jax.lax.scan(fn, (x, 0.0), xs)
        return x, aux, (new_states if want_state else None)

    p_f = params["moe"] if use_moe else params["mlp"]
    p_c = params["cross_attn"] if cross else _none_like_stack(cfg.n_layers)
    cache_xs = (caches.kv.k, caches.kv.v) if (mode == "decode" and caches is not None
                                              and caches.kv is not None) else None
    e_kv = (caches.enc_kv.k, caches.enc_kv.v) if (cross and caches is not None
                                                  and caches.enc_kv is not None) \
        else None

    # assemble scan xs — always pass placeholders so the structure is static
    L = cfg.n_layers
    dummy = jnp.zeros((L, 1), DTYPE)
    xs = (params["attn"], p_f,
          p_c if cross else dummy,
          cache_xs if cache_xs is not None else dummy,
          e_kv if e_kv is not None else dummy)

    def block2(carry, xs_):
        x, aux = carry
        p_a, p_fl, p_cl, cache_l, e_kv_l = xs_
        cache_pair = cache_l if cache_xs is not None else None
        ekv_pair = e_kv_l if e_kv is not None else None
        if mode == "train":
            y, kv = attention_block(x, p_a, cfg, dist, q_offset=q_offset,
                                    window_override=window_override)
        elif mode == "prefill":
            y, kv = attention_block(x, p_a, cfg, dist, q_offset=q_offset,
                                    kv_out=True, window_override=window_override)
        else:
            y, kv = attention_block(x, p_a, cfg, dist, cache=cache_pair,
                                    cache_len=caches.length, ring=ring,
                                    window_override=window_override)
        x = x + y
        if cross:
            yc, _ = attention_block(x, p_cl, cfg, dist, enc_kv=ekv_pair)
            x = x + yc
        y, a = _ffn(x, p_fl, p_fl, cfg, dist, use_moe)
        x = x + y
        x = dist.constrain(x, dist.residual_spec(x.shape[1]))
        return (x, aux + a), kv

    fn = jax.checkpoint(block2) if (cfg.remat and mode == "train") else block2
    (x, aux), kv_stack = jax.lax.scan(fn, (x, 0.0), xs)
    return x, aux, kv_stack


def _none_like_stack(L):
    return jnp.zeros((L, 1), DTYPE)


def _hybrid_stack(params, x, cfg: ModelConfig, dist: Dist, *, mode: str,
                  caches: Optional[Caches], q_offset, ring: bool,
                  window_override):
    """jamba period scan: [attn, mamba ×(attn_every-1)], FFN after each mixer
    alternating dense / MoE (MoE on odd in-period positions)."""
    period = cfg.attn_every
    inner = period - 1
    decode = mode == "decode"

    kv_xs = (caches.kv.k, caches.kv.v) if (decode and caches is not None) else None
    st_xs = caches.ssm if caches is not None and caches.ssm is not None else None

    def period_block(carry, xs):
        x, aux = carry
        p_a, p_m, p_fd, p_fm, kv_l, st_l = xs
        new_kv = None
        new_ssm_list, new_conv_list = [], []
        i_d = i_m = 0
        for pos in range(period):
            if pos == 0:  # attention mixer
                if mode == "train":
                    y, kv = attention_block(
                        x, p_a, cfg, dist, q_offset=q_offset,
                        window_override=window_override)
                elif mode == "prefill":
                    y, kv = attention_block(
                        x, p_a, cfg, dist, q_offset=q_offset, kv_out=True,
                        window_override=window_override)
                else:
                    y, kv = attention_block(
                        x, p_a, cfg, dist, cache=kv_l,
                        cache_len=caches.length, ring=ring,
                        window_override=window_override)
                new_kv = kv
            else:  # mamba mixer
                pm = jax.tree.map(lambda t, j=pos - 1: t[j], p_m)
                st = MambaState(ssm=st_l.ssm[pos - 1], conv=st_l.conv[pos - 1]) \
                    if st_xs is not None else None
                y, new_st = mamba_block(x, pm, cfg, dist, state=st,
                                        return_state=(mode != "train"))
                if new_st is not None:
                    new_ssm_list.append(new_st.ssm)
                    new_conv_list.append(new_st.conv)
            x = x + y
            # FFN: MoE every cfg.moe.every-th position (odd positions)
            if (pos % cfg.moe.every) == (cfg.moe.every - 1):
                pf = jax.tree.map(lambda t, j=i_m: t[j], p_fm)
                y, a = moe_block(x, pf, cfg, dist)
                aux = aux + a
                i_m += 1
            else:
                pf = jax.tree.map(lambda t, j=i_d: t[j], p_fd)
                y = mlp_block(x, pf, cfg)
                i_d += 1
            x = x + y
            x = dist.constrain(x, dist.residual_spec(x.shape[1]))
        new_st = MambaState(ssm=jnp.stack(new_ssm_list),
                            conv=jnp.stack(new_conv_list)) \
            if new_ssm_list else _none_like_stack(1)
        return (x, aux), (new_kv if new_kv is not None else _none_like_stack(1),
                          new_st)

    n_per = cfg.n_layers // period
    dummy = jnp.zeros((n_per, 1), DTYPE)
    xs = (params["attn"], params["mamba"], params["ffn_dense"],
          params["ffn_moe"],
          kv_xs if kv_xs is not None else dummy,
          st_xs if st_xs is not None else dummy)

    fn = jax.checkpoint(period_block) if (cfg.remat and mode == "train") \
        else period_block
    (x, aux), (kv_stack, st_stack) = jax.lax.scan(fn, (x, 0.0), xs)
    return x, aux, kv_stack, st_stack


# ---------------------------------------------------------------------------
# top-level forwards
# ---------------------------------------------------------------------------

def _embed(params, tokens, cfg):
    return jnp.take(params["embed"], tokens, axis=0)


def _unembed_chunked(params, h, labels, cfg: ModelConfig, dist: Dist):
    """Chunked cross-entropy: scan over CE_CHUNK-token slices so the
    (B, S, V) logits never materialise. labels -100 = masked."""
    B, S, D = h.shape
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    V = cfg.padded_vocab
    n = max(S // CE_CHUNK, 1)
    C = S // n
    hs = jnp.moveaxis(h[:, :n * C].reshape(B, n, C, D), 1, 0)
    ls = jnp.moveaxis(labels[:, :n * C].reshape(B, n, C), 1, 0)

    def body(acc, xs):
        hc, lc = xs
        logits = (hc @ w).astype(jnp.float32)  # (B, C, V)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.clip(lc, 0, V - 1)[..., None], axis=-1)[..., 0]
        mask = (lc >= 0).astype(jnp.float32)
        loss = jnp.sum((logz - gold) * mask)
        return (acc[0] + loss, acc[1] + jnp.sum(mask)), None

    (tot, cnt), _ = jax.lax.scan(body, (0.0, 0.0), (hs, ls))
    return tot / jnp.maximum(cnt, 1.0)


def _logits_at(params, h_last, cfg: ModelConfig):
    """h_last: (B, k, D) -> (B, k, V) logits (small k only)."""
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (h_last @ w).astype(jnp.float32)


def _mla_stack(params, x, cfg: ModelConfig, attend, kv):
    """The latent-attention model's layers: the leading dense layers, then
    one scan over the uniform expert layers. ``attend(x, p, layer, kv) ->
    (y, kv)`` is one layer's attention, told its index into the stacked
    caches ``kv``, which every layer threads (so a layer writes only its
    own new rows). Returns (x, kv, picked): picked (L - first_dense, T,
    held) bool, each token's routes to the held experts."""
    n0 = cfg.moe.first_dense
    for layer in range(n0):
        p = jax.tree.map(lambda t, i=layer: t[i], params["dense"])
        with jax.named_scope("mla"):
            y, kv = attend(x, p["attn"], layer, kv)
        x = x + y
        x = x + mlp_block(x, p["mlp"], cfg)

    def block(carry, xs_):
        x, kv = carry
        p_a, p_m, layer = xs_
        with jax.named_scope("mla"):
            y, kv = attend(x, p_a, layer, kv)
        x = x + y
        with jax.named_scope("moe"):
            y, picked = expert_share_block(x, p_m, cfg)
        return (x + y, kv), picked

    (x, kv), picked = jax.lax.scan(
        block, (x, kv),
        (params["attn"], params["moe"], jnp.arange(n0, cfg.n_layers)))
    return x, kv, picked


def _run_mla(params, x, cfg: ModelConfig, caches: Optional[Caches]):
    """``_run_stack`` for latent attention: extend ``caches`` (dense
    latent arenas) at their length, or, with none (prefill, train), fill
    a fresh arena of the sequence's length. The aux output counts the
    token-expert pairs routed to the held experts."""
    B, S, _ = x.shape
    if caches is None:
        caches = init_caches(cfg, B, S)
    pos = jnp.asarray(caches.length)
    positions = jnp.broadcast_to(
        (pos[:, None] if pos.ndim == 1 else pos)
        + jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))

    def attend(x, p, layer, kv):
        return mla_attention_block(x, p, cfg, positions=positions, cache=kv,
                                   layer=layer)

    x, kv, picked = _mla_stack(params, x, cfg, attend, tuple(caches.kv))
    return x, picked.sum(dtype=jnp.int32), (KVCache(*kv), None)


def _run_stack(params, x, cfg, dist, *, mode, caches, q_offset, ring,
               window_override):
    if cfg.mla is not None:
        if q_offset or ring or window_override or dist.active:
            raise ValueError("latent attention runs on one device from "
                             "position 0 or a cache's length, unwindowed")
        return _run_mla(params, x, cfg, caches)
    if cfg.attn_every:
        h, aux, kv_stack, st_stack = _hybrid_stack(
            params, x, cfg, dist, mode=mode, caches=caches, q_offset=q_offset,
            ring=ring, window_override=window_override)
        new_caches = None
        if mode != "train":
            kv = KVCache(k=kv_stack[0], v=kv_stack[1]) \
                if isinstance(kv_stack, tuple) else None
            ssm = st_stack if isinstance(st_stack, MambaState) else None
            new_caches = (kv, ssm)
        return h, aux, new_caches
    h, aux, out = _uniform_stack(
        params, x, cfg, dist, mode=mode, caches=caches, q_offset=q_offset,
        ring=ring, window_override=window_override, kv_out=(mode == "prefill"))
    new_caches = None
    if mode != "train":
        if cfg.kind == "ssm":
            new_caches = (None, out)
        else:
            kv = KVCache(k=out[0], v=out[1]) if isinstance(out, tuple) else None
            new_caches = (kv, None)
    return h, aux, new_caches


def loss_fn(params, batch: dict, cfg: ModelConfig, dist: Dist = NO_DIST):
    """Training loss. batch: tokens (B,S), labels (B,S) and optionally
    frames/patches (B,F,d_model) for audio/vlm frontends."""
    tokens = batch["tokens"]
    x = _embed(params, tokens, cfg)
    x = dist.constrain(x, dist.residual_spec(x.shape[1]))
    labels = batch["labels"]

    if cfg.encoder_layers:  # whisper: encode stub frames, cross-attend
        enc_out = encode(params, batch["frames"], cfg, dist)
        enc_kv = build_enc_kv(params, enc_out, cfg)
        caches = Caches(kv=None, ssm=None, enc_kv=enc_kv,
                        length=jnp.zeros((), jnp.int32))
        h, aux, _ = _run_stack(params, x, cfg, dist, mode="train",
                               caches=caches, q_offset=0, ring=False,
                               window_override=None)
    else:
        if cfg.frontend == "patch":  # vlm: prepend patch embeddings
            patches = batch["patches"].astype(DTYPE)
            x = jnp.concatenate([patches, x], axis=1)
            pad = jnp.full(patches.shape[:2], -1, labels.dtype)
            labels = jnp.concatenate([pad, labels], axis=1)
        h, aux, _ = _run_stack(params, x, cfg, dist, mode="train",
                               caches=None, q_offset=0, ring=False,
                               window_override=None)

    h = rms_norm(h, params["final_ln"], cfg.norm_eps)
    loss = _unembed_chunked(params, h, labels, cfg, dist)
    return loss + 0.01 * aux


def prefill(params, tokens, cfg: ModelConfig, dist: Dist = NO_DIST, *,
            frames=None, patches=None, q_offset=0,
            window_override=None):
    """Full-sequence prefill. Returns (last-token logits (B, V),
    Caches with exact-length KV / final SSM state)."""
    x = _embed(params, tokens, cfg)
    x = dist.constrain(x, dist.residual_spec(x.shape[1]))
    enc_kv = None
    caches_in = None
    if cfg.encoder_layers:
        enc_out = encode(params, frames, cfg, dist)
        enc_kv = build_enc_kv(params, enc_out, cfg)
        caches_in = Caches(kv=None, ssm=None, enc_kv=enc_kv,
                           length=jnp.zeros((), jnp.int32))
    elif cfg.frontend == "patch" and patches is not None:
        x = jnp.concatenate([patches.astype(DTYPE), x], axis=1)

    h, aux, out = _run_stack(params, x, cfg, dist, mode="prefill",
                             caches=caches_in, q_offset=q_offset, ring=False,
                             window_override=window_override)
    h = rms_norm(h, params["final_ln"], cfg.norm_eps)
    logits = _logits_at(params, h[:, -1:, :], cfg)[:, 0]
    kv, ssm = out
    S_new = x.shape[1]
    caches = Caches(kv=kv, ssm=ssm, enc_kv=enc_kv,
                    length=jnp.asarray(q_offset + S_new, jnp.int32))
    return logits, caches


def decode_step(params, tokens, caches: Caches, cfg: ModelConfig,
                dist: Dist = NO_DIST, *, ring=False, window_override=None,
                held_pairs: bool = False):
    """Decode (S small, usually 1) against preallocated caches.
    Returns (logits (B, S, V), updated caches), and with ``held_pairs``
    (latent-attention expert models) the token-expert pairs the step
    routed to the held experts as a third output."""
    x = _embed(params, tokens, cfg)
    x = dist.constrain(x, dist.residual_spec(x.shape[1]))
    h, aux, out = _run_stack(params, x, cfg, dist, mode="decode",
                             caches=caches, q_offset=None, ring=ring,
                             window_override=window_override)
    h = rms_norm(h, params["final_ln"], cfg.norm_eps)
    logits = _logits_at(params, h, cfg)
    kv, ssm = out
    new = Caches(kv=kv if kv is not None else caches.kv,
                 ssm=ssm if ssm is not None else caches.ssm,
                 enc_kv=caches.enc_kv,
                 length=caches.length + tokens.shape[1])
    if held_pairs:
        return logits, new, aux
    return logits, new


def decode_step_paged(params, tokens, k_pages, v_pages, block_table,
                      seq_lens, cfg: ModelConfig, dist: Dist = NO_DIST, *,
                      use_pallas: bool = False, window_override=None,
                      shard=None):
    """One continuous-batching decode iteration over the PAGED substrate.

    tokens: (B, 1); k_pages/v_pages: (L, P, page, KV·Dh) — the shared
    device page store, stacked on the layer axis and lane-dense; it rides
    the layer scan whole in the carry, with the layer index, so that each
    layer writes only its B new rows in place and gathers only the pages
    its table names (donate both slabs to the jitted step); block_table:
    (B, max_pages) int32 (0-padded with the null page); seq_lens: (B,)
    tokens already written per slot. Returns (logits (B, 1, V), k_pages,
    v_pages) — the block table and lengths are host-managed by the
    engine (growth, COW, slot free), not traced state.

    Supports uniform attention stacks only (the engine's serving archs);
    hybrid/SSM/encoder models keep the dense path. Latent attention goes
    to ``decode_step_paged_mla``, which returns a fourth output.
    """
    from repro.models.layers import paged_attention_block
    assert cfg.attention_layers == cfg.n_layers and not cfg.encoder_layers, \
        "paged decode supports uniform attention stacks"
    if cfg.mla is not None:
        if use_pallas or shard is not None or window_override:
            raise ValueError(
                "latent-attention paged decode runs the absorbed jnp form "
                "on one device: no Pallas kernel, shard or window")
        return decode_step_paged_mla(params, tokens, k_pages, v_pages,
                                     block_table, seq_lens, cfg)
    use_moe = cfg.moe is not None and cfg.moe.every == 1
    x = _embed(params, tokens, cfg)
    x = dist.constrain(x, dist.residual_spec(x.shape[1]))
    p_f = params["moe"] if use_moe else params["mlp"]

    def block(carry, xs_):
        x, aux, kp, vp = carry
        p_a, p_fl, layer = xs_
        y, (kp, vp) = paged_attention_block(
            x, p_a, cfg, dist, k_pages=kp, v_pages=vp, layer=layer,
            block_table=block_table, seq_lens=seq_lens,
            use_pallas=use_pallas, window_override=window_override,
            shard=shard)
        x = x + y
        y, a = _ffn(x, p_fl, p_fl, cfg, dist, use_moe)
        x = x + y
        x = dist.constrain(x, dist.residual_spec(x.shape[1]))
        return (x, aux + a, kp, vp), None

    (x, aux, k_pages, v_pages), _ = jax.lax.scan(
        block, (x, 0.0, k_pages, v_pages),
        (params["attn"], p_f, jnp.arange(cfg.n_layers)))
    h = rms_norm(x, params["final_ln"], cfg.norm_eps)
    logits = _logits_at(params, h, cfg)
    return logits, k_pages, v_pages


def decode_step_paged_mla(params, tokens, c_pages, pe_pages, block_table,
                          seq_lens, cfg: ModelConfig):
    """``decode_step_paged`` for latent attention: ``paged_mla_block``
    (absorbed) over the ``c_kv`` and ``k_pe`` slabs (L, P, page, rank) and
    (L, P, page, rope), which ride the layers whole so that each layer
    writes its B new rows in place (donate them to the jitted step).
    Returns (logits (B, 1, V), c_pages, pe_pages, touched): touched
    counts, over the expert layers, the held experts that got at least
    one token of an active slot (``seq_lens`` > 0)."""
    from repro.models.layers import paged_mla_block
    x = _embed(params, tokens, cfg)

    def attend(x, p, layer, kv):
        return paged_mla_block(x, p, cfg, c_pages=kv[0], pe_pages=kv[1],
                               layer=layer, block_table=block_table,
                               seq_lens=seq_lens)

    x, (c_pages, pe_pages), picked = _mla_stack(params, x, cfg, attend,
                                                (c_pages, pe_pages))
    active = (jnp.asarray(seq_lens) > 0)[None, :, None]
    touched = jnp.any(picked & active, axis=1).sum(dtype=jnp.int32)
    h = rms_norm(x, params["final_ln"], cfg.norm_eps)
    return _logits_at(params, h, cfg), c_pages, pe_pages, touched


def paged_shard_reason(cfg: ModelConfig, model_shards: int,
                       data_shards: int = 1) -> str:
    """Why the sharded paged decode step can NOT cover ``cfg`` on a
    (data, model) mesh — empty string when it can. KV heads stripe over
    the model axis only for grouped GQA (contiguous query-head groups per
    KV head; the padded ``qh2kv`` remap scatters query heads across KV
    heads, so a head stripe is not self-contained — the same boundary as
    the Pallas kernel's ``_kernel_ok``)."""
    from repro.models.layers import GROUPED_ATTN
    if not paged_supported_cfg(cfg):
        return "paged decode covers uniform attention stacks only"
    if cfg.mla is not None:
        return ("latent attention keeps one c_kv/k_pe slab pair with no "
                "head axis to stripe; its paged step runs on one device")
    if cfg.moe is not None and cfg.moe.every == 1:
        return ("MoE layers route through their own shard_map dispatch; "
                "the sharded paged step covers dense-MLP stacks")
    if model_shards > 1:
        Hp, KV = cfg.padded_heads, cfg.n_kv_heads
        if not (GROUPED_ATTN and Hp == cfg.n_heads and Hp % KV == 0):
            return (f"model-parallel KV heads need grouped GQA "
                    f"(padded_heads == n_heads, divisible groups); "
                    f"{cfg.name} pads {cfg.n_heads}→{Hp} query heads "
                    f"over {KV} KV heads")
        if KV % model_shards != 0:
            return (f"n_kv_heads={KV} not divisible by model axis "
                    f"{model_shards}")
    del data_shards   # any data axis works: slots shard row-wise
    return ""


def paged_supported_cfg(cfg: ModelConfig) -> bool:
    return cfg.attention_layers == cfg.n_layers and not cfg.encoder_layers


def decode_step_paged_sharded(params, tokens, k_pages, v_pages, block_table,
                              seq_lens, cfg: ModelConfig, mesh, *,
                              use_pallas: bool = False,
                              window_override=None):
    """``decode_step_paged`` under ``jax.shard_map`` on a (data, model)
    mesh: decode slots data-parallel (tokens / block table / seq_lens
    shard by row; every per-slot op is row-independent, so each data
    shard's math is bitwise the full-batch math), KV heads model-parallel
    (each model shard holds (L, P, page, KV/m·Dh) page-slab stripes; the
    inner attention loop is all_gather/psum-free because attention is
    head-local, and the only model-axis collective is the exact
    head-concatenating combine ahead of the output projection inside
    ``paged_attention_block``).

    The block table arrives with BANK-LOCAL page ids (each data shard's
    rows index its own page-slab bank directly — ``DevicePagePool``
    converts global→local host-side), so the per-shard body is literally
    the single-device step. Weights are replicated; logits come back
    row-sharded and reassemble to the global (B, 1, V).
    """
    from jax.sharding import PartitionSpec as P
    from repro.models.layers import PagedShard
    m = int(mesh.shape.get("model", 1))
    d = int(mesh.shape.get("data", 1))
    reason = paged_shard_reason(cfg, m, d)
    if reason:
        raise ValueError(f"cannot shard paged decode over {d}x{m}: {reason}")
    shard = PagedShard("model", m)
    pages_spec = P(None, "data", None, "model")

    def local_step(p, t, kp, vp, tbl, lens):
        return decode_step_paged(p, t, kp, vp, tbl, lens, cfg,
                                 use_pallas=use_pallas,
                                 window_override=window_override,
                                 shard=shard)

    f = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), P("data", None), pages_spec, pages_spec,
                  P("data", None), P("data")),
        out_specs=(P("data", None, None), pages_spec, pages_spec),
        check_vma=False)
    return f(params, tokens, k_pages, v_pages, block_table, seq_lens)
