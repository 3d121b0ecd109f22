"""The dense decoder layer, Qwen2 and Phi-3 alike.

RMSNorm, q/k/v projections (with bias where the configuration has it),
rotary embedding by halves at base ``rope_theta``, causal attention with
grouped KV heads (as many KV heads as query heads is MHA), output
projection, residual; RMSNorm, SwiGLU MLP, residual. The program serves it
as ``ModelConfig(kind="dense")``.

This file is the architecture's whole part of the benchmark
(``bench/model.py`` lists the contract): its shape and configuration
keys, its weights in the program's layout, its reference layer, its
counts of operations and bytes, and the zero K and V of its host pool.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from bench import weights
from bench.flops import BYTES
from bench.reference import HI, mm, q8, rms, rope
from bench.weights import DTYPE, QK_SCALE, normal

Q_BLOCK = 512         # query rows per attention block of the reference


@dataclass(frozen=True)
class Shape:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    qkv_bias: bool
    rope_theta: float
    norm_eps: float
    tie_embeddings: bool

    @property
    def kv_bytes_per_token(self) -> int:
        """bf16 K and V of one token over every layer."""
        return 2 * 2 * self.n_layers * self.n_kv_heads * self.head_dim


def load(name: str, raw: dict) -> Shape:
    """The shape of a configuration file's published keys; a size the file
    lacks comes from its ``assumed``."""
    assumed = raw.get("assumed", {})
    heads = raw["num_attention_heads"]
    shape = Shape(
        name=name,
        n_layers=raw["num_hidden_layers"],
        d_model=raw["hidden_size"],
        n_heads=heads,
        n_kv_heads=raw["num_key_value_heads"],
        head_dim=raw.get("head_dim", assumed.get(
            "head_dim", raw["hidden_size"] // heads)),
        d_ff=raw["intermediate_size"],
        vocab_size=raw["vocab_size"],
        qkv_bias=bool(raw.get("attention_bias",
                              assumed.get("attention_bias", False))),
        rope_theta=float(raw["rope_theta"]),
        norm_eps=float(raw["rms_norm_eps"]),
        tie_embeddings=bool(raw["tie_word_embeddings"]),
    )
    if raw.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{name}: only SwiGLU (silu) MLPs are served")
    return shape


def program_config(s: Shape):
    """The program's ``ModelConfig`` for these shapes."""
    from repro.configs.base import ModelConfig
    cfg = ModelConfig(
        name=s.name, kind="dense", n_layers=s.n_layers, d_model=s.d_model,
        n_heads=s.n_heads, n_kv_heads=s.n_kv_heads, head_dim=s.head_dim,
        d_ff=s.d_ff, vocab_size=s.vocab_size, attn_bias=s.qkv_bias,
        rope_theta=s.rope_theta, norm_eps=s.norm_eps,
        tie_embeddings=s.tie_embeddings, remat=False)
    if cfg.padded_heads != s.n_heads:
        raise ValueError(f"{s.name}: the program would pad {s.n_heads} query "
                         f"heads to {cfg.padded_heads}; the weights here "
                         f"are made for unpadded heads")
    return cfg


# ---- weights ----------------------------------------------------------------

def layer_tensors(s: Shape, root: jax.Array, layer) -> dict:
    """One layer's tensors, in the served type, by name. Biases and norm
    gains are drawn too (around 0 and 1), so the reference checks that
    they are applied."""
    D, H, KV, Dh, F = s.d_model, s.n_heads, s.n_kv_heads, s.head_dim, s.d_ff
    s_attn = (2.0 / (D + H * Dh)) ** 0.5
    s_mlp = (2.0 / (D + F)) ** 0.5

    def k(name):
        return jax.random.fold_in(weights.name_key(root, name), layer)

    t = dict(
        attn_ln=(1.0 + 0.1 * jax.random.normal(k("attn_ln"), (D,))).astype(DTYPE),
        wq=normal(k("wq"), (D, H * Dh), s_attn * QK_SCALE),
        wk=normal(k("wk"), (D, KV * Dh), s_attn * QK_SCALE),
        wv=normal(k("wv"), (D, KV * Dh), s_attn),
        wo=normal(k("wo"), (H * Dh, D), s_attn),
        mlp_ln=(1.0 + 0.1 * jax.random.normal(k("mlp_ln"), (D,))).astype(DTYPE),
        w1=normal(k("w1"), (D, F), s_mlp),
        w2=normal(k("w2"), (F, D), s_mlp),
        w3=normal(k("w3"), (D, F), s_mlp),
    )
    if s.qkv_bias:
        t.update(bq=normal(k("bq"), (H * Dh,), 0.1),
                 bk=normal(k("bk"), (KV * Dh,), 0.1),
                 bv=normal(k("bv"), (KV * Dh,), 0.1))
    return t


def layers_of(s: Shape, root: jax.Array, ids: jax.Array) -> dict:
    """The tensors of layers ``ids``, stacked. The program's tree and the
    reference's single layers both go through this one loop, so that the
    compiler emits the same arithmetic for both."""
    return jax.lax.map(lambda l: layer_tensors(s, root, l), ids)


def program_params(s: Shape, seed: int, padded_vocab: int) -> dict:
    """The program's stacked parameter tree (``models/transformer.py``
    layout: ``embed``, ``final_ln``, ``lm_head`` when untied, ``attn`` and
    ``mlp`` stacked on a leading layer axis), made on the device in one
    jitted call."""

    def make(root):
        layers = layers_of(s, root, jnp.arange(s.n_layers, dtype=jnp.uint32))
        attn = dict(ln=layers["attn_ln"], wq=layers["wq"], wk=layers["wk"],
                    wv=layers["wv"], wo=layers["wo"])
        if s.qkv_bias:
            attn.update(bq=layers["bq"], bk=layers["bk"], bv=layers["bv"])
        return dict(weights.head_params(s, root, padded_vocab), attn=attn,
                    mlp=dict(ln=layers["mlp_ln"], w1=layers["w1"],
                             w2=layers["w2"], w3=layers["w3"]))

    return jax.jit(make)(weights.root_key(seed))


# ---- the reference layer ----------------------------------------------------

@partial(jax.jit, static_argnames=("shape", "fp8"))
def _layer(x, t, *, shape: Shape, fp8: bool):
    """One decoder layer over a whole (T, D) sequence."""
    s = shape
    T = x.shape[0]
    H, KV, Dh = s.n_heads, s.n_kv_heads, s.head_dim
    pos = jnp.arange(T)
    a = rms(x, t["attn_ln"], s.norm_eps)
    q = mm(a, t["wq"], fp8)
    k = mm(a, t["wk"], fp8)
    v = mm(a, t["wv"], fp8)
    if s.qkv_bias:
        q, k, v = q + t["bq"], k + t["bk"], v + t["bv"]
    q = rope(q.reshape(T, H, Dh), pos, s.rope_theta)
    k = rope(k.reshape(T, KV, Dh), pos, s.rope_theta)
    v = v.reshape(T, KV, Dh)
    g = H // KV
    k = jnp.repeat(k, g, axis=1)            # query head h reads KV head h // g
    v = jnp.repeat(v, g, axis=1)
    if fp8:
        k, v = q8(k, -1), q8(v, 0)
    outs = []
    for q0 in range(0, T, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        n = q0 + qb.shape[0]                # the keys this block may see
        if fp8:
            qb = q8(qb, -1)
        sc = jnp.einsum("qhd,khd->hqk", qb, k[:n], precision=HI) / Dh ** 0.5
        mask = (q0 + jnp.arange(qb.shape[0]))[:, None] >= pos[None, :n]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        if fp8:
            p = q8(p, -1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v[:n], precision=HI))
    o = jnp.concatenate(outs).reshape(T, H * Dh)
    x = x + mm(o, t["wo"], fp8)
    m = rms(x, t["mlp_ln"], s.norm_eps)
    up = jax.nn.silu(mm(m, t["w1"], fp8)) * mm(m, t["w3"], fp8)
    return x + mm(up, t["w2"], fp8)


@partial(jax.jit, static_argnames=("shape",))
def _layer_weights(root, layer, *, shape: Shape):
    one = layers_of(shape, root, jnp.reshape(layer, (1,)))
    return jax.tree.map(lambda a: a[0].astype(jnp.float32), one)


def layer_weights(shape: Shape, root: jax.Array, index: int) -> dict:
    """Layer ``index``'s tensors in float32, made again from the seed."""
    return _layer_weights(root, jnp.uint32(index), shape=shape)


def layer(x, t, index: int, *, shape: Shape, fp8: bool):
    """Layer ``index`` (every layer is alike) over a (T, D) sequence."""
    return _layer(x, t, shape=shape, fp8=fp8)


# ---- least operations and bytes ---------------------------------------------

def layer_weight_elems(s: Shape) -> int:
    """Matrix elements of one layer (q, k, v, o projections and SwiGLU)."""
    D, H, KV, Dh, F = s.d_model, s.n_heads, s.n_kv_heads, s.head_dim, s.d_ff
    return D * (H + 2 * KV) * Dh + H * Dh * D + 3 * D * F


def weight_bytes(s: Shape) -> int:
    """Bytes of every weight a forward pass reads once: the layers and the
    output head (the input embedding is gathered row by row, a few rows a
    step, and counted with the activations, not here)."""
    return BYTES * (s.n_layers * layer_weight_elems(s)
                    + s.d_model * s.vocab_size)


def _attn_flops(s: Shape, visible: int) -> int:
    """QK^T and PV of one query over ``visible`` positions, every layer."""
    return 2 * 2 * s.n_layers * s.n_heads * s.head_dim * visible


def _kv_bytes(s: Shape, tokens: int) -> int:
    """bf16 K and V of ``tokens`` positions over every layer."""
    return tokens * s.kv_bytes_per_token


def prefill_chunk(s: Shape, lo: int, hi: int, last: bool) -> tuple[int, int]:
    """(flops, bytes) of one prefill chunk that extends a cache holding
    ``lo`` positions by positions ``lo .. hi-1`` (causal). ``last``: the
    chunk whose final position gives the first token, the one logit row a
    prefill needs."""
    T = hi - lo
    flops = 2 * T * s.n_layers * layer_weight_elems(s)
    # causal: the query at position p sees p + 1 positions
    flops += _attn_flops(s, T * lo + T * (T + 1) // 2)
    nbytes = BYTES * s.n_layers * layer_weight_elems(s)
    nbytes += _kv_bytes(s, lo) + _kv_bytes(s, T)     # read past, write new
    nbytes += 2 * BYTES * T * s.d_model              # embedded rows in, out
    if last:
        flops += 2 * s.d_model * s.vocab_size
        nbytes += BYTES * s.d_model * s.vocab_size
    return flops, nbytes


def decode_step(s: Shape, seq_lens: list[int]) -> tuple[int, int]:
    """(flops, bytes) of one decode step over the active slots, whose
    caches hold ``seq_lens`` positions before the step: one new token
    each, which attends its past and itself, and one logit row each."""
    B = len(seq_lens)
    per_token = 2 * (s.n_layers * layer_weight_elems(s)
                     + s.d_model * s.vocab_size)
    flops = B * per_token + sum(_attn_flops(s, n + 1) for n in seq_lens)
    nbytes = weight_bytes(s)
    nbytes += sum(_kv_bytes(s, n) for n in seq_lens) + _kv_bytes(s, B)
    nbytes += 2 * BYTES * B * s.d_model
    return flops, nbytes


# ---- the host pool ----------------------------------------------------------

def host_kv(cfg, tokens: int):
    """Zero K and V of ``tokens`` positions, (layers, tokens, KV heads,
    head size) each, as ``HostKVPool.put`` takes them."""
    zero = np.zeros((cfg.n_layers, tokens, cfg.n_kv_heads, cfg.head_dim),
                    ml_dtypes.bfloat16)
    return zero, zero
