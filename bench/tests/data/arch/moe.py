"""A Mixtral-style expert layer, as an architecture that a configuration
brings in files of its own (here only for the harness's tests).

Every layer: the qwen2.5 attention (GQA, QKV bias), then RMSNorm and a
routed SwiGLU FFN: router logits over ``n_experts``, softmax, the
``top_k`` most probable experts with their probabilities renormalised to
sum to 1, and the gated sum of those experts' SwiGLU outputs. The program serves it as ``ModelConfig(kind="moe")`` with an
``MoEConfig`` whose ``capacity_factor`` is ``n_experts / top_k``: each
expert's capacity is then every token of the batch, so the program drops
no token and computes what the reference computes.

The file stands alone, as a new architecture's would: it takes from
``bench/arch/dense.py`` only the contract's shape reading, program
configuration and host-pool K and V, and carries its own weights,
attention and counts.
"""
from __future__ import annotations

from dataclasses import asdict, dataclass, replace
from functools import partial

import jax
import jax.numpy as jnp

from bench import weights
from bench.flops import BYTES
from bench.model import BENCH, load_module
from bench.reference import HI, mm, q8, rms, rope
from bench.weights import DTYPE, QK_SCALE, normal

dense = load_module(BENCH / "arch" / "dense.py")
host_kv = dense.host_kv
Q_BLOCK = 512         # query rows per attention block of the reference

# Experts are drawn as one common matrix plus a tenth of one of their own.
# The program's bfloat16 hidden states pick another second expert than the
# float32 reference wherever the router's second and third logits nearly
# tie: about once in 200 token-layers here (CPU, two-layer model). With
# independent experts each such flip moved that token's logits by 0.4-1.9,
# past any limit that the fp8 control fails (0.7-1.0); drawn this close,
# a flip moves them a tenth as far, and routing, gating and dispatch are
# still compared.
EXPERT_SPREAD = 0.1


@dataclass(frozen=True)
class Shape(dense.Shape):
    """``d_ff`` is one expert's width."""
    n_experts: int
    top_k: int


def load(name: str, raw: dict) -> Shape:
    s = Shape(**asdict(dense.load(name, raw)),
              n_experts=raw["num_local_experts"],
              top_k=raw["num_experts_per_tok"])
    if not s.qkv_bias:
        raise ValueError(f"{name}: this architecture has QKV biases")
    return s


def program_config(s: Shape):
    from repro.configs.base import MoEConfig
    return replace(dense.program_config(s), kind="moe", moe=MoEConfig(
        n_experts=s.n_experts, top_k=s.top_k, d_ff_expert=s.d_ff,
        capacity_factor=s.n_experts / s.top_k))


# ---- weights ----------------------------------------------------------------

def layer_tensors(s: Shape, root: jax.Array, layer) -> dict:
    D, H, KV, Dh = s.d_model, s.n_heads, s.n_kv_heads, s.head_dim
    E, F = s.n_experts, s.d_ff
    s_attn = (2.0 / (D + H * Dh)) ** 0.5
    s_mlp = (2.0 / (D + F)) ** 0.5

    def k(name):
        return jax.random.fold_in(weights.name_key(root, name), layer)

    def gain(name):
        return (1.0 + 0.1 * jax.random.normal(k(name), (D,))).astype(DTYPE)

    def experts(name, shape):
        base = jax.random.normal(k(name), shape)
        own = jax.random.normal(k(name + "_own"), (E, *shape))
        return (s_mlp * (base + EXPERT_SPREAD * own)).astype(DTYPE)

    return dict(
        attn_ln=gain("attn_ln"),
        wq=normal(k("wq"), (D, H * Dh), s_attn * QK_SCALE),
        wk=normal(k("wk"), (D, KV * Dh), s_attn * QK_SCALE),
        wv=normal(k("wv"), (D, KV * Dh), s_attn),
        wo=normal(k("wo"), (H * Dh, D), s_attn),
        bq=normal(k("bq"), (H * Dh,), 0.1),
        bk=normal(k("bk"), (KV * Dh,), 0.1),
        bv=normal(k("bv"), (KV * Dh,), 0.1),
        moe_ln=gain("moe_ln"),
        router=normal(k("router"), (D, E), D ** -0.5),
        w1=experts("w1", (D, F)),
        w2=experts("w2", (F, D)),
        w3=experts("w3", (D, F)),
    )


def _layers_of(s: Shape, root: jax.Array, ids: jax.Array) -> dict:
    return jax.lax.map(lambda l: layer_tensors(s, root, l), ids)


def program_params(s: Shape, seed: int, padded_vocab: int) -> dict:
    """``embed``, ``final_ln``, ``lm_head`` when untied, and ``attn`` and
    ``moe`` (``ln``, ``router``, ``w1``/``w2``/``w3``) stacked on a leading
    layer axis."""

    def make(root):
        t = _layers_of(s, root, jnp.arange(s.n_layers, dtype=jnp.uint32))
        attn = {n: t[n] for n in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")}
        return dict(weights.head_params(s, root, padded_vocab),
                    attn=dict(attn, ln=t["attn_ln"]),
                    moe=dict(ln=t["moe_ln"], router=t["router"], w1=t["w1"],
                             w2=t["w2"], w3=t["w3"]))

    return jax.jit(make)(weights.root_key(seed))


# ---- the reference layer ----------------------------------------------------

@partial(jax.jit, static_argnames=("shape", "fp8"))
def _layer(x, t, *, shape: Shape, fp8: bool):
    s = shape
    T = x.shape[0]
    H, KV, Dh = s.n_heads, s.n_kv_heads, s.head_dim
    pos = jnp.arange(T)
    a = rms(x, t["attn_ln"], s.norm_eps)
    q = rope((mm(a, t["wq"], fp8) + t["bq"]).reshape(T, H, Dh), pos,
             s.rope_theta)
    k = rope((mm(a, t["wk"], fp8) + t["bk"]).reshape(T, KV, Dh), pos,
             s.rope_theta)
    v = (mm(a, t["wv"], fp8) + t["bv"]).reshape(T, KV, Dh)
    k = jnp.repeat(k, H // KV, axis=1)      # query head h reads h // (H // KV)
    v = jnp.repeat(v, H // KV, axis=1)
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
    x = x + mm(jnp.concatenate(outs).reshape(T, H * Dh), t["wo"], fp8)
    m = rms(x, t["moe_ln"], s.norm_eps)
    probs = jax.nn.softmax(mm(m, t["router"], fp8), axis=-1)       # (T, E)
    top, idx = jax.lax.top_k(probs, s.top_k)
    rows = jnp.arange(x.shape[0])[:, None]
    gate = jnp.zeros_like(probs).at[rows, idx].set(
        top / top.sum(-1, keepdims=True))
    for e in range(s.n_experts):
        up = jax.nn.silu(mm(m, t["w1"][e], fp8)) * mm(m, t["w3"][e], fp8)
        x = x + gate[:, e:e + 1] * mm(up, t["w2"][e], fp8)
    return x


@partial(jax.jit, static_argnames=("shape",))
def _layer_weights(root, layer, *, shape: Shape):
    one = _layers_of(shape, root, jnp.reshape(layer, (1,)))
    return jax.tree.map(lambda a: a[0].astype(jnp.float32), one)


def layer_weights(shape: Shape, root: jax.Array, index: int) -> dict:
    return _layer_weights(root, jnp.uint32(index), shape=shape)


def layer(x, t, index: int, *, shape: Shape, fp8: bool):
    return _layer(x, t, shape=shape, fp8=fp8)


# ---- least operations and bytes ---------------------------------------------

def _attn_elems(s: Shape) -> int:
    """Matrix elements of one layer's q, k, v and o projections."""
    D, H, KV, Dh = s.d_model, s.n_heads, s.n_kv_heads, s.head_dim
    return D * (H + 2 * KV) * Dh + H * Dh * D


def _token_elems(s: Shape) -> int:
    """Matrix elements one token needs in one layer: attention, the router
    and its ``top_k`` experts. Every token reads ``top_k`` experts; when
    all tokens pick the same ones, no more, so that is also the least a
    layer's weights cost in bytes."""
    return (_attn_elems(s) + s.d_model * s.n_experts
            + s.top_k * 3 * s.d_model * s.d_ff)


def _attn_flops(s: Shape, visible: int) -> int:
    return 2 * 2 * s.n_layers * s.n_heads * s.head_dim * visible


def _kv_bytes(s: Shape, tokens: int) -> int:
    return tokens * s.kv_bytes_per_token


def weight_bytes(s: Shape) -> int:
    """Bytes of every weight: the layers with all their experts, and the
    output head."""
    per_layer = (_attn_elems(s) + s.d_model * s.n_experts
                 + s.n_experts * 3 * s.d_model * s.d_ff)
    return BYTES * (s.n_layers * per_layer + s.d_model * s.vocab_size)


def prefill_chunk(s: Shape, lo: int, hi: int, last: bool) -> tuple[int, int]:
    T = hi - lo
    flops = 2 * T * s.n_layers * _token_elems(s)
    flops += _attn_flops(s, T * lo + T * (T + 1) // 2)
    nbytes = BYTES * s.n_layers * _token_elems(s)
    nbytes += _kv_bytes(s, lo) + _kv_bytes(s, T)
    nbytes += 2 * BYTES * T * s.d_model
    if last:
        flops += 2 * s.d_model * s.vocab_size
        nbytes += BYTES * s.d_model * s.vocab_size
    return flops, nbytes


def decode_step(s: Shape, seq_lens: list[int]) -> tuple[int, int]:
    B = len(seq_lens)
    head = s.d_model * s.vocab_size
    flops = 2 * B * (s.n_layers * _token_elems(s) + head)
    flops += sum(_attn_flops(s, n + 1) for n in seq_lens)
    nbytes = BYTES * (s.n_layers * _token_elems(s) + head)
    nbytes += sum(_kv_bytes(s, n) for n in seq_lens)
    nbytes += _kv_bytes(s, B) + 2 * BYTES * B * s.d_model
    return flops, nbytes
