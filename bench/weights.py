"""Seeded random weights in the serving program's parameter layout.

The benchmark is the checkpoint: it makes the weights from ``--seed`` and
hands them to the program, and the plain reference makes the same weights
again, layer by layer, from the same seed. Neither side reads what the
other made.

Every tensor of layer ``l`` is drawn from ``fold_in(key(name), l)``, so
one layer can be made alone (the reference) or all layers at once by
``lax.map`` (the program's stacked tree, one jitted call on the device,
in bfloat16, the type it is served in). Keys are of the ``rbg`` kind:
their bits come from the device's bit generator, which makes the 3-7 G
normals of a configuration in seconds where threefry takes half a
minute; the stream is a function of the key, the shape and the backend,
and the program's weights and the reference's are made on one backend. Rows of the vocabulary past the
published size (the program pads it to a multiple of 256) are zero, as a
checkpoint loader pads them: their logits are exactly 0, never the best.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.model import Shape

DTYPE = jnp.bfloat16
# q and k are drawn 1.5x wider than the rest, so that q.k / sqrt(Dh) has a
# spread of about 2.25 and attention is peaked, as a trained model's is.
# At 1x it is near uniform over a long prompt: every served position then
# has nearly the same hidden state, one wide top-1 margin holds for a
# whole reply, and an fp8 pass put no token anywhere else (on a TPU v5e,
# 803 served tokens after prompts of 0.5-7.7k, qwen2.5-3b shapes), so the
# check could not tell precisions apart.
QK_SCALE = 1.5

def root_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (seeds may pass 2**32)."""
    key = jax.random.key(0, impl="rbg")
    seed = int(seed)
    while True:
        key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return key


def _key(root: jax.Array, name: str) -> jax.Array:
    return jax.random.fold_in(root, sum(ord(c) * 131 ** i
                                        for i, c in enumerate(name)) % 2**31)


def _normal(key, shape, scale):
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(DTYPE)


def layer_tensors(s: Shape, root: jax.Array, layer) -> dict:
    """One layer's tensors, in the served type, by name. Biases and norm
    gains are drawn too (around 0 and 1), so the reference checks that
    they are applied."""
    D, H, KV, Dh, F = s.d_model, s.n_heads, s.n_kv_heads, s.head_dim, s.d_ff
    s_attn = (2.0 / (D + H * Dh)) ** 0.5
    s_mlp = (2.0 / (D + F)) ** 0.5

    def k(name):
        return jax.random.fold_in(_key(root, name), layer)

    t = dict(
        attn_ln=(1.0 + 0.1 * jax.random.normal(k("attn_ln"), (D,))).astype(DTYPE),
        wq=_normal(k("wq"), (D, H * Dh), s_attn * QK_SCALE),
        wk=_normal(k("wk"), (D, KV * Dh), s_attn * QK_SCALE),
        wv=_normal(k("wv"), (D, KV * Dh), s_attn),
        wo=_normal(k("wo"), (H * Dh, D), s_attn),
        mlp_ln=(1.0 + 0.1 * jax.random.normal(k("mlp_ln"), (D,))).astype(DTYPE),
        w1=_normal(k("w1"), (D, F), s_mlp),
        w2=_normal(k("w2"), (F, D), s_mlp),
        w3=_normal(k("w3"), (D, F), s_mlp),
    )
    if s.qkv_bias:
        t.update(bq=_normal(k("bq"), (H * Dh,), 0.1),
                 bk=_normal(k("bk"), (KV * Dh,), 0.1),
                 bv=_normal(k("bv"), (KV * Dh,), 0.1))
    return t


def layers_of(s: Shape, root: jax.Array, ids: jax.Array) -> dict:
    """The tensors of layers ``ids``, stacked. The program's tree and the
    reference's single layers both go through this one loop, so that the
    compiler emits the same arithmetic for both."""
    return jax.lax.map(lambda l: layer_tensors(s, root, l), ids)


def embedding(s: Shape, root: jax.Array, rows: int) -> jax.Array:
    """(rows, D) input embedding; rows past the published vocabulary are 0."""
    e = _normal(_key(root, "embed"), (rows, s.d_model), s.d_model ** -0.5)
    return jnp.where(jnp.arange(rows)[:, None] < s.vocab_size, e, 0)


def lm_head(s: Shape, root: jax.Array, rows: int) -> jax.Array:
    """(D, rows) output head of an untied model; padded columns are 0."""
    w = _normal(_key(root, "lm_head"), (s.d_model, rows), s.d_model ** -0.5)
    return jnp.where(jnp.arange(rows)[None, :] < s.vocab_size, w, 0)


def final_norm(s: Shape, root: jax.Array) -> jax.Array:
    return (1.0 + 0.1 * jax.random.normal(_key(root, "final_ln"),
                                          (s.d_model,))).astype(DTYPE)


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
        p = dict(embed=embedding(s, root, padded_vocab),
                 final_ln=final_norm(s, root), attn=attn,
                 mlp=dict(ln=layers["mlp_ln"], w1=layers["w1"],
                          w2=layers["w2"], w3=layers["w3"]))
        if not s.tie_embeddings:
            p["lm_head"] = lm_head(s, root, padded_vocab)
        return p

    return jax.jit(make)(root_key(seed))
