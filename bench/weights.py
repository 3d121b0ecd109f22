"""Seeded random weights in the serving program's parameter layout.

The benchmark is the checkpoint: it makes the weights from ``--seed`` and
hands them to the program, and the plain reference makes the same weights
again, layer by layer, from the same seed. Neither side reads what the
other made. This module holds what every architecture shares: the key
scheme, the embedding, the final norm and the output head; each
architecture (``bench/arch/<arch>.py``) makes its layers from these keys.

Every tensor of layer ``l`` is drawn from ``fold_in(name_key(name), l)``,
so one layer can be made alone (the reference) or all layers at once by
``lax.map`` (the program's stacked tree, one jitted call on the device,
in bfloat16, the type it is served in). Keys are of the ``rbg`` kind:
their bits come from the device's bit generator, which makes the 3-7 G
normals of a configuration in seconds where threefry takes half a
minute; the stream is a function of the key, the shape and the backend,
and the program's weights and the reference's are made on one backend.
Rows of the vocabulary past the published size (the program pads it to a
multiple of 256) are zero, as a checkpoint loader pads them: their logits
are exactly 0, never the best.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

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


def name_key(root: jax.Array, name: str) -> jax.Array:
    return jax.random.fold_in(root, sum(ord(c) * 131 ** i
                                        for i, c in enumerate(name)) % 2**31)


def normal(key, shape, scale):
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(DTYPE)


def embedding(s, root: jax.Array, rows: int) -> jax.Array:
    """(rows, D) input embedding; rows past the published vocabulary are 0."""
    e = normal(name_key(root, "embed"), (rows, s.d_model), s.d_model ** -0.5)
    return jnp.where(jnp.arange(rows)[:, None] < s.vocab_size, e, 0)


def lm_head(s, root: jax.Array, rows: int) -> jax.Array:
    """(D, rows) output head of an untied model; padded columns are 0."""
    w = normal(name_key(root, "lm_head"), (s.d_model, rows), s.d_model ** -0.5)
    return jnp.where(jnp.arange(rows)[None, :] < s.vocab_size, w, 0)


def final_norm(s, root: jax.Array) -> jax.Array:
    return (1.0 + 0.1 * jax.random.normal(name_key(root, "final_ln"),
                                          (s.d_model,))).astype(DTYPE)


def head_params(s, root: jax.Array, rows: int) -> dict:
    """The program's ``embed``, ``final_ln`` and, when untied, ``lm_head``
    leaves, with ``rows`` rows of the vocabulary."""
    p = dict(embed=embedding(s, root, rows), final_ln=final_norm(s, root))
    if not s.tie_embeddings:
        p["lm_head"] = lm_head(s, root, rows)
    return p
